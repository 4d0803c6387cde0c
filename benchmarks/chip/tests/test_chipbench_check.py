"""The comparison that decides ``correct``, at a size a CPU holds: sound
runs pass it, the float8 control fails it, and a run with the timed path
broken underneath comes out not correct.  The harness's look for a chip
is skipped; the rest of a run is driven as on the chip.

Each run serves one wave (``--seconds 0``) and checks all of it, so what
is compared does not hang on the CPU's speed.  The tiny configuration's
limit (0.007) was set from CPU readings with the seeded q/k/v biases: sound
runs read at most 0.0035 over seeds 1-8 of both mixes; the control read at
least 0.0086 on 15 of those 16, and 0 on one (tiny.prefix, seed 7: it puts
the served token first at every position)."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import run  # noqa: E402

DATA = HERE / "tests" / "data"
CELLS = ("tiny.chat", "tiny.prefix")


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """A checkout of the benchmark whose cells serve the tiny config."""
    here = tmp_path_factory.mktemp("bench")
    for d in ("configs", "traffic"):
        (here / d).mkdir()
    shutil.copytree(HERE / "metrics", here / "metrics")
    shutil.copy(DATA / "tiny.json", here / "configs" / "tiny.json")
    shutil.copy(DATA / "tiny-chat.json", here / "traffic" / "chat.json")
    shutil.copy(DATA / "tiny-prefix.json", here / "traffic" / "prefix.json")
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["workloads"] = [{"name": c, "config": "tiny",
                       "traffic": c.split(".")[1], "chips": 1, "why": "test"}
                      for c in CELLS]
    for m in b["per_layer"]:
        m["workloads"] = list(CELLS)
    b["per_layer"].append({
        "name": "prefix_hit_token_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "prefix cache",
        "moves": "itl_p95_ms", "workloads": ["tiny.prefix"]})
    (here / "BENCHMARK.json").write_text(json.dumps(b))
    return here


def drive(bench, cell, seed, trace=0, hook=None, control=0):
    args = run.parse(["--workload", cell, "--seed", str(seed),
                      "--seconds", "0", "--trace", str(trace),
                      "--control", str(control)])
    return run.run(args, require_tpu=False, root=bench, here=bench,
                   engine_hook=hook)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(bench, cell):
    res = drive(bench, cell, 2**31 + 77)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert list(res["compared"])[-1] == "logit_gap"
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}


def test_traced_run_reports_per_layer_metrics(bench):
    res = drive(bench, "tiny.prefix", 5, trace=1)
    assert res["correct"]
    m = res["metrics"]
    assert m["window_compiles"]["value"] == 0
    assert 0 < m["batch_occupancy"]["value"] <= 100
    assert 0 < m["prefix_hit_token_share"]["value"] < 100
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", CELLS)
def test_float8_control_fails_the_limit(bench, cell):
    for seed in (1, 2, 3):
        res = drive(bench, cell, seed, control=1)
        c = res["compared"]
        assert res["correct"], c
        assert not res["control_correct"]
        assert c["control_logit_gap"]["value"] > c["logit_gap"]["limit"]
        assert list(c)[-1] == "logit_gap"


def alter_tokens(engine):
    """Every token the greedy pick produces is replaced by the next id."""
    import repro.serving.paged_engine as pe
    orig = pe.greedy

    def altered(logits, vocab):
        return (orig(logits, vocab) + 1) % vocab
    engine._restore = (pe, orig)
    pe.greedy = altered


def keep_state(engine):
    """The decode step returns the pools it was given: its K/V writes are
    lost."""
    import jax.numpy as jnp
    import jax
    step = engine._decode

    def unchanged(params, toks, pools, bt, kv):
        before = jax.tree.map(jnp.copy, pools)
        logits, _ = step(params, toks, pools, bt, kv)
        return logits, before
    engine._decode = unchanged


def qkv_biases(engine):
    return engine.params["blocks"]["l0"]["mixer"]


def drop_bias(engine):
    """The q/k/v biases are left out of the served weights."""
    for p in qkv_biases(engine).values():
        if "b" in p:
            p["b"] = p["b"] * 0


def bias_wrong_head(engine):
    """The q bias lands one head over."""
    import jax.numpy as jnp
    q = qkv_biases(engine)["q"]
    q["b"] = jnp.roll(q["b"], engine.cfg.head_dim, axis=-1)


@pytest.mark.parametrize(
    "fault", [alter_tokens, keep_state, drop_bias, bias_wrong_head],
    ids=["token_altered", "state_unchanged", "qkv_bias_dropped",
         "q_bias_wrong_head"])
def test_broken_timed_path_is_not_correct(bench, fault):
    held = {}

    def hook(engine):
        fault(engine)
        held["e"] = engine
    try:
        res = drive(bench, "tiny.chat", 11, hook=hook)
    finally:
        restore = getattr(held.get("e"), "_restore", None)
        if restore:
            restore[0].greedy = restore[1]
    assert not res["correct"]
    assert res["compared"]["logit_gap"]["value"] > \
        res["compared"]["logit_gap"]["limit"]


def test_no_chip_exits_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(HERE / "run.py"),
                        "--workload", "qwen2-1.5b.chat", "--seed", "1",
                        "--seconds", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_bare_checkout_exits_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "qwen2-1.5b.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
