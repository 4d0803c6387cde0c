"""ChatGLM2's cell at a size a CPU holds: a tiny decoder of ChatGLM2's shape
(half-head rotary in interleaved pairs, q/k/v bias, untied head) served
through the harness is correct against ``references/chatglm2.py``, and a
run whose served model departs from ChatGLM2's equations is not.

The tiny configuration's limit (0.007, as the Qwen2-shaped tiny one's)
sits between CPU readings with the seeded q/k/v biases, over seeds 1-8 and
2**32 + 15: sound runs read at most 0.00048, the float8 control at least
0.148 (the untied head's logits are wider than a tied one's), and the
faults below 0.29-1.55 on seeds 11 and 12."""
import dataclasses
import json
import pathlib
import shutil
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import reference  # noqa: E402
import run  # noqa: E402

DATA = HERE / "tests" / "data"
CELL = "tiny-glm.chat"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """A checkout of the benchmark whose one cell serves the tiny GLM."""
    here = tmp_path_factory.mktemp("bench")
    for d in ("configs", "traffic"):
        (here / d).mkdir()
    shutil.copytree(HERE / "metrics", here / "metrics")
    shutil.copy(DATA / "tiny-glm.json", here / "configs" / "tiny-glm.json")
    shutil.copy(DATA / "tiny-chat.json", here / "traffic" / "chat.json")
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["workloads"] = [{"name": CELL, "config": "tiny-glm", "traffic": "chat",
                       "chips": 1, "why": "test"}]
    for m in b["per_layer"]:
        m["workloads"] = [CELL]
    (here / "BENCHMARK.json").write_text(json.dumps(b))
    return here


def drive(bench, seed, hook=None, control=0):
    args = run.parse(["--workload", CELL, "--seed", str(seed),
                      "--seconds", "0", "--trace", "0",
                      "--control", str(control)])
    return run.run(args, require_tpu=False, root=bench, here=bench,
                   engine_hook=hook)


@pytest.mark.parametrize("seed", [3, 2**32 + 15])
def test_tiny_glm_cell_is_correct(bench, seed):
    res = drive(bench, seed, control=1)
    c = res["compared"]
    assert res["correct"], c
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert not res["control_correct"]
    assert c["control_logit_gap"]["value"] > c["logit_gap"]["limit"]


def rebuilt(**change):
    """The engine rebuilt on the same weights with its config changed, so
    its jitted steps trace the changed model."""
    def fault(engine):
        engine.__init__(dataclasses.replace(engine.cfg, **change),
                        engine.params, engine.pcfg, dtype=engine.dtype)
    return fault


def q_bias_wrong_head(engine):
    """The q bias lands one head over."""
    import jax.numpy as jnp
    q = engine.params["blocks"]["l0"]["mixer"]["q"]
    q["b"] = jnp.roll(q["b"], engine.cfg.head_dim, axis=-1)


@pytest.mark.parametrize(
    "fault", [rebuilt(rope_interleaved=False),
              rebuilt(rope_fraction=1.0, rope_interleaved=False),
              q_bias_wrong_head],
    ids=["halves_not_pairs", "whole_head_rotated", "q_bias_wrong_head"])
def test_departure_from_chatglm2_is_not_correct(bench, fault):
    res = drive(bench, 11, hook=fault)
    assert not res["correct"]
    assert res["compared"]["logit_gap"]["value"] > \
        res["compared"]["logit_gap"]["limit"]


@pytest.mark.parametrize("key,value", [("rope_interleaved", False),
                                       ("rope_fraction", 1.0),
                                       ("tie_embeddings", True)])
def test_reference_refuses_what_chatglm2_is_not(key, value):
    """The reference computes ChatGLM2's equations only: a configuration
    that states another rotary or a tied head is refused, not scored."""
    conf = json.loads((DATA / "tiny-glm.json").read_text())
    model = dict(conf["model"], **{key: value})
    with pytest.raises(ValueError, match=key):
        reference.load("chatglm2").score(model, "bfloat16", 1, [],
                                         s_pad=8, n_read=8)
