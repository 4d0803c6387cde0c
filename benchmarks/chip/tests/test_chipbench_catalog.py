"""Discovery by name, and the benchmark's files agreeing with each other."""
import json
import pathlib
import shutil
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import catalog  # noqa: E402
import traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

# published config.json key -> the program's ModelConfig field
PUBLISHED = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
             "num_layers": "n_layers", "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "multi_query_group_num": "n_kv_heads", "kv_channels": "head_dim",
             "intermediate_size": "d_ff", "ffn_hidden_size": "d_ff",
             "vocab_size": "vocab_size", "padded_vocab_size": "vocab_size",
             "rms_norm_eps": "norm_eps", "layernorm_epsilon": "norm_eps",
             "rope_theta": "rope_theta",
             "tie_word_embeddings": "tie_embeddings"}


def test_a_new_file_of_each_kind_is_found(tmp_path):
    here = tmp_path / "bench"
    for kind in ("configs", "traffic", "metrics"):
        shutil.copytree(HERE / kind, here / kind)
    before = (catalog.configs(here), catalog.mixes(here),
              catalog.metrics(here))
    shutil.copy(HERE / "tests" / "data" / "tiny.json",
                here / "configs" / "tiny.json")
    shutil.copy(HERE / "tests" / "data" / "tiny-chat.json",
                here / "traffic" / "tiny-chat.json")
    (here / "metrics" / "always_one.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    assert catalog.configs(here) == sorted(before[0] + ["tiny"])
    assert catalog.mixes(here) == sorted(before[1] + ["tiny-chat"])
    assert catalog.metrics(here) == sorted(before[2] + ["always_one"])
    assert catalog.config("tiny", here)["model"]["d_model"] == 64
    assert traffic.Mix.load(catalog.mix_path("tiny-chat", here)).wave == 8
    assert catalog.metric("always_one", here).read(None) == 1.0
    for kind in ("configs", "traffic", "metrics"):
        for p in (HERE / kind).iterdir():
            if p.is_file():
                assert (here / kind / p.name).read_bytes() == p.read_bytes()


def test_every_named_part_exists():
    assert {c["name"] for c in BENCH["configs"]} <= set(catalog.configs())
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).resolve() == \
            HERE / "configs" / f"{c['name']}.json"
        assert catalog.config(c["name"])["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert w["config"] in catalog.configs()
        assert w["traffic"] in catalog.mixes()
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    assert {m["name"] for m in BENCH["per_layer"]} <= set(catalog.metrics())


@pytest.mark.parametrize("name", sorted(catalog.configs()))
def test_config_runs_the_published_sizes(name):
    conf = catalog.config(name)
    model, pub = conf["model"], conf["published"]
    changed = set(conf["reduced"])
    for key, field in PUBLISHED.items():
        if key in pub and key not in changed:
            assert model[field] == pub[key], key
    if "torch_dtype" not in changed:
        assert conf["dtype"] == pub["torch_dtype"] == model["dtype"]
    assert conf["reference"] in [p.stem for p in
                                 (HERE / "references").glob("*.py")]


def test_per_layer_workloads_name_cells_that_report_what_they_move():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
