"""Operation and byte counts of the kernels and the whole step, and the
peaks table."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import counts  # noqa: E402

M = {"d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
     "n_layers": 3, "d_ff": 96, "vocab_size": 500}


def test_paged_decode_counts_every_live_position():
    attended = [5, 9, 1]
    f, b = counts.paged_decode(M, attended, dtype_bytes=2)
    # QK^T and PV per position, 4 heads, 3 layers
    flops = sum(4 * n * 16 for n in attended) * 4 * 3
    kv_bytes = sum(2 * n * 16 for n in attended) * 2 * 3 * 2   # K+V, kv heads
    qo_bytes = len(attended) * 2 * 4 * 16 * 2 * 3
    assert f == flops
    assert b == kv_bytes + qo_bytes


def test_flash_prefill_is_causal_over_prefix_and_suffix():
    f, _ = counts.flash_prefill(M, [(0, 4), (8, 2)], dtype_bytes=2)
    pairs = (1 + 2 + 3 + 4) + (9 + 10)
    assert f == 4 * 4 * 16 * pairs * 3


def test_model_flops_match_the_programs_parameter_count():
    from repro.configs import get_config
    for arch in ("qwen2-1.5b", "chatglm2-6b"):
        cfg = get_config(arch)
        m = {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
             "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
             "n_layers": cfg.n_layers, "d_ff": cfg.d_ff,
             "vocab_size": cfg.vocab_size}
        # the program counts the padded vocabulary, norms and the embedding
        embed = cfg.padded_vocab * cfg.d_model
        program = cfg.param_count() - embed * (1 if cfg.tie_embeddings
                                               else 2) \
            - cfg.d_model * (2 * cfg.n_layers + 1)
        assert counts.layer_params(m) * cfg.n_layers == program
        one = counts.model_flops(m, [1], head_tokens=1)
        assert one == pytest.approx(
            2 * (program + cfg.d_model * cfg.vocab_size)
            + 4 * cfg.n_heads * cfg.head_dim * cfg.n_layers)


def test_configs_give_the_published_sizes():
    """qwen2-1.5b holds 1.54 B parameters, chatglm2-6b 6.24 B."""
    for name, want in (("qwen2-1.5b", 1.544e9), ("chatglm2-6b", 6.244e9)):
        m = json.loads((HERE / "configs" / f"{name}.json").read_text())[
            "model"]
        embed = m["vocab_size"] * m["d_model"]
        total = counts.layer_params(m) * m["n_layers"] \
            + embed * (1 if m["tie_embeddings"] else 2)
        assert total == pytest.approx(want, rel=2e-3)


def test_roofline_and_peaks():
    p = counts.peaks("TPU v5 lite")
    assert p["flops"] == 197e12 and p["bytes_per_s"] == 819e9
    assert counts.roofline_s(197e12, 1.0, "TPU v5 lite") == 1.0
    assert counts.roofline_s(1.0, 819e9 * 2, "TPU v5 lite") == 2.0
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
