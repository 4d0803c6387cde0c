"""Validate the analytic cost model against compiled-HLO cost_analysis on
reduced configs where everything can be counted exactly (no layer scan
undercount: we compare per-layer-scaled quantities within tolerance).

This is the calibration that justifies using the analytic model as the
primary FLOP source in EXPERIMENTS.md §Roofline (raw HLO undercounts
lax.scan bodies — demonstrated here too)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeConfig
from repro.models import api
from repro.perf.cost_model import ParallelismDesc, step_cost


def _hlo_flops(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return float(c.cost_analysis().get("flops", 0.0))


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen2-moe-a2.7b", "rwkv6-3b"])
def test_prefill_flops_match_hlo(arch):
    """Reduced config, single chip: analytic forward FLOPs within 40% of
    HLO-counted FLOPs (XLA counts some fusions differently; the roofline
    needs order-of-magnitude-exact, this asserts much tighter)."""
    cfg = get_config(arch).reduced()
    b, s = 2, 64
    shape = ShapeConfig("probe", s, b, "prefill")
    desc = ParallelismDesc(n_chips=1, tp=1, dp=1, causal_discount=1.0)
    ct = step_cost(cfg, shape, desc)

    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    toks = jnp.zeros((b, s), jnp.int32)

    def fwd(p, t):
        from repro.models import transformer as T
        out, _ = T.lm_forward(cfg, p, t)
        return out

    hlo = _hlo_flops(fwd, params, toks)
    assert hlo > 0
    ratio = ct.flops / hlo
    assert 0.6 < ratio < 1.7, f"{arch}: analytic/hlo = {ratio:.3f}"


def test_scan_undercount_demonstration():
    """Documents WHY the analytic model is primary: scanned layers are
    counted once by cost_analysis."""
    from jax import lax

    def unrolled(x, ws):
        for i in range(4):
            x = jnp.tanh(x @ ws[i])
        return x

    def scanned(x, ws):
        return lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)[0]

    x = jnp.zeros((64, 128))
    ws = jnp.zeros((4, 128, 128))
    f_unrolled = _hlo_flops(unrolled, x, ws)
    f_scanned = _hlo_flops(scanned, x, ws)
    assert f_scanned < f_unrolled / 2      # undercount is real


def test_memory_model_tracks_param_count():
    cfg = get_config("gemma2-27b")
    desc = ParallelismDesc(n_chips=256, tp=16, dp=16, fsdp=True)
    ct = step_cost(cfg, SHAPES["train_4k"], desc)
    expect = cfg.param_count() * 2 / 256
    assert abs(ct.weight_bytes_chip - expect) / expect < 1e-6


def test_decode_is_memory_bound_train_not():
    cfg = get_config("gemma2-27b")
    desc = ParallelismDesc(n_chips=256, tp=16, dp=16)
    dec = step_cost(cfg, SHAPES["decode_32k"], desc)
    assert dec.bottleneck() in ("memory", "collective")
    tr = step_cost(cfg, SHAPES["train_4k"],
                   ParallelismDesc(n_chips=256, tp=16, dp=16, fsdp=True))
    assert tr.times()["compute_s"] > dec.times()["compute_s"]
