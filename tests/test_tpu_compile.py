"""Ahead-of-time compiles for a described TPU v5e chip: every Pallas kernel
at the published widths of qwen2-1.5b and smollm-135m, the paged kernels
at chatglm2-6b's, and whole jitted paged-decode and prefill steps of
qwen2-1.5b, and chatglm2-6b's paged-decode step.  Nothing runs — the TPU's
compiler refuses what the chip would refuse (block shapes it cannot tile,
too much fast memory, a program larger than the device), which interpret
mode never shows.  The decode steps must also update the layer-stacked
pool in place: no copy of it, or of one layer of it, and no second pool
held as a temporary.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU's library, and every test
worker imports this file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.backend import use_backend
from repro.kernels.decode_attention.decode_attention import \
    decode_attention_pallas
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.paged_attention.paged_attention import (
    paged_decode_attention_pallas, paged_window_attention_pallas)
from repro.kernels.wkv6.wkv6 import wkv6_pallas
from repro.models import api

V5E_HBM_BYTES = 16 * 2**30
B, S, BS, N_BLOCKS, NB = 4, 512, 8, 1025, 256
N_LAYERS = 3          # the paged kernels read the middle of a stacked pool


@pytest.fixture(scope="module")
def topo():
    # keep libtpu's compiler logs out of the shared temp dir
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def spec(topo, no_compile_cache):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _kernel_call(kernel, spec, h, kv, d, dtype):
    i32 = jnp.int32
    if kernel == "flash":
        return flash_attention_pallas, (spec((1, S, h, d), dtype),
                                        spec((1, S, kv, d), dtype),
                                        spec((1, S, kv, d), dtype))
    if kernel == "decode":
        return decode_attention_pallas, (spec((B, h, d), dtype),
                                         spec((B, 4 * S, kv, d), dtype),
                                         spec((B, 4 * S, kv, d), dtype),
                                         spec((B,), i32))
    pool = spec((N_LAYERS, kv, N_BLOCKS, BS, d), dtype)
    table = (spec((B, NB), i32), spec((B,), i32), spec((), i32))  # + layer
    if kernel == "paged_decode":
        return paged_decode_attention_pallas, (spec((B, h, d), dtype), pool,
                                               pool, *table)
    if kernel == "paged_window":
        return paged_window_attention_pallas, (spec((B, 5, h, d), dtype),
                                               pool, pool, *table)
    seq = spec((1, S // 2, h, d), dtype)
    return wkv6_pallas, (seq, seq, seq, seq, spec((h, d), dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "smollm-135m"])
@pytest.mark.parametrize("kernel", ["flash", "decode", "paged_decode",
                                    "paged_window", "wkv6"])
def test_kernel_compiles_for_v5e(spec, kernel, arch, dtype):
    cfg = get_config(arch)
    fn, args = _kernel_call(kernel, spec, cfg.n_heads, cfg.n_kv_heads,
                            cfg.head_dim_eff, dtype)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["paged_decode", "paged_window"])
def test_paged_kernel_compiles_for_v5e_at_chatglm2_widths(spec, kernel):
    """chatglm2-6b's 32 query heads over 2 kv heads (group 16, 16 rows a
    kv head) at head dim 128, bf16: the live-page sweep at its widest."""
    cfg = get_config("chatglm2-6b")
    fn, args = _kernel_call(kernel, spec, cfg.n_heads, cfg.n_kv_heads,
                            cfg.head_dim_eff, jnp.bfloat16)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _params_and_pools(spec, cfg, dtype=jnp.float32):
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: spec(x.shape, x.dtype), tree)
    params = jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0), dtype))
    pools = jax.eval_shape(
        lambda: api.init_paged_pools(cfg, N_BLOCKS, BS, dtype))
    return place(params), place(pools)


def _fits_one_chip(compiled) -> bool:
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return need < V5E_HBM_BYTES


def _compile_decode_step(spec, cfg, dtype):
    params, pools = _params_and_pools(spec, cfg, dtype)
    step = jax.jit(functools.partial(api.paged_decode_step, cfg),
                   donate_argnums=(2,))
    with use_backend("pallas"):
        compiled = step.lower(params, spec((B, 1), jnp.int32), pools,
                              spec((B, NB), jnp.int32),
                              spec((B,), jnp.int32)).compile()
    return compiled, pools


def _pool_copies(text, pools) -> list:
    """Lines of the compiled HLO that copy the stacked pool or one layer of
    it (a ``copy``, or a fusion whose name starts with ``copy``)."""
    shapes = set()
    for leaf in jax.tree.leaves(pools):
        shapes.add(",".join(map(str, leaf.shape)))
        shapes.add(",".join(map(str, leaf.shape[1:])))
    dims = "|".join(re.escape(s) for s in shapes)
    pat = re.compile(r"%%copy[\w.-]* = \w+\[(%s)\]\{" % dims)
    return [line for line in text.splitlines() if pat.search(line)]


def _pool_bytes(pools) -> int:
    return sum(x.size * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(pools))


def test_paged_decode_step_compiles_for_v5e(spec):
    """The served qwen2-1.5b decode step, float32, at full width: the
    Pallas paged kernel is in the program, the program fits one chip, and
    the stacked pool is never copied whole or a layer at a time.  (Its
    temporaries are the float32 weights' bfloat16 casts, hoisted out of the
    layer scan by the compiler, not a pool: the bfloat16 step checks that.)"""
    compiled, pools = _compile_decode_step(spec, get_config("qwen2-1.5b"),
                                           jnp.float32)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _fits_one_chip(compiled)
    assert _pool_copies(text, pools) == []


def _assert_pool_in_place(compiled, pools):
    """Every layer writes its new K/V into the one carried pool and the
    kernel reads it by layer: no second pool held as a temporary (under an
    eighth of the pools' bytes) and no copy of a pool or of one layer."""
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < _pool_bytes(pools) / 8, (temp, _pool_bytes(pools))
    assert _pool_copies(compiled.as_text(), pools) == []


def test_bf16_decode_step_updates_the_pool_in_place(spec):
    """qwen2-1.5b's decode step as its benchmark cell serves it, bfloat16:
    the pool is updated in place."""
    compiled, pools = _compile_decode_step(spec, get_config("qwen2-1.5b"),
                                           jnp.bfloat16)
    _assert_pool_in_place(compiled, pools)


def test_chatglm2_decode_step_compiles_for_v5e(spec):
    """The served chatglm2-6b decode step at full width in bfloat16, as its
    benchmark cell serves it: the paged kernel is in, the half-head rotary
    in pairs is in the ``attention/rope`` scope, 12.5 GB of weights with
    the pool fit one chip, and the pool is updated in place."""
    compiled, pools = _compile_decode_step(spec, get_config("chatglm2-6b"),
                                           jnp.bfloat16)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "attention/rope/" in text
    assert _fits_one_chip(compiled)
    _assert_pool_in_place(compiled, pools)


def test_prefill_step_compiles_for_v5e(spec):
    """qwen2-1.5b prefill of one 512-token prompt: flash kernel in, fits."""
    cfg = get_config("qwen2-1.5b")
    params, _ = _params_and_pools(spec, cfg)
    step = jax.jit(lambda p, t, n: api.prefill(
        cfg, p, {"tokens": t}, cache_len=S, kv_len=n))
    with use_backend("pallas"):
        compiled = step.lower(params, spec((1, S), jnp.int32),
                              spec((1,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits_one_chip(compiled)
