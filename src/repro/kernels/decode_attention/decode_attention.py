"""Flash-decoding Pallas TPU kernel: one query token per sequence attends to a
long KV cache, blocked along the sequence axis.

Grid (batch, kv_head, kv_blocks), kv innermost; the G query heads that share
a kv head form the matmul rows ([G, d] x [d, kv_block] -> [G, kv_block]),
padded to the 8-sublane minimum.  Running (m, l, acc) stay in VMEM scratch
across the kv sweep.  Per-sequence valid lengths arrive via scalar prefetch
so fully masked tail blocks are skipped without recompilation.

The kernel reads a head-major ``[B, KV, S, D]`` cache: the TPU compiler tiles
the last two block dimensions by (8, 128) unless they span the whole array,
so the head axis cannot be one of them.  The wrapper transposes the
``[B, S, KV, D]`` cache in, one copy of the whole cache per call.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(kv_len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                *, scale: float, softcap: Optional[float],
                window: Optional[int], kv_block: int, nk: int, g_pad: int):
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    kv_len = kv_len_ref[bi]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ki * kv_block
    run = k_start < kv_len
    if window is not None:
        run &= k_start + kv_block > kv_len - 1 - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale      # [g_pad, d]
        k = k_ref[0, 0, :, :].astype(jnp.float32)              # [kvb, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (g_pad, kv_block), 1)
        mask = k_pos < kv_len
        if window is not None:
            mask &= k_pos > kv_len - 1 - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        m_ref[:, :1] = m_new
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("softcap", "window", "scale", "kv_block", "interpret"))
def decode_attention_pallas(
    q: jnp.ndarray,            # [B, H, D]
    k: jnp.ndarray,            # [B, S, KV, D]
    v: jnp.ndarray,            # [B, S, KV, Dv]
    kv_len: jnp.ndarray,       # [B] int32
    *,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    kv_block: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, d = q.shape
    _, s, kv, dv = v.shape
    group = h // kv
    scale = scale if scale is not None else d ** -0.5
    g_pad = max(8, group)

    kv_block = min(kv_block, max(s, 8))
    s_p = -(-s // kv_block) * kv_block
    if s_p != s:
        k = jnp.pad(k, ((0, 0), (0, s_p - s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, s_p - s), (0, 0), (0, 0)))
    nk = s_p // kv_block
    k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)    # [B, KV, S, D]

    qg = q.reshape(b, kv, group, d)
    if g_pad != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))

    kernel = functools.partial(
        _dec_kernel, scale=scale, softcap=softcap, window=window,
        kv_block=kv_block, nk=nk, g_pad=g_pad)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g_pad, d), lambda bi, hi, ki, kvl: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, kv_block, d), lambda bi, hi, ki, kvl: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, kv_block, dv), lambda bi, hi, ki, kvl: (bi, hi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g_pad, dv), lambda bi, hi, ki, kvl: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g_pad, 128), jnp.float32),
            pltpu.VMEM((g_pad, 128), jnp.float32),
            pltpu.VMEM((g_pad, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g_pad, dv), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qg, k, v)
    return out[:, :, :group, :].reshape(b, h, dv)
