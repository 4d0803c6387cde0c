"""FlashAttention-2 style Pallas TPU kernel.

Schedule: grid (batch, q_head, q_blocks, kv_blocks) with the kv dimension
innermost; (m, l, acc) running statistics live in VMEM scratch across the kv
sweep and the output tile is written once, on the last kv step.  Q tiles are
(q_block, head_dim) so the MXU sees [q_block, d] x [d, kv_block] matmuls with
both dims >= 128 for the production block sizes.  GQA is handled in the index
maps (query head h reads kv head h // group) — no KV repetition in HBM.

Causal masking skips fully-masked kv blocks via pl.when; the diagonal block
applies an iota mask.  Sliding-window and Gemma-style softcap are supported so
the same kernel serves llama/qwen (full causal), gemma2 (window + softcap) and
whisper's encoder (bidirectional: causal=False).

The kernel reads head-major ``[B, H, S, D]`` tiles: the TPU compiler tiles
the last two block dimensions by (8, 128) unless they span the whole array,
so the head axis cannot be one of them.  The wrapper transposes the
``[B, S, H, D]`` activations in and out, one copy of q, k, v and the output
per call.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: Optional[int],
               softcap: Optional[float], q_offset: int,
               q_block: int, kv_block: int, nk: int, sq: int, skv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * q_block + q_offset          # absolute position of row 0
    k_start = ki * kv_block

    # Skip kv blocks that are entirely masked out.
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + q_block - 1
    if window is not None:
        # the oldest key this q block may see is q_start - window + 1
        run &= k_start + kv_block > q_start - window + 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale          # [qb, d]
        k = k_ref[0, 0, :, :].astype(jnp.float32)                  # [kvb, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [qb, kvb]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)
        mask = k_pos < skv                       # seq padding
        mask &= q_pos < sq + q_offset
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                                       # [qb, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        m_ref[:, :1] = m_new
        v = v_ref[0, 0, :, :].astype(jnp.float32)                   # [kvb, dv]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_offset", "scale",
                     "q_block", "kv_block", "interpret"),
)
def flash_attention_pallas(
    q: jnp.ndarray,            # [B, Sq, H, D]
    k: jnp.ndarray,            # [B, Skv, KV, D]
    v: jnp.ndarray,            # [B, Skv, KV, Dv]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    q_block: int = 128,
    kv_block: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, sq, h, d = q.shape
    _, skv, kv, dv = v.shape
    group = h // kv
    scale = scale if scale is not None else d ** -0.5

    q_block = min(q_block, max(sq, 8))
    kv_block = min(kv_block, max(skv, 8))
    sq_p = -(-sq // q_block) * q_block
    skv_p = -(-skv // kv_block) * kv_block
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    if skv_p != skv:
        k = jnp.pad(k, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))
    nq, nk = sq_p // q_block, skv_p // kv_block
    q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))     # [B, H, S, D]

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, q_block=q_block, kv_block=kv_block, nk=nk,
        sq=sq, skv=skv)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, kv_block, d), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, kv_block, dv), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, dv), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 128), jnp.float32),   # running max m
            pltpu.VMEM((q_block, 128), jnp.float32),   # running sum l
            pltpu.VMEM((q_block, dv), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return jnp.swapaxes(out, 1, 2)[:, :sq]
