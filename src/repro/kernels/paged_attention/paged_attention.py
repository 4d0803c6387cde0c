"""Paged flash-decoding Pallas TPU kernels: query tokens attend to a KV
cache scattered across fixed-size physical blocks, addressed through a
``[B, nb]`` block table.

Two entry points share one kernel body:

* ``paged_decode_attention_pallas`` — one query token per sequence (the
  continuous-batching decode step);
* ``paged_window_attention_pallas`` — a ``[B, T, H, D]`` query *window* per
  sequence (speculative-decoding verification): the T positions sit at
  absolute offsets ``kv_len .. kv_len+T-1`` and are causally masked against
  the paged history *and each other* (query t sees positions ``<= kv_len+t``).

Pools are head-major, ``[KV, N, bs, D]``: the TPU compiler tiles the last
two dimensions of a block by (8, 128) unless they span the whole array, so
the kv-head axis must not be one of them.  Grid (batch, kv_head,
logical_block); the K/V BlockSpec ``(1, 1, bs, D)`` index maps read the block
table via scalar prefetch — ``(h, bt[b, i], 0, 0)`` — so the DMA engine
fetches exactly the physical block that logical slot ``i`` of sequence ``b``
owns.  No contiguous copy of the cache ever exists: this is the PagedAttention
memory model with the flash-decoding online softmax of
``decode_attention.decode_attention_pallas`` (same (m, l, acc) VMEM scratch
carried across the block sweep; tail blocks past the last valid position are
skipped).

Row layout: the window's T positions and the GQA group ride the same sublane
axis — q is laid out as ``[B, KV, T*gp, D]`` rows (row = t*gp + g, ``gp`` the
group rounded up so the row count hits the fp32 sublane tile of 8).  The
single-token kernel at ``group < 8`` therefore computes ``8/group×``
redundant query rows; the window fold reclaims that padding (T=4, group=2
fills all 8 rows; measured overhead recorded in EXPERIMENTS.md §Perf 7).

jit specialization: the pallas grid depends on the block-table width ``nb``,
so a caller presenting every distinct width would recompile per width.  Both
wrappers bucket ``nb`` up to the next power of two *outside* the jit boundary
(mirroring the engine's ``_padded_len`` prefill bucketing) — padded table
entries duplicate the row's last block, which is always a valid physical
index, and sit entirely past the valid length so the mask keeps them inert.

Block-table entries past a sequence's last block must still be *valid*
physical indices (the serving runtime pads rows with a reserved null block) —
they are masked out, but the index map dereferences them.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def bucket_nb(nb: int) -> int:
    """Power-of-two bucket schedule for the block-table width (compile-count
    cap: every width in (2^(k-1), 2^k] shares one kernel specialization)."""
    b = 1
    while b < nb:
        b *= 2
    return b


def _pad_tables(block_tables: jnp.ndarray) -> jnp.ndarray:
    """Pad [B, nb] -> [B, bucket_nb(nb)] by repeating each row's last entry
    (a valid physical block; the extra logical slots lie past every valid
    position, so the in-kernel mask never admits them)."""
    block_tables = jnp.asarray(block_tables, jnp.int32)
    nb = block_tables.shape[1]
    pad = bucket_nb(nb) - nb
    if pad == 0:
        return block_tables
    return jnp.pad(block_tables, ((0, 0), (0, pad)), mode="edge")


def _group_pad(t: int, group: int) -> int:
    """Smallest gp >= group with t*gp a positive multiple of the fp32
    sublane tile (8) — the T window absorbs padding the single-token layout
    wastes (t=1: gp = pad8(group); t=4, group=2: gp = group, zero waste)."""
    align = 8 // math.gcd(t, 8)
    return -(-group // align) * align


def _paged_kernel(kv_len_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float,
                  softcap: Optional[float], block_size: int, nb: int,
                  rows: int, gp: int, t_span: int):
    """rows = t_span*gp query rows; row r holds window position r // gp and
    attends key positions <= kv_len + r // gp."""
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    base = kv_len_ref[bi]          # history length before the query window

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ki * block_size

    @pl.when(k_start < base + t_span)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale      # [rows, d]
        k = k_ref[0, 0, :, :].astype(jnp.float32)              # [bs, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1)
        t_row = jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 0) // gp
        mask = k_pos <= base + t_row
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

    @pl.when(ki == nb - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("t_span", "group", "softcap", "scale", "interpret"))
def _paged_window_core(
    q: jnp.ndarray,              # [B, T, H, D]
    k_pool: jnp.ndarray,         # [KV, N, bs, D]
    v_pool: jnp.ndarray,         # [KV, N, bs, Dv]
    block_tables: jnp.ndarray,   # [B, nb] int32 (pre-bucketed by the wrapper)
    kv_len: jnp.ndarray,         # [B] int32 — history BEFORE the window
    *,
    t_span: int,
    group: int,
    softcap: Optional[float],
    scale: Optional[float],
    interpret: bool,
) -> jnp.ndarray:
    b, t, h, d = q.shape
    kv, _, bs, dv = v_pool.shape
    nb = block_tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    gp = _group_pad(t, group)
    rows = t * gp

    # [B, T, KV, group, D] -> rows (row = t*gp + g), zero-padded g >= group
    q5 = jnp.moveaxis(q.reshape(b, t, kv, group, d), 1, 2)
    qg = q5.reshape(b, kv, t * group, d)
    if gp != group:
        idx = (jnp.repeat(jnp.arange(t), group) * gp
               + jnp.tile(jnp.arange(group), t))
        qg = jnp.zeros((b, kv, rows, d), q.dtype).at[:, :, idx, :].set(qg)

    kernel = functools.partial(
        _paged_kernel, scale=scale, softcap=softcap, block_size=bs, nb=nb,
        rows=rows, gp=gp, t_span=t)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # kv_len, block_tables
        grid=(b, kv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda bi, hi, ki, kvl, bt: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda bi, hi, ki, kvl, bt: (hi, bt[bi, ki], 0, 0)),
            pl.BlockSpec((1, 1, bs, dv),
                         lambda bi, hi, ki, kvl, bt: (hi, bt[bi, ki], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, dv),
                               lambda bi, hi, ki, kvl, bt: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, dv), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), block_tables.astype(jnp.int32),
      qg, k_pool, v_pool)
    out = out.reshape(b, kv, t, gp, dv)[:, :, :, :group, :]
    return jnp.moveaxis(out, 2, 1).reshape(b, t, h, dv)


def paged_window_attention_pallas(
    q: jnp.ndarray,              # [B, T, H, D] — the draft window
    k_pool: jnp.ndarray,         # [KV, N, bs, D]
    v_pool: jnp.ndarray,         # [KV, N, bs, Dv]
    block_tables: jnp.ndarray,   # [B, nb] int32
    kv_len: jnp.ndarray,         # [B] int32 — history length BEFORE the window
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-token paged attention: window position t (absolute ``kv_len+t``,
    K/V already scattered at ``kv_len .. kv_len+T-1``) attends to cache
    positions ``<= kv_len + t``.  Returns [B, T, H, Dv]."""
    group = q.shape[2] // k_pool.shape[0]
    return _paged_window_core(
        q, k_pool, v_pool, _pad_tables(block_tables),
        jnp.asarray(kv_len, jnp.int32), t_span=q.shape[1], group=group,
        softcap=softcap, scale=scale, interpret=interpret)


def paged_decode_attention_pallas(
    q: jnp.ndarray,              # [B, H, D]
    k_pool: jnp.ndarray,         # [KV, N, bs, D]
    v_pool: jnp.ndarray,         # [KV, N, bs, Dv]
    block_tables: jnp.ndarray,   # [B, nb] int32 (pad rows with a valid block)
    kv_len: jnp.ndarray,         # [B] int32 — valid entries incl. the query
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token paged decode: the query sits at position ``kv_len - 1``
    (its K/V already scattered), i.e. the T=1 window at base ``kv_len - 1``."""
    group = q.shape[1] // k_pool.shape[0]
    out = _paged_window_core(
        q[:, None], k_pool, v_pool, _pad_tables(block_tables),
        jnp.asarray(kv_len, jnp.int32) - 1, t_span=1, group=group,
        softcap=softcap, scale=scale, interpret=interpret)
    return out[:, 0]
