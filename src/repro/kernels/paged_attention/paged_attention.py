"""Paged flash-decoding Pallas TPU kernels: query tokens attend to a KV
cache scattered across fixed-size physical blocks, addressed through a
``[B, nb]`` block table.

Two entry points share one core, ``_paged_window_core``:

* ``paged_decode_attention_pallas`` — one query token per sequence (the
  continuous-batching decode step);
* ``paged_window_attention_pallas`` — a ``[B, T, H, D]`` query *window* per
  sequence (speculative-decoding verification): the T positions sit at
  absolute offsets ``kv_len .. kv_len+T-1`` and are causally masked against
  the paged history *and each other* (query t sees positions ``<= kv_len+t``).

Pools are head-major, ``[KV, N, bs, D]``: the TPU compiler tiles the last
two dimensions of a block by (8, 128) unless they span the whole array, so
the kv-head axis must not be one of them.  The model step hands the kernel
the whole layer-stacked pool ``[L, KV, N, bs, D]`` and the layer to read
(a third scalar prefetch), so no layer's pool is ever sliced out and
copied; a 4-D pool is read as layer 0 of a stack of one.

Live-page sweep (head dims a multiple of 128).  Grid (batch, kv_head); the
pools stay in HBM (``memory_space=ANY``) and the kernel copies K/V pages
itself, through the block table prefetched into SMEM, into a
double-buffered VMEM scratch.  A compute block is ``ppc`` pages, the
fewest that span 128 keys (16 at ``bs`` 8), capped at the bucketed table
width.  The sweep over a sequence's compute blocks stops at its last live
position (``live_pages``): pages past it cost no copy, no compute and no
grid step, and the last block copies only its live pages.  The next
block's copies, across (batch, kv_head) steps too, start before the
current block is computed.  No contiguous copy of the cache ever exists:
this is the PagedAttention memory model with the flash-decoding online
softmax of ``decode_attention.decode_attention_pallas`` ((m, l, acc) in
VMEM scratch, float32 throughout).

Page walk (narrower head dims, e.g. smollm-135m's 64).  Mosaic cannot
slice a ref whose last dim is narrower than the 128-lane tile, so such
pools cannot be copied page by page from inside the kernel.  They keep the
grid (batch, kv_head, logical_block) with BlockSpec index maps that read
the table, ``(h, bt[b, i], 0, 0)``: every table block is copied, and only
the compute stops at the last live page.  Both paths share the block math
(``_attend``).

Row layout: the window's T positions and the GQA group ride the same sublane
axis — q is laid out as ``[B, KV, T*gp, D]`` rows (row = t*gp + g, ``gp`` the
group rounded up so the row count hits the fp32 sublane tile of 8).  The
single-token kernel at ``group < 8`` therefore computes ``8/group×``
redundant query rows; the window fold reclaims that padding (T=4, group=2
fills all 8 rows; measured overhead recorded in EXPERIMENTS.md §Perf 7).

jit specialization: the kernel's shapes depend on the block-table width
``nb``, so a caller presenting every distinct width would recompile per
width.  Both wrappers bucket ``nb`` up to the next power of two *outside*
the jit boundary (mirroring the engine's ``_padded_len`` prefill bucketing)
— padded table entries duplicate the row's last block, which is always a
valid physical index, and sit entirely past the valid length so the mask
keeps them inert.

Block-table entries past a sequence's last block must still be *valid*
physical indices for the page walk (the serving runtime pads rows with a
reserved null block) — they are masked out, but the index map dereferences
them.  The sweep never reads them.
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


def live_pages(base, t_span: int, block_size: int):
    """Pages a row reads when its query window starts at ``base`` (history
    length before the window): those holding positions ``< base + t_span``.
    One expression for the kernel's scalar and a host NumPy array, so the
    engine's page counter and the kernel's bound cannot drift apart."""
    return (base + t_span + block_size - 1) // block_size


def pages_per_block(block_size: int, nb: int) -> int:
    """Pages in one compute block: the fewest that span 128 keys (one
    lane-width of the QK^T tile), at most the bucketed table width."""
    return min(-(-128 // block_size), nb)


def _attend(q, k, v, k_start, base, m_ref, l_ref, acc_ref, *,
            softcap: Optional[float], gp: int, v_limit=None):
    """Fold keys ``k_start .. k_start+len(k)-1`` into the online softmax
    (m, l, acc) of the scaled float32 query rows ``q``; row r holds window
    position r // gp and attends key positions <= base + r // gp.  With
    ``v_limit``, value rows at or past it are zeroed first: they were never
    copied and hold whatever the buffer held before."""
    rows, n = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
    t_row = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0) // gp
    mask = k_pos <= base + t_row
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[:, :1] = l_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
    m_ref[:, :1] = m_new
    if v_limit is not None:
        v_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(v_pos < v_limit, v, 0.0)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv


def _init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _finalize(o_ref, l_ref, acc_ref):
    out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
    o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


def _sweep_kernel(kv_len_ref, bt_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, k_sems, v_sems, state_ref,
                  m_ref, l_ref, acc_ref, *, scale: float,
                  softcap: Optional[float], block_size: int, ppc: int,
                  gp: int, t_span: int):
    """Grid (batch, kv_head).  One step sweeps its sequence's compute blocks
    of ``ppc`` pages and stops at the last live one; dead pages cost no
    copy, no compute and no grid step.

    The stacked pools stay in HBM.  Each live page of layer ``layer_ref[0]``
    is copied into a double-buffered VMEM scratch, and the next block's
    copies (this sequence's, or the first block of the next grid step)
    start before the current block is computed.
    ``state_ref`` carries across grid steps: [0] the buffer the current
    block lands in, [1] whether the step before already started this step's
    first block."""
    b, h = pl.program_id(0), pl.program_id(1)
    n_b, n_h = pl.num_programs(0), pl.num_programs(1)
    bk = ppc * block_size
    base = kv_len_ref[b]           # history length before the query window
    layer = layer_ref[0]
    n_blk = (live_pages(base, t_span, block_size) + ppc - 1) // ppc

    def live_page_loop(bb, i, fn):
        """``fn(j, page)`` for each live page j of compute block i of
        sequence bb, each under its own guard."""
        n_live = live_pages(kv_len_ref[bb], t_span, block_size) - i * ppc
        for j in range(ppc):
            @pl.when(j < n_live)
            def _page(j=j):
                fn(j, bt_ref[bb, i * ppc + j])

    pools = ((k_hbm, k_buf, k_sems), (v_hbm, v_buf, v_sems))

    def copy(which, hh, slot, j, page):
        hbm, buf, sems = pools[which]
        return pltpu.make_async_copy(hbm.at[layer, hh, page],
                                     buf.at[slot, j], sems.at[slot])

    def start(bb, hh, i, slot):
        def both(j, page):
            copy(0, hh, slot, j, page).start()
            copy(1, hh, slot, j, page).start()
        live_page_loop(bb, i, both)

    def wait(i, slot, which):
        live_page_loop(b, i, lambda j, page: copy(which, h, slot, j,
                                                  page).wait())

    @pl.when((b == 0) & (h == 0))
    def _first_step():
        state_ref[0] = 0
        state_ref[1] = 0

    @pl.when((n_blk > 0) & (state_ref[1] == 0))
    def _start_first_block():
        start(b, h, 0, state_ref[0])

    _init(m_ref, l_ref, acc_ref)
    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale          # [rows, d]

    def body(i, carry):
        slot = state_ref[0]
        nxt = 1 - slot

        @pl.when(i + 1 < n_blk)
        def _prefetch_own():
            start(b, h, i + 1, nxt)

        @pl.when(i + 1 == n_blk)
        def _prefetch_next_step():
            last_h = h == n_h - 1
            b2 = jnp.minimum(jnp.where(last_h, b + 1, b), n_b - 1)
            h2 = jnp.where(last_h, 0, h + 1)
            go = ((b < n_b - 1) | ~last_h) & (
                live_pages(kv_len_ref[b2], t_span, block_size) > 0)

            @pl.when(go)
            def _start_next():
                start(b2, h2, 0, nxt)
            state_ref[1] = go.astype(jnp.int32)

        wait(i, slot, 0)
        k = k_buf[slot].astype(jnp.float32).reshape(bk, -1)
        wait(i, slot, 1)
        v = v_buf[slot].astype(jnp.float32).reshape(bk, -1)
        _attend(q, k, v, i * bk, base, m_ref, l_ref, acc_ref,
                softcap=softcap, gp=gp, v_limit=base + t_span)
        state_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, n_blk, body, 0)
    _finalize(o_ref, l_ref, acc_ref)


def _page_kernel(kv_len_ref, bt_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                 m_ref, l_ref, acc_ref, *, scale: float,
                 softcap: Optional[float], block_size: int, nb: int,
                 gp: int, t_span: int):
    """Grid (batch, kv_head, logical_block), one page a step through the
    BlockSpec pipeline: for head dims narrower than the 128-lane tile, whose
    pool refs Mosaic cannot slice for a manual copy.  Every table block is
    copied; compute stops at the last live page."""
    bi, ki = pl.program_id(0), pl.program_id(2)
    base = kv_len_ref[bi]

    @pl.when(ki == 0)
    def _start():
        _init(m_ref, l_ref, acc_ref)

    @pl.when(ki < live_pages(base, t_span, block_size))
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale
        _attend(q, k_ref[0, 0, 0, :, :].astype(jnp.float32),
                v_ref[0, 0, 0, :, :].astype(jnp.float32), ki * block_size,
                base, m_ref, l_ref, acc_ref, softcap=softcap, gp=gp)

    @pl.when(ki == nb - 1)
    def _end():
        _finalize(o_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit,
    static_argnames=("t_span", "group", "softcap", "scale", "interpret"))
def _paged_window_core(
    q: jnp.ndarray,              # [B, T, H, D]
    k_pool: jnp.ndarray,         # [L, KV, N, bs, D]
    v_pool: jnp.ndarray,         # [L, KV, N, bs, Dv]
    block_tables: jnp.ndarray,   # [B, nb] int32 (pre-bucketed by the wrapper)
    kv_len: jnp.ndarray,         # [B] int32 — history BEFORE the window
    layer: jnp.ndarray,          # [1] int32 — the layer of the pools to read
    *,
    t_span: int,
    group: int,
    softcap: Optional[float],
    scale: Optional[float],
    interpret: bool,
) -> jnp.ndarray:
    b, t, h, d = q.shape
    _, kv, _, bs, dv = v_pool.shape
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

    kw = dict(scale=scale, softcap=softcap, block_size=bs, gp=gp, t_span=t)
    row_block = lambda *g: (g[0], g[1], 0, 0)           # noqa: E731
    q_spec = pl.BlockSpec((1, 1, rows, d), row_block)
    o_spec = pl.BlockSpec((1, 1, rows, dv), row_block)
    acc = [pltpu.VMEM((rows, 128), jnp.float32),
           pltpu.VMEM((rows, 128), jnp.float32),
           pltpu.VMEM((rows, dv), jnp.float32)]
    if d % 128 == 0 and dv % 128 == 0:
        ppc = pages_per_block(bs, nb)
        kernel = functools.partial(_sweep_kernel, ppc=ppc, **kw)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,          # kv_len, block_tables, layer
            grid=(b, kv),
            in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=o_spec,
            scratch_shapes=[
                pltpu.VMEM((2, ppc, bs, d), k_pool.dtype),
                pltpu.VMEM((2, ppc, bs, dv), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                *acc],
        )
    else:
        kernel = functools.partial(_page_kernel, nb=nb, **kw)
        page = lambda bi, hi, ki, kvl, bt, ly: (  # noqa: E731
            ly[0], hi, bt[bi, ki], 0, 0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, kv, nb),
            in_specs=[q_spec, pl.BlockSpec((1, 1, 1, bs, d), page),
                      pl.BlockSpec((1, 1, 1, bs, dv), page)],
            out_specs=o_spec,
            scratch_shapes=acc,
        )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, dv), q.dtype),
        # the sweep's copies chain across grid steps: every axis in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid_spec.grid)),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(kv_len.astype(jnp.int32), block_tables.astype(jnp.int32),
      layer.astype(jnp.int32), qg, k_pool, v_pool)
    out = out.reshape(b, kv, t, gp, dv)[:, :, :, :group, :]
    return jnp.moveaxis(out, 2, 1).reshape(b, t, h, dv)


def _stacked(pool: jnp.ndarray) -> jnp.ndarray:
    """The layer-stacked ``[L, KV, N, bs, D]`` view the core reads; a 4-D
    pool is layer 0 of a stack of one (a free reshape)."""
    return pool[None] if pool.ndim == 4 else pool


def paged_window_attention_pallas(
    q: jnp.ndarray,              # [B, T, H, D] — the draft window
    k_pool: jnp.ndarray,         # [L, KV, N, bs, D] or [KV, N, bs, D]
    v_pool: jnp.ndarray,         # [L, KV, N, bs, Dv] or [KV, N, bs, Dv]
    block_tables: jnp.ndarray,   # [B, nb] int32
    kv_len: jnp.ndarray,         # [B] int32 — history length BEFORE the window
    layer=0,                     # int32 scalar — the stacked pools' layer
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-token paged attention: window position t (absolute ``kv_len+t``,
    K/V already scattered at ``kv_len .. kv_len+T-1``) attends to cache
    positions ``<= kv_len + t`` of layer ``layer``.  Returns [B, T, H, Dv]."""
    k_pool, v_pool = _stacked(k_pool), _stacked(v_pool)
    group = q.shape[2] // k_pool.shape[1]
    return _paged_window_core(
        q, k_pool, v_pool, _pad_tables(block_tables),
        jnp.asarray(kv_len, jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), t_span=q.shape[1],
        group=group, softcap=softcap, scale=scale, interpret=interpret)


def paged_decode_attention_pallas(
    q: jnp.ndarray,              # [B, H, D]
    k_pool: jnp.ndarray,         # [L, KV, N, bs, D] or [KV, N, bs, D]
    v_pool: jnp.ndarray,         # [L, KV, N, bs, Dv] or [KV, N, bs, Dv]
    block_tables: jnp.ndarray,   # [B, nb] int32 (pad rows with a valid block)
    kv_len: jnp.ndarray,         # [B] int32 — valid entries incl. the query
    layer=0,                     # int32 scalar — the stacked pools' layer
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token paged decode: the query sits at position ``kv_len - 1``
    (its K/V already scattered), i.e. the T=1 window at base ``kv_len - 1``."""
    out = paged_window_attention_pallas(
        q[:, None], k_pool, v_pool, block_tables,
        jnp.asarray(kv_len, jnp.int32) - 1, layer, softcap=softcap,
        scale=scale, interpret=interpret)
    return out[:, 0]
