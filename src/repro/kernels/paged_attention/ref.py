"""Pure-jnp oracle for paged decode attention: gather the block-table view
into a contiguous cache and defer to the decode_attention oracle.  A
layer-stacked ``[L, KV, N, bs, D]`` pool is read at ``layer``.  Tests only.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.decode_attention.ref import decode_attention_reference


def gather_pool(pool: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Head-major [KV, N, bs, d] pool + [B, nb] table -> contiguous
    [B, nb*bs, KV, d]."""
    b, nb = block_tables.shape
    kv, _, bs, d = pool.shape
    g = pool[:, block_tables]                       # [KV, B, nb, bs, d]
    return jnp.moveaxis(g.reshape(kv, b, nb * bs, d), 0, 2)


def _layer(pool: jnp.ndarray, layer) -> jnp.ndarray:
    return pool[layer] if pool.ndim == 5 else pool


def paged_decode_attention_reference(
    q: jnp.ndarray,              # [B, H, D]  (one new token)
    k_pool: jnp.ndarray,         # [(L,) KV, N, bs, D]   paged K pool
    v_pool: jnp.ndarray,         # [(L,) KV, N, bs, Dv]
    block_tables: jnp.ndarray,   # [B, nb] int32 — physical block per logical slot
    kv_len: jnp.ndarray,         # [B] int32 — valid cache entries per sequence
    layer=0,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    k = gather_pool(_layer(k_pool, layer), block_tables)
    v = gather_pool(_layer(v_pool, layer), block_tables)
    return decode_attention_reference(q, k, v, kv_len, softcap=softcap,
                                      scale=scale)


def paged_window_attention_reference(
    q: jnp.ndarray,              # [B, T, H, D] — draft window
    k_pool: jnp.ndarray,         # [(L,) KV, N, bs, D]
    v_pool: jnp.ndarray,         # [(L,) KV, N, bs, Dv]
    block_tables: jnp.ndarray,   # [B, nb] int32
    kv_len: jnp.ndarray,         # [B] int32 — history length BEFORE the window
    layer=0,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Oracle for the multi-token verify window: one single-token decode per
    window position (position t's K/V already scattered at ``kv_len + t``, so
    its per-position valid length is ``kv_len + t + 1``)."""
    k_pool, v_pool = _layer(k_pool, layer), _layer(v_pool, layer)
    outs = [decode_attention_reference(
        q[:, t], gather_pool(k_pool, block_tables),
        gather_pool(v_pool, block_tables),
        jnp.asarray(kv_len, jnp.int32) + t + 1, softcap=softcap, scale=scale)
        for t in range(q.shape[1])]
    return jnp.stack(outs, axis=1)
