"""Dispatching wrappers for paged decode attention (single-token decode and
the multi-token speculative-verification window).  Pools are one layer's
``[KV, N, bs, D]`` or the layer-stacked ``[L, KV, N, bs, D]`` read at
``layer``."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.backend import get_backend
from repro.kernels.paged_attention.ref import (
    paged_decode_attention_reference, paged_window_attention_reference)
from repro.kernels.paged_attention.xla import (
    paged_decode_attention_xla, paged_window_attention_xla)
from repro.kernels.paged_attention.paged_attention import (
    paged_decode_attention_pallas, paged_window_attention_pallas)

__all__ = ["paged_decode_attention", "paged_window_attention"]


def paged_decode_attention(
    q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
    block_tables: jnp.ndarray, kv_len: jnp.ndarray, layer=0,
    *, softcap: Optional[float] = None, scale: Optional[float] = None,
) -> jnp.ndarray:
    backend = get_backend()
    kw = dict(softcap=softcap, scale=scale)
    if backend == "naive":
        return paged_decode_attention_reference(
            q, k_pool, v_pool, block_tables, kv_len, layer, **kw)
    if backend == "xla":
        return paged_decode_attention_xla(
            q, k_pool, v_pool, block_tables, kv_len, layer, **kw)
    return paged_decode_attention_pallas(
        q, k_pool, v_pool, block_tables, kv_len, layer,
        interpret=(backend == "pallas_interpret"), **kw)


def paged_window_attention(
    q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
    block_tables: jnp.ndarray, kv_len: jnp.ndarray, layer=0,
    *, softcap: Optional[float] = None, scale: Optional[float] = None,
) -> jnp.ndarray:
    """q [B, T, H, D] draft window at base ``kv_len`` (history before the
    window; the window's K/V already scattered).  Returns [B, T, H, Dv]."""
    backend = get_backend()
    kw = dict(softcap=softcap, scale=scale)
    if backend == "naive":
        return paged_window_attention_reference(
            q, k_pool, v_pool, block_tables, kv_len, layer, **kw)
    if backend == "xla":
        return paged_window_attention_xla(
            q, k_pool, v_pool, block_tables, kv_len, layer, **kw)
    return paged_window_attention_pallas(
        q, k_pool, v_pool, block_tables, kv_len, layer,
        interpret=(backend == "pallas_interpret"), **kw)
