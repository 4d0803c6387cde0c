"""Paged decode attention in plain XLA: one dense gather through the block
table materializes the contiguous view, then the same masked partial-softmax
math as decode_attention_xla.  The path off the TPU (CPU, dry-run) and the
reference the Pallas kernel is checked against on the chip — the kernel
avoids the materialized gather entirely.  Pools are head-major
``[KV, N, bs, D]``, or the layer-stacked ``[L, KV, N, bs, D]`` with the
layer to read (see paged_attention.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.xla import decode_attention_partial


def _gather(pool: jnp.ndarray, block_tables: jnp.ndarray,
            layer) -> jnp.ndarray:
    """[KV, N, bs, d] pool (or layer ``layer`` of a stacked [L, KV, N, bs,
    d] one) through a [B, nb] table -> [B, nb*bs, KV, d]."""
    if pool.ndim == 5:
        pool = pool[layer]
    kv, _, bs, d = pool.shape
    b, nb = block_tables.shape
    g = pool[:, block_tables].reshape(kv, b, nb * bs, d)
    return jnp.transpose(g, (1, 2, 0, 3))


@functools.partial(jax.jit, static_argnames=("softcap", "scale"))
def paged_window_attention_xla(
    q: jnp.ndarray,              # [B, T, H, D] — draft window
    k_pool: jnp.ndarray,         # [L, KV, N, bs, D] or [KV, N, bs, D]
    v_pool: jnp.ndarray,         # [L, KV, N, bs, Dv] or [KV, N, bs, Dv]
    block_tables: jnp.ndarray,   # [B, nb] int32
    kv_len: jnp.ndarray,         # [B] int32 — history length BEFORE the window
    layer=0,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Multi-token verify window in plain XLA: one gather materializes the
    contiguous view, then each window position runs the *same* masked
    partial-softmax math as the single-token step (unrolled over the static
    T) — identical per-position shapes keep verify logits bitwise equal to
    sequential decode on CPU, which greedy token-identity rides on."""
    t = q.shape[1]
    k = _gather(k_pool, block_tables, layer)
    v = _gather(v_pool, block_tables, layer)
    outs = []
    for ti in range(t):
        acc, m, l = decode_attention_partial(
            q[:, ti], k, v, kv_len + ti + 1, softcap=softcap, scale=scale)
        outs.append(acc / jnp.maximum(l, 1e-30)[..., None])
    return jnp.stack(outs, axis=1).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "scale"))
def paged_decode_attention_xla(
    q: jnp.ndarray,              # [B, H, D]
    k_pool: jnp.ndarray,         # [L, KV, N, bs, D] or [KV, N, bs, D]
    v_pool: jnp.ndarray,         # [L, KV, N, bs, Dv] or [KV, N, bs, Dv]
    block_tables: jnp.ndarray,   # [B, nb] int32
    kv_len: jnp.ndarray,         # [B] int32
    layer=0,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    k = _gather(k_pool, block_tables, layer)
    v = _gather(v_pool, block_tables, layer)
    acc, m, l = decode_attention_partial(q, k, v, kv_len, softcap=softcap,
                                         scale=scale)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)
