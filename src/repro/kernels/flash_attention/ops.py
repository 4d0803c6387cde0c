"""Dispatching wrapper for flash attention: picks the backend
(naive oracle / blocked-XLA / Pallas TPU / Pallas-interpret) from
``kernels.backend.get_backend`` — Pallas on the TPU, XLA elsewhere.  This is
the symbol the model layers import.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.backend import get_backend
from repro.kernels.flash_attention.ref import flash_attention_reference
from repro.kernels.flash_attention.xla import flash_attention_xla
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas


def flash_attention(
    q: jnp.ndarray,            # [B, Sq, H, D]
    k: jnp.ndarray,            # [B, Skv, KV, D]
    v: jnp.ndarray,            # [B, Skv, KV, Dv]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    q_block: int = 512,
    kv_block: int = 1024,
) -> jnp.ndarray:
    backend = get_backend()
    kw = dict(causal=causal, window=window, softcap=softcap,
              q_offset=q_offset, scale=scale)
    if backend == "naive":
        return flash_attention_reference(q, k, v, **kw)
    if backend == "xla":
        return flash_attention_xla(q, k, v, q_block=q_block, kv_block=kv_block, **kw)
    interp = backend == "pallas_interpret"
    return flash_attention_pallas(
        q, k, v, q_block=min(128, q_block), kv_block=min(512, kv_block),
        interpret=interp, **kw)
