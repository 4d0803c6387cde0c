"""Dispatching wrapper for the WKV6 recurrence."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.backend import get_backend
from repro.kernels.wkv6.ref import wkv6_reference
from repro.kernels.wkv6.xla import wkv6_step, wkv6_xla  # noqa: F401
from repro.kernels.wkv6.wkv6 import wkv6_pallas

__all__ = ["wkv6", "wkv6_step"]


def wkv6(r, k, v, w, u, s0=None, *, chunk: int = 32):
    backend = get_backend()
    if backend == "naive":
        return wkv6_reference(r, k, v, w, u, s0)
    if backend == "xla":
        return wkv6_xla(r, k, v, w, u, s0, chunk=chunk)
    return wkv6_pallas(r, k, v, w, u, s0, chunk=chunk,
                       interpret=(backend == "pallas_interpret"))
