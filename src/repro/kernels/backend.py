"""Kernel-backend selection.

The backend follows the platform: ``'pallas'`` (``pl.pallas_call`` compiled
by the TPU's compiler) where ``jax.default_backend() == "tpu"``, ``'xla'``
(blocked pure-JAX implementations) everywhere else.  ``use_backend`` picks
another one for a scope — tests and parity checks only:

'pallas_interpret' — kernel body interpreted on CPU (correctness validation).
'naive'            — the ref.py oracle (tiny shapes only).
'xla'              — on the TPU, the reference the kernels are checked
                     against.

The choice is read while a function is traced, so a jitted function keeps
the backend it was first traced under.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax

VALID = ("xla", "pallas", "pallas_interpret", "naive")
_override: Optional[str] = None


def get_backend() -> str:
    if _override is not None:
        return _override
    return "pallas" if jax.default_backend() == "tpu" else "xla"


@contextlib.contextmanager
def use_backend(name: str):
    global _override
    if name not in VALID:
        raise ValueError(f"backend {name!r} not in {VALID}")
    prev = _override
    _override = name
    try:
        yield
    finally:
        _override = prev
