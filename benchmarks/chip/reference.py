"""Plain references, one file per architecture under ``references/``, found
by the ``reference`` name in a configuration's file.  A reference imports
nothing of the program and takes nothing it made: it draws its own weights
from the run seed, and the q/k/v biases with ``qkv_biases``, which the
harness also writes into the served weights.

Each reference module provides
``score(model, dtype, seed, rows, *, s_pad, n_read, control) -> list``:
``rows`` are ``(context, first, served)`` with ``context`` the prompt and
all served tokens but the last, and ``served`` the tokens whose logits sit
at positions ``first, first + 1, ...`` of the context.  It returns one
``(gaps, control_gaps)`` pair per row: the widest distance by which a
served token's logit lies below the reference's best, per position, and,
with ``control``, the same for the token that the lower-precision
computation puts first (else None).
"""
from __future__ import annotations

import importlib.util
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def seed_key(seed: int):
    """PRNG key of a run seed of any size: the low 32 bits seed the key and
    the rest is folded in, so seeds past 2**32 stay distinct."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


BIAS_STD = 0.5


def qkv_biases(key, n_layers: int, widths: dict, dtype) -> dict:
    """{"q" | "k" | "v": [n_layers, width]} projection biases drawn from a
    run's key, in the dtype they are served in.  The program's own
    initialisation leaves them at zero, which would hide a bias applied to
    the wrong slice or not at all; the harness writes these into the served
    weights and the reference draws the same."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.fold_in(key, 0xB1A5), len(widths))
    return {n: (jax.random.normal(k, (n_layers, w), jnp.float32)
                * BIAS_STD).astype(dtype)
            for k, (n, w) in zip(ks, sorted(widths.items()))}


def load(name: str):
    path = HERE / "references" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"references.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
