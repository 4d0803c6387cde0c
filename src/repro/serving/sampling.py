"""Token sampling for the serving engine."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.trace import device_scope


def greedy(logits: jnp.ndarray, vocab_size: int) -> jnp.ndarray:
    """logits [B, Vp] -> [B] token ids, restricted to the real vocab."""
    with device_scope("pick"):
        masked = jnp.where(jnp.arange(logits.shape[-1]) < vocab_size, logits,
                           -jnp.inf)
        return jnp.argmax(masked, axis=-1).astype(jnp.int32)


def sample(logits: jnp.ndarray, vocab_size: int, key, *, temperature: float = 1.0,
           top_k: int = 0) -> jnp.ndarray:
    masked = jnp.where(jnp.arange(logits.shape[-1]) < vocab_size, logits, -jnp.inf)
    if temperature <= 0:
        return jnp.argmax(masked, -1).astype(jnp.int32)
    masked = masked / temperature
    if top_k > 0:
        vals, _ = jax.lax.top_k(masked, top_k)
        cut = vals[..., -1:]
        masked = jnp.where(masked < cut, -jnp.inf, masked)
    return jax.random.categorical(key, masked, axis=-1).astype(jnp.int32)
