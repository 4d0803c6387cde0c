"""Shared model primitives: norms, activations, RoPE (standard + M-RoPE),
sinusoidal positions, init helpers.  Pure-functional: params are nested dicts.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

# ----------------------------------------------------------------- init utils

def dense_init(key, d_in: int, d_out: int, dtype, *, bias: bool = False,
               scale: float | None = None):
    std = scale if scale is not None else d_in ** -0.5
    w = (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def apply_dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def norm_init(cfg: ModelConfig, dtype):
    p = {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def apply_norm(cfg: ModelConfig, p, x, *, d: Optional[int] = None):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.norm_eps)
        return (xf * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(axis=-1, keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
    return (xf * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


def group_norm(x, scale, bias, n_groups: int, eps: float = 1e-5):
    """GroupNorm over the last dim split into n_groups (RWKV head norm)."""
    *lead, d = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, n_groups, d // n_groups)
    mean = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(axis=-1, keepdims=True)
    xf = ((xf - mean) * jax.lax.rsqrt(var + eps)).reshape(*lead, d)
    return (xf * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def activation(cfg: ModelConfig, x):
    if cfg.act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


# ----------------------------------------------------------------------- RoPE

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float,
               fraction: float = 1.0, interleaved: bool = False):
    """x: [..., S, H, D]; pos: broadcastable to [..., S] absolute positions.

    Rotates the first ``r = int(fraction * D)`` channels at frequencies
    ``theta ** (-2i / r)`` and passes the rest through: channel i against
    i + r/2 (the two halves), or with ``interleaved`` channel 2i against
    2i + 1 (ChatGLM2's ``apply_rotary_pos_emb``)."""
    d = x.shape[-1]
    r = int(fraction * d)
    if interleaved:
        return _rope_pairs(x, pos, theta, r)
    if r < d:
        return jnp.concatenate([apply_rope(x[..., :r], pos, theta),
                                x[..., r:]], axis=-1)
    freqs = rope_freqs(d, theta)                       # [D/2]
    angles = pos[..., None].astype(jnp.float32) * freqs   # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :]                # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _rope_pairs(x: jnp.ndarray, pos: jnp.ndarray, theta: float, r: int):
    """Pairs (2i, 2i+1) of the first r channels rotated, over the whole
    width so that no size-2 axis lands on the TPU's lanes: each pair's
    angle is repeated on both lanes (0 past r: cos 1, sin 0 pass the rest
    through unchanged), and each lane's partner, -x[2i+1] or x[2i], comes
    from a lane shift either way and a parity select."""
    d = x.shape[-1]
    freqs = jnp.pad(jnp.repeat(rope_freqs(r, theta), 2), (0, d - r))   # [D]
    angles = pos[..., None].astype(jnp.float32) * freqs   # [..., S, D]
    cos = jnp.cos(angles)[..., None, :]                # [..., S, 1, D]
    sin = jnp.sin(angles)[..., None, :]
    xf = x.astype(jnp.float32)
    even = np.arange(d) % 2 == 0
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                        jnp.roll(xf, 1, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype)


def apply_mrope(x: jnp.ndarray, pos3: jnp.ndarray, theta: float,
                sections: tuple[int, int, int]):
    """Qwen2-VL multimodal RoPE.  x: [B, S, H, D]; pos3: [3, B, S] (t, h, w).
    The D/2 rotary frequency channels are split into |sections| groups, each
    rotated by its own position stream."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                          # [D/2]
    # per-channel position stream selection
    sec = jnp.concatenate([jnp.full((s,), i, jnp.int32)
                           for i, s in enumerate(sections)])
    assert sec.shape[0] == d // 2, (sections, d)
    pos_sel = pos3[sec]                                   # [D/2, B, S]
    angles = pos_sel.transpose(1, 2, 0).astype(jnp.float32) * freqs  # [B,S,D/2]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(pos: jnp.ndarray, d_model: int):
    """Whisper-style sinusoidal embeddings for given positions [..., S]."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / max(half - 1, 1))
    ang = pos[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)
