"""Plain reference of a dense decoder with grouped-query attention
(Qwen2, ChatGLM2 as the configurations state them): pre-RMSNorm blocks,
q/k/v projections with bias, rotary positions over the whole head (the two
halves rotated against each other), causal softmax attention in which
query head h reads key/value head h // (heads / kv_heads), a SiLU-gated
MLP, a final RMSNorm and the output head (the embedding, when tied).

Computed in float32 at the highest matmul precision, layer by layer, so
that the widest model fits beside nothing else on one chip.  The weights
are drawn from the run seed along the same key tree as the served model's
random initialisation, and rounded to the dtype they are served in, with
the q/k/v biases of ``reference.qkv_biases``: the reference computes the
checkpoint the engine serves, exactly, and in full precision.

``control=True`` also runs the same computation with every matmul operand
rounded to float8 (e4m3, scaled per row of the reduction): the lower
precision whose result the comparison must refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import qkv_biases, seed_key

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
Q_CHUNK = 256          # query rows per attention block
HEAD_CHUNK = 64        # read positions per output-head block
SUPPORTED = {"name", "family", "n_layers", "d_model", "n_heads", "n_kv_heads",
             "head_dim", "d_ff", "vocab_size", "vocab_pad_mult", "qkv_bias",
             "rope", "rope_theta", "act", "gated_mlp", "norm", "norm_eps",
             "tie_embeddings", "dtype", "source"}


def _check(model: dict) -> None:
    extra = set(model) - SUPPORTED
    if extra:
        raise ValueError(f"dense_gqa reference: unsupported keys {extra}")
    want = {"family": "dense", "rope": "rope", "act": "silu",
            "gated_mlp": True, "norm": "rmsnorm"}
    for k, v in want.items():
        if model.get(k, v) != v:
            raise ValueError(f"dense_gqa reference: {k}={model[k]!r}")


def padded_vocab(model: dict) -> int:
    m = int(model.get("vocab_pad_mult", 256))
    return -(-int(model["vocab_size"]) // m) * m


# ------------------------------------------------------------------ weights

def _as_served(x, dtype):
    return x.astype(dtype).astype(jnp.float32)


def _dense(key, d_in, d_out, dtype):
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * d_in ** -0.5
    return _as_served(w, dtype)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _layer_weights(key, shape, dtype):
    d, h, kv, hd, ff = shape
    block = jax.random.split(key, 1)[0]
    k_attn, k_mlp = jax.random.split(block, 4)[:2]
    ka = jax.random.split(k_attn, 8)
    km = jax.random.split(k_mlp, 3)
    return {"q": _dense(ka[0], d, h * hd, dtype),
            "k": _dense(ka[1], d, kv * hd, dtype),
            "v": _dense(ka[2], d, kv * hd, dtype),
            "o": _dense(ka[3], h * hd, d, dtype),
            "up": _dense(km[0], d, ff, dtype),
            "down": _dense(km[1], ff, d, dtype),
            "gate": _dense(km[2], d, ff, dtype)}


@functools.partial(jax.jit, static_argnames=("vocab", "d", "dtype", "tied"))
def _outer_weights(key, vocab, d, dtype, tied):
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    embed = _as_served(jax.random.normal(k_embed, (vocab, d), jnp.float32)
                       * 0.02, dtype)
    head = embed.T if tied else _as_served(
        jax.random.normal(k_head, (d, vocab), jnp.float32) * d ** -0.5,
        dtype)
    return embed, head, k_blocks


# -------------------------------------------------------------- arithmetic

def _f8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, low):
    """x [..., k] @ w [k, n]; ``low`` rounds both operands to float8."""
    if low:
        x, w = _f8(x, -1), _f8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, low):
    """Causal attention.  q [B, S, H, D]; k, v [B, S, KV, D]."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    if low:
        q, k, v = _f8(q, -1), _f8(k, -1), _f8(v, 1)
    n = -(-s // Q_CHUNK)
    qc = jnp.pad(q, ((0, 0), (0, n * Q_CHUNK - s), (0, 0), (0, 0)))
    qc = qc.reshape(b, n, Q_CHUNK, h, d).swapaxes(0, 1)
    kpos = jnp.arange(s)

    def block(args):
        i, qb = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                        precision=HIGHEST) * d ** -0.5
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if low:
            p = _f8(p, -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(n), qc))
    return out.swapaxes(0, 1).reshape(b, n * Q_CHUNK, h, d)[:, :s]


@functools.partial(jax.jit, static_argnames=("shape", "eps", "theta", "low"))
def _layer(x, w, *, shape, eps, theta, low):
    d, h, kv, hd, ff = shape
    b, s, _ = x.shape
    a = _rms(x, eps)
    q = _rope((_mm(a, w["q"], low) + w["bq"]).reshape(b, s, h, hd), theta)
    k = _rope((_mm(a, w["k"], low) + w["bk"]).reshape(b, s, kv, hd), theta)
    v = (_mm(a, w["v"], low) + w["bv"]).reshape(b, s, kv, hd)
    x = x + _mm(_attention(q, k, v, low).reshape(b, s, h * hd), w["o"], low)
    a = _rms(x, eps)
    up = _mm(a, w["up"], low) * jax.nn.silu(_mm(a, w["gate"], low))
    return x + _mm(up, w["down"], low)


@functools.partial(jax.jit, static_argnames=("eps",))
def _read(x, idx, *, eps):
    """Final-normed hidden states at read positions idx [B, R]."""
    return _rms(jnp.take_along_axis(x, idx[..., None], axis=1), eps)


@functools.partial(jax.jit, static_argnames=("vocab",))
def _gaps(h_ref, h_low, head, served, *, vocab):
    """Per read position: best reference logit minus the reference logit
    of the served token, and of the token the control puts first."""
    n = h_ref.shape[1] // HEAD_CHUNK
    live = jnp.arange(head.shape[1]) < vocab

    def block(args):
        hr, hl, tok = args
        ref = jnp.where(live, jnp.einsum("bpd,dv->bpv", hr, head,
                                        precision=HIGHEST), -jnp.inf)
        best = ref.max(-1)
        gap = best - jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]
        if hl is None:
            return gap, gap
        low = jnp.where(live, _mm(hl, head, True), -jnp.inf)
        pick = jnp.argmax(low, -1)[..., None]
        return gap, best - jnp.take_along_axis(ref, pick, -1)[..., 0]

    split = lambda a: a.reshape(a.shape[0], n, HEAD_CHUNK, *a.shape[2:]) \
        .swapaxes(0, 1)  # noqa: E731
    parts = (split(h_ref), None if h_low is None else split(h_low),
             split(served))
    if h_low is None:
        g, _ = jax.lax.map(lambda a: block((a[0], None, a[1])),
                           (parts[0], parts[2]))
        c = None
    else:
        g, c = jax.lax.map(block, parts)
    merge = lambda a: a.swapaxes(0, 1).reshape(a.shape[1], -1)  # noqa: E731
    return merge(g), (None if c is None else merge(c))


# ------------------------------------------------------------------- entry

def score(model: dict, dtype: str, seed: int, rows: list, *, s_pad: int,
          n_read: int, control: bool = False) -> list:
    _check(model)
    d, h, kv = int(model["d_model"]), int(model["n_heads"]), \
        int(model["n_kv_heads"])
    hd = int(model.get("head_dim") or d // h)
    shape = (d, h, kv, hd, int(model["d_ff"]))
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])
    wdt = jnp.dtype(dtype)
    n_read = -(-n_read // HEAD_CHUNK) * HEAD_CHUNK
    b = len(rows)
    toks = np.zeros((b, s_pad), np.int32)
    idx = np.zeros((b, n_read), np.int32)
    srv = np.zeros((b, n_read), np.int32)
    for i, (ctx, first, served) in enumerate(rows):
        toks[i, :len(ctx)] = ctx
        idx[i, :len(served)] = first + np.arange(len(served))
        srv[i, :len(served)] = served
    embed, head, k_blocks = _outer_weights(
        seed_key(seed), padded_vocab(model), d, wdt,
        bool(model.get("tie_embeddings", False)))
    n_layers = int(model["n_layers"])
    keys = jax.random.split(k_blocks, n_layers)
    if model.get("qkv_bias"):
        bias = {n: b.astype(jnp.float32) for n, b in qkv_biases(
            seed_key(seed), n_layers,
            {"q": h * hd, "k": kv * hd, "v": kv * hd}, wdt).items()}
    else:
        bias = {n: jnp.zeros((n_layers, w), jnp.float32)
                for n, w in (("q", h * hd), ("k", kv * hd), ("v", kv * hd))}
    x = embed[jnp.asarray(toks)]
    xl = _f8(embed, -1)[jnp.asarray(toks)] if control else None
    for i in range(n_layers):
        w = _layer_weights(keys[i], shape, wdt)
        w.update({"b" + n: b[i] for n, b in bias.items()})
        x = _layer(x, w, shape=shape, eps=eps, theta=theta, low=False)
        if control:
            xl = _layer(xl, w, shape=shape, eps=eps, theta=theta, low=True)
        del w
    ji = jnp.asarray(idx)
    hr = _read(x, ji, eps=eps)
    hl = _read(xl, ji, eps=eps) if control else None
    g, c = _gaps(hr, hl, head, jnp.asarray(srv),
                 vocab=int(model["vocab_size"]))
    g = np.asarray(g)
    c = None if c is None else np.asarray(c)
    return [(g[i, :len(r[2])], None if c is None else c[i, :len(r[2])])
            for i, r in enumerate(rows)]
