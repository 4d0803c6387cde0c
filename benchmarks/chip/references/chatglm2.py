"""Plain reference of ChatGLM2 (``THUDM/chatglm2-6b``: ``config.json`` and
``modeling_chatglm.py``), as its equations state it:

- pre-RMSNorm blocks (``layernorm_epsilon``), no bias but on q/k/v
  (``add_qkv_bias``);
- rotary positions on the first ``kv_channels // 2`` channels of each q
  and k head (``RotaryEmbedding(rotary_dim // 2)``), channel 2i against
  2i + 1 at frequency ``10000 ** (-2i / (kv_channels // 2))``, as
  ``apply_rotary_pos_emb`` computes it; the other half passes through;
- causal softmax attention at scale ``1 / sqrt(kv_channels)`` in which
  query head h reads key/value head ``h // (heads / multi_query_group_num)``
  (the layer-number scaling of ``apply_query_key_layer_scaling`` cancels);
- SwiGLU: ``dense_h_to_4h`` gives [x0 | x1] and the MLP computes
  ``dense_4h_to_h(silu(x0) * x1)``;
- a final RMSNorm (``post_layer_norm``) and an untied output layer.

Departures from the published model: it computes in float32 where the
published weights are float16, and the served weights are bfloat16.

The weight draw (along the served model's key tree, rounded to the served
dtype, with ``reference.qkv_biases``), the blocked attention, the float8
control and the gap reduction are ``references/dense_gqa.py``'s, loaded by
``reference.load``; the arithmetic of a layer is this file's.  Computed at
the highest matmul precision, layer by layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference
from reference import qkv_biases, seed_key

_dg = reference.load("dense_gqa")
SUPPORTED = _dg.SUPPORTED | {"rope_fraction", "rope_interleaved"}


def _check(model: dict) -> None:
    extra = set(model) - SUPPORTED
    if extra:
        raise ValueError(f"chatglm2 reference: unsupported keys {extra}")
    want = {"family": "dense", "rope": "rope", "rope_fraction": 0.5,
            "rope_interleaved": True, "qkv_bias": True, "act": "silu",
            "gated_mlp": True, "norm": "rmsnorm", "tie_embeddings": False}
    for k, v in want.items():
        if model.get(k) != v:
            raise ValueError(f"chatglm2 reference: {k}={model.get(k)!r}, "
                             f"ChatGLM2 has {v!r}")


def _rope(x, base):
    """``apply_rotary_pos_emb`` at positions 0..S-1.  x [B, S, H, D]."""
    b, s, h, d = x.shape
    rot = d // 2                       # RotaryEmbedding(kv_channels // 2)
    theta = 1.0 / (base ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    idx_theta = jnp.arange(s, dtype=jnp.float32)[:, None] * theta
    cos = jnp.cos(idx_theta)[None, :, None]           # [1, S, 1, rot/2]
    sin = jnp.sin(idx_theta)[None, :, None]
    xs = x[..., :rot].reshape(b, s, h, rot // 2, 2)
    out = jnp.stack([xs[..., 0] * cos - xs[..., 1] * sin,
                     xs[..., 1] * cos + xs[..., 0] * sin], -1)
    return jnp.concatenate([out.reshape(b, s, h, rot), x[..., rot:]], -1)


@functools.partial(jax.jit, static_argnames=("shape", "eps", "base", "low"))
def _layer(x, w, *, shape, eps, base, low):
    d, h, kv, hd, ff = shape
    b, s, _ = x.shape
    mm = functools.partial(_dg._mm, low=low)
    a = _dg._rms(x, eps)
    q = _rope((mm(a, w["q"]) + w["bq"]).reshape(b, s, h, hd), base)
    k = _rope((mm(a, w["k"]) + w["bk"]).reshape(b, s, kv, hd), base)
    v = (mm(a, w["v"]) + w["bv"]).reshape(b, s, kv, hd)
    ctx = _dg._attention(q, k, v, low).reshape(b, s, h * hd)
    x = x + mm(ctx, w["o"])
    a = _dg._rms(x, eps)
    return x + mm(jax.nn.silu(mm(a, w["gate"])) * mm(a, w["up"]), w["down"])


def score(model: dict, dtype: str, seed: int, rows: list, *, s_pad: int,
          n_read: int, control: bool = False) -> list:
    """``reference.score``'s contract (see ``reference.py``)."""
    _check(model)
    d, h, kv = int(model["d_model"]), int(model["n_heads"]), \
        int(model["n_kv_heads"])
    hd = int(model.get("head_dim") or d // h)
    shape = (d, h, kv, hd, int(model["d_ff"]))
    eps, base = float(model["norm_eps"]), float(model["rope_theta"])
    wdt = jnp.dtype(dtype)
    n_read = -(-n_read // _dg.HEAD_CHUNK) * _dg.HEAD_CHUNK
    toks = np.zeros((len(rows), s_pad), np.int32)
    idx = np.zeros((len(rows), n_read), np.int32)
    srv = np.zeros((len(rows), n_read), np.int32)
    for i, (ctx, first, served) in enumerate(rows):
        toks[i, :len(ctx)] = ctx
        idx[i, :len(served)] = first + np.arange(len(served))
        srv[i, :len(served)] = served
    embed, head, k_blocks = _dg._outer_weights(
        seed_key(seed), _dg.padded_vocab(model), d, wdt, False)
    n_layers = int(model["n_layers"])
    keys = jax.random.split(k_blocks, n_layers)
    bias = {n: b.astype(jnp.float32) for n, b in qkv_biases(
        seed_key(seed), n_layers,
        {"q": h * hd, "k": kv * hd, "v": kv * hd}, wdt).items()}
    x = embed[jnp.asarray(toks)]
    xl = _dg._f8(embed, -1)[jnp.asarray(toks)] if control else None
    for i in range(n_layers):
        w = _dg._layer_weights(keys[i], shape, wdt)
        w.update({"b" + n: b[i] for n, b in bias.items()})
        x = _layer(x, w, shape=shape, eps=eps, base=base, low=False)
        if control:
            xl = _layer(xl, w, shape=shape, eps=eps, base=base, low=True)
        del w
    ji = jnp.asarray(idx)
    hr = _dg._read(x, ji, eps=eps)
    hl = _dg._read(xl, ji, eps=eps) if control else None
    g, c = _dg._gaps(hr, hl, head, jnp.asarray(srv),
                     vocab=int(model["vocab_size"]))
    g = np.asarray(g)
    c = None if c is None else np.asarray(c)
    return [(g[i, :len(r[2])], None if c is None else c[i, :len(r[2])])
            for i, r in enumerate(rows)]
