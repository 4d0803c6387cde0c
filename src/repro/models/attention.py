"""Attention layers: GQA (llama/qwen/gemma/whisper flavours) and MLA
(MiniCPM3 / DeepSeek-style latent attention), with prefill and decode paths.

Distribution strategy (DESIGN.md §6), chosen per call from the ShardingPlan:

* prefill: head-TP via GSPMD when kv-heads divide the model axis; otherwise a
  sequence-parallel shard_map (q sharded along seq, KV gathered, causal offset
  per shard) — this is what makes 40-head / 9-head models run on a 16-wide
  model axis without padding waste.
* decode: flash-decoding — the KV cache is sequence-sharded across
  plan.seq_axes; each shard computes partial softmax stats which are merged
  with a tiny psum (kernels.decode_attention.combine_partials).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import LayerSpec, ModelConfig
from repro.kernels.decode_attention import (
    combine_partials, decode_attention, decode_attention_partial)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_window_attention)
from repro.models.common import apply_dense, apply_mrope, apply_rope, dense_init
from repro.obs.trace import device_scope
from repro.sharding.plan import ShardingPlan, axis_size, constrain, divisible

# --------------------------------------------------------------------- init

def attn_init(cfg: ModelConfig, key, dtype, *, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_eff
    keys = jax.random.split(key, 8)
    if cfg.mla is not None and not cross:
        m = cfg.mla
        return {
            "q_down": dense_init(keys[0], d, m.q_lora_rank, dtype),
            "q_up": dense_init(keys[1], m.q_lora_rank, h * m.qk_head_dim, dtype),
            "kv_down": dense_init(keys[2], d, m.kv_lora_rank + m.qk_rope_head_dim, dtype),
            "kv_up": dense_init(keys[3], m.kv_lora_rank,
                                h * (m.qk_nope_head_dim + m.v_head_dim), dtype),
            "o": dense_init(keys[4], h * m.v_head_dim, d, dtype),
        }
    return {
        "q": dense_init(keys[0], d, h * hd, dtype, bias=cfg.qkv_bias),
        "k": dense_init(keys[1], d, kv * hd, dtype, bias=cfg.qkv_bias),
        "v": dense_init(keys[2], d, kv * cfg.v_head_dim_eff, dtype, bias=cfg.qkv_bias),
        "o": dense_init(keys[3], h * cfg.v_head_dim_eff, d, dtype),
    }


# ----------------------------------------------------------------- helpers

def _qkv(cfg: ModelConfig, p, x, positions):
    """Project + rope.  x: [B, S, d] -> q [B,S,H,hd], k [B,S,KV,hd], v."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_eff
    q = apply_dense(p["q"], x).reshape(b, s, h, hd)
    k = apply_dense(p["k"], x).reshape(b, s, kv, hd)
    v = apply_dense(p["v"], x).reshape(b, s, kv, cfg.v_head_dim_eff)
    with device_scope("rope"):
        if cfg.rope == "rope":
            rot = (cfg.rope_theta, cfg.rope_fraction, cfg.rope_interleaved)
            q = apply_rope(q, positions, *rot)
            k = apply_rope(k, positions, *rot)
        elif cfg.rope == "mrope":
            pos3 = positions if positions.ndim == 3 else jnp.broadcast_to(
                positions, (3,) + positions.shape)
            q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
    return q, k, v


def _mla_qkv(cfg: ModelConfig, p, x, positions):
    """MLA projections.  Returns (q [B,S,H,dn+dr], latent c_kv [B,S,r],
    k_rope [B,S,dr])."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q = apply_dense(p["q_up"], apply_dense(p["q_down"], x))
    q = q.reshape(b, s, h, m.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    with device_scope("rope"):
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    down = apply_dense(p["kv_down"], x)
    c_kv, k_rope = jnp.split(down, [m.kv_lora_rank], axis=-1)
    with device_scope("rope"):
        k_rope = apply_rope(k_rope[:, :, None, :], positions,
                            cfg.rope_theta)[:, :, 0]
    return q, c_kv, k_rope


def _mla_expand(cfg: ModelConfig, p, c_kv, k_rope):
    """Latent -> full K, V.  c_kv [B,S,r], k_rope [B,S,dr]."""
    m = cfg.mla
    b, s, _ = c_kv.shape
    h = cfg.n_heads
    up = apply_dense(p["kv_up"], c_kv).reshape(b, s, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(up, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, m.qk_rope_head_dim))],
        axis=-1)
    return k, v


def _head_spec(plan: Optional[ShardingPlan], n_kv: int):
    """Partition heads over the model axis when divisible, else replicate."""
    if plan is None or plan.model_axis is None:
        return None
    return plan.model_axis if divisible(n_kv, plan.model_axis) else None


def _seq_parallel_prefill(cfg, plan, q, k, v, *, causal, window, softcap):
    """shard_map context-parallel flash attention: q sharded on seq over the
    model axis, K/V replicated (gathered once)."""
    mesh = jax.sharding.get_abstract_mesh()
    ax = plan.model_axis
    batch = plan.batch_axes if len(plan.batch_axes) != 1 else plan.batch_axes[0]
    s_loc = q.shape[1] // axis_size(ax)

    def body(qs, ks, vs):
        idx = jax.lax.axis_index(ax)
        return flash_attention(qs, ks, vs, causal=causal, window=window,
                               softcap=softcap, q_offset=idx * s_loc)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch, ax, None, None), P(batch, None, None, None),
                  P(batch, None, None, None)),
        out_specs=P(batch, ax, None, None),
    )(q, k, v)


def _sharded_decode(cfg, plan, q, k_cache, v_cache, kv_len, *, softcap, window):
    """flash-decoding: KV cache sequence-sharded over plan.seq_axes."""
    mesh = jax.sharding.get_abstract_mesh()
    axes = plan.seq_axes
    batch = plan.batch_axes if len(plan.batch_axes) != 1 else plan.batch_axes[0]
    n_shards = axis_size(axes)
    s_loc = k_cache.shape[1] // n_shards
    ax_tuple = axes if len(axes) > 1 else axes[0]

    def body(qs, ks, vs, kl):
        # flatten shard index across the (possibly multiple) seq axes
        idx = jnp.zeros((), jnp.int32)
        mul = 1
        for a in reversed(axes):
            idx = idx + jax.lax.axis_index(a) * mul
            mul *= axis_size(a)
        start = idx * s_loc
        local_len = jnp.clip(kl - start, 0, s_loc)
        window_lo = None
        if window is not None:
            window_lo = jnp.maximum(kl - window, 0)
        acc, m, l = decode_attention_partial(
            qs, ks, vs, local_len, softcap=softcap,
            window_lo=window_lo, pos_offset=start)
        out = acc
        for a in axes:
            out, m, l = _merge_axis(out, m, l, a)
        return (out / jnp.maximum(l, 1e-30)[..., None]).astype(qs.dtype)

    def _merge_axis(acc, m, l, a):
        m_max = jax.lax.pmax(m, a)
        w = jnp.exp(m - m_max)
        return (jax.lax.psum(acc * w[..., None], a),
                m_max, jax.lax.psum(l * w, a))

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch, None, None), P(batch, ax_tuple, None, None),
                  P(batch, ax_tuple, None, None), P(batch)),
        out_specs=P(batch, None, None),
    )(q, k_cache, v_cache, kv_len)


# ------------------------------------------------------------------- apply

def _run_flash(cfg: ModelConfig, plan, q, k, v, *, causal, window):
    """Pick the prefill attention distribution strategy (DESIGN.md §6):
    head-TP when kv-heads divide the model axis, else sequence-parallel
    shard_map when the seq does, else replicated."""
    s = q.shape[1]
    hs = _head_spec(plan, cfg.n_kv_heads) if cfg.mla is None else \
        _head_spec(plan, cfg.n_heads)
    if hs is not None:
        q = constrain(q, P(_b(plan), None, plan.model_axis, None), plan)
        k = constrain(k, P(_b(plan), None, plan.model_axis, None), plan)
        v = constrain(v, P(_b(plan), None, plan.model_axis, None), plan)
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=cfg.attn_softcap)
    if (plan is not None and plan.model_axis is not None
            and axis_size(plan.model_axis) > 1
            and s % axis_size(plan.model_axis) == 0):
        return _seq_parallel_prefill(cfg, plan, q, k, v, causal=causal,
                                     window=window, softcap=cfg.attn_softcap)
    return flash_attention(q, k, v, causal=causal, window=window,
                           softcap=cfg.attn_softcap)


def attn_prefill(cfg: ModelConfig, spec: LayerSpec, p, x, *, positions,
                 plan: Optional[ShardingPlan], causal: bool = True,
                 cache_len: int = 0, kv_len: Optional[jnp.ndarray] = None,
                 prefix: Optional[dict] = None):
    """Full-sequence attention.  Returns (y, cache_entry or None).
    cache_len > 0 allocates a cache padded to that length; kv_len [B] gives
    per-sequence valid prompt lengths (defaults to the full sequence).

    ``prefix`` ({"k": [B, P, KV, hd], "v": [B, P, KV, dv]}) switches to
    *continuation* prefill: x holds only the uncached suffix of the prompt
    (``positions`` already offset by P); queries attend over the cached
    prefix K/V concatenated with the suffix K/V, causal at absolute
    positions via flash attention's ``q_offset``.  The returned cache entry
    covers the **suffix only** — the prefix K/V already lives in the paged
    pool (serving.prefix_cache decides which blocks are shared).  Plain GQA
    caches only; MLA latents and sliding-window ring buffers are rejected
    (the serving runtime gates on api.paged_compatible)."""
    window = cfg.sliding_window if spec.attn == "window" else None
    if prefix is not None:
        if cfg.mla is not None or window is not None:
            raise NotImplementedError(
                "prefix-continuation prefill needs a plain GQA cache")
        if plan is not None and (plan.model_axis is not None or plan.seq_axes):
            raise NotImplementedError(
                "prefix-continuation prefill: sharded plans not supported")
        q, k, v = _qkv(cfg, p, x, positions)
        b, s, h, _ = q.shape
        k_full = jnp.concatenate([prefix["k"].astype(k.dtype), k], axis=1)
        v_full = jnp.concatenate([prefix["v"].astype(v.dtype), v], axis=1)
        out = flash_attention(q, k_full, v_full, causal=causal,
                              softcap=cfg.attn_softcap,
                              q_offset=prefix["k"].shape[1])
        y = apply_dense(p["o"], out.reshape(b, s, -1))
        cache = None
        if cache_len:
            cache = {"k": _pad_seq(k, cache_len), "v": _pad_seq(v, cache_len)}
        return y, cache
    if cfg.mla is not None:
        q, c_kv, k_rope = _mla_qkv(cfg, p, x, positions)
        k, v = _mla_expand(cfg, p, c_kv, k_rope)
        out = _run_flash(cfg, plan, q, k, v, causal=causal, window=window)
        b, s, h, _ = q.shape
        y = apply_dense(p["o"], out.reshape(b, s, -1))
        cache = None
        if cache_len:
            cache = {"c_kv": _pad_seq(c_kv, cache_len),
                     "k_rope": _pad_seq(k_rope, cache_len)}
        return y, cache

    q, k, v = _qkv(cfg, p, x, positions)
    b, s, h, _ = q.shape
    out = _run_flash(cfg, plan, q, k, v, causal=causal, window=window)
    y = apply_dense(p["o"], out.reshape(b, s, -1))
    cache = None
    if cache_len:
        if window is not None and window < cache_len:
            # sliding-window retention: ring buffer of exactly `window` slots
            # with invariant slot = position % window
            ln = kv_len if kv_len is not None else jnp.full((b,), s, jnp.int32)
            cache = {"k": build_window_cache(k, ln, window),
                     "v": build_window_cache(v, ln, window)}
        else:
            cache = {"k": _pad_seq(k, cache_len), "v": _pad_seq(v, cache_len)}
    return y, cache


def build_window_cache(k: jnp.ndarray, kv_len: jnp.ndarray, w: int) -> jnp.ndarray:
    """Re-layout full-sequence K/V [B, S, ...] into a ring buffer [B, w, ...]
    with slot = position % w, keeping each sequence's most recent w entries
    (kv_len [B] = per-sequence valid length)."""
    b, s = k.shape[:2]

    def one(kb, ln):
        slots = jnp.arange(w)
        # largest position p <= ln-1 with p % w == slot (clamped to >= slot)
        p = slots + w * jnp.maximum((ln - 1 - slots) // w, 0)
        p = jnp.clip(p, 0, s - 1)
        return jnp.take(kb, p, axis=0)

    return jax.vmap(one)(k, kv_len)


def _b(plan):
    if plan is None or not plan.batch_axes:
        return None
    return plan.batch_axes if len(plan.batch_axes) > 1 else plan.batch_axes[0]


def _pad_seq(x, target: int):
    s = x.shape[1]
    if s == target:
        return x
    if s > target:
        return x[:, s - target:]          # keep the most recent entries
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, target - s)
    return jnp.pad(x, pad)


def attn_decode(cfg: ModelConfig, spec: LayerSpec, p, x, cache, kv_len, *,
                plan: Optional[ShardingPlan]):
    """One-token decode.  x: [B, 1, d]; cache entry from attn_prefill;
    kv_len: [B] current lengths (new token position).  Returns (y, cache)."""
    b = x.shape[0]
    window = cfg.sliding_window if spec.attn == "window" else None
    positions = kv_len[:, None]                      # [B, 1]
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(positions, (3, b, 1))

    if cfg.mla is not None:
        m = cfg.mla
        q, c_kv, k_rope = _mla_qkv(cfg, p, x, positions)
        cache = {
            "c_kv": _write_slot(cache["c_kv"], c_kv[:, 0], kv_len),
            "k_rope": _write_slot(cache["k_rope"], k_rope[:, 0], kv_len),
        }
        if getattr(plan, "mla_absorbed", True) if plan is not None else True:
            out = _mla_decode_absorbed(cfg, p, q[:, 0], cache, kv_len + 1)
        else:
            k, v = _mla_expand(cfg, p, cache["c_kv"], cache["k_rope"])
            out = decode_attention(q[:, 0], k, v, kv_len + 1,
                                   softcap=cfg.attn_softcap, window=window)
        y = apply_dense(p["o"], out.reshape(b, -1))
        return y.reshape(b, 1, -1), cache

    q, k, v = _qkv(cfg, p, x, positions)
    use_ring = window is not None and cache["k"].shape[1] <= window
    slot = kv_len % cache["k"].shape[1] if use_ring else kv_len
    head_tp = _head_spec(plan, cfg.n_kv_heads) is not None
    cache = {"k": _write_slot(cache["k"], k[:, 0], slot),
             "v": _write_slot(cache["v"], v[:, 0], slot)}
    if head_tp:
        # head-TP decode: cache + q/k/v are head-sharded over the model axis;
        # attention is fully local per head shard (specs.cache_specs_tree)
        ax = plan.model_axis
        bsp = _b(plan)
        cache = {"k": constrain(cache["k"], P(bsp, None, ax, None), plan),
                 "v": constrain(cache["v"], P(bsp, None, ax, None), plan)}
    if use_ring:
        out = _ring_decode(cfg, q[:, 0], cache, kv_len, window)
    elif plan is not None and plan.seq_axes and not head_tp:
        out = _sharded_decode(cfg, plan, q[:, 0], cache["k"], cache["v"],
                              kv_len + 1, softcap=cfg.attn_softcap, window=window)
    else:
        out = decode_attention(q[:, 0], cache["k"], cache["v"], kv_len + 1,
                               softcap=cfg.attn_softcap, window=window)
    y = apply_dense(p["o"], out.reshape(b, -1))
    return y.reshape(b, 1, -1), cache


def _paged_gate(cfg: ModelConfig, spec: LayerSpec,
                plan: Optional[ShardingPlan]):
    if cfg.mla is not None:
        raise NotImplementedError("paged decode: MLA uses the latent cache")
    if spec.attn == "window" and cfg.sliding_window:
        raise NotImplementedError("paged decode: window layers use ring cache")
    if plan is not None and (plan.model_axis is not None or plan.seq_axes):
        raise NotImplementedError(
            "paged decode: model/seq-sharded plans are not supported yet")


def _write_pages(pool, new, layer, blk, off):
    """pool [L, KV, N, bs, d] <- new [R, KV, d]: row r at
    ``(layer, :, blk[r], off[r], :)``, one ``dynamic_update_slice`` a row,
    in place on the carried pool.  A scatter (``.at[].set``) here makes XLA
    relayout the whole stacked pool around it on every layer."""
    new = new.astype(pool.dtype)
    zero = jnp.zeros((), jnp.int32)
    for r in range(new.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, new[r][None, :, None, None, :],
            (layer, zero, blk[r], off[r], zero))
    return pool


def attn_paged_decode(cfg: ModelConfig, spec: LayerSpec, p, x, pool,
                      block_tables, kv_len, layer, *,
                      plan: Optional[ShardingPlan] = None):
    """One-token decode against a *paged* KV pool.

    x: [B, 1, d]; pool: {"k": [L, KV, N, bs, hd], "v": [L, KV, N, bs, dv]}
    — the layer-stacked head-major physical block pools, of which this
    layer is ``layer`` (int32 scalar); block_tables: [B, nb] int32 (rows
    padded with a valid null block); kv_len: [B] current lengths.  The new
    token's K/V is written into slot ``kv_len`` of its sequence's block
    table in place, then attention reads layer ``layer`` through the table
    (kernels.paged_attention).
    Returns (y, updated pools).  MLA and sliding-window layers keep their
    latent/ring cache paths — the serving runtime gates on api.paged_compatible.
    Sharded decode (head-TP / sequence-sharded pools) is not implemented:
    a plan carrying those axes is rejected rather than silently ignored.
    """
    _paged_gate(cfg, spec, plan)
    b = x.shape[0]
    positions = kv_len[:, None]
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(positions, (3, b, 1))
    q, k, v = _qkv(cfg, p, x, positions)
    bs = pool["k"].shape[3]
    blk = block_tables[jnp.arange(b), kv_len // bs]          # [B] physical ids
    off = kv_len % bs
    with device_scope("kv_write"):
        k_pool = _write_pages(pool["k"], k[:, 0], layer, blk, off)
        v_pool = _write_pages(pool["v"], v[:, 0], layer, blk, off)
    out = paged_decode_attention(q[:, 0], k_pool, v_pool, block_tables,
                                 kv_len + 1, layer, softcap=cfg.attn_softcap)
    y = apply_dense(p["o"], out.reshape(b, -1))
    return y.reshape(b, 1, -1), {"k": k_pool, "v": v_pool}


def attn_paged_spec(cfg: ModelConfig, spec: LayerSpec, p, x, pool,
                    block_tables, kv_len, layer, blk, off, *,
                    plan: Optional[ShardingPlan] = None):
    """Multi-token decode (speculative verification) against a paged pool.

    x: [B, T, d] — the current input token plus T-1 draft tokens per
    sequence; pool and ``layer`` as in ``attn_paged_decode``; kv_len: [B]
    history length *before* the window; blk/off: [B, T] int32 write targets
    for each window position's K/V, computed host-side by the engine from
    its block tables (invalid positions point at the null block, so a slot
    mid-prefill or past its budget never clobbers live blocks).  Each of
    the B·T positions' K/V is written in place, then attention reads
    through the table with causal masking of the window
    (kernels.paged_attention.paged_window_attention).
    Returns (y [B, T, d], updated pools).  Same architecture gates as
    ``attn_paged_decode``."""
    _paged_gate(cfg, spec, plan)
    b, t, _ = x.shape
    positions = kv_len[:, None] + jnp.arange(t)[None, :]
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(positions, (3, b, t))
    q, k, v = _qkv(cfg, p, x, positions)
    blk, off = blk.reshape(-1), off.reshape(-1)
    with device_scope("kv_write"):
        k_pool = _write_pages(pool["k"], k.reshape(b * t, *k.shape[2:]),
                              layer, blk, off)
        v_pool = _write_pages(pool["v"], v.reshape(b * t, *v.shape[2:]),
                              layer, blk, off)
    out = paged_window_attention(q, k_pool, v_pool, block_tables, kv_len,
                                 layer, softcap=cfg.attn_softcap)
    y = apply_dense(p["o"], out.reshape(b, t, -1))
    return y, {"k": k_pool, "v": v_pool}


def _ring_decode(cfg, q, cache, kv_len, window):
    """Decode attention over a ring-buffer window cache (slot = pos % w).
    The query sits at position kv_len; slot s holds position
    kv_len - ((kv_len - s) mod w), masked to the window."""
    b, h, d = q.shape
    k, v = cache["k"], cache["v"]
    w = k.shape[1]
    kv = k.shape[2]
    group = h // kv
    qg = (q.astype(jnp.float32) * (d ** -0.5)).astype(k.dtype).reshape(b, kv, group, d)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                        preferred_element_type=jnp.float32)
    if cfg.attn_softcap is not None:
        logits = cfg.attn_softcap * jnp.tanh(logits / cfg.attn_softcap)
    slots = jnp.arange(w)[None, :]
    pos = kv_len[:, None] - (kv_len[:, None] - slots) % w
    valid = (pos >= 0) & (pos > kv_len[:, None] - window)
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, -1).astype(q.dtype)


def _write_slot(buf, new, idx):
    """buf [B, S, ...] <- new [B, ...] at position idx [B] (per sequence)."""
    def one(b_slice, n, i):
        return jax.lax.dynamic_update_slice_in_dim(b_slice, n[None], i, axis=0)
    return jax.vmap(one)(buf, new, idx)


def _mla_decode_absorbed(cfg: ModelConfig, p, q, cache, kv_len):
    """Matmul-absorbed MLA decode (§Perf hillclimb 1): attention runs in the
    compressed latent space — W_uk is absorbed into the query and W_uv into
    the output, so the per-step latent->K/V expansion (2·S·r·H·(dn+dv) FLOPs
    per layer) disappears.  Identical math to the expanded path:

        score_i = (W_uk^T q_nope)·c_i + q_rope·k_rope_i
        out     = (softmax(score) @ C) @ W_uv

    q: [B, H, dn+dr]; cache c_kv [B, S, r], k_rope [B, S, dr]."""
    m = cfg.mla
    b, h, _ = q.shape
    s = cache["c_kv"].shape[1]
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    # kv_up weight [r, H*(dn+dv)] -> U_k [r, H, dn], U_v [r, H, dv]
    w_up = p["kv_up"]["w"].reshape(m.kv_lora_rank, h,
                                   m.qk_nope_head_dim + m.v_head_dim)
    u_k, u_v = jnp.split(w_up, [m.qk_nope_head_dim], axis=-1)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope.astype(jnp.float32),
                       u_k.astype(jnp.float32))
    scale = m.qk_head_dim ** -0.5
    c = cache["c_kv"]
    kr = cache["k_rope"]
    logits = (jnp.einsum("bhr,bsr->bhs", (q_lat * scale).astype(c.dtype), c,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhd,bsd->bhs",
                           (q_rope.astype(jnp.float32) * scale).astype(kr.dtype),
                           kr, preferred_element_type=jnp.float32))
    mask = jnp.arange(s)[None, :] < kv_len[:, None]
    logits = jnp.where(mask[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", probs.astype(c.dtype), c,
                       preferred_element_type=jnp.float32)
    out = jnp.einsum("bhr,rhd->bhd", o_lat, u_v.astype(jnp.float32))
    return out.astype(q.dtype)


# ------------------------------------------------------------ cross-attention

def cross_attn_prefill(cfg: ModelConfig, p, x, memory, *, plan):
    """Decoder cross-attention over encoder output; returns (y, cache) where
    the cache holds projected K/V of the memory."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_eff
    q = apply_dense(p["q"], x).reshape(b, s, h, hd)
    k = apply_dense(p["k"], memory).reshape(b, memory.shape[1], kv, hd)
    v = apply_dense(p["v"], memory).reshape(b, memory.shape[1], kv, cfg.v_head_dim_eff)
    out = flash_attention(q, k, v, causal=False)
    y = apply_dense(p["o"], out.reshape(b, s, -1))
    return y, {"ck": k, "cv": v}


def cross_attn_decode(cfg: ModelConfig, p, x, cache):
    b = x.shape[0]
    h, hd = cfg.n_heads, cfg.head_dim_eff
    q = apply_dense(p["q"], x).reshape(b, h, hd)
    mem_len = jnp.full((b,), cache["ck"].shape[1], jnp.int32)
    out = decode_attention(q, cache["ck"], cache["cv"], mem_len)
    y = apply_dense(p["o"], out.reshape(b, -1))
    return y.reshape(b, 1, -1)
