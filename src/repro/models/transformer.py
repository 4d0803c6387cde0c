"""Decoder-only LM assembly.

Layers are grouped by the structural repeat period (lcm of the hybrid
attention period, MoE period, window pattern) and stacked, so the stack is a
single lax.scan over groups — MaxText-style: compile time and HLO size stay
O(period), not O(n_layers), and remat applies per scanned group.  Hybrids
(Jamba 1:7 mamba:attn, Gemma2 local/global, MoE every-k) are therefore
configuration, not code.

Cache layout (decode): {"blocks": pytree stacked [n_groups, ...]} whose group
entries are keyed "l0".."l{period-1}", mirroring the parameter tree.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mamba_mod
from repro.models import rwkv as rwkv_mod
from repro.models.common import apply_norm, norm_init, softcap
from repro.models.mlp import (channel_mix_apply, channel_mix_init, mlp_apply,
                              mlp_init, token_shift)
from repro.models.moe import moe_apply, moe_init
from repro.obs.trace import device_scope
from repro.sharding.plan import ShardingPlan, batch_spec, constrain, resid_spec


def _lcm(a, b):
    return a * b // math.gcd(a, b)


def group_period(cfg: ModelConfig) -> int:
    p = 1
    if cfg.attn_period:
        p = _lcm(p, cfg.attn_period)
    if cfg.moe is not None:
        p = _lcm(p, cfg.moe.moe_period)
    if cfg.window_pattern:
        p = _lcm(p, cfg.window_pattern)
    assert cfg.n_layers % p == 0, (cfg.name, cfg.n_layers, p)
    return p


# ------------------------------------------------------------------- blocks

def block_init(cfg: ModelConfig, spec: LayerSpec, key, dtype):
    keys = jax.random.split(key, 4)
    p = {"norm1": norm_init(cfg, dtype), "norm2": norm_init(cfg, dtype)}
    if cfg.post_block_norms:
        p["norm1_post"] = norm_init(cfg, dtype)
        p["norm2_post"] = norm_init(cfg, dtype)
    if spec.mixer == "attn":
        p["mixer"] = attn.attn_init(cfg, keys[0], dtype)
    elif spec.mixer == "mamba":
        p["mixer"] = mamba_mod.mamba_init(cfg, keys[0], dtype)
    else:
        p["mixer"] = rwkv_mod.rwkv_init(cfg, keys[0], dtype)
    if spec.mlp == "moe":
        p["mlp"] = moe_init(cfg, keys[1], dtype)
    elif spec.mixer == "rwkv6":
        p["mlp"] = channel_mix_init(cfg, keys[1], dtype)
    else:
        p["mlp"] = mlp_init(cfg, keys[1], dtype)
    return p


def block_apply(cfg: ModelConfig, spec: LayerSpec, p, x, *, positions, plan,
                cache, kv_len, mode: str, cache_len: int, block_tables=None,
                spec_scatter=None, layer=None):
    """Returns (x, new_cache_entry, aux).  When ``block_tables`` is given the
    decode path reads/writes the layer-stacked paged KV pool at group
    ``layer`` instead of a contiguous cache (attention layers only; gated by
    api.paged_compatible), and returns the whole updated pool.
    ``spec_scatter`` ((blk, off) [B, T] target arrays) switches the paged
    decode to the multi-token speculative-verification window."""
    aux = {}
    h = apply_norm(cfg, p["norm1"], x)
    new_cache = {}
    if block_tables is not None and spec.mixer != "attn":
        raise NotImplementedError(
            f"paged decode only supports attention mixers, got {spec.mixer}")
    if spec.mixer == "attn":
        with device_scope("attention"):
            if mode == "decode" and block_tables is not None \
                    and spec_scatter is not None:
                mx, c = attn.attn_paged_spec(cfg, spec, p["mixer"], h,
                                             cache["mixer"], block_tables,
                                             kv_len, layer, *spec_scatter,
                                             plan=plan)
            elif mode == "decode" and block_tables is not None:
                mx, c = attn.attn_paged_decode(cfg, spec, p["mixer"], h,
                                               cache["mixer"], block_tables,
                                               kv_len, layer, plan=plan)
            elif mode == "decode":
                mx, c = attn.attn_decode(cfg, spec, p["mixer"], h,
                                         cache["mixer"], kv_len, plan=plan)
            else:
                # a cache entry in prefill mode is a cached *prefix* K/V to
                # continue from (serving.prefix_cache suffix prefill)
                mx, c = attn.attn_prefill(cfg, spec, p["mixer"], h,
                                          positions=positions, plan=plan,
                                          cache_len=cache_len, kv_len=kv_len,
                                          prefix=(cache or {}).get("mixer"))
    elif spec.mixer == "mamba":
        if mode != "decode" and cache is not None:
            raise NotImplementedError(
                "prefix-continuation prefill: mamba state is recurrent")
        if mode == "decode":
            mx, c = mamba_mod.mamba_decode(cfg, p["mixer"], h, cache["mixer"])
        else:
            mx, c = mamba_mod.mamba_prefill(cfg, p["mixer"], h,
                                            cache_len=cache_len, kv_len=kv_len)
    else:  # rwkv6
        if mode != "decode" and cache is not None:
            raise NotImplementedError(
                "prefix-continuation prefill: rwkv6 state is recurrent")
        if mode == "decode":
            mx, c = rwkv_mod.rwkv_decode(cfg, p["mixer"], h, cache["mixer"])
        else:
            mx, c = rwkv_mod.rwkv_prefill(cfg, p["mixer"], h,
                                          cache_len=cache_len, kv_len=kv_len)
    if c is not None:
        new_cache["mixer"] = c
    if cfg.post_block_norms:
        mx = apply_norm(cfg, p["norm1_post"], mx)
    x = x + mx
    x = constrain(x, resid_spec(plan, x), plan)

    with device_scope("mlp"):
        h2 = apply_norm(cfg, p["norm2"], x)
        if spec.mlp == "moe":
            my, moe_aux = moe_apply(cfg, p["mlp"], h2, plan=plan)
            aux.update(moe_aux)
        elif spec.mixer == "rwkv6":
            if mode == "decode":
                shifted = cache["cm_shift"][:, None]
                my = channel_mix_apply(cfg, p["mlp"], h2, shifted)
                new_cache["cm_shift"] = h2[:, 0]
            else:
                my = channel_mix_apply(cfg, p["mlp"], h2, token_shift(h2))
                if cache_len:
                    if kv_len is not None:
                        new_cache["cm_shift"] = jax.vmap(
                            lambda v, i: v[jnp.maximum(i - 1, 0)])(h2, kv_len)
                    else:
                        new_cache["cm_shift"] = h2[:, -1]
        else:
            my = mlp_apply(cfg, p["mlp"], h2)
        if cfg.post_block_norms:
            my = apply_norm(cfg, p["norm2_post"], my)
    x = x + my
    x = constrain(x, resid_spec(plan, x), plan)
    return x, (new_cache if new_cache else None), aux


# -------------------------------------------------------------------- stack

def init_params(cfg: ModelConfig, key, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    plan_specs = cfg.layer_plan()
    period = group_period(cfg)
    n_groups = cfg.n_layers // period
    k_embed, k_blocks, k_head = jax.random.split(key, 3)

    params = {
        "embed": {"w": (jax.random.normal(k_embed, (cfg.padded_vocab, cfg.d_model),
                                          jnp.float32) * 0.02).astype(dtype)},
        "final_norm": norm_init(cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = {"w": (jax.random.normal(
            k_head, (cfg.d_model, cfg.padded_vocab), jnp.float32)
            * cfg.d_model ** -0.5).astype(dtype)}

    def init_group(gk):
        sub = {}
        gkeys = jax.random.split(gk, period)
        for i in range(period):
            sub[f"l{i}"] = block_init(cfg, plan_specs[i], gkeys[i], dtype)
        return sub

    # vmapped over the group keys: the stacked [n_groups, ...] weights are
    # built directly (bitwise equal to stacking per-group inits, without a
    # second copy) and a jitted init compiles one group, not every layer
    params["blocks"] = jax.vmap(init_group)(
        jax.random.split(k_blocks, n_groups))
    return params


def apply_stack(cfg: ModelConfig, params, x, *, positions, plan, mode: str,
                cache=None, kv_len=None, cache_len: int = 0,
                block_tables=None, spec_scatter=None):
    """Run all layer groups.  Returns (x, new_cache, aux).

    Paged decode (``block_tables`` given) carries the stacked pools through
    the scan and scans over the group index instead: each layer updates the
    one pool buffer in place and the paged kernel reads it at its layer, so
    no layer's pool is sliced out and restacked.  Every other mode scans
    the cache along with the weights."""
    period = group_period(cfg)
    specs = cfg.layer_plan()[:period]
    paged = block_tables is not None

    def body(carry, xs):
        xc, aux_sum, pools = carry
        gp, gc = xs
        layer = None
        if paged:                 # xs holds the group index, not its cache
            layer, gc = gc, pools
        new_gc = {}
        for i in range(period):
            c_i = gc[f"l{i}"] if gc is not None else None
            xc, nc, aux = block_apply(
                cfg, specs[i], gp[f"l{i}"], xc, positions=positions, plan=plan,
                cache=c_i, kv_len=kv_len, mode=mode, cache_len=cache_len,
                block_tables=block_tables, spec_scatter=spec_scatter,
                layer=layer)
            if nc is not None:
                new_gc[f"l{i}"] = nc
            if "lb_loss" in aux:
                aux_sum = aux_sum + aux["lb_loss"]
        if paged:
            return (xc, aux_sum, new_gc), None
        return (xc, aux_sum, None), (new_gc if new_gc else None)

    if plan is not None and plan.remat and mode == "train":
        body = jax.checkpoint(body)

    groups = jnp.arange(cfg.n_layers // period, dtype=jnp.int32)
    carry = (x, jnp.zeros((), jnp.float32), cache if paged else None)
    xs = (params["blocks"], groups if paged else cache)
    (x, aux_sum, pools), new_cache = lax.scan(body, carry, xs)
    return x, (pools if paged else new_cache), {"lb_loss": aux_sum}


# ----------------------------------------------------------------- LM heads

def embed_tokens(cfg: ModelConfig, params, tokens):
    x = params["embed"]["w"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def lm_head(cfg: ModelConfig, params, x):
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["w"].T
    else:
        logits = x @ params["head"]["w"]
    return softcap(logits.astype(jnp.float32), cfg.final_softcap)


def default_positions(cfg: ModelConfig, b: int, s: int, offset=0):
    pos = jnp.arange(s)[None, :] + jnp.zeros((b, 1), jnp.int32) + offset
    if cfg.rope == "mrope":
        return jnp.broadcast_to(pos, (3, b, s))        # text mode: t=h=w
    return pos


def lm_forward(cfg: ModelConfig, params, tokens, *, plan=None, embeds=None,
               positions=None):
    """Training/scoring forward: [B, S] -> logits [B, S, Vp]."""
    x = embeds if embeds is not None else embed_tokens(cfg, params, tokens)
    b, s = x.shape[:2]
    if positions is None:
        positions = default_positions(cfg, b, s)
    x = constrain(x, batch_spec(plan, 3), plan)
    x, _, aux = apply_stack(cfg, params, x, positions=positions, plan=plan,
                            mode="train")
    x = apply_norm(cfg, params["final_norm"], x)
    return lm_head(cfg, params, x), aux


def _nll_chunk(cfg: ModelConfig, params, x, labels, mask, plan):
    """Cross entropy for one sequence chunk; logits stay vocab-sharded."""
    logits = lm_head(cfg, params, x)
    if plan is not None and plan.model_axis is not None \
            and cfg.padded_vocab % max(1, _axsz(plan.model_axis)) == 0:
        logits = constrain(logits, P(_bspec(plan), None, plan.model_axis), plan)
    logits = jnp.where(
        jnp.arange(cfg.padded_vocab)[None, None, :] < cfg.vocab_size,
        logits, -1e30)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return (nll * mask).sum()


def _axsz(name):
    from repro.sharding.plan import axis_size
    return axis_size(name)


def _bspec(plan):
    if plan is None or not plan.batch_axes:
        return None
    return plan.batch_axes if len(plan.batch_axes) > 1 else plan.batch_axes[0]


def lm_loss(cfg: ModelConfig, params, batch, *, plan=None,
            loss_chunk: int = 2048):
    """batch: {tokens [B,S], labels [B,S], mask [B,S]} (labels = next token).
    The loss is computed in sequence chunks so the [B, chunk, V] logits
    (vocab-sharded over the model axis) never materialize at full length.
    Returns (loss, metrics)."""
    x = batch.get("embeds")
    if x is None:
        x = embed_tokens(cfg, params, batch["tokens"])
    b, s = x.shape[:2]
    positions = default_positions(cfg, b, s)
    x = constrain(x, batch_spec(plan, 3), plan)
    x, _, aux = apply_stack(cfg, params, x, positions=positions, plan=plan,
                            mode="train")
    x = apply_norm(cfg, params["final_norm"], x)

    labels = batch["labels"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    mask = mask.astype(jnp.float32)

    c = min(loss_chunk, s)
    if s % c != 0:
        c = s                      # irregular small shapes: single chunk
    nc = s // c
    if nc <= 1:
        total = _nll_chunk(cfg, params, x, labels, mask, plan)
    else:
        resh = lambda v: jnp.moveaxis(v.reshape(b, nc, c, *v.shape[2:]), 1, 0)

        def body(acc, blk):
            xb, lb, mb = blk
            return acc + _nll_chunk(cfg, params, xb, lb, mb, plan), None

        total, _ = lax.scan(body, jnp.zeros((), jnp.float32),
                            (resh(x), resh(labels), resh(mask)))
    loss = total / jnp.maximum(mask.sum(), 1.0)
    if cfg.moe is not None:
        loss = loss + 0.01 * aux["lb_loss"] / max(cfg.n_layers, 1)
    return loss, {"nll": loss, "lb_loss": aux["lb_loss"]}


def lm_prefill(cfg: ModelConfig, params, tokens, *, plan=None, cache_len: int,
               kv_len=None, embeds=None, prefix_kv=None):
    """Prompt processing.  Returns (last_token_logits [B, Vp], cache).

    ``prefix_kv`` (stacked {"l{i}": {"mixer": {"k": [n_groups, B, P, KV, hd],
    "v": ...}}}, mirroring the decode-cache tree) switches to continuation
    prefill: ``tokens`` holds only the uncached suffix of the prompt, the
    cached prefix K/V is attended through (models.attention.attn_prefill),
    and the returned cache covers the suffix only.  ``kv_len`` then counts
    valid *suffix* tokens."""
    x = embeds if embeds is not None else embed_tokens(cfg, params, tokens)
    b, s = x.shape[:2]
    p_len = 0
    if prefix_kv is not None:
        p_len = jax.tree.leaves(prefix_kv)[0].shape[2]
    positions = default_positions(cfg, b, s, offset=p_len)
    x = constrain(x, batch_spec(plan, 3), plan)
    x, cache, _ = apply_stack(cfg, params, x, positions=positions, plan=plan,
                              mode="prefill", kv_len=kv_len, cache_len=cache_len,
                              cache=prefix_kv)
    x = apply_norm(cfg, params["final_norm"], x)
    if kv_len is not None:
        last = jax.vmap(lambda v, i: v[jnp.maximum(i - 1, 0)])(x, kv_len)
    else:
        last = x[:, -1]
    return lm_head(cfg, params, last), cache


def lm_decode_step(cfg: ModelConfig, params, tokens, cache, kv_len, *, plan=None):
    """One decode step.  tokens [B, 1]; kv_len [B] = current lengths.
    Returns (logits [B, Vp], new_cache)."""
    x = embed_tokens(cfg, params, tokens)
    x, new_cache, _ = apply_stack(cfg, params, x, positions=None, plan=plan,
                                  mode="decode", cache=cache, kv_len=kv_len)
    x = apply_norm(cfg, params["final_norm"], x)
    return lm_head(cfg, params, x[:, 0]), new_cache


def lm_paged_decode_step(cfg: ModelConfig, params, tokens, pools,
                         block_tables, kv_len, *, plan=None):
    """One decode step against paged KV pools.  tokens [B, 1]; pools: the
    stacked layer-group tree from api.init_paged_pools; block_tables [B, nb];
    kv_len [B].  Returns (logits [B, Vp], new_pools)."""
    x = embed_tokens(cfg, params, tokens)
    x, new_pools, _ = apply_stack(cfg, params, x, positions=None, plan=plan,
                                  mode="decode", cache=pools, kv_len=kv_len,
                                  block_tables=block_tables)
    with device_scope("head"):
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_head(cfg, params, x[:, 0])
    return logits, new_pools


def lm_paged_spec_step(cfg: ModelConfig, params, tokens, pools, block_tables,
                       kv_len, blk, off, *, plan=None):
    """Multi-token (speculative-verification) decode step against paged KV
    pools.  tokens [B, T] = current input token + T-1 draft tokens; kv_len
    [B] history *before* the window; blk/off [B, T] per-position scatter
    targets (engine-computed; null block where invalid).  Returns
    (logits [B, T, Vp], new_pools) — logits[:, t] scores the token *after*
    window position t, so the greedy acceptance walk reads them in order."""
    x = embed_tokens(cfg, params, tokens)
    x, new_pools, _ = apply_stack(cfg, params, x, positions=None, plan=plan,
                                  mode="decode", cache=pools, kv_len=kv_len,
                                  block_tables=block_tables,
                                  spec_scatter=(blk, off))
    with device_scope("head"):
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_head(cfg, params, x)
    return logits, new_pools
