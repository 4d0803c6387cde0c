"""Unified model API: family dispatch for init / train-loss / prefill /
decode, plus ``input_specs`` — the ShapeDtypeStruct stand-ins that the
multi-pod dry-run lowers against (weak-type-correct, shardable, no device
allocation).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec as E
from repro.models import transformer as T


def init_params(cfg: ModelConfig, key, dtype=None):
    if cfg.is_encdec:
        return E.init_params(cfg, key, dtype)
    return T.init_params(cfg, key, dtype)


def loss_fn(cfg: ModelConfig, params, batch, *, plan=None):
    if cfg.is_encdec:
        return E.encdec_loss(cfg, params, batch, plan=plan)
    return T.lm_loss(cfg, params, batch, plan=plan)


def prefill(cfg: ModelConfig, params, batch, *, plan=None, cache_len: int,
            kv_len=None, prefix_kv=None):
    """batch: {tokens} (+ frames/embeds for stub frontends).  ``prefix_kv``
    (a stacked K/V tree of an already-computed prompt prefix) requests
    continuation prefill of the uncached suffix — see T.lm_prefill."""
    if cfg.is_encdec:
        if prefix_kv is not None:
            raise NotImplementedError(
                "prefix-continuation prefill: enc-dec uses cross caches")
        return E.encdec_prefill(cfg, params, batch["frames"], batch["tokens"],
                                plan=plan, cache_len=cache_len, kv_len=kv_len)
    return T.lm_prefill(cfg, params, batch["tokens"], plan=plan,
                        cache_len=cache_len, kv_len=kv_len,
                        embeds=batch.get("embeds"), prefix_kv=prefix_kv)


def decode_step(cfg: ModelConfig, params, tokens, cache, kv_len, *, plan=None):
    if cfg.is_encdec:
        return E.encdec_decode_step(cfg, params, tokens, cache, kv_len, plan=plan)
    return T.lm_decode_step(cfg, params, tokens, cache, kv_len, plan=plan)


# -------------------------------------------------------------- paged decode

def paged_decode_step(cfg: ModelConfig, params, tokens, pools, block_tables,
                      kv_len, *, plan=None):
    """Decode one token per sequence against paged KV pools (block-table
    addressed; see kernels.paged_attention).  tokens [B, 1]; block_tables
    [B, nb] int32; kv_len [B]."""
    if cfg.is_encdec:
        raise NotImplementedError("paged decode: enc-dec uses cross caches")
    return T.lm_paged_decode_step(cfg, params, tokens, pools, block_tables,
                                  kv_len, plan=plan)


def paged_spec_step(cfg: ModelConfig, params, tokens, pools, block_tables,
                    kv_len, blk, off, *, plan=None):
    """Speculative-verification step: score T tokens per sequence (the
    current input token plus T-1 drafts) against paged KV pools in one pass.
    tokens [B, T]; blk/off [B, T] scatter targets for each position's K/V
    (null block where the position is invalid); kv_len [B] history length
    before the window.  Returns (logits [B, T, Vp], new_pools)."""
    if cfg.is_encdec:
        raise NotImplementedError("paged decode: enc-dec uses cross caches")
    return T.lm_paged_spec_step(cfg, params, tokens, pools, block_tables,
                                kv_len, blk, off, plan=plan)


def paged_compatible(cfg: ModelConfig) -> tuple[bool, str]:
    """Whether the architecture's decode cache can live in paged KV blocks:
    every mixer a full-attention GQA layer (no MLA latents, no sliding-window
    ring buffers, no mamba/rwkv recurrent state, no enc-dec cross cache)."""
    if cfg.is_encdec:
        return False, "enc-dec cross-attention cache is not paged"
    if cfg.mla is not None:
        return False, "MLA decodes from the compressed latent cache"
    for spec in cfg.layer_plan():
        if spec.mixer != "attn":
            return False, f"{spec.mixer} state is recurrent, not a KV cache"
        if spec.attn == "window" and cfg.sliding_window:
            return False, "sliding-window layers use the ring cache"
    return True, ""


def init_paged_pools(cfg: ModelConfig, n_blocks: int, block_size: int,
                     dtype=jnp.float32, device=None):
    """Zero-initialized paged K/V pools mirroring the decode-cache tree:
    {"l{i}": {"mixer": {"k": [n_groups, KV, n_blocks, bs, hd], "v": ...}}} —
    the stacked layer-group layout, with the per-sequence (b, s) axes
    replaced by the physical (n_blocks, block_size) pool axes shared by
    every sequence.  The decode step carries each stacked pool through its
    layer scan as one buffer, updated in place, and the paged kernel reads
    it at the layer's index.  Head-major, so one (layer, head, block) tile
    is a contiguous [bs, hd] slab the paged kernel DMAs whole.  ``device``
    commits the pools to that device (default: JAX's default device)."""
    ok, why = paged_compatible(cfg)
    if not ok:
        raise ValueError(f"{cfg.name}: {why}")
    from repro.models.transformer import group_period
    period = group_period(cfg)
    n_groups = cfg.n_layers // period
    kv, hd, dv = cfg.n_kv_heads, cfg.head_dim_eff, cfg.v_head_dim_eff
    pools = {}
    for i in range(period):
        pools[f"l{i}"] = {"mixer": {
            "k": jnp.zeros((n_groups, kv, n_blocks, block_size, hd), dtype,
                           device=device),
            "v": jnp.zeros((n_groups, kv, n_blocks, block_size, dv), dtype,
                           device=device),
        }}
    return pools


# ----------------------------------------------------------------- dry-run IO

def _frames_len(cfg: ModelConfig, shape: ShapeConfig) -> int:
    # whisper stub: prefill/train feed seq_len frames; decode uses the fixed
    # cross_kv_len memory
    return shape.seq_len if shape.kind != "decode" else cfg.cross_kv_len


def _dec_prompt_len(cfg: ModelConfig, shape: ShapeConfig) -> int:
    # enc-dec prefill: decoder prompt = seq_len/8 (DESIGN.md §5)
    return max(shape.seq_len // 8, 8)


def input_specs(cfg: ModelConfig, shape: ShapeConfig, *, dtype="bfloat16"):
    """ShapeDtypeStructs for every model input of the (arch × shape) cell.

    train  -> {tokens, labels, mask} (+frames/embeds)
    prefill-> {tokens} (+frames/embeds) and kv_len
    decode -> tokens [B,1], cache tree, kv_len
    """
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f = jnp.dtype(dtype)
    tok = jax.ShapeDtypeStruct((b, s), i32)
    if shape.kind == "train":
        batch = {"tokens": tok, "labels": tok,
                 "mask": jax.ShapeDtypeStruct((b, s), jnp.float32)}
        if cfg.is_encdec:
            batch["frames"] = jax.ShapeDtypeStruct((b, _frames_len(cfg, shape), cfg.d_model), f)
        if cfg.frontend == "vision_stub":
            batch["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), f)
        return {"batch": batch}
    if shape.kind == "prefill":
        if cfg.is_encdec:
            batch = {"frames": jax.ShapeDtypeStruct((b, _frames_len(cfg, shape), cfg.d_model), f),
                     "tokens": jax.ShapeDtypeStruct((b, _dec_prompt_len(cfg, shape)), i32)}
        elif cfg.frontend == "vision_stub":
            batch = {"tokens": tok,
                     "embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model), f)}
        else:
            batch = {"tokens": tok}
        return {"batch": batch, "kv_len": jax.ShapeDtypeStruct((b,), i32)}
    # decode
    cache = cache_specs(cfg, b, s, dtype=f)
    return {"tokens": jax.ShapeDtypeStruct((b, 1), i32), "cache": cache,
            "kv_len": jax.ShapeDtypeStruct((b,), i32)}


def cache_specs(cfg: ModelConfig, b: int, s_max: int, *, dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree matching the decode cache layout."""
    from repro.models.transformer import group_period
    kv, hd, dv = cfg.n_kv_heads, cfg.head_dim_eff, cfg.v_head_dim_eff

    def attn_entry(spec):
        if cfg.mla is not None:
            m = cfg.mla
            return {"c_kv": jax.ShapeDtypeStruct((b, s_max, m.kv_lora_rank), dtype),
                    "k_rope": jax.ShapeDtypeStruct((b, s_max, m.qk_rope_head_dim), dtype)}
        ln = s_max
        if spec.attn == "window" and cfg.sliding_window and cfg.sliding_window < s_max:
            ln = cfg.sliding_window
        return {"k": jax.ShapeDtypeStruct((b, ln, kv, hd), dtype),
                "v": jax.ShapeDtypeStruct((b, ln, kv, dv), dtype)}

    if cfg.is_encdec:
        nl = cfg.n_layers
        entry = {"self": attn_entry(cfg.layer_plan()[0]),
                 "cross": {"ck": jax.ShapeDtypeStruct((b, cfg.cross_kv_len, kv, hd), dtype),
                           "cv": jax.ShapeDtypeStruct((b, cfg.cross_kv_len, kv, dv), dtype)}}
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((nl,) + x.shape, x.dtype), entry)

    period = group_period(cfg)
    n_groups = cfg.n_layers // period
    specs = cfg.layer_plan()[:period]
    group = {}
    for i, spec in enumerate(specs):
        ent: dict = {}
        if spec.mixer == "attn":
            ent["mixer"] = attn_entry(spec)
        elif spec.mixer == "mamba":
            mc = cfg.mamba
            d_in = mc.expand * cfg.d_model
            ent["mixer"] = {"conv": jax.ShapeDtypeStruct((b, mc.d_conv - 1, d_in), dtype),
                            "ssm": jax.ShapeDtypeStruct((b, d_in, mc.d_state), jnp.float32)}
        else:  # rwkv6
            rc = cfg.rwkv
            h = cfg.d_model // rc.head_size
            ent["mixer"] = {"shift": jax.ShapeDtypeStruct((b, cfg.d_model), dtype),
                            "state": jax.ShapeDtypeStruct(
                                (b, h, rc.head_size, rc.head_size), jnp.float32)}
            ent["cm_shift"] = jax.ShapeDtypeStruct((b, cfg.d_model), dtype)
        group[f"l{i}"] = ent
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n_groups,) + x.shape, x.dtype), group)


def param_specs_struct(cfg: ModelConfig, dtype=None):
    """Parameter ShapeDtypeStructs via eval_shape (no allocation)."""
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), dtype))
