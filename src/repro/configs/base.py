"""Config system: model architectures, input shapes, and hardware constants.

Every assigned architecture is a `ModelConfig`; every assigned input shape is a
`ShapeConfig`.  The dry-run / benchmarks iterate the cross product.  Reduced
("smoke") variants of each architecture preserve the structural features
(family, mixer pattern, MoE/MLA/window flags) at CPU-testable scale.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Literal, Optional, Sequence

Family = Literal["dense", "moe", "hybrid", "ssm", "encdec", "vlm"]
MixerKind = Literal["attn", "mamba", "rwkv6"]
AttnKind = Literal["full", "window"]
MLPKind = Literal["dense", "moe"]


def pad_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int                 # per-expert FFN hidden dim
    n_shared_experts: int = 0
    d_shared: int = 0             # per-shared-expert hidden dim (0 -> d_expert)
    moe_period: int = 1           # MoE MLP every k-th layer (others dense d_ff)
    capacity_factor: float = 1.25
    router_noise: float = 0.0

    @property
    def d_shared_eff(self) -> int:
        return self.d_shared or self.d_expert


@dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 768
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2


@dataclass(frozen=True)
class RWKVConfig:
    head_size: int = 64
    decay_lora: int = 64
    token_shift: bool = True


@dataclass(frozen=True)
class LayerSpec:
    """Structural plan for one transformer block."""
    mixer: MixerKind = "attn"
    attn: AttnKind = "full"
    mlp: MLPKind = "dense"


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                      # 0 -> d_model // n_heads
    # --- attention details ---
    qkv_bias: bool = False
    rope: Literal["rope", "mrope", "none"] = "rope"
    rope_theta: float = 10_000.0
    rope_fraction: float = 1.0             # share of a q/k head rotated
    rope_interleaved: bool = False         # rotate pairs (2i, 2i+1), not halves
    mrope_sections: tuple[int, int, int] = (16, 24, 24)
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    sliding_window: Optional[int] = None   # window size for "window" layers
    window_pattern: int = 0                # >0: layer i is full iff i % pattern == pattern-1
    # --- family extensions ---
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    mamba: Optional[MambaConfig] = None
    rwkv: Optional[RWKVConfig] = None
    attn_period: int = 0                   # hybrid: layer i is attn iff i % attn_period == attn_offset
    attn_offset: int = 0
    # --- enc-dec / multimodal ---
    n_encoder_layers: int = 0              # >0 -> encoder-decoder
    frontend: Literal["none", "audio_stub", "vision_stub"] = "none"
    cross_kv_len: int = 1536               # stubbed encoder-memory length for decode shapes
    # --- misc ---
    norm: Literal["rmsnorm", "layernorm"] = "rmsnorm"
    norm_eps: float = 1e-5
    act: Literal["silu", "gelu"] = "silu"
    gated_mlp: bool = True
    tie_embeddings: bool = False
    scale_embeddings: bool = False     # gemma: embed * sqrt(d_model)
    post_block_norms: bool = False     # gemma2 sandwich norms
    vocab_pad_mult: int = 256
    dtype: str = "bfloat16"
    source: str = ""                       # provenance tag from the assignment

    def __post_init__(self):
        if self.rope_fraction == 1.0 and not self.rope_interleaved:
            return
        if self.rope != "rope" or self.mla is not None:
            raise ValueError(f"{self.name}: rope_fraction / rope_interleaved "
                             "apply to plain rotary heads only")
        r = int(self.rope_fraction * self.head_dim_eff)
        if r <= 0 or r % 2 or r > self.head_dim_eff:
            raise ValueError(f"{self.name}: rope_fraction {self.rope_fraction}"
                             f" rotates {r} of {self.head_dim_eff} channels; "
                             "need an even count, at least 2")

    # ------------------------------------------------------------------
    @property
    def head_dim_eff(self) -> int:
        if self.mla is not None:
            return self.mla.qk_head_dim
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def v_head_dim_eff(self) -> int:
        if self.mla is not None:
            return self.mla.v_head_dim
        return self.head_dim_eff

    @property
    def padded_vocab(self) -> int:
        return pad_to(self.vocab_size, self.vocab_pad_mult)

    @property
    def is_encdec(self) -> bool:
        return self.n_encoder_layers > 0

    @property
    def is_subquadratic(self) -> bool:
        """True when long-context decode state does not grow quadratically-
        problematic: SSM / linear-attn / hybrid families."""
        return self.family in ("ssm", "hybrid")

    @property
    def has_decode(self) -> bool:
        return True   # all assigned archs are (or contain) decoders

    # ------------------------------------------------------------------
    def layer_plan(self) -> tuple[LayerSpec, ...]:
        specs = []
        for i in range(self.n_layers):
            if self.rwkv is not None:
                mixer: MixerKind = "rwkv6"
            elif self.mamba is not None and self.attn_period > 0:
                mixer = "attn" if i % self.attn_period == self.attn_offset else "mamba"
            elif self.mamba is not None:
                mixer = "mamba"
            else:
                mixer = "attn"
            if self.window_pattern > 0 and self.sliding_window:
                attn: AttnKind = "full" if i % self.window_pattern == self.window_pattern - 1 else "window"
            elif self.sliding_window:
                attn = "window"
            else:
                attn = "full"
            mlp: MLPKind = "dense"
            if self.moe is not None and i % self.moe.moe_period == self.moe.moe_period - 1:
                mlp = "moe"
            specs.append(LayerSpec(mixer=mixer, attn=attn, mlp=mlp))
        return tuple(specs)

    # ------------------------------------------------------------------
    def _attn_params(self) -> int:
        d, h, kv, hd = self.d_model, self.n_heads, self.n_kv_heads, self.head_dim_eff
        if self.mla is not None:
            m = self.mla
            p = d * m.q_lora_rank + m.q_lora_rank * h * m.qk_head_dim       # q down/up
            p += d * (m.kv_lora_rank + m.qk_rope_head_dim)                  # kv down + rope k
            p += m.kv_lora_rank * h * (m.qk_nope_head_dim + m.v_head_dim)   # kv up
            p += h * m.v_head_dim * d                                       # o proj
            return p
        return d * h * hd + 2 * d * kv * hd + h * self.v_head_dim_eff * d

    def _mamba_params(self) -> int:
        mc = self.mamba
        d_in = mc.expand * self.d_model
        p = self.d_model * 2 * d_in                      # in_proj (x, z)
        p += d_in * mc.d_conv                            # conv1d
        p += d_in * (mc.d_state * 2 + 1)                 # B, C, dt projections (selective)
        p += d_in * mc.d_state + d_in                    # A_log, D
        p += d_in * self.d_model                         # out_proj
        return p

    def _rwkv_params(self) -> int:
        d = self.d_model
        p = 5 * d * d                                    # r,k,v,g,o projections
        p += 2 * d * self.rwkv.decay_lora                # data-dependent decay lora
        p += 8 * d                                       # token-shift mixes, bonus u
        return p

    def _mlp_params(self, hidden: int) -> int:
        n_mat = 3 if self.gated_mlp else 2
        return n_mat * self.d_model * hidden

    def param_count(self, active_only: bool = False) -> int:
        """Total (or per-token active) parameter count, excluding embeddings
        for the `active` MoE accounting convention used in rooflines."""
        total = self.padded_vocab * self.d_model
        if not self.tie_embeddings:
            total += self.padded_vocab * self.d_model
        total += self.d_model  # final norm
        enc_layers = self.n_encoder_layers
        for spec in self.layer_plan():
            if spec.mixer == "attn":
                total += self._attn_params()
            elif spec.mixer == "mamba":
                total += self._mamba_params()
            else:
                total += self._rwkv_params()
            if spec.mlp == "moe":
                m = self.moe
                n_routed = m.top_k if active_only else m.n_experts
                total += n_routed * self._mlp_params(m.d_expert)
                total += m.n_shared_experts * self._mlp_params(m.d_shared_eff)
            else:
                total += self._mlp_params(self.d_ff)
            total += 2 * self.d_model  # 2 norms
        # encoder stack (attention + dense mlp, plus decoder cross-attn)
        if enc_layers:
            per_enc = self._attn_params() + self._mlp_params(self.d_ff) + 2 * self.d_model
            total += enc_layers * per_enc
            total += self.n_layers * (self._attn_params() + self.d_model)  # cross-attn
        return int(total)

    # ------------------------------------------------------------------
    def kv_cache_bytes(self, batch: int, seq: int, bytes_per: int = 2) -> int:
        """Paper §1 cost model, family-aware (§DESIGN 5)."""
        if self.rwkv is not None:
            per_layer = self.n_heads * self.rwkv.head_size ** 2 + 2 * self.d_model
            return int(self.n_layers * batch * per_layer * bytes_per)
        total = 0
        for spec in self.layer_plan():
            if spec.mixer == "mamba":
                mc = self.mamba
                d_in = mc.expand * self.d_model
                total += batch * (d_in * mc.d_state + d_in * mc.d_conv)
            elif spec.mixer == "attn":
                eff_seq = seq
                if spec.attn == "window" and self.sliding_window:
                    eff_seq = min(seq, self.sliding_window)
                if self.mla is not None:
                    width = self.mla.kv_lora_rank + self.mla.qk_rope_head_dim
                else:
                    width = 2 * self.n_kv_heads * self.head_dim_eff
                total += batch * eff_seq * width
        if self.is_encdec:
            total += (self.n_layers * batch * self.cross_kv_len
                      * 2 * self.n_kv_heads * self.head_dim_eff)
        return int(total * bytes_per)

    # ------------------------------------------------------------------
    def reduced(self, *, n_layers: int | None = None) -> "ModelConfig":
        """Smoke-test-scale config of the same structural family."""
        plan_period = max(self.attn_period, 1)
        nl = n_layers or max(2, min(self.n_layers, 2 * plan_period,
                                    2 * (self.moe.moe_period if self.moe else 1)))
        if self.attn_period:
            nl = max(nl, self.attn_period)  # keep ≥1 attn layer in hybrids
        kv_ratio = max(1, self.n_heads // max(self.n_kv_heads, 1))
        n_heads = 4
        n_kv = max(1, n_heads // min(kv_ratio, n_heads))
        kw = dict(
            name=self.name + "-reduced",
            n_layers=nl, d_model=64, n_heads=n_heads, n_kv_heads=n_kv,
            head_dim=16, d_ff=128, vocab_size=512, vocab_pad_mult=64,
            n_encoder_layers=2 if self.is_encdec else 0,
            cross_kv_len=16 if self.is_encdec else self.cross_kv_len,
            sliding_window=8 if self.sliding_window else None,
        )
        if self.rope == "mrope":
            kw["mrope_sections"] = (2, 3, 3)       # sums to head_dim 16 // 2
        if self.moe is not None:
            kw["moe"] = replace(self.moe, n_experts=4, top_k=min(self.moe.top_k, 2),
                                d_expert=96, d_shared=96,
                                n_shared_experts=min(self.moe.n_shared_experts, 1))
        if self.mla is not None:
            kw["mla"] = MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
            kw["head_dim"] = 0
        if self.mamba is not None:
            kw["mamba"] = replace(self.mamba, d_state=8, d_conv=4, expand=2)
        if self.rwkv is not None:
            kw["rwkv"] = replace(self.rwkv, head_size=16, decay_lora=8)
            kw["n_heads"] = 4
        return replace(self, **kw)


StepKind = Literal["train", "prefill", "decode"]


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: StepKind
    needs_subquadratic: bool = False

    def reduced(self) -> "ShapeConfig":
        return replace(self, name=self.name + "-reduced",
                       seq_len=32, global_batch=2)


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode", needs_subquadratic=True),
}


def cell_is_runnable(model: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether (arch × shape) is a defined cell, and why not when skipped."""
    if shape.needs_subquadratic and not model.is_subquadratic:
        return False, "long_500k skipped: pure full-attention arch (DESIGN.md §5)"
    if shape.kind == "decode" and not model.has_decode:
        return False, "decode skipped: encoder-only arch"
    return True, ""


# ----------------------------------------------------------------------
# Hardware constants (TPU v5e target; paper's GPU cluster for the simulator)
@dataclass(frozen=True)
class HWSpec:
    name: str
    peak_flops: float          # per-chip bf16 FLOP/s
    hbm_bw: float              # bytes/s
    hbm_bytes: float
    ici_bw: float              # bytes/s per link
    dcn_bw: float = 25e9 / 8   # inter-pod, per host


TPU_V5E = HWSpec("tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                 hbm_bytes=16 * 2**30, ici_bw=50e9)
