"""Operations and bytes the algorithms need, computed from shapes and the
live lengths of the served requests, and the chips' published peaks.

A kernel's roofline time is the larger of operations over peak FLOP/s and
bytes over peak bytes/s.  Counts cover what the algorithm must do for the
tokens that were really there: block padding, idle batch slots and masked
positions do not count.
"""
from __future__ import annotations

# device_kind -> peaks.  Source: Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       "to counts.PEAKS with its source") from None


def roofline_s(flops: float, nbytes: float, device_kind: str) -> float:
    p = peaks(device_kind)
    return max(flops / p["flops"], nbytes / p["bytes_per_s"])


def _dims(m: dict) -> tuple:
    d, h, kv = int(m["d_model"]), int(m["n_heads"]), int(m["n_kv_heads"])
    hd = int(m.get("head_dim") or d // h)
    return d, h, kv, hd, int(m["n_layers"])


def paged_decode(m: dict, attended: list, dtype_bytes: int) -> tuple:
    """Paged decode attention over all layers.  ``attended`` holds, per
    decoded token, the number of cache positions its query reads (the
    sequence so far, itself included).  Returns (flops, bytes): QK^T and PV
    per position and head; the live K/V read once per kv head, the query
    read and the output written."""
    d, h, kv, hd, nl = _dims(m)
    n_pos = float(sum(attended))
    n_tok = len(attended)
    flops = 4.0 * h * hd * n_pos * nl
    nbytes = (2.0 * kv * hd * n_pos + 2.0 * h * hd * n_tok) \
        * dtype_bytes * nl
    return flops, nbytes


def flash_prefill(m: dict, prompts: list, dtype_bytes: int) -> tuple:
    """Causal flash attention of prefill over all layers.  ``prompts``
    holds (cached_prefix, computed) token counts per prefill call: each of
    the ``computed`` queries reads the prefix and the computed tokens up to
    itself.  Returns (flops, bytes)."""
    d, h, kv, hd, nl = _dims(m)
    pairs = 0.0
    kv_tok = q_tok = 0.0
    for p, s in prompts:
        pairs += s * p + s * (s + 1) / 2.0
        kv_tok += p + s
        q_tok += s
    flops = 4.0 * h * hd * pairs * nl
    nbytes = (2.0 * kv * hd * kv_tok + 2.0 * h * hd * q_tok) \
        * dtype_bytes * nl
    return flops, nbytes


def layer_params(m: dict) -> int:
    """Weights of one layer a token is multiplied by: the attention
    projections and the gated MLP."""
    d, h, kv, hd, nl = _dims(m)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * int(m["d_ff"])


def model_flops(m: dict, contexts: list, head_tokens: int) -> float:
    """Model FLOPs of tokens processed at the given context lengths (the
    positions each token's query reads): 2 per layer weight and token,
    attention, and the output head for the ``head_tokens`` whose logits are
    needed (the embedding lookup does no arithmetic)."""
    d, h, kv, hd, nl = _dims(m)
    return 2.0 * layer_params(m) * nl * len(contexts) \
        + 2.0 * d * int(m["vocab_size"]) * head_tokens \
        + 4.0 * h * hd * nl * float(sum(contexts))
