"""The system under test: the program's paged engine, built as
``serve.py --paged`` builds it, and the waves that drive it.

Only this module and the per-layer readers touch the program.  Its engine
knobs (``serve.MAX_BATCH``, ``serve.BLOCK_SIZE``) are read from the
program, so a later change to batching is measured and not hidden.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from reference import qkv_biases, seed_key

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass
class WaveRecord:
    """What one ``run_continuous`` call served, read from its result."""
    requests: list
    outputs: dict
    t0: float
    t1: float
    steps: int
    prefill_s: float
    decode_s: float
    prefill_tokens: int
    prefix_hit_tokens: int
    inter_token_s: list = field(default_factory=list)
    errors: dict = field(default_factory=dict)

    @classmethod
    def of(cls, reqs, res, t0: float, t1: float) -> "WaveRecord":
        return cls(requests=reqs, outputs=res.outputs, t0=t0, t1=t1,
                   steps=res.steps, prefill_s=res.prefill_s,
                   decode_s=res.decode_s, prefill_tokens=res.prefill_tokens,
                   prefix_hit_tokens=res.prefix_hit_tokens,
                   inter_token_s=list(res.inter_token_s),
                   errors=dict(res.errors))

    @property
    def tokens(self) -> int:
        return sum(len(v) for v in self.outputs.values())


def model_config(conf: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(**conf["model"])


def with_qkv_biases(cfg, params, key, dtype):
    """The program's weights with the q/k/v biases of
    ``reference.qkv_biases`` in place of its zeros (layers stacked one to a
    group, as a dense decoder's are)."""
    if not cfg.qkv_bias:
        return params
    blocks = params["blocks"]
    if set(blocks) != {"l0"}:
        raise ValueError("q/k/v biases: expected one layer per group, got "
                         f"{sorted(blocks)}")
    mixer = blocks["l0"]["mixer"]
    bias = qkv_biases(key, cfg.n_layers, {n: mixer[n]["b"].shape[-1]
                                          for n in ("q", "k", "v")}, dtype)
    for n, b in bias.items():
        mixer[n]["b"] = b.reshape(mixer[n]["b"].shape)
    return params


def build_engine(conf: dict, mix, seed: int):
    """Weights from ``seed`` in one jitted call on the device, in the dtype
    they are served in (q/k/v biases from ``with_qkv_biases``), and the
    engine sized as ``serve.py --paged`` sizes
    it, with the cell's block-table width and decode budget, and a KV pool
    of the mix's ``kv_tokens``."""
    import jax
    import jax.numpy as jnp
    from repro.launch import serve
    from repro.models import api
    from repro.serving.paged_engine import (PagedEngine, PagedEngineConfig,
                                            kv_block_bytes)

    cfg = model_config(conf)
    dtype = jnp.dtype(conf["dtype"])
    params = jax.jit(lambda k: with_qkv_biases(
        cfg, api.init_params(cfg, k, dtype), k, dtype))(seed_key(seed))
    nbytes = DTYPE_BYTES[conf["dtype"]]
    per_token = kv_block_bytes(cfg, serve.BLOCK_SIZE, nbytes) \
        // serve.BLOCK_SIZE
    pcfg = PagedEngineConfig.from_memory_budget(
        cfg, float(mix.kv_tokens * per_token), dtype_bytes=nbytes,
        max_batch=serve.MAX_BATCH, block_size=serve.BLOCK_SIZE,
        max_seq_len=-(-mix.max_seq_len // serve.BLOCK_SIZE)
        * serve.BLOCK_SIZE,
        max_new_tokens=mix.max_new_tokens, prefix_cache=mix.prefix_cache)
    return PagedEngine(cfg, params, pcfg, dtype=dtype)


def run_wave(engine, reqs: list) -> WaveRecord:
    t0 = time.perf_counter()
    res = engine.run_continuous(reqs)
    return WaveRecord.of(reqs, res, t0, time.perf_counter())


def run_window(engine, waves: list, seconds: float) -> list:
    """Waves start while less than ``seconds`` has passed since the first
    began; the last one runs to its end."""
    done: list = []
    start = time.perf_counter()
    for reqs in waves:
        if done and time.perf_counter() - start >= seconds:
            return done
        done.append(run_wave(engine, reqs))
    raise RuntimeError(f"the window outran the mix's {len(waves)} waves: "
                       "raise max_waves in its traffic file")


def release(engine) -> None:
    """Drop the engine's weights so the reference has the device."""
    import gc
    engine.params = None
    gc.collect()


def served_ok(rec: WaveRecord) -> list:
    """Requests of a wave that errored or stopped short of their length."""
    bad = []
    for r in rec.requests:
        out = rec.outputs.get(r.rid)
        if r.rid in rec.errors or out is None \
                or len(out) != r.true_output_len:
            bad.append(r.rid)
    return bad


def window_stats(records: list) -> dict:
    """Output tokens over the window's wall time, and the 95th percentile
    of every gap between a request's consecutive output tokens."""
    window = records[-1].t1 - records[0].t0
    gaps = np.concatenate([np.asarray(r.inter_token_s, float)
                           for r in records])
    return {"tokens_per_s": sum(r.tokens for r in records) / window,
            "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3
            if gaps.size else float("nan")}
