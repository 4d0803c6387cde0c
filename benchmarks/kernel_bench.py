"""Kernel micro-benchmarks: wall-µs of the jitted blocked-XLA paths on CPU
(small shapes — the CPU numbers are for regression tracking, not TPU
projection) plus the analytic TPU-projected times from the cost model."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, emit, persist, timeit_stats
from repro.kernels.decode_attention.xla import decode_attention_xla
from repro.kernels.flash_attention.xla import flash_attention_xla
from repro.kernels.paged_attention.xla import (paged_decode_attention_xla,
                                               paged_window_attention_xla)
from repro.kernels.wkv6.xla import wkv6_xla


def run() -> dict:
    rng = np.random.default_rng(0)
    rows = {}

    b, s, h, kv, d = 1, 512, 8, 2, 64
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    f = jax.jit(lambda q, k, v: flash_attention_xla(q, k, v, q_block=128,
                                                    kv_block=128))
    st = timeit_stats(lambda: jax.block_until_ready(f(q, k, v)), n=5)
    us = st["median_us"]
    flops = 4 * b * s * s * h * d * 0.5
    rows["flash_prefill_512"] = {"us": us, "min_us": st["min_us"],
                                 "gflops_cpu": flops / us / 1e3}
    csv_row("kernel_flash_prefill", us,
            f"min_us={st['min_us']:.1f},cpu_gflops={flops/us/1e3:.1f}")

    qd = jnp.asarray(rng.standard_normal((8, h, d)), jnp.float32)
    kd = jnp.asarray(rng.standard_normal((8, 4096, kv, d)), jnp.float32)
    vd = jnp.asarray(rng.standard_normal((8, 4096, kv, d)), jnp.float32)
    kl = jnp.full((8,), 4096, jnp.int32)
    g = jax.jit(lambda q, k, v, l: decode_attention_xla(q, k, v, l))
    st = timeit_stats(lambda: jax.block_until_ready(g(qd, kd, vd, kl)), n=10)
    us = st["median_us"]
    bytes_touched = kd.size * 4 * 2
    rows["decode_4k"] = {"us": us, "min_us": st["min_us"],
                         "gbps_cpu": bytes_touched / us / 1e3}
    csv_row("kernel_decode_4k", us,
            f"min_us={st['min_us']:.1f},"
            f"cpu_gbps={bytes_touched/us/1e3:.1f}")

    # paged decode: same shape class as decode_4k but block-table addressed
    # (8 seqs x 4096 tokens in 16-slot blocks + a null block) — regressions
    # in the paged path were invisible while only the contiguous kernel was
    # benched.  The multi-token window (T=5: one input + 4 drafts) amortizes
    # the pool sweep over T query positions — us_per_tok is the speculative
    # verify's per-position cost vs the single-token baseline.
    bsz, nb_ = 16, 256
    n_pool = 8 * nb_ + 1
    kpp = jnp.asarray(rng.standard_normal((kv, n_pool, bsz, d)), jnp.float32)
    vpp = jnp.asarray(rng.standard_normal((kv, n_pool, bsz, d)), jnp.float32)
    btp = jnp.asarray(
        1 + rng.permutation(n_pool - 1)[:8 * nb_].reshape(8, nb_), jnp.int32)
    klp = jnp.full((8,), nb_ * bsz, jnp.int32)
    pd = jax.jit(lambda q, k, v, bt, l: paged_decode_attention_xla(
        q, k, v, bt, l))
    st = timeit_stats(lambda: jax.block_until_ready(pd(qd, kpp, vpp, btp,
                                                       klp)), n=10)
    us = st["median_us"]
    rows["paged_decode_4k"] = {"us": us, "min_us": st["min_us"],
                               "gbps_cpu": bytes_touched / us / 1e3}
    csv_row("kernel_paged_decode_4k", us,
            f"min_us={st['min_us']:.1f},"
            f"cpu_gbps={bytes_touched/us/1e3:.1f}")

    t_w = 5
    qw = jnp.asarray(rng.standard_normal((8, t_w, h, d)), jnp.float32)
    pw = jax.jit(lambda q, k, v, bt, l: paged_window_attention_xla(
        q, k, v, bt, l))
    stw = timeit_stats(lambda: jax.block_until_ready(
        pw(qw, kpp, vpp, btp, klp - t_w)), n=10)
    usw = stw["median_us"]
    rows["paged_window_4k_t5"] = {"us": usw, "min_us": stw["min_us"],
                                  "us_per_tok": usw / t_w,
                                  "amortization_vs_decode": us * t_w / usw}
    csv_row("kernel_paged_window_4k_t5", usw,
            f"us_per_tok={usw/t_w:.1f},"
            f"amortization={us*t_w/usw:.2f}x")

    r = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32) * 0.5
    kk = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32) * 0.5
    vv = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
    w = jnp.asarray(np.exp(-np.exp(rng.standard_normal((1, 256, 4, 64)))),
                    jnp.float32)
    u = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32) * 0.3
    h_ = jax.jit(lambda *a: wkv6_xla(*a, chunk=32)[0])
    st = timeit_stats(lambda: jax.block_until_ready(h_(r, kk, vv, w, u)), n=5)
    us = st["median_us"]
    rows["wkv6_256"] = {"us": us, "min_us": st["min_us"]}
    csv_row("kernel_wkv6_256", us, f"min_us={st['min_us']:.1f},chunked")

    emit("kernel_bench", rows)
    persist("kernels",
            latency_s=rows["flash_prefill_512"]["us"] / 1e6,
            extra=rows)
    return rows
