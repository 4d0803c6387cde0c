"""Chip smoke: serve qwen2-1.5b at its published widths through
``serve.py`` on a TPU, and check what came out.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # four replicas on four chips vs one replica

One chip: serve 8 alpaca requests (the workload's own prompt lengths,
``--max-new 32``) with the Pallas kernels, then check that every request
produced tokens, that the compiled paged decode step holds a Pallas kernel
(``tpu_custom_call``), and that the Pallas paged-decode kernel agrees with
the XLA path at the served shapes.  ``--chips 4`` runs only the replica
path: shared-prefix traffic routed over four replicas, replica i's params
and KV pool on ``jax.devices()[i]``, compared by ``outputs_digest`` with the
same requests served by one replica in this process.

Everything runs in this one process.  The last line of stdout is
``{"ok": true, "device": {...}}``; a failed check or phase exits non-zero
without printing it, and so does a run that finds no TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-1.5b"
N_REQUESTS = 8
# the fewest shared-prefix requests (11 templates) whose templates the
# prefix_affinity router's rendezvous hash spreads over all 4 replicas
REPLICA_REQUESTS = 66
MAX_NEW = 32
MAX_SEQ_BOUND = 2048       # >= every prompt (alpaca clips at 512) + MAX_NEW
# Pallas vs XLA paged decode, float32 pools of unit-normal values.  The XLA
# side runs at "highest" matmul precision; the kernel's MXU matmuls may
# round f32 operands to bfloat16 (relative 2^-9), which moves a softmax-
# weighted average of unit-normal values by a few 1e-3.  A one-position
# mask or block error moves the kv_len=1 row by O(1).
ATOL = RTOL = 1e-2


class CheckFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


class CompileStats:
    """Counts XLA compilations and sums their seconds through
    jax.monitoring.  A persistent-cache hit counts as a compile whose
    seconds are the cache read.  Tracing and lowering are left out: their
    spans nest (a jitted function traces the jitted functions it calls)."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == self.BACKEND:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes(device):
    """Peak device memory, where the backend reports it (the TPU does)."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def kv_budget(cfg) -> int:
    """Bytes of a pool that holds MAX_BATCH sequences of MAX_SEQ_BOUND
    tokens, so admission never waits on blocks."""
    from repro.launch import serve
    from repro.serving.paged_engine import kv_block_bytes
    per_token = kv_block_bytes(cfg, serve.BLOCK_SIZE) // serve.BLOCK_SIZE
    return serve.MAX_BATCH * MAX_SEQ_BOUND * per_token


def serve_argv(cfg, *extra: str) -> list:
    return ["--no-reduced", "--arch", ARCH,
            "--max-new", str(MAX_NEW), "--kv-budget", str(kv_budget(cfg)),
            *extra]


def decode_step_hlo(jax, engine) -> str:
    """Compiled HLO of the engine's jitted paged decode step at the shapes
    it served."""
    import jax.numpy as jnp
    from repro.models import api
    pc = engine.pcfg
    pools = jax.eval_shape(lambda: api.init_paged_pools(
        engine.cfg, pc.n_blocks, pc.block_size, engine.dtype))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    lowered = engine._decode.lower(
        engine.params, i32(pc.max_batch, 1), pools,
        i32(pc.max_batch, pc.max_blocks), i32(pc.max_batch))
    return lowered.compile().as_text()


def paged_parity(jax, engine) -> float:
    """Max |Pallas - XLA| of one paged decode-attention call at the served
    shapes (pool, block table width, batch, heads), on random data."""
    import numpy as np
    from repro.kernels.paged_attention.paged_attention import \
        paged_decode_attention_pallas
    from repro.kernels.paged_attention.xla import paged_decode_attention_xla
    cfg, pc = engine.cfg, engine.pcfg
    b, nb, bs = pc.max_batch, pc.max_blocks, pc.block_size
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_eff
    rng = np.random.default_rng(0)
    q = rng.standard_normal((b, h, d), np.float32)
    k_pool = rng.standard_normal((kv, pc.n_blocks, bs, d), np.float32)
    v_pool = rng.standard_normal((kv, pc.n_blocks, bs, d), np.float32)
    bt = rng.integers(1, pc.n_blocks, (b, nb), dtype=np.int32)
    kv_len = np.array([1, bs + 1, nb * bs // 2, nb * bs], np.int32)[:b]
    args = [jax.device_put(x, engine.device)
            for x in (q, k_pool, v_pool, bt, kv_len)]
    got = np.asarray(paged_decode_attention_pallas(*args))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(paged_decode_attention_xla(*args))
    check(np.isfinite(got).all(), "Pallas paged decode produced non-finite "
                                  "values")
    np.testing.assert_allclose(got, ref, atol=ATOL, rtol=RTOL)
    return float(np.abs(got - ref).max())


def one_chip(jax) -> None:
    from repro.configs import get_config
    from repro.launch import serve

    cfg = get_config(ARCH)
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv heads, head_dim "
          f"{cfg.head_dim_eff}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; "
          f"params and KV pool float32")
    stats = CompileStats(jax)
    t0 = time.perf_counter()
    res = serve.main(serve_argv(cfg, "--requests", str(N_REQUESTS)))
    wall = time.perf_counter() - t0
    outs = res["outputs"]
    tokens = sum(len(v) for v in outs.values())
    print(f"served {len(outs)} requests, {tokens} tokens, "
          f"outputs_digest={serve._outputs_digest(outs)}")
    print(f"wall {wall:.1f}s = compile {stats.seconds:.1f}s + serve and "
          f"set-up {wall - stats.seconds:.1f}s")
    print(f"compiles: {stats.compiles} (persistent-cache hits "
          f"{stats.cache_hits}, cache dir "
          f"{jax.config.jax_compilation_cache_dir})")
    print(f"peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")
    check(len(outs) == N_REQUESTS,
          f"{len(outs)} of {N_REQUESTS} requests served")
    empty = [rid for rid, toks in outs.items() if not toks]
    check(not empty, f"requests without tokens: {empty}")

    engine = res["engines"][0]
    hlo = decode_step_hlo(jax, engine)
    check("tpu_custom_call" in hlo,
          "compiled paged decode step has no tpu_custom_call")
    print("decode step: tpu_custom_call present")
    err = paged_parity(jax, engine)
    print(f"paged decode parity: max|pallas - xla| = {err:.3g} "
          f"(atol {ATOL}, rtol {RTOL})")


def four_chips(jax) -> None:
    from repro.configs import get_config
    from repro.launch import serve

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    cfg = get_config(ARCH)
    common = serve_argv(cfg, "--workload", "shared-prefix",
                        "--requests", str(REPLICA_REQUESTS))
    res = serve.main(common + ["--replicas", "4",
                               "--router", "prefix_affinity"])
    placed = [e.device for e in res["engines"]]
    print(f"replica devices: {[str(d) for d in placed]}")
    check(len(set(placed)) == 4, f"replicas share devices: {placed}")
    for e in res["engines"]:
        held = set().union(*(x.devices() for x in jax.tree.leaves(e.params)))
        check(held == {e.device}, f"params of a replica on {held}, "
                                  f"expected {e.device}")
    digest4 = serve._outputs_digest(res["outputs"])
    n4 = len(res["outputs"])
    del res
    gc.collect()
    res = serve.main(common + ["--replicas", "1"])
    digest1 = serve._outputs_digest(res["outputs"])
    print(f"outputs_digest: 4 replicas {digest4} ({n4} requests), "
          f"1 replica {digest1} ({len(res['outputs'])} requests)")
    check(n4 == REPLICA_REQUESTS, f"{n4} of {REPLICA_REQUESTS} served")
    check(digest4 == digest1, "4-replica outputs differ from 1 replica")
    for d in devs[:4]:
        print(f"{d}: peak_bytes_in_use {peak_bytes(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {platform!r})",
              file=sys.stderr)
        return 2
    try:
        from repro.kernels.backend import get_backend
        check(get_backend() == "pallas",
              f"kernel backend {get_backend()!r} on the TPU")
        if args.chips == 4:
            four_chips(jax)
        else:
            one_chip(jax)
    except Exception:           # any failed phase or check fails the run
        traceback.print_exc()
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
