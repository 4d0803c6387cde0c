"""On-chip benchmark of the paged serving path.

  python3 benchmarks/chip/run.py --workload qwen2-1.5b.chat --seed 7 \\
      --seconds 30 --trace 0

Runs one cell of ``BENCHMARK.json`` on the accelerator this process finds,
in this one process: weights and requests from ``--seed``, one warm-up wave
through the served entry (``PagedEngine.run_continuous``) that reaches
every shape the cell's traffic uses, then waves of requests while less
than ``--seconds`` has passed.  ``--trace 0`` reports the cell's
end-to-end metrics.  ``--trace 1`` serves one wave, the first of the
window, under the profiler, and reports its per-layer metrics.  After the
window the served tokens are checked against the configuration's plain
reference; ``--control 1`` also reads the float8 control there (to set
and test a limit; the benchmark's own runs leave it off).

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``; the numbers compared come last, under ``compared``).  No
accelerator, too few chips, or a failed phase: a non-zero exit and no such
line.  JAX's persistent compilation cache lives in ``.jax_cache`` at the
root of the checkout.  Each phase's seconds go to stderr as ``phase`` lines.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from contextlib import contextmanager  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import catalog  # noqa: E402
import check  # noqa: E402
import serving  # noqa: E402
import traffic  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
ANNOTATED = {"_admit": "admit", "_prefill": "prefill",
             "_prefill_suffix": "prefill_suffix",
             "_gather_prefix": "gather_prefix", "_scatter": "scatter",
             "_decode": "decode_dispatch", "_finish": "finish"}


class NoChip(RuntimeError):
    pass


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


@contextmanager
def phase(name: str):
    t = time.perf_counter()
    yield
    print(f"phase {name} {time.perf_counter() - t:.2f} s", file=sys.stderr,
          flush=True)


def setup_jax(chips: int, require_tpu: bool):
    """Finds the chips, then (on a TPU only) points the persistent
    compilation cache at the checkout: nothing is changed where the run is
    refused."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    if require_tpu:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # no eviction: its bookkeeping files fail where a machine sets a
        # size limit for its own cache directory
        jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


class Compiles:
    """XLA compilations seen through jax.monitoring (a persistent-cache hit
    counts too: it still builds an executable)."""

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


def annotate(jax, engine) -> None:
    """Host spans around the engine's calls into each layer, so the trace
    can say what the host did while the device sat idle."""
    for attr, name in ANNOTATED.items():
        fn = getattr(engine, attr, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _name="bench/" + name, **kw):
            with jax.profiler.TraceAnnotation(_name):
                return _fn(*a, **kw)
        setattr(engine, attr, wrapped)


def traced_wave(jax, engine, reqs: list, log_dir: str):
    """One wave under the profiler (Python tracer off)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench/wave"):
            return serving.run_wave(engine, reqs)
    finally:
        jax.profiler.stop_trace()


def log_waves(records: list) -> None:
    """One stderr line per window wave: its tokens, wall seconds, decode
    steps, the host's gap before it, and its widest gap between tokens."""
    prev = records[0].t0
    for k, r in enumerate(records):
        gap = max(r.inter_token_s, default=0.0)
        print(f"wave {k} tokens {r.tokens} seconds {r.t1 - r.t0:.4f} "
              f"steps {r.steps} decode_s {r.decode_s:.4f} "
              f"prefill_s {r.prefill_s:.4f} before_s {r.t0 - prev:.4f} "
              f"widest_gap_ms {gap * 1e3:.2f}", file=sys.stderr)
        prev = r.t1


def per_layer(bench, workload, ctx, here) -> dict:
    out = {}
    for m in catalog.metrics_of(bench, workload, "per_layer"):
        v = catalog.metric(m["name"], here).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def end_to_end(bench, workload, values: dict) -> dict:
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in catalog.metrics_of(bench, workload, "end_to_end")}


def device_info(jax) -> dict:
    dev = jax.devices()[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(peak)}


def run(args, *, require_tpu: bool = True, root: pathlib.Path = ROOT,
        here: pathlib.Path = HERE, engine_hook=None) -> dict:
    """One run of a cell; returns the result object.  ``root`` holds
    ``BENCHMARK.json`` and ``here`` the cell's files; ``engine_hook`` (tests
    only) may alter the engine before the warm-up."""
    bench = catalog.benchmark(root)
    cell = catalog.cell(bench, args.workload)
    with phase("jax_init"):
        jax = setup_jax(int(cell["chips"]), require_tpu)
    conf = catalog.config(cell["config"], here)
    mix = traffic.Mix.load(catalog.mix_path(cell["traffic"], here))
    vocab = int(conf["model"]["vocab_size"])
    seed = args.seed % (1 << 63)
    compiles = Compiles(jax)

    with phase("requests"):
        waves = traffic.make_waves(mix, seed, vocab)
    with phase("weights"):
        engine = serving.build_engine(conf, mix, seed)
    if engine_hook is not None:
        engine_hook(engine)
    with phase("warmup"):
        serving.run_wave(engine, traffic.warmup_wave(
            mix, seed, vocab, engine.pcfg.block_size))
    if args.trace:
        annotate(jax, engine)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    # what set-up made (JAX's own objects, the weights' trees, the mix's
    # requests) stays out of the collector's scans inside the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START

    before = compiles.n
    with phase("window"):
        records = [traced_wave(jax, engine, waves[0], trace_dir)] \
            if args.trace else serving.run_window(engine, waves, args.seconds)
    window_compiles = compiles.n - before
    gc.unfreeze()
    log_waves(records)
    dev = device_info(jax)
    attempted = sum(len(r.requests) for r in records)
    failed = sum(len(serving.served_ok(r)) for r in records)
    pcfg = engine.pcfg
    serving.release(engine)
    del engine

    result = {"correct": False, "attempted": attempted, "failed": failed}
    if args.trace:
        import counts
        import trace_reduce
        with phase("trace_load"):
            devices, host = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        wave = [s for s in host if s.name == "wave"]
        t0, t1 = wave[0].start_ns, wave[0].end_ns
        chips = list(devices.values())
        busy = sum(trace_reduce.busy_s(ops, t0, t1) for ops in chips) \
            / max(1, len(chips))
        ctx = types.SimpleNamespace(
            conf=conf, model=conf["model"], mix=mix, pcfg=pcfg,
            dtype_bytes=serving.DTYPE_BYTES[conf["dtype"]],
            records=records, ops=chips[0] if chips else [],
            host=host, t0=t0, t1=t1, busy_s=busy,
            window_compiles=window_compiles, device_kind=dev["kind"],
            peaks=counts.peaks(dev["kind"]) if require_tpu else None)
        with phase("trace_reduce"):
            result["metrics"] = per_layer(bench, args.workload, ctx, here)
            result["breakdown"] = trace_reduce.breakdown(ctx.ops, host,
                                                         t0, t1)
        dev.update(busy_s=busy, window_s=(t1 - t0) / 1e9)
        result["device"] = dev
    else:
        result["metrics"] = end_to_end(bench, args.workload, dict(
            serving.window_stats(records), setup_s=setup_s))
        result["device"] = dev
    with phase("check"):
        checks = check.compare(conf, mix, seed, records, failed,
                               control=bool(args.control))
    result["correct"] = check.passed(checks)
    if args.control:
        result["control_correct"] = check.passed(checks, "control_logit_gap")
    result["compared"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    if "control_correct" in result:
        print(f"control correct {result['control_correct']}",
              file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
