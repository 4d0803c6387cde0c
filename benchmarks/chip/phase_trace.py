"""A traced run of one cell, as ``run.py --trace 1`` makes it, whose
reduction also reads the engine's own host phases and device scopes
(``phases``):

  python3 benchmarks/chip/phase_trace.py --workload qwen2-1.5b.chat \\
      --seed 7 --seconds 45

The last line of stdout is run.py's result object with ``host_step_ms``
and ``sync_wait_ms`` among its ``metrics``, ``breakdown.idle_gaps`` named
from the harness's spans and the program's together,
``breakdown.idle_by_span``, ``breakdown.host_phases`` and, where the
device's operations carry their scope paths, ``breakdown.device_scopes``
and ``device_scope_ops``.  run.py's own reduction reads only the
harness's ``bench/`` spans; this script wraps its trace loading and its
per-layer readers to add the program's.  Which stat holds the scope paths
goes to stderr as a ``scope_stat`` line.
"""
from __future__ import annotations

import json
import os
import sys

import run  # sets sys.path to this directory and the program's src/

import catalog  # noqa: E402
import phases  # noqa: E402
import trace_reduce  # noqa: E402

METRICS = ("host_step_ms", "sync_wait_ms")


def traced(args, **run_kw) -> dict:
    """``run.run`` of a traced wave with the program's phases read too;
    ``run_kw`` goes to ``run.run`` (tests: a checkout without a chip)."""
    args.trace = 1
    seen: dict = {}

    def load(log_dir: str):
        from jax.profiler import ProfileData
        path = trace_reduce.find_xplane(log_dir)
        pd = ProfileData.from_file(path)
        seen["spans"] = phases.spans(pd)
        seen["stats"] = phases.op_stats(path)
        return trace_reduce.from_profile(pd)

    def per_layer(bench, workload, ctx, here):
        ctx.spans = seen["spans"]
        out = base_per_layer(bench, workload, ctx, here)
        for name in METRICS:
            v = catalog.metric(name, here).read(ctx)
            if v is not None:
                out[name] = {"value": float(v), "unit": "ms"}
        seen["ctx"] = ctx
        return out

    base_load, base_per_layer = trace_reduce.load, run.per_layer
    trace_reduce.load, run.per_layer = load, per_layer
    try:
        result = run.run(args, **run_kw)
    finally:
        trace_reduce.load, run.per_layer = base_load, base_per_layer
    ctx, stats = seen["ctx"], seen["stats"]
    b = phases.breakdown(ctx.ops, ctx.host, ctx.spans, ctx.t0, ctx.t1)
    b.update(phases.device_scopes(ctx.ops, stats, ctx.t0, ctx.t1) or {})
    result["breakdown"] = b
    print(f"scope_stat {phases.scope_stat(stats)} ops {len(ctx.ops)} "
          f"with_stats {sum(o.name in stats for o in ctx.ops)} stat_names "
          f"{sorted({k for s in stats.values() for k in s})}",
          file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    try:
        result = traced(run.parse(argv))
    except run.NoChip as e:
        print(f"phase_trace.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
