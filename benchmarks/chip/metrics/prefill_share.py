"""Model step, prefill (``api.prefill`` through ``_prefill`` and
``_prefill_suffix``): the engine's ``prefill_s`` (admission and prefill,
host clock) over the traced waves' wall time, in %.  Moves
``itl_p95_ms``."""


def read(ctx):
    wall = sum(r.t1 - r.t0 for r in ctx.records)
    return 100.0 * sum(r.prefill_s for r in ctx.records) / wall \
        if wall > 0 else None
