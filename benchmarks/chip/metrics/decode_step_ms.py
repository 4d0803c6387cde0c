"""Model step, decode (``api.paged_decode_step``, greedy pick and host
sync): the engine's ``decode_s`` over its ``steps``, in ms, over the traced
waves.  Moves ``tokens_per_s``."""


def read(ctx):
    steps = sum(r.steps for r in ctx.records)
    return 1e3 * sum(r.decode_s for r in ctx.records) / steps \
        if steps else None
