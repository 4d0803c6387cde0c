"""Engine admission (``PagedEngine._admit``): decoded tokens over the
decode slots the steps offered (steps x max_batch), in %, over the traced
waves.  Moves ``tokens_per_s``."""
import served


def read(ctx):
    steps = sum(r.steps for r in ctx.records)
    if not steps:
        return None
    decoded = len(served.decode_contexts(ctx.records))
    return 100.0 * decoded / (steps * ctx.pcfg.max_batch)
