"""Device: share of the traced window in which no operation ran on the
chip (1 - the union of the device's operation intervals over the window),
in %, from the profiler trace.  Moves ``tokens_per_s``."""


def read(ctx):
    window = (ctx.t1 - ctx.t0) / 1e9
    if window <= 0 or ctx.busy_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / window)
