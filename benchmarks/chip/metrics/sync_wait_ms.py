"""Model step: the mean ``uellm/sync`` span of the traced wave, in ms, the
host blocked on a decode step's tokens while the device runs the step
(``phases.sync_wait_ms``).  None without the program's spans
(``ctx.spans``).  Moves ``tokens_per_s``."""
import phases


def read(ctx):
    program = getattr(ctx, "spans", None)
    return phases.sync_wait_ms(program, ctx.t0, ctx.t1) if program else None
