"""Engine (``PagedEngine.run_continuous``): the host's serial time per
decode step, in ms: the mean over the traced wave's ``uellm/iteration``
spans that hold a ``uellm/sync`` of the iteration less what its ``sync``
and ``prefill`` spans cover (``phases.host_step_ms``).  None without the
program's spans (``ctx.spans``).  Moves ``tokens_per_s``."""
import phases


def read(ctx):
    program = getattr(ctx, "spans", None)
    return phases.host_step_ms(program, ctx.t0, ctx.t1) if program else None
