"""Kernel ``kernels/paged_attention`` (Pallas paged decode): the least time
the chip could take for the decode attention the traced waves needed (live
K/V lengths only, ``counts.paged_decode``) over the summed device time of
the kernel's events, in %.  Moves ``tokens_per_s``."""
import counts
import served
import trace_reduce

PATTERN = r"^%_paged_window_core[.\d]* = "


def read(ctx):
    if ctx.peaks is None:
        return None
    t = trace_reduce.op_time_s(ctx.ops, PATTERN, ctx.t0, ctx.t1)
    attended = served.decode_contexts(ctx.records)
    if t <= 0 or not attended:
        return None
    f, b = counts.paged_decode(ctx.model, attended, ctx.dtype_bytes)
    return 100.0 * counts.roofline_s(f, b, ctx.device_kind) / t
