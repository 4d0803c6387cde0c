"""Kernel ``kernels/flash_attention`` (Pallas causal prefill): the least
time the chip could take for the prefill attention the traced waves needed
(unpadded prompt tokens, cached prefixes read not recomputed,
``counts.flash_prefill``) over the summed device time of the kernel's
events, in %.  Moves ``itl_p95_ms``."""
import counts
import served
import trace_reduce

PATTERN = r"^%flash_attention_pallas[.\d]* = "


def read(ctx):
    if ctx.peaks is None:
        return None
    t = trace_reduce.op_time_s(ctx.ops, PATTERN, ctx.t0, ctx.t1)
    calls = served.prefill_calls(ctx.records, ctx.pcfg.block_size,
                                 ctx.mix.prefix_cache)
    if t <= 0 or not calls:
        return None
    f, b = counts.flash_prefill(ctx.model, calls, ctx.dtype_bytes)
    return 100.0 * counts.roofline_s(f, b, ctx.device_kind) / t
