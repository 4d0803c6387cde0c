"""Device: model FLOPs of every token the traced waves prefilled or decoded
(``counts.model_flops``) over the traced window times the chip's bf16
peak, in %.  Moves ``tokens_per_s``."""
import counts
import served


def read(ctx):
    calls = served.prefill_calls(ctx.records, ctx.pcfg.block_size,
                                 ctx.mix.prefix_cache)
    if ctx.peaks is None or calls is None:
        return None
    ctxs = [p + i + 1 for p, s in calls for i in range(s)]
    dec = served.decode_contexts(ctx.records)
    flops = counts.model_flops(ctx.model, ctxs, head_tokens=len(calls)) \
        + counts.model_flops(ctx.model, dec, head_tokens=len(dec))
    window = (ctx.t1 - ctx.t0) / 1e9
    return 100.0 * flops / (window * ctx.peaks["flops"])
