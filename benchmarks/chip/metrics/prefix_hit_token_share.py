"""Prefix cache (``serving/prefix_cache.py``): prompt tokens served from
cached blocks over all prompt tokens, in %, over the traced waves.  Only
where the cell turns the prefix cache on.  Moves ``itl_p95_ms``."""
import served


def read(ctx):
    if not ctx.mix.prefix_cache:
        return None
    prompt = sum(len(r.tokens) for r, _ in served.requests(ctx.records))
    hit = sum(r.prefix_hit_tokens for r in ctx.records)
    return 100.0 * hit / prompt if prompt else None
