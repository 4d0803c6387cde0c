import os

# Smoke tests and benches must see the real single CPU device — the 512-way
# placeholder override belongs to launch/dryrun.py ONLY.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def dense_greedy(cfg, params, reqs, max_new):
    """The dense greedy reference the paged engine must match token for
    token: each request alone, ``api.prefill`` on a batch of one, then an
    ``api.decode_step`` loop with ``greedy``, stopping after
    ``min(true_output_len, max_new)`` tokens.  Returns rid -> tokens."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.models import api
    from repro.serving.sampling import greedy

    cache_len = max(len(r.tokens) for r in reqs) + max_new
    prefill = jax.jit(functools.partial(api.prefill, cfg,
                                        cache_len=cache_len))
    decode = jax.jit(functools.partial(api.decode_step, cfg))
    outs = {}
    for r in reqs:
        n = len(r.tokens)
        logits, cache = prefill(params,
                                {"tokens": jnp.asarray([r.tokens], jnp.int32)},
                                kv_len=jnp.asarray([n], jnp.int32))
        toks = []
        stop = min(r.true_output_len, max_new)
        while True:
            nxt = greedy(logits, cfg.vocab_size)
            toks.append(int(nxt[0]))
            if len(toks) >= stop:
                break
            logits, cache = decode(params, nxt[:, None], cache,
                                   jnp.asarray([n + len(toks) - 1], jnp.int32))
        outs[r.rid] = toks
    return outs
