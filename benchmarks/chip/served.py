"""What the served requests of some waves imply for the work done, computed
from their prompts and outputs alone (no timing)."""
from __future__ import annotations

import numpy as np


def requests(records: list) -> list:
    """[(request, output tokens)] of every request the waves served."""
    return [(r, rec.outputs[r.rid]) for rec in records for r in rec.requests
            if rec.outputs.get(r.rid)]


def decode_contexts(records: list) -> list:
    """Per decoded token, the positions its query reads: a request with
    prompt P and n outputs decodes tokens 2..n at P + 1 .. P + n - 1 (the
    first token comes from prefill)."""
    out: list = []
    for r, o in requests(records):
        p = len(r.tokens)
        out.extend(range(p + 1, p + len(o)))
    return out


def _hit(prompt: np.ndarray, earlier: list, block: int) -> int:
    """Full blocks of ``prompt`` that an earlier prompt of the wave already
    published (its own full blocks), leaving one token to prefill."""
    cap = (len(prompt) - 1) // block * block
    best = 0
    for e in earlier:
        n = min(cap, len(e) // block * block)
        diff = np.nonzero(prompt[:n] != e[:n])[0]
        same = n if diff.size == 0 else int(diff[0])
        best = max(best, same // block * block)
    return best


def prefill_calls(records: list, block: int, prefix_cache: bool):
    """[(cached prefix, computed tokens)] per request in admission order,
    or None where the prefix-cache hits this implies disagree with the
    engine's own ``prefix_hit_tokens``."""
    calls = []
    for rec in records:
        earlier: list = []
        hits = 0
        for r in rec.requests:
            p = np.asarray(r.tokens)
            h = _hit(p, earlier, block) if prefix_cache else 0
            calls.append((h, len(p) - h))
            hits += h
            earlier.append(p)
        if hits != rec.prefix_hit_tokens:
            return None
    return calls
