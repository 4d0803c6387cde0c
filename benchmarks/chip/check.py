"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished, drawn
from the run seed and always holding the one with the longest output, is
run through the configuration's plain reference over its prompt and served
tokens.  The number compared is the widest gap, over every served token of
the sample, between the reference's best logit and the reference's logit
of the token the engine served.  Greedy decoding in full precision gives 0;
the configuration's ``limits.logit_gap`` sits above what sound runs of the
served precision read and below what the next lower precision reads
(``PERF.md`` gives the readings).  Every request of the window must also
have been served in full: no error and exactly its output length.

With ``control``, the comparison also reads the control: the same
reference computed in float8, at the same positions of the same prompts
and served tokens, and the gap of the token it puts first, held to the same
limit.  The control must come out not correct; the benchmark's own runs do
not read it.
"""
from __future__ import annotations

import numpy as np

import reference


def sample(records: list, seed: int, n: int) -> list:
    """Up to n finished requests: the longest output first, the rest drawn
    from the seed.  Returns [(request, served tokens)]."""
    done = [(r, rec.outputs[r.rid]) for rec in records for r in rec.requests
            if r.rid in rec.outputs and r.rid not in rec.errors
            and rec.outputs[r.rid]]
    if not done:
        return []
    done.sort(key=lambda ro: (-len(ro[1]), ro[0].rid))
    rest = done[1:]
    rng = np.random.default_rng([seed, 0xC4EC])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest and n > 1 else []
    return [done[0]] + [rest[i] for i in sorted(pick)]


def rows_of(picked: list) -> list:
    return [(list(r.tokens) + out[:-1], len(r.tokens) - 1, list(out))
            for r, out in picked]


def logit_gaps(conf: dict, mix, seed: int, picked: list, *,
               control: bool = False) -> list:
    ref = reference.load(conf["reference"])
    return ref.score(conf["model"], conf["dtype"], seed, rows_of(picked),
                     s_pad=mix.max_seq_len, n_read=mix.max_new_tokens,
                     control=control)


def _widest(gaps) -> float:
    return max((float(np.max(g)) for g in gaps), default=float("inf"))


def compare(conf: dict, mix, seed: int, records: list, failed: int, *,
            control: bool = False) -> dict:
    """{short name: {"value", "limit"}} of every number compared, with
    ``control`` also the control's ``control_logit_gap``."""
    picked = sample(records, seed, int(mix.check_requests))
    gaps = logit_gaps(conf, mix, seed, picked, control=control) \
        if picked else []
    limit = float(conf["limits"]["logit_gap"])
    out = {
        "unserved_requests": {"value": failed, "limit": 0},
        "checked_tokens": {"value": sum(len(o) for _, o in picked),
                           "limit": int(mix.check_tokens)},
    }
    if control:
        out["control_logit_gap"] = {"value": _widest(c for _, c in gaps),
                                    "limit": limit}
    out["logit_gap"] = {"value": _widest(g for g, _ in gaps), "limit": limit}
    return out


def passed(checks: dict, gap: str = "logit_gap") -> bool:
    """Every number within its limit; the token count is a floor.  With
    ``gap="control_logit_gap"``, whether the control would pass."""
    c = checks
    return (c["unserved_requests"]["value"] <= c["unserved_requests"]["limit"]
            and c["checked_tokens"]["value"] >= c["checked_tokens"]["limit"]
            and c[gap]["value"] <= c[gap]["limit"])
