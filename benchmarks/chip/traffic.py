"""The one traffic generator.  A mix is a JSON file under ``traffic/`` that
sets its parameters; this module turns it into waves of requests.

Every length is drawn as

    clip(int(base * exp(spread * U) * lognormal(mu, sigma)), min, max)

with U uniform on [0, 1): ``spread = 0`` gives a plain lognormal, and the
Alpaca output shape of the paper's section 5.1 is ``base = 32,
spread = 2.5, sigma = 0.1``.  With ``templates`` set, a prompt is one of
``count`` shared templates of ``length`` tokens followed by a unique suffix
whose length is drawn from ``prompt``.

Steadiness across seeds: wave k (prompt and output lengths, which template
each request uses, and their order, which decides how the wave drains)
comes from the mix's own ``sizes_seed`` and k alone, so every run seed
serves the same work, wave for wave.  The run seed only draws the token
ids.
Every wave's suffixes start with distinct tokens, so a prefix-cache hit is
always exactly the template.  The generator is a copy of the length shapes
of ``repro.data.workload`` (``gen_requests``, ``gen_shared_prefix_requests``)
with the ids drawn over the model's own vocabulary.
"""
from __future__ import annotations

import json
import pathlib
from dataclasses import dataclass

import numpy as np

SLO_S = 1e9            # closed backlog: no deadline is ever near


@dataclass(frozen=True)
class Mix:
    name: str
    wave: int
    prefix_cache: bool
    sizes_seed: int
    max_waves: int
    prompt: dict
    output: dict
    check_requests: int
    check_tokens: int
    kv_tokens: int
    templates: dict | None = None

    @classmethod
    def load(cls, path) -> "Mix":
        d = json.loads(pathlib.Path(path).read_text())
        return cls(name=pathlib.Path(path).stem, wave=int(d["wave"]),
                   prefix_cache=bool(d["prefix_cache"]),
                   sizes_seed=int(d["sizes_seed"]),
                   max_waves=int(d["max_waves"]), prompt=d["prompt"],
                   output=d["output"],
                   check_requests=int(d["check_requests"]),
                   check_tokens=int(d["check_tokens"]),
                   kv_tokens=int(d["kv_tokens"]),
                   templates=d.get("templates"))

    @property
    def template_len(self) -> int:
        return int(self.templates["length"]) if self.templates else 0

    @property
    def max_prompt(self) -> int:
        return self.template_len + int(self.prompt["max"])

    @property
    def max_new_tokens(self) -> int:
        return int(self.output["max"])

    @property
    def max_seq_len(self) -> int:
        """Block-table width: fixed by the clip limits, never by a seed."""
        return self.max_prompt + self.max_new_tokens


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    u = rng.uniform(0.0, 1.0, n)
    x = float(spec["base"]) * np.exp(float(spec.get("spread", 0.0)) * u) \
        * rng.lognormal(float(spec.get("mu", 0.0)), float(spec["sigma"]), n)
    return np.clip(x.astype(np.int64), int(spec["min"]), int(spec["max"]))


def wave_sizes(mix: Mix, k: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(prompt or suffix lengths, output lengths, template index) of wave k,
    in the order they are served; the same for every run seed."""
    rng = np.random.default_rng([mix.sizes_seed, k])
    plen = draw_lengths(rng, mix.prompt, mix.wave)
    olen = draw_lengths(rng, mix.output, mix.wave)
    n_t = int(mix.templates["count"]) if mix.templates else 1
    return plen, olen, np.arange(mix.wave) % n_t


def _request(rid: int, tokens: list, out_len: int):
    from repro.core.types import Request
    return Request(rid=rid, tokens=tokens, input_len=len(tokens), slo=SLO_S,
                   arrival=0.0, true_output_len=int(out_len))


def _templates(mix: Mix, rng: np.random.Generator, vocab: int,
               count: int) -> list:
    return [rng.integers(0, vocab, mix.template_len).tolist()
            for _ in range(count)]


def _suffixes(rng: np.random.Generator, lengths, vocab: int) -> list:
    """Random ids whose first tokens are pairwise distinct."""
    firsts = rng.choice(vocab, size=len(lengths), replace=False)
    out = []
    for f, n in zip(firsts, lengths):
        s = rng.integers(0, vocab, int(n))
        s[0] = f
        out.append(s.tolist())
    return out


def make_waves(mix: Mix, seed: int, vocab: int) -> list:
    """``mix.max_waves`` waves of ``mix.wave`` requests each, for run
    ``seed``: wave k is wave_sizes(mix, k), with ids drawn from the seed."""
    rng = np.random.default_rng([seed, 0x7A11])
    templates = _templates(mix, rng, vocab, int(mix.templates["count"])) \
        if mix.templates else None
    waves = []
    for k in range(mix.max_waves):
        plen, olen, tidx = wave_sizes(mix, k)
        bodies = _suffixes(rng, plen, vocab)
        waves.append([_request(k * mix.wave + i,
                               templates[tidx[i]] + body if templates
                               else body, olen[i])
                      for i, body in enumerate(bodies)])
    return waves


def padded_lengths(lo: int, hi: int, block: int) -> list:
    """Every block-padded length a draw in [lo, hi] can take."""
    first = -(-lo // block) * block
    return list(range(first, -(-hi // block) * block + 1, block))


def warmup_wave(mix: Mix, seed: int, vocab: int, block: int) -> list:
    """One wave that reaches every prefill shape the mix can draw: one
    prompt per block-padded length, and with templates, every suffix length
    behind a cached template as well as every whole-prompt length of a
    miss.  Two output tokens each, so the decode step runs too."""
    rng = np.random.default_rng([seed, 0x3A4])
    lens = padded_lengths(int(mix.prompt["min"]), int(mix.prompt["max"]),
                          block)
    lens = [min(n, int(mix.prompt["max"])) for n in lens]
    if not mix.templates:
        return [_request(i, s, 2)
                for i, s in enumerate(_suffixes(rng, lens, vocab))]
    # misses: a fresh template for each suffix length; then hits: every
    # suffix length behind the first template, which the first miss cached
    temps = _templates(mix, rng, vocab, len(lens))
    bodies = _suffixes(rng, lens + lens, vocab)
    reqs = [_request(i, temps[i] + bodies[i], 2) for i in range(len(lens))]
    reqs += [_request(len(lens) + i, temps[0] + bodies[len(lens) + i], 2)
             for i in range(len(lens))]
    return reqs
