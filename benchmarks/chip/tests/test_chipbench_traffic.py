"""The traffic generator: the same work for every seed, distinct prefix
hits, and a warm-up that reaches every prefill shape."""
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import traffic  # noqa: E402

MIXES = sorted(p.stem for p in (HERE / "traffic").glob("*.json"))


def mix(name):
    return traffic.Mix.load(HERE / "traffic" / f"{name}.json")


def sizes(waves):
    """Each wave's sizes in the order they are served."""
    return [[(len(r.tokens), r.true_output_len) for r in w] for w in waves]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_serves_the_same_sizes(name):
    m = traffic.Mix(**{**mix(name).__dict__, "max_waves": 3})
    a = traffic.make_waves(m, 11, 5000)
    b = traffic.make_waves(m, 2**31 + 12, 5000)
    assert sizes(a) == sizes(b)
    assert [r.tokens for r in a[0]] != [r.tokens for r in b[0]]
    assert all(len(w) == m.wave for w in a)
    assert all(len(r.tokens) <= m.max_prompt for w in a for r in w)
    assert all(1 <= r.true_output_len <= m.max_new_tokens
               for w in a for r in w)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    m = traffic.Mix(**{**mix(name).__dict__, "max_waves": 2})
    a, b = (traffic.make_waves(m, 5, 5000) for _ in range(2))
    assert [[r.tokens for r in w] for w in a] == \
        [[r.tokens for r in w] for w in b]


def test_suffixes_start_with_distinct_tokens():
    m = traffic.Mix(**{**mix("shared-prefix").__dict__, "max_waves": 2})
    for w in traffic.make_waves(m, 3, 65024):
        firsts = [r.tokens[m.template_len] for r in w]
        assert len(set(firsts)) == len(firsts)
        assert len({tuple(r.tokens[:m.template_len]) for r in w}) == \
            m.templates["count"]


@pytest.mark.parametrize("name", MIXES)
def test_warmup_reaches_every_padded_length(name):
    m = mix(name)
    bs = 8
    warm = traffic.warmup_wave(m, 1, 65024, bs)
    pad = lambda n: -(-n // bs) * bs  # noqa: E731
    drawn = set()
    for k in range(20):
        plen, _, _ = traffic.wave_sizes(m, k)
        drawn.update(pad(int(n)) for n in plen)
    lens = {pad(len(r.tokens)) for r in warm}
    if m.templates:
        misses = {m.template_len + n for n in drawn}
        assert misses <= lens
        hits = {pad(len(r.tokens) - m.template_len) for r in warm
                if r.tokens[:m.template_len] == warm[0].tokens[
                    :m.template_len]}
        assert drawn <= hits
    else:
        assert drawn <= lens
    assert all(r.true_output_len == 2 for r in warm)


def test_lengths_follow_the_alpaca_shape():
    rng = np.random.default_rng(0)
    out = traffic.draw_lengths(rng, mix("chat").output, 20000)
    # 32 * e^(2.5U) has mean 32 * (e^2.5 - 1) / 2.5 = 143
    assert 135 < out.mean() < 152
    prompt = traffic.draw_lengths(rng, mix("chat").prompt, 20000)
    assert 85 <= np.median(prompt) <= 95
    assert prompt.min() >= 8 and prompt.max() <= 512
