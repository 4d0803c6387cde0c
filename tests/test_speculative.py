"""Speculative decoding: drafter behavior, engine token-identity across
drafters and mode compositions (chunked prefill, preemption, prefix cache),
rejection rollback, and acceptance-aware pricing in scheduler / simulator /
replica projections."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import dense_greedy
from repro.configs import get_config
from repro.core.scheduler import SchedulerConfig, slo_odbs, spec_speedup
from repro.core.types import Request
from repro.models import api
from repro.serving import (ModelDrafter, NGramDrafter, PagedEngine,
                           PagedEngineConfig)
from repro.serving.simulator import simulate_continuous


@pytest.fixture(scope="module")
def model():
    cfg = get_config("smollm-135m").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def _reqs(cfg, n=6, out_lo=4, out_hi=12, seed=3, rep=True):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        if rep:
            pat = rng.integers(1, cfg.vocab_size, 6).tolist()
            toks = (pat * 4)[:20]
        else:
            toks = rng.integers(1, cfg.vocab_size, 20).tolist()
        reqs.append(Request(rid=i, tokens=toks, input_len=len(toks),
                            slo=60.0, arrival=0.0,
                            true_output_len=int(rng.integers(out_lo, out_hi))))
    return reqs


def _ref(cfg, params, reqs, max_new=12):
    return dense_greedy(cfg, params, reqs, max_new)


# ------------------------------------------------------------------ drafters

def test_ngram_drafter_plain_continuation():
    d = NGramDrafter()
    hist = [1, 2, 3, 4, 5, 9, 9, 1, 2, 3]
    # trailing 3-gram [1,2,3] matched at position 0, continuation 4,5,9,9
    assert d.propose(0, hist, 4) == [4, 5, 9, 9]


def test_ngram_drafter_cyclic_extension():
    d = NGramDrafter()
    hist = [7, 7, 1, 2, 1, 2, 1, 2]
    # period-2 loop: proposals must extend through the loop, not stop at it
    assert d.propose(0, hist, 5) == [1, 2, 1, 2, 1]


def test_ngram_drafter_prefers_longest_ngram():
    d = NGramDrafter(max_ngram=3)
    # 3-gram [1,2,3] -> 8; the 1-gram [3] alone would propose 5 (after pos 4)
    hist = [1, 2, 3, 8, 3, 5, 1, 2, 3]
    assert d.propose(0, hist, 1) == [8]


def test_ngram_drafter_no_match_is_empty():
    d = NGramDrafter()
    assert d.propose(0, [1, 2, 3, 4, 5], 4) == []
    assert d.propose(0, [], 4) == []


def test_model_drafter_self_draft_matches_target(model):
    """A draft model with the *target's own* weights must propose exactly
    the target's greedy continuation (acceptance 1.0 end to end)."""
    cfg, params = model
    reqs = _reqs(cfg, n=4)
    ref = _ref(cfg, params, reqs)
    eng = PagedEngine(cfg, params, PagedEngineConfig(
        max_batch=4, block_size=8, n_blocks=64, max_seq_len=64,
        max_new_tokens=12, spec_tokens=4),
        drafter=ModelDrafter(cfg, params))
    res = eng.run_continuous([copy.copy(r) for r in reqs])
    assert all(res.outputs[r.rid] == ref[r.rid] for r in reqs)
    assert res.acceptance_rate == 1.0
    assert res.drafted_tokens > 0


# --------------------------------------------------------- engine identity

@pytest.mark.parametrize("spec_tokens", [1, 3, 4])
def test_spec_outputs_token_identical(model, spec_tokens):
    cfg, params = model
    reqs = _reqs(cfg)
    ref = _ref(cfg, params, reqs)
    eng = PagedEngine(cfg, params, PagedEngineConfig(
        max_batch=4, block_size=8, n_blocks=64, max_seq_len=64,
        max_new_tokens=12, spec_tokens=spec_tokens))
    res = eng.run_continuous([copy.copy(r) for r in reqs])
    assert all(res.outputs[r.rid] == ref[r.rid] for r in reqs)
    assert res.drafted_tokens >= res.accepted_tokens >= 0


def test_spec_identical_on_adversarial_random_prompts(model):
    """No repetition to exploit: acceptance may be ~0, outputs must still be
    exactly the sequential greedy stream."""
    cfg, params = model
    reqs = _reqs(cfg, rep=False)
    ref = _ref(cfg, params, reqs)
    eng = PagedEngine(cfg, params, PagedEngineConfig(
        max_batch=4, block_size=8, n_blocks=64, max_seq_len=64,
        max_new_tokens=12, spec_tokens=4))
    res = eng.run_continuous([copy.copy(r) for r in reqs])
    assert all(res.outputs[r.rid] == ref[r.rid] for r in reqs)


def test_spec_composes_with_chunked_prefill_preempt_prefix(model):
    """The full PR-2/PR-4 stack under speculation: prefix sharing + COW,
    chunked prefill, lookahead admission, preemption — token-identical."""
    cfg, params = model
    rng = np.random.default_rng(5)
    shared = rng.integers(1, cfg.vocab_size, 16).tolist()
    reqs = []
    for i in range(8):
        toks = shared + (shared[:4] * 3)[:int(rng.integers(4, 12))]
        reqs.append(Request(
            rid=i, tokens=toks, input_len=len(toks),
            slo=1000.0 if i == 0 else float(rng.uniform(0.001, 50)),
            arrival=0.0, true_output_len=int(rng.integers(3, 10))))
    ref = _ref(cfg, params, reqs, max_new=10)
    eng = PagedEngine(cfg, params, PagedEngineConfig(
        max_batch=3, block_size=8, n_blocks=24, max_seq_len=48,
        max_new_tokens=10, spec_tokens=3, prefix_cache=True,
        chunk_tokens=8, preempt=True, admit_lookahead=2))
    res = eng.run_continuous([copy.copy(r) for r in reqs])
    assert all(res.outputs[r.rid] == ref[r.rid] for r in reqs)
    assert res.prefill_chunks > len(reqs)          # chunking engaged
    assert res.prefix_hits > 0                     # sharing engaged


def test_spec_under_forced_preemption(model):
    """Block pressure mid-run with speculation on: the slack resident is
    evicted, recomputed, and everything stays token-identical."""
    cfg, params = model
    rng = np.random.default_rng(11)
    reqs = [Request(rid=0, tokens=[3] * 16, input_len=16, slo=1000.0,
                    arrival=0.0, true_output_len=6),
            Request(rid=1, tokens=rng.integers(1, cfg.vocab_size, 8).tolist(),
                    input_len=8, slo=0.001, arrival=0.0, true_output_len=4)]
    ref = _ref(cfg, params, reqs, max_new=8)
    eng = PagedEngine(cfg, params, PagedEngineConfig(
        max_batch=2, block_size=8, n_blocks=5, max_seq_len=32,
        max_new_tokens=8, chunk_tokens=8, preempt=True, spec_tokens=3))
    res = eng.run_continuous([copy.copy(r) for r in reqs])
    assert res.preemptions >= 1
    assert all(res.outputs[r.rid] == ref[r.rid] for r in reqs)


def test_spec_rejection_rolls_back_blocks(model):
    """An always-wrong drafter forces full rejection every iteration: the
    window's speculative tail blocks must come back (allocator conserves)."""
    cfg, params = model

    class WrongDrafter:
        name = "wrong"

        def propose(self, slot, history, k):
            # vocab-1 is never the greedy pick of this reduced model's
            # outputs in these runs; all drafts rejected
            return [cfg.vocab_size - 1] * k

        def release(self, slot):
            pass

    reqs = _reqs(cfg, n=3, out_lo=6, out_hi=10)
    ref = _ref(cfg, params, reqs)
    eng = PagedEngine(cfg, params, PagedEngineConfig(
        max_batch=3, block_size=4, n_blocks=96, max_seq_len=64,
        max_new_tokens=12, spec_tokens=8), drafter=WrongDrafter())
    res = eng.run_continuous([copy.copy(r) for r in reqs])
    assert all(res.outputs[r.rid] == ref[r.rid] for r in reqs)
    assert res.accepted_tokens == 0
    assert res.drafted_tokens > 0
    assert res.spec_rolled_blocks > 0
    assert res.iterations_per_token >= 0.9 * 1 / 3  # no free lunch


def test_spec_kv_page_counters_count_the_window(model):
    """A verify step's kernel reads the pages holding positions up to the
    end of its whole window (spec_tokens + 1 queries from kv_len), whatever
    a slot's own draft width."""
    cfg, params = model

    class Rejected:
        """Drafts the one token never picked: one token emitted a step."""
        name = "rejected"

        def propose(self, slot, history, k):
            return [cfg.vocab_size - 1] * k

        def release(self, slot):
            pass

    req = _reqs(cfg, n=1)[0]
    req.true_output_len = 6
    eng = PagedEngine(cfg, params, PagedEngineConfig(
        max_batch=1, block_size=4, n_blocks=32, max_seq_len=64,
        max_new_tokens=12, spec_tokens=8), drafter=Rejected())
    res = eng.run_continuous([req])
    # prompt 20; prefill emits token 1, verify steps at kv 20..24 emit 2..6
    assert res.steps == 5
    # pages (kv + 9) / 4 rounded up: 8, 8, 8, 8, 9
    assert res.kv_pages_read == 41
    assert res.kv_pages_table == 5 * 16        # 64 / 4 blocks, one slot


def test_spec_steps_drop_on_draftable_workload(model):
    cfg, params = model
    reqs = _reqs(cfg, n=6, out_lo=8, out_hi=12)
    base = PagedEngine(cfg, params, PagedEngineConfig(
        max_batch=4, block_size=8, n_blocks=64, max_seq_len=64,
        max_new_tokens=12))
    spec = PagedEngine(cfg, params, PagedEngineConfig(
        max_batch=4, block_size=8, n_blocks=64, max_seq_len=64,
        max_new_tokens=12, spec_tokens=4))
    rb = base.run_continuous([copy.copy(r) for r in reqs])
    rs = spec.run_continuous([copy.copy(r) for r in reqs])
    assert rs.outputs == rb.outputs
    assert rs.steps < rb.steps
    assert 0.0 < rs.acceptance_rate <= 1.0


# ----------------------------------------------------------------- pricing

def test_spec_speedup_curve():
    assert spec_speedup(0, 0.9) == 1.0
    assert spec_speedup(4, 0.0) == 1.0
    assert spec_speedup(4, 1.0) == 5.0
    e = spec_speedup(3, 0.5)
    assert abs(e - (1 + 0.5 + 0.25 + 0.125)) < 1e-12
    # monotone in both arguments
    assert spec_speedup(4, 0.6) > spec_speedup(2, 0.6)
    assert spec_speedup(4, 0.8) > spec_speedup(4, 0.4)


def test_scheduler_spec_speedup_widens_batches():
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(32):
        r = Request(rid=i, tokens=[1] * 16, input_len=16,
                    slo=float(rng.uniform(5, 50)), arrival=0.0,
                    true_output_len=64)
        r.predicted_output_len = int(rng.integers(32, 256))
        reqs.append(r)
    cfg = SchedulerConfig(threshold=4e3)
    plain = slo_odbs(reqs, cfg)
    sped = slo_odbs(reqs, SchedulerConfig(threshold=4e3, spec_speedup=3.0))
    assert len(sped) < len(plain)          # fewer, wider batches
    assert max(len(b) for b in sped) >= max(len(b) for b in plain)


def test_simulate_continuous_spec_pricing():
    cfg = get_config("chatglm2-6b")
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, tokens=[1] * 64, input_len=64,
                    slo=200.0, arrival=float(i) * 0.05,
                    true_output_len=int(rng.integers(48, 96)))
            for i in range(24)]
    base = simulate_continuous([copy.copy(r) for r in reqs], cfg,
                               max_batch=4, max_new=128)
    spec = simulate_continuous([copy.copy(r) for r in reqs], cfg,
                               max_batch=4, max_new=128,
                               spec_tokens=4, spec_acceptance=0.7)
    # batched continuous decode: ~1/width iterations per token unspeculated
    assert 1.0 / (4 * 1.5) < base.iterations_per_token <= 1.0
    assert spec.steps < base.steps
    assert spec.iterations_per_token < base.iterations_per_token / 1.5
    assert spec.emitted_tokens == base.emitted_tokens
    # zero acceptance: no fewer iterations, and the window costs compute
    dud = simulate_continuous([copy.copy(r) for r in reqs], cfg,
                              max_batch=4, max_new=128,
                              spec_tokens=4, spec_acceptance=0.0)
    assert dud.steps == base.steps
    assert dud.makespan >= base.makespan


def test_replica_projections_price_acceptance():
    from repro.serving.cluster import Replica
    from repro.serving.simulator import paper_cluster
    cfg = get_config("chatglm2-6b")
    nodes, lat = paper_cluster()
    plain = Replica(0, cfg, nodes, lat, prefix_cache=False)
    spec = Replica(1, cfg, nodes, lat, prefix_cache=False,
                   spec_tokens=4, spec_acceptance=0.7)
    dud = Replica(2, cfg, nodes, lat, prefix_cache=False,
                  spec_tokens=4, spec_acceptance=0.0)
    r = Request(rid=0, tokens=[1] * 64, input_len=64, slo=60.0, arrival=0.0,
                true_output_len=64)
    r.predicted_output_len = 64
    assert spec._decode_seconds(4, 64, 96) < plain._decode_seconds(4, 64, 96)
    # speculation with zero acceptance only adds verify compute
    assert dud._decode_seconds(4, 64, 96) >= plain._decode_seconds(4, 64, 96)
    assert spec.capacity_rps(64, 64) > plain.capacity_rps(64, 64)
    t_plain = plain.projected_finish(r, 0.0)
    t_spec = spec.projected_finish(r, 0.0)
    assert t_spec < t_plain
