"""Iteration-level scheduling in the paged engine: chunked-prefill fidelity
(bit-identical logits, token-identical outputs), the oracle-free admission
charge, null-block pool sizing, SLO-slack preemption with recompute, and the
continuous-serving simulator's stall/preemption model."""
import copy

import numpy as np
import pytest

from conftest import dense_greedy
from repro.configs import get_config
from repro.core.types import Request
from repro.obs import Tracer
from repro.serving import PagedEngine, PagedEngineConfig, kv_block_bytes

BS = 8          # KV block size used throughout


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp
    from repro.models import api
    cfg = get_config("smollm-135m").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def _req(rid, tokens, *, out=4, slo=30.0, arrival=0.0):
    return Request(rid=rid, tokens=list(tokens), input_len=len(tokens),
                   slo=slo, arrival=arrival, true_output_len=out)


def _reqs(cfg, n=5, in_len=20, out_max=8, seed=5):
    rng = np.random.default_rng(seed)
    return [_req(i, rng.integers(0, cfg.vocab_size, in_len).tolist(),
                 out=int(rng.integers(1, out_max + 1))) for i in range(n)]


def _serve(cfg, params, reqs, tracer=None, **kw):
    pcfg_kw = dict(max_batch=4, block_size=BS, n_blocks=64, max_seq_len=64,
                   max_new_tokens=12)
    pcfg_kw.update(kw)
    eng = PagedEngine(cfg, params, PagedEngineConfig(**pcfg_kw),
                      tracer=tracer)
    return eng.run_continuous([copy.copy(r) for r in reqs])


# ------------------------------------------------- chunked-prefill fidelity

@pytest.mark.parametrize("chunk,n", [(8, 24), (8, 20), (16, 24), (16, 20)])
def test_chunked_prefill_logits_bitwise(model, chunk, n):
    """Continuation prefill chained over block-aligned chunk boundaries —
    the exact dataflow the engine runs (each chunk zero-padded to the block
    boundary, ``kv_len`` marking the valid suffix, the accumulated prefix
    sliced to valid tokens) — reproduces the whole-prompt prefill logits
    *bitwise* on CPU, which is what makes chunked greedy decoding
    token-identical by construction.  (Arbitrary *unaligned* chunk matmul
    shapes round differently under XLA CPU tiling; the engine never emits
    them — chunks are block multiples, the tail is padded.)"""
    import jax
    import jax.numpy as jnp
    from repro.models import api
    cfg, params = model
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, n).tolist()
    pad = -(-n // BS) * BS
    full = np.zeros((1, pad), np.int32)
    full[0, :n] = toks
    full_logits, _ = api.prefill(
        cfg, params, {"tokens": jnp.asarray(full)},
        cache_len=pad, kv_len=jnp.asarray([n], jnp.int32))

    prefix = None
    logits = None
    done = 0
    while done < n:
        sn = min(chunk, n - done)
        cl = -(-sn // BS) * BS                 # block-padded, like the engine
        buf = np.zeros((1, cl), np.int32)
        buf[0, :sn] = toks[done:done + sn]
        logits, cache = api.prefill(
            cfg, params, {"tokens": jnp.asarray(buf)},
            cache_len=cl, kv_len=jnp.asarray([sn], jnp.int32),
            prefix_kv=prefix)
        valid = jax.tree.map(lambda c: c[:, :, :sn], cache)
        prefix = valid if prefix is None else jax.tree.map(
            lambda p, c: jnp.concatenate([p, c], axis=2), prefix, valid)
        done += sn
    np.testing.assert_array_equal(np.asarray(full_logits),
                                  np.asarray(logits))


@pytest.mark.parametrize("chunk", [8, 16, 24])
def test_chunked_engine_token_identical(model, chunk):
    """Engine-level chunked prefill (prefix gathered back out of the paged
    pool each chunk) emits exactly the whole-prompt token streams."""
    cfg, params = model
    reqs = _reqs(cfg, n=6, in_len=20)
    whole = _serve(cfg, params, reqs)
    chunked = _serve(cfg, params, reqs, chunk_tokens=chunk)
    for r in reqs:
        assert whole.outputs[r.rid] == chunked.outputs[r.rid], r.rid
    # same block-padded prefill volume, more (or equal) prefill calls
    assert chunked.prefill_tokens == whole.prefill_tokens
    assert chunked.prefill_chunks >= whole.prefill_chunks


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_with_prefix_cache_and_cow(model, chunk):
    """Chunked prefill composes with radix prefix hits and COW partial
    tails: a multi-turn follow-up matching a finished chain's tail block
    still produces identical outputs when its uncached suffix is chunked."""
    cfg, params = model
    rng = np.random.default_rng(3)
    p1 = rng.integers(0, cfg.vocab_size, 12).tolist()
    r1 = _req(0, p1, out=4)
    pre = _serve(cfg, params, [r1], max_batch=1, prefix_cache=True)
    ans = pre.outputs[0]
    p2 = p1 + ans + rng.integers(0, cfg.vocab_size, 21).tolist()
    r2 = _req(1, p2, out=4, arrival=1.0)
    base = _serve(cfg, params, [r1, r2], max_batch=1, prefix_cache=False)
    on = _serve(cfg, params, [r1, r2], max_batch=1, prefix_cache=True,
                chunk_tokens=chunk)
    assert on.cow_forks == 1          # tail block forked before the suffix
    assert on.prefix_hit_tokens > 0
    assert on.outputs == base.outputs
    # template sharing under chunking: two same-template requests served
    # back to back (max_batch=1 — publication happens at prefill
    # *completion*, so a same-wave sibling that begins its chunked prefill
    # before the first completes legitimately misses)
    t1, t2 = _req(2, p1 + [7, 8, 9]), _req(3, p1 + [11, 12, 13])
    off2 = _serve(cfg, params, [t1, t2], max_batch=1, prefix_cache=False)
    on2 = _serve(cfg, params, [t1, t2], max_batch=1, prefix_cache=True,
                 chunk_tokens=chunk)
    assert on2.outputs == off2.outputs
    assert on2.prefix_hits >= 1


# ----------------------------------------------- admission oracle regression

def test_admission_ignores_true_output_len(model):
    """The admission charge must be computable without ground truth:
    requests identical up to ``true_output_len`` get identical worst-case
    reservations and identical can_admit decisions."""
    from repro.serving.paged_engine import PagedDecodeState
    cfg, params = model
    pcfg = PagedEngineConfig(max_batch=2, block_size=BS, n_blocks=8,
                             max_seq_len=64, max_new_tokens=12)
    eng = PagedEngine(cfg, params, pcfg)
    st = PagedDecodeState.create(cfg, pcfg)
    for predicted in (None, 4, 40):
        a = _req(0, [1] * 10, out=2)
        b = _req(1, [1] * 10, out=200)       # only ground truth differs
        a.predicted_output_len = b.predicted_output_len = predicted
        assert eng._worst_blocks(a, 12) == eng._worst_blocks(b, 12)
        assert eng.can_admit(st, a, 12) == eng.can_admit(st, b, 12)
    # and the prediction is clamped to the decode budget, never 512-capped
    c = _req(2, [1] * 10, out=2)
    assert eng._worst_blocks(c, 12) == -(-(10 + 12) // BS)
    c.predicted_output_len = 4
    assert eng._worst_blocks(c, 12) == -(-(10 + 4) // BS)


def test_admission_decisions_identical_with_hidden_truth(model):
    """End-to-end regression: serving the same prompts/predictions with
    wildly different hidden true lengths yields the same admission wave
    pattern (finish timing differs; *decisions* must not leak truth)."""
    cfg, params = model
    reqs_a = _reqs(cfg, n=6, in_len=20, seed=9)
    reqs_b = [copy.copy(r) for r in reqs_a]
    for r in reqs_a:
        r.predicted_output_len = 6
    for r in reqs_b:
        r.predicted_output_len = 6
        r.true_output_len = 1          # hidden truth collapses entirely
    kw = dict(n_blocks=12)                   # tight pool: admission matters
    tr_a, tr_b = Tracer(), Tracer()
    res_a = _serve(cfg, params, reqs_a, tracer=tr_a, **kw)
    res_b = _serve(cfg, params, reqs_b, tracer=tr_b, **kw)
    assert res_a.peak_residents == res_b.peak_residents

    def hol_skips(tr):
        return sum(e.args["hol_skip"] > 0 for e in tr.events
                   if e.name == "admitted")
    assert hol_skips(tr_a) == hol_skips(tr_b)


# --------------------------------------------------- null-block pool sizing

def test_memory_budget_buys_usable_blocks(model):
    """from_memory_budget: the budget maps to *usable* KV capacity — the
    reserved null block rides on top — so the pool the scheduler packs
    against equals what admission can hand out, and usable-block bytes
    never exceed the budget."""
    cfg, _ = model
    bb = kv_block_bytes(cfg, 16)
    for mult in (0.5, 1.0, 2.0, 5.5, 64.0):
        pcfg = PagedEngineConfig.from_memory_budget(cfg, mult * bb)
        implied = max(1, int(mult))
        assert pcfg.usable_blocks == implied, mult
        assert pcfg.n_blocks == implied + 1, mult
        assert pcfg.usable_blocks * bb <= max(mult * bb, bb), mult


def test_single_block_budget_still_serves(model):
    """The floor case: a budget below one block yields one usable block and
    the engine can still serve a one-block request."""
    cfg, params = model
    bb = kv_block_bytes(cfg, BS)
    pcfg = PagedEngineConfig.from_memory_budget(
        cfg, 0.25 * bb, block_size=BS, max_batch=1, max_seq_len=16,
        max_new_tokens=4)
    assert pcfg.usable_blocks == 1
    eng = PagedEngine(cfg, params, pcfg)
    res = eng.run_continuous([_req(0, [1, 2, 3], out=3)], max_new=4)
    assert len(res.outputs[0]) == 3


# ---------------------------------------------------------------- preemption

def test_preemption_recompute_token_identity(model):
    """Block pressure + preempt: the slack-most resident is evicted for a
    tighter arrival, requeued, recomputed — outputs identical to the dense
    reference, and the preemption is visible in the result gauges."""
    cfg, params = model
    reqs = [_req(0, [3] * 8, out=8, slo=1000.0),
            _req(1, [5] * 8, out=4, slo=0.001)]
    ref = dense_greedy(cfg, params, reqs, 8)
    res = _serve(cfg, params, reqs, max_batch=2, n_blocks=4,
                 max_seq_len=32, max_new_tokens=8, preempt=True)
    assert res.preemptions >= 1
    assert res.preempted_tokens >= 1
    for r in reqs:
        assert res.outputs[r.rid] == ref[r.rid], r.rid


def test_no_preempt_blocks_instead(model):
    """Same pressure without --preempt: nobody is evicted (the tight
    arrival waits) and outputs are still correct."""
    cfg, params = model
    reqs = [_req(0, [3] * 8, out=8, slo=1000.0),
            _req(1, [5] * 8, out=4, slo=0.001)]
    res = _serve(cfg, params, reqs, max_batch=2, n_blocks=4,
                 max_seq_len=32, max_new_tokens=8, preempt=False)
    assert res.preemptions == 0
    assert len(res.outputs[0]) == 8 and len(res.outputs[1]) == 4


def test_preemption_never_evicts_tighter_than_arrival(model):
    """A victim must have strictly more slack than the blocked arrival —
    equal-slack residents are left alone (no violation-for-violation
    trades)."""
    cfg, params = model
    reqs = [_req(0, [3] * 8, out=8, slo=5.0),
            _req(1, [5] * 8, out=4, slo=5.0)]
    res = _serve(cfg, params, reqs, max_batch=2, n_blocks=4,
                 max_seq_len=32, max_new_tokens=8, preempt=True)
    assert res.preemptions == 0


def test_no_fruitless_eviction(model):
    """Feasibility precheck: when even evicting every eligible (slacker)
    victim cannot buy the blocked head admission — here a tight co-resident
    is ineligible and holds too much — nobody is preempted; the head simply
    waits for capacity.  (The old evict-then-check loop threw away the
    slack resident's work for zero gain.)"""
    cfg, params = model
    reqs = [_req(0, [3] * 8, out=6, slo=1000.0),    # slack, eligible
            _req(1, [5] * 8, out=6, slo=0.4),       # tighter than the head
            _req(2, [7] * 32, out=2, slo=1.0)]      # blocked long arrival
    res = _serve(cfg, params, reqs, max_batch=3, n_blocks=6,
                 max_seq_len=40, max_new_tokens=8, preempt=True)
    assert res.preemptions == 0
    for r in reqs:
        assert len(res.outputs[r.rid]) == r.true_output_len, r.rid


def test_simulate_continuous_rejects_oversized_request():
    """Engine parity: a request whose budgeted horizon exceeds the pool
    raises instead of silently blocking the admission head forever."""
    from repro.serving import simulate_continuous
    cfg = get_config("chatglm2-6b")
    big = _req(0, [1] * 400, out=8)
    big.predicted_output_len = 8
    with pytest.raises(ValueError, match="blocks"):
        simulate_continuous([big], cfg, block_size=16, n_blocks=20,
                            max_new=16)


def test_monitor_interleave_gauges(model):
    """Chunk/stall/preemption counters surface through Monitor.metrics()."""
    import jax
    import jax.numpy as jnp
    from repro.core import LengthPredictor, Monitor, ResourceProfiler
    from repro.core.profiler import PredictorConfig
    from repro.data.workload import WorkloadConfig, train_pairs
    cfg, params = model
    pred = LengthPredictor(PredictorConfig(vocab=cfg.vocab_size), seed=0)
    toks, lens = train_pairs(WorkloadConfig(vocab=cfg.vocab_size), 64, seed=1)
    pred.fit(toks, lens, epochs=1)
    prof = ResourceProfiler(pred, cfg)
    mon = Monitor(prof)
    reqs = [_req(0, [3] * 8, out=8, slo=1000.0),
            _req(1, [5] * 8, out=4, slo=0.001)]
    pcfg = PagedEngineConfig(max_batch=2, block_size=BS, n_blocks=4,
                             max_seq_len=32, max_new_tokens=8,
                             chunk_tokens=BS, preempt=True)
    eng = PagedEngine(cfg, params, pcfg, monitor=mon)
    eng.run_continuous([copy.copy(r) for r in reqs])
    m = mon.metrics()
    assert m["prefill_chunks"] >= 3
    assert m["preemptions"] >= 1
    assert m["preempted_tokens"] >= 1


# ------------------------------------------- continuous-serving simulation

def _sim_reqs(n=32, rate=8.0, seed=2):
    from repro.data.workload import WorkloadConfig, gen_requests
    reqs = gen_requests(WorkloadConfig(n_requests=n, arrival_rate=rate,
                                       slo_lo=5.0, slo_hi=60.0, seed=seed))
    for i, r in enumerate(reqs):
        r.input_len = 1024 if i % 4 == 0 else 64
        r.tokens = [1] * r.input_len
        r.true_output_len = r.true_output_len % 48 + 8
    return reqs


def test_simulate_continuous_chunking_cuts_p99_itl():
    """The analytic twin of the engine loop: chunked prefill bounds the
    inter-token stall at one chunk, so p99 ITL drops on a long/short mix
    while total work (throughput) stays within a few percent."""
    from repro.serving import simulate_continuous
    cfg = get_config("chatglm2-6b")
    mono = simulate_continuous(_sim_reqs(), cfg, chunk_tokens=0)
    chunk = simulate_continuous(_sim_reqs(), cfg, chunk_tokens=128)
    assert chunk.p99_inter_token_s < 0.5 * mono.p99_inter_token_s
    assert chunk.throughput > 0.9 * mono.throughput
    assert mono.prefill_stall_s > 0
    assert chunk.prefill_chunks > mono.prefill_chunks


def test_simulate_continuous_preemption_frees_tight_arrival():
    """Pool sized for one resident: a slack long-runner is preempted when a
    tight request lands, the tight request finishes inside its SLO, and the
    victim's tokens are recomputed (work conservation is visible)."""
    from repro.serving import simulate_continuous
    cfg = get_config("chatglm2-6b")

    def mk():
        slack = _req(0, [1] * 256, out=200, slo=1e6, arrival=0.0)
        tight = _req(1, [1] * 64, out=8, slo=12.0, arrival=1.0)
        for r in (slack, tight):
            r.predicted_output_len = r.true_output_len
        return [slack, tight]

    kw = dict(max_batch=4, max_new=200, block_size=16, n_blocks=30)
    pre = simulate_continuous(mk(), cfg, preempt=True, **kw)
    nop = simulate_continuous(mk(), cfg, preempt=False, **kw)
    assert pre.preemptions >= 1
    assert pre.preempted_tokens >= 1
    assert nop.preemptions == 0
    tight_pre = next(r for r in pre.requests if r.rid == 1)
    tight_nop = next(r for r in nop.requests if r.rid == 1)
    assert tight_pre.finish_time < tight_nop.finish_time
