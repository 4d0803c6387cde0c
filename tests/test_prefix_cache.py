"""Prefix-sharing KV cache: radix block tree, refcounted copy-on-write
allocator, cache-aware admission/batching, and end-to-end fidelity — greedy
outputs must be token-identical with the prefix cache on vs off while
prefill work and block demand strictly drop on shared-prefix workloads."""
import copy

import numpy as np
import pytest

from repro.core.monitor import Monitor
from repro.core.scheduler import (SchedulerConfig, prefix_affinity_key,
                                  slo_odbs)
from repro.core.types import Request
from repro.data.workload import SharedPrefixConfig, gen_shared_prefix_requests
from repro.obs import Tracer
from repro.serving.kv_cache import BlockAllocator
from repro.serving.prefix_cache import PrefixCache, RadixBlockTree

BS = 8


def _req(rid, tokens, out=4, slo=10.0, arrival=0.0):
    return Request(rid=rid, tokens=list(tokens), input_len=len(tokens),
                   slo=slo, arrival=arrival, true_output_len=out)


# ------------------------------------------------------------ radix tree

def test_radix_match_full_blocks_and_leave_one_token():
    t = RadixBlockTree(4)
    t.insert(list(range(12)), blocks=[10, 11, 12])
    # identical prompt: the last block is excluded (>= 1 token must prefill)
    m = t.match(list(range(12)))
    assert [n.block for n in m.full] == [10, 11]
    assert m.tail is None and m.hit_tokens == 8
    # longer prompt with the same prefix: all three blocks match
    m = t.match(list(range(12)) + [99])
    assert [n.block for n in m.full] == [10, 11, 12]
    # diverging second block: only the first matches
    m = t.match([0, 1, 2, 3, 7, 7, 7, 7, 9])
    assert [n.block for n in m.full] == [10]


def test_radix_partial_tail_match():
    t = RadixBlockTree(4)
    t.insert([0, 1, 2, 3, 4, 5, 6], blocks=[20, 21])   # 1 full + 3-tok tail
    m = t.match([0, 1, 2, 3, 4, 5, 6, 7, 8])
    assert [n.block for n in m.full] == [20]
    assert m.tail is not None and m.tail.block == 21 and m.tail_len == 3
    assert m.hit_tokens == 7
    # tail longer than the prompt allows is not taken
    m = t.match([0, 1, 2, 3, 4, 5])
    assert m.tail_len == 1 or m.tail is None  # only shorter partials match
    # two partials at the same node: the longest admissible one wins
    t.insert([0, 1, 2, 3, 4, 5], blocks=[20, 22])     # 2-tok leaf [4, 5]
    m = t.match([0, 1, 2, 3, 4, 5, 6, 7, 8])
    assert m.tail.block == 21 and m.tail_len == 3


def test_radix_insert_dedups_existing_nodes():
    t = RadixBlockTree(4)
    created = t.insert(list(range(8)), blocks=[1, 2])
    assert len(created) == 2
    created = t.insert(list(range(8)) + [9, 9, 9, 9], blocks=[5, 6, 7])
    # first two spans already exist (their blocks stay pinned), one new node
    assert len(created) == 1 and created[0].block == 7
    assert [n.block for n in t.match(list(range(8)) + [9] * 4 + [0]).full] \
        == [1, 2, 7]


# ------------------------------------------------- refcounted allocator

def test_free_seq_idempotent_and_start_seq_guard():
    a = BlockAllocator(8)
    a.start_seq(1)
    a.alloc(1, 3)
    with pytest.raises(ValueError, match="already live"):
        a.start_seq(1)
    assert a.free_seq(1) == 3
    assert a.free_seq(1) == 0          # double free is a no-op
    a.start_seq(1)                     # recycled id is fine after free


def test_refcount_shared_block_survives_first_free():
    a = BlockAllocator(8)
    [b0] = a.alloc(1, 1)
    a.share(2, [b0])
    assert a.refcnt[b0] == 2
    a.free_seq(1)
    assert a.refcnt[b0] == 1 and b0 not in a.free
    a.free_seq(2)
    assert b0 in a.free                # unretained: straight back to free


def test_refcount_drop_to_zero_parks_retained_block_in_cache():
    a = BlockAllocator(8)
    [b0] = a.alloc(1, 1)
    a.retain(b0)
    a.free_seq(1)
    assert b0 in a.cached and b0 not in a.free
    assert a.used_blocks == 0
    # sharing revives it
    a.share(3, [b0])
    assert b0 not in a.cached and a.refcnt[b0] == 1


def test_pool_exhaustion_mid_decode_and_reclaim():
    a = BlockAllocator(4)
    a.alloc(1, 2)
    [b2] = a.alloc(2, 1)
    a.retain(b2)
    a.free_seq(2)                      # b2 cached; free list has 1 block
    # no reclaimer: a mid-decode growth of 2 blocks exhausts the pool
    with pytest.raises(MemoryError):
        a.alloc(1, 2)
    # with a reclaimer (the prefix tree), the cached block is evicted
    a.reclaimer = lambda n: ([a.release_cached(b)
                              for b in list(a.cached)[:n]], n)[1]
    assert a.can_alloc(2)
    a.alloc(1, 2)
    assert len(a.free) == 0 and len(a.cached) == 0


def test_cow_fork_semantics():
    a = BlockAllocator(8)
    [b0] = a.alloc(1, 1)
    # exclusive, unretained: write in place
    assert a.cow(1, b0) == b0
    # shared: the forker gets a fresh block, the other ref survives
    a.share(2, [b0])
    nb = a.cow(2, b0)
    assert nb != b0 and a.tables[2] == [nb]
    assert a.refcnt[b0] == 1 and a.tables[1] == [b0]
    # retained-but-exclusive: the tree may still serve it -> fork too
    [b1] = a.alloc(3, 1)
    a.retain(b1)
    nb1 = a.cow(3, b1)
    assert nb1 != b1 and b1 in a.cached


# ------------------------------------------------------- prefix cache

def test_prefix_cache_insert_share_evict_cycle():
    a = BlockAllocator(10)
    pc = PrefixCache(a, 4)
    a.start_seq(1)
    blocks = a.alloc(1, 3)
    pc.insert(list(range(12)), blocks)          # 3 full nodes, retained
    a.free_seq(1)
    assert len(a.cached) == 3
    # a new seq shares two blocks net of the leave-one rule
    m = pc.lookup(list(range(12)))
    assert [n.block for n in m.full] == blocks[:2]
    pc.share(2, m)
    assert len(a.cached) == 1
    # pressure: only the unreferenced leaf is evictable
    assert pc.evict(3) == 1
    assert a.stats()["cached"] == 0 and blocks[2] in a.free
    a.free_seq(2)
    # chain returns to cached; LRU eviction cascades leaf-first
    assert len(a.cached) == 2
    assert pc.evict(2) == 2
    assert pc.tree.n_nodes == 0


def test_prefix_cache_eviction_is_lru():
    a = BlockAllocator(10)
    pc = PrefixCache(a, 4)
    a.start_seq(1)
    pc.insert(list(range(4)), a.alloc(1, 1))
    a.start_seq(2)
    pc.insert(list(range(50, 54)), a.alloc(2, 1))
    a.free_seq(1)
    a.free_seq(2)
    pc.lookup(list(range(4)) + [9])      # touch the first chain
    pc.evict(1)
    # the untouched chain went first
    assert pc.lookup(list(range(4)) + [9]).hit_tokens == 4
    assert pc.lookup(list(range(50, 54)) + [9]).hit_tokens == 0


# ------------------------------------------- scheduler / workload / sim

def test_prefix_affinity_key_groups_templates():
    t1, t2 = [1] * BS, [2] * BS
    reqs = [_req(0, t1 + [10], slo=50.0), _req(1, t2 + [11], slo=5.0),
            _req(2, t1 + [12], slo=40.0), _req(3, t2 + [13], slo=45.0)]
    order = sorted(reqs, key=prefix_affinity_key(reqs, block=BS))
    rids = [r.rid for r in order]
    # template-2 group first (min slo 5), members adjacent, slo-sorted inside
    assert rids == [1, 3, 2, 0]
    cfg = SchedulerConfig(prefix_aware=True, prefix_block=BS, max_batch=2,
                          threshold=1e12, memory_budget=1e18)
    for r in reqs:
        r.predicted_output_len = 4
    batches = slo_odbs(reqs, cfg)
    first = {r.rid for r in batches[0].requests}
    assert first == {1, 3}             # shared-prefix pair packed together


def test_shared_prefix_workload_generator():
    cfg = SharedPrefixConfig(n_requests=12, n_templates=3, prefix_len=16,
                             turns=1, seed=0)
    reqs = gen_shared_prefix_requests(cfg)
    assert len(reqs) == 12
    heads = {tuple(r.tokens[:16]) for r in reqs}
    assert len(heads) == 3             # every prompt starts with a template
    # multi-turn: later turns strictly extend the conversation context
    mt = gen_shared_prefix_requests(SharedPrefixConfig(
        n_requests=8, n_templates=2, prefix_len=16, turns=4, seed=1))
    conv0 = [r for i, r in enumerate(mt) if i % 2 == 0]
    for a, b in zip(conv0, conv0[1:]):
        assert b.tokens[:len(a.tokens)] == a.tokens
        assert len(b.tokens) > len(a.tokens)


def test_simulator_prefix_accounting():
    from repro.configs import get_config
    from repro.serving.simulator import simulate
    from repro.core.scheduler import fifo
    cfg = get_config("smollm-135m").reduced()
    reqs = gen_shared_prefix_requests(SharedPrefixConfig(
        n_requests=16, n_templates=2, prefix_len=64, suffix_mean=2.0,
        seed=2))
    for r in reqs:
        r.true_output_len = min(r.true_output_len, 32)
    scfg = SchedulerConfig()
    base = simulate([copy.copy(r) for r in reqs], cfg, fifo, scfg)
    cached = simulate([copy.copy(r) for r in reqs], cfg, fifo, scfg,
                      prefix_cache=True)
    assert base.prefill_tokens_saved == 0
    assert cached.prefill_tokens_saved > 0
    assert cached.prefix_hit_requests > 0
    assert 0.0 < cached.prefill_saved_frac < 1.0
    assert cached.makespan <= base.makespan   # skipped prefill can't slow it
    assert "prefill_tokens_saved" in cached.summary()


# --------------------------------------------------- engine end-to-end

@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import api
    cfg = get_config("smollm-135m").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def _serve(cfg, params, reqs, tracer=None, **pcfg_kw):
    from repro.serving import PagedEngine, PagedEngineConfig
    kw = dict(max_batch=4, block_size=BS, n_blocks=64, max_seq_len=64,
              max_new_tokens=12)
    kw.update(pcfg_kw)
    eng = PagedEngine(cfg, params, PagedEngineConfig(**kw), tracer=tracer)
    return eng.run_continuous([copy.copy(r) for r in reqs])


def _hol_skips(tracer) -> int:
    """Admissions that jumped a blocked queue head (``admitted`` instants
    whose ``hol_skip`` is a queue index past the head)."""
    return sum(e.args["hol_skip"] > 0 for e in tracer.events
               if e.name == "admitted")


def _template_reqs(cfg, n=6, tmpl_len=24, suffix=8, seed=7):
    rng = np.random.default_rng(seed)
    tmpl = [rng.integers(0, cfg.vocab_size, tmpl_len).tolist()
            for _ in range(2)]
    return [_req(i, tmpl[i % 2] + rng.integers(0, cfg.vocab_size,
                                               suffix).tolist(),
                 out=int(rng.integers(2, 8)), arrival=float(i))
            for i in range(n)]


def test_prefix_cache_token_identical_and_fewer_prefill(model):
    """Acceptance: greedy outputs identical with --prefix-cache on vs off
    on a shared-prefix workload, with strictly fewer prefill tokens."""
    cfg, params = model
    reqs = _template_reqs(cfg)
    off = _serve(cfg, params, reqs, prefix_cache=False)
    on = _serve(cfg, params, reqs, prefix_cache=True)
    for r in reqs:
        assert off.outputs[r.rid] == on.outputs[r.rid], r.rid
    assert on.prefill_tokens < off.prefill_tokens
    assert on.prefix_hits >= 4 and on.prefix_hit_tokens > 0


def test_prefix_hits_buy_admission_capacity(model):
    """At a pool too small for the uncached resident set, net-of-hits
    admission fits strictly more concurrent sequences."""
    cfg, params = model
    reqs = _template_reqs(cfg, n=8, seed=11)
    reqs = [copy.copy(r) for r in
            sorted(reqs, key=prefix_affinity_key(reqs, block=BS))]
    off = _serve(cfg, params, reqs, max_batch=6, n_blocks=12,
                 prefix_cache=False)
    on = _serve(cfg, params, reqs, max_batch=6, n_blocks=12,
                prefix_cache=True)
    for r in reqs:
        assert off.outputs[r.rid] == on.outputs[r.rid], r.rid
    assert on.peak_residents >= off.peak_residents + 1


def test_multiturn_partial_tail_cow(model):
    """A follow-up turn whose prompt embeds the previous answer matches
    into the finished chain's partially-filled tail block, which is forked
    copy-on-write before the suffix prefill writes into it."""
    cfg, params = model
    rng = np.random.default_rng(3)
    p1 = rng.integers(0, cfg.vocab_size, 12).tolist()
    r1 = _req(0, p1, out=4)
    pre = _serve(cfg, params, [r1], max_batch=1, n_blocks=32,
                 prefix_cache=True)
    ans = pre.outputs[0]
    # kv chain = p1 + ans[:3] = 15 tokens: 1 full block + 7-token tail
    p2 = p1 + ans + rng.integers(0, cfg.vocab_size, 5).tolist()
    r2 = _req(1, p2, out=4, arrival=1.0)
    on = _serve(cfg, params, [r1, r2], max_batch=1, n_blocks=32,
                prefix_cache=True)
    assert on.prefix_hit_tokens == 15
    assert on.cow_forks == 1
    off = _serve(cfg, params, [r1, r2], max_batch=1, n_blocks=32,
                 prefix_cache=False)
    assert off.outputs == on.outputs
    # share_partial_tails=False: hits stay block-aligned (no COW, fewer
    # continuation-prefill jit shapes), outputs still identical
    aligned = _serve(cfg, params, [r1, r2], max_batch=1, n_blocks=32,
                     prefix_cache=True, share_partial_tails=False)
    assert aligned.prefix_hit_tokens == 8
    assert aligned.cow_forks == 0
    assert aligned.outputs == off.outputs


def test_eviction_under_pressure_keeps_outputs(model):
    """A pool too small to retain every finished chain evicts LRU cached
    blocks to admit new work — outputs stay identical to the uncached run."""
    cfg, params = model
    rng = np.random.default_rng(5)
    reqs = [_req(i, rng.integers(0, cfg.vocab_size, 16).tolist(), out=3,
                 arrival=float(i)) for i in range(6)]
    on = _serve(cfg, params, reqs, max_batch=2, n_blocks=9, max_seq_len=32,
                max_new_tokens=8, prefix_cache=True)
    off = _serve(cfg, params, reqs, max_batch=2, n_blocks=9, max_seq_len=32,
                 max_new_tokens=8, prefix_cache=False)
    assert on.prefix_evictions > 0
    assert off.outputs == on.outputs
    assert on.peak_blocks <= 8


def test_admit_lookahead_skips_blocked_head(model):
    """HOL fix (paged_engine._admit): a too-big queue head no longer stalls
    a later request that fits, bounded by admit_lookahead."""
    cfg, params = model
    rng = np.random.default_rng(9)
    r0 = _req(0, rng.integers(0, cfg.vocab_size, 10).tolist(), out=12)
    big = _req(1, rng.integers(0, cfg.vocab_size, 20).tolist(), out=12,
               arrival=1.0)
    small = _req(2, rng.integers(0, cfg.vocab_size, 8).tolist(), out=4,
                 arrival=2.0)
    kw = dict(max_batch=2, n_blocks=7, max_seq_len=64, max_new_tokens=12)
    fifo_tr, la_tr = Tracer(), Tracer()
    fifo_run = _serve(cfg, params, [r0, big, small], tracer=fifo_tr,
                      admit_lookahead=0, **kw)
    la_run = _serve(cfg, params, [r0, big, small], tracer=la_tr,
                    admit_lookahead=2, **kw)
    assert _hol_skips(fifo_tr) == 0
    assert _hol_skips(la_tr) >= 1          # small jumped the blocked head
    assert fifo_run.outputs == la_run.outputs  # greedy streams unaffected
    assert set(la_run.outputs) == {0, 1, 2}


def test_monitor_prefix_and_pool_gauges():
    from repro.core.profiler import (LengthPredictor, PredictorConfig,
                                     ResourceProfiler)
    from repro.configs import get_config
    from repro.serving.prefix_cache import PrefixCacheStats
    prof = ResourceProfiler(LengthPredictor(PredictorConfig(vocab=64), seed=0),
                            get_config("smollm-135m").reduced())
    mon = Monitor(prof)
    mon.observe_pool({"total": 16, "free": 5, "used": 9, "cached": 2},
                     fragmentation=0.25)
    st = PrefixCacheStats(lookups=4, hits=3, hit_tokens=48, hit_blocks=6,
                          evicted_blocks=2)
    mon.observe_prefix(st, cow_forks=1)
    m = mon.metrics()
    assert m["pool_free_blocks"] == 5 and m["pool_cached_blocks"] == 2
    assert m["pool_fragmentation"] == 0.25
    assert m["prefix_hit_rate"] == 0.75
    assert m["prefix_hit_tokens"] == 48
    assert m["prefix_evicted_blocks"] == 2 and m["prefix_cow_forks"] == 1
