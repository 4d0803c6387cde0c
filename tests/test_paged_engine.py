"""Paged serving runtime: token equivalence with the padded engine, true
continuous admission (prefill proportional to prompts, never to slots),
allocator exhaustion/backpressure, and the batched PagedKVCache scatter."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.types import Batch
from repro.data.workload import WorkloadConfig, gen_requests
from repro.models import api
from repro.serving import (EngineConfig, InferenceEngine, PagedEngine,
                           PagedEngineConfig)
from repro.serving.kv_cache import (BlockAllocator, PagedKVCache,
                                    PagedKVConfig)

BS = 8          # KV block size used throughout


@pytest.fixture(scope="module")
def engines():
    cfg = get_config("smollm-135m").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = InferenceEngine(cfg, params,
                          EngineConfig(max_batch=4, cache_len=64,
                                       max_new_tokens=12))
    peng = PagedEngine(cfg, params,
                       PagedEngineConfig(max_batch=4, block_size=BS,
                                         n_blocks=64, max_seq_len=64,
                                         max_new_tokens=12))
    return cfg, eng, peng


def _reqs(cfg, n=6, out_max=8, seed=5):
    reqs = gen_requests(WorkloadConfig(n_requests=n, seed=seed,
                                       vocab=cfg.vocab_size))
    for r in reqs:
        r.tokens = [t % cfg.vocab_size for t in r.tokens[:10]]
        r.input_len = len(r.tokens)
        r.true_output_len = min(r.true_output_len % out_max + 1, out_max)
    return reqs


def _block_padded(n):
    return -(-n // BS) * BS


def test_paged_matches_padded_tokens(engines):
    """Greedy paged continuous batching emits the exact token streams of the
    paper-mode padded batch for the same requests."""
    cfg, eng, peng = engines
    reqs = _reqs(cfg, 4)
    tl = {r.rid: r.true_output_len for r in reqs}
    res_p = eng.run_batch(Batch(requests=reqs), true_lens=tl)
    res_c = peng.run_continuous(reqs)
    for r in reqs:
        assert res_p.outputs[r.rid] == res_c.outputs[r.rid], r.rid


def test_paged_prefill_proportional_to_prompts(engines):
    """No full-slot re-prefill: admitted prompts are prefilled individually,
    so prefill token count is exactly the (block-padded) sum of prompt
    lengths — independent of how many admission waves slot recycling takes."""
    cfg, eng, peng = engines
    reqs = _reqs(cfg, 7)               # > max_batch=4 -> slots must recycle
    res = peng.run_continuous(reqs)
    assert set(res.outputs) == {r.rid for r in reqs}
    for r in reqs:
        assert len(res.outputs[r.rid]) == min(r.true_output_len, 12)
    assert res.admission_waves >= 2
    assert res.prefill_tokens == sum(_block_padded(len(r.tokens))
                                     for r in reqs)


def test_paged_recycled_slots_match_fresh_padded_decode(engines):
    """Sequences admitted into recycled slots (residents mid-decode) must
    still decode exactly as a fresh padded batch would."""
    cfg, eng, peng = engines
    reqs = _reqs(cfg, 7)
    res_c = peng.run_continuous(reqs)
    late = reqs[4:]
    res_p = eng.run_batch(Batch(requests=late),
                          true_lens={r.rid: r.true_output_len for r in late})
    for r in late:
        assert res_p.outputs[r.rid] == res_c.outputs[r.rid], r.rid


def test_block_backpressure_defers_admission(engines):
    """A pool that cannot hold all requests at once admits in waves gated on
    BlockAllocator.can_alloc, never exceeds the pool, and still serves
    everything."""
    cfg, eng, _ = engines
    params = eng.params
    # worst case per request: ceil((10 + 12)/8) = 3 blocks; pool of 7 usable
    # blocks fits two residents + the null block, not four
    pcfg = PagedEngineConfig(max_batch=4, block_size=BS, n_blocks=8,
                             max_seq_len=64, max_new_tokens=12)
    peng = PagedEngine(cfg, params, pcfg)
    reqs = _reqs(cfg, 5)
    res = peng.run_continuous(reqs)
    assert set(res.outputs) == {r.rid for r in reqs}
    for r in reqs:
        assert len(res.outputs[r.rid]) == min(r.true_output_len, 12)
    assert res.admission_waves >= 3          # backpressure forced deferral
    assert res.peak_blocks <= pcfg.n_blocks - 1
    # outputs unchanged vs the padded engine
    res_p = eng.run_batch(Batch(requests=reqs),
                          true_lens={r.rid: r.true_output_len for r in reqs})
    for r in reqs:
        assert res_p.outputs[r.rid] == res.outputs[r.rid], r.rid


def test_single_token_request_admitted_mid_run(engines):
    """A request whose entire output is its prefill token (stop count 1),
    admitted into a recycled slot mid-run, must not receive an extra decode
    token before the finish scan sees it."""
    cfg, eng, _ = engines
    pcfg = PagedEngineConfig(max_batch=2, block_size=BS, n_blocks=32,
                             max_seq_len=64, max_new_tokens=12)
    peng = PagedEngine(cfg, eng.params, pcfg)
    reqs = _reqs(cfg, 3)
    reqs[0].true_output_len = 2
    reqs[1].true_output_len = 6
    reqs[2].true_output_len = 1      # admitted only after slot 0 recycles
    res = peng.run_continuous(reqs)
    for r in reqs:
        assert len(res.outputs[r.rid]) == r.true_output_len, r.rid
    res_p = eng.run_batch(Batch(requests=reqs),
                          true_lens={r.rid: r.true_output_len for r in reqs})
    for r in reqs:
        assert res_p.outputs[r.rid] == res.outputs[r.rid], r.rid


def test_kv_page_counters_sum_the_kernels_reads(engines):
    """``kv_pages_read`` sums, over decode steps and slots, the table pages
    the paged kernel's bound reads: a slot at history length kv reads the
    pages holding positions 0..kv (the new token's included), a free slot
    its null block.  ``kv_pages_table`` is what a walk of the whole bucketed
    table reads."""
    cfg, eng, _ = engines
    pcfg = PagedEngineConfig(max_batch=2, block_size=BS, n_blocks=32,
                             max_seq_len=64, max_new_tokens=12)
    peng = PagedEngine(cfg, eng.params, pcfg)
    reqs = _reqs(cfg, 2)
    for r, n in zip(reqs, (10, 5)):
        r.tokens, r.input_len = r.tokens[:n], n
    reqs[0].true_output_len, reqs[1].true_output_len = 4, 2
    res = peng.run_continuous(reqs)
    # prefill emits each first token; the decode steps then run at history
    # lengths A: 10, 11, 12 and B: 5 (its slot is free, kv 0, after that)
    assert res.steps == 3
    # pages (kv + 1) / 8 rounded up: step 1 A 2 + B 1, steps 2-3 A 2 + 1
    assert res.kv_pages_read == 3 + 3 + 3
    # 3 steps x 2 slots x 8-block table (64 / 8, already a power of two)
    assert res.kv_pages_table == 3 * 2 * 8
    assert res.kv_pages_read <= res.kv_pages_table


def test_request_larger_than_pool_rejected(engines):
    from repro.core.types import Request
    cfg, eng, _ = engines
    pcfg = PagedEngineConfig(max_batch=2, block_size=BS, n_blocks=3,
                             max_seq_len=64, max_new_tokens=12)
    peng = PagedEngine(cfg, eng.params, pcfg)
    # worst case ceil((30 + 12)/8) = 6 blocks > the 2 usable in the pool
    big = Request(rid=0, tokens=[1] * 30, input_len=30, slo=10.0,
                  arrival=0.0, true_output_len=12)
    with pytest.raises(ValueError, match="blocks"):
        peng.run_continuous([big])


def test_paged_incompatible_arch_rejected():
    cfg = get_config("minicpm3-4b").reduced()          # MLA latent cache
    ok, why = api.paged_compatible(cfg)
    assert not ok and why
    with pytest.raises(ValueError):
        api.init_paged_pools(cfg, 8, 8)


# ----------------------------------------------------------- block allocator

def test_allocator_exhaustion_and_reuse():
    a = BlockAllocator(4)
    assert a.can_alloc(4) and not a.can_alloc(5)
    a.alloc(1, 3)
    with pytest.raises(MemoryError):
        a.alloc(2, 2)
    assert a.free_seq(1) == 3
    assert a.can_alloc(4)
    blocks = a.alloc(2, 4)
    assert sorted(blocks) == [0, 1, 2, 3]


# ------------------------------------------------------ batched append scatter

def test_paged_kv_cache_batched_append_matches_per_token(rng):
    cfg = PagedKVConfig(n_blocks=8, block_size=4, n_kv_heads=2, head_dim=8)
    k_all = rng.standard_normal((11, 2, 8)).astype(np.float32)
    v_all = rng.standard_normal((11, 2, 8)).astype(np.float32)

    batched = PagedKVCache(cfg)
    batched.append(7, jnp.asarray(k_all[:6]), jnp.asarray(v_all[:6]))
    batched.append(7, jnp.asarray(k_all[6:]), jnp.asarray(v_all[6:]))

    loop = PagedKVCache(cfg)
    for t in range(11):
        loop.append(7, jnp.asarray(k_all[t:t + 1]), jnp.asarray(v_all[t:t + 1]))

    kb, vb, lb = batched.gather(7)
    kl, vl, ll = loop.gather(7)
    assert lb == ll == 11
    np.testing.assert_allclose(np.asarray(kb), np.asarray(kl))
    np.testing.assert_allclose(np.asarray(vb), np.asarray(vl))
    np.testing.assert_allclose(np.asarray(kb), k_all)
    np.testing.assert_allclose(np.asarray(vb), v_all)
