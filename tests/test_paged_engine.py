"""Paged serving runtime: token equivalence with the dense greedy reference,
true continuous admission (prefill proportional to prompts, never to slots),
allocator exhaustion/backpressure, and the batched PagedKVCache scatter."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import dense_greedy
from repro.configs import get_config
from repro.data.workload import WorkloadConfig, gen_requests
from repro.models import api
from repro.serving import PagedEngine, PagedEngineConfig
from repro.serving.kv_cache import (BlockAllocator, PagedKVCache,
                                    PagedKVConfig)

BS = 8          # KV block size used throughout


def _paged(cfg, params):
    return PagedEngine(cfg, params,
                       PagedEngineConfig(max_batch=4, block_size=BS,
                                         n_blocks=64, max_seq_len=64,
                                         max_new_tokens=12))


@pytest.fixture(scope="module")
def engines():
    """(cfg, params, paged engine) of the reduced smollm-135m."""
    cfg = get_config("smollm-135m").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params, _paged(cfg, params)


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


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen2-1.5b",
                                  "chatglm2-6b", "qwen2-vl-7b"])
def test_paged_matches_dense_reference(arch):
    """Greedy paged continuous batching emits the exact token streams of the
    dense greedy reference (each request decoded alone) for the same
    requests, on every paged-compatible attention family."""
    cfg = get_config(arch).reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    reqs = _reqs(cfg, 4)
    res = _paged(cfg, params).run_continuous(reqs)
    ref = dense_greedy(cfg, params, reqs, 12)
    for r in reqs:
        assert res.outputs[r.rid] == ref[r.rid], r.rid


def test_paged_prefill_proportional_to_prompts(engines):
    """No full-slot re-prefill: admitted prompts are prefilled individually,
    so prefill token count is exactly the (block-padded) sum of prompt
    lengths — independent of how many admission waves slot recycling takes."""
    cfg, _, peng = engines
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
    still decode exactly as each would decode alone."""
    cfg, params, peng = engines
    reqs = _reqs(cfg, 7)
    res_c = peng.run_continuous(reqs)
    late = reqs[4:]
    ref = dense_greedy(cfg, params, late, 12)
    for r in late:
        assert ref[r.rid] == res_c.outputs[r.rid], r.rid


def test_block_backpressure_defers_admission(engines):
    """A pool that cannot hold all requests at once admits in waves gated on
    BlockAllocator.can_alloc, never exceeds the pool, and still serves
    everything."""
    cfg, params, _ = engines
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
    # outputs unchanged vs the dense reference
    ref = dense_greedy(cfg, params, reqs, 12)
    for r in reqs:
        assert ref[r.rid] == res.outputs[r.rid], r.rid


def test_single_token_request_admitted_mid_run(engines):
    """A request whose entire output is its prefill token (stop count 1),
    admitted into a recycled slot mid-run, must not receive an extra decode
    token before the finish scan sees it."""
    cfg, params, _ = engines
    pcfg = PagedEngineConfig(max_batch=2, block_size=BS, n_blocks=32,
                             max_seq_len=64, max_new_tokens=12)
    peng = PagedEngine(cfg, params, pcfg)
    reqs = _reqs(cfg, 3)
    reqs[0].true_output_len = 2
    reqs[1].true_output_len = 6
    reqs[2].true_output_len = 1      # admitted only after slot 0 recycles
    res = peng.run_continuous(reqs)
    for r in reqs:
        assert len(res.outputs[r.rid]) == r.true_output_len, r.rid
    ref = dense_greedy(cfg, params, reqs, 12)
    for r in reqs:
        assert ref[r.rid] == res.outputs[r.rid], r.rid


def test_kv_page_counters_sum_the_kernels_reads(engines):
    """``kv_pages_read`` sums, over decode steps and slots, the table pages
    the paged kernel's bound reads: a slot at history length kv reads the
    pages holding positions 0..kv (the new token's included), a free slot
    its null block.  ``kv_pages_table`` is what a walk of the whole bucketed
    table reads."""
    cfg, params, _ = engines
    pcfg = PagedEngineConfig(max_batch=2, block_size=BS, n_blocks=32,
                             max_seq_len=64, max_new_tokens=12)
    peng = PagedEngine(cfg, params, pcfg)
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
    cfg, params, _ = engines
    pcfg = PagedEngineConfig(max_batch=2, block_size=BS, n_blocks=3,
                             max_seq_len=64, max_new_tokens=12)
    peng = PagedEngine(cfg, params, pcfg)
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


# --------------------------------------- the decode step updates pools in place

def _stacked_step_case(rng, t):
    """A 3-layer qwen2-shaped model at head dim 128 (the kernel's live-page
    sweep), random pools, and four rows: two live, one a free slot whose
    table is all null block 0, and, for a window (t > 1), one whose last
    position points at the null block as the engine's past-budget ones do.
    Returns (cfg, params, tokens, pools, tables, kv_len, blk, off)."""
    from dataclasses import replace
    cfg = replace(get_config("qwen2-1.5b").reduced(n_layers=3),
                  head_dim=128, n_kv_heads=2)
    params = api.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    n_blocks, nb = 16, 4
    pools = api.init_paged_pools(cfg, n_blocks, BS, jnp.float32)
    pools = jax.tree.map(lambda p: jnp.asarray(
        rng.standard_normal(p.shape).astype(np.float32)), pools)
    tables = np.zeros((4, nb), np.int32)
    tables[0, :2] = [3, 7]
    tables[1, :3] = [5, 1, 12]
    tables[3, :1] = [9]
    kv_len = np.array([BS + 2, 2 * BS - 2, 0, 3], np.int32)
    pos = kv_len[:, None] + np.arange(t)[None, :]
    blk = tables[np.arange(4)[:, None], pos // BS]
    off = (pos % BS).astype(np.int32)
    blk[2], off[2] = 0, 0                       # the free slot
    if t > 1:
        blk[3, -1], off[3, -1] = 0, 0           # past the row's budget
    tokens = rng.integers(0, cfg.vocab_size, (4, t)).astype(np.int32)
    return cfg, params, tokens, pools, tables, kv_len, blk, off


def _per_layer_step(cfg, params, tokens, pools, tables, kv_len,
                    spec_scatter):
    """The decode step with each layer's pool sliced out of the stack,
    updated on its own and written back, layer by layer: the per-layer
    dataflow that the scan's carried pool replaces."""
    from repro.models import transformer as T
    from repro.models.common import apply_norm
    period = T.group_period(cfg)
    specs = cfg.layer_plan()[:period]
    x = T.embed_tokens(cfg, params, tokens)
    for g in range(cfg.n_layers // period):
        gp = jax.tree.map(lambda w: w[g], params["blocks"])
        for i in range(period):
            one = jax.tree.map(lambda p: p[g:g + 1], pools[f"l{i}"])
            x, nc, _ = T.block_apply(
                cfg, specs[i], gp[f"l{i}"], x, positions=None, plan=None,
                cache=one, kv_len=kv_len, mode="decode", cache_len=0,
                block_tables=tables, spec_scatter=spec_scatter,
                layer=jnp.int32(0))
            pools = {**pools, f"l{i}": jax.tree.map(
                lambda p, n: p.at[g].set(n[0]), pools[f"l{i}"], nc)}
    x = apply_norm(cfg, params["final_norm"], x)
    return T.lm_head(cfg, params, x if spec_scatter else x[:, 0]), pools


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("t", [1, 3])
def test_step_writes_only_each_rows_slot(rng, t, backend):
    """One ``paged_decode_step`` (t = 1) or ``paged_spec_step`` (t = 3):
    the pools change only at ``[g, :, blk, off, :]`` of each live row's
    positions, in every group g; the free slot and the past-budget position
    write only the null block; logits and pools equal the per-layer
    dataflow's on the same inputs."""
    from repro.kernels.backend import use_backend
    cfg, params, tokens, pools, tables, kv_len, blk, off = \
        _stacked_step_case(rng, t)
    args = (jnp.asarray(tokens), pools, jnp.asarray(tables),
            jnp.asarray(kv_len))
    spec_scatter = None
    with use_backend(backend):
        if t == 1:
            logits, new = api.paged_decode_step(cfg, params, *args)
        else:
            spec_scatter = (jnp.asarray(blk), jnp.asarray(off))
            logits, new = api.paged_spec_step(cfg, params, *args,
                                              *spec_scatter)
        ref_logits, ref_pools = _per_layer_step(
            cfg, params, *args, spec_scatter)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               atol=1e-5, rtol=1e-5)
    live = {(int(b_), int(o_)) for b_, o_ in zip(blk.ravel(), off.ravel())
            if b_ != 0}
    for old, got, ref in zip(jax.tree.leaves(pools), jax.tree.leaves(new),
                             jax.tree.leaves(ref_pools)):
        old, got = np.asarray(old), np.asarray(got)
        np.testing.assert_allclose(got, np.asarray(ref), atol=1e-5,
                                   rtol=1e-5)
        changed = np.any(got != old, axis=(1, 4))          # [G, N, bs]
        for g in range(changed.shape[0]):
            where = {(int(b_), int(o_))
                     for b_, o_ in np.argwhere(changed[g])}
            assert live <= where, (g, live - where)
            assert {w for w in where if w[0] != 0} == live, g
