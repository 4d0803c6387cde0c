"""Paged decode-attention kernels over head-major ``[KV, N, bs, D]`` pools:
block-table gather parity against the contiguous decode oracle, across the xla / pallas-interpret backends, with
padded (null-block) table tails; multi-token window parity (speculative
verification) and the power-of-two block-table bucketing that caps jit
specialization churn.  Head dims below 128 take the page walk; the
live-page sweep (head dim 128) is checked on bf16 pools at the edges of a
compute block, and against NaN-filled dead blocks.  Cases that give a
stack of layers read one layer of a ``[L, KV, N, bs, D]`` pool whose other
layers are NaN, as the model step hands the kernel its pool."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ref import decode_attention_reference
from repro.kernels.paged_attention.paged_attention import (
    _paged_window_core, bucket_nb, live_pages, pages_per_block,
    paged_decode_attention_pallas, paged_window_attention_pallas)
from repro.kernels.paged_attention.ref import (
    gather_pool, paged_decode_attention_reference,
    paged_window_attention_reference)
from repro.kernels.paged_attention.xla import (paged_decode_attention_xla,
                                               paged_window_attention_xla)

# (b, h, kv, d, block_size, logical_blocks, n_phys_blocks, softcap
#  [, (n_layers, layer)] -- a stacked pool read at that layer)
CASES = [
    (2, 4, 2, 16, 8, 4, 16, None),
    (3, 6, 3, 8, 16, 3, 24, 50.0),
    (1, 8, 8, 32, 4, 6, 32, None),
    (4, 16, 2, 64, 16, 2, 48, None),
    # head dim 64 walks the pages, 128 sweeps them: first, middle, last
    (4, 16, 2, 64, 16, 2, 48, None, (3, 0)),
    (2, 12, 2, 128, 8, 20, 48, None, (3, 0)),
    (4, 16, 2, 64, 16, 2, 48, 50.0, (3, 1)),
    (2, 12, 2, 128, 8, 20, 48, 50.0, (3, 1)),
    (4, 16, 2, 64, 16, 2, 48, None, (3, 2)),
    (2, 12, 2, 128, 8, 20, 48, None, (3, 2)),
]


def _stack(pool, stack):
    """Pool for a case: ``pool`` itself and layer 0, or ``pool`` as layer
    ``layer`` of ``n_layers`` whose other layers are NaN."""
    if not stack:
        return pool, 0
    n_layers, layer = stack
    out = np.full((n_layers,) + pool.shape, np.nan, pool.dtype)
    out[layer] = pool
    return out, layer


def _mk(rng, case):
    b, h, kv, d, bs, nb, n, cap, *stack = case
    stack = stack[0] if stack else None
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    kp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    vp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    bt = rng.permutation(n)[:b * nb].reshape(b, nb).astype(np.int32)
    kv_len = rng.integers(1, nb * bs + 1, size=b).astype(np.int32)
    ref = decode_attention_reference(
        q, gather_pool(jnp.asarray(kp), jnp.asarray(bt)),
        gather_pool(jnp.asarray(vp), jnp.asarray(bt)), kv_len, softcap=cap)
    (kp, layer), (vp, _) = (_stack(p, stack) for p in (kp, vp))
    return q, kp, vp, bt, kv_len, layer, cap, ref


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("impl", ["ref", "xla", "pallas"])
def test_paged_matches_contiguous_oracle(rng, case, impl):
    q, kp, vp, bt, kv_len, layer, cap, ref = _mk(rng, case)
    if impl == "ref":
        out = paged_decode_attention_reference(q, kp, vp, bt, kv_len, layer,
                                               softcap=cap)
    elif impl == "xla":
        out = paged_decode_attention_xla(q, kp, vp, bt, kv_len, layer,
                                         softcap=cap)
    else:
        out = paged_decode_attention_pallas(
            q, kp, vp, jnp.asarray(bt), jnp.asarray(kv_len), layer,
            softcap=cap, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_padded_table_tail_is_inert(rng, impl):
    """Block-table entries past kv_len point at a 'null' physical block the
    serving runtime reuses for every free slot; whatever garbage it holds
    must not leak into the output."""
    b, h, kv, d, bs, nb, n = 2, 4, 2, 16, 8, 4, 16
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    kp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    vp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    bt = (1 + rng.permutation(n - 1)[:b * nb].reshape(b, nb)).astype(np.int32)
    kv_len = np.array([bs + 3, 2 * bs], np.int32)   # <= 2 blocks valid
    fn = paged_decode_attention_xla if impl == "xla" else (
        lambda *a, **k: paged_decode_attention_pallas(*a, interpret=True, **k))
    out1 = np.asarray(fn(q, kp, vp, jnp.asarray(bt), jnp.asarray(kv_len)))
    # retarget the invalid tail at block 0 and scramble block 0's contents
    bt2 = bt.copy()
    bt2[:, 2:] = 0
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[:, 0] = 1e3
    vp2[:, 0] = -1e3
    out2 = np.asarray(fn(q, kp2, vp2, jnp.asarray(bt2), jnp.asarray(kv_len)))
    np.testing.assert_allclose(out1, out2, atol=2e-5, rtol=2e-5)


# ------------------------------------------------- multi-token window kernel

# (b, h, kv, d, block_size, logical_blocks, n_phys_blocks, softcap
#  [, (n_layers, layer)])
WINDOW_CASES = [
    (2, 4, 2, 16, 8, 4, 16, None),       # group 2: the T fold packs rows
    (3, 6, 3, 8, 16, 3, 24, 50.0),       # softcap + group 2 over 3 kv heads
    (1, 8, 8, 32, 4, 6, 32, None),       # MHA (group 1)
    (2, 16, 2, 64, 16, 2, 48, None),     # wide GQA group 8
    # stacked pools: the sweep at the first layer and the middle one, the
    # page walk at the last
    (2, 12, 2, 128, 8, 20, 48, None, (3, 0)),
    (2, 12, 2, 128, 8, 20, 48, 50.0, (3, 1)),
    (2, 16, 2, 64, 16, 2, 48, None, (3, 2)),
]


def _mk_window(rng, case, t):
    b, h, kv, d, bs, nb, n, cap, *stack = case
    stack = stack[0] if stack else None
    q = rng.standard_normal((b, t, h, d)).astype(np.float32)
    kp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    vp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    bt = rng.permutation(n)[:b * nb].reshape(b, nb).astype(np.int32)
    # ragged histories: every sequence a different base length, window fits
    base = rng.integers(0, nb * bs - t + 1, size=b).astype(np.int32)
    ref = paged_window_attention_reference(q, kp, vp, bt, base, softcap=cap)
    (kp, layer), (vp, _) = (_stack(p, stack) for p in (kp, vp))
    return q, kp, vp, bt, base, layer, cap, ref


@pytest.mark.parametrize("case", WINDOW_CASES)
@pytest.mark.parametrize("t", [1, 2, 4, 8])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_window_matches_reference(rng, case, t, impl):
    """[B, T, H, D] verify window: causal against the paged history and the
    window itself, for ragged kv_len and GQA groups."""
    q, kp, vp, bt, base, layer, cap, ref = _mk_window(rng, case, t)
    if impl == "xla":
        out = paged_window_attention_xla(q, kp, vp, jnp.asarray(bt),
                                         jnp.asarray(base), layer,
                                         softcap=cap)
    else:
        out = paged_window_attention_pallas(
            q, kp, vp, jnp.asarray(bt), jnp.asarray(base), layer,
            softcap=cap, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=2e-5)


@pytest.mark.parametrize("case", WINDOW_CASES)
def test_window_t1_reproduces_single_token_kernel(rng, case):
    """T=1 at base kv_len-1 must be *exactly* the single-token paged decode
    kernel — same core, same row layout, bitwise."""
    b, h, kv, d, bs, nb, n, cap, *stack = case
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    kp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    vp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    (kp, layer), (vp, _) = (_stack(p, stack[0] if stack else None)
                            for p in (kp, vp))
    bt = rng.permutation(n)[:b * nb].reshape(b, nb).astype(np.int32)
    kv_len = rng.integers(1, nb * bs + 1, size=b).astype(np.int32)
    single = paged_decode_attention_pallas(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(kv_len), layer, softcap=cap,
        interpret=True)
    window = paged_window_attention_pallas(
        q[:, None], kp, vp, jnp.asarray(bt), jnp.asarray(kv_len) - 1, layer,
        softcap=cap, interpret=True)[:, 0]
    np.testing.assert_array_equal(np.asarray(single), np.asarray(window))


def test_window_causality_within_window(rng):
    """Window position t must not see positions > kv_len + t: scrambling a
    later draft's K/V cannot change an earlier position's output."""
    b, h, kv, d, bs, nb, n, t = 1, 4, 2, 16, 8, 3, 12, 4
    q = rng.standard_normal((b, t, h, d)).astype(np.float32)
    kp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    vp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    bt = rng.permutation(n)[:nb].reshape(1, nb).astype(np.int32)
    base = np.array([5], np.int32)
    out1 = np.asarray(paged_window_attention_xla(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(base)))
    # scramble the *last* window position's K/V slot (logical pos base+t-1)
    pos = int(base[0]) + t - 1
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[:, bt[0, pos // bs], pos % bs] = 1e3
    vp2[:, bt[0, pos // bs], pos % bs] = -1e3
    out2 = np.asarray(paged_window_attention_xla(
        q, kp2, vp2, jnp.asarray(bt), jnp.asarray(base)))
    np.testing.assert_array_equal(out1[:, :t - 1], out2[:, :t - 1])
    assert np.abs(out1[:, t - 1] - out2[:, t - 1]).max() > 1.0


@pytest.mark.parametrize("t", [1, 3])
def test_window_padded_table_tail_is_inert(rng, t):
    b, h, kv, d, bs, nb, n = 2, 4, 2, 16, 8, 4, 16
    q = rng.standard_normal((b, t, h, d)).astype(np.float32)
    kp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    vp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    bt = (1 + rng.permutation(n - 1)[:b * nb].reshape(b, nb)).astype(np.int32)
    base = np.array([bs + 3 - t, 2 * bs - t], np.int32)
    out1 = np.asarray(paged_window_attention_pallas(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(base), interpret=True))
    bt2 = bt.copy()
    bt2[:, 2:] = 0
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[:, 0] = 1e3
    vp2[:, 0] = -1e3
    out2 = np.asarray(paged_window_attention_pallas(
        q, kp2, vp2, jnp.asarray(bt2), jnp.asarray(base), interpret=True))
    np.testing.assert_allclose(out1, out2, atol=2e-5, rtol=2e-5)


# --------------------------------------------- jit specialization bucketing

def test_block_table_width_buckets_cap_compiles(rng):
    """Block-table widths are padded to a power-of-two bucket *outside* the
    jit boundary, so every width in one bucket shares one compilation —
    without this the kernel respecializes per distinct nb."""
    b, h, kv, d, bs, n = 2, 4, 2, 16, 8, 64
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    kp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    vp = rng.standard_normal((kv, n, bs, d)).astype(np.float32)
    outs = {}
    before = _paged_window_core._cache_size()
    for nb in (5, 6, 7, 8):
        bt = rng.permutation(n)[:b * nb].reshape(b, nb).astype(np.int32)
        kv_len = np.minimum(np.array([nb * bs - 2, nb * bs], np.int32),
                            nb * bs)
        outs[nb] = paged_decode_attention_pallas(
            q, kp, vp, jnp.asarray(bt), jnp.asarray(kv_len), interpret=True)
    added = _paged_window_core._cache_size() - before
    assert added == 1, f"nb in 5..8 should share one bucket, added {added}"
    assert all(bucket_nb(nb) == 8 for nb in (5, 6, 7, 8))
    # and the padding itself must be inert: bucketed result == exact result
    nb = 5
    bt = rng.permutation(n)[:b * nb].reshape(b, nb).astype(np.int32)
    kv_len = np.array([nb * bs - 3, nb * bs], np.int32)
    got = paged_decode_attention_pallas(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(kv_len), interpret=True)
    ref = paged_decode_attention_reference(q, kp, vp, bt, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_reads_through_permuted_tables(rng):
    """Same logical sequences under two different physical placements must
    produce identical outputs — the defining property of paging."""
    b, h, kv, d, bs, nb, n = 2, 4, 2, 16, 8, 3, 32
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    seq = rng.standard_normal((b, nb * bs, kv, d)).astype(np.float32)
    val = rng.standard_normal((b, nb * bs, kv, d)).astype(np.float32)
    kv_len = np.array([nb * bs, nb * bs - 5], np.int32)
    outs = []
    for seed in (0, 1):
        r2 = np.random.default_rng(seed)
        bt = r2.permutation(n)[:b * nb].reshape(b, nb).astype(np.int32)
        kp = np.zeros((kv, n, bs, d), np.float32)
        vp = np.zeros((kv, n, bs, d), np.float32)
        for i in range(b):
            for j in range(nb):
                kp[:, bt[i, j]] = seq[i, j * bs:(j + 1) * bs].swapaxes(0, 1)
                vp[:, bt[i, j]] = val[i, j * bs:(j + 1) * bs].swapaxes(0, 1)
        outs.append(np.asarray(paged_decode_attention_xla(
            q, kp, vp, jnp.asarray(bt), jnp.asarray(kv_len))))
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-5, rtol=2e-5)


# ------------------------------------- live-page sweep (lane-wide head dims)

BS_SWEEP, NB_SWEEP, KV_SWEEP, D_SWEEP = 8, 32, 2, 128
PPC = pages_per_block(BS_SWEEP, NB_SWEEP)
# one row per edge of the sweep: a single key, one short of / exactly / one
# past a compute block, and the whole bucketed table
SWEEP_LENGTHS = [1, PPC * BS_SWEEP - 1, PPC * BS_SWEEP, PPC * BS_SWEEP + 1,
                 NB_SWEEP * BS_SWEEP]


def _sweep_case(rng, group, t, softcap, poison):
    """bf16 pools laid out as the engine lays them: block 0 is the null
    block, each row's table lists its live blocks and then the null block.
    Row i attends ``SWEEP_LENGTHS[i]`` keys (its window's last position
    included).  With ``poison`` every physical block outside the rows' live
    prefixes, the null block among them, is NaN.  Returns the kernel's
    output and the reference's, which reads the clean pools."""
    b, h = len(SWEEP_LENGTHS), group * KV_SWEEP
    base = (np.maximum(np.array(SWEEP_LENGTHS), t) - t).astype(np.int32)
    n = 1 + b * NB_SWEEP
    kp, vp = (rng.standard_normal((KV_SWEEP, n, BS_SWEEP, D_SWEEP)).astype(
        jnp.bfloat16) for _ in range(2))
    owned = 1 + rng.permutation(n - 1).reshape(b, NB_SWEEP)
    live = live_pages(base, t, BS_SWEEP)
    bt = np.zeros((b, NB_SWEEP), np.int32)
    for i in range(b):
        bt[i, :live[i]] = owned[i, :live[i]]
    q = rng.standard_normal((b, t, h, D_SWEEP)).astype(np.float32)
    ref = paged_window_attention_reference(
        q, jnp.asarray(kp), jnp.asarray(vp), bt, base, softcap=softcap)
    if poison:
        dead = np.setdiff1d(np.arange(n), bt[bt > 0])
        assert 0 in dead
        kp[:, dead] = np.nan
        vp[:, dead] = np.nan
    out = paged_window_attention_pallas(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(base), softcap=softcap, interpret=True)
    return np.asarray(out), np.asarray(ref), (q, kp, vp, bt, base)


@pytest.mark.parametrize("softcap", [None, 30.0])
@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("group", [6, 16])
def test_sweep_matches_reference_at_block_edges(rng, group, t, softcap):
    """Lane-wide heads take the live-page sweep: parity with the reference
    on bf16 pools at every edge of a compute block, for the groups of
    qwen2-1.5b (6) and chatglm2-6b (16), decode and a verify window."""
    out, ref, (q, kp, vp, bt, base) = _sweep_case(rng, group, t, softcap,
                                                  poison=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    if t == 1:
        single = paged_decode_attention_pallas(
            q[:, 0], jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
            jnp.asarray(base) + 1, softcap=softcap, interpret=True)
        np.testing.assert_array_equal(np.asarray(single), out[:, 0])


@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("group", [6, 16])
def test_sweep_never_reads_dead_pages(rng, group, t):
    """Every block outside the live prefixes, the null block included, is
    NaN, and the output is finite and the reference's; with the dead table
    entries pointing past the end of the pool the output is unchanged."""
    out, ref, (q, kp, vp, bt, base) = _sweep_case(rng, group, t, 30.0,
                                                  poison=True)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # a masked read would hide a NaN; a table entry past the end of the
    # pool cannot be copied at all (the interpreter raises on it)
    past_end = np.where(bt > 0, bt, kp.shape[1]).astype(np.int32)
    out2 = paged_window_attention_pallas(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(past_end),
        jnp.asarray(base), softcap=30.0, interpret=True)
    np.testing.assert_array_equal(np.asarray(out2), out)


def test_live_pages_bounds_the_sweep():
    """The page count the engine reports is the kernel's own bound."""
    base = np.array([-1, 0, 7, 8, 120], np.int32)
    np.testing.assert_array_equal(live_pages(base, 1, 8), [0, 1, 1, 2, 16])
    np.testing.assert_array_equal(live_pages(base, 4, 8), [1, 1, 2, 2, 16])
    assert pages_per_block(8, 256) == 16
    assert pages_per_block(8, 4) == 4
    assert pages_per_block(16, 256) == 8
