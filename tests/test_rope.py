"""Rotary positions: ChatGLM2's half-head rotary in interleaved pairs, the
default whole-head rotary in halves kept bit for bit, and the served paged
path of a ChatGLM2-shaped model agreeing with its full forward pass."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import MLAConfig, ModelConfig
from repro.core.types import Request
from repro.models import api
from repro.models import transformer as T
from repro.models.common import apply_rope, rope_freqs
from repro.serving import PagedEngine, PagedEngineConfig
from repro.serving.paged_engine import PagedDecodeState


def _rope_before(x, pos, theta):
    """``apply_rope`` as it was before the rotary width and pair layout
    became settings: the oracle of the default's bit-identity."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)
    angles = pos[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _glm_rotary(x, pos, base=10000.0):
    """numpy transcription of ChatGLM2's ``modeling_chatglm.py``:
    ``RotaryEmbedding(kv_channels // 2).forward_impl`` builds the cache in
    float32, ``apply_rotary_pos_emb`` rotates channel pairs of the first
    ``rot_dim = 2 * cache.shape[-2]`` channels and passes the rest.
    x [B, S, H, D]; pos [B, S]."""
    f32 = np.float32
    n_elem = x.shape[-1] // 2
    theta = f32(1.0) / (f32(base) ** (np.arange(0, n_elem, 2, dtype=f32)
                                      / f32(n_elem)))
    idx_theta = pos.astype(f32)[..., None] * theta          # [B, S, n/2]
    cache = np.stack([np.cos(idx_theta), np.sin(idx_theta)], -1)
    rot_dim = cache.shape[-2] * 2
    xr, x_pass = x[..., :rot_dim], x[..., rot_dim:]
    xs = xr.reshape(*xr.shape[:-1], rot_dim // 2, 2)
    c = cache[:, :, None]                                   # [B, S, 1, n/2, 2]
    out = np.stack([xs[..., 0] * c[..., 0] - xs[..., 1] * c[..., 1],
                    xs[..., 1] * c[..., 0] + xs[..., 0] * c[..., 1]], -1)
    return np.concatenate([out.reshape(xr.shape), x_pass], -1)


@pytest.mark.parametrize("d", [16, 128])
def test_pairs_match_chatglm2_apply_rotary_pos_emb(d):
    """Fraction 0.5 in pairs is ChatGLM2's rotary.  Both sides compute in
    float32, but numpy's and XLA's ``pow`` may round a frequency to
    neighbouring floats: at position p an angle then moves by up to
    p * 2**-23 rad, ~2.4e-4 at p ~2,000, so values of size <= 4 agree to
    1e-3 there and to 1e-5 at positions below 8.  A rotation in halves
    instead of pairs, or of the whole head, is off by O(1)."""
    rng = np.random.default_rng(15)
    x = rng.standard_normal((2, 6, 3, d)).astype(np.float32)
    pos = np.stack([np.arange(6), 1990 + np.arange(6)]).astype(np.int32)
    want = _glm_rotary(x, pos)
    got = apply_rope(jnp.asarray(x), jnp.asarray(pos), 10000.0, 0.5, True)
    np.testing.assert_allclose(np.asarray(got)[0], want[0], atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got)[1], want[1], atol=1e-3, rtol=0)
    assert np.array_equal(np.asarray(got)[..., d // 2:], x[..., d // 2:])
    halves = apply_rope(jnp.asarray(x), jnp.asarray(pos), 10000.0, 0.5)
    whole = apply_rope(jnp.asarray(x), jnp.asarray(pos), 10000.0, 1.0, True)
    for wrong in (halves, whole):
        assert np.abs(np.asarray(wrong) - want).max() > 0.5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("pos_shape", ["batch", "shared"])
@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_default_rope_is_bit_identical_to_before(dtype, pos_shape, jit):
    """Fraction 1 in halves (every model but ChatGLM2) computes exactly
    what the code did before the settings existed."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((3, 5, 4, 64)), dtype)
    pos = jnp.asarray(rng.integers(0, 4096, (3, 5) if pos_shape == "batch"
                                   else (5,)), jnp.int32)
    new, old = apply_rope, _rope_before
    if jit:
        new, old = jax.jit(new, static_argnums=2), jax.jit(old,
                                                           static_argnums=2)
    assert np.array_equal(np.asarray(new(x, pos, 1e6)),
                          np.asarray(old(x, pos, 1e6)))


def test_partial_halves_rotate_the_leading_channels():
    """Fraction 0.5 in halves rotates the first half of each head as a
    head of that width and passes the rest (a NeoX-style partial rotary)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, 4, 2, 32)), jnp.float32)
    pos = jnp.arange(4)
    got = np.asarray(apply_rope(x, pos, 1e4, 0.5))
    assert np.array_equal(got[..., :16],
                          np.asarray(_rope_before(x[..., :16], pos, 1e4)))
    assert np.array_equal(got[..., 16:], np.asarray(x[..., 16:]))


def test_rope_settings_are_checked():
    """The reduced ChatGLM2 preset keeps its fraction (8 of 16 channels);
    the settings are refused where nothing would read them."""
    cfg = get_config("chatglm2-6b")
    assert (cfg.rope_fraction, cfg.rope_interleaved) == (0.5, True)
    red = cfg.reduced()
    assert (red.head_dim_eff, red.rope_fraction, red.rope_interleaved) == \
        (16, 0.5, True)
    base = dict(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                n_kv_heads=2, d_ff=128, vocab_size=512, head_dim=16)
    for bad in (dict(rope="mrope", rope_interleaved=True),
                dict(rope="none", rope_fraction=0.5),
                dict(mla=MLAConfig(), rope_fraction=0.5),
                dict(rope_fraction=0.35),         # 5 channels: odd
                dict(rope_fraction=0.05)):        # no channel
        with pytest.raises(ValueError):
            ModelConfig(**base, **bad)


# --------------------------------------------------- the served paged path

BS = 4


@pytest.fixture(scope="module")
def glm():
    cfg = get_config("chatglm2-6b").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(15), jnp.float32)
    # nonzero q/k/v biases, as the published checkpoint has
    mixer = params["blocks"]["l0"]["mixer"]
    for i, n in enumerate(("q", "k", "v")):
        mixer[n]["b"] = 0.5 * jax.random.normal(
            jax.random.PRNGKey(100 + i), mixer[n]["b"].shape, jnp.float32)
    return cfg, params


def _requests(cfg):
    rng = np.random.default_rng(15)
    reqs = []
    for i, n in enumerate((13, 21, 9, 17)):
        pat = rng.integers(1, cfg.vocab_size, 5).tolist()
        toks = (pat * 5)[:n]            # repeats give the drafter matches
        reqs.append(Request(rid=i, tokens=toks, input_len=n, slo=60.0,
                            arrival=0.0, true_output_len=6 + 2 * i))
    return reqs


def _recording(engine, monkeypatch):
    """Wraps the engine's jitted prefill, decode and verify steps and the
    decode view so each call's logits can be matched to its requests."""
    seen = {"prefill": [], "step": [], "views": []}
    view = PagedDecodeState.masked_decode_view

    def recorded_view(st):
        seen["views"].append(({s: st.active[s].rid
                               for s in st.decoding_slots()},
                              st.null_block))
        return view(st)
    monkeypatch.setattr(PagedDecodeState, "masked_decode_view",
                        recorded_view)

    def wrap(name, fn):
        def call(params, toks, *a):
            logits, out = fn(params, toks, *a)
            # copies: on the CPU an upload may share the host array that
            # the engine goes on to change in place
            if name == "step":      # a: pools, tables, kv_len[, blk, off]
                blk = np.array(a[3]) if len(a) > 3 else None
                seen["step"].append((np.array(toks), np.array(a[2]), blk,
                                     np.array(logits)))
            else:
                prefix = a[2] if len(a) > 2 else None
                start = 0 if prefix is None else \
                    jax.tree.leaves(prefix)[0].shape[2]
                seen["prefill"].append((np.array(toks)[0, :int(a[0][0])],
                                        start, np.array(logits)[0]))
            return logits, out
        return call
    engine._prefill = wrap("prefill", engine._prefill)
    engine._prefill_suffix = wrap("prefill", engine._prefill_suffix)
    if engine.pcfg.spec_tokens:
        engine._verify = wrap("step", engine._verify)
    else:
        engine._decode = wrap("step", engine._decode)
    return seen


@pytest.mark.parametrize("mode", ["unchunked", "chunked", "speculative"])
def test_paged_path_matches_full_forward(glm, mode, monkeypatch):
    """Prefill (whole, or in chunks through the continuation prefill), then
    paged decode steps or speculative verify windows, through
    ``PagedEngine.run_continuous``: every logit row the engine computed
    equals the full forward's at the same position of the same tokens.
    All float32; the paths differ in summation order only (attention over
    pages and chunks against one softmax over the sequence), ~1e-6 here,
    so 1e-4.  A rotary position off by one in any path moves logits by
    ~1e-1."""
    cfg, params = glm
    pcfg = PagedEngineConfig(
        max_batch=2, block_size=BS, n_blocks=40, max_seq_len=48,
        max_new_tokens=16, chunk_tokens=2 * BS if mode == "chunked" else 0,
        spec_tokens=3 if mode == "speculative" else 0)
    engine = PagedEngine(cfg, params, pcfg)
    seen = _recording(engine, monkeypatch)
    reqs = _requests(cfg)
    res = engine.run_continuous([copy.copy(r) for r in reqs])
    seqs = {r.rid: list(r.tokens) + res.outputs[r.rid] for r in reqs}
    fwd = jax.jit(lambda t: T.lm_forward(cfg, params, t)[0][0])

    def forward(tokens):        # causal: padding past the end reads nothing
        padded = np.zeros((1, pcfg.max_seq_len), np.int32)
        padded[0, :len(tokens)] = tokens
        return np.asarray(fwd(jnp.asarray(padded)))[:len(tokens)]

    full = {rid: forward(s) for rid, s in seqs.items()}
    tol = dict(atol=1e-4, rtol=1e-4)
    n_pref = 0
    for toks, start, logits in seen["prefill"]:
        rid, = [r.rid for r in reqs
                if r.tokens[start:start + len(toks)] == toks.tolist()]
        np.testing.assert_allclose(logits, full[rid][start + len(toks) - 1],
                                   **tol)
        n_pref += 1
    assert n_pref >= len(reqs) + (mode == "chunked")
    assert len(seen["views"]) == len(seen["step"]) == res.steps
    rows = 0
    for (live, null), (toks, kv, blk, logits) in zip(seen["views"],
                                                    seen["step"]):
        for slot, rid in live.items():
            n = int(kv[slot])
            # a verify window's positions past the slot's own window write
            # to the null block and are never read
            w = toks.shape[1] if blk is None else int(
                np.sum(blk[slot] != null))
            window = toks[slot, :w].tolist()
            got = logits[slot].reshape(toks.shape[1], -1)[:w]
            want = forward(seqs[rid][:n] + window)[n:]
            assert window[0] == seqs[rid][n]
            np.testing.assert_allclose(got, want, **tol)
            rows += len(window)
    assert rows >= sum(len(o) for o in res.outputs.values()) - len(reqs)
    if mode == "speculative":
        assert res.drafted_tokens > 0
