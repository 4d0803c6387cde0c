"""WKV6 kernel sweeps: chunked XLA + Pallas (interpret) vs the sequential
oracle, including the strong-decay numerics regime and the decode-step chain."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.wkv6.ref import wkv6_reference
from repro.kernels.wkv6.wkv6 import wkv6_pallas
from repro.kernels.wkv6.xla import wkv6_step, wkv6_xla

CASES = [(2, 64, 3, 16, 16, 16), (1, 50, 2, 8, 8, 16), (2, 33, 4, 32, 32, 8),
         (1, 128, 2, 64, 64, 32)]


def _gen(rng, b, t, h, d, dv, decay_scale=2.0):
    r = rng.standard_normal((b, t, h, d)).astype(np.float32) * 0.5
    k = rng.standard_normal((b, t, h, d)).astype(np.float32) * 0.5
    v = rng.standard_normal((b, t, h, dv)).astype(np.float32)
    w = np.exp(-np.exp(rng.standard_normal((b, t, h, d)) * decay_scale)
               ).astype(np.float32)
    u = (rng.standard_normal((h, d)) * 0.3).astype(np.float32)
    return r, k, v, w, u


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_wkv6_matches_oracle(rng, case, impl):
    b, t, h, d, dv, chunk = case
    r, k, v, w, u = _gen(rng, b, t, h, d, dv)
    o_ref, s_ref = wkv6_reference(r, k, v, w, u)
    if impl == "xla":
        o, s = wkv6_xla(r, k, v, w, u, chunk=chunk)
    else:
        o, s = wkv6_pallas(r, k, v, w, u, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=5e-5, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=5e-5, rtol=5e-4)


def test_wkv6_extreme_decay_stable(rng):
    """w near 0 (instant forget) must not produce inf/nan — the pairwise
    log-space formulation is what makes the chunked kernel safe."""
    r, k, v, w, u = _gen(rng, 1, 48, 2, 16, 16, decay_scale=4.0)
    w = np.minimum(w, 1e-6).astype(np.float32)
    o, s = wkv6_xla(r, k, v, w, u, chunk=16)
    assert np.isfinite(np.asarray(o)).all()
    o_ref, _ = wkv6_reference(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=5e-5,
                               rtol=5e-4)


def test_wkv6_step_chain_matches_scan(rng):
    b, t, h, d, dv = 2, 12, 3, 16, 16
    r, k, v, w, u = _gen(rng, b, t, h, d, dv)
    o_ref, s_ref = wkv6_reference(r, k, v, w, u)
    s = jnp.zeros((b, h, d, dv))
    outs = []
    for i in range(t):
        o, s = wkv6_step(r[:, i], k[:, i], v[:, i], w[:, i], u, s)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(o_ref), atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=1e-5,
                               rtol=1e-4)


def test_wkv6_carried_state(rng):
    """Processing in two halves with carried state == one shot."""
    b, t, h, d, dv = 1, 64, 2, 16, 16
    r, k, v, w, u = _gen(rng, b, t, h, d, dv)
    o_full, s_full = wkv6_xla(r, k, v, w, u, chunk=16)
    o1, s1 = wkv6_xla(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u, chunk=16)
    o2, s2 = wkv6_xla(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u, s1, chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                               np.asarray(o_full), atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=2e-5,
                               rtol=2e-4)


def test_wkv6_pallas_carried_state(rng):
    """The kernel starts from a carried state s0 (no fallback to the XLA
    path): two halves with the state handed over == one shot."""
    b, t, h, d, dv = 1, 64, 2, 16, 16
    r, k, v, w, u = _gen(rng, b, t, h, d, dv)
    o_full, s_full = wkv6_reference(r, k, v, w, u)
    o1, s1 = wkv6_pallas(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u,
                         chunk=16, interpret=True)
    o2, s2 = wkv6_pallas(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u, s1,
                         chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                               np.asarray(o_full), atol=5e-5, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=5e-5,
                               rtol=5e-4)
