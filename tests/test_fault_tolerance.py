"""Fault-tolerance control plane: heartbeats, elastic topology planning,
straggler mitigation; plus gradient compression numerics."""
import numpy as np
import pytest

from repro.distributed import (ElasticTopology, HeartbeatTracker,
                               StragglerMitigator)
from repro.training.grad_compress import (dequantize_int8, quantize_int8,
                                          topk_densify, topk_sparsify)


def test_heartbeat_failure_detection():
    hb = HeartbeatTracker(timeout=10.0)
    hb.beat(0, now=0.0)
    hb.beat(1, now=0.0)
    hb.beat(0, now=8.0)
    assert hb.failed(now=12.0) == [1]
    assert hb.healthy(now=12.0) == [0]


def test_elastic_drops_failed_pod():
    topo = ElasticTopology(pods=2, hosts_per_pod=64)
    plan = topo.plan_after_failures({70})      # host 70 -> pod 1
    assert plan["pods"] == [0]
    assert plan["mesh_shape"] == (1, 16, 16)
    assert not plan["degraded"]


def test_elastic_shrinks_when_all_pods_hit():
    topo = ElasticTopology(pods=2, hosts_per_pod=64)
    plan = topo.plan_after_failures({3, 70})
    assert plan["degraded"]
    assert plan["mesh_shape"][0] == 2
    assert plan["mesh_shape"][1] < 16


def test_straggler_mitigation():
    sm = StragglerMitigator(factor=1.5)
    for r in range(8):
        for _ in range(5):
            sm.record(r, 1.0 if r != 3 else 2.5)
    drained = sm.mitigate()
    assert drained == [3]
    assert 3 not in sm.active_replicas()
    # median unaffected afterwards
    assert abs(sm.median() - 1.0) < 1e-6


def test_int8_grad_compression_error():
    rng = np.random.default_rng(0)
    g = rng.standard_normal((256, 256)).astype(np.float32) * 0.01
    q, s = quantize_int8(g)
    g2 = np.asarray(dequantize_int8(q, s))
    rel = np.abs(g2 - g).mean() / np.abs(g).mean()
    assert rel < 0.03                      # absmax int8 on gaussians: ~1-2%
    assert np.asarray(q).dtype == np.int8


def test_topk_sparsify_roundtrip():
    rng = np.random.default_rng(1)
    g = rng.standard_normal((64, 64)).astype(np.float32)
    payload, residual = topk_sparsify(g, frac=0.1)
    dense = np.asarray(topk_densify(payload))
    # kept + residual reconstructs exactly
    np.testing.assert_allclose(dense + np.asarray(residual), g, atol=1e-6)
    assert (dense != 0).sum() <= int(g.size * 0.1) + 1


def test_dp_mean_compressed_single_device():
    """shard_map int8 DP-mean on a 1-device mesh == plain mean (degenerate
    but exercises the collective path)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.training.grad_compress import dp_mean_compressed

    mesh = jax.make_mesh((1,), ("dp",), (jax.sharding.AxisType.Auto,))
    g = {"w": jnp.ones((8, 8)) * 0.5}

    def f(grads):
        return dp_mean_compressed(grads, "dp")

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=({"w": P()},),
                            out_specs={"w": P()}, check_vma=False))(g)
    np.testing.assert_allclose(np.asarray(out["w"]), 0.5, atol=5e-3)
