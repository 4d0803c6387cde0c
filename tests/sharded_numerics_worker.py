"""Subprocess worker for multi-device numerical tests: runs the sharded
execution paths (TP shard_map MoE, EP all-to-all, seq-sharded flash-decoding,
head-TP decode, sequence-parallel prefill) on 8 placeholder CPU devices and
compares against the unsharded single-device reference.

Launched by tests/test_sharded_numerics.py in its own process because the
main pytest process must keep the real 1-device CPU view.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.models import api
from repro.sharding.plan import ShardingPlan
from repro.sharding.specs import cache_specs_tree, param_specs


def check(arch: str) -> float:
    import dataclasses
    cfg = get_config(arch).reduced()
    if cfg.moe is not None:
        # per-shard capacity drops differ from global drops by design
        # (standard EP semantics); equivalence holds in the no-drop regime
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    key = jax.random.PRNGKey(0)
    params = api.init_params(cfg, key, jnp.float32)
    B, S = 4, 16
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1),
             "mask": jnp.ones((B, S), jnp.float32)}
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(key, (B, 8, cfg.d_model)) * 0.02
    if cfg.frontend == "vision_stub":
        batch["embeds"] = jax.random.normal(key, (B, S, cfg.d_model)) * 0.02

    # unsharded reference
    loss_ref, _ = api.loss_fn(cfg, params, batch)
    pre = {k: batch[k] for k in ("tokens", "frames", "embeds") if k in batch}
    kv_len = jnp.full((B,), S, jnp.int32)
    logits_ref, cache_ref = api.prefill(cfg, params, pre, cache_len=S + 4,
                                        kv_len=kv_len)
    nxt = jnp.argmax(logits_ref[:, :cfg.vocab_size], -1)[:, None]
    dec_ref, _ = api.decode_step(cfg, params, nxt, cache_ref, kv_len)

    # sharded: mesh (data=2, model=4)
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)
    plan = ShardingPlan(batch_axes=("data",), model_axis="model",
                        ep_axis="data" if cfg.moe is not None else None,
                        seq_axes=("model",), remat=False)
    mshape = dict(zip(mesh.axis_names, mesh.devices.shape))
    with jax.sharding.set_mesh(mesh):
        pspecs = param_specs(cfg, plan, params, mshape)
        sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                    is_leaf=lambda s: isinstance(s, P))
        params_s = jax.device_put(params, sh(pspecs))
        batch_s = jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))), batch)

        loss_s, _ = jax.jit(
            lambda p, b: api.loss_fn(cfg, p, b, plan=plan))(params_s, batch_s)

        pre_s = {k: batch_s[k] for k in pre}
        logits_s, cache_s = jax.jit(
            lambda p, b, kl: api.prefill(cfg, p, b, plan=plan,
                                         cache_len=S + 4, kv_len=kl)
        )(params_s, pre_s, kv_len)
        dec_s, _ = jax.jit(
            lambda p, t, c, kl: api.decode_step(cfg, p, t, c, kl, plan=plan)
        )(params_s, nxt, cache_s, kv_len)

    e_loss = abs(float(loss_ref) - float(loss_s))
    e_pre = float(jnp.abs(logits_ref - logits_s).max())
    e_dec = float(jnp.abs(dec_ref - dec_s).max())
    print(f"{arch}: loss_err={e_loss:.2e} prefill_err={e_pre:.2e} "
          f"decode_err={e_dec:.2e}")
    return max(e_loss, e_pre, e_dec)


if __name__ == "__main__":
    archs = sys.argv[1:] or ["smollm-135m"]
    worst = max(check(a) for a in archs)
    assert worst < 5e-4, f"sharded/unsharded divergence {worst}"
    print("OK")
