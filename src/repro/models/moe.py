"""Mixture-of-Experts block with sort-based (capacity-dropping) dispatch.

Dispatch avoids the O(T*E*d) one-hot einsum of Switch-style implementations:
tokens are argsorted by expert id, ranked within expert, gathered into an
[E, C, d] buffer, processed with a batched expert matmul (which shards as
expert-TP over the model axis, or EP over plan.ep_axis), and combined back by
a weighted scatter.  FLOPs ~ E*C*d*f ≈ T*topk*d*f*capacity_factor — the same
as the MegaBlocks-style grouped matmul it models.

Capacity-dropped tokens fall back to the shared expert(s) (or identity),
matching standard practice.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.mlp import mlp_init
from repro.models.common import activation, dense_init
from repro.sharding.plan import ShardingPlan


def moe_init(cfg: ModelConfig, key, dtype):
    m = cfg.moe
    keys = jax.random.split(key, 4 + m.n_shared_experts)
    d, f = cfg.d_model, m.d_expert
    std = d ** -0.5
    n_mat = 3 if cfg.gated_mlp else 2

    def bank(k):
        return (jax.random.normal(k, (m.n_experts, d, f), jnp.float32) * std).astype(dtype)

    p = {
        "router": dense_init(keys[0], d, m.n_experts, dtype, scale=0.02),
        "up": bank(keys[1]),
        "down": (jax.random.normal(keys[2], (m.n_experts, f, d), jnp.float32)
                 * f ** -0.5).astype(dtype),
    }
    if n_mat == 3:
        p["gate"] = bank(keys[3])
    for i in range(m.n_shared_experts):
        p[f"shared_{i}"] = mlp_init(cfg, keys[4 + i], dtype, hidden=m.d_shared_eff)
    return p


def moe_apply(cfg: ModelConfig, p, x, *, plan: Optional[ShardingPlan] = None):
    """x: [B, S, d] -> ([B, S, d], aux_metrics).

    With a plan + mesh, the whole block runs under shard_map: tokens stay
    local to their data shard (so the dispatch argsort never crosses chips),
    expert FFNs are TP-sharded over the model axis, and the only
    communication is the single psum over the model axis that dense TP would
    also pay.  Without a mesh it is the same code, locally."""
    if plan is not None and plan.batch_axes:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is not None and not mesh.empty:
            return _moe_sharded(cfg, p, x, plan, mesh)
    y, aux = _moe_local(cfg, p, x, psum_axis=None)
    return y, aux


def _moe_sharded(cfg: ModelConfig, p, x, plan: ShardingPlan, mesh):
    from jax.sharding import PartitionSpec as P
    batch = plan.batch_axes if len(plan.batch_axes) > 1 else plan.batch_axes[0]
    ax = plan.model_axis
    tp_ok = ax is not None and cfg.moe.d_expert % max(1, _axsize(ax)) == 0
    ep_ax = plan.ep_axis
    ep = _axsize(ep_ax) if ep_ax else 1
    ep_ok = ep > 1 and cfg.moe.n_experts % ep == 0
    # aux metrics vary over the batch (token) axes only — x is replicated
    # over the model axis inside the body
    all_axes = tuple(plan.batch_axes)

    in_specs = (
        _tree_specs(cfg, p, ax if tp_ok else None,
                    ep_axis=ep_ax if ep_ok else None),
        P(batch, None, None),
    )

    # when the batch is replicated (long-context decode) the dispatch buffer
    # is invarying over the ep axis; mark it varying before the all_to_all
    ep_needs_pvary = ep_ok and ep_ax not in tuple(plan.batch_axes)

    def body(pl_, xl):
        y, aux = _moe_local(cfg, pl_, xl, psum_axis=ax if tp_ok else None,
                            ep_axis=ep_ax if ep_ok else None,
                            ep_pvary=ep_needs_pvary)
        if all_axes:
            aux = jax.tree.map(lambda a: jax.lax.pmean(a, all_axes), aux)
        return y, aux

    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=(P(batch, None, None), {"lb_loss": P(), "drop_frac": P()}),
    )(p, x)
    return y, aux


def _axsize(ax) -> int:
    from repro.sharding.plan import axis_size
    return axis_size(ax)


def _tree_specs(cfg: ModelConfig, p, ax, ep_axis=None):
    """PartitionSpec tree for the MoE params inside shard_map."""
    from jax.sharding import PartitionSpec as P
    specs = {
        "router": {"w": P(None, None)},
        "up": P(ep_axis, None, ax),
        "down": P(ep_axis, ax, None),
    }
    if "gate" in p:
        specs["gate"] = P(ep_axis, None, ax)
    for k in p:
        if k.startswith("shared_"):
            s = {"up": {"w": P(None, ax)}, "down": {"w": P(ax, None)}}
            if "gate" in p[k]:
                s["gate"] = {"w": P(None, ax)}
            for nm in ("up", "gate", "down"):
                if nm in p[k] and "b" in p[k][nm]:
                    s[nm]["b"] = P(None)
            specs[k] = s
    return specs


def _moe_local(cfg: ModelConfig, p, x, *, psum_axis, ep_axis=None,
               ep_pvary: bool = False):
    """Token-local MoE; when psum_axis is set the FFN dim is sharded and the
    down-projections are partial sums reduced once at the end.  When ep_axis
    is set the expert banks are sharded over it and the [E, C, d] dispatch
    buffer is exchanged with a tiled all-to-all (capacity-based EP)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)

    logits = (xf @ p["router"]["w"]).astype(jnp.float32)        # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, m.top_k)        # [T, K]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    capacity = max(8, int(t * m.top_k / m.n_experts * m.capacity_factor))
    capacity = min(capacity, t)

    flat_expert = expert_ids.reshape(-1)                         # [T*K]
    flat_token = jnp.repeat(jnp.arange(t), m.top_k)
    flat_gate = gate_vals.reshape(-1)

    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]
    # rank within expert = position - start offset of that expert's run
    counts = jnp.bincount(sorted_expert, length=m.n_experts)     # [E]
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(t * m.top_k) - starts[sorted_expert]
    keep = rank < capacity

    slot = sorted_expert * capacity + jnp.where(keep, rank, 0)
    # gather tokens into [E*C, d]; dropped tokens contribute zero
    buf = jnp.zeros((m.n_experts * capacity, d), x.dtype)
    src = jnp.where(keep, slot, m.n_experts * capacity)          # OOB -> dropped
    buf = buf.at[jnp.minimum(src, m.n_experts * capacity - 1)].add(
        jnp.where(keep[:, None], xf[sorted_token], 0))
    buf = buf.reshape(m.n_experts, capacity, d)

    if ep_axis is not None:
        if ep_pvary:
            buf = jax.lax.pcast(buf, (ep_axis,), to="varying")
        # exchange dispatch buffers: [E, C, d] -> [E/ep, ep*C, d]
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)
    # batched expert matmuls [E(/ep), C, d] x [E(/ep), d, f]; f possibly TP-sharded
    up = jnp.einsum("ecd,edf->ecf", buf, p["up"])
    if cfg.gated_mlp:
        up = activation(cfg, jnp.einsum("ecd,edf->ecf", buf, p["gate"])) * up
    else:
        up = activation(cfg, up)
    out_full = jnp.einsum("ecf,efd->ecd", up, p["down"])
    if ep_axis is not None:
        # route results back: [E/ep, ep*C, d] -> [E, C, d]
        out_full = jax.lax.all_to_all(out_full, ep_axis, split_axis=1,
                                      concat_axis=0, tiled=True)
    out_buf = out_full.reshape(-1, d)

    # combine back: weighted scatter-add to tokens (partial over f when sharded)
    contrib = jnp.where(keep[:, None], out_buf[slot] * sorted_gate[:, None], 0)
    y = jnp.zeros((t, d), contrib.dtype).at[sorted_token].add(contrib)

    for i in range(m.n_shared_experts):
        sp = p[f"shared_{i}"]
        hid = xf @ sp["up"]["w"]
        if cfg.gated_mlp:
            hid = activation(cfg, xf @ sp["gate"]["w"]) * hid
        else:
            hid = activation(cfg, hid)
        y = y + hid @ sp["down"]["w"]

    if psum_axis is not None:
        y = jax.lax.psum(y, psum_axis)
    y = y.astype(x.dtype)

    # aux: load-balance loss (Switch) + drop fraction for monitoring
    me = probs.mean(axis=0)
    ce = jnp.bincount(expert_ids.reshape(-1), length=m.n_experts) / (t * m.top_k)
    aux = {"lb_loss": m.n_experts * jnp.sum(me * ce),
           "drop_frac": 1.0 - keep.mean()}
    return y.reshape(b, s, d), aux
