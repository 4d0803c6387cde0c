"""ShardingPlan: the per-run description of how tensors map onto mesh axes.

The HELR-mesh deployer (repro.core.deployer) *produces* one of these; the
model code *consumes* it via activation constraints, and
repro.sharding.specs turns it into parameter PartitionSpec trees.
plan=None (the default in unit tests) disables all constraints.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class ShardingPlan:
    batch_axes: tuple[str, ...] = ()        # activation batch dims
    model_axis: Optional[str] = None        # tensor parallelism
    fsdp_axes: tuple[str, ...] = ()         # ZeRO-3 param sharding
    seq_axes: tuple[str, ...] = ()          # KV-cache sequence sharding (decode)
    ep_axis: Optional[str] = None           # expert parallelism
    seq_parallel: bool = False              # residuals sharded over model axis
    mla_absorbed: bool = True               # matmul-absorbed MLA decode (§Perf)
    # training-plan fields consumed by repro.training
    remat: bool = False
    microbatches: int = 1


def _mesh_axis_sizes() -> dict[str, int]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return {}
    return dict(mesh.shape)


def axis_size(name) -> int:
    if name is None:
        return 1
    sizes = _mesh_axis_sizes()
    if isinstance(name, str):
        return sizes.get(name, 1)
    total = 1
    for a in name:
        total *= sizes.get(a, 1)
    return total


def divisible(dim: int, axes) -> bool:
    """Can `dim` be sharded across the named axes of the current mesh?"""
    if not axes:
        return False
    total = axis_size(axes)
    return total > 1 and dim % total == 0


def constrain(x: jnp.ndarray, spec: P, plan: Optional[ShardingPlan]):
    """with_sharding_constraint that is a no-op without a plan/mesh."""
    if plan is None or not _mesh_axis_sizes():
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def batch_spec(plan: Optional[ShardingPlan], ndim: int, batch_dim: int = 0) -> P:
    if plan is None:
        return P()
    parts: list = [None] * ndim
    if plan.batch_axes:
        parts[batch_dim] = plan.batch_axes if len(plan.batch_axes) > 1 else plan.batch_axes[0]
    return P(*parts)


def resid_spec(plan: Optional[ShardingPlan], x) -> P:
    """Residual-stream spec between blocks: batch-sharded, and — with
    sequence-parallelism — seq sharded over the model axis (Megatron
    sequence parallelism expressed as a GSPMD constraint)."""
    spec = batch_spec(plan, x.ndim)
    if (plan is not None and plan.seq_parallel and plan.model_axis
            and x.ndim >= 3 and x.shape[1] % max(axis_size(plan.model_axis), 1) == 0
            and axis_size(plan.model_axis) > 1):
        parts = list(spec)
        parts[1] = plan.model_axis
        return P(*parts)
    return spec
