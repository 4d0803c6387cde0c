import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init).  512 placeholder host devices back both the 16x16
# single-pod mesh and the 2x16x16 multi-pod mesh.

"""Multi-pod dry-run: lower + compile every (architecture × input shape) cell
on the production meshes, prove the sharding config is coherent, and capture
memory_analysis / cost_analysis / collective bytes for the roofline.

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--plan helr|baseline]
  python -m repro.launch.dryrun --all --both-meshes
Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>__<plan>.json.
"""
import argparse
import dataclasses
import json
import pathlib
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, cell_is_runnable, get_config, list_archs
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.deployer import candidate_plans, helr_mesh
from repro.launch.mesh import make_production_mesh, mesh_shape_dict
from repro.models import api
from repro.models.transformer import group_period
from repro.perf.cost_model import step_cost
from repro.sharding.plan import ShardingPlan
from repro.sharding.specs import cache_specs_tree, param_specs
from repro.training import OptConfig, TrainConfig, init_opt_state, \
    make_train_step, opt_state_specs

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
          "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
          "s16": 2, "u16": 2}


def _op_operand_bytes(line: str) -> float:
    """Sum operand tensor sizes on an HLO op line (result shape excluded —
    we count the line's RHS operands by re-parsing the argument list)."""
    # take shapes appearing after the '=' (op result shape is first token
    # before '='; operands appear in the call args)
    rhs = line.split("=", 1)[-1]
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(rhs):
        if dt not in _BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES[dt]
    return total


def parse_collectives(hlo_text: str, loop_trips: dict[str, float] | None = None
                      ) -> dict:
    """Sum collective operand bytes from HLO text.  Collectives inside while
    bodies are additionally multiplied by the known trip counts (layer-scan
    groups etc.) to correct XLA's count-once semantics; the caller passes
    {computation_name_fragment: trip_count}."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"^(%?[\w\.\-]+)\s*(\([^)]*\))?\s*->.*\{$", stripped)
        if m and not stripped.startswith("ROOT"):
            cur = m.group(1).lstrip("%")
            comps[cur] = []
            continue
        if stripped.startswith("ENTRY"):
            cur = "__entry__"
            comps[cur] = []
            continue
        if stripped == "}":
            continue
        if cur is not None:
            comps[cur].append(stripped)

    # which computations are while bodies (and their conds)
    while_bodies = set()
    for lines in comps.values():
        for ln in lines:
            if "while(" in ln or " while(" in ln or "= while" in ln:
                for m in re.finditer(r"(?:body|condition)=%?([\w\.\-]+)", ln):
                    while_bodies.add(m.group(1))

    raw = 0.0
    in_loop = 0.0
    by_kind: dict[str, float] = {}
    for name, lines in comps.items():
        looped = any(wb in name for wb in while_bodies) or name in while_bodies
        for ln in lines:
            m = _COLL_RE.search(ln)
            if not m or "=" not in ln:
                continue
            b = _op_operand_bytes(ln)
            raw += b
            by_kind[m.group(1)] = by_kind.get(m.group(1), 0.0) + b
            if looped:
                in_loop += b
    trips = max(loop_trips.values()) if loop_trips else 1.0
    corrected = (raw - in_loop) + in_loop * trips
    return {"raw_bytes": raw, "in_loop_bytes": in_loop,
            "corrected_bytes": corrected, "by_kind": by_kind,
            "loop_trip_assumed": trips}


def pick_plan(cfg: ModelConfig, shape: ShapeConfig, *, multi_pod: bool,
              which: str):
    cands = candidate_plans(cfg, shape, multi_pod=multi_pod)
    feas = [c for c in cands if c.fits] or cands
    if which == "helr":
        return min(feas, key=lambda c: c.step_time)
    return feas[0]          # baseline: first feasible (tp16_dp*)


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, *, multi_pod: bool,
               plan_kind: str = "baseline", verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mshape = mesh_shape_dict(mesh)
    mp = pick_plan(cfg, shape, multi_pod=multi_pod, which=plan_kind)
    plan = mp.plan
    dtype = jnp.bfloat16
    specs_in = api.input_specs(cfg, shape, dtype=dtype)
    params_struct = jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0), dtype))
    record: dict = {
        "arch": cfg.name, "shape": shape.name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "plan": mp.name, "plan_kind": plan_kind,
        "n_chips": 512 if multi_pod else 256,
    }

    from jax.sharding import NamedSharding, PartitionSpec as P

    def shardify(spec_tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                            is_leaf=lambda s: isinstance(s, P))

    with jax.sharding.set_mesh(mesh):
        pspecs = param_specs(cfg, plan, params_struct, mshape)
        batch_axes = plan.batch_axes if len(plan.batch_axes) > 1 else \
            (plan.batch_axes[0] if plan.batch_axes else None)

        if shape.kind == "train":
            opt_kind = mp.desc.optimizer
            tcfg = TrainConfig(opt=OptConfig(kind=opt_kind),
                               microbatches=plan.microbatches)
            opt_struct = jax.eval_shape(
                lambda: init_opt_state(params_struct, tcfg.opt))
            ospecs = opt_state_specs(pspecs, tcfg.opt)
            bspecs = jax.tree.map(lambda _: P(batch_axes), specs_in["batch"])
            step_fn = make_train_step(cfg, plan, tcfg)
            lowered = jax.jit(
                step_fn,
                in_shardings=(shardify(pspecs), shardify(ospecs),
                              shardify(bspecs), NamedSharding(mesh, P())),
                out_shardings=(shardify(pspecs), shardify(ospecs),
                               jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                            {"loss": 0, "grad_norm": 0})),
                donate_argnums=(0, 1),
            ).lower(params_struct, opt_struct, specs_in["batch"],
                    jax.ShapeDtypeStruct((), jnp.int32))
        elif shape.kind == "prefill":
            cache_len = shape.seq_len
            cache_struct = api.cache_specs(cfg, shape.global_batch, cache_len,
                                           dtype=dtype)
            cspecs = cache_specs_tree(cfg, plan, cache_struct, mshape)
            bspecs = jax.tree.map(lambda _: P(batch_axes), specs_in["batch"])

            def prefill_fn(params, batch, kv_len):
                return api.prefill(cfg, params, batch, plan=plan,
                                   cache_len=cache_len, kv_len=kv_len)

            lowered = jax.jit(
                prefill_fn,
                in_shardings=(shardify(pspecs), shardify(bspecs),
                              NamedSharding(mesh, P(batch_axes))),
                out_shardings=(NamedSharding(mesh, P(batch_axes)),
                               shardify(cspecs)),
            ).lower(params_struct, specs_in["batch"], specs_in["kv_len"])
        else:  # decode
            cache_struct = specs_in["cache"]
            cspecs = cache_specs_tree(cfg, plan, cache_struct, mshape)

            def decode_fn(params, tokens, cache, kv_len):
                return api.decode_step(cfg, params, tokens, cache, kv_len,
                                       plan=plan)

            lowered = jax.jit(
                decode_fn,
                in_shardings=(shardify(pspecs),
                              NamedSharding(mesh, P(batch_axes)),
                              shardify(cspecs),
                              NamedSharding(mesh, P(batch_axes))),
                out_shardings=(NamedSharding(mesh, P(batch_axes)),
                               shardify(cspecs)),
                donate_argnums=(2,),
            ).lower(params_struct, specs_in["tokens"], cache_struct,
                    specs_in["kv_len"])

        t0 = time.perf_counter()
        compiled = lowered.compile()
        record["compile_s"] = round(time.perf_counter() - t0, 2)

        mem = compiled.memory_analysis()
        record["memory_analysis"] = _mem_dict(mem)
        ca = compiled.cost_analysis()
        record["hlo_flops"] = float(ca.get("flops", 0.0))
        record["hlo_bytes"] = float(ca.get("bytes accessed", 0.0))

        trips = {"scan": float(cfg.n_layers // group_period(cfg))}
        record["collectives"] = parse_collectives(
            compiled.as_text(), loop_trips=trips)

        # analytic roofline terms
        ct = step_cost(cfg, shape, mp.desc)
        record["analytic"] = {
            "flops_chip": ct.flops, "hbm_bytes_chip": ct.hbm_bytes,
            "coll_bytes_chip": ct.coll_bytes, "model_flops": ct.model_flops,
            "weight_bytes_chip": ct.weight_bytes_chip,
            "kv_bytes_chip": ct.kv_bytes_chip,
            "hbm_resident_chip": ct.hbm_resident,
            "times_s": ct.times(), "bottleneck": ct.bottleneck(),
        }
    if verbose:
        ma = record["memory_analysis"]
        print(f"  compiled in {record['compile_s']}s; "
              f"argbytes/dev={ma.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
              f"temp/dev={ma.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
              f"hlo_flops={record['hlo_flops']:.3e} "
              f"coll_raw={record['collectives']['raw_bytes']:.3e}B")
    return record


def _mem_dict(mem) -> dict:
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        try:
            out[k] = int(getattr(mem, k))
        except Exception:
            pass
    if not out:
        out["repr"] = str(mem)
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, plan_kind: str
             ) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    if not ok:
        print(f"[skip] {arch} × {shape_name}: {why}")
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "skipped": why}
        ART_DIR.mkdir(parents=True, exist_ok=True)
        (ART_DIR / f"{arch}__{shape_name}__{mesh_tag}__{plan_kind}.json"
         ).write_text(json.dumps(rec, indent=1))
        return rec
    print(f"[cell] {arch} × {shape_name} on {mesh_tag} ({plan_kind})")
    try:
        rec = lower_cell(cfg, shape, multi_pod=multi_pod, plan_kind=plan_kind)
    except Exception as e:                        # noqa: BLE001
        print(f"  FAILED: {e}")
        traceback.print_exc()
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "error": str(e)}
    ART_DIR.mkdir(parents=True, exist_ok=True)
    out = ART_DIR / f"{arch}__{shape_name}__{mesh_tag}__{plan_kind}.json"
    out.write_text(json.dumps(rec, indent=1, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--plan", default="baseline", choices=["baseline", "helr"])
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    archs = list_archs() if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]

    failures = 0
    for mp in meshes:
        for arch in archs:
            for shp in shapes:
                rec = run_cell(arch, shp, multi_pod=mp, plan_kind=args.plan)
                if "error" in rec:
                    failures += 1
    if failures:
        raise SystemExit(f"{failures} cells failed")
    print("dry-run complete: all requested cells lowered + compiled")


if __name__ == "__main__":
    main()
