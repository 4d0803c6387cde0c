import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Must precede every other import (jax locks the device count on first init).

"""Perf hillclimb driver (EXPERIMENTS.md §Perf): for the three selected
cells, lower+compile the baseline and each optimization step, record the
roofline terms (analytic + compiled-HLO), and emit the iteration log.

  PYTHONPATH=src python -m repro.launch.hillclimb
"""
import dataclasses
import json
import pathlib

import jax

from repro.configs import SHAPES, get_config
from repro.launch import dryrun as dr
from repro.perf.cost_model import step_cost

ART = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "perf"


def measure(cfg, shape, mp, tag):
    """Lower+compile with the given MeshPlan; return roofline record."""
    import repro.launch.dryrun as dryrun
    # monkey-free: reuse lower_cell but with an explicit plan
    orig = dryrun.pick_plan
    dryrun.pick_plan = lambda *a, **k: mp
    try:
        rec = dryrun.lower_cell(cfg, shape, multi_pod=False,
                                plan_kind=tag, verbose=True)
    finally:
        dryrun.pick_plan = orig
    return rec


def emit(name, steps):
    ART.mkdir(parents=True, exist_ok=True)
    (ART / f"{name}.json").write_text(json.dumps(steps, indent=1, default=str))
    print(f"== {name} ==")
    for s in steps:
        t = s["analytic"]["times_s"]
        print(f"  {s['plan_kind']:24s} comp={t['compute_s']*1e3:9.3f}ms "
              f"mem={t['memory_s']*1e3:8.3f}ms coll={t['collective_s']*1e3:8.3f}ms "
              f"dom={s['analytic']['bottleneck']:10s} hlo_flops={s['hlo_flops']:.3e}")


def climb_minicpm3():
    """Cell 1 (worst useful-FLOPs): minicpm3-4b × decode_32k.
    Hypothesis: the per-step latent->K/V expansion dominates compute
    (2·S·r·H·(dn+dv)·L ≈ 8.6e10·B FLOPs); absorbing W_uk/W_uv into the
    query/output removes it (~60× less attention compute) and flips the cell
    to memory-bound."""
    cfg = get_config("minicpm3-4b")
    shape = SHAPES["decode_32k"]
    base = dr.pick_plan(cfg, shape, multi_pod=False, which="baseline")
    steps = [measure(cfg, shape, base, "baseline_expanded")]

    opt = dataclasses.replace(
        base,
        plan=dataclasses.replace(base.plan, mla_absorbed=True),
        desc=dataclasses.replace(base.desc, mla_absorbed=True))
    opt = dataclasses.replace(opt, cost=step_cost(cfg, shape, opt.desc))
    steps.append(measure(cfg, shape, opt, "opt1_mla_absorbed"))
    emit("hillclimb_minicpm3_decode", steps)
    return steps


def climb_smollm():
    """Cell 2 (most collective-bound): smollm-135m × train_4k.
    Hypothesis A: TP-16 for a 135M model spends 4 allreduces/layer on
    activations (340 ms collective vs 34 ms compute); pure DP over all 256
    chips reduces collectives to one grad sync (~2·N·2B·(255/256)/chip
    ≈ 1.05 GB → ~21 ms) — a ~16× cut.
    Hypothesis B (beyond-paper): int8 gradient compression halves sync bytes
    vs bf16 (×4 vs fp32) — analytic, validated by the shard_map helper's
    correctness tests."""
    cfg = get_config("smollm-135m")
    shape = SHAPES["train_4k"]
    cands = {c.name: c for c in
             __import__("repro.core.deployer", fromlist=["candidate_plans"]
                        ).candidate_plans(cfg, shape, multi_pod=False)}
    base = dr.pick_plan(cfg, shape, multi_pod=False, which="baseline")
    steps = [measure(cfg, shape, base, "baseline_tp16")]
    dp = cands["dp256"]
    steps.append(measure(cfg, shape, dp, "opt1_pure_dp256"))
    # int8 grad sync: analytic only (GSPMD backward owns the collective);
    # record the projected terms
    proj = dict(steps[-1])
    coll = proj["analytic"]["coll_bytes_chip"] / 2.0
    t = dict(proj["analytic"]["times_s"])
    t["collective_s"] = t["collective_s"] / 2.0
    proj = {**proj, "plan_kind": "opt2_int8_gradsync(analytic)",
            "analytic": {**proj["analytic"], "coll_bytes_chip": coll,
                         "times_s": t},
            "hlo_flops": proj["hlo_flops"]}
    steps.append(proj)
    emit("hillclimb_smollm_train", steps)
    return steps


def climb_gemma2():
    """Cell 3 (most serving-representative): gemma2-27b × decode_32k.
    Hypothesis: the step is memory-bound (8.4 ms) on weight reads (3.4 GiB/chip
    → 4.2 ms) + KV reads (~3.4 GiB → 4.2 ms).  int8 KV cache halves the KV
    term (−2.1 ms); the window-layer ring buffers already cut KV 44% vs
    naive full-length caches (counted in the baseline)."""
    cfg = get_config("gemma2-27b")
    shape = SHAPES["decode_32k"]
    base = dr.pick_plan(cfg, shape, multi_pod=False, which="baseline")
    steps = [measure(cfg, shape, base, "baseline_bf16kv")]
    opt = dataclasses.replace(
        base, desc=dataclasses.replace(base.desc, kv_bytes_per=1))
    opt = dataclasses.replace(opt, cost=step_cost(cfg, shape, opt.desc))
    # int8 cache is exercised at reduced scale for accuracy (tests); the
    # full-cell lowering uses the same graph with int8 cache dtype
    steps.append(measure_int8_cache(cfg, shape, opt, "opt1_int8_kv"))
    emit("hillclimb_gemma2_decode", steps)
    return steps


def measure_int8_cache(cfg, shape, mp, tag):
    """Lower the decode cell with an int8 KV cache (dequant on read)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_production_mesh, mesh_shape_dict
    from repro.models import api
    from repro.sharding.specs import cache_specs_tree, param_specs
    import time

    mesh = make_production_mesh()
    mshape = mesh_shape_dict(mesh)
    plan = mp.plan
    specs_in = api.input_specs(cfg, shape, dtype=jnp.bfloat16)
    cache_struct = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.int8)
        if x.dtype == jnp.bfloat16 else x, specs_in["cache"])
    params_struct = jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    rec = {"arch": cfg.name, "shape": shape.name, "mesh": "16x16",
           "plan": mp.name, "plan_kind": tag, "n_chips": 256}

    def shardify(t):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda s: isinstance(s, P))

    with jax.sharding.set_mesh(mesh):
        pspecs = param_specs(cfg, plan, params_struct, mshape)
        cspecs = cache_specs_tree(cfg, plan, cache_struct, mshape)
        ba = plan.batch_axes[0]

        def decode_fn(params, tokens, cache, kv_len):
            # dequantize (scale folded into a per-layer constant here; the
            # engine keeps per-row scales — same bytes, +1 small tensor)
            cache_f = jax.tree.map(
                lambda x: (x.astype(jnp.bfloat16) * jnp.bfloat16(0.05))
                if x.dtype == jnp.int8 else x, cache)
            logits, new_cache = api.decode_step(cfg, params, tokens, cache_f,
                                                kv_len, plan=plan)
            new_q = jax.tree.map(
                lambda new, old: jnp.clip(jnp.round(new / 0.05), -127, 127
                                          ).astype(jnp.int8)
                if old.dtype == jnp.int8 else new, new_cache, cache)
            return logits, new_q

        lowered = jax.jit(
            decode_fn,
            in_shardings=(shardify(pspecs), NamedSharding(mesh, P(ba)),
                          shardify(cspecs), NamedSharding(mesh, P(ba))),
            out_shardings=(NamedSharding(mesh, P(ba)), shardify(cspecs)),
            donate_argnums=(2,),
        ).lower(params_struct, specs_in["tokens"], cache_struct,
                specs_in["kv_len"])
        t0 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t0, 2)
        rec["memory_analysis"] = dr._mem_dict(compiled.memory_analysis())
        ca = compiled.cost_analysis()
        rec["hlo_flops"] = float(ca.get("flops", 0.0))
        rec["hlo_bytes"] = float(ca.get("bytes accessed", 0.0))
        from repro.models.transformer import group_period
        rec["collectives"] = dr.parse_collectives(
            compiled.as_text(),
            loop_trips={"scan": float(cfg.n_layers // group_period(cfg))})
        ct = step_cost(cfg, shape, mp.desc)
        rec["analytic"] = {
            "flops_chip": ct.flops, "hbm_bytes_chip": ct.hbm_bytes,
            "coll_bytes_chip": ct.coll_bytes, "model_flops": ct.model_flops,
            "weight_bytes_chip": ct.weight_bytes_chip,
            "kv_bytes_chip": ct.kv_bytes_chip,
            "hbm_resident_chip": ct.hbm_resident,
            "times_s": ct.times(), "bottleneck": ct.bottleneck(),
        }
    ma = rec["memory_analysis"]
    print(f"  [int8kv] args/dev={ma.get('argument_size_in_bytes',0)/2**30:.2f}GiB")
    return rec


def main():
    climb_minicpm3()
    climb_smollm()
    climb_gemma2()


if __name__ == "__main__":
    main()
