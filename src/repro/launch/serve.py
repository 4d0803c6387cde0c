"""Serving launcher: UELLM pipeline on a real model.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --requests 12 --scheduler slo-odbs

The model runs at its published widths; ``--reduced`` cuts it to the toy
widths a CPU can serve (and prompts to 16/32 tokens).  Paged decode runs the
Pallas kernels on a TPU and the blocked-XLA paths elsewhere
(``kernels.backend``).  JAX's persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.

``--paged`` serves through the paged continuous-batching runtime instead
(block-table KV, per-prompt prefill, allocator-gated admission); the pool is
sized from ``--kv-budget`` bytes — the same budget surface SLO-ODBS uses.
``--prefix-cache`` layers the radix-tree prefix cache on top (shared-prefix
prompts prefill only their uncached suffix; ``--workload shared-prefix``
generates a template-heavy mix that exercises it), and ``--lookahead N``
lets admission skip a too-big queue head when a later request fits.
``--chunk-tokens N`` chunks prompt prefill to N tokens per engine iteration
(interleaved with decode, so residents never stall for a whole prompt;
``-1`` derives N from the scheduler's composite threshold) and ``--preempt``
lets block pressure evict the slack-most resident for recompute instead of
blocking a tight arrival — both also feed the cluster paths (replica load
projections price them).  ``--speculate`` turns on speculative decoding:
``--drafter`` proposes ``--spec-tokens`` candidates per iteration, verified
in one multi-token kernel pass with greedy acceptance (outputs stay
token-identical; the cluster projections price the *measured* acceptance
EMA — warm-started from ``--profile-in``, bootstrap 0.5 before the first
verify pass).  ``--profile-out``/``--profile-in`` persist and reload the
online cost profile (measured phase-time cells, residuals, acceptance) as
a versioned JSON registry, calibrating every pricing model it reaches —
per replica, with ``--pricing-quantile Q`` switching SLO decisions onto a
tail ratio and ``--profile-half-life N`` bounding the profile's memory so
re-provisioned replicas re-learn.

``--replicas N`` lifts serving to the cluster layer (serving/cluster):
requests are routed by ``--router`` across N replicas.  With ``--paged``
each replica owns a real PagedEngine (pool + prefix cache per replica) and
the routed shares are served live; otherwise the replicas are
LatencyModel-backed simulated engines on per-replica HELR deployments —
the cluster-scale path, which ``--autoscale`` extends with the
forecast-driven elastic replica set (``--workload bursty`` exercises it).
``--models`` turns the simulated cluster into a heterogeneous MLaaS
fleet: a mixed-model, tier-skewed trace is served by per-model replica
pools with model-aware routing, and ``--fleet`` picks between one joint
allocator over the shared replica budget (marginal SLO value, model-swap
actions) and independent per-pool autoscalers.

With ``--paged --replicas N`` replica i's params and KV pool live on
``jax.devices()[i % len(jax.devices())]`` — one chip per replica on a
multi-chip host, all on the one device elsewhere.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config
from repro.core import (LengthPredictor, Monitor, ResourceProfiler,
                        SchedulerConfig, derive_chunk_tokens, get_scheduler,
                        helr_mesh)
from repro.core.profiler import PredictorConfig
from repro.data.workload import (MixedWorkloadConfig, SharedPrefixConfig,
                                 WorkloadConfig, gen_mixed_requests,
                                 gen_requests, gen_shared_prefix_requests,
                                 train_pairs)
from repro.models import api
from repro.obs.calibrate import CalibratedLatencyModel
from repro.obs.export import export_trace, metrics_payload, write_metrics
from repro.obs.profile import CostProfiler
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serving import (AutoscalerConfig, EngineConfig, FaultEvent,
                           FaultPlan, FleetAutoscalerConfig, HealthConfig,
                           InferenceEngine, ModelPoolSpec, PagedEngine,
                           PagedEngineConfig, Replica, RetryConfig, Router,
                           RouterConfig, get_drafter, paper_cluster,
                           simulate_cluster)


# engine shape shared by every paged path (single engine and replicas)
MAX_BATCH = 4
BLOCK_SIZE = 8
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path so a later
    process finds what an earlier one compiled.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and nothing is
    changed here."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def _max_seq(reqs, max_new: int) -> int:
    """Block-table width: the longest prompt plus the decode budget, so any
    --max-new value is admissible."""
    max_prompt = max(len(r.tokens) for r in reqs)
    return max(64, -(-(max_prompt + max_new) // BLOCK_SIZE) * BLOCK_SIZE)


def _parse_model_mix(spec: str) -> list:
    """``"arch[:weight],arch[:weight]"`` -> ``[(arch, weight), ...]``."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition(":")
        out.append((name.strip(), float(w) if w else 1.0))
    if not out:
        raise SystemExit("--models: empty model list")
    return out


def _make_drafter(args, cfg):
    """Engine drafter from the CLI flags (None lets the engine default)."""
    if args.spec_tokens > 0 and args.drafter == "model":
        return get_drafter("model", draft_cfg=cfg)
    return None


def _spec_acceptance(args, cprof: CostProfiler) -> float:
    """Speculation acceptance for *planning* (replica projections,
    SchedulerConfig.spec_speedup): the cost profiler's measured EMA —
    warm-started from ``--profile-in``, its bootstrap prior when nothing
    has been measured yet, and live-updated by ``PagedEngine._spec_step``
    once serving starts."""
    return cprof.spec_acceptance if args.spec_tokens else 0.0


def _outputs_digest(done: dict) -> str:
    """Order-independent digest of the generated tokens — two serve runs
    printing the same digest emitted identical output streams (the CI
    profile smoke compares this across --profile-out/--profile-in runs)."""
    blob = json.dumps(sorted((int(k), list(map(int, v)))
                             for k, v in done.items()))
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


def _pricing_counters(cal_models) -> dict:
    """Aggregate coverage counters across every ``CalibratedLatencyModel``
    the run priced through (one per replica on the cluster paths)."""
    agg = {"cell_hits": 0, "phase_hits": 0, "cell_misses": 0}
    for m in cal_models:
        c = m.coverage_counters()
        for k in agg:
            agg[k] += c[k]
    total = sum(agg.values())
    agg["covered_frac"] = round(
        (agg["cell_hits"] + agg["phase_hits"]) / total, 4) if total else 0.0
    return agg


def _write_artifacts(args, mon, tracer, cprof, *, latency_s=None,
                     p99_latency_s=None, throughput=None,
                     utilization=None, cal_models=()) -> None:
    """Export the request-lifecycle trace (``--trace``, Chrome/Perfetto JSON)
    and the shared metrics payload (``--metrics-json`` — same schema the
    benchmarks persist).  Latency quantiles default to the monitor's e2e
    histogram when the caller has no direct measurement.  Profiled runs
    also report how calibrated pricing resolved (coverage counters) and
    which replicas drifted — previously they ended silently."""
    st = mon.stats
    if latency_s is None and st.e2e.n:
        latency_s = st.e2e.total / st.e2e.n
    if p99_latency_s is None and st.e2e.n:
        p99_latency_s = st.e2e.quantile(0.99)
    if args.trace:
        obj = export_trace(tracer, args.trace)
        print(f"trace: {len(obj['traceEvents'])} events -> {args.trace}")
    profile_block = cprof.metrics()
    if cal_models:
        profile_block["pricing"] = _pricing_counters(cal_models)
    if args.metrics_json:
        payload = metrics_payload(
            "serve", latency_s=latency_s, p99_latency_s=p99_latency_s,
            throughput=throughput, utilization=utilization,
            slo_attainment=st.slo_attainment if st.slo_observed else None,
            monitor=mon.metrics(), profile=profile_block)
        write_metrics(args.metrics_json, payload)
        print(f"metrics -> {args.metrics_json}")
    if args.profile_in or args.profile_out:
        if cal_models:
            pc = profile_block["pricing"]
            print(f"calibration: cell_hits={pc['cell_hits']} "
                  f"phase_hits={pc['phase_hits']} "
                  f"cell_misses={pc['cell_misses']} "
                  f"covered_frac={pc['covered_frac']}")
        drift = cprof.drift_by_replica()
        by_rep = " by_replica=" + json.dumps(
            {str(r): n for r, n in drift.items()}) if drift else ""
        mdrift = cprof.drift_by_model()
        by_model = " by_model=" + json.dumps(mdrift) if mdrift else ""
        print(f"drift: {cprof.drift_events} events{by_rep}{by_model}")
        mcov = cprof.model_coverage()
        if mcov:
            cov = {m: {p: c["samples"] for p, c in d.items()}
                   for m, d in mcov.items()}
            print(f"model coverage: {json.dumps(cov)}")
    if args.profile_out:
        cprof.save(args.profile_out)
        cov = {p: c["samples"] for p, c in cprof.coverage().items()}
        subs = f"{len(cprof.replica_profiles)} replica"
        if cprof.model_profiles:
            subs += f" + {len(cprof.model_profiles)} model"
        print(f"profile: {len(cprof.cells)} cells, samples {cov}, "
              f"{subs} sub-profiles -> {args.profile_out}")


def _serve_cluster_live(args, cfg, params, mon, reqs, tracer, cprof,
                        cal_models, devices) -> tuple[dict, list]:
    """Route requests across N real PagedEngine-backed replicas, then serve
    each replica's share live (per-replica pool + prefix cache).  Replica i
    holds its params and pool on ``devices[i % len(devices)]``; every jitted
    step follows those committed inputs.  Returns (outputs, engines)."""
    max_seq = _max_seq(reqs, args.max_new)
    router = Router(RouterConfig(policy=args.router))
    replicas = []
    for i in range(args.replicas):
        nodes, lat = paper_cluster()
        dev = devices[i % len(devices)]
        pcfg = PagedEngineConfig.from_memory_budget(
            cfg, args.kv_budget, max_batch=MAX_BATCH, block_size=BLOCK_SIZE,
            max_seq_len=max_seq, max_new_tokens=args.max_new,
            prefix_cache=args.prefix_cache, admit_lookahead=args.lookahead,
            chunk_tokens=args.chunk_tokens, preempt=args.preempt,
            spec_tokens=args.spec_tokens, drafter=args.drafter)
        rep = Replica(
            i, cfg, nodes, lat, max_batch=MAX_BATCH, block_size=BLOCK_SIZE,
            n_blocks=pcfg.usable_blocks, prefix_cache=args.prefix_cache,
            chunk_tokens=args.chunk_tokens, preempt=args.preempt,
            spec_tokens=args.spec_tokens,
            spec_acceptance=_spec_acceptance(args, cprof),
            engine=PagedEngine(cfg, jax.device_put(params, dev), pcfg,
                               monitor=mon,
                               drafter=_make_drafter(args, cfg),
                               tracer=tracer, track=i,
                               cost_profiler=cprof),
            tracer=tracer)
        if args.profile_in:
            # each replica prices from its own sub-profile (fleet-aggregate
            # fallback); the tail model adds quantile pricing for the
            # SLO-facing projections when --pricing-quantile is set
            rep.price = CalibratedLatencyModel(rep.lm, cprof, replica=i)
            cal_models.append(rep.price)
            if args.pricing_quantile:
                rep.tail = CalibratedLatencyModel(
                    rep.lm, cprof, replica=i,
                    quantile=args.pricing_quantile)
                cal_models.append(rep.tail)
        replicas.append(rep)
    for r in sorted(reqs, key=lambda q: q.arrival):
        rep = router.dispatch(r, replicas, r.arrival)
        if rep is None:
            mon.observe_shed(r)
            continue
        rep.enqueue(r, r.arrival)
    done: dict = {}
    for rep in replicas:
        if not rep.queue:
            continue
        if args.spec_tokens:
            # replicas serve sequentially here, so each one plans at the
            # acceptance the earlier shares already measured
            rep.spec_acceptance = cprof.spec_acceptance
        res = rep.engine.run_continuous(
            sorted(rep.queue, key=lambda q: q.arrival))
        done.update(res.outputs)
        spec = "" if not args.spec_tokens else (
            f", spec acc={res.acceptance_rate:.2f} "
            f"it/tok={res.iterations_per_token:.2f}")
        print(f"replica {rep.rid} on {rep.engine.device}: "
              f"{len(rep.queue)} requests, "
              f"prefill_tokens={res.prefill_tokens}, "
              f"prefix_hits={res.prefix_hits}/{res.prefix_lookups}, "
              f"peak_blocks={res.peak_blocks}{spec}")
    print(f"router: {router.stats.summary()}")
    return done, [rep.engine for rep in replicas]


def _serve_cluster_sim(args, prof, mon, tracer, cprof, cal_models) -> None:
    """Cluster-scale path: LatencyModel-backed replicas on per-replica HELR
    deployments, driven by the discrete-event simulator."""
    full_cfg = get_config(args.arch)
    n = max(args.requests, 128)
    pattern = args.workload if args.workload in ("bursty", "diurnal") \
        else "poisson"
    pools = None
    if args.models:
        # heterogeneous fleet: model-tagged, tier-skewed mixed trace and
        # one replica pool per model over the shared partition budget
        mix = _parse_model_mix(args.models)
        reqs = gen_mixed_requests(MixedWorkloadConfig(
            models=tuple(mix), n_requests=n, arrival_rate=16.0,
            arrival_pattern=pattern, seed=0))
        per = max(1, args.replicas // len(mix))
        pools = [ModelPoolSpec(m, replicas=per, weight=w) for m, w in mix]
    elif args.workload == "shared-prefix":
        reqs = gen_shared_prefix_requests(SharedPrefixConfig(
            n_requests=n, n_templates=max(4, n // 12), prefix_len=96,
            turns=4, arrival_rate=16.0, slo_lo=8.0, slo_hi=60.0, seed=0))
    else:
        reqs = gen_requests(WorkloadConfig(
            n_requests=n, arrival_rate=16.0, arrival_pattern=pattern,
            slo_lo=8.0, slo_hi=60.0, seed=0))
    auto = None
    if args.autoscale:
        if pools is not None and args.fleet == "joint":
            auto = FleetAutoscalerConfig(
                interval=1.0, budget=max(6, 2 * args.replicas),
                min_per_pool=1, spawn_delay=1.0)
        elif pools is not None:
            # replicated per pool by the simulator: independent autoscalers
            auto = AutoscalerConfig(
                interval=1.0, min_replicas=max(1, per),
                max_replicas=max(3, args.replicas), spawn_delay=1.0)
        else:
            auto = AutoscalerConfig(interval=1.0, min_replicas=args.replicas,
                                    max_replicas=max(6, 2 * args.replicas),
                                    spawn_delay=1.0)
    acc = _spec_acceptance(args, cprof)
    sched_cfg = SchedulerConfig()
    if args.spec_tokens:
        sched_cfg = sched_cfg.with_speculation(args.spec_tokens, acc)
    # a warm profile registry calibrates every replica's *pricing* model
    # (projections, shedding, autoscaler capacity) from its own
    # sub-profile; execution physics stay the replica's own analytic
    # model.  --pricing-quantile adds a tail model for the SLO-facing
    # projections (projected_finish, capacity_rps)
    price = tail_price = None
    if args.profile_in and pools is not None:
        # fleet pricing: each replica calibrates from its own sub-profile,
        # falling back to its model's pool aggregate before the fleet view
        def price(lm, rid, model):
            m = CalibratedLatencyModel(lm, cprof, replica=rid, model=model)
            cal_models.append(m)
            return m
        if args.pricing_quantile:
            def tail_price(lm, rid, model):
                m = CalibratedLatencyModel(lm, cprof, replica=rid,
                                           model=model,
                                           quantile=args.pricing_quantile)
                cal_models.append(m)
                return m
    elif args.profile_in:
        def price(lm, rid):
            m = CalibratedLatencyModel(lm, cprof, replica=rid)
            cal_models.append(m)
            return m
        if args.pricing_quantile:
            def tail_price(lm, rid):
                m = CalibratedLatencyModel(lm, cprof, replica=rid,
                                           quantile=args.pricing_quantile)
                cal_models.append(m)
                return m
    faults = retry = health = None
    if args.fault_crash or args.fault_mtbf > 0:
        events = []
        for spec in (args.fault_crash or "").split(","):
            if not spec:
                continue
            ts, _, rid = spec.partition(":")
            events.append(FaultEvent(t=float(ts), kind="crash",
                                     rid=int(rid or 0)))
        faults = FaultPlan(events=events, mtbf=args.fault_mtbf,
                           mttr=args.fault_mttr, seed=args.fault_seed)
        retry = RetryConfig(budget=args.retry_budget,
                            backoff_base=args.retry_backoff)
        tiers = tuple(t for t in (args.brownout_tiers or "").split(",") if t)
        health = HealthConfig(check_interval=args.health_interval,
                              detect_lag=args.detect_lag,
                              brownout_tiers=tiers)
    res = simulate_cluster(
        reqs, full_cfg, get_scheduler(args.scheduler), sched_cfg,
        n_replicas=args.replicas, pools=pools, router=args.router,
        autoscale=auto,
        prefix_cache=args.prefix_cache, chunk_tokens=args.chunk_tokens,
        preempt=args.preempt, spec_tokens=args.spec_tokens,
        spec_acceptance=acc,
        profiler=prof, monitor=mon, tracer=tracer, price=price,
        tail_price=tail_price, faults=faults, retry=retry, health=health)
    print("cluster:", res.summary())
    for s in res.replica_stats:
        tag = f" model={s['model']}" if pools is not None else ""
        print(f"  replica {s['rid']}:{tag} served={s['served']} "
              f"util={s['utilization']} queue_prefill={s['prefill_tokens']} "
              f"saved={s['prefill_tokens_saved']}")


def main(argv=None) -> dict:
    """Serve once from the command line ``argv`` (``sys.argv[1:]`` when
    None).  Returns ``{"outputs": rid -> generated tokens, "engines": the
    PagedEngines that served}`` (both empty on the simulated cluster)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="toy widths (configs.base.reduced) and 16/32-token "
                         "prompts, for a CPU; off: published widths")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--scheduler", default="slo-odbs",
                    choices=["slo-odbs", "slo-dbs", "odbs", "fifo", "s3"])
    ap.add_argument("--continuous", action="store_true",
                    help="beyond-paper continuous batching mode")
    ap.add_argument("--paged", action="store_true",
                    help="paged continuous batching (block-table KV cache)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prefix sharing over the paged pool "
                         "(implies --paged)")
    ap.add_argument("--lookahead", type=int, default=0,
                    help="queue entries scanned past a blocked head "
                         "(paged admission)")
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="per-iteration prefill chunk budget for the paged "
                         "engine (0: whole-prompt prefill at admission; "
                         "-1: derive from the scheduler's composite "
                         "threshold)")
    ap.add_argument("--preempt", action="store_true",
                    help="under block pressure evict the resident with the "
                         "most SLO slack and requeue it for recompute "
                         "instead of blocking a tighter arrival")
    ap.add_argument("--speculate", action="store_true",
                    help="speculative decoding on the paged engine: a "
                         "drafter proposes tokens verified in one "
                         "multi-token kernel pass; greedy acceptance keeps "
                         "outputs token-identical (implies --paged)")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="draft tokens verified per engine iteration")
    ap.add_argument("--drafter", default="ngram",
                    choices=["ngram", "model"],
                    help="draft proposer: deterministic n-gram prompt "
                         "lookup (free), or a small draft LM (here: "
                         "randomly initialized stand-in for a distilled "
                         "checkpoint — plumbing demo, low acceptance)")
    ap.add_argument("--workload", default="alpaca",
                    choices=["alpaca", "shared-prefix", "bursty", "diurnal"],
                    help="alpaca: lognormal Poisson mix; shared-prefix: "
                         "template-heavy prompts exercising the prefix cache; "
                         "bursty/diurnal: arrival patterns for --autoscale")
    ap.add_argument("--replicas", type=int, default=1,
                    help="cluster serving: replicas behind the router")
    ap.add_argument("--models", default=None, metavar="SPEC",
                    help="heterogeneous fleet on the simulated cluster: "
                         "comma list of arch[:weight] (e.g. "
                         "'chatglm2-6b:0.6,qwen2-1.5b:0.4').  Requests "
                         "arrive tagged with a model and an SLO tier, "
                         "replicas form per-model pools, and routing is "
                         "model-aware")
    ap.add_argument("--fleet", default="joint",
                    choices=["joint", "independent"],
                    help="with --models --autoscale: one joint allocator "
                         "over the shared replica budget (marginal SLO "
                         "value, model-swap actions) or independent "
                         "per-pool autoscalers")
    ap.add_argument("--router", default="round_robin",
                    choices=["round_robin", "least_loaded", "prefix_affinity",
                             "slo_aware"],
                    help="dispatch policy of the cluster layer")
    ap.add_argument("--autoscale", action="store_true",
                    help="forecast-driven elastic replica set (simulated "
                         "cluster; --replicas becomes the minimum)")
    ap.add_argument("--fault-crash", default=None, metavar="T:RID[,T:RID]",
                    help="inject scripted replica crashes into the cluster "
                         "sim, e.g. '2.5:1' crashes replica 1 at t=2.5s "
                         "(enables fault mode: health checks, retries)")
    ap.add_argument("--fault-mtbf", type=float, default=0.0,
                    help="seeded random faults: mean seconds between "
                         "failures per replica lane (0 = scripted only)")
    ap.add_argument("--fault-mttr", type=float, default=0.0,
                    help="mean recovery time of recoverable random faults")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the random fault model")
    ap.add_argument("--retry-budget", type=int, default=2,
                    help="re-dispatches granted to a request lost with a "
                         "failed replica before it counts as shed")
    ap.add_argument("--retry-backoff", type=float, default=0.25,
                    help="base seconds of the exponential retry backoff")
    ap.add_argument("--detect-lag", type=float, default=1.0,
                    help="seconds a silent replica stays routable before "
                         "the health layer declares it down")
    ap.add_argument("--health-interval", type=float, default=0.5,
                    help="heartbeat/health-scan cadence in fault mode")
    ap.add_argument("--brownout-tiers", default=None, metavar="T1[,T2]",
                    help="SLO tiers shed in this order under detected "
                         "capacity loss (graceful brownout), e.g. "
                         "'batch,interactive'")
    ap.add_argument("--kv-budget", type=float, default=2e6,
                    help="paged KV pool budget in bytes (shared with SLO-ODBS)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export the request-lifecycle trace as Chrome/"
                         "Perfetto JSON (load in ui.perfetto.dev)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write final metrics (incl. latency quantiles) as "
                         "JSON in the shared benchmark schema")
    ap.add_argument("--profile-out", default=None, metavar="PATH",
                    help="save the online cost profile (measured phase-time "
                         "cells + speculative-acceptance EMA) as a versioned "
                         "JSON registry after serving")
    ap.add_argument("--profile-in", default=None, metavar="PATH",
                    help="warm-start from a saved profile registry: pricing "
                         "models calibrate against its measured cells and "
                         "speculation plans at its measured acceptance")
    ap.add_argument("--pricing-quantile", type=float, default=None,
                    metavar="Q",
                    help="price SLO decisions (slo_aware shed/admit, "
                         "autoscaler capacity) at this quantile of the "
                         "measured observed/predicted ratio instead of its "
                         "mean (e.g. 0.95; needs --profile-in; throughput "
                         "projections stay mean-priced)")
    ap.add_argument("--profile-half-life", type=int, default=0,
                    metavar="N",
                    help="decay the profile's calibration statistics with "
                         "this sample half-life (rotating histograms, "
                         "bounded memory) so a throttled/migrated replica "
                         "re-learns; 0 = never forget.  Ignored with "
                         "--profile-in (the registry's setting wins)")
    args = ap.parse_args(argv)
    configure_compile_cache()
    if args.pricing_quantile is not None \
            and not 0.0 < args.pricing_quantile <= 1.0:
        raise SystemExit("--pricing-quantile must be in (0, 1]")
    if args.autoscale and args.paged:
        raise SystemExit("--autoscale needs the simulated cluster path: "
                         "drop --paged (elasticity has no live-engine mode)")
    if args.models and args.paged:
        raise SystemExit("--models needs the simulated cluster path: "
                         "drop --paged (the heterogeneous fleet has no "
                         "live-engine mode)")
    if (args.prefix_cache or args.speculate) \
            and not (args.replicas > 1 or args.autoscale or args.models):
        args.paged = True          # cluster sim path honors the flags itself
    args.spec_tokens = args.spec_tokens if args.speculate else 0

    # profiling without --trace still needs the span stream: a retain=False
    # tracer is a pure measurement bus (sinks see every event, nothing is
    # stored), so long serve runs profile at O(1) memory
    want_profile = bool(args.profile_in or args.profile_out)
    if args.trace:
        tracer = Tracer()
    elif want_profile:
        tracer = Tracer(retain=False)
    else:
        tracer = NULL_TRACER
    cprof = CostProfiler.load(args.profile_in, tracer=tracer) \
        if args.profile_in else CostProfiler(
            tracer=tracer, half_life=args.profile_half_life or None)
    if want_profile:
        tracer.add_sink(cprof.on_event)
    cal_models: list = []          # CalibratedLatencyModels the run priced by

    if args.chunk_tokens < 0:
        args.chunk_tokens = derive_chunk_tokens(SchedulerConfig(),
                                                block_size=BLOCK_SIZE)
        print(f"chunk budget from scheduler threshold: "
              f"{args.chunk_tokens} tokens/iteration")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"serving {cfg.name} "
          f"(plan for production mesh: "
          f"{helr_mesh(get_config(args.arch), SHAPES['decode_32k']).name})")

    if (args.replicas > 1 or args.autoscale or args.models) \
            and not args.paged:
        # cluster-scale path: simulated replicas, no model weights needed
        pred = LengthPredictor(PredictorConfig(), seed=0)
        toks, lens = train_pairs(WorkloadConfig(), 256, seed=1)
        pred.fit(toks, lens, epochs=8)
        prof = ResourceProfiler(pred, get_config(args.arch))
        mon = Monitor(prof)
        cprof.monitor = mon            # drift attribution lands in metrics
        _serve_cluster_sim(args, prof, mon, tracer, cprof, cal_models)
        print("monitor:", mon.metrics())
        _write_artifacts(args, mon, tracer, cprof, cal_models=cal_models)
        return {"outputs": {}, "engines": []}

    # jitted so no eager temporaries sit beside the weights on the device
    params = jax.jit(lambda key: api.init_params(cfg, key, jnp.float32))(
        jax.random.PRNGKey(0))

    if args.workload == "shared-prefix":
        reqs = gen_shared_prefix_requests(SharedPrefixConfig(
            n_requests=args.requests, n_templates=max(2, args.requests // 6),
            prefix_len=16, suffix_mean=2.0, vocab=cfg.vocab_size, seed=0))
        if args.reduced:
            for r in reqs:
                r.tokens = [t % cfg.vocab_size for t in r.tokens[:32]]
    else:
        pattern = args.workload if args.workload in ("bursty", "diurnal") \
            else "poisson"
        reqs = gen_requests(WorkloadConfig(n_requests=args.requests, seed=0,
                                           vocab=cfg.vocab_size,
                                           arrival_pattern=pattern))
        if args.reduced:
            for r in reqs:
                r.tokens = [t % cfg.vocab_size for t in r.tokens[:16]]
    for r in reqs:
        r.input_len = len(r.tokens)
        r.true_output_len = r.true_output_len % args.max_new + 1
    max_seq = _max_seq(reqs, args.max_new)

    pred = LengthPredictor(PredictorConfig(vocab=cfg.vocab_size), seed=0)
    toks, lens = train_pairs(WorkloadConfig(vocab=cfg.vocab_size), 256, seed=1)
    pred.fit(toks, lens, epochs=8)
    prof = ResourceProfiler(pred, cfg)
    mon = Monitor(prof)
    cprof.monitor = mon                # drift attribution lands in metrics
    prof.profile(reqs)

    t0 = time.perf_counter()
    engines: list = []
    if args.replicas > 1 and args.paged:
        done, engines = _serve_cluster_live(args, cfg, params, mon, reqs,
                                            tracer, cprof, cal_models,
                                            jax.devices())
    elif args.paged:
        pcfg = PagedEngineConfig.from_memory_budget(
            cfg, args.kv_budget, max_batch=MAX_BATCH, block_size=BLOCK_SIZE,
            max_seq_len=max_seq, max_new_tokens=args.max_new,
            prefix_cache=args.prefix_cache,
            admit_lookahead=args.lookahead,
            chunk_tokens=args.chunk_tokens, preempt=args.preempt,
            spec_tokens=args.spec_tokens, drafter=args.drafter)
        print(f"paged pool: {pcfg.usable_blocks} usable blocks (+null) x "
              f"{pcfg.block_size} slots ({args.kv_budget:.0f} B budget, "
              f"prefix_cache={'on' if pcfg.prefix_cache else 'off'}, "
              f"chunk_tokens={pcfg.chunk_tokens}, "
              f"preempt={'on' if pcfg.preempt else 'off'}, "
              f"speculate={pcfg.spec_tokens or 'off'})")
        paged = PagedEngine(cfg, params, pcfg, monitor=mon,
                            drafter=_make_drafter(args, cfg), tracer=tracer,
                            cost_profiler=cprof)
        engines = [paged]
        res = paged.run_continuous(sorted(reqs, key=lambda r: r.arrival))
        done = res.outputs
        print(f"paged: {res.admission_waves} admission waves, "
              f"prefill_tokens={res.prefill_tokens}, "
              f"peak_blocks={res.peak_blocks}, "
              f"kv_util={res.kv_utilization:.3f}, "
              f"waste_vs_padded={res.waste_vs_padded:.3f}")
        if pcfg.spec_tokens:
            print(f"speculate: {pcfg.spec_tokens} drafts/iter "
                  f"({args.drafter}), acceptance={res.acceptance_rate:.3f}, "
                  f"{res.steps} iterations for {res.generated_tokens} "
                  f"tokens ({res.iterations_per_token:.3f} it/tok), "
                  f"rolled_back={res.spec_rolled_blocks} blocks")
        if pcfg.chunk_tokens or pcfg.preempt:
            print(f"interleave: {res.prefill_chunks} chunks, "
                  f"stall={res.prefill_stall_s*1e3:.1f}ms, "
                  f"p99_itl={res.p99_inter_token_s*1e3:.2f}ms, "
                  f"preemptions={res.preemptions} "
                  f"({res.preempted_tokens} tokens recomputed)")
        if pcfg.prefix_cache:
            print(f"prefix: {res.prefix_hits}/{res.prefix_lookups} hits, "
                  f"hit_tokens={res.prefix_hit_tokens}, "
                  f"cow_forks={res.cow_forks}, "
                  f"evictions={res.prefix_evictions}, "
                  f"peak_residents={res.peak_residents}")
    else:
        engine = InferenceEngine(cfg, params, EngineConfig(
            max_batch=MAX_BATCH, cache_len=max_seq,
            max_new_tokens=args.max_new))
        if args.continuous:
            res = engine.run_continuous(
                sorted(reqs, key=lambda r: r.arrival))
            done = res.outputs
        else:
            done = {}
            for b in get_scheduler(args.scheduler)(
                    reqs, SchedulerConfig(max_batch=MAX_BATCH)):
                res = engine.run_batch(
                    b, true_lens={r.rid: r.true_output_len
                                  for r in b.requests})
                done.update(res.outputs)
                for r in b.requests:
                    mon.observe(r)
    dt = time.perf_counter() - t0
    total = sum(len(v) for v in done.values())
    print(f"served {len(done)} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s on {jax.devices()[0].platform})")
    print(f"outputs_digest={_outputs_digest(done)}")
    if args.spec_tokens and cprof.spec_samples:
        print(f"measured acceptance EMA: {cprof.spec_acceptance:.3f} "
              f"({cprof.spec_accepted}/{cprof.spec_drafted} over "
              f"{cprof.spec_samples} verify passes)")
    print("monitor:", mon.metrics())
    _write_artifacts(args, mon, tracer, cprof, throughput=total / dt,
                     cal_models=cal_models)
    return {"outputs": done, "engines": engines}


if __name__ == "__main__":
    main()
