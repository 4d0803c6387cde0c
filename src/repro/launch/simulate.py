"""Simulated cluster launcher: the cluster-scale UELLM path, no model weights.

  PYTHONPATH=src python -m repro.launch.simulate --replicas 2 \
      --router slo_aware --scheduler slo-odbs

Requests are batched by ``--scheduler`` (SLO-ODBS by default) and served by
LatencyModel-backed replicas on per-replica HELR deployments, driven by the
discrete-event simulator (``serving.simulator.simulate_cluster``) — the
cluster-scale path, which ``--autoscale`` extends with the forecast-driven
elastic replica set (``--workload bursty`` exercises it).  ``--models``
turns the cluster into a heterogeneous MLaaS fleet: a mixed-model,
tier-skewed trace is served by per-model replica pools with model-aware
routing, and ``--fleet`` picks between one joint allocator over the shared
replica budget (marginal SLO value, model-swap actions) and independent
per-pool autoscalers.  ``--fault-crash`` / ``--fault-mtbf`` inject replica
failures and turn on health checks, retries and tier-ordered brownout.

Its times are the simulator's, never a device's.  The flags it shares with
``repro.launch.serve`` (model, workload, the engine knobs the replica
projections price, router, cost profile and artifacts) are defined once, by
``serve.add_common_args``.
"""
from __future__ import annotations

import argparse

from repro.configs import SHAPES, get_config
from repro.core import (LengthPredictor, Monitor, ResourceProfiler,
                        SchedulerConfig, get_scheduler, helr_mesh)
from repro.core.profiler import PredictorConfig
from repro.data.workload import (MixedWorkloadConfig, SharedPrefixConfig,
                                 WorkloadConfig, gen_mixed_requests,
                                 gen_requests, gen_shared_prefix_requests,
                                 train_pairs)
from repro.launch import serve
from repro.obs.calibrate import CalibratedLatencyModel
from repro.serving import (AutoscalerConfig, ClusterSimResult, FaultEvent,
                           FaultPlan, FleetAutoscalerConfig, HealthConfig,
                           ModelPoolSpec, RetryConfig, simulate_cluster)


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


def _serve_cluster_sim(args, prof, mon, tracer, cprof, cal_models,
                       spec_tokens: int,
                       chunk_tokens: int) -> ClusterSimResult:
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
    acc = serve.planned_acceptance(spec_tokens, cprof)
    sched_cfg = SchedulerConfig()
    if spec_tokens:
        sched_cfg = sched_cfg.with_speculation(spec_tokens, acc)
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
        prefix_cache=args.prefix_cache, chunk_tokens=chunk_tokens,
        preempt=args.preempt, spec_tokens=spec_tokens,
        spec_acceptance=acc,
        profiler=prof, monitor=mon, tracer=tracer, price=price,
        tail_price=tail_price, faults=faults, retry=retry, health=health)
    print("cluster:", res.summary())
    for s in res.replica_stats:
        tag = f" model={s['model']}" if pools is not None else ""
        print(f"  replica {s['rid']}:{tag} served={s['served']} "
              f"util={s['utilization']} queue_prefill={s['prefill_tokens']} "
              f"saved={s['prefill_tokens_saved']}")
    return res


def main(argv=None) -> ClusterSimResult:
    """Simulate once from the command line ``argv`` (``sys.argv[1:]`` when
    None); returns the simulator's result."""
    ap = argparse.ArgumentParser()
    serve.add_common_args(ap)
    ap.add_argument("--scheduler", default="slo-odbs",
                    choices=["slo-odbs", "slo-dbs", "odbs", "fifo", "s3"],
                    help="batch composer of every simulated replica")
    ap.add_argument("--models", default=None, metavar="SPEC",
                    help="heterogeneous fleet: comma list of arch[:weight] "
                         "(e.g. 'chatglm2-6b:0.6,qwen2-1.5b:0.4').  Requests "
                         "arrive tagged with a model and an SLO tier, "
                         "replicas form per-model pools, and routing is "
                         "model-aware")
    ap.add_argument("--fleet", default="joint",
                    choices=["joint", "independent"],
                    help="with --models --autoscale: one joint allocator "
                         "over the shared replica budget (marginal SLO "
                         "value, model-swap actions) or independent "
                         "per-pool autoscalers")
    ap.add_argument("--autoscale", action="store_true",
                    help="forecast-driven elastic replica set (--replicas "
                         "becomes the minimum)")
    ap.add_argument("--fault-crash", default=None, metavar="T:RID[,T:RID]",
                    help="inject scripted replica crashes, e.g. '2.5:1' "
                         "crashes replica 1 at t=2.5s (enables fault mode: "
                         "health checks, retries)")
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
    args = ap.parse_args(argv)
    serve.configure_compile_cache()
    tracer, cprof = serve.observability(args)
    cal_models: list = []          # CalibratedLatencyModels the run priced by
    spec_tokens, chunk_tokens = serve.engine_knobs(args)

    cfg = get_config(args.arch)
    print(f"serving {(cfg.reduced() if args.reduced else cfg).name} "
          f"(plan for production mesh: "
          f"{helr_mesh(cfg, SHAPES['decode_32k']).name})")
    pred = LengthPredictor(PredictorConfig(), seed=0)
    toks, lens = train_pairs(WorkloadConfig(), 256, seed=1)
    pred.fit(toks, lens, epochs=8)
    prof = ResourceProfiler(pred, cfg)
    mon = Monitor(prof)
    cprof.monitor = mon                # drift attribution lands in metrics
    res = _serve_cluster_sim(args, prof, mon, tracer, cprof, cal_models,
                             spec_tokens, chunk_tokens)
    print("monitor:", mon.metrics())
    serve.write_artifacts(args, mon, tracer, cprof, cal_models=cal_models)
    return res


if __name__ == "__main__":
    main()
