"""Serving launcher: the UELLM pipeline on a real model, served live by the
paged continuous-batching engine (``PagedEngine``).

  PYTHONPATH=src python -m repro.launch.serve --reduced --requests 8

The model runs at its published widths; ``--reduced`` cuts it to the toy
widths a CPU can serve (and prompts to 16/32 tokens).  Paged decode runs the
Pallas kernels on a TPU and the blocked-XLA paths elsewhere
(``kernels.backend``).  JAX's persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.

Requests are served in arrival order (block-table KV, per-prompt prefill,
allocator-gated admission); the pool is sized from ``--kv-budget`` bytes.
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
requests are routed by ``--router`` across N replicas, each owning a real
PagedEngine (pool + prefix cache per replica), and the routed shares are
served live.  Replica i's params and KV pool live on
``jax.devices()[i % len(jax.devices())]`` — one chip per replica on a
multi-chip host, all on the one device elsewhere.

The simulated cluster (LatencyModel-backed replicas, SLO-ODBS batching,
autoscaling, heterogeneous fleets, fault injection) has its own entry
point, ``repro.launch.simulate``.  The flags both read are defined once, by
``add_common_args``.
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
                        SchedulerConfig, derive_chunk_tokens, helr_mesh)
from repro.core.profiler import PredictorConfig
from repro.data.workload import (SharedPrefixConfig, WorkloadConfig,
                                 gen_requests, gen_shared_prefix_requests,
                                 train_pairs)
from repro.models import api
from repro.obs.calibrate import CalibratedLatencyModel
from repro.obs.export import export_trace, metrics_payload, write_metrics
from repro.obs.profile import CostProfiler
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serving import (PagedEngine, PagedEngineConfig, Replica, Router,
                           RouterConfig, get_drafter, paper_cluster)


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


def _quantile(text: str) -> float:
    q = float(text)
    if not 0.0 < q <= 1.0:
        raise argparse.ArgumentTypeError("must be in (0, 1]")
    return q


def add_common_args(ap: argparse.ArgumentParser) -> None:
    """The flags ``serve`` and ``simulate`` both read: the model and its
    workload, the engine knobs the replica projections price, the router,
    and the trace / metrics / cost-profile artifacts."""
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="toy widths (configs.base.reduced) and 16/32-token "
                         "prompts, for a CPU; off: published widths")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prefix sharing over the paged pool")
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
                         "outputs token-identical")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="draft tokens verified per engine iteration")
    ap.add_argument("--workload", default="alpaca",
                    choices=["alpaca", "shared-prefix", "bursty", "diurnal"],
                    help="alpaca: lognormal Poisson mix; shared-prefix: "
                         "template-heavy prompts exercising the prefix cache; "
                         "bursty/diurnal: arrival patterns")
    ap.add_argument("--replicas", type=int, default=1,
                    help="cluster serving: replicas behind the router")
    ap.add_argument("--router", default="round_robin",
                    choices=["round_robin", "least_loaded", "prefix_affinity",
                             "slo_aware"],
                    help="dispatch policy of the cluster layer")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export the request-lifecycle trace as Chrome/"
                         "Perfetto JSON (load in ui.perfetto.dev)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write final metrics (incl. latency quantiles) as "
                         "JSON in the shared metrics schema")
    ap.add_argument("--profile-out", default=None, metavar="PATH",
                    help="save the online cost profile (measured phase-time "
                         "cells + speculative-acceptance EMA) as a versioned "
                         "JSON registry after serving")
    ap.add_argument("--profile-in", default=None, metavar="PATH",
                    help="warm-start from a saved profile registry: pricing "
                         "models calibrate against its measured cells and "
                         "speculation plans at its measured acceptance")
    ap.add_argument("--pricing-quantile", type=_quantile, default=None,
                    metavar="Q",
                    help="price SLO decisions (slo_aware shed/admit, "
                         "autoscaler capacity) at this quantile of the "
                         "measured observed/predicted ratio instead of its "
                         "mean (e.g. 0.95, in (0, 1]; read with "
                         "--profile-in; throughput projections stay "
                         "mean-priced)")
    ap.add_argument("--profile-half-life", type=int, default=0,
                    metavar="N",
                    help="decay the profile's calibration statistics with "
                         "this sample half-life (rotating histograms, "
                         "bounded memory) so a throttled/migrated replica "
                         "re-learns; 0 = never forget.  Ignored with "
                         "--profile-in (the registry's setting wins)")


def engine_knobs(args) -> tuple[int, int]:
    """``(spec_tokens, chunk_tokens)`` of the run: no drafts without
    ``--speculate``, and a negative ``--chunk-tokens`` derived from the
    scheduler's composite threshold."""
    spec_tokens = args.spec_tokens if args.speculate else 0
    chunk_tokens = args.chunk_tokens
    if chunk_tokens < 0:
        chunk_tokens = derive_chunk_tokens(SchedulerConfig(),
                                           block_size=BLOCK_SIZE)
        print(f"chunk budget from scheduler threshold: "
              f"{chunk_tokens} tokens/iteration")
    return spec_tokens, chunk_tokens


def observability(args) -> tuple[Tracer, CostProfiler]:
    """The run's tracer and online cost profiler.  Profiling without
    ``--trace`` still needs the span stream: a ``retain=False`` tracer is a
    pure measurement bus (sinks see every event, nothing is stored), so long
    runs profile at O(1) memory."""
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
    return tracer, cprof


def planned_acceptance(spec_tokens: int, cprof: CostProfiler) -> float:
    """Speculation acceptance for *planning* (replica projections,
    SchedulerConfig.spec_speedup): the cost profiler's measured EMA —
    warm-started from ``--profile-in``, its bootstrap prior when nothing
    has been measured yet, and live-updated by ``PagedEngine._spec_step``
    once serving starts."""
    return cprof.spec_acceptance if spec_tokens else 0.0


def _max_seq(reqs, max_new: int) -> int:
    """Block-table width: the longest prompt plus the decode budget, so any
    --max-new value is admissible."""
    max_prompt = max(len(r.tokens) for r in reqs)
    return max(64, -(-(max_prompt + max_new) // BLOCK_SIZE) * BLOCK_SIZE)


def _make_drafter(pcfg: PagedEngineConfig, cfg):
    """Engine drafter from the config (None lets the engine default)."""
    if pcfg.spec_tokens > 0 and pcfg.drafter == "model":
        return get_drafter("model", draft_cfg=cfg)
    return None


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


def write_artifacts(args, mon, tracer, cprof, *, latency_s=None,
                    p99_latency_s=None, throughput=None,
                    utilization=None, cal_models=()) -> None:
    """Export the request-lifecycle trace (``--trace``, Chrome/Perfetto JSON)
    and the shared metrics payload (``--metrics-json``).  Latency quantiles
    default to the monitor's e2e histogram when the caller has no direct
    measurement.  Profiled runs also report how calibrated pricing resolved
    (coverage counters) and which replicas drifted."""
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


def _serve_cluster_live(args, cfg, params, pcfg, mon, reqs, tracer, cprof,
                        cal_models, devices) -> tuple[dict, list]:
    """Route requests across N real PagedEngine-backed replicas, then serve
    each replica's share live (per-replica pool + prefix cache).  Replica i
    holds its params and pool on ``devices[i % len(devices)]``; every jitted
    step follows those committed inputs.  Returns (outputs, engines)."""
    router = Router(RouterConfig(policy=args.router))
    replicas = []
    for i in range(args.replicas):
        nodes, lat = paper_cluster()
        dev = devices[i % len(devices)]
        rep = Replica(
            i, cfg, nodes, lat, max_batch=MAX_BATCH, block_size=BLOCK_SIZE,
            n_blocks=pcfg.usable_blocks, prefix_cache=pcfg.prefix_cache,
            chunk_tokens=pcfg.chunk_tokens, preempt=pcfg.preempt,
            spec_tokens=pcfg.spec_tokens,
            spec_acceptance=planned_acceptance(pcfg.spec_tokens, cprof),
            engine=PagedEngine(cfg, jax.device_put(params, dev), pcfg,
                               monitor=mon,
                               drafter=_make_drafter(pcfg, cfg),
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
        if pcfg.spec_tokens:
            # replicas serve sequentially here, so each one plans at the
            # acceptance the earlier shares already measured
            rep.spec_acceptance = cprof.spec_acceptance
        res = rep.engine.run_continuous(
            sorted(rep.queue, key=lambda q: q.arrival))
        done.update(res.outputs)
        spec = "" if not pcfg.spec_tokens else (
            f", spec acc={res.acceptance_rate:.2f} "
            f"it/tok={res.iterations_per_token:.2f}")
        print(f"replica {rep.rid} on {rep.engine.device}: "
              f"{len(rep.queue)} requests, "
              f"prefill_tokens={res.prefill_tokens}, "
              f"prefix_hits={res.prefix_hits}/{res.prefix_lookups}, "
              f"peak_blocks={res.peak_blocks}{spec}")
    print(f"router: {router.stats.summary()}")
    return done, [rep.engine for rep in replicas]


def _serve_one(args, cfg, params, pcfg, mon, reqs, tracer,
               cprof) -> tuple[dict, list]:
    """Serve every request on one PagedEngine, in arrival order.  Returns
    (outputs, [engine])."""
    print(f"paged pool: {pcfg.usable_blocks} usable blocks (+null) x "
          f"{pcfg.block_size} slots ({args.kv_budget:.0f} B budget, "
          f"prefix_cache={'on' if pcfg.prefix_cache else 'off'}, "
          f"chunk_tokens={pcfg.chunk_tokens}, "
          f"preempt={'on' if pcfg.preempt else 'off'}, "
          f"speculate={pcfg.spec_tokens or 'off'})")
    engine = PagedEngine(cfg, params, pcfg, monitor=mon,
                         drafter=_make_drafter(pcfg, cfg), tracer=tracer,
                         cost_profiler=cprof)
    res = engine.run_continuous(sorted(reqs, key=lambda r: r.arrival))
    print(f"paged: {res.admission_waves} admission waves, "
          f"prefill_tokens={res.prefill_tokens}, "
          f"peak_blocks={res.peak_blocks}, "
          f"kv_util={res.kv_utilization:.3f}, "
          f"waste_vs_padded={res.waste_vs_padded:.3f}")
    if pcfg.spec_tokens:
        print(f"speculate: {pcfg.spec_tokens} drafts/iter "
              f"({pcfg.drafter}), acceptance={res.acceptance_rate:.3f}, "
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
    return res.outputs, [engine]


def _requests(args, cfg) -> list:
    """The run's requests at the model's vocabulary, prompts cut to 16/32
    tokens with ``--reduced``, output lengths within ``--max-new``."""
    if args.workload == "shared-prefix":
        reqs = gen_shared_prefix_requests(SharedPrefixConfig(
            n_requests=args.requests, n_templates=max(2, args.requests // 6),
            prefix_len=16, suffix_mean=2.0, vocab=cfg.vocab_size, seed=0))
        cut = 32
    else:
        pattern = args.workload if args.workload in ("bursty", "diurnal") \
            else "poisson"
        reqs = gen_requests(WorkloadConfig(n_requests=args.requests, seed=0,
                                           vocab=cfg.vocab_size,
                                           arrival_pattern=pattern))
        cut = 16
    for r in reqs:
        if args.reduced:
            r.tokens = [t % cfg.vocab_size for t in r.tokens[:cut]]
        r.input_len = len(r.tokens)
        r.true_output_len = r.true_output_len % args.max_new + 1
    return reqs


def main(argv=None) -> dict:
    """Serve once from the command line ``argv`` (``sys.argv[1:]`` when
    None).  Returns ``{"outputs": rid -> generated tokens, "engines": the
    PagedEngines that served}``."""
    ap = argparse.ArgumentParser()
    add_common_args(ap)
    ap.add_argument("--lookahead", type=int, default=0,
                    help="queue entries scanned past a blocked head "
                         "(paged admission)")
    ap.add_argument("--drafter", default="ngram",
                    choices=["ngram", "model"],
                    help="draft proposer: deterministic n-gram prompt "
                         "lookup (free), or a small draft LM (here: "
                         "randomly initialized stand-in for a distilled "
                         "checkpoint — plumbing demo, low acceptance)")
    ap.add_argument("--kv-budget", type=float, default=2e6,
                    help="paged KV pool budget in bytes")
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args(argv)
    configure_compile_cache()
    tracer, cprof = observability(args)
    cal_models: list = []          # CalibratedLatencyModels the run priced by
    spec_tokens, chunk_tokens = engine_knobs(args)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"serving {cfg.name} "
          f"(plan for production mesh: "
          f"{helr_mesh(get_config(args.arch), SHAPES['decode_32k']).name})")

    # jitted so no eager temporaries sit beside the weights on the device
    params = jax.jit(lambda key: api.init_params(cfg, key, jnp.float32))(
        jax.random.PRNGKey(0))
    reqs = _requests(args, cfg)
    pcfg = PagedEngineConfig.from_memory_budget(
        cfg, args.kv_budget, max_batch=MAX_BATCH, block_size=BLOCK_SIZE,
        max_seq_len=_max_seq(reqs, args.max_new),
        max_new_tokens=args.max_new, prefix_cache=args.prefix_cache,
        admit_lookahead=args.lookahead, chunk_tokens=chunk_tokens,
        preempt=args.preempt, spec_tokens=spec_tokens, drafter=args.drafter)

    pred = LengthPredictor(PredictorConfig(vocab=cfg.vocab_size), seed=0)
    toks, lens = train_pairs(WorkloadConfig(vocab=cfg.vocab_size), 256, seed=1)
    pred.fit(toks, lens, epochs=8)
    prof = ResourceProfiler(pred, cfg)
    mon = Monitor(prof)
    cprof.monitor = mon                # drift attribution lands in metrics
    prof.profile(reqs)

    t0 = time.perf_counter()
    if args.replicas > 1:
        done, engines = _serve_cluster_live(args, cfg, params, pcfg, mon,
                                            reqs, tracer, cprof, cal_models,
                                            jax.devices())
    else:
        done, engines = _serve_one(args, cfg, params, pcfg, mon, reqs,
                                   tracer, cprof)
    dt = time.perf_counter() - t0
    total = sum(len(v) for v in done.values())
    print(f"served {len(done)} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s on {jax.devices()[0].platform})")
    print(f"outputs_digest={_outputs_digest(done)}")
    if spec_tokens and cprof.spec_samples:
        print(f"measured acceptance EMA: {cprof.spec_acceptance:.3f} "
              f"({cprof.spec_accepted}/{cprof.spec_drafted} over "
              f"{cprof.spec_samples} verify passes)")
    print("monitor:", mon.metrics())
    write_artifacts(args, mon, tracer, cprof, throughput=total / dt,
                    cal_models=cal_models)
    return {"outputs": done, "engines": engines}


if __name__ == "__main__":
    main()
