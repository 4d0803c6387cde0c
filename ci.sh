#!/usr/bin/env bash
# CI smoke: tier-1 test suite + interpret-mode kernel validation.
#
#   ./ci.sh            # everything
#   ./ci.sh kernels    # kernel parity tests only (fast)
#   ./ci.sh serving    # paged-engine + prefix-cache runtime tests (fast)
#   ./ci.sh cluster    # cluster router/autoscaler tests + smoke (fast)
set -euo pipefail
cd "$(dirname "$0")"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

KERNEL_TESTS=(tests/test_kernels_flash.py tests/test_kernels_decode.py
              tests/test_kernels_wkv6.py tests/test_paged_attention.py)
SERVING_TESTS=(tests/test_paged_engine.py tests/test_prefix_cache.py
               tests/test_speculative.py)
CLUSTER_TESTS=(tests/test_cluster.py tests/test_workload.py)

interleave_smoke() {
    echo "== interleave smoke (chunked prefill + forced preemption) =="
    python - <<'PY'
import copy, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.types import Request
from repro.models import api
from repro.serving import PagedEngine, PagedEngineConfig

cfg = get_config("smollm-135m").reduced()
params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
# r0: 16-token prompt -> 2 chunks at chunk_tokens=8; slack SLO (evictable).
# r1: tight arrival that only fits once r0's blocks are reclaimed.
reqs = [Request(rid=0, tokens=[3] * 16, input_len=16, slo=1000.0,
                arrival=0.0, true_output_len=6),
        Request(rid=1, tokens=[5] * 8, input_len=8, slo=0.001,
                arrival=0.0, true_output_len=4)]
# reference: a plain paged run with room for both, whole-prompt prefill
ref = PagedEngine(cfg, params, PagedEngineConfig(
    max_batch=2, block_size=8, n_blocks=24, max_seq_len=32,
    max_new_tokens=8)).run_continuous([copy.copy(r) for r in reqs])
eng = PagedEngine(cfg, params, PagedEngineConfig(
    max_batch=2, block_size=8, n_blocks=5, max_seq_len=32,
    max_new_tokens=8, chunk_tokens=8, preempt=True))
res = eng.run_continuous([copy.copy(r) for r in reqs])
assert res.preemptions >= 1, res.preemptions
assert res.prefill_chunks >= 4, res.prefill_chunks   # 2 chunks + recompute
assert all(res.outputs[r.rid] == ref.outputs[r.rid] for r in reqs)
print(f"interleave smoke: chunks={res.prefill_chunks} "
      f"preemptions={res.preemptions} (token-identical)")
PY
}

spec_smoke() {
    echo "== speculative smoke (n-gram drafter, token-identity) =="
    python - <<'PY'
import copy, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.types import Request
from repro.models import api
from repro.serving import PagedEngine, PagedEngineConfig

cfg = get_config("smollm-135m").reduced()
params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
# cycled prompts: the n-gram drafter must land at least some accepts, and
# greedy acceptance must keep outputs exactly equal to sequential decode
reqs = [Request(rid=i, tokens=([7 + i, 11, 13 + i, 17] * 6)[:20],
                input_len=20, slo=60.0, arrival=0.0, true_output_len=10)
        for i in range(4)]
# reference: the same engine shape without speculation
ref = PagedEngine(cfg, params, PagedEngineConfig(
    max_batch=2, block_size=8, n_blocks=24, max_seq_len=48,
    max_new_tokens=12)).run_continuous([copy.copy(r) for r in reqs])
eng = PagedEngine(cfg, params, PagedEngineConfig(
    max_batch=2, block_size=8, n_blocks=24, max_seq_len=48,
    max_new_tokens=12, spec_tokens=4))
res = eng.run_continuous([copy.copy(r) for r in reqs])
assert all(res.outputs[r.rid] == ref.outputs[r.rid] for r in reqs), \
    "speculation changed outputs"
assert res.drafted_tokens > 0, "drafter never proposed"
print(f"spec smoke: {res.steps} iterations for {res.generated_tokens} "
      f"tokens, acceptance={res.acceptance_rate:.2f} (token-identical)")
PY
}

cluster_smoke() {
    echo "== cluster smoke (2 simulated replicas, slo_aware router) =="
    python - <<'PY'
from repro.configs import get_config
from repro.core import get_scheduler
from repro.core.scheduler import SchedulerConfig
from repro.data.workload import WorkloadConfig, gen_requests
from repro.serving import simulate_cluster

reqs = gen_requests(WorkloadConfig(n_requests=48, arrival_rate=16.0,
                                   slo_lo=5.0, slo_hi=50.0, seed=1))
res = simulate_cluster(reqs, get_config("chatglm2-6b"),
                       get_scheduler("slo-odbs"), SchedulerConfig(),
                       n_replicas=2, router="slo_aware")
assert len(res.finished) + len(res.shed) == 48, res.summary()
assert res.peak_replicas == 2
assert 0.0 <= res.slo_attainment <= 1.0
print("cluster smoke:", res.summary())
PY
}

fault_smoke() {
    echo "== fault smoke (scripted crash + retry, token identity, leak audit) =="
    python - <<'PY'
from repro.configs import get_config
from repro.core import LengthPredictor, Monitor, ResourceProfiler, get_scheduler
from repro.core.profiler import PredictorConfig
from repro.core.scheduler import SchedulerConfig
from repro.data.workload import WorkloadConfig, gen_requests
from repro.obs.export import metrics_payload, validate_metrics
from repro.serving import (FaultEvent, HealthConfig, RetryConfig,
                           simulate_cluster)

cfg = get_config("chatglm2-6b")
reqs = gen_requests(WorkloadConfig(n_requests=48, arrival_rate=12.0,
                                   slo_lo=8.0, slo_hi=50.0, seed=3))
mon = Monitor(ResourceProfiler(LengthPredictor(PredictorConfig(), seed=0),
                               cfg), update_on_miss=False)
res = simulate_cluster(reqs, cfg, get_scheduler("slo-odbs"),
                       SchedulerConfig(), n_replicas=2, router="slo_aware",
                       monitor=mon,
                       faults=[FaultEvent(t=1.0, kind="crash", rid=0)],
                       retry=RetryConfig(budget=2),
                       health=HealthConfig(check_interval=0.2,
                                           detect_lag=0.5))
# crash detected, lost work recovered, every request has exactly one fate
assert mon.stats.failures_by_kind == {"crash": 1}, mon.stats.failures_by_kind
assert mon.stats.request_retries > 0
assert len(res.finished) + len(res.shed) == len(res.requests)
payload = metrics_payload("fault_smoke",
                          slo_attainment=res.slo_attainment,
                          monitor=mon.metrics())
errs = validate_metrics(payload)
assert not errs, errs
assert payload["monitor"]["faults"]["retries"] > 0
print(f"fault smoke: attainment={res.slo_attainment:.3f} "
      f"retries={mon.stats.request_retries} (metrics schema valid)")
PY
    python - <<'PY'
import copy, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.types import Request
from repro.models import api
from repro.serving import PagedEngine, PagedEngineConfig

cfg = get_config("smollm-135m").reduced()
params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)

def engine():
    return PagedEngine(cfg, params, PagedEngineConfig(
        max_batch=2, block_size=8, n_blocks=24, max_seq_len=48,
        max_new_tokens=10))

reqs = [Request(rid=i, tokens=[3 + i] * 12, input_len=12, slo=60.0,
                arrival=0.0, true_output_len=8) for i in range(2)]
ref = engine().run_continuous([copy.copy(r) for r in reqs])
# crash rid=0 two tokens in; every engine run ends with the allocator
# leak audit (run_continuous raises on any leaked block)
crashed = engine().run_continuous([copy.copy(r) for r in reqs],
                                  abort_at={0: 2})
assert crashed.errors == {0: "aborted"}, crashed.errors
partial = crashed.outputs[0]
resumed = engine().run_continuous([copy.copy(reqs[0])],
                                  resume={0: partial})
assert partial == ref.outputs[0][:len(partial)]
assert resumed.outputs[0] == ref.outputs[0], "retry not token-identical"
print(f"fault smoke: abort@{len(partial)} -> resume token-identical, "
      f"zero leaked blocks")
PY
}

fleet_smoke() {
    echo "== fleet smoke (2 models x 2 tiers, model-aware routing, v6 metrics) =="
    python -m repro.launch.simulate --reduced --arch chatglm2-6b \
        --models "chatglm2-6b:0.6,qwen2-1.5b:0.4" --requests 32 \
        --replicas 2 --router slo_aware --fleet joint \
        --metrics-json /tmp/fleet_m.json > /dev/null
    python - <<'PY'
import json
from repro.obs.export import METRICS_SCHEMA_VERSION, validate_metrics

m = json.load(open("/tmp/fleet_m.json"))
errs = validate_metrics(m)
assert not errs, errs
assert m["schema"] == METRICS_SCHEMA_VERSION == 6, m["schema"]
by_key = m["monitor"].get("slo_by_key", {})
models = {k for k in by_key if k.startswith("model:")}
tiers = {k for k in by_key if k.startswith("tier:")}
assert {"model:chatglm2-6b", "model:qwen2-1.5b"} <= models, by_key
assert tiers, by_key
for k, blk in by_key.items():
    assert {"observed", "violations", "attainment"} <= set(blk), (k, blk)
print(f"fleet smoke: per-model attainment "
      f"{ {k: by_key[k]['attainment'] for k in sorted(models)} }, "
      f"tiers { {k: by_key[k]['attainment'] for k in sorted(tiers)} }")
PY
}

traced_smoke() {
    echo "== traced smoke (serve.py --trace/--metrics-json) =="
    python -m repro.launch.serve --reduced --preempt --speculate \
        --chunk-tokens 8 --requests 8 \
        --trace /tmp/trace.json --metrics-json /tmp/m.json > /dev/null
    python - <<'PY'
import json
from repro.obs.export import validate_metrics, validate_trace

obj = json.load(open("/tmp/trace.json"))
errs = validate_trace(obj)
assert not errs, errs
names = {e["name"] for e in obj["traceEvents"] if e["ph"] != "M"}
need = {"queued", "admitted", "prefill_chunk", "finish"}
assert need <= names, need - names
metrics = json.load(open("/tmp/m.json"))
errs = validate_metrics(metrics)
assert not errs, errs
assert metrics["schema"] >= 4, metrics["schema"]   # v4: per-replica drift
mon = metrics["monitor"]
for key in ("queue_wait", "ttft", "itl", "e2e"):
    assert {"p50", "p95", "p99"} <= set(mon[key]), key
print(f"traced smoke: {len(obj['traceEvents'])} events, "
      f"p99_e2e={mon['e2e']['p99']:.3f}s (both artifacts valid)")
PY
}

profile_smoke() {
    echo "== profile smoke (--profile-out / --profile-in round trip) =="
    python -m repro.launch.serve --reduced --speculate \
        --chunk-tokens 8 --requests 8 --profile-out /tmp/prof.json > /tmp/serve_a.log
    python -m repro.launch.serve --reduced --speculate \
        --chunk-tokens 8 --requests 8 --profile-in /tmp/prof.json > /tmp/serve_b.log
    da=$(grep -o 'outputs_digest=[0-9a-f]*' /tmp/serve_a.log)
    db=$(grep -o 'outputs_digest=[0-9a-f]*' /tmp/serve_b.log)
    if [[ -z "$da" || "$da" != "$db" ]]; then
        echo "profile smoke: calibrated pricing changed outputs" \
             "('$da' vs '$db')"
        exit 1
    fi
    python - <<'PY'
import json
from repro.obs import CalibratedLatencyModel, CostProfiler

a = CostProfiler.load("/tmp/prof.json")
b = CostProfiler.from_json(a.to_json())
assert a.to_json() == b.to_json(), "profile registry not byte-stable"
cov = a.coverage()
assert any(c["samples"] > 0 for c in cov.values()), cov
for key, ca in a.cells.items():
    cb = b.cells[key]
    assert ca.ema_s == cb.ema_s and ca.mean_s == cb.mean_s \
        and ca.ratio_ema == cb.ratio_ema, key
# v2 registries carry per-replica sub-profiles; they must survive the
# round trip cell-identical too (serve runs on replica 0)
assert set(a.replica_profiles) == set(b.replica_profiles)
for rid, sub in a.replica_profiles.items():
    for key, ca in sub.cells.items():
        cb = b.replica_profiles[rid].cells[key]
        assert ca.ema_s == cb.ema_s and ca.ratio_ema == cb.ratio_ema, \
            (rid, key)
# a legacy flat (v1) registry still loads — as a fleet-only profile —
# and any other version is refused with a clear error
fleet = a.to_json()["fleet"]
v1 = {"profile_version": 1, "alpha": a.alpha, "drift_tol": a.drift_tol,
      "drift_min_samples": a.drift_min_samples, "drift_events": 1,
      "cells": [{"key": c["key"], "count": c["count"],
                 "ema_s": c["ema_s"], "total_s": c["total_s"],
                 "hist": c["hist"], "ratio_count": c["ratio_count"],
                 "ratio_ema": (c["ratio_num"] / c["ratio_den"])
                 if c["ratio_den"] else 0.0}
                for c in fleet["cells"]],
      "residual": fleet["residual"],
      "phase_ratio": {ph: [pr[0], pr[1] / pr[2] if pr[2] else 0.0]
                      for ph, pr in fleet["phase_ratio"].items()},
      "spec": {"drafted": 0, "accepted": 0, "samples": 0,
               "ema": 0.5, "bootstrap": 0.5}}
old = CostProfiler.from_json(json.loads(json.dumps(v1)))
assert old.replica_profiles == {}, "v1 import must be fleet-only"
assert len(old.cells) == len(a.cells)
assert old.drift_events == 1
try:
    CostProfiler.from_json({"profile_version": 99})
except ValueError as e:
    assert "profile_version" in str(e), e
else:
    raise AssertionError("unknown profile_version was not refused")
print(f"profile smoke: {len(a.cells)} cells "
      f"({len(a.replica_profiles)} replica sub-profiles) round-trip "
      f"identical, v1 loads fleet-only, v99 refused, "
      f"coverage={json.dumps(cov)} (token-identical serve)")
PY
}

validate_artifacts() {
    echo "== bench artifact validation (shared metrics schema) =="
    python - <<'PY'
import glob, json, sys
from repro.obs.export import validate_metrics

files = sorted(glob.glob("artifacts/bench/BENCH_*.json"))
bad = 0
for f in files:
    errs = validate_metrics(json.load(open(f)))
    if errs:
        print(f"{f}: INVALID {errs}")
        bad += 1
if bad:
    sys.exit(1)
print(f"validate_artifacts: {len(files)} BENCH_*.json artifacts valid"
      if files else "validate_artifacts: no artifacts present (ok)")
PY
}

if [[ "${1:-}" == "kernels" ]]; then
    python -m pytest -q "${KERNEL_TESTS[@]}"
    exit 0
fi

if [[ "${1:-}" == "serving" ]]; then
    python -m pytest -q "${SERVING_TESTS[@]}"
    interleave_smoke
    spec_smoke
    exit 0
fi

if [[ "${1:-}" == "cluster" ]]; then
    python -m pytest -q "${CLUSTER_TESTS[@]}" tests/test_faults.py
    cluster_smoke
    fleet_smoke
    fault_smoke
    exit 0
fi

echo "== tier-1 (kernel files deferred to the dedicated step below) =="
IGNORES=()
for t in "${KERNEL_TESTS[@]}"; do IGNORES+=("--ignore=$t"); done
python -m pytest -x -q "${IGNORES[@]}"

echo "== kernel parity (pallas interpret + xla vs oracle) =="
python -m pytest -q "${KERNEL_TESTS[@]}"

interleave_smoke
spec_smoke
cluster_smoke
fleet_smoke
fault_smoke
traced_smoke
profile_smoke
validate_artifacts

echo "ci.sh: all green"
