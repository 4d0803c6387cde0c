"""Quickstart: the UELLM pipeline in ~60 lines.

1. generate a serving workload,
2. train the resource profiler's length predictor,
3. schedule with SLO-ODBS,
4. plan a deployment with HELR,
5. execute the batches on a real (reduced) JAX model, paged engine.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import (HELRConfig, LengthPredictor, Monitor,
                        ResourceProfiler, SchedulerConfig, helr, slo_odbs)
from repro.core.profiler import PredictorConfig
from repro.core.types import DeviceNode
from repro.data.workload import WorkloadConfig, gen_requests, train_pairs
from repro.models import api
from repro.serving import PagedEngine, PagedEngineConfig

# --- 1. workload -----------------------------------------------------------
cfg = get_config("smollm-135m").reduced()
reqs = gen_requests(WorkloadConfig(n_requests=8, seed=0, vocab=cfg.vocab_size))
for r in reqs:                       # trim to demo scale
    r.tokens = [t % cfg.vocab_size for t in r.tokens[:12]]
    r.input_len = len(r.tokens)
    r.true_output_len = r.true_output_len % 8 + 1

# --- 2. resource profiler ---------------------------------------------------
pred = LengthPredictor(PredictorConfig(vocab=cfg.vocab_size), seed=0)
toks, lens = train_pairs(WorkloadConfig(vocab=cfg.vocab_size), 256, seed=1)
acc = pred.fit(toks, lens, epochs=8)
print(f"length predictor trained: bucket accuracy {acc:.2%}")
profiler = ResourceProfiler(pred, cfg)
profiler.profile(reqs)

# --- 3. SLO-ODBS batching ---------------------------------------------------
batches = slo_odbs(reqs, SchedulerConfig(max_batch=4))
print(f"SLO-ODBS grouped {len(reqs)} requests into {len(batches)} batches: "
      f"{[len(b) for b in batches]}")

# --- 4. HELR deployment -----------------------------------------------------
nodes = [DeviceNode(0, 24e9, 35e12, "GPU#0"), DeviceNode(1, 24e9, 30e12, "GPU#1")]
lat = [[0.0, 5e-5], [5e-5, 0.0]]
dmap = helr(cfg.param_count() * 4.0, cfg.n_layers, nodes, lat, HELRConfig())
print(f"HELR device map: path={dmap.path} layers={dmap.layers}")

# --- 5. execute on the real model ------------------------------------------
params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
engine = PagedEngine(cfg, params, PagedEngineConfig(
    max_batch=4, block_size=8, n_blocks=32, max_seq_len=32, max_new_tokens=8))
monitor = Monitor(profiler, update_on_miss=False)
for b in batches:
    res = engine.run_continuous(list(b.requests))
    for r in b.requests:
        monitor.observe(r)
    print(f"batch of {len(b)}: prefill {res.prefill_s*1e3:.1f} ms, "
          f"{res.steps} decode steps, outputs "
          f"{[len(v) for v in res.outputs.values()]}")
print(f"monitor: {monitor.metrics()}")
