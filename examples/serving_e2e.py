"""End-to-end serving driver (the paper's kind of system): a reduced
llama-family model serves a request stream twice on the paged engine — the
paper's SLO-ODBS batches, one batch after another, then the whole stream
continuously in arrival order — and reports latency / throughput /
token-identity between the two.

Run: PYTHONPATH=src python examples/serving_e2e.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import (LengthPredictor, ResourceProfiler, SchedulerConfig,
                        slo_odbs)
from repro.core.profiler import PredictorConfig
from repro.data.workload import WorkloadConfig, gen_requests, train_pairs
from repro.models import api
from repro.serving import PagedEngine, PagedEngineConfig

cfg = get_config("smollm-135m").reduced()
params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
engine = PagedEngine(cfg, params, PagedEngineConfig(
    max_batch=4, block_size=8, n_blocks=64, max_seq_len=64,
    max_new_tokens=16))

reqs = gen_requests(WorkloadConfig(n_requests=12, seed=3, vocab=cfg.vocab_size))
for r in reqs:
    r.tokens = [t % cfg.vocab_size for t in r.tokens[:16]]
    r.input_len = len(r.tokens)
    r.true_output_len = r.true_output_len % 12 + 2

pred = LengthPredictor(PredictorConfig(vocab=cfg.vocab_size), seed=0)
toks, lens = train_pairs(WorkloadConfig(vocab=cfg.vocab_size), 256, seed=1)
pred.fit(toks, lens, epochs=8)
prof = ResourceProfiler(pred, cfg)
prof.profile(reqs)

# --- paper mode: SLO-ODBS batches, served one after another ------------------
t0 = time.perf_counter()
batched_out = {}
total_steps = 0
for b in slo_odbs(reqs, SchedulerConfig(max_batch=4)):
    res = engine.run_continuous(list(b.requests))
    batched_out.update(res.outputs)
    total_steps += res.steps
t_batched = time.perf_counter() - t0
tok_batched = sum(len(v) for v in batched_out.values())
print(f"[SLO-ODBS batches] {tok_batched} tokens in {t_batched:.2f}s "
      f"({total_steps} decode iterations)")

# --- beyond-paper: continuous batching ---------------------------------------
t0 = time.perf_counter()
res_c = engine.run_continuous(sorted(reqs, key=lambda r: r.arrival))
t_cont = time.perf_counter() - t0
tok_cont = sum(len(v) for v in res_c.outputs.values())
print(f"[continuous]       {tok_cont} tokens in {t_cont:.2f}s "
      f"({res_c.steps} decode iterations)")

same = all(batched_out[r.rid] == res_c.outputs[r.rid] for r in reqs)
print(f"token-identical outputs across modes: {same}")
print(f"decode-iteration reduction from continuous batching: "
      f"{total_steps} -> {res_c.steps}")
