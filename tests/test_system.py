"""End-to-end behaviour of the UELLM system: the full pipeline
(workload -> profiler -> SLO-ODBS -> paged JAX engine) produces every answer,
and a short training run on the reduced demo model actually learns."""
import copy

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import (LengthPredictor, Monitor, ResourceProfiler,
                        SchedulerConfig, slo_odbs)
from repro.core.profiler import PredictorConfig
from repro.data.workload import WorkloadConfig, gen_requests, train_pairs
from repro.models import api
from repro.serving import PagedEngine, PagedEngineConfig
from repro.training import OptConfig, TrainConfig, init_training, make_train_step


def test_uellm_pipeline_end_to_end():
    """profile -> schedule -> execute on the real reduced model; every
    request gets exactly its answer; the monitor sees every completion."""
    cfg = get_config("smollm-135m").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    engine = PagedEngine(cfg, params,
                         PagedEngineConfig(max_batch=4, block_size=8,
                                           n_blocks=32, max_seq_len=48,
                                           max_new_tokens=8))
    pred = LengthPredictor(PredictorConfig(vocab=cfg.vocab_size, max_len=8,
                                           n_buckets=4), seed=0)
    prof = ResourceProfiler(pred, cfg)
    mon = Monitor(prof, update_on_miss=False)

    reqs = gen_requests(WorkloadConfig(n_requests=10, seed=2,
                                       vocab=cfg.vocab_size))
    for r in reqs:
        r.tokens = [t % cfg.vocab_size for t in r.tokens[:12]]
        r.input_len = len(r.tokens)
        r.true_output_len = r.true_output_len % 8 + 1
    prof.profile(reqs)
    batches = slo_odbs(reqs, SchedulerConfig(max_batch=4))
    assert sum(len(b) for b in batches) == len(reqs)

    outputs = {}
    for b in batches:
        res = engine.run_continuous(list(b.requests))
        outputs.update(res.outputs)
        for r in b.requests:
            mon.observe(r)
    for r in reqs:
        assert len(outputs[r.rid]) == r.true_output_len
    assert mon.stats.observed == len(reqs)


def test_training_loss_decreases():
    """A few dozen steps on a tiny corpus: loss must drop substantially —
    the end-to-end train-driver invariant."""
    cfg = get_config("smollm-135m").reduced(n_layers=2)
    tcfg = TrainConfig(opt=OptConfig(kind="adamw", lr=3e-3))
    key = jax.random.PRNGKey(0)
    params, opt_state = init_training(cfg, key, tcfg, jnp.float32)
    step_fn = jax.jit(make_train_step(cfg, None, tcfg))

    rng = np.random.default_rng(0)
    base = rng.integers(2, cfg.vocab_size, size=32)
    losses = []
    for step in range(40):
        toks = jnp.asarray(np.stack([np.roll(base, i % 4) for i in range(4)]))
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1),
                 "mask": jnp.ones(toks.shape, jnp.float32)}
        params, opt_state, metrics = step_fn(params, opt_state, batch,
                                             jnp.asarray(step, jnp.int32))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
