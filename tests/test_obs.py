"""Observability layer: log-bucketed histogram quantile error bound and
exact merge, span vocabulary / nesting invariants, Chrome-trace export
schema, Monitor latency-quantile publication, and the tracing-is-free
guarantee (token-identical engine and simulator outputs with tracing on)."""
import copy

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.types import Request
from repro.obs import (EVENT_NAMES, INSTANT_NAMES, NULL_TRACER, SPAN_NAMES,
                       Histogram, LatencyBreakdown, RotatingHistogram,
                       Tracer, check_invariants, export_trace,
                       metrics_payload, slot_row, to_chrome,
                       validate_metrics, validate_trace)
from repro.obs.trace import (DEVICE_SCOPES, HOST_PHASES, PHASE_PREFIX,
                             device_scope, phase)

BS = 8


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp
    from repro.models import api
    cfg = get_config("smollm-135m").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def _req(rid, tokens, *, out=4, slo=30.0, arrival=0.0):
    return Request(rid=rid, tokens=list(tokens), input_len=len(tokens),
                   slo=slo, arrival=arrival, true_output_len=out)


# ------------------------------------------------------------ histograms

@pytest.mark.parametrize("dist,seed", [("lognormal", 0), ("exponential", 1),
                                       ("uniform", 2)])
def test_histogram_quantile_error_bound(dist, seed):
    """Every reported quantile is within sqrt(growth)-1 relative error of
    the true order statistic (same rank convention), on heavy- and
    light-tailed inputs alike."""
    rng = np.random.default_rng(seed)
    xs = {"lognormal": rng.lognormal(-3.0, 1.5, 4000),
          "exponential": rng.exponential(0.05, 4000),
          "uniform": rng.uniform(1e-4, 2.0, 4000)}[dist]
    h = Histogram()
    h.record_many(xs)
    assert h.n == len(xs)
    srt = np.sort(xs)
    for q in (0.01, 0.25, 0.5, 0.9, 0.95, 0.99):
        true = srt[int(q * (h.n - 1))]
        got = h.quantile(q)
        assert abs(got - true) <= h.rel_error_bound * true + 1e-12, (q, dist)
    # extremes are exact, mean is exact
    assert h.quantile(0.0) == srt[0] and h.quantile(1.0) == srt[-1]
    assert h.mean == pytest.approx(xs.mean())


def test_histogram_merge_exact_and_summary():
    """Bucket-wise merge equals recording the union; summary publishes the
    fixed quantile block; mismatched bucketing refuses to merge."""
    rng = np.random.default_rng(3)
    a, b = rng.exponential(0.1, 500), rng.exponential(1.0, 700)
    ha, hb, hu = Histogram(), Histogram(), Histogram()
    ha.record_many(a)
    hb.record_many(b)
    hu.record_many(np.concatenate([a, b]))
    ha.merge(hb)
    assert ha.counts == hu.counts
    assert ha.n == hu.n and ha.total == pytest.approx(hu.total)
    assert ha.quantile(0.95) == hu.quantile(0.95)
    s = ha.summary()
    assert set(s) == {"count", "mean", "p50", "p95", "p99", "max"}
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    with pytest.raises(ValueError):
        ha.merge(Histogram(growth=2.0))
    assert Histogram().summary() == {"count": 0}
    # sub-v_min values collapse into bucket 0, clamped to the observed range
    tiny = Histogram()
    tiny.record_many([0.0, 1e-9, 1e-8])
    assert tiny.counts == {0: 3}
    assert tiny.quantile(0.5) <= tiny.v_min


def test_histogram_edge_cases():
    """Zero and negative samples clamp into bucket 0 (a skewed clock must
    never throw), an empty histogram reports nan quantiles/mean and a bare
    {"count": 0} summary, and merging with an empty histogram is the
    identity in both directions."""
    import math
    h = Histogram()
    h.record(0.0)
    h.record(-3.5)
    assert h.n == 2 and h.counts == {0: 2}
    assert h.min_v == 0.0 and h.max_v == 0.0
    assert h.quantile(0.5) == 0.0          # clamped to the observed range
    assert h.total == 0.0 and h.mean == 0.0

    empty = Histogram()
    assert empty.summary() == {"count": 0}
    assert math.isnan(empty.quantile(0.5)) and math.isnan(empty.mean)

    filled = Histogram()
    filled.record_many([0.01, 0.1, 1.0])
    before = (dict(filled.counts), filled.n, filled.total,
              filled.min_v, filled.max_v)
    filled.merge(Histogram())              # empty into filled: no-op
    assert (dict(filled.counts), filled.n, filled.total,
            filled.min_v, filled.max_v) == before
    receiver = Histogram()
    receiver.merge(filled)                 # filled into empty: copies
    assert receiver.counts == filled.counts and receiver.n == filled.n
    assert receiver.quantile(0.95) == filled.quantile(0.95)
    assert receiver.summary() == filled.summary()


def test_histogram_quantile_monotone_in_q():
    """q1 <= q2 implies quantile(q1) <= quantile(q2), including the exact
    0.0/1.0 extremes and repeated q values."""
    rng = np.random.default_rng(7)
    h = Histogram()
    h.record_many(rng.lognormal(-2.0, 1.0, 2000))
    grid = [0.0, 0.01, 0.1, 0.25, 0.5, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0]
    vals = [h.quantile(q) for q in grid]
    assert vals == sorted(vals)


def test_rotating_histogram_window_retention_and_quantiles():
    """The two-window rotation retains exactly the last (W + n mod W)
    samples once a window has completed; merged quantiles stay within the
    bucket error bound of the retained suffix's order statistics."""
    W = 64
    rng = np.random.default_rng(11)
    xs = rng.lognormal(-3.0, 1.2, 300)
    rh = RotatingHistogram(window=W)
    for v in xs:
        rh.record(v)
    # 300 = 4*64 + 44: previous holds samples 193..256, active the last 44
    retained = xs[4 * W - W:]
    assert rh.n == len(retained) == W + 300 % W
    m = rh.merged()
    srt = np.sort(retained)
    for q in (0.1, 0.5, 0.9, 0.95):
        true = srt[int(q * (m.n - 1))]
        assert abs(m.quantile(q) - true) \
            <= m.rel_error_bound * true + 1e-12, q
    assert rh.quantile(0.5) == m.quantile(0.5)     # facade reads merged
    # a burst is fully forgotten after <= 2W subsequent samples
    spike = RotatingHistogram(window=W)
    for _ in range(W):
        spike.record(100.0)
    for _ in range(2 * W):
        spike.record(0.01)
    assert spike.max_v == pytest.approx(0.01)
    assert spike.quantile(1.0) == pytest.approx(0.01)


def test_rotating_histogram_merge_exact_across_rotation():
    """merged() is bucket-exact: identical counts to a fresh Histogram
    over the retained suffix, so nothing is approximated at the seam."""
    W = 32
    rng = np.random.default_rng(13)
    xs = rng.exponential(0.2, 3 * W + 5)
    rh = RotatingHistogram(window=W)
    for v in xs:
        rh.record(v)
    fresh = Histogram()
    fresh.record_many(xs[2 * W:])                  # the retained suffix
    m = rh.merged()
    assert m.counts == fresh.counts
    assert m.n == fresh.n and m.total == pytest.approx(fresh.total)
    assert m.summary() == fresh.summary()
    # degenerate window=1: previous is always just the last full sample
    tiny = RotatingHistogram(window=1)
    tiny.record(5.0)
    tiny.record(7.0)
    assert tiny.n >= 1 and tiny.quantile(1.0) == pytest.approx(7.0)
    with pytest.raises(ValueError):
        RotatingHistogram(window=0)


# ------------------------------------------------------- span invariants

def test_span_vocabulary_and_nesting_invariants():
    """A well-formed lifecycle passes; unknown names, negative spans, and
    partially-overlapping same-lane spans are flagged.  ``queued`` spans are
    exempt from lane nesting (waits legitimately overlap)."""
    tr = Tracer()
    tr.span("queued", 0.0, 1.0, row=1)
    tr.span("queued", 0.5, 2.0, row=1)          # overlapping waits: fine
    tr.instant("admitted", 1.0, row=slot_row(0))
    tr.span("prefill_chunk", 1.0, 1.5, row=slot_row(0))
    tr.span("decode", 1.5, 1.6, row=slot_row(0))
    tr.instant("finish", 1.6, row=slot_row(0))
    assert check_invariants(tr.events) == []

    bad = Tracer()
    bad.span("warp_drive", 0.0, 1.0)
    assert any("warp_drive" in e for e in check_invariants(bad.events))

    lap = Tracer()
    lap.span("decode", 0.0, 1.0, row=slot_row(0))
    lap.span("verify", 0.5, 1.5, row=slot_row(0))   # partial overlap, 1 lane
    assert check_invariants(lap.events) != []
    # same interval pair on DIFFERENT rows is fine
    ok = Tracer()
    ok.span("decode", 0.0, 1.0, row=slot_row(0))
    ok.span("verify", 0.5, 1.5, row=slot_row(1))
    assert check_invariants(ok.events) == []

    assert SPAN_NAMES & INSTANT_NAMES == set()
    assert EVENT_NAMES == SPAN_NAMES | INSTANT_NAMES


def test_disabled_tracer_records_nothing():
    NULL_TRACER.span("decode", 0.0, 1.0)
    NULL_TRACER.instant("finish", 1.0)
    assert NULL_TRACER.events == [] and not NULL_TRACER


# ----------------------------------------------------------- trace export

def test_chrome_export_schema(tmp_path):
    """Export is valid Chrome-trace JSON: µs timestamps, one async b/e pair
    per queued interval, track/row metadata, vocabulary enforced."""
    tr = Tracer()
    tr.span("queued", 0.25, 1.0, track=2, row=1, args={"rid": 7})
    tr.instant("admitted", 1.0, track=2, row=slot_row(1))
    tr.span("decode", 1.0, 1.5, track=2, row=slot_row(1))
    obj = export_trace(tr, tmp_path / "t.json",
                       track_names={2: "replica two"})
    assert validate_trace(obj) == []
    ev = obj["traceEvents"]
    named = [e for e in ev if e["ph"] != "M"]
    assert {e["ph"] for e in named} == {"X", "i", "b", "e"}
    be = [e for e in named if e["ph"] in "be"]
    assert len(be) == 2 and all(e["name"] == "queued" for e in be)
    assert be[0]["id"] == be[1]["id"]
    x = next(e for e in named if e["ph"] == "X")
    assert x["ts"] == pytest.approx(1.0e6) and x["dur"] == pytest.approx(5e5)
    meta = [e for e in ev if e["ph"] == "M"]
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "replica two" for e in meta)
    assert (tmp_path / "t.json").exists()

    # corrupted exports are rejected
    obj["traceEvents"].append({"name": "decode", "ph": "X", "ts": -1,
                               "dur": -2, "pid": 0, "tid": 0})
    assert validate_trace(obj) != []
    assert validate_trace({"traceEvents": [{"name": "nope", "ph": "X",
                                            "ts": 0, "dur": 0, "pid": 0,
                                            "tid": 0}]}) != []
    assert validate_trace({}) != []


def test_metrics_payload_schema():
    p = metrics_payload("x", latency_s=1.0, p99_latency_s=2.0,
                        monitor={"observed": 1}, extra={"k": 3})
    assert validate_metrics(p) == []
    assert p["schema"] >= 2 and p["throughput"] is None
    assert validate_metrics({"bench": "x", "schema": 1}) != []


# -------------------------------------------------------- monitor quantiles

def test_monitor_publishes_latency_quantiles():
    """Finished requests (with serving-path breakdowns) and interleave
    samples surface as p50/p95/p99 blocks in Monitor.metrics()."""
    from repro.core import LengthPredictor, Monitor, ResourceProfiler
    from repro.core.profiler import PredictorConfig
    cfg = get_config("smollm-135m").reduced()
    pred = LengthPredictor(PredictorConfig(vocab=cfg.vocab_size), seed=0)
    mon = Monitor(ResourceProfiler(pred, cfg))
    for i in range(8):
        r = _req(i, [1 + i] * 6, out=3)
        r.start_time = 0.1 * i
        r.finish_time = 0.1 * i + 1.0 + 0.05 * i
        r.first_token_time = 0.1 * i + 0.4
        r.breakdown = LatencyBreakdown(queue_wait_s=0.1 * i, ttft_s=0.4,
                                       e2e_s=r.finish_time - r.arrival)
        mon.observe(r)
    mon.observe_interleave(chunks=4, stalls=[0.01, 0.02],
                           itl=[0.001, 0.002, 0.004])
    m = mon.metrics()
    for key in ("queue_wait", "ttft", "itl", "e2e", "prefill_stall"):
        assert set(m[key]) == {"count", "mean", "p50", "p95", "p99", "max"}, key
    assert m["ttft"]["count"] == 8 and m["itl"]["count"] == 3
    assert m["e2e"]["p50"] <= m["e2e"]["p99"]


def test_monitor_replica_gauges_peak_and_mean():
    """observe_replicas keeps the peak and running mean across snapshots —
    the final (often drained) snapshot no longer overwrites the story."""
    from repro.core import LengthPredictor, Monitor, ResourceProfiler
    from repro.core.profiler import PredictorConfig
    cfg = get_config("smollm-135m").reduced()
    pred = LengthPredictor(PredictorConfig(vocab=cfg.vocab_size), seed=0)
    mon = Monitor(ResourceProfiler(pred, cfg))
    mon.observe_replicas([4, 6], [0.9, 0.7])
    mon.observe_replicas([0, 0], [0.0, 0.0])      # drained final snapshot
    m = mon.metrics()
    assert m["cluster_queue_peak"] == 6
    assert m["cluster_util_peak"] == pytest.approx(0.9)
    assert m["cluster_queue_mean"] == pytest.approx(2.5)
    assert m["cluster_util_mean"] == pytest.approx(0.4)
    assert m["cluster_queue_depths"] == [0, 0]    # latest still visible


# ----------------------------------------------------- tracing is free

def test_simulator_tracing_identity_and_invariants():
    """simulate_continuous with a live tracer: identical outputs/metrics to
    the untraced run, events pass the structural invariants, and both span
    schemas stay inside the shared vocabulary."""
    from repro.serving import simulate_continuous
    cfg = get_config("chatglm2-6b")

    def mk():
        rng = np.random.default_rng(7)
        reqs = [_req(i, [1] * int(rng.integers(32, 256)),
                     out=int(rng.integers(4, 24)), arrival=0.05 * i)
                for i in range(12)]
        for r in reqs:
            r.input_len = len(r.tokens)
            r.predicted_output_len = r.true_output_len
        return reqs

    tr = Tracer()
    kw = dict(max_batch=4, max_new=24, block_size=16, n_blocks=64,
              chunk_tokens=64, preempt=True)
    traced = simulate_continuous(mk(), cfg, tracer=tr, **kw)
    plain = simulate_continuous(mk(), cfg, **kw)
    assert [(r.rid, r.finish_time) for r in traced.requests] \
        == [(r.rid, r.finish_time) for r in plain.requests]
    assert traced.makespan == plain.makespan
    assert traced.throughput == pytest.approx(plain.throughput)
    assert check_invariants(tr.events) == []
    assert {e.name for e in tr.events} <= EVENT_NAMES
    assert any(e.name == "prefill_chunk" for e in tr.events)
    assert any(e.name == "finish" for e in tr.events)
    assert validate_trace(to_chrome(tr)) == []


def test_engine_tracing_identity(model):
    """PagedEngine with tracing on emits a valid lifecycle trace, the
    generated tokens are bitwise identical to the untraced run, and every
    finished request carries its per-phase latency breakdown."""
    from repro.serving import PagedEngine, PagedEngineConfig
    cfg, params = model
    reqs = [_req(i, [2 + i] * 10, out=4 + i % 3, arrival=0.0)
            for i in range(4)]
    pcfg = PagedEngineConfig(max_batch=2, block_size=BS, n_blocks=32,
                             max_seq_len=48, max_new_tokens=8,
                             chunk_tokens=BS)
    tr = Tracer()
    served = [copy.copy(r) for r in reqs]
    traced = PagedEngine(cfg, params, pcfg, tracer=tr).run_continuous(served)
    plain = PagedEngine(cfg, params, pcfg).run_continuous(
        [copy.copy(r) for r in reqs])
    assert traced.outputs == plain.outputs
    assert check_invariants(tr.events) == []
    names = {e.name for e in tr.events}
    assert {"queued", "admitted", "prefill_chunk", "decode",
            "finish"} <= names
    for r in served:
        assert r.breakdown is not None
        bd = r.breakdown
        assert bd.e2e_s >= bd.ttft_s >= 0
        assert bd.prefill_s > 0


# ------------------------------------------------ host phases in the profiler

def _profiled_wave(engine, reqs, log_dir):
    """Serve ``reqs`` once to compile, then again under ``jax.profiler``;
    returns (result, [(name, start_ns, end_ns)] of the ``uellm/`` spans)."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData
    engine.run_continuous([copy.copy(r) for r in reqs])
    jax.profiler.start_trace(str(log_dir))
    try:
        res = engine.run_continuous([copy.copy(r) for r in reqs])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(ev.name[len(PHASE_PREFIX):], ev.start_ns, ev.end_ns)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith(PHASE_PREFIX)]
    return res, spans


@pytest.mark.parametrize("mode", ["unchunked", "chunked", "speculative"])
def test_engine_host_phases_in_profiler_trace(model, tmp_path, mode):
    """Every decode or verify step leaves exactly one ``sync`` in the
    profiler's trace; the step's scopes nest inside an ``iteration`` and
    every prefill call leaves one ``prefill``; no name falls outside the
    vocabulary."""
    from repro.serving import PagedEngine, PagedEngineConfig
    cfg, params = model
    reqs = [_req(i, [3 + i] * (9 + 5 * i), out=3 + i % 3) for i in range(4)]
    pcfg = PagedEngineConfig(
        max_batch=2, block_size=BS, n_blocks=32, max_seq_len=48,
        max_new_tokens=8, chunk_tokens=BS if mode == "chunked" else 0,
        spec_tokens=2 if mode == "speculative" else 0)
    res, spans = _profiled_wave(PagedEngine(cfg, params, pcfg), reqs,
                                tmp_path)
    names = [n for n, _, _ in spans]
    assert set(names) <= HOST_PHASES
    assert names.count("sync") == res.steps > 0
    assert names.count("prefill") == res.prefill_chunks >= len(reqs)
    assert names.count("drain") == 1
    assert ("draft" in names) == (mode == "speculative")
    iters = [(s, e) for n, s, e in spans if n == "iteration"]
    for n, s, e in spans:
        if n in ("sync", "view", "dispatch", "sample", "emit", "grow"):
            assert any(a <= s and e <= b for a, b in iters), n


def test_phase_names_are_checked():
    """A scope outside the vocabulary is refused where it is opened."""
    with phase("sync"):
        pass
    with pytest.raises(AssertionError):
        phase("decode_step")
    with pytest.raises(AssertionError):
        device_scope("sync")
    assert not HOST_PHASES & DEVICE_SCOPES


@pytest.mark.parametrize("arch", ["chatglm2-6b", "qwen2-1.5b"])
def test_rope_scope_names_the_rotation_in_the_decode_step(arch):
    """In the compiled paged decode step every cos and sin, and for
    ChatGLM2's pairs the negation, lane shifts and parity select, carry
    ``attention/rope`` in their op metadata, the scope path the profiler
    reports as ``tf_op``."""
    import functools
    import re

    import jax
    import jax.numpy as jnp
    from repro.models import api
    cfg = get_config(arch).reduced()
    shape = lambda f: jax.eval_shape(f)   # noqa: E731
    params = shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0),
                                           jnp.float32))
    pools = shape(lambda: api.init_paged_pools(cfg, 8, 4, jnp.float32))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    hlo = jax.jit(functools.partial(api.paged_decode_step, cfg)).lower(
        params, i32(2, 1), pools, i32(2, 4), i32(2)).compile().as_text()
    scoped: dict = {}
    for line in hlo.splitlines():
        m = re.search(r"= \S+ (\w[\w-]*)\(.*op_name=\"([^\"]*)\"", line)
        if m:
            scoped.setdefault(m.group(1), set()).add(
                "/attention/rope/" in m.group(2))
    assert scoped["cosine"] == scoped["sine"] == {True}
    if cfg.rope_interleaved:
        assert True in scoped["negate"] and True in scoped["select"]
        assert True in scoped["concatenate"]
