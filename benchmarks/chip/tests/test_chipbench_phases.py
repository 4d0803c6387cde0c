"""The engine's own phases in a profiler trace: a trace without them
reduces exactly as before, an idle stretch is split over the phases it
spans, the two step readers give known answers, and a traced CPU run of a
tiny cell reports them."""
import json
import pathlib
import shutil
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import catalog  # noqa: E402
import phases  # noqa: E402
import trace_reduce as tr  # noqa: E402
from test_chipbench_trace import XSPACE, recorded  # noqa: E402

DATA = HERE / "tests" / "data"
MS = 1e6

# one decode step's idle stretch [10, 40] ns, spanning three phases
STEP = [tr.Span("wave", 0, 60), tr.Span("iteration", 5, 45),
        tr.Span("emit", 8, 20), tr.Span("grow", 20, 30),
        tr.Span("view", 30, 40), tr.Span("dispatch", 40, 45)]
STEP_OPS = [tr.Op("a", 0, 10), tr.Op("b", 40, 10)]


def with_program_spans(text: str) -> str:
    """The small XSpace with two ``uellm/`` spans on the python line."""
    text = text.replace(
        "events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }",
        "events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }\n"
        "    events { metadata_id: 4 offset_ps: 5000000 "
        "duration_ps: 9000000 }\n"
        "    events { metadata_id: 5 offset_ps: 6500000 "
        "duration_ps: 5000000 }")
    return text.replace(
        'event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } }',
        'event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } }'
        '\n  event_metadata { key: 4 value { id: 4 name: "uellm/iteration" } }'
        '\n  event_metadata { key: 5 value { id: 5 name: "uellm/sync" } }')


def small(text: str):
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(text)
    devices, host = tr.from_profile(pd)
    return devices["/device:TPU:0"], host, phases.spans(pd)


def test_no_program_spans_reduce_as_before():
    ops, host, program = small(XSPACE)
    assert program == []
    assert phases.breakdown(ops, host, program, 1000, 21000) == \
        tr.breakdown(ops, host, 1000, 21000)
    ops, host, t0, t1 = recorded()
    assert phases.breakdown(ops, host, [], t0, t1) == \
        tr.breakdown(ops, host, t0, t1)


def test_program_spans_are_read_beside_the_harness_spans():
    ops, host, program = small(with_program_spans(XSPACE))
    assert sorted(s.name for s in host) == ["decode_dispatch", "wave"]
    assert [(s.name, s.start_ns, s.end_ns) for s in program] == \
        [("iteration", 6000, 15000), ("sync", 7500, 12500)]
    b = phases.breakdown(ops, host, program, 1000, 21000)
    # gap [8000, 13000]: its middle lies in sync, which covers [8000, 12500]
    assert b["idle_gaps"][:2] == [["engine_loop", pytest.approx(6e-6)],
                                  ["sync", pytest.approx(5e-6)]]
    assert dict(b["idle_by_span"]) == {
        "sync": pytest.approx(4.5e-6), "decode_dispatch": pytest.approx(.5e-6),
        "engine_loop": pytest.approx(6e-6)}


def test_idle_by_span_splits_one_gap_over_three_phases():
    by_span = phases.idle_by_span(STEP_OPS, STEP, 0, 60)
    assert dict(by_span) == {"emit": pytest.approx(10e-9),
                             "grow": pytest.approx(10e-9),
                             "view": pytest.approx(10e-9),
                             "engine_loop": pytest.approx(10e-9)}
    # named by its middle, the whole stretch goes to grow
    assert tr.idle_gaps(STEP_OPS, STEP, 0, 60)[0] == ("grow", 10, 40)
    assert phases.idle_by_span(STEP_OPS, [], 0, 60) == \
        [["unattributed", pytest.approx(40e-9)]]


def test_timeline_names_each_piece_by_its_innermost_span():
    assert phases.timeline(STEP) == [
        (0, 5, "wave"), (5, 8, "iteration"), (8, 20, "emit"),
        (20, 30, "grow"), (30, 40, "view"), (40, 45, "dispatch"),
        (45, 60, "wave")]
    assert dict(phases.time_by_span(STEP, 10, 60)) == {
        "emit": pytest.approx(10e-9), "grow": pytest.approx(10e-9),
        "view": pytest.approx(10e-9), "dispatch": pytest.approx(5e-9),
        "wave": pytest.approx(15e-9)}


# three iterations: two decode steps, one without a step (ignored)
STEPS = [tr.Span("iteration", 0, 10 * MS), tr.Span("prefill", 1 * MS, 2 * MS),
         tr.Span("sync", 6 * MS, 9 * MS), tr.Span("emit", 9 * MS, 10 * MS),
         tr.Span("iteration", 10 * MS, 14 * MS),
         tr.Span("sync", 11 * MS, 13 * MS),
         tr.Span("iteration", 14 * MS, 15 * MS),
         tr.Span("finish", 14 * MS, 15 * MS)]


def test_step_readers_give_known_answers():
    # (10 - 3 - 1) and (4 - 2) ms of host time; syncs of 3 and 2 ms
    assert phases.host_step_ms(STEPS) == pytest.approx(4.0)
    assert phases.sync_wait_ms(STEPS) == pytest.approx(2.5)
    assert phases.host_step_ms(STEPS, 10 * MS, 15 * MS) == pytest.approx(2.0)
    no_step = [s for s in STEPS if s.name != "sync"]
    assert phases.host_step_ms(no_step) is None
    assert phases.sync_wait_ms(no_step) is None
    assert phases.host_step_ms([]) is None


@pytest.mark.parametrize("name,value", [("host_step_ms", 4.0),
                                        ("sync_wait_ms", 2.5)])
def test_metric_readers_need_the_program_spans(name, value):
    from types import SimpleNamespace
    reader = catalog.metric(name)
    ctx = SimpleNamespace(t0=0, t1=20 * MS)
    assert reader.read(ctx) is None
    ctx.spans = []
    assert reader.read(ctx) is None
    ctx.spans = STEPS
    assert reader.read(ctx) == pytest.approx(value)


def test_device_scopes_by_the_innermost_scope():
    path = "jit(_decode)/jit(main)/while/body/"
    stats = {"%s.1 = x": {"tf_op": path + "attention/kv_write/scatter",
                          "hlo_category": "data movement"},
             "%d.2 = x": {"tf_op": path + "attention/dot_general"},
             "%c.3 = x": {"tf_op": path + "copy"},
             "%m.4 = x": {"tf_op": path + "mlp/dot_general"}}
    assert phases.scope_stat(stats) == "tf_op"
    ops = [tr.Op("%s.1 = x", 0, 2e9), tr.Op("%d.2 = x", 2e9, 1e9),
           tr.Op("%c.3 = x", 3e9, 4e9), tr.Op("%m.4 = x", 7e9, 1e9),
           tr.Op("%other.5 = x", 8e9, 1e9)]
    got = phases.device_scopes(ops, stats, 0, 10e9)
    assert dict(got["device_scopes"]) == {
        "kv_write": 2.0, "attention": 1.0, "unscoped": 5.0, "mlp": 1.0}
    assert got["device_scope_ops"][:2] == [["unscoped c", 4.0],
                                           ["kv_write s", 2.0]]
    assert phases.device_scopes(ops, {}, 0, 10e9) is None
    assert phases.scope_of("jit(f)/while/body/head_dim") is None


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """A checkout of the benchmark whose one cell serves the tiny config."""
    here = tmp_path_factory.mktemp("bench")
    for d in ("configs", "traffic"):
        (here / d).mkdir()
    shutil.copytree(HERE / "metrics", here / "metrics")
    shutil.copy(DATA / "tiny.json", here / "configs" / "tiny.json")
    shutil.copy(DATA / "tiny-chat.json", here / "traffic" / "chat.json")
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                       "traffic": "chat", "chips": 1, "why": "test"}]
    for m in b["per_layer"]:
        m["workloads"] = ["tiny.chat"]
    (here / "BENCHMARK.json").write_text(json.dumps(b))
    return here


def test_phase_trace_reads_the_program_phases(bench):
    import phase_trace
    import run
    args = run.parse(["--workload", "tiny.chat", "--seed", str(2**31 + 5),
                      "--seconds", "0", "--trace", "1"])
    res = phase_trace.traced(args, require_tpu=False, root=bench, here=bench)
    assert res["correct"], res["compared"]
    m = res["metrics"]
    assert m["host_step_ms"]["value"] > 0 and m["sync_wait_ms"]["value"] > 0
    assert m["host_step_ms"]["value"] + m["sync_wait_ms"]["value"] == \
        pytest.approx(m["decode_step_ms"]["value"], rel=0.5)
    b, dev = res["breakdown"], res["device"]
    idle = sum(s for _, s in b["idle_by_span"])
    assert idle == pytest.approx(dev["window_s"] - dev["busy_s"], rel=1e-6)
    assert {"sync", "view", "dispatch", "emit"} <= \
        dict(b["idle_by_span"]).keys() & dict(b["host_phases"]).keys()
    # the harness is left as it was
    assert run.per_layer.__module__ == "run"
    assert tr.load.__module__ == "trace_reduce"
