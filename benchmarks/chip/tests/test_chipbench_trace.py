"""Reduction of a profiler trace: device busy union, kernel time by name,
idle gaps named by what the host was doing."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import trace_reduce as tr  # noqa: E402

SLICE = HERE / "tests" / "data" / "trace_slice.json"

XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 12000000 duration_ps: 2000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.3 = f32[8] fusion()" } }
  event_metadata { key: 2 value { id: 2
    name: "%_kernel.1 = f32[8] custom-call()" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 6000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/wave" } }
  event_metadata { key: 2 value { id: 2 name: "bench/decode_dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } }
}
"""


@pytest.fixture(scope="module")
def small():
    from jax.profiler import ProfileData
    return tr.from_profile(ProfileData.from_text_proto(XSPACE))


def test_reads_device_ops_and_bench_spans(small):
    devices, host = small
    assert list(devices) == ["/device:TPU:0"]
    ops = devices["/device:TPU:0"]
    assert [tr.op_label(o) for o in ops] == ["fusion", "_kernel", "_kernel"]
    assert sorted(s.name for s in host) == ["decode_dispatch", "wave"]


def test_busy_is_the_union_of_overlapping_ops(small):
    ops = small[0]["/device:TPU:0"]
    # [1000, 8000] and [13000, 15000] ns
    assert tr.busy_s(ops, 1000, 21000) == pytest.approx(9000e-9)
    assert tr.busy_s(ops, 2000, 14000) == pytest.approx(7000e-9)


def test_kernel_time_by_name_or_metadata(small):
    ops = small[0]["/device:TPU:0"]
    assert tr.op_time_s(ops, r"^%_kernel[.\d]* = ", 0, 1e9) == \
        pytest.approx(6e-6)
    assert tr.op_time_s(ops, r"^%fusion", 0, 1e9) == pytest.approx(5e-6)


def test_idle_gaps_take_the_innermost_host_span(small):
    ops, host = small[0]["/device:TPU:0"], small[1]
    gaps = tr.idle_gaps(ops, host, 1000, 21000)
    assert gaps == [("decode_dispatch", 8000, 13000),
                    ("wave", 15000, 21000)]
    b = tr.breakdown(ops, host, 1000, 21000)
    assert b["idle_gaps"][0] == ["engine_loop", pytest.approx(6e-6)]
    assert b["device_ops"][0][1] == pytest.approx(6e-6)
    assert len(b["device_ops"]) == 2


def test_gaps_outside_every_span_are_unattributed():
    ops = [tr.Op("a", 10, 10)]
    assert tr.idle_gaps(ops, [], 0, 40) == [("unattributed", 0, 10),
                                             ("unattributed", 20, 40)]


def recorded():
    d = json.loads(SLICE.read_text())
    ops = [tr.Op(n, s, dur) for n, s, dur in d["ops"]]
    host = [tr.Span(n, s, e) for n, s, e in d["host"]]
    return ops, host, d["t0"], d["t1"]


def test_recorded_chip_trace_reduces_consistently():
    """40 ms of a traced qwen2-1.5b.chat wave on a TPU v5e (a 4e9 B pool):
    twelve layers of one decode step, under one ``while`` op."""
    ops, host, t0, t1 = recorded()
    window = (t1 - t0) / 1e9
    busy = tr.busy_s(ops, t0, t1)
    idle = sum(e - s for _, s, e in tr.idle_gaps(ops, host, t0, t1)) / 1e9
    assert 0 < busy < window
    assert busy + idle == pytest.approx(window, rel=1e-9)
    assert tr.busy_s(ops, t0, t1) <= sum(
        min(o.end_ns, t1) - max(o.start_ns, t0) for o in ops
        if o.end_ns > t0 and o.start_ns < t1) / 1e9 + 1e-12
    b = tr.breakdown(ops, host, t0, t1)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert "while" not in [name for name, _ in b["device_ops"]]
    kernel = r"^%_paged_window_core[.\d]* = "
    assert sum(o.name.startswith("%_paged_window_core") for o in ops) == 12
    assert tr.op_time_s(ops, kernel, t0, t1) == pytest.approx(3.377e-3,
                                                             rel=1e-3)
