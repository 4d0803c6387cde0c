"""The engine's own host phases and device scopes in a profiler trace.

``PagedEngine.run_continuous`` opens a ``jax.profiler.TraceAnnotation``
named ``uellm/<phase>`` around each of its host phases
(``repro.obs.trace.HOST_PHASES``: ``iteration``, ``admit``, ``prefill``,
``grow``, ``view``, ``dispatch``, ``sample``, ``sync``, ``emit``, ...) and
names the decode step's parts with ``jax.named_scope``
(``repro.obs.trace.DEVICE_SCOPES``).  This module reads them:

* ``spans``: the host phases as ``trace_reduce.Span``s, prefix stripped;
* ``breakdown``: ``trace_reduce.breakdown`` over the harness's spans and
  the program's together, plus ``idle_by_span``, which splits each idle
  stretch of the device over the innermost span covering each part of it
  (one gap a step runs emit -> finish -> grow -> view -> dispatch; naming
  it by its middle would hide which phase costs it);
* ``host_step_ms``: the host's serial time per decode step, an
  ``iteration`` less the ``sync`` and ``prefill`` inside it;
* ``sync_wait_ms``: the mean ``sync``, the host blocked on a step's tokens;
* ``time_by_span``: host time by phase;
* ``device_scopes``: device seconds by decode-step scope, and by scope and
  op, from the stat of each device operation that holds its scope path.

A trace with no ``uellm/`` spans (a program without the scopes) gives
``None`` from every reader and ``trace_reduce``'s own breakdown.
"""
from __future__ import annotations

import heapq
import importlib.util
import pathlib
from bisect import bisect_left, bisect_right

import trace_reduce
from trace_reduce import Span

PREFIX = "uellm/"
# the program's ``repro.obs.trace.DEVICE_SCOPES``, written out so that the
# reduction also runs against a program that has no such table
DEVICE_SCOPES = ("attention", "kv_write", "mlp", "head", "pick")


def spans(pd) -> list:
    """The ``uellm/`` host spans of a ProfileData, prefix stripped."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span(ev.name[len(PREFIX):],
                                    float(ev.start_ns), float(ev.end_ns)))
    return sorted(out, key=lambda sp: sp.start_ns)


def timeline(host: list) -> list:
    """[(start, end, name)]: time cut at every span boundary, each piece
    named by the innermost (shortest) span covering it; time no span
    covers is left out."""
    points = sorted({p for sp in host for p in (sp.start_ns, sp.end_ns)})
    by_start = sorted(host, key=lambda sp: sp.start_ns)
    heap: list = []
    out: list = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(by_start) and by_start[i].start_ns <= a:
            sp = by_start[i]
            heapq.heappush(heap, (sp.end_ns - sp.start_ns, i, sp))
            i += 1
        while heap and heap[0][2].end_ns <= a:
            heapq.heappop(heap)
        if heap:
            name = heap[0][2].name
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def idle_by_span(ops: list, host: list, t0: float, t1: float,
                 outer: str = "wave", loop: str = "engine_loop") -> list:
    """[[name, seconds]] of the device's idle time in [t0, t1], each idle
    stretch split over the innermost host span covering each part of it
    ("unattributed" where none does; the bare ``outer`` span is ``loop``,
    as in ``trace_reduce.breakdown``).  The profiler's host and device
    clocks can disagree by a millisecond or more, which moves idle time
    between neighbouring phases; the total a step is not moved."""
    pieces = timeline(host)
    tot: dict = {}
    j = 0
    for _, s, e in trace_reduce.idle_gaps(ops, [], t0, t1):
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        covered = 0.0
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, name = pieces[k]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                name = loop if name == outer else name
                tot[name] = tot.get(name, 0.0) + ov
                covered += ov
            k += 1
        if e - s > covered:
            tot["unattributed"] = tot.get("unattributed", 0.0) \
                + (e - s - covered)
    return trace_reduce.top(((k, v / 1e9) for k, v in tot.items()),
                            n=len(tot))


def time_by_span(program: list, t0: float, t1: float) -> list:
    """[[phase, seconds]] of host time in [t0, t1], each moment given to
    the innermost program span covering it."""
    tot: dict = {}
    for a, b, name in timeline(program):
        a, b = max(a, t0), min(b, t1)
        if b > a:
            tot[name] = tot.get(name, 0.0) + (b - a) / 1e9
    return trace_reduce.top(tot.items(), n=len(tot))


def breakdown(ops: list, host: list, program: list, t0: float,
              t1: float) -> dict:
    """``trace_reduce.breakdown`` with idle gaps named from the harness's
    spans and the program's together; with program spans, also
    ``idle_by_span`` and ``host_phases`` (host time by phase)."""
    out = trace_reduce.breakdown(ops, host + program, t0, t1)
    if program:
        out["idle_by_span"] = idle_by_span(ops, host + program, t0, t1)
        out["host_phases"] = time_by_span(program, t0, t1)
    return out


def _inside(program: list, names: tuple, t0: float, t1: float) -> list:
    return [sp for sp in program
            if sp.name in names and sp.start_ns >= t0 and sp.end_ns <= t1]


def host_step_ms(program: list, t0: float = float("-inf"),
                 t1: float = float("inf")):
    """Mean, over the ``iteration`` spans in [t0, t1] that hold a ``sync``,
    of the iteration's time less what its ``sync`` and ``prefill`` spans
    cover, in ms; None without such iterations."""
    its = _inside(program, ("iteration",), t0, t1)
    kids = sorted(_inside(program, ("sync", "prefill"), t0, t1),
                  key=lambda sp: sp.start_ns)
    starts = [sp.start_ns for sp in kids]
    host = []
    for it in its:
        mine = [sp for sp in kids[bisect_left(starts, it.start_ns):
                                  bisect_right(starts, it.end_ns)]
                if sp.end_ns <= it.end_ns]
        if any(sp.name == "sync" for sp in mine):
            waited = sum(e - s for s, e in trace_reduce.intervals(
                mine, it.start_ns, it.end_ns))
            host.append(it.end_ns - it.start_ns - waited)
    return sum(host) / len(host) / 1e6 if host else None


def sync_wait_ms(program: list, t0: float = float("-inf"),
                 t1: float = float("inf")):
    """Mean duration of the ``sync`` spans in [t0, t1], in ms; None
    without any."""
    syncs = _inside(program, ("sync",), t0, t1)
    return sum(sp.end_ns - sp.start_ns for sp in syncs) / len(syncs) / 1e6 \
        if syncs else None


# ------------------------------------------------------------ device scopes

def scope_of(path: str):
    """The innermost decode-step scope named in an op's scope path
    (``jit(_decode)/while/body/attention/kv_write/scatter`` -> kv_write),
    or None."""
    for part in reversed(path.split("/")):
        if part in DEVICE_SCOPES:
            return part
    return None


def _xplane_pb2():
    """The XSpace protobuf classes, loaded from the installed TensorFlow's
    generated module by path (importing TensorFlow itself would start its
    runtime); None where it is missing."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = pathlib.Path(spec.submodule_search_locations[0],
                        "tsl", "profiler", "protobuf", "xplane_pb2.py")
    if not path.exists():
        return None
    mspec = importlib.util.spec_from_file_location("_xplane_pb2", path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def op_stats(xplane_path: str) -> dict:
    """{op name: {stat name: string value}} from the device planes' event
    metadata (the ``XLA Ops`` events keep their per-op stats there, where
    ``ProfileData`` does not show them); {} without the protobuf classes."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return {}
    xs = pb2.XSpace()
    xs.ParseFromString(pathlib.Path(xplane_path).read_bytes())
    out: dict = {}
    for plane in xs.planes:
        if not plane.name.startswith("/device:"):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for md in plane.event_metadata.values():
            stats = {}
            for st in md.stats:
                if st.HasField("str_value"):
                    stats[names.get(st.metadata_id, "")] = st.str_value
                elif st.HasField("ref_value"):
                    stats[names.get(st.metadata_id, "")] = \
                        names.get(st.ref_value, "")
            if stats:
                out.setdefault(md.name, stats)
    return out


def scope_stat(stats: dict):
    """Name of the stat that holds the scope paths (the one naming a
    decode-step scope for the most ops), or None."""
    hits: dict = {}
    for per_op in stats.values():
        for name, value in per_op.items():
            if scope_of(value) is not None:
                hits[name] = hits.get(name, 0) + 1
    return max(hits, key=hits.get) if hits else None


def device_scopes(ops: list, stats: dict, t0: float, t1: float):
    """({"device_scopes": [[scope, seconds]], "device_scope_ops": [[scope
    and op label, seconds]]}) of device time in [t0, t1] by the innermost
    decode-step scope of each leaf op ("unscoped" outside every scope), or
    None when no op's stats name a scope."""
    stat = scope_stat(stats)
    if stat is None:
        return None
    scope = {name: scope_of(per_op.get(stat, "")) or "unscoped"
             for name, per_op in stats.items()}
    timed = [(scope.get(o.name, "unscoped"), trace_reduce.op_label(o),
              max(0.0, min(o.end_ns, t1) - max(o.start_ns, t0)) / 1e9)
             for o in trace_reduce.leaves(ops)]
    return {"device_scopes": trace_reduce.top(
                ((sc, t) for sc, _, t in timed), n=len(DEVICE_SCOPES) + 1),
            "device_scope_ops": trace_reduce.top(
                ((f"{sc} {lab}", t) for sc, lab, t in timed), n=16)}
