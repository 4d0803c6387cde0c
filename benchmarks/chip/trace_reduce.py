"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
events: the device's operations (one list per chip, from each device
plane's ``XLA Ops`` line) and the host spans that the harness opened with
``jax.profiler.TraceAnnotation`` (names starting ``bench/``).  Everything
after that works on those lists:

* ``busy_s``: the union of the intervals in which an operation ran;
* ``op_time_s``: summed device time of the operations whose name (on the
  TPU, the op's HLO text, ``%fusion.12 = ...``) matches a pattern (a
  kernel's time);
* ``idle_gaps``: the stretches with no operation on the device, each named
  by the innermost host span that covers its middle ("unattributed" when
  none does).
"""
from __future__ import annotations

import functools
import glob
import os
import re
from dataclasses import dataclass

HOST_PREFIX = "bench/"
OPS_LINE = "XLA Ops"


@dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def from_profile(pd) -> tuple[dict, list]:
    """(device plane name -> [Op], host [Span]) from a ProfileData."""
    devices: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = devices.setdefault(plane.name, [])
                for ev in line.events:
                    ops.append(Op(ev.name, float(ev.start_ns),
                                  float(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append(Span(ev.name[len(HOST_PREFIX):],
                                         float(ev.start_ns),
                                         float(ev.end_ns)))
    for ops in devices.values():
        ops.sort(key=lambda o: o.start_ns)
    return devices, host


def load(log_dir: str) -> tuple[dict, list]:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(find_xplane(log_dir)))


def intervals(ops: list, t0: float, t1: float) -> list:
    """Merged [start, end] intervals of the ops, clipped to [t0, t1]."""
    out: list = []
    for o in sorted(ops, key=lambda o: o.start_ns):
        s, e = max(o.start_ns, t0), min(o.end_ns, t1)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(ops: list, t0: float, t1: float) -> float:
    return sum(e - s for s, e in intervals(ops, t0, t1)) / 1e9


def op_time_s(ops: list, pattern: str, t0: float, t1: float) -> float:
    rx = re.compile(pattern)
    return sum(max(0.0, min(o.end_ns, t1) - max(o.start_ns, t0))
               for o in ops if rx.search(o.name)) / 1e9


def _labels(host: list, times: list) -> list:
    """Innermost host span covering each of the ascending ``times``."""
    spans = sorted(host, key=lambda sp: sp.start_ns)
    active: list = []
    out = []
    i = 0
    for t in times:
        while i < len(spans) and spans[i].start_ns <= t:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp.end_ns >= t]
        best = min(active, key=lambda sp: sp.end_ns - sp.start_ns,
                   default=None)
        out.append(best.name if best is not None else "unattributed")
    return out


def idle_gaps(ops: list, host: list, t0: float, t1: float) -> list:
    """[(label, start_ns, end_ns)] of the device's idle stretches in
    [t0, t1], each named by what the host was doing at its middle."""
    gaps = []
    cur = t0
    for s, e in intervals(ops, t0, t1) + [[t1, t1]]:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    labels = _labels(host, [(s + e) / 2 for s, e in gaps])
    return [(lab, s, e) for lab, (s, e) in zip(labels, gaps)]


def top(pairs, n: int = 10) -> list:
    """[[name, total seconds]] of the n largest totals."""
    tot: dict = {}
    for name, secs in pairs:
        tot[name] = tot.get(name, 0.0) + secs
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def op_label(o: Op) -> str:
    """A stable name for an operation: its HLO instruction name without the
    instance number (the TPU trace names an op by its HLO text,
    ``%fusion.12 = ...``)."""
    return _label(o.name)


@functools.lru_cache(maxsize=None)
def _label(name: str) -> str:
    return re.sub(r"\.\d+$", "", name.split(" = ")[0].lstrip("%"))[:120]


def leaves(ops: list) -> list:
    """The ops that hold no other op (a while loop's event spans the ops of
    its body; counting both would count the body twice)."""
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start_ns >= o.end_ns
            or nxt.end_ns > o.end_ns]


def breakdown(ops: list, host: list, t0: float, t1: float,
              outer: str = "wave", loop: str = "engine_loop") -> dict:
    """Top device ops by time, and idle time by host activity; idle under
    only the ``outer`` span (host code between annotated calls) is named
    ``loop``."""
    dev = top((op_label(o), max(0.0, min(o.end_ns, t1) - max(o.start_ns, t0))
               / 1e9) for o in leaves(ops))
    idle = top((loop if lab == outer else lab, (e - s) / 1e9)
               for lab, s, e in idle_gaps(ops, host, t0, t1))
    return {"device_ops": dev, "idle_gaps": idle}
