"""Finds a cell's parts by name: ``BENCHMARK.json`` at the root of the
checkout, a configuration as ``configs/<config>.json``, a traffic mix as
``traffic/<traffic>.json`` and a per-layer metric as ``metrics/<name>.py``.
Adding a configuration, a mix or a metric is adding one file."""
from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent.parent


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def names(kind: str, suffix: str, here: pathlib.Path = HERE) -> list:
    return sorted(p.name[:-len(suffix)] for p in (here / kind).glob(
        "*" + suffix) if not p.name.startswith("_"))


def configs(here: pathlib.Path = HERE) -> list:
    return names("configs", ".json", here)


def mixes(here: pathlib.Path = HERE) -> list:
    return names("traffic", ".json", here)


def metrics(here: pathlib.Path = HERE) -> list:
    return names("metrics", ".py", here)


def config(name: str, here: pathlib.Path = HERE) -> dict:
    return json.loads((here / "configs" / f"{name}.json").read_text())


def mix_path(name: str, here: pathlib.Path = HERE) -> pathlib.Path:
    return here / "traffic" / f"{name}.json"


def metric(name: str, here: pathlib.Path = HERE):
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def metrics_of(bench: dict, workload: str, kind: str) -> list:
    """The ``kind`` ("end_to_end" or "per_layer") metrics a cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]
