"""Resolve a cell by name: its configuration, traffic mix and metrics.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* a configuration is ``BENCHMARK.json``'s ``file`` for it, with its plain
  reference beside it (the same path ending in ``.py``);
* a traffic mix is ``traffic/<traffic>.json`` under the benchmark's
  directory;
* a per-layer metric is ``metrics/<name>.py`` there, whose ``read(run)``
  returns the metric or None.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    config_file: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(bench: dict, workload: str) -> Cell:
    """The cell named ``workload``; LookupError if there is none."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise LookupError(f"no workload {workload!r}; have {sorted(by_name)}")
    w = by_name[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config_file = os.path.join(ROOT, entry["file"])

    def applies(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_load_json(config_file), config_file=config_file,
        traffic=_load_json(os.path.join(BENCH_DIR, "traffic",
                                        f"{w['traffic']}.json")),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config_file: str):
    """The configuration's plain reference module (``<config>.py``)."""
    path = os.path.splitext(config_file)[0] + ".py"
    return _load_module(path, "perfbench_reference_" + os.path.basename(
        path)[:-3].replace(".", "_").replace("-", "_"))


def reader(metric: str) -> Callable:
    """``read(run)`` of the per-layer metric ``metric``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{metric}.py")
    mod = _load_module(path, "perfbench_metric_" + metric.replace(
        ".", "_").replace("-", "_"))
    return mod.read
