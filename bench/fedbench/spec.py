"""A cell, found by its name in ``BENCHMARK.json``.

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); each per-layer metric is
read by ``bench/metrics/<metric>.py``. Adding any of them is adding a
file and an entry.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

from fedbench.reference import BENCH


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)

    def limits(self) -> dict:
        return self.config["limits"]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def config_file(name: str) -> str:
    return os.path.join(BENCH, "configs", name + ".json")


def traffic_file(name: str) -> str:
    return os.path.join(BENCH, "traffic", name + ".json")


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def load(root: str, workload: str) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    w = cells[workload]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, reported)]
    return Cell(name=workload, config=_json(config_file(w["config"])),
                traffic=_json(traffic_file(w["traffic"])), chips=w["chips"],
                end_to_end=e2e, per_layer=per_layer)


def reader(metric: str):
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
