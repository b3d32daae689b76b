"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

The harness keeps no list of its own: a configuration is
``configs/<config>.json``, a traffic mix ``traffic/<traffic>.json`` (whose
``driver`` names ``drivers/<driver>.py``), a per-layer metric
``metrics/<name>.json`` (whose ``reducer`` names ``reducers/<reducer>.py``)
and a cell's limits ``limits/<cell>.json``.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def read_benchmark() -> dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def _overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _overlay(out[key], value)
        else:
            out[key] = value
    return out


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)

    @property
    def driver(self):
        return importlib.import_module(
            f"benchmarks.drivers.{self.traffic['driver']}"
        )


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, rehearse: bool = False) -> Cell:
    bench = read_benchmark()
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has: {known}")
    entry = entries[0]
    by_name = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(ROOT, by_name[entry["config"]]["file"]))
    traffic = _read_json(
        os.path.join(BENCH_DIR, "traffic", entry["traffic"] + ".json")
    )
    if rehearse:
        # the same control flow at sizes a CPU holds; never a device number
        config = _overlay(config, config.get("rehearse", {}))
        traffic = _overlay(traffic, traffic.get("rehearse", {}))
    limits = _read_json(os.path.join(BENCH_DIR, "limits", name + ".json"))
    toy_limits = limits.pop("rehearse", {})  # a toy model's vectors are coarser
    if rehearse:
        limits = _overlay(limits, toy_limits)
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        traffic_name=entry["traffic"],
        config=config,
        traffic=traffic,
        limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def load_metric_reader(metric_name: str):
    """(reduce function, its arguments) of one per-layer metric."""
    spec = _read_json(os.path.join(BENCH_DIR, "metrics", metric_name + ".json"))
    module = importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
    return module.reduce, spec.get("args", {})
