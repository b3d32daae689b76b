"""BENCHMARK.json against the contract's letter, and every name to a file."""

import importlib
import json
import os
import re

import pytest

from benchmarks.harness.loader import BENCH_DIR, ROOT, load_cell, read_benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = read_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in BENCH["end_to_end"]:
        assert set(metric) <= allowed | {"bound"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
        moved = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
        assert moved, "moves names no end-to-end metric"
        for cell in metric.get("workloads", CELLS):
            assert cell in moved[0].get("workloads", CELLS)
    assert set(metric.get("workloads", [])) <= set(CELLS)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(metric):
    with open(os.path.join(BENCH_DIR, "metrics", metric["name"] + ".json")) as f:
        spec = json.load(f)
    module = importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
    assert callable(module.reduce)


def test_roofline_and_mfu_names():
    names = [m["name"] for m in BENCH["per_layer"]]
    rooflines = [m for m in BENCH["per_layer"] if "roofline" in m["name"]]
    assert rooflines and all(m["unit"] == "%" for m in rooflines)
    for roofline in rooflines:  # the whole step's share moves the same metric
        assert any(
            "mfu" in re.split(r"[._]", n) and m["moves"] == roofline["moves"]
            for n, m in zip(names, BENCH["per_layer"])
        )


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and len(config["why"]) <= 200
    assert config["file"].startswith("benchmarks/")
    with open(os.path.join(ROOT, config["file"])) as f:
        body = json.load(f)
    assert body["source"] == config["source"]
    assert "assumed" in body and "guarantees" in body
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell) and NAME.match(entry["traffic"]) and entry["chips"] in (1, 4)
    assert 1 <= len(entry["why"]) <= 200
    loaded = load_cell(cell)
    assert callable(loaded.driver.setup) and callable(loaded.driver.window)
    assert {m["name"] for m in loaded.end_to_end} >= {"setup_s"}
    assert len(loaded.end_to_end) >= 2 and loaded.per_layer
    assert {"vec_err", "topk_gap", "score_err", "e2e_gap"} <= set(loaded.limits)


def test_cells_are_unique_pairs():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs) and len(set(CELLS)) == len(CELLS)


def test_files_under_paths_have_plain_names():
    plain = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                assert plain.match(os.path.relpath(os.path.join(folder, name), ROOT))
