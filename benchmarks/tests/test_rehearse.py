"""Both cells under --rehearse: the last line's keys and the metric names;
the timed path broken underneath: ``correct`` comes out false."""

import json

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness.loader import load_cell, read_benchmark

BENCH = read_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def rehearse(cell, capsys, trace=0, seed=2147483659):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", "1", "--trace", str(trace), "--rehearse"]
    result = run.main(argv)
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    return result, last, captured.err


@pytest.mark.parametrize("cell", CELLS)
def test_last_line(cell, capsys):
    result, last, err = rehearse(cell, capsys)
    assert list(last)[: len(KEYS)] == KEYS and list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    wanted = {m["name"] for m in load_cell(cell).end_to_end}
    assert set(last["metrics"]) == wanted and "setup_s" in wanted
    for entry in last["metrics"].values():
        assert entry["value"] > 0 and set(entry) == {"value", "unit"}
    assert last["device"]["platform"] == "cpu"  # never a device number from here
    compared = last["compared"]
    assert compared["compiles_in_window"] == {"value": 0, "limit": 0}
    tail = err.strip().splitlines()[-len(compared):]
    assert all(line.startswith("compared ") for line in tail)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line_reports_only_what_a_cpu_can(cell, capsys):
    _result, last, _err = rehearse(cell, capsys, trace=1)
    per_layer = {m["name"]: m for m in load_cell(cell).per_layer}
    assert set(last["metrics"]) <= set(per_layer)
    # host spans are read; shares of the chip's peak and of its time are not
    assert last["metrics"] and all(per_layer[n]["unit"] == "ms" for n in last["metrics"])
    assert "busy_s" not in last["device"] and set(last["breakdown"]) == {"device_ops", "idle_gaps"}


def break_embedder(state, fault):
    embedder = state["embedder"]
    sound = embedder._embed_batch

    def broken(texts):
        vectors = sound(texts)
        if fault == "half_batch" and len(vectors) > 1:
            # half of the batch left out, the mean taken over the rest
            half = len(vectors) // 2
            mean = np.mean(vectors[:half], axis=0)
            vectors = vectors[:half] + [mean / np.linalg.norm(mean)] * (len(vectors) - half)
        return vectors

    embedder._embed_batch = broken


def break_index(state, fault):
    index = state["index"]
    if fault == "state_unchanged":
        # a step that returns its state unchanged: the upsert changes nothing
        index.upsert = lambda key, data, metadata: None
    if fault == "answer_altered":
        sound = index.search

        def altered(queries):
            hits = list(sound(queries))
            (key, score), rest = hits[0][0], hits[0][1:]
            hits[0] = ((key + 1, score),) + rest  # the best row's neighbour in its place
            return hits

        index.search = altered


FAULTS = [
    ("minilm-l6-384.retrieve", "half_batch"),
    ("minilm-l6-384.retrieve", "answer_altered"),
    ("bge-base-768.ingest", "half_batch"),
    ("bge-base-768.ingest", "answer_altered"),
    ("bge-base-768.ingest", "state_unchanged"),
]


@pytest.mark.parametrize("cell, fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(cell, fault, capsys, monkeypatch):
    driver = load_cell(cell).driver
    sound_window = driver.window

    def window(run_, state):
        break_embedder(state, fault)
        break_index(state, fault)
        return sound_window(run_, state)

    monkeypatch.setattr(driver, "window", window)
    _result, last, err = rehearse(cell, capsys)
    assert last["correct"] is False
    assert "OVER" in err


def test_a_run_off_the_tpu_without_rehearse_fails_and_prints_no_result():
    import os
    import subprocess
    import sys

    from benchmarks.harness.loader import ROOT

    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=120,
    )
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "no TPU" in done.stderr
