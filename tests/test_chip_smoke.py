"""chip_smoke.py's contract off the chip, and the pieces that keep a run
from passing without the device: the compile-cache placement, the mesh
builder, and the compiled tick's error path."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*args: str, timeout: float) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, SMOKE, *args],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_chip_smoke_refuses_without_a_tpu():
    """No TPU and no --cpu-dry-run: non-zero exit, the no-TPU line, and
    no result on stdout."""
    res = _run_smoke(timeout=120)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert res.stdout.strip() == ""


def test_chip_smoke_refuses_without_the_program(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(SMOKE).read())
    res = subprocess.run(
        [sys.executable, str(alone)],
        cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert res.returncode != 0
    assert "not importable" in res.stderr
    assert res.stdout.strip() == ""


@pytest.mark.slow
def test_chip_smoke_cpu_dry_run_passes():
    res = _run_smoke("--cpu-dry-run", timeout=900)
    assert res.returncode == 0, res.stderr[-2000:]
    lines = [json.loads(l) for l in res.stdout.splitlines()]
    assert lines[-1]["ok"] is True and lines[-1]["dry_run"] is True
    assert lines[-1]["device"]["platform"] == "cpu"
    phases = {l["phase"]: l for l in lines[:-1]}
    for name in ("embed", "retrieve", "topk", "generate", "tick"):
        assert phases[name]["ok"] is True
    # every line says what it is: a CPU dry run
    assert all(
        l["platform"] == "cpu" and l["dry_run"] is True for l in lines[:-1]
    )


# --- the compile cache is placed from outside -------------------------------


def test_compile_cache_leaves_the_environment_variable_alone(monkeypatch):
    import jax

    from pathway_tpu.internals import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.configure_compile_cache() == "/somewhere/else"
    # nothing set in code: jax reads the variable by itself
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_one_path_in_the_checkout(monkeypatch):
    import jax

    from pathway_tpu.internals import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.configure_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.configure_compile_cache() == path  # idempotent
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# --- the mesh builder does not swap backends --------------------------------


def test_make_mesh_raises_on_an_accelerator_with_too_few_devices(monkeypatch):
    """One TPU chip, four asked for: an error — not a mesh of the eight
    virtual CPU devices this test process also has."""
    import jax

    from pathway_tpu.parallel.mesh import make_mesh

    class FakeTpu:
        platform = "tpu"

    monkeypatch.setattr(jax, "local_devices", lambda: [FakeTpu()])
    assert len(jax.devices("cpu")) >= 4
    with pytest.raises(ValueError, match="tpu backend has 1"):
        make_mesh(4)


def test_engine_shards_without_the_devices_raises(monkeypatch):
    import jax

    from pathway_tpu.parallel import mesh as mesh_mod

    class FakeTpu:
        platform = "tpu"

    monkeypatch.setattr(jax, "local_devices", lambda: [FakeTpu()])
    monkeypatch.setenv("PATHWAY_ENGINE_SHARDS", "4")
    monkeypatch.setattr(mesh_mod, "_engine_mesh_resolved", False)
    monkeypatch.setattr(mesh_mod, "_engine_mesh", None)
    with pytest.raises(ValueError):
        mesh_mod.get_engine_mesh()


# --- a broken compiled path is a red run, not an interpreted one ------------


def test_segment_build_error_fails_the_tick(monkeypatch):
    """NotCompilable is a decision and runs the interpreter; any other
    exception while building a segment fails the run."""
    from pathway_tpu.engine import compile as tick_forge
    from pathway_tpu.engine.batch import DiffBatch
    from pathway_tpu.engine.expression_eval import InternalColRef
    from pathway_tpu.engine.nodes import InputNode, OutputNode, RowwiseNode
    from pathway_tpu.engine.runtime import Runtime, StaticSource

    class Src(StaticSource):
        def events(self):
            n = 128
            yield 0, DiffBatch(
                np.arange(n, dtype=np.uint64),
                np.ones(n, dtype=np.int64),
                {"a": np.arange(n, dtype=np.int64)},
            )

    def run():
        rows = []
        inp = InputNode(Src(["a"]), ["a"])
        m = RowwiseNode([inp], {"x": InternalColRef(0, "a") * 2 + 1})
        rt = Runtime([OutputNode(m, lambda t, b: rows.extend(b.iter_rows()))])
        rt.run()
        return rows, rt

    monkeypatch.setenv("PATHWAY_COMPILED_TICK", "1")
    rows, rt = run()
    assert len(rows) == 128
    assert sum(s.compiled_ticks for s in rt.compiled_plan.segments) > 0

    def refuse(*_a, **_k):
        raise tick_forge.NotCompilable("refused for the test")

    monkeypatch.setattr(tick_forge, "_build_program", refuse)
    rows, rt = run()  # a decision: the interpreter serves the tick
    assert len(rows) == 128
    assert sum(s.fallback_ticks for s in rt.compiled_plan.segments) > 0

    def broken(*_a, **_k):
        raise RuntimeError("the compiler said no")

    monkeypatch.setattr(tick_forge, "_build_program", broken)
    with pytest.raises(RuntimeError, match="the compiler said no"):
        run()


# --- a failed device search is not an empty 200 ------------------------------


def _knn_pipeline(query_dim: int):
    import pathway_tpu as pw
    from pathway_tpu.stdlib.indexing import DataIndex, TpuKnn

    schema = pw.schema_from_types(name=str, vec=np.ndarray)
    rng = np.random.default_rng(0)
    docs = pw.debug.table_from_rows(
        schema, [(f"d{i}", rng.normal(size=4).astype(np.float32)) for i in range(8)]
    )
    queries = pw.debug.table_from_rows(
        schema, [("q", rng.normal(size=query_dim).astype(np.float32))]
    )
    index = DataIndex(docs, TpuKnn(docs.vec, dimensions=4))
    res = index.query_as_of_now(queries.vec, number_of_matches=2).select(
        names=pw.right.name
    )
    _keys, cols = pw.debug.table_to_dicts(res)
    return list(cols["names"].values())


def test_malformed_query_is_a_recorded_data_error():
    from pathway_tpu.internals.errors import error_count

    assert len(_knn_pipeline(query_dim=4)[0]) == 2
    assert error_count() == 0
    # a 5-dimensional query against a 4-dimensional corpus: data error,
    # recorded, answered empty
    assert _knn_pipeline(query_dim=5) == [()]
    assert error_count() == 1


def test_device_search_failure_fails_the_tick(monkeypatch):
    from pathway_tpu.ops.knn import DeviceCorpus

    def refused(self, queries, k, metric):
        # what a shape complaint from the device program's lowering looks like
        raise ValueError("block shape is not divisible by (8, 128)")

    monkeypatch.setattr(DeviceCorpus, "topk", refused)
    with pytest.raises(RuntimeError, match="device top-k failed"):
        _knn_pipeline(query_dim=4)


def test_failed_decode_step_stops_the_generation_plane(monkeypatch):
    import time

    from pathway_tpu.generate.scheduler import (
        DecodeScheduler,
        GenerateConfig,
        GenerationRequest,
    )
    from pathway_tpu.serving.admission import ShedError
    from pathway_tpu.xpacks.llm import decoder as dec

    def boom(*_a, **_k):
        raise RuntimeError("Mosaic failed to compile the kernel")

    monkeypatch.setattr(dec, "decode_step", boom)
    sched = DecodeScheduler(
        GenerateConfig(n_pages=8, max_len=64), replica_label="failstop"
    )
    try:
        req = GenerationRequest(
            "r1", [1, 2, 3], deadline=time.monotonic() + 30, max_new_tokens=4
        )
        sched.submit(req)
        res = req.wait(30)
        assert res is not None and res["status"] == 500
        assert "Mosaic failed" in res["error"]
        stats = sched.stats()
        assert "Mosaic failed" in stats["failed"]
        assert stats["free_pages"] == stats["page_capacity"]
        with pytest.raises(ShedError) as shed:
            sched.submit(
                GenerationRequest(
                    "r2", [1], deadline=time.monotonic() + 30, max_new_tokens=1
                )
            )
        assert shed.value.status == 503 and "Mosaic failed" in shed.value.reason
    finally:
        sched.stop()


def test_emulated_float64_is_not_compiled(monkeypatch):
    """On a backend that emulates float64 (a TPU) a chain that touches a
    float64 runs on the interpreter, by decision; integer chains still
    compile."""
    import pathway_tpu as pw
    from pathway_tpu.ops import backend

    class Num(pw.Schema):
        a: int
        b: float

    rows = [(i, i * 0.5) for i in range(128)]

    def run(select):
        pw.internals.parse_graph.G.clear()
        t = pw.debug.table_from_rows(Num, rows)
        _k, cols = pw.debug.table_to_dicts(select(t))
        plan = pw.internals.parse_graph.G.last_runtime.compiled_plan
        return cols, sum(s.compiled_ticks for s in plan.segments)

    def floats(t):  # a float constant and a float input column
        return t.select(y=t.b * 2.5 + 1.0)

    def casts(t):  # integer inputs, a float64 only in between
        return t.select(z=pw.cast(float, t.a) > 3.0)

    def ints(t):
        return t.select(z=t.a * 2 + 1).filter(pw.this.z > 7)

    monkeypatch.setenv("PATHWAY_COMPILED_TICK", "1")
    want = {f.__name__: run(f) for f in (floats, casts, ints)}
    assert all(ticks > 0 for _cols, ticks in want.values())
    monkeypatch.setattr(backend, "float64_native", lambda: False)
    for f in (floats, casts):
        cols, ticks = run(f)
        assert ticks == 0, f.__name__
        assert cols == want[f.__name__][0]
    cols, ticks = run(ints)
    assert ticks > 0 and cols == want["ints"][0]
