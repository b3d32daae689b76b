"""Data-lake writer depth (reference:
src/connectors/data_lake/{delta,iceberg,writer}.rs): transactional
append/overwrite, schema-evolution guards, object storage, compaction,
round-trip write->read for both formats."""

import json
import os

import pytest

import pathway_tpu as pw
from pathway_tpu.debug import table_to_dicts
from pathway_tpu.engine.batch import DiffBatch
from pathway_tpu.io.deltalake import _DeltaWriter, _Store, _replay_log


class KV(pw.Schema):
    k: str = pw.column_definition(primary_key=True)
    v: int


def _write_rows(writer, rows, t=0):
    writer.write_batch(
        t, DiffBatch.from_rows([(i, 1, r) for i, r in enumerate(rows)], ["k", "v"])
    )


def _read_static_delta(uri):
    pw.internals.parse_graph.G.clear()
    t = pw.io.deltalake.read(uri, schema=KV, mode="static")
    _keys, cols = table_to_dicts(t)
    return {cols["k"][key]: cols["v"][key] for key in cols["k"]}


def test_delta_overwrite_mode(tmp_path):
    lake = str(tmp_path / "lake")
    w = _DeltaWriter(_Store(lake), ["k", "v"])
    _write_rows(w, [("a", 1), ("b", 2)])
    assert _read_static_delta(lake) == {"a": 1, "b": 2}
    # overwrite: old parts removed via log actions, only new data remains
    w2 = _DeltaWriter(_Store(lake), ["k", "v"], mode="overwrite")
    _write_rows(w2, [("c", 3)])
    assert _read_static_delta(lake) == {"c": 3}
    # old parquet parts still on disk (no vacuum), but log replay drops them
    files, _meta = _replay_log(_Store(lake))
    assert len(files) == 1


def test_delta_schema_evolution_guard(tmp_path):
    lake = str(tmp_path / "lake")
    w = _DeltaWriter(
        _Store(lake), ["k", "v"], [{"name": "k", "type": "str"}, {"name": "v", "type": "int"}]
    )
    _write_rows(w, [("a", 1)])
    # dropping a column is refused
    with pytest.raises(ValueError, match="drops existing"):
        _DeltaWriter(_Store(lake), ["k"], [{"name": "k", "type": "str"}])
    # changing a type is refused
    with pytest.raises(ValueError, match="changes type"):
        _DeltaWriter(
            _Store(lake),
            ["k", "v"],
            [{"name": "k", "type": "str"}, {"name": "v", "type": "str"}],
        )
    # adding a column needs opt-in
    three = [
        {"name": "k", "type": "str"},
        {"name": "v", "type": "int"},
        {"name": "w", "type": "int"},
    ]
    with pytest.raises(ValueError, match="allow_add"):
        _DeltaWriter(_Store(lake), ["k", "v", "w"], three)
    w3 = _DeltaWriter(
        _Store(lake), ["k", "v", "w"], three, schema_evolution="allow_add"
    )
    w3.write_batch(
        1, DiffBatch.from_rows([(9, 1, ("c", 3, 30))], ["k", "v", "w"])
    )
    # evolved metadata is now the table schema
    _files, meta = _replay_log(_Store(lake))
    assert {f["name"] for f in meta["fields"]} == {"k", "v", "w"}
    # old rows read back with None for the new column
    class KVW(pw.Schema):
        k: str = pw.column_definition(primary_key=True)
        v: int
        w: int | None

    pw.internals.parse_graph.G.clear()
    t = pw.io.deltalake.read(lake, schema=KVW, mode="static")
    _keys, cols = table_to_dicts(t)
    got = {cols["k"][key]: (cols["v"][key], cols["w"][key]) for key in cols["k"]}
    assert got == {"a": (1, None), "c": (3, 30)}


def test_delta_compaction(tmp_path):
    lake = str(tmp_path / "lake")
    w = _DeltaWriter(_Store(lake), ["k", "v"], compact_every=3)
    for i in range(7):
        _write_rows(w, [(f"k{i}", i)], t=i)
    files, _meta = _replay_log(_Store(lake))
    # 7 appends with compact_every=3: active files merged periodically
    assert len(files) <= 3, files
    assert _read_static_delta(lake) == {f"k{i}": i for i in range(7)}


def test_delta_optimistic_concurrency(tmp_path):
    lake = str(tmp_path / "lake")
    w1 = _DeltaWriter(_Store(lake), ["k", "v"])
    w2 = _DeltaWriter(_Store(lake), ["k", "v"])
    # both writers believe they own the same next version; the commit
    # protocol must keep BOTH batches (exclusive create + retry)
    _write_rows(w1, [("a", 1)])
    _write_rows(w2, [("b", 2)])
    assert _read_static_delta(lake) == {"a": 1, "b": 2}


def test_delta_object_store_roundtrip():
    """The same writer/reader path over an fsspec object store (memory://
    here; s3:// uses the identical code path)."""
    import uuid

    uri = f"memory://lake-{uuid.uuid4().hex}"
    w = _DeltaWriter(_Store(uri), ["k", "v"])
    _write_rows(w, [("a", 1), ("b", 2)])
    assert _read_static_delta(uri) == {"a": 1, "b": 2}


def test_delta_streaming_retracts_on_overwrite(tmp_path):
    """The streaming reader emits retractions for removed files, so an
    overwrite flows as an incremental update."""
    import threading
    import time

    lake = str(tmp_path / "lake")
    w = _DeltaWriter(_Store(lake), ["k", "v"])
    _write_rows(w, [("a", 1), ("b", 2)])

    pw.internals.parse_graph.G.clear()
    t = pw.io.deltalake.read(lake, schema=KV, mode="streaming")
    seen = {}
    lock = threading.Lock()

    def on_change(key, row, time, is_addition):
        with lock:
            if is_addition:
                seen[row["k"]] = row["v"]
            else:
                seen.pop(row["k"], None)

    pw.io.subscribe(t, on_change)
    th = threading.Thread(
        target=lambda: pw.run(autocommit_duration_ms=20), daemon=True
    )
    th.start()
    deadline = time.time() + 15
    while time.time() < deadline and seen != {"a": 1, "b": 2}:
        time.sleep(0.05)
    assert seen == {"a": 1, "b": 2}, seen
    w2 = _DeltaWriter(_Store(lake), ["k", "v"], mode="overwrite")
    _write_rows(w2, [("c", 3)], t=1)
    while time.time() < deadline and seen != {"c": 3}:
        time.sleep(0.05)
    rt = pw.internals.parse_graph.G.runtime
    if rt is not None:
        rt.stop()
    th.join(timeout=10)
    assert seen == {"c": 3}, seen


# --- iceberg ---------------------------------------------------------------


def _read_static_iceberg(uri):
    pw.internals.parse_graph.G.clear()
    t = pw.io.iceberg.read(uri, schema=KV, mode="static")
    _keys, cols = table_to_dicts(t)
    return {cols["k"][key]: cols["v"][key] for key in cols["k"]}


def test_iceberg_roundtrip_append_overwrite(tmp_path):
    from pathway_tpu.io.iceberg import _IcebergWriter

    root = str(tmp_path / "warehouse")
    desc = [{"name": "k", "type": "str"}, {"name": "v", "type": "int"}]
    w = _IcebergWriter(root, ["k", "v"], desc)
    _write_rows(w, [("a", 1)])
    w2 = _IcebergWriter(root, ["k", "v"], desc)  # append continues
    _write_rows(w2, [("b", 2)])
    assert _read_static_iceberg(root) == {"a": 1, "b": 2}
    w3 = _IcebergWriter(root, ["k", "v"], desc, mode="overwrite")
    _write_rows(w3, [("c", 3)])
    assert _read_static_iceberg(root) == {"c": 3}
    # snapshot history retained in metadata
    from pathway_tpu.io.iceberg import _current_version, _snapshot_meta

    meta = _snapshot_meta(root, _current_version(root))
    assert len(meta["snapshots"]) >= 3
    assert meta["schema"]["fields"] == desc


def test_iceberg_schema_guard(tmp_path):
    from pathway_tpu.io.iceberg import _IcebergWriter

    root = str(tmp_path / "warehouse")
    desc = [{"name": "k", "type": "str"}, {"name": "v", "type": "int"}]
    w = _IcebergWriter(root, ["k", "v"], desc)
    _write_rows(w, [("a", 1)])
    with pytest.raises(ValueError, match="drops existing"):
        _IcebergWriter(root, ["k"], [{"name": "k", "type": "str"}])
    with pytest.raises(ValueError, match="allow_add"):
        _IcebergWriter(
            root,
            ["k", "v", "w"],
            desc + [{"name": "w", "type": "int"}],
        )
    _IcebergWriter(
        root,
        ["k", "v", "w"],
        desc + [{"name": "w", "type": "int"}],
        schema_evolution="allow_add",
    )


def test_iceberg_streaming_retracts_on_overwrite(tmp_path):
    import threading
    import time

    from pathway_tpu.io.iceberg import _IcebergWriter

    root = str(tmp_path / "warehouse")
    desc = [{"name": "k", "type": "str"}, {"name": "v", "type": "int"}]
    w = _IcebergWriter(root, ["k", "v"], desc)
    _write_rows(w, [("a", 1)])

    pw.internals.parse_graph.G.clear()
    t = pw.io.iceberg.read(root, schema=KV, mode="streaming")
    seen = {}
    lock = threading.Lock()

    def on_change(key, row, time, is_addition):
        with lock:
            if is_addition:
                seen[row["k"]] = row["v"]
            else:
                seen.pop(row["k"], None)

    pw.io.subscribe(t, on_change)
    th = threading.Thread(
        target=lambda: pw.run(autocommit_duration_ms=20), daemon=True
    )
    th.start()
    deadline = time.time() + 15
    while time.time() < deadline and seen != {"a": 1}:
        time.sleep(0.05)
    assert seen == {"a": 1}, seen
    w2 = _IcebergWriter(root, ["k", "v"], desc, mode="overwrite")
    _write_rows(w2, [("z", 9)], t=1)
    while time.time() < deadline and seen != {"z": 9}:
        time.sleep(0.05)
    rt = pw.internals.parse_graph.G.runtime
    if rt is not None:
        rt.stop()
    th.join(timeout=10)
    assert seen == {"z": 9}, seen


def test_delta_overwrite_is_atomic_with_first_batch(tmp_path):
    """Constructing an overwrite writer must NOT empty the table; the
    removes commit together with the first data batch (one atomic delta
    commit — an aborted pipeline leaves the table intact)."""
    lake = str(tmp_path / "lake")
    w = _DeltaWriter(_Store(lake), ["k", "v"])
    _write_rows(w, [("a", 1)])
    w2 = _DeltaWriter(_Store(lake), ["k", "v"], mode="overwrite")
    # no batch written yet: table unchanged
    assert _read_static_delta(lake) == {"a": 1}
    _write_rows(w2, [("b", 2)])
    assert _read_static_delta(lake) == {"b": 2}


def test_iceberg_overwrite_is_atomic_with_first_batch(tmp_path):
    from pathway_tpu.io.iceberg import _IcebergWriter

    root = str(tmp_path / "warehouse")
    desc = [{"name": "k", "type": "str"}, {"name": "v", "type": "int"}]
    w = _IcebergWriter(root, ["k", "v"], desc)
    _write_rows(w, [("a", 1)])
    w2 = _IcebergWriter(root, ["k", "v"], desc, mode="overwrite")
    assert _read_static_iceberg(root) == {"a": 1}
    _write_rows(w2, [("b", 2)])
    assert _read_static_iceberg(root) == {"b": 2}
