"""IVF scale-out index (design note: ops/ivf.py;
reference counterpart: usearch HNSW, usearch_integration.rs:20)."""

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.debug import T, table_to_dicts
from pathway_tpu.stdlib.indexing._index_impls import IvfKnnIndex


def _vec_table(rows):
    import pathway_tpu.debug as dbg

    schema = pw.schema_from_types(name=str, vec=np.ndarray)
    return dbg.table_from_rows(
        schema, [(n, np.asarray(v, dtype=np.float32)) for n, v in rows]
    )


DOCS = [
    ("a", [1.0, 0.0, 0.0]),
    ("b", [0.0, 1.0, 0.0]),
    ("c", [0.0, 0.0, 1.0]),
    ("d", [0.9, 0.1, 0.0]),
]


def test_ivf_data_index_query_small_exact():
    """Below min_train the IVF index scores exactly — the DataIndex matrix
    result matches the brute-force index bit for bit."""
    docs = _vec_table(DOCS)
    queries = _vec_table([("q1", [1.0, 0.0, 0.0]), ("q2", [0.0, 1.0, 0.0])])
    from pathway_tpu.stdlib.indexing import DataIndex, IvfKnn

    index = DataIndex(docs, IvfKnn(docs.vec, dimensions=3))
    result = index.query_as_of_now(queries.vec, number_of_matches=2).select(
        qname=pw.left.name, names=pw.right.name
    )
    _keys, cols = table_to_dicts(result)
    by_q = {cols["qname"][k]: cols["names"][k] for k in cols["qname"]}
    assert by_q["q1"] == ("a", "d")
    assert by_q["q2"][0] == "b"


def test_ivf_metadata_filter():
    import pathway_tpu.debug as dbg

    schema = pw.schema_from_types(name=str, vec=np.ndarray, meta=dict)
    docs = dbg.table_from_rows(
        schema,
        [
            ("a", np.asarray([1.0, 0.0], np.float32), {"lang": "en"}),
            ("b", np.asarray([0.9, 0.1], np.float32), {"lang": "fr"}),
        ],
    )
    queries = T(
        """
        qname | filter
        q1    | lang=='fr'
        """
    ).select(
        qname=pw.this.qname,
        filter=pw.this.filter,
        vec=pw.apply_with_type(
            lambda _: np.asarray([1.0, 0.0], np.float32),
            np.ndarray,
            pw.this.qname,
        ),
    )
    from pathway_tpu.stdlib.indexing import DataIndex, IvfKnn

    index = DataIndex(docs, IvfKnn(docs.vec, docs.meta, dimensions=2))
    result = index.query_as_of_now(
        queries.vec, number_of_matches=1, metadata_filter=queries["filter"]
    ).select(names=pw.right.name)
    _keys, cols = table_to_dicts(result)
    assert list(cols["names"].values()) == [("b",)]


def test_ivf_trained_engine_path():
    """With min_train lowered, the DataIndex query runs through the real
    two-level path (centroids + inverted lists) and still finds the right
    neighbors on clustered data."""
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(8, 16)).astype(np.float32) * 5
    rows = []
    for i in range(512):
        c = i % 8
        rows.append(
            (f"d{i}", centers[c] + rng.normal(size=16).astype(np.float32) * 0.05)
        )
    docs = _vec_table(rows)
    queries = _vec_table([("q", centers[3])])
    from pathway_tpu.stdlib.indexing import DataIndex, IvfKnn

    inner = IvfKnn(
        docs.vec, dimensions=16, min_train=256, n_clusters=8, n_probe=2
    )
    index = DataIndex(docs, inner)
    result = index.query_as_of_now(queries.vec, number_of_matches=5).select(
        names=pw.right.name
    )
    _keys, cols = table_to_dicts(result)
    names = list(cols["names"].values())[0]
    assert len(names) == 5
    # every match must come from cluster 3
    assert all(int(n[1:]) % 8 == 3 for n in names), names


def test_ivf_recall_at_scale():
    """300k clustered vectors, direct index object: recall@10 vs exact
    brute force >= 0.95, probing only ~sqrt(C) of the lists."""
    rng = np.random.default_rng(1)
    n, dim, n_centers = 300_000, 16, 64
    centers = rng.normal(size=(n_centers, dim)).astype(np.float32) * 3
    assign = rng.integers(0, n_centers, size=n)
    data = centers[assign] + rng.normal(size=(n, dim)).astype(np.float32) * 0.3
    index = IvfKnnIndex(dimensions=dim, metric="cosine", min_train=4096)
    for i in range(n):
        index.upsert(i, data[i], None)
    queries = data[rng.choice(n, size=50, replace=False)]
    res = index.search([(q, 10, None) for q in queries])
    assert index.centroids is not None, "index never trained"
    # exact reference
    dn = data / np.linalg.norm(data, axis=1, keepdims=True)
    hits = total = 0
    for qi, q in enumerate(queries):
        qn = q / np.linalg.norm(q)
        sims = dn @ qn
        exact = set(np.argpartition(-sims, 10)[:10].tolist())
        got = {k for k, _s in res[qi]}
        hits += len(exact & got)
        total += 10
    recall = hits / total
    assert recall >= 0.95, recall


def test_ivf_remove_and_update():
    index = IvfKnnIndex(dimensions=2, metric="cosine", min_train=10**9)
    index.upsert(1, [1.0, 0.0], None)
    index.upsert(2, [0.0, 1.0], None)
    res = index.search([([1.0, 0.0], 1, None)])
    assert res[0][0][0] == 1
    index.remove(1)
    res = index.search([([1.0, 0.0], 1, None)])
    assert res[0][0][0] == 2
    index.upsert(2, [1.0, 0.0], None)  # move key 2
    res = index.search([([1.0, 0.0], 1, None)])
    assert res[0][0][0] == 2 and res[0][0][1] > -1e-6


def test_ivf_snapshot_roundtrip():
    rng = np.random.default_rng(2)
    index = IvfKnnIndex(dimensions=4, metric="cosine", min_train=32)
    for i in range(64):
        index.upsert(i, rng.normal(size=4).astype(np.float32), None)
    index.search([(rng.normal(size=4).astype(np.float32), 3, None)])
    state = index.state_dict()
    import pickle

    restored = IvfKnnIndex(dimensions=4, metric="cosine", min_train=32)
    restored.load_state(pickle.loads(pickle.dumps(state)))
    q = rng.normal(size=4).astype(np.float32)
    assert index.search([(q, 5, None)]) == restored.search([(q, 5, None)])


def test_ivf_device_index_recall_and_speed():
    """IvfDeviceIndex (cluster-sorted device corpus, spilled assignment,
    bucketed fine scoring) reaches >=0.95 recall@10 on mixture data — the
    shape real embedding corpora have (reference ANN tier: usearch HNSW,
    src/external_integration/usearch_integration.rs:20)."""
    import numpy as np

    from pathway_tpu.ops.ivf import IvfDeviceIndex

    rng = np.random.default_rng(0)
    n, dim, k = 20_000, 64, 10
    centers = rng.normal(size=(200, dim)).astype(np.float32)
    asn = rng.integers(0, 200, size=n)
    corpus = (centers[asn] + 0.35 * rng.normal(size=(n, dim))).astype(
        np.float32
    )
    ix = IvfDeviceIndex(corpus, n_probe=16, spill=2)
    cn = corpus / np.linalg.norm(corpus, axis=1, keepdims=True)
    qs = corpus[rng.choice(n, 10)] + 0.1 * rng.normal(
        size=(10, dim)
    ).astype(np.float32)
    hits = 0
    for q in qs:
        _s, ids = ix.query(q, k)
        assert len(set(ids.tolist())) == k  # spilled replicas deduped
        qn = q / np.linalg.norm(q)
        exact = np.argpartition(-(cn @ qn), k - 1)[:k]
        hits += len(set(ids.tolist()) & set(exact.tolist()))
    assert hits / (10 * k) >= 0.95
