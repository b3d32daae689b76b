"""Pallas KNN kernel: exactness vs the XLA scoring path (interpret mode on
the CPU backend; the driver bench compares both compiled on TPU).
Reference: src/external_integration/brute_force_knn_integration.rs:22."""

import numpy as np
import pytest


def _random_corpus(n, d, seed=0):
    rng = np.random.default_rng(seed)
    corpus = rng.normal(size=(n, d)).astype(np.float32)
    valid = np.ones(n, dtype=bool)
    valid[n // 3] = False  # a deleted slot must never be returned
    return corpus, valid


def test_pallas_dense_topk_matches_xla():
    import jax.numpy as jnp

    from pathway_tpu.ops import pallas_topk as pt
    from pathway_tpu.ops.knn import dense_topk_prepared, prepare_corpus

    n, d, k = 2048, 64, 7
    corpus, valid = _random_corpus(n, d)
    queries = np.random.default_rng(1).normal(size=(5, d)).astype(np.float32)

    prep, c2 = prepare_corpus(jnp.asarray(corpus), "cosine")
    s_ref, i_ref = dense_topk_prepared(
        jnp.asarray(queries), prep, c2, jnp.asarray(valid), k, metric="cosine"
    )
    s_pl, i_pl = pt.pallas_dense_topk(
        jnp.asarray(queries),
        prep,
        jnp.asarray(valid),
        k,
        metric="cosine",
        interpret=True,
    )
    assert (np.asarray(i_ref) == np.asarray(i_pl)).all()
    assert np.allclose(np.asarray(s_ref), np.asarray(s_pl), atol=1e-6)
    assert (np.asarray(i_pl) != n // 3).all()


def test_index_pallas_kernel_matches_xla():
    from pathway_tpu.stdlib.indexing._index_impls import TpuDenseKnnIndex

    rng = np.random.default_rng(2)
    vecs = rng.normal(size=(300, 16)).astype(np.float32)

    def build(kernel):
        ix = TpuDenseKnnIndex(
            dimensions=16, reserved_space=1024, kernel=kernel
        )
        for i in range(len(vecs)):
            ix.upsert(i, vecs[i], None)
        ix.remove(123)
        return ix

    queries = [(vecs[7], 5, None), (vecs[123], 5, None)]
    res_x = build("xla").search(queries)
    res_p = build("pallas").search(queries)
    for rx, rp in zip(res_x, res_p):
        assert [r[0] for r in rx] == [r[0] for r in rp]
        assert np.allclose(
            [r[1] for r in rx], [r[1] for r in rp], atol=1e-6
        )
    assert res_p[0][0][0] == 7
    assert all(r[0] != 123 for r in res_p[1])


def test_pallas_padded_k10_interpret_matches_xla():
    """Run the PADDED kernel at k=10
    (k not lane-aligned; KP pads to 128 and the caller slices back) — in
    interpret mode, so the pad+slice arithmetic is verified on CPU even
    while the TPU backend is unavailable.  Scores in the padding lanes
    must never leak into the merged top-k."""
    import jax.numpy as jnp

    from pathway_tpu.ops import pallas_topk as pt
    from pathway_tpu.ops.knn import dense_topk_prepared, prepare_corpus

    n, d, k = 2048, 32, 10
    assert pt._kpad(k) == 128 and pt._kpad(k) != k  # genuinely padded
    # lane-boundary pins: exactly-aligned k pads to itself, one past the
    # boundary jumps a full lane width (a k emitted UNpadded does not
    # lower — these keep the ladder honest at its edges)
    assert pt._kpad(1) == 128
    assert pt._kpad(128) == 128
    assert pt._kpad(129) == 256
    corpus, valid = _random_corpus(n, d, seed=5)
    queries = np.random.default_rng(6).normal(size=(3, d)).astype(np.float32)
    prep, c2 = prepare_corpus(jnp.asarray(corpus), "cosine")
    s_ref, i_ref = dense_topk_prepared(
        jnp.asarray(queries), prep, c2, jnp.asarray(valid), k, metric="cosine"
    )
    s_pl, i_pl = pt.pallas_dense_topk(
        jnp.asarray(queries),
        prep,
        jnp.asarray(valid),
        k,
        metric="cosine",
        interpret=True,
    )
    assert s_pl.shape == (3, k) and i_pl.shape == (3, k)
    assert (np.asarray(i_ref) == np.asarray(i_pl)).all()
    assert np.allclose(np.asarray(s_ref), np.asarray(s_pl), atol=1e-6)
    # block-level: per-block candidate tiles slice the KP padding away
    sc, ix = pt.pallas_block_topk(
        jnp.asarray(queries).astype(prep.dtype), prep, jnp.asarray(valid),
        k, interpret=True,
    )
    assert sc.shape == (3, n // pt.BLK, k)
    assert np.isfinite(np.asarray(sc)[:, :, 0]).all()
    # and the lowering gate accepts the padded layout for this shape
    pt.validate_lowering(bq=3, d=d, n=n, k=k)


def test_tpu_lowering_shape_gate():
    """Compiled-mode gate: every block spec the kernel
    will emit for the bench shapes must satisfy the Mosaic TPU rule (last
    two block dims divisible by (8, 128) or equal to the array dims), so a
    kernel that cannot lower on hardware fails the suite even on the CPU
    backend. The rule: green with interpret=True says nothing about
    lowering; a (1, 1, k) block passes there and fails on a TPU."""
    from pathway_tpu.ops import pallas_topk as pt

    # bench shape (1M-row corpus, single query), batched queries, k > 128
    pt.validate_lowering(bq=1, d=384, n=977 * 1024, k=10)
    pt.validate_lowering(bq=16, d=384, n=64 * 1024, k=10)
    pt.validate_lowering(bq=7, d=128, n=2048, k=130)

    # the rule-checker itself must reject an unpadded k tile:
    # block (1, 1, 10) over array (1, 977, 10) — middle dim 1 vs 977
    with pytest.raises(ValueError):
        pt.check_tpu_block_rules((1, 1, 10), (1, 977, 10))
    # and a lane dim neither 128-aligned nor equal to the array's
    with pytest.raises(ValueError):
        pt.check_tpu_block_rules((8, 10), (8, 2048))


def test_kernel_env_var_and_validation(monkeypatch):
    from pathway_tpu.stdlib.indexing._index_impls import TpuDenseKnnIndex

    monkeypatch.setenv("PATHWAY_KNN_KERNEL", "pallas")
    assert TpuDenseKnnIndex(dimensions=4).kernel == "pallas"
    monkeypatch.delenv("PATHWAY_KNN_KERNEL")
    assert TpuDenseKnnIndex(dimensions=4).kernel == "xla"
    with pytest.raises(ValueError):
        TpuDenseKnnIndex(dimensions=4, kernel="cuda")
