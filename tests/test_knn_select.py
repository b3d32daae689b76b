"""The selecting first stage of the exact top-k (`ops/knn._blockmax_topk`):
the same answer as one whole-row `lax.top_k`, ids and all, at the shapes
that take it; the old path where `topk_stage1` keeps it. CPU; what the TPU's
compiler makes of the search program is in `tests/test_trunk_compile.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.ops.knn import (
    SELECT_BLOCK,
    _masked_topk,
    dense_topk_prepared,
    prepare_corpus,
    topk_stage1,
)

_topk = jax.jit(_masked_topk, static_argnames=("k",))


def _same_as_whole_row(scores, k):
    """`_masked_topk` against one `lax.top_k` over the whole row: scores and
    ids, ties included (both give a tie to the lowest row)."""
    got_scores, got_ids = _topk(jnp.asarray(scores), k=k)
    want_scores, want_ids = jax.lax.top_k(jnp.asarray(scores), k)
    np.testing.assert_array_equal(got_scores, want_scores)
    np.testing.assert_array_equal(got_ids, want_ids)


@pytest.mark.parametrize("k", [1, 10, 40])
@pytest.mark.parametrize("b", [1, 8, 32])
@pytest.mark.parametrize("n", [65_536, 131_072, 200_000])  # the last: no multiple of any block
def test_selection_is_the_whole_row_topk(n, b, k):
    assert topk_stage1(n, k) == "blockmax"
    scores = np.random.default_rng(n + b + k).standard_normal((b, n)).astype(np.float32)
    _same_as_whole_row(scores, k)


@pytest.mark.parametrize("b", [3, 12])  # off the ladder: not a whole number of 8-query tiles
def test_selection_at_a_batch_off_the_ladder(b):
    scores = np.random.default_rng(b).standard_normal((b, 50_000)).astype(np.float32)
    _same_as_whole_row(scores, 10)


@pytest.mark.parametrize("placement", ["one_block", "a_block_each", "block_edges"])
def test_selection_wherever_the_best_rows_lie(placement):
    n, k, w = 32_768, 10, SELECT_BLOCK
    rng = np.random.default_rng(5)
    scores = rng.standard_normal((4, n)).astype(np.float32)
    best = 10.0 + rng.permutation(k).astype(np.float32)
    columns = {
        "one_block": 7 * w + 3 + np.arange(k),  # all k inside block 7
        "a_block_each": (np.arange(k) * 23 + 1) * w + 17,  # one in each of k blocks
        "block_edges": np.r_[np.arange(5) * w, np.arange(5) * w + w - 1] + 40 * w,
    }[placement]
    scores[:, columns] = best
    _same_as_whole_row(scores, k)
    _, ids = _topk(jnp.asarray(scores), k=k)
    assert set(np.asarray(ids)[0]) == set(columns)


@pytest.mark.parametrize("above", [0, 4, 9])  # scores strictly above the tied value
def test_ties_across_the_kth_place(above):
    n, k = 40_000, 10
    rng = np.random.default_rng(above)
    scores = rng.uniform(-1.0, 0.0, (2, n)).astype(np.float32)
    tied = rng.choice(n, 3 * k, replace=False)  # a row repeated 3k times
    scores[:, tied] = 0.5
    scores[:, rng.choice(np.setdiff1d(np.arange(n), tied), above, replace=False)] = 0.75
    got_scores, got_ids = _topk(jnp.asarray(scores), k=k)
    want_scores, _ = jax.lax.top_k(jnp.asarray(scores), k)
    np.testing.assert_array_equal(got_scores, want_scores)  # the same multiset, in order
    for row, ids in zip(scores, np.asarray(got_ids)):
        assert len(set(ids)) == k and (row[ids] >= 0.5).all()
    _same_as_whole_row(scores, k)  # and the tie goes to the lowest rows


@pytest.mark.parametrize("valid_rows", [0, 3, 10, 200, 20_000])
def test_masked_rows_through_the_search_program(valid_rows):
    # 200 valid rows are fewer than the k * SELECT_BLOCK candidates, 10 are
    # exactly k, 3 and 0 leave places that only -inf can fill
    n, d, k = 20_480, 16, 10
    assert topk_stage1(n, k) == "blockmax"
    rng = np.random.default_rng(valid_rows)
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    queries = rng.standard_normal((8, d)).astype(np.float32)
    valid = np.zeros(n, bool)
    valid[rng.choice(n, valid_rows, replace=False)] = True
    prep, c2 = prepare_corpus(jnp.asarray(corpus), "cosine", bf16=False)
    scores, ids = dense_topk_prepared(
        jnp.asarray(queries), prep, c2, jnp.asarray(valid), k, metric="cosine", bf16=False
    )
    scores, ids = np.asarray(scores), np.asarray(ids)
    found = min(k, valid_rows)
    assert np.isfinite(scores[:, :found]).all() and (scores[:, found:] == -np.inf).all()
    assert (ids[:, found:] == -1).all()  # -1 only where the score is -inf
    assert valid[ids[:, :found]].all()  # a masked row is never returned
    unit = corpus / np.linalg.norm(corpus, axis=1, keepdims=True)
    cosine = (queries / np.linalg.norm(queries, axis=1, keepdims=True)) @ unit.T
    want = np.sort(np.where(valid, cosine, -np.inf), axis=1)[:, ::-1][:, :found]
    np.testing.assert_allclose(scores[:, :found], want, atol=1e-5)


@pytest.mark.parametrize(
    "n, k, stage1",
    [
        (2_097_152, 10, "blockmax"),  # the retrieve cell
        (2_097_152, 40, "blockmax"),  # 4 x k under a metadata filter
        (786_432, 10, "blockmax"),  # the ingest cells' one-query probe
        (65_536, 10, "blockmax"),
        (10_240, 10, "blockmax"),  # the edge: 8 * k * SELECT_BLOCK == n
        (10_239, 10, "sort"),
        (1_024, 2, "sort"),  # small n
        (65_536, 1_024, "sort"),  # the largest k `supported()` admits
        (2_097_152, 1_024, "blockmax"),
        (2_097_152, 4_096, "sort"),
    ],
)
def test_the_first_stage_follows_from_the_shapes(n, k, stage1):
    assert topk_stage1(n, k) == stage1


@pytest.mark.parametrize("n, k", [(10_239, 10), (65_536, 100), (70_000, 128), (4_096, 64)])
def test_the_old_path_is_kept_where_selection_does_not_pay(n, k):
    assert topk_stage1(n, k) == "sort"
    scores = np.random.default_rng(n).standard_normal((3, n)).astype(np.float32)
    _same_as_whole_row(scores, k)
