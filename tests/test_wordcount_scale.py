"""5M-row streaming wordcount with retractions through the engine
(reference scale proxy:
integration_tests/wordcount/base.py — 5M-line wordcount CI run)."""

from __future__ import annotations

import time

import numpy as np

from pathway_tpu.engine.batch import DiffBatch
from pathway_tpu.engine.nodes import GroupByNode, InputNode, OutputNode
from pathway_tpu.engine.reducers import ReducerSpec
from pathway_tpu.engine.runtime import Runtime, StaticSource


def test_wordcount_5m_rows_with_retractions():
    n = 5_000_000
    n_vocab = 10_000
    tick_rows = 100_000
    vocab = np.array([f"word{i}" for i in range(n_vocab)])
    rng = np.random.default_rng(0)
    idx = rng.integers(0, n_vocab, size=n)
    words = vocab[idx]
    keys = np.arange(n, dtype=np.uint64)

    batches = []
    for lo in range(0, n, tick_rows):
        hi = min(n, lo + tick_rows)
        batches.append(
            DiffBatch(
                keys=keys[lo:hi],
                diffs=np.ones(hi - lo, np.int64),
                columns={"word": words[lo:hi]},
            )
        )
    # 2% retractions of rows already ingested, arriving as the final tick
    retr = rng.choice(n // 2, size=n // 50, replace=False).astype(np.uint64)
    batches.append(
        DiffBatch(
            keys=retr,
            diffs=-np.ones(len(retr), np.int64),
            columns={"word": words[retr]},
        )
    )

    class Src(StaticSource):
        def events(self):
            for i, b in enumerate(batches):
                yield i, b

    inp = InputNode(Src(["word"]), ["word"])
    gb = GroupByNode(
        inp, ["word"], {"count": ReducerSpec(kind="count", arg_cols=())}
    )
    final: dict = {}

    def on_batch(t, b):
        for k, d, vals in b.iter_rows():
            if d > 0:
                final[vals[0]] = vals[1]
            elif final.get(vals[0]) == vals[1]:
                del final[vals[0]]

    out = OutputNode(gb, on_batch)
    rt = Runtime([out])
    t0 = time.perf_counter()
    rt.run()
    dt = time.perf_counter() - t0

    # exact expected counts: inserts minus retractions, per word
    expected = np.bincount(idx, minlength=n_vocab)
    np.subtract.at(expected, idx[retr], 1)
    got = np.zeros(n_vocab, np.int64)
    for w, c in final.items():
        got[int(str(w)[4:])] = c
    assert (got == expected).all()
    rows = n + len(retr)
    # engine-throughput floor: even this 1-core dev box does >500k rows/s;
    # a regression to the per-row path would show up as a 6x drop
    assert rows / dt > 250_000, f"wordcount too slow: {rows / dt:,.0f} rows/s"
