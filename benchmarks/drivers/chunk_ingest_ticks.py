"""Ingest ticks of split-document chunks through a trunk embedder, closed loop.

The tick is ``ingest_ticks``'s own (embed a tick's chunks, upsert them, probe
with one of them), its window, end-to-end metric, counts and sample likewise:
they are imported, not copied. Its own are the set-up (the embedder is built
with ``trunk=`` and given the benchmark's seeded weights,
``harness/sut_trunk.py``) and the comparison that decides ``correct``
(``harness/reference_trunk.py`` in the encoder reference's place).

What is compared, beside ``check.py``'s numbers, each the **widest** reading:

* A sparse layer is not continuous in its input: where a token's 4th and 5th
  corrected router scores nearly tie, bfloat16 rounding upstream sends it to
  another expert than float32 arithmetic would, and either expert is a sound
  answer, as either of two tied rows is a sound top-k. So the routing and the
  arithmetic are judged apart. Before the program's state is freed, the
  sampled texts are forwarded once more through the timed path's own compiled
  program, which also hands out the experts each token went to
  (``replay_err``: the widest distance between a vector served in the window
  and its replay; the replay is the served forward or it is nothing).
* ``route_gap``: over every real token of the sampled texts and every expert
  layer, how far the reference's corrected score of an expert the program
  chose lies below the reference's own 4th best: ``topk_gap`` for the router.
  0 where both chose the same four.
* ``vec_err``, ``e2e_gap``: as in ``check.py``, the reference *following the
  program's choice of experts*: what is left is arithmetic.

The control is the reference at fp8 following the same choice: its vectors
in the served ones' place, and the experts *it* would have chosen, judged by
the float32 reference's scores, in the program's choice's place.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmarks.drivers import common, ingest_ticks
from benchmarks.drivers.common import one_per_class, served_arrays
from benchmarks.drivers.ingest_ticks import counts, end_to_end, sample  # noqa: F401
from benchmarks.harness import check as checks
from benchmarks.harness import reference_trunk, sut, sut_trunk
from benchmarks.harness.traffic import TickStream


def setup(run) -> dict:
    cfg = run.config
    embedder = sut_trunk.build_embedder(cfg, run.cell.config_name)
    run.phase("embedder")
    params = sut_trunk.seed_weights(embedder, run.seed)
    run.phase("weights")
    index = sut.build_index(cfg)
    rows, dim = int(cfg["index"]["rows_resident"]), int(cfg["index"]["dimensions"])
    corpus = sut.make_corpus(rows, dim, run.seed)
    run.phase("corpus")
    sut.load_corpus(index, corpus)
    run.phase("load")
    ticks = TickStream(run.traffic, run.seed)
    run.phase("traffic")
    k = int(cfg["index"]["k"])
    first = ticks.first_pass()
    free = int(cfg["index"]["reserved_space"]) - rows
    max_ticks = free // max(len(t) for t in first)  # the index never grows
    probes = np.random.default_rng(np.random.SeedSequence([run.seed, 5])).integers(
        0, 2**31, size=max_ticks
    )
    # warm: each class of chunk batch, each class a probe text can be, one refresh
    for texts in one_per_class(first):
        embedder._embed_batch(texts)
    for texts in one_per_class([[text] for tick in first for text in tick]):
        vector = embedder._embed_batch(texts)[0]
    index.search([(vector, k, None)])  # uploads and prepares
    index.upsert(0, corpus[0], None)  # an unchanged row: a refresh, no new key
    index.search([(vector, k, None)])
    run.phase("warm")
    return {
        "embedder": embedder, "index": index, "params": params, "corpus": corpus,
        "ticks": ticks, "k": k, "max_ticks": max_ticks, "probes": probes, "next_key": rows,
    }


def window(run, state):
    records = ingest_ticks.window(run, state)
    state["window"] = (run, records)  # ``release`` replays the sampled forwards from it
    return records


def sampled(run, records):
    """The texts the reference sees and the vectors served for them: every
    chunk of the sampled ticks, then every probe of the window."""
    picked = sample(run, records)
    texts = [t for r in picked for t in r.texts] + [r.texts[r.probe] for r in records]
    vectors = np.stack([v for r in picked for v in r.vectors] + [r.probe_vector for r in records])
    return picked, texts, vectors


def release(state) -> None:
    """Before the program's state is freed: the sampled texts once more
    through the embedder, batch by batch as the window forwarded them, for
    the router's choices. Set-up and window are over; nothing here is timed."""
    embedder = state.get("embedder")
    if embedder is not None and "window" in state:
        run, records = state.pop("window")
        max_len = int(run.config["embedder"]["max_len"])
        batches = [r.texts for r in sample(run, records)] + [[r.texts[r.probe]] for r in records]
        vectors, choices = [], []
        for texts in batches:
            again, choice = sut_trunk.forward_again(embedder, texts)
            vectors.append(again)
            pad = max_len - choice.shape[2]
            choices.append(np.pad(choice, ((0, 0), (0, 0), (0, pad), (0, 0)), constant_values=-1))
        state["replay"] = (np.concatenate(vectors), np.concatenate(choices, axis=1))
    common.release(state)


def reference_vectors(run, params, texts, forced, mode="f32"):
    cfg = run.config
    return reference_trunk.embed(
        params, texts, cfg, max_len=int(cfg["embedder"]["max_len"]), mode=mode, forced=forced
    )


def top_choice(scores, k: int):
    """The k experts of the highest scores, [..., k]."""
    return np.argpartition(scores, -k, axis=-1)[..., -k:]


def route_numbers(choice, scores, k: int) -> dict:
    """``route_gap`` of ``choice`` [layers, n, positions, k] under the
    reference's corrected scores [layers, n, positions, E] (NaN where a text
    has no token). A real token sent nowhere is charged a whole score."""
    if not scores.size:  # a trunk without an expert layer
        return {"route_gap": 0.0}
    real = ~np.isnan(scores[..., 0])
    clean = np.where(real[..., None], scores, 0.0)
    kth = np.partition(clean, -k, axis=-1)[..., -k]
    followed = np.take_along_axis(clean, np.maximum(choice, 0), axis=-1)
    lowest = np.where(choice >= 0, followed, kth[..., None] - 1.0).min(axis=-1)
    gap = np.where(real, kth - lowest, 0.0)
    own = np.sort(top_choice(clean, k), axis=-1)
    other = (np.sort(choice, axis=-1) != own).any(axis=-1) & real
    print(
        f"router: {int(real.sum())} real tokens x layers, {int(other.sum())} "
        f"({100 * other.sum() / max(real.sum(), 1):.3f}%) sent to other experts than the "
        f"reference's own choice; texts with one at their last token: "
        f"{int(last_token(other, real).any(axis=0).sum())} of {real.shape[1]}; "
        f"widest route_gap {gap.max():.5f}, by layer {[round(float(g), 5) for g in gap.max(axis=(1, 2))]}",
        file=sys.stderr,
    )
    return {"route_gap": float(gap.max())}


def last_token(flags, real):
    """``flags`` [layers, n, positions] at each text's last real position: [layers, n]."""
    last = np.maximum(real[0].sum(axis=1) - 1, 0)
    return np.take_along_axis(flags, last[None, :, None], axis=2)[..., 0]


def encoder_numbers(want, shown) -> dict:
    err = np.linalg.norm(np.asarray(shown, np.float64) - want, axis=1)
    quartiles = np.percentile(err, [25, 50, 75]).round(4).tolist()
    print(
        f"vectors against the reference following the program's experts: {len(err)}; "
        f"quartiles {quartiles}, widest {err.max():.4f}",
        file=sys.stderr,
    )
    return {"vec_err": float(err.max())}


def check_numbers(run, state, records, control=False) -> dict:
    k, corpus, params = state["k"], state["corpus"], state["params"]
    experts_a_token = int(run.config["num_experts_per_tok"])
    rows0, per_tick = len(corpus), len(records[0].texts)
    picked, texts, vectors = sampled(run, records)
    again, choice = state["replay"]

    # the encoder: the reference follows the program's experts, so the vectors
    # differ by arithmetic; the choice itself is judged by the reference's scores
    if "reference" not in state:
        state["reference"] = reference_vectors(run, params, texts, choice)
    want, scores = state["reference"]
    if control:
        shown, scores_fp8 = reference_vectors(run, params, texts, choice, "fp8")
        judged = top_choice(np.nan_to_num(scores_fp8), experts_a_token)
    else:
        shown, judged = vectors, choice
    numbers = encoder_numbers(want, shown)
    numbers.update(route_numbers(judged, scores, experts_a_token))
    if not control:
        numbers["replay_err"] = float(np.linalg.norm(again.astype(np.float64) - vectors, axis=1).max())

    # the index: every probe of the window against the rows it could see
    rows_host = np.concatenate(
        [corpus] + [np.stack(r.vectors) for r in records]
    ).astype(np.float32, copy=False)
    row_tick = np.concatenate(
        [np.full(rows0, -1, np.int32)]
        + [np.full(len(r.vectors), r.tick, np.int32) for r in records]
    )
    query_tick = np.array([r.tick for r in records], np.int32)
    ids, scores_served = served_arrays([r.hits[0] if r.hits else () for r in records], k)
    own = np.array([rows0 + r.tick * per_tick + r.probe for r in records])
    numbers.update(
        checks.index_numbers(
            np.stack([r.probe_vector for r in records]), query_tick, rows_host, row_tick,
            ids, scores_served, k, control,
        )
    )
    if not control:
        numbers["stale_probes"] = int((ids != own[:, None]).all(axis=1).sum())

    # both layers at once: every probe asked with the reference's own vector
    # of its text, over the reference's own rows for the sampled ticks
    probes_at = sum(len(r.texts) for r in picked)
    at = 0
    for r in picked:
        first = rows0 + r.tick * per_tick
        rows_host[first : first + len(r.texts)] = want[at : at + len(r.texts)]
        at += len(r.texts)
    numbers.update(
        checks.cross_numbers(
            want[probes_at:], query_tick, rows_host, row_tick, ids, k,
            shown[probes_at:] if control else None,
        )
    )
    return numbers


def check(run, state, records) -> dict:
    release(state)
    return check_numbers(run, state, records)
