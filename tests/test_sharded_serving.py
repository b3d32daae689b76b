"""Shard Harbor tests — replica×shard scatter-gather serving and the
standby-writer takeover path.

Covers the acceptance bars in-process and fast (tier-1):

* property: sharded scatter-gather merged top-k equals the unsharded
  top-k over random corpora — ties, deletions mid-stream, and
  per-shard staleness skew included;
* torn shard assignment maps rejected at BOOT (router map validation +
  replica shard bounds + stream-level shard-count fencing);
* 2-shard scatter-gather through the real writer→replica→router path,
  partial-shard outage naming the missing shards;
* writer-kill → standby takeover handoff with incarnation fencing of a
  zombie primary.

The multi-process kill/restart matrix is ``tests/test_distributed.py``'s.
"""

import json
import threading
import time

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _repl_env(monkeypatch):
    monkeypatch.setenv("PATHWAY_DCN_SECRET", "shard-harbor-test-secret")
    monkeypatch.delenv("PATHWAY_SERVING_SHARDS", raising=False)
    monkeypatch.delenv("PATHWAY_SERVING_SHARD_MAP", raising=False)
    monkeypatch.delenv("PATHWAY_MESH_INCARNATION", raising=False)
    from pathway_tpu.parallel import replicate

    yield
    replicate.reset_publisher()


def _wait(pred, timeout=15.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class ToyIndex:
    """Dict-backed index for non-vector payloads (takeover smoke)."""

    def __init__(self):
        self.d: dict[int, tuple] = {}

    def keys(self):
        return list(self.d.keys())

    def upsert(self, key, data, meta):
        self.d[int(key)] = (data, meta)

    def remove(self, key):
        self.d.pop(int(key), None)

    def search(self, triples):
        return [
            tuple((key, 1.0) for key in sorted(self.d)[: int(k)])
            for _q, k, _f in triples
        ]


class ToyVecIndex:
    """Brute-force vector index with the DETERMINISTIC (score desc,
    key asc) tie-break — the same rule merge_topk applies, so sharded
    and unsharded answers are bit-comparable."""

    def __init__(self):
        self.d: dict[int, np.ndarray] = {}

    def keys(self):
        return list(self.d.keys())

    def upsert(self, key, data, meta):
        self.d[int(key)] = np.asarray(data, dtype=np.float32)

    def remove(self, key):
        self.d.pop(int(key), None)

    def search(self, triples):
        out = []
        for q, k, _f in triples:
            qv = np.asarray(q, dtype=np.float32)
            scored = [
                (key, float(qv @ vec)) for key, vec in self.d.items()
            ]
            scored.sort(key=lambda m: (-m[1], m[0]))
            out.append(tuple(scored[: int(k)]))
        return out


def _batch(rows):
    from pathway_tpu.engine.batch import DiffBatch

    return DiffBatch.from_rows(rows, ("_data", "_meta"))


# ---------------------------------------------------------------------------
# merge + map validation (pure units)


def test_merge_topk_equals_brute_force_property():
    from pathway_tpu.serving.router import merge_topk

    rng = np.random.default_rng(7)
    for _trial in range(50):
        n_shards = int(rng.integers(1, 5))
        k = int(rng.integers(1, 8))
        # duplicate scores on purpose: ties must break by key
        pool = [
            [int(key), float(score)]
            for key, score in zip(
                rng.choice(10_000, size=40, replace=False),
                rng.choice([0.1, 0.5, 0.5, 0.9], size=40),
            )
        ]
        shards = [pool[s::n_shards] for s in range(n_shards)]
        per_shard_topk = [
            sorted(s, key=lambda m: (-m[1], m[0]))[:k] for s in shards
        ]
        expect = sorted(pool, key=lambda m: (-m[1], m[0]))[:k]
        assert merge_topk(per_shard_topk, k) == expect


def test_shard_map_validation_rejects_torn_maps(monkeypatch):
    from pathway_tpu.serving.router import (
        FailoverRouter,
        shard_map_from_env,
        validate_shard_map,
    )

    with pytest.raises(ValueError, match="no members"):
        validate_shard_map([["http://a"], []])
    with pytest.raises(ValueError, match="listed in shard"):
        validate_shard_map([["http://a"], ["http://a"]])
    with pytest.raises(ValueError, match="empty"):
        validate_shard_map([])
    # the same rejection through the constructor and the env
    with pytest.raises(ValueError, match="listed in shard"):
        FailoverRouter(shards=[["http://a"], ["http://b", "http://a"]])
    monkeypatch.setenv(
        "PATHWAY_SERVING_SHARD_MAP", "http://a|http://b|"
    )
    with pytest.raises(ValueError, match="no members"):
        shard_map_from_env()


def test_replica_rejects_torn_shard_assignment_at_boot():
    from pathway_tpu.serving.replica import ReplicaServer

    with pytest.raises(ValueError, match="torn shard"):
        ReplicaServer(
            replica_id=0, index_factory=ToyVecIndex, shard=5, n_shards=3
        )
    with pytest.raises(ValueError, match="torn shard"):
        # sharded plane with NO shard assignment
        ReplicaServer(
            replica_id=0, index_factory=ToyVecIndex, shard=-1, n_shards=3
        )


# ---------------------------------------------------------------------------
# property: sharded == unsharded over random corpora


def _apply_ops(index, ops):
    for key, diff, vec in ops:
        if diff > 0:
            index.upsert(key, vec, None)
        else:
            index.remove(key)


def test_scatter_gather_property_random_corpora():
    """Random insert/delete streams with forced score ties: per-shard
    top-k merged with merge_topk is bit-equal to the unsharded index's
    top-k — including per-shard STALENESS SKEW (one shard applied only
    a prefix of its stream; the reference is the union of exactly what
    each shard applied, well-defined because shards own disjoint
    keys)."""
    from pathway_tpu.parallel.replicate import corpus_shard_of
    from pathway_tpu.serving.router import merge_topk

    rng = np.random.default_rng(42)
    DIM = 6
    for trial in range(8):
        n_shards = int(rng.integers(2, 5))
        # a small vector vocabulary FORCES exact-score ties
        vocab = rng.standard_normal((4, DIM)).astype(np.float32)
        live: set[int] = set()
        ops: list[tuple[int, int, np.ndarray | None]] = []
        for _ in range(200):
            if live and rng.random() < 0.3:
                key = int(rng.choice(list(live)))
                live.discard(key)
                ops.append((key, -1, None))
            else:
                key = int(rng.integers(0, 500))
                live.add(key)
                ops.append((key, 1, vocab[int(rng.integers(0, 4))]))
        # per-shard streams (the writer's split), then a skew point per
        # shard: shard s applies only its first skew[s] ops
        shard_ops: list[list] = [[] for _ in range(n_shards)]
        for op in ops:
            s = int(corpus_shard_of([op[0]], n_shards)[0])
            shard_ops[s].append(op)
        skew = [
            int(rng.integers(len(so) // 2, len(so) + 1)) if so else 0
            for so in shard_ops
        ]
        shard_indexes = [ToyVecIndex() for _ in range(n_shards)]
        reference = ToyVecIndex()
        for s in range(n_shards):
            applied = shard_ops[s][: skew[s]]
            _apply_ops(shard_indexes[s], applied)
            _apply_ops(reference, applied)
        for qi in range(5):
            q = vocab[qi % 4] + (
                0 if qi < 4 else rng.standard_normal(DIM).astype(np.float32)
            )
            k = int(rng.integers(1, 9))
            per_shard = [
                [[key, score] for key, score in idx.search([(q, k, None)])[0]]
                for idx in shard_indexes
            ]
            merged = merge_topk(per_shard, k)
            expect = [
                [key, score]
                for key, score in reference.search([(q, k, None)])[0]
            ]
            assert merged == expect, (trial, qi, merged, expect)


# ---------------------------------------------------------------------------
# sharded delta-stream fan-out


def test_sharded_fanout_delivers_only_owned_keys():
    from pathway_tpu.parallel.replicate import (
        DeltaStreamClient,
        DeltaStreamServer,
        corpus_shard_of,
    )

    srv = DeltaStreamServer(0, n_shards=2)
    seen: dict[int, list] = {0: [], 1: [], -1: []}
    ticks: dict[int, list] = {0: [], 1: [], -1: []}
    clients = []
    for shard in (0, 1, -1):
        cl = DeltaStreamClient(
            "127.0.0.1",
            srv.port,
            # full-corpus subscriptions to a sharded writer are an
            # OBSERVER/standby privilege (negative id) — a replica
            # subscribing unsharded would be fenced as torn
            shard if shard >= 0 else -7,
            from_tick=-1,
            on_deltas=lambda t, bs, shard=shard: (
                ticks[shard].append(t),
                seen[shard].extend(
                    k for b in bs for k, _d, _v in b.iter_rows()
                ),
            ),
            shard=shard,
            expect_shards=2 if shard >= 0 else 0,
        )
        cl.start()
        clients.append(cl)
    try:
        keys = list(range(40))
        srv.publish(0, [_batch([(k, 1, (f"d{k}", None)) for k in keys])])
        srv.publish(1, [])  # idle marker reaches every shard
        assert _wait(
            lambda: all(t and t[-1] == 1 for t in ticks.values())
        ), ticks
        dest = corpus_shard_of(keys, 2)
        for shard in (0, 1):
            expect = {k for k, s in zip(keys, dest) if int(s) == shard}
            assert set(seen[shard]) == expect
        assert set(seen[-1]) == set(keys)  # full-corpus subscriber
        # every subscriber tracks freshness tick-by-tick
        for cl in clients:
            assert cl.applied_tick == 1
    finally:
        for cl in clients:
            cl.close()
        srv.close()


def test_stream_fences_torn_shard_count():
    """A replica expecting S shards against a writer splitting into a
    different count never applies a frame (the torn-map guard at the
    stream level)."""
    from pathway_tpu.parallel.replicate import (
        DeltaStreamClient,
        DeltaStreamServer,
    )

    srv = DeltaStreamServer(0, n_shards=2)
    applied: list[int] = []
    cl = DeltaStreamClient(
        "127.0.0.1",
        srv.port,
        0,
        from_tick=-1,
        on_deltas=lambda t, bs: applied.append(t),
        shard=0,
        expect_shards=3,  # torn: writer says 2
    )
    cl.start()
    try:
        srv.publish(0, [_batch([(1, 1, ("a", None))])])
        assert _wait(lambda: cl.config_error is not None, timeout=10)
        assert "torn shard assignment" in cl.config_error
        time.sleep(0.3)
        assert applied == []
        # an UNSHARDED replica (positive id, no expectation) against a
        # sharded writer is torn too — it would hold the full corpus
        # behind a router that thinks it owns one shard
        cl2 = DeltaStreamClient(
            "127.0.0.1",
            srv.port,
            1,
            from_tick=-1,
            on_deltas=lambda t, bs: applied.append(t),
        )
        cl2.start()
        try:
            assert _wait(lambda: cl2.config_error is not None, timeout=10)
            assert applied == []
        finally:
            cl2.close()
    finally:
        cl.close()
        srv.close()


# ---------------------------------------------------------------------------
# scatter-gather end-to-end: writer -> sharded replicas -> router


def _vec_responder(server, values):
    q = np.asarray(values["vec"], dtype=np.float32)
    res = server.search([(q, int(values.get("k", 3)), None)])[0]
    return {"matches": [[int(k), float(s)] for k, s in res]}


def _start_sharded_plane(n_shards=2, members_per_shard=2):
    from pathway_tpu.parallel.replicate import DeltaStreamServer
    from pathway_tpu.serving.replica import ReplicaServer
    from pathway_tpu.serving.router import FailoverRouter

    srv = DeltaStreamServer(0, n_shards=n_shards)
    reps: list[list] = []
    for shard in range(n_shards):
        members = []
        for i in range(members_per_shard):
            members.append(
                ReplicaServer(
                    replica_id=shard * members_per_shard + i,
                    index_factory=ToyVecIndex,
                    writer_port=srv.port,
                    responder=_vec_responder,
                    shard=shard,
                    n_shards=n_shards,
                ).start()
            )
        reps.append(members)
    router = FailoverRouter(
        shards=[
            [f"http://127.0.0.1:{m.http_port}" for m in members]
            for members in reps
        ],
        health_interval_ms=100,
    ).start()
    return srv, reps, router


def test_router_two_shard_scatter_gather_smoke():
    """Tier-1 scatter-gather smoke (<60 s): a 2-shard × 2-member plane
    answers merged global top-k equal to the unsharded reference; a
    member death inside one shard is retried on the shard sibling; a
    WHOLE shard going dark sheds 503 naming the missing shard for
    bounded reads — never silent truncation."""
    import requests

    from pathway_tpu.parallel.replicate import corpus_shard_of

    srv, reps, router = _start_sharded_plane(2, 2)
    try:
        rng = np.random.default_rng(3)
        vecs = {k: rng.standard_normal(4).astype(np.float32) for k in range(30)}
        srv.publish(
            0, [_batch([(k, 1, (v, None)) for k, v in vecs.items()])]
        )
        # a mid-stream deletion crosses the wire too
        srv.publish(1, [_batch([(5, -1, (None, None))])])
        del vecs[5]
        assert _wait(
            lambda: all(m.ready for ms in reps for m in ms), timeout=20
        )
        assert _wait(
            lambda: all(ep.ready for ep in router.endpoints), timeout=10
        )
        # every member holds ONLY its shard's keys (1/S ownership)
        for shard, members in enumerate(reps):
            for m in members:
                owned = set(m.index.keys())
                assert owned, "shard member hydrated nothing"
                assert all(
                    int(corpus_shard_of([k], 2)[0]) == shard for k in owned
                )
        reference = ToyVecIndex()
        for k, v in vecs.items():
            reference.upsert(k, v, None)
        url = f"http://127.0.0.1:{router.port}/query"
        q = rng.standard_normal(4).astype(np.float32)
        r = requests.post(
            url, json={"vec": [float(x) for x in q], "k": 6}, timeout=10
        )
        assert r.status_code == 200, r.text
        assert r.headers["x-pathway-shards"] == "2"
        expect = [
            [k, pytest.approx(s)]
            for k, s in reference.search([(q, 6, None)])[0]
        ]
        assert r.json()["matches"] == expect
        # a CLIENT error surfaces as itself — it must not burn every
        # member and masquerade as a shard outage (404: unknown route)
        r = requests.post(
            f"http://127.0.0.1:{router.port}/nope", json={}, timeout=15
        )
        assert r.status_code == 404
        # member death inside shard 0: the shard sibling answers
        reps[0][0]._http.stop()
        r = requests.post(
            url, json={"vec": [float(x) for x in q], "k": 6}, timeout=15
        )
        assert r.status_code == 200, r.text
        assert r.json()["matches"] == expect
        # WHOLE shard 0 dark: bounded reads shed naming the shard
        reps[0][1]._http.stop()
        assert _wait(
            lambda: all(
                ep.ejected for ep in router.endpoints if ep.shard == 0
            ),
            timeout=15,
        )
        r = requests.post(
            url,
            json={"vec": [float(x) for x in q], "k": 6},
            headers={"x-pathway-max-staleness-ms": "60000"},
            timeout=15,
        )
        assert r.status_code == 503
        assert "Retry-After" in r.headers
        assert r.headers.get("x-pathway-missing-shards") == "0"
        assert "shard" in r.json()["error"]
    finally:
        router.stop()
        for members in reps:
            for m in members:
                m.stop()
        srv.close()


# ---------------------------------------------------------------------------
# standby takeover + zombie fencing


class _InProcWriter:
    """A 'writer role' the in-process takeover respawns: a
    DeltaStreamServer on a FIXED port plus the corpus it republishes
    (the stand-in for the real writer's restore+replay+publish boot)."""

    def __init__(self, port, corpus, incarnation):
        from pathway_tpu.parallel.replicate import DeltaStreamServer

        self.srv = DeltaStreamServer(
            port, incarnation=incarnation, ring_ticks=64
        )
        self.corpus = corpus
        self.tick = 100 * incarnation  # distinct tick ranges per life
        self.srv.set_floor(-1 if incarnation == 0 else self.tick - 1)
        self.publish_corpus()

    def publish_corpus(self):
        rows = [(k, 1, (v, None)) for k, v in sorted(self.corpus.items())]
        self.srv.publish(self.tick, [_batch(rows)] if rows else [])
        self.tick += 1

    def publish(self, rows):
        for k, d, v in rows:
            if d > 0:
                self.corpus[k] = v[0]
            else:
                self.corpus.pop(k, None)
        self.srv.publish(self.tick, [_batch(rows)])
        self.tick += 1


def test_writer_kill_standby_takeover_smoke():
    """Tier-1 takeover smoke (<60 s): the primary dies mid-stream, the
    standby notices within its grace window, bumps the incarnation and
    resumes publishing on the writer endpoint; the replica reconnects,
    re-converges (idempotent re-applies, zero duplicate rows in the
    folded corpus) and keeps serving with error_served == 0."""
    import requests

    from pathway_tpu.parallel.standby import StandbyWriter
    from pathway_tpu.serving.replica import ReplicaServer
    from pathway_tpu.testing.chaos import free_dcn_port

    port = free_dcn_port(1)
    corpus = {k: f"v{k}" for k in range(6)}
    primary = _InProcWriter(port, dict(corpus), incarnation=0)
    rep = ReplicaServer(
        replica_id=0,
        index_factory=ToyIndex,
        writer_port=port,
        responder=lambda s, v: {
            "corpus": {str(k): str(val[0]) for k, val in _toy_items(s)}
        },
    ).start()

    takeovers: list = []

    def on_takeover(standby):
        new = _InProcWriter(
            port, dict(primary.corpus), standby.next_incarnation()
        )
        takeovers.append(new)

    standby = StandbyWriter(
        "127.0.0.1",
        port,
        on_takeover=on_takeover,
        grace_s=0.6,
        poll_s=0.05,
    ).start()
    try:
        assert _wait(lambda: rep.ready, timeout=15)
        assert _wait(lambda: standby.applied_tick >= 0, timeout=15)
        primary.publish([(6, 1, ("v6", None))])
        assert _wait(lambda: 6 in _toy_keys(rep), timeout=10)
        # primary dies mid-stream
        primary.srv.close()
        assert standby.wait_takeover(timeout=20), standby.events
        assert takeovers, "takeover callback never ran"
        new_writer = takeovers[0]
        assert new_writer.srv.incarnation == 1
        # the replica reconnects to the SAME endpoint, now served by
        # the takeover writer, and re-converges on the full corpus
        assert _wait(
            lambda: rep.health()["writer_incarnation"] == 1, timeout=20
        ), rep.health()
        new_writer.publish([(7, 1, ("v7", None))])
        assert _wait(lambda: 7 in _toy_keys(rep), timeout=15)
        # zero replayed-duplicate rows: the folded corpus matches the
        # writer's exactly (re-applied boundary ticks are idempotent)
        assert _toy_dict(rep) == {
            k: (f"v{k}", None) for k in list(range(8))
        }
        # reads keep answering across the handoff window's tail
        r = requests.post(
            f"http://127.0.0.1:{rep.http_port}/query", json={}, timeout=10
        )
        assert r.status_code == 200
        assert r.json()["corpus"]["7"] == "v7"
        assert rep.health()["fenced_writers"] == 0
    finally:
        standby.stop()
        rep.stop()
        primary.srv.close()
        for w in takeovers:
            w.srv.close()


def _toy_items(server):
    return list(server.index.d.items())


def _toy_keys(rep):
    return set(rep.index.d.keys())


def _toy_dict(rep):
    return dict(rep.index.d)


def test_zombie_primary_is_fenced():
    """After a takeover bumped the incarnation, a zombie primary coming
    back on the old endpoint is rejected at suback time: none of its
    frames ever apply."""
    from pathway_tpu.parallel.replicate import (
        DeltaStreamClient,
        DeltaStreamServer,
    )
    from pathway_tpu.testing.chaos import free_dcn_port

    p1, p2 = free_dcn_port(1), free_dcn_port(1)
    applied: list[tuple[int, list]] = []
    cl = DeltaStreamClient(
        "127.0.0.1",
        p1,
        0,
        from_tick=-1,
        on_deltas=lambda t, bs: applied.append(
            (t, [k for b in bs for k, _d, _v in b.iter_rows()])
        ),
        endpoints=[("127.0.0.1", p1), ("127.0.0.1", p2)],
    )
    # the post-takeover writer lives on p2 with incarnation 1
    new_writer = DeltaStreamServer(p2, incarnation=1)
    cl.start()
    zombie = None
    try:
        new_writer.publish(0, [_batch([(1, 1, ("legit", None))])])
        assert _wait(lambda: cl.writer_incarnation == 1, timeout=15)
        assert _wait(lambda: applied and applied[-1][0] == 0, timeout=10)
        # the takeover writer dies too; a ZOMBIE incarnation-0 primary
        # resurfaces on the old endpoint and keeps publishing
        new_writer.close()
        zombie = DeltaStreamServer(p1, incarnation=0)
        zombie.publish(50, [_batch([(666, 1, ("zombie", None))])])
        assert _wait(lambda: cl.fenced_count >= 1, timeout=15)
        time.sleep(0.5)
        assert all(666 not in keys for _t, keys in applied), applied
        assert cl.applied_tick == 0  # nothing from the zombie applied
    finally:
        cl.close()
        new_writer.close()
        if zombie is not None:
            zombie.close()


def test_unsharded_router_refuses_shard_owning_member():
    """The inverse misconfig: a member owning 1/S of the corpus behind
    a PLAIN replicas-list router would serve partial answers with
    healthy 200s — the health loop ejects it on the reported shard
    count instead."""
    from pathway_tpu.parallel.replicate import DeltaStreamServer
    from pathway_tpu.serving.replica import ReplicaServer
    from pathway_tpu.serving.router import FailoverRouter

    srv = DeltaStreamServer(0, n_shards=2)
    member = ReplicaServer(
        replica_id=0,
        index_factory=ToyVecIndex,
        writer_port=srv.port,
        responder=_vec_responder,
        shard=0,
        n_shards=2,
    ).start()
    router = FailoverRouter(
        [f"http://127.0.0.1:{member.http_port}"],
        health_interval_ms=100,
    ).start()
    try:
        srv.publish(0, [_batch([(1, 1, (np.ones(4, np.float32), None))])])
        assert _wait(lambda: member.ready, timeout=15)
        ep = router.endpoints[0]
        assert _wait(lambda: ep.ejected, timeout=10)
        assert "shard-mismatch" in ep.eject_reason
        assert not ep.ready  # never routed to
    finally:
        router.stop()
        member.stop()
        srv.close()


def test_restarted_replica_probes_endpoints_and_shuns_zombie():
    """A FRESH client (restarted replica: empty in-memory fencing
    high-water) facing a live zombie (incarnation 0) on the first
    endpoint AND the legitimate takeover writer (incarnation 1) on the
    second must probe both, subscribe to the highest incarnation, and
    never apply a zombie frame — dialing order must not decide."""
    from pathway_tpu.parallel.replicate import (
        DeltaStreamClient,
        DeltaStreamServer,
    )
    from pathway_tpu.testing.chaos import free_dcn_port

    p1, p2 = free_dcn_port(1), free_dcn_port(1)
    zombie = DeltaStreamServer(p1, incarnation=0)
    legit = DeltaStreamServer(p2, incarnation=1)
    zombie.publish(50, [_batch([(666, 1, ("zombie", None))])])
    legit.publish(0, [_batch([(1, 1, ("legit", None))])])
    applied: list[tuple[int, list]] = []
    cl = DeltaStreamClient(
        "127.0.0.1",
        p1,
        0,
        from_tick=-1,
        on_deltas=lambda t, bs: applied.append(
            (t, [k for b in bs for k, _d, _v in b.iter_rows()])
        ),
        endpoints=[("127.0.0.1", p1), ("127.0.0.1", p2)],
    )
    cl.start()
    try:
        assert _wait(lambda: cl.writer_incarnation == 1, timeout=15)
        assert _wait(lambda: applied and applied[-1][0] == 0, timeout=10)
        time.sleep(0.3)
        assert all(666 not in keys for _t, keys in applied), applied
    finally:
        cl.close()
        zombie.close()
        legit.close()


def test_standby_never_usurps_before_first_contact():
    """A standby booted before (or alongside) its primary must NOT
    take over when the primary is merely slow to open its port — the
    bumped incarnation would fence the legitimate writer forever.  The
    grace clock starts at the first successful contact."""
    from pathway_tpu.parallel.standby import StandbyWriter
    from pathway_tpu.testing.chaos import free_dcn_port

    port = free_dcn_port(1)  # nothing listens here yet
    standby = StandbyWriter(
        "127.0.0.1",
        port,
        on_takeover=lambda s: None,
        grace_s=0.2,
        poll_s=0.05,
    ).start()
    try:
        assert not standby.wait_takeover(timeout=1.5)
        assert not standby.took_over
        # an explicit failure notification still takes over immediately
        standby.notify_failure("test", "operator says dead")
        assert standby.wait_takeover(timeout=10)
    finally:
        standby.stop()


def test_standby_persists_position(tmp_path):
    from pathway_tpu.parallel.replicate import DeltaStreamServer
    from pathway_tpu.parallel.standby import StandbyWriter

    srv = DeltaStreamServer(0)
    pos_file = str(tmp_path / "standby.json")
    standby = StandbyWriter(
        "127.0.0.1",
        srv.port,
        position_path=pos_file,
        grace_s=60.0,
        on_takeover=lambda s: None,
    ).start()
    try:
        def persisted_tick():
            try:
                return json.loads(open(pos_file).read())["applied_tick"]
            except (OSError, ValueError, KeyError):
                return -1

        srv.publish(0, [_batch([(1, 1, ("a", None))])])
        assert _wait(lambda: standby.applied_tick == 0, timeout=15)
        time.sleep(0.6)  # clear the position-write throttle window
        srv.publish(1, [_batch([(2, 1, ("b", None))])])
        # wait on the FILE: applied_tick is assigned before the atomic
        # position write lands
        assert _wait(lambda: persisted_tick() == 1, timeout=15)
    finally:
        standby.stop()
        srv.close()
    # a restarted standby resumes from the persisted position
    restarted = StandbyWriter(
        "127.0.0.1",
        1,  # nothing listens; only the restored position matters
        position_path=pos_file,
        grace_s=3600.0,
        on_takeover=lambda s: None,
    )
    assert restarted.applied_tick == 1
    assert restarted.next_incarnation() >= 1


def test_resume_point_reads_store(tmp_path):
    import pickle

    from pathway_tpu.persistence._runtime_glue import resume_point
    from pathway_tpu.persistence.backends import FilesystemStore

    store = FilesystemStore(str(tmp_path / "pstorage"))
    assert resume_point(store) == {
        "state_time": -1,
        "group_commit_time": -1,
        "last_time": -1,
    }
    store.put(
        "metadata.json",
        json.dumps(
            {"last_time": 42, "chunks": {}, "state": {"gen": 3, "time": 40}}
        ).encode(),
    )
    store.put("group_commit.json", json.dumps({"time": 38}).encode())
    del pickle
    assert resume_point(store) == {
        "state_time": 40,
        "group_commit_time": 38,
        "last_time": 42,
    }


# ---------------------------------------------------------------------------
# shard-filtered hydration + index compaction


def test_tpu_index_filter_keys_compacts():
    from pathway_tpu.stdlib.indexing._index_impls import TpuDenseKnnIndex

    idx = TpuDenseKnnIndex(dimensions=8)
    rng = np.random.default_rng(0)
    vecs = {k: rng.standard_normal(8).astype(np.float32) for k in range(3000)}
    for k, v in vecs.items():
        idx.upsert(k, v, {"k": k})
    full_bytes = idx.resident_bytes()
    assert sorted(idx.keys()) == sorted(vecs)
    idx.filter_keys(lambda k: k < 900)
    assert sorted(idx.keys()) == list(range(900))
    assert idx.metadata == {k: {"k": k} for k in range(900)}
    # the backing buffers actually shrank (the ~1/S memory claim)
    assert idx.resident_bytes() < full_bytes / 2
    # and the survivors still answer exactly
    res = idx.search([(vecs[5], 1, None)])[0]
    assert res[0][0] == 5


def test_replica_hydration_filters_to_shard(tmp_path):
    import pickle

    from pathway_tpu.parallel.replicate import corpus_shard_of
    from pathway_tpu.persistence.backends import FilesystemStore
    from pathway_tpu.serving.replica import ReplicaServer

    src = ToyVecIndex()
    for k in range(50):
        src.upsert(k, np.ones(4, dtype=np.float32) * k, None)
    store = FilesystemStore(str(tmp_path / "pstorage"))
    state = {
        "live_queries": {},
        "emitted": {},
        "index_state": ("pickle", src),
    }
    store.put("states/gen-000001/00003.pkl", pickle.dumps(state))
    store.put(
        "metadata.json",
        json.dumps(
            {
                "last_time": 9,
                "chunks": {},
                "state": {
                    "gen": 1,
                    "time": 9,
                    "nodes": {"3": "ExternalIndexNode"},
                },
            }
        ).encode(),
    )
    rep = ReplicaServer(
        replica_id=0,
        index_factory=ToyVecIndex,
        store_root=str(tmp_path / "pstorage"),
        shard=1,
        n_shards=3,
    )
    rep.hydrate()
    owned = set(rep.index.keys())
    assert owned
    dest = corpus_shard_of(list(range(50)), 3)
    assert owned == {k for k in range(50) if int(dest[k]) == 1}
