#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, on ONE
TPU process, and checks what comes out by the repo's own means:

  embed      SentenceTransformerEmbedder at MiniLM-L6 widths (dim 384,
             6 layers, 12 heads, vocab 30522, max_len 512, seeded weights)
             over a mixed-length batch via its batched UDF path; finite,
             unit-norm, close to the same parameters applied on this
             process's CPU device in float32 at "highest" precision
  retrieve   VectorStoreServer(...).run_server(threaded=True) over a few
             thousand seeded documents plus a ConnectorSubject that adds
             more after start; VectorStoreClient.query answers k results,
             a document's own text finds that document first, a late
             document becomes retrievable, the error log stays empty
  topk       TpuDenseKnnIndex over >= 65,536 x 384 rows (the two-stage
             block branch of ops/knn._masked_topk), ids against exact
             float32 numpy
  generate   ReplicaServer + attach_generate(DecodeScheduler): the Pallas
             paged-attention kernel serves POST /generate (one streamed);
             decode_step pallas vs ref over the batch buckets 1..8
  tick       numeric map -> filter -> map chains and groupbys through
             pw.debug: the int64 chain runs compiled (ticks > 0, no
             compile-fallback event), the float64 chain compiles only where
             the backend has float64, every stream equal to the same
             pipeline with the compiled path off
  four_chips when jax.device_count() >= 4: make_mesh(4), the embedder with
             mesh=, BruteForceKnn(mesh=) giving the single-chip ids, a
             PATHWAY_ENGINE_SHARDS=4 groupby through the device exchange,
             the corpus spread evenly over the four devices' memory

It refuses to run (non-zero exit, one line, no result) unless
``jax.devices()[0].platform == "tpu"``.  No phase is guarded: the first
failure ends the run with a traceback and a non-zero exit code.  The last
line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

``--cpu-dry-run`` (an argument, never a sniffed environment) runs the same
control flow on the CPU at toy sizes, with Pallas in interpret mode, and
labels every line ``"platform": "cpu", "dry_run": true`` — for debugging
the command before chip budget is spent.  It proves nothing about a chip.

Tolerances (each measured on a TPU v5e, PR 21, and stated where used):
  embed      bf16 activations on the chip vs float32 on the CPU: cosine
             >= 0.999 per text and max |diff| <= 1e-2 (measured 0.99997
             and 1.1e-3; bf16 keeps 8 mantissa bits through six layers)
  topk       recall@10 >= 0.95 against exact float32: on a TPU a float32
             dot_general at default precision multiplies in bf16 (measured
             score error 3.5e-4, recall 0.986 on gaussian rows, the
             hardest case; "highest" precision measured 7.5e-8 / 1.0)
  generate   |logits(pallas) - logits(ref)| <= 5e-2 on logits of
             magnitude ~3: the kernel's float32 matmuls also multiply in
             bf16 (measured 1.2e-2, greedy tokens identical)
  tick       integers exact, floats by the engine's own contract (rel
             1e-9); a TPU emulates float64 in pairs of float32 (measured:
             48-bit mantissa, float32 range, float->int casts that round),
             so float64 chains are refused there by decision
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time
import urllib.request

import numpy as np

T0 = time.monotonic()
K = 10
VOCAB = [f"w{i}" for i in range(2_000)]

# sizes: the real ones, and the toy ones of --cpu-dry-run
FULL = dict(
    enc=dict(dim=384, depth=6, heads=12, max_len=512),
    embed_words=(
        2, 5, 9, 17, 30, 45, 60, 90, 120, 180, 240, 300, 360, 420, 480,
        3, 12, 25, 70, 150, 7, 33, 500, 4,
    ),
    n_docs=4_096,
    n_late=64,
    topk_rows=65_536,
    topk_dim=384,
    tick_rows=512,
    mesh_rows=32_768,
)
TOY = dict(
    enc=dict(dim=32, depth=1, heads=2, max_len=64),
    embed_words=(2, 5, 9, 17, 30, 45, 60, 3, 12),
    n_docs=48,
    n_late=6,
    topk_rows=65_536,  # the two-stage top-k branch starts here
    topk_dim=16,
    tick_rows=256,
    mesh_rows=2_048,
)


def prepare_environment(dry: bool) -> None:
    """Before jax is imported."""
    # the embedder first looks for a pretrained tokenizer by name; there
    # is no network here, so tell the hub client not to go looking
    os.environ.setdefault("HF_HUB_OFFLINE", "1")
    if dry:
        # the dry run needs the CPU backend with enough virtual devices
        # to walk the four-chip phase
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()
        return
    # the embed reference runs on this process's own CPU device: keep the
    # CPU backend available BEHIND whatever was asked for (an explicit
    # platform list still fails loudly when its first entry cannot start)
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def seeded_text(rng, n_words: int) -> str:
    return " ".join(rng.choice(VOCAB, size=int(n_words)))


def free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def errors_logged() -> int:
    from pathway_tpu.internals.errors import error_count

    return error_count()


class Smoke:
    """One run: the device it found, its sizes, and the phases."""

    def __init__(self, dry: bool, device: dict, cache_dir: str):
        self.dry = dry
        self.device = device
        self.cache_dir = cache_dir
        self.size = TOY if dry else FULL
        self.label = {"platform": device["platform"]}
        if dry:
            self.label["dry_run"] = True

    def emit(self, phase: str, **fields) -> None:
        """One JSON line per phase result (stdout)."""
        line = {"phase": phase, **self.label, **fields}
        line["t_s"] = round(time.monotonic() - T0, 1)
        print(json.dumps(line), flush=True)

    # --- embed -------------------------------------------------------------

    def embed(self):
        import jax
        import pathway_tpu as pw
        from pathway_tpu.xpacks.llm._encoder import TransformerEncoder
        from pathway_tpu.xpacks.llm.embedders import (
            SentenceTransformerEmbedder,
        )

        enc = self.size["enc"]
        embedder = SentenceTransformerEmbedder(**enc)
        vocab = embedder.tokenizer.vocab_size
        check(vocab == 30522, f"vocab {vocab}")
        rng = np.random.default_rng(11)
        texts = [seeded_text(rng, n) for n in self.size["embed_words"]]

        class S(pw.Schema):
            text: str

        pw.internals.parse_graph.G.clear()
        t = pw.debug.table_from_rows(S, [(x,) for x in texts])
        res = t.select(text=t.text, e=embedder(t.text))  # batched UDF path
        _keys, cols = pw.debug.table_to_dicts(res)
        by_text = {cols["text"][k]: cols["e"][k] for k in cols["text"]}
        out = np.stack([by_text[x] for x in texts])
        check(out.shape == (len(texts), enc["dim"]), f"shape {out.shape}")
        check(bool(np.isfinite(out).all()), "non-finite embedding")
        norm_dev = float(np.abs(np.linalg.norm(out, axis=1) - 1.0).max())
        check(norm_dev < 1e-3, f"not unit-norm: {norm_dev}")

        # reference: the SAME parameters, float32 end to end, on this
        # process's CPU device at "highest" matmul precision
        cpu = jax.devices("cpu")[0]
        ids, mask = embedder.tokenizer.encode_batch(
            texts, embedder.runtime.max_len
        )
        ref_model = TransformerEncoder(
            vocab_size=vocab,
            dim=enc["dim"],
            depth=enc["depth"],
            heads=enc["heads"],
            max_len=enc["max_len"],
            dtype=jax.numpy.float32,
        )
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(
                jax.jit(ref_model.apply)(
                    jax.device_put(embedder.runtime.params, cpu),
                    jax.device_put(ids, cpu),
                    jax.device_put(mask, cpu),
                )
            )
        cos = np.sum(ref * out, axis=1)
        diff = float(np.abs(ref - out).max())
        # tolerance: module docstring (bf16 activations vs float32)
        check(float(cos.min()) >= 0.999, f"cosine to reference {cos.min()}")
        check(diff <= 1e-2, f"max |diff| to reference {diff}")
        check(errors_logged() == 0, "error log not empty after embed")
        self.emit(
            "embed",
            ok=True,
            texts=len(texts),
            token_lengths=[int(m.sum()) for m in mask],
            min_cosine_to_cpu_f32=round(float(cos.min()), 6),
            max_abs_diff=diff,
            unit_norm_dev=norm_dev,
        )
        return embedder

    # --- retrieve through the server ---------------------------------------

    def retrieve(self, embedder) -> None:
        import pathway_tpu as pw
        from pathway_tpu.xpacks.llm.vector_store import (
            VectorStoreClient,
            VectorStoreServer,
        )

        n_docs, n_late = self.size["n_docs"], self.size["n_late"]
        rng = np.random.default_rng(23)

        def doc(tag: str, i: int) -> str:
            return f"{tag}{i} " + seeded_text(rng, rng.integers(6, 24))

        docs = [doc("doc", i) for i in range(n_docs)]
        late = [doc("late", i) for i in range(n_late)]
        release_late = threading.Event()
        stop_subject = threading.Event()

        class LateDocs(pw.io.python.ConnectorSubject):
            def run(self):
                release_late.wait()
                for text in late:
                    self.next(data=text)
                stop_subject.wait()  # the source stays open until the end

        class DocSchema(pw.Schema):
            data: str

        pw.internals.parse_graph.G.clear()
        static = pw.debug.table_from_rows(DocSchema, [(d,) for d in docs])
        stream = pw.io.python.read(
            LateDocs(), schema=DocSchema, autocommit_duration_ms=50
        )
        server = VectorStoreServer(static, stream, embedder=embedder)
        port = free_port()
        thread = server.run_server(host="127.0.0.1", port=port, threaded=True)
        client = VectorStoreClient(host="127.0.0.1", port=port, timeout=120)

        def wait_indexed(n: int, budget_s: float) -> None:
            deadline = time.monotonic() + budget_s
            seen = None
            while time.monotonic() < deadline:
                check(thread.is_alive(), "the server's pw.run thread died")
                try:
                    seen = client.get_vectorstore_statistics()["file_count"]
                except OSError as exc:  # not listening yet
                    seen = repr(exc)
                if seen == n:
                    return
                time.sleep(0.5)
            raise AssertionError(
                f"indexed {seen!r} of {n} documents in {budget_s}s"
            )

        def own_text_first(text: str, what: str) -> None:
            res = client.query(text, k=5)
            check(len(res) == 5, f"{what}: {len(res)} results, wanted 5")
            dists = [round(r["dist"], 5) for r in res]
            check(
                res[0]["text"] == text,
                f"{what}: own text not first: {res[0]['text'][:40]!r} "
                f"dists {dists}",
            )
            check(dists == sorted(dists), f"{what}: not sorted: {dists}")

        try:
            wait_indexed(n_docs, 600.0)
            probes = (0, n_docs // 3, n_docs - 1)
            for i in probes:
                own_text_first(docs[i], f"query doc {i}")
            free = client.query(seeded_text(rng, 8), k=3)
            check(len(free) == 3, f"free-text query: {len(free)} results")
            # a document that arrives after start becomes retrievable
            early = client.query(late[0], k=1)
            check(early[0]["text"] != late[0], "late document visible early")
            release_late.set()
            wait_indexed(n_docs + n_late, 300.0)
            for j in (0, n_late - 1):
                own_text_first(late[j], f"query late doc {j}")
            # a 200 proves nothing by itself (a failed search used to
            # answer empty): the error log must be empty too
            check(errors_logged() == 0, f"{errors_logged()} errors logged")
        finally:
            stop_subject.set()
            release_late.set()
            pw.internals.parse_graph.G.runtime.stop()
            thread.join(timeout=60)
        check(not thread.is_alive(), "the server's pw.run thread did not stop")
        self.emit(
            "retrieve",
            ok=True,
            docs=n_docs,
            late_docs=n_late,
            queries=len(probes) + 4,
            errors_logged=0,
        )

    # --- top-k at the large-corpus path ------------------------------------

    def topk(self) -> None:
        from pathway_tpu.stdlib.indexing._index_impls import TpuDenseKnnIndex

        n, d = self.size["topk_rows"], self.size["topk_dim"]
        rng = np.random.default_rng(0)
        corpus = rng.normal(size=(n, d)).astype(np.float32)
        queries = rng.normal(size=(14, d)).astype(np.float32)
        qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)
        cn = corpus / np.linalg.norm(corpus, axis=1, keepdims=True)
        # exact float32 on the host
        exact = np.argsort(-(qn @ cn.T), axis=1, kind="stable")[:, :K]

        index = TpuDenseKnnIndex(dimensions=d, reserved_space=n)
        for i in range(n):
            index.upsert(i, corpus[i], None)
        # n >= 64 * 1024, k <= 1024: _masked_topk's two-stage block branch
        check(index.corpus.capacity >= 64 * 1024, "corpus below the branch")
        # batches of 1, 2, 3 and 8 queries: the pow2 ladder 1, 2, 4, 8
        # the index pads onto (d=384, k=10)
        ids, lo = [], 0
        for nq in (1, 2, 3, 8):
            batch = [(q, K, None) for q in queries[lo : lo + nq]]
            for matches in index.search(batch):
                check(len(matches) == K, f"{len(matches)} matches")
                ids.append([key for key, _score in matches])
            lo += nq
        ids = np.asarray(ids)
        recall = float(
            np.mean(
                [
                    len(set(a.tolist()) & set(b.tolist())) / K
                    for a, b in zip(ids, exact)
                ]
            )
        )
        # tolerance: module docstring (bf16 multiplies at default
        # precision); ids are judged, not scores
        check(recall >= 0.95, f"recall@{K} {recall} against exact float32")
        check(bool((ids[:, 0] == exact[:, 0]).all()), "top-1 != exact")
        check(errors_logged() == 0, f"{errors_logged()} errors logged")
        self.emit(
            "topk",
            ok=True,
            rows=n,
            dim=d,
            k=K,
            query_buckets=[1, 2, 4, 8],
            recall_at_10_vs_exact_f32=round(recall, 4),
        )

    # --- generate ----------------------------------------------------------

    def generate(self) -> None:
        from pathway_tpu.generate.scheduler import (
            DecodeScheduler,
            GenerateConfig,
        )
        from pathway_tpu.generate.serving import attach_generate
        from pathway_tpu.ops.backend import pallas_mode
        from pathway_tpu.serving.replica import ReplicaServer, text_vector
        from pathway_tpu.stdlib.indexing._index_impls import TpuDenseKnnIndex
        from pathway_tpu.xpacks.llm import decoder as dec

        # on the chip the scheduler must pick the Pallas kernel BY ITSELF;
        # the dry run asks for it, so the same kernel runs (interpreted)
        config = GenerateConfig(kernel="pallas") if self.dry else GenerateConfig()
        dim = 16
        srv = ReplicaServer(
            replica_id=0,
            index_factory=lambda: TpuDenseKnnIndex(dimensions=dim),
            dim=dim,
        )
        for i, text in enumerate(
            ["alpha beta", "gamma delta", "epsilon zeta", "eta theta"]
        ):
            srv.index.upsert(i, text_vector(text, dim), None)
        sched = attach_generate(
            srv, DecodeScheduler(config, replica_label="smoke")
        )
        check(sched.kernel == "pallas", f"scheduler kernel {sched.kernel!r}")
        want_mode = "interpret" if self.dry else "compiled"
        check(pallas_mode() == want_mode, f"pallas mode {pallas_mode()}")
        srv.start()
        try:
            url = f"http://127.0.0.1:{srv.http_port}/generate"
            n_tok = 8
            for prompt in ("what is alpha?", "tell me about gamma"):
                status, raw = post_json(
                    url, {"prompt": prompt, "k": 2, "max_tokens": n_tok}
                )
                check(status == 200, f"/generate {status}: {raw[:200]}")
                body = json.loads(raw)
                check(body["token_count"] == n_tok, f"body {body}")
                check(len(body["retrieved"]) == 2, f"body {body}")
            status, raw = post_json(
                url,
                {"prompt": "stream me", "k": 1, "max_tokens": n_tok,
                 "stream": True},
            )
            check(status == 200, f"streamed /generate {status}")
            lines = [json.loads(x) for x in raw.splitlines()]
            check(len(lines[0]["meta"]["retrieved"]) == 1, f"{lines[0]}")
            n_lines = len([x for x in lines if "token" in x])
            check(n_lines == n_tok, f"{n_lines} streamed token lines")
            last = lines[-1]
            check(
                last.get("done") is True and last.get("token_count") == n_tok,
                f"done line {last}",
            )
            check(sched.drain(timeout=60.0), "scheduler did not drain")
            stats = sched.stats()
            check(stats["failed"] is None, f"scheduler: {stats['failed']}")
            check(
                stats["free_pages"] == stats["page_capacity"],
                f"pages not reclaimed: {stats}",
            )
        finally:
            srv.stop()
            sched.stop()

        # twenty decode steps per batch bucket (1..8; H=4, P=16, Dp=128):
        # the Pallas kernel against the pure-JAX twin on the same device
        cfg = config.decoder_config()
        params = dec.init_params(cfg, seed=config.decoder_seed)
        rng = np.random.default_rng(5)
        worst = 0.0
        for b in (1, 2, 4, 8):
            live = max(1, b - 1)  # the last slot of a batch stays padding
            toks = rng.integers(0, 256, size=(20, b)).astype(np.int32)
            pt = np.zeros((b, cfg.max_pages), np.int32)
            for i in range(live):
                pt[i, :2] = [1 + 2 * i, 2 + 2 * i]
            logits = {}
            for kernel in ("ref", "pallas"):
                k_pool, v_pool = dec.empty_pools(cfg, config.n_pages)
                for step in range(toks.shape[0]):
                    seq_lens = np.zeros(b, np.int32)
                    seq_lens[:live] = step + 1
                    positions = np.zeros(b, np.int32)
                    positions[:live] = step
                    out, k_pool, v_pool = dec.decode_step(
                        params, toks[step], positions, k_pool, v_pool,
                        pt, seq_lens, cfg=cfg, kernel=kernel,
                    )
                logits[kernel] = np.asarray(out)[:live]
            check(bool(np.isfinite(logits["pallas"]).all()), "logits inf/nan")
            diff = float(np.abs(logits["ref"] - logits["pallas"]).max())
            worst = max(worst, diff)
        # tolerance: module docstring (bf16 multiplies in the kernel)
        check(worst <= 5e-2, f"pallas vs ref logits differ by {worst}")
        self.emit(
            "generate",
            ok=True,
            kernel=sched.kernel,
            requests=3,
            streamed=1,
            decode_steps=stats["decode_steps"],
            batch_buckets=[1, 2, 4, 8],
            max_logit_diff_pallas_vs_ref=worst,
        )

    # --- compiled tick -----------------------------------------------------

    def tick(self) -> None:
        import pathway_tpu as pw
        from pathway_tpu.observability.journal import journal
        from pathway_tpu.ops.backend import float64_native

        class Num(pw.Schema):
            a: int
            b: float

        rng = np.random.default_rng(23)
        rows = [
            (int(rng.integers(-500, 500)), float(rng.normal()))
            for _ in range(self.size["tick_rows"])
        ]
        graph = pw.internals.parse_graph.G

        def int_chain(t):  # int64 on the device, the float column rides
            return (
                t.select(x=t.a * 2 + 1, b=t.b)
                .filter(pw.this.x > 0)
                .select(z=pw.this.x + 1, b=pw.this.b)
            )

        def int_groupby(t):
            g = t.select(g=t.a & 15, a=t.a)
            return g.groupby(g.g).reduce(
                g.g, n=pw.reducers.count(), s=pw.reducers.sum(g.a)
            )

        def float_chain(t):  # float64 arithmetic
            return (
                t.select(x=t.a * 2 + 1, y=t.b - t.a)
                .filter(pw.this.x > 0)
                .select(z=pw.this.x + 1, y=pw.this.y * 0.5)
            )

        def float_groupby(t):
            g = t.select(g=t.a & 15, b=t.b)
            return g.groupby(g.g).reduce(g.g, m=pw.reducers.avg(g.b))

        pipelines = (int_chain, int_groupby, float_chain, float_groupby)

        def run(compiled: bool) -> dict:
            old = os.environ.get("PATHWAY_COMPILED_TICK")
            os.environ["PATHWAY_COMPILED_TICK"] = "1" if compiled else "0"
            out = {}
            try:
                for build in pipelines:
                    graph.clear()
                    seq0 = max(
                        (e["seq"] for e in journal().events()), default=0
                    )
                    t = pw.debug.table_from_rows(Num, rows)
                    _k, cols = pw.debug.table_to_dicts(build(t))
                    plan = graph.last_runtime.compiled_plan
                    check((plan is not None) == compiled, "compiled path flag")
                    out[build.__name__] = dict(
                        cols=cols,
                        ticks=sum(s.compiled_ticks for s in plan.segments)
                        if plan
                        else 0,
                        fallbacks=journal().events(
                            kinds=["compile-fallback"], since_seq=seq0
                        ),
                    )
            finally:
                if old is None:
                    os.environ.pop("PATHWAY_COMPILED_TICK", None)
                else:
                    os.environ["PATHWAY_COMPILED_TICK"] = old
            return out

        want = run(compiled=False)
        got = run(compiled=True)

        def same(got_cols: dict, want_cols: dict, what: str) -> int:
            check(set(got_cols) == set(want_cols), f"{what}: columns differ")
            n = 0
            for col, want_col in want_cols.items():
                check(set(got_cols[col]) == set(want_col), f"{what}.{col}")
                for key, w in want_col.items():
                    g = got_cols[col][key]
                    if isinstance(w, float):
                        # the engine's float contract
                        # (tests/test_compiled_tick.py _vals_close)
                        ok = math.isclose(g, w, rel_tol=1e-9, abs_tol=1e-12)
                    else:
                        ok = int(g) == int(w)
                    check(ok, f"{what}.{col}[{key}]: {g!r} != {w!r}")
                    n += 1
            return n

        n = sum(
            same(got[name]["cols"], want[name]["cols"], name) for name in want
        )
        # the program's own contract: integer chains run compiled, with no
        # compile-fallback event
        check(got["int_chain"]["ticks"] > 0, "int chain ran interpreted")
        for name in ("int_chain", "int_groupby"):
            check(not got[name]["fallbacks"], f"{name}: {got[name]}")
        # float64 compiles where the backend has it; a TPU emulates it
        # (ops/backend.py float64_native), so there it is refused BY
        # DECISION, with the reason in the journal
        reasons = [
            e["data"]["reason"] for e in got["float_chain"]["fallbacks"]
        ]
        if float64_native():
            check(got["float_chain"]["ticks"] > 0 and not reasons, "float chain")
        else:
            check(got["float_chain"]["ticks"] == 0, "emulated float64 compiled")
            check(
                reasons and all(r.startswith("float64") for r in reasons),
                f"float chain fallback reasons {reasons}",
            )
        check(errors_logged() == 0, f"{errors_logged()} errors logged")
        self.emit(
            "tick",
            ok=True,
            rows=len(rows),
            int_chain_compiled_ticks=got["int_chain"]["ticks"],
            int_compile_fallback_events=0,
            float64_native=float64_native(),
            float_chain_compiled_ticks=got["float_chain"]["ticks"],
            float_chain_fallback_reasons=reasons,
            values_compared=n,
        )

    # --- four chips --------------------------------------------------------

    def four_chips(self, embedder) -> None:
        if self.device["count"] < 4:
            self.emit(
                "four_chips",
                ok=None,
                ran=False,
                reason=f"jax.device_count() == {self.device['count']} (< 4)",
            )
            return
        import gc

        import pathway_tpu as pw
        from pathway_tpu.engine import sharded
        from pathway_tpu.engine.sharded import ShardedGroupByExec
        from pathway_tpu.parallel import mesh as mesh_mod
        from pathway_tpu.parallel.mesh import make_mesh
        from pathway_tpu.stdlib.indexing import BruteForceKnn, DataIndex
        from pathway_tpu.xpacks.llm.embedders import (
            SentenceTransformerEmbedder,
        )

        graph = pw.internals.parse_graph.G
        mesh = make_mesh(4)
        devices = list(mesh.devices.flat)
        check(
            {d.platform for d in devices} == {self.device["platform"]},
            "the mesh holds devices of another backend",
        )

        # the embedder, batch-sharded over the mesh, against the same
        # seeded weights on one device
        rng = np.random.default_rng(31)
        texts = [seeded_text(rng, n) for n in (4, 9, 20, 33, 50, 12, 7, 28)]
        sharded_embedder = SentenceTransformerEmbedder(
            **self.size["enc"], mesh=mesh
        )
        e1 = np.stack(embedder._embed_batch(texts))
        e4 = np.stack(sharded_embedder._embed_batch(texts))
        emb_diff = float(np.abs(e1 - e4).max())
        check(emb_diff <= 1e-2, f"mesh embedder differs by {emb_diff}")

        # BruteForceKnn(mesh=) through the table API against the same
        # index on one chip
        n, dim = self.size["mesh_rows"], self.size["topk_dim"]
        corpus = rng.normal(size=(n, dim)).astype(np.float32)
        noise = rng.normal(size=(4, dim)).astype(np.float32)
        queries = corpus[:4] + 0.05 * noise
        schema = pw.schema_from_types(name=int, vec=np.ndarray)

        def knn_ids(**index_kwargs) -> dict:
            graph.clear()
            docs = pw.debug.table_from_rows(
                schema, [(i, corpus[i]) for i in range(n)]
            )
            qs = pw.debug.table_from_rows(
                schema, [(i, queries[i]) for i in range(len(queries))]
            )
            inner = BruteForceKnn(
                docs.vec, dimensions=dim, reserved_space=n, **index_kwargs
            )
            res = (
                DataIndex(docs, inner)
                .query_as_of_now(qs.vec, number_of_matches=K)
                .select(q=pw.left.name, names=pw.right.name)
            )
            _k, cols = pw.debug.table_to_dicts(res)
            return {cols["q"][k]: tuple(cols["names"][k]) for k in cols["q"]}

        def in_use() -> list | None:
            stats = [d.memory_stats() for d in devices]
            if any(s is None for s in stats):
                return None  # the CPU backend reports none
            return [int(s["bytes_in_use"]) for s in stats]

        graph.clear()
        gc.collect()
        before = in_use()
        ids4 = knn_ids(mesh=mesh)
        # the finished run still holds its index (graph.last_runtime):
        # what each device gained is its share of the resident corpus
        after = in_use()
        ids1 = knn_ids()
        for q, single in ids1.items():
            check(len(ids4[q]) == K, f"query {q}: {len(ids4[q])} matches")
            check(ids4[q][0] == q == single[0], f"query {q}: nearest differs")
            overlap = len(set(single) & set(ids4[q])) / K
            # the mesh path casts to bf16, the one-chip path multiplies
            # float32 in bf16: the same ids up to near-ties at rank k
            check(overlap >= 0.8, f"query {q}: mesh/single overlap {overlap}")
        spread = "memory_stats() is not available on this backend"
        if before is not None:
            spread = [a - b for a, b in zip(after, before)]
            share = corpus.nbytes / 4
            check(
                all(0.5 * share <= s <= 2.0 * share for s in spread),
                f"corpus of {corpus.nbytes} bytes not spread evenly: {spread}",
            )

        # PATHWAY_ENGINE_SHARDS=4: a groupby whose rows cross the device
        # exchange (engine/sharded.py)
        class Rows(pw.Schema):
            g: int
            v: float

        old_env = os.environ.get("PATHWAY_ENGINE_SHARDS")
        old_min = sharded.DEVICE_EXCHANGE_MIN_ROWS
        os.environ["PATHWAY_ENGINE_SHARDS"] = "4"
        # earlier phases resolved "no engine mesh": read the variable again
        mesh_mod._engine_mesh_resolved = False
        try:
            sharded.DEVICE_EXCHANGE_MIN_ROWS = 64
            graph.clear()
            t = pw.debug.table_from_rows(
                Rows, [(i % 23, float(i)) for i in range(1024)]
            )
            res = t.groupby(t.g).reduce(
                t.g, s=pw.reducers.sum(t.v), c=pw.reducers.count()
            )
            _k, cols = pw.debug.table_to_dicts(res)
            check(sum(cols["c"].values()) == 1024, "sharded groupby: rows")
            total = sum(cols["s"].values())
            check(math.isclose(total, sum(range(1024))), f"sum {total}")
            ex = next(
                e
                for e in graph.last_runtime.execs.values()
                if isinstance(e, ShardedGroupByExec)
            )
            exchanges = ex.router.device_exchanges
            check(exchanges >= 1, "the device exchange was not used")
        finally:
            sharded.DEVICE_EXCHANGE_MIN_ROWS = old_min
            if old_env is None:
                os.environ.pop("PATHWAY_ENGINE_SHARDS", None)
            else:
                os.environ["PATHWAY_ENGINE_SHARDS"] = old_env
            mesh_mod.set_engine_mesh(None)
            graph.clear()
        check(errors_logged() == 0, f"{errors_logged()} errors logged")
        self.emit(
            "four_chips",
            ok=True,
            ran=True,
            mesh_devices=4,
            mesh_embedder_max_diff=emb_diff,
            knn_rows=n,
            corpus_bytes=int(corpus.nbytes),
            corpus_bytes_per_device=spread,
            device_exchanges=exchanges,
        )

    # --- the compile cache -------------------------------------------------

    def compile_cache(self) -> None:
        """Persistent compile cache traffic of this process, from the
        jax.monitoring events the metrics registry bridges."""
        from pathway_tpu.observability import REGISTRY

        events = REGISTRY.get("pathway_jax_events_total")

        def count(name: str) -> int:
            return int(events.labels(f"_jax_compilation_cache_{name}").value)

        hits = count("cache_hits")
        self.emit(
            "compile_cache",
            dir=self.cache_dir,
            from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            requests=count("compile_requests_use_cache"),
            hits=hits,
            misses=count("cache_misses"),
            verdict="hit" if hits else "cold (no hits)",
        )


def post_json(url: str, body: dict, timeout: float = 300.0):
    req = urllib.request.Request(
        url,
        data=json.dumps(body).encode(),
        headers={"content-type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode()


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--cpu-dry-run",
        action="store_true",
        help="same control flow on the CPU at toy sizes (no chip proof)",
    )
    dry = ap.parse_args(argv).cpu_dry_run
    prepare_environment(dry)
    try:
        import pathway_tpu  # noqa: F401
    except ImportError as exc:
        print(
            f"chip_smoke: the pathway_tpu package is not importable from "
            f"{os.getcwd()} ({exc}); run from the root of a checkout",
            file=sys.stderr,
        )
        return 1
    import jax
    import jaxlib

    from pathway_tpu.internals.compile_cache import configure_compile_cache
    from pathway_tpu.internals.native import native_status
    from pathway_tpu.observability import install_jax_metrics
    from pathway_tpu.ops.backend import pallas_mode

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" and not dry:
        print(
            "chip_smoke: no TPU: jax.devices()[0].platform == "
            f"{device['platform']!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}). This script proves "
            "the system on a chip and does not run without one; "
            "--cpu-dry-run walks the control flow on the CPU.",
            file=sys.stderr,
        )
        return 1
    install_jax_metrics()
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    smoke = Smoke(dry, device, cache_dir)
    smoke.emit(
        "start",
        device=device,
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=libtpu_version,
        compile_cache_dir=cache_dir,
        native=native_status(),
        pallas=pallas_mode(),
    )
    # no phase is guarded: the first failure ends the run
    embedder = smoke.embed()
    smoke.retrieve(embedder)
    smoke.topk()
    smoke.generate()
    smoke.tick()
    smoke.four_chips(embedder)
    smoke.compile_cache()
    final = {"ok": True, "device": device}
    if dry:
        final["dry_run"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
