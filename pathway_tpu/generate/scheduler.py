"""Continuous-batching decode scheduler on Surge Gate.

One scheduler per generation replica.  Requests are admitted through
the existing EDF :class:`~pathway_tpu.serving.batcher.MicroBatcher`
(same deadline-at-flush semantics: an expired request is 504'd without
ever touching the device), join the active set BETWEEN decode steps,
and from then on every step advances every active sequence by one
token on the power-of-two pad ladder — batch x padded-seq shapes land
on buckets the jitted ``decode_step`` already compiled (the Tick Forge
compile-cache argument applied to generation).

Prefill IS decode here: a joining sequence's prompt tokens are fed one
per step through the same jitted function (logits ignored until the
prompt is consumed), so there is exactly one code path and a restored
run provably continues the same computation.  ``generate.prefill``
spans cover admission -> first sampled token; ``generate.decode_step``
spans cover each engine step.

Deadline propagation drops expired generations MID-decode: before
every step the scheduler sweeps the active set, answers 504, reclaims
the sequence's pages into the pool and retracts its ledger rows —
never another step for a dead deadline
(``pathway_generate_dropped_mid_decode_total``).

Durability: every ``snapshot_every`` steps the scheduler mirrors pages
that changed since the last mirror (pages fully written earlier are
immutable — bytes written scale with churn, the State Ledger
argument) plus per-sequence resume metadata into the
:class:`~pathway_tpu.generate.kv_cache.KvLedger`, then writes the
incremental segment snapshot.  ``restore=`` rebuilds pools, page
tables and sequence state from the newest manifest; decoding continues
where the snapshot left off and — greedy or seeded sampling being
deterministic — reproduces the uninterrupted run's tokens exactly.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from pathway_tpu.generate.kv_cache import KvLedger, PagePool
from pathway_tpu.serving.admission import DeadlineExceeded, ShedError
from pathway_tpu.serving.batcher import MicroBatcher
from pathway_tpu.serving.config import QoSConfig

_ENV_PREFIX = "PATHWAY_GENERATE_"
# the page-pool default; the Graph Doctor's generation-serving rule
# flags a plane running on it (INFO) — an explicit size is the memory
# budget statement
DEFAULT_PAGES = 64


def generate_enabled_via_env() -> bool:
    """``PATHWAY_GENERATE=1`` arms the generation stage on a replica
    (serving/replica.py main) — off keeps the read plane byte-identical
    to the pre-generation topology."""
    return os.environ.get("PATHWAY_GENERATE", "0").lower() in (
        "1",
        "true",
        "yes",
    )


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(_ENV_PREFIX + name, "")
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(
            f"{_ENV_PREFIX}{name}={raw!r} is not an int"
        ) from None


@dataclass(frozen=True)
class GenerateConfig:
    """Generation-stage policy: decoder shape + page pool + scheduler
    knobs.  Every knob has a ``PATHWAY_GENERATE_*`` override."""

    n_pages: int = DEFAULT_PAGES
    page_size: int = 16
    max_batch: int = 8
    max_new_tokens: int = 32  # default per request (body may lower it)
    max_len: int = 256  # hard per-sequence token bound (pages permitting)
    snapshot_every: int = 0  # decode steps between snapshots; 0 = off
    store_root: str | None = None
    kernel: str = "auto"  # auto | ref | pallas
    decoder_seed: int = 0
    dim: int = 128
    n_layers: int = 2
    n_heads: int = 4
    head_dim: int = 32
    ffn_dim: int = 256

    @classmethod
    def from_env(cls) -> "GenerateConfig":
        kernel = os.environ.get(_ENV_PREFIX + "KERNEL", "") or "auto"
        if kernel not in ("auto", "ref", "pallas"):
            raise ValueError(
                f"{_ENV_PREFIX}KERNEL={kernel!r} must be auto|ref|pallas"
            )
        return cls(
            n_pages=_env_int("PAGES", DEFAULT_PAGES),
            page_size=_env_int("PAGE_SIZE", 16),
            max_batch=_env_int("MAX_BATCH", 8),
            max_new_tokens=_env_int("MAX_TOKENS", 32),
            max_len=_env_int("MAX_LEN", 256),
            snapshot_every=_env_int("SNAPSHOT_EVERY", 0),
            store_root=os.environ.get(_ENV_PREFIX + "STORE") or None,
            kernel=kernel,
            decoder_seed=_env_int("SEED", 0),
        )

    def decoder_config(self):
        from pathway_tpu.xpacks.llm.decoder import DecoderConfig

        return DecoderConfig(
            dim=self.dim,
            n_layers=self.n_layers,
            n_heads=self.n_heads,
            head_dim=self.head_dim,
            ffn_dim=self.ffn_dim,
            max_len=self.max_len,
            page_size=self.page_size,
        )


class GenerationRequest:
    """One admitted-or-not generation crossing the scheduler.  Exposes
    ``deadline`` for the micro-batcher's EDF heap and a ``wait()`` the
    serving handler blocks on (in an executor)."""

    def __init__(
        self,
        request_id: str,
        prompt_tokens: list[int],
        *,
        deadline: float,
        max_new_tokens: int,
        tenant: str | None = None,
        tenant_class: str | None = None,
        temperature: float = 0.0,
        top_k: int = 40,
        seed: int = 0,
        on_token: Callable[[int, bool], None] | None = None,
        traceparent: str | None = None,
    ):
        self.request_id = request_id
        self.prompt_tokens = list(prompt_tokens)
        self.deadline = float(deadline)
        self.order = self.deadline  # MicroBatcher heap key (plain EDF)
        self.max_new_tokens = int(max_new_tokens)
        self.tenant = tenant
        self.tenant_class = tenant_class
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)
        self.on_token = on_token
        self.traceparent = traceparent
        self.created_at = time.monotonic()
        self.done = threading.Event()
        self.result: dict | None = None
        # optional completion hook (the serving handler parks an
        # asyncio.Event behind it so no executor thread blocks per
        # in-flight generation); called AFTER result/done are set
        self.on_done: Callable[[], None] | None = None

    def finish(self, result: dict) -> None:
        self.result = result
        self.done.set()
        if self.on_done is not None:
            try:
                self.on_done()
            except Exception:
                pass

    def wait(self, timeout: float | None = None) -> dict | None:
        self.done.wait(timeout)
        return self.result


@dataclass
class _Seq:
    """One in-flight sequence: request plumbing + decode cursor."""

    seq_id: int
    req: GenerationRequest | None
    tokens: list[int]  # prompt + generated so far
    prompt_len: int
    max_new: int
    temperature: float
    top_k: int
    seed: int
    pages: list[int] = field(default_factory=list)
    n_fed: int = 0  # tokens written into the KV cache
    n_mirrored: int = 0  # tokens covered by the ledger mirror
    generated: list[int] = field(default_factory=list)
    trace_ctx: Any = None  # parsed parent SpanContext (or None)
    first_token_at: float | None = None
    deadline: float = 0.0
    tenant: str | None = None

    @property
    def next_token(self) -> int:
        return self.tokens[self.n_fed]

    @property
    def target_len(self) -> int:
        return self.prompt_len + self.max_new

    def meta(self, now: float) -> dict:
        """Resumable snapshot metadata (deadlines persist as REMAINING
        budget — monotonic clocks do not survive a process)."""
        return {
            "seq_id": self.seq_id,
            "tokens": list(self.tokens),
            "prompt_len": self.prompt_len,
            "max_new": self.max_new,
            "temperature": self.temperature,
            "top_k": self.top_k,
            "seed": self.seed,
            "n_fed": self.n_fed,
            "n_generated": len(self.generated),
            "remaining_ms": max((self.deadline - now) * 1000.0, 0.0),
            "tenant": self.tenant,
            "n_pages": len(self.pages),
        }


_M: dict | None = None


def _metrics() -> dict:
    global _M
    if _M is None:
        from pathway_tpu.observability import REGISTRY

        _M = {
            "tokens": REGISTRY.counter(
                "pathway_generate_tokens_total",
                "tokens generated, by replica and kind (sampled = "
                "returned to a client; prefill = prompt tokens fed "
                "through the decode path)",
                labelnames=("replica", "kind"),
            ),
            "batch": REGISTRY.histogram(
                "pathway_generate_decode_batch_size",
                "live sequences per decode step (before pad-ladder "
                "padding)",
            ),
            "occupancy": REGISTRY.gauge(
                "pathway_generate_page_pool_occupancy",
                "fraction of the KV page pool in use, by replica",
                labelnames=("replica",),
            ),
            "dropped": REGISTRY.counter(
                "pathway_generate_dropped_mid_decode_total",
                "generations dropped MID-decode by deadline "
                "propagation (504, pages reclaimed), by replica",
                labelnames=("replica",),
            ),
            "requests": REGISTRY.counter(
                "pathway_generate_requests_total",
                "generation requests, by replica and outcome",
                labelnames=("replica", "outcome"),
            ),
            "ttft": REGISTRY.histogram(
                "pathway_generate_ttft_seconds",
                "admission -> first sampled token, by replica",
                labelnames=("replica",),
            ),
            "steps": REGISTRY.counter(
                "pathway_generate_decode_steps_total",
                "decode steps executed, by replica",
                labelnames=("replica",),
            ),
        }
    return _M


class DecodeScheduler:
    """Continuous-batching decode loop over the paged KV cache."""

    def __init__(
        self,
        config: GenerateConfig | None = None,
        *,
        qos: QoSConfig | None = None,
        replica_label: str = "0",
        restore: bool = True,
        ledger: Any = None,
    ):
        self.config = config or GenerateConfig.from_env()
        # PATHWAY_SERVING_* overrides apply (deadline budget/clamp,
        # queue bound, ...) — the generation-serving doctor rule clears
        # its deadline WARNING on those env vars, so they must actually
        # govern this plane
        self.qos = qos or QoSConfig.from_env(
            QoSConfig(
                max_batch_size=self.config.max_batch, max_wait_ms=2.0
            )
        )
        self.label = str(replica_label)
        self.dcfg = self.config.decoder_config()
        from pathway_tpu.xpacks.llm import decoder as dec

        self._dec = dec
        self.params = dec.init_params(
            self.dcfg, seed=self.config.decoder_seed
        )
        self.k_pool, self.v_pool = dec.empty_pools(
            self.dcfg, self.config.n_pages
        )
        self.pool = PagePool(self.config.n_pages)
        self.ledger = KvLedger()
        if self.config.kernel == "auto":
            # the Pallas kernel where it compiles (a TPU), the pure-JAX
            # twin elsewhere — the mode comes from ops/backend.py, the
            # one place it is decided
            from pathway_tpu.ops.backend import pallas_interpret

            self.kernel = "ref" if pallas_interpret() else "pallas"
        else:
            self.kernel = self.config.kernel
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._active: list[_Seq] = []
        self._staged: list[GenerationRequest] = []
        self._waiting: list[GenerationRequest] = []
        self._seq_counter = 0
        self._step_count = 0
        self._n_params: int | None = None  # roofline: counted on demand
        self._stopping = False
        self._failed: str | None = None  # cause of a failed decode step
        # out-of-thread snapshot(): executed AT the step boundary by
        # the decode thread (the pools are donated into the jitted
        # step — touching them mid-step from another thread races the
        # donation)
        self._snap_waiters: list = []
        self.finished: dict[str, dict] = {}  # request_id -> result (bounded)
        m = _metrics()
        self._m_tokens = m["tokens"]
        self._m_batch = m["batch"]
        self._m_dropped = m["dropped"].labels(self.label)
        self._m_requests = m["requests"]
        self._m_ttft = m["ttft"].labels(self.label)
        self._m_steps = m["steps"].labels(self.label)
        import weakref

        ref = weakref.ref(self)
        m["occupancy"].labels(self.label).set_function(
            lambda: (
                s.pool.occupancy() if (s := ref()) is not None else 0.0
            )
        )
        if restore and self.config.store_root:
            self._restore(self.config.store_root)
        # Tenant Weave past the admission gate (ROADMAP gen (f)): with
        # a tenant ledger attached, every submitted generation carries
        # the ledger's WFQ virtual-finish tag and the batcher's heap
        # orders by (vfinish, deadline) — a hot tenant's decode backlog
        # drains BEHIND the tail's fresh requests, extending weighted
        # fairness from admission into decode batching.  None keeps the
        # plain-EDF plane byte-identical.
        self.tenant_ledger = ledger
        # Tick Scope memory provider: the generate plane's resident
        # bytes — device KV page pools, the KvLedger arrangements, and
        # the host mirror — under owner "generate:<label>" (weakref: a
        # dead scheduler drops out of the snapshot at the next pull)
        from pathway_tpu.observability import tickscope as _ts

        def _generate_memory(r=ref):
            s = r()
            if s is None:
                return {}
            parts = dict(s.ledger.resident_bytes())
            parts["k_pool_device"] = int(s.k_pool.nbytes)
            parts["v_pool_device"] = int(s.v_pool.nbytes)
            return parts

        _ts.register_memory_provider(
            f"generate:{self.label}", _generate_memory
        )
        self.batcher = MicroBatcher(
            self.qos,
            dispatch=self._dispatch,
            reject=self._reject,
            capacity=self._slots_free,
            name=f"pw-generate-{self.label}",
            # requests carry their own heap key: plain EDF (deadline),
            # or the ledger-stamped (vfinish, deadline) WFQ tag
            order=lambda r: r.order,
        )
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"pw-decode-{self.label}"
        )
        self._thread.start()

    # --- admission --------------------------------------------------------

    def pages_needed(self, req: GenerationRequest) -> int:
        total = len(req.prompt_tokens) + req.max_new_tokens
        return -(-total // self.config.page_size)

    def submit(self, req: GenerationRequest) -> None:
        """Admit one generation request (raises ShedError when it can
        never be served)."""
        total = len(req.prompt_tokens) + req.max_new_tokens
        if total > self.config.max_len:
            raise ShedError(
                400,
                f"prompt+max_tokens ({total}) exceeds the decoder bound "
                f"({self.config.max_len})",
                0.0,
            )
        if self.pages_needed(req) > self.pool.capacity:
            raise ShedError(
                503,
                f"request needs {self.pages_needed(req)} KV pages; the "
                f"pool holds {self.pool.capacity} "
                "(raise PATHWAY_GENERATE_PAGES)",
                1.0,
            )
        with self._lock:
            if self._failed is not None:
                raise ShedError(503, self._failed, 1.0)
            if self._stopping:
                raise ShedError(503, "generation scheduler stopped", 1.0)
            backlog = len(self._waiting) + len(self._staged)
        # the EDF heap is part of the backlog: with the active set full
        # the batcher never dispatches, so without this term the queue
        # bound could never fire and a burst would grow the heap (and
        # its per-request waiters) until every entry 504'd at flush
        backlog += len(self.batcher)
        ledger = self.tenant_ledger
        tag = None
        if ledger is not None:
            # may shed 429 tenant_rate: fairness holds at the decode
            # door too, not just the HTTP admission gate
            tag = ledger.admit(req.tenant, req.tenant_class)
            req.order = (tag, req.deadline)
        if backlog >= self.qos.max_queue:
            if ledger is not None:
                # never entered the queue: give the fair-share token
                # (and, when possible, the WFQ clock advance) back
                ledger.refund(req.tenant, req.tenant_class, tag)
            self._m_requests.labels(self.label, "shed_queue").inc()
            raise ShedError(
                429, "generation queue full", 0.5
            )
        self.batcher.put(req)
        if ledger is not None:
            ledger.commit(req.tenant)

    def _slots_free(self) -> int:
        # dispatch capacity for the batcher: free active-set slots
        with self._lock:
            return max(
                self.config.max_batch
                - len(self._active)
                - len(self._staged)
                - len(self._waiting),
                0,
            )

    def _dispatch(self, reqs: list) -> None:
        # batcher flush thread: sequences JOIN BETWEEN steps — stage
        # them and let the decode loop fold them in at its boundary
        if self.tenant_ledger is not None:
            for r in reqs:
                # advance WFQ virtual time at dispatch (same contract
                # as the gate): later arrivals floor here, so an idle
                # tenant cannot bank virtual credit
                self.tenant_ledger.note_dispatched(r.order)
        with self._lock:
            cause = self._failed
            if cause is None:
                self._staged.extend(reqs)
                self._cond.notify()
                return
        for r in reqs:  # queued before the failure, flushed after it
            r.finish({"status": 500, "error": cause})

    def _reject(self, req: Any, exc: BaseException) -> None:
        if isinstance(exc, DeadlineExceeded):
            self._m_requests.labels(self.label, "expired_queued").inc()
            req.finish(
                {
                    "status": 504,
                    "error": "deadline expired before decode started",
                }
            )
        else:
            self._m_requests.labels(self.label, "shed_queue").inc()
            status = getattr(exc, "status", 503)
            req.finish({"status": status, "error": str(exc) or "shed"})

    # --- the decode loop --------------------------------------------------

    def _run(self) -> None:
        while True:
            with self._cond:
                while (
                    not self._stopping
                    and not self._active
                    and not self._staged
                    and not self._waiting
                    and not self._snap_waiters
                ):
                    self._cond.wait(0.5)
                last_round = self._stopping and not self._active
            self._serve_snapshot_waiters()
            if last_round:
                return
            try:
                self._step()
            except Exception as exc:
                # Nothing a client sends can make a step raise (tokens
                # are bytes, callbacks are guarded one by one), so this
                # is the device, the compiler or a bug.  Fail-stop: the
                # batch answers 500 naming the cause, every later
                # submit() sheds 503 with it, stats()/health carry it,
                # and the loop ends — no request is ever answered by a
                # plane whose decode step does not run.
                import logging

                logging.getLogger("pathway_tpu").exception(
                    "generate: decode step failed; generation stopped"
                )
                cause = f"decode step failed: {type(exc).__name__}: {exc}"
                with self._lock:
                    self._failed = cause
                    doomed = self._active + self._waiting + self._staged
                    self._active, self._waiting, self._staged = [], [], []
                for item in doomed:
                    result = {"status": 500, "error": cause}
                    if isinstance(item, _Seq):
                        self._finish_seq(item, result, outcome="error")
                    else:
                        item.finish(result)
                return

    def _sweep_expired(self, now: float) -> None:
        """Deadline propagation MID-decode: expired actives answer 504
        and their pages return to the pool before any further step."""
        with self._lock:
            dead = [s for s in self._active if s.deadline < now]
            self._active = [s for s in self._active if s.deadline >= now]
            dead_wait = [r for r in self._waiting if r.deadline < now]
            self._waiting = [
                r for r in self._waiting if r.deadline >= now
            ]
        if dead:
            # Fleet Lens: a mid-decode deadline drop is an incident (a
            # client saw a 504 after tokens had already been minted) —
            # one journal event per sweep, not per sequence
            from pathway_tpu.observability.journal import (
                record as journal_record,
            )

            journal_record(
                "mid-decode-drop",
                f"{len(dead)} generation(s) dropped mid-decode by "
                "deadline propagation",
                replica=self.label,
                dropped=len(dead),
                tokens_lost=sum(len(s.generated) for s in dead),
            )
        for s in dead:
            self._m_dropped.inc()
            self._finish_seq(
                s,
                {
                    "status": 504,
                    "error": "deadline expired mid-decode",
                    "tokens": len(s.generated),
                },
                outcome="dropped_mid_decode",
            )
        for r in dead_wait:
            self._m_requests.labels(self.label, "expired_queued").inc()
            r.finish(
                {
                    "status": 504,
                    "error": "deadline expired waiting for KV pages",
                }
            )

    def _admit_staged(self, now: float) -> None:
        """Fold staged + page-starved requests into the active set (at
        the step boundary, never mid-step)."""
        with self._lock:
            incoming = self._waiting + self._staged
            self._waiting, self._staged = [], []
        for req in incoming:
            with self._lock:
                room = len(self._active) < self.config.max_batch
            pages = (
                self.pool.try_alloc(self.pages_needed(req))
                if room
                else None
            )
            if pages is None:
                with self._lock:
                    self._waiting.append(req)  # retried next boundary
                continue
            with self._lock:
                self._seq_counter += 1
                seq_id = self._seq_counter
            from pathway_tpu.observability import tracing

            seq = _Seq(
                seq_id=seq_id,
                req=req,
                tokens=list(req.prompt_tokens),
                prompt_len=len(req.prompt_tokens),
                max_new=req.max_new_tokens,
                temperature=req.temperature,
                top_k=req.top_k,
                seed=req.seed,
                pages=pages,
                trace_ctx=tracing.parse_traceparent(req.traceparent),
                deadline=req.deadline,
                tenant=req.tenant,
            )
            with self._lock:
                self._active.append(seq)

    def _page_table_rows(self, seqs: list[_Seq], bucket: int) -> np.ndarray:
        pt = np.zeros((bucket, self.dcfg.max_pages), np.int32)
        for i, s in enumerate(seqs):
            pt[i, : len(s.pages)] = s.pages
        return pt

    def _step(self) -> None:
        now = time.monotonic()
        self._sweep_expired(now)
        self._admit_staged(now)
        with self._lock:
            batch = list(self._active[: self.config.max_batch])
        if not batch:
            return
        import jax.numpy as jnp

        from pathway_tpu.observability import tracing

        bucket = self.qos.bucket_for(len(batch))
        tokens = np.zeros(bucket, np.int32)
        positions = np.zeros(bucket, np.int32)
        seq_lens = np.zeros(bucket, np.int32)
        for i, s in enumerate(batch):
            tokens[i] = s.next_token
            positions[i] = s.n_fed
            seq_lens[i] = s.n_fed + 1
        pt = self._page_table_rows(batch, bucket)
        span = tracing.get_tracer().span(
            "generate.decode_step",
            replica=self.label,
            batch=len(batch),
            bucket=bucket,
        )
        with span:
            _rt0 = time.perf_counter()
            logits, self.k_pool, self.v_pool = self._dec.decode_step(
                self.params,
                jnp.asarray(tokens),
                jnp.asarray(positions),
                self.k_pool,
                self.v_pool,
                jnp.asarray(pt),
                jnp.asarray(seq_lens),
                cfg=self.dcfg,
                kernel=self.kernel,
            )
            host_logits = np.asarray(logits)
        # Tick Scope roofline, family "paged_attention": one decode step
        # at this bucket. Analytic FLOPs (2 * params * batch for the
        # matmuls + the attention read over the live context) — the
        # pallas kernel has no XLA cost model off-TPU, and lower().
        # compile() here would double every bucket's compile time.
        try:
            from pathway_tpu.observability import tickscope as _ts

            _rl = _ts.roofline()
            _key = f"decode_b{bucket}_{self.kernel}"
            if not _rl.known("paged_attention", _key):
                if self._n_params is None:
                    import jax as _jax

                    self._n_params = sum(
                        l.size
                        for l in _jax.tree_util.tree_leaves(self.params)
                    )
                ctx = int(seq_lens.sum())
                _rl.register(
                    "paged_attention",
                    _key,
                    2.0 * self._n_params * bucket
                    + 4.0
                    * self.dcfg.n_layers
                    * self.dcfg.n_heads
                    * self.dcfg.head_dim
                    * ctx,
                    source="analytic",
                )
            _rl.observe(
                "paged_attention", _key, time.perf_counter() - _rt0
            )
        except Exception:  # pragma: no cover - defensive
            pass
        self._m_batch.observe(len(batch))
        self._m_steps.inc()
        finished: list[tuple[_Seq, dict]] = []
        for i, s in enumerate(batch):
            s.n_fed += 1
            if s.n_fed < s.prompt_len:
                # still feeding the prompt — prefill work is visible in
                # the token accounting (it dominates TTFT cost)
                self._m_tokens.labels(self.label, "prefill").inc()
                continue
            tok = self._dec.sample_token(
                host_logits[i],
                temperature=s.temperature,
                top_k=s.top_k,
                seed=s.seed,
                step=len(s.generated),
            )
            if s.first_token_at is None:
                s.first_token_at = time.monotonic()
                ttft = s.first_token_at - (
                    s.req.created_at if s.req is not None else now
                )
                self._m_ttft.observe(ttft)
                # prefill completion marker: admission -> first sampled
                # token, parented into the request's trace (the span is
                # emitted AT completion so no context token outlives a
                # loop iteration)
                with tracing.get_tracer().span(
                    "generate.prefill",
                    parent=s.trace_ctx,
                    root=s.trace_ctx is None,
                    replica=self.label,
                    prompt_tokens=s.prompt_len,
                    ttft_ms=round(ttft * 1000.0, 3),
                ):
                    pass
            s.generated.append(tok)
            s.tokens.append(tok)
            self._m_tokens.labels(self.label, "sampled").inc()
            done = (
                tok == self._dec.EOS
                or len(s.generated) >= s.max_new
                or s.n_fed + 1 >= self.config.max_len
            )
            if s.req is not None and s.req.on_token is not None:
                try:
                    s.req.on_token(tok, done)
                except Exception:
                    pass
            if done:
                finished.append(
                    (
                        s,
                        {
                            "status": 200,
                            "tokens": list(s.generated),
                            "text": self._dec.decode_tokens(s.generated),
                            "token_count": len(s.generated),
                        },
                    )
                )
        with self._lock:
            self._step_count += 1
            step_n = self._step_count
            done_ids = {id(s) for s, _ in finished}
            self._active = [
                s for s in self._active if id(s) not in done_ids
            ]
        for s, result in finished:
            self._finish_seq(s, result, outcome="ok")
        if finished:
            self.batcher.notify()  # active-set slots freed
        if (
            self.config.snapshot_every > 0
            and self.config.store_root
            and step_n % self.config.snapshot_every == 0
        ):
            self.snapshot()
        from pathway_tpu.testing import faults

        plan = faults.active()
        if plan is not None:
            plan.on_decode_step(step_n)

    def _finish_seq(
        self, seq: _Seq, result: dict, *, outcome: str
    ) -> None:
        """Answer + reclaim: pages return to the pool and the ledger
        retracts the sequence's rows the moment it leaves the plane."""
        with self._lock:  # vs stop(): exactly one side frees
            pages, seq.pages = seq.pages, []
        if pages:
            self.pool.free(pages)
        self.ledger.drop_seq(seq.seq_id)
        self._m_requests.labels(self.label, outcome).inc()
        if seq.req is not None:
            seq.req.finish(result)
            rid = seq.req.request_id
        else:
            rid = f"restored-{seq.seq_id}"
        self.finished[rid] = result
        while len(self.finished) > 256:
            self.finished.pop(next(iter(self.finished)))

    # --- durability -------------------------------------------------------

    def _mirror(self) -> None:
        """Mirror pages that changed since the last mirror (earlier
        pages are immutable once full) + resume metadata into the
        ledger arrangements."""
        now = time.monotonic()
        p = self.config.page_size
        with self._lock:
            # pages captured under the SAME lock _finish_seq swaps them
            # under: an out-of-thread snapshot() racing a completion
            # must never index a reclaimed (possibly reallocated) page
            actives = [(s, list(s.pages)) for s in self._active]
        k_host = None
        v_host = None
        for s, pages in actives:
            if not pages:
                continue  # finished between capture and here
            first_dirty = s.n_mirrored // p
            last = max(s.n_fed - 1, 0) // p
            if s.n_fed > 0 and last < len(pages):
                if k_host is None:
                    # one bulk device->host pull per mirror pass
                    k_host = np.asarray(self.k_pool)
                    v_host = np.asarray(self.v_pool)
                for page_idx in range(first_dirty, last + 1):
                    pid = pages[page_idx]
                    self.ledger.put_page(
                        s.seq_id,
                        page_idx,
                        k_host[:, pid].copy(),
                        v_host[:, pid].copy(),
                    )
            s.n_mirrored = s.n_fed
            self.ledger.put_seq(s.seq_id, s.meta(now))

    def _snapshot_inline(self) -> dict | None:
        root = self.config.store_root
        if not root:
            return None
        self._mirror()
        return self.ledger.snapshot(root)

    def _serve_snapshot_waiters(self) -> None:
        with self._lock:
            waiters, self._snap_waiters = self._snap_waiters, []
        for holder, ev in waiters:
            try:
                holder["result"] = self._snapshot_inline()
            except Exception as exc:
                holder["error"] = exc
            ev.set()

    def snapshot(self, timeout: float = 30.0) -> dict | None:
        """Mirror + write the incremental arrangement snapshot.

        Safe from any thread: an out-of-thread call is executed AT the
        next step boundary by the decode thread (the jitted step
        donates the pools, so another thread must never read them
        mid-step); the decode thread's own periodic call runs inline."""
        if (
            threading.current_thread() is self._thread
            or not self._thread.is_alive()
        ):
            return self._snapshot_inline()
        holder: dict = {}
        ev = threading.Event()
        with self._cond:
            self._snap_waiters.append((holder, ev))
            self._cond.notify()
        if not ev.wait(timeout):
            raise TimeoutError(
                "decode loop did not reach a step boundary in time"
            )
        if "error" in holder:
            raise holder["error"]
        return holder.get("result")

    def _restore(self, root: str) -> None:
        led = KvLedger.restore(root)
        if led is None:
            return
        self.ledger = led
        now = time.monotonic()
        pages = led.live_pages()
        import jax.numpy as jnp

        k_pool = np.array(self.k_pool)  # writable host copies
        v_pool = np.array(self.v_pool)
        assigned: dict[tuple[int, int], int] = {}
        for (seq_id, page_idx), (k_page, v_page, _ident) in pages.items():
            got = self.pool.try_alloc(1)
            if got is None:  # pool shrank across the restart
                raise RuntimeError(
                    "KV page pool too small to restore the snapshot "
                    f"(needs > {self.pool.capacity} pages)"
                )
            pid = got[0]
            assigned[(seq_id, page_idx)] = pid
            k_pool[:, pid] = np.asarray(k_page, np.float32)
            v_pool[:, pid] = np.asarray(v_page, np.float32)
        self.k_pool = jnp.asarray(k_pool)
        self.v_pool = jnp.asarray(v_pool)
        for seq_id, meta in led.live_seqs().items():
            n_fed = int(meta["n_fed"])
            n_pages_owned = int(
                meta.get(
                    "n_pages",
                    -(-max(n_fed, 1) // self.config.page_size),
                )
            )
            page_ids: list[int] = []
            for page_idx in range(n_pages_owned):
                pid = assigned.get((seq_id, page_idx))
                if pid is None:
                    # a page the mirror had not covered yet (or a page
                    # reserved but never written): fresh allocation
                    got = self.pool.try_alloc(1)
                    if got is None:
                        raise RuntimeError(
                            "KV page pool too small to restore"
                        )
                    pid = got[0]
                page_ids.append(pid)
            gen_count = int(meta["n_generated"])
            toks = [int(t) for t in meta["tokens"]]
            seq = _Seq(
                seq_id=seq_id,
                req=None,  # the client died with the old process
                tokens=toks,
                prompt_len=int(meta["prompt_len"]),
                max_new=int(meta["max_new"]),
                temperature=float(meta["temperature"]),
                top_k=int(meta["top_k"]),
                seed=int(meta["seed"]),
                pages=page_ids,
                n_fed=n_fed,
                n_mirrored=n_fed,
                generated=toks[
                    len(toks) - gen_count:] if gen_count else [],
                deadline=now + float(meta["remaining_ms"]) / 1000.0,
                tenant=meta.get("tenant"),
            )
            self._seq_counter = max(self._seq_counter, seq_id)
            self._active.append(seq)
        self.restored_seqs = len(self._active)

    # --- introspection / lifecycle ---------------------------------------

    def stats(self) -> dict:
        with self._lock:
            return {
                "active_seqs": len(self._active),
                "waiting": len(self._waiting) + len(self._staged),
                "decode_steps": self._step_count,
                "free_pages": self.pool.free_pages,
                "page_capacity": self.pool.capacity,
                "kernel": self.kernel,
                "failed": self._failed,
            }

    def drain(self, timeout: float = 30.0) -> bool:
        """Finish everything admitted; returns False on timeout."""
        deadline = time.monotonic() + timeout
        self.batcher.drain()
        while time.monotonic() < deadline:
            with self._lock:
                idle = (
                    not self._active
                    and not self._staged
                    and not self._waiting
                )
            if idle and not len(self.batcher):
                return True
            time.sleep(0.02)
        return False

    def stop(self) -> None:
        with self._lock:
            self._stopping = True
            doomed = self._active + self._waiting + self._staged
            self._active, self._waiting, self._staged = [], [], []
            self._cond.notify()
        self.batcher.close(
            reject_queued=ShedError(
                503, "generation scheduler stopped", 1.0
            )
        )
        for item in doomed:
            req = item.req if isinstance(item, _Seq) else item
            if isinstance(item, _Seq):
                with self._lock:
                    pages, item.pages = item.pages, []
                if pages:
                    self.pool.free(pages)
            if req is not None:
                req.finish(
                    {"status": 503, "error": "scheduler stopped"}
                )
        self._thread.join(timeout=5.0)
