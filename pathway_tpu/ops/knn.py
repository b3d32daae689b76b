"""Dense top-k KNN on TPU — the MXU-native replacement for the reference's
external index family (reference: src/external_integration/
brute_force_knn_integration.rs:22 ndarray matmul top-k, and
usearch_integration.rs HNSW; pattern: TPU-KNN, arXiv 2206.14286).

Design:
- corpus lives in HBM as a padded [capacity, D] array (+ validity mask) so
  shapes stay static across ticks — no recompilation as documents stream in;
  capacity grows by doubling (each size compiles once).
- a change reaches the device as a scatter of the changed rows into the
  resident arrays (raw rows, validity, every prepared copy), donated to one
  jitted program of one shape; the whole mirror is uploaded only where
  there is no device copy to patch, or an eighth of it or more changed.
- scores = queries @ corpus.T runs in bfloat16 on the MXU with f32
  accumulation; invalid slots are masked to -inf, and the exact top-k of
  the [B, N] scores is taken by selection where N is large beside k
  (`topk_stage1`): the maximum of every 128-column block, the k blocks
  with the largest maxima, then `lax.top_k` over those blocks' k x 128
  scores. Exact: a block whose maximum lies above the k-th best score
  holds one of the at most k - 1 scores above it, so the k best blocks
  hold every such score and enough equal to it to fill the k places.
- multi-chip: corpus rows are sharded over the mesh's 'data' axis via
  shard_map — each device computes a local top-k, candidates are
  all-gathered over ICI and merged with a final top-k (the TPU-KNN
  recall@peak-FLOPs recipe).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.observability import device_scopes
from pathway_tpu.observability.device_scopes import scope
from pathway_tpu.observability.tracing import NOOP_SPAN, get_tracer


# The scope names below are op metadata only (nothing computed changes): a
# profiler capture shows the scan, the top-k and the corpus preparation
# under these names instead of XLA's generated ones (device_scopes).


@scope("knn.scores")
def _scores(
    queries: jax.Array, corpus: jax.Array, metric: str, bf16: bool
) -> jax.Array:
    if metric == "cosine":
        qn = queries / (
            jnp.linalg.norm(queries, axis=-1, keepdims=True) + 1e-30
        )
        cn = corpus / (jnp.linalg.norm(corpus, axis=-1, keepdims=True) + 1e-30)
    else:
        qn, cn = queries, corpus
    if bf16:
        qn = qn.astype(jnp.bfloat16)
        cn = cn.astype(jnp.bfloat16)
    dots = jax.lax.dot_general(
        qn,
        cn,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if metric == "l2sq":
        q2 = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
        c2 = jnp.sum(corpus.astype(jnp.float32) ** 2, axis=-1)
        # negative squared distance so that bigger == closer
        return -(q2 - 2.0 * dots + c2[None, :])
    return dots


# Columns in a block of the selecting first stage: one row of a vector
# register's tile, so a block is one contiguous 512 B slice of the scores.
SELECT_BLOCK = 128


def topk_stage1(n: int, k: int) -> str:
    """How ``_masked_topk`` starts on k of n columns, from the shapes alone:
    "blockmax" selects (the k blocks with the largest maxima; no sort of
    size n), "sort" is ``lax.top_k`` as it was (over 1,024-column blocks
    first where n is large). Selecting pays while its ``k * SELECT_BLOCK``
    candidates are few beside n; large k or small n keep the old path (on
    a v5e the two cost the same at the edge, PERF.md section 6, PR 29)."""
    return "blockmax" if 8 * k * SELECT_BLOCK <= n else "sort"


def _blockmax_topk(s: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Top-k of [B, N] scores by selection. The k blocks of
    ``SELECT_BLOCK`` adjacent columns with the largest maxima hold the
    answer: with t the k-th best score, a block with a maximum above t
    holds one of the at most k - 1 scores above t, so fewer than k blocks
    have one, and the rest of the k places go to blocks whose maximum is
    t. Blocks are taken and candidates laid out in ascending row order, so
    a tie goes where ``lax.top_k`` sends it: to the lowest row wherever its
    lowering keeps tied values in order (the CPU's does; the TPU's swapped
    one tied pair among 131,072 candidates at k = 1,024).

    The scores are viewed as [B/8, N/128, 8, 128], which on the TPU is how
    a [B, N] float32 array is tiled anyway: the block maxima and the
    gather of the winning blocks read it in place, so the scores are
    written once and read twice."""
    b, n = s.shape
    w = SELECT_BLOCK
    pad = -n % w
    if pad:
        s = jnp.pad(s, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    nblk = (n + pad) // w
    r = math.gcd(b, 8)
    tiles = s.reshape(b // r, r, nblk, w).transpose(0, 2, 1, 3)
    maxima = tiles.max(-1).transpose(0, 2, 1).reshape(b, nblk)
    _, blocks = jax.lax.top_k(maxima, k)
    blocks = jnp.sort(blocks, axis=-1)  # [B, k], ascending
    q = jnp.arange(b)[:, None]
    cand = tiles[q // r, blocks, q % r].reshape(b, k * w)
    scores, pos = jax.lax.top_k(cand, k)
    idx = jnp.take_along_axis(blocks, pos // w, axis=1) * w + pos % w
    return scores, idx


@scope("knn.topk")
def _masked_topk(s: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Exact top-k over [B, N] scores; ``topk_stage1`` picks the first
    stage. The old one takes the top-k of every 1,024-column block and
    merges the winners (every global top-k element is within the top-k of
    its own block); XLA's TPU backend lowers it to a full sort of each
    block."""
    n = s.shape[-1]
    if topk_stage1(n, k) == "blockmax":
        return _blockmax_topk(s, k)
    blk = 1024
    if n >= 64 * blk and k <= blk:
        nblk = (n + blk - 1) // blk
        pad = nblk * blk - n
        if pad:
            s = jnp.pad(s, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        sb = s.reshape(s.shape[0], nblk, blk)
        sc1, ix1 = jax.lax.top_k(sb, k)  # [B, nblk, k]
        gidx = ix1 + (jnp.arange(nblk, dtype=ix1.dtype) * blk)[None, :, None]
        sc2, pos = jax.lax.top_k(sc1.reshape(s.shape[0], -1), k)
        idx = jnp.take_along_axis(gidx.reshape(s.shape[0], -1), pos, axis=1)
        return sc2, idx
    return jax.lax.top_k(s, k)


@functools.partial(device_scopes.jit, static_argnames=("k", "metric", "bf16"))
def dense_topk(
    queries: jax.Array,  # [B, D] f32
    corpus: jax.Array,  # [N, D] f32 (padded)
    valid: jax.Array,  # [N] bool
    k: int,
    metric: str = "cosine",
    bf16: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (scores [B, k] f32, indices [B, k] i32); invalid rows get
    -inf scores and index -1."""
    s = _scores(queries, corpus, metric, bf16)
    s = jnp.where(valid[None, :], s, -jnp.inf)
    scores, idx = _masked_topk(s, k)
    idx = jnp.where(jnp.isfinite(scores), idx, -1)
    return scores, idx


# --- prepared-corpus fast path ---------------------------------------------
# Normalization + bf16 cast of the corpus is O(N*D) — done once per corpus
# change, NOT per query. Per-query work is one [B,D]x[D,N] MXU matmul + topk.


@functools.partial(device_scopes.jit, static_argnames=("metric", "bf16"))
@scope("corpus.prepare")
def prepare_corpus(corpus: jax.Array, metric: str, bf16: bool = True):
    """Returns (prep [N,D], c2 [N]) — prep is normalized (cosine) and cast;
    c2 is the squared-norm column needed by l2sq."""
    c2 = jnp.sum(corpus.astype(jnp.float32) ** 2, axis=-1)
    if metric == "cosine":
        prep = corpus / (jnp.linalg.norm(corpus, axis=-1, keepdims=True) + 1e-30)
    else:
        prep = corpus
    if bf16:
        prep = prep.astype(jnp.bfloat16)
    return prep, c2


@functools.partial(device_scopes.jit, static_argnames=("k", "metric", "bf16"))
def dense_topk_prepared(
    queries: jax.Array,  # [B, D] f32
    prep: jax.Array,  # [N, D] prepared (normalized/cast)
    c2: jax.Array,  # [N] squared norms (l2sq only)
    valid: jax.Array,  # [N] bool
    k: int,
    metric: str = "cosine",
    bf16: bool = True,
) -> tuple[jax.Array, jax.Array]:
    with scope("knn.scores"):
        if metric == "cosine":
            q = queries / (
                jnp.linalg.norm(queries, axis=-1, keepdims=True) + 1e-30
            )
        else:
            q = queries
        if bf16:
            q = q.astype(jnp.bfloat16)
        dots = jax.lax.dot_general(
            q,
            prep,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if metric == "l2sq":
            q2 = jnp.sum(
                queries.astype(jnp.float32) ** 2, axis=-1, keepdims=True
            )
            s = -(q2 - 2.0 * dots + c2[None, :])
        else:
            s = dots
        s = jnp.where(valid[None, :], s, -jnp.inf)
    scores, idx = _masked_topk(s, k)
    idx = jnp.where(jnp.isfinite(scores), idx, -1)
    return scores, idx


def shard_base_indices(n: int, n_shards: int) -> np.ndarray:
    """Per-row base offset of its shard (local->global index mapping in the
    sharded merge); single source for sharded_topk and the multi-process
    sharded_topk_global."""
    per = n // n_shards
    return (np.arange(n) // per * per).astype(np.int32)


@functools.partial(
    device_scopes.jit, static_argnames=("k", "metric", "bf16", "mesh", "axis")
)
def _sharded_topk_impl(queries, corpus, valid, base_idx, k, metric, bf16, mesh, axis):
    from jax.sharding import PartitionSpec as P

    def local(q, c, v, b):
        s = _scores(q, c, metric, bf16)
        s = jnp.where(v[None, :], s, -jnp.inf)
        kk = min(k, c.shape[0])
        sc, ix = _masked_topk(s, kk)
        ix = ix + b[0]  # local -> global row index
        # gather candidates from all shards over ICI, merge with final top-k
        sc_all = jax.lax.all_gather(sc, axis, axis=1, tiled=True)
        ix_all = jax.lax.all_gather(ix, axis, axis=1, tiled=True)
        sc_f, pos = jax.lax.top_k(sc_all, k)
        ix_f = jnp.take_along_axis(ix_all, pos, axis=1)
        ix_f = jnp.where(jnp.isfinite(sc_f), ix_f, -1)
        return sc_f, ix_f

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axis, None), P(axis), P(axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )(queries, corpus, valid, base_idx)


def sharded_topk(
    queries: jax.Array,
    corpus: jax.Array,
    valid: jax.Array,
    k: int,
    *,
    mesh: Any,
    axis: str = "data",
    metric: str = "cosine",
    bf16: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Multi-chip KNN: corpus sharded over ``axis``; queries replicated;
    local top-k per shard + all-gather merge (TPU-KNN pattern)."""
    n = corpus.shape[0]
    n_shards = mesh.shape[axis]
    assert n % n_shards == 0, "pad corpus to a multiple of the shard count"
    base_idx = shard_base_indices(n, n_shards)
    return _sharded_topk_impl(
        queries, corpus, valid, jnp.asarray(base_idx), k, metric, bf16, mesh, axis
    )


def _ready_if_live(span: Any, arrays: Any) -> None:
    """A span around a transfer or a program is truthful only if it ends
    when the device has the data (``jnp.asarray`` of 2.4 GB returns after
    0.5 ms, the data arrive after 230), so a live span waits for what it
    produced. Each wait is a round trip of its own, some 3 ms on a v5e
    (PERF.md section 6, PR 26), so a refresh has one: the whole upload
    waits for the mirror's copy, the whole ``prepare_corpus`` for a new
    prepared copy, and a scatter refresh once, for the arrays its program
    wrote (its ``corpus.upload`` span ends at the hand-over of the changed
    rows). A disabled tracer's shared no-op span syncs nothing."""
    if span is not NOOP_SPAN:
        jax.block_until_ready(arrays)


# Changed rows go to the device in chunks of this many, whatever their
# number, so one scatter program serves a one-row change and a 256-row tick
# alike (3.1 MB a chunk at dim 768). The last chunk is padded with a slot
# past the capacity, which the scatter drops.
SCATTER_ROWS = 1024


@functools.partial(
    device_scopes.jit,
    static_argnames=("sharding", "valid_sharding"),
    donate_argnames=("device", "valid", "prepared"),
)
def _scatter_rows(
    device: jax.Array,  # [N, D] f32, donated
    valid: jax.Array,  # [N] bool, donated
    prepared: dict,  # (metric, bf16) -> (prep [N, D], c2 [N]), donated
    slots: jax.Array,  # [SCATTER_ROWS] i32; >= N is dropped
    rows: jax.Array,  # [SCATTER_ROWS, D] f32
    row_valid: jax.Array,  # [SCATTER_ROWS] bool
    sharding: Any = None,
    valid_sharding: Any = None,
):
    """Writes the changed rows into the resident arrays: the raw row, its
    validity bit and, for every prepared copy, what ``prepare_corpus``
    makes of that row (it is row-wise). The outputs keep the corpus's
    sharding where it has one."""

    def put(array, updates, pin):
        # no indices_are_sorted / unique_indices, true as they would be:
        # with them XLA's TPU scatter passes over the whole operand, 16 ms
        # at 786,432 x 768 against 1.2 without (PERF.md section 6, PR 27)
        out = array.at[slots].set(updates, mode="drop")
        if pin is not None:
            out = jax.lax.with_sharding_constraint(out, pin)
        return out

    fresh = {}
    for (metric, bf16), (prep, c2) in prepared.items():
        prep_rows, c2_rows = prepare_corpus(rows, metric, bf16)
        fresh[metric, bf16] = (
            put(prep, prep_rows, sharding),
            put(c2, c2_rows, valid_sharding),
        )
    return put(device, rows, sharding), put(valid, row_valid, valid_sharding), fresh


# What the one-chip search scans: a prepared copy of float32 rows. On a TPU
# a float32 matmul at default precision multiplies in bf16 all the same
# (measured on a v5e, PR 21: score error 3.5e-4, recall@10 0.986 vs exact
# float32 on gaussian rows; "highest" precision gives 7.5e-8 / 1.0). The
# ids are the contract; scores carry about three digits there.
SCAN_BF16 = False


class DeviceCorpus:
    """Growable padded corpus living on device.

    Host keeps a float32 mirror, which is the truth. The device holds a
    copy of it, a validity mask and the prepared copies searches asked
    for. ``upsert``/``remove`` write the mirror and note the slot; the
    next ``device_arrays``/``prepared_arrays`` call brings the device up
    to date by handing over the changed rows alone and scattering them
    into the arrays it holds, which are donated to that program: whoever
    took the arrays before a change must not use them after the refresh.
    The whole mirror is uploaded only where there is no device copy to
    patch (the first use, a new capacity, ``mirror_replaced``) or where
    an eighth of it or more changed. Capacity doubles ⇒ O(log N) distinct
    compiled shapes, and the scatter has one shape per capacity and set of
    prepared copies."""

    def __init__(
        self,
        dim: int,
        capacity: int = 1024,
        sharding: Any = None,
        valid_sharding: Any = None,
    ):
        self.valid_sharding = valid_sharding
        self._replicated = None
        self.dim = dim
        # align capacity to lcm(1024, n_shards): a multiple of 1024 (the
        # shapes every program here was compiled and measured at), AND
        # divisible by the mesh shard count so sharded_topk can split rows
        # evenly; padding is masked by `valid`
        align = 1024
        if sharding is not None:
            import math

            from jax.sharding import NamedSharding, PartitionSpec

            n_dev = int(np.prod(list(sharding.mesh.shape.values())))
            align = math.lcm(1024, max(1, n_dev))
            # where a chunk of changed rows goes: whole, to every shard
            self._replicated = NamedSharding(sharding.mesh, PartitionSpec())
        self._align = align
        self.capacity = -(-max(1024, capacity) // align) * align
        self.host = np.zeros((self.capacity, dim), dtype=np.float32)
        self.valid_host = np.zeros(self.capacity, dtype=bool)
        self.free: list[int] = list(range(self.capacity - 1, -1, -1))
        self.slot_of: dict[int, int] = {}  # row key -> slot
        self.key_of: dict[int, int] = {}  # slot -> row key
        # no device copy yet (None) means the next refresh uploads the
        # whole mirror; with one, `_changed` holds the slots it lacks
        self._device: jax.Array | None = None
        self._device_valid: jax.Array | None = None
        self._prepared: dict[tuple[str, bool], tuple[jax.Array, jax.Array]] = {}
        self._changed: set[int] = set()
        self.sharding = sharding
        # every device program run, by what fixes its shape (device_programs)
        self._ran: set[tuple] = set()
        device_scopes.register(self)

    def __len__(self) -> int:
        return len(self.slot_of)

    def device_programs(self):
        """What ``device_scopes.tables()`` lowers again: the preparations,
        scatters and searches made, each at the capacity it ran at."""
        struct = jax.ShapeDtypeStruct

        def corpus_arrays(capacity, copies=()):
            rows = struct((capacity, self.dim), jnp.float32, sharding=self.sharding)
            flags = struct((capacity,), jnp.bool_, sharding=self.valid_sharding)
            prepared = {
                (metric, bf16): (
                    struct(rows.shape, jnp.bfloat16 if bf16 else jnp.float32, sharding=self.sharding),
                    struct(flags.shape, jnp.float32, sharding=self.valid_sharding),
                )
                for metric, bf16 in copies
            }
            return rows, flags, prepared

        for program, capacity, *rest in sorted(self._ran, key=repr):
            if program == "prepare":
                metric, bf16 = rest
                rows, _flags, _ = corpus_arrays(capacity)
                yield f"rows[{capacity}] {metric}", prepare_corpus, (rows, metric, bf16), {}
            elif program == "scatter":
                rows, flags, prepared = corpus_arrays(capacity, rest[0])
                chunk = (
                    struct((SCATTER_ROWS,), jnp.int32, sharding=self._replicated),
                    struct((SCATTER_ROWS, self.dim), jnp.float32, sharding=self._replicated),
                    struct((SCATTER_ROWS,), jnp.bool_, sharding=self._replicated),
                )
                yield (
                    f"rows[{capacity}] copies{len(prepared)}",
                    _scatter_rows,
                    (rows, flags, prepared, *chunk),
                    {"sharding": self.sharding, "valid_sharding": self.valid_sharding},
                )
            else:
                shape, dtype, k, metric = rest
                queries = struct(shape, jax.dtypes.canonicalize_dtype(dtype))
                label = f"queries{list(shape)} rows[{capacity}] k{k} {metric}"
                if program == "xla":  # the span's name for the one-chip search
                    rows, flags, prepared = corpus_arrays(capacity, [(metric, SCAN_BF16)])
                    prep, c2 = prepared[metric, SCAN_BF16]
                    yield label, dense_topk_prepared, (queries, prep, c2, flags, k), {
                        "metric": metric, "bf16": SCAN_BF16,
                    }
                else:  # "sharded": sharded_topk's call of its program
                    rows, flags, _ = corpus_arrays(capacity)
                    mesh, axis = self.sharding.mesh, self.sharding.spec[0]
                    base = struct((capacity,), jnp.int32)
                    yield label, _sharded_topk_impl, (queries, rows, flags, base, k, metric, True, mesh, axis), {}

    def upsert(self, key: int, vector: np.ndarray) -> None:
        slot = self.slot_of.get(key)
        if slot is None:
            if not self.free:
                self._grow()
            slot = self.free.pop()
            self.slot_of[key] = slot
            self.key_of[slot] = key
        self.host[slot] = vector
        self.valid_host[slot] = True
        if self._device is not None:
            self._changed.add(slot)

    def remove(self, key: int) -> None:
        slot = self.slot_of.pop(key, None)
        if slot is None:
            return
        self.key_of.pop(slot, None)
        self.valid_host[slot] = False
        self.free.append(slot)
        if self._device is not None:
            self._changed.add(slot)

    def _grow(self) -> None:
        old_cap = self.capacity
        self.capacity *= 2
        host = np.zeros((self.capacity, self.dim), dtype=np.float32)
        host[:old_cap] = self.host
        self.host = host
        valid = np.zeros(self.capacity, dtype=bool)
        valid[:old_cap] = self.valid_host
        self.valid_host = valid
        self.free.extend(range(self.capacity - 1, old_cap - 1, -1))
        self.mirror_replaced()

    def mirror_replaced(self) -> None:
        """The host mirror is another array than the device copy was made
        from (a new capacity, a restored state, rows written past
        ``upsert``): the device copies are dropped and the next refresh
        uploads the whole mirror."""
        self._device = self._device_valid = None
        self._prepared.clear()
        self._changed.clear()

    def _refresh(self) -> None:
        """Brings the device copies up to date with the mirror."""
        if self._device is not None and not self._changed:
            return
        # a row costs a scatter some 1.7 us (a 1,024-row chunk: gather,
        # three transfers, one program) and the whole upload 0.15-0.3 us at
        # 10 GB/s (v5e, dims 384-768; PERF.md section 6, PR 27)
        if self._device is None or 8 * len(self._changed) >= self.capacity:
            self._upload_all()
            return
        try:
            self._scatter_changed()
        except BaseException:
            # the donated arrays may be gone: upload again next time
            self.mirror_replaced()
            raise

    def _upload_all(self) -> None:
        changed = len(self._changed)
        self.mirror_replaced()  # the old copies go first: never two on the device
        with get_tracer().span(
            "corpus.upload",
            bytes=self.host.nbytes + self.valid_host.nbytes,
            rows=len(self),
            changed_rows=changed,
            full=1,
        ) as span:
            if self.sharding is not None:
                self._device = jax.device_put(self.host, self.sharding)
                self._device_valid = jax.device_put(
                    self.valid_host, self.valid_sharding
                )
            else:
                self._device = jnp.asarray(self.host)
                self._device_valid = jnp.asarray(self.valid_host)
            _ready_if_live(span, (self._device, self._device_valid))

    def _hand_over(self, slots: np.ndarray) -> tuple[jax.Array, ...]:
        """One chunk on its way to the device: at most ``SCATTER_ROWS``
        slots, their rows and validity bits from the mirror, padded with a
        slot past the end. (A buffer a chunk: gathering 100 MB into one
        fresh array ran at 0.4 GB/s on the v5e's host, page by page.)"""
        n = len(slots)
        padded = np.full(SCATTER_ROWS, self.capacity, np.int32)
        padded[:n] = slots
        rows = np.zeros((SCATTER_ROWS, self.dim), np.float32)
        rows[:n] = self.host[slots]
        row_valid = np.zeros(SCATTER_ROWS, bool)
        row_valid[:n] = self.valid_host[slots]
        return jax.device_put((padded, rows, row_valid), self._replicated)

    def _scatter_changed(self) -> None:
        slots = np.fromiter(self._changed, np.int32, len(self._changed))
        self._changed.clear()
        tracer = get_tracer()
        with tracer.span(
            "corpus.upload", rows=len(self), changed_rows=len(slots), full=0
        ) as span:
            chunks = [
                self._hand_over(slots[i : i + SCATTER_ROWS])
                for i in range(0, len(slots), SCATTER_ROWS)
            ]
            span.set_attribute(
                "bytes", sum(a.nbytes for chunk in chunks for a in chunk)
            )
        metrics = sorted({metric for metric, _bf16 in self._prepared})
        with tracer.span(
            "corpus.prepare",
            metric=",".join(metrics),
            bf16=any(bf16 for _metric, bf16 in self._prepared),
            rows=len(self),
        ) as span:
            self._ran.add(("scatter", self.capacity, tuple(sorted(self._prepared))))
            for chunk in chunks:
                self._device, self._device_valid, self._prepared = _scatter_rows(
                    self._device,
                    self._device_valid,
                    self._prepared,
                    *chunk,
                    sharding=self.sharding,
                    valid_sharding=self.valid_sharding,
                )
            _ready_if_live(
                span, (self._device, self._device_valid, self._prepared)
            )

    def device_arrays(self) -> tuple[jax.Array, jax.Array]:
        self._refresh()
        return self._device, self._device_valid

    def prepared_arrays(
        self, metric: str, bf16: bool = True
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """(prep, c2, valid) with normalization/cast amortized across
        queries: made from the whole corpus the first time a (metric,
        bf16) is asked for, kept in step row by row afterwards."""
        device, valid = self.device_arrays()
        key = (metric, bf16)
        if key not in self._prepared:
            with get_tracer().span(
                "corpus.prepare", metric=metric, bf16=bf16, rows=len(self)
            ) as span:
                self._ran.add(("prepare", self.capacity, metric, bf16))
                self._prepared[key] = prepare_corpus(device, metric, bf16)
                _ready_if_live(span, self._prepared[key])
        prep, c2 = self._prepared[key]
        return prep, c2, valid

    def topk(
        self, queries: np.ndarray, k: int, metric: str
    ) -> tuple[np.ndarray, np.ndarray]:
        """The device half of a search: (scores [B, k], slots [B, k]) of
        the top ``k`` rows per query, on the host. The program follows from
        where the corpus lives and ``topk_stage1`` from the shapes; one the
        compiler refuses raises, nothing here retries on another.

        The arrays are fetched first (changed rows are handed over and
        scattered into them there, under spans of their own), so the
        ``index.topk`` span holds the program and the transfer of its
        results and nothing of the refresh; its ``stage1`` is
        ``topk_stage1`` of the rows one device scans and ``k``. The arrays
        are held for this call only: they are donated to the next refresh."""
        if self.sharding is not None:
            # kept as it was, not repaired: a mesh scores the raw rows per
            # query, at sharded_topk's default dtype (ROADMAP D16)
            corpus, valid = self.device_arrays()
            mesh, axis = self.sharding.mesh, self.sharding.spec[0]
            local_rows = corpus.shape[0] // mesh.shape[axis]
            attrs = {
                "kernel": "sharded",
                "stage1": topk_stage1(local_rows, min(k, local_rows)),
            }
            run = functools.partial(
                sharded_topk,
                queries,
                corpus,
                valid,
                k,
                mesh=mesh,
                axis=axis,
                metric=metric,
            )
        else:
            prep, c2, valid = self.prepared_arrays(metric, bf16=SCAN_BF16)
            attrs = {"kernel": "xla", "stage1": topk_stage1(prep.shape[0], k)}
            run = functools.partial(
                dense_topk_prepared,
                queries,
                prep,
                c2,
                valid,
                k,
                metric=metric,
                bf16=SCAN_BF16,
            )
        self._ran.add((attrs["kernel"], self.capacity, queries.shape, queries.dtype, k, metric))
        with get_tracer().span("index.topk", **attrs):
            scores, slots = run()
            return np.asarray(scores), np.asarray(slots)

    def keys_for_slots(self, slots: np.ndarray) -> list[int | None]:
        return [
            self.key_of.get(int(s)) if s >= 0 else None for s in slots
        ]
