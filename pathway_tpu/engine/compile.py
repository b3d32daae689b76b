"""Tick Forge: trace stateless operator chains into fused XLA programs.

The interpreter (engine/runtime.py) walks the exec graph every tick and
dispatches one numpy kernel per operator — at 1M-row ticks a
map→filter→map chain pays one full memory pass per expression node.
Following the full-compilation approach of Julia→TPU (PAPERS.md,
https://arxiv.org/pdf/1810.09868), this module segments the node graph
into maximal chains of *stateless, fixed-schema* operators
(StreamMap/select expression eval, Filter, Reindex with numeric keys,
Concat fan-in) and lowers each chain's expression trees into ONE pure
``jax.jit``-ted function over columnar device arrays.  Filters lower to
masks (the traced program is shape-stable; the host epilogue compresses),
object/string columns pass through host-side untouched, and anything the
tracer cannot prove equivalent — UDFs, async exprs, Pointer-producing
expressions, object-dtype inputs — marks a chain boundary and falls back
to the per-node interpreter, per tick, with identical semantics.

Shape bucketing: programs are cached per (segment id, padded row-count
bucket, input dtype tuple).  Row counts pad up the same power-of-two
ladder the Surge Gate micro-batcher already releases batches on
(serving/config.py ``batch_buckets``), so steady-state serving flushes
and steady ingest ticks hit the cache on nearly every tick; padded rows
are sliced away (map) or masked out (filter) on the host before the
batch continues downstream.

GroupBy's semigroup fast path (count/sum/avg) can also run its partial
aggregation as a jitted ``segment_sum`` program (``semigroup_partials``).
On this box's CPU backend that is a measured LOSS — XLA CPU lowers
scatter-add ~40x slower than numpy 2.0's ``np.ufunc.at`` at 1M rows —
so the device path is opt-in via ``PATHWAY_COMPILED_GROUPBY=1`` and
auto-enables only on real accelerator backends, where scatter lands on
the vector units and the decision flips (TPU-KNN's peak-FLOP/s argument,
https://arxiv.org/pdf/2206.14286).

Knobs:
  PATHWAY_COMPILED_TICK=0     escape hatch — byte-identical interpreter
  PATHWAY_COMPILED_MIN_ROWS   smallest batch worth dispatching (def 64)
  PATHWAY_COMPILED_GROUPBY    1/0 force the device semigroup partials
                              (default: auto — off on cpu backends)

Metrics: pathway_engine_compile_cache_{hits,misses}_total,
pathway_engine_compile_seconds, pathway_engine_compile_fallbacks_total
{reason}; per-segment ``compiled`` flags ride /debug/graph.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Callable, Sequence

import numpy as np

from pathway_tpu.engine.batch import DiffBatch
from pathway_tpu.engine.expression_eval import InternalColRef
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import expression as expr

logger = logging.getLogger("pathway_tpu")


# ---------------------------------------------------------------------------
# knobs


def compiled_tick_enabled() -> bool:
    """Default ON; PATHWAY_COMPILED_TICK=0 restores the byte-identical
    interpreter path (re-read per Runtime like engine_threads)."""
    return os.environ.get("PATHWAY_COMPILED_TICK", "1") != "0"


def compiled_min_rows() -> int:
    """Batches below this size skip the device dispatch — jit-call
    overhead beats fusion wins on tiny ticks."""
    raw = os.environ.get("PATHWAY_COMPILED_MIN_ROWS", "")
    try:
        return max(1, int(raw)) if raw else 64
    except ValueError:
        return 64


def compiled_groupby_enabled() -> bool:
    """Device semigroup partials: explicit 1/0 wins; default auto —
    enabled only when the default jax backend is a real accelerator
    (XLA CPU scatter-add measured ~40x slower than np.add.at here)."""
    raw = os.environ.get("PATHWAY_COMPILED_GROUPBY", "")
    if raw:
        return raw != "0"
    try:
        import jax

        return jax.default_backend() not in ("cpu",)
    except Exception:
        return False


def row_bucket(n: int) -> int:
    """Power-of-two pad bucket — the same ladder Surge Gate's
    micro-batcher releases batches on (serving/config.py), so gated
    serving flushes land on a handful of buckets."""
    b = 8
    while b < n:
        b <<= 1
    return b


# ---------------------------------------------------------------------------
# metrics (process-wide registry; label children cached at module level)


def _metrics():
    global _HITS, _MISSES, _COMPILE_HIST, _FALLBACKS
    if _HITS is None:
        from pathway_tpu.observability import REGISTRY

        _HITS = REGISTRY.counter(
            "pathway_engine_compile_cache_hits_total",
            "compiled-tick programs reused from the shape-bucketed cache",
        )
        _MISSES = REGISTRY.counter(
            "pathway_engine_compile_cache_misses_total",
            "compiled-tick cache misses (trace+compile, or a negative "
            "entry recording a non-lowerable dtype tuple)",
        )
        _COMPILE_HIST = REGISTRY.histogram(
            "pathway_engine_compile_seconds",
            "wall time spent tracing+compiling one segment program",
        )
        _FALLBACKS = REGISTRY.counter(
            "pathway_engine_compile_fallbacks_total",
            "ticks a planned segment ran on the interpreter instead",
            labelnames=("reason",),
        )
    return _HITS, _MISSES, _COMPILE_HIST, _FALLBACKS


_HITS = _MISSES = _COMPILE_HIST = _FALLBACKS = None


class NotCompilable(Exception):
    """This expression/segment cannot be lowered (reason in args[0])."""

    @property
    def reason(self) -> str:
        return self.args[0]


# ---------------------------------------------------------------------------
# structural classification (build-time; shared with the Graph Doctor)

# operators with exact XLA equivalents under the engine's numpy
# semantics.  /, //, %, ** are excluded: their ERROR-poison semantics
# (record_error + per-row poison on zero divisors) have no pure
# counterpart; << >> excluded (negative shift counts are UB and differ
# across backends); @ is object-valued.
_OK_BINOPS = frozenset({"+", "-", "*", "==", "!=", "<", "<=", ">", ">=",
                        "&", "|", "^"})
_CMP_OPS = frozenset({"==", "!=", "<", "<=", ">", ">="})
_BITS_OPS = frozenset({"&", "|", "^"})
_ARITH_OPS = frozenset({"+", "-", "*"})

_CAST_TARGETS = (dt.INT, dt.FLOAT, dt.BOOL)


def classify_expr(e: expr.ColumnExpression) -> str | None:
    """``None`` when the expression is *structurally* lowerable (dtype
    feasibility is still decided per tick against the concrete batch);
    otherwise a short reason used by the planner and the Graph Doctor's
    ``compile-boundary`` rule."""
    if isinstance(e, InternalColRef):
        if e._name == "id":
            return "id column (Pointer-valued)"
        if e._input_index != 0:
            return "multi-input column reference"
        return None
    if isinstance(e, expr.ColumnConstExpression):
        v = e._value
        if isinstance(v, bool):
            return None
        if isinstance(v, int):
            return None if -(2**63) <= v < 2**63 else "big-int constant"
        if isinstance(v, float):
            return None
        return f"object constant ({type(v).__name__})"
    if isinstance(e, expr.ColumnBinaryOpExpression):
        if e._op not in _OK_BINOPS:
            return f"operator {e._op!r} (interpreter-only semantics)"
        return classify_expr(e._left) or classify_expr(e._right)
    if isinstance(e, expr.ColumnUnaryOpExpression):
        if e._op not in ("-", "~", "abs"):
            return f"unary operator {e._op!r}"
        return classify_expr(e._expr)
    if isinstance(e, expr.IfElseExpression):
        return (
            classify_expr(e._if)
            or classify_expr(e._then)
            or classify_expr(e._else)
        )
    if isinstance(e, expr.CoalesceExpression):
        # numeric first arg short-circuits in the interpreter
        return classify_expr(e._args[0])
    if isinstance(e, (expr.FillErrorExpression, expr.UnwrapExpression)):
        return classify_expr(e._expr)
    if isinstance(e, expr.RequireExpression):
        r = classify_expr(e._val)
        if r:
            return r
        for a in e._args:
            r = classify_expr(a)
            if r:
                return r
        return None
    if isinstance(e, expr.CastExpression):
        if e._target.strip_optional() not in _CAST_TARGETS:
            return f"cast to {e._target}"
        return classify_expr(e._expr)
    if isinstance(e, expr.DeclareTypeExpression):
        return classify_expr(e._expr)
    if isinstance(e, (expr.IsNoneExpression, expr.IsNotNoneExpression)):
        return classify_expr(e._expr)
    if isinstance(e, expr.AsyncApplyExpression):
        return "async UDF"
    if isinstance(e, (expr.BatchApplyExpression, expr.ApplyExpression)):
        return "UDF (pw.apply)"
    if isinstance(e, expr.MethodCallExpression):
        return "method call (host-side scalar/vector fn)"
    if isinstance(e, expr.PointerExpression):
        return "pointer derivation (host-side key hash)"
    if isinstance(
        e,
        (
            expr.MakeTupleExpression,
            expr.GetExpression,
            expr.ToStringExpression,
            expr.ConvertExpression,
        ),
    ):
        return "object-valued expression"
    return f"unsupported expression ({type(e).__name__})"


def _is_bare_ref(e: expr.ColumnExpression) -> bool:
    return isinstance(e, InternalColRef) and e._name != "id"


def classify_node(node: Any) -> tuple[bool, str | None]:
    """(chain-member-eligible, reason-if-not).  Structural only; used by
    the planner and the ``compile-boundary`` doctor rule.  Input/Output
    nodes return a non-user-actionable reason the rule filters out."""
    from pathway_tpu.engine.nodes import (
        ConcatNode,
        FilterNode,
        InputNode,
        OutputNode,
        ReindexNode,
        RowwiseNode,
    )

    if isinstance(node, RowwiseNode):
        if len(node.inputs) > 1:
            return False, "stateful (multi-input aligned select)"
        if not node.deterministic:
            return False, "non-deterministic expressions (cached replay)"
        for e in node.exprs.values():
            if _is_bare_ref(e):
                continue
            r = classify_expr(e)
            if r:
                return False, r
        return True, None
    if isinstance(node, FilterNode):
        r = classify_expr(node.predicate)
        return (False, r) if r else (True, None)
    if isinstance(node, ReindexNode):
        r = classify_expr(node.key_expr)
        return (False, r) if r else (True, None)
    if isinstance(node, ConcatNode):
        return True, None
    if isinstance(node, (InputNode, OutputNode)):
        return False, "__io__"
    if getattr(node, "is_stateful", False):
        return False, f"stateful operator ({type(node).__name__})"
    return False, f"unsupported operator ({type(node).__name__})"


def _has_compute(node: Any) -> bool:
    """A node worth paying a device round-trip for: real expression work
    (not a pure projection/rename) or a filter/reindex."""
    from pathway_tpu.engine.nodes import (
        FilterNode,
        ReindexNode,
        RowwiseNode,
    )

    if isinstance(node, (FilterNode, ReindexNode)):
        return True
    if isinstance(node, RowwiseNode):
        return any(not _is_bare_ref(e) for e in node.exprs.values())
    return False


# ---------------------------------------------------------------------------
# lowering: expression tree -> jnp thunk (+ static result dtype)
#
# A lowered value is one of
#   ("host", src)        passthrough of external input column `src`
#   ("dev", thunk, dt)   thunk(inp, memo) -> jnp array during tracing
#   ("const", v, dt)     scalar literal (materialized lazily; the
#                        interpreter materializes via _full, so consts
#                        promote as ARRAYS — mirrored via result_type)
# Thunks are memoized by identity per trace so a chain column referenced
# twice lowers to one subgraph (XLA would CSE anyway; this bounds trace
# time for deep chains).

_I64 = np.dtype(np.int64)
_F64 = np.dtype(np.float64)
_BOOL = np.dtype(bool)
# refusal reason on a backend without native float64 (ops/backend.py)
_F64_EMULATED = "float64 (emulated on this backend)"


def _ev(entry: tuple, inp: dict, memo: dict):
    import jax.numpy as jnp

    kind = entry[0]
    if kind == "host":
        return inp[entry[1]]
    if kind == "const":
        n = inp["__n__"]
        return jnp.full((n,), entry[1], dtype=entry[2])
    thunk = entry[1]
    key = id(thunk)
    r = memo.get(key)
    if r is None:
        r = thunk(inp, memo)
        memo[key] = r
    return r


def _entry_dtype(
    entry: tuple, dtypes: dict[str, np.dtype], where: str
) -> np.dtype:
    if entry[0] == "host":
        d = dtypes[entry[1]]
        if d.kind not in "bifu":
            raise NotCompilable(f"object column {entry[1]!r} ({where})")
        return d
    return entry[2]


def _check_mix(ld: np.dtype, rd: np.dtype) -> None:
    # numpy's uint64/int64 promotion (-> float64) is a trap neither side
    # should fall into silently; and bool arithmetic promotes to int in
    # jax but stays bool in numpy — both are boundaries, not bugs.
    if {ld.kind, rd.kind} == {"u", "i"}:
        raise NotCompilable("mixed signed/unsigned operands")


def _lower(
    e: expr.ColumnExpression,
    env: dict[str, tuple],
    dtypes: dict[str, np.dtype],
    used: "dict[str, None]",
) -> tuple:
    """Lower `e` against the symbolic column environment; returns an
    entry tuple.  Raises NotCompilable — callers fall back per tick."""
    import jax.numpy as jnp

    def dev(entry) -> tuple[Callable, np.dtype]:
        """(thunk, dtype) for any entry — host refs lift to device
        inputs, consts materialize against the batch length."""
        d = _entry_dtype(entry, dtypes, "referenced")
        if entry[0] == "host":
            used[entry[1]] = None
        return (lambda inp, memo, _e=entry: _ev(_e, inp, memo)), d

    if isinstance(e, InternalColRef):
        if e._name == "id":
            raise NotCompilable("id column (Pointer-valued)")
        entry = env.get(e._name)
        if entry is None:
            raise NotCompilable(f"unknown column {e._name!r}")
        # bare refs stay symbolic: host passthroughs never cross the
        # device (object/string columns legally ride along untouched);
        # consumers that lift to the device run their own dtype checks
        # via dev()
        return entry
    if isinstance(e, expr.ColumnConstExpression):
        v = e._value
        if isinstance(v, bool):
            return ("const", bool(v), _BOOL)
        if isinstance(v, int) and not isinstance(v, bool):
            if not -(2**63) <= v < 2**63:
                raise NotCompilable("big-int constant")
            return ("const", int(v), _I64)
        if isinstance(v, float):
            return ("const", float(v), _F64)
        raise NotCompilable(f"object constant ({type(v).__name__})")
    if isinstance(e, expr.ColumnBinaryOpExpression):
        op = e._op
        if op not in _OK_BINOPS:
            raise NotCompilable(f"operator {op!r}")
        lf, ld = dev(_lower(e._left, env, dtypes, used))
        rf, rd = dev(_lower(e._right, env, dtypes, used))
        _check_mix(ld, rd)
        if op in _ARITH_OPS:
            if ld.kind not in "iuf" or rd.kind not in "iuf":
                raise NotCompilable(f"arithmetic on {ld}/{rd}")
            out = np.result_type(ld, rd)
        elif op in _BITS_OPS:
            if ld.kind == "b" and rd.kind == "b":
                out = _BOOL
            elif ld.kind in "iu" and rd.kind in "iu":
                out = np.result_type(ld, rd)
            else:
                raise NotCompilable(f"bitwise op on {ld}/{rd}")
        else:  # comparison
            out = _BOOL
        common = out if op not in _CMP_OPS else np.result_type(ld, rd)
        _J_BIN = {
            "+": jnp.add, "-": jnp.subtract, "*": jnp.multiply,
            "==": jnp.equal, "!=": jnp.not_equal, "<": jnp.less,
            "<=": jnp.less_equal, ">": jnp.greater, ">=":
            jnp.greater_equal, "&": jnp.bitwise_and,
            "|": jnp.bitwise_or, "^": jnp.bitwise_xor,
        }
        fn = _J_BIN[op]

        def thunk(inp, memo, _lf=lf, _rf=rf, _c=common, _fn=fn):
            lo = _lf(inp, memo).astype(_c)
            ro = _rf(inp, memo).astype(_c)
            return _fn(lo, ro)

        return ("dev", thunk, out)
    if isinstance(e, expr.ColumnUnaryOpExpression):
        af, ad = dev(_lower(e._expr, env, dtypes, used))
        if e._op == "-":
            if ad.kind not in "if":
                raise NotCompilable(f"negation on {ad}")
            return ("dev", lambda inp, memo: -af(inp, memo), ad)
        if e._op == "abs":
            if ad.kind not in "ifu":
                raise NotCompilable(f"abs on {ad}")
            import jax.numpy as _jnp

            return ("dev", lambda inp, memo: _jnp.abs(af(inp, memo)), ad)
        if e._op == "~":
            if ad.kind == "b":
                return (
                    "dev",
                    lambda inp, memo: ~af(inp, memo),
                    _BOOL,
                )
            if ad.kind in "iu":
                return ("dev", lambda inp, memo: ~af(inp, memo), ad)
            raise NotCompilable(f"invert on {ad}")
        raise NotCompilable(f"unary operator {e._op!r}")
    if isinstance(e, expr.IfElseExpression):
        cf, cd = dev(_lower(e._if, env, dtypes, used))
        tf, td = dev(_lower(e._then, env, dtypes, used))
        ef, ed = dev(_lower(e._else, env, dtypes, used))
        if td == ed:
            out = td
        elif td.kind in "iuf" and ed.kind in "iuf":
            # interpreter: object array of mixed ints/floats _tightens
            # to float64/int64 = numpy promotion of the two
            _check_mix(td, ed)
            out = np.result_type(td, ed)
        else:
            raise NotCompilable(f"if_else branches {td}/{ed}")

        def thunk(inp, memo, _cf=cf, _tf=tf, _ef=ef, _o=out):
            import jax.numpy as _jnp

            c = _cf(inp, memo).astype(bool)
            return _jnp.where(
                c, _tf(inp, memo).astype(_o), _ef(inp, memo).astype(_o)
            )

        return ("dev", thunk, out)
    if isinstance(e, expr.CoalesceExpression):
        first = _lower(e._args[0], env, dtypes, used)
        # non-object dtype short-circuits in the interpreter
        _entry_dtype(first, dtypes, "coalesce")
        return first
    if isinstance(e, expr.FillErrorExpression):
        inner = _lower(e._expr, env, dtypes, used)
        _entry_dtype(inner, dtypes, "fill_error")
        return inner
    if isinstance(e, expr.UnwrapExpression):
        inner = _lower(e._expr, env, dtypes, used)
        _entry_dtype(inner, dtypes, "unwrap")
        return inner
    if isinstance(e, expr.RequireExpression):
        # numeric deps are never None: require == its value
        for a in e._args:
            _entry_dtype(_lower(a, env, dtypes, used), dtypes, "require")
        return _lower(e._val, env, dtypes, used)
    if isinstance(e, expr.CastExpression):
        t = e._target.strip_optional()
        af, ad = dev(_lower(e._expr, env, dtypes, used))
        if ad.kind not in "bifu":
            raise NotCompilable(f"cast from {ad}")
        if t == dt.INT:
            out = _I64
        elif t == dt.FLOAT:
            out = _F64
        elif t == dt.BOOL:
            out = _BOOL
        else:
            raise NotCompilable(f"cast to {t}")
        return (
            "dev",
            lambda inp, memo, _o=out: af(inp, memo).astype(_o),
            out,
        )
    if isinstance(e, expr.DeclareTypeExpression):
        return _lower(e._expr, env, dtypes, used)
    if isinstance(
        e, (expr.IsNoneExpression, expr.IsNotNoneExpression)
    ):
        af, _ad = dev(_lower(e._expr, env, dtypes, used))
        val = isinstance(e, expr.IsNotNoneExpression)

        def thunk(inp, memo, _af=af, _v=val):
            import jax.numpy as _jnp

            a = _af(inp, memo)
            return _jnp.full(a.shape, _v, dtype=bool)

        return ("dev", thunk, _BOOL)
    r = classify_expr(e)
    raise NotCompilable(r or f"unsupported ({type(e).__name__})")


# ---------------------------------------------------------------------------
# segment program: one jitted fn per (segment, dtype tuple); jax's own
# shape cache handles the bucket dimension, our table counts it


class _Program:
    """Compiled form of one segment for one input-dtype signature."""

    __slots__ = (
        "in_cols", "dev_out", "host_out", "has_mask", "has_keys", "fn",
        "out_names",
    )

    def __init__(self, in_cols, dev_out, host_out, has_mask, has_keys,
                 fn, out_names):
        self.in_cols = in_cols      # ordered device input column names
        self.dev_out = dev_out      # [(name, position-in-fn-result)]
        self.host_out = host_out    # [(name, external src col)]
        self.has_mask = has_mask
        self.has_keys = has_keys
        self.fn = fn
        self.out_names = out_names  # final column order


def _build_program(
    chain: Sequence[Any],
    external_cols: Sequence[str],
    dtypes: dict[str, np.dtype],
) -> _Program:
    """Lower the chain against concrete input dtypes into one jitted
    program.  Raises NotCompilable when this dtype signature cannot be
    proven equivalent (the caller negative-caches it)."""
    import jax
    from pathway_tpu.engine.nodes import (
        ConcatNode,
        FilterNode,
        ReindexNode,
        RowwiseNode,
    )

    env: dict[str, tuple] = {c: ("host", c) for c in external_cols}
    masks: list[tuple] = []
    key_entry: tuple | None = None
    used: dict[str, None] = {}

    for node in chain:
        if isinstance(node, ConcatNode):
            continue  # concat + column select happen host-side
        if isinstance(node, RowwiseNode):
            new_env: dict[str, tuple] = {}
            for out_name, e in node.exprs.items():
                new_env[out_name] = _lower(e, env, dtypes, used)
            env = new_env
        elif isinstance(node, FilterNode):
            entry = _lower(node.predicate, env, dtypes, used)
            d = _entry_dtype(entry, dtypes, "filter predicate")
            if d.kind not in "bifu":
                raise NotCompilable(f"filter predicate dtype {d}")
            if entry[0] == "host":
                # bare-column predicates never pass through dev(), so
                # the device input must be registered here or the traced
                # fn would KeyError on its first dispatch
                used[entry[1]] = None
            masks.append(entry)
        elif isinstance(node, ReindexNode):
            entry = _lower(node.key_expr, env, dtypes, used)
            d = _entry_dtype(entry, dtypes, "reindex keys")
            if d.kind not in "iu" or d.itemsize != 8:
                raise NotCompilable(f"reindex key dtype {d}")
            if entry[0] == "host":
                used[entry[1]] = None  # same as bare-column predicates
            key_entry = entry
        else:  # pragma: no cover - planner never includes others
            raise NotCompilable(f"operator {type(node).__name__}")

    tail = chain[-1]
    out_names = list(tail.column_names)
    dev_out: list[tuple[str, int]] = []
    host_out: list[tuple[str, str]] = []
    dev_entries: list[tuple] = []
    for name in out_names:
        entry = env[name]
        # force consts through the device so literal columns come back
        # with _full's exact dtypes; host refs stay host
        if entry[0] == "host":
            host_out.append((name, entry[1]))
        else:
            _entry_dtype(entry, dtypes, f"output {name!r}")
            dev_out.append((name, len(dev_entries)))
            dev_entries.append(entry)

    if not dev_entries and not masks and key_entry is None:
        raise NotCompilable("no device computation (pure projection)")

    in_cols = list(used.keys())
    if not in_cols:
        # constant-only programs have no batch-length anchor
        raise NotCompilable("constant-only computation")
    n_dev = len(dev_entries)
    mask_entries = list(masks)
    key_e = key_entry

    def fn(*arrays):
        import jax.numpy as jnp

        inp = dict(zip(in_cols, arrays))
        inp["__n__"] = arrays[0].shape[0]
        memo: dict = {}
        outs = [_ev(en, inp, memo) for en in dev_entries]
        if mask_entries:
            m = _ev(mask_entries[0], inp, memo).astype(bool)
            for en in mask_entries[1:]:
                m = m & _ev(en, inp, memo).astype(bool)
            outs.append(m)
        if key_e is not None:
            outs.append(_ev(key_e, inp, memo))
        return tuple(outs)

    with jax.enable_x64(True):
        _refuse_emulated_float64(fn, [dtypes[c] for c in in_cols])
        jfn = jax.jit(fn)

    return _Program(
        in_cols,
        dev_out,
        host_out,
        bool(mask_entries),
        key_e is not None,
        jfn,
        out_names,
    )


def _refuse_emulated_float64(fn: Callable, in_dtypes: Sequence[np.dtype]):
    """Where the backend only emulates float64 (ops/backend.py
    float64_native: a TPU), a program that touches a float64 anywhere —
    input, constant, cast or intermediate — cannot be proven equal to the
    interpreter's numpy float64, so it is not compiled.  The traced
    jaxpr is the one place every such value shows up."""
    from pathway_tpu.ops.backend import float64_native

    if float64_native():
        return
    import jax

    closed = jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct((8,), d) for d in in_dtypes)
    )
    values = list(closed.jaxpr.invars)
    for eqn in closed.jaxpr.eqns:
        values.extend(eqn.outvars)
    if any(getattr(v.aval, "dtype", None) == _F64 for v in values):
        raise NotCompilable(_F64_EMULATED)


# ---------------------------------------------------------------------------
# the runtime-facing segment


class SegmentRunner:
    """One planned chain: head inputs -> fused program -> tail output.

    Holds the per-(bucket, dtype-tuple) program cache; every tick either
    dispatches the jitted program (pad -> run -> slice/mask) or falls
    back to running the chain's own interpreter execs — the very same
    NodeExec objects the interpreter would use, so alternating between
    paths is always safe (members are stateless)."""

    _FALLBACK = object()  # negative cache entry

    def __init__(self, seg_id: int, nodes: Sequence[Any], execs: dict):
        from pathway_tpu.engine.nodes import ConcatNode

        self.seg_id = seg_id
        self.nodes = list(nodes)
        self.execs = execs
        self.head = nodes[0]
        self.tail = nodes[-1]
        self.concat_head = isinstance(self.head, ConcatNode)
        if self.concat_head:
            self.external_cols = list(self.head.column_names)
            self.chain = self.nodes  # concat itself is skipped in build
        else:
            self.external_cols = list(self.head.inputs[0].column_names)
            self.chain = self.nodes
        self._cache: dict[tuple, Any] = {}
        self._lock = threading.Lock()
        self.compiled_ticks = 0
        self.fallback_ticks = 0
        self._min_rows = compiled_min_rows()

    # --- runtime hooks ----------------------------------------------------

    def gather(self, produced: dict) -> list[list[DiffBatch]]:
        return [produced.get(inp.id, []) for inp in self.head.inputs]

    def process(self, t: int, inputs: list[list[DiffBatch]]) -> list[DiffBatch]:
        # gate on the raw input lengths BEFORE paying the head-batch
        # concat: a chronically small-tick segment must not add a full
        # memory pass on top of the interpreter redoing the same concat
        # inside the head exec
        n = sum(len(b) for batches in inputs for b in batches)
        if not n:
            return []
        if n < self._min_rows:
            return self._interpret(t, inputs)
        batch = self._head_batch(inputs)
        # NotCompilable is a decision (this dtype signature does not
        # lower) and runs the interpreter. Any other exception is a bug
        # in the compiled path or a device/compiler failure: it fails
        # the tick, so a broken device path is a red run, never a
        # silently interpreted one.
        try:
            out = self._run_compiled(t, batch, inputs)
        except NotCompilable as nc:
            _metrics()[3].labels(nc.reason[:60]).inc()
            self._journal_fallback(t, nc.reason[:60])
            return self._interpret(t, inputs)
        self.compiled_ticks += 1
        return out

    def _journal_fallback(self, t: int, reason: str) -> None:
        """Incident-journal a compiled-segment fallback ONCE per
        (segment, reason) — the fallback counter ticks every tick, the
        journal records the state transition."""
        seen = getattr(self, "_journaled_reasons", None)
        if seen is None:
            seen = self._journaled_reasons = set()
        if reason in seen:
            return
        seen.add(reason)
        from pathway_tpu.observability.journal import record as journal_record

        journal_record(
            "compile-fallback",
            f"segment {self.seg_id} fell back to the interpreter "
            f"({reason})",
            tick=t,
            segment=self.seg_id,
            reason=reason,
        )

    # --- paths ------------------------------------------------------------

    def _head_batch(self, inputs: list[list[DiffBatch]]) -> DiffBatch:
        from pathway_tpu.engine.nodes import _concat_inputs

        if not self.concat_head:
            return _concat_inputs(
                list(inputs[0]), self.external_cols
            )
        parts = [
            b.select_columns(self.external_cols)
            for batches in inputs
            for b in batches
            if len(b)
        ]
        if not parts:
            return DiffBatch.empty(self.external_cols)
        return DiffBatch.concat(parts)

    def _interpret(
        self, t: int, inputs: list[list[DiffBatch]]
    ) -> list[DiffBatch]:
        """Run the chain on its own interpreter execs (identical to the
        un-segmented engine, including per-node error-log scopes)."""
        from pathway_tpu.internals.errors import set_exec_scope

        self.fallback_ticks += 1
        local: dict[int, list[DiffBatch]] = {}
        for pos, inp in enumerate(self.head.inputs):
            local[inp.id] = list(inputs[pos])
        for node in self.nodes:
            ex = self.execs[node.id]
            ins = [local.get(i.id, []) for i in node.inputs]
            set_exec_scope(getattr(node, "_error_scope", None))
            try:
                local[node.id] = ex.process(t, ins)
            finally:
                set_exec_scope(None)
        return local[self.tail.id]

    def _run_compiled(
        self, t: int, batch: DiffBatch, inputs: list[list[DiffBatch]]
    ) -> list[DiffBatch]:
        import jax

        prog, bucket_key = self._program_for(batch)
        n = len(batch)
        bucket = bucket_key[0]
        ins = []
        for name in prog.in_cols:
            col = batch.columns[name]
            if bucket > n:
                pad = np.zeros(bucket - n, dtype=col.dtype)
                col = np.concatenate([col, pad])
            ins.append(col)
        # roofline attribution (observability/tickscope.py): measured
        # monotonic wall per program execution, against the FLOP estimate
        # registered at build time in _program_for. The np.asarray calls
        # stay inside the window — device->host sync is part of what the
        # tick actually waits for.
        _rt0 = time.perf_counter()
        with jax.enable_x64(True):
            res = prog.fn(*ins)
            outs = [np.asarray(r) for r in res]
        try:
            from pathway_tpu.observability import tickscope as _ts

            _ts.roofline().observe(
                "compiled_tick",
                f"seg_{'-'.join(prog.in_cols)}_rows{bucket}",
                time.perf_counter() - _rt0,
            )
        except Exception:  # pragma: no cover - defensive
            pass
        pos = len(prog.dev_out)
        mask = None
        new_keys = None
        if prog.has_mask:
            mask = outs[pos]
            pos += 1
        if prog.has_keys:
            new_keys = outs[pos]
        for _name, i in prog.dev_out:
            if outs[i].shape != (bucket,):
                raise NotCompilable("non-columnar program output")
        keys = batch.keys
        diffs = batch.diffs
        if new_keys is not None:
            nk = new_keys[:n]
            if nk.dtype.kind == "i" and len(nk) and (nk < 0).any():
                # the interpreter raises OverflowError assigning a
                # negative key into the uint64 key column; reproduce by
                # letting it
                raise NotCompilable("negative reindex key")
            keys = nk.astype(np.uint64)
        if mask is not None:
            idx = np.flatnonzero(mask[:n])
            if len(idx) == 0:
                return []
            keys = keys[idx]
            diffs = diffs[idx]
            cols = {}
            for name, i in prog.dev_out:
                cols[name] = outs[i][idx]
            for name, src in prog.host_out:
                cols[name] = batch.columns[src][idx]
        else:
            cols = {}
            for name, i in prog.dev_out:
                cols[name] = outs[i][:n]
            for name, src in prog.host_out:
                cols[name] = batch.columns[src]
        ordered = {name: cols[name] for name in prog.out_names}
        return [DiffBatch(keys, diffs, ordered)]

    def _program_for(self, batch: DiffBatch) -> tuple[_Program, tuple]:
        hits, misses, compile_hist, _fb = _metrics()
        # the dtype signature covers every external column the chain may
        # reference; lowering decides which of them go to the device
        dkey = tuple(
            batch.columns[c].dtype.str if c in batch.columns else "?"
            for c in self.external_cols
        )
        bucket = row_bucket(len(batch))
        key = (bucket, dkey)
        with self._lock:
            entry = self._cache.get(key)
        if entry is self._FALLBACK:
            hits.inc()
            raise NotCompilable("cached non-lowerable dtype signature")
        if entry is not None:
            hits.inc()
            return entry, key
        misses.inc()
        dtypes = {c: batch.columns[c].dtype for c in batch.columns}
        for c in self.external_cols:
            if batch.columns[c].ndim != 1:
                with self._lock:
                    self._cache[key] = self._FALLBACK
                raise NotCompilable(f"multi-dim column {c!r}")
        t0 = time.perf_counter()
        try:
            prog = _build_program(self.chain, self.external_cols, dtypes)
        except NotCompilable:
            with self._lock:
                self._cache[key] = self._FALLBACK
            raise
        compile_hist.observe(time.perf_counter() - t0)
        with self._lock:
            self._cache[key] = prog
        self._register_with_ledger(prog, bucket, dtypes)
        self._register_roofline(prog, bucket, dtypes)
        return prog, key

    def _register_with_ledger(self, prog: _Program, bucket: int, dtypes):
        """Hand the freshly-built segment program to the Lowering Ledger
        (analysis/lowering.py): ``prove_lowering`` can then AOT-check
        the exact jitted tick this process runs against the TPU rules,
        device-free. Best-effort — the ledger must never break a tick."""
        try:
            import jax

            from pathway_tpu.analysis import lowering as ledger

            args = tuple(
                jax.ShapeDtypeStruct((bucket,), dtypes[c])
                for c in prog.in_cols
            )
            name = (
                f"seg_{'-'.join(prog.in_cols)}_rows{bucket}"
            )
            ledger.register_program(
                name,
                prog.fn,
                args,
                meta={
                    "rows": bucket,
                    "in_cols": list(prog.in_cols),
                    "out_cols": [c for c, _ in prog.dev_out],
                },
            )
        except Exception:  # pragma: no cover - defensive
            pass

    def _register_roofline(self, prog: _Program, bucket: int, dtypes):
        """Register the program's per-call FLOP estimate (XLA cost
        analysis over abstract args — no execution) with the Tick Scope
        roofline, keyed exactly like _run_compiled's observe calls.
        Best-effort: a backend without a cost model just means zero
        registered FLOPs, which the tickscope-coverage doctor rule
        surfaces rather than this path crashing a tick."""
        try:
            import jax

            from pathway_tpu.observability import tickscope as _ts

            args = tuple(
                jax.ShapeDtypeStruct((bucket,), dtypes[c])
                for c in prog.in_cols
            )
            with jax.enable_x64(True):
                flops, nbytes = _ts.estimate_program_cost(prog.fn, *args)
            _ts.roofline().register(
                "compiled_tick",
                f"seg_{'-'.join(prog.in_cols)}_rows{bucket}",
                flops,
                nbytes,
            )
        except Exception:  # pragma: no cover - defensive
            pass


# ---------------------------------------------------------------------------
# planning


class CompiledPlan:
    def __init__(self, segments: list[SegmentRunner]):
        self.segments = segments
        self.by_tail: dict[int, SegmentRunner] = {
            s.tail.id: s for s in segments
        }
        self.member_ids: set[int] = {
            n.id for s in segments for n in s.nodes if n is not s.tail
        }

    def segment_of(self, node_id: int) -> SegmentRunner | None:
        for s in self.segments:
            if any(n.id == node_id for n in s.nodes):
                return s
        return None


def plan_segments(
    order: Sequence[Any], execs: dict
) -> CompiledPlan | None:
    """Greedy maximal-chain segmentation over the runtime's topo order.

    A chain starts at any structurally compilable node and extends while
    the current tail has exactly ONE consumer, that consumer's only
    input is the tail, and the consumer is itself compilable.  Chains
    with no real compute (pure projections/renames) are skipped — a
    device round-trip for a dict re-label is pure loss."""
    if not compiled_tick_enabled():
        return None
    from pathway_tpu.engine.nodes import ConcatNode

    consumers: dict[int, list[Any]] = {n.id: [] for n in order}
    for node in order:
        for inp in node.inputs:
            if inp.id in consumers:
                consumers[inp.id].append(node)

    assigned: set[int] = set()
    segments: list[SegmentRunner] = []
    seg_id = 0
    for node in order:
        if node.id in assigned:
            continue
        ok, _ = classify_node(node)
        if not ok:
            continue
        chain = [node]
        cur = node
        while True:
            cons = consumers.get(cur.id, [])
            if len(cons) != 1:
                break
            nxt = cons[0]
            if nxt.id in assigned or isinstance(nxt, ConcatNode):
                break
            if len(nxt.inputs) != 1 or nxt.inputs[0] is not cur:
                break
            ok, _ = classify_node(nxt)
            if not ok:
                break
            chain.append(nxt)
            cur = nxt
        # a bare Concat head with no chain after it is just the
        # interpreter's concat; segments must contain real compute
        if not any(_has_compute(n) for n in chain):
            continue
        if isinstance(chain[0], ConcatNode) and len(chain) == 1:
            continue
        for n in chain:
            assigned.add(n.id)
        segments.append(SegmentRunner(seg_id, chain, execs))
        seg_id += 1
    if not segments:
        return None
    return CompiledPlan(segments)


# ---------------------------------------------------------------------------
# GroupBy semigroup partials (count/sum/avg) as one jitted program.
#
# np.add.at-equivalent: dcounts[g] = sum(diffs | code==g) and, per
# argument column, part[g] = sum(arr * diffs | code==g).  Exact for
# int64 (wrap-around matches), order-differs-within-group for float64
# (the engine's float contract is allclose).  Opt-in on CPU — see
# module docstring for the measured scatter numbers.

_SEMIGROUP_CACHE: dict[tuple, Any] = {}
_SEMIGROUP_LOCK = threading.Lock()


def semigroup_partials(
    codes: np.ndarray,
    diffs: np.ndarray,
    args: Sequence[np.ndarray | None],
    nu: int,
) -> tuple[np.ndarray, list[np.ndarray | None]]:
    """Device twin of the bulk-groupby scatter pass.  ``args`` is
    positionally aligned with the reducer specs (None = count/multiset,
    no partial).  Only int64/float64 argument columns are supported —
    callers keep the numpy path otherwise."""
    import jax

    hits, misses, compile_hist, _fb = _metrics()
    n = len(codes)
    nb = row_bucket(n)
    gb = row_bucket(nu)  # groups ride the same pad ladder as rows
    arg_sig = tuple(
        None if a is None else np.dtype(a.dtype).str for a in args
    )
    from pathway_tpu.ops.backend import float64_native

    for a in args:
        if a is not None and a.dtype not in (_I64, _F64):
            raise NotCompilable(f"semigroup arg dtype {a.dtype}")
        if a is not None and a.dtype == _F64 and not float64_native():
            raise NotCompilable(_F64_EMULATED)
    key = (nb, gb, arg_sig)
    with _SEMIGROUP_LOCK:
        fn = _SEMIGROUP_CACHE.get(key)
    if fn is None:
        misses.inc()
        t0 = time.perf_counter()
        arg_dts = [
            np.dtype(a.dtype) for a in args if a is not None
        ]

        def build(codes_a, diffs_a, *arg_arrays):
            import jax.numpy as jnp

            dcounts = jax.ops.segment_sum(
                diffs_a, codes_a, num_segments=gb
            )
            parts = []
            for a, d in zip(arg_arrays, arg_dts):
                w = (a * diffs_a.astype(d)) if d == _F64 else (a * diffs_a)
                parts.append(
                    jax.ops.segment_sum(w, codes_a, num_segments=gb)
                )
            return (dcounts, *parts)

        with jax.enable_x64(True):
            fn = jax.jit(build)
        with _SEMIGROUP_LOCK:
            _SEMIGROUP_CACHE[key] = fn
        compile_hist.observe(time.perf_counter() - t0)
    else:
        hits.inc()

    pad = nb - n
    codes_p = codes.astype(np.int32)
    diffs_p = np.asarray(diffs, dtype=np.int64)
    if pad:
        codes_p = np.concatenate(
            [codes_p, np.zeros(pad, dtype=np.int32)]
        )
        diffs_p = np.concatenate([diffs_p, np.zeros(pad, dtype=np.int64)])
    arg_in = []
    for a in args:
        if a is None:
            continue
        ap = np.ascontiguousarray(a)
        if pad:
            ap = np.concatenate([ap, np.zeros(pad, dtype=ap.dtype)])
        arg_in.append(ap)
    with jax.enable_x64(True):
        res = fn(codes_p, diffs_p, *arg_in)
        res = [np.asarray(r) for r in res]
    dcounts = res[0][:nu]
    out: list[np.ndarray | None] = []
    i = 1
    for a in args:
        if a is None:
            out.append(None)
        else:
            out.append(res[i][:nu])
            i += 1
    return dcounts, out
