"""Device time under the program's own names.

The host-side spans of ``tracing.py`` stop where a compiled program starts:
``embed.forward`` is one span around all of a forward's device work. Inside,
the program names its parts with ``jax.named_scope``; the names ride in the
op metadata of the optimized HLO, instruction by instruction, and a device
trace names each event by its instruction.
This module is the join's program half:

* ``VOCABULARY``: every scope name the hot path may open on the device;
  ``scope(name)`` is ``jax.named_scope`` for a name of the vocabulary and
  raises for any other, so a metric that reads a scope can rely on it.
* ``jit(fn)``: ``jax.jit`` under ``fn``'s name with ``DIGEST`` appended. Scope
  names are debug info and the persistent compile cache's key strips them: an
  executable the cache gives back carries the metadata of whichever build
  compiled it first. The HLO module's name is part of the key, so with the
  vocabulary's digest in it a changed vocabulary compiles once more on each
  machine and an unchanged one costs nothing. **Moving a scope's boundary
  without renaming it is not caught: rename the scope** (a name is what a
  metric reads, and a moved boundary is another quantity).
* ``register(owner)``: an owner of hot-path device programs (``EncoderRuntime``,
  ``TrunkRuntime``, ``DeviceCorpus``) notes the abstract signature of each
  program it runs (a set insert a dispatch) and is kept here weakly.
* ``tables()``: **only when asked**, lowers each noted program again with
  ``jax.ShapeDtypeStruct`` arguments, takes ``compile().as_text()`` (a hit in
  jax's caches where one is set; else a compile, which is its caller's cost)
  and returns per program one ``Row`` for every instruction of the entry
  computation and of the computations its ``while`` / ``conditional`` /
  ``call`` instructions run. Nothing is lowered, parsed or kept otherwise.

An instruction's scope is the **innermost** vocabulary name on its
``op_name`` path, ``NO_SCOPE`` where there is none; a fusion's is the one
most of its instructions carry (``rows_of``). An operator joins
``tables()`` to a ``jax.profiler`` capture of a live process by instruction
name, result type and operand names; ``benchmarks/reducers/scope_device_ms.py`` does so for
the benchmark's traced runs.
"""

from __future__ import annotations

import functools
import hashlib
import re
import weakref
from typing import Any, Callable, NamedTuple

import jax

VOCABULARY = (
    "encoder.forward",
    "encoder.embed",
    "encoder.attention",
    "encoder.ffn",
    "encoder.pool",
    "knn.scores",
    "knn.topk",
    "corpus.prepare",
    "trunk.embed",
    "trunk.mla",
    "trunk.gqa_window",
    "trunk.gqa_full",
    "trunk.mamba2",
    "trunk.mamba2.in_proj",
    "trunk.mamba2.conv",
    "trunk.mamba2.scan",
    "trunk.mamba2.gate_out",
    "trunk.gdn",
    "trunk.gdn.in_proj",
    "trunk.gdn.conv",
    "trunk.gdn.scan",
    "trunk.gdn.gate_out",
    "trunk.gqa_gated",
    "trunk.swa_sink",
    "trunk.gqa_partial",
    "trunk.attn.qkv",
    "trunk.attn.kernel",
    "trunk.attn.out",
    "trunk.ffn",
    "trunk.moe",
    "trunk.moe.route",
    "trunk.moe.dispatch",
    "trunk.moe.gather",
    "trunk.moe.experts",
    "trunk.moe.combine",
    "trunk.moe.shared",
    "trunk.mhc",
    "trunk.pool",
)
NO_SCOPE = "(no scope)"


def digest(vocabulary: tuple[str, ...]) -> str:
    return hashlib.sha256("\n".join(vocabulary).encode()).hexdigest()[:8]


DIGEST = digest(VOCABULARY)

_NAMES = frozenset(VOCABULARY)


def scope(name: str):
    """``jax.named_scope(name)`` for a name of the vocabulary."""
    if name not in _NAMES:
        raise ValueError(
            f"{name!r} is not a device scope: add it to device_scopes.VOCABULARY"
        )
    return jax.named_scope(name)


def jit(fn: Callable, **jit_kwargs: Any):
    """``jax.jit(fn)`` whose HLO module is named ``jit_<fn's name>_<DIGEST>``."""

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    name = getattr(fn, "__name__", None) or fn.func.__name__  # a functools.partial has none
    program.__name__ = program.__qualname__ = f"{name}_{DIGEST}"
    return jax.jit(program, **jit_kwargs)


def abstract(tree: Any) -> Any:
    """``tree``'s arrays as ``jax.ShapeDtypeStruct``, each with its sharding
    where it is committed to one, as a call with the arrays themselves would
    lower it."""

    def leaf(array):
        sharding = array.sharding if getattr(array, "committed", False) else None
        return jax.ShapeDtypeStruct(array.shape, array.dtype, sharding=sharding)

    return jax.tree.map(leaf, tree)


def forwards(jitted: Any, params: Any, ran: set, sharding: Any = None):
    """``device_programs()`` of a runtime whose one program is ``jitted(params,
    ids, mask)``: ``ran`` holds (shape, ids dtype, mask dtype) of every call."""
    params = abstract(params)
    for shape, ids_dtype, mask_dtype in sorted(ran, key=repr):
        ids = jax.ShapeDtypeStruct(shape, ids_dtype, sharding=sharding)
        mask = jax.ShapeDtypeStruct(shape, mask_dtype, sharding=sharding)
        yield f"ids{list(shape)}", jitted, (params, ids, mask), {}


# -- who has run what ------------------------------------------------------------

_OWNERS: "weakref.WeakSet[Any]" = weakref.WeakSet()


def register(owner: Any) -> None:
    """``owner.device_programs()`` yields ``(label, jitted function, args,
    kwargs)`` for each program it has run, arrays as ``jax.ShapeDtypeStruct``
    (``abstract``) and the label naming the call's shapes."""
    _OWNERS.add(owner)


class Row(NamedTuple):
    name: str  # the instruction's, without its %
    type: str  # its result type without layouts: bf16[32,512,9216]
    opcode: str
    scope: str
    spans: int = 1  # scopes of the vocabulary among a fusion's instructions
    operands: tuple[str, ...] = ()  # their names: with name and type, what tells two programs' instructions apart


def tables(owners: Any = None) -> dict[str, list[Row]]:
    """{program: rows} of every program the live owners (or the given ones)
    have run; a program is named by its HLO module and the owner's label of
    the call's shapes."""
    out = {}
    for owner in list(_OWNERS) if owners is None else owners:
        for label, jitted, args, kwargs in owner.device_programs():
            text = jitted.lower(*args, **kwargs).compile().as_text()
            module = text.split(",", 1)[0].removeprefix("HloModule ").strip()
            key = f"{module} {label}"
            while key in out:  # two owners ran the same program at the same shapes
                key += "'"
            out[key] = rows_of(text)
    return out


# -- the optimized HLO's text ------------------------------------------------------

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"(?<![=\w.\-])%([\w.\-]+)")  # not the computation of a calls=%... or body=%...
_CALLED = re.compile(r"(?:body|condition|to_apply|calls|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
# instructions whose computations run as operations of their own on the device
_RUNS_COMPUTATIONS = ("while", "conditional", "call", "async-start")


def parse_instruction(line: str) -> tuple[str, str, str, tuple[str, ...]] | None:
    """(name, result type without layouts, opcode, operand names) of an HLO
    instruction's line. A device trace names an event by that line (it
    writes each operand's type before its name, ``as_text()`` the name
    alone; neither has the metadata there)."""
    found = INSTRUCTION.match(line)
    if found is None:
        return None
    name, result, opcode = found.groups()
    rest = line[found.end():].split(", metadata=", 1)[0]
    return name, _LAYOUT.sub("", result), opcode, tuple(_OPERAND.findall(rest))


def scope_of(op_name: str) -> str:
    """The innermost vocabulary name on an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in _NAMES:
            return part
    return NO_SCOPE


def _called(line: str) -> list[str]:
    names = _CALLED.findall(line)
    for group in _BRANCHES.findall(line):
        names += [n.strip().lstrip("%") for n in group.split(",") if n.strip()]
    return names


def rows_of(hlo_text: str) -> list[Row]:
    """One row an instruction of the entry computation and of every
    computation reached from it through ``_RUNS_COMPUTATIONS``.

    A fusion's own metadata is that of one of its roots, and XLA fuses
    across scopes (the four-stream residual's mixing with the sums of the
    norm that follows it, under the sums' name). So a fusion has the scope
    **most of its instructions carry** (XLA's own converts and copies carry
    none and do not vote; a draw goes to the fusion's own metadata, then to
    the vocabulary's order), and ``Row.spans`` says how many scopes of the
    vocabulary its instructions have between them."""
    computations: dict[str, list[str]] = {}
    entry = current = None
    for line in hlo_text.splitlines():
        header = _COMPUTATION.match(line)
        if header:
            current = header.group(2)
            computations[current] = []
            if header.group(1):
                entry = current
        elif line.startswith("}"):
            current = None
        elif current is not None:
            computations[current].append(line)

    def own_scope(line: str) -> str:
        found = _OP_NAME.search(line)
        return scope_of(found.group(1)) if found else NO_SCOPE

    def fusion_scope(line: str) -> tuple[str, int]:
        votes: dict[str, int] = {}
        for called in _called(line):
            for inner in computations.get(called, ()):
                inside = own_scope(inner)
                if inside != NO_SCOPE:
                    votes[inside] = votes.get(inside, 0) + 1
        own = own_scope(line)
        if not votes:
            return own, 1
        most = max(votes.values())
        leaders = [name for name in VOCABULARY if votes.get(name) == most]
        return (own if own in leaders else leaders[0]), len(votes)

    rows, seen, queue = [], set(), [entry] if entry else []
    while queue:
        computation = queue.pop()
        if computation in seen:
            continue
        seen.add(computation)
        for line in computations.get(computation, ()):
            parsed = parse_instruction(line)
            if parsed is None:
                continue
            name, result, opcode, operands = parsed
            scope_, spans = fusion_scope(line) if opcode == "fusion" else (own_scope(line), 1)
            rows.append(Row(name, result, opcode, scope_, spans, operands))
            if opcode in _RUNS_COMPUTATIONS:
                queue += _called(line)
    return rows
