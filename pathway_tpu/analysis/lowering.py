"""The Lowering Ledger: device-free TPU compilability proofs.

The tests run on the CPU backend, so the TPU-shaped codepaths
(ops/paged_attention.py, Tick Forge's jitted segments) are exercised
there in interpret mode — and a kernel that passes in interpret mode
has not thereby lowered for a TPU.
This module turns "will it compile for TPU" into a static, hardware-free
proof with three layers:

1. **Shared static gate** — ``check_tpu_block_rules`` / ``lane_pad`` /
   ``check_block_specs``, the single source of truth for the Mosaic
   (8, 128) tiling rules a Pallas kernel's layout is held to.
   Violations raise :class:`LoweringRuleViolation`, a ``ValueError``
   carrying the violated rule's id.
2. **AOT prover** — :func:`prove_lowering` runs every registered kernel
   family through full TPU (Mosaic) lowering via
   ``jax.export.export(jax.jit(fn), platforms=["tpu"])`` against
   abstract ``ShapeDtypeStruct`` args: compile-only, zero device access,
   works under ``JAX_PLATFORMS=cpu``. Families cover the pow2 pad
   ladder plus the known crash shapes (head_dim 1/32/128/129); VMEM footprints are estimated statically from the
   BlockSpecs and checked against the per-core budget.
3. **Content-addressed manifest** — :func:`write_manifest` emits
   ``LOWERING_r16.json`` with a sha256 per case over the serialized
   StableHLO, so CI diffs catch lowering regressions (a kernel that
   stops lowering, a silently changed module) without hardware.

``engine/compile.py`` registers each segment program it builds at
runtime via :func:`register_program`, so a live process can prove its
actual compiled tick against the TPU rules too (family
``tick_forge_live``).

Module-level imports stay light (no jax): ops modules import this for
the shared gate, and ``pathway_tpu/__init__`` imports analysis early.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import math
from typing import Any, Callable, Iterable

from pathway_tpu.analysis.diagnostics import Diagnostic, Severity

# Mosaic vector-layout geometry: a vreg tiles (sublane, lane) = (8, 128)
# for 32-bit types; every Pallas block's trailing two dims must respect
# it.
SUBLANE = 8
LANE = 128

# Per-core VMEM budget the static estimator checks block residency
# against (v4/v5e-class cores carry 16 MiB of VMEM).
VMEM_LIMIT_BYTES = 16 * 1024 * 1024

MANIFEST_NAME = "LOWERING_r16.json"

# rule ids a finding/manifest entry can name
RULE_8X128 = "mosaic-8x128"
RULE_LANE_PAD = "lane-pad"
RULE_LOWER = "tpu-lowering"
RULE_VMEM = "vmem-budget"


class LoweringRuleViolation(ValueError):
    """A statically-decidable TPU lowering rule was violated.

    Subclasses ``ValueError`` so pre-existing gates (``pytest.raises
    (ValueError)`` in the kernel tests) keep working; carries the stable
    rule id so prover findings can name the violated rule."""

    def __init__(self, rule: str, message: str):
        super().__init__(message)
        self.rule = rule


def lane_pad(d: int) -> int:
    """``d`` padded up to the TPU lane width (multiple of 128) — the
    rule a kernel applies to its minor output dim (paged_attention's
    head_dim pool width)."""
    return -(-int(d) // LANE) * LANE


def check_tpu_block_rules(block_shape, array_shape) -> None:
    """Static mirror of the Mosaic lowering rule: the last two dims of a
    block must be divisible by (8, 128) respectively, or equal the
    corresponding overall-array dims. Raises
    :class:`LoweringRuleViolation` otherwise — the compiled-mode test
    gate calls this for every spec a kernel uses so an un-lowerable
    shape fails the suite even on the CPU backend."""
    if len(block_shape) != len(array_shape):
        raise LoweringRuleViolation(
            RULE_8X128,
            f"block rank {len(block_shape)} != array rank "
            f"{len(array_shape)}",
        )
    if len(block_shape) < 2:
        return
    checks = (
        (block_shape[-2], array_shape[-2], SUBLANE),
        (block_shape[-1], array_shape[-1], LANE),
    )
    for blk_dim, arr_dim, align in checks:
        if blk_dim % align != 0 and blk_dim != arr_dim:
            raise LoweringRuleViolation(
                RULE_8X128,
                f"block shape {tuple(block_shape)} vs array "
                f"{tuple(array_shape)}: dim {blk_dim} is neither "
                f"divisible by {align} nor equal to the array dim "
                f"{arr_dim}",
            )


def check_block_specs(spec_pairs: Iterable[tuple[Any, tuple]]) -> None:
    """Gate a kernel's whole layout: ``spec_pairs`` is the
    [(BlockSpec, array_shape)] list the ops ``_specs`` builders return."""
    for spec, arr_shape in spec_pairs:
        check_tpu_block_rules(spec.block_shape, arr_shape)


def estimate_vmem_bytes(
    spec_pairs: Iterable[tuple[Any, tuple]],
    scratch_shapes: Iterable[tuple] = (),
    itemsize: int = 4,
) -> int:
    """Static VMEM residency of one grid step, from the BlockSpecs alone:
    every in/out block is double-buffered (Mosaic overlaps the next grid
    step's copy with compute), scratch is single-buffered."""
    blocks = sum(
        math.prod(spec.block_shape) * itemsize for spec, _ in spec_pairs
    )
    scratch = sum(math.prod(s) * itemsize for s in scratch_shapes)
    return 2 * blocks + scratch


# ---------------------------------------------------------------------------
# kernel-family registry


@dataclasses.dataclass
class LoweringCase:
    """One provable shape of one kernel family.

    ``build`` returns ``(fn, abstract_args)`` for the AOT export;
    ``static_check`` runs the shared gate (raises on violation);
    ``expect`` is "lower" for shapes that must compile and "reject" for
    shapes the gate must refuse (a gate that stops rejecting a known-bad
    shape is itself a regression); ``vmem`` returns the static VMEM
    estimate in bytes."""

    family: str
    name: str
    shape: dict
    build: Callable[[], tuple[Callable, tuple]] | None = None
    static_check: Callable[[], None] | None = None
    expect: str = "lower"  # "lower" | "reject"
    vmem: Callable[[], int] | None = None
    x64: bool = False


# family name -> provider returning that family's built-in case ladder
FAMILIES: dict[str, Callable[[], list[LoweringCase]]] = {}
# family name -> builder turning a user shape dict into one LoweringCase
FAMILY_SHAPES: dict[str, Callable[[dict], LoweringCase]] = {}


def kernel_family(name: str):
    """Register a kernel family's built-in case provider."""

    def deco(fn):
        FAMILIES[name] = fn
        return fn

    return deco


def family_shape(name: str):
    """Register a family's user-shape case builder (``--prove-shape``)."""

    def deco(fn):
        FAMILY_SHAPES[name] = fn
        return fn

    return deco


def parse_shape_spec(spec: str) -> tuple[str, dict]:
    """``"paged_attention:head_dim=129,b=4"`` -> (family, {dims}).
    Values parse as ints."""
    family, _, rest = spec.partition(":")
    family = family.strip()
    if not family:
        raise ValueError(f"empty family in shape spec {spec!r}")
    shape: dict = {}
    if rest.strip():
        for part in rest.split(","):
            key, sep, val = part.partition("=")
            if not sep:
                raise ValueError(
                    f"bad shape spec {spec!r}: expected key=value, "
                    f"got {part!r}"
                )
            try:
                shape[key.strip()] = int(val)
            except ValueError:
                raise ValueError(
                    f"bad shape spec {spec!r}: {val!r} is not an int"
                ) from None
    return family, shape


def case_for_shape(family: str, shape: dict) -> LoweringCase:
    if family not in FAMILY_SHAPES:
        raise ValueError(
            f"unknown kernel family {family!r}; "
            f"registered: {sorted(FAMILY_SHAPES)}"
        )
    case = FAMILY_SHAPES[family](dict(shape))
    # a user-supplied shape is an assertion it should ship: the gate
    # refusing it is an ERROR finding, never an expected rejection
    case.expect = "lower"
    return case


# --- paged_attention -------------------------------------------------------


def _paged_case(
    b: int, h: int, p: int, dp: int, n_pages: int, max_pages: int
):
    from pathway_tpu.ops import paged_attention as pa

    def static_check():
        pa.validate_lowering(b, h, p, dp, n_pages, max_pages)

    expect = "lower" if dp % LANE == 0 else "reject"
    build = None
    vmem = None
    if expect == "lower":

        def build():
            import functools

            import jax
            import jax.numpy as jnp

            fn = functools.partial(
                pa.paged_attention.__wrapped__,
                sm_scale=1.0 / math.sqrt(dp),
                interpret=False,
            )
            args = (
                jax.ShapeDtypeStruct((b, h, dp), jnp.float32),
                jax.ShapeDtypeStruct((n_pages, h, p, dp), jnp.float32),
                jax.ShapeDtypeStruct((n_pages, h, p, dp), jnp.float32),
                jax.ShapeDtypeStruct((b, max_pages), jnp.int32),
                jax.ShapeDtypeStruct((b,), jnp.int32),
            )
            return fn, args

        def vmem():
            _g, ins, outs, _sh = pa._specs(b, h, p, dp, n_pages, max_pages)
            scratch = ((h, LANE), (h, LANE), (h, dp))
            return estimate_vmem_bytes(ins + outs, scratch)

    return LoweringCase(
        "paged_attention",
        f"b{b}_h{h}_p{p}_dp{dp}",
        {
            "b": b,
            "h": h,
            "p": p,
            "head_dim": dp,
            "n_pages": n_pages,
            "max_pages": max_pages,
        },
        build=build,
        static_check=static_check,
        expect=expect,
        vmem=vmem,
    )


@kernel_family("paged_attention")
def _paged_cases() -> list[LoweringCase]:
    return [
        _paged_case(8, 4, 16, 128, 32, 8),
        _paged_case(4, 8, 8, 256, 16, 4),
        # the head_dim ladder's known-bad rungs: 1, 32 and 129 are not
        # lane-padded and must be rejected by the shared gate
        _paged_case(8, 4, 16, 1, 32, 8),
        _paged_case(8, 4, 16, 32, 32, 8),
        _paged_case(8, 4, 16, 129, 32, 8),
    ]


@family_shape("paged_attention")
def _paged_shape(shape: dict) -> LoweringCase:
    return _paged_case(
        shape.pop("b", 8),
        shape.pop("h", 4),
        shape.pop("p", 16),
        shape.pop("head_dim", shape.pop("dp", 128)),
        shape.pop("n_pages", 32),
        shape.pop("max_pages", 8),
    )


# --- tick_forge (compiled segment programs) --------------------------------


def _forge_case(rows: int) -> LoweringCase:
    def build():
        import jax
        import numpy as np

        import pathway_tpu as pw
        from pathway_tpu.engine.compile import _build_program
        from pathway_tpu.engine.nodes import ALL_NODES

        # declare a canonical stateless chain (map + filter, the shapes
        # plan_segments fuses) without leaking nodes into the caller's
        # declared graph
        n0 = len(ALL_NODES)
        try:
            t = pw.debug.table_from_rows(
                pw.schema_from_types(x=int, y=float), [(1, 1.0)]
            )
            mapped = t.select(
                z=pw.this.x * 2 + 1, w=pw.this.y * 0.5 + pw.this.y
            )
            filt = mapped.filter(pw.this.z > 0)
            chain = [mapped._node, filt._node]
            external = list(chain[0].inputs[0].column_names)
            dtypes = {
                "x": np.dtype("int64"),
                "y": np.dtype("float64"),
            }
            prog = _build_program(chain, external, dtypes)
        finally:
            del ALL_NODES[n0:]
        args = tuple(
            jax.ShapeDtypeStruct((rows,), dtypes[c]) for c in prog.in_cols
        )
        return prog.fn, args

    return LoweringCase(
        "tick_forge",
        f"map_filter_rows{rows}",
        {"rows": rows, "cols": 2},
        build=build,
        x64=True,
    )


@kernel_family("tick_forge")
def _forge_cases() -> list[LoweringCase]:
    # three rungs of the pow2 row-bucket ladder engine/compile.py pads
    # batches onto (row_bucket): floor, a mid rung, a large rung
    return [_forge_case(8), _forge_case(1024), _forge_case(8192)]


@family_shape("tick_forge")
def _forge_shape(shape: dict) -> LoweringCase:
    return _forge_case(shape.pop("rows", 1024))


# --- live segment programs -------------------------------------------------

# segment programs the running engine registered (engine/compile.py
# SegmentRunner._program_for): proven under family "tick_forge_live"
_LIVE_PROGRAMS: dict[str, LoweringCase] = {}
_LIVE_CAP = 64


def register_program(
    name: str,
    fn: Callable,
    arg_structs: tuple,
    *,
    x64: bool = True,
    meta: dict | None = None,
) -> None:
    """Record a jitted segment program for device-free TPU proving.
    Called by the engine after each successful segment build; bounded,
    idempotent per name, and never raises (the ledger must not be able
    to take the tick down)."""
    try:
        if len(_LIVE_PROGRAMS) >= _LIVE_CAP and name not in _LIVE_PROGRAMS:
            return
        args = tuple(arg_structs)
        _LIVE_PROGRAMS[name] = LoweringCase(
            "tick_forge_live",
            name,
            dict(meta or {}),
            build=lambda: (fn, args),
            x64=x64,
        )
    except Exception:  # pragma: no cover - defensive: never break the tick
        pass


def live_cases() -> list[LoweringCase]:
    return [_LIVE_PROGRAMS[k] for k in sorted(_LIVE_PROGRAMS)]


def clear_live_programs() -> None:
    _LIVE_PROGRAMS.clear()


# ---------------------------------------------------------------------------
# the prover


@dataclasses.dataclass
class LoweringReport:
    """Outcome of one :func:`prove_lowering` pass: per-case manifest
    entries plus Doctor-style findings for anything that violated a rule
    or failed to lower."""

    platform: str
    entries: list[dict] = dataclasses.field(default_factory=list)
    findings: list[Diagnostic] = dataclasses.field(default_factory=list)

    def by_status(self, status: str) -> list[dict]:
        return [e for e in self.entries if e["status"] == status]

    def to_manifest(self) -> dict:
        entries = sorted(
            self.entries, key=lambda e: (e["family"], e["case"])
        )
        body = json.dumps(entries, sort_keys=True, default=str)
        return {
            "version": 1,
            "platform": self.platform,
            "vmem_limit_bytes": VMEM_LIMIT_BYTES,
            "content_sha256": hashlib.sha256(
                body.encode("utf-8")
            ).hexdigest(),
            "cases": entries,
        }


def _export_case(fn: Callable, args: tuple, platform: str, x64: bool):
    import jax
    from jax import export as jexport

    wrapped_t = getattr(jax.stages, "Wrapped", ())
    if not isinstance(fn, wrapped_t):
        fn = jax.jit(fn)
    ctx = (
        jax.enable_x64(True) if x64 else contextlib.nullcontext()
    )
    # drop caller-frame provenance from MLIR locations: the loc() lines
    # otherwise embed the *call site* of the prover, which would make
    # the content hash depend on who invoked it
    saved_limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        with ctx:
            return jexport.export(fn, platforms=[platform])(*args)
    finally:
        jax.config.update(
            "jax_traceback_in_locations_limit", saved_limit
        )


def _condense(exc: BaseException, limit: int = 300) -> str:
    """First line of the deepest cause — Mosaic wraps the real
    NotImplementedError several layers down."""
    root = exc
    while root.__cause__ is not None:
        root = root.__cause__
    msg = f"{type(root).__name__}: {root}".splitlines()[0]
    return msg[:limit]


def prove_lowering(
    families: Iterable[str] | None = None,
    cases: Iterable[LoweringCase] | None = None,
    platform: str = "tpu",
    include_live: bool = True,
) -> LoweringReport:
    """AOT-lower every selected case for ``platform`` with zero device
    access and return the report.

    Per case: (1) the shared static gate runs first — expected-reject
    cases must be refused here (a gate regression is an ERROR), and a
    gate refusal of an expected-lower case is an ERROR finding naming
    the kernel, shape and violated rule; (2) surviving cases AOT-export
    through the real Mosaic lowering pipeline and record a sha256 over
    the serialized StableHLO; (3) static VMEM estimates are checked
    against :data:`VMEM_LIMIT_BYTES`."""
    selected: list[LoweringCase]
    if cases is not None:
        selected = list(cases)
    else:
        fams = sorted(FAMILIES) if families is None else list(families)
        unknown = sorted(set(fams) - set(FAMILIES))
        if unknown:
            raise ValueError(
                f"unknown kernel family id(s) {unknown}; "
                f"registered: {sorted(FAMILIES)}"
            )
        selected = []
        for fam in fams:
            selected.extend(FAMILIES[fam]())
        if include_live:
            selected.extend(live_cases())

    report = LoweringReport(platform=platform)
    for case in selected:
        entry: dict = {
            "family": case.family,
            "case": case.name,
            "shape": dict(case.shape),
            "expect": case.expect,
        }
        report.entries.append(entry)

        violation: LoweringRuleViolation | None = None
        if case.static_check is not None:
            try:
                case.static_check()
            except LoweringRuleViolation as exc:
                violation = exc
            except ValueError as exc:  # a gate predating the rule tags
                violation = LoweringRuleViolation(RULE_8X128, str(exc))

        if case.expect == "reject":
            if violation is None:
                entry["status"] = "gate-regression"
                report.findings.append(
                    Diagnostic(
                        RULE_LOWER,
                        Severity.ERROR,
                        f"{case.family} {case.name} {case.shape}: the "
                        "shared lowering gate no longer rejects this "
                        "known-bad shape",
                        fix_hint="restore the check in "
                        "analysis/lowering.py (check_tpu_block_rules / "
                        "the family's validate_lowering)",
                        data={
                            "family": case.family,
                            "case": case.name,
                            "shape": dict(case.shape),
                        },
                    )
                )
            else:
                entry["status"] = "rejected"
                entry["rule"] = violation.rule
                entry["reason"] = str(violation)
            continue

        if violation is not None:
            entry["status"] = "gate-rejected"
            entry["rule"] = violation.rule
            entry["reason"] = str(violation)
            report.findings.append(
                Diagnostic(
                    RULE_LOWER,
                    Severity.ERROR,
                    f"kernel {case.family} shape {case.shape} rejected "
                    f"by the shared lowering gate "
                    f"(rule {violation.rule}): {violation}",
                    fix_hint="pad the offending dim with lane_pad() / "
                    "align blocks to the Mosaic (8, 128) tile — see "
                    "analysis/lowering.py",
                    data={
                        "family": case.family,
                        "case": case.name,
                        "shape": dict(case.shape),
                        "rule": violation.rule,
                    },
                )
            )
            continue

        if case.build is None:
            entry["status"] = "static-only"
        else:
            try:
                fn, args = case.build()
                exported = _export_case(fn, args, platform, case.x64)
                # hash the textual StableHLO, not the serialized
                # bytecode: the text is deterministic per shape while
                # the bytecode embeds per-process trace counters
                text = exported.mlir_module()
                entry["status"] = "lowered"
                entry["stablehlo_sha256"] = hashlib.sha256(
                    text.encode("utf-8")
                ).hexdigest()
                entry["mlir_bytes"] = len(text)
            except Exception as exc:
                entry["status"] = "lowering-failed"
                entry["error"] = _condense(exc)
                report.findings.append(
                    Diagnostic(
                        RULE_LOWER,
                        Severity.ERROR,
                        f"kernel {case.family} shape {case.shape} "
                        f"passed the static gate but failed "
                        f"{platform} lowering: {_condense(exc)}",
                        fix_hint="the static gate under-approximates a "
                        "Mosaic rule; reproduce with "
                        "jax.export.export(jax.jit(fn), "
                        "platforms=['tpu']) and extend the gate",
                        data={
                            "family": case.family,
                            "case": case.name,
                            "shape": dict(case.shape),
                            "rule": RULE_LOWER,
                        },
                    )
                )
                continue

        if case.vmem is not None:
            vmem = int(case.vmem())
            entry["vmem_bytes"] = vmem
            entry["vmem_frac"] = round(vmem / VMEM_LIMIT_BYTES, 4)
            if vmem > VMEM_LIMIT_BYTES:
                report.findings.append(
                    Diagnostic(
                        RULE_VMEM,
                        Severity.ERROR,
                        f"kernel {case.family} shape {case.shape}: "
                        f"static VMEM estimate {vmem} bytes exceeds the "
                        f"per-core budget {VMEM_LIMIT_BYTES}",
                        fix_hint="shrink the block shapes in the "
                        "family's _specs (smaller BLK / page size)",
                        data={
                            "family": case.family,
                            "case": case.name,
                            "shape": dict(case.shape),
                            "rule": RULE_VMEM,
                            "vmem_bytes": vmem,
                        },
                    )
                )
    return report


def write_manifest(
    report: LoweringReport, path: str = MANIFEST_NAME
) -> str:
    """Write the content-addressed manifest and return its path."""
    doc = report.to_manifest()
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True, default=str)
        fh.write("\n")
    return path
