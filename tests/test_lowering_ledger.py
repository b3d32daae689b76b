"""Lowering Ledger (pathway_tpu/analysis/lowering.py): the shared
Mosaic 8x128 gate, the device-free AOT prover (jax.export against the
TPU platform under JAX_PLATFORMS=cpu), the content-addressed manifest,
and live segment-program registration from the engine."""

import json

import jax
import jax.numpy as jnp
import pytest

from pathway_tpu.analysis import lowering as L

# --- shared static gate ----------------------------------------------------


def test_lane_pad_ladder():
    assert L.lane_pad(1) == 128
    assert L.lane_pad(10) == 128
    assert L.lane_pad(128) == 128
    assert L.lane_pad(129) == 256
    assert L.lane_pad(256) == 256


@pytest.mark.parametrize(
    "block, array",
    [
        ((8, 10), (8, 20)),
        # an unpadded k tile: middle dim 1 vs 977
        ((1, 1, 10), (1, 977, 10)),
        # a lane dim neither 128-aligned nor equal to the array's
        ((8, 10), (8, 2048)),
    ],
)
def test_block_rule_violation_carries_rule_id(block, array):
    with pytest.raises(L.LoweringRuleViolation) as ei:
        L.check_tpu_block_rules(block, array)
    assert ei.value.rule == L.RULE_8X128
    # stays a ValueError so pre-existing gates keep working
    assert isinstance(ei.value, ValueError)
    L.check_tpu_block_rules((8, 128), (64, 256))  # aligned: fine
    L.check_tpu_block_rules((8, 20), (8, 20))  # equals array dims: fine


def test_gate_is_single_source_of_truth():
    from pathway_tpu.ops import paged_attention as pa

    assert pa.check_tpu_block_rules is L.check_tpu_block_rules
    assert pa.check_block_specs is L.check_block_specs
    assert pa.lane_pad is L.lane_pad


def test_estimate_vmem_double_buffers_blocks():
    from jax.experimental import pallas as pl

    spec = pl.BlockSpec((8, 128), lambda i: (0, 0))
    est = L.estimate_vmem_bytes([(spec, (8, 256))], [(4, 128)])
    assert est == 2 * 8 * 128 * 4 + 4 * 128 * 4


def test_parse_shape_spec():
    fam, shape = L.parse_shape_spec("paged_attention:head_dim=129,b=4")
    assert fam == "paged_attention"
    assert shape == {"head_dim": 129, "b": 4}
    assert L.parse_shape_spec("paged_attention") == ("paged_attention", {})
    with pytest.raises(ValueError):
        L.parse_shape_spec("fam:k")
    with pytest.raises(ValueError):
        L.parse_shape_spec("fam:k=ten")
    with pytest.raises(ValueError):
        L.case_for_shape("bogus_family", {})


# --- the prover ------------------------------------------------------------


def test_prover_paged_attention_family_lowers_pad_ladder():
    rep = L.prove_lowering(
        families=["paged_attention"], include_live=False
    )
    assert not rep.findings, [f.message for f in rep.findings]
    lowered = rep.by_status("lowered")
    # the lane-padded rungs of the head_dim ladder
    assert {e["case"] for e in lowered} >= {
        "b8_h4_p16_dp128",
        "b4_h8_p8_dp256",
    }
    for e in lowered:
        assert len(e["stablehlo_sha256"]) == 64
        assert e["mlir_bytes"] > 0
        assert 0 < e["vmem_frac"] <= 1
    # and the un-lane-padded widths stay rejected by the gate
    rejected = rep.by_status("rejected")
    assert rejected and rejected[0]["rule"] == L.RULE_LANE_PAD


def test_prover_paged_attention_rejects_bad_head_dims():
    rep = L.prove_lowering(
        families=["paged_attention"], include_live=False
    )
    assert not rep.findings, [f.message for f in rep.findings]
    by_case = {e["case"]: e for e in rep.entries}
    for dp in (1, 32, 129):
        entry = by_case[f"b8_h4_p16_dp{dp}"]
        assert entry["status"] == "rejected"
        assert entry["rule"] == L.RULE_LANE_PAD
    assert by_case["b8_h4_p16_dp128"]["status"] == "lowered"


def test_unpadded_user_shape_is_error_finding():
    """The acceptance path: a deliberately unpadded head_dim injected
    via --prove-shape must be rejected with a finding naming the
    kernel, shape and violated rule."""
    case = L.case_for_shape("paged_attention", {"head_dim": 129})
    rep = L.prove_lowering(cases=[case])
    assert rep.entries[0]["status"] == "gate-rejected"
    (finding,) = rep.findings
    assert finding.severity.name == "ERROR"
    assert finding.data["family"] == "paged_attention"
    assert finding.data["shape"]["head_dim"] == 129
    assert finding.data["rule"] == L.RULE_LANE_PAD
    assert "paged_attention" in finding.message
    assert "129" in finding.message


def test_gate_regression_is_error():
    """A known-bad shape the gate stops rejecting is itself an ERROR."""
    case = L.LoweringCase(
        "fake",
        "now_accepted",
        {"k": 10},
        static_check=lambda: None,
        expect="reject",
    )
    rep = L.prove_lowering(cases=[case])
    assert rep.entries[0]["status"] == "gate-regression"
    (finding,) = rep.findings
    assert finding.severity.name == "ERROR"
    assert "no longer rejects" in finding.message


def test_lowering_failure_is_error_finding():
    def build():
        raise RuntimeError("synthetic lowering failure")

    case = L.LoweringCase("fake", "boom", {}, build=build)
    rep = L.prove_lowering(cases=[case])
    assert rep.entries[0]["status"] == "lowering-failed"
    (finding,) = rep.findings
    assert finding.severity.name == "ERROR"
    assert "synthetic lowering failure" in finding.message


def test_vmem_budget_finding():
    case = L.LoweringCase(
        "fake",
        "huge",
        {},
        vmem=lambda: L.VMEM_LIMIT_BYTES + 1,
    )
    rep = L.prove_lowering(cases=[case])
    (finding,) = rep.findings
    assert finding.data["rule"] == L.RULE_VMEM
    assert finding.severity.name == "ERROR"


def test_unknown_family_raises():
    with pytest.raises(ValueError, match="unknown kernel family"):
        L.prove_lowering(families=["bogus"])


# --- manifest --------------------------------------------------------------


def test_manifest_is_content_addressed(tmp_path):
    rep1 = L.prove_lowering(
        families=["paged_attention"], include_live=False
    )
    rep2 = L.prove_lowering(
        families=["paged_attention"], include_live=False
    )
    m1, m2 = rep1.to_manifest(), rep2.to_manifest()
    # deterministic: same cases -> same content hash
    assert m1["content_sha256"] == m2["content_sha256"]
    # any entry change moves the hash
    rep2.entries[0]["mlir_bytes"] += 1
    assert rep2.to_manifest()["content_sha256"] != m1["content_sha256"]

    path = tmp_path / "LOWERING_r16.json"
    L.write_manifest(rep1, str(path))
    doc = json.loads(path.read_text())
    assert doc["version"] == 1
    assert doc["platform"] == "tpu"
    assert doc["content_sha256"] == m1["content_sha256"]
    assert len(doc["cases"]) == len(rep1.entries)


# --- live segment-program registration -------------------------------------


def test_register_program_and_prove_live():
    L.clear_live_programs()
    try:

        @jax.jit
        def f(x):
            return x * 2 + 1

        L.register_program(
            "seg_test",
            f,
            (jax.ShapeDtypeStruct((64,), jnp.float32),),
            x64=False,
            meta={"rows": 64},
        )
        cases = L.live_cases()
        assert [c.name for c in cases] == ["seg_test"]
        rep = L.prove_lowering(cases=cases)
        assert rep.entries[0]["status"] == "lowered"
        assert not rep.findings
    finally:
        L.clear_live_programs()


def test_segment_runner_registers_with_ledger():
    """The engine hook: running a compiled tick hands the jitted
    segment program to the ledger, and the ledger proves it for TPU."""
    import numpy as np

    import pathway_tpu as pw
    from pathway_tpu.engine.compile import _build_program
    from pathway_tpu.engine.nodes import ALL_NODES

    L.clear_live_programs()
    n0 = len(ALL_NODES)
    try:
        t = pw.debug.table_from_rows(
            pw.schema_from_types(x=int), [(1,)]
        )
        mapped = t.select(y=pw.this.x * 3 + 1)
        chain = [mapped._node]
        external = list(chain[0].inputs[0].column_names)
        dtypes = {"x": np.dtype("int64")}
        prog = _build_program(chain, external, dtypes)
        args = tuple(
            jax.ShapeDtypeStruct((8,), dtypes[c]) for c in prog.in_cols
        )
        L.register_program("seg_x_rows8", prog.fn, args, meta={"rows": 8})
        rep = L.prove_lowering(cases=L.live_cases())
        assert rep.entries[0]["status"] == "lowered", rep.entries
        assert not rep.findings
    finally:
        del ALL_NODES[n0:]
        L.clear_live_programs()
