"""``observability/device_scopes.py``: the vocabulary, the tables the owners
of the hot path's device programs hand out, and the compile cache's trap
(CPU, rehearsal sizes)."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.observability import device_scopes
from pathway_tpu.ops import knn
from pathway_tpu.xpacks.llm import _trunk
from pathway_tpu.xpacks.llm._encoder import EncoderRuntime
from pathway_tpu.xpacks.llm._trunk import TrunkConfig, TrunkRuntime
from tests.test_trunk import toy_dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALLOWED = set(device_scopes.VOCABULARY) | {device_scopes.NO_SCOPE}
INSIDE = {  # the scopes a kind opens inside its own
    "trunk.mamba2": {"trunk.mamba2.in_proj", "trunk.mamba2.conv", "trunk.mamba2.scan", "trunk.mamba2.gate_out"},
    "trunk.moe": {
        "trunk.moe.route", "trunk.moe.dispatch", "trunk.moe.gather",
        "trunk.moe.experts", "trunk.moe.combine", "trunk.moe.shared",
    },
}


def toy_trunk(config_name: str) -> TrunkConfig:
    """A benchmark configuration at its ``rehearse`` sizes."""
    path = os.path.join(ROOT, "benchmarks", "configs", config_name + ".json")
    return TrunkConfig.from_dict(toy_dict(path), name="toy-" + config_name)


def scopes_in(tables: dict) -> set:
    return {row.scope for rows in tables.values() for row in rows}


def test_scope_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="not.in.tuple"):
        device_scopes.scope("not.in.tuple")
    with device_scopes.scope("trunk.moe.dispatch"):
        pass
    assert len(set(device_scopes.VOCABULARY)) == len(device_scopes.VOCABULARY)
    assert device_scopes.DIGEST == device_scopes.digest(device_scopes.VOCABULARY)
    assert device_scopes.digest(device_scopes.VOCABULARY + ("trunk.more",)) != device_scopes.DIGEST


def test_scope_of_takes_the_innermost_name():
    path = "jit(forward_0)/trunk.moe/trunk.moe.experts/while/body/closed_call/dot_general"
    assert device_scopes.scope_of(path) == "trunk.moe.experts"
    assert device_scopes.scope_of("jit(forward_0)/transpose") == device_scopes.NO_SCOPE
    assert device_scopes.scope_of("") == device_scopes.NO_SCOPE


@pytest.mark.parametrize(
    "config_name, kinds",
    [
        ("xing4-29b-a4b", {"mla"}),
        ("command-a-plus-05-2026", {"gqa_window", "gqa_full"}),
        ("granite-4.0-h-small", {"mamba2", "gqa_full"}),
    ],
)
def test_every_instruction_of_a_trunks_forward_gets_a_scope(config_name, kinds):
    config = toy_trunk(config_name)
    table = config.layer_table()
    assert {layer.attention for layer in table} == kinds
    runtime = TrunkRuntime(config, max_len=64, seed=1)
    rng = np.random.default_rng(0)
    ids = rng.integers(2, config.vocab_size, size=(3, 32)).astype(np.int32)
    mask = (np.arange(32)[None, :] < np.array([32, 9, 20])[:, None]).astype(np.float32)
    runtime.forward(ids * mask.astype(np.int32), mask)
    tables = device_scopes.tables([runtime])
    assert list(tables) == [f"jit_forward_{device_scopes.DIGEST} ids[8, 32]"]
    (rows,) = tables.values()
    assert len(rows) > 50 and all(row.name and row.type and row.opcode for row in rows)
    expected = {"trunk.embed", "trunk.pool"}
    for layer in table:
        for opened in (_trunk.ATTENTION[layer.attention].scope, _trunk.FFN[layer.ffn].scope):
            expected |= {opened} | INSIDE.get(opened, set())
        if layer.residual == "mhc":
            expected.add("trunk.mhc")
    # every scope the layer table uses names an instruction of the optimized
    # program. Which of them are rows of their own is the compiler's choice:
    # XLA's CPU backend fuses the experts' row gather, a combine or a
    # convolution into a consumer of another scope, where on the chip they
    # feed a Pallas call and stay operations of their own
    ((_label, program, args, kwargs),) = runtime.device_programs()
    text = program.lower(*args, **kwargs).compile().as_text()
    named = {device_scopes.scope_of(path) for path in re.findall(r'op_name="([^"]*)"', text)}
    assert expected <= named <= ALLOWED
    found = {row.scope for row in rows}
    assert found <= named | {device_scopes.NO_SCOPE}
    assert {"trunk.embed", "trunk.pool"} <= found
    for layer in table:  # every layer's mixer and feed-forward have rows of their own
        for opened in (_trunk.ATTENTION[layer.attention].scope, _trunk.FFN[layer.ffn].scope):
            assert found & ({opened} | INSIDE.get(opened, set()))


def test_the_encoders_forward_names_its_parts():
    runtime = EncoderRuntime(vocab_size=128, dim=32, depth=2, heads=2, max_len=64)
    runtime.forward(np.ones((3, 16), np.int32), np.ones((3, 16), np.float32))
    runtime.forward(np.ones((3, 16), np.int32), np.ones((3, 16), np.float32))  # noted once
    runtime.forward(np.ones((9, 32), np.int32), np.ones((9, 32), np.float32))
    tables = device_scopes.tables([runtime])
    assert sorted(tables) == [
        f"jit_fwd_{device_scopes.DIGEST} ids[16, 32]",
        f"jit_fwd_{device_scopes.DIGEST} ids[8, 16]",
    ]
    for rows in tables.values():
        assert {row.scope for row in rows} <= ALLOWED
        assert {"encoder.embed", "encoder.attention", "encoder.ffn", "encoder.pool"} <= {r.scope for r in rows}


def test_the_corpus_hands_out_its_three_programs():
    corpus = knn.DeviceCorpus(16, capacity=2048)
    rng = np.random.default_rng(0)
    for key in range(40):
        corpus.upsert(key, rng.standard_normal(16))
    corpus.topk(rng.standard_normal((2, 16)).astype(np.float32), 3, "cosine")  # upload, prepare, search
    corpus.upsert(41, rng.standard_normal(16))
    corpus.topk(rng.standard_normal((2, 16)).astype(np.float32), 3, "cosine")  # scatter, search
    tables = device_scopes.tables([corpus])
    digest = device_scopes.DIGEST
    assert sorted(tables) == [
        f"jit__scatter_rows_{digest} rows[2048] copies1",
        f"jit_dense_topk_prepared_{digest} queries[2, 16] rows[2048] k3 cosine",
        f"jit_prepare_corpus_{digest} rows[2048] cosine",
    ]
    assert scopes_in(tables) <= ALLOWED
    by_program = {name.split("_" + digest)[0]: {row.scope for row in rows} for name, rows in tables.items()}
    assert "corpus.prepare" in by_program["jit_prepare_corpus"]
    assert "corpus.prepare" in by_program["jit__scatter_rows"]
    assert {"knn.scores", "knn.topk"} <= by_program["jit_dense_topk_prepared"]


def test_a_sharded_corpus_hands_out_its_search():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    corpus = knn.DeviceCorpus(
        16, capacity=2048, sharding=NamedSharding(mesh, P("data", None)), valid_sharding=NamedSharding(mesh, P("data"))
    )
    rng = np.random.default_rng(0)
    for key in range(40):
        corpus.upsert(key, rng.standard_normal(16))
    corpus.topk(rng.standard_normal((2, 16)).astype(np.float32), 3, "cosine")
    tables = device_scopes.tables([corpus])
    assert list(tables) == [f"jit__sharded_topk_impl_{device_scopes.DIGEST} queries[2, 16] rows[2048] k3 cosine"]
    assert {"knn.scores", "knn.topk"} <= scopes_in(tables) <= ALLOWED


def test_nothing_on_the_hot_path_asks_for_the_tables(monkeypatch):
    from pathway_tpu.stdlib.indexing._index_impls import TpuDenseKnnIndex
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    calls = []
    real = device_scopes.tables
    monkeypatch.setattr(device_scopes, "tables", lambda *a, **k: calls.append(1) or real(*a, **k))
    monkeypatch.setattr(device_scopes, "rows_of", lambda text: calls.append(1) or [])
    embedder = SentenceTransformerEmbedder(dim=32, depth=1, heads=2, max_len=64)
    vectors = embedder._embed_batch(["a b c", "d e f g"])
    index = TpuDenseKnnIndex(32, "cosine")
    for key, vector in enumerate(vectors):
        index.upsert(key, vector, None)
    index.search([(vectors[0], 1, None)])
    index.upsert(7, vectors[1], None)
    index.search([(vectors[0], 1, None)])
    assert not calls
    assert embedder.runtime._ran and len(index.corpus._ran) == 3  # noted all the same
    assert len(device_scopes.tables([embedder.runtime, index.corpus])) == 4 and len(calls) == 5


def test_a_changed_vocabulary_never_reads_the_old_ones_names(tmp_path, monkeypatch):
    """The compile cache's key strips debug info, so a program the cache
    gives back names the scopes of whichever build compiled it first; with
    the vocabulary's digest in the program's name the second build compiles
    anew."""
    from jax.experimental.compilation_cache import compilation_cache

    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)

    def build(name, jit):
        def fwd(x):
            with jax.named_scope(name):
                return jnp.tanh(x) @ x.T

        return jit(fwd).lower(x).compile().as_text()

    def under(vocabulary):
        monkeypatch.setattr(device_scopes, "VOCABULARY", vocabulary)
        monkeypatch.setattr(device_scopes, "_NAMES", frozenset(vocabulary))
        monkeypatch.setattr(device_scopes, "DIGEST", device_scopes.digest(vocabulary))

    before = {
        name: getattr(jax.config, name)
        for name in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache",
        )
    }
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        # the trap: the same function name, another scope in the source
        assert "trunk.old" in build("trunk.old", jax.jit)
        jax.clear_caches()
        stale = build("trunk.new", jax.jit)
        assert "trunk.old" in stale and "trunk.new" not in stale, "the cache no longer strips scope names"
        # the same two builds through device_scopes.jit
        under(("trunk.old",))
        first = build("trunk.old", device_scopes.jit)
        assert "trunk.old" in first and f"jit_fwd_{device_scopes.DIGEST}" in first
        jax.clear_caches()
        under(("trunk.new",))
        second = build("trunk.new", device_scopes.jit)
        assert "trunk.new" in second and "trunk.old" not in second
        assert [row.scope for row in device_scopes.rows_of(second) if row.opcode == "dot"] == ["trunk.new"]
        # an unchanged vocabulary costs nothing: the program comes from the cache
        files = sorted(os.listdir(tmp_path))
        jax.clear_caches()
        assert "trunk.new" in build("trunk.new", device_scopes.jit)
        assert sorted(os.listdir(tmp_path)) == files
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
        jax.clear_caches()


def test_a_fusion_has_the_scope_most_of_its_instructions_carry():
    """XLA fuses across scopes and names the fusion after one of its roots:
    the residual's mixing rides with the sums of the norm that follows it."""
    text = """HloModule jit_forward, is_scheduled=true

%fused_computation.1 (p0: bf16[8,64]) -> (f32[8], bf16[8,64]) {
  %p0 = bf16[8,64]{1,0} parameter(0)
  %convert.1 = f32[8,64]{1,0} convert(%p0), metadata={op_name="jit(forward)/trunk.mhc/convert_element_type"}
  %mul.1 = f32[8,64]{1,0} multiply(%convert.1, %convert.1), metadata={op_name="jit(forward)/trunk.mhc/mul"}
  %convert.2 = bf16[8,64]{1,0} convert(%mul.1), metadata={op_name="jit(forward)/trunk.mhc/convert_element_type"}
  %convert.3 = f32[8,64]{1,0} convert(%convert.2)
  %constant.1 = f32[] constant(0)
  %reduce_sum.1 = f32[8]{0} reduce(%convert.3, %constant.1), dimensions={1}, to_apply=%region_0.1, metadata={op_name="jit(forward)/reduce_sum"}
  ROOT %tuple.1 = (f32[8]{0}, bf16[8,64]{1,0}) tuple(%reduce_sum.1, %convert.2)
}

%fused_computation.2 (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %rsqrt.1 = f32[8]{0} rsqrt(%p0.1), metadata={op_name="jit(forward)/rsqrt"}
}

%fused_computation.3 (p0.2: f32[8]) -> f32[8] {
  %p0.2 = f32[8]{0} parameter(0)
  %exp.1 = f32[8]{0} exponential(%p0.2), metadata={op_name="jit(forward)/trunk.mla/exp"}
  ROOT %tanh.1 = f32[8]{0} tanh(%exp.1), metadata={op_name="jit(forward)/trunk.mhc/tanh"}
}

ENTRY %main.1 (x: bf16[8,64]) -> f32[8] {
  %x = bf16[8,64]{1,0} parameter(0), metadata={op_name="x"}
  %fusion.1 = (f32[8]{0}, bf16[8,64]{1,0}) fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(forward)/reduce_sum"}
  %get-tuple-element.1 = f32[8]{0} get-tuple-element(%fusion.1), index=0
  %fusion.2 = f32[8]{0} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(forward)/rsqrt"}
  ROOT %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(forward)/trunk.mhc/tanh"}
}
"""
    rows = {row.name: row for row in device_scopes.rows_of(text)}
    assert set(rows) == {"x", "fusion.1", "get-tuple-element.1", "fusion.2", "fusion.3"}  # no fusion's inside
    assert rows["fusion.1"] == ("fusion.1", "(f32[8], bf16[8,64])", "fusion", "trunk.mhc", 1, ("x",))  # not its calls=
    assert rows["fusion.3"].operands == ("fusion.2",)
    traced = "%fusion.3 = f32[8]{0:T(1024)S(1)} fusion(f32[8]{0:T(1024)} %fusion.2), kind=kLoop, calls=%fused_computation.3"
    assert device_scopes.parse_instruction(traced) == ("fusion.3", "f32[8]", "fusion", ("fusion.2",))
    assert rows["fusion.2"].scope == device_scopes.NO_SCOPE and rows["fusion.2"].spans == 1
    # a draw between two scopes goes to the fusion's own metadata
    assert rows["fusion.3"].scope == "trunk.mhc" and rows["fusion.3"].spans == 2
    assert rows["x"].scope == device_scopes.NO_SCOPE and rows["x"].opcode == "parameter"
