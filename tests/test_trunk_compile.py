"""Programs of the main paths compiled at the cells' own sizes for a v5e that
is described, not attached: what the chip's compiler would refuse (a block
off the tiling, too much VMEM) fails here, and what it makes of a program
(a kernel, a sort) can be read, at no chip time. Nothing runs. One file for
all of them: the worker that is given it holds the TPU's library."""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from pathway_tpu.ops import block_attention, knn, moe

TOKENS, D, F, EXPERTS, TOP_K = 16384, 3584, 1024, 64, 4  # Xing4.0-29B-A4B, a 32 x 512 tick


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    # a compile for a described chip is written to the cache but cannot be read back
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def test_expert_layer_compiles_for_a_v5e(one_chip, no_cache, monkeypatch):
    # the kernel as Mosaic compiles it: the backend here is the CPU, which would interpret it
    monkeypatch.setattr(moe, "pallas_interpret", lambda: False)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def layer(h, valid, router, bias, w_gate, w_up, w_down):
        return moe.expert_layer(h, valid, router, bias, w_gate, w_up, w_down, top_k=TOP_K, scale=2.0)

    compiled = jax.jit(layer).lower(
        shape((TOKENS, D), jnp.bfloat16), shape((TOKENS,), jnp.bool_),
        shape((D, EXPERTS), jnp.float32), shape((EXPERTS,), jnp.float32),
        shape((EXPERTS, D, F), jnp.bfloat16), shape((EXPERTS, D, F), jnp.bfloat16),
        shape((EXPERTS, F, D), jnp.bfloat16),
    ).compile()
    text = compiled.as_text()
    # gate, up and down: a kernel each, not a dense expansion over the experts
    assert text.count("tpu_custom_call") >= 3 and moe.GMM_KERNEL_NAME in text
    # the rows in and out, not a [rows, experts, width] expansion
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def _largest_shape(hlo: str) -> int:
    """Elements of the largest array shape written in a piece of HLO text."""
    return max(math.prod(map(int, dims.split(","))) for dims in re.findall(r"\[([\d,]+)\]", hlo))


@pytest.mark.parametrize("bucket", [1, 8, 32])
def test_search_program_sorts_nothing_of_the_corpus_size(one_chip, no_cache, bucket):
    # `minilm-l6-384.retrieve`: 2,097,152 x 384 float32 rows, k = 10
    rows, dim, k = 2_097_152, 384, 10
    assert knn.topk_stage1(rows, k) == "blockmax"

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = knn.dense_topk_prepared.lower(
        shape((bucket, dim), jnp.float32), shape((rows, dim), jnp.float32),
        shape((rows,), jnp.float32), shape((rows,), jnp.bool_),
        k=k, metric="cosine", bf16=False,
    ).compile()
    # what is sorted is the k winning blocks' ids; the old first stage sorted all b x N scores
    sorted_sizes = [_largest_shape(line.split(" sort(")[0]) for line in compiled.as_text().splitlines() if " sort(" in line]
    assert all(n <= bucket * rows // 64 for n in sorted_sizes), sorted_sizes
    # the [b, N] scores exist once: written by the scan, read in place by
    # the block maxima and the gather (nothing of their size is a temporary
    # beside them)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * bucket * rows * 4 + 2**20


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("rows, width", [(1, 16384), (2, 8192)], ids=["1x16384", "2x8192"])
def test_the_document_forward_fits_a_v5e_and_keeps_no_logits(one_chip, no_cache, monkeypatch, rows, width):
    """command-a-plus-05-2026's cut (4 layers, 16 of 128 experts, published
    widths) at the 16,384 positions of a whole group: parameters and
    temporaries under the chip's 16.9 GB with room for the index, attention
    as the blocked kernel (no [B, H, T, T] array anywhere), the experts'
    4096 x 4096 matrices staged in column blocks."""
    import functools

    from pathway_tpu.xpacks.llm import _trunk

    monkeypatch.setattr(moe, "pallas_interpret", lambda: False)
    monkeypatch.setattr(block_attention, "pallas_interpret", lambda: False)
    config = _trunk.TrunkConfig.from_file(
        os.path.join(ROOT, "benchmarks", "configs", "command-a-plus-05-2026.json"), name="command-a-plus-05-2026"
    )
    template = jax.eval_shape(lambda: _trunk.init_params(config, 0, jnp.bfloat16))
    params = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), template)
    compiled = jax.jit(functools.partial(_trunk.forward, config=config)).lower(
        params,
        jax.ShapeDtypeStruct((rows, width), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((rows, width), jnp.float32, sharding=one_chip),
    ).compile()
    memory = compiled.memory_analysis()
    assert 9.4e9 < memory.argument_size_in_bytes < 9.6e9  # 4,733M parameters at bfloat16
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 13.5e9  # of 16.9 GB; the index takes 0.54
    text = compiled.as_text()
    # four attention kernels and twelve grouped matmuls, each of them Mosaic's
    assert text.count("tpu_custom_call") >= 16
    assert block_attention.ATTN_KERNEL_NAME in text and moe.GMM_KERNEL_NAME in text
    # the largest array is an expert tensor [16, 4096, 4096]: [B, H, T, T] logits would be 64 to 128 times that
    assert _largest_shape(text) == 16 * 4096 * 4096 < rows * 128 * width * width // 32
    assert moe.column_block(4096, 4096, 2) == 1024


@pytest.mark.parametrize("rows, width", [(1, 16384), (2, 8192)], ids=["1x16384", "2x8192"])
def test_the_paper_forward_fits_a_v5e_and_keeps_no_decay_mask(one_chip, no_cache, monkeypatch, rows, width):
    """granite-4.0-h-small's cut (10 layers, 36 of 72 experts, published
    widths) at the 16,384 positions of a whole group: parameters and
    temporaries under the chip's 16.9 GB with room for the index, every
    Mamba layer's scan as the chunked kernel (no [B, T / 256, H, 256, 256]
    decay mask anywhere), the one full layer as the blocked kernel."""
    import functools

    from pathway_tpu.ops import ssd_scan
    from pathway_tpu.xpacks.llm import _trunk

    for module in (moe, block_attention, ssd_scan):
        monkeypatch.setattr(module, "pallas_interpret", lambda: False)
    config = _trunk.TrunkConfig.from_file(
        os.path.join(ROOT, "benchmarks", "configs", "granite-4.0-h-small.json"), name="granite-4.0-h-small"
    )
    template = jax.eval_shape(lambda: _trunk.init_params(config, 0, jnp.bfloat16))
    params = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), template)
    compiled = jax.jit(functools.partial(_trunk.forward, config=config)).lower(
        params,
        jax.ShapeDtypeStruct((rows, width), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((rows, width), jnp.float32, sharding=one_chip),
    ).compile()
    memory = compiled.memory_analysis()
    assert 9.5e9 < memory.argument_size_in_bytes < 9.53e9  # 4,757,211,776 parameters at bfloat16
    # 5.3 GB of temporaries: one layer's take 2.9 (the experts' 168,448 rows of 4096 in and out, 1.38 GB
    # each, half of them never used: the other chip's pairs), the rest is how ten layers' buffers pack
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0e9  # of 16.9 GB; the index takes 0.54
    text = compiled.as_text()
    # nine scans, one attention kernel and thirty grouped matmuls, each of them Mosaic's
    assert text.count("tpu_custom_call") >= 40
    assert all(name in text for name in (ssd_scan.SSD_KERNEL_NAME, block_attention.ATTN_KERNEL_NAME, moe.GMM_KERNEL_NAME))
    # the largest array is the experts' rows [168,448, 4096] bfloat16 (1.38 GB); one layer's decay mask
    # [B, T / 256, 128, 256, 256] float32 would be 2.1 GB; the largest float32 shape written is the in-projection's accumulator
    assert _largest_shape(text) == moe.plan_rows(rows * width * 10, 36) * 4096
    mask = rows * width * 128 * 256
    floats = [math.prod(map(int, dims.split(","))) for dims in re.findall(r"f32\[([\d,]+)\]", text)]
    assert max(floats) <= rows * width * 16768 < mask and f"{width // 256},128,256,256]" not in text


@pytest.mark.parametrize("rows, width", [(1, 16384), (2, 8192)], ids=["1x16384", "2x8192"])
def test_the_delta_rule_forward_fits_a_v5e_and_keeps_no_chunk_products(one_chip, no_cache, monkeypatch, rows, width):
    """Qwen3-Next-80B-A3B's cut (4 layers, 256 of 512 experts, published
    widths) at the 16,384 positions of a whole group: parameters and
    temporaries under the chip's 16.9 GB with room for the index, every
    Gated DeltaNet layer's scan as the chunked kernel (no [B, T / 64, 32, 64,
    64] array anywhere), the gated full layer as the blocked kernel at head
    width 256."""
    import functools

    from pathway_tpu.ops import gated_delta
    from pathway_tpu.xpacks.llm import _trunk

    for module in (moe, block_attention, gated_delta):
        monkeypatch.setattr(module, "pallas_interpret", lambda: False)
    config = _trunk.TrunkConfig.from_file(
        os.path.join(ROOT, "benchmarks", "configs", "qwen3-next-80b-a3b.json"), name="qwen3-next-80b-a3b"
    )
    template = jax.eval_shape(lambda: _trunk.init_params(config, 0, jnp.bfloat16))
    params = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), template)
    compiled = jax.jit(functools.partial(_trunk.forward, config=config)).lower(
        params,
        jax.ShapeDtypeStruct((rows, width), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((rows, width), jnp.float32, sharding=one_chip),
    ).compile()
    memory = compiled.memory_analysis()
    assert 7.04e9 < memory.argument_size_in_bytes < 7.06e9  # 3,522,030,656 parameters, the router float32
    # 1.6-1.8 GB of temporaries (the experts' 196,352 rows of 2048 in and out, 0.8 GB each, half of them never
    # used: the other chip's pairs); 52% of the chip's 16.9 GB with the parameters
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 9.5e9
    text = compiled.as_text()
    # three scans, one attention kernel and twelve grouped matmuls, each of them Mosaic's
    assert text.count("tpu_custom_call") >= 16
    assert all(name in text for name in (gated_delta.GDN_KERNEL_NAME, block_attention.ATTN_KERNEL_NAME, moe.GMM_KERNEL_NAME))
    chunks = rows * width // gated_delta.CHUNK
    assert f"{chunks // rows},32,64,64]" not in text and f"{rows},{chunks // rows},32,64,64]" not in text
    # the largest float32 shape written is the in-projection's accumulator [B, T, 12,288]
    floats = [math.prod(map(int, dims.split(","))) for dims in re.findall(r"f32\[([\d,]+)\]", text)]
    assert max(floats) == rows * width * 12288


@pytest.mark.parametrize("batch", [32, 8], ids=["batch_32x512", "probe_8x512"])
def test_the_residual_mix_kernels_compile_for_a_v5e(one_chip, no_cache, monkeypatch, batch):
    """Xing4.0-29B-A4B's four streams of 3584 at the cell's two shapes: both
    kernels as Mosaic compiles them, 256 whole rows a block, the second one
    writing where it read."""
    from pathway_tpu.ops import residual_mix

    monkeypatch.setattr(residual_mix, "pallas_interpret", lambda: False)
    n, length, packed = 4, 512, residual_mix.packed_rows(4)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def mix_in(streams, proj, alpha, bias):
        return residual_mix.mix_in(streams, proj, alpha, bias, iters=20, eps=1e-6, rms_eps=1e-6, clamp=(-30.0, 30.0))

    going_in = jax.jit(mix_in).lower(
        shape((n, batch, length, D), jnp.bfloat16), shape((n, D, packed), jnp.bfloat16),
        shape((3,), jnp.float32), shape((packed,), jnp.float32),
    ).compile()
    assert residual_mix.MIX_IN_KERNEL_NAME in going_in.as_text() and residual_mix.row_block(length) == 256
    coming_out = jax.jit(residual_mix.mix_out, donate_argnums=0).lower(
        shape((n, batch, length, D), jnp.bfloat16), shape((batch, length, D), jnp.bfloat16),
        shape((batch, length // 256, packed, 256), jnp.float32),
    ).compile()
    assert residual_mix.MIX_OUT_KERNEL_NAME in coming_out.as_text()
    memory = coming_out.memory_analysis()
    assert memory.alias_size_in_bytes == n * batch * length * D * 2 and memory.temp_size_in_bytes < 2**20  # in place


def test_a_residual_step_passes_over_its_streams_twice_and_no_more(one_chip, no_cache, monkeypatch):
    """One ``_mhc`` sub-layer at the cell's 32 x 512: the stacked [4, 32, 512,
    3584] streams come out of the mix-out kernel and of nothing else (no
    ``jnp.stack``, no second elementwise pass), and the scope ``trunk.mhc``
    holds the two kernels and a few small operations on the parameters, not
    the hundred fusions XLA made of the formulas."""
    from pathway_tpu.observability import device_scopes
    from pathway_tpu.ops import residual_mix
    from pathway_tpu.xpacks.llm import _trunk

    monkeypatch.setattr(residual_mix, "pallas_interpret", lambda: False)
    config = _trunk.TrunkConfig.from_file(
        os.path.join(ROOT, "benchmarks", "configs", "xing4-29b-a4b.json"), name="xing4-29b-a4b"
    )
    template = jax.eval_shape(lambda: _trunk.init_params(config, 0, jnp.bfloat16)["layers"][0]["attn_res"])
    p = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), template)
    streams = jax.ShapeDtypeStruct((4, 32, 512, D), jnp.bfloat16, sharding=one_chip)

    def step(p, streams):
        return _trunk._mhc(p, streams, jnp.tanh, config)

    text = jax.jit(step, donate_argnums=1).lower(p, streams).compile().as_text()
    free = {"parameter", "bitcast", "get-tuple-element", "reshape", "tuple", "constant"}  # no operation of the device's
    rows = [r for r in device_scopes.rows_of(text) if r.opcode not in free]
    stacked = [r for r in rows if r.type.startswith("bf16[4,32,512,3584]")]
    assert [r.name.split(".")[0] for r in stacked] == [residual_mix.MIX_OUT_KERNEL_NAME]
    mhc = [r.name.split(".")[0] for r in rows if r.scope == "trunk.mhc"]
    assert residual_mix.MIX_IN_KERNEL_NAME in mhc and residual_mix.MIX_OUT_KERNEL_NAME in mhc
    assert len(mhc) < 20, mhc


@pytest.mark.parametrize("rows, width", [(1, 16384), (2, 8192)], ids=["1x16384", "2x8192"])
def test_the_window_sink_forward_fits_a_v5e_and_keeps_no_logits(one_chip, no_cache, monkeypatch, rows, width):
    """MiMo-V2.5's cut (7 layers, 16 of 256 experts, published widths) at
    the 16,384 positions of a whole group: parameters and temporaries under
    the chip's 16.9 GB with room for the index, the five window layers and
    the two full layers each as the blocked kernel (no [B, H, T, T] logits
    anywhere), the window's key block following its 128 tokens."""
    import functools

    from pathway_tpu.xpacks.llm import _trunk

    for module in (moe, block_attention):
        monkeypatch.setattr(module, "pallas_interpret", lambda: False)
    config = _trunk.TrunkConfig.from_file(os.path.join(ROOT, "benchmarks", "configs", "mimo-v2.5.json"), name="mimo-v2.5")
    template = jax.eval_shape(lambda: _trunk.init_params(config, 0, jnp.bfloat16))
    params = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), template)
    compiled = jax.jit(functools.partial(_trunk.forward, config=config)).lower(
        params,
        jax.ShapeDtypeStruct((rows, width), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((rows, width), jnp.float32, sharding=one_chip),
    ).compile()
    memory = compiled.memory_analysis()
    assert 6.70e9 < memory.argument_size_in_bytes < 6.72e9  # 3,351,836,480 parameters, the routers float32
    # 2.1-3.1 GB of temporaries (the dense layer's float32 gate and up at [B, T, 16,384]); 58% of the chip's 16.9 GB
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 10.5e9
    text = compiled.as_text()
    # seven attention kernels and eighteen grouped matmuls, each of them Mosaic's
    assert text.count("tpu_custom_call") >= 25
    assert block_attention.ATTN_KERNEL_NAME in text and moe.GMM_KERNEL_NAME in text
    assert block_attention.blocks(width, window=128) == (128, 128)
    # the largest float32 shape written is the dense layer's gate or up [B, T, 16,384]
    floats = [math.prod(map(int, dims.split(","))) for dims in re.findall(r"f32\[([\d,]+)\]", text)]
    assert max(floats) == rows * width * 16384 < rows * 64 * width * width
