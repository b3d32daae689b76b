"""The expert layer's kernels compiled at the published widths for a v5e that
is described, not attached: what the chip's compiler would refuse (a block
off the tiling, too much VMEM) fails here, at no chip time. Nothing runs."""

import os

import jax
import jax.numpy as jnp
import pytest

from pathway_tpu.ops import moe

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
