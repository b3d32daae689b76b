"""The residual mix's two kernels (``ops/residual_mix.py``), interpreted on
the CPU, against the float32 formulas of the benchmark's plain reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_trunk as ref
from pathway_tpu.ops import residual_mix
from pathway_tpu.xpacks.llm import _trunk
from pathway_tpu.xpacks.llm._trunk import TrunkConfig, TrunkRuntime

ALPHA = 0.1  # the coefficients follow the token, and 20 rounds still bring H_res's sums within 1e-4 of 1


def seeded(config: TrunkConfig, seed: int) -> dict:
    """One residual step's parameters as the program seeds them, float32, with
    scalars a_* large enough for the projection to matter."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = {
        name: _trunk._init_leaf(key, shape, kind, jnp.dtype(jnp.float32), config.hc_mult)
        for key, (name, (shape, kind)) in zip(keys, _trunk._mhc_shapes(config).items())
    }
    return dict(p, alpha=jnp.full((3,), ALPHA, jnp.float32))


@pytest.mark.parametrize("rows", [512, 200], ids=["whole_blocks", "padded_positions"])
@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("n", [2, 4])
def test_the_two_kernels_are_the_reference_formulas(n, d, rows):
    config = TrunkConfig(hidden_size=d, hc_mult=n)
    body = {
        "rms_norm_eps": config.rms_norm_eps, "hc_eps": config.hc_eps, "hc_sinkhorn_iters": config.hc_sinkhorn_iters,
        "mhc_h_res_clamp_min": config.mhc_h_res_clamp_min, "mhc_h_res_clamp_max": config.mhc_h_res_clamp_max,
    }
    p = seeded(config, seed=n)
    assert (residual_mix.row_block(512), residual_mix.row_block(208)) == (256, 16)
    streams = jax.random.normal(jax.random.PRNGKey(1), (n, 2, rows, d), jnp.float32)
    out = jnp.tanh(jax.random.normal(jax.random.PRNGKey(2), (2, rows, d), jnp.float32))

    mixed_in, packed = _trunk.mhc_coefficients(p, streams, config)
    h_pre, h_post, h_res = (np.asarray(h) for h in residual_mix.coefficients(packed, n, rows))
    ref_streams = jnp.transpose(streams, (1, 2, 0, 3))  # [B, T, n, d]
    want_pre, want_post, want_res = ref.residual_coefficients(p, ref_streams, body)
    assert np.abs(np.moveaxis(h_pre, 0, -1) - np.asarray(want_pre)).max() < 1e-5
    assert np.abs(np.moveaxis(h_post, 0, -1) - np.asarray(want_post)).max() < 1e-5
    assert np.abs(np.transpose(h_res, (2, 3, 0, 1)) - np.asarray(want_res)).max() < 1e-5
    # the coefficients follow the token, and H_res [n, n, B, T] is doubly stochastic after its 20 rounds
    assert h_pre.std(axis=(1, 2)).min() > 0.01 and h_res.min() > 0
    assert np.abs(h_res.sum(axis=0) - 1).max() < 1e-4 and np.abs(h_res.sum(axis=1) - 1).max() < 1e-4
    want_in = jnp.einsum("btn,btnd->btd", want_pre, ref_streams, precision="highest")
    assert np.abs(np.asarray(mixed_in) - np.asarray(want_in)).max() < 1e-5

    # the whole step, and the second kernel alone: in place it gives what it gives out of place
    got = _trunk._mhc(p, streams, lambda u: out, config)
    want = ref.residual(p, ref_streams, lambda u: out, body)
    assert np.abs(np.asarray(jnp.transpose(got, (1, 2, 0, 3))) - np.asarray(want)).max() < 1e-5
    kept = np.asarray(streams)
    beside = residual_mix.mix_out(streams, out, packed)
    assert np.array_equal(np.asarray(streams), kept)  # not donated: the old streams stand
    in_place = jax.jit(residual_mix.mix_out, donate_argnums=0)(streams, out, packed)
    assert np.array_equal(np.asarray(in_place), np.asarray(beside)) and np.array_equal(np.asarray(beside), np.asarray(got))


@pytest.mark.parametrize("streams, residual", [(4, "mhc_fused"), (1, None)])
def test_the_forward_says_which_residual_path_ran(streams, residual):
    """What ``embed.forward`` carries: ``TrunkRuntime.dispatch``'s account of a forward."""
    config = TrunkConfig(
        vocab_size=64, hidden_size=64, num_hidden_layers=1, num_attention_heads=2, q_lora_rank=16, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=64, n_routed_experts=0,
        first_k_dense_replace=1, hc_mult=streams,
    )
    runtime = TrunkRuntime(config, max_len=16, seed=1)
    _vectors, info = runtime.forward(np.ones((2, 16), np.int32), np.ones((2, 16), np.float32))
    assert info.get("residual") == residual
