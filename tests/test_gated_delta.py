"""The gated delta rule (``ops/gated_delta.py``): the kernel, interpreted on
the CPU, and its XLA twin against the recurrence position by position."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.ops import gated_delta


def recurrence(q, k, v, g, beta, delta=True):
    """S_t = e^g_t S_{t-1} + beta_t k_t (v_t - e^g_t S_{t-1}^T k_t)^T, o_t = S_t^T q_t; value head j
    reads key head j // (H_v / H_k). ``delta=False``: S_t = e^g_t S_{t-1} + beta_t k_t v_t^T."""
    group = v.shape[2] // q.shape[2]
    q, k = jnp.repeat(q, group, axis=2), jnp.repeat(k, group, axis=2)

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[:, None, None] * state
        predicted = jnp.einsum("hkv,hk->hv", state, k_t, precision="highest") if delta else 0.0
        state = state + b_t[:, None, None] * k_t[:, :, None] * (v_t - predicted)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t, precision="highest")

    def one_row(*row):
        return jax.lax.scan(step, jnp.zeros((v.shape[2], q.shape[3], v.shape[3])), row)[1]

    return jax.vmap(one_row)(q, k, v, g, beta)


def scan_inputs(rows, length, key_heads, heads, width, seed=0):
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(rows, length, key_heads, width)) for _ in range(2))
    q, k = (a / np.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k + 0.3))  # keys share a direction, as silu's do
    v = rng.normal(size=(rows, length, heads, width))
    g = np.log(rng.uniform(0.9, 0.999, size=(rows, length, heads)))  # the benchmark's decays
    beta = rng.uniform(0.05, 0.95, size=(rows, length, heads))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)]


SHAPES = [
    # length, chunk, key heads, value heads, width: chunks that divide the length and that do not, one longer
    # than the row; the state carried over 4 to 8 chunks; two value heads a key head (as published) and one
    (64, 16, 2, 4, 16),
    (70, 16, 4, 8, 16),
    (100, 32, 1, 2, 32),
    (96, 256, 2, 4, 8),
    (128, 16, 4, 4, 16),
]


@pytest.mark.parametrize("length, chunk, key_heads, heads, width", SHAPES)
@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_the_chunked_scan_is_the_recurrence(path, length, chunk, key_heads, heads, width):
    inputs = scan_inputs(2, length, key_heads, heads, width, seed=length)
    want = np.asarray(recurrence(*inputs))
    scan = gated_delta.scan_pallas if path == "kernel" else gated_delta.scan_xla  # the kernel interpreted here
    got = np.asarray(scan(*inputs, chunk=chunk))
    assert got.shape == want.shape and np.abs(got - want).max() < 1e-4 * np.abs(want).max()


@pytest.mark.parametrize("length, chunk", [(64, 16), (96, 32)])
def test_a_scan_that_drops_the_carried_state_differs(length, chunk):
    inputs = scan_inputs(2, length, 2, 4, 16, seed=3)
    want = np.asarray(recurrence(*inputs))
    lost = np.concatenate(  # every chunk scanned from a zero state
        [np.asarray(gated_delta.scan_xla(*(a[:, i : i + chunk] for a in inputs), chunk=chunk)) for i in range(0, length, chunk)],
        axis=1,
    )
    assert np.abs(lost - want).max() > 0.05 * np.abs(want).max()
    assert np.abs(lost[:, :chunk] - want[:, :chunk]).max() < 1e-4 * np.abs(want).max()  # the first chunk is the same


@pytest.mark.parametrize("seed", [0, 1])
def test_a_scan_that_drops_the_delta_term_differs(seed):
    inputs = scan_inputs(2, 64, 2, 4, 16, seed=seed)
    want = np.asarray(gated_delta.scan_xla(*inputs, chunk=16))
    plain = np.asarray(recurrence(*inputs, delta=False))  # S += beta k v^T: decayed linear attention
    assert np.abs(plain - want).max() > 0.1 * np.abs(want).max()


def test_padding_with_no_beta_and_no_decay_passes_the_state_through():
    q, k, v, g, beta = scan_inputs(1, 48, 2, 4, 16, seed=5)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, 16)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
    q, k, v, g, beta = map(pad, (q, k, v, g, beta))
    q = q.at[:, 48:].set(q[:, 47:48])  # the same query after the padding reads the same state
    o = np.asarray(gated_delta.scan_xla(q, k, v, g, beta, chunk=16))
    assert np.abs(o[:, 48:] - o[:, 47:48]).max() < 1e-5


@pytest.mark.parametrize("size", [4, 16, 64, 128])
@pytest.mark.parametrize("alike", [0.0, 1.0])
def test_the_unit_lower_inverse_is_the_inverse(size, alike):
    """Keys drawn apart, and keys all alike (a chunk of padding: one token
    repeated), where the series over a whole chunk cancels terms of 1e16."""
    rng = np.random.default_rng(size)
    k = rng.normal(size=(size, 32)) * (1 - alike) + alike
    k /= np.linalg.norm(k, axis=1, keepdims=True)
    a = np.tril(rng.uniform(0.05, 0.95, size=(size, 1)) * (k @ k.T), -1)
    inverse = gated_delta.unit_lower_inverse(jnp.asarray(a, jnp.float32), lambda x, y: jnp.matmul(x, y, precision="highest"))
    assert np.abs(np.asarray(inverse) - np.linalg.inv(np.eye(size) + a)).max() < 1e-5


@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_a_chunk_of_one_token_repeated_is_the_recurrence(path):
    """The padding after a row's last token at the published chunk: keys,
    values and gates all alike."""
    q, k, v, g, beta = scan_inputs(1, 128, 2, 4, 16, seed=9)
    k, v, g = (jnp.broadcast_to(a[:, :1], a.shape) for a in (k, v, g))
    beta = jnp.full(beta.shape, 0.95, jnp.float32)
    want = np.asarray(recurrence(q, k, v, g, beta))
    scan = gated_delta.scan_pallas if path == "kernel" else gated_delta.scan_xla
    got = np.asarray(scan(q, k, v, g, beta, chunk=64))
    assert np.isfinite(got).all() and np.abs(got - want).max() < 1e-4 * np.abs(want).max()


def test_chunk_counts():
    assert gated_delta.chunks_useful([1, 64, 65, 5019]) == 1 + 1 + 2 + 79
    assert gated_delta.chunks_visited(2, 8192) == 256 and gated_delta.chunks_visited(1, 16384) == 256
    assert gated_delta.chunks_useful([70], chunk=16) == 5 and gated_delta.chunks_visited(3, 128, chunk=16) == 24
