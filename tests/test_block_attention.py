"""The blocked attention kernel (``ops/block_attention.py``) with a sink a
query head, a value narrower than the queries and keys, and a key block that
follows a window under ``BLOCK_K``, in interpret mode on the CPU: against a
plain softmax with the sink as one more logit, and at the old arguments the
program it traced before."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.ops import block_attention


def plain(q, k, v, scale, window, sinks):
    """softmax over the allowed keys and the query head's sink (a logit with a value of zero)."""
    length = q.shape[3]
    logits = jnp.einsum("bhgtd,bhsd->bhgts", q, k, precision="highest") * scale
    t, s = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
    allowed = (s <= t) if window is None else (s <= t) & (t - s < window)
    logits = jnp.where(allowed, logits, -jnp.inf)
    if sinks is not None:
        sink = jnp.broadcast_to(sinks[None, :, :, None, None], logits.shape[:-1] + (1,))
        logits = jnp.concatenate([logits, sink], axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)[..., :length]
    return jnp.einsum("bhgts,bhsd->bhgtd", probs, v, precision="highest")


def inputs(length, rows=2, kv_heads=2, group=3, width=24, width_v=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + length), 4)
    q = 2.0 * jax.random.normal(keys[0], (rows, kv_heads, group, length, width))
    k = jax.random.normal(keys[1], (rows, kv_heads, length, width))
    v = jax.random.normal(keys[2], (rows, kv_heads, length, width_v))
    sinks = 1.0 + 2.0 * jax.random.normal(keys[3], (kv_heads, group))
    return q, k, v, sinks


# length, window, block_q, block_k, real tokens a row: the window under, at and over the key block
SINK_CASES = [
    (64, 8, 16, 16, (64, 37)),
    (96, 16, 16, 16, (96, 5)),
    (96, 40, 16, 32, (0, 96)),
    (128, None, 32, 64, (128, 70)),
    (256, 16, 32, None, (256, 129)),  # the key block follows the window
    (200, 24, None, None, (200, 1)),  # a length off the ladder
]


@pytest.mark.parametrize("length, window, block_q, block_k, tokens", SINK_CASES)
def test_sinks_and_a_narrower_value_are_a_softmax_with_one_more_logit(length, window, block_q, block_k, tokens):
    q, k, v, sinks = inputs(length, rows=len(tokens))
    kw = dict(scale=0.2, window=window, block_q=block_q, block_k=block_k, sinks=sinks)
    got = np.asarray(block_attention.attention(q, k, v, lengths=jnp.asarray(tokens, jnp.int32), **kw))
    assert got.shape == q.shape[:-1] + (v.shape[-1],)
    want = np.asarray(plain(q, k, v, 0.2, window, sinks))
    size_q, _ = block_attention.blocks(length, block_q, block_k, window)
    for row, t in enumerate(tokens):
        live_until = min(-(-t // size_q) * size_q, length)
        assert np.abs(got[row, :, :, :t] - want[row, :, :, :t]).max(initial=0.0) < 2e-5
        assert not got[row, :, :, live_until:].any()  # a dead block writes zeros
    # the sink takes mass from the keys: without it the rows are another softmax's
    without = np.asarray(block_attention.attention(q, k, v, **dict(kw, sinks=None)))
    assert np.abs(without[:, :, :, : min(tokens)] - got[:, :, :, : min(tokens)]).max(initial=1.0) > 1e-2


def test_a_row_whose_first_blocks_are_all_masked_starts_from_its_sink():
    """Window 8, key blocks of 16: a query block's first key block holds no
    allowed pair for most of its rows; the sink's finite start keeps them
    exact, and a sink far below the logits is no sink."""
    q, k, v, sinks = inputs(128, rows=1)
    got = block_attention.attention(q, k, v, scale=0.2, window=8, sinks=sinks, block_q=32, block_k=16)
    assert np.abs(np.asarray(got) - np.asarray(plain(q, k, v, 0.2, 8, sinks))).max() < 2e-5
    faint = jnp.full(sinks.shape, -1e4)
    got = block_attention.attention(q, k, v, scale=0.2, window=8, sinks=faint, block_q=32, block_k=16)
    none = block_attention.attention(q, k, v, scale=0.2, window=8, block_q=32, block_k=16)
    assert np.abs(np.asarray(got) - np.asarray(none)).max() < 1e-6


# the parent's kernel at its own arguments, traced (``jax.make_jaxpr``: the
# pallas call, its grid, block specs and kernel body) before the sinks, the
# value's own width and the window's key block came in
OLD_ARGUMENTS = [
    ((2, 2, 4, 64, 16), None, 16, 32, "b80713d3a6064f8c"),
    ((2, 2, 4, 128, 16), 40, 32, 64, "b13bc9e1035a6f67"),
    ((1, 2, 8, 1024, 128), 4096, None, None, "f26fb67a475e4fed"),  # command-a's window layer
    ((1, 2, 8, 1024, 256), None, None, None, "90a0186a82afe409"),  # qwen3-next's full layer
    ((2, 1, 4, 100, 16), 16, None, None, "d20897d5bedd6588"),  # one block a row: the window's block is the row
]


@pytest.mark.parametrize("shape, window, block_q, block_k, traced", OLD_ARGUMENTS)
def test_at_the_old_arguments_the_kernel_is_the_program_it_was(shape, window, block_q, block_k, traced):
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct(shape[:2] + shape[3:], jnp.bfloat16)
    lengths = jax.ShapeDtypeStruct(shape[:1], jnp.int32)

    def call(q, k, v, lengths):
        return block_attention.attention(
            q, k, v, scale=0.125, window=window, lengths=lengths, block_q=block_q, block_k=block_k
        )

    program = str(jax.make_jaxpr(call)(q, kv, kv, lengths))
    assert hashlib.sha256(program.encode()).hexdigest()[:16] == traced


def test_the_key_block_follows_a_window_under_block_k():
    assert block_attention.window_block(None) == block_attention.BLOCK_K == 512
    assert block_attention.window_block(4096) == block_attention.window_block(512) == 512
    assert block_attention.window_block(128) == block_attention.WINDOW_BLOCK_K
    assert block_attention.window_block(16) == block_attention.WINDOW_BLOCK_K
    assert block_attention.window_block(300) == block_attention.WINDOW_BLOCK_K
    assert block_attention.blocks(16384, window=128) == (128, block_attention.WINDOW_BLOCK_K)
    assert block_attention.blocks(16384, window=4096) == block_attention.blocks(16384) == (128, 512)
    # at window 128 and blocks of 128, a query block visits two key blocks: half the pairs are allowed
    useful = block_attention.pairs_allowed(16384, 128) / block_attention.pairs_visited(16384, 128)
    assert 0.49 < useful < 0.51 and block_attention.pairs_visited(16384, 128, block_k=512) > 2.4 * block_attention.pairs_visited(16384, 128)


def visited_by_the_index_maps(length, window, block_q, block_k, tokens):
    """Grid steps the kernel computes, read off its own index maps and
    predicate step by step: a step computes where its row's query block is
    live and its key block is a new one within the block's range."""
    size_q, size_k = block_attention.blocks(length, block_q, block_k, window)
    padded = length + -length % max(size_q, size_k)
    steps = max(block_attention.visited_steps(padded, window, size_q, size_k))
    last = max((tokens + size_q - 1) // size_q - 1, 0)
    computed = 0
    for qi in range(padded // size_q):
        lo, hi = block_attention.kv_range(min(qi, last), size_q, size_k, window)
        mapped = [hi if qi > last else min(lo + j, hi) for j in range(steps)]  # the kv index map
        live = qi * size_q < tokens
        computed += sum(live and lo + j <= hi and mapped[j] == lo + j for j in range(steps))
    return computed * size_q * size_k


@pytest.mark.parametrize("window", [16, 128])
@pytest.mark.parametrize("length, tokens", [(256, 256), (1024, 1000), (1024, 129), (2048, 0)])
def test_pairs_visited_is_a_brute_count_of_the_blocks_the_kernel_visits(window, length, tokens):
    got = block_attention.pairs_visited(length, window, tokens=tokens)
    assert got == visited_by_the_index_maps(length, window, None, None, tokens)
    assert got >= block_attention.pairs_allowed(tokens, window)
