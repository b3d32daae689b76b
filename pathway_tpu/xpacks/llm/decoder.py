"""Tiny bundled decoder for the generation serving plane.

The RAG loop the xpack serves (retrieve -> generate) needs a decoder
the repo can run WITHOUT downloading weights: a small pre-LN
transformer with deterministic random-init parameters (seeded, so the
writer, every replica, and a restarted process all build bit-identical
weights — the ``text_vector`` trick applied to a language model) and a
byte-level tokenizer (no vocab file).  The module is layout-compatible
with real checkpoints: ``init_params`` builds the same pytree a weight
loader would fill in, so swapping in trained weights is a loader, not a
rewrite.

The decode step is ONE jitted function per (batch-bucket, kernel):
embed -> N pre-LN transformer blocks whose attention reads the paged KV
pools through :mod:`pathway_tpu.ops.paged_attention` -> final norm ->
tied-embedding logits.  It also WRITES the current token's K/V into the
pools (functional ``.at[].set`` — the pools are donated so XLA updates
in place), which makes prefill just "decode the prompt token by token
and ignore the logits": one code path, so a kill/restart that restores
the pools mid-sequence provably continues the exact computation.

Sampling is host-side numpy (batch sizes are small at decode): greedy
at ``temperature == 0``, else top-k softmax sampling with a
per-(seed, step) PRNG so a restored run re-draws identical tokens.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.ops.paged_attention import (
    lane_pad,
    paged_attention,
    paged_attention_ref,
)

BOS = 256
EOS = 257
PAD = 258


@dataclass(frozen=True)
class DecoderConfig:
    """Static decoder shape — hashable, so it rides jit as a static arg.

    ``head_dim_padded`` (the KV-pool lane width) pads ``head_dim`` up to
    the TPU 128-lane boundary per the paged-attention layout rules; the
    padded tail is zero in q/k/v so the math is unchanged."""

    vocab_size: int = 259  # 256 bytes + BOS/EOS/PAD
    dim: int = 128
    n_layers: int = 2
    n_heads: int = 4
    head_dim: int = 32
    ffn_dim: int = 256
    max_len: int = 512
    page_size: int = 16

    @property
    def head_dim_padded(self) -> int:
        return lane_pad(self.head_dim)

    @property
    def max_pages(self) -> int:
        return -(-self.max_len // self.page_size)


def init_params(cfg: DecoderConfig, seed: int = 0) -> dict:
    """Deterministic random-init parameter pytree (numpy, f32): the
    same (cfg, seed) always builds bit-identical weights on every
    process — generation replicas need no weight distribution."""
    rng = np.random.default_rng(seed)

    def mat(*shape: int) -> np.ndarray:
        scale = 1.0 / np.sqrt(shape[0])
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    h = cfg.n_heads * cfg.head_dim
    params: dict = {
        "embed": mat(cfg.vocab_size, cfg.dim),
        "pos": (rng.standard_normal((cfg.max_len, cfg.dim)) * 0.02).astype(
            np.float32
        ),
        "lnf_scale": np.ones(cfg.dim, np.float32),
        "lnf_bias": np.zeros(cfg.dim, np.float32),
        "layers": [],
    }
    for _ in range(cfg.n_layers):
        params["layers"].append(
            {
                "ln1_scale": np.ones(cfg.dim, np.float32),
                "ln1_bias": np.zeros(cfg.dim, np.float32),
                "wq": mat(cfg.dim, h),
                "wk": mat(cfg.dim, h),
                "wv": mat(cfg.dim, h),
                "wo": mat(h, cfg.dim),
                "ln2_scale": np.ones(cfg.dim, np.float32),
                "ln2_bias": np.zeros(cfg.dim, np.float32),
                "w1": mat(cfg.dim, cfg.ffn_dim),
                "b1": np.zeros(cfg.ffn_dim, np.float32),
                "w2": mat(cfg.ffn_dim, cfg.dim),
                "b2": np.zeros(cfg.dim, np.float32),
            }
        )
    return params


def empty_pools(
    cfg: DecoderConfig, n_pages: int
) -> tuple[jax.Array, jax.Array]:
    """Zeroed K/V page pools ``[n_layers, n_pages, H, P, Dp]``.  Page 0
    is the sacrificial null page: padded batch slots carry an all-zero
    page table, so their (masked-out) writes land there and never
    clobber a live sequence."""
    shape = (
        cfg.n_layers,
        n_pages,
        cfg.n_heads,
        cfg.page_size,
        cfg.head_dim_padded,
    )
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


def _ln(x: jax.Array, scale: jax.Array, bias: jax.Array) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6) * scale + bias


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "kernel"),
    donate_argnums=(3, 4),
)
def decode_step(
    params: dict,
    tokens: jax.Array,  # [B] int32 token being fed at `positions`
    positions: jax.Array,  # [B] int32 (0-based; 0 for padded slots)
    k_pool: jax.Array,  # [L, n_pages, H, P, Dp] (donated)
    v_pool: jax.Array,  # (donated)
    page_tables: jax.Array,  # [B, max_pages] int32 (all-zero for pads)
    seq_lens: jax.Array,  # [B] int32 valid tokens INCLUDING this one;
    #                         0 marks a padded batch slot
    *,
    cfg: DecoderConfig,
    kernel: str = "ref",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step over the paged KV cache: write this token's K/V,
    attend over each sequence's cached prefix (ragged), and return
    ``(logits [B, vocab], k_pool, v_pool)``."""
    b = tokens.shape[0]
    hd, dp, p = cfg.head_dim, cfg.head_dim_padded, cfg.page_size
    scale = 1.0 / float(np.sqrt(hd))
    page_ids = jnp.take_along_axis(
        page_tables, (positions // p)[:, None], axis=1
    )[:, 0]  # [B] physical page of the current position
    slots = positions % p
    x = params["embed"][tokens] + params["pos"][positions]
    for li, layer in enumerate(params["layers"]):
        hnorm = _ln(x, layer["ln1_scale"], layer["ln1_bias"])

        def heads(y: jax.Array) -> jax.Array:
            y = y.reshape(b, cfg.n_heads, hd)
            return jnp.pad(y, ((0, 0), (0, 0), (0, dp - hd)))

        q = heads(hnorm @ layer["wq"]) * scale
        k = heads(hnorm @ layer["wk"])
        v = heads(hnorm @ layer["wv"])
        # write this token's K/V into its page slot (advanced indexing
        # over [pages, :, slots] yields [B, H, Dp] — matching k/v)
        k_pool = k_pool.at[li, page_ids, :, slots, :].set(k)
        v_pool = v_pool.at[li, page_ids, :, slots, :].set(v)
        attend = (
            paged_attention if kernel == "pallas" else paged_attention_ref
        )
        att = attend(
            q, k_pool[li], v_pool[li], page_tables, seq_lens,
            sm_scale=1.0,  # q is pre-scaled
        )
        att = att[:, :, :hd].reshape(b, cfg.n_heads * hd)
        x = x + att @ layer["wo"]
        hnorm = _ln(x, layer["ln2_scale"], layer["ln2_bias"])
        x = x + (
            jax.nn.gelu(hnorm @ layer["w1"] + layer["b1"]) @ layer["w2"]
            + layer["b2"]
        )
    x = _ln(x, params["lnf_scale"], params["lnf_bias"])
    logits = x @ params["embed"].T
    return logits, k_pool, v_pool


def sample_token(
    logits: np.ndarray,  # [vocab] f32 host logits of ONE sequence
    *,
    temperature: float = 0.0,
    top_k: int = 40,
    seed: int = 0,
    step: int = 0,
) -> int:
    """Greedy at temperature 0, else top-k softmax sampling with a
    per-(seed, step) PRNG — a restored run re-draws the same tokens."""
    if temperature <= 0.0:
        return int(np.argmax(logits))
    top_k = max(int(top_k), 1)
    idx = np.argpartition(logits, -top_k)[-top_k:]
    z = logits[idx].astype(np.float64) / float(temperature)
    z -= z.max()
    probs = np.exp(z)
    probs /= probs.sum()
    # mask to a non-negative 63-bit stream id: client-supplied seeds
    # may be negative, and default_rng rejects negative ints
    stream = ((int(seed) << 20) ^ int(step)) & 0x7FFFFFFFFFFFFFFF
    rng = np.random.default_rng(stream)
    return int(rng.choice(idx, p=probs))


# --- byte tokenizer ---------------------------------------------------------


def encode_text(text: str) -> list[int]:
    """BOS + UTF-8 bytes (truncation is the caller's policy)."""
    return [BOS] + list(str(text).encode("utf-8", errors="replace"))


def decode_tokens(tokens: list[int]) -> str:
    return bytes(t for t in tokens if 0 <= t < 256).decode(
        "utf-8", errors="replace"
    )
