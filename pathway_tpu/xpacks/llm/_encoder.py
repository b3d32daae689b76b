"""Flax sentence-encoder running on TPU — the local-embedder engine behind
SentenceTransformerEmbedder / CrossEncoderReranker
(reference: xpacks/llm/embedders.py:270, rerankers.py:159 — there, torch
sentence-transformers on CPU/GPU; here a bf16 flax transformer jitted per
pad-bucket, batch-sharded over the mesh 'data' axis for multi-chip DP).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from pathway_tpu.observability import device_scopes
from pathway_tpu.observability.device_scopes import scope


class TransformerEncoder(nn.Module):
    vocab_size: int = 30522
    dim: int = 384
    depth: int = 6
    heads: int = 6
    mlp_ratio: int = 4
    max_len: int = 512
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, ids, mask):
        # the scopes are op metadata only (device_scopes): flax names its
        # modules by their order, so the parameter tree is as it was
        with scope("encoder.embed"):
            x = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype)(ids)
            pos = nn.Embed(self.max_len, self.dim, dtype=self.dtype)(
                jnp.arange(ids.shape[1])[None, :]
            )
            x = x + pos
        attn_mask = mask[:, None, None, :] * mask[:, None, :, None]
        for _ in range(self.depth):
            with scope("encoder.attention"):
                h = nn.LayerNorm(dtype=self.dtype)(x)
                h = nn.MultiHeadDotProductAttention(
                    num_heads=self.heads,
                    dtype=self.dtype,
                    deterministic=True,
                )(h, h, mask=attn_mask.astype(bool))
                x = x + h
            with scope("encoder.ffn"):
                h = nn.LayerNorm(dtype=self.dtype)(x)
                h = nn.Dense(self.dim * self.mlp_ratio, dtype=self.dtype)(h)
                h = nn.gelu(h)
                h = nn.Dense(self.dim, dtype=self.dtype)(h)
                x = x + h
        with scope("encoder.pool"):
            x = nn.LayerNorm(dtype=self.dtype)(x)
            # masked mean pool + L2 normalize (sentence-transformers convention)
            denom = jnp.maximum(mask.sum(axis=1, keepdims=True), 1.0)
            pooled = (x * mask[:, :, None]).sum(axis=1) / denom
            pooled = pooled.astype(jnp.float32)
            return pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-12)


class CrossEncoderHead(nn.Module):
    """Encoder + scalar relevance head (query/doc pair scoring)."""

    encoder: TransformerEncoder

    @nn.compact
    def __call__(self, ids, mask):
        emb = self.encoder(ids, mask)
        return nn.Dense(1, dtype=jnp.float32)(emb)[:, 0]


# The count's floor of 8 rows holds up to the 512 rung: a batch is never
# padded past 8 x 512 positions for its floor's sake, so a lone text of
# 2,048 tokens rides at 2 rows and one of 4,096 or more alone.
_FLOOR_POSITIONS = 8 * 512


def _bucket_batch(n: int, width: int = 0) -> int:
    """The rows a batch of ``n`` is padded to at ``width`` positions: a power
    of two from the floor."""
    b = max(1, min(8, _FLOOR_POSITIONS // max(width, 1)))
    while b < n:
        b *= 2
    return b


class EncoderRuntime:
    """Owns params + one jitted forward, which compiles once per (batch,
    seq) shape it is handed. ``dispatch`` pads the batch dimension to a
    power-of-two bucket and forwards the sequence dimension as given: which
    rows ride together at which length is the caller's plan
    (``embedders.length_groups``). Optional mesh → batch-dim DP sharding
    (multi-chip embedding throughput)."""

    def __init__(
        self,
        vocab_size: int = 30522,
        dim: int = 384,
        depth: int = 6,
        heads: int = 6,
        max_len: int = 512,
        seed: int = 0,
        mesh: Any = None,
        axis: str = "data",
        cross_encoder: bool = False,
        model_path: str | None = None,
        param_dtype: Any = None,
    ):
        self.max_len = max_len
        self.pretrained = False
        if model_path is not None and not cross_encoder:
            # pretrained BERT/MiniLM checkpoint: exact post-LN architecture
            # + safetensors weights (_bert.py); replaces the random-init
            # trunk entirely
            from pathway_tpu.xpacks.llm._bert import load_bert_checkpoint

            self.model, self.params = load_bert_checkpoint(
                model_path,
                dtype=param_dtype if param_dtype is not None else jnp.float32,
            )
            self.dim = self.model.dim
            self.max_len = min(max_len, self.model.max_len)
            self.pretrained = True
        else:
            enc = TransformerEncoder(
                vocab_size=vocab_size,
                dim=dim,
                depth=depth,
                heads=heads,
                max_len=max_len,
            )
            self.model = CrossEncoderHead(enc) if cross_encoder else enc
            self.dim = dim
            rng = jax.random.PRNGKey(seed)
            ids0 = jnp.zeros((1, 16), jnp.int32)
            mask0 = jnp.ones((1, 16), jnp.float32)
            self.params = self.model.init(rng, ids0, mask0)
        self.mesh = mesh
        self.axis = axis
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # replicate params; shard activations on batch
            self.params = jax.device_put(
                self.params, NamedSharding(mesh, P())
            )
            self._in_shard = NamedSharding(mesh, P(axis, None))
        else:
            self._in_shard = None

        def fwd(params, ids, mask):
            # op metadata only: a profiler capture shows the forward's ops
            # under this name instead of XLA's generated ones
            with scope("encoder.forward"):
                return self.model.apply(params, ids, mask)

        self._fwd = device_scopes.jit(fwd)
        self._ran: set[tuple] = set()  # (shape, ids dtype, mask dtype) of every forward made
        device_scopes.register(self)

    def device_programs(self):
        """What ``device_scopes.tables()`` lowers again: every forward made."""
        return device_scopes.forwards(self._fwd, self.params, self._ran, self._in_shard)

    def batch_bucket(self, n: int, width: int = 0) -> int:
        """The batch dimension a batch of ``n`` is padded to at ``width`` positions."""
        bucket = _bucket_batch(n, width)
        if self.mesh is not None:
            n_dev = self.mesh.shape[self.axis]
            bucket = max(bucket, n_dev)
            bucket = ((bucket + n_dev - 1) // n_dev) * n_dev
        return bucket

    def dispatch(
        self, ids: np.ndarray, mask: np.ndarray
    ) -> Callable[[], tuple[np.ndarray, dict]]:
        """Starts the forward of one padded batch and returns the call that
        waits for it: vectors [n, dim] and the shape that was really
        forwarded. Several batches dispatched before the first is fetched
        queue on the device and cost one wait, not one each."""
        n = ids.shape[0]
        bucket = self.batch_bucket(n, ids.shape[1])
        if bucket != n:
            ids = np.pad(ids, ((0, bucket - n), (0, 0)))
            mask = np.pad(mask, ((0, bucket - n), (0, 0)))
        ids_j = jnp.asarray(ids)
        mask_j = jnp.asarray(mask)
        if self._in_shard is not None:
            ids_j = jax.device_put(ids_j, self._in_shard)
            mask_j = jax.device_put(mask_j, self._in_shard)
        self._ran.add((ids_j.shape, ids_j.dtype, mask_j.dtype))
        out = self._fwd(self.params, ids_j, mask_j)
        out.copy_to_host_async()
        info = {
            "batch_bucket": bucket,
            "len_bucket": int(ids.shape[1]),
            "tokens_padded": int(ids.size),
        }
        return lambda: (np.asarray(out)[:n], info)

    def forward(self, ids: np.ndarray, mask: np.ndarray) -> tuple[np.ndarray, dict]:
        """``dispatch``, waited for."""
        return self.dispatch(ids, mask)()

    def forward_ids(self, ids: np.ndarray, mask: np.ndarray) -> np.ndarray:
        return self.forward(ids, mask)[0]
