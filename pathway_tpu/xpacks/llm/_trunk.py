"""A decoder-style trunk built from a per-layer table, run as an embedder.

``TrunkConfig`` is read from a model's published ``config.json`` keys. Its
``layer_table`` names, layer by layer, the kind of attention, of feed-forward
and of residual path; ``ATTENTION``, ``FFN`` and ``RESIDUAL`` hold the blocks
by kind, so another architecture adds kinds, not branches. Kinds so far:

* attention ``mla``: multi-head latent attention in its expanded (prefill)
  form, YaRN rotary on the decoupled rotary part, causal, no cache;
  ``gqa_window`` and ``gqa_full``: grouped-query attention through
  ``ops/block_attention.py`` (no logits in HBM, blocks outside the mask and
  query blocks past a row's last real token skipped, each key-value head
  read once for its query heads), the first
  inside a sliding window with rotary over interleaved pairs, the second
  causal over the whole row and unrotated, its softmax scale the config's
  ``attention_multiplier`` where one is given; ``mamba2``: a Mamba-2 mixer in
  the attention's place (in-projection, depthwise causal convolution, the
  chunked state-space scan of ``ops/ssd_scan.py``, gated RMS norm,
  out-projection), prefill form, no state kept between calls;
  ``gated_deltanet``: a Gated DeltaNet mixer in the attention's place
  (``[q | k | v | z]`` and ``[b | a]`` projections, a bias-free depthwise
  causal convolution and silu, q and k L2-normalised a head, ``beta =
  sigmoid(b)`` and ``g = -exp(A_log) softplus(a + dt_bias)``, the chunked
  gated delta rule of ``ops/gated_delta.py``, a gated RMS norm a head,
  out-projection), prefill form; ``gqa_gated``: grouped-query attention
  through ``ops/block_attention.py`` with q and k through the config's norm
  a head, rotary by halves on the first ``head_dim * rotary_pct`` dims and
  the output times ``sigmoid(gate)``, the gate a second half of each query
  head's projection; ``swa_sink`` and ``gqa_partial`` (``mimo_v2``'s window
  and full layers): grouped-query attention through
  ``ops/block_attention.py`` with query and key heads of ``head_dim`` and
  value heads of their own width (``swa_v_head_dim``, ``v_head_dim``),
  rotary by halves on the first ``head_dim * rotary_pct`` dims, each kind
  with its own key-value heads and theta (``swa_num_key_value_heads`` and
  ``swa_rope_theta`` against ``num_key_value_heads`` and ``rope_theta``), the
  output times ``attention_value_scale``; the first sees the last
  ``sliding_window`` tokens and gives each query head a learned **sink** (a
  logit with no value, its ``sinks`` leaf), the second is causal over the
  whole row with none; a model's ``layer_types`` (or ``qwen3_next``'s
  ``full_attention_interval``, or ``mimo_v2``'s ``hybrid_layer_pattern``)
  names them layer by layer;
* feed-forward ``dense`` (gated silu) and ``moe`` (``ops/moe.py``: a sigmoid
  router, its top-k bias-corrected and scaled where the config says so, or
  the top-k of the logits softmaxed over the chosen k; dropless grouped
  experts; shared experts summed, averaged, or scaled by ``sigmoid(h
  w_sg)`` (``sigmoid_gate``), of the routed width each or of
  ``shared_intermediate_size`` together, or none where ``n_shared_experts``
  is 0; a layer has experts from ``first_k_dense_replace`` on where
  ``moe_layer_freq`` divides its index, or where the list ``moe_layer_freq``
  says 1);
* residual ``mhc``: manifold-constrained hyper-connections (arXiv:2512.24880),
  ``hc_mult`` residual streams mixed by a doubly stochastic matrix per token.
  A sub-layer's step is the two Pallas kernels of ``ops/residual_mix.py`` and
  nothing else: ``mix_in`` reads the streams once and makes the coefficients
  (float32: RMS scale, sigmoids, the Sinkhorn rounds) and the sub-layer's
  input, ``mix_out`` reads them once more with the sub-layer's output and
  writes the new streams where the old ones were;
  ``add``: ``x + m attn(norm x)``, then ``x + m ffn(norm x)``, ``m`` the
  config's ``residual_multiplier`` (1 where it has none); ``parallel``: one
  norm a layer, ``x + attn(h) + ffn(h)``;
* norm ``rms``, ``layer`` (mean-centred, a gain and no bias) and
  ``rms_offset`` (zero-centred: ``x / rms(x) * (1 + w)``, every norm of a
  ``qwen3_next`` table, its q/k norms too).

``TrunkConfig.from_dict`` reads the key names of the ``model_type`` it is
given (``cohere2_moe``'s, ``granitemoehybrid``'s, ``qwen3_next``'s and
``mimo_v2``'s beside the default ones) onto the same fields. The embedded
tokens are multiplied by ``embedding_multiplier`` where the config states
one.

``TrunkRuntime`` has ``EncoderRuntime``'s surface and is what
``SentenceTransformerEmbedder(trunk=...)`` runs: the whole forward over a
right-padded batch, causal, the final norm of the (summed) stream at the
last real token, float32, L2-normalised. Parameters and activations are
bfloat16 (float32 accumulation; router scores, softmax, residual
coefficients, norms and the pooled vector in float32), made on the device
from a seed. Left out: multi-token prediction, the output head, decoding.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.observability import device_scopes
from pathway_tpu.observability.device_scopes import scope
from pathway_tpu.ops import block_attention, gated_delta, moe, residual_mix, ssd_scan
from pathway_tpu.xpacks.llm._encoder import _bucket_batch

# ``cohere2_moe``'s names for what the fields below hold, and what its
# modelling code fixes without a key: no correction bias, no scaling, one stream
_COHERE2_MOE_KEYS = {
    "num_experts": "n_routed_experts",
    "num_shared_experts": "n_shared_experts",
    "expert_selection_fn": "scoring_func",
    "intermediate_size": "moe_intermediate_size",  # no key of its own for one expert's width
}
_COHERE2_MOE_FIXED = {"topk_method": "greedy", "routed_scaling_factor": 1.0, "hc_mult": 1}
# ``granitemoehybrid``'s: its modelling code has one shared expert, a plain
# top-k of the logits softmaxed over the chosen k, and one residual stream
_GRANITE_HYBRID_KEYS = {
    "num_local_experts": "n_routed_experts",
    "intermediate_size": "moe_intermediate_size",  # no key of its own for one expert's width
}
_GRANITE_HYBRID_FIXED = {
    "scoring_func": "softmax", "norm_topk_prob": True, "topk_method": "greedy", "routed_scaling_factor": 1.0,
    "n_shared_experts": 1, "first_k_dense_replace": 0, "hc_mult": 1,
}
# ``qwen3_next``'s: its modelling code has the top-k of the logits softmaxed
# over the chosen k, one shared expert behind a sigmoid gate, q/k norms, rotary
# by halves, zero-centred norms and one residual stream; its table follows
# from ``full_attention_interval`` (every n-th layer full, the others linear)
_QWEN3_NEXT_KEYS = {
    "num_experts": "n_routed_experts",
    "shared_expert_intermediate_size": "shared_intermediate_size",
    "partial_rotary_factor": "rotary_pct",
}
_QWEN3_NEXT_FIXED = {
    "scoring_func": "softmax", "topk_method": "greedy", "routed_scaling_factor": 1.0,
    "n_shared_experts": 1, "first_k_dense_replace": 0, "hc_mult": 1, "use_qk_norm": True,
    "shared_expert_combination_strategy": "sigmoid_gate", "position_embedding_type": "rope_half",
}
_QWEN3_NEXT_KINDS = {"linear_attention": "gated_deltanet", "full_attention": "gqa_gated"}
# ``mimo_v2``'s: its table follows from ``hybrid_layer_pattern`` (1 a window
# layer with sinks, 0 a full layer), its experts from the list
# ``moe_layer_freq``; its RMS norm's eps is ``layernorm_epsilon``; no shared
# expert and no scaling where the file says null; rotary by halves; one stream
_MIMO_V2_KEYS = {"layernorm_epsilon": "rms_norm_eps", "partial_rotary_factor": "rotary_pct"}
_MIMO_V2_FIXED = {"first_k_dense_replace": 0, "hc_mult": 1, "position_embedding_type": "rope_half"}
_MIMO_V2_NULLS = {"n_shared_experts": 0, "routed_scaling_factor": 1.0}
_MIMO_V2_KINDS = {1: "swa_sink", 0: "gqa_partial"}
# the rotary each ``model_type`` states; granitemoehybrid's attention is unrotated ("nope")
_POSITIONS = {"granitemoehybrid": "nope", "qwen3_next": "rope_half", "mimo_v2": "rope_half"}
_LAYER_TYPES = {
    "sliding_attention": "gqa_window", "full_attention": "gqa_full",
    "mamba": "mamba2", "attention": "gqa_full",  # granitemoehybrid's names; its attention is unrotated ("nope")
}


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN, as DeepSeek-V3's modelling code reads it."""

    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    type: str = "yarn"


@dataclasses.dataclass(frozen=True)
class TrunkConfig:
    """The published keys a trunk is built from (names as in ``config.json``)."""

    name: str = "trunk"
    vocab_size: int = 131072
    hidden_size: int = 3584
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    first_k_dense_replace: int = 2
    moe_layer_freq: int | tuple[int, ...] = 1  # a list: 1 where a layer has experts
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: RopeScaling | None = None
    hidden_act: str = "silu"
    attention_bias: bool = False
    # grouped-query layers: ``layer_types`` names each layer's attention
    model_type: str = ""
    layer_types: tuple[str, ...] | None = None
    num_key_value_heads: int = 0
    head_dim: int = 0
    sliding_window: int = 0
    position_embedding_type: str = "rope_gptj"
    rotary_pct: float = 1.0
    use_qk_norm: bool = False
    use_parallel_block: bool = False
    use_gated_activation: bool = True
    layer_norm_eps: float | None = None  # given: the norms are layer norms
    shared_expert_combination_strategy: str = "sum"
    shared_intermediate_size: int = 0  # the shared experts' width together; 0: n_shared_experts of the routed width
    attention_multiplier: float | None = None  # a grouped-query layer's softmax scale; None: head_dim ** -0.5
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0  # on each branch of the ``add`` residual
    # Mamba-2 layers (``layer_types`` "mamba")
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_n_groups: int = 1
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    normalization_function: str = "rmsnorm"
    # gated delta rule layers (``qwen3_next``'s "linear_attention")
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_chunk_size: int = 0  # 0: the kernel's own (``gated_delta.CHUNK``)
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple[int, ...] = ()
    use_sliding_window: bool = False
    # window layers with sinks and full layers of split widths (``mimo_v2``)
    swa_num_attention_heads: int = 0  # 0: num_attention_heads
    swa_num_key_value_heads: int = 0
    swa_head_dim: int = 0  # 0: head_dim
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 10000.0
    attention_value_scale: float = 1.0
    add_swa_attention_sink_bias: bool = False
    add_full_attention_sink_bias: bool = False
    attention_projection_layout: str = "fused_qkv"
    hybrid_block_size: int | None = None
    # the chip's share of the routed experts: (first, count); None holds all
    experts_held: tuple[int, int] | None = None

    def __post_init__(self):
        unsupported = {
            # softmax over the chosen k is softmax over all, top-k, renormalised
            "scoring_func": self.scoring_func == "sigmoid" or (self.scoring_func == "softmax" and self.norm_topk_prob),
            "topk_method": self.topk_method in ("noaux_tc", "greedy"),
            "n_group": self.n_group == 1 and self.topk_group == 1,
            "hidden_act": self.hidden_act == "silu",
            "attention_bias": not self.attention_bias,
            "position_embedding_type": self.position_embedding_type
            == _POSITIONS.get(self.model_type, "rope_gptj"),
            "mamba_n_groups": self.mamba_n_groups == 1,
            "mamba_proj_bias": not self.mamba_proj_bias,
            "mamba_conv_bias": self.mamba_conv_bias,
            "mamba_expand": self.mamba_n_heads * self.mamba_d_head in (0, self.mamba_expand * self.hidden_size),
            "normalization_function": self.normalization_function == "rmsnorm",
            "rotary_pct": self.rotary_pct == 1 or self.model_type in ("qwen3_next", "mimo_v2"),
            "use_qk_norm": not self.use_qk_norm or self.model_type == "qwen3_next",
            "use_gated_activation": self.use_gated_activation,
            "shared_expert_combination_strategy": self.shared_expert_combination_strategy
            in ("sum", "average", "sigmoid_gate"),
            "decoder_sparse_step": self.decoder_sparse_step == 1,
            "mlp_only_layers": not self.mlp_only_layers,
            "use_sliding_window": not self.use_sliding_window,
            "add_full_attention_sink_bias": not self.add_full_attention_sink_bias,
            "add_swa_attention_sink_bias": self.add_swa_attention_sink_bias or self.model_type != "mimo_v2",
            "hybrid_block_size": self.hybrid_block_size is None,
            "attention_projection_layout": self.attention_projection_layout == "fused_qkv",
            "swa_num_attention_heads": self.swa_num_attention_heads in (0, self.num_attention_heads),
            "swa_head_dim": self.swa_head_dim in (0, self.head_dim),
        }
        for key, ok in unsupported.items():
            if not ok:
                raise ValueError(f"trunk config: no block for this value of {key!r} yet")

    @classmethod
    def from_dict(cls, config: dict, **overrides: Any) -> "TrunkConfig":
        """From a ``config.json``'s keys; keys that say nothing about the
        trunk's shape are passed over. ``model_type`` ``cohere2_moe``,
        ``granitemoehybrid``, ``qwen3_next`` and ``mimo_v2`` have names of
        their own for some fields; ``qwen3_next``'s table is read from
        ``full_attention_interval``, ``mimo_v2``'s from the first
        ``num_hidden_layers`` of ``hybrid_layer_pattern``. A file
        cut to one chip's share (``experts_held``) counts the experts held
        under the published key and states the published count, the router's
        width, under ``published``."""
        renamed = {
            "cohere2_moe": (_COHERE2_MOE_KEYS, _COHERE2_MOE_FIXED, "num_experts"),
            "granitemoehybrid": (_GRANITE_HYBRID_KEYS, _GRANITE_HYBRID_FIXED, "num_local_experts"),
            "qwen3_next": (_QWEN3_NEXT_KEYS, _QWEN3_NEXT_FIXED, "num_experts"),
            "mimo_v2": (_MIMO_V2_KEYS, _MIMO_V2_FIXED, "n_routed_experts"),
        }.get(config.get("model_type"))
        if renamed is not None:
            keys, fixed, experts_key = renamed
            config = {**fixed, **{keys.get(k, k): v for k, v in config.items()}}
            if config.get("experts_held") is not None:
                config["n_routed_experts"] = config.get("published", {}).get(
                    experts_key, config["n_routed_experts"]
                )
            if not config.get("head_dim"):  # granitemoehybrid states none
                config["head_dim"] = config["hidden_size"] // config["num_attention_heads"]
            interval = config.get("full_attention_interval") if config.get("layer_types") is None else None
            if interval:  # qwen3_next: layer i is full where (i + 1) % interval == 0
                config["layer_types"] = [
                    _QWEN3_NEXT_KINDS["linear_attention" if (i + 1) % interval else "full_attention"]
                    for i in range(config["num_hidden_layers"])
                ]
            if config.get("model_type") == "mimo_v2":
                config.update({k: v for k, v in _MIMO_V2_NULLS.items() if config.get(k) is None})
                pattern = config.get("hybrid_layer_pattern") if config.get("layer_types") is None else None
                if pattern is not None:  # a value without a kind keeps a name the registry refuses
                    config["layer_types"] = [
                        _MIMO_V2_KINDS.get(kind, f"hybrid_layer_pattern {kind}")
                        for kind in pattern[: config["num_hidden_layers"]]
                    ]
        names = {f.name for f in dataclasses.fields(cls)}
        picked = {k: v for k, v in config.items() if k in names}
        if picked.get("layer_types") is not None:
            picked["layer_types"] = tuple(picked["layer_types"])
        scaling = picked.get("rope_scaling")
        if isinstance(scaling, dict):
            known = {f.name for f in dataclasses.fields(RopeScaling)}
            picked["rope_scaling"] = RopeScaling(**{k: v for k, v in scaling.items() if k in known})
        for key in ("experts_held", "mlp_only_layers"):
            if picked.get(key) is not None:
                picked[key] = tuple(picked[key])
        if isinstance(picked.get("moe_layer_freq"), list):
            picked["moe_layer_freq"] = tuple(picked["moe_layer_freq"])
        picked.update(overrides)
        return cls(**picked)

    @classmethod
    def from_file(cls, path: str, **overrides: Any) -> "TrunkConfig":
        with open(path, encoding="utf-8") as f:
            return cls.from_dict(json.load(f), **overrides)

    @classmethod
    def coerce(cls, trunk: Any) -> "TrunkConfig":
        """A ``TrunkConfig``, or the path of a ``config.json``."""
        return trunk if isinstance(trunk, cls) else cls.from_file(os.fspath(trunk))

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def norm_kind(self) -> str:
        if self.model_type == "qwen3_next":
            return "rms_offset"
        return "rms" if self.layer_norm_eps is None else "layer"

    @property
    def gain(self) -> str:
        """A norm's gain as a parameter: its kind of initialisation follows the norm's."""
        return "offset_gain" if self.norm_kind == "rms_offset" else "gain"

    @property
    def chunk(self) -> int:
        """The gated delta rule's chunk."""
        return self.linear_chunk_size or gated_delta.CHUNK

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps if self.layer_norm_eps is None else self.layer_norm_eps

    def layer_table(self) -> tuple["LayerKinds", ...]:
        if self.use_parallel_block:
            residual = "parallel"
        else:
            residual = "mhc" if self.hc_mult > 1 else "add"
        if self.layer_types is not None and len(self.layer_types) < self.num_hidden_layers:
            raise ValueError(
                f"trunk config: {self.num_hidden_layers} layers, layer_types names {len(self.layer_types)}"
            )
        freq = self.moe_layer_freq
        if isinstance(freq, tuple) and len(freq) < self.num_hidden_layers:
            raise ValueError(f"trunk config: {self.num_hidden_layers} layers, moe_layer_freq names {len(freq)}")
        table = []
        for i in range(self.num_hidden_layers):
            sparse = (
                self.n_routed_experts > 0
                and i >= self.first_k_dense_replace
                and (freq[i] == 1 if isinstance(freq, tuple) else i % freq == 0)
            )
            # a type this file has no kind for keeps its own name, and the registry refuses it
            attention = (
                "mla" if self.layer_types is None
                else _LAYER_TYPES.get(self.layer_types[i], self.layer_types[i])
            )
            table.append(LayerKinds(attention, "moe" if sparse else "dense", residual))
        return tuple(table)


class LayerKinds(NamedTuple):
    attention: str
    ffn: str
    residual: str


class Block(NamedTuple):
    """A kind of block: the shapes of its parameters, how it is applied, and
    the ``jax.named_scope`` its device ops carry."""

    shapes: Callable  # (config) -> {name: (shape, init kind)}, possibly nested
    apply: Callable
    scope: str


def rms_norm(x, gain, eps: float):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale * gain.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, gain, eps: float):
    """Mean-centred, a gain and no bias, in float32."""
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps)
    return (centred * scale * gain.astype(jnp.float32)).astype(x.dtype)


def rms_offset_norm(x, offset, eps: float):
    """Zero-centred: ``x / rms(x) * (1 + w)``, in float32."""
    return rms_norm(x, 1.0 + offset.astype(jnp.float32), eps)


NORM = {"rms": rms_norm, "layer": layer_norm, "rms_offset": rms_offset_norm}


def norm(x, gain, config: "TrunkConfig"):
    """The config's kind of norm, in front of a block and before pooling."""
    return NORM[config.norm_kind](x, gain, config.norm_eps)


def _dot(x, w):
    """``x [..., k] @ w [k, ...]`` in x's dtype, accumulated in float32."""
    out = jax.lax.dot_general(
        x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return out.astype(x.dtype)


# -- rotary (YaRN) -----------------------------------------------------------


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(config: TrunkConfig, length: int) -> tuple[np.ndarray, np.ndarray]:
    """cos and sin [length, d_r] of positions 0..length-1."""
    dim, base = config.qk_rope_head_dim, float(config.rope_theta)
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    inv_freq = 1.0 / base**exponent
    attn_scale = 1.0
    scaling = config.rope_scaling
    if scaling is not None and scaling.type == "yarn":
        def correction_dim(rotations):
            return dim * math.log(
                scaling.original_max_position_embeddings / (rotations * 2 * math.pi)
            ) / (2 * math.log(base))

        low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
        high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
        keep = 1.0 - ramp  # 1 where the frequency is left as trained
        inv_freq = inv_freq / scaling.factor * (1 - keep) + inv_freq * keep
        attn_scale = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim
        )
    angles = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    angles = np.concatenate([angles, angles], axis=-1)
    return (
        (np.cos(angles) * attn_scale).astype(np.float32),
        (np.sin(angles) * attn_scale).astype(np.float32),
    )


def softmax_scale(config: TrunkConfig) -> float:
    scale = (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5
    scaling = config.rope_scaling
    if scaling is not None and scaling.mscale_all_dim:
        scale *= yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2
    return scale


def apply_rope(x, cos, sin):
    """``x [B, T, ..., d_r]``: pairs de-interleaved, then rotated by halves."""
    x32 = x.astype(jnp.float32)
    x32 = jnp.concatenate([x32[..., 0::2], x32[..., 1::2]], axis=-1)
    half = x32.shape[-1] // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    shape = (1, cos.shape[0]) + (1,) * (x.ndim - 3) + (cos.shape[1],)
    return (x32 * cos.reshape(shape) + rotated * sin.reshape(shape)).astype(x.dtype)


# -- attention kinds -----------------------------------------------------------


def _mla_shapes(c: TrunkConfig) -> dict:
    heads, qk = c.num_attention_heads, c.qk_nope_head_dim + c.qk_rope_head_dim
    return {
        "wq_a": ((c.hidden_size, c.q_lora_rank), "kernel"),
        "q_norm": ((c.q_lora_rank,), "gain"),
        "wq_b": ((c.q_lora_rank, heads, qk), "kernel"),
        "wkv_a": ((c.hidden_size, c.kv_lora_rank + c.qk_rope_head_dim), "kernel"),
        "kv_norm": ((c.kv_lora_rank,), "gain"),
        "wkv_b": ((c.kv_lora_rank, heads, c.qk_nope_head_dim + c.v_head_dim), "kernel"),
        "wo": ((heads, c.v_head_dim, c.hidden_size), "kernel_out"),
    }


def _mla(p, h, c: TrunkConfig, ctx: dict):
    """Expanded form: every head's keys and values are rebuilt from the latent."""
    d_n, eps = c.qk_nope_head_dim, c.rms_norm_eps
    cos, sin = ctx["rope"]
    q = _dot(rms_norm(_dot(h, p["wq_a"]), p["q_norm"], eps), p["wq_b"])  # [B, T, H, d_n + d_r]
    q_n, q_r = q[..., :d_n], apply_rope(q[..., d_n:], cos, sin)
    kv_a = _dot(h, p["wkv_a"])
    c_kv, k_r = kv_a[..., : c.kv_lora_rank], kv_a[..., c.kv_lora_rank :]
    k_r = apply_rope(k_r, cos, sin)  # one rotary key shared by the heads
    kv = _dot(rms_norm(c_kv, p["kv_norm"], eps), p["wkv_b"])  # [B, T, H, d_n + d_v]
    k_n, v = kv[..., :d_n], kv[..., d_n:]
    mixed = causal_attention(q_n, q_r, k_n, k_r, v, softmax_scale(c))
    out = jax.lax.dot_general(
        mixed.astype(h.dtype), p["wo"].astype(h.dtype), (((2, 3), (0, 1)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return out.astype(h.dtype)


def causal_attention(q_n, q_r, k_n, k_r, v, scale):
    """Causal softmax attention, plain XLA: the [B, H, T, T] float32 logits
    are materialised (jax's Pallas splash kernel ran this in 6.4 ms for 9.0 a
    layer alone and moved the whole forward by nothing: PERF.md, PR 28).
    ``q_n``, ``k_n`` [B, T, H, d_n]; ``q_r`` [B, T, H, d_r]; ``k_r``
    [B, T, d_r], shared by the heads; ``v`` [B, T, H, d_v]."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q_n, k_n, preferred_element_type=jnp.float32)
    logits += jnp.einsum("bqhd,bkd->bhqk", q_r, k_r, preferred_element_type=jnp.float32)
    length = v.shape[1]
    causal = jnp.tril(jnp.ones((length, length), bool))
    logits = jnp.where(causal, logits * scale, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32)


def _gqa_shapes(c: TrunkConfig) -> dict:
    heads, kv_heads, width = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    return {
        "wq": ((c.hidden_size, heads, width), "kernel"),
        "wk": ((c.hidden_size, kv_heads, width), "kernel"),
        "wv": ((c.hidden_size, kv_heads, width), "kernel"),
        "wo": ((heads, width, c.hidden_size), "kernel_out"),
    }


def interleaved_rope_tables(
    config: TrunkConfig, length: int, width: int = 0, theta: float | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """cos and sin [length, width / 2] of positions 0..length-1 (``width``
    the rotated dims, all ``head_dim`` of them by default): pair i turns by
    position x theta^(-2i / width), ``theta`` the config's ``rope_theta``
    unless given."""
    width = width or config.head_dim
    theta = config.rope_theta if theta is None else theta
    inv_freq = 1.0 / float(theta) ** (np.arange(0, width, 2, dtype=np.float64) / width)
    angles = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    return np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)


def _evens_first(w):
    """A projection's head dims reordered evens first: its output comes out
    de-interleaved. The same order on queries and keys leaves q . k as it was."""
    return jnp.concatenate([w[..., 0::2], w[..., 1::2]], axis=-1)


def _rotate_pairs(x, cos, sin):
    """``x`` [B, H, T, d] de-interleaved (first halves a, second halves b of
    the pairs): (a cos - b sin, b cos + a sin)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def _heads(h, w):
    """``h [B, T, d] @ w [d, H, e]`` laid out [B, H, T, e]."""
    out = jnp.einsum("btd,dhe->bhte", h, w.astype(h.dtype), preferred_element_type=jnp.float32)
    return out.astype(h.dtype)


def _gqa(p, h, c: TrunkConfig, ctx: dict, *, window: bool):
    """Grouped-query attention: query head j reads key-value head j // G. A
    window layer turns queries and keys by their positions and sees the last
    ``sliding_window`` tokens, itself included; a full layer turns nothing
    and sees the whole row before it."""
    kv_heads, width = c.num_key_value_heads, c.head_dim
    wq, wk = p["wq"], p["wk"]
    if window:
        wq, wk = _evens_first(wq), _evens_first(wk)
    q, k, v = _heads(h, wq), _heads(h, wk), _heads(h, p["wv"])
    if window:
        cos, sin = ctx["rope_pairs"]
        q, k = _rotate_pairs(q, cos, sin), _rotate_pairs(k, cos, sin)
    batch, heads, length, _ = q.shape
    mixed = block_attention.attention(
        q.reshape(batch, kv_heads, heads // kv_heads, length, width), k, v,
        scale=width**-0.5 if c.attention_multiplier is None else c.attention_multiplier,
        window=c.sliding_window if window else None,
        lengths=ctx.get("lengths"),
    ).reshape(q.shape)
    out = jnp.einsum("bhte,hed->btd", mixed, p["wo"].astype(h.dtype), preferred_element_type=jnp.float32)
    return out.astype(h.dtype)


def _mamba2_shapes(c: TrunkConfig) -> dict:
    heads, states = c.mamba_n_heads, c.mamba_d_state
    inner = heads * c.mamba_d_head
    channels = inner + 2 * states  # x, B and C go through the convolution; one group
    return {
        "w_in": ((c.hidden_size, inner + channels + heads), "kernel"),  # [z | x B C | dt]
        "conv": ((c.mamba_d_conv, channels), "conv"),
        "conv_bias": ((channels,), "conv_bias"),
        "dt_bias": ((heads,), "dt_bias"),
        "A_log": ((heads,), "a_log"),
        "D": ((heads,), "ones"),
        "norm": ((inner,), "gain"),
        "w_out": ((inner, c.hidden_size), "kernel"),
    }


def causal_conv(x, taps, bias=None):
    """Depthwise causal convolution along ``x`` [B, T, C]: position ``t`` sees
    the last ``len(taps)`` positions, itself included (``taps[-1]`` is its
    own), summed in float32, plus ``bias`` where one is given. Before the
    row's start there are zeros."""
    width, length = taps.shape[0], x.shape[1]
    taps = taps.astype(jnp.float32)
    out = taps[-1] * x.astype(jnp.float32)
    if bias is not None:
        out = bias.astype(jnp.float32) + out
    for back in range(1, width):  # each shift its own pad of its own slice: nothing float32 of x's size is kept
        shifted = jnp.pad(x[:, : length - back], ((0, 0), (back, 0), (0, 0)))
        out = out + taps[-1 - back] * shifted.astype(jnp.float32)
    return out


def _mamba2(p, h, c: TrunkConfig, ctx: dict):
    """A Mamba-2 mixer over the whole row (prefill form; the state starts at
    zero and is not kept): ``[z | xBC | dt] = h W_in``, ``xBC <-
    silu(conv(xBC))``, the selective scan over ``x`` [T, heads, d_head] with
    ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, then
    ``rms(y * silu(z)) * g`` over all channels and the out-projection."""
    heads, width, states = c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state
    inner = heads * width
    batch, length, _ = h.shape
    with scope("trunk.mamba2.in_proj"):
        projected = _dot(h, p["w_in"])
    z, dt = projected[..., :inner], projected[..., -heads:]

    def convolved(first: int, channels: int):
        """x, B and C each through their own channels of the convolution: three
        arrays, each laid out for its own reader."""
        at = slice(first, first + channels)
        part = projected[..., inner + first : inner + first + channels]
        return jax.nn.silu(causal_conv(part, p["conv"][:, at], p["conv_bias"][at])).astype(h.dtype)

    with scope("trunk.mamba2.conv"):
        x, b, c_ = convolved(0, inner), convolved(inner, states), convolved(inner + states, states)
    with scope("trunk.mamba2.scan"):
        y = ssd_scan.scan(
            x.reshape(batch, length, heads, width),
            jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)),
            -jnp.exp(p["A_log"].astype(jnp.float32)),
            b, c_, p["D"].astype(jnp.float32), chunk=c.mamba_chunk_size,
        )
    with scope("trunk.mamba2.gate_out"):
        gated = y.reshape(batch, length, inner).astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        return _dot(rms_norm(gated, p["norm"], c.rms_norm_eps).astype(h.dtype), p["w_out"])


def _gqa_gated_shapes(c: TrunkConfig) -> dict:
    heads, kv_heads, width = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    return {
        "wq": ((c.hidden_size, heads, 2 * width), "kernel"),  # a head's query, then its gate
        "wk": ((c.hidden_size, kv_heads, width), "kernel"),
        "wv": ((c.hidden_size, kv_heads, width), "kernel"),
        "q_norm": ((width,), c.gain),
        "k_norm": ((width,), c.gain),
        "wo": ((heads, width, c.hidden_size), "kernel_out"),
    }


def _gqa_gated(p, h, c: TrunkConfig, ctx: dict):
    """Grouped-query attention with an output gate (qwen3_next's full
    layer): ``[q | gate] = h W_q`` a head, q and k through the config's norm
    a head, rotary by halves on the first ``head_dim * rotary_pct`` dims
    (dims i and i + half paired), causal over the whole row, then ``o *
    sigmoid(gate)`` and the out-projection."""
    kv_heads, width = c.num_key_value_heads, c.head_dim
    projected = _heads(h, p["wq"])  # [B, H, T, 2 width]
    q, gate = projected[..., :width], projected[..., width:]
    q, k, v = norm(q, p["q_norm"], c), norm(_heads(h, p["wk"]), p["k_norm"], c), _heads(h, p["wv"])
    cos, sin = ctx["rope_half"]
    turned = 2 * cos.shape[-1]
    q, k = (jnp.concatenate([_rotate_pairs(a[..., :turned], cos, sin), a[..., turned:]], axis=-1) for a in (q, k))
    batch, heads, length, _ = q.shape
    mixed = block_attention.attention(
        q.reshape(batch, kv_heads, heads // kv_heads, length, width), k, v,
        scale=width**-0.5, lengths=ctx.get("lengths"),
    ).reshape(q.shape)
    mixed = (mixed.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(h.dtype)
    out = jnp.einsum("bhte,hed->btd", mixed, p["wo"].astype(h.dtype), preferred_element_type=jnp.float32)
    return out.astype(h.dtype)


def _split_shapes(c: TrunkConfig, kv_heads: int, width_v: int) -> dict:
    heads, width = c.num_attention_heads, c.head_dim
    return {
        "wq": ((c.hidden_size, heads, width), "kernel"),
        "wk": ((c.hidden_size, kv_heads, width), "kernel"),
        "wv": ((c.hidden_size, kv_heads, width_v), "kernel"),
        "wo": ((heads, width_v, c.hidden_size), "kernel_out"),
    }


def _swa_sink_shapes(c: TrunkConfig) -> dict:
    shapes = _split_shapes(c, c.swa_num_key_value_heads, c.swa_v_head_dim)
    shapes["sinks"] = ((c.num_attention_heads,), "sink")
    return shapes


def _gqa_partial_shapes(c: TrunkConfig) -> dict:
    return _split_shapes(c, c.num_key_value_heads, c.v_head_dim)


def _split_gqa(p, h, c: TrunkConfig, ctx: dict, *, window: bool):
    """Grouped-query attention with query and key heads of ``head_dim`` and
    value heads of their own width (``mimo_v2``'s layers): rotary by halves
    on the first ``head_dim * rotary_pct`` dims of q and k (dims i and i +
    half paired), query head j reading key-value head j // G, softmax at
    ``head_dim^-1/2``, the output times ``attention_value_scale``. A window
    layer (``swa_num_key_value_heads``, ``swa_rope_theta``) sees the last
    ``sliding_window`` tokens, itself included, beside its query head's sink;
    a full layer (``num_key_value_heads``, ``rope_theta``) the whole row
    before it and no sink."""
    width = c.head_dim
    with scope("trunk.attn.qkv"):
        q, k, v = _heads(h, p["wq"]), _heads(h, p["wk"]), _heads(h, p["wv"])
        cos, sin = ctx["rope_swa" if window else "rope_full"]
        turned = 2 * cos.shape[-1]
        q, k = (jnp.concatenate([_rotate_pairs(a[..., :turned], cos, sin), a[..., turned:]], axis=-1) for a in (q, k))
    batch, heads, length, _ = q.shape
    kv_heads = k.shape[1]
    with scope("trunk.attn.kernel"):
        mixed = block_attention.attention(
            q.reshape(batch, kv_heads, heads // kv_heads, length, width), k, v,
            scale=width**-0.5, window=c.sliding_window if window else None, lengths=ctx.get("lengths"),
            sinks=p["sinks"].reshape(kv_heads, heads // kv_heads) if window else None,
        ).reshape(batch, heads, length, v.shape[-1])
    with scope("trunk.attn.out"):  # the value scale is linear in v: it is taken on the out-projection's sum
        out = jnp.einsum("bhte,hed->btd", mixed, p["wo"].astype(h.dtype), preferred_element_type=jnp.float32)
        if c.attention_value_scale != 1:
            out = out * c.attention_value_scale
        return out.astype(h.dtype)


def _gdn_shapes(c: TrunkConfig) -> dict:
    heads = c.linear_num_value_heads
    key, value = c.linear_num_key_heads * c.linear_key_head_dim, heads * c.linear_value_head_dim
    return {
        "w_qkvz": ((c.hidden_size, 2 * key + 2 * value), "kernel"),  # [q | k | v | z]
        "w_ba": ((c.hidden_size, 2 * heads), "kernel"),  # [b | a]
        "conv": ((c.linear_conv_kernel_dim, 2 * key + value), "conv"),  # q, k and v; no bias
        "A_log": ((heads,), "a_log"),
        "dt_bias": ((heads,), "ones"),
        "norm": ((c.linear_value_head_dim,), "gain"),  # a plain gain, one a head's channel
        "w_out": ((value, c.hidden_size), "kernel"),
    }


def _l2_normed(x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + eps)


def _gdn(p, h, c: TrunkConfig, ctx: dict):
    """A Gated DeltaNet mixer over the whole row (prefill form; the state
    starts at zero and is not kept): ``[q | k | v | z] = h W_qkvz``, ``[b |
    a] = h W_ba``, ``q k v <- silu(conv(q k v))`` (no bias), q and k
    L2-normalised a head and q scaled by ``d_k^-1/2``, ``beta =
    sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``, the gated delta
    rule of ``ops/gated_delta.py``, then ``rms(o) * w * silu(z)`` a head and
    the out-projection."""
    key_heads, heads = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
    key, value = key_heads * dk, heads * dv
    batch, length, _ = h.shape
    with scope("trunk.gdn.in_proj"):
        projected = _dot(h, p["w_qkvz"])
        b, a = jnp.split(_dot(h, p["w_ba"]).astype(jnp.float32), 2, axis=-1)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(a + p["dt_bias"].astype(jnp.float32))

    def convolved(first: int, channels: int, width: int):
        """q, k and v each through their own channels of the convolution, [B, T, heads, width] float32."""
        at = slice(first, first + channels)
        return jax.nn.silu(causal_conv(projected[..., at], p["conv"][:, at])).reshape(batch, length, -1, width)

    with scope("trunk.gdn.conv"):
        q = (_l2_normed(convolved(0, key, dk)) * dk**-0.5).astype(h.dtype)
        k = _l2_normed(convolved(key, key, dk)).astype(h.dtype)
        v = convolved(2 * key, value, dv).astype(h.dtype)
    with scope("trunk.gdn.scan"):
        o = gated_delta.scan(q, k, v, g, beta, chunk=c.chunk)
    with scope("trunk.gdn.gate_out"):
        z = projected[..., 2 * key + value :].reshape(o.shape).astype(jnp.float32)
        gated = rms_norm(o.astype(jnp.float32), p["norm"], c.rms_norm_eps) * jax.nn.silu(z)
        return _dot(gated.astype(h.dtype).reshape(batch, length, value), p["w_out"])


ATTENTION = {
    "mla": Block(_mla_shapes, _mla, "trunk.mla"),
    "gqa_window": Block(_gqa_shapes, functools.partial(_gqa, window=True), "trunk.gqa_window"),
    "gqa_full": Block(_gqa_shapes, functools.partial(_gqa, window=False), "trunk.gqa_full"),
    "mamba2": Block(_mamba2_shapes, _mamba2, "trunk.mamba2"),
    "gqa_gated": Block(_gqa_gated_shapes, _gqa_gated, "trunk.gqa_gated"),
    "gated_deltanet": Block(_gdn_shapes, _gdn, "trunk.gdn"),
    "swa_sink": Block(_swa_sink_shapes, functools.partial(_split_gqa, window=True), "trunk.swa_sink"),
    "gqa_partial": Block(_gqa_partial_shapes, functools.partial(_split_gqa, window=False), "trunk.gqa_partial"),
}
# the kinds that run ``ops/block_attention.py``, and whether each is a window's
_BLOCKED = {"gqa_window": True, "swa_sink": True, "gqa_full": False, "gqa_gated": False, "gqa_partial": False}

# -- feed-forward kinds --------------------------------------------------------


def _gated_shapes(width_in: int, width: int) -> dict:
    return {
        "w_gate": ((width_in, width), "kernel"),
        "w_up": ((width_in, width), "kernel"),
        "w_down": ((width, width_in), "kernel"),
    }


def _gated_ffn(p, h):
    gate = _dot(h, p["w_gate"]).astype(jnp.float32)
    up = _dot(h, p["w_up"]).astype(jnp.float32)
    return _dot((jax.nn.silu(gate) * up).astype(h.dtype), p["w_down"])


def _dense_shapes(c: TrunkConfig) -> dict:
    return _gated_shapes(c.hidden_size, c.intermediate_size)


def _dense(p, h, c: TrunkConfig, ctx: dict):
    return _gated_ffn(p, h)


def _moe_shapes(c: TrunkConfig) -> dict:
    d, f, held = c.hidden_size, c.moe_intermediate_size, c.held[1]
    shapes = {
        "router": ((d, c.n_routed_experts), "kernel32"),
        "bias": ((c.n_routed_experts,), "router_bias"),
        "w_gate": ((held, d, f), "expert_kernel"),
        "w_up": ((held, d, f), "expert_kernel"),
        "w_down": ((held, f, d), "expert_kernel"),
    }
    if c.n_shared_experts:  # the shared experts side by side: one gated FFN whose output is their sum
        shapes["shared"] = _gated_shapes(d, c.shared_intermediate_size or f * c.n_shared_experts)
    if c.topk_method != "noaux_tc":  # a plain top-k has no correction bias
        del shapes["bias"]
    if c.shared_expert_combination_strategy == "sigmoid_gate":
        shapes["shared_gate"] = ((d, 1), "kernel")
    return shapes


def _moe(p, h, c: TrunkConfig, ctx: dict):
    flat = h.reshape(-1, h.shape[-1])
    routed, counts, choice = moe.expert_layer(
        flat, ctx["valid"], p["router"], p.get("bias"), p["w_gate"], p["w_up"], p["w_down"],
        top_k=c.num_experts_per_tok, scale=c.routed_scaling_factor,
        normalise=c.norm_topk_prob, experts_held=c.experts_held, scoring=c.scoring_func,
    )
    ctx["expert_counts"].append(counts)
    ctx["expert_choice"].append(choice.reshape(h.shape[:-1] + choice.shape[-1:]))
    if not c.n_shared_experts:
        return routed.astype(h.dtype).reshape(h.shape)
    with scope("trunk.moe.shared"):
        shared = _gated_ffn(p["shared"], flat).astype(jnp.float32)
        if c.shared_expert_combination_strategy == "sigmoid_gate":
            shared = shared * jax.nn.sigmoid(_dot(flat, p["shared_gate"]).astype(jnp.float32))
    if c.shared_expert_combination_strategy == "average":
        shared = shared / c.n_shared_experts
    return (routed + shared).astype(h.dtype).reshape(h.shape)


FFN = {
    "dense": Block(_dense_shapes, _dense, "trunk.ffn"),
    "moe": Block(_moe_shapes, _moe, "trunk.moe"),
}

# -- residual kinds ------------------------------------------------------------


def _mhc_shapes(c: TrunkConfig) -> dict:
    n = c.hc_mult
    return {
        "norm": ((n, c.hidden_size), "gain"),
        "proj": ((n, c.hidden_size, n + n + n * n), "mhc_proj"),
        "alpha": ((3,), "mhc_alpha"),  # a_pre, a_post, a_res
        "bias": ((n + n + n * n,), "mhc_bias"),
    }


def mhc_coefficients(p, streams, c: TrunkConfig):
    """The first pass over ``streams`` [n, B, T, d] (``ops/residual_mix.py``
    ``mix_in``): the sub-layer's input ``u = H_pre X`` [B, T, d] and the
    packed float32 coefficients H_pre, H_post, H_res, which ``mix_out``
    reads and ``residual_mix.coefficients`` unpacks."""
    # x' P = rms_scale * (x (gain * P)): the gain goes into the small matrix
    proj = p["norm"].astype(jnp.float32)[:, :, None] * p["proj"].astype(jnp.float32)
    return residual_mix.mix_in(
        streams, proj.astype(streams.dtype), p["alpha"], p["bias"],
        iters=c.hc_sinkhorn_iters, eps=c.hc_eps, rms_eps=c.rms_norm_eps,
        clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max),
    )


def _mhc(p, streams, sublayer, c: TrunkConfig):
    """``X <- H_res X + H_post^T F(u)`` with ``u = H_pre X``; ``sublayer`` is
    F with its own norm in front. Two passes over the streams, a kernel each:
    one going in, one coming out."""
    with scope("trunk.mhc"):
        mixed_in, coef = mhc_coefficients(p, streams, c)
    out = sublayer(mixed_in)
    with scope("trunk.mhc"):
        return residual_mix.mix_out(streams, out, coef)


def _mhc_shapes_of_a_layer(c: TrunkConfig, attention: dict, ffn: dict) -> dict:
    gain = ((c.hidden_size,), c.gain)
    return {
        "attn_res": _mhc_shapes(c), "attn_norm": gain, "attn": attention,
        "ffn_res": _mhc_shapes(c), "ffn_norm": gain, "ffn": ffn,
    }


def _mhc_layer(p, streams, attend, feed, c: TrunkConfig):
    streams = _mhc(p["attn_res"], streams, lambda u: attend(p["attn"], norm(u, p["attn_norm"], c)), c)
    return _mhc(p["ffn_res"], streams, lambda u: feed(p["ffn"], norm(u, p["ffn_norm"], c)), c)


def _mhc_enter(x, c: TrunkConfig):
    return jnp.broadcast_to(x[None], (c.hc_mult,) + x.shape)


def _mhc_exit(streams, last):
    """The summed streams at each row's position ``last``: [B, d] float32.
    One masked sum over the positions, reading the streams where the last
    kernel left them: a gather would first lay all of them out anew."""
    here = jnp.arange(streams.shape[2])[None, :] == last[:, None]  # [B, T]
    return jnp.where(here[None, :, :, None], streams, 0).astype(jnp.float32).sum(axis=(0, 2))


def _add_shapes_of_a_layer(c: TrunkConfig, attention: dict, ffn: dict) -> dict:
    gain = ((c.hidden_size,), c.gain)
    return {"attn_norm": gain, "attn": attention, "ffn_norm": gain, "ffn": ffn}


def _add_branch(x, branch, c: TrunkConfig):
    if c.residual_multiplier == 1:
        return x + branch
    return (x.astype(jnp.float32) + c.residual_multiplier * branch.astype(jnp.float32)).astype(x.dtype)


def _add_layer(p, x, attend, feed, c: TrunkConfig):
    x = _add_branch(x, attend(p["attn"], norm(x, p["attn_norm"], c)), c)
    return _add_branch(x, feed(p["ffn"], norm(x, p["ffn_norm"], c)), c)


def _parallel_shapes_of_a_layer(c: TrunkConfig, attention: dict, ffn: dict) -> dict:
    return {"norm": ((c.hidden_size,), c.gain), "attn": attention, "ffn": ffn}


def _parallel_layer(p, x, attend, feed, c: TrunkConfig):
    """Attention and feed-forward read the same normed input."""
    h = norm(x, p["norm"], c)
    return x + attend(p["attn"], h) + feed(p["ffn"], h)


def _one_stream_exit(x, last):
    return jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0].astype(jnp.float32)


class Residual(NamedTuple):
    """A kind of residual path: it lays out a layer's parameters around the
    attention's and the feed-forward's, and runs the layer: ``attend`` and
    ``feed`` take (their parameters, their normed input)."""

    shapes: Callable  # (config, attention shapes, feed-forward shapes) -> one layer's tree
    layer: Callable  # (parameters, state, attend, feed, config) -> state
    enter: Callable  # (embedded tokens [B, T, d], config) -> state
    exit: Callable  # (state, last [B]) -> [B, d] float32
    path: str | None = None  # what a forward's span says of the path that ran, where there is something to tell apart


RESIDUAL = {
    "mhc": Residual(_mhc_shapes_of_a_layer, _mhc_layer, _mhc_enter, _mhc_exit, path="mhc_fused"),
    "add": Residual(_add_shapes_of_a_layer, _add_layer, lambda x, c: x, _one_stream_exit),
    "parallel": Residual(_parallel_shapes_of_a_layer, _parallel_layer, lambda x, c: x, _one_stream_exit),
}


def _block(registry: dict, kind: str, what: str):
    try:
        return registry[kind]
    except KeyError:
        raise NotImplementedError(
            f"the layer table names the {what} kind {kind!r}; _trunk.py has {sorted(registry)}"
        ) from None


# -- parameters ---------------------------------------------------------------


def param_shapes(config: TrunkConfig) -> dict:
    """The parameter tree as {name: (shape, init kind)}."""
    d = config.hidden_size
    layers = []
    for kinds in config.layer_table():
        layers.append(
            _block(RESIDUAL, kinds.residual, "residual").shapes(
                config,
                _block(ATTENTION, kinds.attention, "attention").shapes(config),
                _block(FFN, kinds.ffn, "feed-forward").shapes(config),
            )
        )
    return {
        "embed": ((config.vocab_size, d), "embedding"),
        "layers": layers,
        "final_norm": ((d,), config.gain),
    }


def _is_leaf(node) -> bool:
    return isinstance(node, tuple) and len(node) == 2 and isinstance(node[1], str)


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype", "streams"))
def _init_leaf(key, shape, kind, dtype, streams):
    """One parameter, made where it will live. Small ones stay float32."""
    normal = functools.partial(jax.random.normal, key, shape, jnp.float32)
    if kind == "gain":
        return 1.0 + 0.1 * normal()
    if kind == "offset_gain":  # a zero-centred norm's w in (1 + w)
        return 0.1 * normal()
    if kind == "embedding":
        return normal().astype(dtype)
    if kind == "kernel":
        return (normal() / math.sqrt(shape[0])).astype(dtype)
    if kind == "kernel32":
        return normal() / math.sqrt(shape[0])
    if kind == "kernel_out":  # [heads, d_v, out]
        return (normal() / math.sqrt(shape[0] * shape[1])).astype(dtype)
    if kind == "expert_kernel":  # [experts, in, out]
        return (normal() / math.sqrt(shape[1])).astype(dtype)
    if kind == "router_bias":
        return 0.01 * normal()
    # Mamba-2's published initialisation (arXiv:2405.21060, its reference code)
    if kind == "conv":  # [taps, channels]
        return (normal() / math.sqrt(shape[0])).astype(dtype)
    if kind == "conv_bias":
        return jnp.zeros(shape, jnp.float32)
    if kind == "a_log":  # A = -exp(A_log) uniform in -16..-1
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":  # softplus(dt_bias) log-uniform in 1e-3..1e-1
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "sink":  # a sink at 0: exp(0) = 1 beside a row's keys
        return jnp.zeros(shape, jnp.float32)
    if kind == "mhc_proj":  # [n, d, n + n + n * n]: fan-in is all the streams
        return (normal() / math.sqrt(shape[0] * shape[1])).astype(dtype)
    if kind == "mhc_alpha":
        return jnp.full(shape, 0.01, jnp.float32)
    if kind == "mhc_bias":
        # H_pre starts near 1/n a stream, H_post near 1, H_res near the identity
        # (diagonal about 0.7: from further out, 20 Sinkhorn rounds do not
        # bring the sums within 1e-4 of 1)
        n = streams
        centre = jnp.concatenate(
            [
                jnp.full((n,), -math.log(n - 1.0) if n > 1 else 0.0),
                jnp.zeros((n,)),
                2.0 * jnp.eye(n).reshape(-1),
            ]
        )
        spread = jnp.concatenate([jnp.full((2 * n,), 0.1), jnp.full((n * n,), 0.3)])
        return centre + spread * normal()
    raise ValueError(f"unknown parameter kind {kind!r}")


def init_params(config: TrunkConfig, key, dtype=jnp.bfloat16):
    """Seeded gaussian parameters, leaf by leaf on the default device, so that
    the whole tree never stands anywhere in float32. ``key`` is a PRNG key or
    a whole number."""
    if isinstance(key, int):
        key = jax.random.PRNGKey(key)
    counter = iter(range(1 << 30))

    def build(node):
        if _is_leaf(node):
            shape, kind = node
            return _init_leaf(
                jax.random.fold_in(key, next(counter)), shape, kind, jnp.dtype(dtype), config.hc_mult
            )
        if isinstance(node, dict):
            return {name: build(child) for name, child in node.items()}
        return [build(child) for child in node]

    return build(param_shapes(config))


# -- the forward ----------------------------------------------------------------


def forward(params, ids, mask, *, config: TrunkConfig):
    """``ids``, ``mask`` [B, T], right-padded. Returns unit vectors [B, d]
    float32, the tokens per expert [expert layers, E] int32 (real tokens, as
    the router sent them) and the router's choice [expert layers, B, T, k]
    int32 (-1 at a padding position)."""
    table = config.layer_table()
    lengths = mask.sum(axis=1).astype(jnp.int32)
    ctx = {"valid": mask.reshape(-1) > 0, "lengths": lengths, "expert_counts": [], "expert_choice": []}
    kinds_of_attention = {kinds.attention for kinds in table}
    if "mla" in kinds_of_attention:
        ctx["rope"] = rope_tables(config, ids.shape[1])
    if "gqa_window" in kinds_of_attention:
        ctx["rope_pairs"] = interleaved_rope_tables(config, ids.shape[1])
    if "gqa_gated" in kinds_of_attention:
        ctx["rope_half"] = interleaved_rope_tables(config, ids.shape[1], int(config.head_dim * config.rotary_pct))
    turned = int(config.head_dim * config.rotary_pct)
    if "swa_sink" in kinds_of_attention:
        ctx["rope_swa"] = interleaved_rope_tables(config, ids.shape[1], turned, config.swa_rope_theta)
    if "gqa_partial" in kinds_of_attention:
        ctx["rope_full"] = interleaved_rope_tables(config, ids.shape[1], turned)
    with scope("trunk.embed"):
        x = params["embed"][ids]
        if config.embedding_multiplier != 1:
            x = (x.astype(jnp.float32) * config.embedding_multiplier).astype(x.dtype)
    state = _block(RESIDUAL, table[0].residual, "residual").enter(x, config)
    for kinds, p in zip(table, params["layers"]):
        attention = _block(ATTENTION, kinds.attention, "attention")
        ffn = _block(FFN, kinds.ffn, "feed-forward")

        def attend(p, h, attention=attention):
            with scope(attention.scope):
                return attention.apply(p, h, config, ctx)

        def feed(p, h, ffn=ffn):
            with scope(ffn.scope):
                return ffn.apply(p, h, config, ctx)

        state = _block(RESIDUAL, kinds.residual, "residual").layer(p, state, attend, feed, config)
    # pool before the last norm: only the last real position of each row is kept
    with scope("trunk.pool"):
        last = jnp.maximum(lengths - 1, 0)
        pooled = norm(_block(RESIDUAL, table[-1].residual, "residual").exit(state, last), params["final_norm"], config)
        vectors = pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-12)
    if not ctx["expert_counts"]:  # no expert layer in the table
        top_k, experts = max(config.num_experts_per_tok, 1), max(config.n_routed_experts, 1)
        return vectors, jnp.zeros((0, experts), jnp.int32), jnp.zeros((0,) + ids.shape + (top_k,), jnp.int32)
    return vectors, jnp.stack(ctx["expert_counts"]), jnp.stack(ctx["expert_choice"])


class TrunkRuntime:
    """``EncoderRuntime``'s surface over a ``TrunkConfig``: owns the
    parameters and one jitted forward per (batch bucket, length bucket).
    ``params`` is made from ``seed`` when first read, unless it was set."""

    def __init__(
        self,
        config: TrunkConfig,
        max_len: int = 512,
        seed: int = 0,
        mesh: Any = None,
        dtype: Any = jnp.bfloat16,
    ):
        if mesh is not None:
            raise NotImplementedError(
                "a trunk runs on one chip: its experts have no exchange across a mesh yet"
            )
        self.config = config
        self.dim = config.hidden_size
        self.max_len = max_len
        self.pretrained = False
        self.dtype = dtype
        self._seed = seed
        self._params = None
        self._fwd = device_scopes.jit(functools.partial(forward, config=config))
        self._ran: set[tuple] = set()  # (shape, ids dtype, mask dtype) of every forward made
        device_scopes.register(self)
        table = config.layer_table()
        # the window (None: the whole row) of each blocked attention layer
        self._windows = [
            config.sliding_window if _BLOCKED[kinds.attention] else None
            for kinds in table
            if kinds.attention in _BLOCKED
        ]
        self._scans = sum(kinds.attention == "mamba2" for kinds in table)
        self._deltas = sum(kinds.attention == "gated_deltanet" for kinds in table)
        path = _block(RESIDUAL, table[0].residual, "residual").path
        self._residual = {} if path is None else {"residual": path}

    @property
    def params(self):
        if self._params is None:
            self._params = init_params(self.config, self._seed, self.dtype)
        return self._params

    @params.setter
    def params(self, tree) -> None:
        self._params = tree

    def batch_bucket(self, n: int, width: int = 0) -> int:
        return _bucket_batch(n, width)

    def device_programs(self):
        """What ``device_scopes.tables()`` lowers again: every forward made."""
        return device_scopes.forwards(self._fwd, self.params, self._ran)

    def _attention_pairs(self, lengths: np.ndarray, width: int) -> dict:
        """What the blocked attention layers of one forward are asked for and
        what their kernel visits, in query-key pairs a head: the pairs inside
        the masks over each row's real tokens, and the pairs of the blocks
        the kernel visits at each layer's window and the forwarded ``width``
        up to each row's last real token (a bucket's padding rows visit
        nothing); ``attn_window_pairs_*`` the same of the window layers
        alone, where the table has one. Nothing for a table without such a
        layer."""
        counts = {}
        for prefix, windows in (
            ("attn_pairs", self._windows),
            ("attn_window_pairs", [w for w in self._windows if w is not None]),
        ):
            if windows:
                counts[prefix + "_allowed"] = sum(
                    block_attention.pairs_allowed(int(t), w) for w in windows for t in lengths
                )
                counts[prefix + "_visited"] = sum(
                    block_attention.pairs_visited(width, w, tokens=int(t)) for w in windows for t in lengths
                )
        return counts

    def _scan_chunks(self, lengths: np.ndarray, rows: int, width: int) -> dict:
        """The chunks of the recurrent scans of one forward, all ``mamba2``
        layers and all ``gated_deltanet`` layers: those that hold a real
        token, and those walked at the forwarded shape. Nothing for a table
        without such a layer."""
        counts = {}
        if self._scans:
            chunk = self.config.mamba_chunk_size
            counts["ssm_chunks_useful"] = self._scans * ssd_scan.chunks_useful(lengths, chunk)
            counts["ssm_chunks_visited"] = self._scans * ssd_scan.chunks_visited(rows, width, chunk)
        if self._deltas:
            chunk = self.config.chunk
            counts["gdn_chunks_useful"] = self._deltas * gated_delta.chunks_useful(lengths, chunk)
            counts["gdn_chunks_visited"] = self._deltas * gated_delta.chunks_visited(rows, width, chunk)
        return counts

    def dispatch(
        self, ids: np.ndarray, mask: np.ndarray, routing: bool = False
    ) -> Callable[[], tuple[np.ndarray, dict]]:
        """Starts the forward of one padded batch and returns the call that
        waits for it (``EncoderRuntime.dispatch``): vectors [n, dim] and what
        was really forwarded, the padded shape and the expert layers' row
        counts, ``residual="mhc_fused"`` where the streams are mixed by the two
        kernels of ``ops/residual_mix.py``, where the table has blocked
        attention layers their
        ``attn_pairs_allowed`` and ``attn_pairs_visited`` (and of its window
        layers alone ``attn_window_pairs_allowed`` and
        ``attn_window_pairs_visited``), where it has
        ``mamba2`` layers their ``ssm_chunks_useful`` and
        ``ssm_chunks_visited``, and where it has ``gated_deltanet`` layers
        their ``gdn_chunks_useful`` and ``gdn_chunks_visited``. ``routing=True``
        adds ``expert_choice`` [expert layers, n, T, k], the experts each
        token went to (-1: nowhere); it stays on the device unless asked for."""
        n = ids.shape[0]
        bucket = self.batch_bucket(n, ids.shape[1])
        lengths = np.asarray(mask).sum(axis=1)
        if bucket != n:
            ids = np.pad(ids, ((0, bucket - n), (0, 0)))
            mask = np.pad(mask, ((0, bucket - n), (0, 0)))
        ids_j, mask_j = jnp.asarray(ids), jnp.asarray(mask)
        self._ran.add((ids_j.shape, ids_j.dtype, mask_j.dtype))
        out, counts, choice = self._fwd(self.params, ids_j, mask_j)
        out.copy_to_host_async()
        info = {
            "batch_bucket": bucket,
            "len_bucket": int(ids.shape[1]),
            "tokens_padded": int(ids.size),
            "trunk": self.config.name,
            **self._residual,
            **self._attention_pairs(lengths, int(ids.shape[1])),
            **self._scan_chunks(lengths, bucket, int(ids.shape[1])),
        }

        def fetch() -> tuple[np.ndarray, dict]:
            rows = np.asarray(counts)
            if rows.size:  # the trunk has expert layers
                first, held = self.config.held
                info.update(
                    expert_rows_useful=int(rows[:, first : first + held].sum()),
                    expert_rows_computed=sum(
                        moe.rows_computed(layer[first : first + held]) for layer in rows
                    ),
                    expert_tokens_max=int(rows.max()),
                    expert_tokens_mean=float(rows.mean()),
                )
            if routing:
                info["expert_choice"] = np.asarray(choice)[:, :n]
            return np.asarray(out)[:n], info

        return fetch

    def forward(
        self, ids: np.ndarray, mask: np.ndarray, routing: bool = False
    ) -> tuple[np.ndarray, dict]:
        """``dispatch``, waited for."""
        return self.dispatch(ids, mask, routing)()

    def forward_ids(self, ids: np.ndarray, mask: np.ndarray) -> np.ndarray:
        return self.forward(ids, mask)[0]
