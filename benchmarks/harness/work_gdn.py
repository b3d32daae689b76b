"""Operations and bytes of a gated-delta-rule trunk configuration's forward,
from the configuration's published keys and a tick's real token counts
alone.

As ``work.py``, ``work_trunk.py``, ``work_gqa.py`` and ``work_ssm.py``: what
the algorithm needs at the stated precision (bfloat16 weights and rows),
whatever implements it. Padding positions, pad rungs, tile padding and masked
corners are not work. The scan is counted at its **recurrent minimum**: a
real token and a value head cost ``6 d_k d_v`` (the read-back ``S^T k``, the
rank-one update, ``S^T q``), and move q and k (bfloat16, a key head each), v
(bfloat16), ``g`` and ``beta`` (float32) in and o (bfloat16) out. A chunked
kernel does more work than this (the triangular inverse, the masked products
inside a chunk), so it reads below 100%, and a better algorithm shows as a
higher share. The routed experts are counted at this chip's share: a token
goes to ``num_experts_per_tok`` of the published experts, of which this chip
holds ``experts_held``.
"""

from __future__ import annotations

from benchmarks.harness.reference_gdn import layer_kinds


def _sizes(config: dict) -> dict:
    keys = (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "moe_intermediate_size",
        "shared_expert_intermediate_size", "num_experts_per_tok", "num_hidden_layers", "linear_num_key_heads",
        "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
    )
    s = {k: int(config[k]) for k in keys}
    s["published_experts"] = int(config["published"]["num_experts"])
    s["held"] = int(config["experts_held"][1])
    kinds = layer_kinds(config)
    s["linear_layers"], s["full_layers"] = kinds.count("linear"), kinds.count("full")
    return s


def experts_a_token_here(config: dict) -> float:
    s = _sizes(config)
    return s["num_experts_per_tok"] * s["held"] / s["published_experts"]


def scan_flops(config: dict, tokens: int) -> float:
    """The delta-rule scans of one sequence, all linear layers, at the recurrent minimum."""
    s = _sizes(config)
    a_token = 6.0 * s["linear_num_value_heads"] * s["linear_key_head_dim"] * s["linear_value_head_dim"]
    return s["linear_layers"] * a_token * tokens


def scan_bytes(config: dict, tokens: int) -> float:
    """q and k in, v in and o out at bfloat16, g and beta (float32) in, a layer."""
    s = _sizes(config)
    keys = s["linear_num_key_heads"] * s["linear_key_head_dim"]
    values = s["linear_num_value_heads"] * s["linear_value_head_dim"]
    a_token = 2.0 * (2 * keys + 2 * values) + 4.0 * 2 * s["linear_num_value_heads"]
    return s["linear_layers"] * a_token * tokens


def attention_pairs(tokens: int) -> int:
    return tokens * (tokens + 1) // 2


def attention_flops(config: dict, tokens: int) -> float:
    """Scores and mixing of one sequence in the full layers: 4 x heads x head_dim an allowed pair."""
    s = _sizes(config)
    return 4.0 * s["num_attention_heads"] * s["head_dim"] * attention_pairs(tokens) * s["full_layers"]


def expert_matmul_flops(config: dict, tokens: int) -> float:
    """The held routed experts' three matmuls for ``tokens`` real tokens, all layers."""
    s = _sizes(config)
    return (
        6.0 * s["hidden_size"] * s["moe_intermediate_size"] * experts_a_token_here(config)
        * tokens * s["num_hidden_layers"]
    )


def expert_matmul_bytes(config: dict, tokens: int) -> float:
    """One batch: the held experts' weights once a layer, and each routed row
    in and out, at bfloat16."""
    s = _sizes(config)
    weights = 3.0 * s["held"] * s["hidden_size"] * s["moe_intermediate_size"] * 2.0
    rows = 2.0 * tokens * experts_a_token_here(config) * s["hidden_size"] * 2.0
    return s["num_hidden_layers"] * (weights + rows)


def linear_layer_flops(config: dict) -> float:
    """A real token through one Gated DeltaNet mixer without its scan: the
    two in-projections, the convolution and the out-projection."""
    s = _sizes(config)
    d = s["hidden_size"]
    keys = s["linear_num_key_heads"] * s["linear_key_head_dim"]
    values = s["linear_num_value_heads"] * s["linear_value_head_dim"]
    return (
        2.0 * d * (2 * keys + 2 * values) + 2.0 * d * 2 * s["linear_num_value_heads"]
        + 2.0 * s["linear_conv_kernel_dim"] * (2 * keys + values) + 2.0 * values * d
    )


def full_layer_flops(config: dict) -> float:
    """A real token through the gated attention's projections (q with its gate, k, v, o), without its pairs."""
    s = _sizes(config)
    d, width = s["hidden_size"], s["head_dim"]
    return 2.0 * d * width * (2 * s["num_attention_heads"] + 2 * s["num_key_value_heads"] + s["num_attention_heads"])


def ffn_flops(config: dict) -> float:
    """A real token through one layer's router, shared expert and its gate, and its held routed experts."""
    s = _sizes(config)
    d = s["hidden_size"]
    return (
        2.0 * d * s["published_experts"] + 6.0 * d * s["shared_expert_intermediate_size"] + 2.0 * d
        + 6.0 * d * s["moe_intermediate_size"] * experts_a_token_here(config)
    )


def forward_flops(config: dict, tokens: int) -> float:
    """The whole forward of one sequence of ``tokens`` real tokens, at this chip's share."""
    s = _sizes(config)
    shared = ffn_flops(config) - 6.0 * s["hidden_size"] * s["moe_intermediate_size"] * experts_a_token_here(config)
    per_token = (
        s["linear_layers"] * linear_layer_flops(config)
        + s["full_layers"] * full_layer_flops(config)
        + s["num_hidden_layers"] * shared
    )
    return (
        tokens * per_token + expert_matmul_flops(config, tokens)
        + scan_flops(config, tokens) + attention_flops(config, tokens)
    )


WORK = {
    "gdn_scan": (scan_flops, scan_bytes, "sequence"),
    "moe_experts": (expert_matmul_flops, expert_matmul_bytes, "batch"),
}
