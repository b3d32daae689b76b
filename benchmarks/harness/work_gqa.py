"""Operations and bytes of a grouped-query trunk configuration's forward, from
the configuration's published keys and a tick's real token counts alone.

As ``work.py`` and ``work_trunk.py``: what the algorithm needs at the stated
precision (bfloat16 weights and rows), whatever implements it. Padding
positions, pad rungs, tile padding and the masked corners of a visited block
are not work. The routed experts are counted at this chip's share: a token
goes to ``num_experts_per_tok`` of the published experts, of which this chip
holds ``experts_held``, so ``k x held / published`` of them on average.
"""

from __future__ import annotations


def _sizes(config: dict) -> dict:
    keys = (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size",
        "num_shared_experts", "num_experts_per_tok", "num_hidden_layers", "sliding_window",
    )
    s = {k: int(config[k]) for k in keys}
    s["published_experts"] = int(config["published"]["num_experts"])
    s["held"] = int(config["experts_held"][1])
    return s


def layer_windows(config: dict) -> list:
    """Each layer's window: ``sliding_window`` or None (the whole row before it)."""
    kinds = list(config["layer_types"])[: int(config["num_hidden_layers"])]
    return [int(config["sliding_window"]) if kind == "sliding_attention" else None for kind in kinds]


def pairs_allowed(tokens: int, window) -> int:
    """Query-key pairs inside the mask for one sequence of ``tokens`` tokens."""
    if window is None or tokens <= window:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def experts_a_token_here(config: dict) -> float:
    s = _sizes(config)
    return s["num_experts_per_tok"] * s["held"] / s["published_experts"]


def attention_flops(config: dict, tokens: int) -> float:
    """Scores and mixing of one sequence, all layers: 4 x heads x head_dim an allowed pair."""
    s = _sizes(config)
    pairs = sum(pairs_allowed(tokens, window) for window in layer_windows(config))
    return 4.0 * s["num_attention_heads"] * s["head_dim"] * pairs


def attention_bytes(config: dict, tokens: int) -> float:
    """q, k, v in and o out once a layer at bfloat16."""
    s = _sizes(config)
    row = 2 * s["num_attention_heads"] * s["head_dim"] + 2 * s["num_key_value_heads"] * s["head_dim"]
    return 2.0 * row * tokens * s["num_hidden_layers"]


def expert_matmul_flops(config: dict, tokens: int) -> float:
    """The held routed experts' three matmuls for ``tokens`` real tokens, all layers."""
    s = _sizes(config)
    return (
        6.0 * s["hidden_size"] * s["intermediate_size"] * experts_a_token_here(config)
        * tokens * s["num_hidden_layers"]
    )


def expert_matmul_bytes(config: dict, tokens: int) -> float:
    """One batch: the held experts' weights once a layer, and each routed row
    in and out, at bfloat16."""
    s = _sizes(config)
    weights = 3.0 * s["held"] * s["hidden_size"] * s["intermediate_size"] * 2.0
    rows = 2.0 * tokens * experts_a_token_here(config) * s["hidden_size"] * 2.0
    return s["num_hidden_layers"] * (weights + rows)


def forward_flops(config: dict, tokens: int) -> float:
    """The whole forward of one sequence of ``tokens`` real tokens, at this chip's share."""
    s = _sizes(config)
    d, width = s["hidden_size"], s["head_dim"]
    projections = 2.0 * d * width * (2 * s["num_attention_heads"] + 2 * s["num_key_value_heads"])
    router = 2.0 * d * s["published_experts"]
    shared = 6.0 * d * s["intermediate_size"] * s["num_shared_experts"]
    per_token = s["num_hidden_layers"] * (projections + router + shared)
    return tokens * per_token + expert_matmul_flops(config, tokens) + attention_flops(config, tokens)


WORK = {
    "attn_block": (attention_flops, attention_bytes, "sequence"),
    "moe_experts": (expert_matmul_flops, expert_matmul_bytes, "batch"),
}
