"""Operations and bytes of a window-with-sinks trunk configuration's forward,
from the configuration's published keys and a tick's real token counts
alone.

As ``work.py``, ``work_trunk.py``, ``work_gqa.py``, ``work_ssm.py`` and
``work_gdn.py``: what the algorithm needs at the stated precision (bfloat16
weights and rows), whatever implements it. Padding positions, pad rungs,
tile padding and the masked corners of a visited block are not work: an
allowed pair costs ``2 (d_qk + d_v)`` a query head (its logit and its share
of the output), window and full layers alike, and a sink costs nothing of
note. The routed experts are counted at this chip's share: a token goes to
``num_experts_per_tok`` of the published experts, of which this chip holds
``experts_held``, so ``k x held / published`` of them on average.
"""

from __future__ import annotations

from benchmarks.harness.reference_swa import layer_kinds, sparse_layers
from benchmarks.harness.work_gqa import pairs_allowed


def _sizes(config: dict) -> dict:
    keys = (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "swa_num_key_value_heads", "head_dim",
        "v_head_dim", "swa_v_head_dim", "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "sliding_window",
    )
    s = {k: int(config[k]) for k in keys}
    s["published_experts"] = int(config["published"]["n_routed_experts"])
    s["held"] = int(config["experts_held"][1])
    s["expert_layers"] = sum(sparse_layers(config))
    return s


def experts_a_token_here(config: dict) -> float:
    s = _sizes(config)
    return s["num_experts_per_tok"] * s["held"] / s["published_experts"]


def _heads(config: dict, kind: str) -> tuple[int, int]:
    """Key-value heads and value width of a ``"window"`` or ``"full"`` layer."""
    s = _sizes(config)
    if kind == "window":
        return s["swa_num_key_value_heads"], s["swa_v_head_dim"]
    return s["num_key_value_heads"], s["v_head_dim"]


def pair_flops(config: dict, kind: str = "window") -> float:
    """An allowed pair over all query heads: its logit and its share of the output."""
    s = _sizes(config)
    return 2.0 * s["num_attention_heads"] * (s["head_dim"] + _heads(config, kind)[1])


def attention_flops(config: dict, tokens: int) -> float:
    """Logits and mixing of one sequence's allowed pairs, all layers."""
    window = int(config["sliding_window"])
    return sum(
        pair_flops(config, kind) * pairs_allowed(tokens, window if kind == "window" else None)
        for kind in layer_kinds(config)
    )


def attention_bytes(config: dict, tokens: int) -> float:
    """q, k, v in and o out once a layer at bfloat16."""
    s = _sizes(config)
    total = 0.0
    for kind in layer_kinds(config):
        kv_heads, width_v = _heads(config, kind)
        row = s["num_attention_heads"] * (s["head_dim"] + width_v) + kv_heads * (s["head_dim"] + width_v)
        total += 2.0 * row * tokens
    return total


def projection_flops(config: dict, kind: str) -> float:
    """A real token through one layer's q, k, v and o projections."""
    s = _sizes(config)
    kv_heads, width_v = _heads(config, kind)
    heads, d = s["num_attention_heads"], s["hidden_size"]
    return 2.0 * d * (heads * s["head_dim"] + kv_heads * (s["head_dim"] + width_v)) + 2.0 * heads * width_v * d


def ffn_flops(config: dict, sparse: bool) -> float:
    """A real token through one layer's dense FFN, or its router and held routed experts."""
    s = _sizes(config)
    d = s["hidden_size"]
    if not sparse:
        return 6.0 * d * s["intermediate_size"]
    return 2.0 * d * s["published_experts"] + 6.0 * d * s["moe_intermediate_size"] * experts_a_token_here(config)


def token_flops(config: dict) -> float:
    """A real token through the whole forward without its attention pairs, at this chip's share."""
    return sum(
        projection_flops(config, kind) + ffn_flops(config, sparse)
        for kind, sparse in zip(layer_kinds(config), sparse_layers(config))
    )


def forward_flops(config: dict, tokens: int) -> float:
    """The whole forward of one sequence of ``tokens`` real tokens, at this chip's share."""
    return tokens * token_flops(config) + attention_flops(config, tokens)


def expert_matmul_flops(config: dict, tokens: int) -> float:
    """The held routed experts' three matmuls for ``tokens`` real tokens, all expert layers."""
    s = _sizes(config)
    return (
        6.0 * s["hidden_size"] * s["moe_intermediate_size"] * experts_a_token_here(config)
        * tokens * s["expert_layers"]
    )


def expert_matmul_bytes(config: dict, tokens: int) -> float:
    """One batch: the held experts' weights once a layer, and each routed row
    in and out, at bfloat16."""
    s = _sizes(config)
    weights = 3.0 * s["held"] * s["hidden_size"] * s["moe_intermediate_size"] * 2.0
    rows = 2.0 * tokens * experts_a_token_here(config) * s["hidden_size"] * 2.0
    return s["expert_layers"] * (weights + rows)


WORK = {
    "attn_block": (attention_flops, attention_bytes, "sequence"),
    "moe_experts": (expert_matmul_flops, expert_matmul_bytes, "batch"),
}
