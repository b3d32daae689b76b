"""Operations and bytes of a trunk configuration's forward, from the
configuration's published keys and a tick's real token counts alone.

As ``work.py``: what the algorithm needs at the stated precision (bfloat16
weights and rows), whatever implements it. Padding positions, pad rungs and
tile padding are not work.
"""

from __future__ import annotations


def _sizes(config: dict) -> dict:
    keys = (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "intermediate_size", "moe_intermediate_size",
        "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "hc_mult",
        "num_hidden_layers", "first_k_dense_replace",
    )
    return {k: int(config[k]) for k in keys}


def expert_layers(config: dict) -> int:
    freq = int(config.get("moe_layer_freq", 1))
    first = int(config["first_k_dense_replace"])
    return sum(1 for i in range(int(config["num_hidden_layers"])) if i >= first and i % freq == 0)


def forward_flops(config: dict, tokens: int) -> float:
    """The whole forward of one sequence of ``tokens`` real tokens."""
    s = _sizes(config)
    d, heads, n = s["hidden_size"], s["num_attention_heads"], s["hc_mult"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    mla = 2.0 * (
        d * s["q_lora_rank"]
        + s["q_lora_rank"] * heads * qk
        + d * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
        + s["kv_lora_rank"] * heads * (s["qk_nope_head_dim"] + s["v_head_dim"])
        + heads * s["v_head_dim"] * d
    )
    # causal: position i scores and mixes i keys
    attention = heads * (qk + s["v_head_dim"]) * float(tokens) * (tokens + 1)
    # a sub-layer's residual: the coefficient projection, H_pre X, H_res X + H_post F
    residual = 2.0 * (n * d * (2 * n + n * n) + n * d + n * n * d + n * d)
    dense = 6.0 * d * s["intermediate_size"]
    f = s["moe_intermediate_size"]
    sparse = 6.0 * d * f * (s["n_shared_experts"] + s["num_experts_per_tok"]) + 2.0 * d * s["n_routed_experts"]
    sparse_layers = expert_layers(config)
    layers = s["num_hidden_layers"]
    per_token = layers * (mla + 2 * residual) + (layers - sparse_layers) * dense + sparse_layers * sparse
    return tokens * per_token + layers * attention


def expert_matmul_flops(config: dict, tokens: int) -> float:
    """The routed experts' three matmuls for ``tokens`` real tokens, all
    expert layers: 6 d f k a token a layer."""
    s = _sizes(config)
    return (
        6.0 * s["hidden_size"] * s["moe_intermediate_size"] * s["num_experts_per_tok"]
        * tokens * expert_layers(config)
    )


def expert_matmul_bytes(config: dict, tokens: int) -> float:
    """One forward: the held experts' weights once a layer, and each routed
    row in and out, at bfloat16."""
    s = _sizes(config)
    held = int((config.get("experts_held") or (0, s["n_routed_experts"]))[1])
    weights = 3.0 * held * s["hidden_size"] * s["moe_intermediate_size"] * 2.0
    rows = 2.0 * tokens * s["num_experts_per_tok"] * s["hidden_size"] * 2.0
    return expert_layers(config) * (weights + rows)


def tick_forwards(tick: dict) -> list[list[int]]:
    """The forwards of one ingest tick, as ``drivers/ingest_ticks.window``
    records them: the chunk batch, then the probe alone (the last entry)."""
    tokens = tick["encoder_tokens"]
    return [tokens[:-1], tokens[-1:]]
