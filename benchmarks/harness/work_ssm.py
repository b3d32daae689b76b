"""Operations and bytes of a hybrid state-space trunk configuration's
forward, from the configuration's published keys and a tick's real token
counts alone.

As ``work.py``, ``work_trunk.py`` and ``work_gqa.py``: what the algorithm
needs at the stated precision (bfloat16 weights and rows), whatever
implements it. Padding positions, pad rungs, tile padding and masked corners
are not work. The scan is counted in its dual form at the published chunk
(``mamba_chunk_size``), a real token and a head: ``2 P N`` for the chunk's
state, ``2 P N`` for reading the carried state, ``2 P`` an allowed pair
inside its chunk, and ``2 N`` an allowed pair once for all heads (``C_t .
B_r``: one group). The routed experts are counted at this chip's share: a
token goes to ``num_experts_per_tok`` of the published experts, of which this
chip holds ``experts_held``.
"""

from __future__ import annotations


def _sizes(config: dict) -> dict:
    keys = (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "intermediate_size",
        "shared_intermediate_size", "num_experts_per_tok", "num_hidden_layers",
        "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_chunk_size",
    )
    s = {k: int(config[k]) for k in keys}
    s["head_dim"] = int(config.get("head_dim") or s["hidden_size"] // s["num_attention_heads"])
    s["published_experts"] = int(config["published"]["num_local_experts"])
    s["held"] = int(config["experts_held"][1])
    kinds = list(config["layer_types"])[: s["num_hidden_layers"]]
    s["mamba_layers"], s["attention_layers"] = kinds.count("mamba"), kinds.count("attention")
    return s


def experts_a_token_here(config: dict) -> float:
    s = _sizes(config)
    return s["num_experts_per_tok"] * s["held"] / s["published_experts"]


def chunk_pairs(tokens: int, chunk: int) -> int:
    """Allowed pairs (r <= t, both in one chunk) of a sequence of ``tokens`` real tokens."""
    whole, rest = divmod(tokens, chunk)
    return whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2


def scan_flops(config: dict, tokens: int) -> float:
    """The selective scans of one sequence, all Mamba layers."""
    s = _sizes(config)
    heads, p, n = s["mamba_n_heads"], s["mamba_d_head"], s["mamba_d_state"]
    pairs = chunk_pairs(tokens, s["mamba_chunk_size"])
    a_layer = heads * (4.0 * p * n * tokens + 2.0 * p * pairs) + 2.0 * n * pairs
    return s["mamba_layers"] * a_layer


def scan_bytes(config: dict, tokens: int) -> float:
    """x in and y out at bfloat16, B and C (bfloat16) and dt (float32) once, a layer."""
    s = _sizes(config)
    heads, p, n = s["mamba_n_heads"], s["mamba_d_head"], s["mamba_d_state"]
    return s["mamba_layers"] * tokens * (2.0 * 2 * heads * p + 2.0 * 2 * n + 4.0 * heads)


def attention_pairs(tokens: int) -> int:
    return tokens * (tokens + 1) // 2


def attention_flops(config: dict, tokens: int) -> float:
    """Scores and mixing of one sequence in the full layers: 4 x heads x head_dim an allowed pair."""
    s = _sizes(config)
    return 4.0 * s["num_attention_heads"] * s["head_dim"] * attention_pairs(tokens) * s["attention_layers"]


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


def mamba_layer_flops(config: dict) -> float:
    """A real token through one Mamba mixer without its scan: in- and
    out-projection and the convolution."""
    s = _sizes(config)
    d, inner, n = s["hidden_size"], s["mamba_n_heads"] * s["mamba_d_head"], s["mamba_d_state"]
    channels = inner + 2 * n
    return 2.0 * d * (inner + channels + s["mamba_n_heads"]) + 2.0 * inner * d + 2.0 * s["mamba_d_conv"] * channels


def forward_flops(config: dict, tokens: int) -> float:
    """The whole forward of one sequence of ``tokens`` real tokens, at this chip's share."""
    s = _sizes(config)
    d, width = s["hidden_size"], s["head_dim"]
    projections = 2.0 * d * width * (2 * s["num_attention_heads"] + 2 * s["num_key_value_heads"])
    router = 2.0 * d * s["published_experts"]
    shared = 6.0 * d * s["shared_intermediate_size"]
    per_token = (
        s["mamba_layers"] * mamba_layer_flops(config)
        + s["attention_layers"] * projections
        + s["num_hidden_layers"] * (router + shared)
    )
    return (
        tokens * per_token + expert_matmul_flops(config, tokens)
        + scan_flops(config, tokens) + attention_flops(config, tokens)
    )


WORK = {
    "ssd_scan": (scan_flops, scan_bytes, "sequence"),
    "moe_experts": (expert_matmul_flops, expert_matmul_bytes, "batch"),
}
