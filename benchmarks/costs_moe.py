"""Operations and bytes a step of a latent-attention / routed-expert language
model needs, from its configuration file (the source's ``config.json`` keys,
``n_routed_experts`` counting the experts held here). Arithmetic only; it
imports nothing of the program.

Conventions as ``costs.py``: one multiply-add = 2 FLOPs; backward costs twice
the forward; recomputed work is not counted; attention counts the causal
triangle, QK^T over the query/key width (nope + rope) and AV over the value
width. The routed experts count the (token, expert) pairs the held experts
are REALLY sent (``pairs_here``, summed over the expert layers, the MTP
module's included; from the program's ``moe.pairs_here``), not the expected
count and not a buffer's size.
"""

from __future__ import annotations


def mla_params(cfg: dict) -> int:
    """Weights of one latent-attention layer's five projections."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q, kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return d * q + q * h * (dn + dr) + d * (kv + dr) + kv * h * (dn + dv) + h * dv * d


def expert_params(cfg: dict) -> int:
    """One expert's (and one shared expert's) three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layers(cfg: dict) -> tuple:
    """(blocks with latent attention, dense feed-forwards, expert layers),
    the MTP module's block counted."""
    depth, dense = cfg["num_hidden_layers"], min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    mtp = cfg["num_nextn_predict_layers"]
    return depth + mtp, dense, depth - dense + mtp


def routed_total(cfg: dict) -> int:
    """The experts the router scores, held here or not."""
    return cfg["experts_held"]["of"] if "experts_held" in cfg else cfg["n_routed_experts"]


def expected_pairs(cfg: dict, tokens: int) -> float:
    """Pairs a uniform router would send the held experts, all expert layers."""
    total = routed_total(cfg)
    return layers(cfg)[2] * tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / total


def attention_flops_forward(cfg: dict, tokens_per_row: int, rows: int) -> float:
    """One layer: QK^T over 192 and AV over 128, the causal triangle, every head."""
    pairs = tokens_per_row * (tokens_per_row + 1) // 2
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return 2 * rows * pairs * cfg["num_attention_heads"] * width


def experts_bytes_forward(cfg: dict, pairs: float, layers_: int, bytes_per_el: int = 2) -> float:
    """The held experts' weights once a layer and each routed row in and out,
    in the compute dtype."""
    weights = layers_ * cfg["n_routed_experts"] * expert_params(cfg)
    return (weights + 2 * pairs * cfg["hidden_size"]) * bytes_per_el


def train_step(cfg: dict, rows: int, tokens_per_row: int, pairs_here: float | None = None) -> dict:
    """Required forward+backward FLOPs of one step, by part (and the routed
    experts' bytes). ``pairs_here``: the pairs the held experts were sent,
    all expert layers together; the uniform router's where not given."""
    tokens = rows * tokens_per_row
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    blocks, dense, expert_layers = layers(cfg)
    total = routed_total(cfg)
    pairs = expected_pairs(cfg, tokens) if pairs_here is None else float(pairs_here)
    mtp = cfg["num_nextn_predict_layers"]
    parts = {
        "mla_projections": blocks * 2 * tokens * mla_params(cfg),
        "attention": blocks * attention_flops_forward(cfg, tokens_per_row, rows),
        "dense_mlp": dense * 2 * tokens * 3 * d * cfg["intermediate_size"],
        "shared_experts": expert_layers * 2 * tokens * cfg["n_shared_experts"] * expert_params(cfg),
        "routed_experts": 2 * pairs * expert_params(cfg),
        "routers": expert_layers * 2 * tokens * d * total,
        "mtp_projection": mtp * 2 * tokens * 2 * d * d,
        "heads": 2 * rows * d * vocab * ((tokens_per_row - 1) + mtp * (tokens_per_row - 2)),
    }
    out = {name: 3 * flops for name, flops in parts.items()}
    out["total"] = sum(out.values())
    out["routed_experts_bytes"] = 3 * experts_bytes_forward(cfg, pairs, expert_layers)
    out["pairs_here"] = pairs
    return out
