"""Operations and bytes a step of a window / global attention, routed-expert
language model (``smallthinker``) needs, from its configuration file (the
source's ``config.json`` keys, ``moe_num_primary_experts`` counting the experts
held here). Arithmetic only; it imports nothing of the program.

Conventions as ``costs.py``: one multiply-add = 2 FLOPs; backward costs twice
the forward; recomputed work is not counted. Attention counts the pairs the
layer REQUIRES: a global layer the causal triangle, a window layer the band
``0 <= i - j < W`` (``band_pairs``), QK^T and AV over the head's width, every
query head. The routed experts count the (token, expert) pairs the held
experts are REALLY sent (``pairs_here``, summed over the expert layers).
"""

from __future__ import annotations


def band_pairs(n: int, window: int | None) -> int:
    """(query, key) pairs of a row of ``n`` with ``0 <= i - j < window``;
    the causal triangle without a window."""
    w = n if window is None else min(window, n)
    return w * (w + 1) // 2 + (n - w) * w


def windows(cfg: dict) -> list:
    """Each layer's window, None for a global layer."""
    return [cfg["sliding_window_size"] if w else None for w in cfg["sliding_window_layout"]]


def attention_params(cfg: dict) -> int:
    """Weights of one attention layer's three projections."""
    d, h, g, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    return d * h * dh + d * 2 * g * dh + h * dh * d


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def routed_total(cfg: dict) -> int:
    """The experts the router scores, held here or not."""
    return cfg["experts_held"]["of"] if "experts_held" in cfg else cfg["moe_num_primary_experts"]


def expected_pairs(cfg: dict, tokens: int) -> float:
    """Pairs a uniform router would send the held experts, all layers."""
    return (cfg["num_hidden_layers"] * tokens * cfg["moe_num_active_primary_experts"]
            * cfg["moe_num_primary_experts"] / routed_total(cfg))


def attention_flops_forward(cfg: dict, tokens_per_row: int, rows: int, window) -> float:
    """One attention layer's QK^T and AV over the pairs it requires."""
    return (2 * rows * band_pairs(tokens_per_row, window) * cfg["num_attention_heads"]
            * 2 * cfg["head_dim"])


def experts_bytes_forward(cfg: dict, pairs: float, layers: int, bytes_per_el: int = 2) -> float:
    """The held experts' weights once a layer and each routed row in and out,
    in the compute dtype."""
    weights = layers * cfg["moe_num_primary_experts"] * expert_params(cfg)
    return (weights + 2 * pairs * cfg["hidden_size"]) * bytes_per_el


def train_step(cfg: dict, rows: int, tokens_per_row: int, pairs_here: float | None = None) -> dict:
    """Required forward+backward FLOPs of one step, by part (and the routed
    experts' bytes). ``pairs_here``: the pairs the held experts were sent, all
    layers together; the uniform router's where not given."""
    tokens = rows * tokens_per_row
    d, vocab, depth = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    pairs = expected_pairs(cfg, tokens) if pairs_here is None else float(pairs_here)
    kinds = windows(cfg)
    parts = {
        "attention_projections": depth * 2 * tokens * attention_params(cfg),
        "window_attention": sum(attention_flops_forward(cfg, tokens_per_row, rows, w)
                                for w in kinds if w is not None),
        "global_attention": sum(attention_flops_forward(cfg, tokens_per_row, rows, None)
                                for w in kinds if w is None),
        "routed_experts": 2 * pairs * expert_params(cfg),
        "routers": depth * 2 * tokens * d * routed_total(cfg),
        "head": 2 * rows * (tokens_per_row - 1) * d * vocab,
    }
    out = {name: 3 * flops for name, flops in parts.items()}
    out["total"] = sum(out.values())
    out["routed_experts_bytes"] = 3 * experts_bytes_forward(cfg, pairs, depth)
    out["pairs_here"] = pairs
    return out
