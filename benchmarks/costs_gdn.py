"""Operations and bytes a step of a linear-attention / gated-attention /
routed-expert language model needs, from its configuration file (the source's
``config.json`` keys, ``num_experts`` counting the experts held here).
Arithmetic only; it imports nothing of the program.

Conventions as ``costs.py``: one multiply-add = 2 FLOPs; backward costs twice
the forward; recomputed work is not counted; a masked product counts the
triangle it keeps. The routed experts count the (token, expert) pairs the held
experts are REALLY sent (``pairs_here``, summed over the expert layers).

The delta rule is counted as the CHUNKED algorithm's minimum at the source's
chunk of 64, whatever kernel or chunk runs it, term by term (``C`` the chunk,
``d_k``, ``d_v`` a head's widths; ``DELTA_TERMS``):

    per chunk and KEY head     K K^T below the diagonal    C (C - 1) / 2 pairs x d_k
                               Q K^T on and below it       C (C + 1) / 2 pairs x d_k
    per chunk and VALUE head   (I + A)^-1, triangular      C^3 / 3 FLOPs
                               (exp(G) K) S_0              C x d_k x d_v
                               T R                         C (C + 1) / 2 pairs x d_v
                               (exp(G) Q) S_0              C x d_k x d_v
                               P U                         C (C + 1) / 2 pairs x d_v
                               (exp(G_C - G) K)^T U        C x d_k x d_v
"""

from __future__ import annotations

DELTA_CHUNK = 64


def layer_kinds(cfg: dict) -> list:
    depth = cfg["num_hidden_layers"]
    kinds = cfg.get("layer_types") or [
        "full_attention" if (l + 1) % cfg["full_attention_interval"] == 0 else "linear_attention"
        for l in range(depth)
    ]
    return list(kinds[:depth])


def linattn_params(cfg: dict) -> int:
    """Weights of one linear-attention layer's three projections."""
    d, hv = cfg["hidden_size"], cfg["linear_num_value_heads"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = hv * cfg["linear_value_head_dim"]
    return d * (2 * keys + 2 * values) + d * 2 * hv + values * d


def attention_params(cfg: dict) -> int:
    """Weights of the gated attention layer's four projections."""
    d, h, g, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    return d * h * 2 * dh + 2 * d * g * dh + h * dh * d


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_total(cfg: dict) -> int:
    """The experts the router scores, held here or not."""
    return cfg["experts_held"]["of"] if "experts_held" in cfg else cfg["num_experts"]


def expected_pairs(cfg: dict, tokens: int) -> float:
    """Pairs a uniform router would send the held experts, all layers."""
    return (cfg["num_hidden_layers"] * tokens * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / routed_total(cfg))


def delta_rule_flops_forward(cfg: dict, tokens: int) -> dict:
    """One linear-attention layer's delta rule over ``tokens`` positions,
    forward, term by term."""
    c, hk, hv = DELTA_CHUNK, cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    chunks = tokens / c
    below, upto = c * (c - 1) // 2, c * (c + 1) // 2
    return {
        "k_kT": chunks * hk * 2 * below * dk,
        "q_kT": chunks * hk * 2 * upto * dk,
        "inverse": chunks * hv * c**3 / 3,
        "k_state": chunks * hv * 2 * c * dk * dv,
        "t_r": chunks * hv * 2 * upto * dv,
        "q_state": chunks * hv * 2 * c * dk * dv,
        "p_u": chunks * hv * 2 * upto * dv,
        "state_update": chunks * hv * 2 * c * dk * dv,
    }


def delta_rule_bytes_forward(cfg: dict, tokens: int, bytes_per_el: int = 2) -> float:
    """What every algorithm must move: q, k, v and the output once each in
    the compute dtype, the log-decay and beta in float32 (the backward, at
    twice this, reads them again with the output's cotangent and writes
    theirs). States kept between the passes are a kernel's own choice of
    residual and are not counted."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return tokens * ((2 * hk * dk + 2 * hv * dv) * bytes_per_el + 2 * 4 * hv)


def attention_flops_forward(cfg: dict, tokens_per_row: int, rows: int) -> float:
    """The gated attention layer: QK^T and AV over 256 + 256 channels, the
    causal triangle, every query head."""
    pairs = tokens_per_row * (tokens_per_row + 1) // 2
    return 2 * rows * pairs * cfg["num_attention_heads"] * 2 * cfg["head_dim"]


def experts_bytes_forward(cfg: dict, pairs: float, layers_: int, bytes_per_el: int = 2) -> float:
    """The held experts' weights once a layer and each routed row in and out,
    in the compute dtype."""
    weights = layers_ * cfg["num_experts"] * expert_params(cfg)
    return (weights + 2 * pairs * cfg["hidden_size"]) * bytes_per_el


def train_step(cfg: dict, rows: int, tokens_per_row: int, pairs_here: float | None = None) -> dict:
    """Required forward+backward FLOPs of one step, by part (and the delta
    rule's and the routed experts' bytes). ``pairs_here``: the pairs the held
    experts were sent, all layers together; the uniform router's where not
    given."""
    tokens = rows * tokens_per_row
    d, vocab, depth = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    kinds = layer_kinds(cfg)
    linear, full = kinds.count("linear_attention"), kinds.count("full_attention")
    pairs = expected_pairs(cfg, tokens) if pairs_here is None else float(pairs_here)
    conv_channels = (2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
                     + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])
    delta = sum(delta_rule_flops_forward(cfg, tokens_per_row).values())
    parts = {
        "linattn_projections": linear * 2 * tokens * linattn_params(cfg),
        "linattn_conv": linear * 2 * tokens * cfg["linear_conv_kernel_dim"] * conv_channels,
        "delta_rule": linear * rows * delta,
        "attention_projections": full * 2 * tokens * attention_params(cfg),
        "attention": full * attention_flops_forward(cfg, tokens_per_row, rows),
        "shared_experts": depth * 2 * tokens * (3 * d * cfg["shared_expert_intermediate_size"] + d),
        "routed_experts": 2 * pairs * expert_params(cfg),
        "routers": depth * 2 * tokens * d * routed_total(cfg),
        "head": 2 * rows * (tokens_per_row - 1) * d * vocab,
    }
    out = {name: 3 * flops for name, flops in parts.items()}
    out["total"] = sum(out.values())
    out["delta_rule_bytes"] = 3 * linear * rows * delta_rule_bytes_forward(cfg, tokens_per_row)
    out["routed_experts_bytes"] = 3 * experts_bytes_forward(cfg, pairs, depth)
    out["pairs_here"] = pairs
    return out
