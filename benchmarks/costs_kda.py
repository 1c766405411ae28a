"""Operations and bytes a step of a KDA / latent-attention / routed-expert
language model needs, from its configuration file (the source's
``config.json`` keys, ``num_experts`` counting the experts held here).
Arithmetic only; it imports nothing of the program.

Conventions as ``costs.py``: one multiply-add = 2 FLOPs; backward costs twice
the forward; recomputed work is not counted; a masked product counts the
triangle it keeps. The routed experts count the (token, expert) pairs the held
experts are REALLY sent (``pairs_here``, summed over the expert layers).

The KDA rule is counted as the CHUNKED algorithm's minimum at the family's
chunk of 64, whatever kernel runs it, term by term (``C`` the chunk, ``d`` a
head's width, keys and values alike; ``kda_flops_forward``): a decayed product
``sum_c x_ic y_jc exp(G_ic - G_jc)`` counts as a product of its pairs over
the ``d`` channels, the exponentials not at all:

    per chunk and head     K K^T . D below the diagonal    C (C - 1) / 2 pairs x d
                           Q K^T . D on and below it       C (C + 1) / 2 pairs x d
                           (I + A)^-1, triangular          C^3 / 3 FLOPs
                           (exp(G) . K) S_0                C x d x d
                           T R                             C (C + 1) / 2 pairs x d
                           (exp(G) . Q) S_0                C x d x d
                           P U                             C (C + 1) / 2 pairs x d
                           (exp(G_C - G) . K)^T U          C x d x d

Its bytes (``kda_bytes_forward``) are what every algorithm must move: q, k, v
and the output once each in the compute dtype, the per-channel log-decay (as
wide as the keys) and beta in float32; the backward, at twice this, reads
them again with the output's cotangent and writes theirs.
"""

from __future__ import annotations

KDA_CHUNK = 64


def layer_kinds(cfg: dict) -> list:
    """``kda`` or ``mla`` a layer, from the 1-BASED lists."""
    kda = set(cfg["linear_attn_config"]["kda_layers"])
    return ["kda" if l + 1 in kda else "mla" for l in range(cfg["num_hidden_layers"])]


def kda_params(cfg: dict) -> int:
    """Weights of one KDA mixer: q | k | v, the write strength, the two
    low-rank gates (rank the head size), the output projection, the
    convolution's taps, A_log, dt_bias and the output norm's gain."""
    lin, d = cfg["linear_attn_config"], cfg["hidden_size"]
    h, dh = lin["num_heads"], lin["head_dim"]
    width = h * dh
    gates = 2 * (d * dh + dh * width)
    return (3 * d * width + d * h + gates + width * d
            + lin["short_conv_kernel_size"] * 3 * width + h + width + dh)


def kda_matmul_params(cfg: dict) -> int:
    """The KDA mixer's weights a token multiplies by (its projections)."""
    lin, d = cfg["linear_attn_config"], cfg["hidden_size"]
    h, dh = lin["num_heads"], lin["head_dim"]
    width = h * dh
    return 3 * d * width + d * h + 2 * (d * dh + dh * width) + width * d


def mla_params(cfg: dict) -> int:
    """Weights of one latent-attention mixer with no query compression."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv, r = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    return d * h * (dn + dr) + d * (r + dr) + r + r * h * (dn + dv) + h * dv * d


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices (the shared expert's are the same)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_total(cfg: dict) -> int:
    """The experts the router scores, held here or not."""
    return cfg["experts_held"]["of"] if "experts_held" in cfg else cfg["num_experts"]


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def param_count(cfg: dict) -> int:
    """Every leaf of the cut: mixers, feed-forwards, the routers with their
    selection bias and count of pairs, the norms, embedding and head."""
    d, vocab, depth = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    kinds = layer_kinds(cfg)
    dense = depth - expert_layers(cfg)
    experts = expert_layers(cfg) * (
        d * routed_total(cfg) + 2 * routed_total(cfg)
        + (cfg["num_experts"] + cfg["num_shared_experts"]) * expert_params(cfg)
    )
    return (kinds.count("kda") * kda_params(cfg) + kinds.count("mla") * mla_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"] + experts + (2 * depth + 1) * d + 2 * vocab * d)


def expected_pairs(cfg: dict, tokens: int) -> float:
    """Pairs a uniform router would send the held experts, all layers."""
    return (expert_layers(cfg) * tokens * cfg["num_experts_per_token"]
            * cfg["num_experts"] / routed_total(cfg))


def experts_bytes_forward(cfg: dict, pairs: float, layers: int, bytes_per_el: int = 2) -> float:
    """The held experts' weights once a layer and each routed row in and out,
    in the compute dtype."""
    weights = layers * cfg["num_experts"] * expert_params(cfg)
    return (weights + 2 * pairs * cfg["hidden_size"]) * bytes_per_el


def kda_flops_forward(cfg: dict, tokens: int) -> dict:
    """One KDA layer's rule over ``tokens`` positions, forward, term by term."""
    lin, c = cfg["linear_attn_config"], KDA_CHUNK
    h, d = lin["num_heads"], lin["head_dim"]
    chunks = tokens / c
    below, upto = c * (c - 1) // 2, c * (c + 1) // 2
    return {
        "k_kT": chunks * h * 2 * below * d,
        "q_kT": chunks * h * 2 * upto * d,
        "inverse": chunks * h * c**3 / 3,
        "k_state": chunks * h * 2 * c * d * d,
        "t_r": chunks * h * 2 * upto * d,
        "q_state": chunks * h * 2 * c * d * d,
        "p_u": chunks * h * 2 * upto * d,
        "state_update": chunks * h * 2 * c * d * d,
    }


def kda_bytes_forward(cfg: dict, tokens: int, bytes_per_el: int = 2) -> float:
    lin = cfg["linear_attn_config"]
    width = lin["num_heads"] * lin["head_dim"]
    return tokens * (4 * width * bytes_per_el + 4 * width + 4 * lin["num_heads"])


def attention_flops_forward(cfg: dict, tokens_per_row: int, rows: int) -> float:
    """The latent attention: QK^T over 128 + 64 channels and AV over 128, the
    causal triangle, every head."""
    pairs = tokens_per_row * (tokens_per_row + 1) // 2
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return 2 * rows * pairs * cfg["num_attention_heads"] * width


def train_step(cfg: dict, rows: int, tokens_per_row: int, pairs_here: float | None = None) -> dict:
    """Required forward+backward FLOPs of one step, by part (and the KDA
    rule's and the routed experts' bytes). ``pairs_here``: the pairs the held experts were sent, all
    layers together; the uniform router's where not given."""
    tokens = rows * tokens_per_row
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    kinds, lin = layer_kinds(cfg), cfg["linear_attn_config"]
    kda, mla = kinds.count("kda"), kinds.count("mla")
    moe = expert_layers(cfg)
    pairs = expected_pairs(cfg, tokens) if pairs_here is None else float(pairs_here)
    width = lin["num_heads"] * lin["head_dim"]
    parts = {
        "kda_projections": kda * 2 * tokens * kda_matmul_params(cfg),
        "kda_conv": kda * 2 * tokens * lin["short_conv_kernel_size"] * 3 * width,
        "kda": kda * rows * sum(kda_flops_forward(cfg, tokens_per_row).values()),
        "attention_projections": mla * 2 * tokens * (mla_params(cfg) - cfg["kv_lora_rank"]),
        "attention": mla * attention_flops_forward(cfg, tokens_per_row, rows),
        "dense_ff": (cfg["num_hidden_layers"] - moe) * 2 * tokens * 3 * d * cfg["intermediate_size"],
        "shared_experts": moe * 2 * tokens * cfg["num_shared_experts"] * expert_params(cfg),
        "routed_experts": 2 * pairs * expert_params(cfg),
        "routers": moe * 2 * tokens * d * routed_total(cfg),
        "head": 2 * rows * (tokens_per_row - 1) * d * vocab,
    }
    out = {name: 3 * flops for name, flops in parts.items()}
    out["total"] = sum(out.values())
    out["kda_bytes"] = 3 * kda * rows * kda_bytes_forward(cfg, tokens_per_row)
    out["routed_experts_bytes"] = 3 * experts_bytes_forward(cfg, pairs, moe)
    out["pairs_here"] = pairs
    return out
