"""Operations and bytes a step of a hybrid state-space / attention language
model needs, from its configuration file (the source's ``config.json`` keys).
Arithmetic only; it imports nothing of the program.

Conventions as ``costs.py``: one multiply-add = 2 FLOPs; backward costs twice
the forward; recomputed work is not counted; attention counts the causal
triangle. The scan is counted as the CHUNKED algorithm's minimum, whatever
implements it: per chunk of Q positions and per head, the masked
``(C B^T . L) X`` product (the Q (Q + 1) / 2 unmasked pairs x P), the chunk's
state ``B^T X`` and the read-out ``C S`` (Q x N x P each), and once per chunk
for all heads (one group) ``C B^T`` (the same pairs x N).
"""

from __future__ import annotations


def _mamba_dims(cfg: dict) -> tuple:
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    return h, p, n, h * p


def layer_matmul_params(cfg: dict, kind: str) -> int:
    """Weights of one block's projections: the mixer's and the SwiGLU's."""
    d, hidden = cfg["hidden_size"], cfg["shared_intermediate_size"]
    mlp = d * 2 * hidden + hidden * d
    if kind == "mamba":
        h, _, n, inner = _mamba_dims(cfg)
        return mlp + d * (2 * inner + 2 * n + h) + inner * d
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    return mlp + d * d + 2 * d * kv + d * d


def scan_flops_forward(cfg: dict, tokens: int) -> float:
    """One Mamba layer's scan over ``tokens`` positions, forward."""
    h, p, n, _ = _mamba_dims(cfg)
    q = cfg["mamba_chunk_size"]
    chunks = tokens / q
    pairs = q * (q + 1) // 2          # the mask leaves the triangle, as in attention
    per_chunk = 2 * pairs * n + h * (2 * pairs * p + 2 * 2 * q * n * p)
    return chunks * per_chunk


def scan_bytes_forward(cfg: dict, tokens: int, bytes_per_el: int = 2) -> float:
    """x, B, C, y once each in the compute dtype, the step in float32, and the
    chunk states written and read once in float32."""
    h, p, n, inner = _mamba_dims(cfg)
    chunks = tokens / cfg["mamba_chunk_size"]
    return tokens * ((2 * inner + 2 * n) * bytes_per_el + 4 * h) + 2 * chunks * h * p * n * 4


def attention_flops_forward(cfg: dict, tokens_per_row: int, rows: int) -> float:
    """QK^T and AV over the causal triangle, every query head."""
    pairs = tokens_per_row * (tokens_per_row + 1) // 2
    return 4 * rows * pairs * cfg["hidden_size"]


def train_step(cfg: dict, rows: int, tokens_per_row: int) -> dict:
    """Required forward+backward FLOPs (and the scan's bytes) of one step."""
    tokens = rows * tokens_per_row
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    dots = sum(2 * tokens * layer_matmul_params(cfg, k) for k in kinds)
    head = 2 * rows * (tokens_per_row - 1) * cfg["hidden_size"] * cfg["vocab_size"]
    n_attn, n_mamba = kinds.count("attention"), kinds.count("mamba")
    attention = n_attn * attention_flops_forward(cfg, tokens_per_row, rows)
    scan = n_mamba * rows * scan_flops_forward(cfg, tokens_per_row)
    return {
        "matmul": 3 * (dots + head),
        "attention": 3 * attention,
        "scan": 3 * scan,
        "scan_bytes": 3 * n_mamba * rows * scan_bytes_forward(cfg, tokens_per_row),
        "total": 3 * (dots + head + attention + scan),
    }
