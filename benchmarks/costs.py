"""Operations and bytes a configuration's work needs, from its shapes.

Everything here is arithmetic over a configuration file
(``benchmarks/configs/<name>.json``) and a pattern's may-attend mask. It
imports nothing of the program and nothing of ``bench.py``: the masks are
written out again here from the published description of each pattern, so a
change to the program's masks cannot move the yardstick.

Conventions: one multiply-add = 2 FLOPs; the backward pass costs twice the
forward; recomputed work is not counted; an attention layer counts only the
(query, key) pairs its pattern leaves unmasked, the causal triangle included.
"""

from __future__ import annotations

import functools
import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def load_peaks(device_kind: str) -> dict:
    """The one table of peaks. A device that is not in it is an error."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise SystemExit(
            f"no peak on record for device kind {device_kind!r}; "
            f"benchmarks/peaks.json knows {sorted(table)}"
        )
    return table[device_kind]


# ---------------------------------------------------------------- shapes


def text_len(cfg: dict) -> int:
    """Text positions with <bos>."""
    return cfg["text_seq_len"] + 1


def image_len(cfg: dict) -> int:
    return cfg["image_fmap_size"] ** 2


def seq_len(cfg: dict) -> int:
    """Positions the transformer runs over: the last image token is never fed."""
    return cfg["text_seq_len"] + image_len(cfg)


def inner_dim(cfg: dict) -> int:
    return cfg["heads"] * cfg["dim_head"]


def layer_kinds(cfg: dict) -> list:
    kinds = cfg["attn_types"]
    return [kinds[i % len(kinds)] for i in range(cfg["depth"])]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one block's four projections (qkv, out, GEGLU in, GEGLU out)."""
    d, inner, hidden = cfg["dim"], inner_dim(cfg), int(cfg["dim"] * cfg["ff_mult"])
    return d * 3 * inner + inner * d + d * 2 * hidden + hidden * d


def text_vocab(cfg: dict) -> int:
    """Text vocabulary with the per-position padding ids."""
    return cfg["num_text_tokens"] + cfg["text_seq_len"]


# ----------------------------------------------------------------- masks


@functools.lru_cache(maxsize=None)
def _mask(kind: str, tl: int, fmap: int, kernel: int) -> np.ndarray:
    """(L, L) bool, True = query row may attend key column, L = tl + fmap**2.

    full: j <= i. Every other pattern: text rows causal over text; image rows
    see all text, and of the image only: the same grid row up to themselves
    (axial_row), the same grid column up to themselves (axial_col), or the
    kernel x kernel window around themselves, no later than themselves
    (conv_like)."""
    n_img = fmap * fmap
    total = tl + n_img
    causal = np.tril(np.ones((total, total), dtype=bool))
    if kind == "full":
        return causal
    mask = np.zeros((total, total), dtype=bool)
    mask[:tl, :tl] = causal[:tl, :tl]
    mask[tl:, :tl] = True
    p = np.arange(n_img)
    row, col = p // fmap, p % fmap
    earlier = p[:, None] >= p[None, :]
    if kind == "axial_row":
        allowed = (row[:, None] == row[None, :]) & earlier
    elif kind == "axial_col":
        allowed = (col[:, None] == col[None, :]) & earlier
    elif kind == "conv_like":
        half = kernel // 2
        allowed = (
            (np.abs(row[:, None] - row[None, :]) <= half)
            & (np.abs(col[:, None] - col[None, :]) <= half)
            & earlier
        )
    else:
        raise ValueError(f"no mask for attention pattern {kind!r}")
    mask[tl:, tl:] = allowed
    return mask


def pattern_mask(cfg: dict, kind: str) -> np.ndarray:
    """The may-attend mask of one layer kind over the positions that run."""
    n = seq_len(cfg)
    full = _mask(kind, text_len(cfg), cfg["image_fmap_size"],
                 cfg.get("conv_kernel_size", 5))
    return full[:n, :n]


def attended_pairs(cfg: dict, kind: str) -> int:
    return int(pattern_mask(cfg, kind).sum())


# ------------------------------------------------------------ train step


def train_step_flops(cfg: dict, batch: int) -> dict:
    """Required forward+backward FLOPs of one train step, split by where they
    run: the four projections of every block and the loss head ('matmul'),
    and QK^T and AV over the unmasked pairs ('attention').

    The head is counted as the loss needs it: text positions against the text
    vocabulary, image positions against the image vocabulary (the other
    logits are masked out of the loss)."""
    n, d = seq_len(cfg), cfg["dim"]
    blocks = 2 * batch * n * layer_matmul_params(cfg) * cfg["depth"]
    head = 2 * batch * d * (
        cfg["text_seq_len"] * text_vocab(cfg)
        + image_len(cfg) * cfg["num_image_tokens"]
    )
    pairs = sum(attended_pairs(cfg, k) for k in layer_kinds(cfg))
    attention = 4 * batch * pairs * inner_dim(cfg)
    return {
        "matmul": 3 * (blocks + head),
        "attention": 3 * attention,
        "total": 3 * (blocks + head + attention),
    }


# --------------------------------------------------------------- serving


def prefill_flops(cfg: dict) -> float:
    """One prompt: <bos> + text through every block, causal attention among
    them, and one head row over the image vocabulary."""
    tl, d = text_len(cfg), cfg["dim"]
    proj = 2 * tl * layer_matmul_params(cfg) * cfg["depth"]
    attn = 4 * (tl * (tl + 1) // 2) * inner_dim(cfg) * cfg["depth"]
    return proj + attn + 2 * d * cfg["num_image_tokens"]


def decode_step_bytes(cfg: dict, frontier_sum: float, bytes_per_el: int = 2) -> float:
    """HBM bytes one batched decode step must stream: every block's weights
    and the image columns of the head once, and the K and V rows of every
    live slot up to its frontier (``frontier_sum`` = the sum over live slots
    of positions cached). Activations, biases and page tables are left out:
    they are under a thousandth of this."""
    weights = (
        layer_matmul_params(cfg) * cfg["depth"]
        + cfg["dim"] * cfg["num_image_tokens"]
    ) * bytes_per_el
    kv = 2 * frontier_sum * inner_dim(cfg) * cfg["depth"] * bytes_per_el
    return weights + kv


def decode_step_flops(cfg: dict, n_live: float, frontier_sum: float) -> float:
    """FLOPs of the live rows of one batched decode step."""
    d = cfg["dim"]
    proj = 2 * n_live * (
        layer_matmul_params(cfg) * cfg["depth"] + d * cfg["num_image_tokens"]
    )
    return proj + 4 * frontier_sum * inner_dim(cfg) * cfg["depth"]
