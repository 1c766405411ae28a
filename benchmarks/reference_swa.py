"""The plain reference of ``SmallThinker`` (``model_type`` ``smallthinker``):
the forward pass, the loss and (with ``reference.py``'s clip and Adam) the
optimizer step of a language model whose layers alternate global attention
without a positional term and sliding-window attention with rotary, every
feed-forward a routed ReGLU expert layer whose router reads the block's
INPUT, in straightforward ``jax.numpy`` and float32, every matmul at
``highest``.

It follows the published ``config.json`` and imports nothing of the program;
it reads only parameter values that ``weights_swa.py`` drew from the seed, by
the names of the program's tree. No bias anywhere, RMSNorm
``x / rms(x) * gain`` with eps from the config. Layer ``l`` with input ``x``:

    u = N_1(x)
    p = softmax(W_r u) over ALL experts, float32         the router, BEFORE attention
    e is chosen where fewer than k others have a larger p;  w_e = p_e / sum_chosen p
    q = W_q u (heads x d),  k = W_k u, v = W_v u (kv heads x d; a group of heads shares one)
    window layer (sliding_window_layout[l] = 1): q, k <- rot(q), rot(k), channel c < d / 2
        turned with c + d / 2 by position * theta^(-2c / d); key j visible to query i
        iff 0 <= i - j < W
    global layer (0): no positional term; key j visible iff j <= i
    h = x + W_o softmax(q k^T / sqrt(d)) v
    y = sum_{e chosen and held} w_e W2_e (relu(W1_e N_2(h)) * W3_e N_2(h))
    x' = h + y;   logits = W_head N(x_L)

What the experts held elsewhere would add is left out, as in the program.

Departures from a literal transcription, each only so that it fits: blocks,
heads and query blocks are rematerialised and mapped one at a time, every
query block scored against ALL keys under a dense mask of the band (or of
the triangle), so no (n, n) table is ever whole; the router's rank is taken a
block of tokens at a time; the experts are a dense loop over the held ones
(another algorithm than sorting pairs and grouping rows). ``_mm``/``_act``
with their fp8 control, ``clip_by_global_norm`` and ``adam_update`` are
IMPORTED from ``reference.py``, not written again.

``mode``: ``f32`` is the reference; ``fp8`` is the CONTROL, both operands of
every matmul in float8_e4m3 under a per-tensor scale (the nearest precision
below the bfloat16 the configuration states). The router's probabilities and
the rotary angles stay float32 in both. ``router='after'``, another control:
the router reads ``N_2(h)``, the expert layer's own input, as a router placed
after attention would.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .reference import HIGHEST, NEG, _act, _mm, adam_update, clip_by_global_norm  # noqa: F401

QUERY_BLOCK = 1024  # query rows whose scores against every key are live at once
RANK_BLOCK = 256    # tokens whose (experts, experts) comparisons are live at once
ROW_BLOCK = 2048    # rows whose expert outputs, or logits, are live at once


def _rms_norm(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * gain.astype(jnp.float32)


def _matmul(a, b, mode):
    return jnp.matmul(_act(a, mode), _act(b, mode), precision=HIGHEST)


def held_range(cfg: dict) -> tuple:
    if "experts_held" in cfg:
        lo, hi = cfg["experts_held"]["range"]
        return int(lo), int(hi)
    return 0, int(cfg["moe_num_primary_experts"])


def window_of(cfg: dict, layer: int):
    """The layer's window in keys, or None for a global layer."""
    return cfg["sliding_window_size"] if cfg["sliding_window_layout"][layer] else None


# ------------------------------------------------------------------ attention


def _rotate(x, theta: float):
    """x: (n, heads, d) at positions 0 … n-1, every channel turned: c < d / 2
    with c + d / 2 by ``position * theta ** (-2c / d)``."""
    n, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = (jnp.arange(n, dtype=jnp.float32)[:, None] * freqs[None, :])[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate((a * cos - b * sin, b * cos + a * sin), axis=-1)


def _attention(u, p, cfg, mode, window):
    """u: (n, hidden) normed, one sequence; ``window`` None for the global layer."""
    n = u.shape[0]
    h, g, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = _mm(u, p["to_q"]["kernel"], mode).reshape(n, h, d)
    kv = _mm(u, p["to_kv"]["kernel"], mode).reshape(n, 2, g, d)
    k, v = kv[:, 0], kv[:, 1]
    if window is not None:
        q, k = _rotate(q, float(cfg["rope_theta"])), _rotate(k, float(cfg["rope_theta"]))
    block = math.gcd(n, QUERY_BLOCK)
    keys = jnp.arange(n)

    def head(i):
        kh, vh = k[:, i // (h // g)], v[:, i // (h // g)]

        @jax.checkpoint
        def rows(b):
            at = b * block + jnp.arange(block)
            gap = at[:, None] - keys[None, :]
            visible = gap >= 0 if window is None else (gap >= 0) & (gap < window)
            scores = _matmul(jax.lax.dynamic_slice_in_dim(q[:, i], b * block, block), kh.T, mode)
            attn = jax.nn.softmax(jnp.where(visible, scores * d**-0.5, NEG), axis=-1)
            return _matmul(attn, vh, mode)

        return jax.lax.map(rows, jnp.arange(n // block)).reshape(n, d)

    out = jax.lax.map(jax.checkpoint(head), jnp.arange(h)).transpose(1, 0, 2)   # (n, h, d)
    return _mm(out.reshape(n, h * d), p["to_out"]["kernel"], mode)


# ------------------------------------------------------------ the expert layer


def expert_weights(u, gate, cfg):
    """-> (n, ALL experts): every token's weight for every expert, zero where
    the token did not choose it. The router over ``u``, float32 in every mode,
    the choice by RANK."""
    k, n = cfg["moe_num_active_primary_experts"], u.shape[0]
    probs = jax.nn.softmax(jnp.matmul(u, gate.astype(jnp.float32), precision=HIGHEST), axis=-1)

    def ranked(block):
        above = jnp.sum(block[:, None, :] > block[:, :, None], axis=-1)   # others ranked higher
        picked = jnp.where(above < k, block, 0.0)
        return picked / jnp.sum(picked, axis=-1, keepdims=True)

    size = math.gcd(n, RANK_BLOCK)
    return jax.lax.map(ranked, probs.reshape(n // size, size, -1)).reshape(probs.shape)


def _experts(x, p, everywhere, cfg, mode):
    """x: (n, hidden) the expert layer's normed input. -> (its output, the
    (token, expert) pairs sent to each of ALL experts)."""
    lo, hi = held_range(cfg)
    weights = everywhere[:, lo:hi]                                      # (n, held)
    w_in, w_out = p["experts_in"].astype(jnp.float32), p["experts_out"].astype(jnp.float32)

    @jax.checkpoint
    def rows(inp):
        x, weights = inp

        def one(y, held):
            w_in, w_out, w = held
            a, b = jnp.split(_mm(x, w_in, mode), 2, axis=-1)
            return y + w[:, None] * _mm(jax.nn.relu(a) * b, w_out, mode), None

        return jax.lax.scan(one, jnp.zeros_like(x), (w_in, w_out, weights.T))[0]

    size = math.gcd(x.shape[0], ROW_BLOCK)
    blocks = lambda t: t.reshape((-1, size) + t.shape[1:])
    y = jax.lax.map(rows, (blocks(x), blocks(weights))).reshape(x.shape)
    return y, jnp.sum(everywhere > 0, axis=0)


# ---------------------------------------------------------------- the model


def _block(x, pm, pf, cfg, mode, window, router):
    eps = cfg["rms_norm_eps"]
    u = _rms_norm(x, pm["norm"]["scale"], eps)
    if router == "before":
        chosen = expert_weights(u, pm["gate"]["kernel"], cfg)
    h = x + _attention(u, pm["fn"], cfg, mode, window)
    u2 = _rms_norm(h, pf["norm"]["scale"], eps)
    if router == "after":
        chosen = expert_weights(u2, pm["gate"]["kernel"], cfg)
    out, load = _experts(u2, pf["fn"], chosen, cfg, mode)
    return h + out, load


def hidden(params, cfg: dict, ids, mode: str = "f32", router: str = "before"):
    """ids: (n,) of one sequence. -> (the final norm's output (n, hidden),
    {an expert layer's path in the tree: the pairs it sent each of ALL
    experts})."""
    x = params["tok_emb"]["embedding"].astype(jnp.float32)[ids]
    blocks, loads = params["transformer"], {}
    for i in range(cfg["num_hidden_layers"]):
        x, load = jax.checkpoint(_block, static_argnums=(3, 4, 5, 6))(
            x, blocks[f"mixer_{i}"], blocks[f"ff_{i}"], cfg, mode, window_of(cfg, i), router
        )
        loads[f"transformer/ff_{i}/fn"] = load
    return _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"]), loads


def _nll(rows, head, labels, mode):
    """Summed cross-entropy, ``ROW_BLOCK`` rows' logits at a time."""

    @jax.checkpoint
    def block(inp):
        rows, labels = inp
        logits_ = _mm(rows, head.T, mode)
        lse = jax.scipy.special.logsumexp(logits_, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits_, labels[:, None], axis=-1)[:, 0])

    size = math.gcd(rows.shape[0], ROW_BLOCK)
    return jnp.sum(jax.lax.map(block, (rows.reshape(-1, size, rows.shape[-1]), labels.reshape(-1, size))))


def loss(params, cfg: dict, ids, mode: str = "f32", positions: int | None = None,
         router: str = "before"):
    """ids: (b, n). -> (mean next-token cross-entropy over positions 0 … n-2
    of every row, {an expert layer's path: the pairs it sent each of ALL
    experts}). ``positions``: only the first that many positions of a row
    are scored (the control that leaves tokens out)."""
    total, count, sent = 0.0, 0, {}
    for row in ids:
        normed, load = hidden(params, cfg, row, mode, router)
        total = total + _nll(normed[:-1][:positions], params["lm_head"], row[1:][:positions], mode)
        count += row[1:][:positions].shape[0]
        sent = {layer: sent.get(layer, 0) + x for layer, x in load.items()}
    return total / count, sent
