"""The plain reference of ``granite-4.0-h-micro``: the forward pass, the loss
and (with ``reference.py``'s clip and Adam) the optimizer step of a hybrid
state-space / grouped-KV-attention language model in straightforward
``jax.numpy`` and float32, every matmul at ``highest``.

It follows the model's published ``config.json`` (``model_type``
``granitemoehybrid``) and the Mamba-2 paper's recurrence, and imports nothing
of the program; it reads only parameter values that ``weights_lm.py`` drew
from the seed, by the names of the program's tree:

    x0     = embedding_multiplier * E[ids]
    h      = x + residual_multiplier * Mixer_l(RMSNorm(x))
    x'     = h + residual_multiplier * MLP(RMSNorm(h))
    MLP(v) = W_out (silu(a) * b),  [a, b] = W_in v
    logits = RMSNorm(x_L) E^T / logits_scaling
    loss   = mean next-token cross-entropy over the vocabulary held here

``attention`` mixer: 32 query heads over 8 key/value heads (query head i
attends key/value head i // 4), no bias, NO positional term,
``softmax(q k^T * attention_multiplier + causal) v``, one head at a time.

``mamba`` mixer, in its QUADRATIC form (no chunks, no carried state: another
algorithm than the program's chunked scan), one head at a time so the (n, n)
matrix fits:

    [z, xBC, dt] = W_in v;   xBC <- silu(conv1d_causal_depthwise(xBC) + b)
    D_t = softplus(dt_t + dt_bias),  A = -exp(A_log),  c = cumsum(D A)
    y_t = sum_{s<=t} exp(c_t - c_s) D_s (C_t . B_s) x_s + D x_t
    out = W_out RMSNorm_g(y * silu(z))

Departures from a literal transcription: blocks are rematerialised
(``jax.checkpoint``) and heads are mapped one at a time, both only so that
the float32 gradient of 772 M parameters fits one chip; neither changes a
number. The four shared helpers (``_mm``/``_act`` with their fp8 control,
``clip_by_global_norm``, ``adam_update``) are IMPORTED from ``reference.py``,
not written again.

``mode``: ``f32`` is the reference; ``fp8`` is the CONTROL, the same code with
both operands of every matmul in float8_e4m3 under a per-tensor scale (the
nearest precision below the bfloat16 the configuration states). Decays stay
float32 in both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import HIGHEST, NEG, _act, _mm, adam_update, clip_by_global_norm  # noqa: F401


def _rms_norm(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * gain.astype(jnp.float32)


def _matmul(a, b, mode):
    return jnp.matmul(_act(a, mode), _act(b, mode), precision=HIGHEST)


def _mlp(x, p, mode):
    a, b = jnp.split(_mm(x, p["Dense_0"]["kernel"], mode), 2, axis=-1)
    return _mm(jax.nn.silu(a) * b, p["Dense_1"]["kernel"], mode)


def _attention(x, p, cfg, mode):
    """x: (n, hidden), one sequence."""
    n = x.shape[0]
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    q = _mm(x, p["to_q"]["kernel"], mode).reshape(n, h, d)
    kv = _mm(x, p["to_kv"]["kernel"], mode).reshape(n, 2, g, d)
    k, v = kv[:, 0], kv[:, 1]
    causal = jnp.tril(jnp.ones((n, n), bool))

    @jax.checkpoint
    def head(i):
        j = i // (h // g)
        scores = _matmul(q[:, i] * cfg["attention_multiplier"], k[:, j].T, mode)
        attn = jax.nn.softmax(jnp.where(causal, scores, NEG), axis=-1)
        return _matmul(attn, v[:, j], mode)

    out = jax.lax.map(head, jnp.arange(h))               # (h, n, d)
    return _mm(out.transpose(1, 0, 2).reshape(n, h * d), p["to_out"]["kernel"], mode)


def _causal_conv(x, kernel, bias):
    """Depthwise: y_t = sum_k kernel[k] x_{t-K+1+k} + bias. x: (n, c)."""
    width, n = kernel.shape[0], x.shape[0]
    padded = jnp.concatenate((jnp.zeros((width - 1, x.shape[1]), x.dtype), x))
    return sum(padded[k : k + n] * kernel[k] for k in range(width)) + bias


def _mamba(x, p, cfg, mode):
    """x: (n, hidden), one sequence."""
    n = x.shape[0]
    h, d, s = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = h * d
    zxbcdt = _mm(x, p["in_proj"]["kernel"], mode)
    z, xbc, dt = jnp.split(zxbcdt, (inner, 2 * inner + 2 * s), axis=-1)
    xbc = jax.nn.silu(_causal_conv(
        xbc, p["conv"]["kernel"].astype(jnp.float32), p["conv"]["bias"].astype(jnp.float32)
    ))
    xs, B, C = jnp.split(xbc, (inner, inner + s), axis=-1)
    xs = xs.reshape(n, h, d)
    step = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))      # (n, h)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    cum = jnp.cumsum(step * A, axis=0)                                  # (n, h)
    scores = _matmul(C, B.T, mode)                                      # (n, n): C_t . B_s
    causal = jnp.tril(jnp.ones((n, n), bool))

    @jax.checkpoint
    def head(i):
        c, dt_i = cum[:, i], step[:, i]
        decay = jnp.exp(jnp.where(causal, c[:, None] - c[None, :], -jnp.inf))
        return _matmul(scores * decay * dt_i[None, :], xs[:, i], mode)

    y = jax.lax.map(head, jnp.arange(h)).transpose(1, 0, 2)            # (n, h, d)
    y = y + xs * p["D"].astype(jnp.float32)[:, None]
    y = _rms_norm(y.reshape(n, inner) * jax.nn.silu(z), p["norm"]["scale"], cfg["rms_norm_eps"])
    return _mm(y, p["out_proj"]["kernel"], mode)


def hidden_states(params, cfg: dict, ids, mode: str = "f32"):
    """ids: (n,) of one sequence. The final-normed hidden states (n, hidden)."""
    eps, mult = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    table = params["tok_emb"]["embedding"].astype(jnp.float32)
    x = cfg["embedding_multiplier"] * table[ids]
    blocks = params["transformer"]
    for i, kind in enumerate(cfg["layer_types"][: cfg["num_hidden_layers"]]):
        mixer = _mamba if kind == "mamba" else _attention

        def block(x, pm, pf, mixer=mixer):
            y = _rms_norm(x, pm["norm"]["scale"], eps)
            x = x + mult * mixer(y, pm["fn"], cfg, mode)
            y = _rms_norm(x, pf["norm"]["scale"], eps)
            return x + mult * _mlp(y, pf["fn"], mode)

        x = jax.checkpoint(block)(x, blocks[f"mixer_{i}"], blocks[f"ff_{i}"])
    return _rms_norm(x, params["final_norm"]["scale"], eps)


def logits(params, cfg: dict, ids, mode: str = "f32"):
    """(n, vocabulary held here) for one sequence."""
    normed = hidden_states(params, cfg, ids, mode)
    return _mm(normed, params["tok_emb"]["embedding"].T, mode) / cfg["logits_scaling"]


def loss(params, cfg: dict, ids, mode: str = "f32", positions: int | None = None):
    """ids: (b, n). Mean cross-entropy of the next token over positions
    0 … n-2 of every row (``positions``: only the first that many of them, the
    planted fault of a loss that leaves tokens out)."""
    total, count = 0.0, 0
    for row in ids:
        lg = logits(params, cfg, row, mode)[:-1][:positions]
        labels = row[1:][:positions]
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
        total, count = total + jnp.sum(lse - picked), count + labels.shape[0]
    return total / count
