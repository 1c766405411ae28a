"""The plain reference of ``Qwen3-Next`` (``model_type`` ``qwen3_next``): the
forward pass, the loss with its load-balance term and (with ``reference.py``'s
clip and Adam) the optimizer step of a linear-attention / gated-attention /
routed-expert language model in straightforward ``jax.numpy`` and float32,
every matmul at ``highest``.

It follows the published ``config.json`` and the family's modelling code, and
imports nothing of the program; it reads only parameter values that
``weights_gdn.py`` drew from the seed, by the names of the program's tree. No
bias anywhere, RMSNorm ``x / rms(x) * gain`` with eps from the config:

    h = x + Mixer_l(N(x));   x' = h + Experts(N(h));   logits = W_head N(x_L)
    Mixer_l = gated softmax attention where (l + 1) % full_attention_interval == 0,
              the gated delta rule elsewhere

Linear attention (``u`` the normed input; ``H_k`` key heads, ``H_v`` value
heads, a key head serving ``H_v / H_k`` value heads), the delta rule as the
SEQUENTIAL recurrence, one position at a time in a ``lax.scan``, no chunk:

    [q | k | v | z] = W_qkvz u;  [b | a] = W_ba u
    [q | k | v] = silu(causal depthwise convolution of width 4, no bias)
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
    q <- q / |q| d_k^-1/2,  k <- k / |k|                    (eps 1e-6 under the root)
    S' = exp(g_t) S_{t-1};  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;  o_t = S_t^T q_t
    y = W_o [RMSNorm_{d_v}(o_t) . silu(z_t)]

Gated attention, ONE query head at a time:

    [q_i | g_i] = (W_q u)_i;  k_j = (W_k u)_j;  v_j = (W_v u)_j
    q_i <- rot(N(q_i)), k_j <- rot(N(k_j)):  channel c < rot / 2 turns with channel
    c + rot / 2 by position * theta^(-2c / rot), float32; channels past rot untouched
    out = W_o [softmax_causal(q_i . k_{i // group} / sqrt(d)) v_{i // group} . sigmoid(g_i)]_i

Expert layer, as a DENSE loop over the experts held here (another algorithm
than sorting pairs and grouping rows), the router's choice by RANK:

    p = softmax(W_g u) over ALL experts;  e is chosen where fewer than k others have a larger p
    w_e = p_e / (sum over ALL chosen of p), zero where not chosen
    y = sum_{e held} w_e SwiGLU_e(u) + sigmoid(w_sg . u) SwiGLU_shared(u)

What the experts held elsewhere would add is left out, as in the program.

    L_aux = E sum_e f_e P_e     f_e = share of (layer, token)s with e among their k (not over k),
                                P_e = mean of p_e; both over ALL layers and tokens of the step
    loss = CE + router_aux_loss_coef L_aux

``f`` carries no gradient, so the step's gradient is the sum of its rows'
when every row is given the STEP's ``f`` (``share``): the driver counts the
loads of all rows first (``loads``) and then takes the gradient a row at a
time, so that the float32 gradient fits one chip.

Departures from a literal transcription: blocks, heads and stretches of the
recurrence are rematerialised and mapped one at a time, and the rank is taken
a block of tokens at a time, only so that it fits. ``_mm``/``_act`` with their
fp8 control, ``clip_by_global_norm`` and ``adam_update`` are IMPORTED from
``reference.py``, not written again.

``mode``: ``f32`` is the reference; ``fp8`` is the CONTROL, both operands of
every matmul in float8_e4m3 under a per-tensor scale (the nearest precision
below the bfloat16 the configuration states). The router's probabilities, the
rotary angles and the recurrence stay float32 in both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .reference import HIGHEST, NEG, _act, _mm, adam_update, clip_by_global_norm  # noqa: F401

STRETCH = 64        # positions of the recurrence rematerialised together
RANK_BLOCK = 256    # tokens whose (experts, experts) comparisons are live at once


def _rms_norm(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * gain.astype(jnp.float32)


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _matmul(a, b, mode):
    return jnp.matmul(_act(a, mode), _act(b, mode), precision=HIGHEST)


def _swiglu(x, w_in, w_out, mode):
    a, b = jnp.split(_mm(x, w_in, mode), 2, axis=-1)
    return _mm(jax.nn.silu(a) * b, w_out, mode)


# ------------------------------------------------------------ linear attention


def delta_recurrence(q, k, v, g, beta):
    """q, k: (n, h, d_k); v: (n, h, d_v); g, beta: (n, h). -> (n, h, d_v).
    The recurrence itself, position by position."""
    n, h, dk = q.shape
    pad = -n % STRETCH
    q, k, v, g, beta = (
        jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)) for t in (q, k, v, g, beta)
    )

    def step(S, inp):
        q, k, v, g, beta = inp
        S = jnp.exp(g)[:, None, None] * S
        u = beta[:, None] * (v - jnp.sum(S * k[:, :, None], axis=1))
        S = S + k[:, :, None] * u[:, None, :]
        return S, jnp.sum(S * q[:, :, None], axis=1)

    @jax.checkpoint
    def stretch(S, inps):
        return jax.lax.scan(step, S, inps)

    blocks = lambda t: t.reshape((-1, STRETCH) + t.shape[1:])
    _, o = jax.lax.scan(
        stretch, jnp.zeros((h, dk, v.shape[-1]), jnp.float32),
        tuple(blocks(t) for t in (q, k, v, g, beta)),
    )
    return o.reshape((-1,) + o.shape[2:])[:n]


def _linear_attention(x, p, cfg, mode):
    """x: (n, hidden), one sequence."""
    n, eps = x.shape[0], cfg["rms_norm_eps"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv, width = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]
    keys, values = hk * dk, hv * dv
    qkvz = _mm(x, p["in_proj_qkvz"]["kernel"], mode)
    ba = _mm(x, p["in_proj_ba"]["kernel"], mode)
    qkv, z = qkvz[:, : 2 * keys + values], qkvz[:, 2 * keys + values :]
    taps = p["conv"]["kernel"].astype(jnp.float32)
    padded = jnp.pad(qkv, ((width - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[j : j + n] * taps[j] for j in range(width)))
    q, k, v = qkv[:, :keys], qkv[:, keys : 2 * keys], qkv[:, 2 * keys :]
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"].astype(jnp.float32)
    )
    q = jnp.repeat(_l2(q.reshape(n, hk, dk)) * dk**-0.5, hv // hk, axis=1)
    k = jnp.repeat(_l2(k.reshape(n, hk, dk)), hv // hk, axis=1)
    o = delta_recurrence(q, k, v.reshape(n, hv, dv), g, beta)
    o = _rms_norm(o, p["norm_scale"], eps).reshape(n, values)
    return _mm(o * jax.nn.silu(z), p["out_proj"]["kernel"], mode)


# ------------------------------------------------------------ gated attention


def _rotate(x, rot: int, theta: float):
    """x: (n, heads, d) at positions 0 … n-1: channel c < rot / 2 turned with
    channel c + rot / 2 by ``position * theta ** (-2c / rot)``."""
    n, half = x.shape[0], rot // 2
    freqs = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = (jnp.arange(n, dtype=jnp.float32)[:, None] * freqs[None, :])[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate((a * cos - b * sin, b * cos + a * sin, x[..., rot:]), axis=-1)


def _gated_attention(x, p, cfg, mode):
    n, eps = x.shape[0], cfg["rms_norm_eps"]
    h, g, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    rot, theta = int(d * cfg["partial_rotary_factor"]), float(cfg["rope_theta"])
    qg = _mm(x, p["to_q"]["kernel"], mode).reshape(n, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = _mm(x, p["to_k"]["kernel"], mode).reshape(n, g, d)
    v = _mm(x, p["to_v"]["kernel"], mode).reshape(n, g, d)
    q = _rotate(_rms_norm(q, p["q_norm"]["scale"], eps), rot, theta)
    k = _rotate(_rms_norm(k, p["k_norm"]["scale"], eps), rot, theta)
    causal = jnp.tril(jnp.ones((n, n), bool))

    @jax.checkpoint
    def head(i):
        j = i // (h // g)
        scores = _matmul(q[:, i], k[:, j].T, mode) * d**-0.5
        attn = jax.nn.softmax(jnp.where(causal, scores, NEG), axis=-1)
        return _matmul(attn, v[:, j], mode)

    out = jax.lax.map(head, jnp.arange(h)).transpose(1, 0, 2)          # (n, h, d)
    out = (out * jax.nn.sigmoid(gate)).reshape(n, h * d)
    return _mm(out, p["to_out"]["kernel"], mode)


# ------------------------------------------------------------ the expert layer


def held_range(cfg: dict) -> tuple:
    lo, hi = cfg["experts_held"]["range"] if "experts_held" in cfg else (0, cfg["num_experts"])
    return int(lo), int(hi)


def routed_total(cfg: dict) -> int:
    return cfg["experts_held"]["of"] if "experts_held" in cfg else cfg["num_experts"]


def expert_weights(x, p, cfg):
    """-> ((n, ALL experts) every token's weight for every expert, zero where
    the token did not choose it; (n, ALL experts) the router's probabilities).
    Float32 in every mode."""
    k, n = cfg["num_experts_per_tok"], x.shape[0]
    probs = jax.nn.softmax(
        jnp.matmul(x, p["gate"]["kernel"].astype(jnp.float32), precision=HIGHEST), axis=-1
    )

    def ranked(block):
        above = jnp.sum(block[:, None, :] > block[:, :, None], axis=-1)   # others ranked higher
        picked = jnp.where(above < k, block, 0.0)
        return picked / jnp.sum(picked, axis=-1, keepdims=True)

    size = math.gcd(n, RANK_BLOCK)
    weights = jax.lax.map(ranked, probs.reshape(n // size, size, -1)).reshape(probs.shape)
    return weights, probs


def _experts(x, p, cfg, mode):
    """x: (n, hidden). -> (the layer's output, the (token, expert) pairs sent
    to each of ALL experts, each expert's summed probability)."""
    lo, hi = held_range(cfg)
    everywhere, probs = expert_weights(x, p, cfg)
    weights = everywhere[:, lo:hi]                                      # (n, held)

    @jax.checkpoint
    def one(y, held):
        w_in, w_out, w = held
        return y + w[:, None] * _swiglu(x, w_in, w_out, mode), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["experts_in"].astype(jnp.float32), p["experts_out"].astype(jnp.float32), weights.T),
    )
    shared = p["shared"]
    open_ = jax.nn.sigmoid(_mm(x, p["shared_gate"]["kernel"], mode))    # (n, 1)
    y = y + open_ * _swiglu(x, shared["Dense_0"]["kernel"], shared["Dense_1"]["kernel"], mode)
    return y, jnp.sum(everywhere > 0, axis=0), jnp.sum(probs, axis=0)


def layer_kind(cfg: dict, i: int) -> str:
    kinds = cfg.get("layer_types")
    if kinds:
        return kinds[i]
    return "full_attention" if (i + 1) % cfg["full_attention_interval"] == 0 else "linear_attention"


def _block(x, pm, pf, cfg, mode, kind: str):
    eps = cfg["rms_norm_eps"]
    mixer = _gated_attention if kind == "full_attention" else _linear_attention
    x = x + mixer(_rms_norm(x, pm["norm"]["scale"], eps), pm["fn"], cfg, mode)
    out, load, prob = _experts(_rms_norm(x, pf["norm"]["scale"], eps), pf["fn"], cfg, mode)
    return x + out, load, prob


def _nll(rows, head, labels, mode):
    logits = _mm(rows, head.T, mode)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0])


def hidden(params, cfg: dict, ids, mode: str = "f32"):
    """ids: (n,) of one sequence. -> (the final norm's output (n, hidden),
    {an expert layer's path in the tree: the pairs it sent each of ALL
    experts}, {the same path: each expert's summed probability})."""
    x = params["tok_emb"]["embedding"].astype(jnp.float32)[ids]
    blocks, loads, probs = params["transformer"], {}, {}
    for i in range(cfg["num_hidden_layers"]):
        x, load, prob = jax.checkpoint(_block, static_argnums=(3, 4, 5))(
            x, blocks[f"mixer_{i}"], blocks[f"ff_{i}"], cfg, mode, layer_kind(cfg, i)
        )
        loads[f"transformer/ff_{i}/fn"], probs[f"transformer/ff_{i}/fn"] = load, prob
    return _rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"]), loads, probs


def logits(params, cfg: dict, ids, mode: str = "f32"):
    return _mm(hidden(params, cfg, ids, mode)[0], params["lm_head"].T, mode)


def loads(params, cfg: dict, ids, mode: str = "f32"):
    """ids: (b, n). -> {an expert layer's path: the pairs the rows sent each
    of ALL experts}, forward only."""
    out = {}
    for row in ids:
        for layer, load in hidden(params, cfg, row, mode)[1].items():
            out[layer] = out.get(layer, 0) + load
    return out


def share_of(cfg: dict, loads_: dict):
    """``f``: (ALL experts,) the share of (layer, token)s that chose each
    expert among their k, from the loads of all layers and rows."""
    load = sum(jnp.asarray(x, jnp.float32) for x in loads_.values())
    return load * cfg["num_experts_per_tok"] / jnp.sum(load)


def aux_of(cfg: dict, share, probs: dict, rows_of_probs: int):
    """``E sum_e f_e P_e`` with ``P`` the mean of ``probs`` ({layer: summed
    probability}) over its ``rows_of_probs`` (layer, token)s."""
    return routed_total(cfg) * jnp.sum(share * sum(probs.values()) / rows_of_probs)


def loss(params, cfg: dict, ids, mode: str = "f32", positions: int | None = None, share=None):
    """ids: (b, n). -> (mean next-token cross-entropy over positions 0 … n-2
    of every row + ``router_aux_loss_coef`` x the load-balance term, {an expert
    layer's path: the pairs it sent each of ALL experts}). ``share``: the
    step's ``f`` where ``ids`` is only some of the step's rows (``share_of``);
    from these rows themselves where not given. ``positions``: only the first
    that many positions of a row are scored (the planted fault of a loss that
    leaves tokens out)."""
    total, count, sent, summed = 0.0, 0, {}, {}
    for row in ids:
        normed, load, prob = hidden(params, cfg, row, mode)
        total = total + _nll(normed[:-1][:positions], params["lm_head"], row[1:][:positions], mode)
        count += row[1:][:positions].shape[0]
        sent = {layer: sent.get(layer, 0) + x for layer, x in load.items()}
        summed = {layer: summed.get(layer, 0) + x for layer, x in prob.items()}
    if share is None:
        share = share_of(cfg, sent)
    rows_of_probs = len(summed) * ids.shape[0] * ids.shape[1]
    aux = aux_of(cfg, jax.lax.stop_gradient(share), summed, rows_of_probs)
    return total / count + cfg.get("router_aux_loss_coef", 0.001) * aux, sent
