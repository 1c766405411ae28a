"""The plain reference of ``Kimi-Linear`` (``model_type`` ``kimi_linear``): the
forward pass, the loss and (with ``reference.py``'s clip and Adam) the
optimizer step of a language model whose layers are Kimi Delta Attention
(KDA) three to one with latent attention that has no positional term, over a
dense feed-forward first and sigmoid-routed experts after, in straightforward
``jax.numpy`` and float32, every matmul at ``highest``.

It follows the published ``config.json`` and the family's modelling code, and
imports nothing of the program; it reads only parameter values that
``weights_kda.py`` drew from the seed, by the names of the program's tree. No
bias anywhere, RMSNorm ``x / rms(x) * gain`` with eps from the config:

    h = x + Mixer_l(N(x));   x' = h + FF_l(N(h));   logits = W_head N(x_L)
    Mixer_l = KDA where l + 1 is in linear_attn_config.kda_layers (1-BASED),
              latent attention where it is in full_attn_layers
    FF_l = SwiGLU of intermediate_size for l < first_k_dense_replace, else the expert layer

KDA (``u`` the normed input; H heads of d keys and d values), the delta rule
as the SEQUENTIAL recurrence, one position at a time in a ``lax.scan``, no
chunk:

    [q | k | v] = silu(causal depthwise convolution of width 4, no bias)(W_qkv u)
    g = -exp(A_log_h) softplus(W_fb W_fa u + dt_bias)      a vector over the d KEY channels
    beta = sigmoid(W_b u)                                 a head
    q <- q / |q| d^-1/2,  k <- k / |k|                    (eps 1e-6 under the root)
    S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;  o_t = S_t^T q_t
    y = W_o [RMSNorm_d(o_t) . sigmoid(W_gb W_ga u)]

Latent attention with no positional term and no query compression, ONE
head at a time:

    q_i = (W_q u)_i (nope | rope channels);  [c_kv | k_r] = W_kva u
    [k_nope_i | v_i] = (W_kvb RMSNorm(c_kv))_i
    s_i = (q_nope_i . k_nope_i + q_rope_i . k_r) / sqrt(nope + rope)   NOTHING rotated
    out = W_o [softmax_causal(s_i) v_i]_i

Expert layer, as a DENSE loop over the experts held here (another algorithm
than sorting pairs and grouping rows), the router's choice by RANK:

    s = sigmoid(W_g u) over ALL experts;   chosen = the k largest of s + b
    (expert e is chosen where fewer than k others score higher)
    w_e = scaling * s_e / (sum over ALL chosen of s + 1e-20), zero where not chosen
    y = sum_{e held} w_e SwiGLU_e(u) + SwiGLU_shared(u)

What the experts held elsewhere would add is left out, as in the program.
After every optimizer step (``balance``, as ``reference_moe.balance``): ``b_e``
goes down by the speed where the step sent expert ``e`` more pairs than the
mean expert, up where fewer, and the layer's ``tokens_per_expert`` takes the
step's count.

Departures from a literal transcription, each only so that it fits: blocks,
heads (of KDA a group of ``HEAD_GROUP``: the group's own columns of every
projection, the groups' outputs summed), query blocks, stretches of the recurrence and rows of the
experts and of the loss are rematerialised and mapped one at a time; every query block is
scored against ALL keys under a dense causal mask, so no (n, n) table is ever
whole; the rank is taken a block of tokens at a time. ``_mm``/``_act`` with
their fp8 control, ``clip_by_global_norm`` and ``adam_update`` are IMPORTED
from ``reference.py``, not written again.

``mode``: ``f32`` is the reference; ``fp8`` is the CONTROL, both operands of
every matmul in float8_e4m3 under a per-tensor scale (the nearest precision
below the bfloat16 the configuration states). The router's scores and the
recurrence stay float32 in both. ``gate='mean'``, another control: each
head's per-channel ``g_t`` is replaced by its mean over the channels, the
scalar decay of a Gated DeltaNet.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .reference import HIGHEST, NEG, _act, _mm, adam_update, clip_by_global_norm  # noqa: F401

STRETCH = 64        # positions of the recurrence rematerialised together
HEAD_GROUP = 4      # KDA heads whose streams and recurrence are live at once
UNROLL = 8          # positions of the recurrence one loop iteration runs, in order
QUERY_BLOCK = 1024  # query rows whose scores against every key are live at once
RANK_BLOCK = 256    # tokens whose (experts, experts) comparisons are live at once
ROW_BLOCK = 2048    # rows of the experts' and the loss's products live at once


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


# ------------------------------------------------------------ KDA


def kda_recurrence(q, k, v, g, beta):
    """q, k, g: (n, h, d_k); v: (n, h, d_v); beta: (n, h). -> (n, h, d_v).
    The recurrence itself, position by position, the decay a key channel."""
    n, h, dk = q.shape
    pad = -n % STRETCH
    q, k, v, g, beta = (
        jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)) for t in (q, k, v, g, beta)
    )

    def step(S, inp):
        q, k, v, g, beta = inp
        S = jnp.exp(g)[:, :, None] * S
        u = beta[:, None] * (v - jnp.sum(S * k[:, :, None], axis=1))
        S = S + k[:, :, None] * u[:, None, :]
        return S, jnp.sum(S * q[:, :, None], axis=1)

    @jax.checkpoint
    def stretch(S, inps):
        return jax.lax.scan(step, S, inps, unroll=UNROLL)

    blocks = lambda t: t.reshape((-1, STRETCH) + t.shape[1:])
    _, o = jax.lax.scan(
        stretch, jnp.zeros((h, dk, v.shape[-1]), jnp.float32),
        tuple(blocks(t) for t in (q, k, v, g, beta)),
    )
    return o.reshape((-1,) + o.shape[2:])[:n]


def _kda(x, p, cfg, mode, gate):
    """x: (n, hidden) normed, one sequence. ``HEAD_GROUP`` heads at a time
    (their columns of every projection, their channels of the convolution,
    their rows of the output projection), the groups' outputs summed."""
    n, eps, lin = x.shape[0], cfg["rms_norm_eps"], cfg["linear_attn_config"]
    h, d, width = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    heads = math.gcd(h, HEAD_GROUP)
    groups = h // heads
    low_f = _mm(x, p["f_a"]["kernel"], mode)                              # (n, rank)
    low_g = _mm(x, p["g_a"]["kernel"], mode)
    beta = jax.nn.sigmoid(_mm(x, p["in_proj_b"]["kernel"], mode)).reshape(n, groups, heads)
    in_qkv = p["in_proj_qkv"]["kernel"].astype(jnp.float32).reshape(-1, 3, groups, heads * d)
    taps = p["conv"]["kernel"].astype(jnp.float32).reshape(width, 3, groups, heads * d)
    f_b = p["f_b"]["kernel"].astype(jnp.float32).reshape(-1, groups, heads * d)
    g_b = p["g_b"]["kernel"].astype(jnp.float32).reshape(-1, groups, heads * d)
    dt_bias = p["dt_bias"].astype(jnp.float32).reshape(groups, heads, d)
    a_log = p["A_log"].astype(jnp.float32).reshape(groups, heads)
    out = p["out_proj"]["kernel"].astype(jnp.float32).reshape(groups, heads * d, -1)

    @jax.checkpoint
    def group(y, i):
        qkv = _mm(x, in_qkv[:, :, i].reshape(-1, 3 * heads * d), mode)    # (n, 3 heads d): q | k | v
        padded = jnp.pad(qkv, ((width - 1, 0), (0, 0)))
        w = taps[:, :, i].reshape(width, 3 * heads * d)
        qkv = jax.nn.silu(sum(padded[j : j + n] * w[j] for j in range(width))).reshape(n, 3, heads, d)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        g = -jnp.exp(a_log[i])[:, None] * jax.nn.softplus(
            _mm(low_f, f_b[:, i], mode).reshape(n, heads, d) + dt_bias[i]
        )
        if gate == "mean":
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        o = kda_recurrence(_l2(q) * d**-0.5, _l2(k), v, g, beta[:, i])              # (n, heads, d)
        o = _rms_norm(o, p["norm_scale"], eps) * jax.nn.sigmoid(
            _mm(low_g, g_b[:, i], mode).reshape(n, heads, d)
        )
        return y + _mm(o.reshape(n, heads * d), out[i], mode), None

    y, _ = jax.lax.scan(group, jnp.zeros((n, out.shape[-1]), jnp.float32), jnp.arange(groups))
    return y


# ------------------------------------------------------------ latent attention


def _mla(x, p, cfg, mode):
    """x: (n, hidden) normed, one sequence."""
    n, eps = x.shape[0], cfg["rms_norm_eps"]
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    q = _mm(x, p["to_q"]["kernel"], mode).reshape(n, h, dn + dr)
    kv_a = _mm(x, p["to_kv_a"]["kernel"], mode)
    c_kv, k_rope = kv_a[:, : cfg["kv_lora_rank"]], kv_a[:, cfg["kv_lora_rank"] :]
    kv = _mm(_rms_norm(c_kv, p["kv_norm"]["scale"], eps), p["to_kv_b"]["kernel"], mode)
    kv = kv.reshape(n, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    block = math.gcd(n, QUERY_BLOCK)
    keys = jnp.arange(n)
    scale = (dn + dr) ** -0.5

    def head(i):
        @jax.checkpoint
        def rows(b):
            at = b * block + jnp.arange(block)
            qb = jax.lax.dynamic_slice_in_dim(q[:, i], b * block, block)
            scores = _matmul(qb[:, :dn], k_nope[:, i].T, mode) + _matmul(qb[:, dn:], k_rope.T, mode)
            visible = at[:, None] >= keys[None, :]
            attn = jax.nn.softmax(jnp.where(visible, scores * scale, NEG), axis=-1)
            return _matmul(attn, v[:, i], mode)

        return jax.lax.map(rows, jnp.arange(n // block)).reshape(n, dv)

    out = jax.lax.map(jax.checkpoint(head), jnp.arange(h)).transpose(1, 0, 2)   # (n, h, dv)
    return _mm(out.reshape(n, h * dv), p["to_out"]["kernel"], mode)


# ------------------------------------------------------------ the expert layer


def held_range(cfg: dict) -> tuple:
    lo, hi = cfg["experts_held"]["range"] if "experts_held" in cfg else (0, cfg["num_experts"])
    return int(lo), int(hi)


def expert_weights(x, p, cfg):
    """(n, ALL experts): every token's weight for every expert, zero where
    the token did not choose it. Float32 in every mode, the choice by RANK a
    block of tokens at a time."""
    k, n = cfg["num_experts_per_token"], x.shape[0]
    scores = jax.nn.sigmoid(
        jnp.matmul(x, p["gate"]["kernel"].astype(jnp.float32), precision=HIGHEST)
    )
    bias = p["e_score_correction_bias"].astype(jnp.float32)

    def ranked(block):
        biased = block + bias
        above = jnp.sum(biased[:, None, :] > biased[:, :, None], axis=-1)   # others ranked higher
        picked = jnp.where(above < k, block, 0.0)
        return cfg["routed_scaling_factor"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

    size = math.gcd(n, RANK_BLOCK)
    return jax.lax.map(ranked, scores.reshape(n // size, size, -1)).reshape(scores.shape)


def _experts(x, p, cfg, mode):
    """x: (n, hidden) normed. -> (the layer's output, the (token, expert)
    pairs sent to each of ALL experts)."""
    lo, hi = held_range(cfg)
    everywhere = expert_weights(x, p, cfg)
    weights = everywhere[:, lo:hi]                                      # (n, held)
    w_in, w_out = p["experts_in"].astype(jnp.float32), p["experts_out"].astype(jnp.float32)
    shared = p["shared"]

    @jax.checkpoint
    def rows(inp):
        x, weights = inp

        def one(y, held):
            w_in, w_out, w = held
            return y + w[:, None] * _swiglu(x, w_in, w_out, mode), None

        y = jax.lax.scan(one, jnp.zeros_like(x), (w_in, w_out, weights.T))[0]
        return y + _swiglu(x, shared["Dense_0"]["kernel"], shared["Dense_1"]["kernel"], mode)

    size = math.gcd(x.shape[0], ROW_BLOCK)
    blocks = lambda t: t.reshape((-1, size) + t.shape[1:])
    y = jax.lax.map(rows, (blocks(x), blocks(weights))).reshape(x.shape)
    return y, jnp.sum(everywhere > 0, axis=0)


# ---------------------------------------------------------------- the model


def layer_kind(cfg: dict, i: int) -> str:
    """``kda`` or ``mla``: layer ``i`` (0-based) by the 1-BASED lists."""
    lin = cfg["linear_attn_config"]
    if i + 1 in lin["kda_layers"]:
        return "kda"
    assert i + 1 in lin["full_attn_layers"], (i, lin)
    return "mla"


def _block(x, pm, pf, cfg, mode, kind: str, experts: bool, gate: str):
    eps = cfg["rms_norm_eps"]
    u = _rms_norm(x, pm["norm"]["scale"], eps)
    x = x + (_kda(u, pm["fn"], cfg, mode, gate) if kind == "kda" else _mla(u, pm["fn"], cfg, mode))
    y = _rms_norm(x, pf["norm"]["scale"], eps)
    if experts:
        out, load = _experts(y, pf["fn"], cfg, mode)
        return x + out, load
    dense = pf["fn"]
    return x + _swiglu(y, dense["Dense_0"]["kernel"], dense["Dense_1"]["kernel"], mode), None


def hidden(params, cfg: dict, ids, mode: str = "f32", gate: str = "channel"):
    """ids: (n,) of one sequence. -> (the final norm's output (n, hidden),
    {an expert layer's path in the tree: the pairs it sent each of ALL
    experts})."""
    x = params["tok_emb"]["embedding"].astype(jnp.float32)[ids]
    blocks, loads = params["transformer"], {}
    for i in range(cfg["num_hidden_layers"]):
        experts = i >= cfg["first_k_dense_replace"]
        x, load = jax.checkpoint(_block, static_argnums=(3, 4, 5, 6, 7))(
            x, blocks[f"mixer_{i}"], blocks[f"ff_{i}"], cfg, mode, layer_kind(cfg, i), experts, gate
        )
        if experts:
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
         gate: str = "channel"):
    """ids: (b, n). -> (mean next-token cross-entropy over positions 0 … n-2
    of every row, {an expert layer's path: the pairs it sent each of ALL
    experts}). ``positions``: only the first that many positions of a row
    are scored (the control that leaves tokens out)."""
    total, count, sent = 0.0, 0, {}
    for row in ids:
        normed, load = hidden(params, cfg, row, mode, gate)
        total = total + _nll(normed[:-1][:positions], params["lm_head"], row[1:][:positions], mode)
        count += row[1:][:positions].shape[0]
        sent = {layer: sent.get(layer, 0) + x for layer, x in load.items()}
    return total / count, sent


def balance(flat_params: dict, loads: dict, speed: float) -> None:
    """After an optimizer step: ``flat_params`` ({path tuple: leaf}) gets, for
    every expert layer, the step's ``tokens_per_expert`` and its selection bias
    moved by ``speed`` against the load."""
    for layer, load in loads.items():
        path = tuple(layer.split("/"))
        load = jnp.asarray(load, jnp.float32)
        bias = flat_params[path + ("e_score_correction_bias",)]
        over, under = load > jnp.mean(load), load < jnp.mean(load)
        flat_params[path + ("e_score_correction_bias",)] = bias - speed * over + speed * under
        flat_params[path + ("tokens_per_expert",)] = load
