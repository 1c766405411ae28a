"""The plain reference of ``JoyAI-LLM-Flash`` (``model_type``
``joyai_llm_flash``: the DeepSeek-V3 family's keys): the forward pass, both
losses and (with ``reference.py``'s clip and Adam) the optimizer step of a
latent-attention / routed-expert language model in straightforward
``jax.numpy`` and float32, every matmul at ``highest``.

It follows the published ``config.json`` and the DeepSeek-V3 report, and
imports nothing of the program; it reads only parameter values that
``weights_moe.py`` drew from the seed, by the names of the program's tree.
No bias anywhere, RMSNorm eps from the config, no multipliers:

    h  = x + MLA(RMSNorm(x));   x' = h + FF_l(RMSNorm(h))
    FF_l = SwiGLU of intermediate_size for l < first_k_dense_replace, else the expert layer
    logits = W_head RMSNorm(x_L);   L_main = mean next-token cross-entropy

``MLA`` (u the normed input), ONE head at a time from the expanded form:

    c_q = RMSNorm(W_qa u);   [q_nope_i | q_rope_i] = (W_qb c_q)_i
    [c_kv | k_rope] = W_kva u;   [k_nope_i | v_i] = (W_kvb RMSNorm(c_kv))_i
    q_rope_i and the one k_rope rotated by position over ADJACENT pairs
    (rope_interleave), angle = position * theta^(-2j / rope_dim), in float32
    s_i = (q_nope_i . k_nope_i + q_rope_i . k_rope) / sqrt(nope + rope)
    out = W_o [softmax_causal(s_i) v_i]_i

Expert layer, as a DENSE loop over the experts held here (another algorithm
than sorting pairs and grouping rows): every token's weight for the expert,
or zero, times that expert's SwiGLU of EVERY token:

    s = sigmoid(W_g u) over ALL experts;   chosen = the per_tok largest of s + b
    (by rank: expert e is chosen where fewer than per_tok others score higher)
    w_e = scaling * s_e / (sum over ALL chosen of s + 1e-20), zero where not chosen
    y = sum_{e held} w_e SwiGLU_e(u) + SwiGLU_shared(u)

What the experts held elsewhere would add is left out, as in the program.

After every optimizer step (``balance``; ``topk_method`` ``noaux_tc``, report
section 2.1.2; the speed is no key of the config: ``bias_update_speed`` of the
configuration file): ``b_e`` goes down by the speed where the step sent expert
``e`` more pairs than the mean expert, up where fewer, and the layer's
``tokens_per_expert`` takes the step's count.

Multi-token prediction (report section 2.2; ``assumed.mtp`` of the
configuration file): ``h'_i = W_eh [RMSNorm(x_L,i) ; RMSNorm(E[t_{i+1}])]``,
one more block of the expert kind, a final RMSNorm of its own, the same head;
``L_mtp`` = cross-entropy of ``t_{i+2}`` over positions 0 … n-3; ``loss =
L_main + lambda L_mtp``. The last position's ``t_{i+1}`` wraps to ``t_0`` and
is not scored (causal attention keeps it from the others).

Departures from a literal transcription: blocks and heads are rematerialised
and mapped one at a time, only so that the float32 gradient fits one chip.
``_mm``/``_act`` with their fp8 control, ``clip_by_global_norm`` and
``adam_update`` are IMPORTED from ``reference.py``, not written again.

``mode``: ``f32`` is the reference; ``fp8`` is the CONTROL, both operands of
every matmul in float8_e4m3 under a per-tensor scale (the nearest precision
below the bfloat16 the configuration states). The router's scores and the
rotary angles stay float32 in both.
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


def _swiglu(x, w_in, w_out, mode):
    a, b = jnp.split(_mm(x, w_in, mode), 2, axis=-1)
    return _mm(jax.nn.silu(a) * b, w_out, mode)


def _rotate(x, theta: float):
    """x: (n, ..., rot) at positions 0 … n-1: channel pair (2j, 2j + 1) turned
    by ``position * theta ** (-2j / rot)``."""
    n, rot = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs[None, :]     # (n, rot / 2)
    angle = angle.reshape((n,) + (1,) * (x.ndim - 2) + (rot // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    turned = jnp.stack((even * cos - odd * sin, odd * cos + even * sin), axis=-1)
    return turned.reshape(x.shape)


def _mla(x, p, cfg, mode):
    """x: (n, hidden), one sequence."""
    n, eps = x.shape[0], cfg["rms_norm_eps"]
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    theta = float(cfg["rope_theta"])
    c_q = _rms_norm(_mm(x, p["to_q_a"]["kernel"], mode), p["q_norm"]["scale"], eps)
    q = _mm(c_q, p["to_q_b"]["kernel"], mode).reshape(n, h, dn + dr)
    kv_a = _mm(x, p["to_kv_a"]["kernel"], mode)
    c_kv, k_rope = kv_a[:, : cfg["kv_lora_rank"]], kv_a[:, cfg["kv_lora_rank"] :]
    kv = _mm(_rms_norm(c_kv, p["kv_norm"]["scale"], eps), p["to_kv_b"]["kernel"], mode)
    kv = kv.reshape(n, h, dn + dv)
    q_nope, q_rope = q[..., :dn], _rotate(q[..., dn:], theta)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k_rope = _rotate(k_rope, theta)                       # (n, dr): ONE key for all heads
    causal = jnp.tril(jnp.ones((n, n), bool))
    scale = (dn + dr) ** -0.5

    @jax.checkpoint
    def head(i):
        scores = _matmul(q_nope[:, i], k_nope[:, i].T, mode) + _matmul(q_rope[:, i], k_rope.T, mode)
        attn = jax.nn.softmax(jnp.where(causal, scores * scale, NEG), axis=-1)
        return _matmul(attn, v[:, i], mode)

    out = jax.lax.map(head, jnp.arange(h))                # (h, n, dv)
    return _mm(out.transpose(1, 0, 2).reshape(n, h * dv), p["to_out"]["kernel"], mode)


def held_range(cfg: dict) -> tuple:
    lo, hi = cfg["experts_held"]["range"] if "experts_held" in cfg else (0, cfg["n_routed_experts"])
    return int(lo), int(hi)


def expert_weights(x, p, cfg):
    """(n, ALL experts): every token's weight for every expert, zero where
    the token did not choose it. Float32 in every mode."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(
        jnp.matmul(x, p["gate"]["kernel"].astype(jnp.float32), precision=HIGHEST)
    )
    biased = scores + p["e_score_correction_bias"].astype(jnp.float32)
    above = jnp.sum(biased[:, None, :] > biased[:, :, None], axis=-1)     # others ranked higher
    chosen = above < k
    picked = jnp.where(chosen, scores, 0.0)
    return cfg["routed_scaling_factor"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20
    )


def _experts(x, p, cfg, mode):
    """x: (n, hidden). -> (the layer's output, the (token, expert) pairs
    sent to each of ALL experts)."""
    lo, hi = held_range(cfg)
    everywhere = expert_weights(x, p, cfg)
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
    y = y + _swiglu(x, shared["Dense_0"]["kernel"], shared["Dense_1"]["kernel"], mode)
    return y, jnp.sum(everywhere > 0, axis=0)


def _block(x, pm, pf, cfg, mode, experts: bool):
    eps = cfg["rms_norm_eps"]
    x = x + _mla(_rms_norm(x, pm["norm"]["scale"], eps), pm["fn"], cfg, mode)
    y = _rms_norm(x, pf["norm"]["scale"], eps)
    if experts:
        out, load = _experts(y, pf["fn"], cfg, mode)
        return x + out, load
    dense = pf["fn"]
    return x + _swiglu(y, dense["Dense_0"]["kernel"], dense["Dense_1"]["kernel"], mode), None


def _nll(rows, head, labels, mode):
    logits = _mm(rows, head.T, mode)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0])


def row_losses(params, cfg: dict, ids, mode: str = "f32", positions: int | None = None):
    """ids: (n,) of one sequence. -> (summed next-token loss, its count,
    summed MTP loss, its count, {an expert layer's path in the tree: the pairs
    it sent each of ALL experts})."""
    eps = cfg["rms_norm_eps"]
    table = params["tok_emb"]["embedding"].astype(jnp.float32)
    head = params["lm_head"]
    blocks, loads = params["transformer"], {}
    x = table[ids]
    for i in range(cfg["num_hidden_layers"]):
        experts = i >= cfg["first_k_dense_replace"]
        x, load = jax.checkpoint(_block, static_argnums=(3, 4, 5))(
            x, blocks[f"mixer_{i}"], blocks[f"ff_{i}"], cfg, mode, experts
        )
        if experts:
            loads[f"transformer/ff_{i}/fn"] = load
    normed = _rms_norm(x, params["final_norm"]["scale"], eps)
    main = _nll(normed[:-1][:positions], head, ids[1:][:positions], mode)

    mtp = params["nextn"]
    both = jnp.concatenate((
        _rms_norm(x, mtp["hnorm"]["scale"], eps),
        _rms_norm(table[jnp.roll(ids, -1)], mtp["enorm"]["scale"], eps),
    ), axis=-1)
    deeper, loads["nextn/block/ff_0/fn"] = jax.checkpoint(_block, static_argnums=(3, 4, 5))(
        _mm(both, mtp["eh_proj"]["kernel"], mode),
        mtp["block"]["mixer_0"], mtp["block"]["ff_0"], cfg, mode, True,
    )
    deeper = _rms_norm(deeper, mtp["final_norm"]["scale"], eps)
    second = _nll(deeper[:-2][:positions], head, ids[2:][:positions], mode)
    n_main, n_second = ids[1:][:positions].shape[0], ids[2:][:positions].shape[0]
    return main, n_main, second, n_second, loads


def loss(params, cfg: dict, ids, mode: str = "f32", positions: int | None = None):
    """ids: (b, n). -> (mean next-token cross-entropy over positions 0 … n-2
    of every row + ``mtp_loss_weight`` x the MTP module's over 0 … n-3,
    {an expert layer's path: the pairs it sent each of ALL experts}).
    ``positions``: only the first that many positions of a row are scored (the
    planted fault of a loss that leaves tokens out)."""
    main = second = 0.0
    n_main = n_second = 0
    loads = {}
    for row in ids:
        a, na, b, nb, sent = row_losses(params, cfg, row, mode, positions)
        main, n_main, second, n_second = main + a, n_main + na, second + b, n_second + nb
        loads = {layer: loads.get(layer, 0) + load for layer, load in sent.items()}
    return main / n_main + cfg["mtp_loss_weight"] * second / n_second, loads


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
