"""A causal language model over the shared ``Transformer`` trunk.

``CausalLM`` is what a text-only decoder needs around the trunk: a token
embedding with its multiplier, the trunk with the block variants of
``models/transformer.py`` (per-layer mixers from ``layer_types``, per-layer
feed-forwards from ``ff_types``, RMSNorm with a fixed residual multiplier,
SwiGLU), a final RMSNorm, and a head with its logit scale, TIED to the
embedding or a matrix of its own; with ``mtp_lambda`` a multi-token-prediction
module and its second loss.

``from_config`` reads a published ``config.json`` in its source's own keys,
nothing renamed, by ``model_type``: ``granitemoehybrid`` (``hidden_size``,
``layer_types``, ``mamba_*``, ``*_multiplier``, …; the first
``num_hidden_layers`` entries of ``layer_types`` run) and ``joyai_llm_flash``
(the DeepSeek-V3 family's keys: ``q_lora_rank``, ``kv_lora_rank``,
``qk_*_head_dim``, ``n_routed_experts``, ``first_k_dense_replace``,
``num_nextn_predict_layers``, …) and ``qwen3_next`` (``full_attention_interval``,
``linear_*``, ``partial_rotary_factor``, ``num_experts``,
``shared_expert_intermediate_size``, …: linear-attention layers with a
corrected, delta-rule state (ops/gdn.py) three to one with gated softmax
attention, softmax-routed experts with a gated shared expert and the family's
load-balance loss) and ``smallthinker`` (``sliding_window_layout``,
``rope_layout``, ``sliding_window_size``, ``moe_*``: sliding-window attention
with rotary beside global attention with no positional term, ReGLU experts
routed from the block's input, before its attention) and ``kimi_linear``
(``linear_attn_config``, ``mla_use_nope``, ``num_experts``,
``moe_router_activation_func``, …: the delta rule with a decay per key
channel (ops/kda.py) three to one with latent attention that has no
positional term and no query compression, sigmoid-routed experts with a
selection bias and a shared expert). What a key asks that is not written
here is refused, not ignored.

Training and whole-sequence evaluation only; serving a stack with
recurrent-state layers is ROADMAP R13, one with latent attention R11, and a
step that commits the MTP module's second token R12.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn
from flax import traverse_util

from ..ops.layers import RMSNorm
from ..ops.moe import balanced_bias
from .transformer import Transformer

Dtype = Any

# rows of the sequence whose logits are live at once in the loss
HEAD_BLOCK_ROWS = 2048


def next_ids(ids):
    """Token i + 1 at position i (the last position wraps)."""
    return jnp.roll(ids, -1, axis=1)


class CausalLM(nn.Module):
    vocab_size: int
    dim: int
    depth: int
    seq_len: int
    layer_types: Tuple[str, ...]
    heads: int = 32
    kv_heads: int = 8
    dim_head: int = 64
    ff_hidden: int = 8192
    attn_scale: Optional[float] = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    norm_eps: float = 1e-5
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ff_types: Optional[Tuple[str, ...]] = None
    mla_q_rank: Optional[int] = 1536
    mla_kv_rank: int = 512
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64
    mla_v_dim: int = 128
    mla_rope_theta: float = 10000.0
    mla_rotary: bool = True
    experts_total: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    experts_per_token: int = 0
    experts_hidden: int = 0
    experts_shared: int = 1
    experts_scaling: float = 1.0
    experts_scoring: str = "sigmoid"
    experts_gate_shared: bool = False
    aux_loss_coef: float = 0.0
    bias_update_speed: float = 0.0
    linattn_key_heads: int = 16
    linattn_value_heads: int = 32
    linattn_key_dim: int = 128
    linattn_value_dim: int = 128
    linattn_conv: int = 4
    attn_rotary_dim: int = 0
    attn_rope_theta: float = 10000.0
    attn_window: int = 0
    experts_activation: str = "swiglu"
    experts_route_first: bool = False
    tie_head: bool = True
    mtp_lambda: Optional[float] = None
    remat: bool = False
    use_flash: bool = True
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @classmethod
    def from_config(cls, cfg: dict, seq_len: int, **overrides) -> "CausalLM":
        """``cfg``: the source's ``config.json`` keys. What this module cannot
        run is refused here, not ignored."""
        family = cfg.get("model_type", "granitemoehybrid")
        if family not in _FAMILIES:
            raise ValueError(f"model_type={family!r}: only {sorted(_FAMILIES)} are written here")
        fields = _FAMILIES[family](cfg)
        fields.update(seq_len=seq_len, **overrides)
        return cls(**fields)

    def setup(self):
        self.tok_emb = nn.Embed(self.vocab_size, self.dim, param_dtype=self.param_dtype)
        self.transformer = Transformer(
            dim=self.dim, depth=self.depth, seq_len=self.seq_len, causal=True,
            heads=self.heads, dim_head=self.dim_head, rotary_emb=False,
            remat=self.remat, use_flash=self.use_flash,
            layer_types=self.layer_types, kv_heads=self.kv_heads,
            attn_scale=self.attn_scale, norm="rmsnorm", norm_eps=self.norm_eps,
            residual_multiplier=self.residual_multiplier, ff_act="swiglu",
            ff_hidden=self.ff_hidden, ssm_heads=self.ssm_heads,
            ssm_head_dim=self.ssm_head_dim, ssm_state=self.ssm_state,
            ssm_conv=self.ssm_conv, ssm_chunk=self.ssm_chunk,
            ff_types=self.ff_types, **self._block_sizes(),
            dtype=self.dtype, param_dtype=self.param_dtype,
        )
        self.final_norm = RMSNorm(self.norm_eps, self.param_dtype)
        if not self.tie_head:
            # (vocab, dim), as the tied table is
            self.lm_head = self.param(
                "lm_head", nn.initializers.normal(self.dim**-0.5),
                (self.vocab_size, self.dim), self.param_dtype,
            )
        if self.mtp_lambda is not None:
            # ``nextn`` and not ``mtp``: a module's name is a component of every scope
            # path beneath it, and ``mtp`` is the device scope of the module's OWN part
            self.nextn = MultiTokenPrediction(
                dim=self.dim, seq_len=self.seq_len, heads=self.heads, ff_hidden=self.ff_hidden,
                norm_eps=self.norm_eps, residual_multiplier=self.residual_multiplier,
                remat=self.remat, use_flash=self.use_flash, block_sizes=self._block_sizes(),
                dtype=self.dtype, param_dtype=self.param_dtype,
            )

    def _block_sizes(self) -> Dict[str, Any]:
        """The mixers' and the expert layer's sizes the trunk and the MTP
        module's one block share."""
        names = (
            "mla_q_rank", "mla_kv_rank", "mla_nope_dim", "mla_rope_dim", "mla_v_dim",
            "mla_rope_theta", "mla_rotary", "experts_total", "experts_held", "experts_per_token",
            "experts_hidden", "experts_shared", "experts_scaling", "experts_scoring",
            "experts_gate_shared", "linattn_key_heads", "linattn_value_heads",
            "linattn_key_dim", "linattn_value_dim", "linattn_conv", "attn_rotary_dim",
            "attn_rope_theta", "attn_window", "experts_activation", "experts_route_first",
        )
        return {name: getattr(self, name) for name in names}

    def __call__(self, ids: jnp.ndarray, return_loss: bool = False):
        """ids: (b, n) token ids. Returns the logits (b, n, vocab) in float32
        or, with ``return_loss``, the mean cross-entropy of every position's
        next token (positions 0 … n-2 predict ids 1 … n-1), plus
        ``mtp_lambda`` times the MTP module's (positions 0 … n-3 predict ids
        2 … n-1) where there is one."""
        with jax.named_scope("embed"):
            table = self.tok_emb.embedding
            x = (jnp.take(table, ids, axis=0) * self.embedding_multiplier).astype(self.dtype)
        out = self.transformer(x)
        with jax.named_scope("head_loss"):
            normed = self.final_norm(out).astype(self.dtype)
            head = jnp.asarray(table if self.tie_head else self.lm_head, self.dtype)
            result = (
                self._nll(normed[:, :-1], head, ids[:, 1:]) if return_loss
                else self._logits(normed, head)
            )
        if self.mtp_lambda is None or not (return_loss or self.is_initializing()):
            return result
        # outside ``head_loss``: the module's block has scopes of its own
        deeper = self._mtp_rows(out, table, ids)
        if not return_loss:
            return result                  # ``init`` made the module's parameters too
        with jax.named_scope("head_loss"):
            return result + self.mtp_lambda * self._nll(deeper[:, :-2], head, ids[:, 2:])

    def _mtp_rows(self, out, table, ids):
        """Position i: the trunk's output there and the embedding of token
        i + 1, for token i + 2; the last position wraps and is not scored."""
        with jax.named_scope("mtp"):
            emb = jnp.take(table, next_ids(ids), axis=0) * self.embedding_multiplier
        return self.nextn(out, emb.astype(self.dtype))

    def _nll(self, rows, head, labels):
        """Mean cross-entropy of ``labels`` under ``rows``' logits, a block of
        rows at a time, its logits recomputed in backward: (n, vocab) float32
        is never whole."""
        total = jnp.zeros((), jnp.float32)
        block_nll = jax.checkpoint(self._block_nll)
        for lo in range(0, rows.shape[1], HEAD_BLOCK_ROWS):
            sl = slice(lo, lo + HEAD_BLOCK_ROWS)
            total = total + block_nll(rows[:, sl], head, labels[:, sl])
        return total / labels.size

    def _logits(self, normed, head):
        logits = jnp.einsum("bnd,vd->bnv", normed, head, preferred_element_type=jnp.float32)
        return logits / self.logits_scaling

    def _block_nll(self, rows, head, labels):
        logits = self._logits(rows, head)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - picked)

    def loss_and_loads(self, params, ids: jnp.ndarray):
        """(the loss, what every expert layer sowed: the pairs it sent each of
        ALL experts and, from a softmax router, each expert's mean
        probability): a train step's ``loss_fn`` beside ``balance``. With
        ``aux_loss_coef`` the loss is the cross-entropy plus that many times
        the family's load-balance term (``aux_loss``); ``__call__`` alone
        returns the cross-entropy."""
        loss, sown = self.apply(
            {"params": params}, ids, return_loss=True, mutable=["moe_stats"]
        )
        if self.aux_loss_coef:
            loads, probs = _sown(sown["moe_stats"])
            loss = loss + self.aux_loss_coef * self.aux_loss(
                [loads[layer] for layer in sorted(probs)], [probs[layer] for layer in sorted(probs)]
            )
        return loss, sown["moe_stats"]

    def aux_loss(self, loads, probs):
        """``E sum_e f_e P_e`` over the expert layers TOGETHER (the family's
        code concatenates their tokens): ``f_e`` the share of (layer, token)s
        that chose expert ``e`` among their ``k`` (not divided by ``k``: a
        uniform router reads ``k``), ``P_e`` the mean probability the router
        gave it. ``loads``: a layer's pairs sent each expert, ``probs``: its
        mean probability, each a sequence of (experts,) over the layers
        (every layer sees every token). The gradient reaches the routers
        through ``P`` only."""
        load = jnp.sum(jnp.stack(loads), axis=0).astype(jnp.float32)
        share = load * self.experts_per_token / jnp.maximum(jnp.sum(load), 1.0)   # 0 before a step
        mean = jnp.mean(jnp.stack(probs).astype(jnp.float32), axis=0)
        return self.experts_total * jnp.sum(share * mean)

    def balance(self, params, sown):
        """A train step's ``after_update`` (``parallel/step.py``): every
        expert layer's (the MTP module's included) ``tokens_per_expert`` takes
        the pairs the step sent each expert; where the layer has a selection
        bias it moves by ``bias_update_speed`` against them
        (``ops/moe.py:balanced_bias``), and where its router is a softmax
        ``router_prob`` takes the mean probability the router gave each
        expert. No leaf the optimizer trains is touched."""
        flat = traverse_util.flatten_dict(params)
        loads, probs = _sown(sown)
        for layer, load in loads.items():
            if layer + ("e_score_correction_bias",) in flat:
                flat[layer + ("e_score_correction_bias",)] = balanced_bias(
                    flat[layer + ("e_score_correction_bias",)], load, self.bias_update_speed
                )
            buffer = flat[layer + ("tokens_per_expert",)]
            flat[layer + ("tokens_per_expert",)] = load.astype(buffer.dtype)
            if layer in probs:
                flat[layer + ("router_prob",)] = probs[layer].astype(buffer.dtype)
        return traverse_util.unflatten_dict(flat)

    def routing_stats(self, params) -> Dict[str, jnp.ndarray]:
        """What the last step sent the expert layers, read from their
        ``tokens_per_expert``: ``moe.pairs_here``, the (token, expert) pairs
        routed to experts held here, summed over the layers,
        ``moe.load_max_over_mean``, the fullest expert's pairs over the mean
        expert's, the worst layer's, over ALL experts, and where the routers
        keep ``router_prob``, ``moe.aux_loss``, the last step's load-balance
        term (``aux_loss``). {} for a model without expert layers."""
        flat = traverse_util.flatten_dict(params)
        loads = [flat[path] for path in sorted(flat) if path[-1] == "tokens_per_expert"]
        if not loads:
            return {}
        loads = jnp.stack(loads).astype(jnp.float32)                # (layers, experts)
        lo, hi = self.experts_held or (0, self.experts_total)
        stats = {
            "moe.pairs_here": jnp.sum(loads[:, lo:hi]).astype(jnp.int32),
            "moe.load_max_over_mean": jnp.max(
                jnp.max(loads, axis=1) / jnp.maximum(jnp.mean(loads, axis=1), 1.0)
            ),
        }
        probs = [flat[path] for path in sorted(flat) if path[-1] == "router_prob"]
        if probs:
            stats["moe.aux_loss"] = self.aux_loss(loads, probs)
        return stats


class MultiTokenPrediction(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3 report, section 2.2):

        h'_i = W_eh [RMSNorm(x_i) ; RMSNorm(E[t_{i+1}])]     (2 dim -> dim)

    then one more block of the expert kind over ``h'`` and a final RMSNorm of
    its own; the caller applies the model's own embedding and head. The
    projection, the norms and the shifted embedding run under the device scope
    ``mtp``; the block under ``attn.mla`` and ``moe`` like any other."""

    dim: int
    seq_len: int
    heads: int
    ff_hidden: int
    norm_eps: float
    residual_multiplier: float
    block_sizes: Dict[str, Any]
    remat: bool = False
    use_flash: bool = True
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, hidden, shifted_emb):
        with jax.named_scope("mtp"):
            norm = lambda name, t: RMSNorm(self.norm_eps, self.param_dtype, name=name)(t)
            both = jnp.concatenate(
                (norm("hnorm", hidden), norm("enorm", shifted_emb)), axis=-1
            ).astype(self.dtype)
            x = nn.Dense(
                self.dim, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype,
                name="eh_proj",
            )(both)
        x = Transformer(
            dim=self.dim, depth=1, seq_len=self.seq_len, causal=True, heads=self.heads,
            rotary_emb=False, remat=self.remat, use_flash=self.use_flash,
            layer_types=("mla",), ff_types=("experts",), norm="rmsnorm",
            norm_eps=self.norm_eps, residual_multiplier=self.residual_multiplier,
            ff_act="swiglu", ff_hidden=self.ff_hidden, **self.block_sizes,
            dtype=self.dtype, param_dtype=self.param_dtype, name="block",
        )(x)
        with jax.named_scope("mtp"):
            return norm("final_norm", x).astype(self.dtype)


def _sown(moe_stats) -> tuple:
    """What the expert layers sowed, by layer path: ({layer: the pairs sent
    each of ALL experts}, {layer: each expert's mean probability}; the
    second only from softmax routers)."""
    loads, probs = {}, {}
    for path, (value,) in traverse_util.flatten_dict(moe_stats).items():
        {"load": loads, "prob": probs}[path[-1]][path[:-1]] = value
    return loads, probs


# ------------------------------------------------------------- the families


def _refuse(cfg: dict, only: dict) -> None:
    for key, value in only.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"{key}={cfg[key]!r}: only {value!r} is written here")


def _share(cfg: dict, count_key: str) -> tuple:
    """(lo, hi, total) of ``experts_held`` (``{"range": [lo, hi], "of":
    total}``: this program is one chip's share of an expert-parallel
    deployment, ``count_key`` then counts the experts HELD, ``hi - lo``, and
    the router scores ``total``); all are held where the key is absent."""
    held = cfg[count_key]
    share = cfg.get("experts_held", {"range": (0, held), "of": held})
    (lo, hi), total = share["range"], share["of"]
    if hi - lo != held or not 0 <= lo < hi <= total:
        raise ValueError(f"experts_held={share} is not {held} of its experts")
    return int(lo), int(hi), total


def _granite_fields(cfg: dict) -> dict:
    _refuse(cfg, {
        "num_local_experts": 0, "num_experts_per_tok": 0, "mamba_n_groups": 1,
        "attention_bias": False, "mamba_proj_bias": False, "mamba_conv_bias": True,
        "position_embedding_type": "nope", "normalization_function": "rmsnorm",
        "hidden_act": "silu", "tie_word_embeddings": True,
    })
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["mamba_expand"] * d != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("mamba_expand * hidden_size != mamba_n_heads * mamba_d_head")
    return dict(
        vocab_size=cfg["vocab_size"], dim=d, depth=cfg["num_hidden_layers"],
        layer_types=tuple(cfg["layer_types"][: cfg["num_hidden_layers"]]),
        heads=h, kv_heads=cfg["num_key_value_heads"], dim_head=d // h,
        ff_hidden=cfg["shared_intermediate_size"],
        attn_scale=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"], norm_eps=cfg["rms_norm_eps"],
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_conv=cfg["mamba_d_conv"],
        ssm_chunk=cfg["mamba_chunk_size"],
    )


def _joyai_fields(cfg: dict) -> dict:
    """``joyai_llm_flash``: the DeepSeek-V3 family's keys, and three the
    source does not have, each with a default: ``experts_held`` (``{"range":
    [lo, hi], "of": total}``: this program is one chip's share of an
    expert-parallel deployment, ``n_routed_experts`` then counts the experts
    HELD, ``hi - lo``, and the router scores ``total``; default: all are held),
    ``mtp_loss_weight`` (0.3) and ``bias_update_speed`` (0.001), the
    DeepSeek-V3 report's values for most of its pre-training."""
    _refuse(cfg, {
        "rope_scaling": None, "n_group": 1, "topk_group": 1, "attention_bias": False,
        "moe_layer_freq": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "hidden_act": "silu", "tie_word_embeddings": False,
        "rope_interleave": True, "num_nextn_predict_layers": 1,
        "num_key_value_heads": cfg["num_attention_heads"],
        "qk_head_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
    })
    depth, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    lo, hi, total = _share(cfg, "n_routed_experts")
    return dict(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], depth=depth,
        layer_types=("mla",) * depth,
        ff_types=("dense",) * min(dense, depth) + ("experts",) * max(depth - dense, 0),
        heads=cfg["num_attention_heads"], ff_hidden=cfg["intermediate_size"],
        norm_eps=cfg["rms_norm_eps"],
        mla_q_rank=cfg["q_lora_rank"], mla_kv_rank=cfg["kv_lora_rank"],
        mla_nope_dim=cfg["qk_nope_head_dim"], mla_rope_dim=cfg["qk_rope_head_dim"],
        mla_v_dim=cfg["v_head_dim"], mla_rope_theta=float(cfg["rope_theta"]),
        experts_total=total, experts_held=(lo, hi),
        experts_per_token=cfg["num_experts_per_tok"],
        experts_hidden=cfg["moe_intermediate_size"],
        experts_shared=cfg["n_shared_experts"],
        experts_scaling=float(cfg["routed_scaling_factor"]),
        bias_update_speed=float(cfg.get("bias_update_speed", 0.001)),
        tie_head=False, mtp_lambda=float(cfg.get("mtp_loss_weight", 0.3)),
    )


def _qwen3_next_fields(cfg: dict) -> dict:
    """``qwen3_next``: the source's own keys, and two it does not have, each
    with a default: ``experts_held`` (as ``_joyai_fields``: ``num_experts``
    then counts the experts HELD and the router scores ``of``) and
    ``router_aux_loss_coef`` (0.001, the family's published default). Layer
    ``l`` is ``full_attention`` where ``(l + 1) % full_attention_interval ==
    0`` and ``linear_attention`` elsewhere (or as ``layer_types`` says, where
    the file has them); every feed-forward is the expert layer, so
    ``intermediate_size`` is read by nothing."""
    _refuse(cfg, {
        "mlp_only_layers": [], "decoder_sparse_step": 1, "rope_scaling": None,
        "use_sliding_window": False, "norm_topk_prob": True, "hidden_act": "silu",
        "tie_word_embeddings": False, "attention_bias": False,
    })
    depth, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    kinds = cfg.get("layer_types") or [
        "full_attention" if (l + 1) % every == 0 else "linear_attention" for l in range(depth)
    ]
    lo, hi, total = _share(cfg, "num_experts")
    shared, width = cfg["shared_expert_intermediate_size"], cfg["moe_intermediate_size"]
    if shared % width:
        raise ValueError(f"shared_expert_intermediate_size={shared} is not whole experts of {width}")
    rotary = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    return dict(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], depth=depth,
        layer_types=tuple(kinds[:depth]), ff_types=("experts",) * depth,
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        dim_head=cfg["head_dim"], ff_hidden=cfg["intermediate_size"],
        norm_eps=cfg["rms_norm_eps"], attn_rotary_dim=rotary,
        attn_rope_theta=float(cfg["rope_theta"]),
        linattn_key_heads=cfg["linear_num_key_heads"],
        linattn_value_heads=cfg["linear_num_value_heads"],
        linattn_key_dim=cfg["linear_key_head_dim"],
        linattn_value_dim=cfg["linear_value_head_dim"],
        linattn_conv=cfg["linear_conv_kernel_dim"],
        experts_total=total, experts_held=(lo, hi),
        experts_per_token=cfg["num_experts_per_tok"], experts_hidden=width,
        experts_shared=shared // width, experts_scoring="softmax", experts_gate_shared=True,
        aux_loss_coef=float(cfg.get("router_aux_loss_coef", 0.001)), tie_head=False,
    )


def _smallthinker_fields(cfg: dict) -> dict:
    """``smallthinker``: the source's own keys, and ``experts_held`` (as
    ``_joyai_fields``: ``moe_num_primary_experts`` then counts the experts
    HELD and the router scores ``of``). Layer ``l`` is a window layer
    (``sliding_attention``: rotary over the whole head at ``rope_theta``, a
    window of ``sliding_window_size`` keys) where both layouts hold 1 at
    ``l``, a global one (``attention``: no positional term, every earlier
    key) where both hold 0. Every feed-forward is the expert layer: softmax
    over all experts, the top ``moe_num_active_primary_experts`` with their
    weights normalised over the chosen, ReGLU experts, no shared expert, the
    router reading the block's normed input before the attention."""
    _refuse(cfg, {
        "rope_scaling": None, "norm_topk_prob": True, "moe_primary_router_apply_softmax": True,
        "tie_word_embeddings": False,
    })
    depth = cfg["num_hidden_layers"]
    windows, ropes = cfg["sliding_window_layout"], cfg["rope_layout"]
    for key, layout in (("sliding_window_layout", windows), ("rope_layout", ropes)):
        if len(layout) != depth or set(layout) - {0, 1}:
            raise ValueError(f"{key}={layout}: {depth} entries of 0 or 1 are written here")
    if windows != ropes:
        raise ValueError(
            f"sliding_window_layout={windows} against rope_layout={ropes}: only window layers "
            "with rotary and global layers without it are written here"
        )
    lo, hi, total = _share(cfg, "moe_num_primary_experts")
    return dict(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], depth=depth,
        layer_types=tuple("sliding_attention" if w else "attention" for w in windows),
        ff_types=("experts",) * depth, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], dim_head=cfg["head_dim"],
        norm_eps=cfg["rms_norm_eps"], attn_rotary_dim=cfg["head_dim"],
        attn_rope_theta=float(cfg["rope_theta"]), attn_window=cfg["sliding_window_size"],
        experts_total=total, experts_held=(lo, hi),
        experts_per_token=cfg["moe_num_active_primary_experts"],
        experts_hidden=cfg["moe_ffn_hidden_size"], experts_shared=0,
        experts_scoring="softmax", experts_activation="reglu", experts_route_first=True,
        tie_head=False,
    )


def _kimi_linear_fields(cfg: dict) -> dict:
    """``kimi_linear``: the source's own keys, and two it does not have, each
    with a default: ``experts_held`` (as ``_joyai_fields``: ``num_experts``
    then counts the experts HELD and the router scores ``of``) and
    ``bias_update_speed`` (0.001, as ``_joyai_fields``). ``linear_attn_config``
    names the layers by 1-BASED number: layer ``l`` is ``kda`` where ``l + 1``
    is among its ``kda_layers``, latent attention where among its
    ``full_attn_layers``; each of the ``num_hidden_layers`` is in exactly one.
    The first ``first_k_dense_replace`` feed-forwards are the dense SwiGLU of
    ``intermediate_size``, every later one the expert layer: sigmoid scores
    over all experts, the top ``num_experts_per_token`` of the scores plus the
    selection bias, their weights normalised over the chosen and times
    ``routed_scaling_factor``, ``num_shared_experts`` shared experts of
    ``moe_intermediate_size``. The latent attention has no positional term
    (``mla_use_nope``) and no query compression (``q_lora_rank`` null)."""
    _refuse(cfg, {
        "rope_scaling": None, "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
        "moe_router_activation_func": "sigmoid", "moe_renormalize": True, "hidden_act": "silu",
        "tie_word_embeddings": False, "q_lora_rank": None, "mla_use_nope": True,
        "num_nextn_predict_layers": 0, "num_key_value_heads": cfg["num_attention_heads"],
    })
    depth, linear = cfg["num_hidden_layers"], cfg["linear_attn_config"]
    kda, full = set(linear["kda_layers"]), set(linear["full_attn_layers"])
    if kda & full or kda | full != set(range(1, depth + 1)):
        raise ValueError(
            f"linear_attn_config kda_layers={sorted(kda)}, full_attn_layers={sorted(full)}: "
            f"each of the layers 1..{depth} (1-based) in exactly one is written here"
        )
    dense = cfg["first_k_dense_replace"]
    lo, hi, total = _share(cfg, "num_experts")
    return dict(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], depth=depth,
        layer_types=tuple("kda" if l + 1 in kda else "mla" for l in range(depth)),
        ff_types=("dense",) * min(dense, depth) + ("experts",) * max(depth - dense, 0),
        heads=cfg["num_attention_heads"], ff_hidden=cfg["intermediate_size"],
        norm_eps=cfg["rms_norm_eps"],
        mla_q_rank=None, mla_kv_rank=cfg["kv_lora_rank"],
        mla_nope_dim=cfg["qk_nope_head_dim"], mla_rope_dim=cfg["qk_rope_head_dim"],
        mla_v_dim=cfg["v_head_dim"], mla_rotary=False,
        linattn_key_heads=linear["num_heads"], linattn_key_dim=linear["head_dim"],
        linattn_conv=linear["short_conv_kernel_size"],
        experts_total=total, experts_held=(lo, hi),
        experts_per_token=cfg["num_experts_per_token"],
        experts_hidden=cfg["moe_intermediate_size"],
        experts_shared=cfg["num_shared_experts"],
        experts_scaling=float(cfg["routed_scaling_factor"]),
        bias_update_speed=float(cfg.get("bias_update_speed", 0.001)), tie_head=False,
    )


_FAMILIES = {
    "granitemoehybrid": _granite_fields, "joyai_llm_flash": _joyai_fields,
    "qwen3_next": _qwen3_next_fields, "smallthinker": _smallthinker_fields,
    "kimi_linear": _kimi_linear_fields,
}
