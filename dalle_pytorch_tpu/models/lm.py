"""A causal language model over the shared ``Transformer`` trunk.

``CausalLM`` is what a text-only decoder needs around the trunk: a token
embedding with its multiplier, the trunk with the block variants of
``models/transformer.py`` (per-layer mixers from ``layer_types``, RMSNorm
with a fixed residual multiplier, SwiGLU), a final RMSNorm, and a head TIED
to the embedding with its logit scale. ``from_config`` reads the keys a
published ``config.json`` of the ``granitemoehybrid`` family uses
(``hidden_size``, ``layer_types``, ``mamba_*``, ``*_multiplier``, …; the first
``num_hidden_layers`` entries of ``layer_types`` run), so a
configuration file is the source's own keys and nothing is renamed.

Training and whole-sequence evaluation only; serving a stack with
recurrent-state layers is ROADMAP R13.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..ops.layers import RMSNorm
from .transformer import Transformer

Dtype = Any

# rows of the sequence whose logits are live at once in the loss
HEAD_BLOCK_ROWS = 2048


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
    remat: bool = False
    use_flash: bool = True
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @classmethod
    def from_config(cls, cfg: dict, seq_len: int, **overrides) -> "CausalLM":
        """``cfg``: the source's ``config.json`` keys. What this module cannot
        run is refused here, not ignored."""
        unsupported = {
            "num_local_experts": 0, "num_experts_per_tok": 0, "mamba_n_groups": 1,
            "attention_bias": False, "mamba_proj_bias": False, "mamba_conv_bias": True,
            "position_embedding_type": "nope", "normalization_function": "rmsnorm",
            "hidden_act": "silu", "tie_word_embeddings": True,
        }
        for key, only in unsupported.items():
            if cfg.get(key, only) != only:
                raise ValueError(f"{key}={cfg[key]!r}: only {only!r} is written here")
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        if cfg["mamba_expand"] * d != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
            raise ValueError("mamba_expand * hidden_size != mamba_n_heads * mamba_d_head")
        fields = dict(
            vocab_size=cfg["vocab_size"], dim=d, depth=cfg["num_hidden_layers"],
            seq_len=seq_len, layer_types=tuple(cfg["layer_types"][: cfg["num_hidden_layers"]]),
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
        fields.update(overrides)
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
            dtype=self.dtype, param_dtype=self.param_dtype,
        )
        self.final_norm = RMSNorm(self.norm_eps, self.param_dtype)

    def __call__(self, ids: jnp.ndarray, return_loss: bool = False):
        """ids: (b, n) token ids. Returns the logits (b, n, vocab) in float32
        or, with ``return_loss``, the mean cross-entropy of every position's
        next token (positions 0 … n-2 predict ids 1 … n-1)."""
        with jax.named_scope("embed"):
            table = self.tok_emb.embedding
            x = (jnp.take(table, ids, axis=0) * self.embedding_multiplier).astype(self.dtype)
        out = self.transformer(x)
        with jax.named_scope("head_loss"):
            normed = self.final_norm(out).astype(self.dtype)
            head = jnp.asarray(table, self.dtype)
            if not return_loss:
                return self._logits(normed, head)
            rows, labels = normed[:, :-1], ids[:, 1:]
            total = jnp.zeros((), jnp.float32)
            # a block of rows at a time, its logits recomputed in backward:
            # (n, vocab) float32 is never whole
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
