"""Transformer composition, TPU-native.

Mirrors the reference's ``Transformer`` capability surface
(transformer.py:130-227): per-layer attention types cycled from
``attn_types`` (full / axial_row / axial_col / conv_like / sparse / mlp),
LayerScale(PreNorm(...)) stacking with depth-dependent init, optional token
shift, optional reversible or rematerialized execution, and the DALL-E 3-part
rotary table — but built as a functional JAX stack: static shapes throughout,
one compiled graph, explicit PRNG keys, and a decode mode that threads KV /
shift caches for O(1)-per-token sampling.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

import contextlib
import functools

from ..ops import kv_policy
from ..ops.attention import GatedAttention, GroupedKVAttention, LatentAttention, PatternAttention
from ..ops.flash_attention import KERNEL_RESIDUAL_NAMES, StaticTable
from ..ops.layers import (
    FeedForward,
    GMLPBlock,
    LayerScale,
    PreNorm,
    PreRMSNorm,
    PreShiftToken,
    RMSNorm,
    SwiGLU,
)
from ..ops.moe import (
    MOE_RESIDUAL_NAMES, MoEFeedForward, RoutedExperts, checkpointed_block, router_logits,
    router_scores,
)
from ..ops.reversible import reversible_forward_only, reversible_sequence
from ..ops.rotary import angles, dalle_rotary_table, lang_freqs
from ..ops.gdn import DELTA_RESIDUAL_NAMES, GatedDeltaNet
from ..ops.kda import KimiDeltaAttention
from ..ops.ssm import MambaMixer

Dtype = Any

ATTENTION_TYPES = ("full", "axial_row", "axial_col", "conv_like", "sparse", "mlp")
# ``layer_types``: mixers that take no pattern, mask or positional table.
# layer type -> (layer kind, device scope)
MIXER_TYPES = {
    "mamba": ("mamba", "ssm"), "attention": ("gqa", "attn.gqa"), "mla": ("mla", "attn.mla"),
    "linear_attention": ("gdn", "linattn"), "full_attention": ("gated", "attn.gated"),
    "sliding_attention": ("swa", "attn.swa"), "kda": ("kda", "linattn"),
}
# ``ff_types``: a layer_types stack's feed-forward kind, layer by layer.
# kind -> device scope
FF_TYPES = {"dense": "ff", "experts": "moe"}


def cast_tuple(val, depth: int = 1) -> tuple:
    if isinstance(val, list):
        val = tuple(val)
    return val if isinstance(val, tuple) else (val,) * depth


@functools.lru_cache(maxsize=None)
def _interned_rotary(data: bytes, shape: tuple) -> StaticTable:
    """Content-interned StaticTable: setup() runs on every init/apply, and
    the fused attention kernel hashes tables by id — interning keeps the
    id stable across traces so nothing retraces or recompiles."""
    return StaticTable(np.frombuffer(data, dtype=np.float32).reshape(shape))


class PreRoutedRMSNorm(nn.Module):
    """The mixer half-block of a block whose expert layer is routed from the
    block's INPUT (``experts_route_first``): ``u = RMSNorm(x)`` feeds the
    router first and then the mixer,

        p = softmax(W_r u)            float32, under ``moe`` / ``moe.router``, named
                                      ``moe_router`` (a block's checkpoint keeps it)
        -> (multiplier * fn(u), p)    the norm and the mixer under ``mixer_scope``

    and the trunk hands ``p`` to the block's expert layer
    (ops/moe.py:RoutedExperts). It sets its own device scopes, so that the
    router's operations count with the expert layer's and under no
    ``attn.*``; the router reads the norm's float32 output."""

    fn: nn.Module
    experts_total: int
    mixer_scope: str
    eps: float = 1e-5
    multiplier: float = 1.0
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, **kwargs):
        with jax.named_scope(self.mixer_scope):
            u = RMSNorm(self.eps, self.param_dtype, name="norm")(x)
        with jax.named_scope("moe"), jax.named_scope("moe.router"):
            rows = u.reshape(-1, u.shape[-1])
            probs = router_scores(router_logits(rows, self.experts_total, self.param_dtype), "softmax")
        with jax.named_scope(self.mixer_scope):
            out = self.fn(u.astype(x.dtype), **kwargs)
        return (out if self.multiplier == 1.0 else out * self.multiplier), probs


def _block_checkpoint(fn, block: str = ""):
    """The one ``jax.checkpoint`` the trunk puts around a block under
    ``remat``: everything is rebuilt in backward but the attention kernels'
    own residuals (``KERNEL_RESIDUAL_NAMES``: quadratic in the row length to
    rebuild, linear to keep), so the rebuilt forward holds no flash kernel —
    all of its results are in memory and XLA drops the call — an expert
    layer's routing, dispatched rows and first grouped product
    (``MOE_RESIDUAL_NAMES``), so its rebuilt forward runs the second product
    alone, and the delta rules' ``T | P`` table (``DELTA_RESIDUAL_NAMES``), so
    a rebuilt gated-delta-rule or KDA layer runs no state-free kernel. A block
    without those layers holds none of the names. An expert layer traced
    inside records route ``remat/moe_residuals`` for ``block``, a delta rule's
    kernels route ``remat/delta_tables``."""
    kv_policy.record_route("remat/attn_residuals", "saved")

    def traced(*args):
        with checkpointed_block(block):
            return fn(*args)

    return jax.checkpoint(
        traced, policy=jax.checkpoint_policies.save_only_these_names(
            *KERNEL_RESIDUAL_NAMES, *MOE_RESIDUAL_NAMES, *DELTA_RESIDUAL_NAMES)
    )


class Transformer(nn.Module):
    """Depth-wise composition of attention + GEGLU feed-forward blocks.

    ``seq_len`` is the model sequence length (text_seq + image_seq for DALL-E;
    the encoder length for CLIP). When ``image_fmap_size`` is set, the
    internal attention pattern length is seq_len + 1 (<bos> included), exactly
    like the reference's internal padding (attention.py:121-124).

    Execution modes: sequential (default), ``reversible=True`` (O(1)
    activation memory via ops/reversible.py), or ``remat=True``
    (``_block_checkpoint`` per block — recompute in backward, standard pytree
    activations; only the flash kernels' output and log-sum-exp, an expert
    layer's routing, rows and first grouped product and the delta rules'
    ``T | P`` table are kept).

    Block variants (models/lm.py's causal language models; every DALL-E and
    CLIP configuration leaves them at their defaults, which are the block
    above): ``layer_types`` gives each layer its mixer in place of
    ``attn_types`` — ``mamba`` (ops/ssm.py:MambaMixer, sized by ``ssm_*``) or
    ``attention`` (grouped-KV causal attention over ``kv_heads`` with the
    softmax scale ``attn_scale``, no positional term) or ``mla`` (latent
    attention, ops/attention.py:LatentAttention, sized by ``mla_*``: its own
    rotary key, nothing of ``rotary_emb``) or ``linear_attention`` (the gated
    delta rule, ops/gdn.py:GatedDeltaNet, sized by ``linattn_*``) or ``kda``
    (the delta rule with a decay per key channel, ops/kda.py:KimiDeltaAttention,
    sized by ``kda_*``, scope ``linattn``) or
    ``full_attention`` (ops/attention.py:GatedAttention: grouped-KV attention
    with per-head norms, rotary over ``attn_rotary_dim`` channels and an
    output gate) or ``sliding_attention`` (GroupedKVAttention with rotary
    over ``attn_rotary_dim`` channels at ``attn_rope_theta`` and a sliding
    window of ``attn_window`` keys, scope ``attn.swa``); ``ff_types`` gives each layer of
    such a stack its feed-forward, ``dense`` (the SwiGLU of ``ff_hidden``) or
    ``experts`` (ops/moe.py:RoutedExperts, sized by ``experts_*``, under the
    device scope ``moe``; ``experts_activation`` ``reglu`` for ReGLU experts;
    ``experts_route_first`` routes it from the block's normed INPUT before
    the mixer, ``PreRoutedRMSNorm``); ``norm='rmsnorm'``
    with a fixed ``residual_multiplier`` replaces LayerNorm + learned
    LayerScale; ``ff_act='swiglu'`` with ``ff_hidden`` replaces the GEGLU
    feed-forward. Such a stack trains and evaluates whole sequences; it has
    no decode mode and no pipeline or sequence-parallel path yet.
    """

    dim: int
    depth: int
    seq_len: int
    reversible: bool = False
    causal: bool = True
    heads: int = 8
    dim_head: int = 64
    ff_mult: float = 4
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    attn_types: Optional[Tuple[str, ...]] = None
    image_fmap_size: Optional[int] = None
    stable: bool = False
    shift_tokens: bool = False
    # extra token-shift ring rows — speculative-decode rollback slack
    # (ops/layers.py:PreShiftToken.pad); 0 for every non-speculative model
    shift_pad: int = 0
    rotary_emb: bool = True
    remat: bool = False
    sparse_layout_seed: int = 0
    use_flash: bool = True
    sp_axis: Optional[str] = None
    pp_axis: Optional[str] = None
    pp_microbatches: int = 4
    ff_experts: int = 0
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    quant: bool = False
    layer_types: Optional[Tuple[str, ...]] = None
    kv_heads: Optional[int] = None
    attn_scale: Optional[float] = None
    norm: str = "layernorm"
    norm_eps: float = 1e-5
    residual_multiplier: Optional[float] = None
    ff_act: str = "geglu"
    ff_hidden: Optional[int] = None
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    mla_q_rank: Optional[int] = 1536
    mla_kv_rank: int = 512
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64
    mla_v_dim: int = 128
    mla_rope_theta: float = 10000.0
    mla_rotary: bool = True
    ff_types: Optional[Tuple[str, ...]] = None
    experts_total: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    experts_per_token: int = 0
    experts_hidden: int = 0
    experts_shared: int = 1
    experts_scaling: float = 1.0
    experts_scoring: str = "sigmoid"
    experts_gate_shared: bool = False
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
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def _attn_seq_len(self) -> int:
        return self.seq_len + 1 if self.image_fmap_size is not None else self.seq_len

    def rotary_table(self) -> Optional[np.ndarray]:
        if not self.rotary_emb:
            return None
        if self.image_fmap_size is not None:
            img_seq_len = self.image_fmap_size**2
            text_len = self.seq_len - img_seq_len + 1
            table = dalle_rotary_table(self.dim_head, text_len, self.image_fmap_size)
        else:
            # plain 1-D rotary fallback (no image grid present)
            table = angles(
                np.arange(self.seq_len), lang_freqs(self.dim_head // 2)
            ).astype(np.float32)
        # zero-pad the angle table to the full head dim: zero angle = identity
        # rotation for the channels the reference leaves untouched, and a
        # full-width table lets apply_rotary_emb stay purely elementwise
        # (measured ~6 ms/step of XLA layout copies at the flagship config)
        pad = self.dim_head - table.shape[-1]
        if pad > 0:
            table = np.pad(table, ((0, 0), (0, pad)))
        return table

    def setup(self):
        attn_types = cast_tuple(self.attn_types or ("full",))
        for t in attn_types:
            if t not in ATTENTION_TYPES:
                raise ValueError(f'attention type "{t}" is not valid')
        if self.rotary_emb and "mlp" in attn_types:
            raise ValueError("gMLP layers cannot be combined with rotary embeddings")
        if self.sp_axis is not None and "mlp" in attn_types:
            raise ValueError(
                "gMLP spatial gating mixes the whole sequence locally and "
                "cannot run sequence-parallel; drop 'mlp' from attn_types "
                "or disable sp"
            )
        if self.ff_experts > 0 and self.moe_every <= 0:
            raise ValueError(
                f"moe_every must be >= 1 (every n-th FF becomes an expert "
                f"layer); got {self.moe_every}"
            )
        self._check_variants()
        attn_blocks, ff_blocks, kinds, scopes, ff_scopes, routed = [], [], [], [], [], []
        for ind in range(self.depth):
            attn_type = attn_types[ind % len(attn_types)]
            scope = f"attn.{attn_type}"
            if self.layer_types is not None:
                attn_type, scope = MIXER_TYPES[self.layer_types[ind]]
                attn = self._mixer(attn_type)
            elif attn_type == "mlp":
                attn = GMLPBlock(
                    dim=self.dim,
                    dim_ff=self.dim * 4,
                    seq_len=self.seq_len,
                    causal=self.causal,
                    dtype=self.dtype,
                    param_dtype=self.param_dtype,
                )
            else:
                attn = PatternAttention(
                    dim=self.dim,
                    seq_len=self._attn_seq_len(),
                    attn_type=attn_type,
                    causal=self.causal,
                    heads=self.heads,
                    dim_head=self.dim_head,
                    dropout=self.attn_dropout,
                    stable=self.stable,
                    image_fmap_size=self.image_fmap_size,
                    layout_seed=self.sparse_layout_seed + ind,
                    use_flash=self.use_flash,
                    sp_axis=self.sp_axis,
                    quant=self.quant,
                    dtype=self.dtype,
                    param_dtype=self.param_dtype,
                )
            ff_scope = FF_TYPES[self.ff_types[ind] if self.ff_types else "dense"]
            if ff_scope == "moe":
                ff = RoutedExperts(
                    dim=self.dim, hidden=self.experts_hidden,
                    experts_total=self.experts_total,
                    experts_held=tuple(self.experts_held or (0, self.experts_total)),
                    per_token=self.experts_per_token, shared=self.experts_shared,
                    scaling=self.experts_scaling, scoring=self.experts_scoring,
                    gate_shared=self.experts_gate_shared, activation=self.experts_activation,
                    dtype=self.dtype, param_dtype=self.param_dtype,
                )
            elif self.ff_act == "swiglu":
                ff = SwiGLU(
                    dim=self.dim,
                    hidden=self.ff_hidden or int(self.dim * self.ff_mult),
                    dtype=self.dtype,
                    param_dtype=self.param_dtype,
                )
            elif self.ff_experts > 0 and ind % self.moe_every == self.moe_every - 1:
                # GShard-style: every moe_every-th FF becomes a Switch-routed
                # expert layer (ops/moe.py); experts shard over the ep axis
                ff = MoEFeedForward(
                    dim=self.dim,
                    num_experts=self.ff_experts,
                    mult=self.ff_mult,
                    capacity_factor=self.moe_capacity_factor,
                    dropout=self.ff_dropout,
                    dtype=self.dtype,
                    param_dtype=self.param_dtype,
                )
            else:
                ff = FeedForward(
                    dim=self.dim,
                    mult=self.ff_mult,
                    dropout=self.ff_dropout,
                    quant=self.quant,
                    dtype=self.dtype,
                    param_dtype=self.param_dtype,
                )

            if self.shift_tokens:
                assert self.image_fmap_size is not None
                attn = PreShiftToken(
                    fn=attn,
                    image_size=self.image_fmap_size,
                    seq_len=self.seq_len,
                    pass_decode=True,
                    pad=self.shift_pad,
                )
                ff = PreShiftToken(
                    fn=ff, image_size=self.image_fmap_size,
                    seq_len=self.seq_len, pad=self.shift_pad,
                )

            routed_first = self.experts_route_first and ff_scope == "moe"
            if routed_first:
                attn = PreRoutedRMSNorm(
                    fn=attn, experts_total=self.experts_total, mixer_scope=scope, eps=self.norm_eps,
                    multiplier=self.residual_multiplier, param_dtype=self.param_dtype,
                    name=self._mixer_name(ind),
                )
            else:
                attn = self._half_block(attn, ind, self._mixer_name(ind))
            attn_blocks.append(attn)
            ff_blocks.append(self._half_block(ff, ind, f"ff_{ind}"))
            kinds.append(attn_type)
            scopes.append(scope)
            ff_scopes.append(ff_scope)
            routed.append(routed_first)

        self.attn_blocks = attn_blocks
        self.ff_blocks = ff_blocks
        self.layer_kinds = tuple(kinds)
        self.layer_scopes = tuple(scopes)
        self.ff_scopes = tuple(ff_scopes)
        # layers whose mixer half-block also returns the expert layer's routing
        self.routed_first = tuple(routed)

    def _mixer_name(self, ind: int) -> str:
        return f"attn_{ind}" if self.layer_types is None else f"mixer_{ind}"

    def _check_variants(self):
        if self.norm not in ("layernorm", "rmsnorm") or self.ff_act not in ("geglu", "swiglu"):
            raise ValueError(f"norm {self.norm!r} / ff_act {self.ff_act!r} is not valid")
        if (self.norm == "rmsnorm") != (self.residual_multiplier is not None):
            raise ValueError(
                "norm='rmsnorm' comes with a fixed residual_multiplier, "
                "'layernorm' with the learned LayerScale (residual_multiplier=None)"
            )
        if self.ff_act == "swiglu" and self.ff_experts > 0:
            raise ValueError("the expert feed-forward is GEGLU only")
        if self.layer_types is None:
            if self.ff_types is not None:
                raise ValueError("ff_types comes with layer_types")
            return
        if self.ff_types is not None and (
            len(self.ff_types) != self.depth or set(self.ff_types) - set(FF_TYPES)
            or self.ff_act != "swiglu"
        ):
            raise ValueError(
                f"ff_types needs {self.depth} entries of {sorted(FF_TYPES)} over "
                f"ff_act='swiglu'; got {self.ff_types} over {self.ff_act!r}"
            )
        bad = [t for t in self.layer_types if t not in MIXER_TYPES]
        if bad or len(self.layer_types) != self.depth:
            raise ValueError(
                f"layer_types needs {self.depth} entries of {sorted(MIXER_TYPES)}; "
                f"got {self.layer_types}"
            )
        if self.shift_tokens or self.reversible or self.sp_axis or self.pp_axis:
            raise ValueError(
                "layer_types stacks run sequentially or under remat only: no "
                "token shift, reversible, sequence- or pipeline-parallel path"
            )
        if self.experts_route_first and (self.norm != "rmsnorm" or self.experts_scoring != "softmax"):
            raise ValueError("routing from the block's input needs rmsnorm and a softmax router")

    def _mixer(self, kind: str) -> nn.Module:
        if kind == "mamba":
            return MambaMixer(
                dim=self.dim, n_heads=self.ssm_heads, d_head=self.ssm_head_dim,
                d_state=self.ssm_state, d_conv=self.ssm_conv, chunk=self.ssm_chunk,
                eps=self.norm_eps, dtype=self.dtype, param_dtype=self.param_dtype,
            )
        if kind == "mla":
            return LatentAttention(
                dim=self.dim, heads=self.heads, q_rank=self.mla_q_rank,
                kv_rank=self.mla_kv_rank, nope_dim=self.mla_nope_dim,
                rope_dim=self.mla_rope_dim, v_dim=self.mla_v_dim,
                rope_theta=self.mla_rope_theta, rotary=self.mla_rotary, eps=self.norm_eps,
                use_flash=self.use_flash, dtype=self.dtype, param_dtype=self.param_dtype,
            )
        if kind == "gdn":
            return GatedDeltaNet(
                dim=self.dim, key_heads=self.linattn_key_heads,
                value_heads=self.linattn_value_heads, key_dim=self.linattn_key_dim,
                value_dim=self.linattn_value_dim, conv=self.linattn_conv,
                eps=self.norm_eps, dtype=self.dtype, param_dtype=self.param_dtype,
            )
        if kind == "kda":
            return KimiDeltaAttention(
                dim=self.dim, heads=self.linattn_key_heads, head_dim=self.linattn_key_dim,
                conv=self.linattn_conv, eps=self.norm_eps, dtype=self.dtype,
                param_dtype=self.param_dtype,
            )
        if kind == "swa":
            return GroupedKVAttention(
                dim=self.dim, heads=self.heads, kv_heads=self.kv_heads or self.heads,
                dim_head=self.dim_head,
                sm_scale=self.dim_head**-0.5 if self.attn_scale is None else self.attn_scale,
                rotary_dim=self.attn_rotary_dim, rope_theta=self.attn_rope_theta,
                window=self.attn_window or None,
                use_flash=self.use_flash, dtype=self.dtype, param_dtype=self.param_dtype,
            )
        if kind == "gated":
            return GatedAttention(
                dim=self.dim, heads=self.heads, kv_heads=self.kv_heads or self.heads,
                dim_head=self.dim_head, rotary_dim=self.attn_rotary_dim,
                rope_theta=self.attn_rope_theta, eps=self.norm_eps,
                use_flash=self.use_flash, dtype=self.dtype, param_dtype=self.param_dtype,
            )
        return GroupedKVAttention(
            dim=self.dim, heads=self.heads, kv_heads=self.kv_heads or self.heads,
            dim_head=self.dim_head,
            sm_scale=self.dim_head**-0.5 if self.attn_scale is None else self.attn_scale,
            use_flash=self.use_flash, dtype=self.dtype, param_dtype=self.param_dtype,
        )

    def _half_block(self, fn: nn.Module, ind: int, name: str) -> nn.Module:
        """The residual branch around a mixer or a feed-forward."""
        if self.norm == "rmsnorm":
            return PreRMSNorm(
                fn=fn, eps=self.norm_eps, multiplier=self.residual_multiplier,
                param_dtype=self.param_dtype, name=name,
            )
        return LayerScale(
            dim=self.dim,
            depth=ind + 1,
            fn=PreNorm(dim=self.dim, fn=fn, param_dtype=self.param_dtype),
            param_dtype=self.param_dtype,
            name=name,
        )

    # ------------------------------------------------------------------ call

    def _block_kwargs(self, ind: int, mask, rot, deterministic, decode,
                      block_len=None, block_start=None):
        """(attn kwargs, ff kwargs) for layer ``ind`` in module-call form."""
        kind = self.layer_kinds[ind]
        if self.layer_types is not None:
            if decode:
                raise NotImplementedError(
                    "a layer_types stack has no decode mode (ROADMAP R9, R13)"
                )
            return dict(deterministic=deterministic), dict(deterministic=deterministic)
        akw: dict = dict(deterministic=deterministic, decode=decode)
        if kind != "mlp":
            akw.update(mask=mask, rotary_pos_emb=rot)
            if block_len is not None:
                akw["block_len"] = block_len
            if block_start is not None:
                akw["block_start"] = block_start
        fkw: dict = dict(deterministic=deterministic)
        if self.shift_tokens:
            fkw.update(decode=decode)
            if block_len is not None:
                # the FF-side PreShiftToken consumes block_len for its own
                # ragged ring advance (it never forwards it to the FF)
                fkw["block_len"] = block_len
            if block_start is not None:
                fkw["block_start"] = block_start
        return akw, fkw

    def __call__(
        self,
        x: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        deterministic: bool = True,
        decode: bool = False,
        block_len: Optional[jnp.ndarray] = None,
        block_start: Optional[jnp.ndarray] = None,
        depth_limit: Optional[int] = None,
    ) -> jnp.ndarray:
        rot_np = self.rotary_table()
        # a content-interned StaticTable, not a traced array: the attention
        # layer materializes it for the unfused/decode paths and consumes it
        # statically in the fused kernel — one source of truth for both
        rot = (
            _interned_rotary(rot_np.astype(np.float32).tobytes(), rot_np.shape)
            if rot_np is not None else None
        )

        if (
            self.pp_axis is not None
            and not decode
            and not self.is_initializing()
        ):
            from ..parallel.context import axis_extent

            if axis_extent(self.pp_axis) > 1:
                return self._pp_forward(x, mask, rot, deterministic)

        sequential = (
            self.is_initializing()
            or decode
            or (not self.reversible and not self.remat)
        )
        # depth_limit (static): run only the first L layers — the
        # early-exit self-draft pass of speculative decoding
        # (serving/engine.py). Decode-mode only: training/prefill always
        # runs the full stack. None (every non-speculative caller) is the
        # full depth.
        depth_eff = (
            self.depth if depth_limit is None
            else min(max(int(depth_limit), 1), self.depth)
        )

        # device-trace scopes (utils/telemetry_names.py DEVICE_SCOPES):
        # ``attn.<attn_type>`` names the layer KIND, not the kernel that runs
        # it, so a reader of a profiler trace keeps finding the same work
        # after a reroute; the Flax module path (``attn_<ind>``) stays beneath
        if sequential and not self.reversible:
            for ind in range(depth_eff):
                akw, fkw = self._block_kwargs(
                    ind, mask, rot, deterministic, decode, block_len,
                    block_start,
                )
                if self.routed_first[ind]:
                    d, probs = self.attn_blocks[ind](x, **akw)
                    fkw = dict(fkw, probs=probs)
                    with jax.named_scope(self.layer_scopes[ind]):
                        x = x + d
                else:
                    with jax.named_scope(self.layer_scopes[ind]):
                        x = x + self.attn_blocks[ind](x, **akw)
                with jax.named_scope(self.ff_scopes[ind]):
                    x = x + self.ff_blocks[ind](x, **fkw)
            return x

        if self.reversible and (self.is_initializing() or decode):
            # reversible wiring, run directly (no custom VJP needed)
            x1, x2 = x, x
            for ind in range(depth_eff):
                akw, fkw = self._block_kwargs(
                    ind, mask, rot, deterministic, decode, block_len,
                    block_start,
                )
                with jax.named_scope(self.layer_scopes[ind]):
                    x1 = x1 + self.attn_blocks[ind](x2, **akw)
                with jax.named_scope("ff"):
                    x2 = x2 + self.ff_blocks[ind](x1, **fkw)
            return (x1 + x2) / 2

        # pure-function paths: remat or reversible training. Block closures
        # return (delta, aux); the Switch load-balance loss rides the aux
        # channel (re-sown below) so MoE composes with O(1)-memory execution.
        fns, params, kwargs = self._pure_blocks(mask, rot, deterministic)

        if self.remat and not self.reversible:
            aux = jnp.zeros((), jnp.float32)
            for ind, ((f, g), (pf, pg), (kwf, kwg)) in enumerate(zip(fns, params, kwargs)):
                d, a = _block_checkpoint(f, "/".join((*self.path, self._mixer_name(ind))))(pf, x, kwf)
                if self.routed_first[ind]:
                    d, probs = d
                    kwg = dict(kwg, probs=probs)
                x = x + d
                dg, ag = _block_checkpoint(g, "/".join((*self.path, f"ff_{ind}")))(pg, x, kwg)
                x = x + dg
                if isinstance(ag, tuple):
                    # what an expert layer sowed (``moe_stats``: the pairs it
                    # sent each expert), put where the layer itself would have
                    ag, stats = ag
                    if self.is_mutable_collection("moe_stats"):
                        self.put_variable("moe_stats", f"ff_{ind}", stats)
                aux = aux + a + ag
            if self.ff_experts > 0:
                self.sow("moe_aux", "load_balance", aux)
            return x

        out, aux = reversible_sequence(
            tuple(fns), params, jnp.concatenate((x, x), -1), kwargs
        )
        if self.ff_experts > 0:
            self.sow("moe_aux", "load_balance", aux)
        y1, y2 = jnp.split(out, 2, axis=-1)
        return (y1 + y2) / 2

    def _pp_forward(self, x, mask, rot, deterministic):
        """GPipe pipeline execution over the ``pp_axis`` mesh axis
        (parallel/pipeline.py): per-layer params are stacked and staged, the
        microbatch schedule runs as one shard_map. Requires homogeneous
        layers (uniform attn_types; 'mlp' has different params and 'sparse'
        a different mask per layer) and no reversible mode. Key-padding
        masks ride the microbatch schedule alongside the activations;
        dropout derives per-(layer, microbatch) keys with fold_in inside the
        schedule (bitwise-deterministic given the base key, though the
        draw pattern differs from the no-pp run, which draws one mask over
        the whole batch). Composes with dp/fsdp/tp — only the pp axis is
        manual in the shard_map; tensor-parallel layers shard via GSPMD
        inside the stage (sp cannot nest: ring attention opens its own
        shard_map)."""
        from jax.sharding import PartitionSpec as P

        from ..parallel.context import active_mesh, axis_extent, batch_axes
        from ..parallel.pipeline import gpipe, stack_layer_params

        kinds = set(self.layer_kinds)
        if len(kinds) != 1 or kinds & {"mlp", "sparse"}:
            raise ValueError(
                f"pipeline parallelism needs one uniform attention type "
                f"(not mlp/sparse, whose layers are heterogeneous); got "
                f"{self.attn_types}"
            )
        if self.ff_experts > 0 and self.moe_every != 1:
            raise ValueError(
                "pipeline parallelism requires homogeneous stages: with "
                "MoE feed-forwards every layer must be MoE (set "
                f"moe_every=1; got moe_every={self.moe_every}, whose "
                "dense/MoE alternation gives stages different param "
                "structures)"
            )
        if self.reversible:
            raise ValueError("pipeline parallelism excludes reversible mode")
        if axis_extent("sp") > 1:
            raise ValueError(
                "pp composes with dp/fsdp/tp but not sp: sequence-parallel "
                "attention opens its own shard_map, which cannot nest "
                "inside the pipeline stage"
            )

        mesh = active_mesh()
        pp = int(mesh.shape[self.pp_axis])
        assert self.depth % pp == 0, (
            f"depth {self.depth} not divisible by pp={pp}"
        )
        # Only the pp axis is manual; dp/fsdp/tp stay auto (GSPMD) inside
        # the stage body, so the microbatch split below sees the GLOBAL
        # batch and tensor-parallel layers shard transparently. The split
        # must still divide evenly across the data-parallel extent.
        dp_total = int(
            np.prod([mesh.shape[a] for a in (batch_axes(mesh) or ())])
        )
        local_b = x.shape[0] // dp_total
        # largest microbatch count that divides the per-shard batch
        n_micro = max(
            m
            for m in range(1, min(self.pp_microbatches, local_b) + 1)
            if local_b % m == 0
        )
        if n_micro < min(self.pp_microbatches, pp):
            import warnings

            warnings.warn(
                f"pipeline microbatches reduced to {n_micro} (requested "
                f"{self.pp_microbatches}; per-shard batch {local_b} has no "
                f"larger divisor) — the GPipe bubble grows accordingly; "
                f"pick a batch size divisible by dp*fsdp*microbatches"
            )

        # with_rng=False: the pipeline derives its own per-(layer, micro)
        # dropout keys below instead of _pure_blocks' per-layer draws
        fns, params, _ = self._pure_blocks(None, rot, deterministic, with_rng=False)
        attn_f, ff_f = fns[0]
        stacked = stack_layer_params(
            [{"attn": pa, "ff": pf} for pa, pf in params]
        )
        # (depth, ...) -> (pp, depth // pp, ...) so dim 0 shards over pp
        stacked = jax.tree_util.tree_map(
            lambda l: l.reshape(pp, self.depth // pp, *l.shape[1:]), stacked
        )

        needs_rng = (
            not deterministic and (self.attn_dropout > 0 or self.ff_dropout > 0)
        )
        base_key = self.make_rng("dropout") if needs_rng else None
        rot_kw = {"rot": rot} if rot is not None else {}

        def layer_fn(p, t, side, layer_idx, micro_idx, key):
            akw, fkw = dict(rot_kw), {}
            if side:
                akw["mask"] = side["mask"]
            if key is not None:
                # one deterministic draw per (layer, microbatch, attn/ff)
                lk = jax.random.fold_in(
                    jax.random.fold_in(key, layer_idx), micro_idx
                )
                akw["rng"] = jax.random.fold_in(lk, 0)
                fkw["rng"] = jax.random.fold_in(lk, 1)
            d, a1 = attn_f(p["attn"], t, akw)
            t = t + d
            d, a2 = ff_f(p["ff"], t, fkw)
            return t + d, a1 + a2

        if self.remat:
            # honor --remat inside the pipeline: recompute each layer's
            # activations in backward instead of storing them across the
            # n_micro + pp - 1 scan ticks
            layer_fn = _block_checkpoint(layer_fn)

        p_specs = jax.tree_util.tree_map(lambda _: P(self.pp_axis), stacked)
        x_spec = P()  # batch stays auto-sharded over dp/fsdp by GSPMD
        side = {"mask": mask} if mask is not None else None
        side_specs = {"mask": P()} if mask is not None else None
        key_spec = None if base_key is None else P()

        def body(p, t, s, k):
            return gpipe(
                lambda pl, tl_, sl, li, mi: layer_fn(pl, tl_, sl, li, mi, k),
                p, t,
                axis_name=self.pp_axis, n_stages=pp, n_micro=n_micro,
                side=s,
            )

        out, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(p_specs, x_spec, side_specs, key_spec),
            out_specs=(x_spec, P()),
            axis_names=frozenset({self.pp_axis}),
            check_vma=False,
        )(stacked, x, side, base_key)
        if self.ff_experts > 0:
            # per-microbatch Switch aux averaged over microbatches — a
            # consistent estimator of the sequential path's full-batch aux
            # (equal when routing statistics match across microbatches)
            self.sow("moe_aux", "load_balance", aux / n_micro)
        return out

    def _pure_blocks(self, mask, rot, deterministic, with_rng=True):
        """Unbound-apply closures + param subtrees + traced-array kwargs for
        the custom-VJP / remat execution paths. ``with_rng=False`` skips the
        per-layer dropout-key draws (the pp path folds its own keys)."""
        variables = self.variables["params"]

        needs_rng = (
            with_rng
            and not deterministic
            and (self.attn_dropout > 0 or self.ff_dropout > 0)
        )

        fns, params, kwargs = [], [], []
        for ind in range(self.depth):
            kind = self.layer_kinds[ind]
            patterned = self.layer_types is None and kind != "mlp"
            attn_mod = self.attn_blocks[ind].clone(parent=None)
            ff_mod = self.ff_blocks[ind].clone(parent=None)

            def make_fn(mod, is_attn, patterned=patterned, scope=self.layer_scopes[ind],
                        ff_scope=self.ff_scopes[ind], routed_first=self.routed_first[ind]):
                static_kwargs = dict(deterministic=deterministic)
                scope = scope if is_attn else ff_scope
                # a PreRoutedRMSNorm sets its own scopes
                scoped = (
                    contextlib.nullcontext if is_attn and routed_first
                    else functools.partial(jax.named_scope, scope)
                )

                def fn(p, t, kw):
                    call_kwargs = dict(static_kwargs)
                    if is_attn and patterned:
                        call_kwargs["mask"] = kw.get("mask")
                        call_kwargs["rotary_pos_emb"] = kw.get("rot")
                    if "probs" in kw:
                        call_kwargs["probs"] = kw["probs"]
                    rngs = {"dropout": kw["rng"]} if "rng" in kw else None
                    with scoped():
                        y, mut = mod.apply(
                            {"params": p}, t, rngs=rngs, mutable=["moe_aux", "moe_stats"],
                            **call_kwargs,
                        )
                    aux = sum(
                        jax.tree_util.tree_leaves(mut.get("moe_aux", {})),
                        jnp.zeros((), jnp.float32),
                    )
                    if "moe_stats" in mut:
                        return y, (aux, mut["moe_stats"])
                    return y, aux

                return fn

            akw: dict = {}
            if patterned:
                if mask is not None:
                    akw["mask"] = mask
                if rot is not None:
                    akw["rot"] = rot
            fkw: dict = {}
            if needs_rng:
                akw["rng"] = self.make_rng("dropout")
                fkw["rng"] = self.make_rng("dropout")

            fns.append((make_fn(attn_mod, True), make_fn(ff_mod, False)))
            params.append((variables[self._mixer_name(ind)], variables[f"ff_{ind}"]))
            kwargs.append((akw, fkw))
        return fns, params, kwargs
