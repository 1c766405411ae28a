"""Core layer primitives (flax.linen), TPU-native.

Covers the reference's transformer building blocks (transformer.py:30-126):
DivideMax, LayerScale, PreNorm, GEGLU feed-forward, and the CogView-style
token-shift wrapper. All modules take explicit compute/param dtypes so the
whole stack can run bf16 on the MXU with f32 parameters.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

Dtype = Any


def stable_softmax(t: jnp.ndarray, axis: int = -1, alpha: float = 32.0**2) -> jnp.ndarray:
    """Numerically-tamed softmax used when ``stable=True``
    (reference attention.py:27-30): divide by alpha before the max-subtraction
    so large logits don't overflow in low precision."""
    t = t / alpha
    t = t - jnp.max(t, axis=axis, keepdims=True)
    return nn.softmax(t * alpha, axis=axis)


def divide_max(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Divide by the per-slice max (reference transformer.py:30-37)."""
    return x / jnp.max(x, axis=axis, keepdims=True)


def layer_scale_init(depth: int) -> float:
    """Depth-dependent LayerScale init (reference transformer.py:40-48):
    0.1 up to depth 18, 1e-5 to 24, 1e-6 beyond."""
    if depth <= 18:
        return 0.1
    if depth <= 24:
        return 1e-5
    return 1e-6


class LayerScale(nn.Module):
    """Scale a wrapped function's output by a learned per-channel gain
    initialised small (CaiT, arXiv:2103.17239; reference transformer.py:40-54)."""

    dim: int
    depth: int
    fn: nn.Module
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, **kwargs):
        init = layer_scale_init(self.depth)
        scale = self.param(
            "scale",
            lambda key, shape: jnp.full(shape, init, dtype=self.param_dtype),
            (self.dim,),
        )
        return self.fn(x, **kwargs) * scale.astype(x.dtype)


class PreNorm(nn.Module):
    """LayerNorm then fn (reference transformer.py:58-65). The norm runs in
    f32 for stability regardless of compute dtype."""

    dim: int
    fn: nn.Module
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, **kwargs):
        y = nn.LayerNorm(dtype=jnp.float32, param_dtype=self.param_dtype)(x)
        return self.fn(y.astype(x.dtype), **kwargs)


def rms_norm(v: jnp.ndarray, gain: jnp.ndarray, eps: float) -> jnp.ndarray:
    """``gain * v / sqrt(mean(v^2) + eps)`` over the last axis, in float32."""
    v = v.astype(jnp.float32)
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True) + eps)
    return v * gain.astype(jnp.float32)


class RMSNorm(nn.Module):
    """RMSNorm with a learned gain (``scale``), float32 whatever the compute
    dtype; returns float32."""

    eps: float = 1e-5
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        gain = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype)
        return rms_norm(x, gain, self.eps)


class PreRMSNorm(nn.Module):
    """``multiplier * fn(RMSNorm(x))``: the pre-norm half-block of the
    models whose residual branches carry a fixed multiplier in place of a
    learned LayerScale."""

    fn: nn.Module
    eps: float = 1e-5
    multiplier: float = 1.0
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, **kwargs):
        y = RMSNorm(self.eps, self.param_dtype, name="norm")(x)
        out = self.fn(y.astype(x.dtype), **kwargs)
        return out if self.multiplier == 1.0 else out * self.multiplier


class QuantDense(nn.Module):
    """Weight-only int8 Dense for serving: ``y = (x @ q) * scale [+ bias]``
    with a per-output-channel symmetric scale.

    Autoregressive decode is bound by weight reads from HBM (every step
    streams every kernel once); int8 storage halves those bytes vs bf16
    (measured 1.05 -> 0.85 ms/token on the flagship config, v5e-1). The
    ``q.astype`` dequant fuses into the consuming matvec loop fusion, so
    the kernel is read from HBM as int8 and widened in registers. Params are
    produced by ``utils/quantize.py`` from a trained checkpoint — training
    through this module is unsupported (int8 params receive no meaningful
    gradients)."""

    features: int
    use_bias: bool = True
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        in_features = x.shape[-1]
        params = {
            "kernel_q": self.param(
                "kernel_q",
                lambda key, shape: jnp.zeros(shape, jnp.int8),
                (in_features, self.features),
            ),
            "scale": self.param(
                "scale",
                lambda key, shape: jnp.ones(shape, jnp.float32),
                (self.features,),
            ),
        }
        if self.use_bias:
            params["bias"] = self.param(
                "bias",
                lambda key, shape: jnp.zeros(shape, self.param_dtype),
                (self.features,),
            )
        # the full matvec IS the all-columns slice: one implementation
        # (``dense_apply_columns``) serves this module and the sliced image
        # head (models/dalle.py:_head_image), so the two cannot diverge
        return dense_apply_columns(params, x, 0, self.dtype)


def dense_apply_columns(params, x: jnp.ndarray, lo: int, dtype) -> jnp.ndarray:
    """The ``[lo:]`` output-column slice of a (Quant)Dense matvec, computed
    from the module's raw param dict — the ONE place the sliced-head
    arithmetic lives, shared between ``QuantDense.__call__``'s math and
    column-sliced consumers (models/dalle.py:_head_image). Handles both the
    int8 serving params ({kernel_q, scale}) and the full-precision
    ({kernel}) layout, bias included when present; the slice of the matvec
    is exact (column j of ``x @ W + b`` depends only on column j of W/b),
    so streaming fewer weight bytes never changes the kept outputs."""
    x = x.astype(dtype)
    if "kernel_q" in params:
        # QuantDense: int8 columns widened in-register, then the
        # per-output-channel scale
        q = jnp.asarray(params["kernel_q"])[:, lo:]
        y = (x @ q.astype(dtype)) * jnp.asarray(params["scale"])[lo:].astype(dtype)
    else:
        y = x @ jnp.asarray(params["kernel"], dtype)[:, lo:]
    if "bias" in params:
        y = y + jnp.asarray(params["bias"])[lo:].astype(dtype)
    return y


class QuantEmbed(nn.Module):
    """int8 embedding table for serving: rows are stored int8 with a
    per-row symmetric scale and dequantized after the gather, so the table
    reads from HBM at half the bf16 bytes. Params come from
    ``utils/quantize.py`` (training through this module is unsupported)."""

    num_embeddings: int
    features: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, ids):
        q = self.param(
            "embedding_q",
            lambda key, shape: jnp.zeros(shape, jnp.int8),
            (self.num_embeddings, self.features),
        )
        scale = self.param(
            "scale",
            lambda key, shape: jnp.ones(shape, jnp.float32),
            (self.num_embeddings,),
        )
        rows = jnp.take(q, ids, axis=0).astype(self.dtype)
        s = jnp.take(scale, ids, axis=0).astype(self.dtype)
        return rows * s[..., None]


def serving_embed(
    quant: bool,
    num_embeddings: int,
    features: int,
    *,
    name: Optional[str] = None,
    dtype: Dtype = jnp.float32,
    param_dtype: Dtype = jnp.float32,
) -> nn.Module:
    """``nn.Embed`` vs int8 ``QuantEmbed`` — the embedding analog of
    ``serving_dense`` (same structural-parallelism contract). param_dtype
    governs only the trainable table; the int8 twin's dtypes are fixed
    (int8 rows, f32 scales)."""
    if quant:
        return QuantEmbed(num_embeddings, features, name=name, dtype=dtype)
    return nn.Embed(num_embeddings, features, name=name, param_dtype=param_dtype)


def serving_dense(
    quant: bool,
    features: int,
    *,
    use_bias: bool = True,
    name: Optional[str] = None,
    dtype: Dtype = jnp.float32,
    param_dtype: Dtype = jnp.float32,
) -> nn.Module:
    """The one place that picks ``nn.Dense`` vs int8 ``QuantDense`` for a
    projection — every Dense-bearing module routes through it so the
    quantized and full-precision trees stay structurally parallel."""
    if quant:
        return QuantDense(
            features, use_bias=use_bias, name=name,
            dtype=dtype, param_dtype=param_dtype,
        )
    return nn.Dense(
        features, use_bias=use_bias, name=name,
        dtype=dtype, param_dtype=param_dtype,
    )


class FeedForward(nn.Module):
    """GEGLU feed-forward (reference transformer.py:69-85): one fused
    projection to 2 * mult * dim, gated gelu, projection back. The doubled
    projection keeps the MXU fed with one large matmul instead of two.
    ``quant=True`` swaps both projections for int8 ``QuantDense`` (serving
    only; see utils/quantize.py)."""

    dim: int
    mult: float = 4.0
    dropout: float = 0.0
    quant: bool = False
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        hidden = int(self.dim * self.mult)
        dense = lambda features: serving_dense(
            self.quant, features, dtype=self.dtype, param_dtype=self.param_dtype
        )
        x = dense(hidden * 2)(x)
        x, gates = jnp.split(x, 2, axis=-1)
        x = x * nn.gelu(gates)
        x = nn.Dropout(self.dropout)(x, deterministic=deterministic)
        x = dense(self.dim)(x)
        return x


class SwiGLU(nn.Module):
    """``W_out (silu(a) * b)`` with ``[a, b] = W_in x``, no bias: one fused
    projection to ``2 * hidden``, as ``FeedForward`` has it."""

    dim: int
    hidden: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        dense = lambda features: nn.Dense(
            features, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype
        )
        a, b = jnp.split(dense(self.hidden * 2)(x), 2, axis=-1)
        return dense(self.dim)(nn.silu(a) * b)


def shift_tokens(x: jnp.ndarray, text_len: int, image_size: int) -> jnp.ndarray:
    """CogView/RWKV token shift over a mixed text+image sequence
    (reference transformer.py:96-126).

    Text tokens (first ``text_len`` positions, <bos> included): the first half
    of channels is replaced by the previous token's. Image tokens (reshaped to
    an image_size x image_size grid, zero-padded to a full grid): the first
    quarter of channels comes from the token one row up, the second quarter
    from the token one column left.

    Static-shape: works on the full sequence; callers pass the model's fixed
    sequence length.
    """
    b, n, d = x.shape
    img_seq_len = image_size**2
    padding = text_len + img_seq_len - n

    x_text, x_img = x[:, :text_len], x[:, text_len:]
    x_img = jnp.pad(x_img, ((0, 0), (0, padding), (0, 0)))
    x_img = x_img.reshape(b, image_size, image_size, d)

    # text: shift half the channels right by one token
    x_text_shift, x_text_pass = jnp.split(x_text, 2, axis=-1)
    x_text_shift = jnp.pad(x_text_shift, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    x_text = jnp.concatenate((x_text_shift, x_text_pass), axis=-1)

    # image: quarter from the row above, quarter from the column left
    q = d // 4
    top, left, passthrough = x_img[..., :q], x_img[..., q : 2 * q], x_img[..., 2 * q :]
    top = jnp.pad(top, ((0, 0), (1, 0), (0, 0), (0, 0)))[:, :-1]
    left = jnp.pad(left, ((0, 0), (0, 0), (1, 0), (0, 0)))[:, :, :-1]
    x_img = jnp.concatenate((top, left, passthrough), axis=-1)

    x_img = x_img.reshape(b, img_seq_len, d)
    if padding:
        x_img = x_img[:, :-padding]
    return jnp.concatenate((x_text, x_img), axis=1)


class PreShiftToken(nn.Module):
    """Apply token shift, then the wrapped function
    (reference transformer.py:89-126).

    In decode mode a history cache of raw inputs supplies the previous-token
    and row-above features the shift needs, so KV-cached sampling stays O(1)
    per step. ``pass_decode`` controls whether the wrapped fn also receives
    the decode flag (attention does, feed-forward doesn't).

    ``pad`` widens the ring by that many EXTRA rows of history — the
    speculative-decode rollback slack (serving/engine.py): a verify block
    of k tokens advances the ring by k, but only ``accepted <= k``
    positions survive, so the next block's descriptor ``block_start`` may
    LAG the stored high-water mark by up to ``pad`` positions and every
    read it needs (prev token, row-above) must still be resident. With
    ``pad=0`` (every non-speculative model) the ring is exactly the
    original ``image_size + 1`` rows and the anchored index arithmetic
    below reduces to the unanchored offsets bit-for-bit.
    """

    fn: nn.Module
    image_size: int
    seq_len: int
    pass_decode: bool = False
    pad: int = 0

    @nn.compact
    def __call__(self, x, decode: bool = False, block_len=None,
                 block_start=None, **kwargs):
        img_seq_len = self.image_size**2
        text_len = self.seq_len - img_seq_len + 1
        inner_kwargs = dict(kwargs)
        if self.pass_decode:
            inner_kwargs["decode"] = decode
            if block_len is not None:
                inner_kwargs["block_len"] = block_len
            if block_start is not None:
                inner_kwargs["block_start"] = block_start

        if not decode:
            x = shift_tokens(x, text_len, self.image_size)
            return self.fn(x, **inner_kwargs)

        b, n, d = x.shape
        # The shift only ever looks back image_size positions (prev token and
        # row-above), so the history is a RING of the last R = image_size + 1
        # raw inputs, newest last: before consuming position pos, row j holds
        # position pos - R + j. A full-sequence (b, total, d) history was the
        # original design; its per-step updates were part of a
        # dynamic-update-slice category trace-measured at 43% of the
        # batch-8 decode program (shared with the K/V cache updates — see
        # ops/attention.py's cost notes for the split and the KV-side fix).
        # The ring is ~40x smaller, uses only STATIC slice indices, and is
        # bit-identical — every read the ring
        # cannot serve (pos 0's "previous", out-of-grid row-above) is already
        # masked to zero inside shift_tokens_decode / the prefill rule.
        R = self.image_size + 1 + self.pad
        is_init = not self.has_variable("cache", "shift_hist")
        hist = self.variable("cache", "shift_hist", jnp.zeros, (b, R, d), x.dtype)
        pos_var = self.variable("cache", "shift_index", lambda: jnp.array(0, jnp.int32))
        if is_init:
            return self.fn(x, **inner_kwargs)

        pos = pos_var.value
        if block_len is not None:
            # RAGGED block (the fused serving iteration): row b's valid
            # tokens are columns [0, block_len[b]) at positions
            # anchor[b] + j, mixing text (prefill rows) and image (decode
            # rows) — the per-position decode rules apply elementwise.
            # ``cat`` maps any position anchor[b] + t (t in [-R, n)) to
            # column R + t: prev is position p-1 (column R+j-1), the
            # row-above token p - image_size (column R+j-image_size;
            # R >= image_size + 1 keeps both indices >= 0). The ring then
            # advances PER ROW by block_len — a pure gather, bitwise
            # equal to the split paths' concatenate update at the same
            # advance (idle rows advance 0 and keep their ring intact).
            #
            # ``block_start`` anchors the block at the DESCRIPTOR's
            # position instead of the stored high-water mark: after a
            # speculative verify commits only ``accepted`` of its
            # block_len tokens (serving/engine.py), the next descriptor
            # lags the stored index by delta = pos - block_start, and
            # every ring read below the anchor shifts down by delta —
            # the per-row cache rewind, realized as index arithmetic on
            # the (pad-widened) ring rather than a device round trip.
            # The rows the over-advance polluted (positions >= anchor)
            # are never read from the ring: in-block positions gather
            # from ``x`` itself. With block_start == pos (every
            # non-speculative dispatch) delta is 0 and every index
            # below equals the unanchored form.
            assert jnp.ndim(pos) == 1, (
                "ragged blocks need a vectorized (b,) shift index "
                "(models/sampling.py:set_decode_offsets)"
            )
            jidx = jnp.arange(n, dtype=jnp.int32)
            cat = jnp.concatenate((hist.value, x), axis=1)  # (b, R+n, d)
            if block_start is None:
                anchor = pos
                delta = jnp.zeros_like(pos)
            else:
                anchor = block_start
                # idle rows (block_len 0) carry garbage descriptors; pin
                # them to delta 0 so their ring state passes through
                delta = jnp.where(
                    block_len > 0, jnp.maximum(pos - block_start, 0), 0
                )
            prev_ix = jnp.where(
                jidx[None] == 0, R - 1 - delta[:, None], R - 1 + jidx[None]
            )
            prev = jnp.take_along_axis(cat, prev_ix[..., None], axis=1)
            above_ix = (
                R - self.image_size + jidx[None]
                - jnp.where(jidx[None] >= self.image_size, 0, 1)
                * delta[:, None]
            )
            row_above = jnp.take_along_axis(
                cat, jnp.clip(above_ix, 0, R + n - 1)[..., None], axis=1
            )
            pos_bj = anchor[:, None] + jidx[None]           # (b, n)
            take = (
                jnp.arange(R, dtype=jnp.int32)[None] + block_len[:, None]
                - jnp.where(
                    jnp.arange(R, dtype=jnp.int32)[None]
                    >= R - block_len[:, None],
                    0, 1,
                ) * delta[:, None]
            )
            take = jnp.clip(take, 0, R + n - 1)
            hist.value = jnp.take_along_axis(cat, take[..., None], axis=1)
            pos_var.value = jnp.where(
                block_len > 0, anchor + block_len, pos
            )
            x = shift_tokens_decode(
                x, pos_bj, prev, row_above, text_len, self.image_size
            )
        elif n > 1:
            # prefill: a block of n text positions (n <= text_len and the
            # whole block must lie inside the text part — callers prefill the
            # prompt; pos is traced so this cannot be asserted). Only the
            # text rule applies: first half of channels from the previous
            # token — block-internal rows shift from the block itself, row 0
            # from the history (zero when the block starts the sequence).
            assert n <= text_len, "prefill blocks must stay within the text part"
            prev_first = jnp.where(pos > 0, hist.value[:, -1:], 0.0)
            prev_block = jnp.concatenate((prev_first, x[:, :-1]), axis=1)
            pos_var.value = pos + n
            hist.value = (
                x[:, n - R :]
                if n >= R
                else jnp.concatenate((hist.value[:, n:], x), axis=1)
            )
            half = d // 2
            x = jnp.concatenate((prev_block[..., :half], x[..., half:]), axis=-1)
        else:
            prev = hist.value[:, R - 1 :]  # position pos - 1
            # position pos - image_size: ring row R - image_size (== 1
            # for the unpadded ring)
            ra = R - self.image_size
            row_above = hist.value[:, ra : ra + 1]
            pos_var.value = pos + 1
            hist.value = jnp.concatenate((hist.value[:, 1:], x), axis=1)
            x = shift_tokens_decode(x, pos, prev, row_above, text_len, self.image_size)
        return self.fn(x, **inner_kwargs)


class AxialPositionalEmbedding(nn.Module):
    """Factorized 2-D learned position embedding over the image grid.

    Re-owns the external ``axial_positional_embedding`` package the reference
    pulls in (dalle_pytorch.py:7,343-344): one (rows, dim) and one (cols, dim)
    parameter whose broadcast sum covers the full grid — O(2·f·d) parameters
    instead of O(f²·d).
    """

    dim: int
    shape: tuple  # (rows, cols)
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, n: int):
        """Return the first ``n`` grid position embeddings, shape (1, n, dim)
        in param dtype (n <= rows * cols)."""
        rows, cols = self.shape
        row_emb = self.param(
            "row_emb", nn.initializers.normal(1.0), (rows, 1, self.dim), self.param_dtype
        )
        col_emb = self.param(
            "col_emb", nn.initializers.normal(1.0), (1, cols, self.dim), self.param_dtype
        )
        grid = (row_emb + col_emb).reshape(rows * cols, self.dim)
        return grid[None, :n]


class SpatialGatingUnit(nn.Module):
    """gMLP spatial gating (arXiv:2105.08050; the reference pulls this in from
    the external g-mlp-pytorch package for attn_type='mlp',
    transformer.py:13,170-178): half the channels gate the other half through
    a learned, optionally causal, seq x seq spatial mixing matrix."""

    seq_len: int
    causal: bool = True
    init_eps: float = 1e-3
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, decode: bool = False):
        n = x.shape[-2]
        res, gate = jnp.split(x, 2, axis=-1)
        gate = nn.LayerNorm(dtype=jnp.float32, param_dtype=self.param_dtype)(gate)
        gate = gate.astype(x.dtype)

        eps = self.init_eps / self.seq_len
        weight = self.param(
            "spatial_weight",
            nn.initializers.uniform(scale=2 * eps),
            (self.seq_len, self.seq_len),
            self.param_dtype,
        ) - eps
        bias = self.param(
            "spatial_bias", nn.initializers.ones, (self.seq_len,), self.param_dtype
        )

        if decode:
            return self._decode_gate(x, res, gate, weight, bias)

        w = weight[:n, :n]
        if self.causal:
            w = jnp.where(jnp.tril(jnp.ones((n, n), dtype=bool)), w, 0.0)
        gate = jnp.einsum("bnd,mn->bmd", gate, w.astype(x.dtype))
        gate = gate + bias[:n, None].astype(x.dtype)
        return res * gate

    def _decode_gate(self, x, res, gate, weight, bias):
        """Decode against the gate-history cache: the gate mixes over the full
        (normalized) gate history — without the cache, a 1-token input would
        see only w[:1, :1] instead of its history row and sampling with 'mlp'
        layers would silently produce garbage. Handles single-token steps and
        multi-token prefill blocks (n > 1) alike."""
        b, n, dh = gate.shape
        is_init = not self.has_variable("cache", "gate_hist")
        hist = self.variable(
            "cache", "gate_hist", jnp.zeros, (b, self.seq_len, dh), gate.dtype
        )
        idx_var = self.variable(
            "cache", "gate_index", lambda: jnp.array(0, jnp.int32)
        )
        if is_init:
            return res * gate

        idx = idx_var.value
        hist.value = jax.lax.dynamic_update_slice(hist.value, gate, (0, idx, 0))
        w_rows = jax.lax.dynamic_slice(weight, (idx, 0), (n, self.seq_len))
        if self.causal:
            cols = jnp.arange(self.seq_len)
            rows = idx + jnp.arange(n)
            w_rows = jnp.where(cols[None, :] <= rows[:, None], w_rows, 0.0)
        out = jnp.einsum("bnd,mn->bmd", hist.value, w_rows.astype(x.dtype))
        out = out + jax.lax.dynamic_slice(bias, (idx,), (n,))[:, None].astype(x.dtype)
        idx_var.value = idx + n
        return res * out


class GMLPBlock(nn.Module):
    """Causal gMLP block used for attn_type='mlp' layers."""

    dim: int
    dim_ff: int
    seq_len: int
    causal: bool = True
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, deterministic: bool = True, decode: bool = False):
        x = nn.Dense(self.dim_ff, dtype=self.dtype, param_dtype=self.param_dtype)(x)
        x = nn.gelu(x)
        x = SpatialGatingUnit(
            seq_len=self.seq_len,
            causal=self.causal,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )(x, decode=decode)
        x = nn.Dense(self.dim, dtype=self.dtype, param_dtype=self.param_dtype)(x)
        return x


def shift_tokens_decode(
    x: jnp.ndarray,
    pos: jnp.ndarray,
    prev_token: jnp.ndarray,
    row_above_token: jnp.ndarray,
    text_len: int,
    image_size: int,
) -> jnp.ndarray:
    """Single-position token shift for the KV-cached decode loop.

    x: (b, n, d) current token features (n == 1 for the classic decode
    step); pos: scalar int32 global position, (b,) per-sequence positions
    (ragged decode offsets / continuous batching), or (b, n) per-token
    positions of a ragged BLOCK (the fused serving iteration) — every
    position test below is elementwise, so all forms broadcast;
    prev_token / row_above_token: (b, n, d) features of positions pos-1
    and pos-image_size (zeros when out of range / across a boundary).
    """
    if jnp.ndim(pos) == 1:
        pos = pos[:, None, None]  # broadcast per-sequence over (b, 1, d)
    elif jnp.ndim(pos) == 2:
        pos = pos[..., None]      # (b, n) per-token over (b, n, d)
    d = x.shape[-1]
    is_text = pos < text_len
    p_img = pos - text_len
    col = p_img % image_size
    row = p_img // image_size

    half, quarter = d // 2, d // 4

    # text branch: first half channels from previous token (zero at pos 0)
    prev_ok_text = (pos > 0) & is_text
    text_shift = jnp.where(prev_ok_text, prev_token[..., :half], 0.0)
    text_out = jnp.concatenate((text_shift, x[..., half:]), axis=-1)

    # image branch
    top_ok = row > 0
    left_ok = col > 0
    top = jnp.where(top_ok, row_above_token[..., :quarter], 0.0)
    left = jnp.where(left_ok, prev_token[..., quarter : 2 * quarter], 0.0)
    img_out = jnp.concatenate((top, left, x[..., 2 * quarter :]), axis=-1)

    return jnp.where(is_text, text_out, img_out)
