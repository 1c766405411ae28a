"""Mamba-2 state-space mixer: the chunked "state-space duality" scan and the
causal depthwise convolution in front of it (the gated norm behind it is
``ops/layers.py:RMSNorm`` of ``y * silu(z)``).

The recurrence, per head (``P`` channels, state ``N``), with ``A < 0``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

``ssd_scan`` computes it in chunks of ``chunk`` positions (Dao & Gu 2024,
"Transformers are SSMs", sec. 6): inside a chunk the masked quadratic form
``(C B^T . L) X`` with ``L[i, j] = exp(c_i - c_j)`` for ``i >= j`` (``c`` the
cumulative sum of ``dt A`` inside the chunk), between chunks the state each
chunk hands to the next. Matmuls run in the compute dtype with float32
accumulation; ``dt``, ``A``, the cumulative log-decays and their exponentials
are float32 whatever the compute dtype (a bf16 table of large arguments is
what broke rotary: PERF.md §7). The backward pass is autodiff of this form;
the per-head ``(chunk, chunk)`` decay matrices are recomputed in it, never
saved (``jax.checkpoint`` around the quadratic part), and computed a block
of heads at a time so that they fit beside a full chip.

One group of ``B``/``C`` shared by all heads (``mamba_n_groups`` 1) is the
only layout written here.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn

from .layers import RMSNorm

Dtype = Any

# heads whose (chunk, chunk) decay matrices are live at once in the
# quadratic part: 64 heads x 32 chunks x 256 x 256 float32 is 537 MB whole
HEAD_BLOCK = 16


def _dot(spec: str, a, b):
    """An einsum of two compute-dtype operands accumulated in float32."""
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def causal_conv1d(x: jnp.ndarray, kernel: jnp.ndarray, bias: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal convolution over (b, n, c): ``y_t = sum_k kernel[k]
    x_{t-K+1+k} + bias`` with zeros before the sequence. ``kernel``: (K, c).
    Accumulates in float32; returns float32."""
    width, n = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0))).astype(jnp.float32)
    kernel = kernel.astype(jnp.float32)
    y = sum(padded[:, k : k + n] * kernel[k] for k in range(width))
    return y + bias.astype(jnp.float32)


def log_decay(dt: jnp.ndarray, A: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumulative sum of ``dt A`` along axis 2 of (b, chunks, chunk,
    heads), float32: the log of the decay from the chunk's start."""
    return jnp.cumsum(dt.astype(jnp.float32) * A.astype(jnp.float32), axis=2)


def carried_states(states: jnp.ndarray, total: jnp.ndarray) -> jnp.ndarray:
    """The state each chunk STARTS from. ``states``: (b, chunks, ...) float32,
    what each chunk adds by its end; ``total``: (b, chunks, heads) float32,
    the log-decay over each whole chunk, broadcast over ``states``' trailing
    axes after heads. The first chunk starts from zero."""
    decay = jnp.exp(total).reshape(total.shape + (1,) * (states.ndim - total.ndim))

    def step(carry, inp):
        add, dec = inp
        return carry * dec + add, carry

    _, starts = jax.lax.scan(
        step, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(decay, 1, 0)),
    )
    return jnp.moveaxis(starts, 0, 1)


def _within_chunk(xdt, cum, scores, dtype):
    """The quadratic part for one block of heads. xdt: (b, c, q, h, p) in the
    compute dtype; cum: (b, c, q, h) float32; scores ``C B^T``: (b, c, q, q)
    float32. Returns (b, c, q, h, p) float32."""
    q = cum.shape[2]
    cum = jnp.moveaxis(cum, 3, 2)                         # (b, c, h, q)
    seg = cum[..., :, None] - cum[..., None, :]           # (b, c, h, i, j)
    lower = jnp.tril(jnp.ones((q, q), bool))
    # masked BEFORE the exponential: above the diagonal seg > 0 can overflow
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    mixed = (scores[:, :, None] * decay).astype(dtype)
    return _dot("bchij,bcjhp->bcihp", mixed, xdt)


def ssd_scan(x, dt, A, B, C, D, chunk: int, dtype: Dtype = jnp.float32) -> jnp.ndarray:
    """x: (b, n, h, p); dt: (b, n, h), already positive (softplus applied);
    A: (h,), negative; B, C: (b, n, state); D: (h,). Returns y: (b, n, h, p)
    float32. ``n`` need not be a multiple of ``chunk``: the tail is padded
    with ``dt = 0`` positions, which neither decay nor feed the state."""
    b, n, h, p = x.shape
    pad = -n % chunk
    if pad:
        x, dt, B, C = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (x, dt, B, C)
        )
    c = (n + pad) // chunk
    dt = dt.astype(jnp.float32).reshape(b, c, chunk, h)
    xdt = (x.astype(jnp.float32).reshape(b, c, chunk, h, p) * dt[..., None]).astype(dtype)
    Bc = B.reshape(b, c, chunk, -1).astype(dtype)
    Cc = C.reshape(b, c, chunk, -1).astype(dtype)
    cum = log_decay(dt, A)                                # (b, c, q, h)
    total = cum[:, :, -1]                                 # (b, c, h)

    # ---- inside each chunk: (C B^T . L) X, a block of heads at a time
    scores = _dot("bcin,bcjn->bcij", Cc, Bc)
    within = jax.checkpoint(_within_chunk, static_argnums=(3,))
    block = HEAD_BLOCK if h % HEAD_BLOCK == 0 else h
    y = jnp.concatenate([
        within(xdt[:, :, :, lo : lo + block], cum[..., lo : lo + block], scores, dtype)
        for lo in range(0, h, block)
    ], axis=3)

    # ---- what each chunk adds to the state by its end: B^T (decay . X)
    to_end = jnp.exp(total[:, :, None] - cum)             # (b, c, q, h)
    weighted = (xdt.astype(jnp.float32) * to_end[..., None]).astype(dtype)
    states = _dot("bcjn,bcjhp->bcnhp", Bc, weighted)
    # ---- the state each chunk starts from, read out through C
    starts = carried_states(states, total[:, :, None])    # (b, c, n, h, p)
    from_start = _dot("bcin,bcnhp->bcihp", Cc, starts.astype(dtype))
    y = y + from_start * jnp.exp(cum)[..., None]
    y = y.reshape(b, n + pad, h, p)[:, :n]
    return y + x[:, :n].astype(jnp.float32) * D.astype(jnp.float32)[:, None]


def inverse_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


def _log_uniform(lo: float, hi: float):
    def init(key, shape, dtype=jnp.float32):
        return jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(lo), jnp.log(hi)))
    return init


class CausalConv1D(nn.Module):
    """``causal_conv1d`` with its (width, channels) kernel and its bias."""

    width: int
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (self.width, c), self.param_dtype
        )
        bias = self.param("bias", nn.initializers.zeros, (c,), self.param_dtype)
        return causal_conv1d(x, kernel, bias)


class MambaMixer(nn.Module):
    """The Mamba-2 mixer: one projection in (gate ``z``, the convolved
    ``x | B | C``, ``dt``), the width-``d_conv`` causal depthwise convolution
    and silu, the scan, RMSNorm of ``y * silu(z)`` over all inner channels,
    one projection out. No bias on the projections. Training and
    whole-sequence evaluation only: a single-token step with a carried state
    is serving's, which this repo does not have yet (ROADMAP R13)."""

    dim: int
    n_heads: int = 64
    d_head: int = 64
    d_state: int = 128
    d_conv: int = 4
    chunk: int = 256
    eps: float = 1e-5
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, v: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        # ``deterministic``: the trunk's uniform half-block argument; no dropout here
        b, n, _ = v.shape
        h, p, s = self.n_heads, self.d_head, self.d_state
        inner, conv_dim = h * p, h * p + 2 * s
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, name=name, dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        zxbcdt = dense(inner + conv_dim + h, "in_proj")(v)
        z, xbc, dt = jnp.split(zxbcdt, (inner, inner + conv_dim), axis=-1)
        # Mamba-2's own initial values: A in [1, 16], the step in
        # [1e-3, 1e-1] (both log-uniform), D = 1
        A_log = self.param(
            "A_log", lambda key, shape: jnp.log(_log_uniform(1.0, 16.0)(key, shape)), (h,)
        )
        dt_bias = self.param(
            "dt_bias", lambda key, shape: inverse_softplus(_log_uniform(1e-3, 1e-1)(key, shape)),
            (h,),
        )
        D = self.param("D", nn.initializers.ones, (h,), self.param_dtype)

        with jax.named_scope("ssm.conv"):
            conv = CausalConv1D(self.d_conv, self.param_dtype, name="conv")
            xbc = jax.nn.silu(conv(xbc)).astype(self.dtype)
        x, B, C = jnp.split(xbc, (inner, inner + s), axis=-1)
        with jax.named_scope("ssm.scan"):
            step = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
            y = ssd_scan(
                x.reshape(b, n, h, p), step, -jnp.exp(A_log.astype(jnp.float32)),
                B, C, D, self.chunk, self.dtype,
            )
        gated = y.reshape(b, n, inner) * jax.nn.silu(z.astype(jnp.float32))
        y = RMSNorm(self.eps, self.param_dtype, name="norm")(gated).astype(self.dtype)
        return dense(self.dim, "out_proj")(y)
