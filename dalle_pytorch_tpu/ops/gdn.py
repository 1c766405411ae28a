"""Gated delta rule linear attention (Gated DeltaNet): the mixer, its chunked
form in XLA and as a Pallas kernel pair.

The recurrence, per value head (keys ``d_k``, values ``d_v``; state ``S``:
``d_k x d_v``, zero before the sequence), with ``g_t <= 0`` and ``0 <= beta_t
<= 1``:

    S'  = exp(g_t) S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T          o_t = S_t^T q_t

The state is CORRECTED, not added to: what the state already answers for
``k_t`` is taken off the value before it is written. That is what the
state-space duality of ``ops/ssm.py`` does not have. In a chunk of ``C``
positions that starts from ``S_0`` (``G`` the cumulative sum of ``g`` inside
the chunk, ``D_ij = exp(G_i - G_j)``):

    A = tril(diag(beta) (K K^T . D), -1)        T = (I + A)^-1     (the WY / UT transform)
    U = T (beta . (V - (exp(G) . K) S_0))       the corrected values, (C, d_v)
    O = (exp(G) . Q) S_0 + tril(Q K^T . D) U
    S_C = exp(G_C) S_0 + (exp(G_C - G) . K)^T U

``G``, its exponentials and ``T`` are float32 whatever the compute dtype (a
rounded table of large arguments is what broke rotary: PERF.md section 7);
the products run in the compute dtype with float32 accumulation. ``T`` is the
inverse of a unit lower-triangular matrix: ``A`` is nilpotent, so
``(I + A)^-1 = (I - A)(I + A^2)(I + A^4) ...`` exactly, ``log2 C - 1``
squarings and as many products, which the MXU takes (float32 at ``HIGHEST``).

Two forms of one algorithm, chosen from the shape
(``delta_rule_kernels_eligible``; no switch) and recorded at the route site
``forward/delta_rule``. Where keys and values are whole lane tiles it is
``gdn_chunk_fwd`` and ``gdn_chunk_bwd`` behind one ``jax.custom_vjp``: a grid
step is one (row, value head, chunk), the chunks of a head run in sequence
with the state in VMEM scratch (backward: its cotangent, from the last chunk
to the first), every ``(C, C)`` matrix is built, inverted and dropped in VMEM.
The forward writes the state each chunk STARTS from (float32, ``d_k x d_v`` a
chunk and head): the backward's only residual beside the operands. Every
other shape keeps the XLA form (``lax.scan`` over the chunks, the inverse by
``solve_triangular``; its backward is autodiff), which is also the oracle the
kernels are tested against. The sequential recurrence itself is the
benchmark's plain reference (``benchmarks/reference_gdn.py``).

Training and whole-sequence evaluation only: a single-token step that carries
``S`` and the convolution's tail is serving's (ROADMAP R13).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kv_policy
from .layers import rms_norm
from .ssm import LANES, CausalConv1D, _mosaic_call, _mxu

Dtype = Any

HIGHEST = jax.lax.Precision.HIGHEST


# ---- what the mixer computes in front of the rule, each by name ------------


def l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def write_strength(b):
    """``beta = sigmoid(b)``, float32: how much of the corrected value is
    written, a value head and position."""
    return jax.nn.sigmoid(b.astype(jnp.float32))


def log_decay(a, A_log, dt_bias):
    """``g = -exp(A_log) softplus(a + dt_bias)``, float32, <= 0: the log of
    what is left of the state after a position, a value head."""
    step = jax.nn.softplus(a.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    return -jnp.exp(A_log.astype(jnp.float32)) * step


def chunk_log_decay(g):
    """Inclusive cumulative sum of ``g`` inside each chunk (the last axis),
    float32: the log of the decay since the chunk's start."""
    return jnp.cumsum(g.astype(jnp.float32), axis=-1)


# ---- the chunk's algebra, shared by both forms -----------------------------


def _masks(c: int):
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return i > j, i >= j, i == j


def _col(row):
    """(1, C) -> (C, 1) without a transpose: the diagonal of the row spread
    over the sublanes."""
    eye = _masks(row.shape[1])[2]
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col):
    eye = _masks(col.shape[0])[2]
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _last(row):
    """The last entry of a (1, C) row as (1, 1), by a masked sum: a slice at
    lane C - 1 keeps its offset, and Mosaic spreads no such value over both
    sublanes and lanes."""
    c = row.shape[1]
    last = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
    return jnp.sum(jnp.where(last, row, 0.0), axis=1, keepdims=True)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for a strictly lower-triangular (C, C) float32 ``a``:
    the finite product ``(I - a)(I + a^2)(I + a^4) ...``."""
    c = a.shape[0]
    dot = lambda x, y: jax.lax.dot_general(
        x, y, (((1,), (0,)), ((), ())), precision=HIGHEST, preferred_element_type=jnp.float32
    )
    power = -a
    out = jnp.where(_masks(c)[2], 1.0, 0.0) + power
    for _ in range(max(c - 1, 1).bit_length() - 1):
        power = dot(power, power)
        out = out + dot(out, power)
    return out


def _chunk_forward(q, k, v, g_row, beta_row, s0, dtype):
    """One chunk of one value head. q, k: (C, d_k), v: (C, d_v) in ``dtype``;
    ``g_row``, ``beta_row``: (1, C) float32; ``s0``: (d_k, d_v) float32.
    Everything the backward rebuilds, by name."""
    c = q.shape[0]
    strict, incl, _ = _masks(c)
    g_col, beta_col = _col(g_row), _col(beta_row)
    # masked BEFORE the exponential: above the diagonal the difference is positive
    decay = jnp.exp(jnp.where(incl, g_col - g_row, -jnp.inf))          # D, 1 on the diagonal
    since_start = jnp.exp(g_col)                                        # (C, 1)
    to_end = jnp.exp(_last(g_row) - g_col)                               # (C, 1)
    k32, q32 = k.astype(jnp.float32), q.astype(jnp.float32)
    kg32, qg32, kd32 = k32 * since_start, q32 * since_start, k32 * to_end
    s16 = s0.astype(dtype)
    a_plain = jnp.where(strict, _mxu(k, k, (1, 1)) * decay, 0.0)        # without beta
    t = _unit_lower_inverse(beta_col * a_plain)
    r_plain = v.astype(jnp.float32) - _mxu(kg32.astype(dtype), s16, (1, 0))
    u = _mxu(t.astype(dtype), (beta_col * r_plain).astype(dtype), (1, 0))   # (C, d_v)
    p = jnp.where(incl, _mxu(q, k, (1, 1)) * decay, 0.0)
    return dict(
        strict=strict, incl=incl, g_col=g_col, beta_col=beta_col, decay=decay,
        since_start=since_start, to_end=to_end, kg32=kg32, qg32=qg32, kd32=kd32, s16=s16,
        a_plain=a_plain, t=t, r_plain=r_plain, u=u, p=p,
    )


def _chunk_outputs(f, s0, g_row, dtype):
    """(the chunk's output (C, d_v) float32, the state at its end)."""
    u16 = f["u"].astype(dtype)
    o = _mxu(f["qg32"].astype(dtype), f["s16"], (1, 0)) + _mxu(f["p"].astype(dtype), u16, (1, 0))
    whole = jnp.broadcast_to(jnp.exp(_last(g_row)), (1, s0.shape[1]))
    s_end = whole * s0 + _mxu(f["kd32"].astype(dtype), u16, (0, 0))
    return o, s_end


# ---- the Pallas kernels -----------------------------------------------------
#
# q, k, v keep the projection's layout, (b, n, heads x width) with heads in
# lanes: a grid step takes its head's 128-lane tile, its key head's for q and
# k (a key head serves ``ratio`` value heads), and XLA never sees a (.., h, d)
# array. The per-position scalars arrive as (b, heads, chunks, C) float32:
# a head's whole table is one small block that stays in VMEM through its
# chunks, and a chunk reads (backward: writes) its ROW; the column
# orientation is made in the kernel (``_col``), so nothing is transposed.


def delta_rule_kernels_eligible(chunk: int, d_k: int, d_v: int) -> bool:
    """The shapes the kernels are written for: keys and values whole lane
    tiles, a chunk whole sublane tiles of the compute dtype and a power of
    two (the inverse's product)."""
    return (
        d_k % LANES == 0 and d_v % LANES == 0 and chunk % 16 == 0
        and chunk & (chunk - 1) == 0
    )


def _gdn_chunk_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, s_ref, state):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        state[...] = jnp.zeros_like(state)             # nothing before the sequence

    dtype = q_ref.dtype
    g_row, beta_row = g_ref[0, 0, pl.ds(c, 1), :], beta_ref[0, 0, pl.ds(c, 1), :]
    s0 = state[...]
    s_ref[0, 0, 0] = s0
    f = _chunk_forward(q_ref[0], k_ref[0], v_ref[0], g_row, beta_row, s0, dtype)
    o, s_end = _chunk_outputs(f, s0, g_row, dtype)
    o_ref[0] = o.astype(o_ref.dtype)
    state[...] = s_end


def _gdn_chunk_bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate,
):
    """Cotangents of one (row, value head, chunk), the chunks from the LAST to
    the first: ``dstate`` carries the cotangent of the state at the chunk's
    end. With ``dR = T^T dU`` the inverse needs no cotangent of its own:
    ``dA = -dR U^T``."""
    step, chunks = pl.program_id(2), pl.num_programs(2)
    c = chunks - 1 - step

    @pl.when(step == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)            # nothing after the sequence

    dtype = q_ref.dtype
    q, k = q_ref[0], k_ref[0]
    g_row, beta_row = g_ref[0, 0, pl.ds(c, 1), :], beta_ref[0, 0, pl.ds(c, 1), :]
    s0 = s_ref[0, 0, 0]
    f = _chunk_forward(q, k, v_ref[0], g_row, beta_row, s0, dtype)
    n = q.shape[0]
    beta_col, decay, since_start, to_end = f["beta_col"], f["decay"], f["since_start"], f["to_end"]
    s16, u16 = f["s16"], f["u"].astype(dtype)
    do16 = do_ref[0].astype(dtype)
    ds = dstate[...]
    ds16 = ds.astype(dtype)
    kd16, kg16, qg16 = (f[name].astype(dtype) for name in ("kd32", "kg32", "qg32"))

    # ---- back through O = (exp(G) Q) S_0 + P U and S_C = exp(G_C) S_0 + Kd^T U
    du = _mxu(f["p"].astype(dtype), do16, (0, 0)) + _mxu(kd16, ds16, (1, 0))     # (C, d_v)
    dr = _mxu(f["t"].astype(dtype), du.astype(dtype), (0, 0))                    # T^T dU
    dr16, bdr = dr.astype(dtype), beta_col * dr
    bdr16 = bdr.astype(dtype)
    dv_ref[0] = bdr.astype(dv_ref.dtype)
    dbeta_col = jnp.sum(dr * f["r_plain"], axis=1, keepdims=True)
    # R = beta (V - (exp(G) K) S_0)
    dkg = -_mxu(bdr16, s16, (1, 1))                                              # (C, d_k)
    dk = since_start * dkg
    dg_col = jnp.sum(dkg * f["kg32"], axis=1, keepdims=True)
    # T = (I + A)^-1, A = beta (K K^T . D) below the diagonal
    da = jnp.where(f["strict"], -_mxu(dr16, u16, (1, 1)), 0.0)
    daa = da * f["a_plain"]
    dbeta_col += jnp.sum(daa, axis=1, keepdims=True)
    daa = daa * beta_col
    dg_col += jnp.sum(daa, axis=1, keepdims=True)
    dg_row = -jnp.sum(daa, axis=0, keepdims=True)
    dm16 = (da * beta_col * decay).astype(dtype)
    dk += _mxu(dm16, k, (1, 0)) + _mxu(dm16, k, (0, 0))
    # P = Q K^T . D on and below the diagonal
    dp = jnp.where(f["incl"], _mxu(do16, u16, (1, 1)), 0.0)
    dpp = dp * f["p"]
    dg_col += jnp.sum(dpp, axis=1, keepdims=True)
    dg_row -= jnp.sum(dpp, axis=0, keepdims=True)
    dqk16 = (dp * decay).astype(dtype)
    dos = _mxu(do16, s16, (1, 1))                                                # dO S_0^T
    dq_ref[0] = (_mxu(dqk16, k, (1, 0)) + since_start * dos).astype(dq_ref.dtype)
    dk += _mxu(dqk16, q, (0, 0))
    dg_col += jnp.sum(dos * f["qg32"], axis=1, keepdims=True)
    # Kd = exp(G_C - G) K
    dkd = _mxu(u16, ds16, (1, 1))                                                # U dS^T
    dk += to_end * dkd
    left = jnp.sum(dkd * f["kd32"], axis=1, keepdims=True)                       # (C, 1)
    dg_col -= left
    whole = jnp.exp(_last(g_row))                                                # (1, 1)
    total = lambda x: jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)
    at_end = total(left) + whole * total(ds * s0)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) == n - 1
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dg_ref[0, 0, pl.ds(c, 1), :] = dg_row + _row(dg_col) + jnp.where(last, at_end, 0.0)
    dbeta_ref[0, 0, pl.ds(c, 1), :] = _row(dbeta_col)
    # ---- the cotangent of the state this chunk started from
    dstate[...] = (
        _mxu(qg16, do16, (0, 0)) + jnp.broadcast_to(whole, (1, ds.shape[1])) * ds
        - _mxu(kg16, bdr16, (0, 0))
    )


def _specs(chunks: int, chunk: int, d_k: int, d_v: int, ratio: int, back: bool = False):
    """The block of every kind of operand in the grid (row, value head,
    chunk); ``back``: the chunks from the last to the first."""
    at = (lambda ci: chunks - 1 - ci) if back else (lambda ci: ci)
    return dict(
        key=pl.BlockSpec((1, chunk, d_k), lambda bi, hi, ci: (bi, at(ci), hi // ratio)),
        own_key=pl.BlockSpec((1, chunk, d_k), lambda bi, hi, ci: (bi, at(ci), hi)),
        value=pl.BlockSpec((1, chunk, d_v), lambda bi, hi, ci: (bi, at(ci), hi)),
        table=pl.BlockSpec((1, 1, chunks, chunk), lambda bi, hi, ci: (bi, hi, 0, 0)),
        state=pl.BlockSpec((1, 1, 1, d_k, d_v), lambda bi, hi, ci: (bi, hi, at(ci), 0, 0)),
    )


def _sizes(q, v, g, key_heads):
    b, _, heads, chunks, chunk = (q.shape[0], *g.shape)
    d_k, d_v = q.shape[-1] // key_heads, v.shape[-1] // heads
    assert heads % key_heads == 0 and delta_rule_kernels_eligible(chunk, d_k, d_v), (q.shape, v.shape, g.shape)
    assert q.shape[1] == chunks * chunk, (q.shape, g.shape)
    return b, heads, chunks, chunk, d_k, d_v, heads // key_heads


# each call a ``jax.jit`` of its own, as ``ops/ssm.py``'s: three mixers, each
# run forward, again under ``remat`` and backward, lower a kernel once a shape
_kernel_call = functools.partial(jax.jit, static_argnames=("key_heads", "interpret"))


@_kernel_call
def _fwd_call(q, k, v, g, beta, *, key_heads, interpret):
    b, heads, chunks, chunk, d_k, d_v, ratio = _sizes(q, v, g, key_heads)
    s = _specs(chunks, chunk, d_k, d_v, ratio)
    return _mosaic_call(
        _gdn_chunk_fwd_kernel, (b, heads, chunks),
        [s["key"], s["key"], s["value"], s["table"], s["table"]], [s["value"], s["state"]],
        [jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct((b, heads, chunks, d_k, d_v), jnp.float32)],
        [pltpu.VMEM((d_k, d_v), jnp.float32)], [q, k, v, g, beta], interpret, name="gdn_chunk_fwd",
    )


@_kernel_call
def _bwd_call(q, k, v, g, beta, states, do, *, key_heads, interpret):
    b, heads, chunks, chunk, d_k, d_v, ratio = _sizes(q, v, g, key_heads)
    s = _specs(chunks, chunk, d_k, d_v, ratio, back=True)
    n = q.shape[1]
    per_head = jax.ShapeDtypeStruct((b, n, heads * d_k), q.dtype)
    table = jax.ShapeDtypeStruct(g.shape, jnp.float32)
    dq, dk, dv, dg, dbeta = _mosaic_call(
        _gdn_chunk_bwd_kernel, (b, heads, chunks),
        [s["key"], s["key"], s["value"], s["table"], s["table"], s["state"], s["value"]],
        [s["own_key"], s["own_key"], s["value"], s["table"], s["table"]],
        [per_head, per_head, jax.ShapeDtypeStruct(v.shape, v.dtype), table, table],
        [pltpu.VMEM((d_k, d_v), jnp.float32)], [q, k, v, g, beta, states, do], interpret,
        name="gdn_chunk_bwd",
    )
    # a key head's cotangent: the sum over the value heads it serves
    over = lambda t: t.reshape(b, n, key_heads, ratio, d_k).astype(jnp.float32).sum(3).reshape(q.shape)
    return over(dq).astype(q.dtype), over(dk).astype(k.dtype), dv, dg, dbeta


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def delta_rule_chunks(q, k, v, g, beta, key_heads, interpret):
    """The rule over whole chunks. q, k: (b, n, key heads x d_k), normalised
    and scaled; v: (b, n, heads x d_v); g: (b, heads, chunks, C) float32, the
    cumulative log-decay INSIDE each chunk; beta: likewise. Returns (b, n,
    heads x d_v) in the dtype of ``v``."""
    return _fwd_call(q, k, v, g, beta, key_heads=key_heads, interpret=interpret)[0]


def _chunks_fwd_rule(q, k, v, g, beta, key_heads, interpret):
    o, states = _fwd_call(q, k, v, g, beta, key_heads=key_heads, interpret=interpret)
    return o, (q, k, v, g, beta, states)


def _chunks_bwd_rule(key_heads, interpret, res, do):
    return _bwd_call(*res, do, key_heads=key_heads, interpret=interpret)


delta_rule_chunks.defvjp(_chunks_fwd_rule, _chunks_bwd_rule)


# ---- the same algorithm in XLA ---------------------------------------------


def _delta_rule_xla(q, k, v, g, beta, key_heads: int, dtype):
    """``delta_rule_chunks`` in XLA: every chunk's matrices at once, the
    inverse by ``solve_triangular``, the state handed from chunk to chunk in a
    ``lax.scan``. The oracle of the kernels' tests, and the form of every
    shape they are not written for."""
    b, n, _ = q.shape
    _, heads, chunks, c = g.shape
    ratio = heads // key_heads
    split = lambda t, h: t.reshape(b, chunks, c, h, -1).transpose(0, 3, 1, 2, 4)   # (b, h, chunks, C, d)
    per_value_head = lambda t: jnp.repeat(split(t, key_heads), ratio, axis=1)
    q, k, v = per_value_head(q.astype(dtype)), per_value_head(k.astype(dtype)), split(v.astype(dtype), heads)
    dot = lambda spec, x, y: jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)
    strict, incl, eye = _masks(c)
    g_col, beta_col = g[..., :, None], beta[..., :, None]
    decay = jnp.exp(jnp.where(incl, g_col - g[..., None, :], -jnp.inf))
    since_start, to_end = jnp.exp(g_col), jnp.exp(g[..., -1:, None] - g_col)
    a = jnp.where(strict, beta_col * dot("bhnid,bhnjd->bhnij", k, k) * decay, 0.0)
    t = jax.scipy.linalg.solve_triangular(
        a + eye, jnp.broadcast_to(jnp.where(eye, 1.0, 0.0), a.shape), lower=True, unit_diagonal=True
    ).astype(dtype)
    p = jnp.where(incl, dot("bhnid,bhnjd->bhnij", q, k) * decay, 0.0).astype(dtype)
    f32 = lambda x: x.astype(jnp.float32)
    kg, qg, kd = ((f32(x) * w).astype(dtype) for x, w in ((k, since_start), (q, since_start), (k, to_end)))
    whole = jnp.exp(g[..., -1])                                         # (b, h, chunks)

    def chunk(s0, inp):
        kg, qg, kd, v, t, p, beta_col, whole = inp
        s16 = s0.astype(dtype)
        r = beta_col * (f32(v) - dot("bhik,bhkv->bhiv", kg, s16))
        u = dot("bhij,bhjv->bhiv", t, r.astype(dtype)).astype(dtype)
        o = dot("bhik,bhkv->bhiv", qg, s16) + dot("bhij,bhjv->bhiv", p, u)
        return whole[..., None, None] * s0 + dot("bhik,bhiv->bhkv", kd, u), o

    chunks_first = lambda x: jnp.moveaxis(x, 2, 0)
    zeros = jnp.zeros((b, heads, k.shape[-1], v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(
        chunk, zeros, tuple(chunks_first(x) for x in (kg, qg, kd, v, t, p, beta_col, whole))
    )
    return o.transpose(1, 0, 3, 2, 4).reshape(b, n, -1).astype(dtype)       # (chunks, b, h, C, d_v)


def gated_delta_rule(q, k, v, g, beta, key_heads: int, chunk: int, dtype: Dtype = jnp.float32):
    """q, k: (b, n, key heads x d_k), already normalised (q scaled too); v:
    (b, n, heads x d_v); g: (b, n, heads), the log-decay, <= 0; beta: (b, n,
    heads). Returns (b, n, heads x d_v) in ``dtype``. ``n`` need not be whole
    chunks: the tail is padded with positions that neither decay nor write
    (``g = 0``, ``beta = 0``)."""
    from .attention import _per_device  # the one shard_map rule of every Mosaic call

    b, n, heads = g.shape
    d_k, d_v = q.shape[-1] // key_heads, v.shape[-1] // heads
    pad = -n % chunk
    q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (q, k, v, g, beta))
    chunks = (n + pad) // chunk
    table = lambda t: t.astype(jnp.float32).reshape(b, chunks, chunk, heads).transpose(0, 3, 1, 2)
    g, beta = chunk_log_decay(table(g)), table(beta)
    if delta_rule_kernels_eligible(chunk, d_k, d_v):
        interpret = kv_policy.pallas_interpret()
        kv_policy.record_route("forward/delta_rule", "gdn_chunk", interpret)
        o = _per_device(
            lambda *operands: delta_rule_chunks(*operands, key_heads, interpret),
            (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta),
        )
    else:
        kv_policy.record_route("forward/delta_rule", "xla")
        o = _delta_rule_xla(q, k, v, g, beta, key_heads, dtype)
    return o[:, :n]


class GatedDeltaNet(nn.Module):
    """The Gated DeltaNet mixer (``qwen3_next``'s ``linear_attention``
    layers), no bias anywhere:

        [q | k | v | z] = W_qkvz u;   [b | a] = W_ba u
        [q | k | v] = silu(causal_conv(q | k | v))          depthwise, width ``conv``
        beta = sigmoid(b);   g = -exp(A_log) softplus(a + dt_bias)
        q <- q / |q| d_k^-1/2,  k <- k / |k|                a key head serves heads / key_heads value heads
        o = the gated delta rule
        y = W_o [RMSNorm_{d_v}(o) . silu(z)]                the norm a head, one gain over d_v

    The columns of ``W_qkvz`` lie ``q | k | v | z`` (the source groups them a
    key head at a time: a permutation of this one)."""

    dim: int
    key_heads: int = 16
    value_heads: int = 32
    key_dim: int = 128
    value_dim: int = 128
    conv: int = 4
    chunk: int = 64
    eps: float = 1e-6
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        # ``deterministic``: the trunk's uniform half-block argument; no dropout here
        b, n, _ = x.shape
        hk, hv, dk, dv = self.key_heads, self.value_heads, self.key_dim, self.value_dim
        keys, values = hk * dk, hv * dv
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, name=name, dtype=self.dtype, param_dtype=self.param_dtype,
        )
        with jax.named_scope("linattn.proj"):
            qkvz = dense(2 * keys + 2 * values, "in_proj_qkvz")(x)
            ba = dense(2 * hv, "in_proj_ba")(x)
        # the family's own initial values: A uniform in (0, 16), the step's bias 1
        A_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-4, 16.0)),
            (hv,),
        )
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,), self.param_dtype)
        gain = self.param("norm_scale", nn.initializers.ones, (dv,), self.param_dtype)

        with jax.named_scope("linattn.conv"):
            conv = CausalConv1D(self.conv, self.dtype, self.param_dtype, use_bias=False, name="conv")
            q, k, v = conv(qkvz[..., : 2 * keys + values], (keys, keys, values))
        with jax.named_scope("linattn.delta"):
            heads_of = lambda t: t.reshape(b, n, hk, dk)
            q = (l2norm(heads_of(q)) * dk**-0.5).astype(self.dtype).reshape(b, n, keys)
            k = l2norm(heads_of(k)).astype(self.dtype).reshape(b, n, keys)
            o = gated_delta_rule(
                q, k, v, log_decay(ba[..., hv:], A_log, dt_bias), write_strength(ba[..., :hv]),
                hk, self.chunk, self.dtype,
            )
        with jax.named_scope("linattn.norm"):
            z = qkvz[..., 2 * keys + values :].astype(jnp.float32)
            y = rms_norm(o.reshape(b, n, hv, dv), gain, self.eps).reshape(b, n, values)
            y = (y * jax.nn.silu(z)).astype(self.dtype)
        with jax.named_scope("linattn.proj"):
            return dense(self.dim, "out_proj")(y)
