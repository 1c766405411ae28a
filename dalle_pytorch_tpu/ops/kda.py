"""Kimi Delta Attention (KDA): the gated delta rule with a decay per KEY
CHANNEL, the mixer around it, its chunked form in XLA and as three Pallas
kernels.

The recurrence, per head (keys and values ``d``; state ``S``: ``d x d``, zero
before the sequence), with ``g_t <= 0`` a vector over the key channels and
``0 <= beta_t <= 1``:

    S'  = Diag(exp(g_t)) S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T          o_t = S_t^T q_t

``ops/gdn.py``'s rule is the case of a ``g_t`` equal over the channels. In a
chunk of ``C`` positions that starts from ``S_0`` (``G``: (C, d), the
cumulative sum of ``g`` inside the chunk; rows ``i``, channels ``c``):

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)   below the diagonal     T = (I + A)^-1
    P_ij =        sum_c q_ic k_jc exp(G_ic - G_jc)   on and below it
    U = T (beta . (V - (exp(G) . K) S_0))
    O = (exp(G) . Q) S_0 + P U
    S_C = Diag(exp(G_C)) S_0 + (exp(G_C - G) . K)^T U

With a scalar decay ``exp(G_i - G_j)`` is one (C, C) matrix and ``A``, ``P``
are a product and a mask. Per channel they are not, and the factored form
``(K . e^G)(K . e^-G)^T`` overflows float32: at the family's initial values a
position's ``g`` reaches -1.6 and a chunk sums it to -100. So a chunk is cut
into sub-chunks of ``SUB`` rows and every factor is an exponential of a
DIFFERENCE that is at most 0:

- rows of sub-chunk ``a`` against every column before it: with ``r`` the
  sub-chunk's first row, ``exp(G_i - G_jc) = exp(G_i - G_r) exp(G_r - G_j)``,
  both factors at most 1, one product on the MXU;
- the diagonal sub-blocks element by element: for each column ``j`` of a
  sub-chunk, ``exp(G_i - G_j)`` over its rows ``i >= j`` and its channels,
  the j-th columns of all of a chunk's sub-chunks in one step.

Nothing is clamped: a factor that underflows stands for a product that is
smaller still. The backward takes the same two paths: for ``M_ij = sum_c x_ic
y_jc exp(G_ic - G_jc)`` and its cotangent ``dM``, ``dx_i = sum_j dM_ij y_j
exp(G_i - G_j)``, ``dy_j = sum_i dM_ij x_i exp(G_i - G_j)`` and ``dG += x . dx
- y . dy``; ``dG`` is per channel, (b, n, heads x d) float32. The inverse,
the masks, ``l2norm``, ``write_strength`` and the convolution are
``ops/gdn.py``'s and ``ops/ssm.py``'s.

Two forms of one algorithm, chosen from the shape (``kda_kernels_eligible``;
no switch) and recorded at the route site ``forward/delta_rule`` as
``kda_chunk`` or ``xla``. Where keys and values are whole lane tiles it is
three kernels behind one ``jax.custom_vjp``, split where the state enters:

- ``kda_chunk_tables`` computes what a chunk computes WITHOUT the state, ``T``
  and ``P``, a grid step one (row, head, chunk), no grid axis sequential. It
  writes them in the compute dtype as one ``(b, heads, n, 2C)`` table: a
  position's row of ``T`` in the lanes ``[0, C)``, of ``P`` in ``[C, 2C)``.
- ``kda_chunk_fwd`` keeps the state: a grid step is one (row, head, chunk),
  the chunks of a head in sequence with its state in VMEM scratch. It writes
  the output and the state each chunk STARTS from (float32).
- ``kda_chunk_bwd`` inverts nothing: the same grid from the last chunk to the
  first with the state's cotangent in scratch, ``T`` and ``P`` read, the
  decayed products rebuilt for ``dq``, ``dk`` and ``dG``.

A block's checkpoint under ``remat`` keeps the table by the name
``ops/gdn.py`` gives the scalar rule's (``DELTA_RESIDUAL_NAMES``): the rebuilt
forward runs ``kda_chunk_fwd`` alone.

Every other shape keeps the XLA form (``lax.scan`` over the chunks, each
chunk's decays as a whole (C, C, d) tensor, the inverse by
``solve_triangular``; its backward is autodiff), which is also the oracle the
kernels are tested against. The sequential recurrence is the benchmark's plain
reference (``benchmarks/reference_kda.py``).

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
from .gdn import (
    _col, _masks, _row, _unit_lower_inverse, kept_tables, l2norm, record_kept_tables, write_strength,
)
from .layers import rms_norm
from .ssm import LANES, VMEM_LIMIT_BYTES, CausalConv1D, _mosaic_call, _mxu

Dtype = Any

F32 = jnp.float32
# rows of a sub-chunk: the diagonal blocks taken element by element
SUB = 16


# ---- what the mixer computes in front of the rule ---------------------------


def channel_log_decay(a, A_log, dt_bias, heads: int):
    """``g = -exp(A_log_h) softplus(a + dt_bias)``, float32, <= 0: the log of
    what is left of each key channel of the state after a position. ``a``:
    (.., heads x d); ``A_log``: (heads,); ``dt_bias``: (heads x d,)."""
    step = jax.nn.softplus(a.astype(F32) + dt_bias.astype(F32))
    rate = jnp.repeat(jnp.exp(A_log.astype(F32)), a.shape[-1] // heads)
    return -rate * step


def chunk_log_decay(g, chunk: int):
    """(b, n, c) with ``n`` whole chunks: the inclusive cumulative sum of
    ``g`` inside each chunk, float32."""
    b, n, c = g.shape
    return jnp.cumsum(g.astype(F32).reshape(b, n // chunk, chunk, c), axis=2).reshape(b, n, c)


# ---- the chunk's algebra, one chunk of one head (the kernels' body) ---------


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _pick_row(x, i):
    """Row ``i`` (static or traced) of (R, d) as (1, d), by a masked sum."""
    return jnp.sum(jnp.where(_iota(x.shape, 0) == i, x, 0.0), axis=0, keepdims=True)


def _decay(later, earlier, keep):
    """``exp(later - earlier)`` where ``keep``, 0 elsewhere: masked BEFORE
    the exponential, where the difference may be positive."""
    return jnp.exp(jnp.where(keep, later - earlier, -jnp.inf))


def _block_sum(x, s: int):
    """(C, d): each row the sum of its sub-block's ``s`` rows."""
    c, d = x.shape
    total = jnp.sum(x.reshape(c // s, s, d), axis=1, keepdims=True)
    return jnp.broadcast_to(total, (c // s, s, d)).reshape(c, d)


def _diagonal_steps(c: int, d: int):
    """What a column step of the diagonal sub-blocks reads, for ALL of a
    chunk's sub-blocks at once: (a row's place in its sub-block, (C, d); the
    first row of each row's sub-block, (C, C): the lane of its column ``j``
    less ``j``)."""
    rows = _iota((c, c), 0)
    return jnp.bitwise_and(_iota((c, d), 0), SUB - 1), rows - jnp.bitwise_and(rows, SUB - 1)


def _earlier_columns(q32, k32, G, dtype):
    """``sum_c x_ic k_jc exp(G_ic - G_jc)`` for ``j`` in an EARLIER sub-chunk
    than ``i``, for x = k and x = q, (C, C) float32 each: a sub-chunk's rows
    against every column before its first row ``r`` by one product,
    ``exp(G_i - G_r) exp(G_r - G_j)`` with both factors at most 1."""
    c, d = k32.shape
    s = SUB
    kk, qk = [jnp.zeros((s, c), F32)], [jnp.zeros((s, c), F32)]
    for r in range(s, c, s):
        ref = _pick_row(G[r : r + s], 0)
        left = jnp.exp(G[r : r + s] - ref)                                      # rows >= r: <= 1
        right = (k32 * _decay(ref, G, _iota((c, d), 0) < r)).astype(dtype)      # rows < r
        kk.append(_mxu((k32[r : r + s] * left).astype(dtype), right, (1, 1)))
        qk.append(_mxu((q32[r : r + s] * left).astype(dtype), right, (1, 1)))
    return jnp.concatenate(kk, axis=0), jnp.concatenate(qk, axis=0)


def _decayed_products(q32, k32, G, dtype):
    """``(K K^T . D, Q K^T . D)`` on and below the diagonal of one chunk,
    ``D_ij = exp(G_i - G_j)`` per channel and summed over the channels; (C, C)
    float32 each. The sub-chunks' earlier columns by products
    (``_earlier_columns``); the diagonal sub-blocks a column ``j`` at a time,
    every sub-block's j-th column in the same step."""
    c, d = k32.shape
    offset, first = _diagonal_steps(c, d)
    lanes, s = _iota((c, c), 1), SUB

    def column(j, acc):
        kk, qk = acc
        at = offset == j
        kjd = _block_sum(jnp.where(at, k32, 0.0), s) * _decay(
            G, _block_sum(jnp.where(at, G, 0.0), s), offset >= j
        )
        place = lanes == first + j
        kk = kk + jnp.where(place, jnp.sum(k32 * kjd, axis=1, keepdims=True), 0.0)
        qk = qk + jnp.where(place, jnp.sum(q32 * kjd, axis=1, keepdims=True), 0.0)
        return kk, qk

    zero = jnp.zeros((c, c), F32)
    kk, qk = jax.lax.fori_loop(0, s, column, (zero, zero))
    kk_earlier, qk_earlier = _earlier_columns(q32, k32, G, dtype)
    return kk + kk_earlier, qk + qk_earlier


def _chunk_tables(q, k, G, beta_row, dtype):
    """``T = (I + A)^-1`` and ``P`` of one chunk as (C, 2C) in ``dtype``
    (``T`` in the lanes [0, C), ``P`` in [C, 2C)). q, k: (C, d); ``G``: (C, d)
    float32; ``beta_row``: (1, C) float32."""
    c = k.shape[0]
    strict, incl, _ = _masks(c, c)
    kk, qk = _decayed_products(q.astype(F32), k.astype(F32), G, dtype)
    a = _col(beta_row) * jnp.where(strict, kk, 0.0)
    t = _unit_lower_inverse(a[None], c)[0]
    return jnp.concatenate([t, jnp.where(incl, qk, 0.0)], axis=-1).astype(dtype)


def _chunk_state(q, k, v, G, beta_row, t, s0, dtype):
    """What one chunk of one head computes from the state it starts with, by
    name (the backward rebuilds it). q, k: (C, d_k), v: (C, d_v), ``G``: (C,
    d_k) float32, ``beta_row``: (1, C), ``t``: (C, C) in ``dtype``, ``s0``:
    (d_k, d_v) float32."""
    c = k.shape[0]
    g_end = _pick_row(G, c - 1)                                               # (1, d_k)
    since_start, to_end = jnp.exp(G), jnp.exp(g_end - G)                     # (C, d_k), <= 1
    k32, q32 = k.astype(F32), q.astype(F32)
    beta_col, s16 = _col(beta_row), s0.astype(dtype)
    kg32 = k32 * since_start
    r_plain = v.astype(F32) - _mxu(kg32.astype(dtype), s16, (1, 0))
    u = _mxu(t, (beta_col * r_plain).astype(dtype), (1, 0))                  # (C, d_v)
    return dict(
        k32=k32, q32=q32, g_end=g_end, since_start=since_start, to_end=to_end,
        beta_col=beta_col, s16=s16, kg32=kg32, qg32=q32 * since_start, kd32=k32 * to_end,
        r_plain=r_plain, u=u,
    )


def _chunk_forward(q, k, v, G, beta_row, t, p, s0, dtype):
    """(the chunk's output (C, d_v) float32, the state at its end)."""
    f = _chunk_state(q, k, v, G, beta_row, t, s0, dtype)
    u16 = f["u"].astype(dtype)
    o = _mxu(f["qg32"].astype(dtype), f["s16"], (1, 0)) + _mxu(p, u16, (1, 0))
    whole = _col(jnp.exp(f["g_end"]))                                       # (d_k, 1)
    return o, whole * s0 + _mxu(f["kd32"].astype(dtype), u16, (0, 0))


def _contract(da, dab, dp, q32, k32, G, dtype):
    """The cotangents through the decayed products of one chunk: with ``e_ij
    = exp(G_i - G_j)`` per channel, ``xa_i = sum_j da_ij k_j e_ij``, ``xp_i =
    sum_j dp_ij k_j e_ij`` and ``y_j = sum_i (dab_ij k_i + dp_ij q_i) e_ij``,
    each (C, d) float32; ``da``, ``dab``, ``dp``: (C, C), zero above the
    diagonal. The same two paths as ``_decayed_products``."""
    c, d = k32.shape
    offset, first = _diagonal_steps(c, d)
    lanes, s = _iota((c, c), 1), SUB

    def column(j, acc):
        xa, xp, y = acc
        at = offset == j
        decay = _decay(G, _block_sum(jnp.where(at, G, 0.0), s), offset >= j)
        kjd = _block_sum(jnp.where(at, k32, 0.0), s) * decay
        pick = lanes == first + j
        col = lambda m: jnp.sum(jnp.where(pick, m, 0.0), axis=1, keepdims=True)
        dp_j = col(dp)
        xa = xa + col(da) * kjd
        xp = xp + dp_j * kjd
        y = y + jnp.where(at, _block_sum((col(dab) * k32 + dp_j * q32) * decay, s), 0.0)
        return xa, xp, y

    zero = jnp.zeros((c, d), F32)
    xa, xp, y = jax.lax.fori_loop(0, s, column, (zero, zero, zero))
    # the earlier sub-chunks, a sub-chunk of rows at a time against its first row r
    xa_rows, xp_rows = [xa[:s]], [xp[:s]]
    for r in range(s, c, s):
        ref = _pick_row(G[r : r + s], 0)
        left = jnp.exp(G[r : r + s] - ref)
        right = _decay(ref, G, _iota((c, d), 0) < r)
        kr16 = (k32 * right).astype(dtype)
        da_r, dab_r, dp_r = (m[r : r + s].astype(dtype) for m in (da, dab, dp))       # (s, C)
        xa_rows.append(xa[r : r + s] + left * _mxu(da_r, kr16, (1, 0)))
        xp_rows.append(xp[r : r + s] + left * _mxu(dp_r, kr16, (1, 0)))
        y = y + right * (
            _mxu(dab_r, (k32[r : r + s] * left).astype(dtype), (0, 0))
            + _mxu(dp_r, (q32[r : r + s] * left).astype(dtype), (0, 0))
        )
    return jnp.concatenate(xa_rows, axis=0), jnp.concatenate(xp_rows, axis=0), y


def _chunk_backward(q, k, v, G, beta_row, t, p, s0, do, ds, dtype):
    """Cotangents of one chunk of one head. ``t``, ``p``: the forward's
    tables in ``dtype``; ``do``: (C, d_v); ``ds``: the cotangent of the state
    at the chunk's END. With ``dR = T^T dU`` the inverse needs no cotangent of
    its own: ``dA = -dR U^T``. Returns (dq, dk, dv, dG, dbeta row, the
    cotangent of the state the chunk STARTED from), float32."""
    c = k.shape[0]
    strict, incl, _ = _masks(c, c)
    f = _chunk_state(q, k, v, G, beta_row, t, s0, dtype)
    k32, q32, beta_col, s16 = f["k32"], f["q32"], f["beta_col"], f["s16"]
    u16, do16, ds16 = f["u"].astype(dtype), do.astype(dtype), ds.astype(dtype)
    kg16, qg16, kd16 = (f[name].astype(dtype) for name in ("kg32", "qg32", "kd32"))

    # ---- back through O = (exp(G) Q) S_0 + P U and S_C = Diag(exp(G_C)) S_0 + Kd^T U
    du = _mxu(p, do16, (0, 0)) + _mxu(kd16, ds16, (1, 0))                        # (C, d_v)
    dr = _mxu(t, du.astype(dtype), (0, 0))                                        # T^T dU
    bdr = beta_col * dr
    bdr16 = bdr.astype(dtype)
    dbeta_col = jnp.sum(dr * f["r_plain"], axis=1, keepdims=True)
    # R = beta (V - (exp(G) K) S_0)
    dkg = -_mxu(bdr16, s16, (1, 1))                                               # (C, d_k)
    dqg = _mxu(do16, s16, (1, 1))                                                 # dO S_0^T
    dkd = _mxu(u16, ds16, (1, 1))                                                 # U dS^T
    dq = f["since_start"] * dqg
    dk = f["since_start"] * dkg + f["to_end"] * dkd
    dG = dkg * f["kg32"] + dqg * f["qg32"] - dkd * f["kd32"]
    whole = jnp.exp(f["g_end"])                                                   # (1, d_k)
    at_end = (
        jnp.sum(dkd * f["kd32"], axis=0, keepdims=True)
        + whole * _row(jnp.sum(ds * s0, axis=1, keepdims=True))
    )
    dG = dG + jnp.where(_iota(dG.shape, 0) == c - 1, at_end, 0.0)
    # T = (I + A)^-1, A = beta (K K^T . D) below the diagonal; P = Q K^T . D on and below it
    da = jnp.where(strict, -_mxu(dr.astype(dtype), u16, (1, 1)), 0.0)
    dp = jnp.where(incl, _mxu(do16, u16, (1, 1)), 0.0)
    xa, xp, y = _contract(da, beta_col * da, dp, q32, k32, G, dtype)
    dbeta_col = dbeta_col + jnp.sum(k32 * xa, axis=1, keepdims=True)
    dq = dq + xp
    dk = dk + beta_col * xa + y
    dG = dG + beta_col * k32 * xa + q32 * xp - k32 * y
    # ---- the cotangent of the state this chunk started from
    ds0 = _mxu(qg16, do16, (0, 0)) + _col(whole) * ds - _mxu(kg16, bdr16, (0, 0))
    return dq, dk, bdr, dG, _row(dbeta_col), ds0


# ---- the Pallas kernels -----------------------------------------------------
#
# q, k, v and the cumulative log-decay keep the projection's layout, (b, n,
# heads x d) with heads in lanes: a grid step takes its head's 128-lane tile
# of each and XLA never sees a (.., h, d) array. ``beta`` arrives as (b,
# heads, chunks, C) float32: a head's whole table is one small block that
# stays in VMEM through its chunks, and a chunk reads (backward: writes) its
# ROW, whose column orientation is made in the kernel (``_col``).


def kda_kernels_eligible(chunk: int, d_k: int, d_v: int) -> bool:
    """The shapes the kernels are written for: keys and values whole lane
    tiles, a chunk whole sub-chunks, a power of two (the inverse's product)
    and at most a lane tile (``T`` and ``P`` share one row of 2C lanes)."""
    return (
        d_k % LANES == 0 and d_v % LANES == 0 and chunk % SUB == 0
        and chunk & (chunk - 1) == 0 and chunk <= LANES
    )


def _kda_chunk_tables_kernel(q_ref, k_ref, g_ref, beta_ref, tp_ref):
    c = pl.program_id(2)
    tp_ref[0, 0] = _chunk_tables(
        q_ref[0], k_ref[0], g_ref[0], beta_ref[0, 0, pl.ds(c, 1), :], q_ref.dtype
    )


def _kda_chunk_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, tp_ref, o_ref, s_ref, state):
    """One chunk of one head a grid step, the chunks of a head in sequence."""
    c, chunk = pl.program_id(2), q_ref.shape[1]

    @pl.when(c == 0)
    def _():
        state[...] = jnp.zeros_like(state)             # nothing before the sequence

    s0 = state[...]
    s_ref[0, 0, 0] = s0
    o, state[...] = _chunk_forward(
        q_ref[0], k_ref[0], v_ref[0], g_ref[0], beta_ref[0, 0, pl.ds(c, 1), :],
        tp_ref[0, 0, :, :chunk], tp_ref[0, 0, :, chunk:], s0, q_ref.dtype,
    )
    o_ref[0] = o.astype(o_ref.dtype)


def _kda_chunk_bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, tp_ref, s_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate,
):
    """Cotangents of one chunk of one head, the chunks from the LAST to the
    first: ``dstate`` carries the cotangent of the state at a chunk's end.
    Nothing is inverted: ``T`` and ``P`` are the forward's."""
    step, chunk = pl.program_id(2), q_ref.shape[1]
    c = pl.num_programs(2) - 1 - step

    @pl.when(step == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)            # nothing after the sequence

    dq, dk, dv, dg, dbeta, dstate[...] = _chunk_backward(
        q_ref[0], k_ref[0], v_ref[0], g_ref[0], beta_ref[0, 0, pl.ds(c, 1), :],
        tp_ref[0, 0, :, :chunk], tp_ref[0, 0, :, chunk:], s_ref[0, 0, 0], do_ref[0], dstate[...],
        q_ref.dtype,
    )
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dg_ref[0] = dg
    dbeta_ref[0, 0, pl.ds(c, 1), :] = dbeta


def _specs(beta, d_k, d_v, back: bool = False):
    """The grid (row, head, chunk) and the block of every kind of operand in
    it; ``back``: the chunks from the last to the first."""
    b, heads, chunks, chunk = beta.shape
    at = (lambda ci: chunks - 1 - ci) if back else (lambda ci: ci)
    return (b, heads, chunks), dict(
        key=pl.BlockSpec((1, chunk, d_k), lambda bi, hi, ci: (bi, at(ci), hi)),
        value=pl.BlockSpec((1, chunk, d_v), lambda bi, hi, ci: (bi, at(ci), hi)),
        table=pl.BlockSpec((1, 1, chunks, chunk), lambda bi, hi, ci: (bi, hi, 0, 0)),
        tp=pl.BlockSpec((1, 1, chunk, 2 * chunk), lambda bi, hi, ci: (bi, hi, at(ci), 0)),
        state=pl.BlockSpec((1, 1, 1, d_k, d_v), lambda bi, hi, ci: (bi, hi, at(ci), 0, 0)),
        carried=pltpu.VMEM((d_k, d_v), F32),
    )


def _widths(q, v, beta):
    b, heads, chunks, chunk = beta.shape
    d_k, d_v = q.shape[-1] // heads, v.shape[-1] // heads
    assert kda_kernels_eligible(chunk, d_k, d_v) and q.shape[1] == chunks * chunk, (
        q.shape, v.shape, beta.shape)
    return d_k, d_v


# each call a ``jax.jit`` of its own, as ``ops/gdn.py``'s: four mixers, each
# run forward, again under ``remat`` and backward, lower a kernel once a shape
_kernel_call = functools.partial(jax.jit, static_argnames=("interpret",))


@_kernel_call
def _tables_call(q, k, g, beta, *, interpret):
    """``T`` and ``P`` of every chunk and head, (b, heads, n, 2C) in the
    compute dtype."""
    d_k, _ = _widths(q, q, beta)
    grid, s = _specs(beta, d_k, d_k)
    b, heads, chunks, chunk = beta.shape
    return pl.pallas_call(
        _kda_chunk_tables_kernel,
        name="kda_chunk_tables",
        grid=grid,
        in_specs=[s["key"], s["key"], s["key"], s["table"]],
        out_specs=s["tp"],
        out_shape=jax.ShapeDtypeStruct((b, heads, chunks * chunk, 2 * chunk), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3, vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(q, k, g, beta)


@_kernel_call
def _fwd_call(q, k, v, g, beta, tp, *, interpret):
    d_k, d_v = _widths(q, v, beta)
    grid, s = _specs(beta, d_k, d_v)
    b, heads, chunks, _ = beta.shape
    return _mosaic_call(
        _kda_chunk_fwd_kernel, grid,
        [s["key"], s["key"], s["value"], s["key"], s["table"], s["tp"]], [s["value"], s["state"]],
        [jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct((b, heads, chunks, d_k, d_v), F32)],
        [s["carried"]], [q, k, v, g, beta, tp], interpret, name="kda_chunk_fwd",
    )


@_kernel_call
def _bwd_call(q, k, v, g, beta, tp, states, do, *, interpret):
    d_k, d_v = _widths(q, v, beta)
    grid, s = _specs(beta, d_k, d_v, back=True)
    return _mosaic_call(
        _kda_chunk_bwd_kernel, grid,
        [s["key"], s["key"], s["value"], s["key"], s["table"], s["tp"], s["state"], s["value"]],
        [s["key"], s["key"], s["value"], s["key"], s["table"]],
        [jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype), jax.ShapeDtypeStruct(g.shape, F32),
         jax.ShapeDtypeStruct(beta.shape, F32)],
        [s["carried"]], [q, k, v, g, beta, tp, states, do], interpret, name="kda_chunk_bwd",
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kda_chunks(q, k, v, g, beta, interpret):
    """The rule over whole chunks. q, k: (b, n, heads x d_k), normalised and
    scaled; v: (b, n, heads x d_v); g: (b, n, heads x d_k) float32, the
    cumulative log-decay INSIDE each chunk; beta: (b, heads, chunks, C)
    float32. Returns (b, n, heads x d_v) in the dtype of ``v``."""
    return _chunks_fwd_rule(q, k, v, g, beta, interpret)[0]


def _chunks_fwd_rule(q, k, v, g, beta, interpret):
    tp = kept_tables(_tables_call(q, k, g, beta, interpret=interpret))
    o, states = _fwd_call(q, k, v, g, beta, tp, interpret=interpret)
    return o, (q, k, v, g, beta, tp, states)


def _chunks_bwd_rule(interpret, res, do):
    return _bwd_call(*res, do, interpret=interpret)


kda_chunks.defvjp(_chunks_fwd_rule, _chunks_bwd_rule)


# ---- the same algorithm in XLA ---------------------------------------------


_dot = lambda spec, x, y: jnp.einsum(spec, x, y, preferred_element_type=F32)


def _kda_xla(q, k, v, g, beta, dtype):
    """``kda_chunks`` in XLA: a chunk at a time in a ``lax.scan`` (each chunk
    rematerialised in backward), its decays as one (C, C, d_k) float32
    tensor masked before the exponential. The oracle of the kernels' tests,
    and the form of every shape they are not written for."""
    b, heads, chunks, c = beta.shape
    split = lambda t: t.reshape(b, chunks, c, heads, -1).transpose(1, 0, 3, 2, 4)
    q, k, v = (split(t.astype(dtype)) for t in (q, k, v))                     # (chunks, b, h, C, d)
    g = split(g)
    beta = beta.transpose(2, 0, 1, 3)[..., None]                               # (chunks, b, h, C, 1)
    strict, incl, eye = _masks(c, c)

    @jax.checkpoint
    def chunk(s0, inp):
        q, k, v, G, beta = inp
        k32, q32 = k.astype(F32), q.astype(F32)
        decay = jnp.exp(jnp.where(incl[..., None], G[..., :, None, :] - G[..., None, :, :], -jnp.inf))
        kd = k32[..., None, :, :] * decay                                       # (b, h, C, C, d)
        a = jnp.where(strict, beta * jnp.sum(k32[..., :, None, :] * kd, axis=-1), 0.0)
        p = jnp.where(incl, jnp.sum(q32[..., :, None, :] * kd, axis=-1), 0.0)
        t = jax.scipy.linalg.solve_triangular(
            a + eye, jnp.broadcast_to(jnp.where(eye, 1.0, 0.0), a.shape), lower=True, unit_diagonal=True
        ).astype(dtype)
        since_start, g_end = jnp.exp(G), G[..., -1:, :]
        kg, qg = ((x * since_start).astype(dtype) for x in (k32, q32))
        kdec = (k32 * jnp.exp(g_end - G)).astype(dtype)
        s16 = s0.astype(dtype)
        r = beta * (v.astype(F32) - _dot("bhik,bhkv->bhiv", kg, s16))
        u = _dot("bhij,bhjv->bhiv", t, r.astype(dtype)).astype(dtype)
        o = _dot("bhik,bhkv->bhiv", qg, s16) + _dot("bhij,bhjv->bhiv", p.astype(dtype), u)
        whole = jnp.exp(g_end)[..., 0, :, None]                                # (b, h, d_k, 1)
        return whole * s0 + _dot("bhik,bhiv->bhkv", kdec, u), o

    zeros = jnp.zeros((b, heads, k.shape[-1], v.shape[-1]), F32)
    _, o = jax.lax.scan(chunk, zeros, (q, k, v, g, beta))
    return o.transpose(1, 0, 3, 2, 4).reshape(b, chunks * c, -1).astype(dtype)   # (chunks, b, h, C, d_v)


def kimi_delta_rule(q, k, v, g, beta, heads: int, chunk: int, dtype: Dtype = F32):
    """q, k: (b, n, heads x d_k), already normalised (q scaled too); v: (b, n,
    heads x d_v); g: (b, n, heads x d_k), the per-channel log-decay, <= 0;
    beta: (b, n, heads). Returns (b, n, heads x d_v) in ``dtype``. ``n`` need
    not be whole chunks: the tail is padded with positions that neither decay
    nor write (``g = 0``, ``beta = 0``)."""
    from .attention import _per_device  # the one shard_map rule of every Mosaic call

    b, n, _ = q.shape
    d_k, d_v = q.shape[-1] // heads, v.shape[-1] // heads
    pad = -n % chunk
    q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (q, k, v, g, beta))
    chunks = (n + pad) // chunk
    g = chunk_log_decay(g, chunk)
    beta = beta.astype(F32).reshape(b, chunks, chunk, heads).transpose(0, 3, 1, 2)
    if kda_kernels_eligible(chunk, d_k, d_v):
        interpret = kv_policy.pallas_interpret()
        kv_policy.record_route("forward/delta_rule", "kda_chunk", interpret)
        record_kept_tables(beta, dtype)
        o = _per_device(
            lambda *operands: kda_chunks(*operands, interpret),
            (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta),
        )
    else:
        kv_policy.record_route("forward/delta_rule", "xla")
        o = _kda_xla(q, k, v, g, beta, dtype)
    return o[:, :n]


def _log_uniform_dt_bias(key, shape, dtype=F32):
    """The inverse softplus of a step ``dt`` drawn log-uniform in [1e-3, 0.1]
    (the Mamba rule): ``softplus(dt_bias) = dt`` at a zero input."""
    dt = jnp.exp(jax.random.uniform(key, shape, F32, jnp.log(1e-3), jnp.log(0.1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class KimiDeltaAttention(nn.Module):
    """The KDA mixer (``kimi_linear``'s ``kda_layers``), no bias anywhere:

        [q | k | v] = silu(causal_conv(W_qkv u))          depthwise, width ``conv``
        g = -exp(A_log) softplus(W_fb W_fa u + dt_bias)   per key channel, rank ``head_dim``
        beta = sigmoid(W_b u)                            a head
        q <- q / |q| d^-1/2,  k <- k / |k|               per head
        o = the delta rule with the per-channel decay
        y = W_o [RMSNorm_d(o) . sigmoid(W_gb W_ga u)]    the norm a head, one gain over d

    The source's separate ``q_proj``, ``k_proj``, ``v_proj`` are one matrix
    here, ``in_proj_qkv`` (their columns side by side)."""

    dim: int
    heads: int = 32
    head_dim: int = 128
    conv: int = 4
    chunk: int = 64
    eps: float = 1e-5
    dtype: Dtype = F32
    param_dtype: Dtype = F32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        # ``deterministic``: the trunk's uniform half-block argument; no dropout here
        b, n, _ = x.shape
        h, d = self.heads, self.head_dim
        width = h * d
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, name=name, dtype=self.dtype, param_dtype=self.param_dtype,
        )
        with jax.named_scope("linattn.proj"):
            qkv = dense(3 * width, "in_proj_qkv")(x)
            strength = dense(h, "in_proj_b")(x)
        # the family's own initial values: A uniform in [1, 16], dt by the Mamba rule
        A_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0)), (h,),
        )
        dt_bias = self.param("dt_bias", _log_uniform_dt_bias, (width,), self.param_dtype)
        gain = self.param("norm_scale", nn.initializers.ones, (d,), self.param_dtype)

        with jax.named_scope("linattn.gate"):
            g = channel_log_decay(dense(width, "f_b")(dense(d, "f_a")(x)), A_log, dt_bias, h)
            gate = dense(width, "g_b")(dense(d, "g_a")(x))
        with jax.named_scope("linattn.conv"):
            conv = CausalConv1D(self.conv, self.dtype, self.param_dtype, use_bias=False, name="conv")
            q, k, v = conv(qkv, (width, width, width))
        with jax.named_scope("linattn.kda"):
            heads_of = lambda t: t.reshape(b, n, h, d)
            q = (l2norm(heads_of(q)) * d**-0.5).astype(self.dtype).reshape(b, n, width)
            k = l2norm(heads_of(k)).astype(self.dtype).reshape(b, n, width)
            o = kimi_delta_rule(q, k, v, g, write_strength(strength), h, self.chunk, self.dtype)
        with jax.named_scope("linattn.norm"):
            y = rms_norm(o.reshape(b, n, h, d), gain, self.eps).reshape(b, n, width)
            y = (y * jax.nn.sigmoid(gate.astype(F32))).astype(self.dtype)
        with jax.named_scope("linattn.proj"):
            return dense(self.dim, "out_proj")(y)
