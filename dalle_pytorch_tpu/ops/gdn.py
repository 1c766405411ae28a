"""Gated delta rule linear attention (Gated DeltaNet): the mixer, its chunked
form in XLA and as three Pallas kernels.

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
inverse of a unit lower-triangular matrix, built from products the MXU takes
(float32 at ``HIGHEST``), exactly: ``A`` is nilpotent, so the diagonal blocks
of 8 are ``(I - A)(I + A^2)(I + A^4)``, and a block twice the size follows
from two of half the size by ``T <- T - T L T`` with ``L`` the block between
them (``_unit_lower_inverse``).

Two forms of one algorithm, chosen from the shape
(``delta_rule_kernels_eligible``; no switch) and recorded at the route site
``forward/delta_rule``. Where keys and values are whole lane tiles it is three
kernels behind one ``jax.custom_vjp``, split where the state enters:

- ``gdn_chunk_tables`` computes what a chunk computes WITHOUT the state: ``D``,
  ``K K^T``, ``Q K^T``, ``T`` and ``P = tril(Q K^T . D)``. Its rows come a
  TILE at a time, ``128 // C`` whole chunks (``chunks_a_tile``), so that a
  product has the MXU's 128 rows, and no grid axis is sequential: a grid step
  is one (row, key head, up to ``TILES_A_STEP`` tiles) and BOTH value heads
  the key head serves (one fetch of ``q`` and ``k``, one ``K K^T``); a tile's
  chunks are inverted as ONE block-diagonal ``(128, 128)`` matrix, and the
  step's chains (tiles x value heads) stand side by side in one call of the
  inverse. It writes ``T`` and ``P`` in the compute dtype as one ``(b, heads,
  n, 2C)`` table: a position's row of ``T`` in the lanes ``[0, C)``, of ``P``
  in ``[C, 2C)``.
- ``gdn_chunk_fwd`` keeps the state and nothing else: a grid step is one (row,
  the value heads of up to ``KEY_HEADS_A_STEP`` key heads, chunk), the chunks
  of a head in sequence with the heads' states in VMEM scratch; a chunk is the
  five products that read the state or follow from one that does (``Kg S_0``,
  ``T (beta R)``, ``Qg S_0``, ``P U``, ``Kd^T U``), and the heads' chains are
  independent. It writes the output and the state each chunk STARTS from
  (float32, ``d_k x d_v`` a chunk and head).
- ``gdn_chunk_bwd`` inverts nothing: the same grid from the last chunk to the
  first with the states' cotangents in scratch, ``T`` and ``P`` read, only
  ``D`` and ``K K^T`` rebuilt; a key head's ``dq`` and ``dk`` are summed over
  its value heads in float32 before they are written.

The forward rule's residuals beside the operands are that table and those
states. A block's checkpoint under ``remat`` keeps the table by name
(``DELTA_RESIDUAL_NAMES``), so the rerun forward runs ``gdn_chunk_fwd`` alone
and writes the states again. A tail that does not fill a tile is padded with
chunks that neither decay nor write. Every other shape keeps the XLA form
(``lax.scan`` over the chunks, the inverse by ``solve_triangular``; its
backward is autodiff), which is also the oracle the kernels are tested
against. The sequential recurrence itself is the benchmark's plain reference
(``benchmarks/reference_gdn.py``).

Training and whole-sequence evaluation only: a single-token step that carries
``S`` and the convolution's tail is serving's (ROADMAP R13).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kv_policy
from .layers import rms_norm
from .moe import _CHECKPOINTED_BLOCK
from .ssm import LANES, VMEM_LIMIT_BYTES, CausalConv1D, _mosaic_call, _mxu

Dtype = Any

HIGHEST = jax.lax.Precision.HIGHEST

# the name a block's checkpoint keeps of the delta rules' kernels
# (models/transformer.py:_block_checkpoint, beside the flash kernels'
# KERNEL_RESIDUAL_NAMES): the state-free kernel's ``T | P`` table, here and in
# ops/kda.py. It costs O(n C d) to rebuild and O(n 2C) to hold, and it is the
# kernel's one result: kept, the rebuilt forward runs no tables kernel.
# Outside a checkpoint the name lowers to nothing.
DELTA_RESIDUAL_NAMES = ("delta_tables",)


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


def _grid(rows: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), axis) for axis in (0, 1))


def _same_block(rows: int, block: int):
    """Where (i, j) of a (rows, rows) tile lie in one diagonal block of
    ``block`` (a power of two wherever a tile holds several)."""
    if rows == block:
        return True
    i, j = (jnp.right_shift(x, block.bit_length() - 1) for x in _grid(rows))
    return i == j


def _masks(rows: int, chunk: int):
    """(strictly below, on or below, on) the diagonal of a (rows, rows) tile
    of ``rows // chunk`` chunks, below only inside a chunk's own block: a
    tile of several chunks is block-diagonal."""
    (i, j), same = _grid(rows), _same_block(rows, chunk)
    return same & (i > j), same & (i >= j), i == j


def _col(row):
    """(1, C) -> (C, 1) without a transpose: the diagonal of the row spread
    over the sublanes."""
    c = row.shape[1]
    return jnp.sum(jnp.where(_masks(c, c)[2], row, 0.0), axis=1, keepdims=True)


def _row(col):
    c = col.shape[0]
    return jnp.sum(jnp.where(_masks(c, c)[2], col, 0.0), axis=0, keepdims=True)


def _last(row):
    """The last entry of a (1, C) row as (1, 1), by a masked sum: a slice at
    lane C - 1 keeps its offset, and Mosaic spreads no such value over both
    sublanes and lanes."""
    c = row.shape[1]
    last = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
    return jnp.sum(jnp.where(last, row, 0.0), axis=1, keepdims=True)


def _unit_lower_inverse(a, block=None):
    """``(I + a)^-1`` for (.., R, R) float32 ``a`` that is strictly lower
    triangular inside diagonal blocks of ``block`` (a power of two; default
    R) and zero outside them; the result is block-diagonal alike. Leading axes
    are independent chains whose products stand side by side in the program.

    The diagonal blocks of 8 (a float32 sublane tile) first: ``a`` is
    nilpotent, so ``(I + a)^-1 = (I - a)(I + a^2)(I + a^4)`` exactly. Then
    block size ``s`` to ``2 s`` until ``block``: with ``L`` the lower-left
    ``s x s`` block of every ``2 s`` block and ``T`` the inverse so far,
    ``T <- T - T L T`` (``L T L = 0``). Both products of such a step have
    non-zero rows only in the lower half of each ``2 s`` block, whole sublane
    tiles: those rows alone pass through the MXU. Float32 at ``HIGHEST``."""
    r, lead = a.shape[-1], tuple(range(a.ndim - 2))
    block = block or r
    assert block & (block - 1) == 0 and r % block == 0, (a.shape, block)
    dot = lambda x, y: jax.lax.dot_general(
        x, y, (((x.ndim - 1,), (y.ndim - 2,)), (lead, lead)),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )
    base = min(block, 8)
    power = -jnp.where(_same_block(r, base), a, 0.0)
    out = jnp.where(_masks(r, r)[2], 1.0, 0.0) + power
    for _ in range(max(base - 1, 1).bit_length() - 1):
        power = dot(power, power)
        out = out + dot(out, power)
    s = base
    while s < block:
        lower = range(s, r, 2 * s)                      # where each 2s block's lower half starts
        rows = lambda x: jnp.concatenate([x[..., lo : lo + s, :] for lo in lower], axis=-2)
        zeros = jnp.zeros((*a.shape[:-2], s, r), jnp.float32)
        spread = lambda y: jnp.concatenate(
            [piece for n in range(len(lower)) for piece in (zeros, y[..., n * s : (n + 1) * s, :])], axis=-2
        )
        below = jnp.where(_same_block(r, 2 * s) & ~_same_block(r, s), a, 0.0)  # L
        out = out - spread(dot(rows(out), spread(dot(rows(below), out))))
        s *= 2
    return out


def _tile_decay(g_row, chunk: int):
    """``D`` of tiles of whole chunks: ``exp(G_i - G_j)`` on and below the
    diagonal of each chunk's own block, 0 elsewhere. ``g_row``: (.., 1, R)."""
    r = g_row.shape[-1]
    g_col = jnp.sum(jnp.where(_masks(r, r)[2], g_row, 0.0), axis=-1, keepdims=True)
    # masked BEFORE the exponential: above the diagonal the difference is positive
    return jnp.exp(jnp.where(_masks(r, chunk)[1], g_col - g_row, -jnp.inf))


def _fold(x, chunk: int):
    """Block-diagonal (.., R, R) tiles as (.., R, C): each chunk's rows keep
    their own block (every other block of a row is zero)."""
    return sum(x[..., lo : lo + chunk] for lo in range(0, x.shape[-1], chunk))


def _tile_tables(qk, kk, g_rows, beta_rows, chunk: int, dtype):
    """What tiles of ``R // chunk`` whole chunks compute WITHOUT the state,
    all of a grid step's (tile, value head) pairs at once along the leading
    axis: ``qk``, ``kk``: their key head's ``Q K^T`` and ``K K^T``, (m, R, R)
    float32; ``g_rows``, ``beta_rows``: (m, 1, R) float32. Returns (m, R, 2C)
    in ``dtype``: a chunk's rows of ``T = (I + A)^-1`` in the lanes [0, C), of
    ``P = tril(Q K^T . D)`` in [C, 2C). ONE call of the inverse: every pair's
    chain side by side."""
    r = g_rows.shape[-1]
    strict, incl, eye = _masks(r, chunk)
    decay = _tile_decay(g_rows, chunk)
    beta_col = jnp.sum(jnp.where(eye, beta_rows, 0.0), axis=-1, keepdims=True)
    t = _unit_lower_inverse(beta_col * jnp.where(strict, kk * decay, 0.0), chunk)
    p = jnp.where(incl, qk * decay, 0.0)
    return jnp.concatenate([_fold(t, chunk), _fold(p, chunk)], axis=-1).astype(dtype)


# a chunk's algebra is a ``jax.jit`` each way: a grid step holds several heads,
# and the kernel's body is then traced once a shape and not once a head
_chunk_jit = functools.partial(jax.jit, static_argnames=("dtype",))


def _chunk_state(q, k, v, g_row, beta_row, t, s0, dtype):
    """What one chunk of one value head computes from the state it starts
    with, by name (the backward rebuilds it). q, k: (C, d_k), v: (C, d_v),
    ``t``: (C, C) in ``dtype``; ``g_row``, ``beta_row``: (1, C) float32;
    ``s0``: (d_k, d_v) float32."""
    g_col, beta_col = _col(g_row), _col(beta_row)
    since_start = jnp.exp(g_col)                                        # (C, 1)
    to_end = jnp.exp(_last(g_row) - g_col)                               # (C, 1)
    k32, q32 = k.astype(jnp.float32), q.astype(jnp.float32)
    kg32, qg32, kd32 = k32 * since_start, q32 * since_start, k32 * to_end
    s16 = s0.astype(dtype)
    r_plain = v.astype(jnp.float32) - _mxu(kg32.astype(dtype), s16, (1, 0))
    u = _mxu(t, (beta_col * r_plain).astype(dtype), (1, 0))                 # (C, d_v)
    return dict(
        beta_col=beta_col, since_start=since_start, to_end=to_end,
        kg32=kg32, qg32=qg32, kd32=kd32, s16=s16, r_plain=r_plain, u=u,
    )


@_chunk_jit
def _chunk_forward(q, k, v, g_row, beta_row, t, p, s0, dtype):
    """(the chunk's output (C, d_v) float32, the state at its end); ``p``:
    (C, C) in ``dtype``."""
    f = _chunk_state(q, k, v, g_row, beta_row, t, s0, dtype)
    u16 = f["u"].astype(dtype)
    o = _mxu(f["qg32"].astype(dtype), f["s16"], (1, 0)) + _mxu(p, u16, (1, 0))
    whole = jnp.broadcast_to(jnp.exp(_last(g_row)), (1, s0.shape[1]))
    return o, whole * s0 + _mxu(f["kd32"].astype(dtype), u16, (0, 0))


@_chunk_jit
def _chunk_backward(q, k, v, g_row, beta_row, t, p, kk, s0, do, ds, dtype):
    """Cotangents of one chunk of one value head. ``t``, ``p``: the forward's
    tables in ``dtype``; ``kk``: ``K K^T`` (C, C) float32, once a key head;
    ``do``: (C, d_v); ``ds``: the cotangent of the state at the chunk's END.
    With ``dR = T^T dU`` the inverse needs no cotangent of its own: ``dA =
    -dR U^T``. Returns (dq, dk, dv, dg row, dbeta row, the cotangent of the
    state the chunk STARTED from), float32."""
    n = q.shape[0]
    strict, incl, _ = _masks(n, n)
    f = _chunk_state(q, k, v, g_row, beta_row, t, s0, dtype)
    beta_col, since_start, to_end = f["beta_col"], f["since_start"], f["to_end"]
    decay = _tile_decay(g_row, n)
    a_plain = jnp.where(strict, kk * decay, 0.0)                                 # without beta
    p32 = p.astype(jnp.float32)
    s16, u16 = f["s16"], f["u"].astype(dtype)
    do16, ds16 = do.astype(dtype), ds.astype(dtype)
    kd16, kg16, qg16 = (f[name].astype(dtype) for name in ("kd32", "kg32", "qg32"))

    # ---- back through O = (exp(G) Q) S_0 + P U and S_C = exp(G_C) S_0 + Kd^T U
    du = _mxu(p, do16, (0, 0)) + _mxu(kd16, ds16, (1, 0))                        # (C, d_v)
    dr = _mxu(t, du.astype(dtype), (0, 0))                                       # T^T dU
    dr16, bdr = dr.astype(dtype), beta_col * dr
    bdr16 = bdr.astype(dtype)
    dbeta_col = jnp.sum(dr * f["r_plain"], axis=1, keepdims=True)
    # R = beta (V - (exp(G) K) S_0)
    dkg = -_mxu(bdr16, s16, (1, 1))                                              # (C, d_k)
    dk = since_start * dkg
    dg_col = jnp.sum(dkg * f["kg32"], axis=1, keepdims=True)
    # T = (I + A)^-1, A = beta (K K^T . D) below the diagonal
    da = jnp.where(strict, -_mxu(dr16, u16, (1, 1)), 0.0)
    daa = da * a_plain
    dbeta_col += jnp.sum(daa, axis=1, keepdims=True)
    daa = daa * beta_col
    dg_col += jnp.sum(daa, axis=1, keepdims=True)
    dg_row = -jnp.sum(daa, axis=0, keepdims=True)
    dm16 = (da * beta_col * decay).astype(dtype)
    dk += _mxu(dm16, k, (1, 0)) + _mxu(dm16, k, (0, 0))
    # P = Q K^T . D on and below the diagonal
    dp = jnp.where(incl, _mxu(do16, u16, (1, 1)), 0.0)
    dpp = dp * p32
    dg_col += jnp.sum(dpp, axis=1, keepdims=True)
    dg_row -= jnp.sum(dpp, axis=0, keepdims=True)
    dqk16 = (dp * decay).astype(dtype)
    dos = _mxu(do16, s16, (1, 1))                                                # dO S_0^T
    dq = _mxu(dqk16, k, (1, 0)) + since_start * dos
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
    dg = dg_row + _row(dg_col) + jnp.where(last, at_end, 0.0)
    # ---- the cotangent of the state this chunk started from
    ds0 = (
        _mxu(qg16, do16, (0, 0)) + jnp.broadcast_to(whole, (1, ds.shape[1])) * ds
        - _mxu(kg16, bdr16, (0, 0))
    )
    return dq, dk, bdr, dg, _row(dbeta_col), ds0


# ---- the Pallas kernels -----------------------------------------------------
#
# q, k, v keep the projection's layout, (b, n, heads x width) with heads in
# lanes: a grid step takes the 128-lane tiles of its heads, its key heads' for
# q and k (a key head serves ``ratio`` value heads), and XLA never sees a
# (.., h, d) array. The per-position scalars arrive as (b, heads, chunks, C)
# float32: the whole table of a step's heads is one small block that stays in
# VMEM through their chunks, and a chunk reads (backward: writes) its ROW; the
# state-free kernel, whose rows come a TILE at a time (``128 // C`` whole
# chunks, one where a chunk is 128 positions or more: what fills the MXU's
# rows where a chunk alone does not), reads the same memory as (b, heads,
# tiles, R), a tile's row. The column orientation is made in the kernel
# (``_col``), so nothing is transposed.


def delta_rule_kernels_eligible(chunk: int, d_k: int, d_v: int) -> bool:
    """The shapes the kernels are written for: keys and values whole lane
    tiles, a chunk whole sublane tiles of the compute dtype and a power of
    two (the inverse's product)."""
    return (
        d_k % LANES == 0 and d_v % LANES == 0 and chunk % 16 == 0
        and chunk & (chunk - 1) == 0
    )


def chunks_a_tile(chunk: int) -> int:
    """Whole chunks in the rows a grid step takes: what fills 128."""
    return max(LANES // chunk, 1)


def _divisor(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is at most ``most``."""
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


def _gdn_chunk_tables_kernel(q_ref, k_ref, g_ref, beta_ref, tp_ref, *, chunk, tiles):
    """The state-free half: one key head, ``tiles`` tiles of rows, every value
    head the key head serves; no grid axis is sequential."""
    rows = q_ref.shape[1] // tiles
    first, heads = pl.program_id(2) * tiles, range(g_ref.shape[1])
    at = [slice(i * rows, (i + 1) * rows) for i in range(tiles)]
    pairs = [(i, h) for i in range(tiles) for h in heads]
    of_key_head = lambda x_ref: [_mxu(x_ref[0, at[i]], k_ref[0, at[i]], (1, 1)) for i in range(tiles)]
    qk, kk = of_key_head(q_ref), of_key_head(k_ref)                 # once a key head
    row = lambda ref: jnp.stack([ref[0, h, pl.ds(first + i, 1), :] for i, h in pairs])
    tables = _tile_tables(
        jnp.stack([qk[i] for i, _ in pairs]), jnp.stack([kk[i] for i, _ in pairs]),
        row(g_ref), row(beta_ref), chunk, q_ref.dtype,
    )
    for n, (i, h) in enumerate(pairs):
        tp_ref[0, h, at[i]] = tables[n]


def _head_operands(refs, h, ratio, d_k, d_v):
    """(q, k, v, t, p) of the step's chunk for its value head ``h``, from
    (q_ref, k_ref, v_ref, tp_ref)."""
    q_ref, k_ref, v_ref, tp_ref = refs
    key, chunk = slice((h // ratio) * d_k, (h // ratio + 1) * d_k), q_ref.shape[1]
    return (
        q_ref[0, :, key], k_ref[0, :, key], v_ref[0, :, h * d_v : (h + 1) * d_v],
        tp_ref[0, h, :, :chunk], tp_ref[0, h, :, chunk:],
    )


def _gdn_chunk_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, tp_ref, o_ref, s_ref, state, *, ratio):
    """The half that carries the state: one chunk of ``heads`` value heads a
    grid step, the chunks of a head in sequence; the heads' chains are
    independent and stand side by side. Only the five products that read the
    state or follow from one that does."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        state[...] = jnp.zeros_like(state)             # nothing before the sequence

    heads, _, d_k, d_v = s_ref.shape[1:]
    for h in range(heads):
        q, k, v, t, p = _head_operands((q_ref, k_ref, v_ref, tp_ref), h, ratio, d_k, d_v)
        s0 = state[h]
        s_ref[0, h, 0] = s0
        o, state[h] = _chunk_forward(
            q, k, v, g_ref[0, h, pl.ds(c, 1), :], beta_ref[0, h, pl.ds(c, 1), :], t, p, s0, q_ref.dtype
        )
        o_ref[0, :, h * d_v : (h + 1) * d_v] = o.astype(o_ref.dtype)


def _gdn_chunk_bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, tp_ref, s_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, *, ratio,
):
    """Cotangents of one chunk of ``heads`` value heads, the chunks from the
    LAST to the first: ``dstate`` carries the cotangent of the state at a
    chunk's end. Nothing is inverted: ``T`` and ``P`` are the forward's. A
    key head's cotangent is summed over the value heads it serves here, in
    float32."""
    step, chunks = pl.program_id(2), pl.num_programs(2)
    c = chunks - 1 - step

    @pl.when(step == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)            # nothing after the sequence

    heads, _, d_k, d_v = s_ref.shape[1:]
    for key_head in range(heads // ratio):
        key = slice(key_head * d_k, (key_head + 1) * d_k)
        kk = _mxu(k_ref[0, :, key], k_ref[0, :, key], (1, 1))
        dq, dk = 0.0, 0.0
        for h in range(key_head * ratio, (key_head + 1) * ratio):
            q, k, v, t, p = _head_operands((q_ref, k_ref, v_ref, tp_ref), h, ratio, d_k, d_v)
            dq_h, dk_h, dv, dg, dbeta, dstate[h] = _chunk_backward(
                q, k, v, g_ref[0, h, pl.ds(c, 1), :], beta_ref[0, h, pl.ds(c, 1), :], t, p, kk,
                s_ref[0, h, 0], do_ref[0, :, h * d_v : (h + 1) * d_v], dstate[h], q_ref.dtype,
            )
            dq, dk = dq + dq_h, dk + dk_h
            dv_ref[0, :, h * d_v : (h + 1) * d_v] = dv.astype(dv_ref.dtype)
            dg_ref[0, h, pl.ds(c, 1), :] = dg
            dbeta_ref[0, h, pl.ds(c, 1), :] = dbeta
        dq_ref[0, :, key] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, key] = dk.astype(dk_ref.dtype)


def _sizes(q, v, g, key_heads):
    b, _, heads, chunks, chunk = (q.shape[0], *g.shape)
    d_k, d_v = q.shape[-1] // key_heads, v.shape[-1] // heads
    assert heads % key_heads == 0 and delta_rule_kernels_eligible(chunk, d_k, d_v), (q.shape, v.shape, g.shape)
    assert q.shape[1] == chunks * chunk, (q.shape, g.shape)
    return b, heads, chunks, chunk, d_k, d_v, heads // key_heads


# how much independent work a grid step holds, from the shape: the state's
# pass takes the value heads of up to KEY_HEADS_A_STEP key heads (their chains
# interleave), the state-free kernel up to TILES_A_STEP tiles of one key head.
# More of either is faster by little and is paid as Python tracing of a longer
# body in every process's set-up (PERF.md section 6, PR 39)
KEY_HEADS_A_STEP = 4
TILES_A_STEP = 4


def _specs(b, heads, chunks, chunk, d_k, d_v, ratio, back: bool = False):
    """The grid (row, group of value heads, chunk) of the pass that carries
    the state and the block of every kind of operand in it; ``back``: the
    chunks from the last to the first."""
    key_heads = _divisor(heads // ratio, KEY_HEADS_A_STEP)
    step_heads = key_heads * ratio
    at = (lambda ci: chunks - 1 - ci) if back else (lambda ci: ci)
    return (b, heads // step_heads, chunks), dict(
        key=pl.BlockSpec((1, chunk, key_heads * d_k), lambda bi, hi, ci: (bi, at(ci), hi)),
        value=pl.BlockSpec((1, chunk, step_heads * d_v), lambda bi, hi, ci: (bi, at(ci), hi)),
        table=pl.BlockSpec((1, step_heads, chunks, chunk), lambda bi, hi, ci: (bi, hi, 0, 0)),
        tp=pl.BlockSpec((1, step_heads, chunk, 2 * chunk), lambda bi, hi, ci: (bi, hi, at(ci), 0)),
        state=pl.BlockSpec((1, step_heads, 1, d_k, d_v), lambda bi, hi, ci: (bi, hi, at(ci), 0, 0)),
        carried=pltpu.VMEM((step_heads, d_k, d_v), jnp.float32),
    )


# each call a ``jax.jit`` of its own, as ``ops/ssm.py``'s: three mixers, each
# run forward, again under ``remat`` and backward, lower a kernel once a shape
_kernel_call = functools.partial(jax.jit, static_argnames=("key_heads", "interpret"))


@_kernel_call
def _tables_call(q, k, g, beta, *, key_heads, interpret):
    """``T`` and ``P`` of every chunk and value head, (b, heads, n, 2C) in
    the compute dtype: ``_tile_tables``'s layout."""
    b, heads, chunks, chunk = (q.shape[0], *g.shape[1:])
    d_k, ratio, group = q.shape[-1] // key_heads, heads // key_heads, chunks_a_tile(chunk)
    assert q.shape[1] == chunks * chunk and chunks % group == 0, (q.shape, g.shape)
    tiles, rows = chunks // group, group * chunk
    per_step = _divisor(tiles, TILES_A_STEP)
    key = pl.BlockSpec((1, per_step * rows, d_k), lambda bi, ki, ti: (bi, ti, ki))
    table = pl.BlockSpec((1, ratio, tiles, rows), lambda bi, ki, ti: (bi, ki, 0, 0))
    as_tiles = lambda t: t.reshape(b, heads, tiles, rows)
    return pl.pallas_call(
        functools.partial(_gdn_chunk_tables_kernel, chunk=chunk, tiles=per_step),
        name="gdn_chunk_tables",
        grid=(b, key_heads, tiles // per_step),
        in_specs=[key, key, table, table],
        out_specs=pl.BlockSpec((1, ratio, per_step * rows, 2 * chunk), lambda bi, ki, ti: (bi, ki, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((b, heads, chunks * chunk, 2 * chunk), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3, vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(q, k, as_tiles(g), as_tiles(beta))


@_kernel_call
def _fwd_call(q, k, v, g, beta, tp, *, key_heads, interpret):
    b, heads, chunks, chunk, d_k, d_v, ratio = sizes = _sizes(q, v, g, key_heads)
    grid, s = _specs(*sizes)
    return _mosaic_call(
        functools.partial(_gdn_chunk_fwd_kernel, ratio=ratio), grid,
        [s["key"], s["key"], s["value"], s["table"], s["table"], s["tp"]], [s["value"], s["state"]],
        [jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct((b, heads, chunks, d_k, d_v), jnp.float32)],
        [s["carried"]], [q, k, v, g, beta, tp], interpret, name="gdn_chunk_fwd",
    )


@_kernel_call
def _bwd_call(q, k, v, g, beta, tp, states, do, *, key_heads, interpret):
    *_, ratio = sizes = _sizes(q, v, g, key_heads)
    grid, s = _specs(*sizes, back=True)
    table = jax.ShapeDtypeStruct(g.shape, jnp.float32)
    return _mosaic_call(
        functools.partial(_gdn_chunk_bwd_kernel, ratio=ratio), grid,
        [s["key"], s["key"], s["value"], s["table"], s["table"], s["tp"], s["state"], s["value"]],
        [s["key"], s["key"], s["value"], s["table"], s["table"]],
        [jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype), table, table],
        [s["carried"]], [q, k, v, g, beta, tp, states, do], interpret, name="gdn_chunk_bwd",
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def delta_rule_chunks(q, k, v, g, beta, key_heads, interpret):
    """The rule over whole tiles of chunks (``chunks_a_tile``). q, k: (b, n,
    key heads x d_k), normalised and scaled; v: (b, n, heads x d_v); g: (b,
    heads, chunks, C) float32, the cumulative log-decay INSIDE each chunk;
    beta: likewise. Returns (b, n, heads x d_v) in the dtype of ``v``."""
    return _chunks_fwd_rule(q, k, v, g, beta, key_heads, interpret)[0]


def kept_tables(tp):
    """The tables kernel's result under ``DELTA_RESIDUAL_NAMES``, as the
    residuals hold it."""
    return checkpoint_name(tp, DELTA_RESIDUAL_NAMES[0])


def record_kept_tables(beta, dtype):
    """Inside a block's checkpoint: route ``remat/delta_tables`` with the block
    and the bytes of the table it keeps, ``(b, heads, chunks x C, 2C)`` in
    ``dtype`` for ``beta`` of ``(b, heads, chunks, C)``."""
    block = _CHECKPOINTED_BLOCK.get()
    if block is not None:
        kept = beta.size * 2 * beta.shape[-1] * jnp.dtype(dtype).itemsize
        kv_policy.record_route("remat/delta_tables", "saved", block=block, bytes=kept)


def _chunks_fwd_rule(q, k, v, g, beta, key_heads, interpret):
    tp = kept_tables(_tables_call(q, k, g, beta, key_heads=key_heads, interpret=interpret))
    o, states = _fwd_call(q, k, v, g, beta, tp, key_heads=key_heads, interpret=interpret)
    return o, (q, k, v, g, beta, tp, states)


def _chunks_bwd_rule(key_heads, interpret, res, do):
    return _bwd_call(*res, do, key_heads=key_heads, interpret=interpret)


delta_rule_chunks.defvjp(_chunks_fwd_rule, _chunks_bwd_rule)


# ---- the same algorithm in XLA ---------------------------------------------


_dot = lambda spec, x, y: jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)


def _split_heads(t, g, heads: int, repeat: int = 1):
    """(b, n, heads x d) as (b, heads x repeat, chunks, C, d), the chunks of ``g``."""
    b, _, chunks, c = g.shape
    return jnp.repeat(t.reshape(b, chunks, c, heads, -1).transpose(0, 3, 1, 2, 4), repeat, axis=1)


def _tables_xla(q, k, g, beta, dtype):
    """(``T``, ``P``) of every chunk, (b, heads, chunks, C, C) in ``dtype``,
    the inverse by ``solve_triangular``. q, k: a value head's, (b, heads,
    chunks, C, d_k)."""
    strict, incl, eye = _masks(g.shape[-1], g.shape[-1])
    decay = jnp.exp(jnp.where(incl, g[..., :, None] - g[..., None, :], -jnp.inf))
    a = jnp.where(strict, beta[..., :, None] * _dot("bhnid,bhnjd->bhnij", k, k) * decay, 0.0)
    t = jax.scipy.linalg.solve_triangular(
        a + eye, jnp.broadcast_to(jnp.where(eye, 1.0, 0.0), a.shape), lower=True, unit_diagonal=True
    )
    p = jnp.where(incl, _dot("bhnid,bhnjd->bhnij", q, k) * decay, 0.0)
    return t.astype(dtype), p.astype(dtype)


def _delta_rule_xla(q, k, v, g, beta, key_heads: int, dtype):
    """``delta_rule_chunks`` in XLA: every chunk's matrices at once, the
    state handed from chunk to chunk in a ``lax.scan``. The oracle of the
    kernels' tests, and the form of every shape they are not written for."""
    b, n, _ = q.shape
    heads = g.shape[1]
    ratio = heads // key_heads
    q, k = (_split_heads(t.astype(dtype), g, key_heads, ratio) for t in (q, k))
    v = _split_heads(v.astype(dtype), g, heads)
    g_col, beta_col = g[..., :, None], beta[..., :, None]
    since_start, to_end = jnp.exp(g_col), jnp.exp(g[..., -1:, None] - g_col)
    t, p = _tables_xla(q, k, g, beta, dtype)
    f32 = lambda x: x.astype(jnp.float32)
    kg, qg, kd = ((f32(x) * w).astype(dtype) for x, w in ((k, since_start), (q, since_start), (k, to_end)))
    whole = jnp.exp(g[..., -1])                                         # (b, h, chunks)

    def chunk(s0, inp):
        kg, qg, kd, v, t, p, beta_col, whole = inp
        s16 = s0.astype(dtype)
        r = beta_col * (f32(v) - _dot("bhik,bhkv->bhiv", kg, s16))
        u = _dot("bhij,bhjv->bhiv", t, r.astype(dtype)).astype(dtype)
        o = _dot("bhik,bhkv->bhiv", qg, s16) + _dot("bhij,bhjv->bhiv", p, u)
        return whole[..., None, None] * s0 + _dot("bhik,bhiv->bhkv", kd, u), o

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
    (``g = 0``, ``beta = 0``), for the kernels up to whole tiles of chunks
    (``chunks_a_tile``)."""
    from .attention import _per_device  # the one shard_map rule of every Mosaic call

    b, n, heads = g.shape
    d_k, d_v = q.shape[-1] // key_heads, v.shape[-1] // heads
    kernels = delta_rule_kernels_eligible(chunk, d_k, d_v)
    pad = -n % (chunk * chunks_a_tile(chunk) if kernels else chunk)
    q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (q, k, v, g, beta))
    chunks = (n + pad) // chunk
    table = lambda t: t.astype(jnp.float32).reshape(b, chunks, chunk, heads).transpose(0, 3, 1, 2)
    g, beta = chunk_log_decay(table(g)), table(beta)
    if kernels:
        interpret = kv_policy.pallas_interpret()
        kv_policy.record_route("forward/delta_rule", "gdn_chunk", interpret)
        record_kept_tables(beta, dtype)
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
