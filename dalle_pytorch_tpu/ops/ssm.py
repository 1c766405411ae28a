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
what broke rotary: PERF.md §7).

The scan has two forms of one algorithm, chosen from the shape
(``ssd_kernels_eligible``; no switch) and recorded at the route site
``forward/ssd``. Where the chunk, the state and a block of heads are
lane-aligned it is four Pallas kernels behind two ``jax.custom_vjp``s:
``ssd_state_fwd`` (what each chunk adds to the state) and ``ssd_chunk_fwd``
(each chunk's output: ``C B^T`` formed once for a block of heads, each head's
``(chunk, chunk)`` decay matrix built in VMEM, multiplied and fed to the MXU,
plus the read-out of the carried state and ``D x``), with ``ssd_state_bwd``
and ``ssd_chunk_bwd``, which rebuild the decay matrices in VMEM: nothing of
that size is ever in HBM, saved or as a cotangent. Every other shape keeps the
einsum form (the oracle the kernels are tested against): its backward pass is
autodiff, the decay matrices recomputed in it (``jax.checkpoint``) a block of
heads at a time so that they fit beside a full chip. Between chunks both forms
call ``log_decay`` and ``carried_states`` of this module by name, in XLA.

The convolution in front of the scan has two forms as well, chosen from the
shape (``ssm_conv_kernel_eligible``; no switch) and recorded at the route
site ``forward/ssm_conv``. Where every piece of ``x | B | C`` is whole lane
tiles and the rows are whole blocks, the convolution, its bias and the
``silu`` behind it are one Pallas kernel pair behind one ``jax.custom_vjp``:
``ssm_conv_fwd`` reads the compute dtype once and writes the three pieces,
``ssm_conv_bwd`` rebuilds the pre-activation from the saved input and
writes the input's cotangent and the taps' and the bias's gradients in one
pass; float32 exists only in registers. Every other shape keeps
``causal_conv1d`` + ``silu`` in XLA (the oracle the kernels are tested
against).

One group of ``B``/``C`` shared by all heads (``mamba_n_groups`` 1) is the
only layout written here.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kv_policy
from .layers import RMSNorm

Dtype = Any

# heads whose (chunk, chunk) decay matrices are live at once in the einsum
# form's quadratic part: 64 heads x 32 chunks x 256 x 256 float32 is 537 MB whole
HEAD_BLOCK = 16
LANES = 128
# the backward kernel holds a dozen (chunk, chunk) and (chunk, 512) float32
# temporaries; the default scoped budget is 16 MiB of the v5e's 128
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


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


# ---- the scan as Pallas kernels -------------------------------------------
#
# Every large operand keeps the projection's layout, (b, n, h p) with heads in
# lanes: a kernel slices its heads statically, and XLA never sees a (.., h, p)
# array (whose tiling differs: a copy of the whole operand at every boundary).
# Per position and head there are only small float32 arrays, handed over as
# COLUMNS (a chunk's positions in sublanes, a block of heads in lanes) and,
# where a kernel needs them along lanes, as ROWS: two orientations from XLA,
# so that no kernel transposes anything.
#
# The step ``dt`` never multiplies ``x`` in the quadratic part: it is folded
# into the exponent, ``C B^T . exp(c_i - (c_j - log dt_j))``, all float32, so
# that the MXU reads ``x`` as the projection wrote it.


def _head_block(h: int, p: int) -> int:
    """Heads a grid step takes: up to 1,024 lanes of ``x`` (a grid step costs
    ~0.55 us whatever it holds), at least the 8 rows a float32 tile of the
    row-oriented operand needs, in whole blocks. 0: no such block."""
    hb = max(8, 8 * LANES // p)
    while hb > 8 and h % hb:
        hb //= 2
    return hb if h % hb == 0 and (hb * p) % LANES == 0 else 0


def ssd_kernels_eligible(chunk: int, h: int, p: int, state: int) -> bool:
    """The shapes the kernels are written for: chunk and state whole lane
    tiles, a head a whole fraction of one (or one), heads in whole blocks
    whose channels are whole lane tiles."""
    return (
        chunk % LANES == 0 and state % LANES == 0 and LANES % p == 0
        and _head_block(h, p) > 0
    )


def _mxu(a, b, contract):
    """``a`` and ``b`` contracted over one axis each on the MXU, accumulated
    in float32. ``contract``: the axis of ``a`` and of ``b``."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _heads(t, p):
    """The (q, p) lane slices of a (q, hb p) block, head by head."""
    return [t[:, lo : lo + p] for lo in range(0, t.shape[1], p)]


def _over_channels(cols, p):
    """A (q, hb) block of per-head columns spread over each head's ``p``
    channels: (q, hb p), the layout of ``x``."""
    q, hb = cols.shape
    return jnp.concatenate(
        [jnp.broadcast_to(cols[:, j : j + 1], (q, p)) for j in range(hb)], axis=-1
    )


def _per_head(t, p):
    """A (q, hb p) block summed over each head's channels: (q, hb). Summed
    under a mask a whole lane tile at a time (a slice at half a tile would
    be moved first)."""
    q, width = t.shape
    head = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 1) // p
    sums = [
        jnp.sum(jnp.where(head == j, t[:, lo : lo + LANES], 0.0), axis=-1, keepdims=True)
        for lo in range(0, width, LANES) for j in range(LANES // p)
    ]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, len(sums)), 1)
    out = jnp.zeros(lane.shape, jnp.float32)
    for j, piece in enumerate(sums):
        out = jnp.where(lane == j, piece, out)
    return out


def _accumulate(ref, value, block):
    """``value`` summed over the head blocks (the innermost grid axis) of one
    (row, chunk) in the VMEM scratch ``ref``."""
    @pl.when(block == 0)
    def _():
        ref[...] = value

    @pl.when(block > 0)
    def _():
        ref[...] += value


def _row_blocks(q):
    """The lower triangle of a (q, q) matrix in row blocks of 128: (rows,
    width) with every column the rows can see; the rest is never computed."""
    return [(slice(lo, lo + LANES), lo + LANES) for lo in range(0, q, LANES)]


def _decays(scores, col, row):
    """One head's ``(C B^T . L)`` and ``L``, a row block at a time, with the
    step folded in: ``L[i, j] = dt_j exp(c_i - c_j)`` for ``i >= j`` and 0
    above, float32, masked BEFORE the exponential (above the diagonal the
    difference is positive and can overflow). ``col``: (q, 1), the
    log-decays; ``row``: (1, q), the log-decays less ``log dt``."""
    q = col.shape[0]
    for rows, width in _row_blocks(q):
        i = jax.lax.broadcasted_iota(jnp.int32, (LANES, width), 0) + rows.start
        lower = i >= jax.lax.broadcasted_iota(jnp.int32, (LANES, width), 1)
        decay = jnp.exp(jnp.where(lower, col[rows] - row[:, :width], -jnp.inf))
        yield rows, width, scores[rows, :width] * decay, decay


def _to_end(cum):
    """``exp(c_last - c_i)``: what is left at the chunk's end of a unit put
    in at position i. cum: a (q, hb) column block."""
    return jnp.exp(cum[-1:] - cum)


def _ssd_state_fwd_kernel(x_ref, dt_ref, cum_ref, bt_ref, s_ref, *, p):
    """What one chunk adds to the state by its end: ``B^T (decay . x dt)``."""
    x = x_ref[0]
    left = _over_channels(dt_ref[0, 0, 0] * _to_end(cum_ref[0, 0, 0]), p)
    weighted = (x.astype(jnp.float32) * left).astype(x.dtype)
    s_ref[0, 0] = _mxu(bt_ref[0], weighted, (1, 0))       # (state, hb p)


def _ssd_state_bwd_kernel(
    x_ref, dt_ref, cum_ref, b_ref, ds_ref, dx_ref, ddt_ref, dcum_ref, db_ref, db_acc, *, p,
):
    block, last = pl.program_id(2), pl.num_programs(2) - 1
    x, ds = x_ref[0], ds_ref[0, 0].astype(x_ref.dtype)
    x32, dt, to_end = x.astype(jnp.float32), dt_ref[0, 0, 0], _to_end(cum_ref[0, 0, 0])
    left = _over_channels(dt * to_end, p)
    dweighted = _mxu(b_ref[0], ds, (1, 0))                # (q, hb p)
    dx_ref[0] = (dweighted * left).astype(dx_ref.dtype)
    # both factors are one value a head: out of the sum over its channels
    dleft = _per_head(dweighted * x32, p)
    ddt_ref[0, 0, 0] = dleft * to_end
    # exp(c_last - c_i): minus to every c_i, their sum to the last
    dexp = dleft * dt * to_end
    at_end = jax.lax.broadcasted_iota(jnp.int32, dexp.shape, 0) == dexp.shape[0] - 1
    dcum_ref[0, 0, 0] = jnp.where(at_end, jnp.sum(dexp, axis=0, keepdims=True), 0.0) - dexp
    weighted = (x32 * left).astype(x.dtype)
    _accumulate(db_acc, _mxu(weighted, ds, (1, 1)), block)  # (q, state)

    @pl.when(block == last)
    def _():
        db_ref[0] = db_acc[...].astype(db_ref.dtype)


def _ssd_chunk_fwd_kernel(x_ref, cum_ref, row_ref, b_ref, c_ref, h_ref, d_ref, y_ref, *, p):
    """One chunk's output for a block of heads: the quadratic part
    ``(C B^T . L) x``, the state the chunk starts from read out through ``C``
    and decayed, and ``D x``."""
    x, cum, row = x_ref[0], cum_ref[0, 0, 0], row_ref[0]
    scores = _mxu(c_ref[0], b_ref[0], (1, 1))             # C B^T: (q, q)
    within = []
    for j, piece in enumerate(_heads(x, p)):
        within.append(jnp.concatenate([
            _mxu(mixed.astype(x.dtype), piece[:width], (1, 0))
            for _, width, mixed, _ in _decays(scores, cum[:, j : j + 1], row[j : j + 1])
        ], axis=0))
    read = _mxu(c_ref[0], h_ref[0, 0].astype(x.dtype), (1, 0))  # C S: (q, hb p)
    y_ref[0] = (
        jnp.concatenate(within, axis=-1)
        + _over_channels(jnp.exp(cum), p) * read + d_ref[0] * x.astype(jnp.float32)
    )


def _ssd_chunk_bwd_kernel(
    x_ref, cum_ref, row_ref, b_ref, c_ref, ct_ref, h_ref, d_ref, dy_ref,
    dx_ref, dcum_ref, drow_ref, db_ref, dc_ref, dh_ref, dd_ref, dscores_acc, dc_acc, *, p,
):
    """Cotangents of one (row, chunk, head block). ``C B^T`` and ``C`` are
    shared by all heads, so their cotangents are summed over the head blocks
    (the innermost grid axis) in VMEM and written at the last. The decay
    matrices are rebuilt here, and the cotangents of their exponents need no
    (q, q) reduction: with ``M = C B^T . L``, the column operand gets
    ``sum_j dM_kj M_kj = dy_k . (M x)_k`` and the row operand
    ``- sum_i dM_ik M_ik = - (M^T dy)_k . x_k``."""
    block, last = pl.program_id(2), pl.num_programs(2) - 1
    x, dy, cum, row = x_ref[0], dy_ref[0], cum_ref[0, 0, 0], row_ref[0]
    Bm, Cm, starts = b_ref[0], c_ref[0], h_ref[0, 0].astype(x_ref.dtype)
    q = x.shape[0]
    x32, dy16 = x.astype(jnp.float32), dy.astype(x.dtype)
    # ---- the quadratic part, head by head
    scores = _mxu(Cm, Bm, (1, 1))
    dscores = [jnp.zeros((LANES, width), jnp.float32) for _, width in _row_blocks(q)]
    within, dx = [], []
    for j, (piece, g) in enumerate(zip(_heads(x, p), _heads(dy16, p))):
        outs, dpiece = [], [0.0] * len(dscores)
        for r, (rows, width, mixed, decay) in enumerate(
            _decays(scores, cum[:, j : j + 1], row[j : j + 1])
        ):
            mixed = mixed.astype(x.dtype)
            outs.append(_mxu(mixed, piece[:width], (1, 0)))
            dscores[r] += _mxu(g[rows], piece[:width], (1, 1)) * decay  # (dy x^T) . L
            seen = _mxu(mixed, g[rows], (0, 0))           # M^T dy: (width, p)
            for c in range(r + 1):
                dpiece[c] += seen[c * LANES : (c + 1) * LANES]
        within.append(jnp.concatenate(outs, axis=0))
        dx.append(jnp.concatenate(dpiece, axis=0))
    within, dx = jnp.concatenate(within, axis=-1), jnp.concatenate(dx, axis=-1)
    drow_ref[0, 0, 0] = -_per_head(dx * x32, p)
    # ---- the state the chunk starts from, through C, decayed since the start
    since_start = _over_channels(jnp.exp(cum), p)
    read = _mxu(Cm, starts, (1, 0))                       # C S: (q, hb p)
    # the ROUNDED dy, as the MXU read it for dx: the two cotangents of the
    # exponents cancel term by term only if both saw the same numbers
    dcum_ref[0, 0, 0] = _per_head(dy16.astype(jnp.float32) * (within + since_start * read), p)
    dread = (dy * since_start).astype(x.dtype)
    dh_ref[0, 0] = _mxu(ct_ref[0], dread, (1, 0))
    # ---- D x
    dx_ref[0] = (dx + d_ref[0] * dy).astype(dx_ref.dtype)
    dd_ref[0, 0] = jnp.sum(dy * x32, axis=0, keepdims=True)
    _accumulate(dscores_acc, jnp.concatenate([
        jnp.pad(d, ((0, 0), (0, q - d.shape[1]))) for d in dscores
    ], axis=0), block)
    _accumulate(dc_acc, _mxu(dread, starts, (1, 1)), block)  # (q, state)

    @pl.when(block == last)
    def _():
        total = dscores_acc[...].astype(Bm.dtype)
        dc_ref[0] = (dc_acc[...] + _mxu(total, Bm, (1, 0))).astype(dc_ref.dtype)
        db_ref[0] = _mxu(total, Cm, (0, 0)).astype(db_ref.dtype)


def _mosaic_call(kernel, grid, in_specs, out_specs, out_shape, scratch, operands, interpret, *, name):
    """One ``pallas_call`` of this module: every grid axis parallel but the
    innermost, along which a kernel sums (the scan: over the head blocks of
    one chunk) or hands rows on (the convolution: from row block to row
    block) in VMEM."""
    return pl.pallas_call(
        kernel,
        name=name,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1) + ("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*operands)


class _Blocks:
    """The grid (batch row, chunk, block of heads) and the block of every
    kind of operand in it."""

    def __init__(self, x, B, chunk, p):
        b, n, hp = x.shape
        h, state = hp // p, B.shape[-1]
        hb = _head_block(h, p)
        assert n % chunk == 0 and ssd_kernels_eligible(chunk, h, p, state), (x.shape, B.shape, chunk, p)
        self.chunk, self.hb, self.dims = chunk, hb, (b, n, h, p, state)
        self.grid = (b, n // chunk, h // hb)
        self.x = pl.BlockSpec((1, chunk, hb * p), lambda bi, ci, hi: (bi, ci, hi))
        self.col = pl.BlockSpec((1, 1, 1, chunk, hb), lambda bi, ci, hi: (bi, ci, hi, 0, 0))
        self.row = pl.BlockSpec((1, hb, chunk), lambda bi, ci, hi: (bi, hi, ci))
        self.bc = pl.BlockSpec((1, chunk, state), lambda bi, ci, hi: (bi, ci, 0))
        self.bc_t = pl.BlockSpec((1, state, chunk), lambda bi, ci, hi: (bi, 0, ci))
        self.state = pl.BlockSpec((1, 1, state, hb * p), lambda bi, ci, hi: (bi, ci, 0, hi))
        self.d = pl.BlockSpec((1, 1, hb * p), lambda bi, ci, hi: (bi, 0, hi))
        self.dd = pl.BlockSpec((1, 1, 1, hb * p), lambda bi, ci, hi: (bi, ci, 0, hi))

    def columns(self, t):
        """(b, n, h) as (b, chunks, head blocks, chunk, hb)."""
        b, n, h, _, _ = self.dims
        t = t.reshape(b, n // self.chunk, self.chunk, h // self.hb, self.hb)
        return t.transpose(0, 1, 3, 2, 4)

    def uncolumns(self, t):
        b, n, h, _, _ = self.dims
        return t.transpose(0, 1, 3, 2, 4).reshape(b, n, h)

    def call(self, kernel, in_specs, out_specs, out_shape, scratch, operands, interpret, *, name):
        return _mosaic_call(
            functools.partial(kernel, p=self.dims[3]), self.grid, in_specs, out_specs,
            out_shape, scratch, operands, interpret, name=name,
        )


# Each call is a ``jax.jit`` of its own: a stack of nine mixers, each run
# forward, again under ``remat`` and backward, then traces and lowers a kernel
# once a shape and not 27 times (6 s of every set-up at the cell's size).
_kernel_call = functools.partial(jax.jit, static_argnames=("chunk", "p", "interpret"))


@_kernel_call
def _states_call(x, dt, cum, B, *, chunk, p, interpret):
    k = _Blocks(x, B, chunk, p)
    b, n, h, _, state = k.dims
    return k.call(
        _ssd_state_fwd_kernel,
        [k.x, k.col, k.col, k.bc_t], k.state,
        jax.ShapeDtypeStruct((b, n // chunk, state, h * p), jnp.float32), [],
        [x, k.columns(dt), k.columns(cum), B.transpose(0, 2, 1)], interpret, name="ssd_state_fwd",
    )


@_kernel_call
def _states_bwd_call(x, dt, cum, B, ds, *, chunk, p, interpret):
    k = _Blocks(x, B, chunk, p)
    cols = k.columns(dt)
    col_shape = jax.ShapeDtypeStruct(cols.shape, jnp.float32)
    dx, ddt, dcum, dB = k.call(
        _ssd_state_bwd_kernel,
        [k.x, k.col, k.col, k.bc, k.state], [k.x, k.col, k.col, k.bc],
        [jax.ShapeDtypeStruct(x.shape, x.dtype), col_shape, col_shape,
         jax.ShapeDtypeStruct(B.shape, B.dtype)],
        [pltpu.VMEM((chunk, B.shape[-1]), jnp.float32)],
        [x, cols, k.columns(cum), B, ds], interpret, name="ssd_state_bwd",
    )
    return dx, k.uncolumns(ddt), k.uncolumns(dcum), dB


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def ssd_chunk_states(x, dt, cum, B, chunk, p, interpret):
    """What each chunk adds to the state by its end. x: (b, n, h p) in the
    compute dtype; dt, cum: (b, n, h) float32; B: (b, n, state). Returns
    (b, chunks, state, h p) float32."""
    return _states_call(x, dt, cum, B, chunk=chunk, p=p, interpret=interpret)


def _states_fwd_rule(x, dt, cum, B, chunk, p, interpret):
    return ssd_chunk_states(x, dt, cum, B, chunk, p, interpret), (x, dt, cum, B)


def _states_bwd_rule(chunk, p, interpret, res, ds):
    return _states_bwd_call(*res, ds, chunk=chunk, p=p, interpret=interpret)


ssd_chunk_states.defvjp(_states_fwd_rule, _states_bwd_rule)


@_kernel_call
def _outputs_call(x, cum, row, B, C, starts, D, *, chunk, p, interpret):
    k = _Blocks(x, B, chunk, p)
    return k.call(
        _ssd_chunk_fwd_kernel,
        [k.x, k.col, k.row, k.bc, k.bc, k.state, k.d], k.x,
        jax.ShapeDtypeStruct(x.shape, jnp.float32), [],
        [x, k.columns(cum), row, B, C, starts, D], interpret, name="ssd_chunk_fwd",
    )


@_kernel_call
def _outputs_bwd_call(x, cum, row, B, C, starts, D, dy, *, chunk, p, interpret):
    k = _Blocks(x, B, chunk, p)
    b, n, h, _, state = k.dims
    cols = k.columns(cum)
    col_shape = jax.ShapeDtypeStruct(cols.shape, jnp.float32)
    bc_shape = jax.ShapeDtypeStruct(B.shape, B.dtype)
    dx, dcum, drow, dB, dC, dstarts, dD = k.call(
        _ssd_chunk_bwd_kernel,
        [k.x, k.col, k.row, k.bc, k.bc, k.bc_t, k.state, k.d, k.x],
        [k.x, k.col, k.col, k.bc, k.bc, k.state, k.dd],
        [jax.ShapeDtypeStruct(x.shape, x.dtype), col_shape, col_shape, bc_shape, bc_shape,
         jax.ShapeDtypeStruct(starts.shape, starts.dtype),
         jax.ShapeDtypeStruct((b, n // chunk, 1, h * p), jnp.float32)],
        [pltpu.VMEM((chunk, chunk), jnp.float32), pltpu.VMEM((chunk, state), jnp.float32)],
        [x, cols, row, B, C, C.transpose(0, 2, 1), starts, D, dy], interpret, name="ssd_chunk_bwd",
    )
    return (
        dx, k.uncolumns(dcum), k.uncolumns(drow).transpose(0, 2, 1), dB, dC, dstarts,
        dD.sum(1),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def ssd_chunk_outputs(x, cum, row, B, C, starts, D, chunk, p, interpret):
    """Every chunk's output from its own positions and the state it starts
    from. x, cum, B as ``ssd_chunk_states``; row: (b, h, n) float32, the
    log-decays less ``log dt`` along lanes; C: (b, n, state); starts: (b,
    chunks, state, h p) float32; D: (b, 1, h p) float32, each head's value over
    its channels, a row a batch row (every operand is split by rows under a
    mesh). Returns (b, n, h p) float32."""
    return _outputs_call(x, cum, row, B, C, starts, D, chunk=chunk, p=p, interpret=interpret)


def _outputs_fwd_rule(x, cum, row, B, C, starts, D, chunk, p, interpret):
    y = ssd_chunk_outputs(x, cum, row, B, C, starts, D, chunk, p, interpret)
    return y, (x, cum, row, B, C, starts, D)


def _outputs_bwd_rule(chunk, p, interpret, res, dy):
    return _outputs_bwd_call(*res, dy, chunk=chunk, p=p, interpret=interpret)


ssd_chunk_outputs.defvjp(_outputs_fwd_rule, _outputs_bwd_rule)


def _ssd_scan_kernels(x, dt, A, B, C, D, chunk, dtype):
    """``ssd_scan`` where the kernels are eligible; between the two kernels
    the chunks' states are handed over in XLA by ``carried_states``."""
    from .attention import _per_device  # the one shard_map rule of every Mosaic call

    b, n, h, p = x.shape
    pad = -n % chunk
    x, dt, B, C = (
        jnp.pad(t.reshape(b, n, -1), ((0, 0), (0, pad), (0, 0))) for t in (x, dt, B, C)
    )
    chunks = (n + pad) // chunk
    x, B, C = (t.astype(dtype) for t in (x, B, C))
    dt = dt.astype(jnp.float32)
    cum = log_decay(dt.reshape(b, chunks, chunk, h), A)    # (b, c, q, h)
    # each head's log-decay over a whole chunk, over that head's channels
    total = jnp.repeat(cum[:, :, -1:], p, axis=-1)         # (b, c, 1, h p)
    cum = cum.reshape(b, n + pad, h)
    # the step inside the exponent; a position that takes no step (the
    # padded tail) is -inf there, and moves nothing in the backward pass.
    # ``lax.select``, not ``jnp.where``: the latter is a jitted helper whose
    # numbered copy in the module made the step's SECOND trace another
    # program than its first (two compiles and two 48 MB cache entries a run)
    steps = dt > 0
    log_dt = jax.lax.select(
        steps, jnp.log(jax.lax.select(steps, dt, jnp.ones_like(dt))),
        jnp.full_like(dt, -jnp.inf),
    )
    interpret = kv_policy.pallas_interpret()
    kv_policy.record_route("forward/ssd", "ssd_chunk", interpret)
    states = _per_device(
        lambda *operands: ssd_chunk_states(*operands, chunk, p, interpret), (x, dt, cum, B)
    )
    starts = carried_states(states, total)                 # (b, c, state, h p)
    y = _per_device(
        lambda *operands: ssd_chunk_outputs(*operands, chunk, p, interpret),
        (x, cum, (cum - log_dt).transpose(0, 2, 1), B, C, starts,
         jnp.broadcast_to(jnp.repeat(D.astype(jnp.float32), p), (b, 1, h * p))),
    )
    return y.reshape(b, n + pad, h, p)[:, :n]


def ssd_scan(x, dt, A, B, C, D, chunk: int, dtype: Dtype = jnp.float32) -> jnp.ndarray:
    """x: (b, n, h, p); dt: (b, n, h), already positive (softplus applied);
    A: (h,), negative; B, C: (b, n, state); D: (h,). Returns y: (b, n, h, p)
    float32. ``n`` need not be a multiple of ``chunk``: the tail is padded
    with ``dt = 0`` positions, which neither decay nor feed the state."""
    b, n, h, p = x.shape
    if ssd_kernels_eligible(chunk, h, p, B.shape[-1]):
        return _ssd_scan_kernels(x, dt, A, B, C, D, chunk, dtype)
    kv_policy.record_route("forward/ssd", "einsum")
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


# ---- the convolution and its silu as Pallas kernels -----------------------
#
# One pass over HBM each way in the compute dtype; the float32 of the taps'
# sum, of the silu and of its derivative exists only in vector registers. A
# grid step takes a block of rows at the full width and writes the pieces the
# mixer splits the channels into (``x | B | C``) as outputs of their own, so
# that no split follows the forward kernel and no concatenation precedes the
# backward one. Inside a step the block is computed a STRIP at a time, a few
# registers an array (computed whole, every one of the ~20 operations an
# element would pass the block through VMEM). Row blocks run in sequence:
# what a block needs of its neighbour, the ``width - 1`` rows before it (after
# it, for a cotangent), is handed over in VMEM scratch.

CONV_ROWS = 256          # rows a grid step takes
STRIP = (64, 256)        # rows, lanes computed at once
EDGE = 16                # rows read before a strip: one bf16 tile
AFTER = 8                # rows of cotangent kept from the strip after: one float32 tile


def ssm_conv_kernel_eligible(n: int, sizes: tuple, width: int) -> bool:
    """The shapes the convolution's kernels are written for: every piece of
    the channels in whole lane tiles, rows in whole blocks, and no more taps
    than a strip keeps rows of its neighbour for."""
    return (
        all(size > 0 and size % LANES == 0 for size in sizes)
        and n > 0 and n % CONV_ROWS == 0 and 1 <= width <= AFTER
    )


def _for_each_strip(sizes, body):
    """``body(piece, lanes, lo, at)`` for every column strip: whole lane
    tiles inside ONE piece, ``lo`` and ``at`` the strip's first lane in its
    piece and in all channels. Equal strips side by side run in a loop, not
    unrolled: a kernel's text is loaded beside the weights once a call site
    (27 a step), and unrolled it cost 3.8 MB of the chip's memory."""
    lanes, base = STRIP[1], 0
    for piece, size in enumerate(sizes):
        whole, rest = divmod(size, lanes)

        def one(j, carry, piece=piece, base=base):
            lo = pl.multiple_of(j * lanes, LANES)
            body(piece, lanes, lo, base + lo)
            return carry

        if whole > 1:
            jax.lax.fori_loop(0, whole, one, 0)
        elif whole:
            body(piece, lanes, 0, base)
        if rest:
            body(piece, rest, whole * lanes, base + whole * lanes)
        base += size


def _taps_sum(ext, taps, bias):
    """A strip's pre-activation ``sum_k taps[k] x_{t-K+1+k} + bias``, float32,
    and the moved copies of ``x`` it is made of. ``ext``: the strip's rows
    behind the ``EDGE`` rows before it, float32."""
    width, rows = taps.shape[0], ext.shape[0] - EDGE
    first = EDGE - width + 1
    moved = [ext[first + k : first + k + rows] for k in range(width)]
    pre = bias + sum(taps[k : k + 1] * moved[k] for k in range(width))
    return pre, moved


def _ssm_conv_fwd_kernel(x_ref, taps_ref, bias_ref, *refs, sizes):
    *y_refs, before_ref = refs
    rows, strip_rows = x_ref.shape[1], STRIP[0]

    @pl.when(pl.program_id(1) == 0)
    def _():
        before_ref[...] = jnp.zeros_like(before_ref)      # zeros before the sequence

    def columns(piece, lanes, lo, at):
        cols, y_ref = pl.ds(at, lanes), y_refs[piece]
        taps, bias = taps_ref[0, :, cols], bias_ref[0, :, cols]

        def strip(r0, ext):
            pre, _ = _taps_sum(ext.astype(jnp.float32), taps, bias)
            y = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)
            y_ref[0, pl.ds(r0, strip_rows), pl.ds(lo, lanes)] = y

        def later(i, carry):
            r0 = pl.multiple_of(i * strip_rows, strip_rows)
            strip(r0, x_ref[0, pl.ds(r0 - EDGE, EDGE + strip_rows), cols])
            return carry

        strip(0, jnp.concatenate([before_ref[:, cols], x_ref[0, :strip_rows, cols]], axis=0))
        jax.lax.fori_loop(1, rows // strip_rows, later, 0)

    _for_each_strip(sizes, columns)
    before_ref[...] = x_ref[0, rows - EDGE :, :]


def _fold(t):
    """(rows, lanes) summed over its row TILES: (8, lanes), no reduction
    across sublanes."""
    return t.reshape(-1, 8, t.shape[-1]).sum(axis=0)


def _ssm_conv_bwd_kernel(x_ref, before_ref, taps_ref, bias_ref, *refs, sizes):
    """Row blocks, and the strips inside one, run from the LAST to the first:
    the cotangent of a strip's input needs the pre-activation's cotangent of
    the rows after it, carried from strip to strip in registers and from
    block to block in ``after_ref``. The rows of ``x`` before the block are a
    second small block of the same operand. The taps' and the bias's
    gradients are summed over the row blocks in their output blocks."""
    dy_refs, (dx_ref, dtaps_ref, dbias_ref, after_ref) = refs[: len(sizes)], refs[len(sizes) :]
    step, last = pl.program_id(1), pl.num_programs(1) - 1
    rows, strip_rows, width = x_ref.shape[1], STRIP[0], taps_ref.shape[1]

    @pl.when(step == 0)
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)        # nothing after the sequence
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    def columns(piece, lanes, lo, at):
        cols, dy_ref = pl.ds(at, lanes), dy_refs[piece]
        taps, bias = taps_ref[0, :, cols], bias_ref[0, :, cols]

        def strip(r0, ext, carry):
            after, sums = carry
            pre, moved = _taps_sum(ext.astype(jnp.float32), taps, bias)
            gate = jax.nn.sigmoid(pre)
            dy = dy_ref[0, pl.ds(r0, strip_rows), pl.ds(lo, lanes)].astype(jnp.float32)
            dpre = dy * gate * (1.0 + pre * (1.0 - gate))
            dext = jnp.concatenate([dpre, after], axis=0)
            dx = sum(
                taps[k : k + 1] * dext[width - 1 - k : width - 1 - k + strip_rows]
                for k in range(width)
            )
            dx_ref[0, pl.ds(r0, strip_rows), cols] = dx.astype(dx_ref.dtype)
            sums = tuple(s + _fold(dpre * m) for s, m in zip(sums, moved)) + (
                sums[-1] + _fold(dpre),
            )
            return dpre[:AFTER], sums

        def later(i, carry):
            r0 = pl.multiple_of((rows // strip_rows - 1 - i) * strip_rows, strip_rows)
            return strip(r0, x_ref[0, pl.ds(r0 - EDGE, EDGE + strip_rows), cols], carry)

        zeros = jnp.zeros((8, lanes), jnp.float32)
        carry = jax.lax.fori_loop(
            0, rows // strip_rows - 1, later, (after_ref[:, cols], (zeros,) * (width + 1))
        )
        before = before_ref[0, :, cols]
        before = jnp.where(step == last, jnp.zeros_like(before), before)  # before the sequence
        after, sums = strip(
            0, jnp.concatenate([before, x_ref[0, :strip_rows, cols]], axis=0), carry
        )
        after_ref[:, cols] = after
        for k in range(width):
            dtaps_ref[0, k : k + 1, cols] += jnp.sum(sums[k], axis=0, keepdims=True)
        dbias_ref[0, :, cols] += jnp.sum(sums[width], axis=0, keepdims=True)

    _for_each_strip(sizes, columns)


class _ConvBlocks:
    """The grid (batch row, block of rows) and the blocks of the
    convolution's operands in it, forward (rows ascending) and backward
    (descending)."""

    def __init__(self, x, taps, sizes):
        b, n, c = x.shape
        width = taps.shape[1]
        assert c == sum(sizes) and ssm_conv_kernel_eligible(n, sizes, width), (x.shape, sizes)
        self.grid = (b, n // CONV_ROWS)
        last, per_block = self.grid[1] - 1, CONV_ROWS // EDGE
        rows = lambda size: pl.BlockSpec((1, CONV_ROWS, size), lambda bi, ri: (bi, ri, 0))
        back = lambda size: pl.BlockSpec((1, CONV_ROWS, size), lambda bi, ri: (bi, last - ri, 0))
        self.rows, self.pieces = rows(c), [rows(size) for size in sizes]
        self.rows_back, self.pieces_back = back(c), [back(size) for size in sizes]
        # the tile of rows that ends where the block starts (for the first
        # block its own first tile, which the kernel replaces with zeros)
        self.before_back = pl.BlockSpec(
            (1, EDGE, c), lambda bi, ri: (bi, jnp.maximum((last - ri) * per_block - 1, 0), 0)
        )
        self.taps = pl.BlockSpec((1, width, c), lambda bi, ri: (bi, 0, 0))
        self.bias = pl.BlockSpec((1, 1, c), lambda bi, ri: (bi, 0, 0))


_conv_kernel_call = functools.partial(jax.jit, static_argnames=("sizes", "interpret"))


@_conv_kernel_call
def _conv_call(x, taps, bias, *, sizes, interpret):
    k = _ConvBlocks(x, taps, sizes)
    b, n, c = x.shape
    return tuple(_mosaic_call(
        functools.partial(_ssm_conv_fwd_kernel, sizes=sizes), k.grid,
        [k.rows, k.taps, k.bias], k.pieces,
        [jax.ShapeDtypeStruct((b, n, size), x.dtype) for size in sizes],
        [pltpu.VMEM((EDGE, c), x.dtype)], [x, taps, bias], interpret, name="ssm_conv_fwd",
    ))


@_conv_kernel_call
def _conv_bwd_call(x, taps, bias, dys, *, sizes, interpret):
    k = _ConvBlocks(x, taps, sizes)
    return tuple(_mosaic_call(
        functools.partial(_ssm_conv_bwd_kernel, sizes=sizes), k.grid,
        [k.rows_back, k.before_back, k.taps, k.bias, *k.pieces_back],
        [k.rows_back, k.taps, k.bias],
        [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (x, taps, bias)],
        [pltpu.VMEM((AFTER, x.shape[-1]), jnp.float32)],
        [x, x, taps, bias, *dys], interpret, name="ssm_conv_bwd",
    ))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ssm_conv(x, taps, bias, sizes, interpret):
    """``silu(causal_conv1d(x))`` in the dtype of ``x``: (b, n, c), split
    along the channels into pieces of ``sizes``. ``taps``: (b, width, c)
    float32 and ``bias``: (b, 1, c) float32, a copy a batch row (every operand
    is split by rows under a mesh). The backward pass keeps only these
    three."""
    return _conv_call(x, taps, bias, sizes=sizes, interpret=interpret)


def _conv_fwd_rule(x, taps, bias, sizes, interpret):
    return ssm_conv(x, taps, bias, sizes, interpret), (x, taps, bias)


def _conv_bwd_rule(sizes, interpret, res, dys):
    return _conv_bwd_call(*res, dys, sizes=sizes, interpret=interpret)


ssm_conv.defvjp(_conv_fwd_rule, _conv_bwd_rule)


def conv_silu(x, kernel, bias, sizes: tuple, dtype: Dtype = jnp.float32) -> tuple:
    """``silu(causal_conv1d(x, kernel, bias))`` in ``dtype``, accumulated and
    activated in float32 and split along the channels into pieces of
    ``sizes``: the kernel pair where the shape is eligible
    (``ssm_conv_kernel_eligible``), XLA everywhere else; which of the two, at
    the route site ``forward/ssm_conv``. ``bias`` None: a convolution without
    one, the same kernels over zeros made at trace time (their cotangent is
    dropped)."""
    from .attention import _per_device  # the one shard_map rule of every Mosaic call

    b, n, c = x.shape
    if bias is None:
        bias = jnp.zeros((c,), jnp.float32)
    width, sizes = kernel.shape[0], tuple(sizes)
    if not ssm_conv_kernel_eligible(n, sizes, width):
        kv_policy.record_route("forward/ssm_conv", "xla")
        y = jax.nn.silu(causal_conv1d(x, kernel, bias)).astype(dtype)
        return tuple(jnp.split(y, np.cumsum(sizes[:-1]), axis=-1))
    interpret = kv_policy.pallas_interpret()
    kv_policy.record_route("forward/ssm_conv", "ssm_conv", interpret)
    return _per_device(
        lambda *operands: ssm_conv(*operands, sizes, interpret),
        (x.astype(dtype), jnp.broadcast_to(kernel.astype(jnp.float32), (b, width, c)),
         jnp.broadcast_to(bias.astype(jnp.float32), (b, 1, c))),
    )


def inverse_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


def _log_uniform(lo: float, hi: float):
    def init(key, shape, dtype=jnp.float32):
        return jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(lo), jnp.log(hi)))
    return init


class CausalConv1D(nn.Module):
    """``conv_silu`` with its (width, channels) kernel and, unless
    ``use_bias`` is off, its bias."""

    width: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    use_bias: bool = True

    @nn.compact
    def __call__(self, x, sizes):
        c = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (self.width, c), self.param_dtype
        )
        bias = (
            self.param("bias", nn.initializers.zeros, (c,), self.param_dtype)
            if self.use_bias else None
        )
        return conv_silu(x, kernel, bias, sizes, self.dtype)


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
            conv = CausalConv1D(self.d_conv, self.dtype, self.param_dtype, name="conv")
            x, B, C = conv(xbc, (inner, s, s))
        with jax.named_scope("ssm.scan"):
            step = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
            y = ssd_scan(
                x.reshape(b, n, h, p), step, -jnp.exp(A_log.astype(jnp.float32)),
                B, C, D, self.chunk, self.dtype,
            )
        gated = y.reshape(b, n, inner) * jax.nn.silu(z.astype(jnp.float32))
        y = RMSNorm(self.eps, self.param_dtype, name="norm")(gated).astype(self.dtype)
        return dense(self.dim, "out_proj")(y)
