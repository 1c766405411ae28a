"""Fused (flash-style) attention Pallas TPU kernels.

The dense attention path materializes the (n, n) score matrix in HBM — at
DALL-E's seq 1280 that is the memory wall that caps batch size (and the
reference's DeepSpeed block-sparse CUDA kernel exists for the same reason,
attention.py:325-384). These kernels stream K/V blocks through VMEM with an
online-softmax accumulator, so activation memory is O(n·d) while the MXU sees
full (block_q x d x block_k) matmuls:

- forward: grid (b·h, n/bq, n/bk); the innermost k dimension iterates
  sequentially with running (max, denom, unnormalized out) in VMEM scratch;
  emits per-row logsumexp for the backward;
- backward: recompute-based (no stored probabilities), ONE kernel at every
  grid (b·h, n/bk, n/bq): a live tile's scores, mask, exp and dp = do·v^T
  are rebuilt once and give dq, dk and dv, five dots a tile; dk/dv sum
  over the inner query blocks in VMEM scratch, dq over the outer key
  blocks in a float32 VMEM row of the whole sequence (float32 partials
  summed by XLA where that row does not fit: ``_bwd_rule``), and
  delta = rowsum(do*o) comes from blocks already in VMEM, never from HBM;
- masking: ``causal=True`` is analytic (above-diagonal blocks execute no
  dots); with a static ``window`` W it is the analytic band
  ``0 <= i - j < W`` (a sliding window: W keys, the query's own included),
  and the grid's inner axis spans only the band's blocks (below); an
  optional static (n, n) pattern mask (ops/masks.py) is streamed
  blockwise for sparse/axial/conv layouts with all-empty blocks skipped the
  same way; an optional runtime (b, n) key-padding mask (the reference's
  ``mask`` argument, attention.py:71-74) is a fourth streamed operand —
  (1, block_k) per grid step — folded into the scores after the static
  mask, so masked training/CLIP text padding keeps the O(n·d) memory
  guarantee instead of falling back to dense (n, n) scores. Rows whose
  every key is masked produce exactly 0 output and 0 gradient (the
  ``_masked_exp`` guard). This one kernel therefore covers the reference's
  dense causal attention, its pad-mask handling, and its DeepSpeed
  variable-sparsity kernel semantics.
  Skipped blocks still DMA their K/V block: index_maps must stay affine in
  the grid indices — an earlier revision routed them through the
  scalar-prefetch table to re-fetch the last live block, which defeats
  Mosaic's DMA pipelining and measured 23x slower at block 256 on v5e.
  A window keeps them affine and moves the grid instead: the forward's
  inner step ``j`` of query block ``qb`` is key block ``qb - span + 1 + j``
  clamped at 0, the backward's of key block ``kb`` query block ``kb + j``
  clamped at the last, ``span`` blocks a row (``_band_span``: 5 of 16 at
  n 16,384, block 1,024, W 4,096). No tile wholly outside the band is
  fetched; a clamped step repeats the block before it (no new DMA) and is
  dead in the visit table. ``window=None``, or a window of n or more, is
  the causal program, step for step.

Parity is tested against the dense masked oracle (ops.attention.dense_attend)
in interpret mode on CPU and compiled on TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .rotary import cos_sin

NEG_INF = -1e30
LANES = 128

# the names a block's checkpoint keeps (models/transformer.py:_block_checkpoint,
# docs/DESIGN.md section 9): a forward kernel here costs O(n^2) to run again
# and its two results cost O(n) to hold
KERNEL_RESIDUAL_NAMES = ("attn_kernel_out", "attn_kernel_lse")


def _name_residuals(o, lse):
    """A forward kernel's two results under ``KERNEL_RESIDUAL_NAMES``, as the
    residuals hold them. BOTH are named: they come from one ``pallas_call``
    and one unsaved output keeps the whole call alive in the recomputed
    forward. Outside a checkpoint the name lowers to nothing."""
    out_name, lse_name = KERNEL_RESIDUAL_NAMES
    return checkpoint_name(o, out_name), checkpoint_name(lse, lse_name)


class StaticMask:
    """Hashable wrapper for a static (n, n) bool may-attend mask, so it can
    ride through custom_vjp/jit static arguments without retracing (identity
    hash — build once per model, e.g. via a cached constructor)."""

    def __init__(self, mask):
        self.mask = np.asarray(mask, dtype=bool)

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


# --------------------------------------------------------------- static maps


def _block_visit_map(
    nq: int, nk: int, block_q: int, block_k: int,
    causal: bool, pattern_mask: Optional[np.ndarray], window: Optional[int] = None,
) -> np.ndarray:
    """Static per-(qb, kb) class: 0 = skip, 1 = needs masking, 2 = dense."""
    visit = np.full((nq, nk), 2, dtype=np.int32)
    if pattern_mask is not None:
        for qb in range(nq):
            for kb in range(nk):
                blk = pattern_mask[
                    qb * block_q : (qb + 1) * block_q,
                    kb * block_k : (kb + 1) * block_k,
                ]
                visit[qb, kb] = 0 if not blk.any() else (2 if blk.all() else 1)
    elif causal:
        for qb in range(nq):
            for kb in range(nk):
                if kb * block_k > (qb + 1) * block_q - 1:
                    visit[qb, kb] = 0  # fully above the diagonal
                elif window is not None and qb * block_q - (kb + 1) * block_k + 1 >= window:
                    visit[qb, kb] = 0  # wholly left of the band
                elif (kb + 1) * block_k - 1 > qb * block_q or (
                    window is not None and (qb + 1) * block_q - 1 - kb * block_k >= window
                ):
                    visit[qb, kb] = 1  # crossing the diagonal or the band's far edge
    return visit


def _band_span(n_blocks: int, block: int, window: int) -> int:
    """Blocks a row of the banded grid spans: the diagonal block and those
    that hold keys up to ``window - 1`` positions back."""
    return min(n_blocks, -(-(window - 1) // block) + 1)


def _band_tables(visit: np.ndarray, span: int) -> tuple:
    """The visit classes on the banded grids: forward (nq, span), entry
    ``[qb, j]`` the class of key block ``qb - span + 1 + j``; backward
    (nk, span), entry ``[kb, j]`` that of query block ``kb + j``. A step the
    index maps clamp (a block index outside the grid) is dead."""
    nq, nk = visit.shape
    fwd = np.zeros((nq, span), np.int32)
    bwd = np.zeros((nk, span), np.int32)
    for qb in range(nq):
        for j in range(span):
            if qb - span + 1 + j >= 0:
                fwd[qb, j] = visit[qb, qb - span + 1 + j]
    for kb in range(nk):
        for j in range(span):
            if kb + j < nq:
                bwd[kb, j] = visit[kb + j, kb]
    live = int((visit > 0).sum())
    assert int((fwd > 0).sum()) == live == int((bwd > 0).sum()), "a live tile lies off the band"
    return fwd, bwd


def window_tiles(n: int, block: int, window: Optional[int]) -> dict:
    """What the forward grid of a causal row of ``n`` at ``block`` visits:
    ``tiles_visited``, the distinct (query block, key block) tiles it fetches
    (every tile of the full grid without a window; with one, those that touch
    the band), beside ``causal_tiles``, the causal triangle's live tiles."""
    nb = n // block
    causal = _block_visit_map(nb, nb, block, block, True, None)
    if window is None or window >= n:
        return {"tiles_visited": nb * nb, "causal_tiles": int((causal > 0).sum())}
    visit = _block_visit_map(nb, nb, block, block, True, None, window)
    return {"tiles_visited": int((visit > 0).sum()), "causal_tiles": int((causal > 0).sum())}


def window_supported(n: int, d: int, block: int) -> bool:
    """A windowed backward keeps dq resident (``DQ_ROW_VMEM_BYTES``): the
    partials form would leave the blocks off the band unwritten."""
    return n // block == 1 or n * d * 4 <= DQ_ROW_VMEM_BYTES


def _scalar_table(visit: np.ndarray) -> np.ndarray:
    """(1, nq*nk) int32 scalar-prefetch payload: the per-(outer, inner) visit
    class consumed by the kernel body to skip compute on dead blocks. (Index
    maps deliberately do NOT consult it — see the module docstring.)"""
    return visit.reshape(1, -1).astype(np.int32)


# ------------------------------------------------------------------ kernels


def _masked_scores(q, k, sm_scale, mask_ref, kmask_ref, visit, row0, col0, bq, bk,
                   window=None):
    """(bq, bk) f32 scores with pattern/causal and runtime key masking
    applied. The QK^T dot runs in the inputs' dtype (bf16 on the MXU fast
    path) with f32 accumulation; the scale is applied on the f32 result.
    ``kmask_ref``: optional (1, 1, bk) int32 block of the runtime
    key-padding mask, broadcast over query rows. ``window``: the band
    ``0 <= rows - cols < window`` in the causal mask's place."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if mask_ref is not None:
        # widen the int8 operand before comparing: Mosaic on v5e cannot
        # lower cmpi on the packed vector<..xi8> layout ("Target does not
        # support this comparison"); the i8->i32 convert is supported and
        # keeps the streamed mask at 1 byte/element
        s = jnp.where(mask_ref[:].astype(jnp.int32) > 0, s, NEG_INF)
    else:
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + row0
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + col0
        dense = visit == 2
        keep = rows >= cols
        if window is not None:
            keep = jnp.logical_and(keep, rows - cols < window)
        s = jnp.where(jnp.logical_or(dense, keep), s, NEG_INF)
    if kmask_ref is not None:
        s = jnp.where(kmask_ref[0] > 0, s, NEG_INF)  # (1, bk) over rows
    return s


def _row_vec(ref):
    """(1, 1, bq) ref block -> (bq, 1) f32."""
    return jax.lax.transpose(ref[0], (1, 0))


def _masked_exp(s, x):
    """exp(s - x) with fully-masked entries forced to 0: rows masked in every
    visited block keep their running max / lse at NEG_INF, where exp(s - x)
    would be 1 — the guard enforces the 'fully-masked rows -> 0 output'
    contract (threshold is unreachable by real scores)."""
    return jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - x), 0.0)


def _fwd_kernel(
    scalar_ref, q_ref, k_ref, v_ref, mask_ref, kmask_ref, o_ref, lse_ref,
    m_scr, l_scr, acc_scr,
    *, sm_scale, block_q, block_k, nk, window=None,
):
    """``nk``: the inner grid's length, all key blocks or the band's span;
    with a ``window`` inner step ``j`` is key block ``qb - nk + 1 + j``
    (clamped at 0: such a step is dead in the table)."""
    qb, j = pl.program_id(1), pl.program_id(2)
    kb = j if window is None else jnp.maximum(qb - nk + 1 + j, 0)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    visit = scalar_ref[0, qb * nk + j]

    @pl.when(visit > 0)
    def _():
        s = _masked_scores(
            q_ref[0], k_ref[0], sm_scale, mask_ref, kmask_ref, visit,
            qb * block_q, kb * block_k, block_q, block_k, window,
        )
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = _masked_exp(s, m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, 0:1] = l_scr[:, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[:, 0:1] = m_new
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:, 0:1] + jnp.log(l_safe)  # (bq, 1)
        lse_ref[0] = jax.lax.transpose(lse, (1, 0))


def _bwd_kernel(
    scalar_ref, q_ref, k_ref, v_ref, mask_ref, kmask_ref, do_ref, o_ref, lse_ref,
    dq_ref, dk_ref, dv_ref, *scratch,
    sm_scale, block_q, block_k, nq, nk, dq_resident, window=None, last_qb=None,
):
    """The backward at every grid: (b·h, key block, query block), each live
    tile visited once. From ONE ``_masked_scores`` + ``_masked_exp`` the tile
    gives ``dv += p^T do``, ``dp = do v^T``, ``ds = p (dp - delta) scale``,
    ``dk += ds^T q`` and ``dq[query block] += ds k``: five dots.
    delta = rowsum(do * o) is taken from the blocks already in VMEM and
    never reaches HBM.

    An output block that several tiles add to is summed in float32 scratch
    and rounded once; one that a single tile completes is written as it is.
    dk and dv sum over the inner query blocks (``scratch[:2]``, (block_k, d)
    and (block_k, dv), where nq > 1). dq sums over the OUTER key blocks:
    with ``dq_resident`` in ``scratch[-1]``, the whole (n, d) row of one b·h,
    written to the resident ``dq_ref`` at the row's last tile; without it
    ``dq_ref`` is this tile's own block (``_bwd_rule`` says which form).

    ``nq``: the inner grid's length, all query blocks or the band's span;
    with a ``window`` inner step ``j`` is query block ``kb + j`` (clamped at
    ``last_qb``: such a step is dead in the table), and the resident dq row
    is zeroed whole at the first step, since a key block visits only the
    query blocks of its band."""
    kb, j = pl.program_id(1), pl.program_id(2)
    qb = j if window is None else jnp.minimum(kb + j, last_qb)
    dk_scr, dv_scr = scratch[:2] if nq > 1 else (None, None)
    dq_row = scratch[-1] if dq_resident else None
    rows = pl.ds(pl.multiple_of(qb * block_q, block_q), block_q)

    if nq > 1:
        @pl.when(j == 0)
        def _():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

    if dq_resident and window is None:
        @pl.when(kb == 0)
        def _():
            dq_row[rows, :] = jnp.zeros((block_q, dq_row.shape[1]), jnp.float32)
    elif dq_resident:
        @pl.when(jnp.logical_and(kb == 0, j == 0))
        def _():
            dq_row[:] = jnp.zeros_like(dq_row)

    visit = scalar_ref[0, kb * nq + j]

    @pl.when(visit > 0)
    def _():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = _masked_scores(
            q, k, sm_scale, mask_ref, kmask_ref, visit,
            qb * block_q, kb * block_k, block_q, block_k, window,
        )
        p = _masked_exp(s, _row_vec(lse_ref))
        dv = jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        delta = jnp.sum(
            do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dq = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        if nq > 1:
            dk_scr[:] += dk
            dv_scr[:] += dv
        else:
            dk_ref[0] = dk.astype(dk_ref.dtype)
            dv_ref[0] = dv.astype(dv_ref.dtype)
        if dq_resident:
            dq_row[rows, :] += dq
        else:
            dq_ref[0] = dq.astype(dq_ref.dtype)

    if nq == 1 or not dq_resident:
        # a dead tile still owes the blocks that it alone writes their zeros
        @pl.when(visit == 0)
        def _():
            if nq == 1:
                dk_ref[0] = jnp.zeros_like(dk_ref[0])
                dv_ref[0] = jnp.zeros_like(dv_ref[0])
            if not dq_resident:
                dq_ref[0] = jnp.zeros_like(dq_ref[0])

    if nq > 1:
        @pl.when(j == nq - 1)
        def _():
            dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    if dq_resident:
        @pl.when(jnp.logical_and(kb == nk - 1, j == nq - 1))
        def _():
            dq_ref[0] = dq_row[:].astype(dq_ref.dtype)


# ------------------------------------------------------------------ plumbing


def _prep(q, pattern_mask, block_q, block_k, causal, window=None):
    """-> (b, h, n, d, nq, nk, the pattern as numpy, the visit map, the
    window or None where it covers the row: then the program is causal's)."""
    b, h, n, d = q.shape
    assert n % block_q == 0 and n % block_k == 0, (
        f"seq {n} must divide block sizes ({block_q}, {block_k})"
    )
    nq, nk = n // block_q, n // block_k
    mask_np = None
    if pattern_mask is not None:
        assert isinstance(pattern_mask, StaticMask), (
            "wrap the pattern mask in StaticMask (hashable static argument)"
        )
        mask_np = pattern_mask.mask
        assert mask_np.shape == (n, n), (mask_np.shape, n)
    if window is not None and window >= n:
        window = None
    if window is not None:
        assert causal and mask_np is None and block_q == block_k and window >= 1, (
            "a window is a causal band over square blocks, with no pattern"
        )
        assert window_supported(n, d, block_k), (
            f"a windowed backward keeps dq resident: {n} x {d} float32 is over "
            f"{DQ_ROW_VMEM_BYTES} bytes"
        )
    visit = _block_visit_map(nq, nk, block_q, block_k, causal, mask_np, window)
    return b, h, n, d, nq, nk, mask_np, visit, window


def _kernel_cost(
    visit: np.ndarray, bh: int, block_q: int, block_k: int, d: int,
    dots_per_block: int, per_step_rows: int, per_outer_rows: int,
    dtype_bytes: int, dv: Optional[int] = None, v_dots: int = 0,
    v_step_rows: int = 0, v_outer_rows: int = 0,
) -> pl.CostEstimate:
    """Cost of one pass over the live blocks — fed to XLA so compiled-module
    cost analysis and the scheduler see the kernel's real FLOPs instead of
    zero for the opaque custom call. ``dots_per_block``: dot_generals the
    body executes per live block (fwd 2: s, o-acc; bwd 5: s, dv, dp, dk,
    dq). Streamed-operand DMA happens on EVERY grid step
    (affine index maps — dead blocks skip compute, not traffic):
    ``per_step_rows`` rows of d move per inner step, ``per_outer_rows`` rows
    once per outer step (operands whose block index only depends on the
    outer grid dimension, plus outputs; the backward's resident dq row
    leaves once a b·h and is counted as its share a key block). Of those
    counts, ``v_dots`` dots
    and ``v_step_rows`` / ``v_outer_rows`` rows have the VALUE width ``dv``
    (v, o, do, dv) where it is not the query/key width ``d``; with one
    width the estimate is the one-width formula's, number for number."""
    live = int((visit > 0).sum())
    n_outer, n_inner = visit.shape
    dv = d if dv is None else dv
    width = lambda count, v_count: (count - v_count) * d + v_count * dv
    return pl.CostEstimate(
        flops=bh * live * 2 * block_q * block_k * width(dots_per_block, v_dots),
        transcendentals=bh * live * block_q * block_k,  # exp
        bytes_accessed=bh
        * (n_outer * n_inner * width(per_step_rows, v_step_rows)
           + n_outer * width(per_outer_rows, v_outer_rows))
        * dtype_bytes,
    )


# one budget for every kernel here that asks Mosaic for more than its default
# scoped VMEM (v5e has 128 MiB physical): _call and _call_plain hand it over,
# fused_qkv_supported derives the packed path's admissible n from it, and the
# backward's resident dq row is held to DQ_ROW_VMEM_BYTES of it
VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, scalar, operands, interpret, cost=None,
          *, name, semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=None):
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        # batch*heads and (forward) outer blocks are independent; a dimension
        # something accumulates over is order-dependent — lets Mosaic pipeline
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=vmem_limit_bytes
        ),
        cost_estimate=cost,
        interpret=interpret,
    )(scalar, *operands)


def _with_optional_masks(kernel, has_mask, has_kmask, n_out, n_scratch):
    """Adapt a kernel with (mask_ref, kmask_ref) slots to calls missing
    either optional operand: the pattern mask and/or the runtime key mask."""

    def wrapped(*refs):
        split = len(refs) - n_out - n_scratch
        ins = list(refs[:split])
        rest = refs[split:]
        fixed, tail = ins[:4], ins[4:]  # scalar, q, k, v | optional + extras
        mask_ref = tail.pop(0) if has_mask else None
        kmask_ref = tail.pop(0) if has_kmask else None
        return kernel(*fixed, mask_ref, kmask_ref, *tail, *rest)

    return wrapped


def _bcast_key_mask(key_mask, b, h, n):
    """(b, n) bool key mask -> (b*h, 1, n) int32 streamed operand. The
    middle singleton keeps the block's sublane dimension equal to the
    array's (Mosaic requires block dims divisible by (8, 128) or equal to
    the array dims — the same layout trick as the lse operand). int32, not
    int8 like the pattern-mask operand: Mosaic on v5e cannot compare the
    packed vector<...xi8> layout this (1, 1, bk) block lowers to ("Target
    does not support this comparison"); the operand is (b·h, n) ints total,
    ~1/(2d) of one K operand, so the wider dtype is noise."""
    assert key_mask.shape == (b, n), (key_mask.shape, (b, n))
    return jnp.broadcast_to(
        key_mask[:, None, :].astype(jnp.int32), (b, h, n)
    ).reshape(b * h, 1, n)


def _flash_fwd(q, k, v, key_mask, causal, pattern_mask, sm_scale, block_q, block_k, interpret,
               window=None):
    b, h, n, d, nq, nk, mask_np, visit, window = _prep(
        q, pattern_mask, block_q, block_k, causal, window)
    dv = v.shape[-1]    # the value width: v's and o's, where it is not q's and k's
    scale = d**-0.5 if sm_scale is None else sm_scale
    bh = b * h
    qf, kf, vf = q.reshape(bh, n, d), k.reshape(bh, n, d), v.reshape(bh, n, dv)
    if window is not None:
        span = _band_span(nk, block_k, window)
        visit = _band_tables(visit, span)[0]
        nk = span

    # index_maps under PrefetchScalarGridSpec receive the scalar-prefetch
    # ref as a trailing argument after the grid indices, but must stay affine
    # in the grid indices (module docstring)
    def key_block(qb, kb):
        return kb if window is None else jnp.maximum(qb - nk + 1 + kb, 0)

    def kv_im(bhi, qb, kb, s):
        return (bhi, key_block(qb, kb), 0)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bhi, qb, kb, s: (bhi, qb, 0)),
        pl.BlockSpec((1, block_k, d), kv_im),
        pl.BlockSpec((1, block_k, dv), kv_im),
    ]
    operands = [qf, kf, vf]
    if mask_np is not None:
        in_specs.append(
            pl.BlockSpec((block_q, block_k), lambda bhi, qb, kb, s: (qb, kb))
        )
        operands.append(jnp.asarray(mask_np, jnp.int8))
    if key_mask is not None:
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda bhi, qb, kb, s: (bhi, 0, key_block(qb, kb)))
        )
        operands.append(_bcast_key_mask(key_mask, b, h, n))

    kernel = _with_optional_masks(
        functools.partial(
            _fwd_kernel, sm_scale=scale, block_q=block_q, block_k=block_k, nk=nk,
            window=window,
        ),
        mask_np is not None,
        key_mask is not None,
        n_out=2,
        n_scratch=3,
    )
    o, lse = _call(
        kernel,
        name="flash_fwd",
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bhi, qb, kb, s: (bhi, qb, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bhi, qb, kb, s: (bhi, 0, qb)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, n), jnp.float32),
        ],
        scratch=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        scalar=jnp.asarray(_scalar_table(visit)),
        operands=operands,
        interpret=interpret,
        cost=_kernel_cost(visit, bh, block_q, block_k, d, 2,
                          2 * block_k, 2 * block_q, q.dtype.itemsize,
                          dv, 1, block_k, block_q),
    )
    return o.reshape(b, h, n, dv), lse.reshape(b, h, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def flash_attention(
    q, k, v,
    key_mask=None,
    causal: bool = True,
    pattern_mask=None,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: Optional[int] = None,
):
    """Fused attention over (b, h, n, d); q is NOT pre-scaled (``sm_scale``
    defaults to d**-0.5). ``v`` may have a width of its own, (b, h, n, dv):
    the output and ``dv`` then have it too, and nothing is padded (latent
    attention: queries and keys of 192, values of 128). ``pattern_mask``: static (n, n) bool array,
    True = may attend; hash by id, so build it once at model setup.
    ``key_mask``: runtime (b, n) bool array, True = key is attendable
    (the reference's pad mask, attention.py:71-74); rows with every key
    masked return exactly 0. ``window``: static; with ``causal`` and square
    blocks, query i sees key j only where ``0 <= i - j < window``, and the
    grid visits the band's tiles only (module docstring)."""
    o, _ = _flash_fwd(q, k, v, key_mask, causal, pattern_mask, sm_scale, block_q, block_k, interpret,
                      window)
    return o


def _fwd_rule(q, k, v, key_mask, causal, pattern_mask, sm_scale, block_q, block_k, interpret,
              window):
    o, lse = _name_residuals(*_flash_fwd(
        q, k, v, key_mask, causal, pattern_mask, sm_scale, block_q, block_k, interpret, window
    ))
    return o, (q, k, v, key_mask, o, lse)


# the backward keeps one b·h's whole dq row (n, d) in float32 VMEM scratch
# while the row's tiles add to it; a longer row takes the partials form
DQ_ROW_VMEM_BYTES = 16 * 1024 * 1024


def _bwd_rule(causal, pattern_mask, sm_scale, block_q, block_k, interpret, window, res, do):
    """One ``pallas_call`` (``_bwd_kernel``) at every grid. Where dq sums over
    the key blocks is chosen from the SHAPE: a row of n·d·4 bytes within
    ``DQ_ROW_VMEM_BYTES`` stays in VMEM and dq leaves the kernel once, in the
    inputs' dtype; a longer row leaves as float32 partials (n/block_k, b·h,
    n, d) that XLA sums (n/block_k times dq's bytes in HBM: the price of a
    row that does not fit). With one key block there is nothing to sum. With
    a window the inner axis spans the band's query blocks (``_band_tables``)."""
    q, k, v, key_mask, o, lse = res
    b, h, n, d, nq, nk, mask_np, visit, window = _prep(
        q, pattern_mask, block_q, block_k, causal, window)
    dv = v.shape[-1]
    scale = d**-0.5 if sm_scale is None else sm_scale
    bh = b * h
    resident = nk > 1 and n * d * 4 <= DQ_ROW_VMEM_BYTES

    qf, kf = q.reshape(bh, n, d), k.reshape(bh, n, d)
    vf, dof, of = (t.reshape(bh, n, dv) for t in (v, do, o))
    lsef = lse.reshape(bh, 1, n)
    mask_op = [] if mask_np is None else [jnp.asarray(mask_np, jnp.int8)]
    km_op = [] if key_mask is None else [_bcast_key_mask(key_mask, b, h, n)]
    visit_t = np.ascontiguousarray(visit.T)
    band = {}
    if window is not None:
        band = dict(window=window, last_qb=nq - 1)
        nq = _band_span(nq, block_q, window)
        visit_t = _band_tables(visit, nq)[1]

    def query_block(kb, qb):
        return qb if window is None else jnp.minimum(kb + qb, band["last_qb"])

    def q_im(bhi, kb, qb, s):
        return (bhi, query_block(kb, qb), 0)

    def kv_im(bhi, kb, qb, s):
        return (bhi, kb, 0)

    in_specs = [
        pl.BlockSpec((1, block_q, d), q_im),
        pl.BlockSpec((1, block_k, d), kv_im),
        pl.BlockSpec((1, block_k, dv), kv_im),
        *(
            [pl.BlockSpec((block_q, block_k), lambda bhi, kb, qb, s: (qb, kb))]
            if mask_np is not None else []
        ),
        *(
            [pl.BlockSpec((1, 1, block_k), lambda bhi, kb, qb, s: (bhi, 0, kb))]
            if key_mask is not None else []
        ),
        pl.BlockSpec((1, block_q, dv), q_im),
        pl.BlockSpec((1, block_q, dv), q_im),
        pl.BlockSpec((1, 1, block_q), lambda bhi, kb, qb, s: (bhi, 0, query_block(kb, qb))),
    ]
    scratch = [
        pltpu.VMEM((block_k, d), jnp.float32), pltpu.VMEM((block_k, dv), jnp.float32),
    ] if nq > 1 else []
    if resident:
        dq_spec = pl.BlockSpec((1, n, d), lambda bhi, kb, qb, s: (bhi, 0, 0))
        dq_shape = jax.ShapeDtypeStruct((bh, n, d), q.dtype)
        scratch.append(pltpu.VMEM((n, d), jnp.float32))
    else:
        dq_spec = pl.BlockSpec(
            (1, block_q, d), lambda bhi, kb, qb, s: (kb * bh + bhi, query_block(kb, qb), 0))
        dq_shape = jax.ShapeDtypeStruct((nk * bh, n, d), jnp.float32 if nk > 1 else q.dtype)
    kernel = _with_optional_masks(
        functools.partial(
            _bwd_kernel, sm_scale=scale, block_q=block_q, block_k=block_k,
            nq=nq, nk=nk, dq_resident=resident, **band,
        ),
        mask_np is not None,
        key_mask is not None,
        n_out=3,
        n_scratch=len(scratch),
    )
    itemsize = q.dtype.itemsize
    # rows of d that dq moves: resident, block_k of the row's n a key block;
    # otherwise a block of its own dtype every inner step
    dq_outer = block_k if resident else 0
    dq_step = 0 if resident else block_q * dq_shape.dtype.itemsize // itemsize
    dq, dk, dv_ = _call(
        kernel,
        name="flash_bwd",
        grid=(bh, nk, nq),
        in_specs=in_specs,
        out_specs=[
            dq_spec,
            pl.BlockSpec((1, block_k, d), kv_im),
            pl.BlockSpec((1, block_k, dv), kv_im),
        ],
        out_shape=[
            dq_shape,
            jax.ShapeDtypeStruct((bh, n, d), q.dtype),
            jax.ShapeDtypeStruct((bh, n, dv), q.dtype),
        ],
        scratch=scratch,
        scalar=jnp.asarray(_scalar_table(visit_t)),
        operands=[qf, kf, vf, *mask_op, *km_op, dof, of, lsef],
        interpret=interpret,
        cost=_kernel_cost(visit_t, bh, block_q, block_k, d, 5,
                          3 * block_q + dq_step, 4 * block_k + dq_outer, itemsize,
                          dv, 2, 2 * block_q, 2 * block_k),
        # dk/dv accumulate over the query blocks, dq over the key blocks
        semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
    )
    if not resident and nk > 1:
        dq = dq.reshape(nk, bh, n, d).sum(axis=0).astype(q.dtype)
    dkm = None if key_mask is None else np.zeros(key_mask.shape, jax.dtypes.float0)
    return (
        dq.reshape(b, h, n, d),
        dk.reshape(b, h, n, d),
        dv_.reshape(b, h, n, dv),
        dkm,
    )


flash_attention.defvjp(_fwd_rule, _bwd_rule)


# ===================================================================== fused
# Packed-qkv single-block path: consumes the attention projection's raw
# (b, n, 3*h*d) output directly and emits (b, n, h*d), with the DALL-E
# rotary rotation applied INSIDE the kernel. This deletes, per layer and
# per direction, the qkv split, three (b, n, h, d) reshapes, three
# (0, 2, 1, 3) transposes and three rotary HBM sweeps (measured ~8 ms/step
# at the flagship config) — the kernel reads head slices straight out of
# the projection layout. Mosaic requires a block's minor dim to be a
# multiple of 128, so the grid processes ceil(128/d) heads per step
# (2 for the flagship d=64), statically unrolled in the kernel body.
# Single-block only (n == block): the production dispatch for seq <= 1280;
# tiled grids keep the per-head kernels above.


class StaticTable:
    """Hashable id-wrapper for a static (n, rot_width) numpy angle table.
    Registered as an EMPTY pytree (all data in the static aux): one object
    serves as the single source of truth for rotary angles on every path —
    it rides through traced kwargs (remat closures, shard_map bodies) as a
    static leaf, the fused kernel consumes it directly, and the unfused /
    decode paths materialize it with jnp.asarray — so the fused and
    fallback paths cannot silently apply different tables."""

    def __init__(self, table):
        self.table = np.asarray(table, dtype=np.float32)

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


jax.tree_util.register_pytree_node(
    StaticTable, lambda t: ((), t), lambda aux, _: aux
)


def _rot_tables(rot, n, d, dtype):
    """cos/sin operands (n, d) in the compute dtype. The angle table is
    zero-padded to the head dim (zero angle = identity rotation); cos/sin
    are taken of the float32 angles and only the results cast, by the same
    `ops/rotary.py:cos_sin` that `apply_rotary_emb` uses, so the fused path
    is bit-compatible with the unfused one at f32.

    The table must be PAIR-CONSTANT (angle identical within each (2i, 2i+1)
    channel pair): the fused backward's inverse rotation computes
    (dy @ P) * sin, which equals the true VJP term (sin * dy) @ P^T only
    under that symmetry. Every table rotary.py produces satisfies it (the
    repeat-2 in `angles`); a foreign table that does not would produce a
    correct forward with silently wrong gradients, so it is rejected here."""
    table = rot.table
    assert table.shape[0] >= n, (table.shape, n)
    table = table[:n]
    if table.shape[1] < d:
        table = np.pad(table, ((0, 0), (0, d - table.shape[1])))
    assert np.array_equal(table[:, 0::2], table[:, 1::2]), (
        "fused rotary requires a pair-constant angle table "
        "(table[:, 0::2] == table[:, 1::2]); see ops/rotary.py:angles"
    )
    return cos_sin(table, dtype)


def _rot_block(t, cos, sin, P):
    """In-kernel rotary: t*cos + rotate_half(t)*sin via the P-matrix dot.
    f32 accumulation (Mosaic requires 32-bit matmul acc); every product is
    an exact signed copy, so the rounding back to the input dtype matches
    the out-of-kernel rotate_half bitwise."""
    return t * cos + jax.lax.dot_general(
        t, P, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(t.dtype) * sin


def _inv_rot_block(t, cosf, sinf, Pf):
    """VJP of _rot_block = rotation by -theta: the rotation is orthogonal
    (P^T = -P, and sin/cos are constant within each rotation pair)."""
    return t * cosf - jax.lax.dot_general(
        t, Pf, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * sinf


def _fused_qkv_fwd_kernel(
    q_ref, k_ref, v_ref, mask_ref, kmask_ref, cos_ref, sin_ref, p_ref, o_ref, lse_ref,
    *, sm_scale, causal, d, hpb,
):
    outs = []
    for j in range(hpb):
        sl = slice(j * d, (j + 1) * d)
        q, k, v = q_ref[0][:, sl], k_ref[0][:, sl], v_ref[0][:, sl]
        if cos_ref is not None:
            cos, sin, P = cos_ref[:], sin_ref[:], p_ref[:].astype(q.dtype)
            q, k, v = (_rot_block(t, cos, sin, P) for t in (q, k, v))
        n = q.shape[0]
        s = _masked_scores(
            q, k, sm_scale, mask_ref, kmask_ref,
            1 if causal else 2, 0, 0, n, n,
        )
        m = jnp.max(s, axis=-1, keepdims=True)
        p = _masked_exp(s, m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) / l_safe
        outs.append(o.astype(o_ref.dtype))
        lse_ref[0, j] = jax.lax.transpose(m + jnp.log(l_safe), (1, 0))
    o_ref[0] = outs[0] if hpb == 1 else jnp.concatenate(outs, axis=-1)


def _fused_qkv_bwd_kernel(
    q_ref, k_ref, v_ref, mask_ref, kmask_ref, cos_ref, sin_ref, p_ref,
    do_ref, o_ref, lse_ref, dq_ref, dk_ref, dv_ref,
    *, sm_scale, causal, d, hpb,
):
    dqs, dks, dvs = [], [], []
    for j in range(hpb):
        sl = slice(j * d, (j + 1) * d)
        q, k, v = q_ref[0][:, sl], k_ref[0][:, sl], v_ref[0][:, sl]
        do = do_ref[0][:, sl]
        if cos_ref is not None:
            cos, sin, P = cos_ref[:], sin_ref[:], p_ref[:].astype(q.dtype)
            q, k, v = (_rot_block(t, cos, sin, P) for t in (q, k, v))
        n = q.shape[0]
        s = _masked_scores(
            q, k, sm_scale, mask_ref, kmask_ref,
            1 if causal else 2, 0, 0, n, n,
        )
        lse_row = jax.lax.transpose(lse_ref[0, j], (1, 0))  # (n, 1)
        p = _masked_exp(s, lse_row)
        dv_h = jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        delta = jnp.sum(
            do.astype(jnp.float32) * o_ref[0][:, sl].astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dq_h = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_h = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        if cos_ref is not None:
            cosf, sinf = cos_ref[:].astype(jnp.float32), sin_ref[:].astype(jnp.float32)
            Pf = p_ref[:].astype(jnp.float32)
            dq_h, dk_h, dv_h = (
                _inv_rot_block(t, cosf, sinf, Pf) for t in (dq_h, dk_h, dv_h)
            )
        dqs.append(dq_h.astype(dq_ref.dtype))
        dks.append(dk_h.astype(dk_ref.dtype))
        dvs.append(dv_h.astype(dv_ref.dtype))
    dq_ref[0] = dqs[0] if hpb == 1 else jnp.concatenate(dqs, axis=-1)
    dk_ref[0] = dks[0] if hpb == 1 else jnp.concatenate(dks, axis=-1)
    dv_ref[0] = dvs[0] if hpb == 1 else jnp.concatenate(dvs, axis=-1)


def _call_plain(kernel, grid, in_specs, out_specs, out_shape, operands, interpret, cost, *, name):
    return pl.pallas_call(
        kernel,
        name=name,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid),
            # the head-group backward holds several (n, n) f32 temporaries
            # at once (s, p, dp, ds); the default 16 MiB scoped-vmem budget
            # is exceeded at n=1280 x 2 heads — v5e has 128 MiB physical
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        cost_estimate=cost,
        interpret=interpret,
    )(*operands)


def fused_qkv_supported(n, heads, dim_head):
    """The packed path needs a lane-aligned whole-row block that fits VMEM
    and 128-aligned head groups. The n bound is derived from the backward's
    VMEM footprint instead of a fixed cap: per head group it materializes
    ~4 (n, n) f32 score-sized temporaries (s, p, dp, ds) x hpb unrolled
    heads, which must fit the 100 MB vmem_limit_bytes set in _call_plain
    (v5e has 128 MB physical) with ~20% headroom for the qkv/do/o blocks
    and double-buffered I/O. At d=64 (hpb=2) this admits n <= 1536
    (75.5 MB; verified to compile and run on v5e) and rejects n = 1792+;
    a fixed n <= 2048 cap used to pass this check yet fail to compile on
    real hardware."""
    hpb = max(1, 128 // dim_head)
    vmem_budget = int(VMEM_LIMIT_BYTES * 0.8)
    bwd_temp_bytes = 4 * n * n * 4 * hpb
    return (
        n % 128 == 0
        and bwd_temp_bytes <= vmem_budget
        and (dim_head * hpb) % 128 == 0
        and heads % hpb == 0
        and (heads * dim_head) % 128 == 0
    )


def _fused_prep(qkv, key_mask, heads, dim_head, rot, pattern_mask):
    b, n, thd = qkv.shape
    d, h = dim_head, heads
    assert thd == 3 * h * d, (qkv.shape, heads, dim_head)
    hpb = max(1, 128 // d)
    assert fused_qkv_supported(n, h, d)
    mask_np = None
    if pattern_mask is not None:
        assert isinstance(pattern_mask, StaticMask)
        mask_np = pattern_mask.mask
        assert mask_np.shape == (n, n)
    mask_op, mask_spec = [], []
    if mask_np is not None:
        mask_op = [jnp.asarray(mask_np, jnp.int8)]
        mask_spec = [pl.BlockSpec((n, n), lambda bi, g: (0, 0))]
    km_op, km_spec = [], []
    if key_mask is not None:
        assert key_mask.shape == (b, n), (key_mask.shape, (b, n))
        km_op = [key_mask[:, None, :].astype(jnp.int32)]
        km_spec = [pl.BlockSpec((1, 1, n), lambda bi, g: (bi, 0, 0))]
    rot_op, rot_spec = [], []
    if rot is not None:
        cos, sin = _rot_tables(rot, n, d, qkv.dtype)
        from .rotary import _rotate_half_matrix

        rot_op = [cos, sin, jnp.asarray(_rotate_half_matrix(d))]
        rot_spec = [pl.BlockSpec((n, d), lambda bi, g: (0, 0))] * 2 + [
            pl.BlockSpec((d, d), lambda bi, g: (0, 0))
        ]
    return b, n, d, h, hpb, mask_op, mask_spec, km_op, km_spec, rot_op, rot_spec


def _fused_cost(b, n, d, h, dots, rot_dots, dtype_bytes):
    """``dots`` big (n, n, d) block dots + ``rot_dots`` rotate-half
    (n, d, d) P-dots per head (fwd: q/k/v rotation = 3; bwd: those plus the
    inverse rotation of the three gradients = 9 total across both)."""
    return pl.CostEstimate(
        flops=b * h * (dots * 2 * n * n * d + rot_dots * 2 * n * d * d),
        transcendentals=b * h * n * n,
        bytes_accessed=b * h * n * d * dtype_bytes * (3 + dots),
    )


def _fused_unpack(kernel, n_extra, mask_op, km_op, rot_op, **static):
    """Positional-ref adapter shared by the fused fwd/bwd pallas bodies:
    q/k/v, then the optional (pattern, key-mask, cos/sin/P) operands, then
    ``n_extra`` trailing inputs (bwd: do, o, lse), then the outputs."""

    def wrapped(*refs):
        split = 3 + len(mask_op) + len(km_op) + len(rot_op) + n_extra
        ins = list(refs[:split])
        outs = refs[split:]
        fixed, rest = ins[:3], ins[3:]
        mr = rest.pop(0) if mask_op else None
        kmr = rest.pop(0) if km_op else None
        cr = rest.pop(0) if rot_op else None
        sr = rest.pop(0) if rot_op else None
        pr = rest.pop(0) if rot_op else None
        return kernel(*fixed, mr, kmr, cr, sr, pr, *rest, *outs, **static)

    return wrapped


def _fused_qkv_fwd(qkv, key_mask, heads, dim_head, rot, causal, pattern_mask, sm_scale, interpret):
    (b, n, d, h, hpb, mask_op, mask_spec, km_op, km_spec, rot_op, rot_spec) = (
        _fused_prep(qkv, key_mask, heads, dim_head, rot, pattern_mask)
    )
    scale = d**-0.5 if sm_scale is None else sm_scale
    g = h // hpb
    w = hpb * d  # block width (a multiple of 128)
    hd = h * d

    def q_im(bi, gi):
        return (bi, 0, gi)

    def k_im(bi, gi):
        return (bi, 0, g + gi)

    def v_im(bi, gi):
        return (bi, 0, 2 * g + gi)

    in_specs = [
        pl.BlockSpec((1, n, w), q_im),
        pl.BlockSpec((1, n, w), k_im),
        pl.BlockSpec((1, n, w), v_im),
        *mask_spec, *km_spec, *rot_spec,
    ]
    wrapped = _fused_unpack(
        _fused_qkv_fwd_kernel, 0, mask_op, km_op, rot_op,
        sm_scale=scale, causal=causal, d=d, hpb=hpb,
    )

    o, lse = _call_plain(
        wrapped,
        name="flash_qkv_fwd",
        grid=(b, g),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, n, w), lambda bi, gi: (bi, 0, gi)),
            pl.BlockSpec((1, hpb, 1, n), lambda bi, gi: (bi, gi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, hd), qkv.dtype),
            jax.ShapeDtypeStruct((b, h, 1, n), jnp.float32),
        ],
        operands=[qkv, qkv, qkv, *mask_op, *km_op, *rot_op],
        interpret=interpret,
        cost=_fused_cost(b, n, d, h, 2, 3 if rot_op else 0, qkv.dtype.itemsize),
    )
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8))
def fused_qkv_attention(
    qkv, key_mask, heads, dim_head, rot=None, causal=True,
    pattern_mask=None, sm_scale=None, interpret=False,
):
    """Packed single-block attention: (b, n, 3*h*d) -> (b, n, h*d), rotary
    (q, k AND v — the reference's quirk, attention.py:63-64) applied inside
    the kernel from the static angle table ``rot`` (StaticTable). Covers
    the reference's dense causal + pad-mask semantics (attention.py:39-86)
    in the projection's own layout: no split/reshape/transpose ops touch
    HBM between the qkv projection and the output projection."""
    o, _ = _fused_qkv_fwd(
        qkv, key_mask, heads, dim_head, rot, causal, pattern_mask, sm_scale, interpret
    )
    return o


def _fused_fwd_rule(qkv, key_mask, heads, dim_head, rot, causal, pattern_mask, sm_scale, interpret):
    o, lse = _name_residuals(*_fused_qkv_fwd(
        qkv, key_mask, heads, dim_head, rot, causal, pattern_mask, sm_scale, interpret
    ))
    return o, (qkv, key_mask, o, lse)


def _fused_bwd_rule(heads, dim_head, rot, causal, pattern_mask, sm_scale, interpret, res, do):
    qkv, key_mask, o, lse = res
    (b, n, d, h, hpb, mask_op, mask_spec, km_op, km_spec, rot_op, rot_spec) = (
        _fused_prep(qkv, key_mask, heads, dim_head, rot, pattern_mask)
    )
    scale = d**-0.5 if sm_scale is None else sm_scale
    g = h // hpb
    w = hpb * d
    hd = h * d

    in_specs = [
        pl.BlockSpec((1, n, w), lambda bi, gi: (bi, 0, gi)),
        pl.BlockSpec((1, n, w), lambda bi, gi: (bi, 0, g + gi)),
        pl.BlockSpec((1, n, w), lambda bi, gi: (bi, 0, 2 * g + gi)),
        *mask_spec, *km_spec, *rot_spec,
        pl.BlockSpec((1, n, w), lambda bi, gi: (bi, 0, gi)),
        pl.BlockSpec((1, n, w), lambda bi, gi: (bi, 0, gi)),
        pl.BlockSpec((1, hpb, 1, n), lambda bi, gi: (bi, gi, 0, 0)),
    ]

    wrapped = _fused_unpack(
        _fused_qkv_bwd_kernel, 3, mask_op, km_op, rot_op,
        sm_scale=scale, causal=causal, d=d, hpb=hpb,
    )

    dq, dk, dv = _call_plain(
        wrapped,
        name="flash_qkv_bwd",
        grid=(b, g),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, n, w), lambda bi, gi: (bi, 0, gi)),
            pl.BlockSpec((1, n, w), lambda bi, gi: (bi, 0, gi)),
            pl.BlockSpec((1, n, w), lambda bi, gi: (bi, 0, gi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, hd), qkv.dtype),
            jax.ShapeDtypeStruct((b, n, hd), qkv.dtype),
            jax.ShapeDtypeStruct((b, n, hd), qkv.dtype),
        ],
        operands=[qkv, qkv, qkv, *mask_op, *km_op, *rot_op, do, o, lse],
        interpret=interpret,
        cost=_fused_cost(b, n, d, h, 5, 6 if rot_op else 0, qkv.dtype.itemsize),
    )
    dqkv = jnp.concatenate((dq, dk, dv), axis=-1)
    dkm = None if key_mask is None else np.zeros(key_mask.shape, jax.dtypes.float0)
    return (dqkv, dkm)


fused_qkv_attention.defvjp(_fused_fwd_rule, _fused_bwd_rule)
