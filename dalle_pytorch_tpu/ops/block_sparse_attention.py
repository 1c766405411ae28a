"""Block-sparse Pallas attention: the grid visits only live block pairs.

The flash kernel (ops/flash_attention.py) streams EVERY (q-block, k-block)
pair and uses its scalar-prefetch visit table to skip compute on dead
blocks — index maps stay affine, so dead blocks still pay their K/V DMA.
That is the right trade for near-dense patterns, and it is why every
sparse/axial/conv variant ran at 0.97-0.99x of full attention there
(pre-ledger note, not in PERF_LEDGER.jsonl):
the sparse patterns pay full memory traffic plus a streamed mask.

Here the grid itself is the sparsity pattern. A host-compiled
``BlockLayout`` flattens the live (q-block, k-block) pairs of the static
pattern (ops/masks.py) into scalar-prefetch tables, and the kernel grid is
``(b*h, n_live_pairs)`` — the K/V index maps dereference the table, so
each step DMAs a DISTINCT live block and Mosaic's double buffering
survives (the ragged decode kernel, ops/ragged_attention.py, established
this idiom: table-indexed page fetches pipeline fine; what measured 23x
slower in the flash experiment was CLAMPING dead steps to re-fetch the
same block). Dead blocks are simply never part of the grid: no DMA, no
compute, and the ``pl.CostEstimate`` scales with live pairs, so the
scheduler sees the real FLOP saving.

Pairs are ordered q-block-major with first/last flags riding the table;
online softmax accumulates (m, l, acc) in VMEM scratch across a q-block's
visited pairs and finalizes on the last one. Partial blocks (diagonal
causal crossings, pattern edges) stream their slice of the elementwise
mask; the backward is the FlashAttention-2 decomposition over the same
pair list (dq q-major, dk/dv over a k-major reordering).

The jnp reference path shares ``cache_block_attend``'s einsums with the
expanded elementwise mask (the ops/ragged_attention.py idiom), so kernel
vs reference parity is pinned allclose in interpret mode on CPU while the
dense-mask semantics stay the single source of truth.

The SP half: ``compile_sp_plan`` assigns q-blocks to ``sp``-axis chips
with a DUAL-BALANCED objective (db-SP, PAPERS.md 2511.23113): greedy LPT
on per-block visited-pair counts under a per-chip block-count cap, so both
the q-block count and the visited-pair count are even per chip — an axial
pattern's skewed rows (text rows attend everything, image rows a thin
band) no longer serialize the slowest chip. ``sp_block_sparse_attend`` is
the shard_map body: all-gather K/V/Q over sp, each chip computes its
assigned (permuted) q-rows, and a static inverse permutation restores
natural order before each chip returns its contiguous shard.

What a grid step costs, and where the kernel is chosen (v5e, PERF.md
section 6, PR 31): at the 128 x 128 block a step is two 128 x 128 x 64 dots,
~0.02 us of MXU work, under ~0.7 us of Mosaic's per-step overhead. At seq
1280 (batch 8, 16 heads: 8 x 16 x 40 live pairs = 5,120 steps in each of the
three kernels) an axial_row or conv_like layer took 10.5 ms forward +
backward against 2.2 ms for the packed single-block flash kernel
(ops/flash_attention.py:fused_qkv_attention, 64 steps) computing the WHOLE
square with the pattern as a streamed mask. So training
(ops/attention.py:PatternAttention.__call__) takes the packed kernel
wherever it is eligible and this kernel only at shapes it cannot run —
seq > 1536 at d 64 (the whole-row block no longer fits VMEM), a ``tp``
mesh, ``use_flash=False`` — and there only for layouts that really skip
block pairs (``ENGAGE_FRAC``). The sequence-parallel path
(``sp_block_sparse_attend``) is gated separately.

Policy: ``DALLE_TPU_SPARSE_KERNEL`` (unset/"auto" = TPU only, "0"/"1"
force — kv_policy.tpu_auto_env semantics) switches the kernel for the
shapes where it is a candidate; the dense-mask paths remain the fallback
and the off-TPU default.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128

# production block edge: the lane dimension must be a multiple of 128 and
# per-grid-step overhead dominates below it (the flash kernel's measured
# floor); layouts for tests/CPU may use any block sizes in interpret mode
DEFAULT_BLOCK = 128

# routing threshold, for the shapes where the pair grid is a candidate at
# all (the packed flash kernel ineligible — see the module docstring): it
# engages only when the compiled layout skips at least this much of the
# dense-causal pair set. A layout whose live stride is finer than the block
# edge (axial_col at fmap <= 128, the 16-block DeepSpeed-style random
# layout) visits every pair — frac 1.0 — and would pay pair-grid overhead
# for zero skipped FLOPs; those patterns stay on the dense/flash paths
# until their geometry actually block-skips
ENGAGE_FRAC = 0.9


def sparse_kernel_enabled() -> bool:
    """Policy knob for routing sparse patterns through this kernel.
    "auto"/unset: TPU only (the CPU tier keeps the dense-mask paths that
    every bitwise contract is pinned on); ``DALLE_TPU_SPARSE_KERNEL=0|1``
    forces either way (tests/bench use 1 with interpret mode on CPU)."""
    from .kv_policy import tpu_auto_env

    return tpu_auto_env("DALLE_TPU_SPARSE_KERNEL")


# ------------------------------------------------------------------- layout


def _pair_lists(visit: np.ndarray):
    """q-major live pair arrays from a (nq, nk) visit map, with synthetic
    all-masked pairs for empty q rows so every output block is written
    (an empty row finalizes with l == 0 -> exact 0 output)."""
    nq, nk = visit.shape
    q_idx, k_idx, kclass = [], [], []
    for qb in range(nq):
        cols = np.flatnonzero(visit[qb])
        if cols.size == 0:
            # synthetic pair: class 0 tells the kernel the mask block may
            # contain live bits belonging to OTHER rows — mask everything
            q_idx.append(qb)
            k_idx.append(min(qb, nk - 1))
            kclass.append(0)
            continue
        for kb in cols:
            q_idx.append(qb)
            k_idx.append(kb)
            kclass.append(int(visit[qb, kb]))
    q_idx = np.asarray(q_idx, np.int32)
    k_idx = np.asarray(k_idx, np.int32)
    kclass = np.asarray(kclass, np.int32)
    first = np.concatenate(([1], (q_idx[1:] != q_idx[:-1]).astype(np.int32)))
    last = np.concatenate(((q_idx[1:] != q_idx[:-1]).astype(np.int32), [1]))
    return q_idx, k_idx, kclass, first, last


def _table(q_idx, k_idx, kclass, first, last) -> np.ndarray:
    """(5, P) int32 scalar-prefetch payload: rows are q-block index,
    k-block index, visit class, first-of-group, last-of-group."""
    return np.stack([q_idx, k_idx, kclass, first, last]).astype(np.int32)


@dataclasses.dataclass(frozen=True, eq=False)
class BlockLayout:
    """Host-compiled block program for one static pattern.

    Hash/eq by identity (the StaticMask idiom, ops/flash_attention.py):
    build once per (pattern config, n) via a cached constructor so jit and
    custom_vjp see a stable static argument. ``mask`` is the elementwise
    (n_pad, n_pad) may-attend matrix, zero-padded past ``n`` — the single
    source of truth both the kernel (streamed int8 blocks) and the jnp
    reference consume, so they cannot drift.
    """

    n: int
    n_pad: int
    block_q: int
    block_k: int
    visit: np.ndarray  # (nq, nk) int32: 0 skip / 1 partial / 2 dense
    mask: np.ndarray  # (n_pad, n_pad) bool
    fwd_table: np.ndarray  # (5, Pq) int32, q-major
    kv_table: np.ndarray  # (5, Pk) int32, k-major

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other

    @property
    def nq(self) -> int:
        return self.visit.shape[0]

    @property
    def nk(self) -> int:
        return self.visit.shape[1]

    @property
    def n_pairs(self) -> int:
        return int((self.visit > 0).sum())

    @property
    def dense_pairs(self) -> int:
        """Block pairs a full-causal layout visits at these block sizes —
        the denominator of the block-skip win."""
        q_hi = (np.arange(self.nq) + 1) * self.block_q - 1
        k_lo = np.arange(self.nk) * self.block_k
        return int((k_lo[None, :] <= q_hi[:, None]).sum())

    @property
    def visited_block_frac(self) -> float:
        """Live pairs / dense-causal pairs: the block-skip FLOP ratio the
        bench asserts < 1.0 for every sparse layout."""
        return self.n_pairs / max(self.dense_pairs, 1)


def compile_block_layout(
    mask: np.ndarray,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
) -> BlockLayout:
    """Compile an elementwise (n, n) may-attend mask into a BlockLayout.

    Ragged tails are zero-padded to the block grid: padded keys are never
    attendable, padded query rows are fully masked and finalize to exact 0
    (sliced off by the caller)."""
    mask = np.asarray(mask, dtype=bool)
    n = mask.shape[0]
    assert mask.shape == (n, n), mask.shape
    nq = -(-n // block_q)
    nk = -(-n // block_k)
    n_pad_q, n_pad_k = nq * block_q, nk * block_k
    n_pad = max(n_pad_q, n_pad_k)
    padded = np.zeros((n_pad, n_pad), dtype=bool)
    padded[:n, :n] = mask

    visit = np.zeros((nq, nk), dtype=np.int32)
    for qb in range(nq):
        row = padded[qb * block_q : (qb + 1) * block_q]
        for kb in range(nk):
            blk = row[:, kb * block_k : (kb + 1) * block_k]
            visit[qb, kb] = 0 if not blk.any() else (2 if blk.all() else 1)

    fwd = _table(*_pair_lists(visit))
    # k-major reordering for the dkv backward: transpose the visit map,
    # build groups per k block, swap the index rows back to (q, k) order
    tk = _table(*_pair_lists(np.ascontiguousarray(visit.T)))
    kv = np.stack([tk[1], tk[0], tk[2], tk[3], tk[4]]).astype(np.int32)
    return BlockLayout(
        n=n, n_pad=n_pad, block_q=block_q, block_k=block_k,
        visit=visit, mask=padded, fwd_table=fwd, kv_table=kv,
    )


# ------------------------------------------------------------------ kernels


def _masked_exp(s, x):
    """exp(s - x) with fully-masked entries forced to 0 (the flash kernel's
    guard): rows dead in every visited block keep m/lse at NEG_INF, where
    exp(s - x) would be 1."""
    return jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - x), 0.0)


def _row_vec(ref):
    """(1, 1, bq) ref block -> (bq, 1) f32."""
    return jax.lax.transpose(ref[0], (1, 0))


def _pair_scores(q, k, sm_scale, mask_ref, kmask_ref, kclass):
    """(bq, bk) f32 scores for one live pair. The streamed mask block is
    applied unless the pair is classified dense (class 2: every bit set,
    the where would be a no-op — skipping it keeps dense blocks pure MXU
    work, the 'causal masking only on diagonal/partial blocks' rule).
    Synthetic class-0 pairs (empty q rows) mask everything."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    # i8 -> i32 widen before compare: Mosaic on v5e cannot lower cmpi on
    # the packed vector<..xi8> layout (flash kernel note)
    live = mask_ref[:].astype(jnp.int32) > 0
    s = jnp.where(kclass == 2, s, jnp.where(live, s, NEG_INF))
    s = jnp.where(kclass == 0, NEG_INF, s)
    if kmask_ref is not None:
        s = jnp.where(kmask_ref[0] > 0, s, NEG_INF)  # (1, bk) over rows
    return s


def _fwd_kernel(
    tab_ref, q_ref, k_ref, v_ref, mask_ref, kmask_ref, o_ref, lse_ref,
    m_scr, l_scr, acc_scr,
    *, sm_scale,
):
    p = pl.program_id(1)
    kclass = tab_ref[2, p]

    @pl.when(tab_ref[3, p] == 1)  # first pair of this q block
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    s = _pair_scores(q_ref[0], k_ref[0], sm_scale, mask_ref, kmask_ref, kclass)
    m_prev = m_scr[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    pv = _masked_exp(s, m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[:, 0:1] = l_scr[:, 0:1] * corr + jnp.sum(pv, axis=-1, keepdims=True)
    m_scr[:, 0:1] = m_new
    acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
        pv.astype(v_ref.dtype), v_ref[0],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )

    @pl.when(tab_ref[4, p] == 1)  # last pair: finalize this q block
    def _():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:, 0:1] + jnp.log(l_safe)
        lse_ref[0] = jax.lax.transpose(lse, (1, 0))


def _bwd_dq_kernel(
    tab_ref, q_ref, k_ref, v_ref, mask_ref, kmask_ref, do_ref, lse_ref,
    delta_ref, dq_ref, dq_scr,
    *, sm_scale,
):
    p = pl.program_id(1)

    @pl.when(tab_ref[3, p] == 1)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    s = _pair_scores(q, k, sm_scale, mask_ref, kmask_ref, tab_ref[2, p])
    pv = _masked_exp(s, _row_vec(lse_ref))
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = pv * (dp - _row_vec(delta_ref)) * sm_scale
    dq_scr[:] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(tab_ref[4, p] == 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    tab_ref, q_ref, k_ref, v_ref, mask_ref, kmask_ref, do_ref, lse_ref,
    delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
    *, sm_scale,
):
    p = pl.program_id(1)

    @pl.when(tab_ref[3, p] == 1)  # first pair of this k block
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    s = _pair_scores(q, k, sm_scale, mask_ref, kmask_ref, tab_ref[2, p])
    pv = _masked_exp(s, _row_vec(lse_ref))
    dv_scr[:] += jax.lax.dot_general(
        pv.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = (pv * (dp - _row_vec(delta_ref)) * sm_scale).astype(q.dtype)
    dk_scr[:] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(tab_ref[4, p] == 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# ------------------------------------------------------------------ plumbing


def _pair_cost(n_pairs, n_qblocks, bh, bq, bk, d, dots, dtype_bytes):
    """Live-pair cost: unlike the flash kernel (affine maps, every block
    DMAs), both compute AND streamed K/V traffic scale with the live pair
    count — this estimate is the block-skip win the scheduler sees."""
    return pl.CostEstimate(
        flops=bh * n_pairs * dots * 2 * bq * bk * d,
        transcendentals=bh * n_pairs * bq * bk,
        bytes_accessed=bh
        * (n_pairs * 2 * bk + n_qblocks * 2 * bq)
        * d
        * dtype_bytes,
    )


def _pair_call(kernel, grid, in_specs, out_specs, out_shape, scratch, table,
               operands, interpret, cost, *, name):
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
        # batch*heads steps are independent; the pair dimension accumulates
        # (q-block groups are contiguous runs) so it must stay ordered
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        cost_estimate=cost,
        interpret=interpret,
    )(table, *operands)


def _opt_kmask(kernel, has_km, n_out, n_scratch):
    """Adapt a kernel with a (mask_ref, kmask_ref) slot pair to calls
    without the optional runtime key-mask operand."""

    def wrapped(*refs):
        split = len(refs) - n_out - n_scratch
        ins = list(refs[:split])
        rest = refs[split:]
        fixed, tail = ins[:5], ins[5:]  # tab, q, k, v, mask | optional km
        km = tail.pop(0) if has_km else None
        return kernel(*fixed, km, *tail, *rest)

    return wrapped


def _bcast_key_mask(key_mask, bh, heads, n):
    """(b, n) bool -> (b*h, 1, n) int32 streamed operand (the flash
    kernel's layout: int32 because Mosaic v5e cannot compare packed i8
    on a (1, 1, bk) block)."""
    b = bh // heads
    assert key_mask.shape == (b, n), (key_mask.shape, (b, n))
    return jnp.broadcast_to(
        key_mask[:, None, :].astype(jnp.int32), (b, heads, n)
    ).reshape(bh, 1, n)


def _specs(bq, bk, d, has_km):
    """Common forward/backward input specs over the scalar pair table:
    K/V index maps dereference the table, so every grid step fetches a
    DISTINCT live block (pipelining-safe, the ragged-kernel idiom);
    the q/out maps revisit their block across a contiguous pair run."""

    def q_im(bhi, p, s):
        return (bhi, s[0, p], 0)

    def kv_im(bhi, p, s):
        return (bhi, s[1, p], 0)

    def mask_im(bhi, p, s):
        return (s[0, p], s[1, p])

    base = [
        pl.BlockSpec((1, bq, d), q_im),
        pl.BlockSpec((1, bk, d), kv_im),
        pl.BlockSpec((1, bk, d), kv_im),
        pl.BlockSpec((bq, bk), mask_im),
    ]
    if has_km:
        base.append(pl.BlockSpec((1, 1, bk), lambda bhi, p, s: (bhi, 0, s[1, p])))
    return base, q_im, kv_im


def _bs_fwd(q, k, v, key_mask, mask_i8, fwd_table, kv_table, sm_scale,
            block_q, block_k, interpret):
    """Forward over flattened (b*h, n, d) operands; returns (o, lse)."""
    bh, nq_rows, d = q.shape
    nk_rows = k.shape[1]
    bq, bk = block_q, block_k
    nq = nq_rows // bq
    n_pairs = fwd_table.shape[1]

    in_specs, q_im, _ = _specs(bq, bk, d, key_mask is not None)
    operands = [q, k, v, mask_i8]
    if key_mask is not None:
        operands.append(key_mask)

    kernel = _opt_kmask(
        functools.partial(_fwd_kernel, sm_scale=sm_scale),
        key_mask is not None, n_out=2, n_scratch=3,
    )
    o, lse = _pair_call(
        kernel,
        name="block_sparse_fwd",
        grid=(bh, n_pairs),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), q_im),
            pl.BlockSpec((1, 1, bq), lambda bhi, p, s: (bhi, 0, s[0, p])),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nq_rows, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, nq_rows), jnp.float32),
        ],
        scratch=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        table=fwd_table,
        operands=operands,
        interpret=interpret,
        cost=_pair_cost(n_pairs, nq, bh, bq, bk, d, 2, q.dtype.itemsize),
    )
    del nk_rows
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _pair_attention(q, k, v, key_mask, mask_i8, fwd_table, kv_table,
                    sm_scale, block_q, block_k, interpret):
    """custom_vjp core over flattened operands. The tables and mask are
    TRACED operands (int gradients are float0 zeros, the flash key-mask
    idiom) so the sp path can select a chip's tables with axis_index —
    only block sizes and the pair counts (via the table shapes) are
    static."""
    o, _ = _bs_fwd(q, k, v, key_mask, mask_i8, fwd_table, kv_table,
                   sm_scale, block_q, block_k, interpret)
    return o


def _pair_fwd_rule(q, k, v, key_mask, mask_i8, fwd_table, kv_table,
                   sm_scale, block_q, block_k, interpret):
    o, lse = _bs_fwd(q, k, v, key_mask, mask_i8, fwd_table, kv_table,
                     sm_scale, block_q, block_k, interpret)
    return o, (q, k, v, key_mask, mask_i8, fwd_table, kv_table, o, lse)


def _pair_bwd_rule(sm_scale, block_q, block_k, interpret, res, do):
    q, k, v, key_mask, mask_i8, fwd_table, kv_table, o, lse = res
    bh, nq_rows, d = q.shape
    nk_rows = k.shape[1]
    bq, bk = block_q, block_k
    nq, nk = nq_rows // bq, nk_rows // bk
    n_pairs_q = fwd_table.shape[1]
    n_pairs_k = kv_table.shape[1]

    # delta = rowsum(do * o): one fused elementwise pass (the split flash
    # kernels derive it in-kernel; at a pair grid the q block is revisited
    # per pair, so hoisting it out is both simpler and cheaper)
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).reshape(bh, 1, nq_rows)
    lsef = lse.reshape(bh, 1, nq_rows)

    has_km = key_mask is not None
    in_specs, q_im, kv_im = _specs(bq, bk, d, has_km)
    km_op = [key_mask] if has_km else []

    def row_im(bhi, p, s):
        return (bhi, 0, s[0, p])

    # ---- dq over the q-major pair list ------------------------------------
    dq_specs = in_specs + [
        pl.BlockSpec((1, bq, d), q_im),
        pl.BlockSpec((1, 1, bq), row_im),
        pl.BlockSpec((1, 1, bq), row_im),
    ]
    dq_kernel = _opt_kmask(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale),
        has_km, n_out=1, n_scratch=1,
    )
    (dq,) = _pair_call(
        dq_kernel,
        name="block_sparse_dq",
        grid=(bh, n_pairs_q),
        in_specs=dq_specs,
        out_specs=[pl.BlockSpec((1, bq, d), q_im)],
        out_shape=[jax.ShapeDtypeStruct((bh, nq_rows, d), q.dtype)],
        scratch=[pltpu.VMEM((bq, d), jnp.float32)],
        table=fwd_table,
        operands=[q, k, v, mask_i8, *km_op, do, lsef, delta],
        interpret=interpret,
        cost=_pair_cost(n_pairs_q, nq, bh, bq, bk, d, 3, q.dtype.itemsize),
    )

    # ---- dk/dv over the k-major pair list ---------------------------------
    dkv_specs = in_specs + [
        pl.BlockSpec((1, bq, d), q_im),
        pl.BlockSpec((1, 1, bq), row_im),
        pl.BlockSpec((1, 1, bq), row_im),
    ]
    dkv_kernel = _opt_kmask(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale),
        has_km, n_out=2, n_scratch=2,
    )
    dk, dv = _pair_call(
        dkv_kernel,
        name="block_sparse_dkv",
        grid=(bh, n_pairs_k),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), kv_im),
            pl.BlockSpec((1, bk, d), kv_im),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nk_rows, d), q.dtype),
            jax.ShapeDtypeStruct((bh, nk_rows, d), q.dtype),
        ],
        scratch=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        table=kv_table,
        operands=[q, k, v, mask_i8, *km_op, do, lsef, delta],
        interpret=interpret,
        cost=_pair_cost(n_pairs_k, nk, bh, bq, bk, d, 4, q.dtype.itemsize),
    )

    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    dkm = None if key_mask is None else f0(key_mask)
    return dq, dk, dv, dkm, f0(mask_i8), f0(fwd_table), f0(kv_table)


_pair_attention.defvjp(_pair_fwd_rule, _pair_bwd_rule)


# ------------------------------------------------------------------- public


def _pad_rows(t, rows, axis):
    pad = rows - t.shape[axis]
    if pad == 0:
        return t
    widths = [(0, 0)] * t.ndim
    widths[axis] = (0, pad)
    return jnp.pad(t, widths)


def block_sparse_attention(
    q, k, v, layout: BlockLayout,
    key_mask=None,
    sm_scale: Optional[float] = None,
    interpret: bool = False,
):
    """Block-sparse attention over (b, h, n, d); q NOT pre-scaled.

    ``layout``: a compiled BlockLayout for this n (build via
    compile_block_layout / attention._cached_block_layout). ``key_mask``:
    runtime (b, n) bool, True = attendable; rows with every key masked
    return exactly 0 (the flash contract — NOT the dense softmax's
    uniform average, which is why parity tests compare live rows)."""
    b, h, n, d = q.shape
    assert layout.n == n, (layout.n, n)
    scale = d**-0.5 if sm_scale is None else sm_scale
    bh = b * h
    qf, kf, vf = (
        _pad_rows(t.reshape(bh, n, d), layout.n_pad, 1) for t in (q, k, v)
    )
    kmf = None
    if key_mask is not None:
        kmf = _pad_rows(
            _bcast_key_mask(key_mask, bh, h, n), layout.n_pad, 2
        )
    o = _pair_attention(
        qf, kf, vf, kmf,
        jnp.asarray(layout.mask, jnp.int8),
        jnp.asarray(layout.fwd_table),
        jnp.asarray(layout.kv_table),
        scale, layout.block_q, layout.block_k, interpret,
    )
    return o[:, :n].reshape(b, h, n, d)


def reference_attend(
    q, k, v, layout: BlockLayout,
    key_mask=None,
    sm_scale: Optional[float] = None,
    stable: bool = False,
):
    """jnp parity path over (b, h, n, d): the layout's elementwise mask fed
    through ``cache_block_attend``'s einsums (the ops/ragged_attention.py
    idiom) — exact dense-mask semantics by construction, and the CPU
    tier-1 oracle the kernel is pinned against."""
    from .attention import cache_block_attend

    b, h, n, d = q.shape
    scale = d**-0.5 if sm_scale is None else sm_scale
    allowed = jnp.asarray(layout.mask[:n, :n])[None, None]  # (1, 1, n, n)
    if key_mask is not None:
        allowed = allowed & key_mask[:, None, None, :]
    out = cache_block_attend(
        (q * scale).transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        allowed,
        stable,
    )
    return out.transpose(0, 2, 1, 3)


# =============================================================== SP balancing


def dual_balanced_assignment(
    weights: np.ndarray, n_chips: int, cap: Optional[int] = None
) -> np.ndarray:
    """db-SP dual-balanced q-block -> chip map (PAPERS.md 2511.23113).

    Greedy LPT on per-block visited-pair counts under a per-chip
    block-count cap ceil(nq / n_chips): both objectives are balanced at
    once — block counts within one of each other (the cap), and pair
    loads within one block's weight (the LPT bound), so an axial
    pattern's heavy text rows spread across chips instead of serializing
    the ring. Host-side numpy over the static layout; nothing traced."""
    weights = np.asarray(weights, dtype=np.int64)
    nq = weights.shape[0]
    assert n_chips >= 1
    if cap is None:
        cap = -(-nq // n_chips)
    loads = np.zeros(n_chips, dtype=np.int64)
    counts = np.zeros(n_chips, dtype=np.int64)
    assign = np.zeros(nq, dtype=np.int64)
    for blk in np.argsort(-weights, kind="stable"):
        elig = np.flatnonzero(counts < cap)
        chip = elig[np.argmin(loads[elig], axis=0)]
        assign[blk] = chip
        loads[chip] += weights[blk]
        counts[chip] += 1
    return assign


@dataclasses.dataclass(frozen=True, eq=False)
class SpPlan:
    """Host-compiled per-chip execution plan for sequence-parallel
    block-sparse attention (identity hash, like BlockLayout). All arrays
    are chip-major so a shard_map body selects its slice with
    ``axis_index`` — the plan itself stays static data."""

    layout: BlockLayout
    sp: int
    assign: np.ndarray  # (nq,) chip per q block
    row_table: np.ndarray  # (sp, rows_pc) int32 global q-row per local row
    inv_perm: np.ndarray  # (n_pad,) int32: natural row -> gathered position
    masks: np.ndarray  # (sp, rows_pc, n_pad) bool: per-chip mask rows
    fwd_tables: np.ndarray  # (sp, 5, Pq_max) int32, local q indices
    kv_tables: np.ndarray  # (sp, 5, Pk_max) int32, local q indices
    pair_counts: np.ndarray  # (sp,) live pairs per chip (balance metric)

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other

    @property
    def rows_per_chip(self) -> int:
        return self.row_table.shape[1]


def _chip_tables(visit_rows: np.ndarray, kind: str) -> np.ndarray:
    if kind == "fwd":
        return _table(*_pair_lists(visit_rows))
    tk = _table(*_pair_lists(np.ascontiguousarray(visit_rows.T)))
    return np.stack([tk[1], tk[0], tk[2], tk[3], tk[4]]).astype(np.int32)


def _pad_table(tab: np.ndarray, width: int, kind: str) -> np.ndarray:
    """Right-pad a (5, P) pair table to a common static width with no-op
    pairs: class 0 (mask-everything), first=0 so scratch is not reset,
    last=0 so nothing finalizes — trailing pads leave the already-written
    output blocks untouched."""
    pad = width - tab.shape[1]
    if pad == 0:
        return tab
    q_end, k_end = tab[0, -1], tab[1, -1]
    filler = np.stack([
        np.full(pad, q_end), np.full(pad, k_end),
        np.zeros(pad), np.zeros(pad), np.zeros(pad),
    ]).astype(np.int32)
    return np.concatenate([tab, filler], axis=1)


def compile_sp_plan(layout: BlockLayout, sp: int) -> SpPlan:
    """Compile the dual-balanced per-chip plan from a BlockLayout."""
    nq, bq = layout.nq, layout.block_q
    weights = (layout.visit > 0).sum(axis=1)
    assign = dual_balanced_assignment(weights, sp)
    cap = -(-nq // sp)

    row_table = np.zeros((sp, cap * bq), dtype=np.int32)
    inv_perm = np.zeros(layout.n_pad, dtype=np.int32)
    masks = np.zeros((sp, cap * bq, layout.n_pad), dtype=bool)
    fwd_tabs, kv_tabs, pair_counts = [], [], []
    for chip in range(sp):
        blocks = np.flatnonzero(assign == chip)
        rows = np.concatenate(
            [np.arange(b * bq, (b + 1) * bq) for b in blocks]
        ) if blocks.size else np.zeros(0, np.int64)
        # pad empty slots with row 0: computed then dropped (inv_perm
        # never points at a pad slot)
        padded = np.concatenate([rows, np.zeros(cap * bq - rows.size, np.int64)])
        row_table[chip] = padded
        inv_perm[rows] = chip * cap * bq + np.arange(rows.size)
        masks[chip] = layout.mask[padded] if padded.size else masks[chip]
        masks[chip, rows.size:] = False  # pad rows attend nothing
        # local visit map: assigned block rows first, all-skip pad rows after
        visit_rows = np.zeros((cap, layout.nk), dtype=np.int32)
        visit_rows[: blocks.size] = layout.visit[blocks]
        fwd_tabs.append(_chip_tables(visit_rows, "fwd"))
        kv_tabs.append(_chip_tables(visit_rows, "kv"))
        pair_counts.append(int((visit_rows > 0).sum()))

    wq = max(t.shape[1] for t in fwd_tabs)
    wk = max(t.shape[1] for t in kv_tabs)
    return SpPlan(
        layout=layout, sp=sp, assign=assign,
        row_table=row_table, inv_perm=inv_perm, masks=masks,
        fwd_tables=np.stack([_pad_table(t, wq, "fwd") for t in fwd_tabs]),
        kv_tables=np.stack([_pad_table(t, wk, "kv") for t in kv_tabs]),
        pair_counts=np.asarray(pair_counts, np.int64),
    )


def sp_block_sparse_attend(
    q, k, v, plan: SpPlan, axis_name: str, axis_size: int,
    *, sm_scale: Optional[float] = None, key_mask=None,
    use_kernel: bool = False, interpret: bool = False, stable: bool = False,
):
    """shard_map body: dual-balanced sequence-parallel sparse attention.

    q, k, v: LOCAL (b, h, n/sp, d) shards of the natural sequence order.
    K/V (and Q, which is re-dealt to chips by the balanced assignment) are
    all-gathered over ``axis_name``; each chip computes its assigned
    q-rows — via the pair kernel when ``use_kernel`` (chip tables selected
    with axis_index as traced operands) or the dense-mask jnp path
    otherwise — then outputs are all-gathered and statically unpermuted so
    every chip returns its natural contiguous shard. Collectives: 4-5
    all-gathers, no permute ring — budgeted under DTL151/DTL154 by the
    train.sp shard contract."""
    b, h, n_local, d = q.shape
    n = n_local * axis_size
    layout = plan.layout
    assert layout.n == n, (layout.n, n)
    scale = d**-0.5 if sm_scale is None else sm_scale
    idx = jax.lax.axis_index(axis_name)

    qf = jax.lax.all_gather(q, axis_name, axis=2, tiled=True)
    kf = jax.lax.all_gather(k, axis_name, axis=2, tiled=True)
    vf = jax.lax.all_gather(v, axis_name, axis=2, tiled=True)
    kmf = None
    if key_mask is not None:
        kmf = jax.lax.all_gather(key_mask, axis_name, axis=1, tiled=True)

    rows = jnp.asarray(plan.row_table)[idx]  # (rows_pc,)
    q_my = jnp.take(qf, rows, axis=2)

    if use_kernel:
        bh = b * h
        rows_pc = plan.rows_per_chip
        qk = _pad_rows(q_my.reshape(bh, rows_pc, d), rows_pc, 1)
        kk = _pad_rows(kf.reshape(bh, n, d), layout.n_pad, 1)
        vk = _pad_rows(vf.reshape(bh, n, d), layout.n_pad, 1)
        kmk = None
        if kmf is not None:
            kmk = _pad_rows(_bcast_key_mask(kmf, bh, h, n), layout.n_pad, 2)
        mask_i8 = jnp.asarray(plan.masks, jnp.int8)[idx]
        o_my = _pair_attention(
            qk, kk, vk, kmk, mask_i8,
            jnp.asarray(plan.fwd_tables)[idx],
            jnp.asarray(plan.kv_tables)[idx],
            scale, layout.block_q, layout.block_k, interpret,
        ).reshape(b, h, rows_pc, d)
    else:
        from .attention import dense_attend

        allowed = jnp.asarray(plan.masks)[idx][:, :n][None, None]
        if kmf is not None:
            allowed = allowed & kmf[:, None, None, :]
        o_my = dense_attend(q_my * scale, kf, vf, allowed, stable)

    o_all = jax.lax.all_gather(o_my, axis_name, axis=2, tiled=True)
    o_nat = jnp.take(o_all, jnp.asarray(plan.inv_perm[:n]), axis=2)
    return jax.lax.dynamic_slice_in_dim(o_nat, idx * n_local, n_local, axis=2)
