"""Ragged paged attention: one attention program for a mixed
prefill+decode iteration ("Ragged Paged Attention", PAPERS.md).

The serving engine's fused iteration (serving/engine.py:_iteration_jit)
hands every cache row a DESCRIPTOR — (kind, start, length, page table) —
padded to one fixed iteration shape: a (B, W) token block where row b's
valid tokens occupy columns [0, length[b]) at positions
start[b] .. start[b] + length[b]. A decode row is length 1, a prefill
chunk up to W, an idle row 0 — raggedness is DATA, not shape, so every
steady mix of prefills and decodes shares one compile signature and the
whole iteration is a single device dispatch (final-chunk iterations are
the one extra, warm-compiled class; serving/engine.py:_iteration_jit).

Two implementations of the attention core — the attention layer
(``PatternAttention._decode_attend_paged``) picks via ``use_kernel``:

- ``reference_attend`` — plain jnp: ``paged_kv.gather`` assembles the
  logical (b, W_cache, h*d) view and ``ops/attention.py:
  cache_block_attend`` does the masked block attention. This is the
  tier-1 path (CPU, ``JAX_PLATFORMS=cpu``) and, by construction, shares
  every einsum with the split prefill-chunk/decode paths — which is what
  makes fused-vs-split ENGINE bit-parity exact for f32 models on CPU
  — the parity tier; bf16 programs round ~1 ulp apart across program
  shapes under XLA fusion — (pinned by
  tests/test_ragged_attention.py). Padding rows cost compute, never
  correctness: invalid query columns produce garbage that the caller
  discards, and their K/V is never written (``paged_kv.append``'s
  per-row ``limit``).

- ``kernel_attend`` — a Pallas TPU kernel streaming K/V PAGES through
  VMEM with an online-softmax accumulator, the page table + per-row
  (start, length) descriptors riding scalar prefetch: the page index map
  dereferences the table (each grid step fetches a DISTINCT physical
  page, so Mosaic's DMA pipelining is preserved — unlike the
  re-fetch-last-block pattern ops/flash_attention.py measured 23x slow),
  and pages past a row's frontier skip their dots. Causal "full" masking
  is analytic in-kernel; non-"full" patterns and key-padding masks take
  the reference path. TPU-only by default (``DALLE_TPU_RAGGED_KERNEL``
  forces it either way; interpret mode runs it anywhere for the parity
  sweeps in tests/test_ragged_attention.py). Kernel-vs-reference is an
  allclose contract (online softmax reassociates the reduction); the
  BIT-parity contracts all live on the reference path.

Width-1 note: the fused block computes EVERY row at the padded width W,
so a 1-token prefill tail or a decode row is just a mostly-masked row of
a gemm-shaped block — the fused path needs no 1-token-tail merge
(``cache_block_attend`` additionally pads genuine width-1 blocks to
width 2, so even W == 1 descriptors stay bit-consistent with wider
blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def use_kernel(causal_full: bool, has_key_mask: bool) -> bool:
    """Kernel eligibility for this call: analytic causal-"full" masking
    only (other patterns keep their mask-row semantics on the reference
    path), no runtime key mask, and a TPU backend unless
    ``DALLE_TPU_RAGGED_KERNEL`` forces either way (the shared tri-state
    gate, ops/kv_policy.py:tpu_auto_env)."""
    from .kv_policy import tpu_auto_env

    return (
        causal_full
        and not has_key_mask
        and tpu_auto_env("DALLE_TPU_RAGGED_KERNEL")
    )


# ------------------------------------------------------------- reference


def reference_attend(q, k_pool, v_pool, table, allowed, stable=False,
                     k_scales=None, v_scales=None):
    """The jnp oracle: gather the paged pools into the logical cache view
    and run the ONE shared masked-block attention. q (b, n, h, d)
    pre-scaled (rotary already applied); pools (b, n_p, page, h*d);
    ``allowed`` broadcastable to (b, 1, n, W_cache). Bitwise identical to
    the split paths' attention core by construction — both are
    ``cache_block_attend`` on the same gathered view. Quantized pools
    (int8 content + parallel (b, n_p, page, h) scale pools; ``k_scales``
    / ``v_scales``) dequantize the gathered view through the ONE shared
    formula (``paged_kv.dequant``) before the attention core — the same
    gather + dequant the split decode path runs, so fused-vs-split
    bitwise parity survives quantization unchanged."""
    from . import paged_kv
    from .attention import cache_block_attend

    k_cache = paged_kv.gather(k_pool, table)  # (b, W, h*d)
    v_cache = paged_kv.gather(v_pool, table)
    if k_scales is not None:
        k_cache = paged_kv.dequant(
            k_cache, paged_kv.gather(k_scales, table), q.dtype
        )
        v_cache = paged_kv.dequant(
            v_cache, paged_kv.gather(v_scales, table), q.dtype
        )
    return cache_block_attend(q, k_cache, v_cache, allowed, stable)


# ---------------------------------------------------------------- kernel


def _ragged_kernel(
    scalar_ref, q_ref, k_ref, v_ref, *refs,
    heads, dim_head, page, n_pages, width, quant,
):
    """One (row, page) grid step: q_ref (1, W, h*d) is row b's whole
    padded block, k_ref/v_ref (1, page, h*d) one physical page of the
    FLATTENED (rows * n_pages, page, h*d) pool view — the table holds
    GLOBAL page ids (ops/paged_kv.py), so a grid step can stream a page
    that physically lives in another row's storage (or the prefix-cache
    arena) — (selected by the TABLE in the index map). Per-head dots with running
    (max, denom, acc) scratch; analytic causal masking from the row's
    ``start`` descriptor; pages past the row's frontier skip compute
    (their DMA still streams — affine-in-j index maps keep Mosaic's
    pipeline; the skipped page's bytes are the price of raggedness-as-
    data).

    ``quant``: int8 pages with parallel per-(token, head) scale pages
    (ks_ref/vs_ref, (1, page, h) f32, selected by the SAME table entry
    so a shared prefix-arena page brings its own scales). Dequantization
    is IN-KERNEL, fused with the page stream: the int8 block widens to
    f32 in registers and multiplies its scale column before the dots —
    the same int8->f32-widen * f32-scale formula as ``paged_kv.dequant``
    — so the kernel streams half the KV bytes per page (plus the small
    h/(h*d) scale stream) and never materializes a dequantized cache."""
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    b_i, j = pl.program_id(0), pl.program_id(1)
    start = scalar_ref[b_i, n_pages]
    # frontier: the highest position this block can attend is its own
    # last VALID query, start + length - 1 (causal); idle rows
    # (length == 0) still visit page 0 so every query row stays finite
    length = scalar_ref[b_i, n_pages + 1]
    last_pos = start + jnp.maximum(length, 1) - 1

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * page <= last_pos)
    def _():
        # (W, page) causal mask for this page: key position j*page + c
        # visible to query row i at position start + i
        qpos = jax.lax.broadcasted_iota(jnp.int32, (width, page), 0) + start
        kpos = jax.lax.broadcasted_iota(jnp.int32, (width, page), 1) + j * page
        visible = kpos <= qpos
        for h_ in range(heads):
            lo = h_ * dim_head
            qh = q_ref[0, :, lo:lo + dim_head]              # (W, d)
            kh = k_ref[0, :, lo:lo + dim_head]              # (page, d)
            vh = v_ref[0, :, lo:lo + dim_head]
            if quant:
                # in-register widen + scale: the shared dequant formula
                # (paged_kv.dequant) applied to one streamed page —
                # INCLUDING its final cast to the compute dtype, so the
                # kernel sees the same rounded K/V values the reference
                # path's gathered-view dequant produces (on a bf16
                # compute tier an uncast f32 product would diverge from
                # the split path in low bits; f32 tiers are unaffected)
                kh = (
                    kh.astype(jnp.float32) * ks_ref[0, :, h_:h_ + 1]
                ).astype(o_ref.dtype)
                vh = (
                    vh.astype(jnp.float32) * vs_ref[0, :, h_:h_ + 1]
                ).astype(o_ref.dtype)
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                               # (W, page)
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_scr[h_, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h_, :, 0:1] = (
                l_scr[h_, :, 0:1] * corr + jnp.sum(p, axis=-1, keepdims=True)
            )
            m_scr[h_, :, 0:1] = m_new
            acc_scr[h_] = acc_scr[h_] * corr + jax.lax.dot_general(
                p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(j == n_pages - 1)
    def _():
        for h_ in range(heads):
            l = l_scr[h_, :, 0:1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, h_ * dim_head:(h_ + 1) * dim_head] = (
                acc_scr[h_] / l_safe
            ).astype(o_ref.dtype)


def kernel_attend(q, k_pool, v_pool, table, start, length, interpret=False,
                  k_scales=None, v_scales=None):
    """Pallas ragged paged attention, causal-"full" masking. q (b, n, h, d)
    pre-scaled; returns (b, n, h, d). The pools are streamed through their
    FLATTENED (rows * n_pages, page, h*d) global view — the id space the
    table indexes (ops/paged_kv.py) — so pools carrying prefix-cache arena
    rows beyond the query batch work unchanged. ``k_scales``/``v_scales``
    (both or neither): int8 pools with parallel (b, n_p, page, h) f32
    scale pools — two more streamed operands riding the SAME table
    dereference, dequantized in-kernel (see the kernel docstring). The
    scale blocks' h-lane minor dim under-fills the 128-lane tile for
    small head counts (VMEM padding, not HBM traffic); a bitcast-packed
    scales-in-page layout is the known upgrade if a TPU profile shows
    the scale stream mattering next to the halved KV bytes."""
    from . import paged_kv

    b, n, h, d = q.shape
    _, n_p, page, hd = k_pool.shape
    l_pages = table.shape[1]
    assert hd == h * d, (k_pool.shape, (h, d))
    quant = k_scales is not None
    assert (k_scales is None) == (v_scales is None)
    qf = q.reshape(b, n, hd)
    k_flat = paged_kv.flat_view(k_pool)
    v_flat = paged_kv.flat_view(v_pool)
    # descriptor payload: per-row [table row | start | length], int32 —
    # the page index map dereferences s[b, j] (a GLOBAL page id into the
    # flat view); the kernel body reads the (start, length) tail
    scalar = jnp.concatenate(
        (table.astype(jnp.int32), start[:, None].astype(jnp.int32),
         length[:, None].astype(jnp.int32)), axis=1,
    )

    kernel = functools.partial(
        _ragged_kernel, heads=h, dim_head=d, page=page, n_pages=l_pages,
        width=n, quant=quant,
    )
    # the page-table indirection: grid step (bi, j) streams PHYSICAL
    # page table[bi, j] of the flat view — possibly another row's
    # storage or a shared prefix-cache arena page
    # (serving/prefix_cache.py); each grid step still fetches a
    # distinct page, preserving DMA pipelining
    page_spec = pl.BlockSpec((1, page, hd), lambda bi, j, s: (s[bi, j], 0, 0))
    in_specs = [
        pl.BlockSpec((1, n, hd), lambda bi, j, s: (bi, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [scalar, qf, k_flat, v_flat]
    kv_bytes = b * l_pages * page * hd * 2 * k_pool.dtype.itemsize
    if quant:
        # scale pages ride the same indirection as their content pages
        scale_spec = pl.BlockSpec(
            (1, page, h), lambda bi, j, s: (s[bi, j], 0, 0)
        )
        in_specs += [scale_spec, scale_spec]
        operands += [
            paged_kv.flat_view(k_scales), paged_kv.flat_view(v_scales),
        ]
        kv_bytes += (
            b * l_pages * page * h * 2 * k_scales.dtype.itemsize
        )
    out = pl.pallas_call(
        kernel,
        name="ragged_paged_attend",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, l_pages),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, n, hd), lambda bi, j, s: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, n, LANES), jnp.float32),
                pltpu.VMEM((h, n, LANES), jnp.float32),
                pltpu.VMEM((h, n, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n, hd), q.dtype),
        # rows are independent; the page dimension accumulates in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * n * l_pages * page * d * 2,
            transcendentals=b * h * n * l_pages * page,
            bytes_accessed=kv_bytes + 2 * b * n * hd * q.dtype.itemsize,
        ),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, n, h, d)


