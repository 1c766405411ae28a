"""Fused single-token decode attention (Pallas TPU kernel) — implemented,
measured, and OFF by default.

The hypothesis: batch-1 decode is latency-bound on the ~12 small XLA ops
between the qkv projection and the output projection, so collapsing them
into one kernel should save their per-op overhead. The measurement
(v5e-1, flagship config): the kernel costs ~29 us/layer in isolation while
the XLA op chain it replaces runs in ~10 us/layer — XLA's fusion pipeline
already collapses the chain well, and the kernel's skinny per-head MXU
matvecs serialize across the 8 head-group programs. End to end the kernel
REGRESSED generation 0.999 -> 1.36 ms/token, so the dispatch in
ops/attention.py is gated on ``FUSED_DECODE_ENABLED`` (env
``DALLE_TPU_FUSED_DECODE=1``), default off. It stays in the tree as a
correct, tested alternative (and a recorded negative result: the same
conclusion as the int8 KV cache — see ops/attention.py — decode here is
bound by weight streaming, not by the attention op chain).

The kernel fuses, per layer:

    rotary(q, k, v)  ->  scores = q K_cache^T (+ the new token's own k)  ->
    causal + key-padding mask  ->  softmax  ->  out = attn [V_cache; v]

- the packed (b, 1, 3 h d) qkv row streams straight from the projection
  (the same three-views-of-one-operand trick as the fused training kernel);
- the K/V caches are READ-ONLY inputs: the current position's contribution
  enters the softmax directly from the just-rotated k/v (its cache row is
  stale), so the kernel never writes the caches — Mosaic cannot store to a
  dynamic sublane row, and an aliased full-block write-back would cost a
  full cache sweep of HBM writes per step. The rotated k/v rows are emitted
  as side outputs and written into the caches by two one-row
  dynamic_update_slices in XLA (in-place on the donated decode state);
- rotary cos/sin rows for position ``idx`` arrive via scalar-prefetch
  index maps (the position picks the block, no in-kernel gather); rotation
  applies to q, k AND v — the DALL-E quirk (reference attention.py:75-78);
- the causal mask is an iota-vs-idx compare (STRICT: the stale cache row at
  idx is excluded; the fresh token adds itself explicitly); the optional
  runtime key-padding mask streams as a pre-transposed (b, L, 1) operand;
- grid (b, h / hpb): each program handles one head group (hpb = 128 / d
  heads) so the lane dimension stays full.

Semantics match ops/attention.py:_decode_attend for attn_type="full",
causal, single-token steps (pinned by tests/test_decode_kernel.py); other
pattern types and multi-token prefill keep the unfused path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# opt-in dispatch (see module docstring): flip via env or monkeypatch
import os

FUSED_DECODE_ENABLED = os.environ.get("DALLE_TPU_FUSED_DECODE", "0") == "1"


def fused_decode_supported(heads: int, dim_head: int) -> bool:
    """One source of truth for the kernel's head-group constraint (the
    dispatch guard in ops/attention.py and the kernel assert both use it):
    lanes must tile into whole heads and heads into whole groups."""
    return 128 % dim_head == 0 and heads % max(1, 128 // dim_head) == 0


def _kernel(
    idx_ref,  # (1,) scalar prefetch: current position
    q_ref, k_new_ref, v_new_ref,  # (1, 1, hpb*d) views of the packed qkv row
    cos_ref, sin_ref,             # (1, 1, hpb*d) rotary rows for position idx
    p_ref,                        # (d, d) rotate-half matrix
    kmask_ref,                    # (1, L, 1) int32 key mask or None
    kcache_ref, vcache_ref,       # (1, L, hpb*d) read-only caches
    o_ref, k_out_ref, v_out_ref,  # (1, 1, hpb*d) outputs
    *, d: int, hpb: int, L: int, scale: float, use_rotary: bool,
):
    idx = idx_ref[0]
    q = q_ref[0].astype(jnp.float32)        # (1, hpb*d)
    k = k_new_ref[0].astype(jnp.float32)
    v = v_new_ref[0].astype(jnp.float32)

    if use_rotary:
        cos = cos_ref[0].astype(jnp.float32)  # (1, hpb*d)
        sin = sin_ref[0].astype(jnp.float32)
        P = p_ref[:].astype(jnp.float32)      # (d, d)

        def rot(t):
            halves = []
            for hi in range(hpb):
                th = t[:, hi * d:(hi + 1) * d]
                rotated = jax.lax.dot_general(
                    th, P, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                halves.append(
                    th * cos[:, hi * d:(hi + 1) * d]
                    + rotated * sin[:, hi * d:(hi + 1) * d]
                )
            return jnp.concatenate(halves, axis=-1)

        q, k, v = rot(q), rot(k), rot(v)

    # the new row reaches the softmax in the caches' dtype — exactly the
    # values the XLA-side row write will store, so fused steps are
    # bit-consistent with later reads of the cache
    k_store = k.astype(k_out_ref.dtype)
    v_store = v.astype(v_out_ref.dtype)
    k_out_ref[0] = k_store
    v_out_ref[0] = v_store
    kq = k_store.astype(jnp.float32)
    vq = v_store.astype(jnp.float32)

    K = kcache_ref[0].astype(jnp.float32)   # (L, hpb*d)
    V = vcache_ref[0].astype(jnp.float32)
    # STRICT past-only mask: the cache row at idx is stale; the fresh
    # token's contribution is added explicitly below
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    live_rows = rows < idx
    new_live = jnp.float32(1.0)
    if kmask_ref is not None:
        km = kmask_ref[0] > 0
        live_rows = jnp.logical_and(live_rows, km)
        # the key-padding mask also applies to the current position's own
        # key (matching the unfused path's allowed &= mask)
        new_live = jnp.max(
            jnp.where(jnp.logical_and(rows == idx, km), 1.0, 0.0)
        )

    # both sweeps run as MXU dots (cross-lane VPU reductions are an order
    # of magnitude slower than a skinny matmul here)
    qs = q * scale
    outs = []
    for hi in range(hpb):
        sl = slice(hi * d, (hi + 1) * d)
        s = jax.lax.dot_general(  # (L, d) x (1, d) -> (L, 1)
            K[:, sl], qs[:, sl], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = jnp.where(live_rows, s, NEG_INF)
        s_new = jnp.sum(kq[:, sl] * qs[:, sl])                     # scalar
        # a key-padding-masked current token must not poison the softmax
        # max: its raw score could exceed every live score by enough to
        # underflow them all (making the output spuriously zero)
        s_new = jnp.where(new_live > 0, s_new, NEG_INF)
        m = jnp.maximum(jnp.max(s), s_new)
        p = jnp.where(live_rows, jnp.exp(s - m), 0.0)              # (L, 1)
        p_new = jnp.exp(s_new - m) * new_live
        l = jnp.sum(p) + p_new
        # every-key-masked rows emit 0 (the flash-kernel convention; the
        # dense path's uniform-average is unreachable in decode — <bos> is
        # always a live key)
        l = jnp.where(l == 0.0, 1.0, l)
        acc = jax.lax.dot_general(  # (1, L) x (L, d) -> (1, d)
            p.reshape(1, L), V[:, sl], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        outs.append((acc + p_new * vq[:, sl]) / l)
    o_ref[0] = jnp.concatenate(outs, axis=-1).astype(o_ref.dtype)


def _kernel_nomask(idx_ref, q_ref, k_new_ref, v_new_ref, cos_ref, sin_ref,
                   p_ref, kcache_ref, vcache_ref,
                   o_ref, k_out_ref, v_out_ref, **kw):
    _kernel(idx_ref, q_ref, k_new_ref, v_new_ref, cos_ref, sin_ref, p_ref,
            None, kcache_ref, vcache_ref, o_ref, k_out_ref, v_out_ref, **kw)


@functools.partial(
    jax.jit,
    static_argnames=("heads", "dim_head", "use_rotary", "interpret"),
)
def fused_decode_attention(
    qkv: jnp.ndarray,         # (b, 1, 3*h*d) packed projection output
    k_cache: jnp.ndarray,     # (b, L, h*d) — read-only here
    v_cache: jnp.ndarray,     # (b, L, h*d)
    idx: jnp.ndarray,         # scalar int32
    cos: jnp.ndarray,         # (T, d) rotary cos table (ignored w/o rotary)
    sin: jnp.ndarray,
    rot_p: jnp.ndarray,       # (d, d) rotate-half matrix
    key_mask: Optional[jnp.ndarray],  # (b, L, 1) int32 or None
    *, heads: int, dim_head: int, use_rotary: bool, interpret: bool = False,
):
    """-> (out, k_row, v_row), each (b, 1, h*d); the caller writes
    k_row/v_row into the caches at ``idx`` (one-row updates in XLA)."""
    b, L, hd = k_cache.shape
    d, h = dim_head, heads
    assert hd == h * d, (k_cache.shape, heads, dim_head)
    assert fused_decode_supported(h, d), (h, d)
    hpb = max(1, 128 // d)
    groups = h // hpb

    idx_arr = jnp.asarray(idx, jnp.int32).reshape(1)

    # index maps under PrefetchScalarGridSpec receive the scalar-prefetch
    # ref LAST: (grid..., scalars)
    qkv_spec = lambda off: pl.BlockSpec(
        (1, 1, hpb * d), lambda b_, g, s: (b_, 0, off * groups + g)
    )
    # rotary rows for position idx: per-head-dim table rows are identical
    # across heads, tile to the group width once at trace time (static).
    # The (T, 1, hpb*d) layout keeps the block's trailing dims equal to the
    # array's (Mosaic requires (8, 128)-divisible or full-dimension blocks);
    # the table may be shorter than the cache (the final position never
    # decodes — it predicts nothing — so its row is never fetched)
    T = cos.shape[0]
    cos_g = jnp.tile(cos, (1, hpb)).reshape(T, 1, hpb * d)
    sin_g = jnp.tile(sin, (1, hpb)).reshape(T, 1, hpb * d)
    row_spec = pl.BlockSpec((1, 1, hpb * d), lambda b_, g, s: (s[0], 0, 0))

    in_specs = [
        qkv_spec(0), qkv_spec(1), qkv_spec(2),
        row_spec, row_spec,
        pl.BlockSpec((d, d), lambda b_, g, s: (0, 0)),
    ]
    operands = [qkv, qkv, qkv, cos_g, sin_g, rot_p]
    if key_mask is not None:
        in_specs.append(pl.BlockSpec((1, L, 1), lambda b_, g, s: (b_, 0, 0)))
        operands.append(key_mask)
    cache_spec = pl.BlockSpec((1, L, hpb * d), lambda b_, g, s: (b_, 0, g))
    in_specs += [cache_spec, cache_spec]
    operands += [k_cache, v_cache]

    kernel = functools.partial(
        _kernel if key_mask is not None else _kernel_nomask,
        d=d, hpb=hpb, L=L, scale=d**-0.5, use_rotary=use_rotary,
    )

    row_out = pl.BlockSpec((1, 1, hpb * d), lambda b_, g, s: (b_, 0, g))
    out, k_row, v_row = pl.pallas_call(
        kernel,
        name="decode_attend",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, groups),
            in_specs=in_specs,
            out_specs=[row_out, row_out, row_out],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, h * d), qkv.dtype),
            jax.ShapeDtypeStruct((b, 1, h * d), k_cache.dtype),
            jax.ShapeDtypeStruct((b, 1, h * d), v_cache.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(idx_arr, *operands)
    return out, k_row, v_row
