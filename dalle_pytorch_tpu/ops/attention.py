"""Attention family, TPU-native.

One module, ``PatternAttention``, implements every attention pattern the
reference spreads over four torch classes (attention.py:39-384): dense causal
("full"), axial row/column ("axial_row"/"axial_col"), convolution-like local
("conv_like"), and DeepSpeed-style block-sparse ("sparse"). Design:

- every pattern is *defined* by a static (L, L) may-attend mask built at model
  construction (ops/masks.py) — shape-static, jit-friendly, no dynamic padding;
- "full" and "sparse" run as one dense masked attention (MXU-sized einsums;
  a Pallas block-sparse kernel can slot under "sparse" without changing
  semantics);
- "axial_row"/"axial_col"/"conv_like" additionally have grouped
  FLOP-efficient paths (row/col batching, conv patches) that the tests verify
  against the dense-masked oracle;
- a KV-cached decode mode serves autoregressive sampling with O(L) work per
  token: the reference re-runs the full prefix per sampled token
  (dalle_pytorch.py:481-486); here each layer attends from the new token to
  its cache through the pattern's mask row.

Quirk preserved for parity: rotary embeddings are applied to q, k *and* v,
exactly as the reference does (attention.py:32-35,63-64).
"""

from __future__ import annotations

from typing import Any, Optional

import functools

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from . import kv_policy
from . import masks as masks_lib
from .flash_attention import (
    StaticMask,
    StaticTable,
    flash_attention,
    fused_qkv_attention,
    fused_qkv_supported,
    window_supported,
    window_tiles,
)
from .layers import RMSNorm, stable_softmax
from . import rotary
from .rotary import angles, apply_rotary_emb, lang_freqs


_FLASH_MASK_CACHE: dict = {}


def _cached_flash_mask(module: "PatternAttention", n: int) -> StaticMask:
    """One StaticMask per (pattern config, n), built exactly once. Keyed on
    the fields ``pattern_mask()`` reads — NOT the module itself: a bound
    flax module (inside apply, holding variables) is unhashable, so an
    lru_cache over the module works at trace time only for unbound calls
    and raises mid-apply."""
    key = (
        module.attn_type, module.seq_len, module.causal,
        module.image_fmap_size, module.kernel_size, module.dilation,
        module.block_size, module.num_random_blocks, module.layout_seed, n,
    )
    cached = _FLASH_MASK_CACHE.get(key)
    if cached is None:
        cached = _FLASH_MASK_CACHE[key] = StaticMask(
            module.pattern_mask()[:n, :n]
        )
    return cached


_BLOCK_LAYOUT_CACHE: dict = {}
_SP_PLAN_CACHE: dict = {}


def _pattern_key(module: "PatternAttention", n: int) -> tuple:
    """The hashable pattern-config key (the `_cached_flash_mask` rule:
    key on the fields ``pattern_mask()`` reads, never the bound module)."""
    return (
        module.attn_type, module.seq_len, module.causal,
        module.image_fmap_size, module.kernel_size, module.dilation,
        module.block_size, module.num_random_blocks, module.layout_seed, n,
    )


def _cached_block_layout(
    module: "PatternAttention", n: int, block: int
) -> "bs_lib.BlockLayout":
    """One compiled BlockLayout per (pattern config, n, block), built once:
    BlockLayout hashes by identity, so jit/custom_vjp retrace only when the
    layout genuinely changes."""
    from . import block_sparse_attention as bs_lib

    key = _pattern_key(module, n) + (block,)
    cached = _BLOCK_LAYOUT_CACHE.get(key)
    if cached is None:
        cached = _BLOCK_LAYOUT_CACHE[key] = bs_lib.compile_block_layout(
            module.pattern_mask()[:n, :n], block, block
        )
    return cached


def _sparse_block(n: int) -> int:
    """Kernel-eligible block edge for the pair-grid sparse kernel: lanes
    must be a multiple of 128 and per-step overhead dominates below it
    (the flash kernel's measured floor), so eligibility is simply n
    divisible by 128 with at least two blocks — the production seqs
    (1280/2048/4096) all qualify; everything else keeps the dense paths."""
    return 128 if n % 128 == 0 and n >= 256 else 0


def _sp_plan_block(n: int, sp: int) -> int:
    """Assignment granularity for the dual-balanced sp plan: the kernel
    edge when eligible, else the largest power-of-two divisor of n that
    still gives every chip a shot at >= 1 block (CPU test shapes)."""
    for b in (128, 64, 32, 16, 8, 4, 2, 1):
        if n % b == 0 and n // b >= sp:
            return b
    return 1


def _cached_sp_plan(module: "PatternAttention", n: int, sp: int):
    """One dual-balanced SpPlan per (pattern config, n, sp)."""
    from . import block_sparse_attention as bs_lib

    block = _sp_plan_block(n, sp)
    key = _pattern_key(module, n) + (sp, block)
    cached = _SP_PLAN_CACHE.get(key)
    if cached is None:
        cached = _SP_PLAN_CACHE[key] = bs_lib.compile_sp_plan(
            _cached_block_layout(module, n, block), sp
        )
    return cached


@functools.lru_cache(maxsize=None)
def _cached_rot_slice(table: StaticTable, n: int) -> StaticTable:
    """Stable-identity [:n] slice of a static rotary table (the fused
    kernel hashes tables by id)."""
    return StaticTable(table.table[:n])


def _flash_block(n: int) -> int:
    """Largest usable flash block: per-grid-iteration overhead dominates the
    kernel at small blocks (measured 10x slower at 128 than 640 for seq
    1280), so prefer the biggest multiple-of-128 divisor of n. 128 also
    bounds the lse block's lane dimension (must divide by 128)."""
    for b in (1280, 1024, 640, 512, 384, 256, 128):
        if n % b == 0:
            return b
    return 0

Dtype = Any

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def _per_device(kernel, args):
    """Run ``kernel(*args)`` — a Pallas attention call — once per device of
    the ambient mesh. XLA cannot partition a Mosaic kernel: left to
    GSPMD/Shardy inside a multi-device jit, lowering stops with
    "Mosaic kernels cannot be automatically partitioned. Please wrap the
    call in a shard_map." (jax 0.9.0, first met on a four-chip v5e — the
    CPU tier interprets the kernels, which partition like any jnp code, so
    it never saw this). Attention is independent over batch and heads, so
    the call is wrapped in a shard_map that splits exactly those: dim 0 of
    every argument and of the result over the data axes (dp, fsdp) and —
    for the (b, h, n, d) layouts — dim 1 over tp. The first argument sets
    the layout: packed (b, n, 3*h*d) qkv, or (b, h, n, d) q.
    The shard_map is manual over every mesh axis not already manual (inside
    the pipeline's pp region only the remaining ones), which is what Mosaic
    requires. A batch or head count the axes do not divide stays
    replicated on that dim. No mesh, or one device: a plain call."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.context import active_mesh, batch_axes

    mesh = active_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(*args)
    context = jax.sharding.get_abstract_mesh()
    manual = frozenset(context.manual_axes)
    free = frozenset(mesh.axis_names) - manual
    per_head = args[0].ndim == 4
    data = tuple(a for a in (batch_axes(mesh) or ()) if a in free)
    if args[0].shape[0] % int(np.prod([mesh.shape[a] for a in data] or [1])):
        data = ()
    head = None
    if per_head and "tp" in free and args[0].shape[1] % mesh.shape["tp"] == 0:
        head = "tp"

    def spec(x):
        dims = [data or None] + ([head] if x.ndim == 4 else [])
        return P(*dims, *([None] * (x.ndim - len(dims))))

    # the result has the first argument's layout
    return jax.shard_map(
        kernel, mesh=context if manual else mesh,
        in_specs=tuple(spec(a) for a in args), out_specs=spec(args[0]),
        axis_names=free, check_vma=False,
    )(*args)


def lane_pack_enabled() -> bool:
    """Whether single-token decode sweeps may use the lane-packed
    formulation (``PatternAttention._cache_attend``). "auto" (default):
    TPU only — it was measured there (0.823 -> 0.813 ms/token, v5e int8)
    and its regrouped contraction is NOT bitwise equal to the plain gemm
    at every head count (h=16, d=64 measured ~5e-7 apart on CPU), while
    the CPU tier is where the fused-vs-split serving BIT-parity gates
    run (tests/test_ragged_attention.py, tools/serve_smoke.py): gating
    the pack off-TPU keeps every CPU decode path on the one shared gemm.
    ``DALLE_TPU_LANE_PACK=0|1`` forces either way (tests use 1 to
    exercise the packed math on CPU)."""
    return kv_policy.tpu_auto_env("DALLE_TPU_LANE_PACK")


def _softmax(scores: jnp.ndarray, stable: bool, axis: int = -1) -> jnp.ndarray:
    scores = scores.astype(jnp.float32)
    return (
        stable_softmax(scores, axis=axis) if stable
        else jax.nn.softmax(scores, axis=axis)
    )


def dense_attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    stable: bool = False,
) -> jnp.ndarray:
    """q, k, v: (..., n, d) with q pre-scaled. mask broadcastable to
    (..., n_q, n_k), True = attend. Softmax accumulates in f32."""
    scores = jnp.einsum("...id,...jd->...ij", q, k, preferred_element_type=jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    attn = _softmax(scores, stable)
    return jnp.einsum("...ij,...jd->...id", attn.astype(v.dtype), v)


def cache_block_attend(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    allowed: jnp.ndarray,
    stable: bool = False,
) -> jnp.ndarray:
    """Masked attention of an n-token query block against a W-row cache
    view: q (b, n, h, d) pre-scaled, k_cache/v_cache any
    (b, W, h*d)-reshapeable rank, ``allowed`` broadcastable to
    (b, 1, n, W). Scores accumulate in f32; masked lanes contribute
    exp(NEG_INF) = 0.

    This is THE multi-token decode-block building block: monolithic
    prefill (``DALLE.prefill_step``), CHUNKED prefill
    (``DALLE.prefill_chunk`` — each chunk attends the already-written
    paged-KV prefix, assembled by ``paged_kv.gather`` through the page
    table, plus its own in-chunk causal rows of the pattern mask), the
    fused ragged iteration (``ops/ragged_attention.py``'s reference
    path), and the n > 1 branch of every cache format all route here
    through ``PatternAttention._cache_attend``. One implementation means
    chunked and monolithic prefill share every einsum, which is what
    makes chunk-size-invariant BIT-parity achievable at all.

    Width-1 blocks are deliberately computed as width-2 gemms (q row
    duplicated, result sliced back): XLA lowers a genuine n == 1 block to
    a matvec whose accumulation differs from the n >= 2 gemm by ~1 ulp
    (CPU, measured 2026-08 and re-confirmed for this fix). The pad
    resolves that caveat IN THE ATTENTION CORE: per-row results here are
    bitwise invariant across every block width n >= 1 AND across batch
    widths (both verified on CPU, pinned by
    tests/test_ragged_attention.py), so the fused ragged path needs no
    1-token-tail special case — its rows are padded to the iteration
    width anyway. NOTE the split engine still merges 1-token final
    chunks (engine._next_chunk): a batch-1 width-1 block's
    PROJECTION/FFN matmuls run as M=1 matvecs with the same ~1-ulp
    accumulation drift, which this pad cannot reach — the residual
    caveat is pinned precisely in tests/test_ragged_attention.py. Cost
    of the pad: one duplicated query row on a path whose work is
    dominated by the W-row cache sweep."""
    b, n, h, d = q.shape
    W = k_cache.shape[1]
    if n == 1:
        out = cache_block_attend(
            jnp.concatenate((q, q), axis=1), k_cache, v_cache, allowed,
            stable,
        )
        return out[:, :1]
    scores = jnp.einsum(
        "bnhd,blhd->bhnl", q, k_cache.reshape(b, W, h, d),
        preferred_element_type=jnp.float32,
    )
    scores = jnp.where(allowed, scores, NEG_INF)
    attn = _softmax(scores, stable)
    return jnp.einsum(
        "bhnl,blhd->bnhd", attn.astype(v_cache.dtype),
        v_cache.reshape(b, W, h, d),
    )


class PatternAttention(nn.Module):
    """Multi-head attention with a static sparsity pattern.

    ``seq_len`` is the full internal sequence length L the pattern is defined
    over (text_len-with-bos + image_fmap_size**2 for DALL-E layers; the plain
    sequence length for CLIP's non-causal encoders). Callers may pass any
    static n <= L of leading positions.
    """

    dim: int
    seq_len: int
    attn_type: str = "full"
    causal: bool = True
    heads: int = 8
    dim_head: int = 64
    dropout: float = 0.0
    stable: bool = False
    image_fmap_size: Optional[int] = None
    kernel_size: int = 5
    dilation: int = 1
    block_size: int = 16
    num_random_blocks: Optional[int] = None
    layout_seed: int = 0
    use_flash: bool = True
    sp_axis: Optional[str] = None
    quant: bool = False
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @property
    def text_len(self) -> int:
        assert self.image_fmap_size is not None
        return self.seq_len - self.image_fmap_size**2

    def pattern_mask(self) -> np.ndarray:
        """The static (L, L) may-attend matrix defining this layer."""
        if self.attn_type == "full":
            if not self.causal:
                return np.ones((self.seq_len, self.seq_len), dtype=bool)
            return masks_lib.causal_mask(self.seq_len)
        if self.attn_type in ("axial_row", "axial_col"):
            return masks_lib.axial_mask(
                self.text_len, self.image_fmap_size, axis=0 if self.attn_type == "axial_row" else 1
            )
        if self.attn_type == "conv_like":
            return masks_lib.conv_mask(
                self.text_len, self.image_fmap_size, self.kernel_size, self.dilation
            )
        if self.attn_type == "sparse":
            return masks_lib.block_sparse_mask(
                self.seq_len,
                block_size=self.block_size,
                text_seq_len=self.text_len - 1,
                num_random_blocks=self.num_random_blocks,
                causal=self.causal,
                seed=self.layout_seed,
            )
        raise ValueError(f'attention type "{self.attn_type}" is not valid')

    # ---------------------------------------------------------------- forward

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        rotary_pos_emb: Optional[jnp.ndarray] = None,
        deterministic: bool = True,
        decode: bool = False,
        force_dense: bool = False,
        block_len: Optional[jnp.ndarray] = None,
        block_start: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        b, n, _ = x.shape
        h, d = self.heads, self.dim_head
        inner = h * d

        from .layers import serving_dense

        dense = lambda features, use_bias, name: serving_dense(
            self.quant, features, use_bias=use_bias, name=name,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )
        qkv = dense(inner * 3, False, "to_qkv")(x)

        # the rotary table may arrive as a StaticTable (the Transformer's
        # single source of truth): the fused kernel consumes it statically,
        # every other path materializes the SAME table here — the two can
        # never diverge
        rot_static = (
            rotary_pos_emb if isinstance(rotary_pos_emb, StaticTable) else None
        )
        if rot_static is not None:
            rotary_pos_emb = jnp.asarray(rot_static.table)

        if decode:
            from . import decode_attention as _dk

            if (
                _dk.FUSED_DECODE_ENABLED
                and n == 1
                and self.use_flash
                and self.attn_type == "full"
                and self.causal
                and _dk.fused_decode_supported(h, d)
                and self._cache_format(b) != "paged"
                and not self._has_windowed_cache()
            ):
                # OPT-IN fused decode kernel (ops/decode_attention.py):
                # measured SLOWER than the XLA op chain on v5e (see that
                # module's docstring), so off unless DALLE_TPU_FUSED_DECODE=1
                out = self._decode_attend_fused(qkv, mask, rotary_pos_emb)
            else:
                # multi-token prefill blocks and non-"full" patterns: the
                # unfused path, (b, n, h, d) end to end against the same
                # n-major caches the kernel aliases. ``block_len`` (b,)
                # marks a RAGGED block (the fused serving iteration): row
                # b's valid tokens are columns [0, block_len[b]) — K/V
                # writes are masked to them and the cache index advances
                # per row (ops/ragged_attention.py).
                q, k, v = (
                    t.reshape(b, n, h, d) for t in jnp.split(qkv, 3, axis=-1)
                )
                out = self._decode_attend(
                    q, k, v, mask, rotary_pos_emb, block_len=block_len,
                    block_start=block_start,
                )
                out = out.reshape(b, n, inner)
        else:
            from ..parallel.context import axis_extent, sp_extent

            use_sp = (
                not force_dense
                and not self.is_initializing()
                and sp_extent(self.sp_axis) > 1
            )
            # ONE rule picks the training kernel of a static pattern, from
            # what the call itself shows (n, heads, mesh, rotary form):
            #   1. the packed single-block flash kernel wherever it is
            #      eligible: q/k/v head slices stream straight out of the
            #      projection layout, rotary applied in-kernel, the non-full
            #      patterns streaming their static int8 mask as an operand —
            #      no split/reshape/transpose/rotary sweeps through HBM;
            #   2. the pair grid (ops/block_sparse_attention.py) only where
            #      (1) cannot run and the COMPILED layout really skips block
            #      pairs (ENGAGE_FRAC; policy-gated, auto = TPU);
            #   3. the blocked flash kernel, then the jnp forms.
            # The chip reading behind the order (v5e, seq 1280; the head of
            # ops/block_sparse_attention.py has it whole): a pair-grid
            # layer 10.5 ms, the packed kernel's whole square 2.2 ms —
            # the pair grid pays per grid step, not per FLOP.
            # (under tensor parallelism the packed (b, n, 3*h*d) layout is
            # split over tp in contiguous thirds, not by head: those meshes
            # take the per-head routes, heads over tp)
            use_packed = (
                not use_sp
                and not force_dense
                and self.use_flash
                and _flash_block(n) == n
                and fused_qkv_supported(n, h, d)
                and (rotary_pos_emb is None or rot_static is not None)
                and axis_extent("tp") == 1
            )
            use_block_sparse = False
            if (
                not use_packed
                and not use_sp
                and not force_dense
                and self.attn_type != "full"
                and _sparse_block(n) > 0
            ):
                from .block_sparse_attention import (
                    ENGAGE_FRAC,
                    sparse_kernel_enabled,
                )

                if sparse_kernel_enabled():
                    # a pattern whose live stride is finer than the
                    # 128-block edge (axial_col at fmap <= 128, the 16-block
                    # DeepSpeed-style random layout) visits every causal
                    # pair — the pair grid would pay kernel overhead for
                    # zero skipped FLOPs, so it declines and the
                    # dense/flash paths keep those patterns
                    layout = _cached_block_layout(self, n, _sparse_block(n))
                    use_block_sparse = (
                        layout.visited_block_frac <= ENGAGE_FRAC
                    )
            if use_packed:
                pattern = (
                    _cached_flash_mask(self, n)
                    if self.attn_type != "full" else None
                )
                rot = (
                    _cached_rot_slice(rot_static, n)
                    if rot_static is not None else None
                )
                interpret = kv_policy.pallas_interpret()
                kv_policy.record_route(
                    f"forward/{self.attn_type}", "fused_qkv_flash", interpret
                )
                causal, scale = self.causal, d**-0.5
                args = (qkv,) if mask is None else (qkv, mask[:, :n])
                out = _per_device(
                    lambda x, km=None: fused_qkv_attention(
                        x, km, h, d, rot, causal, pattern, scale, interpret
                    ),
                    args,
                )
                out = dense(self.dim, True, "to_out")(out)
                return nn.Dropout(self.dropout)(out, deterministic=deterministic)

            q, k, v = (
                t.reshape(b, n, h, d).transpose(0, 2, 1, 3)
                for t in jnp.split(qkv, 3, axis=-1)
            )
            if rotary_pos_emb is not None:
                table = rotary_pos_emb[:n][None, None]  # (1, 1, n, rot)
                q, k, v = (apply_rotary_emb(table, t) for t in (q, k, v))

            if use_sp:
                kv_policy.record_route(
                    f"forward/{self.attn_type}", "sequence_parallel"
                )
                out = self._sp_attend(q, k, v, mask, n)
            elif use_block_sparse:
                out = self._block_sparse_attend(q, k, v, n, mask)
            elif (
                self.use_flash
                and not force_dense
                and _flash_block(n) > 0
            ):
                out = self._flash_attend(q, k, v, n, mask)
            else:
                kv_policy.record_route(
                    f"forward/{self.attn_type}", "jnp_pattern_attend"
                )
                out = self._pattern_attend(
                    q * (d**-0.5), k, v, mask, force_dense=force_dense
                )

            out = out.transpose(0, 2, 1, 3).reshape(b, -1, inner)
        out = dense(self.dim, True, "to_out")(out)
        return nn.Dropout(self.dropout)(out, deterministic=deterministic)

    # ------------------------------------------------------------ flash path

    def _flash_attend(self, q, k, v, n: int, mask=None):
        """Fused Pallas kernel for any static pattern
        (ops/flash_attention.py): O(n·d) memory, per-block skip of masked-out
        regions. A runtime (b, n) key-padding mask streams through the kernel
        as a fourth operand — no dense (n, n) fallback. The non-causal full
        pattern is analytic (all blocks dense), so it carries no (n, n)
        pattern operand either. Interpret mode off-TPU
        (kv_policy.pallas_interpret) so tests run anywhere."""
        block = _flash_block(n)
        pattern = None
        if self.attn_type != "full":
            pattern = _cached_flash_mask(self, n)
        interpret = kv_policy.pallas_interpret()
        kv_policy.record_route(
            f"forward/{self.attn_type}", "blocked_flash", interpret
        )
        causal, scale = self.causal, self.dim_head**-0.5
        args = (q, k, v) if mask is None else (q, k, v, mask[:, :n])
        return _per_device(
            lambda q, k, v, km=None: flash_attention(
                q, k, v, km, causal, pattern, scale, block, block, interpret
            ),
            args,
        )

    # ----------------------------------------------------- block-sparse path

    def _block_sparse_attend(self, q, k, v, n: int, mask=None):
        """Pair-grid block-sparse kernel (ops/block_sparse_attention.py):
        the compiled BlockLayout's live pairs ARE the grid, so masked
        blocks cost neither DMA nor FLOPs. Chosen only at shapes the packed
        single-block flash kernel cannot run (``__call__``'s training gate;
        the chip reading is at the head of that module). Interpret mode
        off-TPU, where the CPU parity tier pins it allclose against the
        dense-mask reference per layout (tests/test_block_sparse.py)."""
        from .block_sparse_attention import block_sparse_attention

        layout = _cached_block_layout(self, n, _sparse_block(n))
        interpret = kv_policy.pallas_interpret()
        kv_policy.record_route(
            f"forward/{self.attn_type}", "block_sparse_pair_grid", interpret
        )
        scale = self.dim_head**-0.5
        args = (q, k, v) if mask is None else (q, k, v, mask[:, :n])
        return _per_device(
            lambda q, k, v, km=None: block_sparse_attention(
                q, k, v, layout, key_mask=km, sm_scale=scale,
                interpret=interpret,
            ),
            args,
        )

    # -------------------------------------------------- sequence parallelism

    def _sp_attend(self, q, k, v, mask, n: int):
        """Sequence-parallel attention over the ``sp_axis`` mesh axis:
        ring attention for the dense-causal pattern
        (ops/ring_attention.py), the DUAL-BALANCED block plan for the
        sparse patterns (ops/block_sparse_attention.py — q-blocks dealt to
        chips so both block and visited-pair counts are even; an axial
        pattern's heavy text rows no longer serialize the slowest chip),
        and Ulysses all-to-all for the non-causal full pattern (CLIP's
        encoders — uniform rows, nothing to balance). The surrounding
        network stays GSPMD-sharded; only this core runs under shard_map.
        The reference has no sequence parallelism at all (SURVEY.md §5.7)."""
        from jax.sharding import PartitionSpec as P

        from ..parallel.context import active_mesh, batch_axes
        from .ring_attention import ring_attention, ulysses_attend

        mesh = active_mesh()
        sp = int(mesh.shape[self.sp_axis])
        assert n % sp == 0, f"seq len {n} not divisible by sp={sp}"
        d = self.dim_head
        scale = d**-0.5

        batch = batch_axes(mesh)
        head = "tp" if "tp" in mesh.axis_names else None
        qspec = P(batch, head, self.sp_axis, None)
        mspec = P(batch, self.sp_axis)

        if self.attn_type == "full" and self.causal:

            def body(q, k, v, km=None):
                return ring_attention(
                    q, k, v, self.sp_axis, sp,
                    causal=True, sm_scale=scale, key_mask=km,
                )

        elif self.attn_type in ("axial_row", "axial_col", "conv_like", "sparse"):
            from .block_sparse_attention import (
                sp_block_sparse_attend,
                sparse_kernel_enabled,
            )

            plan = _cached_sp_plan(self, n, sp)
            # chip-local compute rides the pair kernel at kernel-eligible
            # shapes (the chip tables are traced operands selected by
            # axis_index inside the body); dense-mask jnp otherwise
            from .block_sparse_attention import ENGAGE_FRAC

            use_kernel = (
                plan.layout.block_q == _sparse_block(n) != 0
                and plan.rows_per_chip % 128 == 0
                and plan.layout.visited_block_frac <= ENGAGE_FRAC
                and sparse_kernel_enabled()
            )
            interp = kv_policy.pallas_interpret()
            stable = self.stable
            sp_axis = self.sp_axis

            def body(q, k, v, km=None):
                return sp_block_sparse_attend(
                    q, k, v, plan, sp_axis, sp,
                    sm_scale=scale, key_mask=km,
                    use_kernel=use_kernel, interpret=interp, stable=stable,
                )

        else:

            def local_fn(q, k, v, km):
                return self._pattern_attend(q * scale, k, v, km)

            def body(q, k, v, km=None):
                return ulysses_attend(
                    q, k, v, self.sp_axis, sp, local_fn, key_mask=km
                )

        args = (q, k, v) if mask is None else (q, k, v, mask[:, :n])
        in_specs = (qspec,) * 3 + ((mspec,) if mask is not None else ())
        return jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=qspec,
            check_vma=False,
        )(*args)

    def _pattern_attend(self, q, k, v, mask, force_dense: bool = False):
        """Dispatch to this pattern's FLOP-efficient path (q pre-scaled).

        These grouped forms serve the non-flash shapes (CPU tests, decode
        mask rows, seqs not divisible by 128). At flash-eligible shapes the
        patterns ride the packed flash kernel instead — a measured decision
        (flagship shape: depth 12, seq 1280, batch 8, v5e, 2026-07: a
        pre-ledger note, not in PERF_LEDGER.jsonl):

          full / packed flash kernel     134 ms/step   (59% MFU baseline)
          sparse via flash pattern op    138 ms/step   (0.97x)
          axial_row grouped (this file)  171 ms/step   (0.79x)
          conv_like grouped, rolled      532 ms/step   (0.25x)

        After routing every pattern through the flash pattern operand, all
        four measure 136-137 ms (0.98x of full) at the flagship shape.

        The grouped forms compute 5-40x fewer score FLOPs yet LOSE: with
        attention only ~16% of the flagship step, their HBM-materialized
        score tensors (the image-queries x text-keys f32 block alone is
        537 MB/layer) cost more than the packed kernel's full-square MXU
        compute, which keeps scores in VMEM. A trace of the rolled conv
        path shows 51% loop-fusion + 17% layout-copy time — VPU/HBM work
        XLA cannot turn back into matmuls. Ceiling check: even a perfect
        axial kernel (~20% of full's score FLOPs, in-kernel) would save
        only ~17 ms of 134 (1.15x) — not worth a bespoke Pallas kernel
        next to the 0.97x the shared pattern path already delivers. The
        patterns' value at TPU flash shapes is memory (O(n*d)) and
        reference semantic parity, not speed; their compute win remains
        real where it always was — shapes where flash cannot run."""
        if not force_dense:
            if self.attn_type in ("axial_row", "axial_col"):
                return self._axial_attend(q, k, v, mask)
            if self.attn_type == "conv_like":
                # rematerialize the conv core in backward: its saved
                # activations (f32 text+window score tensors, ~220 MB/layer
                # at the flagship shape) pushed the 12-layer step past HBM
                # (19.5 G > 15.75 G, measured), while recomputing the rolls
                # and dots costs only O(f^2 ks^2 d) VPU work. No params or
                # RNG inside — a pure jax.checkpoint is safe.
                return jax.checkpoint(self._conv_attend)(q, k, v, mask)
        return self._dense_attend(q, k, v, mask)

    # ------------------------------------------------------------ dense paths

    def _key_mask(self, mask: Optional[jnp.ndarray], n: int) -> Optional[jnp.ndarray]:
        if mask is None:
            return None
        return mask[:, None, None, :n]  # (b, 1, 1, n)

    def _dense_attend(self, q, k, v, mask):
        n = q.shape[-2]
        allowed = jnp.asarray(self.pattern_mask()[:n, :n])[None, None]
        key_mask = self._key_mask(mask, n)
        if key_mask is not None:
            allowed = allowed & key_mask
        return dense_attend(q, k, v, allowed, self.stable)

    # ----------------------------------------------------------- axial path

    def _split_text_image(self, t, n):
        """Split (b, h, n, d) into text (static text_len) and image parts,
        padding the image part with zeros to the full grid."""
        f = self.image_fmap_size
        tl = self.text_len
        pad = self.seq_len - n
        text, img = t[..., :tl, :], t[..., tl:, :]
        if pad:
            img = jnp.pad(img, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return text, img.reshape(*t.shape[:2], f, f, t.shape[-1])

    def _axial_attend(self, q, k, v, mask):
        """Grouped axial attention: image queries attend within their own row
        (axial_row) or column (axial_col) plus the whole text prefix; text is
        plain causal. FLOPs: O(f^3) instead of O(f^4) for image-image."""
        b, h, n, d = q.shape
        f, tl = self.image_fmap_size, self.text_len
        axis = 0 if self.attn_type == "axial_row" else 1

        (q_text, q_img), (k_text, k_img), (v_text, v_img) = (
            self._split_text_image(t, n) for t in (q, k, v)
        )
        if axis == 1:  # group by columns: transpose the grid
            q_img, k_img, v_img = (t.swapaxes(2, 3) for t in (q_img, k_img, v_img))

        # text part: causal over text
        tmask = masks_lib.causal_mask(tl)[None, None]
        key_mask = self._key_mask(mask, tl)
        tmask = tmask & key_mask if key_mask is not None else jnp.asarray(tmask)
        out_text = dense_attend(q_text, k_text, v_text, tmask, self.stable)

        # image part: within-line causal + full text
        dots_line = jnp.einsum("bhxid,bhxjd->bhxij", q_img, k_img, preferred_element_type=jnp.float32)
        dots_text = jnp.einsum("bhxid,bhjd->bhxij", q_img, k_text, preferred_element_type=jnp.float32)

        line_mask = jnp.asarray(masks_lib.causal_mask(f))[None, None, None]
        if mask is not None:
            img_mask = jnp.pad(mask[:, tl:], ((0, 0), (0, self.seq_len - mask.shape[1])))
            img_mask = img_mask.reshape(-1, f, f)
            if axis == 1:
                img_mask = img_mask.swapaxes(1, 2)
            # (b, 1, x, 1, j): key j of line x
            line_mask = line_mask & img_mask[:, None, :, None, :]
            dots_text = jnp.where(mask[:, None, None, None, :tl], dots_text, NEG_INF)
        dots_line = jnp.where(line_mask, dots_line, NEG_INF)

        dots = jnp.concatenate((dots_text, dots_line), axis=-1)
        attn = _softmax(dots, self.stable).astype(v.dtype)
        attn_text, attn_line = attn[..., :tl], attn[..., tl:]
        out_img = jnp.einsum("bhxij,bhxjd->bhxid", attn_line, v_img) + jnp.einsum(
            "bhxij,bhjd->bhxid", attn_text, v_text
        )

        if axis == 1:
            out_img = out_img.swapaxes(2, 3)
        out_img = out_img.reshape(b, h, f * f, d)[..., : n - tl, :]
        return jnp.concatenate((out_text, out_img), axis=2)

    # ------------------------------------------------------------- conv path

    def _conv_window_mask(self) -> np.ndarray:
        """(img_seq, ks*ks) static validity mask: window element j of query p
        is a real in-grid position with flat index <= p."""
        f, ks, dil = self.image_fmap_size, self.kernel_size, self.dilation
        pad = ((ks - 1) * dil + 1) // 2
        p = np.arange(f * f)
        r, c = p // f, p % f
        offs = (np.arange(ks) * dil) - pad
        rr = r[:, None, None] + offs[None, :, None]  # (p, ks, 1)
        cc = c[:, None, None] + offs[None, None, :]  # (p, 1, ks)
        rr, cc = np.broadcast_to(rr, (f * f, ks, ks)), np.broadcast_to(cc, (f * f, ks, ks))
        in_grid = (rr >= 0) & (rr < f) & (cc >= 0) & (cc < f)
        idx = rr * f + cc
        ok = in_grid & (idx <= p[:, None, None])
        return ok.reshape(f * f, ks * ks)

    def _conv_attend(self, q, k, v, mask):
        """Conv-like local attention via per-offset grid rolls — the TPU
        analog of the reference's F.unfold over k/v feature maps
        (attention.py:156-158), reformulated so no (b, h, f^2, ks^2, d)
        window tensor is ever materialized: at the flagship shape those
        patch tensors are 400 MB each and blew HBM (21.4 G > 15.75 G,
        measured). Score k of query p is q[p]·k[p + off_k], so each of the
        ks^2 window offsets is one 2-D roll of the k/v grids plus an
        elementwise-product reduction over d — peak extra memory is the
        (b, h, f^2, ks^2) score tensor (~13 MB) and one rolled grid
        (~17 MB) instead. Wrapped-around roll entries land exactly where
        ``_conv_window_mask`` already marks the window invalid (out-of-grid
        or acausal), so masking is unchanged. FLOPs for image-image:
        O(f^2 * ks^2 * d)."""
        b, h, n, d = q.shape
        f, tl, ks, dil = self.image_fmap_size, self.text_len, self.kernel_size, self.dilation
        pad = ((ks - 1) * dil + 1) // 2

        (q_text, q_img), (k_text, k_img), (v_text, v_img) = (
            self._split_text_image(t, n) for t in (q, k, v)
        )

        # text part
        tmask = masks_lib.causal_mask(tl)[None, None]
        key_mask = self._key_mask(mask, tl)
        tmask = tmask & key_mask if key_mask is not None else jnp.asarray(tmask)
        out_text = dense_attend(q_text, k_text, v_text, tmask, self.stable)

        # window offsets in grid coordinates, row-major over the ks x ks
        # kernel — the same ordering _conv_window_mask uses
        offs = [
            ((i * dil) - pad, (j * dil) - pad)
            for i in range(ks) for j in range(ks)
        ]

        def shifted(t, dy, dx):
            # align k/v position (r+dy, c+dx) with query position (r, c)
            return jnp.roll(t, shift=(-dy, -dx), axis=(2, 3))

        dots_win = jnp.stack(
            [
                jnp.einsum(
                    "bhrcd,bhrcd->bhrc", q_img, shifted(k_img, dy, dx),
                    preferred_element_type=jnp.float32,
                )
                for dy, dx in offs
            ],
            axis=-1,
        ).reshape(b, h, f * f, ks * ks)
        q_flat = q_img.reshape(b, h, f * f, d)
        dots_text = jnp.einsum(
            "bhpd,bhjd->bhpj", q_flat, k_text,
            preferred_element_type=jnp.float32,
        )

        win_mask = jnp.asarray(self._conv_window_mask())[None, None]
        if mask is not None:
            img_mask = jnp.pad(mask[:, tl:], ((0, 0), (0, self.seq_len - mask.shape[1])))
            img_mask = img_mask.reshape(-1, f, f)
            valid_k = jnp.stack(
                [
                    jnp.roll(img_mask, shift=(-dy, -dx), axis=(1, 2))
                    for dy, dx in offs
                ],
                axis=-1,
            ).reshape(-1, 1, f * f, ks * ks)  # (b, 1, p, ks*ks)
            win_mask = win_mask & valid_k
            dots_text = jnp.where(mask[:, None, None, :tl], dots_text, NEG_INF)
        dots_win = jnp.where(win_mask, dots_win, NEG_INF)

        dots = jnp.concatenate((dots_text, dots_win), axis=-1)
        attn = _softmax(dots, self.stable).astype(v.dtype)
        attn_text, attn_win = attn[..., :tl], attn[..., tl:]
        attn_grid = attn_win.reshape(b, h, f, f, ks * ks)
        out_img = jnp.einsum("bhpj,bhjd->bhpd", attn_text, v_text)
        out_img = out_img + sum(
            (attn_grid[..., idx, None] * shifted(v_img, dy, dx))
            for idx, (dy, dx) in enumerate(offs)
        ).reshape(b, h, f * f, d)
        out_img = out_img[..., : n - tl, :]
        return jnp.concatenate((out_text, out_img), axis=2)

    # ------------------------------------------------------------ decode path

    def _cache_format(self, b: int) -> str:
        """This decode call's cache layout format ("paged" | "flat" | "4d").

        A SUPPLIED cache's variables win (resized, merged, or replayed
        caches keep the format they were built with); with no cache yet,
        the layout policy decides (ops/kv_policy.py — the named, logged
        replacement for the inline ``b == 8`` magic branch that used to
        live here, with the full measured flat-vs-4-D history in its
        docstring)."""
        if self.has_variable("cache", "cached_key_pages"):
            return "paged"
        if self.has_variable("cache", "cached_key"):
            ck = self.get_variable("cache", "cached_key")
            return "flat" if ck.ndim == 3 else "4d"
        return kv_policy.choose_cache_format(b)

    def _decode_caches(self, b, dtype):
        """The flat/4-D decode cache variables — ONE declaration shared by
        the fused and unfused paths, so prefill (unfused) composes with
        fused per-token steps on bit-identical caches.

        The flat-vs-4-D rank is a measured, batch-conditional layout choice
        (v5e-1 int8 flagship, 2026-07): 4-D (b, L, h, d) compiles to a
        positions-minor layout whose one-row dynamic-update-slice rewrites
        the whole buffer (43% of the batch-8 decode program by trace);
        FLAT (b, L, h*d) fixes that exactly at batch 8 (4,870 -> 6,705
        tok/s) and loses at batches 1/4/16/32 on the same chip. The policy
        lives in ops/kv_policy.py (4-D at b=1, flat at b=8, paged pools —
        ``_paged_caches`` below — elsewhere); every sweep/update site here
        handles either rank, and DALLE_TPU_KV_FORMAT / DALLE_TPU_FLAT_KV
        override for re-measurement."""
        h, d, L = self.heads, self.dim_head, self.seq_len
        fmt = self._cache_format(b)
        assert fmt in ("flat", "4d"), (
            f"paged caches are declared by _paged_caches, not here ({fmt})"
        )
        kv_shape = (b, L, h * d) if fmt == "flat" else (b, L, h, d)
        is_init = not self.has_variable("cache", "cached_key")
        cached_key = self.variable(
            "cache", "cached_key", jnp.zeros, kv_shape, dtype
        )
        cached_value = self.variable(
            "cache", "cached_value", jnp.zeros, kv_shape, dtype
        )
        cache_index = self.variable(
            "cache", "cache_index", lambda: jnp.array(0, dtype=jnp.int32)
        )
        return cached_key, cached_value, cache_index, is_init

    def _decode_attend_fused(self, qkv, mask, rotary_pos_emb):
        """Single-token decode through the fused Pallas kernel
        (ops/decode_attention.py)."""
        from .decode_attention import fused_decode_attention
        from .rotary import _rotate_half_matrix

        b = qkv.shape[0]
        h, d = self.heads, self.dim_head
        L = self.seq_len

        cached_key, cached_value, cache_index, is_init = self._decode_caches(
            b, qkv.dtype
        )
        if is_init:
            return jnp.zeros((b, 1, h * d), qkv.dtype)

        idx = cache_index.value
        use_rotary = rotary_pos_emb is not None
        if use_rotary:
            # angles cast to the compute dtype before cos/sin, matching
            # apply_rotary_emb (ops/rotary.py:82); the kernel widens to f32
            ang = rotary_pos_emb.astype(qkv.dtype)
            cos, sin = jnp.cos(ang), jnp.sin(ang)
        else:
            cos = jnp.zeros((L, d), qkv.dtype)
            sin = cos
        rot_p = jnp.asarray(_rotate_half_matrix(d), qkv.dtype)
        key_mask = None if mask is None else mask[..., None].astype(jnp.int32)

        flat_kv = cached_key.value.ndim == 3
        out, k_row, v_row = fused_decode_attention(
            qkv,
            cached_key.value.reshape(b, L, h * d),
            cached_value.value.reshape(b, L, h * d),
            idx, cos, sin, rot_p, key_mask,
            heads=h, dim_head=d, use_rotary=use_rotary,
            interpret=kv_policy.pallas_interpret(),
        )
        upd = jax.lax.dynamic_update_slice_in_dim
        row_shape = (b, 1, h * d) if flat_kv else (b, 1, h, d)
        cached_key.value = upd(
            cached_key.value, k_row.reshape(row_shape), idx, axis=1
        )
        cached_value.value = upd(
            cached_value.value, v_row.reshape(row_shape), idx, axis=1
        )
        cache_index.value = idx + 1
        return out

    def _has_windowed_cache(self) -> bool:
        """True when a supplied decode cache is narrower than seq_len (the
        segmented decode scan, models/sampling.py, grows the cache arrays
        between scan segments so early tokens sweep a smaller buffer)."""
        if not self.has_variable("cache", "cached_key"):
            return False
        ck = self.get_variable("cache", "cached_key")
        return ck.shape[1] != self.seq_len

    def _decode_attend(self, q, k, v, mask, rotary_pos_emb, block_len=None,
                       block_start=None):
        """Decode against an n-major (b, W, h, d) K/V cache: single-token
        steps or multi-token prefill blocks (n > 1, e.g. the text prompt in
        one parallel pass). Each new token's row of the pattern mask selects
        which cached keys it sees, so attending against the full-length cache
        (zeros beyond the write index, always masked) matches sequential
        decode exactly. The cache keeps positions on the second-major axis so
        the per-token cache-wide QK^T / AV sweeps scan (W, h*d) rows in the
        projection's natural layout and decode needs no head transposes at
        all. (The sweeps themselves are latency-bound on the serial
        cache-update -> read dependency, not layout-bound: per-token cost
        measured identical to the (b, h, W, d) variant.)

        The sweep extent W is the SUPPLIED cache's row count, normally
        seq_len: the segmented decode scan (models/sampling.py) passes
        caches sized to the generation frontier (guaranteeing idx + n <= W)
        so early tokens pay O(W) HBM traffic instead of O(seq_len). Rows in
        [idx + n, W) are zeros under a False pattern-mask column, exactly
        like the full-length case, so the result is mathematically
        identical — masked lanes contribute exp(-inf) = 0 either way (~1 ulp
        summation-order drift where the narrower einsum chunks
        differently)."""
        b, n, h, d = q.shape
        if self._cache_format(b) == "paged":
            return self._decode_attend_paged(
                q, k, v, mask, rotary_pos_emb, block_len=block_len,
                block_start=block_start,
            )
        if block_len is not None or block_start is not None:
            raise ValueError(
                "ragged blocks (block_len/block_start) need the paged cache "
                "format: the flat/4d formats' scalar cache index cannot "
                "advance per row"
            )

        cached_key, cached_value, cache_index, is_init = self._decode_caches(
            b, k.dtype
        )
        if is_init:
            return jnp.zeros_like(q)
        W = cached_key.value.shape[1]

        idx = cache_index.value
        if rotary_pos_emb is not None:
            rows = jax.lax.dynamic_slice_in_dim(rotary_pos_emb, idx, n, axis=0)
            rows = rows[None, :, None, :]  # broadcast over (b, n, h, d)
            q, k, v = (apply_rotary_emb(rows, t) for t in (q, k, v))
        q = q * (d**-0.5)

        flat_kv = cached_key.value.ndim == 3
        cached_key.value = jax.lax.dynamic_update_slice_in_dim(
            cached_key.value, k.reshape(b, n, h * d) if flat_kv else k, idx, axis=1
        )
        cached_value.value = jax.lax.dynamic_update_slice_in_dim(
            cached_value.value, v.reshape(b, n, h * d) if flat_kv else v, idx, axis=1
        )
        cache_index.value = idx + n
        k_cache = cached_key.value
        v_cache = cached_value.value

        allowed = jax.lax.dynamic_slice_in_dim(
            jnp.asarray(self.pattern_mask())[:, :W], idx, n, axis=0
        )[None, None]  # (1, 1, n, W)
        if mask is not None:
            allowed = allowed & mask[:, None, None, :W]
        return self._cache_attend(q, k_cache, v_cache, allowed)

    # ------------------------------------------------------- paged decode

    def _kv_quant(self) -> str:
        """This paged decode call's storage quantization ("none" |
        "int8"). A SUPPLIED cache's variables win — a cache carrying
        scale pools IS quantized, one without them is not, so resized /
        merged / replayed caches keep the format they were built with;
        with no cache yet, the quant policy decides
        (ops/kv_policy.py:choose_kv_quant — explicit ``kv_quant=``
        override context, then DALLE_TPU_KV_QUANT, then "none"). Paged
        format only: the flat/4d caches never consult this (their
        single-stream int8 experiment measured SLOWER — the note at the
        bottom of this file)."""
        if self.has_variable("cache", "cached_key_scale_pages"):
            return "int8"
        if self.has_variable("cache", "cached_key_pages"):
            return "none"
        return kv_policy.choose_kv_quant()

    def _paged_caches(self, b, dtype):
        """The block-paged decode cache variables (ops/paged_kv.py): K/V
        page pools (b, n_pages, page, h*d), a per-sequence page table, and
        a PER-SEQUENCE (b,) write index — the only cache format whose index
        can express ragged decode offsets across the batch (continuous
        batching). Page size comes from kv_policy.page_size().

        Under ``kv_quant="int8"`` (ops/kv_policy.py) the content pools
        store int8 and two PARALLEL scale pools (b, n_pages, page, h)
        f32 ride the same page tables — pool-shaped like the content
        (feat = heads), so every pool primitive (append/gather/
        copy_pages/copy_pages_across/reset_rows and the prefix-cache
        arena indirection) covers scales by construction. Returned
        scale variables are None when unquantized."""
        from . import paged_kv

        h, d, L = self.heads, self.dim_head, self.seq_len
        page = kv_policy.page_size()
        n_p = paged_kv.num_pages(L, page)
        quant = self._kv_quant()
        is_init = not self.has_variable("cache", "cached_key_pages")
        pool_dtype = jnp.int8 if quant == "int8" else dtype
        pool_shape = (b, n_p, page, h * d)
        k_pool = self.variable(
            "cache", "cached_key_pages", jnp.zeros, pool_shape, pool_dtype
        )
        v_pool = self.variable(
            "cache", "cached_value_pages", jnp.zeros, pool_shape, pool_dtype
        )
        k_scale = v_scale = None
        if quant == "int8":
            scale_shape = (b, n_p, page, h)
            k_scale = self.variable(
                "cache", "cached_key_scale_pages", jnp.zeros, scale_shape,
                paged_kv.SCALE_DTYPE,
            )
            v_scale = self.variable(
                "cache", "cached_value_scale_pages", jnp.zeros, scale_shape,
                paged_kv.SCALE_DTYPE,
            )
        table = self.variable("cache", "page_table", paged_kv.identity_table, b, n_p)
        cache_index = self.variable(
            "cache", "cache_index", jnp.zeros, (b,), jnp.int32
        )
        return k_pool, v_pool, k_scale, v_scale, table, cache_index, is_init

    def _decode_attend_paged(self, q, k, v, mask, rotary_pos_emb,
                             block_len=None, block_start=None):
        """Decode against the block-paged cache: rotary rows, pattern-mask
        rows, and the write position are all indexed PER SEQUENCE from the
        (b,) cache index, so a batch whose sequences sit at different
        decode offsets runs in one step (continuous batching — the
        flat/4-D scalar-index formats cannot express it). The per-step
        cache update is a one-row scatter inside one page per sequence;
        the gather then assembles the logical (b, W, h*d) view (W = pages
        * page_size, >= the frontier; rows past a sequence's own frontier
        are zeros under a False pattern-mask column, the same masked-zeros
        argument as the flat path). Attention arithmetic is the shared
        ``_cache_attend``, so paged/flat/4-D parity is exact by
        construction.

        ``block_len`` (b,) marks a RAGGED block — the fused serving
        iteration's descriptor (ops/ragged_attention.py): row b's valid
        tokens are columns [0, block_len[b]) of the padded width-n block.
        K/V writes are masked to the valid columns (``paged_kv.append``
        ``limit``), the cache index advances by block_len per row, and on
        TPU the attention core dispatches to the Pallas ragged
        paged-attention kernel for causal-"full" layers; everywhere else
        it stays the gathered-view ``_cache_attend`` — the SAME einsums
        as the split prefill-chunk/decode paths, which is what makes
        fused-vs-split engine parity bitwise on the f32 CPU tier. Invalid
        columns
        compute garbage that is finite (clipped mask rows keep at least
        one key visible) and discarded by the caller.

        ``block_start`` (b,), optional (requires ``block_len``): anchor the
        block at the DESCRIPTOR's position instead of the stored cache
        index — the speculative-decode rewind (serving/engine.py). A
        verify block writes its full padded length, but only
        ``accepted`` positions survive; the next descriptor's
        block_start lags the stored index by the rejected count, and
        anchoring the write base, rotary rows, and mask rows there makes
        the rejected positions plain overwrites: garbage K/V beyond the
        anchor frontier is causally masked until the next block lands on
        it. With block_start equal to the stored index (every
        non-speculative fused dispatch) the arithmetic is value-identical
        to the unanchored form."""
        from . import paged_kv, ragged_attention

        b, n, h, d = q.shape
        (k_pool, v_pool, k_scale, v_scale, table, cache_index,
         is_init) = self._paged_caches(b, k.dtype)
        if is_init:
            return jnp.zeros_like(q)

        if block_start is not None:
            assert block_len is not None, (
                "block_start anchoring is a ragged-block feature: pass "
                "block_len"
            )
            idx = block_start  # (b,) descriptor anchor
        else:
            idx = cache_index.value  # (b,)
        pos = idx[:, None] + jnp.arange(n, dtype=idx.dtype)[None]  # (b, n)
        if rotary_pos_emb is not None:
            T = rotary_pos_emb.shape[0]
            rows = jnp.take(rotary_pos_emb, jnp.minimum(pos, T - 1), axis=0)
            q, k, v = (
                apply_rotary_emb(rows[:, :, None, :], t) for t in (q, k, v)
            )
        q = q * (d**-0.5)

        hd = h * d
        k_rows, v_rows = k.reshape(b, n, hd), v.reshape(b, n, hd)
        if k_scale is not None:
            # int8 storage: quantize at APPEND time (per-row, per-head
            # symmetric scales — paged_kv.quantize_rows) and append the
            # scales to the parallel scale pools through the SAME table/
            # index/limit, so bytes and scales can never go out of step
            # (the spec-decode rewind overwrites both identically)
            k_rows, k_s = paged_kv.quantize_rows(k_rows, h)
            v_rows, v_s = paged_kv.quantize_rows(v_rows, h)
            k_scale.value = paged_kv.append(
                k_scale.value, table.value, idx, k_s, limit=block_len
            )
            v_scale.value = paged_kv.append(
                v_scale.value, table.value, idx, v_s, limit=block_len
            )
        k_pool.value = paged_kv.append(
            k_pool.value, table.value, idx, k_rows, limit=block_len,
        )
        v_pool.value = paged_kv.append(
            v_pool.value, table.value, idx, v_rows, limit=block_len,
        )
        if block_start is not None:
            # idle rows (block_len 0) carry garbage descriptors; their
            # stored index passes through untouched
            cache_index.value = jnp.where(
                block_len > 0, idx + block_len, cache_index.value
            )
        else:
            cache_index.value = idx + (n if block_len is None else block_len)

        causal_full = self.attn_type == "full" and self.causal
        if (
            block_len is not None
            and ragged_attention.use_kernel(causal_full, mask is not None)
        ):
            interpret = kv_policy.pallas_interpret()
            kv_policy.record_route(
                f"ragged_block/{self.attn_type}", "ragged_paged_kernel",
                interpret,
            )
            return ragged_attention.kernel_attend(
                q, k_pool.value, v_pool.value, table.value, idx, block_len,
                interpret=interpret,
                k_scales=None if k_scale is None else k_scale.value,
                v_scales=None if v_scale is None else v_scale.value,
            )
        if block_len is not None:
            kv_policy.record_route(
                f"ragged_block/{self.attn_type}", "paged_gather_jnp"
            )

        k_cache = paged_kv.gather(k_pool.value, table.value)  # (b, W, h*d)
        v_cache = paged_kv.gather(v_pool.value, table.value)
        if k_scale is not None:
            # read-time dequant of the gathered view: the ONE shared
            # formula (paged_kv.dequant) the Pallas kernel also
            # implements per page, widened back to the compute dtype
            # before the shared attention core
            k_cache = paged_kv.dequant(
                k_cache, paged_kv.gather(k_scale.value, table.value), k.dtype
            )
            v_cache = paged_kv.dequant(
                v_cache, paged_kv.gather(v_scale.value, table.value), v.dtype
            )
        W = k_cache.shape[1]

        pm = jnp.asarray(self.pattern_mask())  # (L, L)
        L = pm.shape[0]
        pm = pm[:, :W] if W <= L else jnp.pad(pm, ((0, 0), (0, W - L)))
        # per-sequence mask rows (jnp.take, clipped): row pos[b, j] of the
        # pattern selects which cached keys step j of sequence b sees
        allowed = jnp.take(pm, jnp.minimum(pos, L - 1), axis=0)  # (b, n, W)
        if mask is not None:
            km = mask[:, :W]
            if km.shape[1] < W:
                km = jnp.pad(km, ((0, 0), (0, W - km.shape[1])))
            allowed = allowed & km[:, None, :]
        return self._cache_attend(q, k_cache, v_cache, allowed[:, None])

    # -------------------------------------------- shared cache arithmetic

    def _cache_attend(self, q, k_cache, v_cache, allowed):
        """Masked attention of q (b, n, h, d — pre-scaled) against a cache
        view of W rows: k_cache/v_cache any (b, W, h*d)-reshapeable rank,
        ``allowed`` broadcastable to (b, 1, n, W). ONE implementation
        serves every cache format, so paged/flat/4-D can only differ in
        storage, never in arithmetic."""
        b, n, h, d = q.shape
        W = k_cache.shape[1]

        if (
            n == 1 and d < 128 and 128 % d == 0 and h % (128 // d) == 0
            and lane_pack_enabled()
        ):
            # lane-packed single-token sweeps: dim_head < 128 half-fills
            # the vector lanes of the (L, h, d) cache tiles, capping the
            # QK/AV sweeps at ~250 GB/s (trace-measured). Packing P=128/d
            # heads per 128-lane tile with a block-diagonal q restores
            # full-lane contractions — same math, better effective
            # bandwidth on the serving hot loop. TPU-gated
            # (lane_pack_enabled): the regrouped contraction is ~1-ulp
            # off the plain gemm at some head counts, and off-TPU the
            # fused-vs-split bit-parity gates need every decode on the
            # one shared gemm below.
            kv_policy.record_route("decode_token", "lane_packed_einsum")
            P_ = 128 // d
            G = h // P_
            eye = jnp.eye(P_, dtype=q.dtype)
            K2 = k_cache.reshape(b, W, G, P_ * d)
            V2 = v_cache.reshape(b, W, G, P_ * d)
            qr = q.reshape(b, G, P_, d)
            qblk = jnp.einsum("bgpd,pq->bgpdq", qr, eye).reshape(b, G, P_ * d, P_)
            s = jnp.einsum(
                "blgc,bgcp->bglp", K2, qblk, preferred_element_type=jnp.float32
            )
            # allowed (b|1, 1, 1, L) -> (b|1, 1, L, 1) over s's (b, g, l, p)
            s = jnp.where(allowed[:, :, 0, :, None], s, NEG_INF)
            att = _softmax(s, self.stable, axis=2)
            og = jnp.einsum(
                "bglp,blgc->bgpc", att.astype(V2.dtype), V2
            )  # (b, G, P, P*d); head p's output is its own 64-lane slice
            out = jnp.stack(
                [og[:, :, p, p * d:(p + 1) * d] for p in range(P_)], axis=2
            )
            return out.reshape(b, 1, h, d)

        kv_policy.record_route(
            "decode_token" if n == 1 else "decode_block", "cache_block_attend"
        )
        return cache_block_attend(q, k_cache, v_cache, allowed, self.stable)

    # Decode cost accounting (int8 serving, v5e-1, measured by trace —
    # tools/analyze_trace.py, 2026-07): of ~0.82 ms/token, the int8 weight
    # matvecs take ~290 us (at/near HBM bandwidth — nothing left there),
    # the QK+AV cache sweeps ~244 us, small ops ~100 us, head+sampling the
    # rest. The sweeps ran at only ~250 GB/s because dim_head=64 half-fills
    # the 128-lane tiles of the (b, L, h, d) caches. The lane-packed XLA
    # reformulation in _cache_attend above (P heads per 128-lane tile,
    # block-diagonal q — same math, ~1 ulp off the plain gemm at some head
    # counts, hence TPU-gated via lane_pack_enabled) recovers part of that:
    # measured int8 0.823 -> 0.813 ms/token, bf16 1.044 -> 1.029
    # (reproduced twice).
    # The same packing done as a Pallas kernel (ops/decode_attention.py)
    # measured SLOWER than XLA's chain (skinny-MXU latency) and stays
    # opt-in; the residual sweep inefficiency is the remaining frontier.
    #
    # NOTE on int8 K/V caches (measured, v5e-1, 2026-07): quantizing the
    # decode caches was tried two ways — int8 storage widened inside the
    # cache dots (0.94 ms/token) and native s8xs8->s32 MXU dots with rowwise
    # scales on q/K/attn/V (1.44 ms/token) — and BOTH lost to the plain
    # bf16 cache (0.84 ms/token). Single-stream decode here is latency-bound
    # on the serial op chain, not HBM-bound: the ~31 MB/step the int8 cache
    # saves is worth ~40 us at HBM bandwidth, while the extra quantize /
    # dequantize elementwise stages add more serial work than that to every
    # one of the 1024 steps. The flat/4d caches therefore stay bf16; int8
    # serving quantizes what decode is actually bound on — the weight
    # matrices and embedding tables (utils/quantize.py). The PAGED serving
    # pools are the different regime that negative result does NOT cover:
    # the engine's batched pools are the largest HBM tenant of a
    # throughput-bound fleet (capacity, prefix-cache arena, and the
    # streamed-page kernel all scale with KV bytes), so they get an
    # opt-in int8 storage format with per-(token, head) scales behind
    # kv_policy.choose_kv_quant — see _paged_caches/_kv_quant above and
    # docs/DESIGN.md §6.1; TPU wall numbers pend a device session.
    #
    # Round-5 serial-chain attack (measured, v5e-1, 2026-07): the "head +
    # sampling the rest" slice of the accounting above was mostly NOT the
    # head matvec — it was the per-step (b, 18k)-wide f32 op chain around
    # it (logits-mask dynamic-slice + where, f32 cast, the [ext:] sampling
    # slice). The image-only head (models/dalle.py:_head_image) computes
    # just the image-vocab head columns and drops that chain entirely:
    # int8 batch-1 0.779 -> 0.686 ms/token. Two windowed-sweep designs were
    # then measured for the O(frontier)-instead-of-O(L) cache sweep idea:
    # (a) static sliced VIEWS of the full cache inside the step — XLA
    # materializes the slice as a per-step copy, +0.11 ms/token, REJECTED;
    # (b) frontier-sized cache ARRAYS grown between scan segments
    # (models/sampling.py:resize_kv) — batch-1 neutral-to-slightly-negative
    # (latency-bound), batch >= 8 wins 12-13% tokens/sec (sweep traffic
    # scales with batch). Hence the batch-adaptive segmentation default in
    # decode_tokens.
    #
    # Dynamic-update-slice traffic (trace-found, v5e-1, 2026-07): a batch-8
    # trace showed 43% of the decode program in DUS. Two separate causes,
    # two fixes: (1) the token-shift histories were full-sequence
    # (b, 1281, dim) buffers updated every step — but the shift only looks
    # back image_size positions, so they are now (image_size+1)-row rings
    # with static slice indices (ops/layers.py:PreShiftToken), worth ~3-4%
    # at batch 1 and ~40x less shift-cache memory; (2) the K/V caches'
    # 4-D shape compiled to a positions-minor layout whose one-row update
    # rewrites the whole buffer — see the measured batch-conditional
    # flat-vs-4-D policy in _decode_caches (batch 8: +38% tokens/sec).


def _causal_attend(q, k, v, scale: float, use_flash: bool, site: str, window=None):
    """Causal softmax attention over (b, h, n, d) with as many key/value as
    query heads: the blocked flash kernels where the length has a usable block
    (``_flash_block``), one dense masked softmax elsewhere; which, at the
    route site ``site``. ``window``: a sliding window, key j visible to query
    i only where ``i - j < window``; the flash kernels then visit the band's
    tiles only, and the route records the window and the tiles its grid
    visits beside the causal triangle's (``flash_attention.window_tiles``)."""
    n, d = q.shape[2], q.shape[3]
    block = _flash_block(n) if use_flash else 0
    if block and window is not None and not window_supported(n, d, block):
        block = 0
    if block:
        interpret = kv_policy.pallas_interpret()
        detail = {} if window is None else dict(window=window, **window_tiles(n, block, window))
        kv_policy.record_route(site, "blocked_flash", interpret, **detail)
        return _per_device(
            lambda q, k, v: flash_attention(
                q, k, v, None, True, None, scale, block, block, interpret, window
            ),
            (q, k, v),
        )
    kv_policy.record_route(site, "dense_masked", **({} if window is None else dict(window=window)))
    allowed = jnp.tril(jnp.ones((n, n), bool))
    if window is not None:
        allowed = jnp.logical_and(allowed, jnp.triu(jnp.ones((n, n), bool), 1 - window))
    return dense_attend(q * scale, k, v, allowed)


class GroupedKVAttention(nn.Module):
    """Causal softmax attention with fewer key/value heads than query heads:
    query head ``i`` attends key/value head ``i // (heads // kv_heads)``. No
    bias. ``sm_scale`` is the model's own softmax scale (not necessarily
    ``dim_head ** -0.5``). By default no positional term and every earlier
    key; ``rotary_dim`` turns the first that many channels of ``q`` and
    ``k`` in half-split pairs (``rotate_half_split`` at ``rope_theta``), and
    ``window`` limits query i to keys ``i - window < j <= i`` (a sliding
    window of ``window`` keys, its own included): the ``smallthinker``
    family's window layer, recorded at ``forward/swa`` where the layer
    without one is recorded at ``forward/gqa``.

    Training route: the key/value heads are broadcast to the query heads in
    front of the blocked flash kernel (ops/flash_attention.py; the sum of the
    gradient over a group is autodiff's; with a window the kernels' grid
    spans the band's tiles only); where the length has no usable block
    (``_flash_block``) it is one dense masked softmax. No kernel here groups
    K/V heads yet, and there is no decode mode: serving a stack with such
    layers is ROADMAP R9's other half."""

    dim: int
    heads: int
    kv_heads: int
    dim_head: int
    sm_scale: float
    use_flash: bool = True
    rotary_dim: int = 0
    rope_theta: float = 10000.0
    window: Optional[int] = None
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        # ``deterministic``: the trunk's uniform half-block argument; no dropout here
        b, n, _ = x.shape
        h, g, d = self.heads, self.kv_heads, self.dim_head
        assert h % g == 0, f"{h} query heads over {g} key/value heads"
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, name=name, dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        turn = lambda t: rotate_half_split(t, self.rotary_dim, self.rope_theta)
        q = dense(h * d, "to_q")(x).reshape(b, n, h, d).transpose(0, 2, 1, 3)
        if self.rotary_dim:
            q = turn(q)
        kv = dense(2 * g * d, "to_kv")(x).reshape(b, n, 2, g, d)

        def grouped(i):   # the key (0) or value (1) heads, one per query head
            t = kv[:, :, i].transpose(0, 2, 1, 3)
            return jnp.repeat(turn(t) if i == 0 and self.rotary_dim else t, h // g, axis=1)

        k, v = grouped(0), grouped(1)
        site = "forward/gqa" if self.window is None else "forward/swa"
        out = _causal_attend(q, k, v, float(self.sm_scale), self.use_flash, site, self.window)
        out = out.transpose(0, 2, 1, 3).reshape(b, n, h * d)
        return dense(self.dim, "to_out")(out)


@functools.lru_cache(maxsize=None)
def _half_split_angles(n: int, rot_dim: int, theta: float) -> np.ndarray:
    """(n, rot_dim / 2) float32 rotary angles of positions 0 … n-1 for the
    HALF-SPLIT pairing: channel ``c`` turns with channel ``c + rot_dim / 2``."""
    return np.einsum(
        "i,j->ij", np.arange(n, dtype=np.float64), lang_freqs(rot_dim, theta)
    ).astype(np.float32)


def rotate_half_split(t, rot_dim: int, theta: float):
    """Rotary over the first ``rot_dim`` channels of (b, h, n, d), pairing
    channel ``c`` with ``c + rot_dim / 2`` (the pairing of the ``qwen3_next``
    family; ``ops/rotary.py`` pairs adjacent channels); the channels past
    ``rot_dim`` pass untouched. Cosine and sine of the float32 angles
    (``ops/rotary.py:cos_sin``)."""
    half = rot_dim // 2
    cos, sin = rotary.cos_sin(jnp.asarray(_half_split_angles(t.shape[2], rot_dim, float(theta))), t.dtype)
    a, b = t[..., :half], t[..., half:rot_dim]
    return jnp.concatenate((a * cos - b * sin, b * cos + a * sin, t[..., rot_dim:]), axis=-1)


def output_gate(out, gate):
    """``out * sigmoid(gate)``, element by element, in the dtype of ``out``."""
    return out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)


class GatedAttention(nn.Module):
    """The ``qwen3_next`` family's softmax attention: grouped key/value heads,
    a per-head RMSNorm of queries and keys, rotary over the first
    ``rotary_dim`` channels (half-split pairs) and an element-wise output gate
    that comes out of the query projection. No bias.

        [q_i | g_i] = (W_q u)_i;   k_j = (W_k u)_j,  v_j = (W_v u)_j
        q_i <- rot(RMSNorm(q_i)),  k_j <- rot(RMSNorm(k_j))
        o_i = softmax_causal(q_i . k_{i // group} / sqrt(d)) v_{i // group}
        y = W_o [o . sigmoid(g)]

    The norms' gains start at 1 (the source writes ``1 + w`` with ``w`` from
    0: the same function and the same Adam trajectory). Training route as
    ``GroupedKVAttention``: the key/value heads broadcast in front of the
    blocked flash kernels, recorded at ``forward/gated_attn``; no decode mode
    (ROADMAP R9)."""

    dim: int
    heads: int
    kv_heads: int
    dim_head: int
    rotary_dim: int
    rope_theta: float = 10000.0
    eps: float = 1e-6
    use_flash: bool = True
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        # ``deterministic``: the trunk's uniform half-block argument; no dropout here
        b, n, _ = x.shape
        h, g, d = self.heads, self.kv_heads, self.dim_head
        assert h % g == 0, f"{h} query heads over {g} key/value heads"
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, name=name, dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        norm = lambda name, t: RMSNorm(self.eps, self.param_dtype, name=name)(t).astype(self.dtype)
        heads_first = lambda t: t.transpose(0, 2, 1, 3)
        qg = dense(h * 2 * d, "to_q")(x).reshape(b, n, h, 2 * d)
        q, gate = qg[..., :d], qg[..., d:].reshape(b, n, h * d)
        k = dense(g * d, "to_k")(x).reshape(b, n, g, d)
        v = dense(g * d, "to_v")(x).reshape(b, n, g, d)
        q = rotate_half_split(heads_first(norm("q_norm", q)), self.rotary_dim, self.rope_theta)
        k = rotate_half_split(heads_first(norm("k_norm", k)), self.rotary_dim, self.rope_theta)
        k, v = (jnp.repeat(t, h // g, axis=1) for t in (k, heads_first(v)))
        out = _causal_attend(q, k, v, float(d**-0.5), self.use_flash, "forward/gated_attn")
        out = heads_first(out).reshape(b, n, h * d)
        return dense(self.dim, "to_out")(output_gate(out, gate))


@functools.lru_cache(maxsize=None)
def _rope_angles(n: int, rot_dim: int, theta: float) -> np.ndarray:
    """(n, rot_dim) float32 rotary angles of positions 0 … n-1: adjacent
    channel pairs share a frequency (``rope_interleave``)."""
    return angles(np.arange(n), lang_freqs(rot_dim, theta)).astype(np.float32)


class LatentAttention(nn.Module):
    """Multi-head latent attention (the DeepSeek-V2/V3 family's MLA) in its
    EXPANDED form, for training and whole-sequence evaluation. No bias.

        c_q = RMSNorm(W_qa u);  [q_nope_i | q_rope_i] = (W_qb c_q)_i
        [c_kv | k_rope] = W_kva u;  [k_nope_i | v_i] = (W_kvb RMSNorm(c_kv))_i
        s_i = (q_nope_i . k_nope_i + rot(q_rope_i) . rot(k_rope)) / sqrt(nope + rope)

    Queries go through a rank-``q_rank`` bottleneck with a norm, keys and
    values through a rank-``kv_rank`` latent with a norm; ONE rotary key of
    width ``rope_dim`` is shared by all heads and rotated by position over
    adjacent channel pairs (angles, cosines and sines in float32,
    ops/rotary.py:cos_sin); query/key width ``nope_dim + rope_dim`` against
    value width ``v_dim``. ``q_rank`` None: the query is not compressed,
    ``[q_nope_i | q_rope_i] = (W_q u)_i`` (``to_q``); ``rotary`` off (NoPE):
    the ``rope_dim`` channels of the queries and of the shared key are not
    rotated and enter the score as they are.

    Training route: the blocked flash kernels with a value width of their own
    (ops/flash_attention.py: nothing is padded to the query width), recorded
    at ``forward/mla``; where the length has no usable block it is one dense
    masked softmax. The absorbed form (attention over the latent itself, no
    per-head keys) is serving's and is not written: ROADMAP R11."""

    dim: int
    heads: int
    q_rank: Optional[int]
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float = 10000.0
    rotary: bool = True
    eps: float = 1e-6
    use_flash: bool = True
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        # ``deterministic``: the trunk's uniform half-block argument; no dropout here
        b, n, _ = x.shape
        h, dn, dr, dv = self.heads, self.nope_dim, self.rope_dim, self.v_dim
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, name=name, dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        norm = lambda name, t: RMSNorm(self.eps, self.param_dtype, name=name)(t).astype(self.dtype)
        heads_first = lambda t: t.transpose(0, 2, 1, 3)

        if self.q_rank is None:
            q = dense(h * (dn + dr), "to_q")(x)
        else:
            c_q = norm("q_norm", dense(self.q_rank, "to_q_a")(x))
            q = dense(h * (dn + dr), "to_q_b")(c_q)
        q = heads_first(q.reshape(b, n, h, dn + dr))
        kv_a = dense(self.kv_rank + dr, "to_kv_a")(x)
        c_kv, k_rope = kv_a[..., : self.kv_rank], kv_a[..., self.kv_rank :]
        kv = heads_first(
            dense(h * (dn + dv), "to_kv_b")(norm("kv_norm", c_kv)).reshape(b, n, h, dn + dv)
        )
        if self.rotary:
            table = jnp.asarray(_rope_angles(n, dr, float(self.rope_theta)))
            q = jnp.concatenate((q[..., :dn], apply_rotary_emb(table, q[..., dn:])), axis=-1)
            k_rope = apply_rotary_emb(table, k_rope[:, None])         # (b, 1, n, dr)
        else:
            k_rope = k_rope[:, None]
        k = jnp.concatenate(
            (kv[..., :dn], jnp.broadcast_to(k_rope, (b, h, n, dr))), axis=-1
        )
        v = kv[..., dn:]

        out = _causal_attend(q, k, v, float((dn + dr) ** -0.5), self.use_flash, "forward/mla")
        out = heads_first(out).reshape(b, n, h * dv)
        return dense(self.dim, "to_out")(out)
