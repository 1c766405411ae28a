"""Block-paged decode KV cache: alloc / append / gather over fixed-size pages.

Ragged Paged Attention (PAPERS.md) is the TPU-native answer to the
batch-conditional cache-layout hack this repo carried (flat at batch 8,
4-D elsewhere — ops/attention.py:_decode_caches history): store K/V in
fixed-size pages of ``page_size`` tokens, reach them through a per-sequence
page table, and make the decode step's cache update a PAGE-LOCAL write. The
layout is then a property of the cache, not of the batch size:

- pools are ``(b, n_pages, page_size, h*d)`` — the minor two dims (one
  page) are identical at every batch size, so XLA's layout choice cannot
  re-tip per batch the way the flat/(b, L, h*d) vs 4-D/(b, L, h, d) ranks
  did (the root cause of serving throughput being non-monotone in batch:
  batch 32 at 6,050 tok/s below batch 8's 6,832: pre-ledger note, not in
  PERF_LEDGER.jsonl);
- the per-step append is a one-row scatter inside one page per sequence —
  never the whole-buffer dynamic-update-slice rewrite the 4-D layout
  compiled to (trace-measured 43% of the batch-8 decode program);
- the write index is PER SEQUENCE (``(b,)`` int32), so requests at
  different decode offsets share one step — continuous batching. The
  flat/4-D formats' scalar index cannot express that;
- the page table indirection (identity inside one jitted generation) is
  the seam a serving layer needs for page reuse / prefix sharing across
  requests without recompiling.

Tables hold GLOBAL physical page ids (PR 10): entry (b, l) names page
``g`` of the FLATTENED (b * n_pages, page_size, feat) pool view —
``g = row * n_pages + p`` for the identity mapping — so a table entry can
reference a page that physically lives in ANOTHER batch row's storage.
That is what makes cross-request prefix sharing a page-table indirection
(serving/prefix_cache.py maps a cache-hit request's prompt pages at the
publisher's physical pages, refcounted, copy-on-write on divergence)
instead of a cache redesign. Identity-mapped callers (every in-jit user:
generation, training-free decode, the batch-1 prefill caches) see
bit-identical behavior — the gather/append arithmetic only reshapes the
pool view, never the data. Sharded serving note: a pjit-sharded pool
would keep tables row-local (a global gather crosses shards); the
single-device serving engine is the consumer of the global form.

Two XLA formulations of the page gather were built and measured (CPU,
this box, 2026-08; pools (8, 10, 128, 1024) bf16, jitted, best of 50):

- ``take``   — ``jnp.take_along_axis`` down the page axis: 0.47 ms/gather.
  XLA fuses the row gather into the consuming attention einsum's operand
  read on TPU, so no (b, L, h*d) copy materializes in HBM.
- ``onehot`` — one-hot(table) matmul against the pool (gather as MXU
  work): 23.5 ms/gather on CPU, ~50x slower — the (b, n_pages, n_pages)
  one-hot contraction re-reads the whole pool per logical page. Kept for
  re-measurement (``DALLE_TPU_PAGED_GATHER=onehot``) because on TPU a
  skinny matmul sometimes beats the gather unit; the CPU loser's numbers
  stay recorded here either way.

A third option — extending the fused Pallas decode kernel
(ops/decode_attention.py) with page-table scalar prefetch — was REJECTED
without building it: that kernel is already a measured negative result for
this decode shape (~29 us/layer vs ~10 us for the XLA op chain it
replaces, v5e; its module docstring), and paging adds an indirection per
K/V block on top of the same skinny-MXU serialization. Revisit only if a
serving cell's trace shows the take-gather path bound on gather overhead
rather than on page bytes.

All functions are pure array ops (no flax state); ops/attention.py owns
the cache variables and calls these.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .kv_policy import DEFAULT_PAGE_SIZE

# Every cache-tree leaf name that is POOL-SHAPED — (rows, n_pages, page,
# feat) storage addressed by global page ids. The serving engine's
# generic pool machinery (arena append, publish/COW/restore page copies,
# eviction resets, snapshot leaf enumeration) pattern-matches on THIS
# tuple, so a new pool kind (the int8 scale pools) rides every seam by
# construction instead of by N hand-updated name lists. CONTENT_KEYS are
# the K/V byte pools; SCALE_KEYS the parallel per-(token, head) scale
# pools that exist only under kv_quant="int8" (ops/kv_policy.py).
CONTENT_KEYS = ("cached_key_pages", "cached_value_pages")
SCALE_KEYS = ("cached_key_scale_pages", "cached_value_scale_pages")
POOL_LEAF_KEYS = CONTENT_KEYS + SCALE_KEYS

# dtype of the per-(token, head) scales — f32, like every QuantDense /
# QuantEmbed scale in ops/layers.py (the repo's one quant idiom)
SCALE_DTYPE = jnp.float32


def quantize_rows(rows: jnp.ndarray, heads: int):
    """Symmetric int8 quantization of K/V rows at APPEND time: ``rows``
    (b, n, heads * d) float -> (int8 rows (b, n, heads * d), f32 scales
    (b, n, heads)). Per-(token, head) granularity: each appended row
    owns its scale, stored in the parallel paged scale pool, so an
    append is position-local and IDEMPOTENT — re-appending the same row
    (preempt replay, the spec-decode reject-suffix overwrite) reproduces
    byte-identical pool content, which is what keeps every standing
    bitwise parity contract intact under quantization. (A literal
    one-scale-per-page scheme would need requantization as the page
    fills, breaking exactly that idempotence.) The arithmetic mirrors
    utils/quantize.py:quantize_kernel: amax/127 scale, zeros quantize
    with scale 1, round-to-nearest-even, clip to [-127, 127]."""
    b, n, hd = rows.shape
    d = hd // heads
    assert heads * d == hd, (rows.shape, heads)
    r = rows.astype(jnp.float32).reshape(b, n, heads, d)
    amax = jnp.max(jnp.abs(r), axis=-1)  # (b, n, heads)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(SCALE_DTYPE)
    q = jnp.clip(
        jnp.round(r / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q.reshape(b, n, hd), scale


def dequant(view: jnp.ndarray, scales: jnp.ndarray, dtype) -> jnp.ndarray:
    """THE dequantization formula, shared verbatim by the jnp reference
    path (gathered (b, W, h*d) int8 view + gathered (b, W, h) scales —
    ops/ragged_attention.py:reference_attend and the split decode path)
    and semantically by the Pallas kernel's in-register widen (same
    int8->f32 widen, same f32 scale multiply, per page instead of per
    view — ops/ragged_attention.py:_ragged_kernel). int8 values are
    exact in f32 and the scale multiply happens in f32 before the cast
    to the compute ``dtype``, so the formula is deterministic
    elementwise: identical pool bytes always dequantize to identical
    values, the keystone of the quantized bitwise-parity tier."""
    b, W, hd = view.shape
    h = scales.shape[-1]
    d = hd // h
    x = view.astype(jnp.float32).reshape(b, W, h, d) * (
        scales.astype(jnp.float32)[..., None]
    )
    return x.reshape(b, W, hd).astype(dtype)


def gather_variant() -> str:
    """``take`` (default) or ``onehot`` — see the measured comparison in the
    module docstring."""
    v = os.environ.get("DALLE_TPU_PAGED_GATHER", "take")
    if v not in ("take", "onehot"):
        raise ValueError(
            f"DALLE_TPU_PAGED_GATHER must be 'take' or 'onehot', got {v!r}"
        )
    return v


def num_pages(length: int, page_size: int = DEFAULT_PAGE_SIZE) -> int:
    """Pages needed to hold ``length`` tokens (ceil division)."""
    assert page_size > 0, page_size
    return -(-length // page_size)


def alloc(
    batch: int,
    length: int,
    feat: int,
    page_size: int = DEFAULT_PAGE_SIZE,
    dtype=jnp.float32,
) -> jnp.ndarray:
    """A zeroed page pool covering ``length`` tokens:
    (batch, num_pages, page_size, feat)."""
    return jnp.zeros((batch, num_pages(length, page_size), page_size, feat), dtype)


def identity_table(batch: int, n_pages: int) -> jnp.ndarray:
    """(batch, n_pages) page table mapping logical page i of row r to
    GLOBAL physical page ``r * n_pages + i`` — row r's own i-th page in
    the flattened pool view. Identity is the invariant every in-jit user
    keeps (resize_kv rebuilds it to truncate/grow pools and tables in
    lockstep); the serving layer's prefix cache
    (serving/prefix_cache.py) is the one consumer that remaps entries
    across rows."""
    return (
        jnp.arange(batch, dtype=jnp.int32)[:, None] * n_pages
        + jnp.arange(n_pages, dtype=jnp.int32)[None]
    )


def flat_view(pool: jnp.ndarray) -> jnp.ndarray:
    """The (rows * n_pages, page, feat) GLOBAL view of a pool — the id
    space page tables index. A pure reshape (no data movement): physical
    page ``g`` is row ``g // n_pages``'s page ``g % n_pages``."""
    rows, n_p, page, feat = pool.shape
    return pool.reshape(rows * n_p, page, feat)


def append(
    pool: jnp.ndarray,
    table: jnp.ndarray,
    index: jnp.ndarray,
    rows: jnp.ndarray,
    limit: jnp.ndarray = None,
) -> jnp.ndarray:
    """Write ``rows`` (b, n, feat) at per-sequence positions
    ``index`` (b,) .. index+n into the paged ``pool`` (rows, n_pages, page,
    feat) through ``table`` (b, n_pages) holding GLOBAL physical page ids.
    Returns the updated pool. ``pool`` may carry MORE storage rows than the
    table has sequences (the serving engine's prefix-cache arena rides as
    extra rows addressable only through remapped table entries); the
    sequence batch is the TABLE's.

    Positions may cross page boundaries mid-block (a prefill block spans
    ceil(n/page) pages); each row lands in page ``pos // page`` at offset
    ``pos % page``. Out-of-capacity positions are dropped, matching the
    flat path's dynamic_update_slice clamp semantics at the buffer edge
    only in never-read positions (callers guarantee index + n <= capacity).

    ``limit`` (b,) int32, optional: per-sequence VALID row count — rows
    j >= limit[b] are dropped, never written. This is the ragged fused
    iteration's write mask (ops/ragged_attention.py): every cache row
    receives the same padded (b, n, feat) block, but a decode row commits
    one position, a prefill chunk its own width, an idle row nothing.
    """
    n_rows, n_p, page, feat = pool.shape
    l_pages = table.shape[1]
    n = rows.shape[1]
    pos = index[:, None] + jnp.arange(n, dtype=index.dtype)[None, :]  # (b, n)
    logical = pos // page
    off = pos % page
    phys = jnp.take_along_axis(table, jnp.minimum(logical, l_pages - 1), axis=1)
    # drop (not clamp) genuinely out-of-capacity rows
    valid = logical < l_pages
    if limit is not None:
        valid = valid & (
            jnp.arange(n, dtype=jnp.int32)[None, :] < limit[:, None]
        )
    phys = jnp.where(valid, phys, n_rows * n_p)  # OOB sentinel, mode="drop"
    flat = flat_view(pool).at[phys, off].set(rows, mode="drop")
    return flat.reshape(pool.shape)


def reset_rows(pool: jnp.ndarray, rows) -> jnp.ndarray:
    """Zero the page pools of the given SLOT rows — the eviction reset.

    A preempted/completed request's pages must not leak stale K/V into the
    slot's next tenant: the serving engine re-prefills the slot from scratch,
    and prefill only overwrites positions [0, T), so stale rows beyond the
    new request's frontier would otherwise survive under the (zeros-masked)
    attention sweep contract. ``rows`` is an int row index or a sequence of
    them; works on any (b, ...) pool-shaped leaf.

    Refcount discipline (serving/prefix_cache.py): this zeros a row's
    NATIVE storage only. Shared prefix pages live in dedicated ARENA rows
    past the slot rows and are reachable only through remapped table
    entries, so evicting a slot that references refcounted shared pages
    must pair this with ``reset_table_rows`` — dropping the REFERENCE —
    and must never name an arena row here: arena content is owned by the
    prefix index and reclaimed only by its own (refcount == 0) eviction.
    The engine asserts the row bound (``Engine._release_slot``); the
    sibling-bit-parity regression lives in tests/test_prefix_cache.py."""
    return pool.at[jnp.asarray(rows)].set(0)


def reset_table_rows(table: jnp.ndarray, rows) -> jnp.ndarray:
    """Restore the identity mapping (global ids ``r * n_pages + i``) for
    the given batch rows of a page table. Eviction hands the slot's own
    physical pages back as a pristine identity-mapped pool (the invariant
    every in-jit user keeps — see ``identity_table``) and, for a slot
    holding shared prefix pages, DROPS the cross-row references without
    touching the shared storage (the refcount-only half of the eviction;
    see ``reset_rows``). The identity stride is ``table.shape[1]``: the
    pool's page axis must equal the table's logical width (arena capacity
    extends the pool's ROW axis, never its page axis)."""
    b, n_p = table.shape
    r = jnp.atleast_1d(jnp.asarray(rows, dtype=table.dtype))
    ident = r[:, None] * n_p + jnp.arange(n_p, dtype=table.dtype)[None]
    return table.at[r].set(ident)


def copy_pages_across(
    dst_pool: jnp.ndarray, src_pool: jnp.ndarray, src, dst, valid=None
) -> jnp.ndarray:
    """Copy whole physical pages ``src`` (global ids into ``src_pool``'s
    flat view) onto pages ``dst`` of ``dst_pool``, zeroing destination
    rows past ``valid`` (per-page valid row counts; None = all rows).
    One gather + one scatter per call — the prefix cache's primitive for
    publish (slot pages -> arena), copy-on-write (shared terminal page ->
    the diverging slot's native page; same pool both sides, see
    ``copy_pages``) and the split engine's hit restore (batched arena ->
    a private batch-1 prefill cache). Destination rows at or past
    ``valid[i]`` are ZEROED, not preserved: a published terminal page
    must not leak the publisher's image K/V, and a COW'd page must
    satisfy the zeros-past-frontier sweep contract even when the
    destination page held stale content.

    An OUT-OF-RANGE ``dst`` id (>= the destination's page count) DROPS
    that copy entirely (scatter mode="drop") — the padding convention of
    the serving engine's fixed-shape donated copy jit
    (serving/engine.py:_copy_pages_jit): call vectors pad to one static
    length with dst = the sentinel, so every publish/COW/restore shares
    one compile signature. In-range ids behave exactly as before (the
    drop mode only changes what out-of-range writes do)."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    content = flat_view(src_pool)[src]  # (k, page, feat)
    if valid is not None:
        page = src_pool.shape[2]
        keep = (
            jnp.arange(page, dtype=jnp.int32)[None]
            < jnp.asarray(valid, jnp.int32)[:, None]
        )
        content = jnp.where(keep[..., None], content, 0)
    return (
        flat_view(dst_pool).at[dst].set(content, mode="drop")
        .reshape(dst_pool.shape)
    )


def copy_pages(pool: jnp.ndarray, src, dst, valid=None) -> jnp.ndarray:
    """``copy_pages_across`` within one pool — see its docstring."""
    return copy_pages_across(pool, pool, src, dst, valid)


def gather(pool: jnp.ndarray, table: jnp.ndarray, variant=None) -> jnp.ndarray:
    """Assemble the logical cache view (b, l_pages * page, feat) from the
    paged pool through a GLOBAL-id table (b, l_pages) — a table entry may
    name a page in ANY storage row, which is what lets the serving prefix
    cache map one physical page into many sequences' views. The ``take``
    variant is the production path (the row gather fuses into the
    consuming einsum); ``onehot`` is the measured-slower MXU formulation
    kept for TPU re-measurement — numbers in the module docstring."""
    n_rows, n_p, page, feat = pool.shape
    b, l_pages = table.shape
    if variant is None:
        variant = gather_variant()
    flat = flat_view(pool)
    if variant == "onehot":
        oh = jax.nn.one_hot(table, n_rows * n_p, dtype=pool.dtype)
        g = jnp.einsum("blg,gpf->blpf", oh, flat)
    else:
        g = jnp.take(flat, table, axis=0)  # (b, l_pages, page, feat)
    return g.reshape(b, l_pages * page, feat)
