"""Autoregressive sampling for DALL-E, TPU-native.

The reference samples by re-running the full forward pass over the whole
prefix for every generated token (dalle_pytorch.py:481-486) — O(L^2) attention
work per token. Here generation is ONE parallel ``DALLE.prefill_step`` pass
over the text prompt (filling every decode cache with MXU-shaped matmuls)
followed by a single ``lax.scan`` over the KV-cached ``DALLE.decode_step``
for the image positions — each step one (1 x L) attention per layer, the
whole sequence one XLA program. Priming beyond the text prompt is
teacher-forced inside the scan via ``known_len``. Randomness flows through
explicit PRNG keys; top-k fractional-threshold filtering, temperature,
image-token priming (reference dalle_pytorch.py:470-479) and CLIP reranking
(dalle_pytorch.py:503-505) all match the reference semantics.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops import kv_policy, paged_kv
from .dalle import DALLE, top_k_filter

# Cache-window growth granularity for the segmented decode scan below.
# None = batch-adaptive (the decode_tokens default); an int overrides:
# 0 disables segmentation (single full-extent scan), k > 0 grows the K/V
# caches every k positions.
DECODE_WINDOW_SEG = None

# Scan-body unroll for the decode loop (see the segmented-scan comment in
# decode_tokens).
DECODE_UNROLL = 4


def _format_ctx(cache_format: Optional[str]):
    """Pin the KV layout for a traced block when the caller asked for one;
    ``None`` leaves the policy (or an enclosing override) in charge."""
    if cache_format is None:
        return contextlib.nullcontext()
    return kv_policy.format_override(cache_format)


def init_decode_cache(
    dalle: DALLE, params, batch_size: int,
    cache_format: Optional[str] = None, kv_quant: Optional[str] = None,
):
    """Materialize the transformer's KV/shift caches for a batch.

    ``cache_format`` pins the KV layout ("paged" | "flat" | "4d");
    ``kv_quant`` the paged pools' storage quantization ("none" | "int8"
    — int8 content pools plus parallel per-(token, head) scale pools;
    ops/kv_policy.py). None defers each to its policy chain. An invalid
    value for either fails typed at resolution time
    (``InvalidKVFormatError``)."""
    token = jnp.zeros((batch_size,), dtype=jnp.int32)
    quant_ctx = (
        contextlib.nullcontext() if kv_quant is None
        else kv_policy.quant_override(kv_policy.resolve_quant(kv_quant))
    )
    with _format_ctx(cache_format), quant_ctx:
        _, mutated = dalle.apply(
            {"params": params},
            token,
            jnp.array(0, jnp.int32),
            method=DALLE.decode_step,
            mutable=["cache"],
        )
    return mutated["cache"]


def set_decode_offsets(cache, offsets):
    """Place each sequence of a PAGED decode cache at its own offset —
    the continuous-batching entry point (requests at different decode
    positions share one step). Rewrites every per-position index in the
    cache tree: the attention K/V write index (already (b,) for paged)
    and the token-shift ring index (scalar -> (b,)). The flat/4-D formats
    store a scalar index and cannot express ragged offsets — attention
    would broadcast the vector wrongly, so this guards against them.

    The caller owns cache CONTENTS: rows at positions >= offsets[i] must
    be zeros/stale-masked (true after init + per-sequence replay or
    ``merge_decode_caches``)."""
    leaves = jax.tree_util.tree_leaves_with_path(cache)
    leaf_keys = {getattr(p[-1], "key", None) for p, _ in leaves}
    if "cached_key" in leaf_keys:
        raise ValueError(
            "ragged decode offsets need the paged cache format "
            '(init_decode_cache(..., cache_format="paged"))'
        )
    if "gate_index" in leaf_keys:
        raise ValueError(
            "ragged decode offsets are unsupported for gMLP ('mlp') layers: "
            "the spatial-gate history (ops/layers.py:SpatialGatingUnit) "
            "indexes by a scalar absolute position"
        )
    offsets = jnp.asarray(offsets, jnp.int32)
    assert offsets.ndim == 1, f"offsets must be (b,), got {offsets.shape}"
    batches = {
        x.shape[0] for p, x in leaves
        if getattr(p[-1], "key", None) == "cached_key_pages"
    }
    if batches != {offsets.shape[0]}:
        raise ValueError(
            f"offsets length {offsets.shape[0]} != cache batch {sorted(batches)}"
            " — a mismatched vector would broadcast into wrong-position writes"
        )

    def fn(path, x):
        if getattr(path[-1], "key", None) in ("cache_index", "shift_index"):
            return offsets
        return x

    return jax.tree_util.tree_map_with_path(fn, cache)


def merge_decode_caches(caches):
    """Stack per-sequence PAGED decode caches (each batch-1, at its own
    decode offset) into one batched cache — how a continuous-batching
    serving loop admits a newly-prefilled request into a running batch.
    Batched leaves concatenate on axis 0; scalar indices (the token-shift
    ring's) stack into (b,) vectors. Paged-only, and no gMLP layers, for
    the same reasons as ``set_decode_offsets``."""
    for c in caches:
        keys = {
            getattr(p[-1], "key", None)
            for p, _ in jax.tree_util.tree_leaves_with_path(c)
        }
        if "cached_key" in keys:
            raise ValueError("merge_decode_caches requires paged caches")
        if "gate_index" in keys:
            raise ValueError(
                "merge_decode_caches cannot merge gMLP ('mlp') caches: the "
                "spatial-gate history indexes by a scalar absolute position"
            )

    row_offsets = []
    total = 0
    for c in caches:
        row_offsets.append(total)
        total += {
            x.shape[0]
            for p, x in jax.tree_util.tree_leaves_with_path(c)
            if getattr(p[-1], "key", None) == "cached_key_pages"
        }.pop()

    def merge(path, *leaves):
        if leaves[0].ndim == 0:
            return jnp.stack(leaves)
        if getattr(path[-1], "key", None) == "page_table":
            # tables hold GLOBAL ids (row * n_pages + page); each cache's
            # rows land at a new row offset in the merged pool, so its
            # row-local references shift by offset * n_pages
            n_p = leaves[0].shape[1]
            leaves = [
                t + off * n_p for t, off in zip(leaves, row_offsets)
            ]
        return jnp.concatenate(leaves, axis=0)

    return jax.tree_util.tree_map_with_path(merge, *caches)


def insert_decode_cache(batched, sub, slot: int):
    """Write a batch-1 PAGED decode cache into row ``slot`` of a batched
    cache — the fixed-slot admission primitive of the serving engine
    (serving/engine.py): a newly-prefilled request lands in a free slot of
    the running batch without rebuilding the whole cache the way
    ``merge_decode_caches`` does.

    Both trees must be fully vectorized (every per-position index a (b,)
    vector — run ``set_decode_offsets`` on each after init/prefill), so
    every leaf pairs as ``batched[slot] = sub[0]``. Returns the updated
    batched cache; the previous tenant's rows are fully overwritten (K/V
    pools, page table, indices, shift history), which is what makes a slot
    reset = inserting a pristine cache."""
    sub_leaves = jax.tree_util.tree_leaves_with_path(sub)
    keys = {getattr(p[-1], "key", None) for p, _ in sub_leaves}
    if "cached_key" in keys:
        raise ValueError("insert_decode_cache requires paged caches")
    if "gate_index" in keys:
        raise ValueError(
            "insert_decode_cache cannot place gMLP ('mlp') caches: the "
            "spatial-gate history indexes by a scalar absolute position"
        )
    for p, x in sub_leaves:
        if x.ndim == 0 or x.shape[0] != 1:
            raise ValueError(
                f"sub-cache leaf {p} is not batch-1-vectorized "
                f"(shape {getattr(x, 'shape', ())}); run set_decode_offsets "
                "on the prefilled cache first"
            )

    def fn(path, b_leaf, s_leaf):
        row = s_leaf[0]
        if getattr(path[-1], "key", None) == "page_table":
            # global-id rebase: the batch-1 cache's table references its
            # own (only) storage row; at slot ``slot`` those pages live
            # ``slot * n_pages`` further into the batched pool's flat view
            row = row + slot * b_leaf.shape[1]
        return b_leaf.at[slot].set(row)

    return jax.tree_util.tree_map_with_path(fn, batched, sub)


@partial(jax.jit, static_argnums=(0, 5, 8, 9, 10, 11))
def decode_tokens(
    dalle: DALLE,
    params,
    tokens: jnp.ndarray,
    known_len: int,
    key: jax.Array,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
    mask: Optional[jnp.ndarray] = None,
    num_steps: Optional[int] = None,
    prefill_len: int = 0,
    window_seg: Optional[int] = None,
    cache_format: Optional[str] = None,
):
    """Run the decode scan over the internal token buffer.

    tokens: (b, n_internal) int32 — position 0 is <bos>; the first
    ``known_len`` positions are prompt (teacher-forced), the rest are filled by
    sampling. ``known_len`` is traced, so varying prompt/prime lengths reuse
    one compilation. Text positions hold remapped text ids, image positions
    hold un-offset image token ids. Scans ``num_steps`` (default
    n_internal - 1) input positions and returns the completed buffer.

    ``prefill_len`` (static): process that many leading positions in one
    parallel ``DALLE.prefill_step`` pass instead of sequential scan steps —
    callers must guarantee known_len >= prefill_len and prefill_len <=
    text_len_internal (image generation prefills the whole text prompt,
    cutting the sequential steps from n_internal-1 to image_seq_len).
    Note: prefill consumes ONE PRNG split for the whole block where the
    sequential path consumed one per position, so sampled tokens for a given
    key differ between prefill_len settings (logits and caches are
    bit-identical; only the key stream shifts).

    ``window_seg`` (static): cache-window growth granularity for the
    segmented scan — None defers to the ``DECODE_WINDOW_SEG`` module
    override and then the batch-adaptive default below; 0 disables
    segmentation. Passing it explicitly keeps the knob trace-visible
    (a mutated module global is ignored by already-cached jit traces).

    ``cache_format`` (static): the decode KV layout, "paged" | "flat" |
    "4d"; None defers to the batch-size policy (ops/kv_policy.py). Static
    so the format participates in the jit cache key; the override context
    wraps the whole traced body, so every layer's cache declaration sees
    the same pinned format.
    """
    b, n_internal = tokens.shape
    fmt = kv_policy.resolve_format(cache_format, b)
    with kv_policy.format_override(fmt):
        return _decode_tokens_body(
            dalle, params, tokens, known_len, key, filter_thres, temperature,
            mask, num_steps, prefill_len, window_seg,
        )


def _decode_tokens_body(
    dalle, params, tokens, known_len, key, filter_thres, temperature,
    mask, num_steps, prefill_len, window_seg,
):
    b, n_internal = tokens.shape
    steps = n_internal - 1 if num_steps is None else num_steps
    text_len_internal = dalle.text_len_internal
    ext = dalle.num_text_tokens_ext

    cache = init_decode_cache(dalle, params, b)

    # after a full-text-prompt prefill, every sampled position is an image
    # position whose text-vocab logits are masked (NEG_INF fill) — slicing to
    # the live image segment samples the same distribution (masked entries
    # rank below every real logit, so the full-vocab k gives the same
    # threshold) and shrinks the per-token top-k sort from total_tokens to
    # num_image_tokens wide; with the reference's fractional k it often
    # disappears entirely (k >= image vocab => no filtering). Like prefill,
    # this shifts the PRNG consumption (categorical draws over a narrower
    # array), so sampled tokens for a given key differ from the full-vocab
    # path while remaining distributionally identical.
    image_only = prefill_len == text_len_internal
    k_full = max(int((1 - filter_thres) * dalle.total_tokens), 1)

    @jax.named_scope("sample")
    def apply_sample(tokens, key, logits, i, sliced=False):
        """Sample the token at position i+1 from consumed-position-i logits
        (teacher-forced while i+1 < known_len). ``sliced`` marks logits that
        arrive already cut to the image vocab (decode_step image_only)."""
        key, sub = jax.random.split(key)
        filtered = (
            top_k_filter(logits if sliced else logits[:, ext:], k=k_full)
            if image_only
            else top_k_filter(logits, thres=filter_thres)
        )
        sample = jax.random.categorical(sub, filtered / temperature, axis=-1)
        nxt = i + 1
        if not image_only:
            sample = jnp.where(nxt >= text_len_internal, sample - ext, sample)
        prev = jax.lax.dynamic_slice_in_dim(tokens, nxt, 1, axis=1)[:, 0]
        new_val = jnp.where(nxt < known_len, prev, sample).astype(tokens.dtype)
        tokens = jax.lax.dynamic_update_slice(tokens, new_val[:, None], (0, nxt))
        return tokens, key

    start = 0
    if prefill_len > 1:
        logits, mutated = dalle.apply(
            {"params": params, "cache": cache},
            tokens[:, :prefill_len],
            mask,
            method=DALLE.prefill_step,
            mutable=["cache"],
        )
        cache = mutated["cache"]
        tokens, key = apply_sample(tokens, key, logits, prefill_len - 1)
        start = prefill_len

    def step(carry, i):
        cache, tokens, key = carry
        tok_in = jax.lax.dynamic_slice_in_dim(tokens, i, 1, axis=1)[:, 0]
        logits, mutated = dalle.apply(
            {"params": params, "cache": cache},
            tok_in,
            i,
            mask,
            image_only=image_only,
            method=DALLE.decode_step,
            mutable=["cache"],
        )
        tokens, key = apply_sample(tokens, key, logits, i, sliced=image_only)
        return (mutated["cache"], tokens, key), None

    def resize_kv(cache, W):
        """Size every layer's K/V cache to W rows (truncate or zero-pad on
        the position axis). Attention sweeps whatever extent it is handed
        (ops/attention.py:_decode_attend), so a smaller ARRAY — not a
        sliced view, which XLA materializes as a per-step copy (measured
        +0.11 ms/token, v5e int8) — is what makes a short window cheap.
        Paged caches resize at PAGE granularity: pools and page tables
        truncate/grow in lockstep on the page axis (tables are identity
        inside a jitted generation — ops/paged_kv.py:identity_table — so
        surviving entries stay valid and grown entries extend the
        identity). Only the K/V caches resize: the token-shift history is
        already a fixed-size ring (ops/layers.py:PreShiftToken) and the
        gMLP gate history indexes by absolute position at full extent."""
        page = kv_policy.page_size()
        n_p = paged_kv.num_pages(W, page)

        def fn(path, x):
            key = getattr(path[-1], "key", None)
            if key in ("cached_key", "cached_value"):
                if x.shape[1] > W:
                    return x[:, :W]
                if x.shape[1] < W:
                    return jnp.pad(
                        x, [(0, 0), (0, W - x.shape[1])] + [(0, 0)] * (x.ndim - 2)
                    )
            elif key in paged_kv.POOL_LEAF_KEYS:
                # content AND scale pools truncate/grow in lockstep on
                # the page axis (the scale pools are pool-shaped with
                # feat = heads; ops/paged_kv.py)
                if x.shape[1] > n_p:
                    return x[:, :n_p]
                if x.shape[1] < n_p:
                    return jnp.pad(
                        x, [(0, 0), (0, n_p - x.shape[1]), (0, 0), (0, 0)]
                    )
            elif key == "page_table":
                # tables hold GLOBAL ids r * n_pages + i whose stride is
                # the pool's page axis — resizing the pool changes the
                # stride, so the identity is REBUILT, not sliced/extended
                # (identity is the in-jit invariant; ops/paged_kv.py)
                if x.shape[1] != n_p:
                    return paged_kv.identity_table(x.shape[0], n_p).astype(
                        x.dtype
                    )
            return x

        return jax.tree_util.tree_map_with_path(fn, cache)

    # The scan is SEGMENTED by cache extent: step i only ever reads cache
    # rows [0, i+1), so a segment ending at position e runs against K/V
    # caches truncated to ceil128(e) rows instead of the full seq_len —
    # identical attention (rows beyond the frontier are zeros under a False
    # mask column either way) at ~30% less sweep HBM traffic averaged over
    # image generation. Per-segment unrolling amortizes loop overhead in
    # the bandwidth-bound decode (measured ~2% p50 latency on v5e at
    # unroll=4).
    # Adaptive segmentation (measured, v5e-1 flagship, 2026-07): K/V sweep
    # traffic scales with batch while the per-segment overhead
    # (scan-boundary cache pads, extra program) is ~fixed, so frontier-sized
    # caches win whenever sweeps are a large share of the step. Measured
    # ms/token (batch 1) and tokens/sec (batched):
    #   int8 b1: seg 0 = 0.686 vs 0.704-0.709 segmented  -> seg 0
    #   bf16 b1: seg 512 = 0.917, seg 256 = 0.929, seg 0 = 1.219 -> seg 512
    #   int8 b8: seg 512 = 5136 vs 4569 unsegmented (+12%); seg 256/1024
    #            worse (4985/4921); int8 b32: 6381 vs 5644 (+13%) -> seg 512
    # Only quantized single-stream decode prefers no segmentation (int8
    # halves the weight stream, leaving the step latency-bound on the
    # serial op chain where the boundary programs only add overhead).
    seg = window_seg if window_seg is not None else DECODE_WINDOW_SEG
    if seg is None:
        seg = 0 if (b == 1 and getattr(dalle, "serve_quant", False)) else 512
    assert seg >= 0, f"window_seg must be >= 0 (0 disables segmentation), got {seg}"
    n_cache = dalle.text_len_internal + dalle.image_seq_len
    carry = (cache, tokens, key)
    s = start
    while s < steps:
        e = min(steps, (s // seg + 1) * seg) if seg else steps
        if seg:
            W = min(n_cache, -(-e // 128) * 128)
            carry = (resize_kv(carry[0], W), carry[1], carry[2])
        carry, _ = jax.lax.scan(
            step, carry, jnp.arange(s, e, dtype=jnp.int32), unroll=DECODE_UNROLL,
        )
        s = e
    _, tokens, _ = carry
    return tokens


def generate_image_tokens(
    dalle: DALLE,
    params,
    text: jnp.ndarray,
    key: jax.Array,
    *,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
    prime_tokens: Optional[jnp.ndarray] = None,
    mask: Optional[jnp.ndarray] = None,
    window_seg: Optional[int] = None,
    cache_format: Optional[str] = None,
) -> jnp.ndarray:
    """text: (b, text_seq_len) raw ids -> sampled image token ids
    (b, image_seq_len)."""
    b = text.shape[0]
    text = text[:, : dalle.text_seq_len].astype(jnp.int32)
    # remap_text touches no params, so the unbound-module call is safe
    internal_text = dalle.remap_text(text)

    n_internal = dalle.text_len_internal + dalle.image_seq_len
    tokens = jnp.zeros((b, n_internal), dtype=jnp.int32)
    tokens = jax.lax.dynamic_update_slice(tokens, internal_text, (0, 0))

    known_len = dalle.text_len_internal
    if prime_tokens is not None:
        assert prime_tokens.shape[1] < dalle.image_seq_len, (
            "number of priming image tokens must be < image_seq_len"
        )
        tokens = jax.lax.dynamic_update_slice(
            tokens, prime_tokens.astype(jnp.int32), (0, dalle.text_len_internal)
        )
        known_len += int(prime_tokens.shape[1])

    tokens = decode_tokens(
        dalle, params, tokens, known_len, key,
        filter_thres=filter_thres, temperature=temperature, mask=mask,
        prefill_len=dalle.text_len_internal, window_seg=window_seg,
        cache_format=cache_format,
    )
    return tokens[:, dalle.text_len_internal :]


def generate_images(
    dalle: DALLE,
    params,
    vae,
    vae_variables,
    text: jnp.ndarray,
    key: jax.Array,
    *,
    clip=None,
    clip_variables=None,
    mask: Optional[jnp.ndarray] = None,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
    img: Optional[jnp.ndarray] = None,
    num_init_img_tokens: Optional[int] = None,
):
    """Full text -> pixels pipeline (reference generate_images,
    dalle_pytorch.py:451-507): optional image priming with
    ``int(0.4375 * image_seq_len)`` tokens, scan-decode, VAE decode, optional
    CLIP rerank. ``vae`` / ``clip`` are flax modules sharing the reference's
    duck-type (get_codebook_indices / decode; __call__ similarity)."""
    text = text[:, : dalle.text_seq_len]  # rerank sees the same truncated text
    prime = None
    if img is not None:
        indices = vae.apply(vae_variables, img, method=type(vae).get_codebook_indices)
        n_prime = (
            int(0.4375 * dalle.image_seq_len)
            if num_init_img_tokens is None
            else num_init_img_tokens
        )
        prime = indices[:, :n_prime]

    img_seq = generate_image_tokens(
        dalle, params, text, key,
        filter_thres=filter_thres, temperature=temperature,
        prime_tokens=prime, mask=mask,
    )
    images = vae.apply(vae_variables, img_seq, method=type(vae).decode)

    if clip is not None:
        scores = clip.apply(clip_variables, text, images)
        return images, scores
    return images


def generate_texts(
    dalle: DALLE,
    params,
    key: jax.Array,
    prompt_tokens: Optional[jnp.ndarray] = None,
    *,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
    tokenizer=None,
):
    """Text-only completion (reference generate_texts,
    dalle_pytorch.py:403-449): start from <bos> (plus an optional encoded
    prompt) and sample out to text_seq_len tokens. Returns (tokens, texts) —
    texts only when a tokenizer with pad-aware decode is supplied."""
    if prompt_tokens is None:
        prompt_tokens = jnp.zeros((1, 1), dtype=jnp.int32)
    b, p = prompt_tokens.shape

    tokens = jnp.zeros((b, dalle.text_len_internal + dalle.image_seq_len), jnp.int32)
    tokens = jax.lax.dynamic_update_slice(tokens, prompt_tokens.astype(jnp.int32), (0, 0))

    tokens = decode_tokens(
        dalle, params, tokens, p, key,
        filter_thres=filter_thres, temperature=temperature,
        num_steps=dalle.text_seq_len - 1,
    )
    text_tokens = tokens[:, : dalle.text_seq_len]

    if tokenizer is None:
        return text_tokens, None
    pad_tokens = set(
        range(dalle.num_text_tokens_ext - dalle.text_seq_len, dalle.num_text_tokens_ext)
    )
    texts = [
        tokenizer.decode([int(t) for t in row], pad_tokens=pad_tokens)
        for row in text_tokens
    ]
    return text_tokens, texts
