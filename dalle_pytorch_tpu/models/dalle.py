"""DALL-E: joint text->image autoregressive transformer, TPU-native.

Capability parity with the reference's ``DALLE`` (dalle_pytorch.py:309-585):
per-position unique padding tokens, <bos> prepend, text/image embedding concat,
static text-vs-image logits mask, and the weighted split cross-entropy loss —
rebuilt as a functional flax module:

- the model consumes **image token ids**, not raw pixels: VAE encode is a
  frozen no-grad lookup in the reference (dalle_pytorch.py:533-540) and lives
  outside the trained graph here (trainers call ``vae.get_codebook_indices``
  under ``stop_gradient`` and feed tokens), so the VAE is never entangled in
  the DALLE parameter pytree;
- the logits mask is a static numpy constant baked at trace time
  (reference registers a buffer, dalle_pytorch.py:388-399);
- ``decode_step`` runs one token through the KV-cached transformer for
  O(seq) per-token sampling — the reference re-runs the full prefix per token
  (dalle_pytorch.py:481-486).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from ..ops.layers import AxialPositionalEmbedding, divide_max
from .transformer import Transformer

Dtype = Any

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def top_k_filter(
    logits: jnp.ndarray, thres: float = 0.5, k: Optional[int] = None
) -> jnp.ndarray:
    """Keep the top ``max(int((1-thres)*vocab), 1)`` logits, fill the rest with
    -inf (reference top_k, dalle_pytorch.py:50-56).

    ``k`` overrides the fraction-derived count — callers that pre-slice the
    logits to a live vocab segment pass the FULL-vocab-derived k so the
    threshold matches the reference exactly; k >= width means no filtering
    (and skips the top-k sort entirely)."""
    num_logits = logits.shape[-1]
    if k is None:
        k = max(int((1 - thres) * num_logits), 1)
    if k >= num_logits:
        return logits
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


class DALLE(nn.Module):
    """Text+image autoregressive LM over a mixed discrete vocabulary.

    ``num_text_tokens`` is the raw text vocab; internally it is extended by
    ``text_seq_len`` per-position padding ids (reference dalle_pytorch.py:338).
    """

    dim: int
    depth: int
    num_text_tokens: int = 10000
    text_seq_len: int = 256
    num_image_tokens: int = 512
    image_fmap_size: int = 32
    heads: int = 8
    dim_head: int = 64
    reversible: bool = False
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    attn_types: Optional[Tuple[str, ...]] = None
    loss_img_weight: float = 7.0
    stable: bool = False
    shift_tokens: bool = True
    # extra token-shift ring rows (speculative-decode rollback slack; see
    # ops/layers.py:PreShiftToken.pad) — cache-shape only, parameters are
    # identical at every value, so a serving engine may clone the model
    # with a wider ring without touching the checkpoint
    shift_pad: int = 0
    rotary_emb: bool = True
    remat: bool = False
    sparse_layout_seed: int = 0
    use_flash: bool = True
    sp_axis: Optional[str] = None
    pp_axis: Optional[str] = None
    pp_microbatches: int = 4
    ff_experts: int = 0
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    serve_quant: bool = False
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    # ------------------------------------------------------------ derived

    @property
    def image_seq_len(self) -> int:
        return self.image_fmap_size**2

    @property
    def num_text_tokens_ext(self) -> int:
        return self.num_text_tokens + self.text_seq_len

    @property
    def total_tokens(self) -> int:
        return self.num_text_tokens_ext + self.num_image_tokens

    @property
    def total_seq_len(self) -> int:
        """Transformer input length (last token never fed, reference
        dalle_pytorch.py:554-556)."""
        return self.text_seq_len + self.image_seq_len

    @property
    def text_len_internal(self) -> int:
        """Text positions including <bos>."""
        return self.text_seq_len + 1

    def logits_mask_np(self) -> np.ndarray:
        """(total_seq_len, total_tokens) bool, True = FORBIDDEN: text positions
        may only predict text tokens, image positions image tokens (reference
        dalle_pytorch.py:388-399)."""
        seq = np.arange(self.total_seq_len)[:, None]
        logit = np.arange(self.total_tokens)[None, :]
        return ((seq >= self.text_seq_len) & (logit < self.num_text_tokens_ext)) | (
            (seq < self.text_seq_len) & (logit >= self.num_text_tokens_ext)
        )

    # -------------------------------------------------------------- setup

    def setup(self):
        from ..ops.layers import serving_embed

        self.text_emb = serving_embed(
            self.serve_quant, self.num_text_tokens_ext, self.dim,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )
        self.image_emb = serving_embed(
            self.serve_quant, self.num_image_tokens, self.dim,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )
        if not self.rotary_emb:
            self.text_pos_emb = nn.Embed(
                self.text_len_internal, self.dim, param_dtype=self.param_dtype
            )
            self.image_pos_emb = AxialPositionalEmbedding(
                dim=self.dim,
                shape=(self.image_fmap_size, self.image_fmap_size),
                param_dtype=self.param_dtype,
            )

        self.transformer = Transformer(
            dim=self.dim,
            depth=self.depth,
            seq_len=self.total_seq_len,
            reversible=self.reversible,
            causal=True,
            heads=self.heads,
            dim_head=self.dim_head,
            attn_dropout=self.attn_dropout,
            ff_dropout=self.ff_dropout,
            attn_types=self.attn_types,
            image_fmap_size=self.image_fmap_size,
            stable=self.stable,
            shift_tokens=self.shift_tokens,
            shift_pad=self.shift_pad,
            rotary_emb=self.rotary_emb,
            remat=self.remat,
            sparse_layout_seed=self.sparse_layout_seed,
            use_flash=self.use_flash,
            sp_axis=self.sp_axis,
            pp_axis=self.pp_axis,
            pp_microbatches=self.pp_microbatches,
            ff_experts=self.ff_experts,
            moe_every=self.moe_every,
            moe_capacity_factor=self.moe_capacity_factor,
            quant=self.serve_quant,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        self.final_norm = nn.LayerNorm(dtype=jnp.float32, param_dtype=self.param_dtype)
        # the vocab projection runs in compute dtype — in f32 this one matmul
        # (n x dim x ~18k vocab) would cost more MXU time than a whole layer;
        # the loss upcasts the logits to f32 before log_softmax
        from ..ops.layers import serving_dense

        self.to_logits = serving_dense(
            self.serve_quant, self.total_tokens,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )

    # ------------------------------------------------------------- helpers

    def remap_text(self, text: jnp.ndarray) -> jnp.ndarray:
        """Give each padding-0 text position its own unique token id and
        prepend <bos>=0 (reference dalle_pytorch.py:521-526)."""
        text_range = jnp.arange(self.text_seq_len, dtype=text.dtype) + (
            self.num_text_tokens_ext - self.text_seq_len
        )
        text = jnp.where(text == 0, text_range, text)
        return jnp.pad(text, ((0, 0), (1, 0)))  # <bos> = 0

    def _full_key_mask(self, mask: Optional[jnp.ndarray], n: int) -> Optional[jnp.ndarray]:
        """Text padding mask (b, text_seq_len) -> (b, n) key mask over the
        internal [bos, text, image] sequence."""
        if mask is None:
            return None
        b = mask.shape[0]
        bos = jnp.ones((b, 1), dtype=bool)
        img = jnp.ones((b, self.image_seq_len), dtype=bool)
        return jnp.concatenate((bos, mask, img), axis=1)[:, :n]

    @jax.named_scope("head_loss")
    def _head(self, out: jnp.ndarray) -> jnp.ndarray:
        if self.stable:
            out = divide_max(out)
        return self.to_logits(self.final_norm(out)).astype(jnp.float32)

    @jax.named_scope("head_loss")
    def _head_image(self, out: jnp.ndarray) -> jnp.ndarray:
        """Image-vocab-only head: the ``[ext:]`` column slice of the
        ``to_logits`` matvec, for decode steps that can only emit image
        tokens (every post-prefill step of image generation). Streams ~55%
        fewer head-weight bytes per token than the full head. The slice
        starts at the 128-aligned column below ``ext`` so the (int8 or bf16)
        kernel read stays tile-aligned; the few extra text columns are
        dropped from the result. The dequant/matvec arithmetic itself lives
        in ``dense_apply_columns`` (ops/layers.py), the one shared contract
        with QuantDense — this sliced head cannot diverge from the full
        head's math."""
        from ..ops.layers import dense_apply_columns

        if self.stable:
            out = divide_max(out)
        normed = self.final_norm(out)
        if self.is_initializing():
            self.to_logits(normed[:, :1])  # materialize the head params
        p = self.variables["params"]["to_logits"]
        ext = self.num_text_tokens_ext
        lo = (ext // 128) * 128
        logits = dense_apply_columns(p, normed, lo, self.dtype)
        return logits[..., ext - lo :].astype(jnp.float32)

    # ------------------------------------------------------------- forward

    def __call__(
        self,
        text: jnp.ndarray,
        image: Optional[jnp.ndarray] = None,
        mask: Optional[jnp.ndarray] = None,
        return_loss: bool = False,
        deterministic: bool = True,
    ):
        """text: (b, text_seq_len) int ids; image: (b, <=image_seq_len) token
        ids in [0, num_image_tokens). Returns logits (b, n, total_tokens) or
        the weighted CE loss (reference dalle_pytorch.py:509-585)."""
        assert text.shape[-1] == self.text_seq_len, (
            f"text length {text.shape[-1]} != text_seq_len {self.text_seq_len}"
        )
        with jax.named_scope("embed"):
            text = self.remap_text(text)
            tokens = self.text_emb(text)
            if not self.rotary_emb:
                tokens = tokens + self.text_pos_emb(jnp.arange(self.text_len_internal))[None]

            if image is not None and image.shape[1] > 0:
                image_tokens = self.image_emb(image)
                if not self.rotary_emb:
                    image_tokens = image_tokens + self.image_pos_emb(
                        image_tokens.shape[1]
                    ).astype(image_tokens.dtype)
                tokens = jnp.concatenate((tokens, image_tokens), axis=1)

            # drop the trailing token: it never predicts anything
            if tokens.shape[1] > self.total_seq_len:
                tokens = tokens[:, : self.total_seq_len]
            n = tokens.shape[1]

            x = tokens.astype(self.dtype)
        if self.sp_axis is not None and not self.is_initializing():
            from ..parallel.context import constrain_seq_sharded

            x = constrain_seq_sharded(x, self.sp_axis, seq_dim=1)
        out = self.transformer(
            x,
            mask=self._full_key_mask(mask, n),
            deterministic=deterministic,
        )
        if return_loss:
            if self.serve_quant:
                raise ValueError(
                    "serve_quant is an inference-only mode (int8 kernels receive "
                    "no meaningful gradients); train with serve_quant=False and "
                    "quantize the checkpoint via utils/quantize.py"
                )
            assert image is not None, "when training, image tokens must be supplied"
            assert image.shape[1] == self.image_seq_len, (
                f"the loss needs the full image sequence, got {image.shape[1]} of "
                f"{self.image_seq_len} tokens"
            )
        with jax.named_scope("head_loss"):
            if self.stable:
                out = divide_max(out)
            normed = self.final_norm(out)
            if return_loss:
                return self._split_head_loss(normed, text, image)
            logits = self.to_logits(normed)  # compute dtype
            lmask = jnp.asarray(self.logits_mask_np()[:n])[None]
            return jnp.where(lmask, NEG_INF, logits.astype(jnp.float32))

    def _split_head_loss(self, normed, text, image):
        """Weighted split CE with a block-diagonal head.

        The logits mask is block-diagonal — text positions may only predict
        text-vocab tokens, image positions image-vocab tokens (reference
        dalle_pytorch.py:388-399) — so masked logits have softmax probability
        0 and gradient 0. Computing only the live blocks of the ``to_logits``
        matmul is therefore EXACTLY the reference's masked cross-entropy
        (same loss, same gradients) at under half the head FLOPs: n x vocab
        becomes text_seq x text_vocab + image_seq x image_vocab. The CE uses
        logsumexp directly so no (b, n, vocab) f32 log-prob array is ever
        materialized (the f32 cast fuses into the reduction).
        """
        if self.is_initializing():
            self.to_logits(normed[:, :1])  # materialize the head params
        p = self.variables["params"]["to_logits"]
        W = jnp.asarray(p["kernel"], self.dtype)
        b_ = jnp.asarray(p["bias"], self.dtype)
        ext = self.num_text_tokens_ext
        tl = self.text_seq_len
        h = normed.astype(self.dtype)

        def segment_ll(hidden, cols, labels):
            logits = hidden @ W[:, cols] + b_[cols]
            lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
            picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
            return picked.astype(jnp.float32) - lse

        ll_text = segment_ll(h[:, :tl], slice(None, ext), text[:, 1:])
        ll_img = segment_ll(h[:, tl:], slice(ext, None), image)
        loss_text = -ll_text.mean()
        loss_img = -ll_img.mean()
        return (loss_text + self.loss_img_weight * loss_img) / (self.loss_img_weight + 1)

    # --------------------------------------------------------------- decode

    def prefill_step(
        self,
        tokens: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        image_only: bool = False,
    ) -> jnp.ndarray:
        """Process the first T text positions in ONE parallel pass, filling
        every decode cache (K/V, token-shift, gMLP gate), and return
        (b, total_tokens) logits predicting position T.

        The reference decodes the whole prompt token-by-token inside its
        sampling loop (dalle_pytorch.py:481-486); a parallel prefill removes
        those T sequential steps and runs MXU-shaped matmuls instead.
        tokens: (b, T) REMAPPED text ids (bos included), T <= text_len_internal
        static; equivalent to T sequential ``decode_step`` calls.

        ``image_only`` (static) requires the block to cover the WHOLE
        prompt (T == text_len_internal): position T is then the first
        image position, whose logits-mask row permits exactly the image
        vocab, so only the image-vocab head columns are computed
        (``_head_image`` — the same measured serving optimization as
        ``decode_step``'s flag, bit-equal to the full head's ``[ext:]``
        slice) and (b, num_image_tokens) logits return with no mask/where
        chain.
        """
        b, T = tokens.shape
        assert T <= self.text_len_internal, (
            f"prefill covers text positions only, got {T} > {self.text_len_internal}"
        )
        with jax.named_scope("embed"):
            emb = self.text_emb(tokens)
            if not self.rotary_emb:
                emb = emb + self.text_pos_emb(jnp.arange(T))[None]

        out = self.transformer(
            emb.astype(self.dtype),
            mask=self._full_key_mask(mask, self.text_len_internal + self.image_seq_len),
            deterministic=True,
            decode=True,
        )
        if image_only:
            assert T == self.text_len_internal, (
                "image_only prefill requires the full prompt: position T "
                "must be the first image position"
            )
            return self._head_image(out[:, -1:])[:, 0]
        logits = self._head(out[:, -1:])[:, 0]
        mask_row = jnp.asarray(self.logits_mask_np())[T - 1 : T]
        return jnp.where(mask_row, NEG_INF, logits)

    def prefill_chunk(
        self,
        tokens: jnp.ndarray,
        start: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        return_logits: bool = True,
        image_only: bool = False,
    ):
        """Process text positions [start, start + c) of the prompt against
        the ALREADY-WRITTEN decode-cache prefix — one budget-bounded slice
        of a prefill, so a serving loop can interleave prompt processing
        with decode iterations instead of stalling every active slot for
        the whole monolithic ``prefill_step``.

        tokens: (b, c) REMAPPED text ids (bos included) for positions
        start..start+c; ``start`` is traced, so every chunk of one width
        shares a compilation (widths: the configured chunk size plus at
        most two ragged tail widths). The attention math is exactly the
        shared block path — ``ops/attention.py:cache_block_attend`` over
        the ``paged_kv.gather`` view of the page tables, with the chunk's
        per-position pattern-mask rows selecting the cache prefix plus the
        in-chunk causal block — so a sequence of ``prefill_chunk`` calls
        covering [0, T) produces a cache BIT-identical to one
        ``prefill_step`` over the same tokens, provided no chunk is a
        batch-1 single token (its PROJECTION matmuls would run as M=1
        matvecs accumulating ~1 ulp differently; the attention core
        itself pads width-1 blocks — ``cache_block_attend``). Pinned by
        tests/test_chunked_prefill.

        Returns (b, total_tokens) logits predicting position start + c
        when ``return_logits`` (the final chunk of a prompt samples the
        first image token from them, matching ``prefill_step``'s head
        row), else None — intermediate chunks skip the head entirely.
        ``image_only`` (static; implies return_logits) requires the chunk
        to END the prompt (start + c == T, unassertable on the traced
        start — callers guarantee it) and computes only the image-vocab
        head columns, exactly like ``prefill_step``'s flag.
        """
        b, c = tokens.shape
        assert c <= self.text_len_internal, (
            f"prefill chunks cover text positions only, got {c} > "
            f"{self.text_len_internal}"
        )
        start = jnp.asarray(start, jnp.int32)
        with jax.named_scope("embed"):
            emb = self.text_emb(tokens)
            if not self.rotary_emb:
                emb = emb + self.text_pos_emb(start + jnp.arange(c))[None]

        out = self.transformer(
            emb.astype(self.dtype),
            mask=self._full_key_mask(mask, self.text_len_internal + self.image_seq_len),
            deterministic=True,
            decode=True,
        )
        if image_only:
            return self._head_image(out[:, -1:])[:, 0]
        if not return_logits:
            return None
        logits = self._head(out[:, -1:])[:, 0]
        lm = jnp.asarray(self.logits_mask_np())
        mask_row = jax.lax.dynamic_slice_in_dim(lm, start + c - 1, 1, axis=0)
        return jnp.where(mask_row, NEG_INF, logits)

    def fused_step(
        self,
        tokens: jnp.ndarray,
        start: jnp.ndarray,
        length: jnp.ndarray,
        final: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        rowwise_head: bool = True,
        all_logits: bool = False,
        depth_limit: Optional[int] = None,
    ) -> jnp.ndarray:
        """One RAGGED block step: a whole mixed prefill+decode serving
        iteration through the transformer in ONE program ("Ragged Paged
        Attention", PAPERS.md; ops/ragged_attention.py).

        tokens: (b, W) per-row token blocks padded to the fixed iteration
        width W — row b's valid tokens are columns [0, length[b]) at
        internal positions start[b] + j. A decode row carries 1 token (an
        image token at its decode position), a prefill-chunk row up to W
        REMAPPED text ids, an idle row nothing (length 0). Raggedness is
        DATA: every (start, length, final) mix shares this one trace, so
        a serving iteration is a single device dispatch with a single
        steady-state compile signature (serving/engine.py:_iteration_jit).

        ``final``: (b,) bool, True for rows whose sampled token the
        caller will CONSUME as a prefill's first image token (the
        final-chunk rows). It selects the head's accumulation shape, not
        its math: the split engine computes decode logits at batch width
        (an M=b gemm) but a prefill's first-token logits in a batch-1
        program whose M=1 head matvec accumulates ~1 ulp differently —
        so this step computes BOTH (the gemm head plus b per-row M=1
        heads) and selects per row, keeping fused output BITWISE equal
        to the split engine for every row kind (pinned by
        tests/test_ragged_attention). ``rowwise_head`` (STATIC) skips
        the per-row heads when the caller knows no row is final — the
        steady-state decode mix, where paying b extra head-weight matvec
        streams every iteration would erode the fusion's dispatch win;
        the engine passes ``bool(final.any())`` computed host-side, so
        this is one extra (warm, never in-trace) compile signature, not
        a per-mix recompile.

        Returns (b, num_image_tokens) image-only logits at each row's
        last valid position (garbage for idle/non-final intermediate
        rows — the engine discards them by kind). Requires the paged
        cache format and no gMLP layers, like every ragged-offset path.

        Speculative decoding (serving/engine.py) adds two STATIC knobs:

        ``all_logits`` returns (b, W, num_image_tokens) logits at EVERY
        block column — the k-token VERIFY head: a verify row's column j
        predicts position start + j + 1, so one ragged dispatch yields
        the target distribution for all k drafted positions. The head is
        one M=(b*W) gemm whose per-row results are bitwise equal to the
        M=b last-column gemm on the f32 parity tier (row-independent dot
        accumulation — the same cross-shape contract that makes
        fused == split); ``rowwise_head`` still overlays the per-row M=1
        head at final-chunk rows' last valid column, so a prefill
        completing inside a speculative iteration keeps split-path
        bit-parity for its first-token logits.

        ``depth_limit`` runs only the first L layers — the early-exit
        self-draft pass (the final norm + head apply to layer L's
        output). Draft quality is whatever the truncated stack gives;
        correctness never depends on it (exact acceptance re-derives
        every token from the full-depth verify logits).

        The block is ANCHORED at the descriptor ``start`` (attention
        write base, rotary/mask rows, shift-ring reads all derive from
        it rather than the stored cache indices), which is what lets a
        speculative rollback be pure descriptor arithmetic: a rejected
        suffix is simply overwritten by the next block dispatched at the
        accepted frontier. For non-speculative callers the stored
        indices equal ``start`` and the anchored arithmetic is
        value-identical.
        """
        b, n = tokens.shape
        assert "mlp" not in tuple(self.attn_types or ("full",)), (
            "fused_step cannot run gMLP layers (scalar-position gate history)"
        )
        pos = start[:, None] + jnp.arange(n, dtype=jnp.int32)[None]  # (b, n)
        is_text = pos < self.text_len_internal

        with jax.named_scope("embed"):
            text_tok = jnp.clip(tokens, 0, self.num_text_tokens_ext - 1)
            img_tok = jnp.clip(tokens, 0, self.num_image_tokens - 1)
            emb = jnp.where(
                is_text[..., None], self.text_emb(text_tok), self.image_emb(img_tok)
            )
            if not self.rotary_emb:
                tpos = jnp.clip(pos, 0, self.text_len_internal - 1)
                ipos = jnp.clip(
                    pos - self.text_len_internal, 0, self.image_seq_len - 1
                )
                img_grid = self.image_pos_emb(self.image_seq_len)
                pe = jnp.where(
                    is_text[..., None],
                    self.text_pos_emb(tpos),
                    jnp.take(img_grid[0], ipos, axis=0),
                )
                emb = emb + pe.astype(emb.dtype)

        out = self.transformer(
            emb.astype(self.dtype),
            mask=self._full_key_mask(
                mask, self.text_len_internal + self.image_seq_len
            ),
            deterministic=True,
            decode=True,
            block_len=length,
            block_start=start,
            depth_limit=depth_limit,
        )
        last = jnp.clip(length - 1, 0, n - 1)
        h_last = jnp.take_along_axis(
            out, last[:, None, None], axis=1
        )  # (b, 1, dim)
        if all_logits:
            # the k-token verify head: logits at EVERY column, one
            # M=(b*W) gemm; final rows' last valid column is overlaid
            # with the per-row M=1 split-parity head below
            cols = self._head_image(out)  # (b, W, V_img)
            if rowwise_head:
                rowwise = jnp.concatenate(
                    [self._head_image(h_last[i:i + 1]) for i in range(b)],
                    axis=0,
                )[:, 0]  # per-row M=1 — the split prefill head
                sel = final[:, None] & (
                    jnp.arange(n, dtype=jnp.int32)[None] == last[:, None]
                )
                cols = jnp.where(sel[..., None], rowwise[:, None, :], cols)
            return cols
        batched = self._head_image(h_last)[:, 0]  # (b, V_img), M=b gemm
        if b == 1 or not rowwise_head:
            return batched
        rowwise = jnp.concatenate(
            [self._head_image(h_last[i:i + 1]) for i in range(b)], axis=0
        )[:, 0]  # per-row M=1 — the split prefill head's accumulation
        return jnp.where(final[:, None], rowwise, batched)

    def decode_step(
        self,
        token: jnp.ndarray,
        pos: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        image_only: bool = False,
    ) -> jnp.ndarray:
        """One KV-cached decode step.

        token: (b,) id of the token at internal position ``pos`` — a remapped
        text id (bos included) when pos < text_len_internal, otherwise an
        un-offset image token id. Returns (b, total_tokens) logits predicting
        position pos+1. The transformer's cache collections must be mutable.
        The supplied K/V caches may be narrower than the full sequence (the
        segmented decode scan sizes them to the generation frontier,
        models/sampling.py) — every layer sweeps whatever extent it is
        handed (Attention._decode_attend).

        ``pos`` may be a SCALAR (the whole batch at one position — the
        decode scan) or a (b,) VECTOR of per-sequence positions (ragged
        decode offsets / continuous batching). The vector form requires a
        paged cache (per-sequence write indices, ops/attention.py); with
        learned positional tables (``rotary_emb=False``) the per-position
        embedding lookup becomes a row gather over the (b,) positions.

        ``image_only`` (static) asserts pos + 1 is an image position and
        computes only the image-vocab slice of the head, returning
        (b, num_image_tokens) logits — exactly the full head's ``[ext:]``
        slice, since image rows of the logits mask permit the whole image
        vocab (``logits_mask_np``). Measured on v5e int8 serving this is
        ~100 us/token: it removes the text-vocab head matvec columns AND
        the full-vocab (b, 18k) f32 mask/where/slice chain from the serial
        per-step op sequence.
        """
        b = token.shape[0]
        ragged = jnp.ndim(pos) == 1
        is_text = pos < self.text_len_internal

        with jax.named_scope("embed"):
            text_tok = jnp.clip(token, 0, self.num_text_tokens_ext - 1)
            img_tok = jnp.clip(token, 0, self.num_image_tokens - 1)
            emb = jnp.where(
                is_text[:, None] if ragged else is_text,
                self.text_emb(text_tok), self.image_emb(img_tok),
            )
            if not self.rotary_emb:
                tpos = jnp.clip(pos, 0, self.text_len_internal - 1)
                ipos = jnp.clip(pos - self.text_len_internal, 0, self.image_seq_len - 1)
                img_grid = self.image_pos_emb(self.image_seq_len)
                if ragged:
                    # per-sequence positions (continuous batching): the learned
                    # tables become row gathers — (b,) indices -> (b, dim)
                    pe = jnp.where(
                        is_text[:, None],
                        self.text_pos_emb(tpos),
                        jnp.take(img_grid[0], ipos, axis=0),
                    )
                else:
                    pe = jnp.where(
                        is_text,
                        self.text_pos_emb(tpos)[None],
                        jax.lax.dynamic_slice_in_dim(img_grid[0], ipos, 1, axis=0),
                    )
                emb = emb + pe.astype(emb.dtype)

        x = emb[:, None, :].astype(self.dtype)
        out = self.transformer(
            x, mask=self._full_key_mask(mask, self.text_len_internal + self.image_seq_len),
            deterministic=True, decode=True,
        )
        if image_only:
            return self._head_image(out)[:, 0]
        logits = self._head(out)[:, 0]
        lm = jnp.asarray(self.logits_mask_np())
        p = jnp.minimum(pos, self.total_seq_len - 1)
        mask_row = lm[p] if ragged else jax.lax.dynamic_slice_in_dim(lm, p, 1, axis=0)
        return jnp.where(mask_row, NEG_INF, logits)
