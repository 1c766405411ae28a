"""Paged KV-cache tests: page-level append/gather semantics, paged vs
flat vs 4-D decode parity (the cache format may only change storage, never
sampled tokens), frontier-windowed paged decode, and ragged decode offsets
(continuous batching) pinned bit-exact against per-sequence decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import (
    DALLE,
    generate_image_tokens,
    init_decode_cache,
    merge_decode_caches,
    set_decode_offsets,
)
from dalle_pytorch_tpu.ops import kv_policy, paged_kv


def small_dalle(**kw):
    defaults = dict(
        dim=32,
        depth=2,
        num_text_tokens=16,
        text_seq_len=4,
        num_image_tokens=12,
        image_fmap_size=2,
        heads=2,
        dim_head=8,
        attn_types=("full", "axial_row"),
        shift_tokens=True,
        rotary_emb=True,
    )
    defaults.update(kw)
    return DALLE(**defaults)


def dalle_inputs(dalle, b=2, seed=0):
    rng = np.random.RandomState(seed)
    text = jnp.asarray(
        rng.randint(1, dalle.num_text_tokens, size=(b, dalle.text_seq_len)), jnp.int32
    )
    image = jnp.asarray(
        rng.randint(0, dalle.num_image_tokens, size=(b, dalle.image_seq_len)), jnp.int32
    )
    return text, image


# ------------------------------------------------------------- page ops


class TestPageOps:
    def test_append_gather_roundtrip_across_page_boundary(self):
        """A block written at an offset that straddles page boundaries must
        read back exactly, with untouched positions still zero."""
        b, L, f, page = 2, 10, 3, 4
        pool = paged_kv.alloc(b, L, f, page)
        assert pool.shape == (b, 3, page, f)
        table = paged_kv.identity_table(b, 3)

        rng = np.random.RandomState(0)
        rows = jnp.asarray(rng.rand(b, 5, f), jnp.float32)
        start = jnp.asarray([3, 3], jnp.int32)  # rows span pages 0, 1 and 2
        pool = paged_kv.append(pool, table, start, rows)

        flat = np.asarray(paged_kv.gather(pool, table))
        expect = np.zeros((b, 3 * page, f), np.float32)
        expect[:, 3:8] = np.asarray(rows)
        np.testing.assert_array_equal(flat, expect)

    def test_append_exactly_at_page_boundary(self):
        b, f, page = 1, 2, 4
        pool = paged_kv.alloc(b, 8, f, page)
        table = paged_kv.identity_table(b, 2)
        row = jnp.ones((b, 1, f))
        pool = paged_kv.append(pool, table, jnp.asarray([4], jnp.int32), row)
        flat = np.asarray(paged_kv.gather(pool, table))
        assert flat[0, 4].sum() == f  # first row of page 1
        assert flat[0, :4].sum() == 0 and flat[0, 5:].sum() == 0

    def test_append_per_sequence_offsets(self):
        """Each sequence writes at its OWN index — the ragged-offsets core."""
        b, f, page = 3, 2, 4
        pool = paged_kv.alloc(b, 12, f, page)
        table = paged_kv.identity_table(b, 3)
        rows = jnp.arange(b * f, dtype=jnp.float32).reshape(b, 1, f) + 1
        idx = jnp.asarray([0, 5, 11], jnp.int32)
        flat = np.asarray(paged_kv.gather(paged_kv.append(pool, table, idx, rows), table))
        for i, p in enumerate([0, 5, 11]):
            np.testing.assert_array_equal(flat[i, p], np.asarray(rows)[i, 0])
            assert np.delete(flat[i], p, axis=0).sum() == 0

    def test_out_of_capacity_rows_are_dropped(self):
        b, f, page = 1, 2, 4
        pool = paged_kv.alloc(b, 4, f, page)
        table = paged_kv.identity_table(b, 1)
        rows = jnp.ones((b, 2, f))
        pool = paged_kv.append(pool, table, jnp.asarray([3], jnp.int32), rows)
        flat = np.asarray(paged_kv.gather(pool, table))
        assert flat[0, 3].sum() == f  # in-capacity row landed
        assert flat[0, :3].sum() == 0  # the overflow row vanished, no wrap

    def test_reset_rows_and_tables(self):
        """Eviction reset (serving engine): the victim's rows go back to
        pristine — zero pages, identity table (GLOBAL ids r * n_pages + i)
        — with other rows untouched."""
        rng = np.random.RandomState(2)
        pool = jnp.asarray(rng.rand(3, 2, 4, 2), jnp.float32)
        table = jnp.asarray([[1, 0], [0, 1], [1, 0]], jnp.int32)
        pool2 = paged_kv.reset_rows(pool, 1)
        assert np.asarray(pool2)[1].sum() == 0
        np.testing.assert_array_equal(np.asarray(pool2)[[0, 2]], np.asarray(pool)[[0, 2]])
        table2 = paged_kv.reset_table_rows(table, [0, 2])
        np.testing.assert_array_equal(
            np.asarray(table2), [[0, 1], [0, 1], [4, 5]]
        )

    def test_identity_table_is_global(self):
        """identity_table row r maps logical page i to GLOBAL physical
        page r * n_pages + i — the flattened-view id space that lets a
        table entry reference another row's storage (prefix sharing)."""
        t = np.asarray(paged_kv.identity_table(3, 2))
        np.testing.assert_array_equal(t, [[0, 1], [2, 3], [4, 5]])

    def test_cross_row_gather_and_append(self):
        """A table entry naming another row's physical page reads (and
        writes through to) that row's storage — the prefix-sharing seam."""
        b, f, page = 2, 2, 4
        pool = paged_kv.alloc(b, 8, f, page)  # (2, 2, 4, 2); global ids 0..3
        table = paged_kv.identity_table(b, 2)
        rows = jnp.full((b, 1, f), 7.0)
        pool = paged_kv.append(
            pool, table, jnp.asarray([0, 0], jnp.int32), rows
        )
        # remap row 1's logical page 0 onto row 0's physical page 0
        shared = table.at[1, 0].set(0)
        flat = np.asarray(paged_kv.gather(pool, shared))
        np.testing.assert_array_equal(flat[1, 0], flat[0, 0])
        # a write through the shared entry lands in row 0's storage
        pool2 = paged_kv.append(
            pool, shared, jnp.asarray([8, 1], jnp.int32),  # row 1 pos 1
            jnp.full((b, 1, f), 3.0),
        )
        assert np.asarray(pool2)[0, 0, 1].sum() == f * 3.0

    def test_copy_pages_zeroes_past_valid(self):
        """copy_pages moves whole physical pages and zeroes destination
        rows past the per-page valid count — the publish / copy-on-write
        primitive (a published terminal page must not leak image K/V)."""
        rng = np.random.RandomState(3)
        pool = jnp.asarray(rng.rand(2, 2, 4, 2), jnp.float32)
        out = np.asarray(paged_kv.copy_pages(pool, src=[1], dst=[3], valid=[2]))
        src = np.asarray(pool).reshape(4, 4, 2)[1]
        np.testing.assert_array_equal(out[1, 1, :2], src[:2])
        assert out[1, 1, 2:].sum() == 0
        # other pages untouched
        np.testing.assert_array_equal(out[0], np.asarray(pool)[0])

    def test_gather_variants_match(self):
        rng = np.random.RandomState(1)
        pool = jnp.asarray(rng.rand(2, 3, 4, 8), jnp.float32)
        table = paged_kv.identity_table(2, 3)
        np.testing.assert_allclose(
            np.asarray(paged_kv.gather(pool, table, variant="take")),
            np.asarray(paged_kv.gather(pool, table, variant="onehot")),
            atol=1e-6,
        )


# ------------------------------------------------- format parity (model)


class TestFormatParity:
    def test_paged_flat_4d_sample_identical_tokens(self, monkeypatch):
        """The cache format may only change the arrays XLA lays out, never
        the sampled tokens. Page size 4 forces multi-page pools so the
        parity covers page-boundary appends inside the real decode loop
        (prefill block + scan), not just single pages."""
        monkeypatch.setenv("DALLE_TPU_KV_PAGE_SIZE", "4")
        jax.clear_caches()  # page size is read at trace time
        try:
            dalle = small_dalle()
            text, image = dalle_inputs(dalle)
            params = dalle.init(jax.random.key(0), text, image)["params"]
            toks = {
                fmt: np.asarray(
                    generate_image_tokens(
                        dalle, params, text, jax.random.key(7), cache_format=fmt
                    )
                )
                for fmt in kv_policy.FORMATS
            }
            np.testing.assert_array_equal(toks["paged"], toks["4d"])
            np.testing.assert_array_equal(toks["flat"], toks["4d"])
        finally:
            jax.clear_caches()

    @pytest.mark.parametrize("kw", [dict(), dict(attn_types=("conv_like", "axial_col"))])
    def test_paged_decode_matches_forward(self, kw, monkeypatch):
        """Sequential paged decode_step reproduces the full-forward logits
        at every position (multi-page, page size 4)."""
        monkeypatch.setenv("DALLE_TPU_KV_PAGE_SIZE", "4")
        dalle = small_dalle(**kw)
        text, image = dalle_inputs(dalle)
        params = dalle.init(jax.random.key(0), text, image)["params"]
        full_logits = np.asarray(dalle.apply({"params": params}, text, image))
        internal = np.concatenate(
            (np.asarray(dalle.remap_text(text)), np.asarray(image)), axis=1
        )
        cache = init_decode_cache(dalle, params, 2, cache_format="paged")
        assert any(
            getattr(p[-1], "key", None) == "cached_key_pages"
            for p, _ in jax.tree_util.tree_leaves_with_path(cache)
        )
        for i in range(dalle.total_seq_len):
            step_logits, mutated = dalle.apply(
                {"params": params, "cache": cache},
                jnp.asarray(internal[:, i]),
                jnp.array(i, jnp.int32),
                method=DALLE.decode_step,
                mutable=["cache"],
            )
            cache = mutated["cache"]
            np.testing.assert_allclose(
                np.asarray(step_logits), full_logits[:, i],
                atol=2e-3, rtol=1e-3,
                err_msg=f"paged decode/forward mismatch at position {i} ({kw})",
            )

    def test_windowed_paged_decode_matches_full(self, monkeypatch):
        """Frontier-sized paged pools (the segmented scan's resize_kv path,
        truncating pools and page tables at page granularity) must produce
        the same logits as the full-extent pool."""
        from dalle_pytorch_tpu.models.sampling import decode_tokens

        monkeypatch.setenv("DALLE_TPU_KV_PAGE_SIZE", "4")
        jax.clear_caches()
        try:
            dalle = small_dalle()
            text, image = dalle_inputs(dalle)
            params = dalle.init(jax.random.key(0), text, image)["params"]
            internal = jnp.concatenate((dalle.remap_text(text), image), axis=1)
            n_internal = dalle.text_len_internal + dalle.image_seq_len
            tokens = jnp.zeros((2, n_internal), jnp.int32)
            tokens = jax.lax.dynamic_update_slice(tokens, internal, (0, 0))
            out = {}
            for seg in (0, 4):  # unsegmented vs resize every 4 positions
                out[seg] = np.asarray(
                    decode_tokens(
                        dalle, params, tokens, dalle.text_len_internal,
                        jax.random.key(3), prefill_len=dalle.text_len_internal,
                        window_seg=seg, cache_format="paged",
                    )
                )
            np.testing.assert_array_equal(out[0], out[4])
        finally:
            jax.clear_caches()


# ------------------------------------------------ ragged offsets (model)


class TestRaggedOffsets:
    def _replay(self, dalle, params, internal, row, upto):
        """Decode sequence ``row`` alone (batch 1, paged) to position upto."""
        cache = init_decode_cache(dalle, params, 1, cache_format="paged")
        for i in range(upto):
            _, mutated = dalle.apply(
                {"params": params, "cache": cache},
                jnp.asarray(internal[row : row + 1, i]),
                jnp.array(i, jnp.int32),
                method=DALLE.decode_step,
                mutable=["cache"],
            )
            cache = mutated["cache"]
        return cache

    def test_merged_ragged_step_matches_per_sequence(self, monkeypatch):
        """THE continuous-batching contract: two sequences replayed to
        different offsets, merged into one batch, stepped ONCE with vector
        positions — logits must equal each sequence's own next step (up to
        the ~1-ulp summation-order drift of batch-2 vs batch-1 einsum
        chunking)."""
        monkeypatch.setenv("DALLE_TPU_KV_PAGE_SIZE", "4")
        dalle = small_dalle()
        text, image = dalle_inputs(dalle)
        params = dalle.init(jax.random.key(0), text, image)["params"]
        internal = np.concatenate(
            (np.asarray(dalle.remap_text(text)), np.asarray(image)), axis=1
        )
        offs = (6, 8)  # one mid-image, one further along — different pages
        caches = [
            self._replay(dalle, params, internal, r, o) for r, o in enumerate(offs)
        ]
        merged = merge_decode_caches(caches)

        tok = jnp.asarray(
            [internal[r, o] for r, o in enumerate(offs)], jnp.int32
        )
        pos = jnp.asarray(offs, jnp.int32)
        ragged_logits, mutated = dalle.apply(
            {"params": params, "cache": merged}, tok, pos,
            method=DALLE.decode_step, mutable=["cache"],
        )

        for r, o in enumerate(offs):
            ref, _ = dalle.apply(
                {"params": params, "cache": caches[r]},
                tok[r : r + 1], jnp.array(o, jnp.int32),
                method=DALLE.decode_step, mutable=["cache"],
            )
            np.testing.assert_allclose(
                np.asarray(ragged_logits[r : r + 1]), np.asarray(ref),
                atol=1e-5, rtol=1e-5,
                err_msg=f"ragged step diverged from per-sequence decode (seq {r})",
            )
        # the merged cache advanced every sequence's own frontier
        idx = [
            np.asarray(x)
            for p, x in jax.tree_util.tree_leaves_with_path(mutated["cache"])
            if getattr(p[-1], "key", None) == "cache_index"
        ]
        for leaf in idx:
            np.testing.assert_array_equal(leaf, np.asarray(offs) + 1)

    def test_set_decode_offsets_rejects_unpaged(self):
        dalle = small_dalle()
        text, image = dalle_inputs(dalle)
        params = dalle.init(jax.random.key(0), text, image)["params"]
        cache = init_decode_cache(dalle, params, 2, cache_format="flat")
        with pytest.raises(ValueError, match="paged"):
            set_decode_offsets(cache, jnp.asarray([1, 2], jnp.int32))

    def test_set_decode_offsets_places_every_index(self, monkeypatch):
        monkeypatch.setenv("DALLE_TPU_KV_PAGE_SIZE", "4")
        dalle = small_dalle()
        text, image = dalle_inputs(dalle)
        params = dalle.init(jax.random.key(0), text, image)["params"]
        cache = init_decode_cache(dalle, params, 2, cache_format="paged")
        offs = jnp.asarray([3, 7], jnp.int32)
        cache = set_decode_offsets(cache, offs)
        for p, x in jax.tree_util.tree_leaves_with_path(cache):
            if getattr(p[-1], "key", None) in ("cache_index", "shift_index"):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(offs))


# ----------------------------------------------------------- the policy


class TestPolicy:
    def test_policy_defaults(self):
        assert kv_policy.choose_cache_format(1) == "4d"
        assert kv_policy.choose_cache_format(8) == "flat"
        for b in (2, 4, 16, 32, 64):
            assert kv_policy.choose_cache_format(b) == "paged"

    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("DALLE_TPU_KV_FORMAT", "paged")
        assert kv_policy.choose_cache_format(8) == "paged"
        monkeypatch.setenv("DALLE_TPU_KV_FORMAT", "bogus")
        with pytest.raises(ValueError):
            kv_policy.choose_cache_format(8)
        monkeypatch.delenv("DALLE_TPU_KV_FORMAT")
        monkeypatch.setenv("DALLE_TPU_FLAT_KV", "1")
        assert kv_policy.choose_cache_format(2) == "flat"
        monkeypatch.setenv("DALLE_TPU_FLAT_KV", "0")
        assert kv_policy.choose_cache_format(8) == "4d"
        monkeypatch.setenv("DALLE_TPU_FLAT_KV", "maybe")
        with pytest.raises(ValueError):
            kv_policy.choose_cache_format(8)

    def test_invalid_override_is_named_error_listing_formats(self, monkeypatch):
        """An unknown format must fail AT POLICY RESOLUTION with the named
        error, naming every valid format — not as a shape error deep inside
        cache init. Covers all three override channels."""
        monkeypatch.setenv("DALLE_TPU_KV_FORMAT", "paged2")
        with pytest.raises(kv_policy.InvalidKVFormatError) as ei:
            kv_policy.choose_cache_format(4)
        for fmt in kv_policy.FORMATS:
            assert fmt in str(ei.value)
        assert "DALLE_TPU_KV_FORMAT" in str(ei.value)
        monkeypatch.delenv("DALLE_TPU_KV_FORMAT")

        with pytest.raises(kv_policy.InvalidKVFormatError, match="cache_format"):
            kv_policy.resolve_format("bogus", 4)
        with pytest.raises(kv_policy.InvalidKVFormatError):
            with kv_policy.format_override("bogus"):
                pass
        # ... and through the model entry point (init at trace time)
        dalle = small_dalle()
        text, image = dalle_inputs(dalle)
        params = dalle.init(jax.random.key(0), text, image)["params"]
        with pytest.raises(kv_policy.InvalidKVFormatError):
            init_decode_cache(dalle, params, 2, cache_format="bogus")
        # the named error stays a ValueError for pre-existing callers
        assert issubclass(kv_policy.InvalidKVFormatError, ValueError)

    def test_choices_are_recorded(self):
        n0 = len(kv_policy.CHOICE_LOG)
        fmt = kv_policy.choose_cache_format(16)
        assert kv_policy.CHOICE_LOG[n0:] == [
            {"cache_format": fmt, "batch": 16,
             "reason": "policy: batch-invariant page-local updates"}
        ]

    def test_format_override_nests_and_restores(self):
        with kv_policy.format_override("flat"):
            assert kv_policy.choose_cache_format(32) == "flat"
            with kv_policy.format_override("paged"):
                assert kv_policy.choose_cache_format(32) == "paged"
            assert kv_policy.choose_cache_format(32) == "flat"
        assert kv_policy.choose_cache_format(32) == "paged"
