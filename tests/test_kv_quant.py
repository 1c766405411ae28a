"""Quantized KV pages (ISSUE 14, ROADMAP 3): int8 paged pools with
per-(token, head) scales in parallel scale pools, quantized at append
and dequantized at READ time — pinned deterministically on CPU:

- policy: the ``kv_quant`` knob resolves through all three channels
  (explicit argument, ``quant_override`` context, ``DALLE_TPU_KV_QUANT``
  env) and an invalid value fails TYPED in each, at resolution time;
- quantizer unit behavior: symmetric amax/127 scales (zeros quantize
  with scale 1), deterministic/idempotent bytes, round-trip error
  bounded, and append->gather->dequant through real page-boundary
  arithmetic equals the direct formula;
- kernel parity: the Pallas ragged kernel's in-register dequant matches
  the jnp reference path (interpret mode) over mixed descriptors and
  through a PERMUTED (non-identity) page table;
- engine parity tiers: quantized-vs-quantized is BITWISE across
  monolithic/chunked/fused/speculative engines (exact AND genuinely
  misdrafting truncated drafters — the reject-suffix rewind overwrites
  bytes and scales identically), preempt-and-requeue replay, and the
  prefix-cache cold/warm hit (incl. the forged-probe collide drill and
  COW divergence on a shared quantized terminal page); quantized-vs-f32
  is the PINNED token-agreement floor
  (kv_policy.KV_QUANT_TOKEN_AGREEMENT_MIN), never a bitwise claim;
- capacity: per-slot KV bytes from the REAL cache leaves give int8
  >= 1.8x the pages of the unquantized format at a fixed budget, the
  ``serve.kv_quant.*`` gauges are registered and published, and the
  committed trace contract pins the quant serving entries to the same
  signature budgets as their unquantized twins.

Page size 2 (env override), as in tests/test_serving.py, so the tiny
model's T=5 prompt spans 3 pages with a partial terminal page and
decode crosses page boundaries mid-flight.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import DALLE
from dalle_pytorch_tpu.models.sampling import (
    init_decode_cache,
    set_decode_offsets,
)
from dalle_pytorch_tpu.ops import kv_policy, paged_kv
from dalle_pytorch_tpu.ops import ragged_attention as ra
from dalle_pytorch_tpu.ops.kv_policy import (
    KV_QUANT_TOKEN_AGREEMENT_MIN,
    InvalidKVFormatError,
)
from dalle_pytorch_tpu.serving import (
    Engine,
    EngineConfig,
    FakeClock,
    Outcome,
    Request,
    check_accounting,
)
from dalle_pytorch_tpu.utils.faults import FAULTS
from dalle_pytorch_tpu.utils.metrics import counters, gauges

REPO = Path(__file__).resolve().parent.parent


def small_dalle(**kw):
    defaults = dict(
        dim=32, depth=2, num_text_tokens=16, text_seq_len=4,
        num_image_tokens=12, image_fmap_size=2, heads=2, dim_head=8,
        attn_types=("full",), shift_tokens=True, rotary_emb=True,
    )
    defaults.update(kw)
    return DALLE(**defaults)


@pytest.fixture(scope="module")
def model():
    dalle = small_dalle()
    rng = np.random.RandomState(0)
    text = jnp.asarray(rng.randint(1, 16, size=(2, 4)), jnp.int32)
    image = jnp.asarray(rng.randint(0, 12, size=(2, 4)), jnp.int32)
    params = dalle.init(jax.random.key(0), text, image)["params"]
    return dalle, params


@pytest.fixture(autouse=True)
def tiny_pages(monkeypatch):
    monkeypatch.setenv("DALLE_TPU_KV_PAGE_SIZE", "2")
    yield


def prompt(i=0):
    rng = np.random.RandomState(100 + i)
    return rng.randint(1, 16, size=(4,)).astype(np.int32)


def req(i, max_new=4, rid=None, p=None, **kw):
    kw.setdefault("seed", i)
    return Request(
        request_id=rid or f"r{i}",
        prompt=prompt(i) if p is None else p,
        max_new_tokens=max_new, **kw
    )


def make_engine(model, clock=None, **cfg_kw):
    dalle, params = model
    cfg_kw.setdefault("max_batch", 2)
    return Engine(
        dalle, params, EngineConfig(**cfg_kw),
        clock=clock or FakeClock(step_dt=1.0),
    )


def run_tokens(model, reqs, **cfg_kw):
    eng = make_engine(model, **cfg_kw)
    for r in reqs:
        assert eng.submit(r) is None
    eng.run(max_steps=1500)
    check_accounting(eng)
    assert all(
        r.outcome is Outcome.COMPLETED for r in eng.results.values()
    ), {k: v.outcome for k, v in eng.results.items()}
    return {rid: np.asarray(r.tokens) for rid, r in eng.results.items()}


# the quantized engine-mode axis: every mode must be BITWISE equal to
# every other (quant-vs-quant is the standing contract). spec-trunc uses
# a GENUINELY misdrafting depth-1-of-2 drafter, so its runs contain real
# reject-suffix rewinds — bitwise tokens prove the rewind restored the
# pre-draft quantized bytes AND scales (later logits read the rewound
# K/V through the dequant formula).
QUANT_MODES = [
    pytest.param(dict(), id="mono"),
    pytest.param(dict(prefill_chunk=2), id="chunked"),
    pytest.param(dict(prefill_chunk=2, fused_iteration=True), id="fused"),
    pytest.param(
        dict(prefill_chunk=2, fused_iteration=True, spec_decode=True,
             spec_k=2),
        id="spec-exact",
    ),
    pytest.param(
        dict(prefill_chunk=2, fused_iteration=True, spec_decode=True,
             spec_k=2, spec_draft_depth=1),
        id="spec-trunc",
    ),
]


# --------------------------------------------------------------- policy


class TestQuantPolicy:
    def test_invalid_argument_typed(self):
        with pytest.raises(InvalidKVFormatError) as e:
            kv_policy.resolve_quant("int4")
        assert "int8" in str(e.value) and "int4" in str(e.value)

    def test_invalid_env_typed(self, monkeypatch):
        monkeypatch.setenv("DALLE_TPU_KV_QUANT", "fp8")
        with pytest.raises(InvalidKVFormatError) as e:
            kv_policy.choose_kv_quant()
        assert "DALLE_TPU_KV_QUANT" in str(e.value)

    def test_invalid_override_typed(self):
        with pytest.raises(InvalidKVFormatError):
            with kv_policy.quant_override("bogus"):
                pass

    def test_channel_precedence(self, monkeypatch):
        monkeypatch.setenv("DALLE_TPU_KV_QUANT", "none")
        with kv_policy.quant_override("int8"):
            assert kv_policy.choose_kv_quant() == "int8"
        assert kv_policy.choose_kv_quant() == "none"
        monkeypatch.setenv("DALLE_TPU_KV_QUANT", "int8")
        assert kv_policy.choose_kv_quant() == "int8"
        assert kv_policy.resolve_quant("none") == "none"

    def test_engine_config_invalid_typed(self, model):
        with pytest.raises(InvalidKVFormatError):
            make_engine(model, kv_quant="int4")


# ------------------------------------------------------------ quantizer


class TestQuantizeRows:
    def test_roundtrip_error_bound(self):
        rng = np.random.RandomState(0)
        rows = jnp.asarray(rng.randn(2, 7, 16), jnp.float32)
        q, s = paged_kv.quantize_rows(rows, heads=2)
        assert q.dtype == jnp.int8 and s.dtype == paged_kv.SCALE_DTYPE
        assert q.shape == rows.shape and s.shape == (2, 7, 2)
        deq = paged_kv.dequant(q, s, jnp.float32)
        # symmetric 127-level quantization: error <= scale/2 per element
        err = np.abs(np.asarray(deq) - np.asarray(rows))
        bound = np.repeat(np.asarray(s), 8, axis=-1) / 2 + 1e-7
        assert np.all(err <= bound)

    def test_zero_rows_scale_one(self):
        q, s = paged_kv.quantize_rows(jnp.zeros((1, 3, 8)), heads=2)
        np.testing.assert_array_equal(np.asarray(q), 0)
        np.testing.assert_array_equal(np.asarray(s), 1.0)

    def test_deterministic_idempotent(self):
        """The bitwise-parity keystone: quantizing the same rows always
        yields identical bytes and scales — a rewind's overwrite or a
        replay's re-append reproduces pool content exactly."""
        rng = np.random.RandomState(1)
        rows = jnp.asarray(rng.randn(1, 5, 16), jnp.float32)
        q1, s1 = jax.jit(paged_kv.quantize_rows, static_argnums=1)(rows, 2)
        q2, s2 = jax.jit(paged_kv.quantize_rows, static_argnums=1)(rows, 2)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))

    def test_append_gather_dequant_through_pages(self):
        """Quantized rows appended across page boundaries gather and
        dequantize back to exactly the direct formula's values."""
        b, n, h, d, page, n_p = 2, 5, 2, 4, 2, 4
        rng = np.random.RandomState(2)
        rows = jnp.asarray(rng.randn(b, n, h * d), jnp.float32)
        q, s = paged_kv.quantize_rows(rows, h)
        pool = jnp.zeros((b, n_p, page, h * d), jnp.int8)
        spool = jnp.zeros((b, n_p, page, h), paged_kv.SCALE_DTYPE)
        table = paged_kv.identity_table(b, n_p)
        idx = jnp.asarray([0, 1], jnp.int32)  # ragged offsets
        pool = paged_kv.append(pool, table, idx, q)
        spool = paged_kv.append(spool, table, idx, s)
        view = paged_kv.dequant(
            paged_kv.gather(pool, table), paged_kv.gather(spool, table),
            jnp.float32,
        )
        direct = paged_kv.dequant(q, s, jnp.float32)
        for r in range(b):
            lo = int(idx[r])
            np.testing.assert_array_equal(
                np.asarray(view[r, lo:lo + n]), np.asarray(direct[r])
            )

    def test_rewind_overwrite_restores_bytes_and_scales(self):
        """The spec-decode reject-suffix seam at the pool level: draft
        garbage written past the accepted frontier, then the anchored
        re-append (the rewind) overwrites it — bytes AND scales end
        exactly equal to a run that never drafted."""
        b, h, d, page, n_p = 1, 2, 4, 2, 4
        rng = np.random.RandomState(3)
        real = jnp.asarray(rng.randn(b, 4, h * d), jnp.float32)
        garbage = jnp.asarray(rng.randn(b, 3, h * d) * 9.0, jnp.float32)

        def fresh():
            return (
                jnp.zeros((b, n_p, page, h * d), jnp.int8),
                jnp.zeros((b, n_p, page, h), paged_kv.SCALE_DTYPE),
            )

        table = paged_kv.identity_table(b, n_p)

        def put(pools, rows, at):
            pool, spool = pools
            q, s = paged_kv.quantize_rows(rows, h)
            idx = jnp.full((b,), at, jnp.int32)
            return (
                paged_kv.append(pool, table, idx, q),
                paged_kv.append(spool, table, idx, s),
            )

        clean = put(fresh(), real, 0)
        drafted = put(fresh(), real[:, :1], 0)
        drafted = put(drafted, garbage, 1)      # the rejected suffix
        drafted = put(drafted, real[:, 1:], 1)  # the anchored rewind
        np.testing.assert_array_equal(
            np.asarray(clean[0]), np.asarray(drafted[0])
        )
        np.testing.assert_array_equal(
            np.asarray(clean[1]), np.asarray(drafted[1])
        )


# -------------------------------------------------------- kernel parity


class TestKernelParityQuant:
    def _quant_pools(self, b, n_p, page, h, d, seed=0):
        rng = np.random.RandomState(seed)
        hd = h * d
        k = jnp.asarray(rng.randn(b, n_p * page, hd), jnp.float32) * 0.3
        v = jnp.asarray(rng.randn(b, n_p * page, hd), jnp.float32) * 0.3
        kq, ks = paged_kv.quantize_rows(k, h)
        vq, vs = paged_kv.quantize_rows(v, h)
        shape = (b, n_p, page)
        return (
            kq.reshape(*shape, hd), vq.reshape(*shape, hd),
            ks.reshape(*shape, h), vs.reshape(*shape, h),
        )

    @pytest.mark.parametrize("label,start,length", [
        ("mixed", [0, 3, 9], [4, 2, 1]),
        ("all_decode", [5, 7, 9], [1, 1, 1]),
        ("with_idle", [0, 0, 6], [4, 0, 2]),
    ], ids=["mixed", "all_decode", "with_idle"])
    def test_kernel_matches_reference_quant(self, label, start, length):
        b, n, h, d, page, n_p = 3, 4, 2, 8, 4, 5
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(b, n, h, d), jnp.float32) * 0.3
        kq, vq, ks, vs = self._quant_pools(b, n_p, page, h, d)
        table = paged_kv.identity_table(b, n_p)
        start = jnp.asarray(start, jnp.int32)
        length = jnp.asarray(length, jnp.int32)
        pos = start[:, None] + jnp.arange(n)[None]
        allowed = (
            jnp.arange(n_p * page)[None, None] <= pos[..., None]
        )[:, None]
        ref = ra.reference_attend(
            q, kq, vq, table, allowed, k_scales=ks, v_scales=vs
        )
        ker = ra.kernel_attend(
            q, kq, vq, table, start, length, interpret=True,
            k_scales=ks, v_scales=vs,
        )
        assert bool(jnp.all(jnp.isfinite(ker)))
        valid = (jnp.arange(n)[None] < length[:, None])[..., None, None]
        np.testing.assert_allclose(
            np.asarray(jnp.where(valid, ker, 0.0)),
            np.asarray(jnp.where(valid, ref, 0.0)),
            atol=2e-6, rtol=2e-6,
        )

    def test_kernel_permuted_table_streams_scales_too(self):
        """A non-identity GLOBAL table (pages living in other rows'
        storage — the prefix-cache shape): the kernel must dereference
        the SAME entry for content and scale pages, or a shared page
        would dequantize under a stranger's scales."""
        b, n, h, d, page, n_p = 2, 3, 2, 8, 4, 4
        rng = np.random.RandomState(4)
        q = jnp.asarray(rng.randn(b, n, h, d), jnp.float32) * 0.3
        kq, vq, ks, vs = self._quant_pools(b, n_p, page, h, d, seed=5)
        perm = rng.permutation(b * n_p).reshape(b, n_p)
        table = jnp.asarray(perm, jnp.int32)
        start = jnp.asarray([2, 8], jnp.int32)
        length = jnp.asarray([3, 1], jnp.int32)
        pos = start[:, None] + jnp.arange(n)[None]
        allowed = (
            jnp.arange(n_p * page)[None, None] <= pos[..., None]
        )[:, None]
        ref = ra.reference_attend(
            q, kq, vq, table, allowed, k_scales=ks, v_scales=vs
        )
        ker = ra.kernel_attend(
            q, kq, vq, table, start, length, interpret=True,
            k_scales=ks, v_scales=vs,
        )
        valid = (jnp.arange(n)[None] < length[:, None])[..., None, None]
        np.testing.assert_allclose(
            np.asarray(jnp.where(valid, ker, 0.0)),
            np.asarray(jnp.where(valid, ref, 0.0)),
            atol=2e-6, rtol=2e-6,
        )


# -------------------------------------------------------- engine parity


class TestEngineQuantParity:
    def test_all_modes_bitwise_equal(self, model):
        """Quant-vs-quant is BITWISE across every engine mode — incl.
        the genuinely misdrafting truncated drafter, whose runs contain
        real reject-suffix rewinds over quantized pages."""
        reqs = lambda: [req(i) for i in range(3)]
        base = run_tokens(model, reqs(), kv_quant="int8")
        for mode in (
            dict(prefill_chunk=2),
            dict(prefill_chunk=2, fused_iteration=True),
            dict(prefill_chunk=2, fused_iteration=True, spec_decode=True,
                 spec_k=2),
            dict(prefill_chunk=2, fused_iteration=True, spec_decode=True,
                 spec_k=2, spec_draft_depth=1),
        ):
            got = run_tokens(model, reqs(), kv_quant="int8", **mode)
            for rid in base:
                np.testing.assert_array_equal(
                    base[rid], got[rid],
                    err_msg=f"{rid} diverged under {mode}",
                )

    def test_truncated_drafter_actually_misdrafts(self, model):
        """The spec-trunc mode above only exercises the rewind if the
        depth-1 drafter genuinely mispredicts — pin that it does."""
        counters.reset()
        run_tokens(
            model, [req(i) for i in range(3)], kv_quant="int8",
            prefill_chunk=2, fused_iteration=True, spec_decode=True,
            spec_k=2, spec_draft_depth=1,
        )
        assert counters.get("serve.spec.rejected") > 0, (
            "depth-1 drafter rejected nothing — the rewind seam was "
            "not exercised"
        )

    def test_quant_vs_f32_agreement_floor(self, model):
        reqs = lambda: [req(i) for i in range(3)]
        f32 = run_tokens(model, reqs(), prefill_chunk=2)
        q = run_tokens(model, reqs(), prefill_chunk=2, kv_quant="int8")
        agree = float(np.mean([
            np.mean(f32[rid] == q[rid]) for rid in f32
        ]))
        assert agree >= KV_QUANT_TOKEN_AGREEMENT_MIN, agree

    def test_preempt_replay_bit_identical(self, model):
        """An injected page_exhaust forces an eviction mid-decode on the
        quantized engine; the evicted request re-prefills (re-quantizes)
        from scratch and its tokens are BIT-identical to the unpreempted
        quantized run."""
        FAULTS.reset()
        counters.reset()
        clean = run_tokens(model, [req(i) for i in range(3)],
                           kv_quant="int8")
        FAULTS.configure("page_exhaust=1")
        eng = make_engine(model, kv_quant="int8")
        for i in range(3):
            assert eng.submit(req(i)) is None
        eng.run(max_steps=1500)
        check_accounting(eng)
        FAULTS.reset()
        assert counters.get("serve.preempted") >= 1
        for rid, r in eng.results.items():
            assert r.outcome is Outcome.COMPLETED, (rid, r.outcome)
            np.testing.assert_array_equal(
                np.asarray(r.tokens), clean[rid],
                err_msg=f"{rid} diverged across quantized preemption",
            )
        assert eng.pool.used == 0

    def test_cold_warm_prefix_hit_bitwise(self, model):
        """Warm full hits against quantized arena pages are bitwise
        equal to the quantized cold run (content-addressed int8 bytes +
        scales mapped read-only through the table)."""
        eng = make_engine(
            model, prefill_chunk=2, prefix_cache=True, kv_quant="int8"
        )
        for i in range(3):
            assert eng.submit(req(i, rid=f"r{i}.c")) is None
        eng.run(max_steps=1500)
        h0 = eng.prefix.stats.hits
        for i in range(3):
            assert eng.submit(req(i, rid=f"r{i}.w")) is None
        eng.run(max_steps=1500)
        check_accounting(eng)
        assert eng.prefix.stats.hits > h0, "warm round never hit"
        for i in range(3):
            np.testing.assert_array_equal(
                np.asarray(eng.results[f"r{i}.c"].tokens),
                np.asarray(eng.results[f"r{i}.w"].tokens),
                err_msg=f"r{i} warm quantized hit diverged from cold",
            )

    def test_forged_probe_rejected_falls_back_cold_bitwise(self, model):
        """The forged-scale/collide probe: a prefix_hash_collide-forged
        lookup is rejected by token verification and the request runs
        cold, bit-identical — forged addresses can never map another
        prompt's quantized bytes or scales."""
        FAULTS.reset()
        counters.reset()
        eng = make_engine(
            model, prefill_chunk=2, prefix_cache=True, kv_quant="int8"
        )
        assert eng.submit(req(0, rid="cold")) is None
        eng.run(max_steps=1500)
        FAULTS.arm("prefix_hash_collide", 1)
        assert eng.submit(req(0, rid="probed")) is None
        eng.run(max_steps=1500)
        check_accounting(eng)
        FAULTS.reset()
        assert counters.get("serve.fault_prefix_hash_collide") == 1
        np.testing.assert_array_equal(
            np.asarray(eng.results["cold"].tokens),
            np.asarray(eng.results["probed"].tokens),
        )

    def test_cow_divergence_leaves_quantized_arena_untouched(self, model):
        """Two same-prompt requests take full hits on the same quantized
        chain (partial terminal page COW'd at map time) and decode to
        DIFFERENT continuations; a third same-prompt request afterwards
        still hits and matches the first bit-for-bit — the shared arena
        bytes and scales were never written through the COW copies."""
        eng = make_engine(
            model, max_batch=2, prefill_chunk=2, prefix_cache=True,
            kv_quant="int8",
        )
        assert eng.submit(req(0, rid="pub", seed=5)) is None
        eng.run(max_steps=1500)
        c0 = counters.get("serve.prefix.cow_copies")
        assert eng.submit(req(0, rid="a", seed=6)) is None
        assert eng.submit(req(0, rid="b", seed=7)) is None
        eng.run(max_steps=1500)
        assert counters.get("serve.prefix.cow_copies") > c0, (
            "terminal page was not COW'd — the divergence never "
            "touched the seam under test"
        )
        assert eng.submit(req(0, rid="a2", seed=6)) is None
        eng.run(max_steps=1500)
        check_accounting(eng)
        assert not np.array_equal(
            np.asarray(eng.results["a"].tokens),
            np.asarray(eng.results["b"].tokens),
        ), "seeds 6/7 sampled identical streams — divergence not exercised"
        np.testing.assert_array_equal(
            np.asarray(eng.results["a"].tokens),
            np.asarray(eng.results["a2"].tokens),
            err_msg="later hit diverged — COW leaked into the arena",
        )

    def test_kv_bytes_per_slot_capacity_and_gauges(self, model):
        gauges.reset()
        base = make_engine(model)
        quant = make_engine(model, kv_quant="int8")
        assert quant.kv_quant == "int8" and base.kv_quant == "none"
        ratio = base.kv_bytes_per_slot / quant.kv_bytes_per_slot
        assert ratio >= 1.8, ratio
        # gauges registered (DTL041) and published at construction
        from dalle_pytorch_tpu.utils import telemetry_names as tn

        assert tn.is_registered("serve.kv_quant.bytes_per_slot", "gauge")
        assert tn.is_registered("serve.kv_quant.pages", "gauge")
        assert gauges.get("serve.kv_quant.bytes_per_slot") == float(
            quant.kv_bytes_per_slot
        )

    def test_quant_cache_leaves_dtypes(self, model):
        dalle, params = model
        cache = init_decode_cache(
            dalle, params, 2, cache_format="paged", kv_quant="int8"
        )
        leaves = {
            getattr(p[-1], "key", None): x
            for p, x in jax.tree_util.tree_leaves_with_path(cache)
        }
        assert leaves["cached_key_pages"].dtype == jnp.int8
        assert leaves["cached_value_pages"].dtype == jnp.int8
        assert (
            leaves["cached_key_scale_pages"].dtype == paged_kv.SCALE_DTYPE
        )
        h = dalle.heads
        assert leaves["cached_key_scale_pages"].shape[-1] == h
        # scale pools are POOL-shaped: same (b, n_pages, page) prefix
        assert (
            leaves["cached_key_scale_pages"].shape[:3]
            == leaves["cached_key_pages"].shape[:3]
        )


# ------------------------------------------------------------ contracts


class TestContracts:
    def test_trace_contract_pins_quant_entries(self):
        """The committed trace contract carries the quantized serving
        entries at the SAME signature budgets as their unquantized twins
        (1 decode / 2 iteration signatures, cache donated) — and the
        quant decode entry's donated (aliased) cache bytes are well
        under the unquantized entry's: DTL141's standing guard that
        quantized KV stays roughly half-size."""
        import re

        contract = json.loads(
            (REPO / "tools" / "trace_contracts.json").read_text()
        )
        entries = contract["entries"]
        dq = entries["serving.decode_quant"]
        iq = entries["serving.iteration_quant"]
        assert dq["max_signatures"] == 1
        assert iq["max_signatures"] == 2
        assert dq["donate"] == ["cache"], "quant decode must donate its cache"
        assert iq["donate"] == ["cache"]

        def cache_bytes(entry):
            # signature keys carry each tree arg as tree#..(<n>L,<b>B);
            # arg order is (model, params, cache, ...) so the SECOND
            # tree is the donated cache
            trees = re.findall(
                r"tree#\w+\(\d+L,(\d+)B\)",
                entry["signatures"][0]["key"],
            )
            assert len(trees) >= 2, entry["signatures"][0]["key"]
            return int(trees[1])

        base_cache = cache_bytes(entries["serving.decode"])
        quant_cache = cache_bytes(dq)
        assert quant_cache * 1.8 <= base_cache, (
            f"quant cache {quant_cache}B not <= ~half of the "
            f"unquantized {base_cache}B — the DTL141 half-size guard"
        )
        # the total HBM budget shrinks by exactly the cache savings
        assert dq["max_hbm_bytes"] < entries["serving.decode"][
            "max_hbm_bytes"
        ]
