"""Serving-engine failure-path tests — every robustness behavior of the
continuous-batching engine pinned deterministically on CPU: typed admission
rejects, deadline expiry mid-decode, preempt-and-requeue with BIT-IDENTICAL
replay, cancellation and page reclamation, watermark degradation, livelock
aging, the preemption cap, and the combined-fault overload scenario where
100% of submitted requests must end in a typed outcome.

Page size 2 (env override) so tiny models cross page boundaries mid-decode
— the page-growth allocation is the natural preemption trigger and the
``page_exhaust`` fault site sits exactly there.
"""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import DALLE, init_decode_cache, insert_decode_cache
from dalle_pytorch_tpu.serving import (
    Engine,
    EngineConfig,
    EngineUnsupportedModel,
    FakeClock,
    Outcome,
    PagePool,
    RejectReason,
    Request,
    Scheduler,
    check_accounting,
    pages_for,
)
from dalle_pytorch_tpu.serving.scheduler import Entry
from dalle_pytorch_tpu.utils.faults import FAULTS
from dalle_pytorch_tpu.utils.metrics import counters, gauges


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
    """One (dalle, params) for the whole module: every engine test shares
    the jit cache, so the suite compiles the prefill/decode programs once."""
    dalle = small_dalle()
    rng = np.random.RandomState(0)
    text = jnp.asarray(rng.randint(1, 16, size=(2, 4)), jnp.int32)
    image = jnp.asarray(rng.randint(0, 12, size=(2, 4)), jnp.int32)
    params = dalle.init(jax.random.key(0), text, image)["params"]
    return dalle, params


@pytest.fixture(autouse=True)
def tiny_pages(monkeypatch):
    """Page size 2: the tiny model's decode then genuinely grows pages
    mid-flight (text_len_internal=5 -> 3 prompt pages; positions 6+ cross
    into growth territory)."""
    monkeypatch.setenv("DALLE_TPU_KV_PAGE_SIZE", "2")
    yield


def prompt(i=0):
    rng = np.random.RandomState(100 + i)
    return rng.randint(1, 16, size=(4,)).astype(np.int32)


def req(i, max_new=4, **kw):
    kw.setdefault("seed", i)
    return Request(
        request_id=f"r{i}", prompt=prompt(i), max_new_tokens=max_new, **kw
    )


def make_engine(model, clock=None, **cfg_kw):
    dalle, params = model
    cfg_kw.setdefault("max_batch", 2)
    return Engine(
        dalle, params, EngineConfig(**cfg_kw),
        clock=clock or FakeClock(step_dt=1.0),
    )


def outcome_accounting_holds(engine):
    check_accounting(engine)
    outcomes = engine.stats()["outcomes"]
    assert sum(outcomes.values()) == engine.stats()["submitted"]
    assert counters.get("serve.submitted") == engine.stats()["submitted"]
    return outcomes


# ----------------------------------------------------- scheduler (pure)


class TestScheduler:
    def test_page_pool_alloc_free(self):
        pool = PagePool(4)
        assert pool.alloc("a", 3) and pool.free == 1
        assert not pool.alloc("b", 2)  # all-or-nothing
        assert pool.alloc("b", 1) and pool.free == 0
        assert pool.free_all("a") == 3 and pool.free == 3
        assert pool.free_all("a") == 0  # idempotent

    def test_pages_for(self):
        assert pages_for(0, 2) == 0
        assert pages_for(1, 2) == 1
        assert pages_for(5, 2) == 3

    def test_priority_order_and_fifo_tiebreak(self):
        s = Scheduler(queue_limit=8)
        for i, pri in enumerate([0, 2, 1, 2]):
            s.submit(Entry(request=req(i, priority=pri), submit_time=0.0, seq=i))
        assert [s.pop().request_id for _ in range(4)] == ["r1", "r3", "r2", "r0"]

    def test_preemption_ages_priority(self):
        """The livelock guard: each eviction boosts effective priority, so
        an evicted request eventually outranks fresh same-priority work."""
        s = Scheduler(queue_limit=8, preempt_priority_boost=1)
        evicted = Entry(request=req(0, priority=0), submit_time=0.0, seq=0,
                        preempt_count=2)
        fresh = Entry(request=req(1, priority=1), submit_time=0.0, seq=1)
        assert s.effective_priority(evicted) == 2 > s.effective_priority(fresh)
        s.requeue(evicted)
        s.submit(fresh)
        assert s.pop() is evicted

    def test_bounded_queue(self):
        s = Scheduler(queue_limit=1)
        assert s.submit(Entry(request=req(0), submit_time=0.0, seq=0))
        assert not s.submit(Entry(request=req(1), submit_time=0.0, seq=1))
        # a requeued (admitted-once) entry neither gets bounced by the
        # bound nor occupies it against fresh arrivals
        popped = s.pop()
        s.requeue(popped)
        assert s.submit(Entry(request=req(2), submit_time=0.0, seq=2))
        assert len(s) == 2


# ------------------------------------------------------------ admission


class TestAdmission:
    def test_demand_exceeds_pool_rejected_typed(self, model):
        eng = make_engine(model, page_budget=2)  # worst case needs 4 pages
        res = eng.submit(req(0))
        assert res is not None and res.outcome is Outcome.REJECTED
        assert res.reject_reason is RejectReason.DEMAND_EXCEEDS_POOL
        assert eng.results["r0"] is res
        outcome_accounting_holds(eng)

    def test_queue_full_rejected_typed(self, model):
        eng = make_engine(model, queue_limit=1)
        assert eng.submit(req(0)) is None
        res = eng.submit(req(1))
        assert res is not None and res.reject_reason is RejectReason.QUEUE_FULL
        eng.run(max_steps=200)
        outcomes = outcome_accounting_holds(eng)
        assert outcomes["completed"] == 1 and outcomes["rejected"] == 1

    def test_duplicate_request_id_raises(self, model):
        eng = make_engine(model)
        assert eng.submit(req(0)) is None
        with pytest.raises(ValueError, match="duplicate"):
            eng.submit(req(0))

    def test_max_new_tokens_bounds(self, model):
        eng = make_engine(model)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(req(0, max_new=99))

    def test_gmlp_model_typed_unsupported(self):
        dalle = small_dalle(attn_types=("mlp", "full"))
        with pytest.raises(EngineUnsupportedModel, match="gMLP"):
            Engine(dalle, params=None)


# --------------------------------------------------- deadlines & cancel


class TestDeadlinesCancel:
    def test_deadline_expiry_mid_decode_frees_pages(self, model):
        clock = FakeClock(step_dt=1.0)
        eng = make_engine(model, clock=clock)
        # admits at t=0; each decode iteration costs 1s; expires mid-decode
        assert eng.submit(req(0, max_new=4, deadline=1.5)) is None
        eng.run(max_steps=200)
        res = eng.results["r0"]
        assert res.outcome is Outcome.DEADLINE_EXCEEDED
        assert res.tokens is not None and 0 < len(res.tokens) < 4  # partial
        assert eng.pool.used == 0
        outcome_accounting_holds(eng)

    def test_deadline_expired_in_queue(self, model):
        clock = FakeClock(step_dt=1.0)
        eng = make_engine(model, max_batch=1, clock=clock)
        assert eng.submit(req(0, max_new=4)) is None
        assert eng.submit(req(1, max_new=4, deadline=2.0)) is None  # waits
        eng.run(max_steps=200)
        assert eng.results["r0"].outcome is Outcome.COMPLETED
        res = eng.results["r1"]
        assert res.outcome is Outcome.DEADLINE_EXCEEDED
        assert res.tokens is None  # never prefilled
        outcome_accounting_holds(eng)

    def test_cancellation_frees_pages(self, model):
        eng = make_engine(model)
        assert eng.submit(req(0, max_new=4)) is None
        eng.step()  # admit + first decode
        assert eng.pool.used > 0
        eng.cancel("r0")
        eng.run(max_steps=200)
        res = eng.results["r0"]
        assert res.outcome is Outcome.CANCELLED
        assert res.tokens is not None and len(res.tokens) < 4
        assert eng.pool.used == 0
        outcome_accounting_holds(eng)

    def test_cancel_queued_request(self, model):
        eng = make_engine(model, max_batch=1)
        assert eng.submit(req(0)) is None
        assert eng.submit(req(1)) is None
        eng.step()  # r0 admitted, r1 queued
        eng.cancel("r1")
        eng.run(max_steps=200)
        assert eng.results["r1"].outcome is Outcome.CANCELLED
        assert eng.results["r1"].tokens is None
        assert eng.results["r0"].outcome is Outcome.COMPLETED
        outcome_accounting_holds(eng)

    def test_request_cancel_fault_site(self, model):
        FAULTS.arm("request_cancel", 1)
        eng = make_engine(model)
        for i in range(2):
            assert eng.submit(req(i)) is None
        eng.run(max_steps=200)
        outcomes = outcome_accounting_holds(eng)
        assert outcomes["cancelled"] == 1
        assert FAULTS.fired.get("request_cancel") == 1

    def test_decode_stall_fault_pushes_past_deadline(self, model):
        FAULTS.arm("decode_stall", 1)
        clock = FakeClock(step_dt=0.0)  # ONLY the stall advances time
        eng = make_engine(model, clock=clock, stall_penalty_s=10.0)
        assert eng.submit(req(0, deadline=5.0)) is None
        eng.run(max_steps=200)
        assert eng.results["r0"].outcome is Outcome.DEADLINE_EXCEEDED
        assert FAULTS.fired.get("decode_stall") == 1
        outcome_accounting_holds(eng)


# ------------------------------------------------- preempt-and-requeue


class TestPreemption:
    def run_trace(self, model, fault_spec=None, **cfg_kw):
        FAULTS.reset()
        counters.reset()
        if fault_spec:
            FAULTS.configure(fault_spec)
        eng = make_engine(model, **cfg_kw)
        for i in range(3):
            assert eng.submit(req(i)) is None
        eng.run(max_steps=500)
        return eng

    def test_preempt_requeue_bit_identical(self, model):
        """THE acceptance criterion: an injected page_exhaust forces an
        eviction; the evicted request re-prefills from scratch and its
        final tokens are BIT-identical to the unpreempted run (pure
        (seed, position) sampling keys + row-independent fixed-width
        decode), and every page returns to the pool."""
        clean = self.run_trace(model)
        clean_tokens = {
            rid: np.asarray(r.tokens) for rid, r in clean.results.items()
        }
        faulted = self.run_trace(model, fault_spec="page_exhaust=1")
        assert FAULTS.fired.get("page_exhaust") == 1
        assert counters.get("serve.preempted") >= 1
        assert any(r.preempt_count > 0 for r in faulted.results.values())
        for rid, r in faulted.results.items():
            assert r.outcome is Outcome.COMPLETED, (rid, r)
            np.testing.assert_array_equal(
                np.asarray(r.tokens), clean_tokens[rid],
                err_msg=f"{rid} tokens diverged across preemption",
            )
        assert faulted.pool.used == 0
        outcome_accounting_holds(faulted)

    def test_preempt_cap_is_typed_failure(self, model):
        eng = self.run_trace(
            model, fault_spec="page_exhaust=1", max_preemptions=0
        )
        outcomes = outcome_accounting_holds(eng)
        assert outcomes["preempt_cap"] == 1
        capped = [
            r for r in eng.results.values()
            if r.outcome is Outcome.PREEMPT_CAP
        ]
        assert capped[0].preempt_count == 1
        assert eng.pool.used == 0

    def test_victim_is_lowest_priority_youngest(self, model):
        """Eviction order: the low-priority request dies for the
        high-priority one's pages, and aging boosts it on requeue."""
        FAULTS.arm("page_exhaust", 1)
        eng = make_engine(model, max_batch=2)
        assert eng.submit(req(0, priority=5)) is None
        assert eng.submit(req(1, priority=0)) is None
        eng.run(max_steps=500)
        assert eng.results["r0"].preempt_count == 0
        assert eng.results["r1"].preempt_count == 1
        assert all(
            r.outcome is Outcome.COMPLETED for r in eng.results.values()
        )
        outcome_accounting_holds(eng)

    def test_natural_exhaustion_under_tight_pool(self, model):
        """No faults: a page budget below the runnable batch's aggregate
        demand makes decode-time growth collide for real; the engine must
        still complete everything via preempt-and-requeue."""
        # worst case per request = pages_for(5 + 3, 2) = 4, prompt = 3.
        # Budget 7 admits two requests (3 + 3 held, 1 free — each passed
        # the worst-vs-free gate at ITS admission instant) whose combined
        # growth then wants 2 more pages than exist: optimistic admission
        # cannot see the collision coming, preemption absorbs it.
        eng = self.run_trace(model, page_budget=7)
        outcomes = outcome_accounting_holds(eng)
        assert outcomes["completed"] == 3
        assert counters.get("serve.preempted") >= 1
        assert eng.pool.used == 0


# ------------------------------------------------ degradation & overload


class TestDegradationOverload:
    def test_watermark_clamp_reported(self, model):
        eng = make_engine(
            model, max_batch=2,
            high_watermark=0.0,  # any occupancy counts as pressure
            degraded_max_new_tokens=2,
        )
        assert eng.submit(req(0, max_new=4)) is None
        assert eng.submit(req(1, max_new=4)) is None
        eng.run(max_steps=200)
        # first admission happens at 0 occupancy -> unclamped; the second
        # sees the first's pages resident -> clamped, and SAYS so
        r0, r1 = eng.results["r0"], eng.results["r1"]
        clamped = [r for r in (r0, r1) if r.clamped_max_new_tokens is not None]
        full = [r for r in (r0, r1) if r.clamped_max_new_tokens is None]
        assert len(clamped) == 1 and len(full) == 1
        assert clamped[0].outcome is Outcome.COMPLETED
        assert len(clamped[0].tokens) == 2 == clamped[0].clamped_max_new_tokens
        assert len(full[0].tokens) == 4
        assert counters.get("serve.clamped") == 1
        outcome_accounting_holds(eng)

    def test_combined_faults_overload_all_accounted(self, model):
        """The combined acceptance scenario: aggregate demand far over the
        pool, a bounded queue, deadlines, and injected prefill_fail +
        page_exhaust (the DALLE_TPU_FAULTS env spec format). No hang, no
        allocation failure, and every submitted request ends in exactly one
        typed outcome with counters summing to 100%."""
        FAULTS.configure("page_exhaust=1,prefill_fail=1")
        clock = FakeClock(step_dt=1.0)
        eng = make_engine(
            model, clock=clock, max_batch=2, page_budget=7, queue_limit=3,
            prefill_attempts=2,
        )
        immediate = []
        for i in range(8):
            r = eng.submit(req(
                i, max_new=4,
                deadline=None if i % 2 else 40.0,
                priority=i % 3,
            ))
            if r is not None:
                immediate.append(r)
        eng.run(max_steps=1000)
        outcomes = outcome_accounting_holds(eng)
        assert sum(outcomes.values()) == 8
        assert outcomes["rejected"] == len(immediate) > 0  # bounded queue bit
        # the transient prefill failure was retried, not surfaced
        assert counters.get("serve.prefill_retries") == 1
        assert FAULTS.fired.get("prefill_fail") == 1
        assert FAULTS.fired.get("page_exhaust") == 1
        assert eng.pool.used == 0
        for r in eng.results.values():
            assert r.outcome in (
                Outcome.COMPLETED, Outcome.REJECTED,
                Outcome.DEADLINE_EXCEEDED, Outcome.PREEMPT_CAP,
            ), r

    def test_prefill_fail_exhausts_attempts_typed(self, model):
        FAULTS.arm("prefill_fail", 5)
        eng = make_engine(model, prefill_attempts=2)
        assert eng.submit(req(0)) is None
        eng.run(max_steps=200)
        res = eng.results["r0"]
        assert res.outcome is Outcome.PREFILL_FAILED
        assert res.prefill_attempts == 2
        assert eng.pool.used == 0
        outcome_accounting_holds(eng)

    def test_gauges_published(self, model):
        gauges.reset()
        eng = make_engine(model)
        assert eng.submit(req(0)) is None
        eng.step()
        snap = gauges.snapshot("serve.")
        assert set(snap) == {
            "serve.pool_occupancy", "serve.running", "serve.prefilling",
            "serve.queued",
            # KV storage-format footprint (ISSUE 14): published from
            # construction on, whatever the kv_quant setting
            "serve.kv_quant.bytes_per_slot", "serve.kv_quant.pages",
        }
        assert snap["serve.running"] == 1
        assert snap["serve.kv_quant.bytes_per_slot"] == float(
            eng.kv_bytes_per_slot
        )
        eng.run(max_steps=200)
        assert gauges.get("serve.pool_occupancy") == 0.0


# --------------------------------------------- decode-path correctness


class TestEngineDecodeParity:
    def test_tokens_independent_of_batch_width_composition(self, model):
        """Row independence at the engine level: the same request produces
        identical tokens alone in a max_batch=1 engine and sharing a
        max_batch=2 engine with unrelated traffic — the property the
        bit-identical preemption replay stands on."""
        dalle, params = model

        def run(max_batch, n_extra):
            eng = Engine(
                dalle, params, EngineConfig(max_batch=max_batch),
                clock=FakeClock(step_dt=0.1),
            )
            assert eng.submit(req(0, max_new=4)) is None
            for i in range(n_extra):
                assert eng.submit(req(10 + i, max_new=4)) is None
            eng.run(max_steps=500)
            return np.asarray(eng.results["r0"].tokens)

        alone = run(1, 0)
        shared = run(2, 3)
        np.testing.assert_array_equal(alone, shared)

    def test_ragged_nonrotary_step_matches_per_sequence(self):
        """Vector-position decode_step with LEARNED positional tables
        (rotary_emb=False — the train_dalle.py CLI default): the merged
        ragged step must match each sequence's own scalar-position step,
        which is what lets generate.py route non-rotary checkpoints
        through the engine."""
        dalle = small_dalle(rotary_emb=False)
        rng = np.random.RandomState(0)
        text = jnp.asarray(rng.randint(1, 16, size=(2, 4)), jnp.int32)
        image = jnp.asarray(rng.randint(0, 12, size=(2, 4)), jnp.int32)
        params = dalle.init(jax.random.key(0), text, image)["params"]
        internal = np.concatenate(
            (np.asarray(dalle.remap_text(text)), np.asarray(image)), axis=1
        )

        def replay(row, upto):
            cache = init_decode_cache(dalle, params, 1, cache_format="paged")
            for i in range(upto):
                _, mutated = dalle.apply(
                    {"params": params, "cache": cache},
                    jnp.asarray(internal[row: row + 1, i]),
                    jnp.array(i, jnp.int32),
                    method=DALLE.decode_step, mutable=["cache"],
                )
                cache = mutated["cache"]
            return cache

        offs = (6, 8)
        caches = [replay(r, o) for r, o in enumerate(offs)]
        from dalle_pytorch_tpu.models import merge_decode_caches

        merged = merge_decode_caches(caches)
        tok = jnp.asarray([internal[r, o] for r, o in enumerate(offs)], jnp.int32)
        ragged_logits, _ = dalle.apply(
            {"params": params, "cache": merged},
            tok, jnp.asarray(offs, jnp.int32),
            method=DALLE.decode_step, mutable=["cache"],
        )
        for r, o in enumerate(offs):
            ref, _ = dalle.apply(
                {"params": params, "cache": caches[r]},
                tok[r: r + 1], jnp.array(o, jnp.int32),
                method=DALLE.decode_step, mutable=["cache"],
            )
            np.testing.assert_allclose(
                np.asarray(ragged_logits[r: r + 1]), np.asarray(ref),
                atol=1e-5, rtol=1e-5,
                err_msg=f"non-rotary ragged step diverged (seq {r})",
            )

    def test_insert_decode_cache_rejects_unvectorized(self, model):
        dalle, params = model
        batched = init_decode_cache(dalle, params, 2, cache_format="paged")
        sub = init_decode_cache(dalle, params, 1, cache_format="paged")
        # scalar shift_index leaf -> must be refused with guidance
        with pytest.raises(ValueError, match="set_decode_offsets"):
            insert_decode_cache(batched, sub, 0)

    def test_insert_decode_cache_rejects_unpaged(self, model):
        dalle, params = model
        batched = init_decode_cache(dalle, params, 2, cache_format="flat")
        with pytest.raises(ValueError, match="paged"):
            insert_decode_cache(batched, batched, 0)


# ------------------------------------------- donation + compile budget


def _leaves_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestDonation:
    """The serving jits donate their cache (ISSUE 8 satellite): donation
    must change HBM residency, never tokens — pinned here bit-exactly
    against non-donating rewraps of the same functions — and the shared
    pristine template must survive it (jax deletes donated buffers on
    CPU too, so any template reuse would crash loudly in this suite).
    The static regression guard is DTL12x in `tools/lint.py --trace`."""

    def test_prefill_and_decode_bit_identical_to_undonated(self, model):
        from functools import partial

        from dalle_pytorch_tpu.models.sampling import set_decode_offsets
        from dalle_pytorch_tpu.serving import engine as eng

        dalle, params = model
        pre_nd = partial(
            jax.jit, static_argnums=(0, 5)
        )(eng._prefill_jit.__wrapped__)
        dec_nd = partial(
            jax.jit, static_argnums=(0, 6)
        )(eng._decode_jit.__wrapped__)
        fresh = set_decode_offsets(
            init_decode_cache(dalle, params, 1, cache_format="paged"),
            jnp.zeros((1,), jnp.int32),
        )
        text = jnp.asarray(prompt(0), jnp.int32)[None, :]
        internal = dalle.remap_text(text)
        T = dalle.text_len_internal
        k = max(int((1 - 0.9) * dalle.total_tokens), 1)
        key = jax.random.fold_in(jax.random.key(7), T)

        donated_in = jax.tree_util.tree_map(jnp.copy, fresh)
        c_d, t_d, img_d = eng._prefill_jit(
            dalle, params, donated_in, internal, key, k, 1.0
        )
        c_n, t_n, img_n = pre_nd(dalle, params, fresh, internal, key, k, 1.0)
        assert int(t_d[0]) == int(t_n[0])
        _leaves_equal(c_d, c_n)
        _leaves_equal(img_d, img_n)

        # one vector-position decode step, donated vs not, equal caches in
        batched = set_decode_offsets(
            init_decode_cache(dalle, params, 2, cache_format="paged"),
            jnp.zeros((2,), jnp.int32),
        )
        batched = insert_decode_cache(batched, c_d, 0)
        batched2 = jax.tree_util.tree_map(jnp.copy, batched)
        tok = jnp.asarray([int(t_d[0]), 0], jnp.int32)
        pos = jnp.asarray([T, 0], jnp.int32)
        keys = jnp.stack([
            jax.random.fold_in(jax.random.key(7), T + 1),
            jax.random.key(0),
        ])
        cd2, sd = eng._decode_jit(dalle, params, batched, tok, pos, keys, k, 1.0)
        cn2, sn = dec_nd(dalle, params, batched2, tok, pos, keys, k, 1.0)
        assert int(sd[0]) == int(sn[0])
        _leaves_equal(cd2, cn2)

    def test_fresh_template_survives_sequential_prefills(self, model):
        """Two requests prefilled back-to-back from the same engine: both
        monolithic prefills start from the SAME pristine template, which
        the donating jit must therefore never consume directly."""
        engine = make_engine(model)
        assert engine.submit(req(0, max_new=3)) is None
        engine.run(max_steps=200)
        assert engine.submit(req(1, max_new=3)) is None
        engine.run(max_steps=200)
        check_accounting(engine)
        assert engine.results["r0"].outcome is Outcome.COMPLETED
        assert engine.results["r1"].outcome is Outcome.COMPLETED
        assert not any(
            x.is_deleted() for x in jax.tree_util.tree_leaves(engine._fresh1)
        ), "donation consumed the shared pristine prefill template"

    def test_decode_jit_compiles_once_steady_state(self, model):
        """The DTL11x acceptance property at runtime: a multi-request
        engine run (admissions landing mid-decode, completions freeing
        slots) feeds `_decode_jit` EXACTLY one compile signature; an
        injected shape-drifting call compiles a second one — the drift
        the committed compile-signature contract turns into a lint
        failure (tests/fixtures_lint: DTL111)."""
        from dalle_pytorch_tpu.serving import engine as eng

        dalle, params = model
        # max_batch=5 is used nowhere else in this module: the signature
        # is fresh, so the compile-count delta is exact, not <=
        engine = Engine(dalle, params, EngineConfig(max_batch=5),
                        clock=FakeClock(step_dt=0.1))
        before = eng._decode_jit._cache_size()
        for i in range(8):
            assert engine.submit(req(i, max_new=4)) is None
        engine.run(max_steps=800)
        check_accounting(engine)
        assert all(
            r.outcome is Outcome.COMPLETED for r in engine.results.values()
        )
        assert eng._decode_jit._cache_size() - before == 1, (
            "steady-state decode recompiled: the engine fed _decode_jit "
            "more than one (shape, dtype, static) signature"
        )
        # inject shape drift: a second engine at a different batch width
        # is a second signature — exactly what DTL111/DTL113 would flag
        # if the registry/engine started producing it
        drift = Engine(dalle, params, EngineConfig(max_batch=6),
                       clock=FakeClock(step_dt=0.1))
        assert drift.submit(req(90, max_new=2)) is None
        drift.run(max_steps=200)
        assert eng._decode_jit._cache_size() - before == 2


# ----------------------------------------------------- release gates


@pytest.mark.slow
def test_serve_smoke_tool():
    """The release gate must pass clean AND absorb an env-armed transient
    prefill fault (the DALLE_TPU_FAULTS inheritance path through a real
    subprocess)."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for extra_env in ({}, {"DALLE_TPU_FAULTS": "prefill_fail=1"}):
        env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
        out = subprocess.run(
            [sys.executable, "tools/serve_smoke.py"],
            capture_output=True, text=True, timeout=600, env=env, cwd=repo,
        )
        assert out.returncode == 0, (extra_env, out.stderr[-2000:])
        assert "serve smoke OK" in out.stderr
