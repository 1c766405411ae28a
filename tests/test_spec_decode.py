"""Speculative decoding through the fused iteration (ISSUE 11, ROADMAP
2) — multi-token decode steps pinned deterministically on CPU:

- paged_kv-level rewind: a verify block writes its FULL width (masked
  append / per-row ``limit``), and rollback is the next block landing on
  the accepted frontier and overwriting the rejected suffix — pinned
  against sequential appends for accept-all, reject-all, and mixed
  per-row acceptance (idle rows untouched);
- the exact-acceptance parity contract: speculative greedy output is
  BIT-IDENTICAL to non-speculative decode on the f32 CPU tier — exact
  drafter (accept rate 1.0) and a genuinely misdrafting truncated-depth
  drafter (rejections exercised), across split/monolithic/fused
  engines, through preempt-and-replay and prefix-cache warm hits;
- the degraded-drafter drill: ``spec_verify_abort`` falls back to plain
  decode for one iteration through the SAME jit signature, output still
  bit-identical, every request in a typed outcome (100% accounting);
- the dispatch/signature contract: a steady speculative trace keeps
  ``_spec_iteration_jit``'s trace cache FLAT (descriptor raggedness —
  verify widths, mixes, the abort fallback — is data, not shape), at
  most one dispatch per iteration, and commits >1 token per verify step
  with the exact drafter (the memory-bound multi-token claim at CPU
  scale); the committed trace contract pins ``serving.iteration_spec``
  to the steady + final signature pair with the cache donated, and the
  PR 10 follow-on page-copy jits (``serving.page_copy[_across]``) to
  one donated fixed-shape signature each;
- TokenBudget: the decode lane is charged the full VERIFY width (device
  work), while progress is accounted in ACCEPTED tokens.

Page size 2 (env override), as in tests/test_ragged_attention.py, so
verify blocks genuinely cross page boundaries mid-block.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import DALLE
from dalle_pytorch_tpu.ops import paged_kv
from dalle_pytorch_tpu.serving import (
    Engine,
    EngineConfig,
    FakeClock,
    Outcome,
    Request,
    check_accounting,
)
from dalle_pytorch_tpu.serving import engine as engine_mod
from dalle_pytorch_tpu.serving.scheduler import TokenBudget
from dalle_pytorch_tpu.utils.faults import FAULTS
from dalle_pytorch_tpu.utils.metrics import counters, gauges, histograms

REPO = Path(__file__).resolve().parent.parent

# the speculative serving mode: spec rides THROUGH the fused iteration
SPEC = dict(prefill_chunk=2, fused_iteration=True, spec_decode=True)


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


@pytest.fixture(scope="module")
def deep_model():
    """A depth-4 stack whose depth-1 early-exit drafter genuinely
    MISDRAFTS — the engine config that exercises rollback (the tiny
    depth-2 model's truncated drafter agrees too often to reject)."""
    dalle = small_dalle(
        depth=4, num_text_tokens=32, text_seq_len=6,
        num_image_tokens=64, image_fmap_size=4,
    )
    rng = np.random.RandomState(0)
    text = jnp.asarray(rng.randint(1, 32, size=(1, 6)), jnp.int32)
    image = jnp.asarray(rng.randint(0, 64, size=(1, 16)), jnp.int32)
    params = dalle.init(jax.random.key(0), text, image)["params"]
    return dalle, params


@pytest.fixture(autouse=True)
def tiny_pages(monkeypatch):
    monkeypatch.setenv("DALLE_TPU_KV_PAGE_SIZE", "2")
    yield


def prompt(i=0, width=4, vocab=16):
    rng = np.random.RandomState(100 + i)
    return rng.randint(1, vocab, size=(width,)).astype(np.int32)


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


def run_requests(model, n=3, max_new=4, reqs=None, **cfg_kw):
    eng = make_engine(model, **cfg_kw)
    for r in reqs if reqs is not None else [req(i, max_new=max_new)
                                            for i in range(n)]:
        assert eng.submit(r) is None
    eng.run(max_steps=800)
    check_accounting(eng)
    return eng


def tokens_of(eng):
    return {
        rid: None if r.tokens is None else np.asarray(r.tokens)
        for rid, r in eng.results.items()
    }


def completed_tokens(eng):
    out = tokens_of(eng)
    for rid, r in eng.results.items():
        assert r.outcome is Outcome.COMPLETED, (rid, r.outcome)
    return out


# ------------------------------------------------ paged_kv rewind pins


class TestPagedRewind:
    """The rollback substrate: a verify block writes its full width
    through the masked ``append``; rejection is the NEXT block anchored
    at the accepted frontier overwriting the rejected suffix. Pinned
    bit-exactly against sequential single-token appends."""

    def _pool(self, b=2, n_p=4, page=2, feat=3):
        pool = jnp.zeros((b, n_p, page, feat), jnp.float32)
        table = paged_kv.identity_table(b, n_p)
        return pool, table

    def _rows(self, b, n, feat=3, seed=0):
        rng = np.random.RandomState(seed)
        return jnp.asarray(rng.randn(b, n, feat), jnp.float32)

    def _sequential(self, pool, table, idx, rows):
        """Reference: append the same rows one position at a time."""
        for j in range(rows.shape[1]):
            pool = paged_kv.append(
                pool, table, idx + j, rows[:, j:j + 1],
                limit=jnp.ones((table.shape[0],), jnp.int32),
            )
        return pool

    def test_accept_all_block_equals_sequential(self):
        pool, table = self._pool()
        idx = jnp.asarray([1, 3], jnp.int32)
        rows = self._rows(2, 3)
        blk = paged_kv.append(
            pool, table, idx, rows, limit=jnp.asarray([3, 3], jnp.int32)
        )
        seq = self._sequential(pool, table, idx, rows)
        np.testing.assert_array_equal(np.asarray(blk), np.asarray(seq))

    def test_reject_all_rewind_overwrites_suffix(self):
        """Verify block A commits only its input token (accepted == 1);
        the corrective block B lands at idx+1 and must overwrite A's
        rejected positions — final pool equals sequential A[0], B."""
        pool, table = self._pool()
        idx = jnp.asarray([0, 2], jnp.int32)
        A = self._rows(2, 3, seed=1)
        B = self._rows(2, 3, seed=2)
        lim = jnp.asarray([3, 3], jnp.int32)
        specpool = paged_kv.append(pool, table, idx, A, limit=lim)
        specpool = paged_kv.append(specpool, table, idx + 1, B, limit=lim)
        seq = self._sequential(pool, table, idx, A[:, :1])
        seq = self._sequential(seq, table, idx + 1, B)
        np.testing.assert_array_equal(np.asarray(specpool), np.asarray(seq))

    def test_mixed_acceptance_per_row_and_idle_rows(self):
        """Row 0 accepts 2 of 3, row 1 accepts all, row 2 is IDLE
        (limit 0 — its pool rows must pass through untouched)."""
        pool, table = self._pool(b=3)
        marker = pool.at[2].set(7.0)  # idle row's pre-existing content
        idx = jnp.asarray([0, 1, 0], jnp.int32)
        A = self._rows(3, 3, seed=3)
        B = self._rows(3, 3, seed=4)
        specpool = paged_kv.append(
            marker, table, idx, A, limit=jnp.asarray([3, 3, 0], jnp.int32)
        )
        # row 0 accepted 2 -> next block at idx+2; row 1 accepted all 3
        # -> next at idx+3; row 2 still idle
        nxt = jnp.asarray([2, 4, 0], jnp.int32)
        specpool = paged_kv.append(
            specpool, table, nxt, B, limit=jnp.asarray([3, 3, 0], jnp.int32)
        )
        # reference: full A sequentially, then B overwriting the suffix
        ref = self._sequential(marker, table, idx, A)
        ref = self._sequential(ref, table, nxt, B)
        # idle row: marker content must survive both appends
        np.testing.assert_array_equal(
            np.asarray(specpool[2]), np.asarray(marker[2])
        )
        np.testing.assert_array_equal(
            np.asarray(specpool[:2]), np.asarray(ref[:2])
        )

    def test_block_crosses_page_boundary(self):
        """A verify block spanning a page boundary (page size 2, width 3
        from offset 1) lands bit-identically to sequential appends."""
        pool, table = self._pool(b=1, n_p=4, page=2)
        idx = jnp.asarray([1], jnp.int32)
        rows = self._rows(1, 3, seed=5)
        blk = paged_kv.append(
            pool, table, idx, rows, limit=jnp.asarray([3], jnp.int32)
        )
        seq = self._sequential(pool, table, idx, rows)
        np.testing.assert_array_equal(np.asarray(blk), np.asarray(seq))


# --------------------------------------------- engine-level bit parity


class TestSpecParity:
    def test_spec_bit_identical_exact_drafter(self, model):
        """THE acceptance contract: speculative engines (spec_k 2 and 3,
        full-depth exact drafter) produce tokens bit-identical to the
        split chunked, monolithic, and plain fused engines."""
        mono = completed_tokens(run_requests(model))
        split = completed_tokens(run_requests(model, prefill_chunk=2))
        fused = completed_tokens(run_requests(
            model, prefill_chunk=2, fused_iteration=True
        ))
        for spec_k in (2, 3):
            spec = completed_tokens(run_requests(model, **SPEC,
                                                 spec_k=spec_k))
            for rid, toks in mono.items():
                np.testing.assert_array_equal(split[rid], toks)
                np.testing.assert_array_equal(fused[rid], toks)
                np.testing.assert_array_equal(
                    spec[rid], toks,
                    err_msg=f"spec_k={spec_k} diverged for {rid}",
                )

    def test_exact_drafter_accepts_everything(self, model):
        """The full-depth drafter IS the target model, so exact-match
        acceptance must accept every draft (accept rate 1.0) — and the
        engine must therefore commit >1 token per verify step."""
        eng = run_requests(model, **SPEC, spec_k=3,
                           max_new=small_dalle().image_seq_len)
        assert eng._spec_drafted > 0
        assert eng._spec_accepted == eng._spec_drafted
        h = histograms.get("serve.spec_accepted_per_step")
        assert h is not None and h.count > 0

    def test_truncated_drafter_rejects_and_stays_bit_identical(
        self, deep_model
    ):
        """The depth-1 early-exit drafter of a depth-4 stack genuinely
        misdrafts — rollback is exercised (accepted < drafted) and the
        committed stream STILL matches plain decode bitwise."""
        split = completed_tokens(run_requests(
            deep_model, n=2, max_new=16, prefill_chunk=2,
            reqs=[req(i, max_new=16, p=prompt(i, width=6, vocab=32))
                  for i in range(2)],
        ))
        eng = run_requests(
            deep_model, n=2, max_new=16, **SPEC, spec_k=3,
            spec_draft_depth=1,
            reqs=[req(i, max_new=16, p=prompt(i, width=6, vocab=32))
                  for i in range(2)],
        )
        assert eng._spec_drafted > 0
        assert eng._spec_accepted < eng._spec_drafted, (
            "depth-1 drafter never rejected — the rollback path was "
            "not exercised"
        )
        spec = completed_tokens(eng)
        for rid, toks in split.items():
            np.testing.assert_array_equal(
                spec[rid], toks,
                err_msg=f"truncated-drafter stream diverged for {rid}",
            )

    def test_spec_preempt_replay_bit_identical(self, model):
        """A page_exhaust eviction mid-decode: the preempted request
        replays through the SPECULATIVE path bit-identically (the
        (seed, position) fold-in keys are position-anchored, so the
        replayed verify steps re-derive the same tokens)."""
        FAULTS.reset()
        counters.reset()
        clean = completed_tokens(run_requests(model, **SPEC, spec_k=2))
        FAULTS.configure("page_exhaust=1")
        try:
            eng = run_requests(model, **SPEC, spec_k=2)
        finally:
            FAULTS.reset()
        assert any(r.preempt_count > 0 for r in eng.results.values())
        for rid, toks in completed_tokens(eng).items():
            np.testing.assert_array_equal(toks, clean[rid])
        assert eng.pool.used == 0

    @pytest.mark.parametrize("spec_draft_depth", [None, 1])
    def test_spec_prefix_warm_hit_bit_identical(self, model,
                                                spec_draft_depth):
        """Prefix-cache warm hits compose with speculation: the warm
        round enters decode from the cached terminal logits and its
        VERIFY steps must still commit the cold round's exact stream."""
        counters.reset()
        cold_plain = completed_tokens(run_requests(model, prefill_chunk=2))
        eng = make_engine(model, prefix_cache=True, **SPEC, spec_k=2,
                          spec_draft_depth=spec_draft_depth)
        for i in range(3):
            assert eng.submit(req(i)) is None
        eng.run(max_steps=800)
        cold = completed_tokens(eng)
        hits0 = eng.prefix.stats.hits
        for i in range(3):
            assert eng.submit(req(i, rid=f"r{i}w")) is None
        eng.run(max_steps=800)
        check_accounting(eng)
        eng.verify_invariants(idle=True)
        assert eng.prefix.stats.hits > hits0, (
            "warm round never hit the prefix index"
        )
        warm = completed_tokens(eng)
        for i in range(3):
            np.testing.assert_array_equal(warm[f"r{i}w"], cold[f"r{i}"])
            np.testing.assert_array_equal(
                warm[f"r{i}w"], cold_plain[f"r{i}"],
                err_msg="spec+prefix stream diverged from plain split",
            )

    def test_spec_deadline_mid_decode_typed(self, model):
        """A deadline sweeping between speculative iterations terminates
        typed and returns the pages that iteration."""
        eng = make_engine(model, **SPEC, spec_k=2,
                          clock=FakeClock(step_dt=1.0))
        assert eng.submit(req(0, max_new=4, deadline=2.5)) is None
        eng.run(max_steps=100)
        check_accounting(eng)
        res = eng.results["r0"]
        assert res.outcome is Outcome.DEADLINE_EXCEEDED
        assert eng.pool.used == 0


# ------------------------------------------------- engine config gates


class TestSpecConfig:
    def test_spec_requires_fused_iteration(self, model):
        with pytest.raises(ValueError, match="fused_iteration"):
            make_engine(model, prefill_chunk=2, spec_decode=True)

    def test_spec_k_validated(self, model):
        with pytest.raises(ValueError, match="spec_k"):
            make_engine(model, **{**SPEC, "spec_k": 0})

    def test_spec_draft_depth_validated(self, model):
        with pytest.raises(ValueError, match="spec_draft_depth"):
            make_engine(model, **SPEC, spec_draft_depth=99)

    def test_budget_charges_verify_width(self):
        """The decode lane is charged the VERIFY width (device work):
        2 verify rows of width 3 consume the same budget as 6 plain
        decode rows, shrinking prefill grants accordingly."""
        tb = TokenBudget(budget=8, chunk=3)
        # plain: 2 decode tokens leave room for both chunks
        assert tb.plan_iteration(2, [3, 3]) == [True, True]
        # speculative: 2 rows * width 3 = 6 tokens; only the head chunk
        # keeps the forward-progress floor
        assert tb.plan_iteration(6, [3, 3]) == [True, False]
        # the floor survives even a fully spent budget
        assert tb.plan_iteration(8, [3, 3]) == [True, False]


# ------------------------------------------ dispatch/signature contract


class TestSpecDispatchContract:
    def test_flat_signature_and_multi_token_steps(self, model):
        """After one warm request compiles both signature classes, a
        mixed multi-request speculative trace compiles NOTHING new
        (verify widths/mixes are data), performs at most one dispatch
        per iteration, and — with the exact drafter — commits MORE
        tokens than it runs verify steps (the >1 accepted token per
        step the ISSUE's CPU record requires)."""
        counters.reset()
        eng = make_engine(model, **SPEC, spec_k=3)
        assert eng.submit(req(9, max_new=4)) is None
        eng.run(max_steps=200)
        sigs0 = engine_mod._spec_iteration_jit._cache_size()
        d0, i0 = eng.dispatches, eng.iterations
        steps0 = counters.get("serve.decode_steps")
        for i in range(3):
            assert eng.submit(req(i, max_new=4)) is None
        eng.run(max_steps=500)
        check_accounting(eng)
        assert engine_mod._spec_iteration_jit._cache_size() == sigs0, (
            "a speculative descriptor mix drifted the compile signature"
        )
        dispatches = eng.dispatches - d0
        iterations = eng.iterations - i0
        assert 0 < dispatches <= iterations, (dispatches, iterations)
        # decode-committed tokens only (the first token of each request
        # lands at the final prefill chunk, not a verify step)
        committed = sum(
            len(r.tokens) - 1 for rid, r in eng.results.items()
            if r.outcome is Outcome.COMPLETED and rid != "r9"
        )
        verify_steps = counters.get("serve.decode_steps") - steps0
        assert committed > verify_steps, (
            f"{committed} tokens over {verify_steps} verify steps — "
            "speculation never beat one token per step"
        )

    def test_spec_counters_and_gauge(self, model):
        counters.reset()
        gauges.reset()
        eng = run_requests(model, **SPEC, spec_k=2)
        drafted = counters.get("serve.spec.drafted")
        accepted = counters.get("serve.spec.accepted")
        rejected = counters.get("serve.spec.rejected")
        assert drafted == eng._spec_drafted > 0
        assert accepted == eng._spec_accepted
        assert drafted == accepted + rejected
        assert gauges.get("serve.spec_accept_frac") == pytest.approx(
            accepted / drafted
        )

    def test_trace_contract_pins_spec_and_page_copy(self):
        """The committed trace contract pins ``serving.iteration_spec``
        to EXACTLY the steady + final signature pair with the cache
        donated (DTL11x budget: descriptor raggedness must stay data),
        and the PR 10 follow-on copy jits to ONE donated fixed-shape
        signature each — the registry<->lowered-aliasing half is
        machine-checked by ``lint --trace --check``
        (tests/test_static_analysis.py); this pin keeps the contract's
        content from being weakened in a future re-emit."""
        contract = json.loads(
            (REPO / "tools" / "trace_contracts.json").read_text()
        )
        spec = contract["entries"]["serving.iteration_spec"]
        assert spec["max_signatures"] == 2
        assert [s["label"] for s in spec["signatures"]] == [
            "steady", "final"
        ]
        assert spec["donate"] == ["cache"]
        assert spec["max_host_callbacks"] == 0
        # the spec + prefix-cache composition: same program over the
        # arena-extended ring-widened cache, same two-signature budget
        arena = contract["entries"]["serving.iteration_spec_prefix"]
        assert arena["max_signatures"] == 2
        assert arena["donate"] == ["cache"]
        # one signature per cache tree: the plain prefix engine's arena
        # tree plus the speculative prefix engine's ring-widened one
        copy = contract["entries"]["serving.page_copy"]
        assert copy["max_signatures"] == 2
        assert [s["label"] for s in copy["signatures"]] == [
            "publish", "publish_spec"
        ]
        assert copy["donate"] == ["cache"]
        assert copy["max_host_visible_outputs"] == 0
        across = contract["entries"]["serving.page_copy_across"]
        assert across["max_signatures"] == 1
        assert across["donate"] == ["dst_cache"]
        assert across["max_host_visible_outputs"] == 0
        # the quantized prefix engine's int8 + scale-pool trees (ISSUE
        # 14) are their OWN entries — signature 0 of an entry is what
        # the audit genuinely lowers and alias-audits, so the quant
        # trees' extra scale leaves must prove their donation aliasing
        # here instead of silently loosening the shared 0 budget above
        for name, label in (
            ("serving.page_copy_quant", "publish_quant"),
            ("serving.page_copy_across_quant", "restore_quant"),
        ):
            q = contract["entries"][name]
            assert q["max_signatures"] == 1
            assert [s["label"] for s in q["signatures"]] == [label]
            assert q["max_host_visible_outputs"] == 0


# ------------------------------------------------ degraded-drafter drill


class TestSpecVerifyAbortDrill:
    def test_abort_degrades_one_iteration_bit_identical(self, model):
        """The ``spec_verify_abort`` drill: the drafter fails for ONE
        iteration; that iteration runs plain decode (verify width 1)
        through the same jit signature, the stream stays bit-identical,
        and EVERY request still ends in a typed outcome."""
        FAULTS.reset()
        counters.reset()
        clean = completed_tokens(run_requests(model, **SPEC, spec_k=2))
        sigs0 = engine_mod._spec_iteration_jit._cache_size()
        FAULTS.configure("spec_verify_abort=1")
        try:
            eng = run_requests(model, **SPEC, spec_k=2)
            fired = FAULTS.fired.get("spec_verify_abort")
        finally:
            FAULTS.reset()
        assert fired == 1
        assert counters.get("serve.spec.fallbacks") == 1
        assert counters.get("serve.fault_spec_verify_abort") == 1
        # the fallback is a width-1 verify row — same signature, no
        # recompile
        assert engine_mod._spec_iteration_jit._cache_size() == sigs0
        # 100% typed-outcome accounting: every submitted request ends in
        # a typed outcome (here: completed), none lost, none duplicated
        assert sorted(eng.results) == [f"r{i}" for i in range(3)]
        for rid, toks in completed_tokens(eng).items():
            np.testing.assert_array_equal(
                toks, clean[rid],
                err_msg=f"degraded iteration changed the stream of {rid}",
            )

    def test_abort_untaken_when_nothing_decodes(self, model):
        """Eligibility: the site is consulted only when decode slots
        exist, so an armed fault cannot silently expire during a
        prefill-only phase."""
        FAULTS.reset()
        eng = make_engine(model, **SPEC, spec_k=2, token_budget=1)
        FAULTS.arm("spec_verify_abort", 1)
        try:
            assert eng.submit(req(0)) is None
            eng.step()  # first chunk only: no decoding slot yet
            assert FAULTS.fired.get("spec_verify_abort") is None
            eng.run(max_steps=200)
            check_accounting(eng)
            assert FAULTS.fired.get("spec_verify_abort") == 1
        finally:
            FAULTS.reset()
        assert eng.results["r0"].outcome is Outcome.COMPLETED
