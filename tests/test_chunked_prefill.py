"""Chunked prefill + one-step-lookahead decode — the iteration-scheduler
contracts pinned deterministically on CPU:

- model-level BIT-parity: any sequence of ``DALLE.prefill_chunk`` calls
  covering the prompt (widths >= 2, ragged tails included) produces a
  cache and final logits bitwise identical to one monolithic
  ``prefill_step``;
- engine-level BIT-parity: chunked and monolithic engines, lookahead on
  and off, all sample identical tokens — and preempt-and-requeue replay
  stays bit-identical with chunking and lookahead on;
- the ``TokenBudget`` policy: decode charged first, chunk-quantum grants,
  head-of-line order, forward-progress floor;
- chunk-granular faults: ``prefill_fail`` fires per chunk, retry resumes
  from the last COMPLETED chunk (never from scratch), attempts exhaust to
  the typed outcome; deadlines and cancellation land BETWEEN chunks with
  pages freed that same iteration;
- TTFT accounting: set at first-token production, once per request,
  carried in the result and the ``serve.ttft_s`` histogram.

Page size 2 (env override), as in tests/test_serving.py, so the tiny
model exercises real page-boundary growth.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import DALLE, init_decode_cache
from dalle_pytorch_tpu.models.sampling import set_decode_offsets
from dalle_pytorch_tpu.serving import (
    Engine,
    EngineConfig,
    FakeClock,
    Outcome,
    Request,
    TokenBudget,
    check_accounting,
)
from dalle_pytorch_tpu.utils.faults import FAULTS
from dalle_pytorch_tpu.utils.metrics import counters, histograms


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


def run_requests(model, n=3, max_new=4, **cfg_kw):
    eng = make_engine(model, **cfg_kw)
    for i in range(n):
        assert eng.submit(req(i, max_new=max_new)) is None
    eng.run(max_steps=500)
    check_accounting(eng)
    return eng


def tokens_of(eng):
    return {
        rid: None if r.tokens is None else np.asarray(r.tokens)
        for rid, r in eng.results.items()
    }


# -------------------------------------------------- TokenBudget (pure)


class TestTokenBudget:
    def test_decode_charged_first_then_chunk_quanta(self):
        tb = TokenBudget(budget=10, chunk=4)
        # 3 decode tokens leave 7: one full chunk + the 3-token remainder
        # of the first prefill, nothing for the second
        assert tb.plan(3, [7, 8]) == [7, 0]

    def test_grants_follow_head_of_line(self):
        tb = TokenBudget(budget=10, chunk=4)
        assert tb.plan(0, [4, 8]) == [4, 4]
        assert tb.plan(0, [12, 8]) == [8, 0]

    def test_forward_progress_floor(self):
        """Decode saturating the budget must not deadlock prefill: the
        head prefill still gets exactly one chunk."""
        tb = TokenBudget(budget=4, chunk=4)
        assert tb.plan(4, [12, 8]) == [4, 0]
        assert tb.plan(400, [12]) == [4]

    def test_ragged_tail_granted(self):
        tb = TokenBudget(budget=16, chunk=4)
        assert tb.plan(0, [6]) == [6]  # 4 + the 2-token tail

    def test_unbounded_budget(self):
        tb = TokenBudget(budget=None, chunk=4)
        assert tb.plan(99, [12, 5]) == [12, 5]

    def test_engine_rejects_one_token_chunks(self, model):
        with pytest.raises(ValueError, match="prefill_chunk"):
            make_engine(model, prefill_chunk=1)


# ------------------------------------------- model-level bit parity


class TestPrefillChunkParity:
    @pytest.mark.parametrize("rotary", [True, False])
    def test_chunkings_bit_identical_to_monolithic(self, rotary):
        """THE tentpole contract at the model layer: every multi-token
        chunking of the prompt — including a ragged final chunk — writes a
        cache and produces final logits BITWISE identical to one
        monolithic prefill_step."""
        dalle = small_dalle(rotary_emb=rotary)
        rng = np.random.RandomState(0)
        text = jnp.asarray(rng.randint(1, 16, size=(1, 4)), jnp.int32)
        image = jnp.asarray(rng.randint(0, 12, size=(1, 4)), jnp.int32)
        params = dalle.init(jax.random.key(0), text, image)["params"]
        internal = dalle.remap_text(text)
        T = dalle.text_len_internal  # 5
        fresh = set_decode_offsets(
            init_decode_cache(dalle, params, 1, cache_format="paged"),
            jnp.zeros((1,), jnp.int32),
        )
        logits_m, mut = dalle.apply(
            {"params": params, "cache": fresh}, internal,
            image_only=True, method=DALLE.prefill_step, mutable=["cache"],
        )
        cache_m = mut["cache"]

        for chunks in ((2, 3), (3, 2), (5,)):
            assert sum(chunks) == T
            cache = fresh
            s = 0
            for c in chunks:
                final = s + c == T
                logits, mut = dalle.apply(
                    {"params": params, "cache": cache},
                    internal[:, s:s + c], jnp.int32(s),
                    return_logits=final, image_only=final,
                    method=DALLE.prefill_chunk, mutable=["cache"],
                )
                cache = mut["cache"]
                s += c
            for (pm, lm), (pc, lc) in zip(
                jax.tree_util.tree_leaves_with_path(cache_m),
                jax.tree_util.tree_leaves_with_path(cache),
            ):
                assert bool(jnp.all(lm == lc)), (
                    f"cache leaf {pm} diverged for chunking {chunks}"
                )
            np.testing.assert_array_equal(
                np.asarray(logits), np.asarray(logits_m),
                err_msg=f"final logits diverged for chunking {chunks}",
            )

    def test_image_only_head_matches_full_head_slice(self, model):
        """prefill's image_only head is the full head's [ext:] slice,
        bitwise — the serving engine samples from it in both the
        monolithic and chunked paths."""
        dalle, params = model
        internal = dalle.remap_text(jnp.asarray(prompt(0)[None], jnp.int32))
        fresh = set_decode_offsets(
            init_decode_cache(dalle, params, 1, cache_format="paged"),
            jnp.zeros((1,), jnp.int32),
        )
        full, _ = dalle.apply(
            {"params": params, "cache": fresh}, internal,
            method=DALLE.prefill_step, mutable=["cache"],
        )
        img, _ = dalle.apply(
            {"params": params, "cache": fresh}, internal,
            image_only=True, method=DALLE.prefill_step, mutable=["cache"],
        )
        np.testing.assert_array_equal(
            np.asarray(img),
            np.asarray(full[:, dalle.num_text_tokens_ext:]),
        )


# ------------------------------------------- engine-level bit parity


class TestChunkedEngineParity:
    def test_chunked_vs_monolithic_bit_identical(self, model):
        """Acceptance: chunked prefill at several chunk sizes (2 -> ragged
        3-token tail; 3 -> ragged 2-token tail; 4 -> the 1-token-tail
        merge rule collapses to one width-5 chunk) produces tokens
        bit-identical to the monolithic engine."""
        mono = tokens_of(run_requests(model))
        for chunk in (2, 3, 4):
            chunked = tokens_of(run_requests(model, prefill_chunk=chunk))
            for rid, toks in mono.items():
                np.testing.assert_array_equal(
                    chunked[rid], toks,
                    err_msg=f"chunk={chunk} diverged for {rid}",
                )

    def test_lookahead_off_parity(self, model):
        base = tokens_of(run_requests(model))
        for cfg in (
            dict(decode_lookahead=False),
            dict(decode_lookahead=False, prefill_chunk=2),
        ):
            got = tokens_of(run_requests(model, **cfg))
            for rid, toks in base.items():
                np.testing.assert_array_equal(got[rid], toks, err_msg=str(cfg))

    def test_preempt_replay_bit_identical_chunked_lookahead(self, model):
        """Acceptance: preempt-and-requeue replay stays BIT-identical with
        chunked prefill AND lookahead decode on (the (seed, position) keys
        make tokens independent of when they are sampled or read back)."""
        FAULTS.reset()
        counters.reset()
        clean = tokens_of(run_requests(model, prefill_chunk=2))
        FAULTS.configure("page_exhaust=1")
        eng = run_requests(model, prefill_chunk=2)
        assert FAULTS.fired.get("page_exhaust") == 1
        assert counters.get("serve.preempted") >= 1
        assert any(r.preempt_count > 0 for r in eng.results.values())
        for rid, r in eng.results.items():
            assert r.outcome is Outcome.COMPLETED, (rid, r)
            np.testing.assert_array_equal(np.asarray(r.tokens), clean[rid])
        assert eng.pool.used == 0


# --------------------------------------- chunk-granular fault drills


class TestChunkFaults:
    def test_chunk_fault_resumes_from_last_completed_chunk(self, model):
        """A prefill_fail mid-prompt must NOT restart the prefill: the
        already-written chunks survive and the retry resumes exactly at
        the failed chunk."""
        FAULTS.reset()
        counters.reset()
        clean = tokens_of(run_requests(model, n=1, prefill_chunk=2,
                                       token_budget=1))
        # token_budget=1 -> exactly one chunk per iteration (the
        # forward-progress floor); T=5 chunks as (2, 3)
        eng = make_engine(model, prefill_chunk=2, token_budget=1)
        assert eng.submit(req(0)) is None
        eng.step()  # claim + first chunk
        slot = next(s for s in eng.slots if s)
        assert slot.phase == "prefill" and slot.filled == 2
        FAULTS.arm("prefill_fail", 1)
        eng.step()  # the FINAL chunk faults
        assert FAULTS.fired.get("prefill_fail") == 1
        slot = next(s for s in eng.slots if s)
        assert slot.filled == 2, "progress was rolled back on a chunk fault"
        eng.run(max_steps=200)
        check_accounting(eng)
        res = eng.results["r0"]
        assert res.outcome is Outcome.COMPLETED
        assert res.prefill_attempts == 1
        assert counters.get("serve.prefill_retries") == 1
        # 2 chunks in the clean run + 2 in the faulted run: the fault cost
        # a retry ITERATION but zero re-run chunks — resume, not restart —
        # and the tokens still match the clean run bit-for-bit
        assert counters.get("serve.prefill_chunks") == 4
        np.testing.assert_array_equal(np.asarray(res.tokens), clean["r0"])

    def test_chunk_fault_exhausts_attempts_typed(self, model):
        FAULTS.reset()
        FAULTS.arm("prefill_fail", 5)
        eng = make_engine(model, prefill_chunk=2, prefill_attempts=2)
        assert eng.submit(req(0)) is None
        eng.run(max_steps=200)
        check_accounting(eng)
        res = eng.results["r0"]
        assert res.outcome is Outcome.PREFILL_FAILED
        assert res.prefill_attempts == 2
        assert res.tokens is None
        assert eng.pool.used == 0

    def test_mid_prefill_deadline_frees_pages_that_iteration(self, model):
        """Acceptance: a deadline arriving mid-prefill terminates BETWEEN
        chunks, with the pages back in the pool the iteration the deadline
        sweeps — not at the end of the prompt."""
        eng = make_engine(model, prefill_chunk=2, token_budget=1,
                          clock=FakeClock(step_dt=1.0))
        assert eng.submit(req(0, deadline=0.5)) is None
        eng.step()  # t=0: claim + first chunk; prompt pages held
        assert eng.pool.used > 0
        slot = next(s for s in eng.slots if s)
        assert slot.phase == "prefill" and 0 < slot.filled < eng.T
        eng.step()  # t=1 > deadline: sweeps mid-prefill
        assert eng.pool.used == 0, "mid-prefill deadline did not free pages"
        res = eng.results["r0"]
        assert res.outcome is Outcome.DEADLINE_EXCEEDED
        assert res.tokens is None  # never produced a token
        assert res.ttft_s is None
        eng.run(max_steps=50)
        check_accounting(eng)

    def test_cancel_mid_prefill(self, model):
        eng = make_engine(model, prefill_chunk=2, token_budget=1)
        assert eng.submit(req(0)) is None
        eng.step()
        slot = next(s for s in eng.slots if s)
        assert slot.phase == "prefill"
        eng.cancel("r0")
        eng.step()
        assert eng.pool.used == 0
        res = eng.results["r0"]
        assert res.outcome is Outcome.CANCELLED
        assert res.tokens is None
        eng.run(max_steps=50)
        check_accounting(eng)

    def test_combined_overload_chunked_all_accounted(self, model):
        """Acceptance: the combined overload + mid-prefill-deadline +
        chunk-fault drill — aggregate demand far over the pool, a bounded
        queue, deadlines tight enough to land mid-prefill (token_budget=1
        stretches every prefill across iterations), and injected
        page_exhaust + chunk-granular prefill_fail. Every submitted
        request must end in exactly one typed outcome, counters sum to
        100%, and the pool drains."""
        FAULTS.reset()
        counters.reset()
        FAULTS.configure("page_exhaust=1,prefill_fail=2")
        clock = FakeClock(step_dt=1.0)
        eng = make_engine(
            model, clock=clock, max_batch=2, page_budget=7, queue_limit=3,
            prefill_attempts=3, prefill_chunk=2, token_budget=1,
        )
        immediate = []
        for i in range(8):
            r = eng.submit(req(
                i, max_new=4,
                deadline=None if i % 2 else 2.0 + 3 * i,
                priority=i % 3,
            ))
            if r is not None:
                immediate.append(r)
        eng.run(max_steps=1000)
        check_accounting(eng)
        outcomes = eng.stats()["outcomes"]
        assert sum(outcomes.values()) == 8
        assert outcomes["rejected"] == len(immediate) > 0
        assert outcomes["deadline_exceeded"] >= 1  # the tight deadlines bit
        assert FAULTS.fired.get("prefill_fail") == 2
        assert FAULTS.fired.get("page_exhaust") == 1
        assert eng.pool.used == 0
        for r in eng.results.values():
            assert r.outcome in (
                Outcome.COMPLETED, Outcome.REJECTED,
                Outcome.DEADLINE_EXCEEDED, Outcome.PREEMPT_CAP,
                Outcome.CANCELLED, Outcome.PREFILL_FAILED,
            ), r


# ------------------------------------------------------------- TTFT


class TestInterference:
    def test_late_prompt_prefill_is_spread_over_decoding_iterations(self, model):
        """A prompt arriving during steady decode: monolithic prefill puts
        the whole prompt into ONE iteration (the decode-gap a chunked engine
        exists to shrink); chunked prefill spreads it over several, and the
        steady request decodes in every one of them."""
        def window(prefill_chunk):
            counters.reset()
            histograms.reset()
            eng = make_engine(model, prefill_chunk=prefill_chunk)
            assert eng.submit(req(0, max_new=4)) is None
            while counters.get("serve.decode_steps") < 1:
                eng.step()  # steady request admitted and decoding
            assert eng.submit(req(1, max_new=2)) is None
            decoded0, iterations = counters.get("serve.decode_steps"), 0
            while histograms.get("serve.ttft_s").count < 2:
                eng.step()  # ... until the late request's first token
                iterations += 1
            decoded = counters.get("serve.decode_steps") - decoded0
            eng.run(max_steps=500)
            check_accounting(eng)
            return iterations, decoded

        assert window(None) == (1, 1)
        iterations, decoded = window(2)
        assert iterations > 1 and decoded == iterations


class TestTtft:
    def test_ttft_in_results_and_histogram(self, model):
        counters.reset()
        histograms.reset()
        eng = run_requests(model, prefill_chunk=2)
        for r in eng.results.values():
            assert r.outcome is Outcome.COMPLETED
            assert r.ttft_s is not None and r.ttft_s >= 0
            # first token lands at or after admission
            assert r.ttft_s >= r.queue_latency_s
            assert "ttft_s" in r.to_json()
        h = histograms.get("serve.ttft_s")
        assert h is not None and h.count == 3  # once per request

    def test_ttft_survives_preemption(self, model):
        """A preempted-and-replayed request keeps its ORIGINAL ttft: the
        replay regenerates the same first token bit-identically, so the
        client-visible first production is the honest latency."""
        FAULTS.reset()
        FAULTS.arm("page_exhaust", 1)
        eng = run_requests(model)
        preempted = [
            r for r in eng.results.values() if r.preempt_count > 0
        ]
        assert preempted
        for r in preempted:
            assert r.ttft_s is not None
            # requeued AFTER its first token: the recorded ttft predates
            # the final admission's queue latency
            assert r.ttft_s <= r.total_latency_s
