"""Cross-request prefix caching (ISSUE 10, ROADMAP 3) — the
content-addressed page index pinned deterministically on CPU:

- chain-index unit behavior: hash-chain addressing with mandatory
  token verification, refcount acquire/release symmetry, leaf-first LRU
  eviction that never victimizes a referenced or interior node, arena
  accounting;
- BIT-parity: a cache-hit request's tokens are identical to the same
  request run cold — full hits (prefill skipped entirely), partial hits
  (chunked resume at the miss boundary), monolithic fallback, across the
  split and fused engines;
- copy-on-write: the partial terminal page is privatized at map time
  (``serve.prefix.cow_copies``); concurrent divergence leaves both the
  diverging request's private copy and the survivor's shared page
  bit-identical vs their cold runs;
- preemption discipline: evicting a cache-hit request drops REFERENCES,
  never arena content — replay and the surviving sibling both stay
  bit-identical, and later requests still hit the same pages;
- the index as eviction tier: unreferenced LRU pages are reclaimed for
  admission BEFORE any running request is preempted;
- fault drills: ``prefix_hash_collide`` (verification rejects the forged
  node, cold fallback, bit-identical tokens) and ``prefix_publish_fail``
  (fail-open: request completes, nothing published);
- refcount accounting in ``Engine.verify_invariants`` mid-flight and at
  drain (the index SURVIVES drain; no request page leaks).

Page size 2 (env override), as in tests/test_serving.py, so the tiny
model's T=5 prompt spans 3 pages with a partial terminal page — the COW
case — and decode crosses page boundaries mid-flight.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import DALLE
from dalle_pytorch_tpu.serving import (
    Engine,
    EngineConfig,
    FakeClock,
    Outcome,
    Request,
)
from dalle_pytorch_tpu.serving.engine import PREFIX_HOLDER
from dalle_pytorch_tpu.serving.prefix_cache import (
    PrefixCache,
    chain_blocks,
)
from dalle_pytorch_tpu.utils.faults import FAULTS
from dalle_pytorch_tpu.utils.metrics import counters, gauges, histograms


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


def run_all(engine, reqs, steps=800):
    for r in reqs:
        assert engine.submit(r) is None
    engine.run(max_steps=steps)
    return {k: list(v.tokens) for k, v in engine.results.items()}


# engine-mode axis shared by the parity suites: monolithic split,
# chunked split, fused (fused requires chunking)
MODES = [
    pytest.param(dict(), id="split-monolithic"),
    pytest.param(dict(prefill_chunk=2), id="split-chunked"),
    pytest.param(dict(prefill_chunk=2, fused_iteration=True), id="fused"),
]


# --------------------------------------------------- chain index (pure)


class TestChainIndex:
    def test_chain_blocks_terminal_partial(self):
        toks = np.arange(5)
        blocks = chain_blocks(toks, 2)
        assert [list(b) for b in blocks] == [[0, 1], [2, 3], [4]]
        # page-aligned prompts have no partial terminal
        assert [len(b) for b in chain_blocks(np.arange(4), 2)] == [2, 2]

    def _publish_chain(self, cache, toks, now=0.0):
        parent = None
        out = []
        for k, block in enumerate(chain_blocks(toks, cache.page_size)):
            page = cache.alloc_page()
            assert page is not None
            parent = cache.insert(
                parent, block, start=k * cache.page_size,
                page_id=page, now=now, ring=object(),
            )
            out.append(parent)
        return out

    def test_probe_matches_shared_prefix_only(self):
        cache = PrefixCache(range(10, 18), page_size=2)
        self._publish_chain(cache, np.asarray([1, 2, 3, 4, 5]))
        # identical prompt: all three nodes, in chain order
        hit = cache.probe(np.asarray([1, 2, 3, 4, 5]), now=1.0)
        assert [n.start for n in hit] == [0, 2, 4]
        assert all(n.last_hit == 1.0 for n in hit)
        # divergence mid-page 1: only the first page matches
        hit = cache.probe(np.asarray([1, 2, 9, 4, 5]), now=2.0)
        assert [n.start for n in hit] == [0]
        # divergence in page 0: nothing
        assert cache.probe(np.asarray([9, 2, 3, 4, 5]), now=3.0) == []
        assert cache.stats.hits == 2 and cache.stats.misses == 1

    def test_probe_verifies_tokens_not_just_hash(self):
        """A forged node at the right digest must be rejected by token
        verification — the hash is an address, never a proof."""
        cache = PrefixCache(range(4), page_size=2)
        (node,) = self._publish_chain(cache, np.asarray([1, 2]))
        # corrupt the stored block in place: the digest still matches the
        # query chain, the contents no longer do
        node.tokens = np.asarray([3, 4], np.int64)
        assert cache.probe(np.asarray([1, 2]), now=1.0) == []
        assert cache.stats.collisions == 1

    def test_refcounts_block_eviction(self):
        cache = PrefixCache(range(8), page_size=2)
        nodes = self._publish_chain(cache, np.asarray([1, 2, 3, 4]))
        cache.acquire(nodes, now=1.0)
        assert cache.evictable() == []
        assert cache.evict_one() is None
        cache.release(nodes)
        # interior node still shielded by its child: leaf-first
        assert [n.start for n in cache.evictable()] == [2]
        assert cache.evict_one().start == 2
        assert cache.evict_one().start == 0
        assert cache.evict_one() is None
        assert cache.free_arena_pages == 8
        cache.verify_invariants()

    def test_eviction_is_lru_by_last_hit(self):
        cache = PrefixCache(range(8), page_size=2)
        self._publish_chain(cache, np.asarray([1, 2]), now=0.0)
        self._publish_chain(cache, np.asarray([5, 6]), now=0.0)
        cache.probe(np.asarray([1, 2]), now=5.0)  # touch chain 1
        assert cache.evict_one().tokens.tolist() == [5, 6]

    def test_release_underflow_asserts(self):
        cache = PrefixCache(range(4), page_size=2)
        nodes = self._publish_chain(cache, np.asarray([1, 2]))
        with pytest.raises(AssertionError):
            cache.release(nodes)

    def test_insert_dedup_violation_asserts(self):
        cache = PrefixCache(range(4), page_size=2)
        self._publish_chain(cache, np.asarray([1, 2]))
        with pytest.raises(AssertionError):
            cache.insert(None, np.asarray([1, 2]), 0, cache.alloc_page(), 0.0)

    def test_upgrade_fills_only_missing_payloads(self):
        cache = PrefixCache(range(4), page_size=2)
        page = cache.alloc_page()
        node = cache.insert(None, np.asarray([1, 2]), 0, page, now=0.0)
        ring1, logits1 = object(), object()
        cache.upgrade(node, ring=ring1, logits=logits1)
        assert node.ring is ring1 and node.logits is logits1
        cache.upgrade(node, ring=object(), logits=object())
        assert node.ring is ring1 and node.logits is logits1  # never replaced

    def test_arena_exhaustion_and_return(self):
        cache = PrefixCache(range(2), page_size=2)
        a, b = cache.alloc_page(), cache.alloc_page()
        assert cache.alloc_page() is None
        cache.return_page(a)
        assert cache.alloc_page() == a


# ------------------------------------------------------- full-hit parity


class TestFullHitParity:
    @pytest.mark.parametrize("mode", MODES)
    def test_warm_tokens_bit_identical_to_cold(self, model, mode):
        cold = run_all(make_engine(model, **mode), [req(0), req(1)])
        eng = make_engine(model, prefix_cache=True, **mode)
        run_all(eng, [req(0)])
        assert counters.get("serve.prefix.misses") == 1
        warm = run_all(eng, [req(0, rid="r0w"), req(1, rid="r1")])
        assert warm["r0w"] == cold["r0"], "full-hit tokens diverged"
        assert warm["r1"] == cold["r1"], "cold sibling diverged"
        assert counters.get("serve.prefix.hits") == 1
        assert eng.prefix.stats.hits == 1
        eng.verify_invariants(idle=True)

    @pytest.mark.parametrize("mode", MODES)
    def test_full_hit_skips_prefill(self, model, mode, monkeypatch):
        """The full-hit request runs NO prefill of its own: the split
        prefill jits are unreachable during the warm run (poisoned here),
        and its dispatch bill — the cached-logits sample plus decode
        steps — never exceeds the cold request's (strictly fewer in the
        chunked modes, whose cold prefill rides extra iterations)."""
        from dalle_pytorch_tpu.serving import engine as engine_mod

        eng = make_engine(model, prefix_cache=True, **mode)
        run_all(eng, [req(0)])
        d_cold = eng.dispatches

        def poisoned(*a, **k):
            raise AssertionError("full hit ran a prefill jit")

        for name in ("_prefill_jit", "_prefill_chunk_jit",
                     "_prefill_last_jit"):
            monkeypatch.setattr(engine_mod, name, poisoned)
        run_all(eng, [req(0, rid="r0w")])
        d_warm = eng.dispatches - d_cold
        assert eng.results["r0w"].outcome is Outcome.COMPLETED
        if mode:  # chunked modes: cold prefill cost extra dispatches
            assert d_warm < d_cold, (d_warm, d_cold)
        else:
            assert d_warm <= d_cold, (d_warm, d_cold)
        assert counters.get("serve.prefix.hits") == 1

    def test_ttft_histogram_split_by_hit_class(self, model):
        eng = make_engine(model, prefix_cache=True)
        run_all(eng, [req(0)])
        assert histograms.get("serve.ttft_cold_s").count == 1
        run_all(eng, [req(0, rid="r0w")])
        assert histograms.get("serve.ttft_full_hit_s").count == 1
        assert histograms.get("serve.ttft_cold_s").count == 1
        assert gauges.get("serve.prefix_hit_frac") == 0.5

    def test_index_survives_drain_and_accounts_pages(self, model):
        """The cache's purpose is CROSS-request reuse: after every request
        drains, the index still holds its pages (charged to the pool) and
        a later identical request still hits."""
        eng = make_engine(model, prefix_cache=True)
        run_all(eng, [req(0)])
        eng.verify_invariants(idle=True)
        n = len(eng.prefix)
        assert n == 3  # T=5, page 2 -> 3 chain pages
        assert eng.pool.held(PREFIX_HOLDER) == n
        assert eng.pool.used == n
        run_all(eng, [req(0, rid="r0w")])
        assert counters.get("serve.prefix.hits") == 1
        eng.verify_invariants(idle=True)


# ---------------------------------------------------- partial-hit parity


def diverge_at(base, j, delta=1):
    """A copy of ``base`` differing exactly at prompt index ``j``."""
    p = np.asarray(base).copy()
    p[j] = ((p[j] - 1 + delta) % 15) + 1
    return p


class TestPartialHitParity:
    @pytest.mark.parametrize("mode", [MODES[1], MODES[2]])
    def test_shared_page_resume_bit_identical(self, model, mode):
        """A prompt sharing one full page with a published chain resumes
        chunked prefill at the miss boundary; tokens match its cold run
        bitwise. Internal row = [bos, t0, t1, t2, t3]: diverging at
        prompt index 2 shares internal positions 0..2 -> chain page 0."""
        pB = diverge_at(prompt(0), 2)
        cold = run_all(
            make_engine(model, **mode), [req(7, rid="rB", p=pB, seed=7)]
        )
        eng = make_engine(model, prefix_cache=True, **mode)
        run_all(eng, [req(0)])
        warm = run_all(eng, [req(7, rid="rB", p=pB, seed=7)])
        assert warm["rB"] == cold["rB"], "partial-hit tokens diverged"
        assert counters.get("serve.prefix.hits") == 1
        assert counters.get("serve.prefix.pages_hit") == 1
        eng.verify_invariants(idle=True)

    def test_monolithic_partial_falls_back_cold(self, model):
        """A split engine without chunking cannot resume mid-prompt: a
        partial chain match is a MISS (no refs leaked) and the request
        runs a full cold prefill, bit-identical."""
        pB = diverge_at(prompt(0), 2)
        cold = run_all(make_engine(model), [req(7, rid="rB", p=pB, seed=7)])
        eng = make_engine(model, prefix_cache=True)
        run_all(eng, [req(0)])
        warm = run_all(eng, [req(7, rid="rB", p=pB, seed=7)])
        assert warm["rB"] == cold["rB"]
        assert counters.get("serve.prefix.hits") == 0
        assert counters.get("serve.prefix.misses") == 2
        assert eng.prefix.total_refs() == 0
        eng.verify_invariants(idle=True)


# ------------------------------------------------------------------ COW


class TestCopyOnWrite:
    @pytest.mark.parametrize("mode", MODES)
    def test_partial_terminal_page_is_privatized(self, model, mode):
        """T=5 is not page-aligned: a full hit COWs the terminal page at
        map time (the first decode write lands inside it), so decode
        never touches arena storage. Counter pinned, and a THIRD
        identical request still hits the unmodified shared pages."""
        cold = run_all(make_engine(model, **mode), [req(0)])
        eng = make_engine(model, prefix_cache=True, **mode)
        run_all(eng, [req(0)])
        warm1 = run_all(eng, [req(0, rid="w1")])
        assert counters.get("serve.prefix.cow_copies") == 1
        warm2 = run_all(eng, [req(0, rid="w2")])
        assert counters.get("serve.prefix.cow_copies") == 2
        assert warm1["w1"] == cold["r0"]
        assert warm2["w2"] == cold["r0"], (
            "decode through the COW'd page corrupted the shared terminal"
        )
        eng.verify_invariants(idle=True)

    @pytest.mark.parametrize("mode", [MODES[1], MODES[2]])
    def test_concurrent_divergence_mid_page(self, model, mode):
        """Two CONCURRENT warm requests over a published prefix, one
        identical (full hit) and one diverging mid-page (partial hit up
        to the divergent page): both must match their cold runs bitwise
        — the diverging request's private pages and the survivor's
        shared mapping never alias."""
        pB = diverge_at(prompt(0), 2)
        reqs = lambda: [  # noqa: E731 - fresh Request objects per engine
            req(0, rid="rA"),
            req(7, rid="rB", p=pB, seed=7),
        ]
        cold = run_all(make_engine(model, **mode), reqs())
        eng = make_engine(model, prefix_cache=True, **mode)
        run_all(eng, [req(0)])  # publisher
        warm = run_all(eng, reqs())
        assert warm["rA"] == cold["rA"], "full-hit request diverged"
        assert warm["rB"] == cold["rB"], "diverging request diverged"
        assert counters.get("serve.prefix.hits") == 2
        eng.verify_invariants(idle=True)


# -------------------------------------------- preemption of shared pages


class TestPreemptionOfSharedPages:
    @pytest.mark.parametrize("mode", MODES)
    def test_preempted_hit_replays_and_sibling_survives(self, model, mode):
        """Preempt-and-requeue of a request MAPPING shared pages: the
        eviction drops references only (arena content untouched —
        ``paged_kv.reset_rows`` guard), replay is bit-identical, the
        concurrently running cold sibling is bit-identical, and a LATER
        warm request still hits the same pages bit-identically."""
        cold = run_all(make_engine(model, **mode), [req(0), req(1)])
        eng = make_engine(model, prefix_cache=True, **mode)
        run_all(eng, [req(0)])
        FAULTS.arm("page_exhaust", 1)
        warm = run_all(eng, [req(0, rid="r0w"), req(1, rid="r1")])
        assert FAULTS.fired.get("page_exhaust") == 1
        assert counters.get("serve.preempted") >= 1
        assert warm["r0w"] == cold["r0"], "replayed hit diverged"
        assert warm["r1"] == cold["r1"], "sibling diverged after eviction"
        eng.verify_invariants(idle=True)
        later = run_all(eng, [req(0, rid="r0x")])
        assert later["r0x"] == cold["r0"], (
            "arena pages corrupted by the eviction reset"
        )
        eng.verify_invariants(idle=True)

    def test_release_asserts_slot_row_bound(self, model):
        """The release reset may only name SLOT rows — an arena row
        through this path would zero shared content for every holder."""
        eng = make_engine(model, prefix_cache=True)
        run_all(eng, [req(0)])
        assert eng.submit(req(0, rid="r0w", max_new=4)) is None
        eng.step()
        slot = next(s for s in eng.slots if s is not None)
        slot.index = eng.config.max_batch  # forge an arena row index
        with pytest.raises(AssertionError, match="arena rows"):
            eng._release_slot(slot)


# --------------------------------------------------- index eviction tier


class TestIndexEvictionTier:
    def test_admission_reclaims_index_before_preempting(self, model):
        """Pool pressure at admission: LRU unreferenced index pages are
        dropped to admit the newcomer; no running request is preempted."""
        n_slot = 5  # pages_for(5 + 4, 2)
        eng = make_engine(
            model, prefix_cache=True, page_budget=n_slot + 4,
            prefix_cache_pages=5, max_batch=1,
        )
        run_all(eng, [req(0)])
        assert len(eng.prefix) == 3
        # distinct prompt: worst case 5 pages, free = 9 - 3(index) = 6
        # ... admits without reclaim; shrink the window with a second
        # publisher first
        run_all(eng, [req(1, rid="q1")])
        assert len(eng.prefix) in (5, 6)  # arena cap may already bite
        free0 = eng.pool.free
        run_all(eng, [req(2, rid="q2")])
        assert eng.results["q2"].outcome is Outcome.COMPLETED
        assert counters.get("serve.prefix.evictions") >= 1, (
            f"admission (free={free0}) should have reclaimed index pages"
        )
        assert counters.get("serve.preempted") == 0, (
            "index reclaim must come BEFORE preemption"
        )
        eng.verify_invariants(idle=True)

    def test_publish_fails_open_when_arena_full_and_referenced(self, model):
        """An arena too small for a second chain whose pages are all
        REFERENCED cannot evict: publish skips fail-open and the request
        still completes."""
        eng = make_engine(
            model, prefix_cache=True, prefix_cache_pages=3, max_batch=2,
        )
        run_all(eng, [req(0)])
        n0 = len(eng.prefix)
        assert n0 >= 1
        # second distinct prompt publishes into a full arena: LRU evicts
        # the first chain leaf-first OR skips — either way accounting holds
        run_all(eng, [req(1, rid="q1")])
        assert eng.results["q1"].outcome is Outcome.COMPLETED
        total = counters.get("serve.prefix.evictions") + counters.get(
            "serve.prefix.publish_skips"
        )
        assert total >= 1
        eng.verify_invariants(idle=True)


# ----------------------------------------------------------- fault drills


class TestFaultDrills:
    def test_prefix_hash_collide_falls_back_cold(self, model):
        """A forged index lookup (hash collision) must be rejected by
        token verification: the engine runs a cold prefill and the tokens
        are bit-identical to an uncached run."""
        cold = run_all(make_engine(model), [req(0)])
        eng = make_engine(model, prefix_cache=True)
        run_all(eng, [req(0)])
        FAULTS.arm("prefix_hash_collide", 1)
        warm = run_all(eng, [req(0, rid="r0c")])
        assert FAULTS.fired.get("prefix_hash_collide") == 1
        assert counters.get("serve.fault_prefix_hash_collide") == 1
        assert eng.prefix.stats.collisions == 1
        assert warm["r0c"] == cold["r0"], (
            "collision fallback served another prompt's K/V"
        )
        eng.verify_invariants(idle=True)

    def test_prefix_publish_fail_is_fail_open(self, model):
        eng = make_engine(model, prefix_cache=True)
        FAULTS.arm("prefix_publish_fail", 1)
        toks = run_all(eng, [req(0)])
        assert FAULTS.fired.get("prefix_publish_fail") == 1
        assert counters.get("serve.fault_prefix_publish_fail") == 1
        assert eng.results["r0"].outcome is Outcome.COMPLETED
        assert len(eng.prefix) == 0, "failed publish leaked index state"
        assert eng.pool.used == 0
        # the NEXT publisher works, and the tokens above were unaffected
        warm = run_all(eng, [req(0, rid="r0b")])
        assert warm["r0b"] == toks["r0"]
        assert len(eng.prefix) == 3
        eng.verify_invariants(idle=True)


# --------------------------------------------------------- release gate


@pytest.mark.slow
def test_serve_smoke_prefix_fault_drills():
    """tools/serve_smoke.py's cold/warm replay must pass clean AND
    compose with each env-armed prefix fault: a forged warm-round probe
    (``prefix_hash_collide``) degrades to cold prefill with bit-identical
    tokens, and a dropped cold-round publish (``prefix_publish_fail``)
    fails open."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for spec in ("prefix_hash_collide=1", "prefix_publish_fail=1"):
        env = dict(os.environ, JAX_PLATFORMS="cpu", DALLE_TPU_FAULTS=spec)
        out = subprocess.run(
            [sys.executable, "tools/serve_smoke.py"],
            capture_output=True, text=True, timeout=600, env=env, cwd=repo,
        )
        assert out.returncode == 0, (spec, out.stderr[-2000:])
        assert "prefix-cache cold/warm replay" in out.stderr, spec


# ------------------------------------------------------ invariants/misc


class TestInvariants:
    def test_midflight_refcount_accounting(self, model):
        """verify_invariants holds at EVERY engine step of a warm run —
        the sum of node refcounts equals the live shared mappings."""
        eng = make_engine(model, prefix_cache=True, prefill_chunk=2,
                          fused_iteration=True)
        run_all(eng, [req(0)])
        pB = diverge_at(prompt(0), 2)
        assert eng.submit(req(0, rid="rA")) is None
        assert eng.submit(req(7, rid="rB", p=pB, seed=7)) is None
        for _ in range(200):
            eng.verify_invariants()
            if not eng.step():
                break
        eng.verify_invariants(idle=True)
        assert eng.prefix.total_refs() == 0

    def test_prefix_cache_off_is_inert(self, model):
        eng = make_engine(model)
        assert eng.prefix is None
        run_all(eng, [req(0)])
        assert counters.get("serve.prefix.hits") == 0
        assert counters.get("serve.prefix.misses") == 0
        eng.verify_invariants(idle=True)

    def test_concurrent_cold_twins_publish_once(self, model):
        """Two requests with ONE prompt admitted together both miss and
        both prefill; the second to publish finds the first's pages in the
        index and is counted as deduplicated, not stored twice."""
        eng = make_engine(model, prefix_cache=True, prefill_chunk=2)
        toks = run_all(eng, [req(0, rid="rA"), req(0, rid="rB")])
        assert toks["rA"] == toks["rB"]
        assert counters.get("serve.prefix.misses") == 2
        assert counters.get("serve.prefix.pages_deduped") > 0
        assert eng.prefix.stats.deduped == counters.get(
            "serve.prefix.pages_deduped"
        )
        eng.verify_invariants(idle=True)

    def test_arena_rows_round_up_and_budget_includes_arena(self, model):
        eng = make_engine(model, prefix_cache=True, prefix_cache_pages=7)
        # 7 pages over 5-page rows -> 2 arena rows = 10 arena pages
        assert eng._arena_rows == 2
        assert eng.prefix.arena_total == 10
        assert eng.pool.total == eng.config.max_batch * 5 + 10
        # arena ids start past the slot rows' global pages
        assert min(eng.prefix._free_pages) == eng.config.max_batch * 5
