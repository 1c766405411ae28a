#!/usr/bin/env python
"""Fleet-scale traffic simulator: retry storms, correlated outages, and
capacity frontiers over virtual time (docs/DESIGN.md §8.4).

A discrete-event workload harness over the injectable serving ``Clock``
that drives hundreds of thousands of simulated requests through an
N-replica ``Router`` fleet in faster-than-real time. Two lanes
cross-validate each other:

* **modeled lane** — the REAL ``Router`` (health machine, breaker,
  respawn ladders, failover, shed, dispatch — every line of
  serving/router.py) over a fleet of ``StubEngine``s: host-only models
  of the engine's admission/step/can_admit/verify_invariants surface
  built from the SAME scheduler primitives the real engine uses
  (``Scheduler``/``PagePool``/``TokenBudget``/``pages_for``), replacing
  only the device work with a per-iteration cost distribution
  set to a round 1.0 ms/token (a pre-ledger note has 0.901 ms/token
  bf16 batch-1 decode on v5e) — a modelled constant, not a measurement
  of the engine. This is what reaches 100k+ requests in seconds.
* **fidelity lane** — the real tiny-model engine fleet on a
  ``FakeClock``, thousands of requests, asserting the modeled lane's
  predicted shed fraction / p99 TTFT / occupancy trajectory within the
  tolerances documented in DESIGN §8.4.

Workloads are seeded generators (Poisson / diurnal / burst arrivals,
zipf-of-prefix template mixes, tenant priority + deadline spreads) plus
a CLOSED-LOOP client model: every typed reject or deadline miss
re-enters the arrival stream through client backoff
(``RetryPolicy.delay``), optionally honoring the server's
``retry_after_s`` hint — which is what makes retry storms real. Fault
schedules composed from the existing chaos sites (``replica_crash``,
``replica_stall``, ``health_flap``, ``replica_respawn_fail``) produce
correlated outage storms.

Virtual-time semantics: the in-process fleet is genuinely
time-multiplexed (``Router.step`` drives every engine sequentially
under one lock), so each busy engine iteration advances the ONE shared
clock by its drawn cost; an idle fleet jumps straight to the next
event (arrival, client retry, breaker readmission, respawn). QPS
numbers are therefore per-process, comparable across scenarios.

In-run asserts (the run fails loudly, not statistically): 100%
typed-outcome accounting (``Router.verify_invariants`` plus
every-logical-request-final), no admission livelock (terminal progress
watchdog), goodput monotone-bounded past saturation, replay-consistent
seeding (one level re-run must produce an identical record), and the
storm-amplification guard — goodput at 2x saturation with jittered
backoff + honored hints >= the unjittered/no-hint baseline, with
desynchronized respawn ladders (no lockstep re-collision).

Modes::

    python tools/traffic_sim.py --smoke      # ~seconds, fast-tier gate
    python tools/traffic_sim.py --quick      # >=100k requests, <60s
    python tools/traffic_sim.py --sweep      # frontier grid (slow tier)
    python tools/traffic_sim.py --fidelity 600   # cross-validate lanes
"""

from __future__ import annotations

import argparse
import heapq
import json
import math
import os
import sys
import time
from collections import OrderedDict
from dataclasses import dataclass, field, replace
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

# a CPU tool: assigned, not defaulted (tools/serve_smoke.py)
os.environ["JAX_PLATFORMS"] = "cpu"

import random

import numpy as np

from dalle_pytorch_tpu.serving.scheduler import (
    Entry, PagePool, Scheduler, TokenBudget, pages_for,
)
from dalle_pytorch_tpu.serving.types import (
    FakeClock, Outcome, RejectReason, Request, RequestResult,
)
from dalle_pytorch_tpu.utils.faults import FAULTS
from dalle_pytorch_tpu.utils.metrics import counters, histograms
from dalle_pytorch_tpu.utils.resilience import RetryPolicy, retry_after_hint

# retriable load-typed rejections; DEMAND_EXCEEDS_POOL is permanent
_RETRIABLE = (RejectReason.QUEUE_FULL, RejectReason.NO_REPLICA)


# ------------------------------------------------------------ cost model


@dataclass(frozen=True)
class IterationCostModel:
    """Virtual cost of one engine scheduling iteration in the modeled
    lane. Defaults are round numbers: decode 1.0 ms/token bf16 (a
    pre-ledger note that predates the engine has 0.901 at batch 1 on
    v5e; PERF_LEDGER.jsonl has no serving line yet), prefill
    amortized well under decode
    (compute-bound batch processing of the whole chunk — the 0.9
    ms/token batch-1 decode figure in DESIGN §6 bounds it above), plus
    a fixed per-iteration dispatch overhead. ``jitter_frac`` draws
    multiplicative lognormal noise from the engine's seeded RNG so two
    replicas never run in artificial lockstep; ``constant`` (used by
    the fidelity-matched configuration) charges exactly ``fixed_s`` per
    iteration, idle or not — the semantics of ``FakeClock.tick``."""

    decode_ms_per_token: float = 1.0
    prefill_ms_per_token: float = 0.12
    fixed_overhead_ms: float = 0.3
    jitter_frac: float = 0.08
    constant: bool = False
    fixed_s: float = 0.0
    tick_idle: bool = False
    # post-decode stage rows (serving/postdecode.py, DESIGN §8.5): the
    # per-image VAE decode / CLIP rerank cost charged on top of token
    # work. 0.0 = the stage model contributes no virtual time.
    vae_ms_per_image: float = 0.0
    rerank_ms_per_image: float = 0.0

    def cost_s(self, decode_tokens: int, prefill_tokens: int,
               rng: Optional[random.Random]) -> float:
        if self.constant:
            return self.fixed_s
        if decode_tokens == 0 and prefill_tokens == 0:
            return self.fixed_overhead_ms / 1e3 if self.tick_idle else 0.0
        ms = (
            self.fixed_overhead_ms
            + self.decode_ms_per_token * decode_tokens
            + self.prefill_ms_per_token * prefill_tokens
        )
        if self.jitter_frac > 0.0 and rng is not None:
            ms *= math.exp(rng.gauss(0.0, self.jitter_frac))
        return ms / 1e3

    def stage_cost_s(self, vae_images: int, reranked: int,
                     rng: Optional[random.Random]) -> float:
        """Virtual cost of this iteration's post-decode stage rows,
        charged on top of token work (zero under the fidelity-matched
        constant clock — its fixed per-iteration tick already covers
        everything the engine did)."""
        if self.constant or (vae_images == 0 and reranked == 0):
            return 0.0
        ms = (
            self.vae_ms_per_image * vae_images
            + self.rerank_ms_per_image * reranked
        )
        if ms > 0.0 and self.jitter_frac > 0.0 and rng is not None:
            ms *= math.exp(rng.gauss(0.0, self.jitter_frac))
        return ms / 1e3

    @staticmethod
    def matched(step_dt: float) -> "IterationCostModel":
        """The fidelity-matched configuration: every iteration costs
        exactly ``step_dt``, like a real engine stepping a
        ``FakeClock(step_dt=...)``."""
        return IterationCostModel(
            constant=True, fixed_s=step_dt, tick_idle=True,
        )


# ------------------------------------------------------------ stub engine


class _StubModel:
    """The two model attributes the router reads off a replica's engine
    (``proto.dalle.image_seq_len`` at submit validation; text length for
    page math)."""

    def __init__(self, text_len_internal: int, image_seq_len: int):
        self.text_len_internal = text_len_internal
        self.image_seq_len = image_seq_len


@dataclass(frozen=True)
class StubEngineConfig:
    """The EngineConfig subset the modeled lane exercises, with the
    same defaults/semantics (serving/engine.py:EngineConfig)."""

    max_batch: int = 8
    page: int = 4
    page_budget: Optional[int] = None      # None = max_batch * pages/slot
    queue_limit: int = 64
    high_watermark: float = 0.85
    degraded_max_new_tokens: Optional[int] = None
    max_preemptions: int = 3
    prefill_chunk: Optional[int] = None    # None = whole prompt at once
    token_budget: Optional[int] = None     # None = max_batch + chunk
    # prefix-template model: LRU capacity in TEMPLATES (0 = off). A full
    # hit shares the template's prompt pages (charged to __prefix__) and
    # skips prefill entirely — the TTFT / hit-rate / arena-share lever.
    prefix_templates: int = 0
    # post-decode stage model (serving/postdecode.py semantics): tokens-
    # complete requests pass VAE_DECODE -> [CLIP_RERANK] -> DONE under a
    # per-iteration stage budget, with enqueue-time pressure degradation
    # to the typed COMPLETED_TOKENS_ONLY outcome.
    stages: bool = False
    stage_budget: int = 2              # stage rows per iteration
    stage_queue_limit: int = 64        # staged backlog -> degrade at entry
    stage_high_watermark: float = 1.0  # occupancy past this -> degrade
    stage_rerank: bool = True


class StubEngine:
    """Host-only model of the engine surface the Router drives.

    Same admission policy (strict head-of-line, watermark clamp, worst-
    case page charging), same preempt-and-requeue discipline (lazy page
    growth, lowest-effective-priority victim, ``max_preemptions`` ->
    typed PREEMPT_CAP), same typed-outcome accounting — only the device
    work is replaced by token counters and a drawn per-iteration cost
    that the engine itself charges to the shared clock. Emits the
    labeled heartbeat counters the router's health machinery reads
    (``serve.admitted`` / ``serve.decode_steps`` / ``serve.prefill_chunks``)
    so stall detection, the breaker and progress accounting all run the
    REAL router code paths."""

    PREFIX_HOLDER = "__prefix__"

    def __init__(self, model: _StubModel, config: StubEngineConfig,
                 cost: IterationCostModel, clock,
                 metric_labels: Optional[dict] = None,
                 fleet_occupancy: Optional[Callable[[], float]] = None,
                 seed: int = 0):
        self.dalle = model
        self.config = config
        self.clock = clock
        self.page = config.page
        self.T = model.text_len_internal
        self.n_pages_slot = pages_for(
            self.T + model.image_seq_len, self.page
        )
        total = config.page_budget or config.max_batch * self.n_pages_slot
        self.pool = PagePool(total)
        self.sched = Scheduler(config.queue_limit)
        self.slots: List[Optional[Entry]] = [None] * config.max_batch
        self.results: Dict[str, RequestResult] = {}
        self._live: set = set()
        self._outcome_counts: Dict[Outcome, int] = {o: 0 for o in Outcome}
        self._submitted = 0
        self._seq = 0
        self._cancel_requested: set = set()
        self.prefix = None                  # router's snapshot path: unused
        self._fleet_occupancy = fleet_occupancy
        self._cost = cost
        self._rng = random.Random(seed)
        self.counters = counters.child(metric_labels)
        self.iterations = 0
        chunk = config.prefill_chunk or self.T
        budget = (
            config.token_budget
            if config.token_budget is not None
            else config.max_batch + chunk
        )
        self._budget = TokenBudget(budget=budget, chunk=chunk)
        self._chunk = chunk
        # per-slot prefill progress / decode tally, keyed by request_id
        self._prompt_left: Dict[str, int] = {}
        self._gen: Dict[str, int] = {}
        # post-decode stage queue (config.stages): tokens-complete
        # entries parked for VAE/rerank rows; they stay LIVE but hold
        # no slot or pages — serving/postdecode.py semantics
        self._staged: List[Entry] = []
        self._stage: Dict[str, str] = {}       # rid -> vae_decode|clip_rerank
        self._stage_hit: Dict[str, Optional[str]] = {}
        # prefix-template LRU: key -> [pages, refcount]
        self._templates: "OrderedDict[bytes, list]" = OrderedDict()

    # -- the submit/cancel/step surface ------------------------------

    def submit(self, request: Request) -> Optional[RequestResult]:
        if not (0 < request.max_new_tokens <= self.dalle.image_seq_len):
            raise ValueError(
                f"max_new_tokens must be in "
                f"[1, {self.dalle.image_seq_len}], "
                f"got {request.max_new_tokens}"
            )
        if (
            request.request_id in self.results
            or request.request_id in self._live
        ):
            raise ValueError(
                f"duplicate request_id {request.request_id!r}"
            )
        self._submitted += 1
        self.counters.inc("serve.submitted")
        now = self.clock.now()
        entry = Entry(request=request, submit_time=now, seq=self._seq)
        self._seq += 1
        if self._worst_case_pages(request.max_new_tokens) > self.pool.total:
            return self._reject(entry, RejectReason.DEMAND_EXCEEDS_POOL)
        if not self.sched.submit(entry):
            return self._reject(entry, RejectReason.QUEUE_FULL)
        self._live.add(request.request_id)
        return None

    def cancel(self, request_id: str) -> None:
        self._cancel_requested.add(request_id)

    def can_admit(self, request: Request) -> bool:
        """The router dispatch gate, same contract as the real engine:
        free slot, empty internal queue, and the worst-case demand of
        the budget the request would receive fits the free pages plus
        what the template arena could reclaim (refcount-0 templates —
        the stub analog of ``prefix.reclaimable_pages()``)."""
        if not any(s is None for s in self.slots):
            return False
        if len(self.sched):
            return False
        eff, _ = self._clamped_budget(request.max_new_tokens)
        avail = self.pool.free + sum(
            pages for pages, ref in self._templates.values() if ref == 0
        )
        return self._worst_case_pages(eff) <= avail

    def step(self) -> bool:
        self._sweep_terminations()
        self._admit()
        decode_tokens, prefill_tokens = self._advance()
        vae_rows, rerank_rows = self._stage_advance()
        worked = bool(
            decode_tokens or prefill_tokens or vae_rows or rerank_rows
        )
        if worked:
            self.iterations += 1
        dt = self._cost.cost_s(decode_tokens, prefill_tokens, self._rng)
        dt += self._cost.stage_cost_s(vae_rows, rerank_rows, self._rng)
        if dt > 0:
            self.clock.advance(dt)
        return worked or bool(self.sched) or bool(self._staged) or any(
            s is not None for s in self.slots
        )

    def live_requests(self) -> List[Request]:
        queued = [e.request for e in self.sched.entries()]
        running = [
            s.request for s in sorted(
                (s for s in self.slots if s is not None),
                key=lambda e: e.seq,
            )
        ]
        staged = [
            e.request for e in sorted(self._staged, key=lambda e: e.seq)
        ]
        return queued + running + staged

    def verify_invariants(self, idle: bool = False) -> None:
        slot_ids = {
            s.request_id for s in self.slots if s is not None
        }
        queued_ids = self.sched.ids()
        staged_ids = {e.request_id for e in self._staged}
        assert not (slot_ids & queued_ids), (
            f"running AND queued: {sorted(slot_ids & queued_ids)}"
        )
        assert not (staged_ids & (slot_ids | queued_ids)), (
            f"staged AND running/queued: "
            f"{sorted(staged_ids & (slot_ids | queued_ids))}"
        )
        assert self._live == slot_ids | queued_ids | staged_ids, (
            f"live {len(self._live)} != slots {len(slot_ids)} + "
            f"queued {len(queued_ids)} + staged {len(staged_ids)}"
        )
        assert len(self.results) + len(self._live) == self._submitted, (
            f"{self._submitted} submitted, {len(self.results)} results, "
            f"{len(self._live)} live"
        )
        holders = self.pool.holders()
        assert holders <= slot_ids | {self.PREFIX_HOLDER}, (
            f"pages held by non-running {sorted(holders - slot_ids)}"
        )
        if idle:
            assert not self._live and not slot_ids

    # -- internals ---------------------------------------------------

    def _clamped_budget(self, want: int) -> Tuple[int, bool]:
        cfg = self.config
        occ = (
            self._fleet_occupancy()
            if self._fleet_occupancy is not None
            else self.pool.occupancy
        )
        if (
            cfg.degraded_max_new_tokens is not None
            and occ > cfg.high_watermark
            and want > cfg.degraded_max_new_tokens
        ):
            return cfg.degraded_max_new_tokens, True
        return want, False

    def _worst_case_pages(self, max_new: int) -> int:
        return pages_for(self.T + max_new - 1, self.page)

    def _template_key(self, request: Request) -> bytes:
        return request.prompt.tobytes()

    def _reclaim_templates(self, want: int) -> None:
        """Evict refcount-0 templates LRU-first until ``want`` pages are
        free (the stub analog of the index's last-resort eviction
        tier)."""
        if want <= self.pool.free:
            return
        for key in list(self._templates):
            pages, ref = self._templates[key]
            if ref:
                continue
            del self._templates[key]
            self.pool.release(self.PREFIX_HOLDER, pages)
            if want <= self.pool.free:
                return

    def _admit(self) -> None:
        now = self.clock.now()
        while any(s is None for s in self.slots) and len(self.sched):
            entry = self.sched.peek()
            eff, clamped = self._clamped_budget(
                entry.request.max_new_tokens
            )
            hit = False
            if self.config.prefix_templates:
                key = self._template_key(entry.request)
                hit = key in self._templates
            prompt_pages = 0 if hit else pages_for(self.T, self.page)
            demand = self._worst_case_pages(eff)
            if demand - (pages_for(self.T, self.page) - prompt_pages) \
                    > self.pool.free:
                self._reclaim_templates(
                    demand - (pages_for(self.T, self.page) - prompt_pages)
                )
            charge = demand - (pages_for(self.T, self.page) - prompt_pages)
            if charge > self.pool.free:
                return                       # strict head-of-line
            self.sched.pop()
            rid = entry.request_id
            # charge the prompt pages now (worst-case admission already
            # verified the rest fits; growth below is lazy)
            assert self.pool.alloc(rid, prompt_pages)
            entry.effective_max_new = eff
            entry.clamped = clamped
            entry.admit_time = now
            if clamped:
                self.counters.inc("serve.clamped")
            if hit:
                key = self._template_key(entry.request)
                self._templates.move_to_end(key)
                self._templates[key][1] += 1
                entry.hit_class = "full"
                self._prompt_left[rid] = 0
                # prefill skipped entirely: first token samples now
                entry.ttft_s = now - entry.submit_time
            else:
                self._prompt_left[rid] = self.T
            self._gen[rid] = 0
            idx = self.slots.index(None)
            self.slots[idx] = entry
            self.counters.inc("serve.admitted")

    def _advance(self) -> Tuple[int, int]:
        """One iteration of device work: decode every active row (one
        token each), then budgeted prefill chunks, split-path style
        (``TokenBudget.plan``: decode charged first, token grants in
        chunk multiples, possibly several chunks per slot per
        iteration, strict head-of-line)."""
        now = self.clock.now()
        decode_tokens = 0
        for entry in self.slots:
            if entry is None:
                continue
            rid = entry.request_id
            if self._prompt_left[rid] > 0:
                continue
            gen = self._gen[rid] + 1
            self._gen[rid] = gen
            decode_tokens += 1
            if entry.ttft_s is None:
                entry.ttft_s = now - entry.submit_time
            s = self.T + gen
            if s < self.T + entry.effective_max_new and s % self.page == 0:
                if not self._grow(entry):
                    continue   # entry was preempted (or capped)
            if gen >= entry.effective_max_new:
                self._finish(entry, Outcome.COMPLETED)
        if decode_tokens:
            self.counters.inc("serve.decode_steps")
        prefilling = sorted(
            (e for e in self.slots
             if e is not None and self._prompt_left[e.request_id] > 0),
            key=lambda e: (-self.sched.effective_priority(e), e.seq),
        )
        grants = self._budget.plan(
            decode_tokens,
            [self._prompt_left[e.request_id] for e in prefilling],
        )
        prefill_tokens = 0
        for entry, grant in zip(prefilling, grants):
            rid = entry.request_id
            while grant > 0:
                chunk = min(self._chunk, self._prompt_left[rid])
                if self._prompt_left[rid] - chunk == 1:
                    chunk += 1   # split-path 1-token-tail merge
                self._prompt_left[rid] -= chunk
                grant -= chunk
                prefill_tokens += chunk
                self.counters.inc("serve.prefill_chunks")
            if self._prompt_left[rid] == 0:
                # prefill completion samples the first token
                if entry.ttft_s is None:
                    entry.ttft_s = now - entry.submit_time
                if entry.prefill_attempts == 0:
                    entry.prefill_attempts = 1
                self._publish_template(entry)
        return decode_tokens, prefill_tokens

    def _grow(self, entry: Entry) -> bool:
        """Lazy +1 page at a page boundary; on exhaustion preempt the
        lowest-effective-priority victim (youngest on ties) — possibly
        the grower itself — and retry the allocation."""
        rid = entry.request_id
        while not self.pool.alloc(rid, 1):
            self._reclaim_templates(1)
            if self.pool.free >= 1:
                continue
            victims = [e for e in self.slots if e is not None]
            victim = min(
                victims,
                key=lambda e: (self.sched.effective_priority(e), -e.seq),
            )
            self._preempt(victim)
            if victim is entry:
                return False
        return True

    def _preempt(self, entry: Entry) -> None:
        rid = entry.request_id
        self._release_slot(entry)
        entry.preempt_count += 1
        self.counters.inc("serve.preempted")
        if entry.preempt_count > self.config.max_preemptions:
            self._terminal(entry, Outcome.PREEMPT_CAP,
                           detail="max_preemptions exceeded")
            return
        # replay from scratch on readmission (the (seed, position)
        # replay contract makes this invisible to the client)
        self.sched.requeue(entry)

    def _release_slot(self, entry: Entry) -> None:
        rid = entry.request_id
        idx = self.slots.index(entry)
        self.slots[idx] = None
        self.pool.free_all(rid)
        if entry.hit_class == "full" and self.config.prefix_templates:
            key = self._template_key(entry.request)
            if key in self._templates:
                self._templates[key][1] -= 1
        entry.hit_class = None
        self._prompt_left.pop(rid, None)
        self._gen.pop(rid, None)

    def _publish_template(self, entry: Entry) -> None:
        """Cold prefill completion publishes the template (fail-open,
        like the real index: skipped when the arena cannot fit)."""
        if not self.config.prefix_templates:
            return
        key = self._template_key(entry.request)
        if key in self._templates:
            return
        pages = pages_for(self.T, self.page)
        while len(self._templates) >= self.config.prefix_templates:
            old = next(iter(self._templates))
            if self._templates[old][1]:
                return                     # LRU head referenced: skip
            del self._templates[old]
            self.pool.release(self.PREFIX_HOLDER, pages)
        if not self.pool.alloc(self.PREFIX_HOLDER, pages):
            return
        self._templates[key] = [pages, 0]

    def _sweep_terminations(self) -> None:
        now = self.clock.now()
        if self._cancel_requested:
            for rid in list(self._cancel_requested):
                entry = self.sched.remove(rid)
                if entry is None:
                    entry = next(
                        (e for e in self.slots
                         if e is not None and e.request_id == rid),
                        None,
                    )
                    if entry is not None:
                        self._release_slot(entry)
                if entry is None:
                    entry = next(
                        (e for e in self._staged if e.request_id == rid),
                        None,
                    )
                    if entry is not None:
                        self._stage_remove(entry)
                if entry is not None:
                    self._terminal(entry, Outcome.CANCELLED)
                self._cancel_requested.discard(rid)
        for entry in self.sched.expired(now):
            self._terminal(entry, Outcome.DEADLINE_EXCEEDED,
                           detail="deadline passed in queue")
        for entry in list(self.slots):
            if entry is None:
                continue
            d = entry.request.deadline
            if d is not None and now > d:
                self._release_slot(entry)
                self._terminal(entry, Outcome.DEADLINE_EXCEEDED,
                               detail="deadline passed mid-flight")
        for entry in list(self._staged):
            d = entry.request.deadline
            if d is not None and now > d:
                self._stage_remove(entry)
                self._terminal(entry, Outcome.DEADLINE_EXCEEDED,
                               detail="deadline passed mid-stage")

    def _finish(self, entry: Entry, outcome: Outcome) -> None:
        hit = entry.hit_class          # cleared by _release_slot
        self._release_slot(entry)
        if outcome is Outcome.COMPLETED and self.config.stages:
            self._stage_enqueue(entry, hit)
            return
        self.counters.inc("serve.completed")
        self._terminal(entry, outcome,
                       detail=f"prefix_hit:{hit}" if hit else "")

    # -- post-decode stage model (config.stages) ---------------------

    def _stage_enqueue(self, entry: Entry, hit: Optional[str]) -> None:
        """Tokens-complete entry enters the modeled pipeline. Pressure
        degradation happens HERE, at the stage boundary, exactly like
        the real pipeline: a typed COMPLETED_TOKENS_ONLY instead of an
        unbounded stage backlog."""
        cfg = self.config
        self.counters.inc("serve.stage.enqueued")
        occ = (
            self._fleet_occupancy()
            if self._fleet_occupancy is not None
            else self.pool.occupancy
        )
        if len(self._staged) >= cfg.stage_queue_limit:
            self.counters.inc("serve.stage.degraded")
            self._terminal(entry, Outcome.COMPLETED_TOKENS_ONLY,
                           detail="stage_backlog")
            return
        if occ > cfg.stage_high_watermark:
            self.counters.inc("serve.stage.degraded")
            self._terminal(entry, Outcome.COMPLETED_TOKENS_ONLY,
                           detail="stage_watermark")
            return
        rid = entry.request_id
        self._stage[rid] = "vae_decode"
        self._stage_hit[rid] = hit
        self._staged.append(entry)

    def _stage_remove(self, entry: Entry) -> None:
        self._staged.remove(entry)
        self._stage.pop(entry.request_id, None)
        self._stage_hit.pop(entry.request_id, None)

    def _stage_advance(self) -> Tuple[int, int]:
        """One iteration of budgeted stage rows, completion-priority
        like the real pipeline (rerank-stage rows dispatch before fresh
        VAE rows). Returns (vae_rows, rerank_rows) for the cost model."""
        if not self._staged:
            return 0, 0
        budget = self.config.stage_budget
        vae_rows = rerank_rows = 0
        order = sorted(
            self._staged,
            key=lambda e: (self._stage[e.request_id] != "clip_rerank",
                           e.seq),
        )
        for entry in order:
            if budget <= 0:
                break
            budget -= 1
            rid = entry.request_id
            if self._stage[rid] == "clip_rerank":
                rerank_rows += 1
                self.counters.inc("serve.stage.reranked")
                self._stage_complete(entry)
            else:
                vae_rows += 1
                self.counters.inc("serve.stage.vae_images")
                if self.config.stage_rerank:
                    self._stage[rid] = "clip_rerank"
                else:
                    self._stage_complete(entry)
        return vae_rows, rerank_rows

    def _stage_complete(self, entry: Entry) -> None:
        hit = self._stage_hit.get(entry.request_id)
        self._stage_remove(entry)
        self.counters.inc("serve.completed")
        self._terminal(entry, Outcome.COMPLETED,
                       detail=f"prefix_hit:{hit}" if hit else "")

    def _terminal(self, entry: Entry, outcome: Outcome,
                  detail: str = "") -> None:
        now = self.clock.now()
        rid = entry.request_id
        self._live.discard(rid)
        if outcome is not Outcome.COMPLETED:
            self.counters.inc(f"serve.{outcome.value}")
        self._outcome_counts[outcome] += 1
        self.results[rid] = RequestResult(
            request_id=rid,
            outcome=outcome,
            tokens=None,
            preempt_count=entry.preempt_count,
            prefill_attempts=entry.prefill_attempts,
            clamped_max_new_tokens=(
                entry.effective_max_new if entry.clamped else None
            ),
            queue_latency_s=(
                None if entry.admit_time is None
                else entry.admit_time - entry.submit_time
            ),
            ttft_s=entry.ttft_s,
            total_latency_s=now - entry.submit_time,
            detail=detail,
        )

    def _reject(self, entry: Entry, reason: RejectReason) -> RequestResult:
        self.counters.inc("serve.rejected")
        self.counters.inc(f"serve.rejected.{reason.value}")
        hint = None
        if reason is RejectReason.QUEUE_FULL:
            occ = (
                self._fleet_occupancy()
                if self._fleet_occupancy is not None
                else self.pool.occupancy
            )
            hint = retry_after_hint(occ)
        result = RequestResult(
            request_id=entry.request_id,
            outcome=Outcome.REJECTED,
            reject_reason=reason,
            total_latency_s=0.0,
            retry_after_s=hint,
        )
        self.results[entry.request_id] = result
        self._outcome_counts[Outcome.REJECTED] += 1
        return result


# -------------------------------------------------------------- workloads


@dataclass(frozen=True)
class Workload:
    """Seeded workload generator spec. Arrivals: ``poisson`` (exponential
    inter-arrival at ``qps``), ``diurnal`` (sinusoidal rate over
    ``period_s``, +/- ``diurnal_amp``), ``burst`` (on/off square wave:
    rate ``qps/duty`` for ``duty`` of each period, near-zero
    otherwise). Templates draw zipf(s) over ``n_templates`` prompt
    templates (the prefix-reuse lever); tenants draw a priority from
    ``priority_weights`` and, with probability ``deadline_frac``, a
    deadline ``deadline_lo..deadline_hi`` seconds out."""

    n_requests: int = 1000
    qps: float = 50.0
    arrival: str = "poisson"            # poisson | diurnal | burst
    period_s: float = 60.0
    diurnal_amp: float = 0.5
    duty: float = 0.25
    n_templates: int = 32
    zipf_s: float = 1.1
    text_len: int = 16
    vocab: int = 15                     # prompt token values in [1, vocab]
    max_new_lo: int = 8
    max_new_hi: int = 24
    priority_weights: Tuple[float, ...] = (0.6, 0.3, 0.1)  # prio 0,1,2
    deadline_frac: float = 0.3
    deadline_lo: float = 2.0
    deadline_hi: float = 10.0
    seed: int = 0


@dataclass
class _Logical:
    """One logical client request across its retry attempts."""

    base: Request
    t_arrival: float
    deadline_window: Optional[float]
    attempt: int = 0
    final: Optional[RequestResult] = None
    final_t: Optional[float] = None     # virtual time the final landed
    retried: int = 0


def _template_prompt(tpl: int, text_len: int, vocab: int) -> np.ndarray:
    # deterministic per-template token row (Weyl-ish hash, no RNG state)
    return np.asarray(
        [((tpl + 1) * 2654435761 + i * 97) % vocab + 1
         for i in range(text_len)],
        np.int32,
    )


def generate_workload(w: Workload) -> List[_Logical]:
    """The seeded arrival stream: a list of logical requests sorted by
    arrival time. Deterministic in ``w.seed`` (replay-consistent
    seeding is asserted in-run)."""
    rng = random.Random(w.seed)
    # zipf CDF over templates
    weights = [1.0 / (k ** w.zipf_s) for k in range(1, w.n_templates + 1)]
    total_w = sum(weights)
    cdf, acc = [], 0.0
    for wt in weights:
        acc += wt / total_w
        cdf.append(acc)
    prompts = [
        _template_prompt(tpl, w.text_len, w.vocab)
        for tpl in range(w.n_templates)
    ]
    import bisect
    out: List[_Logical] = []
    t = 0.0
    for i in range(w.n_requests):
        if w.arrival == "poisson":
            t += rng.expovariate(w.qps)
        elif w.arrival == "diurnal":
            rate = w.qps * (
                1.0 + w.diurnal_amp
                * math.sin(2.0 * math.pi * t / w.period_s)
            )
            t += rng.expovariate(max(rate, w.qps * 0.05))
        elif w.arrival == "burst":
            t += rng.expovariate(w.qps / w.duty)
            if (t % w.period_s) > w.period_s * w.duty:
                # off phase: jump to the next on-window
                t = (t // w.period_s + 1.0) * w.period_s
        else:
            raise ValueError(f"unknown arrival {w.arrival!r}")
        tpl = bisect.bisect_left(cdf, rng.random())
        prio = rng.choices(
            range(len(w.priority_weights)), weights=w.priority_weights,
        )[0]
        window = None
        if rng.random() < w.deadline_frac:
            window = rng.uniform(w.deadline_lo, w.deadline_hi)
        req = Request(
            request_id=f"q{i}",
            prompt=prompts[tpl],
            max_new_tokens=rng.randint(w.max_new_lo, w.max_new_hi),
            deadline=None if window is None else t + window,
            priority=prio,
            seed=w.seed * 100_000 + i,
        )
        out.append(_Logical(base=req, t_arrival=t, deadline_window=window))
    return out


# --------------------------------------------------------------- clients


@dataclass(frozen=True)
class ClientPolicy:
    """Closed-loop client retry model: a load-typed reject or a deadline
    miss re-enters the arrival stream after a backoff. ``honor_hints``
    uses the server's ``retry_after_s`` (jittered by the policy's own
    jitter so honoring a shared hint still desynchronizes); otherwise
    the client backs off on its own ``RetryPolicy.delay`` ladder.
    ``retry.attempts`` is the total attempt budget per logical request
    — exhaustion makes the last typed result final, which is exactly
    how a retry storm turns into lost goodput."""

    retry: RetryPolicy = RetryPolicy(
        attempts=4, base_delay=0.05, max_delay=2.0, jitter=0.5,
        retry_on=(),
    )
    honor_hints: bool = True
    retry_deadline_miss: bool = False
    seed: int = 0

    def backoff(self, attempt: int, hint: Optional[float],
                rng: random.Random) -> float:
        if self.honor_hints and hint is not None:
            d = hint
            if self.retry.jitter > 0.0:
                d *= 1.0 - self.retry.jitter * rng.random()
            return d
        return self.retry.delay(attempt, rng)


# ------------------------------------------------------------ lane driver


class _Watchdog(RuntimeError):
    pass


def run_lane(router, logicals: List[_Logical], policy: ClientPolicy,
             fault_schedule: Optional[List[Tuple[float, str, int]]] = None,
             occupancy_every: int = 64,
             watchdog_iters: int = 200_000) -> dict:
    """Drive one lane to completion: release arrivals and client
    retries against the shared virtual clock, step the router, deliver
    typed results back to the clients, jump idle gaps to the next
    event. Returns the lane record. Raises ``_Watchdog`` on admission
    livelock (no terminal progress for ``watchdog_iters`` fleet
    iterations) — the no-livelock in-run assert."""
    clock = router.clock
    crng = random.Random(policy.seed ^ 0x5EED)
    arrivals = sorted(logicals, key=lambda l: l.t_arrival)
    ai = 0
    retries: List[Tuple[float, int, _Logical]] = []   # heap by due time
    rseq = 0
    outstanding: Dict[str, _Logical] = {}
    pending_final = len(logicals)
    iters = 0
    idle_jumps = 0
    last_progress_iter = 0
    occ_trace: List[Tuple[float, float]] = []
    t0 = clock.now()
    schedule = sorted(fault_schedule or [])
    si = 0

    def submit(lg: _Logical, now: float) -> None:
        nonlocal pending_final
        lg.attempt += 1
        rid = (
            lg.base.request_id if lg.attempt == 1
            else f"{lg.base.request_id}.r{lg.attempt - 1}"
        )
        deadline = None
        if lg.deadline_window is not None:
            deadline = now + lg.deadline_window
        req = replace(
            lg.base, request_id=rid, deadline=deadline,
        )
        rejected = router.submit(req)
        if rejected is None:
            outstanding[rid] = lg
        else:
            deliver(lg, rejected, now)

    def deliver(lg: _Logical, res: RequestResult, now: float) -> None:
        nonlocal pending_final, rseq
        retriable = (
            res.outcome is Outcome.REJECTED
            and res.reject_reason in _RETRIABLE
        ) or (
            policy.retry_deadline_miss
            and res.outcome is Outcome.DEADLINE_EXCEEDED
        )
        if retriable and lg.attempt < max(1, policy.retry.attempts):
            delay = policy.backoff(
                lg.attempt - 1, res.retry_after_s, crng
            )
            lg.retried += 1
            heapq.heappush(retries, (now + delay, rseq, lg))
            rseq += 1
            return
        lg.final = res
        lg.final_t = now
        pending_final -= 1

    while pending_final > 0:
        now = clock.now()
        while si < len(schedule) and schedule[si][0] <= now:
            _, site, n = schedule[si]
            FAULTS.arm(site, n)
            si += 1
        while ai < len(arrivals) and arrivals[ai].t_arrival <= now:
            submit(arrivals[ai], now)
            ai += 1
        while retries and retries[0][0] <= now:
            _, _, lg = heapq.heappop(retries)
            submit(lg, clock.now())
        router.step()
        iters += 1
        # deliver new terminal results (outstanding is bounded by the
        # in-system population, so this poll is cheap)
        if outstanding:
            done = [
                rid for rid in outstanding if rid in router.results
            ]
            for rid in done:
                lg = outstanding.pop(rid)
                deliver(lg, router.results[rid], clock.now())
            if done:
                last_progress_iter = iters
        if iters % occupancy_every == 0:
            occ_trace.append(
                (clock.now() - t0, router.fleet_occupancy())
            )
        if iters % 512 == 0:
            router.verify_invariants()
        if clock.now() <= now:
            # virtual time frozen (idle fleet / dead fleet): jump to the
            # next event — arrival, client retry, breaker readmission,
            # or respawn — the discrete-event skip
            nxt = []
            if ai < len(arrivals):
                nxt.append(arrivals[ai].t_arrival)
            if retries:
                nxt.append(retries[0][0])
            if si < len(schedule):
                nxt.append(schedule[si][0])
            for r in router._replicas:
                if r.respawn_at is not None:
                    nxt.append(r.respawn_at)
                if r.retry_at is not None:
                    nxt.append(r.retry_at)
            if nxt:
                target = min(nxt)
                if target > now:
                    clock.advance(target - now)
                    idle_jumps += 1
                else:
                    clock.advance(1e-4)
            elif outstanding:
                clock.advance(1e-4)
            elif pending_final > 0:
                raise _Watchdog(
                    f"{pending_final} logical requests pending with no "
                    f"future event and an idle fleet"
                )
        if iters - last_progress_iter > watchdog_iters and outstanding:
            raise _Watchdog(
                f"no terminal progress in {watchdog_iters} iterations: "
                f"{len(outstanding)} outstanding"
            )
    router.verify_invariants()
    return _lane_record(router, logicals, occ_trace, clock.now() - t0,
                        iters, idle_jumps)


def _percentile(xs: List[float], q: float) -> Optional[float]:
    if not xs:
        return None
    ys = sorted(xs)
    i = min(len(ys) - 1, int(math.ceil(q * len(ys))) - 1)
    return ys[max(0, i)]


def _arena_share(router) -> float:
    """Fraction of fleet pool pages held by prefix templates at end of
    run (modeled lane only; the real engine reports the analogous
    ``serve.prefix_pages`` gauge)."""
    held, total = 0, 0
    for r in router._replicas:
        eng = r.engine
        if not hasattr(eng, "_templates"):
            return 0.0
        held += sum(pages for pages, _ in eng._templates.values())
        total += eng.pool.total
    return (held / total) if total else 0.0


def _lane_record(router, logicals, occ_trace, duration, iters,
                 idle_jumps) -> dict:
    outcomes: Dict[str, int] = {}
    ttfts: List[float] = []
    lat: List[float] = []
    img_lat: List[float] = []
    client_lat: List[float] = []
    hits = 0
    completed = 0
    degraded = 0
    retries_total = 0
    shed = 0
    for lg in logicals:
        res = lg.final
        assert res is not None, lg.base.request_id
        outcomes[res.outcome.value] = outcomes.get(res.outcome.value, 0) + 1
        retries_total += lg.retried
        if res.outcome in (
            Outcome.COMPLETED_TOKENS_ONLY, Outcome.COMPLETED_UNRANKED,
        ):
            # successes of the degradation policy: the request finished
            # typed, it just shed post-decode work under pressure
            degraded += 1
        if res.outcome is Outcome.COMPLETED:
            completed += 1
            if res.ttft_s is not None:
                ttfts.append(res.ttft_s)
            if res.total_latency_s is not None:
                lat.append(res.total_latency_s)
                # with the stage model on, a COMPLETED entry's total
                # latency IS submit -> image (stages precede DONE)
                img_lat.append(res.total_latency_s)
            if lg.final_t is not None:
                # client-perceived: arrival -> final, across every
                # retry and the router queue — the SLO the frontier
                # holds (engine-side ttft_s excludes fleet queueing)
                client_lat.append(lg.final_t - lg.t_arrival)
            if res.detail.startswith("prefix_hit:"):
                hits += 1
        elif (
            res.outcome is Outcome.REJECTED
            and res.reject_reason in _RETRIABLE
        ):
            shed += 1
    stats = router.stats()
    n = len(logicals)
    occs = [o for _, o in occ_trace]
    return {
        "logical_requests": n,
        "router_submitted": stats["submitted"],
        "outcomes": dict(sorted(outcomes.items())),
        "completed": completed,
        # goodput counts every TYPED successful finish — full
        # completions plus the degradation policy's tokens-only/
        # unranked outcomes (shedding stage work must not read as a
        # goodput collapse; the cost of degrading shows in
        # degraded_frac, not here)
        "goodput_qps": (
            (completed + degraded) / duration
        ) if duration > 0 else 0.0,
        "shed_frac": shed / n if n else 0.0,
        "retries": retries_total,
        "ttft_p50_s": _percentile(ttfts, 0.50),
        "ttft_p99_s": _percentile(ttfts, 0.99),
        "latency_p99_s": _percentile(lat, 0.99),
        "request_image_p50_s": _percentile(img_lat, 0.50),
        "request_image_p99_s": _percentile(img_lat, 0.99),
        "degraded_frac": degraded / n if n else 0.0,
        "client_latency_p50_s": _percentile(client_lat, 0.50),
        "client_latency_p99_s": _percentile(client_lat, 0.99),
        "prefix_hit_frac": (hits / completed) if completed else 0.0,
        "arena_share": _arena_share(router),
        "occupancy_mean": (sum(occs) / len(occs)) if occs else 0.0,
        "occupancy_trace": [
            [round(t, 4), round(o, 4)] for t, o in occ_trace[:200]
        ],
        "virtual_s": duration,
        "arrival_span_s": (
            max(lg.t_arrival for lg in logicals)
            - min(lg.t_arrival for lg in logicals)
        ) if logicals else 0.0,
        "fleet_iterations": iters,
        "idle_jumps": idle_jumps,
        "replica_states": router.replica_states(),
    }


# ---------------------------------------------------------- fleet builders


@dataclass(frozen=True)
class FleetSpec:
    """Modeled-lane fleet shape. ``respawn_jitter`` > 0 turns on the
    seeded backoff jitter in the router's respawn/readmission ladders
    (the satellite fix this sim motivates); the storm baseline runs it
    at 0.0 — the historical lockstep schedule."""

    n_replicas: int = 4
    max_batch: int = 32
    queue_limit: int = 256
    text_len: int = 16
    image_seq_len: int = 64
    page: int = 4
    prefix_templates: int = 0
    degraded_max_new_tokens: Optional[int] = None
    respawn: bool = True
    respawn_base_delay: float = 1.0
    respawn_jitter: float = 0.0
    backoff_seed: int = 0
    stall_timeout_s: float = 30.0
    # post-decode stage model knobs (StubEngineConfig passthrough)
    stages: bool = False
    stage_budget: int = 2
    stage_queue_limit: int = 64
    stage_high_watermark: float = 1.0


def build_modeled_router(spec: FleetSpec, cost: IterationCostModel,
                         seed: int = 0):
    """The REAL Router over a StubEngine fleet, via the
    ``engine_factory`` seam. Imported lazily: router pulls in the
    engine module (jax) — the modeled lane pays that import once but
    never traces anything."""
    from dalle_pytorch_tpu.serving.router import Router, RouterConfig

    model = _StubModel(spec.text_len, spec.image_seq_len)
    stub_cfg = StubEngineConfig(
        max_batch=spec.max_batch,
        page=spec.page,
        queue_limit=spec.max_batch,     # router gate keeps it empty
        degraded_max_new_tokens=spec.degraded_max_new_tokens,
        prefill_chunk=spec.text_len,
        prefix_templates=spec.prefix_templates,
        stages=spec.stages,
        stage_budget=spec.stage_budget,
        stage_queue_limit=spec.stage_queue_limit,
        stage_high_watermark=spec.stage_high_watermark,
    )
    builds = [0]                        # respawn generations get new RNGs

    def factory(rid, clock=None, metric_labels=None, fleet_occupancy=None):
        builds[0] += 1
        return StubEngine(
            model, stub_cfg, cost, clock,
            metric_labels=metric_labels,
            fleet_occupancy=fleet_occupancy,
            seed=seed * 7919 + rid * 101 + builds[0],
        )

    cfg = RouterConfig(
        n_replicas=spec.n_replicas,
        queue_limit=spec.queue_limit,
        respawn=spec.respawn,
        respawn_backoff=RetryPolicy(
            attempts=3, base_delay=spec.respawn_base_delay,
            max_delay=60.0, jitter=spec.respawn_jitter, retry_on=(),
        ),
        breaker_backoff=RetryPolicy(
            attempts=5, base_delay=spec.respawn_base_delay,
            max_delay=60.0, jitter=spec.respawn_jitter, retry_on=(),
        ),
        backoff_seed=spec.backoff_seed,
        stall_timeout_s=spec.stall_timeout_s,
    )
    return Router(
        None, None, cfg, engine_config=None,
        clock=FakeClock(), engine_factory=factory,
    )


# -------------------------------------------------------------- scenarios


def run_frontier(spec: FleetSpec, base: Workload, policy: ClientPolicy,
                 qps_levels: List[float], slo_p99_s: float,
                 cost: IterationCostModel, seed: int) -> dict:
    """Sweep offered QPS levels over a fresh fleet each, report the
    capacity frontier: the highest level whose p99 client latency
    (arrival -> final, across retries) holds the SLO with <1% shed,
    plus goodput/shed/occupancy curves. In-run asserts: accounting,
    replay-consistent seeding (level 0 re-run bit-equal), goodput
    monotone-bounded past saturation."""
    levels = []
    for li, qps in enumerate(qps_levels):
        FAULTS.reset()
        w = replace(base, qps=qps, seed=seed + li)
        router = build_modeled_router(spec, cost, seed=seed + li)
        rec = run_lane(router, generate_workload(w), policy)
        rec["offered_qps"] = qps
        levels.append(rec)

    # replay-consistent seeding: the first level, re-run with the same
    # seed, must produce an IDENTICAL record
    FAULTS.reset()
    w0 = replace(base, qps=qps_levels[0], seed=seed)
    router = build_modeled_router(spec, cost, seed=seed)
    rec0 = run_lane(router, generate_workload(w0), policy)
    rec0["offered_qps"] = qps_levels[0]
    assert json.dumps(rec0, sort_keys=True) == json.dumps(
        levels[0], sort_keys=True
    ), "replay with identical seed diverged"

    # goodput monotone-bounded past saturation: never exceeds offered
    # load, and the post-peak tail never collapses below half the peak
    # (a collapse is the retry-storm signature this harness exists to
    # catch)
    peak = max(l["goodput_qps"] for l in levels)
    peak_i = max(range(len(levels)),
                 key=lambda i: levels[i]["goodput_qps"])
    for l in levels:
        # conservation: completions per virtual second never exceed the
        # REALIZED arrival rate (the nominal level plus Poisson variance)
        realized = (
            l["logical_requests"] / l["virtual_s"]
            if l["virtual_s"] > 0 else float("inf")
        )
        assert l["goodput_qps"] <= realized * 1.001, (
            l["offered_qps"], l["goodput_qps"], realized,
        )
    for l in levels[peak_i:]:
        assert l["goodput_qps"] >= 0.5 * peak, (
            f"goodput collapsed past saturation: "
            f"{l['goodput_qps']:.1f} < 0.5 * {peak:.1f} "
            f"at offered {l['offered_qps']}"
        )

    sustainable = None
    for l in levels:
        # the SLO holds on CLIENT-perceived p99 latency (arrival ->
        # final across retries and fleet queueing); engine-side TTFT
        # stays flat under overload because queue wait lands upstream
        p99 = l["client_latency_p99_s"]
        if p99 is not None and p99 <= slo_p99_s and l["shed_frac"] < 0.01:
            sustainable = l["offered_qps"]
    return {
        "slo_p99_ttft_s": slo_p99_s,
        "sustainable_qps": sustainable,
        "peak_goodput_qps": peak,
        "levels": [
            {k: v for k, v in l.items() if k != "occupancy_trace"}
            for l in levels
        ],
    }


def _mttr_snapshot() -> Tuple[int, float]:
    """(count, sum) over every labeled serve.recovery_s series — the
    respawn MTTR histogram the router observes."""
    n, s = 0, 0.0
    for labels in (
        {"replica": str(i)} for i in range(64)
    ):
        h = histograms.get("serve.recovery_s", labels=labels)
        if h is not None:
            n += h.count
            s += h.sum
    return n, s


def run_storm(spec: FleetSpec, base: Workload, sat_qps: float,
              cost: IterationCostModel, seed: int,
              kills: int = 2, respawn_fails: int = 1) -> dict:
    """The retry-storm scenario: 2x saturation offered load, a
    correlated outage (``kills`` replicas crashed back-to-back through
    the ``replica_crash`` chaos site, plus ``replica_fails`` armed
    ``replica_respawn_fail``s to stretch the ladders), run twice:

    * baseline — jitter-free respawn ladders, clients ignoring
      ``retry_after_s`` (the pre-PR behavior);
    * guarded — seeded jitter in the ladders + clients honoring hints.

    Asserts bounded amplification: guarded goodput >= baseline goodput,
    and the guarded run's respawn ladders are desynchronized (distinct
    ladder delays) while the baseline's are lockstep."""
    outage_t = 1.0   # virtual seconds in: fleet is warm and loaded
    schedule = [(outage_t, "replica_crash", kills)]
    if respawn_fails:
        schedule.append((outage_t, "replica_respawn_fail", respawn_fails))

    def one(jitter: float, honor: bool, tag: str) -> dict:
        FAULTS.reset()
        w = replace(base, qps=2.0 * sat_qps, seed=seed)
        pol = ClientPolicy(
            retry=RetryPolicy(
                attempts=5, base_delay=0.02, max_delay=1.0,
                jitter=0.5 if honor else 0.0, retry_on=(),
            ),
            honor_hints=honor, seed=seed,
        )
        sp = replace(
            spec, respawn_jitter=jitter, backoff_seed=seed + 17,
        )
        router = build_modeled_router(sp, cost, seed=seed)
        # observe the ladder the outage schedules: capture per-replica
        # rung delays (respawn_at - now at scheduling time) as they
        # appear — the lockstep-vs-desynchronized evidence
        delays: Dict[int, List[float]] = {}
        orig_sched = router._schedule_respawn_locked

        def spy(r):
            before = router.clock.now()
            orig_sched(r)
            if r.respawn_at is not None:
                delays.setdefault(r.id, []).append(
                    r.respawn_at - before
                )
        router._schedule_respawn_locked = spy
        rec = run_lane(router, generate_workload(w), pol,
                       fault_schedule=schedule)
        rec["offered_qps"] = 2.0 * sat_qps
        # storm goodput: completions over the DEMAND window. Dividing
        # by full run duration would punish hint-honoring clients for
        # waiting out the outage and reward a baseline that sheds fast
        # and finishes early — the opposite of the guard's point.
        rec["storm_goodput_qps"] = (
            rec["completed"] / rec["arrival_span_s"]
            if rec["arrival_span_s"] > 0 else 0.0
        )
        rec["ladder_first_rung_s"] = [
            round(delays[rid][0], 6) for rid in sorted(delays)
        ]
        rec["tag"] = tag
        return rec

    m0 = _mttr_snapshot()
    baseline = one(jitter=0.0, honor=False, tag="baseline")
    guarded = one(jitter=0.5, honor=True, tag="jitter+hints")
    m1 = _mttr_snapshot()

    # desynchronization: first-rung delays all equal without jitter,
    # distinct with it (no lockstep re-collision)
    b_first = baseline["ladder_first_rung_s"][:kills]
    g_first = guarded["ladder_first_rung_s"][:kills]
    assert len(set(b_first)) <= 1, (
        f"baseline ladders unexpectedly jittered: {b_first}"
    )
    if kills >= 2:
        assert len(set(g_first)) == len(g_first), (
            f"jittered ladders still lockstep: {g_first}"
        )
    assert guarded["completed"] >= baseline["completed"], (
        f"storm amplification guard failed: jitter+hints completed "
        f"{guarded['completed']} < baseline {baseline['completed']}"
    )
    assert guarded["storm_goodput_qps"] >= baseline["storm_goodput_qps"], (
        f"storm amplification guard failed: jitter+hints goodput "
        f"{guarded['storm_goodput_qps']:.2f} < baseline "
        f"{baseline['storm_goodput_qps']:.2f}"
    )
    respawns = m1[0] - m0[0]
    mttr = ((m1[1] - m0[1]) / respawns) if respawns else None
    return {
        "offered_qps": 2.0 * sat_qps,
        "kills": kills,
        "respawn_fails_armed": respawn_fails,
        "respawns_observed": respawns,
        "mttr_mean_s": mttr,
        "baseline": {
            k: v for k, v in baseline.items() if k != "occupancy_trace"
        },
        "guarded": {
            k: v for k, v in guarded.items() if k != "occupancy_trace"
        },
    }


# --------------------------------------------------------- fidelity lane

# modeled-vs-real tolerance contract (docs/DESIGN.md §8.4): the modeled
# lane must predict the real tiny-model fleet's aggregates within these
FIDELITY_TOL = {
    "shed_frac_abs": 0.10,
    "ttft_p99_rel": 0.50,
    "occupancy_abs": 0.15,
}


def run_fidelity(n_requests: int = 600, seed: int = 0,
                 step_dt: float = 0.004,
                 qps: float = 40.0) -> dict:
    """Cross-validate the lanes: the REAL tiny-model engine fleet on a
    ``FakeClock(step_dt)`` versus a StubEngine fleet matched to it
    (same page geometry, batch, queue, chunking — introspected off a
    real replica; every iteration charged exactly ``step_dt``, the
    ``FakeClock.tick`` semantics). Same workload, same seed, same
    closed-loop clients. Asserts the modeled lane's shed fraction, p99
    TTFT and mean occupancy within ``FIDELITY_TOL``."""
    from serve_smoke import build_tiny_model

    from dalle_pytorch_tpu.serving import (
        EngineConfig, Router, RouterConfig,
    )

    dalle, params = build_tiny_model()
    n_replicas = 2
    ecfg = EngineConfig(max_batch=2, prefill_chunk=2)
    rcfg = RouterConfig(n_replicas=n_replicas, queue_limit=64)
    w = Workload(
        n_requests=n_requests, qps=qps, arrival="poisson",
        n_templates=8, text_len=dalle.text_seq_len,
        vocab=dalle.num_text_tokens - 1,
        max_new_lo=2, max_new_hi=dalle.image_seq_len,
        deadline_frac=0.0, seed=seed,
    )
    pol = ClientPolicy(seed=seed)

    # real lane
    FAULTS.reset()
    real_router = Router(
        dalle, params, rcfg, ecfg, clock=FakeClock(step_dt=step_dt),
    )
    proto = real_router._replicas[0].engine
    real = run_lane(real_router, generate_workload(w), pol)

    # modeled lane, matched to the real replica's geometry
    model = _StubModel(proto.T, dalle.image_seq_len)
    stub_cfg = StubEngineConfig(
        max_batch=ecfg.max_batch,
        page=proto.page,
        page_budget=proto.pool.total,
        queue_limit=ecfg.queue_limit,
        high_watermark=ecfg.high_watermark,
        degraded_max_new_tokens=ecfg.degraded_max_new_tokens,
        max_preemptions=ecfg.max_preemptions,
        prefill_chunk=ecfg.prefill_chunk,
        token_budget=ecfg.token_budget,
    )
    cost = IterationCostModel.matched(step_dt)

    def factory(rid, clock=None, metric_labels=None,
                fleet_occupancy=None):
        return StubEngine(
            model, stub_cfg, cost, clock,
            metric_labels=metric_labels,
            fleet_occupancy=fleet_occupancy, seed=seed,
        )

    FAULTS.reset()
    stub_router = Router(
        None, None, rcfg, engine_config=None,
        clock=FakeClock(), engine_factory=factory,
    )
    modeled = run_lane(stub_router, generate_workload(w), pol)

    diffs = {
        "shed_frac_abs": abs(
            modeled["shed_frac"] - real["shed_frac"]
        ),
        "occupancy_abs": abs(
            modeled["occupancy_mean"] - real["occupancy_mean"]
        ),
    }
    if real["ttft_p99_s"] and modeled["ttft_p99_s"]:
        diffs["ttft_p99_rel"] = (
            abs(modeled["ttft_p99_s"] - real["ttft_p99_s"])
            / real["ttft_p99_s"]
        )
    for key, tol in FIDELITY_TOL.items():
        if key in diffs:
            assert diffs[key] <= tol, (
                f"fidelity divergence: {key} = {diffs[key]:.4f} > "
                f"tolerance {tol} (modeled "
                f"{modeled.get(key.split('_abs')[0].split('_rel')[0])} "
                f"vs real)"
            )
    strip = lambda r: {
        k: v for k, v in r.items() if k != "occupancy_trace"
    }
    return {
        "n_requests": n_requests,
        "step_dt": step_dt,
        "offered_qps": qps,
        "tolerances": dict(FIDELITY_TOL),
        "diffs": {k: round(v, 6) for k, v in diffs.items()},
        "real": strip(real),
        "modeled": strip(modeled),
    }


# ----------------------------------------------------------- mode records


def _mode_record(mode: str, seed: int) -> dict:
    """Record skeleton: one self-describing JSON object per run."""
    return {
        "tool": "traffic_sim",
        "schema": 1,
        "mode": mode,
        "seed": seed,
        "cost_model": {
            "decode_ms_per_token": IterationCostModel.decode_ms_per_token,
            "prefill_ms_per_token": IterationCostModel.prefill_ms_per_token,
            "fixed_overhead_ms": IterationCostModel.fixed_overhead_ms,
            "source": "modelled constant, order of a pre-ledger note "
                      "(0.901 ms/token bf16 batch-1 decode, v5e, pre-engine)",
        },
    }


def _count_requests(frontier: dict, storm: Optional[dict]) -> int:
    n = sum(l["logical_requests"] for l in frontier["levels"])
    n += frontier["levels"][0]["logical_requests"]   # the replay re-run
    if storm is not None:
        n += storm["baseline"]["logical_requests"]
        n += storm["guarded"]["logical_requests"]
    return n


def run_modeled(mode: str, seed: int) -> dict:
    """The modeled-lane scenario suite at one of three sizes:

    * ``smoke``  — seconds; the fast-tier subprocess gate.
    * ``quick``  — >=100k logical requests through a 4-replica fleet,
      frontier + storm, <60s wall on CPU (asserted).
    * ``sweep``  — the full grid: every arrival shape, prefix-template
      mix on, a wider QPS ladder (slow tier).
    """
    t_wall = time.monotonic()
    cost = IterationCostModel()
    if mode == "smoke":
        spec = FleetSpec(n_replicas=4, max_batch=8, queue_limit=64)
        base = Workload(n_requests=1_500, n_templates=16)
        qps_levels = [30.0, 70.0]
        storm_kills = spec.n_replicas       # full-fleet correlated outage
    elif mode == "quick":
        spec = FleetSpec(n_replicas=4, max_batch=16, queue_limit=256)
        base = Workload(n_requests=16_000, max_new_lo=8, max_new_hi=16)
        qps_levels = [50.0, 65.0, 80.0, 95.0, 110.0]
        storm_kills = spec.n_replicas
    elif mode == "sweep":
        spec = FleetSpec(
            n_replicas=4, max_batch=32, queue_limit=256,
            prefix_templates=16,
        )
        base = Workload(n_requests=24_000)
        qps_levels = [30.0, 40.0, 50.0, 60.0, 70.0, 80.0, 90.0]
        storm_kills = spec.n_replicas
    else:
        raise ValueError(f"unknown mode {mode!r}")

    policy = ClientPolicy(seed=seed)
    frontier = run_frontier(
        spec, base, policy, qps_levels, slo_p99_s=2.0,
        cost=cost, seed=seed,
    )
    sat = frontier["sustainable_qps"] or frontier["peak_goodput_qps"]
    storm_base = replace(base, n_requests=max(
        1_000, base.n_requests // 3
    ))
    storm = run_storm(
        spec, storm_base, sat_qps=sat, cost=cost, seed=seed,
        kills=storm_kills, respawn_fails=1,
    )

    # post-decode stage frontier (DESIGN §8.5): the same capacity sweep
    # with per-image VAE/CLIP stage rows charged to the clock and the
    # pipeline's pressure degradation armed (watermark 0.95), including
    # a 2x-overload level that must finish TYPED — request->image p99
    # and the degraded fraction are the columns this adds
    stage_cost = replace(
        cost, vae_ms_per_image=4.0, rerank_ms_per_image=2.0,
    )
    # stage_budget=1 caps the pipeline at one row (half a completion)
    # per iteration while short token jobs finish >1 per iteration at
    # saturation — overload overflows the small stage backlog and the
    # policy must shed TYPED, not queue unboundedly
    stage_spec = replace(
        spec, stages=True, stage_budget=1, stage_queue_limit=8,
        stage_high_watermark=0.95,
    )
    stage_base = replace(
        base, n_requests=min(base.n_requests, 4_000),
        max_new_lo=4, max_new_hi=8,
    )
    stage_frontier = run_frontier(
        stage_spec, stage_base, policy,
        [qps_levels[0], 2.0 * qps_levels[-1]], slo_p99_s=2.0,
        cost=stage_cost, seed=seed + 3,
    )
    over = stage_frontier["levels"][-1]
    assert over["degraded_frac"] > 0.0, (
        "2x overload never tripped the stage degradation policy: "
        f"{over}"
    )
    assert over["request_image_p99_s"] is not None, over

    rec = _mode_record(mode, seed)
    rec["fleet"] = {
        "n_replicas": spec.n_replicas,
        "max_batch": spec.max_batch,
        "queue_limit": spec.queue_limit,
        "prefix_templates": spec.prefix_templates,
    }
    rec["frontier"] = frontier
    rec["storm"] = storm
    rec["stage_frontier"] = stage_frontier
    n_total = _count_requests(frontier, storm)
    n_total += _count_requests(stage_frontier, None)
    rec["totals"] = {
        "modeled_requests": n_total,
        "wall_s": round(time.monotonic() - t_wall, 3),
    }
    rec["asserts"] = [
        "typed_accounting_100pct",
        "replay_consistent_seeding",
        "goodput_bounded_past_saturation",
        "storm_amplification_guard",
        "respawn_ladder_desynchronized",
        "stage_overload_degrades_typed",
    ]
    if mode == "sweep":
        # the grid rides on top: one frontier per arrival shape
        rec["arrival_grid"] = {}
        for shape in ("diurnal", "burst"):
            shaped = replace(
                base, arrival=shape,
                n_requests=base.n_requests // 2,
            )
            f = run_frontier(
                spec, shaped, policy, qps_levels[1::2],
                slo_p99_s=2.0, cost=cost, seed=seed + 1,
            )
            rec["arrival_grid"][shape] = f
            rec["totals"]["modeled_requests"] += _count_requests(f, None)
    if mode == "quick":
        assert rec["totals"]["modeled_requests"] >= 100_000, rec["totals"]
        assert spec.n_replicas >= 4
        assert rec["totals"]["wall_s"] < 60.0, (
            f"quick mode exceeded its wall budget: "
            f"{rec['totals']['wall_s']}s"
        )
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
    )
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--smoke", action="store_true",
                   help="seconds-scale gate (fast tier)")
    g.add_argument("--quick", action="store_true",
                   help=">=100k modeled requests, <60s")
    g.add_argument("--sweep", action="store_true",
                   help="full frontier grid (slow tier)")
    g.add_argument("--fidelity", type=int, metavar="N", default=None,
                   help="cross-validate lanes on N real requests")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=Path, default=None,
                    help="write the record JSON here (default stdout)")
    args = ap.parse_args(argv)

    if args.fidelity is not None:
        rec = _mode_record("fidelity", args.seed)
        rec["fidelity"] = run_fidelity(
            n_requests=args.fidelity, seed=args.seed,
        )
        ok = "lanes agree within tolerance"
    else:
        mode = (
            "smoke" if args.smoke else "quick" if args.quick else "sweep"
        )
        rec = run_modeled(mode, args.seed)
        ok = (
            f"{rec['totals']['modeled_requests']} modeled requests, "
            f"wall {rec['totals']['wall_s']}s, sustainable "
            f"{rec['frontier']['sustainable_qps']} qps"
        )
    text = json.dumps(rec, indent=2, sort_keys=True)
    if args.out is not None:
        args.out.write_text(text + "\n")
        print(f"traffic sim: wrote {args.out} ({ok})")
    else:
        print(text)
        print(f"traffic sim: OK ({ok})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
