"""Replicated-front-door tests — every fleet robustness behavior of the
router (serving/router.py) pinned deterministically on CPU: the replica
health state machine (breaker open/backoff/readmit, stall heartbeat,
invariant-violation quarantine), BIT-IDENTICAL cross-replica failover,
shared-clock deadline semantics across a failover, graceful drain,
fleet-watermark degradation, global typed admission, and the combined
chaos scenario where 100% of submitted requests must end in exactly one
typed outcome. Plus the labeled-metrics substrate the per-replica series
stand on (utils/metrics.py child registries).

Same tiny model + page-size-2 override as tests/test_serving.py so decode
genuinely crosses page boundaries mid-flight.
"""

import subprocess
import sys

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
    RejectReason,
    ReplicaState,
    Request,
    Router,
    RouterConfig,
)
from dalle_pytorch_tpu.utils.faults import FAULTS
from dalle_pytorch_tpu.utils.metrics import counters, gauges, histograms
from dalle_pytorch_tpu.utils.resilience import RetryPolicy


@pytest.fixture(scope="module")
def model():
    dalle = DALLE(
        dim=32, depth=2, num_text_tokens=16, text_seq_len=4,
        num_image_tokens=12, image_fmap_size=2, heads=2, dim_head=8,
        attn_types=("full",), shift_tokens=True, rotary_emb=True,
    )
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


def make_router(model, n=2, clock=None, router_kw=None, **eng_kw):
    dalle, params = model
    eng_kw.setdefault("max_batch", 2)
    return Router(
        dalle, params,
        RouterConfig(n_replicas=n, **(router_kw or {})),
        EngineConfig(**eng_kw),
        clock=clock or FakeClock(step_dt=0.1),
    )


def accounting_holds(router):
    router.verify_invariants()
    outcomes = router.stats()["outcomes"]
    assert sum(outcomes.values()) == router.stats()["submitted"]
    return outcomes


# --------------------------------------------------- labeled metrics (pure)


class TestLabeledMetrics:
    def test_counter_label_variants_and_total(self):
        counters.inc("x.n")
        counters.inc("x.n", 2, labels={"replica": "0"})
        counters.inc("x.n", 3, labels={"replica": "1"})
        assert counters.get("x.n") == 1
        assert counters.get("x.n", labels={"replica": "0"}) == 2
        assert counters.total("x.n") == 6
        snap = counters.snapshot("x.")
        assert snap == {
            "x.n": 1, 'x.n{replica="0"}': 2, 'x.n{replica="1"}': 3,
        }

    def test_child_registries_bind_and_compose(self):
        c0 = counters.child({"replica": 0})
        c0.inc("y.n")
        c0.child({"shard": 1}).inc("y.n")
        assert counters.get("y.n", labels={"replica": "0"}) == 1
        assert counters.get("y.n", labels={"replica": "0", "shard": "1"}) == 1
        assert counters.child(None) is counters  # unlabeled path is free
        g = gauges.child({"replica": 2})
        g.set("y.g", 0.5)
        assert gauges.get("y.g", labels={"replica": 2}) == 0.5
        h = histograms.child({"replica": 2})
        h.observe("y.h", 1.0)
        assert histograms.get("y.h", labels={"replica": "2"}).count == 1
        assert histograms.get("y.h") is None  # labeled != unlabeled series

    def test_prometheus_dump_renders_labels(self):
        from dalle_pytorch_tpu.utils.telemetry import TELEMETRY

        counters.inc("z.n", 4, labels={"replica": "1"})
        gauges.set("z.g", 2.0, labels={"replica": "1"})
        histograms.observe("z.h", 0.25, labels={"replica": "1"})
        dump = TELEMETRY.dump()
        assert 'z_n{replica="1"} 4' in dump
        assert 'z_g{replica="1"} 2' in dump
        assert 'z_h_count{replica="1"} 1' in dump
        # label'd bucket lines merge the le label with the series labels
        assert 'z_h_bucket{replica="1",le=' in dump
        # exposition still parses line-for-line (name{...} value)
        for line in dump.splitlines():
            if not line or line.startswith("#"):
                continue
            name, _, value = line.rpartition(" ")
            float(value)
            assert name


# ------------------------------------------------------- health machine


class TestHealthMachine:
    def test_breaker_opens_backs_off_and_readmits(self, model):
        """k consecutive prefill failures open the breaker (DEGRADED, no
        new admissions); the RetryPolicy backoff readmits it, after which
        queued work flows again."""
        clock = FakeClock(step_dt=1.0)
        router = make_router(
            model, n=1, clock=clock,
            router_kw=dict(
                breaker_threshold=2,
                breaker_backoff=RetryPolicy(
                    attempts=5, base_delay=4.0, max_delay=60.0,
                    jitter=0.0, retry_on=(),
                ),
            ),
            prefill_attempts=10,
        )
        FAULTS.arm("prefill_fail", 3)
        assert router.submit(req(0)) is None
        assert router.submit(req(1)) is None
        router.run(max_steps=300)
        outcomes = accounting_holds(router)
        assert outcomes["completed"] == 2
        assert FAULTS.fired.get("prefill_fail") == 3
        assert counters.get("router.breaker_opens") == 1
        assert counters.get("router.readmits") == 1
        # the replica ended back in service
        assert router.replica_states()[0] == "healthy"

    def test_second_router_does_not_inherit_breaker_deltas(self, model):
        """Health baselines snapshot the process-global labeled counters
        at replica construction: a second Router in the same process (the
        smoke/bench clean-then-chaos shape) must not read the first
        fleet's accumulated prefill retries as a spurious first-check
        delta and pop its breaker with zero failures of its own."""
        router_kw = dict(
            breaker_threshold=2,
            breaker_backoff=RetryPolicy(
                attempts=5, base_delay=2.0, max_delay=60.0,
                jitter=0.0, retry_on=(),
            ),
        )
        FAULTS.arm("prefill_fail", 3)
        first = make_router(
            model, n=1, clock=FakeClock(step_dt=1.0),
            router_kw=router_kw, prefill_attempts=10,
        )
        assert first.submit(req(0)) is None
        first.run(max_steps=300)
        assert first.results["r0"].outcome is Outcome.COMPLETED
        opens = counters.get("router.breaker_opens")
        assert opens >= 1  # the first fleet's breaker genuinely tripped
        second = make_router(
            model, n=1, clock=FakeClock(step_dt=1.0),
            router_kw=router_kw, prefill_attempts=10,
        )
        assert second.submit(req(1)) is None
        second.run(max_steps=300)
        assert second.results["r1"].outcome is Outcome.COMPLETED
        assert counters.get("router.breaker_opens") == opens  # no new trip
        assert second.replica_states()[0] == "healthy"

    def test_health_flap_backoff_prevents_admission_livelock(self, model):
        """Repeated spurious health flaps DEGRADE replicas over and over;
        exponential backoff makes each flap progressively quieter instead
        of bouncing admissions forever — everything still completes in
        bounded steps."""
        router = make_router(
            model, n=2, clock=FakeClock(step_dt=1.0),
            router_kw=dict(breaker_backoff=RetryPolicy(
                attempts=10, base_delay=1.0, max_delay=8.0,
                jitter=0.0, retry_on=(),
            )),
        )
        FAULTS.arm("health_flap", 4)
        for i in range(3):
            assert router.submit(req(i)) is None
        router.run(max_steps=500)
        outcomes = accounting_holds(router)
        assert outcomes["completed"] == 3
        assert FAULTS.fired.get("health_flap") == 4
        assert counters.get("router.breaker_opens") == 4

    def test_stall_heartbeat_declares_dead_and_fails_over(self, model):
        """A replica that stops making step progress while holding work is
        declared DEAD by the heartbeat; its request completes on a
        sibling."""
        clock = FakeClock(step_dt=1.0)
        router = make_router(
            model, n=2, clock=clock,
            router_kw=dict(stall_timeout_s=2.5),
        )
        assert router.submit(req(0)) is None
        # let it land in flight, then stall the busy replica repeatedly
        for _ in range(2):
            router.step()
        holder = next(r for r in router._replicas if r.inflight)
        FAULTS.arm("replica_stall", 5)
        router.run(max_steps=300)
        outcomes = accounting_holds(router)
        assert outcomes["completed"] == 1
        assert holder.state is ReplicaState.DEAD
        assert holder.death_reason == "stall_timeout"
        # the fleet survived: the sibling is still serving
        assert any(
            r.state is not ReplicaState.DEAD for r in router._replicas
        )

    def test_invariant_violation_quarantines_replica(self, model):
        """The health machine probes Engine.verify_invariants every
        iteration: a corrupt engine (accounting no longer sums) is
        declared DEAD immediately and its work fails over."""
        router = make_router(model, n=2)
        assert router.submit(req(0)) is None
        for _ in range(2):
            router.step()
        holder = next(r for r in router._replicas if r.inflight)
        holder.engine._submitted += 1  # corrupt: a request got "lost"
        router.run(max_steps=300)
        assert holder.state is ReplicaState.DEAD
        assert holder.death_reason == "invariant_violation"
        res = router.results["r0"]
        assert res.outcome is Outcome.COMPLETED
        assert "failovers=1" in res.detail


# ------------------------------------------------------------- failover


class TestFailover:
    def run_clean(self, model, n_req=2, max_new=4):
        router = make_router(model, n=2)
        for i in range(n_req):
            assert router.submit(req(i, max_new=max_new)) is None
        router.run(max_steps=500)
        return {
            rid: np.asarray(r.tokens) for rid, r in router.results.items()
        }

    def test_cross_replica_replay_bit_identical(self, model):
        """THE acceptance criterion: a request prefilled and PARTIALLY
        DECODED on replica A, requeued when A dies, completes on replica
        B with tokens bit-identical to an uninterrupted run — the
        (seed, position) replay contract across replica boundaries."""
        clean = self.run_clean(model)
        router = make_router(model, n=2)
        for i in range(2):
            assert router.submit(req(i)) is None
        # step until some request has visibly decoded a partial prefix
        for _ in range(200):
            router.step()
            partial = [
                s for r in router._replicas for s in r.engine.slots
                if s and len(s.entry.generated) >= 2
            ]
            if partial:
                break
        assert partial, "no request reached partial decode"
        FAULTS.arm("replica_crash", 1)
        router.run(max_steps=500)
        outcomes = accounting_holds(router)
        assert outcomes["completed"] == 2
        assert counters.get("router.replica_deaths") == 1
        assert counters.get("router.failovers") >= 1
        failed_over = [
            r for r in router.results.values() if "failovers=1" in r.detail
        ]
        assert failed_over, "no request actually failed over"
        for rid, r in router.results.items():
            np.testing.assert_array_equal(
                np.asarray(r.tokens), clean[rid],
                err_msg=f"{rid} tokens diverged across replica failover",
            )
        # failover latency was measured
        fh = histograms.get("router.failover_latency_s")
        assert fh is not None and fh.count >= 1

    def test_deadline_expires_during_failover_shared_clock(self, model):
        """Deadlines are absolute instants on the ONE clock shared by all
        replicas: a request decoding on replica B when B dies keeps the
        same deadline while requeued, and expires typed if no sibling can
        take it in time."""
        clock = FakeClock(step_dt=1.0)
        router = make_router(
            model, n=2, clock=clock,
            router_kw=dict(breaker_backoff=RetryPolicy(
                attempts=3, base_delay=100.0, max_delay=100.0,
                jitter=0.0, retry_on=(),
            )),
        )
        # degrade replica 0 for a long time: the fleet's only admitting
        # replica is #1
        FAULTS.arm("health_flap", 1)
        router.step()
        assert router.replica_states()[0] == "degraded"
        deadline = clock.now() + 8.0
        assert router.submit(Request(
            request_id="victim", prompt=prompt(0), max_new_tokens=4,
            seed=0, deadline=deadline,
        )) is None
        # let it prefill + decode a bit on replica 1
        for _ in range(3):
            router.step()
        holder = router._replicas[1]
        assert "victim" in holder.inflight
        router.kill(holder.id, "crash")
        # no healthy replica: the requeued request waits at the router
        # while the shared clock keeps advancing past its deadline
        router.run(max_steps=300)
        res = router.results["victim"]
        assert res.outcome is Outcome.DEADLINE_EXCEEDED
        assert "router queue" in res.detail
        accounting_holds(router)

    def test_failover_cap_is_typed(self, model):
        router = make_router(model, n=2, router_kw=dict(max_failovers=0))
        assert router.submit(req(0)) is None
        for _ in range(2):
            router.step()
        assert any(r.inflight for r in router._replicas)
        FAULTS.arm("replica_crash", 1)
        router.run(max_steps=300)
        res = router.results["r0"]
        assert res.outcome is Outcome.PREEMPT_CAP
        assert "max_failovers" in res.detail
        accounting_holds(router)

    def test_fleet_death_flushes_typed_no_replica(self, model):
        router = make_router(model, n=1, max_batch=1)
        for i in range(2):
            assert router.submit(req(i)) is None
        for _ in range(2):
            router.step()
        router.kill(0, "crash")
        router.run(max_steps=50)
        outcomes = accounting_holds(router)
        assert outcomes["rejected"] == 2
        for r in router.results.values():
            assert r.reject_reason is RejectReason.NO_REPLICA
        # and new submissions reject immediately, typed
        res = router.submit(req(5))
        assert res is not None
        assert res.reject_reason is RejectReason.NO_REPLICA
        accounting_holds(router)


# ---------------------------------------------------------------- drain


class TestDrain:
    def test_graceful_drain_finishes_inflight_routes_rest(self, model):
        router = make_router(model, n=2, max_batch=1)
        for i in range(3):
            assert router.submit(req(i)) is None
        for _ in range(2):
            router.step()  # one request in flight per replica, one queued
        drained = next(r for r in router._replicas if r.inflight)
        inflight_rid = next(iter(drained.inflight))
        admitted_before = drained.engine._submitted
        router.drain(drained.id)
        assert drained.state is ReplicaState.DRAINING
        router.run(max_steps=500)
        outcomes = accounting_holds(router)
        assert outcomes["completed"] == 3
        # the in-flight request FINISHED on the draining replica (it was
        # not requeued: zero failovers)
        assert "failovers" not in router.results[inflight_rid].detail
        # no new admissions after the drain call, and the replica retired
        assert drained.engine._submitted == admitted_before
        assert drained.state is ReplicaState.DEAD
        assert drained.death_reason == "drained"
        assert counters.get("router.drained") == 1


# ----------------------------------------------- global admission & shed


class TestGlobalAdmission:
    def test_router_queue_full_typed(self, model):
        router = make_router(model, n=1, router_kw=dict(queue_limit=1))
        assert router.submit(req(0)) is None
        res = router.submit(req(1))
        assert res is not None
        assert res.reject_reason is RejectReason.QUEUE_FULL
        assert counters.get("router.shed") == 1
        router.run(max_steps=300)
        outcomes = accounting_holds(router)
        assert outcomes["completed"] == 1 and outcomes["rejected"] == 1

    def test_demand_exceeds_every_pool_typed(self, model):
        router = make_router(model, n=2, page_budget=2)
        res = router.submit(req(0))
        assert res is not None
        assert res.reject_reason is RejectReason.DEMAND_EXCEEDS_POOL
        accounting_holds(router)

    def test_duplicate_and_bounds_raise(self, model):
        router = make_router(model, n=1)
        assert router.submit(req(0)) is None
        with pytest.raises(ValueError, match="duplicate"):
            router.submit(req(0))
        with pytest.raises(ValueError, match="max_new_tokens"):
            router.submit(req(1, max_new=99))
        router.run(max_steps=300)
        accounting_holds(router)

    def test_watermark_degradation_spans_fleet(self, model):
        """The clamp responds to AGGREGATE occupancy: replica 1 is
        completely empty when r1 lands on it, yet r1 is clamped because
        replica 0's resident pages push the FLEET over the watermark —
        per-engine occupancy alone would never clamp here."""
        router = make_router(
            model, n=2, max_batch=1,
            high_watermark=0.25, degraded_max_new_tokens=2,
        )
        assert router.submit(req(0, max_new=4)) is None
        # step 1 dispatches r0 into an engine; step 2 runs that engine's
        # admission, making its prompt pages resident
        for _ in range(2):
            router.step()
        assert router.fleet_occupancy() > 0.25
        empty = [r for r in router._replicas if not r.inflight]
        assert empty and empty[0].engine.pool.occupancy == 0.0
        assert router.submit(req(1, max_new=4)) is None
        router.run(max_steps=500)
        outcomes = accounting_holds(router)
        assert outcomes["completed"] == 2
        r0, r1 = router.results["r0"], router.results["r1"]
        assert r0.clamped_max_new_tokens is None and len(r0.tokens) == 4
        assert r1.clamped_max_new_tokens == 2 and len(r1.tokens) == 2

    def test_combined_chaos_all_typed(self, model):
        """The fleet acceptance scenario: a replica crash + a health flap
        + injected prefill and page faults + deadlines + a cancel, all in
        one run — no hang, and every submitted request ends in exactly
        one typed outcome."""
        FAULTS.configure(
            "replica_crash=1,health_flap=1,prefill_fail=1,page_exhaust=1"
        )
        clock = FakeClock(step_dt=0.5)
        router = make_router(
            model, n=3, clock=clock, max_batch=2, page_budget=7,
            router_kw=dict(queue_limit=6),
        )
        immediate = []
        for i in range(8):
            r = router.submit(req(
                i, max_new=4,
                deadline=None if i % 2 else 60.0,
                priority=i % 3,
            ))
            if r is not None:
                immediate.append(r)
        router.step()
        router.cancel("r3")
        router.run(max_steps=1000)
        outcomes = accounting_holds(router)
        assert sum(outcomes.values()) == 8
        assert outcomes["rejected"] == len(immediate)
        assert outcomes["cancelled"] >= 1
        assert counters.get("router.replica_deaths") == 1
        assert FAULTS.fired.get("replica_crash") == 1
        # live replicas drained their pools; every engine's accounting holds
        for rep in router._replicas:
            if rep.state is not ReplicaState.DEAD:
                rep.engine.verify_invariants(idle=True)


# ------------------------------------------------- engine invariant surface


class TestEngineInvariants:
    def test_verify_invariants_mid_flight_and_idle(self, model):
        dalle, params = model
        eng = Engine(dalle, params, EngineConfig(max_batch=2),
                     clock=FakeClock(step_dt=0.1))
        assert eng.submit(req(0)) is None
        eng.step()
        eng.verify_invariants()          # valid mid-flight
        with pytest.raises(AssertionError, match="not idle"):
            eng.verify_invariants(idle=True)
        eng.run(max_steps=200)
        eng.verify_invariants(idle=True)

    def test_verify_invariants_detects_corruption(self, model):
        dalle, params = model
        eng = Engine(dalle, params, EngineConfig(max_batch=2),
                     clock=FakeClock(step_dt=0.1))
        assert eng.submit(req(0)) is None
        eng.run(max_steps=200)
        eng._submitted += 1  # a request vanished without a result
        with pytest.raises(AssertionError, match="submitted"):
            eng.verify_invariants()


# ----------------------------------------------------- release gates


@pytest.mark.slow
def test_serve_smoke_replicas_tool():
    """The --replicas 2 chaos drill must pass clean AND compose with an
    env-armed prefill fault."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for extra_env in ({}, {"DALLE_TPU_FAULTS": "prefill_fail=1"}):
        env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
        out = subprocess.run(
            [sys.executable, "tools/serve_smoke.py", "--replicas", "2"],
            capture_output=True, text=True, timeout=600, env=env, cwd=repo,
        )
        assert out.returncode == 0, (extra_env, out.stderr[-2000:])
        assert "replica crash drill bit-identically" in out.stderr
