#!/usr/bin/env python
"""Chaos soak gate: a seeded randomized fault schedule against the
serving fleet, with recovery (respawn + process restart) in the loop.

Every robustness mechanism the serving stack owns is exercised from ONE
randomized schedule instead of one-fault-at-a-time drills: each
iteration the seeded RNG may arm any serving fault site
(``page_exhaust``, ``prefill_fail``, ``decode_stall``,
``request_cancel``, ``replica_crash``, ``replica_stall``,
``health_flap``, ``prefix_hash_collide``, ``prefix_publish_fail``,
``replica_respawn_fail``), and at randomized points the WHOLE PROCESS
"crashes": the router object is abandoned mid-flight exactly as a dead
process would leave it (journal unsealed, in-flight work lost), a fresh
router is built, the prefix-cache snapshot is verify-loaded
(``snapshot_corrupt`` armable here), and the journal replays unfinished
requests (``journal_torn`` armable here — a torn tail is dropped and
the harness resubmits it as the client retry the contract prescribes).
Training-side sites (``download``, ``shard_open``, ...) have no take
site in the serving loop and are deliberately not scheduled.

The client half of the loop is closed too (the traffic-sim storm model,
docs/DESIGN.md §8.4): load-typed rejects (``queue_full`` /
``no_replica``) are NOT terminal to the soak client — it honors the
fleet's ``retry_after_s`` hint (seeded jitter on top) and resubmits
under a fresh attempt id, up to a bounded attempt budget, so the soak
exercises client-driven retry pressure and not just server-side faults.
Mid-run a correlated **outage storm** arms ``replica_crash`` for every
replica at once (``--storm-at``, auto-placed at the midpoint), which is
exactly the schedule whose retry amplification the hints exist to damp.

The gate, checked every iteration and at the end:

* ``Router.verify_invariants`` clean EVERY iteration — accounting can
  never drift, even transiently;
* 100% typed-outcome accounting: every submitted request ends in
  exactly one typed outcome, across crashes and restarts;
* bit-parity: every COMPLETED request's tokens equal a fault-free
  reference run's (the (seed, position) replay contract); a request
  re-delivered after an outcome-record loss must match its original
  delivery bitwise (replay idempotency);
* at least one request completes (a soak that rejects everything is a
  failed soak, not a passed one).

Like the other gate tools, the soak runs the full three-stage lint
pre-flight (AST + trace + shard contracts, docs/DESIGN.md §11) before
arming anything — a chaos pass over a broken build proves nothing.

Quick deterministic mode (the default: ``--iters 120 --seed 0``) is the
fast-tier subprocess gate (tests/test_recovery.py); longer soaks ride
``--iters``/``--seed`` sweeps behind the slow tier::

    python tools/chaos_soak.py
    python tools/chaos_soak.py --iters 2000 --seed 7 --replicas 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

# a CPU gate: assigned, not defaulted (tools/serve_smoke.py)
os.environ["JAX_PLATFORMS"] = "cpu"

# fault sites with a take-site reachable from the router loop, and the
# per-iteration probability of arming each (seeded RNG)
SCHEDULED_SITES = (
    "page_exhaust", "prefill_fail", "decode_stall", "request_cancel",
    "replica_crash", "replica_stall", "health_flap",
    "prefix_hash_collide", "prefix_publish_fail", "replica_respawn_fail",
    "vae_decode_fail", "rerank_fail", "stage_timeout",
)
# restart-time sites: armed just before a journal/snapshot load
RESTART_SITES = ("journal_torn", "snapshot_corrupt")


def run_soak(iters: int, seed: int, n_replicas: int, n_req: int,
             fault_p: float, restart_every: int, snap_every: int,
             storm_at: int = -1) -> dict:
    import numpy as np
    from dataclasses import replace

    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, FakeClock, Outcome, RejectReason, Request,
        RequestJournal, Router, RouterConfig, replay_unfinished,
    )
    from dalle_pytorch_tpu.utils.faults import FAULTS
    from serve_smoke import build_tiny_model, build_tiny_stages

    dalle, params = build_tiny_model()
    stages = build_tiny_stages()
    rng = np.random.RandomState(seed)
    prompts = [
        rng.randint(1, 16, size=(4,)).astype(np.int32) for _ in range(n_req)
    ]
    # a few shared prompts so the prefix cache sees real reuse
    for i in range(3, n_req, 3):
        prompts[i] = prompts[0]
    requests = [
        Request(
            request_id=f"soak{i}", prompt=prompts[i],
            max_new_tokens=dalle.image_seq_len, seed=1000 + i,
        )
        for i in range(n_req)
    ]

    # fault-free reference: the bit-parity oracle for every survivor —
    # tokens AND decoded images (the post-decode stages run here too)
    ref_engine = Engine(
        dalle, params, EngineConfig(max_batch=2, prefill_chunk=2),
        stages=stages,
    )
    for req in requests:
        assert ref_engine.submit(req) is None
    reference = ref_engine.run(max_steps=20_000)

    tmp = tempfile.mkdtemp(prefix="chaos_soak_")
    jpath = os.path.join(tmp, "journal.jsonl")
    snapdir = os.path.join(tmp, "prefix_snapshot")
    engine_cfg = EngineConfig(
        max_batch=2, prefill_chunk=2, prefix_cache=True,
    )
    router_cfg = RouterConfig(
        n_replicas=n_replicas, respawn=True,
        stall_timeout_s=5.0,
        # small enough that the outage-storm backlog overflows into
        # load-typed QUEUE_FULL rejects (with retry_after_s hints) the
        # closed-loop client must ride out — a roomy queue would absorb
        # the whole storm and never exercise client retry pressure
        queue_limit=max(2, n_req // 4),
    )
    clock = FakeClock(step_dt=0.25)

    def build_router() -> Router:
        return Router(
            dalle, params, router_cfg, engine_cfg, clock=clock,
            journal=RequestJournal(jpath), stages=stages,
        )

    FAULTS.reset()
    router = build_router()
    if storm_at < 0:
        storm_at = iters // 2 if iters >= 20 else 0
    by_rid = {r.request_id: r for r in requests}
    delivered: dict = {}        # logical rid -> RequestResult (client view)
    submitted: set = set()
    armed_total: dict = {}
    # logical rid -> {"attempt", "due", "rid"}: a load-typed reject the
    # closed-loop client will resubmit ("due" is the virtual resubmit
    # time; None once the attempt is in flight under attempt id "rid")
    retry_state: dict = {}
    client_retries = 0
    hints_honored = 0
    storm_fired_at = None
    restarts = 0
    snapshots = 0
    torn_total = 0
    staged_resumes = 0
    next_req = 0

    def logical(rid: str) -> str:
        return rid.split(".r", 1)[0]

    def classify(lg: str, res) -> None:
        """Closed-loop client: a load-typed reject with attempt budget
        left re-enters the arrival stream after the fleet's
        retry_after_s hint (seeded client jitter on top); anything else
        is the logical request's terminal outcome."""
        nonlocal client_retries, hints_honored
        st = retry_state.get(lg, {"attempt": 0})
        retriable = (
            res.outcome is Outcome.REJECTED
            and res.reject_reason in (
                RejectReason.QUEUE_FULL, RejectReason.NO_REPLICA,
            )
        )
        if retriable and st["attempt"] < 4:
            hint = res.retry_after_s
            if hint is not None:
                hints_honored += 1
            delay = min(
                4.0, hint if hint is not None else 0.25 * 2 ** st["attempt"]
            ) * (1.0 + 0.25 * rng.random())
            retry_state[lg] = {
                "attempt": st["attempt"] + 1,
                "due": clock.now() + delay, "rid": None,
            }
            client_retries += 1
        else:
            retry_state.pop(lg, None)
            delivered[lg] = res

    def fire_retries():
        """Resubmit every due client retry under a fresh attempt id."""
        now = clock.now()
        for lg, st in list(retry_state.items()):
            if st["due"] is None or st["due"] > now:
                continue
            arid = f"{lg}.r{st['attempt']}"
            st["rid"], st["due"] = arid, None
            res = router.submit(replace(by_rid[lg], request_id=arid))
            if res is not None:
                classify(lg, res)

    def poll_results():
        """Deliver new terminal results to the 'client' (attempt ids
        collapse onto their logical request); a re-delivered COMPLETED
        result (outcome record lost to a crash) must match the original
        bitwise — replay idempotency."""
        for rid, res in list(router.results.items()):
            lg = logical(rid)
            if not lg.startswith("soak"):
                continue
            if lg in delivered:
                prev = delivered[lg]
                if (
                    res.outcome is Outcome.COMPLETED
                    and prev.outcome is Outcome.COMPLETED
                ):
                    assert np.array_equal(
                        np.asarray(res.tokens), np.asarray(prev.tokens)
                    ), f"{rid}: re-delivered tokens diverge from original"
                continue
            if res.outcome is Outcome.COMPLETED:
                retry_state.pop(lg, None)
                delivered[lg] = res
                continue
            st = retry_state.get(lg)
            if st is not None:
                # only the latest attempt's terminal result speaks for
                # the logical request; older records are stale
                if st["due"] is None and rid == st["rid"]:
                    classify(lg, res)
                continue
            classify(lg, res)

    def restart():
        """Process death: abandon the router mid-flight, rebuild, load
        the snapshot (verify-on-load), replay the journal — requests
        with a stage-boundary record resume from their LAST COMPLETED
        stage (a journaled image skips VAE entirely; §8.5) — and
        resubmit anything a torn tail dropped (the client-retry
        contract)."""
        nonlocal router, restarts, torn_total, staged_resumes
        restarts += 1
        router._journal.close()  # what a dead process leaves behind
        if rng.random() < 0.5:
            FAULTS.arm("journal_torn", 1)
            armed_total["journal_torn"] = (
                armed_total.get("journal_torn", 0) + 1
            )
        if rng.random() < 0.5:
            FAULTS.arm("snapshot_corrupt", 1)
            armed_total["snapshot_corrupt"] = (
                armed_total.get("snapshot_corrupt", 0) + 1
            )
        router = build_router()
        if Path(snapdir).exists():
            for r in router._replicas:
                if not r.engine.load_prefix_snapshot(snapdir):
                    break  # rejected (corrupt/uncommitted): cold fleet
        torn0 = FAULTS.fired.get("journal_torn", 0)

        def submit_staged(request, tokens, image=None):
            nonlocal staged_resumes
            staged_resumes += 1
            return router.submit_staged(request, tokens, image=image)

        replayed = set(replay_unfinished(
            jpath, router.submit, now=clock.now(),
            submit_staged=submit_staged,
        ))
        torn_total += FAULTS.fired.get("journal_torn", 0) - torn0
        # resubmit what the journal lost (torn tail): the client retry
        # the torn-tail contract prescribes (delivered requests and
        # replayed ones are already accounted)
        for req in requests[:next_req]:
            rid = req.request_id
            if rid in delivered or rid in replayed:
                continue
            if rid in retry_state:
                continue  # the closed-loop client owns this one
            if rid in router.results:
                continue
            if router.submit(req) is not None:
                pass  # typed immediate reject lands in results

    for it in range(iters):
        # staggered arrivals spread across ~80% of the run, with half
        # the workload held back as a storm cohort: while the outage is
        # fresh, demand bursts at several submissions per iteration
        # against a dead fleet and a bounded queue — the retry-storm
        # shape the retry_after_s hints exist to damp (every load-typed
        # reject re-enters through the closed-loop client above)
        storm_window = storm_fired_at is not None and it - storm_fired_at <= 8
        if storm_window:
            burst = min(3, n_req - next_req)
        else:
            cap = n_req - (n_req // 2 if storm_at and it < storm_at else 0)
            arrival_p = min(0.9, n_req / max(1.0, 0.8 * iters))
            burst = 1 if next_req < cap and rng.random() < arrival_p else 0
        for _ in range(burst):
            req = requests[next_req]
            submitted.add(req.request_id)
            next_req += 1
            rejected = router.submit(req)
            if rejected is not None:
                classify(req.request_id, rejected)
        if storm_at and it == storm_at:
            # correlated outage storm: every replica dies at once and
            # the first respawn attempt fails (extending the outage a
            # backoff rung); the NO_REPLICA rejects it sheds are what
            # the client retry pressure rides
            FAULTS.arm("replica_crash", n_replicas)
            FAULTS.arm("replica_respawn_fail", 1)
            armed_total["replica_crash"] = (
                armed_total.get("replica_crash", 0) + n_replicas
            )
            armed_total["replica_respawn_fail"] = (
                armed_total.get("replica_respawn_fail", 0) + 1
            )
            storm_fired_at = it
        if rng.random() < fault_p:
            site = SCHEDULED_SITES[rng.randint(len(SCHEDULED_SITES))]
            FAULTS.arm(site, 1)
            armed_total[site] = armed_total.get(site, 0) + 1
        if snap_every and it and it % snap_every == 0:
            for r in router._replicas:
                if (
                    r.state.value in ("healthy", "degraded", "draining")
                    and r.engine.prefix is not None
                    and len(r.engine.prefix)
                ):
                    r.engine.save_prefix_snapshot(snapdir)
                    snapshots += 1
                    break
        if restart_every and it and it % restart_every == 0:
            restart()
        router.step()
        router.verify_invariants()
        poll_results()
        fire_retries()

    # quiesce: no new faults, drive everything to a terminal outcome
    # (leftover armed faults would keep killing a fleet trying to finish)
    fired = dict(FAULTS.fired)
    FAULTS.reset()
    steps = 0
    while True:
        poll_results()
        missing = submitted - set(delivered)
        if not missing:
            break
        live_ids = {r.request_id for r in router.live_requests()}
        # a retry attempt lost to a crash (admission torn before the
        # journal saw it) never produces a record in this incarnation:
        # re-arm it so fire_retries resubmits under the same attempt id
        for lg, st in retry_state.items():
            if (
                st["due"] is None
                and st["rid"] is not None
                and st["rid"] not in router.results
                and st["rid"] not in live_ids
            ):
                st["due"] = clock.now()
                st["rid"] = None
        fire_retries()
        # client retry for anything lost without a typed record visible
        # to this incarnation (torn admissions after a crash)
        for req in requests[:next_req]:
            rid = req.request_id
            if (
                rid in missing
                and rid in retry_state
            ):
                continue  # the closed-loop client owns this one
            if (
                rid in missing
                and rid not in router.results
                and rid not in live_ids
            ):
                router.submit(req)
        router.step()
        steps += 1
        router.verify_invariants()
        assert steps < 20_000, (
            f"soak quiesce made no progress: {sorted(missing)} undelivered"
        )
    router.verify_invariants()

    # ---- the gate ----
    outcomes: dict = {}
    mismatches = []
    for rid in sorted(submitted):
        res = delivered[rid]
        outcomes[res.outcome.value] = outcomes.get(res.outcome.value, 0) + 1
        ref = reference[rid]
        # survivor bit-parity: tokens for every token-bearing outcome;
        # the decoded image too wherever the pipeline produced one
        # (COMPLETED and the typed-degraded completed_unranked) — the
        # (seed, position) replay contract extended through the stages
        if res.outcome in (
            Outcome.COMPLETED, Outcome.COMPLETED_TOKENS_ONLY,
            Outcome.COMPLETED_UNRANKED,
        ) and not np.array_equal(np.asarray(res.tokens),
                                 np.asarray(ref.tokens)):
            mismatches.append(rid)
        elif res.image is not None and not np.array_equal(
            res.image, ref.image
        ):
            mismatches.append(rid)
        elif (res.outcome is Outcome.COMPLETED
              and res.rerank_score != ref.rerank_score):
            mismatches.append(rid)
    completed = outcomes.get("completed", 0)
    ok = not mismatches and completed >= 1 and len(delivered) >= len(submitted)
    return {
        "ok": bool(ok),
        "iters": iters,
        "seed": seed,
        "n_replicas": n_replicas,
        "submitted": len(submitted),
        "outcomes": outcomes,
        "completed_bit_identical": not mismatches,
        "mismatched": mismatches,
        "faults_armed": armed_total,
        "faults_fired": fired,
        "client_retries": client_retries,
        "retry_hints_honored": hints_honored,
        "storm_at": storm_fired_at,
        "restarts": restarts,
        "snapshots_saved": snapshots,
        "journal_torn_dropped": torn_total,
        "staged_resumes": staged_resumes,
        "replica_states": router.replica_states(),
    }


def run_stage_restart_drill(seed: int = 0) -> dict:
    """Deterministic mid-stage kill/replay drill (docs/DESIGN.md §8.5):
    the process dies with one request parked mid-VAE_DECODE and another
    parked mid-CLIP_RERANK (its decoded image already journaled). The
    restarted fleet must resume EACH from its last journaled stage
    boundary — the mid-rerank request must NOT re-run the VAE (exactly
    one VAE dispatch row in the new incarnation), both must finish
    COMPLETED, and tokens/image/score must be bitwise-identical to a
    fault-free reference run.

    Parking is made deterministic with a long-backoff retry policy (one
    armed stage fault -> the item waits ~100 virtual seconds before its
    next attempt, far longer than the drill runs before "crashing")."""
    import numpy as np

    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, FakeClock, Outcome, Request, RequestJournal,
        Router, RouterConfig, replay_unfinished,
    )
    from dalle_pytorch_tpu.serving.postdecode import (
        STAGE_RERANK, STAGE_VAE, StageConfig,
    )
    from dalle_pytorch_tpu.utils.faults import FAULTS
    from dalle_pytorch_tpu.utils.metrics import counters
    from dalle_pytorch_tpu.utils.resilience import RetryPolicy
    from serve_smoke import build_tiny_model, build_tiny_stages

    dalle, params = build_tiny_model()
    parked_cfg = StageConfig(retry=RetryPolicy(
        attempts=5, base_delay=100.0, max_delay=100.0, jitter=0.0,
        retry_on=(),
    ))
    stages = build_tiny_stages(config=parked_cfg)

    rng = np.random.RandomState(seed)
    reqs = [
        Request(
            request_id=f"mid{i}",
            prompt=rng.randint(1, 16, size=(4,)).astype(np.int32),
            max_new_tokens=dalle.image_seq_len, seed=77 + i,
        )
        for i in range(2)
    ]

    # fault-free reference (default stage config — retry timing cannot
    # change stage values, only when they are produced)
    ref_engine = Engine(
        dalle, params, EngineConfig(max_batch=2, prefill_chunk=2),
        stages=build_tiny_stages(),
    )
    for req in reqs:
        assert ref_engine.submit(req) is None
    reference = ref_engine.run(max_steps=20_000)
    assert all(
        reference[r.request_id].outcome is Outcome.COMPLETED for r in reqs
    )

    tmp = tempfile.mkdtemp(prefix="stage_restart_")
    jpath = os.path.join(tmp, "journal.jsonl")
    clock = FakeClock(step_dt=0.05)
    engine_cfg = EngineConfig(max_batch=2, prefill_chunk=2)
    router_cfg = RouterConfig(n_replicas=1, respawn=False)

    def build() -> Router:
        return Router(
            dalle, params, router_cfg, engine_cfg, clock=clock,
            journal=RequestJournal(jpath), stages=stages,
        )

    FAULTS.reset()
    router = build()

    def parked(rid: str, stage: str):
        pd = router._replicas[0].engine.postdecode
        for st in pd._staged:
            if (st.entry.request.request_id == rid and st.stage == stage
                    and st.attempts > 0):
                return st
        return None

    # 1) mid1: tokens -> VAE ok (image journaled) -> first rerank
    #    dispatch fails -> parked mid-CLIP_RERANK on the long backoff
    FAULTS.arm("rerank_fail", 1)
    assert router.submit(reqs[1]) is None
    for _ in range(1500):
        router.step()
        if parked("mid1", STAGE_RERANK) is not None:
            break
    st1 = parked("mid1", STAGE_RERANK)
    assert st1 is not None and st1.image is not None, (
        "mid1 never parked mid-rerank with a decoded image"
    )

    # 2) mid0: tokens -> first VAE dispatch fails -> parked mid-VAE
    FAULTS.arm("vae_decode_fail", 1)
    assert router.submit(reqs[0]) is None
    for _ in range(1500):
        router.step()
        if parked("mid0", STAGE_VAE) is not None:
            break
    st0 = parked("mid0", STAGE_VAE)
    assert st0 is not None and st0.image is None, (
        "mid0 never parked mid-vae"
    )
    assert parked("mid1", STAGE_RERANK) is not None, (
        "mid1 escaped its backoff before the crash"
    )

    # 3) the process dies with both parked mid-stage
    router._journal.close()
    labels = {"replica": "0"}
    vae0 = counters.get("serve.stage.vae_images", labels=labels)
    rr0 = counters.get("serve.stage.reranked", labels=labels)

    # 4) restart: journal replay resumes each from its last completed
    #    stage — mid0 pre-VAE (no image), mid1 post-VAE (image in hand)
    router = build()
    resumes: dict = {}

    def submit_staged(request, tokens, image=None):
        resumes[request.request_id] = image
        return router.submit_staged(request, tokens, image=image)

    replayed = set(replay_unfinished(
        jpath, router.submit, now=clock.now(), submit_staged=submit_staged,
    ))
    assert replayed == {"mid0", "mid1"}, replayed
    assert set(resumes) == {"mid0", "mid1"}, resumes
    assert resumes["mid0"] is None, "mid0 resumed WITH an image pre-VAE"
    assert resumes["mid1"] is not None, "mid1 lost its journaled image"

    for _ in range(1500):
        router.step()
        if all(r.request_id in router.results for r in reqs):
            break
    router.verify_invariants()

    vae_delta = counters.get("serve.stage.vae_images", labels=labels) - vae0
    rr_delta = counters.get("serve.stage.reranked", labels=labels) - rr0
    assert vae_delta == 1, (
        f"expected exactly one VAE row after restart (mid0 only; mid1 "
        f"resumes past VAE), got {vae_delta}"
    )
    assert rr_delta == 2, f"expected both requests reranked, got {rr_delta}"

    for req in reqs:
        res = router.results[req.request_id]
        ref = reference[req.request_id]
        assert res.outcome is Outcome.COMPLETED, (
            f"{req.request_id}: {res.outcome}"
        )
        assert np.array_equal(
            np.asarray(res.tokens), np.asarray(ref.tokens)
        ), f"{req.request_id}: tokens diverge after mid-stage restart"
        assert np.array_equal(res.image, ref.image), (
            f"{req.request_id}: image not bit-identical after restart"
        )
        assert res.rerank_score == ref.rerank_score, (
            f"{req.request_id}: rerank score diverged"
        )
    return {
        "ok": True,
        "staged_resumes": sorted(resumes),
        "vae_rows_after_restart": int(vae_delta),
        "reranked_after_restart": int(rr_delta),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--iters", type=int, default=120,
                    help="fault-injection iterations (quick gate default)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--fault-p", type=float, default=0.25,
                    help="per-iteration probability of arming a fault")
    ap.add_argument("--restart-every", type=int, default=40,
                    help="process-crash-and-restart period (0 = never)")
    ap.add_argument("--snap-every", type=int, default=15,
                    help="prefix snapshot period (0 = never)")
    ap.add_argument("--storm-at", type=int, default=-1,
                    help="iteration of the correlated full-fleet outage "
                         "storm (-1 = midpoint, 0 = never)")
    args = ap.parse_args(argv)

    # static-analysis pre-flight (docs/DESIGN.md §11), the same three
    # stages as the other gate tools (tools/serve_smoke.py): a corrupt
    # tree, a drifted serving-jit contract, or a collective smuggled
    # into a serving program must fail the soak BEFORE any fault is
    # armed — a chaos gate over a broken build proves nothing
    from serve_smoke import lint_preflight

    if lint_preflight(label="chaos soak") != 0:
        return 1

    drill = run_stage_restart_drill(seed=args.seed)
    print("stage restart drill:", json.dumps(drill, sort_keys=True),
          file=sys.stderr)

    summary = run_soak(
        iters=args.iters, seed=args.seed, n_replicas=args.replicas,
        n_req=args.requests, fault_p=args.fault_p,
        restart_every=args.restart_every, snap_every=args.snap_every,
        storm_at=args.storm_at,
    )
    print(json.dumps(summary, indent=1, sort_keys=True))
    if not summary["ok"]:
        print("chaos soak FAILED", file=sys.stderr)
        return 1
    print(
        f"chaos soak OK: {summary['submitted']} requests all typed across "
        f"{summary['restarts']} process restarts, completed survivors "
        "bit-identical to the fault-free reference", file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
