#!/usr/bin/env python
"""Telemetry release gate: serve_smoke's 3-request scenario with the
flight recorder on, then validate every observability artifact.

Runs ``tools/serve_smoke.py``'s continuous-batching pass in-process with
telemetry enabled, drains the ring, and checks the three contracts a
release needs (docs/DESIGN.md §9):

1. the flight-recorder JSONL parses line-for-line and its spans BALANCE
   (every ``E`` matches a prior ``B``; nothing left open after a clean
   run; zero ring drops);
2. every serving request appears as a ``serve.request`` span chain
   ending in a typed outcome that sums to the engine's own counters —
   including the CHUNKED-prefill pass, whose ``serve.prefill_chunk``
   spans and ``serve.ttft_s`` histogram must be present, and the
   prefix-cache cold/warm replay, whose warm full-hit requests open no
   prefill span at all yet must still close their chains typed, and the
   SPECULATIVE pass, whose per-iteration ``serve.spec_verify`` spans
   (draft+verify+accept dispatch plus synchronous readback) must appear
   balanced with the ``serve_spec_*`` counter series rendering in
   ``/metrics``;
3. the ``/metrics`` exposition renders (every sample line parses as
   ``name{...} value``);
4. the long-prompt-arrival-during-steady-decode interference scenario
   (``_interference_max_gap``, on the tiny model, monolithic and chunked
   prefill) runs with the recorder on, its max-decode-gap is finite and
   positive, and the spans it adds still balance;
5. a 2-replica router pass (serving/router.py) runs traced: every
   request gets a balanced ``router.request`` span chain ending typed,
   the per-replica labeled series (``serve_submitted{replica="0"}``)
   render in the exposition, and ``Engine.verify_invariants`` /
   ``Router.verify_invariants`` — the same public invariant surface the
   router's health machine probes — hold after the run;
6. a controller-on pass (serving/control.py, ISSUE 19) runs traced on
   virtual time: every Controller evaluation lands as one
   ``serve.control.decision`` instant event in the flight file (one per
   decision-log entry — the auditable decision record), the spans the
   pass adds still balance, and the ``serve_vitals_*`` gauges plus the
   ``serve_control_*`` series render in ``/metrics``.

Exit 0 iff all hold::

    python tools/telemetry_smoke.py [--dir DIR]

Composes with fault drills the same way serve_smoke does — e.g.
``DALLE_TPU_FAULTS="prefill_fail=1" python tools/telemetry_smoke.py``
must still pass, with the retry visible in the trace.
"""

from __future__ import annotations

import json
import math
import os
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

# a CPU gate: assigned, not defaulted (tools/serve_smoke.py)
os.environ["JAX_PLATFORMS"] = "cpu"


def _non_postmortem_unclosed(path, summary) -> list:
    """Unclosed spans OTHER than serve_smoke's recovery-drill
    postmortem. A crashed incarnation's ``serve.request`` /
    ``router.request`` chains legally stay open in the flight file —
    they ARE the postmortem of what died in flight (docs/DESIGN.md §9)
    — PROVIDED the restarted incarnation re-opened and closed the same
    request typed later in the file (the §8.3 replay contract). Anything
    else unclosed is a real balance failure."""
    by_id: dict = {}      # span id -> (B record, file position)
    closed: set = set()
    with open(path) as f:
        for pos, line in enumerate(f):
            rec = json.loads(line)
            if rec.get("ph") == "B":
                by_id[rec["id"]] = (rec, pos)
            elif rec.get("ph") == "E":
                closed.add(rec["id"])
    out = []
    for rec in summary["unclosed_records"]:
        _, open_pos = by_id.get(rec["id"], (rec, -1))
        if rec["name"] in ("serve.request", "router.request") and any(
            b["id"] in closed
            and b["name"] == rec["name"]
            and b.get("request_id") == rec.get("request_id")
            and b_pos > open_pos  # the REPLAY chain, not a pre-crash one
            for b, b_pos in by_id.values()
        ):
            continue
        out.append(rec)
    return out


def _interference_max_gap(dalle, params, prefill_chunk) -> float:
    """One request in steady decode, then a full-length prompt arrives
    mid-stream. -> the max gap (s) between decode iterations over the
    window from the late submit to the late request's first token: a
    monolithic prefill is one gap holding the whole prefill, chunked
    prefill bounds each gap by a chunk plus a decode step. Decode
    iterations are read off the ``serve.decode_steps`` counter."""
    import numpy as np

    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, Outcome, Request, check_accounting,
    )
    from dalle_pytorch_tpu.utils.metrics import counters

    engine = Engine(dalle, params, EngineConfig(
        max_batch=2, prefill_chunk=prefill_chunk,
    ))
    n_text, n_image = dalle.text_seq_len, dalle.image_seq_len
    # compile time is not interference: both slots warm outside the window
    for i in range(2):
        engine.submit(Request(
            request_id=f"__warm{i}__", prompt=np.zeros(n_text, np.int32),
            max_new_tokens=min(4, n_image), seed=0,
        ))
    engine.run()
    prompts = np.random.RandomState(0).randint(
        1, dalle.num_text_tokens, size=(2, n_text)
    ).astype(np.int32)
    engine.submit(Request(
        request_id="steady", prompt=prompts[0],
        max_new_tokens=min(6, n_image), seed=1,
    ))
    prev = counters.get("serve.decode_steps")
    while counters.get("serve.decode_steps") - prev < 3:
        engine.step()  # the steady request is admitted and decoding
    t_sub = engine.clock.now()
    engine.submit(Request(
        request_id="late", prompt=prompts[1],
        max_new_tokens=min(2, n_image), seed=2,
    ))
    ts = []
    prev = counters.get("serve.decode_steps")
    while engine.step():
        cur = counters.get("serve.decode_steps")
        if cur > prev:
            ts.append(engine.clock.now())
            prev = cur
    check_accounting(engine)
    for rid in ("steady", "late"):
        assert engine.results[rid].outcome is Outcome.COMPLETED, (
            rid, engine.results[rid]
        )
    window_end = t_sub + engine.results["late"].ttft_s
    window = [t_sub] + [t for t in ts if t < window_end] + [window_end]
    return float(np.max(np.diff(window)))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--dir" in argv:
        out_dir = argv[argv.index("--dir") + 1]
    else:
        out_dir = tempfile.mkdtemp(prefix="dalle_telemetry_smoke_")

    import serve_smoke

    # static-analysis pre-flight (docs/DESIGN.md §11), ALL THREE stages:
    # the AST lint fails a corrupt tree fast, the trace stage
    # (`lint.py --trace --check`) holds the serving jits to their
    # committed compile-signature/donation/readback/HBM contracts, and
    # the shard stage (`lint.py --shard --check`) to the committed
    # no-collectives-in-serving baseline, before the recorder or any
    # engine exists. serve_smoke would also run it, but this gate must
    # fail even when a future refactor stops composing the two.
    if serve_smoke.lint_preflight(label="telemetry smoke") != 0:
        return 1

    from dalle_pytorch_tpu.utils.metrics import counters
    from dalle_pytorch_tpu.utils.telemetry import (
        TELEMETRY,
        validate_flight_file,
    )

    TELEMETRY.configure(enabled=True, flight_dir=out_dir)

    rc = serve_smoke.main()
    if rc != 0:
        print("telemetry smoke FAILED: serve_smoke returned nonzero",
              file=sys.stderr)
        return 1

    path = TELEMETRY.drain("smoke")
    if path is None:
        print("telemetry smoke FAILED: drain produced no flight file",
              file=sys.stderr)
        return 1

    # -- 1. parse + span balance ------------------------------------------
    summary = validate_flight_file(path)
    ok = True

    def check(cond: bool, what: str) -> None:
        nonlocal ok
        if not cond:
            ok = False
            print(f"telemetry smoke FAILED: {what}", file=sys.stderr)

    unbalanced = _non_postmortem_unclosed(path, summary)
    check(unbalanced == [],
          f"unbalanced spans beyond the recovery-drill postmortem: "
          f"{unbalanced}")
    check(TELEMETRY.dropped == 0,
          f"{TELEMETRY.dropped} ring drops in a 3-request run")
    check(TELEMETRY.sink_errors == 0,
          f"{TELEMETRY.sink_errors} flight-recorder sink errors")

    # -- 2. one complete span chain per request, typed outcome ------------
    # submissions span the unlabeled engines AND the recovery drill's
    # router-owned (replica-labeled) engines; a chain is accounted when
    # it either ended typed or is the crash postmortem counted above
    n_req = counters.get("serve.submitted")
    for rid in ("0", "1"):
        n_req += counters.get("serve.submitted", labels={"replica": rid})
    check(n_req >= 3, f"expected >=3 submissions, saw {n_req}")
    outcomes: dict = {}
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("name") == "serve.request" and rec["ph"] == "E":
                check("outcome" in rec,
                      f"serve.request span ended without outcome: {rec}")
                o = rec.get("outcome")
                outcomes[o] = outcomes.get(o, 0) + 1
    unclosed_serve = sum(
        1 for rec in summary["unclosed_records"]
        if rec["name"] == "serve.request"
    )
    check(sum(outcomes.values()) + unclosed_serve == n_req,
          f"{n_req} submitted but {sum(outcomes.values())} request spans "
          f"ended + {unclosed_serve} postmortem ({outcomes})")
    n_completed = counters.get("serve.completed")
    for rid in ("0", "1"):
        n_completed += counters.get(
            "serve.completed", labels={"replica": rid}
        )
    check(outcomes.get("completed", 0) == n_completed,
          f"span outcomes {outcomes} disagree with counter "
          f"serve.completed={n_completed}")

    # chunked-prefill observability: serve_smoke's chunked pass must have
    # left per-chunk spans and the TTFT histogram behind. Count via the
    # validator's by_name (B+E records, rotated generations included)
    # rather than re-parsing the file by hand.
    n_chunk_spans = summary["by_name"].get("serve.prefill_chunk", 0) // 2
    check(n_chunk_spans >= 2,
          f"expected >=2 serve.prefill_chunk spans from the chunked pass, "
          f"saw {n_chunk_spans}")
    from dalle_pytorch_tpu.utils.metrics import histograms
    check(histograms.get("serve.ttft_s") is not None,
          "serve.ttft_s histogram missing after the serving passes")

    # speculative-pass observability (ISSUE 11): every speculative
    # iteration opened one serve.spec_verify span (validate_flight_file
    # already proved balance above), and the draft/accept accounting
    # rendered as counter series + the accepted-per-step histogram
    n_spec_spans = summary["by_name"].get("serve.spec_verify", 0) // 2
    check(n_spec_spans >= 1,
          f"expected >=1 serve.spec_verify spans from the speculative "
          f"pass, saw {n_spec_spans}")
    check(histograms.get("serve.spec_accepted_per_step") is not None,
          "serve.spec_accepted_per_step histogram missing after the "
          "speculative pass")

    # -- 3. the exposition renders ----------------------------------------
    dump = TELEMETRY.dump()
    check("serve_submitted" in dump and "_bucket{" in dump,
          "dump() is missing serving counters or histogram buckets")
    for series in ("serve_spec_drafted", "serve_spec_accepted",
                   "serve_spec_accept_frac"):
        check(series in dump,
              f"speculative series {series!r} missing from /metrics")
    for line in dump.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        try:
            float(value)
        except ValueError:
            check(False, f"unparseable exposition line: {line!r}")
        check(bool(name), f"unparseable exposition line: {line!r}")

    # -- 4. interference scenario with the recorder on --------------------
    dalle, params = serve_smoke.build_tiny_model()
    gaps = {
        chunk: _interference_max_gap(dalle, params, chunk)
        for chunk in (None, 2)
    }
    check(
        all(math.isfinite(g) and g > 0 for g in gaps.values()),
        f"interference gap metric not finite: {gaps}",
    )
    ipath = TELEMETRY.drain("interference")
    check(ipath is not None, "interference drain produced no flight file")
    if ipath is not None:
        isummary = validate_flight_file(ipath)
        iunbalanced = _non_postmortem_unclosed(ipath, isummary)
        check(iunbalanced == [],
              f"interference spans left open: {iunbalanced}")

    # -- 5. replicated front door, traced ---------------------------------
    import numpy as np

    from dalle_pytorch_tpu.serving import (
        EngineConfig, Outcome, Request, Router, RouterConfig,
    )

    router = Router(
        dalle, params, RouterConfig(n_replicas=2),
        EngineConfig(max_batch=2, prefill_chunk=2),
    )
    rng = np.random.RandomState(3)
    for i in range(4):
        router.submit(Request(
            request_id=f"router{i}",
            prompt=rng.randint(1, 16, size=(4,)).astype(np.int32),
            max_new_tokens=dalle.image_seq_len, seed=200 + i,
        ))
    router.run(max_steps=2000)
    router.verify_invariants()          # fleet-level accounting
    for r in router._replicas:
        r.engine.verify_invariants(idle=True)  # each engine, idle-strict
    check(
        all(res.outcome is Outcome.COMPLETED
            for res in router.results.values()),
        f"router pass outcomes: {[r.outcome.value for r in router.results.values()]}",
    )
    rpath = TELEMETRY.drain("router")
    check(rpath is not None, "router drain produced no flight file")
    router_spans = 0
    if rpath is not None:
        rsummary = validate_flight_file(rpath)
        runbalanced = _non_postmortem_unclosed(rpath, rsummary)
        check(runbalanced == [],
              f"router spans left open: {runbalanced}")
        router_spans = rsummary["by_name"].get("router.request", 0) // 2
        check(router_spans >= 4,
              f"expected >=4 router.request spans, saw {router_spans}")
    dump = TELEMETRY.dump()
    for series in ('serve_submitted{replica="0"}',
                   'serve_submitted{replica="1"}',
                   "router_completed", "router_queued"):
        check(series in dump,
              f"per-replica/router series {series!r} missing from /metrics")

    # -- 6. adaptive control loop, traced (ISSUE 19) ----------------------
    from dalle_pytorch_tpu.serving import ControlConfig, Engine, FakeClock

    eng = Engine(dalle, params, EngineConfig(
        max_batch=2, prefill_chunk=2, fused_iteration=True,
        controller=True,
        control=ControlConfig(interval=2),
    ), clock=FakeClock(step_dt=1.0))
    rng = np.random.RandomState(5)
    for i in range(3):
        eng.submit(Request(
            request_id=f"ctrl{i}",
            prompt=rng.randint(1, 16, size=(4,)).astype(np.int32),
            max_new_tokens=dalle.image_seq_len, seed=300 + i,
        ))
    eng.run(max_steps=800)
    eng.verify_invariants(idle=True)
    check(
        all(res.outcome is Outcome.COMPLETED
            for res in eng.results.values()),
        f"controller pass outcomes: "
        f"{[r.outcome.value for r in eng.results.values()]}",
    )
    check(len(eng.controller.log) >= 1,
          "controller pass finished without a single evaluation")
    cpath = TELEMETRY.drain("control")
    check(cpath is not None, "control drain produced no flight file")
    decision_events = 0
    if cpath is not None:
        csummary = validate_flight_file(cpath)
        cunbalanced = _non_postmortem_unclosed(cpath, csummary)
        check(cunbalanced == [],
              f"controller-pass spans left open: {cunbalanced}")
        decision_events = csummary["by_name"].get(
            "serve.control.decision", 0
        )
        check(decision_events == len(eng.controller.log),
              f"{len(eng.controller.log)} controller decisions but "
              f"{decision_events} serve.control.decision events in the "
              f"flight file — the audit trail is incomplete")
    dump = TELEMETRY.dump()
    for series in ("serve_vitals_occupancy", "serve_vitals_decode_gap_s",
                   "serve_control_decisions",
                   "serve_control_budget"):
        check(series in dump,
              f"vitals/control series {series!r} missing from /metrics")

    print(json.dumps({
        "flight_file": path,
        "records": summary["records"],
        "spans": summary["spans"],
        "request_outcomes": outcomes,
        "by_name": summary["by_name"],
        "prefill_chunk_spans": n_chunk_spans,
        "spec_verify_spans": n_spec_spans,
        "interference_max_gap_ms": round(gaps[2] * 1e3, 1),
        "interference_monolithic_max_gap_ms": round(gaps[None] * 1e3, 1),
        "router_request_spans": router_spans,
        "control_decision_events": decision_events,
    }))
    if not ok:
        return 1
    print(f"telemetry smoke OK: {n_req} request span chains balanced, "
          f"{summary['records']} records, /metrics renders, interference "
          f"scenario traced, router pass traced with per-replica series, "
          f"controller pass traced with {decision_events} decision events",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
