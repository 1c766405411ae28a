#!/usr/bin/env python
"""Serving-engine release gate: continuous-batching passes on CPU.

Builds a tiny DALLE in-process (no checkpoint needed) and drives the full
engine lifecycle seven times — CHUNKED prefill (budget-bounded prompt
chunks interleaved with decode; the production serving shape),
monolithic, FUSED (the whole iteration as one ragged ``_iteration_jit``
dispatch; ROADMAP 1), SPECULATIVE (ROADMAP 2: each decode row
self-drafts and the single ragged dispatch verifies — exact acceptance
makes the stream bit-identical to plain decode by construction),
QUANTIZED-KV split and fused (ISSUE 14: int8 paged pools + per-(token,
head) scale pools, dequantized at read — the two quantized passes must
match each other BITWISE, and match the unquantized passes to the
pinned token-agreement floor, never bitwise), and a
PREFIX-CACHE cold/warm replay (ROADMAP 3: the same 3-request scenario
twice through one engine with the content-addressed page index on; the
warm round must hit and match the cold round bitwise) — verifying the
accounting invariant each time:
every request ends in a typed outcome, all pages return to the pool
(the prefix pass additionally checks refcount accounting — references
== mapped table entries, no leaks after drain), and all modes produce
BIT-identical tokens.
A further deterministic drill (FakeClock) lands a deadline MID-PREFILL
and asserts the pages come back that iteration. Exit 0 iff all requests
of all three passes COMPLETE and the drill terminates typed — the gate
a release pipeline runs before shipping a serving build::

    python tools/serve_smoke.py

A post-decode STAGE drill (docs/DESIGN.md §8.5) additionally drives the
tokens -> VAE decode -> CLIP rerank pipeline: clean completions with
images bit-identical to a direct VAE decode, transient stage faults
(``vae_decode_fail``/``rerank_fail``/``stage_timeout``) absorbed by
retry with unchanged bits, and retry exhaustion completing
typed-degraded (``completed_tokens_only`` / ``completed_unranked``) —
never stalled.

Composes with the fault registry for pipeline fault drills. The chunked
pass runs FIRST, so an armed ``prefill_fail`` fires at CHUNK granularity
and the retry must resume from the last completed chunk; an armed
``prefix_hash_collide`` forges a warm-round probe (token verification
must degrade it to cold prefill, tokens still bit-identical) and
``prefix_publish_fail`` drops a cold-round publish (fail-open — later
rounds republish)::

    DALLE_TPU_FAULTS="prefill_fail=1" python tools/serve_smoke.py
    DALLE_TPU_FAULTS="prefix_hash_collide=1" python tools/serve_smoke.py

``--replicas N`` additionally drives the replicated front door
(serving/router.py) through a chaos drill: N replicas serve 2N chunked
requests, ``replica_crash`` is armed MID-RUN to kill the busiest
replica, and the gate requires every request to COMPLETE with tokens
bit-identical to a no-crash router pass — the cross-replica failover
contract. Env-armed faults compose with the drill the same way::

    DALLE_TPU_FAULTS="prefill_fail=1" python tools/serve_smoke.py --replicas 2

Accounting everywhere is asserted through the PUBLIC
``Engine.verify_invariants`` / ``Router.verify_invariants`` — the gate
checks the same invariant surface the router's health machine probes in
production, not a private test helper.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# a CPU gate (tiny dim_head=8 models no kernel compiles for): assigned, not
# defaulted — on a chip host that exports JAX_PLATFORMS=tpu this process
# and the lint children it spawns would otherwise open the chip
os.environ["JAX_PLATFORMS"] = "cpu"


def lint_preflight(label: str = "serve smoke") -> int:
    """Static-analysis pre-flight (docs/DESIGN.md §11), in escalation
    order: first the AST stage alone (``lint.py --check`` — stdlib-only,
    so a corrupt tree still fails in milliseconds), then the TRACE + SHARD
    composition (``lint.py --trace --shard --check``, one subprocess —
    the CLI composes both contract stages in one exit code, so the
    preflight pays one jax+package import, not two): every serving jit
    this gate is about to drive must match its committed
    compile-signature/donation/readback/HBM contract
    (tools/trace_contracts.json) AND hold the committed "no collectives
    in serving" baseline, with the train step holding its per-mesh-kind
    collective/sharding contract (tools/shard_contracts.json), BEFORE a
    request is admitted. Subprocesses on purpose: the AST stage must
    not inherit this process's jax initialization, and the contract
    stages re-import the package fresh so a broken import fails the
    gate, not the drill."""
    import subprocess

    for stage, script, args in (
        ("lint", "lint.py", ["--check"]),
        ("contract-lint", "lint.py", ["--trace", "--shard", "--check"]),
    ):
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / script), *args],
            capture_output=True, text=True, cwd=REPO,
        )
        if proc.returncode != 0:
            print(f"{label} FAILED: {stage} pre-flight found invariant "
                  f"violations:\n{proc.stdout}{proc.stderr}",
                  file=sys.stderr)
            return proc.returncode
    return 0


def build_tiny_model():
    """The gate's model: tiny, rotary, shift-tokens — built in-process so
    the gate needs no checkpoint. Shared with tools/telemetry_smoke.py."""
    import jax
    import numpy as np

    from dalle_pytorch_tpu.models import DALLE

    dalle = DALLE(
        dim=32, depth=2, num_text_tokens=16, text_seq_len=4,
        num_image_tokens=12, image_fmap_size=2, heads=2, dim_head=8,
        attn_types=("full",), rotary_emb=True,
    )
    rng = np.random.RandomState(0)
    text = rng.randint(1, 16, size=(1, 4)).astype(np.int32)
    image = rng.randint(0, 12, size=(1, 4)).astype(np.int32)
    params = dalle.init(jax.random.key(0), text, image)["params"]
    return dalle, params


def build_tiny_stages(config=None):
    """A ``StageSpec`` over the CANONICAL tiny VAE + CLIP — the same
    configs the trace-contract registry pins for ``serving.vae_decode``
    / ``serving.clip_rerank`` (tools/lint/trace/registry.py), so every
    gate that builds stages through this helper (this drill,
    tools/chaos_soak.py, the unit tests) dispatches
    the exact contracted signatures. VAE params are the decode-scope
    tree (``init(..., method="decode")``): the pipeline's contract is
    token ids -> pixels."""
    import jax
    import numpy as np

    from dalle_pytorch_tpu.models.clip import CLIP
    from dalle_pytorch_tpu.models.vae import DiscreteVAE
    from dalle_pytorch_tpu.serving import StageSpec

    if str(REPO / "tools") not in sys.path:
        sys.path.insert(0, str(REPO / "tools"))
    from lint.trace.registry import CANON_CLIP, CANON_VAE

    vae = DiscreteVAE(**CANON_VAE)
    vae_params = vae.init(
        jax.random.key(1), np.zeros((1, vae.image_seq_len), np.int32),
        method="decode",
    )["params"]
    clip = CLIP(**CANON_CLIP)
    clip_params = clip.init(
        jax.random.key(2), np.ones((1, clip.text_seq_len), np.int32),
        np.zeros((1, vae.image_size, vae.image_size, vae.channels),
                 np.float32),
    )["params"]
    kw = {} if config is None else {"config": config}
    return StageSpec(vae=vae, vae_params=vae_params, clip=clip,
                     clip_params=clip_params, **kw)


def run_stage_drill(dalle, params) -> bool:
    """The post-decode pipeline gate (docs/DESIGN.md §8.5): four passes
    over a staged engine on FakeClock (deterministic backoff windows).

    1. CLEAN: 3 requests complete the full tokens -> VAE -> rerank
       pipeline; every image must be BIT-identical to a direct
       ``vae.apply(method="decode")`` of the request's own tokens.
    2. TRANSIENT faults: ``vae_decode_fail=2`` + ``rerank_fail=1`` +
       ``stage_timeout=1`` armed — all within the retry budget, so all
       3 requests still COMPLETE with tokens AND images bit-identical
       to the clean pass, with the retries counted.
    3. VAE retry EXHAUSTION (one request, 3 armed failures): the
       request completes typed-degraded ``completed_tokens_only``.
    4. RERANK exhaustion: typed-degraded ``completed_unranked`` — the
       decoded image survives, bit-identical to the clean pass.

    Env-composed drills (the DTL033 registry contract) ride the same
    passes — counts <= 2 are absorbed by retry (pass 2's shape),
    higher counts surface as typed-degraded outcomes, never stalls::

        DALLE_TPU_FAULTS="vae_decode_fail=2" python tools/serve_smoke.py
        DALLE_TPU_FAULTS="rerank_fail=1,stage_timeout=1" python tools/serve_smoke.py
    """
    import numpy as np

    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, FakeClock, Outcome, Request,
    )
    from dalle_pytorch_tpu.utils.faults import FAULTS
    from dalle_pytorch_tpu.utils.metrics import counters

    spec = build_tiny_stages()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 16, size=(4,)).astype(np.int32)
               for _ in range(3)]

    def run_pass(label, n_req, arm=()):
        eng = Engine(
            dalle, params, EngineConfig(max_batch=2, prefill_chunk=2),
            stages=spec, clock=FakeClock(step_dt=0.05),
        )
        for site, count in arm:
            FAULTS.arm(site, count)
        for i in range(n_req):
            assert eng.submit(Request(
                request_id=f"stage{i}", prompt=prompts[i],
                max_new_tokens=dalle.image_seq_len, seed=40 + i,
            )) is None
        results = eng.run(max_steps=4000)
        eng.verify_invariants(idle=True)
        for rid in sorted(results):
            print(json.dumps({"pass": label, **results[rid].to_json()}))
        print(json.dumps({"pass": label, "stats": eng.stats()}))
        return results

    ok = True
    clean = run_pass("stage_clean", 3)
    for rid, res in clean.items():
        if res.outcome is not Outcome.COMPLETED or res.image is None \
                or res.rerank_score is None:
            ok = False
            print(f"serve smoke FAILED: stage clean {rid} not fully "
                  f"completed ({res.outcome.value})", file=sys.stderr)
            continue
        direct = np.asarray(spec.vae.apply(
            {"params": spec.vae_params},
            np.asarray(res.tokens, np.int32)[None, :], method="decode",
        ))[0].astype(np.float32)
        if not np.array_equal(direct, res.image):
            ok = False
            print(f"serve smoke FAILED: stage clean {rid} image diverges "
                  "from a direct VAE decode of its own tokens",
                  file=sys.stderr)

    retries0 = counters.get("serve.stage.retries")
    faulted = run_pass("stage_faults", 3, arm=(
        ("vae_decode_fail", 2), ("rerank_fail", 1), ("stage_timeout", 1),
    ))
    if counters.get("serve.stage.retries") <= retries0:
        ok = False
        print("serve smoke FAILED: stage fault pass consumed no retries",
              file=sys.stderr)
    for rid, res in faulted.items():
        if res.outcome is not Outcome.COMPLETED:
            ok = False
            print(f"serve smoke FAILED: {rid} did not absorb transient "
                  f"stage faults ({res.outcome.value})", file=sys.stderr)
        elif not (np.array_equal(np.asarray(res.tokens),
                                 np.asarray(clean[rid].tokens))
                  and np.array_equal(res.image, clean[rid].image)):
            ok = False
            print(f"serve smoke FAILED: {rid} tokens/image diverged across "
                  "stage retries", file=sys.stderr)

    # exhaustion passes: every armed count == the retry budget, so the
    # arms are fully consumed in-pass (no reset — env-armed sites for
    # later passes stay intact)
    attempts = spec.config.retry.attempts
    tokens_only = run_pass("stage_degrade_vae", 1,
                           arm=(("vae_decode_fail", attempts),))
    res = tokens_only["stage0"]
    if res.outcome is not Outcome.COMPLETED_TOKENS_ONLY \
            or res.tokens is None or res.image is not None:
        ok = False
        print("serve smoke FAILED: VAE exhaustion did not degrade to "
              f"completed_tokens_only ({res.outcome.value})", file=sys.stderr)
    unranked = run_pass("stage_degrade_rerank", 1,
                        arm=(("rerank_fail", attempts),))
    res = unranked["stage0"]
    if res.outcome is not Outcome.COMPLETED_UNRANKED or res.image is None \
            or res.rerank_score is not None:
        ok = False
        print("serve smoke FAILED: rerank exhaustion did not degrade to "
              f"completed_unranked ({res.outcome.value})", file=sys.stderr)
    elif not np.array_equal(res.image, clean["stage0"].image):
        ok = False
        print("serve smoke FAILED: completed_unranked image diverges from "
              "the clean pass", file=sys.stderr)
    return ok


def run_replicated_drill(dalle, params, n_replicas: int,
                         preempt=None) -> bool:
    """The --replicas chaos drill: kill one replica mid-run, require all
    requests COMPLETE with tokens bit-identical to a no-crash pass."""
    import numpy as np

    from dalle_pytorch_tpu.serving import (
        EngineConfig, Outcome, Request, Router, RouterConfig,
    )
    from dalle_pytorch_tpu.utils.faults import FAULTS

    rng = np.random.RandomState(2)
    n_req = 2 * n_replicas
    prompts = [
        rng.randint(1, 16, size=(4,)).astype(np.int32) for _ in range(n_req)
    ]

    def run_pass(crash: bool):
        router = Router(
            dalle, params,
            RouterConfig(n_replicas=n_replicas),
            EngineConfig(max_batch=2, prefill_chunk=2),
        )
        for i in range(n_req):
            assert router.submit(Request(
                request_id=f"rep{i}", prompt=prompts[i],
                max_new_tokens=dalle.image_seq_len, seed=100 + i,
            )) is None
        steps = 0
        while router.step():
            steps += 1
            assert steps < 2000, "replicated drill made no progress"
            if preempt is not None and preempt.triggered:
                router.shutdown()
                print("serve smoke: SIGTERM — fleet drained",
                      file=sys.stderr)
                sys.exit(0)
            # arm the kill once work is demonstrably in flight (mid-run),
            # exactly once per pass
            if crash and steps == 3:
                FAULTS.arm("replica_crash", 1)
        router.verify_invariants()
        return router

    clean = run_pass(crash=False)
    chaos = run_pass(crash=True)
    ok = True
    dead = [s for s in chaos.replica_states().values() if s == "dead"]
    if len(dead) != 1:
        ok = False
        print(f"serve smoke FAILED: replica drill expected 1 dead replica, "
              f"states {chaos.replica_states()}", file=sys.stderr)
    for i in range(n_req):
        rid = f"rep{i}"
        res = chaos.results[rid]
        print(json.dumps({"pass": "replicated_chaos", **res.to_json()}))
        if res.outcome is not Outcome.COMPLETED:
            ok = False
            print(f"serve smoke FAILED: {rid} did not complete under "
                  f"replica_crash ({res.outcome.value})", file=sys.stderr)
        elif not np.array_equal(
            np.asarray(res.tokens), np.asarray(clean.results[rid].tokens)
        ):
            ok = False
            print(f"serve smoke FAILED: {rid} tokens diverged across "
                  "replica failover", file=sys.stderr)
    print(json.dumps({"pass": "replicated_chaos", "stats": chaos.stats()}))
    return ok


def _drive(router, preempt, snapshot_dir=None, max_steps=2000,
           label="serve smoke"):
    """Drive a router to idle, honoring SIGTERM: the preemption handler's
    flag triggers the serving shutdown path — fleet-wide graceful drain,
    journal seal, prefix snapshot flush — then a clean exit (the serving
    analog of the trainer's emergency checkpoint; docs/DESIGN.md §8.3)."""
    steps = 0
    while router.step():
        steps += 1
        assert steps < max_steps, f"{label}: router made no progress"
        if preempt is not None and preempt.triggered:
            router.shutdown(snapshot_dir=snapshot_dir)
            print(f"{label}: SIGTERM — fleet drained, journal sealed"
                  + (", snapshot flushed" if snapshot_dir else ""),
                  file=sys.stderr)
            sys.exit(0)


def run_recovery_drill(dalle, params, preempt=None) -> bool:
    """The kill-restore-replay pass (docs/DESIGN.md §8.3): a journaled
    prefix-cache router completes two cold requests, snapshots its warm
    index, admits two more, and then the process "dies" mid-flight —
    journal unsealed, router abandoned. A second router restores the
    snapshot (verify-on-load) and replays the journal's unfinished
    requests. The gate: every crash-set request COMPLETES with tokens
    bit-identical to a fault-free reference run, and — when the
    snapshot verified — at least one post-restart request is a prefix
    HIT against the restored arena (it comes back *warm*).

    Env-composed drills (the DTL033 registry contract)::

        DALLE_TPU_FAULTS="journal_torn=1" python tools/serve_smoke.py
        DALLE_TPU_FAULTS="snapshot_corrupt=1" python tools/serve_smoke.py

    A torn tail drops the LAST admitted record — the drill resubmits it
    as the client retry the contract prescribes (tokens still
    bit-identical); a corrupt snapshot is verified-rejected and the
    restart proceeds COLD (no warm-hit requirement, but the rejection
    must be counted)."""
    import tempfile

    import numpy as np

    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, Outcome, Request, RequestJournal, Router,
        RouterConfig, replay_unfinished,
    )
    from dalle_pytorch_tpu.utils.faults import FAULTS
    from dalle_pytorch_tpu.utils.metrics import counters

    rng = np.random.RandomState(3)
    tmpl = [rng.randint(1, 16, size=(4,)).astype(np.int32) for _ in range(2)]
    cold = [
        Request(request_id="rec0", prompt=tmpl[0], max_new_tokens=4, seed=50),
        Request(request_id="rec1", prompt=tmpl[1], max_new_tokens=4, seed=51),
    ]
    # the crash set: rec2 reuses template 0, so its post-restart replay
    # must hit the RESTORED index (published by rec0's cold run)
    crash_set = [
        Request(request_id="rec2", prompt=tmpl[0], max_new_tokens=4, seed=52),
        Request(request_id="rec3", prompt=tmpl[1], max_new_tokens=4, seed=53),
    ]

    ref_engine = Engine(
        dalle, params, EngineConfig(max_batch=2, prefill_chunk=2)
    )
    for req in crash_set:
        assert ref_engine.submit(req) is None
    reference = {
        rid: np.asarray(res.tokens)
        for rid, res in ref_engine.run(max_steps=1000).items()
    }

    tmp = tempfile.mkdtemp(prefix="serve_smoke_recovery_")
    jpath = os.path.join(tmp, "journal.jsonl")
    snapdir = os.path.join(tmp, "prefix_snapshot")
    cfg = EngineConfig(max_batch=2, prefill_chunk=2, prefix_cache=True)

    router = Router(
        dalle, params, RouterConfig(n_replicas=1), cfg,
        journal=RequestJournal(jpath),
    )
    for req in cold:
        assert router.submit(req) is None
    _drive(router, preempt, snapshot_dir=snapdir)
    router.verify_invariants()
    eng = router._replicas[0].engine
    eng.save_prefix_snapshot(snapdir)
    for req in crash_set:
        assert router.submit(req) is None
    router.step()
    router.step()  # demonstrably in flight ...
    router._journal.close()  # ... and now the process is dead

    # the engine's counters are per-replica labeled series (it lives
    # under a router) — read the replica-0 series
    rejected0 = counters.get(
        "serve.snapshot.rejected", labels={"replica": "0"}
    )
    torn0 = counters.get("serve.journal.torn")
    router2 = Router(
        dalle, params, RouterConfig(n_replicas=1), cfg,
        journal=RequestJournal(jpath),
    )
    eng2 = router2._replicas[0].engine
    restored = eng2.load_prefix_snapshot(snapdir)
    replayed = set(replay_unfinished(
        jpath, router2.submit, now=router2.clock.now()
    ))
    torn = counters.get("serve.journal.torn") - torn0
    for req in crash_set:
        # a torn tail lost this admission: the client retries it
        if req.request_id not in replayed:
            assert torn > 0, (
                f"{req.request_id} missing from replay without a torn tail"
            )
            assert router2.submit(req) is None
    _drive(router2, preempt, snapshot_dir=snapdir)
    router2.verify_invariants()

    ok = True
    for req in crash_set:
        res = router2.results[req.request_id]
        print(json.dumps({"pass": "recovery", **res.to_json()}))
        if res.outcome is not Outcome.COMPLETED:
            ok = False
            print(f"serve smoke FAILED: {req.request_id} did not complete "
                  f"after restart ({res.outcome.value})", file=sys.stderr)
        elif not np.array_equal(
            np.asarray(res.tokens), reference[req.request_id]
        ):
            ok = False
            print(f"serve smoke FAILED: {req.request_id} replayed tokens "
                  "diverge from the fault-free reference", file=sys.stderr)
    if restored:
        if eng2.prefix.stats.hits < 1:
            ok = False
            print("serve smoke FAILED: no post-restart request hit the "
                  "restored prefix snapshot", file=sys.stderr)
    else:
        if counters.get(
            "serve.snapshot.rejected", labels={"replica": "0"}
        ) <= rejected0:
            ok = False
            print("serve smoke FAILED: snapshot load failed without a "
                  "counted rejection", file=sys.stderr)
    print(json.dumps({
        "pass": "recovery",
        "snapshot_restored": bool(restored),
        "journal_replayed": sorted(replayed),
        "journal_torn_dropped": torn,
        "prefix_hits_after_restart": eng2.prefix.stats.hits,
        "stats": router2.stats(),
    }))
    return ok


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    n_replicas = (
        int(argv[argv.index("--replicas") + 1]) if "--replicas" in argv else 0
    )

    if lint_preflight() != 0:
        return 1

    from dalle_pytorch_tpu.utils.resilience import PreemptionHandler
    from dalle_pytorch_tpu.utils.telemetry import TELEMETRY

    # SIGTERM contract (docs/DESIGN.md §8.3, the serving analog of the
    # trainer's preemption path): the signal hook drains the flight
    # recorder immediately; the router drive loops poll ``triggered``
    # and run graceful drain + journal seal + snapshot flush before a
    # clean exit.
    with PreemptionHandler(
        on_signal=lambda s: TELEMETRY.drain("preempt_signal")
    ) as preempt:
        return _run_passes(n_replicas, preempt)


def _run_passes(n_replicas: int, preempt) -> int:
    import numpy as np

    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, FakeClock, Outcome, Request,
    )

    dalle, params = build_tiny_model()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 16, size=(4,)).astype(np.int32) for _ in range(3)]

    def run_pass(label: str, **cfg_kw) -> dict:
        engine = Engine(dalle, params, EngineConfig(max_batch=2, **cfg_kw))
        for i in range(3):
            rejected = engine.submit(Request(
                request_id=f"smoke{i}",
                prompt=prompts[i],
                max_new_tokens=dalle.image_seq_len,
                seed=i,
            ))
            assert rejected is None, rejected
        results = engine.run(max_steps=1000)
        engine.verify_invariants(idle=True)
        for rid in sorted(results):
            print(json.dumps({"pass": label, **results[rid].to_json()}))
        print(json.dumps({"pass": label, "stats": engine.stats()}))
        return results

    # chunked first: an env-armed prefill_fail fires at CHUNK granularity
    # and must be absorbed by the resume-from-last-chunk retry
    chunked = run_pass("chunked", prefill_chunk=2)
    mono = run_pass("monolithic")
    # fused ragged-iteration pass (ROADMAP 1): the whole iteration — every
    # granted chunk plus the decode rows — as ONE _iteration_jit dispatch;
    # tokens must be BIT-identical to both split passes. Runs after the
    # split passes so an env-armed fault budget drills the split chunk
    # retry first, but composes with DALLE_TPU_FAULTS the same way
    # (chunk-granular prefill_fail with resume-from-last-chunk)
    fused = run_pass("fused", prefill_chunk=2, fused_iteration=True)
    # speculative pass (ROADMAP 2): every decode row self-drafts spec_k
    # tokens and the single ragged dispatch VERIFIES them; exact
    # acceptance makes the stream bit-identical to all the passes above
    # by construction — asserted below. Composes with DALLE_TPU_FAULTS:
    # an armed ``spec_verify_abort`` degrades one iteration to plain
    # decode (same signature, tokens unchanged)::
    #
    #     DALLE_TPU_FAULTS="spec_verify_abort=1" python tools/serve_smoke.py
    spec = run_pass("spec", prefill_chunk=2, fused_iteration=True,
                    spec_decode=True, spec_k=2)
    # quantized-KV passes (ISSUE 14): int8 paged pools with per-(token,
    # head) scale pools, dequantized at read time. Parity tiers: the two
    # QUANTIZED passes (split-chunked vs fused) must be BIT-identical to
    # each other — the standing quant-vs-quant contract — while
    # quant-vs-unquantized is held to the PINNED token-agreement floor
    # (ops/kv_policy.py:KV_QUANT_TOKEN_AGREEMENT_MIN), never a bitwise
    # claim. Composes with DALLE_TPU_FAULTS like every pass above.
    quant = run_pass("kv_quant_chunked", prefill_chunk=2, kv_quant="int8")
    quant_fused = run_pass("kv_quant_fused", prefill_chunk=2,
                           fused_iteration=True, kv_quant="int8")

    # prefix-cache cold/warm replay (ROADMAP 3): ONE engine with the
    # content-addressed page index runs the SAME 3-request scenario
    # twice. The cold round publishes every prompt's pages; the warm
    # round must HIT (> 0 probes matched) and produce tokens
    # bit-identical to the cold round — the cross-request reuse contract
    # — with the refcount accounting (sum of references == mapped table
    # entries; no leaked pages after drain) asserted through the same
    # public verify_invariants the other passes use
    prefix_engine = Engine(dalle, params, EngineConfig(
        max_batch=2, prefill_chunk=2, prefix_cache=True,
    ))

    def run_prefix_round(label: str) -> dict:
        for i in range(3):
            rejected = prefix_engine.submit(Request(
                request_id=f"smoke{i}.{label}", prompt=prompts[i],
                max_new_tokens=dalle.image_seq_len, seed=i,
            ))
            assert rejected is None, rejected
        prefix_engine.run(max_steps=1000)
        prefix_engine.verify_invariants(idle=True)
        results = {
            rid.split(".")[0]: res
            for rid, res in prefix_engine.results.items()
            if rid.endswith(f".{label}")
        }
        for rid in sorted(results):
            print(json.dumps({"pass": label, **results[rid].to_json()}))
        print(json.dumps({
            "pass": label, "stats": prefix_engine.stats(),
            "prefix": {"hits": prefix_engine.prefix.stats.hits,
                       "misses": prefix_engine.prefix.stats.misses,
                       "pages": len(prefix_engine.prefix)},
        }))
        return results

    cold = run_prefix_round("prefix_cold")
    hits_before_warm = prefix_engine.prefix.stats.hits
    warm = run_prefix_round("prefix_warm")

    ok = True
    if prefix_engine.prefix.stats.hits <= hits_before_warm:
        ok = False
        print("serve smoke FAILED: warm prefix round never hit the index",
              file=sys.stderr)
    for rid in sorted(cold):
        for round_name, res in (("cold", cold[rid]), ("warm", warm[rid])):
            if res.outcome is not Outcome.COMPLETED:
                ok = False
                print(f"serve smoke FAILED: {rid} {round_name} prefix round "
                      f"did not complete ({res.outcome.value})",
                      file=sys.stderr)
        if not np.array_equal(
            np.asarray(cold[rid].tokens), np.asarray(warm[rid].tokens)
        ):
            ok = False
            print(f"serve smoke FAILED: {rid} warm (cache-hit) tokens "
                  "diverge from the cold round", file=sys.stderr)
        if not np.array_equal(
            np.asarray(cold[rid].tokens), np.asarray(chunked[rid].tokens)
        ):
            ok = False
            print(f"serve smoke FAILED: {rid} prefix-engine tokens diverge "
                  "from the uncached chunked pass", file=sys.stderr)
    for rid in sorted(mono):
        ok = ok and mono[rid].outcome is Outcome.COMPLETED
        ok = ok and chunked[rid].outcome is Outcome.COMPLETED
        ok = ok and fused[rid].outcome is Outcome.COMPLETED
        ok = ok and spec[rid].outcome is Outcome.COMPLETED
        if not np.array_equal(
            np.asarray(mono[rid].tokens), np.asarray(chunked[rid].tokens)
        ):
            ok = False
            print(f"serve smoke FAILED: {rid} chunked tokens diverge from "
                  "monolithic", file=sys.stderr)
        if not np.array_equal(
            np.asarray(mono[rid].tokens), np.asarray(fused[rid].tokens)
        ):
            ok = False
            print(f"serve smoke FAILED: {rid} fused tokens diverge from "
                  "the split path", file=sys.stderr)
        if not np.array_equal(
            np.asarray(mono[rid].tokens), np.asarray(spec[rid].tokens)
        ):
            ok = False
            print(f"serve smoke FAILED: {rid} speculative tokens diverge "
                  "from plain decode — the exact-acceptance contract is "
                  "broken", file=sys.stderr)

    # quantized-KV gate: quant-vs-quant bitwise, quant-vs-f32 thresholded
    from dalle_pytorch_tpu.ops.kv_policy import KV_QUANT_TOKEN_AGREEMENT_MIN

    agree_num = agree_den = 0
    for rid in sorted(quant):
        ok = ok and quant[rid].outcome is Outcome.COMPLETED
        ok = ok and quant_fused[rid].outcome is Outcome.COMPLETED
        if not np.array_equal(
            np.asarray(quant[rid].tokens), np.asarray(quant_fused[rid].tokens)
        ):
            ok = False
            print(f"serve smoke FAILED: {rid} quantized fused tokens "
                  "diverge from the quantized split path — the "
                  "quant-vs-quant bitwise contract is broken",
                  file=sys.stderr)
        both = min(len(quant[rid].tokens), len(chunked[rid].tokens))
        agree_num += int(np.sum(
            np.asarray(quant[rid].tokens)[:both]
            == np.asarray(chunked[rid].tokens)[:both]
        ))
        agree_den += both
    agreement = agree_num / max(agree_den, 1)
    if agreement < KV_QUANT_TOKEN_AGREEMENT_MIN:
        ok = False
        print(f"serve smoke FAILED: kv-int8 token agreement {agreement:.3f} "
              f"below the pinned {KV_QUANT_TOKEN_AGREEMENT_MIN} floor",
              file=sys.stderr)
    print(json.dumps({
        "pass": "kv_quant", "token_agreement_vs_unquant": agreement,
        "floor": KV_QUANT_TOKEN_AGREEMENT_MIN,
    }))

    # mid-prefill deadline drill: token_budget=1 throttles prefill to one
    # chunk per iteration (the forward-progress floor), the FakeClock makes
    # "expires mid-prefill" an exact step count, and the pages must be back
    # the iteration the deadline sweeps — never held to the end of the
    # prompt the way a monolithic prefill would
    drill = Engine(
        dalle, params,
        EngineConfig(max_batch=2, prefill_chunk=2, token_budget=1),
        clock=FakeClock(step_dt=1.0),
    )
    assert drill.submit(Request(
        request_id="drill", prompt=prompts[0],
        max_new_tokens=dalle.image_seq_len, seed=0, deadline=0.5,
    )) is None
    drill.run(max_steps=100)
    drill.verify_invariants(idle=True)
    res = drill.results["drill"]
    print(json.dumps({"pass": "mid_prefill_deadline", **res.to_json()}))
    if res.outcome is not Outcome.DEADLINE_EXCEEDED or res.tokens is not None:
        ok = False
        print("serve smoke FAILED: mid-prefill deadline drill did not "
              f"terminate typed mid-prefill ({res.outcome.value})",
              file=sys.stderr)
    if drill.pool.used != 0:
        ok = False
        print("serve smoke FAILED: mid-prefill termination leaked "
              f"{drill.pool.used} pages", file=sys.stderr)

    # kill-restore-replay recovery pass (docs/DESIGN.md §8.3): journaled
    # router + prefix snapshot survive a mid-flight process death with
    # bit-identical replay and a warm restored cache
    ok = run_recovery_drill(dalle, params, preempt) and ok

    # post-decode stage pipeline (docs/DESIGN.md §8.5): full
    # tokens->VAE->rerank completion with bit-identical images, transient
    # stage faults absorbed by retry, exhaustion typed-degraded
    ok = run_stage_drill(dalle, params) and ok

    if n_replicas:
        ok = run_replicated_drill(
            dalle, params, n_replicas, preempt=preempt
        ) and ok

    if not ok:
        print("serve smoke FAILED: not every request completed", file=sys.stderr)
        return 1
    print("serve smoke OK: 3/3 completed chunked, monolithic, fused, "
          "SPECULATIVE (exact-acceptance bit-parity), QUANTIZED-KV "
          "(split-vs-fused bitwise, agreement >= pinned floor vs f32) "
          "AND the prefix-cache "
          "cold/warm replay (bit-identical, warm round "
          "hit the index), mid-prefill deadline drill typed, pool drained, "
          "kill-restore-replay recovery drill bit-identical with a warm "
          "restored cache, POST-DECODE stage drill (bit-identical images, "
          "transient stage faults absorbed, exhaustion typed-degraded)"
          + (f", {2 * n_replicas}/{2 * n_replicas} completed the "
             f"{n_replicas}-replica crash drill bit-identically"
             if n_replicas else ""),
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
