"""Crash-recovery tests (docs/DESIGN.md §8.3): the durable request
journal, the persistent prefix-cache snapshot, replica resurrection, and
the chaos-soak subprocess gate — every mechanism pinned deterministically
on CPU.

The recovery contracts under test:

* journal replay is IDEMPOTENT (outcome records close replayed ids) and
  BIT-IDENTICAL (tokens depend only on (seed, position) fold-ins);
* a torn journal tail is detected, dropped, and counted — never parsed,
  never fatal; mid-file corruption is the typed ``JournalCorrupt``;
* a prefix snapshot is verify-on-load: manifest, shape, and recomputed
  chain digests — ANY failure rejects the WHOLE snapshot and the engine
  falls back cold (``snapshot_corrupt`` drill);
* a restored snapshot serves real prefix HITS bit-identical to cold;
* a killed replica respawns (DEAD → RESPAWNING → HEALTHY) and serves
  again, bit-identically; failed respawns back off and exhaust typed;
  a drained replica stays retired.

Same tiny model + page-size-2 override as tests/test_serving.py so the
terminal prompt page is partial (the snapshot must round-trip the COW
full-hit path too).
"""

import json
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
    JournalCorrupt,
    Outcome,
    ReplicaState,
    Request,
    RequestJournal,
    Router,
    RouterConfig,
    replay_unfinished,
    request_from_record,
    request_to_record,
)
from dalle_pytorch_tpu.serving import engine as engine_mod
from dalle_pytorch_tpu.utils.faults import FAULTS
from dalle_pytorch_tpu.utils.metrics import counters
from dalle_pytorch_tpu.utils.resilience import (
    RetryPolicy,
    verify_file_manifest,
    write_dir_manifest,
)


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
    FAULTS.reset()


def prompt(i=0):
    rng = np.random.RandomState(100 + i)
    return rng.randint(1, 16, size=(4,)).astype(np.int32)


def req(i, max_new=4, **kw):
    kw.setdefault("seed", i)
    return Request(
        request_id=f"r{i}", prompt=prompt(i), max_new_tokens=max_new, **kw
    )


def reference_tokens(model, requests):
    """Fault-free oracle: the same requests on a clean chunked engine."""
    dalle, params = model
    eng = Engine(dalle, params, EngineConfig(max_batch=2, prefill_chunk=2))
    for r in requests:
        assert eng.submit(r) is None
    return {
        rid: np.asarray(res.tokens)
        for rid, res in eng.run(max_steps=2000).items()
    }


# ------------------------------------------------------------- journal


class TestJournal:
    def test_record_roundtrip(self):
        r = req(7, deadline=12.5, priority=2)
        back = request_from_record(request_to_record(r, now=1.0))
        assert back.request_id == r.request_id
        assert np.array_equal(back.prompt, r.prompt)
        assert back.max_new_tokens == r.max_new_tokens
        assert back.deadline == r.deadline
        assert back.priority == r.priority
        assert back.seed == r.seed

    def test_deadline_rebased_onto_restarted_clock(self):
        """A journaled deadline is an instant on the DEAD process's
        monotonic clock; replay must rebase the remaining budget onto
        the restarted clock, not reuse the stale absolute value."""
        r = req(0, deadline=30.0)  # admitted at t=10 -> 20s remaining
        rec = request_to_record(r, now=10.0)
        assert rec["deadline_remaining"] == 20.0
        rebased = request_from_record(rec, now=1000.0)
        assert rebased.deadline == 1020.0
        # without a clock (same-process tests) the absolute value holds
        assert request_from_record(rec).deadline == 30.0
        # deadline-free requests stay deadline-free either way
        rec2 = request_to_record(req(1), now=10.0)
        assert request_from_record(rec2, now=1000.0).deadline is None

    def test_unfinished_is_idempotent(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        j = RequestJournal(p)
        j.append_admitted(req(0), now=0.0)
        j.append_admitted(req(1), now=0.1)
        j.append_outcome("r0", "completed", now=1.0)
        j.close()
        unfinished = RequestJournal.unfinished(p)
        assert [r.request_id for r in unfinished] == ["r1"]
        # replaying re-appends r1; once its outcome lands, nothing is left
        j2 = RequestJournal(p)
        replayed = replay_unfinished(p, lambda r: j2.append_admitted(r, 2.0))
        assert replayed == ["r1"]
        j2.append_outcome("r1", "completed", now=3.0)
        j2.close()
        assert RequestJournal.unfinished(p) == []
        assert RequestJournal.outcomes(p) == {
            "r0": "completed", "r1": "completed",
        }

    def test_torn_tail_dropped_and_counted(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        j = RequestJournal(p)
        j.append_admitted(req(0), now=0.0)
        j.append_admitted(req(1), now=0.1)
        j.close()
        # crash mid-append: the tail record loses its last bytes
        data = open(p, "rb").read()
        open(p, "wb").write(data[:-7])
        torn0 = counters.get("serve.journal.torn")
        records, torn = RequestJournal.load(p)
        assert torn == 1
        assert counters.get("serve.journal.torn") == torn0 + 1
        assert [r["request_id"] for r in records] == ["r0"]
        # the torn admission is simply not in the replay set
        assert [r.request_id for r in RequestJournal.unfinished(p)] == ["r0"]

    def test_journal_torn_fault_drill(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        j = RequestJournal(p)
        j.append_admitted(req(0), now=0.0)
        j.append_admitted(req(1), now=0.1)
        j.close()
        FAULTS.arm("journal_torn", 1)
        fault0 = counters.get("serve.fault_journal_torn")
        records, torn = RequestJournal.load(p)
        assert torn == 1
        assert [r["request_id"] for r in records] == ["r0"]
        assert counters.get("serve.fault_journal_torn") == fault0 + 1
        # the budget is spent: the next load sees the intact file
        records, torn = RequestJournal.load(p)
        assert torn == 0 and len(records) == 2

    def test_torn_tail_counted_once_across_recovery_reads(self, tmp_path):
        """One real torn tail moves serve.journal.torn by exactly ONE
        through a full recovery (reconcile reads outcomes, replay reads
        unfinished, tools re-scan) — secondary reads never re-count."""
        p = str(tmp_path / "j.jsonl")
        j = RequestJournal(p)
        j.append_admitted(req(0), now=0.0)
        j.append_outcome("r0", "completed", now=0.5)
        j.append_admitted(req(1), now=1.0)
        j.close()
        data = open(p, "rb").read()
        open(p, "wb").write(data[:-7])
        torn0 = counters.get("serve.journal.torn")
        seen = {}
        replayed = replay_unfinished(
            p, lambda r: None, reconcile=seen.__setitem__,
        )
        assert replayed == [] and seen == {"r0": "completed"}
        assert counters.get("serve.journal.torn") == torn0 + 1
        # inspection reads leave the counter alone
        RequestJournal.verify(p)
        RequestJournal.outcomes(p)
        RequestJournal.unfinished(p, count=False)
        assert counters.get("serve.journal.torn") == torn0 + 1

    def test_midfile_corruption_raises_typed(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        j = RequestJournal(p)
        j.append_admitted(req(0), now=0.0)
        j.append_admitted(req(1), now=0.1)
        j.append_admitted(req(2), now=0.2)
        j.close()
        lines = open(p).read().splitlines()
        lines[0] = lines[0][:10]  # bit rot on a NON-tail record
        open(p, "w").write("\n".join(lines) + "\n")
        with pytest.raises(JournalCorrupt):
            RequestJournal.load(p)
        ok, reason = RequestJournal.verify(p)
        assert not ok and "unparseable" in reason

    def test_seal_writes_manifest_and_verify(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        j = RequestJournal(p)
        j.append_admitted(req(0), now=0.0)
        j.seal()
        assert verify_file_manifest(p)[0]
        ok, reason = RequestJournal.verify(p)
        assert ok and reason == "ok"
        # an unsealed (crashed) journal still verifies, flagged as such
        j2 = RequestJournal(p)
        j2.append_admitted(req(1), now=1.0)
        j2.close()
        ok, reason = RequestJournal.verify(p)
        assert ok and "unsealed" in reason


# ------------------------------------------------- prefix-cache snapshot


def run_prefix_engine(model, requests, snapshot_dir=None, load_from=None,
                      **eng_kw):
    """One prefix-enabled engine run; optionally snapshot after, or
    verify-load a snapshot before. Returns (engine, results, restored)."""
    dalle, params = model
    eng = Engine(dalle, params, EngineConfig(
        max_batch=2, prefill_chunk=2, prefix_cache=True, **eng_kw
    ))
    restored = None
    if load_from is not None:
        restored = eng.load_prefix_snapshot(load_from)
    for r in requests:
        assert eng.submit(r) is None
    results = eng.run(max_steps=2000)
    eng.verify_invariants(idle=True)
    if snapshot_dir is not None:
        eng.save_prefix_snapshot(snapshot_dir)
    return eng, results, restored


class TestSnapshot:
    def test_roundtrip_warm_hit_bit_identical(self, model, tmp_path):
        snap = str(tmp_path / "prefix_snapshot")
        cold_req = req(0, seed=11)
        _, cold_res, _ = run_prefix_engine(
            model, [cold_req], snapshot_dir=snap
        )
        # a fresh engine restores the snapshot; the same prompt under a
        # NEW seed must be a full-prefix hit and bit-match its own cold
        # reference (prefix reuse shares K/V, never token streams)
        warm_req = Request(
            request_id="warm", prompt=prompt(0), max_new_tokens=4, seed=77,
        )
        ref = reference_tokens(model, [Request(
            request_id="warm", prompt=prompt(0), max_new_tokens=4, seed=77,
        )])
        restored0 = counters.get("serve.snapshot.restored")
        eng, res, restored = run_prefix_engine(
            model, [warm_req], load_from=snap
        )
        assert restored is True
        assert counters.get("serve.snapshot.restored") == restored0 + 1
        assert eng.prefix.stats.hits >= 1, "restored snapshot never hit"
        assert res["warm"].outcome is Outcome.COMPLETED
        assert np.array_equal(np.asarray(res["warm"].tokens), ref["warm"])

    def test_snapshot_corrupt_rejects_to_cold(self, model, tmp_path):
        snap = str(tmp_path / "prefix_snapshot")
        run_prefix_engine(model, [req(0, seed=11)], snapshot_dir=snap)
        FAULTS.arm("snapshot_corrupt", 1)
        rejected0 = counters.get("serve.snapshot.rejected")
        fault0 = counters.get("serve.fault_snapshot_corrupt")
        ref = reference_tokens(model, [req(3, seed=33)])
        eng, res, restored = run_prefix_engine(
            model, [req(3, seed=33)], load_from=snap
        )
        assert restored is False
        assert counters.get("serve.snapshot.rejected") == rejected0 + 1
        assert counters.get("serve.fault_snapshot_corrupt") == fault0 + 1
        # cold fallback still serves, bit-identically
        assert res["r3"].outcome is Outcome.COMPLETED
        assert np.array_equal(np.asarray(res["r3"].tokens), ref["r3"])

    def test_uncommitted_dir_rejected(self, model, tmp_path):
        snap = tmp_path / "prefix_snapshot"
        run_prefix_engine(model, [req(0, seed=11)], snapshot_dir=str(snap))
        (snap / "COMMITTED").unlink()  # the torn-save shape
        _, _, restored = run_prefix_engine(
            model, [req(1, seed=22)], load_from=str(snap)
        )
        assert restored is False

    def test_duplicate_and_incoherent_snapshots_reject_typed(
        self, model, tmp_path
    ):
        """Snapshots that would crash the restore phase (duplicate chain
        nodes, payload arrays missing, foreign cache dtype) must reject
        typed at verify-on-load — never raise mid-build."""
        from dalle_pytorch_tpu.serving.prefix_cache import (
            verify_snapshot_records,
        )

        snap = tmp_path / "prefix_snapshot"
        run_prefix_engine(model, [req(0, seed=11)], snapshot_dir=str(snap))
        index = json.loads((snap / "index.json").read_text())
        # duplicate chain node: insert would die on dedup-on-insert
        ok, reason = verify_snapshot_records(
            [index["nodes"][0], dict(index["nodes"][0])],
            int(index["page_size"]),
        )
        assert not ok and "duplicate" in reason
        # foreign cache dtype: a cast restore would fake warm parity
        tampered = dict(index)
        tampered["dtypes"] = dict(index["dtypes"])
        tampered["dtypes"]["pages_l0"] = "float16"
        (snap / "index.json").write_text(
            json.dumps(tampered, sort_keys=True)
        )
        write_dir_manifest(str(snap))
        rejected0 = counters.get("serve.snapshot.rejected")
        _, _, restored = run_prefix_engine(
            model, [req(1, seed=22)], load_from=str(snap)
        )
        assert restored is False
        # payload array missing (has_ring promised, ring arrays absent)
        import numpy as onp
        with onp.load(snap / "arrays.npz") as z:
            kept = {k: z[k] for k in z.files if not k.startswith("ring")}
        onp.savez(snap / "arrays.npz", **kept)
        (snap / "index.json").write_text(json.dumps(index, sort_keys=True))
        write_dir_manifest(str(snap))
        _, _, restored = run_prefix_engine(
            model, [req(2, seed=23)], load_from=str(snap)
        )
        assert restored is False
        assert counters.get("serve.snapshot.rejected") == rejected0 + 2

    def test_chain_digest_catches_re_manifested_tamper(self, model, tmp_path):
        """The manifest covers bytes; the chain digests cover MEANING: a
        tampered index whose manifest was regenerated still fails the
        mandatory recompute."""
        snap = tmp_path / "prefix_snapshot"
        run_prefix_engine(model, [req(0, seed=11)], snapshot_dir=str(snap))
        index = json.loads((snap / "index.json").read_text())
        index["nodes"][0]["tokens"][0] += 1
        (snap / "index.json").write_text(json.dumps(index, sort_keys=True))
        write_dir_manifest(str(snap))  # "clean" manifest over bad data
        rejected0 = counters.get("serve.snapshot.rejected")
        _, _, restored = run_prefix_engine(
            model, [req(1, seed=22)], load_from=str(snap)
        )
        assert restored is False
        assert counters.get("serve.snapshot.rejected") == rejected0 + 1


# ------------------------------------- quantized-arena snapshot (ISSUE 14)


class TestQuantSnapshot:
    """Snapshot round-trips for QUANTIZED arenas: int8 page bytes and
    f32 scale arrays persist dtype-exact, verify-on-load rejects a
    scale/page length mismatch, a foreign-dtype cast restore, and a
    re-manifested payload tamper (typed reject-to-cold, never a
    mid-restore crash), and a cross-format restore misses at the
    format tag. Restored warm hits are bit-identical to the quantized
    cold run."""

    def _snap(self, model, tmp_path):
        snap = str(tmp_path / "prefix_snapshot")
        _, cold_res, _ = run_prefix_engine(
            model, [req(0, seed=11)], snapshot_dir=snap, kv_quant="int8"
        )
        return snap, cold_res

    def test_roundtrip_dtype_exact_warm_hit_bit_identical(
        self, model, tmp_path
    ):
        snap, _ = self._snap(model, tmp_path)
        index = json.loads(
            (tmp_path / "prefix_snapshot" / "index.json").read_text()
        )
        # the persisted dtypes are the quantized reality, dtype-exact:
        # int8 content pools AND f32 scale pools, under a non-empty
        # format tag
        page_dtypes = sorted({
            v for k, v in index["dtypes"].items() if k.startswith("pages_")
        })
        assert "int8" in page_dtypes and "float32" in page_dtypes
        assert index["kv_format"].startswith("kv:int8:")
        scale_leaves = [
            p for p in index["leaf_paths"] if "scale_pages" in p
        ]
        assert len(scale_leaves) >= 2, index["leaf_paths"]
        # every record carries its payload content digest
        assert all("content_sha256" in r for r in index["nodes"])
        warm_req = Request(
            request_id="warm", prompt=prompt(0), max_new_tokens=4, seed=77,
        )
        ref_eng = Engine(model[0], model[1], EngineConfig(
            max_batch=2, prefill_chunk=2, kv_quant="int8",
        ))
        assert ref_eng.submit(Request(
            request_id="warm", prompt=prompt(0), max_new_tokens=4, seed=77,
        )) is None
        ref = np.asarray(ref_eng.run(max_steps=2000)["warm"].tokens)
        eng, res, restored = run_prefix_engine(
            model, [warm_req], load_from=snap, kv_quant="int8"
        )
        assert restored is True
        assert eng.prefix.stats.hits >= 1, "restored quant arena never hit"
        np.testing.assert_array_equal(np.asarray(res["warm"].tokens), ref)

    def test_cross_format_restore_rejected(self, model, tmp_path):
        snap, _ = self._snap(model, tmp_path)
        rejected0 = counters.get("serve.snapshot.rejected")
        # a quantized snapshot offered to an UNQUANTIZED engine must
        # reject typed (format tag mismatch), never cast int8 bytes
        # into f32 pools as "verified" warm K/V
        _, _, restored = run_prefix_engine(
            model, [req(1, seed=22)], load_from=snap
        )
        assert restored is False
        assert counters.get("serve.snapshot.rejected") == rejected0 + 1

    def test_foreign_dtype_cast_rejected(self, model, tmp_path):
        snap, _ = self._snap(model, tmp_path)
        sp = tmp_path / "prefix_snapshot"
        index = json.loads((sp / "index.json").read_text())
        scale_key = next(
            f"pages_l{j}" for j, p in enumerate(index["leaf_paths"])
            if "scale_pages" in p
        )
        index["dtypes"][scale_key] = "float16"
        (sp / "index.json").write_text(json.dumps(index, sort_keys=True))
        write_dir_manifest(str(sp))
        rejected0 = counters.get("serve.snapshot.rejected")
        _, _, restored = run_prefix_engine(
            model, [req(1, seed=22)], load_from=snap, kv_quant="int8"
        )
        assert restored is False
        assert counters.get("serve.snapshot.rejected") == rejected0 + 1

    def test_scale_length_mismatch_rejected(self, model, tmp_path):
        snap, _ = self._snap(model, tmp_path)
        sp = tmp_path / "prefix_snapshot"
        index = json.loads((sp / "index.json").read_text())
        scale_key = next(
            f"pages_l{j}" for j, p in enumerate(index["leaf_paths"])
            if "scale_pages" in p
        )
        with np.load(sp / "arrays.npz") as z:
            arrays = {k: z[k] for k in z.files}
        arrays[scale_key] = arrays[scale_key][:-1]  # drop one node's scales
        np.savez(sp / "arrays.npz", **arrays)
        write_dir_manifest(str(sp))
        rejected0 = counters.get("serve.snapshot.rejected")
        _, _, restored = run_prefix_engine(
            model, [req(1, seed=22)], load_from=snap, kv_quant="int8"
        )
        assert restored is False
        assert counters.get("serve.snapshot.rejected") == rejected0 + 1

    def test_content_digest_catches_re_manifested_scale_tamper(
        self, model, tmp_path
    ):
        """The manifest covers files, the chain digest covers tokens —
        a flipped SCALE byte behind a regenerated manifest is caught by
        the per-node content digest (forged scales would dequantize
        shared pages to wrong values while every token check passes)."""
        snap, _ = self._snap(model, tmp_path)
        sp = tmp_path / "prefix_snapshot"
        index = json.loads((sp / "index.json").read_text())
        scale_key = next(
            f"pages_l{j}" for j, p in enumerate(index["leaf_paths"])
            if "scale_pages" in p
        )
        with np.load(sp / "arrays.npz") as z:
            arrays = {k: z[k] for k in z.files}
        tampered = arrays[scale_key].copy()
        tampered.reshape(-1)[0] ^= 0xFF  # one scale byte flips
        arrays[scale_key] = tampered
        np.savez(sp / "arrays.npz", **arrays)
        write_dir_manifest(str(sp))  # "clean" manifest over forged scales
        rejected0 = counters.get("serve.snapshot.rejected")
        ref = reference_tokens(model, [req(1, seed=22)])
        eng, res, restored = run_prefix_engine(
            model, [req(1, seed=22)], load_from=snap, kv_quant="int8"
        )
        assert restored is False
        assert counters.get("serve.snapshot.rejected") == rejected0 + 1
        # reject-to-cold still serves; agreement with the f32 oracle is
        # not asserted here (quant engine) — completion + typed reject is
        assert res["r1"].outcome is Outcome.COMPLETED
        assert ref  # oracle computed; the engine ran cold past the reject


# ------------------------------------------------------------- respawn


def make_router(model, n=2, clock=None, journal=None, router_kw=None,
                **eng_kw):
    dalle, params = model
    eng_kw.setdefault("max_batch", 2)
    eng_kw.setdefault("prefill_chunk", 2)
    kw = {"n_replicas": n, "respawn": True}
    kw.update(router_kw or {})
    return Router(
        dalle, params, RouterConfig(**kw), EngineConfig(**eng_kw),
        clock=clock or FakeClock(step_dt=0.1), journal=journal,
    )


class TestRespawn:
    def test_killed_replica_respawns_and_serves_bit_identical(self, model):
        requests = [req(i, seed=40 + i) for i in range(4)]
        ref = reference_tokens(model, requests)
        router = make_router(model, n=2)
        respawns0 = counters.get("router.respawns")
        for r in requests:
            assert router.submit(r) is None
        steps, killed = 0, False
        while router.step():
            steps += 1
            assert steps < 3000
            if not killed and steps == 3:
                FAULTS.arm("replica_crash", 1)
                killed = True
        # idle steps let the backoff expire and the rebuild fire (it may
        # already have fired mid-run — the baseline predates the kill)
        for _ in range(40):
            router.step()
        router.verify_invariants()
        assert counters.get("router.respawns") == respawns0 + 1
        states = router.replica_states()
        assert set(states.values()) == {ReplicaState.HEALTHY.value}, states
        for r in requests:
            res = router.results[r.request_id]
            assert res.outcome is Outcome.COMPLETED
            assert np.array_equal(
                np.asarray(res.tokens), ref[r.request_id]
            ), f"{r.request_id} diverged across kill/failover"
        # the resurrected replica accepts and serves new work
        post = req(9, seed=99)
        assert router.submit(post) is None
        res = router.run(max_steps=2000)["r9"]
        assert res.outcome is Outcome.COMPLETED
        router.verify_invariants()

    def test_respawning_holds_queue_until_fleet_returns(self, model):
        """A 1-replica fleet whose replica dies does NOT flush queued
        work typed while a respawn is pending — the work waits and
        completes after resurrection."""
        router = make_router(model, n=1)
        router.kill(0, reason="test_crash")
        assert router.replica_states()[0] == ReplicaState.RESPAWNING.value
        r = req(0, seed=5)
        assert router.submit(r) is None  # queued, not no_replica-rejected
        res = router.run(max_steps=3000)["r0"]
        assert res.outcome is Outcome.COMPLETED
        assert counters.get("router.respawns") >= 1
        router.verify_invariants()

    def test_respawn_fail_backs_off_then_exhausts_typed(self, model):
        router = make_router(
            model, n=1,
            router_kw={
                "max_respawns": 2,
                "respawn_backoff": RetryPolicy(
                    attempts=3, base_delay=0.2, max_delay=5.0,
                    jitter=0.0, retry_on=(),
                ),
            },
        )
        FAULTS.arm("replica_respawn_fail", 5)
        fault0 = counters.get("router.fault_replica_respawn_fail")
        router.kill(0, reason="test_crash")
        for _ in range(200):
            router.step()
        assert router.replica_states()[0] == ReplicaState.DEAD.value
        assert counters.get("router.fault_replica_respawn_fail") == fault0 + 2
        info = router.stats()["replicas"][0]
        assert "respawns exhausted" in info["death_reason"]
        # a permanently dead fleet rejects typed, immediately
        result = router.submit(req(0))
        assert result is not None
        assert result.outcome is Outcome.REJECTED

    def test_drain_of_respawning_replica_retires_it(self, model):
        """drain() on a RESPAWNING replica must cancel the pending
        respawn and retire it — never re-activate the abandoned stale
        engine (whose in-flight work already failed over)."""
        router = make_router(model, n=2)
        for i in range(2):
            assert router.submit(req(i, seed=80 + i)) is None
        router.step()  # work in flight on replica 0 or 1
        victim = max(
            router._replicas, key=lambda r: len(r.inflight)
        ).id
        router.kill(victim, reason="test_crash")
        assert router.replica_states()[victim] == (
            ReplicaState.RESPAWNING.value
        )
        router.drain(victim)
        assert router.replica_states()[victim] == ReplicaState.DEAD.value
        assert router.stats()["replicas"][victim]["death_reason"] == (
            "drained"
        )
        # the retirement sticks (no respawn fires) and the fleet stays
        # consistent: invariants clean, all work completes on siblings
        results = router.run(max_steps=3000)
        for _ in range(40):
            router.step()
        router.verify_invariants()
        assert router.replica_states()[victim] == ReplicaState.DEAD.value
        assert all(
            res.outcome is Outcome.COMPLETED for res in results.values()
        )

    def test_drained_replica_is_retired_not_respawned(self, model):
        router = make_router(model, n=2)
        router.drain(0)
        for _ in range(30):
            router.step()
        states = router.replica_states()
        assert states[0] == ReplicaState.DEAD.value
        assert router.stats()["replicas"][0]["death_reason"] == "drained"
        # still dead after plenty of backoff time: drains are retirement
        for _ in range(60):
            router.step()
        assert router.replica_states()[0] == ReplicaState.DEAD.value


# ------------------------------------------- process restart (journal)


class TestRestartReplay:
    def test_restart_replays_unfinished_with_warm_hit(self, model, tmp_path):
        jpath = str(tmp_path / "journal.jsonl")
        snap = str(tmp_path / "prefix_snapshot")
        cold = req(0, seed=60)
        # the crash-set request reuses prompt(0): its post-restart
        # replay must hit the RESTORED arena
        crash = Request(
            request_id="crash", prompt=prompt(0), max_new_tokens=4, seed=61,
        )
        ref = reference_tokens(model, [Request(
            request_id="crash", prompt=prompt(0), max_new_tokens=4, seed=61,
        )])
        router = make_router(
            model, n=1, journal=RequestJournal(jpath), prefix_cache=True,
        )
        assert router.submit(cold) is None
        router.run(max_steps=2000)
        router._replicas[0].engine.save_prefix_snapshot(snap)
        assert router.submit(crash) is None
        router.step()  # in flight...
        router._journal.close()  # ...and the process dies

        router2 = make_router(
            model, n=1, journal=RequestJournal(jpath), prefix_cache=True,
        )
        eng2 = router2._replicas[0].engine
        assert eng2.load_prefix_snapshot(snap)
        replayed = replay_unfinished(jpath, router2.submit)
        assert replayed == ["crash"]
        res = router2.run(max_steps=2000)["crash"]
        router2.verify_invariants()
        assert res.outcome is Outcome.COMPLETED
        assert np.array_equal(np.asarray(res.tokens), ref["crash"])
        assert eng2.prefix.stats.hits >= 1, (
            "replayed request missed the restored snapshot"
        )
        # idempotency: the finished request does not replay again
        router2._journal.seal()
        assert RequestJournal.unfinished(jpath) == []

    def test_restarted_fleet_serves_through_the_programs_already_traced(
        self, model, tmp_path
    ):
        """The post-restart serving window (snapshot restore, a warm hit, a
        cold request) adds no signature to any serving jit: recovery time
        holds no compile."""
        snap = str(tmp_path / "prefix_snapshot")
        jits = [
            getattr(engine_mod, name) for name in (
                "_prefill_chunk_jit", "_prefill_last_jit", "_decode_jit",
                "_sample_cached_jit", "_copy_pages_jit",
                "_copy_pages_across_jit",
            )
        ]

        def serve(router, tag):
            # a cold prompt, then the same prompt again (a warm full hit),
            # then another cold one
            for n, i in enumerate((0, 0, 1)):
                assert router.submit(Request(
                    request_id=f"{tag}{n}", prompt=prompt(i),
                    max_new_tokens=4, seed=60 + n,
                )) is None
                router.run(max_steps=2000)
            router.verify_invariants()

        router = make_router(model, n=1, prefix_cache=True)
        serve(router, "first")
        router._replicas[0].engine.save_prefix_snapshot(snap)
        before = [int(j._cache_size()) for j in jits]

        router2 = make_router(model, n=1, prefix_cache=True)
        eng2 = router2._replicas[0].engine
        assert eng2.load_prefix_snapshot(snap)
        serve(router2, "second")
        assert eng2.prefix.stats.hits >= 2  # the first from the restored arena
        assert [int(j._cache_size()) for j in jits] == before

    def test_shutdown_flushes_snapshot_and_leaves_queue_journaled(
        self, model, tmp_path
    ):
        """The SIGTERM path with work IN FLIGHT: shutdown() must finish
        in-flight requests, save the prefix snapshot (the drained
        replica's index is intact and eligible), seal the journal, and
        leave still-queued requests journaled-unfinished for the next
        incarnation — never flushed typed, never snapshot-skipped."""
        jpath = str(tmp_path / "journal.jsonl")
        snap = tmp_path / "prefix_snapshot"
        router = make_router(
            model, n=1, journal=RequestJournal(jpath),
            prefix_cache=True, max_batch=1,
        )
        for i in range(3):
            assert router.submit(req(i, seed=70 + i)) is None
        router.step()  # r0 in flight, r1/r2 queued at the router
        router.shutdown(snapshot_dir=str(snap))
        # in-flight work finished and was journaled terminal
        assert router.results["r0"].outcome is Outcome.COMPLETED
        # the drained (DEAD) replica's non-empty index WAS snapshotted
        assert (snap / "COMMITTED").exists()
        index = json.loads((snap / "index.json").read_text())
        assert len(index["nodes"]) >= 1
        # journal sealed; queued work stays unfinished (not flushed)
        ok, reason = RequestJournal.verify(jpath)
        assert ok and reason == "ok"
        assert sorted(
            r.request_id for r in RequestJournal.unfinished(jpath)
        ) == ["r1", "r2"]
        assert "r1" not in router.results and "r2" not in router.results
        # the next incarnation restores warm and replays both
        router2 = make_router(
            model, n=1, journal=RequestJournal(jpath), prefix_cache=True,
        )
        assert router2._replicas[0].engine.load_prefix_snapshot(str(snap))
        replayed = replay_unfinished(jpath, router2.submit)
        assert sorted(replayed) == ["r1", "r2"]
        results = router2.run(max_steps=2000)
        assert all(
            results[rid].outcome is Outcome.COMPLETED
            for rid in ("r1", "r2")
        )
        router2.verify_invariants()

    def test_live_requests_export(self, model):
        dalle, params = model
        eng = Engine(dalle, params, EngineConfig(
            max_batch=1, prefill_chunk=2, queue_limit=4,
        ))
        for i in range(3):
            assert eng.submit(req(i, seed=i)) is None
        eng.step()  # r0 admitted, r1/r2 queued
        live = eng.live_requests()
        assert [r.request_id for r in live] == ["r1", "r2", "r0"]
        router = make_router(model, n=1, max_batch=1)
        for i in range(3):
            assert router.submit(req(i, seed=i)) is None
        router.step()
        ids = [r.request_id for r in router.live_requests()]
        assert set(ids) == {"r0", "r1", "r2"}
        router.run(max_steps=2000)
        assert router.live_requests() == []


# --------------------------------------------------- chaos soak gates


def test_chaos_mini_soak_subprocess_gate():
    """The fast-tier chaos gate: a seeded, bounded randomized fault
    schedule (all serving sites + replica kill/respawn/process restart)
    must end with 100% typed outcomes and bit-identical survivors."""
    out = subprocess.run(
        [sys.executable, "tools/chaos_soak.py",
         "--iters", "40", "--requests", "4",
         "--restart-every", "18", "--snap-every", "9", "--seed", "0"],
        capture_output=True, text=True, cwd=".",
    )
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"
    summary = json.loads(out.stdout)
    assert summary["ok"] is True
    assert summary["completed_bit_identical"] is True
    assert summary["restarts"] >= 1
    assert sum(summary["outcomes"].values()) == summary["submitted"]


@pytest.mark.slow
def test_chaos_soak_long_subprocess_gate():
    out = subprocess.run(
        [sys.executable, "tools/chaos_soak.py",
         "--iters", "400", "--requests", "12",
         "--restart-every", "60", "--snap-every", "20", "--seed", "1"],
        capture_output=True, text=True, cwd=".",
    )
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"
    summary = json.loads(out.stdout)
    assert summary["ok"] is True
    assert summary["outcomes"].get("completed", 0) >= 1
