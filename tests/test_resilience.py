"""Fault-tolerance suite (docs/DESIGN.md §9) — every resilience behavior
exercised deterministically on CPU through the fault registry:

- retry/backoff policy and its injectable clock,
- preemption handling with REAL signals (SIGTERM → flag → emergency save),
- two-phase-committed checkpoint dirs: torn/corrupt dirs are never
  restored, fallback picks the newest verified step,
- the NaN step-guard: a non-finite step leaves state bit-identical to the
  prior state, a finite step is bit-identical to the unguarded step,
- download/shard retry + quarantine with counter accounting,
- the acceptance scenario: SIGTERM mid-run + corrupted newest checkpoint
  + 2 transient download failures + 1 NaN loss, and the resumed run's
  final params/opt_state equal an unfaulted run's exactly.
"""

import io
import json
import math
import os
import signal
import sys
import tarfile
import urllib.error
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from PIL import Image

from dalle_pytorch_tpu.parallel import (
    TrainLoop,
    create_train_state,
    make_runtime,
    make_train_step,
)
from dalle_pytorch_tpu.utils import (
    FAULTS,
    PreemptionHandler,
    RetryPolicy,
    counters,
    download,
    latest_verified_step,
    load_sharded_checkpoint,
    retry,
    save_sharded_checkpoint,
    verify_step_dir,
)
from dalle_pytorch_tpu.utils.faults import FaultRegistry
from dalle_pytorch_tpu.utils.resilience import (
    verify_dir_manifest,
    write_dir_manifest,
)

TOOLS = Path(__file__).resolve().parent.parent / "tools"


# ------------------------------------------------------------ fault registry


class TestFaultRegistry:
    def test_take_counts_down(self):
        r = FaultRegistry()
        r.arm("download", 2)
        assert [r.take("download") for _ in range(4)] == [True, True, False, False]
        assert r.fired["download"] == 2

    def test_env_spec(self):
        r = FaultRegistry("download=2, shard_open=1,nan_at_step=5")
        assert r.value("nan_at_step") == 5
        assert r.take("nan_at_step") is False  # value site, never consumed
        assert r.take("shard_open") and not r.take("shard_open")
        assert r.active()

    def test_unarmed_is_inert(self):
        r = FaultRegistry()
        assert not r.active() and not r.take("download")
        r.maybe_raise("download", OSError("nope"))  # no-op

    def test_maybe_raise(self):
        r = FaultRegistry()
        r.arm("download", 1)
        with pytest.raises(OSError):
            r.maybe_raise("download", OSError("boom"))
        r.maybe_raise("download", OSError("boom"))  # consumed

    def test_bad_spec(self):
        with pytest.raises(ValueError):
            FaultRegistry("download")

    def test_unknown_site_in_spec_rejected(self):
        """A typo'd site name in DALLE_TPU_FAULTS must fail the run, not
        silently inject nothing (the drill would 'pass' untested)."""
        with pytest.raises(ValueError, match="unknown fault site"):
            FaultRegistry("page_exhaustt=1")

    def test_serving_sites_are_known(self):
        from dalle_pytorch_tpu.utils.faults import KNOWN_SITES

        r = FaultRegistry(
            "page_exhaust=1,prefill_fail=1,decode_stall=1,request_cancel=1"
        )
        for site in ("page_exhaust", "prefill_fail", "decode_stall",
                     "request_cancel"):
            assert site in KNOWN_SITES
            assert r.take(site) and not r.take(site)


class TestFileManifest:
    """Single-file sidecar manifests — what generate.py's checkpoint gate
    stands on (the single-file analog of the step-dir two-phase commit)."""

    def test_save_checkpoint_writes_sidecar_and_verifies(self, tmp_path):
        from dalle_pytorch_tpu.utils.checkpoint import (
            check_checkpoint_file, save_checkpoint,
        )
        from dalle_pytorch_tpu.utils.resilience import verify_file_manifest

        path = tmp_path / "m.ckpt"
        save_checkpoint(str(path), {"w": np.ones(3)}, {"k": 1})
        assert (tmp_path / "m.ckpt.manifest.json").exists()
        ok, reason = verify_file_manifest(str(path))
        assert ok, reason
        check_checkpoint_file(str(path))  # no raise

    def test_corruption_is_typed_error(self, tmp_path):
        from dalle_pytorch_tpu.utils.checkpoint import (
            CheckpointError, check_checkpoint_file, save_checkpoint,
        )

        path = tmp_path / "m.ckpt"
        save_checkpoint(str(path), {"w": np.ones(3)}, {})
        data = bytearray(path.read_bytes())
        data[len(data) // 2] ^= 0xFF
        path.write_bytes(bytes(data))
        with pytest.raises(CheckpointError, match="checksum mismatch"):
            check_checkpoint_file(str(path))

    def test_truncation_is_typed_error(self, tmp_path):
        from dalle_pytorch_tpu.utils.checkpoint import (
            CheckpointError, check_checkpoint_file, save_checkpoint,
        )

        path = tmp_path / "m.ckpt"
        save_checkpoint(str(path), {"w": np.ones(64)}, {})
        path.write_bytes(path.read_bytes()[:-16])  # torn write
        with pytest.raises(CheckpointError, match="size mismatch"):
            check_checkpoint_file(str(path))

    def test_missing_file_is_typed_error(self, tmp_path):
        from dalle_pytorch_tpu.utils.checkpoint import (
            CheckpointError, check_checkpoint_file,
        )

        with pytest.raises(CheckpointError, match="missing"):
            check_checkpoint_file(str(tmp_path / "nope.ckpt"))

    def test_pre_manifest_file_warns_but_loads(self, tmp_path, capsys):
        """Checkpoints saved before the sidecar existed stay loadable
        (warn, don't refuse) unless the caller requires verification."""
        from dalle_pytorch_tpu.utils.checkpoint import (
            CheckpointError, check_checkpoint_file, save_checkpoint,
        )

        path = tmp_path / "m.ckpt"
        save_checkpoint(str(path), {"w": np.ones(3)}, {})
        (tmp_path / "m.ckpt.manifest.json").unlink()
        check_checkpoint_file(str(path))  # warns, no raise
        assert "no manifest sidecar" in capsys.readouterr().err
        with pytest.raises(CheckpointError, match="no manifest"):
            check_checkpoint_file(str(path), require_manifest=True)


class TestCheckpointParts:
    """A plain save above ``PART_BYTES`` writes no file larger than that:
    part files behind an index at the checkpoint's path (the flagship's
    3.6 GiB single file met EFBIG on a TPU machine with a file-size cap)."""

    @pytest.fixture
    def small_parts(self, monkeypatch):
        from dalle_pytorch_tpu.utils import checkpoint

        monkeypatch.setattr(checkpoint, "PART_BYTES", 4096)
        return checkpoint

    STATE = {"w": np.arange(5000, dtype=np.float32), "b": np.ones(7)}

    def test_roundtrip_in_bounded_files(self, tmp_path, small_parts):
        path = tmp_path / "m.ckpt"
        small_parts.save_checkpoint(str(path), self.STATE, {"k": 1})
        parts = sorted(tmp_path.glob("m.ckpt.*.part*"))
        assert len(parts) == 5
        assert all(f.stat().st_size <= 4096 for f in tmp_path.iterdir())
        small_parts.check_checkpoint_file(str(path), require_manifest=True)
        state, meta = small_parts.load_checkpoint(str(path))
        assert meta == {"k": 1}
        np.testing.assert_array_equal(state["w"], self.STATE["w"])
        np.testing.assert_array_equal(state["b"], self.STATE["b"])

    def test_survives_a_file_size_limit(self, tmp_path, small_parts):
        """The chip machine's failure, reproduced: under RLIMIT_FSIZE one
        file of the whole payload is EFBIG, the parts are not."""
        import errno
        import resource

        soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
        resource.setrlimit(resource.RLIMIT_FSIZE, (8192, hard))
        try:
            small_parts.save_checkpoint(str(tmp_path / "m.ckpt"), self.STATE)
            small_parts.PART_BYTES = 1 << 20
            with pytest.raises(OSError) as e:
                small_parts.save_checkpoint(str(tmp_path / "n.ckpt"), self.STATE)
        finally:
            resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))
        assert e.value.errno == errno.EFBIG
        state, _ = small_parts.load_checkpoint(str(tmp_path / "m.ckpt"))
        np.testing.assert_array_equal(state["w"], self.STATE["w"])

    def test_corrupt_and_missing_parts_are_typed_errors(self, tmp_path, small_parts):
        path = tmp_path / "m.ckpt"
        small_parts.save_checkpoint(str(path), self.STATE)
        victim = sorted(tmp_path.glob("m.ckpt.*.part*"))[2]
        data = bytearray(victim.read_bytes())
        data[100] ^= 0xFF
        victim.write_bytes(bytes(data))
        with pytest.raises(small_parts.CheckpointError, match="part0002: checksum"):
            small_parts.check_checkpoint_file(str(path))
        victim.write_bytes(bytes(data[:-1]))
        with pytest.raises(small_parts.CheckpointError, match="part0002: size"):
            small_parts.load_checkpoint(str(path))
        victim.unlink()
        with pytest.raises(small_parts.CheckpointError, match="part0002: missing"):
            small_parts.check_checkpoint_file(str(path))

    def test_resave_leaves_only_the_new_parts(self, tmp_path, small_parts):
        path = tmp_path / "m.ckpt"
        small_parts.save_checkpoint(str(path), self.STATE)
        old = set(tmp_path.glob("m.ckpt.*.part*"))
        small_parts.save_checkpoint(str(path), {"w": self.STATE["w"] + 1})
        new = set(tmp_path.glob("m.ckpt.*.part*"))
        assert new and not (new & old)
        small_parts.check_checkpoint_file(str(path), require_manifest=True)
        # a later save that fits one file takes its parts with it
        small_parts.save_checkpoint(str(path), {"w": np.ones(3)})
        assert not list(tmp_path.glob("m.ckpt.*.part*"))
        state, _ = small_parts.load_checkpoint(str(path))
        np.testing.assert_array_equal(state["w"], np.ones(3))


# -------------------------------------------------------------------- retry


class TestRetry:
    def test_succeeds_after_transients(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient")
            return "ok"

        slept = []
        out = retry(flaky, RetryPolicy(attempts=3, base_delay=1.0, jitter=0.0),
                    sleep=slept.append)
        assert out == "ok" and calls["n"] == 3
        assert slept == [1.0, 2.0]  # exponential, jitter disabled

    def test_exhaustion_reraises_last(self):
        def dead():
            raise OSError("always")

        slept = []
        with pytest.raises(OSError, match="always"):
            retry(dead, RetryPolicy(attempts=2, base_delay=0.0), sleep=slept.append)
        assert slept == []  # base_delay 0 -> no sleeps

    def test_jitter_bounds_and_cap(self):
        import random

        slept = []

        def dead():
            raise OSError("x")

        with pytest.raises(OSError):
            retry(
                dead,
                RetryPolicy(attempts=4, base_delay=1.0, max_delay=2.0, jitter=0.5),
                sleep=slept.append,
                rng=random.Random(0),
            )
        caps = [1.0, 2.0, 2.0]  # min(max_delay, base * 2**i)
        assert len(slept) == 3
        for got, cap in zip(slept, caps):
            assert cap * 0.5 <= got <= cap

    def test_on_retry_hook_and_non_retryable(self):
        seen = []

        def boom():
            raise ValueError("not retryable")

        with pytest.raises(ValueError):
            retry(boom, RetryPolicy(attempts=3, retry_on=(OSError,)),
                  on_retry=lambda i, e: seen.append(i))
        assert seen == []  # ValueError escaped immediately

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("DALLE_TPU_DOWNLOAD_RETRIES", "7")
        monkeypatch.setenv("DALLE_TPU_DOWNLOAD_BACKOFF", "0.125")
        p = RetryPolicy(attempts=3, base_delay=1.0).from_env("DALLE_TPU_DOWNLOAD")
        assert p.attempts == 7 and p.base_delay == 0.125

    def test_zero_attempts_still_tries_once(self):
        # an operator setting <PREFIX>_RETRIES=0 means "no retries", not
        # "never call the function"
        assert retry(lambda: "ok", RetryPolicy(attempts=0)) == "ok"
        with pytest.raises(OSError, match="once"):
            retry(lambda: (_ for _ in ()).throw(OSError("once")),
                  RetryPolicy(attempts=0))


# -------------------------------------------------------------- preemption


class TestPreemptionHandler:
    def test_sigterm_sets_flag_and_restores(self):
        before = signal.getsignal(signal.SIGTERM)
        with PreemptionHandler() as p:
            assert not p.triggered
            os.kill(os.getpid(), signal.SIGTERM)
            assert p.triggered and p.signum == signal.SIGTERM
        assert signal.getsignal(signal.SIGTERM) is before

    def test_second_signal_raises(self):
        with PreemptionHandler(signals=(signal.SIGTERM,)) as p:
            os.kill(os.getpid(), signal.SIGTERM)
            with pytest.raises(KeyboardInterrupt):
                os.kill(os.getpid(), signal.SIGTERM)
            assert p.triggered


# ------------------------------------------------------------- manifests


class TestDirManifest:
    def _dir(self, tmp_path):
        d = tmp_path / "step_00000001"
        d.mkdir()
        (d / "a.bin").write_bytes(b"payload-a")
        (d / "sub").mkdir()
        (d / "sub" / "b.bin").write_bytes(b"payload-b")
        return d

    def test_roundtrip(self, tmp_path):
        d = self._dir(tmp_path)
        write_dir_manifest(d, extra={"step": 1})
        ok, reason = verify_dir_manifest(d)
        assert ok, reason
        m = json.loads((d / "MANIFEST.json").read_text())
        assert set(m["files"]) == {"a.bin", "sub/b.bin"} and m["step"] == 1

    def test_no_commit_marker_is_torn(self, tmp_path):
        d = self._dir(tmp_path)
        write_dir_manifest(d)
        (d / "COMMITTED").unlink()
        ok, reason = verify_dir_manifest(d)
        assert not ok and "commit marker" in reason

    def test_bit_corruption_detected(self, tmp_path):
        d = self._dir(tmp_path)
        write_dir_manifest(d)
        (d / "a.bin").write_bytes(b"payload-X")  # same size, different bytes
        ok, reason = verify_dir_manifest(d)
        assert not ok and "checksum" in reason

    def test_missing_and_truncated_files(self, tmp_path):
        d = self._dir(tmp_path)
        write_dir_manifest(d)
        (d / "a.bin").write_bytes(b"pay")  # truncated
        ok, reason = verify_dir_manifest(d)
        assert not ok and "size" in reason
        (d / "a.bin").unlink()
        ok, reason = verify_dir_manifest(d)
        assert not ok and "missing" in reason


# ---------------------------------------------------- tiny training harness


def _toy_setup(nan_inject_step=None, lr=0.1):
    """1-device runtime + quadratic toy model; returns (state, step_fn,
    make_batch). Deterministic, fast, and donation-correct like the real
    trainer's step."""
    runtime = make_runtime(devices=jax.devices()[:1])
    params = {"w": jnp.eye(4) * 0.5}

    def loss_fn(p, batch, rng):
        pred = batch["x"] @ p["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    opt = optax.adam(lr)
    state, shardings = create_train_state(params, opt, runtime)
    step_fn = make_train_step(
        loss_fn, opt, runtime, shardings, nan_inject_step=nan_inject_step
    )
    return state, step_fn


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    return [
        {
            "x": jnp.asarray(rng.randn(8, 4).astype(np.float32)),
            "y": jnp.asarray(rng.randn(8, 4).astype(np.float32)),
        }
        for _ in range(n)
    ]


def _host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def _train(state, step_fn, batches, *, start=0, ckpt_dir=None,
           save_every=None, preempt=None, on_step=None):
    """Drive the program's own loop (parallel/loop.py) over the toy harness
    the way train_dalle.py does: periodic verified saves and the emergency
    save on a preemption flag both resolve the in-flight verdict first and
    record the next batch index; ``start`` is a resume's first batch.

    -> (state, stopped_early)."""
    loop = TrainLoop(
        lambda s, batch, rng, lr: step_fn(s, batch, rng), state,
        feed=lambda batch: batch, lr=0.0, nan_abort_after=5,
        log=lambda line: None, global_step=int(state.step),
        resume=(0, start - 1),
    )
    for _ in loop.epoch(0, batches):
        step = int(loop.state.step)
        if ckpt_dir and save_every and step % save_every == 0:
            save_sharded_checkpoint(
                ckpt_dir, step, loop.state, meta={"next": loop.resolve() + 1}
            )
        if on_step is not None:
            on_step(step)
        if preempt is not None and preempt.triggered:
            save_sharded_checkpoint(
                ckpt_dir, step, loop.state,
                meta={"next": loop.resolve() + 1, "emergency": True},
            )
            return loop.state, True
    return loop.state, False


# ------------------------------------------------------------- NaN guard


class TestNaNGuard:
    def test_skip_leaves_state_bit_identical(self):
        state, step_fn = _toy_setup(nan_inject_step=0)
        (batch,) = _batches(1)
        before = _host(state)
        state, loss = step_fn(state, batch, jax.random.key(0))
        assert not math.isfinite(float(loss))  # host sees the raw NaN
        after = _host(state)
        for a, b in zip(
            jax.tree_util.tree_leaves(before.params),
            jax.tree_util.tree_leaves(after.params),
        ):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(
            jax.tree_util.tree_leaves(before.opt_state),
            jax.tree_util.tree_leaves(after.opt_state),
        ):
            np.testing.assert_array_equal(a, b)
        assert int(after.step) == 1  # attempts still count
        assert int(after.skipped) == 1 and int(after.consec_skipped) == 1

    def test_finite_loss_nonfinite_grad_rejected_and_signaled(self):
        """The guard keys on loss AND grad norm; the returned loss must be
        NaN for a grad-only rejection so the host's retry/abort verdict
        agrees with the device's select."""
        runtime = make_runtime(devices=jax.devices()[:1])

        def loss_fn(p, batch, rng):
            # value 0 (finite); d/dw sqrt(sum(w*0)) = 0/(2*sqrt(0)) -> NaN
            return jnp.sqrt(jnp.sum(p["w"] * batch["x"][:4, :4] * 0.0))

        opt = optax.adam(0.1)
        params = {"w": np.eye(4, dtype=np.float32) * 0.5}
        state, shardings = create_train_state(params, opt, runtime)
        before = _host(state)
        fn = make_train_step(loss_fn, opt, runtime, shardings)
        (batch,) = _batches(1)
        state, loss = fn(state, batch, jax.random.key(0))
        assert not math.isfinite(float(loss))  # rejection signal
        assert int(state.skipped) == 1 and int(state.consec_skipped) == 1
        for a, b in zip(
            jax.tree_util.tree_leaves(before.params),
            jax.tree_util.tree_leaves(_host(state.params)),
        ):
            np.testing.assert_array_equal(a, b)

    def test_finite_step_matches_unguarded_bitwise(self):
        runtime = make_runtime(devices=jax.devices()[:1])

        def loss_fn(p, batch, rng):
            return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

        opt = optax.adam(0.1)
        (batch,) = _batches(1)
        results = {}
        for guard in (True, False):
            # fresh host params each round: the donated buffers from the
            # first round's step are gone
            params = {"w": np.eye(4, dtype=np.float32) * 0.5}
            state, shardings = create_train_state(params, opt, runtime)
            fn = make_train_step(loss_fn, opt, runtime, shardings, nan_guard=guard)
            state, loss = fn(state, batch, jax.random.key(0))
            results[guard] = (_host(state), float(loss))
        assert results[True][1] == results[False][1]
        for a, b in zip(
            jax.tree_util.tree_leaves(results[True][0]),
            jax.tree_util.tree_leaves(results[False][0]),
        ):
            np.testing.assert_array_equal(a, b)

    def test_consec_counter_resets_and_retry_recovers_parity(self):
        """1 injected NaN + batch retry ends bit-identical to an unfaulted
        run (the trainer's skip-and-refeed policy)."""
        batches = _batches(4)

        clean_state, clean_fn = _toy_setup()
        clean_state, _ = _train(clean_state, clean_fn, batches)

        faulted_state, faulted_fn = _toy_setup(nan_inject_step=2)
        faulted_state, _ = _train(faulted_state, faulted_fn, batches)

        assert int(faulted_state.skipped) == 1
        assert int(faulted_state.consec_skipped) == 0  # reset by recovery
        assert int(faulted_state.step) == int(clean_state.step) + 1
        for a, b in zip(
            jax.tree_util.tree_leaves(_host(faulted_state.params)),
            jax.tree_util.tree_leaves(_host(clean_state.params)),
        ):
            np.testing.assert_array_equal(a, b)

    def test_trailing_nan_on_last_batch_is_still_retried(self):
        """A non-finite verdict on the run's FINAL step must not be
        silently dropped: the loop drains the pending verdict and retries
        before finishing (the epoch-boundary case in train_dalle.py)."""
        batches = _batches(3)
        clean_state, clean_fn = _toy_setup()
        clean_state, _ = _train(clean_state, clean_fn, batches)

        # input step 2 == the dispatch of the last batch
        f_state, f_fn = _toy_setup(nan_inject_step=2)
        f_state, _ = _train(f_state, f_fn, batches)
        assert int(f_state.skipped) == 1
        assert int(f_state.step) == int(clean_state.step) + 1
        for a, b in zip(
            jax.tree_util.tree_leaves(_host(f_state.params)),
            jax.tree_util.tree_leaves(_host(clean_state.params)),
        ):
            np.testing.assert_array_equal(a, b)


# ------------------------------------------- checkpoint verify + fallback


class TestVerifiedCheckpoints:
    def test_corrupt_newest_falls_back_to_verified(self, tmp_path):
        state, step_fn = _toy_setup()
        batches = _batches(3)
        root = str(tmp_path / "cp")
        for k, batch in enumerate(batches, start=1):
            state, _ = step_fn(state, batch, jax.random.key(0))
            if k == 3:
                FAULTS.arm("ckpt_corrupt", 1)  # poison the NEWEST save
            save_sharded_checkpoint(root, k, state, meta={"k": k})
        assert FAULTS.fired.get("ckpt_corrupt") == 1
        assert not (Path(root) / "aux.json.tmp").exists()  # atomic sidecar

        ok, _ = verify_step_dir(str(Path(root) / "step_00000003"))
        assert not ok
        assert latest_verified_step(root) == 2

        restored, meta, step = load_sharded_checkpoint(root, _host(state))
        assert step == 2 and meta == {"k": 2}  # per-step meta, not newest

    def test_torn_dir_without_commit_is_skipped(self, tmp_path):
        state, step_fn = _toy_setup()
        (batch,) = _batches(1)
        state, _ = step_fn(state, batch, jax.random.key(0))
        root = str(tmp_path / "cp")
        save_sharded_checkpoint(root, 1, state, meta={"k": 1})
        # simulate a crash mid-save: orbax wrote files, no commit marker
        torn = Path(root) / "step_00000002"
        torn.mkdir()
        (torn / "half_written.bin").write_bytes(b"\0" * 64)

        restored, meta, step = load_sharded_checkpoint(root, _host(state))
        assert step == 1 and meta == {"k": 1}
        np.testing.assert_array_equal(
            np.asarray(restored.params["w"]), np.asarray(state.params["w"])
        )

    def test_explicit_corrupt_step_refuses(self, tmp_path):
        state, step_fn = _toy_setup()
        (batch,) = _batches(1)
        state, _ = step_fn(state, batch, jax.random.key(0))
        root = str(tmp_path / "cp")
        FAULTS.arm("ckpt_corrupt", 1)
        save_sharded_checkpoint(root, 1, state)
        with pytest.raises(AssertionError, match="verification"):
            load_sharded_checkpoint(root, _host(state), step=1)

    def test_all_torn_refuses(self, tmp_path):
        root = tmp_path / "cp"
        torn = root / "step_00000001"
        torn.mkdir(parents=True)
        (torn / "x.bin").write_bytes(b"x")
        with pytest.raises(AssertionError, match="no verified"):
            load_sharded_checkpoint(str(root), {"w": np.zeros(2)})

    def test_rotation_counts_only_committed_dirs(self, tmp_path):
        """A torn dir must not push the last good fallback out of the
        keep_n window — and gets pruned as junk."""
        state, step_fn = _toy_setup()
        root = tmp_path / "cp"
        (batch,) = _batches(1)
        state, _ = step_fn(state, batch, jax.random.key(0))
        save_sharded_checkpoint(str(root), 1, state, keep_n=2)
        # crash-mid-save debris newer than the good step
        torn = root / "step_00000002"
        torn.mkdir()
        (torn / "half.bin").write_bytes(b"\0" * 32)
        state, _ = step_fn(state, batch, jax.random.key(0))
        save_sharded_checkpoint(str(root), 3, state, keep_n=2)
        kept = sorted(p.name for p in root.glob("step_*"))
        assert kept == ["step_00000001", "step_00000003"]  # torn junk gone

    def test_verify_ckpt_cli(self, tmp_path, capsys):
        sys.path.insert(0, str(TOOLS))
        try:
            import verify_ckpt
        finally:
            sys.path.pop(0)

        state, step_fn = _toy_setup()
        root = str(tmp_path / "cp")
        for k, batch in enumerate(_batches(2), start=1):
            state, _ = step_fn(state, batch, jax.random.key(0))
            save_sharded_checkpoint(root, k, state)
        assert verify_ckpt.main([root]) == 0

        # corrupt the newest -> exit 1, report names the failure
        victim = max(
            (p for p in (Path(root) / "step_00000002").rglob("*")
             if p.is_file() and p.name not in ("MANIFEST.json", "COMMITTED")),
            key=lambda p: p.stat().st_size,
        )
        victim.write_bytes(b"\xff" * victim.stat().st_size)
        assert verify_ckpt.main([root]) == 1
        out = capsys.readouterr().out
        assert "FAIL  step_00000002" in out and "newest verified: step_00000001" in out

        assert verify_ckpt.main([str(tmp_path / "absent")]) == 2


# --------------------------------------------------------- kill-and-resume


class TestKillAndResume:
    def test_emergency_save_then_resume_is_bit_identical(self, tmp_path):
        """Real SIGTERM mid-run -> emergency step-granular save -> a fresh
        'process' resumes and ends bit-identical to an uninterrupted run."""
        batches = _batches(6, seed=1)
        root = str(tmp_path / "cp")

        clean_state, clean_fn = _toy_setup()
        clean_state, _ = _train(clean_state, clean_fn, batches)

        state, step_fn = _toy_setup()
        with PreemptionHandler() as preempt:
            kill = lambda step: step == 3 and os.kill(os.getpid(), signal.SIGTERM)
            state, stopped = _train(
                state, step_fn, batches,
                ckpt_dir=root, preempt=preempt, on_step=kill,
            )
        assert stopped and latest_verified_step(root) == 3

        # "restart": fresh state + step_fn, restore, continue from meta
        state2, step_fn2 = _toy_setup()
        restored, meta, step = load_sharded_checkpoint(root, _host(state2))
        assert step == 3 and meta["emergency"]
        resumed, _ = _train(restored, step_fn2, batches, start=meta["next"])

        assert int(resumed.step) == int(clean_state.step)
        for a, b in zip(
            jax.tree_util.tree_leaves(_host(resumed)),
            jax.tree_util.tree_leaves(_host(clean_state)),
        ):
            np.testing.assert_array_equal(a, b)

    def test_acceptance_all_faults_same_final_state(self, tmp_path):
        """The ISSUE's acceptance scenario, end to end: 2 transient download
        failures fetching the dataset, 1 injected NaN loss (skipped on
        device, batch retried), SIGTERM mid-run (emergency save), and the
        newest checkpoint dir corrupted post-commit — the resumed run falls
        back to the last verified periodic save, replays, and its final
        params/opt_state equal the unfaulted run's bit for bit."""
        # -- data arrives via download() with 2 injected transient failures
        src = tmp_path / "remote" / "data.npy"
        src.parent.mkdir()
        rng = np.random.RandomState(7)
        np.save(src, rng.randn(6, 2, 8, 4).astype(np.float32))
        FAULTS.arm("download", 2)
        local = download(
            str(src), root=str(tmp_path / "cache"),
            policy=RetryPolicy(attempts=3, base_delay=0.0),
        )
        assert FAULTS.fired["download"] == 2
        data = np.load(local)
        batches = [
            {"x": jnp.asarray(d[0]), "y": jnp.asarray(d[1])} for d in data
        ]
        root = str(tmp_path / "cp")

        # -- reference: unfaulted run over the same data
        clean_state, clean_fn = _toy_setup()
        clean_state, _ = _train(clean_state, clean_fn, batches)

        # -- faulted run: NaN at step 2, SIGTERM at step 5, and the
        #    emergency save itself corrupted (post-commit bit rot)
        state, step_fn = _toy_setup(nan_inject_step=2)
        with PreemptionHandler() as preempt:
            def on_step(step):
                if step == 5:
                    FAULTS.arm("ckpt_corrupt", 1)
                    os.kill(os.getpid(), signal.SIGTERM)

            state, stopped = _train(
                state, step_fn, batches,
                ckpt_dir=root, save_every=2, preempt=preempt, on_step=on_step,
            )
        assert stopped
        assert int(state.skipped) == 1  # the injected NaN was rejected
        assert FAULTS.fired.get("ckpt_corrupt") == 1

        # the corrupted emergency dir must NOT be restorable; fallback is
        # the step-4 periodic save
        assert latest_verified_step(root) == 4

        # -- "relaunch": resume exactly like train_dalle.py's startup probe
        state2, step_fn2 = _toy_setup(nan_inject_step=2)  # env still armed
        restored, meta, step = load_sharded_checkpoint(root, _host(state2))
        assert step == 4 and not meta.get("emergency")
        resumed, stopped = _train(
            restored, step_fn2, batches, start=meta["next"]
        )
        assert not stopped

        # one extra dispatch (the retried NaN batch); applied updates equal
        assert int(resumed.step) == int(clean_state.step) + 1
        assert int(resumed.skipped) == 1
        for a, b in zip(
            jax.tree_util.tree_leaves(_host(resumed.params)),
            jax.tree_util.tree_leaves(_host(clean_state.params)),
        ):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(
            jax.tree_util.tree_leaves(_host(resumed.opt_state)),
            jax.tree_util.tree_leaves(_host(clean_state.opt_state)),
        ):
            np.testing.assert_array_equal(a, b)


# ------------------------------------------------------- download resilience


class TestDownloadResilience:
    def test_transient_failures_then_success(self, tmp_path):
        src = tmp_path / "w.bin"
        src.write_bytes(b"\x01\x02\x03")
        FAULTS.arm("download", 2)
        out = download(
            str(src), root=str(tmp_path / "cache"),
            policy=RetryPolicy(attempts=3, base_delay=0.0),
        )
        assert Path(out).read_bytes() == b"\x01\x02\x03"
        assert FAULTS.fired["download"] == 2
        assert counters.get("download.retries") == 2

    def test_stale_tmp_cleaned_on_entry(self, tmp_path):
        src = tmp_path / "w.bin"
        src.write_bytes(b"fresh")
        cache = tmp_path / "cache"
        cache.mkdir()
        stale = cache / "w.bin.tmp"
        stale.write_bytes(b"wedged half-download from a crashed run")
        out = download(str(src), root=str(cache))
        assert Path(out).read_bytes() == b"fresh" and not stale.exists()

    def test_exhaustion_raises_and_leaves_no_tmp(self, tmp_path):
        src = tmp_path / "w.bin"
        src.write_bytes(b"data")
        FAULTS.arm("download", 9)
        with pytest.raises(OSError):
            download(
                str(src), root=str(tmp_path / "cache"),
                policy=RetryPolicy(attempts=2, base_delay=0.0),
            )
        assert counters.get("download.failures") == 1
        assert not list((tmp_path / "cache").glob("*.tmp"))

    def test_timeout_reaches_urlopen(self, tmp_path, monkeypatch):
        seen = {}

        class FakeResp(io.BytesIO):
            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

        def fake_urlopen(url, timeout=None):
            seen["timeout"] = timeout
            return FakeResp(b"remote-bytes")

        monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
        out = download(
            "http://example.invalid/f.bin", root=str(tmp_path / "cache"),
            timeout=7.5,
        )
        assert seen["timeout"] == 7.5
        assert Path(out).read_bytes() == b"remote-bytes"

    def test_timeout_none_means_no_limit(self, tmp_path, monkeypatch):
        seen = {}

        class FakeResp(io.BytesIO):
            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

        monkeypatch.setattr(
            urllib.request, "urlopen",
            lambda url, timeout=None: (seen.update(timeout=timeout), FakeResp(b"x"))[1],
        )
        download("http://example.invalid/h.bin", root=str(tmp_path / "cache"),
                 timeout=None)
        assert seen["timeout"] is None

    def test_timeout_env_override(self, tmp_path, monkeypatch):
        seen = {}

        class FakeResp(io.BytesIO):
            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

        monkeypatch.setattr(
            urllib.request, "urlopen",
            lambda url, timeout=None: (seen.update(timeout=timeout), FakeResp(b"x"))[1],
        )
        monkeypatch.setenv("DALLE_TPU_DOWNLOAD_TIMEOUT", "3")
        download("http://example.invalid/g.bin", root=str(tmp_path / "cache"))
        assert seen["timeout"] == 3.0


# --------------------------------------------------------- shard resilience


class _StubTokenizer:
    vocab_size = 64

    def tokenize(self, text, length, truncate_text=False):
        ids = [(ord(c) % 63) + 1 for c in text[:length]]
        return np.asarray([ids + [0] * (length - len(ids))], dtype=np.int32)


def _make_shard(path, n=2, start=0, with_bad=False):
    with tarfile.open(path, "w") as tf:
        def add(name, data):
            info = tarfile.TarInfo(name)
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))

        for i in range(start, start + n):
            img = Image.new("RGB", (24, 24), (10 * i, 20, 30))
            buf = io.BytesIO()
            img.save(buf, format="PNG")
            add(f"sample{i:04d}.png", buf.getvalue())
            add(f"sample{i:04d}.txt", f"caption {i}".encode())
        if with_bad:
            add("bad0001.png", b"garbage bytes")
            add("bad0001.txt", b"broken image")


class TestShardResilience:
    def _ds(self, spec, attempts=2):
        from dalle_pytorch_tpu.data.webdata import TarImageTextDataset

        return TarImageTextDataset(
            spec, text_len=8, image_size=16, tokenizer=_StubTokenizer(),
            retry_policy=RetryPolicy(attempts=attempts, base_delay=0.0),
        )

    def test_transient_open_retries_then_streams(self, tmp_path):
        _make_shard(tmp_path / "s.tar", n=2)
        ds = self._ds(str(tmp_path / "s.tar"))
        FAULTS.arm("shard_open", 1)
        assert len(list(ds)) == 2
        assert counters.get("webdata.shard_open_retries") == 1
        assert counters.get("webdata.shards_quarantined") == 0

    def test_dead_shard_quarantined_and_not_rehammered(self, tmp_path):
        _make_shard(tmp_path / "shard-0000.tar", n=2, start=0)
        _make_shard(tmp_path / "shard-0001.tar", n=2, start=2)
        ds = self._ds(str(tmp_path / "shard-{0000..0001}.tar"))
        FAULTS.arm("shard_open", 2)  # kills every attempt at the 1st shard
        assert len(list(ds)) == 2  # second shard still streamed
        assert counters.get("webdata.shards_quarantined") == 1
        # epoch 2: quarantined shard skipped WITHOUT new open attempts
        # (the retry counter tallies actual RETRIES: 2 attempts = 1 retry)
        assert len(list(ds)) == 2
        assert counters.get("webdata.quarantined_skips") == 1
        assert counters.get("webdata.shard_open_retries") == 1

    def test_decode_errors_are_counted(self, tmp_path):
        _make_shard(tmp_path / "s.tar", n=2, with_bad=True)
        ds = self._ds(str(tmp_path / "s.tar"))
        assert len(list(ds)) == 2  # bad sample dropped, stream continued
        assert counters.get("webdata.decode_errors") == 1

    def test_midshard_fault_aborts_shard_but_keeps_stream(self, tmp_path):
        _make_shard(tmp_path / "shard-0000.tar", n=2, start=0)
        _make_shard(tmp_path / "shard-0001.tar", n=2, start=2)
        ds = self._ds(str(tmp_path / "shard-{0000..0001}.tar"))
        FAULTS.arm("shard_read", 1)
        got = len(list(ds))
        assert got == 2  # first shard aborted mid-read, second intact
        assert counters.get("webdata.shard_aborts") == 1
