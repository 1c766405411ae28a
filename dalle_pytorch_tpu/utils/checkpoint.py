"""Checkpoint store — dual-format, mirroring the reference's semantics
(SURVEY.md §5.4).

Plain format (reference train_dalle.py:514-519 ``torch.save`` of
``{hparams, vae_params, epoch, weights, opt_state, scheduler_state}``):
one msgpack payload holding json-encoded hparams plus the numpy-ified state
pytree — readable on any host, no framework pickle. A payload above
``PART_BYTES`` is spread over sibling part files no larger than that, and
the file at the checkpoint's path becomes a small index naming them.

Sharded format (reference DeepSpeed ``save_checkpoint`` into a ``-ds-cp/``
dir, train_dalle.py:520-544): an orbax directory checkpoint that writes each
host's addressable shards in parallel — the right format for fsdp/tp-sharded
TrainStates — plus the same ``aux.json`` hparams sidecar the reference keeps
in ``auxiliary.pt``. Rotation keeps the newest N step dirs
(cp_files_to_keep, train_dalle.py:523-526).

Directory saves are two-phase committed (docs/DESIGN.md §9): after orbax
finishes, every file in the step dir is checksummed into ``MANIFEST.json``
and a ``COMMITTED`` marker lands last. ``load_sharded_checkpoint`` restores
only verified step dirs and falls back to the newest verified one — a crash
mid-save (or bit corruption on the newest dir) costs at most the steps since
the previous verified save, never a poisoned restore.
"""

from __future__ import annotations

import hashlib
import json
import shutil
import sys
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np
from flax import serialization

from .faults import FAULTS
from .resilience import (
    COMMIT_NAME,
    FILE_MANIFEST_SUFFIX,
    verify_dir_manifest,
    verify_file_manifest,
    write_dir_manifest,
    write_file_manifest,
)

_HEADER_KEY = "__dalle_tpu_meta__"


class CheckpointError(RuntimeError):
    """Typed load failure: missing, torn, or corrupt checkpoint. CLIs catch
    this and exit nonzero with the reason instead of surfacing a msgpack
    stack trace (or, pre-manifest, silently deserializing garbage)."""


def _to_host(tree: Any) -> Any:
    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)


# Largest file a plain save writes. Hosts cap file sizes (RLIMIT_FSIZE, a
# scratch filesystem's own limit): the flagship's 3.6 GiB single file died
# with EFBIG on a TPU machine whose limit lies somewhere above the 64 MiB
# VAE checkpoint it had just accepted.
PART_BYTES = 32 << 20

_PARTS_MAGIC = b"dalle-tpu-checkpoint-parts\n"


def _part_files(p: Path) -> set:
    return set(p.parent.glob(p.name + ".*.part[0-9]*"))


def _write_parts(p: Path, data: bytes) -> bytes:
    """Write ``data`` as ``<name>.<tag>.partNNNN`` files of at most
    ``PART_BYTES`` beside ``p`` and return the index that takes its place
    at ``p``: the magic line, then json ``[{name, bytes, sha256}, ...]``.
    The tag is derived from the content, so the parts of the save being
    replaced are never overwritten — they stay valid until the index swap."""
    view = memoryview(data)
    chunks = [view[i:i + PART_BYTES] for i in range(0, len(view), PART_BYTES)]
    shas = [hashlib.sha256(c).hexdigest() for c in chunks]
    tag = hashlib.sha256("".join(shas).encode()).hexdigest()[:12]
    index = []
    for i, (chunk, sha) in enumerate(zip(chunks, shas)):
        name = f"{p.name}.{tag}.part{i:04d}"
        p.with_name(name).write_bytes(chunk)
        index.append({"name": name, "bytes": len(chunk), "sha256": sha})
    return _PARTS_MAGIC + json.dumps(index).encode()


def _parts_index(p: Path) -> list:
    """The part entries when the file at ``p`` is an index, else []."""
    with open(p, "rb") as f:
        if f.read(len(_PARTS_MAGIC)) != _PARTS_MAGIC:
            return []
        return json.loads(f.read())


def _part(p: Path, entry: dict, checksum: bool) -> Path:
    """The part file ``entry`` names beside ``p``; a typed refusal when it
    is missing, torn or (with ``checksum``) corrupt."""
    part = p.with_name(entry["name"])
    if not part.exists():
        reason = "missing"
    elif part.stat().st_size != entry["bytes"]:
        reason = "size mismatch (torn write)"
    elif checksum and _file_sha256(part) != entry["sha256"]:
        reason = "checksum mismatch (bit corruption)"
    else:
        return part
    raise CheckpointError(f"checkpoint {p}: part {entry['name']}: {reason}")


def _file_sha256(path: Path) -> str:
    with open(path, "rb") as f:
        return hashlib.file_digest(f, "sha256").hexdigest()


def save_checkpoint(path: str, state: Any, meta: Optional[dict] = None) -> None:
    """Plain save: msgpack of {meta-json, state} with every leaf a host
    numpy array (gathers sharded arrays — use the sharded format for models
    that don't fit one host), in one file or, above ``PART_BYTES``, in part
    files behind an index at ``path``."""
    payload = {
        _HEADER_KEY: json.dumps(meta or {}),
        "state": serialization.to_state_dict(_to_host(state)),
    }
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    data = serialization.msgpack_serialize(payload)
    stale = _part_files(p)
    if len(data) > PART_BYTES:
        data = _write_parts(p, data)
    tmp = p.with_suffix(p.suffix + ".tmp")
    tmp.write_bytes(data)
    # invalidate any PREVIOUS save's sidecar before the content swap: a
    # crash between replace and the new sidecar must leave "no manifest"
    # (unverified but loadable), never a stale manifest describing the old
    # bytes that would condemn a perfectly good new file as corrupt
    Path(str(p) + FILE_MANIFEST_SUFFIX).unlink(missing_ok=True)
    tmp.replace(p)  # atomic: never leave a torn checkpoint
    # sha256+size sidecar, written last (single-file two-phase commit):
    # serving loads verify against it instead of trusting the file; an
    # index carries its parts' checksums, so the sidecar vouches for them
    write_file_manifest(p)
    for old in stale - {p.with_name(e["name"]) for e in _parts_index(p)}:
        old.unlink(missing_ok=True)


def _read_payload(p: Path):
    """The msgpack payload saved at ``p``, reassembled from its parts."""
    index = _parts_index(p)
    if not index:
        return p.read_bytes()
    buf = bytearray(sum(e["bytes"] for e in index))
    view, at = memoryview(buf), 0
    for e in index:
        with open(_part(p, e, checksum=False), "rb") as f:
            f.readinto(view[at:at + e["bytes"]])
        at += e["bytes"]
    return buf


def load_checkpoint(path: str, target: Any = None) -> tuple[Any, dict]:
    """-> (state, meta). With ``target`` (a template pytree) the state is
    restored into that structure; otherwise a raw nested dict is returned."""
    raw = serialization.msgpack_restore(_read_payload(Path(path)))
    meta = json.loads(raw.pop(_HEADER_KEY, "{}"))
    state = raw["state"]
    if target is not None:
        state = serialization.from_state_dict(target, state)
    return state, meta


def check_checkpoint_file(path: str, require_manifest: bool = False) -> None:
    """Refuse a missing/torn/corrupt plain checkpoint BEFORE deserializing
    it — raises ``CheckpointError`` with the manifest verifier's reason.

    Serving entry points (generate.py) call this instead of
    ``assert Path(...).exists()``: an existence check happily loads a file
    truncated by a crashed save or bit-rotted in transit. A checkpoint
    without a sidecar (saved pre-manifest) passes with a stderr warning
    unless ``require_manifest``; msgpack parse errors downstream still
    surface, they are just no longer the FIRST line of defense."""
    ok, reason = verify_file_manifest(path)
    if not ok and reason == "no manifest" and not require_manifest:
        print(
            f"WARNING: {path} has no manifest sidecar (pre-manifest save); "
            "loading unverified", file=sys.stderr,
        )
        return
    if not ok:
        raise CheckpointError(f"checkpoint {path}: {reason}")
    # the sidecar vouches for the index, the index for each part
    for e in _parts_index(Path(path)):
        _part(Path(path), e, checksum=True)


# ----------------------------------------------------------- sharded format


def save_sharded_checkpoint(
    ckpt_dir: str,
    step: int,
    state: Any,
    meta: Optional[dict] = None,
    keep_n: Optional[int] = None,
) -> str:
    """Write ``<ckpt_dir>/step_<n>/`` via orbax (each host writes its own
    shards), checksum+commit it, refresh the ``aux.json`` hparams sidecar
    (atomically — a crash mid-write must not take out the resume metadata
    for every older step), and rotate old step dirs."""
    import orbax.checkpoint as ocp

    root = Path(ckpt_dir)
    root.mkdir(parents=True, exist_ok=True)
    target = (root / f"step_{step:08d}").resolve()
    with ocp.PyTreeCheckpointer() as ckptr:
        ckptr.save(target, state, force=True)
    # manifest/sidecar/rotation are single-writer: the orbax save above is
    # the collective part (and synchronizes hosts); N hosts writing the
    # same MANIFEST.json.tmp on a shared filesystem would race a truncated
    # manifest into a COMMITTED dir
    if jax.process_index() == 0:
        # meta rides in the manifest too: on fallback to an older step the
        # restored meta must describe THAT step, not the newest aux.json
        # write
        write_dir_manifest(target, extra={"step": step, "meta": meta or {}})
        if FAULTS.take("ckpt_corrupt"):
            _corrupt_one_file(target)
        aux = root / "aux.json"
        tmp = aux.with_suffix(".json.tmp")
        tmp.write_text(json.dumps({"meta": meta or {}, "latest": step}))
        tmp.replace(aux)

        if keep_n is not None:
            # rotation counts only COMMITTED dirs — a torn leftover must
            # not push the last good fallback out of the window. Torn dirs
            # (no marker; crash-mid-save debris of the two-phase design)
            # are junk and get pruned outright. Marker presence is cheap;
            # full checksums stay a load-time concern.
            committed, torn = [], []
            for d in sorted(root.glob("step_*")):
                (committed if (d / COMMIT_NAME).exists() else torn).append(d)
            for old in torn + committed[:-keep_n]:
                shutil.rmtree(old, ignore_errors=True)
    return str(target)


def _corrupt_one_file(step_dir: Path) -> None:
    """ckpt_corrupt fault: flip bytes in the largest payload file AFTER the
    manifest committed — models post-commit bit rot / torn replication, the
    case only checksum verification catches (a missing commit marker is the
    easier torn-save case)."""
    payload = [
        p for p in step_dir.rglob("*")
        if p.is_file() and p.name not in ("MANIFEST.json", "COMMITTED")
    ]
    victim = max(payload, key=lambda p: p.stat().st_size)
    data = bytearray(victim.read_bytes())
    for i in range(min(64, len(data))):
        data[i] ^= 0xFF
    victim.write_bytes(data)
    print(f"fault ckpt_corrupt: flipped bytes in {victim}", file=sys.stderr)


def verify_step_dir(step_dir: str) -> tuple[bool, str]:
    """-> (ok, reason): commit marker present and every manifested file
    passes size+sha256. The operator CLI is ``tools/verify_ckpt.py``."""
    return verify_dir_manifest(step_dir)


def latest_verified_step(ckpt_dir: str) -> Optional[int]:
    """Newest step number whose dir verifies; None when none do (or the
    dir doesn't exist) — the trainer's resume probe."""
    root = Path(ckpt_dir)
    if not root.is_dir():
        return None
    for path in sorted(root.glob("step_*"), reverse=True):
        ok, _ = verify_dir_manifest(path)
        if ok:
            return int(path.name.split("_")[1])
    return None


def load_sharded_checkpoint(
    ckpt_dir: str,
    target: Any,
    step: Optional[int] = None,
    shardings: Any = None,
    verify: bool = True,
) -> tuple[Any, dict, int]:
    """Restore the newest VERIFIED (or given) step dir into ``target``'s
    structure, placing leaves with ``shardings`` when given.
    -> (state, meta, step).

    Torn/corrupt step dirs are skipped with a warning and the newest
    verified one wins — the pre-manifest behavior (``steps[-1]``) happily
    restored a half-written dir left by a crash mid-save. An explicitly
    requested ``step`` must itself verify; ``verify=False`` skips that
    re-hash ONLY for a step the caller just verified (the trainer's
    resume probe — checksumming a multi-GB checkpoint twice per launch
    is real time)."""
    import orbax.checkpoint as ocp

    root = Path(ckpt_dir)
    aux = json.loads((root / "aux.json").read_text()) if (root / "aux.json").exists() else {}
    if step is None:
        steps = sorted(root.glob("step_*"), reverse=True)
        assert steps, f"no step_* checkpoints under {ckpt_dir}"
        path = None
        for cand in steps:
            ok, reason = verify_dir_manifest(cand)
            if ok:
                path = cand.resolve()
                break
            print(
                f"checkpoint {cand.name} skipped: {reason}", file=sys.stderr
            )
        assert path is not None, (
            f"no verified step_* checkpoint under {ckpt_dir} "
            f"({len(steps)} dirs present, all torn/corrupt — "
            "run tools/verify_ckpt.py for per-file detail)"
        )
        step = int(path.name.split("_")[1])
    else:
        path = (root / f"step_{step:08d}").resolve()
        if verify:
            ok, reason = verify_dir_manifest(path)
            assert ok, f"requested checkpoint {path} failed verification: {reason}"

    if shardings is not None:
        abstract = jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            target,
            shardings,
        )
        args = __import__("orbax.checkpoint", fromlist=["args"]).args
        with ocp.PyTreeCheckpointer() as ckptr:
            state = ckptr.restore(path, args=args.PyTreeRestore(item=abstract))
    else:
        with ocp.PyTreeCheckpointer() as ckptr:
            state = ckptr.restore(path, item=target)
    try:
        meta = json.loads((path / "MANIFEST.json").read_text()).get("meta")
    except (OSError, ValueError):
        meta = None
    if meta is None:
        meta = aux.get("meta", {})
    return state, meta, step
