"""Multi-process worker + shared training routine for test_multiprocess.py.

The reference actually executes as one OS process per GPU with NCCL
rendezvous (deepspeed_backend.py:36-64, README launcher docs); this is the
TPU-native equivalent — one process per host, ``jax.distributed``
rendezvous, a global dp x fsdp mesh spanning both processes' devices.

Run as a script by the test (``python tests/multiprocess_worker.py
--process_id i ...``), each process pinned to 4 virtual CPU devices, and
also imported by the test for the single-process baseline: the training
math lives in ``run_training`` so the 2-process run and the in-pytest
8-device run execute literally the same code.

Exercises the process_count > 1 paths that single-process tests cannot:
  - ``init_distributed`` rendezvous (parallel/mesh.py)
  - global-array creation from process-local callbacks
  - cross-process ``barrier`` / ``average_all`` / ``to_host`` collectives
  - ``DataLoader`` per-host disjoint sample sharding (data/loader.py)
  - root-only checkpoint write, readable by all after the barrier
    (the reference's root-gated save, train_dalle.py + vae.py barriers)
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

TINY = dict(
    dim=64,
    depth=2,
    num_text_tokens=32,
    text_seq_len=8,
    num_image_tokens=16,
    image_fmap_size=4,
    heads=4,
    dim_head=16,
    attn_types=("full",),
)
BATCH = 16
STEPS = 3


def run_training(runtime):
    """Identical math on any runtime: tiny DALLE, dp/fsdp-sharded Adam,
    STEPS steps on a deterministic global batch.

    -> (losses, update_norm_fingerprint, host_params) where host_params is
    the full (allgathered) post-training parameter tree on every process.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dalle_pytorch_tpu.models import DALLE
    from dalle_pytorch_tpu.parallel import create_train_state, make_train_step

    dalle = DALLE(**TINY)
    rng = np.random.RandomState(0)
    text_np = rng.randint(1, TINY["num_text_tokens"], size=(BATCH, TINY["text_seq_len"])).astype(np.int32)
    image_np = rng.randint(0, TINY["num_image_tokens"], size=(BATCH, TINY["image_fmap_size"] ** 2)).astype(np.int32)

    def loss_fn(p, batch, rng):
        return dalle.apply(
            {"params": p}, batch["text"], batch["image"], return_loss=True
        )

    params = dalle.init(
        jax.random.key(0), jnp.asarray(text_np[:1]), jnp.asarray(image_np[:1])
    )["params"]
    opt = optax.chain(optax.clip_by_global_norm(0.5), optax.adam(1e-3))
    state, shardings = create_train_state(params, opt, runtime)
    step = make_train_step(loss_fn, opt, runtime, shardings)

    # global batch: every process holds the same full numpy batch; each
    # process's devices pull their own shards through the callback
    dsh = runtime.data_sharding

    def globalize(x):
        return jax.make_array_from_callback(x.shape, dsh, lambda idx: x[idx])

    batch = {"text": globalize(text_np), "image": globalize(image_np)}

    p0 = runtime.to_host(state.params)
    losses = []
    fingerprint = None
    for i in range(STEPS):
        state, loss = step(state, batch, jax.random.key(i))
        losses.append(float(loss))
        if i == 0:
            delta = jax.tree_util.tree_map(
                lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32),
                runtime.to_host(state.params), p0,
            )
            fingerprint = float(jnp.sqrt(sum(
                jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(delta)
            )))
    return losses, fingerprint, runtime.to_host(state.params)


def loader_shard_indices(data_dir: str, process_index: int, process_count: int):
    """The per-host sample shard the DataLoader would consume this epoch —
    and prove the pipeline yields by pulling the first batch."""
    from dalle_pytorch_tpu.data import DataLoader, TextImageDataset

    ds = TextImageDataset(
        data_dir, text_len=8, image_size=16, truncate_captions=True
    )
    loader = DataLoader(
        ds, batch_size=4, shuffle=True, seed=7,
        process_index=process_index, process_count=process_count,
    )
    first = next(iter(loader))
    assert first["text"].shape == (4, 8) and first["image"].shape == (4, 16, 16, 3)
    return sorted(loader._indices())


def main(argv=None):
    import argparse
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--process_id", type=int, required=True)
    ap.add_argument("--num_processes", type=int, default=2)
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--local_devices", type=int, default=4)
    ap.add_argument("--data_dir", required=True)
    ap.add_argument("--ckpt", required=True)
    args = ap.parse_args(argv)

    # platform setup must precede the first backend-initializing jax call.
    # Preserve inherited XLA_FLAGS (the environment may carry memory/threading
    # flags the in-pytest baseline also sees) but override the device count —
    # the pytest parent pins 8, this worker needs its own local_devices.
    kept = [
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    os.environ["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={args.local_devices}"]
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_threefry_partitionable", True)
    sys.path.insert(0, str(REPO))
    from dalle_pytorch_tpu.compile_cache import enable_compile_cache

    enable_compile_cache(default=REPO / "tests" / ".jax_cache")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from dalle_pytorch_tpu.parallel import init_distributed, make_runtime
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    init_distributed(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )
    assert jax.process_count() == args.num_processes
    assert jax.local_device_count() == args.local_devices
    n_global = args.num_processes * args.local_devices

    runtime = make_runtime(fsdp=2)  # dp x fsdp over all global devices
    assert runtime.world_size == n_global

    losses, fingerprint, host_params = run_training(runtime)

    # root-only checkpoint write; everyone reads it back after the barrier
    if runtime.is_root_worker():
        save_checkpoint(args.ckpt, {"params": host_params}, meta={"world": n_global})
    runtime.barrier("post-save")
    import numpy as np

    loaded, meta = load_checkpoint(args.ckpt, target={"params": host_params})
    ckpt_ok = meta.get("world") == n_global and all(
        np.allclose(a, b)
        for a, b in zip(
            jax.tree_util.tree_leaves(loaded["params"]),
            jax.tree_util.tree_leaves(host_params),
        )
    )

    avg = runtime.average_all(float(runtime.process_index))
    shard = loader_shard_indices(
        args.data_dir, runtime.process_index, runtime.process_count
    )

    print("MPRESULT " + json.dumps({
        "process_id": args.process_id,
        "world_size": runtime.world_size,
        "losses": losses,
        "fingerprint": fingerprint,
        "ckpt_ok": bool(ckpt_ok),
        "average_all": avg,
        "loader_shard": shard,
    }), flush=True)


if __name__ == "__main__":
    main()
