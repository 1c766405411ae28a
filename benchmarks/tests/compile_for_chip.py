"""Rehearsal 3 of the on-chip-measurement guide, run by hand (not a test):

    JAX_PLATFORMS=cpu python benchmarks/tests/compile_for_chip.py [serve] [train[:<configuration>]]

compiles the programs the cells time, at the cells' own sizes, for a v5e that
is described and not attached, and prints each one's ``memory_analysis()``.
Nothing runs, so it says nothing about results or times.
"""

from __future__ import annotations

import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from benchmarks import costs, traffic
from benchmarks.drivers.serve import build_dalle, param_shapes
from dalle_pytorch_tpu.ops import kv_policy

kv_policy.on_tpu = lambda: True   # the program's one platform decision


def on(device, tree):
    s = SingleDeviceSharding(device)
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree
    )


def report(name, lowered):
    t = time.monotonic()
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"{name}: compiled in {time.monotonic() - t:.0f} s; "
          f"arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
          f"outputs {m.output_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB, "
          f"aliased {m.alias_size_in_bytes / 1e9:.2f} GB; "
          f"tpu_custom_call x{text.count('tpu_custom_call')}", flush=True)


def serve(device):
    from dalle_pytorch_tpu.models.sampling import init_decode_cache, set_decode_offsets
    from dalle_pytorch_tpu.serving import engine as eng

    cfg = costs.load_config("dalle-d12-full")
    mix = traffic.load("backlog-c128", pathlib.Path(__file__).resolve().parent / "data" / "traffic")
    B = mix["engine"]["max_batch"]
    dalle = build_dalle(cfg)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), param_shapes(dalle, cfg)
    )

    def cache_of(b):
        return jax.eval_shape(lambda p: set_decode_offsets(
            init_decode_cache(dalle, p, b, cache_format="paged"), jnp.zeros((b,), jnp.int32)
        ), params)

    keys = jax.eval_shape(lambda: jnp.stack([jax.random.key(0)] * B))
    k_img = max(int((1 - 0.9) * dalle.total_tokens), 1)
    vec = jax.ShapeDtypeStruct((B,), jnp.int32)
    report(f"decode step, {B} slots", eng._decode_jit.lower(
        dalle, on(device, params), on(device, cache_of(B)), on(device, vec),
        on(device, vec), on(device, keys), k_img, 1.0,
    ))
    text = jax.ShapeDtypeStruct((1, dalle.text_len_internal), jnp.int32)
    key1 = jax.eval_shape(lambda: jax.random.key(0))
    report("monolithic prefill, batch 1", eng._prefill_jit.lower(
        dalle, on(device, params), on(device, cache_of(1)), on(device, text),
        on(device, key1), k_img, 1.0,
    ))


def train(device, config):
    import optax
    from jax.sharding import Mesh
    from dalle_pytorch_tpu.parallel.mesh import AXIS_NAMES, MeshRuntime
    from dalle_pytorch_tpu.parallel.step import TrainState, make_train_step
    import numpy as np

    cfg = costs.load_config(config)
    mix = traffic.load("job-b8-synthimg")
    b = mix["batch"]
    dalle = build_dalle(cfg)
    runtime = MeshRuntime(mesh=Mesh(np.asarray([device]).reshape((1,) * 6), AXIS_NAMES))
    optimizer = optax.chain(optax.clip_by_global_norm(0.5), optax.scale_by_adam())
    params = param_shapes(dalle, cfg)
    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32), params=params,
        opt_state=jax.eval_shape(optimizer.init, params),
        skipped=jax.ShapeDtypeStruct((), jnp.int32),
        consec_skipped=jax.ShapeDtypeStruct((), jnp.int32),
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(runtime.mesh, P())
    shardings = jax.tree_util.tree_map(lambda _: rep, state)

    def loss_fn(p, batch, rng):
        return dalle.apply({"params": p}, batch["text"], batch["image"],
                           return_loss=True, deterministic=True, rngs={"dropout": rng})

    step = make_train_step(loss_fn, optimizer, runtime, shardings, dynamic_lr=True)
    place = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), tree)
    batch = {"text": jax.ShapeDtypeStruct((b, cfg["text_seq_len"]), jnp.int32),
             "image": jax.ShapeDtypeStruct((b, costs.image_len(cfg)), jnp.int32)}
    key = jax.eval_shape(lambda: jax.random.key(0))
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    report(f"{config} train step, batch {b}", step.lower(
        place(state), place(batch), place(key), place(lr)))


if __name__ == "__main__":
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    which = sys.argv[1:] or ["serve", "train"]
    if "serve" in which:
        serve(topo.devices[0])
    for name in which:
        if name.startswith("train"):    # train or train:<configuration>
            train(topo.devices[0], name.partition(":")[2] or "dalle-d12-sparse-posemb")
