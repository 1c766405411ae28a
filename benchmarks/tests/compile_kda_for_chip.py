"""``compile_gdn_for_chip.py`` for the KDA / latent-attention cell, by hand:

    JAX_PLATFORMS=cpu python benchmarks/tests/compile_kda_for_chip.py [rows] [tokens] [remat 0|1]

compiles ``train_lm.py``'s step at the cell's size for a v5e that is described
and not attached, prints its ``memory_analysis()`` (arguments + temporaries:
what the step needs of the chip's 16 GB), the routes (the KDA layers'
``forward/delta_rule`` on ``kda_chunk``, the latent attention's
``forward/mla``) and how many Mosaic kernels and grouped products are in the
program. Nothing runs.
"""

from __future__ import annotations

import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import train_lm
from benchmarks import costs
from benchmarks.tests.compile_for_chip import report
from dalle_pytorch_tpu.ops import kv_policy
from dalle_pytorch_tpu.parallel.mesh import AXIS_NAMES, MeshRuntime
from dalle_pytorch_tpu.parallel.step import TrainState, make_train_step

kv_policy.on_tpu = lambda: True
CONFIG = "kimi-linear-48b-a3b-d5-ep32"


def main(rows: int, tokens: int, remat: bool) -> None:
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    cfg = costs.load_config(CONFIG)
    lm = train_lm.build_model(cfg, tokens, bf16=True, remat=remat)
    runtime = MeshRuntime(mesh=Mesh(np.asarray([topo.devices[0]]).reshape((1,) * 6), AXIS_NAMES))
    ids = jax.ShapeDtypeStruct((rows, tokens), jnp.int32)
    params = jax.eval_shape(lm.init, jax.random.key(0), ids)["params"]
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(f"{n:,} leaves' elements", flush=True)
    optimizer = optax.chain(optax.clip_by_global_norm(0.5), optax.scale_by_adam())
    zero = jax.ShapeDtypeStruct((), jnp.int32)
    state = TrainState(step=zero, params=params,
                       opt_state=jax.eval_shape(optimizer.init, params),
                       skipped=zero, consec_skipped=zero)
    rep = NamedSharding(runtime.mesh, P())
    shardings = jax.tree_util.tree_map(lambda _: rep, state)
    step = make_train_step(
        lambda p, batch, rng: lm.loss_and_loads(p, batch["ids"]),
        optimizer, runtime, shardings, dynamic_lr=True, after_update=lm.balance,
    )
    place = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), tree)
    key = jax.eval_shape(lambda: jax.random.key(0))
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    lowered = step.lower(place(state), place({"ids": ids}), place(key), place(lr))
    report(f"{CONFIG} train step, {rows} x {tokens} tokens, remat {remat}", lowered)
    text = lowered.as_text()
    print(f"in the program: {text.count('tpu_custom_call')} Mosaic kernels, "
          f"{text.count('ragged_dot')} grouped products; routes {kv_policy.ROUTE_LOG}")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 1,
         int(sys.argv[2]) if len(sys.argv) > 2 else 16384,
         bool(int(sys.argv[3])) if len(sys.argv) > 3 else True)
