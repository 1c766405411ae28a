"""The repo's shard-audit entry points: six mesh kinds + the serving jits.

This module — like the trace registry it is modeled on — IMPORTS the
package, because its job is to build the REAL programs production runs:

* ``make_train_step`` (donated state, NaN guard on, the canonical
  weighted-CE loss) lowered under the six mesh kinds from
  ``parallel/mesh.py`` (``dp``/``fsdp``/``tp``/``sp``/``pp``/``ep``)
  as seven entries — ``sp`` lowers twice, ring path and dual-balanced
  block-sparse path — each a 2-extent axis over the first two
  host-platform devices —
  abstract lowering plus one host-CPU compile per mesh, no TPU
  anywhere. The model is the trace stage's canonical config, varied only
  where an axis demands structure (``sp`` needs a ring-splittable
  sequence and an axial pattern, ``pp`` a pipeline axis, ``ep`` Switch-
  MoE feed-forwards) — the same variations the 8-device MULTICHIP
  dryrun proves bit-exact;
* every ``serving.*`` jit the TRACE registry declares, lowered as-is
  under its current 1-device placement. Their contract entries commit
  the "no collectives in serving" baseline that ROADMAP item 1
  (pjit-sharded replicas) will consciously renegotiate: the day a psum
  lands in a serving jit, DTL151 fires until the budget is re-emitted
  and reviewed.

Expected shardings come from ``parallel/sharding.py`` itself
(``params_shardings`` / ``opt_state_shardings`` / ``spec_report``) so
the committed contract tracks the rule engine, not a transcription of
it. Axis extents are 2 on purpose: collective COUNTS are structural
(they scale with program shape, not axis extent), and 2-device meshes
keep the audit fast-tier safe.
"""

from __future__ import annotations

from typing import Dict, List

from lint.trace.registry import CANON_MODEL
from lint.shard.types import ShardEntry

_STEP_PATH = "dalle_pytorch_tpu/parallel/step.py"

# per-mesh-kind model variation: an axis only exercises its collectives
# when the model has the structure the axis shards (mirrors the
# __graft_entry__.py dryrun configs). Rows are (entry_name, axis,
# model_kw, moe) — entry_name diverges from the axis when one axis is
# audited under more than one model structure: ``sp`` lowers twice,
# once on the ring path (full+axial_row) and once on the dual-balanced
# block-sparse path (axial_row+sparse), because the two paths have
# different collective contracts (permutes vs all-gathers).
MESH_KINDS = (
    ("dp", "dp", {}, False),
    ("fsdp", "fsdp", {}, False),
    ("tp", "tp", {}, False),
    ("sp", "sp", dict(attn_types=("full", "axial_row"), sp_axis="sp",
                      text_seq_len=8, image_fmap_size=4), False),
    ("sp_sparse", "sp", dict(attn_types=("axial_row", "sparse"),
                             sp_axis="sp", text_seq_len=8,
                             image_fmap_size=4), False),
    ("pp", "pp", dict(pp_axis="pp"), False),
    ("ep", "ep", dict(ff_experts=4, moe_every=1), True),
)


def _flat_paths_and_specs(tree, shardings):
    """Flattened (keystr path, expected HLO sharding string) pairs for an
    abstract arg/out pytree and its matching sharding pytree."""
    import jax

    from lint.shard.audit import hlo_sharding_str

    path_leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    sh_leaves = jax.tree_util.tree_flatten(shardings)[0]
    paths = [jax.tree_util.keystr(kp) for kp, _ in path_leaves]
    expected = [
        hlo_sharding_str(s, len(leaf.shape))
        for (kp, leaf), s in zip(path_leaves, sh_leaves)
    ]
    return paths, expected


def _train_shard_entry(
    name: str, kind: str, model_kw: Dict, moe: bool
) -> ShardEntry:
    """One mesh kind: the full sharded train step, lowered lazily."""
    import jax
    import jax.numpy as jnp
    import optax

    from dalle_pytorch_tpu.models import DALLE
    from dalle_pytorch_tpu.parallel.mesh import make_runtime
    from dalle_pytorch_tpu.parallel.sharding import (
        opt_state_shardings,
        params_shardings,
        params_spec_reports,
    )
    from dalle_pytorch_tpu.parallel.step import TrainState, make_train_step
    from jax.sharding import NamedSharding, PartitionSpec as P

    SDS = jax.ShapeDtypeStruct
    cfg = dict(CANON_MODEL)
    cfg.update(model_kw)
    dalle = DALLE(**cfg)
    devices = jax.devices()
    if len(devices) < 2:
        raise ValueError(
            "the shard audit needs >= 2 host devices — run through "
            "tools/lint.py --shard (it forces an 8-device host platform) "
            "or set XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    runtime = make_runtime(devices=devices[:2], **{kind: 2})
    optimizer = optax.adam(1e-3)

    if moe:
        def loss_fn(params, batch, rng):
            out, mut = dalle.apply(
                {"params": params}, batch[0], batch[1],
                return_loss=True, mutable=["moe_aux"],
            )
            aux = sum(jax.tree_util.tree_leaves(mut.get("moe_aux", {})),
                      jnp.zeros((), jnp.float32))
            return out + 1e-2 * aux
    else:
        def loss_fn(params, batch, rng):
            return dalle.apply(
                {"params": params}, batch[0], batch[1], return_loss=True
            )

    batch = 2  # divisible by every 2-extent data axis
    text = SDS((batch, dalle.text_seq_len), jnp.int32)
    image = SDS((batch, dalle.image_seq_len), jnp.int32)
    params = jax.eval_shape(
        lambda t, i: dalle.init(jax.random.key(0), t, i), text, image
    )["params"]
    opt_state = jax.eval_shape(optimizer.init, params)
    i32 = SDS((), jnp.int32)
    state = TrainState(
        step=i32, params=params, opt_state=opt_state,
        skipped=i32, consec_skipped=i32,
    )
    p_shard = params_shardings(params, runtime.mesh)
    replicated = NamedSharding(runtime.mesh, P())
    shardings = TrainState(
        step=replicated, params=p_shard,
        opt_state=opt_state_shardings(opt_state, p_shard, runtime.mesh),
        skipped=replicated, consec_skipped=replicated,
    )
    train_step = make_train_step(
        loss_fn, optimizer, runtime, shardings, donate=True
    )
    key = jax.eval_shape(lambda: jax.random.key(0))
    args = (state, (text, image), key)
    in_sh = (shardings,
             (runtime.data_sharding, runtime.data_sharding), replicated)
    out_avals = jax.eval_shape(train_step, *args)
    out_sh = (shardings, replicated)

    arg_paths, in_expected = _flat_paths_and_specs(args, in_sh)
    out_paths, out_expected = _flat_paths_and_specs(out_avals, out_sh)

    # parameter leaves sit right after TrainState.step in the flattened
    # argument list (NamedTuple field order) — assert instead of trusting
    n_params = len(jax.tree_util.tree_leaves(params))
    assert arg_paths[1].endswith(
        jax.tree_util.keystr(
            jax.tree_util.tree_flatten_with_path(params)[0][0][0]
        )
    ), "TrainState flatten order changed — fix the param arg offsets"
    intents = []
    for i, rep in enumerate(params_spec_reports(params, runtime.mesh)):
        rep = dict(rep)
        rep["arg"] = 1 + i
        intents.append(rep)

    return ShardEntry(
        name=f"train.{name}",
        path=_STEP_PATH,
        symbol="make_train_step",
        mesh_axes={kind: 2},
        lower=lambda: train_step.lower(*args),
        partitioned=True,
        arg_paths=arg_paths,
        in_shardings=in_expected,
        out_paths=out_paths,
        out_shardings=out_expected,
        param_intents=tuple(intents),
    )


def build_train_entries() -> List[ShardEntry]:
    """The seven mesh-kind train entries alone — the multichip dryrun's
    provenance cross-check audits exactly these (__graft_entry__.py)."""
    return [
        _train_shard_entry(name, kind, model_kw, moe)
        for name, kind, model_kw, moe in MESH_KINDS
    ]


def build_serving_entries() -> List[ShardEntry]:
    """Every ``serving.*`` jit the trace registry declares, lowered as-is
    (signature 0 — collective structure is signature-independent, the
    same rationale as the trace stage's donation audit)."""
    from lint.trace.registry import build_entry_points as trace_entries

    out: List[ShardEntry] = []
    for ep in trace_entries():
        if not ep.name.startswith("serving.") or ep.lower is None:
            continue
        sig = ep.signatures[0]
        out.append(ShardEntry(
            name=ep.name,
            path=ep.path,
            symbol=ep.symbol,
            mesh_axes={},
            lower=(lambda ep=ep, sig=sig: ep.lower(*sig.args)),
            partitioned=False,
        ))
    return out


def build_entry_points() -> List[ShardEntry]:
    return build_train_entries() + build_serving_entries()
