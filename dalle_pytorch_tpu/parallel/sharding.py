"""Declarative parameter/optimizer sharding — the TPU-native answer to the
reference's ``distribute()`` + DeepSpeed ZeRO registration.

The reference distributes by wrapping objects at runtime
(deepspeed_backend.py:135-163) and hand-registers shared parameters for
ZeRO-3 partitioning (dalle_pytorch.py:142-152, vae.py:185-196). Here the same
outcomes are sharding *rules*: a path-pattern table assigns each parameter a
PartitionSpec over the mesh axes, XLA/GSPMD inserts the all-gathers and
reduce-scatters, and optimizer state inherits the parameter specs — which is
exactly ZeRO: parameters and Adam moments sharded over the data-parallel
``fsdp`` axis, gathered on the fly per layer.

Tensor-parallel ("tp") rules follow the Megatron pattern the transformer was
built for: the fused qkv / FF-in projections split their *output* features,
the out / FF-down projections split their *input* features, so each pair
needs only one reduce collective — and XLA places it.
"""

from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (path regex, spec) — first match wins. Paths look like
# "transformer/attn_0/fn/fn/to_qkv/kernel" for named projections and
# "transformer/ff_0/fn/fn/fn/Dense_0/kernel" for the flax-auto-named
# feed-forward projections (LayerScale(PreNorm(PreShiftToken(FeedForward)))
# wraps them in anonymous `fn` attributes, so the FeedForward class name
# never appears in the path). int8 serving renames Dense_i -> QuantDense_i
# and kernel -> kernel_q (ops/layers.py:QuantDense); the patterns cover
# both so tensor-parallel serving keeps the Megatron layout. A SwiGLU
# feed-forward (models/lm.py) has the same two Dense names. The 1-D
# bias/scale leaves fall through to the fallback and replicate, which GSPMD
# reshards for free.
DEFAULT_RULES: Tuple[Tuple[str, P], ...] = (
    # attention: qkv splits heads (output dim) over tp, out-proj splits input
    (r"to_qkv/kernel(_q)?$", P("fsdp", "tp")),
    (r"to_out/kernel(_q)?$", P("tp", "fsdp")),
    # grouped-KV attention (ops/attention.py:GroupedKVAttention): q and the
    # fused k|v split their output features like to_qkv; to_out is above
    # (ops/attention.py:GatedAttention keeps k and v apart: the same layout)
    (r"to_(q|k|v|kv)/kernel$", P("fsdp", "tp")),
    # latent attention (ops/attention.py:LatentAttention): the expansions
    # split their heads (output features) like to_q; the compressions' small
    # outputs (a latent, and the one rotary key every head shares) stay whole
    # across tp, since a norm runs over each; to_out is above
    (r"to_(q|kv)_b/kernel$", P("fsdp", "tp")),
    (r"to_(q|kv)_a/kernel$", P("fsdp", None)),
    # state-space mixer (ops/ssm.py): the same pair, in splits its output
    # channels (z | x B C | dt), out its input channels
    (r"in_proj/kernel$", P("fsdp", "tp")),
    (r"out_proj/kernel$", P("tp", "fsdp")),
    # gated delta rule (ops/gdn.py): q | k | v | z split their output channels
    # like in_proj, out_proj is above; the 2 x heads columns of b | a stay whole
    (r"in_proj_qkvz/kernel$", P("fsdp", "tp")),
    (r"in_proj_ba/kernel$", P("fsdp", None)),
    # the delta rule with a per-channel decay (ops/kda.py): q | k | v split
    # their output channels like in_proj_qkvz, the per-head write strength
    # stays whole like b | a; the low-rank gates' first factors (hidden ->
    # head width) stay whole across tp, their second split their output
    # channels, the log-decay's bias is a fallback vector
    (r"in_proj_qkv/kernel$", P("fsdp", "tp")),
    (r"in_proj_b/kernel$", P("fsdp", None)),
    (r"(f|g)_a/kernel$", P("fsdp", None)),
    (r"(f|g)_b/kernel$", P(None, "tp")),
    # MoE experts: expert dim over ep, hidden over tp (ops/moe.py)
    # (RoutedExperts holds the experts of its own range: the same two leaves
    # at the same places; its router, its selection bias and its count of
    # pairs stay whole)
    (r"experts_in$", P("ep", "fsdp", "tp")),
    (r"experts_out$", P("ep", "tp", "fsdp")),
    (r"gate/kernel$", P(None, None)),
    (r"(e_score_correction_bias|tokens_per_expert|router_prob)$", P(None)),
    (r"shared_gate/kernel$", P(None, None)),
    (r"spatial_weight$", P(None, None)),
    # GEGLU FF / gMLP channel projections: up-projection splits hidden over
    # tp, down-projection splits input — matched by position inside any
    # ff_i / attn_i (gMLP) / FeedForward_i (CLIP) block
    (r"(ff|attn|FeedForward|GMLPBlock)_\d+(/\w+)*/(Quant)?Dense_0/kernel(_q)?$",
     P("fsdp", "tp")),
    (r"(ff|attn|FeedForward|GMLPBlock)_\d+(/\w+)*/(Quant)?Dense_1/kernel(_q)?$",
     P("tp", "fsdp")),
    # vocab-sized tensors: shard the vocab dim over fsdp, features over tp;
    # int8 serving renames embedding -> embedding_q with a per-row scale
    # that shards along the same vocab dim
    (r"(text_emb|image_emb|tok_emb)/embedding(_q)?$", P("fsdp", "tp")),
    (r"(text_emb|image_emb)/scale$", P("fsdp")),
    (r"to_logits/kernel(_q)?$", P("fsdp", "tp")),
    # an untied head (models/lm.py) lies as the embedding does, (vocab, dim);
    # the MTP module's 2 dim -> dim projection splits its output features
    (r"lm_head$", P("fsdp", "tp")),
    (r"eh_proj/kernel$", P("fsdp", "tp")),
    # CLIP latent projections
    (r"to_(text|visual)_latent/kernel$", P("fsdp", "tp")),
    # VAE convs: shard output channels over tp when large
    (r"(Conv|ConvTranspose)_\d+/kernel$", P(None, None, None, "tp")),
    (r"codebook/embedding$", P("fsdp", None)),
)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _fits(shape: Sequence[int], spec: P, mesh: Mesh) -> bool:
    for dim, names in zip(shape, spec):
        if names is None:
            continue
        names = (names,) if isinstance(names, str) else names
        extent = int(np.prod([mesh.shape.get(a, 1) for a in names]))
        if dim % extent != 0:
            return False
    return True


def _fsdp_fallback(shape: Sequence[int], mesh: Mesh, min_size: int) -> P:
    """No explicit rule: shard the largest divisible axis over fsdp (ZeRO
    param partitioning), replicate small tensors."""
    fsdp = mesh.shape.get("fsdp", 1)
    if fsdp == 1 or int(np.prod(shape)) < min_size:
        return P()
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if shape[i] % fsdp == 0:
            spec = [None] * len(shape)
            spec[i] = "fsdp"
            return P(*spec)
    return P()


def _spec_shards(spec: P, mesh: Mesh) -> bool:
    """True when the spec actually splits data on this mesh (some axis with
    extent > 1) — a P("fsdp") on an fsdp=1 mesh shards nothing."""
    for names in spec:
        if names is None:
            continue
        names = (names,) if isinstance(names, str) else names
        if int(np.prod([mesh.shape.get(a, 1) for a in names])) > 1:
            return True
    return False


def spec_report(
    path: str,
    shape: Sequence[int],
    mesh: Mesh,
    rules: Tuple[Tuple[str, P], ...] = DEFAULT_RULES,
    min_size: int = 2**14,
) -> dict:
    """How the rule engine resolved one parameter — the audit seam the
    sharding lint stage (tools/lint/shard/, DTL15x) reads.

    Returns ``{"path", "rule", "requested", "spec", "intent_sharded",
    "sharded"}``: ``rule`` is the matched pattern (None = fallback),
    ``requested`` the rule's spec BEFORE divisibility degradation,
    ``spec`` the final answer ``partition_spec`` returns,
    ``intent_sharded`` whether the rule meant to split data on this mesh
    and ``sharded`` whether the final spec still does. ``intent_sharded
    and not sharded`` is exactly the DTL153 accidental-replication case:
    the declared memory story is fiction for this parameter."""
    for pattern, spec in rules:
        if re.search(pattern, path):
            spec = P(*(list(spec) + [None] * (len(shape) - len(spec)))[: len(shape)])
            requested = spec
            if not _fits(shape, spec, mesh):
                # drop non-dividing axes, keep the rest of the rule
                fixed = []
                for dim, names in zip(shape, spec):
                    if names is None:
                        fixed.append(None)
                        continue
                    tup = (names,) if isinstance(names, str) else names
                    extent = int(np.prod([mesh.shape.get(a, 1) for a in tup]))
                    fixed.append(names if dim % extent == 0 else None)
                spec = P(*fixed)
            return {
                "path": path,
                "rule": pattern,
                "requested": requested,
                "spec": spec,
                "intent_sharded": _spec_shards(requested, mesh),
                "sharded": _spec_shards(spec, mesh),
            }
    spec = _fsdp_fallback(shape, mesh, min_size)
    return {
        "path": path,
        "rule": None,
        "requested": spec,
        "spec": spec,
        "intent_sharded": _spec_shards(spec, mesh),
        "sharded": _spec_shards(spec, mesh),
    }


def partition_spec(
    path: str,
    shape: Sequence[int],
    mesh: Mesh,
    rules: Tuple[Tuple[str, P], ...] = DEFAULT_RULES,
    min_size: int = 2**14,
) -> P:
    """The PartitionSpec for one parameter. Rules that don't divide the shape
    degrade gracefully: offending axes are dropped from the spec."""
    return spec_report(path, shape, mesh, rules, min_size)["spec"]


def params_shardings(
    params: Any,
    mesh: Mesh,
    rules: Tuple[Tuple[str, P], ...] = DEFAULT_RULES,
    min_size: int = 2**14,
) -> Any:
    """Pytree of NamedSharding matching ``params``."""

    def spec_for(path, leaf):
        spec = partition_spec(_path_str(path), leaf.shape, mesh, rules, min_size)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def params_spec_reports(
    params: Any,
    mesh: Mesh,
    rules: Tuple[Tuple[str, P], ...] = DEFAULT_RULES,
    min_size: int = 2**14,
) -> list:
    """One :func:`spec_report` per parameter leaf, in tree-flatten order —
    the same order the leaves appear as flattened jit arguments, which is
    how the sharding audit joins intent (this list) with the lowered
    program's actual per-argument shardings."""
    out = []

    def report(path, leaf):
        out.append(spec_report(_path_str(path), leaf.shape, mesh, rules,
                               min_size))
        return leaf

    jax.tree_util.tree_map_with_path(report, params)
    return out


def opt_state_shardings(opt_state: Any, params_shardings_tree: Any, mesh: Mesh) -> Any:
    """Optimizer-state shardings: any leaf shaped like a parameter (Adam
    moments) inherits that parameter's sharding — ZeRO optimizer-state
    partitioning for free; scalars (step counts) replicate."""
    replicated = NamedSharding(mesh, P())
    params_struct = jax.tree_util.tree_structure(params_shardings_tree)

    # optax states are nested (named)tuples that embed param-shaped subtrees;
    # substitute the params sharding tree wherever the structure matches,
    # replicate everything else (step counters etc.)
    def map_state(state):
        if jax.tree_util.tree_structure(state) == params_struct:
            return params_shardings_tree
        if hasattr(state, "_fields"):  # namedtuple
            return type(state)(**{f: map_state(getattr(state, f)) for f in state._fields})
        if isinstance(state, (tuple, list)):
            return type(state)(map_state(s) for s in state)
        return jax.tree_util.tree_map(lambda _: replicated, state)

    return map_state(opt_state)


def shard_pytree(tree: Any, shardings: Any) -> Any:
    """Place a host pytree onto the mesh with the given shardings."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), tree, shardings
    )
