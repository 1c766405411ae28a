"""Training cells of a latent-attention / routed-expert language model: per
step exactly what ``train_lm.py``'s loop does, as ``drivers/train_lm.py`` (the
packed batch, the window, the norms and the comparison are IMPORTED from
there); what differs is written here: the weights (``weights_moe.py``), the
reference (``reference_moe.py``, which also counts the pairs every expert
layer sent each expert and moves the selection bias against them after every
step, as the program's step does), the program's own count of those pairs
(every expert layer's ``tokens_per_expert``, copied out after EVERY step: the
cost file's pair count is that of the window's own steps, and the compared
steps' count beside the reference's is the proof that no pair is dropped) and
the faults.

One number is compared that the other training cells do not have,
``grad_worst_leaf_distance``: the first gradient as Adam got it against the
reference's, ELEMENT by element (the norm of the difference of a leaf, against
the reference's norm of that leaf or of the median leaf, whichever is larger;
the worst leaf). The norms alone cannot see a fault that turns a gradient
without changing its length: at the seed's weights a rotary table rounded to
bfloat16 or the MTP module fed the wrong token's embedding move no leaf's
NORM by more than the program's own rounding does. The program's first
gradient waits on the host (3.15 GB) until the reference has its own.

And one number for the two leaves that no optimizer writes,
``selection_bias_entries_apart``: the share of (layer, expert) entries whose
selection bias after the compared steps lies more than half a speed from the
reference's. Each entry moves by a whole speed a step, up or down by the SIGN
of its expert's load against the mean, and the program's loads are not the
reference's to the pair (its router reads bfloat16 activations): an expert
within a pair or two of the mean goes the other way, a few entries in a
hundred. By the NORM of the leaf's change those few read percents, beside
the trained leaves' hundredths of a percent, so ``e_score_correction_bias``
and ``tokens_per_expert`` are left out of ``param_change_worst_leaf_gap`` and
held by this count instead.

Controls (``--control``; none is a measurement): ``fp8`` and ``half_tokens``
put the reference, one precision down or with the second half of every row's
positions left out of both losses, in the program's place. The others plant a
fault in the PROGRAM from outside (it has no option for any) and run its
first steps: ``renorm_held_only`` (the weights normalised over the experts
held here only), ``bias_in_weights`` (the selection bias added to the weights
and not only to the choice), ``drop_overflow`` (an expert keeps 1.25 x the
mean expert's pairs and drops the rest), ``bf16_rope`` (the rotary angles
rounded to bfloat16 before their cosine and sine), ``mtp_unshifted`` (the MTP
module given the embedding of token i for that of token i + 1), ``bias_held``
(the selection bias never moved: the program as it stood before it ran the
family's rule).
"""

from __future__ import annotations

import contextlib
import gc
import json
import time

import numpy as np

from .. import harness, reference_moe, weights_moe
from . import train_lm as lm_driver
from .train_lm import ADAM_B1, _flat, _norms, _reducers, _window, compare, packed_batch

PROGRAM_FAULTS = (
    "renorm_held_only", "bias_in_weights", "drop_overflow", "bf16_rope", "mtp_unshifted",
    "bias_held",
)
BUFFERS = ("e_score_correction_bias", "tokens_per_expert")


def _trained(norms: dict) -> dict:
    return {leaf: v for leaf, v in norms.items() if not leaf.endswith(BUFFERS)}


def _biases(flat_params: dict) -> dict:
    return {"/".join(p): np.asarray(x) for p, x in flat_params.items() if p[-1] == BUFFERS[0]}

REFERENCE_CONTROLS = ("fp8", "half_tokens")


def _sizes(ctx) -> None:
    """The rehearsal's own sizes over the cell's (``run.py`` merges only
    ``rehearsal.json``, which knows no language model)."""
    if ctx.rehearsal:
        tiny = json.loads((harness.HERE / "rehearsal_moe.json").read_text())
        ctx.cfg = {**ctx.cfg, **tiny["config"]}
        ctx.mix = {**ctx.mix, **tiny["traffic"]}
        ctx.facts["limits"] = {**ctx.facts["limits"], **tiny["limits"]}


class Job(lm_driver.Job):
    """``drivers/train_lm.py``'s job with this family's weights, and after
    every step a copy of what the step sent every expert of every expert layer
    (the step wrote it into the state; the copy is one small program more a
    step, read on the host only after the window)."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import train_lm
        from dalle_pytorch_tpu.parallel import make_runtime

        self.ctx, mix = ctx, ctx.mix
        self.runtime = make_runtime(devices=jax.local_devices()[: ctx.chips], **mix["mesh"])
        self.lm, self.shapes = lm_driver._build(ctx)
        params = weights_moe.make_params(self.shapes, ctx.seed, jnp.float32)
        self.state, _, self.step_fn = train_lm.build_step(
            self.lm, params, self.runtime, float(mix["clip_grad_norm"]),
        )
        del params
        layers = sorted(p for p in _flat(self.shapes) if p[-1] == "tokens_per_expert")
        self._sent = jax.jit(lambda params: jnp.stack([_flat(params)[p] for p in layers]))
        self.lr = jnp.asarray(float(mix["learning_rate"]))
        self.steps = 0
        self.fed = []
        self.sent = []     # a step: (expert layers, ALL experts) pairs, on the device

    def dispatch(self, ids: np.ndarray, keep: bool = False):
        loss = super().dispatch(ids, keep)
        self.sent.append(self._sent(self.state.params))
        return loss

    def held(self) -> np.ndarray:
        """(steps, expert layers): the pairs sent to the experts held here."""
        lo, hi = reference_moe.held_range(self.ctx.cfg)
        return np.stack([np.asarray(x) for x in self.sent])[:, :, lo:hi].sum(axis=-1)

    def first_steps(self) -> dict:
        import jax.numpy as jnp

        ctx = self.ctx
        out = {"loss": []}
        for step in range(int(ctx.mix["check_steps"])):
            out["loss"].append(float(self.dispatch(self.host_batch(step), keep=True)))
            if step == 0:
                mu = _flat(self.state.opt_state[1].mu)
                out["grad"] = {k: v / (1 - ADAM_B1) for k, v in _norms(mu).items()}
                out["grad_leaves"] = {
                    "/".join(p): np.asarray(x, np.float32) / (1 - ADAM_B1) for p, x in mu.items()
                }
        out["pairs"] = [int(n) for n in self.held().sum(axis=1)]
        out["bias"] = _biases(_flat(self.state.params))
        out["change"] = _trained(_norms(
            _flat(self.state.params),
            minus=lambda p, x: weights_moe.make_leaf(p, x.shape, ctx.seed, jnp.float32),
        ))
        return out


def run(ctx) -> None:
    from dalle_pytorch_tpu.ops.moe import RoutedExperts  # noqa: F401  a program without it fails here, at once

    _sizes(ctx)
    if ctx.control in REFERENCE_CONTROLS:
        _reference_control(ctx)
        return
    with _planted(ctx):
        job = Job(ctx)
        program = job.first_steps()
    ctx.facts["setup_s"] = time.monotonic() - ctx.process_start
    if ctx.control is None:
        _window(ctx, job)
    else:
        ctx.attempted = job.steps
    _routing(ctx, job)
    fed, shapes = job.fed, job.shapes
    job.state = None
    del job
    gc.collect()
    ref = reference_steps(ctx, shapes, fed, "f32", first_gradient=program.pop("grad_leaves"))
    compare(ctx, program, ref)
    _distance(ctx, ref)
    _entries_apart(ctx, program["bias"], ref["bias"])
    _pairs(ctx, program["pairs"], ref["pairs"])


def _routing(ctx, job) -> None:
    """What the expert layers were sent, from every step's own count. The
    cost file counts the routed experts' work by the WINDOW's steps (the
    compared ones' where a control has no window); shown beside it, a layer at
    a time: the held experts' pairs at the window's start, middle and end, and
    the fullest expert over the mean expert, the worst layer of the worst
    step."""
    sent = np.stack([np.asarray(x) for x in job.sent])           # (steps, layers, experts)
    held = job.held()
    first = int(ctx.mix["check_steps"]) if ctx.control is None else 0
    sent, held = sent[first:], held[first:]
    ctx.facts["moe_pairs_per_step"] = float(held.sum(axis=1).mean())
    for name, step in (("start", 0), ("middle", len(held) // 2), ("end", len(held) - 1)):
        ctx.facts[f"moe_held_pairs_a_layer_{name}_not_compared"] = [int(n) for n in held[step]]
    ctx.facts["moe_held_pairs_a_layer_least_most_not_compared"] = [int(held.min()), int(held.max())]
    ctx.facts["moe_load_max_over_mean_not_compared"] = float(
        (sent.max(axis=-1) / sent.mean(axis=-1)).max()
    )


def _distance(ctx, ref: dict) -> None:
    """The first gradient, element by element: the worst leaf's distance from
    the reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    median = float(np.median(list(ref["grad"].values())))
    gaps = {leaf: d / max(ref["grad"][leaf], median) for leaf, d in ref["grad_distance"].items()}
    worst = max(gaps, key=gaps.get)
    ctx.facts["grad_distance_worst_leaf"] = worst
    ctx.compare("grad_worst_leaf_distance", gaps[worst], ctx.facts["limits"]["grad_worst_leaf_distance"])


def _entries_apart(ctx, program: dict, ref: dict) -> None:
    """The share of (layer, expert) entries whose selection bias lies more
    than half a speed from the reference's after the compared steps."""
    half = 0.5 * float(ctx.cfg["bias_update_speed"])
    apart = np.concatenate([np.abs(program[leaf] - ref[leaf]) > half for leaf in sorted(ref)])
    ctx.compare("selection_bias_entries_apart", float(apart.mean()),
                ctx.facts["limits"]["selection_bias_entries_apart"])


def _pairs(ctx, program: list, ref: list) -> None:
    """The pairs routed to the experts held here in the compared steps, the
    program's count beside the reference's. Shown, not compared: the program's
    router reads bfloat16 activations, so a few tokens whose eighth and ninth
    scores lie within a rounding of each other choose otherwise; a DROPPED
    pair is the gradient's and the change's to catch."""
    import sys

    gap = max(abs(p - r) / r for p, r in zip(program, ref))
    ctx.facts["moe_pairs_here_gap_not_compared"] = gap
    shown = {k: v for k, v in ctx.facts.items() if k.startswith(("moe_held", "moe_load"))}
    print(f"read, not compared: moe.pairs_here {program} (reference {ref}), widest gap {gap!r}; "
          f"{shown}", file=sys.stderr)


@contextlib.contextmanager
def _planted(ctx):
    """A fault under the program while its step is traced, planted from
    outside and taken out again."""
    import jax
    import jax.numpy as jnp
    from dalle_pytorch_tpu.models import lm
    from dalle_pytorch_tpu.ops import moe, rotary

    fault, real = ctx.control, (moe.route, rotary.cos_sin, lm.next_ids, lm.balanced_bias)
    lo, hi = reference_moe.held_range(ctx.cfg)

    def renorm_held_only(scores, bias, per_token, scaling):
        chosen, _ = real[0](scores, bias, per_token, scaling)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        here = jnp.where((chosen >= lo) & (chosen < hi), picked, 0.0)
        return chosen, scaling * picked / (jnp.sum(here, axis=-1, keepdims=True) + 1e-20)

    def bias_in_weights(scores, bias, per_token, scaling):
        return real[0](scores + bias, jnp.zeros_like(bias), per_token, scaling)

    def drop_overflow(scores, bias, per_token, scaling):
        chosen, weights = real[0](scores, bias, per_token, scaling)
        tokens, experts = scores.shape
        capacity = int(1.25 * tokens * per_token / experts)
        sent = jax.nn.one_hot(chosen.reshape(-1), experts, dtype=jnp.int32)
        place = jnp.take_along_axis(jnp.cumsum(sent, axis=0), chosen.reshape(-1, 1), axis=1)
        return chosen, jnp.where(place.reshape(chosen.shape) <= capacity, weights, 0.0)

    def bf16_rope(angle_table, dtype):
        rounded = jnp.asarray(angle_table, jnp.float32).astype(jnp.bfloat16)
        return real[1](rounded.astype(jnp.float32), dtype)

    routes = {"renorm_held_only": renorm_held_only, "bias_in_weights": bias_in_weights,
              "drop_overflow": drop_overflow}
    if fault in routes:
        moe.route = routes[fault]
    elif fault == "bf16_rope":
        rotary.cos_sin = bf16_rope
    elif fault == "mtp_unshifted":
        lm.next_ids = lambda ids: ids
    elif fault == "bias_held":
        lm.balanced_bias = lambda bias, load, speed: bias
    elif fault is not None:
        raise SystemExit(f"no control {fault!r}: {REFERENCE_CONTROLS + PROGRAM_FAULTS}")
    try:
        yield
    finally:
        moe.route, rotary.cos_sin, lm.next_ids, lm.balanced_bias = real


def _reference_control(ctx) -> None:
    """The reference one precision down (``fp8``) or with the second half of
    every row's positions left out of both losses (``half_tokens``) stands in
    the program's place. No window."""
    _, shapes = lm_driver._build(ctx)
    mix = ctx.mix
    fed = [packed_batch(mix, ctx.cfg, ctx.seed, step) for step in range(int(mix["check_steps"]))]
    ctx.facts["setup_s"] = time.monotonic() - ctx.process_start
    if ctx.control == "half_tokens":
        stand_in = reference_steps(ctx, shapes, fed, "f32", positions=int(mix["tokens"]) // 2,
                                   keep_gradient=True)
    else:
        stand_in = reference_steps(ctx, shapes, fed, ctx.control, keep_gradient=True)
    ref = reference_steps(ctx, shapes, fed, "f32", first_gradient=stand_in.pop("grad_leaves"))
    ctx.attempted = len(fed)
    compare(ctx, stand_in, ref)
    _distance(ctx, ref)
    _entries_apart(ctx, stand_in["bias"], ref["bias"])


def reference_steps(ctx, shapes, fed: list, mode: str, positions=None,
                    first_gradient=None, keep_gradient=False) -> dict:
    """The plain reference through the first steps, as
    ``drivers/train_lm.py:reference_steps``: float32 weights from the seed,
    the gradient a row at a time, global-norm clip, Adam with its moments on
    the host; after each step the selection bias moved against the step's
    loads (``reference_moe.balance``), and each step's count of pairs routed to
    experts held here.
    ``first_gradient``: {leaf: host array}, what stands in the program's place
    got as its first gradient: its distance from this one is measured a leaf
    at a time (``grad_distance``). ``keep_gradient``: this one is kept on the
    host for such a comparison (``grad_leaves``)."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util

    mix, cfg = ctx.mix, ctx.cfg
    params = weights_moe.make_params(shapes, ctx.seed, jnp.float32)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, ids: reference_moe.loss(p, cfg, ids, mode, positions), has_aux=True,
    ))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    clip = jax.jit(lambda g, n: reference_moe.clip_by_global_norm(
        jax.tree_util.tree_map(lambda x: x / n, g), float(mix["clip_grad_norm"])
    ))

    def leaf_update(p, g, m, v, count):
        delta, m, v = reference_moe.adam_update(g, m, v, count, float(mix["learning_rate"]))
        return p + delta, m, v

    update = jax.jit(leaf_update, static_argnums=(4,), donate_argnums=(0,))
    moments = {
        path: (np.zeros(x.shape, np.float32), np.zeros(x.shape, np.float32))
        for path, x in _flat(shapes).items()
    }
    lo, hi = reference_moe.held_range(cfg)
    out = {"loss": [], "pairs": []}
    for step, ids in enumerate(fed):
        total, loads, grads = 0.0, {}, None
        for row in ids:
            (value, sent), g = grad_fn(params, jnp.asarray(row[None]))
            total += float(value)
            loads = {layer: loads.get(layer, 0) + np.asarray(load) for layer, load in sent.items()}
            grads = g if grads is None else add(grads, g)
        out["loss"].append(total / len(ids))
        out["pairs"].append(int(sum(load[lo:hi].sum() for load in loads.values())))
        grads = clip(grads, float(len(ids)))
        if step == 0:
            out["grad"] = _norms(_flat(grads))
            if first_gradient is not None:
                out["grad_distance"] = {
                    "/".join(p): float(_reducers()[1](g, jnp.asarray(first_gradient["/".join(p)])))
                    for p, g in sorted(_flat(grads).items())
                }
            if keep_gradient:
                out["grad_leaves"] = {"/".join(p): np.asarray(g) for p, g in _flat(grads).items()}
        flat_p, flat_g = _flat(params), _flat(grads)
        del params, grads
        for path in sorted(flat_p):
            m, v = moments[path]
            flat_p[path], m, v = update(flat_p[path], flat_g.pop(path), m, v, step + 1)
            moments[path] = (np.asarray(m), np.asarray(v))
        reference_moe.balance(flat_p, loads, float(cfg["bias_update_speed"]))
        params = traverse_util.unflatten_dict(flat_p)
    out["bias"] = _biases(_flat(params))
    out["change"] = _trained(_norms(
        _flat(params), minus=lambda p, x: weights_moe.make_leaf(p, x.shape, ctx.seed, jnp.float32)
    ))
    return out
