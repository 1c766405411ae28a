"""Training cells of a KDA / latent-attention / routed-expert language model
(``kimi_linear``): per step exactly what ``train_lm.py``'s loop does, as
``drivers/train_lm.py`` (the packed batch, the window, the norms and the
comparison are IMPORTED from there, the window's routing facts, the first
gradient's element-by-element distance, the selection bias's entries apart
and the pair counts from ``drivers/train_moe.py``); what differs is written
here: the weights (``weights_kda.py``), the reference (``reference_kda.py``:
the delta rule with its per-channel decay as the sequential recurrence, every
query block of the latent attention against every key under a dense mask, the
experts as a dense loop over the held ones, the selection bias moved against
every step's loads) and the program's own count of the pairs (every expert
layer's ``tokens_per_expert``, copied out after EVERY step).

The two leaves no optimizer writes (``e_score_correction_bias``,
``tokens_per_expert``) are left out of the parameters' change and the
selection bias is held by ``selection_bias_entries_apart``, as the JoyAI
cell's.

Controls (``--control``; none is a measurement): ``fp8``, ``half_batch`` and
``mean_gate`` put the reference in the program's place, one precision down,
with the second half of the row's positions left out of the loss, or with
each head's per-channel log-decay replaced by its mean over the channels (the
scalar decay of a Gated DeltaNet: what the comparison must see of the
mechanism this configuration adds).
"""

from __future__ import annotations

import gc
import json
import time

import numpy as np

from .. import harness, reference_kda, weights_kda
from . import train_lm as lm_driver
from .train_lm import ADAM_B1, _flat, _norms, _reducers, _window, compare, packed_batch
from .train_moe import _biases, _distance, _entries_apart, _pairs, _routing

REFERENCE_CONTROLS = ("fp8", "half_batch", "mean_gate")
BUFFERS = ("e_score_correction_bias", "tokens_per_expert")


def _trained(norms: dict) -> dict:
    return {leaf: v for leaf, v in norms.items() if not leaf.endswith(BUFFERS)}


def _sizes(ctx) -> None:
    """The rehearsal's own sizes over the cell's (``run.py`` merges only
    ``rehearsal.json``, which knows no language model)."""
    if ctx.rehearsal:
        tiny = json.loads((harness.HERE / "rehearsal_kda.json").read_text())
        ctx.cfg = {**ctx.cfg, **tiny["config"]}
        ctx.mix = {**ctx.mix, **tiny["traffic"]}
        ctx.facts["limits"] = {**ctx.facts["limits"], **tiny["limits"]}


class Job(lm_driver.Job):
    """``drivers/train_lm.py``'s job with this family's weights, and after
    every step a copy of what the step sent every expert of every expert layer
    (as ``drivers/train_moe.py``'s: one small program more a step, read on the
    host only after the window)."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import train_lm
        from dalle_pytorch_tpu.parallel import make_runtime

        self.ctx, mix = ctx, ctx.mix
        self.runtime = make_runtime(devices=jax.local_devices()[: ctx.chips], **mix["mesh"])
        self.lm, self.shapes = lm_driver._build(ctx)
        params = weights_kda.make_params(self.shapes, ctx.seed, jnp.float32)
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
        lo, hi = reference_kda.held_range(self.ctx.cfg)
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
            minus=lambda p, x: weights_kda.make_leaf(p, x.shape, ctx.seed, jnp.float32),
        ))
        return out


def run(ctx) -> None:
    from dalle_pytorch_tpu.ops.kda import KimiDeltaAttention  # noqa: F401  a program without it fails here, at once

    _sizes(ctx)
    if ctx.control is not None:
        _reference_control(ctx)
        return
    job = Job(ctx)
    program = job.first_steps()
    ctx.facts["setup_s"] = time.monotonic() - ctx.process_start
    _window(ctx, job)
    _routing(ctx, job)
    fed, shapes = job.fed, job.shapes
    job.state = None
    del job
    gc.collect()
    ref = reference_steps(ctx, shapes, fed, first_gradient=program.pop("grad_leaves"))
    compare(ctx, program, ref)
    _distance(ctx, ref)
    _entries_apart(ctx, program["bias"], ref["bias"])
    _pairs(ctx, program["pairs"], ref["pairs"])


def _reference_control(ctx) -> None:
    """The reference one precision down (``fp8``), with the second half of the
    row's positions left out of the loss (``half_batch``) or with the mean of
    each head's log-decay over its channels (``mean_gate``) stands in the
    program's place. No window."""
    if ctx.control not in REFERENCE_CONTROLS:
        raise SystemExit(f"no control {ctx.control!r}: {REFERENCE_CONTROLS}")
    _, shapes = lm_driver._build(ctx)
    mix = ctx.mix
    fed = [packed_batch(mix, ctx.cfg, ctx.seed, step) for step in range(int(mix["check_steps"]))]
    ctx.facts["setup_s"] = time.monotonic() - ctx.process_start
    stand_in = {
        "fp8": dict(mode="fp8"),
        "half_batch": dict(positions=int(mix["tokens"]) // 2),
        "mean_gate": dict(gate="mean"),
    }[ctx.control]
    stand_in = reference_steps(ctx, shapes, fed, keep_gradient=True, **stand_in)
    ref = reference_steps(ctx, shapes, fed, first_gradient=stand_in.pop("grad_leaves"))
    ctx.attempted = len(fed)
    compare(ctx, stand_in, ref)
    _distance(ctx, ref)
    _entries_apart(ctx, stand_in["bias"], ref["bias"])


def reference_steps(ctx, shapes, fed: list, mode: str = "f32", positions=None, gate="channel",
                    first_gradient=None, keep_gradient=False) -> dict:
    """The plain reference through the first steps, as
    ``drivers/train_moe.py:reference_steps``: float32 weights from the seed,
    the gradient a row at a time, global-norm clip, Adam with its moments on
    the host; after each step the selection bias moved against the step's
    loads (``reference_kda.balance``), and each step's count of pairs routed
    to experts held here. ``first_gradient``, ``keep_gradient``: as there."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util

    mix, cfg = ctx.mix, ctx.cfg
    params = weights_kda.make_params(shapes, ctx.seed, jnp.float32)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, ids: reference_kda.loss(p, cfg, ids, mode, positions, gate), has_aux=True,
    ))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    clip = jax.jit(lambda g, n: reference_kda.clip_by_global_norm(
        jax.tree_util.tree_map(lambda x: x / n, g), float(mix["clip_grad_norm"])
    ))

    def leaf_update(p, g, m, v, count):
        delta, m, v = reference_kda.adam_update(g, m, v, count, float(mix["learning_rate"]))
        return p + delta, m, v

    update = jax.jit(leaf_update, static_argnums=(4,), donate_argnums=(0,))
    moments = {
        path: (np.zeros(x.shape, np.float32), np.zeros(x.shape, np.float32))
        for path, x in _flat(shapes).items()
    }
    lo, hi = reference_kda.held_range(cfg)
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
        reference_kda.balance(flat_p, loads, float(cfg["bias_update_speed"]))
        params = traverse_util.unflatten_dict(flat_p)
    out["bias"] = _biases(_flat(params))
    out["change"] = _trained(_norms(
        _flat(params), minus=lambda p, x: weights_kda.make_leaf(p, x.shape, ctx.seed, jnp.float32)
    ))
    return out
