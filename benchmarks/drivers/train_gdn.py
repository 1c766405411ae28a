"""Training cells of a linear-attention / gated-attention / routed-expert
language model (``qwen3_next``): per step exactly what ``train_lm.py``'s loop
does, as ``drivers/train_lm.py`` (the packed batch, the window, the norms and
the comparison are IMPORTED from there, the window's routing facts, the first
gradient's element-by-element distance and the pair counts from
``drivers/train_moe.py``); what differs is written here: the weights
(``weights_gdn.py``), the reference (``reference_gdn.py``: the delta rule as
the sequential recurrence, and a load-balance term whose ``f`` is the WHOLE
step's, so the reference counts every row's loads before it takes the
gradient a row at a time), the program's own count of the pairs (every expert
layer's ``tokens_per_expert``, copied out after EVERY step) and the faults.

The two leaves no optimizer writes (``tokens_per_expert``, ``router_prob``:
the step's counters) are left out of the parameters' change; this family has
no selection bias, so nothing stands in ``selection_bias_entries_apart``'s
place.

Controls (``--control``; none is a measurement): ``fp8`` and ``half_tokens``
put the reference, one precision down or with the second half of every row's
positions left out of the loss, in the program's place. The others plant a
fault in the PROGRAM from outside (it has no option for any) and run its
first steps: ``no_decay`` (the state never decays: g = 0), ``beta_one`` (every
corrected value written whole), ``no_qk_l2norm`` (queries and keys of the
delta rule not normalised), ``bf16_decay`` (the cumulative log-decay inside a
chunk rounded to bfloat16 before its exponentials), ``gate_off`` (the
attention's output gate left out), ``renorm_held_only`` (the experts' weights
normalised over the experts held here only), ``shared_gate_off`` (the shared
expert not multiplied by its gate).
"""

from __future__ import annotations

import contextlib
import gc
import json
import sys
import time

import numpy as np

from .. import harness, reference_gdn, weights_gdn
from . import train_lm as lm_driver
from .train_lm import ADAM_B1, _flat, _norms, _reducers, _window, compare, packed_batch
from .train_moe import _distance, _pairs, _routing

PROGRAM_FAULTS = (
    "no_decay", "beta_one", "no_qk_l2norm", "bf16_decay", "gate_off", "renorm_held_only",
    "shared_gate_off",
)
REFERENCE_CONTROLS = ("fp8", "half_tokens")
BUFFERS = ("tokens_per_expert", "router_prob")


def _trained(norms: dict) -> dict:
    return {leaf: v for leaf, v in norms.items() if not leaf.endswith(BUFFERS)}


def _sizes(ctx) -> None:
    """The rehearsal's own sizes over the cell's (``run.py`` merges only
    ``rehearsal.json``, which knows no language model)."""
    if ctx.rehearsal:
        tiny = json.loads((harness.HERE / "rehearsal_gdn.json").read_text())
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
        params = weights_gdn.make_params(self.shapes, ctx.seed, jnp.float32)
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
        lo, hi = reference_gdn.held_range(self.ctx.cfg)
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
        out["change"] = _trained(_norms(
            _flat(self.state.params),
            minus=lambda p, x: weights_gdn.make_leaf(p, x.shape, ctx.seed, jnp.float32),
        ))
        return out


def run(ctx) -> None:
    from dalle_pytorch_tpu.ops.gdn import GatedDeltaNet  # noqa: F401  a program without it fails here, at once

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
    _pairs(ctx, program["pairs"], ref["pairs"])


@contextlib.contextmanager
def _planted(ctx):
    """A fault under the program while its step is traced, planted from
    outside and taken out again."""
    import jax
    import jax.numpy as jnp
    from dalle_pytorch_tpu.ops import attention, gdn, moe

    fault = ctx.control
    lo, hi = reference_gdn.held_range(ctx.cfg)
    real_route, real_cum = moe.route, gdn.chunk_log_decay

    def renorm_held_only(scores, bias, per_token, scaling):
        chosen, _ = real_route(scores, bias, per_token, scaling)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        here = jnp.where((chosen >= lo) & (chosen < hi), picked, 0.0)
        return chosen, scaling * picked / (jnp.sum(here, axis=-1, keepdims=True) + 1e-20)

    plants = {
        "no_decay": (gdn, "log_decay", lambda a, A_log, dt_bias: jnp.zeros(a.shape, jnp.float32)),
        "beta_one": (gdn, "write_strength", lambda b: jnp.ones(b.shape, jnp.float32)),
        "no_qk_l2norm": (gdn, "l2norm", lambda x, eps=1e-6: x.astype(jnp.float32)),
        # ``reduce_precision`` and not a pair of casts: XLA erases the pair
        # (PERF.md section 7, the granite cell's fault of the same name)
        "bf16_decay": (gdn, "chunk_log_decay",
                       lambda g: jax.lax.reduce_precision(real_cum(g), exponent_bits=8, mantissa_bits=7)),
        "gate_off": (attention, "output_gate", lambda out, gate: out),
        "renorm_held_only": (moe, "route", renorm_held_only),
        "shared_gate_off": (moe, "shared_gate", lambda shared, logit: shared),
    }
    if fault is not None and fault not in plants:
        raise SystemExit(f"no control {fault!r}: {REFERENCE_CONTROLS + PROGRAM_FAULTS}")
    if fault is None:
        yield
        return
    module, name, planted = plants[fault]
    real = getattr(module, name)
    setattr(module, name, planted)
    try:
        yield
    finally:
        setattr(module, name, real)


def _reference_control(ctx) -> None:
    """The reference one precision down (``fp8``) or with the second half of
    every row's positions left out of the loss (``half_tokens``) stands in the
    program's place. No window."""
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
    _leaves_shown(ctx, stand_in, ref)


def _leaves_shown(ctx, stand_in: dict, ref: dict) -> None:
    """Beside a control's worst leaves, what its MEDIAN leaf reads in each of
    the three numbers and how many leaves read 1 or more: a stand-in that
    loses leaves whole is told from one that moves every leaf a little."""
    shown = {}
    for what, leaves in (("grad", stand_in["grad"]), ("change", stand_in["change"])):
        median = float(np.median(list(ref[what].values())))
        shown[what] = [abs(v - ref[what][leaf]) / max(ref[what][leaf], median)
                       for leaf, v in leaves.items()]
    median = float(np.median(list(ref["grad"].values())))
    shown["distance"] = [d / max(ref["grad"][leaf], median) for leaf, d in ref["grad_distance"].items()]
    worst = {k: ctx.facts[k] for k in ("grad_worst_leaf", "change_worst_leaf", "grad_distance_worst_leaf")}
    print(f"read, not compared: worst leaves {worst}; "
          + "; ".join(f"{what} median leaf {float(np.median(v))!r}, {sum(x >= 0.999 for x in v)} of "
                      f"{len(v)} leaves at 1 or more" for what, v in shown.items()),
          file=sys.stderr)


def reference_steps(ctx, shapes, fed: list, mode: str, positions=None,
                    first_gradient=None, keep_gradient=False) -> dict:
    """The plain reference through the first steps, as
    ``drivers/train_moe.py:reference_steps``: float32 weights from the seed,
    global-norm clip, Adam with its moments on the host. A step is two passes
    over its rows: the loads of every row first (forward only: the
    load-balance term's ``f`` is the whole step's), then the gradient a row
    at a time under that ``f``. ``first_gradient``, ``keep_gradient``: as
    there."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util

    mix, cfg = ctx.mix, ctx.cfg
    params = weights_gdn.make_params(shapes, ctx.seed, jnp.float32)
    loads_fn = jax.jit(lambda p, ids: reference_gdn.loads(p, cfg, ids, mode))
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, ids, share: reference_gdn.loss(p, cfg, ids, mode, positions, share), has_aux=True,
    ))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    clip = jax.jit(lambda g, n: reference_gdn.clip_by_global_norm(
        jax.tree_util.tree_map(lambda x: x / n, g), float(mix["clip_grad_norm"])
    ))

    def leaf_update(p, g, m, v, count):
        delta, m, v = reference_gdn.adam_update(g, m, v, count, float(mix["learning_rate"]))
        return p + delta, m, v

    update = jax.jit(leaf_update, static_argnums=(4,), donate_argnums=(0,))
    moments = {
        path: (np.zeros(x.shape, np.float32), np.zeros(x.shape, np.float32))
        for path, x in _flat(shapes).items()
    }
    lo, hi = reference_gdn.held_range(cfg)
    out = {"loss": [], "pairs": []}
    for step, ids in enumerate(fed):
        loads = {}
        for row in ids:
            for layer, load in loads_fn(params, jnp.asarray(row[None])).items():
                loads[layer] = loads.get(layer, 0) + np.asarray(load)
        share = reference_gdn.share_of(cfg, loads)
        total, grads = 0.0, None
        for row in ids:
            (value, _), g = grad_fn(params, jnp.asarray(row[None]), share)
            total += float(value)
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
        params = traverse_util.unflatten_dict(flat_p)
    out["change"] = _trained(_norms(
        _flat(params), minus=lambda p, x: weights_gdn.make_leaf(p, x.shape, ctx.seed, jnp.float32)
    ))
    return out
