"""Language-model training cells: per step exactly what ``train_lm.py``'s loop
does: a fresh host batch of packed documents from the seed, one step of the
program's ``make_train_step`` (built by ``train_lm.build_step``), the loss
read before the next dispatch.

As ``drivers/train.py``: set-up builds ONE compiled step with its state and
drives it from the seed through its first ``check_steps`` steps, the window
goes on with that same object, and afterwards the plain reference
(``reference_lm.py``) follows those first steps from the same weights and
batches: each step's loss, the first gradient as Adam got it, the parameters'
change. The state is 12 bytes a parameter and fills the chip, so whatever is
compared is reduced a leaf at a time, and the reference keeps its Adam moments
on the host.

Controls (``--control``; none is a measurement): ``fp8`` and ``half_tokens``
put the reference, one precision down or with the second half of every row's
tokens left out of the loss, in the program's place; ``no_carry`` and
``bf16_decay`` plant a fault in the PROGRAM's scan (the state carried between
chunks left out; the cumulative log-decays rounded to bfloat16) and run its
first steps.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import json
import time

import numpy as np

from .. import costs_lm, harness, reference_lm, weights_lm
from .train import ADAM_B1, compare

PROGRAM_FAULTS = ("no_carry", "bf16_decay")
REFERENCE_CONTROLS = ("fp8", "half_tokens")


def packed_batch(mix: dict, cfg: dict, seed: int, step: int) -> np.ndarray:
    """(rows, tokens) int32 from (seed, step): documents of ``document_tokens``
    length (log-uniform), ids uniform over the vocabulary held here but for
    the end-of-text id, packed end to end with one end-of-text id between
    them; the last document of a row is cut where the row ends."""
    rng = np.random.default_rng([int(seed), 11, int(step)])
    rows, tokens = int(mix["rows"]), int(mix["tokens"])
    eot, spec = int(mix.get("end_of_text_id", 0)), mix["document_tokens"]
    out = rng.integers(1, cfg["vocab_size"], size=(rows, tokens), dtype=np.int64)
    for row in out:
        at = 0
        while True:
            at += int(round(np.exp(rng.uniform(np.log(spec["min"]), np.log(spec["max"])))))
            if at >= tokens:
                break
            row[at] = eot
            at += 1
    return out.astype(np.int32)


def _sizes(ctx) -> None:
    """The rehearsal's own sizes over the cell's (``run.py`` merges only
    ``rehearsal.json``, which knows no language model)."""
    if ctx.rehearsal:
        tiny = json.loads((harness.HERE / "rehearsal_lm.json").read_text())
        ctx.cfg = {**ctx.cfg, **tiny["config"]}
        ctx.mix = {**ctx.mix, **tiny["traffic"]}


def _build(ctx):
    import jax
    import jax.numpy as jnp
    import train_lm

    cfg, mix = ctx.cfg, ctx.mix
    dtype = cfg.get("compute_dtype") or cfg["assumed"]["compute_dtype"]
    lm = train_lm.build_model(
        cfg, int(mix["tokens"]), bf16=dtype == "bfloat16", remat=bool(mix["remat"]),
    )
    ids = jnp.zeros((1, int(mix["tokens"])), jnp.int32)
    shapes = jax.eval_shape(lm.init, jax.random.key(0), ids)["params"]
    return lm, shapes


def _flat(tree) -> dict:
    from flax import traverse_util

    return traverse_util.flatten_dict(tree)


@functools.lru_cache(maxsize=None)
def _reducers():
    """(norm of a leaf, norm of the difference of two): jitted once a process,
    so that a shape compiles once however many runs share the process."""
    import jax
    import jax.numpy as jnp

    return (
        jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))),
        jax.jit(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y)))),
    )


def _norms(flat: dict, minus=None) -> dict:
    """{'a/b/c': l2 norm} of every leaf (of leaf - minus(path, leaf) where
    given), one leaf at a time."""
    norm, diff = _reducers()
    return {
        "/".join(p): float(norm(x) if minus is None else diff(x, minus(p, x)))
        for p, x in sorted(flat.items())
    }


class Job:
    """The compiled step, its state and its feed: one object for the first
    steps and for the window."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import train_lm
        from dalle_pytorch_tpu.parallel import make_runtime

        self.ctx, mix = ctx, ctx.mix
        self.runtime = make_runtime(devices=jax.local_devices()[: ctx.chips], **mix["mesh"])
        self.lm, self.shapes = _build(ctx)
        params = weights_lm.make_params(self.shapes, ctx.seed, jnp.float32)
        self.state, _, self.step_fn = train_lm.build_step(
            self.lm, params, self.runtime, float(mix["clip_grad_norm"]),
        )
        del params
        self.lr = jnp.asarray(float(mix["learning_rate"]))
        self.steps = 0
        self.fed = []    # ids of the first steps, on the host

    def host_batch(self, step: int) -> np.ndarray:
        with harness.span("bench.host_batch"):
            return packed_batch(self.ctx.mix, self.ctx.cfg, self.ctx.seed, step)

    def dispatch(self, ids: np.ndarray, keep: bool = False):
        import jax
        import jax.numpy as jnp

        with harness.span("bench.train_step"):
            self.state, loss = self.step_fn(
                self.state, {"ids": jnp.asarray(ids)}, jax.random.key(self.steps), self.lr,
            )
        if keep:
            self.fed.append(ids)
        self.steps += 1
        return loss

    def first_steps(self) -> dict:
        """The program through ``check_steps`` steps: losses, the first
        gradient as Adam got it, the parameters' change."""
        ctx = self.ctx
        out = {"loss": []}
        for step in range(int(ctx.mix["check_steps"])):
            out["loss"].append(float(self.dispatch(self.host_batch(step), keep=True)))
            if step == 0:
                mu = _norms(_flat(self.state.opt_state[1].mu))
                out["grad"] = {k: v / (1 - ADAM_B1) for k, v in mu.items()}
        import jax.numpy as jnp

        out["change"] = _norms(
            _flat(self.state.params),
            minus=lambda p, x: weights_lm.make_leaf(p, x.shape, ctx.seed, jnp.float32),
        )
        return out


def run(ctx) -> None:
    import train_lm  # noqa: F401  a program without it fails here, at once

    _sizes(ctx)
    if ctx.control in REFERENCE_CONTROLS:
        _reference_control(ctx)
        return
    with _planted(ctx.control):
        job = Job(ctx)
        program = job.first_steps()
    ctx.facts["setup_s"] = time.monotonic() - ctx.process_start
    if ctx.control is None:
        _window(ctx, job)
    else:
        ctx.attempted = job.steps
    fed, shapes = job.fed, job.shapes
    job.state = None
    del job
    gc.collect()
    compare(ctx, program, reference_steps(ctx, shapes, fed, "f32"))


def _window(ctx, job) -> None:
    import jax

    mix = ctx.mix
    counter = ctx.facts["compile_counter"]
    compiles_before = counter.n
    t0 = time.monotonic()
    tracer = harness.TraceSlice(ctx, t0)
    losses, prev, first = [], None, job.steps
    now = t0
    while now < t0 + ctx.seconds:
        tracer.maybe_start(now)
        batch = job.host_batch(job.steps)
        if prev is not None:
            with harness.span("bench.wait_for_verdict"):
                losses.append(float(prev))
        prev = job.dispatch(batch)
        now = time.monotonic()
        tracer.maybe_stop(now)
    with harness.span("bench.wait_for_verdict"):
        losses.append(float(jax.block_until_ready(prev)))
    t1 = time.monotonic()
    tracer.maybe_stop(t1, force=True)
    steps, elapsed = job.steps - first, t1 - t0
    ctx.end_to_end["train_tokens_per_s_chip"] = (
        steps * int(mix["rows"]) * int(mix["tokens"]) / elapsed / ctx.chips
    )
    ctx.compiles_in_window = counter.n - compiles_before
    ctx.attempted = steps
    ctx.failed = sum(1 for x in losses if not np.isfinite(x))
    ctx.memory_peak_bytes = harness.memory_peak(jax.local_devices()[: ctx.chips])
    ctx.reduced = tracer.reduce(ctx.chips)
    ctx.facts.update(window_s=elapsed, steps=steps, rows=int(mix["rows"]),
                     tokens=int(mix["tokens"]), trace_overhead_s=tracer.overhead_s,
                     compiles_in_setup=compiles_before)


@contextlib.contextmanager
def _planted(fault):
    """A fault under the program's scan while its step is traced, planted
    from outside (the program has no option for it) and taken out again."""
    import jax.numpy as jnp
    from dalle_pytorch_tpu.ops import ssm

    real = {name: getattr(ssm, name) for name in ("carried_states", "log_decay")}
    if fault == "no_carry":
        ssm.carried_states = lambda states, total: jnp.zeros_like(states)
    elif fault == "bf16_decay":
        ssm.log_decay = lambda dt, A: real["log_decay"](
            dt.astype(jnp.bfloat16).astype(jnp.float32), A.astype(jnp.bfloat16).astype(jnp.float32)
        ).astype(jnp.bfloat16).astype(jnp.float32)
    elif fault is not None:
        raise SystemExit(f"no control {fault!r}: {REFERENCE_CONTROLS + PROGRAM_FAULTS}")
    try:
        yield
    finally:
        for name, fn in real.items():
            setattr(ssm, name, fn)


def _reference_control(ctx) -> None:
    """The reference one precision down (``fp8``) or with the second half of
    every row's positions left out of the loss (``half_tokens``) stands in
    the program's place. No window."""
    _, shapes = _build(ctx)
    mix = ctx.mix
    fed = [packed_batch(mix, ctx.cfg, ctx.seed, step) for step in range(int(mix["check_steps"]))]
    ctx.facts["setup_s"] = time.monotonic() - ctx.process_start
    ref = reference_steps(ctx, shapes, fed, "f32")
    if ctx.control == "half_tokens":
        stand_in = reference_steps(ctx, shapes, fed, "f32", positions=int(mix["tokens"]) // 2)
    else:
        stand_in = reference_steps(ctx, shapes, fed, ctx.control)
    ctx.attempted = len(fed)
    compare(ctx, stand_in, ref)


def reference_steps(ctx, shapes, fed: list, mode: str, positions=None) -> dict:
    """The plain reference through the first steps: float32 weights from the
    seed, the gradient a row at a time, global-norm clip, Adam with its
    moments kept on the host (float32 state of 772 M parameters is 12.4 GB)."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util

    mix, cfg = ctx.mix, ctx.cfg
    params = weights_lm.make_params(shapes, ctx.seed, jnp.float32)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, ids: reference_lm.loss(p, cfg, ids, mode, positions)
    ))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    clip = jax.jit(lambda g, n: reference_lm.clip_by_global_norm(
        jax.tree_util.tree_map(lambda x: x / n, g), float(mix["clip_grad_norm"])
    ))

    def leaf_update(p, g, m, v, count):
        delta, m, v = reference_lm.adam_update(g, m, v, count, float(mix["learning_rate"]))
        return p + delta, m, v

    update = jax.jit(leaf_update, static_argnums=(4,), donate_argnums=(0,))
    moments = {
        path: (np.zeros(x.shape, np.float32), np.zeros(x.shape, np.float32))
        for path, x in _flat(shapes).items()
    }
    out = {"loss": []}
    for step, ids in enumerate(fed):
        total, grads = 0.0, None
        for row in ids:
            value, g = grad_fn(params, jnp.asarray(row[None]))
            total += float(value)
            grads = g if grads is None else add(grads, g)
        out["loss"].append(total / len(ids))
        grads = clip(grads, float(len(ids)))
        if step == 0:
            out["grad"] = _norms(_flat(grads))
        flat_p, flat_g = _flat(params), _flat(grads)
        del params, grads
        for path in sorted(flat_p):
            m, v = moments[path]
            flat_p[path], m, v = update(flat_p[path], flat_g.pop(path), m, v, step + 1)
            moments[path] = (np.asarray(m), np.asarray(v))
        params = traverse_util.unflatten_dict(flat_p)
    out["change"] = _norms(
        _flat(params), minus=lambda p, x: weights_lm.make_leaf(p, x.shape, ctx.seed, jnp.float32)
    )
    return out
