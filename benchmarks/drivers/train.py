"""Training cells: per step exactly what ``train_dalle.py``'s loop does: a
fresh host batch from the seed, the ``vae_encode`` jit, one step of the
program's ``make_train_step``.

Set-up builds ONE compiled step with its state, drives it from the seed
through its first ``check_steps`` steps (through the window's own call and
feed) and hands that same object to the window. After the window the plain
reference follows those first steps from the same weights and batches, and
the two are compared: each step's loss, the first gradient as Adam got it
(from its first moment after one step), and the parameters' change.
"""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

from .. import costs, harness, reference, traffic, weights
from .serve import build_dalle, param_shapes

ADAM_B1 = 0.9
# a leaf whose reference gradient is under this share of the median leaf's
# moves under Adam by round-off alone: left out of the change
NEGLIGIBLE_GRADIENT = 1e-3


def _leaf_norms(tree) -> dict:
    """{'a/b/c': l2 norm} of every leaf, as floats."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util

    flat = traverse_util.flatten_dict(tree)
    paths = sorted(flat)
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs])(
        [flat[p] for p in paths]
    )
    return {"/".join(p): float(n) for p, n in zip(paths, norms)}


def worst_leaf_gap(program: dict, ref: dict, skip=()) -> tuple:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Returns (gap, leaf)."""
    median = float(np.median(list(ref.values())))
    worst, where = 0.0, ""
    for leaf, r in ref.items():
        if leaf in skip:
            continue
        gap = abs(program[leaf] - r) / max(r, median)
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


class Job:
    """The compiled step, its state and its feed: one object for the first
    steps and for the window."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import optax
        from dalle_pytorch_tpu.models import DiscreteVAE
        from dalle_pytorch_tpu.parallel import make_runtime
        from dalle_pytorch_tpu.parallel.step import create_train_state, make_train_step

        self.ctx, mix, cfg = ctx, ctx.mix, ctx.cfg
        self.runtime = make_runtime(devices=jax.local_devices()[: ctx.chips], **mix["mesh"])
        self.dalle = build_dalle(cfg)
        self.shapes = param_shapes(self.dalle, cfg)
        params = self.initial_params()

        vae = DiscreteVAE(**cfg["vae"])
        s = cfg["vae"]["image_size"]
        key = jax.random.key(0)
        vae_shapes = jax.eval_shape(
            lambda: vae.init({"params": key, "gumbel": key}, jnp.zeros((1, s, s, 3)))
        )["params"]
        vae_params = weights.make_params(vae_shapes, ctx.seed, jnp.float32, salt="vae")

        # train_dalle.py's jit closes over the VAE's weights; here they are an
        # operand, so that the program is the same for every seed and is
        # found in the compile cache (the name in the trace is the same)
        def vae_encode(vae_params, img):
            return vae.apply({"params": vae_params}, img, method="get_codebook_indices")

        encode = jax.jit(vae_encode, out_shardings=self.runtime.data_sharding)
        self.vae_encode = lambda img: encode(vae_params, img)

        optimizer = optax.chain(
            optax.clip_by_global_norm(float(mix["clip_grad_norm"])),
            optax.scale_by_adam(),
        )
        self.state, shardings = create_train_state(params, optimizer, self.runtime)
        del params
        dalle = self.dalle

        def loss_fn(p, batch, rng):
            return dalle.apply(
                {"params": p}, batch["text"], batch["image"],
                return_loss=True, deterministic=True, rngs={"dropout": rng},
            )

        self.step_fn = make_train_step(
            loss_fn, optimizer, self.runtime, shardings, dynamic_lr=True,
        )
        self.lr = jnp.asarray(float(mix["learning_rate"]))
        self.steps = 0
        self.fed = []    # (text, image tokens) of the first steps, on the host

    def initial_params(self):
        import jax.numpy as jnp

        return weights.make_params(self.shapes, self.ctx.seed, jnp.float32)

    def host_batch(self, step: int) -> dict:
        with harness.span("bench.host_batch"):
            return traffic.train_batch(self.ctx.mix, self.ctx.cfg, self.ctx.seed, step)

    def dispatch(self, batch: dict, keep: bool = False):
        """vae_encode then the train step, as the CLI's loop issues them.
        Returns the step's loss, still on the device."""
        import jax
        import jax.numpy as jnp

        with harness.span("bench.vae_encode"):
            tokens = self.vae_encode(batch["image"])
        with harness.span("bench.train_step"):
            self.state, loss = self.step_fn(
                self.state, {"text": jnp.asarray(batch["text"]), "image": tokens},
                jax.random.key(self.steps), self.lr,
            )
        if keep:
            self.fed.append((np.asarray(batch["text"]), np.asarray(tokens)))
        self.steps += 1
        return loss


def run(ctx) -> None:
    import jax

    mix, cfg = ctx.mix, ctx.cfg
    if ctx.control is not None:
        _control_only(ctx)
        return
    job = Job(ctx)
    check_steps = int(mix["check_steps"])
    program = {"loss": []}
    for step in range(check_steps):
        loss = job.dispatch(job.host_batch(step), keep=True)
        program["loss"].append(float(loss))
        if step == 0:
            mu = job.state.opt_state[1].mu
            program["grad"] = {k: v / (1 - ADAM_B1) for k, v in _leaf_norms(mu).items()}
    p0 = job.initial_params()
    program["change"] = _leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a - b, job.state.params, p0)
    )
    del p0

    # ------------------------------------------------------------ window
    ctx.facts["setup_s"] = time.monotonic() - ctx.process_start
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
    steps = job.steps - first
    elapsed = t1 - t0
    image_tokens = steps * int(mix["batch"]) * costs.image_len(cfg)
    ctx.end_to_end["train_tokens_per_s_chip"] = image_tokens / elapsed / ctx.chips
    ctx.compiles_in_window = counter.n - compiles_before
    ctx.attempted = steps
    ctx.failed = sum(1 for x in losses if not np.isfinite(x))
    ctx.memory_peak_bytes = harness.memory_peak(jax.local_devices()[: ctx.chips])
    ctx.reduced = tracer.reduce(ctx.chips)
    ctx.facts.update(window_s=elapsed, steps=steps, batch=int(mix["batch"]),
                     trace_overhead_s=tracer.overhead_s, compiles_in_setup=compiles_before)

    fed, shapes = job.fed, job.shapes
    job.state = None
    del job
    gc.collect()
    ref = reference_steps(ctx, shapes, fed, "f32")
    compare(ctx, program, ref)


def _control_only(ctx) -> None:
    """A control stands in the program's place: the reference computed one
    precision down (``fp8``), or with a fault planted (``half_batch``: the
    second half of every batch left out, the mean taken over the rest). No
    window; the readings are compared as a run's are."""
    import jax

    cfg, mix = ctx.cfg, ctx.mix
    import jax.numpy as jnp

    dalle = build_dalle(cfg)

    text0 = jnp.zeros((1, cfg["text_seq_len"]), jnp.int32)
    image0 = jnp.zeros((1, costs.image_len(cfg)), jnp.int32)
    shapes = jax.eval_shape(dalle.init, jax.random.key(0), text0, image0)["params"]
    rng = traffic.rng_for(ctx.seed, "control_tokens")
    fed = []
    for step in range(int(mix["check_steps"])):
        batch = traffic.train_batch(mix, cfg, ctx.seed, step)
        tokens = rng.integers(0, cfg["num_image_tokens"], (int(mix["batch"]), costs.image_len(cfg)))
        fed.append((batch["text"], tokens.astype(np.int32)))
    ctx.facts["setup_s"] = time.monotonic() - ctx.process_start
    ref = reference_steps(ctx, shapes, fed, "f32")
    if ctx.control == "half_batch":
        half = int(mix["batch"]) // 2
        stand_in = reference_steps(ctx, shapes, [(t[:half], i[:half]) for t, i in fed], "f32")
    else:
        stand_in = reference_steps(ctx, shapes, fed, ctx.control)
    ctx.attempted = int(mix["check_steps"])
    compare(ctx, stand_in, ref)


def reference_steps(ctx, shapes, fed: list, mode: str) -> dict:
    """The plain reference through the first steps: float32 weights from the
    seed, the loss and its gradient one row at a time (so that it fits beside
    nothing else on the chip), global-norm clip, Adam."""
    import jax
    import jax.numpy as jnp

    mix = ctx.mix
    rows = int(mix.get("reference_rows", 1))
    params = weights.make_params(shapes, ctx.seed, jnp.float32)
    cfg = ctx.cfg
    p0 = params
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, i: reference.loss(p, cfg, t, i, mode)
    ))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu = zeros, zeros
    out = {"loss": []}
    clip = jax.jit(lambda g, n: reference.clip_by_global_norm(
        jax.tree_util.tree_map(lambda x: x / n, g), float(mix["clip_grad_norm"])
    ))
    update = jax.jit(
        lambda p, g, m, v, count: _apply(p, *reference.adam_update(
            g, m, v, count, float(mix["learning_rate"])
        )),
        static_argnums=(4,),
    )
    for step, (text, image) in enumerate(fed):
        n_blocks = len(text) // rows
        total, grads = 0.0, zeros
        for b in range(n_blocks):
            sl = slice(b * rows, (b + 1) * rows)
            value, g = grad_fn(params, jnp.asarray(text[sl]), jnp.asarray(image[sl]))
            total += float(value)
            grads = add(grads, g)
        out["loss"].append(total / n_blocks)
        grads = clip(grads, float(n_blocks))
        if step == 0:
            out["grad"] = _leaf_norms(grads)
        params, mu, nu = update(params, grads, mu, nu, step + 1)
    out["change"] = _leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, params, p0))
    return out


def _apply(params, delta, mu, nu):
    import jax

    return jax.tree_util.tree_map(lambda p, d: p + d, params, delta), mu, nu


def compare(ctx, program: dict, ref: dict) -> None:
    limits = ctx.facts["limits"]
    for i, (p, r) in enumerate(zip(program["loss"], ref["loss"]), 1):
        gap = abs(p - r) / abs(r)
        if f"loss_gap_step{i}" in limits:
            ctx.compare(f"loss_step{i}_gap", gap, limits[f"loss_gap_step{i}"])
        else:
            # read and shown, not compared: no control moves it (limits file)
            ctx.facts[f"loss_step{i}_gap_not_compared"] = gap
            print(f"read, not compared: loss_step{i}_gap {gap!r}", file=sys.stderr)
    gap, leaf = worst_leaf_gap(program["grad"], ref["grad"])
    ctx.facts["grad_worst_leaf"] = leaf
    ctx.compare("grad_norm_worst_leaf_gap", gap, limits["grad_norm_worst_leaf_gap"])
    median = float(np.median(list(ref["grad"].values())))
    skip = {k for k, v in ref["grad"].items() if v < NEGLIGIBLE_GRADIENT * median}
    gap, leaf = worst_leaf_gap(program["change"], ref["change"], skip)
    ctx.facts["change_worst_leaf"] = leaf
    ctx.facts["leaves_left_out"] = sorted(skip)
    ctx.compare("param_change_worst_leaf_gap", gap, limits["param_change_worst_leaf_gap"])
