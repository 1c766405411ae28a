"""Compiled, sharded train-step builder.

The reference's training engine is imperative: DeepSpeed wraps the model and
optimizer and hides gradient all-reduce inside ``engine.backward()/step()``
(deepspeed_backend.py:135-163, train_dalle.py:574-584). Here the whole update
is ONE jitted function with explicit input/output shardings: XLA fuses the
forward, backward and optimizer, inserts the gradient reduce-scatters /
all-gathers implied by the fsdp/tp specs, and overlaps them with compute on
ICI. ``donate`` recycles the parameter/optimizer buffers so the update is
in-place in HBM.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..utils import profiling as _profiling  # noqa: F401  (TELEMETRY spans -> profiler)
from .mesh import MeshRuntime
from .sharding import opt_state_shardings, params_shardings, shard_pytree


class TrainState(NamedTuple):
    """Minimal pytree train state (step, params, opt_state) plus the NaN
    step-guard's device-side counters: ``skipped`` (total non-finite steps
    rejected) and ``consec_skipped`` (current run of rejections — the
    trainer hard-aborts past a threshold; docs/DESIGN.md §9)."""

    step: jnp.ndarray
    params: Any
    opt_state: Any
    skipped: jnp.ndarray
    consec_skipped: jnp.ndarray


def create_train_state(
    params: Any,
    optimizer: optax.GradientTransformation,
    runtime: MeshRuntime,
    rules=None,
) -> tuple[TrainState, TrainState]:
    """Build a sharded TrainState and its sharding tree.

    Parameters are placed according to the partition rules (fsdp/tp); the
    optimizer state inherits parameter shardings — the ZeRO-style
    optimizer-state partitioning the reference gates behind DeepSpeed config
    (train_dalle.py:483-488).
    """
    kwargs = {} if rules is None else {"rules": rules}
    p_shard = params_shardings(params, runtime.mesh, **kwargs)
    params = shard_pytree(params, p_shard)
    opt_state = jax.jit(
        optimizer.init, out_shardings=opt_state_shardings(
            jax.eval_shape(optimizer.init, params), p_shard, runtime.mesh
        )
    )(params)
    o_shard = opt_state_shardings(opt_state, p_shard, runtime.mesh)
    replicated = NamedSharding(runtime.mesh, P())
    # distinct zero buffers: the step is donated, and donating one buffer
    # through several leaves is an XLA error. Placed on the mesh like every
    # other leaf: a counter left on the default device has another type than
    # the one the step hands back, so the SECOND call traced, lowered and
    # loaded the whole step again (~10 s of every run's set-up), and where
    # that second trace numbers a helper function otherwise, compiled it again
    # under another cache key (PERF.md section 6, PR 33)
    zero = lambda: jax.device_put(jnp.zeros((), jnp.int32), replicated)
    state = TrainState(
        step=zero(), params=params, opt_state=opt_state,
        skipped=zero(), consec_skipped=zero(),
    )
    shardings = TrainState(
        step=replicated, params=p_shard, opt_state=o_shard,
        skipped=replicated, consec_skipped=replicated,
    )
    return state, shardings


def make_train_step(
    loss_fn: Callable[..., Any],
    optimizer: optax.GradientTransformation,
    runtime: MeshRuntime,
    state_shardings: TrainState,
    has_aux: bool = False,
    donate: bool = True,
    dynamic_lr: bool = False,
    data_shardings: Any = None,
    nan_guard: bool = True,
    nan_inject_step: Optional[int] = None,
    after_update: Optional[Callable[[Any, Any], Any]] = None,
):
    """Compile ``(state, batch, rng[, lr]) -> (state, loss[, aux])``.

    ``loss_fn(params, batch, rng)`` must be pure; reductions over the sharded
    batch are global under jit, so the reference's explicit ``average_all``
    loss collective (train_dalle.py:587) is implicit here.

    ``dynamic_lr=True`` adds a traced learning-rate argument and applies
    ``-lr`` scaling in the step — the optimizer chain must then end at
    unscaled update directions (e.g. ``scale_by_adam`` without ``scale``), so
    host-side schedulers (ReduceLROnPlateau) change lr without recompiling.

    ``nan_guard=True`` (default) checks finiteness of the loss and the
    global gradient norm INSIDE the compiled step and ``jnp.where``-selects
    the prior params/opt_state when non-finite — a rejected step costs
    nothing extra and never syncs the host (no ``lax.cond`` either: both
    branches' values already exist, selection is cheaper than a branch on
    TPU). On a finite step the selects are identity, so guarded and
    unguarded steps are bit-identical (pinned in tests/test_resilience.py).
    The returned loss doubles as the rejection signal: NaN whenever the
    step was rejected (even when only the grads were non-finite), finite
    otherwise — the host (loop.py) keys its batch-retry and the
    K-consecutive-rejections abort (--nan_abort_after) off exactly the
    device's decision.

    ``after_update(params, aux) -> params`` runs on the parameters the
    optimizer has updated, with what ``loss_fn`` returned beside the loss
    (``loss_fn`` then returns ``(loss, aux)`` whether or not ``has_aux`` hands
    ``aux`` on to the caller): buffers among the leaves that a rule other than
    the optimizer's writes once a step (an expert layer's selection bias,
    models/lm.py:CausalLM.balance). A rejected step keeps the old ones.

    ``nan_inject_step`` is the fault hook (utils/faults.py nan_at_step):
    the loss is forced to NaN at that global step, compiled in as a trace
    constant — None (the default) adds nothing to the program.
    """
    replicated = NamedSharding(runtime.mesh, P())

    out_shardings = (
        (state_shardings, replicated, replicated)
        if has_aux
        else (state_shardings, replicated)
    )
    if data_shardings is None:
        data_shardings = runtime.data_sharding  # batch-dim sharding, all leaves
    in_shardings = [state_shardings, data_shardings, replicated]
    if dynamic_lr:
        in_shardings.append(replicated)

    @partial(
        jax.jit,
        in_shardings=tuple(in_shardings),
        out_shardings=out_shardings,
        donate_argnums=(0,) if donate else (),
    )
    def train_step(state: TrainState, batch, rng, lr=None):
        with_aux = has_aux or after_update is not None
        grad_fn = jax.value_and_grad(loss_fn, has_aux=with_aux)
        out, grads = grad_fn(state.params, batch, rng)
        loss, aux = out if with_aux else (out, None)
        if nan_inject_step is not None:
            loss = jnp.where(
                state.step == nan_inject_step,
                jnp.asarray(jnp.nan, loss.dtype), loss,
            )
        with jax.named_scope("update"):
            with jax.named_scope("update.optimizer"):
                updates, opt_state = optimizer.update(
                    grads, state.opt_state, state.params
                )
                if dynamic_lr:
                    updates = jax.tree_util.tree_map(lambda u: -lr * u, updates)
                params = optax.apply_updates(state.params, updates)
                if after_update is not None:
                    params = after_update(params, aux)
            skipped, consec = state.skipped, state.consec_skipped
            if nan_guard:
                with jax.named_scope("update.nan_guard"):
                    finite = jnp.isfinite(loss) & jnp.isfinite(
                        optax.global_norm(grads)
                    )
                    keep = lambda new, old: jax.tree_util.tree_map(
                        lambda n, o: jnp.where(finite, n, o), new, old
                    )
                    params = keep(params, state.params)
                    opt_state = keep(opt_state, state.opt_state)
                    skipped = skipped + jnp.where(finite, 0, 1).astype(jnp.int32)
                    consec = jnp.where(finite, 0, consec + 1).astype(jnp.int32)
                    # the returned loss IS the rejection signal: NaN for ANY
                    # rejected step — including finite-loss/non-finite-grad —
                    # so the host's retry/abort verdict always agrees with
                    # the device's select
                    loss = jnp.where(
                        finite, loss, jnp.asarray(jnp.nan, loss.dtype)
                    )
        new_state = TrainState(
            step=state.step + 1, params=params, opt_state=opt_state,
            skipped=skipped, consec_skipped=consec,
        )
        if has_aux:
            return new_state, loss, aux
        return new_state, loss

    return _with_ambient_mesh(train_step, runtime)


def _with_ambient_mesh(jitted, runtime: MeshRuntime):
    """Wrap a jitted step so calls (and AOT ``lower``) trace with the mesh
    ambiently active — the sp attention paths build shard_map bodies at trace
    time and need the concrete mesh (parallel/context.py). No-op once the
    trace is cached."""

    def with_mesh(*args, **kw):
        with runtime.activate():
            return jitted(*args, **kw)

    def lower(*args, **kw):
        with runtime.activate():
            return jitted.lower(*args, **kw)

    with_mesh.jitted = jitted
    with_mesh.lower = lower
    return with_mesh


def make_eval_step(
    loss_fn: Callable[..., Any],
    runtime: MeshRuntime,
    state_shardings: TrainState,
    has_aux: bool = False,
):
    replicated = NamedSharding(runtime.mesh, P())

    @partial(
        jax.jit,
        in_shardings=(state_shardings.params, runtime.data_sharding, replicated),
    )
    def eval_step(params, batch, rng):
        return loss_fn(params, batch, rng)

    return _with_ambient_mesh(eval_step, runtime)
