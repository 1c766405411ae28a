"""Mixture-of-experts feed-forwards with expert parallelism: the Switch layer
of ``DALLE``'s ``--moe_experts`` (``MoEFeedForward``, below) and the expert
layer of the DeepSeek-V3 family's language models (``RoutedExperts``, at the
end: sigmoid scores, top-k of all experts, no dropped pair, a shared expert,
and a layer that is told which experts it holds).

``MoEFeedForward``: the reference has no MoE (SURVEY.md §2.2 lists EP as n/a); this is a
beyond-parity scaling axis in the GShard/Switch lineage, built so GSPMD can
shard it over the ``ep`` mesh axis with zero manual collectives:

- Switch-style top-1 routing: a linear gate scores experts per token; each
  token goes to its argmax expert, weighted by the gate probability
  (straight-through for the dropped experts' gradient via the prob weight);
- capacity-based dispatch: each expert processes at most
  ``capacity_factor * tokens / num_experts`` tokens per example; overflow
  tokens fall through the residual (standard Switch behavior). Dispatch and
  combine are one-hot einsums over a (tokens, experts, capacity) tensor —
  the mesh-tensorflow formulation whose expert dimension GSPMD shards over
  ``ep``, turning the einsums into all_to_all exchanges on ICI;
- a load-balance auxiliary loss (mean routed fraction x mean gate prob per
  expert, scaled by E — Switch eq. 4) is written to the mutable ``moe_aux``
  collection; trainers add ``moe_aux_weight * sum(aux)`` to the objective
  (train_dalle.py does when --moe_experts > 0);
- expert weights are (E, ...) leaves; parallel/sharding.py's rules place
  them as P("ep", ...), so each device stores and computes only its
  experts.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name

from . import kv_policy
from .layers import SwiGLU

Dtype = Any

# the names a block's checkpoint keeps of ``RoutedExperts`` (models/transformer.py:
# _block_checkpoint, beside the flash kernels' KERNEL_RESIDUAL_NAMES): the router's
# scores, choice and picked scores; the dispatch (the pairs' order, the group
# sizes, the sorted tokens and weights); the first chunk's gathered rows; its
# first grouped product. Each costs 2-10 times a dot's time per byte to rebuild: kept,
# the rebuilt forward runs no router, no sort, no row gather and only the second
# product. Outside a checkpoint a name lowers to nothing.
MOE_RESIDUAL_NAMES = ("moe_router", "moe_dispatch", "moe_rows", "moe_h")
ROUTER, DISPATCH, ROWS, PRODUCT = MOE_RESIDUAL_NAMES

# the block whose checkpoint is being traced, while it is (``checkpointed_block``):
# an expert layer in it records what the names keep (route ``remat/moe_residuals``)
_CHECKPOINTED_BLOCK: contextvars.ContextVar = contextvars.ContextVar("block", default=None)


@contextlib.contextmanager
def checkpointed_block(block: str):
    """Inside: a block's checkpoint, named ``block``, is tracing its function."""
    token = _CHECKPOINTED_BLOCK.set(block)
    try:
        yield
    finally:
        _CHECKPOINTED_BLOCK.reset(token)


class MoEFeedForward(nn.Module):
    """Switch-routed GEGLU feed-forward over ``num_experts`` experts."""

    dim: int
    num_experts: int
    mult: float = 4.0
    capacity_factor: float = 1.25
    dropout: float = 0.0
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        b, n, d = x.shape
        e = self.num_experts
        hidden = int(self.dim * self.mult)
        cap = max(int(self.capacity_factor * n / e), 1)

        gate_logits = nn.Dense(
            e, use_bias=False, dtype=jnp.float32,
            param_dtype=self.param_dtype, name="gate",
        )(x.astype(jnp.float32))  # (b, n, e) — routing in f32 for stability
        probs = jax.nn.softmax(gate_logits, axis=-1)
        expert_idx = jnp.argmax(probs, axis=-1)  # (b, n)
        expert_prob = jnp.take_along_axis(probs, expert_idx[..., None], axis=-1)[..., 0]

        # position of each token within its expert's capacity buffer:
        # running count of same-expert tokens before it (scan order = seq)
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)  # (b, n, e)
        position = jnp.cumsum(onehot, axis=1) * onehot  # 1-based where routed
        position = jnp.sum(position, axis=-1) - 1  # (b, n), -1 never happens
        keep = position < cap  # overflow tokens fall through

        # load-balance aux (Switch eq. 4): E * sum_e f_e * P_e
        frac = jnp.mean(onehot.astype(jnp.float32), axis=(0, 1))  # (e,)
        prob_mean = jnp.mean(probs, axis=(0, 1))
        aux = e * jnp.sum(frac * prob_mean)
        self.sow("moe_aux", "load_balance", aux)

        # dispatch: (b, n, e, cap) one-hot; combine re-weights by gate prob
        pos_oh = jax.nn.one_hot(
            jnp.where(keep, position, cap), cap, dtype=x.dtype
        )  # (b, n, cap); out-of-capacity rows are all-zero
        dispatch = onehot.astype(x.dtype)[..., None] * pos_oh[:, :, None, :]
        combine = dispatch * expert_prob[..., None, None].astype(x.dtype)

        xs = jnp.einsum("bnec,bnd->ebcd", dispatch, x.astype(self.dtype))

        w_in = self.param(
            "experts_in", nn.initializers.lecun_normal(),
            (e, d, hidden * 2), self.param_dtype,
        )
        w_out = self.param(
            "experts_out", nn.initializers.lecun_normal(),
            (e, hidden, d), self.param_dtype,
        )
        h = jnp.einsum(
            "ebcd,edh->ebch", xs, w_in.astype(self.dtype)
        )
        h, gates = jnp.split(h, 2, axis=-1)
        h = h * jax.nn.gelu(gates)
        h = nn.Dropout(self.dropout)(h, deterministic=deterministic)
        ys = jnp.einsum("ebch,ehd->ebcd", h, w_out.astype(self.dtype))

        return jnp.einsum("bnec,ebcd->bnd", combine, ys)


# rows of the first chunk over the pairs a uniform router would send the held
# experts: what a layer always computes, rounded up to whole CHUNK_MULTIPLEs
HEADROOM = 1.25
CHUNK_MULTIPLE = 256


def route(scores, bias, per_token: int, scaling: float):
    """(tokens, experts) sigmoid scores -> the ``per_token`` experts each token
    chose, (tokens, per_token) indices, and their weights. The bias moves the
    choice only; the weights are the scores themselves, normalised over all
    the chosen (held here or not) and scaled."""
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), per_token)
    chosen = checkpoint_name(chosen, ROUTER)
    picked = checkpoint_name(jnp.take_along_axis(scores, chosen, axis=-1), ROUTER)
    return chosen, scaling * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def _named_scores(logits, scoring: str):
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    return checkpoint_name(scores, ROUTER)


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def router_scores(logits, scoring: str):
    """``sigmoid`` or ``softmax`` (over the last axis) of float32 router logits,
    named ``moe_router``. The derivative is jax's own, written over the NAMED
    scores, so that a checkpoint which keeps them rebuilds neither the function
    nor the dot in front of it (jax's rules read the function's unnamed output)."""
    return _named_scores(logits, scoring)


@router_scores.defjvp
def _router_scores_jvp(scoring, primals, tangents):
    (logits,), (dot,) = primals, tangents
    s = _named_scores(logits, scoring)
    if scoring == "sigmoid":
        return s, dot * (s * (1 - s))
    return s, s * (dot - (s * dot).sum(-1, keepdims=True))


def shared_gate(shared, logit):
    """The shared expert's output times ``sigmoid`` of its gate's one logit a
    token (the ``qwen3_next`` family), in the dtype of ``shared``."""
    return shared * jax.nn.sigmoid(logit.astype(jnp.float32)).astype(shared.dtype)


def balanced_bias(bias, load, speed: float):
    """The selection bias after a step that sent each expert ``load`` pairs
    (``topk_method`` ``noaux_tc``, DeepSeek-V3 report section 2.1.2): down by
    ``speed`` where an expert was sent more than the mean, up where fewer."""
    load = load.astype(jnp.float32)
    return bias + speed * jnp.sign(jnp.mean(load) - load).astype(bias.dtype)


# an expert's gated activation: ``W_out (act(a) * b)`` with ``[a, b] = W_in u``
ACTIVATIONS = {"swiglu": nn.silu, "reglu": jax.nn.relu}


def _chunk(c, u, w_in, w_out, sorted_w, sorted_tok, starts, sizes, chunk: int, dtype,
           activation: str = "swiglu"):
    """Chunk ``c`` of the pairs sorted by expert: (its tokens, its weighted
    expert outputs in float32). Rows past the count have weight 0. The
    gathered rows and the first product's output are named: a block's
    checkpoint keeps the first chunk's (the further chunks' loop has a
    backward of its own, which keeps nothing)."""
    with jax.named_scope("moe.dispatch"):
        first = c * chunk
        tok = jax.lax.dynamic_slice(sorted_tok, (first,), (chunk,))
        w = jax.lax.dynamic_slice(sorted_w, (first,), (chunk,))
        groups = jnp.clip(
            jnp.minimum(starts + sizes, first + chunk) - jnp.maximum(starts, first), 0, chunk,
        ).astype(jnp.int32)
        # the rows past the count go to the last expert with weight 0: the
        # TPU's grouped product leaves rows of NO group unwritten (NaNs from
        # the chip's memory reached the sum through 0 * NaN)
        groups = groups.at[-1].add(chunk - jnp.sum(groups))
        rows = checkpoint_name(jnp.take(u, tok, axis=0).astype(dtype), ROWS)
    with jax.named_scope("moe.experts"):
        h = jax.lax.ragged_dot(rows, w_in, groups, preferred_element_type=jnp.float32)
        a, g = jnp.split(checkpoint_name(h.astype(dtype), PRODUCT), 2, axis=-1)
        out = jax.lax.ragged_dot(ACTIVATIONS[activation](a) * g, w_out, groups,
                                 preferred_element_type=jnp.float32)
    with jax.named_scope("moe.combine"):
        return tok, out * w[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _further_chunks(y, u, w_in, w_out, sorted_w, sorted_tok, starts, sizes, count, chunk, dtype,
                    activation):
    """``y`` plus chunks 1, 2, … of the sorted pairs, as many as ``count``
    needs: a loop whose trip count is read from the data, so a count that
    passes the first chunk costs what it needs and no more. Such a loop has
    no automatic transpose; the backward below walks the same chunks,
    recomputing each, so nothing is kept for them."""
    def body(c, y):
        tok, out = _chunk(c, u, w_in, w_out, sorted_w, sorted_tok, starts, sizes, chunk, dtype,
                          activation)
        return y.at[tok].add(out)

    return jax.lax.fori_loop(1, -(-count // chunk), body, y)


def _further_fwd(y, u, w_in, w_out, sorted_w, sorted_tok, starts, sizes, count, chunk, dtype,
                 activation):
    out = _further_chunks(y, u, w_in, w_out, sorted_w, sorted_tok, starts, sizes, count, chunk, dtype,
                          activation)
    return out, (u, w_in, w_out, sorted_w, sorted_tok, starts, sizes, count)


def _further_bwd(chunk, dtype, activation, residuals, dy):
    u, w_in, w_out, sorted_w, sorted_tok, starts, sizes, count = residuals
    floats = (u, w_in, w_out, sorted_w)

    def body(c, sums):
        def weighted(u, w_in, w_out, sorted_w):
            return _chunk(c, u, w_in, w_out, sorted_w, sorted_tok, starts, sizes, chunk, dtype,
                          activation)[1]

        tok = jax.lax.dynamic_slice(sorted_tok, (c * chunk,), (chunk,))
        grads = jax.vjp(weighted, *floats)[1](jnp.take(dy, tok, axis=0))
        return tuple(s + g.astype(jnp.float32) for s, g in zip(sums, grads))

    zeros = tuple(jnp.zeros(x.shape, jnp.float32) for x in floats)
    sums = jax.lax.fori_loop(1, -(-count // chunk), body, zeros)
    whole = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return (dy, *(s.astype(x.dtype) for s, x in zip(sums, floats)),
            whole(sorted_tok), whole(starts), whole(sizes), whole(count))


_further_chunks.defvjp(_further_fwd, _further_bwd)


def router_logits(u, total: int, param_dtype):
    """(tokens, ``total``) float32 router logits of the rows ``u``, from the
    Dense ``gate`` of the calling module. bf16_3x: activations that are exact
    in bfloat16 stay so, the router's weights are not rounded; one pass would
    round them before the sigmoid or the softmax."""
    return nn.Dense(
        total, use_bias=False, dtype=jnp.float32, param_dtype=param_dtype,
        precision=jax.lax.Precision.HIGH, name="gate",
    )(u.astype(jnp.float32))


class RoutedExperts(nn.Module):
    """The expert feed-forward of the DeepSeek-V3 family (``topk_method``
    ``noaux_tc``, one group), told which experts it holds.

        s = sigmoid(W_g u)                   float32, over ALL ``experts_total``
        chosen = top-k of (s + b)            b: the selection bias, for choosing only
        w_e = scaling * s_e / (sum_chosen s + 1e-20)   over all k chosen, held or not
        y = sum_{e chosen and held} w_e SwiGLU_e(u) + SwiGLU_shared(u)

    ``experts_held = (lo, hi)``: the layer routes over the total and computes
    its own. What the absent experts would add is left out, and on one chip
    nothing is exchanged: the partial ``y`` goes on (model-configs guide §4).
    ``e_score_correction_bias`` and ``tokens_per_expert`` are leaves of the
    tree so that a checkpoint carries them, and buffers in effect: they enter
    nothing differentiable (top-k gives indices), their gradients are zero and
    Adam leaves them bit for bit. What writes them is the model's
    ``after_update`` (``models/lm.py:CausalLM.balance``, once a step from the
    loads this layer sows): ``tokens_per_expert`` takes the pairs the step sent
    each of ALL experts, and the bias moves against them (``balanced_bias``).

    ``scoring='softmax'`` is the ``qwen3_next`` family's router in the same
    layer: ``s = softmax(W_g u)`` over all experts, the top-k of ``s`` itself,
    the weights normalised over the chosen, NO selection bias (the leaf is not
    made) and no scaling; beside the loads it sows each expert's mean
    probability (``prob``), from which the model forms the family's
    load-balance loss (``models/lm.py:CausalLM.loss_and_loads``), and
    ``router_prob`` is the buffer that keeps the last step's mean.
    ``gate_shared`` multiplies the shared expert by ``sigmoid(w_sg . u)``
    (``shared_gate``), one logit a token.

    ``activation='reglu'`` makes every routed expert ``W_out (relu(a) * b)``
    (the ``smallthinker`` family) where ``swiglu`` has ``silu(a)``. A softmax
    layer can be HANDED its router's probabilities, ``probs`` (tokens, ALL
    experts) in float32: the block that routes from its own input computes
    them before its mixer (models/transformer.py:PreRoutedRMSNorm) and this
    layer then has no ``gate`` of its own; the choice, the weights and what
    it sows are the same.

    No pair is dropped and nothing is a capacity. The (token, expert) pairs
    of the held experts are sorted by expert (index arrays of the worst-case
    length ``tokens * per_token``: integers) and the experts are two grouped
    matrix products over the sorted rows (``jax.lax.ragged_dot``). The rows
    are gathered a chunk at a time, ``HEADROOM`` times the expected count:
    the first chunk always (where the real count fits, the usual case,
    it is all the work there is), then as many further chunks as the count
    needs, in a loop whose trip count is read from the data
    (``_further_chunks``: its own backward, each chunk recomputed), so that no
    buffer has the worst-case size and a count that passes the first chunk
    costs what it needs. Exact for every count from none to all. (Tried and
    left: a ``lax.cond`` around a scan over ALL the other chunks cost 117 ms
    a layer whenever a count passed the buffer, which a router that Adam has
    moved does in some steps of some seeds: the step's time then depended on
    the seed by 2 %; with a ``cond`` inside that scan's body the scan stacked
    its loop-invariant residuals, 6 GB. The first chunk stands outside any
    loop: operations inside one reach the device trace without their scope.)"""

    dim: int
    hidden: int
    experts_total: int
    experts_held: Tuple[int, int]
    per_token: int
    shared: int = 1
    scaling: float = 1.0
    scoring: str = "sigmoid"
    gate_shared: bool = False
    activation: str = "swiglu"
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def _chunk_rows(self, pairs: int) -> int:
        lo, hi = self.experts_held
        expected = HEADROOM * pairs * (hi - lo) / self.experts_total
        return min(max(-(-int(expected) // CHUNK_MULTIPLE), 1) * CHUNK_MULTIPLE, pairs)

    def _kept_bytes(self, tokens: int, padded: int, chunk: int) -> dict:
        """Bytes a block's checkpoint keeps of this layer, by name: the float32
        scores (handed probabilities are kept by the mixer's checkpoint), the
        int32 choice and float32 picked scores; the int32 order and group sizes,
        the padded sorted tokens and weights; the first chunk's rows and first
        product in ``dtype``."""
        width, pairs = jnp.dtype(self.dtype).itemsize, tokens * self.per_token
        held = self.experts_held[1] - self.experts_held[0]
        return {
            ROUTER: 4 * (tokens * self.experts_total + 2 * pairs),
            DISPATCH: 4 * (pairs + held + 2 * padded),
            ROWS: width * chunk * self.dim,
            PRODUCT: width * chunk * 2 * self.hidden,
        }

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True, probs=None) -> jnp.ndarray:
        b, n, d = x.shape
        tokens, k, total = b * n, self.per_token, self.experts_total
        lo, hi = self.experts_held
        held = hi - lo
        assert 0 <= lo < hi <= total and k <= total, (self.experts_held, total, k)
        assert self.activation in ACTIVATIONS and (self.activation == "swiglu" or not self.shared)
        u = x.reshape(tokens, d)

        with jax.named_scope("moe.router"):
            assert self.scoring in ("sigmoid", "softmax"), self.scoring
            assert probs is None or self.scoring == "softmax", "only softmax probabilities are handed"
            if self.scoring == "softmax":
                scores = (
                    router_scores(router_logits(u, total, self.param_dtype), "softmax")
                    if probs is None else probs.reshape(tokens, total)
                )
                chosen, weights = route(scores, jnp.zeros((total,), scores.dtype), k, self.scaling)
                self.sow("moe_stats", "prob", jnp.mean(scores, axis=0))
                self.param("router_prob", nn.initializers.zeros, (total,), self.param_dtype)
            else:
                scores = router_scores(router_logits(u, total, self.param_dtype), "sigmoid")
                bias = self.param(
                    "e_score_correction_bias", nn.initializers.zeros, (total,), self.param_dtype
                )
                chosen, weights = route(scores, bias, k, self.scaling)
            # pairs sent to each of ALL experts: CausalLM.balance reads it
            experts = jnp.arange(total, dtype=chosen.dtype)
            self.sow("moe_stats", "load", jnp.sum(chosen.reshape(-1, 1) == experts, axis=0))
            self.param("tokens_per_expert", nn.initializers.zeros, (total,), self.param_dtype)

        with jax.named_scope("moe.dispatch"):
            pairs = tokens * k
            flat = chosen.reshape(pairs)
            here = (flat >= lo) & (flat < hi)
            key = jnp.where(here, flat - lo, held)          # the others last
            order = checkpoint_name(jnp.argsort(key, stable=True), DISPATCH)
            sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype), axis=0)
            sizes = checkpoint_name(sizes, DISPATCH)
            starts = jnp.cumsum(sizes) - sizes
            count = jnp.sum(sizes)
            chunk = self._chunk_rows(pairs)
            n_chunks = -(-pairs // chunk)
            pad = n_chunks * chunk - pairs
            sorted_tok = checkpoint_name(jnp.pad(order // k, (0, pad)), DISPATCH)
            sorted_w = jnp.pad(jnp.where(here, weights.reshape(pairs), 0.0)[order], (0, pad))
            sorted_w = checkpoint_name(sorted_w, DISPATCH)

        w_in = self.param(
            "experts_in", nn.initializers.lecun_normal(), (held, d, 2 * self.hidden),
            self.param_dtype,
        ).astype(self.dtype)
        w_out = self.param(
            "experts_out", nn.initializers.lecun_normal(), (held, self.hidden, d),
            self.param_dtype,
        ).astype(self.dtype)
        kv_policy.record_route("forward/moe_experts", "ragged_dot")
        block = _CHECKPOINTED_BLOCK.get()
        if block is not None:
            kept = self._kept_bytes(tokens, chunk * n_chunks, chunk)
            kv_policy.record_route("remat/moe_residuals", "saved", block=block, bytes=kept)

        operands = (u, w_in, w_out, sorted_w, sorted_tok, starts, sizes)
        tok, out = _chunk(0, *operands, chunk, self.dtype, self.activation)
        with jax.named_scope("moe.combine"):
            y = jnp.zeros((tokens, d), jnp.float32).at[tok].add(out)
        if n_chunks > 1:
            y = _further_chunks(y, *operands, count, chunk, self.dtype, self.activation)

        with jax.named_scope("moe.shared"):
            shared = SwiGLU(
                dim=d, hidden=self.hidden * self.shared, dtype=self.dtype,
                param_dtype=self.param_dtype, name="shared",
            )(x) if self.shared else 0.0
            if self.gate_shared:
                shared = shared_gate(shared, nn.Dense(
                    1, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype,
                    name="shared_gate",
                )(x))
        with jax.named_scope("moe.combine"):
            return y.astype(self.dtype).reshape(b, n, d) + shared
