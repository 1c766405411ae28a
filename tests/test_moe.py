"""Mixture-of-experts + expert-parallelism tests (ops/moe.py).

The reference has no MoE; these pin the beyond-parity Switch layer: routing
semantics, capacity overflow, the load-balance aux, DALLE integration, and
ep-sharded-vs-single-device equivalence on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import DALLE
from dalle_pytorch_tpu.ops.moe import MoEFeedForward
from dalle_pytorch_tpu.parallel import (
    create_train_state,
    make_runtime,
    make_train_step,
    params_shardings,
    shard_pytree,
)


class TestMoELayer:
    def make(self, e=4, cap=4.0):
        return MoEFeedForward(dim=16, num_experts=e, mult=2.0, capacity_factor=cap)

    def test_output_shape_and_aux(self):
        moe = self.make()
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 12, 16), jnp.float32)
        params = moe.init(jax.random.key(0), x)["params"]
        out, mut = moe.apply({"params": params}, x, mutable=["moe_aux"])
        assert out.shape == x.shape
        (aux,) = jax.tree_util.tree_leaves(mut["moe_aux"])
        # Switch aux is >= 1 (equals 1 at perfect balance)
        assert float(aux) >= 1.0 - 1e-5

    def test_matches_manual_expert_computation(self):
        """With generous capacity, every token's output must equal
        prob * expert_mlp(token) for its argmax expert."""
        moe = self.make(e=2, cap=8.0)
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(1, 6, 16), jnp.float32)
        params = moe.init(jax.random.key(0), x)["params"]
        out = moe.apply({"params": params}, x)

        gate = np.asarray(params["gate"]["kernel"], np.float64)
        w_in = np.asarray(params["experts_in"], np.float64)
        w_out = np.asarray(params["experts_out"], np.float64)
        xs = np.asarray(x[0], np.float64)
        logits = xs @ gate
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        from math import erf

        for t in range(6):
            eidx = int(np.argmax(probs[t]))
            h = xs[t] @ w_in[eidx]
            h, g = np.split(h, 2)
            act = h * (g * 0.5 * (1 + np.vectorize(erf)(g / np.sqrt(2))))
            expected = probs[t, eidx] * (act @ w_out[eidx])
            np.testing.assert_allclose(
                np.asarray(out[0, t]), expected, atol=1e-4
            )

    def test_capacity_overflow_drops_to_zero(self):
        """With capacity 1 and all tokens routed to one expert, only the
        first token per example gets processed; the rest output exactly 0."""
        moe = MoEFeedForward(dim=8, num_experts=2, mult=2.0, capacity_factor=0.1)
        x = jnp.ones((1, 10, 8), jnp.float32)  # identical tokens, same expert
        params = moe.init(jax.random.key(0), x)["params"]
        out = np.asarray(moe.apply({"params": params}, x))
        assert np.abs(out[0, 0]).max() > 0
        np.testing.assert_array_equal(out[0, 1:], 0.0)


class TestDALLEMoE:
    def make(self, **kw):
        return DALLE(
            dim=32,
            depth=2,
            num_text_tokens=64,
            text_seq_len=8,
            num_image_tokens=32,
            image_fmap_size=4,
            heads=4,
            dim_head=8,
            attn_types=("full",),
            shift_tokens=False,
            ff_experts=4,
            **kw,
        )

    def batch(self, b=4):
        rng = np.random.RandomState(2)
        return (
            jnp.asarray(rng.randint(1, 64, size=(b, 8)), jnp.int32),
            jnp.asarray(rng.randint(0, 32, size=(b, 16)), jnp.int32),
        )

    def test_moe_layers_present_and_train(self):
        dalle = self.make()
        text, image = self.batch()
        params = dalle.init(jax.random.key(0), text, image)["params"]
        # every 2nd layer's ff is an MoE (moe_every=2 default)
        ff1 = params["transformer"]["ff_1"]["fn"]["fn"]
        assert "experts_in" in ff1 and "gate" in ff1
        # dense layers remain dense
        assert "Dense_0" in params["transformer"]["ff_0"]["fn"]["fn"]

        def loss(p):
            out, mut = dalle.apply(
                {"params": p}, text, image, return_loss=True,
                mutable=["moe_aux"],
            )
            return out + 1e-2 * sum(jax.tree_util.tree_leaves(mut["moe_aux"]))

        l, g = jax.jit(jax.value_and_grad(loss))(params)
        assert np.isfinite(float(l))
        gate_g = g["transformer"]["ff_1"]["fn"]["fn"]["gate"]["kernel"]
        assert np.abs(np.asarray(gate_g)).max() > 0  # aux reaches the gate

    def test_ep_sharded_matches_single_device(self):
        dalle = self.make()
        text, image = self.batch(b=8)
        params = dalle.init(jax.random.key(0), text, image)["params"]

        def loss(p):
            return dalle.apply({"params": p}, text, image, return_loss=True)

        l0, g0 = jax.jit(jax.value_and_grad(loss))(params)

        rt = make_runtime(dp=2, ep=4)
        sh = params_shardings(params, rt.mesh)
        p_sh = shard_pytree(params, sh)
        # expert leaves actually shard over ep
        exp = p_sh["transformer"]["ff_1"]["fn"]["fn"]["experts_in"]
        assert exp.addressable_shards[0].data.shape[0] == 1  # 4 experts / ep=4
        l1, g1 = jax.jit(
            jax.value_and_grad(loss), in_shardings=(sh,), out_shardings=(None, sh)
        )(p_sh)

        np.testing.assert_allclose(float(l0), float(l1), rtol=2e-5)
        for a, e in zip(
            jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g0)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(e), atol=1e-5, rtol=1e-3
            )

    def test_moe_train_step_reduces_loss(self):
        import optax

        rt = make_runtime(dp=2, ep=4)
        dalle = self.make()
        text, image = self.batch(b=8)
        batch = {"text": text, "image": image}
        params = dalle.init(jax.random.key(0), text, image)["params"]
        opt = optax.adam(1e-3)
        state, shardings = create_train_state(params, opt, rt)

        def loss_fn(p, b, rng):
            out, mut = dalle.apply(
                {"params": p}, b["text"], b["image"], return_loss=True,
                mutable=["moe_aux"],
            )
            return out + 1e-2 * sum(jax.tree_util.tree_leaves(mut["moe_aux"]))

        step = make_train_step(loss_fn, opt, rt, shardings)
        losses = []
        for i in range(3):
            state, loss = step(state, batch, jax.random.key(i))
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_moe_decode_runs(self):
        """KV-decode with MoE layers: single-token routing must work."""
        from dalle_pytorch_tpu.models import generate_image_tokens

        dalle = self.make()
        text, image = self.batch(b=2)
        params = dalle.init(jax.random.key(0), text, image)["params"]
        toks = generate_image_tokens(dalle, params, text, jax.random.key(1))
        seq = np.asarray(toks)
        assert seq.shape == (2, 16)
        assert (seq >= 0).all() and (seq < 32).all()


class TestMoEMemoryModes:
    """MoE must compose with O(1)-activation-memory execution: the Switch
    aux loss rides the (delta, aux) channel of the pure-closure block fns
    (ops/reversible.py) instead of sow, so remat/reversible training sees
    the identical load-balance objective (VERDICT r3 ask #4)."""

    def make(self, **kw):
        return DALLE(
            dim=32, depth=2, num_text_tokens=30, text_seq_len=6,
            num_image_tokens=16, image_fmap_size=3, heads=2, dim_head=8,
            attn_types=("full",), shift_tokens=False,
            ff_experts=4, moe_every=2, **kw,
        )

    def batch(self):
        rng = np.random.RandomState(0)
        return (
            jnp.asarray(rng.randint(1, 30, (2, 6)), jnp.int32),
            jnp.asarray(rng.randint(0, 16, (2, 9)), jnp.int32),
        )

    def _run(self, model, params, text, image):
        def loss_fn(p):
            out, mut = model.apply(
                {"params": p}, text, image, return_loss=True,
                mutable=["moe_aux"],
            )
            aux = sum(jax.tree_util.tree_leaves(mut["moe_aux"]))
            return out + 1e-2 * aux, (out, aux)

        (_, (loss, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return float(loss), float(aux), grads

    def test_remat_matches_sequential_exactly(self):
        text, image = self.batch()
        seq = self.make()
        params = seq.init(jax.random.key(0), text, image)["params"]
        l0, a0, g0 = self._run(seq, params, text, image)
        l1, a1, g1 = self._run(self.make(remat=True), params, text, image)
        np.testing.assert_allclose(l0, l1, rtol=1e-6)
        np.testing.assert_allclose(a0, a1, rtol=1e-5)
        for a, e in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g0)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e), atol=1e-5, rtol=1e-3)

    def test_reversible_trains_and_aux_reaches_gate(self):
        text, image = self.batch()
        rev = self.make(reversible=True)
        params = rev.init(jax.random.key(0), text, image)["params"]
        loss, aux, grads = self._run(rev, params, text, image)
        assert np.isfinite(loss) and aux >= 1.0 - 1e-5
        flat = jax.tree_util.tree_leaves(grads)
        assert all(np.isfinite(np.asarray(g)).all() for g in flat)
        gate_g = grads["transformer"]["ff_1"]["fn"]["fn"]["gate"]["kernel"]
        assert np.abs(np.asarray(gate_g)).max() > 0

    def test_reversible_custom_vjp_forward_matches_direct_wiring(self):
        """The custom-VJP primal (training path) must produce the same loss
        and aux as the bound direct wiring (init path) on identical params."""
        text, image = self.batch()
        rev = self.make(reversible=True)
        out, vars0 = jax.jit(
            lambda k: rev.init_with_output(k, text, image, return_loss=True),
        )(jax.random.key(0))
        params = vars0["params"]
        aux0 = sum(jax.tree_util.tree_leaves(vars0["moe_aux"]))
        loss1, mut = rev.apply(
            {"params": params}, text, image, return_loss=True, mutable=["moe_aux"]
        )
        aux1 = sum(jax.tree_util.tree_leaves(mut["moe_aux"]))
        np.testing.assert_allclose(float(out), float(loss1), rtol=1e-5)
        np.testing.assert_allclose(float(aux0), float(aux1), rtol=1e-5)


# ------------------------------------------------ the routed-expert layer
# ops/moe.py:RoutedExperts (sigmoid scores, top-k of ALL experts, a selection
# bias, a shared expert, told which experts it holds) against the plain
# reference's DENSE loop (benchmarks/reference_moe.py: every token's weight or
# zero times every held expert's SwiGLU of every token; imports nothing of the
# program) for every count a layer can meet.

from benchmarks import reference_moe  # noqa: E402
from dalle_pytorch_tpu.ops import moe as moe_ops  # noqa: E402
from dalle_pytorch_tpu.ops.moe import RoutedExperts, balanced_bias, route  # noqa: E402

TOTAL, PER_TOKEN, DIM, WIDTH = 8, 2, 32, 16


def routed_layer(held, **over):
    return RoutedExperts(dim=DIM, hidden=WIDTH, experts_total=TOTAL, experts_held=held,
                         per_token=PER_TOKEN, shared=1, scaling=2.5, **over)


def whole_layer_params(seed=0, bias=None):
    """The uncut layer's weights: all TOTAL experts."""
    x = jax.random.normal(jax.random.key(seed), (2, 20, DIM))
    p = routed_layer((0, TOTAL)).init(jax.random.key(seed + 1), x)["params"]
    drawn = 0.02 * jax.random.normal(jax.random.key(seed + 2), (TOTAL,))
    return x, {**p, "e_score_correction_bias": drawn if bias is None else jnp.asarray(bias)}


def share_of(p, held):
    lo, hi = held
    return {**p, "experts_in": p["experts_in"][lo:hi], "experts_out": p["experts_out"][lo:hi]}


def reference_layer(x, p, held):
    """(output, pairs routed here) of the reference, a sequence at a time."""
    cfg = dict(num_experts_per_tok=PER_TOKEN, routed_scaling_factor=2.5,
               n_routed_experts=held[1] - held[0], experts_held={"range": held, "of": TOTAL})
    with jax.default_matmul_precision("highest"):
        rows = [reference_moe._experts(row, p, cfg, "f32") for row in x]
    return jnp.stack([y for y, _ in rows]), sum(int(jnp.sum(n[held[0]:held[1]])) for _, n in rows)


def far(bias_on, value):
    bias = np.zeros(TOTAL, np.float32)
    bias[list(bias_on)] = value
    return bias


CASES = {
    "no_pair_routed_here": dict(held=(2, 4), bias=far((2, 3), -10.0), pairs=0),
    "all_pairs_routed_here": dict(held=(0, TOTAL), bias=None, pairs=2 * 20 * PER_TOKEN),
    "a_skewed_router": dict(held=(2, 4), bias=far((2,), 10.0), pairs=None),
    "an_even_router": dict(held=(4, 8), bias=None, pairs=None),
}


@pytest.mark.parametrize("chunk_rows", [None, 8], ids=["one_chunk", "chunks_of_8"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_routed_experts_match_the_references_dense_loop(case, chunk_rows, monkeypatch):
    """Output, pair count and every gradient, for every count a layer can
    meet; in chunks of 8 rows the count passes the bounded buffer and the
    layer takes its loop over further chunks: exact either way, no pair
    dropped."""
    spec = CASES[case]
    held = spec["held"]
    x, whole = whole_layer_params(bias=spec["bias"])
    p = share_of(whole, held)
    if chunk_rows is not None:
        monkeypatch.setattr(moe_ops, "HEADROOM", 0.0)
        monkeypatch.setattr(moe_ops, "CHUNK_MULTIPLE", chunk_rows)
    layer = routed_layer(held)
    target = jax.random.normal(jax.random.key(5), x.shape)

    def program(p, x):
        y, sown = layer.apply({"params": p}, x, mutable=["moe_stats"])
        return jnp.sum(y * target), (y, sown["moe_stats"]["load"][0])

    with jax.default_matmul_precision("highest"):
        (_, (y, load)), grads = jax.value_and_grad(program, argnums=(0, 1), has_aux=True)(p, x)
        want, pairs = reference_layer(x, p, held)
        want_grads = jax.grad(
            lambda p, x: jnp.sum(reference_layer(x, p, held)[0] * target), argnums=(0, 1)
        )(p, x)
    assert int(jnp.sum(load)) == 2 * 20 * PER_TOKEN           # every pair goes somewhere
    assert int(jnp.sum(load[held[0]:held[1]])) == pairs
    if spec["pairs"] is not None:
        assert pairs == spec["pairs"]
    if case == "a_skewed_router":
        assert int(load[2]) == 2 * 20                             # every token chose expert 2
    assert float(jnp.max(jnp.abs(y - want))) < 2e-5
    flat, want_flat = jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)
    for g, w in zip(flat, want_flat):
        assert float(jnp.max(jnp.abs(g - w))) < 1e-4 * (1.0 + float(jnp.max(jnp.abs(w)))), g.shape
    assert not np.any(np.asarray(grads[0]["e_score_correction_bias"]))   # chooses, never trained


def test_the_shares_partial_results_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide, section 4: four chips hold
    two experts each; their partial results, with the shared expert (which
    every chip computes alike) counted once, are the uncut reference layer."""
    x, whole = whole_layer_params(seed=3)
    uncut, pairs = reference_layer(x, whole, (0, TOTAL))
    assert pairs == 2 * 20 * PER_TOKEN
    with jax.default_matmul_precision("highest"):
        shared = jnp.stack([
            reference_moe._swiglu(row, whole["shared"]["Dense_0"]["kernel"],
                                  whole["shared"]["Dense_1"]["kernel"], "f32") for row in x
        ])
        shares = [(lo, lo + 2) for lo in range(0, TOTAL, 2)]
        parts = [routed_layer(held).apply({"params": share_of(whole, held)}, x) for held in shares]
    total = sum(part - shared for part in parts) + shared
    assert float(jnp.max(jnp.abs(total - uncut))) < 5e-5
    # and no share alone is the layer
    assert float(jnp.max(jnp.abs(parts[0] - uncut))) > 1e-2


def test_the_selection_bias_moves_against_the_load():
    """``noaux_tc``: down by the speed where an expert was sent more than the
    mean, up where fewer, unmoved at the mean; the reference's rule gives the
    same."""
    bias = jnp.asarray([0.5, -0.5, 0.0, 0.25])
    load = jnp.asarray([30, 10, 20, 20])
    np.testing.assert_allclose(
        np.asarray(balanced_bias(bias, load, 0.01)), [0.49, -0.49, 0.0, 0.25], rtol=1e-6
    )
    flat = {("l", "e_score_correction_bias"): bias}
    reference_moe.balance(flat, {"l": load}, 0.01)
    np.testing.assert_allclose(
        np.asarray(flat[("l", "e_score_correction_bias")]), [0.49, -0.49, 0.0, 0.25], rtol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(flat[("l", "tokens_per_expert")]), np.asarray(load))


def test_the_weights_are_normalised_over_all_the_chosen_and_the_bias_only_chooses():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(0), (50, TOTAL)))
    bias = jnp.asarray(far((5,), 10.0))
    chosen, weights = route(scores, bias, PER_TOKEN, 2.5)
    assert bool(jnp.all(jnp.any(chosen == 5, axis=-1)))           # the bias chose expert 5
    np.testing.assert_allclose(np.asarray(jnp.sum(weights, -1)), 2.5, rtol=1e-6)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)         # the scores, not scores + bias
    np.testing.assert_allclose(
        np.asarray(weights), np.asarray(2.5 * picked / picked.sum(-1, keepdims=True)), rtol=1e-6
    )
