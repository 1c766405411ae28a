"""The sixth family through the language-model path: ``kimi_linear`` (Kimi
Delta Attention, the delta rule with a decay per KEY CHANNEL, three to one
with latent attention that has no positional term and no query compression; a
dense SwiGLU first, then sigmoid-routed experts of which a range is held, a
selection bias and a shared expert) held to benchmarks/reference_kda.py:
float32, the delta rule as the sequential recurrence, attention one head at a
time, the experts as a dense loop over the held ones, the router's choice by
rank; it imports nothing of the program.

The rule's two forms (ops/kda.py: the XLA form and the three Pallas kernels,
interpreted on the CPU at shapes they are eligible for) are held to the
sequential recurrence in the output and in all five cotangents, the
per-channel ``dg`` among them, at gates of the family's initial strength over
whole chunks (where a factored ``exp(-G)`` would overflow float32) and across
a tail that is not whole chunks.
"""

import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from benchmarks import costs, costs_kda, reference_kda, weights_kda
from benchmarks.drivers import train_kda as kda_driver
from benchmarks.drivers import train_lm as driver
from benchmarks.drivers.train import worst_leaf_gap
from dalle_pytorch_tpu.models.lm import CausalLM
from dalle_pytorch_tpu.ops import gdn, kda, kv_policy
from dalle_pytorch_tpu.ops.attention import LatentAttention
from dalle_pytorch_tpu.ops.moe import RoutedExperts

ROOT = pathlib.Path(__file__).resolve().parent.parent
CELL = costs.load_config("kimi-linear-48b-a3b-d5-ep32")
N = 72          # a chunk of 64 and a padded tail
CFG = {**CELL, **json.loads((ROOT / "benchmarks/rehearsal_kda.json").read_text())["config"]}
BUFFERS = kda_driver.BUFFERS


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def leaf_gaps(got, want) -> dict:
    flat_g, flat_w = traverse_util.flatten_dict(got), traverse_util.flatten_dict(want)
    return {
        "/".join(k): float(jnp.max(jnp.abs(flat_g[k] - w)) / (jnp.max(jnp.abs(w)) + 1e-12))
        for k, w in flat_w.items()
    }


# ------------------------------------------------------------ the rule


def rule_inputs(n, heads, d, seed=0, strength=1.6):
    """q, k normalised per head (q scaled), a per-channel log-decay uniform
    in [-2 strength, 0]: at 1.6 (the family's initial values reach about
    -1.6 a position: A up to 16, dt up to 0.1) a chunk of 64 sums to about
    -100, past float32's e^88."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = (gdn.l2norm(jax.random.normal(ks[0], (2, n, heads, d))) * d**-0.5).reshape(2, n, heads * d)
    k = gdn.l2norm(jax.random.normal(ks[1], (2, n, heads, d))).reshape(2, n, heads * d)
    v = jax.random.normal(ks[2], (2, n, heads * d))
    g = -2 * strength * jax.random.uniform(ks[3], (2, n, heads * d))
    beta = jax.random.uniform(ks[4], (2, n, heads))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (2, n, heads * d))


def recurrence(q, k, v, g, beta, heads):
    """The reference's recurrence over (b, n, heads x width) operands."""
    b, n, _ = q.shape
    split = lambda t: t.reshape(n, heads, -1)
    return jnp.stack([
        reference_kda.kda_recurrence(split(q[i]), split(k[i]), split(v[i]), split(g[i]), beta[i]).reshape(n, -1)
        for i in range(b)
    ])


def assert_follows(got_fn, want_fn, args, cotangent, tol):
    got, vjp = jax.vjp(got_fn, *args)
    want, want_vjp = jax.vjp(want_fn, *args)
    assert float(jnp.max(jnp.abs(got - want))) <= tol * float(jnp.max(jnp.abs(want)))
    for name, a, r in zip(("q", "k", "v", "g", "beta"), vjp(cotangent), want_vjp(cotangent)):
        assert a.shape == r.shape, name
        assert float(jnp.max(jnp.abs(a - r))) <= tol * max(float(jnp.max(jnp.abs(r))), 1.0), name


# (form, heads, width, chunk, positions, strength): the XLA form at widths the
# kernels do not take; the kernels at whole lane tiles, the family's chunk of
# 64 (sub-chunks of 16 against earlier ones AND the diagonal blocks) and a
# chunk of 16 (diagonal blocks alone); tails of 8 and 22 positions. A strong
# gate in a chunk of 16 is 6 a position, so that its chunk too sums past -88
CASES = [
    ("xla", 2, 16, 16, 40, 6.0),
    ("xla", 2, 16, 32, 70, 0.02),
    ("kernels", 2, 128, 64, 150, 1.6),
    ("kernels", 1, 128, 64, 128, 0.02),
    ("kernels", 2, 128, 16, 40, 6.0),
]


@pytest.mark.parametrize("form,heads,d,chunk,n,strength", CASES)
def test_both_forms_match_the_sequential_recurrence_and_every_cotangent(form, heads, d, chunk, n, strength):
    """Float32 throughout (the products at ``highest``): the chunked forms
    reorder sums of a few hundred terms, so the outputs and the cotangents
    agree to 1e-5 of their largest entry; the strong gates decay the state
    to nothing within a chunk, the weak ones carry it across every chunk."""
    args, cotangent = rule_inputs(n, heads, d, strength=strength)
    if strength > 1:
        total = kda.chunk_log_decay(jnp.pad(args[3], ((0, 0), (0, -n % chunk), (0, 0))), chunk)
        assert float(jnp.min(total)) < -88.7        # exp(-G) of a chunk is past float32
    kv_policy.ROUTE_LOG.clear()
    assert_follows(
        lambda *a: kda.kimi_delta_rule(*a, heads, chunk, jnp.float32),
        lambda *a: recurrence(*a, heads), args, cotangent, tol=1e-5,
    )
    route = [r["impl"] for r in kv_policy.ROUTE_LOG if r["site"] == "forward/delta_rule"]
    assert route == ["kda_chunk" if form == "kernels" else "xla"]


def test_the_kernels_match_the_xla_form(monkeypatch):
    """The same operands through both forms: the XLA form is the kernels'
    oracle, float32 to 1e-5 as above."""
    args, cotangent = rule_inputs(136, 2, 128, seed=3)
    rule = lambda *a: kda.kimi_delta_rule(*a, 2, 64, jnp.float32)
    out, vjp = jax.vjp(rule, *args)
    got = vjp(cotangent)
    monkeypatch.setattr(kda, "kda_kernels_eligible", lambda *shape: False)
    want, want_vjp = jax.vjp(rule, *args)
    assert float(jnp.max(jnp.abs(out - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))
    for a, r in zip(got, want_vjp(cotangent)):
        assert float(jnp.max(jnp.abs(a - r))) <= 1e-5 * max(float(jnp.max(jnp.abs(r))), 1.0)


@pytest.mark.parametrize("d,chunk", [(16, 16), (128, 64)], ids=["xla", "kernels"])
def test_a_gate_equal_across_channels_is_the_scalar_rule_of_ops_gdn(d, chunk):
    """``g`` the same over a head's channels is Gated DeltaNet's decay: the
    two modules' chunked rules agree (float32, to 1e-5), one key head a
    value head."""
    args, _ = rule_inputs(100, 2, d, seed=5, strength=0.3)
    q, k, v, _, beta = args
    scalar = -0.6 * jax.random.uniform(jax.random.key(9), (2, 100, 2))
    g = jnp.repeat(scalar, d, axis=-1)
    got = kda.kimi_delta_rule(q, k, v, g, beta, 2, chunk, jnp.float32)
    want = gdn.gated_delta_rule(q, k, v, scalar, beta, 2, chunk, jnp.float32)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))


def test_a_decay_that_differs_by_channel_is_not_the_mean_decay():
    """What ``mean_gate`` stands for: the per-channel decay replaced by its
    mean over the channels moves the output by far more than rounding."""
    args, _ = rule_inputs(72, 2, 16, seed=6, strength=0.5)
    q, k, v, g, beta = args
    mean = jnp.repeat(g.reshape(2, 72, 2, 16).mean(-1), 16, axis=-1)
    got = kda.kimi_delta_rule(q, k, v, g, beta, 2, 16)
    other = kda.kimi_delta_rule(q, k, v, mean, beta, 2, 16)
    assert float(jnp.linalg.norm(got - other) / jnp.linalg.norm(got)) > 0.05


def test_eligibility_is_read_from_the_shape():
    assert kda.kda_kernels_eligible(64, 128, 128)
    assert kda.kda_kernels_eligible(16, 256, 128)
    assert not kda.kda_kernels_eligible(64, 64, 128)     # keys not whole lane tiles
    assert not kda.kda_kernels_eligible(48, 128, 128)    # not a power of two
    assert not kda.kda_kernels_eligible(8, 128, 128)     # not whole sub-chunks
    assert not kda.kda_kernels_eligible(256, 128, 128)   # T and P would not share a row of lanes


def test_the_mixers_parameters_and_scopes():
    mixer = kda.KimiDeltaAttention(dim=64, heads=4, head_dim=16)
    x = jax.random.normal(jax.random.key(0), (1, 24, 64))
    p = mixer.init(jax.random.key(1), x)["params"]
    shapes = {"/".join(k): v.shape for k, v in traverse_util.flatten_dict(p).items()}
    assert shapes == {
        "in_proj_qkv/kernel": (64, 192), "in_proj_b/kernel": (64, 4), "f_a/kernel": (64, 16),
        "f_b/kernel": (16, 64), "g_a/kernel": (64, 16), "g_b/kernel": (16, 64),
        "out_proj/kernel": (64, 64), "conv/kernel": (4, 192), "A_log": (4,), "dt_bias": (64,),
        "norm_scale": (16,),
    }
    assert 0.0 <= float(p["A_log"].min()) and float(p["A_log"].max()) <= np.log(16.0) + 1e-6
    dt = jax.nn.softplus(p["dt_bias"])
    assert 1e-3 - 1e-7 <= float(dt.min()) and float(dt.max()) <= 0.1 + 1e-7
    text = jax.jit(lambda p, x: mixer.apply({"params": p}, x)).lower(p, x).as_text(debug_info=True)
    for scope in ("linattn.proj", "linattn.gate", "linattn.conv", "linattn.kda", "linattn.norm"):
        assert scope in text, scope


# ------------------------------------------------------------ latent attention

MLA = dict(num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           kv_lora_rank=32, rms_norm_eps=1e-5)


@pytest.mark.parametrize("n,route", [(256, "blocked_flash"), (24, "dense_masked")])
def test_latent_attention_without_positions_or_query_latent_matches_the_reference(n, route):
    layer = LatentAttention(dim=64, heads=4, q_rank=None, kv_rank=32, nope_dim=16, rope_dim=8,
                            v_dim=16, rotary=False, eps=1e-5)
    x = jax.random.normal(jax.random.key(0), (2, n, 64))
    p = layer.init(jax.random.key(1), x)["params"]
    assert "to_q" in p and "to_q_a" not in p and "q_norm" not in p
    kv_policy.ROUTE_LOG.clear()
    out, vjp = jax.vjp(jax.jit(lambda p, x: layer.apply({"params": p}, x)), p, x)
    assert [r["impl"] for r in kv_policy.ROUTE_LOG if r["site"] == "forward/mla"] == [route]
    reference = lambda p, x: jnp.stack([reference_kda._mla(row, p, MLA, "f32") for row in x])
    want, want_vjp = jax.vjp(jax.jit(reference), p, x)
    # float32 against float32: the flash kernels' blocked softmax reorders sums
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5 * float(jnp.max(jnp.abs(want)))
    cotangent = jax.random.normal(jax.random.key(3), out.shape)
    gaps = leaf_gaps(vjp(cotangent)[0], want_vjp(cotangent)[0])
    assert max(gaps.values()) < 2e-4, gaps
    # positions move nothing: a row shifted by one keeps its outputs' order
    rotated = LatentAttention(dim=64, heads=4, q_rank=None, kv_rank=32, nope_dim=16, rope_dim=8,
                              v_dim=16, rotary=True, eps=1e-5)
    assert float(jnp.max(jnp.abs(rotated.apply({"params": p}, x) - out))) > 1e-3


# ------------------------------------------------------------ the expert layer

EXPERTS = dict(num_experts=4, num_experts_per_token=2, moe_intermediate_size=32, hidden_size=64,
               routed_scaling_factor=2.446)


def expert_layer(held=(2, 6), total=8):
    return RoutedExperts(dim=64, hidden=32, experts_total=total, experts_held=held, per_token=2,
                         scaling=2.446, scoring="sigmoid", shared=1)


def expert_weights(seed=0, n=48):
    x = jax.random.normal(jax.random.key(seed), (2, n, 64))
    shapes = jax.eval_shape(expert_layer((0, 8)).init, jax.random.key(0), x)["params"]
    return x, weights_kda.make_params(shapes, seed, jnp.float32)


def held_part(params, lo, hi):
    return {**params, "experts_in": params["experts_in"][lo:hi], "experts_out": params["experts_out"][lo:hi]}


def test_the_expert_layer_matches_the_dense_loop():
    x, whole = expert_weights()
    p = held_part(whole, 2, 6)
    cfg = {**EXPERTS, "experts_held": {"range": [2, 6], "of": 8}}
    assert {"e_score_correction_bias", "tokens_per_expert", "shared"} <= set(p)
    out, vjp = jax.vjp(lambda p, x: expert_layer().apply({"params": p}, x), p, x)
    ref = lambda p, x: reference_kda._experts(x.reshape(-1, 64), p, cfg, "f32")[0]
    want, want_vjp = jax.vjp(ref, p, x)
    # float32 both; the program groups rows by expert, the reference loops
    np.testing.assert_allclose(out.reshape(-1, 64), want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    cotangent = jax.random.normal(jax.random.key(3), out.shape)
    gaps = {k: v for k, v in leaf_gaps(vjp(cotangent)[0], want_vjp(cotangent.reshape(-1, 64))[0]).items()
            if not k.endswith(BUFFERS)}
    assert max(gaps.values()) < 2e-4, gaps


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """model-configs guide section 4: four chips hold two experts each of
    eight; every share routes over all eight (the selection bias chooses,
    the weights are normalised over all chosen) and computes its own part;
    the parts, with what every chip computes alike (the shared expert)
    counted once, are the uncut reference's layer."""
    x, whole = expert_weights(seed=3)
    rows = x.reshape(-1, 64)
    uncut, load = reference_kda._experts(rows, whole, {**EXPERTS, "num_experts": 8}, "f32")
    none_held = {**EXPERTS, "experts_held": {"range": [0, 1], "of": 8}, "num_experts": 1}
    alone = dict(whole, experts_in=whole["experts_in"][:1] * 0, experts_out=whole["experts_out"][:1] * 0)
    shared, _ = reference_kda._experts(rows, alone, none_held, "f32")
    total = -3 * shared                      # every share adds it; it counts once
    for lo in (0, 2, 4, 6):
        part = expert_layer((lo, lo + 2)).apply({"params": held_part(whole, lo, lo + 2)}, x)
        total = total + part.reshape(-1, 64)
    # float32: four partial sums against one
    np.testing.assert_allclose(total, uncut, atol=5e-5 * float(jnp.max(jnp.abs(uncut))))
    assert int(load.sum()) == 2 * 48 * 2


# ------------------------------------------------------------ the whole model


def model_and_params(remat=False, seed=5, **over):
    cfg = {**CFG, **over}
    lm = CausalLM.from_config(cfg, seq_len=N, remat=remat)
    ids = jax.random.randint(jax.random.key(1), (2, N), 0, cfg["vocab_size"])
    shapes = jax.eval_shape(lm.init, jax.random.key(0), ids)["params"]
    return lm, weights_kda.make_params(shapes, seed, jnp.float32), ids, cfg


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_leafs_gradient_match_the_reference(remat):
    lm, params, ids, cfg = model_and_params(remat=remat)
    assert lm.layer_types == ("kda",) * 3 + ("mla", "kda")
    assert lm.ff_types == ("dense",) + ("experts",) * 4 and lm.experts_held == (2, 6) and not lm.tie_head
    (loss, sown), grads = jax.jit(jax.value_and_grad(lm.loss_and_loads, has_aux=True))(params, ids)
    (want, want_loads), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference_kda.loss(p, cfg, ids), has_aux=True))(params)
    # float32 both: the loss to a few roundings, every leaf's gradient to 2e-4 of its largest entry
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)
    gaps = {k: v for k, v in leaf_gaps(grads, want_grads).items() if not k.endswith(BUFFERS)}
    assert max(gaps.values()) < 2e-4, max(gaps.items(), key=lambda kv: kv[1])
    # the step's counters: what every layer sent each of ALL experts, and the bias moved against it
    balanced = lm.balance(params, sown)
    flat_p, flat_b = traverse_util.flatten_dict(params), traverse_util.flatten_dict(balanced)
    reference_kda.balance(flat_p, {k: np.asarray(v) for k, v in want_loads.items()}, cfg["bias_update_speed"])
    for i in range(1, 5):
        path = ("transformer", f"ff_{i}", "fn")
        np.testing.assert_array_equal(flat_b[path + ("tokens_per_expert",)], flat_p[path + ("tokens_per_expert",)])
        np.testing.assert_allclose(flat_b[path + ("e_score_correction_bias",)],
                                   flat_p[path + ("e_score_correction_bias",)], atol=1e-7)
        assert float(flat_b[path + ("tokens_per_expert",)].sum()) == ids.size * cfg["num_experts_per_token"]


# ------------------------------------------------------------ the configuration


def test_the_cells_file_reads_and_counts_602_435_456_parameters():
    lm = CausalLM.from_config(CELL, seq_len=16384)
    assert lm.layer_types == ("kda", "kda", "kda", "mla", "kda")
    assert lm.ff_types == ("dense",) + ("experts",) * 4
    assert (lm.experts_total, lm.experts_held, lm.experts_per_token, lm.experts_hidden,
            lm.experts_shared, lm.experts_scaling, lm.experts_scoring) == (256, (0, 8), 8, 1024, 1, 2.446, "sigmoid")
    assert (lm.mla_q_rank, lm.mla_rotary, lm.mla_kv_rank, lm.mla_nope_dim, lm.mla_rope_dim,
            lm.mla_v_dim) == (None, False, 512, 128, 64, 128)
    assert (lm.linattn_key_heads, lm.linattn_key_dim, lm.linattn_conv, lm.ff_hidden, lm.bias_update_speed) == (32, 128, 4, 9216, 0.001)
    shapes = jax.eval_shape(lm.init, jax.random.key(0), jnp.zeros((1, 16384), jnp.int32))["params"]
    flat = traverse_util.flatten_dict(shapes)
    total = sum(int(np.prod(x.shape)) for x in flat.values())
    # by part: KDA 39,514,272 x 4, MLA 29,114,880, dense 63,700,992, an expert layer's
    # held part 64,291,328 x 4 (its count of pairs included), embedding + head, 11 norms
    assert total == costs_kda.param_count(CELL) == 602_435_456
    mixer = sum(int(np.prod(x.shape)) for p, x in flat.items() if p[:2] == ("transformer", "mixer_0")
                and p[2] == "fn")
    assert mixer == costs_kda.kda_params(CELL) == 39_514_272
    assert flat[("tok_emb", "embedding")].shape == flat[("lm_head",)].shape == (20480, 2304)
    assert set(CELL["reduced"]) == set(CELL["published"])
    # the source's own file, with none of this program's keys: every expert held, 27 layers
    source = {k: v for k, v in CELL.items() if k not in (
        "experts_held", "published", "assumed", "deployment", "reduced", "not_run", "not_read")}
    whole = CausalLM.from_config({**source, **CELL["published"]}, seq_len=16384)
    assert (whole.experts_total, whole.experts_held, whole.depth, whole.vocab_size) == (256, (0, 256), 27, 163840)
    assert whole.layer_types[:8] == ("kda",) * 3 + ("mla",) + ("kda",) * 3 + ("mla",)
    assert whole.layer_types[-3:] == ("kda", "kda", "mla")     # the published lists end with 26 and 27


@pytest.mark.parametrize("key,value", [
    ("num_expert_group", 2), ("topk_group", 2), ("moe_router_activation_func", "softmax"),
    ("q_lora_rank", 1536), ("mla_use_nope", False), ("num_nextn_predict_layers", 1),
    ("moe_renormalize", False), ("tie_word_embeddings", True), ("moe_layer_freq", 2),
    ("experts_held", {"range": [0, 8], "of": 8}),
])
def test_config_keys_this_model_cannot_run_are_refused(key, value):
    with pytest.raises(ValueError, match=key):
        CausalLM.from_config({**CFG, key: value}, seq_len=N)


@pytest.mark.parametrize("kda_layers,full", [
    ([1, 2, 3, 4], [4, 5]),     # layer 4 in both
    ([1, 2, 3], [4]),           # layer 5 in neither
    ([0, 1, 2, 4], [3]),        # 0-based by mistake
])
def test_the_layer_lists_are_one_based_and_cover_every_layer_once(kda_layers, full):
    lin = dict(CFG["linear_attn_config"], kda_layers=kda_layers, full_attn_layers=full)
    with pytest.raises(ValueError, match="linear_attn_config"):
        CausalLM.from_config({**CFG, "linear_attn_config": lin}, seq_len=N)


def test_the_cell_takes_every_kernel_route():
    """At the cell's size the KDA layers take the three kernels and the
    latent attention the blocked flash kernels."""
    lm = CausalLM.from_config(CELL, seq_len=16384, dtype=jnp.bfloat16, remat=True)
    kv_policy.ROUTE_LOG.clear()
    jax.eval_shape(lm.init, jax.random.key(0), jnp.zeros((1, 16384), jnp.int32))
    routes = {r["site"]: r["impl"] for r in kv_policy.ROUTE_LOG}
    assert routes["forward/delta_rule"] == "kda_chunk"
    assert routes["forward/mla"] == "blocked_flash"
    assert routes["forward/moe_experts"] == "ragged_dot"


def test_the_steps_arithmetic():
    need = costs_kda.train_step(CELL, 1, 16384)
    # the configuration's arithmetic (PERF.md section 4): 42.1 TFLOP a step forward and backward
    assert abs(need["total"] - 42.09e12) < 0.01e12
    assert abs(need["kda"] - 0.835e12) < 0.001e12
    assert abs(need["attention"] - 8.247e12) < 0.001e12
    assert need["pairs_here"] == 4 * 16384 * 8 * 8 / 256


# --------------------------------------------------------- three train steps


def step_ctx(seed=11, control=None, **mix):
    base = dict(rows=1, tokens=N, document_tokens={"min": 4, "max": N}, mesh={"dp": 1},
                learning_rate=3e-4, clip_grad_norm=0.5, remat=True, check_steps=3)
    return types.SimpleNamespace(cfg=CFG, mix={**base, **mix}, seed=seed, chips=1,
                                 control=control, facts={})


# the sound program stays under every one of these (float32 on the CPU); a
# control has to pass at least one
LIMITS = {"loss": 1e-5, "grad": 1e-4, "change": 1e-3}


def three_step_gaps(program, ref) -> dict:
    gaps = {f"loss{i}": abs(p - r) / abs(r) for i, (p, r) in
            enumerate(zip(program["loss"], ref["loss"]), 1)}
    gaps["grad"] = worst_leaf_gap(program["grad"], ref["grad"])[0]
    gaps["change"] = worst_leaf_gap(program["change"], ref["change"])[0]
    return gaps


def over_a_limit(gaps: dict) -> bool:
    return (max(gaps[k] for k in ("loss1", "loss2", "loss3")) > LIMITS["loss"]
            or gaps["grad"] > LIMITS["grad"] or gaps["change"] > LIMITS["change"])


@pytest.fixture(scope="module")
def reference_three_steps():
    ctx = step_ctx()
    _, shapes = driver._build(ctx)
    fed = [driver.packed_batch(ctx.mix, ctx.cfg, ctx.seed, s) for s in range(3)]
    with jax.default_matmul_precision("highest"):
        return shapes, fed, kda_driver.reference_steps(ctx, shapes, fed, keep_gradient=True)


def test_three_steps_of_make_train_step_match_the_references_three(reference_three_steps):
    _, _, ref = reference_three_steps
    job = kda_driver.Job(step_ctx())
    program = job.first_steps()
    assert job.steps == 3 and int(job.state.step) == 3 and int(job.state.skipped) == 0
    assert not any(leaf.endswith(BUFFERS) for leaf in program["change"])
    gaps = three_step_gaps(program, ref)
    assert not over_a_limit(gaps), gaps
    assert program["pairs"] == ref["pairs"]            # no pair dropped
    for leaf, bias in ref["bias"].items():              # the selection bias moved alike
        np.testing.assert_allclose(program["bias"][leaf], bias, atol=1e-6)


@pytest.mark.parametrize("control", kda_driver.REFERENCE_CONTROLS)
def test_each_control_fails_the_three_steps(control, reference_three_steps):
    shapes, fed, ref = reference_three_steps
    stand_in = {"fp8": dict(mode="fp8"), "half_batch": dict(positions=N // 2),
                "mean_gate": dict(gate="mean")}[control]
    program = kda_driver.reference_steps(step_ctx(), shapes, fed, **stand_in)
    assert over_a_limit(three_step_gaps(program, ref))


def test_the_leaves_have_sharding_rules_and_a_step_on_a_mesh_matches_one_chip():
    from jax.sharding import PartitionSpec as P
    from dalle_pytorch_tpu.parallel import make_runtime
    from dalle_pytorch_tpu.parallel.sharding import params_spec_reports

    lm, params, ids, _ = model_and_params()
    runtime = make_runtime(dp=1, fsdp=2, tp=2, devices=jax.devices()[:4])
    specs = {r["path"]: (r["rule"], r["spec"]) for r in params_spec_reports(params, runtime.mesh, min_size=0)}
    want = {
        "transformer/mixer_0/fn/in_proj_qkv/kernel": P("fsdp", "tp"),
        "transformer/mixer_0/fn/in_proj_b/kernel": P("fsdp", None),
        "transformer/mixer_0/fn/f_a/kernel": P("fsdp", None),
        "transformer/mixer_0/fn/f_b/kernel": P(None, "tp"),
        "transformer/mixer_0/fn/g_a/kernel": P("fsdp", None),
        "transformer/mixer_0/fn/g_b/kernel": P(None, "tp"),
        "transformer/mixer_0/fn/out_proj/kernel": P("tp", "fsdp"),
        "transformer/mixer_3/fn/to_q/kernel": P("fsdp", "tp"),
        "transformer/mixer_3/fn/to_kv_a/kernel": P("fsdp", None),
        "transformer/mixer_3/fn/to_kv_b/kernel": P("fsdp", "tp"),
        "transformer/mixer_3/fn/to_out/kernel": P("tp", "fsdp"),
        "transformer/ff_0/fn/Dense_0/kernel": P("fsdp", "tp"),
        "transformer/ff_1/fn/experts_in": P("ep", "fsdp", "tp"),
        "transformer/ff_1/fn/gate/kernel": P(None, None),
        "transformer/ff_1/fn/e_score_correction_bias": P(None),
        "transformer/ff_1/fn/tokens_per_expert": P(None),
        "lm_head": P("fsdp", "tp"),
    }
    for path, spec in want.items():
        rule, got = specs[path]
        assert rule is not None and got == spec, (path, rule, got)
    loss = lambda p: lm.loss_and_loads(p, ids)[0]
    one, one_g = jax.jit(jax.value_and_grad(loss))(params)
    with runtime.activate():
        many, many_g = jax.jit(jax.value_and_grad(loss))(params)
    # float32 on four virtual devices against one: the partitioner reorders sums
    assert abs(float(one) - float(many)) < 1e-5
    assert max(leaf_gaps(many_g, one_g).values()) < 1e-3


def test_train_lm_cli_trains_saves_and_resumes_the_family(tmp_path, monkeypatch):
    """``train_lm.py --config <file>`` needs nothing but the configuration
    file; the checkpoint carries the mixers' sizes and the share and
    restores them."""
    import sys
    import train_lm
    from dalle_pytorch_tpu.data import SimpleTokenizer
    from dalle_pytorch_tpu.utils import TELEMETRY, MetricsLogger
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    docs = tmp_path / "docs"
    docs.mkdir()
    for i in range(16):
        (docs / f"{i}.txt").write_text(" ".join(f"word{(i * 7 + j) % 13}" for j in range(40)))
    vocab = SimpleTokenizer().vocab_size
    cfg = {**CFG, "hidden_size": 32, "vocab_size": vocab}
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    losses = []
    real_log = MetricsLogger.log
    monkeypatch.setattr(MetricsLogger, "log", lambda self, logs, step=None: (
        losses.append(logs["loss"]) if "loss" in logs else None, real_log(self, logs, step=step))[1])
    out = tmp_path / "lm"
    argv = ["--config", str(tmp_path / "config.json"), "--image_text_folder", str(docs),
            "--text_seq_len", "32", "--batch_size", "8", "--epochs", "1", "--remat",
            "--learning_rate", "3e-3", "--lm_output_file_name", str(out)]
    try:
        monkeypatch.setattr(sys, "argv", ["train_lm.py"] + argv)
        train_lm.main()
        assert losses and np.all(np.isfinite(losses))
        assert abs(losses[0] - np.log(vocab)) < 1.0
        _, meta = load_checkpoint(f"{out}.ckpt")
        assert meta["config"]["layer_types"] == ["kda"] * 3 + ["mla", "kda"]
        assert meta["config"]["mla_q_rank"] is None and meta["config"]["mla_rotary"] is False
        assert meta["config"]["experts_held"] == [2, 6] and meta["config"]["linattn_key_heads"] == 4
        first = len(losses)
        monkeypatch.setattr(sys, "argv", ["train_lm.py", "--lm_path", f"{out}.ckpt"] + argv[:-2]
                            + ["--lm_output_file_name", str(out), "--epochs", "2"])
        train_lm.main()
        assert len(losses) > first and losses[first] < losses[0]
    finally:
        TELEMETRY.configure(enabled=False)
