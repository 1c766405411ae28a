"""The third family through the language-model path: ``qwen3_next`` (the
gated delta rule three to one with gated softmax attention, softmax-routed
experts of which a range is held, a gated shared expert, the family's
load-balance loss) held to benchmarks/reference_gdn.py: float32, the delta
rule as the sequential recurrence, attention one head at a time, the experts
as a dense loop over the held ones, the router's choice by rank; it imports
nothing of the program. ops/gdn.py's own forms are held in tests/test_gdn.py.
"""

import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from benchmarks import costs, reference_gdn, weights_gdn
from benchmarks.drivers import train_gdn as gdn_driver
from benchmarks.drivers import train_lm as driver
from benchmarks.drivers.train import worst_leaf_gap
from dalle_pytorch_tpu.models.lm import CausalLM
from dalle_pytorch_tpu.ops import kv_policy
from dalle_pytorch_tpu.ops.attention import GatedAttention, rotate_half_split
from dalle_pytorch_tpu.ops.moe import RoutedExperts

ROOT = pathlib.Path(__file__).resolve().parent.parent
CELL = costs.load_config("qwen3-next-80b-a3b-d4-ep16")
N = 72          # a chunk of 64 and a padded tail
CFG = {**CELL, **json.loads((ROOT / "benchmarks/rehearsal_gdn.json").read_text())["config"]}


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


def model_and_params(remat=False, seed=5, **over):
    cfg = {**CFG, **over}
    lm = CausalLM.from_config(cfg, seq_len=N, remat=remat)
    ids = jax.random.randint(jax.random.key(1), (2, N), 0, cfg["vocab_size"])
    shapes = jax.eval_shape(lm.init, jax.random.key(0), ids)["params"]
    return lm, weights_gdn.make_params(shapes, seed, jnp.float32), ids, cfg


# ----------------------------------------------------------- gated attention

ATTN = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=32, partial_rotary_factor=0.25,
            rope_theta=1e7, rms_norm_eps=1e-6)


def attention_layer(use_flash=True):
    return GatedAttention(dim=64, heads=4, kv_heads=2, dim_head=32, rotary_dim=8, rope_theta=1e7,
                          use_flash=use_flash)


def attention_weights(n, seed=0):
    x = jax.random.normal(jax.random.key(seed), (2, n, 64))
    p = attention_layer().init(jax.random.key(seed + 1), x)["params"]
    # norm gains off 1, so that a path which dropped one would show
    noise = lambda a: a + 0.1 * jax.random.normal(jax.random.key(7), a.shape)
    return x, {**p, "q_norm": {"scale": noise(p["q_norm"]["scale"])},
               "k_norm": {"scale": noise(p["k_norm"]["scale"])}}


@pytest.mark.parametrize("n,use_flash,route", [
    (256, True, "blocked_flash"), (24, True, "dense_masked"), (256, False, "dense_masked"),
])
def test_gated_attention_matches_one_head_at_a_time_on_either_route(n, use_flash, route):
    x, p = attention_weights(n)
    kv_policy.ROUTE_LOG.clear()
    out, vjp = jax.vjp(jax.jit(lambda p, x: attention_layer(use_flash).apply({"params": p}, x)), p, x)
    assert [r["impl"] for r in kv_policy.ROUTE_LOG if r["site"] == "forward/gated_attn"] == [route]
    reference = lambda p, x: jnp.stack(
        [reference_gdn._gated_attention(row, p, ATTN, "f32") for row in x])
    want, want_vjp = jax.vjp(jax.jit(reference), p, x)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5 * float(jnp.max(jnp.abs(want)))
    cotangent = jax.random.normal(jax.random.key(3), out.shape)
    gaps = leaf_gaps(vjp(cotangent)[0], want_vjp(cotangent)[0])
    assert max(gaps.values()) < 2e-4, gaps


@pytest.mark.parametrize("left_out", ["output_gate", "rotary", "q_norm"])
def test_a_part_of_the_gated_attention_left_out_fails(left_out, monkeypatch):
    from dalle_pytorch_tpu.ops import attention

    x, p = attention_weights(24)
    if left_out == "output_gate":
        monkeypatch.setattr(attention, "output_gate", lambda out, gate: out)
    elif left_out == "rotary":
        monkeypatch.setattr(attention, "rotate_half_split", lambda t, rot, theta: t)
    else:
        p = {**p, "q_norm": {"scale": jnp.ones_like(p["q_norm"]["scale"])}}
    out = attention_layer().apply({"params": p}, x)
    _, real = attention_weights(24)
    want = jnp.stack([reference_gdn._gated_attention(row, real, ATTN, "f32") for row in x])
    assert float(jnp.linalg.norm(out - want) / jnp.linalg.norm(want)) > 0.02


def test_the_partial_rotary_pairs_channel_c_with_c_plus_half_at_a_late_position():
    """Position 8,191 of 8,192 at the cell's widths (head 256, 64 channels
    turned, theta 1e7): float32 against numpy's float64, bfloat16 inside
    bfloat16's own rounding, because cosine and sine are taken of the float32
    angles; the channels past 64 untouched."""
    n, d, rot, theta = 8192, 256, 64, 1e7
    t = jax.random.normal(jax.random.key(0), (1, 1, n, d))
    got = np.asarray(rotate_half_split(t, rot, theta))[0, 0, -1]
    x = np.asarray(t, np.float64)[0, 0, -1]
    angle = (n - 1) * theta ** (-np.arange(0, rot, 2) / rot)
    a, b = x[: rot // 2], x[rot // 2 : rot]
    want = np.concatenate([a * np.cos(angle) - b * np.sin(angle), b * np.cos(angle) + a * np.sin(angle), x[rot:]])
    np.testing.assert_allclose(got, want, atol=2e-3)     # the float32 ANGLE at 8,191 is good to 5e-4
    np.testing.assert_array_equal(got[rot:], np.asarray(t)[0, 0, -1, rot:])
    half = np.asarray(rotate_half_split(t.astype(jnp.bfloat16), rot, theta).astype(jnp.float32))[0, 0, -1]
    np.testing.assert_allclose(half, want, atol=0.03)


# ------------------------------------------------------------ the expert layer

EXPERTS = dict(num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
               shared_expert_intermediate_size=32, hidden_size=64)


def expert_layer(held=(2, 6), total=8):
    return RoutedExperts(dim=64, hidden=32, experts_total=total, experts_held=held, per_token=2,
                         scoring="softmax", gate_shared=True)


def expert_weights(seed=0, n=48):
    x = jax.random.normal(jax.random.key(seed), (2, n, 64))
    shapes = jax.eval_shape(expert_layer((0, 8)).init, jax.random.key(0), x)["params"]
    return x, weights_gdn.make_params(shapes, seed, jnp.float32)


def held_part(params, lo, hi):
    return {**params, "experts_in": params["experts_in"][lo:hi], "experts_out": params["experts_out"][lo:hi]}


def test_the_expert_layer_matches_the_dense_loop_and_sows_what_the_loss_needs():
    x, whole = expert_weights()
    p = held_part(whole, 2, 6)
    cfg = {**EXPERTS, "experts_held": {"range": [2, 6], "of": 8}}
    assert "e_score_correction_bias" not in p and {"router_prob", "tokens_per_expert", "shared_gate"} <= set(p)
    (out, sown), vjp = jax.vjp(
        lambda p, x: expert_layer().apply({"params": p}, x, mutable=["moe_stats"]), p, x)
    ref = lambda p, x: reference_gdn._experts(x.reshape(-1, 64), p, cfg, "f32")
    (want, load, prob), want_vjp = jax.vjp(ref, p, x)
    np.testing.assert_allclose(out.reshape(-1, 64), want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    np.testing.assert_array_equal(sown["moe_stats"]["load"][0], load)
    np.testing.assert_allclose(sown["moe_stats"]["prob"][0] * 2 * 48, prob, rtol=1e-5)   # the mean
    assert int(load.sum()) == 2 * 48 * 2 and abs(float(prob.sum()) - 2 * 48) < 1e-3
    cotangent = jax.random.normal(jax.random.key(3), out.shape)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, sown)
    got_g = vjp((cotangent, zeros))[0]
    want_g = want_vjp((cotangent.reshape(-1, 64), np.zeros(load.shape, jax.dtypes.float0), jnp.zeros_like(prob)))[0]
    gaps = {k: v for k, v in leaf_gaps(got_g, want_g).items() if not k.endswith(gdn_driver.BUFFERS)}
    assert max(gaps.values()) < 2e-4, gaps


def test_the_shares_routed_parts_and_the_gated_shared_expert_once_add_up_to_the_uncut_layer():
    """model-configs guide section 4: four chips hold two experts each of
    eight; every share routes over all eight and computes its own part; the
    parts, with what every chip computes alike (the gated shared expert)
    counted once, are the uncut reference's layer."""
    x, whole = expert_weights(seed=3)
    uncut, _, _ = reference_gdn._experts(
        x.reshape(-1, 64), whole, {**EXPERTS, "num_experts": 8}, "f32")
    shared_alone = dict(whole, experts_in=whole["experts_in"][:1] * 0, experts_out=whole["experts_out"][:1] * 0)
    shared, _, _ = reference_gdn._experts(
        x.reshape(-1, 64), shared_alone, {**EXPERTS, "num_experts": 1, "experts_held": {"range": [0, 1], "of": 8}}, "f32")
    total = -3 * shared                      # every share adds it; it counts once
    for lo in (0, 2, 4, 6):
        part = expert_layer((lo, lo + 2)).apply({"params": held_part(whole, lo, lo + 2)}, x)
        total = total + part.reshape(-1, 64)
    np.testing.assert_allclose(total, uncut, atol=5e-5 * float(jnp.max(jnp.abs(uncut))))


@pytest.mark.parametrize("fault", ["renorm_held_only", "shared_gate_off"])
def test_a_fault_in_the_expert_layer_fails(fault):
    x, whole = expert_weights(seed=4)
    p = held_part(whole, 2, 6)
    cfg = {**EXPERTS, "experts_held": {"range": [2, 6], "of": 8}}
    ctx = types.SimpleNamespace(control=fault, cfg=cfg)
    with gdn_driver._planted(ctx):
        out = expert_layer().apply({"params": p}, x)
    want, _, _ = reference_gdn._experts(x.reshape(-1, 64), p, cfg, "f32")
    assert float(jnp.linalg.norm(out.reshape(-1, 64) - want) / jnp.linalg.norm(want)) > 0.05
    np.testing.assert_allclose(
        expert_layer().apply({"params": p}, x).reshape(-1, 64), want, atol=1e-4)   # taken out again


# ------------------------------------------------------------ the whole model


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_leafs_gradient_match_the_reference(remat):
    lm, params, ids, cfg = model_and_params(remat=remat)
    assert lm.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert lm.ff_types == ("experts",) * 4 and lm.experts_held == (2, 6) and not lm.tie_head
    (loss, sown), grads = jax.jit(jax.value_and_grad(lm.loss_and_loads, has_aux=True))(params, ids)
    (want, want_loads), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference_gdn.loss(p, cfg, ids), has_aux=True))(params)
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)
    gaps = {k: v for k, v in leaf_gaps(grads, want_grads).items() if not k.endswith(gdn_driver.BUFFERS)}
    assert max(gaps.values()) < 2e-4, max(gaps.items(), key=lambda kv: kv[1])
    # the load-balance term is the train step's: ``__call__`` alone is the cross-entropy
    plain = float(lm.apply({"params": params}, ids, return_loss=True))
    balanced = lm.balance(params, sown)
    stats = lm.routing_stats(balanced)
    aux = float(stats["moe.aux_loss"])
    assert abs(float(loss) - plain - cfg["router_aux_loss_coef"] * aux) < 1e-6
    assert 0.9 * cfg["num_experts_per_tok"] < aux < 2 * cfg["num_experts_per_tok"]   # a uniform router reads k
    # no pair dropped: every expert of every layer was sent what the reference sent it
    for i in range(4):
        sent = np.asarray(balanced["transformer"][f"ff_{i}"]["fn"]["tokens_per_expert"])
        np.testing.assert_array_equal(sent, np.asarray(want_loads[f"transformer/ff_{i}/fn"]))
        assert sent.sum() == ids.size * cfg["num_experts_per_tok"]
        prob = np.asarray(balanced["transformer"][f"ff_{i}"]["fn"]["router_prob"])
        assert abs(prob.sum() - 1.0) < 1e-5
    assert int(stats["moe.pairs_here"]) == sum(int(v[2:6].sum()) for v in want_loads.values()) > 0
    assert lm.routing_stats(params)["moe.pairs_here"] == 0          # no step has run
    router = grads["transformer"]["ff_1"]["fn"]["gate"]["kernel"]
    assert float(jnp.max(jnp.abs(router))) > 0


def test_the_gradient_a_row_at_a_time_under_the_steps_share_is_the_steps_gradient():
    """What the driver's reference does so that its float32 gradient fits:
    the loads of every row first, then the rows one by one under that f."""
    _, params, ids, cfg = model_and_params()
    (want, _), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference_gdn.loss(p, cfg, ids), has_aux=True))(params)
    share = reference_gdn.share_of(cfg, reference_gdn.loads(params, cfg, ids))
    row_grad = jax.jit(jax.value_and_grad(
        lambda p, row: reference_gdn.loss(p, cfg, row[None], share=share), has_aux=True))
    total, grads = 0.0, None
    for row in ids:
        (value, _), g = row_grad(params, row)
        total += float(value) / len(ids)
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    assert abs(total - float(want)) < 1e-6 * float(want)
    gaps = leaf_gaps(jax.tree_util.tree_map(lambda x: x / len(ids), grads), want_grads)
    assert max(v for k, v in gaps.items() if not k.endswith(gdn_driver.BUFFERS)) < 1e-5


@pytest.mark.parametrize("key,value", [
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2), ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("use_sliding_window", True), ("norm_topk_prob", False), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True), ("attention_bias", True), ("model_type", "qwen3_moe"),
    ("experts_held", {"range": [0, 8], "of": 8}), ("shared_expert_intermediate_size", 48),
])
def test_config_keys_this_model_cannot_run_are_refused(key, value):
    with pytest.raises(ValueError, match=key):
        CausalLM.from_config({**CFG, key: value}, seq_len=N)


def test_from_config_reads_the_cells_file_and_the_sources_own_keys():
    lm = CausalLM.from_config(CELL, seq_len=8192)
    assert (lm.depth, lm.experts_total, lm.experts_held, lm.experts_per_token) == (4, 512, (0, 32), 10)
    assert lm.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert (lm.heads, lm.kv_heads, lm.dim_head, lm.attn_rotary_dim, lm.attn_rope_theta) == (16, 2, 256, 64, 1e7)
    assert (lm.linattn_key_heads, lm.linattn_value_heads, lm.linattn_key_dim, lm.linattn_value_dim,
            lm.linattn_conv) == (16, 32, 128, 128, 4)
    assert (lm.experts_hidden, lm.experts_shared, lm.experts_scoring, lm.experts_gate_shared,
            lm.aux_loss_coef, lm.mtp_lambda) == (512, 1, "softmax", True, 0.001, None)
    # the source's own file, with none of this program's keys: every expert is held, 48 layers
    source = {k: v for k, v in CELL.items()
              if k not in ("experts_held", "router_aux_loss_coef", "published", "assumed",
                           "deployment", "reduced", "not_run", "not_read")}
    whole = CausalLM.from_config({**source, **CELL["published"]}, seq_len=8192)
    assert (whole.experts_total, whole.experts_held, whole.depth, whole.vocab_size) == (
        512, (0, 512), 48, 151936)
    assert whole.layer_types[:8] == (("linear_attention",) * 3 + ("full_attention",)) * 2
    assert whole.aux_loss_coef == 0.001
    # no width differs from the published row, and the count the file's arithmetic states
    shapes = jax.eval_shape(lm.init, jax.random.key(0), jnp.zeros((1, 8192), jnp.int32))["params"]
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)) == 625_671_232
    assert set(CELL["reduced"]) == set(CELL["published"]) == {"num_hidden_layers", "num_experts", "vocab_size"}


def test_the_cell_takes_every_kernel_route():
    """At the cell's sizes the convolution without a bias, the delta rule and
    the gated attention all take their kernels, and the experts the grouped
    products; the route sites say so."""
    from dalle_pytorch_tpu.ops import gdn, ssm
    from dalle_pytorch_tpu.ops.attention import _flash_block

    assert ssm.ssm_conv_kernel_eligible(8192, (2048, 2048, 4096), 4)
    assert gdn.delta_rule_kernels_eligible(64, 128, 128) and _flash_block(8192) == 1024
    lm = CausalLM.from_config(CELL, seq_len=8192, dtype=jnp.bfloat16)
    kv_policy.ROUTE_LOG.clear()
    jax.eval_shape(lm.init, jax.random.key(0), jnp.zeros((1, 8192), jnp.int32))
    routes = {(r["site"], r["impl"]) for r in kv_policy.ROUTE_LOG}
    assert routes == {
        ("forward/ssm_conv", "ssm_conv"), ("forward/delta_rule", "gdn_chunk"),
        ("forward/gated_attn", "blocked_flash"), ("forward/moe_experts", "ragged_dot"),
    }


# --------------------------------------------------------- three train steps


def step_ctx(seed=11, control=None, **mix):
    base = dict(rows=2, tokens=N, document_tokens={"min": 4, "max": N}, mesh={"dp": 1},
                learning_rate=3e-4, clip_grad_norm=0.5, remat=True, check_steps=3)
    return types.SimpleNamespace(cfg=CFG, mix={**base, **mix}, seed=seed, chips=1,
                                 control=control, facts={})


# the sound program stays under every one of these (float32 on the CPU); a
# fault has to pass at least one
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


def first_gradient_distance(program, ref) -> float:
    median = float(np.median(list(ref["grad"].values())))
    return max(
        float(np.linalg.norm(program["grad_leaves"][leaf] - g)) / max(ref["grad"][leaf], median)
        for leaf, g in ref["grad_leaves"].items()
    )


@pytest.fixture(scope="module")
def reference_three_steps():
    ctx = step_ctx()
    _, shapes = driver._build(ctx)
    fed = [driver.packed_batch(ctx.mix, ctx.cfg, ctx.seed, s) for s in range(3)]
    with jax.default_matmul_precision("highest"):
        return gdn_driver.reference_steps(ctx, shapes, fed, "f32", keep_gradient=True)


def test_three_steps_of_make_train_step_match_the_references_three(reference_three_steps):
    job = gdn_driver.Job(step_ctx())
    program = job.first_steps()
    assert job.steps == 3 and int(job.state.step) == 3 and int(job.state.skipped) == 0
    assert not any(leaf.endswith(gdn_driver.BUFFERS) for leaf in program["change"])
    gaps = three_step_gaps(program, reference_three_steps)
    assert not over_a_limit(gaps), gaps
    assert program["pairs"] == reference_three_steps["pairs"]            # no pair dropped
    assert first_gradient_distance(program, reference_three_steps) < 1e-4
    # the step left its counters in the state
    stats = job.lm.routing_stats(job.state.params)
    assert int(stats["moe.pairs_here"]) == program["pairs"][-1] and float(stats["moe.aux_loss"]) > 1.0


@pytest.mark.parametrize("fault", gdn_driver.PROGRAM_FAULTS)
def test_a_fault_planted_in_the_program_fails_the_three_steps(fault, reference_three_steps):
    from dalle_pytorch_tpu.ops import attention, gdn, moe

    real = (gdn.log_decay, gdn.write_strength, gdn.l2norm, gdn.chunk_log_decay,
            attention.output_gate, moe.route, moe.shared_gate)
    with gdn_driver._planted(step_ctx(control=fault)):
        program = gdn_driver.Job(step_ctx()).first_steps()
    assert (gdn.log_decay, gdn.write_strength, gdn.l2norm, gdn.chunk_log_decay,
            attention.output_gate, moe.route, moe.shared_gate) == real
    gaps = three_step_gaps(program, reference_three_steps)
    assert over_a_limit(gaps), gaps
    assert first_gradient_distance(program, reference_three_steps) > 0.01


def test_half_of_the_tokens_left_out_of_the_loss_fails(reference_three_steps):
    ctx = step_ctx()
    _, shapes = driver._build(ctx)
    fed = [driver.packed_batch(ctx.mix, ctx.cfg, ctx.seed, s) for s in range(3)]
    half = gdn_driver.reference_steps(ctx, shapes, fed, "f32", positions=N // 2)
    gaps = three_step_gaps(half, reference_three_steps)
    assert over_a_limit(gaps) and gaps["grad"] > 0.05, gaps


@pytest.mark.parametrize("rejected", [False, True], ids=["applied", "rejected"])
def test_the_step_writes_the_routers_counters_unless_it_is_rejected(rejected):
    import optax
    from dalle_pytorch_tpu.parallel import create_train_state, make_runtime, make_train_step

    lm, params, ids, cfg = model_and_params(remat=True)
    runtime = make_runtime(devices=jax.local_devices()[:1], dp=1)
    optimizer = optax.chain(optax.clip_by_global_norm(0.5), optax.scale_by_adam())
    state, shardings = create_train_state(params, optimizer, runtime)
    step = make_train_step(
        lambda p, batch, rng: lm.loss_and_loads(p, batch["ids"]), optimizer, runtime, shardings,
        dynamic_lr=True, after_update=lm.balance, nan_inject_step=0 if rejected else None,
        donate=False,
    )
    new, loss = step(state, {"ids": ids}, jax.random.key(0), jnp.asarray(3e-4))
    after = new.params["transformer"]["ff_1"]["fn"]
    sent, prob = np.asarray(after["tokens_per_expert"]), np.asarray(after["router_prob"])
    if rejected:
        assert np.isnan(float(loss)) and int(new.skipped) == 1
        assert not sent.any() and not prob.any()
    else:
        assert sent.sum() == ids.size * cfg["num_experts_per_tok"] and abs(prob.sum() - 1) < 1e-5
    # Adam leaves the counters' moments at zero: they enter nothing differentiable
    assert not np.any(np.asarray(new.opt_state[1].mu["transformer"]["ff_1"]["fn"]["router_prob"]))


def test_the_leaves_have_sharding_rules_and_a_step_on_a_mesh_matches_one_chip():
    from jax.sharding import PartitionSpec as P
    from dalle_pytorch_tpu.parallel import make_runtime
    from dalle_pytorch_tpu.parallel.sharding import params_spec_reports

    lm, params, ids, _ = model_and_params()
    runtime = make_runtime(dp=1, fsdp=2, tp=2, devices=jax.devices()[:4])
    specs = {r["path"]: (r["rule"], r["spec"]) for r in params_spec_reports(params, runtime.mesh, min_size=0)}
    want = {
        "transformer/mixer_0/fn/in_proj_qkvz/kernel": P("fsdp", "tp"),
        "transformer/mixer_0/fn/in_proj_ba/kernel": P("fsdp", None),
        "transformer/mixer_0/fn/out_proj/kernel": P("tp", "fsdp"),
        "transformer/mixer_3/fn/to_q/kernel": P("fsdp", "tp"),
        "transformer/mixer_3/fn/to_k/kernel": P("fsdp", "tp"),
        "transformer/mixer_3/fn/to_v/kernel": P("fsdp", "tp"),
        "transformer/mixer_3/fn/to_out/kernel": P("tp", "fsdp"),
        "transformer/ff_0/fn/experts_in": P("ep", "fsdp", "tp"),
        "transformer/ff_0/fn/gate/kernel": P(None, None),
        "transformer/ff_0/fn/shared_gate/kernel": P(None, None),
        "transformer/ff_0/fn/router_prob": P(None),
        "transformer/ff_0/fn/tokens_per_expert": P(None),
        "transformer/ff_0/fn/shared/Dense_0/kernel": P("fsdp", "tp"),
        "lm_head": P("fsdp", "tp"),
    }
    for path, spec in want.items():
        rule, got = specs[path]
        assert rule is not None and got == spec, (path, rule, got)
    loss = lambda p: lm.loss_and_loads(p, ids)[0]
    one, one_g = jax.jit(jax.value_and_grad(loss))(params)
    with runtime.activate():
        many, many_g = jax.jit(jax.value_and_grad(loss))(params)
    assert abs(float(one) - float(many)) < 1e-5
    assert max(leaf_gaps(many_g, one_g).values()) < 1e-3


def test_train_lm_cli_trains_saves_and_resumes_the_family(tmp_path, monkeypatch):
    """``train_lm.py --config <file>`` needs nothing but the configuration
    file; with --telemetry it emits the routers' counters; the checkpoint
    carries the share and the mixers' sizes and restores them."""
    import sys
    import train_lm
    from dalle_pytorch_tpu.data import SimpleTokenizer
    from dalle_pytorch_tpu.utils import TELEMETRY, MetricsLogger, counters, gauges
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
            "--learning_rate", "3e-3", "--telemetry", "--telemetry_dir", str(tmp_path / "flight"),
            "--lm_output_file_name", str(out)]
    before = counters.get("moe.pairs_here")
    try:
        monkeypatch.setattr(sys, "argv", ["train_lm.py"] + argv)
        train_lm.main()
        assert losses and np.all(np.isfinite(losses))
        assert abs(losses[0] - np.log(vocab)) < 1.0
        assert counters.get("moe.pairs_here") > before and gauges.get("moe.aux_loss") > 1.0
        _, meta = load_checkpoint(f"{out}.ckpt")
        assert meta["config"]["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
        assert meta["config"]["experts_held"] == [2, 6] and meta["config"]["experts_scoring"] == "softmax"
        first = len(losses)
        monkeypatch.setattr(sys, "argv", ["train_lm.py", "--lm_path", f"{out}.ckpt"] + argv[:-2]
                            + ["--lm_output_file_name", str(out), "--epochs", "2"])
        train_lm.main()
        assert len(losses) > first and losses[first] < losses[0]
    finally:
        TELEMETRY.configure(enabled=False)
