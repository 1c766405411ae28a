"""The fourth family through the language-model path: ``smallthinker``
(sliding-window attention with rotary three to one with global attention
without a positional term, ReGLU experts of which a range is held, routed
from the block's input before its attention) held to
benchmarks/reference_swa.py: float32, every query block against every key
under a dense mask of the band, the router before the attention, the experts
as a dense loop over the held ones, the router's choice by rank; it imports
nothing of the program. The windowed flash kernels' own parity is in
tests/test_flash.py."""

import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from benchmarks import costs, costs_swa, reference_swa, weights_swa
from benchmarks.drivers import train_lm as driver
from benchmarks.drivers import train_swa as swa_driver
from benchmarks.drivers.train import worst_leaf_gap
from dalle_pytorch_tpu.models.lm import CausalLM
from dalle_pytorch_tpu.ops import kv_policy
from dalle_pytorch_tpu.ops.moe import RoutedExperts

ROOT = pathlib.Path(__file__).resolve().parent.parent
CELL = costs.load_config("smallthinker-21b-a3b-d4-ep4")
N = 72
CFG = {**CELL, **json.loads((ROOT / "benchmarks/rehearsal_swa.json").read_text())["config"]}
BUFFERS = swa_driver.BUFFERS


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


def model_and_params(n=N, remat=False, seed=5, **over):
    cfg = {**CFG, **over}
    lm = CausalLM.from_config(cfg, seq_len=n, remat=remat)
    ids = jax.random.randint(jax.random.key(1), (2, n), 0, cfg["vocab_size"])
    shapes = jax.eval_shape(lm.init, jax.random.key(0), ids)["params"]
    return lm, weights_swa.make_params(shapes, seed, jnp.float32), ids, cfg


# ------------------------------------------------------------ the whole model


@pytest.mark.parametrize("n,remat,route", [
    (N, False, "dense_masked"), (N, True, "dense_masked"),
    (256, False, "blocked_flash"), (256, True, "blocked_flash"),
])
def test_loss_and_every_leafs_gradient_match_the_reference(n, remat, route):
    """Window 24 at 72 and 256 positions: the dense masked softmax and the
    windowed flash kernels (one block: the band's edge masked inside it).
    Float32 at highest on both sides, so the tolerances are rounding's: the
    loss to 2e-6 of itself, every gradient leaf to 2e-4 of its largest entry
    (the program sums the experts' pairs in another order)."""
    lm, params, ids, cfg = model_and_params(n, remat)
    assert lm.layer_types == ("attention",) + ("sliding_attention",) * 3
    assert lm.ff_types == ("experts",) * 4 and lm.experts_held == (2, 6) and not lm.tie_head
    kv_policy.ROUTE_LOG.clear()
    (loss, sown), grads = jax.jit(jax.value_and_grad(lm.loss_and_loads, has_aux=True))(params, ids)
    routes = {r["site"]: r for r in kv_policy.ROUTE_LOG}
    assert routes["forward/swa"]["impl"] == route and routes["forward/swa"]["window"] == 24
    assert routes["forward/gqa"]["impl"] == route and "window" not in routes["forward/gqa"]
    (want, want_loads), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference_swa.loss(p, cfg, ids), has_aux=True))(params)
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)
    gaps = {k: v for k, v in leaf_gaps(grads, want_grads).items() if not k.endswith(BUFFERS)}
    assert max(gaps.values()) < 2e-4, max(gaps.items(), key=lambda kv: kv[1])
    # no pair dropped: every expert of every layer was sent what the reference sent it
    balanced = lm.balance(params, sown)
    for i in range(4):
        sent = np.asarray(balanced["transformer"][f"ff_{i}"]["fn"]["tokens_per_expert"])
        np.testing.assert_array_equal(sent, np.asarray(want_loads[f"transformer/ff_{i}/fn"]))
        assert sent.sum() == ids.size * cfg["moe_num_active_primary_experts"]
    stats = lm.routing_stats(balanced)
    assert int(stats["moe.pairs_here"]) == sum(int(v[2:6].sum()) for v in want_loads.values()) > 0
    # the router lives with the block's input, and its gradient reaches it
    assert "gate" not in params["transformer"]["ff_1"]["fn"]
    assert float(jnp.max(jnp.abs(grads["transformer"]["mixer_1"]["gate"]["kernel"]))) > 0


def test_the_window_shows_in_the_result():
    """The same weights with the window as wide as the row give another loss:
    the band, not the triangle, is what the window layers attend."""
    lm, params, ids, cfg = model_and_params()
    wide = CausalLM.from_config({**cfg, "sliding_window_size": N}, seq_len=N)
    narrow, whole = (float(m.apply({"params": params}, ids, return_loss=True)) for m in (lm, wide))
    assert abs(narrow - whole) > 1e-3 * whole
    want, _ = reference_swa.loss(params, {**cfg, "sliding_window_size": N}, ids)
    assert abs(whole - float(want)) < 2e-6 * float(want)


@pytest.mark.parametrize("remat", [False, True])
def test_a_blocks_attention_weights_do_not_move_its_routing(remat):
    """The router reads the block's normed input: new attention weights in
    layer 1 leave layer 1's routing (its probabilities and loads) as they
    were, and move layer 2's, whose input the attention changed."""
    lm, params, ids, _ = model_and_params(remat=remat)
    attention = params["transformer"]["mixer_1"]["fn"]
    moved = jax.tree_util.tree_map(lambda w: w * 1.5 + 0.05, attention)
    other = {**params, "transformer": {**params["transformer"], "mixer_1": {
        **params["transformer"]["mixer_1"], "fn": moved}}}
    (_, before), (_, after) = (jax.jit(lm.loss_and_loads)(p, ids) for p in (params, other))
    sown = lambda stats, layer, what: np.asarray(stats["transformer"][layer]["fn"][what][0])
    for what in ("prob", "load"):
        np.testing.assert_array_equal(sown(before, "ff_1", what), sown(after, "ff_1", what))
    assert not np.array_equal(sown(before, "ff_2", "prob"), sown(after, "ff_2", "prob"))


# ------------------------------------------------------------ the expert layer


def test_four_shares_of_16_add_up_to_the_uncut_64_expert_layer():
    """model-configs guide section 4: four chips hold 16 experts each of 64;
    every share is handed the same probabilities over all 64 and computes
    its own part; the parts sum to the uncut reference's layer (ReGLU, no
    shared expert)."""
    d, width, total, k = 32, 16, 64, 6
    cfg = {"moe_num_active_primary_experts": k, "moe_num_primary_experts": total}
    u = jax.random.normal(jax.random.key(0), (2, 48, d))
    gate = jax.random.normal(jax.random.key(1), (d, total)) / np.sqrt(d)
    probs = jax.nn.softmax(u.reshape(-1, d) @ gate, axis=-1)
    layer = lambda lo: RoutedExperts(dim=d, hidden=width, experts_total=total, experts_held=(lo, lo + 16),
                                     per_token=k, shared=0, scoring="softmax", activation="reglu")
    shapes = jax.eval_shape(layer(0).init, jax.random.key(0), u, probs=probs)["params"]
    whole = weights_swa.make_params(
        {**shapes, "experts_in": jax.ShapeDtypeStruct((total, d, 2 * width), jnp.float32),
         "experts_out": jax.ShapeDtypeStruct((total, width, d), jnp.float32)}, 3, jnp.float32)
    uncut, load = reference_swa._experts(
        u.reshape(-1, d), whole, reference_swa.expert_weights(u.reshape(-1, d), gate, cfg), cfg, "f32")
    parts = 0.0
    for lo in (0, 16, 32, 48):
        held = {**whole, "experts_in": whole["experts_in"][lo:lo + 16],
                "experts_out": whole["experts_out"][lo:lo + 16]}
        parts = parts + layer(lo).apply({"params": held}, u, probs=probs).reshape(-1, d)
    np.testing.assert_allclose(parts, uncut, atol=2e-5 * float(jnp.max(jnp.abs(uncut))))
    assert int(load.sum()) == 2 * 48 * k


# ------------------------------------------------------------ the configuration


def test_the_cells_file_reads_and_counts_656_529_920_parameters():
    lm = CausalLM.from_config(CELL, seq_len=16384)
    assert (lm.depth, lm.experts_total, lm.experts_held, lm.experts_per_token) == (4, 64, (0, 16), 6)
    assert lm.layer_types == ("attention",) + ("sliding_attention",) * 3
    assert (lm.heads, lm.kv_heads, lm.dim_head, lm.attn_rotary_dim, lm.attn_rope_theta,
            lm.attn_window) == (28, 4, 128, 128, 1.5e6, 4096)
    assert (lm.experts_hidden, lm.experts_shared, lm.experts_scoring, lm.experts_activation,
            lm.experts_route_first, lm.aux_loss_coef, lm.tie_head) == (768, 0, "softmax", "reglu", True, 0.0, False)
    shapes = jax.eval_shape(lm.init, jax.random.key(0), jnp.zeros((1, 16384), jnp.int32))["params"]
    flat = traverse_util.flatten_dict(shapes)
    trained = sum(int(np.prod(x.shape)) for p, x in flat.items() if p[-1] not in BUFFERS)
    assert trained == 656_529_920
    assert flat[("tok_emb", "embedding")].shape == flat[("lm_head",)].shape == (37984, 2560)
    assert set(CELL["reduced"]) == set(CELL["published"])
    # the source's own file, with none of this program's keys: every expert held, 52 layers
    source = {k: v for k, v in CELL.items()
              if k not in ("experts_held", "published", "assumed", "deployment", "reduced", "not_run", "not_read")}
    whole = CausalLM.from_config({**source, **CELL["published"]}, seq_len=16384)
    assert (whole.experts_total, whole.experts_held, whole.depth, whole.vocab_size) == (64, (0, 64), 52, 151936)
    assert whole.layer_types[:8] == (("attention",) + ("sliding_attention",) * 3) * 2


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn", "factor": 4}), ("norm_topk_prob", False),
    ("moe_primary_router_apply_softmax", False), ("tie_word_embeddings", True),
    ("sliding_window_layout", [0, 1, 2, 1]), ("rope_layout", [0, 1, 1]),
    ("sliding_window_layout", [0, 1, 1, 0]), ("model_type", "smallthinker_moe"),
    ("experts_held", {"range": [0, 8], "of": 8}),
])
def test_config_keys_this_model_cannot_run_are_refused(key, value):
    with pytest.raises(ValueError, match=key):
        CausalLM.from_config({**CFG, key: value}, seq_len=N)


def test_the_cell_takes_the_windowed_kernels():
    """At the cell's size the window layers take the blocked flash kernels
    with the window, and the route says how many tiles the banded grid
    visits beside the causal triangle's: 70 of 136 at 16,384 and 4,096."""
    lm = CausalLM.from_config(CELL, seq_len=16384, dtype=jnp.bfloat16, remat=True)
    kv_policy.ROUTE_LOG.clear()
    jax.eval_shape(lm.init, jax.random.key(0), jnp.zeros((1, 16384), jnp.int32))
    routes = {r["site"]: r for r in kv_policy.ROUTE_LOG}
    assert {k: v for k, v in routes["forward/swa"].items() if k != "interpret"} == {
        "site": "forward/swa", "impl": "blocked_flash", "window": 4096,
        "tiles_visited": 70, "causal_tiles": 136}
    assert routes["forward/gqa"]["impl"] == "blocked_flash"
    assert routes["forward/moe_experts"]["impl"] == "ragged_dot"


def test_the_band_pairs_and_the_steps_work():
    assert costs_swa.band_pairs(16384, 4096) == 58_722_304
    assert costs_swa.band_pairs(16384, None) == 16384 * 16385 // 2
    need = costs_swa.train_step(CELL, 1, 16384)
    # the configuration's arithmetic (PERF.md section 4): 11.57 TFLOP forward, 34.7 a step
    assert abs(need["total"] / 3 - 11.57e12) < 0.01e12
    assert abs(need["window_attention"] / 3 - 2.53e12) < 0.01e12
    assert abs(need["global_attention"] / 3 - 1.92e12) < 0.01e12


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
        return shapes, fed, swa_driver.reference_steps(ctx, shapes, fed, keep_gradient=True)


def test_three_steps_of_make_train_step_match_the_references_three(reference_three_steps):
    _, _, ref = reference_three_steps
    job = swa_driver.Job(step_ctx())
    program = job.first_steps()
    assert job.steps == 3 and int(job.state.step) == 3 and int(job.state.skipped) == 0
    assert not any(leaf.endswith(BUFFERS) for leaf in program["change"])
    gaps = three_step_gaps(program, ref)
    assert not over_a_limit(gaps), gaps
    assert program["pairs"] == ref["pairs"]            # no pair dropped


@pytest.mark.parametrize("control", ["no_window", "post_attention_router", "half_batch"])
def test_each_control_fails_the_three_steps(control, reference_three_steps):
    """``no_window`` planted in the program (taken out again after); the
    router after the attention and half of the tokens as reference stand-ins."""
    from dalle_pytorch_tpu.ops import attention

    shapes, fed, ref = reference_three_steps
    real = attention._causal_attend
    if control == "no_window":
        with swa_driver._planted(control):
            program = swa_driver.Job(step_ctx()).first_steps()
        assert attention._causal_attend is real
    else:
        stand_in = {"post_attention_router": dict(router="after"), "half_batch": dict(positions=N // 2)}
        program = swa_driver.reference_steps(step_ctx(), shapes, fed, **stand_in[control])
    assert over_a_limit(three_step_gaps(program, ref))


def test_the_leaves_have_sharding_rules_and_a_step_on_a_mesh_matches_one_chip():
    from jax.sharding import PartitionSpec as P
    from dalle_pytorch_tpu.parallel import make_runtime
    from dalle_pytorch_tpu.parallel.sharding import params_spec_reports

    lm, params, ids, _ = model_and_params()
    runtime = make_runtime(dp=1, fsdp=2, tp=2, devices=jax.devices()[:4])
    specs = {r["path"]: (r["rule"], r["spec"]) for r in params_spec_reports(params, runtime.mesh, min_size=0)}
    want = {
        "transformer/mixer_0/gate/kernel": P(None, None),
        "transformer/mixer_1/gate/kernel": P(None, None),
        "transformer/mixer_1/fn/to_q/kernel": P("fsdp", "tp"),
        "transformer/mixer_1/fn/to_kv/kernel": P("fsdp", "tp"),
        "transformer/mixer_1/fn/to_out/kernel": P("tp", "fsdp"),
        "transformer/ff_0/fn/experts_in": P("ep", "fsdp", "tp"),
        "transformer/ff_0/fn/experts_out": P("ep", "tp", "fsdp"),
        "transformer/ff_0/fn/router_prob": P(None),
        "transformer/ff_0/fn/tokens_per_expert": P(None),
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
    file; the checkpoint carries the window, the activation and where the
    router reads, and restores them."""
    import sys
    import train_lm
    from dalle_pytorch_tpu.data import SimpleTokenizer
    from dalle_pytorch_tpu.utils import MetricsLogger
    from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint

    docs = tmp_path / "docs"
    docs.mkdir()
    for i in range(16):
        (docs / f"{i}.txt").write_text(" ".join(f"word{(i * 7 + j) % 13}" for j in range(40)))
    vocab = SimpleTokenizer().vocab_size
    cfg = {**CFG, "hidden_size": 32, "vocab_size": vocab, "sliding_window_size": 8}
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    losses = []
    real_log = MetricsLogger.log
    monkeypatch.setattr(MetricsLogger, "log", lambda self, logs, step=None: (
        losses.append(logs["loss"]) if "loss" in logs else None, real_log(self, logs, step=step))[1])
    out = tmp_path / "lm"
    argv = ["--config", str(tmp_path / "config.json"), "--image_text_folder", str(docs),
            "--text_seq_len", "32", "--batch_size", "8", "--epochs", "1", "--remat",
            "--learning_rate", "3e-3", "--lm_output_file_name", str(out)]
    monkeypatch.setattr(sys, "argv", ["train_lm.py"] + argv)
    train_lm.main()
    assert losses and np.all(np.isfinite(losses)) and abs(losses[0] - np.log(vocab)) < 1.0
    _, meta = load_checkpoint(f"{out}.ckpt")
    assert meta["config"]["layer_types"] == ["attention"] + ["sliding_attention"] * 3
    assert (meta["config"]["attn_window"], meta["config"]["experts_activation"],
            meta["config"]["experts_route_first"]) == (8, "reglu", True)
    first = len(losses)
    monkeypatch.setattr(sys, "argv", ["train_lm.py", "--lm_path", f"{out}.ckpt"] + argv[:-2]
                        + ["--lm_output_file_name", str(out), "--epochs", "2"])
    train_lm.main()
    assert len(losses) > first and losses[first] < losses[0]
