"""models/lm.py:CausalLM through the program's normal train path, held to the
plain reference (benchmarks/reference_lm.py: float32, quadratic state-space
form, imports nothing of the program): grouped-KV attention, the loss, every
leaf's gradient, three steps of ``make_train_step``; planted faults that must
FAIL; the new leaves' sharding; the CLI with save and resume; and the DALL-E
configurations' parameter trees, which this model's block variants must not
have moved."""

import hashlib
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from benchmarks import costs, reference_lm, weights_lm
from benchmarks.drivers import train_lm as driver
from benchmarks.drivers.train import worst_leaf_gap
from dalle_pytorch_tpu.models.lm import CausalLM
from dalle_pytorch_tpu.ops.attention import GroupedKVAttention

ROOT = pathlib.Path(__file__).resolve().parents[1]
CFG = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2, vocab_size=50,
    shared_intermediate_size=96, num_hidden_layers=3,
    layer_types=["mamba", "attention", "mamba", "mamba"],   # the first three run
    attention_multiplier=0.2, embedding_multiplier=12, residual_multiplier=0.22,
    logits_scaling=8, rms_norm_eps=1e-5, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=8, mamba_d_conv=4, mamba_chunk_size=8, mamba_expand=2,
)
N = 21    # two carried chunk boundaries and a ragged tail


def model_and_params(remat=False, seed=5, **over):
    lm = CausalLM.from_config({**CFG, **over}, seq_len=N, remat=remat)
    ids = jax.random.randint(jax.random.key(1), (2, N), 0, CFG["vocab_size"])
    shapes = jax.eval_shape(lm.init, jax.random.key(0), ids)["params"]
    return lm, weights_lm.make_params(shapes, seed, jnp.float32), ids


def reference_grad(params, ids, cfg=CFG):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: reference_lm.loss(p, cfg, ids))(params)


def leaf_gaps(got, want) -> dict:
    flat_g, flat_w = traverse_util.flatten_dict(got), traverse_util.flatten_dict(want)
    return {
        "/".join(k): float(jnp.max(jnp.abs(flat_g[k] - w)) / (jnp.max(jnp.abs(w)) + 1e-12))
        for k, w in flat_w.items()
    }


# ------------------------------------------------------- grouped-KV attention


def plain_gqa(x, p, heads, kv_heads, d, scale, group_of):
    b, n, _ = x.shape
    q = (x @ p["to_q"]["kernel"]).reshape(b, n, heads, d)
    kv = (x @ p["to_kv"]["kernel"]).reshape(b, n, 2, kv_heads, d)
    pick = jnp.asarray([group_of(i) for i in range(heads)])
    k, v = kv[:, :, 0][:, :, pick], kv[:, :, 1][:, :, pick]      # KV heads repeated
    s = jnp.einsum("bihd,bjhd->bhij", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e30)
    out = jnp.einsum("bhij,bjhd->bihd", jax.nn.softmax(s, -1), v).reshape(b, n, heads * d)
    return out @ p["to_out"]["kernel"]


@pytest.mark.parametrize("n,use_flash", [(128, True), (24, True), (128, False)])
def test_grouped_kv_attention_matches_a_plain_softmax_with_kv_heads_repeated(n, use_flash):
    """n = 128 takes the blocked flash kernel (interpreted here), 24 has no
    usable block and takes the dense route; both are the same function."""
    attn = GroupedKVAttention(dim=32, heads=8, kv_heads=2, dim_head=8, sm_scale=0.2,
                              use_flash=use_flash)
    x = jax.random.normal(jax.random.key(0), (2, n, 32))
    p = attn.init(jax.random.key(1), x)["params"]
    assert set(p) == {"to_q", "to_kv", "to_out"} and p["to_kv"]["kernel"].shape == (32, 32)
    want = plain_gqa(x, p, 8, 2, 8, 0.2, lambda i: i // 4)
    np.testing.assert_allclose(attn.apply({"params": p}, x), want, rtol=2e-4, atol=2e-5)
    # planted: query head i on KV head i % kv_heads is another function
    wrong = plain_gqa(x, p, 8, 2, 8, 0.2, lambda i: i % 2)
    assert float(jnp.max(jnp.abs(wrong - want))) > 1e-2


# ------------------------------------------------ loss and every leaf's gradient


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_leafs_gradient_match_the_reference(remat):
    lm, params, ids = model_and_params(remat)
    loss, grads = jax.value_and_grad(
        lambda p: lm.apply({"params": p}, ids, return_loss=True)
    )(params)
    want, want_grads = reference_grad(params, ids)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    gaps = leaf_gaps(grads, want_grads)
    assert len(gaps) == 2 + 3 * 3 + 2 * 9 + 4        # every leaf of the tree
    assert max(gaps.values()) < 1e-4, max(gaps.items(), key=lambda kv: kv[1])
    logits = lm.apply({"params": params}, ids)
    want_logits = jnp.stack([reference_lm.logits(params, CFG, row) for row in ids])
    np.testing.assert_allclose(logits, want_logits, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("left_out", ["logits_scaling", "residual_multiplier",
                                      "embedding_multiplier", "attention_multiplier"])
def test_a_multiplier_left_out_fails_the_comparison(left_out):
    neutral = {"attention_multiplier": (CFG["hidden_size"] // CFG["num_attention_heads"]) ** -0.5}
    lm, params, ids = model_and_params(**{left_out: neutral.get(left_out, 1.0)})
    loss, grads = jax.value_and_grad(
        lambda p: lm.apply({"params": p}, ids, return_loss=True)
    )(params)
    want, want_grads = reference_grad(params, ids)
    loss_gap = abs(float(loss) - float(want)) / float(want)
    assert loss_gap > 1e-4 or max(leaf_gaps(grads, want_grads).values()) > 1e-2, left_out


def test_config_keys_this_model_cannot_run_are_refused():
    for key, value in [("num_local_experts", 8), ("mamba_n_groups", 2),
                       ("position_embedding_type", "rope"), ("attention_bias", True)]:
        with pytest.raises(ValueError, match=key):
            CausalLM.from_config({**CFG, key: value}, seq_len=N)
    lm = CausalLM.from_config(CFG, seq_len=N)
    assert lm.layer_types == ("mamba", "attention", "mamba") and lm.depth == 3
    with pytest.raises(NotImplementedError):
        _, params, ids = model_and_params()
        lm.apply({"params": params}, jnp.zeros((2, N, 64)), decode=True,
                 method=lambda m, x, decode: m.transformer(x, decode=decode))


# --------------------------------------------------------- three train steps


def step_ctx(seed=7, **mix):
    base = dict(rows=2, tokens=N, document_tokens={"min": 3, "max": N}, mesh={"dp": 1},
                learning_rate=3e-4, clip_grad_norm=0.5, remat=True, check_steps=3)
    return types.SimpleNamespace(
        cfg={**CFG, "compute_dtype": "float32"}, mix={**base, **mix}, seed=seed, chips=1,
    )


# the sound program stays under every one of these (float32 on the CPU); a
# fault has to pass at least one
LIMITS = {"loss": 1e-5, "grad": 1e-4, "change": 1e-3}


def over_a_limit(gaps: dict) -> bool:
    return (max(gaps[k] for k in ("loss1", "loss2", "loss3")) > LIMITS["loss"]
            or gaps["grad"] > LIMITS["grad"] or gaps["change"] > LIMITS["change"])


def three_step_gaps(program, ref) -> dict:
    gaps = {f"loss{i}": abs(p - r) / abs(r) for i, (p, r) in
            enumerate(zip(program["loss"], ref["loss"]), 1)}
    gaps["grad"] = worst_leaf_gap(program["grad"], ref["grad"])[0]
    gaps["change"] = worst_leaf_gap(program["change"], ref["change"])[0]
    return gaps


@pytest.fixture(scope="module")
def reference_three_steps():
    ctx = step_ctx()
    _, shapes = driver._build(ctx)
    fed = [driver.packed_batch(ctx.mix, ctx.cfg, ctx.seed, s) for s in range(3)]
    with jax.default_matmul_precision("highest"):
        return driver.reference_steps(ctx, shapes, fed, "f32")


def test_three_steps_of_make_train_step_match_the_references_three(reference_three_steps):
    """train_lm.build_step -> create_train_state -> make_train_step, through
    the benchmark driver's own Job, on the packed batches of the seed."""
    job = driver.Job(step_ctx())
    program = job.first_steps()
    assert job.steps == 3 and int(job.state.step) == 3 and int(job.state.skipped) == 0
    gaps = three_step_gaps(program, reference_three_steps)
    assert not over_a_limit(gaps), gaps
    # and the packed batch is what the traffic file says
    ids = job.fed[0]
    assert ids.shape == (2, N) and ids.dtype == np.int32
    assert ids.min() >= 0 and ids.max() < CFG["vocab_size"] and (ids == 0).any()


@pytest.mark.parametrize("fault", ["no_carry", "bf16_decay"])
def test_a_fault_planted_in_the_programs_scan_fails_the_three_steps(fault, reference_three_steps):
    with driver._planted(fault):
        program = driver.Job(step_ctx()).first_steps()
    gaps = three_step_gaps(program, reference_three_steps)
    assert over_a_limit(gaps), gaps
    # the fault is taken out again
    from dalle_pytorch_tpu.ops import ssm
    assert ssm.carried_states.__module__ == ssm.log_decay.__module__ == ssm.__name__


def test_half_of_the_tokens_left_out_of_the_loss_fails(reference_three_steps):
    ctx = step_ctx()
    _, shapes = driver._build(ctx)
    fed = [driver.packed_batch(ctx.mix, ctx.cfg, ctx.seed, s) for s in range(3)]
    with jax.default_matmul_precision("highest"):
        half = driver.reference_steps(ctx, shapes, fed, "f32", positions=N // 2)
    gaps = three_step_gaps(half, reference_three_steps)
    assert over_a_limit(gaps) and gaps["grad"] > 0.05, gaps


def test_the_cells_mixers_take_the_kernels_and_the_tiny_ones_the_einsums():
    """The routes of the state-space scan and of the convolution in front of
    it are read from the shape alone (``ssd_kernels_eligible``,
    ``ssm_conv_kernel_eligible``) and recorded at ``forward/ssd`` and
    ``forward/ssm_conv``: the
    benchmark's configuration at its published widths is the shape the
    kernels are written for; this file's is not."""
    from dalle_pytorch_tpu.ops import kv_policy

    def routes(lm, n):
        kv_policy.ROUTE_LOG.clear()
        jax.eval_shape(lm.init, jax.random.key(0), jnp.zeros((1, n), jnp.int32))
        return [r for r in kv_policy.ROUTE_LOG if r["site"] in ("forward/ssd", "forward/ssm_conv")]

    cell = json.loads((ROOT / "benchmarks/configs/granite-4.0-h-micro-d10.json").read_text())
    lm = CausalLM.from_config({**cell, "num_hidden_layers": 2}, seq_len=512, dtype=jnp.bfloat16)
    assert routes(lm, 512) == [
        {"site": "forward/ssm_conv", "impl": "ssm_conv", "interpret": True},
        {"site": "forward/ssd", "impl": "ssd_chunk", "interpret": True},
    ]
    tiny, _, _ = model_and_params()
    assert routes(tiny, N) == [
        {"site": "forward/ssm_conv", "impl": "xla", "interpret": None},
        {"site": "forward/ssd", "impl": "einsum", "interpret": None},
    ]


# ------------------------------------------------------------------ sharding


def test_the_new_leaves_have_sharding_rules():
    from jax.sharding import PartitionSpec as P
    from dalle_pytorch_tpu.parallel import make_runtime
    from dalle_pytorch_tpu.parallel.sharding import params_spec_reports

    lm, params, _ = model_and_params()
    mesh = make_runtime(dp=1, fsdp=2, tp=2, devices=jax.devices()[:4]).mesh
    specs = {r["path"]: (r["rule"], r["spec"]) for r in params_spec_reports(params, mesh, min_size=0)}
    want = {
        "transformer/mixer_0/fn/in_proj/kernel": P("fsdp", "tp"),
        "transformer/mixer_0/fn/out_proj/kernel": P("tp", "fsdp"),
        "transformer/mixer_1/fn/to_q/kernel": P("fsdp", "tp"),
        "transformer/mixer_1/fn/to_kv/kernel": P("fsdp", "tp"),
        "transformer/mixer_1/fn/to_out/kernel": P("tp", "fsdp"),
        "transformer/ff_2/fn/Dense_0/kernel": P("fsdp", "tp"),
        "transformer/ff_2/fn/Dense_1/kernel": P("tp", "fsdp"),
        "tok_emb/embedding": P("fsdp", "tp"),
    }
    for path, spec in want.items():
        rule, got = specs[path]
        assert rule is not None and got == spec, (path, rule, got)


def test_a_step_on_an_fsdp_tp_mesh_matches_one_chip():
    import optax
    import train_lm
    from dalle_pytorch_tpu.parallel import make_runtime

    losses = []
    for kw in (dict(dp=1, devices=jax.devices()[:1]), dict(dp=1, fsdp=2, tp=2, devices=jax.devices()[:4])):
        lm, params, ids = model_and_params()    # the step donates its state
        state, _, step = train_lm.build_step(lm, params, make_runtime(**kw), 0.5)
        state, loss = step(state, {"ids": ids}, jax.random.key(0), jnp.asarray(3e-4))
        losses.append((float(loss), float(optax.global_norm(state.params))))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)


# ----------------------------------------------------------------------- CLI


def test_train_lm_cli_saves_and_resumes(tmp_path, monkeypatch):
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
    cfg = {**CFG, "hidden_size": 32, "num_attention_heads": 4, "shared_intermediate_size": 48,
           "mamba_n_heads": 4, "vocab_size": vocab}
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    losses = []
    real_log = MetricsLogger.log
    monkeypatch.setattr(MetricsLogger, "log", lambda self, logs, step=None: (
        losses.append(logs["loss"]) if "loss" in logs else None, real_log(self, logs, step=step))[1])
    out = tmp_path / "lm"
    argv = ["--config", str(tmp_path / "config.json"), "--image_text_folder", str(docs),
            "--text_seq_len", "32", "--batch_size", "8", "--epochs", "2", "--remat",
            "--learning_rate", "3e-3", "--lm_output_file_name", str(out)]
    monkeypatch.setattr(sys, "argv", ["train_lm.py"] + argv)
    train_lm.main()
    assert losses and np.all(np.isfinite(losses)) and losses[0] > np.log(vocab) - 1
    state, meta = load_checkpoint(f"{out}.ckpt")
    assert meta["model_class"] == "CausalLM" and meta["epoch"] == 1 and meta["has_opt_state"]
    assert meta["config"]["layer_types"] == ["mamba", "attention", "mamba"]
    first = len(losses)
    monkeypatch.setattr(sys, "argv", ["train_lm.py", "--lm_path", f"{out}.ckpt"] + argv[:-2]
                        + ["--lm_output_file_name", str(out), "--epochs", "3"])
    train_lm.main()
    assert len(losses) > first and np.all(np.isfinite(losses[first:]))
    # resumed from the trained weights, not from a fresh init
    assert losses[first] < losses[0]
    _, meta = load_checkpoint(f"{out}.ckpt")
    assert meta["epoch"] == 2


# ------------------------------------- the DALL-E configurations did not move

DALLE_TREES = {   # leaves, sha256 of the sorted "path shape dtype" lines, at PR 31
    "dalle-d12-full": (162, "019495564e81d9bd186c48a016dbfb1b935ee02d5fccab3f0d385b43225dbd82"),
    "dalle-d12-sparse": (162, "019495564e81d9bd186c48a016dbfb1b935ee02d5fccab3f0d385b43225dbd82"),
    "dalle-d12-sparse-posemb": (165, "75f11ac5ec1bdf15d2c1933b5a5f8636f84646d1c5b8bace356652157a5905b7"),
}


@pytest.mark.parametrize("name", sorted(DALLE_TREES))
def test_every_dalle_configurations_parameter_tree_is_what_it_was(name):
    from benchmarks.drivers.serve import build_dalle, param_shapes

    on_disk = {f.stem for f in (ROOT / "benchmarks" / "configs").glob("dalle-*.json")}
    assert on_disk == set(DALLE_TREES)
    cfg = costs.load_config(name)
    shapes = param_shapes(build_dalle(cfg), cfg)
    lines = sorted(
        f"{jax.tree_util.keystr(k)} {tuple(v.shape)} {v.dtype}"
        for k, v in jax.tree_util.tree_leaves_with_path(shapes)
    )
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()
    assert (len(lines), digest) == DALLE_TREES[name]


# ------------------------------------------------- the second family: JoyAI
# model_type joyai_llm_flash (the DeepSeek-V3 family's keys): latent
# attention, routed experts of which a range is held, a shared expert, an
# untied head and the multi-token-prediction module, through the same train
# path and held to benchmarks/reference_moe.py (float32, one head at a time,
# the experts as a dense loop; imports nothing of the program).

from benchmarks import reference_moe, weights_moe  # noqa: E402
from benchmarks.drivers import train_moe as moe_driver  # noqa: E402

MOE_N = 40
MOE_CFG = {
    **costs.load_config("joyai-llm-flash-d6-ep16"),
    **json.loads((ROOT / "benchmarks/rehearsal_moe.json").read_text())["config"],
}


def moe_model_and_params(remat=False, seed=5, **over):
    cfg = {**MOE_CFG, **over}
    lm = CausalLM.from_config(cfg, seq_len=MOE_N, remat=remat)
    ids = jax.random.randint(jax.random.key(1), (2, MOE_N), 0, cfg["vocab_size"])
    shapes = jax.eval_shape(lm.init, jax.random.key(0), ids)["params"]
    return lm, weights_moe.make_params(shapes, seed, jnp.float32), ids, cfg


@pytest.mark.parametrize("remat", [False, True])
def test_joyai_loss_and_every_leafs_gradient_match_the_reference(remat):
    """Both losses (next token, and the MTP module's token after next), the
    untied head and every leaf, the selection bias included (zero in both)."""
    lm, params, ids, cfg = moe_model_and_params(remat=remat)
    assert lm.ff_types == ("dense", "experts", "experts") and lm.experts_held == (2, 6)
    assert not lm.tie_head and lm.mtp_lambda == 0.3 and "lm_head" in params and "nextn" in params
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: lm.apply({"params": p}, ids, return_loss=True))(params)
        (want, want_loads), want_grads = jax.value_and_grad(
            lambda p: reference_moe.loss(p, cfg, ids), has_aux=True)(params)
        _, loads = lm.loss_and_loads(params, ids)
        balanced = lm.balance(params, loads)
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)
    gaps = leaf_gaps(grads, want_grads)
    assert max(gaps.values()) < 2e-4, max(gaps.items(), key=lambda kv: kv[1])
    # no pair dropped: every expert of every layer was sent what the reference
    # sent it, and the selection bias moves against that as the reference's does
    flat = traverse_util.flatten_dict(dict(params))
    reference_moe.balance(flat, want_loads, cfg["bias_update_speed"])
    want_balanced = traverse_util.unflatten_dict(flat)
    for layer in (("transformer", "ff_1", "fn"), ("transformer", "ff_2", "fn"),
                  ("nextn", "block", "ff_0", "fn")):
        got, wanted = balanced, want_balanced
        for name in layer:
            got, wanted = got[name], wanted[name]
        sent = np.asarray(got["tokens_per_expert"])
        np.testing.assert_array_equal(sent, np.asarray(wanted["tokens_per_expert"]))
        assert sent.sum() == ids.size * cfg["num_experts_per_tok"]
        np.testing.assert_array_equal(
            np.asarray(got["e_score_correction_bias"]), np.asarray(wanted["e_score_correction_bias"])
        )
    stats = lm.routing_stats(balanced)
    lo, hi = lm.experts_held
    assert int(stats["moe.pairs_here"]) == sum(int(v[lo:hi].sum()) for v in want_loads.values()) > 0
    assert float(stats["moe.load_max_over_mean"]) >= 1.0
    assert lm.routing_stats(params)["moe.pairs_here"] == 0          # no step has run
    bias = grads["transformer"]["ff_1"]["fn"]["e_score_correction_bias"]
    assert not np.any(np.asarray(bias))


def test_the_mtp_modules_loss_is_the_references_second_loss():
    """With the next-token loss taken out (the weight of the second loss is
    what is left of a difference), the MTP module alone agrees."""
    lm, params, ids, cfg = moe_model_and_params()
    with jax.default_matmul_precision("highest"):
        both = float(lm.apply({"params": params}, ids, return_loss=True))
        more = float(lm.clone(mtp_lambda=1.3).apply({"params": params}, ids, return_loss=True))
        rows = [reference_moe.row_losses(params, cfg, row) for row in ids]
    second = sum(float(r[2]) for r in rows) / sum(r[3] for r in rows)
    assert abs((more - both) - second) < 1e-5 * second
    assert rows[0][1] == MOE_N - 1 and rows[0][3] == MOE_N - 2


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn", "factor": 40}), ("n_group", 8), ("topk_group", 4),
    ("attention_bias", True), ("moe_layer_freq", 2), ("scoring_func", "softmax"),
    ("topk_method", "greedy"), ("norm_topk_prob", False), ("tie_word_embeddings", True),
    ("rope_interleave", False), ("num_nextn_predict_layers", 2), ("num_key_value_heads", 2),
    ("model_type", "deepseek_v2"),
])
def test_joyai_config_keys_this_model_cannot_run_are_refused(key, value):
    with pytest.raises(ValueError, match=key):
        CausalLM.from_config({**MOE_CFG, key: value}, seq_len=MOE_N)


def test_from_config_builds_both_families_and_reads_the_share_it_is_given():
    cell = costs.load_config("joyai-llm-flash-d6-ep16")
    lm = CausalLM.from_config(cell, seq_len=4096)
    assert (lm.depth, lm.experts_total, lm.experts_held, lm.experts_per_token) == (6, 256, (0, 16), 8)
    assert lm.layer_types == ("mla",) * 6 and lm.ff_types == ("dense",) + ("experts",) * 5
    assert (lm.mla_q_rank, lm.mla_kv_rank, lm.mla_nope_dim, lm.mla_rope_dim, lm.mla_v_dim) == (
        1536, 512, 128, 64, 128)
    with pytest.raises(ValueError, match="experts_held"):
        CausalLM.from_config({**cell, "experts_held": {"range": [0, 32], "of": 256}}, seq_len=4096)
    # the source's own file, with none of this program's three keys: every expert is held
    source = {k: v for k, v in cell.items()
              if k not in ("experts_held", "mtp_loss_weight", "bias_update_speed", "published",
                           "assumed", "deployment", "reduced")}
    whole = CausalLM.from_config({**source, "n_routed_experts": 256}, seq_len=4096)
    assert (whole.experts_total, whole.experts_held) == (256, (0, 256))
    assert (whole.mtp_lambda, whole.bias_update_speed) == (0.3, 0.001) == (
        lm.mtp_lambda, lm.bias_update_speed)
    granite = costs.load_config("granite-4.0-h-micro-d10")
    assert CausalLM.from_config(granite, seq_len=64).ff_types is None
    # the count the configuration file states
    shapes = jax.eval_shape(lm.init, jax.random.key(0), jnp.zeros((1, 4096), jnp.int32))["params"]
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)) == 787_534_848


def moe_step_ctx(seed=11, control=None, **mix):
    base = dict(rows=2, tokens=MOE_N, document_tokens={"min": 4, "max": MOE_N}, mesh={"dp": 1},
                learning_rate=3e-4, clip_grad_norm=0.5, remat=True, check_steps=3)
    return types.SimpleNamespace(cfg=MOE_CFG, mix={**base, **mix}, seed=seed, chips=1,
                                 control=control, facts={})


@pytest.fixture(scope="module")
def moe_reference_three_steps():
    ctx = moe_step_ctx()
    _, shapes = driver._build(ctx)
    fed = [driver.packed_batch(ctx.mix, ctx.cfg, ctx.seed, s) for s in range(3)]
    with jax.default_matmul_precision("highest"):
        return moe_driver.reference_steps(ctx, shapes, fed, "f32", keep_gradient=True)


def first_gradient_distance(program, ref) -> float:
    """The driver's ``grad_worst_leaf_distance``: the first gradient element
    by element, the worst leaf."""
    median = float(np.median(list(ref["grad"].values())))
    return max(
        float(np.linalg.norm(program["grad_leaves"][leaf] - g)) / max(ref["grad"][leaf], median)
        for leaf, g in ref["grad_leaves"].items()
    )


def entries_apart(program, ref, speed=MOE_CFG["bias_update_speed"]) -> float:
    """The driver's ``selection_bias_entries_apart``."""
    ctx = types.SimpleNamespace(
        cfg={"bias_update_speed": speed}, facts={"limits": {"selection_bias_entries_apart": 1.0}},
        compare=lambda name, value, limit: ctx.facts.update({name: value}),
    )
    moe_driver._entries_apart(ctx, program["bias"], ref["bias"])
    return ctx.facts["selection_bias_entries_apart"]


def test_joyai_three_steps_of_make_train_step_match_the_references_three(moe_reference_three_steps):
    job = moe_driver.Job(moe_step_ctx())
    program = job.first_steps()
    assert job.steps == 3 and int(job.state.step) == 3 and int(job.state.skipped) == 0
    ref = moe_reference_three_steps
    gaps = three_step_gaps(program, ref)
    # the two leaves no optimizer writes are held by their own count
    assert not any(leaf.endswith(moe_driver.BUFFERS) for leaf in program["change"])
    assert entries_apart(program, ref) == 0.0
    assert not over_a_limit(gaps), gaps
    assert program["pairs"] == moe_reference_three_steps["pairs"]       # no pair dropped
    assert first_gradient_distance(program, moe_reference_three_steps) < 1e-4


@pytest.mark.parametrize("rejected", [False, True], ids=["applied", "rejected"])
def test_the_step_writes_the_expert_layers_buffers_unless_it_is_rejected(rejected):
    """``make_train_step(after_update=CausalLM.balance)``: an applied step
    leaves what it sent every expert in ``tokens_per_expert`` and has moved
    the selection bias by the speed against it; a step the ``nan_guard``
    rejects keeps both as they were."""
    import optax
    from dalle_pytorch_tpu.parallel import create_train_state, make_runtime, make_train_step

    lm, params, ids, cfg = moe_model_and_params(remat=True)
    runtime = make_runtime(devices=jax.local_devices()[:1], dp=1)
    optimizer = optax.chain(optax.clip_by_global_norm(0.5), optax.scale_by_adam())
    state, shardings = create_train_state(params, optimizer, runtime)
    step = make_train_step(
        lambda p, batch, rng: lm.loss_and_loads(p, batch["ids"]), optimizer, runtime, shardings,
        dynamic_lr=True, after_update=lm.balance, nan_inject_step=0 if rejected else None,
        donate=False,
    )
    new, loss = step(state, {"ids": ids}, jax.random.key(0), jnp.asarray(3e-4))
    before, after = (s.params["transformer"]["ff_1"]["fn"] for s in (state, new))
    sent, moved = np.asarray(after["tokens_per_expert"]), np.asarray(
        after["e_score_correction_bias"] - before["e_score_correction_bias"])
    if rejected:
        assert np.isnan(float(loss)) and int(new.skipped) == 1
        assert not sent.any() and not moved.any()
    else:
        assert sent.sum() == ids.size * cfg["num_experts_per_tok"]
        np.testing.assert_allclose(
            moved, cfg["bias_update_speed"] * np.sign(sent.mean() - sent), atol=1e-9)
        assert moved.any()


def test_a_selection_bias_that_is_never_moved_fails_by_its_entries(moe_reference_three_steps):
    """``bias_held``, the program as it stood before it ran the family's
    rule: every trained leaf agrees and most entries of the bias lie a speed
    or more from the reference's."""
    with moe_driver._planted(moe_step_ctx(control="bias_held")):
        program = moe_driver.Job(moe_step_ctx()).first_steps()
    assert entries_apart(program, moe_reference_three_steps) > 0.5


@pytest.mark.parametrize(
    "fault", [f for f in moe_driver.PROGRAM_FAULTS if f not in ("bf16_rope", "bias_held")])
def test_a_fault_planted_in_the_joyai_program_fails_the_three_steps(fault, moe_reference_three_steps):
    """``bf16_rope`` is not among them: at 40 positions a bfloat16 angle is
    exact to three digits; tests/test_mla.py holds it at position 4,095."""
    from dalle_pytorch_tpu.models import lm as lm_module
    from dalle_pytorch_tpu.ops import moe, rotary

    real = (moe.route, rotary.cos_sin, lm_module.next_ids, lm_module.balanced_bias)
    with moe_driver._planted(moe_step_ctx(control=fault)):
        program = moe_driver.Job(moe_step_ctx()).first_steps()
    gaps = three_step_gaps(program, moe_reference_three_steps)
    assert over_a_limit(gaps), gaps
    assert (moe.route, rotary.cos_sin, lm_module.next_ids, lm_module.balanced_bias) == real
    # element by element the first gradient is far off, whatever its norms say
    assert first_gradient_distance(program, moe_reference_three_steps) > 0.05


def test_half_of_the_tokens_left_out_of_both_joyai_losses_fails(moe_reference_three_steps):
    ctx = moe_step_ctx()
    _, shapes = driver._build(ctx)
    fed = [driver.packed_batch(ctx.mix, ctx.cfg, ctx.seed, s) for s in range(3)]
    with jax.default_matmul_precision("highest"):
        half = moe_driver.reference_steps(ctx, shapes, fed, "f32", positions=MOE_N // 2)
    gaps = three_step_gaps(half, moe_reference_three_steps)
    assert over_a_limit(gaps) and gaps["grad"] > 0.05, gaps


def test_the_joyai_leaves_have_sharding_rules_on_a_cpu_mesh():
    from jax.sharding import PartitionSpec as P
    from dalle_pytorch_tpu.parallel import make_runtime, params_shardings, shard_pytree
    from dalle_pytorch_tpu.parallel.sharding import params_spec_reports

    _, params, _, _ = moe_model_and_params()
    mesh = make_runtime(dp=1, fsdp=2, tp=2, devices=jax.devices()[:4]).mesh
    specs = {r["path"]: (r["rule"], r["spec"]) for r in params_spec_reports(params, mesh, min_size=0)}
    want = {
        "transformer/mixer_0/fn/to_q_a/kernel": P("fsdp", None),
        "transformer/mixer_0/fn/to_q_b/kernel": P("fsdp", "tp"),
        "transformer/mixer_0/fn/to_kv_a/kernel": P("fsdp", None),
        "transformer/mixer_0/fn/to_kv_b/kernel": P("fsdp", "tp"),
        "transformer/mixer_0/fn/to_out/kernel": P("tp", "fsdp"),
        "transformer/ff_0/fn/Dense_0/kernel": P("fsdp", "tp"),
        "transformer/ff_1/fn/experts_in": P("ep", "fsdp", "tp"),
        "transformer/ff_1/fn/experts_out": P("ep", "tp", "fsdp"),
        "transformer/ff_1/fn/gate/kernel": P(None, None),
        "transformer/ff_1/fn/e_score_correction_bias": P(None),
        "transformer/ff_1/fn/shared/Dense_0/kernel": P("fsdp", "tp"),
        "transformer/ff_1/fn/shared/Dense_1/kernel": P("tp", "fsdp"),
        "nextn/block/ff_0/fn/experts_in": P("ep", "fsdp", "tp"),
        "nextn/block/mixer_0/fn/to_q_b/kernel": P("fsdp", "tp"),
        "nextn/eh_proj/kernel": P("fsdp", "tp"),
        "lm_head": P("fsdp", "tp"),
        "tok_emb/embedding": P("fsdp", "tp"),
    }
    for path, spec in want.items():
        rule, got = specs[path]
        assert rule is not None and got == spec, (path, rule, got)
    # and the leaves lie where the rules say
    runtime = make_runtime(dp=1, fsdp=2, tp=2, devices=jax.devices()[:4])
    placed = shard_pytree(params, params_shardings(params, runtime.mesh))
    head = placed["lm_head"]
    assert head.sharding.spec == P("fsdp", "tp") and len(head.addressable_shards) == 4
    assert head.addressable_shards[0].data.shape == (head.shape[0] // 2, head.shape[1] // 2)


def test_the_joyai_cell_takes_the_blocked_flash_route_and_the_grouped_products():
    from dalle_pytorch_tpu.ops import kv_policy

    cell = costs.load_config("joyai-llm-flash-d6-ep16")
    lm = CausalLM.from_config({**cell, "num_hidden_layers": 2}, seq_len=4096, dtype=jnp.bfloat16)
    kv_policy.ROUTE_LOG.clear()
    jax.eval_shape(lm.init, jax.random.key(0), jnp.zeros((1, 4096), jnp.int32))
    routes = {(r["site"], r["impl"]) for r in kv_policy.ROUTE_LOG}
    assert routes == {("forward/mla", "blocked_flash"), ("forward/moe_experts", "ragged_dot")}


def test_train_lm_cli_trains_saves_and_resumes_the_joyai_family(tmp_path, monkeypatch):
    """``train_lm.py --config <joyai file>`` goes through the same loop as the
    granite configuration; with --telemetry it counts what the expert layers
    held here are sent; the checkpoint carries the share and restores it."""
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
    cfg = {**MOE_CFG, "hidden_size": 32, "vocab_size": vocab, "num_hidden_layers": 2}
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
        # both losses at the seed: ln(vocab) and 0.3 of it
        assert losses[0] > 1.3 * np.log(vocab) - 1
        assert counters.get("moe.pairs_here") > before and gauges.get("moe.load_max_over_mean") >= 1.0
        _, meta = load_checkpoint(f"{out}.ckpt")
        assert meta["config"]["ff_types"] == ["dense", "experts"]
        assert meta["config"]["experts_held"] == [2, 6] and meta["config"]["experts_total"] == 8
        first = len(losses)
        monkeypatch.setattr(sys, "argv", ["train_lm.py", "--lm_path", f"{out}.ckpt"] + argv[:-2]
                            + ["--lm_output_file_name", str(out), "--epochs", "2"])
        train_lm.main()
        assert len(losses) > first and losses[first] < losses[0]
    finally:
        TELEMETRY.configure(enabled=False)
