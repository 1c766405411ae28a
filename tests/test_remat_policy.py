"""``remat=True`` and the attention kernels (models/transformer.py:
``_block_checkpoint``): a block's checkpoint rebuilds everything in backward
but the flash kernels' output and log-sum-exp, which it keeps by name
(ops/flash_attention.py:``KERNEL_RESIDUAL_NAMES``). Held here, on the CPU with
interpreted kernels at tiny widths: the gradient's jaxpr holds each attention
forward kernel once a layer where the bare ``jax.checkpoint`` held it twice;
the gradients are the bare checkpoint's to the bit and ``remat=False``'s to
rounding; the pipeline path and a mesh of several devices (the kernels then
run inside ``ops/attention.py:_per_device``'s shard_map) keep the count; and
the route ``remat/attn_residuals`` says the rule engaged.

The same checkpoint keeps an expert layer's routing, dispatch, gathered rows
and first grouped product (ops/moe.py:``MOE_RESIDUAL_NAMES``), in the three
expert families: its rebuilt forward holds one grouped product where it held
two, and no sort and no ``top_k``; the gradients are the bare checkpoint's to
the bit, also where the count passes the first chunk; the route
``remat/moe_residuals`` is recorded for each expert block and no other; a
block without experts lowers as it did.

And it keeps the delta rules' ``T | P`` table (ops/gdn.py:
``DELTA_RESIDUAL_NAMES``), in a KDA stack and a gated-delta-rule stack whose
heads are whole lane tiles, so that the rule takes its kernels: the gradient
holds the tables kernel once a delta-rule layer where the bare checkpoint held
it twice; the gradients are the bare checkpoint's to the bit; the route
``remat/delta_tables`` is recorded for each delta-rule block and no other; a
block without the kernels lowers as it did."""

import collections
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks import costs
from dalle_pytorch_tpu.models import DALLE
from dalle_pytorch_tpu.models import transformer
from dalle_pytorch_tpu.models.lm import CausalLM
from dalle_pytorch_tpu.ops import kv_policy
from dalle_pytorch_tpu.ops import moe as moe_ops
from dalle_pytorch_tpu.ops.flash_attention import KERNEL_RESIDUAL_NAMES
from dalle_pytorch_tpu.ops.moe import MOE_RESIDUAL_NAMES
from dalle_pytorch_tpu.parallel import make_runtime

ROOT = pathlib.Path(__file__).resolve().parents[1]
N = 128     # the smallest row the flash kernels take: one block
DEPTH = 2
ROUTE = {"site": "remat/attn_residuals", "impl": "saved", "interpret": None}

GQA = dict(
    model_type="granitemoehybrid",
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2, vocab_size=50,
    shared_intermediate_size=96, num_hidden_layers=DEPTH, layer_types=["attention"] * DEPTH,
    attention_multiplier=0.2, embedding_multiplier=12, residual_multiplier=0.22,
    logits_scaling=8, rms_norm_eps=1e-5, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=8, mamba_d_conv=4, mamba_chunk_size=8, mamba_expand=2,
)
# the rehearsal's tiny joyai: a dense block, an expert block and the MTP
# module's block, each with a latent-attention layer
MLA = {
    **costs.load_config("joyai-llm-flash-d6-ep16"),
    **json.loads((ROOT / "benchmarks/rehearsal_moe.json").read_text())["config"],
    "num_hidden_layers": DEPTH,
}

def rehearsal(cell, name):
    return {**costs.load_config(cell), **json.loads((ROOT / name).read_text())["config"]}


# the three expert families at their rehearsals' sizes: sigmoid scores, SwiGLU
# experts and a shared expert (joyai, the dense block, one expert block and the
# MTP module's); softmax scores and a gated shared expert (qwen3next, four
# expert blocks); probabilities handed from the block's mixer and ReGLU experts
# (smallthinker, four expert blocks)
EXPERTS = {
    "sigmoid_swiglu_shared": MLA,
    "softmax_gated_shared": rehearsal("qwen3-next-80b-a3b-d4-ep16", "benchmarks/rehearsal_gdn.json"),
    "handed_probs_reglu": rehearsal("smallthinker-21b-a3b-d4-ep4", "benchmarks/rehearsal_swa.json"),
}
# the delta rules at heads of 128 keys and 128 values, the kernels' smallest:
# KDA two to one with latent attention (2 heads) and two gated-delta-rule
# layers (a key head serving 2 value heads), each otherwise its rehearsal
DELTA = {
    "kda": {
        **rehearsal("kimi-linear-48b-a3b-d5-ep32", "benchmarks/rehearsal_kda.json"),
        "num_hidden_layers": 3,
        "linear_attn_config": {
            "full_attn_layers": [3], "head_dim": 128, "kda_layers": [1, 2], "num_heads": 2,
            "short_conv_kernel_size": 4,
        },
    },
    "gdn": {
        **EXPERTS["softmax_gated_shared"], "num_hidden_layers": 2,
        "linear_num_key_heads": 1, "linear_num_value_heads": 2,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    },
}
TABLES = {"kda": "kda_chunk_tables", "gdn": "gdn_chunk_tables"}
DALLE_FULL = dict(
    dim=128, depth=DEPTH, num_text_tokens=64, text_seq_len=64, num_image_tokens=32,
    image_fmap_size=8, heads=2, dim_head=64, attn_types=("full",),
)
# family -> the attention forward kernel its layers call on one device, and
# how many attention layers the stack has
FWD_KERNEL = {"gqa": "flash_fwd", "mla": "flash_fwd", "full": "flash_qkv_fwd"}
LAYERS = {"gqa": DEPTH, "mla": DEPTH + 1, "full": DEPTH, "kda": 2}


def build(family, remat, **over):
    """(loss of the parameters, parameters) of a tiny stack of ``family``."""
    if family == "full":
        model = DALLE(**{**DALLE_FULL, **over}, remat=remat)
        text = jax.random.randint(jax.random.key(1), (4, 64), 1, 64)
        image = jax.random.randint(jax.random.key(2), (4, 64), 0, 32)
        params = model.init(jax.random.key(0), text[:1], image[:1])["params"]
        return lambda p: model.apply({"params": p}, text, image, return_loss=True), params
    model = CausalLM.from_config({"gqa": GQA, "mla": MLA, **EXPERTS, **DELTA}[family], seq_len=N, remat=remat)
    ids = jax.random.randint(jax.random.key(1), (4, N), 0, 50)      # every vocabulary holds 50
    params = model.init(jax.random.key(0), ids)["params"]
    return lambda p: model.apply({"params": p}, ids, return_loss=True), params


def kernel_calls(jaxpr, into=None) -> collections.Counter:
    """How often each named ``pallas_call`` stands in ``jaxpr``, the jaxprs
    of its checkpoints, shard_maps, scans and custom rules included."""
    into = collections.Counter() if into is None else into
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            into[eqn.params["name"]] += 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            kernel_calls(inner, into)
    return into


def grad_kernel_calls(loss, params) -> collections.Counter:
    return kernel_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)


def bare_checkpoint(monkeypatch):
    """From here on the trunk builds the checkpoint it used before: nothing kept."""
    monkeypatch.setattr(transformer, "_block_checkpoint", lambda fn, block="": jax.checkpoint(fn))


def names_only(monkeypatch, *names):
    """From here on the trunk's checkpoint keeps ``names`` alone, a policy a block."""
    monkeypatch.setattr(transformer, "_block_checkpoint", lambda fn, block="": jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(*names)))


def attention_names_only(monkeypatch):
    """The flash kernels' names alone, as before the expert layer's names."""
    names_only(monkeypatch, *KERNEL_RESIDUAL_NAMES)


def leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def assert_equal_to_rounding(got, want):
    for a, b in zip(leaves(got), leaves(want)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5 * scale, a.shape


@pytest.mark.parametrize("family", ["gqa", "mla", "full"])
def test_the_gradient_holds_each_attention_forward_kernel_once_a_layer(family):
    kv_policy.ROUTE_LOG.clear()
    calls = grad_kernel_calls(*build(family, remat=True))
    assert calls[FWD_KERNEL[family]] == LAYERS[family], calls
    assert calls == grad_kernel_calls(*build(family, remat=False)), calls
    assert ROUTE in kv_policy.ROUTE_LOG


@pytest.mark.parametrize("family", ["gqa", "mla", "full"])
def test_the_bare_checkpoint_held_it_twice(family, monkeypatch):
    """What the count above is compared with: the test cannot pass because
    the counter sees nothing inside a checkpoint."""
    bare_checkpoint(monkeypatch)
    kv_policy.ROUTE_LOG.clear()
    calls = grad_kernel_calls(*build(family, remat=True))
    assert calls[FWD_KERNEL[family]] == 2 * LAYERS[family], calls
    assert ROUTE not in kv_policy.ROUTE_LOG


@pytest.mark.parametrize("family", ["gqa", "mla", "full", *sorted(DELTA)])
def test_gradients_are_the_bare_checkpoints_to_the_bit(family, monkeypatch):
    """The kept arrays are the ones the rebuilt forward would have produced.
    Operation by operation, not under one ``jit``: there XLA fuses the two
    programs' interpreted kernels differently on the CPU and the last bit of
    a sum moves with the fusion, not with the checkpoint."""
    loss, params = build(family, remat=True)
    kept = jax.value_and_grad(loss)(params)
    bare_checkpoint(monkeypatch)
    bare = jax.value_and_grad(loss)(params)
    for a, b in zip(leaves(kept), leaves(bare)):
        assert a.dtype == b.dtype and bool(jnp.all(a == b)), a.shape


@pytest.mark.parametrize("family", ["gqa", "mla", "full"])
def test_gradients_match_no_remat_to_rounding(family):
    kept = jax.jit(jax.value_and_grad(build(family, remat=True)[0]))
    plain_loss, params = build(family, remat=False)
    assert_equal_to_rounding(kept(params), jax.jit(jax.value_and_grad(plain_loss))(params))


def test_the_names_lower_to_nothing_outside_a_checkpoint():
    """``remat=False`` (the DALL-E cell): the forward rule's two names are
    identities, and the lowered program holds no trace of them; nor of the
    expert layer's four, in a model that names all six."""
    loss, params = build("full", remat=False)
    names = [
        eqn.params["name"] for eqn in jax.make_jaxpr(jax.grad(loss))(params).jaxpr.eqns
        if eqn.primitive.name == "name"
    ]
    assert sorted(names) == sorted(KERNEL_RESIDUAL_NAMES * DEPTH)
    text = jax.jit(jax.grad(loss)).lower(params).as_text()
    assert not any(name in text for name in KERNEL_RESIDUAL_NAMES)

    loss, params = build("handed_probs_reglu", remat=False)
    names = {
        eqn.params["name"] for eqn in eqns(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
        if eqn.primitive.name == "name"
    }
    assert names == set(KERNEL_RESIDUAL_NAMES + MOE_RESIDUAL_NAMES)
    text = jax.jit(jax.grad(loss)).lower(params).as_text()
    assert not any(name in text for name in names)


# ------------------------------------------------------ the expert layer's names


def eqns(jaxpr, rebuilt=False, only_rebuilt=False):
    """Every equation of ``jaxpr`` and of the jaxprs inside its equations; with
    ``only_rebuilt``, only those inside a checkpoint's backward (``remat2``):
    the rebuilt forward and its transposes."""
    for eqn in jaxpr.eqns:
        if rebuilt or not only_rebuilt:
            yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns(inner, rebuilt or eqn.primitive.name == "remat2", only_rebuilt)


def grad_primitives(loss, params, only_rebuilt=False) -> collections.Counter:
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    return collections.Counter(eqn.primitive.name for eqn in eqns(jaxpr, only_rebuilt=only_rebuilt))


def expert_blocks(family) -> set:
    """The blocks an expert family's stack checkpoints with an expert layer."""
    model = CausalLM.from_config(EXPERTS[family], seq_len=N)
    blocks = {f"transformer/ff_{i}" for i, kind in enumerate(model.ff_types) if kind == "experts"}
    return blocks | ({"nextn/block/ff_0"} if model.mtp_lambda is not None else set())


def one_chunk(monkeypatch):
    """Every layer's first chunk holds all its pairs: no loop over further chunks."""
    monkeypatch.setattr(moe_ops, "CHUNK_MULTIPLE", 1 << 20)


def chunks_of_8(monkeypatch):
    """Chunks of 8 rows: every layer's count passes the first chunk."""
    monkeypatch.setattr(moe_ops, "HEADROOM", 0.0)
    monkeypatch.setattr(moe_ops, "CHUNK_MULTIPLE", 8)


@pytest.mark.parametrize("family", sorted(EXPERTS))
def test_a_rebuilt_expert_layer_runs_one_grouped_product_where_it_ran_two(family, monkeypatch):
    """Two forward products, four in the backward pass and, rebuilt, only the
    second: 7 a layer, where the bare checkpoint rebuilt both (8) and no
    checkpoint rebuilds none (6)."""
    one_chunk(monkeypatch)
    layers = len(expert_blocks(family))
    products = lambda remat: grad_primitives(*build(family, remat=remat))["ragged_dot_general"]
    assert products(True) == 7 * layers
    assert products(False) == 6 * layers
    bare_checkpoint(monkeypatch)
    assert products(True) == 8 * layers


@pytest.mark.parametrize("family", sorted(EXPERTS))
def test_the_rebuilt_forward_holds_no_sort_and_no_top_k(family, monkeypatch):
    """Nor the router's dot and function: the kept scores, choice and order
    stand in for them. The checkpoint that kept the attention kernels' names
    alone rebuilt a sort and a ``top_k`` a layer, so the count does see inside
    the checkpoint."""
    loss, params = build(family, remat=True)
    kept = grad_primitives(loss, params, only_rebuilt=True)
    assert kept["sort"] == kept["top_k"] == 0, kept
    attention_names_only(monkeypatch)
    before = grad_primitives(loss, params, only_rebuilt=True)
    layers = len(expert_blocks(family))
    assert before["sort"] == before["top_k"] == layers, before
    # the router's dot a layer, and for softmax scores its `exp`
    assert before["dot_general"] - kept["dot_general"] == layers
    assert before["exp"] - kept["exp"] == (0 if family == "sigmoid_swiglu_shared" else layers)


@pytest.mark.parametrize("chunks", [one_chunk, chunks_of_8], ids=["one_chunk", "chunks_of_8"])
@pytest.mark.parametrize("family", sorted(EXPERTS))
def test_expert_gradients_are_the_bare_checkpoints_to_the_bit(family, chunks, monkeypatch):
    """What is kept is what the rebuilt forward would have produced, and the
    loop over further chunks keeps its own backward. Operation by operation,
    as above."""
    chunks(monkeypatch)
    loss, params = build(family, remat=True)
    kept = jax.value_and_grad(loss)(params)
    bare_checkpoint(monkeypatch)
    bare = jax.value_and_grad(loss)(params)
    for a, b in zip(leaves(kept), leaves(bare)):
        assert a.dtype == b.dtype and bool(jnp.all(a == b)), a.shape


@pytest.mark.parametrize("family", [*sorted(EXPERTS), "gqa", "full"])
def test_the_expert_route_is_recorded_for_expert_blocks_and_no_other(family):
    """One entry an expert block, with the bytes each name keeps of it; none
    for a stack without experts, and none without ``remat``."""
    want = expert_blocks(family) if family in EXPERTS else set()
    routes = lambda: [r for r in kv_policy.ROUTE_LOG if r["site"] == "remat/moe_residuals"]
    loss, params = build(family, remat=False)
    kv_policy.ROUTE_LOG.clear()
    jax.make_jaxpr(jax.grad(loss))(params)
    assert routes() == []
    loss, params = build(family, remat=True)
    kv_policy.ROUTE_LOG.clear()
    jax.make_jaxpr(jax.grad(loss))(params)
    assert sorted(r["block"] for r in routes()) == sorted(want)
    for r in routes():
        assert r["impl"] == "saved" and tuple(r["bytes"]) == MOE_RESIDUAL_NAMES
        assert all(n > 0 for n in r["bytes"].values())


@pytest.mark.parametrize("family", ["gqa", "full"])
def test_a_block_without_experts_lowers_as_it_did(family, monkeypatch):
    """granite's and DALL-E's blocks: the gradient's jaxpr under the policy
    with the expert layer's names is the one under the attention kernels'
    names alone, equation for equation (the policy function's address aside)."""
    loss, params = build(family, remat=True)
    text = lambda: re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(jax.grad(loss))(params)))
    now = text()
    attention_names_only(monkeypatch)
    assert now == text()


@pytest.mark.parametrize("family", ["gqa", "full", *sorted(EXPERTS)])
def test_a_block_without_a_delta_rule_kernel_lowers_as_it_did(family, monkeypatch):
    """granite's, DALL-E's, joyai's and smallthinker's blocks, and qwen3next's
    at its rehearsal's heads of 16 (the rule's XLA form): the gradient's jaxpr
    under the policy with the delta rules' name is the one without it."""
    loss, params = build(family, remat=True)
    text = lambda: re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(jax.grad(loss))(params)))
    now = text()
    names_only(monkeypatch, *KERNEL_RESIDUAL_NAMES, *MOE_RESIDUAL_NAMES)
    assert now == text()


# ------------------------------------------------------ the delta rules' table


def delta_blocks(family) -> set:
    """The mixer blocks a stack checkpoints with a delta rule's kernels."""
    model = CausalLM.from_config(DELTA[family], seq_len=N)
    return {
        f"transformer/mixer_{i}" for i, kind in enumerate(model.layer_types)
        if kind in ("kda", "linear_attention")
    }


@pytest.mark.parametrize("family", sorted(DELTA))
def test_the_gradient_holds_the_tables_kernel_once_a_delta_rule_layer(family, monkeypatch):
    """Kept, the table stands in for the state-free kernel in the rebuilt
    forward: once a layer, where the bare checkpoint ran it twice. The kernel
    that carries the state still runs again (its states are not kept), and
    the backward kernel once."""
    layers = len(delta_blocks(family))
    loss, params = build(family, remat=True)
    kept = grad_kernel_calls(loss, params)
    tables, fwd, bwd = (TABLES[family].replace("tables", part) for part in ("tables", "fwd", "bwd"))
    assert (kept[tables], kept[fwd], kept[bwd]) == (layers, 2 * layers, layers), kept
    bare_checkpoint(monkeypatch)
    bare = grad_kernel_calls(loss, params)
    assert (bare[tables], bare[fwd], bare[bwd]) == (2 * layers, 2 * layers, layers), bare


@pytest.mark.parametrize("family", [*sorted(DELTA), "softmax_gated_shared", "gqa", "full"])
def test_the_delta_route_is_recorded_for_delta_rule_blocks_and_no_other(family):
    """One entry a delta-rule block, with the table's bytes: 4 rows x 2 heads
    x 128 positions x 128 lanes in float32; none for a stack whose rule takes
    its XLA form, none for a stack without the rule, and none without ``remat``."""
    want = delta_blocks(family) if family in DELTA else set()
    routes = lambda: [r for r in kv_policy.ROUTE_LOG if r["site"] == "remat/delta_tables"]
    loss, params = build(family, remat=False)
    kv_policy.ROUTE_LOG.clear()
    jax.make_jaxpr(jax.grad(loss))(params)
    assert routes() == []
    loss, params = build(family, remat=True)
    kv_policy.ROUTE_LOG.clear()
    jax.make_jaxpr(jax.grad(loss))(params)
    assert sorted(r["block"] for r in routes()) == sorted(want)
    for r in routes():
        assert r["impl"] == "saved" and r["bytes"] == 4 * 2 * N * 128 * 4


# ------------------------------------------- several devices, and the pipeline


@pytest.mark.parametrize("family,mesh,kernel", [
    ("gqa", {"fsdp": 2, "tp": 2}, "flash_fwd"),
    ("mla", {"fsdp": 2, "tp": 2}, "flash_fwd"),
    ("full", {"fsdp": 2, "tp": 2}, "flash_fwd"),      # tp > 1: the per-head kernels
    ("full", {}, "flash_qkv_fwd"),                    # dp = 4: the packed kernel
    ("kda", {"fsdp": 2, "tp": 2}, "kda_chunk_tables"),  # a head a device
], ids=["gqa_fsdp2_tp2", "mla_fsdp2_tp2", "full_fsdp2_tp2", "full_dp4", "kda_fsdp2_tp2"])
def test_the_count_holds_through_the_per_device_shard_map(family, mesh, kernel, monkeypatch):
    """The checkpoint's partial evaluation sees the names through the
    shard_map the kernels run in on a mesh of several devices."""
    rt = make_runtime(devices=jax.devices()[:4], **mesh)
    loss, params = build(family, remat=True)
    with rt.activate():
        kept = grad_kernel_calls(loss, params)
        bare_checkpoint(monkeypatch)
        bare = grad_kernel_calls(loss, params)
    assert kept[kernel] == LAYERS[family] and bare[kernel] == 2 * LAYERS[family], (kept, bare)


def test_the_pipeline_path_takes_the_same_helper(monkeypatch):
    """``_pp_forward`` stacks the layers and scans one ``layer_fn`` over the
    schedule: the kernel stands once in the jaxpr for the forward pass and,
    under the bare checkpoint, once more for the backward pass's rebuild."""
    rt = make_runtime(devices=jax.devices()[:4], pp=2)
    loss, params = build("full", remat=True, pp_axis="pp", pp_microbatches=2)
    kv_policy.ROUTE_LOG.clear()
    with rt.activate():
        kept = grad_kernel_calls(loss, params)
        assert ROUTE in kv_policy.ROUTE_LOG
        bare_checkpoint(monkeypatch)
        bare = grad_kernel_calls(loss, params)
    assert kept["flash_qkv_fwd"] == 1 and bare["flash_qkv_fwd"] == 2, (kept, bare)
    assert kept["flash_qkv_bwd"] == bare["flash_qkv_bwd"] == 1


def test_gradients_on_a_mesh_match_one_device():
    loss, params = build("gqa", remat=True)
    want = jax.jit(jax.value_and_grad(loss))(params)
    rt = make_runtime(devices=jax.devices()[:4], fsdp=2, tp=2)
    with rt.activate():
        got = jax.jit(jax.value_and_grad(loss))(params)
    assert_equal_to_rounding(got, want)
