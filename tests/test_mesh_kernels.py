"""Pallas attention under a multi-device mesh (ISSUE 21, found on a
four-chip v5e): XLA cannot partition a Mosaic kernel — inside a
multi-device jit, lowering for the TPU stops with "Mosaic kernels cannot
be automatically partitioned. Please wrap the call in a shard_map." The
CPU tier never saw it, because interpreted kernels are plain jnp code that
partitions like any other. ``ops/attention.py:_per_device`` wraps each
kernel call in a shard_map over batch (dp, fsdp) and heads (tp).

Two checks, both on the CPU:

- the sharded train step CROSS-LOWERS for the TPU platform on a 4-device
  host mesh with the compiled (non-interpret) kernel branch forced — the
  exact lowering rule that failed on the chip — for dp, fsdp x tp and pp
  meshes; and it fails the same way again if the wrapper is
  bypassed, so the check cannot pass vacuously;
- numerically, the wrapped kernels under an fsdp x tp mesh match the
  unwrapped single-device call, forward and gradient.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dalle_pytorch_tpu.models import DALLE
from dalle_pytorch_tpu.ops import attention as attention_mod
from dalle_pytorch_tpu.ops import kv_policy
from dalle_pytorch_tpu.ops.attention import PatternAttention
from dalle_pytorch_tpu.parallel import (
    create_train_state,
    make_runtime,
    make_train_step,
)

# seq 64 + 8*8 = 128: the smallest shape the flash kernels take
MODEL = dict(
    dim=128, depth=1, num_text_tokens=64, text_seq_len=64, num_image_tokens=32,
    image_fmap_size=8, heads=2, dim_head=64, dtype=jnp.bfloat16,
)


def lower_for_tpu(attn_types=("full",), **mesh):
    """StableHLO of the sharded train step, lowered for the TPU platform
    on the first four host devices."""
    pp = mesh.get("pp", 1)
    dalle = DALLE(
        **{**MODEL, "depth": pp}, attn_types=attn_types,
        pp_axis="pp" if pp > 1 else None,
    )
    rt = make_runtime(devices=jax.devices()[:4], **mesh)
    text = jnp.ones((4, 64), jnp.int32)
    image = jnp.ones((4, 64), jnp.int32)
    shapes = jax.eval_shape(
        lambda: dalle.init(jax.random.key(0), text[:1], image[:1])
    )["params"]
    params = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    opt = optax.adam(1e-3)
    state, shardings = create_train_state(params, opt, rt)
    step = make_train_step(
        lambda p, b, rng: dalle.apply(
            {"params": p}, b["text"], b["image"], return_loss=True
        ),
        opt, rt, shardings,
    )
    with rt.activate():
        traced = step.jitted.trace(
            state, {"text": text, "image": image}, jax.random.key(0)
        )
        return traced.lower(lowering_platforms=("tpu",)).as_text()


@pytest.fixture
def compiled_branch(monkeypatch):
    """Trace the branch a TPU takes: kernels compiled, not interpreted."""
    monkeypatch.setattr(kv_policy, "on_tpu", lambda: True)


@pytest.mark.parametrize("attn_types,mesh", [
    (("full",), {}),                          # dp=4: fused-qkv kernel
    (("axial_row",), {"fsdp": 2, "tp": 2}),   # per-head flash, heads over tp
    (("full",), {"pp": 2}),                   # nested in the pp region
], ids=["dp4", "axial_fsdp2_tp2", "pp2_dp2"])
def test_sharded_train_step_lowers_for_tpu(compiled_branch, attn_types, mesh):
    text = lower_for_tpu(attn_types, **mesh)
    assert "tpu_custom_call" in text  # the kernels are really in there


def test_unwrapped_kernel_reproduces_the_chip_failure(compiled_branch, monkeypatch):
    monkeypatch.setattr(
        attention_mod, "_per_device",
        lambda kernel, args: kernel(*args),
    )
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        lower_for_tpu(("full",))


def test_wrapped_kernels_match_single_device():
    """fsdp=2 x tp=2: batch and heads both split. Interpreted kernels, so
    this pins the shard_map plumbing (specs, mask operand, result layout),
    not Mosaic."""
    attn = PatternAttention(
        dim=128, seq_len=129, heads=2, dim_head=64, image_fmap_size=8,
        attn_type="axial_row",
    )
    x = jax.random.normal(jax.random.key(0), (4, 128, 128))
    mask = jnp.ones((4, 128), bool).at[:, -5:].set(False)
    params = attn.init(jax.random.key(1), x)

    def loss(p, x):
        return (attn.apply(p, x, mask=mask) ** 2).sum()

    want, want_g = jax.value_and_grad(loss)(params, x)
    rt = make_runtime(devices=jax.devices()[:4], fsdp=2, tp=2)
    kv_policy.ROUTE_LOG.clear()
    with rt.activate():
        got, got_g = jax.jit(jax.value_and_grad(loss))(params, x)
    # tp > 1: the per-head flash path, not the packed-qkv one
    assert {"site": "forward/axial_row", "impl": "blocked_flash",
            "interpret": True} in kv_policy.ROUTE_LOG
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


# ---- the state-space scan's kernels (ops/ssm.py) go through the same wrapper


def _mixer_loss(dtype):
    from dalle_pytorch_tpu.ops.ssm import MambaMixer

    # the smallest mixer the kernels take: 8 heads of 64, state 128, chunk 128
    mixer = MambaMixer(dim=128, n_heads=8, d_head=64, d_state=128, chunk=128, dtype=dtype)
    return mixer, lambda p, v: (mixer.apply(p, v).astype(jnp.float32) ** 2).sum()


@pytest.mark.parametrize("mesh", [{}, {"fsdp": 2, "tp": 2}], ids=["dp4", "fsdp2_tp2"])
def test_state_space_kernels_lower_for_tpu_under_a_mesh(compiled_branch, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dalle_pytorch_tpu.parallel.context import batch_axes

    mixer, loss = _mixer_loss(jnp.bfloat16)
    rt = make_runtime(devices=jax.devices()[:4], **mesh)
    rows = NamedSharding(rt.mesh, P(batch_axes(rt.mesh)))
    v = jax.ShapeDtypeStruct((4, 256, 128), jnp.bfloat16, sharding=rows)
    with rt.activate():
        params = jax.eval_shape(mixer.init, jax.random.key(0), v)
        traced = jax.jit(jax.value_and_grad(loss)).trace(params, v)
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
    # the scan's state and chunk and the convolution, forward and backward
    assert text.count("tpu_custom_call") >= 6


@pytest.mark.parametrize("n,conv", [(200, "xla"), (256, "ssm_conv")])
def test_state_space_kernels_under_a_mesh_match_single_device(n, conv):
    """A ragged length (the scan pads it, the convolution keeps XLA's form)
    and a whole block of rows (the convolution's kernels too)."""
    mixer, loss = _mixer_loss(jnp.float32)
    v = jax.random.normal(jax.random.key(0), (4, n, 128))
    params = mixer.init(jax.random.key(1), v)
    want, want_g = jax.value_and_grad(loss)(params, v)
    rt = make_runtime(devices=jax.devices()[:4], fsdp=2, tp=2)
    kv_policy.ROUTE_LOG.clear()
    with rt.activate():
        got, got_g = jax.jit(jax.value_and_grad(loss))(params, v)
    assert {"site": "forward/ssd", "impl": "ssd_chunk", "interpret": True} in kv_policy.ROUTE_LOG
    assert [r["impl"] for r in kv_policy.ROUTE_LOG if r["site"] == "forward/ssm_conv"] == [conv]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g), jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.max(jnp.abs(b))) + 1e-6, rtol=2e-4)


# ---- the gated delta rule's kernels (ops/gdn.py) go through the same wrapper


def _delta_loss(dtype):
    from dalle_pytorch_tpu.ops.gdn import GatedDeltaNet

    # the smallest mixer the kernels take: keys and values of one lane tile
    mixer = GatedDeltaNet(dim=128, key_heads=1, value_heads=2, key_dim=128, value_dim=128,
                          chunk=16, dtype=dtype)
    return mixer, lambda p, v: (mixer.apply(p, v).astype(jnp.float32) ** 2).sum()


@pytest.mark.parametrize("mesh", [{}, {"fsdp": 2, "tp": 2}], ids=["dp4", "fsdp2_tp2"])
def test_delta_rule_kernels_lower_for_tpu_under_a_mesh(compiled_branch, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dalle_pytorch_tpu.parallel.context import batch_axes

    mixer, loss = _delta_loss(jnp.bfloat16)
    rt = make_runtime(devices=jax.devices()[:4], **mesh)
    rows = NamedSharding(rt.mesh, P(batch_axes(rt.mesh)))
    v = jax.ShapeDtypeStruct((4, 256, 128), jnp.bfloat16, sharding=rows)
    with rt.activate():
        params = jax.eval_shape(mixer.init, jax.random.key(0), v)
        traced = jax.jit(jax.value_and_grad(loss)).trace(params, v)
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
    # the rule and the convolution without a bias, forward and backward
    assert text.count("tpu_custom_call") >= 4


def test_delta_rule_kernels_under_a_mesh_match_single_device():
    mixer, loss = _delta_loss(jnp.float32)
    v = jax.random.normal(jax.random.key(0), (4, 40, 128))
    params = mixer.init(jax.random.key(1), v)
    want, want_g = jax.value_and_grad(loss)(params, v)
    rt = make_runtime(devices=jax.devices()[:4], fsdp=2, tp=2)
    kv_policy.ROUTE_LOG.clear()
    with rt.activate():
        got, got_g = jax.jit(jax.value_and_grad(loss))(params, v)
    assert {"site": "forward/delta_rule", "impl": "gdn_chunk", "interpret": True} in kv_policy.ROUTE_LOG
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g), jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.max(jnp.abs(b))) + 1e-6, rtol=2e-4)
