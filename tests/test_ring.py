"""Sequence-parallelism tests: ring attention + Ulysses all-to-all parity
against the dense oracle, and full-model sp-vs-single-device equivalence on
the 8-device virtual CPU mesh (conftest.py).

The reference has no sequence parallelism (SURVEY.md §5.7); these tests pin
the TPU-native sp layer: sharding the sequence over the ``sp`` mesh axis must
be a pure layout change — identical forward values and gradients.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dalle_pytorch_tpu.models import DALLE
from dalle_pytorch_tpu.ops.attention import PatternAttention, dense_attend
from dalle_pytorch_tpu.ops.ring_attention import ring_attention, ulysses_attend
from dalle_pytorch_tpu.parallel import activate_mesh, make_runtime


def sp_mesh(n=8):
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n), ("sp",))


def causal_oracle(q, k, v, scale, key_mask=None):
    mask = jnp.tril(jnp.ones((q.shape[2], q.shape[2]), bool))[None, None]
    if key_mask is not None:
        mask = mask & key_mask[:, None, None, :]
    return dense_attend(q * scale, k, v, mask)


@pytest.mark.parametrize("use_mask", [False, True])
def test_ring_attention_forward_parity(use_mask):
    mesh = sp_mesh()
    rng = np.random.RandomState(0)
    b, h, n, d = 2, 4, 64, 16
    q, k, v = (jnp.asarray(rng.randn(b, h, n, d), jnp.float32) for _ in range(3))
    scale = d**-0.5
    # keep key 0 visible so no causal row is fully masked (the dense oracle
    # averages V on fully-masked rows; ring's contract returns exact 0 there,
    # covered by test_ring_attention_noncausal_and_masked_rows)
    km = (
        jnp.asarray(rng.rand(b, n) > 0.2).at[:, 0].set(True)
        if use_mask
        else None
    )

    body = functools.partial(
        ring_attention, axis_name="sp", axis_size=8, causal=True, sm_scale=scale
    )
    spec = P(None, None, "sp", None)
    if use_mask:
        fn = jax.jit(
            jax.shard_map(
                lambda q, k, v, m: body(q, k, v, key_mask=m),
                mesh=mesh,
                in_specs=(spec, spec, spec, P(None, "sp")),
                out_specs=spec,
                check_vma=False,
            )
        )
        out = fn(q, k, v, km)
    else:
        fn = jax.jit(
            jax.shard_map(
                body, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                check_vma=False,
            )
        )
        out = fn(q, k, v)

    expected = causal_oracle(q, k, v, scale, km)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


def test_ring_attention_noncausal_and_masked_rows():
    """Non-causal ring matches dense; a fully-masked query row yields 0."""
    mesh = sp_mesh()
    rng = np.random.RandomState(1)
    b, h, n, d = 1, 2, 32, 8
    q, k, v = (jnp.asarray(rng.randn(b, h, n, d), jnp.float32) for _ in range(3))
    scale = d**-0.5
    km = jnp.zeros((b, n), bool)  # nothing attendable anywhere

    spec = P(None, None, "sp", None)
    fn = jax.jit(
        jax.shard_map(
            lambda q, k, v, m: ring_attention(
                q, k, v, "sp", 8, causal=False, sm_scale=scale, key_mask=m
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec, P(None, "sp")),
            out_specs=spec,
            check_vma=False,
        )
    )
    out = fn(q, k, v, km)
    np.testing.assert_array_equal(np.asarray(out), 0.0)

    km = jnp.ones((b, n), bool)
    out = fn(q, k, v, km)
    expected = dense_attend(q * scale, k, v, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


def test_ring_attention_gradient_parity():
    mesh = sp_mesh()
    rng = np.random.RandomState(2)
    b, h, n, d = 1, 2, 32, 8
    q, k, v = (jnp.asarray(rng.randn(b, h, n, d), jnp.float32) for _ in range(3))
    w = jnp.asarray(rng.randn(b, h, n, d), jnp.float32)
    scale = d**-0.5
    spec = P(None, None, "sp", None)

    ring = jax.shard_map(
        functools.partial(
            ring_attention, axis_name="sp", axis_size=8, causal=True, sm_scale=scale
        ),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    )
    g_ring = jax.jit(jax.grad(lambda q, k, v: (ring(q, k, v) * w).sum(), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(
        jax.grad(lambda q, k, v: (causal_oracle(q, k, v, scale) * w).sum(), argnums=(0, 1, 2))
    )(q, k, v)
    for a, e in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), atol=3e-5)


def test_ulysses_parity_dense():
    mesh = sp_mesh()
    rng = np.random.RandomState(3)
    b, h, n, d = 2, 8, 40, 16
    q, k, v = (jnp.asarray(rng.randn(b, h, n, d), jnp.float32) for _ in range(3))
    scale = d**-0.5
    km = jnp.asarray(rng.rand(b, n) > 0.3)
    spec = P(None, None, "sp", None)

    def attend(q, k, v, km):
        mask = jnp.tril(jnp.ones((q.shape[2], q.shape[2]), bool))[None, None]
        mask = mask & km[:, None, None, :]
        return dense_attend(q * scale, k, v, mask)

    fn = jax.jit(
        jax.shard_map(
            lambda q, k, v, m: ulysses_attend(q, k, v, "sp", 8, attend, key_mask=m),
            mesh=mesh,
            in_specs=(spec, spec, spec, P(None, "sp")),
            out_specs=spec,
            check_vma=False,
        )
    )
    out = fn(q, k, v, km)
    expected = causal_oracle(q, k, v, scale, km)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


# --------------------------------------------------------------- model level


def tiny_dalle(sp_axis=None, attn_types=("full", "axial_row")):
    return DALLE(
        dim=32,
        depth=2,
        num_text_tokens=64,
        text_seq_len=8,
        num_image_tokens=32,
        image_fmap_size=4,
        heads=8,
        dim_head=8,
        attn_types=attn_types,
        shift_tokens=False,
        sp_axis=sp_axis,
    )


@pytest.mark.parametrize(
    "attn_types", [("full",), ("axial_row", "axial_col"), ("conv_like", "sparse")]
)
def test_dalle_sp_matches_single_device(attn_types):
    """Same params, same batch: sp-sharded loss & grads == unsharded loss &
    grads for every attention family (ring for full, Ulysses otherwise)."""
    base = tiny_dalle(None, attn_types)
    sp_model = tiny_dalle("sp", attn_types)

    rng = np.random.RandomState(4)
    text = jnp.asarray(rng.randint(1, 64, size=(2, 8)), jnp.int32)
    image = jnp.asarray(rng.randint(0, 32, size=(2, 16)), jnp.int32)
    params = base.init(jax.random.key(0), text, image)["params"]

    def loss_base(p):
        return base.apply({"params": p}, text, image, return_loss=True)

    def loss_sp(p):
        return sp_model.apply({"params": p}, text, image, return_loss=True)

    l0, g0 = jax.jit(jax.value_and_grad(loss_base))(params)

    runtime = make_runtime(dp=2, fsdp=1, tp=1, sp=4)
    with runtime.activate():
        l1, g1 = jax.jit(jax.value_and_grad(loss_sp))(params)

    np.testing.assert_allclose(float(l0), float(l1), rtol=2e-5)
    flat0 = jax.tree_util.tree_leaves(g0)
    flat1 = jax.tree_util.tree_leaves(g1)
    for a, e in zip(flat1, flat0):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(e), atol=5e-4, rtol=5e-3
        )


def test_dalle_sp_with_text_mask():
    base = tiny_dalle(None, ("full", "axial_col"))
    sp_model = tiny_dalle("sp", ("full", "axial_col"))
    rng = np.random.RandomState(5)
    text = jnp.asarray(rng.randint(1, 64, size=(2, 8)), jnp.int32)
    image = jnp.asarray(rng.randint(0, 32, size=(2, 16)), jnp.int32)
    mask = jnp.asarray(rng.rand(2, 8) > 0.3)
    params = base.init(jax.random.key(0), text, image)["params"]

    l0 = jax.jit(
        lambda p: base.apply({"params": p}, text, image, mask=mask, return_loss=True)
    )(params)
    runtime = make_runtime(dp=1, fsdp=1, tp=2, sp=4)
    with runtime.activate():
        l1 = jax.jit(
            lambda p: sp_model.apply({"params": p}, text, image, mask=mask, return_loss=True)
        )(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=2e-5)


def test_sp_train_step_end_to_end():
    """A full sharded train step over a dp×tp×sp mesh runs and reduces loss
    deterministically (make_train_step activates the mesh itself)."""
    import optax

    from dalle_pytorch_tpu.parallel import create_train_state, make_train_step

    runtime = make_runtime(dp=2, fsdp=1, tp=2, sp=2)
    model = tiny_dalle("sp")
    rng = np.random.RandomState(6)
    batch = {
        "text": jnp.asarray(rng.randint(1, 64, size=(4, 8)), jnp.int32),
        "image": jnp.asarray(rng.randint(0, 32, size=(4, 16)), jnp.int32),
    }
    params = model.init(jax.random.key(0), batch["text"], batch["image"])["params"]
    opt = optax.adam(1e-3)
    state, shardings = create_train_state(params, opt, runtime)

    def loss_fn(p, batch, rng):
        return model.apply({"params": p}, batch["text"], batch["image"], return_loss=True)

    step = make_train_step(loss_fn, opt, runtime, shardings)
    losses = []
    for i in range(3):
        state, loss = step(state, batch, jax.random.key(i))
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
