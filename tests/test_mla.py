"""Latent attention (ops/attention.py:LatentAttention) held to the plain
reference (benchmarks/reference_moe.py: float32, one head at a time from the
expanded form, imports nothing of the program); the blocked flash kernels at a
value width of their own against the dense route, forward and gradients; and
the rotary cast every rotary path goes through (ops/rotary.py:cos_sin): bf16
rotary at position 4,095 against the float32 table."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_moe
from dalle_pytorch_tpu.ops import kv_policy, rotary
from dalle_pytorch_tpu.ops.attention import LatentAttention, dense_attend

# the package exports the function of the same name over the module
fa = importlib.import_module("dalle_pytorch_tpu.ops.flash_attention")

CFG = dict(num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           q_lora_rank=48, kv_lora_rank=32, rms_norm_eps=1e-6, rope_theta=32000000)


def layer(use_flash=True, dtype=jnp.float32):
    return LatentAttention(
        dim=64, heads=4, q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16,
        rope_theta=32000000.0, eps=1e-6, use_flash=use_flash, dtype=dtype,
    )


def weights(n, seed=0):
    x = jax.random.normal(jax.random.key(seed), (2, n, 64))
    p = layer().init(jax.random.key(seed + 1), x)["params"]
    # norm gains off 1, so that a path which dropped one would show
    noise = lambda a: a + 0.1 * jax.random.normal(jax.random.key(7), a.shape)
    p = {**p, "q_norm": {"scale": noise(p["q_norm"]["scale"])},
         "kv_norm": {"scale": noise(p["kv_norm"]["scale"])}}
    return x, p


def reference(x, p):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([reference_moe._mla(row, p, CFG, "f32") for row in x])


@pytest.mark.parametrize("n,use_flash,route", [
    (256, True, "blocked_flash"), (24, True, "dense_masked"), (256, False, "dense_masked"),
])
def test_latent_attention_matches_the_reference_on_either_route(n, use_flash, route):
    """n = 256 takes the blocked flash kernels (interpreted here) at widths
    24 against 16; 24 has no usable block and takes the dense route."""
    x, p = weights(n)
    kv_policy.ROUTE_LOG.clear()
    with jax.default_matmul_precision("highest"):
        out = layer(use_flash).apply({"params": p}, x)
    assert [r["impl"] for r in kv_policy.ROUTE_LOG if r["site"] == "forward/mla"] == [route]
    want = reference(x, p)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5 * float(jnp.max(jnp.abs(want)))


def test_latent_attention_gradients_match_the_reference():
    x, p = weights(128)
    target = jax.random.normal(jax.random.key(9), (2, 128, 64))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p, x: jnp.sum(layer().apply({"params": p}, x) * target),
                       argnums=(0, 1))(p, x)
        want = jax.grad(lambda p, x: jnp.sum(reference(x, p) * target), argnums=(0, 1))(p, x)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(g - w))) < 1e-4 * float(jnp.max(jnp.abs(w))), g.shape


def test_latent_attention_in_bfloat16_stays_within_its_band():
    """bfloat16 activations over float32 parameters: every product rounds to
    8 bits, so the output stands within about 2 % of the float32 reference's
    largest entry, and far from a different function's (a dropped rotary key
    or norm reads tens of percent)."""
    x, p = weights(256)
    out = layer(dtype=jnp.bfloat16).apply({"params": p}, x.astype(jnp.bfloat16))
    want = reference(x, p)
    gap = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))
    assert out.dtype == jnp.bfloat16 and gap < 0.03, gap


def test_a_layer_that_leaves_the_rotation_out_fails_the_reference(monkeypatch):
    x, p = weights(24)
    want = reference(x, p)
    attention = importlib.import_module("dalle_pytorch_tpu.ops.attention")
    monkeypatch.setattr(attention, "apply_rotary_emb", lambda table, t: t)
    flat = layer().apply({"params": p}, x)
    assert float(jnp.max(jnp.abs(flat - want)) / jnp.max(jnp.abs(want))) > 0.01


# ------------------------------------------------- the value width of its own


@pytest.mark.parametrize("d,dv,n,block", [(192, 128, 256, 128), (24, 16, 256, 128), (24, 16, 128, 128)])
def test_flash_with_its_own_value_width_matches_the_dense_route(d, dv, n, block):
    """Forward and the three gradients; (24, 16, 128) is one block, where
    the backward kernel writes every block from its one tile."""
    keys = jax.random.split(jax.random.key(0), 4)
    q, k = (jax.random.normal(keys[i], (1, 2, n, d)) for i in (0, 1))
    v = jax.random.normal(keys[2], (1, 2, n, dv))
    do = jax.random.normal(keys[3], (1, 2, n, dv))
    scale = d**-0.5
    causal = jnp.tril(jnp.ones((n, n), bool))
    flash = lambda q, k, v: fa.flash_attention(q, k, v, None, True, None, scale, block, block, True)
    dense = lambda q, k, v: dense_attend(q * scale, k, v, causal)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(flash, q, k, v)
        want, want_vjp = jax.vjp(dense, q, k, v)
        assert out.shape == (1, 2, n, dv)
        assert float(jnp.max(jnp.abs(out - want))) < 1e-5
        for g, w in zip(vjp(do), want_vjp(do)):
            assert g.shape == w.shape and float(jnp.max(jnp.abs(g - w))) < 2e-5


def test_one_width_keeps_its_cost_estimate_number_for_number():
    """A caller with one width hands XLA the estimate it always did."""
    visit = np.ones((4, 4), np.int32)
    args = (visit, 8, 128, 128, 64, 3, 256, 512, 2)
    plain = fa._kernel_cost(*args)
    same = fa._kernel_cost(*args, 64, 1, 128, 256)
    wider = fa._kernel_cost(*args, 128, 1, 128, 256)
    assert (plain.flops, plain.bytes_accessed) == (same.flops, same.bytes_accessed)
    assert wider.flops > plain.flops and wider.bytes_accessed > plain.bytes_accessed


# ------------------------------------------------------------ the rotary cast


def test_bf16_rotary_at_position_4095_holds_to_the_float32_table():
    """The angle at position 4,095 and frequency 1 is 4,095 rad, where
    bfloat16 steps by 16: cos and sin are taken of the float32 angle and only
    the results are cast (ROADMAP R1)."""
    table = rotary.angles(np.arange(4096), rotary.lang_freqs(64, 32000000.0)).astype(np.float32)
    t = jax.random.normal(jax.random.key(0), (4096, 64))
    want = rotary.apply_rotary_emb(jnp.asarray(table), t)
    got = rotary.apply_rotary_emb(jnp.asarray(table), t.astype(jnp.bfloat16))
    assert got.dtype == jnp.bfloat16
    gap = jnp.abs(got.astype(jnp.float32) - want)
    assert float(jnp.max(gap[4095])) < 0.05 and float(jnp.max(gap)) < 0.05
    # the table rounded first is a different rotation at the far positions
    rounded = jnp.asarray(table).astype(jnp.bfloat16).astype(jnp.float32)
    wrong = rotary.apply_rotary_emb(rounded, t)
    assert float(jnp.max(jnp.abs(wrong - want)[4095])) > 0.5


def test_the_fused_kernels_rotary_operands_are_the_unfused_paths():
    table = rotary.angles(np.arange(64), rotary.lang_freqs(16)).astype(np.float32)
    for dtype in (jnp.float32, jnp.bfloat16):
        cos, sin = fa._rot_tables(fa.StaticTable(table), 64, 16, dtype)
        want_cos, want_sin = rotary.cos_sin(jnp.asarray(table), dtype)
        assert cos.dtype == dtype
        assert bool(jnp.all(cos == want_cos)) and bool(jnp.all(sin == want_sin))
