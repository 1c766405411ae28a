"""Block-sparse pair-grid attention tests (ops/block_sparse_attention.py).

Four tiers, mirroring the module's contract:
- layout compilation: the BlockLayout's visit map, pair tables, and
  visited-block fraction against hand-checkable properties of the axial /
  conv / strided patterns, including ragged tails (n not a multiple of the
  block edge);
- kernel vs reference: interpret-mode pair-grid kernel pinned allclose —
  values and gradients — against the jnp path that shares
  ``cache_block_attend``'s einsums, per layout and with runtime key masks
  (the flash contract on dead rows: exact 0, asserted separately);
- dual balancing: ``dual_balanced_assignment`` keeps per-chip q-block
  counts within one block and visited-pair loads within one block's
  weight (the LPT bound) on the skewed axial layout;
- sp composition: the shard_map'd dual-balanced path (jnp and kernel
  chip-local compute) against the single-device reference, and the
  routed DALLE train-step loss-parity pin vs 1-device.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from dalle_pytorch_tpu.models import DALLE
from dalle_pytorch_tpu.ops import masks as masks_lib
from dalle_pytorch_tpu.ops.block_sparse_attention import (
    block_sparse_attention,
    compile_block_layout,
    compile_sp_plan,
    dual_balanced_assignment,
    reference_attend,
    sp_block_sparse_attend,
)
from dalle_pytorch_tpu.parallel import make_runtime


def _axial(n_text=8, f=4, axis=0):
    return masks_lib.axial_mask(n_text, f, axis=axis)  # n = n_text + f*f


def _conv(n_text=8, f=4):
    return masks_lib.conv_mask(n_text, f, 3, 1)


def _strided(n=64):
    return masks_lib.block_sparse_mask(
        n, block_size=8, text_seq_len=15, causal=True, seed=0
    )


# axial_col needs a grid wider than the block edge for its column stride
# to leave dead blocks (at f == block every block catches a column member)
LAYOUT_CASES = [
    ("axial_row", _axial(axis=0), 4),
    ("axial_col", _axial(8, 8, axis=1), 4),
    ("conv_like", _conv(), 4),
    ("strided", _strided(), 8),
]
LAYOUT_IDS = [c[0] for c in LAYOUT_CASES]


# ------------------------------------------------------------ layout compile


@pytest.mark.parametrize("name,mask,block", LAYOUT_CASES, ids=LAYOUT_IDS)
def test_layout_visit_map_matches_mask(name, mask, block):
    n = mask.shape[0]
    layout = compile_block_layout(mask, block, block)
    assert layout.n == n
    assert layout.n_pad % block == 0
    for qb in range(layout.nq):
        for kb in range(layout.nk):
            blk = layout.mask[
                qb * block : (qb + 1) * block, kb * block : (kb + 1) * block
            ]
            expect = 0 if not blk.any() else (2 if blk.all() else 1)
            assert layout.visit[qb, kb] == expect
    # every sparse pattern must actually skip blocks vs the dense-causal
    # grid — the premise of the whole kernel
    assert layout.n_pairs < layout.dense_pairs
    assert 0.0 < layout.visited_block_frac < 1.0


def test_layout_ragged_tail_pads_dead():
    mask = _axial()  # n = 24
    layout = compile_block_layout(mask, 16, 16)  # n_pad = 32, ragged tail
    assert layout.n_pad == 32
    # padded rows/cols are never attendable
    assert not layout.mask[24:, :].any()
    assert not layout.mask[:, 24:].any()


def test_engage_frac_separates_flagship_patterns():
    """The routing threshold at flagship geometry (text 256, fmap 32,
    block 128): axial_col's live stride (fmap=32) is finer than the block
    edge, so every causal pair stays live and the pair grid must decline;
    axial_row/conv_like skip enough pairs to engage. ENGAGE_FRAC drifting
    past either side silently turns into kernel-overhead-for-nothing or a
    lost block-skip win."""
    from dalle_pytorch_tpu.ops.block_sparse_attention import ENGAGE_FRAC

    def frac(pattern):
        mask = masks_lib.pattern_mask(pattern, 256, 32)
        return compile_block_layout(mask, 128, 128).visited_block_frac

    assert frac("axial_col") == 1.0
    assert frac("axial_col") > ENGAGE_FRAC
    assert frac("axial_row") <= ENGAGE_FRAC
    assert frac("conv_like") <= ENGAGE_FRAC


@pytest.mark.parametrize("name,mask,block", LAYOUT_CASES, ids=LAYOUT_IDS)
def test_layout_tables_cover_every_block(name, mask, block):
    """Every q block appears in the fwd table (its output must finalize)
    and every k block in the kv table (its dk/dv must be written), with
    exactly one first and one last flag per contiguous group."""
    layout = compile_block_layout(mask, block, block)
    for tab, idx_row, n_blocks in (
        (layout.fwd_table, 0, layout.nq),
        (layout.kv_table, 1, layout.nk),
    ):
        groups = tab[idx_row]
        assert set(groups.tolist()) == set(range(n_blocks))
        # contiguous groups: first/last flags frame each run
        change = np.flatnonzero(np.diff(groups) != 0)
        firsts = np.concatenate(([0], change + 1))
        lasts = np.concatenate((change, [groups.size - 1]))
        assert np.array_equal(np.flatnonzero(tab[3] == 1), firsts)
        assert np.array_equal(np.flatnonzero(tab[4] == 1), lasts)


# ------------------------------------------------------- kernel vs reference


def _rand_qkv(rng, b, h, n, d):
    return (
        jnp.asarray(rng.standard_normal((b, h, n, d)), jnp.float32)
        for _ in range(3)
    )


@pytest.mark.parametrize("name,mask,block", LAYOUT_CASES, ids=LAYOUT_IDS)
def test_kernel_matches_reference(name, mask, block):
    rng = np.random.default_rng(0)
    n = mask.shape[0]
    b, h, d = 1, 2, 32
    layout = compile_block_layout(mask, block, block)
    q, k, v = _rand_qkv(rng, b, h, n, d)
    o_k = block_sparse_attention(q, k, v, layout, interpret=True)
    o_r = reference_attend(q, k, v, layout)
    np.testing.assert_allclose(o_k, o_r, atol=2e-5, rtol=1e-5)


def test_kernel_ragged_tail_matches_reference():
    rng = np.random.default_rng(1)
    mask = _axial()  # n = 24, block 16 -> n_pad 32
    layout = compile_block_layout(mask, 16, 16)
    q, k, v = _rand_qkv(rng, 1, 2, 24, 32)
    o_k = block_sparse_attention(q, k, v, layout, interpret=True)
    o_r = reference_attend(q, k, v, layout)
    np.testing.assert_allclose(o_k, o_r, atol=2e-5, rtol=1e-5)


def test_kernel_key_mask_and_dead_rows():
    """Runtime key mask streams through the kernel; rows whose every
    visible key is masked return exactly 0 (the flash contract — the
    dense softmax's uniform average is NOT reproduced), so parity is
    asserted on live rows and the zero on dead ones."""
    rng = np.random.default_rng(2)
    mask = _axial()
    n, b, h, d = 24, 2, 2, 32
    layout = compile_block_layout(mask, 4, 4)
    q, k, v = _rand_qkv(rng, b, h, n, d)
    km = np.ones((b, n), bool)
    km[0, :1] = False  # kills text row 0 (attends only bos)
    km[1, 20:] = False
    kmj = jnp.asarray(km)
    o_k = block_sparse_attention(q, k, v, layout, key_mask=kmj, interpret=True)
    o_r = reference_attend(q, k, v, layout, key_mask=kmj)
    live = (mask[None] & km[:, None, :]).any(-1)  # (b, n)
    lr = jnp.asarray(live)[:, None, :, None]
    assert not bool(live.all())  # the dead-row case is actually exercised
    np.testing.assert_allclose(
        jnp.where(lr, o_k, 0.0), jnp.where(lr, o_r, 0.0), atol=2e-5, rtol=1e-5
    )
    assert float(jnp.max(jnp.abs(jnp.where(lr, 0.0, o_k)))) == 0.0


@pytest.mark.parametrize(
    "name,mask,block", LAYOUT_CASES[:2] + LAYOUT_CASES[3:], ids=LAYOUT_IDS[:2] + LAYOUT_IDS[3:]
)
def test_kernel_gradients_match_reference(name, mask, block):
    rng = np.random.default_rng(3)
    n = mask.shape[0]
    b, h, d = 1, 2, 32
    layout = compile_block_layout(mask, block, block)
    q, k, v = _rand_qkv(rng, b, h, n, d)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gk = jax.grad(
        loss(lambda q, k, v: block_sparse_attention(q, k, v, layout, interpret=True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        loss(lambda q, k, v: reference_attend(q, k, v, layout)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(a, b_, atol=5e-4, rtol=5e-3)


# ------------------------------------------------------------- dual balance


def test_dual_balanced_assignment_bounds():
    """Skewed axial weights: block counts within one of each other (the
    cap) and pair loads within one block's weight (the LPT bound)."""
    layout = compile_block_layout(_axial(), 4, 4)
    weights = (layout.visit > 0).sum(axis=1)
    assert weights.max() > weights.min()  # the pattern IS skewed
    for chips in (2, 3, 4):
        assign = dual_balanced_assignment(weights, chips)
        counts = np.bincount(assign, minlength=chips)
        loads = np.bincount(assign, weights=weights, minlength=chips)
        assert counts.max() - counts.min() <= 1
        assert loads.max() - loads.min() <= weights.max()


def test_sp_plan_balances_pairs_within_one_block():
    layout = compile_block_layout(_axial(), 4, 4)
    row_weight = (layout.visit > 0).sum(axis=1).max()
    for sp in (2, 4):
        plan = compile_sp_plan(layout, sp)
        # every q row dealt exactly once and recoverable by inv_perm
        seen = np.sort(plan.row_table.ravel())
        assert set(range(layout.n_pad)) <= set(seen.tolist())
        spread = plan.pair_counts.max() - plan.pair_counts.min()
        assert spread <= row_weight


# -------------------------------------------------------------- sp parity


def _sp_setup(sp, use_kernel):
    rng = np.random.default_rng(4)
    mask = _axial(axis=1)
    n, b, h, d = 24, 2, 2, 16
    layout = compile_block_layout(mask, 4, 4)
    plan = compile_sp_plan(layout, sp)
    q, k, v = _rand_qkv(rng, b, h, n, d)
    km = np.ones((b, n), bool)
    km[0, 5:9] = False
    kmj = jnp.asarray(km)
    mesh = Mesh(np.asarray(jax.devices()[:sp]).reshape(sp), ("sp",))
    qspec = P(None, None, "sp", None)

    def body(q, k, v, km):
        return sp_block_sparse_attend(
            q, k, v, plan, "sp", sp, sm_scale=d**-0.5, key_mask=km,
            use_kernel=use_kernel, interpret=True,
        )

    f = jax.shard_map(
        body, mesh=mesh, in_specs=(qspec,) * 3 + (P(None, "sp"),),
        out_specs=qspec, check_vma=False,
    )
    return f, layout, q, k, v, kmj


@pytest.mark.parametrize("use_kernel", [False, True])
def test_sp_attend_matches_reference(use_kernel):
    f, layout, q, k, v, km = _sp_setup(4, use_kernel)
    o_sp = f(q, k, v, km)
    o_r = reference_attend(q, k, v, layout, key_mask=km)
    tol = dict(atol=2e-5, rtol=1e-5)
    if use_kernel:
        # kernel dead-row contract differs from the dense softmax; this
        # layout + mask keeps every row live (bos column stays visible)
        live = (np.asarray(layout.mask[:24, :24])[None] & np.asarray(km)[:, None]).any(-1)
        assert bool(live.all())
    np.testing.assert_allclose(o_sp, o_r, **tol)


def test_sp_attend_gradients_match_reference():
    f, layout, q, k, v, km = _sp_setup(4, False)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gs = jax.grad(loss(lambda q, k, v: f(q, k, v, km)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(
        loss(lambda q, k, v: reference_attend(q, k, v, layout, key_mask=km)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b_ in zip(gs, gr):
        np.testing.assert_allclose(a, b_, atol=5e-4, rtol=5e-3)


# --------------------------------------------------------- routed train step


def _tiny_dalle(sp_axis, attn_types):
    return DALLE(
        dim=32, num_text_tokens=64, text_seq_len=8, depth=2, heads=8,
        dim_head=8, num_image_tokens=32, image_fmap_size=4,
        attn_types=attn_types, rotary_emb=False, sp_axis=sp_axis,
    )


def test_dalle_sp_sparse_loss_matches_single_device():
    """The routed dual-balanced sp path: DALLE train-step loss on the sp
    mesh pinned against the 1-device run for sparse attention types."""
    base = _tiny_dalle(None, ("axial_row", "sparse"))
    sp_model = _tiny_dalle("sp", ("axial_row", "sparse"))
    rng = np.random.RandomState(7)
    text = jnp.asarray(rng.randint(1, 64, size=(2, 8)), jnp.int32)
    image = jnp.asarray(rng.randint(0, 32, size=(2, 16)), jnp.int32)
    params = base.init(jax.random.key(0), text, image)["params"]

    l0 = jax.jit(
        lambda p: base.apply({"params": p}, text, image, return_loss=True)
    )(params)
    runtime = make_runtime(dp=2, fsdp=1, tp=1, sp=4)
    with runtime.activate():
        l1 = jax.jit(
            lambda p: sp_model.apply(
                {"params": p}, text, image, return_loss=True
            )
        )(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=2e-5)


# ------------------------------------------------------------ training gate


def _pattern_layer(attn_type, n, heads=16, **kw):
    """A PatternAttention over n leading positions of a text + image-grid
    sequence: the flagship 32 x 32 grid from n 1280 up, 8 x 8 below."""
    from dalle_pytorch_tpu.ops.attention import PatternAttention

    return PatternAttention(
        dim=heads * 64, seq_len=n + 1, heads=heads, dim_head=64,
        image_fmap_size=32 if n >= 1280 else 8, attn_type=attn_type, **kw,
    )


GATE_CASES = [
    # the flagship shape: the packed kernel is eligible, every kind rides it
    ("axial_row", 1280, {}, "fused_qkv_flash"),
    ("conv_like", 1280, {}, "fused_qkv_flash"),
    ("axial_col", 1280, {}, "fused_qkv_flash"),
    ("full", 1280, {}, "fused_qkv_flash"),
    # n 2048: the packed kernel's whole-row block no longer fits VMEM, so
    # the pair grid takes the layouts that skip block pairs (ENGAGE_FRAC)
    ("axial_row", 2048, {}, "block_sparse_pair_grid"),
    ("conv_like", 2048, {}, "block_sparse_pair_grid"),
    # use_flash=False rules the packed kernel out: the pair grid as before
    ("axial_row", 1280, {"use_flash": False}, "block_sparse_pair_grid"),
    ("conv_like", 1280, {"use_flash": False}, "block_sparse_pair_grid"),
]


@pytest.mark.parametrize(
    "attn_type,n,kw,impl", GATE_CASES,
    ids=[f"{a}-{n}-{'noflash' if kw else 'flash'}" for a, n, kw, _ in GATE_CASES],
)
def test_training_gate_prefers_packed_kernel(monkeypatch, attn_type, n, kw, impl):
    """The training-route gate of PatternAttention, with the pair grid
    switched ON (as on a TPU): the packed single-block flash kernel takes
    every call it is eligible for, the pair grid only what it cannot run.
    Abstract trace only, so the flagship shape costs nothing."""
    from dalle_pytorch_tpu.ops import kv_policy

    monkeypatch.setenv("DALLE_TPU_SPARSE_KERNEL", "1")
    monkeypatch.setattr(kv_policy, "ROUTE_LOG", [])
    attn = _pattern_layer(attn_type, n, **kw)
    x = jax.ShapeDtypeStruct((1, n, attn.dim), jnp.float32)
    params = jax.eval_shape(attn.init, jax.random.key(0), x)
    jax.eval_shape(attn.apply, params, x)
    assert [r["impl"] for r in kv_policy.ROUTE_LOG] == [impl]
    assert kv_policy.ROUTE_LOG[0]["site"] == f"forward/{attn_type}"


@pytest.mark.parametrize("attn_type", ["axial_row", "conv_like"])
def test_packed_kernel_patterns_match_dense_through_module(monkeypatch, attn_type):
    """The route the gate now picks for the block-skipping patterns, at the
    smallest packed shape (n 256, 2 heads of 64): output and parameter
    gradients of the module against its own force_dense path, with the
    pair grid switched on so that the gate really has the choice."""
    from dalle_pytorch_tpu.ops import kv_policy

    monkeypatch.setenv("DALLE_TPU_SPARSE_KERNEL", "1")
    monkeypatch.setattr(kv_policy, "ROUTE_LOG", [])
    n = 256
    attn = _pattern_layer(attn_type, n, heads=2)
    x = jax.random.normal(jax.random.key(0), (2, n, attn.dim))
    mask = (jax.random.uniform(jax.random.key(1), (2, n)) > 0.3).at[:, 0].set(True)
    params = attn.init(jax.random.key(2), x)

    for m in (None, mask):
        np.testing.assert_allclose(
            np.asarray(attn.apply(params, x, mask=m)),
            np.asarray(attn.apply(params, x, mask=m, force_dense=True)),
            atol=3e-4, rtol=3e-4,
        )
    got = jax.grad(lambda p: (attn.apply(p, x, mask=mask) ** 2).sum())(params)
    want = jax.grad(
        lambda p: (attn.apply(p, x, mask=mask, force_dense=True) ** 2).sum()
    )(params)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-3)
    assert {r["impl"] for r in kv_policy.ROUTE_LOG} == {
        "fused_qkv_flash", "jnp_pattern_attend"
    }
