"""Parity tests for the Pallas flash-attention kernel (ops/flash_attention.py)
against the dense masked oracle (ops.attention.dense_attend), forward AND
gradients, at realistic sequence lengths — including the flagship DALL-E
seq 1280 — in interpret mode on CPU.

Reference semantics being matched: dense causal attention
(/root/reference/dalle_pytorch/attention.py:71-79) and DeepSpeed
variable-sparsity block attention (attention.py:338-351).
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.ops import masks as masks_lib
from dalle_pytorch_tpu.ops.attention import dense_attend
from dalle_pytorch_tpu.ops.flash_attention import StaticMask, flash_attention

# the module itself (``ops/__init__.py`` exports the function under its name)
fa = importlib.import_module("dalle_pytorch_tpu.ops.flash_attention")


def _qkv(key, b, h, n, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, h, n, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


def _oracle(q, k, v, mask_np):
    scale = q.shape[-1] ** -0.5
    return dense_attend(q * scale, k, v, jnp.asarray(mask_np)[None, None])


def _flash(q, k, v, causal, pattern, block):
    return flash_attention(
        q, k, v,
        causal=causal,
        pattern_mask=pattern,
        sm_scale=q.shape[-1] ** -0.5,
        block_q=block,
        block_k=block,
        interpret=True,
    )


@pytest.mark.parametrize("n,block", [(128, 64), (256, 128)])
def test_causal_forward_parity(n, block):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 3, n, 64)
    out = _flash(q, k, v, True, None, block)
    ref = _oracle(q, k, v, masks_lib.causal_mask(n))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("n,block", [(128, 64), (256, 128)])
def test_causal_grad_parity(n, block):
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 2, n, 64)
    mask = masks_lib.causal_mask(n)

    def f_flash(q, k, v):
        return (_flash(q, k, v, True, None, block) ** 2).sum()

    def f_ref(q, k, v):
        return (_oracle(q, k, v, mask) ** 2).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_block_sparse_forward_parity():
    n = 256
    mask = masks_lib.block_sparse_mask(n, block_size=16, text_seq_len=64, seed=3)
    q, k, v = _qkv(jax.random.PRNGKey(2), 2, 2, n, 64)
    out = _flash(q, k, v, True, StaticMask(mask), 64)
    ref = _oracle(q, k, v, mask)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_block_sparse_grad_parity():
    n = 128
    mask = masks_lib.block_sparse_mask(n, block_size=16, text_seq_len=32, seed=5)
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 2, n, 64)

    def f_flash(q, k, v):
        return (_flash(q, k, v, True, StaticMask(mask), 32) ** 2).sum()

    def f_ref(q, k, v):
        return (_oracle(q, k, v, mask) ** 2).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_fully_masked_rows_zero_output_and_grads():
    """A query row masked in every block must emit 0 output and leak no
    gradient (ADVICE.md round-1 finding: m stays NEG_INF so p became 1)."""
    n = 64
    mask = np.tril(np.ones((n, n), dtype=bool))
    mask[5, :] = False  # row 5 sees nothing
    mask[40, :] = False
    q, k, v = _qkv(jax.random.PRNGKey(4), 1, 1, n, 64)
    out = _flash(q, k, v, False, StaticMask(mask), 32)
    np.testing.assert_allclose(out[0, 0, 5], 0.0, atol=1e-6)
    np.testing.assert_allclose(out[0, 0, 40], 0.0, atol=1e-6)

    def f(q, k, v):
        return (_flash(q, k, v, False, StaticMask(mask), 32) ** 2).sum()

    dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(dq[0, 0, 5], 0.0, atol=1e-6)
    # dk/dv get no contribution from the masked rows: compare against the
    # oracle with those rows excluded
    mask_j = jnp.asarray(mask)[None, None]

    def f_ref(q, k, v):
        out = dense_attend(q * (64**-0.5), k, v, mask_j)
        live = jnp.asarray(mask.any(axis=1), jnp.float32)[None, None, :, None]
        return ((out * live) ** 2).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(dk, g_ref[1], atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(dv, g_ref[2], atol=5e-4, rtol=5e-4)


# ------------------------------------------- the one backward kernel, tiled


def _dead_tile_mask(n, block):
    """Causal, and the LAST query block sees nothing of the FIRST key block
    (a dead tile under the diagonal) and half of the next one's columns."""
    mask = masks_lib.causal_mask(n).copy()
    mask[n - block:, :block] = False
    mask[n - block:, block:block + block // 2] = False
    return mask


def _tiled_case(case, n, block):
    """(q, k, v, do, flash kwargs, the (b, 1|h, n, n) pairs that may attend,
    tolerance) of one case of the tiled backward."""
    d, dv, dtype, tol = 32, 32, jnp.float32, 2e-5
    if case == "own_value_width":
        d, dv = 24, 16   # latent attention's 192 / 128, scaled down
    if case == "bf16":
        dtype, tol = jnp.bfloat16, 0.06
    keys = jax.random.split(jax.random.PRNGKey(n + block), 5)
    q, k = (jax.random.normal(keys[i], (2, 2, n, d), dtype) for i in (0, 1))
    v, do = (jax.random.normal(keys[i], (2, 2, n, dv), dtype) for i in (2, 3))
    kwargs, pairs = dict(causal=True), masks_lib.causal_mask(n)
    if case == "non_causal":
        kwargs, pairs = dict(causal=False), np.ones((n, n), bool)
    if case in ("dead_tiles", "masked_row"):
        pairs = _dead_tile_mask(n, block)
        if case == "masked_row":
            pairs[n - 5, :] = False   # a row that sees nothing at all
        kwargs = dict(causal=True, pattern_mask=StaticMask(pairs))
    allowed = jnp.asarray(pairs)[None, None]
    if case == "key_mask":
        km = _rand_key_mask(keys[4], 2, n, fully_masked_batch=None).at[:, 0].set(False)
        kwargs["key_mask"] = km
        allowed = allowed & km[:, None, None, :]
    return q, k, v, do, kwargs, allowed, tol


TILED_CASES = ["causal", "non_causal", "dead_tiles", "key_mask", "masked_row",
               "own_value_width", "bf16"]


@pytest.mark.parametrize("dq_form", ["resident", "partials"])
@pytest.mark.parametrize("grid", [1, 2, 4])
@pytest.mark.parametrize("case", TILED_CASES)
def test_tiled_backward_matches_the_dense_oracle(case, grid, dq_form, monkeypatch):
    """dq, dk and dv of the one backward kernel on a grid x grid tiling
    against the dense oracle's, with dq summed over the key blocks in its
    resident VMEM row and (a row over the budget) as float32 partials; at
    grid 1 every block is one tile's and nothing is summed (``dead_tiles``
    is then one dead tile: all zeros)."""
    if dq_form == "partials":
        monkeypatch.setattr(fa, "DQ_ROW_VMEM_BYTES", 0)
    block = 32
    n = grid * block
    q, k, v, do, kwargs, allowed, tol = _tiled_case(case, n, block)
    scale = q.shape[-1] ** -0.5

    def flash(q, k, v):
        return flash_attention(
            q, k, v, sm_scale=scale, block_q=block, block_k=block, interpret=True, **kwargs
        )

    def dense(q, k, v):
        out = dense_attend(q * scale, k, v, allowed)
        return jnp.where(jnp.any(allowed, axis=-1)[..., None], out, 0.0)

    f32 = lambda t: t.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(flash, q, k, v)
        want, want_vjp = jax.vjp(dense, f32(q), f32(k), f32(v))
        got, wanted = vjp(do), want_vjp(f32(do))
    assert float(jnp.max(jnp.abs(f32(out) - want))) < tol
    for g, w, like in zip(got, wanted, (q, k, v)):
        assert g.shape == like.shape and g.dtype == like.dtype
        assert float(jnp.max(jnp.abs(f32(g) - w))) < tol * max(1.0, float(jnp.max(jnp.abs(w))))
    if case == "masked_row":
        assert float(jnp.max(jnp.abs(got[0][:, :, n - 5]))) == 0.0


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("n,block", [(128, 32), (64, 64)])
def test_the_backward_is_one_kernel_of_five_dots(n, block):
    """A tiled call's backward (and a single block's) holds exactly one
    ``pallas_call``, ``flash_bwd``, whose body has five ``dot_general``s: the
    tile's scores are rebuilt once for dq, dk and dv."""
    q, k, v = _qkv(jax.random.PRNGKey(20), 1, 2, n, 32)

    def backward(q, k, v, do):
        return jax.vjp(lambda *qkv: _flash(*qkv, True, None, block), q, k, v)[1](do)

    jaxpr = jax.make_jaxpr(backward)(q, k, v, q).jaxpr
    calls = [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]
    assert sorted(e.params["name"] for e in calls) == ["flash_bwd", "flash_fwd"]
    (bwd,) = [e for e in calls if e.params["name"] == "flash_bwd"]
    dots = [e for e in _eqns(bwd.params["jaxpr"]) if e.primitive.name == "dot_general"]
    assert len(dots) == 5


def _band(n, window):
    """The dense oracle's pairs of a sliding window: key j visible to query
    i iff 0 <= i - j < window."""
    gap = np.arange(n)[:, None] - np.arange(n)[None, :]
    return (gap >= 0) & (gap < window)


@pytest.mark.parametrize("n,block,window", [
    (256, 64, 40),     # under a block
    (256, 64, 64),     # a block
    (256, 64, 100),    # not a multiple of the block
    (256, 32, 1),      # the query alone
    (128, 128, 50),    # one block: the band inside it
])
def test_windowed_kernels_match_the_dense_band(n, block, window):
    """Forward and dq, dk, dv of the banded grid against the dense masked
    oracle over the band's pairs."""
    q, k, v = _qkv(jax.random.PRNGKey(window), 2, 2, n, 32)
    do = jax.random.normal(jax.random.PRNGKey(7), q.shape)
    scale = q.shape[-1] ** -0.5
    flash = lambda q, k, v: flash_attention(q, k, v, None, True, None, scale, block, block, True, window)
    dense = lambda q, k, v: dense_attend(q * scale, k, v, jnp.asarray(_band(n, window))[None, None])
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(flash, q, k, v)
        want, want_vjp = jax.vjp(dense, q, k, v)
        got, wanted = vjp(do), want_vjp(do)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for g, w in zip(got, wanted):
        np.testing.assert_allclose(g, w, atol=2e-5 * max(1.0, float(jnp.max(jnp.abs(w)))))


@pytest.mark.parametrize("window", [256, 1000])
def test_a_window_that_covers_the_row_is_the_causal_program(window):
    """``window >= n`` traces the causal kernels, equation for equation, and
    gives their result bit for bit."""
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 2, 256, 32)

    def grads(window):
        f = lambda q, k, v: flash_attention(q, k, v, None, True, None, None, 64, 64, True, window)
        return lambda q, k, v: jax.vjp(f, q, k, v)[1](q)

    assert str(jax.make_jaxpr(grads(window))(q, k, v)) == str(jax.make_jaxpr(grads(None))(q, k, v))
    for a, b in zip(grads(window)(q, k, v), grads(None)(q, k, v)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("n,block,window", [
    (16384, 1024, 4096), (256, 64, 40), (256, 64, 64), (256, 64, 100), (512, 64, 300), (256, 64, 1),
])
def test_the_banded_grid_visits_exactly_the_tiles_that_touch_the_band(n, block, window):
    """Brute force over the element band: the tiles the forward and the
    backward grid address (after the clamp) are exactly those holding a pair
    of the band, each live tile is live in the tables, and ``window_tiles``
    counts them beside the causal triangle's."""
    nb = n // block
    gap = np.arange(n)[:, None] - np.arange(n)[None, :]
    band = (gap >= 0) & (gap < window)
    pairs = band.reshape(nb, block, nb, block).any(axis=(1, 3))
    span = fa._band_span(nb, block, window)
    forward = {(qb, max(qb - span + 1 + j, 0)) for qb in range(nb) for j in range(span)}
    backward = {(min(kb + j, nb - 1), kb) for kb in range(nb) for j in range(span)}
    touched = set(zip(*np.nonzero(pairs)))
    assert forward == touched and backward == touched
    visit = fa._block_visit_map(nb, nb, block, block, True, None, window)
    fwd, bwd = fa._band_tables(visit, span)
    assert int((fwd > 0).sum()) == int((bwd > 0).sum()) == len(touched)
    full = band.reshape(nb, block, nb, block).all(axis=(1, 3))
    assert np.array_equal(visit == 2, full) and np.array_equal(visit > 0, pairs)
    counts = fa.window_tiles(n, block, window)
    assert counts["tiles_visited"] == len(touched)
    assert counts["causal_tiles"] == nb * (nb + 1) // 2
    if (n, block, window) == (16384, 1024, 4096):
        assert (span, counts["tiles_visited"], counts["causal_tiles"]) == (5, 70, 136)


@pytest.mark.slow
def test_flagship_seq_1280_forward_parity():
    """The exact shape that crashed round 1: seq 1280 (= 256 text + 1024
    image), block 128 — forward parity vs the dense oracle."""
    n, block = 1280, 128
    q, k, v = _qkv(jax.random.PRNGKey(6), 1, 2, n, 64)
    out = _flash(q, k, v, True, None, block)
    ref = _oracle(q, k, v, masks_lib.causal_mask(n))
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


@pytest.mark.slow
def test_flagship_seq_1280_grad_runs():
    n, block = 1280, 128
    q, k, v = _qkv(jax.random.PRNGKey(7), 1, 1, n, 64)

    def f(q, k, v):
        return _flash(q, k, v, True, None, block).sum()

    dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    assert np.isfinite(np.asarray(dq)).all()
    assert np.isfinite(np.asarray(dk)).all()
    assert np.isfinite(np.asarray(dv)).all()


def test_bfloat16_forward_close():
    n = 128
    q, k, v = _qkv(jax.random.PRNGKey(8), 1, 2, n, 64, jnp.bfloat16)
    out = _flash(q, k, v, True, None, 64)
    ref = _oracle(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        masks_lib.causal_mask(n),
    )
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref, atol=5e-2, rtol=5e-2
    )


# --------------------------------------------------- runtime key-padding mask


def _km_oracle(q, k, v, mask_np, km):
    """Dense oracle with a runtime (b, n) key mask folded in. Rows whose
    every key is masked follow the kernel's contract: exactly 0 output."""
    scale = q.shape[-1] ** -0.5
    allowed = jnp.asarray(mask_np)[None, None] & km[:, None, None, :]
    out = dense_attend(q * scale, k, v, allowed)
    live = jnp.any(allowed, axis=-1)[..., None]
    return jnp.where(live, out, 0.0)


def _rand_key_mask(key, b, n, fully_masked_batch=0):
    km = jax.random.uniform(key, (b, n)) > 0.3
    if fully_masked_batch is not None:
        km = km.at[fully_masked_batch].set(False)
    return km


def test_key_mask_forward_parity():
    """Ref attention.py:71-74 pad-mask semantics through the flash kernel:
    random key masks, one batch with EVERY key masked (all rows -> 0)."""
    b, h, n, d, block = 3, 2, 128, 64, 64
    q, k, v = _qkv(jax.random.PRNGKey(10), b, h, n, d)
    km = _rand_key_mask(jax.random.PRNGKey(11), b, n)
    out = flash_attention(
        q, k, v, key_mask=km, causal=True,
        sm_scale=d**-0.5, block_q=block, block_k=block, interpret=True,
    )
    ref = _km_oracle(q, k, v, masks_lib.causal_mask(n), km)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # the fully-masked batch is exactly zero
    np.testing.assert_allclose(out[0], 0.0, atol=0.0)


def test_key_mask_grad_parity():
    b, h, n, d, block = 2, 2, 128, 64, 64
    q, k, v = _qkv(jax.random.PRNGKey(12), b, h, n, d)
    km = _rand_key_mask(jax.random.PRNGKey(13), b, n, fully_masked_batch=None)
    # hand-mask a few single rows' entire key set via the causal prefix:
    # key 0 masked makes row 0 fully masked
    km = km.at[:, 0].set(False)
    mask_np = masks_lib.causal_mask(n)

    def f_flash(q, k, v):
        o = flash_attention(
            q, k, v, key_mask=km, causal=True,
            sm_scale=d**-0.5, block_q=block, block_k=block, interpret=True,
        )
        return (o**2).sum()

    def f_ref(q, k, v):
        return (_km_oracle(q, k, v, mask_np, km) ** 2).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b_, atol=5e-4, rtol=5e-4)


def test_key_mask_with_block_sparse_pattern():
    """Key mask composes with a static sparse pattern (both stream through
    the same kernel)."""
    n, block = 128, 32
    mask = masks_lib.block_sparse_mask(n, block_size=16, text_seq_len=32, seed=7)
    q, k, v = _qkv(jax.random.PRNGKey(14), 2, 2, n, 64)
    km = _rand_key_mask(jax.random.PRNGKey(15), 2, n, fully_masked_batch=None)
    out = flash_attention(
        q, k, v, key_mask=km, causal=True, pattern_mask=StaticMask(mask),
        sm_scale=64**-0.5, block_q=block, block_k=block, interpret=True,
    )
    ref = _km_oracle(q, k, v, mask, km)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_key_mask_noncausal():
    """CLIP's masked non-causal text encoder shape: no pattern operand at
    all (analytic all-dense visit map) + runtime key mask."""
    n, block = 256, 128
    q, k, v = _qkv(jax.random.PRNGKey(16), 2, 2, n, 64)
    km = _rand_key_mask(jax.random.PRNGKey(17), 2, n, fully_masked_batch=None)
    out = flash_attention(
        q, k, v, key_mask=km, causal=False,
        sm_scale=64**-0.5, block_q=block, block_k=block, interpret=True,
    )
    ref = _km_oracle(q, k, v, np.ones((n, n), bool), km)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_key_mask_keeps_linear_memory():
    """The VERDICT round-2 regression guard: a key-padding mask must NOT
    bounce attention to the dense path — no (n, n)-shaped buffer may appear
    anywhere in the lowered computation (fwd or bwd)."""
    import re

    b, h, n, d, block = 2, 2, 256, 64, 128
    q, k, v = _qkv(jax.random.PRNGKey(18), b, h, n, d)
    km = _rand_key_mask(jax.random.PRNGKey(19), b, n, fully_masked_batch=None)

    def loss(q, k, v, km):
        o = flash_attention(
            q, k, v, key_mask=km, causal=True,
            sm_scale=d**-0.5, block_q=block, block_k=block, interpret=True,
        )
        return (o**2).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v, km).as_text()
    square = re.compile(rf"\[(?:\d+,)*{n},{n}\]")
    offenders = [l for l in hlo.split("\n") if square.search(l)]
    assert not offenders, f"(n, n) buffers materialized:\n" + "\n".join(offenders[:5])


def test_pattern_attention_masked_dispatches_flash(monkeypatch):
    """ops/attention.py no longer gates the flash path on mask is None: a
    masked full-causal PatternAttention must call flash_attention, and its
    output must match the dense fallback."""
    from dalle_pytorch_tpu.ops import attention as attention_mod

    b, n, dim = 2, 128, 128
    module = attention_mod.PatternAttention(
        dim=dim, seq_len=n, attn_type="full", causal=True, heads=2, dim_head=64
    )
    x = jax.random.normal(jax.random.PRNGKey(20), (b, n, dim))
    mask = _rand_key_mask(jax.random.PRNGKey(21), b, n, fully_masked_batch=None)
    # keep row 0 live (bos-like): a fully-masked row would legitimately
    # differ between flash (0) and dense fallback (uniform average)
    mask = mask.at[:, 0].set(True)
    params = module.init(jax.random.PRNGKey(0), x, mask=mask)

    calls = []
    real_flash = attention_mod.flash_attention
    real_fused = attention_mod.fused_qkv_attention

    def spy_flash(*args, **kw):
        calls.append(kw.get("key_mask"))
        return real_flash(*args, **kw)

    def spy_fused(qkv, key_mask, *args, **kw):
        calls.append(key_mask)
        return real_fused(qkv, key_mask, *args, **kw)

    monkeypatch.setattr(attention_mod, "flash_attention", spy_flash)
    monkeypatch.setattr(attention_mod, "fused_qkv_attention", spy_fused)
    out_flash = module.apply(params, x, mask=mask)
    assert calls and calls[0] is not None, "masked call bypassed the flash kernel"

    out_dense = module.apply(params, x, mask=mask, force_dense=True)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_dense), atol=2e-5, rtol=2e-5
    )


# ------------------------------------------------- packed-qkv fused kernel


def _rand_rotary(n, d, key):
    """A pair-constant angle table like the real DALL-E one (repeat-2
    structure is what makes the in-kernel inverse rotation valid)."""
    from dalle_pytorch_tpu.ops.flash_attention import StaticTable

    half = jax.random.normal(key, (n, d // 2))
    table = jnp.repeat(half, 2, axis=-1)
    return StaticTable(np.asarray(table))


def test_fused_qkv_matches_unfused_through_transformer():
    """The packed single-block path (split/reshape/transpose/rotary all
    inside the kernel) must match the dense reference path through a real
    Transformer — forward AND parameter gradients, with and without a
    key-padding mask, rotary on."""
    from dalle_pytorch_tpu.models.transformer import Transformer

    # depth 1 / n 128 is the smallest config the packed path admits
    # (n % 128 == 0, heads % hpb == 0); layer stacking is covered elsewhere
    kw = dict(dim=128, depth=1, seq_len=128, causal=True, heads=2, dim_head=64,
              image_fmap_size=8, rotary_emb=True)
    tr = Transformer(**kw)
    tr_dense = Transformer(**kw, use_flash=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 128))
    mask = (jax.random.uniform(jax.random.PRNGKey(1), (2, 128)) > 0.3).at[:, 0].set(True)
    params = tr.init(jax.random.PRNGKey(2), x)

    import dalle_pytorch_tpu.ops.attention as A
    calls = []
    real = A.fused_qkv_attention

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    A.fused_qkv_attention = spy
    try:
        for m in (None, mask):
            np.testing.assert_allclose(
                np.asarray(tr.apply(params, x, mask=m)),
                np.asarray(tr_dense.apply(params, x, mask=m)),
                atol=3e-4, rtol=3e-4,
            )
        # gradients: the masked case only (unmasked grads are pinned by
        # test_causal_grad_parity and test_fused_qkv_direct_parity)
        gf = jax.tree_util.tree_leaves(
            jax.grad(lambda p: (tr.apply(p, x, mask=mask) ** 2).sum())(params)
        )
        gd = jax.tree_util.tree_leaves(
            jax.grad(lambda p: (tr_dense.apply(p, x, mask=mask) ** 2).sum())(params)
        )
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-3)
    finally:
        A.fused_qkv_attention = real
    assert calls, "fused path never dispatched"


def test_fused_qkv_direct_parity():
    """fused_qkv_attention vs the per-head pipeline it replaces: split ->
    (b, h, n, d) -> rotary on q, k AND v -> masked dense attention."""
    from dalle_pytorch_tpu.ops.flash_attention import fused_qkv_attention
    from dalle_pytorch_tpu.ops.rotary import apply_rotary_emb

    b, n, h, d = 2, 128, 2, 64
    qkv = jax.random.normal(jax.random.PRNGKey(3), (b, n, 3 * h * d))
    km = _rand_key_mask(jax.random.PRNGKey(4), b, n, fully_masked_batch=None)
    km = km.at[:, 0].set(True)
    rot = _rand_rotary(n, d, jax.random.PRNGKey(5))

    def reference(qkv):
        q, k, v = (t.reshape(b, n, h, d).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        table = jnp.asarray(rot.table)[None, None]
        q, k, v = (apply_rotary_emb(table, t) for t in (q, k, v))
        allowed = jnp.asarray(masks_lib.causal_mask(n))[None, None] & km[:, None, None, :]
        out = dense_attend(q * d**-0.5, k, v, allowed)
        return out.transpose(0, 2, 1, 3).reshape(b, n, h * d)

    def fused(qkv):
        return fused_qkv_attention(
            qkv, km, h, d, rot, True, None, d**-0.5, True
        )

    np.testing.assert_allclose(
        np.asarray(fused(qkv)), np.asarray(reference(qkv)), atol=2e-5, rtol=2e-5
    )
    cot = jax.random.normal(jax.random.PRNGKey(6), (b, n, h * d))
    g_fused = jax.grad(lambda q_: (fused(q_) * cot).sum())(qkv)
    g_ref = jax.grad(lambda q_: (reference(q_) * cot).sum())(qkv)
    np.testing.assert_allclose(
        np.asarray(g_fused), np.asarray(g_ref), atol=5e-4, rtol=5e-4
    )


@pytest.mark.slow
def test_flagship_seq1280_key_mask_parity():
    """Masked parity at the flagship seq 1280 (VERDICT round-2 item 1)."""
    n, block = 1280, 128
    q, k, v = _qkv(jax.random.PRNGKey(22), 1, 2, n, 64)
    km = _rand_key_mask(jax.random.PRNGKey(23), 1, n, fully_masked_batch=None)
    km = km.at[:, 0].set(True)
    out = flash_attention(
        q, k, v, key_mask=km, causal=True,
        sm_scale=64**-0.5, block_q=block, block_k=block, interpret=True,
    )
    ref = _km_oracle(q, k, v, masks_lib.causal_mask(n), km)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


@pytest.mark.slow
def test_flagship_production_block_parity():
    """seq 1280 at the PRODUCTION block size (_flash_block(1280) — one
    whole-row block), not a test-sized one: block-size-dependent code
    (diagonal classification, scratch shapes, the kb==0 / kb==nk-1
    epilogues) must be exercised at the configuration the flagship model
    actually dispatches to."""
    from dalle_pytorch_tpu.ops.attention import _flash_block

    n = 1280
    block = _flash_block(n)
    assert block == 1280, "update this test if the block heuristic changes"
    q, k, v = _qkv(jax.random.PRNGKey(5), 1, 2, n, 64)
    out = _flash(q, k, v, True, None, block)
    mask = masks_lib.causal_mask(n)
    want = _oracle(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)

    # gradient PARITY at the single-block configuration (nq == nk == 1: the
    # backward kernel sums nothing and writes each block from its one tile)
    # — finiteness alone would miss a wrong write there
    cot = jax.random.normal(jax.random.PRNGKey(7), out.shape)

    def flash_loss(q, k, v):
        return (_flash(q, k, v, True, None, block) * cot).sum()

    def oracle_loss(q, k, v):
        return (_oracle(q, k, v, mask) * cot).sum()

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(oracle_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want_g, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=5e-5, rtol=5e-5,
            err_msg=f"d{name} mismatch at production block",
        )


def test_fused_qkv_supported_vmem_bound():
    """The n cap must come from the backward's VMEM footprint (4 (n,n) f32
    temporaries x heads-per-block against the 100 MB limit with headroom),
    not a fixed constant: at d=64 (hpb=2) n=2048 needs ~134 MB and must be
    rejected, while the flagship n=1280 (~52 MB) stays admitted."""
    from dalle_pytorch_tpu.ops.flash_attention import fused_qkv_supported

    assert fused_qkv_supported(1280, 16, 64)
    assert fused_qkv_supported(1536, 16, 64)  # 75.5 MB — compiles on v5e
    assert not fused_qkv_supported(1792, 16, 64)  # 102 MB — over budget
    assert not fused_qkv_supported(2048, 16, 64)
    # smaller heads-per-block (d=128, hpb=1) halves the footprint: 2048
    # needs ~67 MB and fits
    assert fused_qkv_supported(2048, 8, 128)
    assert not fused_qkv_supported(1280 + 64, 16, 64)  # alignment still holds


def test_rot_tables_reject_non_pair_constant():
    """_inv_rot_block is only a valid VJP for pair-constant angle tables
    (table[:, 0::2] == table[:, 1::2]); a foreign table violating that must
    be rejected loudly instead of yielding silently wrong gradients."""
    from dalle_pytorch_tpu.ops.flash_attention import StaticTable, _rot_tables

    good = np.repeat(np.linspace(0, 1, 8 * 4).reshape(8, 4), 2, axis=1)
    cos, sin = _rot_tables(StaticTable(good.astype(np.float32)), 8, 8, jnp.float32)
    assert cos.shape == (8, 8)

    bad = good.copy()
    bad[:, 1] += 0.5  # break one pair
    with pytest.raises(AssertionError, match="pair-constant"):
        _rot_tables(StaticTable(bad.astype(np.float32)), 8, 8, jnp.float32)
