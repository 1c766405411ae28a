"""ops/ssm.py: the chunked state-space scan against two other algorithms for
the same recurrence (step by step; the quadratic form), forward and
gradients, at lengths that are and are not multiples of the chunk; the Pallas
kernels of the scan, interpreted on the CPU at small eligible shapes, against
the einsum form and the recurrence; the causal depthwise convolution and the
gated norm against plain transcriptions; the convolution's kernel pair,
interpreted, against that convolution and ``silu`` in XLA; and planted faults
that must FAIL those comparisons, through the einsum form and through the
kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from dalle_pytorch_tpu.ops import kv_policy, ssm
from dalle_pytorch_tpu.ops.layers import RMSNorm

B, H, P, N, CHUNK = 2, 4, 8, 6, 16


def inputs(n, seed=0, dims=(B, H, P, N)):
    b, h, p, state = dims
    ks = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(ks[0], (b, n, h, p))
    # steps and decays of Mamba-2's own range, so that state survives a chunk
    dt = jnp.exp(jax.random.uniform(ks[1], (b, n, h), minval=np.log(1e-3), maxval=np.log(1e-1)))
    A = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    Bm = jax.random.normal(ks[3], (b, n, state))
    Cm = jax.random.normal(ks[4], (b, n, state))
    D = 1.0 + 0.1 * jax.random.normal(ks[5], (h,))
    return x, dt, A, Bm, Cm, D


def recurrence(x, dt, A, Bm, Cm, D):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D x_t."""
    def step(S, inp):
        xt, dtt, Bt, Ct = inp
        S = S * jnp.exp(dtt * A)[..., None, None] + (dtt[..., None] * xt)[..., None] * Bt[:, None, None, :]
        return S, jnp.einsum("bhpn,bn->bhp", S, Ct) + D[:, None] * xt
    S0 = jnp.zeros((x.shape[0], *x.shape[2:], Bm.shape[-1]))
    _, ys = jax.lax.scan(step, S0, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, Bm, Cm)))
    return jnp.moveaxis(ys, 0, 1)


def quadratic(x, dt, A, Bm, Cm, D):
    """y_t = sum_{s<=t} exp(c_t - c_s) dt_s (C_t . B_s) x_s + D x_t."""
    n = x.shape[1]
    c = jnp.cumsum(dt * A, axis=1)                                 # (b, n, h)
    seg = c[:, :, None, :] - c[:, None, :, :]                       # (b, t, s, h)
    mask = jnp.tril(jnp.ones((n, n), bool))[None, :, :, None]
    w = jnp.exp(jnp.where(mask, seg, -jnp.inf)) * jnp.einsum("btn,bsn->bts", Cm, Bm)[..., None]
    return jnp.einsum("btsh,bsh,bshp->bthp", w, dt, x) + D[:, None] * x


def scan(*args, dtype=jnp.float32):
    return ssm.ssd_scan(*args, chunk=CHUNK, dtype=dtype)


@pytest.mark.parametrize("n", [3 * CHUNK, 3 * CHUNK - 5, CHUNK // 2])
@pytest.mark.parametrize("other", [recurrence, quadratic])
def test_chunked_scan_matches_the_other_algorithms_in_float32(n, other):
    args = inputs(n)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(scan(*args), other(*args), rtol=2e-5, atol=2e-5)
        w = jax.random.normal(jax.random.key(9), (B, n, H, P))
        loss = lambda f: (lambda *a: jnp.sum(f(*a) * w))
        g = jax.grad(loss(scan), argnums=range(6))(*args)
        g_other = jax.grad(loss(other), argnums=range(6))(*args)
    for name, a, b in zip("x dt A B C D".split(), g, g_other):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4 * scale, err_msg=name)


def test_chunked_scan_in_bfloat16_stays_in_its_band(monkeypatch):
    """bf16 matmul operands over float32 decays: every output within 3 % of
    the output's scale (bf16 rounds to 2**-8 = 0.4 %; sums of ~50 products).
    The CPU backend has no bf16 x bf16 -> f32 dot, so the operands are widened
    just before each dot: a product of two bf16 values is exact in float32,
    so the numbers are those of the chip's dot up to the order of the sum."""
    real = ssm._dot
    seen = []

    def widened(spec, a, b):
        seen.append((a.dtype, b.dtype))
        return real(spec, a.astype(jnp.float32), b.astype(jnp.float32))

    monkeypatch.setattr(ssm, "_dot", widened)
    args = inputs(4 * CHUNK - 3, seed=1)
    exact = recurrence(*args)
    scale = float(jnp.max(jnp.abs(exact)))
    got = scan(*args, dtype=jnp.bfloat16)
    assert got.dtype == jnp.float32
    assert seen and all(d == (jnp.bfloat16, jnp.bfloat16) for d in seen), seen
    err = float(jnp.max(jnp.abs(got - exact))) / scale
    assert 1e-5 < err < 0.03, err


def test_the_carried_state_matters_and_leaving_it_out_fails(monkeypatch):
    args = inputs(3 * CHUNK, seed=2)
    exact = recurrence(*args)
    monkeypatch.setattr(ssm, "carried_states", lambda states, total: jnp.zeros_like(states))
    broken = scan(*args)
    # the first chunk starts from zero either way; every later chunk differs
    np.testing.assert_allclose(broken[:, :CHUNK], exact[:, :CHUNK], rtol=2e-5, atol=2e-5)
    gap = float(jnp.max(jnp.abs(broken[:, CHUNK:] - exact[:, CHUNK:])))
    assert gap > 0.05 * float(jnp.max(jnp.abs(exact))), gap


def test_leaving_d_out_fails():
    x, dt, A, Bm, Cm, D = inputs(2 * CHUNK, seed=3)
    exact = recurrence(x, dt, A, Bm, Cm, D)
    without = scan(x, dt, A, Bm, Cm, jnp.zeros_like(D))
    assert float(jnp.max(jnp.abs(without - exact))) > 0.5


# ---- the Pallas kernels, interpreted, at small shapes they are eligible for

# (chunk, heads): one block of 8 heads and one row block; two blocks of 16
# heads (what all heads share is summed over them) and two row blocks
SMALL = {"c128h8": (128, 8), "c256h32": (256, 32)}
KP, KN = 64, 128


def kernel_inputs(shape, n, seed=0):
    chunk, h = SMALL[shape]
    assert ssm.ssd_kernels_eligible(chunk, h, KP, KN)
    return chunk, inputs(n, seed, dims=(1, h, KP, KN))


def einsum_form(monkeypatch):
    """``ssd_scan`` held to its einsum form at any shape."""
    def scan(*args, **kwargs):
        with monkeypatch.context() as m:
            m.setattr(ssm, "ssd_kernels_eligible", lambda *a: False)
            return ssm.ssd_scan(*args, **kwargs)
    return scan


@pytest.mark.parametrize("shape,n", [
    ("c128h8", 2 * 128), ("c128h8", 3 * 128), ("c128h8", 3 * 128 - 84), ("c256h32", 2 * 256 - 7),
])
@pytest.mark.parametrize("other", ["einsum", "recurrence"])
def test_the_kernels_match_the_einsum_form_and_the_recurrence_in_float32(
    shape, n, other, monkeypatch
):
    chunk, args = kernel_inputs(shape, n)
    kernels = lambda *a: ssm.ssd_scan(*a, chunk=chunk)
    oracle = recurrence if other == "recurrence" else (
        lambda *a: einsum_form(monkeypatch)(*a, chunk=chunk)
    )
    kv_policy.ROUTE_LOG.clear()
    with jax.default_matmul_precision("highest"):
        got = kernels(*args)
        assert {"site": "forward/ssd", "impl": "ssd_chunk", "interpret": True} in kv_policy.ROUTE_LOG
        np.testing.assert_allclose(got, oracle(*args), rtol=5e-5, atol=5e-5)
        w = jax.random.normal(jax.random.key(9), got.shape)
        loss = lambda f: (lambda *a: jnp.sum(f(*a) * w))
        g = jax.grad(loss(kernels), argnums=range(6))(*args)
        g_other = jax.grad(loss(oracle), argnums=range(6))(*args)
    for name, a, b in zip("x dt A B C D".split(), g, g_other):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4 * scale, err_msg=name)


def test_the_kernels_in_bfloat16_stay_in_their_band(monkeypatch):
    """As the einsum form's band test: bf16 operands on the MXU, widened just
    before each dot, float32 everything else. And the gradients: each within
    twice the einsum form's own distance from the float32 gradient (or 0.5 %),
    ``A``'s above all, which is a sum of differences that cancel term by
    term only while both cotangents of the exponents see the same rounded
    ``dy`` (one taken from the unrounded ``dy`` read 2.4 % here)."""
    real, seen = ssm._mxu, []

    def widened(a, b, contract):
        seen.append((a.dtype, b.dtype))
        return real(a.astype(jnp.float32), b.astype(jnp.float32), contract)

    monkeypatch.setattr(ssm, "_mxu", widened)
    chunk, args = kernel_inputs("c256h32", 3 * 256 - 40, seed=1)
    exact = recurrence(*args)
    scale = float(jnp.max(jnp.abs(exact)))
    got = ssm.ssd_scan(*args, chunk=chunk, dtype=jnp.bfloat16)
    assert got.dtype == jnp.float32
    assert seen and all(d == (jnp.bfloat16, jnp.bfloat16) for d in seen), set(seen)
    err = float(jnp.max(jnp.abs(got - exact))) / scale
    assert 1e-5 < err < 0.03, err

    w = jax.random.normal(jax.random.key(9), got.shape)
    grads = lambda f, **kw: jax.grad(
        lambda *a: jnp.sum(f(*a, chunk=chunk, **kw) * w), argnums=range(6)
    )(*args)
    with jax.default_matmul_precision("highest"):
        g_exact = grads(einsum_form(monkeypatch))
    g_kernels = grads(ssm.ssd_scan, dtype=jnp.bfloat16)
    g_einsum = grads(einsum_form(monkeypatch), dtype=jnp.bfloat16)
    for name, k, e, f in zip("x dt A B C D".split(), g_kernels, g_einsum, g_exact):
        off = lambda g: float(jnp.linalg.norm(g - f) / jnp.linalg.norm(f))
        assert off(k) < max(2 * off(e), 0.005), (name, off(k), off(e))


@pytest.mark.parametrize("fault", ["no_carry", "bf16_decay"])
def test_the_planted_faults_fail_through_the_kernels(fault, monkeypatch):
    """The two faults the benchmark plants from outside, by replacing the
    module's ``carried_states`` and ``log_decay`` (benchmarks/drivers/
    train_lm.py), reach the kernel route too."""
    chunk, args = kernel_inputs("c128h8", 3 * 128, seed=2)
    exact = recurrence(*args)
    scale = float(jnp.max(jnp.abs(exact)))
    healthy = float(jnp.max(jnp.abs(ssm.ssd_scan(*args, chunk=chunk) - exact)))
    assert healthy < 1e-4 * scale
    if fault == "no_carry":
        monkeypatch.setattr(ssm, "carried_states", lambda states, total: jnp.zeros_like(states))
    else:
        real = ssm.log_decay
        rounded = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
        monkeypatch.setattr(ssm, "log_decay", lambda dt, A: rounded(real(rounded(dt), rounded(A))))
    broken = ssm.ssd_scan(*args, chunk=chunk)
    if fault == "no_carry":
        # the first chunk starts from zero either way; every later chunk differs
        np.testing.assert_allclose(broken[:, :chunk], exact[:, :chunk], rtol=5e-5, atol=5e-5)
        assert float(jnp.max(jnp.abs(broken[:, chunk:] - exact[:, chunk:]))) > 0.05 * scale
    else:
        assert float(jnp.max(jnp.abs(broken - exact))) > 100 * max(healthy, 1e-6 * scale)


def test_eligibility_is_read_from_the_shape():
    assert ssm.ssd_kernels_eligible(256, 64, 64, 128)        # the cell's mixer
    assert ssm._head_block(64, 64) == 16 and ssm._head_block(8, 64) == 8
    assert not ssm.ssd_kernels_eligible(CHUNK, H, P, N)      # this file's tiny shapes
    assert not ssm.ssd_kernels_eligible(256, 64, 64, 64)     # a state of half a lane tile
    assert not ssm.ssd_kernels_eligible(192, 64, 64, 128)    # a chunk of one and a half
    assert not ssm.ssd_kernels_eligible(256, 12, 64, 128)    # heads in no whole block
    kv_policy.ROUTE_LOG.clear()
    scan(*inputs(CHUNK))
    assert kv_policy.ROUTE_LOG == [{"site": "forward/ssd", "impl": "einsum", "interpret": None}]


def test_causal_depthwise_convolution():
    x = jax.random.normal(jax.random.key(0), (2, 11, 5))
    k = jax.random.normal(jax.random.key(1), (4, 5))
    b = jax.random.normal(jax.random.key(2), (5,))
    want = np.zeros((2, 11, 5), np.float32)
    for t in range(11):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(x[:, t - 3 + j] * k[j])
    np.testing.assert_allclose(ssm.causal_conv1d(x, k, b), want + np.asarray(b), rtol=1e-5, atol=1e-5)
    # causal: a later input moves no earlier output
    moved = ssm.causal_conv1d(x.at[:, 7].add(1.0), k, b)
    np.testing.assert_array_equal(moved[:, :7], ssm.causal_conv1d(x, k, b)[:, :7])


# ---- the convolution's kernel pair, interpreted, against the XLA form


def xla_form(x, k, b, sizes, dtype=jnp.float32):
    """``causal_conv1d`` + ``silu`` + the split: what ``conv_silu`` computes
    at every shape its predicate refuses, and the oracle of its kernels."""
    b = jnp.zeros((x.shape[-1],)) if b is None else b     # a convolution without a bias
    y = jax.nn.silu(ssm.causal_conv1d(x, k, b)).astype(dtype)
    return tuple(jnp.split(y, np.cumsum(sizes[:-1]), axis=-1))


def conv_inputs(b, n, sizes, width, seed=0, dtype=jnp.float32, with_bias=True):
    ks = jax.random.split(jax.random.key(seed), 4)
    c = sum(sizes)
    x = jax.random.normal(ks[0], (b, n, c)).astype(dtype)
    k = jax.random.normal(ks[1], (width, c)) * 0.5
    bias = jax.random.normal(ks[2], (c,))
    cotangent = jax.random.normal(ks[3], (b, n, c))
    return (x, k, bias if with_bias else None), cotangent


def conv_value_and_grads(f, args, cotangent, sizes, dtype=jnp.float32):
    """The pieces side by side in float32, and the gradients of input, taps
    and bias under ``cotangent``."""
    def weighted(*a):
        whole = jnp.concatenate(f(*a, sizes, dtype), axis=-1).astype(jnp.float32)
        return jnp.sum(whole * cotangent), whole

    operands = tuple(i for i, a in enumerate(args) if a is not None)
    (_, whole), grads = jax.value_and_grad(weighted, argnums=operands, has_aux=True)(*args)
    return whole, grads


def assert_follows_the_xla_form(args, cotangent, sizes):
    y, grads = conv_value_and_grads(ssm.conv_silu, args, cotangent, sizes)
    want, want_grads = conv_value_and_grads(xla_form, args, cotangent, sizes)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    for name, a, b in zip(["x", "taps", "bias"], grads, want_grads):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6 * scale, err_msg=name)


R = ssm.CONV_ROWS
CONV_SHAPES = {
    # (batch, rows, pieces, taps): three row blocks, two equal column strips in
    # a loop and two single ones, two batch rows
    "rows3_strips4_b2": (2, 3 * R, (2 * ssm.STRIP[1], 128, 128), 4),
    # the sequence starts AND ends inside the first block: nothing is handed over
    "one_block": (1, R, (128,), 4),
    # a piece of one whole strip and a narrower rest; as many taps as the
    # strip after keeps rows for
    "rest_strip_taps8": (1, 2 * R, (ssm.STRIP[1] + 128, 256), ssm.AFTER),
    "taps2": (2, 2 * R, (128, 128), 2),
    # the second user (ops/gdn.py): q | k | v, and NO bias (a fifth entry)
    "q_k_v_no_bias": (1, R, (256, 256, 512), 4, False),
}


@pytest.mark.parametrize("shape", sorted(CONV_SHAPES))
def test_the_convolutions_kernels_follow_the_xla_form_in_float32(shape):
    b, n, sizes, width, *no_bias = CONV_SHAPES[shape]
    assert ssm.ssm_conv_kernel_eligible(n, sizes, width)
    kv_policy.ROUTE_LOG.clear()
    args, cotangent = conv_inputs(b, n, sizes, width, with_bias=not no_bias)
    assert_follows_the_xla_form(args, cotangent, sizes)
    assert kv_policy.ROUTE_LOG == [
        {"site": "forward/ssm_conv", "impl": "ssm_conv", "interpret": True}
    ]
    pieces = ssm.conv_silu(*args, sizes)
    assert [p.shape for p in pieces] == [(b, n, size) for size in sizes]


def test_the_convolutions_kernels_in_bfloat16_stay_in_the_xla_forms_band():
    """bf16 in and out, float32 between: the output and each gradient within
    twice the XLA form's own distance from float32 (or 1e-3: the form and the
    kernels round the same float32 numbers, so most of them agree to the
    bit)."""
    b, n, sizes, width = CONV_SHAPES["rows3_strips4_b2"]
    args, cotangent = conv_inputs(b, n, sizes, width, seed=1)
    exact = conv_value_and_grads(xla_form, args, cotangent, sizes)
    half = (args[0].astype(jnp.bfloat16),) + args[1:]
    kernels = conv_value_and_grads(ssm.conv_silu, half, cotangent, sizes, jnp.bfloat16)
    form = conv_value_and_grads(xla_form, half, cotangent, sizes, jnp.bfloat16)
    assert kernels[1][0].dtype == jnp.bfloat16 and kernels[1][1].dtype == jnp.float32
    off = lambda a, f: float(
        jnp.linalg.norm(a.astype(jnp.float32) - f) / jnp.linalg.norm(f)
    )
    names = ["y", "x", "taps", "bias"]
    for name, k, e, f in zip(names, (kernels[0], *kernels[1]), (form[0], *form[1]),
                             (exact[0], *exact[1])):
        assert 0 < off(e, f) < 0.01, (name, off(e, f))
        assert off(k, f) < max(2 * off(e, f), 1e-3), (name, off(k, f), off(e, f))


@pytest.mark.parametrize("way", ["forward", "backward"])
def test_the_convolutions_kernels_are_causal_both_ways(way):
    """A later input moves no earlier output; an earlier cotangent moves no
    later input gradient. Moved at a block's last row, so that the rows
    handed to the next block are the ones that carry it."""
    b, n, sizes, width = CONV_SHAPES["rows3_strips4_b2"]
    args, cotangent = conv_inputs(b, n, sizes, width, seed=2)
    at = 2 * R - 1
    if way == "forward":
        y, _ = conv_value_and_grads(ssm.conv_silu, args, cotangent, sizes)
        moved_args = (args[0].at[:, at].add(1.0),) + args[1:]
        moved, _ = conv_value_and_grads(ssm.conv_silu, moved_args, cotangent, sizes)
        np.testing.assert_array_equal(moved[:, :at], y[:, :at])
        reach = np.abs(np.asarray(moved - y)).max(axis=(0, 2))
        assert np.all(reach[at : at + width] > 0) and not reach[at + width :].any()
    else:
        _, (dx, _, _) = conv_value_and_grads(ssm.conv_silu, args, cotangent, sizes)
        _, (moved, _, _) = conv_value_and_grads(
            ssm.conv_silu, args, cotangent.at[:, at].add(1.0), sizes
        )
        np.testing.assert_array_equal(moved[:, at + 1 :], dx[:, at + 1 :])
        reach = np.abs(np.asarray(moved - dx)).max(axis=(0, 2))
        assert np.all(reach[at - width + 1 : at + 1] > 0) and not reach[: at - width + 1].any()


def test_the_convolutions_form_is_read_from_the_shape():
    inner, state = 64 * 64, 128
    assert ssm.ssm_conv_kernel_eligible(8192, (inner, state, state), 4)     # the cell's mixer
    assert not ssm.ssm_conv_kernel_eligible(8192, (inner, state, 64), 4)    # a piece off the lane tile
    assert not ssm.ssm_conv_kernel_eligible(8192 - 5, (inner, state, state), 4)  # a ragged length
    assert not ssm.ssm_conv_kernel_eligible(8192, (inner, state, state), ssm.AFTER + 1)
    for n, sizes in [(R, (128, 64)), (R - 5, (128, 128)), (20, (5,))]:
        kv_policy.ROUTE_LOG.clear()
        args, cotangent = conv_inputs(1, n, sizes, 4)
        got = conv_value_and_grads(ssm.conv_silu, args, cotangent, sizes)
        want = conv_value_and_grads(xla_form, args, cotangent, sizes)
        assert kv_policy.ROUTE_LOG == [{"site": "forward/ssm_conv", "impl": "xla", "interpret": None}]
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("way", ["forward", "backward"])
def test_leaving_out_the_rows_handed_between_blocks_fails(way, monkeypatch):
    """The planted fault: every block starts as the first does, from zeros
    (forward: the input's rows before it; backward: the cotangent's rows after
    it). The comparison every shape above passes must not."""
    name = "_ssm_conv_fwd_kernel" if way == "forward" else "_ssm_conv_bwd_kernel"
    real = getattr(ssm, name)

    def forgetful(*refs, **static):
        refs[-1][...] = jnp.zeros_like(refs[-1])     # the scratch that carries the rows
        real(*refs, **static)

    # two blocks of a shape no other test uses: the calls are jitted by shape
    sizes = (384,)
    args, cotangent = conv_inputs(1, 2 * R, sizes, 4, seed=3)
    assert_follows_the_xla_form(args, cotangent, sizes)
    calls = (ssm._conv_call, ssm._conv_bwd_call)
    try:
        monkeypatch.setattr(ssm, name, forgetful)
        for call in calls:
            call.clear_cache()
        with pytest.raises(AssertionError, match="Mismatched elements"):
            assert_follows_the_xla_form(args, cotangent, sizes)
    finally:
        monkeypatch.undo()
        for call in calls:
            call.clear_cache()
    assert_follows_the_xla_form(args, cotangent, sizes)


def test_gated_rms_norm_is_over_all_inner_channels():
    y = jax.random.normal(jax.random.key(0), (2, 5, 12))
    z = jax.random.normal(jax.random.key(1), (2, 5, 12))
    norm = RMSNorm(eps=1e-5)
    gain = 1.0 + 0.1 * jax.random.normal(jax.random.key(2), (12,))
    got = norm.apply({"params": {"scale": gain}}, y * jax.nn.silu(z))
    v = np.asarray(y * jax.nn.silu(z), np.float64)
    want = v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5) * np.asarray(gain)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_mamba_mixer_params_and_scopes():
    mixer = ssm.MambaMixer(dim=16, n_heads=H, d_head=P, d_state=N, chunk=CHUNK)
    v = jax.random.normal(jax.random.key(0), (1, 20, 16))
    variables = mixer.init(jax.random.key(1), v)
    shapes = {"/".join(k): x.shape for k, x in
              traverse_util.flatten_dict(variables["params"]).items()}
    inner = H * P
    assert shapes == {
        "in_proj/kernel": (16, 2 * inner + 2 * N + H), "conv/kernel": (4, inner + 2 * N),
        "conv/bias": (inner + 2 * N,), "A_log": (H,), "dt_bias": (H,), "D": (H,),
        "norm/scale": (inner,), "out_proj/kernel": (inner, 16),
    }
    # Mamba-2's own start: A in [1, 16], the step in [1e-3, 1e-1]
    p = variables["params"]
    assert np.all((np.exp(p["A_log"]) >= 1) & (np.exp(p["A_log"]) <= 16))
    step = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert np.all((step >= 1e-3 * 0.999) & (step <= 1e-1 * 1.001))
    text = jax.jit(lambda v: mixer.apply(variables, v)).lower(v).as_text(debug_info=True)
    assert "ssm.conv" in text and "ssm.scan" in text
