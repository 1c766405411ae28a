"""ops/ssm.py: the chunked state-space scan against two other algorithms for
the same recurrence (step by step; the quadratic form), forward and
gradients, at lengths that are and are not multiples of the chunk; the causal
depthwise convolution and the gated norm against plain transcriptions; and
planted faults that must FAIL those comparisons."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from dalle_pytorch_tpu.ops import ssm
from dalle_pytorch_tpu.ops.layers import RMSNorm

B, H, P, N, CHUNK = 2, 4, 8, 6, 16


def inputs(n, seed=0):
    ks = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(ks[0], (B, n, H, P))
    # steps and decays of Mamba-2's own range, so that state survives a chunk
    dt = jnp.exp(jax.random.uniform(ks[1], (B, n, H), minval=np.log(1e-3), maxval=np.log(1e-1)))
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    Bm = jax.random.normal(ks[3], (B, n, N))
    Cm = jax.random.normal(ks[4], (B, n, N))
    D = 1.0 + 0.1 * jax.random.normal(ks[5], (H,))
    return x, dt, A, Bm, Cm, D


def recurrence(x, dt, A, Bm, Cm, D):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D x_t."""
    def step(S, inp):
        xt, dtt, Bt, Ct = inp
        S = S * jnp.exp(dtt * A)[..., None, None] + (dtt[..., None] * xt)[..., None] * Bt[:, None, None, :]
        return S, jnp.einsum("bhpn,bn->bhp", S, Ct) + D[:, None] * xt
    S0 = jnp.zeros((x.shape[0], H, P, N))
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
