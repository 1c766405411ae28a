"""The gated delta rule (ops/gdn.py) against the sequential recurrence of the
plain reference (benchmarks/reference_gdn.py: one position at a time, float32,
no chunk): the chunked XLA form and the Pallas kernel pair, interpreted on the
CPU at small shapes they are eligible for, outputs and every gradient; rows
that are not whole chunks; decays near 0 and near -20 a position; beta near 0
and near 1; and planted faults that must fail.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_gdn
from dalle_pytorch_tpu.ops import gdn, kv_policy

B, HK, HV = 2, 1, 2


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def inputs(n, d, seed=0, decay=1.0, beta=None):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = (gdn.l2norm(jax.random.normal(ks[0], (B, n, HK, d))) * d**-0.5).reshape(B, n, HK * d)
    k = gdn.l2norm(jax.random.normal(ks[1], (B, n, HK, d))).reshape(B, n, HK * d)
    v = jax.random.normal(ks[2], (B, n, HV * d))
    g = -decay * jax.random.uniform(ks[3], (B, n, HV))
    b = jax.random.uniform(ks[4], (B, n, HV)) if beta is None else jnp.full((B, n, HV), beta)
    return (q, k, v, g, b), jax.random.normal(ks[5], (B, n, HV * d))


def recurrence(q, k, v, g, beta):
    """The reference's recurrence over (b, n, heads x width) operands."""
    b, n, h = g.shape
    heads = lambda t, count: t.reshape(n, count, -1)
    rows = [
        reference_gdn.delta_recurrence(
            jnp.repeat(heads(q[i], HK), h // HK, axis=1), jnp.repeat(heads(k[i], HK), h // HK, axis=1),
            heads(v[i], h), g[i], beta[i],
        ).reshape(n, -1)
        for i in range(b)
    ]
    return jnp.stack(rows)


def rule(chunk, dtype=jnp.float32):
    return lambda *a: gdn.gated_delta_rule(*a, HK, chunk, dtype)


def assert_follows(f, want, args, cotangent, tol=2e-5):
    got, vjp = jax.vjp(f, *args)
    ref, ref_vjp = jax.vjp(want, *args)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(got - ref))) <= tol * scale
    for name, a, r in zip("q k v g beta".split(), vjp(cotangent), ref_vjp(cotangent)):
        assert float(jnp.max(jnp.abs(a - r))) <= tol * max(float(jnp.max(jnp.abs(r))), 1.0), name


# (keys and values' width, chunk): 8 is not lane-aligned and takes the XLA
# form; 128 takes the kernel pair, interpreted
FORMS = {"xla": (8, 16), "kernels": (128, 16)}


@pytest.mark.parametrize("n", [48, 43, 9])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_both_forms_match_the_recurrence_in_float32(form, n):
    d, chunk = FORMS[form]
    kv_policy.ROUTE_LOG.clear()
    args, cotangent = inputs(n, d)
    assert_follows(rule(chunk), recurrence, args, cotangent)
    impl = {"site": "forward/delta_rule", "impl": "xla", "interpret": None} if form == "xla" else {
        "site": "forward/delta_rule", "impl": "gdn_chunk", "interpret": True}
    assert kv_policy.ROUTE_LOG == [impl]


@pytest.mark.parametrize("decay,beta", [(0.01, None), (20.0, None), (1.0, 1e-3), (1.0, 1.0 - 1e-3)])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_decays_near_0_and_near_minus_20_and_beta_near_0_and_1(form, decay, beta):
    d, chunk = FORMS[form]
    args, cotangent = inputs(40, d, seed=1, decay=decay, beta=beta)
    assert_follows(rule(chunk), recurrence, args, cotangent)


def test_the_kernels_match_the_xla_form_at_the_sources_chunk(monkeypatch):
    args, cotangent = inputs(128, 128, seed=2, decay=0.05)
    out, vjp = jax.vjp(rule(64), *args)
    grads = vjp(cotangent)
    monkeypatch.setattr(gdn, "delta_rule_kernels_eligible", lambda *a: False)
    xla = jax.vjp(rule(64), *args)
    np.testing.assert_allclose(out, xla[0], atol=2e-6)
    for a, r in zip(grads, xla[1](cotangent)):
        np.testing.assert_allclose(a, r, atol=2e-5 * max(float(jnp.max(jnp.abs(r))), 1.0))


@pytest.mark.parametrize("form", sorted(FORMS))
def test_in_bfloat16_both_forms_stay_in_their_band(form):
    d, chunk = FORMS[form]
    args, _ = inputs(48, d, seed=3)
    want = recurrence(*args)
    got = rule(chunk, jnp.bfloat16)(*args).astype(jnp.float32)
    assert got.dtype == jnp.float32 and rule(chunk, jnp.bfloat16)(*args).dtype == jnp.bfloat16
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert 1e-4 < err < 2e-2, err


@pytest.mark.parametrize("fault", ["no_decay", "beta_one", "no_correction", "no_carry"])
def test_a_planted_fault_fails(fault, monkeypatch):
    args, _ = inputs(48, 128, seed=4)
    q, k, v, g, beta = args
    want = recurrence(*args)
    if fault == "no_decay":
        got = rule(16)(q, k, v, jnp.zeros_like(g), beta)
    elif fault == "beta_one":
        got = rule(16)(q, k, v, g, jnp.ones_like(beta))
    elif fault == "no_correction":
        # the rank-one ADDITION of a state-space layer: what the rule is not
        monkeypatch.setattr(gdn, "_unit_lower_inverse", lambda a: jnp.eye(a.shape[0]) + 0 * a)
        gdn._fwd_call.clear_cache()       # the kernel call is a jit of its own
        got = rule(16)(*args)
        gdn._fwd_call.clear_cache()
    else:
        got = jnp.concatenate([rule(16)(*(t[:, lo : lo + 16] for t in args)) for lo in (0, 16, 32)], axis=1)
    gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert gap > 0.02, gap         # the program itself reads 2e-5 here


def test_the_inverse_is_the_inverse():
    a = jnp.tril(jax.random.normal(jax.random.key(0), (64, 64)) * 0.3, -1)
    np.testing.assert_allclose(
        gdn._unit_lower_inverse(a) @ (jnp.eye(64) + a), jnp.eye(64), atol=2e-4
    )


def test_eligibility_is_read_from_the_shape():
    assert gdn.delta_rule_kernels_eligible(64, 128, 128)          # the cell's
    assert gdn.delta_rule_kernels_eligible(16, 128, 256)
    assert not gdn.delta_rule_kernels_eligible(64, 64, 128)       # half a lane tile of keys
    assert not gdn.delta_rule_kernels_eligible(48, 128, 128)      # no power of two
    assert not gdn.delta_rule_kernels_eligible(8, 128, 128)       # under a bfloat16 sublane tile


def test_the_mixers_parameters_and_scopes():
    mixer = gdn.GatedDeltaNet(dim=32, key_heads=2, value_heads=4, key_dim=8, value_dim=8, chunk=16)
    x = jax.random.normal(jax.random.key(0), (2, 24, 32))
    params = mixer.init(jax.random.key(1), x)["params"]
    shapes = jax.tree_util.tree_map(lambda t: t.shape, params)
    assert shapes == {
        "in_proj_qkvz": {"kernel": (32, 2 * 16 + 2 * 32)}, "in_proj_ba": {"kernel": (32, 8)},
        "conv": {"kernel": (4, 2 * 16 + 32)}, "A_log": (4,), "dt_bias": (4,), "norm_scale": (8,),
        "out_proj": {"kernel": (32, 32)},
    }
    assert float(jnp.min(jnp.exp(params["A_log"]))) > 0 and float(jnp.max(jnp.exp(params["A_log"]))) < 16
    text = jax.jit(lambda p, t: mixer.apply({"params": p}, t)).lower(params, x).as_text(debug_info=True)
    for scope in ("linattn.proj", "linattn.conv", "linattn.delta", "linattn.norm"):
        assert scope in text, scope
    assert mixer.apply({"params": params}, x).shape == x.shape
