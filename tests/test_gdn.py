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


# at the source's chunk a tile is two chunks and (HV = 2 = ratio) a grid step
# of the state-free kernel two tiles: 128 positions leave the step's second
# tile to the grid's edge, 192 end in a chunk that is padded to a tile, 256
# fill a step
@pytest.mark.parametrize("n", [128, 192, 256])
def test_the_kernels_match_the_xla_form_at_the_sources_chunk(n, monkeypatch):
    args, cotangent = inputs(n, 128, seed=2, decay=0.05)
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
        monkeypatch.setattr(gdn, "_unit_lower_inverse", lambda a, block=None: jnp.eye(a.shape[-1]) + 0 * a)
        gdn._tables_call.clear_cache()    # the state-free kernel's call is a jit of its own
        got = rule(16)(*args)
        gdn._tables_call.clear_cache()
    else:
        got = jnp.concatenate([rule(16)(*(t[:, lo : lo + 16] for t in args)) for lo in (0, 16, 32)], axis=1)
    gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert gap > 0.02, gap         # the program itself reads 2e-5 here


def kernel_operands(n, chunk, seed=5):
    """``delta_rule_chunks``'s operands as ``gated_delta_rule`` hands them over."""
    (q, k, v, g, beta), _ = inputs(n, 128, seed=seed)
    table = lambda t: t.reshape(B, n // chunk, chunk, HV).transpose(0, 3, 1, 2)
    return q, k, v, gdn.chunk_log_decay(table(g)), table(beta)


def test_the_inverse_is_built_once_a_forward_call_and_never_in_the_backward(monkeypatch):
    built = []
    real = gdn._unit_lower_inverse
    monkeypatch.setattr(gdn, "_unit_lower_inverse", lambda a, block=None: (built.append((a.shape, block)), real(a, block))[1])
    calls = (gdn._tables_call, gdn._fwd_call, gdn._bwd_call)
    for call in calls:
        call.clear_cache()
    operands = kernel_operands(256, 64)
    traced = jax.make_jaxpr(
        lambda *a: jax.vjp(lambda *b: gdn.delta_rule_chunks(*b, HK, True), *a)[1](a[2])
    )(*operands)
    for call in calls:
        call.clear_cache()
    # one call, in the state-free kernel: a grid step's two tiles of two chunks, both value heads
    assert built == [((2 * HV, 128, 128), 64)], built
    names = str(traced)
    for kernel in ("gdn_chunk_tables", "gdn_chunk_fwd", "gdn_chunk_bwd"):
        assert names.count(f"name={kernel}") == 1, kernel


@pytest.mark.parametrize("n,chunk", [(256, 64), (128, 16)])
def test_the_forwards_tables_are_the_xla_forms(n, chunk):
    q, k, v, g, beta = kernel_operands(n, chunk)
    tp = gdn._tables_call(q, k, g, beta, key_heads=HK, interpret=True)
    assert tp.shape == (B, HV, n, 2 * chunk)
    per_head = lambda t: gdn._split_heads(t, g, HK, HV // HK)
    t, p = gdn._tables_xla(per_head(q), per_head(k), g, beta, jnp.float32)
    tp = tp.reshape(B, HV, n // chunk, chunk, 2 * chunk)
    np.testing.assert_allclose(tp[..., :chunk], t, atol=2e-6)
    np.testing.assert_allclose(tp[..., chunk:], p, atol=2e-6)


def test_the_inverse_is_the_inverse():
    a = jnp.tril(jax.random.normal(jax.random.key(0), (64, 64)) * 0.3, -1)
    np.testing.assert_allclose(
        gdn._unit_lower_inverse(a) @ (jnp.eye(64) + a), jnp.eye(64), atol=2e-4
    )


@pytest.mark.parametrize("rows,block", [(128, 64), (128, 16), (128, 128), (16, 16), (8, 8)])
def test_the_inverse_of_a_tile_of_blocks_is_the_blocks_inverses(rows, block):
    a = jnp.tril(jax.random.normal(jax.random.key(1), (3, rows, rows)) * 0.3, -1)
    at = np.arange(rows) // block
    a = jnp.where(at[:, None] == at[None, :], a, 0.0)
    want = np.linalg.inv(np.eye(rows) + np.asarray(a, np.float64))
    got = gdn._unit_lower_inverse(a, block)
    np.testing.assert_allclose(got, want, atol=2e-6 * np.max(np.abs(want)))
    assert float(jnp.max(jnp.abs(jnp.where(at[:, None] == at[None, :], 0.0, got)))) == 0.0


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
