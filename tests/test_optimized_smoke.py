"""Always-on optimized-XLA smoke subset.

conftest.py runs the whole suite under JAX_DISABLE_MOST_OPTIMIZATIONS=1
(a measured ~35% compile-time win for the compile-dominated suite), which
means every other parity test exercises the UNOPTIMIZED XLA pipeline while
the benchmark and serving run fully optimized — a miscompile or numerical
divergence introduced by XLA's optimization passes (exactly the bug class
the parity suite exists to catch) would pass CI undetected (ADVICE.md
round 5). This file is the counterweight: one decode-parity and one
attention-parity case re-run with the optimization pipeline ENABLED, every
run, kept tiny so they stay in tier-1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import DALLE, generate_image_tokens
from dalle_pytorch_tpu.ops.attention import PatternAttention


@pytest.fixture
def optimized_xla():
    """Flip the process-wide config to the optimized pipeline for one test;
    clear compiled-program caches on both edges so nothing compiled under
    the other setting is reused."""
    prev = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    jax.clear_caches()
    try:
        yield
    finally:
        jax.config.update("jax_disable_most_optimizations", prev)
        jax.clear_caches()


def test_decode_parity_with_optimizations_enabled(optimized_xla):
    """KV-cached decode (prefill + scan, the serving path) vs the full
    forward pass, under the optimized XLA pipeline: the logits argmax chain
    that picks every sampled token must agree with the parallel forward."""
    dalle = DALLE(
        dim=32, depth=2, num_text_tokens=16, text_seq_len=4,
        num_image_tokens=12, image_fmap_size=2, heads=2, dim_head=8,
        attn_types=("full", "axial_row"),
    )
    rng = np.random.RandomState(0)
    text = jnp.asarray(rng.randint(1, 16, size=(2, 4)), jnp.int32)
    image = jnp.asarray(rng.randint(0, 12, size=(2, 4)), jnp.int32)
    params = dalle.init(jax.random.key(0), text, image)["params"]

    full_logits = np.asarray(dalle.apply({"params": params}, text, image))
    internal = np.concatenate(
        (np.asarray(dalle.remap_text(text)), np.asarray(image)), axis=1
    )
    from dalle_pytorch_tpu.models import init_decode_cache

    cache = init_decode_cache(dalle, params, 2)
    for i in range(dalle.total_seq_len):
        step_logits, mutated = dalle.apply(
            {"params": params, "cache": cache},
            jnp.asarray(internal[:, i]),
            jnp.array(i, jnp.int32),
            method=DALLE.decode_step,
            mutable=["cache"],
        )
        cache = mutated["cache"]
        np.testing.assert_allclose(
            np.asarray(step_logits), full_logits[:, i], atol=2e-3, rtol=1e-3,
            err_msg=f"optimized-XLA decode/forward mismatch at position {i}",
        )
    # the end-to-end sampler also runs (prefill + segmented scan compile
    # under the optimized pipeline) and stays in-vocab
    toks = np.asarray(generate_image_tokens(dalle, params, text, jax.random.key(1)))
    assert ((toks >= 0) & (toks < dalle.num_image_tokens)).all()


def test_serving_decode_parity_with_optimizations_enabled(optimized_xla):
    """The serving path's pinned contract — chunked prefill bit-identical
    to monolithic — re-run with the optimization pipeline ENABLED: the
    continuous-batching engine's prefill/decode programs (the ones
    production serving actually compiles) must sample the
    same tokens either way (ADVICE.md round 5: every other serving test
    runs unoptimized)."""
    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, FakeClock, Outcome, Request,
    )

    dalle = DALLE(
        dim=32, depth=2, num_text_tokens=16, text_seq_len=4,
        num_image_tokens=12, image_fmap_size=2, heads=2, dim_head=8,
        attn_types=("full",), shift_tokens=True, rotary_emb=True,
    )
    rng = np.random.RandomState(0)
    text = jnp.asarray(rng.randint(1, 16, size=(2, 4)), jnp.int32)
    image = jnp.asarray(rng.randint(0, 12, size=(2, 4)), jnp.int32)
    params = dalle.init(jax.random.key(0), text, image)["params"]

    def serve(prefill_chunk):
        eng = Engine(
            dalle, params,
            EngineConfig(max_batch=2, prefill_chunk=prefill_chunk),
            clock=FakeClock(step_dt=1.0),
        )
        for i in range(2):
            assert eng.submit(Request(
                request_id=f"o{i}",
                prompt=rng.__class__(100 + i).randint(
                    1, 16, size=(4,)).astype(np.int32),
                max_new_tokens=4, seed=i,
            )) is None
        eng.run(max_steps=200)
        for r in eng.results.values():
            assert r.outcome is Outcome.COMPLETED, r
        return {k: np.asarray(r.tokens) for k, r in eng.results.items()}

    mono = serve(prefill_chunk=None)
    chunked = serve(prefill_chunk=2)
    assert mono.keys() == chunked.keys()
    for rid in mono:
        np.testing.assert_array_equal(
            mono[rid], chunked[rid],
            err_msg=f"{rid}: optimized-XLA serving chunked/monolithic "
                    "divergence",
        )


@pytest.mark.parametrize("attn_type", ["axial_row", "conv_like"])
def test_attention_parity_with_optimizations_enabled(optimized_xla, attn_type):
    """Grouped FLOP-efficient attention vs the dense-masked oracle under
    the optimized XLA pipeline."""
    attn = PatternAttention(
        dim=32, seq_len=21, attn_type=attn_type, heads=2, dim_head=16,
        image_fmap_size=4,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 32))
    params = attn.init(jax.random.PRNGKey(1), x)
    eff = attn.apply(params, x)
    dense = attn.apply(params, x, force_dense=True)
    np.testing.assert_allclose(np.asarray(eff), np.asarray(dense), atol=2e-5)
