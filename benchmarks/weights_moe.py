"""Weights of a latent-attention / routed-expert language model from the seed.

``weights.py``'s rules by leaf name (kernels by fan-in, norm gains) hold for
every leaf they know; the embedding and the five leaves for which
``weights._leaf`` raises are drawn here:

    embedding    (vocabulary, hidden)      noise of unit variance. This model
                 has no embedding multiplier, and every block adds an output of
                 RMS ~1 at these kernels' scale: rows of 1/sqrt(hidden) leave
                 the stream after layer 0 the same for every token to a few
                 percent, the routers then send most tokens to the same few
                 experts (fullest expert 3-6 x the mean of all 256, and more as
                 Adam moves a router whose inputs all point one way) and the
                 step's time depends on the seed. At unit variance the
                 token's own part stands beside the blocks' outputs as in a
                 trained model (fullest expert 1.6-2.3 x the mean; my chip
                 run, PR 36, `benchmarks/.work/probe.py`)

    experts_in   (held, hidden, 2 width)   noise / sqrt(hidden): an expert's fan-in
    experts_out  (held, width, hidden)     noise / sqrt(width)
    lm_head      (vocabulary, hidden)      noise / sqrt(hidden), as the embedding
    e_score_correction_bias  (experts,)    0.01 x noise: the sigmoid scores of
                 neighbours in rank lie about 0.007 apart near the eighth of
                 256, so a bias of this size changes some choices and a fault
                 that puts it into the weights shows
    tokens_per_expert        (experts,)    zero: no step has run

The program and the reference both read their weights from here, by the
leaf's path, and neither takes anything the other has made. Keyed as
``weights_lm.make_leaf`` keys a leaf (seed, path), under a salt of its own.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np
from flax import traverse_util

from . import weights


def _leaf(key, path: tuple, shape: tuple, dtype):
    name = path[-1]
    if name == "embedding":
        value = jax.random.normal(key, shape, jnp.float32)
    elif name in ("experts_in", "experts_out"):
        value = jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[1])
    elif name == "lm_head":
        value = jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-1])
    elif name == "e_score_correction_bias":
        value = 0.01 * jax.random.normal(key, shape, jnp.float32)
    elif name == "tokens_per_expert":
        value = jnp.zeros(shape, jnp.float32)
    else:
        return weights._leaf(key, path, shape, dtype)
    return value.astype(dtype)


# one program a (rule, shape): the rules read the last two names of a path
_draw = jax.jit(_leaf, static_argnums=(1, 2, 3))


def make_leaf(path: tuple, shape: tuple, seed: int, dtype, salt: str = "moe"):
    """One leaf, by the seed (its two halves folded in: seeds run past 2**31)
    and the leaf's path."""
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    sub = jax.random.fold_in(key, zlib.crc32("/".join((salt,) + path).encode()) & 0x7FFFFFFF)
    return _draw(sub, tuple(path[-2:]), tuple(shape), dtype)


def make_params(shapes, seed: int, dtype, salt: str = "moe"):
    """``shapes``: a pytree of ShapeDtypeStructs (nested dicts). One jitted
    call a leaf, as ``weights_lm.make_params``."""
    flat = traverse_util.flatten_dict(shapes)
    return traverse_util.unflatten_dict({
        path: make_leaf(path, flat[path].shape, seed, dtype, salt) for path in sorted(flat)
    })
