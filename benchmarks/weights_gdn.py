"""Weights of a linear-attention / gated-attention / routed-expert language
model from the seed.

``weights.py``'s rules by leaf name (kernels by fan-in: the projections, the
router, the shared expert's gate and the convolution's taps; norm gains near
1) hold for every leaf they know; the embedding and the leaves for which
``weights._leaf`` raises are drawn here:

    embedding    (vocabulary, hidden)      noise of unit variance, as
                 ``weights_moe.py`` draws it and for its reason: no embedding
                 multiplier, so rows of 1/sqrt(hidden) would leave the stream
                 after layer 0 the same for every token and the routers would
                 send most tokens to the same few experts
    experts_in   (held, hidden, 2 width)   noise / sqrt(hidden): an expert's fan-in
    experts_out  (held, width, hidden)     noise / sqrt(width)
    lm_head      (vocabulary, hidden)      noise / sqrt(hidden)
    A_log        (value heads,)            log of U(0, 16), the family's own start
                 (A = exp(A_log) in (0, 16): most heads forget within a position
                 or two, a few carry their state through many chunks)
    dt_bias      (value heads,)            1 + 0.02 noise (the family starts it at 1)
    norm_scale   (value width,)            1 + 0.02 noise: the gated norm's gain
    tokens_per_expert, router_prob  (experts,)   zero: no step has run

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
    elif name == "A_log":
        value = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-4, 16.0))
    elif name in ("dt_bias", "norm_scale"):
        value = 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif name in ("tokens_per_expert", "router_prob"):
        value = jnp.zeros(shape, jnp.float32)
    else:
        return weights._leaf(key, path, shape, dtype)
    return value.astype(dtype)


# one program a (rule, shape): the rules read the last two names of a path
_draw = jax.jit(_leaf, static_argnums=(1, 2, 3))


def make_leaf(path: tuple, shape: tuple, seed: int, dtype, salt: str = "gdn"):
    """One leaf, by the seed (its two halves folded in: seeds run past 2**31)
    and the leaf's path."""
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    sub = jax.random.fold_in(key, zlib.crc32("/".join((salt,) + path).encode()) & 0x7FFFFFFF)
    return _draw(sub, tuple(path[-2:]), tuple(shape), dtype)


def make_params(shapes, seed: int, dtype, salt: str = "gdn"):
    """``shapes``: a pytree of ShapeDtypeStructs (nested dicts). One jitted
    call a leaf, as ``weights_lm.make_params``."""
    flat = traverse_util.flatten_dict(shapes)
    return traverse_util.unflatten_dict({
        path: make_leaf(path, flat[path].shape, seed, dtype, salt) for path in sorted(flat)
    })
