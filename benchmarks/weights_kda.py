"""Weights of a KDA / latent-attention / routed-expert language model
(``kimi_linear``) from the seed.

``weights_moe.py``'s rules hold for every leaf they know (kernels by fan-in,
the convolution's taps among them; norm gains near 1; the embedding of unit
variance; the held experts by their fan-in; the head by 1/sqrt(hidden); the
selection bias 0.01 x noise; ``tokens_per_expert`` zero); the KDA mixer's
three vectors are drawn here, as the family's modelling code starts them:

    A_log        (heads,)          log of U(1, 16): A = exp(A_log) in [1, 16]
    dt_bias      (heads x d,)      the inverse softplus of dt, dt log-uniform in
                                   [1e-3, 0.1] (the Mamba rule): at a zero
                                   input a channel's g is -A dt, so most channels
                                   keep their state for tens of positions and a
                                   few forget within one
    norm_scale   (d,)              1 + 0.02 noise: the gated norm's gain

The program and the reference both read their weights from here, by the
leaf's path, and neither takes anything the other has made. Keyed as
``weights_moe.make_leaf`` keys a leaf (seed, path), under a salt of its own.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
from flax import traverse_util

from . import weights_moe

SALT = "kda"


def _leaf(key, path: tuple, shape: tuple, dtype):
    name = path[-1]
    if name == "A_log":
        value = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(0.1)))
        value = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "norm_scale":
        value = 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:
        return weights_moe._leaf(key, path, shape, dtype)
    return value.astype(dtype)


# one program a (rule, shape): the rules read the last two names of a path
_draw = jax.jit(_leaf, static_argnums=(1, 2, 3))


def make_leaf(path: tuple, shape: tuple, seed: int, dtype):
    """One leaf, by the seed (its two halves folded in: seeds run past 2**31)
    and the leaf's path."""
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    sub = jax.random.fold_in(key, zlib.crc32("/".join((SALT,) + path).encode()) & 0x7FFFFFFF)
    return _draw(sub, tuple(path[-2:]), tuple(shape), dtype)


def make_params(shapes, seed: int, dtype):
    """``shapes``: a pytree of ShapeDtypeStructs (nested dicts). One jitted
    call a leaf, as ``weights_moe.make_params``."""
    flat = traverse_util.flatten_dict(shapes)
    return traverse_util.unflatten_dict({
        path: make_leaf(path, flat[path].shape, seed, dtype) for path in sorted(flat)
    })
