"""Weights of a state-space / attention language model from the seed.

``weights.py``'s rules by leaf name (kernels by fan-in, embeddings, norm
gains, biases) hold for every leaf they know; the three leaves of a Mamba-2
mixer for which ``weights._leaf`` raises get the values Mamba-2 itself starts
from. With normal noise in ``A_log`` and ``dt_bias`` the state forgets within
a few tokens, and no comparison could see the state carried between chunks:

    A_log    log of U[1, 16]                    (A = -exp(A_log) in [-16, -1])
    dt_bias  softplus^-1 of log-uniform [1e-3, 1e-1]
    D        1 + 0.02 noise

The program and the reference both read their weights from here, by the
leaf's path, and neither takes anything the other has made.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
from flax import traverse_util

from . import weights


def _leaf(key, path: tuple, shape: tuple, dtype):
    name = path[-1]
    if name == "A_log":
        value = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        value = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "D":
        value = 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:
        return weights._leaf(key, path, shape, dtype)
    return value.astype(dtype)


# one program a (rule, shape): the rules read the last two names of a path
_draw = jax.jit(_leaf, static_argnums=(1, 2, 3))


def make_leaf(path: tuple, shape: tuple, seed: int, dtype, salt: str = "lm"):
    """One leaf, keyed as ``weights.make_params`` keys it: by the seed (its two
    halves folded in: seeds run past 2**31) and the leaf's path."""
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    sub = jax.random.fold_in(key, zlib.crc32("/".join((salt,) + path).encode()) & 0x7FFFFFFF)
    return _draw(sub, tuple(path[-2:]), tuple(shape), dtype)


def make_params(shapes, seed: int, dtype, salt: str = "lm"):
    """``shapes``: a pytree of ShapeDtypeStructs (nested dicts). One jitted
    call a leaf: 772 M parameters drawn in ONE program would hold every
    leaf's noise at once."""
    flat = traverse_util.flatten_dict(shapes)
    return traverse_util.unflatten_dict({
        path: make_leaf(path, flat[path].shape, seed, dtype, salt) for path in sorted(flat)
    })
