"""Weights from the seed, made on the device in one jitted call.

The program gives only the SHAPES of its parameter tree (``jax.eval_shape`` of
its ``init``); every value is drawn here, by a rule on the leaf's name and
shape, so the program and the plain reference start from the same numbers and
neither takes anything the other has made. The values are those of a model a
few steps into training rather than of a fresh ``init``: biases and norm gains
are not all exactly 0 and 1, so a path that dropped one would show.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np
from flax import traverse_util


def _leaf(key, path: tuple, shape: tuple, dtype):
    name = path[-1]
    noise = jax.random.normal(key, shape, jnp.float32)
    if name == "kernel":
        fan_in = int(np.prod(shape[:-1]))
        value = noise / np.sqrt(fan_in)
    elif name in ("embedding", "row_emb", "col_emb"):
        value = noise / np.sqrt(shape[-1])
    elif name == "scale" and len(path) >= 2 and (
        path[-2].startswith("LayerNorm") or path[-2].endswith("norm")
    ):
        value = 1.0 + 0.02 * noise
    elif name == "scale":
        # LayerScale gain of a block (0.1 up to depth 18 in the source)
        value = 0.1 * (1.0 + 0.02 * noise)
    elif name == "bias":
        value = 0.02 * noise
    else:
        raise ValueError(f"no rule for parameter {'/'.join(path)} {shape}")
    return value.astype(dtype)


def make_params(shapes, seed: int, dtype, salt: str = "dalle"):
    """``shapes``: a pytree of ShapeDtypeStructs (nested dicts). Returns the
    same tree filled from ``seed``, every leaf in ``dtype``. A leaf's stream
    is keyed by its path, not by its place in the tree, so adding a leaf
    moves no other."""
    flat = traverse_util.flatten_dict(shapes)
    paths = sorted(flat)

    def build(key):
        out = {}
        for path in paths:
            sub = jax.random.fold_in(
                key, zlib.crc32("/".join((salt,) + path).encode()) & 0x7FFFFFFF
            )
            out[path] = _leaf(sub, path, tuple(flat[path].shape), dtype)
        return out

    # seeds run past 2**31: fold the two halves in, key() wants 32 bits
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return traverse_util.unflatten_dict(jax.jit(build)(key))
