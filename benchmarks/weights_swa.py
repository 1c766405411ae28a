"""Weights of a window / global attention, routed-expert language model
(``smallthinker``) from the seed.

Every leaf of this family has a rule in ``weights_gdn.py`` and is drawn by it,
under a salt of its own (``swa``): kernels by fan-in (the attention's
projections and the router, ``gate``), norm gains near 1, the embedding of
unit variance (no embedding multiplier: rows of 1/sqrt(hidden) would leave
the routers of every layer the same few experts), ``experts_in`` /
``experts_out`` by an expert's fan-in, ``lm_head`` by 1/sqrt(hidden), the two
counters ``tokens_per_expert`` and ``router_prob`` zero. The program and the
reference both read their weights from here, by the leaf's path.
"""

from __future__ import annotations

from . import weights_gdn

SALT = "swa"


def make_leaf(path: tuple, shape: tuple, seed: int, dtype):
    return weights_gdn.make_leaf(path, shape, seed, dtype, SALT)


def make_params(shapes, seed: int, dtype):
    return weights_gdn.make_params(shapes, seed, dtype, SALT)
