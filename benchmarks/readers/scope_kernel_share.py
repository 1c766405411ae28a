"""``scope_share`` for a step in which XLA strips some operations of their
scope: device time of one compiled module's operations under the given scopes
OR named like one of ``kernels`` (``fnmatch`` on the custom call's name), over
the device time of all of the module's operations in the traced slice, in
percent; with ``none_of`` the rest.

The TPU compiler expands a grouped matrix product (``jax.lax.ragged_dot``)
into a custom call ``ragged-dot-*`` that carries NO name stack, so the expert
layer's two products reach the trace under no scope at all; by their name
they are counted where the program put them (``moe.experts``, inside
``moe``) and not with the unattributed."""

import fnmatch

from benchmarks import scope_reduce


def read(ctx, module, scopes=None, none_of=None, kernels=()):
    reduced = scope_reduce.of_run(ctx)
    rows = reduced.get("by_scope", {}).get(module) if reduced else None
    if not rows:
        return None
    named = sum(
        sec for path, kernel, sec in rows
        if scope_reduce.matches(path, scopes or none_of)
        or any(fnmatch.fnmatchcase(kernel, pattern) for pattern in kernels)
    )
    if not named:
        return None    # a program that gives none of these scopes: nothing to read
    total = sum(sec for _, _, sec in rows)
    return 100.0 * (named if scopes else total - named) / total
