"""Device time of one compiled module's operations under the given scopes
(``jax.named_scope`` names the program gives, ``benchmarks/scope_reduce.py``;
``attn.*`` is every attention kind) over the device time of all of the
module's operations in the traced slice, in percent. With ``none_of`` it is
the rest: operations under none of those scopes (a fusion takes its root's
scope, so what XLA merges across a boundary, and what carries no name stack,
shows here)."""

from benchmarks import scope_reduce


def read(ctx, module, scopes=None, none_of=None):
    reduced = scope_reduce.of_run(ctx)
    rows = reduced.get("by_scope", {}).get(module) if reduced else None
    if not rows:
        return None
    named = scope_reduce.scope_seconds(reduced, module, scopes or none_of)
    if not named:
        return None    # a program that gives none of these scopes: nothing to read
    total = sum(sec for _, _, sec in rows)
    return 100.0 * (named if scopes else total - named) / total
