"""Device time of one compiled module's collective operations (all-reduce,
all-gather, reduce-scatter, their -start and -done halves included) over the
device time of all of the module's operations in the traced slice, first
chip, in percent. A module with no collective (one chip) has nothing to
read."""

from benchmarks import trace_reduce

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter")


def read(ctx, module):
    r = ctx.reduced
    cats = r.get("by_module_category", {}).get(module) if r else None
    if not cats:
        return None
    seconds = trace_reduce.category_seconds(r, module, COLLECTIVES)
    if not seconds:
        return None
    return 100.0 * seconds / sum(cats.values())
