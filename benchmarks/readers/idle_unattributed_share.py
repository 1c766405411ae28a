"""The share of the device's idle time in the traced slice that none of the
program's own phase spans explains, in percent: idle gaps (of 5 us and more,
the rule ``trace_reduce`` applies to ``bench.*``) whose midpoint no span
matching ``spans`` covers, over all such gaps."""

import fnmatch

from benchmarks import scope_reduce


def read(ctx, spans):
    reduced = scope_reduce.of_run(ctx)
    gaps = reduced.get("idle_gaps") if reduced else None
    if not gaps or not reduced.get("host_spans"):
        return None
    idle = sum(sec for sec, _ in gaps)
    unexplained = sum(
        sec for sec, names in gaps
        if not any(fnmatch.fnmatchcase(n, spans) for n in names)
    )
    return 100.0 * unexplained / idle
