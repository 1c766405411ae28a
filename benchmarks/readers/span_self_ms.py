"""Mean self time of one of the program's host spans per execution of the
span that counts iterations (``per``), in ms, from the host plane of the
traced slice: a span's duration less the spans nested directly inside it
(``benchmarks/scope_reduce.py``)."""

from benchmarks import scope_reduce


def read(ctx, span, per):
    spans = scope_reduce.of_run(ctx).get("host_spans", {})
    if span not in spans or not spans.get(per, {}).get("count"):
        return None
    return 1e3 * spans[span]["self_seconds"] / spans[per]["count"]
