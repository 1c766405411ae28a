"""Required forward+backward FLOPs of the window's steps over what the chips
could do in its seconds, in percent. Attention counts only the pairs each
layer's pattern leaves unmasked; recomputation is not counted. The seconds
the profiler took to start and to write its slice, in which no step can be
dispatched, are taken out of a traced window."""

from benchmarks import costs


def read(ctx):
    steps = ctx.facts.get("steps")
    if not steps or not ctx.peaks:
        return None
    flops = steps * costs.train_step_flops(ctx.cfg, ctx.facts["batch"])["total"]
    seconds = ctx.facts["window_s"] - ctx.facts.get("trace_overhead_s", 0.0)
    return 100.0 * flops / (seconds * ctx.peaks["bf16_flops_per_s"] * ctx.chips)
