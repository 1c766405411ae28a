"""As ``train_mfu``, for a language-model cell: the required forward+backward
FLOPs of the window's steps (``costs_lm.train_step``: projections, head, the
attention layer's causal pairs, the chunked scan's minimum; recomputation not
counted) over what the chips could do in its seconds, in percent, the seconds
the profiler took out of a traced window."""

from benchmarks import costs_lm


def read(ctx):
    steps = ctx.facts.get("steps")
    if not steps or not ctx.peaks or "tokens" not in ctx.facts:
        return None
    flops = steps * costs_lm.train_step(ctx.cfg, ctx.facts["rows"], ctx.facts["tokens"])["total"]
    seconds = ctx.facts["window_s"] - ctx.facts.get("trace_overhead_s", 0.0)
    return 100.0 * flops / (seconds * ctx.peaks["bf16_flops_per_s"] * ctx.chips)
