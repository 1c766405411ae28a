"""Model FLOPs of every prompt and image token the window processed over what
the chips could do in its seconds, in percent: per decode step the live rows'
projections and head columns and attention over their actual frontiers, per
admitted request one prefill (``benchmarks/costs.py``)."""

from benchmarks import costs


def read(ctx):
    samples = ctx.facts.get("samples")
    if not samples or not ctx.peaks:
        return None
    flops = sum(costs.decode_step_flops(ctx.cfg, n, f) for n, f in samples)
    flops += ctx.facts.get("prefills", 0) * costs.prefill_flops(ctx.cfg)
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * flops / (ctx.facts["window_s"] * peak)
