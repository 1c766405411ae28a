"""As ``train_gdn_mfu``, for a KDA / latent-attention / routed-expert
language-model cell (``kimi_linear``): the required forward+backward FLOPs of
the window's steps (``costs_kda.train_step``: the projections and the
convolution, the KDA rule at chunk 64 term by term, the latent attention's
causal pairs over 192 + 128, the dense feed-forward, the shared experts and
routers, the pairs the held experts were REALLY sent, the head; recomputation
not counted) over what the chips could do in its seconds, in percent, the
seconds the profiler took out of a traced window."""

from benchmarks import costs_kda


def read(ctx):
    steps = ctx.facts.get("steps")
    if not steps or not ctx.peaks or "moe_pairs_per_step" not in ctx.facts:
        return None
    need = costs_kda.train_step(
        ctx.cfg, ctx.facts["rows"], ctx.facts["tokens"], ctx.facts["moe_pairs_per_step"]
    )
    seconds = ctx.facts["window_s"] - ctx.facts.get("trace_overhead_s", 0.0)
    return 100.0 * steps * need["total"] / (seconds * ctx.peaks["bf16_flops_per_s"] * ctx.chips)
