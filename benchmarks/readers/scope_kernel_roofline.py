"""One attention KIND's share of the compute roofline inside the train step,
in percent: the forward+backward FLOPs the layers of that kind require (QK^T
and AV over the pairs the kind's pattern leaves unmasked,
``costs.attended_pairs``) for the step's executions in the traced slice at
the chip's peak, over the device time of the Pallas kernels that ran under
the kind's scope (``attn.<kind>``, ``benchmarks/scope_reduce.py``). It reads
the work a layer kind requires, whatever kernel runs it: a reroute moves the
number and keeps its meaning. Bound by compute at these shapes."""

from benchmarks import costs, scope_reduce


def read(ctx, module, kind):
    r = ctx.reduced
    mod = r.get("modules", {}).get(module) if r else None
    if not mod or not ctx.peaks:
        return None
    seconds = scope_reduce.scope_seconds(
        scope_reduce.of_run(ctx), module, scopes=[f"attn.{kind}"], kernels_only=True
    )
    if not seconds:
        return None
    layers = costs.layer_kinds(ctx.cfg).count(kind)
    # forward 4 FLOPs a pair and channel, backward twice that; per chip
    required = (
        3 * 4 * ctx.facts["batch"] * costs.attended_pairs(ctx.cfg, kind)
        * costs.inner_dim(ctx.cfg) * layers / ctx.chips
    )
    return 100.0 * mod["count"] * required / ctx.peaks["bf16_flops_per_s"] / seconds
