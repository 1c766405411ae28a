"""A kind of kernel's share of the compute roofline inside the train step, in
percent: the required FLOPs of that kind (``attention``: QK^T and AV over
unmasked pairs; ``matmul``: projections and loss head) for the step's
executions in the traced slice at the chip's peak, over the device time of
the step's operations in the HLO categories given (``custom-call``: the
Pallas attention kernels; ``convolution`` and ``output fusion``: the TPU
compiler's names for a dot and for a fusion rooted in one). Both kinds are
bound by compute at these shapes."""

from benchmarks import costs, trace_reduce


def read(ctx, module, categories, flops):
    r = ctx.reduced
    mod = r.get("modules", {}).get(module) if r else None
    if not mod or not ctx.peaks:
        return None
    seconds = trace_reduce.category_seconds(r, module, tuple(categories))
    if not seconds:
        return None
    # per chip: each runs its share of the batch
    required = costs.train_step_flops(ctx.cfg, ctx.facts["batch"])[flops] / ctx.chips
    return 100.0 * mod["count"] * required / ctx.peaks["bf16_flops_per_s"] / seconds
