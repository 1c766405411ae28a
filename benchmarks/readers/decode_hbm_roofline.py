"""The decode step's share of its HBM roofline, in percent: the bytes its
executions in the traced slice had to stream (weights and image-head columns
once a step, K and V rows of the live slots up to the frontiers the harness
recorded) at the chip's peak bandwidth, over the device time of those
executions. The bound is HBM: at 64 rows the step's FLOPs would take a
twentieth of the time its bytes do."""

from benchmarks import costs


def read(ctx, module):
    mod = ctx.reduced.get("modules", {}).get(module)
    samples = ctx.facts.get("samples")
    if not mod or not mod["seconds"] or not samples or not ctx.peaks:
        return None
    frontier = sum(f for _, f in samples) / len(samples)
    least = mod["count"] * costs.decode_step_bytes(ctx.cfg, frontier) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / mod["seconds"]
