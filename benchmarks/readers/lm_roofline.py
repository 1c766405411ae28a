"""A mixer kind's share of its roofline inside a language-model train step,
in percent: the least time the chip could take for the work that kind
REQUIRES in the step's executions of the traced slice, over the device time
spent under the kind's scope, whatever implements it.

``scan``: the chunked state-space scan's minimum (``costs_lm``): the larger
of its FLOPs at the chip's peak and its bytes at the HBM's rate, over ALL
device seconds under ``ssm.scan``. ``attention``: the causal pairs' QK^T and
AV at the chip's peak, over the Pallas kernels under ``attn.gqa``.

The executions in the slice are counted as a FRACTION: the slice's seconds
over the window's seconds a step (the profiler's own seconds taken out). A
step of half a second puts four in a 2 s slice, the first and last of them
cut by its edges; counting every execution that touches the slice (as the
readers of the 0.13 s DALL-E step do) would read a quarter too high here."""

from benchmarks import costs_lm, scope_reduce


def read(ctx, module, what):
    r = ctx.reduced
    mod = r.get("modules", {}).get(module) if r else None
    if not mod or not ctx.peaks or "tokens" not in ctx.facts or not r.get("window_s"):
        return None
    step_s = (ctx.facts["window_s"] - ctx.facts.get("trace_overhead_s", 0.0)) / ctx.facts["steps"]
    executions = r["window_s"] / step_s
    scope, kernels_only = ("ssm.scan", False) if what == "scan" else ("attn.gqa", True)
    seconds = scope_reduce.scope_seconds(
        scope_reduce.of_run(ctx), module, scopes=[scope], kernels_only=kernels_only
    )
    if not seconds:
        return None
    need = costs_lm.train_step(ctx.cfg, ctx.facts["rows"], ctx.facts["tokens"])
    if what == "scan":
        least = max(need["scan"] / ctx.peaks["bf16_flops_per_s"],
                    need["scan_bytes"] / ctx.peaks["hbm_bytes_per_s"])
    else:
        least = need["attention"] / ctx.peaks["bf16_flops_per_s"]
    return 100.0 * executions * least / ctx.chips / seconds
