"""A part's share of its roofline inside a routed-expert language-model train
step, in percent: the least time the chip could take for the work the part
REQUIRES in the step's executions of the traced slice, over the device time
spent on it.

``attention``: the causal pairs' QK^T over the query/key width and AV over
the value width, every latent-attention block (the MTP module's too), forward
and backward, at the chip's peak, over the Pallas kernels under ``attn.mla``.
``experts``: the LARGER of the routed pairs' FLOPs at the chip's peak and the
held experts' weights + rows at the HBM's rate (``costs_moe``; the pairs the
program counted, ``moe.pairs_here``), over ALL device seconds under
``moe.experts``, whatever implements it, and those of the custom calls named
like ``kernels``: the TPU compiler's expansion of ``jax.lax.ragged_dot``
(``ragged-dot-*``) carries no name stack, so the two grouped products, most
of the work, would otherwise be missing from under their scope.

The executions in the slice are counted as a FRACTION, as ``lm_roofline``
counts them: the slice's seconds over the window's seconds a step."""

import fnmatch

from benchmarks import costs_moe, scope_reduce


def read(ctx, module, what, kernels=()):
    r = ctx.reduced
    mod = r.get("modules", {}).get(module) if r else None
    if (not mod or not ctx.peaks or "moe_pairs_per_step" not in ctx.facts
            or not r.get("window_s")):
        return None
    step_s = (ctx.facts["window_s"] - ctx.facts.get("trace_overhead_s", 0.0)) / ctx.facts["steps"]
    executions = r["window_s"] / step_s
    scope, kernels_only = ("moe.experts", False) if what == "experts" else ("attn.mla", True)
    seconds = scope_reduce.scope_seconds(
        scope_reduce.of_run(ctx), module, scopes=[scope], kernels_only=kernels_only
    )
    seconds += sum(
        sec for path, kernel, sec in scope_reduce.of_run(ctx).get("by_scope", {}).get(module, [])
        if any(fnmatch.fnmatchcase(kernel, pattern) for pattern in kernels)
        and not scope_reduce.matches(path, [scope])
    )
    if not seconds:
        return None
    need = costs_moe.train_step(
        ctx.cfg, ctx.facts["rows"], ctx.facts["tokens"], ctx.facts["moe_pairs_per_step"]
    )
    if what == "experts":
        least = max(need["routed_experts"] / ctx.peaks["bf16_flops_per_s"],
                    need["routed_experts_bytes"] / ctx.peaks["hbm_bytes_per_s"])
    else:
        least = need["attention"] / ctx.peaks["bf16_flops_per_s"]
    return 100.0 * executions * least / ctx.chips / seconds
