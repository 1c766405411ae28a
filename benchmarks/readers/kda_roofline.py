"""A part's share of its roofline inside a KDA / latent-attention /
routed-expert language-model train step, in percent: the least time the chip
could take for the work the part REQUIRES in the step's executions of the
traced slice, over the device time spent on it (as ``gdn_roofline`` reads its
cell, from this family's keys: ``costs_kda``).

``kda``: the LARGER of the KDA rule's FLOPs at the chip's peak and its bytes
at the HBM's rate (counted at chunk 64 term by term whatever kernel runs it,
the KDA layers together, forward and backward), over the Pallas kernels under
``linattn.kda`` (``kda_chunk_tables``, ``kda_chunk_fwd``, ``kda_chunk_bwd``;
the rerun forward of ``remat`` counted in the time, not in the work).
``attention``: the latent attention's causal pairs, QK^T over 128 + 64
channels and AV over 128, every head, at the chip's peak, over the Pallas
kernels under ``attn.mla``. ``experts``: the LARGER of the routed pairs'
FLOPs at the chip's peak and the held experts' weights + rows at the HBM's
rate (the pairs the program counted), over ALL device seconds under
``moe.experts`` and those of the custom calls named like ``kernels`` (the TPU
compiler's ``ragged-dot-*`` carry no name stack).

The executions in the slice are counted as a FRACTION, as ``gdn_roofline``
counts them: the slice's seconds over the window's seconds a step."""

import fnmatch

from benchmarks import costs_kda, scope_reduce

PARTS = {
    # what: (scope, Pallas kernels only, the FLOPs' key, the bytes' key or None)
    "kda": ("linattn.kda", True, "kda", "kda_bytes"),
    "attention": ("attn.mla", True, "attention", None),
    "experts": ("moe.experts", False, "routed_experts", "routed_experts_bytes"),
}


def read(ctx, module, what, kernels=()):
    r = ctx.reduced
    mod = r.get("modules", {}).get(module) if r else None
    if (not mod or not ctx.peaks or "moe_pairs_per_step" not in ctx.facts
            or not r.get("window_s")):
        return None
    step_s = (ctx.facts["window_s"] - ctx.facts.get("trace_overhead_s", 0.0)) / ctx.facts["steps"]
    executions = r["window_s"] / step_s
    scope, kernels_only, flops, bytes_ = PARTS[what]
    reduced = scope_reduce.of_run(ctx)
    seconds = scope_reduce.scope_seconds(reduced, module, scopes=[scope], kernels_only=kernels_only)
    seconds += sum(
        sec for path, kernel, sec in reduced.get("by_scope", {}).get(module, [])
        if any(fnmatch.fnmatchcase(kernel, pattern) for pattern in kernels)
        and not scope_reduce.matches(path, [scope])
    )
    if not seconds:
        return None
    need = costs_kda.train_step(
        ctx.cfg, ctx.facts["rows"], ctx.facts["tokens"], ctx.facts["moe_pairs_per_step"]
    )
    least = need[flops] / ctx.peaks["bf16_flops_per_s"]
    if bytes_:
        least = max(least, need[bytes_] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * executions * least / ctx.chips / seconds
