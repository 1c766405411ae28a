"""The single registry of telemetry names (docs/DESIGN.md §9 and §11).

Every counter, gauge, histogram, span, and event name the package emits
is declared here, per kind — one dot-separated namespace per subsystem
(``serve.*`` engine, ``router.*`` front door, ``train.*`` trainer,
``data.*``/``webdata.*`` loaders, ``download.*`` fetcher,
``compile.*`` the compile ledger, ``telemetry.*`` the layer itself). The static checker
(``tools/lint.py``, finding DTL041) flags any literal passed to
``counters.inc`` / ``gauges.set`` / ``histograms.observe`` /
``TELEMETRY.span|begin|event`` — and to ``jax.named_scope`` or
``pl.pallas_call(name=...)`` (``DEVICE_SCOPES``, ``KERNEL_NAMES``: the
names the device trace carries) — that is not registered under the
matching kind, and DTL042 flags registered names missing from the
DESIGN.md §9 tables — so the registry, the code, and the operator docs
cannot drift.

This module is parsed by AST (never imported) by the linter, so keep the
sets as flat literals. It is also importable at runtime (host-side only,
like the rest of the observability layer) for tools and tests that want
to validate names programmatically.

Dynamic names: a handful of call sites build names from enum values
(``f"serve.{outcome.value}"``). Their full expansions are registered
here explicitly — the checker validates the f-string's literal head
against the registered names, so a renamed namespace still fails lint
while a new enum member only needs its expansion added here.

Span-duration histograms (``<span>_s``, auto-observed by
utils/telemetry.py) are derived — see ``SPAN_DURATION_HISTOGRAMS`` —
and are valid histogram names wherever tools read them.
"""

from __future__ import annotations

# --------------------------------------------------------------- spans

SPANS = frozenset({
    # serving engine (serving/engine.py)
    "serve.request",        # submit -> typed outcome (the lifecycle span)
    "serve.prefill",        # monolithic, or cross-iteration when chunked
    "serve.prefill_chunk",  # one per chunk, synced in-span
    "serve.slot_insert",
    "serve.decode_step",    # one per DISPATCHED decode step (split mode)
    "serve.iteration",      # one per fused ragged iteration (one dispatch)
    "serve.spec_verify",    # one per speculative iteration: draft+verify+
                            # accept dispatch and its synchronous readback
    # Engine.step cut into its phases: each a lexical child of serve.step,
    # so a phase's self time is its span minus its children, and under a
    # profiler capture each lands on the device events' clock
    # (utils/profiling.py; DESIGN.md §9 "Spans on the profiler's clock")
    "serve.step",           # one whole Engine.step() call
    "serve.step.sweep",     # deadlines, cancels
    "serve.step.admit",     # admission; serve.prefill* nest inside
    "serve.step.plan",      # dispatchable-slot selection, page growth
    "serve.step.fold_keys", # per-slot PRNG keys + token/key scatters
    "serve.step.dispatch",  # the one model-jit call of the iteration
    "serve.step.readback",  # the one host<-device sync
    "serve.step.release",   # one per released slot (pages + row reset)
    "serve.step.stages",    # post-decode pipeline; serve.stage.* nest inside
    "serve.step.publish",   # vitals, controller, gauges
    # post-decode pipeline (serving/postdecode.py): one span per batched
    # stage dispatch — the auto "<span>_s" histograms ARE the per-stage
    # latency distributions
    "serve.stage.vae_decode",
    "serve.stage.clip_rerank",
    # replicated front door (serving/router.py)
    "router.request",       # router submit -> typed outcome
    "router.step",          # one replica's Engine.step() (attr replica)
    # trainer (train_dalle.py)
    "train.step",           # dispatch -> verdict (device-inclusive)
    "train.data_wait",
    "train.ckpt_save",
})

# -------------------------------------------------------------- events

EVENTS = frozenset({
    # serving engine
    "serve.admit",
    "serve.first_token",
    "serve.evict",
    "serve.decode_stall",
    "serve.prefill_retry",
    "serve.prefix_hit",      # admission mapped >=1 cached prompt page
    "serve.snapshot_reject", # prefix snapshot failed verify-on-load
    # adaptive control loop (serving/control.py): one per controller
    # evaluation, carrying its input vitals and output knobs — the
    # audit/replay record (DESIGN.md §8.6)
    "serve.control.decision",
    # replicated front door
    "router.respawn",        # dead replica rebuilt and readmitted HEALTHY
    "router.respawn_fail",   # a respawn attempt failed (or exhausted)
    "router.shed",
    "router.drain",
    "router.drained",
    "router.failover",
    "router.failover_dispatch",
    "router.invariant_violation",
    "router.breaker_open",
    "router.readmit",
    # trainer
    "train.nan_skip",
    "train.nan_abort",
    "train.preempt_signal",
    # data loaders (data/webdata.py)
    "data.shard_open",
    "data.shard_quarantined",
    "data.shard_abort",
    # the compile ledger (utils/profiling.py:COMPILE_LEDGER): one per program
    # traced, lowered, and loaded or compiled (attrs kind, fun_name, seconds,
    # cache_hit): WHICH program compiled, and when, in a postmortem
    "compile.request",
})

# ------------------------------------------------------------ counters

COUNTERS = frozenset({
    # (token, expert) pairs routed to experts held here, all expert layers
    # of the last step (models/lm.py:CausalLM.routing_stats, train_lm.py every log step)
    "moe.pairs_here",
    # serving engine lifecycle
    "serve.submitted",
    "serve.admitted",
    "serve.completed",
    "serve.rejected",
    # typed-outcome tallies (f"serve.{outcome.value}" expansions)
    "serve.deadline_exceeded",
    "serve.cancelled",
    "serve.preempt_cap",
    "serve.prefill_failed",
    "serve.completed_tokens_only",
    "serve.completed_unranked",
    # typed-reject tallies (f"serve.rejected.{reason.value}" expansions)
    "serve.rejected.demand_exceeds_pool",
    "serve.rejected.queue_full",
    "serve.rejected.no_replica",
    # engine work/robustness tallies
    "serve.clamped",
    "serve.preempted",
    "serve.decode_steps",
    "serve.dispatches",     # model-jit dispatches (fused: 1/iteration)
    "serve.tokens_committed",  # tokens appended to a request's output
    "serve.prefill_chunks",
    "serve.prefill_retries",
    "serve.fault_request_cancel",
    "serve.fault_prefill_fail",
    "serve.fault_decode_stall",
    "serve.fault_page_exhaust",
    "serve.fault_prefix_hash_collide",
    "serve.fault_prefix_publish_fail",
    "serve.fault_spec_verify_abort",
    "serve.fault_journal_torn",
    "serve.fault_snapshot_corrupt",
    "serve.fault_vae_decode_fail",
    "serve.fault_rerank_fail",
    "serve.fault_stage_timeout",
    "serve.fault_control_stall",
    # adaptive control loop (serving/control.py; DESIGN.md §8.6)
    "serve.control.decisions",    # controller evaluations run
    "serve.control.adjustments",  # evaluations that changed >=1 knob
    "serve.control.stalls",       # evaluations degraded to static defaults
    # post-decode pipeline (serving/postdecode.py; DESIGN.md §8.5)
    "serve.stage.enqueued",        # requests entering the pipeline
    "serve.stage.vae_images",      # VAE_DECODE stage completions (images)
    "serve.stage.reranked",        # CLIP_RERANK stage completions (scores)
    "serve.stage.retries",         # failed stage attempts backed off
    "serve.stage.timeouts",        # dispatches past the stage time budget
    "serve.stage.degraded",        # typed-degraded completions (both kinds)
    "serve.stage.journal_records", # stage-boundary WAL records written
    # crash recovery (serving/journal.py + engine snapshot; §8.3)
    "serve.journal.appended",   # admitted-request WAL records written
    "serve.journal.replayed",   # unfinished requests resubmitted on restart
    "serve.journal.torn",       # torn tail records detected and dropped
    "serve.snapshot.saved",     # prefix-cache snapshots committed to disk
    "serve.snapshot.restored",  # snapshots verified and restored (warm start)
    "serve.snapshot.rejected",  # snapshots refused by verify-on-load
    # speculative decoding (serving/engine.py:_spec_iteration)
    "serve.spec.drafted",     # draft tokens proposed to verify rows
    "serve.spec.accepted",    # drafts committed by exact-match acceptance
    "serve.spec.rejected",    # drafts discarded (rolled back)
    "serve.spec.fallbacks",   # iterations degraded to plain decode
    # cross-request prefix cache (serving/prefix_cache.py)
    "serve.prefix.hits",          # probes matching >=1 page
    "serve.prefix.misses",        # probes matching nothing
    "serve.prefix.pages_hit",     # cached pages mapped/copied at admission
    "serve.prefix.pages_deduped", # publish-side pages already indexed
    "serve.prefix.cow_copies",    # shared terminal pages privatized
    "serve.prefix.published",     # pages newly committed to the index
    "serve.prefix.evictions",     # LRU index evictions (budget/arena)
    "serve.prefix.publish_skips", # fail-open publishes (arena/budget full)
    # replicated front door
    "router.submitted",
    "router.shed",
    "router.drains",
    "router.drained",
    "router.readmits",
    "router.breaker_opens",
    "router.replica_deaths",
    "router.failovers",
    "router.no_replica",
    "router.fault_replica_crash",
    "router.fault_replica_stall",
    "router.fault_health_flap",
    "router.fault_replica_respawn_fail",
    "router.respawns",          # dead replicas rebuilt and readmitted
    # typed-outcome tallies (f"router.{outcome.value}" expansions)
    "router.completed",
    "router.rejected",
    "router.deadline_exceeded",
    "router.cancelled",
    "router.preempt_cap",
    "router.prefill_failed",
    "router.completed_tokens_only",
    "router.completed_unranked",
    # trainer
    "train.nan_skips",
    # data paths (the webdata.* names data.* events carry; DESIGN.md §8)
    "webdata.decode_errors",
    "webdata.shard_open_retries",
    "webdata.shards_quarantined",
    "webdata.shards_opened",
    "webdata.quarantined_skips",
    "webdata.shard_aborts",
    "download.retries",
    "download.failures",
    # the compile ledger: backend compile requests, those the persistent
    # cache served, those compiled afresh (requests = hits + misses);
    # chip_smoke.py's children report them, benchmarks/readers/setup_ledger.py
    # reads the misses of set-up as setup.fresh_compiles
    "compile.requests",
    "compile.cache_hits",
    "compile.cache_misses",
    # the telemetry layer's self-accounting
    "telemetry.dropped",
    "telemetry.sink_errors",
})

# -------------------------------------------------------------- gauges

GAUGES = frozenset({
    # the fullest expert's pairs over the mean expert's, the worst layer's
    "moe.load_max_over_mean",
    # the last step's load-balance term E sum_e f_e P_e of a softmax-routed
    # model (models/lm.py:CausalLM.routing_stats; a uniform router reads k)
    "moe.aux_loss",
    "serve.pool_occupancy",
    "serve.running",
    "serve.prefilling",
    "serve.queued",
    "serve.stage.queued",        # requests parked in the post-decode pipeline
    "serve.prefix_hit_frac",     # hits / (hits + misses), lifetime
    "serve.prefix_pages",        # pages currently held by the index
    "serve.spec_accept_frac",    # accepted / drafted, lifetime
    # KV storage-format footprint (quantized-KV capacity lever, §6.1):
    # bytes of K/V storage (content + scale pools) per slot row, and
    # total physical pages per pool (slots + prefix arena) — int8 pools
    # roughly halve bytes_per_slot (tests/test_kv_quant.py: >= 1.8x)
    "serve.kv_quant.bytes_per_slot",
    "serve.kv_quant.pages",
    # engine vitals: sliding-window reductions over existing metrics
    # (utils/vitals.py; DESIGN.md §8.6) — the controller's inputs
    "serve.vitals.spec_accept_rate",    # windowed accepted/drafted
    "serve.vitals.prefix_hit_frac",     # windowed hits/(hits+misses)
    "serve.vitals.decode_gap_s",        # windowed max inter-iteration gap
    "serve.vitals.stage_lag",           # windowed mean post-decode depth
    "serve.vitals.deadline_miss_rate",  # windowed misses/terminations
    "serve.vitals.occupancy",           # windowed mean pool occupancy
    # effective knob levels the control loop last applied
    "serve.control.spec_k",
    "serve.control.budget",
    "serve.control.watermark",
    "serve.control.prefix_pages_target",
    "router.queued",
    "router.fleet_occupancy",
    "router.replicas_live",
    "router.replica_state_code",
    # seconds from the compile ledger's installation (start-up) to the first
    # finite verdict (parallel/loop.py): the operator's time-to-first-step
    "train.first_step_s",
})

# ---------------------------------------------------------- histograms

HISTOGRAMS = frozenset({
    "serve.queue_wait_s",
    "serve.ttft_s",
    "serve.request_latency_s",
    "serve.completed_latency_s",
    # request -> image end-to-end latency: submit to full-pipeline DONE
    # (image-bearing completions only; DESIGN.md §8.5)
    "serve.stage.request_to_image_s",
    "router.failover_latency_s",
    # TTFT split by prefix-cache hit class (serve.ttft_s still carries
    # every request; a cached-vs-cold comparison reads these)
    "serve.ttft_full_hit_s",
    "serve.ttft_partial_hit_s",
    "serve.ttft_cold_s",
    # tokens committed per speculative verify step (1 .. spec_k+1)
    "serve.spec_accepted_per_step",
    # replica kill -> healthy-again (respawn) MTTR, per replica label
    "serve.recovery_s",
    # backoff hints attached to load-typed rejections (queue_full /
    # no_replica): what the fleet told clients to wait — the traffic
    # sim's storm-amplification guard reads this distribution
    "router.retry_after_s",
    # the compile ledger, one observation per program (sum = seconds,
    # count = programs): its own Python trace, its conversion to MLIR, its
    # backend request (a cache hit included), the cache's read inside a hit.
    # The benchmark's setup.* metrics read the same events from the ledger
    "compile.trace_s",
    "compile.lower_s",
    "compile.backend_s",
    "compile.cache_load_s",
})

# ------------------------------------------- device scopes and kernels

# jax.named_scope names inside the jitted steps: what a reader of a
# profiler trace must know (the layer KIND, the stage), nothing about the
# implementation under it. They reach every device event through the HLO
# op_name metadata; the backward pass inherits them through JAX's
# transpose(jvp(...)) wrapping. The Flax module path stays beneath.
DEVICE_SCOPES = frozenset({
    # one per attention sublayer, by its attn_type (models/transformer.py)
    "attn.full",
    "attn.axial_row",
    "attn.axial_col",
    "attn.conv_like",
    "attn.sparse",
    "attn.mlp",
    # layer_types stacks (models/lm.py): the grouped-KV attention mixer, and
    # the whole state-space mixer with its convolution and its scan inside
    "attn.gqa",
    "ssm",
    "ssm.conv",
    "ssm.scan",
    # latent attention (ops/attention.py:LatentAttention), and the routed
    # expert feed-forward with its parts (ops/moe.py:RoutedExperts): scores
    # and choice; sort, group sizes and the gather of rows; the grouped
    # products over the held experts; the weighted scatter back and the sum
    # with the shared expert, which runs under its own scope
    "attn.mla",
    "moe",
    "moe.router",
    "moe.dispatch",
    "moe.experts",
    "moe.combine",
    "moe.shared",
    # the gated delta rule's mixer (ops/gdn.py:GatedDeltaNet) with its parts:
    # the projections in and out; the causal convolution and its silu; the
    # gates, the L2 norms and the chunked rule; the gated per-head norm. And
    # the gated softmax attention of the same family
    # (ops/attention.py:GatedAttention), read with every other attn.*
    "linattn",
    "linattn.proj",
    "linattn.conv",
    "linattn.delta",
    "linattn.norm",
    "attn.gated",
    # the KDA mixer (ops/kda.py:KimiDeltaAttention) runs under linattn with
    # linattn.proj, linattn.conv and linattn.norm as above, and two of its
    # own: the low-rank gates with the per-channel log-decay, and the L2
    # norms with the chunked rule of the per-channel decay
    "linattn.gate",
    "linattn.kda",
    # the sliding-window attention layer (ops/attention.py:GroupedKVAttention
    # with a window and rotary), read with every other attn.*; its route site
    # forward/swa records the window, tiles_visited (the band's tiles the
    # flash grid fetches) and causal_tiles (the causal triangle's). The
    # router of a block routed from its input runs before it, under moe and
    # moe.router (models/transformer.py:PreRoutedRMSNorm)
    "attn.swa",
    # the multi-token-prediction module's own projection, norms and shifted
    # embedding (models/lm.py); its block runs under attn.mla and moe
    "mtp",
    "ff",                   # one per feed-forward sublayer
    "embed",                # token + positional embeddings (models/dalle.py, lm.py)
    "head_loss",            # final norm, logits, the weighted cross-entropy
    "sample",               # top-k, gumbel, the draw (serving jits)
    "vae.encode",           # DiscreteVAE.get_codebook_indices
    # parallel/step.py: the clip lives in the optimizer chain it is handed
    "update",
    "update.optimizer",
    "update.nan_guard",
})

# pl.pallas_call(name=...): kernel and pass, ending in a letter (trace
# reducers strip trailing digits and dots)
KERNEL_NAMES = frozenset({
    "flash_fwd",            # ops/flash_attention.py, blocked
    "flash_bwd",            #   dq, dk and dv from one pass over the live tiles
    "flash_qkv_fwd",        #   packed whole-row (fused qkv) route
    "flash_qkv_bwd",
    "block_sparse_fwd",     # ops/block_sparse_attention.py pair grid
    "block_sparse_dq",
    "block_sparse_dkv",
    "ragged_paged_attend",  # ops/ragged_attention.py
    "decode_attend",        # ops/decode_attention.py
    "ssd_state_fwd",        # ops/ssm.py: what each chunk adds to the state
    "ssd_state_bwd",
    "ssd_chunk_fwd",        #   each chunk's output, decay matrices in VMEM
    "ssd_chunk_bwd",
    "ssm_conv_fwd",         #   the causal convolution, its bias and its silu
    "ssm_conv_bwd",
    "gdn_chunk_tables",     # ops/gdn.py: the gated delta rule; what a chunk computes without
    "gdn_chunk_fwd",        #   the state (the inverse), no sequential axis; the pass that
    "gdn_chunk_bwd",        #   carries the state (backward: its cotangent) in VMEM scratch
    "kda_chunk_tables",     # ops/kda.py: the delta rule with a per-channel decay; the same
    "kda_chunk_fwd",        #   three parts, the decayed products by sub-chunks, the
    "kda_chunk_bwd",        #   backward's cotangent of the log-decay per channel
})

# span durations are auto-observed as "<span>_s" (utils/telemetry.py);
# derived here so readers can validate against it
SPAN_DURATION_HISTOGRAMS = frozenset(s + "_s" for s in SPANS)

ALL_NAMES = (
    SPANS | EVENTS | COUNTERS | GAUGES | HISTOGRAMS
    | DEVICE_SCOPES | KERNEL_NAMES
)

_KINDS = {
    "span": SPANS,
    "event": EVENTS,
    "counter": COUNTERS,
    "gauge": GAUGES,
    "histogram": HISTOGRAMS | SPAN_DURATION_HISTOGRAMS,
    "scope": DEVICE_SCOPES,
    "kernel": KERNEL_NAMES,
}


def is_registered(name: str, kind: str = None) -> bool:
    """True iff ``name`` is registered (optionally under ``kind`` in
    span/event/counter/gauge/histogram/scope/kernel)."""
    if kind is None:
        return name in ALL_NAMES or name in SPAN_DURATION_HISTOGRAMS
    return name in _KINDS[kind]
