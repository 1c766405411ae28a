"""Continuous-batching serving engine over the paged KV cache.

The request lifecycle (docs/DESIGN.md, serving failure model):

    submit -> [rejected] | queued -> admitted (slot claimed)
           -> prefilling (budget-bounded chunks, chunked mode)
           -> decoding (one vector-position decode_step per iteration)
           -> completed | deadline_exceeded | cancelled
           -> (page exhaustion) evicted -> requeued (aged) -> ... -> preempt_cap

Composition of the PR-1/PR-2 primitives: the engine owns ONE batched paged
decode cache of ``max_batch`` fixed slots (every index leaf vectorized via
``set_decode_offsets``), prefills each admitted request alone (batch-1) and
lands it in a free slot with ``insert_decode_cache`` — the
admit-mid-flight shape of Ragged Paged Attention serving (PAPERS.md) — and
steps all active slots with a single jitted vector-position
``DALLE.decode_step``. Faults (``utils/faults.py`` sites ``page_exhaust``,
``prefill_fail``, ``decode_stall``, ``request_cancel``) make every failure
path deterministic on CPU.

Chunked prefill (``EngineConfig.prefill_chunk``): instead of one monolithic
``_prefill_jit`` call that stalls every active decode slot for the whole
prompt, an admitted request claims its slot in a PREFILLING state and its
prompt is processed in fixed-size chunks (``DALLE.prefill_chunk`` against
the request's own batch-1 paged cache), interleaved with decode iterations
under a per-iteration token budget (``scheduler.TokenBudget``: decode
tokens first, leftover to prefill chunks, head-of-line). Deadlines,
cancellation, and preempt-and-requeue therefore land BETWEEN chunks —
pages are freed the iteration the termination sweeps, not at the end of an
uninterruptible prefill — and the ``prefill_fail`` fault fires at chunk
granularity with retry resuming from the last completed chunk. The final
chunk samples the first image token exactly like the monolithic path, so
chunked and monolithic prefill are BIT-identical (the split chunker never
emits a batch-1 width-1 block — its projection matmuls would run as M=1
matvecs with ~1-ulp-different accumulation — merging such a tail into its
predecessor; the fused path pads rows to the iteration width instead and
needs no merge).

One-step-lookahead decode (``EngineConfig.decode_lookahead``, default on):
iteration N+1's decode step is dispatched BEFORE iteration N's sampled
tokens are read back — the next step's inputs are the previous step's
still-on-device samples plus host-known positions and (seed, position)
fold-in keys, so the host decision point stays but the device-to-host sync
hides behind the next dispatch. Completion is count-based (fixed
``max_new_tokens`` — the host knows a slot's budget without reading token
values), and deadline/cancel semantics are defined AT READBACK TIME: a
sample still in flight when its request terminates is simply dropped, and
replay-after-eviction stays bit-identical because tokens depend only on
the (seed, position) fold-in keys, never on when they were read.

Fused ragged iteration (``EngineConfig.fused_iteration``; ROADMAP 1,
"Ragged Paged Attention"): the split scheduler above still costs one jit
DISPATCH per prefill chunk plus one per decode step — per-iteration host
overhead that scales with the prefill mix, with a compile signature per
chunk class. Fused mode collapses a whole TokenBudget iteration into ONE
``_iteration_jit`` dispatch over ``DALLE.fused_step``: every cache row
gets a (start, length, final) descriptor padded to the fixed iteration
width (the chunk size), prefilling rows write their chunks DIRECTLY into
their row of the batched cache (no private batch-1 cache, no insert —
chunks are gathered in-trace from an on-device prompts buffer), and the
decode rows ride the same block. Raggedness is data, not shape: a
steady-state iteration has exactly one compile signature (DTL11x) and
one dispatch regardless of the mix, and grants up to ``max_batch``
prefill chunks IN PARALLEL where the split path ran them sequentially.
Scheduling semantics are preserved — decode-first budget with the
head-of-line floor (``TokenBudget.plan_iteration``), chunk-granular
``prefill_fail`` with resume-from-last-chunk, terminations between
iterations with same-iteration page release — and fused output is
BIT-identical to the split engine for f32 models on CPU — the parity
tier every smoke/test gate runs on (every row kind shares the
split paths' exact einsums; ops/ragged_attention.py).

Determinism contract (pinned by tests/test_serving.py +
tests/test_chunked_prefill.py): a request's token at internal position p
is sampled with ``fold_in(key(seed), p)``, and all decode math is
row-independent at fixed batch width (the jitted step always runs the
full ``max_batch``; inactive slots compute garbage that is discarded,
never read cross-row). Re-running an evicted request therefore reproduces
its tokens bit-identically — preemption costs work, never changes output.

Observability (docs/DESIGN.md §9): every request is one
``serve.request`` telemetry span — begun at submit, ended with its typed
outcome — with ``serve.prefill`` (cross-iteration in chunked mode, one
``serve.prefill_chunk`` child per chunk) / ``serve.slot_insert`` child
spans, admit/evict/stall/first_token events, and one ``serve.decode_step``
span per engine iteration (with lookahead on, its duration covers
dispatching step N plus reading back step N-1); queue-wait, TTFT, and
request-latency land in ``serve.*`` histograms. All of it is host-side
(``utils/telemetry.py`` never touches jax) and free when telemetry is
disabled.

Throughput note: this loop dispatches one jitted step per generated token
(a host decision point between steps is the price of admission control,
deadlines, and preemption; lookahead hides the readback half of that
price). Single-shot batch generation without a request lifecycle should
keep using ``models/sampling.py``'s fused scan — the CLI (generate.py)
routes through THIS engine so serving behavior is exercised end-to-end,
and falls back to the scan only for engine-unsupported models.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import shutil
from dataclasses import dataclass
from functools import partial
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.dalle import DALLE, top_k_filter
from ..models.sampling import (
    init_decode_cache,
    insert_decode_cache,
    set_decode_offsets,
)
from ..ops import kv_policy, paged_kv
from ..utils.faults import FAULTS
from ..utils.metrics import counters, gauges, histograms
from ..utils.resilience import (
    retry_after_hint, verify_dir_manifest, write_dir_manifest,
)
from ..utils.telemetry import TELEMETRY
from ..utils import vitals as vitals_mod
from .control import ControlConfig, Controller
from .postdecode import PostDecodePipeline, StageSpec
from .prefix_cache import (
    PrefixCache,
    chain_blocks,
    snapshot_records,
    verify_snapshot_records,
)
from .scheduler import Entry, PagePool, Scheduler, TokenBudget, pages_for
from .types import (
    Clock,
    EngineUnsupportedModel,
    Outcome,
    RejectReason,
    Request,
    RequestResult,
)


@dataclass(frozen=True)
class EngineConfig:
    """Operator knobs. Defaults are deliberately permissive (pool = full
    physical capacity, no degradation pressure, monolithic prefill) so a
    bare engine behaves like plain batched decode; tests and bench tighten
    them to create pressure."""

    max_batch: int = 4
    # logical page budget; None = full physical capacity (B * pages/slot)
    page_budget: Optional[int] = None
    queue_limit: int = 64
    filter_thres: float = 0.9
    temperature: float = 1.0
    # occupancy fraction above which newly admitted requests are clamped
    high_watermark: float = 0.85
    degraded_max_new_tokens: Optional[int] = None
    max_preemptions: int = 3
    preempt_priority_boost: int = 1
    prefill_attempts: int = 2
    stall_penalty_s: float = 1.0
    # chunked prefill: prompt tokens per chunk (>= 2 — a batch-1 width-1
    # chunk's projection matmuls are M=1 matvecs that accumulate ~1 ulp
    # differently from gemms; the split path merges 1-token tails, the
    # fused path pads rows instead). None = monolithic.
    prefill_chunk: Optional[int] = None
    # per-iteration token budget shared between decode tokens and prefill
    # chunk tokens (chunked mode only). None = max_batch + prefill_chunk,
    # i.e. every decode slot steps AND at most one chunk prefills per
    # iteration — the max decode stall is one chunk's latency.
    token_budget: Optional[int] = None
    # dispatch decode step N+1 before reading back step N's samples
    decode_lookahead: bool = True
    # execute each engine iteration — every prefill chunk plus the vector
    # decode step — as ONE fused ragged dispatch (_iteration_jit over
    # DALLE.fused_step; requires prefill_chunk). Raggedness is data, so a
    # steady-state iteration has exactly one compile signature and one
    # device dispatch regardless of the prefill/decode mix (ROADMAP 1,
    # "Ragged Paged Attention"). Off by default pending TPU measurement;
    # fused output is pinned bit-identical to the split path on the f32
    # CPU parity tier
    # (tests/test_ragged_attention.py, tools/serve_smoke.py --fused).
    fused_iteration: bool = False
    # speculative decoding through the fused iteration (ROADMAP 2): each
    # decoding slot self-drafts up to ``spec_k`` tokens per iteration (an
    # in-trace chain of single-token draft steps over the SAME checkpoint
    # — no second model) and the fused dispatch VERIFIES them as one
    # ragged descriptor row of width spec_k+1, committing the exact-match
    # accepted prefix plus one bonus target sample. Acceptance compares
    # the drafted token against the token the target model samples with
    # the same (seed, position) fold-in key, so speculative output is
    # BIT-IDENTICAL to non-speculative decode by construction — the
    # drafter only moves the accept rate, never the tokens. Rejected
    # positions roll back via descriptor anchoring: the next block
    # re-dispatches at the accepted frontier and simply overwrites them
    # (masked append / per-row limit + per-row cache-index rewind;
    # ops/attention.py:_decode_attend_paged, ops/layers.py:
    # PreShiftToken). Requires fused_iteration; forces synchronous
    # sample readback (the host needs the accepted count to build the
    # next descriptors — the sync is amortized over up to spec_k+1
    # tokens per step). Off by default pending TPU measurement.
    spec_decode: bool = False
    # drafted tokens per slot per iteration (>= 1); the verify row width
    # is spec_k + 1 and the fused block width max(prefill_chunk, spec_k+1)
    spec_k: int = 3
    # early-exit drafter depth: run only the first N layers for draft
    # steps (the truncated-depth self-draft). None = full depth — the
    # EXACT drafter, whose drafts reproduce the target samples bitwise on
    # the f32 parity tier (accept rate 1.0); useful as the correctness
    # harness and as the upper bound the truncated drafter trades away.
    spec_draft_depth: Optional[int] = None
    # cross-request prefix caching (serving/prefix_cache.py, ROADMAP 3):
    # content-addressed immutable prompt pages with refcounts. A probe at
    # admission maps every verified hit page into the slot's page table
    # read-only; a FULL-prefix hit skips prefill entirely (first token
    # sampled from the cached terminal logits) and a partial hit resumes
    # chunked prefill at the miss boundary (chunked modes only — a
    # monolithic engine serves full hits and falls back to cold
    # otherwise). Shared page content lives in ARENA rows appended to
    # the batched pools, reachable only through remapped table entries.
    prefix_cache: bool = False
    # arena capacity in pages; rounded UP to whole storage rows. None =
    # four prompts' worth (a few distinct templates stay resident).
    prefix_cache_pages: Optional[int] = None
    # paged-KV storage quantization (ops/kv_policy.py QUANTS): "int8"
    # stores the K/V page pools as int8 with parallel per-(token, head)
    # f32 scale pools, quantized at append and dequantized at read
    # in-kernel (Pallas ragged path) / in the shared jnp formula
    # (paged_kv.dequant) — roughly HALVING the engine's largest HBM
    # tenant (tests/test_kv_quant.py holds the bytes per slot to >= 1.8x).
    # What that buys in slots, arena working set and decode speed is a
    # pre-ledger expectation, not in PERF_LEDGER.jsonl: no cell serves yet.
    # Parity tiers: quantized-vs-quantized holds the standing BITWISE
    # contract (cold/warm hit, split/fused, preempt replay, spec
    # decode); quantized-vs-f32 is the pinned token-agreement threshold
    # (kv_policy.KV_QUANT_TOKEN_AGREEMENT_MIN), never a bitwise claim.
    # None defers to DALLE_TPU_KV_QUANT / the "none" default; an
    # invalid value fails typed at Engine construction.
    kv_quant: Optional[str] = None
    # ---- observability & adaptive control (docs/DESIGN.md §8.6) ----
    # engine vitals: sliding-window reductions over existing metrics,
    # published as serve.vitals.* gauges each iteration (utils/vitals.py)
    vitals: bool = False
    # window length, in worked iterations
    vitals_window: int = 32
    # deterministic adaptive control loop (serving/control.py): maps
    # vitals windows to effective knobs between iterations, through
    # data-only channels that cannot recompile. Implies vitals.
    controller: bool = False
    # controller thresholds; None = ControlConfig() defaults
    control: Optional[ControlConfig] = None


_PREFILL = "prefill"
_DECODE = "decode"

# PagePool holder id for pages owned by the prefix index (the logical
# budget treats cached pages like any resident pages: droppable, but
# accounted — the index is its own eviction tier)
PREFIX_HOLDER = "__prefix__"


class _AdmitHit:
    """One admission's usable prefix-cache probe result: the verified
    chain nodes the slot will consume (references already ACQUIRED —
    every non-admission path must release), whether they cover the full
    prompt, and how many pages the slot maps SHARED (demand shrinks by
    exactly these; split-mode partial hits copy instead, so they share
    none)."""

    def __init__(self, nodes, full: bool = False, shared: int = 0):
        self.nodes = nodes
        self.full = full
        self.shared = shared

    @property
    def n_pages(self) -> int:
        return len(self.nodes)

    @property
    def kind(self):
        if not self.nodes:
            return None
        return "full" if self.full else "partial"

    @property
    def coverage(self) -> int:
        return self.nodes[-1].coverage if self.nodes else 0


_NO_HIT = _AdmitHit(nodes=())


class _Slot:
    """A running request bound to one cache row. Phase ``prefill``: the
    request owns the slot index and its prompt pages while its chunks run
    against a private batch-1 cache (``cache1``; ``filled`` = positions
    written so far). Phase ``decode``: the cache row is live in the batched
    cache and the slot participates in the vector decode step."""

    def __init__(self, entry: Entry, index: int, first_token: int,
                 pos: int, admit_seq: int, phase: str = _DECODE):
        self.entry = entry
        self.index = index
        self.tok = first_token   # last sampled token (not yet cached)
        self.pos = pos           # its internal position
        self.admit_seq = admit_seq
        self.phase = phase
        self.cancelled = False
        # chunked-prefill state
        self.cache1 = None       # batch-1 cache being filled chunk by chunk
        self.internal = None     # (1, T) remapped prompt ids on device
        self.filled = 0          # prompt positions written so far
        self.prefill_span: Optional[int] = None
        # True iff this slot's next input token is still on device in the
        # engine's pending (in-flight) sample array — the lookahead seam
        self.tok_on_device = False
        # prefix-cache state (serving/prefix_cache.py): index nodes this
        # slot maps read-only (refcounts held until release), ring-seam
        # snapshots captured at page boundaries during prefill (keyed by
        # boundary position; published with the pages at completion), and
        # the terminal image-head logits for the full-prefix entry
        self.shared_nodes: list = []
        self.boundary_rings: dict = {}
        self.final_logits = None
        # boundary below which snapshots are pointless (already indexed)
        self.snap_from = 0


@partial(jax.jit, static_argnums=(0, 5), donate_argnums=(2,))
def _prefill_jit(dalle: DALLE, params, cache, internal_text, key, k: int,
                 temperature):
    """One parallel prefill over the full text prompt + the first image
    token sampled from its logits. ``image_only`` computes just the
    image-vocab head columns — bit-equal to slicing the full head at
    ``[ext:]`` (models/dalle.py:_head_image) but without dequantizing the
    text-vocab columns or running the full-vocab mask chain; with the
    full-vocab-derived ``k`` the top-k threshold matches the reference's
    fractional-k semantics exactly (models/sampling.py).

    The cache argument is DONATED (as in every serving jit here): the
    output cache aliases the input's buffers in HBM instead of
    double-buffering the paged KV pool for the duration of the call.
    Callers must treat the passed-in cache as consumed — the engine hands
    this jit a private copy of its pristine template
    (``_fresh_prefill_cache``), never ``_fresh1`` itself. The aliasing is
    a lint contract: ``tools/lint.py --trace`` DTL12x checks the lowered
    computation, not just this decorator."""
    img, mutated = dalle.apply(
        {"params": params, "cache": cache},
        internal_text,
        image_only=True,
        method=DALLE.prefill_step,
        mutable=["cache"],
    )
    with jax.named_scope("sample"):
        tok = jax.random.categorical(
            key, top_k_filter(img, k=k) / temperature, axis=-1
        )
    # the raw last-position logits ride along for the prefix cache's
    # terminal payload (a full-prefix hit re-samples from EXACTLY these
    # values with its own key); unread when prefix caching is off
    return mutated["cache"], tok, img


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _prefill_chunk_jit(dalle: DALLE, params, cache, chunk, start):
    """One intermediate prefill chunk: text positions [start, start+c)
    written into the batch-1 cache; no logits (the head is skipped).
    The cache is donated — chunk N+1's cache lives in chunk N's buffers,
    so a chunked prefill holds ONE batch-1 cache in HBM, not two."""
    _, mutated = dalle.apply(
        {"params": params, "cache": cache},
        chunk, start,
        return_logits=False,
        method=DALLE.prefill_chunk,
        mutable=["cache"],
    )
    return mutated["cache"]


@partial(jax.jit, static_argnums=(0, 5), donate_argnums=(2,))
def _prefill_last_jit(dalle: DALLE, params, cache, chunk, start, k: int,
                      key, temperature):
    """The FINAL prefill chunk + the first image token sampled from its
    logits — the exact head + sampling ops of ``_prefill_jit`` (same
    image-only head columns, same full-vocab-derived k), so chunked and
    monolithic prefill draw the same token from the same
    ``fold_in(key(seed), T)`` key. Cache donated, like every serving jit."""
    img, mutated = dalle.apply(
        {"params": params, "cache": cache},
        chunk, start,
        image_only=True,
        method=DALLE.prefill_chunk,
        mutable=["cache"],
    )
    with jax.named_scope("sample"):
        tok = jax.random.categorical(
            key, top_k_filter(img, k=k) / temperature, axis=-1
        )
    # raw logits for the prefix cache's terminal payload (see _prefill_jit)
    return mutated["cache"], tok, img


@partial(jax.jit, static_argnums=(0, 6), donate_argnums=(2,))
def _decode_jit(dalle: DALLE, params, cache, tok, pos, keys, k: int,
                temperature):
    """One vector-position decode step over every slot; per-slot PRNG keys
    (vmapped categorical) keep each row's sample stream independent of the
    batch composition around it. The batched cache is donated: the step's
    output cache aliases the input's buffers, so steady-state decode holds
    ONE copy of the paged KV pool in HBM instead of double-buffering it
    every token (the engine reassigns ``self.cache`` from the return value
    and never touches the consumed input again)."""
    logits, mutated = dalle.apply(
        {"params": params, "cache": cache},
        tok, pos,
        image_only=True,
        method=DALLE.decode_step,
        mutable=["cache"],
    )
    with jax.named_scope("sample"):
        filtered = top_k_filter(logits, k=k) / temperature
        samples = jax.vmap(jax.random.categorical)(keys, filtered)
    return mutated["cache"], samples.astype(jnp.int32)


@partial(jax.jit, static_argnums=(0, 9, 10, 12), donate_argnums=(2,))
def _iteration_jit(dalle: DALLE, params, cache, prompts, tok, start, length,
                   final, keys, width: int, k: int, temperature,
                   any_final: bool = False):
    """One ENTIRE TokenBudget iteration as a single device dispatch: every
    granted prefill chunk plus the vector-position decode step run as one
    ragged (B, width) block through ``DALLE.fused_step`` (descriptors —
    start/length/final — are DATA, so every prefill/decode mix shares
    this one steady-state compile signature; DTL11x pins it to exactly
    one). Per-row token sources are resolved IN-TRACE: a decode row
    (start >= T, i.e. at an image position) consumes ``tok`` — the
    previous iteration's still-on-device samples where lookahead applies
    — while a prefill row gathers its chunk from its row of the
    ``prompts`` buffer, so the host never touches token values on the
    steady path. ``any_final`` (static, host-known scheduling fact) is
    the ONE extra signature class: iterations containing a FINAL chunk
    additionally run the per-row split-parity heads
    (``DALLE.fused_step`` ``rowwise_head``) — both classes compile at
    warmup, in-trace recompiles stay zero, and the steady mixed
    prefill+decode iteration remains exactly one signature. Sampling is
    the split paths' exact op sequence
    (image-only top-k + per-row fold-in keys, vmapped categorical); rows
    whose sample the host will not consume (idle, intermediate chunks)
    burn a filler key and are discarded by kind at readback. The batched
    cache is DONATED like every serving jit (PR 8 discipline): the
    iteration's output cache aliases its input's buffers, audited by
    DTL12x on the lowered computation."""
    B, T = prompts.shape
    j = jnp.arange(width, dtype=jnp.int32)[None]
    chunk = jnp.take_along_axis(
        prompts, jnp.minimum(start[:, None] + j, T - 1), axis=1
    )
    dec_tok = jnp.pad(tok[:, None], ((0, 0), (0, width - 1)))
    tokens = jnp.where((start >= T)[:, None], dec_tok, chunk)
    logits, mutated = dalle.apply(
        {"params": params, "cache": cache},
        tokens, start, length, final,
        rowwise_head=any_final,
        method=DALLE.fused_step,
        mutable=["cache"],
    )
    with jax.named_scope("sample"):
        filtered = top_k_filter(logits, k=k) / temperature
        samples = jax.vmap(jax.random.categorical)(keys, filtered)
    if any_final:
        # final-chunk iterations (already their own warm signature class)
        # also surface the raw per-row logits: the prefix cache's terminal
        # payload for rows completing their prefill this dispatch
        return mutated["cache"], samples.astype(jnp.int32), logits
    return mutated["cache"], samples.astype(jnp.int32), None


def spec_model(dalle: DALLE, spec_k: int) -> DALLE:
    """The speculative-serving clone of a checkpointed model: identical
    parameters, token-shift ring widened by ``spec_k`` rows — the
    rollback slack that lets a rejected verify suffix be rewound by
    descriptor arithmetic (ops/layers.py:PreShiftToken.pad). ONE
    definition shared by ``Engine.__init__`` and the trace-audit
    registry (tools/lint/trace/registry.py) so the committed contract's
    cache avals derive from the code, not a transcription of it."""
    if not dalle.shift_tokens:
        return dalle
    return dalle.clone(shift_pad=spec_k)


def fused_width(config: EngineConfig) -> int:
    """The fused iteration's static block width: the prefill chunk, or —
    with speculation on — wide enough to carry a full verify row
    (spec_k drafts plus the committed input token). Shared with the
    trace-audit registry for the same no-transcription reason as
    ``spec_model``."""
    if config.spec_decode:
        return max(config.prefill_chunk, config.spec_k + 1)
    return config.prefill_chunk


@partial(jax.jit, static_argnums=(0, 9, 10, 12, 13, 14),
         donate_argnums=(2,))
def _spec_iteration_jit(dalle: DALLE, params, cache, prompts, tok, start,
                        length, final, base_keys, width: int, k: int,
                        temperature, any_final: bool, spec_k: int,
                        draft_depth: Optional[int]):
    """One SPECULATIVE TokenBudget iteration as a single device dispatch
    (ROADMAP 2): draft, verify, and accept without the host ever touching
    a token value mid-step.

    Descriptor semantics extend ``_iteration_jit``'s: a prefill-chunk row
    is unchanged; a decode row becomes a VERIFY row of ``length`` =
    1 + (drafted tokens), its columns carrying [tok, d_1, .., d_γ] at
    positions start .. start+γ — the exact ragged (start, length, final)
    shape the fused kernel already executes for prefill chunks, which is
    the whole point: verifying k tokens streams the weights ONCE, like
    decoding one.

    In-trace stages:

    1. DRAFT — ``spec_k`` sequential width-1 ``fused_step`` calls through
       the first ``draft_depth`` layers (None = full depth, the exact
       drafter), each sampling d_i with the SAME fold_in(seed, pos+i+1)
       key the verify column will use. The draft threads a FUNCTIONAL
       cache chain that is DISCARDED — the verify below starts from the
       original cache value, so draft numerics can never leak into
       committed state. (The chain's K/V writes cost XLA one copy of the
       drafted layers' pools per iteration; acceptable on the CPU parity
       tier, to be re-measured on TPU where a stash-based drafter is the
       known upgrade.)

    2. VERIFY — one ``fused_step`` over the full mixed block with
       ``all_logits=True``: per-column image logits for every row, the
       per-row M=1 split-parity head overlaid at final-chunk rows.

    3. ACCEPT — sample every column with its own key (one flat vmapped
       categorical — per-cell bitwise equal to the plain path's per-row
       vmap), then take the longest prefix where draft == target sample
       (exact-match acceptance: temperature/top-k sampling is
       deterministic given the (seed, position) key, so this commits
       BIT-IDENTICALLY what sequential decode would have produced —
       between 1 and spec_k+1 tokens per row per step). ``accepted`` is
       returned per row; the host advances positions by it, and the next
       dispatch's descriptors land on the accepted frontier, overwriting
       the rejected suffix (K/V) while the anchored shift-ring reads skip
       it (PreShiftToken delta) — the rollback is descriptor arithmetic,
       not a device round trip.

    The cache is DONATED like every serving jit. Static ``any_final``
    stays the one extra warm signature class (DTL11x: steady + final,
    exactly two)."""
    B, T = prompts.shape
    j = jnp.arange(width, dtype=jnp.int32)[None]
    chunk = jnp.take_along_axis(
        prompts, jnp.minimum(start[:, None] + j, T - 1), axis=1
    )
    # the (B, W) sampling-key matrix, derived IN-TRACE from the per-slot
    # base keys (``Engine._base_keys``, set once per admission): column
    # j of row b is fold_in(key(seed_b), start_b + j + 1) — exactly the
    # key sequential decode uses at that position (a verify row's column
    # j predicts position start+j+1) AND, at a final chunk's last valid
    # column, fold_in(key(seed), T) (the final chunk ends exactly at T:
    # Engine._next_chunk_fused). One fused derivation instead of a
    # per-column host key loop; unused columns fold garbage positions
    # whose samples the acceptance mask and the caller discard.
    keys = jax.vmap(
        lambda kb, p: jax.vmap(lambda q: jax.random.fold_in(kb, q))(p)
    )(base_keys, start[:, None] + j + 1)
    is_verify = start >= T  # image positions = decode/verify rows
    no_final = jnp.zeros((B,), bool)
    d_len = jnp.where(is_verify, 1, 0).astype(jnp.int32)
    draft_cache = cache
    cur = tok
    drafts = []
    for i in range(spec_k):
        dlog, dmut = dalle.apply(
            {"params": params, "cache": draft_cache},
            cur[:, None], start + i, d_len, no_final,
            rowwise_head=False, depth_limit=draft_depth,
            method=DALLE.fused_step, mutable=["cache"],
        )
        draft_cache = dmut["cache"]
        with jax.named_scope("sample"):
            dfilt = top_k_filter(dlog, k=k) / temperature
            cur = jax.vmap(jax.random.categorical)(
                keys[:, i], dfilt
            ).astype(jnp.int32)
        drafts.append(cur)
    del draft_cache  # the chain is scratch; verify starts from `cache`

    dec_row = jnp.concatenate(
        [tok[:, None]] + [d[:, None] for d in drafts], axis=1
    )
    dec_row = jnp.pad(dec_row, ((0, 0), (0, width - 1 - spec_k)))
    tokens = jnp.where(is_verify[:, None], dec_row, chunk)
    logits, mutated = dalle.apply(
        {"params": params, "cache": cache},
        tokens, start, length, final,
        rowwise_head=any_final, all_logits=True,
        method=DALLE.fused_step, mutable=["cache"],
    )  # (B, width, V_img)
    with jax.named_scope("sample"):
        filtered = top_k_filter(logits, k=k) / temperature
        samples = jax.vmap(jax.random.categorical)(
            keys.reshape(B * width), filtered.reshape(B * width, -1)
        ).reshape(B, width).astype(jnp.int32)
    if spec_k:
        dmat = jnp.concatenate([d[:, None] for d in drafts], axis=1)
        valid = (
            jnp.arange(spec_k, dtype=jnp.int32)[None] < length[:, None] - 1
        )
        matched = valid & (dmat == samples[:, :spec_k])
        m = jnp.cumprod(matched.astype(jnp.int32), axis=1).sum(axis=1)
    else:
        m = jnp.zeros((B,), jnp.int32)
    accepted = jnp.where(is_verify & (length > 0), m + 1, 0)
    if any_final:
        last = jnp.clip(length - 1, 0, width - 1)
        flogits = jnp.take_along_axis(
            logits, last[:, None, None], axis=1
        )[:, 0]
        return mutated["cache"], samples, accepted, flogits
    return mutated["cache"], samples, accepted, None


@partial(jax.jit, static_argnums=(2,))
def _sample_cached_jit(logits, key, k: int, temperature):
    """Sample a first image token from CACHED terminal prefill logits —
    the full-prefix-hit path runs no prefill at all, so the exact
    top-k/temperature/categorical op sequence of ``_prefill_jit``'s tail
    re-runs here against the published logits values with the request's
    own ``fold_in(key(seed), T)`` key. Elementwise + sort ops on
    identical inputs, so the sampled token is bit-identical to the cold
    run's on every platform (no matmul reassociation in this program)."""
    with jax.named_scope("sample"):
        return jax.random.categorical(
            key, top_k_filter(logits, k=k) / temperature, axis=-1
        )


@partial(jax.jit, donate_argnums=(0,))
def _copy_pages_jit(cache, src, dst, valid):
    """Publish / copy-on-write page copies as ONE donated fixed-shape
    dispatch (the PR 10 follow-on): the eager pool-sized ``.at[].set``
    rewrites that used to run per publish/map now ride a single jit
    whose src/dst/valid vectors are PADDED to the engine's fixed copy
    width (``Engine._padded_copy``), so every call shares one compile
    signature and stays inside the zero-in-trace-compile contract
    (DTL11x; registry entry ``serving.page_copy``). Padding rows carry
    an out-of-range dst id and are DROPPED by the scatter
    (``paged_kv.copy_pages_across`` mode="drop"). The cache is donated —
    the copy happens in the pool's own buffers, never double-buffering
    it on the host path."""
    def fn(path, x):
        if getattr(path[-1], "key", None) in paged_kv.POOL_LEAF_KEYS:
            return paged_kv.copy_pages(x, src, dst, valid)
        return x

    return jax.tree_util.tree_map_with_path(fn, cache)


@partial(jax.jit, donate_argnums=(0,))
def _copy_pages_across_jit(dst_cache, src_cache, src, dst, valid):
    """The cross-pool variant of ``_copy_pages_jit``: the SPLIT engine's
    partial-hit restore copies shared arena pages out of the batched
    pools into a private batch-1 prefill cache (whose chunk jits cannot
    reach the batched storage). Same fixed padded shape, destination
    cache donated; registry entry ``serving.page_copy_across``."""
    def fn(path, x1, xb):
        if getattr(path[-1], "key", None) in paged_kv.POOL_LEAF_KEYS:
            return paged_kv.copy_pages_across(x1, xb, src, dst, valid)
        return x1

    return jax.tree_util.tree_map_with_path(fn, dst_cache, src_cache)


@partial(jax.jit, donate_argnums=(0,))
def _map_prefix_jit(cache, idx, ids, n_ids, offset, ring):
    """Prefix-hit publish/map as ONE donated fixed-shape dispatch (the
    PR 10 follow-on finishing what ``_copy_pages_jit`` started): the
    eager per-admission ``.at[].set`` leaf rewrites (page-table row,
    cache/shift indices, shift-ring seam) now ride a single jit shared by
    all three admission shapes — fused partial-hit map, split-mode
    batch-1 seeding (``n_ids == 0``: the page-table update is a no-op),
    and the full-hit map — so the zero-in-trace-compile contract holds by
    construction (DTL11x; registry entries ``serving.prefix_map`` /
    ``serving.prefix_map_quant``). ``ids`` is padded to the fixed
    page-table row width with ``n_ids`` real entries; ``ring`` is the
    terminal node's keystr-keyed shift-ring dict, traced as a pytree.
    The cache is donated, and every output leaf is a DISTINCT buffer by
    XLA's output-buffer rules — which is also what makes the split-mode
    seeding safe once the chunk jits donate the batch-1 cache (the old
    eager path had to build per-leaf fresh index arrays by hand)."""

    def fn(path, x):
        key = getattr(path[-1], "key", None)
        if key == "page_table":
            row = x[idx]
            pos = jnp.arange(row.shape[-1], dtype=jnp.int32)
            return x.at[idx].set(
                jnp.where(pos < n_ids, ids[: row.shape[-1]], row)
            )
        if key in ("cache_index", "shift_index"):
            return x.at[idx].set(jnp.asarray(offset, x.dtype))
        if key == "shift_hist":
            return x.at[idx].set(
                ring[jax.tree_util.keystr(path)].astype(x.dtype)
            )
        return x

    return jax.tree_util.tree_map_with_path(fn, cache)


def _append_arena_rows(cache, rows: int):
    """Append ``rows`` zeroed storage rows to every K/V page-pool leaf —
    the prefix cache's arena. Tables, indices, and shift rings stay at
    the slot batch width: arena pages hold CONTENT only, reachable
    through remapped (global-id) table entries, never dispatched as
    query rows. Pure; the trace registry reuses it under eval_shape so
    the committed contract sees the same avals the engine runs."""
    if rows <= 0:
        return cache

    def fn(path, x):
        if getattr(path[-1], "key", None) in paged_kv.POOL_LEAF_KEYS:
            return jnp.pad(x, [(0, rows)] + [(0, 0)] * (x.ndim - 1))
        return x

    return jax.tree_util.tree_map_with_path(fn, cache)


def arena_rows_for(prefix_cache_pages: Optional[int], prompt_pages: int,
                   n_pages_slot: int) -> int:
    """Arena sizing shared by ``Engine.__init__`` and the trace-audit
    registry (tools/lint/trace/registry.py) — the ONE definition of how
    many whole storage rows back a requested page budget, so the
    committed contract derives its cache avals from the code, not from
    a transcription of it. ``None`` requests the default: four prompts'
    worth (a few distinct templates stay resident)."""
    want = (
        prefix_cache_pages if prefix_cache_pages is not None
        else 4 * prompt_pages
    )
    return -(-max(1, want) // n_pages_slot)


SNAPSHOT_INDEX = "index.json"
SNAPSHOT_ARRAYS = "arrays.npz"


def _snap_pack(arr) -> Tuple[np.ndarray, str]:
    """Persist-safe byte view of one device/host array: npz cannot carry
    extension dtypes (bf16) natively, so every persisted array is stored
    as uint8 bytes plus its dtype name — bit-exact round trip for every
    dtype the cache can hold."""
    a = np.ascontiguousarray(np.asarray(arr))
    return a.view(np.uint8), a.dtype.name


def _snap_unpack(packed: np.ndarray, dtype_name: str) -> jnp.ndarray:
    return jnp.asarray(
        np.ascontiguousarray(packed).view(np.dtype(dtype_name))
    )


def _node_content_digest(arrays: Dict[str, np.ndarray], i: int,
                         n_leaves: int, n_ring: int, rec: dict) -> str:
    """sha256 over node ``i``'s PERSISTED payload bytes — its page row
    in every pool leaf (K/V content AND, under kv_quant, the scale
    pools), its ring-seam arrays, and its terminal logits, all in the
    packed (uint8) representation that lands on disk. The chain digest
    covers the node's MEANING (tokens, under the format-salted root);
    this covers its stored REPRESENTATION, so a re-manifested tamper of
    ``arrays.npz`` — page bytes or scales flipped, manifest regenerated
    — fails verify-on-load typed instead of serving forged K/V warm.
    Computed by save and recomputed by load from the same packed
    arrays."""
    hasher = hashlib.sha256()
    for j in range(n_leaves):
        hasher.update(np.ascontiguousarray(arrays[f"pages_l{j}"][i]))
    if rec.get("has_ring"):
        for k in range(n_ring):
            hasher.update(np.ascontiguousarray(arrays[f"ring{i}_{k}"]))
    if rec.get("has_logits"):
        hasher.update(np.ascontiguousarray(arrays[f"logits{i}"]))
    return hasher.hexdigest()


def _ring_snapshot(cache, row: int) -> dict:
    """The shift-ring seam of one cache row: every layer's ``shift_hist``
    slice, keyed by tree path (stable across batch widths, so a snapshot
    from a batch-1 prefill cache restores into the batched cache and
    vice versa). Lazy device slices — nothing syncs."""
    out = {}
    for path, x in jax.tree_util.tree_leaves_with_path(cache):
        if getattr(path[-1], "key", None) == "shift_hist":
            out[jax.tree_util.keystr(path)] = x[row]
    return out


class Engine:
    """See module docstring. Host-side state machine + one device cache."""

    def __init__(self, dalle: DALLE, params, config: EngineConfig = EngineConfig(),
                 clock: Optional[Clock] = None,
                 metric_labels: Optional[dict] = None,
                 fleet_occupancy=None,
                 stages: Optional[StageSpec] = None):
        attn_types = tuple(dalle.attn_types or ("full",))
        if "mlp" in attn_types:
            raise EngineUnsupportedModel(
                "gMLP ('mlp') layers cannot run under the serving engine: "
                "the spatial-gate history indexes by a scalar absolute "
                "position, so per-slot ragged offsets cannot be expressed"
            )
        if config.prefill_chunk is not None and config.prefill_chunk < 2:
            raise ValueError(
                f"prefill_chunk must be >= 2 (a batch-1 width-1 chunk runs "
                f"its projection matmuls as M=1 matvecs that accumulate "
                f"~1 ulp differently from gemms, breaking split-path "
                f"bit-parity with monolithic prefill; the fused path pads "
                f"rows to the iteration width instead), got "
                f"{config.prefill_chunk}"
            )
        self.spec = config.spec_decode
        if self.spec:
            if not config.fused_iteration:
                raise ValueError(
                    "spec_decode runs THROUGH the fused iteration (a verify "
                    "step is a ragged descriptor row of the single "
                    "dispatch); enable fused_iteration"
                )
            if config.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {config.spec_k}")
            if config.spec_draft_depth is not None and not (
                1 <= config.spec_draft_depth <= dalle.depth
            ):
                raise ValueError(
                    f"spec_draft_depth must be in [1, {dalle.depth}] or "
                    f"None (full depth), got {config.spec_draft_depth}"
                )
            # widen the token-shift ring by spec_k rows — the rollback
            # slack (cache-shape only; parameters untouched)
            dalle = spec_model(dalle, config.spec_k)
        self.dalle = dalle
        self.params = params
        self.config = config
        self.clock = clock or Clock()
        # label-bound metric registries: a router passes
        # ``metric_labels={"replica": "<id>"}`` so every counter/gauge/
        # histogram this engine writes becomes a per-replica series
        # (``serve.occupancy{replica="1"}``); unlabeled engines get the
        # process-wide registries back unchanged (child(None) is identity)
        self.counters = counters.child(metric_labels)
        self.gauges = gauges.child(metric_labels)
        self.histograms = histograms.child(metric_labels)
        # injectable occupancy for the watermark clamp: a router passes a
        # FLEET-aggregate occupancy so degradation responds to pressure
        # anywhere in the fleet (a dead sibling's load lands here), not
        # just this engine's own pool
        self._fleet_occupancy = fleet_occupancy

        self.page = kv_policy.page_size()
        self.T = dalle.text_len_internal
        self.n_pages_slot = pages_for(self.T + dalle.image_seq_len, self.page)
        # paged-KV storage quantization, resolved ONCE and pinned for
        # every cache this engine builds (the batched cache, the prefill
        # template, and therefore every jit signature) — an invalid
        # config value fails typed here, and ambient env drift after
        # construction cannot desynchronize the engine's caches
        self.kv_quant = kv_policy.resolve_quant(config.kv_quant)
        # prefix-cache arena sizing: whole storage ROWS appended to the
        # batched pools (global ids keep the identity stride == the
        # table width; ops/paged_kv.py), so requested pages round up
        self._arena_rows = 0
        arena_pages = 0
        if config.prefix_cache:
            self._arena_rows = arena_rows_for(
                config.prefix_cache_pages,
                pages_for(self.T, self.page),
                self.n_pages_slot,
            )
            arena_pages = self._arena_rows * self.n_pages_slot
        budget = (
            config.page_budget
            if config.page_budget is not None
            else config.max_batch * self.n_pages_slot + arena_pages
        )
        self.pool = PagePool(budget)
        self.sched = Scheduler(
            config.queue_limit,
            preempt_priority_boost=config.preempt_priority_boost,
        )
        if config.prefill_chunk is not None:
            tokens = (
                config.token_budget
                if config.token_budget is not None
                else config.max_batch + config.prefill_chunk
            )
            self.budget: Optional[TokenBudget] = TokenBudget(
                budget=tokens, chunk=config.prefill_chunk
            )
        else:
            self.budget = None

        B = config.max_batch
        # fixed-slot batched cache; every index leaf vectorized once
        self.cache = set_decode_offsets(
            init_decode_cache(
                dalle, params, B, cache_format="paged",
                kv_quant=self.kv_quant,
            ),
            jnp.zeros((B,), jnp.int32),
        )
        # prefix cache: arena rows appended to the POOL leaves only (page
        # tables/indices stay B-wide — arena pages are reachable purely
        # through remapped table entries), plus the host-side index over
        # the arena's global page-id range. The index's chain root is
        # salted with this engine's KV-format tag so content hashes
        # cover the stored representation — quantized bytes + scales —
        # not just the tokens (prefix_cache.chain_root).
        self.prefix: Optional[PrefixCache] = None
        if config.prefix_cache:
            self.cache = _append_arena_rows(self.cache, self._arena_rows)
            n_p = self.n_pages_slot
            arena_ids = range(B * n_p, (B + self._arena_rows) * n_p)
            self.prefix = PrefixCache(
                list(arena_ids), self.page,
                format_tag=self._kv_format_tag(),
            )
        # the pristine init tree's index leaves alias one buffer
        # (set_decode_offsets hands cache_index and shift_index the same
        # offsets array). Every path that donates the batched cache
        # (_map_prefix_jit at admission, the fused iteration jit) forbids
        # aliased inputs; one copy de-aliases the tree once
        self.cache = jax.tree_util.tree_map(jnp.copy, self.cache)
        self._prefix_hits = 0
        self._prefix_misses = 0
        # pristine batch-1 cache, the TEMPLATE every prefill starts from.
        # The prefill jits donate their cache argument (the output aliases
        # the input in HBM), so this template itself must never be passed
        # in — callers go through _fresh_prefill_cache(), which hands the
        # jit a private copy (one small memcpy per admission vs
        # double-buffering the cache for every prefill call).
        self._fresh1 = set_decode_offsets(
            init_decode_cache(
                dalle, params, 1, cache_format="paged",
                kv_quant=self.kv_quant,
            ),
            jnp.zeros((1,), jnp.int32),
        )
        self.slots: List[Optional[_Slot]] = [None] * B
        self.results: Dict[str, RequestResult] = {}
        # incremental outcome tally (updated wherever a result is stored):
        # keeps stats() and the router's per-iteration verify_invariants
        # probe O(outcomes), not O(results) — a long-lived engine's result
        # dict grows without bound
        self._outcome_counts: Dict[Outcome, int] = {o: 0 for o in Outcome}
        # open telemetry lifecycle spans: one "serve.request" per live
        # request, ended with its typed outcome (docs/DESIGN.md §9). The
        # dict stays empty when telemetry is disabled (begin returns None
        # and end(None) is a no-op), so the engine pays ~nothing.
        self._req_spans: Dict[str, Optional[int]] = {}
        self._cancel_requested: set = set()
        self._live: set = set()  # queued or running request ids
        self._seq = 0
        self._admit_seq = 0
        self._submitted = 0
        # in-flight decode step awaiting readback: (device samples, slots
        # dispatched). With lookahead on, this is read back one iteration
        # behind its dispatch; off, it is consumed the same iteration.
        self._pending: Optional[Tuple[jax.Array, List[_Slot]]] = None
        # filler PRNG keys and token row, built ONCE: the per-iteration
        # dispatch only folds keys for ACTIVE slots and scatters them over
        # this cached base instead of rebuilding B host keys + a full
        # jnp.stack every step (the measured per-iteration host overhead)
        self._filler_keys = jnp.stack([jax.random.key(0)] * B)
        self._zero_tok = jnp.zeros((B,), jnp.int32)
        # top-k count derived from the FULL vocab (reference fractional-k
        # semantics over the pre-sliced image logits; models/sampling.py)
        self.k_img = max(int((1 - config.filter_thres) * dalle.total_tokens), 1)
        # fused ragged iteration (ROADMAP 1): one _iteration_jit dispatch
        # per engine iteration. Prefilling rows build their prompt
        # DIRECTLY in their row of the batched cache (no private batch-1
        # cache, no insert), reading their chunks from the on-device
        # prompts buffer — the host only moves descriptors.
        self.fused = config.fused_iteration
        if self.fused:
            if config.prefill_chunk is None:
                raise ValueError(
                    "fused_iteration requires chunked prefill "
                    "(prefill_chunk): the fused block width is the chunk "
                    "width"
                )
            self._W = fused_width(config)
            self._prompts = jnp.zeros((B, self.T), jnp.int32)
        # speculative-decode state: lifetime draft/accept tallies (the
        # serve.spec_accept_frac gauge) and the per-slot BASE sampling
        # keys — key(seed), written once per admission; the spec jit
        # folds positions into them in-trace, so the synchronous hot
        # loop never assembles keys on the host
        self._spec_drafted = 0
        self._spec_accepted = 0
        if self.spec:
            self._base_keys = jnp.stack([jax.random.key(0)] * B)
        # fixed copy width for the donated publish/COW/restore page-copy
        # jits (_copy_pages_jit): a publish copies at most the prompt's
        # pages, a COW/restore fewer — one padded shape covers all
        self._copy_pad = pages_for(self.T, self.page)
        # dispatch accounting: model-jit calls and
        # engine iterations that did device work — steady-state fused mode
        # is exactly 1 dispatch/iteration, the split path one per prefill
        # chunk plus one decode step
        self.dispatches = 0
        self.iterations = 0
        self.tokens_committed = 0
        # KV footprint accounting (the quantized-KV capacity lever,
        # docs/DESIGN.md §6.1): bytes of K/V storage — content AND
        # scale pools — per slot row, computed from the REAL cache
        # leaves so the reported number can never drift from what the
        # engine allocates. Published once here and re-published with
        # the other gauges each iteration (serve.kv_quant.* names).
        self.kv_bytes_per_slot = sum(
            int(np.prod(x.shape[1:])) * x.dtype.itemsize
            for _, x in self._pool_leaf_paths()
        )
        self._total_pool_pages = (
            (config.max_batch + self._arena_rows) * self.n_pages_slot
        )
        # post-decode pipeline (serving/postdecode.py, DESIGN.md §8.5):
        # tokens-complete requests transition VAE_DECODE -> [CLIP_RERANK]
        # -> DONE under their own per-iteration stage budget; staged
        # requests stay LIVE (no result yet) but hold no slot or pages.
        # The pipeline degrades against the same fleet-or-pool occupancy
        # signal the token watermark uses.
        self.postdecode: Optional[PostDecodePipeline] = None
        if stages is not None:
            self.postdecode = PostDecodePipeline(
                stages,
                clock=self.clock,
                counters=self.counters,
                gauges=self.gauges,
                histograms=self.histograms,
                finish=self._finish_staged,
                occupancy=lambda: (
                    self._fleet_occupancy()
                    if self._fleet_occupancy is not None
                    else self.pool.occupancy
                ),
            )
        # observability & adaptive control (docs/DESIGN.md §8.6). The
        # EFFECTIVE knobs start at the config values and only ever move
        # through the controller's data-only channels: the spec verify
        # width stays within the pre-traced ceiling (config.spec_k, the
        # static argument), the watermark is host arithmetic, and the
        # TokenBudget swaps at a FIXED chunk width — controller off, all
        # three equal the config and the engine is bit-identical to one
        # built without this block.
        self._eff_spec_k = config.spec_k
        self._eff_watermark = config.high_watermark
        self.vitals: Optional[vitals_mod.Vitals] = None
        self.controller: Optional[Controller] = None
        self._control_interval = 0
        if config.vitals or config.controller:
            self.vitals = vitals_mod.Vitals(window=config.vitals_window)
        if config.controller:
            cc = config.control if config.control is not None else (
                ControlConfig()
            )
            self._control_interval = cc.interval
            self.controller = Controller(
                cc,
                spec_k_ceiling=config.spec_k if self.spec else None,
                budget_default=(
                    self.budget.budget if self.budget is not None else None
                ),
                chunk=(
                    self.budget.chunk if self.budget is not None else 1
                ),
                watermark_default=config.high_watermark,
                prefix_enabled=self.prefix is not None,
            )
        self._publish_kv_gauges()

    def _kv_format_tag(self) -> bytes:
        """This engine's KV storage-format descriptor: quantization,
        page size, and the pool/scale leaf dtypes — the prefix chain's
        root salt and the snapshot compatibility key. Derived from the
        REAL cache leaves, so the tag tracks the code's storage choices,
        never a transcription of them. The default unquantized format
        keeps the empty (pre-quantization) tag for snapshot continuity."""
        if self.kv_quant == "none":
            return b""
        dts = sorted({
            np.dtype(x.dtype).name for _, x in self._pool_leaf_paths()
        })
        return (
            f"kv:{self.kv_quant}:page{self.page}:{','.join(dts)}".encode()
        )

    def _publish_kv_gauges(self) -> None:
        self.gauges.set(
            "serve.kv_quant.bytes_per_slot", float(self.kv_bytes_per_slot)
        )
        self.gauges.set(
            "serve.kv_quant.pages", float(self._total_pool_pages)
        )

    # ------------------------------------------------------------ public

    def submit(self, request: Request) -> Optional[RequestResult]:
        """Queue a request; returns the RequestResult immediately on a
        typed reject, else None (the result lands in ``self.results`` at a
        terminal outcome)."""
        if not (0 < request.max_new_tokens <= self.dalle.image_seq_len):
            raise ValueError(
                f"max_new_tokens must be in [1, {self.dalle.image_seq_len}], "
                f"got {request.max_new_tokens}"
            )
        if request.request_id in self.results or request.request_id in self._live:
            raise ValueError(f"duplicate request_id {request.request_id!r}")
        self._submitted += 1
        self.counters.inc("serve.submitted")
        now = self.clock.now()
        entry = Entry(request=request, submit_time=now, seq=self._seq)
        self._seq += 1
        self._req_spans[request.request_id] = TELEMETRY.begin(
            "serve.request",
            request_id=request.request_id,
            priority=request.priority,
            max_new_tokens=request.max_new_tokens,
        )
        if self._worst_case_pages(request.max_new_tokens) > self.pool.total:
            return self._reject(entry, RejectReason.DEMAND_EXCEEDS_POOL)
        if not self.sched.submit(entry):
            return self._reject(entry, RejectReason.QUEUE_FULL)
        self._live.add(request.request_id)
        return None

    def submit_staged(self, request: Request, tokens,
                      image=None) -> Optional[RequestResult]:
        """Admit a request DIRECTLY into the post-decode pipeline with
        its token work already done — the crash-replay / failover resume
        path (serving/journal.py:replay_unfinished): ``tokens`` are the
        journaled completed image tokens, ``image`` (if present) the
        journaled VAE output, so the request resumes at VAE_DECODE or
        CLIP_RERANK instead of re-decoding. Same typed contract as
        ``submit``: None on acceptance, the result lands in
        ``self.results`` at a terminal outcome (possibly immediately, if
        pipeline pressure degrades it at the door)."""
        if self.postdecode is None:
            raise ValueError("engine built without stages=StageSpec(...)")
        if request.request_id in self.results or request.request_id in self._live:
            raise ValueError(f"duplicate request_id {request.request_id!r}")
        self._submitted += 1
        self.counters.inc("serve.submitted")
        now = self.clock.now()
        entry = Entry(request=request, submit_time=now, seq=self._seq)
        self._seq += 1
        entry.generated = [int(t) for t in np.asarray(tokens).reshape(-1)]
        self._req_spans[request.request_id] = TELEMETRY.begin(
            "serve.request",
            request_id=request.request_id,
            priority=request.priority,
            max_new_tokens=request.max_new_tokens,
        )
        self._live.add(request.request_id)
        # resume paths never re-announce: their stage records are durable
        self.postdecode.enqueue(
            entry, np.asarray(tokens, np.int32), image=image, announce=False
        )
        return None

    def can_admit_staged(self, request: Request) -> bool:
        """Whether a staged (tokens-complete) request can be dispatched
        here — the router's failover gate. Pipeline pressure is handled
        by typed degradation at enqueue, so the only requirement is that
        this engine runs the stages at all."""
        return self.postdecode is not None

    def cancel(self, request_id: str) -> None:
        """Request cancellation; takes effect at the next scheduling
        iteration (queued requests terminate without ever prefilling;
        requests mid-chunked-prefill terminate between chunks)."""
        self._cancel_requested.add(request_id)

    def step(self) -> bool:
        """One scheduling iteration: terminations -> admission -> device
        work. Split mode: one decode step then budgeted prefill chunks,
        each its own jit dispatch. Fused mode: the whole iteration —
        decode rows AND granted prefill chunks — as ONE ragged dispatch.
        Returns False when the engine is fully idle."""
        # every phase is a lexical child span of ``serve.step`` (a phase's
        # self time = its span minus its children); under any profiler
        # capture they land on the device events' clock
        # (utils/profiling.py), which is what attributes a device gap to
        # the host phase that covers it (DESIGN.md §9)
        with TELEMETRY.span("serve.step"):
            with TELEMETRY.span("serve.step.sweep"):
                self._sweep_terminations()
            with TELEMETRY.span("serve.step.admit"):
                self._admit()
            if self.fused:
                worked = (
                    self._spec_iteration() if self.spec
                    else self._fused_iteration()
                )
            else:
                worked = self._decode_once()
                # split mode's budgeted chunks: each is a
                # ``serve.prefill_chunk`` child of ``serve.step``
                worked = self._advance_prefills() or worked
            if self.postdecode is not None:
                # post-decode stage work runs AFTER the token work of the
                # iteration, metered by its own budget — subordinate to
                # decode by construction (DESIGN.md §8.5)
                with TELEMETRY.span("serve.step.stages"):
                    worked = self.postdecode.step() or worked
            if worked:
                self.iterations += 1
            self.clock.tick()
            with TELEMETRY.span("serve.step.publish"):
                if self.vitals is not None and worked:
                    self._observe_vitals()
                    if (
                        self.controller is not None
                        and self.iterations % self._control_interval == 0
                    ):
                        self._run_controller()
                self._publish_gauges()
        return (worked or bool(self.sched) or any(self.slots)
                or bool(self.postdecode))

    def run(self, max_steps: Optional[int] = None) -> Dict[str, RequestResult]:
        """Drive until idle. ``max_steps`` is a test/ops safety valve: the
        loop provably terminates (every iteration completes, terminates, or
        advances some request — the token budget always grants the head
        prefill at least one chunk — and admission cannot deadlock: an
        empty engine has the whole pool free and over-pool demands were
        rejected at submit), so hitting the valve is a bug, reported
        loudly."""
        steps = 0
        while self.step():
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"engine made no terminal progress in {max_steps} steps: "
                    f"{sum(bool(s) for s in self.slots)} running, "
                    f"{len(self.sched)} queued"
                )
        return self.results

    def stats(self) -> dict:
        return {
            "submitted": self._submitted,
            "running": sum(bool(s) and s.phase == _DECODE for s in self.slots),
            "prefilling": sum(
                bool(s) and s.phase == _PREFILL for s in self.slots
            ),
            "queued": len(self.sched),
            "staged": 0 if self.postdecode is None else len(self.postdecode),
            "tokens_committed": self.tokens_committed,
            "pool_total": self.pool.total,
            "pool_used": self.pool.used,
            "pool_occupancy": self.pool.occupancy,
            "outcomes": {
                o.value: n for o, n in self._outcome_counts.items()
            },
        }

    # ------------------------------------------------------- terminations

    def _sweep_terminations(self) -> None:
        now = self.clock.now()
        running = [s for s in self.slots if s]
        if running and FAULTS.take("request_cancel"):
            victim = max(running, key=lambda s: s.admit_seq)
            self.counters.inc("serve.fault_request_cancel")
            self._cancel_requested.add(victim.entry.request_id)
        # cancellations: queued first (never prefilled -> no tokens) ...
        for rid in list(self._cancel_requested):
            entry = self.sched.remove(rid)
            if entry is not None:
                self._cancel_requested.discard(rid)
                self._finish(entry, Outcome.CANCELLED, tokens=None)
        # ... then running (mid-prefill included: the slot and its pages
        # come back THIS iteration, between chunks)
        for slot in list(self.slots):
            if slot and slot.entry.request_id in self._cancel_requested:
                self._cancel_requested.discard(slot.entry.request_id)
                self._release_slot(slot)
                self._finish(
                    slot.entry, Outcome.CANCELLED,
                    tokens=self._partial_tokens(slot),
                )
        # ... then staged (post-decode pipeline): cancel and deadline in
        # one sweep — the typed outcome carries the partial results
        # (tokens always, the image if VAE had finished)
        if self.postdecode is not None:
            for rid in self.postdecode.sweep(self._cancel_requested, now):
                self._cancel_requested.discard(rid)
        # cancels naming unknown or already-finished requests (a normal
        # client race) must not accumulate forever in a long-lived engine
        self._cancel_requested &= self._live
        # deadlines: queued and running alike, checked every iteration so
        # pages come back the step the deadline passes, not at completion
        # (and for a chunked prefill, between chunks — never only at the
        # end of the prompt)
        for entry in self.sched.expired(now):
            self._finish(entry, Outcome.DEADLINE_EXCEEDED, tokens=None)
        for slot in list(self.slots):
            d = slot.entry.request.deadline if slot else None
            if slot and d is not None and now > d:
                self._release_slot(slot)
                self._finish(
                    slot.entry, Outcome.DEADLINE_EXCEEDED,
                    tokens=self._partial_tokens(slot),
                )

    @staticmethod
    def _partial_tokens(slot: _Slot) -> Optional[np.ndarray]:
        """Tokens delivered with a mid-flight termination: the read-back
        prefix for a decoding slot (a sample still in flight is NOT
        included — lookahead's at-readback-time semantics), None for a
        slot that never finished its prefill."""
        if slot.phase == _PREFILL:
            return None
        return np.asarray(slot.entry.generated, np.int32)

    # ---------------------------------------------------------- admission

    def _admit(self) -> None:
        while True:
            free = [i for i, s in enumerate(self.slots) if s is None]
            if not free:
                return
            entry = self.sched.peek()
            if entry is None:
                return
            # re-check demand against CURRENT free pages (strict
            # head-of-line; see Scheduler docstring for the starvation
            # rationale). Demand uses the clamped budget the request would
            # actually get, so degradation widens the door it is sized
            # for — and a prefix-cache hit SHRINKS it by the pages the
            # slot will map shared instead of allocating (probe first:
            # the hit length is part of the admission decision).
            eff_max_new, clamped = self._degraded_budget(entry)
            hit = self._probe_admission(entry)
            demand = self._worst_case_pages(eff_max_new) - hit.shared
            if demand > self.pool.free and not self._reclaim_index_pages(
                demand - self.pool.free
            ):
                if hit.nodes:
                    self.prefix.release(hit.nodes)
                return
            entry = self.sched.pop()
            entry.effective_max_new = eff_max_new
            entry.clamped = clamped
            if clamped:
                self.counters.inc("serve.clamped")
            if self.spec:
                # the slot's draft/verify BASE key, set once per
                # admission (preemption replay re-admits through here):
                # _spec_iteration_jit folds positions into it in-trace
                self._base_keys = self._base_keys.at[free[0]].set(
                    jax.random.key(entry.request.seed)
                )
            prompt_pages = pages_for(self.T, self.page) - hit.shared
            ok = self.pool.alloc(entry.request_id, prompt_pages)
            assert ok, "admission checked worst-case > prompt pages"
            if hit.full:
                self._claim_full_hit_slot(entry, free[0], hit)
                continue
            if self.config.prefill_chunk is not None:
                self._claim_prefill_slot(entry, free[0], hit)
                continue
            req_span = self._req_spans.get(entry.request_id)
            try:
                with TELEMETRY.span(
                    "serve.prefill",
                    request_id=entry.request_id, parent=req_span,
                    attempt=entry.prefill_attempts,
                ):
                    cache1, tok0, img = self._prefill(entry)
            except _PrefillFault:
                self.pool.free_all(entry.request_id)
                entry.prefill_attempts += 1
                self.counters.inc("serve.prefill_retries")
                TELEMETRY.event(
                    "serve.prefill_retry", request_id=entry.request_id,
                    parent=req_span, attempt=entry.prefill_attempts,
                )
                if entry.prefill_attempts >= self.config.prefill_attempts:
                    self._finish(
                        entry, Outcome.PREFILL_FAILED, tokens=None,
                        detail="prefill failed after "
                               f"{entry.prefill_attempts} attempts",
                    )
                else:
                    self.sched.requeue(entry)
                continue
            idx = free[0]
            ring = (
                _ring_snapshot(cache1, 0) if self.prefix is not None else None
            )
            with TELEMETRY.span(
                "serve.slot_insert",
                request_id=entry.request_id, parent=req_span, slot=idx,
            ):
                self.cache = insert_decode_cache(self.cache, cache1, idx)
            now = self.clock.now()
            entry.admit_time = now
            entry.generated = [int(tok0)]
            self._commit_tokens(1)
            # queue wait = submit (or preemption requeue's ORIGINAL
            # submit) to this admission — what the client experienced
            self.histograms.observe("serve.queue_wait_s", now - entry.submit_time)
            TELEMETRY.event(
                "serve.admit", request_id=entry.request_id, parent=req_span,
                slot=idx, queue_wait_s=now - entry.submit_time,
                clamped=clamped,
            )
            slot = _Slot(
                entry, idx, first_token=int(tok0), pos=self.T,
                admit_seq=self._admit_seq,
            )
            self._admit_seq += 1
            if self.prefix is not None:
                # monolithic prefill observes only the TERMINAL boundary
                # (intermediate page states never surface to the host),
                # so published interior nodes are content-only and the
                # terminal node carries the full-hit payload
                slot.boundary_rings[self.T] = ring
                slot.final_logits = img
            self.slots[idx] = slot
            self.counters.inc("serve.admitted")
            self._note_prefix_outcome(entry, hit, req_span, idx)
            self._record_first_token(entry, now)
            if len(entry.generated) >= entry.effective_max_new:
                self._complete(slot)

    def _claim_prefill_slot(
        self, entry: Entry, idx: int, hit: "_AdmitHit" = None
    ) -> None:
        """Chunked-mode admission: the request claims its slot and prompt
        pages NOW; the prompt itself is processed chunk by chunk across the
        following iterations (``_advance_prefills``). A PARTIAL prefix-
        cache hit starts the chunk machinery at the miss boundary instead
        of position 0: fused mode MAPS the hit pages into the slot's page
        table read-only (refcounts held until release) and restores the
        boundary's shift-ring seam in place; split mode COPIES the hit
        pages into the private batch-1 cache (its chunk jits cannot reach
        the batched pools) — compute is still skipped, the refs are
        dropped once the copy is dispatched."""
        if hit is None:
            hit = _NO_HIT
        now = self.clock.now()
        entry.admit_time = now
        req_span = self._req_spans.get(entry.request_id)
        self.histograms.observe("serve.queue_wait_s", now - entry.submit_time)
        TELEMETRY.event(
            "serve.admit", request_id=entry.request_id, parent=req_span,
            slot=idx, queue_wait_s=now - entry.submit_time,
            clamped=entry.clamped,
        )
        slot = _Slot(
            entry, idx, first_token=-1, pos=0,
            admit_seq=self._admit_seq, phase=_PREFILL,
        )
        self._admit_seq += 1
        internal = jnp.asarray(self._internal_tokens(entry), jnp.int32)[None]
        nodes = hit.nodes
        s = hit.coverage
        if self.fused:
            # fused mode: the row prefills IN PLACE in the batched cache
            # (reset to pristine at release), chunks gathered in-trace
            # from the prompts buffer — one small row write per admission
            self._prompts = self._prompts.at[idx].set(internal[0])
            if nodes:
                ids = np.zeros(self.n_pages_slot, np.int32)
                ids[: len(nodes)] = [n.page_id for n in nodes]
                self.cache = _map_prefix_jit(
                    self.cache, np.int32(idx), jnp.asarray(ids),
                    np.int32(len(nodes)), np.int32(s), nodes[-1].ring,
                )
                slot.shared_nodes = list(nodes)
        else:
            slot.cache1 = self._fresh_prefill_cache()
            slot.internal = internal
            if nodes:
                src = [n.page_id for n in nodes]
                # seam + index seeding through the shared donated map jit
                # (page-table no-op: n_ids == 0 — the pages arrive via the
                # cross-pool copy below, already slot-local)
                slot.cache1 = _map_prefix_jit(
                    slot.cache1, np.int32(0),
                    jnp.zeros(self.n_pages_slot, jnp.int32),
                    np.int32(0), np.int32(s), nodes[-1].ring,
                )
                # arena -> batch-1 pool restore through the donated
                # fixed-shape cross-pool copy jit (full pages: valid ==
                # page size)
                slot.cache1 = _copy_pages_across_jit(
                    slot.cache1, self.cache, *self._padded_copy(
                        src, list(range(len(src))),
                        [self.page] * len(src),
                        dst_total=self.n_pages_slot,
                    )
                )
                self.prefix.release(nodes)
        slot.filled = s
        slot.snap_from = s
        slot.prefill_span = TELEMETRY.begin(
            "serve.prefill",
            request_id=entry.request_id, parent=req_span,
            attempt=entry.prefill_attempts, chunked=True, resumed_at=s,
        )
        self.slots[idx] = slot
        self.counters.inc("serve.admitted")
        self._note_prefix_outcome(entry, hit, req_span, idx)

    def _claim_full_hit_slot(
        self, entry: Entry, idx: int, hit: "_AdmitHit"
    ) -> None:
        """FULL-prefix-hit admission: no prefill at all. Every cached
        prompt page is mapped into the slot's table read-only, the
        terminal shift-ring seam is restored, and the first image token
        is sampled from the cached terminal logits with the request's own
        ``fold_in(key(seed), T)`` key — bit-identical to the cold prefill
        (``_sample_cached_jit``). A PARTIAL terminal page (T not page-
        aligned) is privatized immediately — copy-on-write at map time:
        the request's very first decode write lands past the shared
        prefix INSIDE that page, so the copy (into the slot's own zeroed
        native page, prompt rows only) happens before the write can
        touch shared storage. The slot enters decode THIS iteration."""
        now = self.clock.now()
        entry.admit_time = now
        req_span = self._req_spans.get(entry.request_id)
        self.histograms.observe("serve.queue_wait_s", now - entry.submit_time)
        TELEMETRY.event(
            "serve.admit", request_id=entry.request_id, parent=req_span,
            slot=idx, queue_wait_s=now - entry.submit_time,
            clamped=entry.clamped,
        )
        nodes = hit.nodes
        terminal = nodes[-1]
        cow = terminal.valid < self.page
        shared = nodes[:-1] if cow else list(nodes)
        n_p = self.n_pages_slot
        T = self.T

        ids = np.zeros(n_p, np.int32)
        ids[: len(shared)] = [n.page_id for n in shared]
        self.cache = _map_prefix_jit(
            self.cache, np.int32(idx), jnp.asarray(ids),
            np.int32(len(shared)), np.int32(T), terminal.ring,
        )
        if cow:
            # the map-time COW rides the donated fixed-shape copy jit —
            # one warm dispatch, not an eager pool-sized rewrite
            self.cache = _copy_pages_jit(
                self.cache, *self._padded_copy(
                    [terminal.page_id], [idx * n_p + len(nodes) - 1],
                    [terminal.valid],
                )
            )
            self.prefix.release([terminal])
            self.counters.inc("serve.prefix.cow_copies")
        slot = _Slot(
            entry, idx, first_token=-1, pos=T,
            admit_seq=self._admit_seq, phase=_DECODE,
        )
        self._admit_seq += 1
        slot.shared_nodes = shared
        slot.snap_from = T
        key = jax.random.fold_in(jax.random.key(entry.request.seed), T)
        self.dispatches += 1
        self.counters.inc("serve.dispatches")
        tok = _sample_cached_jit(
            terminal.logits, key, self.k_img, self.config.temperature
        )
        tok0 = int(tok[0])
        entry.generated = [tok0]
        self._commit_tokens(1)
        slot.tok = tok0
        self.slots[idx] = slot
        self.counters.inc("serve.admitted")
        self._note_prefix_outcome(entry, hit, req_span, idx, cow=cow)
        # stamp AFTER the sample's host sync: every other path's first-
        # token stamp includes its compute, so the cached-vs-cold TTFT
        # comparison must charge the cached path its sample dispatch too
        self._record_first_token(entry, self.clock.now())
        if len(entry.generated) >= entry.effective_max_new:
            self._complete(slot)

    # ------------------------------------------------------- prefix cache

    def _internal_tokens(self, entry: Entry) -> np.ndarray:
        """The request's INTERNAL prompt row (bos + remap) as host ints —
        the prefix chain key and the publish source of truth; computed
        once per request (one tiny device roundtrip), cached on the
        entry so preemption replays reuse it."""
        if entry.internal_tokens is None:
            text = jnp.asarray(entry.request.prompt, jnp.int32)[None, :]
            entry.internal_tokens = np.asarray(self.dalle.remap_text(text))[0]
        return entry.internal_tokens

    def _probe_admission(self, entry: Entry) -> _AdmitHit:
        """Probe the prefix index with the prompt's chain and filter to
        the USABLE prefix: a full hit needs the terminal payload (ring +
        logits); a partial hit needs the chunk machinery and a RESUMABLE
        boundary strictly inside the prompt (split mode additionally
        refuses a 1-token tail — it would chunk as a width-1 M=1 matvec,
        the bit-parity hazard `_next_chunk` exists to avoid). References
        on the returned nodes are ACQUIRED here."""
        if self.prefix is None:
            return _NO_HIT
        toks = self._internal_tokens(entry)
        col0 = self.prefix.stats.collisions
        # count=False: a page-blocked head-of-line entry re-probes every
        # scheduling iteration; _note_prefix_outcome tallies ONE hit or
        # miss per admission so stats track the serve.prefix.* counters
        nodes = self.prefix.probe(toks, self.clock.now(), count=False)
        if self.prefix.stats.collisions > col0:
            # a forged/colliding lookup was rejected by token
            # verification (the prefix_hash_collide drill): the walk
            # stopped at the collision — cold prefill from there
            self.counters.inc("serve.fault_prefix_hash_collide")
        full = (
            bool(nodes)
            and nodes[-1].coverage == self.T
            and nodes[-1].logits is not None
            and nodes[-1].ring is not None
        )
        if not full:
            if self.config.prefill_chunk is None:
                nodes = []
            else:
                while nodes and (
                    not nodes[-1].resumable
                    or nodes[-1].coverage >= self.T
                    or (
                        not self.fused
                        and self.T - nodes[-1].coverage == 1
                    )
                ):
                    nodes.pop()
        if not nodes:
            return _NO_HIT
        shared = len(nodes) if (full or self.fused) else 0
        if full and nodes[-1].valid < self.page:
            shared -= 1  # the partial terminal page is COW'd, not shared
        self.prefix.acquire(nodes, self.clock.now())
        return _AdmitHit(nodes=nodes, full=full, shared=shared)

    def _note_prefix_outcome(
        self, entry: Entry, hit: _AdmitHit, req_span, idx: int,
        cow: bool = False,
    ) -> None:
        """Hit/miss accounting for one admission (replays count again —
        they re-probe). The TTFT hit-class label sticks to the admission
        that will produce the first token."""
        if self.prefix is None:
            return
        if hit.n_pages:
            self._prefix_hits += 1
            self.prefix.stats.hits += 1
            self.counters.inc("serve.prefix.hits")
            self.counters.inc("serve.prefix.pages_hit", hit.n_pages)
            TELEMETRY.event(
                "serve.prefix_hit", request_id=entry.request_id,
                parent=req_span, slot=idx, pages=hit.n_pages,
                kind=hit.kind, coverage=hit.coverage, cow=cow,
            )
        else:
            self._prefix_misses += 1
            self.prefix.stats.misses += 1
            self.counters.inc("serve.prefix.misses")
        if entry.ttft_s is None:
            entry.hit_class = hit.kind

    def _reclaim_index_pages(self, n: int) -> bool:
        """The index's own eviction tier: drop LRU unreferenced leaf
        nodes (refcounted pages are never victims) until ``n`` logical
        pages are freed — tried BEFORE any running request is preempted
        (an index page only costs future recompute; a preemption
        discards real work). False when the index cannot help — checked
        BEFORE evicting anything: a partial reclaim that still misses
        the target would wipe the cached working set without admitting
        a single request."""
        if self.prefix is None or self.prefix.reclaimable_pages() < n:
            return False
        freed = 0
        while freed < n:
            if self.prefix.evict_one() is None:
                break
            self.pool.release(PREFIX_HOLDER, 1)
            self.counters.inc("serve.prefix.evictions")
            freed += 1
        return freed >= n

    # -------------------------------------------- prefix-cache snapshot

    def _pool_leaf_paths(self) -> List[Tuple[str, object]]:
        """(keystr, leaf) for every K/V page-pool leaf, keystr-sorted —
        the stable leaf enumeration the snapshot format keys on."""
        out = []
        for path, x in jax.tree_util.tree_leaves_with_path(self.cache):
            if getattr(path[-1], "key", None) in paged_kv.POOL_LEAF_KEYS:
                out.append((jax.tree_util.keystr(path), x))
        return sorted(out, key=lambda kv: kv[0])

    def save_prefix_snapshot(self, dirpath: str) -> int:
        """Persist the prefix index + its arena page content to
        ``dirpath`` with the PR 2 two-phase COMMITTED manifest
        (utils/resilience.py:write_dir_manifest — the marker lands LAST,
        so a crash mid-save leaves an uncommitted dir that loaders
        skip). Contents: ``index.json`` (chain records from
        ``snapshot_records`` + format/shape metadata) and ``arrays.npz``
        (per-node page bytes for every pool leaf, ring seams, terminal
        logits — all byte-packed for dtype-exact round trips). Returns
        the number of nodes persisted. Host-side and off the hot path:
        one device sync per pool leaf."""
        assert self.prefix is not None, (
            "save_prefix_snapshot needs prefix_cache enabled"
        )
        # write-aside + swap: the new snapshot is built and COMMITTED in
        # a sibling .tmp dir, then swapped in — a crash anywhere during
        # the build leaves the PREVIOUS committed snapshot untouched at
        # ``dirpath`` (re-saving in place would destroy the last good
        # state during exactly the crash window this file guards
        # against; the only unprotected instant is between the two
        # renames, where the old state survives at ``.old``)
        final = Path(dirpath)
        root = Path(str(final) + ".tmp")
        if root.exists():
            shutil.rmtree(root)
        root.mkdir(parents=True, exist_ok=True)
        records = snapshot_records(self.prefix)
        nodes = {n.digest.hex(): n for n in self.prefix.nodes()}
        leaves = self._pool_leaf_paths()
        n_p = self.n_pages_slot
        arrays: Dict[str, np.ndarray] = {}
        dtypes: Dict[str, str] = {}
        for j, (keystr, x) in enumerate(leaves):
            host = np.asarray(x)
            stack = (
                np.stack([
                    host[rec["page_id"] // n_p, rec["page_id"] % n_p]
                    for rec in records
                ])
                if records else np.zeros((0,) + host.shape[2:], host.dtype)
            )
            arrays[f"pages_l{j}"], dtypes[f"pages_l{j}"] = _snap_pack(stack)
        ring_paths: List[str] = []
        for rec in records:
            node = nodes[rec["digest"]]
            if node.ring is not None and not ring_paths:
                ring_paths = sorted(node.ring)
        for i, rec in enumerate(records):
            node = nodes[rec["digest"]]
            if node.ring is not None:
                assert sorted(node.ring) == ring_paths, (
                    "ring leaf paths differ across nodes"
                )
                for k, rp in enumerate(ring_paths):
                    key = f"ring{i}_{k}"
                    arrays[key], dtypes[key] = _snap_pack(node.ring[rp])
            if node.logits is not None:
                arrays[f"logits{i}"], dtypes[f"logits{i}"] = _snap_pack(
                    node.logits
                )
        for i, rec in enumerate(records):
            rec["content_sha256"] = _node_content_digest(
                arrays, i, len(leaves), len(ring_paths), rec
            )
        index = {
            "format": 1,
            "page_size": self.page,
            "T": self.T,
            "n_pages_slot": n_p,
            # the KV storage-format tag: the chain digests above were
            # derived under this root salt, and a restore into an engine
            # of a DIFFERENT storage format (quantized vs not, other
            # dtypes) must reject typed before any bytes land
            "kv_format": self._kv_format_tag().decode(),
            "leaf_paths": [k for k, _ in leaves],
            "ring_paths": ring_paths,
            "dtypes": dtypes,
            "nodes": records,
        }
        np.savez(root / SNAPSHOT_ARRAYS, **arrays)
        (root / SNAPSHOT_INDEX).write_text(
            json.dumps(index, sort_keys=True)
        )
        write_dir_manifest(str(root), extra={"meta": {
            "kind": "prefix_snapshot", "nodes": len(records),
        }})
        old = Path(str(final) + ".old")
        if old.exists():
            shutil.rmtree(old)
        if final.exists():
            final.rename(old)
        root.rename(final)
        if old.exists():
            shutil.rmtree(old)
        self.counters.inc("serve.snapshot.saved")
        return len(records)

    def _reject_snapshot(self, reason: str) -> bool:
        self.counters.inc("serve.snapshot.rejected")
        TELEMETRY.event("serve.snapshot_reject", reason=reason[:200])
        return False

    def load_prefix_snapshot(self, dirpath: str) -> bool:
        """Restore a persisted prefix index into THIS engine's (empty)
        index — the warm-restart path. Verification is mandatory and
        layered, because the sha-addressed pages mean corruption
        detection is token/hash verification, not trust: (1) the
        two-phase dir manifest (torn/bit-rotted files), (2) format and
        shape compatibility against this engine's cache, (3) every
        node's chain digest RECOMPUTED from its stored tokens
        (``verify_snapshot_records``; the ``snapshot_corrupt`` fault
        tampers a block here so the reject path is drillable). ANY
        failure rejects the whole snapshot (``serve.snapshot.rejected``)
        and the engine continues with a cold index — a wrong page served
        warm is corruption; a cold start is just latency. Returns True
        iff the index was restored."""
        assert self.prefix is not None, (
            "load_prefix_snapshot needs prefix_cache enabled"
        )
        assert len(self.prefix) == 0, (
            "snapshot restore targets a fresh (empty) index"
        )
        ok, reason = verify_dir_manifest(dirpath)
        if not ok:
            return self._reject_snapshot(f"manifest: {reason}")
        root = Path(dirpath)
        try:
            index = json.loads((root / SNAPSHOT_INDEX).read_text())
            with np.load(root / SNAPSHOT_ARRAYS) as z:
                arrays = {k: z[k] for k in z.files}
        except (OSError, ValueError, KeyError) as e:
            return self._reject_snapshot(f"unreadable: {e}")
        if index.get("format") != 1:
            return self._reject_snapshot(
                f"unknown format {index.get('format')!r}"
            )
        records = list(index.get("nodes", []))
        if records and FAULTS.take("snapshot_corrupt"):
            # forge bit rot the manifest missed: one token of the first
            # block flips — the chain-digest recompute below must catch it
            self.counters.inc("serve.fault_snapshot_corrupt")
            records[0] = dict(
                records[0],
                tokens=[int(t) + 1 for t in records[0]["tokens"]],
            )
        leaves = self._pool_leaf_paths()
        dtypes = index.get("dtypes", {})
        ring_paths = index.get("ring_paths", [])
        if index.get("page_size") != self.page or index.get("T") != self.T:
            return self._reject_snapshot(
                "shape mismatch: snapshot "
                f"(page={index.get('page_size')}, T={index.get('T')}) vs "
                f"engine (page={self.page}, T={self.T})"
            )
        tag = self._kv_format_tag().decode()
        if index.get("kv_format", "") != tag:
            # a cross-format restore (quantized snapshot into an f32
            # engine or vice versa) would cast foreign bytes into place
            # as "verified" warm K/V — and its chain digests live under
            # a different root salt anyway (prefix_cache.chain_root)
            return self._reject_snapshot(
                f"kv format mismatch: snapshot "
                f"{index.get('kv_format', '')!r} vs engine {tag!r}"
            )
        if index.get("leaf_paths") != [k for k, _ in leaves]:
            return self._reject_snapshot("cache leaf paths differ")
        for j, (keystr, x) in enumerate(leaves):
            # the restore would otherwise CAST foreign-dtype pages into
            # place as "verified" warm K/V — a bf16 snapshot restored
            # into an f32 build must reject, not silently convert (warm
            # hits are contracted bit-identical to cold compute)
            want = dtypes.get(f"pages_l{j}")
            have = np.dtype(x.dtype).name
            if want != have:
                return self._reject_snapshot(
                    f"cache dtype mismatch at {keystr}: snapshot "
                    f"{want} vs engine {have}"
                )
        ok, reason = verify_snapshot_records(
            records, self.page, format_tag=self._kv_format_tag()
        )
        if not ok:
            return self._reject_snapshot(reason)
        # every payload the build phase will dereference must exist with
        # a coherent shape — a KeyError mid-restore would crash the
        # recovering process instead of the contracted reject-to-cold
        for j in range(len(leaves)):
            stack = arrays.get(f"pages_l{j}")
            if stack is None or stack.shape[0] != len(records):
                return self._reject_snapshot(
                    f"page array pages_l{j} missing or wrong length"
                )
        for i, rec in enumerate(records):
            if rec["has_ring"] and any(
                f"ring{i}_{k}" not in arrays or f"ring{i}_{k}" not in dtypes
                for k in range(len(ring_paths))
            ):
                return self._reject_snapshot(
                    f"record {i}: ring payload missing from arrays"
                )
            if rec["has_logits"] and (
                f"logits{i}" not in arrays or f"logits{i}" not in dtypes
            ):
                return self._reject_snapshot(
                    f"record {i}: logits payload missing from arrays"
                )
        # content digests: the chain digest (above) covers each node's
        # MEANING; this covers its stored REPRESENTATION — quantized
        # page bytes, scales, ring seams, logits — so arrays.npz cannot
        # be tampered behind a regenerated manifest
        for i, rec in enumerate(records):
            want = rec.get("content_sha256")
            have = _node_content_digest(
                arrays, i, len(leaves), len(ring_paths), rec
            )
            if want != have:
                return self._reject_snapshot(
                    f"record {i}: page content digest mismatch "
                    "(tampered or missing payload bytes)"
                )
        if len(records) > self.prefix.free_arena_pages:
            return self._reject_snapshot(
                f"{len(records)} nodes exceed the "
                f"{self.prefix.free_arena_pages}-page arena"
            )
        if not self.pool.alloc(PREFIX_HOLDER, len(records)):
            return self._reject_snapshot(
                f"{len(records)} pages exceed the free page budget"
            )
        now = self.clock.now()
        by_digest: Dict[str, object] = {}
        gids: List[int] = []
        for i, rec in enumerate(records):
            page_id = self.prefix.alloc_page()
            assert page_id is not None, "free_arena_pages said it fits"
            parent = (
                None if rec["parent"] is None else by_digest[rec["parent"]]
            )
            ring = None
            if rec["has_ring"]:
                ring = {
                    rp: _snap_unpack(
                        arrays[f"ring{i}_{k}"], dtypes[f"ring{i}_{k}"]
                    )
                    for k, rp in enumerate(ring_paths)
                }
            logits = None
            if rec["has_logits"]:
                logits = _snap_unpack(
                    arrays[f"logits{i}"], dtypes[f"logits{i}"]
                )
            node = self.prefix.insert(
                parent, np.asarray(rec["tokens"], np.int64),
                start=int(rec["start"]), page_id=page_id, now=now,
                ring=ring, logits=logits,
            )
            by_digest[rec["digest"]] = node
            gids.append(page_id)
        if gids:
            n_p = self.n_pages_slot
            rows = jnp.asarray([g // n_p for g in gids], jnp.int32)
            cols = jnp.asarray([g % n_p for g in gids], jnp.int32)
            content = {
                keystr: _snap_unpack(
                    arrays[f"pages_l{j}"], dtypes[f"pages_l{j}"]
                )
                for j, (keystr, _) in enumerate(leaves)
            }

            def fn(path, x):
                k = jax.tree_util.keystr(path)
                if k in content:
                    return x.at[rows, cols].set(
                        content[k].astype(x.dtype)
                    )
                return x

            self.cache = jax.tree_util.tree_map_with_path(fn, self.cache)
        self.counters.inc("serve.snapshot.restored")
        return True

    # --------------------------------------------------- request export

    def live_requests(self) -> List[Request]:
        """Restorable descriptors of every request the engine still owes
        a terminal outcome — queued first (submission order), then
        running (admission order). Replaying exactly these on a fresh
        engine reproduces their tokens bit-identically (the (seed,
        position) contract); the crash-recovery export surface."""
        queued = [e.request for e in self.sched.entries()]
        running = [
            s.entry.request
            for s in sorted(
                (s for s in self.slots if s), key=lambda s: s.admit_seq
            )
        ]
        staged = (
            [] if self.postdecode is None
            else [s.entry.request for s in self.postdecode._staged]
        )
        return queued + running + staged

    def _maybe_snapshot(self, slot: _Slot, cache, row: int) -> None:
        """Capture the shift-ring seam when a prefill lands exactly on a
        page boundary (or the prompt end) beyond the already-indexed
        prefix — the payload that makes the published node RESUMABLE.
        Boundaries the chunk schedule never lands on are simply not
        captured; their nodes publish content-only."""
        if self.prefix is None:
            return
        s = slot.filled
        if s <= slot.snap_from:
            return
        if s == self.T or s % self.page == 0:
            slot.boundary_rings[s] = _ring_snapshot(cache, row)

    def _publish(self, slot: _Slot) -> None:
        """Publish a completing request's fully written prompt pages into
        the prefix index (dedup-on-insert): pages already on the chain
        are counted deduped (and upgraded with any seam/logits payloads
        this run observed); new pages are copied into arena pages — one
        batched device copy — and committed with their boundary rings.
        Fail-open by contract: arena/budget exhaustion or the
        ``prefix_publish_fail`` fault skip publication and the request
        still completes with its pages private."""
        entry = slot.entry
        if FAULTS.take("prefix_publish_fail"):
            self.counters.inc("serve.fault_prefix_publish_fail")
            self.prefix.stats.publish_skips += 1
            self.counters.inc("serve.prefix.publish_skips")
            return
        toks = self._internal_tokens(entry)
        blocks = chain_blocks(toks, self.page)
        now = self.clock.now()
        existing = self.prefix.match(toks)
        dedup = max(0, len(existing) - len(slot.shared_nodes))
        if dedup:
            self.prefix.stats.deduped += dedup
            self.counters.inc("serve.prefix.pages_deduped", dedup)
        for node in existing:
            self.prefix.upgrade(
                node,
                ring=slot.boundary_rings.get(node.coverage),
                logits=(
                    slot.final_logits if node.coverage == self.T else None
                ),
            )
        if len(existing) == len(blocks):
            return
        # pin the chain (and each new node) against the LRU reclaim the
        # allocation below may trigger — a reclaimed parent would orphan
        # its children
        protected = list(existing)
        self.prefix.acquire(protected, now)
        src, dst, valids = [], [], []
        try:
            parent = existing[-1] if existing else None
            n_p = self.n_pages_slot
            for k in range(len(existing), len(blocks)):
                block = blocks[k]
                cov = k * self.page + len(block)
                ring = slot.boundary_rings.get(cov)
                logits = slot.final_logits if cov == self.T else None
                if cov == self.T and ring is None and logits is None:
                    # a terminal node with neither seam nor logits can
                    # serve no hit (full needs logits, partial trims
                    # coverage >= T) — e.g. a full-hit slot republishing
                    # its COW page after the original terminal was
                    # evicted mid-decode. Don't spend an arena page on
                    # it; the next cold run publishes the payloads.
                    break
                page_id = self.prefix.alloc_page()
                if page_id is None and self._reclaim_index_pages(1):
                    page_id = self.prefix.alloc_page()
                if page_id is None:
                    self.prefix.stats.publish_skips += 1
                    self.counters.inc("serve.prefix.publish_skips")
                    break
                if not self.pool.alloc(PREFIX_HOLDER, 1):
                    if not (
                        self._reclaim_index_pages(1)
                        and self.pool.alloc(PREFIX_HOLDER, 1)
                    ):
                        self.prefix.return_page(page_id)
                        self.prefix.stats.publish_skips += 1
                        self.counters.inc("serve.prefix.publish_skips")
                        break
                node = self.prefix.insert(
                    parent, block, start=k * self.page, page_id=page_id,
                    now=now, ring=ring, logits=logits,
                )
                self.prefix.acquire([node], now)
                protected.append(node)
                parent = node
                src.append(slot.index * n_p + k)
                dst.append(page_id)
                valids.append(len(block))
        finally:
            self.prefix.release(protected)
        if not dst:
            return
        # ONE donated fixed-shape dispatch for the whole publish (the
        # PR 10 follow-on): padded to the engine's copy width so every
        # publish shares a single compile signature, off the host path
        self.cache = _copy_pages_jit(
            self.cache, *self._padded_copy(src, dst, valids)
        )
        self.counters.inc("serve.prefix.published", len(dst))

    def _padded_copy(self, src, dst, valids, dst_total: Optional[int] = None):
        """Pad a page-copy request to the engine's fixed copy width
        (``self._copy_pad`` — a publish copies at most the prompt's
        pages, a COW/restore fewer) so the donated copy jits
        (``_copy_pages_jit``/``_copy_pages_across_jit``) compile exactly
        once per engine. Padding entries carry dst == ``dst_total`` (the
        scatter's out-of-range drop sentinel;
        ops/paged_kv.py:copy_pages_across) and valid 0. ``dst_total``
        defaults to the batched cache's page count."""
        if dst_total is None:
            dst_total = (
                (self.config.max_batch + self._arena_rows)
                * self.n_pages_slot
            )
        P = self._copy_pad
        assert len(src) <= P, (len(src), P)
        pad = P - len(src)
        return (
            jnp.asarray(list(src) + [0] * pad, jnp.int32),
            jnp.asarray(list(dst) + [dst_total] * pad, jnp.int32),
            jnp.asarray(list(valids) + [0] * pad, jnp.int32),
        )

    def _degraded_budget(self, entry: Entry) -> tuple:
        return self._clamped_budget(entry.request.max_new_tokens)

    def _clamped_budget(self, want: int) -> tuple:
        """(effective max_new_tokens, clamped?) under the watermark
        degradation policy. Occupancy is this engine's own pool unless a
        router injected a fleet aggregate (``fleet_occupancy``) — then
        pressure anywhere in the fleet clamps admissions everywhere, which
        is what makes degradation span replica boundaries."""
        cfg = self.config
        occ = (
            self._fleet_occupancy()
            if self._fleet_occupancy is not None
            else self.pool.occupancy
        )
        if (
            cfg.degraded_max_new_tokens is not None
            and occ > self._eff_watermark
            and want > cfg.degraded_max_new_tokens
        ):
            return cfg.degraded_max_new_tokens, True
        return want, False

    def can_admit(self, request: Request) -> bool:
        """Router dispatch gate: True iff ``submit()`` now would be
        admitted at the very next scheduling iteration — a free slot
        exists, the internal queue is empty (preemption/retry requeues own
        the head-of-line), and the worst-case page demand of the budget
        the request would actually receive fits the currently free pages.
        Keeping dispatch behind this gate is what keeps a replica's
        internal queue empty, so a drain or failover never has to claw
        queued work back out of an engine."""
        if not any(s is None for s in self.slots):
            return False
        if len(self.sched):
            return False
        eff_max_new, _ = self._clamped_budget(request.max_new_tokens)
        avail = self.pool.free
        if self.prefix is not None:
            # the index is its own last-resort eviction tier: _admit
            # reclaims unreferenced index pages before refusing, so they
            # are available to a dispatch decision even though the pool
            # charges them to __prefix__ — without this a tightly
            # budgeted prefix replica would gate itself shut forever.
            # (A prefix HIT can only shrink the real demand further;
            # probing here would cost a device roundtrip per poll, so
            # the gate stays conservative on that side.)
            avail += self.prefix.reclaimable_pages()
        return self._worst_case_pages(eff_max_new) <= avail

    def _fresh_prefill_cache(self):
        """A donate-safe copy of the pristine batch-1 cache template: the
        prefill jits consume (donate) their cache argument, and donating
        ``_fresh1`` itself would invalidate the template for every later
        admission (a real invalidation — jax deletes donated buffers on
        CPU too, so tests catch any template reuse)."""
        return jax.tree_util.tree_map(jnp.copy, self._fresh1)

    def _worst_case_pages(self, max_new: int) -> int:
        # positions WRITTEN to cache: the prompt (T) plus every generated
        # token except the last (a sampled token is cached only when the
        # next step consumes it)
        return pages_for(self.T + max_new - 1, self.page)

    def _prefill(self, entry: Entry):
        if FAULTS.take("prefill_fail"):
            self.counters.inc("serve.fault_prefill_fail")
            raise _PrefillFault(entry.request_id)
        text = jnp.asarray(entry.request.prompt, jnp.int32)[None, :]
        internal = self.dalle.remap_text(text)
        key = jax.random.fold_in(
            jax.random.key(entry.request.seed), self.T
        )
        self.dispatches += 1
        self.counters.inc("serve.dispatches")
        cache1, tok, img = _prefill_jit(
            self.dalle, self.params, self._fresh_prefill_cache(), internal,
            key, self.k_img, self.config.temperature,
        )
        return cache1, int(tok[0]), img

    # ----------------------------------------------------- chunked prefill

    def _next_chunk(self, filled: int) -> int:
        """Width of the next SPLIT-path prefill chunk: the configured
        size, except a would-be 1-token TAIL is merged into this chunk.
        The attention core no longer cares (``cache_block_attend`` pads
        width-1 blocks to width-2 gemms), but a batch-1 width-1 chunk
        still runs its PROJECTION/FFN matmuls as M=1 matvecs whose
        accumulation differs ~1 ulp from the M>=2 gemm (pinned by
        tests/test_ragged_attention.py), so the split path keeps the
        merge. The FUSED path needs no such special case: every row of
        its fixed-width block is padded to the iteration width, so its
        tails are gemm-shaped by construction (``_next_chunk_fused``)."""
        chunk = self.config.prefill_chunk
        c = min(chunk, self.T - filled)
        if self.T - filled - c == 1:
            c += 1
        return c

    def _next_chunk_fused(self, filled: int) -> int:
        """Width of the next FUSED-path prefill chunk: the configured
        size or the plain ragged tail — no 1-token-tail merge, because
        the fused block computes every row at the fixed iteration width
        (a 1-token tail is just one valid column of a padded row)."""
        return min(self.config.prefill_chunk, self.T - filled)

    def _plan_fused_prefills(self, decode_tokens: int) -> List[Tuple["_Slot", int]]:
        """One fused iteration's prefill chunk grants, shared by the
        plain and SPECULATIVE iterations: in-progress prefills served
        head-of-line by effective priority under the ``TokenBudget``
        policy after decode's charge (``decode_tokens`` — one token per
        active slot in plain mode, the summed verify widths in
        speculative mode). The ``prefill_fail`` fault fires per granted
        chunk; a retry resumes from the last completed chunk, exhausted
        attempts finish the request typed."""
        pre = [
            s for s in self.slots
            if s and s.phase == _PREFILL and s.filled < self.T
        ]
        pre.sort(key=lambda s: (
            -self.sched.effective_priority(s.entry), s.admit_seq
        ))
        grants = self.budget.plan_iteration(
            decode_tokens, [self._next_chunk_fused(s.filled) for s in pre]
        )
        chunks: List[Tuple[_Slot, int]] = []
        for slot, take in zip(pre, grants):
            if not take:
                continue
            entry = slot.entry
            if FAULTS.take("prefill_fail"):
                self.counters.inc("serve.fault_prefill_fail")
                entry.prefill_attempts += 1
                self.counters.inc("serve.prefill_retries")
                TELEMETRY.event(
                    "serve.prefill_retry", request_id=entry.request_id,
                    parent=self._req_spans.get(entry.request_id),
                    attempt=entry.prefill_attempts, chunk_start=slot.filled,
                )
                if entry.prefill_attempts >= self.config.prefill_attempts:
                    self._release_slot(slot)
                    self._finish(
                        entry, Outcome.PREFILL_FAILED, tokens=None,
                        detail="prefill failed after "
                               f"{entry.prefill_attempts} attempts "
                               f"({slot.filled}/{self.T} tokens prefilled)",
                    )
                continue  # retry next iteration, from this same chunk
            chunks.append((slot, self._next_chunk_fused(slot.filled)))
        return chunks

    def _advance_dispatched_chunks(self, chunks, final, flogits,
                                   tok_on_device: bool = False) -> None:
        """Post-dispatch bookkeeping for one fused iteration's prefill
        chunks, shared by the plain and SPECULATIVE dispatches: advance
        the fill frontier, slice publish ring seams from the batched
        cache, and transition final-chunk rows to decode AT DISPATCH —
        the row's cache is fully written and its first image token is in
        the in-flight samples, so the next iteration dispatches it as a
        decode row; the token VALUE lands in ``entry.generated`` at
        readback. The per-row terminal logits (the prefix cache's
        full-hit payload) are captured on the warm final class. The
        plain fused path marks the first sample as riding the device
        (``tok_on_device`` — the lookahead seam); the speculative path
        reads it back synchronously the same iteration instead."""
        for s, c in chunks:
            s.filled += c
            self._maybe_snapshot(s, self.cache, s.index)
            if final[s.index]:
                if self.prefix is not None and flogits is not None:
                    s.final_logits = flogits[s.index][None]
                TELEMETRY.end(s.prefill_span, outcome="completed")
                s.prefill_span = None
                s.phase = _DECODE
                s.pos = self.T
                s.tok_on_device = tok_on_device

    def _advance_prefills(self) -> bool:
        """Run this iteration's budgeted prefill chunks: in-progress
        prefills are served head-of-line by effective priority, each
        granted tokens by the ``TokenBudget`` policy after decode's share.
        The ``prefill_fail`` fault fires PER CHUNK; a retry resumes from
        the last completed chunk (``slot.filled`` is never rolled back),
        and exhausting ``prefill_attempts`` is the same typed
        ``prefill_failed`` outcome as the monolithic path."""
        pre = [s for s in self.slots if s and s.phase == _PREFILL]
        if not pre:
            return False
        pre.sort(key=lambda s: (
            -self.sched.effective_priority(s.entry), s.admit_seq
        ))
        n_decode = sum(
            1 for s in self.slots if s and s.phase == _DECODE
        )
        grants = self.budget.plan(n_decode, [self.T - s.filled for s in pre])
        worked = False
        for slot, grant in zip(pre, grants):
            entry = slot.entry
            req_span = self._req_spans.get(entry.request_id)
            while grant > 0 and self.slots[slot.index] is slot:
                c = self._next_chunk(slot.filled)
                if FAULTS.take("prefill_fail"):
                    self.counters.inc("serve.fault_prefill_fail")
                    entry.prefill_attempts += 1
                    self.counters.inc("serve.prefill_retries")
                    TELEMETRY.event(
                        "serve.prefill_retry", request_id=entry.request_id,
                        parent=req_span, attempt=entry.prefill_attempts,
                        chunk_start=slot.filled,
                    )
                    if entry.prefill_attempts >= self.config.prefill_attempts:
                        self._release_slot(slot)
                        self._finish(
                            entry, Outcome.PREFILL_FAILED, tokens=None,
                            detail="prefill failed after "
                                   f"{entry.prefill_attempts} attempts "
                                   f"({slot.filled}/{self.T} tokens "
                                   "prefilled)",
                        )
                    break  # retry next iteration, from this same chunk
                worked = True
                self.counters.inc("serve.prefill_chunks")
                final = slot.filled + c >= self.T
                chunk = jax.lax.dynamic_slice_in_dim(
                    slot.internal, slot.filled, c, axis=1
                )
                with TELEMETRY.span(
                    "serve.prefill_chunk",
                    request_id=entry.request_id, parent=slot.prefill_span,
                    start=slot.filled, tokens=c,
                ):
                    self.dispatches += 1
                    self.counters.inc("serve.dispatches")
                    if final:
                        key = jax.random.fold_in(
                            jax.random.key(entry.request.seed), self.T
                        )
                        slot.cache1, tok, img = _prefill_last_jit(
                            self.dalle, self.params, slot.cache1, chunk,
                            jnp.int32(slot.filled), self.k_img, key,
                            self.config.temperature,
                        )
                        if self.prefix is not None:
                            slot.final_logits = img
                        tok0 = int(tok[0])
                    else:
                        slot.cache1 = _prefill_chunk_jit(
                            self.dalle, self.params, slot.cache1, chunk,
                            jnp.int32(slot.filled),
                        )
                        # sync the chunk before leaving its span: chunks
                        # are the budgeted unit of work, so letting their
                        # futures pile up behind the per-iteration decode
                        # readback would re-create exactly the unbounded
                        # decode stall this scheduler exists to prevent
                        # (the backlog drains in one spike at the next
                        # hard sync — measured on CPU as a final-chunk
                        # iteration costing several chunks' latency). The
                        # sync also makes serve.prefill_chunk_s a real
                        # chunk-latency histogram.
                        jax.block_until_ready(slot.cache1)
                slot.filled += c
                grant -= c
                # page-boundary ring seams for the publish payload —
                # captured from the private cache while it exists
                self._maybe_snapshot(slot, slot.cache1, 0)
                if final:
                    self._finish_prefill(slot, tok0)
                    break
        return worked

    def _finish_prefill(self, slot: _Slot, tok0: int) -> None:
        """The final chunk sampled the first image token: land the batch-1
        cache in the slot's row of the batched cache and transition to the
        decode phase — the chunked analog of the monolithic admission
        tail."""
        entry = slot.entry
        now = self.clock.now()
        req_span = self._req_spans.get(entry.request_id)
        TELEMETRY.end(slot.prefill_span, outcome="completed")
        slot.prefill_span = None
        with TELEMETRY.span(
            "serve.slot_insert",
            request_id=entry.request_id, parent=req_span, slot=slot.index,
        ):
            self.cache = insert_decode_cache(self.cache, slot.cache1, slot.index)
        slot.cache1 = None
        slot.internal = None
        entry.generated = [tok0]
        self._commit_tokens(1)
        slot.tok = tok0
        slot.pos = self.T
        slot.phase = _DECODE
        slot.tok_on_device = False
        self._record_first_token(entry, now)
        if len(entry.generated) >= entry.effective_max_new:
            self._complete(slot)

    # ------------------------------------------------------ fused iteration

    def _fused_iteration(self) -> bool:
        """One TokenBudget iteration as ONE device dispatch
        (``_iteration_jit``): the host assembles per-row DESCRIPTORS —
        decode rows for every dispatchable decoding slot (page growth and
        preemption exactly as ``_decode_once``), one prefill chunk for
        each granted prefilling slot (``TokenBudget.plan_iteration``;
        ``prefill_fail`` still fires at CHUNK granularity per row, retry
        resuming from ``slot.filled``) — and scatters positions and
        fold-in keys; token VALUES stay on device (decode inputs ride the
        previous iteration's sample array, chunks are gathered in-trace
        from the prompts buffer). Lookahead semantics are unchanged: with
        it on, this iteration's samples are read back next iteration, so
        a final chunk's first image token flows into its own decode phase
        without ever visiting the host.

        This deliberately PARALLELS ``_decode_once``/``_dispatch_decode``
        rather than sharing helpers: the two modes differ in pending
        structure (bare slots vs (slot, kind) tuples), chunk handling,
        and transition timing, and the split scheduler is the path
        slated for retirement once fused mode is TPU-measured — folding
        them together would couple a frozen, pinned code path to one
        still expected to evolve. A fix to genuinely shared logic (the
        page-growth/preemption loop, the lookahead swap) currently needs
        applying in both."""
        cfg = self.config
        if FAULTS.take("decode_stall"):
            self.counters.inc("serve.fault_decode_stall")
            TELEMETRY.event(
                "serve.decode_stall", penalty_s=cfg.stall_penalty_s
            )
            self.clock.advance(cfg.stall_penalty_s)
        pending = self._pending
        with TELEMETRY.span("serve.step.plan"):
            # a pending FINAL-chunk sample counts like a decode sample: it
            # becomes generated[0] at readback (completion is count-based)
            in_flight = (
                set() if pending is None else {id(s) for s, _ in pending[1]}
            )
            dispatchable = [
                s for s in self.slots
                if s and s.phase == _DECODE
                and len(s.entry.generated) + (1 if id(s) in in_flight else 0)
                < s.entry.effective_max_new
            ]
            for slot in sorted(
                dispatchable,
                key=lambda s: -self.sched.effective_priority(s.entry),
            ):
                if self.slots[slot.index] is not slot:
                    continue
                # pages covering [0, pos], minus the prefix pages the slot
                # maps SHARED (charged to the index, not to this request)
                needed = slot.pos // self.page + 1 - len(slot.shared_nodes)
                deficit = needed - self.pool.held(slot.entry.request_id)
                if deficit > 0 and not self._alloc_or_preempt(slot, deficit):
                    continue
            dispatchable = [s for s in dispatchable if self.slots[s.index] is s]

            chunks = self._plan_fused_prefills(len(dispatchable))

        worked = False
        with TELEMETRY.span(
            "serve.iteration",
            n_decode=len(dispatchable), n_prefill=len(chunks),
            lookahead=cfg.decode_lookahead,
        ) if (dispatchable or chunks) else contextlib.nullcontext():
            new_pending = None
            if dispatchable or chunks:
                worked = True
                new_pending = self._dispatch_fused(dispatchable, chunks,
                                                   pending)
            if cfg.decode_lookahead:
                prev, self._pending = pending, new_pending
            else:
                prev, self._pending = new_pending, None
            if prev is not None:
                worked = True
                with TELEMETRY.span("serve.step.readback"):
                    self._fused_readback(prev)
        return worked

    def _dispatch_fused(self, dispatchable: List[_Slot],
                        chunks: List[Tuple[_Slot, int]], pending):
        """Dispatch one fused ragged iteration. Descriptor assembly only:
        start/length/final vectors, fold-in keys for the rows whose
        samples will be consumed (decode rows and final chunks), host
        token scatter only for decode inputs not already on device."""
        B = self.config.max_batch
        with TELEMETRY.span("serve.step.fold_keys"):
            start = np.zeros((B,), np.int32)
            length = np.zeros((B,), np.int32)
            final = np.zeros((B,), bool)
            host_idx: List[int] = []
            host_tok: List[int] = []
            key_idx: List[int] = []
            key_list = []
            entries: List[Tuple[_Slot, str]] = []
            for s in dispatchable:
                start[s.index] = s.pos
                length[s.index] = 1
                key_idx.append(s.index)
                key_list.append(jax.random.fold_in(
                    jax.random.key(s.entry.request.seed), s.pos + 1
                ))
                if pending is None or not s.tok_on_device:
                    host_idx.append(s.index)
                    host_tok.append(s.tok)
                entries.append((s, _DECODE))
            for s, c in chunks:
                self.counters.inc("serve.prefill_chunks")
                start[s.index] = s.filled
                length[s.index] = c
                if s.filled + c >= self.T:
                    final[s.index] = True
                    key_idx.append(s.index)
                    key_list.append(jax.random.fold_in(
                        jax.random.key(s.entry.request.seed), self.T
                    ))
                    entries.append((s, _PREFILL))
            if dispatchable:
                self.counters.inc("serve.decode_steps")
            tok = pending[0] if pending is not None else self._zero_tok
            if host_idx:
                tok = tok.at[jnp.asarray(host_idx)].set(
                    jnp.asarray(host_tok, jnp.int32)
                )
            keys = self._filler_keys
            if key_idx:
                keys = keys.at[jnp.asarray(key_idx)].set(jnp.stack(key_list))
            jit_args = (
                self.dalle, self.params, self.cache, self._prompts,
                tok, jnp.asarray(start), jnp.asarray(length), jnp.asarray(final),
                keys, self._W, self.k_img, self.config.temperature,
                bool(final.any()),
            )
        self.dispatches += 1
        self.counters.inc("serve.dispatches")
        with TELEMETRY.span("serve.step.dispatch"):
            self.cache, samples, flogits = _iteration_jit(*jit_args)
        for s in self.slots:
            if s is not None and s.phase == _DECODE:
                s.tok_on_device = False
        for s in dispatchable:
            s.pos += 1
            s.tok_on_device = True
        self._advance_dispatched_chunks(
            chunks, final, flogits, tok_on_device=True
        )
        return samples, entries

    def _fused_readback(self, prev) -> None:
        """Apply one fused iteration's host decisions: record decode
        tokens (dropping rows terminated since dispatch — at-readback-time
        semantics, as in ``_readback``) and land final-chunk first tokens,
        transitioning those slots to the decode phase."""
        samples, entries = prev
        samples = np.asarray(samples)
        committed = 0
        for s, kind in entries:
            if self.slots[s.index] is not s:
                continue  # terminated/evicted while the step was in flight
            committed += 1
            if kind == _DECODE:
                s.tok = int(samples[s.index])
                s.entry.generated.append(s.tok)
                if len(s.entry.generated) >= s.entry.effective_max_new:
                    self._complete(s)
            else:
                self._finish_prefill_fused(s, int(samples[s.index]))
        self._commit_tokens(committed)

    def _finish_prefill_fused(self, slot: _Slot, tok0: int) -> None:
        """Readback half of a fused prefill completion: the phase
        transition (and the prefill span's end) happened at DISPATCH
        (``_dispatch_fused``), and the slot may since have been
        dispatched as a decode row with its own sample in flight — so
        this records the token value and the TTFT, and must NOT touch
        phase/pos/tok_on_device."""
        entry = slot.entry
        entry.generated = [tok0]
        slot.tok = tok0
        self._record_first_token(entry, self.clock.now())
        if len(entry.generated) >= entry.effective_max_new:
            self._complete(slot)

    # -------------------------------------------------- speculative decode

    def _spec_iteration(self) -> bool:
        """One SPECULATIVE TokenBudget iteration (ROADMAP 2): the same
        descriptor assembly as ``_fused_iteration``, except every decode
        row becomes a VERIFY row of width 1 + min(spec_k, remaining - 1)
        — up to spec_k self-drafted tokens checked by exact-match
        acceptance in the single ragged dispatch — and the iteration is
        SYNCHRONOUS: the sample matrix and per-row accepted counts are
        read back before the next dispatch is assembled, because the
        next descriptors must start at the accepted frontier (the
        rollback is descriptor anchoring; ops/attention.py,
        ops/layers.py). The readback the lookahead seam used to hide is
        amortized over up to spec_k+1 committed tokens per row per step;
        ``decode_lookahead`` is a no-op here and ``self._pending`` stays
        None (the seam carries its k samples WITHIN the iteration).

        The TokenBudget charges the decode lane the full VERIFY widths
        (the tokens the dispatch actually computes); progress — request
        completion, tokens/sec, the accept histograms — is accounted in
        ACCEPTED tokens (scheduler.TokenBudget docstring).

        The ``spec_verify_abort`` fault (a drafter failure) degrades ONE
        iteration to plain decode — verify width 1, drafts ignored —
        through the SAME jit signature, so the fallback can never
        recompile; output is bit-identical by construction (a width-1
        verify row IS a plain decode row), and the degradation is
        counted (``serve.spec.fallbacks``)."""
        cfg = self.config
        if FAULTS.take("decode_stall"):
            self.counters.inc("serve.fault_decode_stall")
            TELEMETRY.event(
                "serve.decode_stall", penalty_s=cfg.stall_penalty_s
            )
            self.clock.advance(cfg.stall_penalty_s)
        with TELEMETRY.span("serve.step.plan"):
            dispatchable = [
                s for s in self.slots
                if s and s.phase == _DECODE
                and len(s.entry.generated) < s.entry.effective_max_new
            ]
            spec_on = True
            if dispatchable and FAULTS.take("spec_verify_abort"):
                spec_on = False
                self.counters.inc("serve.fault_spec_verify_abort")
                self.counters.inc("serve.spec.fallbacks")
            widths: Dict[int, int] = {}
            for s in dispatchable:
                remaining = s.entry.effective_max_new - len(s.entry.generated)
                # capping the verify width at the remaining budget keeps the
                # worst-case page demand identical to plain decode (the last
                # written position never passes T + max_new - 2). The
                # EFFECTIVE spec_k (controller-adjustable, <= the static
                # cfg.spec_k the jit was traced with) is pure row data — the
                # adaptation channel that cannot recompile (DESIGN §8.6)
                widths[id(s)] = 1 if not spec_on else min(
                    self._eff_spec_k + 1, remaining
                )
            for slot in sorted(
                dispatchable,
                key=lambda s: -self.sched.effective_priority(s.entry),
            ):
                if self.slots[slot.index] is not slot:
                    continue
                # pages covering the whole verify block [0, pos + k - 1],
                # minus the prefix pages the slot maps shared
                k_b = widths[id(slot)]
                needed = (
                    (slot.pos + k_b - 1) // self.page + 1
                    - len(slot.shared_nodes)
                )
                deficit = needed - self.pool.held(slot.entry.request_id)
                if deficit > 0 and not self._alloc_or_preempt(slot, deficit):
                    continue
            dispatchable = [s for s in dispatchable if self.slots[s.index] is s]

            # decode charged at VERIFY width: a speculative row occupies its
            # whole block of the iteration's token budget, so prefill grants
            # shrink exactly as if that many plain decode rows ran
            chunks = self._plan_fused_prefills(
                sum(widths[id(s)] for s in dispatchable)
            )

        if not dispatchable and not chunks:
            return False
        drafted = sum(widths[id(s)] - 1 for s in dispatchable)
        with TELEMETRY.span(
            "serve.iteration",
            n_decode=len(dispatchable), n_prefill=len(chunks),
            lookahead=False, spec=spec_on,
        ):
            with TELEMETRY.span(
                "serve.spec_verify",
                n_verify=len(dispatchable), drafted=drafted,
            ):
                prev = self._dispatch_spec(dispatchable, widths, chunks)
                with TELEMETRY.span("serve.step.readback"):
                    self._spec_readback(prev)
        return True

    def _dispatch_spec(self, verifies: List[_Slot], widths: Dict[int, int],
                       chunks: List[Tuple[_Slot, int]]):
        """Dispatch one speculative fused iteration: descriptor assembly
        only — sampling keys derive in-trace from the per-slot base keys
        (column j of a verify row uses ``fold_in(key(seed), pos+j+1)``,
        the SAME key the sequential decode step at that position would
        use, and the key the in-trace drafter samples d_j with —
        exact-match acceptance compares like with like). Sync mode:
        input tokens are always host-scattered (the accepted-last token
        lives at a data-dependent column of the previous sample
        matrix)."""
        B, W = self.config.max_batch, self._W
        with TELEMETRY.span("serve.step.fold_keys"):
            start = np.zeros((B,), np.int32)
            length = np.zeros((B,), np.int32)
            final = np.zeros((B,), bool)
            host_idx: List[int] = []
            host_tok: List[int] = []
            entries: List[Tuple[_Slot, str, int]] = []
            for s in verifies:
                k_b = widths[id(s)]
                start[s.index] = s.pos
                length[s.index] = k_b
                host_idx.append(s.index)
                host_tok.append(s.tok)
                entries.append((s, _DECODE, k_b))
            for s, c in chunks:
                self.counters.inc("serve.prefill_chunks")
                start[s.index] = s.filled
                length[s.index] = c
                if s.filled + c >= self.T:
                    final[s.index] = True
                    entries.append((s, _PREFILL, c))
            if verifies:
                self.counters.inc("serve.decode_steps")
            # the token scatter rides a FIXED padded shape (index vector
            # padded to B with an out-of-range drop sentinel): a speculative
            # trace mixes every (verify-width, final-chunk) combination, and
            # an un-padded scatter would compile one tiny module per distinct
            # row count — in-trace compiles the zero-compile contract
            # forbids. Sampling keys are derived entirely IN-TRACE from
            # self._base_keys (written at admission), no per-iteration key
            # assembly at all.
            tok = self._zero_tok
            if host_idx:
                pad = B - len(host_idx)
                tok = tok.at[jnp.asarray(host_idx + [B] * pad)].set(
                    jnp.asarray(host_tok + [0] * pad, jnp.int32), mode="drop"
                )
            jit_args = (
                self.dalle, self.params, self.cache, self._prompts,
                tok, jnp.asarray(start), jnp.asarray(length), jnp.asarray(final),
                self._base_keys, W, self.k_img, self.config.temperature,
                bool(final.any()), self.config.spec_k,
                self.config.spec_draft_depth,
            )
        self.dispatches += 1
        self.counters.inc("serve.dispatches")
        with TELEMETRY.span("serve.step.dispatch"):
            self.cache, samples, accepted, flogits = _spec_iteration_jit(
                *jit_args
            )
        self._advance_dispatched_chunks(chunks, final, flogits)
        return samples, accepted, entries

    def _spec_readback(self, prev) -> None:
        """Apply one speculative iteration's host decisions: commit each
        verify row's accepted prefix (1..k tokens, bit-identical to what
        sequential decode would have produced — exact-match acceptance),
        advance the host position to the accepted frontier (the next
        dispatch's descriptors realize the rewind), land final-chunk
        first tokens, and tally the draft/accept accounting."""
        samples, accepted, entries = prev
        samples = np.asarray(samples)
        accepted = np.asarray(accepted)
        committed = 0
        for s, kind, k_b in entries:
            if self.slots[s.index] is not s:
                continue  # terminated/evicted by the termination sweep
            committed += 1 if kind != _DECODE else int(accepted[s.index])
            if kind == _DECODE:
                acc = int(accepted[s.index])
                assert 1 <= acc <= k_b, (
                    f"accepted count {acc} outside verify width "
                    f"[1, {k_b}] — the acceptance scan is corrupt"
                )
                toks = [int(t) for t in samples[s.index, :acc]]
                s.entry.generated.extend(toks)
                s.tok = toks[-1]
                s.pos += acc
                n_drafted = k_b - 1
                self._spec_drafted += n_drafted
                self._spec_accepted += acc - 1
                self.counters.inc("serve.spec.drafted", n_drafted)
                self.counters.inc("serve.spec.accepted", acc - 1)
                self.counters.inc(
                    "serve.spec.rejected", n_drafted - (acc - 1)
                )
                self.histograms.observe(
                    "serve.spec_accepted_per_step", float(acc)
                )
                if len(s.entry.generated) >= s.entry.effective_max_new:
                    self._complete(s)
            else:
                self._finish_prefill_fused(s, int(samples[s.index, k_b - 1]))
        self._commit_tokens(committed)

    def _record_first_token(self, entry: Entry, now: float) -> None:
        """TTFT bookkeeping: set once per request (a preempted request's
        replay regenerates the token — the client-visible first token was
        the FIRST production)."""
        if entry.ttft_s is not None:
            return
        entry.ttft_s = now - entry.submit_time
        self.histograms.observe("serve.ttft_s", entry.ttft_s)
        if self.prefix is not None:
            # TTFT split by hit class: what the zipf bench's cached-vs-
            # cold comparison reads (docs/DESIGN.md §9)
            if entry.hit_class == "full":
                self.histograms.observe("serve.ttft_full_hit_s", entry.ttft_s)
            elif entry.hit_class == "partial":
                self.histograms.observe(
                    "serve.ttft_partial_hit_s", entry.ttft_s
                )
            else:
                self.histograms.observe("serve.ttft_cold_s", entry.ttft_s)
        TELEMETRY.event(
            "serve.first_token", request_id=entry.request_id,
            parent=self._req_spans.get(entry.request_id),
            ttft_s=entry.ttft_s,
        )

    # -------------------------------------------------------------- decode

    def _decode_once(self) -> bool:
        cfg = self.config
        if FAULTS.take("decode_stall"):
            self.counters.inc("serve.fault_decode_stall")
            TELEMETRY.event(
                "serve.decode_stall", penalty_s=cfg.stall_penalty_s
            )
            self.clock.advance(cfg.stall_penalty_s)
        pending = self._pending
        with TELEMETRY.span("serve.step.plan"):
            in_flight = (
                set() if pending is None else {id(s) for s in pending[1]}
            )
            # a slot whose in-flight sample will hit its budget at readback is
            # NOT dispatched again (completion is count-based: the host knows
            # the tally without reading token values — the lookahead seam)
            dispatchable = [
                s for s in self.slots
                if s and s.phase == _DECODE
                and len(s.entry.generated) + (1 if id(s) in in_flight else 0)
                < s.entry.effective_max_new
            ]
            # page growth: writing position ``pos`` needs pages [0, pos//page];
            # allocate on boundary crossings, preempting on failure
            for slot in sorted(
                dispatchable,
                key=lambda s: -self.sched.effective_priority(s.entry),
            ):
                if self.slots[slot.index] is not slot:
                    continue  # evicted by a previous iteration of this loop
                # pages covering [0, pos], minus the prefix pages the slot
                # maps SHARED (charged to the index, not to this request)
                needed = slot.pos // self.page + 1 - len(slot.shared_nodes)
                deficit = needed - self.pool.held(slot.entry.request_id)
                if deficit > 0 and not self._alloc_or_preempt(slot, deficit):
                    continue  # the requester itself was evicted
            dispatchable = [s for s in dispatchable if self.slots[s.index] is s]
        worked = False
        # ONE span per dispatched decode step; with lookahead it brackets
        # the dispatch of step N AND the (synchronizing) readback of step
        # N-1 — opened/closed host-side, adding no device syncs of its
        # own. A trailing readback with nothing left to dispatch drains
        # outside any span.
        with TELEMETRY.span(
            "serve.decode_step",
            n_active=len(dispatchable), lookahead=cfg.decode_lookahead,
        ) if dispatchable else contextlib.nullcontext():
            new_pending = None
            if dispatchable:
                worked = True
                self.counters.inc("serve.decode_steps")
                new_pending = self._dispatch_decode(dispatchable, pending)
            if cfg.decode_lookahead:
                prev, self._pending = pending, new_pending
            else:
                prev, self._pending = new_pending, None
            if prev is not None:
                worked = True
                with TELEMETRY.span("serve.step.readback"):
                    self._readback(prev)
        return worked

    def _dispatch_decode(self, dispatchable: List[_Slot], pending):
        """Dispatch one vector-position decode step. Input tokens come
        from the previous step's still-on-device samples where possible
        (``tok_on_device``); only host-decided tokens (a fresh prefill's
        first token, a replay) are scattered in. The per-slot fold-in keys
        are computed for ACTIVE slots only and scattered over the cached
        filler-key array."""
        B = self.config.max_batch
        with TELEMETRY.span("serve.step.fold_keys"):
            pos = np.zeros((B,), np.int32)
            host_idx: List[int] = []
            host_tok: List[int] = []
            key_idx: List[int] = []
            key_list = []
            for s in dispatchable:
                pos[s.index] = s.pos
                key_idx.append(s.index)
                # the token at position pos+1 is drawn from this key — pure
                # (seed, position) addressing, independent of batch history
                key_list.append(jax.random.fold_in(
                    jax.random.key(s.entry.request.seed), s.pos + 1
                ))
                if pending is None or not s.tok_on_device:
                    host_idx.append(s.index)
                    host_tok.append(s.tok)
            tok = pending[0] if pending is not None else self._zero_tok
            if host_idx:
                tok = tok.at[jnp.asarray(host_idx)].set(
                    jnp.asarray(host_tok, jnp.int32)
                )
            keys = self._filler_keys.at[jnp.asarray(key_idx)].set(
                jnp.stack(key_list)
            )
            jit_args = (
                self.dalle, self.params, self.cache,
                tok, jnp.asarray(pos), keys,
                self.k_img, self.config.temperature,
            )
        self.dispatches += 1
        self.counters.inc("serve.dispatches")
        with TELEMETRY.span("serve.step.dispatch"):
            self.cache, samples = _decode_jit(*jit_args)
        for s in self.slots:
            if s is not None and s.phase == _DECODE:
                s.tok_on_device = False
        for s in dispatchable:
            s.pos += 1
            s.tok_on_device = True
        return samples, list(dispatchable)

    def _commit_tokens(self, n: int) -> None:
        """Count ``n`` tokens appended to some ``entry.generated`` — the
        one place tokens committed are tallied (a preempted request's
        replay commits, and counts, its tokens again)."""
        self.tokens_committed += n
        self.counters.inc("serve.tokens_committed", n)

    def _readback(self, prev) -> None:
        """Read back one dispatched step's samples (the only host<-device
        sync of the loop) and apply its host decisions: record tokens,
        complete slots that hit their budget. Samples belonging to slots
        terminated or evicted since dispatch are dropped here — deadline /
        cancel semantics are defined at readback time."""
        samples, slots = prev
        samples = np.asarray(samples)
        committed = 0
        for s in slots:
            if self.slots[s.index] is not s:
                continue  # terminated/evicted while the step was in flight
            s.tok = int(samples[s.index])
            s.entry.generated.append(s.tok)
            committed += 1
            if len(s.entry.generated) >= s.entry.effective_max_new:
                self._complete(s)
        self._commit_tokens(committed)

    def _alloc_or_preempt(self, slot: _Slot, n: int) -> bool:
        """Allocate ``n`` pages for ``slot``, evicting victims until it
        fits — unreferenced prefix-index pages first (LRU; refcounted
        pages are never victims), then running requests. Returns False
        when the requester itself was the victim."""
        while True:
            blocked = FAULTS.take("page_exhaust")
            if blocked:
                self.counters.inc("serve.fault_page_exhaust")
            if not blocked and self.pool.alloc(slot.entry.request_id, n):
                return True
            if not blocked and self._reclaim_index_pages(1):
                continue
            victim = self._pick_victim()
            assert victim is not None, "requester is running, so a victim exists"
            self._preempt(victim)
            if victim is slot:
                return False

    def _pick_victim(self) -> Optional[_Slot]:
        """Lowest effective priority dies first; within a priority the
        YOUNGEST admission dies (it has the least sunk prefill+decode work
        and the shortest replay). Mid-prefill slots are eligible victims —
        their pages free between chunks like any other eviction."""
        running = [s for s in self.slots if s]
        if not running:
            return None
        return min(
            running,
            key=lambda s: (self.sched.effective_priority(s.entry), -s.admit_seq),
        )

    def _preempt(self, slot: _Slot) -> None:
        self._release_slot(slot)
        entry = slot.entry
        entry.preempt_count += 1
        self.counters.inc("serve.preempted")
        TELEMETRY.event(
            "serve.evict", request_id=entry.request_id,
            parent=self._req_spans.get(entry.request_id),
            preempt_count=entry.preempt_count,
            tokens_discarded=len(entry.generated),
        )
        if entry.preempt_count > self.config.max_preemptions:
            self._finish(
                entry, Outcome.PREEMPT_CAP,
                tokens=np.asarray(entry.generated, np.int32),
                detail=f"evicted {entry.preempt_count} times "
                       f"(cap {self.config.max_preemptions})",
            )
            return
        # full restart: partial tokens are discarded — the (seed, position)
        # sampling keys regenerate them bit-identically on replay
        entry.generated = []
        entry.admit_time = None
        self.sched.requeue(entry)

    # ----------------------------------------------------------- plumbing

    def _release_slot(self, slot: _Slot) -> None:
        """Return the slot's pages; for a DECODING slot additionally reset
        its batched-cache row to pristine: page pools zeroed
        (``paged_kv.reset_rows`` — stale K/V must not leak to the next
        tenant), page tables back to identity
        (``paged_kv.reset_table_rows``), and every other per-row leaf
        (indices, shift history) zeroed — the catch-all default, so a new
        cache leaf is reset-safe by construction. A SPLIT-mode PREFILLING
        slot never wrote its batched row (its chunks live in a private
        batch-1 cache, dropped here) so it skips the device reset; a
        FUSED-mode prefilling slot wrote its chunks in place and resets
        like a decoding slot.

        Prefix-cache discipline: shared mappings are RELEASED (refcount
        only — the pages live in arena rows the reset below cannot name;
        ``paged_kv.reset_rows``), and the row bound is asserted so an
        arena row can never be zeroed through this path.

        One ``serve.step.release`` span per call: a release happens once
        per finished (or evicted) request, nested in whichever phase of
        the iteration decided it."""
        with TELEMETRY.span("serve.step.release"):
            if slot.shared_nodes:
                self.prefix.release(slot.shared_nodes)
                slot.shared_nodes = []
            self.pool.free_all(slot.entry.request_id)
            idx = slot.index
            assert 0 <= idx < self.config.max_batch, (
                f"slot reset named row {idx} outside the slot rows "
                f"[0, {self.config.max_batch}) — arena rows are owned by the "
                "prefix index and are never reset here"
            )
            if slot.phase == _PREFILL:
                TELEMETRY.end(
                    slot.prefill_span, outcome="aborted", filled=slot.filled
                )
                slot.prefill_span = None
                slot.cache1 = None
                slot.internal = None
                if not self.fused:
                    # split mode: the chunks lived in a private batch-1 cache
                    # (dropped above); the batched row was never written
                    self.slots[idx] = None
                    return
                # fused mode: the row's chunks were written straight into the
                # batched cache — fall through to the same device reset a
                # decoding slot gets

            def fn(path, x):
                key = getattr(path[-1], "key", None)
                if key in paged_kv.POOL_LEAF_KEYS:
                    return paged_kv.reset_rows(x, idx)
                if key == "page_table":
                    return paged_kv.reset_table_rows(x, idx)
                return x.at[idx].set(jnp.zeros_like(x[idx]))

            self.cache = jax.tree_util.tree_map_with_path(fn, self.cache)
            self.slots[slot.index] = None

    def _complete(self, slot: _Slot) -> None:
        if self.prefix is not None:
            # publish BEFORE release: the copies read the slot's native
            # pages, which the release reset zeroes
            self._publish(slot)
        self._release_slot(slot)
        if self.postdecode is not None:
            # tokens complete but the REQUEST is not: it transitions into
            # the post-decode pipeline (slot and pages already released —
            # staged work holds no kv), staying live until a stage
            # outcome lands. serve.completed moves with it: counted at
            # the pipeline's COMPLETED, so the counter keeps meaning
            # "requests fully served".
            self.postdecode.enqueue(
                slot.entry, np.asarray(slot.entry.generated, np.int32)
            )
            return
        self.counters.inc("serve.completed")
        self._finish(
            slot.entry, Outcome.COMPLETED,
            tokens=np.asarray(slot.entry.generated, np.int32),
        )

    def _finish_staged(self, entry: Entry, outcome: Outcome,
                       tokens: Optional[np.ndarray],
                       image=None, score=None, detail: str = "") -> None:
        """Terminal sink for the post-decode pipeline — every staged
        request ends here with its typed outcome and whatever results
        its completed stages produced."""
        if outcome is Outcome.COMPLETED:
            self.counters.inc("serve.completed")
        self._finish(entry, outcome, tokens, detail=detail,
                     image=image, rerank_score=score)

    def _reject(self, entry: Entry, reason: RejectReason) -> RequestResult:
        self.counters.inc("serve.rejected")
        self.counters.inc(f"serve.rejected.{reason.value}")
        TELEMETRY.end(
            self._req_spans.pop(entry.request_id, None),
            outcome=Outcome.REJECTED.value, reject_reason=reason.value,
        )
        self.histograms.observe("serve.request_latency_s", 0.0)
        # load-typed rejections carry a backoff hint scaled by current
        # pressure (fleet-wide when routed, this engine's pool alone when
        # standalone); DEMAND_EXCEEDS_POOL is permanent — no hint
        hint = None
        if reason is RejectReason.QUEUE_FULL:
            occ = (
                self._fleet_occupancy()
                if self._fleet_occupancy is not None
                else self.pool.occupancy
            )
            hint = retry_after_hint(occ)
        result = RequestResult(
            request_id=entry.request_id,
            outcome=Outcome.REJECTED,
            reject_reason=reason,
            total_latency_s=0.0,
            retry_after_s=hint,
        )
        self.results[entry.request_id] = result
        self._outcome_counts[Outcome.REJECTED] += 1
        return result

    def _finish(self, entry: Entry, outcome: Outcome,
                tokens: Optional[np.ndarray], detail: str = "",
                image=None, rerank_score=None) -> None:
        now = self.clock.now()
        self._live.discard(entry.request_id)
        if outcome is not Outcome.COMPLETED:
            self.counters.inc(f"serve.{outcome.value}")
        # the lifecycle span ends HERE, in its typed outcome — the flight
        # recorder's per-request chain is submit(B) .. outcome(E)
        TELEMETRY.end(
            self._req_spans.pop(entry.request_id, None),
            outcome=outcome.value,
            n_tokens=0 if tokens is None else int(len(tokens)),
            preempt_count=entry.preempt_count,
            detail=detail,
        )
        self.histograms.observe("serve.request_latency_s", now - entry.submit_time)
        if outcome is Outcome.COMPLETED:
            self.histograms.observe(
                "serve.completed_latency_s", now - entry.submit_time
            )
        self._outcome_counts[outcome] += 1
        self.results[entry.request_id] = RequestResult(
            request_id=entry.request_id,
            outcome=outcome,
            tokens=tokens,
            preempt_count=entry.preempt_count,
            prefill_attempts=entry.prefill_attempts,
            clamped_max_new_tokens=(
                entry.effective_max_new if entry.clamped else None
            ),
            queue_latency_s=(
                None if entry.admit_time is None
                else entry.admit_time - entry.submit_time
            ),
            ttft_s=entry.ttft_s,
            total_latency_s=now - entry.submit_time,
            image=image,
            rerank_score=rerank_score,
            detail=detail,
        )

    def verify_invariants(self, idle: bool = False) -> None:
        """Assert the typed-outcome accounting invariant, raising
        ``AssertionError`` on violation. Public because it is a RELEASE
        and HEALTH surface, not just a test helper: the smoke gates
        (tools/serve_smoke.py, tools/telemetry_smoke.py) assert it after
        every pass, and the replica router (serving/router.py) probes it
        every scheduling iteration — an engine that breaks its own
        accounting is declared DEAD and failed over, because a lost or
        duplicated request is exactly the corruption the fleet exists to
        prevent.

        Always checked (valid mid-flight):
          * every submitted request is live XOR has exactly one result;
          * live requests are exactly the queued + running sets;
          * every page holder is a running request (or the prefix index);
          * outcome counts sum to the result count;
          * prefix refcount accounting: the index's budget charge equals
            its page count, arena pages neither leak nor alias, and the
            sum of node refcounts equals the shared table mappings the
            live slots hold.
        With ``idle=True`` (after ``run()``): additionally nothing queued
        or running, no live in-flight decode step, and the pool drained
        down to exactly the index's pages (the cache SURVIVES drain —
        cross-request reuse is its purpose; no request page leaks).

        Cost: O(live requests + slots), independent of how many results a
        long-lived engine has accumulated (outcome tallies are
        incremental) — cheap enough for the router to probe every
        scheduling iteration."""
        running_ids = {s.entry.request_id for s in self.slots if s}
        queued_ids = self.sched.ids()
        staged_ids = (
            set() if self.postdecode is None else set(self.postdecode.ids())
        )
        both = [rid for rid in self._live if rid in self.results]
        assert not both, f"request both live and finished: {sorted(both)}"
        assert len(self.results) + len(self._live) == self._submitted, (
            f"{self._submitted} submitted but {len(self.results)} results "
            f"+ {len(self._live)} live"
        )
        assert self._live == queued_ids | running_ids | staged_ids, (
            f"live set {sorted(self._live)} != queued {sorted(queued_ids)} "
            f"| running {sorted(running_ids)} | staged {sorted(staged_ids)}"
        )
        assert not staged_ids & (queued_ids | running_ids), (
            f"request staged while queued/running: "
            f"{sorted(staged_ids & (queued_ids | running_ids))}"
        )
        assert self.pool.holders() - {PREFIX_HOLDER} <= running_ids, (
            "page leak: pages held by non-running requests "
            f"{sorted(self.pool.holders() - {PREFIX_HOLDER} - running_ids)}"
        )
        index_pages = 0
        if self.prefix is not None:
            index_pages = len(self.prefix)
            assert self.pool.held(PREFIX_HOLDER) == index_pages, (
                f"prefix budget drift: index holds {index_pages} pages "
                f"but is charged {self.pool.held(PREFIX_HOLDER)}"
            )
            self.prefix.verify_invariants()
            mapped = sum(len(s.shared_nodes) for s in self.slots if s)
            refs = self.prefix.total_refs()
            assert refs == mapped, (
                f"prefix refcount drift: {refs} references held but "
                f"{mapped} shared table mappings live"
            )
        outcomes = self.stats()["outcomes"]
        assert sum(outcomes.values()) == len(self.results), outcomes
        if not idle:
            return
        assert not running_ids and not queued_ids, "engine not idle"
        assert not staged_ids, (
            f"engine idle with staged post-decode work: {sorted(staged_ids)}"
        )
        # pending entries are bare slots (split) or (slot, kind) tuples
        # (fused); normalize before the identity check
        pending_slots = [] if self._pending is None else [
            s[0] if isinstance(s, tuple) else s for s in self._pending[1]
        ]
        assert not any(
            self.slots[s.index] is s for s in pending_slots
        ), "engine idle with a live in-flight decode step"
        assert self.pool.used == index_pages, (
            f"page leak: {self.pool.used} pages still held with only "
            f"{index_pages} owned by the prefix index"
        )

    # ------------------------- vitals & adaptive control (DESIGN §8.6)

    def _observe_vitals(self) -> None:
        """Push one iteration's plain-number sample set into the vitals
        windows — cumulative counters in, windowed reductions out
        (utils/vitals.py). Strictly host arithmetic."""
        occ = (
            self._fleet_occupancy()
            if self._fleet_occupancy is not None
            else self.pool.occupancy
        )
        self.vitals.observe_iteration(
            now=self.clock.now(),
            occupancy=occ,
            stage_queued=(
                0.0 if self.postdecode is None else len(self.postdecode)
            ),
            spec_drafted=self._spec_drafted,
            spec_accepted=self._spec_accepted,
            prefix_hits=self._prefix_hits,
            prefix_misses=self._prefix_misses,
            deadline_misses=self._outcome_counts[Outcome.DEADLINE_EXCEEDED],
            terminations=sum(self._outcome_counts.values()),
        )

    def _run_controller(self) -> None:
        """One controller evaluation between iterations: vitals window
        in, effective knobs out, the whole decision journaled as a
        ``serve.control.decision`` event. A raising controller (the
        ``control_stall`` fault, or a real bug) degrades every knob to
        its static default — typed, counted, and never fatal to decode
        progress."""
        snap = self.vitals.snapshot()
        self.counters.inc("serve.control.decisions")
        try:
            decision = self.controller.evaluate(self.iterations, snap)
        except Exception:
            self.counters.inc("serve.fault_control_stall")
            self.counters.inc("serve.control.stalls")
            self.controller.reset()
            decision = self.controller.record_stall(self.iterations, snap)
        if decision.changed:
            self.counters.inc("serve.control.adjustments")
        self._apply_knobs(decision)
        TELEMETRY.event(
            "serve.control.decision",
            iteration=decision.iteration,
            changed=decision.changed,
            stalled=decision.stalled,
            reasons=list(decision.reasons),
            vitals=dict(decision.vitals),
            knobs=dict(decision.knobs),
        )

    def _apply_knobs(self, decision) -> None:
        """Apply a Decision's knobs through the data-only channels (see
        serving/control.py's knob/channel table) and publish the
        effective levels as ``serve.control.*`` gauges."""
        k = decision.knobs
        if self.spec and k.get("spec_k") is not None:
            # clamp to the pre-traced ceiling: the static argument the
            # spec jit was traced with is config.spec_k, and the
            # effective width only narrows rows within it
            self._eff_spec_k = min(
                max(1, int(k["spec_k"])), self.config.spec_k
            )
        self._eff_watermark = float(k["watermark"])
        if self.budget is not None and k.get("budget") is not None:
            b = max(1, int(k["budget"]))
            if b != self.budget.budget:
                # same chunk width: grant SIZES are what the traces see;
                # only the per-iteration grant COUNT moves
                self.budget = TokenBudget(budget=b, chunk=self.budget.chunk)
        tgt = k.get("prefix_pages_target")
        if tgt is not None and self.prefix is not None:
            excess = len(self.prefix) - max(0, int(tgt))
            if excess > 0:
                self._reclaim_index_pages(
                    min(excess, self.prefix.reclaimable_pages())
                )
        self.gauges.set("serve.control.spec_k", float(self._eff_spec_k))
        self.gauges.set(
            "serve.control.budget",
            float(self.budget.budget)
            if self.budget is not None and self.budget.budget is not None
            else -1.0,
        )
        self.gauges.set("serve.control.watermark", self._eff_watermark)
        self.gauges.set(
            "serve.control.prefix_pages_target",
            -1.0 if tgt is None else float(tgt),
        )

    def _publish_gauges(self) -> None:
        self._publish_kv_gauges()
        if self.vitals is not None:
            self.vitals.publish(self.gauges)
        self.gauges.set("serve.pool_occupancy", self.pool.occupancy)
        self.gauges.set(
            "serve.running",
            sum(bool(s) and s.phase == _DECODE for s in self.slots),
        )
        self.gauges.set(
            "serve.prefilling",
            sum(bool(s) and s.phase == _PREFILL for s in self.slots),
        )
        self.gauges.set("serve.queued", len(self.sched))
        if self.postdecode is not None:
            self.gauges.set("serve.stage.queued", len(self.postdecode))
        if self.spec:
            self.gauges.set(
                "serve.spec_accept_frac",
                self._spec_accepted / self._spec_drafted
                if self._spec_drafted else 0.0,
            )
        if self.prefix is not None:
            probes = self._prefix_hits + self._prefix_misses
            self.gauges.set(
                "serve.prefix_hit_frac",
                self._prefix_hits / probes if probes else 0.0,
            )
            self.gauges.set("serve.prefix_pages", float(len(self.prefix)))


class _PrefillFault(RuntimeError):
    """Internal: a prefill_fail injection fired (transient by contract)."""


def check_accounting(engine: Engine) -> None:
    """Back-compat alias for ``Engine.verify_invariants(idle=True)`` —
    the original test-helper name, kept because tests and bench call it
    pervasively. New code should call the method."""
    engine.verify_invariants(idle=True)
