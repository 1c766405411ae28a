"""Decode KV-cache layout policy — one named decision point, observable.

The decode cache's array layout used to be an inline magic branch
(``flat = b == 8`` in ops/attention.py:_decode_caches): correct at the one
measured point, a silent perf cliff everywhere near it, and invisible to
users when it fell back. This module replaces it with a *policy*:

- ``"paged"``  — block-paged cache (ops/paged_kv.py): fixed 128-token pages
  in (b, n_pages, page, h*d) layout behind a per-sequence page table and a
  per-sequence (b,) write index. The per-step update touches one page row,
  so the update cost is a property of the CACHE, not of the batch size —
  the structural fix for the 4-D layout's whole-buffer dynamic-update-slice
  rewrites that made serving throughput non-monotone in batch (batch 32
  at 6,050 tok/s vs batch 8's 6,832 on v5e: pre-ledger note, not in
  PERF_LEDGER.jsonl, as every figure in this docstring). Also the only
  format with ragged per-sequence decode offsets (continuous batching).
- ``"flat"``  — (b, L, h*d): the measured batch-8 winner (+38% tok/s over
  4-D there, v5e 2026-07), and a measured LOSER at batches 1/4/16/32 on the
  same chip/compiler.
- ``"4d"``    — (b, L, h, d): the measured batch-1 winner (0.660 vs
  0.747 ms/token int8); its one-row update compiles to a positions-minor
  layout whose DUS tax grows with batch (trace-measured 43% of the batch-8
  decode program before the flat fix).

Default policy (the pre-ledger notes above are the provenance; no cell of
BENCHMARK.json serves yet, ROADMAP D3): 4-D at
batch 1, flat at batch 8, paged everywhere else. Batch 1 and 8 keep their
proven layouts; every other batch — where 4-D was only ever the lesser
evil — gets the format whose update cost does not scale with the buffer.

Every choice is emitted once per (format, batch) through the
``dalle_tpu.kv_policy`` logger and recorded in ``CHOICE_LOG`` so an
unexpected layout fallback is observable instead of a silent perf cliff.

Overrides, strongest first:
- ``format_override(fmt)`` context manager (how an explicit
  ``cache_format=`` argument reaches the attention layers at trace time);
- ``DALLE_TPU_KV_FORMAT`` = paged|flat|4d;
- legacy ``DALLE_TPU_FLAT_KV`` = 0|1 (maps to 4d|flat), kept for
  re-measurement scripts.

Environment overrides are read at TRACE time: flipping one under an
already-cached jit requires ``jax.clear_caches()`` (the existing
re-measurement workflow; tests do the same).
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import os
from typing import Iterator, Optional

logger = logging.getLogger("dalle_tpu.kv_policy")

FORMATS = ("paged", "flat", "4d")

DEFAULT_PAGE_SIZE = 128

# ------------------------------------------------------------ KV quant
#
# Storage quantization of the PAGED pools (ops/paged_kv.py): "int8"
# stores K/V pages as int8 with per-(token, head) symmetric scales in a
# parallel paged scale pool, quantized at append time and dequantized at
# READ time in-kernel — in the Pallas ragged path the int8 pages stream
# through VMEM and widen in registers (ops/ragged_attention.py), in the
# jnp reference path the gathered view dequantizes through the same
# formula (paged_kv.dequant), so the two paths cannot drift. The knob is
# orthogonal to the layout FORMAT above and applies to the paged format
# only (the flat/4d decode caches never consulted it — their one
# measured int8 experiment LOST on single-stream latency; see the
# measured note at the bottom of ops/attention.py. The serving engine's
# batched paged pools are a different regime: the largest HBM tenant
# under a stream-bound roofline, where halved bytes mean ~2x slots and
# ~2x prefix-cache arena at fixed HBM).
#
# Override channels, strongest first (mirroring the format channels; an
# invalid value fails TYPED at resolution time in every one of them):
# - ``quant_override(q)`` context manager (how an explicit ``kv_quant=``
#   argument — models/sampling.py:init_decode_cache, EngineConfig —
#   reaches the attention layers at trace time);
# - ``DALLE_TPU_KV_QUANT`` = none|int8;
# - default policy: "none".
#
# Parity tiers (docs/DESIGN.md §6.1): quantized-vs-quantized holds the
# standing BITWISE contract everywhere (cold vs warm prefix hit, split
# vs fused engines, preempt replay, spec decode) — quantization is a
# deterministic per-row elementwise map, so the PR 9/10/11 parity
# arguments carry over unchanged. Quantized-vs-f32 is a pinned
# token-AGREEMENT threshold (below), asserted in tests; it is never a
# bitwise claim.

QUANTS = ("none", "int8")

# pinned quantized-vs-f32 token-agreement floor (fraction of generated
# positions whose sampled token matches the unquantized run, same seed):
# asserted by tests/test_kv_quant.py and tools/serve_smoke.py.
# Position-wise agreement is chance-level after a
# first divergence, so the floor is deliberately below the typically
# observed ~1.0 on the tiny f32 CPU tier — it guards against the
# quantizer breaking (agreement collapsing toward the random-token
# floor), not against single near-tie sample flips.
KV_QUANT_TOKEN_AGREEMENT_MIN = 0.5


class InvalidKVFormatError(ValueError):
    """Raised at POLICY-RESOLUTION time for an unknown cache format (from
    ``DALLE_TPU_KV_FORMAT``, legacy ``DALLE_TPU_FLAT_KV``, or an explicit
    ``cache_format=`` argument) — a bad override must fail here, naming the
    valid formats, not as a shape error deep inside cache init. Subclasses
    ValueError so pre-existing ``except ValueError`` callers keep working."""

    def __init__(self, source: str, got: object, valid: tuple = FORMATS):
        super().__init__(
            f"{source} must be one of {valid}, got {got!r}"
        )
        self.source = source
        self.got = got
        self.valid = valid

# every (format, batch, reason) decision made this process, in order
CHOICE_LOG: list = []
_EMITTED: set = set()

# a ContextVar, not a module global: concurrent traces (a serving layer
# jitting two generations with different formats on different threads)
# must not see each other's override
_OVERRIDE: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "dalle_tpu_kv_format_override", default=None
)

_QUANT_OVERRIDE: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "dalle_tpu_kv_quant_override", default=None
)


def on_tpu() -> bool:
    """THE platform decision for kernel routing and Pallas lowering: jax's
    default backend is a TPU. Every "compiled kernel or not" choice in ops/
    resolves here — ``pallas_interpret`` for the interpret flag of every
    ``pallas_call`` and ``tpu_auto_env`` for the opt-in kernels — so a
    process that lost the chip (libtpu failing to initialise leaves jax on
    the CPU with a warning) answers the same way everywhere, and one
    assertion on this backend covers every kernel (chip_smoke.py makes it
    in each child and then checks the lowered programs for Mosaic
    custom calls). jax is imported lazily: this module stays import-light
    for pure policy callers."""
    import jax

    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """The ``interpret=`` argument of every ``pallas_call`` in ops/:
    compiled by Mosaic on a TPU, interpreted everywhere else (the CPU
    test tier). Read at trace time."""
    return not on_tpu()


def tpu_auto_env(name: str) -> bool:
    """Tri-state env gate for TPU-only optimizations: "auto" (the
    default when the variable is unset) resolves to ``on_tpu()``;
    "1"/"0" force either way. ONE parser for every such knob —
    ``DALLE_TPU_LANE_PACK`` (ops/attention.py:lane_pack_enabled),
    ``DALLE_TPU_RAGGED_KERNEL`` (ops/ragged_attention.py:use_kernel) and
    ``DALLE_TPU_SPARSE_KERNEL``
    (ops/block_sparse_attention.py:sparse_kernel_enabled) — so platform
    resolution and error wording cannot drift between them."""
    v = os.environ.get(name, "auto")
    if v not in ("auto", "0", "1"):
        raise ValueError(f"{name} must be 'auto', '0' or '1', got {v!r}")
    if v == "auto":
        return on_tpu()
    return v == "1"


# every distinct attention-implementation choice made at trace time this
# process, in order: which kernel or jnp path each attention call site
# took, and whether its Pallas kernel was compiled or interpreted. The
# selections themselves are legitimate (pattern, shape, platform); the
# record makes them observable, like CHOICE_LOG for the cache layout —
# chip_smoke.py asserts the flagship's expected routes from it.
ROUTE_LOG: list = []


def record_route(site: str, impl: str, interpret: Optional[bool] = None, **detail) -> None:
    """Note that attention call site ``site`` resolved to implementation
    ``impl`` (``interpret``: the Pallas mode, None for jnp paths; ``detail``:
    what else the choice fixed, e.g. a sliding window and the tiles its grid
    visits). Once per distinct choice, also emitted on the
    ``dalle_tpu.kv_policy`` logger."""
    entry = {"site": site, "impl": impl, "interpret": interpret, **detail}
    if entry in ROUTE_LOG:
        return
    ROUTE_LOG.append(entry)
    logger.info("attention route: %s -> %s (interpret=%s%s)", site, impl, interpret,
                "".join(f", {k}={v}" for k, v in detail.items()))


def page_size() -> int:
    """Page row count; ``DALLE_TPU_KV_PAGE_SIZE`` overrides (tests use tiny
    pages to exercise page-boundary arithmetic on small models)."""
    raw = os.environ.get("DALLE_TPU_KV_PAGE_SIZE")
    if raw in (None, ""):
        return DEFAULT_PAGE_SIZE
    size = int(raw)
    if size <= 0:
        raise ValueError(f"DALLE_TPU_KV_PAGE_SIZE must be > 0, got {raw!r}")
    return size


@contextlib.contextmanager
def format_override(fmt: Optional[str]) -> Iterator[None]:
    """Pin the cache format for every ``choose_cache_format`` call in the
    block — the trace-time channel for an explicit ``cache_format=``
    argument (models/sampling.py wraps its whole traced body in this, so
    the format participates in the jit cache key as a static argument
    rather than as hidden module state)."""
    if fmt is not None and fmt not in FORMATS:
        raise InvalidKVFormatError("cache_format", fmt)
    token = _OVERRIDE.set(fmt)
    try:
        yield
    finally:
        _OVERRIDE.reset(token)


def _emit(fmt: str, batch: int, reason: str) -> None:
    key = (fmt, batch, reason)
    CHOICE_LOG.append({"cache_format": fmt, "batch": batch, "reason": reason})
    if key in _EMITTED:
        return
    _EMITTED.add(key)
    logger.info("decode KV cache format: %s (batch=%d, %s)", fmt, batch, reason)


def choose_cache_format(batch: int) -> str:
    """Resolve the decode cache format for a batch (called at trace time by
    ops/attention.py when no cache exists yet). See module docstring for the
    policy and its measured provenance."""
    override = _OVERRIDE.get()
    if override is not None:
        fmt, reason = override, "explicit override"
    else:
        env = os.environ.get("DALLE_TPU_KV_FORMAT")
        legacy = os.environ.get("DALLE_TPU_FLAT_KV")
        if env not in (None, ""):
            if env not in FORMATS:
                raise InvalidKVFormatError("DALLE_TPU_KV_FORMAT", env)
            fmt, reason = env, "DALLE_TPU_KV_FORMAT"
        elif legacy not in (None, ""):
            if legacy not in ("0", "1"):
                raise InvalidKVFormatError(
                    "DALLE_TPU_FLAT_KV", legacy, valid=("0", "1")
                )
            fmt, reason = ("flat" if legacy == "1" else "4d"), "DALLE_TPU_FLAT_KV"
        elif batch == 1:
            fmt, reason = "4d", "policy: measured batch-1 layout (v5e 2026-07)"
        elif batch == 8:
            fmt, reason = "flat", "policy: measured batch-8 layout (v5e 2026-07)"
        else:
            fmt, reason = "paged", "policy: batch-invariant page-local updates"
    _emit(fmt, batch, reason)
    return fmt


def resolve_format(cache_format: Optional[str], batch: int) -> str:
    """An explicit ``cache_format`` argument wins; ``None`` defers to the
    policy. Entry point for models/sampling.py."""
    if cache_format is not None:
        if cache_format not in FORMATS:
            raise InvalidKVFormatError("cache_format", cache_format)
        _emit(cache_format, batch, "cache_format argument")
        return cache_format
    return choose_cache_format(batch)


# ------------------------------------------------------------ KV quant


@contextlib.contextmanager
def quant_override(quant: Optional[str]) -> Iterator[None]:
    """Pin the KV storage quantization for every ``choose_kv_quant`` call
    in the block — the trace-time channel for an explicit ``kv_quant=``
    argument (models/sampling.py:init_decode_cache wraps its traced body
    in this, so the serving engine's caches can never drift from the
    ambient environment between the batched cache and its prefill
    template)."""
    if quant is not None and quant not in QUANTS:
        raise InvalidKVFormatError("kv_quant", quant, valid=QUANTS)
    token = _QUANT_OVERRIDE.set(quant)
    try:
        yield
    finally:
        _QUANT_OVERRIDE.reset(token)


def choose_kv_quant() -> str:
    """Resolve the paged-pool storage quantization ("none" | "int8") —
    called at trace time by ops/attention.py when no cache exists yet (a
    SUPPLIED cache's variables win there, exactly like the layout
    format). Channel order and error typing mirror
    ``choose_cache_format``; see the KV-quant block in the module
    docstring area above for the policy rationale."""
    override = _QUANT_OVERRIDE.get()
    if override is not None:
        quant, reason = override, "explicit override"
    else:
        env = os.environ.get("DALLE_TPU_KV_QUANT")
        if env not in (None, ""):
            if env not in QUANTS:
                raise InvalidKVFormatError(
                    "DALLE_TPU_KV_QUANT", env, valid=QUANTS
                )
            quant, reason = env, "DALLE_TPU_KV_QUANT"
        else:
            quant, reason = "none", "policy: default unquantized"
    key = ("kv_quant", quant, reason)
    if key not in _EMITTED:
        _EMITTED.add(key)
        logger.info("decode KV quantization: %s (%s)", quant, reason)
    return quant


def resolve_quant(kv_quant: Optional[str]) -> str:
    """An explicit ``kv_quant`` argument wins; ``None`` defers to the
    override/env/policy chain. Entry point for
    models/sampling.py:init_decode_cache and the serving EngineConfig —
    an invalid value fails TYPED here, at resolution time, naming the
    valid quants (never as a dtype error deep inside cache init)."""
    if kv_quant is not None:
        if kv_quant not in QUANTS:
            raise InvalidKVFormatError("kv_quant", kv_quant, valid=QUANTS)
        return kv_quant
    return choose_kv_quant()
