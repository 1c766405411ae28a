"""Headline benchmark: flagship DALL-E train-step MFU on one chip, plus p50
autoregressive generation latency.

Config matches BASELINE.md's target row — DALLE depth=12 / dim=1024 /
256 text + 1024 image tokens (the reference's train_dalle.py hot loop,
SURVEY.md §3.1) — compiled as one jitted train step in bf16.

FLOPs come from the compiled module's XLA cost analysis (the analog of the
reference's DeepSpeed flops profiler, train_dalle.py:473-480); the Pallas
attention kernels contribute via pl.CostEstimate. A hand-derived analytic
count cross-checks it (the run warns if they diverge >10%).

Output: one JSON line per metric; the LAST line is the headline train-MFU
metric. vs_baseline is against the driver's >=45%-MFU north-star target
(BASELINE.json); the reference itself publishes no numbers (BASELINE.md).
Every record carries a shared provenance stamp (git sha, jax/jaxlib
versions, device kind, env flags, seed — ISSUE 19); ``--flagship`` runs
the full serve matrix, the adaptive-control record, and the Pallas
block-size sweep as one measurement session whose output becomes the next
committed BENCH_r*.json trend point (tools/bench_trend.py --check).
"""

import json
import os
import sys
import time

# this file's own directory: the chip tool runs a COPY of the tree whose
# root is not promised to be any particular path
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dalle_pytorch_tpu.compile_cache import enable_compile_cache

enable_compile_cache()

# bf16 peak FLOP/s per chip by device kind (v5e = 197 TF)
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
    # nominal, for the on_cpu sections only (ROADMAP S0/D7 deletes both)
    "cpu": 5e11,
}

# HBM bandwidth per chip, bytes/sec (v5e = 819 GB/s; v4 = 1228; v6e = 1640)
PEAK_HBM_BPS = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,
    "TPU v6 lite": 1640e9,
    # nominal, for the on_cpu sections only (ROADMAP S0/D7 deletes both)
    "cpu": 50e9,
}

DEPTH, DIM, HEADS, DIM_HEAD = 12, 1024, 16, 64
TEXT_SEQ, IMAGE_FMAP = 256, 32


# ------------------------------------------------------- compile counting
# Recompiles are a first-class serving metric (a shape-drift recompile
# mid-trace is latency the percentiles silently eat): every throughput/
# serve record carries compile counts so a recompile regression shows up
# in BENCH_r*.json, not just in a p99 mystery. Two complementary
# counters: a global XLA backend-compile event listener, and per-jit
# signature-cache sizes for the serving hot loop (the same jits
# `tools/lint.py --trace` holds to a committed signature budget).

_BACKEND_COMPILES = {"n": 0, "installed": False}


def _install_compile_listener():
    if _BACKEND_COMPILES["installed"]:
        return
    _BACKEND_COMPILES["installed"] = True
    import jax.monitoring as _monitoring

    def _on_duration(name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            _BACKEND_COMPILES["n"] += 1

    _monitoring.register_event_duration_secs_listener(_on_duration)


def backend_compiles() -> int:
    """Total XLA backend compiles observed so far."""
    _install_compile_listener()
    return _BACKEND_COMPILES["n"]


def serving_jit_signatures() -> dict:
    """Compiled-signature count per serving hot-loop jit (the
    `_cache_size` of each jit's trace cache). Steady state after warmup:
    deltas must be ZERO — `_decode_jit` in particular is contracted to
    exactly one signature per engine config (DTL11x)."""
    from dalle_pytorch_tpu.models import sampling as _sampling
    from dalle_pytorch_tpu.serving import engine as _engine
    from dalle_pytorch_tpu.serving import postdecode as _postdecode

    fns = {
        "prefill": _engine._prefill_jit,
        "prefill_chunk": _engine._prefill_chunk_jit,
        "prefill_last": _engine._prefill_last_jit,
        "decode": _engine._decode_jit,
        "iteration": _engine._iteration_jit,
        "iteration_spec": _engine._spec_iteration_jit,
        "sample_cached": _engine._sample_cached_jit,
        "page_copy": _engine._copy_pages_jit,
        "page_copy_across": _engine._copy_pages_across_jit,
        "decode_tokens": _sampling.decode_tokens,
        "stage_vae_decode": _postdecode._vae_decode_jit,
        "stage_clip_rerank": _postdecode._clip_rerank_jit,
    }
    return {name: int(fn._cache_size()) for name, fn in fns.items()}


def _sig_delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


# ------------------------------------------------------ provenance stamp
# A BENCH_r*.json tail is only a trend point if the reader can tell
# which code, which jax, which device, and which knobs produced it
# (ISSUE 19): every record main() emits goes through _emit, which stamps
# one shared provenance block — git sha, jax/jaxlib versions, device
# kind, the DALLE_TPU_*/JAX_* env flags in effect, and the record's own
# seed — so tools/bench_trend.py comparisons are never apples-to-unknown.

_PROVENANCE = None


def provenance() -> dict:
    """The cached per-process provenance block (computed once: the git
    sha and device kind cannot change mid-run)."""
    global _PROVENANCE
    if _PROVENANCE is None:
        import platform as _platform
        import subprocess

        try:
            sha = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=10,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            ).stdout.strip() or None
        except Exception:
            sha = None
        try:
            import jaxlib
            jaxlib_version = jaxlib.__version__
        except Exception:
            jaxlib_version = None
        dev = jax.devices()[0]
        _PROVENANCE = {
            "git_sha": sha,
            "jax_version": jax.__version__,
            "jaxlib_version": jaxlib_version,
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "n_devices": jax.device_count(),
            "python": _platform.python_version(),
            "env_flags": {
                k: v for k, v in sorted(os.environ.items())
                if k.startswith(("DALLE_TPU_", "JAX_", "XLA_FLAGS"))
            },
        }
    return _PROVENANCE


def _emit(record: dict) -> dict:
    """Print one metric record as a JSON line with the provenance block
    attached. The record's own fields always win; its seed (``seed`` or
    ``arrival_seed``, whichever the section reports) is folded into the
    stamp so replaying the exact run needs nothing beyond the record."""
    out = dict(record)
    prov = dict(provenance())
    for key in ("arrival_seed", "seed"):
        if key in out:
            prov["seed"] = out[key]
            break
    out.setdefault("provenance", prov)
    print(json.dumps(out))
    return out


NUM_TEXT, NUM_IMAGE = 10000, 8192
BATCH = 8


def _peak(table: dict) -> float:
    """This device's row of a peak table. A device kind the table does not
    know is an error, never another device's numbers."""
    kind = jax.devices()[0].device_kind
    for k, v in table.items():
        if k.lower() in kind.lower():
            return v
    raise KeyError(
        f"no peak on record for device kind {kind!r}; known: {sorted(table)}"
    )


def peak_flops() -> float:
    return _peak(PEAK_FLOPS)


def peak_hbm_bps() -> float:
    return _peak(PEAK_HBM_BPS)


def kv_sweep_bytes_per_token(kv_quant: str = "none",
                             kv_dtype_bytes: int = 2) -> float:
    """HBM bytes the K + V cache sweep streams per cached position per
    layer-pair, by KV storage format: ``kv_dtype_bytes`` per element for
    the unquantized pools (bf16 = 2), or 1 int8 byte per element plus a
    4-byte f32 scale per (token, head) for ``kv_quant="int8"``
    (ops/paged_kv.py:quantize_rows) — the recomputed stream-bound input:
    bytes roughly halve, so the kv_sweep_weight_stream_hbm_roofline
    bound RISES by the same factor at the sweep-dominated batches."""
    if kv_quant == "int8":
        return 2 * HEADS * (DIM_HEAD * 1 + 4)
    return 2 * HEADS * DIM_HEAD * kv_dtype_bytes


def decode_roofline_tokens_per_sec(
    batch: int,
    int8: bool = True,
    depth: int = DEPTH,
    fmap: int = IMAGE_FMAP,
    frontier_avg: float | None = None,
    kv_quant: str = "none",
) -> float:
    """Named bound: **kv_sweep_weight_stream_hbm_roofline** — the decode
    tokens/sec ceiling from HBM bytes alone, derived here so the batch
    sweep's records carry a bound instead of an asserted story.

    Per decode step the chip must stream, once per STEP (amortized across
    the batch):
      - the transformer matmul weights: depth * 16 * dim^2 params
        (qkv 3d^2 + out d^2 + GEGLU 12d^2), 1 byte/param int8, 2 bf16;
      - the image-vocab head slice: dim * num_image_tokens columns
        (models/dalle.py:_head_image; embeddings are row gathers,
        negligible);
    and, once per SEQUENCE (scales with batch):
      - the K + V cache sweep: 2 * depth * frontier * heads * dim_head
        rows of bf16 (2 bytes) — ``frontier_avg`` defaults to the
        segmented scan's average window, (text_len + L) / 2 rounded to the
        128-row segment grid (models/sampling.py:resize_kv).

    tok/s(batch) = batch / (step_bytes / HBM_bytes_per_sec). The bound is
    MONOTONE in batch by construction — the weight stream amortizes while
    sweeps scale linearly, saturating at the sweep asymptote
    HBM / (2 * depth * frontier * h * d * 2) tokens/sec — so any measured
    tokens/sec DECLINE with batch (batch 32's 6,050 vs batch 8's 6,832,
    BENCH_r05) is a layout/update artifact, not bandwidth: exactly the
    DUS rewrite cost the paged cache removes structurally. Compute (the
    lane-packed sweeps' MXU work) and the serial op chain sit below this
    roofline at every batch here, so bytes are the binding resource.
    ``depth``/``fmap`` must be the BENCHED model's (the CPU sweep runs a
    reduced config; a full-size bound next to a reduced measurement would
    make the attribution story wrong)."""
    n = TEXT_SEQ + fmap**2
    if frontier_avg is None:
        # average ceil-to-128 cache window over the image-token scan
        t = TEXT_SEQ + 1
        frontier_avg = (-(-t // 128) * 128 + -(-n // 128) * 128) / 2
    wbytes = 1 if int8 else 2
    weight_bytes = depth * 16 * DIM * DIM * wbytes + DIM * NUM_IMAGE * wbytes
    # K+V sweep bytes per position: bf16 by default; kv_quant="int8"
    # swaps in the quantized stream (int8 + per-head scales) and the
    # bound rises accordingly — the recomputed int8 stream roofline
    sweep_bytes = depth * frontier_avg * kv_sweep_bytes_per_token(kv_quant)
    step_bytes = weight_bytes + batch * sweep_bytes
    return batch / (step_bytes / peak_hbm_bps())


def _kv_bytes_per_slot(fmt: str, depth: int, fmap: int,
                       kv_quant: str) -> int:
    """KV cache bytes one sequence slot occupies across all layers for a
    given layout format + storage quantization (bf16 elements for the
    unquantized flagship; int8 + per-(token, head) f32 scales under
    kv_quant="int8" — paged only: the flat/4d formats never consulted
    the quant knob). Paged slots round up to whole pages."""
    from dalle_pytorch_tpu.ops import kv_policy as _kvp, paged_kv as _pkv

    n = TEXT_SEQ + 1 + fmap * fmap  # internal positions incl. <bos>
    if fmt == "paged":
        page = _kvp.page_size()
        n = _pkv.num_pages(n, page) * page
    else:
        kv_quant = "none"
    return int(depth * n * kv_sweep_bytes_per_token(kv_quant))


def bench_decode_sweep(on_cpu: bool, batch_sizes=(1, 8, 16, 32, 64),
                       formats=("4d", "flat", "paged"), int8: bool = True):
    """Decode throughput sweep over batch x cache format — the measurement
    the layout policy (ops/kv_policy.py) stands on. Each record carries the
    derived HBM roofline (``decode_roofline_tokens_per_sec`` above) under
    ``bound_name`` so a non-monotone measured curve is immediately
    attributable: the bound is monotone in batch, so a decline is a
    layout/update artifact of that format, not bandwidth."""
    from dalle_pytorch_tpu.models.sampling import generate_image_tokens
    from dalle_pytorch_tpu.ops import kv_policy

    if on_cpu:
        batch_sizes = (1, 2)
    dalle, params, depth, fmap = _serving_model(on_cpu, int8)
    rng = np.random.RandomState(0)

    results = []
    prev_paged_tps = None
    for b in batch_sizes:
        text = jnp.asarray(
            rng.randint(1, NUM_TEXT, size=(b, TEXT_SEQ)), jnp.int32
        )
        policy_fmt = kv_policy.choose_cache_format(b)
        for fmt in formats:
            def gen(key, fmt=fmt):
                return generate_image_tokens(
                    dalle, params, text, key, cache_format=fmt
                )

            bc0 = backend_compiles()
            np.asarray(gen(jax.random.key(0)))  # compile
            bc1 = backend_compiles()
            times = []
            for i in range(2 if on_cpu else 3):
                t0 = time.perf_counter()
                np.asarray(gen(jax.random.key(i)))
                times.append(time.perf_counter() - t0)
            bc2 = backend_compiles()
            p50 = float(np.percentile(times, 50))
            tps = b * fmap * fmap / p50
            rec = {
                "metric": f"decode_sweep_tokens_per_sec_batch{b}_{fmt}"
                          + ("_int8" if int8 else ""),
                "compiles_warm": bc1 - bc0,
                "compiles_timed": bc2 - bc1,
                "value": round(tps, 1),
                "unit": "tokens/sec",
                "vs_baseline": None,
                "batch": b,
                "cache_format": fmt,
                "policy_default_format": policy_fmt,
                "page_size": kv_policy.page_size() if fmt == "paged" else None,
                "batch_latency_ms": round(p50 * 1e3, 1),
                "bound_name": "kv_sweep_weight_stream_hbm_roofline",
                "roofline_tokens_per_sec": round(
                    decode_roofline_tokens_per_sec(
                        b, int8=int8, depth=depth, fmap=fmap
                    ), 1
                ),
                # the KV format axis (ops/kv_policy.py kv_quant): what
                # the pools store, the per-slot KV bytes that implies,
                # and the RECOMPUTED stream bound under int8 pages —
                # bytes roughly halve, so the bound rises by the same
                # factor where sweeps dominate (the quantized-KV lever)
                "kv_quant": kv_policy.choose_kv_quant(),
                "kv_bytes_per_slot": _kv_bytes_per_slot(
                    fmt, depth, fmap, kv_policy.choose_kv_quant()
                ),
                "roofline_tokens_per_sec_kv_int8": round(
                    decode_roofline_tokens_per_sec(
                        b, int8=int8, depth=depth, fmap=fmap,
                        kv_quant="int8",
                    ), 1
                ),
                "roofline_note": "derived in bench.py:decode_roofline_tokens_"
                                 "per_sec; monotone in batch by construction",
                "device": jax.devices()[0].device_kind,
            }
            if fmt == "paged":
                rec["monotone_vs_prev_batch"] = (
                    None if prev_paged_tps is None else bool(tps >= prev_paged_tps)
                )
                prev_paged_tps = tps
            results.append(rec)
    return results


def bench_continuous_batching(on_cpu: bool, int8: bool = True):
    """Ragged-offsets decode microbench: one paged-cache step serves a batch
    whose sequences sit at DIFFERENT decode positions (continuous batching —
    requests joining mid-flight instead of waiting for the batch to drain).
    Measures steady-state tokens/sec of the jitted vector-position
    ``decode_step``; cache contents are synthetic (cost is what's measured —
    correctness of the ragged step is pinned bit-exact against per-sequence
    decode in tests/test_paged_kv.py)."""
    from dalle_pytorch_tpu.models import DALLE
    from dalle_pytorch_tpu.models.sampling import (
        init_decode_cache, set_decode_offsets,
    )

    b = 4 if on_cpu else 8
    n_steps = 8 if on_cpu else 128
    dalle, params, depth, fmap = _serving_model(on_cpu, int8)

    cache = init_decode_cache(dalle, params, b, cache_format="paged")
    T = dalle.text_len_internal
    # spread the batch across the image-token range — each sequence at its
    # own frontier, the shape a continuous-batching serving loop sees
    offsets = T + (np.arange(b) * dalle.image_seq_len) // b
    cache = set_decode_offsets(cache, offsets)
    pos0 = jnp.asarray(offsets, jnp.int32)

    # all n_steps inside ONE jitted scan: per-step dispatch cost would
    # swamp the ms-scale step (see _scan_step_time)
    @jax.jit
    def run(cache, pos, tok):
        def body(carry, _):
            cache, pos, tok = carry
            logits, mutated = dalle.apply(
                {"params": params, "cache": cache}, tok, pos,
                image_only=True, method=DALLE.decode_step, mutable=["cache"],
            )
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (mutated["cache"], pos + 1, tok), None

        (cache, pos, tok), _ = jax.lax.scan(
            body, (cache, pos, tok), None, length=n_steps
        )
        return tok

    tok = jnp.zeros((b,), jnp.int32)
    bc0 = backend_compiles()
    np.asarray(run(cache, pos0, tok))  # compile + warm
    bc1 = backend_compiles()
    t0 = time.perf_counter()
    np.asarray(run(cache, pos0, tok))
    dt = time.perf_counter() - t0
    bc2 = backend_compiles()
    tps = b * n_steps / dt
    return {
        "metric": "decode_continuous_batching_tokens_per_sec_batch"
                  f"{b}" + ("_int8" if int8 else ""),
        "compiles_warm": bc1 - bc0,
        "compiles_timed": bc2 - bc1,
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "batch": b,
        "cache_format": "paged",
        "ragged_offsets": [int(o) for o in offsets],
        "ms_per_step": round(dt * 1e3 / n_steps, 3),
        "device": jax.devices()[0].device_kind,
    }


def bench_serve(on_cpu: bool, int8: bool = True, seed: int = 0):
    """--serve: drive the continuous-batching engine (serving/engine.py)
    with a synthetic Poisson-ish arrival trace (seeded exponential
    inter-arrivals — deterministic offered load, real wall-clock service)
    and record the REQUEST-level metrics the one-shot throughput sections
    cannot see. The trace runs TWICE — telemetry off, then on — so the
    record both measures the span path's overhead (the acceptance bound:
    tokens/sec with telemetry on vs off) and sources its percentiles from
    the telemetry ``Histogram`` snapshots (utils/metrics.py) instead of a
    hand-rolled sort: request latency, queue wait, and the prefill vs
    decode-step split all come from the same ``serve.*`` histograms an
    operator dashboard reads. The engine runs under a deliberately
    tightened page budget + watermark so the record also shows how the
    robustness machinery behaves at pressure, not just the happy path."""
    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, Outcome, Request, check_accounting,
    )
    from dalle_pytorch_tpu.utils.metrics import counters, histograms
    from dalle_pytorch_tpu.utils.telemetry import TELEMETRY

    dalle, params, depth, fmap = _serving_model(on_cpu, int8)
    rng = np.random.RandomState(seed)
    n_req = 6 if on_cpu else 64
    max_batch = 2 if on_cpu else 8
    tokens_per = fmap * fmap
    mean_ia = 0.05 if on_cpu else 0.2  # mean inter-arrival, seconds

    cfg = EngineConfig(
        max_batch=max_batch,
        queue_limit=max(2, n_req // 2),  # bounded: overload can reject
        high_watermark=0.75,
        degraded_max_new_tokens=tokens_per,  # report-only at this load
    )
    # ONE seeded trace, replayed identically in both runs
    arrivals = np.cumsum(rng.exponential(scale=mean_ia, size=n_req))
    prompts = rng.randint(1, NUM_TEXT, size=(n_req, TEXT_SEQ)).astype(np.int32)
    priorities = rng.randint(0, 3, size=n_req)

    def run_trace(telemetry_on: bool) -> dict:
        # no flight dir: the ring holds the hot-path records (drops are
        # counted and reported — bounded memory is part of the contract)
        TELEMETRY.configure(enabled=telemetry_on, ring_size=1 << 15)
        engine = Engine(dalle, params, cfg)
        sig0, bc0 = serving_jit_signatures(), backend_compiles()
        # warm the jits outside the timed trace (compile is not latency);
        # max_new_tokens=2 so the warm request runs a real decode step —
        # at 1 it completed at admission and left _decode_jit's compile
        # INSIDE the timed window (visible as compiles_in_trace=1 before
        # this fix)
        warm = Request(request_id="__warm__",
                       prompt=np.zeros(TEXT_SEQ, np.int32),
                       max_new_tokens=2, seed=0)
        engine.submit(warm)
        engine.run()
        sig1, bc1 = serving_jit_signatures(), backend_compiles()
        histograms.reset()  # percentiles cover the timed trace only
        c0 = {k: counters.get(f"serve.{k}") for k in
              ("rejected", "preempted", "deadline_exceeded", "completed")}
        occ_samples = []
        # all times on the ENGINE's clock: deadlines are compared against
        # engine.clock.now() inside the engine, and mixing clock epochs
        # (perf_counter vs monotonic) is undefined across platforms
        t0 = engine.clock.now()
        submitted = 0
        while True:
            now = engine.clock.now() - t0
            while submitted < n_req and arrivals[submitted] <= now:
                engine.submit(Request(
                    request_id=f"req{submitted}",
                    prompt=prompts[submitted],
                    max_new_tokens=tokens_per,
                    deadline=t0 + arrivals[submitted]
                             + (120 if on_cpu else 600),
                    priority=int(priorities[submitted]),
                    seed=seed * 7919 + submitted,
                ))
                submitted += 1
            busy = engine.step()
            occ_samples.append(engine.pool.occupancy)
            if not busy:
                if submitted >= n_req:
                    break
                time.sleep(min(0.005, max(0.0, arrivals[submitted] - now)))
        wall = engine.clock.now() - t0
        check_accounting(engine)
        sig2, bc2 = serving_jit_signatures(), backend_compiles()
        done = [
            r for r in engine.results.values()
            if r.outcome is Outcome.COMPLETED and r.request_id != "__warm__"
        ]
        return {
            "wall": wall,
            "tps": sum(len(r.tokens) for r in done) / wall,
            "delta": {k: counters.get(f"serve.{k}") - v
                      for k, v in c0.items()},
            "occ": occ_samples,
            "pool_pages": engine.pool.total,
            "dropped": TELEMETRY.dropped,
            # compile accounting: warm pays for signatures, the timed
            # trace must not (jit deltas all zero = no recompile
            # regression; the backend count additionally catches compiles
            # OUTSIDE the serving jits, e.g. per-slot cache-insert ops)
            "compiles_warm": bc1 - bc0,
            "compiles_trace": bc2 - bc1,
            "jit_signatures_warm": _sig_delta(sig1, sig0),
            "jit_recompiles_trace": _sig_delta(sig2, sig1),
        }

    def pct(name: str, q: float) -> float:
        h = histograms.get(name)
        return 0.0 if h is None else round(h.percentile(q) * 1e3, 1)

    off = run_trace(telemetry_on=False)
    # request-latency/queue-wait histograms are METRICS (engine observes
    # them unconditionally), so the headline percentiles come from the
    # telemetry-OFF run — free of the span-path overhead this record
    # measures separately. Only the span-fed phase splits (prefill /
    # decode_step durations) need the ON run.
    headline = {
        "value": pct("serve.completed_latency_s", 50),
        "p95_ms": pct("serve.completed_latency_s", 95),
        "p99_ms": pct("serve.completed_latency_s", 99),
        "queue_p50_ms": pct("serve.queue_wait_s", 50),
        "queue_p95_ms": pct("serve.queue_wait_s", 95),
        # time-to-first-token (submit -> first image token), histogram-
        # sourced like the other splits; observed unconditionally, so the
        # clean telemetry-off run is the source
        "ttft_p50_ms": pct("serve.ttft_s", 50),
        "ttft_p95_ms": pct("serve.ttft_s", 95),
        "ttft_p99_ms": pct("serve.ttft_s", 99),
    }
    on = run_trace(telemetry_on=True)

    TELEMETRY.configure(enabled=False)
    overhead = 1.0 - on["tps"] / off["tps"] if off["tps"] else 0.0
    return {
        "metric": f"serve_request_latency_p50_ms_batch{max_batch}"
                  + ("_int8" if int8 else ""),
        **headline,
        "unit": "ms",
        "vs_baseline": None,
        "prefill_p50_ms": pct("serve.prefill_s", 50),
        "prefill_p95_ms": pct("serve.prefill_s", 95),
        "decode_step_p50_ms": pct("serve.decode_step_s", 50),
        "decode_step_p95_ms": pct("serve.decode_step_s", 95),
        "latency_source": "telemetry_histogram (log buckets, <=1.26x "
                          "relative error; utils/metrics.py:Histogram); "
                          "latency/queue from the telemetry-off run, "
                          "prefill/decode splits from the on run",
        "n_requests": n_req,
        "completed": on["delta"]["completed"],
        "rejected": on["delta"]["rejected"],
        "preempted": on["delta"]["preempted"],
        "deadline_exceeded": on["delta"]["deadline_exceeded"],
        "pool_occupancy_mean": round(float(np.mean(on["occ"])), 3),
        "pool_occupancy_max": round(float(np.max(on["occ"])), 3),
        "pool_pages": on["pool_pages"],
        "tokens_per_request": tokens_per,
        # telemetry-OFF run is the clean headline; the on/off pair is the
        # measured span-path overhead (acceptance: bounded and reported)
        "completed_tokens_per_sec": round(off["tps"], 1),
        "tokens_per_sec_telemetry_on": round(on["tps"], 1),
        "telemetry_overhead_frac": round(float(overhead), 4),
        "telemetry_ring_dropped": on["dropped"],
        # recompile regressions as a first-class metric: compile counts
        # per run phase (warm vs timed trace), per serving jit and
        # backend-wide. Healthy steady state: every *_in_trace count is 0
        # — the telemetry-OFF (headline) run is the source, the ON run is
        # cross-checked to confirm telemetry adds no compiles
        "compiles_warm": off["compiles_warm"],
        "compiles_in_trace": off["compiles_trace"],
        "compiles_in_trace_telemetry_on": on["compiles_trace"],
        "jit_signatures_warm": off["jit_signatures_warm"],
        "jit_recompiles_in_trace": off["jit_recompiles_trace"],
        "compile_counter_source": "jax.monitoring backend_compile events "
                                  "+ per-jit _cache_size deltas",
        "mean_interarrival_s": mean_ia,
        "arrival_seed": seed,
        "max_batch": max_batch,
        "device": jax.devices()[0].device_kind,
    }


def bench_serve_quant(on_cpu: bool, int8: bool = True, seed: int = 0,
                      model=None):
    """--serve companion: the quantized-KV record (ROADMAP 3 / ISSUE 14).
    One seeded request set runs through TWO otherwise-identical engines —
    ``kv_quant="none"`` (bf16/f32 paged pools) and ``kv_quant="int8"``
    (int8 pools + per-(token, head) f32 scale pools, dequantized at read
    time in-kernel) — and the record reports the capacity and fidelity
    story with its acceptance checks IN-BENCH:

      * at a fixed KV HBM budget the int8 format fits >= 1.8x the pages
        of the unquantized format (``kv_pages_per_budget_ratio``,
        computed from the engines' REAL cache leaves — reported
        ``kv_bytes_per_slot`` roughly halves);
      * the quantized timed window performs ZERO backend compiles and
        ZERO serving-jit recompiles (quantize-at-append / dequant-at-
        read are in-trace data ops — no signature drift; DTL11x holds
        the same budget on the quant contract entries);
      * quantized-vs-unquantized token agreement meets the PINNED floor
        (ops/kv_policy.py:KV_QUANT_TOKEN_AGREEMENT_MIN) — the
        thresholded parity tier; quantized-vs-quantized bitwise parity
        is the standing contract pinned by tests/test_kv_quant.py, not
        re-measured here.

    The recomputed int8 stream roofline rides along: halved sweep bytes
    raise the kv_sweep_weight_stream_hbm_roofline bound at the
    sweep-dominated batches (TPU wall numbers pend a device session)."""
    from dalle_pytorch_tpu.ops import kv_policy
    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, Outcome, Request, check_accounting,
    )

    if model is None:
        dalle, params, depth, fmap = _serving_model(on_cpu, int8)
    else:
        dalle, params = model
        depth, fmap = dalle.depth, dalle.image_fmap_size
    rng = np.random.RandomState(seed)
    n_req = 4 if on_cpu else 16
    max_new = 4 if on_cpu else fmap * fmap
    vocab = min(NUM_TEXT, dalle.num_text_tokens)
    prompts = rng.randint(
        1, vocab, size=(n_req, dalle.text_seq_len)
    ).astype(np.int32)
    chunk = max(2, dalle.text_len_internal // 8)

    def run_engine(kv_quant: str):
        cfg = EngineConfig(
            max_batch=2, prefill_chunk=chunk, kv_quant=kv_quant,
        )
        engine = Engine(dalle, params, cfg)
        # warm outside the timed window (compile is not latency)
        warm = Request(request_id="__warm__",
                       prompt=np.zeros(dalle.text_seq_len, np.int32),
                       max_new_tokens=2, seed=0)
        engine.submit(warm)
        engine.run(max_steps=20000)
        sig0, bc0 = serving_jit_signatures(), backend_compiles()
        t0 = time.perf_counter()
        for i in range(n_req):
            engine.submit(Request(
                request_id=f"q{i}", prompt=prompts[i],
                max_new_tokens=max_new, seed=seed * 7919 + i,
            ))
        engine.run(max_steps=40000)
        wall = time.perf_counter() - t0
        sig1, bc1 = serving_jit_signatures(), backend_compiles()
        check_accounting(engine)
        toks = {
            rid: np.asarray(r.tokens)
            for rid, r in engine.results.items()
            if r.outcome is Outcome.COMPLETED and rid != "__warm__"
        }
        assert len(toks) == n_req, (
            f"kv_quant={kv_quant}: {len(toks)}/{n_req} completed"
        )
        return {
            "tokens": toks,
            "wall": wall,
            "tps": sum(len(t) for t in toks.values()) / wall,
            "kv_bytes_per_slot": engine.kv_bytes_per_slot,
            "n_pages_slot": engine.n_pages_slot,
            "compiles_trace": bc1 - bc0,
            "jit_recompiles_trace": _sig_delta(sig1, sig0),
        }

    base = run_engine("none")
    quant = run_engine("int8")

    # capacity at a fixed KV HBM budget, from the REAL cache leaves:
    # pages the budget buys = budget // bytes-per-page of each format
    budget = 1 << 30  # 1 GiB of KV pool — any fixed budget, ratio is scale-free
    bpp_base = base["kv_bytes_per_slot"] / base["n_pages_slot"]
    bpp_quant = quant["kv_bytes_per_slot"] / quant["n_pages_slot"]
    pages_base = int(budget // bpp_base)
    pages_quant = int(budget // bpp_quant)
    ratio = pages_quant / pages_base
    assert ratio >= 1.8, (
        f"int8 KV pages per fixed budget only {ratio:.2f}x the "
        f"unquantized format (>= 1.8x required)"
    )
    assert quant["compiles_trace"] == 0, (
        f"quantized serving path compiled in-trace: "
        f"{quant['compiles_trace']}"
    )
    assert all(v == 0 for v in quant["jit_recompiles_trace"].values()), (
        f"quantized serving path re-traced a serving jit: "
        f"{quant['jit_recompiles_trace']}"
    )

    # quantized-vs-unquantized token agreement (position-wise fraction,
    # averaged over requests) against the pinned floor
    agree = float(np.mean([
        np.mean(base["tokens"][rid] == quant["tokens"][rid])
        for rid in base["tokens"]
    ]))
    floor = kv_policy.KV_QUANT_TOKEN_AGREEMENT_MIN
    assert agree >= floor, (
        f"kv-int8 token agreement {agree:.3f} below the pinned "
        f"{floor} floor"
    )

    return {
        "metric": "serve_kv_quant_int8" + ("_int8w" if int8 else ""),
        "value": round(ratio, 3),
        "unit": "pages_per_budget_ratio_int8_vs_unquant",
        "vs_baseline": None,
        "kv_quant": "int8",
        "kv_bytes_per_slot_unquant": base["kv_bytes_per_slot"],
        "kv_bytes_per_slot_int8": quant["kv_bytes_per_slot"],
        "kv_pages_per_budget_ratio": round(ratio, 3),
        "kv_pages_per_budget_unquant": pages_base,
        "kv_pages_per_budget_int8": pages_quant,
        "token_agreement_vs_unquant": round(agree, 4),
        "token_agreement_floor": floor,
        "completed": len(quant["tokens"]),
        "n_requests": n_req,
        "tokens_per_sec_unquant": round(base["tps"], 1),
        "tokens_per_sec_int8": round(quant["tps"], 1),
        "cpu_wall_caveat": (
            "CPU walls measure dispatch overhead, not the HBM stream the "
            "int8 format halves; TPU numbers pend a device session"
        ) if on_cpu else None,
        "compiles_in_trace_int8": quant["compiles_trace"],
        "jit_recompiles_in_trace_int8": quant["jit_recompiles_trace"],
        "bound_name": "kv_sweep_weight_stream_hbm_roofline",
        "roofline_tokens_per_sec_batch8": round(
            decode_roofline_tokens_per_sec(8, int8=int8, depth=depth,
                                           fmap=fmap), 1
        ),
        "roofline_tokens_per_sec_batch8_kv_int8": round(
            decode_roofline_tokens_per_sec(8, int8=int8, depth=depth,
                                           fmap=fmap, kv_quant="int8"), 1
        ),
        "arrival_seed": seed,
        "device": jax.devices()[0].device_kind,
    }


def bench_serve_fused(on_cpu: bool, int8: bool | None = None, seed: int = 0,
                      model=None):
    """--serve companion: the unified ragged-iteration record (ROADMAP 1,
    "Ragged Paged Attention"). One staggered arrival trace runs through
    TWO chunked engines — SPLIT (one jit dispatch per prefill chunk plus
    one per decode step) and FUSED (``_iteration_jit``: every granted
    chunk plus the decode rows in ONE dispatch) — and the record reports
    ``dispatches_per_iteration`` for both, the per-iteration dispatch
    overhead the fusion removes, and wall/throughput for context. The
    acceptance checks run IN-BENCH:

      * the fused trace contains genuinely MIXED iterations (prefilling
        and decoding slots coexist) and still never exceeds one dispatch
        per iteration (``engine.dispatches <= engine.iterations`` — the
        steady-state 1-dispatch contract, which DTL11x pins at the
        compile-signature level);
      * the fused timed trace performs ZERO jit recompiles and ZERO
        backend compiles (the PR 8 compile listener + per-jit signature
        deltas — descriptor raggedness is data, so no mix can drift the
        signature);
      * completed tokens are BIT-identical split vs fused for f32
        models (the parity tier — the tiny-model smoke/test gates run
        there). For the bf16 flagship the comparison is REPORTED, not
        asserted: XLA fuses bf16 elementwise chains differently across
        program shapes (the W-wide fused block vs the n=1 split step),
        rounding some intermediates one bf16 ulp apart, and on TPU the
        lane-packed split decode adds the same drift class — near-tie
        tokens can legitimately flip.

    ``int8`` defaults to bf16 on CPU (the same per-call head-dequant CPU
    artifact bench_serve_interference documents); wall-clock comparisons
    between the modes on CPU also carry the fused path's padded-row
    compute, so the structural dispatch counts are the headline and the
    times are context. ``model`` overrides the flagship serving model
    (tests pass a tiny one)."""
    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, Outcome, Request, check_accounting,
    )

    if int8 is None:
        int8 = not on_cpu
    if model is None:
        dalle, params, _, fmap = _serving_model(on_cpu, int8)
    else:
        dalle, params = model
        fmap = dalle.image_fmap_size
    T = dalle.text_len_internal
    chunk = max(2, T // 16)
    n_req = 5 if on_cpu else 32
    max_batch = 2 if on_cpu else 8
    max_new = min(fmap * fmap, 6 if on_cpu else 48)
    rng = np.random.RandomState(seed)
    vocab = min(NUM_TEXT, dalle.num_text_tokens)
    prompts = rng.randint(
        1, vocab, size=(n_req, dalle.text_seq_len)
    ).astype(np.int32)

    def run_mode(fused: bool) -> dict:
        engine = Engine(dalle, params, EngineConfig(
            max_batch=max_batch, prefill_chunk=chunk, fused_iteration=fused,
        ))
        # warm every signature outside the timed trace (both slot indices
        # see their first insert/reset; the fused mode's ONE signature
        # covers chunks, final chunks and decode alike)
        for i in range(2):
            engine.submit(Request(
                request_id=f"__warm{i}__",
                prompt=np.zeros(dalle.text_seq_len, np.int32),
                max_new_tokens=2, seed=0,
            ))
        engine.run()
        sig0, bc0 = serving_jit_signatures(), backend_compiles()
        d0, i0 = engine.dispatches, engine.iterations
        mixed_iterations = 0
        submitted = 0

        def submit_next():
            nonlocal submitted
            engine.submit(Request(
                request_id=f"req{submitted}", prompt=prompts[submitted],
                max_new_tokens=max_new, seed=seed * 7919 + submitted,
            ))
            submitted += 1

        t0 = time.perf_counter()
        while True:
            # staggered submits (by iteration count, not wall clock, so
            # both modes see the same admission schedule): prefills keep
            # arriving while earlier requests decode -> mixed iterations
            while submitted < n_req and (
                submitted == 0 or engine.iterations - i0 >= submitted * 2
            ):
                submit_next()
            phases = {s.phase for s in engine.slots if s}
            if len(phases) == 2:
                mixed_iterations += 1
            if not engine.step():
                if submitted >= n_req:
                    break
                # idle with arrivals pending (iterations stop advancing
                # when nothing works, so the gate alone would deadlock):
                # release the next request now
                submit_next()
        wall = time.perf_counter() - t0
        check_accounting(engine)
        sig1, bc1 = serving_jit_signatures(), backend_compiles()
        dispatches = engine.dispatches - d0
        iterations = engine.iterations - i0
        toks = {
            r.request_id: np.asarray(r.tokens)
            for r in engine.results.values()
            if r.outcome is Outcome.COMPLETED
            and not r.request_id.startswith("__warm")
        }
        assert len(toks) == n_req, (
            f"{'fused' if fused else 'split'} trace completed "
            f"{len(toks)}/{n_req}"
        )
        return {
            "dispatches": dispatches,
            "iterations": iterations,
            "per_iter": dispatches / max(iterations, 1),
            "wall": wall,
            "tps": sum(len(t) for t in toks.values()) / wall,
            "mixed_iterations": mixed_iterations,
            "compiles_trace": bc1 - bc0,
            "jit_recompiles_trace": _sig_delta(sig1, sig0),
            "tokens": toks,
        }

    split = run_mode(fused=False)
    fused = run_mode(fused=True)

    # acceptance: mixed iterations, one dispatch per fused iteration, no
    # in-trace compiles, bit-identical output
    assert fused["mixed_iterations"] > 0, (
        "fused trace never interleaved prefill with decode — the record "
        "would not exercise the ragged mix"
    )
    assert fused["dispatches"] <= fused["iterations"], (
        f"fused engine exceeded one dispatch per iteration: "
        f"{fused['dispatches']} dispatches / {fused['iterations']} iterations"
    )
    assert split["dispatches"] > split["iterations"], (
        "split trace never needed more than one dispatch per iteration — "
        "the comparison is degenerate (no mixed prefill+decode pressure)"
    )
    assert fused["compiles_trace"] in (0, -1), (
        f"fused timed trace compiled {fused['compiles_trace']} modules"
    )
    assert all(v in (0, -1) for v in fused["jit_recompiles_trace"].values()), (
        f"fused timed trace recompiled serving jits: "
        f"{fused['jit_recompiles_trace']}"
    )
    ident = [
        rid for rid, t in split["tokens"].items()
        if np.array_equal(fused["tokens"][rid], t)
    ]
    bit_identical = len(ident) == n_req
    # BIT-parity is asserted on the f32 parity tier only (the tiny-model
    # gates: tools/serve_smoke.py --fused pass,
    # tests/test_ragged_attention.py). The flagship serving model is
    # bf16, where XLA fuses elementwise chains differently across
    # PROGRAM SHAPES — the fused W-wide block and the split n=1 step
    # round some bf16 intermediates one ulp apart (measured: identical
    # eager, 2^-6 max logit delta jitted, page-size dependent), so a
    # near-tie token can legitimately flip and bf16 cross-program
    # bitwise identity is not a stable property to assert. Reported
    # instead; on TPU the split engine's lane-packed decode adds the
    # same class of drift (ops/attention.py:lane_pack_enabled).
    if jnp.dtype(dalle.dtype) == jnp.float32:
        assert bit_identical, "fused tokens diverged from the split engine"

    return {
        "metric": f"serve_fused_dispatches_per_iteration_batch{max_batch}"
                  + ("_int8" if int8 and model is None else ""),
        "int8": bool(int8),
        "value": round(fused["per_iter"], 4),
        "unit": "dispatches/iteration",
        "vs_baseline": None,
        "split_dispatches_per_iteration": round(split["per_iter"], 4),
        "dispatch_overhead_removed_per_iteration": round(
            split["per_iter"] - fused["per_iter"], 4
        ),
        "fused_dispatches": fused["dispatches"],
        "fused_iterations": fused["iterations"],
        "split_dispatches": split["dispatches"],
        "split_iterations": split["iterations"],
        "mixed_iterations_fused": fused["mixed_iterations"],
        # asserted for f32 models (the parity tier); for the bf16
        # flagship it is reported — see the fusion-rounding note above
        "fused_tokens_bit_identical_to_split": bool(bit_identical),
        "requests_bit_identical": len(ident),
        "parity_note": "bitwise parity is the f32 tier's contract "
                       "(serve_smoke fused pass, test_ragged_attention); "
                       "bf16 programs round ~1 ulp apart across program "
                       "shapes under XLA fusion, so flagship parity is "
                       "reported, not asserted",
        "compiles_in_trace_fused": fused["compiles_trace"],
        "jit_recompiles_in_trace_fused": fused["jit_recompiles_trace"],
        "wall_split_s": round(split["wall"], 3),
        "wall_fused_s": round(fused["wall"], 3),
        "tokens_per_sec_split": round(split["tps"], 1),
        "tokens_per_sec_fused": round(fused["tps"], 1),
        "wall_note": "CPU wall times include the fused path's padded-row "
                     "compute; the structural dispatch counts are the "
                     "headline, TPU wall numbers pend a device session",
        "prefill_chunk": chunk,
        "n_requests": n_req,
        "max_new_tokens": max_new,
        "arrival_seed": seed,
        "max_batch": max_batch,
        "device": jax.devices()[0].device_kind,
    }


def _interference_trace(dalle, params, *, prefill_chunk, steady_new,
                        long_new, seed=0):
    """Drive one engine through the interference scenario: one request in
    steady decode, then a full-length prompt arrives mid-stream. Returns
    (max decode-iteration gap in seconds over the arrival→first-token
    window, the late request's ttft_s).

    Decode iterations are detected via the ``serve.decode_steps`` counter
    (metrics-side, always on — no telemetry dependency); the gap window is
    anchored at the late submit and closed at its first token, so a
    monolithic prefill shows up as one giant gap (no decode iterations
    land inside the window) while chunked prefill bounds every gap by one
    chunk's latency plus a decode step."""
    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, Outcome, Request, check_accounting,
    )
    from dalle_pytorch_tpu.utils.metrics import counters

    engine = Engine(dalle, params, EngineConfig(
        max_batch=2, prefill_chunk=prefill_chunk,
    ))
    text_seq = dalle.text_seq_len
    # warm every jit (monolithic prefill or the chunk widths, decode step)
    # outside the measured window — compile time is not interference. TWO
    # concurrent warm requests, so BOTH slot indices see their first
    # insert/release/decode here (the per-slot .at[i] cache ops compile on
    # first use per index)
    for i in range(2):
        engine.submit(Request(
            request_id=f"__warm{i}__", prompt=np.zeros(text_seq, np.int32),
            max_new_tokens=4, seed=0,
        ))
    engine.run()
    rng = np.random.RandomState(seed)
    vocab = min(NUM_TEXT, dalle.num_text_tokens)
    prompts = rng.randint(1, vocab, size=(2, text_seq)).astype(np.int32)
    engine.submit(Request(
        request_id="steady", prompt=prompts[0],
        max_new_tokens=steady_new, seed=1,
    ))
    prev = counters.get("serve.decode_steps")
    while counters.get("serve.decode_steps") - prev < 3:
        engine.step()  # steady request admitted and visibly decoding
    t_sub = engine.clock.now()
    engine.submit(Request(
        request_id="late", prompt=prompts[1],
        max_new_tokens=long_new, seed=2,
    ))
    ts = []
    prev = counters.get("serve.decode_steps")
    while engine.step():
        cur = counters.get("serve.decode_steps")
        if cur > prev:
            ts.append(engine.clock.now())
            prev = cur
    check_accounting(engine)
    for rid in ("steady", "late"):
        assert engine.results[rid].outcome is Outcome.COMPLETED, (
            rid, engine.results[rid]
        )
    ttft = engine.results["late"].ttft_s
    window_end = t_sub + ttft
    window = [t_sub] + [t for t in ts if t < window_end] + [window_end]
    return float(np.max(np.diff(window))), float(ttft)


def bench_serve_interference(on_cpu: bool, int8: bool | None = None,
                             seed: int = 0, quick: bool = False, model=None):
    """--serve companion: the long-prompt-arrival-during-steady-decode
    scenario. A request decodes steadily; a max-length prompt arrives; the
    record reports the MAX DECODE-ITERATION GAP the arrival caused — the
    interference metric chunked prefill exists to shrink — measured twice,
    with chunked prefill on (the headline ``value``) and with monolithic
    prefill, plus both TTFTs. Outside ``quick`` mode the record also
    ASSERTS the acceptance bound: the chunked gap must beat the monolithic
    gap (which contains the whole prefill). ``model`` overrides the
    flagship serving model (the telemetry smoke gate passes a tiny one).

    ``int8`` defaults to bf16 on CPU and int8 on device: this record
    measures SCHEDULING interference, and on CPU the int8 path pays a
    per-call head-weight dequantization that inflates the one-position
    final-chunk program to the same order as a whole prefill — an XLA-CPU
    artifact the TPU serving path does not have."""
    if int8 is None:
        int8 = not on_cpu
    if model is None:
        dalle, params, _, fmap = _serving_model(on_cpu, int8)
    else:
        dalle, params = model
        fmap = dalle.image_fmap_size
    T = dalle.text_len_internal
    chunk = max(2, T // 16)
    steady_new = min(fmap * fmap, 6 if quick else 48)
    long_new = min(fmap * fmap, 2 if quick else 8)
    # a max-gap is a wall-clock order statistic, so one OS scheduling
    # stall during the chunked trace can exceed the whole monolithic
    # prefill; re-measure the pair on a violated margin (the structural
    # gap — a full prefill vs one chunk — survives every clean run)
    # instead of failing the bench on a single noisy sample
    for attempt in range(3):
        mono_gap, mono_ttft = _interference_trace(
            dalle, params, prefill_chunk=None,
            steady_new=steady_new, long_new=long_new, seed=seed,
        )
        chunked_gap, chunked_ttft = _interference_trace(
            dalle, params, prefill_chunk=chunk,
            steady_new=steady_new, long_new=long_new, seed=seed,
        )
        if quick or chunked_gap < mono_gap:
            break
    if not quick:
        # the tentpole acceptance: with chunked prefill the decode loop
        # never stalls for the whole prefill — the max gap is bounded by
        # one chunk (+ a decode step), strictly below the monolithic gap
        assert chunked_gap < mono_gap, (
            f"chunked prefill did not shrink the decode-interference gap: "
            f"chunked {chunked_gap * 1e3:.1f} ms >= monolithic "
            f"{mono_gap * 1e3:.1f} ms (3 attempts)"
        )
    return {
        "metric": "serve_interference_max_decode_gap_ms_batch2"
                  + ("_int8" if int8 and model is None else ""),
        "int8": bool(int8),
        "value": round(chunked_gap * 1e3, 1),
        "unit": "ms",
        "vs_baseline": None,
        "monolithic_max_gap_ms": round(mono_gap * 1e3, 1),
        "gap_ratio": round(chunked_gap / mono_gap, 4) if mono_gap else None,
        "ttft_chunked_ms": round(chunked_ttft * 1e3, 1),
        "ttft_monolithic_ms": round(mono_ttft * 1e3, 1),
        "prefill_chunk": chunk,
        "n_chunks": -(-T // chunk),
        "prompt_positions": T,
        "steady_max_new_tokens": steady_new,
        "arrival_seed": seed,
        "device": jax.devices()[0].device_kind,
    }


def bench_serve_stages(on_cpu: bool, seed: int = 0):
    """--serve companion: the post-decode pipeline record (docs/DESIGN.md
    §8.5). One arrival trace through a chunked engine with the
    VAE_DECODE -> CLIP_RERANK stages enabled (the canonical
    contract-shape stage models from the trace registry; both stage jits
    warmed via ``PostDecodePipeline.warmup()``): a 2x-overload burst up
    front — every completion past the stage watermark must shed its
    post-decode work as a TYPED degraded outcome, never queue
    unboundedly — then a drained tail that measures the steady
    request->image end-to-end distribution.

    Record: request->image p50/p95/p99 (<-
    ``serve.stage.request_to_image_s``), per-stage latency
    (``vae_p50_ms``/``rerank_p50_ms`` <- the auto
    ``serve.stage.vae_decode_s``/``serve.stage.clip_rerank_s`` span
    histograms) and ``degraded_frac`` over the overload burst.

    In-bench asserts: 100% typed outcomes; the overload produced
    typed-degraded completions; the max decode-iteration gap with both
    stage jits in the dispatch mix stays within the chunked interference
    bound (one decode dispatch + granted prefill chunks + at most ONE
    batched dispatch per stage per iteration — stage work is budgeted,
    so a stage backlog can never stall the token loop for its whole
    depth); zero backend compiles and zero serving-jit recompiles
    inside the trace."""
    tools_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    from serve_smoke import build_tiny_model, build_tiny_stages

    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, Outcome, Request, check_accounting,
    )
    from dalle_pytorch_tpu.serving.postdecode import StageConfig
    from dalle_pytorch_tpu.utils.metrics import counters, histograms
    from dalle_pytorch_tpu.utils.telemetry import TELEMETRY

    dalle, params = build_tiny_model()
    tokens_per = dalle.image_seq_len
    text_len = dalle.text_seq_len
    rng = np.random.RandomState(seed)
    n_over = 8
    n_tail = 4 if on_cpu else 8
    prompts = rng.randint(
        1, 16, size=(n_over + n_tail, text_len)
    ).astype(np.int32)

    # watermark 0.05: any OTHER request still holding kv pages when a
    # completion reaches the stage boundary reads as past-saturation ->
    # typed degrade. The burst therefore degrades (slots stay occupied
    # the whole drain) while the spaced tail (own pages released before
    # enqueue, fleet otherwise idle) runs the full pipeline.
    stages = build_tiny_stages(config=StageConfig(high_watermark=0.05))
    cfg = EngineConfig(max_batch=2, prefill_chunk=2)

    def run_trace():
        TELEMETRY.configure(enabled=True, ring_size=1 << 14)
        engine = Engine(dalle, params, cfg, stages=stages)
        sig0, bc0 = serving_jit_signatures(), backend_compiles()
        # warm: both stage jits at the contract batch width, plus token
        # requests at BOTH slot occupancies — the per-occupancy eager
        # ops (slot insert, batched sampling state) compile here, not in
        # the timed trace
        engine.postdecode.warmup()
        engine.submit(Request(
            request_id="__warm__", prompt=np.zeros(text_len, np.int32),
            max_new_tokens=tokens_per, seed=0,
        ))
        engine.run(max_steps=50_000)
        for i in (1, 2):
            engine.submit(Request(
                request_id=f"__warm{i}__",
                prompt=np.zeros(text_len, np.int32),
                max_new_tokens=tokens_per, seed=i,
            ))
        engine.run(max_steps=50_000)
        sig1, bc1 = serving_jit_signatures(), backend_compiles()
        histograms.reset()  # percentiles cover the timed trace only

        gaps: list = []
        last_decode = [None]

        def drive():
            while True:
                d0 = counters.get("serve.decode_steps")
                busy = engine.step()
                if counters.get("serve.decode_steps") > d0:
                    t = time.perf_counter()
                    if last_decode[0] is not None:
                        gaps.append(t - last_decode[0])
                    last_decode[0] = t
                if not busy:
                    return

        # 2x-overload burst against the 2-slot engine
        for i in range(n_over):
            engine.submit(Request(
                request_id=f"ov{i}", prompt=prompts[i],
                max_new_tokens=tokens_per, seed=seed * 7919 + i,
            ))
        drive()
        # drained tail: steady-state request->image samples
        for i in range(n_tail):
            engine.submit(Request(
                request_id=f"tail{i}", prompt=prompts[n_over + i],
                max_new_tokens=tokens_per, seed=seed * 31 + i,
            ))
            drive()
        check_accounting(engine)
        sig2, bc2 = serving_jit_signatures(), backend_compiles()
        TELEMETRY.configure(enabled=False)
        results = {
            rid: r for rid, r in engine.results.items()
            if not rid.startswith("__warm")
        }
        return results, gaps, {
            "compiles_warm": bc1 - bc0,
            "compiles_trace": bc2 - bc1,
            "jit_signatures_warm": _sig_delta(sig1, sig0),
            "jit_recompiles_trace": _sig_delta(sig2, sig1),
        }

    def hmax(name: str) -> float:
        h = histograms.get(name)
        return 0.0 if h is None or h.count == 0 else h.max

    # a max-gap is a wall-clock order statistic (see
    # bench_serve_interference): re-measure on a violated margin instead
    # of failing the bench on one OS scheduling stall
    for attempt in range(3):
        results, gaps, compiles = run_trace()
        bound = 2.0 * (
            hmax("serve.decode_step_s")
            + 2.0 * hmax("serve.prefill_chunk_s")
            + hmax("serve.stage.vae_decode_s")
            + hmax("serve.stage.clip_rerank_s")
        ) + 0.01
        max_gap = max(gaps) if gaps else 0.0
        if max_gap <= bound:
            break
    assert max_gap <= bound, (
        f"stage dispatches stalled the decode loop past the chunked "
        f"interference bound: max gap {max_gap * 1e3:.1f} ms > "
        f"bound {bound * 1e3:.1f} ms (3 attempts)"
    )

    assert len(results) == n_over + n_tail
    untyped = {
        rid: r.outcome for rid, r in results.items()
        if r.outcome not in (Outcome.COMPLETED,
                             Outcome.COMPLETED_TOKENS_ONLY,
                             Outcome.COMPLETED_UNRANKED)
    }
    assert not untyped, f"untyped stage outcomes: {untyped}"
    over = [results[f"ov{i}"] for i in range(n_over)]
    degraded = [
        r for r in over if r.outcome is not Outcome.COMPLETED
    ]
    assert degraded, (
        "2x overload never tripped the stage degradation policy"
    )
    for r in degraded:
        assert r.outcome is Outcome.COMPLETED_TOKENS_ONLY, r.outcome
        assert r.tokens is not None and r.image is None, r.request_id
    completed = [
        r for r in results.values() if r.outcome is Outcome.COMPLETED
    ]
    assert len(completed) >= n_tail, (
        f"drained tail did not complete the full pipeline: "
        f"{len(completed)} < {n_tail}"
    )
    for r in completed:
        assert r.image is not None and r.rerank_score is not None, (
            r.request_id
        )
    assert compiles["compiles_trace"] in (0, -1), (
        f"stage timed trace compiled {compiles['compiles_trace']} modules"
    )
    assert all(
        v in (0, -1) for v in compiles["jit_recompiles_trace"].values()
    ), (
        f"stage timed trace recompiled serving jits: "
        f"{compiles['jit_recompiles_trace']}"
    )

    def pct(name: str, q: float) -> float:
        h = histograms.get(name)
        return 0.0 if h is None or h.count == 0 else round(
            h.percentile(q) * 1e3, 2
        )

    return {
        "metric": "serve_stage_request_to_image_p99_ms_batch2",
        "value": pct("serve.stage.request_to_image_s", 99),
        "unit": "ms",
        "vs_baseline": None,
        "p50_ms": pct("serve.stage.request_to_image_s", 50),
        "p95_ms": pct("serve.stage.request_to_image_s", 95),
        "p99_ms": pct("serve.stage.request_to_image_s", 99),
        "vae_p50_ms": pct("serve.stage.vae_decode_s", 50),
        "rerank_p50_ms": pct("serve.stage.clip_rerank_s", 50),
        "degraded_frac": round(len(degraded) / n_over, 4),
        "overload_requests": n_over,
        "tail_requests": n_tail,
        "max_decode_gap_ms": round(max_gap * 1e3, 2),
        "decode_gap_bound_ms": round(bound * 1e3, 2),
        **compiles,
        "device": jax.devices()[0].device_kind,
    }


def bench_serve_prefix(on_cpu: bool, int8: bool | None = None, seed: int = 0,
                       model=None):
    """--serve companion: the cross-request prefix-cache record (ROADMAP
    3, serving/prefix_cache.py). A seeded ZIPF-OF-PREFIXES arrival trace
    — a small pool of prompt templates drawn with zipf popularity, the
    production shape of templated text-to-image traffic — runs through
    one chunked engine with the content-addressed page index on, and the
    record reports the cache-hit rate, pages deduplicated at publish,
    and TTFT p50/p95 split cached-vs-cold (the ``serve.ttft_full_hit_s``
    / ``serve.ttft_cold_s`` histograms). Acceptance runs IN-BENCH:

      * hit rate > 0.5 (the zipf head re-uses its templates);
      * full-hit TTFT p50 strictly beats cold TTFT p50 — the cached
        admission pays one cached-logits sample where cold pays the
        whole chunked prefill;
      * cache-hit tokens are BIT-identical to the template's cold run:
        every request of a template carries the template's seed, so the
        cold first occurrence and every later hit must sample the same
        token sequence (the deeper split/fused/COW/preemption parity
        matrix lives in tests/test_prefix_cache.py);
      * the timed trace performs ZERO jit recompiles and ZERO backend
        compiles (PR 8 listener) — warm-up pays for the full-hit
        admission ops and ``_sample_cached_jit`` per slot index.

    ``int8`` defaults to bf16 on CPU (the head-dequant CPU artifact the
    sibling records document); ``model`` overrides the flagship serving
    model (tests pass a tiny one)."""
    from dalle_pytorch_tpu.ops import kv_policy
    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, Outcome, Request, check_accounting, pages_for,
    )
    from dalle_pytorch_tpu.utils.metrics import counters, histograms

    if int8 is None:
        int8 = not on_cpu
    if model is None:
        dalle, params, _, fmap = _serving_model(on_cpu, int8)
    else:
        dalle, params = model
        fmap = dalle.image_fmap_size
    T = dalle.text_len_internal
    chunk = max(2, T // 16)
    n_req = 9 if on_cpu else 48
    n_templates = 3 if on_cpu else 6
    max_batch = 2 if on_cpu else 8
    max_new = min(fmap * fmap, 4 if on_cpu else 32)
    zipf_exponent = 1.2
    rng = np.random.RandomState(seed)
    vocab = min(NUM_TEXT, dalle.num_text_tokens)
    templates = rng.randint(
        1, vocab, size=(n_templates, dalle.text_seq_len)
    ).astype(np.int32)
    # zipf popularity over template ranks; the first n_templates requests
    # are the forced cold first-occurrences (every template gets a clean
    # cold TTFT sample), the tail is the zipf draw
    w = 1.0 / np.arange(1, n_templates + 1) ** zipf_exponent
    draws = rng.choice(n_templates, size=n_req - n_templates, p=w / w.sum())
    prompt_pages = pages_for(T, kv_policy.page_size())

    engine = Engine(dalle, params, EngineConfig(
        max_batch=max_batch, prefill_chunk=chunk, prefix_cache=True,
        # headroom: every template chain + the warm chain stay resident
        prefix_cache_pages=(n_templates + 2) * prompt_pages,
    ))

    def submit(template, rid):
        rejected = engine.submit(Request(
            request_id=rid, prompt=templates[template] if template >= 0
            else np.zeros(dalle.text_seq_len, np.int32),
            max_new_tokens=max_new if template >= 0 else 2,
            # the template's OWN seed: cold first occurrence and every
            # later cache hit must sample identical tokens (in-bench
            # bit-parity)
            seed=seed * 7919 + (template if template >= 0 else -1),
        ))
        assert rejected is None, (rid, rejected)

    # warm-up, outside the timed trace: two concurrent cold requests
    # publish the warm chain and exercise both slot indices' insert ops;
    # then two concurrent FULL HITS warm _sample_cached_jit, the hit
    # admission's table-write ops and the COW copy for both slots, and
    # the dedup publish path
    for phase in range(2):
        for i in range(2):
            submit(-1, f"__warm{phase}{i}__")
        engine.run()
    sig0, bc0 = serving_jit_signatures(), backend_compiles()
    histograms.reset()  # TTFT percentiles cover the timed trace only
    hits0 = counters.get("serve.prefix.hits")
    miss0 = counters.get("serve.prefix.misses")
    dedup0 = counters.get("serve.prefix.pages_deduped")
    cow0 = counters.get("serve.prefix.cow_copies")

    t0 = engine.clock.now()
    # cold phase: each template's first occurrence runs to completion
    # (publish included) before the next — clean cold TTFT samples, no
    # publisher races
    for t in range(n_templates):
        submit(t, f"cold{t}")
        engine.run()
    # zipf phase: staggered submits (by iteration count — deterministic
    # admission schedule) so hits overlap decode like production traffic
    i0 = engine.iterations
    submitted = 0
    while True:
        while submitted < len(draws) and (
            submitted == 0
            or engine.iterations - i0 >= submitted * 2
        ):
            submit(int(draws[submitted]), f"zipf{submitted}")
            submitted += 1
        if not engine.step():
            if submitted >= len(draws):
                break
            submit(int(draws[submitted]), f"zipf{submitted}")
            submitted += 1
    wall = engine.clock.now() - t0
    check_accounting(engine)
    engine.verify_invariants(idle=True)
    sig1, bc1 = serving_jit_signatures(), backend_compiles()

    probes = (
        counters.get("serve.prefix.hits") - hits0
        + counters.get("serve.prefix.misses") - miss0
    )
    hit_rate = (counters.get("serve.prefix.hits") - hits0) / max(probes, 1)
    pages_deduped = counters.get("serve.prefix.pages_deduped") - dedup0
    cow_copies = counters.get("serve.prefix.cow_copies") - cow0
    compiles_trace = bc1 - bc0
    recompiles = _sig_delta(sig1, sig0)

    def pct(name, q):
        h = histograms.get(name)
        return None if h is None or not h.count else round(
            h.percentile(q) * 1e3, 2
        )

    ttft_cached_p50 = pct("serve.ttft_full_hit_s", 50)
    ttft_cold_p50 = pct("serve.ttft_cold_s", 50)

    # in-bench acceptance
    by_template: dict = {}
    for r in engine.results.values():
        if r.request_id.startswith("__warm"):
            continue
        assert r.outcome is Outcome.COMPLETED, (r.request_id, r.outcome)
        t = int(draws[int(r.request_id[4:])]) if r.request_id.startswith(
            "zipf") else int(r.request_id[4:])
        by_template.setdefault(t, []).append(np.asarray(r.tokens))
    for t, seqs in by_template.items():
        for s in seqs[1:]:
            assert np.array_equal(seqs[0], s), (
                f"template {t}: cache-hit tokens diverged from the cold run"
            )
    assert hit_rate > 0.5, (
        f"zipf trace hit rate {hit_rate:.3f} <= 0.5 — the index is not "
        "absorbing the template head"
    )
    assert ttft_cached_p50 is not None and ttft_cold_p50 is not None
    assert ttft_cached_p50 < ttft_cold_p50, (
        f"full-hit TTFT p50 {ttft_cached_p50}ms did not beat cold "
        f"{ttft_cold_p50}ms"
    )
    assert compiles_trace in (0, -1), (
        f"zipf timed trace compiled {compiles_trace} modules"
    )
    assert all(v in (0, -1) for v in recompiles.values()), (
        f"zipf timed trace recompiled serving jits: {recompiles}"
    )

    return {
        "metric": f"serve_prefix_hit_rate_batch{max_batch}"
                  + ("_int8" if int8 and model is None else ""),
        "int8": bool(int8),
        "value": round(hit_rate, 4),
        "unit": "hit_fraction",
        "vs_baseline": None,
        "hit_rate": round(hit_rate, 4),
        "pages_deduped": int(pages_deduped),
        "cow_copies": int(cow_copies),
        "index_pages_resident": len(engine.prefix),
        "ttft_cached_p50_ms": ttft_cached_p50,
        "ttft_cached_p95_ms": pct("serve.ttft_full_hit_s", 95),
        "ttft_cold_p50_ms": ttft_cold_p50,
        "ttft_cold_p95_ms": pct("serve.ttft_cold_s", 95),
        "ttft_source": "serve.ttft_full_hit_s / serve.ttft_cold_s "
                       "histograms (utils/metrics.py), timed trace only",
        "compiles_in_trace": compiles_trace,
        "jit_recompiles_in_trace": recompiles,
        "wall_s": round(wall, 3),
        "n_requests": n_req,
        "n_templates": n_templates,
        "zipf_exponent": zipf_exponent,
        "prefill_chunk": chunk,
        "max_new_tokens": max_new,
        "prompt_pages": prompt_pages,
        "arrival_seed": seed,
        "max_batch": max_batch,
        "device": jax.devices()[0].device_kind,
    }


def bench_serve_spec(on_cpu: bool, int8: bool | None = None, seed: int = 0,
                     model=None, spec_k: int = 3,
                     spec_draft_depth: int | None = None):
    """--serve companion: the speculative-decoding record (ROADMAP 2,
    ISSUE 11). One seeded staggered arrival trace runs through TWO fused
    engines — plain (one committed token per decode row per iteration)
    and SPECULATIVE (``_spec_iteration_jit``: each decode row self-drafts
    up to ``spec_k`` tokens and the single ragged dispatch verifies them,
    committing the exact-match accepted prefix plus one bonus target
    sample) — and the record reports the tokens/sec ratio, the overall
    draft-acceptance rate, and the accepted-tokens-per-verify-step
    distribution (the ``serve.spec_accepted_per_step`` histogram). The
    acceptance checks run IN-BENCH:

      * >1 accepted token per verify step on the seeded trace (the
        multi-token-decode claim — weight-stream cost amortized over
        the accepted prefix; the CPU-recordable half of the >1.5x
        tokens/sec target, whose wall-clock half pends a device
        session);
      * the speculative timed trace performs ZERO backend compiles and
        ZERO jit recompiles (verify widths, mixes, and budget-capped
        tail steps are all descriptor DATA under the one steady + one
        final-class signature pair that DTL11x pins for
        ``serving.iteration_spec``);
      * completed tokens are BIT-identical speculative vs plain for f32
        models (exact acceptance: the drafter moves the accept rate,
        never a token value). For the bf16 flagship the comparison is
        REPORTED, not asserted — the same cross-program-shape rounding
        caveat bench_serve_fused documents, with the additional wrinkle
        that a bf16 near-tie flip only changes WHICH tokens commit per
        step, never their values vs sequential bf16 decode of the same
        program shape.

    ``spec_draft_depth`` selects the early-exit drafter (None = the
    exact full-depth self-draft). CPU wall times carry the in-trace
    draft chain's un-stashed K/V copies (the documented CPU artifact;
    the TPU drafter stash is the known upgrade), so the structural
    accepted-per-step numbers are the headline and the tokens/sec ratio
    is context on CPU."""
    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, Outcome, Request, check_accounting,
    )
    from dalle_pytorch_tpu.utils.metrics import counters, histograms

    if int8 is None:
        int8 = not on_cpu
    if model is None:
        dalle, params, _, fmap = _serving_model(on_cpu, int8)
    else:
        dalle, params = model
        fmap = dalle.image_fmap_size
    T = dalle.text_len_internal
    chunk = max(2, T // 16)
    n_req = 5 if on_cpu else 32
    max_batch = 2 if on_cpu else 8
    max_new = min(fmap * fmap, 8 if on_cpu else 48)
    rng = np.random.RandomState(seed)
    vocab = min(NUM_TEXT, dalle.num_text_tokens)
    prompts = rng.randint(
        1, vocab, size=(n_req, dalle.text_seq_len)
    ).astype(np.int32)

    def run_mode(spec: bool) -> dict:
        engine = Engine(dalle, params, EngineConfig(
            max_batch=max_batch, prefill_chunk=chunk, fused_iteration=True,
            spec_decode=spec, spec_k=spec_k,
            spec_draft_depth=spec_draft_depth if spec else None,
        ))
        # warm both signature classes (steady + final chunk) and both
        # slot indices outside the timed trace
        for i in range(2):
            engine.submit(Request(
                request_id=f"__warm{i}__",
                prompt=np.zeros(dalle.text_seq_len, np.int32),
                max_new_tokens=2, seed=0,
            ))
        engine.run()
        histograms.reset()  # accepted-per-step covers the timed trace only
        sig0, bc0 = serving_jit_signatures(), backend_compiles()
        d0, i0 = engine.dispatches, engine.iterations
        drafted0 = counters.get("serve.spec.drafted")
        accepted0 = counters.get("serve.spec.accepted")
        steps0 = counters.get("serve.decode_steps")
        submitted = 0

        def submit_next():
            nonlocal submitted
            engine.submit(Request(
                request_id=f"req{submitted}", prompt=prompts[submitted],
                max_new_tokens=max_new, seed=seed * 7919 + submitted,
            ))
            submitted += 1

        t0 = time.perf_counter()
        while True:
            # staggered submits by iteration count — the same
            # deterministic admission schedule for both modes
            while submitted < n_req and (
                submitted == 0 or engine.iterations - i0 >= submitted * 2
            ):
                submit_next()
            if not engine.step():
                if submitted >= n_req:
                    break
                submit_next()
        wall = time.perf_counter() - t0
        check_accounting(engine)
        sig1, bc1 = serving_jit_signatures(), backend_compiles()
        toks = {
            r.request_id: np.asarray(r.tokens)
            for r in engine.results.values()
            if r.outcome is Outcome.COMPLETED
            and not r.request_id.startswith("__warm")
        }
        assert len(toks) == n_req, (
            f"{'spec' if spec else 'plain'} trace completed "
            f"{len(toks)}/{n_req}"
        )
        n_committed = sum(len(t) for t in toks.values())
        h = histograms.get("serve.spec_accepted_per_step")
        return {
            "wall": wall,
            "tps": n_committed / wall,
            "dispatches": engine.dispatches - d0,
            "iterations": engine.iterations - i0,
            "verify_steps": counters.get("serve.decode_steps") - steps0,
            "drafted": counters.get("serve.spec.drafted") - drafted0,
            "accepted": counters.get("serve.spec.accepted") - accepted0,
            "accepted_per_step": None if h is None or not h.count else {
                "count": int(h.count),
                "mean": round(h.sum / h.count, 3),
                "p50": round(h.percentile(50), 2),
                "p95": round(h.percentile(95), 2),
                "min": h.min,
                "max": h.max,
            },
            "compiles_trace": bc1 - bc0,
            "jit_recompiles_trace": _sig_delta(sig1, sig0),
            "tokens": toks,
        }

    plain = run_mode(spec=False)
    spec = run_mode(spec=True)

    # in-bench acceptance
    assert spec["drafted"] > 0, "speculative trace never drafted"
    accept_rate = spec["accepted"] / spec["drafted"]
    dist = spec["accepted_per_step"]
    assert dist is not None and dist["mean"] > 1.0, (
        f"speculation committed {dist} accepted tokens per verify step — "
        "never beat plain decode's one token per step"
    )
    assert spec["verify_steps"] < plain["verify_steps"], (
        f"speculative trace needed {spec['verify_steps']} verify steps vs "
        f"{plain['verify_steps']} plain decode steps for the same tokens"
    )
    assert spec["dispatches"] <= spec["iterations"], (
        "speculative engine exceeded one dispatch per iteration"
    )
    assert spec["compiles_trace"] in (0, -1), (
        f"speculative timed trace compiled {spec['compiles_trace']} modules"
    )
    assert all(v in (0, -1) for v in spec["jit_recompiles_trace"].values()), (
        f"speculative timed trace recompiled serving jits: "
        f"{spec['jit_recompiles_trace']}"
    )
    ident = [
        rid for rid, t in plain["tokens"].items()
        if np.array_equal(spec["tokens"][rid], t)
    ]
    bit_identical = len(ident) == n_req
    if jnp.dtype(dalle.dtype) == jnp.float32:
        assert bit_identical, (
            "speculative tokens diverged from plain decode on the f32 "
            "parity tier"
        )

    return {
        "metric": f"serve_spec_accepted_tokens_per_step_batch{max_batch}"
                  + ("_int8" if int8 and model is None else ""),
        "int8": bool(int8),
        "value": dist["mean"],
        "unit": "accepted_tokens/verify_step",
        "vs_baseline": None,
        "spec_k": spec_k,
        "spec_draft_depth": spec_draft_depth,
        "accept_rate": round(accept_rate, 4),
        "accepted_per_step": dist,
        "drafted": spec["drafted"],
        "accepted": spec["accepted"],
        "verify_steps_spec": spec["verify_steps"],
        "decode_steps_plain": plain["verify_steps"],
        "tokens_per_sec_spec": round(spec["tps"], 1),
        "tokens_per_sec_plain": round(plain["tps"], 1),
        "tps_ratio_spec_over_plain": round(spec["tps"] / plain["tps"], 4),
        "wall_spec_s": round(spec["wall"], 3),
        "wall_plain_s": round(plain["wall"], 3),
        "wall_note": "CPU wall carries the in-trace draft chain's "
                     "un-stashed K/V copies and padded-row compute; the "
                     "accepted-per-step distribution is the headline, "
                     "TPU tokens/sec pends a device session",
        "spec_dispatches": spec["dispatches"],
        "spec_iterations": spec["iterations"],
        "spec_tokens_bit_identical_to_plain": bool(bit_identical),
        "requests_bit_identical": len(ident),
        "parity_note": "exact acceptance makes speculative output "
                       "bit-identical by construction on the f32 parity "
                       "tier (asserted; tests/test_spec_decode.py); bf16 "
                       "flagship parity is reported like "
                       "bench_serve_fused's",
        "compiles_in_trace": spec["compiles_trace"],
        "jit_recompiles_in_trace": spec["jit_recompiles_trace"],
        "prefill_chunk": chunk,
        "n_requests": n_req,
        "max_new_tokens": max_new,
        "arrival_seed": seed,
        "max_batch": max_batch,
        "device": jax.devices()[0].device_kind,
    }


def bench_serve_replicas(on_cpu: bool, n_replicas: int = 3, seed: int = 0,
                         int8: bool = True):
    """--serve --replicas N: drive the replicated front door
    (serving/router.py) through one seeded arrival trace TWICE — clean,
    then with ``replica_crash`` armed to kill one replica mid-trace — and
    record aggregate tokens/sec, per-replica occupancy, and failover
    latency. The chaos run IS the acceptance gate and asserts in-bench:

      * 100% of requests end in a typed outcome (none lost, none
        duplicated — ``Router.verify_invariants`` after the run);
      * every completed request's tokens are BIT-identical to the
        no-fault run (the (seed, position) replay contract across replica
        boundaries);
      * the surviving replicas absorbed the requeued load: everything
        still completes, throughput degrades rather than collapses.

    Watermark degradation is left OFF here so clean and chaos runs have
    identical per-request budgets (a fleet-occupancy clamp would change
    token COUNTS between runs, which is degradation working as designed
    but would muddy the bit-parity comparison this record pins).

    CPU reading note: the router steps its in-process replicas
    SEQUENTIALLY on the host, so killing one replica can *raise*
    tokens/sec on CPU (fewer engines per router iteration) and
    ``chaos_throughput_degradation_frac`` can go negative. On real
    hardware replicas own separate chips and step concurrently; the
    number to trust cross-platform is the failover latency and the
    typed-outcome/bit-parity gate, not the CPU degradation sign."""
    from dalle_pytorch_tpu.serving import (
        EngineConfig, Outcome, Request, Router, RouterConfig,
    )
    from dalle_pytorch_tpu.utils.faults import FAULTS
    from dalle_pytorch_tpu.utils.metrics import counters, histograms

    dalle, params, depth, fmap = _serving_model(on_cpu, int8)
    rng = np.random.RandomState(seed)
    n_req = 3 * n_replicas if on_cpu else 16 * n_replicas
    max_batch = 2 if on_cpu else 8
    tokens_per = min(fmap * fmap, 16) if on_cpu else fmap * fmap
    mean_ia = 0.05 if on_cpu else 0.2

    arrivals = np.cumsum(rng.exponential(scale=mean_ia, size=n_req))
    prompts = rng.randint(1, NUM_TEXT, size=(n_req, TEXT_SEQ)).astype(np.int32)
    priorities = rng.randint(0, 3, size=n_req)
    crash_at = n_req // 2  # submission index arming the mid-trace kill

    def run_trace(crash: bool) -> dict:
        FAULTS.reset()
        histograms.reset()
        router = Router(
            dalle, params,
            RouterConfig(n_replicas=n_replicas, queue_limit=n_req + 1),
            EngineConfig(max_batch=max_batch),
        )
        # warm every replica's jits outside the timed trace (least-loaded
        # routing spreads one warm request per replica's free pool)
        for i in range(n_replicas):
            router.submit(Request(
                request_id=f"__warm{i}__",
                prompt=np.zeros(TEXT_SEQ, np.int32),
                max_new_tokens=1, seed=0,
            ))
        router.run(max_steps=10_000)
        deaths0 = counters.get("router.replica_deaths")
        t0 = router.clock.now()
        submitted = 0
        occ: dict = {r.id: [] for r in router._replicas}
        t_crash = None
        armed = False
        while True:
            now = router.clock.now() - t0
            # arm the kill mid-trace, once the fleet demonstrably has
            # in-flight work — the next step's victim (the busiest
            # replica) then carries requests to fail over
            if (
                crash and not armed and submitted >= crash_at
                and any(r.inflight for r in router._replicas)
            ):
                FAULTS.arm("replica_crash", 1)
                armed = True
            while submitted < n_req and arrivals[submitted] <= now:
                router.submit(Request(
                    request_id=f"req{submitted}",
                    prompt=prompts[submitted],
                    max_new_tokens=tokens_per,
                    deadline=t0 + arrivals[submitted] + (300 if on_cpu else 600),
                    priority=int(priorities[submitted]),
                    seed=seed * 7919 + submitted,
                ))
                submitted += 1
            busy = router.step()
            if t_crash is None and counters.get("router.replica_deaths") > deaths0:
                t_crash = router.clock.now() - t0
            for r in router._replicas:
                occ[r.id].append(r.engine.pool.occupancy)
            if not busy:
                if submitted >= n_req:
                    break
                time.sleep(min(0.005, max(0.0, arrivals[submitted] - now)))
        wall = router.clock.now() - t0
        router.verify_invariants()
        done = {
            rid: r for rid, r in router.results.items()
            if r.outcome is Outcome.COMPLETED and not rid.startswith("__warm")
        }
        stats = router.stats()
        return {
            "wall": wall,
            "tps": sum(len(r.tokens) for r in done.values()) / wall,
            "tokens": {rid: np.asarray(r.tokens) for rid, r in done.items()},
            "outcomes": stats["outcomes"],
            "per_replica_occupancy": {
                rid: round(float(np.mean(v)), 3) for rid, v in occ.items()
            },
            "replica_states": router.replica_states(),
            "deaths": counters.get("router.replica_deaths") - deaths0,
            "failovers": counters.get("router.failovers"),
            "t_crash": t_crash,
        }

    clean = run_trace(crash=False)
    chaos = run_trace(crash=True)

    # ---- the chaos gate (ISSUE 6 acceptance) ----
    assert chaos["deaths"] == 1, chaos["deaths"]
    n_results = sum(chaos["outcomes"].values())
    assert n_results == n_req + n_replicas, (  # trace + warmups, all typed
        f"{n_req + n_replicas} submitted but {n_results} typed outcomes"
    )
    for rid, toks in clean["tokens"].items():
        assert rid in chaos["tokens"], f"{rid} lost in the chaos run"
        assert np.array_equal(toks, chaos["tokens"][rid]), (
            f"{rid} tokens diverged across replica failover"
        )
    assert chaos["tps"] > 0, chaos
    assert chaos["failovers"] >= 1, chaos  # someone actually failed over

    fh = histograms.get("router.failover_latency_s")
    degradation = 1.0 - chaos["tps"] / clean["tps"] if clean["tps"] else 0.0
    return {
        "metric": f"serve_replicas{n_replicas}_tokens_per_sec"
                  + ("_int8" if int8 else ""),
        "value": round(clean["tps"], 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "n_replicas": n_replicas,
        "n_requests": n_req,
        "max_batch_per_replica": max_batch,
        "tokens_per_request": tokens_per,
        "aggregate_tokens_per_sec": round(clean["tps"], 1),
        "per_replica_occupancy_mean": clean["per_replica_occupancy"],
        # chaos (kill-one-replica-mid-trace) record
        "chaos_tokens_per_sec": round(chaos["tps"], 1),
        "chaos_throughput_degradation_frac": round(float(degradation), 4),
        "chaos_outcomes": {k: v for k, v in chaos["outcomes"].items() if v},
        "chaos_replica_states": chaos["replica_states"],
        "chaos_requests_failed_over": chaos["failovers"],
        "chaos_crash_at_s": (
            None if chaos["t_crash"] is None else round(chaos["t_crash"], 3)
        ),
        "failover_latency_p50_ms": (
            None if fh is None else round(fh.percentile(50) * 1e3, 1)
        ),
        "failover_latency_max_ms": (
            None if fh is None else round(fh.max * 1e3, 1)
        ),
        "bit_identical_vs_clean": True,  # asserted above
        "mean_interarrival_s": mean_ia,
        "arrival_seed": seed,
        "device": jax.devices()[0].device_kind,
    }


def bench_serve_recovery(on_cpu: bool, seed: int = 0, int8: bool = True):
    """--serve: the crash-recovery record (docs/DESIGN.md §8.3). Three
    phases against a journaled, prefix-cached, respawn-enabled router:

      1. *Cold trace* — a template-pool arrival trace populates the
         prefix index and the cold-TTFT histogram; the warm index is
         snapshotted (two-phase COMMITTED manifest).
      2. *Replica kill → respawn* — ``replica_crash`` kills the busiest
         replica mid-trace; the respawn policy rebuilds it
         (DEAD→RESPAWNING→HEALTHY) and the record reports the
         kill→healthy MTTR from the ``serve.recovery_s`` histogram.
      3. *Process restart* — the router is abandoned mid-flight
         (journal unsealed — a real crash), a fresh router restores the
         snapshot (verify-on-load), replays the journal's unfinished
         requests, and serves one more template request that must be a
         prefix HIT against the RESTORED arena. The record reports
         warm-vs-cold TTFT after restore and the backend-compile /
         serving-jit-signature deltas across the post-restart serving
         window (zero: restart must not re-enter compilation on the
         hot path — the jit caches are process-global and every shape
         was warmed in phase 1).

    In-bench asserts (the ISSUE 12 acceptance): every journal-replayed
    request completes with tokens bit-identical to a fault-free
    reference run, the snapshot restore produced at least one warm hit,
    at least one respawn happened with finite MTTR, and the
    post-restart serving window performed zero backend compiles and
    zero serving-jit recompiles.

    SIGTERM during the drive loops triggers the serving preemption
    path: router graceful drain + journal seal + snapshot flush before
    exit (the serving analog of the trainer's emergency checkpoint)."""
    import tempfile

    from dalle_pytorch_tpu.serving import (
        Engine, EngineConfig, Outcome, Request, RequestJournal, Router,
        RouterConfig, replay_unfinished,
    )
    from dalle_pytorch_tpu.utils.faults import FAULTS
    from dalle_pytorch_tpu.utils.metrics import counters, histograms
    from dalle_pytorch_tpu.utils.resilience import (
        PreemptionHandler, RetryPolicy,
    )
    from dalle_pytorch_tpu.utils.telemetry import TELEMETRY

    dalle, params, depth, fmap = _serving_model(on_cpu, int8)
    rng = np.random.RandomState(seed)
    tokens_per = min(fmap * fmap, 16) if on_cpu else fmap * fmap
    n_cold = 4 if on_cpu else 16
    templates = [
        rng.randint(1, NUM_TEXT, size=(TEXT_SEQ,)).astype(np.int32)
        for _ in range(2)
    ]
    tmp = tempfile.mkdtemp(prefix="bench_recovery_")
    jpath = os.path.join(tmp, "journal.jsonl")
    snapdir = os.path.join(tmp, "prefix_snapshot")
    engine_cfg = EngineConfig(
        max_batch=2 if on_cpu else 8, prefill_chunk=16, prefix_cache=True,
    )
    router_cfg = RouterConfig(
        n_replicas=2, respawn=True,
        respawn_backoff=RetryPolicy(
            attempts=3, base_delay=0.05 if on_cpu else 0.5,
            max_delay=5.0, jitter=0.0, retry_on=(),
        ),
    )

    def make_request(i: int, template: int) -> Request:
        return Request(
            request_id=f"rec{i}", prompt=templates[template],
            max_new_tokens=tokens_per, seed=seed * 7919 + i,
        )

    # fault-free reference for the phase-3 bit-parity gate
    ref_engine = Engine(dalle, params, engine_cfg)
    ref_reqs = [make_request(100, 0), make_request(101, 1)]
    for r in ref_reqs:
        assert ref_engine.submit(r) is None
    reference = {
        rid: np.asarray(res.tokens)
        for rid, res in ref_engine.run(max_steps=50_000).items()
    }

    FAULTS.reset()
    histograms.reset()
    router = Router(
        dalle, params, router_cfg, engine_cfg,
        journal=RequestJournal(jpath),
    )

    def drive(rt, ph):
        steps = 0
        while True:
            if ph.triggered:
                # the serving preemption path: graceful drain + durable
                # flush, then exit — the SIGTERM contract
                rt.shutdown(snapshot_dir=snapdir)
                raise SystemExit(0)
            if not rt.step():
                return
            steps += 1
            assert steps < 100_000, "recovery bench made no progress"

    with PreemptionHandler(
        on_signal=lambda s: TELEMETRY.drain("preempt_signal")
    ) as ph:
        # ---- phase 1: cold trace + snapshot ----
        for i in range(n_cold):
            assert router.submit(make_request(i, i % 2)) is None
        drive(router, ph)
        router.verify_invariants()
        eng0 = next(
            r.engine for r in router._replicas
            if r.engine.prefix is not None and len(r.engine.prefix)
        )
        snap_nodes = eng0.save_prefix_snapshot(snapdir)

        # ---- phase 2: replica kill -> respawn MTTR ----
        respawns0 = counters.get("router.respawns")
        kill_reqs = [make_request(n_cold + i, i % 2) for i in range(4)]
        for r in kill_reqs:
            assert router.submit(r) is None
        armed = False
        steps = 0
        while True:
            if ph.triggered:
                router.shutdown(snapshot_dir=snapdir)
                raise SystemExit(0)
            if not armed and any(r.inflight for r in router._replicas):
                FAULTS.arm("replica_crash", 1)
                armed = True
            busy = router.step()
            steps += 1
            assert steps < 100_000, "phase 2 made no progress"
            if (
                not busy
                and counters.get("router.respawns") > respawns0
            ):
                break
        router.verify_invariants()
        respawns = counters.get("router.respawns") - respawns0

        def pct(name, q):
            # engine histograms are per-replica labeled series; report
            # the busiest replica's (the one that observed the class)
            best = None
            for rid in range(router_cfg.n_replicas):
                h = histograms.get(name, labels={"replica": str(rid)})
                if h is not None and (best is None or h.count > best.count):
                    best = h
            return (
                None if best is None
                else round(best.percentile(q) * 1e3, 2)
            )

        # freeze every phase-1/2 statistic NOW: the histograms reset at
        # the restart boundary below so the "after restore" TTFT split
        # carries ONLY post-restart samples, not pre-crash warm hits
        ttft_cold_p50 = pct("serve.ttft_cold_s", 50)
        rh = None
        for rid in range(router_cfg.n_replicas):
            rh = rh or histograms.get(
                "serve.recovery_s", labels={"replica": str(rid)}
            )
        mttr_p50 = None if rh is None else round(rh.percentile(50) * 1e3, 1)
        mttr_max = None if rh is None else round(rh.max * 1e3, 1)

        # ---- phase 3: process restart from journal + snapshot ----
        # the crash set shares (prompt, seed) with the reference run,
        # which is what makes the bit-parity gate meaningful
        crash_reqs = ref_reqs
        for r in crash_reqs:
            assert router.submit(r) is None
        router.step()
        router.step()  # demonstrably in flight
        router._journal.close()  # the process dies here

        t_restart = time.perf_counter()
        histograms.reset()  # the post-restart measurement window opens
        router2 = Router(
            dalle, params, router_cfg, engine_cfg,
            journal=RequestJournal(jpath),
        )
        restored = all(
            r.engine.load_prefix_snapshot(snapdir)
            for r in router2._replicas
        )
        replayed = replay_unfinished(
            jpath, router2.submit, now=router2.clock.now()
        )
        compiles0 = backend_compiles()
        sigs0 = serving_jit_signatures()
        drive(router2, ph)
        router2.verify_invariants()
        recovery_wall = time.perf_counter() - t_restart
        compiles = backend_compiles() - compiles0
        sig_delta = _sig_delta(serving_jit_signatures(), sigs0)
        # router2's engines are fresh, so their lifetime hit tallies ARE
        # the post-restart hits (serve.prefix.hits is per-replica
        # labeled; the engines' own stats aggregate cleanly here)
        warm_hits = sum(
            r.engine.prefix.stats.hits
            for r in router2._replicas if r.engine.prefix is not None
        )

    # ---- gates ----
    assert respawns >= 1, "no replica respawned in phase 2"
    for rid in [r.request_id for r in crash_reqs]:
        res = router2.results[rid]
        assert res.outcome is Outcome.COMPLETED, (rid, res.outcome)
        assert np.array_equal(np.asarray(res.tokens), reference[rid]), (
            f"{rid} post-restart tokens diverge from the fault-free "
            "reference"
        )
    assert restored, "snapshot restore was rejected on a clean save"
    assert warm_hits >= 1, "no post-restart prefix hit on the restored arena"
    assert compiles in (0, -1), (
        f"{compiles} backend compiles in the post-restart serving window"
    )
    assert all(v <= 0 for v in sig_delta.values()), sig_delta

    return {
        "metric": "serve_recovery_mttr_ms" + ("_int8" if int8 else ""),
        "value": mttr_p50,
        "unit": "ms",
        "vs_baseline": None,
        "respawns": respawns,
        "mttr_max_ms": mttr_max,
        "snapshot_nodes": snap_nodes,
        "snapshot_restored": bool(restored),
        "journal_replayed": len(replayed),
        "restart_recovery_wall_s": round(recovery_wall, 3),
        "warm_hits_after_restore": warm_hits,
        # warm: post-restart window only (histograms reset at t_restart);
        # cold: the phase-1 cold trace, frozen before the reset
        "ttft_warm_after_restore_p50_ms": pct("serve.ttft_full_hit_s", 50),
        "ttft_cold_p50_ms": ttft_cold_p50,
        "bit_identical_replay": True,   # asserted above
        "post_restart_backend_compiles": compiles,
        "post_restart_jit_signature_delta": sig_delta,
        "mttr_source": "serve.recovery_s{replica=i} (kill -> healthy)",
        "device": jax.devices()[0].device_kind,
    }


def bench_serve_control(on_cpu: bool, seed: int = 0):
    """--serve / --flagship companion: the adaptive control loop measured
    IN-BENCH (ISSUE 19). Two forced regimes drive the Controller's two
    headline knob channels end to end through REAL engines, and the
    record asserts the adaptation happened through zero-recompile
    channels:

      * spec channel — a depth-4 f32 model whose depth-1 early-exit
        drafter genuinely misdrafts (~0.3 windowed accept rate, below
        ``spec_accept_low``) runs controller-on vs controller-off over
        one seeded trace. Asserted: the effective verify width steps
        DOWN from the pre-traced ceiling; the post-warmup trace performs
        ZERO backend compiles and ZERO serving-jit recompiles (the
        width is descriptor DATA under the pre-traced signatures); and
        completed tokens are BIT-identical controller-on vs -off
        (exact-match acceptance absorbs any verify width — the
        controller moves cost, never output).
      * budget channel — the same geometry decodes on a virtual clock
        whose per-iteration dt jumps 100x mid-trace: the deterministic
        stand-in for interference (the traffic simulator's virtual-time
        idiom; real-gap wall clock lives in bench_serve_interference).
        Asserted: the TokenBudget holds at its default while gaps sit
        under the SLO threshold, tightens toward the liveness floor
        while they exceed it, relaxes back to the default once the
        vitals window flushes, keeps the SAME chunk width throughout
        (grant geometry never re-traces), and every request still
        completes (the head-of-line floor).

    The record's value is the spec channel's width drop."""
    from dalle_pytorch_tpu.models import DALLE
    from dalle_pytorch_tpu.serving import (
        ControlConfig, Engine, EngineConfig, FakeClock, Outcome, Request,
        check_accounting,
    )

    # misdrafting geometry (tests/test_control.py): small enough for any
    # host, deep enough that the depth-1 drafter's accept rate sits well
    # under the default spec_accept_low
    dalle = DALLE(
        dim=32, depth=4, num_text_tokens=32, text_seq_len=6,
        num_image_tokens=64, image_fmap_size=4, heads=2, dim_head=8,
        attn_types=("full",), shift_tokens=True, rotary_emb=True,
    )
    rng = np.random.RandomState(seed)
    text = jnp.asarray(rng.randint(1, 32, size=(1, 6)), jnp.int32)
    image = jnp.asarray(rng.randint(0, 64, size=(1, 16)), jnp.int32)
    params = dalle.init(jax.random.key(0), text, image)["params"]

    n_req, max_new, spec_k = 4, 10, 3
    prompts = [
        np.random.RandomState(seed * 7919 + 100 + i)
        .randint(1, 32, size=(6,)).astype(np.int32)
        for i in range(n_req)
    ]

    def submit_all(eng, max_new_tokens):
        for i in range(n_req):
            eng.submit(Request(
                request_id=f"r{i}", prompt=prompts[i],
                max_new_tokens=max_new_tokens, seed=seed * 31 + i,
            ))

    # ---- spec channel: forced-low accept, parity, zero recompiles ----
    def spec_run(controller: bool):
        eng = Engine(dalle, params, EngineConfig(
            max_batch=2, prefill_chunk=2, fused_iteration=True,
            spec_decode=True, spec_k=spec_k, spec_draft_depth=1,
            controller=controller,
            control=ControlConfig(interval=4) if controller else None,
        ), clock=FakeClock(step_dt=1.0))
        # warm both signature classes + slot indices outside the measured
        # trace
        for i in range(2):
            eng.submit(Request(
                request_id=f"__warm{i}__",
                prompt=np.zeros(6, np.int32), max_new_tokens=2, seed=0,
            ))
        eng.run(max_steps=400)
        sig0, bc0 = serving_jit_signatures(), backend_compiles()
        submit_all(eng, max_new)
        eng.run(max_steps=800)
        sig1, bc1 = serving_jit_signatures(), backend_compiles()
        check_accounting(eng)
        toks = {
            r.request_id: np.asarray(r.tokens)
            for r in eng.results.values()
            if r.outcome is Outcome.COMPLETED
            and not r.request_id.startswith("__warm")
        }
        assert len(toks) == n_req, (
            f"{'controller-on' if controller else 'controller-off'} trace "
            f"completed {len(toks)}/{n_req}"
        )
        return (
            eng, toks,
            bc1 - bc0, _sig_delta(sig1, sig0),
        )

    eng_on, toks_on, compiles_trace, sig_trace = spec_run(controller=True)
    _, toks_off, _, _ = spec_run(controller=False)

    assert eng_on._eff_spec_k < spec_k, (
        f"controller never stepped spec_k down from {spec_k} under the "
        f"misdrafter's forced-low accept rate"
    )
    reasons = [r for d in eng_on.controller.log for r in d.reasons]
    assert "spec_down" in reasons
    assert compiles_trace in (0, -1), (
        f"adaptive trace compiled {compiles_trace} modules — a knob "
        f"channel re-traced"
    )
    assert all(v in (0, -1) for v in sig_trace.values()), (
        f"adaptive trace recompiled serving jits: {sig_trace}"
    )
    assert all(
        np.array_equal(toks_on[rid], toks_off[rid]) for rid in toks_off
    ), "controller-on tokens diverged from controller-off (f32 parity)"
    spec_vitals = eng_on.vitals.snapshot()

    # ---- budget channel: virtual-time interference ----
    cc = ControlConfig(interval=2, gap_high_s=0.5)
    clock = FakeClock(step_dt=0.02)
    eng = Engine(dalle, params, EngineConfig(
        max_batch=2, prefill_chunk=2, fused_iteration=True,
        controller=True, control=cc, vitals_window=4,
    ), clock=clock)
    budget_default, chunk = eng.budget.budget, eng.budget.chunk
    floor = max(chunk, int(budget_default * cc.budget_min_frac))
    submit_all(eng, 16)  # fmap^2 caps max_new at 16 on this geometry
    steps = 0
    while steps < 10 and eng.step():
        steps += 1
    assert eng.budget.budget == budget_default, (
        "budget moved while every gap sat under the SLO threshold"
    )
    clock.step_dt = 2.0  # interference regime: every gap breaches the SLO
    budget_min = budget_default
    steps = 0
    while steps < 10 and eng.step():
        steps += 1
        budget_min = min(budget_min, eng.budget.budget)
    assert budget_min < budget_default, (
        "budget never tightened under forced interference gaps"
    )
    clock.step_dt = 0.02  # interference clears; the vitals window flushes
    recovered = False
    while eng.step():
        recovered = recovered or eng.budget.budget == budget_default
    assert recovered, "budget never relaxed back after interference cleared"
    assert eng.budget.chunk == chunk, (
        "budget adaptation changed the grant chunk — that is a retrace "
        "channel"
    )
    results = {
        r.request_id: r for r in eng.results.values()
    }
    assert len(results) == n_req and all(
        r.outcome is Outcome.COMPLETED for r in results.values()
    ), "budget tightening starved a request (head-of-line floor broken)"
    check_accounting(eng)

    return {
        "metric": "serve_control_spec_k_steps_down",
        "value": float(spec_k - eng_on._eff_spec_k),
        "unit": "verify_width_steps",
        "vs_baseline": None,
        "spec_k_ceiling": spec_k,
        "spec_k_adapted": int(eng_on._eff_spec_k),
        "windowed_accept_rate": round(spec_vitals["spec_accept_rate"], 4),
        "decisions": len(eng_on.controller.log),
        "adjustments": sum(d.changed for d in eng_on.controller.log),
        "controller_on_tokens_bit_identical_to_off": True,  # asserted
        "compiles_in_trace": compiles_trace,
        "jit_recompiles_in_trace": sig_trace,
        "budget_default": budget_default,
        "budget_min_under_interference": budget_min,
        "budget_floor": floor,
        "budget_recovered_to_default": True,  # asserted
        "budget_chunk": chunk,
        "gap_slo_s": cc.gap_high_s,
        "clock_note": "virtual time (FakeClock): the dt jump is the "
                      "deterministic interference stand-in; wall-clock "
                      "interference lives in bench_serve_interference",
        "n_requests": n_req,
        "max_new_tokens": max_new,
        "arrival_seed": seed,
        "device": jax.devices()[0].device_kind,
    }


def bench_pallas_block_sweep(on_cpu: bool, seq: int = 1280,
                             fmap: int = IMAGE_FMAP):
    """--flagship companion: the Pallas pair-grid block-size sweep
    (ISSUE 19). The flagship-seq axial_row mask is recompiled into a
    BlockLayout at each (block_q, block_k) and the kernel runs at that
    granularity; every record carries the structural ledger — visited
    pair count and ``visited_block_frac`` (the executed-FLOP ratio) —
    plus a per-grid-step VMEM working-set estimate against the ~16 MiB
    per-core budget the Pallas guide documents, and the kernel wall
    time. The sweep is the measured block-size trade: smaller blocks
    hug the mask tighter (lower visited frac, fewer dead FLOPs) but
    shrink the per-step MXU tile and multiply grid steps; bigger blocks
    amortize grid overhead but pay for more masked-out work and a
    bigger VMEM slice. Off-TPU the kernel runs in interpret mode — the
    same trace the Mosaic lowering consumes, so CPU wall times rank
    trace overheads only (TPU wall clock pends a device session) and
    the structural ledger is the headline. Parity vs the shared-einsum
    reference is asserted at EVERY block size: layout granularity must
    never change an output bit."""
    from dalle_pytorch_tpu.ops import block_sparse_attention as bs
    from dalle_pytorch_tpu.ops.masks import pattern_mask

    text_len = seq - fmap * fmap
    mask = pattern_mask("axial_row", text_len, fmap)
    rng = np.random.RandomState(0)
    q, k, v = (
        jnp.asarray(rng.randn(1, 2, seq, DIM_HEAD), jnp.float32)
        for _ in range(3)
    )
    interpret = jax.devices()[0].platform != "tpu"
    vmem_budget = 16 * 1024 * 1024
    n_reps = 2 if on_cpu else 10

    results = []
    for bq, bk in ((64, 64), (128, 128), (256, 256)):
        lay = bs.compile_block_layout(mask, bq, bk)
        run = lambda: bs.block_sparse_attention(
            q, k, v, lay, sm_scale=DIM_HEAD**-0.5, interpret=interpret
        )
        out = run()
        ref = bs.reference_attend(q, k, v, lay, sm_scale=DIM_HEAD**-0.5)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 2e-5, (
            f"bq{bq}/bk{bk}: block granularity changed the kernel output "
            f"(max err {err} vs the shared-einsum reference)"
        )
        t0 = time.perf_counter()
        for _ in range(n_reps):
            jax.block_until_ready(run())
        dt = (time.perf_counter() - t0) / n_reps
        # per-grid-step VMEM residency, f32: q/out tiles (bq x d), k/v
        # tiles (bk x d), the score tile (bq x bk), m/l rows
        vmem_est = 4 * (
            2 * bq * DIM_HEAD + 2 * bk * DIM_HEAD + bq * bk + 2 * bq
        )
        results.append({
            "metric": f"pallas_block_sweep_time_bq{bq}_bk{bk}_seq{seq}",
            "value": round(dt * 1e3, 2),
            "unit": "ms",
            "vs_baseline": None,
            "pattern": "axial_row",
            "n_pairs": lay.n_pairs,
            "dense_pairs": lay.dense_pairs,
            "visited_block_frac": round(lay.visited_block_frac, 4),
            "vmem_bytes_per_step_est": vmem_est,
            "vmem_frac_of_budget": round(vmem_est / vmem_budget, 4),
            "kernel_reference_max_err": err,
            "interpret_mode": interpret,
            "wall_clock_note": (
                "interpret-mode trace on a non-TPU host: structural "
                "ledger is the headline, MXU wall clock pends a device "
                "session" if interpret else None
            ),
            "reps": n_reps,
            "text_len": text_len,
            "image_fmap": fmap,
            "device": jax.devices()[0].device_kind,
        })
    return results


def model_flops_per_step(batch: int, depth: int = DEPTH) -> float:
    """Analytic fwd+bwd matmul FLOPs per train step, standard MFU convention
    (backward = 2x forward; recompute does not count)."""
    n = TEXT_SEQ + IMAGE_FMAP**2  # 1280
    total_tokens = NUM_TEXT + TEXT_SEQ + NUM_IMAGE
    per_layer_params = 16 * DIM * DIM  # qkv 3d² + out d² + GEGLU 12d²
    matmul_params = depth * per_layer_params + DIM * total_tokens
    fwd = 2 * batch * n * matmul_params  # dense matmuls
    fwd += depth * 4 * batch * n * n * (HEADS * DIM_HEAD)  # QK^T + AV
    return 3 * fwd


def device_flops_per_step(batch: int, depth: int = DEPTH, rotary: bool = True) -> float:
    """FLOPs the hardware actually executes per step — the cross-check
    target for XLA cost analysis. Differs from the MFU convention in the
    attention kernels: the recompute-based flash backward re-derives the
    score matrix in both the dq and dk/dv passes (4 + 6 block dots vs the
    convention's 4), and partially-masked blocks execute full-square.
    ``rotary`` mirrors the benchmarked model's rotary_emb flag: the
    in-kernel rotate-half P-dots only execute when the fused path receives
    a rotary table (counting them unconditionally overstated device FLOPs
    ~6% for a no-rotary config)."""
    from dalle_pytorch_tpu.ops.attention import _flash_block
    from dalle_pytorch_tpu.ops.flash_attention import (
        _block_visit_map,
        fused_qkv_supported,
    )

    n = TEXT_SEQ + IMAGE_FMAP**2
    per_layer_params = 16 * DIM * DIM
    dense = 3 * 2 * batch * n * depth * per_layer_params
    # the loss head executes only the block-diagonal live blocks (text
    # positions x text vocab + image positions x image vocab — the logits
    # mask zeroes everything else, models/dalle.py:_split_head_loss); the
    # model-FLOPs convention above still counts the full n x vocab head,
    # same as it counts full-square attention that flash skips
    ext = NUM_TEXT + TEXT_SEQ
    dense += 3 * 2 * batch * DIM * (TEXT_SEQ * ext + IMAGE_FMAP**2 * NUM_IMAGE)

    block = _flash_block(n)
    if block == n and fused_qkv_supported(n, HEADS, DIM_HEAD):
        # packed single-block path: fwd 2 dots + ONE fused backward pass of
        # 5 dots (s, dp, dq, dv, dk) = 7 per head, plus (when the model has
        # rotary) the in-kernel rotate-half P-dots (3 fwd + 6 bwd per head:
        # q/k/v rotation in both passes and the inverse rotation of the
        # three grads) — matches _fused_cost in ops/flash_attention.py
        attn = depth * batch * HEADS * 7 * 2 * n * n * DIM_HEAD
        if rotary:
            attn += depth * batch * HEADS * 9 * 2 * n * DIM_HEAD * DIM_HEAD
    elif block:
        visit = _block_visit_map(n // block, n // block, block, block, True, None)
        live = int((visit > 0).sum())
        # fwd 2 dots + dq 3 (s, dp, dq) + dkv 4 (s, dv, dp, dk) = 9
        # block-dots per live block (matches the kernels' CostEstimates)
        attn = depth * batch * HEADS * live * 9 * 2 * block * block * DIM_HEAD
    else:
        attn = depth * 9 * batch * n * n * (HEADS * DIM_HEAD) // 2
    return dense + attn


def compiled_flops(compiled, fallback: float) -> float:
    """FLOPs of one step from XLA cost analysis (pallas kernels included via
    their CostEstimate); falls back to the analytic count when the backend
    exposes no cost model."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
        return flops if flops > 0 else fallback
    except Exception:
        return fallback


def build(batch: int, depth: int, attn_types=("full",)):
    from dalle_pytorch_tpu.models import DALLE
    from dalle_pytorch_tpu.parallel import create_train_state, make_runtime, make_train_step

    dalle = DALLE(
        dim=DIM,
        depth=depth,
        num_text_tokens=NUM_TEXT,
        text_seq_len=TEXT_SEQ,
        num_image_tokens=NUM_IMAGE,
        image_fmap_size=IMAGE_FMAP,
        heads=HEADS,
        dim_head=DIM_HEAD,
        attn_types=attn_types,
        dtype=jnp.bfloat16,
    )
    rng = np.random.RandomState(0)
    batch_data = {
        "text": jnp.asarray(rng.randint(1, NUM_TEXT, size=(batch, TEXT_SEQ)), jnp.int32),
        "image": jnp.asarray(
            rng.randint(0, NUM_IMAGE, size=(batch, IMAGE_FMAP**2)), jnp.int32
        ),
    }

    runtime = make_runtime(devices=jax.devices()[:1])
    params = jax.jit(dalle.init)(
        jax.random.key(0), batch_data["text"], batch_data["image"]
    )["params"]
    opt = optax.chain(optax.clip_by_global_norm(0.5), optax.adam(3e-4))
    state, shardings = create_train_state(params, opt, runtime)

    def loss_fn(p, b, rng):
        return dalle.apply({"params": p}, b["text"], b["image"], return_loss=True)

    step = make_train_step(loss_fn, opt, runtime, shardings)
    return dalle, state, step, batch_data


def bench_train(on_cpu: bool):
    batch = 2 if on_cpu else BATCH
    depth = 2 if on_cpu else DEPTH
    dalle, state, step, batch_data = build(batch, depth)

    lowered = step.lower(state, batch_data, jax.random.key(0))
    compiled = lowered.compile()
    analytic = model_flops_per_step(batch, depth)
    device_analytic = device_flops_per_step(batch, depth, rotary=dalle.rotary_emb)
    xla_flops = compiled_flops(compiled, device_analytic)

    # warmup / compile; float() is the device->host sync
    for i in range(3):
        state, loss = step(state, batch_data, jax.random.key(i))
    float(loss)

    n_steps = 3 if on_cpu else 20
    t0 = time.perf_counter()
    for i in range(n_steps):
        state, loss = step(state, batch_data, jax.random.key(i))
    float(loss)
    dt = time.perf_counter() - t0

    step_time = dt / n_steps
    # MFU uses the standard model-FLOPs convention; the XLA cost analysis
    # (which counts executed FLOPs incl. backward recompute) cross-checks
    # the device-FLOPs analytic to catch accounting drift
    mfu = analytic / step_time / peak_flops()
    hw_util = xla_flops / step_time / peak_flops()
    result = {
        "metric": "train_mfu_dalle_depth12_dim1024_seq1280_1chip",
        "value": round(float(mfu), 4),
        "unit": "fraction_of_peak_bf16",
        "vs_baseline": round(float(mfu) / 0.45, 4),
        "image_tokens_per_sec_per_chip": round(batch * IMAGE_FMAP**2 / step_time, 1),
        "samples_per_sec": round(batch / step_time, 2),
        "step_time_ms": round(step_time * 1e3, 2),
        "hw_flops_utilization": round(float(hw_util), 4),
        "xla_vs_analytic_device_flops": round(xla_flops / device_analytic, 3),
        "batch": batch,
        "depth": depth,
        "device": jax.devices()[0].device_kind,
        "loss": round(float(loss), 4),
    }
    if abs(xla_flops / device_analytic - 1) > 0.10:
        print(
            f"WARNING: cost-analysis FLOPs diverge "
            f"{xla_flops / device_analytic:.2f}x from the device analytic",
            file=sys.stderr,
        )
    return result


def _time_steps(step, state, batch_data, n_warm: int, n_steps: int):
    """Warm (compile + settle) then time n_steps; float() is the
    device->host sync that ends each timed region."""
    for i in range(n_warm):
        state, loss = step(state, batch_data, jax.random.key(i))
    float(loss)
    t0 = time.perf_counter()
    for i in range(n_steps):
        state, loss = step(state, batch_data, jax.random.key(i))
    float(loss)
    return (time.perf_counter() - t0) / n_steps, float(loss)


def _scan_step_time(step, state, batch_data, k_small: int = 5, k_big: int = 25,
                    reps: int = 3):
    """Device-bound step time for SMALL steps: run k chained steps inside one
    jitted lax.scan and difference two iteration counts —
    (t(k_big) - t(k_small)) / (k_big - k_small) cancels the fixed per-call
    dispatch cost that would swamp a single-digit-ms step. The jitted step inlines under the scan,
    so the measured body is the exact compiled step. Every timed call reuses
    the SAME input state: feeding a call's output back in would change
    layouts and silently retrace. Calls ``step.jitted`` directly (no
    ambient-mesh wrapper), so it serves single-device steps only — an sp>1
    step would need the runtime mesh active at trace time."""

    def make(k):
        @jax.jit
        def k_steps(st, key):
            def body(c, i):
                c2, loss = step.jitted(
                    c, batch_data, jax.random.fold_in(key, i)
                )
                return c2, loss

            c, losses = jax.lax.scan(body, st, jnp.arange(k))
            return c, losses[-1]

        return k_steps

    f_small, f_big = make(k_small), make(k_big)
    float(f_small(state, jax.random.key(0))[1])  # compile + warm
    loss = float(f_big(state, jax.random.key(0))[1])

    def timed(fn):
        best = float("inf")
        for r in range(reps):
            t0 = time.perf_counter()
            _, l = fn(state, jax.random.key(r))
            float(l)
            best = min(best, time.perf_counter() - t0)
        return best

    t_small, t_big = timed(f_small), timed(f_big)
    dt = (t_big - t_small) / (k_big - k_small)
    if dt <= 0:
        # host-noise pathology (t_big <= t_small): fall back to the
        # conservative per-iteration bound rather than writing a zero or
        # negative step time into the benchmark record
        print(
            f"WARNING: non-positive differenced step time ({dt*1e3:.3f} ms); "
            f"falling back to t_big/k_big", file=sys.stderr,
        )
        dt = t_big / k_big
    return dt, loss


def bench_sparse_patterns(on_cpu: bool):
    """Per-pattern flagship train-step time PLUS the structural block-skip
    ledger — the reference's entire reason for conv/axial/block-sparse
    attention is COST reduction
    (/root/reference/dalle_pytorch/attention.py:90-384, README's sparse
    training runs), so each pattern must be measured against full attention,
    not just proven numerically equivalent.

    BENCH_r05 measured the sparse patterns at 0.97-0.99x full at seq 1280
    because masks.py fed dense masks to a dense kernel — the mask zeroed
    FLOPs it still paid for. The block-sparse Pallas kernel
    (ops/block_sparse_attention.py) skips dead (q, k) block pairs
    outright, so each timing record now carries its compiled layout's
    ``visited_block_frac`` — the FLOP ratio the pair-grid actually
    executes — and the seq sweep extends the ledger to 2048/4096 where
    skipping pays more. Two things are ASSERTED in-bench (structure is
    checkable on any host): every sparse layout visits strictly fewer
    block pairs than dense-causal, and the kernel (interpret mode — the
    same trace the TPU lowering uses, minus Mosaic) agrees with the
    shared-einsum reference at the flagship seq. Wall-clock kernel wins
    are TPU-pending: on CPU the kernel is gated off
    (DALLE_TPU_SPARSE_KERNEL auto = TPU only), so the timed steps below
    measure the dense-mask path."""
    from dalle_pytorch_tpu.ops import block_sparse_attention as bs
    from dalle_pytorch_tpu.ops.masks import causal_mask, pattern_mask

    batch = 2 if on_cpu else BATCH
    depth = 2 if on_cpu else DEPTH
    n_steps = 3 if on_cpu else 20
    # per-pattern mask kwargs + whether the pair grid is expected to
    # engage (ops/block_sparse_attention.ENGAGE_FRAC). axial_col's live
    # stride (fmap) is finer than the 128-block edge at every geometry
    # here, so every block pair stays live and the kernel DECLINES — that
    # is asserted too, because silently engaging on a frac-1.0 layout is
    # the overhead-for-nothing failure mode. "sparse" only block-skips
    # when its DeepSpeed-style layout block matches the MXU grid, so the
    # ledger measures it at block_size=128 (the long-context serving
    # configuration); the 16-block default peppers every 128-pair.
    patterns = ("axial_row", "axial_col", "conv_like", "sparse")
    cases = {
        "axial_row": ({}, True),
        "axial_col": ({}, False),
        "conv_like": ({}, True),
        "sparse": (dict(block_size=128), True),
    }

    # structural ledger: one compiled BlockLayout per (pattern, seq). The
    # sweep geometries keep text_len = n - fmap^2 so the total is exactly
    # the 128-divisible n the kernel's block grid wants; 2048/4096 are the
    # long-context shapes ROADMAP item 3 targets.
    sweep = ((1280, 32), (2048, 42), (4096, 62))
    layouts = {}
    for n, fmap in sweep:
        text_len = n - fmap * fmap
        dense_elems = float(causal_mask(n).sum())
        for pattern in patterns:
            kwargs, engages = cases[pattern]
            mask = pattern_mask(pattern, text_len, fmap, **kwargs)
            lay = bs.compile_block_layout(mask, 128, 128)
            # the bench IS the gate: an engaging layout that fails to
            # skip block pairs is exactly the BENCH_r05 regression this
            # kernel exists to fix
            if engages:
                assert lay.n_pairs < lay.dense_pairs, (
                    f"{pattern}@seq{n}: visited {lay.n_pairs} >= "
                    f"dense-causal {lay.dense_pairs} block pairs — block "
                    f"skipping is not engaging"
                )
            assert (lay.visited_block_frac <= bs.ENGAGE_FRAC) == engages, (
                f"{pattern}@seq{n}: frac {lay.visited_block_frac:.3f} "
                f"routes {'into' if not engages else 'away from'} the "
                f"pair grid — the engage expectation drifted"
            )
            layouts[(pattern, n)] = (lay, float(mask.sum()) / dense_elems)

    # kernel-vs-reference agreement, pinned at the flagship seq with small
    # b/h so the interpret sweep stays CPU-tier safe
    rng = np.random.RandomState(0)
    n_par = sweep[0][0]
    parity = {}
    for pattern in patterns:
        lay, _ = layouts[(pattern, n_par)]
        q, k, v = (
            jnp.asarray(rng.randn(1, 2, n_par, DIM_HEAD), jnp.float32)
            for _ in range(3)
        )
        out = bs.block_sparse_attention(
            q, k, v, lay, sm_scale=DIM_HEAD**-0.5, interpret=True
        )
        ref = bs.reference_attend(q, k, v, lay, sm_scale=DIM_HEAD**-0.5)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 2e-5, (
            f"{pattern}@seq{n_par}: block-sparse kernel diverges from the "
            f"shared-einsum reference (max err {err})"
        )
        parity[pattern] = err

    results = []
    _, state, step, batch_data = build(batch, depth)
    full_time, _ = _time_steps(step, state, batch_data, 3, n_steps)
    del state, step

    kernel_on = bs.sparse_kernel_enabled()
    for pattern in patterns:
        kwargs, engages = cases[pattern]
        _, state, step, batch_data = build(batch, depth, attn_types=(pattern,))
        step_time, loss = _time_steps(step, state, batch_data, 3, n_steps)
        del state, step
        lay, elem_frac = layouts[(pattern, n_par)]
        active = kernel_on and engages
        results.append({
            "metric": f"train_step_time_attn_{pattern}",
            "value": round(step_time * 1e3, 2),
            "unit": "ms",
            "vs_baseline": None,
            "full_attn_step_time_ms": round(full_time * 1e3, 2),
            "speedup_vs_full": round(full_time / step_time, 3),
            "visited_block_frac": round(lay.visited_block_frac, 4),
            "element_mask_density": round(elem_frac, 4),
            "kernel_reference_max_err": parity[pattern],
            "kernel_engages": engages,
            "mask_kwargs": kwargs or None,
            "sparse_kernel_active": bool(active),
            "wall_clock_note": None if active else (
                "pair grid declines on a frac-1.0 layout — dense-mask "
                "path measured" if not engages else
                "sparse kernel gated to TPU — timed steps measure the "
                "dense-mask path; the block-skip wall-clock win is "
                "TPU-pending (visited_block_frac is its measured FLOP "
                "ratio)"
            ),
            "batch": batch,
            "depth": depth,
            "device": jax.devices()[0].device_kind,
            "loss": round(loss, 4),
        })

    for n, fmap in sweep:
        for pattern in patterns:
            kwargs, engages = cases[pattern]
            lay, elem_frac = layouts[(pattern, n)]
            results.append({
                "metric": f"block_skip_visited_frac_{pattern}_seq{n}",
                "value": round(lay.visited_block_frac, 4),
                "unit": "fraction_of_dense_causal_block_pairs",
                "vs_baseline": None,
                "n_pairs": lay.n_pairs,
                "dense_pairs": lay.dense_pairs,
                "element_mask_density": round(elem_frac, 4),
                "block": lay.block_q,
                "kernel_engages": engages,
                "mask_kwargs": kwargs or None,
                "text_len": n - fmap * fmap,
                "image_fmap": fmap,
                "device": "structural",
            })
    return results


def _serving_model(on_cpu: bool, int8: bool):
    """The flagship serving model (reduced depth/fmap on CPU), initialized
    and pushed through ``prepare_for_serving`` — ONE definition for every
    decode bench section (latency, throughput, sweep, continuous batching)
    so they cannot drift onto different models. Returns
    (dalle, params, depth, fmap)."""
    from dalle_pytorch_tpu.models import DALLE
    from dalle_pytorch_tpu.utils.quantize import prepare_for_serving

    depth = 2 if on_cpu else DEPTH
    fmap = 8 if on_cpu else IMAGE_FMAP
    dalle = DALLE(
        dim=DIM, depth=depth, num_text_tokens=NUM_TEXT, text_seq_len=TEXT_SEQ,
        num_image_tokens=NUM_IMAGE, image_fmap_size=fmap,
        heads=HEADS, dim_head=DIM_HEAD, attn_types=("full",),
        dtype=jnp.bfloat16,
    )
    rng = np.random.RandomState(0)
    text1 = jnp.asarray(rng.randint(1, NUM_TEXT, size=(1, TEXT_SEQ)), jnp.int32)
    params = jax.jit(dalle.init)(
        jax.random.key(0), text1, jnp.zeros((1, fmap * fmap), jnp.int32)
    )["params"]
    dalle, params = prepare_for_serving(dalle, params, int8=int8)
    return dalle, params, depth, fmap


def bench_gen_throughput(on_cpu: bool, batch_sizes=(8, 32), int8: bool = True,
                         base_ms_per_token: float | None = None):
    """Batched serving throughput (tokens/sec): decode is weight-streaming
    bound at batch 1 (ops/attention.py cost notes), and weight reads amortize
    across the batch. The reference batches prompts the same way
    (generate.py:114-118) but re-forwards the full prefix per token; here it
    is the same prefill + lax.scan KV decode the latency bench uses, just
    batched.

    Why scaling plateaus (measured bound, v5e-1 int8): only the weight
    stream amortizes. The K/V cache sweeps scale linearly with batch —
    at batch 8 the frontier-sized sweeps are already ~0.5 ms/token of HBM
    traffic against the ~0.27 ms amortized weight stream — so tokens/sec
    approaches the sweep-bandwidth asymptote rather than batch-linear
    scaling. Frontier-sized caches (models/sampling.py) moved batch 8 from
    4,569 to ~5,000 tok/s; the residual gap to the HBM roofline is the
    half-filled-lane sweep inefficiency recorded in ops/attention.py."""
    from dalle_pytorch_tpu.models.sampling import generate_image_tokens

    if on_cpu:
        batch_sizes = (2,)
    dalle, params, _, fmap = _serving_model(on_cpu, int8)
    rng = np.random.RandomState(0)

    from dalle_pytorch_tpu.ops import kv_policy

    results = []
    # the batch-1 leg only exists to anchor scaling_vs_batch1 — reuse the
    # latency bench's p50 when the caller already measured it (the full
    # suite), re-measure only in selective --throughput mode. Explicit
    # None-test (not truthiness): a degenerate 0.0 anchor must surface as
    # a division error, never silently re-measure under a different
    # methodology.
    base_tps = (
        None if base_ms_per_token is None else 1e3 / base_ms_per_token
    )
    # provenance of the scaling anchor, carried in every record: the reused
    # anchor is bench_generation's 5-rep p50, the in-sweep one this loop's
    # 2-3-rep p50 — same model/config, different rep counts
    anchor = (
        "bench_generation_p50_5rep" if base_ms_per_token is not None
        else "in_sweep_p50_3rep"
    )
    batches = (
        tuple(batch_sizes) if base_tps is not None else (1,) + tuple(batch_sizes)
    )
    for b in batches:
        text = jnp.asarray(
            rng.randint(1, NUM_TEXT, size=(b, TEXT_SEQ)), jnp.int32
        )

        def gen(key):
            return generate_image_tokens(dalle, params, text, key)

        bc0 = backend_compiles()
        np.asarray(gen(jax.random.key(0)))  # compile
        bc1 = backend_compiles()
        times = []
        for i in range(2 if on_cpu else 3):
            t0 = time.perf_counter()
            np.asarray(gen(jax.random.key(i)))
            times.append(time.perf_counter() - t0)
        bc2 = backend_compiles()
        p50 = float(np.percentile(times, 50))
        tps = b * fmap * fmap / p50
        if b == 1:
            base_tps = tps
            continue  # batch-1 latency already reported by bench_generation
        results.append({
            "metric": f"gen_throughput_tokens_per_sec_batch{b}"
                      + ("_int8" if int8 else ""),
            "compiles_warm": bc1 - bc0,
            "compiles_timed": bc2 - bc1,
            "value": round(tps, 1),
            "unit": "tokens/sec",
            "vs_baseline": None,
            "scaling_vs_batch1": round(tps / base_tps, 2),
            "batch1_anchor": anchor,
            "batch": b,
            "cache_format": kv_policy.choose_cache_format(b),
            "tokens_per_image": int(fmap * fmap),
            "batch_latency_ms": round(p50 * 1e3, 1),
            "amortized_ms_per_image": round(p50 * 1e3 / b, 1),
            "device": jax.devices()[0].device_kind,
        })
    return results


def bench_vae_train(on_cpu: bool):
    """DiscreteVAE train-step perf at the reference's default train_vae
    config (/root/reference/train_vae.py:31-67: image 128, 8192 tokens,
    3 layers, 2 resnet blocks, emb 512, hidden 256, batch 8) in bf16 — the
    conv-dominated second hot loop. Utilization is achieved-TFLOP/s from XLA
    cost analysis, cross-checked against an independent parse of the
    compiled HLO (utils/hlo_breakdown.py)."""
    import optax as _optax

    from dalle_pytorch_tpu.models import DiscreteVAE
    from dalle_pytorch_tpu.parallel import (
        create_train_state, make_runtime, make_train_step,
    )
    from dalle_pytorch_tpu.utils.hlo_breakdown import parse_hlo_flops

    image_size = 32 if on_cpu else 128
    batch = 2 if on_cpu else 8
    vae = DiscreteVAE(
        image_size=image_size,
        num_tokens=8192,
        codebook_dim=512,
        num_layers=3,
        num_resnet_blocks=2,
        hidden_dim=256,
        kl_div_loss_weight=0.0,
        dtype=jnp.bfloat16,
    )
    rng = np.random.RandomState(0)
    images = jnp.asarray(
        rng.rand(batch, image_size, image_size, 3), jnp.float32
    )
    params = jax.jit(vae.init)(
        {"params": jax.random.key(0), "gumbel": jax.random.key(1)}, images
    )["params"]
    opt = _optax.adam(1e-3)
    runtime = make_runtime(devices=jax.devices()[:1])
    state, shardings = create_train_state(params, opt, runtime)

    def loss_fn(p, batch_d, rng_key):
        return vae.apply(
            {"params": p}, batch_d["images"], return_loss=True,
            temp=1.0, rngs={"gumbel": rng_key},
        )

    step = make_train_step(loss_fn, opt, runtime, shardings)
    batch_data = {"images": images}
    compiled = step.lower(state, batch_data, jax.random.key(0)).compile()
    xla_flops = compiled_flops(compiled, 0.0)
    hlo_groups = parse_hlo_flops(compiled.as_text())
    hlo_flops = sum(v["fwd"] + v["bwd"] for v in hlo_groups.values())

    if on_cpu:
        step_time, loss = _time_steps(step, state, batch_data, 1, 2)
    else:
        step_time, loss = _scan_step_time(step, state, batch_data)
    achieved = (xla_flops or hlo_flops) / step_time
    return {
        "metric": "train_vae_step_time_img128_l3_r2_batch8",
        "value": round(step_time * 1e3, 2),
        "unit": "ms",
        "vs_baseline": None,
        "achieved_tflops": round(achieved / 1e12, 1),
        "hw_flops_utilization": round(achieved / peak_flops(), 4),
        "samples_per_sec": round(batch / step_time, 1),
        "xla_vs_hlo_parse_flops": round(xla_flops / hlo_flops, 3)
        if hlo_flops else None,
        "batch": batch,
        "image_size": image_size,
        "device": jax.devices()[0].device_kind,
        "loss": round(loss, 4),
    }


def bench_clip_train(on_cpu: bool):
    """CLIP dual-encoder train-step perf at the model's default config
    (models/clip.py: dim 512, 6+6 layers, image 256 / patch 32, text 256)
    in bf16, batch 16 — the third trainer loop (train_clip.py; the reference
    README trains CLIP with the same contrastive loss)."""
    import optax as _optax

    from dalle_pytorch_tpu.models import CLIP
    from dalle_pytorch_tpu.parallel import (
        create_train_state, make_runtime, make_train_step,
    )
    from dalle_pytorch_tpu.utils.hlo_breakdown import parse_hlo_flops

    batch = 2 if on_cpu else 16
    image_size = 64 if on_cpu else 256
    depth = 2 if on_cpu else 6
    clip = CLIP(
        visual_image_size=image_size,
        text_enc_depth=depth,
        visual_enc_depth=depth,
        dtype=jnp.bfloat16,
    )
    rng = np.random.RandomState(0)
    batch_data = {
        "text": jnp.asarray(
            rng.randint(1, clip.num_text_tokens, size=(batch, clip.text_seq_len)),
            jnp.int32,
        ),
        "image": jnp.asarray(
            rng.rand(batch, image_size, image_size, 3), jnp.float32
        ),
    }
    params = jax.jit(clip.init)(
        jax.random.key(0), batch_data["text"], batch_data["image"]
    )["params"]
    opt = _optax.adam(1e-3)
    runtime = make_runtime(devices=jax.devices()[:1])
    state, shardings = create_train_state(params, opt, runtime)

    def loss_fn(p, b, rng_key):
        return clip.apply(
            {"params": p}, b["text"], b["image"],
            text_mask=b["text"] != 0, return_loss=True,
        )

    step = make_train_step(loss_fn, opt, runtime, shardings)
    compiled = step.lower(state, batch_data, jax.random.key(0)).compile()
    xla_flops = compiled_flops(compiled, 0.0)
    hlo_groups = parse_hlo_flops(compiled.as_text())
    hlo_flops = sum(v["fwd"] + v["bwd"] for v in hlo_groups.values())

    if on_cpu:
        step_time, loss = _time_steps(step, state, batch_data, 1, 2)
    else:
        step_time, loss = _scan_step_time(step, state, batch_data)
    achieved = (xla_flops or hlo_flops) / step_time
    return {
        "metric": "train_clip_step_time_dim512_d6x6_img256_batch16",
        "value": round(step_time * 1e3, 2),
        "unit": "ms",
        "vs_baseline": None,
        "achieved_tflops": round(achieved / 1e12, 1),
        "hw_flops_utilization": round(achieved / peak_flops(), 4),
        "samples_per_sec": round(batch / step_time, 1),
        "xla_vs_hlo_parse_flops": round(xla_flops / hlo_flops, 3)
        if hlo_flops else None,
        "batch": batch,
        "image_size": image_size,
        "device": jax.devices()[0].device_kind,
        "loss": round(loss, 4),
    }


def bench_generation(on_cpu: bool, int8: bool = False):
    """p50 single-chip autoregressive generation latency: scan-decode the
    full 1024 image tokens (BASELINE.md metric row 3). ``int8`` serves the
    same model through the weight-only-quantized path (utils/quantize.py)."""
    from dalle_pytorch_tpu.models.sampling import generate_image_tokens

    # bf16 (+ optional int8) serving: decode is HBM-bound on weight reads
    # (generate.py runs the same transform)
    dalle, params, _, fmap = _serving_model(on_cpu, int8)
    rng = np.random.RandomState(0)
    text = jnp.asarray(rng.randint(1, NUM_TEXT, size=(1, TEXT_SEQ)), jnp.int32)

    def gen(key):
        return generate_image_tokens(dalle, params, text, key)

    toks = gen(jax.random.key(0))  # compile
    np.asarray(toks)

    times = []
    for i in range(2 if on_cpu else 5):
        t0 = time.perf_counter()
        toks = gen(jax.random.key(i))
        np.asarray(toks)
        times.append(time.perf_counter() - t0)
    p50 = float(np.percentile(times, 50))
    name = "gen_latency_p50_image1024_tokens_1chip"
    return {
        "metric": name + ("_int8" if int8 else ""),
        "value": round(p50 * 1e3, 1),
        "unit": "ms",
        "vs_baseline": None,  # reference publishes no latency number
        "tokens_generated": int(fmap * fmap),
        "ms_per_token": round(p50 * 1e3 / (fmap * fmap), 3),
        "device": jax.devices()[0].device_kind,
    }


def bench_breakdown(on_cpu: bool):
    """--breakdown: per-module FLOPs table from the compiled HLO (the analog
    of the reference's DeepSpeed flops-profiler module table,
    /root/reference/train_dalle.py:473-480). Dots/convs are charged from
    their compiled shapes; the pallas attention custom-calls from the same
    analytic estimate their CostEstimates feed XLA."""
    from dalle_pytorch_tpu.utils.hlo_breakdown import format_table, parse_hlo_flops

    batch = 2 if on_cpu else BATCH
    depth = 2 if on_cpu else DEPTH
    dalle, state, step, batch_data = build(batch, depth)
    compiled = step.lower(state, batch_data, jax.random.key(0)).compile()

    n = TEXT_SEQ + IMAGE_FMAP**2
    # per-custom-call analytic FLOPs (fused packed-qkv kernel: fwd 2 block
    # dots + 3 rotary P-dots per head; bwd 5 + 6 — see device_flops_per_step)
    fwd_cc = batch * HEADS * (2 * 2 * n * n * DIM_HEAD + 3 * 2 * n * DIM_HEAD * DIM_HEAD)
    bwd_cc = batch * HEADS * (5 * 2 * n * n * DIM_HEAD + 6 * 2 * n * DIM_HEAD * DIM_HEAD)

    def cc_flops(line: str):
        # pallas kernels lose op_name metadata in compiled HLO; classify by
        # structure — the fused fwd returns (bf16 out, f32 lse), the
        # single-pass bwd returns the (dq, dk, dv) triple
        if 'custom_call_target="tpu_custom_call"' not in line:
            return None
        head = line.split("custom-call(", 1)[0]
        # count result tensors in the (possibly tuple) output shape: fwd
        # returns 2 (out, lse), bwd returns the 3-tuple (dq, dk, dv); dtype
        # substrings are unreliable in f32 runs
        kind = "bwd" if head.count("[") >= 3 else "fwd"
        return ("transformer/attn[pallas]", kind, fwd_cc if kind == "fwd" else bwd_cc)

    groups = parse_hlo_flops(compiled.as_text(), custom_call_flops=cc_flops)

    # measured step time for the proportional-time column
    for i in range(2):
        state, loss = step(state, batch_data, jax.random.key(i))
    float(loss)
    t0 = time.perf_counter()
    n_steps = 2 if on_cpu else 10
    for i in range(n_steps):
        state, loss = step(state, batch_data, jax.random.key(i))
    float(loss)
    step_time = (time.perf_counter() - t0) / n_steps

    print(format_table(groups, step_time_s=step_time, peak_flops=peak_flops()))


def run_flagship(on_cpu: bool):
    """--flagship: the flagship measurement session (ISSUE 19) — the full
    serve matrix (split + int8-KV + fused + speculative + prefix
    warm/cold + staged post-decode), the interference and recovery
    drills, the adaptive-control record, and the Pallas block-size
    sweep, every record provenance-stamped by _emit. Pipe stdout into a
    BENCH_rNN.json ``tail`` and ``tools/bench_trend.py --check`` gates
    the next session on the trend."""
    _emit(bench_serve(on_cpu))
    _emit(bench_serve_quant(on_cpu))
    _emit(bench_serve_fused(on_cpu))
    _emit(bench_serve_spec(on_cpu))
    _emit(bench_serve_prefix(on_cpu))
    _emit(bench_serve_stages(on_cpu))
    _emit(bench_serve_interference(on_cpu))
    _emit(bench_serve_recovery(on_cpu))
    _emit(bench_serve_control(on_cpu))
    for r in bench_pallas_block_sweep(on_cpu):
        _emit(r)


def main():
    on_cpu = jax.devices()[0].platform == "cpu"
    if "--breakdown" in sys.argv:
        bench_breakdown(on_cpu)
        return
    if "--flagship" in sys.argv:
        run_flagship(on_cpu)
        return
    # selective sections for iterating (--gen / --patterns / --throughput /
    # --sweep / --ragged / --vae / --clip); no flag = the full suite,
    # headline train-MFU line LAST
    only = {f for f in ("--gen", "--patterns", "--throughput", "--sweep",
                        "--ragged", "--serve", "--vae", "--clip") if f in sys.argv}
    if only:
        gen_int8 = None
        if "--gen" in only:
            _emit(bench_generation(on_cpu))
            gen_int8 = bench_generation(on_cpu, int8=True)
            _emit(gen_int8)
        if "--throughput" in only:
            base = gen_int8["ms_per_token"] if gen_int8 else None
            for r in bench_gen_throughput(on_cpu, base_ms_per_token=base):
                _emit(r)
        if "--sweep" in only:
            for r in bench_decode_sweep(on_cpu):
                _emit(r)
        if "--ragged" in only:
            _emit(bench_continuous_batching(on_cpu))
        if "--serve" in only:
            _emit(bench_serve(on_cpu))
            _emit(bench_serve_quant(on_cpu))
            _emit(bench_serve_fused(on_cpu))
            _emit(bench_serve_interference(on_cpu))
            _emit(bench_serve_stages(on_cpu))
            _emit(bench_serve_prefix(on_cpu))
            _emit(bench_serve_spec(on_cpu))
            _emit(bench_serve_recovery(on_cpu))
            _emit(bench_serve_control(on_cpu))
            if "--replicas" in sys.argv:
                n = int(sys.argv[sys.argv.index("--replicas") + 1])
                _emit(bench_serve_replicas(on_cpu, n_replicas=n))
        if "--patterns" in only:
            for r in bench_sparse_patterns(on_cpu):
                _emit(r)
        if "--vae" in only:
            _emit(bench_vae_train(on_cpu))
        if "--clip" in only:
            _emit(bench_clip_train(on_cpu))
        return
    # each section prints as soon as it is measured (a later section's
    # failure must not discard already-spent device time); the headline
    # train-MFU section runs and prints last
    _emit(bench_generation(on_cpu))
    gen_int8 = bench_generation(on_cpu, int8=True)
    _emit(gen_int8)
    for r in bench_gen_throughput(
        on_cpu, base_ms_per_token=gen_int8["ms_per_token"]
    ):
        _emit(r)
    # paged-only sweep in the full suite (the policy-default formats are
    # already covered by the latency/throughput sections above); the full
    # 3-format matrix runs under --sweep
    for r in bench_decode_sweep(on_cpu, formats=("paged",)):
        _emit(r)
    _emit(bench_continuous_batching(on_cpu))
    for r in bench_sparse_patterns(on_cpu):
        _emit(r)
    _emit(bench_vae_train(on_cpu))
    _emit(bench_clip_train(on_cpu))
    _emit(bench_train(on_cpu))


if __name__ == "__main__":
    main()
