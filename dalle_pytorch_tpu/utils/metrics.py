"""Metrics sink: console + optional Weights & Biases, root-rank-guarded.

Mirrors the reference's observability surface (SURVEY.md §5.5): per-step
loss/lr logs (train_dalle.py:589-599), throughput as ``sample_per_sec``
computed over 10-step windows (train_dalle.py:568-569,621-624), periodic
sample images, and run config capture — with wandb optional (gated import)
instead of required, and an MFU gauge the reference lacks.
"""

from __future__ import annotations

import bisect
import json
import math
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

# ------------------------------------------------------------------ labels
#
# Every registry below supports Prometheus-style labels: a series is
# (name, labels) — ``serve.pool_occupancy{replica="1"}`` — not a
# string-concatenated metric name. Callers either pass ``labels={...}``
# per call or bind them once with ``child(labels)``, which returns a view
# with the same mutating API (the serving engine binds ``replica=<id>``
# so one router run yields per-replica series without touching any call
# site). ``child(None)`` returns the registry itself, so the unlabeled
# path pays nothing.

LabelSet = Tuple[Tuple[str, str], ...]


def _labelset(labels: Optional[Dict[str, Any]]) -> LabelSet:
    """Canonical (sorted, stringified) form — the dict-key half of a
    series identity."""
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def render_series(name: str, labelset: LabelSet) -> str:
    """Human/snapshot rendering: ``name{k="v",...}`` (bare name when
    unlabeled) — matches the Prometheus exposition sample syntax."""
    if not labelset:
        return name
    inner = ",".join(f'{k}="{v}"' for k, v in labelset)
    return f"{name}{{{inner}}}"


class _ChildView:
    """A registry view with labels pre-bound. Forwards every call with the
    bound labels merged under any per-call labels (call-site wins on key
    collision). Children of children compose."""

    def __init__(self, base, labels: Dict[str, Any]):
        self._base = base
        self._labels = {str(k): str(v) for k, v in labels.items()}

    def _merge(self, labels: Optional[Dict[str, Any]]) -> Dict[str, str]:
        if not labels:
            return self._labels
        return {**self._labels, **{str(k): str(v) for k, v in labels.items()}}

    def child(self, labels: Optional[Dict[str, Any]] = None):
        if not labels:
            return self
        return _ChildView(self._base, self._merge(labels))

    # forwarded API (whichever of these the base registry has)
    def inc(self, name, n=1, labels=None):
        return self._base.inc(name, n, labels=self._merge(labels))

    def set(self, name, value, labels=None):
        return self._base.set(name, value, labels=self._merge(labels))

    def observe(self, name, value, labels=None, **kw):
        return self._base.observe(name, value, labels=self._merge(labels), **kw)

    def get(self, name, *a, labels=None, **kw):
        return self._base.get(name, *a, labels=self._merge(labels), **kw)


class Counters:
    """Process-wide named counters for fault accounting (docs/DESIGN.md §9).

    Data-path degradation (skipped samples, quarantined shards, download
    retries) must be COUNTED, not just warned about — a run that silently
    dropped 30% of its shards looks healthy in the loss curve. Producers
    (data/webdata.py, utils/download.py) ``inc`` from loader threads;
    the trainer snapshots into the step metrics. Thread-safe; the
    ``_GUARDED_BY`` table is the machine-checked contract (tools/lint.py
    DTL051, docs/DESIGN.md §11)."""

    _GUARDED_BY = {"_lock": ("_counts",)}

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[Tuple[str, LabelSet], int] = {}

    def inc(self, name: str, n: int = 1,
            labels: Optional[Dict[str, Any]] = None) -> int:
        key = (name, _labelset(labels))
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + n
            return self._counts[key]

    def get(self, name: str, labels: Optional[Dict[str, Any]] = None) -> int:
        with self._lock:
            return self._counts.get((name, _labelset(labels)), 0)

    def total(self, name: str) -> int:
        """Sum over every label variant of ``name`` (the unlabeled series
        included) — the fleet aggregate of a per-replica counter."""
        with self._lock:
            return sum(v for (n, _), v in self._counts.items() if n == name)

    def child(self, labels: Optional[Dict[str, Any]] = None):
        return self if not labels else _ChildView(self, labels)

    def series(self, prefix: str = "") -> List[Tuple[str, LabelSet, int]]:
        """(name, labelset, value) triples — the exposition-layer view."""
        with self._lock:
            return sorted(
                (n, ls, v) for (n, ls), v in self._counts.items()
                if n.startswith(prefix)
            )

    def snapshot(self, prefix: str = "") -> Dict[str, int]:
        return {
            render_series(n, ls): v for n, ls, v in self.series(prefix)
        }

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()


counters = Counters()


class Gauges:
    """Process-wide named gauges (last value wins) — the level companion to
    ``Counters``. The serving engine publishes pool occupancy and queue/
    running depths here each scheduling pass so an operator dashboard (or a
    test) reads the engine's current pressure without reaching into it.
    Thread-safe for the same reason Counters is."""

    _GUARDED_BY = {"_lock": ("_values",)}

    def __init__(self):
        self._lock = threading.Lock()
        self._values: Dict[Tuple[str, LabelSet], float] = {}

    def set(self, name: str, value: float,
            labels: Optional[Dict[str, Any]] = None) -> None:
        with self._lock:
            self._values[(name, _labelset(labels))] = float(value)

    def get(self, name: str, default: float = 0.0,
            labels: Optional[Dict[str, Any]] = None) -> float:
        with self._lock:
            return self._values.get((name, _labelset(labels)), default)

    def child(self, labels: Optional[Dict[str, Any]] = None):
        return self if not labels else _ChildView(self, labels)

    def series(self, prefix: str = "") -> List[Tuple[str, LabelSet, float]]:
        with self._lock:
            return sorted(
                (n, ls, v) for (n, ls), v in self._values.items()
                if n.startswith(prefix)
            )

    def snapshot(self, prefix: str = "") -> Dict[str, float]:
        return {
            render_series(n, ls): v for n, ls, v in self.series(prefix)
        }

    def reset(self) -> None:
        with self._lock:
            self._values.clear()


gauges = Gauges()


class Histogram:
    """Fixed log-spaced-bucket distribution metric — the percentile
    companion to ``Counters``/``Gauges`` (docs/DESIGN.md §9).

    Request latency, queue wait, step time, and data-wait are
    distributions, not levels: a mean hides the p99 that pages an
    operator. Buckets are log-spaced (``per_decade`` per factor of 10,
    spanning [lo, hi)) so one default geometry covers microsecond span
    overheads and hundred-second checkpoint saves with bounded relative
    error: a reported percentile is the upper bound of its value's
    bucket, so it is within one bucket factor (default 10^0.1 ~ 1.26x)
    of the true order statistic. count/sum/min/max are exact.

    Thread-safe; observation is a bisect + three adds (no allocation),
    cheap enough for the serving engine's per-iteration path.
    """

    _GUARDED_BY = {"_lock": ("_counts", "count", "sum", "min", "max")}

    def __init__(self, lo: float = 1e-6, hi: float = 1e3,
                 per_decade: int = 10):
        assert 0 < lo < hi and per_decade > 0
        n = int(math.ceil(per_decade * math.log10(hi / lo))) + 1
        # upper bucket bounds; values above bounds[-1] land in overflow
        self.bounds: List[float] = [
            lo * 10.0 ** (i / per_decade) for i in range(n)
        ]
        self._counts = [0] * (n + 1)  # +1: overflow (+Inf) bucket
        self._lock = threading.Lock()
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf

    def observe(self, value: float) -> None:
        v = float(value)
        i = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self._counts[i] += 1
            self.count += 1
            self.sum += v
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v

    def percentile(self, q: float) -> float:
        """Upper bound of the bucket holding the q-th percentile value
        (Prometheus ``histogram_quantile`` convention, conservative
        direction). Overflow-bucket hits report the exact observed max."""
        with self._lock:
            return self._percentile_locked(q)

    def _percentile_locked(self, q: float) -> float:
        if self.count == 0:
            return 0.0
        rank = max(1, math.ceil(q / 100.0 * self.count))
        seen = 0
        for i, c in enumerate(self._counts):
            seen += c
            if seen >= rank:
                if i >= len(self.bounds):  # overflow
                    return self.max
                return min(self.bounds[i], self.max)
        return self.max  # unreachable; counts sum to self.count

    def snapshot(self) -> Dict[str, float]:
        # one lock hold for the whole snapshot: the old unlocked reads
        # could interleave with a concurrent observe() and report a count
        # that disagrees with its own percentiles (surfaced by DTL051
        # once Histogram declared its _GUARDED_BY table)
        with self._lock:
            return {
                "count": self.count,
                "sum": self.sum,
                "min": 0.0 if self.count == 0 else self.min,
                "max": 0.0 if self.count == 0 else self.max,
                "p50": self._percentile_locked(50),
                "p95": self._percentile_locked(95),
                "p99": self._percentile_locked(99),
            }

    def buckets(self) -> List[Tuple[float, int]]:
        """(upper_bound, CUMULATIVE count) pairs up to the last nonzero
        bucket, plus the (+Inf, total) terminator — the Prometheus
        ``_bucket{le=...}`` exposition shape."""
        with self._lock:
            return self._buckets_locked()

    def _buckets_locked(self) -> List[Tuple[float, int]]:
        out: List[Tuple[float, int]] = []
        cum = 0
        last_nonzero = max(
            (i for i, c in enumerate(self._counts) if c), default=-1
        )
        for i, c in enumerate(self._counts[: len(self.bounds)]):
            cum += c
            if i <= last_nonzero:
                out.append((self.bounds[i], cum))
        out.append((math.inf, self.count))
        return out

    def exposition(self) -> Dict[str, Any]:
        """Atomic snapshot for the Prometheus renderer: buckets, sum,
        count, and quantiles from ONE lock hold — a concurrent observe()
        between separate reads would otherwise render a ``_count`` that
        disagrees with its own ``le="+Inf"`` bucket (Prometheus requires
        them equal within a scrape)."""
        with self._lock:
            return {
                "buckets": self._buckets_locked(),
                "sum": self.sum,
                "count": self.count,
                "quantiles": {
                    q: self._percentile_locked(q) for q in (50, 95, 99)
                },
            }

    def checkpoint(self) -> "HistogramCheckpoint":
        """Freeze the cumulative state for later ``snapshot_delta``.

        The Prometheus series stays monotone — windowing is the READER's
        subtraction, never a reset of the producer's counters (resetting
        would corrupt every other consumer's rate() over the same
        series). One lock hold, so the checkpoint is internally
        consistent with itself."""
        with self._lock:
            return HistogramCheckpoint(
                counts=tuple(self._counts), count=self.count, sum=self.sum,
                max=self.max,
            )

    def snapshot_delta(
        self, prev: Optional["HistogramCheckpoint"] = None
    ) -> Dict[str, float]:
        """Windowed stats since ``prev`` (a ``checkpoint()``): count, sum,
        mean, p50/p95/p99 computed over the bucket-count DIFFERENCES, so
        sliding-window percentiles never require resetting the cumulative
        series. ``prev=None`` — or a checkpoint from a different bucket
        geometry, or one newer than the current state (the registry was
        reset) — degrades to the full lifetime window.

        Window percentiles inherit the bucket resolution: each is the
        upper bound of its delta bucket (overflow hits report the
        lifetime max, the only max the buckets retain)."""
        with self._lock:
            dc = list(self._counts)
            count, total = self.count, self.sum
            if prev is not None and len(prev.counts) == len(dc):
                cand = [c - p for c, p in zip(dc, prev.counts)]
                if min(cand, default=0) >= 0 and self.count >= prev.count:
                    dc = cand
                    count = self.count - prev.count
                    total = self.sum - prev.sum
            out = {"count": float(count), "sum": total,
                   "mean": total / count if count else 0.0}
            for q in (50, 95, 99):
                out[f"p{q}"] = self._rank_walk_locked(dc, count, q)
            return out

    def _rank_walk_locked(self, dc: List[int], count: int, q: float) -> float:
        if count <= 0:
            return 0.0
        rank = max(1, math.ceil(q / 100.0 * count))
        seen = 0
        for i, c in enumerate(dc):
            seen += c
            if seen >= rank:
                if i >= len(self.bounds):  # overflow
                    return self.max
                return min(self.bounds[i], self.max)
        return self.max  # unreachable; dc sums to count


class HistogramCheckpoint:
    """Immutable cumulative-state marker for ``Histogram.snapshot_delta``
    — counts tuple + count/sum/max frozen under one lock hold."""

    __slots__ = ("counts", "count", "sum", "max")

    def __init__(self, counts: Tuple[int, ...], count: int, sum: float,
                 max: float):
        self.counts = counts
        self.count = count
        self.sum = sum
        self.max = max


class GaugeRing:
    """Fixed-capacity ring of gauge samples — the sliding-window
    companion to ``Gauges`` for level metrics (occupancy, queue depth,
    iteration gap) whose last value alone cannot answer "over the recent
    window". Push is O(1) and allocation-free after warmup; ``window()``
    reduces the live samples in one lock hold. Old samples fall off by
    capacity, so the window length is measured in pushes (the vitals
    layer pushes once per engine iteration)."""

    _GUARDED_BY = {"_lock": ("_buf", "_next", "_filled")}

    def __init__(self, capacity: int = 64):
        assert capacity >= 1, capacity
        self.capacity = capacity
        self._lock = threading.Lock()
        self._buf: List[float] = [0.0] * capacity
        self._next = 0
        self._filled = 0

    def push(self, value: float) -> None:
        v = float(value)
        with self._lock:
            self._buf[self._next] = v
            self._next = (self._next + 1) % self.capacity
            if self._filled < self.capacity:
                self._filled += 1

    def values(self) -> List[float]:
        """Live samples, oldest first."""
        with self._lock:
            if self._filled < self.capacity:
                return self._buf[: self._filled]
            return self._buf[self._next:] + self._buf[: self._next]

    def window(self) -> Dict[str, float]:
        """count/last/mean/min/max over the live samples (one lock
        hold); all-zero when nothing has been pushed yet."""
        with self._lock:
            n = self._filled
            if n == 0:
                return {"count": 0.0, "last": 0.0, "mean": 0.0,
                        "min": 0.0, "max": 0.0}
            if n < self.capacity:
                live = self._buf[:n]
            else:
                live = self._buf
            return {
                "count": float(n),
                "last": self._buf[(self._next - 1) % self.capacity],
                "mean": sum(live) / n,
                "min": min(live),
                "max": max(live),
            }


class Histograms:
    """Process-wide named histograms, created on first observe — same
    registry shape as ``Counters``/``Gauges`` so producers never
    pre-declare. The span API (utils/telemetry.py) feeds ``<span>_s``
    duration histograms here automatically."""

    _GUARDED_BY = {"_lock": ("_hists",)}

    def __init__(self):
        self._lock = threading.Lock()
        self._hists: Dict[Tuple[str, LabelSet], Histogram] = {}

    def observe(self, name: str, value: float,
                labels: Optional[Dict[str, Any]] = None, **hist_kw) -> None:
        key = (name, _labelset(labels))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = Histogram(**hist_kw)
        h.observe(value)

    def get(self, name: str,
            labels: Optional[Dict[str, Any]] = None) -> Optional[Histogram]:
        with self._lock:
            return self._hists.get((name, _labelset(labels)))

    def child(self, labels: Optional[Dict[str, Any]] = None):
        return self if not labels else _ChildView(self, labels)

    def series(self, prefix: str = "") -> List[Tuple[str, LabelSet, Histogram]]:
        with self._lock:
            return sorted(
                ((n, ls, h) for (n, ls), h in self._hists.items()
                 if n.startswith(prefix)),
                key=lambda t: (t[0], t[1]),
            )

    def snapshot(self, prefix: str = "") -> Dict[str, Dict[str, float]]:
        return {
            render_series(n, ls): h.snapshot() for n, ls, h in self.series(prefix)
        }

    def items(self) -> List[Tuple[str, Histogram]]:
        """Unlabeled-compatible view: (rendered name, Histogram) pairs."""
        return [(render_series(n, ls), h) for n, ls, h in self.series()]

    def reset(self) -> None:
        with self._lock:
            self._hists.clear()


histograms = Histograms()


class MetricsLogger:
    def __init__(
        self,
        project: Optional[str] = None,
        run_name: Optional[str] = None,
        config: Optional[dict] = None,
        enabled: bool = True,
        use_wandb: bool = False,
        log_file: Optional[str] = None,
        entity: Optional[str] = None,
    ):
        self.enabled = enabled
        self._wandb = None
        self._file = None
        if not enabled:
            return
        if use_wandb:
            try:
                import wandb

                self._wandb = wandb
                wandb.init(project=project or "dalle_tpu", name=run_name,
                           entity=entity, config=config)
            except ImportError:
                print("wandb not installed; falling back to console logs", file=sys.stderr)
        if log_file:
            self._file = open(log_file, "a")
        if config:
            self.log_text(f"config: {json.dumps(config, default=str)}")

    def log(self, metrics: dict, step: Optional[int] = None) -> None:
        if not self.enabled:
            return
        if self._wandb is not None:
            self._wandb.log(metrics, step=step)
        line = " ".join(
            f"{k}={v:.5g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in metrics.items()
        )
        prefix = f"step {step}: " if step is not None else ""
        print(prefix + line, flush=True)
        if self._file:
            self._file.write(json.dumps({"step": step, **metrics}, default=str) + "\n")
            self._file.flush()

    def log_text(self, text: str) -> None:
        if self.enabled:
            print(text, flush=True)

    def log_counters(self, step: Optional[int] = None, prefix: str = "") -> None:
        """Emit the named fault counters (nonzero only) as metrics."""
        snap = {k: v for k, v in counters.snapshot(prefix).items() if v}
        if snap:
            self.log(snap, step=step)

    def log_images(self, name: str, images, step: Optional[int] = None, captions=None):
        """images: (b, h, w, 3) float in [0,1]; saved to wandb when active."""
        if not self.enabled or self._wandb is None:
            return
        imgs = [
            self._wandb.Image(
                (im * 255).clip(0, 255).astype("uint8"),
                caption=None if captions is None else captions[i],
            )
            for i, im in enumerate(images)
        ]
        self._wandb.log({name: imgs}, step=step)

    def log_histogram(self, name: str, values, step: Optional[int] = None):
        """Full-distribution histogram (the reference's codebook-collapse
        monitor, train_vae.py:252-262 logs wandb.Histogram(codes)); console
        falls back to a compact quantile summary."""
        if not self.enabled:
            return
        import numpy as np

        flat = np.asarray(values).reshape(-1)
        if self._wandb is not None:
            self._wandb.log({name: self._wandb.Histogram(flat)}, step=step)
        qs = np.percentile(flat, [0, 25, 50, 75, 100])
        self.log_text(
            f"step {step}: {name} histogram n={flat.size} "
            f"min/q25/med/q75/max={'/'.join(f'{q:g}' for q in qs)} "
            f"unique={np.unique(flat).size}"
        )

    def log_artifact(
        self,
        name: str,
        path: str,
        type: str = "model",
        metadata: Optional[dict] = None,
    ):
        """Upload a file as a wandb artifact (the reference's per-epoch
        checkpoint upload, train_dalle.py:637-649 / train_vae.py:298-313),
        with the part files a large plain checkpoint keeps beside its
        index (utils/checkpoint.py); no-op without an active wandb run."""
        if not self.enabled or self._wandb is None:
            return
        artifact = self._wandb.Artifact(name, type=type, metadata=metadata or {})
        artifact.add_file(path)
        p = Path(path)
        for part in sorted(p.parent.glob(p.name + ".*.part[0-9]*")):
            artifact.add_file(str(part))
        self._wandb.run.log_artifact(artifact)

    def finish(self):
        if self._wandb is not None:
            self._wandb.finish()
        if self._file:
            self._file.close()


class Throughput:
    """sample_per_sec over an N-step window (train_dalle.py:621-624).

    The window test counts STEPS, not samples: the old
    ``total_samples % (samples * window)`` check silently never fired
    once per-step sample counts varied (last-batch remainder, ragged
    serving batches) — the running total stops being a multiple of the
    current step's ``samples * window`` and the rate is never emitted
    again. Samples are summed separately so the reported rate is exact
    for ragged windows too."""

    def __init__(self, window: int = 10):
        assert window > 0
        self.window = window
        self._t0 = time.perf_counter()
        self._steps = 0
        self._samples = 0

    def update(self, samples: int) -> Optional[float]:
        """Add one step's samples; returns samples/sec once per window."""
        self._steps += 1
        self._samples += samples
        if self._steps % self.window == 0:
            now = time.perf_counter()
            rate = self._samples / (now - self._t0)
            self._t0 = now
            self._samples = 0
            return rate
        return None


def mfu(flops_per_step: float, step_time_s: float, peak_flops: float) -> float:
    return flops_per_step / step_time_s / peak_flops
