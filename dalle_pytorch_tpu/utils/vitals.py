"""Engine vitals: sliding-window reductions over the existing metrics.

The cumulative Prometheus series (utils/metrics.py) answer "since boot";
an adaptive control loop needs "over the last few dozen iterations" —
the spec accept-rate RIGHT NOW, the decode-iteration gap RIGHT NOW. This
module computes those windows host-side, strictly as a READER of numbers
the engine already produces: the engine pushes one plain-number sample
set per iteration (``observe_iteration``), and ``publish`` reduces the
live windows into the ``serve.vitals.*`` gauges plus a snapshot dict the
controller (serving/control.py) consumes. Nothing here resets or mutates
the cumulative series — windowing is subtraction over ring samples
(metrics.GaugeRing) and checkpoint deltas (Histogram.snapshot_delta),
never a producer-side reset.

Host-only by lint contract (DTL021, tools/lint/config.py): no jax
anywhere in this module. It holds no device number: how close a kernel
or a step runs to its roofline is read from a profiler trace by the
benchmark (``benchmarks/``, PERF.md §3), against its one table of peaks.
"""

from __future__ import annotations

from typing import Dict, Optional

from .metrics import GaugeRing


def _window_delta(ring: GaugeRing) -> float:
    """last - first over a ring of CUMULATIVE samples — the windowed
    increment of a monotone counter series."""
    vals = ring.values()
    if len(vals) < 2:
        return 0.0
    return vals[-1] - vals[0]


class Vitals:
    """Sliding-window engine vitals, published as ``serve.vitals.*``.

    One ``observe_iteration`` per engine iteration (plain numbers only),
    one ``publish`` whenever the gauges should refresh. The window is
    measured in iterations (``window`` pushes per ring). Single-writer
    by design — the engine loop is the only producer — while the rings
    themselves are thread-safe for concurrent scrape-side readers.
    """

    def __init__(self, window: int = 32):
        assert window >= 2, window
        self.window = window
        # level series: windowed directly
        self._occupancy = GaugeRing(window)
        self._stage_lag = GaugeRing(window)
        self._gap = GaugeRing(window)
        # cumulative series: windowed as last-first ring deltas
        self._spec_drafted = GaugeRing(window)
        self._spec_accepted = GaugeRing(window)
        self._prefix_hits = GaugeRing(window)
        self._prefix_misses = GaugeRing(window)
        self._deadline_misses = GaugeRing(window)
        self._terminations = GaugeRing(window)
        self._last_now: Optional[float] = None
        self.iterations = 0

    def observe_iteration(
        self, *, now: float, occupancy: float, stage_queued: float,
        spec_drafted: float, spec_accepted: float,
        prefix_hits: float, prefix_misses: float,
        deadline_misses: float, terminations: float,
    ) -> None:
        """Push one iteration's sample set. All counter-style arguments
        are CUMULATIVE (lifetime) values; the vitals layer windows them."""
        if self._last_now is not None:
            self._gap.push(max(0.0, now - self._last_now))
        self._last_now = now
        self._occupancy.push(occupancy)
        self._stage_lag.push(stage_queued)
        self._spec_drafted.push(spec_drafted)
        self._spec_accepted.push(spec_accepted)
        self._prefix_hits.push(prefix_hits)
        self._prefix_misses.push(prefix_misses)
        self._deadline_misses.push(deadline_misses)
        self._terminations.push(terminations)
        self.iterations += 1

    def snapshot(self) -> Dict[str, float]:
        """The windowed vitals the controller consumes — plain floats,
        every key present every time (a deterministic controller must
        never branch on key existence)."""
        drafted = _window_delta(self._spec_drafted)
        accepted = _window_delta(self._spec_accepted)
        hits = _window_delta(self._prefix_hits)
        misses = _window_delta(self._prefix_misses)
        dl = _window_delta(self._deadline_misses)
        terms = _window_delta(self._terminations)
        return {
            "iterations": float(self.iterations),
            "spec_accept_rate": accepted / drafted if drafted > 0 else 0.0,
            "spec_drafted": drafted,
            "prefix_hit_frac": (
                hits / (hits + misses) if hits + misses > 0 else 0.0
            ),
            "decode_gap_s": self._gap.window()["max"],
            "stage_lag": self._stage_lag.window()["mean"],
            "deadline_miss_rate": dl / terms if terms > 0 else 0.0,
            "occupancy": self._occupancy.window()["mean"],
        }

    def publish(self, gauges) -> Dict[str, float]:
        """Reduce the live windows into the ``serve.vitals.*`` gauges
        (``gauges``: the engine's label-bound registry view) and return
        the same snapshot dict for the controller."""
        snap = self.snapshot()
        gauges.set("serve.vitals.spec_accept_rate", snap["spec_accept_rate"])
        gauges.set("serve.vitals.prefix_hit_frac", snap["prefix_hit_frac"])
        gauges.set("serve.vitals.decode_gap_s", snap["decode_gap_s"])
        gauges.set("serve.vitals.stage_lag", snap["stage_lag"])
        gauges.set(
            "serve.vitals.deadline_miss_rate", snap["deadline_miss_rate"]
        )
        gauges.set("serve.vitals.occupancy", snap["occupancy"])
        return snap
