"""Engine vitals layer (utils/vitals.py) and its metrics substrate
(ISSUE 19): windowed histogram deltas that never reset the cumulative
Prometheus series, the gauge ring's sliding reductions, and the Vitals
windows the controller consumes — all pure host arithmetic, no engine
required."""

import math

import pytest

from dalle_pytorch_tpu.utils.metrics import (
    GaugeRing,
    Histogram,
    HistogramCheckpoint,
    gauges,
)
from dalle_pytorch_tpu.utils.vitals import Vitals


# ------------------------------------------------------------ GaugeRing


class TestGaugeRing:
    def test_empty_window_is_zero(self):
        r = GaugeRing(4)
        assert r.values() == []
        assert r.window() == {
            "count": 0.0, "last": 0.0, "mean": 0.0, "min": 0.0, "max": 0.0,
        }

    def test_partial_fill(self):
        r = GaugeRing(4)
        r.push(1.0)
        r.push(3.0)
        assert r.values() == [1.0, 3.0]
        w = r.window()
        assert w["count"] == 2.0 and w["last"] == 3.0
        assert w["mean"] == 2.0 and w["min"] == 1.0 and w["max"] == 3.0

    def test_wraparound_drops_oldest(self):
        r = GaugeRing(3)
        for v in (1.0, 2.0, 3.0, 4.0, 5.0):
            r.push(v)
        assert r.values() == [3.0, 4.0, 5.0]
        w = r.window()
        assert w["min"] == 3.0 and w["max"] == 5.0 and w["last"] == 5.0

    def test_capacity_one(self):
        r = GaugeRing(1)
        r.push(7.0)
        r.push(9.0)
        assert r.values() == [9.0]
        assert r.window()["mean"] == 9.0


# -------------------------------------------- Histogram.snapshot_delta


class TestSnapshotDelta:
    def test_window_excludes_pre_checkpoint(self):
        h = Histogram()
        for v in (0.001, 0.002, 0.003):
            h.observe(v)
        ck = h.checkpoint()
        h.observe(10.0)
        h.observe(20.0)
        d = h.snapshot_delta(ck)
        assert d["count"] == 2.0
        assert d["sum"] == pytest.approx(30.0)
        assert d["mean"] == pytest.approx(15.0)
        # window p50 lands in the 10s decade, far from the 1ms samples
        assert d["p50"] > 1.0
        # cumulative series untouched
        assert h.count == 5 and h.snapshot()["count"] == 5

    def test_none_checkpoint_is_lifetime(self):
        h = Histogram()
        h.observe(1.0)
        h.observe(2.0)
        d = h.snapshot_delta(None)
        assert d["count"] == 2.0 and d["sum"] == pytest.approx(3.0)

    def test_empty_window(self):
        h = Histogram()
        h.observe(1.0)
        ck = h.checkpoint()
        d = h.snapshot_delta(ck)
        assert d["count"] == 0.0 and d["sum"] == pytest.approx(0.0)
        assert d["p50"] == 0.0 and d["p99"] == 0.0

    def test_geometry_mismatch_degrades_to_lifetime(self):
        h = Histogram()
        h.observe(1.0)
        alien = HistogramCheckpoint(counts=(0, 0), count=0, sum=0.0,
                                    max=-math.inf)
        d = h.snapshot_delta(alien)
        assert d["count"] == 1.0

    def test_stale_checkpoint_after_reset_degrades(self):
        # a checkpoint NEWER than the current state (someone rebuilt the
        # histogram) must not produce negative windows
        h = Histogram()
        for _ in range(5):
            h.observe(1.0)
        ck = h.checkpoint()
        h2 = Histogram()
        h2.observe(2.0)
        d = h2.snapshot_delta(ck)
        assert d["count"] == 1.0 and d["sum"] == pytest.approx(2.0)

    def test_window_percentiles_track_window_not_lifetime(self):
        h = Histogram()
        for _ in range(100):
            h.observe(0.001)
        ck = h.checkpoint()
        for _ in range(10):
            h.observe(100.0)
        # lifetime p50 still sits at the 1ms mass; the window's is 100s
        assert h.percentile(50) < 0.01
        d = h.snapshot_delta(ck)
        assert d["p50"] > 50.0

    def test_checkpoint_charges_nothing_to_cumulative(self):
        h = Histogram()
        h.observe(1.0)
        before = h.snapshot()
        h.checkpoint()
        h.snapshot_delta(h.checkpoint())
        assert h.snapshot() == before


# --------------------------------------------------------------- Vitals


def feed(v, n, *, dt=1.0, drafted=0, accepted=0, hits=0, misses=0,
         dl=0, terms=0, occ=0.5, stage=0.0, t0=0.0):
    """Push n iterations of CUMULATIVE samples growing linearly."""
    for i in range(1, n + 1):
        v.observe_iteration(
            now=t0 + i * dt, occupancy=occ, stage_queued=stage,
            spec_drafted=drafted * i, spec_accepted=accepted * i,
            prefix_hits=hits * i, prefix_misses=misses * i,
            deadline_misses=dl * i, terminations=terms * i,
        )


class TestVitals:
    def test_windowed_accept_rate(self):
        v = Vitals(window=8)
        feed(v, 20, drafted=4, accepted=3)
        snap = v.snapshot()
        assert snap["spec_accept_rate"] == pytest.approx(0.75)
        assert snap["spec_drafted"] == pytest.approx(4 * 7)  # window deltas
        assert snap["iterations"] == 20.0

    def test_rate_is_windowed_not_lifetime(self):
        # 10 iterations at accept 1.0, then 10 at accept 0 — the window
        # must read ~0 while the lifetime frac would read ~0.5
        v = Vitals(window=4)
        for i in range(1, 11):
            v.observe_iteration(
                now=float(i), occupancy=0.5, stage_queued=0,
                spec_drafted=4 * i, spec_accepted=4 * i,
                prefix_hits=0, prefix_misses=0,
                deadline_misses=0, terminations=0,
            )
        for i in range(11, 21):
            v.observe_iteration(
                now=float(i), occupancy=0.5, stage_queued=0,
                spec_drafted=4 * i, spec_accepted=40,
                prefix_hits=0, prefix_misses=0,
                deadline_misses=0, terminations=0,
            )
        assert v.snapshot()["spec_accept_rate"] == pytest.approx(0.0)

    def test_gap_and_miss_rate(self):
        v = Vitals(window=8)
        feed(v, 10, dt=0.25, dl=1, terms=4)
        snap = v.snapshot()
        assert snap["decode_gap_s"] == pytest.approx(0.25)
        assert snap["deadline_miss_rate"] == pytest.approx(0.25)
        assert snap["occupancy"] == pytest.approx(0.5)

    def test_zero_denominators(self):
        v = Vitals(window=4)
        feed(v, 2)
        snap = v.snapshot()
        assert snap["spec_accept_rate"] == 0.0
        assert snap["prefix_hit_frac"] == 0.0
        assert snap["deadline_miss_rate"] == 0.0

    def test_publish_sets_registered_gauges(self):
        v = Vitals(window=4)
        feed(v, 6, drafted=4, accepted=2, hits=1, misses=1)
        snap = v.publish(gauges)
        assert gauges.get("serve.vitals.spec_accept_rate") == pytest.approx(
            snap["spec_accept_rate"]
        )
        assert gauges.get("serve.vitals.prefix_hit_frac") == pytest.approx(0.5)
        assert gauges.get("serve.vitals.decode_gap_s") == pytest.approx(1.0)
        assert gauges.get("serve.vitals.occupancy") == pytest.approx(0.5)
        assert gauges.get("serve.vitals.deadline_miss_rate") == 0.0
        assert gauges.get("serve.vitals.stage_lag") == 0.0

    def test_snapshot_keys_are_stable(self):
        # a deterministic controller must never branch on key existence
        v = Vitals(window=4)
        keys0 = set(v.snapshot())
        feed(v, 10, drafted=4, accepted=4)
        assert set(v.snapshot()) == keys0
