"""Bench trend gate (tools/bench_trend.py, ISSUE 19): a ``BENCH_r*``-style
history parses into per-metric series, the gate exits 0 on a flat
history, and the SEEDED regression fixture
(tests/fixtures_bench/regression_new.jsonl) proves the red path — a
regressed latency folded in as the newest point exits nonzero. The
history is SYNTHETIC (tests/fixtures_bench/history_r0*.jsonl, passed via
the tool's --root/--history-glob): the repo's own BENCH_r*.json records
are frozen, pre-ledger claims and no longer a gate's input. Pure stdlib +
subprocess; no jax."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
TOOL = REPO / "tools" / "bench_trend.py"
FIXTURES = REPO / "tests" / "fixtures_bench"
FIXTURE = FIXTURES / "regression_new.jsonl"
HISTORY = ("--root", str(FIXTURES), "--history-glob", "history_r*.jsonl")

sys.path.insert(0, str(REPO / "tools"))
import bench_trend  # noqa: E402


# ------------------------------------------------------------ unit layer


class TestParsing:
    def test_parse_records_skips_non_metric_lines(self):
        text = "\n".join([
            "not json",
            json.dumps({"assert": "zero compiles"}),
            json.dumps({"metric": "m", "value": "not-a-number"}),
            json.dumps({"metric": "m", "value": 1.5, "unit": "ms"}),
        ])
        recs = bench_trend.parse_records(text)
        assert recs == [{"metric": "m", "value": 1.5, "unit": "ms"}]

    def test_load_history_file_reads_tail_shape(self, tmp_path):
        p = tmp_path / "BENCH_r99.json"
        tail = json.dumps({"metric": "m", "value": 2.0}) + "\n"
        p.write_text(json.dumps({"n": 99, "rc": 0, "tail": tail}))
        assert bench_trend.load_history_file(str(p)) == [
            {"metric": "m", "value": 2.0}
        ]

    def test_load_history_file_reads_raw_jsonl(self, tmp_path):
        p = tmp_path / "new.jsonl"
        p.write_text(json.dumps({"metric": "m", "value": 3.0}) + "\n")
        assert bench_trend.load_history_file(str(p)) == [
            {"metric": "m", "value": 3.0}
        ]

    def test_repeated_metric_within_file_keeps_last(self, tmp_path):
        p = tmp_path / "a.jsonl"
        p.write_text(
            json.dumps({"metric": "m", "value": 1.0}) + "\n"
            + json.dumps({"metric": "m", "value": 2.0}) + "\n"
        )
        series = bench_trend.collect_series([str(p)])
        assert series["m"] == [("a.jsonl", 2.0, None)]


class TestDirection:
    @pytest.mark.parametrize("metric,unit,want", [
        ("serve_ttft_p95_cold", None, "lower"),
        ("gen_latency_p50_image1024_tokens_1chip", "ms", "lower"),
        ("train_mfu_dalle_depth12", None, "higher"),
        ("serve_decode_tokens_per_sec", None, "higher"),
        ("serve_spec_accept_per_step", None, "higher"),
        ("jit_recompiles_in_trace", None, "lower"),
        ("mystery_number", None, None),
        ("mystery_number", "s", "lower"),
    ])
    def test_direction(self, metric, unit, want):
        assert bench_trend.direction(metric, unit) == want


class TestEvaluate:
    def _series(self, values, metric="x_latency_ms"):
        return {metric: [(f"r{i}", v, "ms") for i, v in enumerate(values)]}

    def test_ok_within_tolerance(self):
        rows = bench_trend.evaluate(self._series([10.0, 10.0, 11.0]), 0.5)
        assert rows[0]["status"] == "ok"
        assert rows[0]["baseline"] == 10.0

    def test_regression_past_tolerance(self):
        rows = bench_trend.evaluate(self._series([10.0, 10.0, 16.0]), 0.5)
        assert rows[0]["status"] == "regressed"

    def test_median_baseline_resists_outlier(self):
        # a single historical spike must not raise the baseline enough
        # to mask a real regression
        rows = bench_trend.evaluate(
            self._series([10.0, 10.0, 100.0, 16.0]), 0.5
        )
        assert rows[0]["baseline"] == 10.0
        assert rows[0]["status"] == "regressed"

    def test_higher_is_better_direction(self):
        series = {"x_mfu": [("r0", 0.5, None), ("r1", 0.2, None)]}
        rows = bench_trend.evaluate(series, 0.25)
        assert rows[0]["status"] == "regressed"
        series = {"x_mfu": [("r0", 0.5, None), ("r1", 0.45, None)]}
        assert bench_trend.evaluate(series, 0.25)[0]["status"] == "ok"

    def test_single_point_and_unknown_direction_ungated(self):
        rows = bench_trend.evaluate(self._series([10.0]), 0.5)
        assert rows[0]["status"] == "ungated"
        rows = bench_trend.evaluate(
            {"mystery": [("r0", 1.0, None), ("r1", 99.0, None)]}, 0.5
        )
        assert rows[0]["status"] == "ungated"


# ------------------------------------------------- gate (CLI) layer


def run_tool(*args):
    return subprocess.run(
        [sys.executable, str(TOOL), *args],
        capture_output=True, text=True, cwd=str(REPO),
    )


class TestGate:
    def test_check_exits_zero_on_flat_history(self):
        proc = run_tool(*HISTORY, "--check")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        summary = json.loads(proc.stdout.strip().splitlines()[-1])
        assert summary["regressed"] == 0
        assert summary["gated"] >= 1  # the gate is not vacuous

    def test_seeded_regression_fixture_fails_red(self):
        proc = run_tool(*HISTORY, "--new", str(FIXTURE), "--check")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "REGRESSION gen_latency_p50" in proc.stderr
        rows = [
            json.loads(l) for l in proc.stdout.strip().splitlines()
        ]
        regressed = [
            r for r in rows if r.get("status") == "regressed"
        ]
        assert len(regressed) == 1
        assert regressed[0]["latest_source"] == "regression_new.jsonl"

    def test_without_check_regression_still_exits_zero(self):
        # report-only mode never gates: the pre-flight opts in with
        # --check
        proc = run_tool(*HISTORY, "--new", str(FIXTURE))
        assert proc.returncode == 0
