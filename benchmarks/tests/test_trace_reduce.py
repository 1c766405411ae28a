"""The reduction from a trace to numbers: on a hand-made trace whose answers
are known, and on a small trace recorded on the chip (the first 0.1 s of a
traced slice of each cell, kept under ``benchmarks/tests/data``)."""

import json
import pathlib

import pytest

from benchmarks import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data"


def hand_made():
    ops = [
        ["fusion.1", 0.010, 0.020, "loop fusion"],
        ["fusion.2", 0.025, 0.010, "loop fusion"],        # overlaps fusion.1 by 5 ms
        ["while.3", 0.050, 0.030, "while"],               # a wrapper: its children follow
        ["convolution.4", 0.050, 0.020, "convolution"],
        ["custom-call.5", 0.070, 0.010, "custom-call"],
        ["fusion.6", 0.090, 0.000001, "loop fusion"],
        ["fusion.7", 0.090003, 0.000001, "loop fusion"],  # 2 us after fusion.6
    ]
    modules = [["jit_a(17)", 0.010, 0.025, ""], ["jit_b(18)", 0.050, 0.030, ""]]
    host = [
        ["bench.slice", 0.0, 0.100, ""],
        ["bench.step", 0.000, 0.045, ""],
        ["bench.submit", 0.036, 0.008, ""],               # inside bench.step
        ["bench.step", 0.046, 0.050, ""],
    ]
    return {"chips": [{"ops": ops, "modules": modules}], "host": host}


def test_busy_union_module_sums_and_gap_attribution():
    r = trace_reduce.reduce(hand_made())
    assert r["window_s"] == pytest.approx(0.100)
    # [10, 35] + [50, 80] + two 1 us operations
    assert r["busy_s"] == pytest.approx(0.025 + 0.030 + 2e-6)
    assert r["modules"]["jit_a"] == {"count": 1, "seconds": pytest.approx(0.025)}
    assert r["modules"]["jit_b"]["seconds"] == pytest.approx(0.030)
    cats = r["by_module_category"]
    assert cats["jit_a"]["loop fusion"] == pytest.approx(0.030)   # sums, not the union
    assert cats["jit_b"] == {"convolution": pytest.approx(0.020), "custom-call": pytest.approx(0.010)}
    assert "while" not in cats["jit_b"]
    assert trace_reduce.category_seconds(r, "jit_b", ("custom",)) == pytest.approx(0.010)
    gaps = dict(r["idle_gaps"])
    # 0-10 and 80-90 ms lie under bench.step alone; 35-50 has its middle
    # (42.5 ms) inside bench.submit, the innermost span there
    assert gaps["bench.step"] == pytest.approx(0.010 + 0.010 + (0.100 - 0.090004), abs=1e-6)
    assert gaps["bench.submit"] == pytest.approx(0.015)
    assert gaps["between_ops_of_one_program"] == pytest.approx(2e-6)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["device_ops"][0][0] == "fusion" and r["device_ops"][0][1] == pytest.approx(0.030 + 2e-6)


def test_a_trace_without_device_operations_reduces_to_nothing():
    assert trace_reduce.reduce({"chips": [], "host": []}) == {}
    assert trace_reduce.reduce({"chips": [{"ops": [], "modules": []}], "host": []}) == {}


def test_head_keeps_the_start_of_the_slice():
    sample = trace_reduce.head(hand_made(), 0.04)
    assert [e[0] for e in sample["chips"][0]["ops"]] == ["fusion.1", "fusion.2"]
    assert trace_reduce.reduce(sample)["window_s"] == pytest.approx(0.04)


@pytest.mark.parametrize("path", sorted(DATA.glob("trace-sample-*.json")), ids=lambda p: p.stem)
def test_recorded_chip_trace(path):
    assert path.stat().st_size < 1_000_000
    trace = json.loads(path.read_text())
    r = trace_reduce.reduce(trace)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(v for _, v in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9
    assert any(name.startswith("jit_") for name in r["modules"])
    per_module = sum(m["seconds"] for m in r["modules"].values())
    per_op = sum(v for cats in r["by_module_category"].values() for v in cats.values())
    # operations inside modules cannot outlast the modules by more than the
    # overlap of asynchronous copies
    assert per_op <= 1.2 * per_module + 1e-3
