"""Every entry of BENCHMARK.json resolves its files, and every per-layer
metric names cells that report the end-to-end metric it moves."""

import importlib
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_workload_resolves_its_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        mix = json.loads((ROOT / "benchmarks" / "traffic" / f"{w['traffic']}.json").read_text())
        driver = importlib.import_module(f"benchmarks.drivers.{mix['driver']}")
        assert callable(driver.run)
        limits = json.loads((ROOT / "benchmarks" / "limits" / f"{w['name']}.json").read_text())
        assert limits["limits"]
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert {c["name"] for c in BENCH["configs"]} == {w["config"] for w in BENCH["workloads"]}


def test_every_metric_has_its_reader_and_moves_what_its_cells_report():
    cells = {w["name"] for w in BENCH["workloads"]}
    reported = {
        e["name"]: set(e.get("workloads", cells)) for e in BENCH["end_to_end"]
    }
    assert reported["setup_s"] == cells
    for cell in cells:
        assert sum(cell in v for k, v in reported.items() if k != "setup_s") >= 1
    covered = set()
    for m in BENCH["per_layer"]:
        spec = json.loads((ROOT / "benchmarks" / "metrics" / f"{m['name']}.json").read_text())
        reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
        assert callable(reader.read)
        assert {k: spec[k] for k in ("layer", "unit", "moves", "source")} == {
            k: m[k] for k in ("layer", "unit", "moves", "source")
        }
        for cell in m["workloads"]:
            assert cell in reported[m["moves"]], (m["name"], cell)
            covered.add(cell)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert covered == cells
