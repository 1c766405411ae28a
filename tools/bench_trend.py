#!/usr/bin/env python
"""Bench trend gate: per-metric regression checks over the committed
``BENCH_r*.json`` history (ISSUE 19).

Every flagship measurement session appends to a committed history —
``BENCH_rNN.json`` files whose ``tail`` field holds the run's JSONL
records (one ``{"metric": ..., "value": ..., "unit": ...}`` object per
line). This tool turns that history into a NUMBER a PR can be gated on,
instead of a vibe:

- default: print the per-metric trend table (baseline, latest, delta,
  verdict) as JSON lines;
- ``--new FILE``: fold a fresh run's records (raw JSONL, or a
  BENCH_r-style JSON with a ``tail``) in as the latest point;
- ``--check``: exit nonzero iff any gated metric REGRESSED past its
  tolerance. (No longer a stage of the serve_smoke/chaos_soak lint
  pre-flight: the committed history is frozen — BENCH_r05.json is its
  last point — and a gate over records that cannot grow guards nothing;
  ROADMAP S0 points this tool at the ledger.)

Direction is inferred per metric (latency/time/bytes/gap fall, MFU/
throughput/accept/hit rates rise); metrics whose direction is unknown
are reported but never gated. The baseline is the MEDIAN of the prior
points — a single historical outlier can neither mask nor fake a
regression. Pure stdlib, no jax: runs anywhere the repo checks out.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
from typing import Dict, List, Optional, Tuple

HISTORY_GLOB = "BENCH_r*.json"

# fractional tolerance before a delta counts as a regression; per-metric
# overrides first, the default for everything else. CPU-tier timings are
# noisy — the gate catches step changes, not jitter.
DEFAULT_TOLERANCE = 0.5
TOLERANCES: Dict[str, float] = {
    # MFU is a stable ratio: hold it tighter than wall-clock timings
    "train_mfu_dalle_depth12_dim1024_seq1280_1chip": 0.25,
}

# direction markers, matched against the metric name (and the unit as a
# fallback): the FIRST match wins, so put the more specific ones first
_LOWER_MARKERS = (
    "latency", "step_time", "_time", "gap", "_s_", "wait", "ttft",
    "bytes", "compiles", "recompiles", "mttr", "recovery",
)
_HIGHER_MARKERS = (
    "mfu", "per_sec", "per_s", "throughput", "tokens_sec", "accept",
    "hit_frac", "hit_rate", "images_per", "frac_of_roofline", "speedup",
)


def direction(metric: str, unit: Optional[str] = None) -> Optional[str]:
    """'lower' / 'higher' = which way is better; None = ungated."""
    name = metric.lower()
    for m in _LOWER_MARKERS:
        if m in name:
            return "lower"
    for m in _HIGHER_MARKERS:
        if m in name:
            return "higher"
    if unit in ("s", "ms", "us"):
        return "lower"
    return None


def parse_records(text: str) -> List[dict]:
    """Metric records from JSONL text: objects with a string ``metric``
    and a numeric ``value``; everything else is skipped (bench output
    interleaves assertions and notes with the records)."""
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if (
            isinstance(obj, dict)
            and isinstance(obj.get("metric"), str)
            and isinstance(obj.get("value"), (int, float))
        ):
            out.append(obj)
    return out


def load_history_file(path: str) -> List[dict]:
    """Records from one history point — a BENCH_r-style JSON whose
    ``tail`` holds the JSONL, or a raw JSONL file."""
    with open(path) as f:
        text = f.read()
    try:
        obj = json.loads(text)
    except ValueError:
        obj = None
    if isinstance(obj, dict) and "tail" in obj:
        return parse_records(obj.get("tail") or "")
    return parse_records(text)


def collect_series(
    history_paths: List[str], new_path: Optional[str] = None
) -> Dict[str, List[Tuple[str, float, Optional[str]]]]:
    """metric -> ordered [(source, value, unit)] across history (path
    order = chronological; the glob sorts rNN lexically) plus the
    optional new point last. A metric repeated within one file keeps its
    last value (reruns within a session supersede)."""
    series: Dict[str, List[Tuple[str, float, Optional[str]]]] = {}
    for path in list(history_paths) + ([new_path] if new_path else []):
        per_file: Dict[str, Tuple[float, Optional[str]]] = {}
        for rec in load_history_file(path):
            per_file[rec["metric"]] = (
                float(rec["value"]), rec.get("unit")
            )
        name = os.path.basename(path)
        for metric, (value, unit) in sorted(per_file.items()):
            series.setdefault(metric, []).append((name, value, unit))
    return series


def evaluate(
    series: Dict[str, List[Tuple[str, float, Optional[str]]]],
    tolerance: float = DEFAULT_TOLERANCE,
) -> List[dict]:
    """One verdict row per metric. Gated metrics with >=2 points compare
    the LATEST value against the median of the prior points; single-point
    or direction-unknown metrics report ``ungated``."""
    rows = []
    for metric in sorted(series):
        points = series[metric]
        unit = points[-1][2]
        d = direction(metric, unit)
        latest_src, latest, _ = points[-1]
        row = {
            "metric": metric,
            "n_points": len(points),
            "latest": latest,
            "latest_source": latest_src,
            "unit": unit,
            "direction": d,
        }
        if d is None or len(points) < 2:
            row["status"] = "ungated"
            rows.append(row)
            continue
        baseline = statistics.median(v for _, v, _ in points[:-1])
        tol = TOLERANCES.get(metric, tolerance)
        row["baseline"] = baseline
        row["tolerance"] = tol
        if baseline == 0:
            row["status"] = "ungated"
            rows.append(row)
            continue
        delta = (latest - baseline) / abs(baseline)
        row["delta_frac"] = delta
        regressed = (
            delta > tol if d == "lower" else delta < -tol
        )
        row["status"] = "regressed" if regressed else "ok"
        rows.append(row)
    return rows


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--history-glob", default=HISTORY_GLOB,
        help="committed history files, sorted = chronological",
    )
    ap.add_argument(
        "--root", default=None,
        help="directory the history glob is relative to (default: the "
             "repo root this tool lives in)",
    )
    ap.add_argument(
        "--new", default=None, metavar="FILE",
        help="fold a fresh run's records (JSONL or BENCH_r-style JSON) "
             "in as the latest point",
    )
    ap.add_argument(
        "--tolerance", type=float, default=DEFAULT_TOLERANCE,
        help="default fractional regression tolerance",
    )
    ap.add_argument(
        "--check", action="store_true",
        help="exit nonzero iff any gated metric regressed",
    )
    args = ap.parse_args(argv)

    root = args.root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    paths = sorted(glob.glob(os.path.join(root, args.history_glob)))
    if not paths and not args.new:
        print(json.dumps({"error": "no history matched", "root": root}))
        return 2

    series = collect_series(paths, args.new)
    rows = evaluate(series, args.tolerance)
    for row in rows:
        print(json.dumps(row))
    regressed = [r for r in rows if r["status"] == "regressed"]
    summary = {
        "summary": "bench_trend",
        "history_points": len(paths) + (1 if args.new else 0),
        "metrics": len(rows),
        "gated": sum(r["status"] != "ungated" for r in rows),
        "regressed": len(regressed),
    }
    print(json.dumps(summary))
    if args.check and regressed:
        for r in regressed:
            print(
                f"REGRESSION {r['metric']}: latest {r['latest']:.6g} vs "
                f"baseline {r['baseline']:.6g} "
                f"(delta {r['delta_frac']:+.1%}, tol "
                f"{r['tolerance']:.0%}, {r['direction']} is better)",
                file=sys.stderr,
            )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
