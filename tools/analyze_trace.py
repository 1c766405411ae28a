#!/usr/bin/env python
"""Digest a jax.profiler trace into per-category / per-op device-time tables.

Capture a trace with ``train_dalle.py --profile_trace_dir DIR`` (or
jax.profiler directly), then

    python tools/analyze_trace.py DIR [--module NAME] [--top N]

reads the Chrome-format ``*.trace.json.gz`` the profiler writes (no
tensorboard needed), picks the longest-running XLA module (or the one
matching --module), and prints device time by HLO category and by
deduplicated op family — e.g. on the flagship train step this shows the
dense matmuls at ~86% of peak, the pallas attention custom-calls, and the
elementwise/optimizer tail (the numbers that motivated, and then bounded,
the round-4 kernel work).
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import sys


def load_trace(path: str) -> list:
    files = sorted(glob.glob(path + "/**/*.trace.json.gz", recursive=True))
    if not files:
        files = sorted(glob.glob(path)) if path.endswith(".gz") else []
    if not files:
        sys.exit(f"no *.trace.json.gz under {path}")
    with gzip.open(files[-1]) as f:
        return json.load(f)["traceEvents"]


def analyze(events: list, module: str | None, top: int) -> str:
    lanes = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            lanes[(e["pid"], e["tid"])] = e["args"].get("name", "")

    mods = [
        e for e in events
        if e.get("ph") == "X" and lanes.get((e.get("pid"), e.get("tid"))) == "XLA Modules"
        and (module is None or module in e.get("name", ""))
    ]
    if not mods:
        return "no XLA module executions in trace" + (
            f" matching {module!r}" if module else ""
        )
    target = max(mods, key=lambda m: m["dur"])
    t0, t1 = target["ts"], target["ts"] + target["dur"]

    cats: collections.Counter = collections.Counter()
    fams: collections.Counter = collections.Counter()
    for e in events:
        if e.get("ph") != "X" or lanes.get((e.get("pid"), e.get("tid"))) != "XLA Ops":
            continue
        # multi-device traces have one lane set per device (pid): only the
        # target module's own device may be charged, or N devices' ops
        # stack into one window and shares exceed 100%
        if e.get("pid") != target.get("pid"):
            continue
        if e["ts"] < t0 or e["ts"] >= t1:
            continue
        args = e.get("args", {})
        cat = args.get("hlo_category", "?")
        if cat == "while":
            continue  # wrapper op: its children are counted individually
        cats[cat] += e["dur"]
        fam = (args.get("deduplicated_name") or e["name"]).split(".")[0]
        fams[fam] += e["dur"]

    span = target["dur"] / 1e3
    lines = [f"module {target['name'][:70]}  span {span:.2f} ms", ""]
    lines.append(f"{'HLO category':<28}{'ms':>10}{'share':>8}")
    lines.append("-" * 46)
    for c, d in cats.most_common(top):
        lines.append(f"{c:<28}{d / 1e3:>10.2f}{d / 1e3 / span:>8.1%}")
    lines.append("")
    lines.append(f"{'op family (deduplicated)':<28}{'ms':>10}{'share':>8}")
    lines.append("-" * 46)
    for n, d in fams.most_common(top):
        lines.append(f"{n:<28}{d / 1e3:>10.2f}{d / 1e3 / span:>8.1%}")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir", help="profiler output dir (or a .trace.json.gz)")
    ap.add_argument("--module", default=None,
                    help="substring of the XLA module to analyze "
                         "(default: longest execution)")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()
    print(analyze(load_trace(args.trace_dir), args.module, args.top))


if __name__ == "__main__":
    main()
