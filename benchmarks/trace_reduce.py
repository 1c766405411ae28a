"""From a profiler trace to numbers: the device's busy time, each compiled
module's time, device time by module and HLO category, the operations that
took most time, and every idle gap charged to the host span that covers it.

Two steps, so the second can be checked on a small recorded trace:
``load_xplane`` turns the profiler's ``.xplane.pb`` into plain lists (only
``jax.profiler.ProfileData`` is needed), ``reduce`` does the arithmetic on
those lists and touches no jax.

Device events come from the ``XLA Ops`` and ``XLA Modules`` lines of the
``/device:TPU:<n>`` planes, host spans from every ``bench.*`` annotation the
harness wrote (``jax.profiler.TraceAnnotation``) on the host plane; both are
on the profiler's one clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

HOST_SPAN_PREFIX = "bench."
SLICE_SPAN = "bench.slice"
# wrappers whose children are events of their own on the same line
WRAPPER_CATEGORIES = ("while", "conditional", "call")
MIN_HOST_GAP_S = 5e-6


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path: str, max_chips: int = 4) -> dict:
    """{"chips": [{"ops": [...], "modules": [...]}], "host": [...]} with
    every event as [name, start_s, dur_s, category]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, host = [], []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            chip = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                key = "ops" if line.name == "XLA Ops" else "modules"
                for ev in line.events:
                    name, cat = ev.name, ""
                    if key == "ops":
                        for k, v in ev.stats:
                            if k == "hlo_category":
                                cat = str(v)
                                break
                        name, derived = split_hlo(name)
                        cat = cat or derived
                    chip[key].append(
                        [name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9, cat]
                    )
            chips.append((plane.name, chip))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host.append([ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9, ""])
    chips.sort(key=lambda c: int(c[0].rsplit(":", 1)[1]))
    return {"chips": [c for _, c in chips[:max_chips]], "host": host}


def split_hlo(text: str) -> tuple:
    """An operation event on the TPU is named by its whole HLO line,
    ``%name.3 = shape opcode(operands), kind=kLoop, ...``. Returns the short
    name and a category in the words XLA's own ``hlo_category`` uses: the
    opcode, ``<kind> fusion`` for a fusion, and ``convolution`` (the TPU's
    name for every dot) wherever the fused operations' names say one is
    inside."""
    if " = " not in text:
        return text, ""
    short, rest = text.split(" = ", 1)
    short = short.lstrip("%")
    m = re.search(r"\s([a-z][a-z\-]*)\(", " " + rest)
    opcode = m.group(1) if m else ""
    if opcode == "fusion":
        kind = re.search(r"kind=k(\w+)", rest)
        opcode = f"{kind.group(1).lower()} fusion" if kind else "fusion"
    if "convolution" in short or re.search(r"(^|_)dot(_|\.|$)", short):
        opcode = "convolution fusion" if "fusion" in opcode else "convolution"
    return short, opcode


def head(trace: dict, seconds: float) -> dict:
    """The first ``seconds`` of the slice (or of the trace), as a trace of the
    same form: small enough to keep as a recorded sample."""
    starts = [s for name, s, _, _ in trace["host"] if name == SLICE_SPAN] or [
        min((s for c in trace["chips"] for _, s, _, _ in c["ops"]), default=0.0)
    ]
    lo, hi = starts[0], starts[0] + seconds
    keep = lambda evs: [
        [n, round(s - lo, 9), round(d, 9), c] for n, s, d, c in evs if s >= lo and s + d <= hi
    ]
    return {
        "chips": [{"ops": keep(c["ops"]), "modules": keep(c["modules"])} for c in trace["chips"]],
        "host": keep(trace["host"]) + [[SLICE_SPAN, 0.0, seconds, ""]],
    }


def _union(intervals: list) -> list:
    """Sorted disjoint [start, end] covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def module_name(raw: str) -> str:
    """'jit__decode_jit(1234567)' -> 'jit__decode_jit'."""
    return re.sub(r"\(\d+\)$", "", raw)


class _HostSpans:
    """The innermost (shortest) harness span covering a point in time. The
    harness's spans follow one another and nest two deep at most, so the few
    that start last before the point are all that can cover it."""

    LOOK_BACK = 16

    def __init__(self, host: list):
        self.spans = sorted(
            (s, s + d, name) for name, s, d, _ in host if name != SLICE_SPAN
        )
        self.starts = [s for s, _, _ in self.spans]

    def covering(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t)
        best, best_dur = "no_harness_span", None
        for s, e, name in self.spans[max(i - self.LOOK_BACK, 0):i]:
            if t <= e and (best_dur is None or e - s < best_dur):
                best, best_dur = name, e - s
        return best


def reduce(trace: dict, top: int = 10) -> dict:
    """See the module docstring. Times in seconds; ``busy_s`` is the mean
    over the chips traced; sums by module, category and operation are of the
    first chip (every chip runs the same program)."""
    chips, host = trace["chips"], trace["host"]
    if not chips or not any(c["ops"] for c in chips):
        return {}
    slices = [(s, s + d) for name, s, d, _ in host if name == SLICE_SPAN]
    if slices:
        lo, hi = slices[0]
    else:
        lo = min(s for c in chips for _, s, _, _ in c["ops"])
        hi = max(s + d for c in chips for _, s, d, _ in c["ops"])
    busy = []
    for chip in chips:
        covered = _clip(_union([[s, s + d] for _, s, d, _ in chip["ops"]]), lo, hi)
        busy.append(sum(e - s for s, e in covered))
    first = chips[0]

    modules: dict = {}
    spans = []
    for name, s, d, _ in first["modules"]:
        if s + d <= lo or s >= hi:
            continue
        m = modules.setdefault(module_name(name), {"count": 0, "seconds": 0.0})
        m["count"] += 1
        m["seconds"] += d
        spans.append((s, s + d, module_name(name)))
    spans.sort()

    by_module_cat: dict = {}
    by_op: dict = {}
    starts = [s for s, _, _ in spans]
    for name, s, d, cat in first["ops"]:
        if s < lo or s >= hi or cat in WRAPPER_CATEGORIES:
            continue
        i = bisect.bisect_right(starts, s) - 1
        mod = spans[i][2] if i >= 0 and s < spans[i][1] else "no_module"
        cats = by_module_cat.setdefault(mod, {})
        cats[cat or "uncategorised"] = cats.get(cat or "uncategorised", 0.0) + d
        family = re.sub(r"[.\d]+$", "", name) or name
        by_op[family] = by_op.get(family, 0.0) + d

    covered = _clip(_union([[s, s + d] for _, s, d, _ in first["ops"]]), lo, hi)
    gaps, cursor = [], lo
    for s, e in covered + [[hi, hi]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    by_span: dict = {}
    spans_of_host = _HostSpans(host)
    for s, e in gaps:
        # the pauses of a microsecond or two between one operation and the
        # next inside a program are the device's own, not the host's
        span = (
            "between_ops_of_one_program" if e - s < MIN_HOST_GAP_S
            else spans_of_host.covering((s + e) / 2)
        )
        by_span[span] = by_span.get(span, 0.0) + (e - s)

    order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "chips": len(chips),
        "modules": modules,
        "by_module_category": by_module_cat,
        "device_ops": [[k, v] for k, v in order(by_op)],
        "idle_gaps": [[k, v] for k, v in order(by_span)],
        "longest_gap_s": max((e - s for s, e in gaps), default=0.0),
    }


def category_seconds(reduced: dict, module: str, categories: tuple) -> float:
    """Device seconds of ``module``'s operations whose HLO category contains
    one of ``categories`` (case-insensitive substrings)."""
    cats = reduced.get("by_module_category", {}).get(module, {})
    return sum(
        v for k, v in cats.items()
        if any(c.lower() in k.lower() for c in categories)
    )
