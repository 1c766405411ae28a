"""From a profiler trace to the program's own names: every device operation
with the scope path the program gave it (``jax.named_scope``, the Flax module
path beneath) and, for a Pallas kernel, its ``name=``; every host span the
program wrote (``TELEMETRY`` spans, bridged by ``utils/profiling.py``).

Where the names arrive (looked at by hand in a chip trace of PR 30): an
``XLA Ops`` event is named by its HLO line, which holds the instruction's name
(``%block_sparse_fwd.6 = ... custom_call_target="tpu_custom_call"``) but not
its ``metadata={op_name=...}``. The name stack is the ``tf_op`` stat of the
event's METADATA record in the ``.xplane.pb``
(``jit(train_step)/transpose(jvp(DALLE))/transformer/attn.axial_row/attn_1/…``),
which ``jax.profiler.ProfileData`` does not show. So the device planes are read
here from the file's bytes, by the few fields of the ``XSpace`` message that
are needed (tsl/profiler/protobuf/xplane.proto); host spans come through
``ProfileData`` exactly as ``trace_reduce`` reads them.

``load`` gives a trace in ``trace_reduce``'s form with two additions, so that
both reducers take it: every chip has ``scopes``, one index per operation into
the top-level ``scope_table`` of paths, and ``host`` also holds the program's
spans (dot-separated lower-case names not starting with ``bench.``). ``head``
keeps the start of it as a small recorded sample; ``reduce`` does the
arithmetic and touches no jax. A trace of a program without scopes or named
kernels (the parent of PR 30) loads with empty paths, and every reader of
these numbers then finds nothing to read.
"""

from __future__ import annotations

import bisect
import fnmatch
import re

from . import harness, trace_reduce

# TELEMETRY names: one dot-separated lower-case namespace per subsystem
# (``serve.step.fold_keys``; not the CPU backend's ``copy.117``)
PROGRAM_SPAN = re.compile(r"[a-z_]+(\.[a-z_][a-z_0-9]*)+")
FACT = "_scope_trace"


# ------------------------------------------------------- the file's bytes


def _fields(buf, start: int, end: int):
    """(field number, wire type, value) of one protobuf message: an int for
    a varint, (start, end) offsets into ``buf`` for a length-delimited field,
    None for a fixed-width one (none is needed here)."""
    i = start
    while i < end:
        # two varints: the key, then (wire types 0 and 2) a value or a length
        pair = []
        for _ in range(2):
            value = shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            pair.append(value)
            if pair[0] & 7 not in (0, 2):
                break
        number, wire = pair[0] >> 3, pair[0] & 7
        if wire == 0:
            yield number, wire, pair[1]
        elif wire == 2:
            yield number, wire, (i, i + pair[1])
            i += pair[1]
        elif wire in (1, 5):
            yield number, wire, None
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane file")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key, value = 0, None
    for n, _, v in _fields(buf, *span):
        if n == 1:
            key = v
        elif n == 2:
            value = v
    return key, value


def _device_plane(buf, span) -> dict:
    """One ``/device:TPU:<n>`` XPlane: its operation and module events with
    times in seconds, and each operation's ``tf_op`` path."""
    lines, event_meta, stat_names = [], [], {}
    for n, _, v in _fields(buf, *span):
        if n == 3:
            lines.append(v)
        elif n == 4:
            event_meta.append(v)
        elif n == 5:
            key, value = _map_entry(buf, v)
            for m, _, w in _fields(buf, *value):
                if m == 2:
                    stat_names[key] = _text(buf, w)
    tf_op = next((k for k, name in stat_names.items() if name == "tf_op"), None)
    names, paths = {}, {}
    for entry in event_meta:
        key, value = _map_entry(buf, entry)
        for m, _, w in _fields(buf, *value):
            if m == 2:
                names[key] = _text(buf, w)
            elif m == 5 and tf_op is not None:
                stat = {k: x for k, _, x in _fields(buf, *w)}
                if stat.get(1) == tf_op:
                    # the string itself, or a reference to a stat's name
                    paths[key] = (
                        _text(buf, stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
                    )
    out = {"ops": [], "modules": [], "paths": []}
    for line in lines:
        name, t0_ns, events = "", 0, []
        for m, _, w in _fields(buf, *line):
            if m == 2:
                name = _text(buf, w)
            elif m == 3:
                t0_ns = w
            elif m == 4:
                events.append(w)
        if name not in ("XLA Ops", "XLA Modules"):
            continue
        for ev in events:
            f = {k: x for k, _, x in _fields(buf, *ev) if k in (1, 2, 3)}
            start = (t0_ns * 1000 + f.get(2, 0)) * 1e-12
            dur = f.get(3, 0) * 1e-12
            text = names.get(f.get(1), "")
            if name == "XLA Modules":
                out["modules"].append([text, start, dur, ""])
                continue
            short, cat = trace_reduce.split_hlo(text)
            out["ops"].append([short, start, dur, cat])
            out["paths"].append(paths.get(f.get(1), "").rstrip(":"))
    return out


def load(path: str, max_chips: int = 4) -> dict:
    """See the module docstring."""
    from jax.profiler import ProfileData

    with open(path, "rb") as fh:
        data = fh.read()
    buf = memoryview(data)
    planes = []
    for n, _, v in _fields(buf, 0, len(buf)):
        if n != 1:
            continue
        name = next((_text(buf, w) for m, _, w in _fields(buf, *v) if m == 2), "")
        if re.fullmatch(r"/device:TPU:\d+", name):
            planes.append((int(name.rsplit(":", 1)[1]), _device_plane(buf, v)))
    planes.sort(key=lambda p: p[0])
    index, chips = {"": 0}, []    # path -> its place in the table, in order
    for _, plane in planes[:max_chips]:
        scopes = [index.setdefault(p, len(index)) for p in plane.pop("paths")]
        chips.append({**plane, "scopes": scopes})
    host = []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(trace_reduce.HOST_SPAN_PREFIX) or PROGRAM_SPAN.fullmatch(ev.name):
                    host.append([ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9, ""])
    return {"chips": chips, "host": host, "scope_table": list(index)}


def head(trace: dict, seconds: float) -> dict:
    """The first ``seconds`` of the slice, as ``trace_reduce.head`` cuts it,
    with the scopes of the operations kept and the table cut to them."""
    starts = [s for name, s, _, _ in trace["host"] if name == trace_reduce.SLICE_SPAN] or [
        min((s for c in trace["chips"] for _, s, _, _ in c["ops"]), default=0.0)
    ]
    lo, hi = starts[0], starts[0] + seconds
    out = trace_reduce.head(trace, seconds)
    used: dict = {0: 0}
    for chip, cut in zip(trace["chips"], out["chips"]):
        kept = [
            i for (_, s, d, _), i in zip(chip["ops"], chip["scopes"]) if s >= lo and s + d <= hi
        ]
        cut["scopes"] = [used.setdefault(i, len(used)) for i in kept]
    out["scope_table"] = [trace["scope_table"][i] for i in used]
    return out


# ---------------------------------------------------------- the arithmetic


def matches(path: str, patterns) -> bool:
    """True if a component of the scope path (the part between ``/`` and the
    brackets of ``jvp(...)``, ``transpose(...)``) is one of ``patterns``
    (``fnmatch``: ``attn.*`` is every attention kind)."""
    return any(
        fnmatch.fnmatchcase(token, pattern)
        for token in re.split(r"[/()]+", path) for pattern in patterns
    )


def kernel_of(name: str, category: str) -> str:
    """The Pallas kernel an operation is, or '': 'block_sparse_fwd.6' ->
    'block_sparse_fwd'. XLA names a custom call of its own after its opcode
    (``custom-call.73``: a bitcast, a bounds hint, no time to speak of); a
    Pallas call carries the kernel's name (``fn`` where it was given none)."""
    family = re.sub(r"[.\d]+$", "", name) or name
    return family if category == "custom-call" and family != "custom-call" else ""


def reduce(trace: dict) -> dict:
    """Device seconds of the first chip's operations inside the slice, summed
    by (compiled module, scope path, Pallas kernel or ''), with the window and
    the wrappers as ``trace_reduce.reduce`` takes them; the host's program
    spans with their self times; and the device's idle gaps, each with the
    program spans that cover its midpoint."""
    chips, host = trace["chips"], trace["host"]
    first = chips[0] if chips else {"ops": [], "modules": [], "scopes": []}
    table = trace["scope_table"]
    slices = [(s, s + d) for name, s, d, _ in host if name == trace_reduce.SLICE_SPAN]
    if slices:
        lo, hi = slices[0]
    elif first["ops"]:
        lo = min(s for _, s, _, _ in first["ops"])
        hi = max(s + d for _, s, d, _ in first["ops"])
    else:
        return {}
    spans = sorted(
        (s, s + d, trace_reduce.module_name(name))
        for name, s, d, _ in first["modules"] if s + d > lo and s < hi
    )
    starts = [s for s, _, _ in spans]
    by_scope: dict = {}
    for (name, s, d, cat), idx in zip(first["ops"], first["scopes"]):
        if s < lo or s >= hi or cat in trace_reduce.WRAPPER_CATEGORIES:
            continue
        i = bisect.bisect_right(starts, s) - 1
        mod = spans[i][2] if i >= 0 and s < spans[i][1] else "no_module"
        key = (table[idx], kernel_of(name, cat))
        rows = by_scope.setdefault(mod, {})
        rows[key] = rows.get(key, 0.0) + d

    program = sorted(
        (s, s + d, name) for name, s, d, _ in host
        if not name.startswith(trace_reduce.HOST_SPAN_PREFIX) and s + d > lo and s < hi
    )
    covered = trace_reduce._clip(
        trace_reduce._union([[s, s + d] for _, s, d, _ in first["ops"]]), lo, hi
    )
    gaps, cursor = [], lo
    for s, e in covered + [[hi, hi]]:
        if s - cursor >= trace_reduce.MIN_HOST_GAP_S:
            mid = (cursor + s) / 2
            gaps.append([s - cursor, sorted({n for a, b, n in program if a <= mid <= b})])
        cursor = max(cursor, e)
    return {
        "window_s": hi - lo,
        "by_scope": {
            mod: [[path, kernel, sec] for (path, kernel), sec in rows.items()]
            for mod, rows in by_scope.items()
        },
        "host_spans": _self_times(program),
        "idle_gaps": gaps,
    }


def _self_times(program: list) -> dict:
    """{name: {"count", "seconds", "self_seconds"}}: a span's self time is its
    duration less the spans nested directly inside it (the program's lexical
    spans nest on one thread; a span that only overlaps is no child)."""
    out: dict = {}
    stack: list = []   # [end, name, start, seconds of direct children]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, start, inside = stack.pop()
            row = out.setdefault(name, {"count": 0, "seconds": 0.0, "self_seconds": 0.0})
            row["count"] += 1
            row["seconds"] += end - start
            row["self_seconds"] += end - start - inside
            if stack:
                stack[-1][3] += end - start
    for s, e, name in program:
        close(s)
        if stack and e > stack[-1][0]:
            continue   # overlaps its neighbour without nesting: another thread's
        stack.append([e, name, s, 0.0])
    close(float("inf"))
    return out


def scope_seconds(reduced: dict, module: str, scopes, kernels_only: bool = False) -> float:
    """Device seconds of ``module``'s operations whose path has a component
    among ``scopes``; with ``kernels_only`` the Pallas kernels alone."""
    return sum(
        sec for path, kernel, sec in reduced.get("by_scope", {}).get(module, [])
        if (kernel or not kernels_only) and matches(path, scopes)
    )


def of_run(ctx) -> dict:
    """The reduction of this run's traced slice, read once a run and kept in
    ``ctx.facts``; {} where no slice was traced. It also puts its own head of
    the trace, scopes included, in the place of the harness's sample, so that
    ``--dump`` keeps a recorded trace that every reducer can read."""
    if FACT not in ctx.facts:
        reduced = {}
        slice_dir = harness.WORK / f"trace-{ctx.workload['name']}"
        if ctx.trace and list(slice_dir.rglob("*.xplane.pb")):
            trace = load(trace_reduce.find_xplane(str(slice_dir)), ctx.chips)
            reduced = reduce(trace)
            ctx.facts["_trace_sample"] = head(trace, harness.SAMPLE_TRACE_S)
        ctx.facts[FACT] = reduced
    return ctx.facts[FACT]
