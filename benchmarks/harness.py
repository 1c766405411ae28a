"""What every driver shares: the run's context, the compile counter, the
trace slice, the readers of per-layer metrics, and the result line."""

from __future__ import annotations

import importlib
import json
import os
import pathlib
import resource
import shutil
import sys
import time
from dataclasses import dataclass, field

HERE = pathlib.Path(__file__).resolve().parent
WORK = HERE / ".work"            # traces; listed in .gitignore
MAX_FILE_BYTES = 32 * 2**20      # no file the benchmark writes may pass this
SAMPLE_TRACE_S = 0.45            # of a slice, kept by --dump as a small recorded trace


@dataclass
class Context:
    """One run: what was asked for, and what the driver found."""

    workload: dict
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    control: str | None
    process_start: float
    chips: int = 1
    device_kind: str = ""
    peaks: dict = field(default_factory=dict)
    # filled by the driver
    end_to_end: dict = field(default_factory=dict)
    facts: dict = field(default_factory=dict)      # counters and spans for the readers
    compared: list = field(default_factory=list)   # (name, value, limit)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    reduced: dict = field(default_factory=dict)    # trace_reduce.reduce()
    compiles_in_window: int = 0

    def compare(self, name: str, value: float, limit: float) -> None:
        self.compared.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(
            v == v and v <= lim for _, v, lim in self.compared
        )


class CompileCounter:
    """Backend compiles seen so far (``jax.monitoring``'s duration event)."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.n = 0

        def on_duration(name, _secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        monitoring.register_event_duration_secs_listener(on_duration)


def fsize_limit() -> int | None:
    soft = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    return None if soft == resource.RLIM_INFINITY else int(soft)


class TraceSlice:
    """A few seconds of the window under ``jax.profiler``. ``maybe_start`` and
    ``maybe_stop`` are called between steps; the slice opens ``after`` seconds
    into the window and closes ``length`` seconds later."""

    def __init__(self, ctx: Context, window_start: float, after: float = 1.0):
        self.ctx = ctx
        self.on = ctx.trace
        self.length = float(ctx.mix.get("trace_slice_s", 2.0))
        self.begin_at = window_start + min(after, ctx.seconds / 4)
        self.dir = WORK / f"trace-{ctx.workload['name']}"
        self.started = self.stopped = None
        self._slice = None
        self.overhead_s = 0.0   # starting the profiler and writing the trace

    def maybe_start(self, now: float) -> None:
        if not self.on or self.started is not None or now < self.begin_at:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=options)
        self._slice = jax.profiler.TraceAnnotation("bench.slice")
        self._slice.__enter__()
        self.started = time.monotonic()
        self.overhead_s += self.started - now

    def maybe_stop(self, now: float, force: bool = False) -> None:
        if self.started is None or self.stopped is not None:
            return
        if not force and now < self.started + self.length:
            return
        import jax

        self._slice.__exit__(None, None, None)
        began = time.monotonic()
        try:
            jax.profiler.stop_trace()
        except OSError as e:
            raise SystemExit(f"the trace could not be written ({e}); "
                             f"RLIMIT_FSIZE is {fsize_limit()}")
        self.overhead_s += time.monotonic() - began
        self.stopped = now

    def reduce(self, chips: int) -> dict:
        from . import trace_reduce

        if self.stopped is None:
            return {}
        path = trace_reduce.find_xplane(str(self.dir))
        size = os.path.getsize(path)
        if size > MAX_FILE_BYTES:
            raise SystemExit(
                f"trace slice {path} is {size} bytes, over the {MAX_FILE_BYTES} "
                "this benchmark allows itself: shorten trace_slice_s"
            )
        self.loaded = trace_reduce.load_xplane(path, chips)
        reduced = trace_reduce.reduce(self.loaded)
        reduced["trace_bytes"] = size
        self.ctx.facts["_trace_sample"] = trace_reduce.head(self.loaded, SAMPLE_TRACE_S)
        return reduced


def span(name: str):
    """A host span in the profiler's trace; free when no trace is running."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def load_metric(name: str) -> dict:
    return json.loads((HERE / "metrics" / f"{name}.json").read_text())


def read_per_layer(ctx: Context, bench: dict) -> dict:
    """Every per-layer metric of ``BENCHMARK.json`` that lists this cell (or
    lists none), through the reader its own file names. A reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for entry in bench["per_layer"]:
        cells = entry.get("workloads")
        if cells is not None and ctx.workload["name"] not in cells:
            continue
        spec = load_metric(entry["name"])
        reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def emit(ctx: Context, metrics: dict, devices) -> None:
    """The numbers compared, beside their limits, as the last lines of
    standard error; then the one result line on standard output."""
    checks = {n: {"value": v, "limit": lim} for n, v, lim in ctx.compared}
    for n, c in checks.items():
        print(f"compared {n}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": ctx.chips,
        "memory_peak_bytes": ctx.memory_peak_bytes,
    }
    line = {
        "correct": ctx.correct,
        "attempted": ctx.attempted,
        "failed": ctx.failed,
        "metrics": metrics,
        "device": device,
    }
    if ctx.trace and not ctx.rehearsal and not ctx.reduced.get("busy_s"):
        raise SystemExit("the traced slice holds no device operation: no result")
    if ctx.trace and ctx.reduced.get("busy_s"):
        device["busy_s"] = ctx.reduced["busy_s"]
        device["window_s"] = ctx.reduced["window_s"]
        line["breakdown"] = {
            "device_ops": ctx.reduced["device_ops"],
            "idle_gaps": ctx.reduced["idle_gaps"],
        }
    line["compiles_in_window"] = ctx.compiles_in_window
    line["read_not_compared"] = {
        k[: -len("_not_compared")]: v for k, v in ctx.facts.items() if k.endswith("_not_compared")
    }
    line["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def dump(ctx: Context, directory: str) -> None:
    """The reduced trace and the scalar facts of the run as one small JSON."""
    out = pathlib.Path(directory)
    out.mkdir(parents=True, exist_ok=True)
    facts = {
        k: v for k, v in ctx.facts.items()
        if isinstance(v, (int, float, str)) or k in ("leaves_left_out", "limits")
    }
    name = f"{ctx.workload['name']}-seed{ctx.seed}-trace{int(ctx.trace)}.json"
    (out / name).write_text(json.dumps({
        "end_to_end": ctx.end_to_end, "facts": facts, "reduced": ctx.reduced,
        "compared": ctx.compared, "memory_peak_bytes": ctx.memory_peak_bytes,
    }, indent=1))
    sample = ctx.facts.get("_trace_sample")
    if sample:
        (out / f"trace-sample-{ctx.workload['name']}.json").write_text(json.dumps(sample))
