"""The jax side of the telemetry layer: the program's spans on the
profiler's clock, the trainer's step-window capture, and the compile ledger.

``utils/telemetry.py`` is host-only by contract (DTL021) and keeps its own
monotonic clock. Importing THIS module (``serving/``, ``parallel/step.py``
and the CLIs do) sets ``TELEMETRY.annotate`` to
``jax.profiler.TraceAnnotation``, so every lexical ``TELEMETRY.span()`` also
opens a host annotation. Outside a capture that is a flag test; inside one —
started by the benchmark, ``--profile_trace_dir`` or an operator's
``jax.profiler.start_trace`` — the span lands in the same ``.xplane.pb`` as
the device events, on their clock, with no switch to flip
(docs/DESIGN.md §9).

A profiler capture starts after set-up and shows no compile. What set-up is
made of — which program was traced, lowered, loaded from the persistent
cache or compiled afresh, for how long and when — is ``COMPILE_LEDGER``:
``jax.monitoring``'s compile events on ``time.monotonic()``, installed by
``compile_cache.enable_compile_cache()`` and always on (DESIGN §9 "Why was
set-up slow / which step recompiled").
"""

from __future__ import annotations

import re
import threading
import time
from collections import deque
from typing import Any, Iterable, NamedTuple, Optional

import jax

from .metrics import counters, gauges, histograms
from .telemetry import TELEMETRY

TELEMETRY.annotate = jax.profiler.TraceAnnotation


class StepCapture:
    """A ``jax.profiler`` capture of ``length`` consecutive steps of a loop,
    opening at ``first_step``. Both edges wait for the device, so neither
    compilation nor an earlier step's tail lands in the capture.
    ``trace_dir=None`` (no capture asked for, or not the root worker)
    makes every call a no-op."""

    def __init__(self, trace_dir: Optional[str], first_step: int,
                 length: int = 3):
        self.trace_dir = trace_dir
        self.first_step = int(first_step)
        self.length = int(length)
        self.open = False

    def at_step(self, step: int, wait_on: Any) -> bool:
        """Call before dispatching ``step``; ``wait_on`` is what the last
        dispatched step produced. True when this call closed the capture."""
        if self.trace_dir is None:
            return False
        if step == self.first_step:
            jax.block_until_ready(wait_on)
            jax.profiler.start_trace(self.trace_dir)
            self.open = True
        elif step == self.first_step + self.length and self.open:
            self.close(wait_on)
            return True
        return False

    def close(self, wait_on: Any = None) -> None:
        """Stop a capture that is still open (the loop ended or was
        preempted inside the window)."""
        if not self.open:
            return
        if wait_on is not None:
            jax.block_until_ready(wait_on)
        jax.profiler.stop_trace()
        self.open = False


# ------------------------------------------------------------ compile ledger

# jax.monitoring's duration events -> the ledger's kinds. ``backend`` fires
# once per compile REQUEST, a persistent-cache hit included; ``cache_load``
# is the part of a hit's ``backend`` interval spent reading the cache.
DURATION_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
SUMMED_KINDS = ("trace", "lower", "backend")  # cache_load lies inside backend


class CompileRecord(NamedTuple):
    """One compile event, ``start``/``end`` on ``time.monotonic()``. The point
    kinds ``cache_hit`` and ``cache_miss`` have ``seconds`` 0."""

    start: float
    end: float
    kind: str  # trace | lower | backend | cache_load | cache_hit | cache_miss
    fun_name: str  # jax's: ``train_step`` traced, ``jit(train_step)`` after
    seconds: float


def program_of(fun_name: str) -> str:
    """``jit(train_step)`` and ``train_step`` are one program's events."""
    m = re.fullmatch(r"(?:jit|pmap)\((.*)\)", fun_name)
    return m.group(1) if m else fun_name


def summarize(records: Iterable[CompileRecord], since: float = float("-inf"),
              until: float = float("inf")) -> dict:
    """What the records that END in ``[since, until]`` add up to.

    A jit traced inside another's trace reports its own ``trace`` event inside
    the outer one's interval: a record whose interval lies inside another
    record's of the same kind is nested and adds nothing to the sums (adjacent
    and partly overlapping records each count in full). ``seconds`` holds the
    sums by kind, ``programs`` the same by outermost program
    (``program_of``), ``costliest`` the ten programs with the most
    trace + lower + backend seconds, dearest first."""
    kept = [r for r in records if since <= r.end <= until]
    seconds = {kind: 0.0 for kind in DURATION_KINDS.values()}
    programs: dict = {}
    hits = misses = 0
    # widest first among those that start together: a record is nested iff
    # one sorted before it, of its kind, ends no earlier
    reach: dict = {}
    for r in sorted(kept, key=lambda r: (r.start, -r.end)):
        if r.kind == "cache_hit":
            hits += 1
        elif r.kind == "cache_miss":
            misses += 1
        if r.kind not in seconds:
            continue
        if r.end <= reach.get(r.kind, float("-inf")):
            continue
        reach[r.kind] = r.end
        seconds[r.kind] += r.seconds
        of = programs.setdefault(program_of(r.fun_name), dict.fromkeys(seconds, 0.0))
        of[r.kind] += r.seconds
    cost = {p: sum(of[k] for k in SUMMED_KINDS) for p, of in programs.items()}
    return {
        "seconds": seconds,
        "programs": programs,
        "requests": sum(r.kind == "backend" for r in kept),
        "cache_hits": hits,
        "cache_misses": misses,
        "costliest": sorted(cost.items(), key=lambda kv: -kv[1])[:10],
        "records": len(kept),
    }


class _InFlight(threading.local):
    """What one thread's listeners hold between events: ``traces``, fun_name ->
    its last ``trace`` record since the thread's last ``lower`` event, and
    ``events``, (kind, end, seconds) of the cache's nameless events since its
    last ``backend`` event."""

    def __init__(self):
        self.traces: dict = {}
        self.events: list = []


class CompileLedger:
    """Every compile event of the process, in order (see ``CompileRecord``).

    The persistent cache's events carry no name and fire inside the
    ``backend`` interval of the request they belong to, just before its own
    event: they wait, per thread, and take that event's ``fun_name``. A
    ``cache_miss`` is a request with no hit inside it. jax's own
    ``cache_misses`` event is not that: it fires only where an entry is
    WRITTEN, so never for a program compiled afresh in under
    ``jax_persistent_cache_min_compile_time_secs``.

    Bounded: past ``cap`` records the oldest go and are counted in
    ``dropped``. Listeners run only at compile events; a steady loop that
    compiles nothing runs none of this."""

    def __init__(self, cap: int = 65536):
        self._lock = threading.Lock()
        self._records: deque = deque(maxlen=int(cap))
        self._waiting = _InFlight()
        self.dropped = 0
        self.installed_at: Optional[float] = None

    def install(self) -> None:
        """Register the one listener pair; later calls do nothing."""
        with self._lock:
            if self.installed_at is not None:
                return
            self.installed_at = time.monotonic()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw: Any) -> None:
        if event == CACHE_HIT_EVENT:
            self._waiting.events.append(("cache_hit", time.monotonic(), 0.0))

    def _on_duration(self, event: str, seconds: float, fun_name: str = "",
                     **_kw: Any) -> None:
        kind = DURATION_KINDS.get(event)
        if kind is None:
            return
        end = time.monotonic()
        if kind == "cache_load":
            self._waiting.events.append((kind, end, seconds))
            return
        record = CompileRecord(end - seconds, end, kind, fun_name, seconds)
        new = [record]
        if kind == "trace":
            # every jnp function a step calls is a jit traced inside its
            # trace, thousands in a set-up: only the ledger keeps those. A
            # program's own trace is the one its ``lower`` follows (lowering
            # rules trace jnp functions too, so not simply the last)
            self._waiting.traces[fun_name] = record
        elif kind == "lower":
            traced = self._waiting.traces.get(program_of(fun_name))
            self._waiting.traces.clear()
            if traced is not None:
                self._publish(traced)
            self._publish(record)
        else:
            inside, self._waiting.events = self._waiting.events, []
            hit = any(k == "cache_hit" for k, _, _ in inside)
            if not hit:
                inside.append(("cache_miss", end, 0.0))
            for k, at, took in inside:
                new.append(CompileRecord(at - took, at, k, fun_name, took))
                if k == "cache_load":
                    histograms.observe("compile.cache_load_s", took)
            counters.inc("compile.requests")
            if hit:
                counters.inc("compile.cache_hits")
            else:
                counters.inc("compile.cache_misses")
            self._publish(record, cache_hit=hit)
        with self._lock:
            self.dropped += max(0, len(self._records) + len(new) - self._records.maxlen)
            self._records.extend(new)

    @staticmethod
    def _publish(r: CompileRecord, **attrs: Any) -> None:
        """One program's own event under the program's names: its histogram
        (sum = seconds, count = requests) and, with the ring enabled, the
        flight recorder's ``compile.request``."""
        histograms.observe(f"compile.{r.kind}_s", r.seconds)
        TELEMETRY.event("compile.request", kind=r.kind, fun_name=r.fun_name,
                        seconds=r.seconds, **attrs)

    def records(self) -> list:
        with self._lock:
            return list(self._records)

    def summary(self, since: float = float("-inf"), until: float = float("inf")) -> dict:
        """``summarize`` over this ledger's records, plus ``dropped``."""
        return {**summarize(self.records(), since, until), "dropped": self.dropped}

    def first_step_line(self) -> Optional[str]:
        """The operator's view of set-up, made when the first step's verdict
        is read: what every program so far cost, and seconds since
        ``install`` (also gauge ``train.first_step_s``). None where no
        ledger is installed."""
        if self.installed_at is None:
            return None
        now = time.monotonic()
        s = self.summary(self.installed_at, now)
        gauges.set("train.first_step_s", now - self.installed_at)
        sec = s["seconds"]
        dearest = (f"; costliest program {s['costliest'][0][0]} "
                   f"{s['costliest'][0][1]:.1f} s" if s["costliest"] else "")
        return (
            f"first step verdict {now - self.installed_at:.1f} s after start-up: traced "
            f"{sec['trace']:.1f} s, lowered {sec['lower']:.1f} s, loaded or compiled "
            f"{sec['backend']:.1f} s ({s['requests']} requests: {s['cache_hits']} cache "
            f"hits taking {sec['cache_load']:.1f} s, {s['cache_misses']} compiled afresh)"
            f"{dearest}"
        )


COMPILE_LEDGER = CompileLedger()
