"""Unified telemetry: spans, events, flight recorder, and /metrics.

The observability layer every hot path reports through (docs/DESIGN.md
§9). Three pieces, one module:

**Spans and events.** ``span("serve.prefill", request_id=...)`` is a
context manager timing one host-side phase; ``begin``/``end`` are the
non-lexical form for spans that straddle loop iterations (a serving
request's whole lifecycle, a train step from dispatch to its verdict);
``event(...)`` is a point-in-time record. Every record is a flat dict —
``{"ts", "ph" ("B"|"E"|"I"), "name", "id", "parent", **attrs}`` — on a
monotonic clock. The clock is injectable and duck-types the serving
``Clock`` protocol (``.now() -> float``; ``serving/types.py``), so
``FakeClock``-driven tests pin span timing deterministically. Span
durations are auto-observed into a ``<name>_s`` histogram
(``utils.metrics.histograms``), which is how request latency, queue
wait, step time, and data wait become first-class percentiles instead
of ad-hoc sorts in bench code.

**Flight recorder.** Records land in a bounded in-memory ring buffer;
when a flight directory is configured, a full ring DRAINS to a JSONL
file (rotation) instead of dropping, and drains also fire from the
``PreemptionHandler`` signal callback and an atexit hook — so a SIGTERM
or NaN-abort leaves a structured record of the run's last seconds, with
any still-open ``"B"`` records showing exactly what was in flight.
Without a flight dir the ring drops oldest (counted). Telemetry is
observability, not control: every sink failure FAILS OPEN — counted
under ``telemetry.sink_errors`` (injectable via the
``telemetry_sink_fail`` fault site), never raised into train/serve.

**Exposition.** ``dump()`` renders counters, gauges, and histograms as
Prometheus-style text; ``serve_metrics(port)`` serves it at
``GET /metrics`` from a stdlib ``http.server`` daemon thread bound to
127.0.0.1 only (no auth — localhost scrape or port-forward; off by
default). Root-rank-guard the same way ``MetricsLogger`` is: only the
root worker passes ``enabled=True``.

Disabled (the default) is a TRUE no-op: no threads, no files, no
records — ``span()`` yields immediately. Enable programmatically
(``TELEMETRY.configure(enabled=True, ...)``) or by environment for CLI
subprocesses, mirroring ``DALLE_TPU_FAULTS``::

    DALLE_TPU_TELEMETRY=1
    DALLE_TPU_TELEMETRY_DIR=/tmp/flight     # optional: flight recorder
    DALLE_TPU_TELEMETRY_PORT=9100           # optional: /metrics server

This module is deliberately host-side only — it must never import jax
or touch device values (a per-token device sync would be a measurement
that destroys what it measures); callers pass plain Python numbers.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional, Tuple

from .faults import FAULTS
from .metrics import counters, gauges, histograms

ENV_ENABLE = "DALLE_TPU_TELEMETRY"
ENV_DIR = "DALLE_TPU_TELEMETRY_DIR"
ENV_PORT = "DALLE_TPU_TELEMETRY_PORT"


class _MonotonicClock:
    """Default time source; same protocol as ``serving.types.Clock``
    (duck-typed here so telemetry never imports the serving package)."""

    def now(self) -> float:
        import time

        return time.monotonic()


class Telemetry:
    """See module docstring. One process-wide instance (``TELEMETRY``)
    is the normal entry point; tests build private ones.

    The ring state below is written from serve/train threads, loader
    threads, AND re-entrantly from signal handlers; ``_GUARDED_BY`` is
    the machine-checked contract for which fields the RLock guards
    (tools/lint.py DTL051, docs/DESIGN.md §11)."""

    _GUARDED_BY = {"_lock": ("_buf", "_open", "_next_id", "_flight_path")}

    def __init__(self, clock=None, ring_size: int = 4096):
        self._lock = threading.RLock()  # reentrant: drain can fire from a
        # signal handler interrupting a thread that already holds the lock
        self.clock = clock or _MonotonicClock()
        self.enabled = False
        # name -> context manager entered around every lexical span();
        # wiring, not state: reset() leaves it (see span())
        self.annotate = None
        self.ring_size = int(ring_size)
        self._buf: deque = deque()
        self._open: Dict[int, Tuple[str, float]] = {}  # sid -> (name, t0)
        self._tls = threading.local()  # per-thread span stack (nesting)
        self._next_id = 1
        self.dropped = 0
        self.sink_errors = 0
        self.flight_dir: Optional[str] = None
        self.flight_max_bytes = 16 << 20
        self._flight_path: Optional[str] = None
        self._server = None
        self._server_thread = None
        self._atexit_registered = False

    # ------------------------------------------------------------- config

    def configure(
        self,
        enabled: Optional[bool] = None,
        ring_size: Optional[int] = None,
        flight_dir: Optional[str] = None,
        flight_max_bytes: Optional[int] = None,
        metrics_port: Optional[int] = None,
        clock=None,
    ) -> "Telemetry":
        """Reconfigure in place; returns self. ``enabled=False`` tears
        everything down (server thread stopped, atexit unregistered) so a
        disabled config is a true no-op even after a previous enable."""
        with self._lock:
            if clock is not None:
                self.clock = clock
            if ring_size is not None:
                assert ring_size > 0
                self.ring_size = int(ring_size)
            if flight_dir is not None:
                self.flight_dir = flight_dir or None
                self._flight_path = None
            if flight_max_bytes is not None:
                self.flight_max_bytes = int(flight_max_bytes)
            if enabled is not None:
                self.enabled = bool(enabled)
            if not self.enabled:
                self._stop_server()
                self._unregister_atexit()
                return self
            if self.flight_dir and not self._atexit_registered:
                atexit.register(self._atexit_drain)
                self._atexit_registered = True
            if metrics_port is not None:
                self.serve_metrics(metrics_port)
        return self

    def reset(self) -> None:
        """Back to the pristine disabled state (test hermeticity)."""
        with self._lock:
            self.configure(enabled=False)
            self._buf.clear()
            self._open.clear()
            self.dropped = 0
            self.sink_errors = 0
            self.flight_dir = None
            self._flight_path = None
            self.clock = _MonotonicClock()
            self._tls = threading.local()

    # -------------------------------------------------------------- spans

    def _stack(self) -> List[int]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def begin(self, name: str, parent: Optional[int] = None,
              **attrs: Any) -> Optional[int]:
        """Open a non-lexical span; returns its id (None when disabled —
        ``end(None)`` is a no-op, so call sites need no guards). The
        parent defaults to the calling thread's innermost ``span()``."""
        if not self.enabled:
            return None
        t = self.clock.now()
        with self._lock:
            sid = self._next_id
            self._next_id += 1
            self._open[sid] = (name, t)
        if parent is None:
            st = self._stack()
            parent = st[-1] if st else None
        self._record({"ts": t, "ph": "B", "name": name, "id": sid,
                      "parent": parent, **attrs})
        return sid

    def end(self, span_id: Optional[int], **attrs: Any) -> None:
        """Close a span opened with ``begin``; observes its duration into
        the ``<name>_s`` histogram."""
        if span_id is None or not self.enabled:
            return
        with self._lock:
            name, t0 = self._open.pop(span_id, (None, None))
        t = self.clock.now()
        rec = {"ts": t, "ph": "E", "id": span_id, **attrs}
        if name is not None:
            rec["name"] = name
            rec["dur_s"] = t - t0
            histograms.observe(f"{name}_s", t - t0)
        self._record(rec)

    @contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Optional[int]]:
        """Lexical span: times the with-block, nests via a per-thread
        stack (children record this span as ``parent``).

        The with-block also runs inside ``self.annotate(name)`` when that
        hook is set, WHETHER OR NOT the ring is enabled: the jax side
        (utils/profiling.py) sets it to ``jax.profiler.TraceAnnotation``,
        so any profiler capture holds the program's lexical spans on the
        device events' clock. ``begin``/``end`` spans are not bridged: a
        profiler annotation must close in the order it opened on its
        thread, and those straddle iterations. With the ring disabled and
        no hook, a span makes no record and no call."""
        annotate = self.annotate
        note = None if annotate is None else annotate(name)
        if note is not None:
            note.__enter__()
        sid = self.begin(name, **attrs) if self.enabled else None
        if sid is not None:
            st = self._stack()
            st.append(sid)
        try:
            yield sid
        finally:
            if sid is not None:
                if st and st[-1] == sid:
                    st.pop()
                self.end(sid)
            if note is not None:
                note.__exit__(None, None, None)

    def event(self, name: str, **attrs: Any) -> None:
        """Point-in-time record (``ph: "I"``)."""
        if not self.enabled:
            return
        st = self._stack()
        parent = attrs.pop("parent", st[-1] if st else None)
        self._record({"ts": self.clock.now(), "ph": "I", "name": name,
                      "parent": parent, **attrs})

    # ------------------------------------------------------- ring + drain

    def _record(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._buf) >= self.ring_size:
                if self.flight_dir:
                    self._drain_locked("ring_full")  # rotation
                else:
                    self._buf.popleft()  # oldest dropped, counted
                    self.dropped += 1
                    counters.inc("telemetry.dropped")
            self._buf.append(rec)

    def drain(self, reason: str = "explicit") -> Optional[str]:
        """Flush the ring to the flight-recorder file. Returns the file
        path (None when there is nothing to write or no dir configured).
        NEVER raises — telemetry fails open (docs/DESIGN.md §9)."""
        if not self.enabled:
            return None
        with self._lock:
            return self._drain_locked(reason)

    def _drain_locked(self, reason: str) -> Optional[str]:
        if not self.flight_dir or not self._buf:
            return None
        records = list(self._buf)
        self._buf.clear()  # fail open: a failed write drops, never blocks
        try:
            FAULTS.maybe_raise(
                "telemetry_sink_fail", OSError("injected telemetry_sink_fail")
            )
            path = self._flight_file_locked()
            lines = [json.dumps(rec, default=str) for rec in records]
            lines.append(json.dumps(
                {"ts": self.clock.now(), "ph": "I",
                 "name": "telemetry.drain", "n": len(records),
                 "reason": reason, "dropped": self.dropped}
            ))
            data = ("\n".join(lines) + "\n").encode()
            # ONE unbuffered append write, not a buffered loop: a SIGTERM
            # drain re-entering through the RLock mid-loop would otherwise
            # interleave its complete lines between a buffered writer's
            # partial flushes and tear a JSON line — the nested drain now
            # lands entirely before or after this block
            fd = os.open(path, os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)
            try:
                while data:
                    data = data[os.write(fd, data):]
            finally:
                os.close(fd)
            return path
        except Exception as e:
            self.sink_errors += 1
            counters.inc("telemetry.sink_errors")
            try:
                import sys

                print(f"telemetry drain failed (open): {type(e).__name__}: {e}",
                      file=sys.stderr)
            except Exception:
                pass
            return None

    def _flight_file_locked(self) -> str:
        """Per-PID JSONL path; rotates (one generation, ``.1``) past
        ``flight_max_bytes`` so a long-lived server bounds its disk use.
        ``_locked``: only called under ``_lock`` (from the drain)."""
        if self._flight_path is None:
            os.makedirs(self.flight_dir, exist_ok=True)
            self._flight_path = os.path.join(
                self.flight_dir, f"flight-{os.getpid()}.jsonl"
            )
        p = self._flight_path
        try:
            if os.path.getsize(p) > self.flight_max_bytes:
                os.replace(p, p + ".1")
        except OSError:
            pass  # no file yet
        return p

    def _atexit_drain(self) -> None:
        try:
            self.drain("atexit")
        except Exception:
            pass  # fail open, even at interpreter teardown

    # --------------------------------------------------------- exposition

    @staticmethod
    def _prom_name(name: str) -> str:
        out = []
        for ch in name:
            out.append(ch if ch.isalnum() or ch == "_" else "_")
        s = "".join(out)
        return ("_" + s) if s and s[0].isdigit() else (s or "_")

    @staticmethod
    def _prom_labels(labelset, extra: str = "") -> str:
        """Render a metrics ``LabelSet`` (plus an optional pre-rendered
        pair like ``le="..."``) as a ``{...}`` sample suffix."""
        parts = [f'{k}="{v}"' for k, v in labelset]
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}" if parts else ""

    def dump(self) -> str:
        """Prometheus-style text exposition of every counter, gauge, and
        histogram in ``utils.metrics`` plus the telemetry self-metrics.
        Labeled series (``serve.occupancy{replica="1"}`` — per-replica
        serving metrics) render as proper label'd samples sharing one
        ``# TYPE`` line per metric name."""
        lines: List[str] = []
        typed: set = set()

        def type_line(n: str, kind: str) -> None:
            if n not in typed:
                typed.add(n)
                lines.append(f"# TYPE {n} {kind}")

        for name, labelset, v in counters.series():
            n = self._prom_name(name)
            type_line(n, "counter")
            lines.append(f"{n}{self._prom_labels(labelset)} {v}")
        for name, labelset, v in gauges.series():
            n = self._prom_name(name)
            type_line(n, "gauge")
            lines.append(f"{n}{self._prom_labels(labelset)} {v:g}")
        for name, labelset, hist in histograms.series():
            n = self._prom_name(name)
            type_line(n, "histogram")
            # one atomic snapshot per histogram: buckets/_sum/_count/
            # quantiles must agree within a scrape (a concurrent
            # observe() between separate locked reads would render a
            # _count above the +Inf bucket)
            exp = hist.exposition()
            for ub, cum in exp["buckets"]:
                le = "+Inf" if ub == float("inf") else f"{ub:.6g}"
                suffix = self._prom_labels(labelset, f'le="{le}"')
                lines.append(f"{n}_bucket{suffix} {cum}")
            lines.append(
                f"{n}_sum{self._prom_labels(labelset)} {exp['sum']:.9g}"
            )
            lines.append(
                f"{n}_count{self._prom_labels(labelset)} {exp['count']}"
            )
            for q, label in ((50, "0.5"), (95, "0.95"), (99, "0.99")):
                suffix = self._prom_labels(labelset, f'quantile="{label}"')
                lines.append(f"{n}{suffix} {exp['quantiles'][q]:.9g}")
        lines.append("# TYPE telemetry_ring_dropped counter")
        lines.append(f"telemetry_ring_dropped {self.dropped}")
        lines.append("# TYPE telemetry_sink_errors counter")
        lines.append(f"telemetry_sink_errors {self.sink_errors}")
        return "\n".join(lines) + "\n"

    def serve_metrics(self, port: int) -> Optional[int]:
        """Start the /metrics daemon thread on 127.0.0.1:``port`` (0 picks
        a free port); returns the bound port. Idempotent; no-op when
        disabled. Localhost-only by design — see the security note in
        docs/DESIGN.md §9."""
        if not self.enabled:
            return None
        with self._lock:
            if self._server is not None:
                return self._server.server_address[1]
            from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

            telemetry = self

            class Handler(BaseHTTPRequestHandler):
                def do_GET(self):  # noqa: N802 (stdlib API name)
                    if self.path.rstrip("/") not in ("", "/metrics".rstrip("/")):
                        self.send_error(404)
                        return
                    body = telemetry.dump().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "text/plain; version=0.0.4"
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)

                def log_message(self, *a):  # silence per-request stderr spam
                    pass

            try:
                self._server = ThreadingHTTPServer(("127.0.0.1", port), Handler)
            except OSError as e:
                self.sink_errors += 1
                counters.inc("telemetry.sink_errors")
                import sys

                print(f"telemetry /metrics bind failed (open): {e}",
                      file=sys.stderr)
                return None
            self._server_thread = threading.Thread(
                target=self._server.serve_forever,
                name="telemetry-metrics",
                daemon=True,
            )
            self._server_thread.start()
            return self._server.server_address[1]

    def _stop_server(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            if self._server_thread is not None:
                self._server_thread.join(timeout=5)
            self._server = None
            self._server_thread = None

    def _unregister_atexit(self) -> None:
        if self._atexit_registered:
            atexit.unregister(self._atexit_drain)
            self._atexit_registered = False


# process-wide instance; env spec read once at import so CLI subprocesses
# (smoke gates, e2e tests) inherit an enabled recorder through the
# environment with zero plumbing — the FAULTS pattern
TELEMETRY = Telemetry()
if os.environ.get(ENV_ENABLE, "") not in ("", "0", "false"):
    _port: Optional[int] = None
    if os.environ.get(ENV_PORT):
        try:
            _port = int(os.environ[ENV_PORT])
        except ValueError:
            # fail open, like every other telemetry error: a typo'd port
            # must not turn package import into a crash
            import sys as _sys

            print(
                f"ignoring non-integer {ENV_PORT}="
                f"{os.environ[ENV_PORT]!r} (telemetry fails open)",
                file=_sys.stderr,
            )
    TELEMETRY.configure(
        enabled=True,
        flight_dir=os.environ.get(ENV_DIR),
        metrics_port=_port,
    )


def validate_flight_file(path: str) -> Dict[str, Any]:
    """Parse + structurally validate a flight-recorder JSONL file: every
    line must parse, every ``E`` must follow a matching ``B`` (same id).
    A rotated previous generation (``<path>.1``) is stitched in first, so
    a span whose B/E pair straddles a size-cap rotation still balances;
    an E whose B was rotated beyond the kept generation is counted under
    ``orphan_ends`` (only possible past TWO rotations), not an error.
    Returns a summary dict with ``records``, ``spans`` (closed),
    ``unclosed`` (ids still open — legitimate in a crash/preemption
    capture: they ARE the postmortem), ``orphan_ends``, and ``by_name``
    counts. Raises ValueError on structural corruption. Shared by
    tools/telemetry_smoke.py and the tests."""
    prev = path + ".1"
    streams = [prev, path] if os.path.exists(prev) else [path]
    rotated = len(streams) > 1
    open_spans: Dict[int, Dict[str, Any]] = {}
    closed = 0
    records = 0
    orphan_ends = 0
    by_name: Dict[str, int] = {}
    for fpath in streams:
        with open(fpath) as f:
            for lineno, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError as e:
                    raise ValueError(f"{fpath}:{lineno}: bad JSON: {e}")
                records += 1
                ph = rec.get("ph")
                ts = rec.get("ts")
                if ph not in ("B", "E", "I") or not isinstance(ts, (int, float)):
                    raise ValueError(f"{fpath}:{lineno}: malformed record {rec}")
                if "name" in rec:
                    by_name[rec["name"]] = by_name.get(rec["name"], 0) + 1
                if ph == "B":
                    open_spans[rec["id"]] = rec
                elif ph == "E":
                    if rec["id"] in open_spans:
                        open_spans.pop(rec["id"])
                        closed += 1
                    elif rotated:
                        orphan_ends += 1  # its B fell off the .1 horizon
                    else:
                        raise ValueError(
                            f"{fpath}:{lineno}: E without B for id {rec['id']}"
                        )
    return {
        "records": records,
        "spans": closed,
        "unclosed": sorted(open_spans),
        "unclosed_records": list(open_spans.values()),
        "orphan_ends": orphan_ends,
        "by_name": by_name,
    }
