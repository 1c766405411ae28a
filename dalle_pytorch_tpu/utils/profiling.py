"""The jax side of the telemetry layer: the program's spans on the
profiler's clock, and the trainer's step-window capture.

``utils/telemetry.py`` is host-only by contract (DTL021) and keeps its own
monotonic clock. Importing THIS module (``serving/``, ``parallel/step.py``
and the CLIs do) sets ``TELEMETRY.annotate`` to
``jax.profiler.TraceAnnotation``, so every lexical ``TELEMETRY.span()`` also
opens a host annotation. Outside a capture that is a flag test; inside one —
started by the benchmark, ``--profile_trace_dir`` or an operator's
``jax.profiler.start_trace`` — the span lands in the same ``.xplane.pb`` as
the device events, on their clock, with no switch to flip
(docs/DESIGN.md §9).
"""

from __future__ import annotations

from typing import Any, Optional

import jax

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
