"""The host half of the train step's NaN guard: which batch goes next.

``make_train_step`` (step.py) rejects a non-finite update ON the device and
hands back NaN as the loss. What follows from that verdict is policy, and it
lives here once, for every training CLI:

* one dispatch in flight. The next batch is fetched BEFORE the in-flight
  step's loss is read, so host batch preparation overlaps the device; the read
  itself is a true sync point, because step N's outcome chooses step N+1's
  input.
* a rejected batch is fed again, first, under the SAME rng key (keys count
  applied updates, not dispatch attempts), and the batch fetched meanwhile
  stays stashed: a recovered run applies the update sequence of an unfaulted
  one, bit for bit (tests/test_resilience.py).
* the run of consecutive rejections is the device's counter
  (``state.consec_skipped``: it includes skips from before a resume); at
  ``nan_abort_after`` the flight recorder is drained, the caller's emergency
  hook runs, and the process exits.
* the first finite verdict logs, once a loop, what set-up cost: seconds
  traced, lowered, loaded or compiled, the cache's hits, the programs compiled
  afresh and the costliest one (``utils/profiling.py:COMPILE_LEDGER``).
* ``resolve()`` reads the in-flight verdict on demand, so what a caller saves
  (scheduler state, the index of the last consumed batch) includes it.

What a batch becomes, the learning-rate schedule, logging, profiling, saving,
sampling and the reaction to a preemption signal are the caller's, done in the
body of its ``for`` over ``epoch()`` or passed in as plain callables.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Iterable, Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..utils import TELEMETRY, counters
from ..utils.profiling import COMPILE_LEDGER


class Dispatch(NamedTuple):
    """One step handed to the device; its verdict is still pending."""

    index: int  # the batch's position in its epoch
    batch: Any  # what ``feed`` made of it, as the step received it
    loss: Any  # the step's loss, on the device (NaN: the update was rejected)


class TrainLoop:
    """Drives ``step_fn(state, feed(batch), rng, lr) -> (state, loss)``.

    ``state`` (a ``TrainState``), ``global_step`` (dispatch attempts),
    ``applied_steps`` (finite verdicts) and ``lr`` are public and current
    whenever the caller's code runs.

    ``on_applied(loss) -> lr`` is called once per finite verdict (a plateau
    scheduler's ``step``). ``on_abort(index)`` is the emergency hook of the
    consecutive-rejection abort: ``index`` is the last batch whose update IS
    in ``state`` (the rejected batch's predecessor), and what it returns, if
    anything, is appended to the exit message. ``resume=(epoch, index)``
    skips the batches up to ``index`` of that epoch, consumed before a
    preemption.
    """

    def __init__(
        self,
        step_fn: Callable[..., tuple],
        state: Any,
        *,
        feed: Callable[[Any], Any],
        lr: float,
        nan_abort_after: int,
        log: Callable[[str], None],
        on_applied: Optional[Callable[[float], float]] = None,
        on_abort: Optional[Callable[[int], Optional[str]]] = None,
        global_step: int = 0,
        resume: tuple[int, int] = (-1, -1),
    ):
        self.state = state
        self.lr = lr
        self.global_step = global_step
        # keys the step rng by BATCH, not by dispatch attempt: a batch retried
        # after a rejection reuses its key
        self.applied_steps = global_step - int(state.skipped)
        self._step_fn = step_fn
        self._feed = feed
        self._nan_abort_after = nan_abort_after
        self._log = log
        self._on_applied = on_applied
        self._on_abort = on_abort
        self._resume = resume
        self._loss = None  # the in-flight step's loss: its verdict is pending
        self._span = None  # the open train.step span (dispatch -> verdict)
        self._last_fed = None  # (index, batch as fetched) of the last dispatch
        self._refeed = False  # its update was rejected: it goes again, first
        self._set_up_logged = False  # what set-up cost, at the first finite verdict

    def epoch(self, epoch: int, batches: Iterable) -> Iterator[Dispatch]:
        """Dispatch every batch of one epoch, yielding after each dispatch;
        the epoch ends only when the last verdict asked for no retry."""
        batches = enumerate(batches)
        nxt, exhausted = None, False
        while True:
            if nxt is None and not exhausted:
                # host-side stall on the data path: the data-wait vs step
                # split of the percentile histograms (docs/DESIGN.md §9)
                with TELEMETRY.span("train.data_wait", epoch=epoch):
                    for cand in batches:
                        if epoch == self._resume[0] and cand[0] <= self._resume[1]:
                            continue  # consumed before the preemption
                        nxt = cand
                        break
                    else:
                        exhausted = True
            self._verdict()
            if self._refeed:
                fed, self._refeed = self._last_fed, False  # a fetched nxt stays stashed
            elif nxt is not None:
                fed, nxt = nxt, None
            else:
                return
            self._last_fed = fed
            # train.step runs from dispatch (feed included) to the VERDICT,
            # so its histogram is the real step latency, device included
            self._span = TELEMETRY.begin(
                "train.step", step=self.global_step, epoch=epoch,
            )
            batch = self._feed(fed[1])
            self.state, self._loss = self._step_fn(
                self.state, batch, jax.random.key(self.applied_steps),
                jnp.asarray(self.lr),
            )
            yield Dispatch(fed[0], batch, self._loss)
            self.global_step += 1

    def resolve(self) -> int:
        """Read the in-flight verdict now (before a save, on preemption).
        -> the index of the last batch of this epoch whose update is in
        ``state``: a just-rejected batch does not count, a resume replays it."""
        self._verdict()
        return self._last_fed[0] - self._refeed

    def _verdict(self) -> None:
        if self._loss is None:
            return
        loss = float(self._loss)  # waits for the step
        self._loss = None
        finite = math.isfinite(loss)
        TELEMETRY.end(self._span, loss=loss, finite=finite)
        self._span = None
        if finite:
            self.applied_steps += 1
            if not self._set_up_logged:
                # the operator's answer to "why did this job take two minutes
                # to its first step" (docs/DESIGN.md §9)
                self._set_up_logged = True
                line = COMPILE_LEDGER.first_step_line()
                if line is not None:
                    self._log(line)
            if self._on_applied is not None:
                self.lr = self._on_applied(loss)
            return
        consec = int(self.state.consec_skipped)
        step = self.global_step - 1
        counters.inc("train.nan_skips")
        TELEMETRY.event("train.nan_skip", step=step, consec=consec)
        self._log(
            f"step {step}: non-finite loss — update skipped on device, "
            f"retrying batch ({consec}/{self._nan_abort_after})"
        )
        if consec >= self._nan_abort_after:
            # drain BEFORE the emergency hook: the postmortem must reach disk
            # even if a save hangs
            TELEMETRY.event("train.nan_abort", step=step, consec=consec)
            TELEMETRY.drain("nan_abort")
            note = self._on_abort(self._last_fed[0] - 1) if self._on_abort else None
            raise SystemExit(
                f"{consec} consecutive non-finite steps — aborting"
                + (f" ({note})" if note else "")
            )
        self._refeed = True
