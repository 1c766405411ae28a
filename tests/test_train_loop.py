"""The training loop's dispatch/verdict/retry policy (parallel/loop.py),
driven by a SCRIPTED step: no model, no compile. The step returns a given
verdict sequence and records what it was handed per dispatch, and every fetch
and every loss read is logged in order, so each clause of the policy that
train_dalle.py and train_lm.py share is pinned on the program's own loop.
(The bit-identical recovery on a real compiled step: tests/test_resilience.py.)
"""

import json
import math
from pathlib import Path
from typing import NamedTuple

import jax
import pytest

from dalle_pytorch_tpu.parallel import TrainLoop
from dalle_pytorch_tpu.utils import TELEMETRY, counters
from dalle_pytorch_tpu.utils.telemetry import validate_flight_file

REPO = Path(__file__).resolve().parent.parent


class _State(NamedTuple):
    step: int = 0
    skipped: int = 0
    consec_skipped: int = 0


class _Loss:
    """A loss 'on the device': reading it is the sync point, and is logged."""

    def __init__(self, value, tag, events):
        self.value, self.tag, self.events = value, tag, events

    def __float__(self):
        self.events.append(("read", self.tag))
        return self.value


class _Script:
    """``verdicts[k]`` decides dispatch k (True: applied, loss k + 0.5;
    False: rejected, NaN); past its end every step is applied."""

    def __init__(self, verdicts=()):
        self.verdicts = list(verdicts)
        self.events = []  # ("fetch" | "feed" | "dispatch" | "read", batch tag)
        self.dispatched = []  # (batch tag, rng key data, lr) per dispatch

    def step_fn(self, state, batch, rng, lr):
        k = len(self.dispatched)
        finite = self.verdicts[k] if k < len(self.verdicts) else True
        tag = int(batch["ids"] if "ids" in batch else batch["text"])
        self.dispatched.append(
            (tag, tuple(jax.random.key_data(rng).tolist()), float(lr))
        )
        self.events.append(("dispatch", tag))
        state = _State(
            step=state.step + 1,
            skipped=state.skipped + (not finite),
            consec_skipped=0 if finite else state.consec_skipped + 1,
        )
        return state, _Loss(k + 0.5 if finite else math.nan, tag, self.events)

    def batches(self, n, make):
        for i in range(n):
            self.events.append(("fetch", i))
            yield make(i)

    def tags(self):
        return [d[0] for d in self.dispatched]


def _key(n):
    return tuple(jax.random.key_data(jax.random.key(n)).tolist())


# the two callers' feeds: train_dalle.py encodes the image and passes the text
# on; train_lm.py hands the packed ids over
FEEDS = {
    "image_text": (
        lambda i: {"text": i, "image": f"pixels{i}"},
        lambda b: {"text": b["text"], "image": f"tokens({b['image']})"},
    ),
    "ids": (lambda i: {"ids": i}, lambda b: {"ids": b["ids"]}),
}


def _loop(script, feed, state=_State(), **kw):
    def logged_feed(batch):
        out = feed(batch)
        script.events.append(("feed", out.get("ids", out.get("text"))))
        return out

    kw.setdefault("lr", 0.5)
    kw.setdefault("nan_abort_after", 5)
    kw.setdefault("log", lambda line: None)
    return TrainLoop(script.step_fn, state, feed=logged_feed, **kw)


@pytest.fixture(params=sorted(FEEDS))
def feeds(request):
    return FEEDS[request.param]


class TestContract:
    """The five clauses both CLIs rely on, over both feeds."""

    def test_all_finite_dispatches_each_batch_once_in_order(self, feeds):
        make, feed = feeds
        s = _Script()
        loop = _loop(s, feed)
        seen = [d.index for d in loop.epoch(0, s.batches(4, make))]
        assert seen == s.tags() == [0, 1, 2, 3]
        assert [d[1] for d in s.dispatched] == [_key(k) for k in range(4)]
        assert loop.global_step == 4 and loop.applied_steps == 4
        assert loop.state.step == 4

    def test_rejected_batch_refed_next_same_key_stashed_follows(self, feeds):
        make, feed = feeds
        s = _Script([True, False, True])
        loop = _loop(s, feed)
        seen = [d.index for d in loop.epoch(0, s.batches(4, make))]
        # batch 1 rejected, fed again at once; batch 2 (fetched while 1 was
        # in flight) follows: none lost, none doubled
        assert seen == s.tags() == [0, 1, 1, 2, 3]
        assert [d[1] for d in s.dispatched] == [
            _key(0), _key(1), _key(1), _key(2), _key(3),
        ]
        assert [e[1] for e in s.events if e[0] == "fetch"] == [0, 1, 2, 3]
        # the retried batch goes through feed again (the image is re-encoded)
        assert [e[1] for e in s.events if e[0] == "feed"] == [0, 1, 1, 2, 3]

    def test_rejection_of_last_batch_is_retried_before_epoch_ends(self, feeds):
        make, feed = feeds
        s = _Script([True, True, False])
        loop = _loop(s, feed)
        seen = [d.index for d in loop.epoch(0, s.batches(3, make))]
        assert seen == [0, 1, 2, 2]
        assert s.dispatched[-1][1] == s.dispatched[-2][1] == _key(2)
        assert loop.applied_steps == 3 and loop.state.skipped == 1

    def test_applied_steps_and_lr_advance_on_finite_verdicts_only(self, feeds):
        make, feed = feeds
        s = _Script([True, False, False, True])
        stepped = []

        def on_applied(loss):
            stepped.append(loss)
            return 0.5 ** (len(stepped) + 1)

        loop = _loop(s, feed, on_applied=on_applied)
        for _ in loop.epoch(0, s.batches(3, make)):
            pass
        # dispatches: 0 ok, 1 nan, 1 nan, 1 ok, 2 ok
        assert s.tags() == [0, 1, 1, 1, 2]
        assert stepped == [0.5, 3.5, 4.5]  # the scheduler saw applied losses only
        assert loop.applied_steps == 3 and loop.global_step == 5
        assert loop.lr == 0.0625
        # the step is handed the lr of the verdicts BEFORE it: both retries of
        # batch 1 run under the lr batch 0's loss set
        assert [d[2] for d in s.dispatched] == [0.5, 0.25, 0.25, 0.25, 0.125]

    def test_resolve_reports_a_rejected_batch_as_unconsumed(self, feeds):
        make, feed = feeds
        s = _Script([True, False, True])
        loop = _loop(s, feed)
        reported = []
        for d in loop.epoch(0, s.batches(3, make)):
            reported.append((d.index, loop.resolve()))
        # batch 1's first dispatch was rejected: a save then records batch 0
        # as the last consumed one, and the resume replays batch 1
        assert reported == [(0, 0), (1, 0), (1, 1), (2, 2)]
        assert s.tags() == [0, 1, 1, 2]  # resolving early changes no choice


def test_next_batch_is_fetched_before_the_verdict_is_read():
    make, feed = FEEDS["ids"]
    s = _Script()
    for _ in _loop(s, feed).epoch(0, s.batches(3, make)):
        pass
    assert s.events == [
        ("fetch", 0), ("feed", 0), ("dispatch", 0),
        ("fetch", 1), ("read", 0), ("feed", 1), ("dispatch", 1),
        ("fetch", 2), ("read", 1), ("feed", 2), ("dispatch", 2),
        ("read", 2),  # the last verdict is read before the epoch may end
    ]


def test_one_dispatch_in_flight_across_epochs():
    """An epoch returns with no verdict pending, so the caller's end-of-epoch
    save needs no resolve()."""
    make, feed = FEEDS["ids"]
    s = _Script([True, False])
    loop = _loop(s, feed)
    for epoch in range(2):
        for _ in loop.epoch(epoch, s.batches(2, make)):
            pass
        assert s.events[-1][0] == "read"
    assert s.tags() == [0, 1, 1, 0, 1]
    assert [d[1] for d in s.dispatched] == [_key(k) for k in (0, 1, 1, 2, 3)]


@pytest.mark.parametrize("note", ["state saved for post-mortem at out-cp", None])
def test_abort_at_the_limit_drains_then_calls_the_hook_once(tmp_path, note):
    make, feed = FEEDS["ids"]
    TELEMETRY.configure(enabled=True, flight_dir=str(tmp_path))
    s = _Script([True, True, False, False, False])
    hooked, logged = [], []

    def on_abort(index):
        # the flight recorder is on disk BEFORE the emergency hook runs
        flight = list(tmp_path.glob("flight-*.jsonl"))
        hooked.append((index, bool(flight) and "train.nan_abort" in flight[0].read_text()))
        return note

    loop = _loop(s, feed, nan_abort_after=3, log=logged.append, on_abort=on_abort)
    with pytest.raises(SystemExit) as exit_:
        for _ in loop.epoch(0, s.batches(5, make)):
            pass
    want = "3 consecutive non-finite steps — aborting"
    assert str(exit_.value) == (f"{want} ({note})" if note else want)
    # batch 2 was rejected three times; its predecessor is the last consumed
    assert s.tags() == [0, 1, 2, 2, 2] and hooked == [(1, True)]
    # the first finite verdict logs what set-up cost, once (the compile ledger)
    assert logged[0].startswith("first step verdict ") and "compiled afresh" in logged[0]
    assert logged[1:] == [
        f"step {k}: non-finite loss — update skipped on device, "
        f"retrying batch ({n}/3)"
        for k, n in ((2, 1), (3, 2), (4, 3))
    ]


def test_consecutive_count_is_the_states_own():
    """Two rejections before a resume plus one after reach a limit of 3: the
    count is read from ``state.consec_skipped``, not kept by the host."""
    make, feed = FEEDS["ids"]
    s = _Script([False])
    resumed = _State(step=7, skipped=2, consec_skipped=2)
    loop = _loop(s, feed, state=resumed, nan_abort_after=3, global_step=7)
    assert loop.applied_steps == 5  # keys go on from the applied updates
    with pytest.raises(SystemExit, match="^3 consecutive non-finite steps"):
        for _ in loop.epoch(0, s.batches(2, make)):
            pass
    assert s.dispatched[0][1] == _key(5)


def test_no_abort_hook_is_needed():
    make, feed = FEEDS["ids"]
    s = _Script([False])
    with pytest.raises(SystemExit, match="^1 consecutive non-finite steps — aborting$"):
        for _ in _loop(s, feed, nan_abort_after=1).epoch(0, s.batches(1, make)):
            pass


def test_resume_skips_consumed_batches_in_the_resume_epoch_only():
    make, feed = FEEDS["ids"]
    s = _Script()
    loop = _loop(s, feed, resume=(1, 2), global_step=7, state=_State(step=7))
    for epoch in (1, 2):
        seen = [d.index for d in loop.epoch(epoch, s.batches(5, make))]
        assert seen == ([3, 4] if epoch == 1 else [0, 1, 2, 3, 4])
    assert s.dispatched[0][1] == _key(7)
    # an epoch whose every batch was consumed dispatches nothing
    assert list(_loop(_Script(), feed, resume=(0, 9)).epoch(0, s.batches(3, make))) == []


def _flight(tmp_path):
    (path,) = tmp_path.glob("flight-*.jsonl")
    return [json.loads(line) for line in path.read_text().splitlines()], str(path)


def test_step_span_closes_at_the_verdict_and_data_wait_balances(tmp_path):
    make, feed = FEEDS["ids"]
    TELEMETRY.configure(enabled=True, flight_dir=str(tmp_path))
    s = _Script([True, False, True])
    for _ in _loop(s, feed, global_step=10, state=_State(step=10)).epoch(3, s.batches(3, make)):
        pass
    TELEMETRY.drain("test")
    records, path = _flight(tmp_path)
    summary = validate_flight_file(path)
    assert summary["unclosed"] == []
    # 3 fetches and the one that found the loader exhausted
    assert summary["by_name"]["train.data_wait"] == 2 * 4
    begins = [r for r in records if r.get("name") == "train.step" and r["ph"] == "B"]
    ends = [r for r in records if r.get("name") == "train.step" and r["ph"] == "E"]
    assert [(r["step"], r["epoch"]) for r in begins] == [(10 + k, 3) for k in range(4)]
    assert [r["finite"] for r in ends] == [True, False, True, True]
    assert [r["loss"] for r in ends if r["finite"]] == [0.5, 2.5, 3.5]
    assert math.isnan(ends[1]["loss"])


def test_rejection_is_counted_and_recorded_under_the_registered_names(tmp_path):
    make, feed = FEEDS["ids"]
    TELEMETRY.configure(enabled=True, flight_dir=str(tmp_path))
    s = _Script([True, False, False])
    for _ in _loop(s, feed).epoch(0, s.batches(2, make)):
        pass
    TELEMETRY.drain("test")
    records, _ = _flight(tmp_path)
    skips = [r for r in records if r.get("name") == "train.nan_skip"]
    assert [(r["step"], r["consec"]) for r in skips] == [(1, 1), (2, 2)]
    assert counters.get("train.nan_skips") == 2
    assert not [r for r in records if r.get("name") == "train.nan_abort"]


def test_preemption_mid_epoch_resolves_the_verdict_then_stops(tmp_path):
    """What both CLIs do on a raised flag: resolve, save what resolve()
    reports, leave. Nothing is left open or pending behind them."""
    make, feed = FEEDS["ids"]
    TELEMETRY.configure(enabled=True, flight_dir=str(tmp_path))
    s = _Script([True, False])
    loop = _loop(s, feed)
    saved = None
    steps = loop.epoch(0, s.batches(4, make))
    for d in steps:
        if d.index == 1:  # the flag is seen after batch 1's dispatch
            saved = loop.resolve()
            break
    steps.close()
    assert saved == 0  # batch 1 was rejected: the relaunch replays it
    assert s.events[-1] == ("read", 1) and s.tags() == [0, 1]
    assert loop.resolve() == 0  # nothing pending: asking again reads nothing
    assert s.events.count(("read", 1)) == 1
    TELEMETRY.drain("test")
    assert validate_flight_file(_flight(tmp_path)[1])["unclosed"] == []


@pytest.mark.parametrize("cli", ["train_dalle.py", "train_lm.py"])
def test_cli_drives_the_library_loop(cli):
    src = (REPO / cli).read_text()
    assert "process_verdict" not in src and "while True" not in src
    assert "TrainLoop" in src and ".epoch(epoch, loader)" in src
