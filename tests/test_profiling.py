"""The program's own names on the profiler's clock (ISSUE 30,
docs/DESIGN.md §9):

- the bridge: ``Telemetry.annotate`` is entered and exited once around every
  lexical ``span()``, nested in order, whether or not the ring is enabled;
  never for ``begin``/``end``; with no hook and no ring a span makes no
  record and no call; ``utils/profiling.py`` sets the process-wide hook to
  ``jax.profiler.TraceAnnotation``;
- the engine: ``Engine.step`` is one ``serve.step`` span whose phases are its
  lexical children, in split, fused and speculative modes, and
  ``serve.tokens_committed`` counts exactly the tokens in ``results`` plus the
  live ``entry.generated``;
- the device names: every scope of ``DEVICE_SCOPES`` that applies appears in
  the lowered train step and serving jits, each ``attn.<kind>`` exactly for
  the layers of that kind;
- the trainer's step-window capture opens and closes once, waiting for the
  device on both edges.
"""

import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import DALLE, DiscreteVAE
from dalle_pytorch_tpu.serving import Engine, EngineConfig, FakeClock, Request
from dalle_pytorch_tpu.utils import profiling
from dalle_pytorch_tpu.utils.telemetry import TELEMETRY, Telemetry
from dalle_pytorch_tpu.utils.telemetry_names import DEVICE_SCOPES, SPANS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Recorder:
    """A fake ``annotate``: ``log`` holds ("enter" | "exit", name) in order."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class Note:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return Note()


def nesting(log):
    """Replay a Recorder log: [(name, parent name or None)] for every span,
    asserting that each exit closes the innermost open span."""
    stack, out = [], []
    for what, name in log:
        if what == "enter":
            out.append((name, stack[-1] if stack else None))
            stack.append(name)
        else:
            assert stack and stack[-1] == name, (name, stack)
            stack.pop()
    assert not stack, stack
    return out


# ----------------------------------------------------------------- bridge


class TestBridge:
    def test_hook_entered_and_exited_once_per_span_nested_in_order(self):
        t, rec = Telemetry(clock=FakeClock()), Recorder()
        t.annotate = rec
        t.configure(enabled=True)
        with t.span("serve.step"):
            with t.span("serve.step.sweep"):
                pass
            with t.span("serve.step.admit"):
                pass
        assert rec.log == [
            ("enter", "serve.step"),
            ("enter", "serve.step.sweep"), ("exit", "serve.step.sweep"),
            ("enter", "serve.step.admit"), ("exit", "serve.step.admit"),
            ("exit", "serve.step"),
        ]
        # the ring recorded the same three spans, B and E each
        assert [r["ph"] for r in t._buf].count("B") == 3

    def test_hook_is_entered_with_the_ring_disabled(self):
        t, rec = Telemetry(), Recorder()
        t.annotate = rec
        with t.span("serve.step") as sid:
            assert sid is None
        assert rec.log == [("enter", "serve.step"), ("exit", "serve.step")]
        assert not t._buf and not t._open

    def test_begin_end_are_ring_only(self):
        t, rec = Telemetry(clock=FakeClock()), Recorder()
        t.annotate = rec
        t.configure(enabled=True)
        t.end(t.begin("serve.request", request_id="r0"), outcome="completed")
        t.event("serve.admit")
        assert rec.log == []
        assert [r["ph"] for r in t._buf] == ["B", "E", "I"]

    def test_no_hook_and_no_ring_is_no_record_and_no_call(self, monkeypatch):
        t = Telemetry()
        assert t.annotate is None and not t.enabled

        def boom(*a, **k):
            raise AssertionError("a disabled span() touched the ring")

        for method in ("begin", "end", "_record", "_stack"):
            monkeypatch.setattr(t, method, boom)
        with t.span("serve.step", attr=1) as sid:
            assert sid is None
        assert not t._buf

    def test_hook_exits_when_the_block_raises(self):
        t, rec = Telemetry(), Recorder()
        t.annotate = rec
        with pytest.raises(KeyError):
            with t.span("serve.step"):
                raise KeyError("x")
        assert rec.log == [("enter", "serve.step"), ("exit", "serve.step")]

    def test_profiling_bridges_the_process_wide_instance(self):
        assert profiling.TELEMETRY is TELEMETRY
        assert TELEMETRY.annotate is jax.profiler.TraceAnnotation
        TELEMETRY.reset()   # wiring, not state: a reset leaves it
        assert TELEMETRY.annotate is jax.profiler.TraceAnnotation


# ----------------------------------------------------------------- engine


@pytest.fixture(scope="module")
def model():
    dalle = DALLE(
        dim=32, depth=2, num_text_tokens=16, text_seq_len=4,
        num_image_tokens=12, image_fmap_size=2, heads=2, dim_head=8,
        attn_types=("full",), shift_tokens=True, rotary_emb=True,
    )
    rng = np.random.RandomState(0)
    text = jnp.asarray(rng.randint(1, 16, size=(2, 4)), jnp.int32)
    image = jnp.asarray(rng.randint(0, 12, size=(2, 4)), jnp.int32)
    return dalle, dalle.init(jax.random.key(0), text, image)["params"]


@pytest.fixture
def recorder():
    rec, before = Recorder(), TELEMETRY.annotate
    TELEMETRY.annotate = rec
    yield rec
    TELEMETRY.annotate = before


MODES = {
    "split": dict(prefill_chunk=2),
    "fused": dict(prefill_chunk=2, fused_iteration=True),
    "spec": dict(prefill_chunk=2, fused_iteration=True, spec_decode=True, spec_k=2),
}
EVERY_STEP = ("serve.step.sweep", "serve.step.admit", "serve.step.plan",
              "serve.step.publish")


def live_tokens(eng):
    done = sum(len(r.tokens) for r in eng.results.values() if r.tokens is not None)
    return done + sum(len(s.entry.generated) for s in eng.slots if s)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_engine_step_is_one_span_with_its_phases_inside(mode, model, recorder):
    dalle, params = model
    eng = Engine(dalle, params, EngineConfig(max_batch=2, **MODES[mode]),
                 clock=FakeClock(step_dt=1.0))
    rng = np.random.RandomState(3)
    for i in range(3):
        eng.submit(Request(
            request_id=f"r{i}", prompt=rng.randint(1, 16, size=(4,)).astype(np.int32),
            max_new_tokens=dalle.image_seq_len, seed=10 + i,
        ))
    assert not TELEMETRY.enabled    # the ring is off: the hook alone is driven
    totals, whole = {}, 0
    for _ in range(200):
        start = len(recorder.log)
        more = eng.step()
        spans = nesting(recorder.log[start:])
        assert spans[0] == ("serve.step", None)
        assert [n for n, _ in spans].count("serve.step") == 1
        names = [n for n, _ in spans]
        assert all(n in SPANS for n in names), set(names) - SPANS
        for phase in EVERY_STEP:
            assert names.count(phase) == 1, (phase, names)
        for phase in ("serve.step.fold_keys", "serve.step.dispatch",
                      "serve.step.readback", "serve.step.stages"):
            assert names.count(phase) <= 1, (phase, names)
        assert names.count("serve.step.fold_keys") == names.count("serve.step.dispatch")
        if {"serve.step.fold_keys", "serve.step.dispatch", "serve.step.readback"} <= set(names):
            whole += 1
        for n in names:
            totals[n] = totals.get(n, 0) + 1
        # the one place tokens are tallied agrees with the tokens themselves
        assert eng.stats()["tokens_committed"] == live_tokens(eng)
        if not more:
            break
    assert not more and len(eng.results) == 3
    assert whole >= 1, totals
    assert totals["serve.step.release"] == 3          # one per finished request
    assert totals["serve.step.dispatch"] + totals.get("serve.prefill_chunk", 0) == eng.dispatches
    assert eng.stats()["tokens_committed"] == 3 * dalle.image_seq_len


# ----------------------------------------------------------- device names


def _registry():
    sys.path[:0] = [p for p in (REPO, os.path.join(REPO, "tools")) if p not in sys.path]
    spec = importlib.util.spec_from_file_location(
        "_trace_registry_for_names", os.path.join(REPO, "tools/lint/trace/registry.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def scope_paths(lowered):
    """Every name-stack path of a lowered program's operations."""
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def has(path, scope):
    return re.search(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)", path) is not None


KINDS = ("full", "axial_row", "axial_col", "conv_like")


def test_train_step_carries_every_scope_and_each_kind_on_its_own_layers():
    reg = _registry()
    dalle = DALLE(**dict(reg.CANON_MODEL, depth=8, attn_types=KINDS, rotary_emb=False))
    entry = reg._train_entry(dalle, 2)
    paths = scope_paths(entry.lower(*entry.signatures[0].args))
    for scope in ("embed", "head_loss", "ff", "update", "update.optimizer",
                  "update.nan_guard", *(f"attn.{k}" for k in KINDS)):
        assert scope in DEVICE_SCOPES
        assert any(has(p, scope) for p in paths), scope
    for i in range(dalle.depth):
        mine = {p for p in paths if has(p, f"attn_{i}")}
        assert mine, i
        kind = KINDS[i % len(KINDS)]
        assert all(has(p, f"attn.{kind}") for p in mine), (i, kind)
        assert all(has(p, "ff") for p in paths if has(p, f"ff_{i}"))
    # forward and backward alike
    assert any(has(p, "attn.axial_row") and "transpose(" in p for p in paths)


def test_serving_jits_carry_embed_head_and_sample_scopes():
    entries = {e.name: e for e in _registry().build_entry_points()}
    for name in ("serving.prefill", "serving.decode", "serving.iteration",
                 "serving.iteration_spec", "serving.prefill_last"):
        e = entries[name]
        paths = scope_paths(e.lower(*e.signatures[0].args))
        for scope in ("embed", "attn.full", "ff", "head_loss", "sample"):
            assert any(has(p, scope) for p in paths), (name, scope)
    cached = entries["serving.sample_cached"]
    assert any(has(p, "sample") for p in scope_paths(cached.lower(*cached.signatures[0].args)))


def test_vae_encode_scope():
    vae = DiscreteVAE(image_size=8, num_layers=1, num_tokens=12, codebook_dim=8, hidden_dim=8)
    img = jnp.zeros((1, 8, 8, 3))
    key = jax.random.key(0)
    variables = jax.eval_shape(lambda: vae.init({"params": key, "gumbel": key}, img))
    encode = jax.jit(lambda v, x: vae.apply(v, x, method="get_codebook_indices"))
    assert any(has(p, "vae.encode") for p in scope_paths(encode.lower(variables, img)))


# ------------------------------------------------------ step-window capture


def test_step_capture_opens_and_closes_once(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop",)))
    monkeypatch.setattr(jax, "block_until_ready", lambda x: calls.append(("wait", x)))
    cap = profiling.StepCapture("/tmp/trace", first_step=5)
    closed = [cap.at_step(step, f"state{step}") for step in range(12)]
    assert closed == [False] * 8 + [True] + [False] * 3
    assert calls == [("wait", "state5"), ("start", "/tmp/trace"),
                     ("wait", "state8"), ("stop",)]
    cap.close("late")              # nothing open: nothing happens
    assert len(calls) == 4

    off = profiling.StepCapture(None, first_step=0)
    assert not any(off.at_step(s, None) for s in range(5)) and len(calls) == 4

    cut = profiling.StepCapture("/tmp/trace", first_step=1)
    cut.at_step(1, "s1")
    cut.close()                    # preempted inside the window: no wait
    assert calls[-2:] == [("start", "/tmp/trace"), ("stop",)]
