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
  device on both edges;
- the compile ledger (ISSUE 40): one listener pair however often it is
  installed; ``trace``, ``lower`` and ``backend`` of a named program with its
  ``fun_name``; the persistent cache's hit, load and miss under the name of
  the request they fired inside; a jit traced inside another's trace is kept
  and summed once; nothing recorded by calls of a compiled function; the cap
  drops the oldest and counts them; ``compile.request`` reaches a flight file.
"""

import importlib.util
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import DALLE, DiscreteVAE
from dalle_pytorch_tpu.serving import Engine, EngineConfig, FakeClock, Request
from dalle_pytorch_tpu.utils import profiling
from dalle_pytorch_tpu.compile_cache import enable_compile_cache
from dalle_pytorch_tpu.utils.metrics import counters, gauges, histograms
from dalle_pytorch_tpu.utils.profiling import (
    COMPILE_LEDGER,
    CompileLedger,
    CompileRecord,
    summarize,
)
from dalle_pytorch_tpu.utils.telemetry import TELEMETRY, Telemetry, validate_flight_file
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


# ------------------------------------------------------------ compile ledger

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


def _since(mark, name=None):
    """The process-wide ledger's records that ended after the time ``mark``
    (a worker's ledger is at its cap, so not after an index), of the program
    ``name`` if given."""
    return [r for r in COMPILE_LEDGER.records() if r.end >= mark
            and name in (None, profiling.program_of(r.fun_name))]


def test_enable_compile_cache_installs_one_listener_pair():
    import jax._src.monitoring as monitoring

    enable_compile_cache()
    pair = (len(monitoring.get_event_duration_listeners()), len(monitoring.get_event_listeners()))
    installed_at = COMPILE_LEDGER.installed_at
    enable_compile_cache()
    COMPILE_LEDGER.install()
    assert (len(monitoring.get_event_duration_listeners()),
            len(monitoring.get_event_listeners())) == pair
    assert COMPILE_LEDGER.installed_at == installed_at <= time.monotonic()
    mine = [f for f in monitoring.get_event_duration_listeners()
            if getattr(f, "__self__", None) is COMPILE_LEDGER]
    assert len(mine) == 1


def test_a_named_program_is_traced_lowered_and_requested_once_each():
    mark = before = time.monotonic()

    @jax.jit
    def ledger_probe_named(x):
        return jnp.tanh(x) @ x

    ledger_probe_named(jnp.ones((8, 8))).block_until_ready()
    after = time.monotonic()
    got = _since(mark, "ledger_probe_named")
    by_kind = {r.kind: r for r in got}
    assert sorted(r.kind for r in got if r.seconds) == ["backend", "lower", "trace"]
    assert by_kind["trace"].fun_name == "ledger_probe_named"
    assert by_kind["lower"].fun_name == by_kind["backend"].fun_name == "jit(ledger_probe_named)"
    for r in got:
        assert before <= r.start <= r.end <= after and abs((r.end - r.start) - r.seconds) < 1e-9
    assert by_kind["trace"].end <= by_kind["lower"].end <= by_kind["backend"].start + 1e-3
    assert counters.get("compile.requests") >= 1
    assert histograms.get("compile.trace_s").snapshot()["count"] >= 1

    # a hundred calls of the compiled function are a hundred cache lookups in C++
    x = jnp.ones((8, 8))
    x.block_until_ready()
    mark = time.monotonic()
    for _ in range(100):
        x = ledger_probe_named(x)
    x.block_until_ready()
    assert _since(mark) == []


def test_the_persistent_caches_events_take_the_requests_name(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    def probe():
        @jax.jit
        def ledger_probe_cached(x):
            return jnp.cos(x) * 3.0 + x

        return ledger_probe_cached

    from dalle_pytorch_tpu.compile_cache import ENV_VAR

    directory = ENV_VAR.lower()      # the option the variable places
    keep = {k: getattr(jax.config, k) for k in (
        directory, "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update(directory, str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        mark = time.monotonic()
        probe()(jnp.ones((4, 4))).block_until_ready()      # compiled, and written
        first = _since(mark, "ledger_probe_cached")
        mark = time.monotonic()
        probe()(jnp.ones((4, 4))).block_until_ready()      # a new jit of the same program
        second = _since(mark, "ledger_probe_cached")
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert [r.kind for r in first if not r.seconds] == ["cache_miss"]
    assert not any(r.kind == "cache_load" for r in first)
    kinds = [r.kind for r in second]
    assert kinds.count("cache_hit") == 1 and kinds.count("cache_load") == 1
    assert "cache_miss" not in kinds
    backend = next(r for r in second if r.kind == "backend")
    for r in second:
        if r.kind in ("cache_hit", "cache_load"):
            assert r.fun_name == "jit(ledger_probe_cached)"
            assert backend.start - 1e-3 <= r.start <= r.end <= backend.end
    assert counters.get("compile.cache_hits") == 1 and counters.get("compile.cache_misses") >= 1
    assert counters.get("compile.requests") == (
        counters.get("compile.cache_hits") + counters.get("compile.cache_misses"))
    assert histograms.get("compile.cache_load_s").snapshot()["count"] == 1


def test_a_jit_traced_inside_anothers_trace_is_kept_and_summed_once():

    @jax.jit
    def ledger_probe_inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def ledger_probe_outer(x):
        return ledger_probe_inner(x).sum()

    mark = since = time.monotonic()
    ledger_probe_outer(jnp.ones((4, 4))).block_until_ready()
    inner = _since(mark, "ledger_probe_inner")
    assert [r.kind for r in inner] == ["trace"]            # in the list, never lowered alone
    found = COMPILE_LEDGER.summary(since, time.monotonic())
    assert "ledger_probe_inner" not in found["programs"]
    outer = found["programs"]["ledger_probe_outer"]
    traced = next(r for r in _since(mark, "ledger_probe_outer") if r.kind == "trace")
    assert outer["trace"] == traced.seconds and outer["lower"] > 0 and outer["backend"] > 0
    assert traced.start <= inner[0].start and inner[0].end <= traced.end
    assert found["costliest"][0][0] == "ledger_probe_outer"


def _rec(start, end, kind, name):
    return CompileRecord(float(start), float(end), kind, name, float(end - start))


def test_summary_of_a_hand_written_list():
    recs = [
        _rec(0, 10, "trace", "step"),              # outermost
        _rec(1, 3, "trace", "kernel"),             # nested in step's trace
        _rec(3, 6, "trace", "kernel"),             # nested, adjacent to the one before
        _rec(4, 5, "trace", "add"),                # nested twice over
        _rec(10, 12, "lower", "jit(step)"),        # another kind: its own nesting
        _rec(12, 20, "backend", "jit(step)"),
        _rec(13, 13, "cache_hit", "jit(step)"),
        _rec(13, 19, "cache_load", "jit(step)"),
        _rec(20, 24, "trace", "init"),             # adjacent to nothing of its kind: counts
        _rec(22, 28, "trace", "overlap"),          # overlaps init, inside nothing: counts in full
        _rec(28, 29, "backend", "jit(init)"),
        _rec(29, 29, "cache_miss", "jit(init)"),
        _rec(30, 31, "trace", "step"),             # the same program again, later
        _rec(30, 31, "trace", "twin"),             # the same interval: one of the two is outermost
        _rec(100, 140, "backend", "jit(reference)"),   # after ``until``
        _rec(101, 101, "cache_miss", "jit(reference)"),
    ]
    found = summarize(recs, 0.0, 50.0)
    assert found["seconds"] == {"trace": 10 + 4 + 6 + 1, "lower": 2.0, "backend": 9.0, "cache_load": 6.0}
    assert found["programs"]["step"] == {"trace": 11.0, "lower": 2.0, "backend": 8.0, "cache_load": 6.0}
    assert "kernel" not in found["programs"] and "add" not in found["programs"]
    assert "twin" not in found["programs"] and "reference" not in found["programs"]
    assert (found["requests"], found["cache_hits"], found["cache_misses"]) == (2, 1, 1)
    assert found["costliest"][:2] == [("step", 21.0), ("overlap", 6.0)]
    assert found["records"] == 14
    late = summarize(recs, 50.0)
    assert late["seconds"]["backend"] == 40.0 and late["cache_misses"] == 1 and late["records"] == 2
    # the order of the list does not matter
    assert summarize(recs[::-1], 0.0, 50.0)["seconds"] == found["seconds"]


def test_the_cap_drops_the_oldest_and_counts_them():
    ledger = CompileLedger(cap=4)       # never installed: fed by hand
    for i in range(3):
        ledger._on_duration(TRACE, 0.5, fun_name=f"f{i}")
        ledger._on_duration(LOWER, 0.25, fun_name=f"jit(f{i})")
    kept = ledger.records()
    assert len(kept) == 4 and ledger.dropped == 2
    assert [r.fun_name for r in kept] == ["f1", "jit(f1)", "f2", "jit(f2)"]
    ledger._on_duration(BACKEND, 1.0, fun_name="jit(f2)")      # two records at once
    assert ledger.dropped == 4 and [r.kind for r in ledger.records()][-2:] == ["backend", "cache_miss"]
    assert ledger.summary()["dropped"] == 4
    ledger._on_duration("/jax/some/other_duration", 1.0)        # not a compile event
    assert ledger.dropped == 4
    assert ledger.installed_at is None and ledger.first_step_line() is None


def test_a_compile_request_reaches_the_flight_file_by_name(tmp_path):
    TELEMETRY.configure(enabled=True, flight_dir=str(tmp_path))

    @jax.jit
    def ledger_probe_flight(x):
        return jnp.exp(x) - 1.0

    ledger_probe_flight(jnp.ones((4,))).block_until_ready()
    path = TELEMETRY.drain("test")
    found = validate_flight_file(path)
    assert found["unclosed"] == [] and found["by_name"]["compile.request"] >= 3
    import json

    mine = [json.loads(l) for l in open(path) if "ledger_probe_flight" in l]
    assert [(r["name"], r["ph"], r["kind"], r["fun_name"]) for r in mine] == [
        ("compile.request", "I", "trace", "ledger_probe_flight"),
        ("compile.request", "I", "lower", "jit(ledger_probe_flight)"),
        ("compile.request", "I", "backend", "jit(ledger_probe_flight)"),
    ]
    assert mine[2]["cache_hit"] in (True, False) and all(r["seconds"] > 0 for r in mine)
    # only a program's own trace is published: the jnp functions traced inside it stay in the ledger
    kinds = [json.loads(l).get("kind") for l in open(path) if '"compile.request"' in l]
    assert kinds.count("trace") <= kinds.count("lower") == kinds.count("backend")


def test_the_first_step_line_and_its_gauge():
    line = COMPILE_LEDGER.first_step_line()
    assert re.fullmatch(
        r"first step verdict [0-9.]+ s after start-up: traced [0-9.]+ s, lowered [0-9.]+ s, "
        r"loaded or compiled [0-9.]+ s \([0-9]+ requests: [0-9]+ cache hits taking [0-9.]+ s, "
        r"[0-9]+ compiled afresh\)(; costliest program .+ [0-9.]+ s)?", line), line
    assert gauges.get("train.first_step_s") > 0
