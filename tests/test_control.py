"""Adaptive control loop (serving/control.py + engine wiring, ISSUE 19).

Two layers. The PURE layer pins the Controller as a deterministic
function: ladder steps with explicit hysteresis, noise gates, the
``control_stall`` raise, and same-inputs -> same-decision-sequence. The
ENGINE layer pins the contracts that make runtime adaptation safe at
all: a controller-on engine (forced-low accept via the misdrafting
depth-1 drafter) steps the effective spec_k DOWN while producing tokens
BIT-IDENTICAL to the controller-off engine (every knob channel is data
to the jits — exact-match acceptance absorbs any verify width, budget
swaps keep the chunk width), with ZERO new jit signatures; and the
``control_stall`` drill degrades to static defaults with 100% typed
accounting, never touching decode progress.

Page size 2 (env override) as in tests/test_spec_decode.py, so verify
blocks cross page boundaries mid-block.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import DALLE
from dalle_pytorch_tpu.serving import (
    ControlConfig,
    Controller,
    Engine,
    EngineConfig,
    FakeClock,
    Outcome,
    Request,
    check_accounting,
)
from dalle_pytorch_tpu.serving import engine as engine_mod
from dalle_pytorch_tpu.serving.control import ControlStall
from dalle_pytorch_tpu.utils.faults import FAULTS
from dalle_pytorch_tpu.utils.metrics import counters, gauges


@pytest.fixture(autouse=True)
def tiny_pages(monkeypatch):
    monkeypatch.setenv("DALLE_TPU_KV_PAGE_SIZE", "2")
    yield


@pytest.fixture(scope="module")
def deep_model():
    """Depth-4 stack whose depth-1 early-exit drafter genuinely
    misdrafts (~0.3 accept rate on this geometry) — the forced-low
    accept signal the spec ladder reacts to."""
    dalle = DALLE(
        dim=32, depth=4, num_text_tokens=32, text_seq_len=6,
        num_image_tokens=64, image_fmap_size=4, heads=2, dim_head=8,
        attn_types=("full",), shift_tokens=True, rotary_emb=True,
    )
    rng = np.random.RandomState(0)
    text = jnp.asarray(rng.randint(1, 32, size=(1, 6)), jnp.int32)
    image = jnp.asarray(rng.randint(0, 64, size=(1, 16)), jnp.int32)
    params = dalle.init(jax.random.key(0), text, image)["params"]
    return dalle, params


def vit(**kw):
    """A full vitals snapshot (every key always present)."""
    base = {
        "iterations": 0.0, "spec_accept_rate": 0.0, "spec_drafted": 0.0,
        "prefix_hit_frac": 0.0, "decode_gap_s": 0.0, "stage_lag": 0.0,
        "deadline_miss_rate": 0.0, "occupancy": 0.0,
    }
    base.update(kw)
    return base


def make_controller(**kw):
    cfg = kw.pop("config", ControlConfig())
    defaults = dict(
        spec_k_ceiling=3, budget_default=6, chunk=2,
        watermark_default=0.85, prefix_enabled=True,
    )
    defaults.update(kw)
    return Controller(cfg, **defaults)


# ---------------------------------------------------- pure ladder tests


class TestLadder:
    def test_spec_steps_down_and_floors_at_one(self):
        c = make_controller()
        low = vit(spec_drafted=10.0, spec_accept_rate=0.1)
        for want in (2, 1, 1, 1):
            d = c.evaluate(0, low)
            assert d.knobs["spec_k"] == float(want)
        assert "spec_down" not in c.log[-1].reasons  # floored: no change

    def test_spec_steps_back_up_to_ceiling(self):
        c = make_controller()
        c.evaluate(0, vit(spec_drafted=10.0, spec_accept_rate=0.1))
        assert c.knobs["spec_k"] == 2.0
        for want in (3, 3):
            d = c.evaluate(1, vit(spec_drafted=10.0, spec_accept_rate=0.95))
            assert d.knobs["spec_k"] == float(want)  # never past ceiling

    def test_spec_noise_gate(self):
        c = make_controller(config=ControlConfig(spec_min_drafts=8))
        d = c.evaluate(0, vit(spec_drafted=4.0, spec_accept_rate=0.0))
        assert d.knobs["spec_k"] == 3.0 and not d.changed

    def test_spec_hysteresis_band_holds(self):
        c = make_controller()
        # between low and high: no movement either way
        d = c.evaluate(0, vit(spec_drafted=10.0, spec_accept_rate=0.6))
        assert d.knobs["spec_k"] == 3.0 and not d.changed

    def test_budget_tightens_under_gap_and_floors(self):
        c = make_controller()
        high = vit(decode_gap_s=1.0)
        for want in (4, 3, 3):  # floor = max(chunk, 6*0.5) = 3
            d = c.evaluate(0, high)
            assert d.knobs["budget"] == float(want)

    def test_budget_relaxes_back_to_default(self):
        c = make_controller()
        c.evaluate(0, vit(decode_gap_s=1.0))
        for want in (6, 6):  # +chunk, capped at the default
            d = c.evaluate(1, vit(decode_gap_s=0.0))
            assert d.knobs["budget"] == float(want)

    def test_budget_hysteresis_band_holds(self):
        cfg = ControlConfig(gap_high_s=1.0, gap_low_frac=0.5)
        c = make_controller(config=cfg)
        c.evaluate(0, vit(decode_gap_s=2.0))
        assert c.knobs["budget"] == 4.0
        # in (low, high]: hold
        d = c.evaluate(1, vit(decode_gap_s=0.8))
        assert d.knobs["budget"] == 4.0 and not d.changed

    def test_watermark_clamp_and_restore(self):
        c = make_controller()
        d = c.evaluate(0, vit(deadline_miss_rate=0.5))
        assert d.knobs["watermark"] == 0.5 and "watermark_clamp" in d.reasons
        d = c.evaluate(1, vit(deadline_miss_rate=0.2))  # in the band: hold
        assert d.knobs["watermark"] == 0.5 and not d.changed
        d = c.evaluate(2, vit(deadline_miss_rate=0.0))
        assert d.knobs["watermark"] == 0.85
        assert "watermark_restore" in d.reasons

    def test_prefix_shed_and_restore(self):
        c = make_controller()
        d = c.evaluate(0, vit(occupancy=0.95))
        assert d.knobs["prefix_pages_target"] == 0.0
        assert "prefix_shed" in d.reasons
        d = c.evaluate(1, vit(occupancy=0.6))  # in the band: hold
        assert d.knobs["prefix_pages_target"] == 0.0 and not d.changed
        d = c.evaluate(2, vit(occupancy=0.1))
        assert d.knobs["prefix_pages_target"] is None
        assert "prefix_restore" in d.reasons

    def test_disabled_knobs_never_move(self):
        c = make_controller(spec_k_ceiling=None, budget_default=None,
                            prefix_enabled=False)
        d = c.evaluate(0, vit(spec_drafted=10.0, spec_accept_rate=0.0,
                              decode_gap_s=5.0, occupancy=1.0))
        assert d.knobs["spec_k"] is None
        assert d.knobs["budget"] is None
        assert d.knobs["prefix_pages_target"] is None

    def test_stall_fault_raises_typed(self):
        c = make_controller()
        FAULTS.arm("control_stall", 1)
        with pytest.raises(ControlStall):
            c.evaluate(0, vit())
        assert FAULTS.fired.get("control_stall") == 1
        c.evaluate(1, vit())  # disarmed: back to normal

    def test_reset_restores_defaults(self):
        c = make_controller()
        c.evaluate(0, vit(spec_drafted=10.0, spec_accept_rate=0.0,
                          decode_gap_s=5.0, deadline_miss_rate=1.0))
        assert c.knobs != c.defaults()
        c.reset()
        assert c.knobs == c.defaults()

    def test_log_is_bounded(self):
        c = make_controller(config=ControlConfig(max_log=8))
        for i in range(20):
            c.evaluate(i, vit())
        assert len(c.log) == 8
        assert c.log[-1].iteration == 19

    def test_deterministic_decision_sequence(self):
        # same snapshot sequence into two fresh controllers -> identical
        # decision sequences, field for field
        snaps = [
            vit(spec_drafted=10.0, spec_accept_rate=r, decode_gap_s=g,
                deadline_miss_rate=m, occupancy=o)
            for r, g, m, o in [
                (0.1, 1.0, 0.0, 0.5), (0.2, 0.0, 0.5, 0.95),
                (0.9, 0.1, 0.0, 0.1), (0.95, 2.0, 0.3, 0.99),
            ]
        ]
        a, b = make_controller(), make_controller()
        for i, s in enumerate(snaps):
            a.evaluate(i, s)
            b.evaluate(i, s)
        assert [(d.iteration, d.knobs, d.reasons, d.changed)
                for d in a.log] == [
            (d.iteration, d.knobs, d.reasons, d.changed) for d in b.log
        ]


# ------------------------------------------------------ engine-level


SPEC = dict(
    max_batch=2, prefill_chunk=2, fused_iteration=True,
    spec_decode=True, spec_k=3, spec_draft_depth=1,
)


def prompt(i):
    return np.random.RandomState(100 + i).randint(
        1, 32, size=(6,)
    ).astype(np.int32)


def run_engine(model, *, n=4, max_new=10, **cfg_kw):
    dalle, params = model
    kw = dict(SPEC)
    kw.update(cfg_kw)
    eng = Engine(
        dalle, params, EngineConfig(**kw), clock=FakeClock(step_dt=1.0)
    )
    for i in range(n):
        eng.submit(Request(
            request_id=f"r{i}", prompt=prompt(i),
            max_new_tokens=max_new, seed=i,
        ))
    results = eng.run(max_steps=800)
    return eng, results


def tokens_of(results):
    return {rid: list(map(int, r.tokens)) for rid, r in results.items()}


class TestEngineControl:
    def test_spec_k_steps_down_under_forced_low_accept(self, deep_model):
        eng, results = run_engine(
            deep_model, controller=True,
            control=ControlConfig(interval=4),
        )
        assert all(
            r.outcome is Outcome.COMPLETED for r in results.values()
        )
        # the misdrafter's ~0.3 windowed accept rate sits below
        # spec_accept_low: the effective width must have stepped down
        # from the pre-traced ceiling
        assert eng._eff_spec_k < eng.config.spec_k
        reasons = [r for d in eng.controller.log for r in d.reasons]
        assert "spec_down" in reasons
        assert counters.get("serve.control.decisions") == len(
            eng.controller.log
        )
        assert counters.get("serve.control.adjustments") >= 1
        assert gauges.get("serve.control.spec_k") == float(eng._eff_spec_k)
        check_accounting(eng)

    def test_controller_on_tokens_bit_identical_to_off(self, deep_model):
        _, off = run_engine(deep_model)
        sig_count = engine_mod._spec_iteration_jit._cache_size()
        eng, on = run_engine(
            deep_model, controller=True,
            control=ControlConfig(interval=2),
        )
        # adaptation really happened AND the tokens are the same bits:
        # the verify width is data, exact-match acceptance absorbs it
        assert eng._eff_spec_k < eng.config.spec_k
        assert tokens_of(on) == tokens_of(off)
        # ...through the pre-traced signatures only (no recompile)
        assert engine_mod._spec_iteration_jit._cache_size() == sig_count

    def test_budget_tightens_under_interference_and_relaxes_back(
        self, deep_model
    ):
        """The budget channel through a REAL engine on virtual time: the
        per-iteration dt jumps 100x mid-trace (the deterministic stand-in
        for interference). The TokenBudget holds while gaps sit under the
        SLO threshold, tightens while they exceed it, relaxes back once the
        vitals window flushes, keeps the SAME chunk width throughout (grant
        geometry never re-traces), and every request still completes (the
        head-of-line floor)."""
        dalle, params = deep_model
        clock = FakeClock(step_dt=0.02)
        eng = Engine(dalle, params, EngineConfig(
            max_batch=2, prefill_chunk=2, fused_iteration=True,
            controller=True, vitals_window=4,
            control=ControlConfig(interval=2, gap_high_s=0.5),
        ), clock=clock)
        budget_default, chunk = eng.budget.budget, eng.budget.chunk
        for i in range(4):
            eng.submit(Request(
                request_id=f"r{i}", prompt=prompt(i),
                max_new_tokens=16, seed=i,
            ))
        for _ in range(10):
            assert eng.step()
        assert eng.budget.budget == budget_default  # every gap under the SLO
        clock.step_dt = 2.0  # interference: every gap breaches it
        tightest = budget_default
        for _ in range(10):
            assert eng.step()
            tightest = min(tightest, eng.budget.budget)
        assert tightest < budget_default
        clock.step_dt = 0.02  # interference clears
        recovered = False
        while eng.step():
            recovered = recovered or eng.budget.budget == budget_default
        assert recovered
        assert eng.budget.chunk == chunk
        assert len(eng.results) == 4 and all(
            r.outcome is Outcome.COMPLETED for r in eng.results.values()
        )
        check_accounting(eng)

    def test_decision_sequence_replays_bit_deterministically(
        self, deep_model
    ):
        a, _ = run_engine(
            deep_model, controller=True, control=ControlConfig(interval=2)
        )
        b, _ = run_engine(
            deep_model, controller=True, control=ControlConfig(interval=2)
        )
        assert len(a.controller.log) >= 2
        assert [
            (d.iteration, d.vitals, d.knobs, d.reasons, d.changed,
             d.stalled)
            for d in a.controller.log
        ] == [
            (d.iteration, d.vitals, d.knobs, d.reasons, d.changed,
             d.stalled)
            for d in b.controller.log
        ]

    def test_control_stall_drill_typed_accounting(self, deep_model):
        FAULTS.arm("control_stall", 1)
        eng, results = run_engine(
            deep_model, controller=True,
            control=ControlConfig(interval=2),
        )
        # the stall consumed the armed fault, was typed and counted, and
        # degraded the knobs to static defaults at that evaluation
        assert FAULTS.fired.get("control_stall") == 1
        assert counters.get("serve.fault_control_stall") == 1
        assert counters.get("serve.control.stalls") == 1
        stalled = [d for d in eng.controller.log if d.stalled]
        assert len(stalled) == 1
        assert stalled[0].knobs == eng.controller.defaults()
        # 100% typed accounting: every submitted request has a typed
        # outcome, decode progress never depended on the controller
        assert len(results) == 4
        assert all(
            r.outcome is Outcome.COMPLETED for r in results.values()
        )
        check_accounting(eng)

    def test_vitals_gauges_published_during_run(self, deep_model):
        run_engine(deep_model, controller=True, vitals=True)
        published = set(gauges.snapshot("serve.vitals."))
        for name in (
            "serve.vitals.spec_accept_rate",
            "serve.vitals.decode_gap_s",
            "serve.vitals.occupancy",
            "serve.vitals.deadline_miss_rate",
            "serve.vitals.stage_lag",
            "serve.vitals.prefix_hit_frac",
        ):
            assert name in published, name
        assert gauges.get("serve.vitals.decode_gap_s") == pytest.approx(1.0)

    def test_vitals_off_publishes_nothing(self, deep_model):
        run_engine(deep_model)
        assert gauges.snapshot("serve.vitals.") == {}

    def test_controller_off_knobs_never_move(self, deep_model):
        eng, _ = run_engine(deep_model)
        assert eng.controller is None and eng.vitals is None
        assert eng._eff_spec_k == eng.config.spec_k
        assert eng._eff_watermark == eng.config.high_watermark
