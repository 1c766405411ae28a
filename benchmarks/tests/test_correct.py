"""The comparison that decides ``correct``, shown to pass and shown to fail.

Each test drives a whole run of ``benchmarks/run.py`` at the rehearsal's tiny
size on the CPU (``--rehearsal``, which skips the look for a chip and nothing
else). A sound run comes out correct; every control (the lower precision in
the program's place) and every fault planted under the timed path comes out
not correct:

  serving   the program's int8 path switched on; a token altered where the
            decode step produces it
  training  the reference in fp8; half of the batch left out and the mean
            taken over the rest (as a control and planted in the step); a
            step that returns its state unchanged

The exchange between chips has no fault to plant: every cell runs on one.
"""

import json
import pathlib

import pytest

from benchmarks import run as bench_run

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SERVE = "serve-d12full-backlog"
TRAIN = BENCH["workloads"][0]["name"]


@pytest.fixture(scope="module")
def with_serving_cell(tmp_path_factory):
    """BENCHMARK.json with the serving backlog cell added. The cell waits
    under Open questions in PERF.md (held to the plain reference the program's
    serving path is at fault on the chip); its driver is driven here, on the
    CPU, from the traffic file under ``tests/data/traffic``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": SERVE, "config": "dalle-d12-full", "traffic": "backlog-c128",
        "chips": 1, "why": "closed loop",
    })
    bench["end_to_end"].append({
        "name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.1,
        "source": "host_clock", "workloads": [SERVE],
    })
    for f in sorted((ROOT / "benchmarks" / "metrics").glob("*.json")):
        spec = json.loads(f.read_text())
        if spec["moves"] == "serve_tokens_per_s":
            bench["per_layer"].append({
                k: spec[k] for k in ("name", "unit", "better", "source", "layer", "moves")
            } | {"workloads": [SERVE]})
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return ["--benchmark-json", str(path), "--traffic-dir", str(DATA / "traffic")]


def drive(capsys, workload, seed, *extra):
    rc = bench_run.main([
        "--workload", workload, "--seed", str(seed), "--seconds", "1.5",
        "--trace", "0", "--rehearsal", *extra,
    ])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metrics"] == {}, "a rehearsal prints no metric"
    return line


@pytest.mark.parametrize("workload", [SERVE, TRAIN])
def test_sound_run_is_correct(capsys, workload, with_serving_cell):
    extra = with_serving_cell if workload == SERVE else []
    line = drive(capsys, workload, 2**31 + 41, *extra)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload,control", [
    (SERVE, "int8"), (TRAIN, "fp8"), (TRAIN, "half_batch"),
])
def test_control_is_not_correct(capsys, workload, control, with_serving_cell):
    extra = with_serving_cell if workload == SERVE else []
    line = drive(capsys, workload, 2**31 + 42, "--control", control, *extra)
    assert line["correct"] is False
    over = [n for n, c in line["checks"].items() if c["value"] > c["limit"]]
    assert over, line["checks"]


def test_altered_token_is_not_correct(capsys, monkeypatch, with_serving_cell):
    from dalle_pytorch_tpu.serving import engine

    real = engine._decode_jit

    def altered(dalle, *args):
        cache, samples = real(dalle, *args)
        return cache, (samples + 1) % dalle.num_image_tokens

    monkeypatch.setattr(engine, "_decode_jit", altered)
    line = drive(capsys, SERVE, 2**31 + 43, *with_serving_cell)
    assert line["correct"] is False
    assert line["checks"]["logit_gap_mean"]["value"] > line["checks"]["logit_gap_mean"]["limit"]


def _plant_in_step(monkeypatch, wrap):
    from dalle_pytorch_tpu.parallel import step as step_mod

    real = step_mod.make_train_step

    def planted(loss_fn, optimizer, runtime, shardings, **kw):
        kw["donate"] = False
        return wrap(real(loss_fn, optimizer, runtime, shardings, **kw))

    monkeypatch.setattr(step_mod, "make_train_step", planted)


def test_state_left_unchanged_is_not_correct(capsys, monkeypatch):
    def wrap(step):
        def unchanged(state, batch, rng, lr):
            _, loss = step(state, batch, rng, lr)
            return state, loss
        return unchanged

    _plant_in_step(monkeypatch, wrap)
    line = drive(capsys, TRAIN, 2**31 + 44)
    assert line["correct"] is False
    change = line["checks"]["param_change_worst_leaf_gap"]
    assert change["value"] == pytest.approx(1.0, abs=1e-6)   # nothing moved


def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    def wrap(step):
        def half(state, batch, rng, lr):
            n = batch["text"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()}, rng, lr)
        return half

    _plant_in_step(monkeypatch, wrap)
    line = drive(capsys, TRAIN, 2**31 + 45)
    assert line["correct"] is False
