"""The set-up metrics on a made-up compile ledger: each of the six values, the
records after set-up's end left out, a program without the ledger giving
nothing; the metric files against their ``BENCHMARK.json`` entries; and a
rehearsal of a language-model cell, which lists the six names."""

import json
import pathlib
import sys
import types

import pytest

from benchmarks.readers import setup_ledger
from dalle_pytorch_tpu.utils.profiling import CompileRecord, summarize

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAMES = ("setup.step_trace_s", "setup.step_lower_s", "setup.step_load_s",
         "setup.other_programs_s", "setup.fresh_compiles", "setup.unaccounted_share")
START = 1000.0          # ``process_start`` on the ledger's clock
SETUP_S = 40.0


def rec(start, end, kind, name):
    return CompileRecord(START + start, START + end, kind, name, float(end - start))


RECORDS = [
    rec(2, 3, "trace", "init"), rec(3, 3.5, "lower", "jit(init)"),
    rec(3.5, 4.5, "backend", "jit(init)"), rec(4.5, 4.5, "cache_miss", "jit(init)"),
    rec(10, 17, "trace", "train_step"), rec(11, 13, "trace", "_chunk_jit"),   # a kernel body, inside
    rec(17, 18.25, "lower", "jit(train_step)"), rec(18.25, 21.75, "backend", "jit(train_step)"),
    rec(18.5, 18.5, "cache_hit", "jit(train_step)"), rec(18.5, 21.5, "cache_load", "jit(train_step)"),
    rec(30, 30.5, "backend", "jit(reduce)"), rec(30.5, 30.5, "cache_miss", "jit(reduce)"),
    # the window's and the reference's: after set-up's end
    rec(45, 46, "trace", "train_step"), rec(46, 47, "backend", "jit(train_step)"),
    rec(47, 47, "cache_miss", "jit(train_step)"),
    rec(80, 120, "backend", "jit(reference_step)"), rec(120, 120, "cache_miss", "jit(reference_step)"),
]


class Ledger:
    installed_at = START

    def summary(self, since, until):
        return {**summarize(RECORDS, since, until), "dropped": 0}


def ctx(**facts):
    return types.SimpleNamespace(process_start=START, facts={"setup_s": SETUP_S, **facts})


def read(name, c=None):
    spec = json.loads((ROOT / "benchmarks" / "metrics" / f"{name}.json").read_text())
    assert spec["reader"] == "setup_ledger"
    return setup_ledger.read(c or ctx(), **spec["args"])


def test_the_six_values_of_a_made_up_set_up(monkeypatch):
    monkeypatch.setattr(setup_ledger, "_ledger", Ledger)
    got = {name: read(name) for name in NAMES}
    assert got == {
        "setup.step_trace_s": 7.0,            # the kernel body traced inside it is not added
        "setup.step_lower_s": 1.25,
        "setup.step_load_s": 3.5,             # the cache's read lies inside the request
        "setup.other_programs_s": 1.0 + 0.5 + 1.0 + 0.5,
        "setup.fresh_compiles": 2,            # init and reduce; not the window's, not the reference's
        "setup.unaccounted_share": 100.0 * (40.0 - 14.75) / 40.0,
    }
    seconds = sum(got[n] for n in NAMES[:4])
    assert seconds <= SETUP_S and 0.0 <= got["setup.unaccounted_share"] <= 100.0
    with pytest.raises(ValueError):
        setup_ledger.read(ctx(), "no_such_value")


def test_a_set_up_without_the_step_or_without_the_ledger_gives_nothing(monkeypatch):
    monkeypatch.setattr(setup_ledger, "_ledger", Ledger)
    for name in NAMES:
        assert setup_ledger.read(ctx(), name.split(".", 1)[1], program="decode_step") is None
        assert read(name, types.SimpleNamespace(process_start=START, facts={})) is None
    monkeypatch.undo()
    # the parent of PR 40: utils/profiling.py has no COMPILE_LEDGER
    monkeypatch.setitem(sys.modules, "dalle_pytorch_tpu.utils.profiling", types.ModuleType("profiling"))
    assert setup_ledger._ledger() is None
    assert all(read(name) is None for name in NAMES)


def test_each_metric_file_agrees_with_its_entry():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-6:] == list(NAMES)
    cells = [w["name"] for w in bench["workloads"]][:4]
    for name in NAMES:
        spec = json.loads((ROOT / "benchmarks" / "metrics" / f"{name}.json").read_text())
        entry = entries[name]
        for key in ("name", "layer", "unit", "moves", "source", "better"):
            assert spec[key] == entry[key], (name, key)
        assert (entry["layer"], entry["moves"], entry["source"], entry["better"]) == (
            "set-up", "setup_s", "host_clock", "lower")
        assert entry["workloads"] == cells


def test_a_language_model_rehearsal_lists_the_six_names(capsys):
    from benchmarks import run as bench_run

    rc = bench_run.main([
        "--workload", "train-granite4hmicro-d10-s8k", "--seed", str(2**31 + 40),
        "--seconds", "1.5", "--trace", "0", "--rehearsal",
    ])
    assert rc == 0
    out = capsys.readouterr()
    assert json.loads(out.out.strip().splitlines()[-1])["metrics"] == {}
    names = next(l for l in out.err.splitlines() if l.startswith("no metric printed"))
    for name in NAMES:
        assert name in names, names
