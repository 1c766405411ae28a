"""The reduction by the program's own names (``benchmarks/scope_reduce.py``):
on a hand-made trace whose answers are known, on a trace recorded on the chip
by PR 30 (``--dump``: the first 0.45 s of a traced slice of the training cell,
scopes and kernel names kept; under another name than ``trace-sample-*``, whose
test holds a step's module events to fit inside the cut), through the readers of the nine metrics that read it, and
in the serving rehearsal, where a capture started by the harness holds the
engine's own spans."""

import json
import pathlib
import re

import pytest

from benchmarks import costs, harness, scope_reduce, trace_reduce

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "train-d12sparse-posemb-b8"
SAMPLE = DATA / f"scope-sample-{CELL}.json"
KINDS = ("full", "axial_row", "axial_col", "conv_like")
TOP = ["attn.*", "ff", "embed", "head_loss", "update"]
NEW = [f"kernel.train.attention_roofline.{k}" for k in KINDS] + [
    f"step.train.{n}_share" for n in ("attn", "ff", "head_loss", "update", "unattributed")
]

FWD = "jit(train_step)/jvp(DALLE)/transformer/"
BWD = "jit(train_step)/transpose(jvp(DALLE))/transformer/"


def hand_made():
    table = [
        "",
        FWD + "attn.full/attn_0/fn/to_qkv/dot_general",
        FWD + "attn.full/attn_0/fn/flash_qkv_fwd/pallas_call",
        FWD + "attn.axial_row/attn_1/fn/fn._block_sparse_attend/block_sparse_fwd/pallas_call",
        BWD + "attn.axial_row/attn_1/fn/fn._block_sparse_attend/block_sparse_dq/pallas_call",
        FWD + "ff/ff_0/fn/LayerNorm_0/add",
        "jit(train_step)/update/update.optimizer/mul",
    ]
    ops = [                                                      # scope
        ["fusion.1", 0.010, 0.010, "convolution fusion"],        # attn.full
        ["custom-call.73", 0.020, 0.000001, "custom-call"],      # attn.full, XLA's own
        ["flash_qkv_fwd.1", 0.021, 0.004, "custom-call"],        # attn.full, a kernel
        # the residual add of attn_0 fused into ff_0's norm: the root's scope
        ["fusion.9", 0.025, 0.006, "loop fusion"],               # ff
        ["block_sparse_fwd.2", 0.040, 0.020, "custom-call"],     # attn.axial_row
        ["while.3", 0.060, 0.040, "while"],                      # a wrapper
        ["block_sparse_dq.2", 0.060, 0.030, "custom-call"],      # attn.axial_row, backward
        ["fusion.10", 0.090, 0.002, "loop fusion"],              # no name stack
        ["fusion.11", 0.092, 0.008, "loop fusion"],              # update
        ["fusion.12", 0.150, 0.010, "loop fusion"],              # another module's
    ]
    scopes = [1, 1, 2, 5, 3, 0, 4, 0, 6, 0]
    modules = [["jit_train_step(7)", 0.010, 0.090, ""], ["jit_other(8)", 0.150, 0.010, ""]]
    host = [
        ["bench.slice", 0.0, 0.200, ""],
        ["bench.step", 0.0, 0.200, ""],
        ["serve.step", 0.000, 0.120, ""],
        ["serve.step.sweep", 0.001, 0.002, ""],
        ["serve.step.dispatch", 0.004, 0.005, ""],
        ["serve.step.readback", 0.100, 0.019, ""],
        ["serve.step.release", 0.105, 0.010, ""],        # nested in the readback
        ["serve.step", 0.120, 0.080, ""],
        ["serve.step.dispatch", 0.121, 0.003, ""],
    ]
    return {
        "chips": [{"ops": ops, "modules": modules, "scopes": scopes}],
        "host": host, "scope_table": table,
    }


def test_seconds_by_scope_kernel_and_module():
    r = scope_reduce.reduce(hand_made())
    assert r["window_s"] == pytest.approx(0.200)
    sec = lambda **kw: scope_reduce.scope_seconds(r, "jit_train_step", **kw)
    assert sec(scopes=["attn.full"]) == pytest.approx(0.010 + 0.000001 + 0.004)
    assert sec(scopes=["attn.full"], kernels_only=True) == pytest.approx(0.004)
    # forward and backward of the kind, two kernels
    assert sec(scopes=["attn.axial_row"], kernels_only=True) == pytest.approx(0.050)
    assert sec(scopes=["attn.*"]) == pytest.approx(0.014001 + 0.050)
    # the straddling fusion is its root's: ff
    assert sec(scopes=["ff"]) == pytest.approx(0.006)
    # ``update.optimizer`` lies under ``update``
    assert sec(scopes=["update"]) == pytest.approx(0.008)
    total = sum(s for _, _, s in r["by_scope"]["jit_train_step"])
    assert total == pytest.approx(0.080001)             # the while wrapper is left out
    # the scopes do not overlap; what none of them holds is the one unnamed fusion
    assert sum(sec(scopes=[s]) for s in TOP) == pytest.approx(sec(scopes=TOP))
    assert total - sec(scopes=TOP) == pytest.approx(0.002)
    assert scope_reduce.scope_seconds(r, "jit_other", TOP) == 0.0
    kernels = {k for _, k, _ in r["by_scope"]["jit_train_step"] if k}
    assert kernels == {"flash_qkv_fwd", "block_sparse_fwd", "block_sparse_dq"}


def test_host_self_times_and_gap_attribution():
    r = scope_reduce.reduce(hand_made())
    spans = r["host_spans"]
    assert "bench.step" not in spans
    assert spans["serve.step"]["count"] == 2
    assert spans["serve.step.readback"]["self_seconds"] == pytest.approx(0.019 - 0.010)
    assert spans["serve.step.release"]["self_seconds"] == pytest.approx(0.010)
    # 120 + 80 ms, less the children nested directly inside (not the release)
    assert spans["serve.step"]["self_seconds"] == pytest.approx(
        0.200 - 0.002 - 0.005 - 0.019 - 0.003
    )
    # idle: 0-10 ms (its middle, 5 ms, under the first dispatch); 20.001-21,
    # 31-40, 100-150 (middle 125 ms) and 160-200: under serve.step alone
    gaps = {round(sec, 6): names for sec, names in r["idle_gaps"]}
    assert gaps.pop(0.010) == ["serve.step", "serve.step.dispatch"]
    assert sorted(gaps) == [0.000999, 0.009, 0.040, 0.050]
    assert all(names == ["serve.step"] for names in gaps.values())

    class Ctx:
        facts = {scope_reduce.FACT: r}
    from benchmarks.readers import idle_unattributed_share, span_self_ms

    assert idle_unattributed_share.read(Ctx, "serve.step.*") == pytest.approx(
        100 * 0.099999 / 0.109999
    )
    assert span_self_ms.read(Ctx, "serve.step.readback", "serve.step") == pytest.approx(4.5)
    assert span_self_ms.read(Ctx, "serve.step.stages", "serve.step") is None


def test_head_keeps_scopes_and_stays_readable_by_both_reducers():
    whole = hand_made()
    sample = scope_reduce.head(whole, 0.05)
    chip = sample["chips"][0]
    assert [e[0] for e in chip["ops"]] == ["fusion.1", "custom-call.73", "flash_qkv_fwd.1", "fusion.9"]
    assert [sample["scope_table"][i] for i in chip["scopes"]] == [
        whole["scope_table"][i] for i in (1, 1, 2, 5)
    ]
    assert len(sample["scope_table"]) == 4              # '' and the three in use
    assert trace_reduce.reduce(sample)["window_s"] == pytest.approx(0.05)
    # long enough to keep the step's module event whole
    longer = scope_reduce.head(whole, 0.11)
    assert json.loads(json.dumps(longer)) == longer
    assert [e[0] for e in longer["chips"][0]["ops"]][-1] == "fusion.11"
    r = scope_reduce.reduce(longer)
    assert scope_reduce.scope_seconds(r, "jit_train_step", scopes=["ff"]) == pytest.approx(0.006)
    assert scope_reduce.scope_seconds(r, "jit_train_step", scopes=["update"]) == pytest.approx(0.008)


def test_a_program_that_names_nothing_gives_its_readers_nothing():
    """The parent of PR 30: no scope, kernels called ``fn``. No reader raises,
    and none reports."""
    trace = hand_made()
    # the Flax module path is there, as at the parent; the scopes are not
    trace["scope_table"] = [
        re.sub(r"(attn\.\w+|ff|update(\.\w+)?)/", "", p) for p in trace["scope_table"]
    ]
    assert any("attn_1" in p for p in trace["scope_table"])
    trace["host"] = [h for h in trace["host"] if h[0].startswith("bench.")]
    for op in trace["chips"][0]["ops"]:
        if op[3] == "custom-call":
            op[0] = "fn.1"
    ctx = _ctx(scope_reduce.reduce(trace))
    assert _read_new(ctx) == {}
    assert scope_reduce.reduce({"chips": [], "host": [], "scope_table": [""]}) == {}


def _ctx(reduced, old=None):
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    ctx = harness.Context(
        workload=cell, cfg=costs.load_config(cell["config"]), mix={}, seed=0, seconds=30.0,
        trace=True, rehearsal=False, control=None, process_start=0.0,
        device_kind="TPU v5 lite",
    )
    ctx.peaks = costs.load_peaks(ctx.device_kind)
    ctx.reduced = old or {"modules": {"jit_train_step": {"count": 1, "seconds": 0.09}}}
    ctx.facts.update({"batch": 8, scope_reduce.FACT: reduced})
    return ctx


def _read_new(ctx):
    entries = {"per_layer": [m for m in BENCH["per_layer"] if m["name"] in NEW]}
    assert len(entries["per_layer"]) == 9
    return {k: v["value"] for k, v in harness.read_per_layer(ctx, entries).items()}


def test_the_nine_entries_resolve_and_list_the_cell():
    import importlib

    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-9:] == NEW      # appended, in order
    for name in NEW:
        spec = harness.load_metric(name)
        assert callable(importlib.import_module(f"benchmarks.readers.{spec['reader']}").read)
        m = by_name[name]
        assert {k: spec[k] for k in ("layer", "unit", "moves", "source", "better")} == {
            k: m[k] for k in ("layer", "unit", "moves", "source", "better")
        }
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "train_tokens_per_s_chip" and m["source"] == "device_trace"
    # the serving driver's, waiting beside its five for a serving cell
    waiting = sorted(p.stem for p in (ROOT / "benchmarks" / "metrics").glob("engine.*.json"))
    assert "engine.idle_unattributed_share.rate" in waiting
    assert sum(n.startswith("engine.phase_ms.rate.") for n in waiting) == 9
    for name in waiting:
        spec = harness.load_metric(name)
        assert spec["moves"] == "serve_tokens_per_s" and name not in by_name
        assert callable(importlib.import_module(f"benchmarks.readers.{spec['reader']}").read)


def test_recorded_chip_trace_reads_all_nine_and_they_close():
    """What ISSUE 30 asks of a traced run of the cell, on the recorded 0.45 s:
    every new metric reads, none over 100 %; the five shares sum to 100; the
    Pallas seconds by kind sum to the step's ``custom-call`` seconds, and the
    per-kind rooflines, weighted by them, give back the lumped one."""
    trace = json.loads(SAMPLE.read_text())
    table = trace["scope_table"]
    assert any("attn.axial_row" in p for p in table) and any("update" in p for p in table)
    names = {scope_reduce.kernel_of(n, c) for n, _, _, c in trace["chips"][0]["ops"]} - {""}
    assert names == {"flash_qkv_fwd", "flash_qkv_bwd", "block_sparse_fwd",
                     "block_sparse_dq", "block_sparse_dkv"}
    old = trace_reduce.reduce(trace)
    assert not any(n in ("fn", "fn._block_sparse_attend") for n, _ in old["device_ops"])
    reduced = scope_reduce.reduce(trace)
    ctx = _ctx(reduced, old)
    got = _read_new(ctx)
    assert sorted(got) == sorted(NEW)
    assert all(0.0 < v <= 100.0 for v in got.values()), got
    shares = [got[f"step.train.{n}_share"] for n in ("attn", "ff", "head_loss", "update", "unattributed")]
    assert sum(shares) == pytest.approx(100.0, abs=0.5)
    by_kind = {
        k: scope_reduce.scope_seconds(reduced, "jit_train_step", scopes=[f"attn.{k}"], kernels_only=True)
        for k in KINDS
    }
    lumped_s = trace_reduce.category_seconds(old, "jit_train_step", ("custom-call", "custom call"))
    assert sum(by_kind.values()) == pytest.approx(lumped_s, rel=0.01)
    from benchmarks.readers import train_kernel_roofline

    lumped = train_kernel_roofline.read(
        ctx, **harness.load_metric("kernel.train.attention_roofline")["args"]
    )
    weighted = sum(got[f"kernel.train.attention_roofline.{k}"] * by_kind[k] for k in KINDS)
    assert weighted / sum(by_kind.values()) == pytest.approx(lumped, abs=0.1)
    # the routes, from the trace alone: the pair grid runs axial_row and conv_like
    for kind, kernel in (("full", "flash_qkv"), ("axial_row", "block_sparse"),
                         ("axial_col", "flash_qkv"), ("conv_like", "block_sparse")):
        kernels = {
            k for p, k, _ in reduced["by_scope"]["jit_train_step"]
            if k and scope_reduce.matches(p, [f"attn.{kind}"])
        }
        assert kernels and all(k.startswith(kernel) for k in kernels), (kind, kernels)


def test_serving_rehearsal_holds_the_engines_spans_and_prints_the_new_names(capsys, tmp_path):
    """A capture started by anyone (here the harness's TraceSlice, on the CPU)
    holds the engine's own spans on the host plane, and the serving driver's
    two new kinds of metric read them: the rehearsal prints their names."""
    from benchmarks import run as bench_run

    serve = "serve-d12full-backlog"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": serve, "config": "dalle-d12-full", "traffic": "backlog-c128",
        "chips": 1, "why": "closed loop",
    })
    bench["end_to_end"].append({
        "name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.1,
        "source": "host_clock", "workloads": [serve],
    })
    for f in sorted((ROOT / "benchmarks" / "metrics").glob("engine.*.json")):
        spec = json.loads(f.read_text())
        bench["per_layer"].append({
            k: spec[k] for k in ("name", "unit", "better", "source", "layer", "moves")
        } | {"workloads": [serve]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    rc = bench_run.main([
        "--workload", serve, "--seed", str(2**31 + 77), "--seconds", "1.5", "--trace", "1",
        "--rehearsal", "--benchmark-json", str(path), "--traffic-dir", str(DATA / "traffic"),
    ])
    assert rc == 0
    out = capsys.readouterr()
    assert json.loads(out.out.strip().splitlines()[-1])["metrics"] == {}
    names = next(l for l in out.err.splitlines() if l.startswith("no metric printed"))
    assert "engine.idle_unattributed_share.rate" in names
    for phase in ("sweep", "admit", "plan", "fold_keys", "dispatch", "readback", "publish"):
        assert f"engine.phase_ms.rate.{phase}" in names
    trace = scope_reduce.load(
        trace_reduce.find_xplane(str(harness.WORK / f"trace-{serve}")), 1
    )
    spans = {name for name, *_ in trace["host"]}
    assert {"bench.slice", "bench.step", "serve.step", "serve.step.fold_keys",
            "serve.step.dispatch", "serve.step.readback"} <= spans
    host = scope_reduce.reduce(trace)["host_spans"]
    assert host["serve.step.dispatch"]["count"] == host["serve.step"]["count"] > 0
