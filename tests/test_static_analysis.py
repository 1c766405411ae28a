"""dalle-tpu-lint framework tests (tools/lint/, docs/DESIGN.md §11).

Two layers:

1. **Fixture corpus** (tests/fixtures_lint/): known-bad snippets, AST-
   parsed only (never imported), with exact finding codes AND lines
   pinned per checker — each one a violation the checker would have
   caught at review time that runtime tests would miss. Includes one
   inline-suppressed case and one baselined case, pinning both escape
   hatches.
2. **The repo gate**: ``python tools/lint.py --check`` over the whole
   package must exit 0 — the same pre-flight tools/serve_smoke.py and
   tools/telemetry_smoke.py run. A lint finding anywhere in the tree
   fails the fast tier here, not at the next release drill.

The AST stage is stdlib-only and never imports the package it checks,
so those tests run in milliseconds with no jax involvement. The TRACE
stage (tools/lint/trace/, ``--trace``, DTL1xx) is the exception by
design: its fixture registry jits are traced (never executed) with jax
on CPU, and the repo gate audits the real package's entry points
against tools/trace_contracts.json.
"""

import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

from lint import (  # noqa: E402  (tools/lint package, stdlib-only)
    FaultConfig,
    LayerRule,
    LintConfig,
    NamesConfig,
    default_config,
    run_lint,
)

FX = "tests/fixtures_lint"


def fixture_config(**kw) -> LintConfig:
    base = dict(
        repo_root=str(REPO),
        scan_roots=(),
        exclude=(),
        layer_rules=(),
        faults=None,
        names=None,
        baseline_path=None,
    )
    base.update(kw)
    return LintConfig(**base)


def codes_lines(findings):
    return sorted((f.code, f.line) for f in findings)


# ------------------------------------------------------------- purity


class TestPurity:
    def run(self, baseline=None):
        cfg = fixture_config(baseline_path=baseline)
        return run_lint(cfg, paths=[f"{FX}/fx_purity.py"],
                        checkers=["purity"])

    def test_exact_codes_and_lines(self):
        res = self.run()
        assert codes_lines(res.findings) == [
            ("DTL011", 19),   # if on traced value
            ("DTL011", 66),   # while on traced value (baselined case, no
                              # baseline loaded in this run)
            ("DTL011", 73),   # twin branch 1
            ("DTL011", 75),   # twin branch 2
            ("DTL012", 29),   # float() on propagated taint
            ("DTL012", 30),   # .item()
            ("DTL013", 36),   # time.time() in the jitted fn
            ("DTL013", 41),   # np.random reached from a jitted fn
            ("DTL014", 37),   # mutable module-global closure
        ], [f.render() for f in res.findings]

    def test_colliding_anchors_get_occurrence_suffixes(self):
        """Two same-shape violations in one function must carry DISTINCT
        baseline keys — otherwise one baseline entry would silently
        grandfather every future violation of that shape there."""
        res = self.run()
        keys = sorted(f.key for f in res.findings
                      if "twin_branches" in f.anchor)
        assert keys == [
            f"{FX}/fx_purity.py::DTL011::twin_branches:If",
            f"{FX}/fx_purity.py::DTL011::twin_branches:If#2",
        ]

    def test_static_args_and_none_checks_are_clean(self):
        res = self.run()
        lines = {f.line for f in res.findings}
        assert 26 not in lines   # `if n > 2` — n is static_argnums
        assert 51 not in lines   # `if mask is None` — structure check

    def test_inline_suppression(self):
        res = self.run()
        sup = [f for f in res.suppressed]
        assert [(f.code, f.line) for f in sup] == [("DTL011", 59)]
        assert not any(f.line == 59 for f in res.findings)

    def test_baseline_grandfathers_and_reports_stale(self):
        res = self.run(baseline=f"{FX}/fx_baseline.json")
        assert ("DTL011", 66) not in codes_lines(res.findings)
        assert [(f.code, f.line) for f in res.baselined] == [("DTL011", 66)]
        assert res.stale_baseline == []


# ----------------------------------------------------------- layering


class TestLayering:
    def test_host_only_rule_flags_lazy_imports_too(self):
        cfg = fixture_config(layer_rules=(
            LayerRule(name="fx-host-only",
                      files=(f"{FX}/fx_layering_host.py",),
                      forbid=("jax", "flax"), why="fixture"),
        ))
        res = run_lint(cfg, paths=[f"{FX}/fx_layering_host.py"],
                       checkers=["layering"])
        assert codes_lines(res.findings) == [
            ("DTL021", 4), ("DTL021", 8),
        ], [f.render() for f in res.findings]

    def test_ops_must_not_import_serving(self):
        cfg = fixture_config(layer_rules=(
            LayerRule(name="fx-ops",
                      files=(f"{FX}/fx_layering_ops.py",),
                      forbid=("dalle_pytorch_tpu.serving",), why="fixture"),
        ))
        res = run_lint(cfg, paths=[f"{FX}/fx_layering_ops.py"],
                       checkers=["layering"])
        assert codes_lines(res.findings) == [
            ("DTL021", 4),   # from x.serving import engine
            ("DTL021", 5),   # from x.serving.types import Request
            ("DTL021", 8),   # from x import serving — the from-parent
                             # spelling lands in the alias list
        ], [f.render() for f in res.findings]

    def test_relative_imports_resolve_against_package(self):
        # the REAL repo rule: utils/telemetry.py's `from .faults import`
        # resolves to dalle_pytorch_tpu.utils.faults and must NOT trip
        # the host-only rule, while any jax import would
        res = run_lint(default_config(str(REPO)),
                       paths=["dalle_pytorch_tpu/utils/telemetry.py"],
                       checkers=["layering"])
        assert res.clean, [f.render() for f in res.findings]


# -------------------------------------------------------- fault sites


class TestFaultSites:
    def run(self):
        cfg = fixture_config(faults=FaultConfig(
            registry_path=f"{FX}/fx_faults_registry.py",
            exercise_roots=(f"{FX}/fx_faults_tests.py",),
        ))
        return run_lint(cfg, paths=[f"{FX}/fx_faults.py"],
                        checkers=["fault-sites"], full=True)

    def test_unknown_dead_and_undrilled_sites(self):
        res = self.run()
        by_code = {}
        for f in res.findings:
            by_code.setdefault(f.code, []).append(f)
        # two unregistered literals at their exact take-site lines
        assert [(f.line, f.anchor) for f in by_code["DTL031"]] == [
            (21, "typo_site"), (23, "typo_site_2"),
        ]
        # dead_site is registered + drilled but never taken
        assert [f.anchor for f in by_code["DTL032"]] == ["dead_site"]
        # undrilled_site is registered + taken but never exercised —
        # the corpus docstring MENTIONING "undrilled_site=1" does not
        # count (documentation of a drill is not a drill)
        assert [f.anchor for f in by_code["DTL033"]] == ["undrilled_site"]

    def test_narrowed_scan_skips_registry_completeness(self):
        cfg = fixture_config(faults=FaultConfig(
            registry_path=f"{FX}/fx_faults_registry.py",
            exercise_roots=(f"{FX}/fx_faults_tests.py",),
        ))
        res = run_lint(cfg, paths=[f"{FX}/fx_faults.py"],
                       checkers=["fault-sites"])  # full defaults to False
        assert {f.code for f in res.findings} == {"DTL031"}


# ----------------------------------------------------- telemetry names


class TestTelemetryNames:
    def run(self, full=True):
        cfg = fixture_config(names=NamesConfig(
            registry_path=f"{FX}/fx_names_registry.py",
            doc_path=f"{FX}/fx_names_doc.md",
        ))
        return run_lint(cfg, paths=[f"{FX}/fx_names.py"],
                        checkers=["telemetry-names"], full=full)

    def test_typo_kind_mismatch_and_bad_fstring_head(self):
        res = self.run(full=False)
        assert codes_lines(res.findings) == [
            ("DTL041", 9),    # fx.typo: unregistered
            ("DTL041", 10),   # fx.known used as gauge: kind mismatch
            ("DTL041", 16),   # f"fx.bogus.{...}": head matches nothing
        ], [f.render() for f in res.findings]

    def test_span_duration_histograms_are_derived(self):
        res = self.run(full=False)
        assert 12 not in {f.line for f in res.findings}  # fx.request_s ok

    def test_device_scopes_and_kernel_names(self):
        """``jax.named_scope`` literals and ``pallas_call(name=)`` (direct
        or through an ops/ wrapper) are names like every other; a kernel
        with no name at all is a finding too."""
        cfg = fixture_config(names=NamesConfig(
            registry_path=f"{FX}/fx_names_registry.py",
            doc_path=f"{FX}/fx_names_doc.md",
        ))
        res = run_lint(cfg, paths=[f"{FX}/fx_device_names.py"],
                       checkers=["telemetry-names"], full=False)
        assert codes_lines(res.findings) == [
            ("DTL041", 13),   # fx_scoop: unregistered scope
            ("DTL041", 17),   # f"fx_ffn.{...}": head matches no scope
            ("DTL041", 20),   # fx_kernel_bwd: unregistered kernel
            ("DTL041", 21),   # pallas_call without name=
            ("DTL041", 22),   # unregistered, through a wrapper
            ("DTL041", 23),   # a kernel's name used as a scope
        ], [f.render() for f in res.findings]

    def test_doc_crosscheck(self):
        res = self.run(full=True)
        dtl042 = [f for f in res.findings if f.code == "DTL042"]
        # fx.wait pins whole-token doc matching: it PREFIXES the
        # documented `fx.wait_s` and must still count as undocumented
        assert [f.anchor for f in dtl042] == ["fx.undocumented", "fx.wait"]


# ------------------------------------------------------------- locks


class TestLocks:
    def run(self):
        return run_lint(fixture_config(), paths=[f"{FX}/fx_locks.py"],
                        checkers=["locks"])

    def test_unguarded_read_and_write(self):
        res = self.run()
        assert codes_lines(res.findings) == [
            ("DTL051", 24),   # write outside the lock
            ("DTL051", 27),   # torn read outside the lock
            ("DTL051", 37),   # malformed table fails LOUD, not silent
            ("DTL051", 43),   # typo'd guarded field __init__ never sets
        ], [f.render() for f in res.findings]

    def test_exemptions(self):
        res = self.run()
        lines = {f.line for f in res.findings}
        assert 11 not in lines and 12 not in lines  # __init__ exempt
        assert 30 not in lines                      # *_locked convention
        assert 21 not in lines                      # locked lambda is fine
        assert [(f.code, f.line) for f in res.suppressed] == [
            ("DTL051", 33),
        ]


# ---------------------------------------------------- repo-level gates


class TestRepoGate:
    def test_lint_check_exits_zero_on_the_repo(self):
        """THE acceptance gate: the whole package is finding-free (or
        explicitly baselined) under all five checkers."""
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "lint.py"), "--check"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0, (
            f"lint --check failed:\n{proc.stdout}\n{proc.stderr}"
        )

    def test_json_mode_emits_parseable_findings(self):
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "lint.py"), "--json",
             f"{FX}/fx_locks.py"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0  # report mode never gates
        recs = [json.loads(line) for line in proc.stdout.splitlines()]
        assert {r["code"] for r in recs} == {"DTL051"}
        assert all(r["key"].startswith(f"{FX}/fx_locks.py::") for r in recs)

    def test_check_mode_fails_on_findings(self):
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "lint.py"), "--check",
             f"{FX}/fx_locks.py"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 1
        assert "DTL051" in proc.stdout

    def test_check_mode_fails_on_stale_baseline(self, tmp_path):
        """The baseline can only shrink: a key whose finding was fixed
        fails the full-scan gate until it is pruned (a lingering dead
        key could mask a future same-shape violation)."""
        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps([
            {"key": "gone/file.py::DTL011::fixed_long_ago:If",
             "note": "stale"},
        ]))
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "lint.py"), "--check",
             "--baseline", str(bl)],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 1, proc.stderr
        assert "stale baseline entry" in proc.stderr

    def test_guarded_by_tables_are_declared(self):
        """The seeded lock-discipline contracts exist where PR 6's
        thread-safety lives: Router, the metrics registries, the
        telemetry ring."""
        import ast

        want = {
            "dalle_pytorch_tpu/serving/router.py": {"Router"},
            "dalle_pytorch_tpu/utils/metrics.py": {
                "Counters", "Gauges", "Histograms", "Histogram",
            },
            "dalle_pytorch_tpu/utils/telemetry.py": {"Telemetry"},
        }
        for path, classes in want.items():
            tree = ast.parse((REPO / path).read_text())
            declared = {
                cls.name
                for cls in ast.walk(tree) if isinstance(cls, ast.ClassDef)
                if any(
                    isinstance(n, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == "_GUARDED_BY"
                        for t in n.targets
                    )
                    for n in cls.body
                )
            }
            assert classes <= declared, (path, declared)

    def test_fault_registry_is_one_to_one(self):
        """Every KNOWN_SITES entry has a production take-site and a
        test/tool drill — the cross-reference the checker enforces
        (finding nothing IS the assertion)."""
        res = run_lint(default_config(str(REPO)),
                       checkers=["fault-sites"])
        assert res.clean, [f.render() for f in res.findings]

    def test_telemetry_names_match_registry_and_docs(self):
        res = run_lint(default_config(str(REPO)),
                       checkers=["telemetry-names"])
        assert res.clean, [f.render() for f in res.findings]


# ------------------------------------------------------- trace stage


_TRACE_CACHE: dict = {}


def trace_fixture_raw():
    """Audit the fixture registry once per session (the audit imports jax
    and traces every fixture jit — cached so each pinned-code test below
    reads the same result instead of re-tracing)."""
    if "raw" not in _TRACE_CACHE:
        from lint.trace import run_trace  # imports jax (fixture jits)

        _TRACE_CACHE["raw"] = run_trace(
            str(REPO),
            f"{FX}/fx_trace_registry.py",
            f"{FX}/fx_trace_contract.json",
        )
    return _TRACE_CACHE["raw"]


def trace_fixture_result(baseline=None):
    """Fold the fixture trace findings through the SHARED suppression/
    baseline machinery (run_lint extra_findings) — the same path the CLI
    composes the two stages on."""
    findings, reports = trace_fixture_raw()
    cfg = fixture_config(baseline_path=baseline)
    res = run_lint(cfg, paths=[f"{FX}/fx_trace_registry.py"], checkers=[],
                   full=True, extra_findings=findings)
    return res, reports


class TestTrace:
    """Fixture corpus for the --trace stage (tools/lint/trace/): >=2
    seeded violations per DTL1xx checker family at pinned codes and
    anchors, plus the suppression/baseline escapes and the
    contract-file round trip."""

    def test_exact_codes_and_anchors(self):
        res, _ = trace_fixture_result()
        got = sorted((f.code, f.anchor) for f in res.findings)
        assert got == [
            ("DTL101", "fx.uncommitted"),          # registered, uncommitted
            ("DTL102", "fx.ghost"),                # contract-only: stale
            ("DTL111", "fx.drift:w6"),             # unlisted signature
            ("DTL112", "fx.drift:float32[12]"),    # stale signature
            ("DTL113", "fx.drift"),                # over signature budget
            ("DTL121", "fx.not_donated:x"),        # declared, not donated
            ("DTL121", "fx.undeclared:undeclared"),  # donated, undeclared
            ("DTL122", "fx.plain"),                # declared on non-jit
            ("DTL122", "fx.unaliased"),            # donated, unaliased
            ("DTL131", "fx.chatty"),               # 2 callbacks > 0
            ("DTL132", "fx.chatty"),               # 3 visible outputs > 1
            ("DTL141", "fx.fat"),                  # HBM over budget
            ("DTL141", "fx.fat2"),                 # HBM over budget
        ], [f.render() for f in res.findings]

    def test_inline_suppression(self):
        # fx.fat3 exceeds its byte budget exactly like fx.fat/fat2 but
        # carries `# dtl: disable=DTL141` on its def line — the shared
        # escape hatch works for trace findings too
        res, _ = trace_fixture_result()
        assert [(f.code, f.anchor) for f in res.suppressed] == [
            ("DTL141", "fx.fat3"),
        ]

    def test_findings_anchor_on_def_lines(self):
        res, _ = trace_fixture_result()
        src = (REPO / FX / "fx_trace_registry.py").read_text().splitlines()
        want = next(
            i for i, line in enumerate(src, 1)
            if line.startswith("def _not_donated")
        )
        f = next(x for x in res.findings if x.anchor == "fx.not_donated:x")
        assert f.line == want and f.path == f"{FX}/fx_trace_registry.py"

    def test_clean_entry_stays_clean(self):
        # fx.donate_ok donates, aliases, and matches its contract exactly
        res, _ = trace_fixture_result()
        assert not any("fx.donate_ok" in f.anchor for f in res.findings)

    def test_baseline_grandfathers_with_stable_key(self, tmp_path):
        bl = tmp_path / "trace_baseline.json"
        bl.write_text(json.dumps([{
            "key": f"{FX}/fx_trace_registry.py::DTL113::fx.drift",
            "note": "fixture: grandfathered signature-budget overrun",
        }]))
        res, _ = trace_fixture_result(baseline=str(bl))
        assert ("DTL113", "fx.drift") not in [
            (f.code, f.anchor) for f in res.findings
        ]
        assert [(f.code, f.anchor) for f in res.baselined] == [
            ("DTL113", "fx.drift"),
        ]
        assert res.stale_baseline == []

    def test_emit_contract_round_trip(self):
        """A contract regenerated from the current registry must clear
        every budget/signature finding — what survives is exactly the
        donation drift between what the registry DECLARES and what the
        traced programs DO (that divergence is in the code, not the
        contract, so re-emitting cannot paper over it)."""
        from lint.trace import check_reports, emit_contract

        _, reports = trace_fixture_raw()
        fresh = emit_contract(reports)
        findings = check_reports(
            reports, fresh, "fresh.json", str(REPO)
        )
        got = sorted((f.code, f.anchor) for f in findings)
        assert got == [
            ("DTL121", "fx.not_donated:x"),
            ("DTL121", "fx.undeclared:undeclared"),
            ("DTL122", "fx.plain"),
            ("DTL122", "fx.unaliased"),
        ], got

    def test_trace_baseline_key_not_stale_for_ast_only_scan(self, tmp_path):
        """A baselined DTL1xx (trace-stage) key must NOT be judged stale
        by a scan that never ran the trace stage — otherwise one
        legitimately grandfathered trace finding would fail every plain
        `--check` run (including the smoke gates' stage-1 AST
        pre-flight). It IS judged when the trace stage ran (an empty
        extra_findings list means 'ran, found nothing')."""
        from lint import Finding  # noqa: F401  (core import side)

        bl = tmp_path / "bl.json"
        bl.write_text(json.dumps([{
            "key": f"{FX}/fx_trace_registry.py::DTL141::fx.gone",
            "note": "trace finding fixed long ago",
        }]))
        cfg = fixture_config(baseline_path=str(bl))
        # AST-only (trace stage did not run): unseen, not stale
        res = run_lint(cfg, paths=[f"{FX}/fx_purity.py"], checkers=[],
                       full=True, extra_findings=None)
        assert res.stale_baseline == []
        # trace stage ran and produced nothing matching: NOW it is stale
        res = run_lint(cfg, paths=[f"{FX}/fx_purity.py"], checkers=[],
                       full=True, extra_findings=[])
        assert res.stale_baseline == [
            f"{FX}/fx_trace_registry.py::DTL141::fx.gone"
        ]

    def test_trace_suppression_survives_narrowed_ast_paths(self):
        """Trace findings anchor in files the AST stage may not have
        scanned (narrowed paths); their inline suppressions must load on
        demand instead of silently going live."""
        from lint import Finding

        src = (REPO / FX / "fx_trace_registry.py").read_text().splitlines()
        line = next(
            i for i, l in enumerate(src, 1) if l.startswith("def _fat3")
        )
        fake = Finding(
            code="DTL141", path=f"{FX}/fx_trace_registry.py", line=line,
            message="synthetic overrun", anchor="fx.fat3",
        )
        res = run_lint(
            fixture_config(), paths=[f"{FX}/fx_purity.py"], checkers=[],
            extra_findings=[fake],
        )
        assert res.findings == []
        assert [(f.code, f.anchor) for f in res.suppressed] == [
            ("DTL141", "fx.fat3"),
        ]

    def test_hbm_report_shape(self):
        """The per-entry report carries the per-jit HBM decomposition
        the DESIGN.md §11 operator workflow reads."""
        _, reports = trace_fixture_raw()
        rep = next(r for r in reports if r["name"] == "fx.donate_ok")
        sig = rep["signatures"][0]
        assert sig["arg_bytes"] == 64          # two f32[8]
        assert sig["out_bytes"] == 36          # f32[8] + scalar
        assert sig["aliased_bytes"] == 32      # donated x aliases out[0]
        assert sig["hbm_bytes"] == 68
        assert rep["max_host_visible_outputs"] == 1


class TestTraceCLI:
    """--trace through the real CLI: composition with the AST stage in
    one exit code, and THE acceptance gate on the repo contract."""

    def test_fixture_registry_fails_check(self):
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "lint.py"),
             "--trace", "--check",
             "--trace-registry", f"{FX}/fx_trace_registry.py",
             "--contract", f"{FX}/fx_trace_contract.json",
             f"{FX}/fx_trace_registry.py"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 1, proc.stderr
        for code in ("DTL111", "DTL121", "DTL122", "DTL131", "DTL132",
                     "DTL141"):
            assert code in proc.stdout, (code, proc.stdout)
        # the suppressed fx.fat3 overrun must NOT be a live finding
        assert "fx.fat3" not in proc.stdout

    def test_repo_trace_gate_exits_zero(self):
        """THE acceptance gate: every registered entry point of the real
        package matches tools/trace_contracts.json — signatures closed,
        donation aliased, readbacks bounded, HBM inside budget."""
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "lint.py"),
             "--trace", "--check"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0, (
            f"lint --trace --check failed:\n{proc.stdout}\n{proc.stderr}"
        )

    def test_emit_contract_matches_committed(self):
        """The committed contract is exactly what --emit-contract derives
        from the current registry — no drift between file and tree."""
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "lint.py"),
             "--trace", "--emit-contract"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr
        emitted = json.loads(proc.stdout)
        committed = json.loads(
            (REPO / "tools" / "trace_contracts.json").read_text()
        )
        assert emitted == committed


# --------------------------------------------------- lock-order cycles


class TestLockOrder:
    """DTL052 fixture corpus (tests/fixtures_lint/fx_lock_order.py):
    order-inversion cycles, the non-reentrant self-deadlock, the RLock
    reentry exemption, and the two escape hatches."""

    def run(self, baseline=None):
        cfg = fixture_config(baseline_path=baseline)
        return run_lint(cfg, paths=[f"{FX}/fx_lock_order.py"],
                        checkers=["locks"])

    def test_exact_codes_and_lines(self):
        res = self.run()
        assert codes_lines(res.findings) == [
            ("DTL052", 23),   # CycleAB: a->b vs b->a inversion
            ("DTL052", 38),   # SelfDeadlock: plain-Lock re-acquire
            ("DTL052", 78),   # CycleBaselined (no baseline in this run)
        ], [f.render() for f in res.findings]

    def test_anchors_name_the_cycle(self):
        res = self.run()
        assert sorted(f.anchor for f in res.findings) == [
            "CycleAB:_a->_b",
            "CycleBaselined:_e->_f",
            "SelfDeadlock:_m->_m",
        ]

    def test_rlock_reentry_is_sanctioned(self):
        # ReentrantOK nests an RLock under itself — the Router pattern —
        # and must stay clean
        res = self.run()
        assert not any("ReentrantOK" in f.anchor for f in res.findings)

    def test_closure_acquisition_is_not_an_edge(self):
        # a nested def DEFINED under a lock executes later without it:
        # ClosureNotAnEdge's worker must not create a phantom g->h edge
        # (its h->g order elsewhere is the only real one — no cycle)
        res = self.run()
        assert not any("ClosureNotAnEdge" in f.anchor
                       for f in res.findings)

    def test_inline_suppression(self):
        res = self.run()
        assert [(f.code, f.line) for f in res.suppressed] == [
            ("DTL052", 59),
        ]
        assert not any("CycleSuppressed" in f.anchor for f in res.findings)

    def test_baseline_grandfathers(self, tmp_path):
        bl = tmp_path / "bl.json"
        bl.write_text(json.dumps([{
            "key": f"{FX}/fx_lock_order.py::DTL052::CycleBaselined:_e->_f",
            "note": "fixture: grandfathered lock-order cycle",
        }]))
        res = self.run(baseline=str(bl))
        assert [(f.code, f.anchor) for f in res.baselined] == [
            ("DTL052", "CycleBaselined:_e->_f"),
        ]
        assert not any("CycleBaselined" in f.anchor for f in res.findings)

    def test_repo_lock_classes_are_cycle_free(self):
        """The production lock owners (Router, metrics, telemetry) must
        stay acyclic — finding nothing IS the assertion."""
        res = run_lint(default_config(str(REPO)), checkers=["locks"])
        assert res.clean, [f.render() for f in res.findings]


# ------------------------------------------------------- shard stage


_SHARD_CACHE: dict = {}


def shard_fixture_raw():
    """Audit the fixture shard registry once per session (lowers every
    fixture jit over the 2-device host mesh and compiles the one
    partitioned entry — cached so each pinned-code test below reads the
    same result instead of re-lowering)."""
    if "raw" not in _SHARD_CACHE:
        from lint.shard import run_shard  # imports jax (fixture jits)

        _SHARD_CACHE["raw"] = run_shard(
            str(REPO),
            f"{FX}/fx_shard_registry.py",
            f"{FX}/fx_shard_contract.json",
        )
    return _SHARD_CACHE["raw"]


def shard_fixture_result(baseline=None):
    findings, reports = shard_fixture_raw()
    cfg = fixture_config(baseline_path=baseline)
    res = run_lint(cfg, paths=[f"{FX}/fx_shard_registry.py"], checkers=[],
                   full=True, extra_findings=findings, stages={"shard"})
    return res, reports


class TestShard:
    """Fixture corpus for the --shard stage (tools/lint/shard/): >=2
    seeded violations per DTL15x checker family at pinned codes and
    anchors, plus the suppression/baseline escapes and the
    contract-file round trip."""

    def test_exact_codes_and_anchors(self):
        res, _ = shard_fixture_result()
        got = sorted((f.code, f.anchor) for f in res.findings)
        assert got == [
            ("DTL151", "fx.noisy:all-reduce"),        # over budget
            ("DTL151", "fx.unlisted:collective-permute"),  # unlisted kind
            ("DTL152", "fx.drifted:lowered"),         # rules vs lowered
            ("DTL152", "fx.stale_contract:contract"),  # contract drift
            ("DTL153", "fx.replicated:w1"),           # declared sharded,
            ("DTL153", "fx.replicated:w2"),           # lowered replicated
            ("DTL154", "fx.resharder"),               # 2 constraints > 0
            ("DTL154", "fx.resharder2"),              # 3 constraints > 1
            ("DTL155", "fx.ghost"),                   # contract-only: stale
            ("DTL155", "fx.uncommitted"),             # registered, uncommitted
        ], [f.render() for f in res.findings]

    def test_findings_anchor_on_def_lines(self):
        res, _ = shard_fixture_result()
        f = next(x for x in res.findings if x.anchor == "fx.resharder")
        assert f.line == 86 and f.path == f"{FX}/fx_shard_registry.py"
        ghost = next(x for x in res.findings if x.anchor == "fx.ghost")
        assert ghost.path == f"{FX}/fx_shard_contract.json"

    def test_inline_suppression(self):
        # fx.sneaky is over its all-reduce budget exactly like fx.noisy
        # but carries `# dtl: disable=DTL151` on its def line
        res, _ = shard_fixture_result()
        assert [(f.code, f.anchor) for f in res.suppressed] == [
            ("DTL151", "fx.sneaky:all-reduce"),
        ]

    def test_clean_entries_stay_clean(self):
        # fx.clean (lowered) and fx.partitioned (compiled, with its one
        # contracted GSPMD all-reduce) match the contract exactly
        res, reports = shard_fixture_result()
        for name in ("fx.clean", "fx.partitioned"):
            assert not any(name in f.anchor for f in res.findings)
        part = next(r for r in reports if r["name"] == "fx.partitioned")
        assert part["level"] == "partitioned"
        assert part["collectives"] == {"all-reduce": 1}

    def test_baseline_grandfathers_with_stable_key(self, tmp_path):
        bl = tmp_path / "shard_baseline.json"
        bl.write_text(json.dumps([{
            "key": f"{FX}/fx_shard_registry.py::DTL154::fx.resharder2",
            "note": "fixture: grandfathered reshard-budget overrun",
        }]))
        res, _ = shard_fixture_result(baseline=str(bl))
        assert ("DTL154", "fx.resharder2") not in [
            (f.code, f.anchor) for f in res.findings
        ]
        assert [(f.code, f.anchor) for f in res.baselined] == [
            ("DTL154", "fx.resharder2"),
        ]
        assert res.stale_baseline == []

    def test_emit_contract_round_trip(self):
        """A contract regenerated from the current registry must clear
        every budget/1:1 finding — what survives is exactly the
        code-level drift: DTL152's rules-vs-lowered disagreement and
        DTL153's accidental replication live in the code, not the
        contract, so re-emitting cannot paper over them."""
        from lint.shard import check_reports, emit_contract

        _, reports = shard_fixture_raw()
        fresh = emit_contract(reports)
        findings = check_reports(reports, fresh, "fresh.json", str(REPO))
        got = sorted((f.code, f.anchor) for f in findings)
        assert got == [
            ("DTL152", "fx.drifted:lowered"),
            ("DTL153", "fx.replicated:w1"),
            ("DTL153", "fx.replicated:w2"),
        ], got

    def test_shard_baseline_key_unseen_unless_shard_ran(self, tmp_path):
        """A baselined DTL15x key must NOT be judged stale by a scan
        that never ran the shard stage — a trace-only `--trace --check`
        run (stages={'trace'}) treats it as unseen, a shard run
        (stages={'shard'}) judges it."""
        bl = tmp_path / "bl.json"
        key = f"{FX}/fx_shard_registry.py::DTL151::fx.gone"
        bl.write_text(json.dumps([{"key": key, "note": "fixed long ago"}]))
        cfg = fixture_config(baseline_path=str(bl))
        res = run_lint(cfg, paths=[f"{FX}/fx_purity.py"], checkers=[],
                       full=True, extra_findings=[], stages={"trace"})
        assert res.stale_baseline == []
        res = run_lint(cfg, paths=[f"{FX}/fx_purity.py"], checkers=[],
                       full=True, extra_findings=[], stages={"shard"})
        assert res.stale_baseline == [key]

    def test_serving_entries_commit_zero_collectives(self):
        """The committed repo contract IS the 'no collectives in
        serving' baseline ROADMAP item 1 will renegotiate: every
        serving.* entry must budget an empty collective map, and the
        seven train.* mesh-kind entries must all be present (sp lowers
        twice — ring path and dual-balanced block-sparse path)."""
        committed = json.loads(
            (REPO / "tools" / "shard_contracts.json").read_text()
        )
        entries = committed["entries"]
        kinds = {n.split(".", 1)[1] for n in entries if n.startswith("train.")}
        assert kinds == {"dp", "fsdp", "tp", "sp", "sp_sparse", "pp", "ep"}
        serving = [n for n in entries if n.startswith("serving.")]
        assert len(serving) >= 10
        for name in serving:
            assert entries[name]["collectives"] == {}, name
        # the sharded mesh kinds actually shard: fsdp/tp commit sharded
        # param specs and nonzero collective budgets
        for kind in ("fsdp", "tp"):
            e = entries[f"train.{kind}"]
            assert e["param_specs"], kind
            assert e["collectives"], kind


class TestShardCLI:
    """--shard through the real CLI: composition in one exit code, and
    THE acceptance gate on the repo contract."""

    def test_fixture_registry_fails_check(self):
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "lint.py"),
             "--shard", "--check",
             "--shard-registry", f"{FX}/fx_shard_registry.py",
             "--shard-contract", f"{FX}/fx_shard_contract.json",
             f"{FX}/fx_shard_registry.py"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 1, proc.stderr
        for code in ("DTL151", "DTL152", "DTL153", "DTL154", "DTL155"):
            assert code in proc.stdout, (code, proc.stdout)
        # the suppressed fx.sneaky overrun must NOT be a live finding
        assert "fx.sneaky" not in proc.stdout

    def test_repo_shard_gate_exits_zero(self):
        """THE acceptance gate: make_train_step under all six mesh kinds
        and every registered serving jit match
        tools/shard_contracts.json — collective budgets closed, specs
        agreed, nothing accidentally replicated, reshard sites
        budgeted."""
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "lint.py"),
             "--shard", "--check"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0, (
            f"lint --shard --check failed:\n{proc.stdout}\n{proc.stderr}"
        )

    def test_emit_contract_matches_committed(self):
        """The committed shard contract is exactly what --emit-contract
        derives from the current registry — the pinned round trip."""
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "lint.py"),
             "--shard", "--emit-contract"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr
        emitted = json.loads(proc.stdout)
        committed = json.loads(
            (REPO / "tools" / "shard_contracts.json").read_text()
        )
        assert emitted == committed

    def test_emit_contract_requires_exactly_one_stage(self):
        for args in (["--emit-contract"],
                     ["--trace", "--shard", "--emit-contract"]):
            proc = subprocess.run(
                [sys.executable, str(REPO / "tools" / "lint.py"), *args],
                capture_output=True, text=True, cwd=REPO,
            )
            assert proc.returncode == 2, (args, proc.stdout)
            assert "exactly one of" in proc.stderr
