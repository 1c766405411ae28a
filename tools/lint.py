#!/usr/bin/env python
"""dalle-tpu-lint CLI: AST + trace + shard-level invariant checks.

Usage::

    python tools/lint.py [--json] [--check] [--checks a,b,...]
                         [--trace] [--shard] [--emit-contract] [paths...]

* no flags: report findings (human-readable), always exit 0;
* ``--check``: exit 1 when any non-suppressed, non-baselined finding
  survives — the release-gate / CI mode (tools/serve_smoke.py,
  tools/telemetry_smoke.py and tools/chaos_soak.py run this as their
  pre-flight);
* ``--json``: one JSON object per finding on stdout;
* ``--checks``: comma list from {purity, layering, fault-sites,
  telemetry-names, locks} (default: all);
* ``--trace``: ALSO run the semantic stage (tools/lint/trace/): trace
  every registered jit entry point to a ClosedJaxpr over abstract avals
  and audit compile signatures, buffer donation/aliasing, host
  syncs/readbacks, and static HBM footprints against the committed
  ``tools/trace_contracts.json`` (DTL1xx codes). This stage imports jax
  and the package (still CPU-only, no device execution) and composes
  with the AST stage in one exit code;
* ``--shard``: ALSO run the mesh stage (tools/lint/shard/): lower
  ``make_train_step`` under each of the six mesh kinds over a forced
  8-device host platform (plus every serving jit under its 1-device
  placement) and audit collective budgets, in/out sharding specs,
  accidental replication, and in-program reshard constraints against
  the committed ``tools/shard_contracts.json`` (DTL15x codes). Host CPU
  only — no TPU anywhere; composes with the other stages in one exit
  code;
* ``--emit-contract`` (with exactly one of ``--trace``/``--shard``):
  print the contract JSON derived from the current registry to stdout
  and exit — the blessed update after an intentional signature/
  footprint/budget change;
* ``--trace-registry`` / ``--contract`` and ``--shard-registry`` /
  ``--shard-contract``: override the registry module / contract file
  per stage (fixture tests use these);
* ``paths``: repo-relative files/dirs for the AST stage (default: the
  package + CLI entrypoints — see tools/lint/config.py). The trace and
  shard stages always audit every registered entry point.

Finding codes, the suppression comment (``# dtl: disable=DTL0xx``), and
the baseline policy (tools/lint_baseline.json) are documented in
docs/DESIGN.md §11, tools/lint/__init__.py (DTL0xx),
tools/lint/trace/__init__.py (DTL1xx), and tools/lint/shard/__init__.py
(DTL15x). Without ``--trace``/``--shard`` the linter is stdlib-only and
never imports the package it checks — it runs in milliseconds with no
jax in sight.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_TOOLS_DIR)
# the tools/lint/ package shadows this script on sys.path (regular
# packages win over same-named modules in the same directory)
sys.path.insert(0, _TOOLS_DIR)

from lint import default_config, run_lint  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tools/lint.py",
        description="dalle-tpu-lint: AST-based invariant checks",
    )
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as JSON lines")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on any live finding (gate mode)")
    ap.add_argument("--checks", default=None,
                    help="comma list of checkers to run (default: all)")
    ap.add_argument("--baseline", default=None,
                    help="override the baseline file "
                         "(default: tools/lint_baseline.json)")
    ap.add_argument("--trace", action="store_true",
                    help="also run the trace-level jaxpr/lowering audit "
                         "(DTL1xx; imports jax, CPU-only)")
    ap.add_argument("--shard", action="store_true",
                    help="also run the mesh-aware sharding/collective "
                         "audit (DTL15x; imports jax, forces an 8-device "
                         "host platform, CPU-only)")
    ap.add_argument("--emit-contract", action="store_true",
                    dest="emit_contract",
                    help="with --trace or --shard: print that stage's "
                         "contract JSON derived from the current registry "
                         "and exit")
    ap.add_argument("--contract", default=None,
                    help="override the trace contract file "
                         "(default: tools/trace_contracts.json)")
    ap.add_argument("--trace-registry", default=None, dest="trace_registry",
                    help="override the trace registry module path")
    ap.add_argument("--shard-contract", default=None, dest="shard_contract",
                    help="override the shard contract file "
                         "(default: tools/shard_contracts.json)")
    ap.add_argument("--shard-registry", default=None, dest="shard_registry",
                    help="override the shard registry module path")
    ap.add_argument("paths", nargs="*",
                    help="repo-relative files/dirs (default: scan roots)")
    args = ap.parse_args(argv)

    config = default_config(_REPO_ROOT)
    if args.baseline is not None:
        import dataclasses

        config = dataclasses.replace(config, baseline_path=args.baseline)
    checkers = (
        [c.strip() for c in args.checks.split(",") if c.strip()]
        if args.checks else None
    )

    if args.emit_contract and args.trace == args.shard:
        print("lint: --emit-contract requires exactly one of --trace / "
              "--shard (each stage owns its own contract file)",
              file=sys.stderr)
        return 2

    extra_findings = None
    stages = set()
    if args.trace or args.shard:
        # env prepared HERE, before any jax import: the semantic stages
        # pull in jax and the audited package; the AST-only invocation
        # stays stdlib-pure and millisecond-fast. CPU-pinned: the audits
        # are abstract/host-only (eval_shape/make_jaxpr/lower + host-CPU
        # compiles for the mesh stage) and must not grab an accelerator —
        # assigned, not defaulted: a chip host may export JAX_PLATFORMS=tpu.
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.shard:
            # the mesh audit needs a multi-device host platform (the
            # test suite's own 8-virtual-device setup); must be set
            # before jax initializes its backend
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=8"
                ).strip()
        extra_findings = []
    if args.trace:
        from lint.trace import emit_contract, run_trace, trace_reports_only

        tcfg = config.trace
        registry = args.trace_registry or tcfg.registry_path
        contract = args.contract or tcfg.contract_path
        try:
            if args.emit_contract:
                reports = trace_reports_only(_REPO_ROOT, registry)
                print(json.dumps(emit_contract(reports), indent=2))
                return 0
            trace_findings, reports = run_trace(
                _REPO_ROOT, registry, contract
            )
        except (ImportError, ValueError, OSError, RuntimeError,
                SyntaxError) as e:
            print(f"lint: trace stage error: {e}", file=sys.stderr)
            return 2
        extra_findings.extend(trace_findings)
        stages.add("trace")
        if not args.as_json:
            # the per-jit report (signatures / readbacks / HBM) goes to
            # stderr: it is operator context, not findings
            for r in sorted(reports, key=lambda r: r["name"]):
                print(
                    f"lint: trace {r['name']}: "
                    f"{len(r['signatures'])} signature(s), "
                    f"{r['max_callbacks']} callback(s), "
                    f"{r['max_host_visible_outputs']} host-visible "
                    f"output(s), {r['max_hbm_bytes']} HBM bytes "
                    f"(aliased {r['signatures'][0]['aliased_bytes']})",
                    file=sys.stderr,
                )
    if args.shard:
        from lint.shard import (
            emit_contract as emit_shard_contract,
            run_shard,
            shard_reports_only,
        )

        scfg = config.shard
        registry = args.shard_registry or scfg.registry_path
        contract = args.shard_contract or scfg.contract_path
        try:
            if args.emit_contract:
                reports = shard_reports_only(_REPO_ROOT, registry)
                print(json.dumps(emit_shard_contract(reports), indent=2))
                return 0
            shard_findings, reports = run_shard(
                _REPO_ROOT, registry, contract
            )
        except (ImportError, ValueError, OSError, RuntimeError,
                SyntaxError, AssertionError) as e:
            print(f"lint: shard stage error: {e}", file=sys.stderr)
            return 2
        extra_findings.extend(shard_findings)
        stages.add("shard")
        if not args.as_json:
            # per-entry mesh report to stderr: operator context
            for r in sorted(reports, key=lambda r: r["name"]):
                mesh = ",".join(f"{k}={v}" for k, v in r["mesh"].items())
                coll = (", ".join(f"{k}:{v}" for k, v in
                                  sorted(r["collectives"].items()))
                        or "none")
                print(
                    f"lint: shard {r['name']} [{mesh or '1-device'}] "
                    f"({r['level']}): collectives {coll}; "
                    f"{r['reshard_constraints']} reshard constraint(s); "
                    f"{r['sharded_in_args']}/{r['in_args']} sharded args",
                    file=sys.stderr,
                )

    try:
        result = run_lint(config, paths=args.paths or None, checkers=checkers,
                          extra_findings=extra_findings,
                          stages=stages or None)
    except (ValueError, OSError, SyntaxError) as e:
        print(f"lint: error: {e}", file=sys.stderr)
        return 2

    if args.as_json:
        for f in result.findings:
            print(json.dumps(f.to_json()))
    else:
        for f in result.findings:
            print(f.render())
    n = len(result.findings)
    summary = (
        f"lint: {n} finding{'s' if n != 1 else ''} "
        f"({len(result.suppressed)} suppressed, "
        f"{len(result.baselined)} baselined)"
    )
    print(summary, file=sys.stderr)
    for key in result.stale_baseline:
        # a stale entry means the finding it excused is gone: prune it
        print(f"lint: stale baseline entry {key} — remove it from the "
              f"baseline file", file=sys.stderr)
    if args.check and (result.findings or result.stale_baseline):
        # stale entries FAIL the gate too: the baseline can only shrink,
        # and a dead key must not linger to mask a future same-shape
        # violation
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
