"""DTL041-042: telemetry-name registry cross-reference.

Every counter/gauge/histogram/span/event name in the package must come
from the single registry (``utils/telemetry_names.py``) — a typo'd
metric name is a series nobody's dashboard, bench mapping, or smoke gate
ever finds, failing silently forever. The registry is per-kind, so a
counter name used as a gauge is also a finding.

Checked call shapes (first positional argument):

* ``counters.inc/get(...)``, ``gauges.set/get(...)``,
  ``histograms.observe/get(...)`` — receiver's last attribute component
  must literally be ``counters``/``gauges``/``histograms`` (the module
  registries or an engine's ``self.counters`` child view);
* ``TELEMETRY.begin/span(...)`` (spans) and ``TELEMETRY.event(...)``;
* ``jax.named_scope(...)`` (``DEVICE_SCOPES``: the names the jitted
  steps put on every device event of a profiler trace) and the ``name=``
  keyword of ``pl.pallas_call(...)`` or of one of the ops/ wrappers that
  forward it (``KERNEL_NAMES``). A ``pallas_call`` with no ``name=`` is
  a finding too: an unnamed kernel is ``fn`` in every trace.

Literal names must be registered exactly. f-strings with a literal head
(``f"serve.rejected.{reason.value}"``) must have a head that prefixes at
least one registered name of that kind — dynamic tails stay checkable at
the namespace level without enumerating runtime values. Histogram reads
additionally accept ``<span>_s`` for any registered span (the duration
histograms utils/telemetry.py derives automatically).

**DTL042** closes the docs loop: every registered name must appear in
the docs/DESIGN.md §9 name tables, so the registry, the code, and the
operator documentation cannot drift apart (`*` wildcards in the doc are
not honored — names are enumerated).
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set

from .core import (
    Finding,
    SourceFile,
    assign_lineno,
    fstring_prefix,
    parse_frozensets,
    str_const,
)

_REGISTRY_SETS = ("SPANS", "EVENTS", "COUNTERS", "GAUGES", "HISTOGRAMS",
                  "DEVICE_SCOPES", "KERNEL_NAMES")
# ops/ helpers that hand their ``name=`` keyword on to ``pl.pallas_call``
_KERNEL_CALL_WRAPPERS = {"_call", "_call_plain", "_pair_call"}

# receiver last-component -> (checked methods, registry kind)
_RECEIVERS = {
    "counters": ({"inc", "get"}, "COUNTERS"),
    "gauges": ({"set", "get"}, "GAUGES"),
    "histograms": ({"observe", "get"}, "HISTOGRAMS"),
}
_TELEMETRY_METHODS = {
    "begin": "SPANS",
    "span": "SPANS",
    "event": "EVENTS",
}


def _kind_word(kind: str) -> str:
    """'COUNTERS' -> 'counter', 'DEVICE_SCOPES' -> 'device scope'."""
    return kind.lower().replace("_", " ")[:-1]


def _receiver_tail(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def _load_registry(path: str) -> Dict[str, Set[str]]:
    sets = parse_frozensets(path, _REGISTRY_SETS)
    return {k: sets.get(k, set()) for k in _REGISTRY_SETS}


def check(files: Sequence[SourceFile], config,
          full: bool = True) -> List[Finding]:
    nc = config.names
    if nc is None:
        return []
    registry_ab = os.path.join(config.repo_root, nc.registry_path)
    reg = _load_registry(registry_ab)
    all_names: Set[str] = set().union(*reg.values())
    if not all_names:
        return [Finding(
            "DTL041", nc.registry_path, 1,
            "could not extract any name sets from the telemetry-name "
            "registry", anchor="registry",
        )]
    # span-duration histograms are derived, not declared twice
    hist_names = reg["HISTOGRAMS"] | {s + "_s" for s in reg["SPANS"]}
    kind_names = dict(reg)
    kind_names["HISTOGRAMS"] = hist_names

    findings: List[Finding] = []
    for sf in files:
        if sf.path == nc.registry_path:
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            callee = _receiver_tail(fn)
            arg = None
            kind = None
            if callee == "pallas_call" or callee in _KERNEL_CALL_WRAPPERS:
                kind = "KERNEL_NAMES"
                arg = next(
                    (kw.value for kw in node.keywords if kw.arg == "name"),
                    None,
                )
                if arg is None:
                    if callee == "pallas_call":
                        findings.append(Finding(
                            "DTL041", sf.path, node.lineno,
                            "pallas_call without name=: the kernel is "
                            "anonymous in every profiler trace — name it "
                            f"and register the name in {nc.registry_path}",
                            anchor="KERNEL_NAMES:<unnamed>",
                        ))
                    continue
            elif callee == "named_scope" and node.args:
                kind = "DEVICE_SCOPES"
                arg = node.args[0]
            elif isinstance(fn, ast.Attribute) and node.args:
                tail = _receiver_tail(fn.value)
                if tail in _RECEIVERS:
                    methods, kind_key = _RECEIVERS[tail]
                    if fn.attr in methods:
                        kind = kind_key
                elif tail == "TELEMETRY" and fn.attr in _TELEMETRY_METHODS:
                    kind = _TELEMETRY_METHODS[fn.attr]
                arg = node.args[0]
            if kind is None:
                continue
            name = str_const(arg)
            valid = kind_names[kind]
            if name is not None:
                if name not in valid:
                    where = (f"registered as "
                             f"{', '.join(sorted(k for k, v in kind_names.items() if name in v))}"
                             if name in set().union(*kind_names.values())
                             else "not in the registry")
                    findings.append(Finding(
                        "DTL041", sf.path, node.lineno,
                        f"telemetry name {name!r} used as {_kind_word(kind)} "
                        f"is {where} — add it to "
                        f"{nc.registry_path} (and docs §9) or fix the typo",
                        anchor=f"{kind}:{name}",
                    ))
                continue
            prefix = fstring_prefix(arg)
            if prefix is None:
                continue  # a variable name: not statically checkable
            if not prefix:
                continue  # f-string with no literal head (e.g. f"{name}_s")
            if not any(v.startswith(prefix) for v in valid):
                findings.append(Finding(
                    "DTL041", sf.path, node.lineno,
                    f"dynamic telemetry name with head {prefix!r} matches "
                    f"no registered {_kind_word(kind)} — register the expanded "
                    f"names or fix the namespace",
                    anchor=f"{kind}:{prefix}*",
                ))

    # DTL042: registry entries absent from the docs name tables (a
    # registry-completeness direction — full scans only, like DTL032/033)
    if not full:
        return findings
    doc_ab = os.path.join(config.repo_root, nc.doc_path)
    section = _doc_section(doc_ab, nc.doc_section)
    # documented = appears as a whole backtick-quoted token (optionally
    # with a label suffix, `name{replica=i}`). A raw substring test
    # would let a name that PREFIXES another documented name (router.drain
    # vs router.drained) pass undocumented.
    spans = set(re.findall(r"`([^`]+)`", section))
    reg_line = assign_lineno(registry_ab, "SPANS")

    def documented(name: str) -> bool:
        return name in spans or any(
            s.startswith(name + "{") for s in spans
        )

    for kind in _REGISTRY_SETS:
        for name in sorted(reg[kind]):
            if not documented(name):
                findings.append(Finding(
                    "DTL042", nc.registry_path, reg_line,
                    f"registered {_kind_word(kind)} {name!r} is not "
                    f"documented in {nc.doc_path} {nc.doc_section}* — "
                    f"add it to the name tables (backtick-quoted)",
                    anchor=name,
                ))
    return findings


def _doc_section(path: str, heading_prefix: str) -> str:
    """Text of the doc section whose heading starts with
    ``heading_prefix``, up to the next same-level heading."""
    if not os.path.exists(path):
        return ""
    with open(path, encoding="utf-8") as f:
        lines = f.read().splitlines()
    out: List[str] = []
    inside = False
    for line in lines:
        if line.startswith("## "):
            if inside:
                break
            inside = line.startswith(heading_prefix)
            continue
        if inside:
            out.append(line)
    return "\n".join(out)
