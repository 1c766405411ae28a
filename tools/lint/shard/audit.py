"""The shard-stage auditor: mesh-aware lowering analysis + contract checking.

For every registered :class:`~.types.ShardEntry` this module

* lowers the program (the entry's thunk — ``fn.lower(...)`` under the
  entry's mesh; abstract avals, no device execution) and counts, in the
  lowered text, the explicit ``stablehlo.*`` collective ops and the
  ``sdy.sharding_constraint`` sites a program declares mid-flight;
* for ``partitioned`` entries (multi-device meshes) ALSO compiles the
  lowered program on the host-platform device mesh and counts the
  collectives in the post-SPMD-partitioning HLO — the ground truth that
  includes every all-gather/all-reduce the partitioner *inserted*, which
  is exactly what the lowered text cannot show;
* reads per-argument/per-result shardings from jax's OWN objects —
  ``Compiled.input_shardings`` / ``Compiled.output_shardings`` (an
  argument jit dropped as unused comes back ``None``) — never from
  attribute text in the MLIR: that text is a property of the lowering
  dialect (``mhlo.sharding`` under GSPMD, ``sdy.sharding`` under Shardy)
  and changed under this audit once already. Entries that declare
  nothing to judge (no expected shardings, no parameter intents, not
  partitioned) are not compiled and report no shardings.

The per-entry facts are checked against the committed contract file
(``tools/shard_contracts.json``), yielding DTL15x findings (code table
in ``tools/lint/shard/__init__.py``). ``emit_contract`` regenerates the
contract from the current registry — the blessed-update workflow, the
same shape as the trace stage's.

Collective counts come from COMPILED programs, so they depend on the
XLA pass pipeline; the audit pins ``jax_disable_most_optimizations``
(True — the rawest, most deterministic partitioner output, and the
test suite's own setting) for the duration of every audit and restores
it after, so the committed counts are identical in-process under
pytest, under the CLI, and inside the multichip dryrun's provenance
cross-check.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..core import Finding
from ..trace.audit import _def_line, _load_registry
from .types import ShardEntry

# canonical op-kind names (contract keys); left = compiled-HLO spelling,
# right = lowered-StableHLO spelling
_COLLECTIVE_OPS: Tuple[Tuple[str, str], ...] = (
    ("all-gather", "all_gather"),
    ("all-reduce", "all_reduce"),
    ("reduce-scatter", "reduce_scatter"),
    ("collective-permute", "collective_permute"),
    ("all-to-all", "all_to_all"),
)



@contextlib.contextmanager
def _pinned_compile_flags():
    """Pin the XLA pipeline knob the collective counts depend on, restore
    on exit (the audit may run in-process inside pytest or a bench)."""
    import jax

    prev = bool(jax.config._read("jax_disable_most_optimizations"))
    jax.config.update("jax_disable_most_optimizations", True)
    try:
        yield
    finally:
        jax.config.update("jax_disable_most_optimizations", prev)


# ------------------------------------------------------------- shardings


def hlo_sharding_str(sharding, ndim: int) -> str:
    """Canonical text of a jax ``Sharding`` on a rank-``ndim`` array
    (``{replicated}``, ``{devices=[2,1]<=[2]}``, ...). THE one call into
    jax's sharding -> HLO conversion: the registry derives its EXPECTED
    strings through it and the audit its ACTUAL ones, so a jax that
    renames it breaks both in this one place, loudly."""
    return str(sharding._to_xla_hlo_sharding(ndim))


def compiled_shardings(
    lowered, compiled,
) -> Tuple[List[Optional[str]], List[Optional[str]]]:
    """Per-argument and per-result sharding strings of a compiled
    program, flattened in argument order. ``None`` marks an argument jit
    dropped as unused (``keep_unused=False`` is the production default —
    the canonical loss ignores its rng): it never reaches the program, so
    it is neither sharded nor replicated."""
    import jax

    def flat(tree):
        return jax.tree_util.tree_leaves(tree, is_leaf=lambda x: x is None)

    args, kwargs = compiled.input_shardings
    infos = jax.tree_util.tree_leaves(lowered.args_info)
    in_sh = flat(args) + flat(kwargs)
    assert len(in_sh) == len(infos), (len(in_sh), len(infos))
    ins = [
        None if sh is None else hlo_sharding_str(sh, len(info.shape))
        for sh, info in zip(in_sh, infos)
    ]
    out_sh = flat(compiled.output_shardings)
    out_infos = jax.tree_util.tree_leaves(lowered.out_info)
    assert len(out_sh) == len(out_infos), (len(out_sh), len(out_infos))
    outs = [
        None if sh is None else hlo_sharding_str(sh, len(info.shape))
        for sh, info in zip(out_sh, out_infos)
    ]
    return ins, outs


# --------------------------------------------------------------- counting


def lowered_collectives(text: str) -> Dict[str, int]:
    """Explicit collective ops in PRE-partitioning StableHLO — shard_map
    psums/ppermutes the source wrote. GSPMD-inserted collectives do not
    exist yet at this level (see :func:`compiled_collectives`)."""
    out: Dict[str, int] = {}
    for canon, st in _COLLECTIVE_OPS:
        n = len(re.findall(rf"stablehlo\.{st}\b", text))
        if n:
            out[canon] = n
    return out


def compiled_collectives(text: str) -> Dict[str, int]:
    """Collective instructions in POST-partitioning compiled HLO (async
    ``-start`` forms count once; ``-done`` halves don't)."""
    out: Dict[str, int] = {}
    for canon, _ in _COLLECTIVE_OPS:
        # opcode-followed-by-operands; operand REFERENCES (`%all-reduce.3`)
        # never carry the paren, and tuple-shaped results (`= (f32[..],
        # f32[..]) all-to-all(`) rule out anchoring on the result type
        n = len(re.findall(rf"\b{canon}(?:-start)?\(", text))
        if n:
            out[canon] = n
    return out


_CONSTRAINT_RE = re.compile(
    r"sdy\.sharding_constraint\s+%[\w.#]+\s+<@(\w+),\s*\[([^\]]*)\]>"
)


def reshard_constraints(text: str) -> int:
    """In-program ``sdy.sharding_constraint`` sites a PROGRAMMER declared:
    the ``with_sharding_constraint``-shaped reshard point mid-flight —
    each one a potential device-to-device copy, so the count is
    contract-budgeted (DTL154). shard_map boundaries are not constraint
    ops under Shardy (``sdy.manual_computation`` carries its specs as
    attributes), so nothing needs netting out for them. What IS excluded
    are jax's own annotations, which can move nothing: those on the
    ``@empty_mesh`` (PRNG key data), and those inside partial-manual
    regions that name no mesh axis and leave a dimension open (``{?}``)
    or have no dimension at all. A constraint counts when it sits on a
    real mesh and names an axis, or pins every dimension closed (an
    explicit ``P()`` replication)."""
    n = 0
    for mesh, dims in _CONSTRAINT_RE.findall(text):
        if mesh == "empty_mesh":
            continue
        names_axis = '"' in dims
        all_closed = bool(dims.strip()) and "?" not in dims
        if names_axis or all_closed:
            n += 1
    return n


def _digest(items: Sequence[Optional[str]]) -> str:
    joined = "\n".join("-" if x is None else x for x in items)
    return hashlib.sha1(joined.encode()).hexdigest()[:16]


def _spec_repr(spec) -> str:
    return repr(tuple(spec))


# --------------------------------------------------------------- auditing


def audit_shard_entry(ep: ShardEntry) -> Dict[str, Any]:
    """Lower (and for multi-device meshes compile) one entry; return the
    per-entry report the checkers and ``--emit-contract`` consume."""
    judged = bool(ep.in_shardings or ep.out_shardings or ep.param_intents)
    actual_in: List[Optional[str]] = []
    actual_out: List[Optional[str]] = []
    with _pinned_compile_flags():
        lowered = ep.lower()
        text = lowered.as_text()
        explicit = lowered_collectives(text)
        compiled = lowered.compile() if ep.partitioned or judged else None
        if ep.partitioned:
            level = "partitioned"
            collectives = compiled_collectives(compiled.as_text())
        else:
            level = "lowered"
            collectives = dict(explicit)
        if compiled is not None:
            actual_in, actual_out = compiled_shardings(lowered, compiled)

    arg_paths = list(ep.arg_paths)
    in_expected = list(ep.in_shardings)
    # the intent->arg join is only sound when expected and actual args
    # line up 1:1; when they don't, the <arity> DTL152 mismatch below
    # fails the gate LOUDLY and DTL153 must stay silent rather than
    # misjoin to the wrong args
    intents_judgeable = (not ep.in_shardings
                         or len(in_expected) == len(actual_in))

    in_mismatches: List[Tuple[str, str, str]] = []
    out_mismatches: List[Tuple[str, str, str]] = []
    if in_expected:
        if len(in_expected) != len(actual_in):
            in_mismatches.append((
                "<arity>", f"{len(in_expected)} args",
                f"{len(actual_in)} args",
            ))
        for path, exp, act in zip(arg_paths, in_expected, actual_in):
            # act None: jit dropped the argument — nothing to compare
            if exp is not None and act is not None and act != exp:
                in_mismatches.append((path, exp, act))
    if ep.out_shardings:
        if len(ep.out_shardings) != len(actual_out):
            out_mismatches.append((
                "<arity>", f"{len(ep.out_shardings)} results",
                f"{len(actual_out)} results",
            ))
        for path, exp, act in zip(ep.out_paths, ep.out_shardings, actual_out):
            if exp is not None and act != exp:
                out_mismatches.append((path, exp, act or "<none>"))

    # DTL153: rule-engine intent said "sharded", the compiled program says
    # "fully replicated" — join on the flattened argument index. An arg
    # jit DROPPED (None) never reaches the program at all: that is
    # unused, not replicated — skip it rather than misreport.
    replicated_intents: List[Dict[str, Any]] = []
    for intent in ep.param_intents:
        if not intents_judgeable or not intent.get("intent_sharded"):
            continue
        pos = intent.get("arg")
        if pos is None or pos >= len(actual_in):
            continue
        act = actual_in[pos]
        if act is not None and ("replicated" in act or "maximal" in act):
            replicated_intents.append(intent)

    param_specs = {
        intent["path"]: _spec_repr(intent["spec"])
        for intent in ep.param_intents
        if intent.get("intent_sharded")
    }

    return {
        "name": ep.name,
        "path": ep.path,
        "symbol": ep.symbol,
        "mesh": dict(ep.mesh_axes),
        "level": level,
        "collectives": collectives,
        "explicit_collectives": explicit,
        "reshard_constraints": reshard_constraints(text),
        "in_args": sum(1 for s in actual_in if s is not None),
        "out_vals": len(actual_out),
        "sharded_in_args": sum(
            1 for s in actual_in
            if s is not None and "replicated" not in s and "maximal" not in s
        ),
        # over the args that reach the program (dropped ones carry none)
        "in_sharding_digest": _digest(
            [s for s in actual_in if s is not None]
        ),
        "out_sharding_digest": _digest(actual_out),
        "in_mismatches": in_mismatches,
        "out_mismatches": out_mismatches,
        "replicated_intents": replicated_intents,
        "param_specs": param_specs,
    }


# ---------------------------------------------------------- the contract


def load_contract(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if not isinstance(data, dict) or "entries" not in data:
        raise ValueError(
            f"shard contract {path}: want a JSON object with an "
            f'"entries" map, got {type(data).__name__}'
        )
    return data


def emit_contract(reports: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Contract JSON derived from the current registry + audit — commit
    the output after an INTENTIONAL change (a renegotiated collective
    budget, a new sharding rule), exactly like re-baselining. What it
    CANNOT clear: DTL152 lowered-vs-derived drift and DTL153 accidental
    replication live in the code, not the contract."""
    entries: Dict[str, Any] = {}
    for r in sorted(reports, key=lambda r: r["name"]):
        entries[r["name"]] = {
            "path": r["path"],
            "mesh": r["mesh"],
            "level": r["level"],
            "collectives": {
                k: r["collectives"][k] for k in sorted(r["collectives"])
            },
            "max_reshard_constraints": r["reshard_constraints"],
            "in_sharding_digest": r["in_sharding_digest"],
            "out_sharding_digest": r["out_sharding_digest"],
            "sharded_in_args": r["sharded_in_args"],
            "param_specs": {
                k: r["param_specs"][k] for k in sorted(r["param_specs"])
            },
        }
    import jax

    # the counts are a property of (program, partitioner): a jax upgrade
    # may legitimately move them — the stamp says which jax the committed
    # numbers describe, so a red gate after an upgrade reads as "re-emit
    # and review", not as a regression in the program
    return {"version": 1, "jax_version": jax.__version__, "entries": entries}


def check_reports(
    reports: List[Dict[str, Any]],
    contract: Dict[str, Any],
    contract_path: str,
    repo_root: str,
) -> List[Finding]:
    """Compare audit reports against the committed contract; every
    divergence is a DTL15x finding anchored on the entry point."""
    findings: List[Finding] = []
    entries = contract.get("entries", {})
    by_name = {r["name"]: r for r in reports}

    def add(code, rep, msg, anchor_suffix=""):
        findings.append(Finding(
            code=code,
            path=rep["path"],
            line=_def_line(repo_root, rep["path"], rep["symbol"]),
            message=msg,
            anchor=rep["name"] + anchor_suffix,
        ))

    # ---- DTL155: registry <-> contract 1:1 (the DTL101/102 mirror) ----
    for name in sorted(set(entries) - set(by_name)):
        findings.append(Finding(
            code="DTL155", path=contract_path, line=1,
            message=f"contract entry '{name}' matches no registered shard "
                    f"entry point — prune it (the contract, like the "
                    f"baseline, can only track live code)",
            anchor=name,
        ))

    for rep in reports:
        name = rep["name"]
        c = entries.get(name)
        if c is None:
            add("DTL155", rep,
                f"shard entry point '{name}' has no committed contract "
                f"entry — run `python tools/lint.py --shard "
                f"--emit-contract` and review the diff")
            continue

        # ---- DTL151: per-op-kind collective budget --------------------
        budget = c.get("collectives", {})
        for op in sorted(rep["collectives"]):
            n = rep["collectives"][op]
            if op not in budget:
                add("DTL151", rep,
                    f"'{name}' ({rep['level']}) contains {n} {op} "
                    f"collective(s) the contract does not list — an "
                    f"unlisted collective is the silent-resharding bug "
                    f"class: HBM and ICI pay for it on every step",
                    anchor_suffix=f":{op}")
            elif n > budget[op]:
                add("DTL151", rep,
                    f"'{name}' ({rep['level']}) contains {n} {op} "
                    f"collective(s), contract budget is {budget[op]} — "
                    f"the program grew communication; if intentional, "
                    f"re-emit the contract", anchor_suffix=f":{op}")

        # ---- DTL152: in/out sharding-spec contract --------------------
        mismatches = rep["in_mismatches"] + rep["out_mismatches"]
        if mismatches:
            head = "; ".join(
                f"{p}: rules derive {e}, lowered program carries {a}"
                for p, e, a in mismatches[:3]
            )
            more = len(mismatches) - 3
            add("DTL152", rep,
                f"'{name}' lowered arg/result shardings drift from the "
                f"specs parallel/sharding.py derives ({len(mismatches)} "
                f"mismatch(es): {head}"
                + (f"; +{more} more" if more > 0 else "") + ") — the "
                f"rule engine and what GSPMD is handed no longer agree",
                anchor_suffix=":lowered")
        drift = []
        if rep["in_sharding_digest"] != c.get("in_sharding_digest"):
            drift.append("in-sharding digest")
        if rep["out_sharding_digest"] != c.get("out_sharding_digest"):
            drift.append("out-sharding digest")
        if rep["sharded_in_args"] != c.get("sharded_in_args"):
            drift.append(
                f"sharded-arg count {rep['sharded_in_args']} != "
                f"{c.get('sharded_in_args')}"
            )
        committed_specs = c.get("param_specs", {})
        if rep["param_specs"] != committed_specs:
            changed = sorted(
                set(rep["param_specs"].items())
                ^ set(committed_specs.items())
            )
            drift.append(
                "param specs "
                + ", ".join(f"{k}={v}" for k, v in changed[:3])
                + (f" +{len(changed) - 3} more" if len(changed) > 3 else "")
            )
        if drift:
            add("DTL152", rep,
                f"'{name}' sharding contract drift vs {contract_path}: "
                + "; ".join(drift) + " — if the rule change is "
                f"intentional, re-emit the contract",
                anchor_suffix=":contract")

        # ---- DTL153: accidental replication ---------------------------
        for intent in rep["replicated_intents"]:
            add("DTL153", rep,
                f"'{name}' parameter {intent['path']} is declared sharded "
                f"by rule {intent.get('rule')!r} "
                f"(requested {_spec_repr(intent['requested'])}) but the "
                f"lowered program replicates it — the fsdp/tp memory "
                f"story is fiction for this parameter",
                anchor_suffix=f":{intent['path']}")

        # ---- DTL154: in-program reshard constraints -------------------
        max_cons = c.get("max_reshard_constraints", 0)
        if rep["reshard_constraints"] > max_cons:
            add("DTL154", rep,
                f"'{name}' contains {rep['reshard_constraints']} "
                f"in-program sharding-constraint site(s) (net of "
                f"shard_map boundaries), budget {max_cons} — each "
                f"unbudgeted constraint is a potential device-to-device "
                f"reshard copy inside the hot program")

    return findings


# ------------------------------------------------------------ the runner


def run_shard(
    repo_root: str,
    registry_path: str,
    contract_path: str,
) -> Tuple[List[Finding], List[Dict[str, Any]]]:
    """The ``--shard`` stage: load the registry, audit every entry, check
    against the contract. Returns (findings, reports); findings feed the
    shared suppression/baseline machinery in ``core.run_lint``."""
    # contract problems are knowable in microseconds — check BEFORE the
    # multi-second lower/compile sweep
    ab_contract = (contract_path if os.path.isabs(contract_path)
                   else os.path.join(repo_root, contract_path))
    if not os.path.exists(ab_contract):
        raise OSError(
            f"shard contract file {contract_path} not found — generate "
            f"it with `python tools/lint.py --shard --emit-contract > "
            f"{contract_path}`"
        )
    contract = load_contract(ab_contract)
    mod = _load_registry(repo_root, registry_path)
    eps: List[ShardEntry] = mod.build_entry_points()
    reports = [audit_shard_entry(ep) for ep in eps]
    rel_contract = contract_path.replace(os.sep, "/")
    findings = check_reports(reports, contract, rel_contract, repo_root)
    return findings, reports


def shard_reports_only(repo_root: str, registry_path: str):
    """Audit without a contract (``--emit-contract`` path)."""
    mod = _load_registry(repo_root, registry_path)
    return [audit_shard_entry(ep) for ep in mod.build_entry_points()]
