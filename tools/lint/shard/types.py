"""Shard-stage data types: mesh-aware entry-point registry records.

Deliberately jax-free (the trace-stage ``types.py`` pattern): a registry
module — the repo's ``tools/lint/shard/registry.py`` or a test fixture —
imports this to DECLARE its entries; all lowering/compiling lives in
``audit.py``.

A :class:`ShardEntry` names one jitted program together with the mesh it
runs under and everything the sharding audit needs to judge it:

* ``lower`` is a zero-argument thunk returning the ``jax.stages.Lowered``
  program (the thunk owns arg construction and any ambient-mesh
  activation, so building the entry list stays cheap until the audit
  actually runs);
* ``partitioned`` asks the audit to ALSO compile the lowered program and
  count collectives in the post-SPMD-partitioning HLO — the ground truth
  for multi-device meshes, where GSPMD inserts collectives the source
  never wrote. Single-device entries skip the compile: partitioning is
  the identity there, and the PRE-partitioning StableHLO is where an
  explicit collective (a shard_map psum) cannot be elided away;
* ``arg_paths``/``in_shardings`` (and the ``out_*`` twins) are the
  flattened per-argument tree paths and EXPECTED HLO sharding strings
  the registry derives from ``parallel/sharding.py`` — the audit
  compares them 1:1 against the compiled program's own
  ``input_shardings``/``output_shardings`` (DTL152). Empty sequences
  skip the check (the 1-device serving entries);
* ``param_intents`` is the :func:`parallel.sharding.spec_report` list
  for the parameter leaves (with ``"arg"`` indices into the flattened
  argument list), feeding the DTL153 accidental-replication check.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Mapping, Optional, Sequence


@dataclass(frozen=True)
class ShardEntry:
    """One registered program under one named mesh."""

    name: str
    path: str                       # repo-relative file (finding anchor)
    symbol: str                     # def name, for line lookup
    mesh_axes: Mapping[str, int]    # {} for plain 1-device jits
    lower: Callable[[], Any]        # thunk -> jax.stages.Lowered
    partitioned: bool = False       # compile & count post-SPMD collectives
    arg_paths: Sequence[str] = ()
    in_shardings: Sequence[Optional[str]] = ()
    out_paths: Sequence[str] = ()
    out_shardings: Sequence[Optional[str]] = ()
    param_intents: Sequence[Dict[str, Any]] = field(default_factory=tuple)
