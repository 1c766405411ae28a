"""dalle-tpu-lint, stage 3: mesh-aware sharding & collective audit
(``--shard``).

The AST stage (DTL0xx) checks what the source says; the trace stage
(DTL1xx, ``--trace``) checks the program XLA gets on one device. This
stage checks what the program COSTS on a mesh: every registered entry
point (``registry.py``: ``make_train_step`` under each of the six mesh
kinds from ``parallel/mesh.py``, plus every serving jit under its
current 1-device placement) is lowered over a host-platform device mesh
— and, for multi-device meshes, compiled on host CPU so the
post-SPMD-partitioning HLO is inspectable — then audited against the
committed ``tools/shard_contracts.json``. The failure modes this
catches are invisible in source and only show up as HBM blowups or
collective storms at run time: an accidentally replicated weight, a
hidden resharding copy, an unbudgeted all-gather.

Finding codes (docs/DESIGN.md §11.2):

=========  ==================================================================
DTL151     per-entry collective budget by op kind (all-gather / all-reduce
           / reduce-scatter / collective-permute / all-to-all): a count
           over the committed budget, or a kind the contract does not
           list at all — the silent-resharding bug class caught at lint
           time. Serving entries commit the "no collectives in serving"
           baseline ROADMAP item 1 will consciously renegotiate
DTL152     in/out sharding-spec contract: the program's actual arg/result
           shardings (read from jax's ``Compiled.input_shardings`` /
           ``output_shardings``, never from MLIR attribute text) vs the
           specs ``parallel/sharding.py:params_shardings`` derives (the
           ``:lowered`` anchor — drift between the rule engine and what
           the partitioner is handed lives in CODE and survives
           --emit-contract),
           and the derived specs/digests vs the committed contract (the
           ``:contract`` anchor — cleared by an intentional re-emit)
DTL153     accidental replication: a parameter the rules declare sharded
           but whose lowered sharding is fully replicated — the fsdp/tp
           memory story is fiction for that parameter. Lives in code;
           --emit-contract cannot clear it
DTL154     in-program sharding-constraint sites
           (``sdy.sharding_constraint``, net of jax's own all-open
           annotations) over the entry's budget — each one a potential
           device-to-device reshard copy not attributable to a declared
           spec boundary
DTL155     registry <-> contract 1:1 with stale-entry failure (the
           DTL101/102 mirror): an unregistered contract entry or an
           uncommitted registry entry both fail ``--check``
=========  ==================================================================

Like the trace stage this package imports jax AND the audited package —
``tools/lint/__init__.py`` must never import it; ``tools/lint.py``
loads it only under ``--shard`` (forcing an 8-device host platform
first). Findings flow through the same suppression/baseline machinery
and compose with the other stages in one exit code. ``--emit-contract``
regenerates the contract (the blessed-update workflow; how to
renegotiate the serving collective budget when multi-chip serving
lands is documented in docs/DESIGN.md §11.2).
"""

from __future__ import annotations

from .audit import (
    audit_shard_entry,
    check_reports,
    compiled_collectives,
    emit_contract,
    load_contract,
    hlo_sharding_str,
    lowered_collectives,
    reshard_constraints,
    run_shard,
    shard_reports_only,
)
from .types import ShardEntry

__all__ = [
    "ShardEntry",
    "audit_shard_entry",
    "check_reports",
    "compiled_collectives",
    "emit_contract",
    "load_contract",
    "hlo_sharding_str",
    "lowered_collectives",
    "reshard_constraints",
    "run_shard",
    "shard_reports_only",
]
