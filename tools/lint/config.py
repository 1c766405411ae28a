"""Lint configuration: the invariants are DATA, this module declares them.

Everything a checker needs to know about *this* repository lives here —
the scan roots, the import-layer map, where the fault-site registry and
the telemetry-name registry live — so the checkers themselves stay
generic and the fixture tests can swap in miniature configs
(tests/test_static_analysis.py builds configs pointing at
tests/fixtures_lint/). ``default_config(repo_root)`` is the one the CLI
and the release gates run.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class LayerRule:
    """One import-layering constraint: files matching ``files`` (fnmatch
    patterns or directory prefixes, repo-relative posix paths) must not
    import any module whose dotted path starts with an entry of
    ``forbid`` (matched on dot boundaries; relative imports are resolved
    against the file's package path first)."""

    name: str
    files: Tuple[str, ...]
    forbid: Tuple[str, ...]
    why: str = ""


@dataclass(frozen=True)
class FaultConfig:
    """Fault-site cross-reference inputs. ``registry_path`` is AST-parsed
    for the ``KNOWN_SITES``/``_VALUE_SITES`` frozensets (the checker never
    imports the package); ``exercise_roots`` are the test/tool corpora a
    site must appear in (as an exact string literal, or inside a
    ``site=N`` env-spec fragment) to count as drilled."""

    registry_path: str
    exercise_roots: Tuple[str, ...]


@dataclass(frozen=True)
class NamesConfig:
    """Telemetry-name registry inputs. ``registry_path`` is AST-parsed for
    the per-kind frozensets (SPANS/EVENTS/COUNTERS/GAUGES/HISTOGRAMS);
    ``doc_path``/``doc_section`` locate the DESIGN.md name tables every
    registered name must appear in."""

    registry_path: str
    doc_path: str
    doc_section: str = "## 9."


@dataclass(frozen=True)
class TraceConfig:
    """Trace-stage (``--trace``) inputs: the entry-point registry module
    (imported by file path — the one module of the linter that DOES
    import jax and the package, so it is loaded only on demand) and the
    committed contract file the audit gates against."""

    registry_path: str = "tools/lint/trace/registry.py"
    contract_path: str = "tools/trace_contracts.json"


@dataclass(frozen=True)
class ShardConfig:
    """Shard-stage (``--shard``) inputs: the mesh-aware entry-point
    registry module (imported by file path, jax + package on demand —
    the trace-stage pattern) and the committed contract file the
    collective/sharding audit gates against."""

    registry_path: str = "tools/lint/shard/registry.py"
    contract_path: str = "tools/shard_contracts.json"


@dataclass(frozen=True)
class LintConfig:
    repo_root: str
    # files/dirs (repo-relative) the checkers scan by default
    scan_roots: Tuple[str, ...]
    # fnmatch patterns (repo-relative) excluded from any scan
    exclude: Tuple[str, ...]
    layer_rules: Tuple[LayerRule, ...]
    faults: Optional[FaultConfig]
    names: Optional[NamesConfig]
    baseline_path: Optional[str] = None
    trace: Optional[TraceConfig] = None
    shard: Optional[ShardConfig] = None


# the host-side observability/resilience layer: imported from loader
# threads, signal handlers, and the serving hot loop — a jax import here
# is a latent device sync (and a measurement that destroys what it
# measures; utils/telemetry.py module docstring). Generalizes the old
# source-grep pin in tests/test_telemetry.py.
_HOST_ONLY_FILES = (
    "dalle_pytorch_tpu/utils/telemetry.py",
    "dalle_pytorch_tpu/utils/telemetry_names.py",
    "dalle_pytorch_tpu/utils/metrics.py",
    "dalle_pytorch_tpu/utils/faults.py",
    "dalle_pytorch_tpu/utils/resilience.py",
    "dalle_pytorch_tpu/utils/vitals.py",
)

_JAX_STACK = ("jax", "jaxlib", "flax", "optax")


def default_layer_rules() -> Tuple[LayerRule, ...]:
    return (
        LayerRule(
            name="host-only-utils",
            files=_HOST_ONLY_FILES,
            forbid=_JAX_STACK
            + (
                "dalle_pytorch_tpu.serving",
                "dalle_pytorch_tpu.models",
                "dalle_pytorch_tpu.ops",
                "dalle_pytorch_tpu.parallel",
                "dalle_pytorch_tpu.data",
            ),
            why="telemetry/metrics/faults/resilience are host-side only: "
                "no jax (device syncs), no package layers above utils "
                "(the serving Clock protocol is duck-typed on purpose)",
        ),
        LayerRule(
            name="ops-below-serving",
            files=("dalle_pytorch_tpu/ops/*.py",),
            forbid=("dalle_pytorch_tpu.serving",),
            why="kernels/cache primitives are the bottom layer; the "
                "serving engine composes them, never the reverse",
        ),
        LayerRule(
            name="library-below-entrypoints",
            files=("dalle_pytorch_tpu/*.py", "dalle_pytorch_tpu/*/*.py"),
            forbid=("train_dalle", "train_vae", "train_clip", "train_lm",
                    "generate", "benchmarks"),
            why="library code must not import the CLI entrypoints or the "
                "benchmark (script-level side effects, circular bootstrap)",
        ),
        LayerRule(
            name="parallel-below-serving",
            files=("dalle_pytorch_tpu/parallel/*.py",),
            forbid=("dalle_pytorch_tpu.serving",),
            why="the training runtime (mesh, step, loop) is what every "
                "trainer shares; it knows nothing of the serving engine",
        ),
    )


def default_config(repo_root: str) -> LintConfig:
    repo_root = os.path.abspath(repo_root)
    return LintConfig(
        repo_root=repo_root,
        scan_roots=(
            "dalle_pytorch_tpu",
            "train_dalle.py",
            "train_vae.py",
            "train_clip.py",
            "train_lm.py",
            "generate.py",
        ),
        exclude=(
            "*/__pycache__/*",
            "tests/fixtures_lint/*",
            # the linter's own sources are full of deliberate bad
            # examples (checker docstrings, fixture snippets) — they are
            # neither scan targets nor a drill corpus
            "tools/lint.py",
            "tools/lint/*",
        ),
        layer_rules=default_layer_rules(),
        faults=FaultConfig(
            registry_path="dalle_pytorch_tpu/utils/faults.py",
            exercise_roots=("tests", "tools"),
        ),
        names=NamesConfig(
            registry_path="dalle_pytorch_tpu/utils/telemetry_names.py",
            doc_path="docs/DESIGN.md",
            doc_section="## 9.",
        ),
        baseline_path="tools/lint_baseline.json",
        trace=TraceConfig(),
        shard=ShardConfig(),
    )
