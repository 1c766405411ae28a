"""The living documents name what exists.

README.md, docs/DESIGN.md and PARITY.md are read by whoever runs this repo:
a path, a command or a benchmark name quoted there has to be in the tree.
They quote no speed figure of their own: `PERF_LEDGER.jsonl` is rewritten
after every change, `PERF.md` reads it, and PERF.md in turn has to cover every
cell and per-layer metric `BENCHMARK.json` declares.
"""

import fnmatch
import json
import os
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCS = ("README.md", "docs/DESIGN.md", "PARITY.md")
BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())

# a short path is relative to one of these
ROOTS = ("", "dalle_pytorch_tpu/", "tools/", "tests/", "benchmarks/", "docs/")
# file names of the reference implementation (PARITY.md maps them) and of
# files a run writes, which no checkout holds
NOT_OURS = {
    "dalle_pytorch.py", "deepspeed_backend.py", "distributed_utils.py",
    "distributed_backends/*.py", "tokenizer.py", "setup.py",
    "config.json", "MANIFEST.json", "index.json",
}

PATH = re.compile(r"`([\w./*-]+\.(?:py|jsonl|json|md))(?::[^`]*)?`")
COMMAND = re.compile(r"\bpython3? ([\w./-]+\.py)\b")
QUOTED = re.compile(r"`([^`\s]+)`")
# what looks like a cell or a metric of the benchmark
BENCH_NAME = re.compile(
    r"^(?:train|serve)-[\w.-]+$|roofline|_share\b|^train\.\w*mfu$|^serve\.mfu"
)


@pytest.fixture(scope="module")
def tracked():
    """Every file of the checkout, by its path from the root (the driver's
    checkout may have no .git, so this walks instead of asking git)."""
    skip = {".git", "__pycache__", ".jax_cache", ".pytest_cache", "chiprun_out",
            ".proof", ".work"}
    found = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        found.update((Path(root) / f).relative_to(REPO).as_posix() for f in files)
    return found


@pytest.mark.parametrize("doc", DOCS)
def test_quoted_paths_exist(doc, tracked):
    basenames = {p.rsplit("/", 1)[-1] for p in tracked}
    missing = []
    for path in {m.group(1) for m in PATH.finditer((REPO / doc).read_text())}:
        if path in NOT_OURS:
            continue
        if "/" not in path and "*" not in path:
            found = path in basenames
        else:
            found = any(
                fnmatch.filter(tracked, root + path) for root in ROOTS
            )
        if not found:
            missing.append(path)
    assert not missing, f"{doc} quotes paths that are not in the tree: {sorted(missing)}"


@pytest.mark.parametrize("doc", DOCS)
def test_python_commands_name_tracked_scripts(doc, tracked):
    scripts = {m.group(1) for m in COMMAND.finditer((REPO / doc).read_text())}
    missing = sorted(s for s in scripts if s not in tracked)
    assert not missing, f"{doc} runs scripts that are not in the tree: {missing}"


@pytest.mark.parametrize("doc", DOCS)
def test_benchmark_names_are_declared(doc):
    declared = {
        entry["name"]
        for key in ("configs", "workloads", "end_to_end", "per_layer")
        for entry in BENCHMARK[key]
    }
    # metrics whose cells wait (no serving cell yet) have their file already
    waiting = {p.stem for p in (REPO / "benchmarks" / "metrics").glob("*.json")}
    quoted = {
        m.group(1) for m in QUOTED.finditer((REPO / doc).read_text())
        if BENCH_NAME.search(m.group(1)) and not PATH.fullmatch(m.group(0))
    }
    unknown = sorted(quoted - declared - waiting)
    assert not unknown, f"{doc} quotes benchmark names nobody declares: {unknown}"


def test_readme_quotes_no_speed_figure():
    figure = re.compile(r"\d[\d,.]*\s*(?:tok/s|tokens/s|ms/token|ms/step|% ?MFU)")
    found = figure.findall((REPO / "README.md").read_text())
    assert not found, f"README.md quotes speed figures ({found}): they live in the ledger"


def _section(text: str, number: int) -> str:
    """The body of PERF.md's section ``number`` (``## N. ...``)."""
    m = re.search(rf"^## {number}\. .*?(?=^## \d+\. |\Z)", text, re.M | re.S)
    assert m, f"PERF.md has no section {number}"
    return m.group(0)


def test_perf_md_has_a_paragraph_for_every_cell():
    cells = _section((REPO / "PERF.md").read_text(), 4)
    missing = [
        w["name"] for w in BENCHMARK["workloads"]
        if not re.search(rf"^(?:[-*|] )?`{re.escape(w['name'])}`", cells, re.M)
    ]
    assert not missing, f"PERF.md section 4 has no paragraph for {missing}"


def test_perf_md_places_every_per_layer_metric_in_a_layer():
    layers = _section((REPO / "PERF.md").read_text(), 3)
    missing = [
        m["name"] for m in BENCHMARK["per_layer"] if f"`{m['name']}`" not in layers
    ]
    assert not missing, f"PERF.md section 3 does not place {missing}"
