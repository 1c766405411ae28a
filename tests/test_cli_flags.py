"""CLI flag-surface parity vs the reference's train_dalle.py.

The reference's user-facing contract is its argparse surface
(/root/reference/train_dalle.py:33-135). This test diffs that surface
against ours so a reference user can port a launch command unchanged:
every reference flag must either exist verbatim here or appear in the
explicit, documented substitution table below. It reads the reference
file with a regex rather than importing it (the reference pulls in torch
CUDA modules at import time).
"""

import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
REFERENCE = Path("/root/reference/train_dalle.py")

# Reference flags deliberately replaced by a TPU-native analog (not a gap
# — each row is a conscious substitution, documented at the cited site).
SUBSTITUTED = {
    # DeepSpeed flops-profiler dump -> XLA trace capture
    # (train_dalle.py --profile_trace_dir/--profile_step)
    "--flops_profiler": ("--profile_trace_dir", "--profile_step"),
}


def _ref_flags():
    # every quoted '--flag' in an add_argument(...) call; calls span lines
    # (e.g. the reference's --wds at train_dalle.py:48-53), so match over
    # each call's full argument span, not per-line
    text = REFERENCE.read_text()
    flags = set()
    for m in re.finditer(r"add_argument\(", text):
        span = text[m.end():m.end() + 400]
        span = span.split(")")[0]  # flags precede any ')' in the call
        flags.update(re.findall(r"'(--[\w\-]+)'", span))
    return flags


def _our_flags():
    sys.path.insert(0, str(REPO))
    try:
        from train_dalle import build_parser
    finally:
        sys.path.pop(0)
    parser = build_parser()
    flags = set()
    for action in parser._actions:
        flags.update(s for s in action.option_strings if s.startswith("--"))
    return flags


@pytest.mark.skipif(not REFERENCE.exists(), reason="reference tree absent")
def test_reference_flag_surface_is_covered():
    ref, ours = _ref_flags(), _our_flags()
    assert ref, "regex found no reference flags — parsing broke"
    missing = []
    for flag in sorted(ref):
        if flag in ours:
            continue
        subs = SUBSTITUTED.get(flag)
        if subs:
            absent = [s for s in subs if s not in ours]
            assert not absent, (
                f"substitution for {flag} lists {absent} which our parser "
                "does not define — fix the table or the parser"
            )
            continue
        missing.append(flag)
    assert not missing, (
        f"reference flags with no analog here: {missing} — add them (or a "
        "documented substitution) so reference launch commands port cleanly"
    )


def test_substitution_table_is_not_stale():
    # a substituted flag that later lands verbatim should be dropped from
    # the table so the docs stay honest
    ours = _our_flags()
    stale = [f for f in SUBSTITUTED if f in ours]
    assert not stale, f"flags now implemented verbatim, prune from table: {stale}"
