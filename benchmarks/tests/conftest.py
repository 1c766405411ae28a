"""The benchmark's own tests run on the CPU, by hand:

    python -m pytest benchmarks/tests -q

They are not part of the repository's tier-1 tests."""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
