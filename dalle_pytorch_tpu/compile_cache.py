"""Where the persistent XLA compilation cache lives — one rule, one place.

Every entry point (train_vae.py, train_dalle.py, train_clip.py,
train_lm.py, generate.py, the children of chip_smoke.py) calls
``enable_compile_cache()`` first thing, and the test harness calls it with
its own default. The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself; this module
  leaves it alone and sets no other directory in code. That is how the
  machine that runs the program (a chip host, CI) places the cache.
- unset: one FIXED path — ``<checkout>/.jax_cache`` (git-ignored), or the
  caller's ``default``. Never a name built from ``tempfile``, a pid or the
  clock: a directory that moves between runs never hits.

This module imports jax lazily so a jax-free parent (chip_smoke.py) can ask
``cache_dir()`` which directory its children will use.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional, Union

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

CHECKOUT = Path(__file__).resolve().parent.parent
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def cache_dir(default: Optional[Union[str, Path]] = None) -> str:
    """The cache directory in force: the environment's if set, else
    ``default`` (the fixed ``<checkout>/.jax_cache`` when not given)."""
    return os.environ.get(ENV_VAR) or str(default or DEFAULT_DIR)


def enable_compile_cache(default: Optional[Union[str, Path]] = None) -> str:
    """Apply the rule above and return the directory in force. Call before
    the first compile; safe to call more than once. Also installs the compile
    ledger (``utils/profiling.py:COMPILE_LEDGER``), once: every entry point
    calls this first, so no compile request precedes the ledger."""
    path = cache_dir(default)
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    from .utils.profiling import COMPILE_LEDGER

    COMPILE_LEDGER.install()
    return path
