"""Lazy g++ build for the native BPE engine.

The shared library is compiled on first use into the package directory (or
``DALLE_TPU_NATIVE_DIR``) and rebuilt only when the sources are newer —
the ctypes analog of setuptools' build_ext, without requiring an install
step. pybind11 is not part of this image; the engine exposes a plain C ABI.
"""

from __future__ import annotations

import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

_SRC_DIR = Path(__file__).parent
_SOURCES = [_SRC_DIR / "bpe_tokenizer.cc"]
_HEADERS = [_SRC_DIR / "unicode_tables.h"]
_LOCK = threading.Lock()


def _out_dir() -> Path:
    d = os.environ.get("DALLE_TPU_NATIVE_DIR")
    if d:
        return Path(d)
    if os.access(_SRC_DIR, os.W_OK):
        return _SRC_DIR
    return Path.home() / ".cache" / "dalle_tpu" / "native"


def lib_path() -> Path:
    return _out_dir() / "libdalle_bpe.so"


def build(force: bool = False) -> Optional[Path]:
    """Compile (if stale) and return the .so path; None when no toolchain."""
    with _LOCK:
        so = lib_path()
        deps = _SOURCES + _HEADERS
        if (
            not force
            and so.exists()
            and so.stat().st_mtime >= max(p.stat().st_mtime for p in deps)
        ):
            return so
        so.parent.mkdir(parents=True, exist_ok=True)
        # a name of this process's own: test workers that find no .so all
        # build at once, and one's os.replace took another's file away
        tmp = so.with_suffix(f".{os.getpid()}.so.tmp")
        cmd = [
            os.environ.get("CXX", "g++"),
            "-O2", "-std=c++17", "-shared", "-fPIC",
            *(str(s) for s in _SOURCES),
            "-o", str(tmp),
        ]
        try:
            subprocess.run(
                cmd, check=True, capture_output=True, text=True, timeout=300
            )
        except (OSError, subprocess.SubprocessError):
            return None
        os.replace(tmp, so)  # atomic: concurrent loaders never see a partial .so
        return so
