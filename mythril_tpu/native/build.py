"""Lazy in-tree build of the native library (g++ -O2 -shared -fPIC).

No pybind11 in this environment, so the boundary is a C ABI loaded with
ctypes.  The .so is keyed on a hash of the committed sources' CONTENTS and
the compile command (``_libmythril_native.<hash>.so``), never on mtimes: a
tree copied with a stale library can only ever load the build its own
sources produce.  Concurrent builds are serialized with an exclusive lock so
parallel pytest workers don't race the compiler.
"""

from __future__ import annotations

import fcntl
import hashlib
import logging
import os
import shutil
import subprocess
from pathlib import Path
from typing import Optional

log = logging.getLogger(__name__)

_DIR = Path(__file__).parent
_SRC_DIR = _DIR / "src"
_SOURCES = ["bitblast.cpp", "keccak.cpp"]
_FLAGS = ["-O2", "-std=c++17", "-shared", "-fPIC"]


def _lib_path() -> Path:
    h = hashlib.sha256(" ".join(_FLAGS).encode())
    for name in _SOURCES:
        h.update(name.encode())
        h.update((_SRC_DIR / name).read_bytes())
    return _DIR / f"_libmythril_native.{h.hexdigest()[:16]}.so"


def build_library() -> Path:
    """Path to the library built from the committed sources, building it
    if needed; raises when it cannot be built."""
    lib = _lib_path()
    if lib.exists():
        return lib
    gxx = shutil.which("g++") or shutil.which("c++")
    if gxx is None:
        raise RuntimeError("no C++ compiler on PATH")
    with open(_DIR / "_libmythril_native.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if lib.exists():
            return lib
        tmp = lib.with_suffix(".so.tmp")
        cmd = [gxx, *_FLAGS, "-o", str(tmp),
               *[str(_SRC_DIR / s) for s in _SOURCES]]
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
        if proc.returncode != 0:
            raise RuntimeError(f"native build failed:\n{proc.stderr[-2000:]}")
        os.replace(tmp, lib)
        return lib


def library_path() -> Optional[Path]:
    """``build_library()``, or None when it cannot be built (the solver
    tiers that use it then stay off; ``chip_smoke.py`` fails instead)."""
    try:
        return build_library()
    except (OSError, RuntimeError, subprocess.SubprocessError) as e:
        log.warning("native library unavailable: %s", e)
        return None
