"""Lazy g++ build + ctypes load of the native CSV scanner.

The shared object compiles once per source change from the committed
``csv_scan.cpp`` into the checkout's own ignored cache directory
(``utils.paths.cache_dir("native")`` — never the home directory or a temp
dir, so nothing but what git would commit decides what runs), keyed by a
hash of the source so edits rebuild and stale binaries never load.
Everything is best-effort: no compiler, failed compile, or failed load all
mean "return None" and callers use the pure-Python scanner
(``csv_index._scan_row_offsets_py``).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading
from typing import Optional

import numpy as np

from agent_tpu.utils.paths import cache_dir

_SRC = os.path.join(os.path.dirname(__file__), "csv_scan.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _build() -> Optional[str]:
    """Compile csv_scan.cpp → cached .so; returns the path or None."""
    gxx = shutil.which("g++") or shutil.which("c++") or shutil.which("clang++")
    if gxx is None or not os.path.exists(_SRC):
        return None
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(cache_dir("native"), f"csv_scan_{digest}.so")
    if os.path.exists(out):
        return out
    tmp = out + f".tmp{os.getpid()}"
    try:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        proc = subprocess.run(
            [gxx, "-O3", "-shared", "-fPIC", _SRC, "-o", tmp],
            capture_output=True,
            timeout=120,
        )
        if proc.returncode != 0:
            return None
        os.replace(tmp, out)  # atomic: concurrent builders race harmlessly
        return out
    except (OSError, subprocess.TimeoutExpired):
        return None
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        so = _build()
        if so is None:
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(so)
            lib.csv_scan_offsets.argtypes = [
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
            ]
            lib.csv_scan_offsets.restype = ctypes.c_int64
            lib.csv_scan_free.argtypes = [ctypes.POINTER(ctypes.c_int64)]
            lib.csv_scan_free.restype = None
            _lib = lib
        except OSError:
            _load_failed = True
        return _lib


def scan_row_offsets_native(path: str) -> Optional[np.ndarray]:
    """Row-start offsets via the C++ scanner, or None to use the Python path."""
    lib = _get_lib()
    if lib is None:
        return None
    out = ctypes.POINTER(ctypes.c_int64)()
    n = lib.csv_scan_offsets(os.fsencode(path), ctypes.byref(out))
    if n < 0:
        return None
    try:
        return np.ctypeslib.as_array(out, shape=(n,)).astype(np.int64, copy=True)
    finally:
        lib.csv_scan_free(out)


def native_available() -> bool:
    return _get_lib() is not None
