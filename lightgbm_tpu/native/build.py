"""On-demand g++ build + ctypes loader for the native helpers."""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

_lock = threading.Lock()
_lib = None
_tried = False


def _build(src: Path, out: Path) -> bool:
    """g++ ``src`` into ``out``; a failure is logged (once per process —
    ``load_native`` tries once) and the caller falls back to NumPy."""
    # no -march=native: the cached .so may be shared across hosts (NFS,
    # container images) and a binary search gains little from wide SIMD.
    # compile to a temp file and os.replace: concurrent builders (the
    # multi-process launcher) must never let a reader map a half-written ELF
    tmp = out.with_name(f"{out.name}.{os.getpid()}.tmp")
    cmd = [
        "g++", "-O3", "-fopenmp", "-shared", "-fPIC",
        str(src), "-o", str(tmp),
    ]
    try:
        r = subprocess.run(cmd, capture_output=True, timeout=120)
        if r.returncode != 0 or not tmp.exists():
            _warn_build_failed(r.stderr.decode("utf-8", "replace")[-500:])
            return False
        os.replace(tmp, out)
        return True
    except (OSError, subprocess.TimeoutExpired) as e:
        _warn_build_failed(f"{type(e).__name__}: {e}")
        return False
    finally:
        if tmp.exists():
            try:
                tmp.unlink()
            except OSError:
                pass


def _warn_build_failed(why: str) -> None:
    from ..utils.log import log_warning

    log_warning(
        "native binning helper did not build (g++ -O3 -fopenmp "
        f"native/binning.cpp): {why.strip() or 'no compiler output'}; "
        "binning falls back to NumPy (same results, slower ingest)"
    )


def load_native() -> Optional[ctypes.CDLL]:
    """The compiled helper library, or None (NumPy fallback)."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("LGBM_TPU_NO_NATIVE"):
            return None
        here = Path(__file__).parent
        src = here / "binning.cpp"
        out = here / "_binning.so"

        def _load():
            lib = ctypes.CDLL(str(out))
            lib.bin_numeric_f64.argtypes = [
                ctypes.c_void_p, ctypes.c_longlong, ctypes.c_void_p,
                ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_double,
                ctypes.c_void_p,
            ]
            lib.greedy_find_bin.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_longlong,
                ctypes.c_int, ctypes.c_double, ctypes.c_double,
                ctypes.c_void_p,
            ]
            lib.greedy_find_bin.restype = ctypes.c_int
            return lib

        try:
            if not out.exists() or out.stat().st_mtime < src.stat().st_mtime:
                if not _build(src, out):
                    return None
            try:
                _lib = _load()
            except AttributeError:
                # stale cached .so predating a newly added symbol
                # (mtime-preserving copies skip the rebuild): rebuild once
                out.unlink(missing_ok=True)
                _lib = _load() if _build(src, out) else None
        except (OSError, AttributeError):
            _lib = None
        return _lib
