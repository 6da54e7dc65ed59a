"""ctypes loader for the native host-data-path library (native/gather.cpp).

Build-on-first-use: compiles the shared library with g++ into the package's
``native/`` directory the first time it's needed (pybind11 is not in this
image; ctypes + extern "C" needs no Python headers at all). The sha256 of
``gather.cpp`` is recorded beside the ``.so``; a library whose record is
missing or differs is rebuilt, so what runs is built from the source as it
stands — file times do not survive a copy and are never consulted. Every
entry point has a numpy fallback, so the framework runs — just slower on
the host-streaming path — on boxes without a toolchain.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

log = logging.getLogger("neuroimagedisttraining_tpu.native")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "native")
_SRC = os.path.join(_NATIVE_DIR, "gather.cpp")
_SO = os.path.join(_NATIVE_DIR, "libnidt_gather.so")
_SO_SRC_HASH = _SO + ".sha256"  # sha256 of the gather.cpp that built _SO

_lock = threading.Lock()
_lib: ctypes.CDLL | bool | None = None  # None = not tried, False = failed

DEFAULT_THREADS = min(8, os.cpu_count() or 1)


def _src_hash() -> str | None:
    """sha256 of ``gather.cpp``; None when the source is absent (a
    binary-only install: the ``.so`` is then used as it is)."""
    try:
        with open(_SRC, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()
    except OSError:
        return None


def _built_from(src_hash: str) -> bool:
    try:
        with open(_SO_SRC_HASH) as f:
            return os.path.isfile(_SO) and f.read().strip() == src_hash
    except OSError:
        return False


def _build() -> bool:
    # build beside the target and rename: a concurrent silo process
    # never loads a half-written library
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        # surface WHY the numpy slow path is in use; logged once per
        # process because load() latches _lib = False after this fails
        stderr = getattr(e, "stderr", None)
        detail = (stderr.decode("utf-8", errors="replace").strip()
                  if stderr else str(e))
        log.warning("native gather build failed (%s); falling back to the "
                    "numpy slow path: %s", " ".join(cmd), detail)
        return False


def load() -> ctypes.CDLL | None:
    """The library handle, building it if necessary; None when unavailable."""
    global _lib
    with _lock:
        if _lib is False:
            return None
        if _lib is not None:
            return _lib
        src_hash = _src_hash()
        if src_hash is None:
            fresh = os.path.isfile(_SO)
        else:
            fresh = _built_from(src_hash)
            if not fresh and _build():
                with open(_SO_SRC_HASH, "w") as f:
                    f.write(src_hash + "\n")
                fresh = True
        if not fresh:
            _lib = False
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            _lib = False
            return None
        lib.nidt_gather_rows_u8.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int]
        _lib = lib
        return lib


def gather_rows(src: np.ndarray, idx: np.ndarray,
                out: np.ndarray | None = None,
                n_threads: int = DEFAULT_THREADS) -> np.ndarray:
    """dst[i] = src[idx[i]] — multithreaded row gather for uint8 sources,
    numpy fallback otherwise. ``out`` may supply a preallocated target
    (e.g. a slice of the padded round buffer)."""
    idx = np.ascontiguousarray(idx, np.int64)
    lib = load()
    if (lib is None or src.dtype != np.uint8
            or not src.flags["C_CONTIGUOUS"]):
        gathered = src[idx]
        if out is None:
            return gathered
        out[: len(idx)] = gathered
        return out
    row_bytes = int(np.prod(src.shape[1:], dtype=np.int64)) * src.itemsize
    if out is None:
        out = np.empty((len(idx),) + src.shape[1:], src.dtype)
    dst = out[: len(idx)]
    assert dst.flags["C_CONTIGUOUS"]
    lib.nidt_gather_rows_u8(
        src.ctypes.data, idx.ctypes.data, len(idx), row_bytes,
        dst.ctypes.data, n_threads)
    return out
