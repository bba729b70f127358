"""ctypes bindings for the C++ host runtime (native/ directory).

The reference's native layer is MPI's C library plus mpi4py's Cython
buffer packing (SURVEY.md §2); this module binds the rebuild's C++
equivalent — digitize / counting-sort pack / row gather — for the CPU
oracle and host-side tooling. pybind11 is not in this image, so the C ABI
+ ctypes is the binding (no build-time Python deps).

Building the .so is opt-in: call :func:`build` explicitly (the tests
do), or set ``MPI_GRID_NATIVE_BUILD=1`` to allow a g++ build on
first use. Only a library built from the committed files is loaded: a
stamp next to the .so holds the hash of the source and ``build.sh`` (its
flags), and a .so whose stamp does not match is rebuilt, or ignored where
no build is allowed. Every entry point has a NumPy fallback so the package works
without a toolchain; the first silent fallback on a native-requested call
is logged so users know which path produced their numbers (``available()``
reports which path is live).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_LIB_NAME = "libgrid_redistribute_native.so"
_SOURCES = ("grid_redistribute_native.cpp", "build.sh")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_logged_fallback = False
_log = logging.getLogger(__name__)


def _native_dir() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
        "native",
    )


def _source_hash() -> str:
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_native_dir(), name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read())
    return h.hexdigest()


def _stamp_path() -> str:
    return os.path.join(_native_dir(), _LIB_NAME + ".sha256")


def _built_from_sources() -> bool:
    """True when the .so on disk was built from the committed files."""
    try:
        with open(_stamp_path()) as f:
            stamp = f.read().strip()
    except OSError:
        return False
    return os.path.exists(os.path.join(_native_dir(), _LIB_NAME)) and (
        stamp == _source_hash()
    )


def _run_build(timeout: float) -> None:
    """native/build.sh, then the stamp; raises on failure."""
    subprocess.run(
        [os.path.join(_native_dir(), "build.sh")], check=True,
        capture_output=True, timeout=timeout,
    )
    with open(_stamp_path(), "w") as f:
        f.write(_source_hash() + "\n")


def build(timeout: float = 120) -> bool:
    """Build the C++ library (native/build.sh, g++) if not already loaded.

    Explicit opt-in for the compiler invocation; returns True when the
    library is usable afterwards, False (with a log line) otherwise.
    """
    global _tried
    if os.environ.get("MPI_GRID_NO_NATIVE"):
        return False  # user opted out: never compile
    if _load() is not None:
        return True
    script = os.path.join(_native_dir(), "build.sh")
    if not os.path.exists(script):
        _log.warning("native build script missing: %s", script)
        return False
    try:
        _run_build(timeout)
    except (subprocess.SubprocessError, OSError) as e:
        _log.warning("native build failed (%s); using NumPy fallback", e)
        return False
    with _lock:
        _tried = False  # retry the load now that the .so exists
    return _load() is not None


def _note_fallback() -> None:
    """Log once when a native-requested call falls back to NumPy."""
    global _logged_fallback
    if os.environ.get("MPI_GRID_NO_NATIVE"):
        return  # deliberate opt-out: fallback is the requested behavior
    with _lock:
        if _logged_fallback:
            return
        _logged_fallback = True
    _log.warning(
        "C++ host runtime unavailable (call utils.native.build() or "
        "set MPI_GRID_NATIVE_BUILD=1); using NumPy fallback"
    )


def _load() -> Optional[ctypes.CDLL]:
    """Load (building on first use if opted in) the C++ library.

    The module lock only guards the ``_lib``/``_tried`` handoff; the
    slow work — filesystem probes, the opt-in g++ build subprocess,
    ``dlopen`` — runs OUTSIDE the critical section (racecheck T003: no
    blocking call while holding a lock). A concurrent caller that
    arrives while the one-time probe/build is still in flight sees
    ``_tried`` already set and takes the NumPy fallback for that call —
    the same loud-but-safe fallback contract every entry point has."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
    lib = _probe_and_load()
    with _lock:
        _lib = lib
        return _lib


def _probe_and_load() -> Optional[ctypes.CDLL]:
    if os.environ.get("MPI_GRID_NO_NATIVE"):
        return None
    path = os.path.join(_native_dir(), _LIB_NAME)
    if not _built_from_sources():
        if not os.environ.get("MPI_GRID_NATIVE_BUILD"):
            return None  # missing or stale: build() rebuilds it
        try:
            _run_build(120)
        except (subprocess.SubprocessError, OSError):
            return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    if lib.grn_abi_version() != 1:
        return None
    lib.grn_bin.argtypes = [
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.grn_count_sort.argtypes = [
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.grn_gather_rows.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_char_p,
    ]
    return lib


def available() -> bool:
    """True when the C++ library is loaded (vs NumPy fallback)."""
    return _load() is not None


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def bin_positions(pos: np.ndarray, domain, grid) -> np.ndarray:
    """Destination rank per row — C++ twin of binning.rank_of_position."""
    lib = _load()
    if lib is None:
        _note_fallback()
        from mpi_grid_redistribute_tpu.ops import binning

        return binning.rank_of_position(pos, domain, grid, xp=np)
    pos = np.ascontiguousarray(pos, dtype=np.float32)
    n, ndim = pos.shape
    lo = np.asarray(domain.lo, dtype=np.float64)
    hi = np.asarray(domain.hi, dtype=np.float64)
    per = np.asarray(domain.periodic, dtype=np.int32)
    gshape = np.asarray(grid.shape, dtype=np.int32)
    dest = np.empty((n,), dtype=np.int32)
    lib.grn_bin(
        _ptr(pos, ctypes.c_float),
        n,
        ndim,
        _ptr(lo, ctypes.c_double),
        _ptr(hi, ctypes.c_double),
        _ptr(per, ctypes.c_int32),
        _ptr(gshape, ctypes.c_int32),
        _ptr(dest, ctypes.c_int32),
    )
    return dest


def count_sort(dest: np.ndarray, nranks: int) -> Tuple[np.ndarray, np.ndarray]:
    """(counts, stable order grouping rows by destination).

    Sentinel ``nranks`` entries group at the tail and are not counted.
    O(N + R) counting sort in C++; NumPy fallback uses bincount + stable
    argsort.
    """
    lib = _load()
    dest = np.ascontiguousarray(dest, dtype=np.int32)
    if lib is None:
        _note_fallback()
        counts = np.bincount(
            dest, minlength=nranks + 1
        )[:nranks].astype(np.int64)
        return counts, np.argsort(dest, kind="stable").astype(np.int64)
    n = dest.shape[0]
    counts = np.empty((nranks,), dtype=np.int64)
    order = np.empty((n,), dtype=np.int64)
    lib.grn_count_sort(
        _ptr(dest, ctypes.c_int32),
        n,
        nranks,
        _ptr(counts, ctypes.c_int64),
        _ptr(order, ctypes.c_int64),
    )
    return counts, order


def gather_rows(src: np.ndarray, order: np.ndarray) -> np.ndarray:
    """out[j] = src[order[j]] — the pack gather, one memcpy pass in C++."""
    lib = _load()
    if lib is None:
        _note_fallback()
        return src[order]
    src = np.ascontiguousarray(src)
    order = np.ascontiguousarray(order, dtype=np.int64)
    out = np.empty((order.shape[0],) + src.shape[1:], dtype=src.dtype)
    row_bytes = src.dtype.itemsize
    for s in src.shape[1:]:
        row_bytes *= s
    lib.grn_gather_rows(
        src.ctypes.data_as(ctypes.c_char_p),
        _ptr(order, ctypes.c_int64),
        order.shape[0],
        row_bytes,
        out.ctypes.data_as(ctypes.c_char_p),
    )
    return out
