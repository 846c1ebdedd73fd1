"""Native extension loader: compile-on-first-use C++ via ctypes.

The reference ships its runtime (text parsing, IO) as compiled C++
(src/io/parser.cpp, text_reader.h). Here the native piece is built
lazily with the system toolchain and loaded through ctypes — no
pybind11, no install step; everything degrades to the numpy paths when
a compiler is unavailable.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Optional

import numpy as np

from .log import log_warning

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "native")
_LIB = None
_LIB_TRIED = False


def _build_dir() -> str:
    """Per-user 0700 cache dir: a shared predictable /tmp path would
    let another local user plant a .so at the known hash name
    (CWE-379)."""
    d = os.environ.get("LIGHTGBM_TPU_BUILD_DIR")
    if not d:
        base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
            os.path.expanduser("~"), ".cache")
        d = os.path.join(base, "lightgbm_tpu", "native")
    os.makedirs(d, mode=0o700, exist_ok=True)
    st = os.stat(d)
    if st.st_uid != os.getuid():
        raise PermissionError(f"native build dir {d} not owned by us")
    return d


def _load() -> Optional[ctypes.CDLL]:
    """Compile (if needed) and dlopen the fastparse library."""
    global _LIB, _LIB_TRIED
    if _LIB is not None or _LIB_TRIED:
        return _LIB
    _LIB_TRIED = True
    if os.environ.get("LIGHTGBM_TPU_NO_NATIVE"):
        return None
    src = os.path.join(_NATIVE_DIR, "fastparse.cpp")
    if not os.path.exists(src):
        return None
    with open(src, "rb") as fh:
        tag = hashlib.sha256(fh.read()).hexdigest()[:16]
    try:
        so = os.path.join(_build_dir(), f"fastparse_{tag}.so")
    except PermissionError as e:
        log_warning(f"native fastparse disabled: {e}")
        return None
    if not os.path.exists(so):
        # compile to a private temp name, then atomic-rename: a
        # concurrent process never dlopens a half-written file
        tmp = f"{so}.{os.getpid()}.tmp"
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
               "-fopenmp", src, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True,
                           timeout=120)
            os.replace(tmp, so)
        except Exception as e:  # compiler missing / failed: fall back
            log_warning(f"native fastparse build failed ({e}); "
                        "falling back to numpy text parsing")
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        log_warning(f"native fastparse load failed ({e})")
        return None
    lib.ltpu_sniff.restype = ctypes.c_int
    lib.ltpu_sniff.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_char)]
    lib.ltpu_parse_dense.restype = ctypes.c_int64
    lib.ltpu_parse_dense.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_char,
        ctypes.c_int64, ctypes.c_int64,
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")]
    lib.ltpu_bin_columns.restype = None
    lib.ltpu_bin_columns.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p]
    _LIB = lib
    return lib


def parse_dense_text(path: str, skip_header: bool) -> Optional[np.ndarray]:
    """Parse a delimited numeric file to [rows, cols] float64 with the
    native parser; None when native is unavailable (caller falls back
    to numpy)."""
    lib = _load()
    if lib is None:
        return None
    with open(path, "rb") as fh:
        buf = fh.read()
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    delim = ctypes.c_char()
    rc = lib.ltpu_sniff(buf, len(buf), int(skip_header),
                        ctypes.byref(rows), ctypes.byref(cols),
                        ctypes.byref(delim))
    if rc != 0 or rows.value <= 0 or cols.value <= 0:
        return None
    out = np.empty((rows.value, cols.value), np.float64)
    got = lib.ltpu_parse_dense(buf, len(buf), int(skip_header),
                               delim.value, rows.value, cols.value, out)
    if got != rows.value:
        out = out[:got]
    return out


def bin_columns_native(X: np.ndarray, col_indices: np.ndarray,
                       bounds_list, nan_to: np.ndarray,
                       out_dtype,
                       nan_cells: Optional[np.ndarray] = None
                       ) -> Optional[np.ndarray]:
    """Bin numerical columns of a row-major matrix with the native
    kernel (ltpu_bin_columns); None when native is unavailable or the
    matrix dtype is unsupported (caller falls back to numpy).

    ``bounds_list``: per-selected-column float64 ascending upper
    bounds; ``nan_to``: per-selected-column target bin for NaN cells;
    ``nan_cells``: a C-contiguous int64 array, one entry a selected
    column, to which the kernel adds the NaN cells it saw there.
    """
    lib = _load()
    if lib is None or X.ndim != 2:
        return None
    if X.dtype == np.float32:
        is_f64 = 0
    elif X.dtype == np.float64:
        is_f64 = 1
    else:
        return None
    X = np.ascontiguousarray(X)
    n, F = X.shape
    C = len(col_indices)
    bnd_off = np.zeros((C + 1,), np.int64)
    for i, b in enumerate(bounds_list):
        bnd_off[i + 1] = bnd_off[i] + len(b)
    bounds = np.concatenate(bounds_list).astype(np.float64) \
        if C else np.zeros((0,), np.float64)
    out = np.empty((n, C), out_dtype)
    lib.ltpu_bin_columns(
        X.ctypes.data_as(ctypes.c_void_p), is_f64, n, F,
        np.ascontiguousarray(col_indices, np.int32), C,
        np.ascontiguousarray(bounds), bnd_off,
        np.ascontiguousarray(nan_to, np.int32),
        out.ctypes.data_as(ctypes.c_void_p),
        int(out.dtype == np.uint16),
        None if nan_cells is None
        else nan_cells.ctypes.data_as(ctypes.c_void_p))
    return out
