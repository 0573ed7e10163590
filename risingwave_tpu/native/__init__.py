"""Native host runtime kernels (C++), compiled on first use.

The reference's host runtime is native Rust end to end; here the pieces
with real per-row Python overhead — batch key/value serde and vnode
hashing on the persistence path — are C++ behind ctypes. `_rowcodec.so`
is built ONLY from the tracked `rowcodec.cc` next to this file (the
artifact is git-ignored), with `g++` on first use. A machine without a
toolchain keeps working on the pure-Python twins (`lib()` returns None
and callers take them), but the choice is never silent: it is logged
once, at WARNING with the reason, and `chip_smoke.py` reports it.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import tempfile
from functools import lru_cache
from typing import Optional

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "rowcodec.cc")
_log = logging.getLogger(__name__)


@lru_cache(maxsize=1)
def lib() -> Optional[ctypes.CDLL]:
    so = os.path.join(os.path.dirname(__file__), "_rowcodec.so")

    def build() -> None:
        with tempfile.TemporaryDirectory() as td:
            tmp = os.path.join(td, "rowcodec.so")
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC],
                check=True, capture_output=True)
            os.replace(tmp, so)

    try:
        if (not os.path.exists(so)
                or os.path.getmtime(so) < os.path.getmtime(_SRC)):
            build()
        try:
            l = ctypes.CDLL(so)
        except OSError:
            # stale or foreign-arch artifact: rebuild for THIS machine
            build()
            l = ctypes.CDLL(so)
        l.mc_encode_i64.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p]
        l.row_encode_i64.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p]
        l.crc32_i64_cols.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p]
        _log.info("native row codec loaded: %s", so)
        return l
    except (OSError, subprocess.CalledProcessError) as e:
        # no toolchain / unloadable artifact: the Python twins take over.
        # lru_cache makes this the ONE place the choice is made and said.
        detail = (e.stderr.decode(errors="replace")[-400:]
                  if isinstance(e, subprocess.CalledProcessError)
                  and e.stderr else "")
        _log.warning("native row codec unavailable (%r %s): the persist "
                     "path runs on the pure-Python row codec", e, detail)
        return None


def mc_encode_i64_batch(vals: np.ndarray) -> Optional[np.ndarray]:
    """vals [n, k] int64 -> [n, 9k] uint8 memcomparable keys (asc, no
    nulls); None if the native lib is unavailable."""
    l = lib()
    if l is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    n, k = vals.shape
    out = np.empty((n, 9 * k), dtype=np.uint8)
    l.mc_encode_i64(vals.ctypes.data, n, k, out.ctypes.data)
    return out


def row_encode_i64_batch(vals: np.ndarray, nb: int) -> Optional[np.ndarray]:
    """vals [n, k] int64 -> [n, nb + 8k] uint8 value rows (no nulls)."""
    l = lib()
    if l is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    n, k = vals.shape
    out = np.empty((n, nb + 8 * k), dtype=np.uint8)
    l.row_encode_i64(vals.ctypes.data, n, k, nb, out.ctypes.data)
    return out


def crc32_i64_batch(vals: np.ndarray) -> Optional[np.ndarray]:
    """vals [n, k] int64 -> uint32 [n] crc32 (vnode hash)."""
    l = lib()
    if l is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    n, k = vals.shape
    out = np.empty(n, dtype=np.uint32)
    l.crc32_i64_cols(vals.ctypes.data, n, k, out.ctypes.data)
    return out
