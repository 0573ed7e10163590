"""Native host runtime kernels (C++), compiled on first use.

The reference's host runtime is native Rust end to end; here the pieces
with real per-row Python overhead — vnode hashing and the SST record
packer on the persistence path — are C++ behind ctypes (which releases
the GIL for the call). `_rowcodec.so` is built ONLY from the tracked `rowcodec.cc` next to this file (the
artifact is git-ignored), with `g++` on first use. A machine without a
toolchain keeps working on the pure-Python twins (`lib()` returns None
and callers take them), but the choice is never silent: it is logged
once, at WARNING with the reason.
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
_SO = os.path.join(os.path.dirname(__file__), "_rowcodec.so")
_log = logging.getLogger(__name__)


@lru_cache(maxsize=1)
def lib() -> Optional[ctypes.CDLL]:
    so = _SO

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
            l.sst_unpack_fixed      # an artifact of an older rowcodec.cc
        except (OSError, AttributeError):
            # stale or foreign-arch artifact: rebuild for THIS machine
            build()
            l = ctypes.CDLL(so)
        l.crc32_i64_cols.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p]
        l.sst_pack_fixed.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p]
        l.sst_pack_fixed.restype = ctypes.c_int64
        l.sst_index.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        l.sst_index.restype = ctypes.c_int64
        l.sst_unpack_fixed.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p]
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


def sst_pack_fixed(keys: np.ndarray, vals: np.ndarray, put: np.ndarray,
                   out: np.ndarray) -> Optional[int]:
    """Write the SST records of a fixed-width run (keys [n, K] uint8, vals
    [n, V] uint8, put [n] bool; a row with put False is a tombstone) into
    the uint8 buffer `out`; returns the bytes written, None if the native
    lib is unavailable (state/sstable.py then packs with numpy)."""
    l = lib()
    if l is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.uint8)
    vals = np.ascontiguousarray(vals, dtype=np.uint8)
    put = np.ascontiguousarray(put, dtype=np.bool_)
    n, k = keys.shape
    size = n * (8 + k) + int(np.count_nonzero(put)) * vals.shape[1]
    if vals.shape[0] != n or put.shape != (n,) or out.dtype != np.uint8 \
            or not out.flags.c_contiguous or out.size < size:
        raise ValueError(f"sst_pack_fixed: {keys.shape} keys, {vals.shape} "
                         f"values, {put.shape} puts into {out.size} bytes")
    return l.sst_pack_fixed(keys.ctypes.data, vals.ctypes.data,
                            put.ctypes.data, n, k, vals.shape[1],
                            out.ctypes.data)


def sst_index(body: bytes, off: int, end: int, count: int
              ) -> Optional[tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """(key offsets int64, key lengths uint32, value lengths uint32 with
    0xFFFFFFFF for a tombstone) of the `count` SST records in
    `body[off:end]`; None if the native lib is unavailable. Raises
    ValueError where the records do not end exactly at `end`."""
    l = lib()
    if l is None:
        return None
    koff = np.empty(count, dtype=np.int64)
    klen = np.empty(count, dtype=np.uint32)
    vlen = np.empty(count, dtype=np.uint32)
    if not 0 <= off <= end <= len(body):
        raise ValueError(f"sst_index: [{off}, {end}) of {len(body)} bytes")
    got = l.sst_index(body, end, off, count, koff.ctypes.data,
                      klen.ctypes.data, vlen.ctypes.data)
    if got != end:
        raise ValueError(f"sst_index: {count} records end at {got}, not "
                         f"at {end}")
    return koff, klen, vlen


def sst_unpack_fixed(body: bytes, koff: np.ndarray, put: np.ndarray,
                     kw: int, vw: int) -> tuple[np.ndarray, np.ndarray]:
    """(keys [n, kw], values [n, vw]) of the records at `koff` (from
    `sst_index`, which also showed every key kw and every put value vw
    bytes wide). Only called where `sst_index` returned a result."""
    koff = np.ascontiguousarray(koff, dtype=np.int64)
    put = np.ascontiguousarray(put, dtype=np.bool_)
    n = len(koff)
    last_end = int(koff[-1]) + kw + 4 + (vw if put[-1] else 0) if n else 0
    if put.shape != (n,) or last_end > len(body):
        raise ValueError("sst_unpack_fixed: records run past the body")
    keys = np.empty((n, kw), dtype=np.uint8)
    vals = np.empty((n, vw), dtype=np.uint8)
    lib().sst_unpack_fixed(body, koff.ctypes.data, put.ctypes.data, n, kw,
                           vw, keys.ctypes.data, vals.ctypes.data)
    return keys, vals
