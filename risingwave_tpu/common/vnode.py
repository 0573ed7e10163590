"""Consistent-hash virtual nodes.

Reference: src/common/src/hash/consistent_hash/vnode.rs:34-157 — 256 vnodes,
`vnode = crc32(dist_key) % 256`, computed vectorized per chunk
(`VirtualNode::compute_chunk`). Here the crc32 runs *on device* as a
byte-table-lookup kernel over the key columns' little-endian bytes, so routing
never leaves HBM. Data-distribution decisions (vnode -> shard) all key off
this single function.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from .floatbits import (float_identity_bits, float_pair_bits,
                        float_pair_bits_np)

VNODE_BITS = 8
VNODE_COUNT = 1 << VNODE_BITS  # 256


@lru_cache(maxsize=1)
def _crc32_table_np() -> np.ndarray:
    poly = np.uint32(0xEDB88320)
    table = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = np.uint32(i)
        for _ in range(8):
            c = np.where(c & 1, (c >> np.uint32(1)) ^ poly, c >> np.uint32(1))
        table[i] = c
    return table


def _uint_image(col: jnp.ndarray, f64_bits) -> jnp.ndarray:
    """Column -> unsigned ints of the same width. The TPU compiler has no
    bitcast FROM f64 (common/floatbits.py), so an f64 column goes through
    `f64_bits` instead of a reinterpreting view."""
    if col.dtype == jnp.bool_:
        return col.astype(jnp.uint8)
    if col.dtype == jnp.float64:
        return f64_bits(col).view(jnp.uint64)
    return col.view(jnp.dtype(f"uint{8 * col.dtype.itemsize}"))


def crc32_columns(columns: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Vectorized crc32 over the little-endian bytes of fixed-width columns.

    columns: arrays of identical leading shape [N]; each element contributes
    its dtype's width in bytes, column-major in argument order (a stable,
    injective-enough serialization standing in for the reference's
    value-encoding bytes).
    Returns uint32 [N].
    """
    table = jnp.asarray(_crc32_table_np())
    crc = jnp.full(columns[0].shape[0], 0xFFFFFFFF, dtype=jnp.uint32)
    for col in columns:
        nbytes = col.dtype.itemsize
        # reinterpret to unsigned of same width, then peel bytes LE
        u = _uint_image(col, float_identity_bits).astype(jnp.uint64)
        for b in range(nbytes):
            byte = ((u >> jnp.uint64(8 * b)) & jnp.uint64(0xFF)).astype(jnp.uint32)
            idx = (crc ^ byte) & jnp.uint32(0xFF)
            crc = (crc >> jnp.uint32(8)) ^ jnp.take(table, idx.astype(jnp.int32))
    return crc ^ jnp.uint32(0xFFFFFFFF)


def compute_vnodes(key_columns: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """vnode per row = splitmix64(key columns) % 256  (int32 [N]).

    Reference semantics at vnode.rs:126 (`compute_chunk`): one consistent
    hash over the distribution-key columns, modulo VNODE_COUNT. The
    reference hashes with crc32; here the mixer is a splitmix64 chain —
    measured on TPU, the table-driven crc's 8 byte-gathers cost ~13ms per
    131k-row chunk (small-table gathers do not vectorize on the VPU) and
    even a branchless bitwise crc32 costs 6.6ms from its 64-step serial
    dependency chain, while the splitmix chain is pure wide ALU ops at
    microseconds. Any consistent hash preserves the vnode contract; crc32
    itself remains (crc32_columns) for value-serialization golden tests.
    """
    h = jnp.full(key_columns[0].shape[0], 0x243F6A8885A308D3,
                 dtype=jnp.uint64)
    for col in key_columns:
        u = _uint_image(col, float_pair_bits)
        x = h ^ (u.astype(jnp.uint64) * jnp.uint64(0x9E3779B97F4A7C15))
        x = x + jnp.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
        h = x ^ (x >> jnp.uint64(31))
    return (h & jnp.uint64(VNODE_COUNT - 1)).astype(jnp.int32)


def crc32_numpy(columns: Sequence[np.ndarray]) -> np.ndarray:
    """Host mirror of crc32_columns (golden tests, meta-side placement)."""
    table = _crc32_table_np()
    crc = np.full(len(columns[0]), 0xFFFFFFFF, dtype=np.uint32)
    for col in columns:
        col = np.asarray(col)
        if col.dtype == np.bool_:
            col = col.astype(np.uint8)
        nbytes = col.dtype.itemsize
        u = col.view(f"uint{8 * nbytes}").astype(np.uint64)
        for b in range(nbytes):
            byte = ((u >> np.uint64(8 * b)) & np.uint64(0xFF)).astype(np.uint32)
            idx = (crc ^ byte) & np.uint32(0xFF)
            crc = (crc >> np.uint32(8)) ^ table[idx]
    return crc ^ np.uint32(0xFFFFFFFF)


def compute_vnodes_numpy(key_columns: Sequence[np.ndarray]) -> np.ndarray:
    """Host mirror of compute_vnodes — MUST produce identical vnodes (the
    meta side places state by the same hash the device routes by)."""
    with np.errstate(over="ignore"):
        h = np.full(len(key_columns[0]), 0x243F6A8885A308D3, dtype=np.uint64)
        for col in key_columns:
            col = np.asarray(col)
            if col.dtype == np.bool_:
                col = col.astype(np.uint8)
            if col.dtype == np.float64:
                # same f32-pair image the device hashes (floatbits.py)
                col = float_pair_bits_np(col)
            u = col.view(f"uint{8 * col.dtype.itemsize}").astype(np.uint64)
            x = h ^ (u * np.uint64(0x9E3779B97F4A7C15))
            x = x + np.uint64(0x9E3779B97F4A7C15)
            x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
            x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
            h = x ^ (x >> np.uint64(31))
    return (h & np.uint64(VNODE_COUNT - 1)).astype(np.int32)
