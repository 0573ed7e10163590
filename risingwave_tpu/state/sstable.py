"""SSTable — one immutable sorted run of (key, value | tombstone) entries.

Reference: src/storage/src/hummock/sstable/{builder.rs,mod.rs} — block-based
format with bloom filters and a footer. Here one checkpoint flush is a few
MB at most, so the format is a single self-checksummed block parsed whole on
open: entries are stored sorted, tombstones are explicit (a delete must mask
older versions in lower levels until bottom-level compaction drops it).

Layout (little-endian), unchanged since the first SST this repo wrote:
    magic "RWS1"
    u32 count | u64 epoch
    count * ( u32 klen | key | u32 vlen_or_TOMB | value )
    u32 crc32(everything after magic)

In memory a run is ARRAY-BACKED where it can be. An `SsTable` is a list of
parts in key order; every key starts with its table's 4-byte id, so the
entries of one table are one contiguous stretch of the run and a part holds
the entries of whole tables only:

- `FixedPart`: ONE table whose keys are all K bytes and whose values are
  all V bytes wide — a `[n, K]` uint8 key matrix (sorted, unique), a
  `[n, V]` value matrix and a put lane (False = tombstone). Searched with
  `searchsorted`, packed into the layout above by the native memcpy loop
  (`native.sst_pack_fixed`; a numpy twin where no toolchain built it). No
  `bytes` object exists per entry until a read asks for that entry.
- `ListPart`: parallel `bytes` lists, for whatever is not fixed-width (row
  form writes, FLOAT64 / descending keys, the log store's records).

`SsTable.parse` (what is READ from the object store: crc check first) gives
the same parts: the native codec indexes the records and copies each
fixed-width table out as a `FixedPart`; without a toolchain every entry goes
to one `ListPart`, as it always did.

`merge_runs` is the one merge behind both a checkpoint's upload and a
compaction: write segments or runs, oldest first, the newest version of a
key wins; a table whose inputs are all fixed-width of one shape merges as
arrays (concatenate, stable sort, keep the last of each key), any other
table by the dict overlay this module always used.
"""

from __future__ import annotations

import struct
import zlib
from bisect import bisect_left
from typing import Iterator, Optional, Sequence, Union

import numpy as np

from .. import native
from .store import ColumnarSegment

MAGIC = b"RWS1"
META_MAGIC = b"RWM1"
TOMBSTONE = 0xFFFFFFFF


class SsTableCorruption(Exception):
    pass


class MetaCorruption(SsTableCorruption):
    """A framed meta object (MANIFEST/CATALOG/backup manifest) failed its
    checksum — same detection class as an SST, same quarantine rules."""


def frame_meta(body: bytes) -> bytes:
    """Self-checksummed framing for meta objects — the MANIFEST and
    CATALOG carry the same crc32 integrity envelope SSTs always had, so
    a torn or bit-rotted manifest is DETECTED at open instead of being
    json-decoded into a plausible-but-wrong world."""
    return META_MAGIC + body + struct.pack("<I", zlib.crc32(body))


def unframe_meta(data: bytes, name: str = "meta") -> bytes:
    """Verify + strip the meta frame. Unframed blobs pass through —
    stores written before the framing existed still open (their json
    layer keeps rejecting garbage, just without crc attribution)."""
    if data[:4] != META_MAGIC:
        return data
    body, (crc,) = data[4:-4], struct.unpack("<I", data[-4:])
    if zlib.crc32(body) != crc:
        raise MetaCorruption(f"{name}: checksum mismatch")
    return body


def _pack_entries(entries) -> list[bytes]:
    """The record bytes of key-sorted, key-unique (key, value | None)s."""
    parts = []
    prev = None
    for k, v in entries:
        assert prev is None or prev < k, "entries must be sorted+unique"
        prev = k
        parts.append(struct.pack("<I", len(k)))
        parts.append(k)
        if v is None:
            parts.append(struct.pack("<I", TOMBSTONE))
        else:
            parts.append(struct.pack("<I", len(v)))
            parts.append(v)
    return parts


def build_sstable(epoch: int,
                  entries: Sequence[tuple[bytes, Optional[bytes]]]) -> bytes:
    """entries must be key-sorted and key-unique; value None = tombstone."""
    body = b"".join([struct.pack("<IQ", len(entries), epoch)]
                    + _pack_entries(entries))
    return MAGIC + body + struct.pack("<I", zlib.crc32(body))


class ListPart:
    """A stretch of a run as parallel `bytes` lists (sorted, unique)."""

    __slots__ = ("keys", "vals")

    def __init__(self, keys: list[bytes], vals: list[Optional[bytes]]):
        self.keys = keys
        self.vals = vals

    def __len__(self) -> int:
        return len(self.keys)

    @property
    def min_key(self) -> bytes:
        return self.keys[0]

    @property
    def max_key(self) -> bytes:
        return self.keys[-1]

    @property
    def payload_bytes(self) -> int:
        return sum(len(k) for k in self.keys) \
            + sum(len(v) for v in self.vals if v is not None)

    @property
    def packed_size(self) -> int:
        return 8 * len(self.keys) + self.payload_bytes

    def pack_into(self, out: np.ndarray) -> None:
        out[:] = np.frombuffer(
            b"".join(_pack_entries(zip(self.keys, self.vals))),
            dtype=np.uint8)

    def get(self, key: bytes) -> tuple[bool, Optional[bytes]]:
        i = bisect_left(self.keys, key)
        if i < len(self.keys) and self.keys[i] == key:
            return True, self.vals[i]
        return False, None

    def _bounds(self, start: bytes, end: bytes) -> tuple[int, int]:
        i = bisect_left(self.keys, start)
        return i, max(i, bisect_left(self.keys, end) if end
                      else len(self.keys))

    def iter_range(self, start: bytes, end: bytes
                   ) -> Iterator[tuple[bytes, Optional[bytes]]]:
        for r in range(*self._bounds(start, end)):
            yield self.keys[r], self.vals[r]

    def range_part(self, start: bytes, end: bytes) -> "ListPart":
        """The entries in [start, end) as a part of their own."""
        i, j = self._bounds(start, end)
        return ListPart(self.keys[i:j], self.vals[i:j])


class FixedPart(ColumnarSegment):
    """One table's stretch of a run as arrays: a ColumnarSegment whose
    rows are key-sorted and key-unique, so it is its own index."""

    __slots__ = ()

    def _sorted(self) -> tuple[np.ndarray, np.ndarray]:
        if self._index is None:
            self._index = (self.key_view, np.arange(len(self)))
        return self._index

    @property
    def min_key(self) -> bytes:
        return self.keys[0].tobytes()

    @property
    def max_key(self) -> bytes:
        return self.keys[-1].tobytes()

    @property
    def payload_bytes(self) -> int:
        return len(self) * self.keys.shape[1] \
            + int(np.count_nonzero(self.put)) * self.vals.shape[1]

    @property
    def packed_size(self) -> int:
        return 8 * len(self) + self.payload_bytes

    def pack_into(self, out: np.ndarray) -> None:
        if native.sst_pack_fixed(self.keys, self.vals, self.put,
                                 out) is None:
            out[:] = _pack_fixed_numpy(self.keys, self.vals, self.put)

    def iter_range(self, start: bytes, end: bytes
                   ) -> Iterator[tuple[bytes, Optional[bytes]]]:
        i, j = self._bounds(start, end)
        kw, vw = self.keys.shape[1], self.vals.shape[1]
        for lo in range(i, j, 1024):         # lazily, a block at a time
            hi = min(lo + 1024, j)
            kbuf = self.keys[lo:hi].tobytes()
            vbuf = self.vals[lo:hi].tobytes()
            for r, put in enumerate(self.put[lo:hi].tolist()):
                yield (kbuf[r * kw:(r + 1) * kw],
                       vbuf[r * vw:(r + 1) * vw] if put else None)

    def range_part(self, start: bytes, end: bytes) -> "FixedPart":
        """The entries in [start, end) as a part of their own (views)."""
        i, j = self._bounds(start, end)
        return FixedPart(self.table_id, self.keys[i:j], self.vals[i:j],
                         self.put[i:j])


Part = Union[ListPart, FixedPart]


def _pack_fixed_numpy(keys: np.ndarray, vals: np.ndarray,
                      put: np.ndarray) -> np.ndarray:
    """numpy twin of `native.sst_pack_fixed`: every record laid out at
    full width, then the value bytes of the tombstones compressed away."""
    n, kw = keys.shape
    vw = vals.shape[1]
    rec = np.empty((n, 8 + kw + vw), dtype=np.uint8)
    rec[:, :4] = np.frombuffer(struct.pack("<I", kw), dtype=np.uint8)
    rec[:, 4:4 + kw] = keys
    rec[:, 4 + kw:8 + kw] = np.frombuffer(struct.pack("<I", vw),
                                          dtype=np.uint8)
    rec[~put, 4 + kw:8 + kw] = 0xFF
    rec[:, 8 + kw:] = vals
    if put.all():
        return rec.ravel()
    keep = np.ones(rec.shape, dtype=bool)
    keep[~put, 8 + kw:] = False
    return rec[keep]


def build_sstable_parts(epoch: int, parts: Sequence[Part]) -> bytes:
    """The SST of `parts` (in key order): byte for byte what
    `build_sstable(epoch, <their entries>)` gives."""
    sizes = [p.packed_size for p in parts]
    out = np.empty(4 + 12 + sum(sizes) + 4, dtype=np.uint8)
    out[:16] = np.frombuffer(
        MAGIC + struct.pack("<IQ", sum(len(p) for p in parts), epoch),
        dtype=np.uint8)
    off = 16
    for part, size in zip(parts, sizes):
        part.pack_into(out[off:off + size])
        off += size
    out[off:] = np.frombuffer(
        struct.pack("<I", zlib.crc32(memoryview(out)[4:off])),
        dtype=np.uint8)
    return out.tobytes()


def _append_part(parts: list[Part], part: Part) -> None:
    """`part` after `parts` in key order; adjacent ListParts become one."""
    if isinstance(part, ListPart) and parts \
            and isinstance(parts[-1], ListPart):
        parts[-1] = ListPart(parts[-1].keys + part.keys,
                             parts[-1].vals + part.vals)
    else:
        parts.append(part)


# ------------------------------------------------------------------- merge
def _dict_as_segment(puts: dict, like: ColumnarSegment
                     ) -> Optional[ColumnarSegment]:
    """Row-form writes as arrays of `like`'s shape, None if any key or
    value has another width."""
    kw, vw = like.keys.shape[1], like.vals.shape[1]
    if any(len(k) != kw for k in puts) \
            or any(v is not None and len(v) != vw for v in puts.values()):
        return None
    n, zero = len(puts), bytes(vw)
    return ColumnarSegment(
        like.table_id,
        np.frombuffer(b"".join(puts), dtype=np.uint8).reshape(n, kw),
        np.frombuffer(b"".join(zero if v is None else v
                               for v in puts.values()),
                      dtype=np.uint8).reshape(n, vw),
        np.fromiter((v is not None for v in puts.values()), bool, n))


def _merge_table_fixed(pieces: list, drop_tombstones: bool
                       ) -> Optional[FixedPart]:
    """One table's pieces (oldest first) merged as arrays, None if they
    are not all of one fixed-width shape."""
    arrays = [p for p in pieces if not isinstance(p, dict)]
    if not arrays or len({(a.keys.shape[1], a.vals.shape[1])
                          for a in arrays}) != 1:
        return None
    segs = [_dict_as_segment(p, arrays[0]) if isinstance(p, dict) else p
            for p in pieces]
    if any(s is None for s in segs):
        return None
    if len(segs) == 1 and isinstance(segs[0], FixedPart) \
            and not drop_tombstones:
        return segs[0]
    keys = np.concatenate([s.keys for s in segs])
    vals = np.concatenate([s.vals for s in segs])
    put = np.concatenate([s.put for s in segs])
    view = keys.view(f"S{keys.shape[1]}").ravel()
    order = np.argsort(view, kind="stable")
    in_order = view[order]
    newest = np.ones(len(order), dtype=bool)         # last of each key
    newest[:-1] = in_order[1:] != in_order[:-1]
    sel = order[newest]
    if drop_tombstones:
        sel = sel[put[sel]]
    return FixedPart(arrays[0].table_id, np.take(keys, sel, axis=0),
                     np.take(vals, sel, axis=0), put[sel])


def _merge_table_generic(pieces: list, drop_tombstones: bool) -> ListPart:
    merged: dict[bytes, Optional[bytes]] = {}
    for p in pieces:
        merged.update(p if isinstance(p, dict) else p.to_puts())
    items = sorted((k, v) for k, v in merged.items()
                   if v is not None or not drop_tombstones)
    return ListPart([k for k, _ in items], [v for _, v in items])


def merge_runs(sources: Sequence[Union[dict, ColumnarSegment, ListPart]],
               drop_tombstones: bool = False) -> list[Part]:
    """Merge write segments and / or parts of runs, given OLDEST FIRST,
    into the parts of one run: per key the newest version, tombstones
    kept unless `drop_tombstones` (nothing lives below the output)."""
    tables: dict[bytes, list] = {}         # 4-byte table id -> its pieces
    for src in sources:
        if not len(src):
            continue
        if isinstance(src, ColumnarSegment):
            tables.setdefault(src.keys[0, :4].tobytes(), []).append(src)
            continue
        split: dict[bytes, dict] = {}
        for k, v in (src.items() if isinstance(src, dict)
                     else zip(src.keys, src.vals)):
            split.setdefault(k[:4], {})[k] = v
        for table, puts in split.items():
            tables.setdefault(table, []).append(puts)
    parts: list[Part] = []
    for table in sorted(tables):
        part = _merge_table_fixed(tables[table], drop_tombstones) \
            or _merge_table_generic(tables[table], drop_tombstones)
        if len(part):
            _append_part(parts, part)
    return parts


def _parse_entries(body: bytes, off: int, count: int) -> ListPart:
    keys: list[bytes] = []
    vals: list[Optional[bytes]] = []
    for _ in range(count):
        (klen,) = struct.unpack_from("<I", body, off)
        off += 4
        keys.append(body[off:off + klen])
        off += klen
        (vlen,) = struct.unpack_from("<I", body, off)
        off += 4
        if vlen == TOMBSTONE:
            vals.append(None)
        else:
            vals.append(body[off:off + vlen])
            off += vlen
    return ListPart(keys, vals)


def _parse_parts(body: bytes, off: int, end: int, count: int
                 ) -> list[Part]:
    """The parts of the `count` records in `body[off:end]`: a table whose
    records are all of one key and one value width comes out a FixedPart
    (indexed and copied by the native codec), anything else — and
    everything, where no toolchain built the codec — entry by entry."""
    index = native.sst_index(body, off, end, count) if count else None
    if index is None:
        return [_parse_entries(body, off, count)] if count else []
    koff, klen, vlen = index
    put = vlen != TOMBSTONE
    # the 4-byte table id of every key as a number; a shorter key is a
    # stretch of its own (numbered below zero)
    head = np.frombuffer(body, dtype=np.uint8)[
        np.minimum(koff, end - 4)[:, None] + np.arange(4)]
    table = np.where(klen >= 4, head.view(">u4").ravel().astype(np.int64),
                     -1 - np.arange(count))
    cuts = [0] + (np.flatnonzero(table[1:] != table[:-1]) + 1).tolist() \
        + [count]
    parts: list[Part] = []
    for lo, hi in zip(cuts, cuts[1:]):
        kws, vws = np.unique(klen[lo:hi]), np.unique(vlen[lo:hi][put[lo:hi]])
        if len(kws) == 1 and len(vws) == 1 and kws[0] >= 4:
            keys, vals = native.sst_unpack_fixed(
                body, koff[lo:hi], put[lo:hi], int(kws[0]), int(vws[0]))
            parts.append(FixedPart(int(table[lo]), keys, vals, put[lo:hi]))
        else:
            _append_part(parts, _parse_entries(body, int(koff[lo]) - 4,
                                               hi - lo))
    return parts


class SsTable:
    """One run in memory: its parts in key order (see the module doc)."""

    def __init__(self, sst_id: int, epoch: int, parts: Sequence[Part]):
        self.sst_id = sst_id
        self.epoch = epoch
        self.parts = list(parts)
        self._max_keys = [p.max_key for p in self.parts]

    @classmethod
    def parse(cls, sst_id: int, data: bytes) -> "SsTable":
        if data[:4] != MAGIC:
            raise SsTableCorruption(f"sst {sst_id}: bad magic")
        (crc,) = struct.unpack("<I", data[-4:])
        if zlib.crc32(memoryview(data)[4:-4]) != crc:
            raise SsTableCorruption(f"sst {sst_id}: checksum mismatch")
        count, epoch = struct.unpack_from("<IQ", data, 4)
        try:
            parts = _parse_parts(data, 16, len(data) - 4, count)
        except ValueError as e:
            raise SsTableCorruption(f"sst {sst_id}: {e}") from None
        return cls(sst_id, epoch, parts)

    def __len__(self) -> int:
        return sum(len(p) for p in self.parts)

    @property
    def payload_bytes(self) -> int:
        """Key bytes plus value bytes: the size compaction budgets by."""
        return sum(p.payload_bytes for p in self.parts)

    def entries(self) -> Iterator[tuple[bytes, Optional[bytes]]]:
        return self.iter_range(b"", b"")

    @property
    def keys(self) -> list[bytes]:
        return [k for k, _ in self.entries()]

    @property
    def vals(self) -> list[Optional[bytes]]:
        return [v for _, v in self.entries()]

    def get(self, key: bytes) -> tuple[bool, Optional[bytes]]:
        """(found, value) — found with value None means tombstone."""
        i = bisect_left(self._max_keys, key)
        if i < len(self.parts):
            return self.parts[i].get(key)
        return False, None

    def parts_in(self, start: bytes, end: bytes) -> Iterator[Part]:
        """The parts that may hold a key in [start, end)."""
        for part in self.parts[bisect_left(self._max_keys, start):]:
            if end and part.min_key >= end:
                break
            yield part

    def iter_range(self, start: bytes, end: bytes
                   ) -> Iterator[tuple[bytes, Optional[bytes]]]:
        for part in self.parts_in(start, end):
            yield from part.iter_range(start, end)

    @property
    def min_key(self) -> bytes:
        return self.parts[0].min_key if self.parts else b""

    @property
    def max_key(self) -> bytes:
        return self._max_keys[-1] if self.parts else b""
