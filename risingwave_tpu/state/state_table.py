"""StateTable — the single state abstraction every stateful executor uses.

Reference: src/stream/src/common/table/state_table.rs (1602 LoC): typed rows
over a LocalStateStore; key = vnode(dist_key) ++ memcomparable(pk); a
mem-table buffers writes between barriers; `commit(new_epoch)` flushes and
seals the epoch. API parity targets: init_epoch (:179), get_row (:708),
insert/delete/update (:875-921), write_chunk (:946), update_watermark (:1029),
commit (:1036), iter_with_vnode/iter_with_prefix (:1255,1315),
update_vnode_bitmap (:778).

TPU division of labor: device executors keep their *compute* state resident
in HBM; the StateTable is the *durability* path — at each barrier the
executor writes its state delta here, `commit` flushes to the state store,
and recovery rebuilds HBM arrays by scanning this table. Consistency checks
(insert-must-not-exist etc.) mirror the reference's OpConsistencyLevel
(mem_table.rs) and catch changelog bugs early.

The mem-table is a list of write SEGMENTS in staging order. One
`write_chunk_columns` call is one `ColumnarSegment` (state/store.py),
whatever the table's column types, NULLs in its value columns and a
descending pk included: the `[n, K]` key matrix, `[n, V]` value matrix and
put lane the batch codec made (state/serde.py `BatchCodec`: every type is
fixed-width on the host), never taken apart into a `bytes` object per key —
`commit` hands it to the store as it is, and it stays that way up to the L0
run (state/hummock.py, state/sstable.py). What goes to a dict segment, the
row form (`row_path_rows` counts it): a batch's rows with a NULL in a pk
column, whose key is shorter than the others' (flag 0x00 and no body), and
the writes that are no batch — insert / delete / update / write_chunk_rows
(a source's offsets, dedup, a simple agg's one row, a table with a conflict
check). A later segment overlays an earlier one and within a columnar
segment the last row of a key counts, so the last write wins exactly as one
dict gave it. What reaches the object store (`RWS1`) is the same bytes
either way, and what `RowSerde.decode` / `decode_memcomparable` read.
"""

from __future__ import annotations

from typing import Iterator, Optional, Sequence, Union

import numpy as np

from ..common.types import Schema
from ..common.vnode import VNODE_COUNT, compute_vnodes_numpy
from .serde import BatchCodec, RowSerde, encode_memcomparable
from .store import (ColumnarSegment, StateStore, WriteBatch,
                    encode_table_key, segments_get, segments_range)


class StateTableError(Exception):
    pass


class StateTable:
    def __init__(
        self,
        store: StateStore,
        table_id: int,
        schema: Schema,
        pk_indices: Sequence[int],
        dist_key_indices: Optional[Sequence[int]] = None,
        vnode_bitmap: Optional[np.ndarray] = None,
        pk_descending: Optional[Sequence[bool]] = None,
        check_consistency: bool = True,
    ):
        self.store = store
        self.table_id = table_id
        self.schema = schema
        self.pk_indices = tuple(pk_indices)
        # dist key defaults to the pk prefix = first pk column (reference
        # defaults dist key ⊆ pk); empty tuple = singleton (vnode 0).
        self.dist_key_indices = tuple(dist_key_indices if dist_key_indices is not None
                                      else self.pk_indices[:1])
        self.vnode_bitmap = (np.ones(VNODE_COUNT, dtype=bool)
                             if vnode_bitmap is None else np.asarray(vnode_bitmap, dtype=bool))
        self.pk_descending = tuple(pk_descending) if pk_descending is not None else None
        self.check_consistency = check_consistency
        self._pk_types = tuple(schema[i].data_type for i in self.pk_indices)
        self._serde = RowSerde(schema)
        # mem-table: write segments in staging order (see module doc). A
        # dict segment maps full key -> row (None = delete); a columnar
        # one holds pre-ENCODED values, decoded lazily on read-through.
        self._mem: list[Union[dict[bytes, Optional[tuple]],
                              ColumnarSegment]] = []
        self.epoch: Optional[int] = None
        # rows `write_chunk_rows` has staged, ever: the row form's share of
        # this table's writes (a columnar segment counts none)
        self.row_path_rows = 0
        self._codec = BatchCodec(schema, self.pk_indices, self.pk_descending,
                                 key_prefix=5)

    # ------------------------------------------------------------- keys
    def _vnode_of(self, row: tuple) -> int:
        if not self.dist_key_indices:
            return 0
        cols = [np.asarray([0 if row[i] is None else row[i]])
                for i in self.dist_key_indices]
        # match column dtypes so host hash == device hash
        cols = [c.astype(self.schema[i].data_type.np_dtype)
                for c, i in zip(cols, self.dist_key_indices)]
        return int(compute_vnodes_numpy(cols)[0])

    def _key_of(self, row: tuple) -> bytes:
        pk = tuple(row[i] for i in self.pk_indices)
        return encode_table_key(
            self.table_id, self._vnode_of(row),
            encode_memcomparable(pk, self._pk_types, self.pk_descending))

    def key_of_pk(self, pk: tuple, vnode: int) -> bytes:
        return encode_table_key(
            self.table_id, vnode, encode_memcomparable(pk, self._pk_types, self.pk_descending))

    def vnode_of_pk(self, pk: tuple) -> int:
        """Vnode for a pk tuple (requires dist_key ⊆ pk, the reference's
        batch point-get precondition)."""
        if not self.dist_key_indices:
            return 0
        pos = [self.pk_indices.index(i) for i in self.dist_key_indices]
        cols = [np.asarray([pk[p]]).astype(
            self.schema[i].data_type.np_dtype)
            for p, i in zip(pos, self.dist_key_indices)]
        return int(compute_vnodes_numpy(cols)[0])

    def vnode_key_range(self, vnode: int) -> tuple[bytes, bytes]:
        """[start, end) covering one vnode of this table."""
        start = encode_table_key(self.table_id, vnode, b"")
        end = (encode_table_key(self.table_id, vnode + 1, b"")
               if vnode + 1 < VNODE_COUNT
               else (self.table_id + 1).to_bytes(4, "big"))
        return start, end

    # ------------------------------------------------------------ writes
    def init_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def _row_segment(self) -> dict[bytes, Optional[tuple]]:
        """The dict segment row-form writes go to: the newest segment if
        it is one, else a new one on top."""
        if not self._mem or not isinstance(self._mem[-1], dict):
            self._mem.append({})
        return self._mem[-1]

    def _mem_get(self, key: bytes) -> tuple[bool, Optional[tuple]]:
        """(found, row) of the newest staged write of `key`; found with
        row None is a staged delete."""
        found, v = segments_get(self._mem, key)
        return found, self._staged_row(v)

    def _staged_row(self, v) -> Optional[tuple]:
        """A mem-table value as a row: dict segments hold rows, columnar
        ones encoded values."""
        return self._serde.decode(v) if isinstance(v, bytes) else v

    def insert(self, row: tuple) -> None:
        k = self._key_of(row)
        if self.check_consistency and self._mem_get(k)[1] is not None:
            raise StateTableError(f"double insert for key {row!r} in table {self.table_id}")
        self._row_segment()[k] = tuple(row)

    def delete(self, row: tuple) -> None:
        # Always record a tombstone: an insert+delete within one epoch must
        # still delete any version of the key from a PRIOR epoch in the store
        # (cancelling the put alone would resurrect the old row).
        self._row_segment()[self._key_of(row)] = None

    def update(self, old_row: tuple, new_row: tuple) -> None:
        ko, kn = self._key_of(old_row), self._key_of(new_row)
        if ko == kn:
            self._row_segment()[kn] = tuple(new_row)
        else:
            self.delete(old_row)
            self.insert(new_row)

    def write_chunk_rows(self, rows: Sequence[tuple[int, tuple]]) -> None:
        """rows: (op, values) with chunk Op encoding (write_chunk :946).
        Vnodes for the whole batch are hashed in one vectorized pass — this
        is the per-barrier hot path."""
        from ..common.chunk import OP_INSERT, OP_UPDATE_INSERT
        if not rows:
            return
        self.row_path_rows += len(rows)
        vnodes = self._vnodes_of_batch([r for _, r in rows])
        seg = self._row_segment()
        for (op, row), vn in zip(rows, vnodes):
            k = self.key_of_pk(tuple(row[i] for i in self.pk_indices), int(vn))
            seg[k] = (tuple(row) if op in (OP_INSERT, OP_UPDATE_INSERT)
                      else None)

    def _vnodes_of_batch(self, rows: Sequence[tuple]) -> np.ndarray:
        if not self.dist_key_indices:
            return np.zeros(len(rows), dtype=np.int32)
        # NULL dist-key values hash as 0 — this MUST agree with the
        # device-side hash, which sees an invalid lane's canonical 0 data
        # (outer-join padding rows route through dispatchers that way)
        cols = [
            np.asarray([0 if r[i] is None else r[i] for r in rows],
                       dtype=self.schema[i].data_type.np_dtype)
            for i in self.dist_key_indices
        ]
        return compute_vnodes_numpy(cols)

    def write_chunk_columns(self, ops: np.ndarray, cols: Sequence[np.ndarray],
                            vis: np.ndarray,
                            valids: Optional[Sequence[Optional[np.ndarray]]]
                            = None) -> None:
        """Columnar batch write — the per-barrier persistence hot path.

        Keys and values of the whole batch are encoded by the table's
        `BatchCodec` (state/serde.py) and staged as ONE columnar segment,
        whatever the schema. `ops` uses chunk Op encoding; rows with vis
        False are skipped; `valids[j]` (None = no NULLs) marks column j's
        NULL lanes. Only rows with a NULL in a pk column, whose key has no
        fixed width, take the row form: no other key can equal theirs, so
        their order against the segment does not matter."""
        from ..common.chunk import OP_INSERT, OP_UPDATE_INSERT, HostChunk
        if len(cols) != len(self.schema):
            raise StateTableError(
                f"table {self.table_id}: {len(cols)} columns written, "
                f"{len(self.schema)} in its schema")
        ops, vis = np.asarray(ops), np.asarray(vis, dtype=bool)
        cols = [np.asarray(c) for c in cols]
        if valids is not None:
            valids = [None if v is None else np.asarray(v, dtype=bool)
                      for v in valids]
            pk_valids = [valids[i] for i in self.pk_indices
                         if valids[i] is not None]
            null_pk = vis & ~np.logical_and.reduce(pk_valids) \
                if pk_valids else None
            if null_pk is not None and null_pk.any():
                self.write_chunk_rows(
                    HostChunk(ops, null_pk, cols, valids).rows())
                vis = vis & ~null_pk
        idx = np.flatnonzero(vis)
        if idx.size == 0:
            return
        ops = ops[idx]
        cols = [c[idx] for c in cols]
        if valids is not None:
            valids = [None if v is None else v[idx] for v in valids]
        codec = self._codec
        cols = codec.typed(cols, valids)
        keys = codec.encode_keys(cols)
        keys[:, :4] = np.frombuffer(self.table_id.to_bytes(4, "big"),
                                    dtype=np.uint8)
        # the vnode MUST be compute_vnodes_numpy's over the dist-key columns
        # at their own dtypes (== the device hash), as `_vnode_of` has it:
        # per-row gets and deletes compute their keys that way
        keys[:, 4] = (compute_vnodes_numpy(
            [cols[i] for i in self.dist_key_indices])
            if self.dist_key_indices else 0)
        self._mem.append(ColumnarSegment(
            self.table_id, keys, codec.encode_values(cols, valids),
            (ops == OP_INSERT) | (ops == OP_UPDATE_INSERT)))

    # ------------------------------------------------------------- reads
    def get_row(self, pk: tuple, dist_values: Optional[tuple] = None) -> Optional[tuple]:
        """Read-through: mem-table first, then the store (:708)."""
        row_for_vnode = [None] * len(self.schema)
        for j, i in enumerate(self.pk_indices):
            row_for_vnode[i] = pk[j]
        if dist_values is not None:
            for j, i in enumerate(self.dist_key_indices):
                row_for_vnode[i] = dist_values[j]
        k = self._key_of(tuple(row_for_vnode))
        found, row = self._mem_get(k)
        if found:
            return row
        v = self.store.get(k)
        return self._serde.decode(v) if v is not None else None

    def get_rows(self, pks: Sequence[tuple]) -> list:
        """Batch point-get (requires dist_key ⊆ pk): vnodes for the whole
        batch hash in one vectorized pass, mem-table first, then the
        store's committed + sealed view via `get_many`. This is the
        evicted-range read-through: a reload of spilled state resolves
        every touched key in one pass instead of N `get_row` calls."""
        if not pks:
            return []
        if self.dist_key_indices:
            pos = [self.pk_indices.index(i) for i in self.dist_key_indices]
            cols = [np.asarray([0 if pk[p] is None else pk[p]
                                for pk in pks]).astype(
                        self.schema[i].data_type.np_dtype)
                    for p, i in zip(pos, self.dist_key_indices)]
            vns = compute_vnodes_numpy(cols)
        else:
            vns = np.zeros(len(pks), dtype=np.int32)
        keys = [self.key_of_pk(tuple(pk), int(vn))
                for pk, vn in zip(pks, vns)]
        out: list = []
        pending_keys, pending_pos = [], []
        for i, k in enumerate(keys):
            found, row = self._mem_get(k)
            out.append(row)
            if not found:
                pending_keys.append(k)
                pending_pos.append(i)
        for i, v in zip(pending_pos, self.store.get_many(pending_keys)):
            if v is not None:
                out[i] = self._serde.decode(v)
        return out

    def iter_vnode(self, vnode: int) -> Iterator[tuple[bytes, tuple]]:
        """All rows of one vnode, pk order, mem-table merged (:1255)."""
        start, end = self.vnode_key_range(vnode)
        merged: dict[bytes, Optional[tuple]] = {}
        for k, v in self.store.scan_range(start, end):
            merged[k] = self._serde.decode(v)
        merged.update(
            (k, self._staged_row(v))
            for k, v in segments_range(self._mem, start, end).items())
        for k in sorted(merged):
            if merged[k] is not None:
                yield k, merged[k]

    def iter_all(self) -> Iterator[tuple[bytes, tuple]]:
        for vn in np.flatnonzero(self.vnode_bitmap):
            yield from self.iter_vnode(int(vn))

    # ----------------------------------------------------------- barrier
    def commit(self, new_epoch: int) -> int:
        """Flush mem-table to the store and advance the epoch (:1036).
        Returns number of kv writes."""
        assert self.epoch is not None, "init_epoch not called"
        n = 0
        for seg in self._mem:
            if isinstance(seg, dict):
                seg = {k: None if row is None else self._serde.encode(row)
                       for k, row in seg.items()}
            if len(seg):
                n += len(seg)
                self.store.ingest_batch(
                    WriteBatch(self.table_id, self.epoch, seg))
        self._mem.clear()
        self.epoch = new_epoch
        return n

    def update_vnode_bitmap(self, bitmap: np.ndarray) -> None:
        """Scaling: this instance now owns a different vnode set (:778).
        Mem-table must be empty (only called at barriers)."""
        assert not self._mem, "dirty mem-table during reschedule"
        self.vnode_bitmap = np.asarray(bitmap, dtype=bool)
