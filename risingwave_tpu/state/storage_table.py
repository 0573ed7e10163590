"""StorageTable — the batch/serving read path over an MV's committed state.

Reference: src/storage/src/table/batch_table/storage_table.rs:56,646-661 —
batch queries point-get and range-scan a materialized table at a pinned
snapshot epoch (the Hummock version meta committed), never seeing
uncommitted streaming writes.

TPU build: reads are HOST-side (serving pulls rows out of the system, so
there is nothing to gain — and a blocking fetch per read to lose — from
routing them through the device). Snapshot isolation comes from the
store's `committed_only` read mode: Hummock serves only SSTs under the
manifest; streaming epochs still in the shared buffer are invisible. Key
construction is DELEGATED to a StateTable (one copy of the
`table_id ++ vnode ++ memcomparable(pk)` layout), so batch reads always
find streaming writes.
"""

from __future__ import annotations

from typing import Iterator, Optional, Sequence

import numpy as np

from ..common.types import Schema
from ..common.vnode import VNODE_COUNT
from .serde import RowSerde
from .state_table import StateTable
from .store import StateStore


class StorageTable:
    """Read-only batch access to a (materialized) table's committed state."""

    def __init__(self, store: StateStore, table_id: int, schema: Schema,
                 pk_indices: Sequence[int],
                 dist_key_indices: Optional[Sequence[int]] = None,
                 pk_descending: Optional[Sequence[bool]] = None):
        # a private StateTable carries the key layout; its mem-table is
        # never written (reads here are store-only, committed snapshot)
        self._layout = StateTable(
            store, table_id=table_id, schema=schema, pk_indices=pk_indices,
            dist_key_indices=dist_key_indices, pk_descending=pk_descending)
        self.store = store
        self.table_id = table_id
        self.schema = schema
        self.pk_indices = tuple(pk_indices)
        self._serde = RowSerde(schema)

    @classmethod
    def for_state_table(cls, t: StateTable) -> "StorageTable":
        """Batch-read view of an existing StateTable (same key layout)."""
        return cls(t.store, t.table_id, t.schema, t.pk_indices,
                   dist_key_indices=t.dist_key_indices,
                   pk_descending=t.pk_descending)

    # --------------------------------------------------------------- reads
    def get_row(self, pk: tuple) -> Optional[tuple]:
        """Committed point lookup by primary key
        (storage_table.rs point-get path)."""
        pk = tuple(pk)
        key = self._layout.key_of_pk(pk, self._layout.vnode_of_pk(pk))
        for _, row in self._iter_keyrange(key, key + b"\xff"):
            return row
        return None

    def _iter_keyrange(self, start: bytes, end: bytes
                       ) -> Iterator[tuple[bytes, tuple]]:
        for k, v in self.store.iter_range(start, end, committed_only=True):
            yield k, self._serde.decode(v)

    def scan_vnode_after(self, vnode: int, after_pk: Optional[tuple],
                         limit: int, max_epoch: Optional[int] = None
                         ) -> tuple[list[tuple], bool]:
        """Up to `limit` rows of one vnode with pk STRICTLY after
        `after_pk` (None = from the vnode's start), in pk order — the
        backfill snapshot-batch read (no_shuffle_backfill.rs's per-epoch
        snapshot stream). max_epoch bounds staged-epoch visibility so the
        read is consistent with a specific barrier. Returns (rows,
        vnode_exhausted)."""
        start, end = self._layout.vnode_key_range(vnode)
        if after_pk is not None:
            # memcomparable keys order like their pk tuples: the next key
            # strictly after an exact pk is key ++ 0x00
            start = self._layout.key_of_pk(tuple(after_pk), vnode) + b"\x00"
        rows: list[tuple] = []
        for k, v in self.store.iter_range(start, end, committed_only=False,
                                          max_epoch=max_epoch):
            rows.append(self._serde.decode(v))
            if len(rows) > limit:
                break
        if len(rows) > limit:
            return rows[:limit], False
        return rows, True

    def batch_iter_vnode(self, vnode: int) -> Iterator[tuple]:
        """Committed rows of one vnode in pk order
        (storage_table.rs:646 batch_iter_vnode)."""
        start, end = self._layout.vnode_key_range(vnode)
        for _, row in self._iter_keyrange(start, end):
            yield row

    def batch_iter(self, vnode_bitmap: Optional[np.ndarray] = None
                   ) -> Iterator[tuple]:
        """Full committed scan (optionally restricted to a vnode subset —
        the distributed-scan unit the batch scheduler hands each task)."""
        vnodes = (range(VNODE_COUNT) if vnode_bitmap is None
                  else np.flatnonzero(vnode_bitmap))
        for vn in vnodes:
            yield from self.batch_iter_vnode(int(vn))

    def snapshot_with_keys(self, max_epoch: Optional[int] = None,
                           committed_only: bool = False
                           ) -> tuple[list[tuple], list[bytes]]:
        """(rows, store keys) of the whole table in key order, with
        staged (shared-buffer) epochs <= `max_epoch` visible on top of
        the committed base — the serving cache's build scan: at barrier
        collection this sees EXACTLY the epochs the barrier sealed,
        whether or not the background uploader has committed them yet,
        so the cache and the changelog hook agree on where incremental
        maintenance takes over. `committed_only=True` restricts to the
        manifest snapshot — the changelog subscription's backfill read,
        which must align exactly with `store.committed_epoch()` so the
        tail (committed log entries > that epoch) overlaps nothing."""
        rows: list[tuple] = []
        keys: list[bytes] = []
        for vn in range(VNODE_COUNT):
            start, end = self._layout.vnode_key_range(vn)
            for k, v in self.store.iter_range(start, end,
                                              committed_only=committed_only,
                                              max_epoch=max_epoch):
                keys.append(k)
                rows.append(self._serde.decode(v))
        return rows, keys

    def to_numpy(self, vnode_bitmap: Optional[np.ndarray] = None
                 ) -> list[np.ndarray]:
        """Whole committed table as one numpy column set (RowSeqScan's
        chunk form, the input to batch expression evaluation)."""
        return self.to_numpy_with_validity(vnode_bitmap)[0]

    def to_numpy_with_validity(
            self, vnode_bitmap: Optional[np.ndarray] = None
    ) -> tuple[list[np.ndarray], list[np.ndarray]]:
        """(columns, validity masks) — NULL cells decode as None in row
        form; here they become (0, valid=False) so the batch path carries
        real NULL semantics instead of fabricating values (ADVICE r2 #2)."""
        return rows_to_columns(self.schema,
                               list(self.batch_iter(vnode_bitmap)))


def rows_to_columns(schema: Schema, rows: list
                    ) -> tuple[list[np.ndarray], list[np.ndarray]]:
    """Shared rows->(columns, validity) conversion: the ONE place the
    None-cell convention (0 + valid=False) is encoded."""
    cols, valids = [], []
    for j, f in enumerate(schema):
        vals = [r[j] for r in rows]
        valid = np.asarray([v is not None for v in vals], dtype=bool)
        arr = np.asarray([0 if v is None else v for v in vals],
                         dtype=f.data_type.np_dtype)
        cols.append(arr)
        valids.append(valid)
    return cols, valids
