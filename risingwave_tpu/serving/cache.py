"""SnapshotCache — an epoch-tagged columnar snapshot of one MV.

The Materialize executor publishes its effective changelog (post
conflict-resolution upserts/deletes) through an `MvChangelogHook`; the
ServingManager drains the hook at every collected barrier and calls
`advance`, so the cache tracks the MV exactly one barrier behind the
stream — at the epoch the barrier just sealed — without ever re-scanning
the LSM. A full scan happens only on first touch and after recovery.

Concurrency model (the epoch pin): queries never read the cache's
mutable state directly. `snapshot` is an immutable published view; a
query PINS it on the event loop before moving to a worker thread and
unpins after. `advance` runs on the event loop between epochs:

  * pins == 0  -> nobody can observe the current snapshot, so the live
    mask / pk index mutate in place (zero-copy steady state);
  * pins  > 0  -> the mutable state is first detached (live mask + pk
    index copied), so the pinned snapshot's arrays are frozen forever
    and worker threads race nothing.

Row storage is append-only: updates tombstone the old position and
append the new version, so data columns at positions a pinned snapshot
can see are immutable by construction. Scans compact live rows in
STORE-KEY ORDER (vnode ++ memcomparable(pk)), which makes cached results
bit-identical — including row order — to the StorageTable full-scan
path.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

from ..common.chunk import OP_INSERT, OP_UPDATE_INSERT, HostChunk
from ..common.types import Schema

# effective changelog ops (post conflict-resolution): PUT upserts by pk
# (matching the state table's last-write-wins mem-table), DEL removes
OP_PUT = 1
OP_DEL = -1


class EffectiveChunk:
    """The effective changelog of one chunk a NO_CHECK materialize wrote
    in columns: its inserts land last-write-wins in the mem-table, upserts
    at the storage level, so every visible row is a PUT or a DEL. It stays
    the chunk's host lanes until a changelog tap KEEPS it (`rows`, once for
    all taps): a tap nobody reads drops it at the barrier unread."""

    __slots__ = ("host", "_rows")

    def __init__(self, host: HostChunk):
        self.host = host
        self._rows = None

    def rows(self) -> list:
        if self._rows is None:
            self._rows = [
                (OP_PUT if op in (OP_INSERT, OP_UPDATE_INSERT) else OP_DEL,
                 row) for op, row in self.host.rows()]
        return self._rows


def pending_rows(batches: list) -> list:
    """A tap's pending interval as `[(OP_PUT | OP_DEL, row)]`: each batch
    is such a list (`on_rows`) or an `EffectiveChunk`, in arrival order."""
    out: list = []
    for b in batches:
        out.extend(b.rows() if isinstance(b, EffectiveChunk) else b)
    return out

_MIN_CAPACITY = 64


class Snapshot:
    """Immutable published view of one MV at one epoch. All fields are
    frozen once the snapshot is observable by a worker thread (see the
    module docstring's pin protocol)."""

    __slots__ = ("schema", "pk_indices", "cols", "valids", "live",
                 "rowkeys", "n", "pk_index", "epoch", "pins",
                 "_compact", "_lock")

    def __init__(self, schema: Schema, pk_indices: tuple, cols, valids,
                 live, rowkeys, n: int, pk_index: dict, epoch: int):
        self.schema = schema
        self.pk_indices = pk_indices
        self.cols = cols
        self.valids = valids
        self.live = live
        self.rowkeys = rowkeys
        self.n = n
        self.pk_index = pk_index
        self.epoch = epoch
        self.pins = 0
        self._compact = None
        self._lock = threading.Lock()

    @property
    def row_count(self) -> int:
        return len(self.pk_index)

    def lookup(self, pk: tuple) -> Optional[int]:
        """pk -> row position (the point-lookup index probe)."""
        return self.pk_index.get(pk)

    def point_rel(self, pos: Optional[int]):
        """(cols, valids) for zero or one row — the O(1) read."""
        if pos is None:
            return ([c[:0].copy() for c in self.cols],
                    [v[:0].copy() for v in self.valids])
        return ([c[pos:pos + 1].copy() for c in self.cols],
                [v[pos:pos + 1].copy() for v in self.valids])

    def compact(self):
        """(cols, valids) of the live rows in store-key order — the scan
        form. Memoized per snapshot; safe to call from worker threads."""
        with self._lock:
            if self._compact is None:
                idx = np.flatnonzero(self.live[:self.n])
                order = sorted(idx.tolist(), key=self.rowkeys.__getitem__)
                o = np.asarray(order, dtype=np.int64)
                self._compact = ([c[o] for c in self.cols],
                                 [v[o] for v in self.valids])
            return self._compact


class SnapshotCache:
    """Mutable per-MV cache state; publishes immutable Snapshots."""

    def __init__(self, name: str, schema: Schema,
                 pk_indices: Sequence[int], layout):
        self.name = name
        self.schema = schema
        self.pk_indices = tuple(pk_indices)
        # a StateTable carrying the MV's key layout: delta rows get the
        # same `vnode ++ memcomparable(pk)` ordering key the store scan
        # yields, so cached and scanned row order agree exactly
        self._layout = layout
        self._np_dtypes = [np.dtype(f.data_type.np_dtype) for f in schema]
        self._cap = 0
        self._n = 0
        self._cols: list[np.ndarray] = []
        self._valids: list[np.ndarray] = []
        self._live: Optional[np.ndarray] = None
        self._rowkeys: list[bytes] = []
        self._pk_index: dict = {}
        self.snapshot: Optional[Snapshot] = None
        self.applied_rows = 0     # changelog rows applied incrementally
        self.rebuilds = 0         # full rescans (first touch / recovery)

    # ------------------------------------------------------------- keys
    def _canon(self, v, j: int):
        if v is None:
            return None
        return np.asarray(v, dtype=self._np_dtypes[j]).item()

    def canon_pk_of_row(self, row: tuple) -> tuple:
        return tuple(self._canon(row[i], i) for i in self.pk_indices)

    def _key_of_pk(self, pk: tuple) -> bytes:
        return self._layout.key_of_pk(pk, self._layout.vnode_of_pk(pk))

    # ------------------------------------------------------------ build
    def build(self, rows: list, keys: list, epoch: int) -> None:
        """Full (re)build from a consistent store scan at `epoch` —
        `rows`/`keys` in store-key order (StorageTable.snapshot_with_keys)."""
        n = len(rows)
        self._cap = max(_MIN_CAPACITY, 1 << max(0, (n - 1).bit_length()))
        self._cols = []
        self._valids = []
        for j, f in enumerate(self.schema):
            arr = np.zeros(self._cap, dtype=self._np_dtypes[j])
            val = np.zeros(self._cap, dtype=bool)
            for i, r in enumerate(rows):
                v = r[j]
                if v is not None:
                    arr[i] = v
                    val[i] = True
            self._cols.append(arr)
            self._valids.append(val)
        self._live = np.zeros(self._cap, dtype=bool)
        self._live[:n] = True
        self._rowkeys = list(keys)
        self._n = n
        self._pk_index = {self.canon_pk_of_row(r): i
                          for i, r in enumerate(rows)}
        self.rebuilds += 1
        self._publish(epoch)

    # ---------------------------------------------------------- advance
    def advance(self, batches: list, epoch: int) -> None:
        """Apply drained changelog batches `[(epoch, [(op, row), ...])]`
        (ascending epochs <= `epoch`) and publish the new snapshot."""
        snap = self.snapshot
        if snap is not None and snap.pins > 0:
            # detach: the pinned snapshot keeps the current mask/index
            # untouched forever; mutation continues on private copies
            self._live = self._live.copy()
            self._pk_index = dict(self._pk_index)
        for _e, rows in batches:
            for op, row in rows:
                pk = self.canon_pk_of_row(row)
                if op == OP_DEL:
                    pos = self._pk_index.pop(pk, None)
                    if pos is not None:
                        self._live[pos] = False
                else:
                    old = self._pk_index.get(pk)
                    if old is not None:
                        self._live[old] = False
                        key = self._rowkeys[old]
                    else:
                        key = self._key_of_pk(pk)
                    self._append(row, key)
                    self._pk_index[pk] = self._n - 1
                self.applied_rows += 1
        self._publish(epoch)

    def _append(self, row: tuple, key: bytes) -> None:
        pos = self._n
        if pos >= self._cap:
            new_cap = max(_MIN_CAPACITY, self._cap * 2)
            self._cols = [self._grow(c, new_cap) for c in self._cols]
            self._valids = [self._grow(v, new_cap) for v in self._valids]
            self._live = self._grow(self._live, new_cap)
            self._cap = new_cap
        for j, v in enumerate(row):
            if v is None:
                self._cols[j][pos] = 0
                self._valids[j][pos] = False
            else:
                self._cols[j][pos] = v
                self._valids[j][pos] = True
        self._live[pos] = True
        self._rowkeys.append(key)
        self._n = pos + 1

    @staticmethod
    def _grow(arr: np.ndarray, cap: int) -> np.ndarray:
        out = np.zeros(cap, dtype=arr.dtype)
        out[:len(arr)] = arr
        return out

    def _publish(self, epoch: int) -> None:
        self.snapshot = Snapshot(
            self.schema, self.pk_indices, list(self._cols),
            list(self._valids), self._live, self._rowkeys, self._n,
            self._pk_index, epoch)


class MvChangelogHook:
    """Attached to a MaterializeExecutor as `serving_hook`: collects the
    epoch's effective changelog rows and stamps them with the sealed
    epoch at each barrier. The buffer holds AT MOST one barrier interval
    while the MV has no cache (stamped batches are dropped at the
    barrier), so never-queried MVs cost nothing."""

    __slots__ = ("name", "active", "_pending", "_by_epoch")

    def __init__(self, name: str):
        self.name = name
        self.active = False
        self._pending: list = []
        self._by_epoch: list = []   # [(sealed_epoch, rows)]

    def on_rows(self, rows) -> None:
        """`[(OP_PUT | OP_DEL, row)]` or an `EffectiveChunk` (never an
        empty one): held as handed over, rows made only where kept."""
        if rows:
            self._pending.append(rows)

    def on_barrier(self, sealed_epoch: int) -> None:
        batches = self._pending
        self._pending = []
        if self.active and batches:
            self._by_epoch.append((sealed_epoch, pending_rows(batches)))

    def drain(self, upto_epoch: int) -> list:
        """Stamped batches with epoch <= upto_epoch, ascending."""
        out = [b for b in self._by_epoch if b[0] <= upto_epoch]
        self._by_epoch = [b for b in self._by_epoch if b[0] > upto_epoch]
        return out

    def activate(self) -> None:
        """Start buffering stamped batches. `_pending` is PRESERVED: the
        actor runs ahead of barrier collection, so by the time the
        manager builds the cache (at collection) the hook may already
        hold the next open interval's rows — dropping them would lose
        that interval forever. Everything <= the build epoch was
        dropped at its own barrier (inactive stamps discard) and is in
        the build scan; `_by_epoch` is necessarily empty here."""
        self.active = True
