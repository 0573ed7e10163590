"""State store interfaces + in-memory implementation.

Reference: `StateStore`/`LocalStateStore` traits (src/storage/src/store.rs:
172-257) — epoch-versioned KV with table-scoped reads, per-epoch `sync` for
checkpoint durability. Keys follow the reference layout
`table_id ++ vnode ++ memcomparable(pk)` (hummock_sdk/src/key.rs) so range
scans per vnode are contiguous.

`MemoryStateStore` is the reference's `MemoryStateStore`
(src/storage/src/memory.rs): a sorted map, epochs tracked for sync semantics
but everything stays in RAM. The durable LSM variant is state/hummock.py.
"""

from __future__ import annotations

import asyncio
import bisect
import heapq
from dataclasses import dataclass
from typing import Iterator, Optional, Union

import numpy as np


def encode_table_key(table_id: int, vnode: int, pk_bytes: bytes) -> bytes:
    return table_id.to_bytes(4, "big") + vnode.to_bytes(1, "big") + pk_bytes


def lazy_merge_ranges(streams):
    """K-way merge of (key, value|None) iterators, each ascending by key,
    ordered NEWEST FIRST; yields live (key, value) lazily with the newest
    version of each key winning. Lazy matters: backfill snapshot batches
    stop after `limit` rows, and an eager range materialization would make
    every per-barrier batch O(remaining rows) instead of O(limit)."""
    h = []
    for pri, it in enumerate(streams):
        it = iter(it)
        for k, v in it:
            heapq.heappush(h, (k, pri, v, it))
            break
    prev_key = None
    while h:
        k, pri, v, it = heapq.heappop(h)
        for nk, nv in it:
            heapq.heappush(h, (nk, pri, nv, it))
            break
        if k == prev_key:
            continue
        prev_key = k
        if v is not None:
            yield k, v


class ColumnarSegment:
    """One batch of writes to one table as the batch codec made it: a
    `[n, K]` uint8 key matrix, a `[n, V]` uint8 value matrix and a put lane
    (False = tombstone; that row's value is ignored). Rows are in STAGING
    order and keys may repeat: the last row of a key is the write that
    counts. No `bytes` object exists per key; reads build a sorted index on
    first use and materialise only what they return. The arrays are never
    written after construction, so a segment can be read from the upload
    thread while the event loop serves reads from it."""

    __slots__ = ("table_id", "keys", "vals", "put", "_index")

    def __init__(self, table_id: int, keys: np.ndarray, vals: np.ndarray,
                 put: np.ndarray):
        assert keys.ndim == 2 and vals.ndim == 2 \
            and len(keys) == len(vals) == len(put)
        self.table_id = table_id
        self.keys = np.ascontiguousarray(keys, dtype=np.uint8)
        self.vals = np.ascontiguousarray(vals, dtype=np.uint8)
        self.put = np.ascontiguousarray(put, dtype=np.bool_)
        self._index = None

    def __len__(self) -> int:
        return len(self.put)

    @property
    def key_view(self) -> np.ndarray:
        """The key matrix as `[n]` fixed-width byte strings: numpy orders
        and compares them like `bytes` of that one width (memcmp)."""
        return self.keys.view(f"S{self.keys.shape[1]}").ravel()

    def _sorted(self) -> tuple[np.ndarray, np.ndarray]:
        """(keys in order, their rows): built once, on the first read.
        The sort is stable, so among equal keys the newest row is last."""
        if self._index is None:
            view = self.key_view
            order = np.argsort(view, kind="stable")
            self._index = (view[order], order)
        return self._index

    def _value_at(self, row: int) -> Optional[bytes]:
        return self.vals[row].tobytes() if self.put[row] else None

    def get(self, key: bytes) -> tuple[bool, Optional[bytes]]:
        """(found, value) — found with value None means tombstone."""
        if len(key) != self.keys.shape[1]:
            return False, None
        skeys, order = self._sorted()
        needle = np.frombuffer(key, dtype=skeys.dtype)[0]
        i = int(np.searchsorted(skeys, needle, side="right")) - 1
        if i < 0 or skeys[i] != needle:
            return False, None
        return True, self._value_at(order[i])

    def _bounds(self, start: bytes, end: bytes) -> tuple[int, int]:
        """Positions [i, j) of the sorted index that hold the keys in
        [start, end) (`end` empty = unbounded)."""
        skeys, _ = self._sorted()
        width = self.keys.shape[1]

        def first_at_or_after(key: bytes) -> int:
            # pad or cut the bound to the key width; a LONGER bound with an
            # equal head sorts after the stored key
            head = np.frombuffer(key[:width].ljust(width, b"\0"),
                                 dtype=skeys.dtype)[0]
            side = "right" if len(key) > width else "left"
            return int(np.searchsorted(skeys, head, side=side))

        i = first_at_or_after(start)
        return i, max(i, first_at_or_after(end) if end else len(skeys))

    def range_items(self, start: bytes, end: bytes
                    ) -> list[tuple[bytes, Optional[bytes]]]:
        """The newest write of every key in [start, end), in key order."""
        skeys, order = self._sorted()
        i, j = self._bounds(start, end)
        if i == j:
            return []
        newest = np.ones(j - i, dtype=bool)      # last row of each key
        newest[:-1] = skeys[i + 1:j] != skeys[i:j - 1]
        return [(self.keys[r].tobytes(), self._value_at(r))
                for r in order[i:j][newest]]

    def to_puts(self) -> dict[bytes, Optional[bytes]]:
        """The per-key form (a dict in staging order, later rows
        overwriting earlier ones): what a store that keeps dicts, or a
        merge that cannot stay columnar, falls back to."""
        width, vwidth = self.keys.shape[1], self.vals.shape[1]
        kbuf, vbuf = self.keys.tobytes(), self.vals.tobytes()
        return {kbuf[r * width:(r + 1) * width]:
                (vbuf[r * vwidth:(r + 1) * vwidth] if p else None)
                for r, p in enumerate(self.put.tolist())}


def segments_get(segs: list, key: bytes) -> tuple[bool, object]:
    """(found, value) of the newest write of `key` in write segments (dicts
    or ColumnarSegments) given in staging order; found with value None is
    a delete."""
    for seg in reversed(segs):
        if isinstance(seg, dict):
            if key in seg:
                return True, seg[key]
        else:
            found, v = seg.get(key)
            if found:
                return True, v
    return False, None


def segments_range(segs: list, start: bytes, end: bytes) -> dict:
    """The newest write per key in [start, end) (`end` empty = unbounded)
    of write segments given in staging order, as one dict."""
    merged: dict = {}
    for seg in segs:
        merged.update(
            ((k, v) for k, v in seg.items()
             if start <= k and (not end or k < end))
            if isinstance(seg, dict) else seg.range_items(start, end))
    return merged


@dataclass
class WriteBatch:
    table_id: int
    epoch: int
    # key -> value (None = tombstone/delete), or the same writes columnar
    puts: Union[dict[bytes, Optional[bytes]], ColumnarSegment]


class StateStore:
    """Epoch-versioned KV. Writes are staged per epoch and become readable
    immediately to the writer (mem-table semantics handled by StateTable);
    `sync(epoch)` makes everything up to `epoch` durable.

    Deferred-flush protocol (the async-checkpoint hook): a stateful
    executor's barrier-time persist splits into a device-dispatch half
    (the ACTOR runs it at the barrier: the views, the counts it awaits,
    the count-dependent prefix slicing/packing — `utils/d2h.py`
    `defer_prefix_flush`) and ONE host half registered here via
    `defer_flush(epoch, wait, cont)`:

      * `wait()` -> payload: a PURE device wait (`utils/d2h.py
        fetch_flat` of the buffer the actor packed). The background
        uploader runs it on a worker thread. It MUST NOT dispatch jax
        ops — a second thread dispatching concurrently with the event
        loop deadlocks jax.
      * `cont(payload)`: runs on the event loop and is HOST-ONLY: unpack,
        write and commit state tables. A device op dispatched here would
        queue behind the next interval's programs, and the flush would
        wait a whole collect for it.

    With `defer_enabled` False (the default — unit tests driving
    executors directly, inline-sync mode) the pair runs at once, the
    wait on a worker thread and awaited. The barrier coordinator's
    background uploader enables deferral and drains the queue before
    sealing each epoch, so the stream never waits for the d2h + encode +
    ingest cost."""

    def __init__(self):
        # FIFO of (epoch, wait, cont, table_id); epoch = the shared-buffer
        # epoch the flush writes into (must run before that epoch seals);
        # table_id attributes the flush to its owning executor's primary
        # state table so per-fragment recovery can discard exactly the
        # rebuilt fragment's pending flushes (None = untagged, never
        # discarded selectively)
        self._deferred: list[tuple] = []
        self.defer_enabled = False

    async def defer_flush(self, epoch: int, wait, cont,
                          table_id=None) -> None:
        if self.defer_enabled:
            self._deferred.append((epoch, wait, cont, table_id))
        else:
            cont(await asyncio.to_thread(wait))

    def take_deferred(self, epoch: int) -> list:
        """Pop every flush registered for epochs <= epoch, in
        registration order, as (table_id, wait, cont)."""
        taken = [(t, wait, cont)
                 for e, wait, cont, t in self._deferred if e <= epoch]
        self._deferred = [d for d in self._deferred if d[0] > epoch]
        return taken

    def discard_staged_tables(self, table_ids) -> None:
        """Per-fragment recovery: drop the STAGED (uncommitted shared-
        buffer) writes and pending deferred flushes of exactly these
        tables. The rest of the shared buffer — surviving fragments'
        partial-epoch writes — stays put and commits with the next
        checkpoint (`seal` sweeps every staged epoch <= its target), so
        a survivor whose dirty tracking already cleared at the failed
        barrier never loses its flushed rows. The rebuilt fragment
        re-reads its tables at the committed view and re-stages the
        replayed intervals itself."""
        ids = set(table_ids)
        self._deferred = [d for d in self._deferred if d[3] not in ids]
        self._discard_staged(ids)

    def _discard_staged(self, table_ids: set) -> None:
        """Drop the shared buffer's writes to these tables."""
        raise NotImplementedError

    @staticmethod
    def _discard_from_dict(buf: dict, table_ids: set) -> None:
        for k in [k for k in buf
                  if int.from_bytes(k[:4], "big") in table_ids]:
            del buf[k]

    def run_deferred(self, epoch: int) -> None:
        for _, wait, cont in self.take_deferred(epoch):
            cont(wait())

    @staticmethod
    def _count_write_keys(batch: WriteBatch) -> None:
        """`state_write_keys_total{path}`: every `ingest_batch` counts
        the keys it is handed, by the form they came in."""
        from ..utils.metrics import STATE_WRITE_KEYS
        STATE_WRITE_KEYS[isinstance(batch.puts, ColumnarSegment)].inc(
            len(batch.puts))

    def get(self, key: bytes) -> Optional[bytes]:
        raise NotImplementedError

    def get_committed(self, key: bytes) -> Optional[bytes]:
        """Point get against the COMMITTED snapshot only — staged and
        sealed-but-uncommitted epochs are invisible. The log store's
        delivery cursor reads through here: a cursor staged by a
        checkpoint that never committed must not be resumed from
        (logstore/log.py)."""
        raise NotImplementedError

    def get_many(self, keys) -> list:
        """Batch point-get over the same read view as `get` (mem-table
        merging is the StateTable's job): the evicted-range read-through
        path — a reload of spilled state resolves its keys against the
        committed + sealed (staged) view in one call. Backends with a
        cheaper batched lookup override this."""
        return [self.get(k) for k in keys]

    def iter_range(self, start: bytes, end: bytes,
                   committed_only: bool = False,
                   max_epoch: Optional[int] = None
                   ) -> Iterator[tuple[bytes, bytes]]:
        """committed_only=True restricts to the committed (synced)
        snapshot. max_epoch bounds which STAGED (shared-buffer) epochs are
        visible — the backfill snapshot-read isolation: a reader at
        barrier E must not see epochs the upstream ingested past E
        (no_shuffle_backfill.rs reads the upstream table at exactly the
        barrier epoch)."""
        raise NotImplementedError

    def scan_range(self, start: bytes, end: bytes
                   ) -> list[tuple[bytes, bytes]]:
        """Everything `iter_range(start, end)` yields, at once: for a
        reader that takes the whole range anyway (a table's recovery scan),
        so a backend may merge it in bulk rather than entry by entry."""
        return list(self.iter_range(start, end))

    def ingest_batch(self, batch: WriteBatch) -> None:
        raise NotImplementedError

    def sync(self, epoch: int) -> dict:
        """Flush everything sealed up to `epoch` durable; returns sync info
        (sst ids etc.) for the checkpoint manifest."""
        raise NotImplementedError

    def committed_epoch(self) -> int:
        raise NotImplementedError


class MemoryStateStore(StateStore):
    """Sorted base map + per-epoch shared buffers (the same staging shape
    as Hummock-lite, minus durability): `ingest_batch` stages, `sync`
    applies destructively. Keeping staged epochs distinct is what lets
    `iter_range(max_epoch=...)` serve the backfill's epoch-consistent
    snapshot reads on the in-memory store too."""

    def __init__(self):
        super().__init__()
        self._keys: list[bytes] = []       # sorted, synced base
        self._vals: dict[bytes, bytes] = {}
        self._shared: dict[int, dict[bytes, Optional[bytes]]] = {}
        self._committed_epoch = 0

    def get(self, key: bytes) -> Optional[bytes]:
        for epoch in sorted(self._shared, reverse=True):
            buf = self._shared[epoch]
            if key in buf:
                return buf[key]
        return self._vals.get(key)

    def get_committed(self, key: bytes) -> Optional[bytes]:
        # the synced base map IS the committed view (sync() applies
        # destructively — the in-memory analogue of the manifest)
        return self._vals.get(key)

    def iter_range(self, start: bytes, end: bytes,
                   committed_only: bool = False,
                   max_epoch: Optional[int] = None):
        streams = []
        if not committed_only:
            for epoch in sorted(self._shared, reverse=True):  # newest first
                if max_epoch is not None and epoch > max_epoch:
                    continue
                buf = self._shared[epoch]
                streams.append(sorted(
                    (k, v) for k, v in buf.items() if start <= k < end))

        def base():
            i = bisect.bisect_left(self._keys, start)
            while i < len(self._keys) and self._keys[i] < end:
                k = self._keys[i]
                yield k, self._vals[k]
                i += 1
        streams.append(base())
        yield from lazy_merge_ranges(streams)

    def ingest_batch(self, batch: WriteBatch) -> None:
        self._count_write_keys(batch)
        puts = batch.puts
        if isinstance(puts, ColumnarSegment):
            puts = puts.to_puts()      # this store keeps dicts: volatile
        self._shared.setdefault(batch.epoch, {}).update(puts)

    def _discard_staged(self, table_ids: set) -> None:
        for buf in self._shared.values():
            self._discard_from_dict(buf, table_ids)

    def sync(self, epoch: int) -> dict:
        self.run_deferred(epoch)
        for e in sorted(e for e in self._shared if e <= epoch):
            for k, v in self._shared.pop(e).items():
                if v is None:
                    if k in self._vals:
                        del self._vals[k]
                        i = bisect.bisect_left(self._keys, k)
                        if i < len(self._keys) and self._keys[i] == k:
                            self._keys.pop(i)
                else:
                    if k not in self._vals:
                        bisect.insort(self._keys, k)
                    self._vals[k] = v
        self._committed_epoch = max(self._committed_epoch, epoch)
        return {"uncommitted_ssts": []}

    def committed_epoch(self) -> int:
        return self._committed_epoch

    def reset_uncommitted(self) -> None:
        """Recovery entry point (see HummockStateStore.reset_uncommitted)."""
        self._shared.clear()
        self._deferred.clear()
