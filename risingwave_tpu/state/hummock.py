"""Hummock-lite — the durable LSM state store behind checkpoints.

Reference: src/storage/src/hummock/ (shared buffer -> L0 SST upload on
`sync`, version manifest via meta, compaction; store.rs:172-257 and
docs/checkpoint.md:38-44). The shape kept here:

- `ingest_batch` stages writes in a per-epoch shared buffer (immediately
  readable — mem-table read-through semantics match MemoryStateStore). A
  per-epoch buffer is a list of write SEGMENTS in staging order: a
  `ColumnarSegment` (state/store.py: the key matrix, value matrix and put
  lane of one `StateTable.write_chunk_columns` call, as the batch codec
  made them) or a dict (row-form writes, the log store, source offsets).
  Later segments overlay earlier ones; within a columnar segment the last
  row of a key counts.
- The checkpoint pipeline is split into three phases (reference: the
  event-handler uploader, src/storage/src/hummock/event_handler/uploader/ —
  epochs seal at the barrier, SSTs build/upload in background tasks, and
  the version commit applies them strictly in epoch order):
    * `seal(epoch)`   — cheap: move every buffered epoch <= `epoch` into an
      immutable SealedBatch on the sealed queue (no merging, no encoding).
    * `upload_sealed(batch)` — slow, thread-safe: merge the batch into ONE
      sorted run (`sstable.merge_runs`: a table written only in fixed-width
      columnar segments is sorted and packed as arrays, with no Python
      object per key), build the SST, PUT it to the object store. Touches
      only the immutable batch and the object store, so a background thread
      can run it while the stream keeps computing.
    * `commit_sealed(batch)` — the commit point: insert the run the upload
      phase sorted into L0 (array-backed; the bytes just built are not
      parsed back — `SsTable.parse` and its crc check are for what is READ
      from the object store), maybe compact, atomically swap the
      manifest. Refuses out-of-order
      commits (`batch` must be the oldest sealed batch). Only after the
      manifest lands is the epoch committed — a crash at any point recovers
      to the last manifest, never a torn state.
  `sync(epoch)` remains the inline composition of the three (seal + drain
  the sealed queue in order) for tests and non-pipelined callers.
- Reads merge: shared buffer (newest epoch wins) > sealed-but-uncommitted
  batches (newest first) > L0 (newest SST wins) > L1. committed_only reads
  see neither staged nor sealed data.
- When L0 grows past a threshold, a full compaction merges L0+L1 into one
  bottom-level SST and drops tombstones (the reference's compactor collapsed
  to its essential effect). Inline and background compaction run the same
  `merge_runs` over the runs' parts and install its output without a parse.

The object format (`RWS1`, state/sstable.py) and the manifest did not change
when runs became array-backed: an upload PUTs the bytes
`build_sstable(epoch, sorted(<dict overlay of the same writes>.items()))`
gives.

Recovery: `HummockStateStore.open(object_store)` reads the manifest and
serves `get`/`iter_range` at the committed version; `committed_epoch()`
seeds the barrier coordinator's epoch floor.
"""

from __future__ import annotations

import json
from typing import Iterator, Optional, Union

from .object_store import ObjectStore, ResilientObjectStore
from .sstable import (Part, SsTable, SsTableCorruption, build_sstable_parts,
                      frame_meta, merge_runs, unframe_meta)
from .store import (ColumnarSegment, StateStore, WriteBatch,
                    lazy_merge_ranges, segments_get, segments_range)

MANIFEST_PATH = "MANIFEST"
QUARANTINE_PREFIX = "quarantine/"


def _sst_path(sst_id: int) -> str:
    return f"ssts/{sst_id:010d}.sst"


# one write segment of a per-epoch buffer: row form or columnar
Segment = Union[dict, ColumnarSegment]


class SealedBatch:
    """Immutable snapshot of shared-buffer epochs <= seal_epoch, queued for
    background upload. The per-epoch segment lists are kept distinct (not
    merged) so reads and `max_epoch` filtering keep exact shared-buffer
    semantics until the commit lands; the merge happens in `upload_sealed`,
    off the barrier path. `sst_id` is allocated at seal time (on the event
    loop, so ids stay ordered even with uploads in flight); the upload
    phase sets `parts`: the merged run whose SST it PUT, and what
    `commit_sealed` installs into L0."""

    __slots__ = ("seal_epoch", "epochs", "sst_id", "parts")

    def __init__(self, seal_epoch: int, epochs: dict[int, list[Segment]]):
        self.seal_epoch = seal_epoch
        self.epochs = epochs
        self.sst_id: Optional[int] = None
        self.parts: Optional[list[Part]] = None

    @property
    def is_empty(self) -> bool:
        return not any(len(seg) for segs in self.epochs.values()
                       for seg in segs)


class CompactionTask:
    """One planned background merge: a contiguous OLDEST tail of L0
    (optionally plus L1). Planned on the event loop (`plan_compaction`
    allocates the output id and snapshots the immutable input SsTables),
    merged + uploaded on a worker thread (`merge_compaction` — touches
    only the snapshot and the object store), installed back on the loop
    at a commit point (`install_compaction` — one manifest swap). A crash
    between merge and install leaves at worst an orphan output object
    that the scrubber sweeps."""

    __slots__ = ("run_ids", "ssts", "l1_id", "l1_sst", "into_l1",
                 "out_sst_id", "out_epoch", "input_bytes", "parts",
                 "keys_in", "keys_out")

    def __init__(self, runs: list["SsTable"], l1: Optional["SsTable"],
                 into_l1: bool, out_sst_id: int):
        self.run_ids = [t.sst_id for t in runs]   # newest-first, as in _l0
        self.ssts = runs
        self.l1_sst = l1
        self.l1_id = l1.sst_id if l1 is not None else None
        self.into_l1 = into_l1                    # output becomes the bottom
        self.out_sst_id = out_sst_id
        self.out_epoch = max([t.epoch for t in runs]
                             + ([l1.epoch] if l1 is not None else []))
        self.input_bytes = sum(t.payload_bytes for t in runs) \
            + (l1.payload_bytes if l1 is not None else 0)
        self.parts: Optional[list[Part]] = None   # the merged run, once PUT
        self.keys_in = sum(len(t) for t in runs) \
            + (len(l1) if l1 is not None else 0)
        self.keys_out = 0

    @property
    def input_ids(self) -> list[int]:
        return self.run_ids + ([self.l1_id] if self.l1_id is not None
                               else [])


def _merge_ssts(l1: Optional[SsTable], l0: list[SsTable],
                drop_tombstones: bool) -> list[Part]:
    """The one run that L1 (if any) below `l0` (newest first) reads as."""
    oldest_first = ([l1] if l1 is not None else []) + l0[::-1]
    return merge_runs([p for t in oldest_first for p in t.parts],
                      drop_tombstones)


class HummockStateStore(StateStore):
    L0_COMPACT_THRESHOLD = 8

    def __init__(self, object_store: ObjectStore,
                 backup_store: Optional[ObjectStore] = None):
        super().__init__()
        # every backend rides the retry layer: transient PUT/GET faults
        # absorb below the recovery machinery (bounded backoff, per-op
        # deadline); persistent faults keep the fail-stop path
        self.objects = ResilientObjectStore.wrap(object_store)
        # read-path integrity (see _read_sst): durably-corrupt objects
        # are quarantined here (paths) and — when a backup store is
        # attached — restored from their verified backup copy instead of
        # crash-looping; /healthz reports `degraded` while non-empty.
        # Attaching the backup AT OPEN (ctor arg; SET backup_path covers
        # the running session) matters for the reopen-after-corruption
        # path: the manifest load below already reads every referenced
        # SST, so a bit-rotted object heals during open instead of
        # crash-looping the restart
        self.quarantined: list[str] = []
        self.restored_objects: list[str] = []
        self.backup_store: Optional[ObjectStore] = backup_store
        # epoch -> its write segments in staging order (see module doc)
        self._shared: dict[int, list[Segment]] = {}
        # sealed-but-uncommitted batches, oldest first (the uploader queue)
        self._sealed: list[SealedBatch] = []
        self._l0: list[SsTable] = []   # newest first
        self._l1: Optional[SsTable] = None
        self._next_sst_id = 1
        self._committed_epoch = 0
        # Cluster mode (cluster/): compute-node handles share this object
        # store but NEVER own the manifest — the meta handle is the single
        # writer (reference: only meta commits Hummock versions). A
        # non-owner installs its own SSTs into its local L0 for
        # read-through, skips the manifest swap, and never compacts
        # (compaction rewrites + deletes objects the manifest references).
        self.manifest_owner = True
        # Non-owner handles retain every batch they sealed + uploaded
        # until META confirms the cluster commit (the `committed` push):
        # an epoch the dead worker never sealed can NEVER commit, and
        # without retention the survivors' share of that epoch would
        # have left the staged model (sealed, locally installed) while
        # the manifest never learns of it — silent durable loss on the
        # next crash. Per-worker partial recovery RESTAGES these into
        # the shared buffer so the next checkpoint re-seals them.
        self._unconfirmed: list[SealedBatch] = []
        # Inline compaction is the STANDALONE fallback (stores driven by
        # sync() with no coordinator). When a BackgroundCompactor attaches
        # it flips this off: the commit path then does O(1) work and the
        # compactor owns every merge (state/compactor.py).
        self.inline_compaction = True
        # Output sst ids of in-flight background merges: the scrubber's
        # orphan keep-set must cover them (the object exists before any
        # manifest references it).
        self.compaction_inflight: set[int] = set()
        if self.objects.exists(MANIFEST_PATH):
            self._load_manifest()

    def set_sst_id_block(self, base: int) -> None:
        """Give this handle a disjoint SST-id namespace (cluster compute
        nodes): ids allocated by concurrent worker handles over one shared
        object store must never collide, so meta hands each worker a
        high block per deployment generation."""
        self._next_sst_id = max(self._next_sst_id, base)

    # ------------------------------------------------------------ manifest
    def _load_manifest(self) -> None:
        m = json.loads(unframe_meta(self.objects.read(MANIFEST_PATH),
                                    MANIFEST_PATH))
        assert m.get("format") == 1, f"unknown manifest format {m}"
        self._committed_epoch = m["committed_epoch"]
        self._next_sst_id = m["next_sst_id"]
        self._l0 = [self._read_sst(i) for i in m["l0"]]
        self._l1 = (self._read_sst(m["l1"])
                    if m["l1"] is not None else None)

    # --------------------------------------------------- read-path integrity
    def _read_sst(self, sst_id: int) -> SsTable:
        """Checksum-verified SST read with the transient/durable split:
        a crc mismatch retries ONCE (torn page cache / transient media —
        the re-read observes the real bytes); a second mismatch is
        DURABLE corruption — the object is quarantined and restored from
        its verified backup copy when one is attached, instead of
        crash-looping the recovery engine against the same bad bytes."""
        path = _sst_path(sst_id)
        try:
            return SsTable.parse(sst_id, self.objects.read(path))
        except SsTableCorruption:
            from ..utils.metrics import STORAGE_CRC_RETRIES
            STORAGE_CRC_RETRIES.inc()
            try:
                return SsTable.parse(sst_id, self.objects.read(path))
            except SsTableCorruption:
                return SsTable.parse(
                    sst_id, self._quarantine_and_restore(path))

    def _quarantine_and_restore(self, path: str) -> bytes:
        """Durable corruption: park the bad bytes under quarantine/ (the
        post-mortem evidence — never served again), then restore the
        object from the attached backup's checksum-verified copy. No
        backup (or the backup lacks it): raise — named, loud, and
        exactly-once-preserving (fail-stop, never silent serving)."""
        from ..utils.metrics import STORAGE_QUARANTINED, STORAGE_RESTORED
        try:
            bad = self.objects.read(path)
            self.objects.upload(
                QUARANTINE_PREFIX + path.replace("/", "_"), bad)
        except Exception:  # noqa: BLE001 — quarantine is best-effort
            pass
        if path not in self.quarantined:
            self.quarantined.append(path)
        STORAGE_QUARANTINED.set(float(len(self.quarantined)))
        if self.backup_store is not None:
            from .backup import read_backup_object
            data = read_backup_object(self.backup_store, path)
            if data is not None:
                self.objects.upload(path, data)
                self.restored_objects.append(path)
                STORAGE_RESTORED.inc()
                return data
        raise SsTableCorruption(
            f"{path}: durable corruption (quarantined) and no verified "
            f"backup copy to restore from")

    def scrub_verify(self, path: str) -> bool:
        """One scrubber probe: read + integrity-check `path` without
        mutating any in-memory state. Returns True when the object
        verifies (possibly after the one transient re-read), False when
        it is durably corrupt — quarantined, and restored when a backup
        is attached (the False return still marks the pass degraded so
        the operator sees the incident)."""

        def _check() -> None:
            data = self.objects.read(path)
            if path.startswith("ssts/"):
                SsTable.parse(0, data)
            else:
                json.loads(unframe_meta(data, path))

        try:
            _check()
            return True
        except SsTableCorruption:
            from ..utils.metrics import STORAGE_CRC_RETRIES
            STORAGE_CRC_RETRIES.inc()
            try:
                _check()
                return True
            except SsTableCorruption:
                try:
                    self._quarantine_and_restore(path)
                except SsTableCorruption:
                    pass      # quarantined without a backup: stay degraded
                return False
        except Exception:  # noqa: BLE001 — read errors own the fail-stop
            return False

    def refresh_manifest(self) -> None:
        """Re-point this handle at the CURRENT committed manifest
        without reopening (per-worker partial recovery: a surviving
        compute node's manifest snapshot is from deploy time, so reads
        of the DEAD worker's committed rows — re-placed actors
        recovering their vnode ranges, source offsets — would otherwise
        see a stale, possibly empty view). Staged buffers, retained
        batches and the worker's disjoint SST-id block are untouched;
        the local L0/L1 are replaced by the manifest's (which includes
        every worker's committed SSTs — this worker's own confirmed
        installs are manifest-covered by definition)."""
        keep_next = self._next_sst_id
        if self.objects.exists(MANIFEST_PATH):
            self._load_manifest()
        self._next_sst_id = max(self._next_sst_id, keep_next)

    def _write_manifest(self) -> None:
        m = {
            "format": 1,
            "committed_epoch": self._committed_epoch,
            "next_sst_id": self._next_sst_id,
            "l0": [t.sst_id for t in self._l0],
            "l1": self._l1.sst_id if self._l1 is not None else None,
        }
        self.objects.upload(MANIFEST_PATH,
                            frame_meta(json.dumps(m).encode()))

    # --------------------------------------------------------------- reads
    def _staged(self) -> Iterator[tuple[int, list[Segment]]]:
        """(epoch, segments) of every uncommitted buffer, newest first:
        the shared buffer, then the sealed queue (sealed = still staged)."""
        for epoch in sorted(self._shared, reverse=True):
            yield epoch, self._shared[epoch]
        for batch in reversed(self._sealed):          # newest batch first
            for epoch in sorted(batch.epochs, reverse=True):
                yield epoch, batch.epochs[epoch]

    def get(self, key: bytes) -> Optional[bytes]:
        for _epoch, segs in self._staged():
            found, v = segments_get(segs, key)
            if found:
                return v
        return self.get_committed(key)

    def get_committed(self, key: bytes) -> Optional[bytes]:
        """Point get at the COMMITTED snapshot (SSTs under the manifest
        only): the shared buffer and the sealed-but-uncommitted queue
        are invisible, exactly like `iter_range(committed_only=True)`.
        The log store reads its delivery cursor here — a cursor staged
        by an epoch whose commit never landed dies with the crash, and
        resuming from it would skip the epochs it covered."""
        for sst in self._l0:
            found, v = sst.get(key)
            if found:
                return v
        if self._l1 is not None:
            found, v = self._l1.get(key)
            if found:
                return v
        return None

    def iter_range(self, start: bytes, end: bytes,
                   committed_only: bool = False,
                   max_epoch: Optional[int] = None
                   ) -> Iterator[tuple[bytes, bytes]]:
        """committed_only=True reads the COMMITTED snapshot (SSTs under the
        manifest), excluding the uncommitted shared buffer — the batch/
        serving read isolation (reference: StorageTable::batch_iter at a
        pinned snapshot epoch, batch_table/storage_table.rs:646).
        max_epoch additionally bounds which shared-buffer epochs are
        visible (SSTs are always <= the last sync, which is <= any
        in-flight barrier epoch, so only staged epochs need filtering)."""
        streams = []
        if not committed_only:
            for epoch, segs in self._staged():    # newest first
                if max_epoch is not None and epoch > max_epoch:
                    continue
                streams.append(
                    sorted(segments_range(segs, start, end).items()))
        for sst in self._l0:                      # newest first
            streams.append(sst.iter_range(start, end))
        if self._l1 is not None:
            streams.append(self._l1.iter_range(start, end))
        yield from lazy_merge_ranges(streams)

    def scan_range(self, start: bytes, end: bytes
                   ) -> list[tuple[bytes, bytes]]:
        """`iter_range(start, end)` in one piece, through the merge the
        uploads and the compactor use: every run's share of the range
        (array slices where the run is array-backed) and the staged writes
        above them, newest version per key, tombstones dropped. Recovery's
        scans read a table this way; the lazy k-way merge stays for
        readers that stop early (backfill snapshot batches)."""
        runs = ([self._l1] if self._l1 is not None else []) + self._l0[::-1]
        pieces: list = [part.range_part(start, end) for sst in runs
                        for part in sst.parts_in(start, end)]
        for _epoch, segs in reversed(list(self._staged())):
            pieces.append(segments_range(segs, start, end))
        return [kv for part in merge_runs(pieces, drop_tombstones=True)
                for kv in part.iter_range(b"", b"")]

    def committed_epoch(self) -> int:
        return self._committed_epoch

    def reset_uncommitted(self) -> None:
        """Drop the shared buffer AND the sealed-but-uncommitted queue —
        the recovery entry point (reference: recovery resumes at the last
        committed Hummock version; anything newer was never externally
        visible). A process restart gets this for free; an in-process
        restart (rescale, failover tests) must call it or stale
        uncommitted epochs would leak into new ones. The caller must have
        stopped the background uploader first (BarrierCoordinator.
        abort_uploads) — an in-flight upload can at worst leave an orphan
        SST, which no manifest references."""
        self._shared.clear()
        self._sealed.clear()
        self._deferred.clear()
        self._unconfirmed.clear()

    # ------------------------------------------- worker commit confirmation
    def confirm_committed(self, epoch: int) -> None:
        """Meta's `committed` notification reached this worker handle:
        every retained batch the cluster commit covered is durable in
        the shared manifest — drop it from the retention list."""
        self._unconfirmed = [b for b in self._unconfirmed
                             if b.seal_epoch > epoch]

    def restage_unconfirmed(self) -> None:
        """Per-worker partial recovery: move every sealed-but-never-
        confirmed batch BACK into the shared buffer under its original
        epochs, so the next checkpoint re-seals (and meta re-commits)
        the survivors' share of the aborted epochs. Their local-L0
        installs are REMOVED: a rebuilt actor recovers its state by
        reading this handle, and the uncommitted suffix must be visible
        through the staged buffer ONLY — where the recovery's
        discard_staged_tables can drop the rebuilt fragments' share
        before the exchange replay re-derives it (left in L0 it would
        double-apply). Restaged epochs are older keys, so the next
        `seal` sweeps them in exact overlay order."""
        drop_ids = {b.sst_id for b in self._unconfirmed
                    if b.sst_id is not None}
        if drop_ids:
            self._l0 = [t for t in self._l0 if t.sst_id not in drop_ids]
        for b in self._unconfirmed:
            for e in sorted(b.epochs):
                # original staging order preserved; existing (newer)
                # staged writes for the same epoch overlay the restage
                self._shared[e] = b.epochs[e] + self._shared.get(e, [])
        self._unconfirmed = []

    # -------------------------------------------------------------- writes
    def ingest_batch(self, batch: WriteBatch) -> None:
        self._count_write_keys(batch)
        segs = self._shared.setdefault(batch.epoch, [])
        if isinstance(batch.puts, ColumnarSegment):
            segs.append(batch.puts)
        elif segs and isinstance(segs[-1], dict):
            segs[-1].update(batch.puts)
        else:
            segs.append(dict(batch.puts))

    def _discard_staged(self, table_ids: set) -> None:
        for epoch, segs in self._shared.items():
            for seg in segs:
                if isinstance(seg, dict):
                    self._discard_from_dict(seg, table_ids)
            self._shared[epoch] = [
                seg for seg in segs if isinstance(seg, dict)
                or seg.table_id not in table_ids]

    # ------------------------------------------------- seal/upload/commit
    def seal(self, epoch: int) -> SealedBatch:
        """Phase 1, cheap (at the barrier / on the event loop): move every
        shared-buffer epoch <= `epoch` into an immutable SealedBatch on the
        sealed queue. The batch stays readable (and retryable: the staged
        writes are not dropped until `commit_sealed`) — the generalization
        of the old upload-before-drop invariant to a queue of batches."""
        assert not self._sealed or epoch >= self._sealed[-1].seal_epoch, \
            f"seal epochs must be monotone ({epoch} after " \
            f"{self._sealed[-1].seal_epoch})"
        eps = sorted(e for e in self._shared if e <= epoch)
        batch = SealedBatch(epoch, {e: self._shared.pop(e) for e in eps})
        if not batch.is_empty:
            batch.sst_id = self._next_sst_id
            self._next_sst_id += 1
        self._sealed.append(batch)
        return batch

    def upload_sealed(self, batch: SealedBatch) -> None:
        """Phase 2, slow: merge + build + PUT the batch's SST. Thread-safe
        (touches only the immutable batch and the object store), so the
        background uploader runs it via asyncio.to_thread while the stream
        keeps computing. No store state mutates here; a failure or a crash
        mid-upload leaves at worst an orphan object no manifest references."""
        if batch.sst_id is None or batch.parts is not None:
            return
        parts = merge_runs([seg for e in sorted(batch.epochs)
                            for seg in batch.epochs[e]])   # oldest first
        self.objects.upload(_sst_path(batch.sst_id),
                            build_sstable_parts(batch.seal_epoch, parts))
        batch.parts = parts

    def commit_sealed(self, batch: SealedBatch) -> dict:
        """Phase 3, the commit point (event loop only): install the SST
        into L0, advance the committed epoch, maybe compact, atomically
        swap the manifest. STRICTLY in seal order — `batch` must be the
        oldest sealed batch, so a fast epoch N+1 upload can never publish
        a manifest missing epoch N."""
        assert self._sealed and self._sealed[0] is batch, (
            "manifest swaps must land in seal order (epoch "
            f"{batch.seal_epoch} is not the oldest sealed batch)")
        new_ids: list[int] = []
        if batch.sst_id is not None:
            assert batch.parts is not None, \
                "commit_sealed before upload_sealed"
            self._l0.insert(
                0, SsTable(batch.sst_id, batch.seal_epoch, batch.parts))
            new_ids.append(batch.sst_id)
        self._sealed.pop(0)
        self._committed_epoch = max(self._committed_epoch, batch.seal_epoch)
        if not self.manifest_owner:
            # compute-node handle: the local L0 install above gives this
            # worker read-through to its own flushed state; the COMMIT
            # POINT (manifest swap) belongs to meta, which installs these
            # SSTs via commit_remote only after every worker reported
            # sealed. No compaction either — meta owns object lifetime.
            # Retain the batch until meta's `committed` notification:
            # see _unconfirmed in __init__ (worker partial recovery).
            self._unconfirmed.append(batch)
            return {"uncommitted_ssts": new_ids}
        obsolete: list[int] = []
        if self.inline_compaction \
                and len(self._l0) > self.L0_COMPACT_THRESHOLD:
            obsolete = self._compact()
        # manifest swap = the commit point; object deletes strictly after
        self._write_manifest()
        for sst_id in obsolete:
            self.objects.delete(_sst_path(sst_id))
        return {"uncommitted_ssts": new_ids}

    def commit_remote(self, epoch: int, sst_ids: list[int]) -> None:
        """Meta-side commit of a cluster checkpoint: install the SSTs
        every compute node uploaded for `epoch` (disjoint key ranges —
        the state is vnode-partitioned) into L0 and swap the manifest.
        Called strictly in epoch order by the coordinator's background
        committer, and ONLY after all workers reported sealed — the
        cluster generalization of `commit_sealed`'s commit point."""
        assert self.manifest_owner, "only the meta handle commits"
        assert epoch > self._committed_epoch, \
            f"cluster commit out of order ({epoch} <= {self._committed_epoch})"
        for sst_id in sst_ids:
            self._l0.insert(0, self._read_sst(sst_id))
        self._committed_epoch = epoch
        obsolete: list[int] = []
        if self.inline_compaction \
                and len(self._l0) > self.L0_COMPACT_THRESHOLD:
            obsolete = self._compact()
        self._write_manifest()
        for sst_id in obsolete:
            self.objects.delete(_sst_path(sst_id))

    def sync(self, epoch: int) -> dict:
        """Inline composition of the pipeline: run any deferred executor
        flushes, seal, then drain the sealed queue in order (uploading
        batches the background path has not gotten to). Tests and the
        non-pipelined coordinator mode call this; the pipelined path calls
        the phases directly."""
        self.run_deferred(epoch)
        self.seal(epoch)
        new_ids: list[int] = []
        while self._sealed and self._sealed[0].seal_epoch <= epoch:
            b = self._sealed[0]
            self.upload_sealed(b)
            new_ids.extend(self.commit_sealed(b)["uncommitted_ssts"])
        return {"uncommitted_ssts": new_ids}

    # ---------------------------------------------------------- compaction
    def _compact(self) -> list[int]:
        """Full merge of L1 + L0 into one bottom-level SST; tombstones are
        dropped (nothing lives below L1). Returns obsolete sst ids — the
        caller deletes them only after the new manifest is durable."""
        live = _merge_ssts(self._l1, self._l0, drop_tombstones=True)
        obsolete = [t.sst_id for t in self._l0]
        if self._l1 is not None:
            obsolete.append(self._l1.sst_id)
        sst_id = self._next_sst_id
        self._next_sst_id += 1
        self.objects.upload(
            _sst_path(sst_id),
            build_sstable_parts(self._committed_epoch, live))
        self._l1 = SsTable(sst_id, self._committed_epoch, live)
        self._l0 = []
        return obsolete

    # ------------------------------------- background compaction protocol
    def l0_run_count(self) -> int:
        return len(self._l0)

    def read_amp(self) -> int:
        """Sorted runs a point read may have to consult (L0 runs + L1)."""
        return len(self._l0) + (1 if self._l1 is not None else 0)

    def plan_compaction(self, floor_epoch: int, max_runs: int,
                        max_bytes: int) -> Optional[CompactionTask]:
        """Pick a bounded merge: the OLDEST contiguous tail of L0,
        size-tiered (stop once the byte budget is spent), restricted to
        runs at or below the pin floor — a run newer than the floor is
        never rewritten, so no version or tombstone a pinned reader
        could need is ever collapsed. When the selection covers all of
        L0 the existing L1 joins (budget permitting) and the output
        becomes the new bottom level, where tombstones drop; otherwise
        the output is an L0 run at the tail position and tombstones are
        carried (older runs below may still hold the key). Returns None
        when nothing is eligible. Event-loop only (allocates the output
        sst id and registers it with the scrubber keep-set)."""
        assert self.manifest_owner, "only the manifest owner compacts"
        eligible: list[SsTable] = []           # oldest-first
        spent = 0
        for sst in reversed(self._l0):
            if sst.epoch > floor_epoch:
                break
            size = sst.payload_bytes
            if eligible and (len(eligible) >= max_runs
                             or spent + size > max_bytes):
                break
            eligible.append(sst)
            spent += size
        if not eligible:
            return None
        covers_l0 = len(eligible) == len(self._l0)
        l1 = None
        if covers_l0 and self._l1 is not None \
                and spent + self._l1.payload_bytes <= max_bytes:
            l1 = self._l1
        into_l1 = covers_l0 and (l1 is not None or self._l1 is None)
        if len(eligible) < 2 and not into_l1:
            return None                        # a 1-run rewrite buys nothing
        runs = list(reversed(eligible))        # back to newest-first order
        task = CompactionTask(runs, l1, into_l1, self._next_sst_id)
        self._next_sst_id += 1
        self.compaction_inflight.add(task.out_sst_id)
        return task

    def merge_compaction(self, task: CompactionTask) -> None:
        """Thread-safe merge + build + PUT of a planned task: touches only
        the immutable input SsTables and the object store (the uploader
        discipline of `upload_sealed`). A crash here leaves an orphan
        output object no manifest references."""
        parts = _merge_ssts(task.l1_sst, task.ssts,
                            drop_tombstones=task.into_l1)
        task.keys_out = sum(len(p) for p in parts)
        self.objects.upload(_sst_path(task.out_sst_id),
                            build_sstable_parts(task.out_epoch, parts))
        task.parts = parts

    def install_compaction(self, task: CompactionTask) -> Optional[list[int]]:
        """Commit point of a background merge (event loop only): swap the
        merged output in for its inputs and write ONE manifest. Returns
        the obsolete sst ids (already deleted — strictly after the
        manifest landed), or None when the task no longer applies (the
        manifest was reloaded underneath it: restore, quarantine reopen).
        An abandoned output is an orphan the scrubber sweeps."""
        assert self.manifest_owner and task.parts is not None
        k = len(task.run_ids)
        tail = [t.sst_id for t in self._l0[-k:]]
        l1_now = self._l1.sst_id if self._l1 is not None else None
        if tail != task.run_ids \
                or (task.l1_id is not None and l1_now != task.l1_id):
            self.abandon_compaction(task)
            return None
        out = SsTable(task.out_sst_id, task.out_epoch, task.parts)
        if task.into_l1:
            self._l1 = out
            self._l0 = self._l0[:-k]
        else:
            self._l0 = self._l0[:-k] + [out]
        self._write_manifest()
        self.compaction_inflight.discard(task.out_sst_id)
        obsolete = task.input_ids
        for sst_id in obsolete:
            self.objects.delete(_sst_path(sst_id))
        return obsolete

    def abandon_compaction(self, task: CompactionTask) -> None:
        """Drop a planned/merged task without installing it. The output
        object (if uploaded) is left as an orphan for the scrubber."""
        self.compaction_inflight.discard(task.out_sst_id)

    # ------------------------------------------------------------- helpers
    @classmethod
    def open(cls, object_store: ObjectStore) -> "HummockStateStore":
        """Recovery entry: attach to whatever the last manifest committed."""
        return cls(object_store)
