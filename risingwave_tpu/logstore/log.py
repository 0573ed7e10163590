"""Changelog log store — durable, epoch-indexed egress decoupling.

Reference: `src/stream/src/common/log_store_impl/` — the sink executor
does not deliver to the external target directly; it appends each epoch's
changelog to a KV log whose writes are persisted WITH the checkpoint, a
background reader delivers committed epochs to the target after the
commit, and target-side sequence dedupe absorbs the one-epoch redelivery
window around a crash. That decoupling is what turns the documented
at-least-once crash window of direct at-barrier delivery into
exactly-once without falling into the at-most-once trap (deliver-after-
commit alone drops the epoch if the process dies between commit and
delivery — recovery does not replay committed epochs; the log does).

Two log layouts over the session's one StateStore:

  * `SinkChangelog` — the per-sink delivery log, keyed by a dense
    SEQUENCE number (`table_id ++ 0x00 ++ seq_be8`). The sequence is
    what targets dedupe on: it is minted at append time, becomes
    durable only when the checkpoint commits (append stages the entry
    at the SEALED epoch, so it rides the exact `seal -> upload_sealed
    -> commit_sealed` path the rest of the epoch's state takes), and a
    replay after a crash re-mints the SAME numbers for the re-computed
    epochs — cross-restart dedupe finally works, unlike the wall-clock
    epoch ids the old direct path handed targets. A delivery CURSOR
    (`table_id ++ 0x01`) and log truncation below it ride the same
    checkpoint, so the log stays bounded by the delivery lag.
  * `MvChangelog` — the per-MV subscription log, keyed by the sealed
    EPOCH (`table_id ++ 0x00 ++ epoch_be8`): subscribers hand-off from
    a committed snapshot at epoch E0 to tailing entries with epoch >
    E0 (subscription.py). Activation is lazy (an MV nobody subscribes
    to logs nothing), mirroring the serving cache's changelog hook.

`LogStoreHub` is the per-coordinator authority (owned by the
BarrierCoordinator exactly like the Memory/Serving managers): it is
pulsed at every checkpoint COMMIT, owns the per-sink background
delivery tasks and the per-subscription pumps, and fail-stops the
coordinator when a delivery raises (recovery then replays from the
last committed epoch, exactly like an upload failure).
"""

from __future__ import annotations

import asyncio
from typing import Iterator, Optional

from ..serving.cache import pending_rows
from ..state.serde import RowSerde
from ..state.store import StateStore, WriteBatch
from ..utils.metrics import (
    GLOBAL_METRICS, LOGSTORE_APPEND_BYTES, SINK_DELIVERED_EPOCHS,
    SINK_DELIVERED_ROWS,
)

# key-space layout under one log table id
_ENTRIES = 0x00        # log entries: tid ++ 0x00 ++ index_be8
_CURSOR = 0x01         # delivery cursor: tid ++ 0x01
_SUBCUR = 0x02         # durable subscription cursors: tid ++ 0x02 ++ name


def _entry_key(table_id: int, index: int) -> bytes:
    return table_id.to_bytes(4, "big") + bytes([_ENTRIES]) \
        + index.to_bytes(8, "big")


def _cursor_key(table_id: int) -> bytes:
    return table_id.to_bytes(4, "big") + bytes([_CURSOR])


def _sub_cursor_key(table_id: int, name: str) -> bytes:
    return table_id.to_bytes(4, "big") + bytes([_SUBCUR]) \
        + name.encode("utf-8")


def _sub_cursor_range(table_id: int) -> tuple[bytes, bytes]:
    return (table_id.to_bytes(4, "big") + bytes([_SUBCUR]),
            table_id.to_bytes(4, "big") + bytes([_SUBCUR + 1]))


def _entry_range(table_id: int, after_index: int) -> tuple[bytes, bytes]:
    """[start, end) covering entries with index > after_index."""
    return (_entry_key(table_id, after_index + 1),
            _cursor_key(table_id))


class _LogCodec:
    """Value codec for one log entry: u32 row count, then per row one op
    byte + u32 length + RowSerde bytes. The epoch the entry belongs to
    is prefixed (sink entries are seq-keyed but targets still receive
    the epoch id for observability)."""

    def __init__(self, schema):
        self.schema = schema
        self._serde = RowSerde(schema)

    def encode(self, epoch: int, rows: list) -> bytes:
        out = bytearray()
        out += epoch.to_bytes(8, "big")
        out += len(rows).to_bytes(4, "big")
        for op, vals in rows:
            enc = self._serde.encode(vals)
            out += bytes([op & 0xFF])
            out += len(enc).to_bytes(4, "big")
            out += enc
        return bytes(out)

    def decode(self, blob: bytes) -> tuple[int, list]:
        epoch = int.from_bytes(blob[:8], "big")
        n = int.from_bytes(blob[8:12], "big")
        pos = 12
        rows = []
        for _ in range(n):
            op = blob[pos]
            if op >= 128:                 # signed ops (OP_DEL = -1)
                op -= 256
            ln = int.from_bytes(blob[pos + 1:pos + 5], "big")
            pos += 5
            rows.append((op, self._serde.decode(blob[pos:pos + ln])))
            pos += ln
        return epoch, rows


class SinkChangelog:
    """The per-sink delivery log (seq-keyed). All writes stage into the
    store's shared buffer at the SEALED epoch of the checkpoint barrier
    that produced them, so the log entry, the delivery cursor and the
    truncation tombstones commit atomically with the rest of the epoch —
    a crash replays neither more nor less than the stream state does."""

    def __init__(self, store: StateStore, table_id: int, schema):
        self.store = store
        self.table_id = table_id
        self.codec = _LogCodec(schema)
        # next sequence number to mint: resume from the COMMITTED state
        # (a crash discards staged entries AND the in-memory counter
        # dies with the process — both sides restart from the same
        # committed prefix, so re-minted numbers match re-computed
        # epochs exactly)
        self._next_seq = max(self.committed_max_seq(),
                             self.read_cursor()) + 1

    # ------------------------------------------------------------ writes
    def append(self, epoch: int, rows: list) -> int:
        """Stage one epoch's changelog under the next sequence number at
        `epoch` (the sealed epoch — the write rides its checkpoint).
        Returns the sequence number minted."""
        seq = self._next_seq
        self._next_seq += 1
        blob = self.codec.encode(epoch, rows)
        self.store.ingest_batch(WriteBatch(
            self.table_id, epoch, {_entry_key(self.table_id, seq): blob}))
        LOGSTORE_APPEND_BYTES.inc(len(blob))
        return seq

    def persist_cursor(self, epoch: int, delivered_seq: int) -> None:
        """Stage the delivery cursor + truncate entries <= it, riding the
        same checkpoint as this barrier's append. After a crash the
        durable cursor is exactly what delivery resumes after; entries
        at or below it are never read again, so tombstoning them in the
        SAME atomic commit keeps the log bounded by delivery lag."""
        puts: dict[bytes, Optional[bytes]] = {
            _cursor_key(self.table_id): delivered_seq.to_bytes(8, "big")}
        start, end = _entry_range(self.table_id, 0)
        for k, _v in self.store.iter_range(start, end):
            if int.from_bytes(k[5:13], "big") <= delivered_seq:
                puts[k] = None
            else:
                break
        self.store.ingest_batch(WriteBatch(self.table_id, epoch, puts))

    # ------------------------------------------------------------- reads
    def read_cursor(self) -> int:
        """The durable delivery cursor from the COMMITTED view: staged
        (uncommitted) cursor writes vanish in a crash, so startup must
        resume from what actually committed."""
        v = self.store.get_committed(_cursor_key(self.table_id))
        return int.from_bytes(v, "big") if v is not None else 0

    def committed_max_seq(self) -> int:
        last = 0
        start, end = _entry_range(self.table_id, 0)
        for k, _v in self.store.iter_range(start, end,
                                           committed_only=True):
            last = int.from_bytes(k[5:13], "big")
        return last

    def read_committed(self, after_seq: int
                       ) -> Iterator[tuple[int, int, list]]:
        """(seq, epoch, rows) for committed entries with seq >
        after_seq, ascending — the delivery read. Only the committed
        view: a sealed-but-uncommitted epoch must never reach the
        target (delivering it and then crashing before the commit would
        replay the epoch under a fresh sequence number = a duplicate)."""
        start, end = _entry_range(self.table_id, after_seq)
        for k, v in self.store.iter_range(start, end, committed_only=True):
            epoch, rows = self.codec.decode(v)
            yield int.from_bytes(k[5:13], "big"), epoch, rows


class MvChangelog:
    """The per-MV subscription log (epoch-keyed). One writer per
    materialize actor; a parallel materialize's writers share the log
    table and stage disjoint row sets at the same epochs (vnode-
    partitioned state ⇒ disjoint pks), under per-writer sub-keys so
    concurrent actors never clobber one entry."""

    def __init__(self, store: StateStore, table_id: int, schema,
                 pk_indices, state_table=None, n_writers: int = 1):
        self.store = store
        self.table_id = table_id
        self.schema = schema
        self.pk_indices = tuple(pk_indices)
        # the MV's state table (subscription backfills scan its
        # committed snapshot; its id/layout ship to replicas so their
        # row keys — and thus scan order — match bit-identically)
        self.state_table = state_table
        self.codec = _LogCodec(schema)
        self.writers = [MvChangelogWriter(self, i)
                        for i in range(n_writers)]
        # sealed epoch at/below which nothing is logged (set at
        # activation — everything <= it is covered by the snapshot a
        # subscriber backfills from)
        self.active_from: Optional[int] = None
        # newest sealed epoch a writer passed while the log was
        # inactive: its rows were dropped at the writer's barrier, which
        # runs BEFORE the coordinator collects that barrier
        self.dropped_through = 0
        # retention floor this incarnation truncated to (the durable
        # truth is the committed tombstones; this just avoids rescanning
        # when nothing advanced)
        self.truncated_below = 0

    @property
    def active(self) -> bool:
        return self.active_from is not None

    def activate(self, last_collected_epoch: int) -> None:
        """Start logging. Every sealed epoch AFTER `last_collected_epoch`
        lands in the log (writers preserve their open-interval buffer,
        mirroring MvChangelogHook.activate), so a subscriber that
        snapshots at any committed E0 >= `active_from` tails entries
        > E0 with no gap and no overlap. An epoch a writer has already
        sealed and dropped is not logged even if the coordinator has
        not collected it yet: the floor covers it, so the snapshot
        does."""
        if self.active_from is None:
            self.active_from = max(last_collected_epoch,
                                   self.dropped_through)

    def deactivate(self) -> None:
        self.active_from = None

    # ------------------------------------- durable subscription cursors
    def persist_sub_cursor(self, name: str, cursor_epoch: int,
                           stage_epoch: int) -> None:
        """Stage a named subscription's delivered-through epoch; it
        commits with the next checkpoint, so after a reconnect the
        durable cursor is at or (by at most the delivery-to-checkpoint
        window) behind what the subscriber actually applied — resuming
        the tail from it re-delivers at most that window, which
        epoch-keyed application dedupes."""
        self.store.ingest_batch(WriteBatch(
            self.table_id, stage_epoch,
            {_sub_cursor_key(self.table_id, name):
             cursor_epoch.to_bytes(8, "big")}))

    def read_sub_cursor(self, name: str) -> Optional[int]:
        v = self.store.get_committed(_sub_cursor_key(self.table_id, name))
        return int.from_bytes(v, "big") if v is not None else None

    def committed_sub_cursors(self) -> dict[str, int]:
        start, end = _sub_cursor_range(self.table_id)
        out = {}
        for k, v in self.store.iter_range(start, end, committed_only=True):
            out[k[5:].decode("utf-8")] = int.from_bytes(v, "big")
        return out

    def drop_sub_cursor(self, name: str, stage_epoch: int) -> None:
        """Forget a named subscription (tombstone its durable cursor) —
        without this an abandoned replica pins retention forever."""
        self.store.ingest_batch(WriteBatch(
            self.table_id, stage_epoch,
            {_sub_cursor_key(self.table_id, name): None}))

    # --------------------------------------------------------- retention
    def truncate_below(self, floor_epoch: int, stage_epoch: int) -> None:
        """Tombstone committed entries with epoch <= floor_epoch (the
        minimum subscriber cursor): every subscriber — live pump or
        durable named cursor — has already consumed them, so they ride
        the next checkpoint out, exactly like the sink log's delivery-
        cursor truncation. The log stays bounded by subscriber lag
        instead of growing for the MV's lifetime."""
        start, end = _entry_range(self.table_id, 0)
        puts: dict[bytes, Optional[bytes]] = {}
        for k, _v in self.store.iter_range(start, end,
                                           committed_only=True):
            if int.from_bytes(k[5:13], "big") <= floor_epoch:
                puts[k] = None
            else:
                break
        if puts:
            self.store.ingest_batch(WriteBatch(
                self.table_id, stage_epoch, puts))
        self.truncated_below = max(self.truncated_below, floor_epoch)

    # ------------------------------------------------------------- reads
    def read_committed(self, after_epoch: int
                       ) -> Iterator[tuple[int, list]]:
        """(epoch, merged rows) for committed entries with epoch >
        after_epoch, ascending. Per-writer sub-entries of one epoch are
        merged in writer order (their pk sets are disjoint, so the
        order never changes the applied result)."""
        start, end = _entry_range(self.table_id, 0)
        start = self.table_id.to_bytes(4, "big") + bytes([_ENTRIES]) \
            + (after_epoch + 1).to_bytes(8, "big")
        cur_epoch = None
        cur_rows: list = []
        for k, v in self.store.iter_range(start, end, committed_only=True):
            epoch = int.from_bytes(k[5:13], "big")
            _e, rows = self.codec.decode(v)
            if epoch != cur_epoch:
                if cur_epoch is not None:
                    yield cur_epoch, cur_rows
                cur_epoch, cur_rows = epoch, []
            cur_rows.extend(rows)
        if cur_epoch is not None:
            yield cur_epoch, cur_rows


class MvChangelogWriter:
    """Attached to one MaterializeExecutor as `changelog_log`: buffers
    the interval's effective changelog (the same post-conflict rows the
    serving hook carries) and stages it under the sealed epoch at each
    barrier while the log is active."""

    __slots__ = ("log", "writer_idx", "_pending")

    def __init__(self, log: MvChangelog, writer_idx: int):
        self.log = log
        self.writer_idx = writer_idx
        self._pending: list = []

    def on_rows(self, rows) -> None:
        """As `MvChangelogHook.on_rows`: a row list or an `EffectiveChunk`,
        turned into rows only by a barrier that stages them."""
        if rows:
            self._pending.append(rows)

    def on_barrier(self, sealed_epoch: int) -> None:
        batches = self._pending
        self._pending = []
        if not batches:
            return
        if not self.log.active:
            self.log.dropped_through = max(self.log.dropped_through,
                                           sealed_epoch)
            return
        rows = pending_rows(batches)
        key = self.log.table_id.to_bytes(4, "big") + bytes([_ENTRIES]) \
            + sealed_epoch.to_bytes(8, "big") \
            + self.writer_idx.to_bytes(2, "big")
        blob = self.log.codec.encode(sealed_epoch, rows)
        self.log.store.ingest_batch(WriteBatch(
            self.log.table_id, sealed_epoch, {key: blob}))
        LOGSTORE_APPEND_BYTES.inc(len(blob))


class SinkDelivery:
    """Background delivery for one sink: reads the COMMITTED log past
    the cursor and writes each entry to the target exactly once per
    sequence number, waking on every checkpoint commit. Failures park on
    the hub and fail-stop the coordinator at the next injection (the
    upload-failure discipline), so recovery owns retries."""

    def __init__(self, hub: "LogStoreHub", name: str, log: SinkChangelog,
                 target):
        self.hub = hub
        self.name = name
        self.log = log
        self.target = target
        self.delivered_seq = max(log.read_cursor(), target.committed_seq())
        self.delivered_epochs = 0
        self.closing = False
        self.task: Optional[asyncio.Task] = None
        self._lock = asyncio.Lock()
        self._lag = GLOBAL_METRICS.gauge("logstore_subscription_lag_epochs",
                                         subscription=f"sink/{name}")

    def spawn(self) -> None:
        if self.task is None or self.task.done():
            self.task = asyncio.get_running_loop().create_task(
                self._run(), name=f"sink-delivery-{self.name}")

    async def _run(self) -> None:
        seen = self.hub.commit_seq
        while not self.closing:
            try:
                await self.deliver_pending()
            except asyncio.CancelledError:
                raise
            except BaseException as e:  # noqa: BLE001 — park for injection
                self.hub.fail(self.name, e)
                return
            seen = await self.hub.wait_commit(seen)

    async def deliver_pending(self) -> None:
        """Deliver every committed entry past the cursor, in order. The
        lock serializes the background loop against an explicit
        `drain()` — double delivery of one seq to a deduping target is
        harmless but to a callback target it would not be."""
        async with self._lock:
            while True:
                batch = list(self.log.read_committed(self.delivered_seq))
                self._lag.set(float(len(batch)))
                if not batch:
                    break
                for seq, epoch, rows in batch:
                    if seq > self.target.committed_seq():
                        await asyncio.to_thread(
                            self.target.write, seq, epoch, rows)
                        SINK_DELIVERED_ROWS.inc(len(rows))
                    self.delivered_seq = seq
                    self.delivered_epochs += 1
                    SINK_DELIVERED_EPOCHS.inc()
                    self._lag.dec()

    def pending(self) -> bool:
        for _ in self.log.read_committed(self.delivered_seq):
            return True
        return False

    def stop(self) -> None:
        self.closing = True
        if self.task is not None and not self.task.done():
            self.task.cancel()
        GLOBAL_METRICS.remove("logstore_subscription_lag_epochs",
                              subscription=f"sink/{self.name}")


class LogStoreHub:
    """Per-coordinator log-store authority (meta/barrier_manager.py owns
    one like the Memory/Serving managers). Commit pulses drive delivery
    and subscription pumps; `drain()` is the quiesce point run by
    `run_rounds`/`stop_all` so callers observe delivered targets the
    same way they observe committed state."""

    def __init__(self, store: StateStore):
        self.store = store
        self.sinks: dict[str, SinkDelivery] = {}
        self.mv_logs: dict[str, MvChangelog] = {}
        self.subscriptions: list = []     # live _SubscriptionPump objects
        self.collected_epoch = 0
        self.commit_seq = 0
        self._commit_event = asyncio.Event()
        self.failure: Optional[tuple[str, BaseException]] = None
        self.aborted = False
        # durable event log (meta/event_log.py), attached by the
        # session: a sink parking on delivery failure leaves a record
        self.event_log = None
        # durable-cursor lease (SET subscription_cursor_ttl_ms): a named
        # cursor with NO live pump renewing its lease for this long
        # stops pinning changelog retention — the abandoned-replica
        # escape hatch. 0 = never expire. `_cursor_seen` is the lease
        # clock: (mv, cursor) -> monotonic time last renewed (a live
        # pump renews; an orphan's clock starts at first observation).
        self.sub_cursor_ttl_ms = 0
        self._cursor_seen: dict[tuple[str, str], float] = {}

    # ------------------------------------------------------ registration
    def register_sink(self, name: str, log: SinkChangelog,
                      target) -> SinkDelivery:
        """Called by the sink executor at its first barrier; replaces a
        previous incarnation's task (re-create after drop, recovery
        rebuilds on a fresh hub so collisions are same-session only)."""
        old = self.sinks.pop(name, None)
        if old is not None:
            old.stop()
        d = SinkDelivery(self, name, log, target)
        self.sinks[name] = d
        d.spawn()
        return d

    def unregister_sink(self, name: str) -> None:
        d = self.sinks.pop(name, None)
        if d is not None:
            d.stop()

    def register_mv(self, name: str, table_id: int, schema, pk_indices,
                    state_table=None, n_writers: int = 1) -> MvChangelog:
        log = MvChangelog(self.store, table_id, schema, pk_indices,
                          state_table=state_table, n_writers=n_writers)
        cursors = log.committed_sub_cursors()
        if cursors:
            # durable named cursors survive a restart: re-activate
            # immediately so the rebuilt writers log every post-recovery
            # epoch — entries in (min cursor, committed] are already
            # durable in the log (retention floors at the min cursor),
            # so a reconnecting subscriber's resume stays gapless across
            # the crash
            log.activate(min(cursors.values()))
        self.mv_logs[name] = log
        return log

    def unregister_mv(self, name: str) -> None:
        self.mv_logs.pop(name, None)
        # live subscriptions of a dropped MV can never see another
        # entry; stop their pumps instead of leaving them parked on the
        # commit pulse forever
        for pump in [p for p in self.subscriptions if p.mv == name]:
            pump.stop()

    # ----------------------------------------------------------- commits
    def pinning_sub_cursors(self, name: str, log: MvChangelog) -> dict:
        """The durable named cursors still HOLDING `log`'s retention: a
        cursor whose lease lapsed (no live pump under that name within
        `sub_cursor_ttl_ms`) is excluded — retention advances past it,
        and a later resubscribe under the name falls back to
        backfill-then-tail instead of resuming. Renewals happen here:
        every call stamps cursors with a live pump, so the TTL clock
        only runs while the subscriber is actually away."""
        import time
        durable = log.committed_sub_cursors()
        if not durable:
            return {}
        now = time.monotonic()
        live = {p.cursor_name for p in self.subscriptions
                if p.mv == name and p.cursor_name is not None}
        ttl_s = self.sub_cursor_ttl_ms / 1e3
        out = {}
        for cname, cur in durable.items():
            key = (name, cname)
            if cname in live:
                self._cursor_seen[key] = now
            seen = self._cursor_seen.setdefault(key, now)
            if ttl_s <= 0 or cname in live or (now - seen) < ttl_s:
                out[cname] = cur
        return out

    def on_commit(self, epoch: int) -> None:
        """Pulsed by the coordinator at every checkpoint commit (inline
        sync, background uploader, and cluster commit_remote paths).
        Also the MV-changelog retention point: entries below every
        subscriber's cursor (live pumps AND durable named cursors whose
        lease has not lapsed) are tombstoned, staged at the current open
        epoch so the truncation rides the next checkpoint."""
        self.commit_seq += 1
        self._commit_event.set()
        for name, log in self.mv_logs.items():
            if not log.active:
                continue
            durable = log.committed_sub_cursors()
            pinning = self.pinning_sub_cursors(name, log)
            live_names = {p.cursor_name for p in self.subscriptions
                          if p.mv == name}
            # a lapsed lease is released DURABLY: the cursor tombstone
            # rides the next checkpoint, so expiry survives restart
            # (register_mv would otherwise resurrect retention from the
            # stale cursor) and a later resubscribe under the name
            # deterministically backfills instead of resuming
            for cname in set(durable) - set(pinning) - live_names:
                log.drop_sub_cursor(cname, self.collected_epoch)
                self._cursor_seen.pop((name, cname), None)
            cursors = [p.cursor_epoch for p in self.subscriptions
                       if p.mv == name]
            cursors.extend(pinning.values())
            if not cursors:
                if durable:
                    # every holder was an expired cursor: stop paying
                    # the log entirely — truncate to the sealed floor
                    # and deactivate (a resubscribe re-activates with a
                    # fresh backfill handoff)
                    log.truncate_below(self.collected_epoch,
                                       self.collected_epoch)
                    log.deactivate()
                continue
            floor = min(cursors)
            if floor > log.truncated_below:
                log.truncate_below(floor, self.collected_epoch)

    def on_barrier(self, barrier) -> None:
        """Collected-barrier hook: remember the sealed epoch — the
        activation floor for MV logs (everything <= it is in table
        state, everything after will be logged once active)."""
        self.collected_epoch = barrier.epoch.prev

    async def wait_commit(self, seen: int) -> int:
        while self.commit_seq == seen:
            self._commit_event.clear()
            await self._commit_event.wait()
        return self.commit_seq

    def fail(self, name: str, exc: BaseException) -> None:
        if self.failure is None:
            self.failure = (name, exc)
            if self.event_log is not None:
                self.event_log.emit("sink_park", sink=name,
                                    error=repr(exc))
        self.commit_seq += 1
        self._commit_event.set()          # wake waiters so they observe it

    def check_failure(self) -> None:
        if self.failure is not None:
            name, exc = self.failure
            raise RuntimeError(
                f"sink delivery {name!r} failed; recovery must replay "
                f"from the last committed epoch") from exc

    # ------------------------------------------------------------- drain
    async def drain(self) -> None:
        """Deliver everything committed (quiesce point; NOT part of the
        barrier path). Raises a parked delivery failure like
        drain_uploads raises an upload failure — a failure DURING this
        drain parks the same way (wrapped in the standard fail-stop
        RuntimeError), so tick's auto-recovery owns the retry instead
        of a raw connector error escaping to the caller."""
        self.check_failure()
        for d in list(self.sinks.values()):
            try:
                await d.deliver_pending()
            except asyncio.CancelledError:
                raise
            except BaseException as e:  # noqa: BLE001 — park it
                self.fail(d.name, e)
                break
        for pump in list(self.subscriptions):
            try:
                await pump.pump_pending()
            except (ConnectionResetError, BrokenPipeError, OSError):
                # a vanished subscriber is the subscriber's problem —
                # sink failures fail-stop, subscription failures just
                # close the subscription
                pump.stop()
        self.check_failure()

    def abort(self) -> None:
        """Crash/recovery entry: cancel every background task. Durable
        cursors are already exact (they commit with checkpoints), so
        the rebuilt topology's fresh tasks resume exactly-once."""
        self.aborted = True
        for d in self.sinks.values():
            d.stop()
        self.sinks.clear()
        for pump in list(self.subscriptions):
            pump.stop()
        self.subscriptions.clear()
        self.commit_seq += 1
        self._commit_event.set()          # release parked subscribe waits

    # --------------------------------------------------------- reporting
    def report(self) -> list[tuple]:
        """SHOW subscriptions rows: (name, kind, cursor, delivered,
        active)."""
        rows = []
        for name in sorted(self.sinks):
            d = self.sinks[name]
            rows.append((f"sink/{name}", "delivery",
                         str(d.delivered_seq), str(d.delivered_epochs),
                         "failed" if self.failure
                         and self.failure[0] == name else "live"))
        for pump in self.subscriptions:
            rows.append((f"{pump.mv}/{pump.sub_id}", "changelog",
                         str(pump.cursor_epoch),
                         str(pump.delivered_batches),
                         "live" if not pump.closing else "closed"))
        return rows
