"""Background compaction & retention plane.

Reference: the dedicated compactor node (src/storage/compactor/ and the
`fast_compactor_runner`) — compaction is a SUBSYSTEM, not a side effect
of committing. The shape kept here:

- `BackgroundCompactor` is barrier-paced: the coordinator pulses it in
  the same synchronous between-epochs window the scrubber uses. Each
  pulse does O(1) loop work — harvest a finished merge (one manifest
  swap, deletes strictly after), refresh gauges, and maybe START a new
  merge on a worker thread (`asyncio.to_thread`, the PR 2 uploader
  discipline). The commit path itself never merges: attaching the
  compactor flips `HummockStateStore.inline_compaction` off.
- Merges are bounded and tiered: the oldest contiguous tail of L0,
  capped by a byte budget that accrues per barrier (pacing — bytes
  rewritten per interval is bounded) and a max run count. Only when a
  merge covers all of L0 does L1 join and tombstones drop (nothing
  lives below the bottom level).
- `PinRegistry` aggregates the minimum pinned epoch across every reader
  that could look below the committed tip: serving snapshot pins,
  durable subscription cursors + live pumps (LogStoreHub), explicit
  scan/backup pins. No run newer than that floor is ever rewritten, so
  no version or tombstone a pinned reader could need is collapsed.
- Fail-safety: a merge-thread crash or an abandoned install leaves at
  worst an orphan output object — `compaction_inflight` keeps live
  outputs out of the scrubber's sweep, and everything else is exactly
  the orphan shape the PR 12 scrubber already collects.
- `BrokerRetentionManager` rides the same pulse: the earliest DURABLE
  offset per broker partition (min over committed source offsets — the
  connector's in-memory offset runs ahead of the checkpoint and must
  not gate deletion) is pushed to the broker, which drops whole sealed
  segments below it and key-compacts changelog topics.
"""

from __future__ import annotations

import asyncio
import time
from typing import Callable, Optional

from ..utils.faults import FAULTS
from ..utils.metrics import (COMPACTION_BYTES_REWRITTEN, COMPACTION_RUNS,
                             COMPACTION_SECONDS, LSM_L0_RUNS, LSM_READ_AMP,
                             RETENTION_SEGMENTS_DROPPED,
                             retention_floor_gauge)
from .hummock import CompactionTask, HummockStateStore


class PinRegistry:
    """Aggregates the minimum pinned epoch across every source that can
    read below the committed tip. `floor()` returns the epoch below
    which versions/tombstones may be collapsed: +inf (no constraint)
    when nothing pins. Explicit pins (backfill scans, backups) use
    pin()/unpin() tokens; serving and logstore sources are polled."""

    def __init__(self):
        self.serving = None          # ServingManager, attached by the coord
        self.logstore = None         # LogStoreHub, attached by the coord
        self._explicit: dict[int, tuple[str, int]] = {}  # token -> (src, ep)
        self._next_token = 1

    # ------------------------------------------------------ explicit pins
    def pin(self, epoch: int, source: str = "scan") -> int:
        token = self._next_token
        self._next_token += 1
        self._explicit[token] = (source, int(epoch))
        return token

    def unpin(self, token: int) -> None:
        self._explicit.pop(token, None)

    # ----------------------------------------------------------- the floor
    def floors(self) -> dict[str, Optional[int]]:
        """Per-source minimum pinned epoch (None = source holds nothing)."""
        out: dict[str, Optional[int]] = {
            "serving": None, "subscriptions": None,
            "scan": None, "backup": None,
        }
        if self.serving is not None:
            pinned = [ent.cache.snapshot.epoch
                      for ent in self.serving._mvs.values()
                      if ent.cache is not None
                      and ent.cache.snapshot is not None
                      and ent.cache.snapshot.pins > 0]
            if pinned:
                out["serving"] = min(pinned)
        if self.logstore is not None:
            cursors: list[int] = []
            for name, log in self.logstore.mv_logs.items():
                cursors.extend(
                    self.logstore.pinning_sub_cursors(name, log).values())
            cursors.extend(p.cursor_epoch
                           for p in self.logstore.subscriptions)
            if cursors:
                out["subscriptions"] = min(cursors)
        for source, epoch in self._explicit.values():
            if out.get(source) is None or epoch < out[source]:
                out[source] = epoch
        return out

    def floor(self) -> float:
        present = [e for e in self.floors().values() if e is not None]
        return min(present) if present else float("inf")


class BackgroundCompactor:
    """Barrier-paced leveled compactor for a manifest-owning Hummock
    store. Owned by the BarrierCoordinator; `on_barrier` runs in the
    synchronous between-epochs window. At most one merge is in flight."""

    def __init__(self, store, serving=None, logstore=None):
        self.store = store
        self.pins = PinRegistry()
        self.pins.serving = serving
        self.pins.logstore = logstore
        # pacing/trigger knobs (Session CONFIG_VARS plumb here)
        self.interval = 1            # pulse every N barriers; 0 disables
        self.l0_trigger = 4          # start merging once L0 exceeds this
        self.budget_bytes = 8 << 20  # credit accrued per pulse
        self.max_runs = 8            # runs per merge (bounded work)
        self.credit_cap_bytes = 512 << 20
        self.event_log = None
        self.retention: Optional[BrokerRetentionManager] = None
        # state
        self._barriers = 0
        self._credit = 0
        self._floor_sources: set = set()
        self._job: Optional[asyncio.Task] = None
        self._task: Optional[CompactionTask] = None
        # counters for SHOW compaction / the soak gate
        self.runs_total = 0
        self.bytes_rewritten_total = 0
        self.keys_dropped_total = 0
        self.installs_abandoned = 0
        self.merge_failures = 0
        self.last_output: Optional[dict] = None

    # --------------------------------------------------------------- admin
    @property
    def active(self) -> bool:
        return (self.interval > 0
                and isinstance(self.store, HummockStateStore)
                and self.store.manifest_owner)

    def configure(self, interval: Optional[int] = None,
                  l0_trigger: Optional[int] = None,
                  budget_bytes: Optional[int] = None,
                  max_runs: Optional[int] = None) -> None:
        if interval is not None:
            self.interval = int(interval)
        if l0_trigger is not None:
            self.l0_trigger = max(1, int(l0_trigger))
        if budget_bytes is not None:
            self.budget_bytes = max(0, int(budget_bytes))
        if max_runs is not None:
            self.max_runs = max(2, int(max_runs))
        self._sync_inline_flag()

    def _sync_inline_flag(self) -> None:
        """The commit path runs inline full merges ONLY while no live
        compactor owns the store (standalone stores, or the operator
        disabled the compactor with SET compaction_interval=0)."""
        if isinstance(self.store, HummockStateStore) \
                and self.store.manifest_owner:
            self.store.inline_compaction = not self.active

    # -------------------------------------------------------------- pulse
    def on_barrier(self, epoch: int) -> None:
        self._sync_inline_flag()
        if not self.active:
            return
        self._barriers += 1
        if self._barriers % self.interval:
            return
        self._pulse(epoch)
        if self.retention is not None:
            self.retention.on_barrier(epoch)

    def _pulse(self, epoch: int) -> None:
        store = self.store
        LSM_L0_RUNS.set(float(store.l0_run_count()))
        LSM_READ_AMP.set(float(store.read_amp()))
        floors = self.pins.floors()
        for source, ep in floors.items():
            retention_floor_gauge(source).set(
                float(ep if ep is not None else -1))
        # a pin source that vanished (DROP SINK, subscription gone) must
        # take its labelled gauge with it, or /metrics grows forever
        from ..utils.metrics import GLOBAL_METRICS
        for source in self._floor_sources - set(floors):
            GLOBAL_METRICS.remove("retention_floor_epoch", source=source)
        self._floor_sources = set(floors)
        self._harvest()
        self._credit = min(self._credit + self.budget_bytes * self.interval,
                           self.credit_cap_bytes)
        if self._job is not None or self._task is not None:
            return                      # one merge in flight at a time
        # write-amplification-aware trigger: merge when the read fan-out
        # exceeds the configured depth (every extra L0 run is one more
        # sorted run each read consults)
        if store.l0_run_count() <= self.l0_trigger:
            return
        present = [e for e in floors.values() if e is not None]
        floor = min(present) if present else epoch
        task = store.plan_compaction(floor, self.max_runs, self._credit)
        if task is None:
            return
        self._credit = max(0, self._credit - task.input_bytes)
        self._task = task
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        if loop is None:                # synchronous harness (unit tests)
            self._merge(task)
            self._harvest()
        else:
            self._job = loop.create_task(asyncio.to_thread(
                self._merge, task))

    def _merge(self, task: CompactionTask) -> None:
        """Worker-thread half: merge + upload (store.merge_compaction is
        thread-safe). Timing and fault injection live here."""
        if FAULTS.active \
                and FAULTS.hit("compaction_merge",
                               sst_id=task.out_sst_id) is not None:
            from ..utils.faults import FaultInjected
            raise FaultInjected("compaction_merge")
        t0 = time.monotonic()
        self.store.merge_compaction(task)
        COMPACTION_SECONDS.observe(time.monotonic() - t0)

    def _harvest(self) -> None:
        """Loop-side half: collect a finished merge and install it under
        one manifest swap. A merge failure is NOT fatal — the invariant
        is that at worst an orphan object exists, which the scrubber
        sweeps — so it is recorded and the trigger simply refires."""
        job, task = self._job, self._task
        if task is None or (job is not None and not job.done()):
            return
        self._job, self._task = None, None
        if job is not None:
            exc = None if job.cancelled() else job.exception()
            if job.cancelled() or exc is not None:
                self.store.abandon_compaction(task)
                self.merge_failures += 1
                if self.event_log is not None and exc is not None:
                    self.event_log.emit("compaction_failed",
                                        sst_id=task.out_sst_id,
                                        error=repr(exc))
                return
        if task.parts is None:          # merge never ran (aborted early)
            self.store.abandon_compaction(task)
            return
        obsolete = self.store.install_compaction(task)
        if obsolete is None:            # manifest moved underneath us
            self.installs_abandoned += 1
            return
        self.runs_total += 1
        self.bytes_rewritten_total += task.input_bytes
        self.keys_dropped_total += task.keys_in - task.keys_out
        COMPACTION_RUNS.inc()
        COMPACTION_BYTES_REWRITTEN.inc(task.input_bytes)
        LSM_L0_RUNS.set(float(self.store.l0_run_count()))
        LSM_READ_AMP.set(float(self.store.read_amp()))
        self.last_output = {
            "out_sst": task.out_sst_id, "inputs": obsolete,
            "into_l1": task.into_l1, "bytes": task.input_bytes,
            "keys_dropped": task.keys_in - task.keys_out,
        }
        if self.event_log is not None:
            self.event_log.emit("compaction_run", **self.last_output)

    # ----------------------------------------------------------- lifecycle
    async def drain(self) -> None:
        """Wait out an in-flight merge and install it (backup/shutdown
        quiesce — mirrors BarrierCoordinator.drain_uploads)."""
        if self._job is not None:
            try:
                await self._job
            except Exception:  # noqa: BLE001 — recorded by _harvest
                pass
        self._harvest()

    def abort(self) -> None:
        """Recovery entry (mirrors abort_uploads): drop the in-flight
        merge. The thread may still finish its upload — that object is
        an orphan no manifest references; the scrubber sweeps it."""
        if self._job is not None:
            self._job.cancel()
        if self._task is not None:
            self.store.abandon_compaction(self._task)
        self._job, self._task = None, None

    # ----------------------------------------------------------- reporting
    def report(self) -> list[tuple[str, str]]:
        rows = [
            ("enabled", str(self.active).lower()),
            ("interval", str(self.interval)),
            ("l0_trigger", str(self.l0_trigger)),
            ("budget_bytes", str(self.budget_bytes)),
            ("max_runs", str(self.max_runs)),
            ("credit_bytes", str(self._credit)),
            ("in_flight", str(self._task is not None).lower()),
            ("runs_total", str(self.runs_total)),
            ("bytes_rewritten_total", str(self.bytes_rewritten_total)),
            ("keys_dropped_total", str(self.keys_dropped_total)),
            ("installs_abandoned", str(self.installs_abandoned)),
            ("merge_failures", str(self.merge_failures)),
        ]
        if isinstance(self.store, HummockStateStore):
            rows += [("l0_runs", str(self.store.l0_run_count())),
                     ("read_amp", str(self.store.read_amp()))]
        for source, ep in self.pins.floors().items():
            rows.append((f"floor_{source}",
                         "-" if ep is None else str(ep)))
        if self.last_output is not None:
            rows.append(("last_run", str(self.last_output)))
        if self.retention is not None:
            rows.extend(self.retention.report())
        return rows


class BrokerRetentionManager:
    """Pushes earliest-DURABLE-offset floors to brokers so they can drop
    whole sealed segments (and key-compact changelog topics) below what
    every consumer has checkpointed. Floors come from the source
    executors' committed-offset history: the newest per-split offset
    snapshot whose epoch the store has committed — never the live
    connector offset, which runs ahead of the checkpoint and would
    reopen the exactly-once window on recovery."""

    def __init__(self, store, source_execs: Callable[[], dict]):
        self.store = store
        self.source_execs = source_execs
        self.interval = 0               # barriers between pushes; 0 = off
        self.event_log = None
        self._barriers = 0
        self._job: Optional[asyncio.Task] = None
        self.segments_dropped_total = 0
        self.floors_pushed: dict[tuple[str, int], int] = {}
        self.push_failures = 0

    def configure(self, interval: Optional[int] = None) -> None:
        if interval is not None:
            self.interval = int(interval)

    def _durable_floors(self) -> dict[tuple[str, int], tuple[int, object]]:
        """(topic, partition) -> (min committed offset, client). A
        partition consumed by ANY split without a committed offset yet
        contributes floor 0 (drop nothing)."""
        committed = self.store.committed_epoch()
        floors: dict[tuple[str, int], tuple[int, object]] = {}
        for ex in self.source_execs().values():
            hist = getattr(ex, "offset_history", None)
            durable: dict = {}
            if hist:
                for ep, offs in reversed(hist):
                    if ep <= committed:
                        durable = offs
                        break
            for sid, conn in getattr(ex, "splits", []):
                topic = getattr(conn, "topic", None)
                part = getattr(conn, "partition", None)
                client = getattr(conn, "client", None)
                if topic is None or part is None or client is None:
                    continue
                off = int(durable.get(sid, 0))
                key = (topic, int(part))
                if key not in floors or off < floors[key][0]:
                    floors[key] = (off, client)
        return floors

    def on_barrier(self, epoch: int) -> None:
        if self.interval <= 0:
            return
        self._barriers += 1
        if self._barriers % self.interval:
            return
        if self._job is not None:
            if not self._job.done():
                return
            self._job = None
        floors = {k: v for k, v in self._durable_floors().items()
                  if v[0] > 0 and self.floors_pushed.get(k) != v[0]}
        if not floors:
            return
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            self._push(floors)
            return
        self._job = loop.create_task(asyncio.to_thread(self._push, floors))

    def _push(self, floors: dict) -> None:
        """Worker-thread half: one RPC per changed partition floor."""
        for (topic, part), (off, client) in floors.items():
            try:
                res = client.set_retention_floor(topic, part, off)
            except Exception:  # noqa: BLE001 — broker away: retry later
                self.push_failures += 1
                continue
            self.floors_pushed[(topic, part)] = off
            dropped = int((res or {}).get("segments_dropped", 0))
            if dropped:
                self.segments_dropped_total += dropped
                RETENTION_SEGMENTS_DROPPED.inc(dropped)
                if self.event_log is not None:
                    self.event_log.emit(
                        "broker_segments_dropped", topic=topic,
                        partition=part, floor=off, segments=dropped)

    def report(self) -> list[tuple[str, str]]:
        return [
            ("retention_interval", str(self.interval)),
            ("retention_floors_pushed", str(len(self.floors_pushed))),
            ("retention_segments_dropped",
             str(self.segments_dropped_total)),
            ("retention_push_failures", str(self.push_failures)),
        ]
