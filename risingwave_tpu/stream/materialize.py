"""Materialize executor — terminal op maintaining the MV's state table.

Reference: src/stream/src/executor/mview/materialize.rs (:52,65,141-183):
applies the changelog to the MV table with a ConflictBehavior, commits at
barriers. The MV table *is* the queryable result (batch side reads it at a
committed snapshot).

Serving hook: when the session registers the MV with the serving layer
(serving/manager.py), `serving_hook` carries the EFFECTIVE changelog —
the post-conflict-resolution upserts/deletes actually applied to the
table — so the per-MV SnapshotCache replays exactly what the storage
sees, and stamps each interval's rows with the sealed epoch at the
barrier.

Changelog log: `changelog_log` (logstore/log.py MvChangelogWriter,
registered alongside the serving hook) receives the SAME effective
rows and stages them into the durable per-MV log under the sealed
epoch at each barrier — the feed changelog subscriptions and serving
replicas tail after the checkpoint commits. While no subscription has
activated the log, the writer drops its buffer at each barrier, so
unsubscribed MVs pay nothing durable.

A NO_CHECK table (every MV over stream operators) takes each chunk as ONE
columnar write from one fetch of the chunk's host lanes, and hands both
taps those lanes (`serving/cache.py` `EffectiveChunk`): Python rows exist
only where an active tap keeps them. OVERWRITE / IGNORE read, compare and
write row by row.
"""

from __future__ import annotations

import enum
from typing import Optional

from ..common.chunk import (
    HostChunk, StreamChunk, OP_INSERT, OP_UPDATE_INSERT,
)
from ..state.state_table import StateTable
from ..utils.d2h import fetch_chunk, off_loop
from .executor import Executor
from .message import Barrier, BarrierKind, Watermark


class ConflictBehavior(enum.Enum):
    NO_CHECK = "no_check"            # trust the changelog (MV over stream ops)
    OVERWRITE = "overwrite"          # upsert by pk (tables with pk)
    IGNORE = "ignore_conflict"       # first write wins


class MaterializeExecutor(Executor):
    def __init__(self, input: Executor, table: StateTable,
                 conflict: ConflictBehavior = ConflictBehavior.NO_CHECK):
        self.input = input
        self.schema = input.schema
        self.pk_indices = table.pk_indices
        self.table = table
        self.conflict = conflict
        self.identity = f"Materialize(table={table.table_id})"
        # serving changelog tap (serving/cache.py MvChangelogHook); set by
        # the session when the MV registers with the serving layer
        self.serving_hook = None
        # durable changelog tap (logstore/log.py MvChangelogWriter); set
        # by the session when the MV registers with the log-store hub
        self.changelog_log = None

    async def execute(self):
        first = True
        async for msg in self.input.execute():
            if isinstance(msg, StreamChunk):
                # the chunk is on the device until the program that makes
                # it has run: that wait, and the copy of its lanes, are
                # taken off the loop
                self._apply(await off_loop(fetch_chunk, msg))
                yield msg
            elif isinstance(msg, Barrier):
                # a dataflow created mid-session initializes on its first
                # OBSERVED barrier, which need not be the Initial kind
                # (MV-on-MV actors join a running epoch stream)
                if first or msg.kind is BarrierKind.INITIAL:
                    first = False
                    self.table.init_epoch(msg.epoch.curr)
                else:
                    self.table.commit(msg.epoch.curr)
                if self.serving_hook is not None:
                    # the interval just committed belongs to the epoch
                    # this barrier seals
                    self.serving_hook.on_barrier(msg.epoch.prev)
                if self.changelog_log is not None:
                    # staged at the sealed epoch: the log entry rides
                    # this barrier's checkpoint, committing atomically
                    # with the table state it describes
                    self.changelog_log.on_barrier(msg.epoch.prev)
                yield msg
            else:
                yield msg

    def _apply(self, host: HostChunk) -> None:
        from ..serving.cache import OP_DEL, OP_PUT, EffectiveChunk
        if not host.vis.any():
            return
        hook = self.serving_hook
        clog = self.changelog_log
        if self.conflict is ConflictBehavior.NO_CHECK:
            self.table.write_chunk_columns(host.ops, host.cols, host.vis,
                                           host.valids)
            eff = EffectiveChunk(host)
            if hook is not None:
                hook.on_rows(eff)
            if clog is not None:
                clog.on_rows(eff)
            return
        rows = host.rows()
        eff = []
        for op, row in rows:
            if op in (OP_INSERT, OP_UPDATE_INSERT):
                pk = tuple(row[i] for i in self.table.pk_indices)
                existing = self.table.get_row(pk, dist_values=tuple(
                    row[i] for i in self.table.dist_key_indices))
                if existing is not None:
                    if self.conflict is ConflictBehavior.IGNORE:
                        continue
                    self.table.update(existing, row)
                else:
                    self.table.insert(row)
                eff.append((OP_PUT, row))
            else:
                self.table.delete(row)
                eff.append((OP_DEL, row))
        if eff:
            if hook is not None:
                hook.on_rows(eff)
            if clog is not None:
                clog.on_rows(eff)
