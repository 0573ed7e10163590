"""Source executor.

Reference: src/stream/src/executor/source/source_executor.rs — the stream is
a select over (dedicated barrier channel, connector chunks); barriers always
win, Pause/Resume/Throttle mutations gate the connector side, and the split
offsets are committed to a state table at each checkpoint barrier
(state_table_handler.rs) so recovery reseeks the connector.
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from typing import Optional, Protocol

from ..common.chunk import StreamChunk
from ..common.types import Schema
from ..state.state_table import StateTable
from .executor import Executor
from .message import Barrier, BarrierKind, ThrottleMutation


class Connector(Protocol):
    schema: Schema
    offset: int

    def next_chunk(self) -> StreamChunk: ...
    def seek(self, offset: int) -> None: ...


class SourceExecutor(Executor):
    def __init__(self, source_id: int, connector: Optional[Connector] = None,
                 barrier_queue: "asyncio.Queue[Barrier]" = None,
                 state_table: Optional[StateTable] = None,
                 rate_limit_rows_per_barrier: Optional[int] = None,
                 emit_watermarks: bool = False,
                 watermark_lag_us: int = 0,
                 max_inflight_chunks: int = 16,
                 splits: Optional[list] = None,
                 name: Optional[str] = None):
        """Single-connector form (connector=...) or split-assigned form
        (splits=[(split_id, connector), ...] — reference: the actor's
        split assignment from SourceManager)."""
        self.source_id = source_id
        # catalog name for labelled per-split series + SHOW sources
        self.source_name = name or f"src{source_id}"
        if splits is None:
            splits = [(0, connector)]
        assert splits and all(c is not None for _, c in splits)
        self.splits = list(splits)
        # (epoch, {split_id: offset}) snapshots taken at each offset
        # commit — the broker retention plane's durable-floor source
        self.offset_history: list[tuple[int, dict]] = []
        self.connector = self.splits[0][1]
        self.schema = self.connector.schema
        self.barrier_queue = barrier_queue
        self.state_table = state_table
        self.rate_limit = rate_limit_rows_per_barrier
        self.identity = f"Source({source_id})"
        self.paused = False
        # Connector-declared watermarks (reference: WATERMARK FOR clause on
        # sources + WatermarkFilterExecutor). The connector computes them on
        # host (no device readback); the source emits after each chunk.
        def has_wm(c):
            # probe through split wrappers: the wrapper defines the
            # method unconditionally, the capability lives on the inner
            return hasattr(getattr(c, "inner", c), "current_watermark")
        self.emit_watermarks = emit_watermarks and all(
            has_wm(c) for _, c in self.splits)
        # watermark lag (reference: WATERMARK FOR ts AS ts - interval):
        # downstream lookback joins/windows need rows to outlive the raw
        # event-time frontier by their window span
        self.watermark_lag_us = watermark_lag_us
        self._last_wm: Optional[int] = None
        # Device-credit flow control (reference: permit-based exchange
        # channels, executor/exchange/permit.rs — bounded records in flight).
        # JAX dispatch is asynchronous: without a bound, the host enqueues
        # device programs far ahead of execution, queue depth explodes, and
        # every downstream consistency signal (telemetry readbacks, barrier
        # collection) lags unboundedly. The TPU runs programs in submission
        # order, so "chunk N's generator output is ready" implies every
        # program enqueued before it (the whole pipeline for chunk N-1) has
        # executed: one token per emitted chunk bounds TOTAL pipeline depth.
        self.max_inflight_chunks = max_inflight_chunks
        self._tokens: deque = deque()
        # reference stream_source_output_rows_counts (streaming_stats.rs:214).
        # Semantics: host-known emitted rows — exact when the connector
        # exposes `last_chunk_rows`, padded chunk capacity otherwise (no
        # per-chunk d2h sync is allowed to count device-visible rows).
        from ..utils.metrics import GLOBAL_METRICS
        self._rows_metric = GLOBAL_METRICS.counter(
            "stream_source_output_rows_counts", source_id=str(source_id))
        # owning actor's ActorObs (stream/monitor.py): time parked on the
        # barrier queue is ALIGN wait (idle between intervals), not
        # barrier-processing work — without this the whole inter-barrier
        # idle time would be misattributed to the persist phase
        self.obs = None

    async def _get_barrier(self):
        obs = self.obs
        if obs is None:
            return await self.barrier_queue.get()
        t0 = time.monotonic_ns()
        b = await self.barrier_queue.get()
        obs.add_input_wait(time.monotonic_ns() - t0)
        return b

    async def _acquire_credit(self) -> None:
        # Block (in a worker thread, keeping the event loop live) rather
        # than poll `is_ready`: passive polling can see completion
        # events late, which would gate the whole pipeline on the poll
        # period. A blocking wait returns as soon as the oldest
        # in-flight chunk's pipeline has really run.
        while len(self._tokens) >= self.max_inflight_chunks:
            token = self._tokens.popleft()
            await asyncio.to_thread(token.block_until_ready)

    def _recover_offset(self) -> bool:
        """Seek every owned split to its committed offset; True if any
        split resumed from one."""
        if self.state_table is None:
            return False
        # keyed by SPLIT ID: split ids are stable across rebuilds while
        # actor ids are not (rescale/recovery reallocate them) — a
        # re-assigned split finds its committed offset wherever it lands
        # (reference: state_table_handler.rs keyed by split id)
        resumed = False
        for sid, conn in self.splits:
            row = self.state_table.get_row((sid,))
            if row is not None:
                conn.seek(row[1])
                resumed = True
        return resumed

    def _watermark(self):
        """The watermark message of the owned splits' current offsets
        (safe frontier = MIN over them: a lagging split may still hold
        earlier rows), or None while it has not advanced."""
        wm = min(c.current_watermark()
                 for _, c in self.splits) - self.watermark_lag_us
        if self._last_wm is not None and wm <= self._last_wm:
            return None
        self._last_wm = wm
        from ..common.types import DataType
        from .message import Watermark
        return Watermark(self.splits[0][1].watermark_col,
                         DataType.TIMESTAMP, wm)

    def _commit_offset(self, barrier: Barrier) -> None:
        self._update_split_metrics()
        if self.state_table is None:
            return
        # upsert (split_id, next_offset) per owned split; offsets ride
        # the same epoch commit as operator state => exactly-once resume
        self.state_table.write_chunk_rows(
            [(0, (sid, conn.offset)) for sid, conn in self.splits])
        self.state_table.commit(barrier.epoch.curr)
        # Committed-offset history for the broker retention plane: the
        # rows above are STAGED at barrier.epoch.prev (StateTable.commit
        # writes at the pre-advance epoch), so they are durable once the
        # store's committed epoch reaches it. The retention manager takes
        # the newest snapshot at-or-below the committed epoch — never the
        # live connector offset, which runs ahead of the checkpoint.
        self.offset_history.append(
            (barrier.epoch.prev,
             {sid: int(conn.offset) for sid, conn in self.splits}))
        del self.offset_history[:-16]

    # ------------------------------------------------- split observability
    def _update_split_metrics(self) -> None:
        """Per-split offset/lag gauges, refreshed at barrier cadence
        (host-known values only — lag reads the connector's CACHED
        broker high watermark, never an RPC on the barrier path)."""
        from ..utils.metrics import GLOBAL_METRICS
        for sid, conn in self.splits:
            GLOBAL_METRICS.gauge(
                "source_split_offset", source=self.source_name,
                split=str(sid)).set(float(conn.offset))
            lag = getattr(conn, "lag_rows", None)
            if lag is not None:
                GLOBAL_METRICS.gauge(
                    "source_lag_rows", source=self.source_name,
                    split=str(sid)).set(float(lag()))

    def remove_split_metrics(self) -> None:
        """Deployment teardown: labelled per-split series die with the
        executor (the per-actor streaming-series rule)."""
        from ..utils.metrics import GLOBAL_METRICS
        for sid, _conn in self.splits:
            GLOBAL_METRICS.remove("source_split_offset",
                                  source=self.source_name, split=str(sid))
            GLOBAL_METRICS.remove("source_lag_rows",
                                  source=self.source_name, split=str(sid))

    def split_report(self) -> list[tuple]:
        """SHOW sources rows: (split_id, offset, lag-or-None)."""
        out = []
        for sid, conn in self.splits:
            lag = getattr(conn, "lag_rows", None)
            out.append((sid, conn.offset,
                        lag() if lag is not None else None))
        return out

    def _adopt_splits(self, assigned) -> None:
        """AddSplitsMutation arrival (a barrier): take ownership of
        newly-discovered splits. A split already owned is skipped
        (mutation replay across recovery); a split with a committed
        offset resumes there (a re-assigned split finds its state
        wherever it lands, the `_recover_offset` rule). Offsets for the
        new splits commit from THIS barrier on."""
        for sid, conn in assigned:
            if any(s == sid for s, _ in self.splits):
                continue
            if self.state_table is not None:
                row = self.state_table.get_row((sid,))
                if row is not None:
                    conn.seek(row[1])
            self.splits.append((sid, conn))
            # watermark safety: the frontier is a MIN over owned splits,
            # so a split that cannot report one disables emission rather
            # than silently over-advancing it
            if self.emit_watermarks and not hasattr(
                    getattr(conn, "inner", conn), "current_watermark"):
                self.emit_watermarks = False

    async def execute(self):
        # First message is always the Initial barrier (reference: actors are
        # built, then the Add/Initial barrier arrives before any data).
        barrier = await self._get_barrier()
        if self.state_table is not None:
            self.state_table.init_epoch(barrier.epoch.curr)
        # recover on the FIRST observed barrier whatever its kind: a
        # rescale/MV-on-MV rebuild joins a running epoch stream where the
        # Initial barrier happened long ago
        resumed = self._recover_offset()
        # the first barrier can already carry mutations (a split
        # discovered between build and the first injection must not be
        # dropped — the enumerator will never re-announce it)
        self._apply_mutation(barrier)
        yield barrier
        if resumed and self.emit_watermarks:
            # A resumed source re-states the watermark of its committed
            # offsets BEFORE its first chunk. The one it sent after the
            # last committed chunk was held by its consumers, not stored
            # (a join evicts by it during its NEXT apply): without it the
            # first chunk after a restart is applied with no cleaning
            # watermark, a windowed join's pool then holds one interval
            # more than it ever does in steady state, and q7's 2^19 pool
            # crossed its growth threshold there by the luck of the
            # checkpoint count (a 2^20 recompile inside a timed recovery).
            wm = self._watermark()
            if wm is not None:
                yield wm

        sent_this_interval = 0
        while True:
            if self.paused:
                barrier = await self._get_barrier()
            else:
                try:
                    barrier = self.barrier_queue.get_nowait()
                except asyncio.QueueEmpty:
                    barrier = None
            if barrier is not None:
                self._apply_mutation(barrier)
                self._commit_offset(barrier)
                sent_this_interval = 0
                yield barrier
                if barrier.is_stop(self.source_id):
                    return
                continue
            if self.rate_limit is not None and sent_this_interval >= self.rate_limit:
                # throttled: wait for the next barrier
                barrier = await self._get_barrier()
                self._apply_mutation(barrier)
                self._commit_offset(barrier)
                sent_this_interval = 0
                yield barrier
                if barrier.is_stop(self.source_id):
                    return
                continue
            if all(getattr(c, "exhausted", False)
                   for _, c in self.splits):
                # finite connectors (ArrowSource): nothing to read until
                # something external appends — block on barriers instead
                # of busy-spinning empty chunks through the dataflow
                barrier = await self._get_barrier()
                self._apply_mutation(barrier)
                self._commit_offset(barrier)
                sent_this_interval = 0
                yield barrier
                if barrier.is_stop(self.source_id):
                    return
                continue
            await self._acquire_credit()
            # round-robin across owned splits (reference: the reader
            # stream interleaves its assigned splits), skipping splits
            # with nothing to read — a lagging split must not starve the
            # rest behind empty chunks (all-exhausted was handled above)
            self._rr = getattr(self, "_rr", 0)
            conn = self.splits[self._rr % len(self.splits)][1]
            self._rr += 1
            for _ in range(len(self.splits) - 1):
                if not getattr(conn, "exhausted", False):
                    break
                conn = self.splits[self._rr % len(self.splits)][1]
                self._rr += 1
            chunk = conn.next_chunk()
            self._tokens.append(chunk.columns[0].data)
            # Visible rows come from HOST knowledge only: a d2h sync per
            # chunk would serialise the steady state with dispatch. A
            # connector that tracks its own fill exposes `last_chunk_rows`
            # (generators fill every chunk, so capacity is exact for them);
            # otherwise padded capacity is used, which OVER-counts partial
            # chunks by their padding — the conservative direction for the
            # rate limiter, and documented in the metric name below.
            rows_host = getattr(conn, "last_chunk_rows", None)
            if rows_host is None:
                rows_host = chunk.capacity
            self._rows_metric.inc(rows_host)
            if self.rate_limit is not None:
                sent_this_interval += rows_host
            yield chunk
            if self.emit_watermarks:
                wm = self._watermark()
                if wm is not None:
                    yield wm
            # let barriers/other actors in
            await asyncio.sleep(0)

    def _apply_mutation(self, barrier: Barrier) -> None:
        if barrier.is_pause():
            self.paused = True
        from .message import AddSplitsMutation, ResumeMutation
        if isinstance(barrier.mutation, ResumeMutation):
            self.paused = False
        if isinstance(barrier.mutation, ThrottleMutation):
            for actor_id, limit in barrier.mutation.limits:
                if actor_id == self.source_id:
                    self.rate_limit = limit
        if isinstance(barrier.mutation, AddSplitsMutation):
            self._adopt_splits(
                barrier.mutation.assignments.get(self.source_id, ()))
