"""Executor base — async message-stream transforms.

Reference: the `Executor` trait (src/stream/src/executor/mod.rs:157-216):
an executor is a single-consumer stream of Message{Chunk,Barrier,Watermark}
with a schema and identity; executors wrap their inputs, barriers flow
through every executor in order. Here an executor is an async generator
(`execute()`); the device work inside stateful executors is a pure jitted
step function — the async host layer never holds the GIL against XLA.
"""

from __future__ import annotations

from typing import AsyncIterator, Optional, Sequence

from ..common.chunk import StreamChunk
from ..common.types import Schema
from .message import Barrier, BarrierKind, Message, Watermark


class Executor:
    schema: Schema
    identity: str = "Executor"
    pk_indices: tuple[int, ...] = ()

    def execute(self) -> AsyncIterator[Message]:
        raise NotImplementedError

    def fence_tokens(self) -> list:
        """Device arrays the epoch fence must wait on at a barrier.

        Per-chunk programs are covered by the last chunk flowing to the
        actor, but stateful executors dispatch MORE device work while
        handling the barrier itself (flush/evict/purge/persist views) after
        yielding their last chunk; the actor blocks on these tokens (no
        data transfer) before reporting the barrier collected, so an epoch
        is only 'collected' once all its device programs have executed.
        Default: delegate to `input`(s); stateful executors add their
        current state root."""
        toks: list = []
        inp = getattr(self, "input", None)
        if inp is not None:
            toks.extend(inp.fence_tokens())
        for i in getattr(self, "inputs", ()) or ():
            toks.extend(i.fence_tokens())
        return toks

    def __repr__(self):
        return self.identity


def gather_fence_tokens(node) -> list:
    """Duck-typed fence-token walk for arbitrary chain heads (sinks and
    test harness wrappers often wrap an Executor without subclassing)."""
    ft = getattr(node, "fence_tokens", None)
    if callable(ft):
        return ft()
    toks: list = []
    inp = getattr(node, "input", None)
    if inp is not None:
        toks.extend(gather_fence_tokens(inp))
    for i in getattr(node, "inputs", ()) or ():
        toks.extend(gather_fence_tokens(i))
    return toks


class StatefulUnaryExecutor(Executor):
    """Template for single-input stateful executors — holds the barrier
    protocol invariants in ONE place (reference: every stateful executor
    repeats this sequence; here hash_agg-style control flow is shared):

      first/INITIAL barrier  -> init_epoch + recover, no flush
      data chunk             -> on_chunk (device dispatch, no transfers)
      barrier                -> watchdog fail-stop (awaited: the loop
                                is free while the device reaches the
                                pack) BEFORE the checkpoint commits,
                                then flush -> persist -> emit

    Subclasses implement the hooks; `watchdog_interval` must be 1 (check
    every barrier) or None (transfer-free mode, no d2h fetch ever — see
    HashAggExecutor for why that mode exists)."""

    state_table = None

    def _init_stateful(self, state_table, watchdog_interval) -> None:
        if watchdog_interval not in (None, 1):
            raise ValueError(
                "watchdog_interval must be 1 (check before every checkpoint "
                "commit) or None (transfer-free mode): any lag would let a "
                "checkpoint commit unverified state")
        self.state_table = state_table
        self.watchdog_interval = watchdog_interval
        self._applied_since_flush = False

    # ------------------------------------------------------------- hooks
    def on_chunk(self, chunk: StreamChunk) -> Optional[StreamChunk]:
        """Apply a chunk; return an output chunk to emit now (or None)."""
        raise NotImplementedError

    async def check_watchdog(self) -> None:
        """Fetch device error counters; raise to fail-stop pre-commit.
        The pack is dispatched here, its wait awaited off the loop
        (`utils/d2h.py` `off_loop(fetch_small, pack)`)."""

    def flush(self) -> Optional[StreamChunk]:
        """Barrier-time changelog emission (None = nothing to emit)."""
        return None

    def persist(self, barrier: Barrier, flushed: Optional[StreamChunk]):
        """Write state rows + commit the state table at this barrier; an
        `async def` override is awaited."""
        if self.state_table is not None:
            self.state_table.commit(barrier.epoch.curr)

    def recover_state(self, epoch: int) -> None:
        """Rebuild device state from the state table (INITIAL barrier)."""

    def on_clean_barrier(self, barrier: Barrier) -> None:
        """Post-persist barrier work (eviction/purge/rebuild)."""

    def map_watermark(self, wm: Watermark) -> Optional[Watermark]:
        return wm

    # ---------------------------------------------------------- template
    async def execute(self):
        first = True
        async for msg in self.input.execute():
            if isinstance(msg, StreamChunk):
                out = self.on_chunk(msg)
                self._applied_since_flush = True
                if out is not None:
                    yield out
            elif isinstance(msg, Barrier):
                if first or msg.kind is BarrierKind.INITIAL:
                    first = False
                    if self.state_table is not None:
                        self.state_table.init_epoch(msg.epoch.curr)
                        self.recover_state(msg.epoch.curr)
                    yield msg
                    continue
                stopping = msg.mutation is not None and msg.is_stop_any()
                if self.watchdog_interval and (
                        stopping or self._applied_since_flush):
                    await self.check_watchdog()
                flushed = None
                if self._applied_since_flush:
                    self._applied_since_flush = False
                    flushed = self.flush()
                pending = self.persist(msg, flushed)
                if pending is not None:
                    # a persist that hands its writes to the checkpoint's
                    # uploader (utils/d2h.py defer_prefix_flush)
                    await pending
                self.on_clean_barrier(msg)
                if flushed is not None:
                    yield flushed
                yield msg
            else:
                out = self.map_watermark(msg)
                if out is None:
                    continue
                for w in (out if isinstance(out, list) else [out]):
                    yield w


class StatelessUnaryExecutor(Executor):
    """Common shape: map chunks, forward barriers/watermarks."""

    def __init__(self, input: Executor):
        self.input = input
        self.schema = input.schema
        self.pk_indices = input.pk_indices

    def map_chunk(self, chunk: StreamChunk) -> Optional[StreamChunk]:
        raise NotImplementedError

    def map_watermark(self, wm: Watermark) -> Optional[Watermark]:
        return wm

    def on_barrier(self, barrier: Barrier) -> None:
        pass

    async def execute(self):
        async for msg in self.input.execute():
            if isinstance(msg, StreamChunk):
                out = self.map_chunk(msg)
                if out is not None:
                    yield out
            elif isinstance(msg, Barrier):
                self.on_barrier(msg)
                yield msg
            else:
                wm = self.map_watermark(msg)
                if wm is None:
                    continue
                for w in (wm if isinstance(wm, list) else [wm]):
                    yield w
