"""Small stream executors: Values, Union, Expand, NoOp, FlowControl,
WatermarkFilter.

Reference: src/stream/src/executor/{values.rs, union.rs, expand.rs,
no_op.rs, flow_control.rs, watermark_filter.rs}.
"""

from __future__ import annotations

import asyncio
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import Column, StreamChunk, OP_INSERT
from ..common.types import DataType, Schema
from ..state.state_table import StateTable
from .exchange import Channel, MergeExecutor
from .executor import Executor, StatelessUnaryExecutor
from .message import Barrier, BarrierKind, Watermark
from ..ops.jit_state import jit_state


class ValuesExecutor(Executor):
    """Emit a fixed set of rows once, after the Initial barrier
    (reference values.rs — the VALUES clause of a streaming insert)."""

    def __init__(self, schema: Schema, rows: Sequence[tuple],
                 barrier_queue: "asyncio.Queue[Barrier]"):
        self.schema = schema
        self.rows = list(rows)
        self.barrier_queue = barrier_queue
        self.identity = f"Values({len(self.rows)} rows)"
        self.pk_indices = ()

    async def execute(self):
        barrier = await self.barrier_queue.get()
        yield barrier
        if self.rows:
            cols = [np.asarray([r[j] for r in self.rows],
                               dtype=f.data_type.np_dtype)
                    for j, f in enumerate(self.schema)]
            yield StreamChunk.from_numpy(self.schema, cols)
        while True:
            barrier = await self.barrier_queue.get()
            yield barrier
            if barrier.mutation is not None and barrier.is_stop_any():
                return


class UnionExecutor(MergeExecutor):
    """N-way stream union = barrier-aligned merge (reference union.rs is
    merge without the exchange); schemas must match."""

    def __init__(self, channels: Sequence[Channel], schema: Schema):
        super().__init__(channels, schema)
        self.identity = f"Union({len(self.channels)})"


class NoOpExecutor(StatelessUnaryExecutor):
    """Identity passthrough (reference no_op.rs — plan-shape padding)."""

    identity = "NoOp"
    # Mesh-chain fusion: identity is trivially safe per-shard, so NoOp
    # plan padding must not break the prelude-capable producer walk
    # (q5's source -> project -> NoOp leg).
    mesh_hollow = False

    def mesh_prelude_fn(self):
        return lambda chunk: chunk

    def map_chunk(self, chunk: StreamChunk) -> StreamChunk:
        return chunk


class ExpandExecutor(StatelessUnaryExecutor):
    """Grouping-sets row multiplication (reference expand.rs): each input
    row is emitted once per subset, with non-subset columns NULLed and a
    flag column identifying the subset. One jitted program emits one chunk
    of capacity n_subsets * input_capacity."""

    def __init__(self, input: Executor, column_subsets: Sequence[Sequence[int]]):
        super().__init__(input)
        self.subsets = [tuple(s) for s in column_subsets]
        in_fields = list(input.schema)
        self.schema = Schema(tuple(
            in_fields + [type(in_fields[0])("flag", DataType.INT64)]))
        self.identity = f"Expand({len(self.subsets)} subsets)"
        self._step = jit_state(self._step_impl, name="expand_step")

    def _step_impl(self, chunk: StreamChunk) -> StreamChunk:
        K = len(self.subsets)
        N = chunk.capacity

        def tiled(a):
            return jnp.tile(a, K)

        cols = []
        for j, c in enumerate(chunk.columns):
            data = tiled(c.data)
            valid = tiled(c.valid_mask())
            # NULL out columns not in the subset for each copy
            keep = np.zeros(K * N, dtype=bool)
            for k, subset in enumerate(self.subsets):
                if j in subset:
                    keep[k * N:(k + 1) * N] = True
            valid = valid & jnp.asarray(keep)
            cols.append(Column(data, valid))
        flag = jnp.repeat(jnp.arange(K, dtype=jnp.int64), N)
        cols.append(Column(flag))
        return StreamChunk(tuple(cols), tiled(chunk.ops),
                           tiled(chunk.vis), self.schema)

    def map_chunk(self, chunk: StreamChunk) -> StreamChunk:
        return self._step(chunk)


class FlowControlExecutor(Executor):
    """Rate limiter (reference flow_control.rs): a token bucket of
    `rows_per_sec`; a chunk that exceeds the available tokens WAITS in
    place, which backpressures everything behind it (barriers included) —
    messages are never reordered across epochs, matching the reference's
    in-order await on its rate limiter. Throttle mutations adjust the
    rate at runtime."""

    def __init__(self, input: Executor, actor_id: int,
                 rows_per_sec: Optional[int]):
        self.input = input
        self.actor_id = actor_id
        self.schema = input.schema
        self.pk_indices = input.pk_indices
        self.limit = rows_per_sec
        self.identity = f"FlowControl({rows_per_sec}/s)"

    async def execute(self):
        import time

        from .message import ThrottleMutation
        tokens = 0.0
        last = time.monotonic()
        async for msg in self.input.execute():
            if isinstance(msg, StreamChunk) and self.limit is not None:
                need = msg.num_rows_host()
                while True:
                    if self.limit == 0:
                        # rate 0 pauses the stream IN PLACE (barriers wait
                        # behind the chunk; to pause without stalling
                        # checkpoints use a PauseMutation at the source)
                        await asyncio.sleep(0.05)
                        continue
                    now = time.monotonic()
                    tokens = min(tokens + (now - last) * self.limit,
                                 float(max(self.limit, need)))
                    last = now
                    if tokens >= need:
                        tokens -= need
                        break
                    await asyncio.sleep((need - tokens) / self.limit)
                yield msg
            elif isinstance(msg, Barrier):
                if isinstance(msg.mutation, ThrottleMutation):
                    for aid, lim in msg.mutation.limits:
                        if aid == self.actor_id:
                            self.limit = lim
                yield msg
            else:
                yield msg


class WatermarkFilterExecutor(Executor):
    """Generate watermarks from an event-time column and filter late rows
    (reference watermark_filter.rs): wm = max(seen ts) - lag; rows with
    ts < wm are dropped; the current wm per vnode persists in a state
    table so recovery resumes monotonically."""

    def __init__(self, input: Executor, time_col: int, lag_us: int = 0,
                 state_table: Optional[StateTable] = None):
        self.input = input
        self.schema = input.schema
        self.pk_indices = input.pk_indices
        self.time_col = time_col
        self.lag_us = lag_us
        self.state_table = state_table
        self.identity = f"WatermarkFilter(col={time_col}, lag={lag_us}us)"
        self._wm: Optional[int] = None
        self._max_dev = None
        self._step = jit_state(self._step_impl, name="watermark_filter_step")

    def _step_impl(self, chunk: StreamChunk, cur_max):
        ts = chunk.columns[self.time_col].data
        # filter against the watermark BEFORE this chunk, then advance:
        # in-chunk disorder must not retroactively drop rows the emitted
        # watermark still admits (reference filters at the current wm)
        keep = chunk.vis & (ts >= cur_max - self.lag_us)
        seen = jnp.where(chunk.vis, ts, cur_max)
        new_max = jnp.maximum(cur_max, jnp.max(seen))
        return StreamChunk(chunk.columns, chunk.ops, keep,
                           chunk.schema), new_max

    async def execute(self):
        first = True
        async for msg in self.input.execute():
            if isinstance(msg, StreamChunk):
                if self._max_dev is None:
                    seed = getattr(self, "_recovered_max", None)
                    self._max_dev = jnp.asarray(
                        seed if seed is not None else -(1 << 62),
                        dtype=jnp.int64)
                out, self._max_dev = self._step(msg, self._max_dev)
                yield out
            elif isinstance(msg, Barrier):
                if first or msg.kind is BarrierKind.INITIAL:
                    first = False
                    if self.state_table is not None:
                        self.state_table.init_epoch(msg.epoch.curr)
                        row = self.state_table.get_row((0,))
                        if row is not None:
                            self._wm = row[1]
                            # the persisted value is the WATERMARK (already
                            # lag-subtracted); the running max must be
                            # wm + lag or recovery would re-admit rows
                            # below the emitted watermark
                            self._max_dev = None
                            self._recovered_max = self._wm + self.lag_us
                    yield msg
                    continue
                # ONE fetch per barrier (a blocking fetch serialises
                # with dispatch; lag-free sources avoid even this one)
                if self._max_dev is not None:
                    cur = int(np.asarray(self._max_dev))
                    wm = cur - self.lag_us
                    if self._wm is None or wm > self._wm:
                        self._wm = wm
                        yield Watermark(self.time_col,
                                        self.schema[self.time_col].data_type,
                                        wm)
                if self.state_table is not None:
                    if self._wm is not None:
                        self.state_table.write_chunk_rows(
                            [(int(OP_INSERT), (0, self._wm))])
                    self.state_table.commit(msg.epoch.curr)
                yield msg
            else:
                yield msg
