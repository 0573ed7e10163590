"""HopWindow executor — stateless sliding-window expansion.

Reference: src/stream/src/executor/hop_window.rs:386 — each input row is
emitted once per window it falls into (window_size / window_slide copies)
with computed window_start / window_end columns appended; pure map, no
state. The whole expansion is ONE jitted program emitting ONE chunk of
static capacity n_windows * input_capacity (copy k shifts the aligned
window start back by k slides). One big program beats n_windows small ones:
per-program dispatch overhead is the dominant cost for sub-ms kernels,
and downstream executors amortize their own per-chunk
overhead over n_windows times more rows.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from ..common.chunk import Column, StreamChunk
from ..common.types import DataType, Field, Schema
from .executor import Executor, StatelessUnaryExecutor
from .message import Watermark
from ..ops.jit_state import jit_state


class HopWindowExecutor(StatelessUnaryExecutor):
    # Mesh-chain fusion: hollow hop passes raw chunks through; the K-fold
    # expansion runs per-shard inside the downstream fused program (see
    # ProjectExecutor — same contract; hop is row-wise per input row, the
    # K copies of a row stay on the producing shard until the shuffle).
    mesh_hollow = False

    def mesh_prelude_fn(self):
        return self._step_impl

    def __init__(self, input: Executor, time_col: int,
                 window_slide_us: int, window_size_us: int,
                 output_indices: Sequence[int] | None = None):
        super().__init__(input)
        assert window_size_us > 0 and window_slide_us > 0
        self.time_col = time_col
        self.slide = window_slide_us
        self.size = window_size_us
        self.n_windows = math.ceil(window_size_us / window_slide_us)
        in_fields = list(input.schema)
        full_fields = in_fields + [Field("window_start", DataType.TIMESTAMP),
                                   Field("window_end", DataType.TIMESTAMP)]
        ws_full, we_full = len(in_fields), len(in_fields) + 1
        # output pruning (reference hop_window.rs applies output_indices);
        # window_start_idx / window_end_idx are OUTPUT positions (-1 = pruned)
        self.output_indices = (tuple(output_indices) if output_indices is not None
                               else tuple(range(len(full_fields))))
        self._ws_full, self._we_full = ws_full, we_full
        self.schema = Schema(tuple(full_fields[i] for i in self.output_indices))
        def _outpos(full_idx: int) -> int:
            return self.output_indices.index(full_idx) if full_idx in self.output_indices else -1
        self.window_start_idx = _outpos(ws_full)
        self.window_end_idx = _outpos(we_full)
        self.identity = (f"HopWindow(col={time_col}, slide={window_slide_us}us, "
                         f"size={window_size_us}us)")
        self._step = jit_state(self._step_impl, name="hop_window_step")

    def _step_impl(self, chunk: StreamChunk) -> StreamChunk:
        K = self.n_windows
        ts = chunk.columns[self.time_col].data
        ks = jnp.repeat(jnp.arange(K, dtype=ts.dtype), chunk.capacity)
        tiled = lambda a: jnp.tile(a, K)
        ts_t = tiled(ts)
        # aligned window containing ts, shifted back k slides. floor-div
        # handles negative timestamps correctly (pre-epoch event time).
        ws = (jnp.floor_divide(ts_t, self.slide) - ks) * self.slide
        we = ws + self.size
        # row in window iff ws <= ts < we; ws <= ts always holds, the upper
        # bound can fail when slide does not divide size
        vis = tiled(chunk.vis) & (ts_t < we)
        full = tuple(
            Column(tiled(c.data), None if c.valid is None else tiled(c.valid))
            for c in chunk.columns) + (Column(ws), Column(we))
        cols = tuple(full[i] for i in self.output_indices)
        return StreamChunk(cols, tiled(chunk.ops), vis, self.schema)

    async def execute(self):
        async for msg in self.input.execute():
            if isinstance(msg, StreamChunk):
                if self.mesh_hollow:
                    yield msg       # expansion runs fused downstream
                    continue
                yield self._step(msg)
            elif isinstance(msg, Watermark):
                wm = self.map_watermark(msg)
                if wm is not None:
                    yield wm
            else:
                yield msg

    def map_watermark(self, wm: Watermark):
        if wm.col_idx == self.time_col:
            # a watermark on event time implies one on window_start lagged
            # by the full window size (reference derives the same bound)
            if self.window_start_idx < 0:
                return None
            ws = (wm.val // self.slide - (self.n_windows - 1)) * self.slide
            return Watermark(self.window_start_idx, DataType.TIMESTAMP, ws)
        # input-column watermarks remap through the output pruning
        if wm.col_idx in self.output_indices:
            return wm.with_idx(self.output_indices.index(wm.col_idx))
        return None
