"""Sort executor (emit-on-window-close) — watermark-driven buffer flush.

Reference: src/stream/src/executor/sort.rs + sort_buffer.rs — rows buffer
in a state table keyed by the sort (event-time) column; when the watermark
advances, all rows with sort_key <= watermark are emitted IN ORDER and
deleted from the buffer. This is the EOWC building block (append-only
output, late rows already filtered by the upstream watermark filter).

TPU re-design: the buffer is a fixed-capacity device row store (columns
[C] + live mask). Appending a chunk is one jitted compaction-scatter; the
watermark flush is a second jitted step that selects ripe rows, sorts them
by the sort key, emits them as an ordered chunk, and compacts the
survivors to the front. Overflow (buffer full) is counted on device and
fail-stopped at the barrier, like every bounded structure here.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import Column, StreamChunk, OP_INSERT, op_sign
from ..ops.hash_table import stable_lexsort
from ..state.state_table import StateTable
from ..utils.d2h import fetch_small, off_loop
from .executor import Executor, StatefulUnaryExecutor
from .message import Barrier, Watermark
from ..ops.jit_state import jit_state


class SortExecutor(StatefulUnaryExecutor):
    """Append-only EOWC sort on an int-comparable column."""

    def __init__(self, input: Executor, sort_col: int,
                 capacity: int = 1 << 14,
                 state_table: Optional[StateTable] = None,
                 watchdog_interval: Optional[int] = 1):
        self.input = input
        self.schema = input.schema
        self.pk_indices = input.pk_indices
        self.sort_col = sort_col
        self.capacity = capacity
        self.identity = f"Sort(col={sort_col}, eowc)"
        self._col_dtypes = tuple(f.data_type.jnp_dtype for f in self.schema)
        C = capacity
        self.rows = tuple(jnp.zeros(C, dtype=dt) for dt in self._col_dtypes)
        self.live = jnp.zeros(C, dtype=bool)
        self._pending_wm: Optional[int] = None
        # buffer arrays + errs are threaded and re-bound at both call
        # sites; nothing aliases them between steps: donate
        self._append = jit_state(self._append_impl,
                                 donate_argnums=(0, 1, 2),
                                 name="sort_append")
        self._flush_ripe = jit_state(self._flush_ripe_impl,
                                     donate_argnums=(0, 1),
                                     name="sort_flush_ripe")
        self._errs_dev = jnp.zeros((), dtype=jnp.int32)
        self._init_stateful(state_table, watchdog_interval)

    def fence_tokens(self) -> list:
        return [self.live] + super().fence_tokens()

    # --------------------------------------------------------------- steps
    def _append_impl(self, rows, live, errs, chunk: StreamChunk):
        C = self.capacity
        act = chunk.vis & (op_sign(chunk.ops) > 0)
        n_viol = jnp.sum((chunk.vis & (op_sign(chunk.ops) < 0))
                         .astype(jnp.int32))
        # free slots compacted: rank free slots and incoming rows
        free_rank = jnp.cumsum((~live).astype(jnp.int32)) - 1
        slot_of_rank = jnp.zeros(C, dtype=jnp.int32).at[
            jnp.where(~live, free_rank, C)].set(
                jnp.arange(C, dtype=jnp.int32), mode="drop")
        in_rank = jnp.cumsum(act.astype(jnp.int32)) - 1
        n_free = jnp.sum((~live).astype(jnp.int32))
        ok = act & (in_rank < n_free)
        n_over = jnp.sum(act.astype(jnp.int32)) - jnp.sum(
            ok.astype(jnp.int32))
        tgt = jnp.where(ok, slot_of_rank[jnp.clip(in_rank, 0, C - 1)], C)
        new_rows = tuple(
            r.at[tgt].set(c.data.astype(r.dtype), mode="drop")
            for r, c in zip(rows, chunk.columns))
        new_live = live.at[tgt].set(True, mode="drop")
        return new_rows, new_live, errs + n_viol + n_over

    def _flush_ripe_impl(self, rows, live, wm):
        """Emit rows with sort_key <= wm in sort order; keep the rest."""
        C = self.capacity
        key = rows[self.sort_col]
        ripe = live & (key <= wm)
        # order ripe rows by key (stable), invalid last
        order = stable_lexsort((jnp.arange(C), key, ~ripe))
        out_cols = tuple(r[order] for r in rows)
        out_vis = ripe[order]
        keep = live & ~ripe
        return out_cols, out_vis, rows, keep

    # --------------------------------------------------------------- hooks
    def map_watermark(self, wm: Watermark):
        if wm.col_idx == self.sort_col:
            self._pending_wm = wm.val
            # a watermark alone ripens buffered rows (e.g. right after
            # recovery): force the barrier flush even with no new chunks
            self._applied_since_flush = True
            return wm
        return None

    async def check_watchdog(self) -> None:
        n = int(await off_loop(fetch_small, self._errs_dev))
        if n:
            raise RuntimeError(
                f"sort buffer overflow or append-only violation ({n} "
                f"rows, capacity {self.capacity})")

    def flush(self) -> Optional[StreamChunk]:
        if self._pending_wm is None:
            return None
        wm = self._pending_wm
        self._pending_wm = None
        cols, vis, self.rows, self.live = self._flush_ripe(
            self.rows, self.live, wm)
        ops = jnp.full(self.capacity, OP_INSERT, dtype=jnp.int8)
        return StreamChunk(tuple(Column(c) for c in cols), ops, vis,
                           self.schema)

    def on_chunk(self, chunk: StreamChunk):
        self.rows, self.live, self._errs_dev = self._append(
            self.rows, self.live, self._errs_dev, chunk)
        self._dirty_persist = True
        return None

    def persist(self, barrier: Barrier, flushed) -> None:
        if self.state_table is None:
            return
        if getattr(self, "_dirty_persist", False) or flushed is not None:
            self._dirty_persist = False
            # snapshot the live buffer through the columnar batch path
            # (the same hot path as hash_agg persistence)
            cols = [np.asarray(r) for r in self.rows]
            ops = np.zeros(self.capacity, dtype=np.int8)  # OP_INSERT
            self.state_table.write_chunk_columns(
                ops, cols, np.asarray(self.live))
            if flushed is not None:
                # tombstone rows flushed out this epoch
                del_ops = np.ones(flushed.capacity, dtype=np.int8)
                self.state_table.write_chunk_columns(
                    del_ops, [np.asarray(c.data) for c in flushed.columns],
                    np.asarray(flushed.vis))
        self.state_table.commit(barrier.epoch.curr)

    def recover_state(self, epoch: int) -> None:
        rows = [row for _, row in self.state_table.iter_all()]
        if not rows:
            return
        cap = max(64, 1 << int(np.ceil(np.log2(len(rows) + 1))))
        n = len(rows)
        vis = np.arange(cap) < n
        arrays = [np.resize(np.asarray([r[j] for r in rows]), cap)
                  for j in range(len(self._col_dtypes))]
        chunk = StreamChunk(
            tuple(Column(jnp.asarray(a)) for a in arrays),
            jnp.full(cap, OP_INSERT, dtype=jnp.int8),
            jnp.asarray(vis), self.schema)
        self.on_chunk(chunk)
        self._applied_since_flush = False
