"""Append-only dedup executor.

Reference: src/stream/src/executor/dedup/append_only_dedup.rs — emit only
the first row seen for each dedup-key; later duplicates are dropped. Input
must be append-only (the reference builds this only under append-only
plans); delete-like rows are counted on device and fail-stopped at the
barrier, before the epoch's checkpoint commits.

TPU re-design: the seen-key set is the open-addressing `HashTable` in HBM.
One jitted step per chunk: probe (which keys already existed), insert, and
keep exactly the first in-chunk occurrence of each new key (segment-min of
row ids per slot). Keys newly seen since the last checkpoint are tracked in
a device bitmap and compacted out once per barrier for the StateTable
(pk-only rows, like the reference's dedup state table).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import StreamChunk, OP_INSERT, op_sign
from ..ops.hash_table import HashTable, lookup, lookup_or_insert
from ..ops.jit_state import jit_state
from ..state.state_table import StateTable
from ..utils.d2h import fetch_small, off_loop
from .executor import Executor, StatefulUnaryExecutor
from .message import Barrier


class AppendOnlyDedupExecutor(StatefulUnaryExecutor):
    def __init__(self, input: Executor, dedup_key_indices: Sequence[int],
                 capacity: int = 1 << 16,
                 state_table: Optional[StateTable] = None,
                 watchdog_interval: Optional[int] = 1):
        self.input = input
        self.key_indices = tuple(dedup_key_indices)
        self.schema = input.schema
        self.pk_indices = self.key_indices
        self.capacity = capacity
        self.identity = f"AppendOnlyDedup(keys={self.key_indices})"
        self._key_dtypes = tuple(
            input.schema[i].data_type.jnp_dtype for i in self.key_indices)
        self.table = HashTable.empty(capacity, self._key_dtypes)
        self.fresh = jnp.zeros(capacity, dtype=bool)  # new since persist
        # table, fresh bitmap, and error accumulator are threaded (the
        # only refs are re-bound in on_chunk) — donate; _fresh_keys is a
        # read-only persistence view, never donated
        self._apply = jit_state(self._apply_impl, donate_argnums=(0, 1, 2),
                                name="dedup_apply")
        self._fresh_keys = jit_state(self._fresh_keys_impl,
                                     name="dedup_fresh_keys")
        self._errs_dev = jnp.zeros((), dtype=jnp.int32)
        self._init_stateful(state_table, watchdog_interval)

    def fence_tokens(self) -> list:
        return [self.table.keys[0]] + super().fence_tokens()

    def _apply_impl(self, table: HashTable, fresh, errs,
                    chunk: StreamChunk):
        # append-only contract: delete-like rows are a violation (counted
        # on device, fail-stopped pre-commit) and never touch the state
        active = chunk.vis & (op_sign(chunk.ops) > 0)
        n_viol = jnp.sum((chunk.vis & (op_sign(chunk.ops) < 0))
                         .astype(jnp.int32))
        key_cols = [chunk.columns[i].data for i in self.key_indices]
        N = chunk.capacity
        pre = lookup(table, key_cols, active)         # existing keys
        table2, slots, n_un = lookup_or_insert(table, key_cols, active)
        C = table2.capacity
        new = active & (pre < 0) & (slots >= 0)
        # first in-chunk occurrence per slot wins
        row_ids = jnp.arange(N, dtype=jnp.int32)
        seg = jnp.where(new, slots, C)
        first = jax.ops.segment_min(row_ids, seg, C + 1)
        keep = new & (first[jnp.clip(slots, 0, C)] == row_ids)
        fresh2 = fresh.at[seg].set(True, mode="drop")
        return table2, fresh2, errs + n_un + n_viol, keep

    def _fresh_keys_impl(self, table: HashTable, fresh):
        """Compact the fresh keys to the front (for persistence)."""
        C = table.capacity
        rank = jnp.cumsum(fresh.astype(jnp.int32)) - 1
        sel = jnp.zeros(C, dtype=jnp.int32).at[
            jnp.where(fresh, rank, C)].set(jnp.arange(C, dtype=jnp.int32),
                                           mode="drop")
        n = jnp.sum(fresh.astype(jnp.int32))
        return tuple(k[sel] for k in table.keys), n

    # -------------------------------------------------------------- hooks
    def on_chunk(self, chunk: StreamChunk) -> StreamChunk:
        self.table, self.fresh, self._errs_dev, keep = self._apply(
            self.table, self.fresh, self._errs_dev, chunk)
        return StreamChunk(chunk.columns, chunk.ops, keep, chunk.schema)

    async def check_watchdog(self) -> None:
        n = int(await off_loop(fetch_small, self._errs_dev))
        if n:
            raise RuntimeError(
                f"dedup overflow or append-only violation ({n} rows, "
                f"capacity {self.capacity})")

    def persist(self, barrier: Barrier, flushed) -> None:
        if self.state_table is None:
            return
        keys, n = self._fresh_keys(self.table, self.fresh)
        n = int(n)
        if n:
            keys_np = [np.asarray(k)[:n] for k in keys]
            rows = [(int(OP_INSERT), tuple(k[r].item() for k in keys_np))
                    for r in range(n)]
            self.state_table.write_chunk_rows(rows)
        self.fresh = jnp.zeros(self.capacity, dtype=bool)
        self.state_table.commit(barrier.epoch.curr)

    def recover_state(self, epoch: int) -> None:
        rows = [row for _, row in self.state_table.iter_all()]
        if not rows:
            return
        n = len(rows)
        cap = self.capacity
        while n > 0.7 * cap:
            cap *= 2
        if cap != self.capacity:
            self.capacity = cap
            self.fresh = jnp.zeros(cap, dtype=bool)
        key_cols = [
            jnp.asarray(np.asarray([r[j] for r in rows]), dtype=dt)
            for j, dt in enumerate(self._key_dtypes)]
        table = HashTable.empty(cap, self._key_dtypes)
        self.table, _, n_un = lookup_or_insert(
            table, key_cols, jnp.ones(n, dtype=bool))
        assert int(n_un) == 0
