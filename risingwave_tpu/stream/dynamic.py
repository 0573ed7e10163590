"""Now + DynamicFilter executors.

Reference: src/stream/src/executor/now.rs (a barrier-driven one-row
changelog of the epoch timestamp) and dynamic_filter.rs (filter a stream
against a CHANGING scalar — the right side is a one-row stream such as a
global max or NOW(); when the scalar moves, rows crossing the boundary
emit inserts/deletes).

TPU re-design of DynamicFilter: the reference range-scans its
column-ordered state for the crossed interval. Here the left rows live
in the dense sorted row store (pk-hash order) and the barrier flush
recomputes `col OP rhs` over ALL rows, emitting the hash-membership DIFF
against the previously-passing set — O(C) vectorized per barrier, no
range index, and retractions/updates of left rows fall out of the same
diff (the identical pattern the retractable TopN/OverWindow use).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import (
    Column, StreamChunk, OP_DELETE, OP_INSERT, OP_UPDATE_INSERT,
)
from ..common.floatbits import float_identity_bits
from ..common.types import DataType, Field, Schema
from ..ops.jit_state import jit_state
from ..utils.d2h import fetch_small, off_loop
from .executor import Executor
from .align import LEFT, RIGHT, barrier_align
from .message import Barrier, BarrierKind, Watermark
from .sorted_join import _HSENTINEL, key_hash
from .sorted_store import GrowableSortedStore, sorted_store_apply


class NowExecutor(Executor):
    """One-row changelog of the epoch's physical timestamp, updated at
    every barrier (now.rs): UpdateDelete(old) + UpdateInsert(new)."""

    def __init__(self, barrier_queue, name: str = "now"):
        self.barrier_queue = barrier_queue
        self.schema = Schema((Field(name, DataType.TIMESTAMP),))
        self.pk_indices = ()
        self.identity = "Now"
        self._last: Optional[int] = None

    @staticmethod
    def _epoch_us(epoch: int) -> int:
        return (epoch >> 16) * 1000          # physical ms -> us

    def _chunk(self, rows) -> StreamChunk:
        ops = np.asarray([op for op, _ in rows], dtype=np.int8)
        vals = np.asarray([v for _, v in rows], dtype=np.int64)
        return StreamChunk.from_numpy(self.schema, [vals], ops=ops,
                                      capacity=4)

    async def execute(self):
        while True:
            barrier: Barrier = await self.barrier_queue.get()
            ts = self._epoch_us(barrier.epoch.curr)
            if self._last is None:
                yield self._chunk([(OP_INSERT, ts)])
                self._last = ts
            elif ts > self._last:
                yield self._chunk([(OP_DELETE, self._last),
                                   (OP_INSERT, ts)])
                self._last = ts
            yield barrier
            if barrier.is_stop_any():
                return


class DynamicFilterExecutor(GrowableSortedStore, Executor):
    """left WHERE left[key_col] OP right_scalar, right_scalar changing."""

    _SECONDARY = ("em_hash", "em_cols", "em_valids")

    def __init__(self, left: Executor, right: Executor, key_col: int,
                 op: str = "greater_than",
                 capacity: int = 1 << 14,
                 pk_indices: Optional[Sequence[int]] = None,
                 watchdog_interval: Optional[int] = 1):
        assert op in ("greater_than", "greater_than_or_equal",
                      "less_than", "less_than_or_equal")
        self.inputs = (left, right)
        self.schema = left.schema
        self.pk_indices = tuple(
            pk_indices if pk_indices is not None
            else (left.pk_indices or range(len(left.schema))))
        self.key_col = key_col
        self.op = op
        self.capacity = capacity
        self.identity = f"DynamicFilter(${key_col} {op} <rhs>)"
        C = capacity
        dts = tuple(f.data_type.jnp_dtype for f in left.schema)
        self.khash = jnp.full(C, _HSENTINEL, dtype=jnp.int64)
        self.cols = tuple(jnp.zeros(C, dtype=dt) for dt in dts)
        self.valids = tuple(jnp.zeros(C, dtype=bool) for _ in dts)
        self.n = jnp.int32(0)
        self.em_hash = jnp.full(C, _HSENTINEL, dtype=jnp.int64)
        self.em_cols = tuple(jnp.zeros(C, dtype=dt) for dt in dts)
        self.em_valids = tuple(jnp.zeros(C, dtype=bool) for _ in dts)
        self.em_n = jnp.int32(0)
        self._errs_dev = jnp.zeros(2, dtype=jnp.int32)
        # store pytree + errs threaded (em_* is a fresh gather): donate;
        # _flush consumes/replaces the em_* previous-emission set
        self._apply = jit_state(
            partial(sorted_store_apply, pk_idx=self.pk_indices,
                    capacity=self.capacity),
            donate_argnums=(0, 1, 2, 3, 4), name="dynamic_filter_apply")
        self._flush = jit_state(self._flush_impl,
                                donate_argnums=(4, 5, 6, 7),
                                name="dynamic_filter_flush")
        self._wd_pack = jit_state(
            lambda e, n: jnp.concatenate([e, n[None].astype(jnp.int32)]),
            name="dynamic_filter_wd_pack")
        self._rhs: Optional[int] = None      # host scalar (tiny rhs rows)
        self._dirty = False
        if watchdog_interval not in (None, 1):
            raise ValueError("watchdog_interval must be 1 or None")
        self.watchdog_interval = watchdog_interval

    # ------------------------------------------------------------- flush
    def _flush_impl(self, khash, cols, valids, n, em_hash, em_cols,
                    em_valids, em_n, rhs):
        C = self.capacity
        live = jnp.arange(C, dtype=jnp.int32) < n
        x = cols[self.key_col]
        xv = valids[self.key_col]
        if self.op == "greater_than":
            passing = x > rhs
        elif self.op == "greater_than_or_equal":
            passing = x >= rhs
        elif self.op == "less_than":
            passing = x < rhs
        else:
            passing = x <= rhs
        passing = passing & live & xv

        lanes = []
        for c, v in zip(cols, valids):
            d = (float_identity_bits(c)
                 if jnp.issubdtype(c.dtype, jnp.floating)
                 else c.astype(jnp.int64))
            lanes.append(jnp.where(v, d, 0))
            lanes.append(v.astype(jnp.int64))
        rhash = jnp.where(passing, key_hash(lanes), _HSENTINEL)
        order = jnp.argsort(rhash, stable=True)
        new_hash = rhash[order]
        n_new = jnp.sum(passing.astype(jnp.int32))
        new_cols = tuple(c[order] for c in cols)
        new_valids = tuple(v[order] for v in valids)

        def member(a_hash, a_n, b_hash):
            i = jnp.clip(jnp.searchsorted(b_hash, a_hash), 0, C - 1)
            return (jnp.arange(C) < a_n) & (b_hash[i] == a_hash)

        old_still = member(em_hash, em_n, new_hash)
        emit_del = (jnp.arange(C) < em_n) & ~old_still
        new_was = member(new_hash, n_new, em_hash)
        emit_ins = (jnp.arange(C) < n_new) & ~new_was
        out_cols = tuple(
            Column(jnp.concatenate([ec, nc]), jnp.concatenate([ev, nv]))
            for ec, nc, ev, nv in zip(em_cols, new_cols, em_valids,
                                      new_valids))
        ops = jnp.concatenate([
            jnp.full(C, OP_DELETE, dtype=jnp.int8),
            jnp.full(C, OP_INSERT, dtype=jnp.int8)])
        vis = jnp.concatenate([emit_del, emit_ins])
        return (new_hash, new_cols, new_valids, n_new.astype(jnp.int32),
                out_cols, ops, vis)

    # ----------------------------------------------------------- stream
    async def execute(self):
        first = True
        async for kind, s, msg in barrier_align(*self.inputs):
            if kind == "chunk":
                if s == RIGHT:
                    # one-row dynamic side, applied in changelog order: an
                    # insert sets the scalar, a delete of the CURRENT
                    # value with no replacement clears it (no rhs row =>
                    # the condition has no value and nothing passes)
                    for op, vals in msg.to_rows():
                        if op in (OP_INSERT, OP_UPDATE_INSERT):
                            self._rhs = vals[0]
                        elif vals[0] == self._rhs:
                            self._rhs = None
                    self._dirty = True
                else:
                    (self.khash, self.cols, self.valids, self.n,
                     self._errs_dev) = self._apply(
                        self.khash, self.cols, self.valids, self.n,
                        self._errs_dev, msg)
                    self._dirty = True
            elif kind == "barrier":
                barrier: Barrier = msg
                if first or barrier.kind is BarrierKind.INITIAL:
                    first = False
                    yield barrier
                    continue
                if self._dirty and self._rhs is None \
                        and int(self.em_n) != 0:
                    # rhs row retracted: the previously-passing set
                    # empties (use a sentinel no row passes)
                    sentinel = (jnp.iinfo(jnp.int64).max
                                if self.op.startswith("greater")
                                else jnp.iinfo(jnp.int64).min)
                    (self.em_hash, self.em_cols, self.em_valids,
                     self.em_n, out_cols, ops, vis) = self._flush(
                        self.khash, self.cols, self.valids, self.n,
                        self.em_hash, self.em_cols, self.em_valids,
                        self.em_n, jnp.int64(sentinel))
                    self._dirty = False
                    yield StreamChunk(out_cols, ops, vis, self.schema)
                if self._dirty and self._rhs is not None:
                    (self.em_hash, self.em_cols, self.em_valids,
                     self.em_n, out_cols, ops, vis) = self._flush(
                        self.khash, self.cols, self.valids, self.n,
                        self.em_hash, self.em_cols, self.em_valids,
                        self.em_n, jnp.int64(self._rhs))
                    self._dirty = False
                    yield StreamChunk(out_cols, ops, vis, self.schema)
                if self.watchdog_interval:
                    vals = await off_loop(
                        fetch_small, self._wd_pack(self._errs_dev, self.n))
                    if int(vals[0]) or int(vals[1]):
                        raise RuntimeError(
                            f"dynamic filter state errors "
                            f"{vals[:2].tolist()}")
                    self._maybe_grow(int(vals[2]))
                yield barrier
            else:
                wm: Watermark = msg
                if s == LEFT:
                    if wm.col_idx != self.key_col:
                        # ADVICE r4 #4: a dynamic filter must not forward
                        # non-key-column watermarks — ANY threshold
                        # movement (rising for >, falling for <) deletes
                        # rows whose values on those columns sit below an
                        # already-forwarded watermark, violating the
                        # contract downstream (del_miss fail-stop on a
                        # state-cleaned store)
                        continue
                    elif self.op in ("greater_than",
                                     "greater_than_or_equal") \
                            and self._rhs is not None:
                        # the key-column watermark is capped at the rhs:
                        # a rising threshold later DELETES rows in
                        # (old_rhs, new_rhs], which an uncapped watermark
                        # would have let downstream state-clean away
                        # (reference: dynamic filter wm passthrough caps
                        # at the current bound)
                        yield Watermark(wm.col_idx, wm.data_type,
                                        min(wm.val, self._rhs))
