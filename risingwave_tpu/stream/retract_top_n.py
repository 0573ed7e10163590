"""Retractable (Group)TopN — full-input sorted state, per-barrier diff.

Reference: src/stream/src/executor/top_n/ (top_n_cache.rs): the
retractable path persists ALL input rows so a deleted top row can be
refilled from below; the cache keeps the top-K hot. The append-only
variant lives in top_n.py; THIS executor handles retracting inputs
(e.g. TopN over an aggregation's changelog).

TPU re-design: the whole live input lives in a dense array store sorted
by a 63-bit hash of the ROW KEY (the stream key — retractions address
rows by it), maintained with the same searchsorted/merge machinery as
sorted_join.py's own-side update. Nothing data-dependent per chunk.
At each barrier the flush program:

  1. lexsorts live rows by (group hash, order key, row key) — iterated
     stable argsorts, compile-friendly;
  2. ranks rows within their group runs (cummax over run starts);
  3. selects ranks in [offset, offset+limit) as the NEW top set;
  4. diffs it against the LAST EMITTED top set by full-row hash
     membership (two searchsorteds) and emits Deletes for dropped rows
     and Inserts for new ones — refill-from-below falls out naturally:
     when a top row is retracted, rank promotion pulls the next row in
     and the diff emits it.

The one top-N executor: every ORDER BY ... LIMIT and rank filter plans
it, append-only inputs included. Given a state table it persists each
interval's input chunks by stream key and replays the stored rows on
recovery.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from functools import partial

from ..common.chunk import Column, StreamChunk, OP_DELETE, OP_INSERT
from ..ops.hash_table import stable_lexsort
from .executor import Executor, StatefulUnaryExecutor
from .message import Barrier, Watermark
from ..ops.jit_state import jit_state
from ..utils.d2h import fetch_small, off_loop
from .sorted_join import _HSENTINEL, key_hash
from .sorted_store import GrowableSortedStore, sorted_store_apply


class RetractableTopNExecutor(GrowableSortedStore,
                              StatefulUnaryExecutor):
    """Output: the rows whose rank within their group (by order_col,
    direction) falls in [offset, offset+limit), maintained incrementally
    under inserts AND retractions."""

    def __init__(self, input: Executor,
                 group_key_indices: Sequence[int],
                 order_col=None, limit: int = 0, offset: int = 0,
                 descending: bool = False,
                 order_specs: Optional[Sequence[tuple]] = None,
                 capacity: int = 1 << 14,
                 state_table=None,
                 pk_indices: Optional[Sequence[int]] = None,
                 watchdog_interval: Optional[int] = 1):
        self.input = input
        self.schema = input.schema
        self.pk_indices = tuple(
            pk_indices if pk_indices is not None
            else (input.pk_indices or range(len(input.schema))))
        self.group_key_indices = tuple(group_key_indices)
        # order_specs: [(col, descending)] most-significant first
        # (top_n_cache.rs handles arbitrary order keys the same way);
        # (order_col, descending) kept as the single-key shorthand
        if order_specs is None:
            assert order_col is not None
            order_specs = [(order_col, descending)]
        self.order_specs = tuple((int(c), bool(d)) for c, d in order_specs)
        self.limit = limit
        self.offset = offset
        self.capacity = capacity
        self.identity = (f"RetractTopN(g={self.group_key_indices}, "
                         f"by={self.order_specs}, k={limit})")
        C = capacity
        dts = tuple(f.data_type.jnp_dtype for f in input.schema)
        self._col_dtypes = dts
        # dense store sorted by row-key hash
        self.khash = jnp.full(C, _HSENTINEL, dtype=jnp.int64)
        self.cols = tuple(jnp.zeros(C, dtype=dt) for dt in dts)
        self.valids = tuple(jnp.zeros(C, dtype=bool) for _ in dts)
        self.n = jnp.int32(0)
        # last emitted top set, as a sorted array of full-row hashes plus
        # the row payloads (for emitting deletes)
        self.top_hash = jnp.full(C, _HSENTINEL, dtype=jnp.int64)
        self.top_cols = tuple(jnp.zeros(C, dtype=dt) for dt in dts)
        self.top_valids = tuple(jnp.zeros(C, dtype=bool) for _ in dts)
        self.top_n = jnp.int32(0)
        self._errs_dev = jnp.zeros(2, dtype=jnp.int32)  # [row_ovf, del_miss]
        # the dense store pytree (khash, cols, valids, n) + errs is
        # threaded and aliased nowhere (the emitted top set is a fresh
        # gather): donate. _flush consumes/replaces the top_* triplet.
        self._apply = jit_state(
            partial(sorted_store_apply, pk_idx=self.pk_indices,
                    capacity=self.capacity),
            donate_argnums=(0, 1, 2, 3, 4), name="retract_top_n_apply")
        # ONE d2h fetch per barrier: errs and the live count ride together
        self._wd_pack = jit_state(
            lambda e, n: jnp.concatenate([e, n[None].astype(jnp.int32)]),
            name="retract_top_n_wd_pack")
        self._flush = jit_state(self._flush_impl,
                                donate_argnums=(4, 5, 6, 7),
                                name="retract_top_n_flush")
        # durability: the state table materializes the FULL input row set
        # keyed by the stream key (the reference's TopN state table holds
        # all input rows too, top_n_state.rs); each epoch's buffered
        # chunks apply to it at the barrier, recovery re-inserts them
        self._epoch_chunks: list[StreamChunk] = []
        self._init_stateful(state_table, watchdog_interval)

    # ------------------------------------------------------------- flush
    def _flush_impl(self, khash, cols, valids, n, top_hash, top_cols,
                    top_valids, top_n):
        """Compute the new top set, diff vs the last emitted one."""
        C = self.capacity
        live = jnp.arange(C, dtype=jnp.int32) < n
        ghash = (key_hash([cols[i] for i in self.group_key_indices])
                 if self.group_key_indices
                 else jnp.zeros(C, dtype=jnp.int64))
        # order keys least-significant first for the lexsort; DESC via
        # bitwise complement (overflow-free order reversal on ints)
        okeys = []
        for c, desc in reversed(self.order_specs):
            oval = cols[c]
            if jnp.issubdtype(oval.dtype, jnp.floating):
                okeys.append(-oval if desc else oval)
            else:
                # bitwise complement reverses int order overflow-free
                okeys.append(~oval if desc else oval)
        # sort live rows by (group, order..., row hash); dead rows last
        order = stable_lexsort(tuple(
            [khash] + okeys
            + [jnp.where(live, ghash, jnp.iinfo(jnp.int64).max)]))
        s_g = jnp.where(live, ghash, jnp.iinfo(jnp.int64).max)[order]
        new_run = jnp.concatenate([jnp.array([True]),
                                   s_g[1:] != s_g[:-1]])
        pos = jnp.arange(C, dtype=jnp.int32)
        run_start = jax.lax.cummax(jnp.where(new_run, pos, 0))
        rank = pos - run_start
        s_live = live[order]
        in_top = s_live & (rank >= self.offset) & (
            rank < self.offset + self.limit)
        # full-row hash identifies a row across top sets
        s_cols = [c[order] for c in cols]
        rhash = key_hash(s_cols)
        topk = jnp.where(in_top, rhash, _HSENTINEL)
        torder = jnp.argsort(topk, stable=True)
        new_hash = topk[torder]
        n_top = jnp.sum(in_top.astype(jnp.int32))
        new_cols = tuple(c[torder] for c in s_cols)
        new_valids = tuple(v[order][torder] for v in valids)

        # membership diffs via searchsorted (hashes are sorted arrays)
        def member(a_hash, a_n, b_hash):
            i = jnp.searchsorted(b_hash, a_hash)
            i = jnp.clip(i, 0, C - 1)
            return (jnp.arange(C) < a_n) & (b_hash[i] == a_hash)

        old_still = member(top_hash, top_n, new_hash)   # in both
        emit_del = (jnp.arange(C) < top_n) & ~old_still
        new_was = member(new_hash, n_top, top_hash)
        emit_ins = (jnp.arange(C) < n_top) & ~new_was

        out_cols = tuple(
            Column(jnp.concatenate([tc, nc]),
                   jnp.concatenate([tv, nv]))
            for tc, nc, tv, nv in zip(top_cols, new_cols, top_valids,
                                      new_valids))
        ops = jnp.concatenate([
            jnp.full(C, OP_DELETE, dtype=jnp.int8),
            jnp.full(C, OP_INSERT, dtype=jnp.int8)])
        vis = jnp.concatenate([emit_del, emit_ins])
        return (new_hash, new_cols, new_valids, n_top.astype(jnp.int32),
                out_cols, ops, vis)

    # -------------------------------------------------------------- hooks
    def on_chunk(self, chunk: StreamChunk) -> None:
        (self.khash, self.cols, self.valids, self.n,
         self._errs_dev) = self._apply(self.khash, self.cols, self.valids,
                                       self.n, self._errs_dev, chunk)
        if self.state_table is not None:
            self._epoch_chunks.append(chunk)
        return None

    def persist(self, barrier: Barrier, flushed) -> None:
        if self.state_table is None:
            return
        for c in self._epoch_chunks:
            vis = np.asarray(c.vis)
            if vis.any():
                self.state_table.write_chunk_columns(
                    np.asarray(c.ops), [np.asarray(col.data)
                                        for col in c.columns], vis)
        self._epoch_chunks = []
        self.state_table.commit(barrier.epoch.curr)

    def recover_state(self, epoch: int) -> None:
        rows = [r for _, r in self.state_table.iter_all()]
        if not rows:
            return
        self._presize_for(len(rows))
        from ..state.storage_table import rows_to_columns
        cap = 1 << max(6, (len(rows) - 1).bit_length())
        for ofs in range(0, len(rows), cap):
            part = rows[ofs:ofs + cap]
            arrays, valids = rows_to_columns(self.schema, part)
            c = StreamChunk.from_numpy(
                self.schema, arrays, capacity=cap,
                valids=[None if v.all() else v for v in valids])
            (self.khash, self.cols, self.valids, self.n,
             self._errs_dev) = self._apply(self.khash, self.cols,
                                           self.valids, self.n,
                                           self._errs_dev, c)
        # Seed the diff BASELINE: the downstream MV materialized exactly
        # the top set of this recovered (checkpoint-consistent) store, so
        # compute it once and DISCARD the output — the next real flush
        # then emits only genuine changes. Without this, rows that left
        # the top set across the rebuild would never receive a Delete
        # (re-emitting inserts is idempotent; omitted deletes are not).
        (self.top_hash, self.top_cols, self.top_valids, self.top_n,
         _c, _o, _v) = self._flush(
            self.khash, self.cols, self.valids, self.n,
            self.top_hash, self.top_cols, self.top_valids, self.top_n)

    def flush(self) -> Optional[StreamChunk]:
        (self.top_hash, self.top_cols, self.top_valids, self.top_n,
         out_cols, ops, vis) = self._flush(
            self.khash, self.cols, self.valids, self.n,
            self.top_hash, self.top_cols, self.top_valids, self.top_n)
        return StreamChunk(out_cols, ops, vis, self.schema)

    _SECONDARY = ("top_hash", "top_cols", "top_valids")

    async def check_watchdog(self) -> None:
        vals = await off_loop(fetch_small,
                              self._wd_pack(self._errs_dev, self.n))
        if int(vals[0]):
            raise RuntimeError(
                f"retractable TopN overflow ({int(vals[0])} rows dropped; "
                f"capacity {self.capacity})")
        if int(vals[1]):
            raise RuntimeError(
                f"retractable TopN: {int(vals[1])} deletes matched no row")
        self._maybe_grow(int(vals[2]))

    def fence_tokens(self) -> list:
        return [self.n, self.top_n] + super().fence_tokens()

    def map_watermark(self, wm: Watermark) -> Optional[Watermark]:
        return None          # ranks can change; no watermark survives
