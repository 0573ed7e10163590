"""General (retraction-capable) OverWindow — window functions over a
changing input.

Reference: src/stream/src/executor/over_window/general.rs (~1100 LoC):
per-partition BTree caches, delta application, affected-range recompute,
changelog emission. The append-only fast path lives in over_window.py.

TPU re-design: the FULL input lives in the dense sorted row store
(sorted_store.py — shared with retractable TopN). At each barrier, ONE
program lexsorts live rows by (partition hash, order keys, row key),
computes every window function with segmented scans (cumsum/cummax over
partition runs — no per-partition loops), and emits the DIFF against the
previously-emitted (row ++ outputs) set by hash membership: rows whose
outputs changed produce Delete(old)/Insert(new) pairs, inserted/deleted
rows fall out of the same diff. Affected-partition tracking is
unnecessary — the full recompute is a handful of O(C) vectorized passes,
which on TPU is cheaper than managing per-partition deltas.

Window functions (WindowSpec.kind):
  row_number          1-based position within partition by order keys
  rank                ties (equal order keys) share a rank
  sum / count / avg   over UNBOUNDED PRECEDING..CURRENT ROW, or a
                      bounded frame of `preceding` rows (ROWS BETWEEN n
                      PRECEDING AND CURRENT ROW) via prefix-sum
                      differences
All functions evaluate per the ROW order; retractions upstream shift
later rows' values and the diff re-emits exactly those rows.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import Column, StreamChunk, OP_DELETE, OP_INSERT
from ..common.floatbits import float_identity_bits
from ..common.types import DataType, Field, Schema
from ..ops.hash_table import stable_lexsort
from ..ops.jit_state import jit_state
from ..utils.d2h import fetch_small, off_loop
from .executor import Executor, StatefulUnaryExecutor
from .message import Barrier, Watermark
from .sorted_join import _HSENTINEL, key_hash
from .sorted_store import (GrowableSortedStore, segment_starts,
                           sorted_store_apply)


@dataclass(frozen=True)
class WindowSpec:
    """One window function call (reference: WindowFuncCall)."""

    kind: str         # row_number|rank|dense_rank|sum|count|avg|
    #                     lag|lead|first_value
    arg: Optional[int] = None       # input column (None for rank family)
    preceding: Optional[int] = None  # None = UNBOUNDED PRECEDING
    name: str = ""
    offset: int = 1                 # lag/lead row offset

    def ret_type(self, in_schema: Schema) -> DataType:
        if self.kind in ("row_number", "rank", "dense_rank", "count"):
            return DataType.INT64
        if self.kind == "avg":
            return DataType.FLOAT64
        if self.kind in ("lag", "lead", "first_value"):
            # row values pass through UNCHANGED (no promotion)
            return in_schema[self.arg].data_type
        at = in_schema[self.arg].data_type
        # sum promotes: a narrow-int running sum would silently wrap when
        # cast back (the streaming agg path promotes the same way)
        if at in (DataType.FLOAT64, DataType.FLOAT32):
            return DataType.FLOAT64
        return DataType.INT64


class GeneralOverWindowExecutor(GrowableSortedStore,
                                StatefulUnaryExecutor):
    def __init__(self, input: Executor,
                 partition_by: Sequence[int],
                 order_specs: Sequence[tuple],     # [(col, desc)]
                 windows: Sequence[WindowSpec],
                 capacity: int = 1 << 14,
                 state_table=None,
                 pk_indices: Optional[Sequence[int]] = None,
                 watchdog_interval: Optional[int] = 1):
        self.input = input
        in_schema = input.schema
        self.partition_by = tuple(partition_by)
        self.order_specs = tuple((int(c), bool(d)) for c, d in order_specs)
        self.windows = tuple(windows)
        for w in self.windows:
            assert w.kind in ("row_number", "rank", "dense_rank", "sum",
                              "count", "avg", "lag", "lead",
                              "first_value"), w
            if w.preceding is not None:
                assert w.kind in ("sum", "count", "avg"), \
                    "bounded frames support sum/count/avg"
            if w.kind in ("lag", "lead"):
                assert w.offset >= 1, "lag/lead offset must be >= 1"
        self.schema = Schema(tuple(in_schema) + tuple(
            Field(w.name or f"w{j}", w.ret_type(in_schema))
            for j, w in enumerate(self.windows)))
        self.in_width = len(in_schema)
        self.pk_indices = tuple(
            pk_indices if pk_indices is not None
            else (input.pk_indices or range(len(in_schema))))
        self.capacity = capacity
        self.identity = (f"GeneralOverWindow(p={self.partition_by}, "
                         f"o={self.order_specs}, "
                         f"f={[w.kind for w in self.windows]})")
        C = capacity
        dts = tuple(f.data_type.jnp_dtype for f in in_schema)
        self.khash = jnp.full(C, _HSENTINEL, dtype=jnp.int64)
        self.cols = tuple(jnp.zeros(C, dtype=dt) for dt in dts)
        self.valids = tuple(jnp.zeros(C, dtype=bool) for _ in dts)
        self.n = jnp.int32(0)
        # previously-emitted (input ++ outputs) set for the barrier diff
        out_dts = tuple(f.data_type.jnp_dtype for f in self.schema)
        self.em_hash = jnp.full(C, _HSENTINEL, dtype=jnp.int64)
        self.em_cols = tuple(jnp.zeros(C, dtype=dt) for dt in out_dts)
        self.em_valids = tuple(jnp.zeros(C, dtype=bool) for _ in out_dts)
        self.em_n = jnp.int32(0)
        self._errs_dev = jnp.zeros(2, dtype=jnp.int32)
        # store pytree + errs threaded (em_* is a fresh gather): donate;
        # _flush consumes/replaces the em_* previous-emission set
        self._apply = jit_state(
            partial(sorted_store_apply, pk_idx=self.pk_indices,
                    capacity=self.capacity),
            donate_argnums=(0, 1, 2, 3, 4),
            name="general_over_window_apply")
        # ONE d2h fetch per barrier: errs and the live count ride together
        self._wd_pack = jit_state(
            lambda e, n: jnp.concatenate([e, n[None].astype(jnp.int32)]),
            name="general_over_window_wd_pack")
        self._flush = jit_state(self._flush_impl,
                                donate_argnums=(4, 5, 6, 7),
                                name="general_over_window_flush")
        self._epoch_chunks: list[StreamChunk] = []
        self._init_stateful(state_table, watchdog_interval)

    # ------------------------------------------------------------- flush
    def _compute_windows(self, cols, valids, live):
        """-> (out data cols, out valid cols) for the window functions,
        aligned with the (partition, order)-sorted row order."""
        C = self.capacity
        ghash = (key_hash([cols[i] for i in self.partition_by])
                 if self.partition_by else jnp.zeros(C, dtype=jnp.int64))
        gkey = jnp.where(live, ghash, jnp.iinfo(jnp.int64).max)
        okeys = []
        for c, desc in reversed(self.order_specs):
            oval = cols[c]
            if jnp.issubdtype(oval.dtype, jnp.floating):
                okeys.append(-oval if desc else oval)
            else:
                okeys.append(~oval if desc else oval)
        # tiebreak on store position (the store is khash-sorted, so this
        # is deterministic row identity)
        order = stable_lexsort(tuple(
            [jnp.arange(C, dtype=jnp.int32)] + okeys + [gkey]))
        s_live = live[order]
        new_run, run_start = segment_starts(gkey[order])
        pos = jnp.arange(C, dtype=jnp.int32)
        idx_in_part = pos - run_start

        # tie runs: a new tie starts when the partition OR any order key
        # changes
        tie_new = new_run
        for c, _ in self.order_specs:
            sv = cols[c][order]
            tie_new = tie_new | jnp.concatenate(
                [jnp.array([True]), sv[1:] != sv[:-1]])
        tie_start = jax.lax.cummax(jnp.where(tie_new, pos, 0))
        # per-row partition END (for lead): run starts of the REVERSED
        # sorted keys are reversed run ends
        _, rev_start = segment_starts(gkey[order][::-1])
        run_end = (C - 1) - rev_start[::-1]

        outs, out_valids = [], []
        for w in self.windows:
            if w.kind == "row_number":
                outs.append((idx_in_part + 1).astype(jnp.int64))
                out_valids.append(s_live)
                continue
            if w.kind == "rank":
                outs.append((tie_start - run_start + 1).astype(jnp.int64))
                out_valids.append(s_live)
                continue
            if w.kind == "dense_rank":
                dcs = jnp.cumsum(tie_new.astype(jnp.int64))
                outs.append(dcs - dcs[run_start] + 1)
                out_valids.append(s_live)
                continue
            if w.kind in ("lag", "lead", "first_value"):
                raw = cols[w.arg][order]
                rawv = valids[w.arg][order]
                if w.kind == "first_value":
                    src = run_start
                    in_part = jnp.ones(C, dtype=bool)
                elif w.kind == "lag":
                    src = pos - w.offset
                    in_part = src >= run_start
                else:
                    src = pos + w.offset
                    in_part = src <= run_end
                srcc = jnp.clip(src, 0, C - 1)
                outs.append(raw[srcc])
                out_valids.append(s_live & in_part & rawv[srcc])
                continue
            av = cols[w.arg][order]
            avalid = valids[w.arg][order] & s_live
            if w.kind == "count":
                x = avalid.astype(jnp.int64)
            elif jnp.issubdtype(av.dtype, jnp.floating) or w.kind == "avg":
                x = jnp.where(avalid, av.astype(jnp.float64), 0.0)
            else:
                x = jnp.where(avalid, av.astype(jnp.int64), 0)
            cs = jnp.cumsum(x)
            base = cs[run_start] - x[run_start]     # exclusive @ part start
            seg = cs - base                          # inclusive within part
            if w.preceding is not None:
                # frame [j - preceding, j]: subtract the prefix ending
                # before the frame (clamped to the partition start)
                lo = pos - (w.preceding + 1)
                in_part = lo >= run_start
                lo_c = jnp.clip(lo, 0, C - 1)
                seg = seg - jnp.where(in_part, seg[lo_c], 0)
            if w.kind in ("avg", "sum"):
                cnt = jnp.cumsum(avalid.astype(jnp.int64))
                cbase = cnt[run_start] - avalid[run_start].astype(jnp.int64)
                cseg = cnt - cbase
                if w.preceding is not None:
                    lo = pos - (w.preceding + 1)
                    in_part = lo >= run_start
                    lo_c = jnp.clip(lo, 0, C - 1)
                    cseg = cseg - jnp.where(in_part, cnt[lo_c] - cbase, 0)
                if w.kind == "avg":
                    outs.append(seg / jnp.maximum(cseg, 1))
                else:
                    # sum over an all-NULL frame is NULL, not 0
                    # (ADVICE r4 #1 — count alone stays always-valid)
                    outs.append(seg)
                out_valids.append(s_live & (cseg > 0))
            else:
                outs.append(seg)
                out_valids.append(s_live)
        return order, outs, out_valids

    def _flush_impl(self, khash, cols, valids, n, em_hash, em_cols,
                    em_valids, em_n):
        C = self.capacity
        live = jnp.arange(C, dtype=jnp.int32) < n
        order, wouts, wvalids = self._compute_windows(cols, valids, live)
        s_cols = [c[order] for c in cols]
        s_valids = [v[order] for v in valids]
        out_fields = tuple(self.schema)[self.in_width:]
        full_cols = s_cols + [
            o.astype(f.data_type.jnp_dtype)
            for o, f in zip(wouts, out_fields)]
        full_valids = s_valids + list(wvalids)
        s_live = live[order]

        # identity for the diff: hash over ALL columns (floats as their
        # identity bits, common/floatbits.py)
        lanes = []
        for c, v in zip(full_cols, full_valids):
            x = (float_identity_bits(c)
                 if jnp.issubdtype(c.dtype, jnp.floating)
                 else c.astype(jnp.int64))
            lanes.append(jnp.where(v, x, 0))
            lanes.append(v.astype(jnp.int64))
        rhash = jnp.where(s_live, key_hash(lanes), _HSENTINEL)
        rorder = jnp.argsort(rhash, stable=True)
        new_hash = rhash[rorder]
        n_new = jnp.sum(s_live.astype(jnp.int32))
        new_cols = tuple(c[rorder] for c in full_cols)
        new_valids = tuple(v[rorder] for v in full_valids)

        def lanes_of(cols_, valids_):
            out = []
            for c, v in zip(cols_, valids_):
                x = (float_identity_bits(c)
                     if jnp.issubdtype(c.dtype, jnp.floating)
                     else c.astype(jnp.int64))
                out.append(jnp.where(v, x, 0))
                out.append(v.astype(jnp.int64))
            return out

        new_lanes = lanes_of(new_cols, new_valids)
        em_lanes = lanes_of(em_cols, em_valids)

        def member(a_hash, a_n, a_lanes, b_hash, b_lanes):
            # hash probe + EXACT all-lane compare (ADVICE r4 #2): a
            # collision can only cause a redundant delete+insert of an
            # identical row, never a suppressed changelog emission
            i = jnp.clip(jnp.searchsorted(b_hash, a_hash), 0, C - 1)
            same = b_hash[i] == a_hash
            for la, lb in zip(a_lanes, b_lanes):
                same = same & (lb[i] == la)
            return (jnp.arange(C) < a_n) & same

        old_still = member(em_hash, em_n, em_lanes, new_hash, new_lanes)
        emit_del = (jnp.arange(C) < em_n) & ~old_still
        new_was = member(new_hash, n_new, new_lanes, em_hash, em_lanes)
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

    # -------------------------------------------------------------- hooks
    def on_chunk(self, chunk: StreamChunk) -> None:
        (self.khash, self.cols, self.valids, self.n,
         self._errs_dev) = self._apply(self.khash, self.cols, self.valids,
                                       self.n, self._errs_dev, chunk)
        if self.state_table is not None:
            self._epoch_chunks.append(chunk)
        return None

    def flush(self) -> Optional[StreamChunk]:
        (self.em_hash, self.em_cols, self.em_valids, self.em_n,
         out_cols, ops, vis) = self._flush(
            self.khash, self.cols, self.valids, self.n,
            self.em_hash, self.em_cols, self.em_valids, self.em_n)
        return StreamChunk(out_cols, ops, vis, self.schema)

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
        in_schema = Schema(tuple(self.schema)[:self.in_width])
        cap = 1 << max(6, (len(rows) - 1).bit_length())
        for ofs in range(0, len(rows), cap):
            part = rows[ofs:ofs + cap]
            arrays, valids = rows_to_columns(in_schema, part)
            c = StreamChunk.from_numpy(
                in_schema, arrays, capacity=cap,
                valids=[None if v.all() else v for v in valids])
            (self.khash, self.cols, self.valids, self.n,
             self._errs_dev) = self._apply(self.khash, self.cols,
                                           self.valids, self.n,
                                           self._errs_dev, c)
        # seed the diff baseline (same rationale as retractable TopN):
        # the downstream materialized exactly these outputs pre-crash
        (self.em_hash, self.em_cols, self.em_valids, self.em_n,
         _c, _o, _v) = self._flush(
            self.khash, self.cols, self.valids, self.n,
            self.em_hash, self.em_cols, self.em_valids, self.em_n)

    _SECONDARY = ("em_hash", "em_cols", "em_valids")

    async def check_watchdog(self) -> None:
        vals = await off_loop(fetch_small,
                              self._wd_pack(self._errs_dev, self.n))
        if int(vals[0]):
            raise RuntimeError(
                f"over-window store overflow ({int(vals[0])} rows "
                f"dropped; capacity {self.capacity})")
        if int(vals[1]):
            raise RuntimeError(
                f"over-window: {int(vals[1])} deletes matched no row")
        self._maybe_grow(int(vals[2]))

    def fence_tokens(self) -> list:
        return [self.n, self.em_n] + super().fence_tokens()

    def map_watermark(self, wm: Watermark) -> Optional[Watermark]:
        return None      # any row's outputs can change retroactively
