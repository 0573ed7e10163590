"""HashJoin executor — streaming two-sided equi-join with device state.

Reference: src/stream/src/executor/hash_join.rs (JoinSide :76,141; aligned
2-input loop `into_stream` :478; `eq_join_oneside` :792) and the join state
at managed_state/join/mod.rs:238-268 — each side keeps a multimap
join_key -> rows; a chunk from one side probes the OTHER side's map to emit
joined changelog rows, then updates its OWN map.

TPU re-design: each side's multimap is a struct-of-arrays in HBM —
  * key_table: open-addressing HashTable over the join-key columns [CK]
  * head[CK]:  first row index of the key's chain (-1 = empty)
  * rows/valids: per-column row store [CR] + next[CR] links + live[CR]
Applying a chunk is ONE jitted step: probe the other side's key table, walk
all chains in lock-step (a while_loop over the longest chain, each iteration
a cumsum-compaction append into a fixed-capacity match buffer), then apply
deletes (chain walk + claim contest tombstones one instance per delete) and
inserts (batch row allocation + vectorized multi-push-front chain link that
handles duplicate keys within the chunk by sorting rows by key slot).

Changelog contract: an insert-like input row emits Insert matches, a
delete-like row emits Delete matches (update pairs degrade to Delete/Insert,
as the reference does when pairs cannot be kept adjacent). Inner join only —
degree tables for outer joins are the next increment.

Deletion identifies rows by the side's pk within the key chain. Rows are
never unlinked (chains stay intact); tombstones are reclaimed by the
barrier-time rebuild, exactly like HashAgg's zombie purge.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import (
    Column, StreamChunk, OP_DELETE, OP_INSERT, op_sign,
)
from ..common.types import Field, Schema
from ..memory.accounting import pytree_bytes
from ..memory.spill import HostSpill
from ..ops.hash_table import (HashTable, lookup, lookup_or_insert,
                              lru_stamp, pack_rows, stable_lexsort)
from ..ops.jit_state import jit_state
from ..state.state_table import StateTable
from .align import LEFT, RIGHT, barrier_align
from .executor import Executor
from .message import Barrier, BarrierKind, Watermark


@jax.tree_util.register_pytree_node_class
@dataclass
class JoinSideState:
    """Device state of one join side (key table cap CK, row store cap CR)."""

    key_table: HashTable                 # over join-key columns [CK]
    head: jnp.ndarray                    # int32 [CK], -1 = empty chain
    rows: tuple[jnp.ndarray, ...]        # per input column [CR]
    valids: tuple[jnp.ndarray, ...]      # per input column bool [CR]
    next: jnp.ndarray                    # int32 [CR]
    live: jnp.ndarray                    # bool [CR]
    dirty: jnp.ndarray                   # bool [CR] — changed since persist
    top: jnp.ndarray                     # int32 scalar — rows ever allocated

    def tree_flatten(self):
        return ((self.key_table, self.head, self.rows, self.valids,
                 self.next, self.live, self.dirty, self.top), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        kt, head, rows, valids, nxt, live, dirty, top = children
        return cls(kt, head, tuple(rows), tuple(valids), nxt, live, dirty, top)

    @property
    def key_capacity(self) -> int:
        return self.head.shape[0]

    @property
    def row_capacity(self) -> int:
        return self.live.shape[0]


def _empty_side(key_capacity: int, row_capacity: int,
                key_dtypes: Sequence, col_dtypes: Sequence) -> JoinSideState:
    return JoinSideState(
        key_table=HashTable.empty(key_capacity, key_dtypes),
        head=jnp.full(key_capacity, -1, dtype=jnp.int32),
        rows=tuple(jnp.zeros(row_capacity, dtype=dt) for dt in col_dtypes),
        valids=tuple(jnp.zeros(row_capacity, dtype=bool) for _ in col_dtypes),
        next=jnp.full(row_capacity, -1, dtype=jnp.int32),
        live=jnp.zeros(row_capacity, dtype=bool),
        dirty=jnp.zeros(row_capacity, dtype=bool),
        top=jnp.int32(0),
    )


def _bulk_insert(side: JoinSideState, slots: jnp.ndarray, ins: jnp.ndarray,
                 col_data: Sequence[jnp.ndarray], col_valid: Sequence[jnp.ndarray],
                 dirty_vals: jnp.ndarray):
    """Insert the masked rows into the side's row store + chains.

    slots: key slot per row (from lookup_or_insert); ins: bool mask; rows with
    the SAME key slot within the batch are chained among themselves (sorted by
    slot, linked in batch order, head points at the batch's last row — the
    probe order of a chain is reverse insertion order, which is fine for an
    unordered multimap). Returns (side', n_row_overflow).
    """
    CK = side.key_capacity
    CR = side.row_capacity
    N = slots.shape[0]
    n_ins = jnp.sum(ins.astype(jnp.int32))
    rank = jnp.cumsum(ins.astype(jnp.int32)) - 1
    new_ridx = side.top + rank                       # row id per inserted row
    ok = ins & (new_ridx < CR)
    tgt = jnp.where(ok, new_ridx, CR)
    rows = tuple(r.at[tgt].set(d.astype(r.dtype), mode="drop")
                 for r, d in zip(side.rows, col_data))
    valids = tuple(v.at[tgt].set(m, mode="drop")
                   for v, m in zip(side.valids, col_valid))
    live = side.live.at[tgt].set(True, mode="drop")
    dirty = side.dirty.at[tgt].set(dirty_vals, mode="drop")

    # chain link: sort batch rows by key slot so same-slot rows are adjacent
    seg = jnp.where(ok, slots, CK)
    order = jnp.argsort(seg, stable=True)            # [N]
    sseg = seg[order]
    sridx = new_ridx[order]
    prev_same = jnp.concatenate([jnp.array([False]), sseg[1:] == sseg[:-1]])
    prev_ridx = jnp.concatenate([jnp.array([0], dtype=sridx.dtype), sridx[:-1]])
    old_head = side.head[jnp.clip(sseg, 0, CK - 1)]
    nxt_val = jnp.where(prev_same, prev_ridx, old_head).astype(jnp.int32)
    s_ok = ok[order]
    nxt = side.next.at[jnp.where(s_ok, sridx, CR)].set(nxt_val, mode="drop")
    is_last = jnp.concatenate([sseg[:-1] != sseg[1:], jnp.array([True])])
    head = side.head.at[
        jnp.where(s_ok & is_last, sseg, CK)].set(sridx.astype(jnp.int32), mode="drop")
    top = jnp.minimum(side.top + n_ins, CR).astype(jnp.int32)
    n_overflow = jnp.maximum(side.top + n_ins - CR, 0)
    return JoinSideState(side.key_table, head, rows, valids, nxt, live,
                         dirty, top), n_overflow


class HashJoinExecutor(Executor):
    """Inner equi-join. Output schema = left columns ++ right columns
    (optionally projected by output_indices); output pk = left pk ++ right pk.

    condition: optional expression over the FULL (left++right) output row,
    applied as a post-probe filter (the reference's non-equi `cond`)."""

    def __init__(self, left: Executor, right: Executor,
                 left_key_indices: Sequence[int],
                 right_key_indices: Sequence[int],
                 left_pk_indices: Sequence[int],
                 right_pk_indices: Sequence[int],
                 key_capacity: int = 1 << 14,
                 row_capacity: int = 1 << 16,
                 match_factor: int = 2,
                 condition=None,
                 output_indices: Optional[Sequence[int]] = None,
                 state_tables: Optional[tuple[StateTable, StateTable]] = None,
                 clean_watermark_cols: tuple[Optional[int], Optional[int]] = (None, None),
                 watchdog_interval: Optional[int] = 1):
        self.inputs = (left, right)
        self.key_indices = (tuple(left_key_indices), tuple(right_key_indices))
        self.pk_indices_side = (tuple(left_pk_indices), tuple(right_pk_indices))
        assert len(self.key_indices[0]) == len(self.key_indices[1])
        lt, rt = left.schema, right.schema
        for li, ri in zip(*self.key_indices):
            assert lt[li].data_type.np_dtype == rt[ri].data_type.np_dtype, \
                f"join key dtype mismatch {lt[li]} vs {rt[ri]}"
        self._key_dtypes = tuple(
            lt[i].data_type.jnp_dtype for i in self.key_indices[0])
        self._col_dtypes = (
            tuple(f.data_type.jnp_dtype for f in lt),
            tuple(f.data_type.jnp_dtype for f in rt),
        )
        full_fields = [Field(f"l_{f.name}" if f.name in {g.name for g in rt} else f.name,
                             f.data_type, f.scale) for f in lt]
        full_fields += [Field(f"r_{f.name}" if f.name in {g.name for g in lt} else f.name,
                              f.data_type, f.scale) for f in rt]
        self.output_indices = (tuple(output_indices) if output_indices is not None
                               else tuple(range(len(full_fields))))
        self.schema = Schema(tuple(full_fields[i] for i in self.output_indices))
        out_pk_full = (tuple(self.pk_indices_side[0])
                       + tuple(len(lt) + i for i in self.pk_indices_side[1]))
        self.pk_indices = tuple(self.output_indices.index(i)
                                for i in out_pk_full if i in self.output_indices)
        self.key_capacity = [key_capacity, key_capacity]
        self.row_capacity = [row_capacity, row_capacity]
        self.match_factor = match_factor
        self.condition = condition
        self.state_tables = state_tables or (None, None)
        self.clean_cols = tuple(clean_watermark_cols)
        self._pending_clean: list[Optional[int]] = [None, None]
        self.identity = (f"HashJoin(l={self.key_indices[0]}, "
                         f"r={self.key_indices[1]})")
        self.sides = [self._empty(s) for s in (LEFT, RIGHT)]
        # Donation: the OWN side (arg 0) and the error accumulator (arg 2)
        # are threaded — `self.sides[s] = self._apply(self.sides[s], ...)`
        # holds the only reference — so their table buffers update in
        # place. The OTHER side (arg 1) is read-only and must never be
        # donated: it is still live as self.sides[1 - s].
        self._apply = jit_state(self._apply_impl, static_argnames=("side",),
                                donate_argnums=(0, 2), name="hash_join_apply")
        self._persist_view = jit_state(self._persist_view_impl,
                                       name="hash_join_persist_view")
        self._evict = jit_state(self._evict_impl, static_argnames=("side",),
                                donate_argnums=(0,), name="hash_join_evict")
        self._evict_rows = jit_state(self._evict_rows_impl,
                                     static_argnames=("side",),
                                     name="hash_join_evict_rows")
        self._stats = jit_state(self._stats_impl, name="hash_join_stats")
        self._rehash = jit_state(self._rehash_impl,
                                 static_argnames=("side", "new_ck", "new_cr"),
                                 donate_argnums=(0,), name="hash_join_rehash")
        # multi-chunk apply: consecutive same-side chunks inside one
        # barrier interval scan through the probe/update step in ONE
        # dispatch; the run drains on side switch, barrier, or watermark,
        # so cross-side and chunk/watermark ordering are preserved exactly
        self._use_chunk_batching = True
        self._batch_max = 8
        self._run_chunks: list[StreamChunk] = []
        self._run_side: Optional[int] = None
        self._apply_scans: dict = {}
        self.rebuilds = 0
        # 1 = fetch + fail-stop before every checkpoint commit; None =
        # NO fetch ever, not even at stop (see HashAggExecutor: a
        # blocking d2h fetch serialises with dispatch, so
        # latency-critical pipelines can keep the whole process
        # transfer-free and rest on CPU-backend tests for correctness)
        if watchdog_interval not in (None, 1):
            raise ValueError(
                "watchdog_interval must be 1 or None (a lagged check would "
                "let checkpoints commit unverified state)")
        self.watchdog_interval = watchdog_interval
        self._dirty_since_flush = [False, False]
        # device-resident watchdog accumulator + latest per-side load stats;
        # fetched once per barrier (see _apply_impl docstring)
        self._errs_dev = jnp.zeros(4, dtype=jnp.int32)
        zero = jnp.zeros((), dtype=jnp.int32)
        self._occ_dev = [zero, zero]
        self._top_dev = [zero, zero]
        self._occ_known = [0, 0]
        self._top_known = [0, 0]
        self._watchdog_pack = jit_state(
            lambda errs, ol, tl, orr, tr: jnp.concatenate(
                [errs, jnp.stack([ol, tl, orr, tr])]),
            name="hash_join_watchdog_pack")
        # watermark bookkeeping: per side, last seen watermark per key position
        self._key_wms: list[dict[int, int]] = [{}, {}]
        self._emitted_key_wm: dict[int, int] = {}
        # ---- HBM memory manager hooks (memory/manager.py): per-ROW
        # int64 LRU epoch stamps per side; cold clean rows tombstone +
        # spill to host, the shrinking rehash reclaims their HBM, and a
        # later touch (probe, delete, or same-key insert) reloads the
        # key's rows at drain time before the chunk applies.
        self._mem_lru_on = False
        self._slot_epoch: list = [None, None]      # int64 [CR] per side
        self._spill = [HostSpill(), HostSpill()]
        self.mem_evicted_bytes = 0
        self.mem_reload_count = 0
        # keys the reload-LFU guard kept resident through an eviction
        # round (memory/manager.py ReloadGuard, set as self.mem_guard)
        self.mem_guard_protected = 0
        self._lru_stamp = jit_state(self._lru_stamp_impl,
                                    donate_argnums=(1,),
                                    name="hash_join_lru_stamp")
        self._mem_stats = jit_state(self._mem_stats_impl,
                                    name="hash_join_mem_stats")
        self._mem_pack = jit_state(self._mem_pack_impl,
                                   name="hash_join_mem_pack")
        self._mem_evict_apply = jit_state(self._mem_evict_impl,
                                          donate_argnums=(0,),
                                          name="hash_join_mem_evict")
        self._mem_reloads: dict = {}

    def fence_tokens(self) -> list:
        toks = [s.top for s in self.sides if s is not None]
        return toks + super().fence_tokens()

    def _empty(self, side: int) -> JoinSideState:
        return _empty_side(self.key_capacity[side], self.row_capacity[side],
                           self._key_dtypes, self._col_dtypes[side])

    # ------------------------------------------------------------- apply
    def _apply_impl(self, own: JoinSideState, other: JoinSideState,
                    errs: jnp.ndarray, chunk: StreamChunk, side: int):
        """Probe `other`, emit matches, update `own`. Returns
        (own', match buffers, errs', occ, top) — errs is the int32[4]
        device accumulator [unresolved, delete-miss, match-overflow,
        row-overflow]; it stays on device and the host fetches it once per
        barrier (a d2h copy serializes into the device stream, so per-chunk
        fetches would gate throughput on copy latency)."""
        key_idx = self.key_indices[side]
        pk_idx = self.pk_indices_side[side]
        N = chunk.capacity
        CRo = other.row_capacity
        CRs = own.row_capacity
        CKs = own.key_capacity
        M = self.match_factor * N

        key_cols = [chunk.columns[i].data for i in key_idx]
        key_valid = jnp.ones(N, dtype=bool)
        for i in key_idx:
            key_valid &= chunk.columns[i].valid_mask()
        active = chunk.vis & key_valid               # NULL keys never join
        signs = op_sign(chunk.ops)
        row_ids = jnp.arange(N, dtype=jnp.int32)

        # ---- within-chunk pk-run resolution ----
        # The reference applies rows strictly in order, so one chunk may
        # insert AND delete the same pk. Lexsort active rows by pk (row order
        # as tiebreak); each equal-pk run nets out to at most one effective
        # stored-row delete (the run's first op, if delete-like) and one
        # effective insert (the run's last op, if insert-like). Probe
        # emission below still uses every row — only STATE updates net out.
        sort_keys = [row_ids]                        # least significant
        for p in pk_idx:
            sort_keys.append(chunk.columns[p].data)
        sort_keys.append(~active)                    # inactive rows last
        order = stable_lexsort(tuple(sort_keys))
        s_act = active[order]
        same = s_act[1:] & s_act[:-1]
        for p in pk_idx:
            d = chunk.columns[p].data[order]
            same = same & (d[1:] == d[:-1])
        run_start = jnp.concatenate([jnp.array([True]), ~same])
        run_end = jnp.concatenate([~same, jnp.array([True])])
        s_signs = signs[order]
        eff_del_s = run_start & (s_signs < 0) & s_act
        eff_ins_s = run_end & (s_signs > 0) & s_act
        is_del = jnp.zeros(N, dtype=bool).at[order].set(eff_del_s)
        is_ins = jnp.zeros(N, dtype=bool).at[order].set(eff_ins_s)

        # ---- probe the other side: lock-step chain walk ----
        oslot = lookup(other.key_table, key_cols, active)
        cursor = jnp.where(oslot >= 0,
                           other.head[jnp.clip(oslot, 0, None)], -1)

        def pcond(st):
            cursor, m, _, _ = st
            return jnp.any(cursor >= 0)

        def pbody(st):
            cursor, m, out_own, out_oth = st
            cc = jnp.clip(cursor, 0, None)
            alive = (cursor >= 0) & other.live[cc]
            rank = jnp.cumsum(alive.astype(jnp.int32)) - 1
            pos = m + rank
            tgt = jnp.where(alive & (pos < M), pos, M)
            out_own = out_own.at[tgt].set(row_ids, mode="drop")
            out_oth = out_oth.at[tgt].set(cursor, mode="drop")
            m = (m + jnp.sum(alive.astype(jnp.int32))).astype(jnp.int32)
            cursor = jnp.where(cursor >= 0, other.next[cc], -1)
            return cursor, m, out_own, out_oth

        _, m_total, out_own, out_oth = jax.lax.while_loop(
            pcond, pbody,
            (cursor, jnp.int32(0),
             jnp.zeros(M, dtype=jnp.int32), jnp.zeros(M, dtype=jnp.int32)))
        n_match_overflow = jnp.maximum(m_total - M, 0)

        # ---- own-side update: deletes first (update pairs retract the OLD
        # row before the new one lands — reference applies rows in order) ----
        own_table, slots, n_un = lookup_or_insert(own.key_table, key_cols, active)
        own = JoinSideState(own_table, own.head, own.rows, own.valids,
                            own.next, own.live, own.dirty, own.top)
        dcur = jnp.where(is_del & (slots >= 0),
                         own.head[jnp.clip(slots, 0, None)], -1)

        def dcond(st):
            dcur = st[0]
            return jnp.any(dcur >= 0)

        def dbody(st):
            dcur, live, dirty, found = st
            cc = jnp.clip(dcur, 0, None)
            alive = (dcur >= 0) & live[cc]
            pkm = jnp.ones(N, dtype=bool)
            for p in pk_idx:
                pkm &= own.rows[p][cc] == chunk.columns[p].data.astype(own.rows[p].dtype)
            cand = alive & pkm & ~found
            # claim contest: at most one delete consumes a given row
            claim = jnp.full(CRs, N, dtype=jnp.int32)
            claim = claim.at[jnp.where(cand, dcur, CRs)].min(row_ids, mode="drop")
            win = cand & (claim[cc] == row_ids)
            live = live.at[jnp.where(win, dcur, CRs)].set(False, mode="drop")
            dirty = dirty.at[jnp.where(win, dcur, CRs)].set(True, mode="drop")
            found = found | win
            dcur = jnp.where(found | (dcur < 0), -1, own.next[cc])
            return dcur, live, dirty, found

        _, live2, dirty2, found = jax.lax.while_loop(
            dcond, dbody,
            (dcur, own.live, own.dirty, jnp.zeros(N, dtype=bool)))
        n_del_miss = jnp.sum((is_del & ~found).astype(jnp.int32))
        own = JoinSideState(own.key_table, own.head, own.rows, own.valids,
                            own.next, live2, dirty2, own.top)

        # ---- inserts ----
        own, n_row_overflow = _bulk_insert(
            own, slots, is_ins,
            [c.data for c in chunk.columns],
            [c.valid_mask() for c in chunk.columns],
            jnp.ones(N, dtype=bool))

        # ---- output assembly: left cols ++ right cols ----
        m_ok = jnp.minimum(m_total, M)
        out_vis = jnp.arange(M) < m_ok
        own_cols = [Column(jnp.take(c.data, out_own, axis=0),
                           jnp.take(c.valid_mask(), out_own, axis=0))
                    for c in chunk.columns]
        oc = jnp.clip(out_oth, 0, None)
        oth_cols = [Column(r[oc], v[oc])
                    for r, v in zip(other.rows, other.valids)]
        cols = own_cols + oth_cols if side == LEFT else oth_cols + own_cols
        ops_out = jnp.where(jnp.take(signs, out_own) > 0,
                            OP_INSERT, OP_DELETE).astype(jnp.int8)
        occ = jnp.sum(own.key_table.occupied.astype(jnp.int32))
        errs = errs + jnp.stack([
            n_un, n_del_miss, n_match_overflow, n_row_overflow,
        ]).astype(jnp.int32)
        return (own, tuple(cols), ops_out, out_vis, errs, occ, own.top)

    # ------------------------------------------------------- persistence
    def _persist_view_impl(self, side_state: JoinSideState):
        """Compacted dirty rows -> (cols..., valid flags..., ops, vis)."""
        CR = side_state.row_capacity
        dirty = side_state.dirty
        rank = jnp.cumsum(dirty.astype(jnp.int32)) - 1
        ids = jnp.arange(CR, dtype=jnp.int32)
        sel = jnp.zeros(CR, dtype=jnp.int32).at[
            jnp.where(dirty, rank, CR)].set(ids, mode="drop")
        n_dirty = jnp.sum(dirty.astype(jnp.int32))
        vis = ids < n_dirty
        ops = jnp.where(side_state.live[sel], OP_INSERT, OP_DELETE).astype(jnp.int8)
        cols = tuple(r[sel] for r in side_state.rows)
        return cols, ops, vis

    def _persist(self, barrier: Barrier) -> None:
        """Overlap-friendly durable flush (see HashAggExecutor._persist):
        the persist/evict views dispatch here against non-donated buffers
        and the dirty bits reset on-device immediately; the blocking d2h
        + columnar writes + commit run as a staged deferred store flush —
        inline by default, on the background uploader in pipelined mode.
        Both sides' payloads (full persist views + evict-delete prefixes)
        pack into ONE flat fetch; evict counts ride a separate tiny
        counts fetch first."""
        jobs = []    # (state_table, persist-view arrays|None, evict|None)
        ev_counts = []
        for s in (LEFT, RIGHT):
            st = self.state_tables[s]
            if st is None:
                continue
            dev = None
            if self._dirty_since_flush[s]:
                cols, ops, vis = self._persist_view(self.sides[s])
                dev = [ops, vis] + list(cols)
                side = self.sides[s]
                self.sides[s] = JoinSideState(
                    side.key_table, side.head, side.rows, side.valids,
                    side.next, side.live,
                    jnp.zeros(side.row_capacity, dtype=bool), side.top)
                self._dirty_since_flush[s] = False
            ev = None
            if self._pending_clean[s] is not None \
                    and self.clean_cols[s] is not None:
                ev_cols_dev, n_ev = self._evict_rows(
                    self.sides[s], self._pending_clean[s], side=s)
                ev = list(ev_cols_dev)
                ev_counts.append(jnp.ravel(n_ev))
            jobs.append((st, dev, ev))
        if not jobs:
            return
        from ..utils.d2h import (fetch_flat, finish_prefix_groups,
                                 prepare_prefix_groups)
        counts_dev = jnp.concatenate(ev_counts) if ev_counts else None
        new_epoch = barrier.epoch.curr
        cell: dict = {}

        def wait_counts():
            return np.asarray(counts_dev) if counts_dev is not None else None

        def cont_prepare(counts):
            groups, plan, ci = [], [], 0
            for _, dev, ev in jobs:
                g_dev = g_ev = None
                n_ev = 0
                if dev is not None:
                    g_dev = len(groups)
                    groups.append((dev, int(dev[0].shape[0])))  # full view
                if ev is not None:
                    n_ev = int(counts[ci])
                    ci += 1
                    if n_ev:
                        g_ev = len(groups)
                        groups.append((ev, n_ev))
                plan.append((g_dev, g_ev, n_ev))
            cell["plan"] = plan
            if groups:
                cell["prep"] = prepare_prefix_groups(groups)

        def wait_flat():
            prep = cell.get("prep")
            return fetch_flat(prep[0]) if prep is not None else None

        def cont_apply(host_flat):
            prep = cell.get("prep")
            outs = (finish_prefix_groups(host_flat, prep[1], prep[2])
                    if prep is not None else [])
            for (st, _, _), (g_dev, g_ev, n_ev) in zip(jobs, cell["plan"]):
                if g_dev is not None:
                    host = outs[g_dev]
                    vis_np = host[1].astype(bool, copy=False)
                    if vis_np.any():
                        # columnar batch write (state_table.rs:946): the
                        # C++ codec path, no per-row Python
                        st.write_chunk_columns(host[0], host[2:], vis_np)
                if g_ev is not None:
                    st.write_chunk_columns(
                        np.full(n_ev, OP_DELETE, dtype=np.int8),
                        outs[g_ev], np.ones(n_ev, dtype=bool))
                st.commit(new_epoch)

        jobs[0][0].store.defer_flush(barrier.epoch.prev,
                                     (wait_counts, cont_prepare),
                                     (wait_flat, cont_apply),
                                     table_id=jobs[0][0].table_id)

    def _evict_rows_impl(self, side_state: JoinSideState, wm, side: int):
        col = self.clean_cols[side]
        CR = side_state.row_capacity
        evict = side_state.live & (side_state.rows[col] < wm)
        rank = jnp.cumsum(evict.astype(jnp.int32)) - 1
        sel = jnp.zeros(CR, dtype=jnp.int32).at[
            jnp.where(evict, rank, CR)].set(jnp.arange(CR, dtype=jnp.int32),
                                            mode="drop")
        n = jnp.sum(evict.astype(jnp.int32))
        return tuple(r[sel] for r in side_state.rows), n

    def _evict_impl(self, side_state: JoinSideState, wm, side: int):
        col = self.clean_cols[side]
        keep = ~(side_state.live & (side_state.rows[col] < wm))
        return JoinSideState(
            side_state.key_table, side_state.head, side_state.rows,
            side_state.valids, side_state.next, side_state.live & keep,
            side_state.dirty, side_state.top)

    def recover(self) -> None:
        # spilled rows live in the durable tables too; recovery rebuilds
        # everything resident and drops the host spill
        for sp in self._spill:
            sp.clear()
        self._slot_epoch = [None, None]
        for s in (LEFT, RIGHT):
            st = self.state_tables[s]
            if st is None:
                continue
            rows = [r for _, r in st.iter_all()]
            if not rows:
                continue
            n = len(rows)
            self.row_capacity[s] = max(self.row_capacity[s],
                                       1 << (int(n / 0.7)).bit_length())
            self.key_capacity[s] = max(self.key_capacity[s],
                                       1 << (int(n / 0.7)).bit_length())
            self.sides[s] = self._empty(s)
            cap = 1 << max(1, (n - 1).bit_length())
            sch = self.inputs[s].schema
            arrays = [np.asarray([r[i] for r in rows], dtype=f.data_type.np_dtype)
                      for i, f in enumerate(sch)]
            chunk = StreamChunk.from_numpy(sch, arrays, capacity=cap)
            out = self._apply(self.sides[s],
                              self._empty(1 - s) if self.sides[1 - s] is None
                              else self.sides[1 - s], self._errs_dev, chunk,
                              side=s)
            self.sides[s] = out[0]
            self._errs_dev = out[4]
            # recovery rows are already durable: clear dirty
            side = self.sides[s]
            self.sides[s] = JoinSideState(
                side.key_table, side.head, side.rows, side.valids, side.next,
                side.live, jnp.zeros(side.row_capacity, dtype=bool), side.top)

    # ------------------------------------------------- HBM memory manager
    def state_bytes(self) -> int:
        extras = tuple(g for g in self._slot_epoch if g is not None)
        return pytree_bytes((self.sides, extras))

    @property
    def mem_spilled_rows(self) -> int:
        return self._spill[LEFT].rows + self._spill[RIGHT].rows

    def memory_enable_lru(self) -> None:
        self._mem_lru_on = True

    def _lru_stamp_impl(self, dirty, slot_epoch, epoch):
        return lru_stamp(slot_epoch, dirty, epoch)

    def _mem_stamp(self, s: int, epoch: int) -> None:
        if self._slot_epoch[s] is None \
                or self._slot_epoch[s].shape[0] != self.row_capacity[s]:
            self._slot_epoch[s] = jnp.full(self.row_capacity[s], epoch,
                                           dtype=jnp.int64)
            return
        self._slot_epoch[s] = self._lru_stamp(
            self.sides[s].dirty, self._slot_epoch[s], epoch)

    def _mem_stats_impl(self, side_state: JoinSideState, slot_epoch):
        return side_state.live & ~side_state.dirty, slot_epoch

    def _mem_pack_impl(self, side_state: JoinSideState, slot_epoch, thresh):
        evict = (side_state.live & ~side_state.dirty
                 & (slot_epoch <= thresh))
        return pack_rows(evict, list(side_state.rows)
                         + list(side_state.valids))

    def _mem_evict_impl(self, side_state: JoinSideState, slot_epoch,
                        thresh):
        """Tombstone the cold rows (chains stay intact); the shrinking
        rehash right after reclaims the slots."""
        drop = (side_state.live & ~side_state.dirty
                & (slot_epoch <= thresh))
        return JoinSideState(
            side_state.key_table, side_state.head, side_state.rows,
            side_state.valids, side_state.next, side_state.live & ~drop,
            side_state.dirty, side_state.top)

    def _mem_fetch_stats(self, s: int, epoch: int):
        """(live mask, stamps, cold stamps asc, this-interval churn) for
        one side in ONE packed fetch."""
        from ..utils.d2h import fetch_columns
        live_dev, ep_dev = self._mem_stats(self.sides[s],
                                           self._slot_epoch[s])
        live_np, ep_np = fetch_columns([live_dev, ep_dev])
        live_np = live_np.astype(bool)
        cold = np.sort(ep_np[live_np & (ep_np < epoch)])
        return live_np, ep_np, cold, int((ep_np == epoch).sum())

    @staticmethod
    def _mem_cap_for(n_survive: int, touched_now: int) -> int:
        """Survivors + one interval of fresh rows at 0.35 target load —
        no immediate re-grow, no mid-epoch overflow."""
        c = 256
        while n_survive + touched_now > 0.35 * c:
            c *= 2
        return c

    def _mem_do_evict(self, s: int, epoch: int, thresh: int,
                      new_cr: int, survivors_hint: int) -> int:
        """Pack + spill side `s` rows stamped <= thresh, tombstone them,
        rehash the row store at new_cr. Returns bytes freed."""
        from ..utils.d2h import fetch_prefix_groups
        guard = getattr(self, "mem_guard", None)
        t_dev = jnp.int64(thresh)
        cols_dev, n_dev = self._mem_pack(self.sides[s],
                                         self._slot_epoch[s], t_dev)
        n = int(np.asarray(n_dev))
        nc = len(self._col_dtypes[s])
        protected: list = []
        if n:
            host = fetch_prefix_groups([(list(cols_dev), n)])[0]
            for r in range(n):
                vals = tuple(host[c][r].item() for c in range(nc))
                valids = tuple(bool(host[nc + c][r]) for c in range(nc))
                key = tuple(vals[i] for i in self.key_indices[s])
                if guard is not None \
                        and guard.is_protected((id(self), s), key):
                    # reload-LFU guard: probe-hot key — keep it
                    # device-resident, re-insert after the rehash
                    protected.append((vals, valids))
                else:
                    self._spill[s].add(key, (vals, valids))
        before = pytree_bytes(self.sides[s])
        self.sides[s] = self._mem_evict_apply(
            self.sides[s], self._slot_epoch[s], t_dev)
        self.sides[s] = self._rehash(
            self.sides[s], side=s, new_ck=self.key_capacity[s],
            new_cr=new_cr)
        self.row_capacity[s] = new_cr
        self._slot_epoch[s] = None
        self.rebuilds += 1
        occ2, _, top2 = self._stats(self.sides[s])
        self._occ_known[s], self._top_known[s] = int(occ2), int(top2)
        if protected:
            self._mem_reload_rows(s, protected)
            self.mem_guard_protected += len(protected)
            guard.note_protected(len(protected))
        freed = max(0, before - pytree_bytes(self.sides[s]))
        self.mem_evicted_bytes += freed
        return freed

    def memory_evict(self, target_bytes: int, epoch: int) -> int:
        """Budget response: spill each side's coldest rows to host and
        rehash the row store smaller. Runs between epochs (manager
        hook); dirty rows never spill — the persist path owns them until
        the next flush."""
        if not self._mem_lru_on:
            return 0
        freed_total = 0
        order = sorted((LEFT, RIGHT),
                       key=lambda s: -pytree_bytes(self.sides[s]))
        for s in order:
            if freed_total >= target_bytes:
                break
            if self._slot_epoch[s] is None:
                continue
            live_np, ep_np, cold, touched_now = \
                self._mem_fetch_stats(s, epoch)
            if cold.size == 0:
                continue
            total_live = int(live_np.sum())
            bps = max(1, pytree_bytes(self.sides[s])
                      // max(1, self.row_capacity[s]))
            removed, thresh = 0, None
            for t in np.unique(cold):
                removed = int((cold <= t).sum())
                thresh = int(t)
                if (self.row_capacity[s]
                        - self._mem_cap_for(total_live - removed,
                                            touched_now)) * bps \
                        >= target_bytes - freed_total:
                    break
            new_cr = self._mem_cap_for(total_live - removed, touched_now)
            if thresh is None or new_cr >= self.row_capacity[s]:
                continue
            freed_total += self._mem_do_evict(s, epoch, thresh, new_cr,
                                              total_live - removed)
        return freed_total

    def memory_maintain(self, epoch: int) -> None:
        """Steady-state LRU tick: spill cold rows BEFORE a side's row
        store reaches the growth threshold — eviction is the plan,
        capacity resize the fallback."""
        if not self._mem_lru_on:
            return
        for s in (LEFT, RIGHT):
            if self._slot_epoch[s] is None:
                continue
            if self._top_known[s] <= 0.55 * self.row_capacity[s]:
                continue
            live_np, ep_np, cold, touched_now = \
                self._mem_fetch_stats(s, epoch)
            if cold.size == 0:
                continue
            total_live = int(live_np.sum())
            need = (total_live + touched_now
                    - int(0.35 * self.row_capacity[s]))
            removed, thresh = 0, None
            for t in np.unique(cold):
                removed = int((cold <= t).sum())
                thresh = int(t)
                if removed >= need:
                    break
            new_cr = min(self.row_capacity[s],
                         self._mem_cap_for(total_live - removed,
                                           touched_now))
            self._mem_do_evict(s, epoch, thresh, new_cr,
                               total_live - removed)

    def _mem_check_reload(self, side: int, chunks: list) -> None:
        """Read-through miss handling before a run applies: a chunk from
        `side` probes the other side and mutates its own, so spilled keys
        on EITHER side that the chunk's keys touch reload first."""
        if not (self._spill[LEFT] or self._spill[RIGHT]):
            return
        from ..utils.d2h import fetch_columns
        key_idx = self.key_indices[side]
        nk = len(key_idx)
        arrays = []
        for ch in chunks:
            arrays.extend(ch.columns[i].data for i in key_idx)
            arrays.append(ch.vis)
        host = fetch_columns(arrays)
        keys: list = []
        seen: set = set()
        for ci in range(len(chunks)):
            part = host[ci * (nk + 1):(ci + 1) * (nk + 1)]
            idx = np.flatnonzero(part[-1].astype(bool))
            for vals in zip(*(c[idx] for c in part[:nk])):
                k = tuple(v.item() for v in vals)
                if k not in seen:
                    seen.add(k)
                    keys.append(k)
        guard = getattr(self, "mem_guard", None)
        for t in (side, 1 - side):
            touched = self._spill[t].take_touched(keys)
            if touched:
                if guard is not None:
                    guard.note((id(self), t), list(touched))
                self._mem_reload_rows(
                    t, [rw for rows in touched.values() for rw in rows])
                self.mem_reload_count += len(touched)
                from ..utils.metrics import HBM_RELOADS
                HBM_RELOADS.inc(len(touched))

    def _mem_reload_rows(self, t: int, entries: list) -> None:
        """Re-insert spilled rows into side `t`'s store (clean — they are
        already durable); rides the same bulk-insert machinery recovery
        replays through."""
        if not entries:
            return
        n = len(entries)
        # pre-grow so the reload cannot overflow the row store
        if self._top_known[t] + n > 0.7 * self.row_capacity[t] \
                or self._occ_known[t] + n > 0.7 * self.key_capacity[t]:
            new_cr, new_ck = self.row_capacity[t], self.key_capacity[t]
            while self._top_known[t] + n > 0.7 * new_cr:
                new_cr *= 2
            while self._occ_known[t] + n > 0.7 * new_ck:
                new_ck *= 2
            self.sides[t] = self._rehash(self.sides[t], side=t,
                                         new_ck=new_ck, new_cr=new_cr)
            self.row_capacity[t], self.key_capacity[t] = new_cr, new_ck
            self._slot_epoch[t] = None
            occ2, _, top2 = self._stats(self.sides[t])
            self._occ_known[t], self._top_known[t] = int(occ2), int(top2)
        B = 1 << max(0, (n - 1).bit_length())
        pad = entries + [entries[0]] * (B - n)
        active = jnp.asarray(np.arange(B) < n)
        col_data = tuple(
            jnp.asarray(np.asarray([e[0][c] for e in pad],
                                   dtype=np.dtype(dt)))
            for c, dt in enumerate(self._col_dtypes[t]))
        col_valid = tuple(
            jnp.asarray(np.asarray([e[1][c] for e in pad], dtype=bool))
            for c in range(len(self._col_dtypes[t])))
        prog = self._mem_reloads.get((B, t))
        if prog is None:
            prog = jit_state(partial(self._mem_reload_impl, side=t),
                             donate_argnums=(0, 1),
                             name=f"hash_join_mem_reload{B}_s{t}")
            self._mem_reloads[(B, t)] = prog
        self.sides[t], self._errs_dev = prog(
            self.sides[t], self._errs_dev, col_data, col_valid, active)
        self._top_known[t] += n

    def _mem_reload_impl(self, own: JoinSideState, errs, col_data,
                         col_valid, active, side: int):
        key_cols = [col_data[i] for i in self.key_indices[side]]
        table, slots, n_un = lookup_or_insert(own.key_table, key_cols,
                                              active)
        own = JoinSideState(table, own.head, own.rows, own.valids,
                            own.next, own.live, own.dirty, own.top)
        B = active.shape[0]
        own, n_ro = _bulk_insert(own, slots, active & (slots >= 0),
                                 col_data, col_valid,
                                 jnp.zeros(B, dtype=bool))
        zero = jnp.int32(0)
        errs = errs + jnp.stack([n_un.astype(jnp.int32), zero, zero,
                                 n_ro.astype(jnp.int32)])
        return own, errs

    def _clean_spilled(self, s: int, wm) -> None:
        """Watermark cleaning of evicted (spilled) join rows: rows whose
        clean column fell below the watermark can never match again —
        drop them from the spill and tombstone them durably."""
        col = self.clean_cols[s]
        if col is None or not self._spill[s]:
            return
        dead_rows: list = []
        for k in list(self._spill[s].keys()):
            rows = self._spill[s].pop(k)
            for vals, valids in rows:
                if vals[col] < wm:
                    dead_rows.append((vals, valids))
                else:
                    self._spill[s].add(k, (vals, valids))
        if dead_rows and self.state_tables[s] is not None:
            self.state_tables[s].write_chunk_rows(
                [(int(OP_DELETE), vals) for vals, _ in dead_rows])

    # ---------------------------------------------------------- rebuild
    def _stats_impl(self, side_state: JoinSideState):
        occ = jnp.sum(side_state.key_table.occupied.astype(jnp.int32))
        live = jnp.sum(side_state.live.astype(jnp.int32))
        # live distinct keys: a key is live if its chain has a live row
        CR = side_state.row_capacity
        return occ, live, side_state.top

    def _rehash_impl(self, side_state: JoinSideState, side: int,
                     new_ck: int, new_cr: int) -> JoinSideState:
        """Compact live rows into a fresh side (zombie purge / growth)."""
        CR = side_state.row_capacity
        keep = side_state.live | side_state.dirty   # dirty dead rows must
        # survive until persisted as deletes
        rank = jnp.cumsum(keep.astype(jnp.int32)) - 1
        sel = jnp.zeros(CR, dtype=jnp.int32).at[
            jnp.where(keep, rank, CR)].set(jnp.arange(CR, dtype=jnp.int32),
                                           mode="drop")
        n_keep = jnp.sum(keep.astype(jnp.int32))
        act = jnp.arange(CR) < n_keep
        key_cols = [side_state.rows[i][sel] for i in self.key_indices[side]]
        fresh = _empty_side(new_ck, new_cr, self._key_dtypes,
                            self._col_dtypes[side])
        table, slots, n_un = lookup_or_insert(fresh.key_table, key_cols,
                                              act & side_state.live[sel])
        fresh = JoinSideState(table, fresh.head, fresh.rows, fresh.valids,
                              fresh.next, fresh.live, fresh.dirty, fresh.top)
        fresh, _ = _bulk_insert(
            fresh, slots, act & side_state.live[sel],
            [r[sel] for r in side_state.rows],
            [v[sel] for v in side_state.valids],
            side_state.dirty[sel])
        # dirty dead rows: append after live ones (not linked into chains)
        dead = act & ~side_state.live[sel] & side_state.dirty[sel]
        rank_d = jnp.cumsum(dead.astype(jnp.int32)) - 1
        tgt = jnp.where(dead, fresh.top + rank_d, new_cr)
        rows = tuple(fr.at[tgt].set(r[sel], mode="drop")
                     for fr, r in zip(fresh.rows, side_state.rows))
        dirty = fresh.dirty.at[tgt].set(True, mode="drop")
        top = jnp.minimum(fresh.top + jnp.sum(dead.astype(jnp.int32)),
                          new_cr).astype(jnp.int32)
        return JoinSideState(fresh.key_table, fresh.head, rows, fresh.valids,
                             fresh.next, fresh.live, dirty, top)

    def _maybe_rebuild(self) -> None:
        for s in (LEFT, RIGHT):
            ck, cr = self.key_capacity[s], self.row_capacity[s]
            # load knowledge from the barrier watchdog fetch gates the
            # (rare, blocking) exact stats readback — same scheme as HashAgg
            if self._occ_known[s] <= 0.7 * ck and self._top_known[s] <= 0.7 * cr:
                continue
            occ, live, top = self._stats(self.sides[s])
            occ, live, top = int(occ), int(live), int(top)
            if occ <= 0.7 * ck and top <= 0.7 * cr:
                continue
            new_ck = ck * 2 if occ > 0.35 * ck else ck
            new_cr = cr * 2 if live > 0.35 * cr else cr
            self.sides[s] = self._rehash(self.sides[s], side=s,
                                         new_ck=new_ck, new_cr=new_cr)
            self.key_capacity[s], self.row_capacity[s] = new_ck, new_cr
            self._slot_epoch[s] = None       # geometry changed: restamp
            self.rebuilds += 1
            occ2, _, top2 = self._stats(self.sides[s])
            self._occ_known[s], self._top_known[s] = int(occ2), int(top2)

    # --------------------------------------------------------- watchdog
    def _check_watchdog(self) -> None:
        """ONE small blocking fetch of the device-accumulated error counts
        and per-side load stats — called per BARRIER, never per chunk (a
        per-chunk d2h fetch gates throughput on copy latency). Errors
        fail-stop BEFORE this epoch's checkpoint
        commits; recovery replays from the last committed epoch."""
        vals = np.asarray(self._watchdog_pack(
            self._errs_dev, self._occ_dev[LEFT], self._top_dev[LEFT],
            self._occ_dev[RIGHT], self._top_dev[RIGHT]))
        n_un, n_miss, n_mo, n_ro = (int(x) for x in vals[:4])
        for s in (LEFT, RIGHT):
            self._occ_known[s] = int(vals[4 + 2 * s])
            self._top_known[s] = int(vals[5 + 2 * s])
        if n_un:
            raise RuntimeError(
                f"join key-table overflow ({n_un} keys unresolved)")
        if n_mo:
            raise RuntimeError(
                f"join match-buffer overflow ({n_mo} matches dropped; "
                f"raise match_factor)")
        if n_ro:
            raise RuntimeError(
                f"join row-store overflow ({n_ro} rows dropped)")
        if n_miss:
            raise RuntimeError(
                f"join changelog inconsistency: {n_miss} deletes matched "
                f"no stored row")

    # ---------------------------------------------------- multi-chunk apply
    def _make_apply_scan(self, k: int, side: int):
        def scan_impl(own: JoinSideState, other: JoinSideState,
                      errs: jnp.ndarray, *chunks):
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *chunks)

            def step(carry, chunk):
                own_, errs_ = carry
                own_, cols, ops, vis, errs_, occ, top = self._apply_impl(
                    own_, other, errs_, chunk, side)
                return (own_, errs_), (cols, ops, vis, occ, top)

            (own2, errs2), (cols, ops, vis, occs, tops) = jax.lax.scan(
                step, (own, errs), stacked)
            # stacked per-step outputs [k, M] flatten into one chunk of
            # capacity k*M — row order is step-major, matching the
            # sequential per-chunk emission order
            flat_cols = tuple(
                Column(c.data.reshape(-1),
                       None if c.valid is None else c.valid.reshape(-1))
                for c in cols)
            return (own2, flat_cols, ops.reshape(-1), vis.reshape(-1),
                    errs2, occs[-1], tops[-1])

        return jit_state(scan_impl, donate_argnums=(0, 2),
                         name=f"hash_join_apply_scan{k}_s{side}")

    def _run_matches(self, side: int, chunk: StreamChunk) -> bool:
        p = self._run_chunks
        return (self._run_side == side and bool(p)
                and p[-1].capacity == chunk.capacity
                and jax.tree_util.tree_structure(p[-1])
                == jax.tree_util.tree_structure(chunk))

    def _enqueue_chunk(self, side: int, chunk: StreamChunk) -> list:
        if not self._use_chunk_batching:
            self._run_chunks, self._run_side = [chunk], side
            return self._drain_run()
        outs = []
        if self._run_chunks and not self._run_matches(side, chunk):
            outs.extend(self._drain_run())
        self._run_chunks.append(chunk)
        self._run_side = side
        if len(self._run_chunks) >= self._batch_max:
            outs.extend(self._drain_run())
        return outs

    def _drain_run(self) -> list:
        run, s = self._run_chunks, self._run_side
        if not run:
            return []
        self._run_chunks, self._run_side = [], None
        self._mem_check_reload(s, run)
        if len(run) == 1:
            (self.sides[s], cols, ops, vis, self._errs_dev, occ,
             top) = self._apply(self.sides[s], self.sides[1 - s],
                                self._errs_dev, run[0], side=s)
        else:
            # power-of-two batch bucket; fillers are all-invisible views
            # of the last chunk (no probe, no state change, no matches)
            k = 1 << (len(run) - 1).bit_length()
            if k > len(run):
                last = run[-1]
                filler = StreamChunk(last.columns, last.ops,
                                     jnp.zeros(last.capacity, dtype=bool),
                                     last.schema)
                run = run + [filler] * (k - len(run))
            scan = self._apply_scans.get((k, s))
            if scan is None:
                scan = self._make_apply_scan(k, s)
                self._apply_scans[(k, s)] = scan
            (self.sides[s], cols, ops, vis, self._errs_dev, occ,
             top) = scan(self.sides[s], self.sides[1 - s],
                         self._errs_dev, *run)
        self._occ_dev[s], self._top_dev[s] = occ, top
        self._dirty_since_flush[s] = True
        out = StreamChunk(
            tuple(cols[i] for i in self.output_indices), ops, vis,
            self.schema)
        if self.condition is not None:
            pred = self.condition.eval(cols)
            out = out.mask(pred.data & pred.valid_mask())
        return [out]

    # ----------------------------------------------------------- stream
    async def execute(self):
        first = True
        async for kind, s, msg in barrier_align(*self.inputs):
            if kind == "chunk":
                for out in self._enqueue_chunk(s, msg):
                    yield out
            elif kind == "barrier":
                for out in self._drain_run():
                    yield out
                barrier: Barrier = msg
                if first or barrier.kind is BarrierKind.INITIAL:
                    first = False
                    for st in self.state_tables:
                        if st is not None:
                            st.init_epoch(barrier.epoch.curr)
                    self.recover()
                    yield barrier
                    continue
                stopping = barrier.mutation is not None and barrier.is_stop_any()
                # watchdog_interval=None => NO fetch ever, not even at stop
                # (same contract as HashAggExecutor: a blocking d2h
                # fetch serialises with dispatch); correctness
                # in that mode rests on CPU-backend tests + the device-side
                # purge below.
                if self.watchdog_interval and (
                        stopping or any(self._dirty_since_flush)):
                    self._check_watchdog()
                # LRU epoch stamp BEFORE persist resets the dirty bits
                if self._mem_lru_on:
                    for s2 in (LEFT, RIGHT):
                        if self._dirty_since_flush[s2]:
                            self._mem_stamp(s2, barrier.epoch.curr)
                self._persist(barrier)
                for s2 in (LEFT, RIGHT):
                    if (self._pending_clean[s2] is not None
                            and self.clean_cols[s2] is not None):
                        self._clean_spilled(s2, self._pending_clean[s2])
                        self.sides[s2] = self._evict(
                            self.sides[s2], self._pending_clean[s2], side=s2)
                        self._pending_clean[s2] = None
                        if self.watchdog_interval is None:
                            # transfer-free mode: reclaim tombstoned rows
                            # with a same-capacity device rehash — without
                            # occupancy readbacks the host can never
                            # trigger one (see HashAggExecutor)
                            self.sides[s2] = self._rehash(
                                self.sides[s2], side=s2,
                                new_ck=self.key_capacity[s2],
                                new_cr=self.row_capacity[s2])
                self._maybe_rebuild()
                yield barrier
            else:
                # watermark: drain first so emitted outputs precede it
                for out in self._drain_run():
                    yield out
                wm: Watermark = msg
                if self.clean_cols[s] is not None and wm.col_idx == self.clean_cols[s]:
                    self._pending_clean[s] = wm.val
                # key-column watermarks: emit min over both sides on both
                # output key positions (reference join watermark derivation)
                if wm.col_idx in self.key_indices[s]:
                    kpos = self.key_indices[s].index(wm.col_idx)
                    self._key_wms[s][kpos] = wm.val
                    other_wm = self._key_wms[1 - s].get(kpos)
                    if other_wm is not None:
                        val = min(wm.val, other_wm)
                        if self._emitted_key_wm.get(kpos) != val:
                            self._emitted_key_wm[kpos] = val
                            n_left = len(self.inputs[LEFT].schema)
                            for full_idx in (self.key_indices[LEFT][kpos],
                                             n_left + self.key_indices[RIGHT][kpos]):
                                if full_idx in self.output_indices:
                                    yield Watermark(
                                        self.output_indices.index(full_idx),
                                        wm.data_type, val)
