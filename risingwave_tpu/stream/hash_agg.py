"""HashAgg executor — grouped streaming aggregation with device state.

Reference: src/stream/src/executor/hash_agg.rs — groups keyed by `HashKey`
live in a managed cache; chunks are applied group-wise (`apply_chunk`:349);
at each barrier the executor diffs old vs new agg values and emits change
rows (`flush_data`:436), then commits its state tables.

TPU re-design: the group map is a `HashTable` in HBM plus parallel state
arrays [C] (one per agg call) and a row-count array. Applying a chunk is one
jitted step: slot assignment (open addressing) -> segment-reduce partials by
slot -> combine into states, marking touched slots dirty. The barrier flush
is a second jitted step that compacts dirty slots to the front and lays out
UpdateDelete/UpdateInsert pairs (Insert for born groups, Delete for died
ones) exactly like the reference's changelog contract. Zombie slots (groups
at row_count 0) keep their keys so probe chains stay intact; the executor
rebuilds/grows the table when load crosses the threshold.

min/max over append-only input keep one scalar per group; over a retracting
input (the reference's materialized input state, aggregation/minput.rs) they
keep a bounded top-K value buffer per group (ops/extrema.py) whose bound is a
fail-stop contract: the barrier watchdog raises before the checkpoint commits
where the buffer can no longer know the extremum.
"""

from __future__ import annotations

import asyncio
from dataclasses import dataclass
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import (
    Column, StreamChunk, OP_DELETE, OP_INSERT, OP_UPDATE_DELETE,
    OP_UPDATE_INSERT, op_sign,
)
from ..common.types import Field, Schema
from ..expr.agg import AggCall, AggKind
from ..ops.extrema import (
    extrema_emit, extrema_empty, extrema_lossy_groups,
    extrema_mask_keep, extrema_underflow, extrema_update,
)
from ..memory.accounting import pytree_bytes
from ..memory.spill import HostSpill
from ..ops.hash_table import (
    BUCKET_SLOTS, HashTable, compact_mask, lookup_or_insert_counted,
    lru_stamp, needs_rebuild,
)
from ..ops.jit_state import jit_state
from ..state.state_table import StateTable
from ..utils.d2h import defer_prefix_flush, fetch_small, off_loop
from ..utils.trace import span
from ..utils.metrics import (
    GLOBAL_METRICS, HASH_AGG_EMIT_ROWS, HASH_AGG_EVICT_GROUPS,
    HASH_AGG_EXTREMA_ERRORS, HASH_AGG_EXTREMA_LOSSY_GROUPS, HASH_AGG_PURGES,
    HASH_AGG_REHASH_ROWS, HASH_PROBE_FALLBACK_ROWS,
)
from .executor import Executor
from .message import Barrier, BarrierKind, Watermark


# the fail-stop counts of a retractable MIN/MAX (ops/extrema.py), in the
# order the apply step accumulates them and
# `hash_agg_extrema_errors_total{kind=...}` names them
EXTREMA_KINDS = ("underflow", "dropped_delete", "negative_residue")

# the narrowest barrier flush: this many dirty slots (twice the rows)
FLUSH_MIN_SLOTS = 128

# A table that is mostly zombies (groups the watermark cleaned, or that
# emptied) is purged at its own capacity once occupancy PLUS the most slots
# one interval has claimed would pass this share of it. Lower than
# `needs_rebuild`'s 0.7, which is a mark for keys that arrive a few at a
# time: `lookup_or_insert` places a whole chunk's new keys by the bucket
# fills it found BEFORE the chunk, so a chunk of fresh keys overflows a
# bucket by the tail of its arrivals in one bucket, long before the mean
# fill is near 16 (32,768 fresh keys into 2^19 slots: first unplaced rows
# between 0.62 and 0.69 full, measured; memory_maintain's `_mem_cap_for`
# keeps to the same 0.35 for the same reason). A purge costs what
# survives it (`_rehash_keep`), an overflow costs the epoch.
ZOMBIE_PURGE_MARK = 0.35

# rows one call of the recovery's replay program takes (never more than
# the table has slots)
RECOVER_BATCH = 1 << 14

# survivors one step of a rebuild's loop re-inserts (never more than the
# old table has slots): a rebuild costs a step per block of survivors, and
# no step is as wide as the table. On the chip a step of 2^12 takes 7.1 ms
# and one of 2^14 18.5 ms (1,824 survivors of 2^19 slots: one step either
# way), and 180,000 survivors of 2^20 slots take 0.207 s in 44 steps and
# 0.221 s in 11 (PERF.md section 6, PR 43)
REHASH_BLOCK = 1 << 12


@jax.tree_util.register_pytree_node_class
@dataclass
class AggState:
    """Device state of one HashAgg instance (all arrays share capacity C)."""

    table: HashTable
    agg_states: tuple[jnp.ndarray, ...]   # one [C] per agg call
    row_count: jnp.ndarray                # int64 [C] — group liveness
    dirty: jnp.ndarray                    # bool [C] — touched since flush
    prev_exists: jnp.ndarray              # bool [C] — group was in output
    prev_emit: tuple[jnp.ndarray, ...]    # last emitted value per agg [C]

    def tree_flatten(self):
        return ((self.table, self.agg_states, self.row_count, self.dirty,
                 self.prev_exists, self.prev_emit), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        table, agg_states, row_count, dirty, prev_exists, prev_emit = children
        return cls(table, tuple(agg_states), row_count, dirty,
                   prev_exists, tuple(prev_emit))


class HashAggExecutor(Executor):
    # the name MemoryManager.register() gave this agg: the `executor`
    # label of its series (utils/metrics.py HASH_AGG_*)
    mem_name: Optional[str] = None

    def __init__(self, input: Executor, group_key_indices: Sequence[int],
                 agg_calls: Sequence[AggCall], capacity: int = 1 << 16,
                 state_table: Optional[StateTable] = None,
                 group_key_names: Optional[Sequence[str]] = None,
                 cleaning_watermark_col: Optional[int] = None,
                 watchdog_interval: Optional[int] = 1,
                 minput_k: int = 32):
        self.input = input
        self.group_key_indices = tuple(group_key_indices)
        self.agg_calls = tuple(agg_calls)
        self.specs = tuple(c.spec() for c in agg_calls)
        # retractable MIN/MAX use materialized-input top-K value buffers
        # (reference minput.rs); linear aggs keep one scalar per group
        self.minput_k = minput_k
        self._retractable = tuple(
            c.kind in (AggKind.MIN, AggKind.MAX) and not c.append_only
            for c in agg_calls)
        in_schema = input.schema
        gk_names = list(group_key_names or
                        [in_schema[i].name for i in self.group_key_indices])
        self.schema = Schema(tuple(
            [Field(n, in_schema[i].data_type)
             for n, i in zip(gk_names, self.group_key_indices)]
            + [Field(f"agg{j}", c.ret_type) for j, c in enumerate(agg_calls)]))
        self.pk_indices = tuple(range(len(self.group_key_indices)))
        self.capacity = capacity
        self.state_table = state_table
        # Watermark state cleaning (reference: StateTable::update_watermark
        # state_table.rs:1029 -> Hummock table watermarks): groups whose
        # watermark-column key falls below the watermark can never be touched
        # again, so their state is zeroed on device at the barrier. The slot
        # stays occupied (probe chains intact) until a rebuild purges it.
        # `cleaning_watermark_col` is an INPUT column index and must be one
        # of the group keys.
        self.cleaning_watermark_key: Optional[int] = (
            None if cleaning_watermark_col is None
            else self.group_key_indices.index(cleaning_watermark_col))
        self._pending_clean_wm: Optional[int] = None
        # group-key watermarks observed since the last flush, by output
        # position. The aggregate buffers an interval's updates until the
        # barrier flush, so a watermark forwarded on arrival would
        # OVERTAKE them: downstream (a join cleaning its state by this
        # watermark) evicts rows whose retraction is still sitting in
        # this executor, and fail-stops on "delete matched no stored
        # row" as soon as one interval spans more event time than the
        # source's watermark lag. Held until the flushed chunk is out
        # (reference: hash_agg.rs `buffered_watermarks`, emitted after
        # flush_data at the barrier).
        self._held_wms: dict[int, Watermark] = {}
        self.identity = f"HashAgg(keys={self.group_key_indices})"
        self._key_dtypes = tuple(
            in_schema[i].data_type.jnp_dtype for i in self.group_key_indices)
        self.state = self._initial_state(capacity)
        # State-threading programs donate the AggState pytree (and the
        # device watchdog accumulator) so XLA updates the table buffers in
        # place: `self.state = self._apply(self.state, ...)` is the only
        # reference, which is the donation contract. Read-only views
        # (_live_zombie, _evict_keys, _persist_view) must NOT donate —
        # the state stays live after them.
        self._apply = jit_state(self._apply_impl, donate_argnums=(0, 1),
                                name="hash_agg_apply")
        self._flush = jit_state(self._flush_impl, donate_argnums=(0,),
                                static_argnames=("n_slots",),
                                name="hash_agg_flush")
        self._live_zombie = jit_state(self._live_zombie_impl,
                                      name="hash_agg_live_zombie")
        self._evict = jit_state(self._evict_impl, donate_argnums=(0,),
                                name="hash_agg_evict")
        self._evict_keys = jit_state(self._evict_keys_impl,
                                     name="hash_agg_evict_keys")
        self._rehash = jit_state(self._rehash_impl, static_argnums=1,
                                 donate_argnums=(0,), name="hash_agg_rehash")
        self._persist_view = jit_state(self._persist_view_impl,
                                       static_argnames=("n_slots",),
                                       name="hash_agg_persist_view")
        # multi-chunk apply: chunks buffered within a barrier interval are
        # applied in ONE dispatch via lax.scan over a stacked batch (the
        # sharded subclass opts out — its programs are shard_map-wrapped)
        self._use_chunk_batching = True
        self._batch_max = 8
        self._pending_chunks: list[StreamChunk] = []
        self._apply_scans: dict[int, object] = {}
        # load/overflow watchdog (see _check_watchdog). watchdog_interval =
        # barriers between watchdog fetches; None disables the fetch
        # ENTIRELY (even at stop) — a blocking d2h fetch serialises
        # with program dispatch, so latency-critical pipelines can keep
        # the whole process transfer-free. In that
        # mode correctness rests on CPU-backend tests of the same pipeline
        # shapes and on device-side zombie purges keeping occupancy
        # bounded; overflow still accumulates on device for post-hoc
        # inspection.
        if watchdog_interval not in (None, 1):
            raise ValueError(
                "watchdog_interval must be 1 (check before every checkpoint "
                "commit) or None (transfer-free mode): any lag would let a "
                "checkpoint commit state whose overflow counter was never "
                "checked, defeating the fail-stop contract")
        self.watchdog_interval = watchdog_interval
        self.rebuilds = 0
        self._occ_known = 0
        # the most slots one barrier interval has claimed, and the groups
        # alive after this barrier's evict (None: no watchdog fetch at it);
        # both from the watchdog fetch, for `_maybe_rebuild_at_barrier`
        self._claim_peak = 0
        self._live_known: Optional[int] = None
        self._applied_since_flush = False
        # ---- HBM memory manager hooks (memory/manager.py) ----
        # LRU hotness is an int64 epoch stamp PER SLOT, advanced at each
        # barrier from the interval's dirty bitmap — one elementwise
        # select per interval, no device->host sync on the data path.
        # Cold slots spill their rows to the host dict; a later touch of
        # a spilled key reloads it at drain time before the chunk
        # applies.
        self._mem_lru_on = False
        self._slot_epoch = None             # int64 [C] device, lazy
        # shrink floor: below ~64 buckets the two-choice overflow
        # probability stops being negligible at moderate load, so
        # eviction never shrinks under this (tests override)
        self._mem_min_capacity = 1024
        self._spill = HostSpill()
        self.mem_evicted_bytes = 0
        self.mem_reload_count = 0
        # keys the reload-LFU guard kept resident through an eviction
        # round (memory/manager.py ReloadGuard, set as self.mem_guard)
        self.mem_guard_protected = 0
        self._lru_stamp = jit_state(self._lru_stamp_impl,
                                    donate_argnums=(1,),
                                    name="hash_agg_lru_stamp")
        self._mem_stats = jit_state(self._mem_stats_impl,
                                    name="hash_agg_mem_stats")
        self._mem_pack = jit_state(self._mem_pack_impl,
                                   name="hash_agg_mem_pack")
        self._mem_rehash = jit_state(self._mem_rehash_impl,
                                     static_argnames=("new_capacity",),
                                     donate_argnums=(0,),
                                     name="hash_agg_mem_rehash")
        self._mem_reloads: dict[int, object] = {}
        self._recover_rows = jit_state(self._recover_impl,
                                       donate_argnums=(0, 1),
                                       name="hash_agg_recover")
        # device-accumulated watchdog counters, int32 [2]: rows the table
        # could not place or fold plus the retractable calls' errors
        # (fail-stop), rows whose probe went past the fingerprint lane and
        # one verify (hash_table._probe; a metric); with a retractable
        # MIN/MAX three more, the errors by kind (EXTREMA_KINDS)
        self._overflow_width = 2 + (len(EXTREMA_KINDS)
                                    if any(self._retractable) else 0)
        self._overflow_dev = jnp.zeros(self._overflow_width,
                                       dtype=jnp.int32)
        self._probe_fallback_seen = 0
        self._extrema_errs_seen = [0] * len(EXTREMA_KINDS)
        self._occ_dev = jnp.zeros((), dtype=jnp.int32)
        self._watchdog_pack = jit_state(self._watchdog_pack_impl,
                                        name="hash_agg_watchdog_pack")
        # what the last watchdog fetch read of this barrier interval, for
        # the actor's phase dict (take_phase_counts)
        self._phase_counts: dict = {}
        # The barrier's flush and persist view lay the dirty slots out at
        # the front of capacity-wide buffers; a consumer's cost follows
        # the WIDTH of the chunk it is handed (a second agg's probe, a
        # join's sorts, the MV's d2h), so a 2^20-slot table that moved
        # 6,000 groups handed on 2^21-row chunks. Where the watchdog fetch
        # has brought the dirty count, both programs stop at a power-of-two
        # number of dirty slots that holds it with room. The width only
        # ever grows (each new one is a compile, and a consumer's), so
        # steady traffic settles on one in its first interval. None = not
        # known (no watchdog fetch): the whole capacity.
        self._dirty_slots_known: Optional[int] = None
        self._flush_slots = FLUSH_MIN_SLOTS

    def _watchdog_pack_impl(self, state: AggState, ov, occ, wm):
        """The barrier's one fetch: [overflow, occupied, probe fallback,
        rows the flush that follows will emit, lossy groups, dirty slots,
        live groups, live groups the pending cleaning watermark `wm` is
        about to evict (`_evict_keys_impl`'s mask; 0 where the executor
        cleans nothing), then the extrema errors by kind where a call is
        retractable]. The emit count repeats `_flush_impl`'s visibility per
        slot, without its compaction: both read the same state, nothing
        runs between."""
        exists = state.row_count > 0
        unchanged = state.prev_exists & exists
        n_lossy = jnp.int32(0)
        for j, (st, pe) in enumerate(zip(state.agg_states,
                                         state.prev_emit)):
            unchanged &= self._call_emit(j, st) == pe
            if self._retractable[j]:
                n_lossy += extrema_lossy_groups(st, state.row_count)
        moved = state.dirty & ~unchanged
        n_emit = (jnp.sum((moved & state.prev_exists).astype(jnp.int32))
                  + jnp.sum((moved & exists).astype(jnp.int32)))
        n_dirty = jnp.sum(state.dirty.astype(jnp.int32))
        n_live = jnp.sum(exists.astype(jnp.int32))
        n_evict = jnp.int32(0)
        if self.cleaning_watermark_key is not None:
            below = state.table.keys[self.cleaning_watermark_key] < wm
            n_evict = jnp.sum((exists & state.table.occupied
                               & below).astype(jnp.int32))
        return jnp.concatenate([
            jnp.stack([ov[0], occ, ov[1], n_emit, n_lossy, n_dirty,
                       n_live, n_evict]),
            ov[2:]])

    def take_phase_counts(self) -> dict:
        """This barrier interval's `agg_emit_rows` and `agg_evict_groups`
        (and, with a retractable MIN/MAX, `agg_extrema_lossy_groups`) for
        the actor's phase dict: host numbers the watchdog fetch brought,
        absent where it made none; `agg_rehash_rows` (the groups it
        re-inserted) where the barrier rebuilt the table, and `agg_purges`
        where that dropped the zombies at the table's own capacity."""
        counts, self._phase_counts = self._phase_counts, {}
        return counts

    def fence_tokens(self) -> list:
        # the state root depends on every program dispatched this epoch,
        # including barrier-time evict/purge work
        return [self.state.table.keys[0]] + super().fence_tokens()

    # ------------------------------------------------------------ state
    def _initial_state(self, capacity: int) -> AggState:
        """Constructor-time state; sharded variants override this to place
        global arrays over the mesh while _empty_state stays LOCAL (it is
        called inside jitted per-shard impls like _rehash_impl)."""
        return self._empty_state(capacity)

    # ---- per-call state polymorphism: linear scalar vs extrema buffer
    def _call_init_state(self, j: int, capacity: int):
        if self._retractable[j]:
            return extrema_empty(capacity, self.minput_k,
                                 self.specs[j].state_dtype)
        return self.specs[j].init_state((capacity,))

    def _call_emit(self, j: int, st):
        if self._retractable[j]:
            # match the scalar path's ret-type cast (schema dtype contract)
            return extrema_emit(
                st, self.specs[j].init, self.specs[j].state_dtype).astype(
                    self.agg_calls[j].ret_type.jnp_dtype)
        return self.specs[j].emit(st)

    def _empty_state(self, capacity: int) -> AggState:
        table = HashTable.empty(capacity, self._key_dtypes)
        return AggState(
            table=table,
            agg_states=tuple(self._call_init_state(j, capacity)
                             for j in range(len(self.specs))),
            row_count=jnp.zeros(capacity, dtype=jnp.int64),
            dirty=jnp.zeros(capacity, dtype=bool),
            prev_exists=jnp.zeros(capacity, dtype=bool),
            prev_emit=tuple(
                jnp.zeros(capacity, dtype=c.ret_type.jnp_dtype)
                for c in self.agg_calls),
        )

    # ------------------------------------------------------- chunk apply
    def _apply_impl(self, state: AggState, overflow, chunk: StreamChunk):
        key_cols = [chunk.columns[i].data for i in self.group_key_indices]
        table, slots, n_unresolved, n_fallback = lookup_or_insert_counted(
            state.table, key_cols, chunk.vis)
        C = table.capacity
        ok = slots >= 0
        # segment id per row; trash segment C for masked rows
        seg = jnp.where(ok, slots, C)
        signs = jnp.where(ok, op_sign(chunk.ops), 0)
        row_count = state.row_count + jax.ops.segment_sum(
            signs.astype(jnp.int64), seg, C + 1)[:C]
        new_states = []
        n_err = jnp.int32(0)
        ext_errs = []       # per retractable call, int32 [3] by EXTREMA_KINDS
        for j, (spec, call, st) in enumerate(
                zip(self.specs, self.agg_calls, state.agg_states)):
            if call.arg is None:
                values = jnp.zeros(chunk.capacity, dtype=spec.state_dtype)
                valid_in = jnp.ones(chunk.capacity, dtype=bool)
            else:
                col = chunk.columns[call.arg]
                values = col.data
                # NULL inputs don't contribute (reference strict agg
                # semantics)
                valid_in = col.valid_mask()
            if self._retractable[j]:
                st2, e = extrema_update(
                    st, values.astype(spec.state_dtype), valid_in, signs,
                    seg, C, is_max=(call.kind is AggKind.MAX))
                # a group no row is left of holds no untracked value
                st2 = (st2[0], st2[1], st2[2] & (row_count > 0))
                # lossy + emptied + live rows = unknowable extremum
                e = jnp.concatenate(
                    [extrema_underflow(st2, row_count)[None], e])
                n_err = n_err + jnp.sum(e)
                ext_errs.append(e)
                new_states.append(st2)
            else:
                row_signs = jnp.where(valid_in, signs, 0)
                part = spec.partial(values, row_signs, seg, C + 1)[:C]
                new_states.append(spec.combine(st, part))
        dirty = state.dirty.at[seg].set(True, mode="drop")
        new_state = AggState(table, tuple(new_states), row_count, dirty,
                             state.prev_exists, state.prev_emit)
        # watchdog counters stay ON DEVICE: overflow accumulates across the
        # epoch and occupancy rides along as the latest value; the host
        # fetches both ONCE per barrier. A d2h copy serialises into the
        # device stream, so per-chunk copies would gate throughput on
        # copy latency.
        occ = jnp.sum(table.occupied.astype(jnp.int32))
        # keep the accumulator's dtype stable (the segment sums promote to
        # int64): donation can only reuse the input buffer — and lax.scan
        # only accepts the carry — when the dtype round-trips
        counts = jnp.stack([n_unresolved + n_err, n_fallback])
        if ext_errs:
            counts = jnp.concatenate([counts, sum(ext_errs)])
        overflow = (overflow + counts).astype(overflow.dtype)
        return new_state, overflow, occ

    # ---------------------------------------------------------- flush
    def _flush_impl(self, state: AggState, n_slots: Optional[int] = None):
        """Emit the barrier diff as one chunk of capacity 2 * n_slots (2*C
        where `n_slots` is None) with interleaved UD/UI pairs; returns
        (state', chunk arrays...). `n_slots` must hold every dirty slot:
        the caller has the count from the watchdog fetch.

        Compaction is a cumsum-scatter (O(C) scan), not a sort: dirty slot
        with rank j lands at output positions 2j (old value) / 2j+1 (new)."""
        C = state.table.capacity
        R = C if n_slots is None else n_slots
        exists_now = state.row_count > 0
        dirty = state.dirty
        rank = jnp.cumsum(dirty.astype(jnp.int32)) - 1   # rank among dirty
        slot_ids = jnp.arange(C, dtype=jnp.int32)
        # scatter: d_slot[j] = slot of j-th dirty entry (garbage past n_dirty)
        d_slot = jnp.zeros(C, dtype=jnp.int32).at[
            jnp.where(dirty, rank, C)].set(slot_ids, mode="drop")
        n_dirty = jnp.sum(dirty.astype(jnp.int32))
        d_slot = d_slot[:R]
        existed = state.prev_exists[d_slot]
        exists = exists_now[d_slot]
        is_dirty = slot_ids[:R] < n_dirty

        # no-change skip (reference agg_group.rs:71 build_change -> NoChange):
        # a group that existed before, still exists, and whose emitted outputs
        # are all unchanged produces no changelog rows
        unchanged = existed & exists
        for j, (st, pe) in enumerate(zip(state.agg_states, state.prev_emit)):
            unchanged &= self._call_emit(j, st)[d_slot] == pe[d_slot]

        # output row j at positions 2j (old) and 2j+1 (new)
        vis_old = is_dirty & existed & ~unchanged   # UD or Delete
        vis_new = is_dirty & exists & ~unchanged    # UI or Insert
        ops_old = jnp.where(exists, OP_UPDATE_DELETE, OP_DELETE)
        ops_new = jnp.where(existed, OP_UPDATE_INSERT, OP_INSERT)

        def interleave(a, b):
            return jnp.stack([a, b], axis=1).reshape(2 * R)

        out_ops = interleave(ops_old, ops_new).astype(jnp.int8)
        out_vis = interleave(vis_old, vis_new)
        out_cols = []
        for tk in state.table.keys:
            v = tk[d_slot]
            out_cols.append(interleave(v, v))
        new_emit = []
        for j, (st, pe) in enumerate(zip(state.agg_states, state.prev_emit)):
            cur = self._call_emit(j, st)
            new_emit.append(cur)
            out_cols.append(interleave(pe[d_slot], cur[d_slot]))

        prev_exists = exists_now
        prev_emit = tuple(new_emit)
        state2 = AggState(state.table, state.agg_states, state.row_count,
                          jnp.zeros(C, dtype=bool), prev_exists, prev_emit)
        return state2, tuple(out_cols), out_ops, out_vis

    def _live_zombie_impl(self, state: AggState):
        """int32 [2]: occupied slots, live groups — one small fetch."""
        occ = jnp.sum(state.table.occupied.astype(jnp.int32))
        live = jnp.sum((state.row_count > 0).astype(jnp.int32))
        return jnp.stack([occ, live])

    def _evict_keys_impl(self, state: AggState, watermark):
        """Compacted group keys of live groups below the cleaning watermark —
        the rows that must be DELETED from the durable state table when the
        device state is zeroed (reference: StateTable::update_watermark ->
        Hummock table-watermark pruning keeps committed state bounded)."""
        j = self.cleaning_watermark_key
        evict = (state.table.occupied & (state.table.keys[j] < watermark)
                 & (state.row_count > 0))
        C = state.table.capacity
        rank = jnp.cumsum(evict.astype(jnp.int32)) - 1
        sel = jnp.zeros(C, dtype=jnp.int32).at[
            jnp.where(evict, rank, C)].set(jnp.arange(C, dtype=jnp.int32),
                                           mode="drop")
        n = jnp.sum(evict.astype(jnp.int32))
        return tuple(tk[sel] for tk in state.table.keys), n

    def _evict_impl(self, state: AggState, watermark) -> AggState:
        """Zero out groups below the state-cleaning watermark. Slots remain
        occupied zombies (chain-safe); rebuilds reclaim them later."""
        j = self.cleaning_watermark_key
        evict = state.table.occupied & (state.table.keys[j] < watermark)
        keep = ~evict
        def zero_call(jj, st):
            if self._retractable[jj]:
                return extrema_mask_keep(st, keep)
            return jnp.where(keep, st, self.specs[jj].init)

        return AggState(
            table=state.table,
            agg_states=tuple(
                zero_call(jj, st)
                for jj, st in enumerate(state.agg_states)),
            row_count=jnp.where(keep, state.row_count, 0),
            dirty=state.dirty & keep,
            prev_exists=state.prev_exists & keep,
            prev_emit=tuple(jnp.where(keep, p, 0) for p in state.prev_emit),
        )

    def _rehash_impl(self, state: AggState, new_capacity: int) -> AggState:
        """Device-side rebuild: re-insert surviving groups into a fresh
        table of `new_capacity` slots, a block at a time. Pure XLA — no
        host roundtrip; only a capacity CHANGE triggers a recompile
        (distinct static shape)."""
        keep = state.table.occupied & (
            (state.row_count > 0) | (state.dirty & state.prev_exists))
        return self._rehash_keep(state, keep, new_capacity)[0]

    def _rehash_keep(self, state: AggState, keep: jnp.ndarray,
                     new_capacity: int):
        """Shared rebuild body: exactly `state`'s `keep` slots in a fresh
        state of `new_capacity` (growth/purge keeps all survivors; memory
        eviction additionally drops the cold groups), and the survivors no
        slot was found for: none up to 0.69 of the new table in
        tests/test_hash_agg_rehash.py, where ONE block leaves some at
        half; a barrier's rebuild asks for half at most and fail-stops on
        any.

        The survivors are compacted to the front of ONE capacity-wide
        index lane and re-inserted REHASH_BLOCK at a time by a loop whose
        trip count the device takes from its own survivor count: a step
        gathers a block's keys and lanes from the old state, inserts the
        keys into the carried table and writes the lanes at their slots
        (`_write_rows`, the replay's and the reload's step). What a
        rebuild costs follows what survives it, whatever the capacities:
        the compaction and the fresh state's fills are all that is as wide
        as a table. Each block is placed by the bucket fills the blocks
        before it left (`lookup_or_insert` reads them once a call), so the
        survivors spread over their two buckets as a stream of chunks
        would, not as one chunk of fresh keys (ZOMBIE_PURGE_MARK)."""
        C = state.table.capacity
        W = min(C, REHASH_BLOCK)
        sel, n_keep = compact_mask(keep)
        if C % W:
            # a whole last block: a slice never starts where it would end
            # past the lane
            sel = jnp.pad(sel, (0, W - C % W))
        lanes = (state.agg_states, state.row_count, state.dirty,
                 state.prev_exists, state.prev_emit)

        def block(carry):
            i, fresh, n_un = carry
            at = i * W
            blk = jax.lax.dynamic_slice(sel, (at,), (W,))
            active = at + jnp.arange(W, dtype=jnp.int32) < n_keep
            fresh, un, _ = self._write_rows(
                fresh, [tk[blk] for tk in state.table.keys], active,
                *jax.tree_util.tree_map(lambda lane: lane[blk], lanes))
            return i + 1, fresh, n_un + un.astype(jnp.int32)

        # inside a shard_map a loop's carry keeps its type: what is the
        # same on every shard (the empty state, the counters) enters the
        # loop as per-shard as the survivors make it
        per_shard = jax.typeof(n_keep).vma
        init = jax.tree_util.tree_map(
            lambda x: jax.lax.pcast(
                x, tuple(per_shard - jax.typeof(x).vma), to="varying"),
            (jnp.int32(0), self._empty_state(new_capacity), jnp.int32(0)))
        _, fresh, n_un = jax.lax.while_loop(
            lambda carry: carry[0] * W < n_keep, block, init)
        return fresh, n_un

    # --------------------------------------------------------- rebuild
    def _rehash_to(self, new_capacity: int) -> None:
        """Dispatch the device-side rehash: zombies dropped, the survivors
        re-inserted into a fresh table of `new_capacity` slots."""
        self.state = self._rehash(self.state, new_capacity)
        self.capacity = new_capacity
        self.rebuilds += 1
        # slot geometry changed: restamp lazily (everything hot, and one
        # interval later the LRU discriminates again)
        self._slot_epoch = None

    def _rebuild(self, new_capacity: int) -> int:
        """Purge zombies / grow, and read the rebuilt occupancy back ON the
        calling thread: the memory manager's reload path (by design on the
        loop). The barrier's own rebuild awaits that readback off the loop
        (`_maybe_rebuild_at_barrier`)."""
        self._rehash_to(new_capacity)
        return int(fetch_small(self._live_zombie(self.state))[0])

    def _precompile_purge(self):
        """Compile (lower + compile, nothing runs) the two programs of a
        same-capacity purge for the table as it stands, where zombies are
        this executor's steady state: it cleans by watermark and watches
        its occupancy. The first purge comes many barriers after warm-up;
        compiled there it is a compile inside somebody's timed window.
        After a growth the programs of the new capacity compile when first
        needed, as the growth itself does.

        On a worker thread, started at the INITIAL barrier and awaited at
        the end of the first barrier after it (`execute`), not on the way:
        no purge is due before an interval has run, and a recovery does
        not hold its replay for two executables it will not call for many
        checkpoints (the rehash is an insert of REHASH_BLOCK rows in a
        loop, as large as the replay's program). Only this pair:
        executables loaded side by side on the v5e's host each took three
        to four times as long (PERF.md section 6, PR 40), so the replay
        and the first apply stay where they are asked for, on the loop.
        Returns the task, or None where there is nothing to compile."""
        if self.cleaning_watermark_key is None or not self.watchdog_interval:
            return None
        state, capacity = self.state, self.capacity

        def compile_both():
            self._live_zombie.precompile(state)
            self._rehash.precompile(state, capacity)

        return asyncio.ensure_future(asyncio.to_thread(compile_both))

    async def _check_watchdog(self) -> None:
        """ONE small fetch of the device-accumulated counters
        (`_watchdog_pack_impl`) — called per BARRIER, never per chunk. The
        counters accumulate on device across the epoch; fetching them per
        chunk gates throughput on d2h copy latency, so the fetch is a plain
        np.asarray of a few scalars, once per barrier: the pack dispatched
        here, the wait — for the device to reach it, behind the interval's
        applies — awaited off the loop (utils/d2h.py `off_loop`).

        Overflow fail-stops BEFORE this epoch's checkpoint commits, so a
        chunk the table dropped rows from is never made durable; recovery
        replays from the last committed epoch (SURVEY.md §3.5). Capacity
        provisioning + barrier-time growth make this a last-resort
        watchdog."""
        wm = self._pending_clean_wm
        vals = await off_loop(fetch_small, self._watchdog_pack(
            self.state, self._overflow_dev, self._occ_dev,
            jnp.int64(np.iinfo(np.int64).min if wm is None else wm)))
        self._note_probe_fallback(int(vals[2]))
        self._note_flush_counts(int(vals[3]), int(vals[4]), int(vals[7]))
        self._dirty_slots_known = int(vals[5])
        ext = self._note_extrema_errors([int(v) for v in vals[8:]])
        n_un = int(vals[0])
        if ext:
            raise RuntimeError(
                f"retractable MIN/MAX lost its bound mid-epoch ({ext}; "
                f"top-{self.minput_k} value buffer per group); the "
                f"extremum is unknowable without a refill from durable "
                f"input state, so the epoch must not commit")
        if n_un:
            raise RuntimeError(
                f"hash-agg table overflow mid-epoch ({n_un} rows, "
                f"capacity {self.capacity}); recovery must replay the "
                f"epoch with a larger table")
        occ = int(vals[1])
        # the slots this interval claimed: what the next one may ask for
        self._claim_peak = max(self._claim_peak, occ - self._occ_known)
        self._occ_known = occ
        # the groups alive once this barrier's evict has run
        self._live_known = int(vals[6]) - int(vals[7])

    def _note_probe_fallback(self, total: int) -> None:
        """Publish the device's running fallback-row count as the increase
        since the last watchdog fetch."""
        HASH_PROBE_FALLBACK_ROWS.inc(total - self._probe_fallback_seen)
        self._probe_fallback_seen = total

    def _note_flush_counts(self, n_emit: int, n_lossy: int,
                           n_evict: int) -> None:
        """What the flush after this watchdog fetch emits, how many live
        groups the barrier's evict cleans, and how many groups of a
        retractable MIN/MAX are lossy: into the registry, and kept for the
        epoch trace."""
        label = self.mem_name or self.identity
        GLOBAL_METRICS.counter(HASH_AGG_EMIT_ROWS, executor=label).inc(
            n_emit)
        GLOBAL_METRICS.counter(HASH_AGG_EVICT_GROUPS, executor=label).inc(
            n_evict)
        self._phase_counts = {"agg_emit_rows": n_emit,
                              "agg_evict_groups": n_evict}
        if any(self._retractable):
            GLOBAL_METRICS.gauge(HASH_AGG_EXTREMA_LOSSY_GROUPS,
                                 executor=label).set(float(n_lossy))
            self._phase_counts["agg_extrema_lossy_groups"] = n_lossy

    def _note_extrema_errors(self, totals: list) -> dict:
        """Publish the device's running fail-stop counts of the retractable
        calls as the increase since the last fetch; returns the kinds that
        are non-zero (the caller fail-stops on any)."""
        label = self.mem_name or self.identity
        for i, (kind, total) in enumerate(zip(EXTREMA_KINDS, totals)):
            GLOBAL_METRICS.counter(
                HASH_AGG_EXTREMA_ERRORS, executor=label, kind=kind).inc(
                    total - self._extrema_errs_seen[i])
            self._extrema_errs_seen[i] = total
        return {k: n for k, n in zip(EXTREMA_KINDS, totals) if n}

    async def _maybe_rebuild_at_barrier(self) -> None:
        """Barrier-time purge and growth: the table is examined between
        epochs, on what the barrier's watchdog fetch brought. Past
        `needs_rebuild`'s 0.7 it is rebuilt — at twice the capacity where
        the LIVE set crowds it (a re-jit of the apply step, which is why
        this never happens mid-epoch), else at its own. Before that, a
        table that one more interval like the fullest so far would take
        past ZOMBIE_PURGE_MARK is purged at its own capacity if that frees
        at least half of what is occupied: groups that die as fast as they
        come (a DISTINCT per window under a watermark) never reach 0.7, and
        no chunk of fresh keys meets a crowded table. The purge is the span
        `agg.purge`: the rehash's dispatch and the awaited readback of the
        rebuilt occupancy (the wait is for the device to REACH the rehash
        behind the barrier's other programs — the rehash itself costs a
        block insert per REHASH_BLOCK survivors —; the loop is not held).
        A rebuild that placed fewer groups than were alive fail-stops the
        epoch, and one that ran says how many it re-inserted
        (`agg_rehash_rows`, `hash_agg_rehash_rows_total`)."""
        occ, cap = self._occ_known, self.capacity
        grow_mark = occ > 0.7 * cap
        if not grow_mark and occ + self._claim_peak <= ZOMBIE_PURGE_MARK * cap:
            return

        def plan(occ: int, live: int) -> Optional[int]:
            """The capacity to rebuild at, or None."""
            if grow_mark:
                rebuild, new_cap = needs_rebuild(occ, live, cap)
                return new_cap if rebuild else None
            return cap if occ - live >= live else None

        live = self._live_known
        if live is not None and plan(occ, live) is None:
            return
        with span("agg.purge"):
            if live is None:
                # no watchdog fetch at this barrier (nothing applied since
                # the last flush, yet the evict made zombies)
                occ, live = (int(v) for v in await off_loop(
                    fetch_small, self._live_zombie(self.state)))
            new_cap = plan(occ, live)
            if new_cap is None:
                return
            self._rehash_to(new_cap)
            self._occ_known = int((await off_loop(
                fetch_small, self._live_zombie(self.state)))[0])
        if self._occ_known < live:
            # after the barrier's flush and evict the survivors ARE the
            # live groups: fewer slots occupied is a group the rebuild
            # found no slot for, and its state is gone
            raise RuntimeError(
                f"hash-agg rebuild placed {self._occ_known} of {live} "
                f"groups (capacity {new_cap}); recovery must replay the "
                f"epoch with a larger table")
        label = self.mem_name or self.identity
        # the groups the rebuild re-inserted: what it cost
        GLOBAL_METRICS.counter(HASH_AGG_REHASH_ROWS, executor=label).inc(live)
        counts = self._phase_counts
        counts["agg_rehash_rows"] = counts.get("agg_rehash_rows", 0) + live
        if new_cap == cap:
            GLOBAL_METRICS.counter(HASH_AGG_PURGES, executor=label).inc()
            counts["agg_purges"] = counts.get("agg_purges", 0) + 1

    # ------------------------------------------------- HBM memory manager
    def state_bytes(self) -> int:
        """EXACT device-state bytes (memory/accounting.py): static pytree
        shapes, no transfer, no estimate."""
        extra = () if self._slot_epoch is None else (self._slot_epoch,)
        return pytree_bytes((self.state,) + extra)

    @property
    def mem_spilled_rows(self) -> int:
        return self._spill.rows

    def memory_enable_lru(self) -> None:
        self._mem_lru_on = True

    def _lru_stamp_impl(self, dirty, slot_epoch, epoch):
        return lru_stamp(slot_epoch, dirty, epoch)

    def _mem_stamp(self, epoch: int) -> None:
        if self._slot_epoch is None \
                or self._slot_epoch.shape[0] != self.capacity:
            # first stamp / post-rebuild: everything counts as hot now;
            # one interval later untouched slots fall behind again
            self._slot_epoch = jnp.full(self.capacity, epoch,
                                        dtype=jnp.int64)
            return
        self._slot_epoch = self._lru_stamp(self.state.dirty,
                                           self._slot_epoch, epoch)

    def _mem_stats_impl(self, state: AggState, slot_epoch):
        """Per-slot (live, stamp) packed for ONE fetch (eviction only)."""
        live = state.table.occupied & (state.row_count > 0) & ~state.dirty
        return live, slot_epoch

    def _mem_pack_impl(self, state: AggState, slot_epoch, thresh):
        """Compact the to-evict rows (live, clean, stamp <= thresh) to
        the buffer prefix in durable-row layout."""
        evict = (state.table.occupied & (state.row_count > 0)
                 & ~state.dirty & (slot_epoch <= thresh))
        sel, n = compact_mask(evict)
        return tuple(self._durable_cols_at(state, sel)), n

    def _mem_rehash_impl(self, state: AggState, slot_epoch, thresh,
                         new_capacity: int) -> AggState:
        """Rebuild WITHOUT the evicted cold rows — frees their slots and
        (with a smaller new_capacity) the HBM behind them."""
        drop = ((state.row_count > 0) & ~state.dirty
                & (slot_epoch <= thresh))
        keep = (state.table.occupied
                & ((state.row_count > 0) | (state.dirty & state.prev_exists))
                & ~drop)
        return self._rehash_keep(state, keep, new_capacity)[0]

    def _mem_fetch_stats(self, epoch: int):
        """(live mask, stamps, cold stamps asc, this-interval touch count)
        in ONE packed fetch — the eviction decision inputs."""
        from ..utils.d2h import fetch_columns
        live_dev, ep_dev = self._mem_stats(self.state, self._slot_epoch)
        live_np, ep_np = fetch_columns([live_dev, ep_dev])
        live_np = live_np.astype(bool)
        cold = np.sort(ep_np[live_np & (ep_np < epoch)])
        return live_np, ep_np, cold, int((ep_np == epoch).sum())

    def _mem_cap_for(self, n_survive: int, touched_now: int) -> int:
        """Post-eviction capacity: survivors + one more interval of fresh
        keys at a 0.35 target load, so the shrunk table neither re-grows
        immediately nor hits a mid-epoch bucket-overflow fail-stop."""
        c = max(2 * BUCKET_SLOTS, self._mem_min_capacity)
        while n_survive + touched_now > 0.35 * c:
            c *= 2
        return c

    def _mem_do_evict(self, epoch: int, thresh: int,
                      new_cap: int, survivors: int) -> int:
        """Pack + spill slots stamped <= thresh, rehash at new_cap.
        Returns bytes freed (0 for a same-capacity cold purge — the win
        there is distance from the overflow cliff, not bytes)."""
        from ..utils.d2h import fetch_prefix_groups
        guard = getattr(self, "mem_guard", None)
        cols_dev, n_dev = self._mem_pack(self.state, self._slot_epoch,
                                         jnp.int64(thresh))
        n = int(np.asarray(n_dev))
        protected: list = []
        if n:
            host = fetch_prefix_groups([(list(cols_dev), n)])[0]
            nk = len(self.group_key_indices)
            for r in range(n):
                row = tuple(c[r].item() for c in host)
                if guard is not None \
                        and guard.is_protected(id(self), row[:nk]):
                    # reload-LFU guard: reloaded >= 2x within the window
                    # -> exempt from this round, re-insert below
                    protected.append(row)
                else:
                    self._spill.set(row[:nk], row)
        before = self.state_bytes()
        self.state = self._mem_rehash(self.state, self._slot_epoch,
                                      jnp.int64(thresh),
                                      new_capacity=new_cap)
        self.capacity = new_cap
        self._slot_epoch = jnp.full(new_cap, epoch, dtype=jnp.int64)
        self._occ_known = max(0, survivors)
        if protected:
            self._mem_reload_rows(protected)
            self.mem_guard_protected += len(protected)
            guard.note_protected(len(protected))
        freed = max(0, before - self.state_bytes())
        self.mem_evicted_bytes += freed
        return freed

    def memory_evict(self, target_bytes: int, epoch: int) -> int:
        """Budget response: spill the coldest slots to host and SHRINK
        the table. Called by the MemoryManager between epochs (executor
        idle); the packed fetches follow the same per-barrier d2h
        discipline as the persist path. Returns bytes actually freed."""
        if not self._mem_lru_on or self._slot_epoch is None:
            return 0
        live_np, ep_np, cold, touched_now = self._mem_fetch_stats(epoch)
        if cold.size == 0:
            return 0
        total_live = int(live_np.sum())
        bps = max(1, self.state_bytes() // max(1, self.capacity))
        # oldest-first: the smallest evicted count whose shrink covers
        # the target (stamps are whole epochs — the cut is exact)
        removed, thresh = 0, None
        for t in np.unique(cold):
            removed = int((cold <= t).sum())
            thresh = int(t)
            if (self.capacity
                    - self._mem_cap_for(total_live - removed,
                                        touched_now)) * bps \
                    >= target_bytes:
                break
        new_cap = self._mem_cap_for(total_live - removed, touched_now)
        if thresh is None or new_cap >= self.capacity:
            return 0               # shrink impossible — hot set owns it
        return self._mem_do_evict(epoch, thresh, new_cap,
                                  total_live - removed)

    def memory_maintain(self, epoch: int) -> None:
        """Steady-state LRU tick: once eviction is on, cold slots spill
        BEFORE occupancy reaches the growth threshold — eviction is the
        plan, capacity resize the fallback. Evicts the oldest stamps
        until occupancy (plus one interval of headroom) sits at the 0.35
        target; a same-capacity purge still counts (it buys distance
        from the overflow cliff)."""
        if not self._mem_lru_on or self._slot_epoch is None:
            return
        if self._occ_known <= 0.55 * self.capacity:
            return
        live_np, ep_np, cold, touched_now = self._mem_fetch_stats(epoch)
        if cold.size == 0:
            return
        total_live = int(live_np.sum())
        need = total_live + touched_now - int(0.35 * self.capacity)
        removed, thresh = 0, None
        for t in np.unique(cold):
            removed = int((cold <= t).sum())
            thresh = int(t)
            if removed >= need:
                break
        new_cap = min(self.capacity,
                      self._mem_cap_for(total_live - removed,
                                        touched_now))
        self._mem_do_evict(epoch, thresh, new_cap, total_live - removed)

    def _mem_check_reload(self, chunks: list) -> None:
        """Read-through miss handling: before a drain applies, reload any
        spilled key the chunks touch (one packed fetch of the chunks' key
        columns — only paid while spilled state exists)."""
        if not self._spill:
            return
        from ..utils.d2h import fetch_columns
        nk = len(self.group_key_indices)
        arrays = []
        for ch in chunks:
            arrays.extend(ch.columns[i].data for i in self.group_key_indices)
            arrays.append(ch.vis)
        host = fetch_columns(arrays)
        seen: set = set()
        touched: list = []
        for ci in range(len(chunks)):
            part = host[ci * (nk + 1):(ci + 1) * (nk + 1)]
            vis = part[-1].astype(bool)
            idx = np.flatnonzero(vis)
            for vals in zip(*(c[idx] for c in part[:nk])):
                k = tuple(v.item() for v in vals)
                if k in seen:
                    continue
                seen.add(k)
                if k in self._spill:
                    touched.append(k)
        if not touched:
            return
        guard = getattr(self, "mem_guard", None)
        if guard is not None:
            guard.note(id(self), touched)
        rows = [row for k in touched for row in self._spill.pop(k)]
        self._mem_reload_rows(rows)
        self.mem_reload_count += len(touched)
        from ..utils.metrics import HBM_RELOADS
        HBM_RELOADS.inc(len(touched))

    def _mem_reload_rows(self, rows: list) -> None:
        """Scatter spilled durable-layout rows back into live state (the
        same row format recovery replays — read-through rides the replay
        machinery). Keys insert via lookup_or_insert; unresolved inserts
        accumulate into the overflow watchdog (fail-stop -> recovery
        rebuilds larger), but the host pre-grows when occupancy is known
        to crowd."""
        if not rows:
            return
        n = len(rows)
        if self._occ_known + n > 0.7 * self.capacity:
            cap = self.capacity
            while self._occ_known + n > 0.7 * cap:
                cap *= 2
            self._occ_known = self._rebuild(cap)
        B = 1 << max(0, (n - 1).bit_length())
        reload = self._mem_reloads.get(B)
        if reload is None:
            reload = jit_state(self._mem_reload_impl, donate_argnums=(0, 1),
                               name=f"hash_agg_mem_reload{B}")
            self._mem_reloads[B] = reload
        self.state, self._overflow_dev = reload(
            self.state, self._overflow_dev, *self._durable_rows_to_cols(
                rows, B))
        self._applied_since_flush = True
        self._occ_known += n

    def _durable_rows_to_cols(self, rows: list, width: int) -> tuple:
        """Durable-layout rows (`_durable_cols_at`: keys ++ raw agg states
        ++ row_count) as device columns `width` long, the tail a repeat of
        the first row under a false `active`: (key_cols, call_cols,
        row_count, active), what `_scatter_rows` takes."""
        n = len(rows)
        pad = rows + [rows[0]] * (width - n)
        active = jnp.asarray(np.arange(width) < n)
        nk = len(self.group_key_indices)
        key_cols = tuple(
            jnp.asarray(np.asarray([r[j] for r in pad],
                                   dtype=np.dtype(self._key_dtypes[j])))
            for j in range(nk))
        call_cols = []
        off = nk
        for j, spec in enumerate(self.specs):
            if self._retractable[j]:
                K = self.minput_k
                vals = jnp.asarray(np.asarray(
                    [[r[off + k] for k in range(K)] for r in pad]),
                    dtype=spec.state_dtype)
                cnts = jnp.asarray(np.asarray(
                    [[r[off + K + k] for k in range(K)] for r in pad],
                    dtype=np.int32))
                lossy = jnp.asarray(np.asarray(
                    [bool(r[off + 2 * K]) for r in pad]))
                call_cols.append((vals, cnts, lossy))
                off += 2 * K + 1
            else:
                call_cols.append(jnp.asarray(
                    np.asarray([r[off] for r in pad])).astype(
                        spec.state_dtype))
                off += 1
        row_count = jnp.asarray(np.asarray([r[off] for r in pad],
                                           dtype=np.int64))
        return key_cols, tuple(call_cols), row_count, active

    def _scatter_rows(self, state: AggState, key_cols, call_cols, row_count,
                      active, dirty: bool):
        """Insert the active durable-layout rows into `state`, each group
        as already emitted (`prev_exists`, `prev_emit` of its state), marked
        `dirty` or not: (state', rows no slot was found for, probe
        fallbacks)."""
        ones = jnp.ones(active.shape[0], dtype=bool)
        return self._write_rows(
            state, key_cols, active,
            tuple(cs if self._retractable[j]
                  else cs.astype(state.agg_states[j].dtype)
                  for j, cs in enumerate(call_cols)),
            row_count, ones if dirty else None, ones,
            tuple(self._call_emit(j, cs) for j, cs in enumerate(call_cols)))

    def _write_rows(self, state: AggState, key_cols, active, agg_states,
                    row_count, dirty, prev_exists, prev_emit):
        """Find or claim a slot of `state`'s table for each active row's
        key and write the row of every lane there (`agg_states` shaped as
        the state's, a row a group; `dirty` None leaves that lane as it
        is): (state', rows no slot was found for, probe fallbacks). Every
        op is as wide as the rows, none as the table."""
        table, slots, n_un, n_fb = lookup_or_insert_counted(
            state.table, key_cols, active)
        tgt = jnp.where(active & (slots >= 0), slots, table.capacity)

        def put(lane, rows):
            return lane.at[tgt].set(rows, mode="drop")

        return AggState(
            table=table,
            agg_states=jax.tree_util.tree_map(put, state.agg_states,
                                              tuple(agg_states)),
            row_count=put(state.row_count, row_count),
            dirty=state.dirty if dirty is None else put(state.dirty, dirty),
            prev_exists=put(state.prev_exists, prev_exists),
            prev_emit=jax.tree_util.tree_map(put, state.prev_emit,
                                             tuple(prev_emit)),
        ), n_un, n_fb

    def _mem_reload_impl(self, state: AggState, overflow, key_cols,
                         call_cols, row_count, active):
        # dirty=True: re-persists the rows (idempotent upsert), keeps the
        # LRU stamp hot, and the flush's no-change skip still emits no
        # changelog because prev_emit matches
        state, n_un, n_fb = self._scatter_rows(
            state, key_cols, call_cols, row_count, active, dirty=True)
        return state, overflow.at[:2].add(
            jnp.stack([n_un, n_fb]).astype(overflow.dtype))

    def _recover_impl(self, state: AggState, unplaced, key_cols, call_cols,
                      row_count, active):
        state, n_un, _ = self._scatter_rows(
            state, key_cols, call_cols, row_count, active, dirty=False)
        return state, unplaced + n_un

    def _clean_spilled(self, wm) -> None:
        """Watermark state cleaning of EVICTED ranges: spilled keys below
        the cleaning watermark leave the spill dict and (when durable)
        the state table, in step with the device-side zeroing."""
        if not self._spill or self.cleaning_watermark_key is None:
            return
        j = self.cleaning_watermark_key
        dead = self._spill.purge(lambda k, rows: k[j] < wm)
        if dead and self.state_table is not None:
            keys_np = [
                np.asarray([k[i] for k, _ in dead],
                           dtype=np.dtype(self._key_dtypes[i]))
                for i in range(len(self.group_key_indices))]
            self._apply_evict_deletes(keys_np, len(dead))

    # ------------------------------------------------------- persistence
    def _persist_views(self, barrier: Barrier):
        """Dispatch-only first half of the barrier's durable flush: the
        packed persist / evict views queue behind the epoch's applies,
        into fresh non-donated buffers, BEFORE the flush resets `dirty` and
        the evict zeroes the groups. Returns what `_persist` finishes:
        `(rows, evict)`, each None or `(device arrays, count)`.

        d2h discipline (a fetch has a fixed per-call cost): dirty rows are
        compacted to the buffer prefix, and the whole payload — ops, vis,
        every column, evict keys — ships as one packed payload
        (utils/d2h.py) after at most one fetch of counts."""
        if self.state_table is None:
            return None
        rows = evict = None
        if self._applied_since_flush:
            cols, ops, vis, n_dirty = self._flush_persist_view()
            # the watchdog's fetch already brought this very count (its
            # pack and the view read the same state); without one the
            # device's is fetched with the evict count
            known = self._dirty_slots_known
            rows = ([ops, vis] + list(cols),
                    n_dirty if known is None else known)
        if (self.cleaning_watermark_key is not None
                and self._pending_clean_wm is not None):
            # evicted groups leave the durable table in the SAME epoch their
            # device state is zeroed, so committed state stays bounded and
            # recovery never resurrects dead windows (mem-table is a dict:
            # these tombstones override any insert staged above)
            keys_dev, n_ev = self._evict_keys(self.state,
                                              self._pending_clean_wm)
            evict = (list(keys_dev), n_ev)
        return rows, evict

    async def _persist(self, barrier: Barrier, views) -> None:
        """Second half, after everything else the barrier dispatches: the
        counts the host lacks are awaited, the payload's prefixes packed
        (dispatched HERE, by the actor, ahead of the next interval's
        programs), and the rest handed to the store as one deferred stage —
        a pure wait for that pack and the host-only write + commit; inline
        by default, drained by the barrier coordinator's background
        uploader in pipelined mode (`defer_prefix_flush`)."""
        if views is None:
            return
        st = self.state_table
        on_dev = [jnp.ravel(p[1]) for p in views
                  if p is not None and not isinstance(p[1], int)]
        new_epoch = barrier.epoch.curr

        def plan(counts):
            fetched = iter(() if counts is None else counts)
            nd, nev = (0 if p is None else p[1] if isinstance(p[1], int)
                       else int(next(fetched)) for p in views)
            groups = [(p[0], n) for p, n in zip(views, (nd, nev)) if n]

            def write(outs):
                outs = iter(outs)
                if nd:
                    host = next(outs)
                    st.write_chunk_columns(host[0], host[2:], host[1])
                if nev:
                    self._apply_evict_deletes(next(outs), nev)
                st.commit(new_epoch)

            return groups, write

        await defer_prefix_flush(
            st.store, barrier.epoch.prev, st.table_id,
            jnp.concatenate(on_dev) if on_dev else None, plan)

    def _apply_evict_deletes(self, keys_np, n: int) -> None:
        width = sum(self._call_persist_width(j)
                    for j in range(len(self.specs))) + 1
        pad = np.zeros(n, dtype=np.int64)   # non-pk columns unused by delete
        self.state_table.write_chunk_columns(
            np.full(n, OP_DELETE, dtype=np.int8),
            [np.asarray(k)[:n] for k in keys_np] + [pad] * width,
            np.ones(n, dtype=bool))

    def _flush_persist_view(self):
        """The state rows that changed this epoch (computed pre-flush)."""
        return self._persist_view(self.state, n_slots=self._dirty_width())

    def _dirty_width(self) -> Optional[int]:
        """Dirty slots the barrier's flush and persist view lay out: a
        power of two that holds the count the watchdog fetch brought,
        never narrower than before; None (the whole capacity) where no
        fetch brought one."""
        if self._dirty_slots_known is None:
            return None
        if self._dirty_slots_known > self._flush_slots:
            # twice the count: the next width is needed only when an
            # interval dirties twice the groups of the one that set this
            while self._flush_slots < 2 * self._dirty_slots_known:
                self._flush_slots *= 2
        return min(self._flush_slots, self.capacity)

    def _persist_view_impl(self, st: AggState,
                           n_slots: Optional[int] = None):
        # persisted row = keys ++ raw agg states ++ row_count; same
        # cumsum-compaction as the flush step. Pure in `st` so the
        # sharded subclass can run it per shard under shard_map.
        R = st.table.capacity if n_slots is None else n_slots
        exists_now = st.row_count > 0
        d_slot, n_dirty = compact_mask(st.dirty)
        d_slot = d_slot[:R]
        is_dirty = jnp.arange(R, dtype=jnp.int32) < n_dirty
        exists = exists_now[d_slot]
        existed = st.prev_exists[d_slot]
        vis = is_dirty & (exists | existed)
        ops = jnp.where(exists, OP_INSERT, OP_DELETE).astype(jnp.int8)
        cols = self._durable_cols_at(st, d_slot)
        return cols, ops, vis, n_dirty

    def _durable_cols_at(self, st: AggState, sel: jnp.ndarray) -> list:
        """Durable-row column layout (keys ++ raw agg states ++
        row_count) gathered at `sel` — shared by the persist view and the
        memory-eviction spill pack, so spilled rows and persisted rows
        are byte-for-byte the same format."""
        cols = [tk[sel] for tk in st.table.keys]
        for j, ags in enumerate(st.agg_states):
            if self._retractable[j]:
                vals, cnts, lossy = ags
                for k in range(self.minput_k):
                    cols.append(vals[sel, k])
                for k in range(self.minput_k):
                    cols.append(cnts[sel, k].astype(jnp.int64))
                cols.append(lossy[sel].astype(jnp.int64))
            else:
                cols.append(ags[sel])
        cols.append(st.row_count[sel])
        return cols

    def _call_persist_width(self, j: int) -> int:
        """Columns one agg call contributes to the durable state row."""
        return (2 * self.minput_k + 1) if self._retractable[j] else 1

    def recover(self, barrier_epoch: int) -> None:
        """Rebuild device state from the state table (recovery path)."""
        # spilled rows are in the durable table too (eviction never
        # deletes them), so recovery rebuilds EVERYTHING resident and the
        # host spill is simply dropped
        self._spill.clear()
        if self.state_table is None:
            return
        rows = [r for _, r in self.state_table.iter_all()]
        if not rows:
            return
        # Runtime capacity growth is not persisted; size the recovery table
        # from the actual persisted row count so a post-growth crash can
        # always be recovered (ADVICE r1: a hard assert at the constructor
        # capacity made such recovery permanently fail).
        need = 1 << max(self.capacity.bit_length() - 1,
                        (int(len(rows) / 0.7)).bit_length())
        self.capacity = max(self.capacity, need)
        self.state = self._state_from_rows(rows, self.capacity)
        self._occ_known = len(rows)

    def _state_from_rows(self, rows: list, capacity: int) -> AggState:
        """One LOCAL AggState of `capacity` holding exactly `rows` (the
        durable-row layout of _flush_persist_view). The sharded subclass
        calls this per shard and concatenates along the mesh axis.

        Replayed in batches of ONE width through one jitted program
        (`hash_agg_recover`), the last short batch too: the rows a crash
        leaves differ from run to run, and a replay shaped by their count
        was a chain of eager programs compiled anew inside every timed
        recovery (as the sorted join's replay, PR 30)."""
        state = self._empty_state(capacity)
        if not rows:
            return state
        batch = min(RECOVER_BATCH, capacity)
        unplaced = jnp.zeros((), dtype=jnp.int32)
        for i in range(0, len(rows), batch):
            state, unplaced = self._recover_rows(
                state, unplaced,
                *self._durable_rows_to_cols(rows[i:i + batch], batch))
        assert int(unplaced) == 0, "recovered rows overflowed the table"
        return state

    # ---------------------------------------------------- multi-chunk apply
    def _apply_chunk_now(self, chunk: StreamChunk) -> None:
        self._mem_check_reload([chunk])
        self._apply_chunk_raw(chunk)

    def _apply_chunk_raw(self, chunk: StreamChunk) -> None:
        self.state, self._overflow_dev, self._occ_dev = self._apply(
            self.state, self._overflow_dev, chunk)
        self._applied_since_flush = True

    def _enqueue_chunk(self, chunk: StreamChunk) -> None:
        """Buffer a chunk for the batched scan apply. Output only happens
        at the barrier flush, so deferring applies to the interval end is
        observationally identical to per-chunk applies — minus k-1
        dispatches per k-chunk interval."""
        if not self._use_chunk_batching:
            self._apply_chunk_now(chunk)
            return
        p = self._pending_chunks
        if p and (p[-1].capacity != chunk.capacity
                  or jax.tree_util.tree_structure(p[-1])
                  != jax.tree_util.tree_structure(chunk)):
            # only identically-shaped chunks stack; mixed runs split
            self._drain_pending()
        self._pending_chunks.append(chunk)
        if len(self._pending_chunks) >= self._batch_max:
            self._drain_pending()

    def _drain_pending(self) -> None:
        p = self._pending_chunks
        if not p:
            return
        self._pending_chunks = []
        if len(p) == 1:
            self._apply_chunk_now(p[0])
            return
        self._mem_check_reload(p)
        # bucket the batch length to a power of two so the scan program
        # set stays tiny; filler chunks are all-invisible views of the
        # last chunk's arrays (zero-copy) and contribute nothing
        k = 1 << (len(p) - 1).bit_length()
        if k > len(p):
            last = p[-1]
            filler = StreamChunk(last.columns, last.ops,
                                 jnp.zeros(last.capacity, dtype=bool),
                                 last.schema)
            p = p + [filler] * (k - len(p))
        scan = self._apply_scans.get(k)
        if scan is None:
            scan = self._make_apply_scan(k)
            self._apply_scans[k] = scan
        self.state, self._overflow_dev, self._occ_dev = scan(
            self.state, self._overflow_dev, *p)
        self._applied_since_flush = True

    def _make_apply_scan(self, k: int):
        def scan_impl(state, overflow, *chunks):
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *chunks)

            def step(carry, chunk):
                st, ov = carry
                st, ov2, occ = self._apply_impl(st, ov, chunk)
                # the overflow counter promotes to int64 through the
                # segment sums; scan needs a dtype-stable carry
                return (st, ov2.astype(ov.dtype)), occ

            (st, ov), occs = jax.lax.scan(step, (state, overflow), stacked)
            return st, ov, occs[-1]

        return jit_state(scan_impl, donate_argnums=(0, 1),
                         name=f"hash_agg_apply_scan{k}")

    # ----------------------------------------------------------- stream
    async def execute(self):
        first = True
        purge_ahead = None      # the task of `_precompile_purge`
        async for msg in self.input.execute():
            if isinstance(msg, StreamChunk):
                self._enqueue_chunk(msg)
            elif isinstance(msg, Barrier):
                self._drain_pending()
                if first or msg.kind is BarrierKind.INITIAL:
                    first = False
                    if self.state_table is not None:
                        self.state_table.init_epoch(msg.epoch.curr)
                        self.recover(msg.epoch.curr)
                    purge_ahead = self._precompile_purge()
                    yield msg
                    continue
                stopping = msg.mutation is not None and msg.is_stop_any()
                self._live_known = None
                # watchdog_interval=None => NO fetch ever (not even at
                # stop): a blocking d2h fetch serialises with dispatch.
                # Correctness in that mode rests on CPU-backend tests
                # of the same pipeline shapes + device-side zombie purges
                # below keeping occupancy bounded.
                if self.watchdog_interval and (
                        stopping or self._applied_since_flush):
                    await self._check_watchdog()
                # LRU epoch stamp BEFORE the flush resets dirty (one
                # segment_max per interval; no-op while eviction is off)
                if self._mem_lru_on and self._applied_since_flush:
                    self._mem_stamp(msg.epoch.curr)
                views = self._persist_views(msg)
                flushed = self._applied_since_flush
                if flushed:
                    self._applied_since_flush = False
                    self.state, cols, ops, vis = self._flush(
                        self.state, n_slots=self._dirty_width())
                    self._dirty_slots_known = None
                    yield StreamChunk(
                        tuple(Column(c) for c in cols), ops, vis, self.schema)
                if (self.cleaning_watermark_key is not None
                        and self._pending_clean_wm is not None):
                    self._clean_spilled(self._pending_clean_wm)
                    self.state = self._evict(self.state, self._pending_clean_wm)
                    self._pending_clean_wm = None
                    flushed = True
                    if self.watchdog_interval is None:
                        # transfer-free mode: evicted groups leave zombie
                        # slots, and without occupancy readbacks the host
                        # can never trigger a purge — so purge ON DEVICE
                        # with a same-capacity rehash (compiles once, no
                        # host roundtrip) to keep occupancy == live set.
                        self.state = self._rehash(self.state, self.capacity)
                # last of the barrier's dispatches, so the device has the
                # flush and the evict to run while the counts travel
                await self._persist(msg, views)
                # held watermarks follow the interval's flushed updates
                for held in self._held_wms.values():
                    yield held
                self._held_wms.clear()
                # in the poll that yields the barrier: the consumers have
                # their chunk and their watermarks to work on meanwhile
                if purge_ahead is not None:
                    # whatever is left of it is set-up, not a later
                    # interval's; the barrier's own programs are on the
                    # device meanwhile
                    await purge_ahead
                    purge_ahead = None
                if flushed:
                    await self._maybe_rebuild_at_barrier()
                yield msg
            else:
                # watermarks on group-key columns pass through re-indexed,
                # AFTER the next flush (see _held_wms); others are consumed
                # (reference: watermark inference)
                wm: Watermark = msg
                if wm.col_idx in self.group_key_indices:
                    pos = self.group_key_indices.index(wm.col_idx)
                    if pos == self.cleaning_watermark_key:
                        self._pending_clean_wm = wm.val
                    if self._applied_since_flush or self._pending_chunks:
                        self._held_wms[pos] = wm.with_idx(pos)
                    else:
                        # nothing buffered since the last flush, nothing
                        # to overtake: a resumed source's re-stated
                        # watermark reaches the consumers before the
                        # first chunk, as it had before the crash
                        yield wm.with_idx(pos)
