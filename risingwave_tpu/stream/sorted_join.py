"""Sorted-merge streaming join — dense sorted state, no chains, no loops.

The engine's one streaming join: the reference's two-sided streaming
equi-join (src/stream/src/executor/hash_join.rs:478 with the multimap state
of managed_state/join/mod.rs:238-268): a chunk from one side probes the
OTHER side's stored rows and emits joined changelog rows, then updates its
OWN store (update pairs degrade to Delete/Insert; NULL keys never match,
and neither do NaN keys).

TPU re-design — why not the reference's chained hash multimap:
  * The chain walk is a `lax.while_loop` whose trip count is the longest
    key chain: hot keys turn one chunk into hundreds of tiny dependent
    kernel launches.
  * Slots are reclaimed only by a barrier-time rebuild, so the row store
    must hold A WHOLE EPOCH of inserts on top of the live set. That makes
    throughput = row_capacity x barrier_rate — the measured q7/q8 ceiling.

Here each side's state is a *dense, sorted* struct-of-arrays: rows
[0, n) sorted ascending by a 63-bit ORDER HASH: its high 39 bits are a
hash of the join key, its low 24 a hash of the row's pk (`order_hash`).
The rows of one key are still one contiguous range, and inside it a row
stands where its pk puts it (exact key equality re-checked on every
candidate, so hash collisions only cost a wasted compare — they can never
produce a wrong match). Everything is
sort / searchsorted / cumsum / scatter / gather over static shapes, zero
data-dependent control flow. In the per-chunk programs (apply, evict) a
scatter or a gather has CHUNK-many or match-buffer-many indices, never
one per pool slot: what moves a whole column of a pool moves it by
log-step shifts and selects (`ops/monotone_move.py`, `insert` below); only
the barrier's lane diff (Durability, below) still indexes every slot. A
binary search is only ever asked
CHUNK-many questions (of a pool); a rank with a question per pool slot or
per match-buffer slot has ascending questions, so it is counted instead:
a histogram of the searched array's bounds and a prefix sum
(`_rank_of_ascending`) — O(N + C), where a search costs a C-index gather
for each of its log N steps:

  probe   lo/hi = searchsorted(other.khash, key range of h) — each chunk
          row's matches are a CONTIGUOUS RANGE. Ranges are expanded into a
          fixed match buffer [M] with cumsum offsets; the slot -> range
          map is the counted rank of the offsets (`_range_owner`; no
          loop, unlike the chain walk).
  evict   rows with clean-col < watermark are dropped DURING the same
          merge program that inserts new rows — per chunk, not per
          barrier. State capacity therefore bounds the LIVE set only;
          epoch churn is unlimited. This is what lifts the q7/q8 cap.
  insert  incoming rows are sorted by hash and merged into the kept rows
          by ONE searchsorted of the new hashes into the pool, which gives
          each new row its rank among the kept rows and, counted, each
          pool row its rank among the new ones (`_merge_ranks`; stable:
          state rows stay before new rows of equal hash). The merge is
          no permutation: a kept pool row slides LEFT over the dropped
          rows before it, then RIGHT past the new rows that sort before
          it, and never overtakes another — two monotone moves, each
          log2 stages of "shift every lane by 2^k, select" at memory
          speed (`compact`, `expand`; `_merge_sorted`). The N new rows
          land in the gaps the same way (`expand` by their rank among
          the kept rows) — O(C log C) elementwise bandwidth, no table
          sort, no index per pool slot or per new row (a scatter of one
          pool column cost 67 ns a SLOT on a v5e: 87% of q4's device
          time at 2^22, PERF.md §6, PR 35).
  delete  a retraction searches its own side for its (key, pk) order hash
          and takes the row whose (key, pk) compare equal: a handful of
          candidates however many rows share the key (q5 as published
          joins on the window alone, `num >= maxn` is its condition: every
          count of a window shares one key, tens of thousands of rows,
          and a search by key alone expanded every one of them for every
          retraction, past any match buffer); one victim per retraction
          (within-chunk insert/delete runs on the same pk are netted
          first: a run's first delete and last insert are the ones that
          take effect).

`append_only=(left, right)` statically removes the retraction machinery
from a side's program — the common windowed-join case compiles to the
probe + merge path alone.

Outer joins (join_type left/right/full) follow the reference's degree
design (managed_state/join/mod.rs:252-261): every stored row carries its
count of condition-passing matches on the other side. A chunk's probe
scatter-adds signed deltas into the OTHER side's degree column; rows whose
degree transitions 0 -> >0 retract their NULL-padded output row, and
> 0 -> 0 (re-)emit it — computed per chunk as NET transitions (transient
flips within one chunk cancel, the Delete/Insert degradation the reference
applies when pairs can't stay adjacent). Unmatched rows on an outer side
emit their NULL-padded row inline at insert/delete time, including
NULL-key rows (which can never match). The non-equi condition therefore
evaluates INSIDE the jitted apply.

Durability (state_tables): the dense sorted layout has no stable SLOT a
dirty bit could follow (merge-inserts shift every row), so the dirty
information rides WITH the row instead: each side carries a provenance
lane `src` — a stored row's position in the state as of the last durable
flush (`_snap`, kept by aliasing the arrays that were live then), or -1 if
it was inserted since. Merge and eviction move it exactly like `degree`.
At a barrier the changed rows fall out elementwise: live rows with
src < 0 are the inserts; positions of `_snap` that no live row carries
any more are the deletes (one scatter marks the carried ones). A stored
row is never edited in place (only `degree` is, and degrees are not
persisted), so "same snapshot position" IS "same row": no hashing, no
compare, no collision case, and the cost follows the capacity once, not
capacity x log(capacity). A row deleted and re-inserted unchanged within
one interval is written as delete + insert of identical rows (deletes go
first, so the table ends the same). Changed rows compact into
[deletes][inserts] buffers, are written columnar to the per-side
StateTable, and committed at every barrier (reference:
state_table.rs:1036 commits everything at every checkpoint); then the
live side becomes the new base (`_rebase`). Degrees are NOT persisted:
recovery replays the stored rows through the normal probe path (right
side first into an empty mesh, then left probing right), which rebuilds
both sides' degree columns and the condition evaluation for free — a
TPU-first simplification of the reference's degree tables
(managed_state/join/mod.rs:252).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import (
    Column, StreamChunk, OP_DELETE, OP_INSERT, op_sign,
)
from ..common.floatbits import float_pair_bits
from ..common.types import Field, Schema
from ..memory.accounting import pytree_bytes
from ..memory.spill import HostSpill
from ..ops.hash_table import pack_rows, stable_lexsort
from ..ops.jit_state import jit_state
from ..ops.monotone_move import compact, expand
from ..utils.d2h import (
    fetch_flat, fetch_small, finish_prefix_groups, off_loop,
    prepare_prefix_groups,
)
from ..utils.metrics import (
    GLOBAL_METRICS, JOIN_LIVE_ROWS, JOIN_MATCH_BUFFER_PEAK, JOIN_MATCH_ROWS,
    JOIN_PERSIST_ROWS)
from .align import LEFT, RIGHT, barrier_align
from .executor import Executor
from .message import Barrier, BarrierKind, Watermark

# Padding value for khash beyond the live prefix: int64 max keeps
# searchsorted ranges inside [0, n) (a real 63-bit hash equals it with
# probability ~2^-63, and even then the exact-key compare rejects the row).
_HSENTINEL = jnp.iinfo(jnp.int64).max
# "No watermark yet" eviction threshold — below any real event time.
NO_WATERMARK = -(1 << 62)


def key_hash(key_cols: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """63-bit nonnegative hash of the composite key (splitmix64 chain).

    A float column enters through `float_pair_bits`, an integer image of
    its VALUE (-0.0 and +0.0 alike, as `==` has them; every NaN alike; the
    same arithmetic on every backend and in numpy) — a value cast would
    hash 1.2 and 1.7 alike. An integer column enters by value."""
    h = jnp.full(key_cols[0].shape[0], 0x243F6A8885A308D3, dtype=jnp.uint64)
    for c in key_cols:
        if jnp.issubdtype(c.dtype, jnp.floating):
            c = float_pair_bits(c)
        x = h ^ (c.astype(jnp.uint64) * jnp.uint64(0x9E3779B97F4A7C15))
        x = x + jnp.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
        h = x ^ (x >> jnp.uint64(31))
    return (h >> jnp.uint64(1)).astype(jnp.int64)


# The order hash's low bits, taken from the pk: a key keeps 39 bits (a
# 2^19-row pool probes a foreign key's range once in 2^20 probes, and the
# exact compare rejects it), a retraction meets a foreign row of its key
# once in 2^24 rows that share the key.
_PK_MASK = (1 << 24) - 1
_KEY_MASK = ((1 << 63) - 1) & ~_PK_MASK


def order_hash(h: jnp.ndarray, pk_cols: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """The hash a side's store is ordered by: the key hash `h` with its low
    24 bits replaced by a hash of the pk columns."""
    return (h & _KEY_MASK) | (key_hash(pk_cols) & _PK_MASK)


@jax.tree_util.register_pytree_node_class
@dataclass
class SortedSideState:
    """One side's store: dense prefix [0, n), ascending by khash (the
    rows' `order_hash`)."""

    khash: jnp.ndarray                 # int64 [C], sentinel beyond n
    cols: tuple[jnp.ndarray, ...]      # per input column [C]
    valids: tuple[jnp.ndarray, ...]    # per input column bool [C]
    degree: jnp.ndarray                # int32 [C] — matches on other side
    src: jnp.ndarray                   # int32 [C] — the row's position in
    #                                    the last flushed state, -1 if it
    #                                    was inserted since (provenance)
    n: jnp.ndarray                     # int32 scalar — live rows

    def tree_flatten(self):
        return ((self.khash, self.cols, self.valids, self.degree,
                 self.src, self.n), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        kh, cols, valids, degree, src, n = children
        return cls(kh, tuple(cols), tuple(valids), degree, src, n)

    @property
    def capacity(self) -> int:
        return self.khash.shape[0]

    def lanes(self) -> tuple[list, list]:
        """Every per-row lane, `khash` first, and the padding each holds
        behind n: what a merge or an eviction moves, row by row."""
        nk = len(self.cols)
        return ([self.khash, *self.cols, *self.valids, self.degree, self.src],
                [_HSENTINEL] + [0] * nk + [False] * nk + [0, -1])

    @classmethod
    def from_lanes(cls, lanes: Sequence[jnp.ndarray], n) -> "SortedSideState":
        nk = (len(lanes) - 3) // 2
        return cls(lanes[0], tuple(lanes[1:1 + nk]),
                   tuple(lanes[1 + nk:1 + 2 * nk]), lanes[-2], lanes[-1], n)


def _empty_sorted_side(capacity: int, col_dtypes: Sequence) -> SortedSideState:
    return SortedSideState(
        khash=jnp.full(capacity, _HSENTINEL, dtype=jnp.int64),
        cols=tuple(jnp.zeros(capacity, dtype=dt) for dt in col_dtypes),
        valids=tuple(jnp.zeros(capacity, dtype=bool) for _ in col_dtypes),
        degree=jnp.zeros(capacity, dtype=jnp.int32),
        src=jnp.full(capacity, -1, dtype=jnp.int32),
        n=jnp.int32(0),
    )


def grow_sorted_arrays(khash, cols, valids, new_capacity: int):
    """Reallocate a sorted dense store at a larger capacity (live prefix
    unchanged, padding = hash sentinel / zeros). Device-side concat —
    subsequent programs re-jit at the new static shape (reference role:
    src/common/src/estimate_size/ + cache growth; here growth is the
    memory-pressure response instead of fail-stop)."""
    pad = new_capacity - khash.shape[0]
    assert pad > 0
    kh = jnp.concatenate([khash, jnp.full(pad, _HSENTINEL,
                                          dtype=khash.dtype)])
    cols2 = tuple(jnp.concatenate([c, jnp.zeros(pad, dtype=c.dtype)])
                  for c in cols)
    valids2 = tuple(jnp.concatenate([v, jnp.zeros(pad, dtype=bool)])
                    for v in valids)
    return kh, cols2, valids2


def _rank_of_ascending(idx: jnp.ndarray, weight, size: int) -> jnp.ndarray:
    """rank[t] = sum of `weight` over the entries with idx <= t, for t in
    [0, size): a histogram and a prefix sum. It is what a search of `size`
    ASCENDING queries through the array whose bounds are `idx` returns,
    at the cost of len(idx) + size instead of size x log(len(idx)) — for
    a rank whose queries outnumber the searched rows. An idx at or past
    `size` counts toward no position (dropped: the buffer keeps its
    power-of-two length)."""
    hist = jnp.zeros(size, dtype=jnp.int32).at[idx].add(weight, mode="drop")
    return jnp.cumsum(hist)


def _range_owner(offs: jnp.ndarray, size: int) -> jnp.ndarray:
    """Which range each slot j of a [size] expansion buffer belongs to:
    #{i : offs[i] <= j}, `offs` the inclusive prefix sums of the range
    lengths (`searchsorted(offs, arange(size), side="right")`, counted).
    Empty ranges repeat their offset and add up; an offset at or past
    `size` — int64: a hot key's total can pass 2^31 — is clipped to
    `size` BEFORE the cast and owns no slot."""
    return _rank_of_ascending(jnp.minimum(offs, size).astype(jnp.int32),
                              1, size)


def _merge_ranks(khash: jnp.ndarray, dead_cum: jnp.ndarray,
                 nh: jnp.ndarray, is_new: jnp.ndarray):
    """The two ranks of the stable merge of the sorted new hashes `nh`
    (real where `is_new`, sentinel padding behind) into the sorted pool
    `khash`, whose dead rows `dead_cum` counts (inclusive prefix sum of
    the dead mask):
      new_lt[t]  = # new rows with hash <  khash[t]     ([C])
      kept_le[r] = # LIVE pool rows with hash <= nh[r]  ([N])
    ONE search — the N new hashes into the pool — gives both: nh[r] <
    khash[t] exactly when idx[r] <= t, so new_lt is the rank of t among
    the idx (pool rows stay before new rows of equal hash either way)."""
    idx = jnp.searchsorted(khash, nh, side="right").astype(jnp.int32)
    dead_before = jnp.where(idx > 0, dead_cum[jnp.clip(idx - 1, 0)], 0)
    new_lt = _rank_of_ascending(idx, is_new.astype(jnp.int32),
                                khash.shape[0])
    return new_lt, idx - dead_before


def _merge_sorted(keep: jnp.ndarray, drops: bool, n_new,
                  pool: Sequence[jnp.ndarray], fills: Sequence,
                  new: Sequence, ranks: Optional[tuple] = None):
    """The stable merge of the chunk's new rows into the pool rows with
    `keep`: the state the merge leaves, every lane dense and in hash
    order, padding included. `pool` are the pool's lanes [C], the order
    hash first, `fills` their padding; `new` the new rows' lanes [N] in
    the same order, sorted by hash, the first `n_new` real and sentinel
    hashes behind (None: a new row gets that lane's fill). `drops` says
    statically whether `keep` can leave a gap in the live prefix (a side
    that neither cleans nor retracts only appends: its kept rows need no
    compaction). `ranks` is (new_lt, kept_le) as `_merge_ranks` states
    them, from a caller whose order is more than its first lane (a top-N
    ranked by several key lanes): the merge then searches nothing.

    No row overtakes another on its way. A kept pool row slides left
    over the dropped rows before it (`compact`), then right past the new
    rows that sort before it (`expand` by `new_lt`, at most N); new row r
    slides right past the kept pool rows that sort at or before it
    (`expand` by `kept_le`), into the gaps the pool rows left. Returns
    (lanes', n', rows dropped past the capacity)."""
    C, N = pool[0].shape[0], new[0].shape[0]
    dead_cum = jnp.cumsum((~keep).astype(jnp.int32))
    n_kept = C - dead_cum[C - 1]
    new_ok = jnp.arange(N, dtype=jnp.int32) < n_new
    new_lt, kept_le = ranks if ranks is not None else _merge_ranks(
        pool[0], dead_cum, new[0], new_ok)
    pool, fills = list(pool), list(fills)
    occupied = keep
    if drops:
        # the ranks were taken where the rows stood: `new_lt` moves along
        *pool, new_lt = compact(keep, pool + [new_lt], fills + [0])
        occupied = jnp.arange(C, dtype=jnp.int32) < n_kept
    pool, occupied = expand(occupied, new_lt, N, pool, fills)

    def pool_wide(x, fill):
        # a new row past the pool's length lands past its capacity
        return x[:C] if N >= C else jnp.pad(x, (0, C - N),
                                            constant_values=fill)

    given = [i for i, nx in enumerate(new) if nx is not None]
    landed, _ = expand(
        pool_wide(new_ok, False), pool_wide(kept_le, 0), C - 1,
        [pool_wide(new[i].astype(pool[i].dtype), fills[i]) for i in given],
        [fills[i] for i in given])
    for i, nx in zip(given, landed):
        pool[i] = jnp.where(occupied, pool[i], nx)
    n_after = n_kept + n_new
    return (pool, jnp.minimum(n_after, C).astype(jnp.int32),
            jnp.maximum(n_after - C, 0))


class SortedJoinExecutor(Executor):
    """Equi-join (inner, outer, temporal) over sorted dense state. Keys
    of any column type: the state is ordered by `key_hash`, candidates
    are verified by `==` on the stored key columns."""

    # the name MemoryManager.register() gave this join: the `executor`
    # label of its series (utils/metrics.py JOIN_*)
    mem_name: Optional[str] = None

    def __init__(self, left: Executor, right: Executor,
                 left_key_indices: Sequence[int],
                 right_key_indices: Sequence[int],
                 left_pk_indices: Sequence[int],
                 right_pk_indices: Sequence[int],
                 capacity: int = 1 << 17,
                 match_factor: int = 2,
                 match_factors: Optional[tuple] = None,
                 condition=None,
                 join_type: str = "inner",
                 output_indices: Optional[Sequence[int]] = None,
                 append_only: tuple[bool, bool] = (False, False),
                 clean_watermark_cols: tuple[Optional[int], Optional[int]] = (None, None),
                 clean_specs: Optional[tuple] = None,
                 state_tables: Optional[tuple] = None,
                 temporal: bool = False,
                 watchdog_interval: Optional[int] = 1):
        self.inputs = (left, right)
        self.key_indices = (tuple(left_key_indices), tuple(right_key_indices))
        self.pk_indices_side = (tuple(left_pk_indices), tuple(right_pk_indices))
        assert len(self.key_indices[0]) == len(self.key_indices[1])
        lt, rt = left.schema, right.schema
        for li, ri in zip(*self.key_indices):
            assert lt[li].data_type.np_dtype == rt[ri].data_type.np_dtype, \
                f"join key dtype mismatch {lt[li]} vs {rt[ri]}"
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
        # the output stream key is only valid if EVERY stream-key column
        # survives the projection — a partial key is not unique, and a
        # keyed downstream consumer would mis-address retractions
        # (ADVICE r3 #4); advertise no key rather than a wrong one
        if all(i in self.output_indices for i in out_pk_full):
            self.pk_indices = tuple(self.output_indices.index(i)
                                    for i in out_pk_full)
        else:
            self.pk_indices = ()
        self.capacity = [capacity, capacity]
        self.match_factor = match_factor
        # per-side probe buffers: side s's matches are bounded by 1 per
        # row when the OTHER side's rows are unique per join key (its
        # stream key is covered by its equi keys) — the planner passes
        # (2, 64)-style asymmetric factors so a wide chunk probing a
        # unique side doesn't allocate a match_factor-times-wider buffer
        self.match_factors = (tuple(match_factors) if match_factors
                              else (match_factor, match_factor))
        self.condition = condition
        assert join_type in ("inner", "left", "right", "full")
        # Cleaning specs generalize clean_watermark_cols (which maps to
        # ("own", col)) — the reference's planner derives the same three
        # shapes from watermark inference:
        #   ("own", col)                evict below THIS side's watermark
        #                               on col (caller asserts safety)
        #   ("pair", col, kpos)         col is equi-key kpos; evict below
        #                               min of BOTH sides' key watermarks
        #                               (windowed joins — safe even when
        #                               one side lags)
        #   ("band", col, other_col, d[, cap_col])
        #                               residual condition bounds col >
        #                               other.other_col + d; evict below
        #                               other side's watermark + d
        #                               (interval joins). cap_col: for a
        #                               retracting side, additionally cap
        #                               the bound at OWN watermark on
        #                               cap_col — retractions below it
        #                               can no longer arrive
        if clean_specs is None:
            clean_specs = tuple(
                None if c is None else ("own", c)
                for c in clean_watermark_cols)
        self.clean_specs = tuple(clean_specs)
        # Watermark eviction drops rows WITHOUT probing, so it cannot
        # maintain the other side's degree column; combining state
        # cleaning with outer semantics would silently corrupt NULL-row
        # accounting (an evicted row's matches keep degree>0 forever).
        # The reference has the same tension (TTL cleaning is documented
        # as inconsistency-introducing for outer joins); fail loudly.
        if join_type != "inner":
            assert self.clean_specs == (None, None), \
                "outer joins do not support watermark state cleaning"
        self.join_type = join_type
        # Temporal join (reference: temporal_join.rs — FOR SYSTEM_TIME AS
        # OF PROCTIME()): the right side is a TABLE snapshot; its updates
        # maintain state but emit NOTHING (no retroactive fixes of
        # earlier outputs), so only left arrivals produce rows. Left
        # probes read the right side's state as of processing time.
        if temporal:
            assert join_type in ("inner", "left"),                 "temporal joins are inner or left"
        self.temporal = temporal
        # side s "preserves" its unmatched rows (emits NULL-padded output)
        self._outer = (join_type in ("left", "full"),
                       join_type in ("right", "full"))
        self.append_only = tuple(append_only)
        # the column each side's evict programs compare against
        self.clean_cols = tuple(None if sp is None else sp[1]
                                for sp in self.clean_specs)
        self._pending_clean: list[int] = [NO_WATERMARK, NO_WATERMARK]
        # per-side col -> latest watermark value (feeds clean-spec bounds)
        self._wms: list[dict[int, int]] = [{}, {}]
        self.identity = (f"SortedJoin(l={self.key_indices[0]}, "
                         f"r={self.key_indices[1]})")
        self.state_tables = tuple(state_tables) if state_tables else (None, None)
        self.sides = [self._empty(s) for s in (LEFT, RIGHT)]
        # the device state as of the last durable flush, kept by aliasing:
        # the live rows' `src` lane indexes it (_rebase)
        self._snap = [self.sides[LEFT], self.sides[RIGHT]]
        self._src_iotas: dict[int, jnp.ndarray] = {}
        self._flush_dirty = [False, False]
        # rows this barrier interval's durable flushes wrote, for the
        # actor's phase dict (take_phase_counts)
        self._phase_counts: dict = {}
        # Donation: ONLY the error accumulator (arg 2). The side states
        # must NOT be donated: `_snap` keeps the last-persisted side as
        # the durable diff base by ALIASING the live arrays (_rebase), so
        # the buffers an apply consumes are still live as the snapshot.
        self._apply = jit_state(self._apply_impl,
                                static_argnames=("side", "match_factor"),
                                donate_argnums=(2,),
                                name="sorted_join_apply")
        # what `recover()` and the spill reload replay stored rows through:
        # the same program with only the state for outputs, so the
        # compiler drops what served the emitted rows alone (the match
        # buffer's gathers). Each of the three has a name of its own: the
        # name is what tells their programs apart in a trace
        # (ops/jit_state.py PROGRAMS)
        self._replay = jit_state(self._replay_impl,
                                 static_argnames=("side", "match_factor"),
                                 donate_argnums=(2,),
                                 name="sorted_join_replay")
        # the stream's applies: the same program, which also folds what
        # the chunk asked of its match buffer into `_match_dev`
        self._apply_counted = jit_state(
            self._apply_counted_impl,
            static_argnames=("side", "match_factor"), donate_argnums=(2, 3),
            name="sorted_join_apply_counted")
        self._evict = jit_state(self._evict_impl, static_argnames=("side",),
                                name="sorted_join_evict")
        self._diff = jit_state(self._diff_impl, name="sorted_join_diff")
        if watchdog_interval not in (None, 1):
            raise ValueError("watchdog_interval must be 1 or None")
        self.watchdog_interval = watchdog_interval
        self.rebuilds = 0
        # device error accumulator [match_overflow, del_miss, row_overflow];
        # fetched once per barrier: a fetch per chunk would serialise
        # dispatch behind the device
        self._errs_dev = jnp.zeros(3, dtype=jnp.int32)
        zero = jnp.zeros((), dtype=jnp.int32)
        self._n_dev = [zero, zero]
        self._dirty = [False, False]
        # this barrier interval's [rows emitted by LEFT chunks, by RIGHT
        # chunks, most equi-key candidates one LEFT chunk found, one RIGHT
        # chunk]: folded by the stream's applies, fetched and zeroed by
        # the watchdog pack. None = not counted (the mesh join)
        self._match_dev = jnp.zeros(4, dtype=jnp.int32)
        # rows of the match buffer each side's last chunk was given
        self._match_width = [0, 0]
        self._watchdog_pack = jit_state(
            lambda errs, nl, nr, match: (
                jnp.concatenate([errs, jnp.stack([nl, nr]), match]),
                jnp.zeros_like(match)),
            donate_argnums=(3,), name="sorted_join_watchdog_pack")
        self._key_wms: list[dict[int, int]] = [{}, {}]
        self._emitted_key_wm: dict[int, int] = {}
        # watermark value a side's state is already clean to (skip
        # repeated idle-evicts while the watermark holds still)
        self._cleaned_to = [NO_WATERMARK, NO_WATERMARK]
        # ---- HBM memory manager hooks (memory/manager.py): the dense
        # sorted stores have fixed capacity, so the pressure response is
        # occupancy-driven SPILL — ahead of the overflow cliff, the
        # OLDEST rows (by the state-cleaning column, the coldness axis of
        # a windowed join) move to host; a chunk whose key touches a
        # spilled window reloads it through the normal apply path (the
        # recovery-replay shape) before probing. Inner joins only —
        # eviction cannot maintain outer-join degrees, same restriction
        # as watermark cleaning.
        self._mem_on = False
        self._spill = [HostSpill(), HostSpill()]
        self.mem_evicted_bytes = 0
        self.mem_reload_count = 0
        self._mem_cc_range_prog = jit_state(
            self._mem_cc_range_impl, static_argnames=("side",),
            name="sorted_join_mem_range")
        self._mem_pack_prog = jit_state(
            self._mem_pack_impl, static_argnames=("side",),
            name="sorted_join_mem_pack")
        self._mem_kh_cut_prog = jit_state(
            self._mem_kh_cut_impl, static_argnames=("frac_num",),
            name="sorted_join_mem_kh_cut")

    def fence_tokens(self) -> list:
        return [s.n for s in self.sides] + super().fence_tokens()

    def _empty(self, side: int) -> SortedSideState:
        return _empty_sorted_side(self.capacity[side], self._col_dtypes[side])

    # ------------------------------------------------------------- apply
    def _apply_impl(self, own: SortedSideState, other: SortedSideState,
                    errs: jnp.ndarray, chunk: StreamChunk, wm_own, side: int,
                    match_factor: Optional[int] = None):
        """`_apply_core` without its candidate count: (own', other_degree',
        out_cols, out_ops, out_vis, errs', n_own)."""
        return self._apply_core(own, other, errs, chunk, wm_own, side,
                                match_factor)[:7]

    def _replay_impl(self, own: SortedSideState, other: SortedSideState,
                     errs: jnp.ndarray, chunk: StreamChunk, wm_own,
                     side: int, match_factor: Optional[int] = None):
        """`_apply_core` for rows that were emitted when they first came:
        (own', other_degree', errs', n_own)."""
        out = self._apply_core(own, other, errs, chunk, wm_own, side,
                               match_factor)
        return out[0], out[1], out[5], out[6]

    def _apply_counted_impl(self, own: SortedSideState,
                            other: SortedSideState, errs: jnp.ndarray,
                            match: jnp.ndarray, chunk: StreamChunk, wm_own,
                            side: int, match_factor: Optional[int] = None):
        """`_apply_impl` + `match'`: the rows this chunk emitted added to
        `match[side]`, its equi-key candidates (what it asked of the match
        buffer, before key equality and the condition; saturating at
        2^31 - 1) folded into the peak `match[2 + side]`."""
        *out, total = self._apply_core(own, other, errs, chunk, wm_own,
                                       side, match_factor)
        n_emit = jnp.sum(out[4], dtype=jnp.int32)
        peak = jnp.minimum(total, jnp.iinfo(jnp.int32).max).astype(jnp.int32)
        match = match.at[side].add(n_emit).at[2 + side].max(peak)
        return (*out, match)

    def _apply_core(self, own: SortedSideState, other: SortedSideState,
                    errs: jnp.ndarray, chunk: StreamChunk, wm_own, side: int,
                    match_factor: Optional[int] = None):
        """Probe `other`, emit matches (+ outer-join NULL rows and degree
        transitions), evict+update `own` in one program.

        Returns (own', other_degree', out_cols, out_ops, out_vis, errs',
        n_own, candidates). Output rows are laid out in up to three segments:
        [0, M)       inner matches
        [M, 2M)      other-side NULL-row transitions   (outer only)
        [2M, 2M+N)   own-side unmatched NULL rows      (own outer only)
        """
        key_idx = self.key_indices[side]
        pk_idx = self.pk_indices_side[side]
        N = chunk.capacity
        C = own.capacity
        Co = other.capacity
        M = (match_factor or self.match_factor) * N
        append_only = self.append_only[side]

        key_cols = [chunk.columns[i].data for i in key_idx]
        key_valid = jnp.ones(N, dtype=bool)
        for i, kc in zip(key_idx, key_cols):
            key_valid &= chunk.columns[i].valid_mask()
            if jnp.issubdtype(kc.dtype, jnp.floating):
                # a NaN equals nothing, itself included: like a NULL key
                # it joins nothing and is not stored, so its retraction
                # has no stored row to look for
                key_valid &= ~jnp.isnan(kc)
        active = chunk.vis & key_valid     # NULL and NaN keys never join
        signs = op_sign(chunk.ops)
        row_ids = jnp.arange(N, dtype=jnp.int32)
        h = key_hash(key_cols)
        h_own = order_hash(h, [chunk.columns[p].data for p in pk_idx])

        # ---- within-chunk pk-run netting ----
        if append_only:
            is_ins = active
            is_del = jnp.zeros(N, dtype=bool)
        else:
            sort_keys = [row_ids]
            for p in pk_idx:
                sort_keys.append(chunk.columns[p].data)
            sort_keys.append(~active)
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

        # ---- probe the other side: contiguous hash ranges ----
        lo = jnp.searchsorted(other.khash, h & _KEY_MASK,
                              side="left").astype(jnp.int32)
        hi = jnp.searchsorted(other.khash, h | _PK_MASK,
                              side="right").astype(jnp.int32)
        # int64 offsets: a hot-key chunk's total candidate-match count can
        # exceed 2^31 (120k-row key run probed by a 20k-row chunk); an int32
        # cumsum would wrap negative and silently drop every match while
        # the overflow counter read zero
        lens = jnp.where(active, (hi - lo).astype(jnp.int64), 0)
        offs = jnp.cumsum(lens)
        total = offs[N - 1]
        j = jnp.arange(M, dtype=jnp.int64)
        src = _range_owner(offs, M)
        srcc = jnp.clip(src, 0, N - 1)
        prev = jnp.where(srcc > 0, offs[jnp.clip(srcc - 1, 0)], 0)
        pos = jnp.clip(lo[srcc] + (j - prev), 0, Co - 1).astype(jnp.int32)
        emit = (j < jnp.minimum(total, M)) & (pos < other.n)
        # exact key equality (hash collisions rejected here)
        for kc, oi in zip(key_cols, self.key_indices[1 - side]):
            emit &= other.cols[oi][pos] == kc[srcc].astype(other.cols[oi].dtype)
        n_match_overflow = jnp.maximum(total - M, 0)

        # ---- match-segment assembly: own row (from chunk) ++ other row ----
        own_cols = [Column(jnp.take(c.data, srcc, axis=0),
                           jnp.take(c.valid_mask(), srcc, axis=0))
                    for c in chunk.columns]
        oth_cols = [Column(r[pos], v[pos])
                    for r, v in zip(other.cols, other.valids)]
        cols = own_cols + oth_cols if side == LEFT else oth_cols + own_cols
        if self.condition is not None:
            pred = self.condition.eval(cols)
            emit &= pred.data.astype(bool) & pred.valid_mask()
        ops_out = jnp.where(jnp.take(signs, srcc) > 0,
                            OP_INSERT, OP_DELETE).astype(jnp.int8)

        outer_own = self._outer[side]
        outer_other = self._outer[1 - side]
        any_outer = outer_own or outer_other
        # condition-passing matches per chunk row (stored as the inserted
        # row's initial degree; zero => own NULL-row emission when outer)
        if any_outer:
            match_cnt = jax.ops.segment_sum(
                emit.astype(jnp.int32), srcc, num_segments=N)
        else:
            match_cnt = None

        if outer_other or outer_own:
            # signed degree delta onto the OTHER side's rows
            d_sign = jnp.where(emit, jnp.take(signs, srcc), 0)
            other_degree = other.degree.at[
                jnp.where(emit, pos, Co)].add(d_sign, mode="drop")
        else:
            other_degree = other.degree

        if outer_other:
            # NET degree transitions on the other side -> NULL-row flips
            touched = jnp.zeros(Co, dtype=bool).at[
                jnp.where(emit, pos, Co)].set(True, mode="drop")
            o_live = jnp.arange(Co, dtype=jnp.int32) < other.n
            was0 = other.degree == 0
            now0 = other_degree == 0
            t_del = touched & o_live & was0 & ~now0   # retract NULL row
            t_ins = touched & o_live & ~was0 & now0   # re-emit NULL row
            t_any = t_del | t_ins
            trank = jnp.cumsum(t_any.astype(jnp.int32)) - 1
            # positions of transition rows compacted into a [M] buffer
            tsel = jnp.zeros(M, dtype=jnp.int32).at[
                jnp.where(t_any & (trank < M), trank, M)].set(
                jnp.arange(Co, dtype=jnp.int32), mode="drop")
            n_trans = jnp.sum(t_any.astype(jnp.int32))
            t_vis = jnp.arange(M, dtype=jnp.int32) < jnp.minimum(n_trans, M)
            t_ops = jnp.where(t_del[tsel], OP_DELETE, OP_INSERT).astype(
                jnp.int8)
            n_match_overflow = n_match_overflow + jnp.maximum(n_trans - M, 0)
        else:
            tsel = t_vis = t_ops = None

        if outer_own:
            # own rows with no condition-passing match (incl. NULL keys)
            zerom = (active & (match_cnt == 0)) | (chunk.vis & ~key_valid)
            z_ops = jnp.where(signs > 0, OP_INSERT, OP_DELETE).astype(
                jnp.int8)
        else:
            zerom = z_ops = None

        if any_outer:
            # full output: [M matches][M transitions][N own-unmatched]
            def seg_col(match_c: Column, oth_row=None, oth_valid=None,
                        own_chunk_col=None, own_side_seg=True):
                parts_d = [match_c.data]
                parts_v = [match_c.valid_mask()]
                if outer_other:
                    if own_side_seg:       # own-side columns: NULL padding
                        parts_d.append(jnp.zeros(M, dtype=match_c.data.dtype))
                        parts_v.append(jnp.zeros(M, dtype=bool))
                    else:                  # other-side columns: real values
                        parts_d.append(oth_row[tsel])
                        parts_v.append(oth_valid[tsel])
                if outer_own:
                    if own_side_seg:       # own columns: chunk values
                        parts_d.append(own_chunk_col.data)
                        parts_v.append(own_chunk_col.valid_mask())
                    else:                  # other columns: NULL padding
                        parts_d.append(jnp.zeros(N, dtype=match_c.data.dtype))
                        parts_v.append(jnp.zeros(N, dtype=bool))
                return Column(jnp.concatenate(parts_d),
                              jnp.concatenate(parts_v))

            own_full = [seg_col(mc, own_chunk_col=cc, own_side_seg=True)
                        for mc, cc in zip(own_cols, chunk.columns)]
            oth_full = [seg_col(mc, oth_row=r, oth_valid=v,
                                own_side_seg=False)
                        for mc, r, v in zip(oth_cols, other.cols,
                                            other.valids)]
            cols = (own_full + oth_full if side == LEFT
                    else oth_full + own_full)
            ops_parts = [ops_out]
            vis_parts = [emit]
            if outer_other:
                ops_parts.append(t_ops)
                vis_parts.append(t_vis)
            if outer_own:
                ops_parts.append(z_ops)
                vis_parts.append(zerom)
            ops_out = jnp.concatenate(ops_parts)
            emit = jnp.concatenate(vis_parts)

        # ---- own-side update: evict + delete + merge-insert ----
        live = jnp.arange(C, dtype=jnp.int32) < own.n
        if self.clean_cols[side] is not None:
            cc = self.clean_cols[side]
            keep = live & ~(own.cols[cc] < wm_own)
        else:
            keep = live

        if not append_only:
            dlo = jnp.searchsorted(own.khash, h_own,
                                   side="left").astype(jnp.int32)
            dhi = jnp.searchsorted(own.khash, h_own,
                                   side="right").astype(jnp.int32)
            dlens = jnp.where(is_del, (dhi - dlo).astype(jnp.int64), 0)
            doffs = jnp.cumsum(dlens)
            dtot = doffs[N - 1]
            dsrc = _range_owner(doffs, M)
            dsrcc = jnp.clip(dsrc, 0, N - 1)
            dprev = jnp.where(dsrcc > 0, doffs[jnp.clip(dsrcc - 1, 0)], 0)
            dpos = jnp.clip(dlo[dsrcc] + (j - dprev), 0,
                            C - 1).astype(jnp.int32)
            cand = (j < jnp.minimum(dtot, M)) & keep[dpos]
            for kc, ki in zip(key_cols, key_idx):
                cand &= own.cols[ki][dpos] == kc[dsrcc].astype(own.cols[ki].dtype)
            for p in pk_idx:
                cand &= (own.cols[p][dpos]
                         == chunk.columns[p].data[dsrcc].astype(own.cols[p].dtype))
            # one victim per retraction: the lowest matching state pos
            victim = jnp.full(N, C, dtype=jnp.int32).at[
                jnp.where(cand, dsrcc, N)].min(dpos, mode="drop")
            found = victim < C
            keep = keep.at[jnp.where(found, victim, C)].set(False, mode="drop")
            n_del_miss = jnp.sum((is_del & ~found).astype(jnp.int32))
        else:
            n_del_miss = jnp.int32(0)

        # merge: kept state rows + new rows, both in hash order
        ins_h = jnp.where(is_ins, h_own, _HSENTINEL)
        iorder = jnp.argsort(ins_h, stable=True)          # new rows first
        moved, n_after, n_row_overflow = _merge_sorted(
            keep, self.clean_cols[side] is not None or not append_only,
            jnp.sum(is_ins.astype(jnp.int32)), *own.lanes(),
            [ins_h[iorder]]
            + [c.data[iorder] for c in chunk.columns[:len(own.cols)]]
            + [c.valid_mask()[iorder] for c in chunk.columns[:len(own.cols)]]
            # provenance travels with the kept rows; merged-in rows land
            # on the -1 fill
            + [match_cnt[iorder] if any_outer else None, None])
        own2 = SortedSideState.from_lanes(moved, n_after)
        errs = errs + jnp.stack(
            [n_match_overflow, n_del_miss, n_row_overflow]).astype(jnp.int32)
        return (own2, other_degree, tuple(cols), ops_out, emit, errs, own2.n,
                total)

    # ------------------------------------------------------------- evict
    def _evict_impl(self, own: SortedSideState, wm, kh, side: int):
        """Barrier-time eviction: rows below the side's watermark bound
        (idle cleaning — the apply path evicts inline) and/or rows whose
        key hash falls under `kh` (memory spill's fallback axis when the
        time axis cannot discriminate; pass -1 to disable — key hashes
        are nonnegative 63-bit)."""
        C = own.capacity
        cc = self.clean_cols[side]
        live = jnp.arange(C, dtype=jnp.int32) < own.n
        drop = own.khash < kh
        if cc is not None:
            drop = drop | (own.cols[cc] < wm)
        keep = live & ~drop
        return SortedSideState.from_lanes(
            compact(keep, *own.lanes()), jnp.sum(keep.astype(jnp.int32)))

    def _evict_side(self, s: int, wm, kh) -> None:
        self.sides[s] = self._evict(self.sides[s], wm, kh, side=s)
        # the watchdog's live count follows (it fed on apply outputs only)
        self._n_dev[s] = self.sides[s].n

    # ------------------------------------------------------- persistence
    @staticmethod
    def _diff_impl(cur: SortedSideState, snap: SortedSideState):
        """The rows that changed since `snap` was flushed, read off the
        provenance lane: a live row of `cur` with src < 0 is an insert; a
        live row of `snap` whose position no row of `cur` carries is a
        delete. O(C) elementwise, one scatter and the compaction — no sort,
        no search.
        Returns compacted (del_cols, n_del, ins_cols, n_ins), both in
        state order; only the first n entries of each are meaningful."""
        C, Cs = cur.capacity, snap.capacity
        live_c = jnp.arange(C, dtype=jnp.int32) < cur.n
        live_s = jnp.arange(Cs, dtype=jnp.int32) < snap.n
        carried = live_c & (cur.src >= 0)
        # an int32 mask, not a bool one: the TPU compiler sorts the indices
        # of a `pred` scatter first (a capacity-sized sort, ~1 ms of the 4
        # at 2^19, but eight more seconds to compile)
        survived = jnp.zeros(Cs, dtype=jnp.int32).at[
            jnp.where(carried, cur.src, Cs)].set(1, mode="drop")

        def compact(mask, cols):
            cap = mask.shape[0]
            rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
            sel = jnp.zeros(cap, dtype=jnp.int32).at[
                jnp.where(mask, rank, cap)].set(
                jnp.arange(cap, dtype=jnp.int32), mode="drop")
            return tuple(c[sel] for c in cols), jnp.sum(mask.astype(jnp.int32))

        del_cols, n_del = compact(live_s & (survived == 0), snap.cols)
        ins_cols, n_ins = compact(live_c & ~carried, cur.cols)
        return del_cols, n_del, ins_cols, n_ins

    def _src_iota(self, capacity: int) -> jnp.ndarray:
        """src of a side that IS the diff base: every row at its own
        position (the sharded subclass: each shard's local positions)."""
        return jnp.arange(capacity, dtype=jnp.int32)

    def _rebase(self, s: int) -> None:
        """The live side becomes the diff base (after a flush, a recovery,
        a spill that must not turn into durable deletes): `_snap` aliases
        it and its provenance lane restarts at the identity. The one place
        that does both, so no site re-points the base and keeps a stale
        lane."""
        C = self.capacity[s]
        if C not in self._src_iotas:
            self._src_iotas[C] = self._src_iota(C)
        self.sides[s] = replace(self.sides[s], src=self._src_iotas[C])
        self._snap[s] = self.sides[s]

    def _count_persisted(self, s: int, n_del: int, n_ins: int) -> None:
        for op, n in (("delete", n_del), ("insert", n_ins)):
            GLOBAL_METRICS.counter(
                JOIN_PERSIST_ROWS, executor=self.mem_name or self.identity,
                side=("left", "right")[s], op=op).inc(n)
            key = f"join_persist_{op}_rows"
            self._phase_counts[key] = self._phase_counts.get(key, 0) + n

    def take_phase_counts(self) -> dict:
        """This barrier interval's share of `join_persist_rows_total`, both
        sides together (`join_persist_delete_rows`,
        `join_persist_insert_rows`; absent where no durable flush ran), and
        what the watchdog's fetch brought (`_publish_match`,
        `_publish_live_rows`; absent where it made none), for the actor's
        phase dict."""
        counts, self._phase_counts = self._phase_counts, {}
        return counts

    def _publish_live_rows(self, n_left: int, n_right: int,
                           shards: int = 1) -> None:
        """How full the pools are, from the counts the watchdog's barrier
        fetch brings anyway; the fuller side's `join_live_rows` over its
        `join_capacity` (all `shards` together) goes into the phase dict."""
        for side, n in (("left", n_left), ("right", n_right)):
            GLOBAL_METRICS.gauge(
                JOIN_LIVE_ROWS, executor=self.mem_name or self.identity,
                side=side).set(float(n))
        n, cap = max(zip((n_left, n_right), self.capacity),
                     key=lambda nc: nc[0] / nc[1])
        self._phase_counts.update(join_live_rows=n,
                                  join_capacity=cap * shards)

    def _publish_match(self, rows, peaks) -> None:
        """What this interval's chunks asked of their match buffers, from
        the watchdog's fetch: `join_match_rows` (emitted rows, both sides),
        and of the side that came nearest its buffer's end
        `join_match_peak` (the most equi-key candidates one chunk found)
        over `join_match_width` (that buffer's rows: factor x chunk width;
        more candidates than that fail-stop the epoch)."""
        label = self.mem_name or self.identity
        for s, side in enumerate(("left", "right")):
            GLOBAL_METRICS.counter(JOIN_MATCH_ROWS, executor=label,
                                   side=side).inc(rows[s])
            GLOBAL_METRICS.gauge(JOIN_MATCH_BUFFER_PEAK, executor=label,
                                 side=side).set(float(peaks[s]))
        peak, width = max(zip(peaks, self._match_width),
                          key=lambda pw: pw[0] / max(1, pw[1]))
        self._phase_counts.update(join_match_rows=sum(rows),
                                  join_match_peak=peak,
                                  join_match_width=width)

    async def _persist(self, barrier: Barrier) -> None:
        """Write both sides' changed rows to their StateTables, before the
        barrier leaves the join.

        d2h discipline: a fetch has a fixed per-call cost, so a side's
        diff ships in TWO calls — one for its two counts, one for every
        changed row as one packed payload (utils/d2h.py), never a fetch
        per column. Both sides' diffs are dispatched first; every wait is
        awaited off the loop (`off_loop`), the count-dependent packing and
        the writes stay on it."""
        diffs = []
        for s in (LEFT, RIGHT):
            if self.state_tables[s] is not None and self._flush_dirty[s]:
                del_cols, n_del, ins_cols, n_ins = self._diff(
                    self.sides[s], self._snap[s])
                diffs.append((s, del_cols, ins_cols,
                              jnp.stack([n_del, n_ins])))
                self._rebase(s)
                self._flush_dirty[s] = False
        packs = []
        for s, del_cols, ins_cols, counts_dev in diffs:
            counts = await off_loop(fetch_small, counts_dev)
            nd, ni = int(counts[0]), int(counts[1])
            self._count_persisted(s, nd, ni)
            if nd or ni:
                packs.append((s, nd, ni, prepare_prefix_groups(
                    [(list(del_cols), nd), (list(ins_cols), ni)])))
        for s, nd, ni, (flat, metas, meta) in packs:
            dels, inss = finish_prefix_groups(
                await off_loop(fetch_flat, flat), metas, meta)
            self._write_diff(self.state_tables[s], nd, dels, ni, inss)
        for st in self.state_tables:
            if st is not None:
                st.commit(barrier.epoch.curr)

    @staticmethod
    def _write_diff(st, nd: int, dels, ni: int, inss) -> None:
        """A side's fetched diff into its StateTable, deletes strictly
        before inserts: an updated row (same pk, new values) diffs as
        delete(old)+insert(new) on one key."""
        for op, n, cols in ((OP_DELETE, nd, dels), (OP_INSERT, ni, inss)):
            if n:
                st.write_chunk_columns(np.full(n, op, dtype=np.int8), cols,
                                       np.ones(n, dtype=bool))

    def _recover_reset(self, s: int, rows: list) -> None:
        """Size a side for recovery and reset it to empty (the sharded
        subclass sizes by the WORST shard's row count instead)."""
        n = len(rows)
        while n > 0.7 * self.capacity[s]:
            self.capacity[s] *= 2
        self.sides[s] = self._empty(s)

    def recover(self) -> None:
        """Rebuild device state from the per-side StateTables.

        Replays RIGHT rows first (LEFT is empty, so nothing matches), then
        LEFT rows, whose probe of the restored RIGHT rebuilds the degree
        columns on BOTH sides (match_cnt for left inserts, scatter-adds
        for right rows) including the non-equi condition — so degrees need
        no durable table of their own. Replay outputs are discarded."""
        # spilled rows are in the durable tables too (eviction re-points
        # the diff base instead of deleting); recovery rebuilds them
        # resident and the host spill is dropped
        for sp in self._spill:
            sp.clear()
        if all(st is None for st in self.state_tables):
            return
        rows_by_side: list[list] = []
        for s in (LEFT, RIGHT):
            st = self.state_tables[s]
            rows_by_side.append(
                [] if st is None else [r for _, r in st.iter_all()])
        for s in (LEFT, RIGHT):
            self._recover_reset(s, rows_by_side[s])
        # An apply passes over the whole pool whatever the chunk carries
        # (0.35 s at 2^19 on a v5e before PR 35's moves, milliseconds
        # since): 2^14 rows a batch, not 2^12, replays q7's 236 k rows in
        # 15 applies, not 58. Never wider than a pool.
        batch = min(1 << 14, *self.capacity)
        # generous match buffer: a replay batch probes the FULL restored
        # other side; overflow here would silently corrupt degrees, and
        # the barrier watchdog fail-stops on the counter if it ever trips.
        # Only LEFT rows find anything to match (RIGHT replays into an
        # empty LEFT), so the LEFT side's factor decides: a RIGHT side
        # whose every row probes a whole window of LEFT rows (q5's
        # `num >= maxn`: a factor in the thousands) is not paid here
        mf = max(self.match_factors[LEFT], 64)
        # flag read by the sharded dispatch: replay rows are already in
        # join-input schema, so chain preludes (raw-chunk transforms)
        # and the mesh ingest log must not see them
        self._state_replay = True
        try:
            for s in (RIGHT, LEFT):
                rows = rows_by_side[s]
                sch = self.inputs[s].schema
                for i in range(0, len(rows), batch):
                    part = rows[i:i + batch]
                    arrays = [np.asarray([r[k] for r in part],
                                         dtype=f.data_type.np_dtype)
                              for k, f in enumerate(sch)]
                    # every batch at ONE capacity, the last short one too:
                    # a tail at its own power of two is one more apply
                    # program a side, met or not by the luck of the row
                    # count (seconds of a timed recovery each)
                    (self.sides[s], degree, self._errs_dev,
                     self._n_dev[s]) = self._replay(
                        self.sides[s], self.sides[1 - s], self._errs_dev,
                        StreamChunk.from_numpy(sch, arrays, capacity=batch),
                        jnp.int64(NO_WATERMARK), side=s, match_factor=mf)
                    self.sides[1 - s] = replace(self.sides[1 - s],
                                                degree=degree)
        finally:
            self._state_replay = False
        for s in (LEFT, RIGHT):
            self._rebase(s)

    # ------------------------------------------------- HBM memory manager
    def state_bytes(self) -> int:
        return pytree_bytes(self.sides)

    @property
    def mem_spilled_rows(self) -> int:
        return self._spill[LEFT].rows + self._spill[RIGHT].rows

    def memory_enable_lru(self) -> None:
        self._mem_on = True

    def _mem_local_slices(self, s: int) -> list:
        """Local side-state views the spill programs run over (the
        sharded subclass returns one slice per shard)."""
        return [self.sides[s]]

    def _mem_live_ns(self) -> list:
        vals = np.asarray(jnp.stack([self.sides[LEFT].n,
                                     self.sides[RIGHT].n]))
        return [int(vals[0]), int(vals[1])]

    def _mem_cc_range_impl(self, side_state: SortedSideState, side: int):
        cc = self.clean_cols[side]
        C = side_state.capacity
        live = jnp.arange(C, dtype=jnp.int32) < side_state.n
        v = side_state.cols[cc].astype(jnp.int64)
        big = jnp.iinfo(jnp.int64).max
        lo = jnp.min(jnp.where(live, v, big))
        hi = jnp.max(jnp.where(live, v, -big))
        return lo, hi

    def _mem_pack_impl(self, side_state: SortedSideState, cc_thresh,
                       kh_thresh, side: int):
        cc = self.clean_cols[side]
        C = side_state.capacity
        live = jnp.arange(C, dtype=jnp.int32) < side_state.n
        mask = side_state.khash < kh_thresh
        if cc is not None:
            mask = mask | (side_state.cols[cc] < cc_thresh)
        return pack_rows(live & mask, list(side_state.cols)
                         + list(side_state.valids))

    def _mem_kh_cut_impl(self, side_state: SortedSideState, frac_num: int):
        """Key-hash value at the frac_num/4 quantile of the live prefix
        (the store is SORTED by khash, so a quantile is one gather), cut
        back to the start of its key's range: a threshold never parts the
        rows of one key."""
        idx = jnp.clip(side_state.n * frac_num // 4 - 1, 0,
                       side_state.capacity - 1)
        return jnp.where(side_state.n > 0,
                         side_state.khash[idx] & _KEY_MASK, jnp.int64(-1))

    def _mem_cc_range(self, s: int) -> tuple[int, int]:
        parts = [self._mem_cc_range_prog(sl, side=s)
                 for sl in self._mem_local_slices(s)]
        arr = np.asarray(jnp.stack([x for p in parts for x in p]))
        return int(arr[0::2].min()), int(arr[1::2].max())

    def memory_maintain(self, epoch: int) -> None:
        """Barrier-time manager tick: sides past 60% occupancy spill cold
        rows to host ahead of the overflow cliff, so a tight fixed
        capacity degrades to host traffic instead of fail-stop +
        recovery-resize. Coldness axis: the state-cleaning (event-time)
        column when its live range discriminates — oldest windows first;
        otherwise (one hot window owns the shard) a key-hash prefix, so
        the spill is uniform over keys and reloads stay key-targeted."""
        if not self._mem_on or self.join_type != "inner":
            return
        ns = None
        for s in (LEFT, RIGHT):
            if ns is None:
                ns = self._mem_live_ns()
            if ns[s] <= 0.6 * self.capacity[s]:
                continue
            cc_t, kh_t = NO_WATERMARK, -1
            if self.clean_cols[s] is not None:
                lo, hi = self._mem_cc_range(s)
                if hi > lo:
                    cc_t = min(hi, lo + max(1, (hi - lo) // 2))
            if cc_t == NO_WATERMARK:
                # hash-prefix fallback: keep only the newest quarter of
                # capacity's worth so one interval's burst still fits
                vals = np.asarray(jnp.stack(
                    [self._mem_kh_cut_prog(sl, 3)
                     for sl in self._mem_local_slices(s)]))
                kh_t = int(np.median(vals))
                if kh_t <= 0:
                    continue
            self._mem_spill_below(s, cc_t, kh_t)

    def _mem_spill_below(self, s: int, cc_thresh: int,
                         kh_thresh: int) -> int:
        """Pack + fetch the rows under the thresholds, park them in the
        host spill, drop them on device. The durable table KEEPS them
        (the durable diff is re-based past the eviction), which is what
        makes crash recovery rebuild them for free."""
        from ..utils.d2h import fetch_prefix_groups
        nc = len(self._col_dtypes[s])
        t_dev = jnp.int64(cc_thresh)
        kh_dev = jnp.int64(kh_thresh)
        packs = [self._mem_pack_prog(sl, t_dev, kh_dev, side=s)
                 for sl in self._mem_local_slices(s)]
        counts = np.asarray(jnp.stack([p[1] for p in packs]))
        total = int(counts.sum())
        if total == 0:
            return 0
        groups = [(list(p[0]), int(c))
                  for p, c in zip(packs, counts) if int(c)]
        for host in fetch_prefix_groups(groups):
            for r in range(host[0].shape[0]):
                vals = tuple(host[c][r].item() for c in range(nc))
                valids = tuple(bool(host[nc + c][r]) for c in range(nc))
                key = tuple(vals[i] for i in self.key_indices[s])
                self._spill[s].add(key, (vals, valids))
        self._evict_side(s, t_dev, kh_dev)
        # the eviction must NOT become durable deletes: re-base the diff
        # so the next persist skips it (the rows stay in the table for
        # recovery; reloads re-insert them as idempotent upserts)
        self._rebase(s)
        from ..utils.metrics import HBM_EVICTIONS
        HBM_EVICTIONS.inc()
        return total

    def _mem_check_reload(self, side: int, chunk: StreamChunk) -> None:
        """Read-through before a chunk applies: its keys can probe the
        other side and retract on its own, so spilled keys on EITHER side
        reload first (one packed fetch of the chunk's key columns, paid
        only while spilled state exists)."""
        from ..utils.d2h import fetch_columns
        key_idx = self.key_indices[side]
        host = fetch_columns(
            [chunk.columns[i].data for i in key_idx] + [chunk.vis])
        idx = np.flatnonzero(host[-1].astype(bool))
        keys, seen = [], set()
        for vals in zip(*(c[idx] for c in host[:-1])):
            k = tuple(v.item() for v in vals)
            if k not in seen:
                seen.add(k)
                keys.append(k)
        for t in (side, 1 - side):
            touched = self._spill[t].take_touched(keys)
            if touched:
                self._mem_reload_rows(
                    t, [rw for rows in touched.values() for rw in rows])
                self.mem_reload_count += len(touched)
                from ..utils.metrics import HBM_RELOADS
                HBM_RELOADS.inc(len(touched))

    def _mem_reload_rows(self, t: int, entries: list) -> None:
        """Replay spilled rows through the normal apply path — the exact
        recovery-replay shape — and DISCARD the emitted matches (they
        were already emitted when the rows first arrived; inner join, so
        no degree side effects)."""
        if not entries:
            return
        sch = self.inputs[t].schema
        mf = max(self.match_factors[t], 64)
        batch = 1 << 12
        for i in range(0, len(entries), batch):
            part = entries[i:i + batch]
            cap = 1 << max(1, (len(part) - 1).bit_length())
            cols = []
            for c, f in enumerate(sch):
                data = np.zeros(cap, dtype=f.data_type.np_dtype)
                valid = np.zeros(cap, dtype=bool)
                for r, (vals, valids) in enumerate(part):
                    data[r] = vals[c]
                    valid[r] = valids[c]
                cols.append(Column(jnp.asarray(data), jnp.asarray(valid)))
            ch = StreamChunk(tuple(cols),
                             jnp.full(cap, OP_INSERT, dtype=jnp.int8),
                             jnp.asarray(np.arange(cap) < len(part)), sch)
            (self.sides[t], degree, self._errs_dev,
             self._n_dev[t]) = self._replay(
                self.sides[t], self.sides[1 - t], self._errs_dev, ch,
                jnp.int64(self._pending_clean[t]), side=t, match_factor=mf)
            self.sides[1 - t] = replace(self.sides[1 - t], degree=degree)
        self._dirty[t] = True
        self._flush_dirty[t] = True

    def _mem_clean_spilled(self, s: int) -> None:
        """Watermark cleaning of evicted ranges: spilled rows below the
        side's eviction bound can never match again — drop them and write
        their durable tombstones."""
        wm = self._pending_clean[s]
        col = self.clean_cols[s]
        if col is None or wm == NO_WATERMARK or not self._spill[s]:
            return
        dead: list = []
        for k in list(self._spill[s].keys()):
            rows = self._spill[s].pop(k)
            for vals, valids in rows:
                if vals[col] < wm:
                    dead.append(vals)
                else:
                    self._spill[s].add(k, (vals, valids))
        if dead and self.state_tables[s] is not None:
            self.state_tables[s].write_chunk_rows(
                [(int(OP_DELETE), vals) for vals in dead])

    # ---------------------------------------------------------- cleaning
    def _recompute_pending(self) -> None:
        """Re-derive each side's eviction bound from the latest observed
        watermarks per its clean spec (monotone: watermarks only grow)."""
        for t in (LEFT, RIGHT):
            spec = self.clean_specs[t]
            if spec is None:
                continue
            kind = spec[0]
            if kind == "own":
                v = self._wms[t].get(spec[1])
            elif kind == "pair":
                kpos = spec[2]
                a = self._wms[t].get(self.key_indices[t][kpos])
                b = self._wms[1 - t].get(self.key_indices[1 - t][kpos])
                v = None if a is None or b is None else min(a, b)
            elif kind == "band":
                o = self._wms[1 - t].get(spec[2])
                v = None if o is None else o + spec[3]
                if len(spec) > 4 and spec[4] is not None:
                    own = self._wms[t].get(spec[4])
                    v = None if own is None or v is None else min(v, own)
            else:
                raise ValueError(f"unknown clean spec {spec!r}")
            if v is not None and v > self._pending_clean[t]:
                self._pending_clean[t] = v

    def _maybe_grow(self) -> None:
        """Double a side's capacity at 0.7 occupancy (memory-pressure
        growth instead of fail-stop; needs the watchdog's barrier fetch
        for the live count — transfer-free mode keeps fixed capacity)."""
        known = getattr(self, "_n_known", None)
        if known is None:
            return
        for s in (LEFT, RIGHT):
            if known[s] <= 0.7 * self.capacity[s]:
                continue
            new_c = self.capacity[s] * 2
            for attr, st in (("sides", self.sides), ("_snap", self._snap)):
                side = st[s]
                if side is None or side.capacity >= new_c:
                    continue
                kh, cols, valids = grow_sorted_arrays(
                    side.khash, side.cols, side.valids, new_c)
                pad = new_c - side.capacity
                deg = jnp.concatenate([
                    side.degree, jnp.zeros(pad, dtype=jnp.int32)])
                src = jnp.concatenate([
                    side.src, jnp.full(pad, -1, dtype=jnp.int32)])
                st[s] = SortedSideState(kh, cols, valids, deg, src, side.n)
            self.capacity[s] = new_c
            self.rebuilds += 1

    # --------------------------------------------------------- watchdog
    async def _check_watchdog(self) -> None:
        packed, self._match_dev = self._watchdog_pack(
            self._errs_dev, self._n_dev[LEFT], self._n_dev[RIGHT],
            self._match_dev)
        vals = [int(x) for x in await off_loop(fetch_small, packed)]
        n_mo, n_miss, n_ro = vals[:3]
        self._n_known = vals[3:5]
        self._publish_live_rows(*self._n_known)
        self._publish_match(vals[5:7], vals[7:9])
        if n_mo:
            raise RuntimeError(
                f"sorted-join match-buffer overflow ({n_mo} matches "
                f"dropped; raise match_factor)")
        if n_ro:
            raise RuntimeError(
                f"sorted-join state overflow ({n_ro} rows dropped; "
                f"capacity {self.capacity})")
        if n_miss:
            raise RuntimeError(
                f"sorted-join changelog inconsistency: {n_miss} deletes "
                f"matched no stored row")

    # ----------------------------------------------------------- stream
    async def execute(self):
        first = True
        async for kind, s, msg in barrier_align(*self.inputs):
            if kind == "chunk":
                if self._spill[LEFT] or self._spill[RIGHT]:
                    self._mem_check_reload(s, msg)
                wm = jnp.int64(self._pending_clean[s])
                self._cleaned_to[s] = self._pending_clean[s]
                mf = self.match_factors[s]
                args = (self.sides[s], self.sides[1 - s], self._errs_dev)
                if self._match_dev is None:
                    out = self._apply(*args, msg, wm, side=s, match_factor=mf)
                else:
                    *out, self._match_dev = self._apply_counted(
                        *args, self._match_dev, msg, wm, side=s,
                        match_factor=mf)
                    self._match_width[s] = mf * msg.capacity
                (self.sides[s], oth_degree, cols, ops, vis, self._errs_dev,
                 self._n_dev[s]) = out
                self.sides[1 - s] = replace(self.sides[1 - s],
                                            degree=oth_degree)
                self._dirty[s] = True
                self._flush_dirty[s] = True
                if self.temporal and s == RIGHT:
                    continue        # table-side updates emit nothing
                yield StreamChunk(
                    tuple(cols[i] for i in self.output_indices), ops, vis,
                    self.schema)
            elif kind == "barrier":
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
                dirty_any = any(self._dirty)
                # idle sides still clean by watermark at barriers
                for s2 in (LEFT, RIGHT):
                    if (self.clean_cols[s2] is not None
                            and self._pending_clean[s2] != NO_WATERMARK
                            and self._pending_clean[s2] != self._cleaned_to[s2]
                            and not self._dirty[s2]):
                        self._evict_side(
                            s2, jnp.int64(self._pending_clean[s2]),
                            jnp.int64(-1))
                        self._cleaned_to[s2] = self._pending_clean[s2]
                        self._flush_dirty[s2] = True
                    self._mem_clean_spilled(s2)
                    self._dirty[s2] = False
                # watchdog BEFORE the durable commit: errors fail-stop
                # this epoch's checkpoint
                if self.watchdog_interval and (stopping or dirty_any):
                    await self._check_watchdog()
                    self._maybe_grow()
                await self._persist(barrier)
                yield barrier
            else:
                wm: Watermark = msg
                self._wms[s][wm.col_idx] = wm.val
                self._recompute_pending()
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
