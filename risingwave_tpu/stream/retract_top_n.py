"""(Group)TopN — a dense sorted store ranked at every barrier.

Reference: src/stream/src/executor/top_n/ (top_n_cache.rs, group_top_n.rs,
group_top_n_appendonly.rs). Two things plan this executor (frontend/
binder.py): `ORDER BY ... LIMIT` over a query's changelog (`_plan_top_n`: no
group key, a singleton fragment) and a RANK FILTER — `rank <= N` / `< N` /
`= 1` directly over `ROW_NUMBER() OVER (PARTITION BY g ORDER BY o)` — which
becomes a group top-N in the fragment of its input (`_plan_over_window`:
upstream's `over_window_to_topn_rule`). Any other use of a window function
stays a `general_over_window`.

The rows live in a dense array store (sorted_store.py): a prefix [0, n) of
fixed-capacity lanes, merged into by log-step moves. What it holds, and in
WHICH ORDER, depends on the input. TIES on the order key are broken by the
stream key ascending either way (for a generated `_row_id`: arrival order,
the earlier row first), as upstream orders its `TopNCache` by (order key,
stream key); never by the key's hash.

* RETRACTING input (a top-N over an aggregate's or a join's changelog): ALL
  input rows, so a deleted top row is refilled from below — rank promotion
  pulls the next row in — sorted by a 63-bit hash of the ROW KEY (the
  stream key: retractions address rows by it; `sorted_store_apply`). At
  each barrier the live rows are lexsorted by (group hash, order key,
  stream key) — iterated stable argsorts over the capacity — and ranked
  within their group runs. The last emitted top set is a second store
  sorted by a full-row hash; the new set is diffed against it by
  membership (two searchsorteds): Deletes for rows that left, Inserts for
  rows that came. A rank that moved is a Delete + Insert of the row under
  its old and new rank. The state table holds every input row.

* APPEND-ONLY input (the binder knows: `info.append_only`): only the rows
  that can still rank — at most `offset + limit` a group after each barrier
  — because nothing can promote a dropped row, and nothing ever has to
  find a row by its key. So the store is kept in RANK ORDER: ascending by
  (group hash, order keys as `_sort_keys` states them, stream-key tie
  columns), the group hash its first lane. A chunk is sorted by that key
  (N rows, not the capacity), each row's place found by ONE lexicographic
  search through the store's key lanes, and the lanes moved by the joins'
  merge given the two ranks (`sorted_join._merge_sorted`). The store
  carries one hidden int32 lane per row, the rank it was last emitted
  under (none yet: fresh since the last barrier, or kept below `offset`),
  so the store IS the emitted baseline: no second copy. The barrier runs
  two programs around ONE small fetch: `retract_top_n_rank` reads a row's
  rank off the run boundaries of the group-hash lane (position less run
  start: no sort, no gather) and counts what changed; `retract_top_n_emit`
  gathers the changed rows — an Insert, a Delete (pushed past rank N:
  dropped from the store for good), or, where the rank is an output
  column, an adjacent UpdateDelete / UpdateInsert pair under the old and
  the new rank — and compacts the store to the kept rows, which leaves
  them in rank order. Two groups whose hashes collide rank as one run (the
  retracting form draws its runs on the same hash). The state table holds
  exactly the kept rows; a recovery merges them back in whatever order
  they come.

Either way the emitted chunk is as wide as a power of two over twice the
rows that changed (the count rides the barrier's one fetch; the width only
grows, as a hash agg's flush), never `2 x capacity`; without a watchdog
fetch (`watchdog_interval=None`) nothing tells the host the count and the
chunk is capacity-wide. The durable write is columnar and off the event
loop: the barrier dispatches the packs, the checkpoint uploader waits for
them and writes (`utils/d2h.py` `defer_prefix_flush`).

`emit_rank` adds the 1-based rank within the group as a last INT64 output
column (`ROW_NUMBER()` selected above the rank filter).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import (
    Column, StreamChunk, OP_DELETE, OP_INSERT, OP_UPDATE_DELETE,
    OP_UPDATE_INSERT, op_sign,
)
from ..common.types import DataType, Field, Schema
from ..ops.hash_table import stable_lexsort
from ..ops.jit_state import jit_state
from ..ops.monotone_move import compact
from ..utils.d2h import defer_prefix_flush, fetch_small, off_loop
from ..utils.metrics import (
    GLOBAL_METRICS, TOP_N_EMIT_ROWS, TOP_N_LIVE_ROWS, TOP_N_PRUNED_ROWS,
    TOP_N_SORTED_ROWS,
)
from ..utils.trace import span
from .executor import Executor, StatefulUnaryExecutor
from .message import Barrier, Watermark
from .sorted_join import (
    _HSENTINEL, _merge_sorted, _rank_of_ascending, grow_sorted_arrays,
    key_hash,
)
from .sorted_store import (
    GrowableSortedStore, segment_starts, sorted_store_apply,
)

# the hidden lane of an append-only store: the 0-based rank a row was last
# emitted under, or one of these
_FRESH = -2          # arrived since the last barrier
_UNEMITTED = -1      # kept at a barrier, below `offset`: never sent

# the narrowest chunk a flush lays out
FLUSH_MIN_ROWS = 256

RANK_COLUMN = "_rank"


def _valid_bits(valids) -> jnp.ndarray:
    """Validity lanes (at most 63) as ONE int64 lane, bit j = column j
    valid: what a persist view ships instead of a bool lane a column."""
    bits = jnp.zeros(valids[0].shape[0], dtype=jnp.int64)
    for j, v in enumerate(valids):
        bits = bits | (v.astype(jnp.int64) << j)
    return bits


def _valids_of_bits(bits: np.ndarray, n_cols: int) -> Optional[list]:
    """Host inverse of `_valid_bits`; None where no cell is NULL."""
    if bool(np.all(bits == (1 << n_cols) - 1)):   # an empty lane too
        return None
    return [((bits >> j) & 1).astype(bool) for j in range(n_cols)]


def _nth_set(mask: jnp.ndarray, width: int):
    """(src, n): for slot s of `width`, the position of the s-th set bit of
    `mask` (clipped where s >= n), and the number of set bits. A search of
    `width` slots through the prefix sum: no capacity-wide scatter."""
    cum = jnp.cumsum(mask.astype(jnp.int32))
    src = jnp.searchsorted(cum, jnp.arange(width, dtype=jnp.int32),
                           side="right")
    return jnp.clip(src, 0, mask.shape[0] - 1).astype(jnp.int32), cum[-1]


def _lex_lt(a: Sequence, b: Sequence) -> jnp.ndarray:
    """a < b, lane-wise lexicographic over the key lanes, the most
    significant first, in the order a sort puts them: every NaN alike and
    behind every number, -0.0 and 0.0 alike."""
    lt = jnp.zeros(a[0].shape, dtype=bool)
    eq = jnp.ones(a[0].shape, dtype=bool)
    for x, y in zip(a, b):
        x_lt, x_eq = x < y, x == y
        if jnp.issubdtype(x.dtype, jnp.floating):
            x_nan, y_nan = jnp.isnan(x), jnp.isnan(y)
            x_lt, x_eq = x_lt | (y_nan & ~x_nan), x_eq | (x_nan & y_nan)
        lt, eq = lt | (eq & x_lt), eq & x_eq
    return lt


class RetractableTopNExecutor(GrowableSortedStore,
                              StatefulUnaryExecutor):
    """Output: the rows whose rank within their group (by `order_specs`,
    ties by the stream key) falls in [offset, offset+limit), maintained
    incrementally under inserts AND, unless `append_only`, retractions."""

    def __init__(self, input: Executor,
                 group_key_indices: Sequence[int],
                 order_col=None, limit: int = 0, offset: int = 0,
                 descending: bool = False,
                 order_specs: Optional[Sequence[tuple]] = None,
                 capacity: int = 1 << 14,
                 state_table=None,
                 pk_indices: Optional[Sequence[int]] = None,
                 watchdog_interval: Optional[int] = 1,
                 append_only: bool = False,
                 emit_rank: bool = False):
        self.input = input
        self.store_schema = input.schema
        self.emit_rank = bool(emit_rank)
        self.schema = input.schema if not emit_rank else Schema(
            tuple(input.schema) + (Field(RANK_COLUMN, DataType.INT64),))
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
        # ties: the stream-key columns the order key does not name already
        ordered = {c for c, _ in self.order_specs}
        self._tie_cols = tuple(p for p in self.pk_indices
                               if p not in ordered)
        self.limit = limit
        self.offset = offset
        self.capacity = capacity
        self.append_only = bool(append_only)
        self.identity = (f"RetractTopN(g={self.group_key_indices}, "
                         f"by={self.order_specs}, k={limit}"
                         f"{', append-only' if append_only else ''})")
        C = capacity
        dts = tuple(f.data_type.jnp_dtype for f in input.schema)
        self._col_dtypes = dts
        self._n_in = len(dts)
        # the dense store: `khash` is the row-key hash it is sorted by, or,
        # for an append-only input, the GROUP hash that leads its rank
        # order; an append-only store's last lane is the emitted rank (see
        # the module docstring)
        lanes = dts + ((jnp.int32,) if self.append_only else ())
        self.khash = jnp.full(C, _HSENTINEL, dtype=jnp.int64)
        self.cols = tuple(jnp.zeros(C, dtype=dt) for dt in lanes)
        self.valids = tuple(jnp.zeros(C, dtype=bool) for _ in lanes)
        self.n = jnp.int32(0)
        self._errs_dev = jnp.zeros(2, dtype=jnp.int32)  # [row_ovf, del_miss]
        if not self.append_only:
            # last emitted top set, as a sorted array of full-row hashes
            # plus the row payloads (for emitting deletes), rank included
            # where it is an output column
            top_dts = dts + ((jnp.int64,) if self.emit_rank else ())
            self.top_hash = jnp.full(C, _HSENTINEL, dtype=jnp.int64)
            self.top_cols = tuple(jnp.zeros(C, dtype=dt) for dt in top_dts)
            self.top_valids = tuple(jnp.zeros(C, dtype=bool)
                                    for _ in top_dts)
            self.top_n = jnp.int32(0)
        # the width of the flush's chunk (and of the persist view): a power
        # of two over twice the count the barrier's fetch brought, only
        # ever wider (each new one is a compile here and downstream)
        self._emit_width = FLUSH_MIN_ROWS
        self._persist_width = FLUSH_MIN_ROWS
        # what a barrier's programs left for flush() / persist()
        self._flushed = None
        self._ready: Optional[StreamChunk] = None
        self._persist_view = None
        self._persist_counts: Optional[tuple] = None
        self._pack_dev = None
        self._epoch_chunks: list[StreamChunk] = []
        self._phase_counts: dict = {}
        # rows the interval's chunks brought to an append-only store's
        # N-wide sorts (their capacities: what a sort costs by)
        self._chunk_rows = 0
        self._build_programs()
        self._init_stateful(state_table, watchdog_interval)

    def _build_programs(self) -> None:
        """The programs close over the capacity: built at construction and
        again after a growth."""
        C = self.capacity
        if self.append_only:
            self._apply = jit_state(self._apply_fresh_impl,
                                    donate_argnums=(0, 1, 2, 3, 4),
                                    name="retract_top_n_apply")
            self._rank = jit_state(self._rank_impl,
                                   name="retract_top_n_rank")
            self._emit = jit_state(
                self._emit_impl, donate_argnums=(0, 1, 2),
                static_argnames=("width", "persist_width", "durable"),
                name="retract_top_n_emit")
        else:
            # the dense store pytree (khash, cols, valids, n) + errs is
            # threaded and aliased nowhere (the emitted top set is a fresh
            # gather): donate. _flush consumes/replaces the top_* triplet.
            self._apply = jit_state(
                partial(sorted_store_apply, pk_idx=self.pk_indices,
                        capacity=C),
                donate_argnums=(0, 1, 2, 3, 4), name="retract_top_n_apply")
            self._flush = jit_state(self._flush_impl,
                                    donate_argnums=(4, 5, 6, 7),
                                    name="retract_top_n_flush")
            self._narrow = jit_state(self._narrow_impl,
                                     static_argnames=("width",),
                                     name="retract_top_n_narrow")
        # ONE d2h fetch per barrier: errs and the live count ride together
        self._wd_pack = jit_state(
            lambda e, n, m: jnp.concatenate(
                [e, jnp.stack([n, m]).astype(jnp.int32)]),
            name="retract_top_n_wd_pack")

    # ------------------------------------------------------------ ranking
    def _sort_keys(self, cols) -> list:
        """The lexsort's keys within a group, least significant first: the
        stream key (ascending), then the order keys; DESC via bitwise
        complement (overflow-free order reversal on ints), negation on
        floats."""
        keys = [cols[p] for p in reversed(self._tie_cols)]
        for c, desc in reversed(self.order_specs):
            oval = cols[c]
            if jnp.issubdtype(oval.dtype, jnp.floating):
                keys.append(-oval if desc else oval)
            else:
                keys.append(~oval if desc else oval)
        return keys

    def _group_hash(self, cols) -> jnp.ndarray:
        """A hash of the group columns (0 where there is no group key):
        what a rank's runs are drawn on, and the first lane of an
        append-only store's order."""
        if not self.group_key_indices:
            return jnp.zeros(cols[0].shape[0], dtype=jnp.int64)
        return key_hash([cols[i] for i in self.group_key_indices])

    def _rank_rows(self, cols, live):
        """(order, rank, s_live): the permutation that sorts the live rows
        by (group, order key, stream key) with the dead ones behind, each
        sorted position's 0-based rank within its group run, and which
        sorted positions are live."""
        g = jnp.where(live, self._group_hash(cols), _HSENTINEL)
        order = stable_lexsort(tuple(self._sort_keys(cols) + [g]))
        _, run_start = segment_starts(g[order])
        pos = jnp.arange(live.shape[0], dtype=jnp.int32)
        return order, pos - run_start, live[order]

    # ------------------------------------------- retracting input: flush
    def _flush_impl(self, khash, cols, valids, n, top_hash, top_cols,
                    top_valids, top_n):
        """Compute the new top set, diff vs the last emitted one."""
        C = self.capacity
        live = jnp.arange(C, dtype=jnp.int32) < n
        order, rank, s_live = self._rank_rows(cols, live)
        in_top = s_live & (rank >= self.offset) & (
            rank < self.offset + self.limit)
        s_cols = [c[order] for c in cols]
        s_valids = [v[order] for v in valids]
        if self.emit_rank:
            s_cols.append(rank.astype(jnp.int64) + 1)
            s_valids.append(in_top)
        # full-row hash identifies a row (under its rank) across top sets
        rhash = key_hash(s_cols)
        topk = jnp.where(in_top, rhash, _HSENTINEL)
        torder = jnp.argsort(topk, stable=True)
        new_hash = topk[torder]
        n_top = jnp.sum(in_top.astype(jnp.int32))
        new_cols = tuple(c[torder] for c in s_cols)
        new_valids = tuple(v[torder] for v in s_valids)

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

    @staticmethod
    def _narrow_impl(out_cols, ops, vis, width: int):
        """The visible rows of the flush's `2 x capacity` layout, in their
        order (every Delete before every Insert), as a chunk `width`
        wide."""
        src, n = _nth_set(vis, width)
        return (tuple(Column(c.data[src], c.valid[src]) for c in out_cols),
                ops[src], jnp.arange(width, dtype=jnp.int32) < n)

    # ----------------------------------------- append-only input: programs
    def _key_lanes(self, ghash, cols) -> list:
        """The composite key an append-only store is ordered by, the most
        significant lane first: group hash, order keys, stream-key ties."""
        return [ghash] + self._sort_keys(cols)[::-1]

    def _place(self, khash, cols, n, q_keys) -> jnp.ndarray:
        """For every query key (N lanes-wise keys, as `_key_lanes`), the
        number of stored rows whose composite key is below it: ONE
        lexicographic binary search through the live prefix, the key
        lanes gathered at N positions a step."""
        C, N = self.capacity, q_keys[0].shape[0]
        key_cols = sorted({c for c, _ in self.order_specs}
                          | set(self._tie_cols))

        def step(_, bounds):
            lo, hi = bounds
            mid = (lo + hi) >> 1
            at = jnp.minimum(mid, C - 1)
            below = _lex_lt(
                self._key_lanes(khash[at], {c: cols[c][at]
                                            for c in key_cols}), q_keys)
            open_ = lo < hi
            return (jnp.where(open_ & below, mid + 1, lo),
                    jnp.where(open_ & ~below, mid, hi))

        lo, _ = jax.lax.fori_loop(
            0, C.bit_length(), step,
            (jnp.zeros(N, dtype=jnp.int32),
             jnp.full(N, n, dtype=jnp.int32)))
        return lo

    def _apply_fresh_impl(self, khash, cols, valids, n, errs,
                          chunk: StreamChunk):
        """Merge the chunk's rows into the store IN RANK ORDER: sort the
        chunk by the composite key (N wide), find each row's place by one
        search, move the lanes by the joins' merge given the two ranks.
        The hidden lane of a new row is `_FRESH`. No row of the chunk
        equals a stored one (the stream key is in the key). A retraction
        has no business here: it is counted as a delete that matched
        nothing and fail-stops the barrier."""
        N, C, nk = chunk.capacity, self.capacity, self._n_in + 1
        signs = op_sign(chunk.ops)
        is_ins = chunk.vis & (signs > 0)
        n_new = jnp.sum(is_ins.astype(jnp.int32))
        n_retract = jnp.sum((chunk.vis & (signs < 0)).astype(jnp.int32))
        data = [c.data.astype(dt) for c, dt in zip(chunk.columns,
                                                   self._col_dtypes)]
        ghash = jnp.where(is_ins, self._group_hash(data), _HSENTINEL)
        order = stable_lexsort(tuple(self._sort_keys(data) + [ghash]))
        s_ghash = ghash[order]
        s_data = [d[order] for d in data]
        idx = self._place(khash, cols, n, self._key_lanes(s_ghash, s_data))
        # stored rows are all kept (a dense prefix: nothing to compact) and
        # none equals a new one: the ranks `sorted_join._merge_ranks` takes
        # from its one search
        new_ok = jnp.arange(N, dtype=jnp.int32) < n_new
        new_lt = _rank_of_ascending(idx, new_ok.astype(jnp.int32), C)
        moved, n_after, n_row_overflow = _merge_sorted(
            jnp.arange(C, dtype=jnp.int32) < n, False, n_new,
            [khash, *cols, *valids], [_HSENTINEL] + [0] * nk + [False] * nk,
            [s_ghash, *s_data, jnp.full(N, _FRESH, dtype=jnp.int32)]
            + [c.valid_mask()[order] for c in chunk.columns[:self._n_in]]
            + [jnp.ones(N, dtype=bool)], ranks=(new_lt, idx))
        errs = errs + jnp.stack([n_row_overflow, n_retract]).astype(
            errs.dtype)
        return (moved[0], tuple(moved[1:1 + nk]), tuple(moved[1 + nk:]),
                n_after, errs)

    def _store_ranks(self, khash, n):
        """(rank, live): the store is in rank order, so a row's 0-based
        rank within its group is its distance from the start of its run
        of the group-hash lane."""
        pos = jnp.arange(self.capacity, dtype=jnp.int32)
        _, run_start = segment_starts(khash)
        return pos - run_start, pos < n

    def _changes(self, rank, live, erank):
        """Per stored row, what the barrier does with it: (ins, dele, upd)
        for the changelog, (p_ins, p_del) for the state table, `kept` for
        the store."""
        K = self.offset + self.limit
        kept = live & (rank < K)
        in_win = kept & (rank >= self.offset)
        was = live & (erank >= 0)
        fresh = live & (erank == _FRESH)
        ins = in_win & ~was
        dele = was & ~in_win
        upd = (in_win & was & (erank != rank) if self.emit_rank
               else jnp.zeros_like(ins))
        return ins, dele, upd, fresh & kept, live & ~fresh & ~kept, kept

    def _rank_impl(self, khash, erank, n, errs):
        """The barrier's counts, from the store as it stands: no sort, no
        gather, nothing handed to the emitting program. Returns pack =
        [row overflow, delete misses, live rows, rows the changelog takes,
        state-table inserts, deletes, rows kept, rows pruned]."""
        rank, live = self._store_ranks(khash, n)
        ins, dele, upd, p_ins, p_del, kept = self._changes(rank, live, erank)

        def count(m):
            return jnp.sum(m.astype(jnp.int32))

        return jnp.concatenate([errs, jnp.stack([
            n.astype(jnp.int32),
            count(ins) + count(dele) + 2 * count(upd),
            count(p_ins), count(p_del), count(kept),
            count(live & ~kept)])])

    def _emit_impl(self, khash, cols, valids, n, *,
                   width: int, persist_width: int, durable: bool):
        """The barrier's other half, at the widths the counts allow: the
        changelog chunk (`width` rows, in rank order), the state table's
        inserts and deletes (`persist_width` rows each; None where not
        `durable`), and the store compacted to the kept rows — still in
        rank order — with their ranks as the new baseline."""
        C = self.capacity
        data, dvalid = cols[:self._n_in], valids[:self._n_in]
        erank = cols[-1]
        rank, live = self._store_ranks(khash, n)
        ins, dele, upd, p_ins, p_del, kept = self._changes(rank, live, erank)

        # changelog: a changed row takes one slot, a rank shift two
        # adjacent ones (UpdateDelete under the old rank, UpdateInsert
        # under the new)
        slots = (ins.astype(jnp.int32) + dele.astype(jnp.int32)
                 + 2 * upd.astype(jnp.int32))
        cum = jnp.cumsum(slots)
        s = jnp.arange(width, dtype=jnp.int32)
        row = jnp.clip(jnp.searchsorted(cum, s, side="right"), 0,
                       C - 1).astype(jnp.int32)
        first = s == cum[row] - slots[row]
        is_upd, is_ins = upd[row], ins[row]
        ops = jnp.where(
            is_upd, jnp.where(first, OP_UPDATE_DELETE, OP_UPDATE_INSERT),
            jnp.where(is_ins, OP_INSERT, OP_DELETE)).astype(jnp.int8)
        vis = s < cum[-1]
        out_cols = [Column(c[row], v[row]) for c, v in zip(data, dvalid)]
        if self.emit_rank:
            old = (is_upd & first) | (~is_upd & ~is_ins)
            out_cols.append(Column(
                jnp.where(old, erank[row], rank[row]).astype(jnp.int64) + 1,
                vis))

        view = None
        if durable:
            i_row, _ = _nth_set(p_ins, persist_width)
            d_row, _ = _nth_set(p_del, persist_width)
            view = ([c[i_row] for c in data]
                    + [_valid_bits([v[i_row] for v in dvalid])],
                    [data[p][d_row] for p in self.pk_indices])

        # the store: rows past rank `offset + limit` go (nothing can
        # promote them), the rest carry their rank as the next baseline
        stamped = jnp.where(kept & (rank >= self.offset), rank,
                            _UNEMITTED).astype(jnp.int32)
        lanes = compact(
            kept, [khash, *data, stamped, *valids],
            [_HSENTINEL] + [0] * (self._n_in + 1)
            + [False] * (self._n_in + 1))
        nl = self._n_in + 1
        return (lanes[0], tuple(lanes[1:1 + nl]), tuple(lanes[1 + nl:]),
                jnp.sum(kept.astype(jnp.int32)), tuple(out_cols), ops, vis,
                view)

    # -------------------------------------------------------------- hooks
    def _apply_chunk(self, chunk: StreamChunk) -> None:
        (self.khash, self.cols, self.valids, self.n,
         self._errs_dev) = self._apply(self.khash, self.cols, self.valids,
                                       self.n, self._errs_dev, chunk)

    def on_chunk(self, chunk: StreamChunk) -> None:
        self._apply_chunk(chunk)
        if self.append_only:
            self._chunk_rows += chunk.capacity
        elif self.state_table is not None:
            self._epoch_chunks.append(chunk)
        return None

    def _width_for(self, count: int, width: int) -> int:
        """`width`, or the power of two over twice `count` where `count`
        no longer fits it; never past what a flush can emit at all."""
        if count > width:
            while width < 2 * count:
                width *= 2
        return min(width, 2 * self.capacity)

    def _run_flush(self, n_emit: Optional[int], n_persist: int = 0,
                   discard: bool = False) -> None:
        """Dispatch the barrier's emitting program(s) at the widths the
        counts ask for (`n_emit` None: no fetch brought them, the chunk is
        capacity-wide; `discard`: a recovery's baseline pass, whose output
        nobody reads, at the widths there are) and leave the chunk for
        `flush()`, the state table's rows for `persist()`."""
        durable = self.state_table is not None and not discard
        if self.append_only:
            C = self.capacity
            if discard:
                width, pwidth = self._emit_width, self._persist_width
            elif n_emit is None:
                width, pwidth = 2 * C, C
            else:
                width = self._emit_width = self._width_for(
                    n_emit, self._emit_width)
                pwidth = self._persist_width = min(C, self._width_for(
                    n_persist, self._persist_width))
            (self.khash, self.cols, self.valids, self.n, out_cols, ops,
             vis, view) = self._emit(
                self.khash, self.cols, self.valids, self.n,
                width=width, persist_width=pwidth, durable=durable)
            self._persist_view = view
        else:
            out_cols, ops, vis = self._flushed
            self._flushed = None
            if n_emit is not None:
                self._emit_width = self._width_for(n_emit,
                                                   self._emit_width)
                out_cols, ops, vis = self._narrow(
                    out_cols, ops, vis, width=self._emit_width)
        self._ready = StreamChunk(tuple(out_cols), ops, vis, self.schema)

    def _run_rank(self):
        """Dispatch the barrier's ranking program (an append-only store:
        ranks off the run boundaries, and the counts; else the capacity-wide
        sort, rank and diff); returns the device scalar(s) the watchdog's
        pack takes along."""
        if self.append_only:
            self._pack_dev = self._rank(self.khash, self.cols[-1], self.n,
                                        self._errs_dev)
            return self._pack_dev
        (self.top_hash, self.top_cols, self.top_valids, self.top_n,
         out_cols, ops, vis) = self._flush(
            self.khash, self.cols, self.valids, self.n,
            self.top_hash, self.top_cols, self.top_valids, self.top_n)
        self._flushed = (out_cols, ops, vis)
        return self._wd_pack(self._errs_dev, self.n,
                             jnp.sum(vis.astype(jnp.int32)))

    def flush(self) -> Optional[StreamChunk]:
        if self._ready is None:
            # no watchdog fetch at this barrier: rank and emit back to
            # back, at the full width
            self._run_rank()
            self._run_flush(None)
        out, self._ready = self._ready, None
        return out

    async def check_watchdog(self) -> None:
        """The barrier's ONE fetch: the error counters, the live count and,
        where the interval applied anything, what the flush is about to
        emit — so `topn.flush` spans the ranking program's dispatch, the
        awaited fetch and the emitting program's dispatch."""
        if not self._applied_since_flush:
            vals = await off_loop(fetch_small, self._wd_pack(
                self._errs_dev, self.n, jnp.int32(0)))
            self._fail_on(int(vals[0]), int(vals[1]))
            return
        with span("topn.flush"):
            vals = [int(v) for v in await off_loop(fetch_small,
                                                   self._run_rank())]
            self._fail_on(vals[0], vals[1])
            if self.append_only:
                n_live, n_emit, p_ins, p_del, n_kept, n_pruned = vals[2:]
                self._run_flush(n_emit, max(p_ins, p_del))
                self._persist_counts = (p_ins, p_del)
            else:
                n_live, n_emit = vals[2:]
                n_kept, n_pruned = n_live, 0
                self._run_flush(n_emit)
        self._note_counts(n_kept, n_emit, n_pruned)
        # the fullest the store was: what the next interval may ask for
        self._maybe_grow(n_live)

    def _fail_on(self, n_overflow: int, n_miss: int) -> None:
        if n_overflow:
            raise RuntimeError(
                f"retractable TopN overflow ({n_overflow} rows dropped; "
                f"capacity {self.capacity})")
        if n_miss:
            raise RuntimeError(
                f"retractable TopN: {n_miss} deletes matched no row"
                + (" (a retraction reached an append-only top-N)"
                   if self.append_only else ""))

    def _note_counts(self, n_live: int, n_emit: int, n_pruned: int) -> None:
        """What the barrier's fetch brought, into the registry and kept
        for the epoch trace: the rows the store holds after the barrier
        over its capacity, the rows the changelog took (inserts, deletes
        and both halves of update pairs), the rows dropped as beyond rank
        N, and the rows the interval sorted to keep the store ranked (an
        append-only store: its chunks'; else the capacity, at the flush)."""
        label = self.identity
        n_sorted = self._chunk_rows if self.append_only else self.capacity
        self._chunk_rows = 0
        GLOBAL_METRICS.counter(TOP_N_SORTED_ROWS, executor=label).inc(
            n_sorted)
        GLOBAL_METRICS.gauge(TOP_N_LIVE_ROWS, executor=label).set(
            float(n_live))
        GLOBAL_METRICS.counter(TOP_N_EMIT_ROWS, executor=label).inc(n_emit)
        GLOBAL_METRICS.counter(TOP_N_PRUNED_ROWS, executor=label).inc(
            n_pruned)
        self._phase_counts = dict(
            topn_live_rows=n_live, topn_capacity=self.capacity,
            topn_emit_rows=n_emit, topn_pruned_rows=n_pruned,
            topn_sorted_rows=n_sorted)

    def take_phase_counts(self) -> dict:
        """This barrier's `topn_live_rows` / `topn_capacity` /
        `topn_emit_rows` / `topn_pruned_rows` / `topn_sorted_rows` for the
        actor's phase dict:
        host numbers the watchdog fetch brought, absent where it made
        none."""
        counts, self._phase_counts = self._phase_counts, {}
        return counts

    # -------------------------------------------------------- durability
    async def persist(self, barrier: Barrier, flushed) -> None:
        """The state table's share of the barrier, columnar and off the
        loop: the packs are dispatched here, the checkpoint's uploader
        waits for them and writes. An append-only store writes the rows it
        kept of the interval's arrivals and deletes the ones it pruned; a
        full store writes the interval's input chunks as they came."""
        st = self.state_table
        if st is None:
            return
        n_cols = self._n_in
        counts_dev = None
        if self.append_only:
            view, self._persist_view = self._persist_view, None
            known, self._persist_counts = self._persist_counts, None
            if view is not None and known is None:
                # no watchdog fetch brought the counts: the rank program's
                # pack is awaited with the flush instead
                counts_dev = self._pack_dev
            self._pack_dev = None

            def plan(counts):
                if view is None:
                    groups = []
                else:
                    p_ins, p_del = known if counts is None else (
                        int(counts[4]), int(counts[5]))
                    groups = [(view[0], p_ins), (view[1], p_del)]
                return groups, write

            def write(outs):
                if outs:
                    ins, dels = outs
                    self._write_rows(
                        OP_INSERT, ins[:n_cols],
                        _valids_of_bits(ins[n_cols], n_cols))
                    cols = [np.zeros(len(dels[0]), dtype=np.int64)] * n_cols
                    for p, lane in zip(self.pk_indices, dels):
                        cols[p] = lane
                    self._write_rows(OP_DELETE, cols, None)
                st.commit(barrier.epoch.curr)
        else:
            chunks, self._epoch_chunks = self._epoch_chunks, []
            groups = [
                ([c.ops, c.vis] + [col.data for col in c.columns[:n_cols]]
                 + [_valid_bits([col.valid_mask()
                                 for col in c.columns[:n_cols]])],
                 c.capacity) for c in chunks]

            def write(outs):
                for host in outs:
                    st.write_chunk_columns(
                        host[0], host[2:2 + n_cols], host[1].astype(bool),
                        _valids_of_bits(host[2 + n_cols], n_cols))
                st.commit(barrier.epoch.curr)

            def plan(_counts):
                return groups, write

        await defer_prefix_flush(st.store, barrier.epoch.prev, st.table_id,
                                 counts_dev, plan)

    def _write_rows(self, op: int, cols, valids) -> None:
        n = len(cols[0])
        if n:
            self.state_table.write_chunk_columns(
                np.full(n, op, dtype=np.int8), cols, np.ones(n, dtype=bool),
                valids)

    def recover_state(self, epoch: int) -> None:
        rows = [r for _, r in self.state_table.iter_all()]
        if not rows:
            return
        self._presize_for(len(rows))
        from ..state.storage_table import rows_to_columns
        # every batch at ONE capacity, the last short one too: one apply
        # program whatever the row count
        cap = min(1 << 14, self.capacity)
        for ofs in range(0, len(rows), cap):
            arrays, valids = rows_to_columns(self.store_schema,
                                             rows[ofs:ofs + cap])
            self._apply_chunk(StreamChunk.from_numpy(
                self.store_schema, arrays, capacity=cap,
                valids=[None if v.all() else v for v in valids]))
        # Seed the diff BASELINE: the downstream MV materialized exactly
        # the top set of this recovered (checkpoint-consistent) store, so
        # compute it once and DISCARD the output — the next real flush
        # then emits only genuine changes. Without this, rows that left
        # the top set across the rebuild would never receive a Delete
        # (re-emitting inserts is idempotent; omitted deletes are not).
        # An append-only store held only kept rows: the pass prunes
        # nothing and stamps every row with the rank the MV has it under.
        self._run_rank()
        self._run_flush(None, discard=True)
        self._ready = self._persist_view = self._pack_dev = None

    # ------------------------------------------------------------- growth
    _SECONDARY = ("top_hash", "top_cols", "top_valids")

    def state_bytes(self) -> int:
        if not self.append_only:
            return super().state_bytes()
        from ..memory.accounting import pytree_bytes
        return pytree_bytes((self.khash, self.cols, self.valids))

    def _grow_to(self, new_c: int) -> None:
        self.khash, self.cols, self.valids = grow_sorted_arrays(
            self.khash, self.cols, self.valids, new_c)
        if not self.append_only:
            self.top_hash, self.top_cols, self.top_valids = \
                grow_sorted_arrays(self.top_hash, self.top_cols,
                                   self.top_valids, new_c)
        self.capacity = new_c
        self._build_programs()

    def fence_tokens(self) -> list:
        own = [self.n] if self.append_only else [self.n, self.top_n]
        return own + super().fence_tokens()

    def map_watermark(self, wm: Watermark) -> Optional[Watermark]:
        return None          # ranks can change; no watermark survives
