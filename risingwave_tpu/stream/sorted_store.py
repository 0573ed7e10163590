"""Dense sorted row store — the shared state layout for the executors that
rank or frame rows they hold: general OverWindow and a top-N over a
RETRACTING input hold their FULL input here; a top-N over an append-only
input (retract_top_n.py) holds only the rows that can still rank, pruned at
every barrier, with one hidden lane for the rank each was last emitted
under.

Rows live in a dense prefix [0, n) of fixed-capacity arrays. Where rows
can be retracted (`sorted_store_apply`: OverWindow, a retracting top-N,
the mesh top-N) the prefix is sorted by a 63-bit hash of the STREAM KEY
(retractions address rows by it), maintained with the same
searchsorted/merge machinery as sorted_join.py's own-side update: per
chunk, one jitted program nets within-chunk pk runs, finds delete victims
by (hash, pk) match, and merge-inserts the survivors through the join's
own `_merge_sorted` (kept rows and new rows move by log-step shifts,
`ops/monotone_move.py`: no index per stored row) — static shapes, no
data-dependent control flow. An append-only top-N never looks a row up:
it keeps the same lanes in RANK order instead (group hash first; its own
apply in retract_top_n.py, the same merge given its ranks) and reads
ranks off `segment_starts`.

Reference analogue: the row-holding state tables behind
top_n_state.rs / over_window's partition cache — re-designed dense for
the TPU instead of per-key BTree ranges.
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from ..common.chunk import StreamChunk, op_sign
from ..ops.hash_table import stable_lexsort
from .sorted_join import _HSENTINEL, _merge_sorted, _range_owner, key_hash


def sorted_store_apply(khash, cols, valids, n, errs, chunk: StreamChunk,
                       pk_idx: tuple, capacity: int):
    """Insert/retract chunk rows into the sorted dense store. Returns
    (khash', cols', valids', n', errs' + [row_overflow, del_miss])."""
    N = chunk.capacity
    C = capacity
    active = chunk.vis
    signs = op_sign(chunk.ops)
    row_ids = jnp.arange(N, dtype=jnp.int32)
    h = key_hash([chunk.columns[i].data for i in pk_idx])

    # within-chunk pk-run netting (sorted_join semantics)
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
    is_del = jnp.zeros(N, dtype=bool).at[order].set(
        run_start & (s_signs < 0) & s_act)
    is_ins = jnp.zeros(N, dtype=bool).at[order].set(
        run_end & (s_signs > 0) & s_act)

    live = jnp.arange(C, dtype=jnp.int32) < n
    keep = live
    # deletes: exact (hash, pk) match
    dlo = jnp.searchsorted(khash, h, side="left").astype(jnp.int32)
    dhi = jnp.searchsorted(khash, h, side="right").astype(jnp.int32)
    M = 2 * N
    dlens = jnp.where(is_del, (dhi - dlo).astype(jnp.int64), 0)
    doffs = jnp.cumsum(dlens)
    dtot = doffs[N - 1]
    j = jnp.arange(M, dtype=jnp.int64)
    dsrc = _range_owner(doffs, M)
    dsrcc = jnp.clip(dsrc, 0, N - 1)
    dprev = jnp.where(dsrcc > 0, doffs[jnp.clip(dsrcc - 1, 0)], 0)
    dpos = jnp.clip(dlo[dsrcc] + (j - dprev), 0, C - 1).astype(jnp.int32)
    cand = (j < jnp.minimum(dtot, M)) & keep[dpos]
    for p in pk_idx:
        cand &= (cols[p][dpos]
                 == chunk.columns[p].data[dsrcc].astype(cols[p].dtype))
    victim = jnp.full(N, C, dtype=jnp.int32).at[
        jnp.where(cand, dsrcc, N)].min(dpos, mode="drop")
    found = victim < C
    keep = keep.at[jnp.where(found, victim, C)].set(False, mode="drop")
    n_del_miss = jnp.sum((is_del & ~found).astype(jnp.int32))

    # merge inserts (stable, state rows before equal-hash new rows)
    ins_h = jnp.where(is_ins, h, _HSENTINEL)
    iorder = jnp.argsort(ins_h, stable=True)
    nk = len(cols)
    moved, n_after, n_row_overflow = _merge_sorted(
        keep, True, jnp.sum(is_ins.astype(jnp.int32)),
        [khash, *cols, *valids], [_HSENTINEL] + [0] * nk + [False] * nk,
        [ins_h[iorder]] + [c.data[iorder] for c in chunk.columns[:nk]]
        + [c.valid_mask()[iorder] for c in chunk.columns[:nk]])
    errs = errs + jnp.stack([n_row_overflow, n_del_miss]).astype(jnp.int32)
    return (moved[0], tuple(moved[1:1 + nk]), tuple(moved[1 + nk:]), n_after,
            errs)


def segment_starts(sorted_group_ids: jnp.ndarray):
    """For an array sorted by group id: (new_run mask, run_start positions
    broadcast per element) — the standard segmented-scan primitives."""
    import jax
    C = sorted_group_ids.shape[0]
    new_run = jnp.concatenate([jnp.array([True]),
                               sorted_group_ids[1:] != sorted_group_ids[:-1]])
    pos = jnp.arange(C, dtype=jnp.int32)
    run_start = jax.lax.cummax(jnp.where(new_run, pos, 0))
    return new_run, run_start


class GrowableSortedStore:
    """Mixin for executors holding the dense sorted store plus one
    same-capacity secondary (the last-emitted set): doubles both at 0.7
    occupancy instead of fail-stopping, and pre-sizes before a recovery
    replay so state that grew past the constructor capacity recovers.
    Subclasses set _SECONDARY to the (hash, cols, valids) attr names (the
    top-N overrides `_grow_to`: its append-only form has no secondary)."""

    _SECONDARY: tuple = ()

    def state_bytes(self) -> int:
        """Exact accounted HBM bytes of the primary + secondary stores
        (memory/accounting.py) — registers the executor with the memory
        manager for per-flow accounting. Eviction for the dense sorted
        layout is a ROADMAP open item; growth is the pressure response."""
        from ..memory.accounting import pytree_bytes
        h, c, v = self._SECONDARY
        return pytree_bytes((self.khash, self.cols, self.valids,
                             getattr(self, h), getattr(self, c),
                             getattr(self, v)))

    def _grow_to(self, new_c: int) -> None:
        from functools import partial
        from ..ops.jit_state import jit_state
        from .sorted_join import grow_sorted_arrays
        self.khash, self.cols, self.valids = grow_sorted_arrays(
            self.khash, self.cols, self.valids, new_c)
        h, c, v = self._SECONDARY
        kh2, c2, v2 = grow_sorted_arrays(
            getattr(self, h), getattr(self, c), getattr(self, v), new_c)
        setattr(self, h, kh2)
        setattr(self, c, c2)
        setattr(self, v, v2)
        self.capacity = new_c
        # same donation contract as the constructor-time _apply: the
        # primary store pytree is threaded, the secondary never aliases it
        self._apply = jit_state(
            partial(sorted_store_apply, pk_idx=self.pk_indices,
                    capacity=new_c),
            donate_argnums=(0, 1, 2, 3, 4),
            name=f"{type(self).__name__}_apply")

    def _maybe_grow(self, n_live: int) -> None:
        if n_live > 0.7 * self.capacity:
            self._grow_to(self.capacity * 2)

    def _presize_for(self, n_rows: int) -> None:
        """Before a recovery replay: make room for every persisted row
        (the store may have grown past the constructor capacity before
        the crash)."""
        c = self.capacity
        while n_rows > 0.7 * c:
            c *= 2
        if c != self.capacity:
            self._grow_to(c)
