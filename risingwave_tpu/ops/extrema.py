"""Retractable MIN/MAX state — materialized-input top-K value buffers.

Reference: src/stream/src/executor/aggregation/minput.rs — retractable
extrema keep the input values materialized in a state table with a cached
top-N window; deleting the current extremum refills from the next cached
value (or the state table on cache miss).

TPU re-design: per group, a dense buffer of the K best DISTINCT values
with multiplicities, entirely in HBM:

    vals [C, K]   sorted best-first (desc for max, asc for min)
    cnts [C, K]   multiplicity per value (0 = empty cell)
    lossy [C]     True once any insert was dropped past the K-th value —
                  from then on deletes of untracked values are legal

One jitted update per chunk: net (group, value) deltas by run-reduction,
top-K chunk candidates per group, then a per-group 3K merge (sort + adjacent
equal-value combine) over the groups the chunk touches. Inconsistencies
(a delete that matches no tracked value while the buffer is NOT lossy, or
a buffer that empties while rows remain and history was lossy) are counted
on device and fail-stopped by the executor watchdog before the checkpoint
commits; the reference instead refills from its state table, which is the
durable follow-up for this design (buffer persists with the lossy flag).

What a lossy buffer still guarantees: every live value at least as good as
its WORST tracked value is tracked, with its exact multiplicity. A lossy
group therefore admits no insert worse than its worst tracked value even
where the buffer has room (the reference's TopNStateCache skips an insert
past the last cached key while the cache is not the whole table): an
untracked live value may lie between the two, and admitting the insert
would let a later delete of the better values answer with it. Such a group
drains sooner and fail-stops (`extrema_underflow`) where it would have
answered wrongly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .hash_table import stable_lexsort, stable_lexsort_rows


def _order_key(vals, is_max):
    if not is_max:
        return vals
    # ints: bitwise-not is a monotone-decreasing map with no overflow at
    # the dtype extremes (unary minus overflows at iinfo.min);
    # floats: negation is safe (-inf is fine)
    if jnp.issubdtype(vals.dtype, jnp.floating):
        return -vals
    return jnp.invert(vals)


def extrema_empty(C: int, K: int, dtype) -> tuple:
    return (jnp.zeros((C, K), dtype=dtype),
            jnp.zeros((C, K), dtype=jnp.int32),
            jnp.zeros(C, dtype=bool))


def extrema_update(state: tuple, values, valid_in, signs, seg, C: int,
                   is_max: bool):
    """Apply one chunk's rows to the buffers.

    values: [N] input column; valid_in: [N] non-null mask; signs: [N] in
    {-1, 0, +1}; seg: [N] group slot (C = trash). Returns
    (state', errs int32 [2]): the two fail-stop counts of the bound,
    (deletes of more than K distinct values of one group in this chunk,
    deletes of an untracked value of a group that is not lossy).

    The merge runs over the groups the chunk TOUCHES, at most M = min(N, C)
    of them: their buffers are gathered into `[M, K]`, merged with the
    chunk's candidates, and scattered back. Its cost follows the chunk, not
    the table: a 2^20-slot table whose chunk touches ten groups sorts ten
    rows of 3K lanes, not 2^20."""
    vals, cnts, lossy = state
    K = vals.shape[1]
    N = values.shape[0]
    M = min(N, C)
    act = (signs != 0) & valid_in & (seg < C)
    sgs = jnp.where(act, signs, 0)
    sseg = jnp.where(act, seg, C)

    # ---- net delta per (group, value) run ----
    okey = _order_key(values, is_max)
    order = stable_lexsort((okey, sseg))
    o_seg = sseg[order]
    o_val = values[order]
    o_key = okey[order]
    o_sign = sgs[order]
    live = o_seg < C
    seg_leader = jnp.concatenate([jnp.array([True]),
                                  o_seg[1:] != o_seg[:-1]])
    leader = seg_leader | jnp.concatenate([jnp.array([False]),
                                           o_val[1:] != o_val[:-1]])
    run_id = jnp.cumsum(leader.astype(jnp.int32)) - 1
    run_delta_all = jax.ops.segment_sum(o_sign, run_id, N)
    run_delta = run_delta_all[run_id]           # per sorted row

    # ---- the touched groups, densely numbered in slot order ----
    # (the trash segment sorts last; where it is the (M+1)-th group its
    # number M falls off every [M]-sized target below)
    gid = jnp.cumsum(seg_leader.astype(jnp.int32)) - 1
    g = jnp.minimum(gid, M - 1)
    g_slot = jnp.full(M, C, dtype=jnp.int32).at[
        jnp.where(seg_leader & live, gid, M)].set(
        o_seg.astype(jnp.int32), mode="drop")
    touched = g_slot < C
    g_read = jnp.minimum(g_slot, C - 1)
    t_vals = vals[g_read]
    t_cnts = jnp.where(touched[:, None], cnts[g_read], 0)
    t_lossy = lossy[g_read] & touched

    # per-SIGN candidate ranks (zero-delta runs consume no slots):
    # positives and negatives each get K candidate slots per group. Keeping
    # the best-K inserts is sound (a dropped insert cannot belong to the
    # merged top-K this chunk; if it matters later the group is lossy and
    # underflow fail-stops). Deletes target TRACKED values (<= K distinct
    # per group), so K delete slots suffice unless one chunk deletes more
    # than K distinct values of one group — that residue cannot be applied
    # to a bounded buffer soundly, so it always fail-stops.
    pos = jnp.arange(N, dtype=jnp.int32)
    seg_start = jax.lax.cummax(jnp.where(seg_leader, pos, 0))

    def rank_among(mask):
        """Rank of each masked leader within its group, in value order."""
        m = leader & mask & live
        cnt = jnp.cumsum(m.astype(jnp.int32))
        return (cnt - 1) - (cnt[seg_start] - m[seg_start])

    # a lossy group admits nothing worse than its worst tracked value
    # (module docstring); an empty lossy buffer admits nothing at all and
    # fail-stops as an underflow
    n_tracked = jnp.sum((t_cnts > 0).astype(jnp.int32), axis=1)
    worst = jnp.take_along_axis(
        t_vals, jnp.maximum(n_tracked - 1, 0)[:, None], axis=1)[:, 0]
    worse = o_key > _order_key(worst, is_max)[g]
    shut = t_lossy[g] & (worse | (n_tracked[g] == 0))
    is_pos = (run_delta > 0) & ~shut
    is_neg = run_delta < 0
    rank_pos = rank_among(is_pos)
    rank_neg = rank_among(is_neg)

    keep_pos = leader & live & is_pos & (rank_pos < K)
    drop_pos = leader & live & is_pos & (rank_pos >= K)
    keep_neg = leader & live & is_neg & (rank_neg < K)
    drop_neg = leader & live & is_neg & (rank_neg >= K)
    lossy2 = t_lossy.at[jnp.where(drop_pos, gid, M)].set(True, mode="drop")
    err_dropped_del = jnp.sum(drop_neg.astype(jnp.int32))

    def scatter_cand(keep, rank):
        tgt_row = jnp.where(keep, gid, M)
        tgt_col = jnp.where(keep, jnp.minimum(rank, K - 1), 0)
        cv = jnp.zeros((M + 1, K), dtype=vals.dtype)
        cv = cv.at[tgt_row, tgt_col].set(o_val, mode="drop")
        cc = jnp.zeros((M + 1, K), dtype=jnp.int32)
        cc = cc.at[tgt_row, tgt_col].set(run_delta, mode="drop")
        return cv[:M], cc[:M]

    cand_vals_p, cand_cnts_p = scatter_cand(keep_pos, rank_pos)
    cand_vals_n, cand_cnts_n = scatter_cand(keep_neg, rank_neg)

    # ---- per-group 3K merge (K state + K insert-cands + K delete-cands)
    m_vals = jnp.concatenate([t_vals, cand_vals_p, cand_vals_n], axis=1)
    m_cnts = jnp.concatenate([t_cnts, cand_cnts_p, cand_cnts_n], axis=1)
    m_valid = m_cnts != 0
    sort_idx = stable_lexsort_rows((_order_key(m_vals, is_max), ~m_valid))
    s_vals = jnp.take_along_axis(m_vals, sort_idx, axis=1)
    s_cnts = jnp.take_along_axis(m_cnts, sort_idx, axis=1)
    s_valid = jnp.take_along_axis(m_valid, sort_idx, axis=1)
    # adjacent equal-value combine (state values and cand values are each
    # distinct, so at most one duplicate pair per value)
    dup = (s_valid[:, 1:] & s_valid[:, :-1]
           & (s_vals[:, 1:] == s_vals[:, :-1]))
    add = jnp.where(dup, s_cnts[:, 1:], 0)
    s_cnts = s_cnts.at[:, :-1].add(add)
    s_valid = s_valid.at[:, 1:].set(jnp.where(dup, False, s_valid[:, 1:]))
    # negative residue = delete of an untracked value
    neg = s_valid & (s_cnts < 0)
    err_neg = jnp.sum((neg & ~lossy2[:, None]).astype(jnp.int32))
    s_valid = s_valid & (s_cnts > 0)
    # resort (combined zeros / negatives drop out), keep best K
    sort2 = stable_lexsort_rows((_order_key(s_vals, is_max), ~s_valid))
    f_vals = jnp.take_along_axis(s_vals, sort2, axis=1)
    f_cnts = jnp.take_along_axis(s_cnts, sort2, axis=1)
    f_valid = jnp.take_along_axis(s_valid, sort2, axis=1)
    lossy3 = lossy2 | jnp.any(f_valid[:, K:], axis=1)
    out_vals = jnp.where(f_valid[:, :K], f_vals[:, :K], 0)
    out_cnts = jnp.where(f_valid[:, :K], f_cnts[:, :K], 0)
    errs = jnp.stack([err_dropped_del, err_neg]).astype(jnp.int32)
    # untouched rows of the [M] view carry slot C and fall off
    return (vals.at[g_slot].set(out_vals, mode="drop"),
            cnts.at[g_slot].set(out_cnts, mode="drop"),
            lossy.at[g_slot].set(lossy3, mode="drop")), errs


def extrema_emit(state: tuple, init, dtype):
    """Best value per group (identity where the buffer is empty)."""
    vals, cnts, _ = state
    has = cnts[:, 0] > 0
    return jnp.where(has, vals[:, 0], jnp.asarray(init, dtype=dtype))


def extrema_underflow(state: tuple, row_count) -> jnp.ndarray:
    """Groups with live rows, an empty buffer, and lossy history — the
    extremum is unknowable without a durable refill: fail-stop count."""
    vals, cnts, lossy = state
    empty = cnts[:, 0] <= 0
    return jnp.sum((empty & lossy & (row_count > 0)).astype(jnp.int32))


def extrema_lossy_groups(state: tuple, row_count) -> jnp.ndarray:
    """Live groups whose answer rests on the buffer not draining."""
    return jnp.sum((state[2] & (row_count > 0)).astype(jnp.int32))


def extrema_mask_keep(state: tuple, keep) -> tuple:
    """Watermark eviction: zero the buffers of evicted groups."""
    vals, cnts, lossy = state
    return (jnp.where(keep[:, None], vals, 0),
            jnp.where(keep[:, None], cnts, 0),
            lossy & keep)
