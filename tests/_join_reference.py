"""TEST-ONLY reference for the streaming join: a nested-loop multiset join.

Independent of every executor: two lists of live rows, every pair compared
with Python's `==` on the key columns. A key that is None (SQL NULL)
matches nothing, and neither does a NaN (it is not `==` to itself);
`-0.0 == 0.0` holds, so the two zeros join. Rows are plain tuples; a side
is a multiset (a list), so duplicate rows count.
"""

from collections import Counter


def keys_match(lrow, rrow, lkeys, rkeys) -> bool:
    return all(lrow[i] is not None and rrow[j] is not None
               and lrow[i] == rrow[j] for i, j in zip(lkeys, rkeys))


def join_rows(left, right, lkeys=(0,), rkeys=(0,), join_type="inner",
              condition=None) -> Counter:
    """The join of two row multisets as a Counter of output rows
    `lrow + rrow`; an outer side's unmatched row is padded with None.
    `condition(lrow, rrow)` is the non-equi part of ON."""
    n_l = len(left[0]) if left else 0
    n_r = len(right[0]) if right else 0
    out = Counter()
    r_matched = [False] * len(right)
    for lrow in left:
        hit = False
        for ri, rrow in enumerate(right):
            if keys_match(lrow, rrow, lkeys, rkeys) and (
                    condition is None or condition(lrow, rrow)):
                out[tuple(lrow) + tuple(rrow)] += 1
                r_matched[ri] = hit = True
        if not hit and join_type in ("left", "full"):
            out[tuple(lrow) + (None,) * n_r] += 1
    if join_type in ("right", "full"):
        for rrow, m in zip(right, r_matched):
            if not m:
                out[(None,) * n_l + tuple(rrow)] += 1
    return out


class InnerJoinReference:
    """Both sides' live rows, fed the chunks an executor is fed.

    `apply(side, rows)` takes one chunk as `(sign, row)` pairs in chunk
    order and returns what an inner join emits for it: every row joins the
    OTHER side as it stood before the chunk, signed like the row. The own
    side is then updated row by row (a delete removes one equal row)."""

    def __init__(self, lkeys=(0,), rkeys=(0,)):
        self.keys = (tuple(lkeys), tuple(rkeys))
        self.live = ([], [])

    def apply(self, side: int, rows) -> Counter:
        other = self.live[1 - side]
        out = Counter()
        for sign, row in rows:
            for orow in other:
                pair = (row, orow) if side == 0 else (orow, row)
                if keys_match(*pair, *self.keys):
                    out[(sign, tuple(pair[0]) + tuple(pair[1]))] += 1
            if sign > 0:
                self.live[side].append(tuple(row))
            else:
                self.live[side].remove(tuple(row))
        return out

    def joined(self) -> Counter:
        return join_rows(self.live[0], self.live[1], *self.keys)


# --------------------------------------------------------------------------
# The scatter form of the sorted pool's moves: what `ops/monotone_move.py`
# and `sorted_join._merge_sorted` replaced (PR 35), kept as their reference.
# Every row gets an index (`out.at[tgt].set(x, mode="drop")`); the ranks of
# the merge are the two binary searches, in numpy.

def compact_by_scatter(keep, lanes, fills):
    import jax.numpy as jnp
    C = keep.shape[0]
    tgt = jnp.where(keep, jnp.cumsum(keep.astype(jnp.int32)) - 1, C)
    return [jnp.full(C, f, dtype=x.dtype).at[tgt].set(x, mode="drop")
            for x, f in zip(lanes, fills)]


def expand_by_scatter(occupied, amount, lanes, fills):
    """(lanes', occupied'): entry t to t + amount[t], past the end dropped."""
    import jax.numpy as jnp
    C = occupied.shape[0]
    tgt = jnp.where(occupied, jnp.arange(C, dtype=jnp.int32) + amount, C)
    moved = [jnp.full(C, f, dtype=x.dtype).at[tgt].set(x, mode="drop")
             for x, f in zip(lanes, fills)]
    return moved, jnp.zeros(C, dtype=bool).at[tgt].set(True, mode="drop")


def merge_by_scatter(khash, keep, nh, n_new, lanes, fills, new_lanes):
    """`_merge_sorted`'s contract, the way `_apply_core` did it before:
    kept pool row t to kept_rank[t] + #{new hashes < khash[t]}, new row r
    to r + #{kept pool hashes <= nh[r]}, one scatter a lane for each.
    Returns (khash', lanes', n', rows dropped past the capacity)."""
    import jax.numpy as jnp
    import numpy as np
    khash, keep, nh = np.asarray(khash), np.asarray(keep), np.asarray(nh)
    C, N = len(khash), len(nh)
    n_new = int(n_new)
    new_lt = np.searchsorted(nh[:n_new], khash, side="left")
    kept_le = np.searchsorted(khash[keep], nh, side="right")
    pos_t = np.cumsum(keep) - 1 + new_lt
    pos_r = np.arange(N) + kept_le
    tgt_t = jnp.asarray(np.where(keep & (pos_t < C), pos_t, C))
    tgt_r = jnp.asarray(np.where((np.arange(N) < n_new) & (pos_r < C),
                                 pos_r, C))
    sentinel = np.iinfo(np.int64).max
    out = []
    for x, f, nx in zip([jnp.asarray(khash), *lanes], [sentinel, *fills],
                        [jnp.asarray(nh), *new_lanes]):
        y = jnp.full(C, f, dtype=x.dtype).at[tgt_t].set(x, mode="drop")
        if nx is not None:
            y = y.at[tgt_r].set(nx.astype(x.dtype), mode="drop")
        out.append(y)
    n_after = int(keep.sum()) + n_new
    return out[0], out[1:], min(n_after, C), max(n_after - C, 0)

