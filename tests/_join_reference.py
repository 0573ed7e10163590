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
