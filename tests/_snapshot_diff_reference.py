"""TEST-ONLY reference for the sorted join's durable diff.

The sort-and-search snapshot diff that `SortedJoinExecutor._diff_impl`
was until PR 27 (both states' rows hashed, both hash arrays sorted, each
side searched in the other, candidates compared lane by lane), kept as
the independent statement of "which rows changed between two states": it
looks only at row CONTENT and knows nothing of the provenance lane the
package's diff reads. `check_diffs_against_reference` puts it beside
every diff an executor makes.
"""

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.floatbits import float_identity_bits
from risingwave_tpu.stream.sorted_join import _HSENTINEL, key_hash


def row_lanes(st) -> list:
    """Row identity/content lanes: khash ++ data (invalid lanes canonical
    0, floats as their identity bits) ++ valid bits."""
    lanes = [st.khash]
    for c, v in zip(st.cols, st.valids):
        x = (float_identity_bits(c)
             if jnp.issubdtype(c.dtype, jnp.floating)
             else c.astype(jnp.int64))
        lanes.append(jnp.where(v, x, 0))
    lanes.extend(v.astype(jnp.int64) for v in st.valids)
    return lanes


@jax.jit
def snapshot_diff(cur, snap):
    """Rows in `cur` not in `snap` (inserts) and rows in `snap` not in
    `cur` (deletes), matched by row hash + exact compare. Returns
    compacted (del_cols, n_del, ins_cols, n_ins)."""
    def rowhash(st):
        lanes = row_lanes(st)
        live = jnp.arange(st.capacity, dtype=jnp.int32) < st.n
        return jnp.where(live, key_hash(lanes), _HSENTINEL), live

    rh_c, live_c = rowhash(cur)
    rh_s, live_s = rowhash(snap)
    order_c = jnp.argsort(rh_c)
    order_s = jnp.argsort(rh_s)
    lanes_c = row_lanes(cur)
    lanes_s = row_lanes(snap)

    def unmatched(rh_a, live_a, lanes_a, rh_b_sorted, order_b, lanes_b,
                  cap_b):
        pos = jnp.clip(jnp.searchsorted(rh_b_sorted, rh_a), 0, cap_b - 1)
        cand = order_b[pos]
        eq = rh_b_sorted[pos] == rh_a
        for la, lb in zip(lanes_a, lanes_b):
            eq &= la == lb[cand]
        return live_a & ~eq

    ins_mask = unmatched(rh_c, live_c, lanes_c, rh_s[order_s], order_s,
                         lanes_s, snap.capacity)
    del_mask = unmatched(rh_s, live_s, lanes_s, rh_c[order_c], order_c,
                         lanes_c, cur.capacity)

    def compact(mask, cols):
        cap = mask.shape[0]
        rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
        sel = jnp.zeros(cap, dtype=jnp.int32).at[
            jnp.where(mask, rank, cap)].set(
            jnp.arange(cap, dtype=jnp.int32), mode="drop")
        return tuple(c[sel] for c in cols), jnp.sum(mask.astype(jnp.int32))

    del_cols, n_del = compact(del_mask, snap.cols)
    ins_cols, n_ins = compact(ins_mask, cur.cols)
    return del_cols, n_del, ins_cols, n_ins


def net_changes(diff) -> tuple[Counter, Counter]:
    """(deletes, inserts) of one diff as multisets of row tuples, with
    identical delete + insert pairs cancelled: the lane writes a row that
    left and came back unchanged within one interval as such a pair, the
    content diff writes nothing for it, and the table ends the same."""
    del_cols, n_del, ins_cols, n_ins = diff

    def rows(cols, n):
        host = [np.asarray(c)[:int(n)] for c in cols]
        return Counter(zip(*(h.tolist() for h in host)))

    dels, inss = rows(del_cols, n_del), rows(ins_cols, n_ins)
    both = dels & inss
    return dels - both, inss - both


def check_diffs_against_reference(join, rows_return=False) -> list:
    """From here on, every diff `join` makes (each side, each shard slice)
    is also made by the reference and asserted equal: as multisets, and,
    unless the traffic lets a row leave and return unchanged within one
    interval (`rows_return`), in its two counts too, so a lane that
    rewrites rows that did not change is caught. Returns the list that
    collects each diff's (n_del, n_ins) as the executor counted them."""
    real = join._diff
    seen: list = []

    def diff(cur, snap):
        out = real(cur, snap)
        ref = snapshot_diff(cur, snap)
        assert net_changes(out) == net_changes(ref)
        seen.append((int(out[1]), int(out[3])))
        assert rows_return or seen[-1] == (int(ref[1]), int(ref[3]))
        return out

    join._diff = diff
    return seen
