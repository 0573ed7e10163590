"""`ops/monotone_move.py` (compact / expand by log-step shifts) and the
sorted pool's moves built on it (`_merge_sorted`, the join's evict)
against the scatter form they replaced, kept in
tests/_join_reference.py: every output array equal, the padding behind
the live prefix included."""

import numpy as np
import pytest

import jax.numpy as jnp

from _join_reference import (compact_by_scatter, expand_by_scatter,
                             merge_by_scatter)
from risingwave_tpu.common import DataType, schema
from risingwave_tpu.ops.monotone_move import compact, expand
from risingwave_tpu.stream.sorted_join import (
    SortedJoinExecutor, SortedSideState, _merge_sorted)

_SENT = np.iinfo(np.int64).max

# a lane of each dtype the pools hold, with the padding its scatter had:
# a column, `degree`, `src`, a validity lane, a FLOAT32 column
LANE_KINDS = [(np.int64, 0), (np.int32, 0), (np.int32, -1), (np.bool_, False),
              (np.float32, 0.0)]


def _lanes(rng, C):
    lanes = []
    for dt, _ in LANE_KINDS:
        if dt is np.bool_:
            lanes.append(rng.random(C) < 0.5)
        elif dt is np.float32:
            lanes.append(rng.random(C).astype(dt) + 1)
        else:
            lanes.append(rng.integers(1, 1 << 30, C).astype(dt))
    return [jnp.asarray(x) for x in lanes], [f for _, f in LANE_KINDS]


def _assert_lanes_equal(got, want):
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype and g.shape == w.shape, i
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), str(i))


def _mask(rng, C, name):
    live = np.arange(C) < {"empty": 0, "full": C}.get(name, (5 * C) // 8)
    if name in ("every_row_dead", "empty"):
        return np.zeros(C, dtype=bool)
    if name in ("no_row_dead", "full"):
        return live
    if name == "one_survivor_at_the_end":
        return np.arange(C) == C - 1
    if name == "alternating":
        return live & (np.arange(C) % 2 == 1)
    return live & (rng.random(C) < 0.6)


@pytest.mark.parametrize("C", [1, 64, 100], ids=lambda c: f"C{c}")
@pytest.mark.parametrize("name", [
    "random", "empty", "full", "every_row_dead", "no_row_dead",
    "one_survivor_at_the_end", "alternating"])
def test_compact_equals_the_scatter(name, C):
    rng = np.random.default_rng(sum(map(ord, name)) + C)
    keep = jnp.asarray(_mask(rng, C, name))
    lanes, fills = _lanes(rng, C)
    _assert_lanes_equal(compact(keep, lanes, fills),
                        compact_by_scatter(keep, lanes, fills))


def _amounts(rng, C, name):
    """(occupied [C], amount [C] nondecreasing over the occupied, bound)."""
    occupied = _mask(rng, C, "random")
    bound = 16
    if name == "at_rest":
        bound = 0
    elif name == "bound_past_the_capacity":
        bound = 4 * C
    elif name == "prefix":
        occupied = np.arange(C) < C // 2
    elif name == "nobody":
        occupied[:] = False
    amount = np.sort(rng.integers(0, bound + 1, C))
    if name == "all_by_the_bound":
        amount[:] = bound
    elif name == "pushed_past_the_end":
        occupied[-3:] = True            # the last ones leave the lane
        amount[-5:] = bound
    # gaps between occupied entries must hold what moves through them:
    # spread the entries so that no two land on one position
    pos = np.flatnonzero(occupied)
    ok = np.ones(len(pos), dtype=bool)
    last = -1
    for i, p in enumerate(pos):
        if p + amount[p] <= last:
            ok[i] = False
        else:
            last = p + amount[p]
    occupied[pos[~ok]] = False
    return occupied, amount.astype(np.int32), bound


@pytest.mark.parametrize("C", [64, 100], ids=lambda c: f"C{c}")
@pytest.mark.parametrize("name", [
    "random", "at_rest", "bound_past_the_capacity", "prefix", "nobody",
    "all_by_the_bound", "pushed_past_the_end"])
def test_expand_equals_the_scatter(name, C):
    rng = np.random.default_rng(sum(map(ord, name)) + C)
    occupied, amount, bound = _amounts(rng, C, name)
    lanes, fills = _lanes(rng, C)
    got, got_occ = expand(jnp.asarray(occupied), jnp.asarray(amount), bound,
                          lanes, fills)
    want, want_occ = expand_by_scatter(jnp.asarray(occupied),
                                       jnp.asarray(amount), lanes, fills)
    _assert_lanes_equal(got, want)
    np.testing.assert_array_equal(np.asarray(got_occ), np.asarray(want_occ))
    if name == "pushed_past_the_end":
        assert int(want_occ.sum()) < int(occupied.sum())


# (C, N, live rows, new rows, share of live rows kept, distinct hashes)
MERGE_CASES = {
    "ties": (64, 16, 40, 9, 0.7, 12),
    "no_ties": (64, 16, 40, 9, 0.7, 1 << 40),
    "empty_pool": (64, 16, 0, 9, 1.0, 12),
    "full_pool_nothing_new": (64, 16, 64, 0, 1.0, 12),
    "full_pool_overflows": (64, 16, 64, 5, 1.0, 12),
    "overflows_by_more_than_the_dead": (64, 16, 62, 16, 0.9, 12),
    "every_row_dead": (64, 16, 40, 9, 0.0, 12),
    "no_row_dead": (64, 16, 40, 9, 1.0, 12),
    "nothing_new": (64, 16, 40, 0, 0.7, 12),
    "chunk_all_new": (64, 16, 40, 16, 0.7, 12),
    "more_new_rows_than_live": (64, 32, 5, 27, 0.6, 12),
    "chunk_as_wide_as_pool": (32, 32, 20, 12, 0.7, 12),
    "chunk_wider_than_pool": (8, 32, 5, 27, 0.6, 12),
    "one_hash": (64, 16, 40, 9, 0.7, 1),
}


def _merge_case(name, drops):
    C, N, n, n_new, kept, distinct = MERGE_CASES[name]
    rng = np.random.default_rng(sum(map(ord, name)))
    khash = np.full(C, _SENT, dtype=np.int64)
    khash[:n] = np.sort(rng.integers(0, distinct, n))
    nh = np.full(N, _SENT, dtype=np.int64)
    nh[:n_new] = np.sort(rng.integers(0, distinct, n_new))
    live = np.arange(C) < n
    keep = live & (rng.random(C) < kept) if drops else live
    lanes, fills = _lanes(rng, C)
    # a real pool's padding holds the fills
    lanes = [jnp.where(jnp.asarray(live), x, jnp.asarray(f, x.dtype))
             for x, f in zip(lanes, fills)]
    new_lanes, _ = _lanes(rng, N)
    new_lanes[2] = None            # `src`: a merged-in row gets the -1 fill
    return khash, keep, nh, n_new, lanes, fills, new_lanes


@pytest.mark.parametrize("drops", [True, False],
                         ids=["side_that_drops_rows", "side_that_only_appends"])
@pytest.mark.parametrize("name", list(MERGE_CASES))
def test_merge_equals_the_scatter(name, drops):
    """Pool rows stay before new rows of equal hash (every lane carries
    distinct values, so an order swapped inside a run of equal hashes
    shows), rows past the capacity are dropped and counted, the padding
    holds each lane's fill."""
    khash, keep, nh, n_new, lanes, fills, new_lanes = _merge_case(name, drops)
    moved, n_after, overflow = _merge_sorted(
        jnp.asarray(keep), drops, jnp.int32(n_new),
        [jnp.asarray(khash), *lanes], [_SENT, *fills],
        [jnp.asarray(nh), *new_lanes])
    want_kh, want, want_n, want_overflow = merge_by_scatter(
        khash, keep, nh, n_new, lanes, fills, new_lanes)
    kh = moved[0]
    _assert_lanes_equal(moved, [want_kh, *want])
    assert n_after.dtype == jnp.int32
    assert (int(n_after), int(overflow)) == (want_n, want_overflow)
    if "overflows" in name or name == "chunk_wider_than_pool":
        assert want_overflow > 0
    assert np.all(np.diff(np.asarray(kh)) >= 0)


@pytest.mark.parametrize("C,N", [(16, 1), (32, 5), (64, 32)])
def test_merge_of_random_pools_equals_the_scatter(C, N):
    """70 random pools a shape: dead rows, duplicate hashes, any fill of the
    pool and of the chunk, N from 1 to C / 2."""
    import jax
    rng = np.random.default_rng(35 + C)
    merge = jax.jit(lambda kh, keep, nh, n_new, lane, new: _merge_sorted(
        keep, True, n_new, [kh, lane], [_SENT, 0], [nh, new]))
    for trial in range(70):
        n = int(rng.integers(0, C + 1))
        n_new = int(rng.integers(0, N + 1))
        khash = np.full(C, _SENT, dtype=np.int64)
        khash[:n] = np.sort(rng.integers(0, 10, n))
        nh = np.full(N, _SENT, dtype=np.int64)
        nh[:n_new] = np.sort(rng.integers(0, 10, n_new))
        keep = (np.arange(C) < n) & (rng.random(C) < rng.random())
        lane = jnp.asarray(rng.integers(1, 1 << 40, C))
        new = jnp.asarray(rng.integers(1, 1 << 40, N))
        got = merge(jnp.asarray(khash), jnp.asarray(keep), jnp.asarray(nh),
                    jnp.int32(n_new), lane, new)
        want = merge_by_scatter(khash, keep, nh, n_new, [lane], [0], [new])
        _assert_lanes_equal(got[0], [want[0], *want[1]])
        assert (int(got[1]), int(got[2])) == want[2:], trial


# ------------------------------------------------- the join's own programs

L_SCHEMA = schema(("k", DataType.INT64), ("lv", DataType.INT64),
                  ("ts", DataType.INT64))


class _NoInput:
    schema = L_SCHEMA
    pk_indices = (1,)


def _join(capacity):
    return SortedJoinExecutor(
        _NoInput(), _NoInput(), left_key_indices=[0], right_key_indices=[0],
        left_pk_indices=[1], right_pk_indices=[1], capacity=capacity,
        clean_watermark_cols=(2, 2))


def _side(rng, C, n):
    live = np.arange(C) < n
    khash = np.where(live, np.sort(rng.integers(0, 50, C)), _SENT)
    cols = tuple(jnp.asarray(np.where(live, rng.integers(1, 100, C), 0))
                 for _ in range(3))
    valids = tuple(jnp.asarray(live & (rng.random(C) < 0.8))
                   for _ in range(3))
    degree = jnp.asarray(np.where(live, rng.integers(0, 4, C), 0)
                         .astype(np.int32))
    src = np.where(live, np.arange(C), -1)
    return SortedSideState(jnp.asarray(khash), cols, valids, degree,
                           jnp.asarray(src.astype(np.int32)), jnp.int32(n))


@pytest.mark.parametrize("name", ["by_watermark", "by_key_hash", "by_both",
                                  "nothing", "everything", "empty_side"])
def test_evict_equals_the_scatter(name):
    C = 64
    rng = np.random.default_rng(sum(map(ord, name)))
    own = _side(rng, C, 0 if name == "empty_side" else 40)
    wm = {"by_watermark": 50, "by_both": 50, "everything": 1000}.get(name, 0)
    kh = {"by_key_hash": 20, "by_both": 20}.get(name, -1)
    got = _join(C)._evict_impl(own, jnp.int64(wm), jnp.int64(kh), side=0)
    keep = ((jnp.arange(C) < own.n) & ~(own.khash < kh)
            & ~(own.cols[2] < wm))
    nk = len(own.cols)
    want = compact_by_scatter(
        keep, [own.khash, *own.cols, *own.valids, own.degree, own.src],
        [_SENT] + [0] * nk + [False] * nk + [0, -1])
    _assert_lanes_equal(got.lanes()[0], want)
    assert int(got.n) == int(keep.sum())
    if name in ("by_watermark", "by_key_hash", "by_both"):
        assert 0 < int(got.n) < int(own.n)


def test_replay_program_returns_the_applys_state():
    """`_replay_impl` (what `recover()` and the spill reload run stored rows
    through) is the apply with the emitted rows left out: the side, the
    other side's degrees, the error counters and the count are the
    apply's, leaf for leaf."""
    import jax
    from risingwave_tpu.common.chunk import StreamChunk
    C, N = 64, 16
    rng = np.random.default_rng(35)
    own, other = _side(rng, C, 40), _side(rng, C, 30)
    chunk = StreamChunk.from_numpy(
        L_SCHEMA, [rng.integers(0, 50, 11), rng.integers(100, 200, 11),
                   rng.integers(1, 100, 11)], capacity=N)
    args = (own, other, jnp.zeros(3, jnp.int32), chunk, jnp.int64(30))
    join = _join(C)
    full = join._apply_impl(*args, side=0)
    got = join._replay_impl(*args, side=0)
    want = (full[0], full[1], full[5], full[6])
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert int(got[3]) == int(got[0].n) > 0
