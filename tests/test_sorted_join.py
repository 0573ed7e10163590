"""SortedJoinExecutor: changelog semantics vs a golden model, on integer
keys and on FLOAT64 keys, and differential runs against the nested-loop
reference of tests/_join_reference.py on identical scripted inputs.

The join is the reference's hash_join.rs into_stream in behaviour: the
same multiset of emitted change rows for any interleaving of
inserts/deletes/update pairs, NULL keys, and watermark cleaning.
"""

import asyncio
import functools
from collections import Counter

import numpy as np
import pytest

from _join_reference import InnerJoinReference, join_rows
from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, StreamChunk,
)
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.stream import Barrier, BarrierKind, Watermark
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.stream.sorted_join import SortedJoinExecutor, key_hash
from risingwave_tpu.utils.metrics import (GLOBAL_METRICS, JOIN_LIVE_ROWS,
                                          JOIN_PERSIST_ROWS)

L_SCHEMA = schema(("k", DataType.INT64), ("lv", DataType.INT64))
R_SCHEMA = schema(("k", DataType.INT64), ("rv", DataType.INT64))
LF_SCHEMA = schema(("k", DataType.FLOAT64), ("lv", DataType.INT64))
RF_SCHEMA = schema(("k", DataType.FLOAT64), ("rv", DataType.INT64))

# Scenarios below run once per key dtype. A float scenario's keys are
# k / 4 + 0.125: exact in binary, never integral, and several of them
# share an integer part, so a hash of the truncated value would put them
# in one hash range and only the exact compare would tell them apart.
KEY_DTYPES = ["int64", "float64"]
key_dtypes = pytest.mark.parametrize("kd", KEY_DTYPES)


def schemas(kd):
    return (LF_SCHEMA, RF_SCHEMA) if kd == "float64" else (L_SCHEMA, R_SCHEMA)


def K(kd, k):
    return k / 4 + 0.125 if kd == "float64" else k


def keyed(kd, rows):
    """(op, k, v) rows with the logical key k turned into `kd`'s key."""
    return [(op, K(kd, k), v) for op, k, v in rows]


class ScriptSource(Executor):
    def __init__(self, sch, messages):
        self.schema = sch
        self.messages = messages
        self.identity = "ScriptSource"

    async def execute(self):
        for m in self.messages:
            yield m
            await asyncio.sleep(0)


def chunk(sch, rows, cap=16):
    ops = np.asarray([r[0] for r in rows], dtype=np.int8)
    cols = [np.asarray([r[1 + i] for r in rows], dtype=f.data_type.np_dtype)
            for i, f in enumerate(sch)]
    return StreamChunk.from_numpy(sch, cols, ops=ops, capacity=cap)


def barrier(curr, prev, kind=BarrierKind.CHECKPOINT):
    return Barrier(EpochPair(curr, prev), kind)


async def run_sorted(l_msgs, r_msgs, kd="int64", **kw):
    kw.setdefault("capacity", 64)
    ls, rs = schemas(kd)
    join = SortedJoinExecutor(
        ScriptSource(ls, l_msgs), ScriptSource(rs, r_msgs),
        left_key_indices=[0], right_key_indices=[0],
        left_pk_indices=[1], right_pk_indices=[1], **kw)
    out = []
    async for m in join.execute():
        out.append(m)
    return join, out


def changelog_counter(out):
    """Multiset of (sign, row) over all emitted chunks — op pairs degrade
    to Delete/Insert, so compare by sign."""
    c = Counter()
    for m in out:
        if isinstance(m, StreamChunk):
            for op, vals in m.to_rows():
                sign = 1 if op in (OP_INSERT, OP_UPDATE_INSERT) else -1
                c[(sign, vals)] += 1
    return c


def net(counter):
    """barrier_align interleaves the two sides nondeterministically, and
    different interleavings legitimately differ in transient +/- pairs —
    the interleaving-independent invariant is the NET changelog."""
    acc = Counter()
    for (sign, row), cnt in counter.items():
        acc[row] += sign * cnt
    return {r: c for r, c in acc.items() if c}


def _named_nan(row):
    """A NaN is not `==` itself: name it, so that equal rows are equal."""
    return tuple("NaN" if v != v else v for v in row)


def _accumulate(out):
    """Net changelog -> final row multiset; NULLs (by validity) as None."""
    acc = Counter()
    for m in out:
        if not isinstance(m, StreamChunk):
            continue
        vis = np.asarray(m.vis)
        ops = np.asarray(m.ops)[vis]
        data = [np.asarray(c.data)[vis].tolist() for c in m.columns]
        valid = [np.asarray(c.valid_mask())[vis] for c in m.columns]
        for r in range(len(ops)):
            row = _named_nan(d[r] if v[r] else None
                             for d, v in zip(data, valid))
            acc[row] += 1 if ops[r] in (OP_INSERT, OP_UPDATE_INSERT) else -1
    return Counter({k: v for k, v in acc.items() if v})


def _golden_outer(events, join_type):
    """The reference's join of what a list of (side, op, key, pk) events
    leaves live: output rows (l_k, l_pk, r_k, r_pk), None for NULL."""
    live = [{}, {}]   # side -> pk -> key
    for side, op, k, pk in events:
        if op == OP_INSERT:
            live[side][pk] = k
        else:
            live[side].pop(pk, None)
    return join_rows(*([(k, pk) for pk, k in lv.items()] for lv in live),
                     join_type=join_type)


def joined(kd, lk, lv, rk, rv):
    return (K(kd, lk), lv, K(kd, rk), rv)


# ------------------------------------------- scenarios, once per key dtype

@key_dtypes
async def test_inner_join_basic(kd):
    ls, rs = schemas(kd)
    l = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(ls, keyed(kd, [(OP_INSERT, 1, 10), (OP_INSERT, 2, 20)])),
         barrier(2, 1)]
    r = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(rs, keyed(kd, [(OP_INSERT, 1, 100), (OP_INSERT, 3, 300)])),
         barrier(2, 1)]
    _, out = await run_sorted(l, r, kd)
    assert changelog_counter(out) == Counter(
        {(1, joined(kd, 1, 10, 1, 100)): 1})


@key_dtypes
async def test_join_both_orders_and_duplicates(kd):
    # left rows arrive first epoch; right rows with duplicate keys second
    ls, rs = schemas(kd)
    l = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(ls, keyed(kd, [(OP_INSERT, 1, 10), (OP_INSERT, 1, 11)])),
         barrier(2, 1),
         barrier(3, 2)]
    r = [barrier(1, 0, BarrierKind.INITIAL),
         barrier(2, 1),
         chunk(rs, keyed(kd, [(OP_INSERT, 1, 100), (OP_INSERT, 1, 101),
                              (OP_INSERT, 1, 102)])),
         barrier(3, 2)]
    _, out = await run_sorted(l, r, kd)
    assert net(changelog_counter(out)) == {
        joined(kd, 1, lv, 1, rv): 1
        for lv in (10, 11) for rv in (100, 101, 102)}


@key_dtypes
async def test_join_retraction(kd):
    ls, rs = schemas(kd)
    l = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(ls, keyed(kd, [(OP_INSERT, 1, 10)])),
         barrier(2, 1),
         barrier(3, 2)]
    r = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(rs, keyed(kd, [(OP_INSERT, 1, 100)])),
         barrier(2, 1),
         chunk(rs, keyed(kd, [(OP_DELETE, 1, 100)])),
         barrier(3, 2)]
    _, out = await run_sorted(l, r, kd)
    row = joined(kd, 1, 10, 1, 100)
    assert changelog_counter(out) == Counter({(1, row): 1, (-1, row): 1})


@key_dtypes
async def test_join_update_pair_retracts_old_match(kd):
    """An UD/UI pair on the right (e.g. a max-agg output) swaps matches."""
    ls, rs = schemas(kd)
    l = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(ls, keyed(kd, [(OP_INSERT, 5, 50), (OP_INSERT, 7, 70)])),
         barrier(2, 1),
         barrier(3, 2)]
    r = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(rs, keyed(kd, [(OP_INSERT, 5, 900)])),
         barrier(2, 1),
         chunk(rs, keyed(kd, [(OP_UPDATE_DELETE, 5, 900),
                              (OP_UPDATE_INSERT, 7, 900)])),
         barrier(3, 2)]
    _, out = await run_sorted(l, r, kd)
    assert net(changelog_counter(out)) == {joined(kd, 7, 70, 7, 900): 1}


@key_dtypes
async def test_join_within_chunk_update_pair_same_pk(kd):
    # UD/UI with the same key AND pk (a value-in-place change):
    # delete-then-insert must leave the one new row stored
    ls, rs = schemas(kd)
    l = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(ls, keyed(kd, [(OP_INSERT, 1, 10)])),
         barrier(2, 1),
         barrier(3, 2)]
    r = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(rs, keyed(kd, [(OP_INSERT, 1, 100)])),
         barrier(2, 1),
         chunk(rs, keyed(kd, [(OP_UPDATE_DELETE, 1, 100),
                              (OP_UPDATE_INSERT, 1, 100)])),
         barrier(3, 2)]
    join, out = await run_sorted(l, r, kd)
    assert net(changelog_counter(out)) == {joined(kd, 1, 10, 1, 100): 1}
    assert int(join.sides[1].n) == 1


@key_dtypes
async def test_join_condition(kd):
    from risingwave_tpu.expr import call, col
    cond = call("greater_than", col(3), col(1))  # rv > lv
    ls, rs = schemas(kd)
    l = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(ls, keyed(kd, [(OP_INSERT, 1, 10), (OP_INSERT, 1, 200)])),
         barrier(2, 1)]
    r = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(rs, keyed(kd, [(OP_INSERT, 1, 100)])),
         barrier(2, 1)]
    _, out = await run_sorted(l, r, kd, condition=cond)
    assert changelog_counter(out) == Counter(
        {(1, joined(kd, 1, 10, 1, 100)): 1})


def random_script(rng, kd, n_epochs, rows_per_chunk):
    """Random inserts/deletes on both sides, one chunk a side an epoch.
    Returns the two message lists and the reference fed the same rows."""
    ref = InnerJoinReference()
    ls, rs = schemas(kd)
    msgs = ([barrier(1, 0, BarrierKind.INITIAL)],
            [barrier(1, 0, BarrierKind.INITIAL)])
    next_pk = [0, 1_000_000]
    for epoch in range(2, 2 + n_epochs):
        for s, sch in ((0, ls), (1, rs)):
            rows = []
            for _ in range(int(rng.integers(*rows_per_chunk))):
                live = ref.live[s]
                if live and rng.random() < 0.35:
                    row = live[int(rng.integers(len(live)))]
                    rows.append((OP_DELETE,) + row)
                    ref.apply(s, [(-1, row)])
                else:
                    row = (K(kd, int(rng.integers(0, 6))), next_pk[s])
                    next_pk[s] += 1
                    rows.append((OP_INSERT,) + row)
                    ref.apply(s, [(1, row)])
            msgs[s].append(chunk(sch, rows, cap=16))
            msgs[s].append(barrier(epoch, epoch - 1))
    return msgs, ref


@key_dtypes
async def test_join_golden_random(kd):
    """Random inserts/deletes on both sides; the accumulated changelog must
    equal the inner join of the final live multisets."""
    (l_msgs, r_msgs), ref = random_script(
        np.random.default_rng(7), kd, n_epochs=5, rows_per_chunk=(12, 13))
    _, out = await run_sorted(l_msgs, r_msgs, kd, capacity=256,
                              match_factor=16)
    got = net(changelog_counter(out))
    assert got and got == dict(ref.joined())


@key_dtypes
async def test_differential_vs_reference_random(kd):
    """Randomized differential test: the message streams the executor runs
    and the rows the nested-loop reference is fed are the same; the NET
    changelog must be the reference's join of what is live at the end."""
    (l_msgs, r_msgs), ref = random_script(
        np.random.default_rng(17), kd, n_epochs=12, rows_per_chunk=(1, 8))
    _, out = await run_sorted(l_msgs, r_msgs, kd, capacity=256)
    got = net(changelog_counter(out))
    assert got == dict(ref.joined())
    # every delete must retract a prior insert (no negative prefix)
    assert all(c > 0 for c in got.values())


@key_dtypes
@pytest.mark.parametrize("match_factor", [
    2,    # the default: M = 32 candidate slots for a 16-row chunk
    64,   # q5full's shape: M = 1024 > the pool's 256, so the match
    #       buffer's slot -> range ranks map M-many slots to few rows
])
def test_differential_lockstep_apply(kd, match_factor):
    """Deterministic differential: apply the SAME chunk sequence directly
    through the join's _apply and through the reference (no async
    interleaving) — per-chunk outputs and live state multisets must match
    exactly."""
    import jax.numpy as jnp
    from risingwave_tpu.stream.sorted_join import NO_WATERMARK

    rng = np.random.default_rng(11)
    ls, rs = schemas(kd)
    ref = InnerJoinReference()
    sj = SortedJoinExecutor(
        ScriptSource(ls, []), ScriptSource(rs, []),
        left_key_indices=[0], right_key_indices=[0],
        left_pk_indices=[1], right_pk_indices=[1], capacity=256,
        match_factor=match_factor)
    next_pk = [0, 1_000_000]

    def sj_live(s):
        st = sj.sides[s]
        n = int(np.asarray(st.n))
        return Counter(zip(*(np.asarray(c)[:n].tolist() for c in st.cols)))

    wm = jnp.int64(NO_WATERMARK)
    for _ in range(40):
        side = int(rng.integers(0, 2))
        rows, pool = [], list(ref.live[side])
        for _ in range(int(rng.integers(1, 8))):
            if pool and rng.random() < 0.4:
                row = pool.pop(int(rng.integers(len(pool))))
                rows.append((OP_DELETE,) + row)
            else:
                row = (K(kd, int(rng.integers(0, 6))), next_pk[side])
                next_pk[side] += 1
                pool.append(row)
                rows.append((OP_INSERT,) + row)
        c = chunk(ls if side == 0 else rs, rows)
        (sj.sides[side], _od, cols_s, ops_s, vis_s, sj._errs_dev, _) = \
            sj._apply(sj.sides[side], sj.sides[1 - side], sj._errs_dev, c,
                      wm, side=side)
        out_s = StreamChunk(tuple(cols_s[i] for i in sj.output_indices),
                            ops_s, vis_s, sj.schema)
        want = ref.apply(side, [(1 if r[0] == OP_INSERT else -1, r[1:])
                                for r in rows])
        assert changelog_counter([out_s]) == want
        assert sj_live(side) == Counter(ref.live[side])
    assert int(np.asarray(sj._errs_dev).sum()) == 0


# ------------------------------------------------------- ranks by counting

_SENT = np.iinfo(np.int64).max


def _merge_case(name):
    """(pool hashes [C] sorted with sentinel padding, keep mask [C], new
    hashes [N] sorted with sentinel padding, n_new)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    C, N, n, n_new = 64, 16, 40, 9
    if name == "n_new_zero":
        n_new = 0
    elif name == "n_new_all":
        n_new = N
    elif name == "pool_empty":
        n = 0
    elif name == "pool_full":
        n = C
    elif name == "chunk_wider_than_pool":
        C, N, n, n_new = 8, 32, 5, 27
    # few distinct values: ties inside the pool, inside the chunk and
    # between the two are the rule, not the exception
    hi = 12 if name != "no_ties" else 1 << 40
    khash = np.full(C, _SENT, dtype=np.int64)
    khash[:n] = np.sort(rng.integers(0, hi, n))
    nh = np.full(N, _SENT, dtype=np.int64)
    nh[:n_new] = np.sort(rng.integers(0, hi, n_new))
    if name == "new_above_every_pool_row" and n_new:
        nh[:n_new] = np.sort(rng.integers(hi, 2 * hi, n_new))
    keep = (np.arange(C) < n) & (rng.random(C) < 0.7)
    return khash, keep, nh, n_new


def _range_case(name):
    """(range lengths [N] int64, M)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    N, M = 16, 64
    lens = rng.integers(0, 5, N).astype(np.int64)
    if name == "empty_ranges_repeat":
        lens[rng.random(N) < 0.6] = 0
        lens[:3] = 0                       # leading empties: offset 0, thrice
    elif name == "all_empty":
        lens[:] = 0
    elif name == "total_over_M":
        lens = rng.integers(3, 12, N).astype(np.int64)
    elif name == "offset_past_2_31":
        lens[5] = (1 << 31) + 7            # the hot-key case: int64 offsets
        lens[9] = 1 << 33
    elif name == "exactly_M":
        lens[:] = M // N
    elif name == "slots_outnumber_rows":
        M = 1024                           # q5full's shape: M >> N
    return lens, M


@pytest.mark.parametrize("name", [
    "ties", "no_ties", "n_new_zero", "n_new_all", "pool_empty", "pool_full",
    "chunk_wider_than_pool", "new_above_every_pool_row"])
def test_merge_ranks_equal_the_searches(name):
    """`_merge_ranks` against the two binary searches it replaced, value
    for value: stored rows stay before new rows of equal hash, sentinel
    padding on both arrays counts for nothing."""
    import jax.numpy as jnp
    from risingwave_tpu.stream.sorted_join import _merge_ranks
    khash, keep, nh, n_new = _merge_case(name)
    dead_cum = np.cumsum(~keep).astype(np.int32)
    new_lt, kept_le = _merge_ranks(jnp.asarray(khash), jnp.asarray(dead_cum),
                                   jnp.asarray(nh),
                                   jnp.arange(len(nh)) < n_new)
    assert new_lt.dtype == jnp.int32 and kept_le.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(new_lt), np.searchsorted(nh, khash, side="left"))
    want_le = (keep[None, :] & (khash[None, :] <= nh[:, None])).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(kept_le), want_le)
    # and the merge they drive is a permutation onto [0, n_kept + n_new)
    tgt = np.concatenate([
        (np.cumsum(keep) - 1 + np.asarray(new_lt))[keep],
        (np.arange(len(nh)) + np.asarray(kept_le))[:n_new]])
    assert sorted(tgt.tolist()) == list(range(int(keep.sum()) + n_new))


@pytest.mark.parametrize("name", [
    "random", "empty_ranges_repeat", "all_empty", "total_over_M",
    "offset_past_2_31", "exactly_M", "slots_outnumber_rows"])
def test_range_owner_equals_the_search(name):
    """`_range_owner` against `searchsorted(offs, arange(M), 'right')`: a
    row with an empty range repeats its offset (duplicates add up), an
    offset at or past M — or past 2^31 — owns no slot, and what the apply
    derives from it (`total`, hence the overflow count) is untouched."""
    import jax.numpy as jnp
    from risingwave_tpu.stream.sorted_join import _range_owner
    lens, M = _range_case(name)
    offs = np.cumsum(lens)
    got = _range_owner(jnp.asarray(offs), M)
    assert got.dtype == jnp.int32 and got.shape == (M,)
    np.testing.assert_array_equal(
        np.asarray(got), np.searchsorted(offs, np.arange(M), side="right"))
    # slot j < min(total, M) lies inside its owner's range
    n_live = int(min(offs[-1], M))
    owner = np.asarray(got)[:n_live]
    start = np.concatenate([[0], offs[:-1]])
    assert (owner < len(lens)).all()
    assert ((start[owner] <= np.arange(n_live))
            & (np.arange(n_live) < offs[owner])).all()


def test_match_overflow_count_is_total_minus_buffer():
    """20 left rows of one key probe 5 stored right rows: 100 candidates
    into a 64-slot buffer. The overflow counter reads the 36 that did not
    fit, and the 64 that did are all emitted."""
    import jax.numpy as jnp
    from risingwave_tpu.stream.sorted_join import NO_WATERMARK
    sj = SortedJoinExecutor(
        ScriptSource(L_SCHEMA, []), ScriptSource(R_SCHEMA, []),
        left_key_indices=[0], right_key_indices=[0],
        left_pk_indices=[1], right_pk_indices=[1], capacity=64)
    wm = jnp.int64(NO_WATERMARK)
    rc = chunk(R_SCHEMA, [(OP_INSERT, 1, 100 + i) for i in range(5)], cap=32)
    sj.sides[1], _, _, _, _, sj._errs_dev, _ = sj._apply(
        sj.sides[1], sj.sides[0], sj._errs_dev, rc, wm, side=1)
    lc = chunk(L_SCHEMA, [(OP_INSERT, 1, i) for i in range(20)], cap=32)
    _, _, _, _, vis, errs, _ = sj._apply(
        sj.sides[0], sj.sides[1], sj._errs_dev, lc, wm, side=0)
    assert np.asarray(errs).tolist() == [36, 0, 0]
    assert int(np.asarray(vis).sum()) == 64


# ------------------------------------------------------- FLOAT64 key values

def _float_chunk(sch, ops, keys, vals, key_valid=None):
    return StreamChunk.from_numpy(
        sch, [np.asarray(keys, dtype=np.float64),
              np.asarray(vals, dtype=np.int64)],
        ops=np.asarray(ops, dtype=np.int8), capacity=16,
        valids=[None if key_valid is None else np.asarray(key_valid), None])


@pytest.mark.parametrize("case", ["signed_zero", "nan", "null"])
async def test_float_key_values(case):
    """-0.0 and +0.0 are one key (they are `==`); a NaN key equals nothing,
    itself included, so it joins nothing; a NULL key never joins. In a LEFT
    join each unmatched left row shows NULL-padded, and retracting it takes
    the padded row back without tripping the delete-miss watchdog."""
    nan = float("nan")
    lkey, rkey, lvalid = {"signed_zero": (-0.0, 0.0, True),
                          "nan": (nan, nan, True),
                          "null": (1.5, 1.5, False)}[case]
    l = [barrier(1, 0, BarrierKind.INITIAL),
         _float_chunk(LF_SCHEMA, [OP_INSERT, OP_INSERT], [lkey, 2.5],
                      [10, 20], key_valid=[lvalid, True]),
         barrier(2, 1),
         _float_chunk(LF_SCHEMA, [OP_DELETE], [2.5], [20]),
         barrier(3, 2)]
    r = [barrier(1, 0, BarrierKind.INITIAL),
         _float_chunk(RF_SCHEMA, [OP_INSERT, OP_INSERT], [rkey, 2.5],
                      [100, 200]),
         barrier(2, 1), barrier(3, 2)]
    want = join_rows([(lkey if lvalid else None, 10)],
                     [(rkey, 100), (2.5, 200)], join_type="left")
    _, out = await run_sorted(list(l), list(r), "float64", join_type="left")
    got = _accumulate(out)
    assert got == Counter({_named_nan(row): c for row, c in want.items()})
    (row,) = got
    if case == "signed_zero":
        assert row == (-0.0, 10, 0.0, 100)
        assert np.signbit(row[0]) and not np.signbit(row[2])
    else:
        assert row[1:] == (10, None, None)
    # the unmatched row's own retraction (a NaN / NULL key is not stored,
    # so there is no stored row it could miss)
    l2 = l[:3] + [_float_chunk(LF_SCHEMA, [OP_DELETE, OP_DELETE],
                               [lkey, 2.5], [10, 20],
                               key_valid=[lvalid, True]), barrier(3, 2)]
    join, out2 = await run_sorted(l2, list(r), "float64", join_type="left")
    assert _accumulate(out2) == Counter()
    assert int(join.sides[0].n) == 0


# the parent's `key_hash` (PR 27) on these columns, computed there
_INT64_KEYS = np.array([0, 1, -1, 7, 2**40 + 3, -2**62, 2**63 - 1],
                       dtype=np.int64)
_INT32_KEYS = np.array([5, -5, 0, 123456789, 42, 1, -1], dtype=np.int32)
_PINNED = {
    "int64": [1610172448792072464, 3510239654435238608, 5813959610162567889,
              3858702458973525374, 460566425422708489, 7726348192752811715,
              2339369700579237230],
    "int64,int32": [8817788084488479165, 6296065877570977183,
                    4620001967084662399, 7504037406712506029,
                    6818141598011270626, 1306871546894992134,
                    5234006721980145236],
    "int32": [2149605058787081185, 2064198114435380727, 1610172448792072464,
              5161658241008303108, 1551494370527391033, 3510239654435238608,
              5813959610162567889],
}


def _key_hash_np(cols):
    """numpy twin of `key_hash`: a float column by its `float_pair_bits_np`
    image, an integer column by value."""
    from risingwave_tpu.common.floatbits import float_pair_bits_np
    m = np.uint64
    with np.errstate(over="ignore"):
        h = np.full(len(cols[0]), 0x243F6A8885A308D3, dtype=m)
        for c in cols:
            c = np.asarray(c)
            if np.issubdtype(c.dtype, np.floating):
                c = float_pair_bits_np(c)
            x = h ^ (c.astype(m) * m(0x9E3779B97F4A7C15))
            x = x + m(0x9E3779B97F4A7C15)
            x = (x ^ (x >> m(30))) * m(0xBF58476D1CE4E5B9)
            x = (x ^ (x >> m(27))) * m(0x94D049BB133111EB)
            h = x ^ (x >> m(31))
    return (h >> m(1)).astype(np.int64)


def test_key_hash_integer_columns_as_before_and_float_twin():
    """Integer key columns hash to the bits they hashed to before float keys
    were taken (the state's order, and every compiled join program, follow
    them); a float column hashes alike in jnp and in numpy, so a host
    mirror of a device hash agrees; -0.0 / +0.0 alike, every NaN alike."""
    import jax.numpy as jnp
    cols = {"int64": [_INT64_KEYS], "int64,int32": [_INT64_KEYS, _INT32_KEYS],
            "int32": [_INT32_KEYS]}
    for name, want in _PINNED.items():
        got = np.asarray(key_hash([jnp.asarray(c) for c in cols[name]]))
        assert got.tolist() == want, name
        assert _key_hash_np(cols[name]).tolist() == want, name
    nan2 = np.array([0x7FF8000000000001], dtype=np.uint64).view(np.float64)[0]
    f = np.array([0.0, -0.0, 1.2, 1.7, -1.2, 0.1 + 0.2, 0.3, 1e300, -1e300,
                  1e-310, np.inf, -np.inf, np.nan, nan2, 2.0**53 + 2, 7.25],
                 dtype=np.float64)
    got = np.asarray(key_hash([jnp.asarray(f), jnp.asarray(
        np.arange(len(f), dtype=np.int64) % 3)]))
    assert got.tolist() == _key_hash_np(
        [f, np.arange(len(f), dtype=np.int64) % 3]).tolist()
    h = np.asarray(key_hash([jnp.asarray(f)]))
    assert h[0] == h[1] and h[12] == h[13]
    assert h[2] != h[3] and h[2] != h[4] and (h >= 0).all()


def test_key_hash_float_keys_with_one_integer_part_spread():
    """64 keys 7.0 + i / 64: a hash of the value cast to an integer gives
    all of them ONE hash (one candidate range, 64 compares a probe)."""
    import jax.numpy as jnp
    f = 7.0 + np.arange(64, dtype=np.float64) / 64
    h = np.asarray(key_hash([jnp.asarray(f)]))
    assert len(set(h.tolist())) >= 32
    assert len(set((h >> 55).tolist())) >= 32    # in the top bits too


def test_retraction_and_update_pair():
    async def go():
        l = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(L_SCHEMA, [(OP_INSERT, 1, 10)]),
             barrier(2, 1),
             chunk(L_SCHEMA, [(OP_UPDATE_DELETE, 1, 10),
                              (OP_UPDATE_INSERT, 1, 11)]),
             chunk(L_SCHEMA, [(OP_DELETE, 1, 11)]),
             barrier(3, 2)]
        r = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(R_SCHEMA, [(OP_INSERT, 1, 100)]),
             barrier(2, 1),
             barrier(3, 2)]
        _, out = await run_sorted(l, r)
        got = changelog_counter(out)
        # insert 10 -> +, retract 10 -> -, insert 11 -> +, delete 11 -> -
        assert got == Counter({
            (1, (1, 10, 1, 100)): 1, (-1, (1, 10, 1, 100)): 1,
            (1, (1, 11, 1, 100)): 1, (-1, (1, 11, 1, 100)): 1,
        })
    asyncio.run(go())


def test_null_keys_never_match():
    async def go():
        lcols = [np.asarray([1, 1], dtype=np.int64),
                 np.asarray([10, 11], dtype=np.int64)]
        lc = StreamChunk.from_numpy(
            L_SCHEMA, lcols, ops=np.zeros(2, dtype=np.int8), capacity=16,
            valids=[np.asarray([True, False]), None])
        l = [barrier(1, 0, BarrierKind.INITIAL), lc, barrier(2, 1)]
        r = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(R_SCHEMA, [(OP_INSERT, 1, 100)]),
             barrier(2, 1)]
        _, out = await run_sorted(l, r)
        got = changelog_counter(out)
        assert got == Counter({(1, (1, 10, 1, 100)): 1})
    asyncio.run(go())


def test_within_chunk_update_pair_same_key():
    async def go():
        l = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(L_SCHEMA, [(OP_INSERT, 7, 1)]),
             barrier(2, 1)]
        r = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(R_SCHEMA, [(OP_INSERT, 7, 50)]),
             barrier(2, 1),
             chunk(R_SCHEMA, [(OP_UPDATE_DELETE, 7, 50),
                              (OP_UPDATE_INSERT, 7, 51)]),
             barrier(3, 2)]
        _, out = await run_sorted(l, r)
        got = changelog_counter(out)
        assert got == Counter({
            (1, (7, 1, 7, 50)): 1, (-1, (7, 1, 7, 50)): 1,
            (1, (7, 1, 7, 51)): 1,
        })
    asyncio.run(go())


def test_watermark_eviction_inline():
    """Rows below the clean watermark must be evicted by the NEXT apply on
    that side (not only at barriers) — the property that removes the
    epoch-churn capacity cap."""
    async def go():
        l = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(L_SCHEMA, [(OP_INSERT, 1, 10)]),
             Watermark(1, DataType.INT64, 1000),   # evict lv < 1000
             chunk(L_SCHEMA, [(OP_INSERT, 2, 2000)]),
             barrier(2, 1)]
        r = [barrier(1, 0, BarrierKind.INITIAL),
             barrier(2, 1),
             chunk(R_SCHEMA, [(OP_INSERT, 1, 100), (OP_INSERT, 2, 200)]),
             barrier(3, 2)]
        l += [barrier(3, 2)]
        join, out = await run_sorted(
            l, r, clean_watermark_cols=(1, None))
        got = changelog_counter(out)
        # (1, 10) was evicted before the right chunk probed: only (2,2000)
        assert got == Counter({(1, (2, 2000, 2, 200)): 1})
        assert int(np.asarray(join.sides[0].n)) == 1
    asyncio.run(go())


def test_append_only_fast_path():
    """append_only sides compile without the retraction machinery but
    produce the same changelog."""
    async def go():
        l = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(L_SCHEMA, [(OP_INSERT, 1, 10), (OP_INSERT, 1, 11),
                              (OP_INSERT, 2, 20)]),
             barrier(2, 1)]
        r = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(R_SCHEMA, [(OP_INSERT, 1, 100)]),
             chunk(R_SCHEMA, [(OP_INSERT, 2, 200)]),
             barrier(2, 1)]
        _, out = await run_sorted(l, r, append_only=(True, True))
        got = changelog_counter(out)
        assert got == Counter({
            (1, (1, 10, 1, 100)): 1, (1, (1, 11, 1, 100)): 1,
            (1, (2, 20, 2, 200)): 1,
        })
    asyncio.run(go())


def test_overflow_fail_stops():
    async def go():
        rows = [(OP_INSERT, i, i) for i in range(20)]
        l = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(L_SCHEMA, rows, cap=32), barrier(2, 1)]
        r = [barrier(1, 0, BarrierKind.INITIAL), barrier(2, 1)]
        with pytest.raises(RuntimeError, match="state overflow"):
            await run_sorted(l, r, capacity=16)
    asyncio.run(go())


# ---------------------------------------------------------------- outer joins

def _run_outer(events, join_type, n_epochs=4):
    """Split events into epochs, run the executor, compare final result."""
    msgs = [[barrier(1, 0, BarrierKind.INITIAL)],
            [barrier(1, 0, BarrierKind.INITIAL)]]
    per = max(1, len(events) // n_epochs)
    epoch = 2
    for i in range(0, len(events), per):
        batch = events[i:i + per]
        for side in (0, 1):
            rows = [(op, k, pk) for s, op, k, pk in batch if s == side]
            if rows:
                msgs[side].append(chunk(L_SCHEMA if side == 0 else R_SCHEMA,
                                        rows))
        msgs[0].append(barrier(epoch, epoch - 1))
        msgs[1].append(barrier(epoch, epoch - 1))
        epoch += 1

    async def go():
        _, out = await run_sorted(list(msgs[0]), list(msgs[1]),
                                  capacity=256, join_type=join_type,
                                  match_factor=16)
        return out
    out = asyncio.run(go())
    assert _accumulate(out) == _golden_outer(events, join_type), \
        f"{join_type} mismatch"


def test_left_outer_basic_transitions():
    events = [
        (0, OP_INSERT, 1, 10),     # left 1 unmatched -> (1,10,NULL)
        (1, OP_INSERT, 1, 100),    # match -> retract NULL, emit (1,10,1,100)
        (1, OP_DELETE, 1, 100),    # unmatch -> back to (1,10,NULL)
        (1, OP_INSERT, 2, 200),    # right 2 has no left: nothing (left join)
    ]
    _run_outer(events, "left")


def test_right_and_full_outer():
    events = [
        (0, OP_INSERT, 1, 10),
        (1, OP_INSERT, 2, 200),
        (0, OP_INSERT, 2, 20),
        (1, OP_INSERT, 1, 100),
        (0, OP_DELETE, 1, 10),
    ]
    _run_outer(events, "right")
    _run_outer(events, "full")


def test_outer_null_keys_emit_padded():
    async def go():
        lcols = [np.asarray([5, 7], dtype=np.int64),
                 np.asarray([50, 70], dtype=np.int64)]
        lc = StreamChunk.from_numpy(
            L_SCHEMA, lcols, ops=np.zeros(2, dtype=np.int8), capacity=16,
            valids=[np.asarray([False, True]), None])
        l = [barrier(1, 0, BarrierKind.INITIAL), lc, barrier(2, 1)]
        r = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(R_SCHEMA, [(OP_INSERT, 7, 700)]),
             barrier(2, 1)]
        _, out = await run_sorted(l, r, join_type="left")
        return out
    out = asyncio.run(go())
    # NULL-key left row emits (NULL, 50, NULL, NULL); key-7 row matches
    assert _accumulate(out) == Counter({
        (None, 50, None, None): 1, (7, 70, 7, 700): 1})


def test_outer_randomized_golden():
    rng = np.random.default_rng(23)
    for join_type in ("left", "right", "full"):
        live = [dict(), dict()]
        next_pk = [0, 1_000_000]
        events = []
        for _ in range(120):
            side = int(rng.integers(0, 2))
            if live[side] and rng.random() < 0.35:
                pk = int(rng.choice(list(live[side].keys())))
                k = live[side].pop(pk)
                events.append((side, OP_DELETE, k, pk))
            else:
                k = int(rng.integers(0, 5))
                pk = next_pk[side]
                next_pk[side] += 1
                live[side][pk] = k
                events.append((side, OP_INSERT, k, pk))
        _run_outer(events, join_type, n_epochs=10)


# ---------------------------------------------------------------- durability

def _durable_tables(store, base=30, kd="int64"):
    from risingwave_tpu.state import StateTable
    ls, rs = schemas(kd)
    return (StateTable(store, base, ls, pk_indices=[1]),
            StateTable(store, base + 1, rs, pk_indices=[1]))


@key_dtypes
async def test_sorted_persist_recover_inner(kd):
    from risingwave_tpu.state import MemoryStateStore
    store = MemoryStateStore()
    ls, rs = schemas(kd)
    l = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(ls, keyed(kd, [(OP_INSERT, 1, 10), (OP_INSERT, 2, 20)])),
         barrier(2, 1)]
    r = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(rs, keyed(kd, [(OP_INSERT, 1, 100)])),
         barrier(2, 1)]
    await run_sorted(l, r, kd, state_tables=_durable_tables(store, kd=kd))
    store.sync(2)

    # restart: right side gains a row matching recovered left row 2
    l2 = [barrier(3, 2, BarrierKind.INITIAL), barrier(4, 3)]
    r2 = [barrier(3, 2, BarrierKind.INITIAL),
          chunk(rs, keyed(kd, [(OP_INSERT, 2, 200)])),
          barrier(4, 3)]
    _, out2 = await run_sorted(l2, r2, kd,
                               state_tables=_durable_tables(store, kd=kd))
    assert changelog_counter(out2) == Counter(
        {(1, joined(kd, 2, 20, 2, 200)): 1})


def test_sorted_persist_update_across_restart():
    """An in-place value update (same pk) diffs as delete+insert on one
    key; after restart the NEW value must be the joinable one."""
    from risingwave_tpu.state import MemoryStateStore
    store = MemoryStateStore()

    async def run1():
        l = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(L_SCHEMA, [(OP_INSERT, 1, 10)]),
             barrier(2, 1),
             chunk(L_SCHEMA, [(OP_UPDATE_DELETE, 1, 10),
                              (OP_UPDATE_INSERT, 2, 10)]),
             barrier(3, 2)]
        r = [barrier(1, 0, BarrierKind.INITIAL), barrier(2, 1),
             barrier(3, 2)]
        await run_sorted(l, r, state_tables=_durable_tables(store, 40))
    asyncio.run(run1())
    store.sync(3)

    async def run2():
        l2 = [barrier(4, 3, BarrierKind.INITIAL), barrier(5, 4)]
        r2 = [barrier(4, 3, BarrierKind.INITIAL),
              chunk(R_SCHEMA, [(OP_INSERT, 2, 200)]),
              barrier(5, 4)]
        _, out = await run_sorted(l2, r2,
                                  state_tables=_durable_tables(store, 40))
        return out
    out2 = asyncio.run(run2())
    # key moved 1 -> 2 (pk stays 10): only the new key matches
    assert changelog_counter(out2) == Counter({(1, (2, 10, 2, 200)): 1})


def test_sorted_outer_recover_rebuilds_degrees():
    """LEFT join: an unmatched left row crosses a crash; the first
    post-recovery match must retract its NULL-padded row — which only
    happens if recovery rebuilt the degree columns."""
    from risingwave_tpu.state import MemoryStateStore
    store = MemoryStateStore()

    async def run1():
        l = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(L_SCHEMA, [(OP_INSERT, 1, 10), (OP_INSERT, 2, 20)]),
             barrier(2, 1)]
        r = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(R_SCHEMA, [(OP_INSERT, 1, 100)]),
             barrier(2, 1)]
        _, out = await run_sorted(l, r, join_type="left",
                                  state_tables=_durable_tables(store, 50))
        return out
    out1 = asyncio.run(run1())
    store.sync(2)
    assert _accumulate(out1) == Counter({(1, 10, 1, 100): 1,
                                         (2, 20, None, None): 1})

    async def run2():
        l2 = [barrier(3, 2, BarrierKind.INITIAL), barrier(4, 3)]
        r2 = [barrier(3, 2, BarrierKind.INITIAL),
              chunk(R_SCHEMA, [(OP_INSERT, 2, 200)]),
              barrier(4, 3)]
        _, out = await run_sorted(l2, r2, join_type="left",
                                  state_tables=_durable_tables(store, 50))
        return out
    out2 = asyncio.run(run2())
    # net effect of the new match: -NULL row, +match row
    assert _accumulate(out2) == Counter({(2, 20, None, None): -1,
                                         (2, 20, 2, 200): 1})


@key_dtypes
async def test_sorted_state_cleaning_durable(kd):
    """Rows below the per-side cleaning watermark are evicted from device
    AND durable state (the diff writes their deletes). Integer keys clean
    on the key column, as a windowed join does; float keys on the event
    time beside it, as a plan would ask (no watermark is a float)."""
    from risingwave_tpu.state import MemoryStateStore
    store = MemoryStateStore()
    ls, rs = schemas(kd)
    cc, wm = (0, 5) if kd == "int64" else (1, 15)
    l = [barrier(1, 0, BarrierKind.INITIAL),
         chunk(ls, keyed(kd, [(OP_INSERT, 1, 10), (OP_INSERT, 9, 20)])),
         barrier(2, 1),
         Watermark(cc, DataType.INT64, wm),
         barrier(3, 2)]
    r = [barrier(1, 0, BarrierKind.INITIAL), barrier(2, 1),
         Watermark(cc, DataType.INT64, wm),
         barrier(3, 2)]
    join, _ = await run_sorted(
        l, r, kd, clean_watermark_cols=(cc, cc),
        state_tables=_durable_tables(store, 60, kd))
    store.sync(3)
    lt, _ = _durable_tables(store, 60, kd)
    assert [r for _, r in lt.iter_all()] == [(K(kd, 9), 20)]
    assert int(join.sides[0].n) == 1


def test_sorted_persist_recover_randomized():
    """Random two-sided churn, crash at a random barrier, recover, more
    churn: final accumulated changelog (run1 pre-crash committed prefix is
    replayed from scratch semantics) — instead compare post-recovery
    behavior to a fresh join fed the LIVE state + the post-crash script."""
    rng = np.random.default_rng(11)
    from risingwave_tpu.state import MemoryStateStore
    store = MemoryStateStore()
    live = [dict(), dict()]
    next_pk = [0, 1_000_000]

    def rand_rows(side, n):
        rows = []
        for _ in range(n):
            if live[side] and rng.random() < 0.3:
                pk = int(rng.choice(list(live[side].keys())))
                rows.append((OP_DELETE, live[side].pop(pk), pk))
            else:
                k = int(rng.integers(0, 8))
                pk = next_pk[side]
                next_pk[side] += 1
                live[side][pk] = k
                rows.append((OP_INSERT, k, pk))
        return rows

    l1 = [barrier(1, 0, BarrierKind.INITIAL)]
    r1 = [barrier(1, 0, BarrierKind.INITIAL)]
    for ep in range(2, 6):
        l1 += [chunk(L_SCHEMA, rand_rows(0, 10), cap=16), barrier(ep, ep - 1)]
        r1 += [chunk(R_SCHEMA, rand_rows(1, 10), cap=16), barrier(ep, ep - 1)]

    async def run1():
        await run_sorted(l1, r1, state_tables=_durable_tables(store, 70),
                         capacity=128, match_factor=16)
    asyncio.run(run1())
    store.sync(5)
    live_at_crash = [dict(live[0]), dict(live[1])]

    l2 = [barrier(6, 5, BarrierKind.INITIAL)]
    r2 = [barrier(6, 5, BarrierKind.INITIAL)]
    for ep in range(7, 10):
        l2 += [chunk(L_SCHEMA, rand_rows(0, 10), cap=16), barrier(ep, ep - 1)]
        r2 += [chunk(R_SCHEMA, rand_rows(1, 10), cap=16), barrier(ep, ep - 1)]

    async def run2():
        _, out = await run_sorted(l2, r2,
                                  state_tables=_durable_tables(store, 70),
                                  capacity=128, match_factor=16)
        return out
    out2 = asyncio.run(run2())

    # golden: join-of-final-live minus join-of-live-at-crash
    def inner(state):
        c = Counter()
        for lpk, lk in state[0].items():
            for rpk, rk in state[1].items():
                if lk == rk:
                    c[(lk, lpk, rk, rpk)] += 1
        return c
    want = inner(live)
    want.subtract(inner(live_at_crash))
    got = Counter()
    for m in out2:
        if isinstance(m, StreamChunk):
            for op, vals in m.to_rows():
                sign = 1 if op in (OP_INSERT, OP_UPDATE_INSERT) else -1
                got[vals] += sign
    assert ({k: v for k, v in got.items() if v}
            == {k: v for k, v in want.items() if v})


# ------------------------------------------------ the diff by provenance lane
# Each case: constructor options, the share of a chunk's rows that retract a
# live row, and what else the run goes through.
DIFF_CASES = {
    "append_only": dict(kw=dict(append_only=(True, True)), p_delete=0.0),
    "retracting": dict(kw={}, p_delete=0.35),
    "left_outer": dict(kw=dict(join_type="left"), p_delete=0.35),
    "full_outer": dict(kw=dict(join_type="full"), p_delete=0.35),
    # keys are event times; the right side sits out every other interval,
    # so both the apply's inline eviction and the barrier's run
    "watermark_eviction": dict(kw=dict(clean_watermark_cols=(0, 0)),
                               p_delete=0.2, watermarks=True),
    "maybe_grow": dict(kw=dict(capacity=16), p_delete=0.1),
    # append-only: a spilled row that is reloaded and retracted within one
    # interval stays in the durable table (a fault older than the lane; the
    # content diff has it too: PERF.md, program faults not repaired)
    "spill_repoint": dict(kw=dict(capacity=64), p_delete=0.0, spill=True),
    "recover": dict(kw={}, p_delete=0.3, restart_after=4),
}


def _persist_counters(join):
    """[deletes, inserts] `join` has written so far, both sides together."""
    label = join.mem_name or join.identity
    return np.asarray([sum(
        GLOBAL_METRICS.counter(JOIN_PERSIST_ROWS, executor=label, side=sd,
                               op=op).value for sd in ("left", "right"))
        for op in ("delete", "insert")])


@pytest.mark.parametrize("case", list(DIFF_CASES))
def test_lane_diff_equals_snapshot_diff(case):
    """Random churn over several barriers: every diff the persist makes
    equals the content diff of the same two states (the old sort-and-search
    program, kept under tests/), a diff right after a barrier — the first
    after recover() too — holds 0 rows, and the durable tables end equal to
    the device state."""
    from _snapshot_diff_reference import check_diffs_against_reference
    from risingwave_tpu.memory import MemoryManager
    from risingwave_tpu.state import MemoryStateStore
    cfg = DIFF_CASES[case]
    rng = np.random.default_rng(sorted(DIFF_CASES).index(case))
    store = MemoryStateStore()
    live = [dict(), dict()]          # pk -> key
    next_pk = [0, 1_000_000]
    n_barriers = 8

    def rand_rows(side, ep):
        rows = []
        for _ in range(int(rng.integers(6, 16))):
            if live[side] and rng.random() < cfg["p_delete"]:
                pk = int(rng.choice(list(live[side])))
                rows.append((OP_DELETE, live[side].pop(pk), pk))
            else:
                k = (ep * 10 + int(rng.integers(0, 10))
                     if cfg.get("watermarks") else int(rng.integers(0, 8)))
                pk = next_pk[side]
                next_pk[side] += 1
                live[side][pk] = k
                rows.append((OP_INSERT, k, pk))
        return rows

    def script(first, last, kind):
        msgs = [[barrier(first, first - 1, kind)],
                [barrier(first, first - 1, kind)]]
        for ep in range(first + 1, last + 1):
            for side, sch in ((0, L_SCHEMA), (1, R_SCHEMA)):
                if not (cfg.get("watermarks") and side == 1 and ep % 2):
                    msgs[side].append(chunk(sch, rand_rows(side, ep)))
                if cfg.get("watermarks"):
                    wm = (ep - 3) * 10
                    msgs[side].append(Watermark(0, DataType.INT64, wm))
                    for pk in [p for p, k in live[side].items() if k < wm]:
                        del live[side][pk]
                msgs[side].append(barrier(ep, ep - 1))
        return msgs

    async def run(msgs):
        kw = dict(capacity=128, match_factor=16,
                  state_tables=_durable_tables(store, 90))
        kw.update(cfg["kw"])
        join = SortedJoinExecutor(
            ScriptSource(L_SCHEMA, msgs[0]), ScriptSource(R_SCHEMA, msgs[1]),
            left_key_indices=[0], right_key_indices=[0],
            left_pk_indices=[1], right_pk_indices=[1], **kw)
        mgr = None
        if cfg.get("spill"):
            mgr = MemoryManager()
            mgr.register("join", join)
            mgr.configure(budget_bytes=1)
        seen = check_diffs_against_reference(join)
        written = _persist_counters(join)
        async for m in join.execute():
            if isinstance(m, Barrier):
                if mgr is not None:
                    mgr.on_barrier(m.epoch.curr)
                for s in (0, 1):
                    join._diff(join.sides[s], join._snap[s])
                    assert seen.pop() == (0, 0), (case, m.epoch.curr, s)
        # the two series the mechanism brings: what the flushes wrote, and
        # (from the last watchdog fetch) what the pools hold
        written = _persist_counters(join) - written
        assert written.tolist() == [sum(nd for nd, _ in seen),
                                    sum(ni for _, ni in seen)]
        label = join.mem_name or join.identity
        if mgr is None:
            assert [GLOBAL_METRICS.gauge(JOIN_LIVE_ROWS, executor=label,
                                         side=sd).value
                    for sd in ("left", "right")] == [
                int(join.sides[0].n), int(join.sides[1].n)]
        else:
            mgr.unregister(label)
            assert not [k for k in GLOBAL_METRICS.labelled_series(
                JOIN_LIVE_ROWS) if ("executor", label) in k[1]]
        return join, seen

    cut = cfg.get("restart_after", n_barriers)
    join, seen = asyncio.run(run(script(1, 1 + cut, BarrierKind.INITIAL)))
    if cut < n_barriers:
        store.sync(1 + cut)
        join, seen2 = asyncio.run(run(
            script(2 + cut, 2 + n_barriers, BarrierKind.INITIAL)))
        seen += seen2
    store.sync(2 + n_barriers)

    assert sum(ni for _, ni in seen) > 40, seen
    if cfg["p_delete"] or cfg.get("watermarks"):
        assert sum(nd for nd, _ in seen) > 5, seen
    if case == "maybe_grow":
        assert join.rebuilds >= 1
    if case == "spill_repoint":
        assert join.mem_reload_count > 0 or join.mem_spilled_rows > 0
    for s, table in enumerate(_durable_tables(store, 90)):
        st = join.sides[s]
        held = Counter(zip(*(np.asarray(c)[:int(st.n)].tolist()
                             for c in st.cols)))
        for rows in join._spill[s]._d.values():
            held.update(vals for vals, _ in rows)
        # (a side that took a chunk evicts at its NEXT apply: rows under
        # the last watermark may still be held, and are then durable too)
        wm_last = (n_barriers - 2) * 10 if cfg.get("watermarks") else -1
        assert (Counter({r: c for r, c in held.items() if r[0] >= wm_last})
                == Counter((k, pk) for pk, k in live[s].items()))
        assert Counter(r for _, r in table.iter_all()) == held, (case, s)


def test_lane_diff_row_that_leaves_and_returns_is_a_pair():
    """The one difference from the content diff: a row deleted and
    re-inserted unchanged within one interval is written as delete +
    insert (deletes first), where the content diff wrote nothing; the
    table ends the same."""
    from _snapshot_diff_reference import check_diffs_against_reference
    from risingwave_tpu.state import MemoryStateStore
    store = MemoryStateStore()

    async def go():
        l = [barrier(1, 0, BarrierKind.INITIAL),
             chunk(L_SCHEMA, [(OP_INSERT, 1, 10), (OP_INSERT, 2, 20)]),
             barrier(2, 1),
             chunk(L_SCHEMA, [(OP_DELETE, 1, 10)]),
             chunk(L_SCHEMA, [(OP_INSERT, 1, 10)]),
             barrier(3, 2)]
        r = [barrier(1, 0, BarrierKind.INITIAL), barrier(2, 1),
             barrier(3, 2)]
        join = SortedJoinExecutor(
            ScriptSource(L_SCHEMA, l), ScriptSource(R_SCHEMA, r),
            left_key_indices=[0], right_key_indices=[0],
            left_pk_indices=[1], right_pk_indices=[1], capacity=64,
            state_tables=_durable_tables(store, 95))
        seen = check_diffs_against_reference(join, rows_return=True)
        async for _ in join.execute():
            pass
        return seen
    assert asyncio.run(go()) == [(0, 2), (1, 1)]
    store.sync(3)
    lt, _ = _durable_tables(store, 95)
    assert sorted(r for _, r in lt.iter_all()) == [(1, 10), (2, 20)]


def _long_index_ops(text):
    """Of a lowered program's text: the lengths of every scatter's updates,
    every gather's indices and every sort's operands, by op."""
    import re
    found = {"scatter": [], "gather": [], "sort": []}
    for op, types in re.findall(
            r'"stablehlo\.(scatter|gather|sort)"\(.*?\}[>)] : \(([^)]*)\) ->',
            text, flags=re.DOTALL):
        tensors = re.findall(r"tensor<(\d+)(?:x\d+)*x\w+>", types)
        # scatter: (operand.., indices, updates..); gather: (operand,
        # indices); sort: (operands..): the last is as long as the index
        found[op].append(int(tensors[-1]))
    return found


@functools.lru_cache(maxsize=None)
def _lowered_join_programs(C, N, factor):
    """{name: stablehlo text} of the join's capacity-priced programs at a
    capacity C no other length of theirs equals."""
    import jax
    import jax.numpy as jnp
    from risingwave_tpu.stream.sorted_join import (NO_WATERMARK,
                                                   _empty_sorted_side)
    side = _empty_sorted_side(C, (jnp.int64,) * 2)
    c = chunk(L_SCHEMA, [(OP_INSERT, 1, 1)], cap=N)
    texts = {}
    for append_only in (True, False):
        sj = SortedJoinExecutor(
            ScriptSource(L_SCHEMA, []), ScriptSource(R_SCHEMA, []),
            left_key_indices=[0], right_key_indices=[0],
            left_pk_indices=[1], right_pk_indices=[1], capacity=C,
            match_factor=factor, append_only=(append_only, append_only),
            clean_watermark_cols=(None, None) if append_only else (1, 1))
        name = "apply_append_only" if append_only else "apply_retracting"
        texts[name] = jax.jit(sj._apply_impl, static_argnames=("side",)).lower(
            side, side, jnp.zeros(3, jnp.int32), c, jnp.int64(NO_WATERMARK),
            side=0).as_text()
    texts["evict"] = jax.jit(sj._evict_impl, static_argnames=("side",)).lower(
        side, jnp.int64(0), jnp.int64(-1), side=0).as_text()
    texts["diff"] = jax.jit(SortedJoinExecutor._diff_impl).lower(
        side, side).as_text()
    return texts


def test_lane_diff_program_has_no_sort_and_no_loop():
    """A count on the CPU, of the program as jax lowers it (before any
    backend rewrites it): the diff is elementwise passes, prefix sums,
    scatters and gathers, whatever the capacity. (Its scatters and gathers
    ARE capacity-long: the one join program PR 35 left so, see
    `test_join_programs_index_no_pool_slot`.)"""
    C = 1 << 12
    text = _lowered_join_programs(C, 16, 32)["diff"]
    found = _long_index_ops(text)
    assert C in found["scatter"] and C in found["gather"]
    assert text.count("stablehlo.sort") == 0
    assert text.count("stablehlo.while") == 0


@pytest.mark.parametrize("program", ["apply_append_only", "apply_retracting",
                                     "evict"])
def test_join_programs_index_no_pool_slot(program):
    """A count on the CPU, of the per-chunk programs as jax lowers them:
    what moves a whole pool column moves it by log-step shifts and selects
    (`ops/monotone_move.py`) — no scatter whose updates, no gather whose
    indices and no sort whose operand are CAPACITY-long. What is left of
    those ops is chunk-long or match-buffer-long. (The barrier's lane diff
    is not among them yet: PERF.md §6, PR 35.)"""
    N, C, factor = 16, 1 << 12, 32           # M = 512: three distinct lengths
    text = _lowered_join_programs(C, N, factor)[program]
    found = _long_index_ops(text)
    for op, lengths in found.items():
        assert C not in lengths, (op, lengths)
    if program.startswith("apply"):
        # the new rows' scatters, the match buffer's gathers, the one sort
        # of the chunk's hashes: the parser sees them
        assert N in found["scatter"] and N * factor in found["gather"]
        assert set(found["sort"]) == {N}
    assert f"tensor<{C}xi64>" in text and "stablehlo.dynamic_slice" in text


@pytest.mark.parametrize("append_only", [True, False],
                         ids=["append_only_side", "retracting_side"])
def test_apply_program_searches_only_with_chunk_many_queries(append_only):
    """A count on the CPU, of the apply as jax lowers it: every binary
    search left in it (a `stablehlo.while` of `searchsorted`) asks
    CHUNK-many questions — the probe's lo / hi, a retraction's dlo / dhi,
    the merge's one search of the new hashes. The ranks that have a
    question per POOL slot (`new_lt`) or per MATCH-BUFFER slot (`src`,
    `dsrc`) are a histogram and a prefix sum, so no SEARCH carries a
    tensor of either length. The only other loops are the moves of the
    pool's rows (PR 35): one loop over a move's table of steps, whose body
    is one stage of shifts and selects."""
    import re
    N, C, factor = 16, 1 << 12, 32           # M = 512: three distinct lengths
    name = "apply_append_only" if append_only else "apply_retracting"
    text = _lowered_join_programs(C, N, factor)[name]
    loops = re.findall(r"stablehlo\.while\(([^\n]*)", text)
    # a move carries the pool-wide mask of the stage; a search does not
    moves = [sig for sig in loops if f"tensor<{C}xi1>" in sig]
    searches = [sig for sig in loops if f"tensor<{C}xi1>" not in sig]
    # jax lowers `searchsorted` once per (shapes, side): the text holds the
    # 'left' body and the 'right' body, both of chunk-many queries into
    # the pool
    assert len(searches) == 2, loops
    for sig in searches:
        # the bounds a search narrows are its i32 tensors: one per query
        assert set(re.findall(r"tensor<(\d+)xi32>", sig)) == {str(N)}, sig
    # kept rows and new rows `expand`; a side with dead rows `compact`s first
    assert len(moves) == (2 if append_only else 3), loops
    for sig in moves:
        # pool-wide lanes and the table of steps, nothing match-buffer-long
        assert f"tensor<{N * factor}x" not in sig, sig
        assert set(re.findall(r"tensor<(\d+)xi32>", sig)) - {str(C)} <= {
            str((C - 1).bit_length()), str(N.bit_length())}, sig
    assert f"tensor<{C}xi32>" in text and f"tensor<{N * factor}xi32>" in text
