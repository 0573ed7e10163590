"""ShardedSortedJoinExecutor on the 8-device virtual CPU mesh: identical
changelog (net) and state vs the single-shard SortedJoinExecutor, driven
through the full executor loop with barriers and retractions."""

import asyncio
from collections import Counter

import numpy as np
import pytest

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_INSERT, StreamChunk,
)
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.parallel import make_mesh
from risingwave_tpu.stream import Barrier, BarrierKind
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.stream.sharded_join import ShardedSortedJoinExecutor
from risingwave_tpu.stream.sorted_join import SortedJoinExecutor

L_SCHEMA = schema(("k", DataType.INT64), ("lv", DataType.INT64))
R_SCHEMA = schema(("k", DataType.INT64), ("rv", DataType.INT64))


class ScriptSource(Executor):
    def __init__(self, sch, messages):
        self.schema = sch
        self.messages = messages
        self.identity = "ScriptSource"

    async def execute(self):
        for m in self.messages:
            yield m
            await asyncio.sleep(0)


def chunk(sch, rows, cap=32):
    ops = np.asarray([r[0] for r in rows], dtype=np.int8)
    cols = [np.asarray([r[1 + i] for r in rows], dtype=np.int64)
            for i in range(len(sch))]
    return StreamChunk.from_numpy(sch, cols, ops=ops, capacity=cap)


def barrier(curr, prev, kind=BarrierKind.CHECKPOINT):
    return Barrier(EpochPair(curr, prev), kind)


def net_changelog(out):
    acc = Counter()
    for m in out:
        if isinstance(m, StreamChunk):
            for op, vals in m.to_rows():
                sign = 1 if op in (OP_INSERT, OP_UPDATE_INSERT) else -1
                acc[vals] += sign
    return {k: v for k, v in acc.items() if v}


def _script(seed=3, rounds=10):
    rng = np.random.default_rng(seed)
    live = [dict(), dict()]
    next_pk = [0, 1_000_000]
    msgs = [[barrier(1, 0, BarrierKind.INITIAL)],
            [barrier(1, 0, BarrierKind.INITIAL)]]
    epoch = 2
    for _ in range(rounds):
        for side in (0, 1):
            rows = []
            for _ in range(int(rng.integers(2, 10))):
                if live[side] and rng.random() < 0.3:
                    pk = int(rng.choice(list(live[side].keys())))
                    k = live[side].pop(pk)
                    rows.append((OP_DELETE, k, pk))
                else:
                    k = int(rng.integers(0, 12))
                    pk = next_pk[side]
                    next_pk[side] += 1
                    live[side][pk] = k
                    rows.append((OP_INSERT, k, pk))
            sch = L_SCHEMA if side == 0 else R_SCHEMA
            msgs[side].append(chunk(sch, rows))
        msgs[0].append(barrier(epoch, epoch - 1))
        msgs[1].append(barrier(epoch, epoch - 1))
        epoch += 1
    return msgs


async def _collect(join):
    out = []
    async for m in join.execute():
        out.append(m)
    return out


def test_sharded_matches_single_shard():
    msgs = _script()
    mesh = make_mesh(8)

    async def go():
        sj = ShardedSortedJoinExecutor(
            ScriptSource(L_SCHEMA, list(msgs[0])),
            ScriptSource(R_SCHEMA, list(msgs[1])), mesh,
            left_key_indices=[0], right_key_indices=[0],
            left_pk_indices=[1], right_pk_indices=[1],
            capacity=128, match_factor=8)
        ref = SortedJoinExecutor(
            ScriptSource(L_SCHEMA, list(msgs[0])),
            ScriptSource(R_SCHEMA, list(msgs[1])),
            left_key_indices=[0], right_key_indices=[0],
            left_pk_indices=[1], right_pk_indices=[1],
            capacity=512, match_factor=8)
        out_s = await _collect(sj)
        out_r = await _collect(ref)
        assert net_changelog(out_s) == net_changelog(out_r)
        assert net_changelog(out_s)          # non-trivial workload
        # per-shard row counts sum to the reference's state size
        n_total = sum(int(np.asarray(sj._n_dev[s]).sum()) for s in (0, 1))
        n_ref = sum(int(np.asarray(ref.sides[s].n)) for s in (0, 1))
        assert n_total == n_ref
    asyncio.run(go())


def test_sharded_outer_join():
    msgs = _script(seed=9, rounds=6)
    mesh = make_mesh(8)

    async def go():
        sj = ShardedSortedJoinExecutor(
            ScriptSource(L_SCHEMA, list(msgs[0])),
            ScriptSource(R_SCHEMA, list(msgs[1])), mesh,
            left_key_indices=[0], right_key_indices=[0],
            left_pk_indices=[1], right_pk_indices=[1],
            capacity=128, match_factor=8, join_type="left")
        ref = SortedJoinExecutor(
            ScriptSource(L_SCHEMA, list(msgs[0])),
            ScriptSource(R_SCHEMA, list(msgs[1])),
            left_key_indices=[0], right_key_indices=[0],
            left_pk_indices=[1], right_pk_indices=[1],
            capacity=512, match_factor=8, join_type="left")
        out_s = await _collect(sj)
        out_r = await _collect(ref)

        def net_with_nulls(out):
            acc = Counter()
            for m in out:
                if not isinstance(m, StreamChunk):
                    continue
                vis = np.asarray(m.vis)
                ops = np.asarray(m.ops)[vis]
                data = [np.asarray(c.data)[vis] for c in m.columns]
                valid = [np.asarray(c.valid_mask())[vis]
                         for c in m.columns]
                for r in range(len(ops)):
                    row = tuple(int(d[r]) if v[r] else None
                                for d, v in zip(data, valid))
                    acc[row] += 1 if ops[r] in (OP_INSERT,
                                                OP_UPDATE_INSERT) else -1
            return {k: v for k, v in acc.items() if v}
        assert net_with_nulls(out_s) == net_with_nulls(out_r)
    asyncio.run(go())


@pytest.mark.parametrize("case", ["retracting", "left_outer", "recover"])
def test_sharded_lane_diff_equals_snapshot_diff_per_shard(case):
    """Four virtual devices, durable: every shard's diff (the lane holds
    shard-LOCAL positions) equals the content diff of the same two slices,
    a diff right after a barrier holds 0 rows on every shard (after the
    mesh recover() too), and the tables end equal to the shards' rows."""
    from _snapshot_diff_reference import check_diffs_against_reference
    from risingwave_tpu.state import MemoryStateStore, StateTable
    store = MemoryStateStore()
    msgs = _script(seed=5, rounds=8)
    mesh = make_mesh(4)

    def tables():
        return (StateTable(store, 80, L_SCHEMA, pk_indices=[1]),
                StateTable(store, 81, R_SCHEMA, pk_indices=[1]))

    async def run(lm, rm):
        sj = ShardedSortedJoinExecutor(
            ScriptSource(L_SCHEMA, lm), ScriptSource(R_SCHEMA, rm), mesh,
            left_key_indices=[0], right_key_indices=[0],
            left_pk_indices=[1], right_pk_indices=[1],
            capacity=128, match_factor=8, state_tables=tables(),
            join_type="left" if case == "left_outer" else "inner")
        seen = check_diffs_against_reference(sj)
        async for m in sj.execute():
            if isinstance(m, Barrier):
                for s in (0, 1):
                    for sh in range(sj.n_shards):
                        sj._diff(sj._shard_slice(sj.sides[s], sh, s),
                                 sj._shard_slice(sj._snap[s], sh, s))
                        assert seen.pop() == (0, 0), (m.epoch.curr, s, sh)
        return sj, seen

    if case == "recover":
        # rounds 1-4, a restart over the same store, rounds 5-8
        cut = 1 + 4 * 2                      # Initial + 4 x (chunk, barrier)
        asyncio.run(run(msgs[0][:cut], msgs[1][:cut]))
        store.sync(5)
        rest = [[barrier(6, 5, BarrierKind.INITIAL)] + m[cut:] for m in msgs]
        for m in rest:                       # epochs 6.. -> 7..
            for i, b in enumerate(m[1:], 1):
                if isinstance(b, Barrier):
                    m[i] = barrier(b.epoch.curr + 1, b.epoch.prev + 1)
        sj, seen = asyncio.run(run(*rest))
        last = 10
    else:
        sj, seen = asyncio.run(run(list(msgs[0]), list(msgs[1])))
        last = 9
    store.sync(last)
    assert sum(nd for nd, _ in seen) > 3 and sum(ni for _, ni in seen) > 10
    # both counts per shard, so several shards took part
    assert len([1 for c in seen if c != (0, 0)]) > sj.n_shards
    for s, table in enumerate(tables()):
        held = Counter()
        for sh in range(sj.n_shards):
            st = sj._shard_slice(sj.sides[s], sh, s)
            held.update(zip(*(np.asarray(c)[:int(st.n)].tolist()
                              for c in st.cols)))
        assert Counter(r for _, r in table.iter_all()) == held, s
        assert sum(held.values()) > 0
