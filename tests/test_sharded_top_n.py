"""ShardedTopNExecutor: the retractable top-N under shard_map on the
8-device virtual CPU mesh, driven with real barriers and compared for
bit-identity against the single-device executor at quiesced offsets —
grouped mode (group-key routing) and global mode (stream-key routing +
candidate all_gather), plus durable crash/recovery with ingest replay
preload and the overflow fail-stop."""

import asyncio
from collections import Counter

import numpy as np
import pytest

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import OP_DELETE, OP_INSERT, StreamChunk
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.parallel import make_mesh
from risingwave_tpu.stream import Barrier, BarrierKind
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.stream.retract_top_n import RetractableTopNExecutor
from risingwave_tpu.stream.sharded_top_n import ShardedTopNExecutor

SCHEMA = schema(("g", DataType.INT64), ("v", DataType.INT64),
                ("pk", DataType.INT64))


class ScriptSource(Executor):
    pk_indices = (2,)

    def __init__(self, msgs):
        self.schema = SCHEMA
        self.msgs = msgs
        self.identity = "ScriptSource"

    async def execute(self):
        for m in self.msgs:
            yield m
            await asyncio.sleep(0)


def chunk(rows, cap=64):
    ops = np.asarray([r[0] for r in rows], dtype=np.int8)
    cols = [np.asarray([r[1 + i] for r in rows], dtype=np.int64)
            for i in range(3)]
    return StreamChunk.from_numpy(SCHEMA, cols, ops=ops, capacity=cap)


def barrier(curr, prev, kind=BarrierKind.CHECKPOINT):
    return Barrier(EpochPair(curr, prev), kind)


async def drive(ex):
    out = []
    async for m in ex.execute():
        out.append(m)
    return out


def mv_apply(out):
    mv = Counter()
    for m in out:
        if isinstance(m, StreamChunk):
            for op, row in m.to_rows():
                if op in (OP_INSERT, 3):
                    mv[row] += 1
                else:
                    mv[row] -= 1
                    if mv[row] == 0:
                        del mv[row]
    return mv


def _script(seed, n_rounds=4, n_groups=12, per_round=48, delete_frac=0.25):
    """INITIAL + rounds of (chunk, barrier): inserts with unique pks and
    valid deletes of previously-inserted rows."""
    rng = np.random.default_rng(seed)
    live = {}
    next_pk = 0
    msgs = [barrier(1, 0, BarrierKind.INITIAL)]
    ep = 2
    for _ in range(n_rounds):
        rows = []
        for _ in range(per_round):
            if live and rng.random() < delete_frac:
                pk = int(rng.choice(list(live)))
                g, v = live.pop(pk)
                rows.append((OP_DELETE, g, v, pk))
            else:
                g = int(rng.integers(0, n_groups))
                v = int(rng.integers(0, 1000))
                live[next_pk] = (g, v)
                rows.append((OP_INSERT, g, v, next_pk))
                next_pk += 1
        msgs.append(chunk(rows))
        msgs.append(barrier(ep, ep - 1))
        ep += 1
    return msgs


@pytest.mark.parametrize("group_keys,desc,rank", [
    ((0,), False, False), ((0,), True, False), ((), False, False),
    ((), True, False),
    # the rank-filter plan's arguments: the rank as an output column (both
    # executors emit a moved rank as a Delete + Insert over a retracting
    # input, so the accumulated MVs agree rank for rank)
    ((0,), True, True)])
async def test_sharded_topn_matches_single_device(group_keys, desc, rank):
    msgs = _script(seed=5 + len(group_keys) + desc)
    mesh = make_mesh(8)
    kw = dict(group_key_indices=group_keys, order_col=1, limit=3,
              descending=desc, pk_indices=(2,), emit_rank=rank)
    sharded = ShardedTopNExecutor(ScriptSource(msgs), mesh=mesh,
                                  capacity=64, **kw)
    got = mv_apply(await drive(sharded))
    # the fused shuffle+apply plane must actually engage
    assert sharded.mesh_shuffle_applies > 0

    plain = RetractableTopNExecutor(ScriptSource(msgs), capacity=512, **kw)
    want = mv_apply(await drive(plain))
    assert got == want and len(got) > 0
    assert all(len(row) == 3 + rank for row in got)


async def test_sharded_global_topn_offset_refill_across_shards():
    """Global mode with an offset: retracting top rows must refill from
    candidates held on OTHER shards (the all_gather re-rank path)."""
    mesh = make_mesh(8)
    ins = [(OP_INSERT, 0, 10 * i, i) for i in range(24)]
    msgs = [barrier(1, 0, BarrierKind.INITIAL), chunk(ins), barrier(2, 1),
            # retract the current best three (v=0,10,20)
            chunk([(OP_DELETE, 0, 0, 0), (OP_DELETE, 0, 10, 1),
                   (OP_DELETE, 0, 20, 2)]),
            barrier(3, 2)]
    kw = dict(group_key_indices=(), order_col=1, limit=4, offset=2,
              pk_indices=(2,))
    got = mv_apply(await drive(ShardedTopNExecutor(
        ScriptSource(msgs), mesh=mesh, capacity=64, **kw)))
    want = mv_apply(await drive(RetractableTopNExecutor(
        ScriptSource(msgs), capacity=256, **kw)))
    # ranks [2, 6) by v asc after the retraction: v=50..80
    assert got == want == Counter({(0, 50 + 10 * i, 5 + i): 1
                                   for i in range(4)})


async def test_sharded_topn_durable_crash_recover_converges():
    """Per-shard durable persist -> crash -> recover (INITIAL barrier
    rebuild partitioned by the same routing) -> more input -> the
    accumulated MV equals a single-device run with no crash."""
    from risingwave_tpu.state import MemoryStateStore, StateTable
    store = MemoryStateStore()

    def table():
        return StateTable(store, 41, SCHEMA, pk_indices=[2])

    all_msgs = _script(seed=9, n_rounds=4)
    # split after the second checkpoint: [INITIAL, c, b2, c, b3 | c, b4, ...]
    cut = 5
    msgs1, tail = all_msgs[:cut], all_msgs[cut:]
    msgs2 = [barrier(3, 2, BarrierKind.INITIAL)] + tail

    mesh = make_mesh(8)
    kw = dict(group_key_indices=(0,), order_col=1, limit=3,
              pk_indices=(2,))
    sh1 = ShardedTopNExecutor(ScriptSource(msgs1), mesh=mesh, capacity=64,
                              state_table=table(), **kw)
    out1 = await drive(sh1)
    store.sync(2)
    del sh1                    # device state dies with the executor

    sh2 = ShardedTopNExecutor(ScriptSource(msgs2), mesh=mesh, capacity=64,
                              state_table=table(), **kw)
    out2 = await drive(sh2)
    got = mv_apply(out1 + out2)

    want = mv_apply(await drive(RetractableTopNExecutor(
        ScriptSource(all_msgs), capacity=512, **kw)))
    assert got == want and len(got) > 0


async def test_sharded_topn_replay_preload_refuses_nothing():
    """scope=mesh recovery path: the uncommitted ingest suffix staged via
    preload_replay applies at the first barrier after the durable
    rebuild, converging with a run that never crashed."""
    from risingwave_tpu.state import MemoryStateStore, StateTable
    store = MemoryStateStore()

    def table():
        return StateTable(store, 42, SCHEMA, pk_indices=[2])

    committed = chunk([(OP_INSERT, 0, 5, 0), (OP_INSERT, 1, 7, 1)])
    uncommitted = chunk([(OP_INSERT, 0, 3, 2), (OP_DELETE, 1, 7, 1)])

    mesh = make_mesh(8)
    kw = dict(group_key_indices=(0,), order_col=1, limit=2,
              pk_indices=(2,))
    msgs1 = [barrier(1, 0, BarrierKind.INITIAL), committed, barrier(2, 1)]
    sh1 = ShardedTopNExecutor(ScriptSource(msgs1), mesh=mesh, capacity=64,
                              state_table=table(), **kw)
    out1 = await drive(sh1)
    store.sync(2)
    # crash after epoch 2 committed; the in-flight chunk was only in the
    # producer's replay log — a scope=mesh recovery preloads it
    del sh1

    msgs2 = [barrier(3, 2, BarrierKind.INITIAL), barrier(4, 3)]
    sh2 = ShardedTopNExecutor(ScriptSource(msgs2), mesh=mesh, capacity=64,
                              state_table=table(), **kw)
    sh2.preload_replay([uncommitted])
    out2 = await drive(sh2)
    got = mv_apply(out1 + out2)

    full = [barrier(1, 0, BarrierKind.INITIAL), committed, barrier(2, 1),
            uncommitted, barrier(3, 2)]
    want = mv_apply(await drive(RetractableTopNExecutor(
        ScriptSource(full), capacity=256, **kw)))
    assert got == want == Counter({(0, 3, 2): 1, (0, 5, 0): 1})


async def test_sharded_topn_overflow_fail_stops():
    """A shard exceeding its per-shard capacity must raise at the
    barrier watchdog fetch, not silently drop rows."""
    mesh = make_mesh(8)
    # 64 rows in ONE group -> one shard needs 64 slots but has 16
    rows = [(OP_INSERT, 7, i, i) for i in range(64)]
    msgs = [barrier(1, 0, BarrierKind.INITIAL), chunk(rows),
            barrier(2, 1)]
    sh = ShardedTopNExecutor(ScriptSource(msgs), mesh=mesh, capacity=16,
                             group_key_indices=(0,), order_col=1, limit=3,
                             pk_indices=(2,))
    with pytest.raises(RuntimeError, match="overflow"):
        await drive(sh)
