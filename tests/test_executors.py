"""Dedup / SimpleAgg / StatelessSimpleAgg executor tests.

Golden-model style (reference executor #[cfg(test)] suites): scripted
chunks + barriers in, changelog out, compared against plain-Python models.
"""

import asyncio
from collections import Counter

import numpy as np
import pytest

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, StreamChunk,
)
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.expr.agg import agg_max, agg_sum, count_star
from risingwave_tpu.state import MemoryStateStore, StateTable
from risingwave_tpu.stream import (
    AppendOnlyDedupExecutor, Barrier, BarrierKind,
    SimpleAggExecutor, StatelessSimpleAggExecutor,
)
from risingwave_tpu.stream.executor import Executor

SCHEMA = schema(("k", DataType.INT64), ("v", DataType.INT64))


class ScriptSource(Executor):
    def __init__(self, sch, messages):
        self.schema = sch
        self.messages = messages
        self.identity = "ScriptSource"

    async def execute(self):
        for m in self.messages:
            yield m
            await asyncio.sleep(0)


def chunk(rows, cap=16):
    ops = np.asarray([r[0] for r in rows], dtype=np.int8)
    ks = np.asarray([r[1] for r in rows], dtype=np.int64)
    vs = np.asarray([r[2] for r in rows], dtype=np.int64)
    return StreamChunk.from_numpy(SCHEMA, [ks, vs], ops=ops, capacity=cap)


def barrier(curr, prev, kind=BarrierKind.CHECKPOINT):
    return Barrier(EpochPair(curr, prev), kind)


async def drive(executor):
    out = []
    async for msg in executor.execute():
        out.append(msg)
    return out


def rows_of(out):
    got = []
    for m in out:
        if isinstance(m, StreamChunk):
            for op, row in m.to_rows():
                got.append((op, row))
    return got


# ------------------------------------------------------------------ dedup

async def test_dedup_first_wins():
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 10), (OP_INSERT, 2, 20),
                   (OP_INSERT, 1, 30)]),
            chunk([(OP_INSERT, 2, 40), (OP_INSERT, 3, 50)]),
            barrier(2, 1)]
    dd = AppendOnlyDedupExecutor(ScriptSource(SCHEMA, msgs), [0], capacity=32)
    got = rows_of(await drive(dd))
    assert got == [(OP_INSERT, (1, 10)), (OP_INSERT, (2, 20)),
                   (OP_INSERT, (3, 50))]


async def test_dedup_persist_recover():
    store = MemoryStateStore()

    def make_table():
        return StateTable(store, table_id=7,
                          schema=schema(("k", DataType.INT64)),
                          pk_indices=(0,))

    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 10), (OP_INSERT, 2, 20)]),
            barrier(2, 1)]
    dd = AppendOnlyDedupExecutor(ScriptSource(SCHEMA, msgs), [0],
                                 capacity=32, state_table=make_table())
    await drive(dd)
    store.sync(1)

    # restart: keys 1,2 must be remembered
    msgs2 = [barrier(3, 2, BarrierKind.INITIAL),
             chunk([(OP_INSERT, 1, 99), (OP_INSERT, 4, 40)]),
             barrier(4, 3)]
    dd2 = AppendOnlyDedupExecutor(ScriptSource(SCHEMA, msgs2), [0],
                                  capacity=32, state_table=make_table())
    got = rows_of(await drive(dd2))
    assert got == [(OP_INSERT, (4, 40))]


# -------------------------------------------------------------- simple agg

async def test_stateless_simple_agg_partials():
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 10), (OP_INSERT, 2, 20)]),
            chunk([(OP_INSERT, 3, 5), (OP_DELETE, 3, 5)]),
            barrier(2, 1)]
    agg = StatelessSimpleAggExecutor(
        ScriptSource(SCHEMA, msgs), [count_star(), agg_sum(1)])
    got = rows_of(await drive(agg))
    assert got == [(OP_INSERT, (2, 30)), (OP_INSERT, (0, 0))]


async def test_simple_agg_changelog():
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 10), (OP_INSERT, 2, 20)]),
            barrier(2, 1),
            chunk([(OP_DELETE, 1, 10)]),
            barrier(3, 2),
            barrier(4, 3)]
    agg = SimpleAggExecutor(ScriptSource(SCHEMA, msgs),
                            [count_star(), agg_sum(1)])
    got = rows_of(await drive(agg))
    assert got == [(OP_INSERT, (2, 30)),
                   (OP_UPDATE_DELETE, (2, 30)), (OP_UPDATE_INSERT, (1, 20))]


async def test_simple_agg_persist_recover():
    store = MemoryStateStore()
    def make_table():
        return StateTable(
            store, table_id=9,
            schema=schema(("slot", DataType.INT64), ("c", DataType.INT64),
                          ("s", DataType.INT64), ("rc", DataType.INT64)),
            pk_indices=(0,))

    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 10), (OP_INSERT, 2, 20)]),
            barrier(2, 1)]
    agg = SimpleAggExecutor(ScriptSource(SCHEMA, msgs),
                            [count_star(), agg_sum(1)],
                            state_table=make_table())
    await drive(agg)
    store.sync(1)

    msgs2 = [barrier(3, 2, BarrierKind.INITIAL),
             chunk([(OP_INSERT, 5, 5)]),
             barrier(4, 3)]
    agg2 = SimpleAggExecutor(ScriptSource(SCHEMA, msgs2),
                             [count_star(), agg_sum(1)],
                             state_table=make_table())
    got = rows_of(await drive(agg2))
    # recovered (2, 30) -> (3, 35) as an update, not a fresh Insert
    assert got == [(OP_UPDATE_DELETE, (2, 30)), (OP_UPDATE_INSERT, (3, 35))]
