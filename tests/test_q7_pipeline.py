"""Nexmark q7 end-to-end: tumble-window max price joined back to bids.

Reference workload: /root/reference/src/tests/simulation/src/nexmark/q7.sql —
  SELECT B.auction, B.price, B.bidder, B.date_time FROM bid B JOIN
    (SELECT MAX(price) maxprice, window_end FROM TUMBLE(bid, 10) GROUP BY
     window_end) Q
  ON B.price = Q.maxprice
     AND B.date_time BETWEEN Q.window_end - 10 AND Q.window_end

This is the first multi-operator graph: one scripted source broadcast to two
branches (raw bids / window-max agg) whose outputs meet in a HashJoin with a
non-equi condition. Exercises BroadcastDispatcher, channels, 2-input barrier
alignment, agg UD/UI retraction flowing through the join, and changelog
correctness vs a golden python model.
"""

import asyncio
from collections import Counter

import numpy as np

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import OP_INSERT, StreamChunk
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.expr import call, col, lit
from risingwave_tpu.expr.agg import agg_max
from risingwave_tpu.stream import (
    Barrier, BarrierKind, BroadcastDispatcher, Channel, ChannelInput,
    HashAggExecutor, SortedJoinExecutor, ProjectExecutor, StopMutation,
)
from risingwave_tpu.stream.executor import Executor

BID = schema(("auction", DataType.INT64), ("bidder", DataType.INT64),
             ("price", DataType.INT64), ("date_time", DataType.TIMESTAMP))

W = 10  # window size (same unit as date_time)


class ScriptSource(Executor):
    def __init__(self, sch, messages):
        self.schema = sch
        self.messages = messages
        self.identity = "ScriptSource"

    async def execute(self):
        for m in self.messages:
            yield m
            await asyncio.sleep(0)


def bid_chunk(rows, cap=16):
    cols = [np.asarray([r[i] for r in rows], dtype=np.int64) for i in range(4)]
    return StreamChunk.from_numpy(BID, cols, capacity=cap)


def barrier(curr, prev, kind=BarrierKind.CHECKPOINT, mutation=None):
    return Barrier(EpochPair(curr, prev), kind, mutation)


def build_q7(source: Executor):
    ch_l, ch_r = Channel(), Channel()
    disp = BroadcastDispatcher([ch_l, ch_r])

    async def pump():
        async for m in source.execute():
            await disp.dispatch(m)

    right_in = ChannelInput(ch_r, BID)
    # TUMBLE: window_end = tumble_end(date_time, W); keep price
    proj = ProjectExecutor(
        right_in,
        [call("tumble_end", col(3, DataType.TIMESTAMP), lit(W)), col(2)],
        names=["window_end", "price"])
    agg = HashAggExecutor(proj, group_key_indices=[0],
                          agg_calls=[agg_max(1, append_only=True)],
                          capacity=64, group_key_names=["window_end"])
    # join: B.price == Q.maxprice AND window_end - W <= date_time <= window_end
    cond = call("and",
                call("greater_than", col(3, DataType.TIMESTAMP),
                     call("subtract", col(4, DataType.TIMESTAMP), lit(W))),
                call("less_than_or_equal", col(3, DataType.TIMESTAMP),
                     col(4, DataType.TIMESTAMP)))
    join = SortedJoinExecutor(
        ChannelInput(ch_l, BID), agg,
        left_key_indices=[2], right_key_indices=[1],
        left_pk_indices=[0, 1, 2, 3], right_pk_indices=[0],
        capacity=256, match_factor=8,
        condition=cond,
        output_indices=[0, 2, 1, 3])   # auction, price, bidder, date_time
    return join, pump


def golden(all_bids):
    """Final q7 content: bids at the max price of their window."""
    by_window = {}
    for a, b, p, t in all_bids:
        we = (t - t % W) + W
        by_window.setdefault(we, []).append((a, b, p, t))
    want = Counter()
    for we, bids in by_window.items():
        mx = max(p for _, _, p, _ in bids)
        for a, b, p, t in bids:
            if p == mx:
                want[(a, p, b, t)] += 1
    return want


def changelog_counter(out):
    c = Counter()
    for m in out:
        if isinstance(m, StreamChunk):
            for op, row in m.to_rows():
                c[row] += 1 if op in (0, 3) else -1
    return +c


async def run_pipeline(msgs):
    src = ScriptSource(BID, msgs)
    join, pump = build_q7(src)
    pump_task = asyncio.create_task(pump())
    out = []
    async for m in join.execute():
        out.append(m)
    await pump_task
    return out


async def test_q7_small():
    bids1 = [(1, 100, 50, 3), (2, 101, 80, 5), (3, 102, 80, 7)]
    bids2 = [(4, 103, 99, 8), (5, 104, 10, 12)]
    msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        bid_chunk(bids1),
        barrier(2, 1),
        bid_chunk(bids2),
        barrier(3, 2),
        barrier(4, 3, mutation=StopMutation(frozenset())),
    ]
    out = await run_pipeline(msgs)
    # window (0,10]: max 99 -> bid 4 only; window (10,20]: max 10 -> bid 5
    assert changelog_counter(out) == golden(bids1 + bids2)


async def test_q7_retraction_across_epochs():
    """A later higher bid in the same window must retract earlier join rows
    (agg UD/UI pair flows through the join as delete+insert)."""
    e1 = [(1, 100, 50, 3)]
    e2 = [(2, 101, 80, 5)]          # new max in same window: retract bid 1
    e3 = [(3, 102, 80, 7)]          # ties max: joins too
    msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        bid_chunk(e1), barrier(2, 1),
        bid_chunk(e2), barrier(3, 2),
        bid_chunk(e3), barrier(4, 3),
        barrier(5, 4, mutation=StopMutation(frozenset())),
    ]
    out = await run_pipeline(msgs)
    assert changelog_counter(out) == golden(e1 + e2 + e3)


async def test_q7_golden_random():
    rng = np.random.default_rng(11)
    msgs = [barrier(1, 0, BarrierKind.INITIAL)]
    all_bids = []
    for epoch in range(2, 8):
        rows = []
        for _ in range(10):
            a = int(rng.integers(0, 5))
            b = int(rng.integers(100, 120))
            p = int(rng.integers(1, 30))
            t = int(rng.integers(0, 40))
            rows.append((a, b, p, t))
        all_bids += rows
        msgs.append(bid_chunk(rows))
        msgs.append(barrier(epoch, epoch - 1))
    msgs.append(barrier(8, 7, mutation=StopMutation(frozenset())))
    out = await run_pipeline(msgs)
    assert changelog_counter(out) == golden(all_bids)
