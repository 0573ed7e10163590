"""Multi-chunk barrier intervals: coalescing + batched scan apply.

Regression contract for the O(1)-dispatches-per-interval work:
(a) results through the coalesced/batched paths are IDENTICAL to the
    un-coalesced per-chunk path (hash_agg), and
(b) compile counts stay bounded — shape bucketing means a run with
    varying chunk cardinalities and batch lengths stops recompiling
    after warmup.
"""

import asyncio

import numpy as np

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import (
    ChunkCoalescer, OP_INSERT, OP_DELETE, StreamChunk,
)
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.expr.agg import agg_sum, count_star
from risingwave_tpu.stream import Barrier, BarrierKind, HashAggExecutor
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.utils.metrics import GLOBAL_METRICS

SCHEMA = schema(("k", DataType.INT64), ("v", DataType.INT64))


class ScriptSource(Executor):
    def __init__(self, sch, messages):
        self.schema = sch
        self.messages = messages
        self.identity = "ScriptSource"
        self.pk_indices = ()

    async def execute(self):
        for m in self.messages:
            yield m
            await asyncio.sleep(0)


def chunk(rows, cap=16):
    ops = np.asarray([r[0] for r in rows], dtype=np.int8)
    ks = np.asarray([r[1] for r in rows], dtype=np.int64)
    vs = np.asarray([r[2] for r in rows], dtype=np.int64)
    return StreamChunk.from_numpy(SCHEMA, [ks, vs], ops=ops, capacity=cap)


def barrier(curr, kind=BarrierKind.CHECKPOINT):
    return Barrier(EpochPair(curr, curr - 1), kind)


def _interval_chunks(epoch, n_chunks, cap=16):
    """Deterministic pseudo-random insert rows, varying cardinality."""
    rng = np.random.RandomState(1000 + epoch)
    out = []
    for i in range(n_chunks):
        n = int(rng.randint(1, cap))
        rows = [(OP_INSERT, int(rng.randint(0, 7)), int(rng.randint(0, 100)))
                for _ in range(n)]
        out.append(chunk(rows, cap=cap))
    return out


def _script(n_intervals, n_chunks, cap=16):
    msgs = [barrier(1, BarrierKind.INITIAL)]
    for e in range(2, 2 + n_intervals):
        msgs.extend(_interval_chunks(e, n_chunks, cap))
        msgs.append(barrier(e))
    return msgs


async def _collect_rows(executor):
    rows = []
    async for msg in executor.execute():
        if isinstance(msg, StreamChunk):
            rows.extend(msg.to_rows())
    return rows


# ------------------------------------------------------------- hash_agg

async def _run_agg(batching: bool, coalesce: int = 0):
    msgs = _script(n_intervals=4, n_chunks=6)
    if coalesce:
        co = ChunkCoalescer(coalesce)
        packed = []
        for m in msgs:
            if isinstance(m, StreamChunk):
                packed.extend(co.push(m))
            else:
                packed.extend(co.flush())
                packed.append(m)
        msgs = packed
    src = ScriptSource(SCHEMA, msgs)
    agg = HashAggExecutor(src, [0], [count_star(), agg_sum(1)], capacity=64)
    agg._use_chunk_batching = batching
    return await _collect_rows(agg)


async def test_agg_batched_equals_per_chunk():
    base = await _run_agg(batching=False)
    batched = await _run_agg(batching=True)
    assert batched == base


async def test_agg_coalesced_equals_per_chunk():
    # coalescing merges chunks, which changes batch composition and with
    # it the two-choice slot assignment — groups emit at the barrier in a
    # different SLOT order, but the changelog content must be identical
    # as a set (flush rows are independent per group)
    base = await _run_agg(batching=False)
    coalesced = await _run_agg(batching=False, coalesce=128)
    both = await _run_agg(batching=True, coalesce=128)
    assert sorted(coalesced) == sorted(base)
    assert sorted(both) == sorted(base)


# ------------------------------------------- compile-count boundedness

async def test_compile_count_bounded_after_warmup():
    """Varying cardinalities + batch lengths must not retrace: after the
    warmup pass ONE executor's program cache covers every bucketed shape
    (jit caches are per-program, so the run must reuse the executor)."""
    def compiles():
        snap = GLOBAL_METRICS.snapshot().get("jit_compile_count", [])
        return sum(e["value"] for e in snap if not e["labels"])

    def script(intervals, seed_base):
        msgs = [barrier(1, BarrierKind.INITIAL)]
        for e in range(2, 2 + intervals):
            msgs.extend(_interval_chunks(seed_base + e, 1 + (e % 6)))
            msgs.append(barrier(e))
        return msgs

    agg = HashAggExecutor(ScriptSource(SCHEMA, script(6, 0)), [0],
                          [count_star(), agg_sum(1)], capacity=64)
    await _collect_rows(agg)       # warmup: traces apply/scan/flush shapes
    c0 = compiles()
    agg.input = ScriptSource(SCHEMA, script(6, 50))
    await _collect_rows(agg)       # same shapes, different data/cardinality
    c1 = compiles()
    assert c1 == c0, f"recompiled after warmup: {c1 - c0} new traces"


# ------------------------------------------------- coalescer unit tests

def test_coalescer_packs_and_preserves_rows():
    co = ChunkCoalescer(64)
    c1 = chunk([(OP_INSERT, 1, 10), (OP_INSERT, 2, 20)], cap=16)
    c2 = chunk([(OP_DELETE, 1, 10)], cap=16)
    c3 = chunk([(OP_INSERT, 3, 30)], cap=8)
    assert co.push(c1) == []
    assert co.push(c2) == []
    assert co.push(c3) == []
    out = co.flush()
    assert len(out) == 1
    merged = out[0]
    # power-of-two bucketed capacity, row order preserved exactly
    assert merged.capacity in (32, 64)
    assert merged.to_rows() == (c1.to_rows() + c2.to_rows() + c3.to_rows())
    assert co.flush() == []


def test_coalescer_respects_max_capacity():
    co = ChunkCoalescer(32)
    big = chunk([(OP_INSERT, 9, 9)], cap=64)
    small = chunk([(OP_INSERT, 1, 1)], cap=16)
    assert co.push(small) == []
    out = co.push(big)          # oversized chunk drains + passes through
    assert [c.capacity for c in out] == [16, 64]
    # two 16s fit under 32; a third forces a drain of the packed pair
    a, b, c = (chunk([(OP_INSERT, i, i)], cap=16) for i in (1, 2, 3))
    assert co.push(a) == []
    assert co.push(b) == []
    out = co.push(c)
    assert len(out) == 1 and out[0].capacity == 32
    assert [x.to_rows() for x in co.flush()] == [c.to_rows()]
