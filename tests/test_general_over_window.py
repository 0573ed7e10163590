"""GeneralOverWindowExecutor vs a per-row numpy oracle: retracting
inputs, multi-column ORDER BY, bounded + unbounded frames.

Reference semantics: src/stream/src/executor/over_window/general.rs —
the accumulated changelog must equal the window functions evaluated over
the final live row set (and intermediate emissions must be consistent
diffs, which the accumulation checks implicitly).
"""

import asyncio
from collections import Counter

import numpy as np

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, StreamChunk,
)
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.stream import (
    Barrier, BarrierKind, GeneralOverWindowExecutor, WindowSpec,
)
from risingwave_tpu.stream.executor import Executor

SCH = schema(("pk", DataType.INT64), ("p", DataType.INT64),
             ("o", DataType.INT64), ("v", DataType.INT64))


class Script(Executor):
    def __init__(self, sch, messages):
        self.schema = sch
        self.messages = messages
        self.identity = "Script"
        self.pk_indices = (0,)

    async def execute(self):
        for m in self.messages:
            yield m
            await asyncio.sleep(0)


def chunk(rows, cap=16):
    ops = np.asarray([r[0] for r in rows], dtype=np.int8)
    cols = [np.asarray([r[1 + i] for r in rows], dtype=np.int64)
            for i in range(len(SCH))]
    return StreamChunk.from_numpy(SCH, cols, ops=ops, capacity=cap)


def barrier(curr, prev, kind=BarrierKind.CHECKPOINT):
    return Barrier(EpochPair(curr, prev), kind)


def accumulate(out):
    acc = Counter()
    for m in out:
        if not isinstance(m, StreamChunk):
            continue
        vis = np.asarray(m.vis)
        ops = np.asarray(m.ops)[vis]
        data = [np.asarray(c.data)[vis] for c in m.columns]
        valid = [np.asarray(c.valid_mask())[vis] for c in m.columns]
        for r in range(len(ops)):
            row = tuple(
                (float(d[r]) if np.issubdtype(d.dtype, np.floating)
                 else int(d[r])) if v[r] else None
                for d, v in zip(data, valid))
            sign = 1 if ops[r] in (OP_INSERT, OP_UPDATE_INSERT) else -1
            acc[row] += sign
    return Counter({k: v for k, v in acc.items() if v})


def oracle(live_rows, windows, order_specs):
    """live_rows: list of (pk, p, o, v) -> Counter of output rows."""
    out = Counter()
    parts = {}
    for row in live_rows:
        parts.setdefault(row[1], []).append(row)
    for p, rows in parts.items():
        def sort_key(r):
            return tuple((-r[c] if d else r[c]) for c, d in order_specs) \
                + (r[0],)
        rows = sorted(rows, key=sort_key)
        for j, r in enumerate(rows):
            vals = []
            for w in windows:
                if w.kind == "row_number":
                    vals.append(j + 1)
                elif w.kind == "rank":
                    k = j
                    while k > 0 and all(
                            rows[k - 1][c] == r[c]
                            for c, _ in order_specs):
                        k -= 1
                    vals.append(k + 1)
                else:
                    lo = 0 if w.preceding is None else max(
                        0, j - w.preceding)
                    frame = [x[w.arg] for x in rows[lo:j + 1]]
                    if w.kind == "sum":
                        vals.append(sum(frame))
                    elif w.kind == "count":
                        vals.append(len(frame))
                    else:
                        vals.append(sum(frame) / len(frame))
            out[tuple(r) + tuple(vals)] += 1
    return out


async def run(messages, windows, order_specs=((2, False),),
              partition_by=(1,), **kw):
    ex = GeneralOverWindowExecutor(
        Script(SCH, messages), partition_by, order_specs, windows,
        capacity=64, **kw)
    out = []
    async for m in ex.execute():
        out.append(m)
    return ex, out


def test_row_number_and_running_sum_with_retractions():
    windows = (WindowSpec("row_number"), WindowSpec("sum", arg=3),
               WindowSpec("count", arg=3))
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 10, 5, 100),
                   (OP_INSERT, 2, 10, 3, 200),
                   (OP_INSERT, 3, 20, 1, 50)]),
            barrier(2, 1),
            # retract the o=3 row: the o=5 row's row_number/sum shift
            chunk([(OP_DELETE, 2, 10, 3, 200),
                   (OP_INSERT, 4, 10, 4, 400)]),
            barrier(3, 2)]
    _, out = asyncio.run(run(msgs, windows))
    live = [(1, 10, 5, 100), (3, 20, 1, 50), (4, 10, 4, 400)]
    assert accumulate(out) == oracle(live, windows, ((2, False),))


def test_append_only_arrival_order_over_two_chunks():
    """Append-only rows ordered by arrival, two chunks inside ONE interval:
    row_number and the running sum / count of each partition carry over
    from the first chunk's rows to the second's."""
    windows = (WindowSpec("row_number"), WindowSpec("sum", arg=3),
               WindowSpec("count", arg=3))
    arrivals = [(1, 10), (2, 5), (1, 3), (1, 7), (2, 8), (1, 1)]  # (p, v)
    live = [(i, p, i, v) for i, (p, v) in enumerate(arrivals)]
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT,) + r for r in live[:4]]),
            chunk([(OP_INSERT,) + r for r in live[4:]]),
            barrier(2, 1)]
    _, out = asyncio.run(run(msgs, windows))
    got = accumulate(out)
    assert got == oracle(live, windows, ((2, False),))
    # partition 1, arrival order: (row_number, running sum, count)
    assert sorted(r[4:] for r in got if r[1] == 1) == [
        (1, 10, 1), (2, 13, 2), (3, 20, 3), (4, 21, 4)]


def test_rank_ties_and_multi_order():
    windows = (WindowSpec("rank"),)
    order_specs = ((2, False), (3, True))
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 1, 5, 9),
                   (OP_INSERT, 2, 1, 5, 9),      # tie on both keys
                   (OP_INSERT, 3, 1, 5, 7),
                   (OP_INSERT, 4, 1, 2, 1)]),
            barrier(2, 1)]
    _, out = asyncio.run(run(msgs, windows, order_specs=order_specs))
    live = [(1, 1, 5, 9), (2, 1, 5, 9), (3, 1, 5, 7), (4, 1, 2, 1)]
    assert accumulate(out) == oracle(live, windows, order_specs)


def test_bounded_frame_avg():
    windows = (WindowSpec("avg", arg=3, preceding=1),)
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, i, 1, i, i * 10) for i in range(1, 6)]),
            barrier(2, 1),
            chunk([(OP_DELETE, 3, 1, 3, 30)]),
            barrier(3, 2)]
    _, out = asyncio.run(run(msgs, windows))
    live = [(i, 1, i, i * 10) for i in (1, 2, 4, 5)]
    assert accumulate(out) == oracle(live, windows, ((2, False),))


def test_randomized_vs_oracle():
    rng = np.random.default_rng(5)
    windows = (WindowSpec("row_number"), WindowSpec("rank"),
               WindowSpec("sum", arg=3),
               WindowSpec("avg", arg=3, preceding=2))
    live = {}
    next_pk = 0
    msgs = [barrier(1, 0, BarrierKind.INITIAL)]
    for ep in range(2, 8):
        rows = []
        for _ in range(8):
            if live and rng.random() < 0.35:
                pk = int(rng.choice(list(live)))
                p, o, v = live.pop(pk)
                rows.append((OP_DELETE, pk, p, o, v))
            else:
                pk = next_pk
                next_pk += 1
                p = int(rng.integers(0, 3))
                # unique order key: with ties, tiebreak order is
                # implementation-defined (executor: row-key hash; oracle:
                # pk) and frame contents would legitimately differ
                o = pk
                v = int(rng.integers(0, 100))
                live[pk] = (p, o, v)
                rows.append((OP_INSERT, pk, p, o, v))
        msgs += [chunk(rows), barrier(ep, ep - 1)]
    _, out = asyncio.run(run(msgs, windows))
    rows_live = [(pk, p, o, v) for pk, (p, o, v) in live.items()]
    assert accumulate(out) == oracle(rows_live, windows, ((2, False),))


def test_persist_recover():
    from risingwave_tpu.state import MemoryStateStore, StateTable
    store = MemoryStateStore()
    windows = (WindowSpec("sum", arg=3),)

    def table():
        return StateTable(store, 33, SCH, pk_indices=[0])

    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 1, 1, 10), (OP_INSERT, 2, 1, 2, 20)]),
            barrier(2, 1)]
    asyncio.run(run(msgs, windows, state_table=table()))
    store.sync(2)

    msgs2 = [barrier(3, 2, BarrierKind.INITIAL),
             chunk([(OP_INSERT, 3, 1, 3, 5)]),
             barrier(4, 3)]
    _, out = asyncio.run(run(msgs2, windows, state_table=table()))
    # only the NEW row's output appears (earlier rows' sums unchanged)
    assert accumulate(out) == Counter({(3, 1, 3, 5, 35): 1})
