"""Retractable TopN: refill-from-below under retractions, golden-checked
against full recomputation (reference: top_n_cache.rs retractable path).
"""

import asyncio
from collections import Counter

import numpy as np
import pytest

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_INSERT, StreamChunk,
)
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.frontend import Session
from risingwave_tpu.stream import Barrier, BarrierKind
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.stream.retract_top_n import RetractableTopNExecutor

SCHEMA = schema(("g", DataType.INT64), ("v", DataType.INT64),
                ("pk", DataType.INT64))


class Script(Executor):
    pk_indices = (2,)

    def __init__(self, msgs):
        self.schema = SCHEMA
        self.msgs = msgs
        self.identity = "Script"

    async def execute(self):
        for m in self.msgs:
            yield m
            await asyncio.sleep(0)


def chunk(rows, cap=32):
    ops = np.asarray([r[0] for r in rows], dtype=np.int8)
    cols = [np.asarray([r[1 + i] for r in rows], dtype=np.int64)
            for i in range(3)]
    return StreamChunk.from_numpy(SCHEMA, cols, ops=ops, capacity=cap)


def bar(curr, prev, kind=BarrierKind.CHECKPOINT):
    return Barrier(EpochPair(curr, prev), kind)


def _net(out):
    acc = Counter()
    for m in out:
        if isinstance(m, StreamChunk):
            for op, vals in m.to_rows():
                acc[vals] += 1 if op in (OP_INSERT, OP_UPDATE_INSERT) else -1
    return {k: v for k, v in acc.items() if v}


def _golden(live, group_keys, order_col, limit, offset=0, desc=False):
    """Recompute the top set from the live row dict."""
    from collections import defaultdict
    groups = defaultdict(list)
    for row in live.values():
        g = tuple(row[i] for i in group_keys) if group_keys else ()
        groups[g].append(row)
    out = Counter()
    for g, rows in groups.items():
        rows.sort(key=lambda r: (r[order_col], r))
        if desc:
            rows.sort(key=lambda r: (-r[order_col],))
        for r in rows[offset:offset + limit]:
            out[r] += 1
    return dict(out)


async def _run(msgs, **kw):
    t = RetractableTopNExecutor(Script(msgs), **kw)
    out = []
    async for m in t.execute():
        out.append(m)
    return out


async def test_refill_from_below():
    """Deleting a top row promotes the next-best (the retractable path
    the append-only executor cannot serve)."""
    msgs = [bar(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 10, 1), (OP_INSERT, 1, 20, 2),
                   (OP_INSERT, 1, 30, 3), (OP_INSERT, 1, 40, 4)]),
            bar(2, 1),
            chunk([(OP_DELETE, 1, 10, 1)]),     # top-1 (asc) retracted
            bar(3, 2)]
    out = await _run(msgs, group_key_indices=(0,), order_col=1, limit=2)
    net = _net(out)
    assert net == {(1, 20, 2): 1, (1, 30, 3): 1}


def _ins(rows):
    """(g, v) rows as inserts with the running index as pk."""
    return [(OP_INSERT, g, v, pk) for pk, (g, v) in rows]


# append-only inputs of shapes the retraction tests do not take: several
# groups over two intervals, DESC with an OFFSET, and no group key at all
TOP_N_SHAPES = {
    "smallest_two_groups": dict(
        chunks=[[(1, 30), (1, 10), (2, 7)], [(1, 20), (1, 5), (2, 9)]],
        kw=dict(group_key_indices=(0,), order_col=1, limit=2),
        want={(1, 10), (1, 5), (2, 7), (2, 9)}),
    # desc sorted: 9 8 7 4 3 1; skip 1, take 2 -> {8, 7}
    "descending_with_offset": dict(
        chunks=[[(1, v) for v in (4, 9, 1, 7, 3, 8)]],
        kw=dict(group_key_indices=(0,), order_col=1, limit=2, offset=1,
                descending=True),
        want={(1, 8), (1, 7)}),
    "ungrouped": dict(
        chunks=[[(1, 30), (2, 10)], [(3, 20), (4, 40)]],
        kw=dict(group_key_indices=(), order_col=1, limit=2),
        want={(2, 10), (3, 20)}),
}


@pytest.mark.parametrize("case", list(TOP_N_SHAPES))
async def test_top_n_shapes(case):
    cfg = TOP_N_SHAPES[case]
    msgs, live, pk = [bar(1, 0, BarrierKind.INITIAL)], {}, 0
    for ep, rows in enumerate(cfg["chunks"], start=2):
        numbered = list(enumerate(rows, start=pk))
        pk += len(rows)
        live.update({i: (g, v, i) for i, (g, v) in numbered})
        msgs += [chunk(_ins(numbered)), bar(ep, ep - 1)]
    net = _net(await _run(msgs, **cfg["kw"]))
    assert set(net.values()) == {1}
    assert {r[:2] for r in net} == cfg["want"]
    kw = cfg["kw"]
    assert net == _golden(live, kw["group_key_indices"], 1, kw["limit"],
                          kw.get("offset", 0), kw.get("descending", False))


async def test_randomized_golden_with_retractions():
    rng = np.random.default_rng(5)
    live = {}
    next_pk = 0
    msgs = [bar(1, 0, BarrierKind.INITIAL)]
    epoch = 2
    for _ in range(12):
        rows = []
        for _ in range(int(rng.integers(2, 10))):
            if live and rng.random() < 0.4:
                pk = int(rng.choice(list(live)))
                g, v, _ = live.pop(pk)
                rows.append((OP_DELETE, g, v, pk))
            else:
                g = int(rng.integers(0, 4))
                v = int(rng.integers(0, 100))
                pk = next_pk
                next_pk += 1
                live[pk] = (g, v, pk)
                rows.append((OP_INSERT, g, v, pk))
        msgs.append(chunk(rows))
        msgs.append(bar(epoch, epoch - 1))
        epoch += 1
    out = await _run(list(msgs), group_key_indices=(0,), order_col=1,
                     limit=3, capacity=256)
    assert _net(out) == _golden(live, (0,), 1, 3)
    # descending variant over the same stream
    out = await _run(list(msgs), group_key_indices=(0,), order_col=1,
                     limit=3, capacity=256, descending=True)
    assert _net(out) == _golden(live, (0,), 1, 3, desc=True)


async def test_sql_top_n_over_agg():
    """CREATE MV ... GROUP BY ... ORDER BY n DESC LIMIT k — a TopN over a
    retracting agg changelog, checked against the batch engine."""
    s = Session()
    await s.execute("CREATE SOURCE bid WITH (connector='nexmark', "
                    "table='bid', chunk_size=256, rate_limit=512)")
    await s.execute("CREATE MATERIALIZED VIEW counts AS SELECT auction "
                    "AS a, count(*) AS n FROM bid GROUP BY auction")
    await s.execute("CREATE MATERIALIZED VIEW top3 AS SELECT a, n FROM "
                    "counts ORDER BY n DESC LIMIT 3")
    await s.tick(4)
    got = s.query("SELECT a, n FROM top3 ORDER BY 2 DESC, 1")
    want = s.query("SELECT a, n FROM counts ORDER BY 2 DESC, 1 LIMIT 3")
    # ties at the boundary can legitimately differ; compare the n values
    assert [n for _, n in got] == [n for _, n in want]
    assert len(got) == 3
    await s.drop_all()


async def test_sql_top_n_survives_rescale_and_recovery(tmp_path):
    """The review repro: ALTER PARALLELISM (and actor-death recovery) on a
    TopN MV rebuilds the executor from its durable full-input state; the
    recovered store must absorb the agg changelog's retractions."""
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / "d")))
    s = Session(store=store)
    await s.execute("CREATE SOURCE bid WITH (connector='nexmark', "
                    "table='bid', chunk_size=128, rate_limit=256)")
    await s.execute("CREATE MATERIALIZED VIEW t AS SELECT auction AS a, "
                    "count(*) AS n FROM bid GROUP BY auction "
                    "ORDER BY n DESC LIMIT 3")
    await s.tick(3)
    await s.execute("ALTER MATERIALIZED VIEW t SET PARALLELISM = 2")
    await s.tick(3)                      # agg retractions hit rebuilt TopN
    rows = s.query("SELECT a, n FROM t")
    assert len(rows) == 3

    # actor-death auto-recovery over the same topology
    victim = s.catalog.mvs["t"].deployment.tasks[0]
    victim.cancel()
    try:
        await victim
    except (asyncio.CancelledError, Exception):
        pass
    await s.tick(3)
    assert s.recoveries >= 1
    rows = s.query("SELECT a, n FROM t")
    assert len(rows) == 3
    await s.drop_all()


async def test_float_value_changing_below_its_integer_part_is_emitted():
    """The top set is diffed by a hash of the whole row: a row whose FLOAT64
    column moves from 1.2 to 1.7, all else equal, is another row (a hash of
    the value cast to an integer kept the old one in the view)."""
    sch = schema(("g", DataType.INT64), ("v", DataType.FLOAT64),
                 ("pk", DataType.INT64))

    class FScript(Script):
        def __init__(self, msgs):
            super().__init__(msgs)
            self.schema = sch

    def fchunk(rows):
        return StreamChunk.from_numpy(
            sch, [np.asarray([r[1] for r in rows], dtype=np.int64),
                  np.asarray([r[2] for r in rows], dtype=np.float64),
                  np.asarray([r[3] for r in rows], dtype=np.int64)],
            ops=np.asarray([r[0] for r in rows], dtype=np.int8), capacity=32)

    msgs = [bar(1, 0, BarrierKind.INITIAL),
            fchunk([(OP_INSERT, 1, 1.2, 1), (OP_INSERT, 1, 5.5, 2)]),
            bar(2, 1),
            fchunk([(OP_DELETE, 1, 1.2, 1), (OP_INSERT, 1, 1.7, 1)]),
            bar(3, 2)]
    t = RetractableTopNExecutor(FScript(msgs), group_key_indices=(0,),
                                order_col=1, limit=1)
    out = [m async for m in t.execute()]
    assert _net(out) == {(1, 1.7, 1): 1}
