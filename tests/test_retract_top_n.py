"""Retractable TopN: refill-from-below under retractions, golden-checked
against full recomputation (reference: top_n_cache.rs retractable path);
and the append-only form, whose store is kept in rank order
(group_top_n_appendonly.rs), against an oracle that re-ranks every row.
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
from risingwave_tpu.frontend import Session
from risingwave_tpu.stream import Barrier, BarrierKind
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.state import MemoryStateStore, StateTable
from risingwave_tpu.stream.retract_top_n import (
    _FRESH, _UNEMITTED, RetractableTopNExecutor,
)
from risingwave_tpu.stream.sorted_join import _HSENTINEL

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


# ------------------------------------------------------ the append-only form
#
# A store in RANK order: (group hash, order keys, stream-key ties) ascending
# over a dense prefix. One golden test over several barriers, against an
# oracle that keeps every row ever sent and re-ranks it in Python.

# (g, v, w, pk): v is the order key (INT64 or FLOAT64), w a second one, pk
# the running row id (arrival order): the stream key
INT_SCHEMA = schema(("g", DataType.INT64), ("v", DataType.INT64),
                    ("w", DataType.INT64), ("pk", DataType.INT64))
FLOAT_SCHEMA = schema(("g", DataType.INT64), ("v", DataType.FLOAT64),
                      ("w", DataType.INT64), ("pk", DataType.INT64))
PK = 3


def _random_intervals(seed, intervals=6, groups=7):
    rng = np.random.default_rng(seed)
    return [[[(int(rng.integers(0, groups)), int(rng.integers(0, 6)),
               int(rng.integers(0, 3)))
              for _ in range(int(rng.integers(1, 30)))]
             for _ in range(int(rng.integers(1, 3)))]
            for _ in range(intervals)]


# intervals -> chunks -> (g, v, w) rows; the pk is numbered as they come
APPEND_ONLY_CASES = {
    # the same price again and again: the earlier row ranks first, across
    # chunks and across barriers
    "ties_by_arrival": dict(
        kw=dict(group_key_indices=(0,), order_col=1, limit=2),
        intervals=[[[(1, 5, 0), (1, 5, 0), (2, 5, 0)], [(1, 5, 0)]],
                   [[(1, 5, 0), (2, 5, 0), (2, 5, 0)]],
                   [[(1, 4, 0), (1, 5, 0)]]]),
    "offset_descending": dict(
        kw=dict(group_key_indices=(0,), order_col=1, limit=2, offset=1,
                descending=True),
        intervals=[[[(1, v, 0) for v in (4, 9, 1, 7)]],
                   [[(1, 3, 0), (1, 8, 0), (2, 8, 0)]],
                   [[(1, 9, 0), (2, 1, 0), (2, 2, 0), (2, 3, 0)]]]),
    "two_order_columns_one_descending": dict(
        kw=dict(group_key_indices=(0,),
                order_specs=[(1, False), (2, True)], limit=3),
        intervals=[[[(1, 5, 1), (1, 5, 2), (1, 5, 0), (2, 1, 1)]],
                   [[(1, 5, 2), (1, 4, 0), (2, 1, 2)], [(1, 5, 3)]],
                   [[(2, 1, 2), (2, 0, 0), (1, 5, 3)]]]),
    # fractions, a negative, both zeros (equal: ranked by arrival)
    "float64_descending": dict(
        schema=FLOAT_SCHEMA,
        kw=dict(group_key_indices=(0,), order_col=1, limit=2,
                descending=True),
        intervals=[[[(1, 1.25, 0), (1, -3.5, 0), (1, 0.0, 0), (2, -0.0, 0)]],
                   [[(1, -0.0, 0), (1, 1.75, 0), (2, 0.0, 0), (2, -1.5, 0)]],
                   [[(2, 0.5, 0), (1, 1.5, 0), (1, 1.75, 0)]]]),
    "no_group_key": dict(
        kw=dict(group_key_indices=(), order_col=1, limit=3),
        intervals=[[[(1, 30, 0), (2, 10, 0)]], [[(3, 20, 0), (4, 40, 0)]],
                   [[(5, 10, 0), (6, 5, 0)], [(7, 50, 0)]]]),
    # the second interval holds nothing but groups the store has not met
    "a_chunk_of_a_whole_new_group": dict(
        kw=dict(group_key_indices=(0,), order_col=1, limit=2),
        intervals=[[[(5, 3, 0), (5, 1, 0), (5, 2, 0)]],
                   [[(2, 9, 0), (2, 8, 0), (9, 7, 0), (2, 7, 0)]],
                   [[(5, 0, 0), (9, 1, 0)]]]),
    # the second interval only pushes stored rows past rank N: every row
    # of it ranks first, the old top leaves the MV and the state table
    "a_chunk_that_only_pushes_rows_past_rank_n": dict(
        kw=dict(group_key_indices=(0,), order_col=1, limit=2),
        intervals=[[[(1, 10, 0), (1, 11, 0), (2, 10, 0)]],
                   [[(1, 1, 0), (1, 2, 0)]],
                   [[(1, 0, 0)]]]),
    "random_groups": dict(
        kw=dict(group_key_indices=(0,),
                order_specs=[(1, True), (2, False)], limit=3, offset=1),
        intervals=_random_intervals(11)),
    "random_two_group_columns": dict(
        kw=dict(group_key_indices=(0, 2), order_col=1, limit=2),
        intervals=_random_intervals(12, groups=3)),
}


class Steps(Executor):
    """A scripted input that lets the test look at its consumer between
    messages: by the time it is asked for the next one, the consumer is
    done with the last."""
    pk_indices = (PK,)

    def __init__(self, sch, msgs, between):
        self.schema, self.msgs, self.between = sch, msgs, between
        self.identity = "Steps"

    async def execute(self):
        for m in self.msgs:
            yield m
            await asyncio.sleep(0)
            self.between(m)


def _ao_chunk(sch, rows, cap=32):
    cols = [np.asarray([r[i] for r in rows], dtype=f.data_type.np_dtype)
            for i, f in enumerate(sch)]
    return StreamChunk.from_numpy(sch, cols, capacity=cap)


def _rank_key(kw, row):
    """The oracle's order within a group: order keys, then arrival."""
    specs = kw.get("order_specs") or [(kw["order_col"],
                                       kw.get("descending", False))]
    return tuple(-row[c] if d else row[c] for c, d in specs) + (row[PK],)


def _oracle(kw, seen):
    """row -> 0-based rank within its group, over every row ever sent."""
    groups = {}
    for row in seen:
        groups.setdefault(tuple(row[i] for i in kw["group_key_indices"]),
                          []).append(row)
    return {row: r for rows in groups.values()
            for r, row in enumerate(sorted(rows,
                                           key=lambda x: _rank_key(kw, x)))}


def _store(top):
    """The live prefix as host rows [(khash, row, erank)], after checking
    that it IS a prefix: nothing but padding behind n."""
    n = int(top.n)
    kh = np.asarray(top.khash)
    cols = [np.asarray(c) for c in top.cols]
    valids = [np.asarray(v) for v in top.valids]
    assert (kh[n:] == _HSENTINEL).all()
    assert all((c[n:] == 0).all() for c in cols)
    assert all(not v[n:].any() and v[:n].all() for v in valids)
    return [(int(kh[i]), tuple(c[i].item() for c in cols[:-1]),
             int(cols[-1][i])) for i in range(n)]


def _assert_rank_order(top, kw, rows):
    """Strictly ascending by (group hash, order keys, arrival), and the
    first lane is the hash of the row's GROUP columns."""
    want = np.asarray(top._group_hash(
        [np.asarray([r[1][c] for r in rows], dtype=np.asarray(col).dtype)
         for c, col in enumerate(top.cols[:-1])])) if rows else []
    assert [r[0] for r in rows] == [int(h) for h in want]
    keys = [(h,) + _rank_key(kw, row) for h, row, _ in rows]
    assert all(a < b for a, b in zip(keys, keys[1:])), keys


def _apply_changelog(mv: Counter, chunk) -> None:
    for op, vals in chunk.to_rows():
        mv[vals] += 1 if op in (OP_INSERT, OP_UPDATE_INSERT) else -1
        assert mv[vals] in (0, 1), (op, vals)
        if op == OP_UPDATE_DELETE:
            assert vals[-1] >= 1


@pytest.mark.parametrize("emit_rank", [False, True],
                         ids=["rows", "rows_and_rank"])
@pytest.mark.parametrize("case", list(APPEND_ONLY_CASES))
async def test_append_only_store_stays_in_rank_order(case, emit_rank):
    cfg = APPEND_ONLY_CASES[case]
    sch, kw = cfg.get("schema", INT_SCHEMA), cfg["kw"]
    offset, limit = kw.get("offset", 0), kw["limit"]
    msgs, seen, expect = [bar(1, 0, BarrierKind.INITIAL)], [], {}
    for ep, chunks in enumerate(cfg["intervals"], start=2):
        for rows in chunks:
            numbered = [r + (len(seen) + i,) for i, r in enumerate(rows)]
            seen += numbered
            msgs.append(_ao_chunk(sch, numbered))
            expect[len(msgs) - 1] = ("chunk", list(seen))
        msgs.append(bar(ep, ep - 1))
        expect[len(msgs) - 1] = ("barrier", list(seen))
    at = {id(m): i for i, m in enumerate(msgs)}
    msg_rows = {i: set(zip(*[np.asarray(c.data).tolist()
                             for c in m.columns]))
                for i, m in enumerate(msgs) if isinstance(m, StreamChunk)}
    table = StateTable(MemoryStateStore(), 5, sch, (PK,))
    pruned = set()        # rows a barrier dropped: they never come back

    def between(msg):
        what, rows = expect.get(at[id(msg)], (None, None))
        if what is None:
            return
        held = _store(top)
        _assert_rank_order(top, kw, held)
        rank = _oracle(kw, rows)
        kept = {r for r in rows if rank[r] < offset + limit}
        if what == "chunk":
            # every row that could rank before the chunk, and the chunk's
            assert {r[1] for r in held} == set(rows) - pruned
            assert all(er == _FRESH for _, row, er in held
                       if row in msg_rows[at[id(msg)]])
            return
        pruned.update(set(rows) - kept)
        assert {r[1] for r in held} == kept
        # the hidden lane is the rank the MV has the row under
        for _, row, er in held:
            assert er == (rank[row] if rank[row] >= offset else _UNEMITTED)
        assert {r for _, r in table.iter_all()} == kept

    top = RetractableTopNExecutor(
        Steps(sch, msgs, between), capacity=256, append_only=True,
        emit_rank=emit_rank, state_table=table, **kw)
    mv, barriers = Counter(), 0
    async for m in top.execute():
        if isinstance(m, StreamChunk):
            _apply_changelog(mv, m)
            continue
        if m.kind is BarrierKind.INITIAL:
            continue
        # the MV after this barrier: the oracle's window, rank and all
        barriers += 1
        rows = expect[at[id(m)]][1]
        rank = _oracle(kw, rows)
        want = {(r + (rank[r] + 1,) if emit_rank else r)
                for r in rows if offset <= rank[r] < offset + limit}
        assert {r for r, c in mv.items() if c} == want
    assert barriers == len(cfg["intervals"])
    assert int(np.asarray(top._errs_dev).sum()) == 0


async def test_recovery_in_reverse_order_rebuilds_the_same_store():
    """`recover_state` places the durable rows wherever they belong, in
    whatever order the state table hands them: replayed backwards, the
    store, its hidden ranks and the next barrier's changelog are those of
    the executor that never stopped."""
    kw = dict(group_key_indices=(0,),
              order_specs=[(1, True), (2, False)], limit=3, offset=1)
    intervals = _random_intervals(21, intervals=5, groups=9)
    seen = []

    def script(chunks_by_interval, first_epoch, initial):
        msgs = [bar(first_epoch, first_epoch - 1, BarrierKind.INITIAL)] \
            if initial else []
        for ep, chunks in enumerate(chunks_by_interval,
                                    start=first_epoch + 1):
            for rows in chunks:
                numbered = [r + (len(seen) + i,) for i, r in enumerate(rows)]
                seen.extend(numbered)
                msgs.append(_ao_chunk(INT_SCHEMA, numbered))
            msgs.append(bar(ep, ep - 1))
        return msgs

    def build(msgs, store):
        return RetractableTopNExecutor(
            Steps(INT_SCHEMA, msgs, lambda m: None), capacity=256,
            append_only=True, emit_rank=True,
            state_table=StateTable(store, 5, INT_SCHEMA, (PK,)), **kw)

    before, after = script(intervals[:4], 1, True), script(intervals[4:], 5,
                                                           False)
    # the executor that never stops: its store at the barrier the other
    # one dies at, and what it emits from there on
    whole = build(before + after, MemoryStateStore())
    tail, snapshot = [], None
    async for m in whole.execute():
        if isinstance(m, Barrier) and m.epoch.curr == 5:
            snapshot = _store(whole)
        elif snapshot is not None and isinstance(m, StreamChunk):
            tail.append(m.to_rows())
    assert snapshot and tail

    store = MemoryStateStore()
    async for _ in build(before, store).execute():
        pass
    second = build([bar(5, 4, BarrierKind.INITIAL)] + after, store)
    real = second.state_table.iter_all
    second.state_table.iter_all = lambda: reversed(list(real()))
    replayed = []
    async for m in second.execute():
        if isinstance(m, Barrier) and m.kind is BarrierKind.INITIAL:
            assert _store(second) == snapshot
            _assert_rank_order(second, kw, snapshot)
        elif isinstance(m, StreamChunk):
            replayed.append(m.to_rows())
    assert replayed == tail
    assert _store(second) == _store(whole)
