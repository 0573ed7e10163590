"""The MV's changelog taps over a materialize that writes in columns.

A NO_CHECK `MaterializeExecutor` writes each chunk as one columnar batch and
hands its two taps — the serving cache's hook and the changelog log's
writer — the chunk's host lanes; rows are made only by a tap that keeps
them. What an ACTIVE tap receives, and one activated MID-interval with
chunks already pending, has to be row for row what `chunk.to_rows()` gave
it before: NULL lanes as None (an outer join's padding), update pairs as
DEL then PUT, invisible rows nowhere.
"""

import asyncio

import numpy as np
import pytest

from risingwave_tpu.common import DataType, EpochPair, schema
from risingwave_tpu.common.chunk import (OP_DELETE, OP_INSERT,
                                         OP_UPDATE_DELETE, OP_UPDATE_INSERT,
                                         StreamChunk)
from risingwave_tpu.frontend import Session
from risingwave_tpu.frontend import sql as ast
from risingwave_tpu.frontend.batch import run_batch_select_full
from risingwave_tpu.logstore import ChangelogSubscription
from risingwave_tpu.logstore.log import MvChangelog
from risingwave_tpu.serving.cache import OP_DEL, OP_PUT, MvChangelogHook
from risingwave_tpu.state import MemoryStateStore, StateTable
from risingwave_tpu.state.storage_table import StorageTable
from risingwave_tpu.stream import MaterializeExecutor
from risingwave_tpu.stream.message import Barrier, BarrierKind

SCH = schema(("k", DataType.INT64), ("name", DataType.VARCHAR),
             ("x", DataType.FLOAT64), ("ok", DataType.BOOLEAN))
CAP = 16
PK = (0, 3)             # k, NULL in some cases, and ok, never NULL


def make_chunk(rng, null_pk: bool):
    """(chunk, its visible rows as the per-cell conversion gives them)."""
    n = int(rng.integers(3, CAP - 2))
    cols = [rng.integers(0, 6, size=n), rng.integers(0, 4, size=n),
            rng.integers(-8, 8, size=n) / 4.0, rng.integers(0, 2, size=n)]
    valids = [rng.random(n) > 0.3 if null_pk else None,
              rng.random(n) > 0.4, rng.random(n) > 0.4, None]
    ops = rng.choice([OP_INSERT, OP_DELETE, OP_UPDATE_DELETE,
                      OP_UPDATE_INSERT], size=n)
    chunk = StreamChunk.from_numpy(SCH, cols, ops=ops, capacity=CAP,
                                   valids=valids)
    hide = rng.random(CAP) < 0.2
    chunk = StreamChunk(chunk.columns, chunk.ops,
                        chunk.vis & ~np.asarray(hide), SCH)
    rows = []
    for r in np.flatnonzero(np.asarray(chunk.vis)):
        rows.append((int(np.asarray(chunk.ops)[r]), tuple(
            np.asarray(c.data)[r].item()
            if c.valid is None or np.asarray(c.valid)[r] else None
            for c in chunk.columns)))
    return chunk, rows


def effective(rows):
    return [(OP_PUT if op in (OP_INSERT, OP_UPDATE_INSERT) else OP_DEL, row)
            for op, row in rows]


class Script:
    """An input that plays messages and calls `between()` after the n-th."""

    def __init__(self, messages, call_after: int, between):
        self.schema = SCH
        self.messages, self.call_after, self.between = \
            messages, call_after, between

    async def execute(self):
        for i, m in enumerate(self.messages):
            yield m
            if i == self.call_after:
                self.between()


@pytest.mark.parametrize("null_pk", [False, True],
                         ids=["pk_not_null", "null_pk_lanes"])
@pytest.mark.parametrize("tap", ["serving_hook", "changelog_log"])
def test_a_tap_activated_between_two_barriers_gets_the_open_intervals_rows(
        tap, null_pk):
    rng = np.random.default_rng(3 + null_pk)
    store = MemoryStateStore()
    table = StateTable(store, 5, SCH, PK, check_consistency=False)
    (a, rows_a), (b, rows_b), (c, rows_c), (d, rows_d) = (
        make_chunk(rng, null_pk) for _ in range(4))
    epochs = [1 << 16, 2 << 16, 3 << 16, 4 << 16]
    barriers = [Barrier(EpochPair(epochs[0], 0), BarrierKind.INITIAL)] + [
        Barrier(EpochPair(e, p)) for p, e in zip(epochs, epochs[1:])]
    # interval 1: a (nobody listens); interval 2: b, ACTIVATE, c; 3: d
    hook = MvChangelogHook("mv")
    log = MvChangelog(store, 6, SCH, PK, state_table=table)

    def activate():
        assert (hook if tap == "serving_hook" else log.writers[0])._pending
        hook.activate() if tap == "serving_hook" \
            else log.activate(epochs[0])
    mat = MaterializeExecutor(Script(
        [barriers[0], a, barriers[1], b, c, barriers[2], d, barriers[3]],
        3, activate), table)
    if tap == "serving_hook":
        mat.serving_hook = hook
    else:
        mat.changelog_log = log.writers[0]

    async def run():
        async for msg in mat.execute():
            if isinstance(msg, Barrier) and msg.epoch.prev:
                store.sync(msg.epoch.prev)
    asyncio.run(run())

    want = [(epochs[1], effective(rows_b + rows_c)),
            (epochs[2], effective(rows_d))]
    got = hook.drain(epochs[-1]) if tap == "serving_hook" \
        else list(log.read_committed(0))
    assert got == want
    assert any(v is None for _op, row in want[0][1] for v in row)
    # and the table holds what the same rows leave in a dict, NULLs kept
    image = {}
    for op, row in effective(rows_a + rows_b + rows_c + rows_d):
        image[(row[0], row[3])] = row if op == OP_PUT else None
    assert sorted((r for _k, r in table.iter_all()), key=repr) == sorted(
        (r for r in image.values() if r is not None), key=repr)
    null_pk_rows = sum(row[0] is None for _op, row in
                       rows_a + rows_b + rows_c + rows_d)
    assert table.row_path_rows == null_pk_rows
    assert (null_pk_rows > 0) == null_pk


def test_an_inactive_tap_never_makes_a_row(monkeypatch):
    """Registered on every MV, read on few: until activated, a tap drops
    the interval's lanes at the barrier without turning one into rows."""
    from risingwave_tpu.common.chunk import HostChunk
    made = []
    real = HostChunk.rows
    monkeypatch.setattr(HostChunk, "rows",
                        lambda self: made.append(1) or real(self))
    rng = np.random.default_rng(1)
    store = MemoryStateStore()
    table = StateTable(store, 5, SCH, PK, check_consistency=False)
    hook = MvChangelogHook("mv")
    log = MvChangelog(store, 6, SCH, PK, state_table=table)
    chunks = [make_chunk(rng, False)[0] for _ in range(2)]
    mat = MaterializeExecutor(Script(
        [Barrier(EpochPair(1 << 16, 0), BarrierKind.INITIAL), chunks[0],
         Barrier(EpochPair(2 << 16, 1 << 16)), chunks[1],
         Barrier(EpochPair(3 << 16, 2 << 16))], -1, None), table)
    mat.serving_hook, mat.changelog_log = hook, log.writers[0]

    async def run():
        async for _ in mat.execute():
            pass
    asyncio.run(run())
    assert not made and not hook._pending and not hook._by_epoch
    assert log.dropped_through == 2 << 16
    assert len(list(table.iter_all())) > 0 and table.row_path_rows == 0


async def test_outer_join_padding_reaches_the_cache_and_a_subscriber():
    """NEXMark auctions LEFT JOIN persons, most rows NULL-padded: a serving
    cache built mid-stream answers as the store scan does, and a
    subscription started mid-stream rebuilds the MV from its backfill and
    its tail, None for None."""
    s = Session()
    await s.execute("CREATE SOURCE auction WITH (connector='nexmark', "
                    "table='auction', chunk_size=256, rate_limit=512)")
    await s.execute("CREATE SOURCE person WITH (connector='nexmark', "
                    "table='person', chunk_size=256, rate_limit=512)")
    await s.execute(
        "CREATE MATERIALIZED VIEW lj AS "
        "SELECT A.id, P.name, P.id AS pid FROM auction A "
        "LEFT OUTER JOIN person P ON A.seller = P.id AND A.category = 10")
    await s.tick(2)
    q = "SELECT id, name, pid FROM lj"
    s.query(q)                                  # marks the MV wanted
    sub = ChangelogSubscription(s.coord.logstore, "lj")
    start = asyncio.create_task(sub.start())
    await s.tick(1)
    backfill = await start
    pk = backfill["pk_indices"]
    applied = {tuple(r[i] for i in pk): tuple(r) for r in backfill["rows"]}
    await s.tick(3)
    scan = run_batch_select_full(s.catalog, ast.parse(q))[2]
    assert s.query(q) == scan
    rep = {r["mv"]: r for r in s.coord.serving.report()}
    assert rep["lj"]["hits"] > 0 and rep["lj"]["applied_rows"] > 0
    assert any(r[1] is None and r[2] is None for r in scan) \
        and any(r[1] is not None for r in scan)
    # the subscriber: the committed table (hidden columns and all, the name
    # as its id), once the tail has caught up with the last commit
    stored = StorageTable.for_state_table(
        s.coord.logstore.mv_logs["lj"].state_table)
    want = {tuple(r[i] for i in pk): tuple(r)
            for r in stored.snapshot_with_keys(committed_only=True)[0]}
    assert len(want) > len(backfill["rows"]) > 0
    while applied != want:
        _epoch, rows = await sub.next_batch(timeout=15)
        for op, row in rows:
            key = tuple(row[i] for i in pk)
            if op == OP_DEL:
                applied.pop(key, None)
            else:
                applied[key] = tuple(row)
    assert sum(r[1] is None for r in applied.values()) \
        == sum(r[1] is None for r in scan) > 0
    sub.close()
    await s.drop_all()
