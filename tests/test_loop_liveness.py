"""The event-loop thread never waits for the device, and the uploader never
dispatches to it (utils/d2h.py): every barrier-time fetch of an executor is
a pure wait on a worker thread, awaited; every pack a checkpoint's flush
needs is enqueued by the actor at its barrier; what the store defers is one
pure wait and a host-only continuation per table.
"""

import asyncio
import importlib
import threading
import time
from collections import Counter

import numpy as np
import pytest

from benchmark.harness import drive, spec
from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import OP_INSERT, StreamChunk
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.expr.agg import count_star
from risingwave_tpu.frontend import Session
from risingwave_tpu.state import (
    HummockStateStore, LocalFsObjectStore, MemoryStateStore, StateTable)
from risingwave_tpu.stream import Barrier, BarrierKind, HashAggExecutor
from risingwave_tpu.stream.dynamic import DynamicFilterExecutor
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.stream.general_over_window import (
    GeneralOverWindowExecutor)
from risingwave_tpu.stream.retract_top_n import RetractableTopNExecutor
from risingwave_tpu.stream.sharded_agg import ShardedHashAggExecutor
from risingwave_tpu.stream.sharded_join import ShardedSortedJoinExecutor
from risingwave_tpu.stream.sharded_top_n import ShardedTopNExecutor
from risingwave_tpu.stream.snapshot_join_agg import SnapshotJoinAggExecutor
from risingwave_tpu.stream.sorted_join import SortedJoinExecutor
from risingwave_tpu.utils import d2h
from risingwave_tpu.utils.metrics import D2H_WAIT_ON_LOOP_SECONDS

# the module: the attribute `risingwave_tpu.ops.jit_state` is a function
jit_state = importlib.import_module("risingwave_tpu.ops.jit_state")
W = 10_000_000
BID = ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
       "chunk_size=256, rate_limit=512)")
Q8_SOURCES = (
    "CREATE SOURCE person WITH (connector='nexmark', table='person', "
    "primary_key='id', chunk_size=128, rate_limit=256, emit_watermarks=1)",
    "CREATE SOURCE auction WITH (connector='nexmark', table='auction', "
    "primary_key='id', chunk_size=384, rate_limit=768, emit_watermarks=1)")
COUNTS = ("CREATE MATERIALIZED VIEW counts AS SELECT auction AS a, "
          "count(*) AS n FROM bid GROUP BY auction")
TOP = ("CREATE MATERIALIZED VIEW mv AS SELECT a, n FROM counts "
       "ORDER BY n DESC LIMIT 3")
JOIN = (f"CREATE MATERIALIZED VIEW mv AS SELECT P.id, P.window_start "
        f"FROM TUMBLE(person, date_time, {W}) P "
        f"JOIN TUMBLE(auction, date_time, {W}) A "
        f"ON P.id = A.seller AND P.window_start = A.window_start")
MESH = "SET streaming_parallelism_devices = 4"

# executor under test -> (the DDL that plans it, the MV that holds it)
PIPELINES = {
    HashAggExecutor: ([BID, COUNTS], "counts"),
    SortedJoinExecutor: ([*Q8_SOURCES, JOIN], "mv"),
    ShardedHashAggExecutor: ([BID, MESH, COUNTS], "counts"),
    ShardedSortedJoinExecutor: ([*Q8_SOURCES, MESH, JOIN], "mv"),
    RetractableTopNExecutor: ([BID, COUNTS, TOP], "mv"),
    ShardedTopNExecutor: ([BID, MESH, COUNTS, TOP], "mv"),
    GeneralOverWindowExecutor: ([BID, (
        "CREATE MATERIALIZED VIEW mv AS SELECT auction, price, "
        "lag(price) OVER (PARTITION BY auction ORDER BY price) AS lg "
        "FROM bid")], "mv"),
    DynamicFilterExecutor: ([
        "CREATE SOURCE auction WITH (connector='nexmark', table='auction',"
        " primary_key='id', chunk_size=64, rate_limit=64)",
        "CREATE MATERIALIZED VIEW mv AS SELECT id, expires FROM auction "
        "WHERE expires > now()"], "mv"),
    SnapshotJoinAggExecutor: ([
        "CREATE SOURCE part WITH (connector='tpch', table='part', "
        "chunk_size=512, rate_limit=512, primary_key='p_partkey')",
        "CREATE SOURCE lineitem WITH (connector='tpch', table='lineitem', "
        "chunk_size=512, rate_limit=1024)",
        "CREATE MATERIALIZED VIEW mv AS "
        "SELECT sum(L.l_extendedprice) / 7.0 AS avg_yearly "
        "FROM lineitem L JOIN part P ON P.p_partkey = L.l_partkey "
        "JOIN (SELECT l_partkey AS agg_partkey, "
        "0.2 * avg(l_quantity) AS avg_quantity "
        "FROM lineitem GROUP BY l_partkey) A "
        "ON A.agg_partkey = L.l_partkey "
        "AND L.l_quantity < A.avg_quantity "
        "WHERE P.p_brand = 'Brand#23'"], "mv"),
}


def _chain(node):
    """Every executor below `node`, itself included."""
    while node is not None:
        yield node
        for inp in getattr(node, "inputs", None) or ():
            yield from _chain(inp)
        node = getattr(node, "input", None)


def _executors(session, mv: str, klass) -> list:
    dep = session.catalog.mvs[mv].deployment
    return [ex for roots in dep.roots.values() for root in roots
            for ex in _chain(root) if type(ex) is klass]


class SlowFetches:
    """`utils/d2h.py` `_in_wait_span` — what `fetch_small` and `fetch_flat`
    bottom out in — made to take `seconds` longer, as a device that has not
    reached the pack yet does; keeps, per fetch, the thread it ran on and
    what `probe()` read before and after it."""

    def __init__(self, monkeypatch, seconds: float, probe=lambda: 0):
        self.fetches: list = []
        self.entered = threading.Event()
        real = d2h._in_wait_span

        def slow(fetch, nbytes):
            before = probe()
            self.entered.set()
            time.sleep(seconds)
            self.fetches.append((threading.get_ident(), before, probe()))
            return real(fetch, nbytes)

        monkeypatch.setattr(d2h, "_in_wait_span", slow)


# ------------------------------------------------- (a) the loop stays live

@pytest.mark.parametrize("klass", list(PIPELINES), ids=lambda k: k.__name__)
async def test_the_loop_ticks_while_a_barrier_waits_for_the_device(
        klass, monkeypatch):
    """With every fetch 0.2 s slow, a coroutine ticking every 10 ms on the
    same loop goes on ticking while the executor's barrier is processed:
    each fetch runs on a worker thread, and in the median the loop gets
    through most of the 20 ticks a wait is long (not all, and not in
    every wait: this box runs six test processes, and a new shape's
    compile holds the loop too; a fetch made ON the loop lets through
    none, by construction)."""
    ddl, mv = PIPELINES[klass]
    s = Session()
    for stmt in ddl:
        await s.execute(stmt)
    assert _executors(s, mv, klass), f"{klass.__name__} was not planned"
    await s.tick(4)     # the programs compiled, the mesh's send sized
    ticks = [0]

    async def ticker():
        while True:
            ticks[0] += 1
            await asyncio.sleep(0.01)

    slow = SlowFetches(monkeypatch, 0.2, probe=lambda: ticks[0])
    loop_thread = threading.get_ident()
    t = asyncio.create_task(ticker())
    try:
        await s.tick(1)
    finally:
        t.cancel()
    assert slow.fetches, "the barrier made no fetch: the test is vacuous"
    for thread, _, _ in slow.fetches:
        assert thread != loop_thread, "a fetch blocked the event loop"
    during = sorted(after - before for _, before, after in slow.fetches)
    assert during[len(during) // 2] >= 10, during
    monkeypatch.undo()
    await s.drop_all()


# ------------------------------- (b) no d2h_wait on the loop, q5 and q7

@pytest.mark.parametrize("cell_name", ["q5.sat", "q7.sat", "q17.sat"])
async def test_no_barrier_fetch_waits_on_the_loop_thread(cell_name,
                                                         tmp_path):
    """Ten durable checkpoints of the benchmark's cell at rehearsal size:
    `d2h_wait_on_loop_seconds_total` does not move (`q17.sat`: the snapshot
    join-agg's counts and its packed rows are both awaited fetches)."""
    cell = spec.Cell(spec.load_benchmark(), cell_name, rehearsal=True)
    s, _, _ = await drive.deploy(cell, 2147483659, str(tmp_path / "store"))
    stamps = drive.Stamps(s.coord)
    before = D2H_WAIT_ON_LOOP_SECONDS.value
    for i in range(10):
        await drive.checkpoint(
            s, cell.query.MV, stamps,
            {t: (i + 1) * q for t, q in cell.quotas.items()})
    await s.coord.drain_uploads()
    assert len(s.coord.committed_epochs) >= 10
    assert D2H_WAIT_ON_LOOP_SECONDS.value == before
    await s.crash()


def test_a_fetch_on_the_loop_thread_is_counted():
    import jax.numpy as jnp
    x = jnp.arange(4)
    before = D2H_WAIT_ON_LOOP_SECONDS.value
    d2h.fetch_small(x)              # no loop running: not counted
    assert D2H_WAIT_ON_LOOP_SECONDS.value == before

    async def on_loop():
        d2h.fetch_small(x)
        mid = D2H_WAIT_ON_LOOP_SECONDS.value
        assert mid > before
        await d2h.off_loop(d2h.fetch_small, x)
        await d2h.off_loop(d2h.fetch_flat, (x, None))
        assert D2H_WAIT_ON_LOOP_SECONDS.value == mid
    asyncio.run(on_loop())


# ----------------------- (c) the uploader dispatches nothing to the device

async def test_the_uploader_dispatches_nothing_and_the_agg_defers_one_stage(
        tmp_path, monkeypatch):
    """Durable q5 at rehearsal size, four checkpoints: every StateJit
    dispatch and every pack of a fetch's payload is made by an actor's
    task, none by `epoch-uploader` (so the counters do not move across a
    flush of their own accord); each checkpoint the hash agg hands the
    store exactly one (wait, cont) for its table."""
    cell = spec.Cell(spec.load_benchmark(), "q5.sat", rehearsal=True)
    s, _, _ = await drive.deploy(cell, 2147483659, str(tmp_path / "store"))
    by_task: Counter = Counter()

    def task_name() -> str:
        t = asyncio.current_task()
        return t.get_name() if t is not None else "no-task"

    class Dispatches:
        def inc(self, n=1):
            by_task["dispatch", task_name()] += 1
            jit_state_real.inc(n)

    jit_state_real = jit_state.DEVICE_DISPATCHES
    monkeypatch.setattr(jit_state, "DEVICE_DISPATCHES", Dispatches())
    real_pack = d2h.pack_for_fetch

    def pack(arrays):
        by_task["pack", task_name()] += 1
        return real_pack(arrays)

    monkeypatch.setattr(d2h, "pack_for_fetch", pack)
    deferred: list = []
    real_defer = s.store.defer_flush

    async def defer(epoch, wait, cont, table_id=None):
        deferred.append((epoch, table_id))
        await real_defer(epoch, wait, cont, table_id=table_id)

    monkeypatch.setattr(s.store, "defer_flush", defer)
    assert s.store.defer_enabled
    stamps = drive.Stamps(s.coord)
    for i in range(4):
        await drive.checkpoint(
            s, cell.query.MV, stamps,
            {t: (i + 1) * q for t, q in cell.quotas.items()})
    await s.coord.drain_uploads()
    (agg,) = _executors(s, cell.query.MV, HashAggExecutor)
    per_epoch = Counter(e for e, t in deferred
                        if t == agg.state_table.table_id)
    assert len(per_epoch) == 4 and set(per_epoch.values()) == {1}
    kinds = {kind for kind, _ in by_task}
    assert kinds == {"dispatch", "pack"}
    for (kind, task), n in by_task.items():
        assert task.startswith("actor-"), (kind, task, n)
    await s.crash()


# ------------------------------------------------- (d) fail-stop order

SCHEMA = schema(("k", DataType.INT64), ("v", DataType.INT64))


class Script(Executor):
    def __init__(self, messages):
        self.schema = SCHEMA
        self.messages = messages
        self.identity = "Script"

    async def execute(self):
        for m in self.messages:
            yield m
            await asyncio.sleep(0)


def chunk(rows, cap=16):
    cols = [np.asarray([r[i] for r in rows], dtype=np.int64)
            for i in range(2)]
    return StreamChunk.from_numpy(
        SCHEMA, cols, ops=np.full(len(rows), OP_INSERT, dtype=np.int8),
        capacity=cap)


def bar(curr, prev, kind=BarrierKind.CHECKPOINT):
    return Barrier(EpochPair(curr, prev), kind)


def _overflowing_agg(store):
    """Epoch 2 is fine; epoch 3 brings 80 groups to a 32-slot table."""
    table = StateTable(
        store, table_id=10, pk_indices=[0],
        schema=schema(("k", DataType.INT64), ("count", DataType.INT64),
                      ("_row_count", DataType.INT64)))
    many = [(k, k) for k in range(80)]
    src = Script([bar(1, 0, BarrierKind.INITIAL), chunk([(1, 1), (2, 2)]),
                  bar(2, 1), chunk(many, cap=128), bar(3, 2), bar(4, 3)])
    return HashAggExecutor(src, [0], [count_star()], capacity=32,
                           state_table=table), [table], "table overflow"


def _overflowing_join(store):
    """Epoch 2 stores 40 left rows of one key; epoch 3's one right row
    finds 40 candidates for a match buffer of 2 x 16."""
    tables = (StateTable(store, 30, SCHEMA, pk_indices=[1]),
              StateTable(store, 31, SCHEMA, pk_indices=[1]))
    left = [bar(1, 0, BarrierKind.INITIAL),
            chunk([(7, pk) for pk in range(40)], cap=64),
            bar(2, 1), bar(3, 2), bar(4, 3)]
    right = [bar(1, 0, BarrierKind.INITIAL), bar(2, 1),
             chunk([(7, 1000)]), bar(3, 2), bar(4, 3)]
    return SortedJoinExecutor(
        Script(left), Script(right), left_key_indices=[0],
        right_key_indices=[0], left_pk_indices=[1], right_pk_indices=[1],
        capacity=128, match_factor=2,
        state_tables=tables), list(tables), "match-buffer overflow"


@pytest.mark.parametrize("slow_s", [0.0, 0.1], ids=["fast", "slow_fetch"])
@pytest.mark.parametrize("build", [_overflowing_agg, _overflowing_join],
                         ids=["agg_overflow", "join_match_overflow"])
async def test_an_awaited_watchdog_fail_stops_before_the_barrier_leaves(
        build, slow_s, monkeypatch):
    """The overflow raises out of the executor before barrier 3 is yielded
    and before anything of epoch 3 is staged or committed, however long
    the watchdog's fetch leaves the loop to other tasks."""
    store = MemoryStateStore()
    ex, tables, what = build(store)
    if slow_s:
        SlowFetches(monkeypatch, slow_s)
    out = []
    with pytest.raises(RuntimeError, match=what):
        async for msg in ex.execute():
            out.append(msg)
    barriers = [m.epoch.curr for m in out if isinstance(m, Barrier)]
    assert barriers == [1, 2]
    assert not store._deferred
    committed = [len(list(t.iter_all())) for t in tables]
    store.sync(4)
    # what epoch 2 committed, and nothing of the failed epoch after a sync
    assert [len(list(t.iter_all())) for t in tables] == committed
    assert sum(committed) in (2, 40)


# --------------------------- (e) cancelled inside an awaited fetch

async def test_an_actor_cancelled_inside_an_awaited_fetch_recovers_exactly(
        tmp_path, monkeypatch):
    """The agg's actor is cancelled while its watchdog's fetch is in a
    worker thread: the generator closes, the thread finishes its wait on
    its own, and recovery rebuilds from the last committed epoch: the MV
    equals a host recount at its committed offset."""
    from oracle import committed_offsets, nexmark_prefix
    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / "d")))
    s = Session(store=store)
    await s.execute(BID)
    await s.execute(COUNTS)
    await s.tick(3)
    dep = s.catalog.mvs["counts"].deployment
    (victim,) = [task for actor, task in zip(dep.actors, dep.tasks)
                 if any(type(ex) is HashAggExecutor
                        for ex in _chain(actor.consumer))]
    slow = SlowFetches(monkeypatch, 0.3)
    tick = asyncio.create_task(s.tick(1, max_recoveries=4))
    while not slow.entered.is_set():
        await asyncio.sleep(0.002)
    assert not victim.done()
    victim.cancel()
    await tick
    monkeypatch.undo()
    assert s.recoveries >= 1
    await s.tick(2)
    got = Counter(s.query("SELECT a, n FROM counts"))
    off = committed_offsets(s, "counts")["bid"]
    exp = Counter(Counter(int(a) for a in nexmark_prefix("bid", off)[0])
                  .items())
    assert off > 0 and got == exp
    await s.drop_all()
