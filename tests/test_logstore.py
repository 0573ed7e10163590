"""Changelog log store: exactly-once sinks, atomic log+checkpoint
commit, subscription backfill-then-tail, serving replicas.

Reference: src/stream/src/common/log_store_impl/ — the epoch batch
persists WITH the checkpoint, delivery happens after the commit, and
target-side sequence dedupe absorbs the crash window. The kill matrix
here proves the whole claim: a file-sink target receives every
committed epoch exactly once (no dupes, no drops) across a crash
injected at every interesting point of the delivery path.
"""

import asyncio
import json

import numpy as np
import pytest

from risingwave_tpu.frontend import Session
from risingwave_tpu.logstore import ChangelogSubscription, ServingReplica
from risingwave_tpu.logstore.log import MvChangelog, SinkChangelog
from risingwave_tpu.state import (
    HummockStateStore, LocalFsObjectStore, MemoryStateStore,
)


# ------------------------------------------------------------ unit layer

def test_sink_changelog_seq_resume_and_truncate():
    """Sequence numbers mint densely, resume from the COMMITTED prefix
    after a crash (staged entries die), and truncation below the cursor
    rides a later epoch."""
    store = MemoryStateStore()
    log = SinkChangelog(store, table_id=77, schema=_kv_schema())
    assert log.append(100, [(0, (1, 10))]) == 1
    assert log.append(200, [(0, (2, 20))]) == 2
    # nothing committed yet: the committed view is empty
    assert list(log.read_committed(0)) == []
    store.sync(200)
    got = list(log.read_committed(0))
    assert [(s, e) for s, e, _r in got] == [(1, 100), (2, 200)]
    assert got[0][2] == [(0, (1, 10))]

    # crash: staged seq 3 dies; a fresh writer re-mints 3
    log.append(300, [(0, (3, 30))])
    store.reset_uncommitted()
    log2 = SinkChangelog(store, table_id=77, schema=_kv_schema())
    assert log2.append(301, [(0, (3, 31))]) == 3
    store.sync(301)

    # cursor + truncation commit together; entries <= cursor vanish
    log2.persist_cursor(400, delivered_seq=2)
    store.sync(400)
    assert log2.read_cursor() == 2
    assert [s for s, _e, _r in log2.read_committed(0)] == [3]
    # a writer opening after the truncation still resumes past it
    log3 = SinkChangelog(store, table_id=77, schema=_kv_schema())
    assert log3.append(500, [(0, (4, 40))]) == 4


def test_mv_changelog_epoch_merge_and_activation():
    """Per-writer sub-entries of one epoch merge; inactive writers drop
    their buffer at the barrier; activation preserves the open
    interval."""
    store = MemoryStateStore()
    log = MvChangelog(store, table_id=88, schema=_kv_schema(),
                      pk_indices=(0,), n_writers=2)
    w0, w1 = log.writers
    w0.on_rows([(1, (1, 10))])
    w0.on_barrier(100)            # inactive: dropped
    store.sync(100)
    assert list(log.read_committed(0)) == []

    w0.on_rows([(1, (2, 20))])    # open interval buffered...
    log.activate(100)             # ...and preserved across activation
    w1.on_rows([(1, (3, 30))])
    w0.on_barrier(200)
    w1.on_barrier(200)
    store.sync(200)
    got = list(log.read_committed(100))
    assert len(got) == 1
    epoch, rows = got[0]
    assert epoch == 200
    assert sorted(r[0] for _op, r in rows) == [2, 3]
    # cursor semantics: nothing at or below the floor
    assert list(log.read_committed(200)) == []


def _kv_schema():
    from risingwave_tpu.common import DataType, schema
    return schema(("k", DataType.INT64), ("v", DataType.INT64))


# -------------------------------------------------- kill-at-any-point

def _write_rows(path: str, rows) -> None:
    with open(path, "a") as f:
        for k, v in rows:
            f.write(json.dumps({"k": k, "v": v}) + "\n")


async def _run_sink_session(tmp_path, kill_at: int, kill_mode: str,
                            tag: str):
    """One full lifecycle over a durable store: 30 source rows arrive in
    3 waves, a crash is injected at the `kill_at`-th target write
    (`before` the write lands, or `after` it lands but before the
    cursor can advance), auto-recovery rides tick. Returns the
    delivered (seq, rows) records."""
    from risingwave_tpu.stream.sink import FileSink
    d = str(tmp_path / f"data_{tag}")
    src_path = str(tmp_path / f"src_{tag}.jsonl")
    out_path = str(tmp_path / f"out_{tag}.jsonl")
    open(src_path, "w").close()

    real_write = FileSink.write
    state = {"n": 0, "armed": kill_at > 0}

    def crashing_write(self, seq, epoch, rows):
        if state["armed"]:
            state["n"] += 1
            if state["n"] == kill_at:
                state["armed"] = False
                if kill_mode == "after":
                    real_write(self, seq, epoch, rows)
                raise RuntimeError(
                    f"injected sink crash ({kill_mode} write {kill_at})")
        return real_write(self, seq, epoch, rows)

    FileSink.write = crashing_write
    try:
        s = Session(store=HummockStateStore(LocalFsObjectStore(d)))
        await s.execute(
            f"CREATE SOURCE src WITH (connector='jsonl', "
            f"path='{src_path}', columns='k int64, v int64')")
        await s.execute(
            f"CREATE SINK f AS SELECT k, v FROM src "
            f"WITH (connector='file', path='{out_path}')")
        for wave in range(5):
            _write_rows(src_path, [(wave * 6 + i, (wave * 6 + i) * 7)
                                   for i in range(6)])
            await s.tick(2, max_recoveries=4)
        # the injected crash may also fire during these settle ticks
        await s.tick(2, max_recoveries=4)
        await s.drop_all()
    finally:
        FileSink.write = real_write
    recs = [json.loads(ln) for ln in open(out_path) if ln.strip()]
    return recs, state, s.recoveries


@pytest.mark.parametrize("kill_at,kill_mode", [
    (0, "none"),                   # control: no crash
    (1, "before"), (1, "after"),   # first delivery
    (2, "before"), (3, "after"),   # mid-stream
    (4, "before"), (5, "after"),   # late (after recoveries settled)
])
async def test_kill_at_any_point_exactly_once(tmp_path, kill_at,
                                              kill_mode):
    """THE acceptance gate: across a crash at any point of the delivery
    path, the file-sink target receives every committed epoch exactly
    once — sequence numbers dense and duplicate-free, content exactly
    the source rows, nothing dropped, nothing doubled."""
    recs, state, recoveries = await _run_sink_session(
        tmp_path, kill_at, kill_mode, f"{kill_at}{kill_mode}")
    if kill_at > 0:
        # the injected crash must actually have fired AND recovered —
        # otherwise the exactly-once claim below is vacuous
        assert not state["armed"], \
            f"kill point {kill_at} never reached ({state['n']} writes)"
        assert recoveries >= 1
    seqs = [r["seq"] for r in recs]
    assert seqs == list(range(1, len(seqs) + 1)), \
        f"sequence not dense/unique: {seqs}"
    delivered = [tuple(vals) for r in recs for _op, vals in r["rows"]]
    expected = [(i, i * 7) for i in range(30)]
    assert delivered == expected, (
        f"kill {kill_mode}@{kill_at}: delivered {len(delivered)} rows, "
        f"first diff at "
        f"{next((i for i, (a, b) in enumerate(zip(delivered, expected)) if a != b), 'len')}")


async def test_crash_between_seal_and_commit_replays_cleanly(tmp_path):
    """A crash after the log entry sealed but BEFORE the manifest swap:
    the entry dies with the epoch (it was never visible to delivery),
    recovery replays the interval, the re-minted sequence number
    matches, and the target still sees everything exactly once."""
    d = str(tmp_path / "data")
    src_path = str(tmp_path / "src.jsonl")
    out_path = str(tmp_path / "out.jsonl")
    open(src_path, "w").close()
    _write_rows(src_path, [(i, i) for i in range(10)])

    store = HummockStateStore(LocalFsObjectStore(d))
    real_commit = HummockStateStore.commit_sealed
    state = {"n": 0, "armed": True}

    def crashing_commit(self, batch):
        if state["armed"]:
            state["n"] += 1
            if state["n"] == 2:
                state["armed"] = False
                raise RuntimeError("injected crash between seal and commit")
        return real_commit(self, batch)

    HummockStateStore.commit_sealed = crashing_commit
    try:
        s = Session(store=store)
        await s.execute(
            f"CREATE SOURCE src WITH (connector='jsonl', "
            f"path='{src_path}', columns='k int64, v int64')")
        await s.execute(
            f"CREATE SINK f AS SELECT k, v FROM src "
            f"WITH (connector='file', path='{out_path}')")
        await s.tick(3, max_recoveries=4)
        _write_rows(src_path, [(10 + i, 10 + i) for i in range(5)])
        await s.tick(3, max_recoveries=4)
        await s.drop_all()
    finally:
        HummockStateStore.commit_sealed = real_commit
    recs = [json.loads(ln) for ln in open(out_path) if ln.strip()]
    seqs = [r["seq"] for r in recs]
    assert seqs == list(range(1, len(seqs) + 1))
    delivered = [tuple(vals) for r in recs for _op, vals in r["rows"]]
    assert delivered == [(i, i) for i in range(15)]
    assert s.recoveries >= 1


async def test_log_truncates_below_durable_cursor(tmp_path):
    """The delivery cursor persists with checkpoints and the log
    truncates below it — the log stays bounded by delivery lag."""
    d = str(tmp_path / "data")
    src_path = str(tmp_path / "src.jsonl")
    out_path = str(tmp_path / "out.jsonl")
    open(src_path, "w").close()
    s = Session(store=HummockStateStore(LocalFsObjectStore(d)))
    await s.execute(
        f"CREATE SOURCE src WITH (connector='jsonl', path='{src_path}', "
        f"columns='k int64, v int64')")
    await s.execute(
        f"CREATE SINK f AS SELECT k, v FROM src "
        f"WITH (connector='file', path='{out_path}')")
    for wave in range(4):
        _write_rows(src_path, [(wave, wave)])
        await s.tick(2)
    log = s.catalog.sinks["f"].executor.log
    assert log.read_cursor() >= 1
    # committed entries at or below the durable cursor were tombstoned
    live = [seq for seq, _e, _r in log.read_committed(0)]
    assert all(seq > log.read_cursor() for seq in live)
    await s.drop_all()


# ---------------------------------------------------------- subscriptions

async def test_subscription_backfill_then_tail_no_gap_overlap():
    """Backfill at committed E0, tail strictly ascending epochs > E0;
    applying backfill + tail reproduces the MV exactly."""
    s = Session()
    await s.execute("CREATE TABLE t (k int64, v int64)")
    await s.execute("INSERT INTO t VALUES (1, 10), (2, 20)")
    await s.tick(2)
    sub = ChangelogSubscription(s.coord.logstore, "t")
    start = asyncio.create_task(sub.start())
    await s.tick(1)               # commit past the activation floor
    backfill = await start
    e0 = backfill["epoch"]
    applied = {tuple(r[i] for i in backfill["pk_indices"]): tuple(r)
               for r in backfill["rows"]}
    assert len(applied) == 2

    seen_epochs = []
    for wave in range(3):
        await s.execute(f"INSERT INTO t VALUES ({3 + wave}, {30 + wave})")
        await s.tick(2)
        epoch, rows = await sub.next_batch(timeout=15)
        seen_epochs.append(epoch)
        for op, row in rows:
            pk = tuple(row[i] for i in backfill["pk_indices"])
            if op == -1:
                applied.pop(pk, None)
            else:
                applied[pk] = tuple(row)
    # no overlap with the backfill, no gaps, strictly ascending
    assert all(e > e0 for e in seen_epochs)
    assert seen_epochs == sorted(seen_epochs)
    assert len(set(seen_epochs)) == len(seen_epochs)
    # the MV carries a hidden _row_id pk; SELECT * projects it away —
    # compare the visible columns exactly (count + content)
    q_rows = s.query("SELECT * FROM t")
    assert sorted((r[0], r[1]) for r in applied.values()) == \
        sorted(tuple(r) for r in q_rows)
    sub.close()
    rows = s.show("subscriptions")
    assert not any(r[1] == "changelog" for r in rows)
    await s.drop_all()


async def test_subscription_unknown_mv_rejected():
    from risingwave_tpu.logstore import SubscribeError
    s = Session()
    sub = ChangelogSubscription(s.coord.logstore, "nope")
    with pytest.raises(SubscribeError):
        await sub.start()


async def test_activation_floor_covers_an_epoch_dropped_before_its_collect():
    """A writer seals an epoch at ITS barrier, before the coordinator
    collects that barrier. A subscribe landing in between activated the
    log at the older collected epoch and snapshotted there: the epoch
    the inactive writer had just dropped was in neither the snapshot
    nor the log, and the replica missed its rows for good (seen one run
    in three under load). The floor now covers the dropped epoch."""
    s = Session()
    await s.execute(
        "CREATE SOURCE src WITH (connector='nexmark', table='auction', "
        "chunk_size=64, rate_limit=128, primary_key='id')")
    await s.execute("CREATE MATERIALIZED VIEW mv AS SELECT id FROM src")
    await s.tick(2)
    log = s.coord.logstore.mv_logs["mv"]
    assert not log.active
    collected = s.coord.logstore.collected_epoch
    # the materialize executor passes the next barrier (log inactive:
    # the interval's rows are dropped) ...
    log.writers[0].on_rows([(0, (1,))])
    log.writers[0].on_barrier(collected + 1)
    # ... and a subscriber activates before the coordinator collects it
    log.activate(collected)
    assert log.active_from == collected + 1
    log.deactivate()
    await s.drop_all()
    await s.shutdown()


async def test_replica_bit_identical_under_concurrent_barriers():
    """A serving replica over a real socket answers point lookups
    bit-identical to the meta-side serving cache while barriers keep
    flowing — the acceptance's second clause."""
    s = Session()
    await s.execute(
        "CREATE SOURCE src WITH (connector='nexmark', table='auction', "
        "chunk_size=64, rate_limit=128, primary_key='id')")
    await s.execute(
        "CREATE MATERIALIZED VIEW mv AS "
        "SELECT id, seller, reserve FROM src")
    await s.tick(2)
    # warm the meta-side serving cache (first touch marks wanted)
    s.query("SELECT * FROM mv")
    await s.tick(1)
    srv = await s.start_subscription_server(0)

    stop = asyncio.Event()

    async def ticker():
        while not stop.is_set():
            await s.tick(1)
            await asyncio.sleep(0)

    tick_task = asyncio.create_task(ticker())
    try:
        rep = await ServingReplica.connect("127.0.0.1", srv.port, "mv")
        for _ in range(4):
            await asyncio.sleep(0.05)
            # compare at a matched epoch: wait until the replica caught
            # up to the meta cache's published snapshot
            snap = s.coord.serving._mvs["mv"].cache.snapshot
            await rep.wait_epoch(snap.epoch, timeout=20)
            snap2 = s.coord.serving._mvs["mv"].cache.snapshot
            if snap2.epoch != snap.epoch or rep.epoch != snap.epoch:
                continue              # barriers moved on; try next round
            mc, mv_ = snap.compact()
            rc, rv = rep.rows()
            assert all(a.dtype == b.dtype and np.array_equal(a, b)
                       for a, b in zip(mc, rc))
            assert all(np.array_equal(a, b) for a, b in zip(mv_, rv))
            # point lookups answer identically from both sides
            if snap.row_count:
                pk0 = next(iter(snap.pk_index))
                pos = snap.lookup(pk0)
                cols, _ = snap.point_rel(pos)
                meta_row = tuple(c[0].item() for c in cols)
                assert rep.lookup(pk0) == meta_row
            assert rep.lookup((-(10 ** 12),)) is None
    finally:
        stop.set()
        await tick_task
    # the replica kept applying batches while barriers flowed
    assert rep.batches_applied > 0
    await rep.close()
    await s.drop_all()
    await s.shutdown()


async def test_replica_catches_up_exact_final_state():
    """After quiescing, the replica equals the meta cache exactly —
    including through deletes (TopN retractions exercise OP_DEL)."""
    s = Session()
    await s.execute("CREATE TABLE t (k int64, v int64)")
    await s.execute("INSERT INTO t VALUES (1, 1), (2, 2), (3, 3)")
    await s.tick(2)
    s.query("SELECT * FROM t")        # warm meta cache
    await s.tick(1)
    srv = await s.start_subscription_server(0)
    connect = asyncio.create_task(
        ServingReplica.connect("127.0.0.1", srv.port, "t"))
    await s.tick(1)
    rep = await connect
    await s.execute("INSERT INTO t VALUES (4, 4), (5, 5)")
    await s.tick(2)
    snap = s.coord.serving._mvs["t"].cache.snapshot
    await rep.wait_epoch(snap.epoch, timeout=20)
    mc, mval = snap.compact()
    rc, rv = rep.rows()
    assert all(np.array_equal(a, b) for a, b in zip(mc, rc))
    assert all(np.array_equal(a, b) for a, b in zip(mval, rv))
    await rep.close()
    await s.drop_all()
    await s.shutdown()


async def test_replica_disconnect_never_fails_the_stream():
    """A subscriber vanishing (process death, network) closes its
    subscription; barriers and sink delivery keep flowing."""
    s = Session()
    await s.execute("CREATE TABLE t (k int64, v int64)")
    await s.execute("INSERT INTO t VALUES (1, 1)")
    await s.tick(2)
    srv = await s.start_subscription_server(0)
    connect = asyncio.create_task(
        ServingReplica.connect("127.0.0.1", srv.port, "t"))
    await s.tick(1)
    rep = await connect
    # abrupt connection death (no unsubscribe handshake)
    await rep.conn.close()
    await s.execute("INSERT INTO t VALUES (2, 2)")
    await s.tick(3)               # must not raise / recover
    assert s.recoveries == 0
    assert s.query("SELECT count(*) FROM t")[0][0] == 2
    await s.drop_all()
    await s.shutdown()


async def test_parallel_materialize_serving_registration():
    """The carried serving gap: an MV whose materialize fragment is
    PARALLEL now registers with the serving manager (one hook per
    actor) and serves from the cache, bit-identical to the scan path."""
    from risingwave_tpu.common import DataType, schema as mk_schema
    from risingwave_tpu.plan import BuildEnv, build_graph
    from risingwave_tpu.plan.graph import (
        Exchange, Fragment, Node, StreamGraph)
    from risingwave_tpu.meta import BarrierCoordinator

    store = MemoryStateStore()
    coord = BarrierCoordinator(store)
    env = BuildEnv(store, coord)
    g = StreamGraph()
    g.add(Fragment(1, Node("nexmark_source",
                           dict(table="bid", chunk_size=64,
                                rate_limit=256, durable=True)),
                   dispatch="hash", dist_key_indices=(0,)))
    g.add(Fragment(2, Node("materialize", dict(pk_indices=[0, 3]),
                           inputs=(Exchange(1),)),
                   parallelism=2))
    dep = build_graph(g, env)
    roots = dep.roots[2]
    assert len(roots) == 2
    hooks = coord.serving.register_mv(
        "pmv", roots[0].table, roots[0].table.schema,
        roots[0].table.pk_indices, n_hooks=len(roots))
    for r, h in zip(roots, hooks):
        r.serving_hook = h
    dep.spawn()
    await coord.run_rounds(2)
    # touch -> wanted -> built at the next collected barrier
    assert coord.serving.pin(["pmv"]) is None
    await coord.run_rounds(2)
    pins = coord.serving.pin(["pmv"])
    assert pins is not None
    try:
        cache_cols, cache_valids = pins["pmv"].compact()
        from risingwave_tpu.state.storage_table import StorageTable
        await coord.drain_uploads()
        storage = StorageTable.for_state_table(roots[0].table)
        rows, _keys = storage.snapshot_with_keys(
            max_epoch=coord.serving.collected_epoch)
        assert pins["pmv"].row_count == len(rows)
        for j in range(len(cache_cols)):
            scan_col = np.asarray(
                [0 if r[j] is None else r[j] for r in rows],
                dtype=cache_cols[j].dtype)
            assert np.array_equal(cache_cols[j], scan_col)
    finally:
        coord.serving.unpin(pins)
    await coord.stop_all()
    for t in dep.tasks:
        if not t.done():
            t.cancel()


async def test_send_blocked_seconds_sender_attribution():
    """Satellite: seconds parked on a FULL downstream channel are
    charged to the SENDING actor's series (the receiver-labelled
    blocked_put series stays — it names the culprit)."""
    from risingwave_tpu.stream.exchange import Channel
    from risingwave_tpu.utils.metrics import MetricsRegistry
    reg = MetricsRegistry()
    ch = Channel(capacity=1)
    ch.send_obs = reg.counter(
        "stream_exchange_send_blocked_seconds_total",
        actor="7", executor="x", output="0")
    await ch.send(1)

    async def drain_later():
        await asyncio.sleep(0.1)
        await ch.recv()

    t = asyncio.ensure_future(drain_later())
    await ch.send(2)              # blocks ~0.1s on the full queue
    await t
    assert ch.send_obs.value >= 0.05
    await ch.recv()


async def test_send_blocked_series_registered_at_debug():
    """End-to-end: at metric_level=debug a deployed pipeline carries
    sender-labelled send-blocked series in the registry."""
    s = Session()
    await s.execute("SET metric_level = 'debug'")
    await s.execute("CREATE SOURCE bid WITH (connector='nexmark', "
                    "table='bid', chunk_size=64, rate_limit=128)")
    await s.execute("CREATE MATERIALIZED VIEW mv AS "
                    "SELECT auction, max(price) FROM bid GROUP BY auction")
    await s.tick(2)
    from risingwave_tpu.utils.metrics import GLOBAL_METRICS
    names = {name for (name, _labels) in GLOBAL_METRICS.counters}
    assert "stream_exchange_send_blocked_seconds_total" in names
    await s.drop_all()
    # series die with the deployment (no lingering labels in scrapes)
    assert not any(
        name == "stream_exchange_send_blocked_seconds_total"
        for (name, _labels) in GLOBAL_METRICS.counters)


# ------------------------------------- durable cursors + retention (r9)

async def test_durable_cursor_resume_skips_backfill(tmp_path):
    """A NAMED subscription persists its delivered-through epoch with
    each checkpoint; reconnecting under the same name resumes the tail
    from the durable cursor — no backfill rows ship, the log stayed
    active while nobody was connected, and the resumed tail continues
    strictly past the cursor."""
    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / "d")))
    s = Session(store=store)
    await s.execute("CREATE SOURCE bid WITH (connector='nexmark', "
                    "table='bid', chunk_size=64, rate_limit=128)")
    await s.execute("CREATE MATERIALIZED VIEW mv AS "
                    "SELECT auction, price FROM bid "
                    "WHERE price > 1000000")
    await s.tick(2)
    sub = ChangelogSubscription(s.coord.logstore, "mv", cursor_name="r1")
    start = asyncio.create_task(sub.start())
    await s.tick(1)
    backfill = await start
    assert not backfill.get("resume")
    await s.tick(3)
    delivered = []
    while not sub.queue.empty():
        delivered.append(sub.queue.get_nowait())
    assert delivered
    sub.close()

    log = s.coord.logstore.mv_logs["mv"]
    # the durable cursor keeps the log ACTIVE (and retention pinned)
    # while the subscriber is away — that is the whole point
    assert log.active
    # the committed cursor may LAG the delivered tail by the delivery-
    # to-checkpoint window, but it exists and sits in the tail
    cursor = log.read_sub_cursor("r1")
    assert cursor is not None and cursor >= backfill["epoch"]
    await s.tick(3)

    sub2 = ChangelogSubscription(s.coord.logstore, "mv",
                                 cursor_name="r1")
    backfill2 = await sub2.start()
    assert backfill2.get("resume") is True
    assert "rows" not in backfill2
    await s.tick(2)
    resumed = []
    while not sub2.queue.empty():
        resumed.append(sub2.queue.get_nowait())
    assert resumed
    assert all(e > backfill2["epoch"] for e, _r in resumed)
    assert [e for e, _ in resumed] == sorted(e for e, _ in resumed)
    sub2.close()
    await s.drop_all()


async def test_mv_changelog_retention_truncates_below_min_cursor(
        tmp_path):
    """Entries below the minimum subscriber cursor (live pumps AND
    durable named cursors) are tombstoned at checkpoint commit — the
    log is bounded by subscriber lag, mirroring the sink log's
    delivery-cursor truncation."""
    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / "d")))
    s = Session(store=store)
    await s.execute("CREATE SOURCE bid WITH (connector='nexmark', "
                    "table='bid', chunk_size=64, rate_limit=128)")
    await s.execute("CREATE MATERIALIZED VIEW mv AS "
                    "SELECT auction, price FROM bid "
                    "WHERE price > 1000000")
    await s.tick(2)
    sub = ChangelogSubscription(s.coord.logstore, "mv", cursor_name="r1")
    start = asyncio.create_task(sub.start())
    await s.tick(1)
    backfill = await start
    await s.tick(6)
    delivered = 0
    while not sub.queue.empty():
        sub.queue.get_nowait()
        delivered += 1
    assert delivered >= 3, "append-only MV must change every interval"
    log = s.coord.logstore.mv_logs["mv"]
    # retention advanced with the pump cursor...
    assert log.truncated_below > 0
    # ...and the committed log retains strictly fewer entries than were
    # delivered (the consumed prefix is tombstoned; only the suffix
    # inside the cursor-to-checkpoint window survives)
    entries = list(log.read_committed(0))
    assert len(entries) < delivered
    assert all(e > backfill["epoch"] for e, _ in entries)
    sub.close()
    await s.drop_all()


async def test_durable_cursor_survives_session_restart(tmp_path):
    """Crash + catalog recovery: the durable cursor (committed with the
    checkpoints) re-activates the rebuilt MV log at registration, so a
    reconnect under the same name still RESUMES instead of
    re-backfilling — and applying the resumed tail over the
    pre-restart snapshot equals the post-restart MV exactly."""
    data = str(tmp_path / "d")
    store = HummockStateStore(LocalFsObjectStore(data))
    s = Session(store=store)
    await s.execute("CREATE SOURCE bid WITH (connector='nexmark', "
                    "table='bid', chunk_size=64, rate_limit=128)")
    await s.execute("CREATE MATERIALIZED VIEW mvw AS "
                    "SELECT window_end, max(price) AS maxprice "
                    "FROM TUMBLE(bid, date_time, 1000000) "
                    "GROUP BY window_end")
    await s.tick(2)
    sub = ChangelogSubscription(s.coord.logstore, "mvw",
                                cursor_name="rep")
    start = asyncio.create_task(sub.start())
    await s.tick(1)
    backfill = await start
    state = {tuple(r[i] for i in backfill["pk_indices"]): tuple(r)
             for r in backfill["rows"]}
    await s.tick(4)
    applied_through = backfill["epoch"]
    while not sub.queue.empty():
        epoch, rows = sub.queue.get_nowait()
        for op, row in rows:
            pk = tuple(row[i] for i in backfill["pk_indices"])
            if op == -1:
                state.pop(pk, None)
            else:
                state[pk] = tuple(row)
        applied_through = epoch

    # hard crash; the durable cursor may lag what we applied by the
    # delivery-to-checkpoint window
    await s.crash()
    s2 = Session(store=HummockStateStore(LocalFsObjectStore(data)))
    await s2.recover()
    log2 = s2.coord.logstore.mv_logs["mvw"]
    assert log2.active, "durable cursor must re-activate the log"
    assert log2.read_sub_cursor("rep") is not None

    sub2 = ChangelogSubscription(s2.coord.logstore, "mvw",
                                 cursor_name="rep")
    backfill2 = await sub2.start()
    assert backfill2.get("resume") is True
    await s2.tick(4)
    while not sub2.queue.empty():
        epoch, rows = sub2.queue.get_nowait()
        if epoch <= applied_through:
            continue              # cursor-lag re-delivery window
        for op, row in rows:
            pk = tuple(row[i] for i in backfill["pk_indices"])
            if op == -1:
                state.pop(pk, None)
            else:
                state[pk] = tuple(row)
    expect = sorted(s2.query("SELECT window_end, maxprice FROM mvw"))
    assert sorted(state.values()) == expect
    sub2.close()
    await s2.drop_all()


async def test_replica_resubscribe_resumes_over_socket(tmp_path):
    """Socket-level reconnect: a replica with a cursor name drops its
    connection, resubscribes, gets a RESUME (no backfill rows ship),
    and the tail keeps advancing its snapshot — answers stay correct
    (auction rows are insert-only, so any pk the replica holds must
    equal the meta MV's row for that pk)."""
    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / "d")))
    s = Session(store=store)
    await s.execute(
        "CREATE SOURCE src WITH (connector='nexmark', table='auction', "
        "chunk_size=64, rate_limit=128, primary_key='id')")
    await s.execute("CREATE MATERIALIZED VIEW mv AS "
                    "SELECT id, seller, reserve FROM src")
    await s.tick(2)
    await s.start_subscription_server(0)
    port = s.subscriptions.port
    task = asyncio.create_task(
        ServingReplica.connect("127.0.0.1", port, "mv",
                               cursor_name="rep"))
    await s.tick(2)
    replica = await task
    assert not replica.resumed
    await s.tick(3)
    rows_before = replica.cache.snapshot.row_count

    # drop the connection (server keeps the durable cursor + the log)
    await replica.conn.close()
    await s.tick(2)
    await replica.resubscribe("127.0.0.1", port)
    assert replica.resumed, "reconnect must resume, not re-backfill"
    applied_at_resume = replica.batches_applied
    for _ in range(20):
        await s.tick(1)
        if replica.batches_applied > applied_at_resume:
            break
    assert replica.batches_applied > applied_at_resume, \
        "tail must keep flowing after the resume"
    assert replica.cache.snapshot.row_count > rows_before
    # insert-only rows never mutate: every pk the replica holds answers
    # exactly like the meta MV
    meta = {r[0]: tuple(r)
            for r in s.query("SELECT id, seller, reserve FROM mv")}
    checked = 0
    for pk in list(replica.cache.snapshot.pk_index)[:8]:
        got = replica.lookup(pk)
        # the state table may carry trailing hidden columns the SELECT
        # projects away; the visible prefix must match exactly
        assert got[:3] == meta[got[0]]
        checked += 1
    assert checked > 0
    await replica.close()
    await s.stop_subscription_server()
    await s.drop_all()
    await s.shutdown()


async def test_cursor_ttl_lease_releases_retention(tmp_path):
    """A durable named cursor with NO live subscriber for longer than
    `subscription_cursor_ttl_ms` stops holding the MV changelog: the
    cursor is tombstoned durably, retention advances, the log
    deactivates when nothing else pins it, and a resubscribe under the
    same name falls back to backfill-then-tail instead of resuming."""
    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / "d")))
    s = Session(store=store)
    await s.execute("SET subscription_cursor_ttl_ms = 150")
    await s.execute("CREATE SOURCE bid WITH (connector='nexmark', "
                    "table='bid', chunk_size=64, rate_limit=128)")
    await s.execute("CREATE MATERIALIZED VIEW mv AS "
                    "SELECT auction, price FROM bid "
                    "WHERE price > 1000000")
    await s.tick(2)
    sub = ChangelogSubscription(s.coord.logstore, "mv", cursor_name="r1")
    start = asyncio.create_task(sub.start())
    await s.tick(1)
    backfill = await start
    await s.tick(2)
    sub.close()                 # subscriber abandons its cursor
    log = s.coord.logstore.mv_logs["mv"]
    assert log.active           # still pinned: lease not lapsed yet
    # resubscribe WITHIN the TTL still resumes
    sub2 = ChangelogSubscription(s.coord.logstore, "mv",
                                 cursor_name="r1")
    assert (await sub2.start()).get("resume") is True
    sub2.close()
    # lease lapses: the next commit pulse drops the cursor durably and
    # the log stops holding anything
    await asyncio.sleep(0.25)
    await s.tick(2)
    assert log.read_sub_cursor("r1") is None, \
        "expired cursor must be tombstoned durably"
    assert not log.active, "nothing pins the log once the lease lapsed"
    # after the TTL a resubscribe under the name is a FRESH backfill
    sub3 = ChangelogSubscription(s.coord.logstore, "mv",
                                 cursor_name="r1")
    start3 = asyncio.create_task(sub3.start())
    await s.tick(1)
    backfill3 = await start3
    assert not backfill3.get("resume")
    assert "rows" in backfill3
    sub3.close()
    await s.drop_all()


async def test_cursor_ttl_zero_never_expires(tmp_path):
    """Default TTL (0): an abandoned cursor pins the log indefinitely —
    the pre-TTL behavior stays the default (drop_sub_cursor is the only
    release)."""
    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / "d")))
    s = Session(store=store)
    await s.execute("CREATE SOURCE bid WITH (connector='nexmark', "
                    "table='bid', chunk_size=64, rate_limit=128)")
    await s.execute("CREATE MATERIALIZED VIEW mv AS "
                    "SELECT auction, price FROM bid "
                    "WHERE price > 1000000")
    await s.tick(2)
    sub = ChangelogSubscription(s.coord.logstore, "mv", cursor_name="r1")
    start = asyncio.create_task(sub.start())
    await s.tick(1)
    await start
    await s.tick(2)
    sub.close()
    await asyncio.sleep(0.15)
    await s.tick(2)
    log = s.coord.logstore.mv_logs["mv"]
    assert log.active
    assert log.read_sub_cursor("r1") is not None
    await s.drop_all()
