"""Cluster control plane (cluster/): meta + first-class compute nodes.

A 2-worker deployment over vnode-partitioned fragments must converge
bit-identically to the single-process run and to the generator-prefix
oracle; a checkpoint must refuse to commit until EVERY worker reports
sealed state; a killed worker triggers auto-recovery that re-places the
fragments over the survivor and converges exactly-once from the last
committed epoch; and the cluster HBM budget partitions per worker,
observable through SHOW memory / the worker scrapes.

Reference: meta driving compute nodes (GlobalBarrierManager per-worker
injection/collection, LocalStreamManager::build_actors, Hummock commit
after all CN sync reports).
"""

import asyncio
import os
import socket
import subprocess
import sys
import time
from collections import Counter

import numpy as np
import pytest

from risingwave_tpu.frontend import Session
from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore

AGG_DDL = [
    ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
     "chunk_size=256, splits=2, rate_limit=512)"),
    ("CREATE MATERIALIZED VIEW agg AS SELECT auction, count(*) AS n, "
     "max(price) AS mx FROM bid GROUP BY auction"),
]

W = 10_000_000
Q7_DDL = [
    ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
     "chunk_size=256, splits=2, rate_limit=512, inter_event_us=250, "
     f"emit_watermarks=1, watermark_lag_us={2 * W})"),
    ("CREATE MATERIALIZED VIEW q7 AS "
     "SELECT B.auction, B.price, B.bidder, B.date_time "
     "FROM bid B JOIN ("
     "  SELECT max(price) AS maxprice, window_end "
     f"  FROM TUMBLE(bid, date_time, {W}) GROUP BY window_end) B1 "
     "ON B.price = B1.maxprice "
     f"AND B.date_time > B1.window_end - {W} "
     "AND B.date_time <= B1.window_end"),
]


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_workers():
    """Two workers -> (ports, processes), both listeners up."""
    # no stdio pipes (pytest fd capture vs a child sharing stdio);
    # pre-pick the ports and poll for the listeners — the established
    # worker-spawn idiom (test_remote_fragment.py)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ports = [_free_port(), _free_port()]
    procs = [subprocess.Popen(
        [sys.executable, "-m", "risingwave_tpu.worker", str(port)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        for port in ports]
    deadline = time.monotonic() + 60
    waiting = list(ports)
    while waiting and time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", waiting[0]),
                                     timeout=1).close()
            waiting.pop(0)
        except OSError:
            time.sleep(0.2)
    if waiting:
        _terminate(procs)
        raise RuntimeError("worker never started listening")
    return ports, procs


def _terminate(procs) -> None:
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        p.wait(timeout=10)


@pytest.fixture(scope="module")
def two_workers():
    """ONE pair of compute nodes for every test that kills none: each
    test brings its own store (tmp_path) and its own meta session, and a
    worker serves a fresh ComputeNode per control connection."""
    ports, procs = _spawn_workers()
    yield ports, procs
    _terminate(procs)


@pytest.fixture()
def own_two_workers():
    """A pair of its own for a test that kills one."""
    ports, procs = _spawn_workers()
    yield ports, procs
    _terminate(procs)


async def _cluster_session(tmp_path, ports, name="c") -> Session:
    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / name)))
    s = Session(store=store)
    addr = ",".join(f"127.0.0.1:{p}" for p in ports)
    await s.execute(f"SET cluster = '{addr}'")
    return s


def _split_offsets(session) -> dict:
    """Committed per-split source offsets, read from the source state
    table over the META store handle (the committed manifest is exactly
    what the cluster commit protocol published)."""
    from risingwave_tpu.common.types import DataType, Field, Schema
    from risingwave_tpu.state.state_table import StateTable
    from risingwave_tpu.state.storage_table import StorageTable
    sch = Schema((Field("split_id", DataType.INT64),
                  Field("offset", DataType.INT64)))
    for tid in range(1, 40):
        st = StateTable(session.store, table_id=tid, schema=sch,
                        pk_indices=(0,))
        try:
            rows = list(StorageTable.for_state_table(st).batch_iter())
        except Exception:  # noqa: BLE001 — not this table's layout
            continue
        if rows and all(len(r) == 2 for r in rows) \
                and {r[0] for r in rows} <= {0, 1}:
            return {int(k): int(v) for k, v in rows}
    return {}


def _prefix_indices(offsets: dict, chunk_size: int, n_splits: int):
    """Global generator row indices covered by the committed per-split
    offsets (split k owns blocks b % n_splits == k — connectors/
    split.py BlockSplitConnector)."""
    idx = []
    for k, off in offsets.items():
        for j in range(off // chunk_size):
            b = j * n_splits + k
            idx.extend(range(b * chunk_size, (b + 1) * chunk_size))
    return np.asarray(sorted(idx), dtype=np.int64)


def _agg_oracle(offsets: dict, chunk_size: int = 256):
    from risingwave_tpu.connectors import NexmarkGenerator
    gen = NexmarkGenerator("bid", chunk_size=1 << 16)
    c = gen.next_chunk()
    auction = np.asarray(c.columns[0].data)
    price = np.asarray(c.columns[2].data)
    idx = _prefix_indices(offsets, chunk_size, 2)
    assert idx.size, "no committed rows"
    a, p = auction[idx], price[idx]
    cnt = Counter(a.tolist())
    mx: dict = {}
    for ai, pi in zip(a.tolist(), p.tolist()):
        mx[ai] = max(mx.get(ai, 0), pi)
    return sorted((k, cnt[k], mx[k]) for k in cnt)


async def test_two_worker_agg_bit_identical_to_single_process(
        tmp_path, two_workers):
    """Same DDL, same paced rounds: the 2-worker deployment and the
    single-process run commit identical offsets and the MV contents are
    bit-identical; both equal the generator-prefix oracle."""
    ports, _ = two_workers
    s = await _cluster_session(tmp_path, ports)
    for d in AGG_DDL:
        await s.execute(d)
    rows = await s.execute("SHOW cluster")
    assert len(rows) == 2 and all(r[2] == "alive" for r in rows)
    for _ in range(6):
        await s.tick()
    cluster_rows = sorted(s.query("SELECT auction, n, mx FROM agg"))
    offsets = _split_offsets(s)
    await s.shutdown()

    single = Session(store=HummockStateStore(
        LocalFsObjectStore(str(tmp_path / "single"))))
    for d in AGG_DDL:
        await single.execute(d)
    for _ in range(6):
        await single.tick()
    single_rows = sorted(single.query("SELECT auction, n, mx FROM agg"))
    single_offsets = _split_offsets(single)
    await single.shutdown()

    assert offsets and offsets == single_offsets, (offsets,
                                                   single_offsets)
    assert cluster_rows == single_rows
    assert cluster_rows == _agg_oracle(offsets)


async def test_two_worker_q7_converges_to_single_process(tmp_path,
                                                         two_workers):
    """The north-star q7 shape (shared source, tumble MAX agg, interval
    join) over vnode-partitioned fragments across 2 workers: results
    bit-identical to the single-process run at identical committed
    offsets."""
    ports, _ = two_workers
    s = await _cluster_session(tmp_path, ports)
    for d in Q7_DDL:
        await s.execute(d)
    # 4 rounds: the join emits from the first round and retracts at
    # the third (a higher max arrives), so the result is non-empty on
    # both runs; the equality assert is tick-count-symmetric
    for _ in range(4):
        await s.tick()
    cluster_rows = sorted(s.query(
        "SELECT auction, price, bidder, date_time FROM q7"))
    offsets = _split_offsets(s)
    await s.shutdown()

    single = Session(store=HummockStateStore(
        LocalFsObjectStore(str(tmp_path / "single"))))
    for d in Q7_DDL:
        await single.execute(d)
    for _ in range(4):
        await single.tick()
    single_rows = sorted(single.query(
        "SELECT auction, price, bidder, date_time FROM q7"))
    single_offsets = _split_offsets(single)
    await single.shutdown()

    assert offsets == single_offsets
    assert cluster_rows == single_rows
    assert cluster_rows, "q7 emitted nothing — widen the run"


async def test_checkpoint_commit_waits_for_every_worker(tmp_path):
    """The cluster commit point: a checkpoint epoch must NOT commit
    after only SOME workers reported sealed — the manifest swap waits
    for all of them (protocol-level, with stub worker handles)."""
    from risingwave_tpu.meta.barrier_manager import BarrierCoordinator

    class StubWorker:
        def __init__(self, wid):
            self.worker_id = wid
            self.sealed: dict = {}
            self.waiters: dict = {}

        async def inject(self, barrier):
            pass

        async def wait_sealed(self, epoch):
            if epoch in self.sealed:
                return self.sealed.pop(epoch)
            fut = asyncio.get_running_loop().create_future()
            self.waiters[epoch] = fut
            return await fut

        def report(self, epoch, ssts):
            if epoch in self.waiters:
                self.waiters.pop(epoch).set_result(ssts)
            else:
                self.sealed[epoch] = ssts

    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / "d")))
    coord = BarrierCoordinator(store)
    w1, w2 = StubWorker(1), StubWorker(2)
    coord.register_worker(w1)
    coord.register_worker(w2)

    async def round_trip():
        b = await coord.inject_barrier()
        coord.collect_worker(1, b.epoch.curr)
        coord.collect_worker(2, b.epoch.curr)
        await asyncio.wait_for(coord.wait_collected(b), 10)
        return b

    b0 = await round_trip()      # prev == INVALID: nothing to commit
    b1 = await round_trip()      # commits b0.curr (== b1.prev)
    b2 = await round_trip()      # commits b1.curr (== b2.prev)
    assert b1.epoch.prev == b0.epoch.curr > 0

    # only worker 1 reports sealed — the manifest must NOT move
    w1.report(b1.epoch.prev, [])
    w1.report(b2.epoch.prev, [])
    await asyncio.sleep(0.3)
    assert store.committed_epoch() == 0, \
        "committed before all workers sealed"
    assert b1.epoch.prev not in coord.committed_epochs

    # worker 2 completes both epochs; commits land strictly in order
    w2.report(b1.epoch.prev, [])
    w2.report(b2.epoch.prev, [])
    await asyncio.wait_for(coord.drain_uploads(), 10)
    assert coord.committed_epochs[-2:] == [b1.epoch.prev, b2.epoch.prev]
    assert store.committed_epoch() == b2.epoch.prev


async def test_worker_kill_auto_recovery_converges(tmp_path,
                                                   own_two_workers):
    """Kill one compute node mid-run: the lease/connection failure
    detector fails the epoch, auto-recovery re-places every fragment
    over the survivor at the ORIGINAL parallelism (same vnode bitmaps
    over the shared state), sources resume from committed offsets, and
    the MV converges to the exactly-once oracle."""
    ports, procs = own_two_workers
    s = await _cluster_session(tmp_path, ports)
    for d in AGG_DDL:
        await s.execute(d)
    for _ in range(4):
        await s.tick()
    pre = s.query("SELECT auction, n, mx FROM agg")
    assert pre, "no rows before the kill"

    procs[1].kill()
    procs[1].wait(timeout=10)
    for _ in range(5):
        await s.tick(max_recoveries=4)
    assert s.recoveries >= 1
    rows = await s.execute("SHOW cluster")
    assert [r[2] for r in rows] == ["alive"], rows

    got = sorted(s.query("SELECT auction, n, mx FROM agg"))
    offsets = _split_offsets(s)
    assert got == _agg_oracle(offsets)
    await s.shutdown()


async def test_single_worker_kill_partial_recovery(tmp_path,
                                                   own_two_workers):
    """The per-worker recovery radius: killing ONE compute node
    re-places only its actors (plus their downstream closure) onto the
    survivor — scope=worker, strictly fewer actors than the topology,
    the survivor's STORE OBJECT stays open across the recovery (no
    reset+reopen), and the MV converges bit-identical to the
    generator-prefix oracle at the committed offsets."""
    ports, procs = own_two_workers
    s = await _cluster_session(tmp_path, ports)
    for d in AGG_DDL:
        await s.execute(d)
    for _ in range(4):
        await s.tick()
    h1 = s.cluster.workers[1]
    store_id_before = (await h1.call("ping", timeout=10))["store_id"]
    all_actors = sorted(
        a for dep in s.cluster.deployments.values()
        for ids in dep.rebuild_info["actors"].values() for a in ids)

    procs[1].kill()
    procs[1].wait(timeout=10)
    for _ in range(5):
        await s.tick(max_recoveries=4)

    assert s.recoveries == 1
    assert s.last_recovery["scope"] == "worker"
    assert s.last_recovery["cause"] == "worker_death"
    rebuilt = set(s.last_recovery["actors"])
    assert rebuilt < set(all_actors), (rebuilt, all_actors)
    # the survivor kept its store OBJECT — partial recovery re-points
    # it at the committed manifest instead of reset+reopen
    store_id_after = (await h1.call("ping", timeout=10))["store_id"]
    assert store_id_after == store_id_before
    rows = await s.execute("SHOW cluster")
    assert [r[2] for r in rows] == ["alive"], rows
    got = sorted(s.query("SELECT auction, n, mx FROM agg"))
    offsets = _split_offsets(s)
    assert got == _agg_oracle(offsets)
    # keeps converging with more progress
    for _ in range(2):
        await s.tick()
    got = sorted(s.query("SELECT auction, n, mx FROM agg"))
    assert got == _agg_oracle(_split_offsets(s))
    await s.shutdown()


async def test_cluster_hbm_budget_partitioned_and_show_memory(
        tmp_path, two_workers):
    """`SET hbm_budget_bytes` on the meta session partitions evenly
    across the live workers (each node's MemoryManager gets its share),
    and SHOW memory aggregates every worker's per-executor accounting
    under a worker prefix."""
    ports, _ = two_workers
    s = await _cluster_session(tmp_path, ports)
    for d in AGG_DDL:
        await s.execute(d)
    await s.execute("SET hbm_budget_bytes = 1048576")
    for _ in range(3):
        await s.tick()

    scrapes = await s.cluster.scrape_all()
    assert set(scrapes) == {1, 2}
    for wid, text in scrapes.items():
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("hbm_budget_bytes"))
        assert float(line.rsplit(" ", 1)[1]) == 1048576 // 2, (wid, line)

    rows = await s.execute("SHOW memory")
    owners = {r[0].split("/")[0] for r in rows}
    assert {"w1", "w2"} <= owners, rows
    assert any(int(r[1]) > 0 for r in rows), rows
    await s.shutdown()


async def test_meta_metrics_merge_worker_label(tmp_path, two_workers):
    """The meta monitor's /metrics includes every worker's series under
    worker="wN" — one Prometheus scrape sees the whole cluster."""
    ports, _ = two_workers
    s = await _cluster_session(tmp_path, ports)
    for d in AGG_DDL:
        await s.execute(d)
    for _ in range(2):
        await s.tick()
    mon = await s.start_monitor(0)
    reader, writer = await asyncio.open_connection("127.0.0.1", mon.port)
    writer.write(b"GET /metrics HTTP/1.0\r\n\r\n")
    await writer.drain()
    body = (await asyncio.wait_for(reader.read(), 30)).decode()
    writer.close()
    assert 'worker="w1"' in body and 'worker="w2"' in body
    # worker barrier latencies merged next to the unlabelled meta series
    assert body.count("meta_barrier_latency_seconds_count") >= 3
    await s.shutdown()


def test_merge_worker_label_rewrites_series_lines():
    from risingwave_tpu.meta.monitor_service import merge_worker_label
    text = ("# TYPE foo counter\n"
            "foo 3\n"
            'bar{actor="1",executor="x y"} 2.5\n')
    out = merge_worker_label(text, "w7")
    assert 'foo{worker="w7"} 3' in out
    assert 'bar{worker="w7",actor="1",executor="x y"} 2.5' in out
    assert "# TYPE foo counter" in out


async def test_cluster_rejects_dict_typed_state_and_mv_on_mv(
        tmp_path, two_workers):
    """v1 contract: dict-encoded columns in durable state and MV-on-MV
    refuse the deploy loudly instead of running wrong."""
    ports, _ = two_workers
    s = await _cluster_session(tmp_path, ports)
    await s.execute(
        "CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
        "chunk_size=256, splits=2, rate_limit=512)")
    with pytest.raises(Exception, match="dict-encoded"):
        # channel is VARCHAR and lands in materialize state
        await s.execute(
            "CREATE MATERIALIZED VIEW v AS SELECT auction, channel "
            "FROM bid")
    await s.execute(
        "CREATE MATERIALIZED VIEW ok AS SELECT auction, count(*) AS n "
        "FROM bid GROUP BY auction")
    with pytest.raises(Exception, match="stream_scan|MV-on-MV"):
        await s.execute(
            "CREATE MATERIALIZED VIEW vv AS SELECT auction FROM ok")
    await s.shutdown()


async def _http_get(port: int, path: str) -> str:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
    await writer.drain()
    raw = await asyncio.wait_for(reader.read(), 30)
    writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    assert b" 200 " in head.split(b"\r\n", 1)[0], head
    return body.decode()


async def test_cluster_flight_recorder_over_real_sockets(
        tmp_path, two_workers):
    """One 2-worker deployment, the whole flight-recorder surface:

    - the meta tracer's stitched per-epoch timeline carries the span
      bundles BOTH workers shipped on their sealed reports, rendered
      by /debug/traces in every format (worker offsets relative to
      each worker's own inject receipt);
    - the on-demand profilers fan out to the workers and merge;
    - a worker-side channel stall wedges an epoch past the watchdog
      threshold, and the merged report meta prints (pulling EVERY live
      worker's own await tree over the real socket) names the stalled
      worker, its remaining actors, and the parked frame."""
    import contextlib
    import io
    import json
    ports, _ = two_workers
    s = await _cluster_session(tmp_path, ports)
    for d in AGG_DDL:
        await s.execute(d)
    for _ in range(3):
        await s.tick()
    mon = await s.start_monitor(0)

    payload = json.loads(await _http_get(
        mon.port, "/debug/traces?format=json"))
    assert payload["traces"], payload
    stitched = [t for t in payload["traces"]
                if {"1", "2"} <= set(t.get("worker_spans", {}))]
    assert stitched, [sorted(t.get("worker_spans", {}))
                      for t in payload["traces"]]

    text = await _http_get(mon.port, "/debug/traces")
    assert "-- w1" in text and "-- w2" in text, text

    # chrome export keeps the worker attribution as pids 1 and 2
    events = json.loads(await _http_get(
        mon.port, "/debug/traces?format=chrome"))
    assert {1, 2} <= {e["pid"] for e in events}, events[:5]

    # profilers merge worker output under wN prefixes next to the
    # meta-local sections
    from risingwave_tpu.utils.profiler import parse_collapsed
    cpu = await _http_get(mon.port, "/debug/profile/cpu?seconds=0.3")
    stacks = parse_collapsed(cpu)
    assert stacks, cpu[:500]
    assert any(frames[0] in ("w1", "w2")
               for frames, _ in stacks), cpu[:500]
    heap = await _http_get(mon.port, "/debug/profile/heap?seconds=0.3")
    assert "# heap profile" in heap
    assert "w1/" in heap or "w2/" in heap, heap[:500]
    dev = await _http_get(mon.port, "/debug/profile/device")
    assert "# device profile" in dev
    assert "w1/" in dev and "w2/" in dev, dev[:500]

    await s.execute("SET barrier_stall_threshold_ms = 400")
    # rides the cluster config push: each worker's process-global
    # injector arms, and its ChannelInput consumer parks 1.5s on the
    # next matching chunk (fires once — at=1,times=1 defaults)
    await s.execute(
        "SET fault_injection = 'channel_stall:ms=1500'")
    try:
        err = io.StringIO()
        with contextlib.redirect_stderr(err):
            for _ in range(3):
                await s.tick()
        report = err.getvalue()
        assert "[stuck barrier]" in report, report[:2000] or "(empty)"
        assert "remaining actors" in report
        # one section per live worker, each with its own await tree
        assert "== worker w1 ==" in report, report
        assert "== worker w2 ==" in report, report
        assert "task " in report, report
        # the stall also landed in the durable event log
        stalls = s.event_log.records(kind="barrier_stall")
        assert stalls and stalls[-1]["remaining"], stalls
    finally:
        # the workers are the module's: whatever happens above, the next
        # test gets them with nothing armed
        await s.execute("SET fault_injection = ''")
    await s.shutdown()
