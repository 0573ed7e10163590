"""Chaos gate — deterministic fault injection over a q7-shaped durable run.

Every fault class the FaultInjector models (utils/faults.py) is injected
into its own fresh durable session running the q7 window aggregation
(source -> project -> tumble project -> hash_agg -> materialize: four
fragments, four actors — the same shape the recovery tests and
logstore gate use):

  mv_actor_crash   actor exception at the TERMINAL (materialize)
                   fragment -> blast radius is one fragment: partial
                   recovery rebuilds ONLY that actor; the agg fragment
                   keeps its device state and the exchange channels
                   replay the in-flight interval
  poison_chunk     corrupt payload kills the CONSUMING (materialize)
                   actor -> same partial scope
  interior_crash   actor exception at an INTERIOR fragment (hash_agg,
                   which has a downstream consumer) -> scope=CONE: the
                   agg AND its downstream materialize rebuild together,
                   the upstream source/project chain keeps its device
                   state, the cone's inbound frontier replays
  mesh_crash       the FUSED MESH fragment (streaming_parallelism_
                   devices=2 on the virtual mesh) crashes -> scope=MESH:
                   the fused program re-runs from the committed epoch
                   over the replayed ingest instead of tearing the
                   deployment down
  mesh_topn_crash  the q5 lowering: the SHARDED TOP-N actor (ORDER BY n
                   DESC LIMIT k over the retracting agg changelog,
                   streaming_parallelism_devices=2) crashes ->
                   scope=MESH; recovery re-plans it sharded and the
                   rows re-characterize against the upstream recount
  dcn_drop         2-WORKER cluster run: one DCN output leg severed
                   mid-epoch -> scope=WORKER: the dead leg's consumer
                   closure rebuilds in place, the surviving producer
                   rewinds its replay buffer into the rebuilt consumer,
                   survivors' stores stay open
  upload_fail      checkpoint upload raises -> fail-stop -> full
                   recovery from the committed epoch
  kill_during_recovery  interior crash + crashes injected inside BOTH
                   recovery paths (mid cone rebuild, then mid
                   DDL-replay) -> the retry converges (re-entrancy)
  channel_stall    the consumer parks 400ms on one chunk -> NO recovery,
                   the barrier just completes late
  upload_delay     the checkpoint upload sleeps 400ms -> NO recovery,
                   the pipelined commit just lands late (delivery and
                   replay-buffer trims follow it)

plus the STORAGE-PLANE classes (state/object_store.py retry layer,
state/hummock.py read-path integrity, state/backup.py verified
backup/restore — transient faults absorb BELOW the recovery radius
engine, durable faults repair from backup):

  object_put_flake    two consecutive transient PUT failures during
                   checkpoint upload -> absorbed by the bounded-retry
                   wrapper: ZERO recoveries, retries counted, MV
                   bit-identical to the oracle
  object_get_flake    a transient GET failure on the scrub read path ->
                   absorbed the same way, zero recoveries
  object_get_corrupt_transient  one corrupted GET payload -> the crc
                   retry re-reads clean: zero recoveries, nothing
                   quarantined
  sst_corrupt_durable  an on-disk SST bit-rotted AFTER a backup -> the
                   scrubber detects it, quarantines the bad bytes,
                   restores the object from its checksum-verified
                   backup copy, /healthz flips degraded — zero
                   recoveries, the engine never serves the corruption
  backup_restore_coldstart  BACKUP TO twice (the second run must copy
                   only the new generation's objects), then a REAL
                   FRESH PROCESS runs RESTORE FROM into an empty
                   primary and converges bit-identical to the
                   generator-prefix oracle at the restored committed
                   offset; a deliberately corrupted backup object is
                   REFUSED loudly at restore time

plus the external-ingress/egress classes over an in-process broker
(connectors/broker.py — the fail-stop -> auto-recovery path, never a
hang):

  broker_fetch_fail   the source's partition fetch raises -> the
                   consuming actor dies -> recovery reseeks the
                   committed offsets; the MV converges to exactly the
                   produced rows (no loss, no duplication)
  broker_append_fail  the sink's topic append raises -> delivery parks
                   and fail-stops the next injection; after recovery
                   the topic holds dense, duplicate-free delivery
                   sequence numbers and exactly the MV's changelog

Exits non-zero unless ALL hold:

  * every run converges BIT-IDENTICAL to the generator-prefix oracle:
    the MV's rows equal a numpy recount of the bid generator prefix at
    the committed source offset (window_end -> max(price));
  * every CONTAINED fault recovers at its named scope — fragment, cone,
    mesh, worker — with the matching recovery_total{scope=...} label in
    /metrics, and rebuilds a STRICT SUBSET of the topology's actors
    (asserted on the actor-id sets reported in last_recovery);
  * fragment/cone/worker-scope recovery p50s beat the full-recovery p50
    AND fragment stays under the absolute budget (0.5s on CPU — a
    partial rebuild is host-side re-wiring plus state reload, not a
    DDL replay);
  * recovery_total{scope=...,cause=...} and recovery_duration_seconds
    render in /metrics, and /healthz carries the last-recovery fields
    (scope/cause/duration) — recovery is observable end to end.

CI usage (CPU backend):

    JAX_PLATFORMS=cpu python scripts/chaos_profile.py
"""

import asyncio
import json
import os
import socket
import subprocess
import sys
import time
import urllib.request
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the mesh_crash class needs a multi-device mesh on the CPU backend
# (same virtual-device trick as tests/conftest.py) — must precede any
# jax import
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

from risingwave_tpu.utils.compile_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

WINDOW_US = 1_000_000
FRAGMENT_P50_BUDGET_S = 0.5


def _ddl() -> list:
    return [
        "SET streaming_watchdog = 0",
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         "chunk_size=128, inter_event_us=2000, rate_limit=512)"),
        ("CREATE MATERIALIZED VIEW q7w AS "
         "SELECT window_end, max(price) AS maxprice "
         f"FROM TUMBLE(bid, date_time, {WINDOW_US}) GROUP BY window_end"),
    ]


def _oracle(offset: int) -> Counter:
    """Numpy recount of the generator prefix: window_end -> max(price),
    the exactly-once convergence target."""
    import numpy as np
    from risingwave_tpu.connectors import NexmarkGenerator
    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    gen = NexmarkGenerator("bid", chunk_size=max(256, offset),
                           cfg=NexmarkConfig(inter_event_us=2000))
    c = gen.next_chunk()
    price = np.asarray(c.columns[2].data)[:offset]
    dt = np.asarray(c.columns[5].data)[:offset]
    we = dt - dt % WINDOW_US + WINDOW_US
    out: Counter = Counter()
    for w in np.unique(we):
        out[(int(w), int(price[we == w].max()))] += 1
    return out


def _committed_offset(session, mv: str = "q7w") -> int:
    from risingwave_tpu.state.storage_table import StorageTable
    from risingwave_tpu.stream.source import SourceExecutor
    dep = session.catalog.mvs[mv].deployment
    for roots in dep.roots.values():
        for root in roots:
            node = root
            while node is not None:
                if isinstance(node, SourceExecutor):
                    rows = list(StorageTable.for_state_table(
                        node.state_table).batch_iter())
                    return int(rows[0][1]) if rows else 0
                node = getattr(node, "input", None)
    raise AssertionError("no source executor")


async def _run_fault(name: str, tmp: str, arm, pre_ddl=()) -> dict:
    """One fresh durable session, one injected fault class: warm up,
    arm the injector, tick through the fault and its recovery, then
    verify convergence against the oracle. `arm(session) -> spec`."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    store = HummockStateStore(
        LocalFsObjectStore(os.path.join(tmp, name)))
    s = Session(store=store)
    for sql in pre_ddl:
        await s.execute(sql)
    for sql in _ddl():
        await s.execute(sql)
    await s.tick(3)
    spec = arm(s)
    await s.execute(f"SET fault_injection = '{spec}'")
    await s.tick(5, max_recoveries=4)
    await s.execute("SET fault_injection = ''")
    await s.tick(2)

    offset = _committed_offset(s)
    got = Counter(s.query("SELECT window_end, maxprice FROM q7w"))
    expected = _oracle(offset)
    total_actors = sorted(
        a.actor_id
        for f in list(s.catalog.mvs.values()) + list(s.catalog.sinks.values())
        for a in f.deployment.actors)

    # observability surfaces, scraped over a real socket
    await s.start_monitor(0)
    port = s.monitor.port

    def _get(path: str) -> str:
        # off the loop: the monitor serves ON this loop, so a blocking
        # urlopen here would deadlock the scrape
        return urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5).read().decode()

    metrics = await asyncio.to_thread(_get, "/metrics")
    healthz = json.loads(await asyncio.to_thread(_get, "/healthz"))
    await s.stop_monitor()
    out = {
        "fault": name,
        "converged": got == expected,
        "offset": offset,
        "mv_rows": sum(got.values()),
        "recoveries": s.recoveries,
        "last_recovery": s.last_recovery,
        "total_actors": total_actors,
        "metrics_recovery_total": "recovery_total" in metrics,
        "metrics_recovery_duration":
            "recovery_duration_seconds" in metrics,
        "healthz_last_recovery": healthz.get("last_recovery"),
    }
    await s.drop_all()
    return out


def _mv_actor(session) -> int:
    mv = session.catalog.mvs["q7w"]
    return mv.deployment.frag_actor_ids[mv.mv_fragment][0]


def _agg_actor(session) -> int:
    """The hash_agg fragment's actor — upstream of the terminal one."""
    from risingwave_tpu.plan.build import _iter_executor_chain
    mv = session.catalog.mvs["q7w"]
    dep = mv.deployment
    for fid, roots in dep.roots.items():
        if fid == mv.mv_fragment:
            continue
        for root in roots:
            for ex in _iter_executor_chain(root):
                if "HashAgg" in getattr(ex, "identity", ""):
                    return dep.frag_actor_ids[fid][0]
    raise AssertionError("no hash_agg fragment")


async def _run_broker_faults(tmp: str) -> list:
    """The ingress/egress fault classes need a broker in the loop: a
    fresh session per class over an in-process broker (tests cover the
    socket transport; the fault path is transport-independent)."""
    import json as _json
    from risingwave_tpu.broker import Broker, register_inproc
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore

    out = []

    # ---- broker_fetch_fail: source fetch dies mid-ingest ----
    broker = Broker(os.path.join(tmp, "broker_in"), fsync=False)
    register_inproc("chaos_in", broker)
    broker.create_topic("ev", 1)
    rows = [_json.dumps({"k": i, "v": i * 7}).encode() for i in range(400)]
    broker.append("ev", 0, rows[:250])
    s = Session(store=HummockStateStore(
        LocalFsObjectStore(os.path.join(tmp, "broker_fetch_fail"))))
    await s.execute("SET streaming_watchdog = 0")
    await s.execute(
        "CREATE SOURCE ev WITH (connector='broker', topic='ev', "
        "brokers='inproc://chaos_in', columns='k int64, v int64', "
        "chunk_size=64, discovery_interval_ms=0, append_only=1)")
    await s.execute("CREATE MATERIALIZED VIEW bm AS SELECT k, v FROM ev")
    await s.tick(2)
    await s.execute("SET fault_injection = 'broker_fetch_fail:at=2'")
    broker.append("ev", 0, rows[250:])
    await s.tick(5, max_recoveries=4)
    await s.execute("SET fault_injection = ''")
    await s.tick(2)
    got = Counter(s.query("SELECT k, v FROM bm"))
    expected = Counter((i, i * 7) for i in range(400))
    out.append({"fault": "broker_fetch_fail",
                "converged": got == expected,
                "mv_rows": sum(got.values()),
                "recoveries": s.recoveries,
                "last_recovery": s.last_recovery})
    await s.drop_all()

    # ---- broker_append_fail: sink delivery dies mid-append ----
    s = Session(store=HummockStateStore(
        LocalFsObjectStore(os.path.join(tmp, "broker_append_fail"))))
    await s.execute("SET streaming_watchdog = 0")
    await s.execute(
        "CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
        "chunk_size=128, inter_event_us=2000, rate_limit=512)")
    await s.execute("SET fault_injection = 'broker_append_fail:at=2'")
    await s.execute(
        "CREATE SINK q7b AS SELECT window_end, max(price) AS maxprice "
        f"FROM TUMBLE(bid, date_time, {WINDOW_US}) GROUP BY window_end "
        "WITH (connector='broker', topic='q7b', "
        "brokers='inproc://chaos_in')")
    await s.tick(5, max_recoveries=4)
    await s.execute("SET fault_injection = ''")
    await s.tick(3)
    # topic-side exactly-once: dense unique seqs, replay-consistent rows
    seqs = []
    live: Counter = Counter()
    dangling = 0
    from risingwave_tpu.broker.log import PartitionLog
    for p in range(broker.list_partitions("q7b")):
        pl = PartitionLog(os.path.join(tmp, "broker_in", "q7b",
                                       f"p{p:05d}"), fsync=False)
        for rec in pl.fetch(0, 1_000_000):
            o = _json.loads(rec)
            key = (o.get("window_end"), o.get("maxprice"))
            if o.get("__op") == 1:
                if live[key] <= 0:
                    dangling += 1
                else:
                    live[key] -= 1
            else:
                live[key] += 1
    # batch metas carry the delivery seqs — walk them via the log index
    for p in range(broker.list_partitions("q7b")):
        pl = broker._parts[("q7b", p)]
        for base, _n, seg, pos in pl._index:
            import struct as _struct
            import zlib as _zlib
            with open(seg, "rb") as f:
                f.seek(pos)
                ln, _crc = _struct.unpack("!II", f.read(8))
                body = f.read(ln)
            _b, _nr, ml = _struct.unpack_from("!QII", body)
            if ml:
                seqs.append(_json.loads(body[16:16 + ml])["seq"])
    seqs.sort()
    windows = [k[0] for k, c in live.items() for _ in range(c)]
    out.append({"fault": "broker_append_fail",
                "converged": (seqs == list(range(1, len(seqs) + 1))
                              and len(seqs) > 0 and dangling == 0
                              and len(windows) == len(set(windows))),
                "delivered_seqs": len(seqs),
                "recoveries": s.recoveries,
                "last_recovery": s.last_recovery})
    await s.drop_all()
    return out


CHILD_RESTORE_SRC = r"""
import asyncio, json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")

async def main():
    bak, primary = sys.argv[1], sys.argv[2]
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    from risingwave_tpu.state.storage_table import StorageTable
    from risingwave_tpu.stream.source import SourceExecutor
    s = Session(store=HummockStateStore(LocalFsObjectStore(primary)))
    meta = await s.execute("RESTORE FROM '%s'" % bak)
    offset = 0
    dep = s.catalog.mvs["q7w"].deployment
    for roots in dep.roots.values():
        for root in roots:
            node = root
            while node is not None:
                if isinstance(node, SourceExecutor):
                    rows = list(StorageTable.for_state_table(
                        node.state_table).batch_iter())
                    offset = int(rows[0][1]) if rows else 0
                node = getattr(node, "input", None)
    rows = sorted(s.query("SELECT window_end, maxprice FROM q7w"))
    print(json.dumps({"restore": meta, "offset": offset, "rows": rows}))
    await s.crash()

asyncio.run(main())
"""


async def _run_storage_faults(tmp: str) -> tuple[list, dict]:
    """The storage-plane classes: transient object faults absorb BELOW
    the recovery machinery (zero recoveries, retries counted), durable
    corruption repairs from backup, and the incremental backup restores
    bit-identical over a REAL fresh process."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    from risingwave_tpu.utils.metrics import (OBJECT_RETRIES,
                                              RECOVERY_TOTAL,
                                              STORAGE_CRC_RETRIES)
    out = []

    async def _q7(name, pre=()):
        store = HummockStateStore(
            LocalFsObjectStore(os.path.join(tmp, name)))
        s = Session(store=store)
        for sql in pre:
            await s.execute(sql)
        for sql in _ddl():
            await s.execute(sql)
        await s.tick(3)
        return s, store

    def _conv(s):
        offset = _committed_offset(s)
        got = Counter(s.query("SELECT window_end, maxprice FROM q7w"))
        return got == _oracle(offset), offset, sum(got.values())

    async def _transient(name, spec, pre=()):
        s, store = await _q7(name, pre=pre)
        r0 = OBJECT_RETRIES.value
        c0 = STORAGE_CRC_RETRIES.value
        t0 = RECOVERY_TOTAL.value
        await s.execute(f"SET fault_injection = '{spec}'")
        await s.tick(4)
        await s.execute("SET fault_injection = ''")
        await s.tick(1)
        conv, offset, nrows = _conv(s)
        res = {"fault": name, "converged": conv, "offset": offset,
               "mv_rows": nrows, "recoveries": s.recoveries,
               "retries_delta": OBJECT_RETRIES.value - r0,
               "crc_retries_delta": STORAGE_CRC_RETRIES.value - c0,
               "recovery_total_delta": RECOVERY_TOTAL.value - t0,
               "quarantined": list(store.quarantined)}
        await s.drop_all()
        return res

    scrub_on = ("SET storage_scrub_interval = 1",
                "SET storage_scrub_batch = 4")
    out.append(await _transient(
        "object_put_flake", "object_put_fail:at=1,times=2"))
    out.append(await _transient(
        "object_get_flake", "object_get_fail:at=1,kind=sst",
        pre=scrub_on))
    out.append(await _transient(
        "object_get_corrupt_transient", "object_get_corrupt:at=1,kind=sst",
        pre=scrub_on))

    # ---- durable SST corruption -> quarantine + restore-from-backup ----
    s, store = await _q7("sst_corrupt_durable",
                         pre=("SET storage_scrub_interval = 1",
                              "SET storage_scrub_batch = 8"))
    bak_repair = os.path.join(tmp, "sst_corrupt_durable_bak")
    await s.execute(f"BACKUP TO '{bak_repair}'")
    t0 = RECOVERY_TOTAL.value
    sst = store._l0[0] if store._l0 else store._l1
    sst_path = os.path.join(tmp, "sst_corrupt_durable", "ssts",
                            f"{sst.sst_id:010d}.sst")
    with open(sst_path, "r+b") as f:     # bit-rot AFTER the backup
        f.seek(24)
        f.write(b"\xde\xad\xbe\xef")
    await s.tick(4)                      # scrub pulse finds + repairs it
    from risingwave_tpu.state.sstable import SsTable
    healed = True
    try:
        SsTable.parse(sst.sst_id, open(sst_path, "rb").read())
    except Exception:  # noqa: BLE001
        healed = False
    await s.start_monitor(0)
    port = s.monitor.port
    healthz = json.loads(await asyncio.to_thread(
        lambda: urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5)
        .read().decode()))
    await s.stop_monitor()
    conv, offset, nrows = _conv(s)
    out.append({"fault": "sst_corrupt_durable", "converged": conv,
                "offset": offset, "mv_rows": nrows,
                "recoveries": s.recoveries,
                "recovery_total_delta": RECOVERY_TOTAL.value - t0,
                "quarantined": list(store.quarantined),
                "restored": list(store.restored_objects),
                "healed_on_disk": healed,
                "healthz_degraded": bool(healthz.get("degraded"))})
    await s.drop_all()

    # ---- incremental backup + cold-start restore in a FRESH process ----
    s, store = await _q7("coldstart_primary")
    bak = os.path.join(tmp, "coldstart_bak")
    meta1 = await s.execute(f"BACKUP TO '{bak}'")
    await s.tick(3)
    meta2 = await s.execute(f"BACKUP TO '{bak}'")
    final_offset = _committed_offset(s)
    final_rows = sorted(s.query("SELECT window_end, maxprice FROM q7w"))
    await s.crash()

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)

    def _restore_child(primary):
        return subprocess.run(
            [sys.executable, "-c", CHILD_RESTORE_SRC, bak, primary],
            capture_output=True, timeout=300, env=env, cwd=repo)

    child = _restore_child(os.path.join(tmp, "coldstart_fresh"))
    restored = {}
    if child.returncode == 0:
        restored = json.loads(child.stdout.decode().strip().split("\n")[-1])
    conv = (bool(restored)
            and Counter(map(tuple, restored["rows"]))
            == _oracle(restored["offset"])
            and restored["offset"] == final_offset
            and [list(r) for r in final_rows] == restored["rows"])
    # a corrupted backup object must REFUSE loudly at restore time
    from risingwave_tpu.state.backup import load_backup_manifest
    ledger = load_backup_manifest(LocalFsObjectStore(bak))
    sst_name = sorted(n for n in ledger["objects"] if n.startswith("ssts/"))[0]
    with open(os.path.join(bak, *sst_name.split("/")), "r+b") as f:
        f.seek(16)
        f.write(b"\x66\x6f\x6f\x21")
    child2 = _restore_child(os.path.join(tmp, "coldstart_fresh2"))
    refused = (child2.returncode != 0
               and b"BackupCorruption" in child2.stderr)
    out.append({"fault": "backup_restore_coldstart",
                "converged": conv,
                "recoveries": 0,
                "backup_gen1": meta1, "backup_gen2": meta2,
                "child_rc": child.returncode,
                "corrupt_backup_refused": refused,
                "child2_rc": child2.returncode})
    verdict_bits = {
        "storage_transient_zero_recoveries": all(
            r["recoveries"] == 0 and r["recovery_total_delta"] == 0
            for r in out if r["fault"] != "backup_restore_coldstart"),
        "storage_retries_counted": (
            out[0]["retries_delta"] > 0 and out[1]["retries_delta"] > 0
            and out[2]["crc_retries_delta"] > 0),
        "storage_transient_nothing_quarantined": all(
            not r["quarantined"] for r in out[:3]),
        "storage_all_converged": all(
            r["converged"] for r in out),
        "sst_corrupt_durable_repaired": (
            bool(out[3]["quarantined"]) and bool(out[3]["restored"])
            and out[3]["healed_on_disk"] and out[3]["healthz_degraded"]),
        "backup_incremental_copy_only_new": (
            meta2["generation"] == meta1["generation"] + 1
            and meta2["skipped"] > 0
            and meta2["copied"] < meta2["objects"]),
        "coldstart_restore_converged": conv,
        "corrupt_backup_refused": refused,
    }
    return out, verdict_bits


def _mesh_actor(session) -> int:
    """The fused mesh fragment's actor (the agg lowered onto the
    virtual device mesh under streaming_parallelism_devices=2)."""
    dep = session.catalog.mvs["q7w"].deployment
    assert dep.mesh_actor_ids, "no mesh fragment deployed"
    return dep.mesh_actor_ids[0]


async def _run_mesh_topn_crash(tmp: str) -> dict:
    """scope=MESH for the q5 lowering: crash the SHARDED TOP-N actor
    (ORDER BY n DESC LIMIT k over a retracting agg changelog, lowered
    onto the device mesh). Recovery must rebuild only the mesh radius,
    re-plan the executor SHARDED (durable full-input store + ingest
    replay), and converge: the top-N rows must characterize exactly
    against the batch recount of the upstream MV, which itself must
    match the generator-prefix recount at the committed offset."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    from risingwave_tpu.stream.sharded_top_n import ShardedTopNExecutor
    k = 5
    store = HummockStateStore(
        LocalFsObjectStore(os.path.join(tmp, "mesh_topn_crash")))
    s = Session(store=store)
    await s.execute("SET streaming_parallelism_devices = 2")
    await s.execute(
        "CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
        "chunk_size=128, rate_limit=512)")
    await s.execute("CREATE MATERIALIZED VIEW counts AS SELECT auction "
                    "AS a, count(*) AS n FROM bid GROUP BY auction")
    await s.execute("CREATE MATERIALIZED VIEW t5 AS SELECT a, n FROM "
                    f"counts ORDER BY n DESC LIMIT {k}")
    await s.tick(3)
    dep = s.catalog.mvs["t5"].deployment
    assert dep.mesh_actor_ids, "top-N did not deploy on the mesh"
    victim = dep.mesh_actor_ids[0]
    await s.execute(
        f"SET fault_injection = 'actor_crash:actor={victim},at=2'")
    await s.tick(5, max_recoveries=4)
    await s.execute("SET fault_injection = ''")
    await s.tick(2)

    replanned = []
    for roots in s.catalog.mvs["t5"].deployment.roots.values():
        for root in roots:
            node = root
            while node is not None:
                if isinstance(node, ShardedTopNExecutor):
                    replanned.append(node)
                node = getattr(node, "input", None)

    # characterization: order-key vector vs the batch engine's recount
    # of the upstream MV (ties at the k-boundary may pick either key),
    # every (a, n) pair present upstream, and the upstream MV anchored
    # to the generator prefix at its committed offset
    got = s.query("SELECT a, n FROM t5 ORDER BY 2 DESC, 1")
    want = s.query(f"SELECT a, n FROM counts ORDER BY 2 DESC, 1 LIMIT {k}")
    base = dict(s.query("SELECT a, n FROM counts"))
    import numpy as np
    from risingwave_tpu.connectors import NexmarkGenerator
    offset = _committed_offset(s, mv="counts")
    gen = NexmarkGenerator("bid", chunk_size=max(256, offset))
    auction = np.asarray(gen.next_chunk().columns[0].data)[:offset]
    recount = Counter(auction.tolist())
    converged = (
        [n for _, n in got] == [n for _, n in want]
        and len(got) == min(k, len(base))
        and all(base.get(a) == n for a, n in got)
        and base == {int(a): int(n) for a, n in recount.items()})
    total_actors = sorted(
        a.actor_id
        for f in list(s.catalog.mvs.values()) + list(s.catalog.sinks.values())
        for a in f.deployment.actors)
    out = {
        "fault": "mesh_topn_crash",
        "converged": converged,
        "offset": offset,
        "mv_rows": len(got),
        "recoveries": s.recoveries,
        "last_recovery": s.last_recovery,
        "total_actors": total_actors,
        "replanned_sharded": bool(replanned),
    }
    await s.drop_all()
    return out


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_worker(port: int) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen(
        [sys.executable, "-m", "risingwave_tpu.worker", str(port)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port),
                                     timeout=1).close()
            return p
        except OSError:
            time.sleep(0.2)
    p.terminate()
    raise RuntimeError("worker never started listening")


async def _run_cluster_dcn(tmp: str) -> dict:
    """The WORKER radius over a real 2-worker cluster: sever one DCN
    output leg mid-epoch (dcn_drop, armed on the workers through the
    cluster config push) — the consumer's downstream closure rebuilds
    in place at scope=worker, the surviving producer rewinds its
    replay buffer into the rebuilt consumer, survivors keep their
    store objects, and the MV converges bit-identical to the
    generator-prefix oracle at the committed per-split offsets."""
    import numpy as np
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    ports = [_free_port(), _free_port()]
    procs = [_spawn_worker(p) for p in ports]
    try:
        s = Session(store=HummockStateStore(
            LocalFsObjectStore(os.path.join(tmp, "dcn"))))
        addr = ",".join(f"127.0.0.1:{p}" for p in ports)
        await s.execute(f"SET cluster = '{addr}'")
        await s.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
            "chunk_size=256, splits=2, rate_limit=512)")
        await s.execute(
            "CREATE MATERIALIZED VIEW agg AS SELECT auction, "
            "count(*) AS n, max(price) AS mx FROM bid GROUP BY auction")
        for _ in range(4):
            await asyncio.wait_for(s.tick(), 60)
        all_actors = sorted(
            a for dep in s.cluster.deployments.values()
            for ids in dep.rebuild_info["actors"].values() for a in ids)
        await s.execute("SET fault_injection = 'dcn_drop:at=3'")
        for _ in range(6):
            await asyncio.wait_for(s.tick(max_recoveries=4), 60)
        await s.execute("SET fault_injection = ''")
        await asyncio.wait_for(s.tick(2), 60)

        got = sorted(s.query("SELECT auction, n, mx FROM agg"))
        # generator-prefix oracle at the committed per-split offsets
        from risingwave_tpu.common.types import (DataType, Field,
                                                 Schema)
        from risingwave_tpu.connectors import NexmarkGenerator
        from risingwave_tpu.state.state_table import StateTable
        from risingwave_tpu.state.storage_table import StorageTable
        sch = Schema((Field("split_id", DataType.INT64),
                      Field("offset", DataType.INT64)))
        offsets = {}
        for tid in range(1, 40):
            st = StateTable(s.store, table_id=tid, schema=sch,
                            pk_indices=(0,))
            try:
                rows = list(StorageTable.for_state_table(st).batch_iter())
            except Exception:  # noqa: BLE001 — not this table's layout
                continue
            if rows and all(len(r) == 2 for r in rows) \
                    and {r[0] for r in rows} <= {0, 1}:
                offsets = {int(k): int(v) for k, v in rows}
                break
        gen = NexmarkGenerator("bid", chunk_size=1 << 16)
        c = gen.next_chunk()
        auction = np.asarray(c.columns[0].data)
        price = np.asarray(c.columns[2].data)
        idx = []
        for k, off in offsets.items():
            for j in range(off // 256):
                b = j * 2 + k
                idx.extend(range(b * 256, (b + 1) * 256))
        idx = np.asarray(sorted(idx), dtype=np.int64)
        a, p = auction[idx], price[idx]
        cnt = Counter(a.tolist())
        mx: dict = {}
        for ai, pi in zip(a.tolist(), p.tolist()):
            mx[ai] = max(mx.get(ai, 0), pi)
        oracle = sorted((k, cnt[k], mx[k]) for k in cnt)
        out = {
            "fault": "dcn_drop",
            "converged": got == oracle and bool(offsets),
            "mv_rows": sum(g[1] for g in got),
            "recoveries": s.recoveries,
            "last_recovery": s.last_recovery,
            "total_actors": all_actors,
        }
        await s.shutdown()
        return out
    finally:
        for p_ in procs:
            if p_.poll() is None:
                p_.terminate()


async def main() -> int:
    import tempfile
    tmp = tempfile.mkdtemp(prefix="chaos_profile_")
    results = []

    results.append(await _run_fault(
        "mv_actor_crash", tmp,
        lambda s: f"actor_crash:actor={_mv_actor(s)},at=2"))
    results.append(await _run_fault(
        "poison_chunk", tmp,
        lambda s: f"poison_chunk:actor={_mv_actor(s)},at=3"))
    results.append(await _run_fault(
        "interior_crash", tmp,
        lambda s: f"actor_crash:actor={_agg_actor(s)},at=2"))
    results.append(await _run_fault(
        "mesh_crash", tmp,
        lambda s: f"actor_crash:actor={_mesh_actor(s)},at=2",
        pre_ddl=("SET streaming_parallelism_devices = 2",)))
    mesh_topn = await _run_mesh_topn_crash(tmp)
    results.append(await _run_fault(
        "upload_fail", tmp, lambda s: "upload_fail:at=1"))
    results.append(await _run_fault(
        "kill_during_recovery", tmp,
        lambda s: (f"actor_crash:actor={_agg_actor(s)},at=2;"
                   "recovery_crash:phase=partial,at=1;"
                   "recovery_crash:phase=full,at=1")))
    results.append(await _run_fault(
        "channel_stall", tmp,
        lambda s: f"channel_stall:actor={_mv_actor(s)},at=2,ms=400"))
    results.append(await _run_fault(
        "upload_delay", tmp, lambda s: "upload_delay:at=1,ms=400"))
    dcn = await _run_cluster_dcn(tmp)
    results_cluster = [dcn, mesh_topn]
    broker_results = await _run_broker_faults(tmp)
    storage_results, storage_verdict = await _run_storage_faults(tmp)
    for r in (results + results_cluster + broker_results
              + storage_results):
        print(json.dumps(r))

    by_name = {r["fault"]: r for r in results}
    frag_runs = [by_name["mv_actor_crash"], by_name["poison_chunk"]]
    cone_runs = [by_name["interior_crash"]]
    mesh_runs = [by_name["mesh_crash"], mesh_topn]
    full_runs = [by_name["upload_fail"], by_name["kill_during_recovery"]]
    contained = frag_runs + cone_runs + mesh_runs + [dcn]

    def _p50(runs):
        xs = sorted(r["last_recovery"]["duration_s"] for r in runs)
        return xs[len(xs) // 2]

    frag_p50 = _p50(frag_runs)
    cone_p50 = _p50(cone_runs)
    mesh_p50 = _p50(mesh_runs)
    worker_p50 = _p50([dcn])
    full_p50 = _p50(full_runs)
    stall = by_name["channel_stall"]
    delay = by_name["upload_delay"]
    # scope labels land in the process-global registry as the runs go
    from risingwave_tpu.utils.metrics import GLOBAL_METRICS
    final_metrics = GLOBAL_METRICS.render_prometheus()
    verdict = {
        "all_converged": all(r["converged"]
                             for r in results + results_cluster),
        "delay_no_recovery": delay["recoveries"] == 0,
        "fragment_scope": all(
            r["last_recovery"]["scope"] == "fragment" for r in frag_runs),
        "cone_scope": all(
            r["last_recovery"]["scope"] == "cone" for r in cone_runs),
        "mesh_scope": all(
            r["last_recovery"]["scope"] == "mesh" for r in mesh_runs),
        "worker_scope": dcn["last_recovery"]["scope"] == "worker",
        # every contained radius rebuilds a STRICT subset of the actors
        "contained_rebuild_strictly_fewer": all(
            set(r["last_recovery"]["actors"]) < set(r["total_actors"])
            for r in contained),
        "full_scope": all(
            r["last_recovery"]["scope"] == "full"
            and set(r["last_recovery"]["actors"]) == set(r["total_actors"])
            for r in full_runs),
        "stall_no_recovery": stall["recoveries"] == 0,
        "fragment_recovery_p50_s": round(frag_p50, 5),
        "cone_recovery_p50_s": round(cone_p50, 5),
        "mesh_recovery_p50_s": round(mesh_p50, 5),
        "worker_recovery_p50_s": round(worker_p50, 5),
        "full_recovery_p50_s": round(full_p50, 5),
        "fragment_beats_full": frag_p50 < full_p50,
        "cone_beats_full": cone_p50 < full_p50,
        # channel-free mesh replay: the rebuilt fused executor preloads
        # the MeshIngestLog suffix (one fused scan, no per-chunk channel
        # re-delivery), so the mesh radius must stay cheaper than full
        "mesh_beats_full": mesh_p50 < full_p50,
        "worker_beats_full": worker_p50 < full_p50,
        "fragment_under_budget": frag_p50 < FRAGMENT_P50_BUDGET_S,
        "scope_labels_in_metrics": all(
            f'scope="{sc}"' in final_metrics
            for sc in ("fragment", "cone", "mesh", "worker", "full")),
        "recovery_metrics_visible": all(
            r["metrics_recovery_total"] and r["metrics_recovery_duration"]
            for r in results),
        "healthz_last_recovery": all(
            r["healthz_last_recovery"] is not None
            and "scope" in r["healthz_last_recovery"]
            for r in frag_runs + cone_runs + [by_name["mesh_crash"]]
            + full_runs),
        # the q5 lowering's crash run must come back SHARDED
        "mesh_topn_replanned_sharded": mesh_topn["replanned_sharded"],
        # external ingress/egress faults take the fail-stop -> recovery
        # path (never a hang) and converge exactly-once
        "broker_faults_converged": all(
            r["converged"] and r["recoveries"] >= 1
            for r in broker_results),
    }
    # storage plane: transient classes absorb below the radius engine,
    # durable corruption repairs from backup, cold-start restore over a
    # real fresh process converges (bits computed in _run_storage_faults)
    verdict.update(storage_verdict)
    print(json.dumps({"verdict": verdict}))
    ok = all(v for k, v in verdict.items()
             if isinstance(v, bool))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
