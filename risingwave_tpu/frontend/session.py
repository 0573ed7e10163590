"""Session — SQL in, materialized views + batch query results out.

Reference: SessionImpl::run_statement (src/frontend/src/session.rs:866) +
handler::handle dispatching DDL/queries, with the catalog tracking every
object. One Session owns one state store; each CREATE MATERIALIZED VIEW
deploys a fragment graph with its own barrier coordinator over that store
(meta-lite: single process, many dataflows); SELECT over an MV runs the
batch path (StorageTable committed-snapshot scan + numpy evaluation —
serving reads stay off the device: a blocking d2h per query would
serialise with the streaming dataflow's dispatch).
"""

from __future__ import annotations

import asyncio
import json
import os
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..common.types import Schema
from ..connectors.nexmark import BID_SCHEMA, PERSON_SCHEMA, AUCTION_SCHEMA
from ..meta.barrier_manager import BarrierCoordinator
from ..plan import BuildEnv, Deployment, build_graph
from ..state import MemoryStateStore, StorageTable
from . import sql as ast
from .binder import (BindError, Scope, StreamPlanner, bind_scalar,
                     contains_agg, expand_star)
from .np_eval import eval_numpy

_NEXMARK_SCHEMAS = {"bid": BID_SCHEMA, "person": PERSON_SCHEMA,
                    "auction": AUCTION_SCHEMA}


@dataclass
class SourceDef:
    name: str
    schema: Schema
    options: dict      # builder args for the source node


@dataclass
class SinkDef:
    name: str
    schema: Schema
    deployment: Deployment
    sink_fragment: int
    upstream_taps: tuple = ()
    sql: str = ""
    sources: tuple = ()                # source names this sink reads

    @property
    def executor(self):
        return self.deployment.roots[self.sink_fragment][0]


@dataclass
class MvDef:
    name: str
    schema: Schema
    pk_indices: tuple
    deployment: Deployment
    coord: BarrierCoordinator
    mv_fragment: int
    tap: object = None                 # TapDispatcher on the MV root actor
    upstream_taps: tuple = ()          # (upstream MvDef, Channel) to detach
    sql: str = ""                      # original DDL (durable catalog)
    append_only: bool = False          # changelog has no retractions
    parallelism: int = 1
    sources: tuple = ()                # source names this MV reads

    @property
    def table(self):
        return self.deployment.roots[self.mv_fragment][0].table


class Catalog:
    def __init__(self):
        self.sources: dict[str, SourceDef] = {}
        self.mvs: dict[str, MvDef] = {}
        self.sinks: dict[str, SinkDef] = {}

    def source(self, name: str) -> SourceDef:
        if name not in self.sources:
            raise BindError(f"unknown source {name!r}")
        return self.sources[name]


CATALOG_PATH = "CATALOG"

# per-process engine counter: two Sessions in one process (the stitched
# cross-engine gate does exactly that) must not share an engine id
_ENGINE_SEQ = 0


def _parse_metric_level(v) -> str:
    """SET metric_level validator: canonical lowercase name, rejects
    unknown levels at SET time (not at the next barrier)."""
    from ..stream.monitor import MetricLevel
    return MetricLevel.parse(v).name.lower()


class Session:
    """One coordinator drives EVERY dataflow of the session (the reference
    has one GlobalBarrierManager for all streaming jobs): MV-on-MV needs
    all MVs on a single aligned epoch stream."""

    # session variables (reference: common/src/session_config/ — a 40+
    # field derive struct; this is the streaming-relevant subset) with
    # (default, validator)
    CONFIG_VARS = {
        "streaming_join_capacity": (1 << 17, int),
        "streaming_join_match_factor": (64, int),
        "streaming_agg_capacity": (1 << 16, int),
        "streaming_watchdog": (1, int),      # 0 disables d2h error fetches
        "streaming_parallelism": (1, int),
        # >1 deploys hash-distributed agg/join fragments as SINGLE
        # actors whose state is sharded over an N-device jax Mesh on the
        # vnode axis (stream/sharded_agg.py, sharded_join.py) — the TPU
        # analogue of the reference's parallel-unit placement
        # (meta/src/stream/stream_graph/schedule.rs)
        "streaming_parallelism_devices": (1, int),
        "streaming_over_window_capacity": (1 << 14, int),
        "streaming_top_n_capacity": (1 << 14, int),
        "streaming_dynamic_filter_capacity": (1 << 14, int),
        # "host:port" of a running fragment worker
        # (python -m risingwave_tpu.worker): join fragments deploy there
        # over the DCN tier; requires streaming_durability = 0 in v1
        "streaming_fragment_worker": ("", str),
        # 0 disables the snapshot join-agg fusion (binder.py
        # _try_snapshot_join_agg) — the q17 shape then plans the
        # generic changelog join cascade
        "streaming_snapshot_fuse": (1, int),
        # 0 = in-memory state backend for stateful executors (reference:
        # the in-memory hummock backend) — no per-barrier state-table
        # flush; crash recovery then replays sources from scratch
        "streaming_durability": (1, int),
        # > 0: exchange receivers pack runs of consecutive small chunks
        # between barriers into one chunk of up to this total capacity
        # (power-of-two bucketed shapes, zero steady-state recompiles) —
        # each downstream stateful executor then pays one dispatch per
        # interval instead of one per chunk (common/chunk.py
        # ChunkCoalescer). 0 = off.
        "streaming_chunk_coalesce": (0, int),
        # bounded window of sealed-but-uncommitted checkpoint epochs the
        # background uploader may hold (meta/barrier_manager.py): barriers
        # complete at seal, SST build/upload/manifest-swap overlap the next
        # epochs' compute. 0 = inline sync on the barrier path.
        "checkpoint_max_inflight": (2, int),
        # HBM budget for device-resident executor state (memory/): 0 =
        # accounting only; > 0 = the coordinator's MemoryManager evicts
        # cold key groups to host at barriers (read-through reload on a
        # later touch) so the accounted total stays under budget
        "hbm_budget_bytes": (0, int),
        # 'lru' = epoch-stamped coldest-first (the only policy); 'none'
        # disables eviction while keeping accounting
        "memory_eviction_policy": ("lru", str),
        # serving pool admission bound (serving/pool.py): at most this
        # many batch queries execute concurrently on worker threads
        "serving_max_concurrency": (4, int),
        # per-query serving timeout in ms; 0 = unbounded
        "serving_query_timeout_ms": (0, int),
        # 1 = per-MV snapshot caches maintained incrementally from the
        # changelog (epoch-pinned reads, pk point-lookup index); 0 =
        # every SELECT re-scans the committed LSM snapshot
        "serving_cache": (1, int),
        # observability plane (stream/monitor.py): off = no per-actor
        # instrumentation at all; info (default) = epoch-trace phase
        # splits only; debug = full per-actor/per-channel labelled
        # series (stream_actor_row_count{actor,executor}, queue depth,
        # blocked-put seconds, hash occupancy, ...)
        "metric_level": ("info", lambda v: _parse_metric_level(v)),
        # monitor HTTP endpoint (meta/monitor_service.py): /metrics,
        # /healthz, /debug/traces, /debug/await_tree. 0 = off (default)
        "monitor_port": (0, int),
        # changelog subscription endpoint (logstore/subscription.py):
        # serving replicas connect here over the control-plane wire,
        # subscribe to an MV's changelog with backfill-then-tail, and
        # answer point lookups from their own snapshot cache. 0 = off.
        "subscription_port": (0, int),
        # durable-cursor lease (logstore/): a NAMED subscription cursor
        # with no live subscriber renewing it for this long stops
        # pinning MV changelog retention — resubscribing within the TTL
        # still resumes the tail; after it, the subscription falls back
        # to backfill-then-tail. 0 (default) = cursors never expire
        # (drop_sub_cursor is the only release).
        "subscription_cursor_ttl_ms": (0, int),
        # stuck-barrier watchdog threshold: an in-flight epoch older
        # than this logs format_stuck_barrier_report once and bumps
        # barrier_stalls_total; 0 disables the watchdog
        "barrier_stall_threshold_ms": (60000, int),
        # 1 (default): exchange channels buffer the uncommitted message
        # suffix (trimmed at every checkpoint commit) and an actor
        # failure whose blast radius is contained to ONE terminal
        # fragment rebuilds only that fragment's actors from the last
        # committed epoch — upstream fragments keep their device state
        # and replay the in-flight interval from the channel buffers.
        # 0: every failure takes the full stop-the-world recovery.
        "partial_recovery": (1, int),
        # exponential-backoff base between CONSECUTIVE auto-recovery
        # attempts inside one tick (the first recovery is immediate; a
        # persistent fault then waits base*2^(n-1) with +-50% jitter,
        # capped at 5s, instead of hot-looping through max_recoveries).
        # 0 disables the backoff. recovery_backoff_seconds_total counts
        # the waited seconds.
        "recovery_backoff_ms": (50, int),
        # flap detection: more than this many recoveries of the SAME
        # cause within the trailing window (utils/metrics.py
        # RECOVERY_FLAP_WINDOW_S) marks that cause FLAPPING — the
        # backoff base escalates toward the 5s cap even on the first
        # attempt of a tick (a fault that keeps coming back must stop
        # hammering rebuilds), `recovery_flapping{cause}` flips to 1 in
        # /metrics, and /healthz reports `degraded`. 0 disables.
        "recovery_flap_threshold": (3, int),
        # ---- fault-tolerant storage plane (state/) ----
        # quarantine repair source: a local-dir backup written by
        # BACKUP TO (which also sets this). When set, a durably-corrupt
        # SST restores from its checksum-verified backup copy instead
        # of crash-looping; '' detaches.
        "backup_path": ("", str),
        # background scrubber cadence (state/scrub.py): verify a batch
        # of manifest-referenced objects + sweep orphan SSTs every N
        # collected barriers. 0 disables the scrubber.
        "storage_scrub_interval": (16, int),
        # objects integrity-verified per scrub pulse
        "storage_scrub_batch": (2, int),
        # background compaction (state/compactor.py): consider a merge
        # every N collected barriers. 0 disables and falls back to the
        # inline commit-path merge (standalone-store behavior).
        "compaction_interval": (1, int),
        # L0 run count that arms a merge (read amp stays near this)
        "compaction_l0_trigger": (4, int),
        # rewrite budget credited per barrier interval — paces merge
        # work against ingest so compaction can't starve the loop
        "compaction_budget_bytes": (8 << 20, int),
        # max L0 runs folded per merge (bounds single-task latency)
        "compaction_max_runs": (8, int),
        # broker retention (state/compactor.py): push earliest-durable-
        # offset floors to brokers every N barriers so they drop whole
        # sealed segments below every consumer's checkpoint. 0 = off.
        "broker_retention_interval": (0, int),
        # backup generations kept point-in-time restorable in the
        # ledger (RESTORE FROM ... AT GENERATION n)
        "backup_keep_generations": (8, int),
        # bounded retry budget of the ResilientObjectStore wrapper: a
        # transient PUT/GET absorbs up to N-1 retries (seeded backoff +
        # jitter) below the recovery machinery before it surfaces as a
        # persistent fail-stop fault
        "object_store_retries": (4, int),
        # deterministic fault injection (utils/faults.py): named fault
        # points armed by spec, e.g.
        #   SET fault_injection = 'actor_crash:actor=4,at=2'
        #   SET fault_injection = 'upload_fail;recovery_crash:phase=full'
        # '' disarms. ZERO hot-path cost when off (sites guard on one
        # attribute read). Consumed by scripts/chaos_profile.py.
        "fault_injection": ("", str),
        # cluster mode (cluster/): comma-separated compute-node
        # addresses ("host:port,host:port"). Setting it attaches the
        # session's coordinator to the workers as a meta service: every
        # subsequent CREATE MV/SINK deploys vnode-partitioned fragments
        # ACROSS the workers, barriers inject/collect per worker over
        # RPC, and checkpoints commit only after all workers report
        # sealed state. '' detaches. Requires a shared-filesystem
        # Hummock store and streaming_durability = 1.
        "cluster": ("", str),
    }

    def __init__(self, store=None):
        self.store = store if store is not None else MemoryStateStore()
        self.catalog = Catalog()
        # restore the string dictionary BEFORE anything can mint ids
        # (bind-time literals, parsers): MV state on this store holds
        # dict ids from the previous incarnation (common/types.py)
        objects = getattr(self.store, "objects", None)
        dict_restored = 0
        if objects is not None:
            from ..common.types import load_dict_log
            dict_restored = load_dict_log(objects)
        self.coord = BarrierCoordinator(self.store)
        self.coord.dict_cursor = dict_restored
        self.env = BuildEnv(self.store, self.coord)
        self.env.session = self
        self.config = {k: v for k, (v, _) in self.CONFIG_VARS.items()}
        # durable catalog: ordered DDL log + the table-id floor each MV was
        # built at, so a replay rebinds the SAME state-table ids
        # (reference: catalog in the meta store, meta/src/manager/catalog/).
        # The persisted log loads EAGERLY: a session that issues DDL on an
        # existing store without calling recover() must append to the
        # stored log, not clobber it.
        self._ddl_log: list[dict] = []
        self._recovering = False
        blob = self._load_catalog_blob()
        if blob:
            self._ddl_log = list(json.loads(blob)["ddl"])
        self.recoveries = 0
        # most recent auto-recovery: {"scope","cause","duration_s",
        # "actors"} — surfaced by /healthz (meta/monitor_service.py)
        self.last_recovery = None
        # (monotonic time, cause) of recent recoveries — the flap
        # detector's window (recovery_flap_threshold)
        from collections import deque as _deque
        self._recovery_log = _deque(maxlen=256)
        self.env.partial_recovery = bool(self.config["partial_recovery"])
        # durable event log (meta/event_log.py): notable cluster events
        # append next to the object store and survive restart; memory-
        # only ring on a pure in-memory store. SESSION-owned so it
        # survives the coordinator swap a full recovery performs.
        from ..meta.event_log import EventLog
        self.event_log = EventLog(getattr(objects, "root", None))
        # barrier-paced metrics history (utils/metrics_history.py),
        # session-owned like the event log (a recovery's coordinator
        # swap must not truncate telemetry history); _apply_obs_config
        # points the live coordinator at it
        from ..utils.metrics_history import MetricsHistory
        self.metrics_history = MetricsHistory()
        # engine identity stamped into broker sink batch metas so a
        # downstream engine's ingest spans link back across the broker
        # (utils/trace.py stitch_chrome_traces); unique per process
        global _ENGINE_SEQ
        _ENGINE_SEQ += 1
        self.engine_id = f"engine-{os.getpid()}-{_ENGINE_SEQ}"
        # worker-local event records last stitched by the cluster
        # SHOW events / /debug/events fan-out (worker_id -> records);
        # the rw_events system table reads this cache synchronously
        self._worker_events_cache: dict = {}
        # recovery post-mortem spans, session-owned for the same reason
        # (/debug/traces must describe the recovery that replaced the
        # coordinator whose tracer used to hold them)
        from ..utils.trace import RecoveryRing
        self.recovery_ring = RecoveryRing()
        # monitor HTTP endpoint (SET monitor_port / start_monitor)
        self.monitor = None
        # changelog subscription endpoint (SET subscription_port /
        # start_subscription_server); reads self.coord live, so it
        # serves across auto-recovery coordinator swaps
        self.subscriptions = None
        # cluster manager (SET cluster = 'host:port,...'): when set, the
        # session IS the meta node and deploys onto compute nodes
        self.cluster = None
        self._apply_memory_config()
        self._apply_serving_config()
        self._apply_obs_config()
        self._apply_logstore_config()
        self._apply_storage_config()

    def _apply_storage_config(self) -> None:
        """Plumb the storage-plane session vars to the live store +
        coordinator scrubber (re-applied after auto-recovery swaps the
        coordinator): scrub cadence, object-store retry budget, and the
        quarantine repair source (backup_path)."""
        self.coord.scrubber.configure(
            interval=self.config.get("storage_scrub_interval", 16),
            batch=self.config.get("storage_scrub_batch", 2))
        objects = getattr(self.store, "objects", None)
        if objects is not None and hasattr(objects, "max_attempts"):
            objects.max_attempts = max(
                1, self.config.get("object_store_retries", 4))
        comp = getattr(self.coord, "compactor", None)
        if comp is not None:
            comp.configure(
                interval=self.config.get("compaction_interval", 1),
                l0_trigger=self.config.get("compaction_l0_trigger", 4),
                budget_bytes=self.config.get("compaction_budget_bytes",
                                             8 << 20),
                max_runs=self.config.get("compaction_max_runs", 8))
            comp.retention.configure(
                interval=self.config.get("broker_retention_interval", 0))
        if hasattr(self.store, "backup_store"):
            path = self.config.get("backup_path", "")
            if path:
                from ..state import LocalFsObjectStore
                cur = getattr(self.store.backup_store, "root", None)
                if cur != path:
                    self.store.backup_store = LocalFsObjectStore(path)
            else:
                self.store.backup_store = None

    def _apply_memory_config(self) -> None:
        """Plumb the memory session vars to the live coordinator's
        MemoryManager (re-applied after auto-recovery rebuilds it)."""
        self.coord.memory.configure(
            budget_bytes=self.config["hbm_budget_bytes"],
            policy=self.config["memory_eviction_policy"])

    def _apply_serving_config(self) -> None:
        """Plumb the serving session vars to the live coordinator's
        ServingManager (re-applied after auto-recovery rebuilds it)."""
        self.coord.serving.configure(
            enabled=bool(self.config["serving_cache"]),
            max_concurrency=self.config["serving_max_concurrency"],
            timeout_ms=self.config["serving_query_timeout_ms"])

    def _apply_obs_config(self) -> None:
        """Plumb the observability session vars to the live coordinator:
        metric level re-instruments deployed actors in place, the stall
        threshold feeds the stuck-barrier watchdog (re-applied after
        auto-recovery rebuilds the coordinator)."""
        self.coord.stats.configure(self.config["metric_level"])
        thr = self.config["barrier_stall_threshold_ms"]
        self.coord.stall_threshold_ms = float(thr) if thr > 0 else None
        # attach the session-owned durable event log to every emitter
        # living on the (swappable) coordinator — re-running this after
        # auto-recovery re-attaches it to the new incarnation
        self.coord.event_log = self.event_log
        self.coord.scrubber.event_log = self.event_log
        self.coord.logstore.event_log = self.event_log
        # metrics history: session-owned store, coordinator-paced pulse
        self.coord.metrics_history = self.metrics_history

    def _apply_logstore_config(self) -> None:
        """Plumb the log-store session vars to the live hub (re-applied
        after auto-recovery swaps the coordinator)."""
        self.coord.logstore.sub_cursor_ttl_ms = self.config.get(
            "subscription_cursor_ttl_ms", 0)

    async def start_monitor(self, port: int = 0):
        """Start (or move) the monitor HTTP endpoint; port 0 binds an
        ephemeral port (the chosen one lands in `self.monitor.port`)."""
        from ..meta.monitor_service import MonitorService
        if self.monitor is not None:
            await self.monitor.stop()
        self.monitor = await MonitorService(self, port=port).start()
        return self.monitor

    async def stop_monitor(self) -> None:
        if self.monitor is not None:
            await self.monitor.stop()
            self.monitor = None

    async def start_subscription_server(self, port: int = 0):
        """Start (or move) the changelog subscription endpoint; port 0
        binds an ephemeral port (chosen one in
        `self.subscriptions.port`)."""
        from ..logstore.subscription import SubscriptionServer
        if self.subscriptions is not None:
            await self.subscriptions.stop()
        self.subscriptions = await SubscriptionServer(
            self, port=port).start()
        return self.subscriptions

    async def stop_subscription_server(self) -> None:
        if self.subscriptions is not None:
            await self.subscriptions.stop()
            self.subscriptions = None

    # ------------------------------------------------------ durable catalog
    def _persist_catalog(self) -> None:
        if self._recovering:
            return
        blob = json.dumps({"format": 1, "ddl": self._ddl_log}).encode()
        objects = getattr(self.store, "objects", None)
        if objects is not None:          # Hummock: atomic object swap
            # same self-checksummed framing the MANIFEST carries: a
            # bit-rotted catalog is detected at load, not replayed
            from ..state.sstable import frame_meta
            objects.upload(CATALOG_PATH, frame_meta(blob))
        else:                            # in-memory: survives in-process
            self.store._catalog_blob = blob
    def _load_catalog_blob(self):
        objects = getattr(self.store, "objects", None)
        if objects is not None:
            if objects.exists(CATALOG_PATH):
                from ..state.sstable import unframe_meta
                return unframe_meta(objects.read(CATALOG_PATH),
                                    CATALOG_PATH)
            return None
        return getattr(self.store, "_catalog_blob", None)

    async def backup(self, dest_object_store) -> dict:
        """Consistent backup of the session's durable state (manifest,
        SSTs, catalog/DDL log) into another object store — INCREMENTAL
        and generation-stamped: only objects the destination does not
        already hold at the recorded checksum copy (SSTs are immutable,
        so a steady-state backup moves just the new generation's
        objects), each copy read back + verified before it enters the
        backup ledger (state/backup.py). Holds the coordinator's rounds
        lock so no sync/compaction/manifest swap runs mid-copy
        (reference: src/storage/backup/src/, the meta snapshot taken
        under the barrier manager's pause). Registered in-process
        brokers' data directories ride the same ledger under
        `broker/<name>/...` (their batch framing makes a torn active-
        segment tail harmless on restore, so appends need no quiesce);
        `extract_backup_prefix` materializes them back."""
        import os as _os
        from ..state.backup import backup_objects
        objects = getattr(self.store, "objects", None)
        if objects is None:
            raise BindError("backup needs a durable (Hummock) store")
        from ..broker.server import _INPROC
        from ..state import LocalFsObjectStore
        aux = {}
        for bname, broker in sorted(_INPROC.items()):
            root = getattr(broker, "root", None)
            if root and _os.path.isdir(root):
                aux[f"broker/{bname}"] = LocalFsObjectStore(root)
        async with self.coord._rounds_lock:
            # the rounds lock stops NEW barriers; the background uploader
            # may still hold sealed-but-uncommitted epochs — drain them so
            # no manifest swap runs mid-copy
            await self.coord.drain_uploads()
            # the rounds lock quiesces sync/compaction (every MANIFEST
            # swap), but DDL catalog uploads run outside it — snapshot
            # the catalog NOW and write the snapshot last, so the backup
            # is (catalog-as-of-start, manifest quiesced): concurrent
            # DDL can only leave unreferenced extra state in the copy,
            # never a catalog pointing at absent state
            extra = ({CATALOG_PATH: objects.read(CATALOG_PATH)}
                     if objects.exists(CATALOG_PATH) else None)
            # the copy itself runs off-loop so pgwire/sinks/actors stay
            # responsive during a large backup
            return await asyncio.to_thread(
                backup_objects, objects, dest_object_store, extra, aux,
                max(1, self.config.get("backup_keep_generations", 8)))

    async def restore_from(self, path: str,
                           generation: Optional[int] = None) -> dict:
        """Cold-start disaster recovery (RESTORE FROM '<path>'
        [AT GENERATION <n>]): verify EVERY object of the backup against
        its ledger checksum, copy the chosen generation's verified set
        (default: newest; older retained generations resolve
        superseded bytes from the archive — point-in-time restore)
        into this session's FRESH primary store, re-point
        the store at the restored manifest, reload the string dictionary
        and DDL log, then replay the DDL log — the restored session
        converges from the backup's committed epoch exactly like a
        normal post-crash recovery. Refuses a non-empty session/store:
        restoring over a live world would interleave two histories."""
        from ..state import LocalFsObjectStore
        from ..state.backup import restore_objects
        objects = getattr(self.store, "objects", None)
        if objects is None:
            raise BindError("restore needs a durable (Hummock) store")
        if self.catalog.mvs or self.catalog.sinks or self._ddl_log:
            raise BindError(
                "RESTORE FROM requires an empty session (no DDL log, "
                "no live flows) over a fresh store")
        backup = LocalFsObjectStore(path)
        # verification + copy run off-loop (reads every backup object)
        meta = await asyncio.to_thread(restore_objects, backup, objects,
                                       generation)
        # re-point the live handles at the restored world
        self.store.refresh_manifest()
        from ..common.types import load_dict_log
        self.coord.dict_cursor = load_dict_log(objects)
        self.coord._prev_epoch = max(self.coord._prev_epoch,
                                     self.store.committed_epoch())
        blob = self._load_catalog_blob()
        if blob:
            self._ddl_log = list(json.loads(blob)["ddl"])
        # the backup that restored us is by construction a valid
        # quarantine repair source going forward
        self.config["backup_path"] = path
        self._apply_storage_config()
        await self.recover()
        return meta

    async def recover(self) -> None:
        """Replay the persisted DDL log: re-register sources, re-deploy
        every MV with its original table ids (their materialized state is
        already in the store; sources re-seek their committed offsets).
        The playground calls this on startup with --data."""
        log = list(self._ddl_log)
        if not log:
            return
        self._recovering = True
        saved_config = dict(self.config)
        try:
            for entry in log:
                self.env._next_table_id = entry.get(
                    "table_id_floor", self.env._next_table_id)
                self._replay_parallelism = entry.get("parallelism", 1)
                # each entry replays under ITS OWN planning-time config;
                # entries without one (sources, old logs) use the defaults
                self.config = {**saved_config, **entry.get("config", {})}
                self.env.chunk_coalesce_max = self.config.get(
                    "streaming_chunk_coalesce", 0)
                await self.execute(entry["sql"])
        finally:
            self.config = saved_config
            self._recovering = False
            self._replay_parallelism = 1
        self._ddl_log = list(log)
        # one Initial barrier over the fully-reattached topology
        if self.catalog.mvs:
            await self.coord.run_rounds(0)

    # --------------------------------------------------------------- DDL
    async def execute(self, sql_text: str):
        stmt = ast.parse(sql_text)
        if isinstance(stmt, ast.CreateSource):
            out = self._create_source(stmt)
            if not self._recovering:
                self._ddl_log = [e for e in self._ddl_log if not (
                    e["kind"] == "source" and e["name"] == stmt.name)]
                self._ddl_log.append({"kind": "source", "name": stmt.name,
                                      "sql": sql_text})
                self._persist_catalog()
            return out
        if isinstance(stmt, ast.CreateSink):
            if stmt.name in self.catalog.sinks:
                raise BindError(f"sink {stmt.name!r} already exists")
            floor = self.env._next_table_id   # BEFORE build, like MVs
            out = await self._create_sink(stmt, sql_text)
            if not self._recovering:
                self._ddl_log = [e for e in self._ddl_log if not (
                    e["kind"] == "sink" and e["name"] == stmt.name)]
                self._ddl_log.append({"kind": "sink", "name": stmt.name,
                                      "sql": sql_text,
                                      "table_id_floor": floor,
                                      "config": dict(self.config)})
                self._persist_catalog()
            return out
        if isinstance(stmt, ast.CreateMV):
            if stmt.name in self.catalog.mvs:
                raise BindError(f"MV {stmt.name!r} already exists")
            floor = self.env._next_table_id
            out = await self._create_mv(
                stmt, sql_text,
                parallelism=getattr(self, "_replay_parallelism", 1)
                if self._recovering
                else self.config["streaming_parallelism"])
            if not self._recovering:
                self._ddl_log = [e for e in self._ddl_log if not (
                    e["kind"] == "mv" and e["name"] == stmt.name)]
                # the session config the MV was planned under persists with
                # it: recovery must rebuild the SAME capacities/tuning
                entry = {"kind": "mv", "name": stmt.name,
                         "sql": sql_text, "table_id_floor": floor,
                         "config": dict(self.config)}
                if self.cluster is not None:
                    # cluster MVs MUST replay at their planned
                    # parallelism: the vnode bitmaps the durable state
                    # was partitioned under are per-actor-idx
                    entry["parallelism"] = out.parallelism
                self._ddl_log.append(entry)
                self._persist_catalog()
            return out
        if isinstance(stmt, ast.AlterParallelism):
            return await self.alter_parallelism(stmt.name, stmt.parallelism)
        if isinstance(stmt, ast.Drop):
            return await self._drop(stmt)
        if isinstance(stmt, ast.CreateTable):
            # a DML-able BASE TABLE (reference: CREATE TABLE + dml.rs +
            # TableSource): composed from the jsonl source (the
            # append-only file IS the durable DML log — replayable
            # offsets, open-vocabulary dict durability included) plus an
            # auto-materialization so batch SELECTs and MV-on-MV work.
            # Both sub-DDLs land in the catalog log, so recovery replays
            # them in order.
            if stmt.name in self.catalog.sources \
                    or stmt.name in self.catalog.mvs:
                raise BindError(f"{stmt.name!r} already exists")
            colspec = ", ".join(f"{n} {t}" for n, t in stmt.columns)
            path = self._dml_path(stmt.name)
            # TRUNCATE: a re-created table must not resurrect a dropped
            # incarnation's rows (recovery replays the SOURCE DDL, not
            # CreateTable, so replay never truncates)
            open(path, "w").close()
            await self.execute(
                f"CREATE SOURCE {stmt.name} WITH (connector='jsonl', "
                f"path='{path}', columns='{colspec}', is_table=1)")
            return await self.execute(
                f"CREATE MATERIALIZED VIEW {stmt.name} AS "
                f"SELECT * FROM {stmt.name}")
        if isinstance(stmt, ast.Insert):
            return self._insert(stmt)
        if isinstance(stmt, ast.BackupStmt):
            from ..state import LocalFsObjectStore
            meta = await self.backup(LocalFsObjectStore(stmt.path))
            # the backup destination doubles as the quarantine repair
            # source from here on (SET backup_path to change/detach)
            self.config["backup_path"] = stmt.path
            self._apply_storage_config()
            self.event_log.emit(
                "backup", path=stmt.path,
                generation=meta.get("generation"),
                epoch=meta.get("epoch"))
            return meta
        if isinstance(stmt, ast.RestoreStmt):
            meta = await self.restore_from(stmt.path,
                                           stmt.generation)
            self.event_log.emit(
                "restore", path=stmt.path,
                generation=(meta or {}).get("generation")
                if isinstance(meta, dict) else None)
            return meta
        if isinstance(stmt, ast.Explain):
            return self.explain(stmt.stmt)
        if isinstance(stmt, ast.ExplainMv):
            return self.explain_mv(stmt.name)
        if isinstance(stmt, ast.Show):
            if self.cluster is not None and stmt.what in ("cluster",
                                                          "memory",
                                                          "events"):
                return await self._show_cluster(
                    stmt.what, limit=getattr(stmt, "limit", None),
                    kind=getattr(stmt, "kind", None),
                    since=getattr(stmt, "since", None))
            return self.show(stmt.what,
                             limit=getattr(stmt, "limit", None),
                             kind=getattr(stmt, "kind", None),
                             since=getattr(stmt, "since", None))
        if isinstance(stmt, ast.SetVar):
            if stmt.name not in self.CONFIG_VARS:
                raise BindError(f"unknown session variable {stmt.name!r}")
            _, conv = self.CONFIG_VARS[stmt.name]
            self.config[stmt.name] = conv(stmt.value)
            if stmt.name == "streaming_chunk_coalesce":
                # build-time knob, read by build_graph when wiring
                # exchange receivers (plan/build.py)
                self.env.chunk_coalesce_max = self.config[stmt.name]
            elif stmt.name == "checkpoint_max_inflight":
                # runtime-mutable on the LIVE coordinator (the ALTER
                # SYSTEM analogue): takes effect at the next barrier
                self.coord.checkpoint_max_inflight = self.config[stmt.name]
            elif stmt.name in ("hbm_budget_bytes",
                               "memory_eviction_policy"):
                # runtime-mutable on the live MemoryManager: enabling a
                # budget starts LRU tracking on every deployed executor;
                # in cluster mode the budget is PARTITIONED across the
                # live workers and forwarded to each
                self._apply_memory_config()
                if self.cluster is not None:
                    await self.cluster.push_config()
            elif stmt.name in ("serving_max_concurrency",
                               "serving_query_timeout_ms",
                               "serving_cache"):
                # runtime-mutable on the live ServingManager/pool
                self._apply_serving_config()
            elif stmt.name in ("metric_level",
                               "barrier_stall_threshold_ms"):
                # runtime-mutable: re-instruments live actors / adjusts
                # the stuck-barrier watchdog (cluster-wide when attached)
                self._apply_obs_config()
                if self.cluster is not None:
                    await self.cluster.push_config()
            elif stmt.name == "subscription_cursor_ttl_ms":
                # runtime-mutable on the live LogStoreHub: the next
                # commit pulse re-evaluates which durable cursors still
                # pin changelog retention
                self._apply_logstore_config()
            elif stmt.name in ("backup_path", "storage_scrub_interval",
                               "storage_scrub_batch",
                               "object_store_retries",
                               "compaction_interval",
                               "compaction_l0_trigger",
                               "compaction_budget_bytes",
                               "compaction_max_runs",
                               "broker_retention_interval"):
                # runtime-mutable on the live store/scrubber/compactor:
                # the next pulse and the next object op see the new
                # policy
                self._apply_storage_config()
            elif stmt.name == "partial_recovery":
                # build-time knob: channels allocated after this carry
                # (or not) the replay buffers; classification also
                # re-checks it at failure time
                self.env.partial_recovery = bool(self.config[stmt.name])
                if self.cluster is not None:
                    await self.cluster.push_config()
            elif stmt.name == "fault_injection":
                from ..utils.faults import FAULTS
                try:
                    FAULTS.arm(self.config[stmt.name])
                except ValueError as e:
                    raise BindError(str(e))
                if self.cluster is not None:
                    # cluster fault points (dcn_drop, worker_crash_
                    # partial) fire inside WORKER processes — forward
                    # the spec so their process-global injectors arm too
                    await self.cluster.push_config()
            elif stmt.name == "cluster":
                await self._configure_cluster(self.config[stmt.name])
            elif stmt.name == "monitor_port":
                # 0 stops the endpoint; a port starts/moves it
                port = self.config[stmt.name]
                if port > 0:
                    await self.start_monitor(port)
                else:
                    await self.stop_monitor()
            elif stmt.name == "subscription_port":
                port = self.config[stmt.name]
                if port > 0:
                    await self.start_subscription_server(port)
                else:
                    await self.stop_subscription_server()
            return self.config[stmt.name]
        if isinstance(stmt, ast.Select):
            return self.query_select(stmt)
        raise BindError(f"unsupported statement {stmt!r}")

    async def _drop(self, stmt: ast.Drop) -> str:
        """DROP ... (reference: handler/drop_*.rs; dependents refuse)."""
        kind, name = stmt.kind, stmt.name
        if kind == "sink":
            if name not in self.catalog.sinks:
                raise BindError(f"unknown sink {name!r}")
            await self.drop_sink(name)
            return "DROP_SINK"
        if kind == "materialized_view":
            if name not in self.catalog.mvs:
                raise BindError(f"unknown materialized view {name!r}")
            await self.drop_mv(name)
            return "DROP_MATERIALIZED_VIEW"
        # table = its auto-materialization + its source; source = just
        # the catalog entry (a source has no running deployment of its
        # own — deployments embed their connector at build time).
        # Dependent MVs/sinks refuse the drop: their DDL-log entries
        # could never replay after the source entry is pruned.
        src = self.catalog.sources.get(name)
        if src is None:
            raise BindError(f"unknown {kind} {name!r}")
        is_table = bool(src.options.get("is_table"))
        if kind == "table" and not (is_table and name in self.catalog.mvs):
            raise BindError(f"{name!r} is not a table")
        if kind == "source" and is_table:
            raise BindError(f"{name!r} is a table (use DROP TABLE)")
        deps = [d.name
                for d in (list(self.catalog.mvs.values())
                          + list(self.catalog.sinks.values()))
                if name in getattr(d, "sources", ()) and d.name != name]
        if deps:
            raise BindError(f"cannot drop {name!r}: {deps} read it")
        if kind == "table":
            await self.drop_mv(name)
        self.catalog.sources.pop(name, None)
        self._ddl_log = [e for e in self._ddl_log
                         if not (e["kind"] == "source"
                                 and e["name"] == name)]
        self._persist_catalog()
        if is_table:
            import os as _os
            try:
                _os.remove(src.options["path"])
            except OSError:
                pass
        return "DROP_TABLE" if kind == "table" else "DROP_SOURCE"

    def _dml_path(self, table: str) -> str:
        """Stable per-table DML log path: inside the durable store's
        root when there is one (survives restarts), else a
        session-stable temp dir (in-process recovery reuses it)."""
        import os
        import tempfile
        objects = getattr(self.store, "objects", None)
        root = getattr(objects, "root", None) if objects else None
        if root is None:
            root = getattr(self.store, "_dml_dir", None)
            if root is None:
                root = tempfile.mkdtemp(prefix="rwtpu_dml_")
                self.store._dml_dir = root
        d = os.path.join(root, "dml")
        os.makedirs(d, exist_ok=True)
        return os.path.join(d, f"{table}.jsonl")

    def _insert(self, stmt: ast.Insert) -> int:
        """INSERT INTO <jsonl-backed table> VALUES ... — append whole
        JSON lines; the tailing source picks them up at the next
        barrier (reference: dml.rs rows ride a channel into the
        TableSource; exactly-once from the committed line offset)."""
        src = self.catalog.sources.get(stmt.name)
        if src is None or src.options.get("connector") != "jsonl":
            raise BindError(
                f"{stmt.name!r} is not an INSERT-able table (CREATE "
                "TABLE name (col type, ...) or a jsonl source)")
        from ..common.types import DataType
        names = list(src.schema.names)
        lines = []
        for row in stmt.rows:
            if len(row) != len(names):
                raise BindError(
                    f"INSERT row has {len(row)} values, table "
                    f"{stmt.name!r} has {len(names)} columns")
            obj = {}
            for f, v in zip(src.schema, row):
                if isinstance(v, ast.UnOp) and v.op == "neg" \
                        and isinstance(v.arg, ast.Lit) \
                        and isinstance(v.arg.value, (int, float)):
                    val = -v.arg.value
                elif isinstance(v, ast.Lit):
                    val = v.value
                else:
                    raise BindError("INSERT VALUES must be literals")
                if val is None:
                    continue
                dt = f.data_type
                ok = (isinstance(val, str)
                      if dt in (DataType.VARCHAR, DataType.BYTEA,
                                DataType.JSONB)
                      else isinstance(val, bool)
                      if dt is DataType.BOOLEAN
                      else isinstance(val, (int, float))
                      and not isinstance(val, bool)
                      if dt.is_float
                      else isinstance(val, int)
                      and not isinstance(val, bool))
                if not ok:
                    raise BindError(
                        f"INSERT value {val!r} does not fit column "
                        f"{f.name} ({dt.value})")
                obj[f.name] = val
            lines.append(json.dumps(obj))
        with open(src.options["path"], "a") as f:
            f.write("".join(ln + "\n" for ln in lines))
        return len(lines)

    def explain(self, stmt) -> list:
        """EXPLAIN: plan WITHOUT deploying, return the fragment graph as
        text rows (reference: handler/explain.rs over the planner's
        explain output; snapshot format shared with tests/goldens)."""
        from ..plan.graph import render_graph
        # same parallelism the CREATE path would deploy with — EXPLAIN
        # must preview the actual topology
        planner = StreamPlanner(
            self.catalog, config=self.config,
            parallelism=self.config["streaming_parallelism"])
        if isinstance(stmt, ast.CreateMV):
            plan = planner.plan_select(stmt.select)
        elif isinstance(stmt, ast.CreateSink):
            plan = planner.plan_sink(stmt.select, dict(stmt.options))
        elif isinstance(stmt, ast.Select):
            # a bare SELECT executes on the numpy BATCH engine over a
            # committed snapshot — explain THAT pipeline, not a
            # streaming plan that never runs
            return [(ln,) for ln in _render_batch_plan(stmt)]
        else:
            raise BindError(
                "EXPLAIN supports SELECT / CREATE MATERIALIZED VIEW / "
                "CREATE SINK")
        return [(ln,) for ln in render_graph(plan.graph)]

    def explain_mv(self, name: str) -> list:
        """EXPLAIN MATERIALIZED VIEW <name>: the LIVE deployed executor
        chains annotated with per-executor HBM accounting — which MV owns
        the device memory, what spilled, how often reloads hit."""
        from ..memory.accounting import format_bytes
        from ..plan.build import _iter_executor_chain
        if name not in self.catalog.mvs:
            raise BindError(f"unknown materialized view {name!r}")
        mv = self.catalog.mvs[name]
        participants = {id(p) for p in
                        self.coord.memory._participants.values()}
        lines = [f"materialized view {name} "
                 f"(parallelism={mv.parallelism})"]
        for fid in sorted(mv.deployment.roots):
            lines.append(f"fragment {fid}")
            for root in mv.deployment.roots[fid]:
                for ex in _iter_executor_chain(root):
                    if id(ex) in participants:
                        lines.append(
                            f"  {ex.identity}: "
                            f"state_bytes={ex.state_bytes()} "
                            f"({format_bytes(ex.state_bytes())}) "
                            f"evicted_bytes="
                            f"{getattr(ex, 'mem_evicted_bytes', 0)} "
                            f"reload_count="
                            f"{getattr(ex, 'mem_reload_count', 0)} "
                            f"spilled_rows="
                            f"{getattr(ex, 'mem_spilled_rows', 0)}")
                    else:
                        lines.append(f"  {ex.identity}")
        return [(ln,) for ln in lines]

    async def _configure_cluster(self, addrs: str) -> None:
        """SET cluster = 'host:port,host:port' — attach this session's
        coordinator to the compute nodes as the meta service ('' to
        detach). Must precede any streaming DDL: a topology cannot be
        half local, half clustered."""
        from ..cluster.meta_service import ClusterManager
        if self.cluster is not None:
            await self.cluster.stop()
            self.cluster = None
        if not addrs.strip():
            return
        if self.catalog.mvs or self.catalog.sinks:
            raise BindError(
                "SET cluster must run before any MV/sink exists "
                "(drop them first)")
        if not self.config.get("streaming_durability", 1):
            raise BindError(
                "cluster mode requires streaming_durability = 1 "
                "(workers flush vnode-partitioned state to the shared "
                "store; recovery replays from the committed epoch)")
        if self.config.get("checkpoint_max_inflight", 2) < 1:
            # the cluster commit point is inherently asynchronous (all
            # workers must report sealed); a zero window has no meaning
            self.config["checkpoint_max_inflight"] = 1
            self.coord.checkpoint_max_inflight = 1
        mgr = ClusterManager(
            self, [a.strip() for a in addrs.split(",") if a.strip()])
        await mgr.connect()
        self.cluster = mgr

    async def _show_cluster(self, what: str, limit=None, kind=None,
                            since=None) -> list:
        if what == "cluster":
            return self.cluster.registry_rows()
        if what == "events":
            # meta's own records plus every worker's local log, stitched
            # on the wall timestamp and tagged by origin — the incident
            # record survives any single worker's crash
            per_worker = await self.cluster.events_all(
                limit=limit, kind=kind, since=since)
            self._worker_events_cache = per_worker
            merged = [("meta", r) for r in self.event_log.records(
                limit=limit, kind=kind, since=since)]
            for wid, recs in sorted(per_worker.items()):
                merged.extend((f"w{wid}", r) for r in recs)
            merged.sort(key=lambda e: e[1].get("ts", 0))
            if limit is not None:
                merged = merged[-int(limit):]
            rows = []
            for origin, r in merged:
                extra = {k: v for k, v in r.items()
                         if k not in ("seq", "ts", "kind")}
                rows.append((origin, str(r.get("seq", "")),
                             f"{r.get('ts', 0):.3f}", r.get("kind"),
                             json.dumps(extra, sort_keys=True,
                                        default=str)))
            return rows
        # SHOW memory, cluster-wide: the meta rows (usually none — the
        # actors live in the workers) plus every worker's, labelled
        rows = [(r["executor"], str(r["state_bytes"]),
                 str(r["evicted_bytes"]), str(r["reload_count"]),
                 str(r["spilled_rows"]))
                for r in self.coord.memory.report()]
        for r in await self.cluster.memory_report_all():
            rows.append((r["executor"], str(r["state_bytes"]),
                         str(r["evicted_bytes"]), str(r["reload_count"]),
                         str(r["spilled_rows"])))
        return rows

    def show(self, what: str, limit=None, kind=None, since=None) -> list:
        """SHOW <objects|variable> (reference: handler/show.rs +
        session_config reads)."""
        if what == "events":
            # the durable event log, newest last: (seq, ts, kind,
            # details-json). Filter parity with /debug/events:
            # `SHOW events KIND 'recovery' SINCE <ts> LIMIT n`
            rows = []
            for r in self.event_log.records(limit=limit or 32,
                                            kind=kind, since=since):
                extra = {k: v for k, v in r.items()
                         if k not in ("seq", "ts", "kind")}
                rows.append((str(r["seq"]),
                             f"{r['ts']:.3f}", r["kind"],
                             json.dumps(extra, sort_keys=True)))
            return rows
        if what == "memory":
            # per-executor HBM accounting from the memory manager
            return [(r["executor"], str(r["state_bytes"]),
                     str(r["evicted_bytes"]), str(r["reload_count"]),
                     str(r["spilled_rows"]))
                    for r in self.coord.memory.report()]
        if what == "serving":
            # per-MV snapshot-cache state from the serving manager:
            # (mv, cache epoch, rows, hits, misses, point_lookups)
            return [(r["mv"], str(r["epoch"]), str(r["rows"]),
                     str(r["hits"]), str(r["misses"]),
                     str(r["point_lookups"]))
                    for r in self.coord.serving.report()]
        if what == "sources":
            # one row PER LIVE SPLIT: (source, split, offset, lag) —
            # lag is broker-high-watermark minus consumed offset for
            # broker splits, "-" for connectors with no external
            # watermark; a source with no running executor (no MV/sink
            # reads it yet) shows a placeholder row
            rows = []
            live: dict[str, list] = {}
            for aid in sorted(self.coord.source_execs):
                ex = self.coord.source_execs[aid]
                live.setdefault(ex.source_name, []).extend(
                    ex.split_report())
            for n in sorted(self.catalog.sources):
                if n in live:
                    for sid, off, lag in sorted(live[n]):
                        rows.append((n, str(sid), str(off),
                                     "-" if lag is None else str(lag)))
                else:
                    rows.append((n, "-", "-", "-"))
            return rows
        if what == "storage":
            # the storage plane's operator surface: retry/scrub/orphan/
            # quarantine/backup state as (key, value) rows — the SQL
            # twin of the storage_* series in /metrics
            from ..state.backup import load_backup_manifest
            from ..utils.metrics import (BACKUP_GENERATION,
                                         OBJECT_RETRIES,
                                         OBJECT_TMP_SWEPT,
                                         STORAGE_CRC_RETRIES,
                                         STORAGE_RESTORED)
            rows = [("object_store_retries_total",
                     str(int(OBJECT_RETRIES.value))),
                    ("object_store_tmp_swept_total",
                     str(int(OBJECT_TMP_SWEPT.value))),
                    ("crc_retries_total",
                     str(int(STORAGE_CRC_RETRIES.value))),
                    ("restored_from_backup_total",
                     str(int(STORAGE_RESTORED.value)))]
            for k, v in sorted(self.coord.scrubber.report().items()):
                rows.append((f"scrub_{k}", str(v)))
            q = getattr(self.store, "quarantined", None)
            if q is not None:
                rows.append(("quarantined_objects",
                             ",".join(q) if q else "0"))
            path = self.config.get("backup_path", "")
            rows.append(("backup_path", path or "-"))
            gen = int(BACKUP_GENERATION.value)
            if path and not gen:
                # a repair source attached without a backup run this
                # process: read the generation off the ledger itself
                try:
                    from ..state import LocalFsObjectStore
                    m = load_backup_manifest(LocalFsObjectStore(path))
                    gen = m["generation"] if m else 0
                except Exception:  # noqa: BLE001 — display-only
                    gen = 0
            rows.append(("backup_generation", str(gen) if gen else "-"))
            return rows
        if what == "compaction":
            # the background compaction + retention plane as (key,
            # value) rows: knobs, run/rewrite counters, L0 depth / read
            # amp, per-source retention floors, last merge, broker
            # floor pushes (state/compactor.py)
            return [(k, v) for k, v in self.coord.compactor.report()]
        if what in ("tables", "materialized_views"):
            return [(n,) for n in sorted(self.catalog.mvs)]
        if what == "sinks":
            return [(n,) for n in sorted(self.catalog.sinks)]
        if what == "subscriptions":
            # (name, kind, cursor, delivered, state) for sink delivery
            # tasks and live changelog subscriptions (logstore/)
            return self.coord.logstore.report()
        if what == "all":
            return [(k, str(v)) for k, v in sorted(self.config.items())]
        if what in self.CONFIG_VARS:
            return [(str(self.config[what]),)]
        raise BindError(f"unknown SHOW target {what!r}")

    def _create_source(self, stmt: ast.CreateSource) -> SourceDef:
        opts = dict(stmt.options)
        connector = opts.pop("connector", "nexmark")
        if connector == "broker":
            # external broker ingress (connectors/broker.py): splits are
            # the topic's partitions, offsets are dense record offsets
            # committed in barrier state, and partition growth is picked
            # up live by the split enumerator at a barrier
            from ..broker.client import BrokerClient
            from ..connectors.file_source import parse_columns
            topic = opts.pop("topic", None)
            brokers = opts.pop("brokers", None)
            colspec = opts.pop("columns", None)
            if not topic or not brokers or not colspec:
                raise BindError(
                    "broker connector needs topic=..., brokers=... and "
                    "columns='name type, ...'")
            try:
                schema = parse_columns(colspec)
            except ValueError as e:
                raise BindError(str(e))
            args = {"connector": "broker", "topic": topic,
                    "brokers": brokers, "columns": colspec,
                    "chunk_size": int(opts.pop("chunk_size", 256)),
                    "partitions": int(opts.pop("partitions", 1)),
                    "discovery_interval_ms":
                        int(opts.pop("discovery_interval_ms", 1000)),
                    # topics can carry changelog ops (engine->engine
                    # pipelines ship retractions as `__op` records);
                    # append_only=1 opts into the insert-only fast paths
                    "append_only": bool(int(opts.pop("append_only", 0)))}
            for k in ("rate_limit",):
                if k in opts:
                    args[k] = int(opts.pop(k))
            if "primary_key" in opts:
                pk_name = opts.pop("primary_key")
                if pk_name not in schema.names:
                    raise BindError(
                        f"primary_key {pk_name!r} not a column")
                args["primary_key"] = list(schema.names).index(pk_name)
            if opts:
                raise BindError(f"unknown broker options {sorted(opts)}")
            if not args["append_only"] and "primary_key" not in args:
                # changelog records (`__op` deletes) must address rows:
                # a keyless retracting stream cannot plan. Insert-only
                # topics opt into the fast paths explicitly.
                raise BindError(
                    "broker source needs primary_key=... (changelog "
                    "topics) or append_only=1 (insert-only topics)")
            # ensure the topic + bind the CURRENT partition count (the
            # binder's parallelism bound; the count only ever grows, and
            # the build re-reads the live count)
            try:
                client = BrokerClient(brokers)
                args["splits"] = client.create_topic(
                    topic=topic, partitions=args["partitions"])
                client.close()
            except (OSError, ConnectionError, RuntimeError) as e:
                raise BindError(f"broker {brokers!r} unreachable: {e}")
            src = SourceDef(stmt.name, schema, args)
            self.catalog.sources[stmt.name] = src
            return src
        if connector == "jsonl":
            # external file-tailing source (connectors/file_source.py):
            # a split = one append-only JSONL file, offset = line number
            from ..connectors.file_source import parse_columns
            path = opts.pop("path", None)
            colspec = opts.pop("columns", None)
            if not path or not colspec:
                raise BindError(
                    "jsonl connector needs path=... and "
                    "columns='name type, ...'")
            try:
                schema = parse_columns(colspec)
            except ValueError as e:
                raise BindError(str(e))
            args = {"connector": "jsonl", "path": path,
                    "columns": colspec,
                    "chunk_size": int(opts.pop("chunk_size", 256))}
            if "rate_limit" in opts:
                args["rate_limit"] = int(opts.pop("rate_limit"))
            if "primary_key" in opts:
                pk_name = opts.pop("primary_key")
                if pk_name not in schema.names:
                    raise BindError(
                        f"primary_key {pk_name!r} not a column")
                args["primary_key"] = list(schema.names).index(pk_name)
            if "is_table" in opts:
                args["is_table"] = bool(int(opts.pop("is_table")))
            if opts:
                raise BindError(
                    f"unknown jsonl options {sorted(opts)}")
            src = SourceDef(stmt.name, schema, args)
            self.catalog.sources[stmt.name] = src
            return src
        if connector == "tpch":
            from ..connectors.tpch import TPCH_SCHEMAS
            schemas = TPCH_SCHEMAS
        elif connector == "nexmark":
            schemas = _NEXMARK_SCHEMAS
        else:
            raise BindError(f"unknown connector {connector!r}")
        table = opts.pop("table", stmt.name)
        if table not in schemas:
            raise BindError(f"unknown {connector} table {table!r}")
        if connector == "tpch":
            bad = {"emit_watermarks", "watermark_lag_us", "inter_event_us",
                   "base_time_us"} & set(opts)
            if bad:
                raise BindError(
                    f"options {sorted(bad)} are not supported by the "
                    "tpch connector (no event-time column)")
        args = {"connector": connector, "table": table,
                "chunk_size": int(opts.pop("chunk_size", 4096))}
        if "splits" in opts:
            args["splits"] = int(opts.pop("splits"))
        cfg = {}
        for k in ("inter_event_us", "base_time_us", "hot_auction_ratio",
                  "hot_bidder_ratio", "hot_seller_bucket"):
            if k in opts:
                cfg[k] = int(opts.pop(k))
        for k in ("hot_auction_ratio", "hot_bidder_ratio",
                  "hot_seller_bucket"):
            if cfg.get(k, 1) < 1:
                raise BindError(f"{k} must be at least 1")
        if cfg:
            args["cfg"] = cfg
        if connector == "tpch":
            # which data: the scale factor sizes the key universe, the seed
            # is a dynamic argument of the generator's program
            args["scale_factor"] = float(opts.pop("scale_factor", 1))
            args["seed"] = int(opts.pop("seed", 0))
            if args["scale_factor"] <= 0 or args["seed"] < 0:
                raise BindError(
                    "tpch: scale_factor must be positive, seed not negative")
        if "emit_watermarks" in opts:
            v = opts.pop("emit_watermarks")
            args["emit_watermarks"] = v in (True, 1, "1", "true", "t", "on")
        if "primary_key" in opts:
            # reference: PRIMARY KEY on CREATE TABLE/SOURCE — declares a
            # unique column so downstream state needs no generated row id
            pk_name = opts.pop("primary_key")
            names = list(schemas[table].names)
            if pk_name not in names:
                raise BindError(f"primary_key {pk_name!r} not a column")
            args["primary_key"] = names.index(pk_name)
        for k in ("watermark_lag_us", "rate_limit"):
            if k in opts:
                args[k] = int(opts.pop(k))
        src = SourceDef(stmt.name, schemas[table], args)
        self.catalog.sources[stmt.name] = src
        return src

    async def _create_mv(self, stmt: ast.CreateMV,
                         sql_text: str = "",
                         parallelism: int = 1,
                         table_id_floor=None) -> MvDef:
        from ..stream import TapDispatcher
        if table_id_floor is not None:
            self.env._next_table_id = table_id_floor
        if self.cluster is not None:
            return await self._create_mv_cluster(stmt, sql_text,
                                                 parallelism)
        planner = StreamPlanner(self.catalog, parallelism=parallelism,
                                config=self.config)
        plan = planner.plan_select(stmt.select)
        # bring-up holds the rounds lock: actor registration + tap attach
        # must not interleave with an in-flight barrier round (the
        # reference pauses the barrier loop around an Add command)
        async with self.coord._rounds_lock:
            self.env.pending_taps = []
            self.env.memory_scope = stmt.name
            dep = build_graph(plan.graph, self.env)
            self.env.memory_scope = None
            root = dep.roots[plan.mv_fragment][0]
            actor = next(a for a in dep.actors if a.consumer is root)
            assert actor.dispatcher is None, "MV fragment must be terminal"
            tap = TapDispatcher()
            actor.dispatcher = tap
            dep.spawn()
            # upstream taps learn this deployment's actor set so a Stop
            # barrier covering it detaches the channel at the barrier
            dep_ids = {a.actor_id for a in dep.actors}
            for up, ch in self.env.pending_taps:
                up.tap.set_consumers(ch, dep_ids)
            mv = MvDef(stmt.name, plan.schema, plan.pk_indices, dep,
                       self.coord, plan.mv_fragment, tap=tap,
                       upstream_taps=tuple(self.env.pending_taps),
                       sql=sql_text,
                       append_only=getattr(plan, "append_only", False),
                       parallelism=parallelism,
                       sources=tuple(sorted(
                           getattr(planner, "used_sources", ()))))
            self.catalog.mvs[stmt.name] = mv
            # serving registration: every Materialize executor publishes
            # its effective changelog through a hook (one per actor — a
            # parallel materialize's vnode-disjoint changelogs merge at
            # the barrier); the per-MV snapshot cache builds lazily on
            # first query touch
            roots = dep.roots[plan.mv_fragment]
            hooks = self.coord.serving.register_mv(
                stmt.name, roots[0].table, roots[0].table.schema,
                roots[0].table.pk_indices, n_hooks=len(roots))
            for r, h in zip(roots, hooks):
                r.serving_hook = h
            # durable changelog log (logstore/): the feed for changelog
            # subscriptions + serving replicas. Allocated AFTER the
            # graph build so recovery replay (which re-floors table ids
            # and rebuilds the same graph) derives the same log id.
            # Lazy: writers drop their buffer until a subscription
            # activates the log.
            clog = self.coord.logstore.register_mv(
                stmt.name, self.env.alloc_table_id(),
                roots[0].table.schema, roots[0].table.pk_indices,
                state_table=roots[0].table, n_writers=len(roots))
            for r, w in zip(roots, clog.writers):
                r.changelog_log = w
        # bring the new dataflow up: the first MV gets the Initial
        # barrier; later MVs initialize on the next ordinary barrier.
        # During catalog recovery NO barrier may run until the WHOLE
        # topology is reattached — a barrier between two re-created MVs
        # would advance upstream state while a finished-backfill consumer
        # is not yet tapped, losing its delta forever (the reference's
        # recovery rebuilds all actors before resuming barriers,
        # meta/src/barrier/recovery.rs:332).
        if not self._recovering:
            await self.coord.run_rounds(0 if not self.coord._started else 1)
        return mv

    async def _create_mv_cluster(self, stmt: ast.CreateMV,
                                 sql_text: str,
                                 parallelism: int) -> MvDef:
        """CREATE MV onto the cluster: the whole graph deploys across
        the compute nodes (vnode-partitioned fragments, cross-worker
        exchange over the DCN tier); meta keeps only a shadow handle on
        the MV's shared state table so batch SELECTs scan the committed
        snapshot the cluster commit protocol publishes."""
        n_live = len(self.cluster.live_workers())
        if not self._recovering:
            # fresh DDL spreads over every live worker; recovery keeps
            # the ORIGINAL parallelism (the vnode bitmaps the durable
            # state was written under), re-placed over the survivors
            parallelism = max(parallelism, n_live)
        planner = StreamPlanner(self.catalog, parallelism=parallelism,
                                config=self.config)
        plan = planner.plan_select(stmt.select)
        async with self.coord._rounds_lock:
            dep = await self.cluster.deploy(
                plan.graph, scope=stmt.name,
                mv_fragment=plan.mv_fragment, want_table=True)
            mv = MvDef(stmt.name, plan.schema, plan.pk_indices, dep,
                       self.coord, plan.mv_fragment, tap=None,
                       sql=sql_text,
                       append_only=getattr(plan, "append_only", False),
                       parallelism=parallelism,
                       sources=tuple(sorted(
                           getattr(planner, "used_sources", ()))))
            self.catalog.mvs[stmt.name] = mv
            # NO serving-cache registration: the materialize changelog
            # stays in the workers; meta serves from the committed
            # snapshot (ROADMAP item 3's replica direction lifts this)
        if not self._recovering:
            await self.coord.run_rounds(0 if not self.coord._started
                                        else 1)
        return mv

    # ------------------------------------------------------------ runtime
    def _check_sink_options(self, opts: dict) -> None:
        """Reject invalid sink options BEFORE the graph builds: a
        builder exception mid-build leaves half-registered actors on
        the coordinator (they never collect -> every later barrier
        hangs), so anything checkable from the options alone must fail
        here, at bind time."""
        if opts.get("connector") != "broker":
            return
        if not opts.get("topic") or not opts.get("brokers"):
            raise BindError("broker sink needs topic=... and brokers=...")
        force = opts.get("type") == "append-only" or str(
            opts.get("force_append_only", "")).lower() in ("true", "1")
        if int(opts.get("partitions", 1)) > 1 and not force:
            raise BindError(
                "broker sink with partitions > 1 requires an "
                "append-only changelog (WITH type='append-only'): one "
                "delivery batch lands whole in one partition, and "
                "retractions need the single-partition total order")
        try:
            from ..broker.client import BrokerClient
            client = BrokerClient(opts["brokers"])
            client.ping()
            client.close()
        except (OSError, ConnectionError, RuntimeError) as e:
            raise BindError(
                f"broker {opts['brokers']!r} unreachable: {e}")

    async def _create_sink(self, stmt, sql_text: str = "") -> "SinkDef":
        self._check_sink_options(dict(stmt.options))
        if self.cluster is not None:
            return await self._create_sink_cluster(stmt, sql_text)
        planner = StreamPlanner(self.catalog, config=self.config)
        plan = planner.plan_sink(stmt.select, stmt.options)
        async with self.coord._rounds_lock:
            self.env.pending_taps = []
            self.env.memory_scope = stmt.name
            dep = build_graph(plan.graph, self.env)
            self.env.memory_scope = None
            dep_ids = {a.actor_id for a in dep.actors}
            for up, ch in self.env.pending_taps:
                up.tap.set_consumers(ch, dep_ids)
            dep.spawn()
            sink = SinkDef(stmt.name, plan.schema, dep, plan.mv_fragment,
                           upstream_taps=tuple(self.env.pending_taps),
                           sql=sql_text,
                           sources=tuple(sorted(
                               getattr(planner, "used_sources", ()))))
            self.catalog.sinks[stmt.name] = sink
        if not self._recovering:
            await self.coord.run_rounds(
                0 if not self.coord._started else 1)
        return sink

    async def _create_sink_cluster(self, stmt, sql_text: str) -> "SinkDef":
        n_live = len(self.cluster.live_workers())
        planner = StreamPlanner(self.catalog, parallelism=n_live,
                                config=self.config)
        plan = planner.plan_sink(stmt.select, stmt.options)
        async with self.coord._rounds_lock:
            dep = await self.cluster.deploy(
                plan.graph, scope=stmt.name,
                mv_fragment=plan.mv_fragment, want_table=False)
            sink = SinkDef(stmt.name, plan.schema, dep, plan.mv_fragment,
                           sql=sql_text,
                           sources=tuple(sorted(
                               getattr(planner, "used_sources", ()))))
            self.catalog.sinks[stmt.name] = sink
        if not self._recovering:
            await self.coord.run_rounds(0 if not self.coord._started
                                        else 1)
        return sink

    async def alter_parallelism(self, name: str, n: int) -> MvDef:
        """Online rescale (reference: ALTER ... SET PARALLELISM, riding a
        meta reschedule — scale.rs:370): stop ONE MV's actors at a barrier
        (state flushes durably), rebuild its graph with the hash fragments
        at parallelism n binding the SAME table ids, and resume — other
        dataflows keep running throughout; the vnode-sliced state tables
        are re-read per new actor bitmap (state_table.rs:778)."""
        if name not in self.catalog.mvs:
            raise BindError(f"unknown MV {name!r}")
        mv = self.catalog.mvs[name]
        dependents = [d.name for d in list(self.catalog.mvs.values())
                      + list(self.catalog.sinks.values())
                      if any(up.name == name for up, _ in d.upstream_taps)]
        if dependents:
            raise BindError(
                f"cannot rescale {name!r}: {dependents} tap it "
                f"(drop them first)")
        entry = next(e for e in self._ddl_log
                     if e["kind"] == "mv" and e["name"] == name)
        await mv.deployment.stop()
        for up, ch in mv.upstream_taps:
            up.tap.remove(ch)
        del self.catalog.mvs[name]
        stmt = ast.parse(entry["sql"])
        self._recovering = True     # suppress log append inside execute
        saved_next_tid = self.env._next_table_id
        try:
            out = await self._create_mv(
                stmt, entry["sql"], parallelism=n,
                table_id_floor=entry["table_id_floor"])
        finally:
            self._recovering = False
            # the rebuild rewound the allocator to the MV's old floor;
            # restore the high-watermark or later DDL would hand out
            # table ids already owned by OTHER live MVs
            self.env._next_table_id = max(self.env._next_table_id,
                                          saved_next_tid)
        entry["parallelism"] = n
        self._persist_catalog()
        await self.coord.run_rounds(1)
        return out

    async def drop_sink(self, name: str) -> None:
        sink = self.catalog.sinks.pop(name)
        # stop drains uploads AND sink delivery (stop_all's quiesce), so
        # the final epoch reaches the target before the task dies here
        await sink.deployment.stop()
        self.coord.logstore.unregister_sink(name)
        for up, ch in sink.upstream_taps:
            up.tap.remove(ch)
        self._ddl_log = [e for e in self._ddl_log
                         if not (e["kind"] == "sink" and e["name"] == name)]
        self._persist_catalog()

    async def tick(self, rounds: int = 1,
                   interval_s: Optional[float] = None,
                   max_recoveries: int = 3) -> None:
        """Advance the session's barrier loop (meta's periodic injection).

        Barrier-collection failure (a dead actor) triggers AUTOMATIC
        recovery and the tick is retried; no operator in the loop
        (reference: meta/src/barrier/recovery.rs:332-625). The failure
        is first CLASSIFIED (`_classify_failure`): a blast radius
        contained to one terminal fragment rebuilds only that
        fragment's actors from the last committed epoch (upstream
        keeps its device state, channels replay the in-flight
        interval); anything wider falls back to the full stop-the-world
        rebuild. Consecutive attempts back off exponentially with
        jitter (`recovery_backoff_ms`) so a persistent fault cannot
        hot-loop through `max_recoveries`; a crash DURING recovery
        (mid DDL replay) counts as an attempt and is retried too."""
        flows_logged = any(e["kind"] in ("mv", "sink")
                           for e in self._ddl_log)
        if not self.catalog.mvs and not self.catalog.sinks \
                and not flows_logged:
            return
        attempts = 0
        while True:
            try:
                if flows_logged and not self.catalog.mvs \
                        and not self.catalog.sinks:
                    # a prior recovery died mid-DDL-replay (catalog
                    # cleared, log intact — e.g. the broker a sink
                    # targets was still down): resume recovering
                    # instead of silently no-opping the tick
                    raise RuntimeError(
                        "catalog empty with flows in the DDL log; "
                        "resuming interrupted recovery")
                await self.coord.run_rounds(rounds, interval_s=interval_s)
                return
            except RuntimeError:
                recovered = False
                while not recovered:
                    attempts += 1
                    if attempts > max_recoveries:
                        raise
                    await self._recovery_backoff(attempts)
                    try:
                        await self._recover_auto(
                            cause_hint="recovery_retry"
                            if attempts > 1 else None)
                        recovered = True
                    except asyncio.CancelledError:
                        raise
                    except BaseException:
                        # recovery itself died (kill-during-recovery):
                        # the DDL log is intact, the next attempt
                        # replays it from scratch
                        continue

    def flapping_causes(self) -> list[str]:
        """Causes whose recovery rate exceeds `recovery_flap_threshold`
        within the trailing flap window — non-empty means the session is
        DEGRADED (recoveries keep converging but the fault keeps coming
        back; /healthz surfaces it, the backoff escalates on it)."""
        import time as _time
        from ..utils.metrics import RECOVERY_FLAP_WINDOW_S
        thr = self.config.get("recovery_flap_threshold", 3)
        if thr <= 0 or not self._recovery_log:
            return []
        now = _time.monotonic()
        counts: dict[str, int] = {}
        for t, cause in self._recovery_log:
            if now - t <= RECOVERY_FLAP_WINDOW_S:
                counts[cause] = counts.get(cause, 0) + 1
        return sorted(c for c, n in counts.items() if n > thr)

    def _flap_excess(self) -> int:
        """How far past the flap threshold the worst cause is — feeds
        the backoff exponent so a flapping fault escalates toward the
        5s cap instead of hammering immediate rebuilds."""
        import time as _time
        from ..utils.metrics import RECOVERY_FLAP_WINDOW_S
        thr = self.config.get("recovery_flap_threshold", 3)
        if thr <= 0 or not self._recovery_log:
            return 0
        now = _time.monotonic()
        counts: dict[str, int] = {}
        for t, cause in self._recovery_log:
            if now - t <= RECOVERY_FLAP_WINDOW_S:
                counts[cause] = counts.get(cause, 0) + 1
        return max((n - thr for n in counts.values()), default=0)

    async def _recovery_backoff(self, attempt: int) -> None:
        """Exponential backoff with +-50% jitter between consecutive
        recovery attempts; the FIRST recovery of a tick is immediate
        (fast path for the common one-shot fault) UNLESS the flap
        detector says this fault keeps coming back — then even the
        first attempt waits, with the excess recovery rate feeding the
        exponent (recovery_total{cause} rates -> backoff base)."""
        base = self.config.get("recovery_backoff_ms", 50) / 1000.0
        effective = attempt + self._flap_excess()
        if effective < 2 or base <= 0:
            return
        import random
        from ..utils.metrics import RECOVERY_BACKOFF
        delay = min(base * (2 ** (effective - 2)), 5.0) \
            * (0.5 + random.random())
        RECOVERY_BACKOFF.inc(delay)
        await asyncio.sleep(delay)

    # ------------------------------------------------------------ recovery
    @staticmethod
    def _terminal_fid(flow):
        return (flow.mv_fragment if isinstance(flow, MvDef)
                else flow.sink_fragment)

    def _classify_failure(self):
        """Blast-radius classification (reference: the recovery scope
        decision in meta/src/barrier/recovery.rs — regional vs global).
        Returns a LIST of recovery units, one per independently
        recoverable radius:

            ("fragment", cause, flow, {terminal_fid})   terminal only
            ("cone",     cause, flow, cone_fids)        {failed + its
                                                        downstream cone}
            ("mesh",     cause, flow, cone_fids)        a fused mesh
                                                        fragment failed
            ("worker",   cause, None, plan)             cluster radius
            ("full",     cause, None, None)             stop-the-world

        Failures spanning SEVERAL deployments classify per deployment —
        two simultaneous contained faults recover independently instead
        of collapsing to one global full recovery. Any radius the
        classifier cannot prove contained is a single "full" unit with
        the cause named; correctness never weakens."""
        coord = self.coord
        if self.cluster is not None:
            return [self._classify_cluster_failure()]
        if coord._upload_failure is not None:
            return [("full", "upload_failure", None, None)]
        if coord.logstore.failure is not None:
            return [("full", "sink_delivery", None, None)]
        failed = dict(coord.failed_actors)
        if not failed:
            return [("full", "unknown", None, None)]
        if any(aid < 0 for aid in failed):
            return [("full", "worker_death", None, None)]
        if not bool(self.config.get("partial_recovery", 1)):
            return [("full", "partial_recovery_off", None, None)]
        # group the failed actors by owning deployment: the coordinator
        # records ALL failed actors, and each affected flow classifies
        # (and recovers) on its own
        by_dep: dict[int, tuple] = {}
        for aid in failed:
            for f in (list(self.catalog.mvs.values())
                      + list(self.catalog.sinks.values())):
                fid = getattr(f.deployment, "actor_fragment",
                              {}).get(aid)
                if fid is not None:
                    ent = by_dep.setdefault(id(f.deployment), (f, set()))
                    ent[1].add(fid)
                    break
            else:
                return [("full", "unknown_actor", None, None)]
        units = [self._classify_flow(f, fids)
                 for f, fids in by_dep.values()]
        for u in units:
            if u[0] == "full":
                return [u]        # one global rebuild covers everything
        return units

    def _classify_cluster_failure(self):
        """Cluster radius: a single worker's death (lease/connection
        loss) or a contained worker-reported actor failure (e.g. a
        severed DCN leg) rebuilds the affected actors — re-placed onto
        survivors when their worker died — plus their downstream
        closure; surviving workers keep their stores open at the
        committed manifest and every actor outside the closure keeps
        running. Anything wider is a full cluster recovery with the
        cause named."""
        coord = self.coord
        mgr = self.cluster
        if not bool(self.config.get("partial_recovery", 1)):
            return ("full", "partial_recovery_off", None, None)
        dead = sorted(wid for wid, h in mgr.workers.items()
                      if not h.info.alive)
        if len(dead) > 1:
            return ("full", "multi_worker", None, None)
        if coord.logstore.failure is not None:
            return ("full", "sink_delivery", None, None)
        # an upload failure raised by the dead worker's vanished sealed
        # report is subsumed by the worker radius (the aborted epochs
        # replay from the committed manifest); any OTHER upload failure
        # is a real store error
        if coord._upload_failure is not None and not dead:
            return ("full", "upload_failure", None, None)
        failed = dict(coord.failed_actors)
        # positive ids are worker-REPORTED actor failures (the worker
        # process itself is alive); negative ids are worker pseudo-
        # actors whose epochs failed
        actor_ids = sorted(aid for aid in failed if aid > 0)
        if not dead and not actor_ids:
            return ("full", "unknown", None, None)
        plan = mgr.plan_partial(dead[0] if dead else None, actor_ids)
        if plan is None:
            return ("full", "cluster", None, None)
        return ("worker", "worker_death" if dead else "dcn_failure",
                None, plan)

    def _classify_flow(self, flow, failed_fids):
        """One deployment's radius: the failed fragments plus their
        transitive downstream consumers (the CONE — every consumer saw
        part of the aborted interval's output, so its uncommitted state
        is tainted and it rebuilds with the failure). The cone's inbound
        frontier must be fully replay-buffered; upstream producers keep
        their device state."""
        dep = flow.deployment
        if dep.rebuild_info is None:
            return ("full", "unsupported_deployment", None, None)
        graph = dep.rebuild_info["graph"]
        cone = set(failed_fids)
        changed = True
        while changed:
            changed = False
            for fid in list(cone):
                for d, _k in dep.fragment_consumers.get(fid, ()):
                    if d not in cone:
                        cone.add(d)
                        changed = True
        mesh = any(aid in self.coord.mesh_fragments
                   for fid in cone
                   for aid in dep.frag_actor_ids.get(fid, ()))
        for fid in cone:
            frag = graph.fragments[fid]
            if getattr(frag, "remote_worker", None):
                return ("full", "remote_fragment", None, None)
            kinds = {n.kind for n in _fragment_node_kinds(frag)}
            if "stream_scan" in kinds:
                return ("full", "backfill_fragment", None, None)
            if fid in failed_fids and "nexmark_source" in kinds:
                # a source fragment has no inbound replay frontier to
                # re-drive it — its cone is the whole deployment with
                # nothing buffered upstream of the failure
                return ("full", "source_fragment", None, None)
        terminal = self._terminal_fid(flow)
        tap = getattr(flow, "tap", None)
        if terminal in cone and tap is not None and tap.channels:
            # a live MV-on-MV consumer taps the terminal — it saw part
            # of the aborted interval through a channel outside the
            # deployment's rebuild scope
            return ("full", "downstream_tap", None, None)
        # the flow must be durable: a volatile fragment has no committed
        # state to rebuild from
        entry = next((e for e in self._ddl_log
                      if e["name"] == flow.name
                      and e["kind"] in ("mv", "sink")), None)
        if entry is None or entry.get("config", {}).get(
                "streaming_durability", 1) == 0:
            return ("full", "volatile", None, None)
        # every edge ENTERING the cone (the inbound frontier) must carry
        # a replay buffer; intra-cone edges are reset and re-driven by
        # the rebuilt producers themselves
        for (u, d, k), mat in dep.rebuild_info["channels"].items():
            if d not in cone or u in cone:
                continue
            for row in mat:
                for ch in row:
                    if not ch.replay_enabled:
                        return ("full", "unbuffered_edge", None, None)
        scope = ("mesh" if mesh
                 else "fragment" if cone == {terminal}
                 else "cone")
        return (scope, "actor_exception", flow, cone)

    async def _recover_auto(self, cause_hint=None) -> None:
        """Classify, then recover every unit at its narrowest correct
        scope. Any exception during a partial path falls back to ONE
        full rebuild — partial recovery is an optimization, never a
        weaker correctness mode."""
        import time as _time
        t0 = _time.monotonic_ns()
        units = self._classify_failure()
        cause = units[0][1]
        if cause == "unknown" and cause_hint:
            # a retry after a crashed recovery starts from a fresh
            # coordinator with no failure marker — name it honestly
            cause = cause_hint
        if units[0][0] != "full":
            try:
                # independent radii recover one after another; each
                # notes its own scope/duration/actors so the metrics
                # and /healthz reflect every contained rebuild
                for scope, u_cause, flow, plan in units:
                    t_u = _time.monotonic_ns()
                    if scope == "worker":
                        rebuilt = await self._worker_partial_recover(plan)
                    else:
                        rebuilt = await self._partial_recover(flow, plan)
                    self._note_recovery(scope, u_cause, t_u, rebuilt)
                return
            except asyncio.CancelledError:
                raise
            except BaseException:
                cause = "partial_recovery_failed"
        await self._auto_recover()
        all_ids = sorted(
            a.actor_id
            for f in (list(self.catalog.mvs.values())
                      + list(self.catalog.sinks.values()))
            for a in f.deployment.actors)
        self._note_recovery("full", cause, t0, all_ids)

    def _note_recovery(self, scope: str, cause: str, t0_ns: int,
                       actors) -> None:
        import time as _time
        from ..utils.metrics import (GLOBAL_METRICS, RECOVERY_BUCKETS,
                                     RECOVERY_DURATION, RECOVERY_TOTAL)
        dur_ns = _time.monotonic_ns() - t0_ns
        RECOVERY_TOTAL.inc()
        GLOBAL_METRICS.counter("recovery_total", scope=scope,
                               cause=cause).inc()
        RECOVERY_DURATION.observe(dur_ns / 1e9)
        GLOBAL_METRICS.histogram("recovery_duration_seconds",
                                 buckets=RECOVERY_BUCKETS,
                                 scope=scope).observe(dur_ns / 1e9)
        self.last_recovery = {"scope": scope, "cause": cause,
                              "duration_s": round(dur_ns / 1e9, 6),
                              "actors": list(actors)}
        self.coord.tracer.note_recovery(scope, cause, dur_ns, actors)
        # session-owned ring: survives the coordinator swap a FULL
        # recovery performs (the tracer above dies with it)
        self.recovery_ring.note_recovery(scope, cause, dur_ns, actors)
        self.event_log.emit("recovery", scope=scope, cause=cause,
                            duration_s=round(dur_ns / 1e9, 6),
                            actors=list(actors))
        # flap detection: the recovery RATE per cause feeds the backoff
        # base and the degraded surface (recovery_flapping{cause})
        self._recovery_log.append((_time.monotonic(), cause))
        flapping = set(self.flapping_causes())
        seen = {c for _, c in self._recovery_log}
        for c in seen:
            GLOBAL_METRICS.gauge("recovery_flapping", cause=c).set(
                1.0 if c in flapping else 0.0)
            if c in flapping:
                self.event_log.emit("flap_detected", cause=c)

    async def _partial_recover(self, flow, cone) -> list[int]:
        """Rebuild one deployment's failure CONE in place (the narrow
        scope the classifier proved safe): cancel the cone's actors,
        discard exactly its staged uncommitted writes, reset the
        intra-cone channels, rebuild the same actor/table ids from the
        committed epoch in topo order, re-attach the terminal plumbing
        (tap, serving hooks, changelog writers) when the cone includes
        the terminal, arm replay on every edge entering the cone (the
        inbound frontier), respawn. The coordinator, every fragment
        UPSTREAM of the cone, and their device state are untouched —
        upstream never re-backfills. `cone` may be a single terminal
        fragment (PR 9's scope), an interior fragment plus its
        downstream consumers, or a cone containing a fused mesh
        fragment. Returns the rebuilt actor ids (the chaos gate asserts
        this set is strictly smaller than the full topology's)."""
        from ..plan.build import rebuild_fragment
        from ..utils.faults import FAULTS, FaultInjected
        coord = self.coord
        dep = flow.deployment
        cone = set(cone) if not isinstance(cone, set) else cone
        terminal = self._terminal_fid(flow)
        self.recoveries += 1
        async with coord._rounds_lock:
            # 1. let fully-collected checkpoints finish committing: after
            # this the ONLY uncommitted staged state belongs to the
            # failed (never-collected) epoch(s). Raises on a parked
            # upload failure -> caller falls back to full recovery.
            # Sink DELIVERY drains too: a rebuilt sink target recovers
            # its committed seq from the target itself (e.g. the
            # FileSink file scan), so an in-flight delivery write racing
            # the rebuild would make the crash-window entry deliver
            # twice.
            await coord.drain_uploads()
            await coord.logstore.drain()
            if FAULTS.active and FAULTS.hit(
                    "recovery_crash", phase="partial") is not None:
                raise FaultInjected("injected crash during partial "
                                    "recovery")
            # 2. cancel every cone fragment's actor tasks (dead + kin)
            ids = set()
            for fid in cone:
                ids.update(dep.frag_actor_ids[fid])
            by_id = {a.actor_id: i for i, a in enumerate(dep.actors)}
            for aid in sorted(ids):
                t = dep.tasks[by_id[aid]]
                if not t.done():
                    t.cancel()
                try:
                    await t
                except (asyncio.CancelledError, Exception):
                    pass
            # 3. drop the cone's staged uncommitted writes + pending
            # deferred flushes; fragments upstream of the cone keep
            # their partial-epoch writes, which commit with the next
            # checkpoint (their dirty tracking already cleared at the
            # failed barrier)
            table_ids = set()
            for fid in cone:
                table_ids.update(dep.frag_tables.get(fid, {}).values())
            clog = coord.logstore.mv_logs.get(flow.name)
            if isinstance(flow, MvDef) and terminal in cone \
                    and clog is not None:
                table_ids.add(clog.table_id)
            discard = getattr(self.store, "discard_staged_tables", None)
            if discard is not None and table_ids:
                discard(table_ids)
            # 4. the coordinator survives: clear the failure marker and
            # the never-collected epochs; injection resumes at the same
            # epoch stream every surviving actor already follows
            coord.clear_failure()
            # 5. reset INTRA-cone channels: both ends are rebuilt, so
            # queued leftovers and the buffered suffix belong to dead
            # incarnations — the rebuilt producers re-derive and
            # re-emit the suffix themselves (starting with the
            # synthetic INITIAL they receive from the frontier)
            for (u, d, k), mat in dep.rebuild_info["channels"].items():
                if d in cone and u in cone:
                    for row in mat:
                        for ch in row:
                            ch.reset_for_rebuild()
            # 5b. channel-free mesh replay (ROADMAP 3d): capture each
            # mesh-resident agg's uncommitted ingest suffix — sealed
            # uncommitted MeshIngestLog intervals, the log's open
            # interval, and undrained pending chunks — BEFORE the
            # rebuild discards the executors. The suffix is preloaded
            # straight into the rebuilt fused program (one fused scan
            # at the first post-INITIAL barrier) and the frontier
            # channels skip exactly these chunk objects by identity,
            # so recovery re-runs ZERO per-chunk host dispatches.
            # Identity matching requires the channel message object ==
            # the logged object, so coalescing disables the fast path.
            def _mesh_preload_exec(fid):
                for root in dep.roots.get(fid, []):
                    node = root
                    while node is not None:
                        if callable(getattr(node, "preload_replay",
                                            None)):
                            return node
                        node = getattr(node, "input", None)
                return None
            mesh_preload: dict[int, list] = {}
            if getattr(self.env, "chunk_coalesce_max", 0) == 0:
                for fid in cone:
                    # a rebuilt (intra-cone) producer re-derives and
                    # re-emits the suffix itself — preloading too would
                    # double-apply it
                    if any(u in cone
                           for (u, d, _k) in dep.rebuild_info["channels"]
                           if d == fid):
                        continue
                    ex = _mesh_preload_exec(fid)
                    if ex is None:
                        continue
                    chunks = []
                    log = getattr(ex, "ingest_log", None)
                    if log is not None:
                        for _ep, chs in log.entries():
                            chunks.extend(chs)
                        chunks.extend(log._pending)
                    chunks.extend(getattr(ex, "_pending_chunks", []))
                    if chunks:
                        mesh_preload[fid] = chunks
            # 6. rebuild the cone's actors in topo order (same ids,
            # same tables — producers exist before their consumers
            # poll, exactly like the initial build)
            graph = dep.rebuild_info["graph"]
            order = [f for f in graph.topo_order() if f in cone]
            new_actors = []
            self.env.memory_scope = flow.name
            try:
                for fid in order:
                    new_actors.extend(rebuild_fragment(dep, fid))
            finally:
                self.env.memory_scope = None
            # 6b. hand the captured suffix to the REBUILT executors
            # (installed into the pending queue at their INITIAL
            # barrier, after the durable state rebuild)
            for fid, chunks in list(mesh_preload.items()):
                ex = _mesh_preload_exec(fid)
                if ex is not None:
                    ex.preload_replay(chunks)
                else:
                    del mesh_preload[fid]
            # 7. re-attach terminal plumbing when the cone includes it
            if isinstance(flow, MvDef) and terminal in cone:
                roots = dep.roots[terminal]
                root_actor = next(a for a in new_actors
                                  if a.consumer is roots[0])
                assert root_actor.dispatcher is None
                root_actor.dispatcher = flow.tap     # empty by contract
                hooks = coord.serving.register_mv(
                    flow.name, roots[0].table, roots[0].table.schema,
                    roots[0].table.pk_indices, n_hooks=len(roots))
                for r, h in zip(roots, hooks):
                    r.serving_hook = h
                if clog is not None:
                    # same durable log (subscriptions keep their pumps);
                    # FRESH writers — the old ones hold the aborted
                    # interval's rows, which replay recomputes
                    from ..logstore.log import MvChangelogWriter
                    clog.state_table = roots[0].table
                    clog.writers = [MvChangelogWriter(clog, i)
                                    for i in range(len(roots))]
                    for r, w in zip(roots, clog.writers):
                        r.changelog_log = w
            # 8. arm replay on every FRONTIER edge (entering the cone),
            # THEN spawn: the rebuilt consumers see a synthetic INITIAL
            # barrier at the committed point, the buffered uncommitted
            # suffix, then the live stream (queue duplicates skipped by
            # sequence number); interior rebuilt fragments propagate
            # that INITIAL + their recomputed output through the reset
            # intra-cone channels
            for (u, d, k), mat in dep.rebuild_info["channels"].items():
                if d not in cone or u in cone:
                    continue
                skips = mesh_preload.get(d)
                for row in mat:
                    for ch in row:
                        if skips:
                            ch.begin_replay(
                                skip_refs={id(c) for c in skips})
                        else:
                            ch.begin_replay()
            for a in new_actors:
                dep.tasks[by_id[a.actor_id]] = a.spawn()
        return sorted(ids)

    async def _worker_partial_recover(self, plan) -> list[int]:
        """Cluster radius (cluster/meta_service.py owns the protocol):
        re-place the dead worker's actors onto survivors and rebuild
        their downstream closure in place — surviving workers keep
        their stores open at the committed manifest and every actor
        outside the closure keeps running."""
        self.recoveries += 1
        async with self.coord._rounds_lock:
            # stale worker failure reports racing the rebuild are
            # dropped by the push handler while this is set (their
            # actors are already being torn down)
            self._recovering = True
            try:
                return await self.cluster.partial_recover(plan)
            finally:
                self._recovering = False

    async def _auto_recover(self) -> None:
        """Tear down every actor, drop uncommitted store state, rebuild
        all dataflows from the DDL log at the committed epoch, resume."""
        self.recoveries += 1
        await self.crash()
        # VOLATILE sessions (every MV planned with streaming_durability
        # = 0) recover by recomputing from scratch: stateful executors
        # lost their state, but source offsets and MV tables would
        # otherwise SURVIVE in the still-alive in-memory store —
        # resuming sources past state the executors no longer have
        # silently loses joins/aggregates (found round 5: pre-crash
        # person rows x post-crash auction rows vanished). A whole-store
        # reset is the reference's in-memory-backend semantics: process
        # state dies with the failure, everything replays from offset 0
        # and the rebuilt MVs converge exactly.
        flows = [e for e in self._ddl_log
                 if e["kind"] in ("mv", "sink")]
        all_volatile = flows and all(
            e.get("config", {}).get("streaming_durability", 1) == 0
            for e in flows)
        if all_volatile and isinstance(self.store, MemoryStateStore):
            blob = getattr(self.store, "_catalog_blob", None)
            self.store = MemoryStateStore()
            if blob is not None:
                self.store._catalog_blob = blob
        else:
            reset = getattr(self.store, "reset_uncommitted", None)
            if reset is not None:
                reset()
        # fresh coordinator: epochs re-floor at the committed epoch, no
        # stale in-flight state (the dict-delta cursor carries over — the
        # dictionary itself survives in-process recovery)
        old_cursor = self.coord.dict_cursor
        self.coord = BarrierCoordinator(
            self.store,
            checkpoint_max_inflight=self.config.get(
                "checkpoint_max_inflight", 2))
        self.coord.dict_cursor = old_cursor
        self.env = BuildEnv(
            self.store, self.coord,
            chunk_coalesce_max=self.config.get(
                "streaming_chunk_coalesce", 0),
            partial_recovery=bool(self.config.get("partial_recovery", 1)))
        self.env.session = self
        self._apply_memory_config()
        # fresh ServingManager with the coordinator: every cache is
        # invalidated and rebuilds from the recovered epoch on its next
        # touch (the recovery-consistency contract)
        self._apply_serving_config()
        # fresh StreamingStats/watchdog ride the new coordinator; the
        # monitor endpoint (if any) reads `self.coord` live, so it keeps
        # serving across the swap
        self._apply_obs_config()
        self._apply_logstore_config()
        # fresh scrubber rides the new coordinator; retry budget +
        # quarantine repair source re-attach to the (surviving) store
        self._apply_storage_config()
        if self.cluster is not None:
            # prune dead workers, reset survivors (reopen their store
            # handles at the committed manifest, fresh SST blocks) and
            # re-attach them to the new coordinator; the DDL replay
            # below re-places every fragment over the smaller live set
            await self.cluster.on_recovery()
        self.catalog.mvs.clear()
        self.catalog.sinks.clear()
        log = list(self._ddl_log)
        self._recovering = True
        saved_config = dict(self.config)
        from ..utils.faults import FAULTS, FaultInjected
        try:
            for i, entry in enumerate(log):
                if FAULTS.active and FAULTS.hit(
                        "recovery_crash", phase="full",
                        entry=i) is not None:
                    # kill-during-recovery (chaos harness): the DDL log
                    # is intact, tick retries the whole recovery
                    raise FaultInjected(
                        f"injected crash during recovery replay "
                        f"(entry {i})")
                self.env._next_table_id = entry.get(
                    "table_id_floor", self.env._next_table_id)
                self._replay_parallelism = entry.get("parallelism", 1)
                # each entry replays under ITS OWN planning-time config;
                # entries without one (sources, old logs) use the defaults
                self.config = {**saved_config, **entry.get("config", {})}
                self.env.chunk_coalesce_max = self.config.get(
                    "streaming_chunk_coalesce", 0)
                await self.execute(entry["sql"])
        finally:
            self.config = saved_config
            self._recovering = False
            self._replay_parallelism = 1
        self._ddl_log = log
        await self.coord.run_rounds(0)

    async def drop_mv(self, name: str) -> None:
        """Stop one MV's actors and detach its upstream taps. MVs that
        READ this one must be dropped first (the reference rejects
        dropping a relation with dependents)."""
        dependents = [d.name for d in list(self.catalog.mvs.values())
                      + list(self.catalog.sinks.values())
                      if any(up.name == name for up, _ in d.upstream_taps)]
        if dependents:
            raise BindError(
                f"cannot drop {name!r}: {dependents} read it")
        mv = self.catalog.mvs.pop(name)
        self.coord.serving.unregister_mv(name)
        self.coord.logstore.unregister_mv(name)
        await mv.deployment.stop()
        for up, ch in mv.upstream_taps:
            up.tap.remove(ch)
        self._ddl_log = [e for e in self._ddl_log
                         if not (e["kind"] == "mv" and e["name"] == name)]
        self._persist_catalog()

    async def crash(self) -> None:
        """Abandon every actor task WITHOUT the stop protocol — the
        process-kill simulation used by restart/recovery tests. Catalog
        and store are left as-is (a real crash persists both). The
        background uploader dies with the process too: sealed-but-
        uncommitted epochs are dropped (commit point = manifest swap, so
        nothing torn is ever visible) and recovery replays from the last
        committed epoch."""
        for d in (list(self.catalog.mvs.values())
                  + list(self.catalog.sinks.values())):
            for t in d.deployment.tasks:
                if not t.done():
                    t.cancel()
                try:
                    await t
                except (asyncio.CancelledError, Exception):
                    pass
        if self.cluster is not None:
            # workers abandon their actors too (a real meta crash takes
            # the control connections down and the workers self-reset;
            # in-process crash simulation must do it explicitly)
            await self.cluster.reset_all()
        await self.coord.abort_uploads()

    async def drop_all(self) -> None:
        for name in reversed(list(self.catalog.sinks)):
            await self.drop_sink(name)
        # reverse creation order: downstream MVs tap upstream ones
        for name in reversed(list(self.catalog.mvs)):
            await self.drop_mv(name)

    async def shutdown(self) -> None:
        """Graceful stop WITHOUT dropping: actors stop at a barrier, the
        durable catalog and state stay for the next incarnation (the
        playground's exit path under --data; drop_all would erase the
        DDL log)."""
        await self.stop_monitor()
        await self.stop_subscription_server()
        self.event_log.close()
        self.metrics_history.close()
        if self.cluster is not None:
            for name in reversed(list(self.catalog.sinks)):
                sink = self.catalog.sinks.pop(name)
                await sink.deployment.stop()
            for name in reversed(list(self.catalog.mvs)):
                await self.catalog.mvs[name].deployment.stop()
            self.catalog.mvs.clear()
            await self.cluster.stop()
            self.cluster = None
        else:
            for name in reversed(list(self.catalog.sinks)):
                sink = self.catalog.sinks.pop(name)
                await sink.deployment.stop()
                for up, ch in sink.upstream_taps:
                    up.tap.remove(ch)
            for name in reversed(list(self.catalog.mvs)):
                mv = self.catalog.mvs[name]
                await mv.deployment.stop()
                for up, ch in mv.upstream_taps:
                    up.tap.remove(ch)
            self.catalog.mvs.clear()
        await self.coord.join_watchdog()

    # -------------------------------------------------------- batch query
    def query(self, sql_text: str) -> list[tuple]:
        stmt = ast.parse(sql_text)
        assert isinstance(stmt, ast.Select), "query() takes SELECT"
        return self.query_select(stmt)

    def query_select(self, sel: ast.Select) -> list[tuple]:
        """Serving path, synchronous form (REPL / tests on the loop
        thread): pinned snapshot caches + point-lookup index when the
        MVs are cached, else the batch engine over committed MV
        snapshots (reference: local batch execution, scheduler/local.rs
        over batch/src/executor/ — scan/filter/join/agg/sort/limit)."""
        return self.query_select_full(sel)[2]

    def query_select_full(self, sel: ast.Select):
        """-> (names, types, rows), synchronously. A cache miss marks
        the MV wanted (the next collected barrier builds its cache) and
        falls back to the full-scan path."""
        from .batch import run_batch_select_full
        from ..serving.executor import rel_mv_names, run_pinned_select
        from .system_tables import SYSTEM_TABLES, make_system_scan
        serving = self.coord.serving
        names = rel_mv_names(sel.rel)
        if names and any(n in SYSTEM_TABLES for n in names):
            # rw_* system tables: synthesized relations through the
            # stock batch pipeline (they are not MVs — never pinned)
            return run_batch_select_full(
                self.catalog, sel, scan=make_system_scan(self))
        pins = serving.pin(names) if names else None
        if pins is None:
            return run_batch_select_full(self.catalog, sel)
        try:
            return run_pinned_select(self.catalog, sel, pins, serving)
        finally:
            serving.unpin(pins)

    async def run_serving_select(self, sel: ast.Select):
        """-> (names, types, rows). The concurrent serving path (pgwire
        and any async caller): snapshots pin ON THE LOOP (atomic wrt
        barrier-time cache advancement), then the pure-numpy pipeline
        runs on a ServingPool worker thread under admission control and
        the per-query timeout — a big scan no longer stalls barrier
        injection. Uncached queries stay on the loop (the legacy
        committed-snapshot scan) and mark their MVs wanted."""
        from .batch import run_batch_select_full
        from ..serving.executor import rel_mv_names, run_pinned_select
        from .system_tables import SYSTEM_TABLES, make_system_scan
        serving = self.coord.serving
        names = rel_mv_names(sel.rel)
        if names and any(n in SYSTEM_TABLES for n in names):
            return run_batch_select_full(
                self.catalog, sel, scan=make_system_scan(self))
        pins = serving.pin(names) if names else None
        if pins is None:
            return run_batch_select_full(self.catalog, sel)
        return await serving.pool.run(
            lambda: run_pinned_select(self.catalog, sel, pins, serving),
            cleanup=lambda: serving.unpin(pins))


def _fragment_node_kinds(frag) -> list:
    """Every plan Node of one fragment's tree (Exchange leaves excluded)
    — the blast-radius classifier checks kinds (e.g. stream_scan) here."""
    from ..plan.graph import Exchange
    out = []

    def walk(n):
        if isinstance(n, Exchange):
            return
        out.append(n)
        for i in n.inputs:
            walk(i)

    walk(frag.root)
    return out


def _render_batch_plan(sel) -> list:
    """Batch (serving) pipeline of a bare SELECT as text — mirrors the
    executor order in frontend/batch.py."""
    def rel_lines(rel, depth):
        pad = "  " * depth
        if isinstance(rel, ast.TableRel):
            return [f"{pad}batch_scan {rel.name}"
                    + (f" AS {rel.alias}" if rel.alias else "")]
        if isinstance(rel, ast.JoinRel):
            jt = getattr(rel, "join_type", "inner")
            return ([f"{pad}batch_hash_join type={jt}"]
                    + rel_lines(rel.left, depth + 1)
                    + rel_lines(rel.right, depth + 1))
        return [f"{pad}{type(rel).__name__}"]

    out = []
    depth = 0
    if sel.limit is not None or sel.offset:
        out.append("batch_limit "
                   f"limit={sel.limit} offset={sel.offset}")
        depth += 1
    if sel.order_by:
        out.append("  " * depth + "batch_sort")
        depth += 1
    if sel.group_by or any(contains_agg(it.expr) for it in sel.items):
        out.append("  " * depth + "batch_hash_agg")
        depth += 1
    out.append("  " * depth + "batch_project")
    depth += 1
    if sel.where is not None:
        out.append("  " * depth + "batch_filter")
        depth += 1
    out.extend(rel_lines(sel.rel, depth))
    return out
