"""Barrier coordinator — the system heartbeat (meta-lite).

Reference: meta's GlobalBarrierManager (src/meta/src/barrier/mod.rs:481,634,
669,779) + the CN-side LocalBarrierManager (src/stream/src/task/
barrier_manager.rs) collapsed into one in-process coordinator: paces barrier
injection (`barrier_interval_ms`, system_param/mod.rs:77), pushes barriers
into every source's dedicated channel, waits until every actor reports
collection, then completes the epoch IN ORDER. Barrier latency (inject ->
collected) is the headline latency metric (grafana meta_barrier_latency).

Checkpoint durability is PIPELINED (reference: the Hummock event-handler
uploader, src/storage/src/hummock/event_handler/uploader/ — epochs seal at
the barrier, SSTs build/upload in background tasks, version commits apply
in order): a checkpoint barrier only ENQUEUES its epoch to the background
uploader task; the deferred executor flushes (blocking d2h), shared-buffer
seal, SST build/upload and the in-order manifest swap all run behind the
stream, so epoch N+1's compute overlaps epoch N's durable flush. A bounded
in-flight window (`checkpoint_max_inflight`, default 2) backpressures
barrier INJECTION when full — recovery replay distance stays bounded and a
slow object store degrades throughput, never correctness. `committed_epoch`
still advances only at the manifest swap, strictly in epoch order; with
`checkpoint_max_inflight=0` (or a store without seal support) the old
inline `store.sync()` path runs unchanged.
"""

from __future__ import annotations

import asyncio
import sys
import time
from dataclasses import dataclass, field
from typing import Optional

from ..common.epoch import EpochPair, next_epoch, INVALID_EPOCH
from ..memory.manager import MemoryManager
from ..serving.manager import ServingManager
from ..state.store import StateStore
from ..stream.message import Barrier, BarrierKind, Mutation
from ..utils.faults import FAULTS, FaultInjected
from ..utils.trace import (
    SPAN_LOG, SpanScope, current_scope, set_scope, span,
)


@dataclass
class EpochState:
    barrier: Barrier
    remaining: set[int]
    done: asyncio.Event = field(default_factory=asyncio.Event)


@dataclass
class _UploadJob:
    """One checkpoint handed to the background uploader."""
    prev_epoch: int          # the epoch being made durable
    curr_epoch: int          # barrier whose trace gets the phase spans
    enqueued_ns: int = 0     # where its `flush.queue` span starts


async def _off_loop(fn, *args):
    """`asyncio.to_thread`, and as the span `flush.loop_wait` how long its
    result then waited for the event loop: the actors' host work runs on
    the same loop as the uploader's continuations."""
    def run():
        out = fn(*args)
        return out, time.monotonic_ns()
    out, t_done = await asyncio.to_thread(run)
    sc = current_scope()
    if sc is not None:
        sc.leaf("flush.loop_wait", t_done, time.monotonic_ns())
    return out


class BarrierCoordinator:
    def __init__(self, store: StateStore, interval_ms: int = 1000,
                 checkpoint_frequency: int = 1,
                 checkpoint_max_inflight: int = 2):
        self.store = store
        self.interval_ms = interval_ms
        self.checkpoint_frequency = checkpoint_frequency
        self.source_queues: list[asyncio.Queue] = []
        self.actor_ids: set[int] = set()
        self._epochs: dict[int, EpochState] = {}
        # Seed from the store's committed epoch: post-restart epochs must be
        # strictly greater than anything a previous incarnation committed
        # (reference: recovery resumes at the last committed Hummock epoch).
        self._prev_epoch = store.committed_epoch()
        self._barrier_count = 0
        self._started = False
        self.latencies_ns: list[int] = []
        self.committed_epochs: list[int] = []
        self._stopped = False
        self._failure: Optional[tuple] = None
        # EVERY reported failure this generation (actor_id -> exc):
        # `_failure` keeps the first-reported one for messages, but the
        # blast-radius classification must see all of them — two actors
        # dying in one epoch across two fragments is a full recovery,
        # not a partial rebuild of whichever reported last
        self.failed_actors: dict[int, BaseException] = {}
        # exchange channels with replay buffers (plan/build.py): trimmed
        # at every checkpoint commit so each holds exactly the
        # uncommitted message suffix per-fragment recovery would replay
        self.replay_channels: list = []
        # Serializes whole ROUNDS (inject..collect) across concurrent
        # callers: the REPL's \tick / DDL bring-up can otherwise interleave
        # with the background ticker on the same coordinator, breaking the
        # in-order epoch completion contract (ADVICE r2 #1).
        self._rounds_lock = asyncio.Lock()
        # open-vocabulary dict durability (common/types.py): strings
        # minted below this cursor are already in the durable delta log.
        # Seeded to the CURRENT dict length when the store was restored
        # from a log (Session sets dict_cursor); 0 on a fresh store so
        # the first checkpoint persists everything minted so far.
        self.dict_cursor = 0
        # headline health metric (reference meta_barrier_latency,
        # grafana/risingwave-dev-dashboard.dashboard.py:894)
        from ..utils.metrics import GLOBAL_METRICS
        self._metrics_latency = GLOBAL_METRICS.histogram(
            "meta_barrier_latency_seconds")
        # per-epoch spans (utils/trace.py — the reference's barrier
        # TracingContext + grafana trace panel analogue)
        from ..utils.trace import EpochTracer
        self.tracer = EpochTracer()
        # durable event log (meta/event_log.py): the session attaches
        # its log here (and re-attaches after recovery swaps the
        # coordinator); None = no emissions. Every control-plane
        # incident this coordinator detects (barrier stalls, broker
        # split adoptions) goes through the one choke point.
        self.event_log = None
        # barrier-paced metrics history (utils/metrics_history.py): the
        # session swaps in its own long-lived instance (survives
        # recovery's coordinator replacement) and configures retention;
        # compute nodes keep this default so workers sample locally too.
        from ..utils.metrics_history import MetricsHistory
        self.metrics_history = MetricsHistory()
        # stuck-barrier watchdog (the MonitorService/risectl-trace
        # analogue): a background task fires once per stalled epoch when
        # an in-flight barrier exceeds this threshold — logs the full
        # format_stuck_barrier_report and bumps barrier_stalls_total.
        # None/0 disables. SET barrier_stall_threshold_ms plumbs here.
        self.stall_threshold_ms: float | None = 60_000.0
        self._watchdog_task: Optional[asyncio.Task] = None
        self._stalls_reported: set[int] = set()
        from ..utils.metrics import BARRIER_STALLS
        self._m_stalls = BARRIER_STALLS
        # actor-level streaming metrics registrar (stream/monitor.py):
        # build_graph registers every actor chain, SET metric_level
        # re-instruments live actors through Session._apply_obs_config
        from ..stream.monitor import StreamingStats
        self.stats = StreamingStats()
        # HBM budget authority (memory/manager.py): executors register at
        # build time, accounting gauges refresh at every collected
        # barrier, and eviction runs here — between epochs, when every
        # executor is idle — once a budget is configured (Session plumbs
        # hbm_budget_bytes / memory_eviction_policy through).
        self.memory = MemoryManager()
        # Storage scrubber (state/scrub.py): verifies manifest-referenced
        # objects and sweeps orphan SSTs on the same between-epochs pulse
        # the memory manager uses; no-ops on non-durable stores. Session
        # plumbs storage_scrub_interval / storage_scrub_batch here.
        from ..state.scrub import StorageScrubber
        self.scrubber = StorageScrubber(store)
        # Serving authority (serving/manager.py): per-MV snapshot caches
        # advance at every collected barrier — the same between-epochs
        # moment the memory manager uses — so pinned reads always sit on
        # a sealed epoch, consistent across every MV of the coordinator.
        self.serving = ServingManager()
        # Changelog log-store authority (logstore/log.py): per-sink
        # delivery tasks and changelog-subscription pumps wake on the
        # commit pulse this coordinator emits at every checkpoint commit
        # (inline, background-uploader and cluster paths alike); a
        # delivery failure parks here and fail-stops the next injection
        # exactly like an upload failure.
        from ..logstore.log import LogStoreHub
        self.logstore = LogStoreHub(store)
        # Background compaction & retention plane (state/compactor.py):
        # barrier-paced merges off the commit path (attaching it flips
        # HummockStateStore.inline_compaction off), pin-aware GC over
        # serving pins + durable subscription cursors, and broker
        # retention floors from committed source offsets. Pulsed in the
        # same between-epochs window as the scrubber; Session plumbs
        # compaction_* / broker_retention_interval here.
        from ..state.compactor import (BackgroundCompactor,
                                       BrokerRetentionManager)
        self.compactor = BackgroundCompactor(
            store, serving=self.serving, logstore=self.logstore)
        self.compactor.retention = BrokerRetentionManager(
            store, lambda: self.source_execs)
        self.compactor._sync_inline_flag()
        # ---- async epoch uploader (the checkpoint pipeline) ----
        self._upload_q: asyncio.Queue[_UploadJob] = asyncio.Queue()
        self._uploader_task: Optional[asyncio.Task] = None
        self._inflight = 0            # enqueued-but-uncommitted checkpoints
        self._slot_free = asyncio.Event()
        self._slot_free.set()
        self._upload_failure: Optional[BaseException] = None
        self.upload_busy_ns = 0       # total background flush+upload+commit
        self.backpressure_wait_ns = 0  # injection stalls on a full window
        from ..utils.metrics import (
            CHECKPOINT_BACKPRESSURE_SECONDS, CHECKPOINT_COMMIT_SECONDS,
            CHECKPOINT_INFLIGHT, CHECKPOINT_SEAL_SECONDS,
            CHECKPOINT_UPLOAD_SECONDS)
        self._m_seal = CHECKPOINT_SEAL_SECONDS
        self._m_upload = CHECKPOINT_UPLOAD_SECONDS
        self._m_commit = CHECKPOINT_COMMIT_SECONDS
        self._m_inflight = CHECKPOINT_INFLIGHT
        self._m_backpressure = CHECKPOINT_BACKPRESSURE_SECONDS
        # ---- fused mesh fragments (plan/build.py _register_mesh) ----
        # actor_id -> (n_shards, identity). A fused mesh fragment lowers
        # a whole exchange -> sharded-executor chain onto the device mesh
        # as ONE actor: its S shards participate in every epoch as ONE
        # collection (one entry in EpochState.remaining, one fence on the
        # sharded state — a collective boundary), where the host-exchange
        # alternative is S actors = S collections + S per-device fences
        # per epoch. The registry makes that legible to /healthz and tests.
        self.mesh_fragments: dict[int, tuple[int, str]] = {}
        self._mesh_shuffle_labels: dict[int, tuple] = {}
        # ---- fused mesh CHAINS (plan/build.py _fuse_mesh_chains) ----
        # chain label -> {"fids": (producer..., consumer),
        # "consumer_actor": id}. A chain spans MULTIPLE fragments whose
        # producer stages were hollowed into the consumer's fused program:
        # one epoch fence covers the whole chain (hollow producers are
        # fence-exempt — they dispatch no device programs).
        self.mesh_chains: dict[str, dict] = {}
        # ---- cluster mode (cluster/meta_service.py) ----
        # worker_id -> WorkerHandle: barriers are ALSO injected over RPC
        # into every compute node's source queues, each worker collects
        # its own actors and reports ONCE per epoch (the per-worker
        # injection/collection path of the reference GlobalBarrierManager);
        # workers appear in EpochState.remaining as pseudo-actors with
        # NEGATIVE ids (-worker_id), so collection/failure machinery is
        # shared with the in-process path.
        self.workers: dict[int, object] = {}
        # compute-node side: called with (epoch, sst_ids) when this
        # process's store finished seal+upload+local-install for an epoch
        # — the worker's "sealed" report to meta rides it
        self.commit_listener = None
        # ---- split discovery (connectors/broker.py) ----
        # enumerators polled at barrier injection: membership growth in
        # an external source (a topic gaining partitions) comes back as
        # an AddSplitsMutation riding the injected barrier — assignment
        # is totally ordered with data, the source_manager.rs discipline
        self.split_enumerators: list = []
        self._enum_by_frag: dict[int, object] = {}
        # live source executors by actor id (SHOW sources: splits,
        # offsets, lag); builders register, Deployment.stop removes
        self.source_execs: dict[int, object] = {}
        self.checkpoint_max_inflight = checkpoint_max_inflight

    # ------------------------------------------------- checkpoint pipeline
    @property
    def checkpoint_max_inflight(self) -> int:
        return self._ckpt_max_inflight

    @checkpoint_max_inflight.setter
    def checkpoint_max_inflight(self, n: int) -> None:
        """Runtime-mutable (SET checkpoint_max_inflight / ALTER SYSTEM):
        0 restores the inline-sync path; >0 bounds the pipeline window.
        Also flips the store's deferred-flush gate so executors only defer
        their d2h persists when a background uploader will drain them."""
        self._ckpt_max_inflight = int(n)
        if hasattr(self.store, "defer_enabled"):
            self.store.defer_enabled = self.pipelined
        self._slot_free.set()         # re-evaluate any backpressured waiter

    @property
    def pipelined(self) -> bool:
        # cluster mode is ALWAYS pipelined: the commit point is "all
        # workers reported sealed", which by construction runs behind the
        # barrier (there is no inline path across processes)
        if self.workers:
            return True
        return self._ckpt_max_inflight > 0 and hasattr(self.store, "seal")

    # -------------------------------------------------------- registration
    def register_source(self, queue: asyncio.Queue) -> None:
        self.source_queues.append(queue)

    def register_actor(self, actor_id: int) -> None:
        self.actor_ids.add(actor_id)

    def register_mesh_fragment(self, actor_id: int, n_shards: int,
                               identity: str = "",
                               shuffle_labels=()) -> None:
        """A fused mesh fragment announces itself: `actor_id` is its ONE
        collection unit covering all `n_shards` device shards;
        `shuffle_labels` name its executors' mesh_shuffle_* series."""
        from ..utils.metrics import GLOBAL_METRICS
        self.mesh_fragments[actor_id] = (int(n_shards), identity)
        self._mesh_shuffle_labels[actor_id] = tuple(shuffle_labels)
        GLOBAL_METRICS.gauge("mesh_fragment_shards",
                             actor=str(actor_id)).set(float(n_shards))

    def register_mesh_chain(self, chain: str, fids,
                            consumer_actor: int) -> None:
        """A fused mesh chain announces itself: producer fragments
        `fids[:-1]` run hollow (their stages execute inside the consumer
        fragment's fused program), `fids[-1]` is the consumer whose fence
        covers the chain."""
        from ..utils.metrics import GLOBAL_METRICS
        self.mesh_chains[chain] = {"fids": tuple(fids),
                                   "consumer_actor": int(consumer_actor)}
        GLOBAL_METRICS.gauge("mesh_chain_fragments", chain=chain).set(
            float(len(fids)))

    def unregister_mesh_chain(self, chain: str) -> None:
        from ..utils.metrics import GLOBAL_METRICS
        if self.mesh_chains.pop(chain, None) is not None:
            GLOBAL_METRICS.remove("mesh_chain_fragments", chain=chain)

    def unregister_mesh_fragment(self, actor_id: int) -> None:
        from ..utils.metrics import GLOBAL_METRICS
        if self.mesh_fragments.pop(actor_id, None) is not None:
            # the labelled series dies with the fragment (same rule as
            # per-actor streaming series)
            GLOBAL_METRICS.remove("mesh_fragment_shards",
                                  actor=str(actor_id))
            from ..utils.metrics import (MESH_SHUFFLE_COUNTERS,
                                         MESH_SHUFFLE_MAX_FILL)
            for label in self._mesh_shuffle_labels.pop(actor_id, ()):
                for name in (*MESH_SHUFFLE_COUNTERS, MESH_SHUFFLE_MAX_FILL):
                    GLOBAL_METRICS.remove(name, executor=label)

    def split_enumerator(self, frag_key: int, factory):
        """One enumerator per source fragment, shared by its actors and
        surviving per-fragment rebuilds (keyed by the retained fragment
        object): the first builder call creates+registers it, later
        calls — other actors, a rebuild — reuse it so already-announced
        splits are never re-assigned."""
        en = self._enum_by_frag.get(frag_key)
        if en is None:
            en = factory()
            en.frag_key = frag_key
            self._enum_by_frag[frag_key] = en
            self.split_enumerators.append(en)
        return en

    def unregister_split_enumerator(self, en) -> None:
        if en in self.split_enumerators:
            self.split_enumerators.remove(en)
        if en.frag_key is not None:
            self._enum_by_frag.pop(en.frag_key, None)

    def register_source_exec(self, ex) -> None:
        self.source_execs[ex.source_id] = ex

    def unregister_source_exec(self, actor_id: int) -> None:
        ex = self.source_execs.pop(actor_id, None)
        if ex is not None:
            ex.remove_split_metrics()

    def _poll_split_enumerators(self):
        """Merge every enumerator's newly-discovered splits into one
        mutation (None when nothing changed). Polls are throttled inside
        each enumerator; a poll failure (broker away) skips this round
        — discovery must never fail injection."""
        adds: dict[int, list] = {}
        for en in list(self.split_enumerators):
            try:
                a = en.poll()
            except Exception:  # noqa: BLE001 — discovery is best-effort
                a = None
            if a:
                for sid, sp in a.items():
                    adds.setdefault(sid, []).extend(sp)
        if not adds:
            return None
        if self.event_log is not None:
            # split adoption is a topology event an operator wants in
            # the post-mortem record (rw_event_logs analogue)
            self.event_log.emit(
                "broker_split_adopt",
                splits={str(sid): [getattr(s, "split_id", str(s))
                                   for s in sp]
                        for sid, sp in adds.items()})
        from ..stream.message import AddSplitsMutation
        return AddSplitsMutation(
            {sid: tuple(v) for sid, v in adds.items()})

    def register_worker(self, handle) -> None:
        """Attach a compute node (cluster mode): it participates in every
        epoch as pseudo-actor -worker_id until removed."""
        self.workers[handle.worker_id] = handle
        self.actor_ids.add(-handle.worker_id)

    def remove_worker(self, worker_id: int) -> None:
        self.workers.pop(worker_id, None)
        self.actor_ids.discard(-worker_id)

    def collect_worker(self, worker_id: int, epoch: int) -> None:
        """A compute node reports every one of ITS actors collected the
        epoch (reference: the CN's BarrierComplete RPC)."""
        st = self._epochs.get(epoch)
        if st is None:
            return
        self.tracer.collect(epoch, -worker_id)
        st.remaining.discard(-worker_id)
        if not st.remaining:
            st.done.set()

    def worker_failed(self, worker_id: int, exc: BaseException) -> None:
        """Lease expiry / connection loss: fail in-flight epochs fast,
        exactly like an in-process actor death (the session's tick-path
        auto-recovery then rebuilds over the surviving worker set)."""
        self.actor_failed(-worker_id, exc)

    # ----------------------------------------------------------- collection
    def collect(self, actor_id: int, barrier: Barrier) -> None:
        st = self._epochs.get(barrier.epoch.curr)
        if st is None:
            return
        self.tracer.collect(barrier.epoch.curr, actor_id)
        st.remaining.discard(actor_id)
        if not st.remaining:
            st.done.set()

    def collect_phases(self, actor_id: int, barrier: Barrier,
                       phases: dict) -> None:
        """Actors report their interval phase split (apply / persist /
        align ns, stream/actor.py) just before collecting — it lands on
        the open epoch span so `\\trace` shows who did what."""
        self.tracer.collect_phases(barrier.epoch.curr, actor_id, phases)

    def actor_failed(self, actor_id: int, exc: BaseException) -> None:
        """Failure detection (reference: barrier-collection failure on meta
        triggers global recovery, barrier/recovery.rs:332): a dead actor
        can never collect, so every in-flight and future barrier wait must
        fail fast instead of hanging the coordinator forever."""
        if self._failure is None:
            self._failure = (actor_id, exc)
        self.failed_actors[actor_id] = exc
        for st in self._epochs.values():
            st.done.set()
        # the failure path has its own diagnosis; a stall report on a
        # dead coordinator would be noise (and the task would otherwise
        # poll the never-deleted failed epoch forever)
        self._stop_watchdog()

    def clear_failure(self) -> None:
        """Per-fragment recovery keeps THIS coordinator (surviving actors
        hold references to it): drop the failure marker and every
        never-collected epoch so injection resumes where it left off —
        the next barrier continues from `_prev_epoch`, and a late
        `collect` for a cleared epoch is ignored by construction."""
        self._failure = None
        self.failed_actors.clear()
        for epoch in list(self._epochs):
            self.tracer.end(epoch)
            del self._epochs[epoch]
        self._stalls_reported.clear()

    # ------------------------------------------------ replay-buffer trims
    def register_replay_channels(self, channels) -> None:
        self.replay_channels.extend(channels)

    def unregister_replay_channels(self, channels) -> None:
        drop = {id(c) for c in channels}
        self.replay_channels = [c for c in self.replay_channels
                                if id(c) not in drop]

    def _trim_replay_buffers(self, committed_epoch: int) -> None:
        for ch in self.replay_channels:
            ch.trim_replay(committed_epoch)

    def _trim_at_local_commit(self, epoch: int) -> None:
        """Trim pulse at a LOCAL commit: on a compute node the local
        commit_sealed only installs read-through state — the epoch is
        durable only when META's manifest swap covers it (the
        `committed` push, cluster/compute_node.py rpc_committed). A
        worker trimming at its own seal would throw away exactly the
        suffix per-worker recovery must replay."""
        if getattr(self.store, "manifest_owner", True):
            self._trim_replay_buffers(epoch)

    def clear_upload_failure(self) -> None:
        """Worker-partial recovery subsumes an upload failure caused by
        the dead worker's vanished sealed report: the aborted epochs
        replay from the committed manifest, so the parked error must
        not fail the resumed injection stream."""
        self._upload_failure = None

    # ------------------------------------------------------------ injection
    async def inject_barrier(self, mutation: Optional[Mutation] = None,
                             kind: Optional[BarrierKind] = None) -> Barrier:
        if self._failure is not None:
            actor_id, exc = self._failure
            raise RuntimeError(f"actor {actor_id} died") from exc
        if self._upload_failure is not None:
            exc = self._upload_failure
            raise RuntimeError(
                "checkpoint upload/commit failed; recovery must replay "
                "from the last committed epoch") from exc
        # a parked sink-delivery failure fail-stops injection the same
        # way (the target is unreachable/raising; recovery replays from
        # the committed epoch and delivery resumes after the durable
        # cursor — exactly-once either way)
        self.logstore.check_failure()
        # split discovery rides otherwise-unadorned barriers (a Pause/
        # Stop/Throttle keeps its own mutation; growth waits one round)
        if mutation is None and self.split_enumerators:
            mutation = self._poll_split_enumerators()
        if kind is None:
            self._barrier_count += 1
            is_ckpt = (self._barrier_count % self.checkpoint_frequency) == 0
            kind = BarrierKind.CHECKPOINT if is_ckpt else BarrierKind.BARRIER
        if kind is BarrierKind.CHECKPOINT:
            # bounded in-flight window: a full uploader queue backpressures
            # INJECTION (not collection) so barrier latency stays honest
            # and recovery replay distance stays <= the window
            await self._acquire_ckpt_slot()
        curr = next_epoch(self._prev_epoch)
        epoch = EpochPair(curr, self._prev_epoch)
        barrier = Barrier(epoch, kind, mutation, (), time.monotonic_ns())
        self._epochs[curr] = EpochState(barrier, set(self.actor_ids))
        self._prev_epoch = curr
        self.tracer.begin(curr, spans=self.stats.level > 0)
        self._ensure_watchdog()
        for q in self.source_queues:
            await q.put(barrier)
        # per-worker injection (cluster mode): the barrier rides the
        # control RPC into every compute node's local source queues; a
        # send failure IS a worker failure (fail fast, then recovery)
        for wid, handle in list(self.workers.items()):
            try:
                await handle.inject(barrier)
            except Exception as e:  # noqa: BLE001 — connection-level death
                self.worker_failed(wid, e)
        return barrier

    async def inject_remote(self, barrier: Barrier) -> Barrier:
        """Compute-node side of cluster injection: meta already chose the
        epoch/kind/mutation; this LocalBarrierManager role just fans the
        barrier into ITS source queues and tracks ITS actors' collection.
        Returns a rebased barrier whose inject timestamp is local (the
        per-worker latency metric must not mix two monotonic clocks)."""
        if self._failure is not None:
            actor_id, exc = self._failure
            raise RuntimeError(f"actor {actor_id} died") from exc
        if self._upload_failure is not None:
            exc = self._upload_failure
            raise RuntimeError("checkpoint upload failed") from exc
        barrier = Barrier(barrier.epoch, barrier.kind, barrier.mutation,
                          (), time.monotonic_ns())
        curr = barrier.epoch.curr
        st = EpochState(barrier, set(self.actor_ids))
        self._epochs[curr] = st
        if not st.remaining:
            # a worker hosting zero actors of the current topology still
            # participates in the protocol (it reports collected at once)
            st.done.set()
        self._prev_epoch = curr
        self.tracer.begin(curr, spans=self.stats.level > 0)
        self._ensure_watchdog()
        for q in self.source_queues:
            await q.put(barrier)
        return barrier

    # --------------------------------------------------- stuck-barrier watchdog
    def _ensure_watchdog(self) -> None:
        """Spawn the watchdog while epochs are in flight (it exits when
        the coordinator drains, so an idle session holds no timer)."""
        if not self.stall_threshold_ms:
            return
        t = self._watchdog_task
        if t is None or t.done() or t.cancelling():
            self._watchdog_task = asyncio.get_running_loop().create_task(
                self._watchdog(), name="barrier-watchdog")

    async def _watchdog(self) -> None:
        """Fire ONCE per stalled epoch: when an in-flight barrier's age
        exceeds `stall_threshold_ms`, log the full diagnosis (partial
        span: who already collected; await tree: where the rest are
        parked) and bump `barrier_stalls_total`. The reference gets this
        from risectl's await-tree dump via the MonitorService; here it is
        automatic."""
        from ..utils.trace import format_stuck_barrier_report
        while True:
            if not self._epochs:
                return        # respawned by the next inject
            thr = self.stall_threshold_ms
            if thr:
                now = time.monotonic_ns()
                for epoch, st in list(self._epochs.items()):
                    tr = self.tracer._open.get(epoch)
                    if tr is None or epoch in self._stalls_reported:
                        continue
                    age_ms = (now - tr.inject_ns) / 1e6
                    if age_ms >= thr:
                        self._stalls_reported.add(epoch)
                        self._m_stalls.inc()
                        remaining = sorted(st.remaining)
                        if self.event_log is not None:
                            self.event_log.emit(
                                "barrier_stall", epoch=epoch,
                                age_ms=round(age_ms, 1),
                                remaining=remaining)
                        # cluster mode: pull every live worker's own
                        # stuck-barrier report (its in-flight remaining
                        # actors + await tree) over rpc.py — the merged
                        # report then names the stalled WORKER, ACTOR
                        # and parked FRAME, not just "phase collect".
                        # The watchdog is an async task, so the fan-out
                        # awaits here without blocking collection.
                        worker_reports = None
                        if self.workers:
                            worker_reports = {}
                            for wid, handle in list(self.workers.items()):
                                try:
                                    worker_reports[wid] = await \
                                        handle.call("dump_tasks",
                                                    timeout=5)
                                except Exception as e:  # noqa: BLE001
                                    worker_reports[wid] = \
                                        f"(unreachable: {e!r})"
                        # stderr, NOT stdout: benchmark/run.py and the
                        # profile scripts print JSON result lines on this
                        # process's stdout — a multi-line diagnosis landing
                        # there mid-measurement would corrupt the parse
                        # (the watchdog is a diagnostic channel, and
                        # diagnostics belong on stderr)
                        print(
                            f"[stuck barrier] epoch {epoch} in flight "
                            f"{age_ms:.0f}ms (threshold {thr:.0f}ms); "
                            f"remaining actors {remaining}\n"
                            + format_stuck_barrier_report(
                                self, worker_reports),
                            flush=True, file=sys.stderr)
            poll_s = max(0.02, min(1.0, (thr or 1000.0) / 1e3 / 8))
            await asyncio.sleep(poll_s)

    def _stop_watchdog(self) -> None:
        t = self._watchdog_task
        if t is not None and not t.done():
            t.cancel()

    async def join_watchdog(self) -> None:
        """Session shutdown: cancel the watchdog and wait until it has
        ended (the barrier path only cancels; it never waits)."""
        self._stop_watchdog()
        if self._watchdog_task is not None:
            await asyncio.gather(self._watchdog_task,
                                 return_exceptions=True)

    async def wait_collected(self, barrier: Barrier) -> None:
        st = self._epochs[barrier.epoch.curr]
        await st.done.wait()
        if self._failure is not None:
            # close the span before raising — the FAILED epoch's trace
            # is exactly what a post-mortem \trace wants to show
            self.tracer.end(barrier.epoch.curr)
            actor_id, exc = self._failure
            raise RuntimeError(
                f"actor {actor_id} died; epoch {barrier.epoch.curr} cannot "
                f"complete — recovery must restart from the last committed "
                f"checkpoint") from exc
        # complete IN ORDER (reference mod.rs:779): this epoch seals epoch.prev
        if barrier.kind is BarrierKind.CHECKPOINT and barrier.epoch.prev != INVALID_EPOCH:
            # dict deltas BEFORE the manifest commit: state committed in
            # this epoch may reference freshly-minted string ids, which
            # must be durable no later than the rows that carry them (an
            # orphan dict suffix after a crash is harmless — append-only,
            # stable ids). Manifest owner only: cluster compute nodes
            # share the object store, and concurrent delta writers would
            # race the log rename (their per-process dicts are local —
            # the v1 cluster contract keeps dict-typed columns out of
            # durable state, enforced at deploy).
            objects = getattr(self.store, "objects", None)
            if objects is not None and getattr(self.store,
                                               "manifest_owner", True):
                from ..common.types import persist_dict_delta
                self.dict_cursor = persist_dict_delta(
                    objects, self.dict_cursor)
            if self.pipelined:
                # seal/upload/commit run behind the stream: the barrier
                # completes as soon as the epoch is enqueued, so the
                # latency below excludes the whole durable flush. In
                # cluster mode the same queue carries the epoch to the
                # background committer, which waits for EVERY worker's
                # sealed report before swapping the manifest.
                self._enqueue_upload(barrier)
                self.tracer.end(barrier.epoch.curr)
            else:
                t_sync = time.monotonic_ns()
                res = self.store.sync(barrier.epoch.prev)
                self.committed_epochs.append(barrier.epoch.prev)
                if self.commit_listener is not None:
                    self.commit_listener(
                        barrier.epoch.prev,
                        (res or {}).get("uncommitted_ssts", []))
                self.logstore.on_commit(barrier.epoch.prev)
                self._trim_at_local_commit(barrier.epoch.prev)
                self.tracer.end(barrier.epoch.curr,
                                sync_ns=time.monotonic_ns() - t_sync)
        else:
            self.tracer.end(barrier.epoch.curr)
        lat_ns = time.monotonic_ns() - barrier.inject_time_ns
        self.latencies_ns.append(lat_ns)
        self._metrics_latency.observe(lat_ns / 1e9)
        del self._epochs[barrier.epoch.curr]
        self._stalls_reported.discard(barrier.epoch.curr)
        if not self._epochs:
            self._stop_watchdog()
        # budget check at barrier collection: the epoch is complete and,
        # with no other barrier in flight (rounds inject one at a time), no
        # executor is inside its barrier handling — parked in an awaited
        # fetch with counts in hand that an eviction would put out of date
        # (utils/d2h.py off_loop); runs synchronously (no awaits) so no
        # actor interleaves mid-eviction. A pulse that finds another
        # barrier in flight is skipped: the next one comes with its collect
        if not self._epochs:
            self.memory.on_barrier(barrier.epoch.curr)
        # serving caches advance to the sealed epoch in the same
        # synchronous window (a wanted-but-absent cache pays its one
        # full build scan here, before incremental maintenance takes
        # over)
        self.serving.on_barrier(barrier)
        # the log-store hub tracks the sealed epoch: it is the
        # activation floor for MV changelog logs (everything <= it is
        # table state a subscription backfills; everything after is
        # logged once active)
        self.logstore.on_barrier(barrier)
        # storage scrub pulse (throttled internally): verify a bounded
        # slice of the referenced objects, account/sweep orphans — in
        # cluster mode orphans are counted but never deleted (a worker's
        # in-flight upload is invisible to meta)
        self.scrubber.on_barrier(barrier.epoch.curr,
                                 cluster_mode=bool(self.workers))
        # compaction & retention pulse (state/compactor.py): harvest a
        # finished background merge (one manifest swap, deletes strictly
        # after), maybe start the next one on a worker thread, and push
        # broker retention floors — the commit path above never merges
        self.compactor.event_log = self.event_log
        self.compactor.retention.event_log = self.event_log
        self.compactor.on_barrier(barrier.epoch.curr)
        # metrics-history pulse LAST: every gauge the pulses above
        # refresh (HBM accounting, serving cache rows, retention
        # floors) is already current when sampled; internally throttled
        # by its interval and never raises into the barrier path
        self.metrics_history.on_barrier(barrier.epoch.curr)
        # cross-engine trace links staged by broker connectors/sinks
        # during the epoch attach to the (just-closed) trace now
        self._drain_trace_links(barrier.epoch.curr)

    def _drain_trace_links(self, epoch: int) -> None:
        """Collect (engine, epoch, span, topic/partition/offset) link
        records staged by BrokerPartitionConnector ingests and
        BrokerSink deliveries onto the epoch's trace."""
        links = []
        for exec_ in list(self.source_execs.values()):
            for _sid, conn in getattr(exec_, "splits", ()):
                drain = getattr(conn, "drain_trace_links", None)
                if drain is not None:
                    try:
                        links.extend(drain())
                    except Exception:
                        pass
        if links:
            self.tracer.add_links(epoch, links)

    async def run_rounds(self, n: int, interval_s: Optional[float] = None) -> None:
        """Inject n barriers, waiting for each to complete. The very first
        barrier of this coordinator's life is Initial (reference: the Add/
        Initial barrier precedes all data); later calls continue the normal
        cadence — a mid-stream Initial would skip syncing the previous epoch.
        interval_s=None => as fast as collection allows (bench mode);
        otherwise paced like the reference's 1s default."""
        async with self._rounds_lock:
            if not self._started:
                self._started = True
                b = await self.inject_barrier(kind=BarrierKind.INITIAL)
                await self.wait_collected(b)
            for _ in range(n):
                if interval_s:
                    await asyncio.sleep(interval_s)
                b = await self.inject_barrier()
                await self.wait_collected(b)
            # settle: uploads overlap ACROSS the rounds above, but callers
            # of run_rounds/tick (tests, the playground ticker, DDL
            # bring-up) expect the committed snapshot to include every
            # ticked epoch once this returns. Latency metrics are already
            # recorded per barrier, so the drain never inflates them; the
            # bench/profile measured loops call inject/wait directly and
            # keep full overlap. Sink delivery drains the same way: once
            # a tick returns, everything it committed has reached the
            # targets (delivery latency never lands in barrier latency).
            await self.drain_uploads()
            await self.logstore.drain()

    async def stop_all(self, actor_ids: Optional[set[int]] = None) -> None:
        from ..stream.message import StopMutation
        async with self._rounds_lock:
            ids = frozenset(actor_ids if actor_ids is not None
                            else self.actor_ids)
            b = await self.inject_barrier(mutation=StopMutation(ids))
            await self.wait_collected(b)
            # a stop is a quiesce point: everything enqueued must be
            # durable — and delivered to sink targets — before the
            # caller reads committed state / tears the deployment down
            await self.drain_uploads()
            await self.logstore.drain()

    # -------------------------------------------------- background uploader
    def _enqueue_upload(self, barrier: Barrier) -> None:
        self._inflight += 1
        self._m_inflight.set(self._inflight)
        self._upload_q.put_nowait(
            _UploadJob(barrier.epoch.prev, barrier.epoch.curr,
                       time.monotonic_ns()))
        if self._uploader_task is None or self._uploader_task.done():
            self._uploader_task = asyncio.get_running_loop().create_task(
                self._upload_worker(), name="epoch-uploader")

    async def _acquire_ckpt_slot(self) -> None:
        if not self.pipelined:
            return
        t0 = time.monotonic_ns()
        while (self._inflight >= self._ckpt_max_inflight
               and self.pipelined and self._upload_failure is None
               and self._failure is None):
            self._slot_free.clear()
            await self._slot_free.wait()
        waited = time.monotonic_ns() - t0
        if waited:
            self.backpressure_wait_ns += waited
            self._m_backpressure.inc(waited / 1e9)

    async def _upload_worker(self) -> None:
        """Drains the checkpoint queue STRICTLY in order: per epoch, run
        the executors' deferred flushes (each ONE pure d2h wait on a
        worker thread and a host-only continuation back on the loop: the
        uploader dispatches nothing to the device — the actors enqueued
        every pack at their barrier, ahead of the next interval's
        programs, utils/d2h.py),
        seal the shared buffer, build+upload the SST off the loop, then
        swap the manifest on the loop. A failure parks the error for the
        next inject_barrier (fail-stop: recovery replays from the last
        committed epoch, exactly like an actor death).

        Each job is one `flush` span with its stages as children, behind
        the `flush.queue` span of its wait in the queue (utils/trace.py):
        the job's SpanScope is the scope in force, and `asyncio.to_thread`
        copies it, so a stage's `fetch_flat` on the worker thread records
        its `d2h_wait` under the stage."""
        store = self.store
        while True:
            if self._upload_q.empty():
                return        # respawned by the next enqueue; no parked task
            job = self._upload_q.get_nowait()
            root_sid = SPAN_LOG.anchors(job.curr_epoch)[0]
            # no scope where the epoch records no spans (metric_level=off)
            scope = SpanScope("uploader") if root_sid else None
            set_scope(scope)
            if scope is not None:
                taken_ns = time.monotonic_ns()
                scope.leaf("flush.queue", job.enqueued_ns, taken_ns)
                flush = scope.open(taken_ns)

            def end_flush(t1: int = 0) -> None:
                if scope is not None:
                    scope.close(flush, "flush", t1)
                    scope.flush(job.curr_epoch, root_sid)
            try:
                if self.workers:
                    # cluster commit: the epoch is durable once EVERY
                    # compute node sealed + uploaded its share; only then
                    # does meta install their SSTs and swap the manifest
                    # (the reference's commit_epoch on meta after all CN
                    # barrier-complete reports carry their synced SSTs)
                    t0 = time.monotonic_ns()
                    sst_ids: list[int] = []
                    with span("flush.upload"):
                        for handle in list(self.workers.values()):
                            sst_ids.extend(await handle.wait_sealed(
                                job.prev_epoch))
                    t2 = time.monotonic_ns()
                    with span("flush.commit"):
                        self.store.commit_remote(job.prev_epoch,
                                                 sorted(sst_ids))
                    t3 = time.monotonic_ns()
                    self.committed_epochs.append(job.prev_epoch)
                    self.logstore.on_commit(job.prev_epoch)
                    self._trim_replay_buffers(job.prev_epoch)
                    # confirm the commit to every worker: they drop
                    # their retained sealed batches and trim their
                    # replay buffers (local channels + DCN legs) to the
                    # uncommitted suffix — the cluster-wide twin of the
                    # local trim pulse
                    for handle in list(self.workers.values()):
                        try:
                            await handle.notify_committed(job.prev_epoch)
                        except Exception:  # noqa: BLE001 — detector owns it
                            pass
                    self.upload_busy_ns += t3 - t0
                    self._m_upload.observe((t2 - t0) / 1e9)
                    self._m_commit.observe((t3 - t2) / 1e9)
                    end_flush(t3)
                    self.tracer.annotate(job.curr_epoch, upload_ns=t2 - t0,
                                         commit_ns=t3 - t2,
                                         committed_at_ns=t3)
                    self._inflight -= 1
                    self._m_inflight.set(self._inflight)
                    self._slot_free.set()
                    self._upload_q.task_done()
                    continue
                t0 = time.monotonic_ns()
                for table_id, wait, cont in store.take_deferred(
                        job.prev_epoch):
                    with span(f"flush.stage:{table_id}"):
                        cont(await _off_loop(wait))
                with span("flush.seal"):
                    batch = store.seal(job.prev_epoch)
                t1 = time.monotonic_ns()
                if FAULTS.active:
                    # chaos harness: an injected store fault takes the
                    # exact fail-stop path a real PUT error takes
                    d = FAULTS.hit("upload_delay", epoch=job.prev_epoch)
                    if d is not None:
                        await asyncio.sleep(d.get("ms", 100) / 1e3)
                    if FAULTS.hit("upload_fail",
                                  epoch=job.prev_epoch) is not None:
                        raise FaultInjected(
                            f"injected upload_fail at epoch "
                            f"{job.prev_epoch}")
                with span("flush.upload"):
                    await _off_loop(store.upload_sealed, batch)
                t2 = time.monotonic_ns()
                with span("flush.commit"):
                    res = store.commit_sealed(batch)
                t3 = time.monotonic_ns()
                end_flush(t3)
                self.committed_epochs.append(job.prev_epoch)
                # annotate BEFORE the commit listener: on a compute node
                # the listener ships this epoch's closed span to meta
                # piggybacked on the sealed report, and the span must
                # already carry its checkpoint-pipeline phases
                self.tracer.annotate(job.curr_epoch, seal_ns=t1 - t0,
                                     upload_ns=t2 - t1, commit_ns=t3 - t2,
                                     committed_at_ns=t3)
                if self.commit_listener is not None:
                    self.commit_listener(
                        job.prev_epoch,
                        (res or {}).get("uncommitted_ssts", []))
                self.logstore.on_commit(job.prev_epoch)
                self._trim_at_local_commit(job.prev_epoch)
                self.upload_busy_ns += t3 - t0
                self._m_seal.observe((t1 - t0) / 1e9)
                self._m_upload.observe((t2 - t1) / 1e9)
                self._m_commit.observe((t3 - t2) / 1e9)
            except asyncio.CancelledError:
                self._inflight -= 1
                self._slot_free.set()
                self._upload_q.task_done()
                raise
            except BaseException as e:  # noqa: BLE001 — park for injection
                self._upload_failure = e
                if scope is not None and scope.cur:
                    # the failed flush's spans, for the post-mortem
                    scope.cur = flush[0]
                    end_flush()
            self._inflight -= 1
            self._m_inflight.set(self._inflight)
            self._slot_free.set()
            self._upload_q.task_done()

    async def drain_uploads(self) -> None:
        """Block until every enqueued checkpoint has committed (or failed).
        Quiesce point for stop/backup/profiling — NOT part of the barrier
        path."""
        if self._uploader_task is not None:
            await self._upload_q.join()
        await self.compactor.drain()
        await self.scrubber.drain()
        if self._upload_failure is not None:
            exc = self._upload_failure
            raise RuntimeError(
                "checkpoint upload/commit failed during drain") from exc

    async def abort_uploads(self) -> None:
        """Crash/recovery entry: cancel the uploader and drop queued jobs
        WITHOUT committing them. An upload already in flight can at worst
        leave an orphan SST no manifest references; the commit point
        (manifest swap) never runs for aborted epochs, so the caller's
        `reset_uncommitted` + replay from `committed_epoch` stays exact.
        Sink delivery and subscription pumps die here too — their
        durable cursors commit with checkpoints, so the rebuilt
        topology's fresh tasks resume exactly-once."""
        self._stop_watchdog()
        self.logstore.abort()
        # in-flight background merge: abandon it — its output (if the
        # thread finishes the upload anyway) is an orphan the scrubber
        # sweeps; no manifest ever references it
        self.compactor.abort()
        t = self._uploader_task
        self._uploader_task = None
        if t is not None and not t.done():
            t.cancel()
            try:
                await t
            except (asyncio.CancelledError, Exception):
                pass
        while not self._upload_q.empty():
            self._upload_q.get_nowait()
            self._upload_q.task_done()
        self._inflight = 0
        self._m_inflight.set(0)
        self._slot_free.set()

    def upload_overlap_pct(self) -> Optional[float]:
        """% of background durable-flush busy time hidden behind compute:
        100 * (1 - injection_backpressure / uploader_busy). None before
        the first pipelined checkpoint commits."""
        if self.upload_busy_ns <= 0:
            return None
        hidden = max(0, self.upload_busy_ns - self.backpressure_wait_ns)
        return round(100.0 * hidden / self.upload_busy_ns, 1)

    # -------------------------------------------------------------- metrics
    def barrier_latency_percentile(self, p: float) -> float:
        if not self.latencies_ns:
            return 0.0
        xs = sorted(self.latencies_ns)
        i = min(len(xs) - 1, int(p * len(xs)))
        return xs[i] / 1e9
