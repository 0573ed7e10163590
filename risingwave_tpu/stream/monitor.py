"""Streaming stats — the per-actor observability plane.

Reference: `StreamingMetrics` (src/stream/src/executor/monitor/
streaming_stats.rs, ~150 labelled Prometheus series) gated by a
`MetricLevel` knob (common/src/config.rs `MetricLevel`): per-actor and
per-executor series are Debug-level so production clusters can turn the
label cardinality (and collection cost) off without losing the headline
totals. This module is that subsystem for the TPU port:

  * `MetricLevel` — off | info | debug (SET metric_level ...);
  * `ActorObs` — one bundle of instruments per actor: row/chunk counts,
    busy vs. align-wait seconds, dispatch fanout, plus the interval
    phase split (apply / persist / align, and their parts dispatch /
    apply_wait / persist_wait / fence / input_wait) the EpochTrace shows, the
    `SpanScope` the interval's spans wait in (utils/trace.py), and for
    an actor whose chain holds a sharded executor what crossed the mesh
    (mesh_rows / mesh_rows_max_shard / mesh_shuffle_bytes), and what its
    hash aggs emitted and its sorted joins persisted (agg_emit_rows /
    agg_extrema_lossy_groups / join_persist_delete_rows /
    join_persist_insert_rows);
  * `ChannelObs` — queue depth + blocked-put (backpressure) seconds on
    every exchange channel feeding an actor;
  * `StreamingStats` — the per-coordinator registrar: `build_graph`
    registers every actor chain through it (the same walk the
    MemoryManager uses), `Deployment.stop` unregisters, and
    `SET metric_level` re-instruments live actors in place.

Cost discipline (no per-chunk d2h): per-chunk row counts accumulate
as LAZY device scalars (`chunk.cardinality()` sums the visibility mask
on device) and are fetched ONCE per actor-barrier, right after the
epoch fence already blocked on the interval's programs — never a
per-chunk d2h. At `off`, actors carry no obs object at all and the hot
loop is the pre-observability one.
"""

from __future__ import annotations

import enum
import time
from contextlib import aclosing
from typing import Optional

import numpy as np

from ..utils.metrics import GLOBAL_METRICS, MetricsRegistry
from ..utils.trace import SPAN_LOG, SpanScope, TraceAnnotation


class MetricLevel(enum.IntEnum):
    """Collection verbosity (reference common/src/config.rs MetricLevel,
    collapsed to the three tiers the engine distinguishes)."""

    OFF = 0       # no per-actor instrumentation, no phase tracking
    INFO = 1      # phase splits for \trace; no per-actor series (default)
    DEBUG = 2     # full per-actor/per-channel labelled series

    @classmethod
    def parse(cls, v) -> "MetricLevel":
        if isinstance(v, cls):
            return v
        if isinstance(v, int):
            return cls(v)
        s = str(v).strip().lower()
        try:
            return {"off": cls.OFF, "disabled": cls.OFF,
                    "info": cls.INFO, "debug": cls.DEBUG}[s]
        except KeyError:
            raise ValueError(
                f"unknown metric_level {v!r} (expected off|info|debug)")


def dispatcher_channels(d) -> list:
    """The output Channel objects a dispatcher feeds (sender-side
    instrumentation walk; remote/DCN legs are not Channels and are
    skipped — their backpressure is visible on the socket, not here)."""
    from .exchange import Channel
    if d is None:
        return []
    out = []
    outs = getattr(d, "outputs", None)
    if outs is not None:
        out.extend(outs)
    if getattr(d, "output", None) is not None:
        out.append(d.output)
    chans = getattr(d, "channels", None)   # TapDispatcher: (ch, ids) pairs
    if chans is not None:
        out.extend(ch for ch, _ids in chans)
    subs = getattr(d, "dispatchers", None)  # FanoutDispatcher
    if subs is not None:
        for sub in subs:
            out.extend(dispatcher_channels(sub))
    return [c for c in out if isinstance(c, Channel)]


def dispatcher_fanout(d) -> int:
    """Number of output channels a dispatcher feeds right now (Tap
    fanout is runtime-extendable, so this re-reads on every call)."""
    if d is None:
        return 0
    outs = getattr(d, "outputs", None)
    if outs is not None:
        return len(outs)
    if getattr(d, "output", None) is not None:
        return 1
    chans = getattr(d, "channels", None)   # TapDispatcher: (ch, ids) pairs
    if chans is not None:
        return len(chans)
    subs = getattr(d, "dispatchers", None)  # FanoutDispatcher
    if subs is not None:
        return sum(dispatcher_fanout(x) for x in subs)
    return 1


class ChannelObs:
    """Queue depth + blocked-put accounting for one exchange channel,
    labelled by the RECEIVING actor (backpressure blames the slow
    consumer, which is what an operator wants to see)."""

    __slots__ = ("depth", "blocked_put", "keys")

    def __init__(self, registry: MetricsRegistry, actor_label: str,
                 executor_label: str, input_idx: int):
        labels = dict(actor=actor_label, executor=executor_label,
                      input=str(input_idx))
        self.depth = registry.gauge("stream_exchange_queue_depth", **labels)
        self.blocked_put = registry.counter(
            "stream_exchange_blocked_put_seconds_total", **labels)
        self.keys = [("stream_exchange_queue_depth", labels),
                     ("stream_exchange_blocked_put_seconds_total", labels)]


class ExecutorObs:
    """Per-executor child handle inside a fused chain: attributes the
    actor's row flow and wall time to each executor position (labels
    {actor, executor, pos}; pos 0 = chain root, so the root child's
    row count equals the actor-level total). The hot path only stashes
    the chunk's vis-mask reference — NO device dispatch per chunk (a
    per-executor jnp.sum would multiply dispatch count by chain
    length); `ActorObs.on_barrier` flushes every child after the epoch
    fence already blocked, so the host-side count there syncs nothing
    extra."""

    __slots__ = ("row_count", "busy_seconds", "_vis", "busy_ns",
                 "keys")

    def __init__(self, registry: MetricsRegistry, actor_id: int,
                 executor_label: str, pos: int):
        labels = dict(actor=str(actor_id), executor=executor_label,
                      pos=str(pos))
        self.row_count = registry.counter(
            "stream_actor_row_count", **labels)
        self.busy_seconds = registry.counter(
            "stream_actor_busy_seconds_total", **labels)
        self.keys = [("stream_actor_row_count", labels),
                     ("stream_actor_busy_seconds_total", labels)]
        self._vis = []
        self.busy_ns = 0

    def note_chunk(self, chunk) -> None:
        self._vis.append(chunk.vis)

    def flush(self) -> None:
        if self._vis:
            n = 0
            for v in self._vis:
                n += int(np.asarray(v).sum())
            self.row_count.inc(n)
            self._vis.clear()
        if self.busy_ns:
            self.busy_seconds.inc(self.busy_ns / 1e9)
            self.busy_ns = 0


def _wrap_executor(ex) -> None:
    """Install the per-executor counting passthrough ONCE per executor
    instance. The wrapper consults `ex._exec_obs` per message (None =
    pure passthrough), so `SET metric_level` toggles attribution live
    without touching a generator chain that is already running. Row
    counts stay lazy device scalars; the wall clock charged to a child
    is the time its frame (and everything upstream of it) took to
    produce each item — pos-ordered series therefore nest, and the
    difference between adjacent positions isolates one executor."""
    if getattr(ex, "_exec_obs_wrapped", False):
        return
    inner = ex.execute

    def execute(*a, **k):
        async def _gen():
            t0 = time.monotonic_ns()
            # closed with this wrapper: a passthrough must not turn
            # the close of `ex` into a later finalizer task
            async with aclosing(inner(*a, **k)) as items:
                async for item in items:
                    obs = ex._exec_obs
                    if obs is not None:
                        obs.busy_ns += time.monotonic_ns() - t0
                        if hasattr(item, "cardinality"):
                            obs.note_chunk(item)
                    yield item
                    t0 = time.monotonic_ns()
        return _gen()

    ex._exec_obs = None
    ex.execute = execute
    ex._exec_obs_wrapped = True


class ActorObs:
    """Per-actor instrument bundle. Interval cells reset at each
    barrier; the phase split they produce rides into the EpochTrace."""

    __slots__ = (
        "actor_id", "debug", "apply_ns", "persist_ns", "input_wait_ns",
        "fence_ns", "_row_acc", "row_count", "chunks_in", "chunks_out",
        "dispatch", "busy_seconds", "align_seconds", "keys",
        "_occupancy", "registry", "children", "mesh", "counted", "tables",
        "apply_wait_ns", "persist_wait_ns", "scope",
    )

    def __init__(self, registry: MetricsRegistry, actor_id: int,
                 executor_label: str, debug: bool):
        self.registry = registry
        self.actor_id = actor_id
        self.debug = debug
        # interval phase cells (ns), reset at every barrier
        self.apply_ns = 0
        self.persist_ns = 0
        self.input_wait_ns = 0
        self.fence_ns = 0
        self.apply_wait_ns = 0        # d2h waits inside chunk polls
        self.persist_wait_ns = 0      # d2h waits inside the barrier poll
        # the interval's spans until the barrier names their epoch; its
        # `dispatch_ns` is the interval's fourth new phase cell
        self.scope = SpanScope(actor_id)
        self._row_acc = None          # lazy device scalar (sum of chunk
        #                               cardinalities this interval)
        self._occupancy = []          # (executor_label, part, gauge, fn)
        self.children = []            # ExecutorObs, chain-walk order
        self.mesh = []                # the chain's sharded executors
        #                               (stream/mesh_shuffle.py)
        self.counted = []             # the chain's executors that count
        #                               rows per interval
        #                               (take_phase_counts)
        self.tables = []              # [StateTable, rows seen] of the
        #                               chain's executors (row_path_rows)
        self.keys = []
        if debug:
            labels = dict(actor=str(actor_id), executor=executor_label)
            self.row_count = registry.counter(
                "stream_actor_row_count", **labels)
            self.chunks_in = registry.counter(
                "stream_actor_in_chunk_count", **labels)
            self.chunks_out = registry.counter(
                "stream_actor_out_chunk_count", **labels)
            self.dispatch = registry.counter(
                "stream_actor_dispatch_total", **labels)
            self.busy_seconds = registry.counter(
                "stream_actor_busy_seconds_total", **labels)
            self.align_seconds = registry.counter(
                "stream_actor_barrier_align_seconds_total", **labels)
            self.keys = [
                (n, labels) for n in (
                    "stream_actor_row_count", "stream_actor_in_chunk_count",
                    "stream_actor_out_chunk_count",
                    "stream_actor_dispatch_total",
                    "stream_actor_busy_seconds_total",
                    "stream_actor_barrier_align_seconds_total")]
        else:
            self.row_count = self.chunks_in = self.chunks_out = None
            self.dispatch = self.busy_seconds = self.align_seconds = None

    # ------------------------------------------------------ the actor's polls
    def begin_poll(self) -> tuple:
        """One poll of the chain starts: its span is in force from here, so
        what the chain dispatches and fetches inside it is its child."""
        t0 = time.monotonic_ns()
        anno = TraceAnnotation("rw:actor.poll")
        anno.__enter__()
        return (t0, self.input_wait_ns, self.scope.wait_ns,
                self.scope.open(t0), anno)

    def end_poll(self, poll: tuple, barrier: bool) -> None:
        """The poll yielded a chunk / watermark (apply) or the barrier
        (persist: every executor's flush / persist / commit ran inside
        it). The poll less the channel-recv waits accrued inside it is the
        phase's time; its d2h waits are the phase's `*_wait_ns`."""
        t0, w0, d0, handle, anno = poll
        now = time.monotonic_ns()
        busy = max(0, now - t0 - (self.input_wait_ns - w0))
        waited = self.scope.wait_ns - d0
        if barrier:
            self.persist_ns += busy
            self.persist_wait_ns += waited
        else:
            self.apply_ns += busy
            self.apply_wait_ns += waited
        anno.__exit__(None, None, None)
        self.scope.close(handle, "actor.persist" if barrier
                         else "actor.apply", now)

    def begin_fence(self) -> tuple:
        anno = TraceAnnotation("rw:actor.fence")
        anno.__enter__()
        return time.monotonic_ns(), anno

    def end_fence(self, fence: tuple) -> None:
        t0, anno = fence
        now = time.monotonic_ns()
        anno.__exit__(None, None, None)
        self.fence_ns += now - t0
        self.scope.leaf("actor.fence", t0, now)

    # ------------------------------------------------------ hot-path notes
    def add_input_wait(self, ns: int) -> None:
        """Exchange inputs (ChannelInput/Merge) report channel recv
        waits here — the align component of the phase split — as each
        ends: a child span of the poll that waited."""
        self.input_wait_ns += ns
        now = time.monotonic_ns()
        self.scope.leaf("actor.input_wait", now - ns, now)

    def note_chunk_in(self) -> None:
        if self.chunks_in is not None:
            self.chunks_in.inc()

    def note_chunk_out(self, chunk, fanout: int) -> None:
        if self.chunks_out is not None:
            self.chunks_out.inc()
            self.dispatch.inc(fanout)
            # lazy device scalar: no transfer until the barrier flush
            card = chunk.cardinality()
            self._row_acc = (card if self._row_acc is None
                             else self._row_acc + card)

    # --------------------------------------------------------- barrier flush
    def flush_spans(self, epoch: int) -> None:
        """The interval's spans learn their epoch when its barrier arrives,
        as its phases do; what no poll was in force for hangs off the
        epoch's `collect`."""
        self.scope.flush(epoch, SPAN_LOG.anchors(epoch)[1])

    def on_barrier(self) -> dict:
        """Close the interval: fetch the accumulated row count (the
        epoch fence already blocked on this interval's programs, so the
        8-byte readback is transfer-only), flush the busy/align
        counters, refresh occupancy gauges, and return the phase split
        for the epoch trace."""
        align_ns = self.input_wait_ns + self.fence_ns
        scope = self.scope
        phases = {"apply_ns": self.apply_ns,
                  "persist_ns": self.persist_ns,
                  "align_ns": align_ns,
                  "input_wait_ns": self.input_wait_ns,
                  "fence_ns": self.fence_ns,
                  "dispatch_ns": scope.dispatch_ns,
                  "apply_wait_ns": self.apply_wait_ns,
                  "persist_wait_ns": self.persist_wait_ns}
        if self.mesh:
            # what crossed the mesh this interval (rows received in all
            # and by the fullest shard, all_to_all bytes), as the
            # executors' barrier watchdog fetch brought it: host numbers
            # by now. Of several mesh executors in one chain, the most
            # skewed one's.
            phases.update(max(
                (ex.take_mesh_interval() for ex in self.mesh),
                key=lambda iv: (iv["mesh_rows_max_shard"]
                                / max(1, iv["mesh_rows"]))))
        for ex in self.counted:
            # rows the chain's hash aggs flushed downstream, its sorted
            # joins wrote durably, matched and hold this interval: host
            # numbers, from the fetches those executors make at the
            # barrier anyway (summed where a chain holds two of a kind)
            for k, n in ex.take_phase_counts().items():
                phases[k] = phases.get(k, 0) + n
        # rows the chain's state tables took in row form since the last
        # barrier (state/state_table.py `row_path_rows`: a Python tuple and
        # an encoded key a row), 0 included: a batch of any schema is one
        # columnar segment, so what is left is `write_chunk_rows`' own
        # callers and a batch's NULL-pk rows. A deferred flush writes
        # behind its barrier, so an interval reads what the store drained
        # during it.
        if self.tables:
            phases["row_path_rows"] = 0
        for seen in self.tables:
            total = seen[0].row_path_rows
            phases["row_path_rows"] += total - seen[1]
            seen[1] = total
        if self.debug:
            if self._row_acc is not None:
                self.row_count.inc(int(np.asarray(self._row_acc)))
            self.busy_seconds.inc((self.apply_ns + self.persist_ns) / 1e9)
            self.align_seconds.inc(align_ns / 1e9)
            for child in self.children:
                child.flush()
            for _label, _part, gauge, fn in self._occupancy:
                try:
                    gauge.set(float(fn()))
                except Exception:
                    pass
        self.apply_ns = self.persist_ns = 0
        self.input_wait_ns = self.fence_ns = 0
        self.apply_wait_ns = self.persist_wait_ns = 0
        scope.dispatch_ns = scope.wait_ns = 0
        self._row_acc = None
        return phases

    def add_occupancy_gauge(self, executor_label: str, part: str,
                            fn) -> None:
        labels = dict(actor=str(self.actor_id), executor=executor_label,
                      part=part)
        gauge = self.registry.gauge("stream_executor_hash_occupancy",
                                    **labels)
        self._occupancy.append((executor_label, part, gauge, fn))
        self.keys.append(("stream_executor_hash_occupancy", labels))


def _state_tables_of(ex) -> list:
    """The StateTables an executor writes: `state_table` (aggs, sources),
    `state_tables` (a join's two sides), `table` (materialize)."""
    from ..state.state_table import StateTable
    found = [getattr(ex, "state_table", None), getattr(ex, "table", None),
             *(getattr(ex, "state_tables", None) or ())]
    return [t for t in found if isinstance(t, StateTable)]


def _iter_chain(root):
    """Every executor reachable from a fragment root through input(s) —
    the same walk plan/build.py uses for memory registration."""
    seen: set[int] = set()
    stack = [root]
    while stack:
        node = stack.pop()
        if node is None or id(node) in seen:
            continue
        seen.add(id(node))
        yield node
        inp = getattr(node, "input", None)
        if inp is not None:
            stack.append(inp)
        for i in getattr(node, "inputs", ()) or ():
            stack.append(i)


def _occupancy_parts(ex):
    """(part, fn) occupancy fractions for hash-table executors — duck
    typed on the host-known occupancy the growth logic already tracks
    (`_occ_known`), so reading it costs nothing on device."""
    occ = getattr(ex, "_occ_known", None)
    if occ is None:
        return []
    if isinstance(occ, (list, tuple)):
        caps = getattr(ex, "key_capacity", None)
        if not isinstance(caps, (list, tuple)) or len(caps) != len(occ):
            return []
        names = ("left", "right") if len(occ) == 2 else tuple(
            str(i) for i in range(len(occ)))
        return [(names[i],
                 (lambda e=ex, i=i: (e._occ_known[i] /
                                     max(1, e.key_capacity[i]))))
                for i in range(len(occ))]
    cap = getattr(ex, "capacity", None)
    if not isinstance(cap, int) or cap <= 0:
        return []
    return [("all", lambda e=ex: e._occ_known / max(1, e.capacity))]


class StreamingStats:
    """Per-coordinator registrar for actor-level streaming metrics.

    `build_graph` registers every (actor, chain root) pair here right
    where it registers with the MemoryManager; `Deployment.stop`
    unregisters, which REMOVES the actor's series from the registry so
    dead actors don't linger in scrapes. `configure()` re-instruments
    live actors in place, so `SET metric_level` takes effect without a
    redeploy."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None else GLOBAL_METRICS
        self.level = MetricLevel.INFO
        # actor_id -> (actor, root, scope)
        self._regs: dict[int, tuple] = {}

    # ------------------------------------------------------------- config
    def configure(self, level) -> None:
        lv = MetricLevel.parse(level)
        if lv == self.level:
            return
        self.level = lv
        for actor_id in list(self._regs):
            actor, root, scope = self._regs[actor_id]
            self._uninstrument(actor, root)
            self._instrument(actor, root, scope)

    # ------------------------------------------------------- registration
    def register(self, scope: str, actor, root) -> None:
        self._regs[actor.actor_id] = (actor, root, scope)
        self._instrument(actor, root, scope)

    def unregister(self, actor_id: int) -> None:
        reg = self._regs.pop(actor_id, None)
        if reg is not None:
            self._uninstrument(reg[0], reg[1])

    def actor_series_count(self) -> int:
        """Per-actor series currently registered (tests / REPL)."""
        return sum(len(a.obs.keys) for a, _r, _s in self._regs.values()
                   if getattr(a, "obs", None) is not None)

    # ----------------------------------------------------- instrumentation
    def _instrument(self, actor, root, scope: str) -> None:
        from .exchange import ChannelInput, MergeExecutor
        if self.level <= MetricLevel.OFF:
            actor.obs = None
            return
        debug = self.level >= MetricLevel.DEBUG
        executor_label = f"{scope}/{getattr(root, 'identity', 'Executor')}"
        obs = ActorObs(self.registry, actor.actor_id, executor_label,
                       debug)
        chan_idx = 0
        for pos, ex in enumerate(_iter_chain(root)):
            # per-executor attribution: wrap execute() once (pure
            # passthrough until a child handle fills the slot); at
            # debug, each chain position gets its own {actor, executor,
            # pos} row/busy series so a hot fused chain names the
            # executor, not just the actor
            _wrap_executor(ex)
            if debug:
                child = ExecutorObs(
                    self.registry, actor.actor_id,
                    f"{scope}/"
                    f"{getattr(ex, 'identity', type(ex).__name__)}", pos)
                ex._exec_obs = child
                obs.children.append(child)
                obs.keys.extend(child.keys)
            else:
                ex._exec_obs = None
        for ex in _iter_chain(root):
            if hasattr(ex, "take_mesh_interval"):
                obs.mesh.append(ex)
            if hasattr(ex, "take_phase_counts"):
                obs.counted.append(ex)
            obs.tables.extend([t, t.row_path_rows]
                              for t in _state_tables_of(ex))
            if hasattr(ex, "barrier_queue") and hasattr(ex, "obs"):
                # sources: barrier-queue wait is align (idle) time
                ex.obs = obs
            if isinstance(ex, (ChannelInput, MergeExecutor)):
                ex.obs = obs
                if debug:
                    chans = ([ex.channel] if isinstance(ex, ChannelInput)
                             else list(ex.channels))
                    for ch in chans:
                        ch.obs = ChannelObs(self.registry,
                                            str(actor.actor_id),
                                            ex.identity, chan_idx)
                        obs.keys.extend(ch.obs.keys)
                        chan_idx += 1
            elif debug:
                for part, fn in _occupancy_parts(ex):
                    obs.add_occupancy_gauge(ex.identity, part, fn)
        if debug:
            # sender-side backpressure attribution: seconds THIS actor
            # spends parked on a FULL downstream channel are charged to
            # it (the receiver-labelled blocked_put series keeps naming
            # the culprit; this one names who pays)
            for out_idx, ch in enumerate(
                    dispatcher_channels(actor.dispatcher)):
                labels = dict(actor=str(actor.actor_id),
                              executor=executor_label,
                              output=str(out_idx))
                ch.send_obs = self.registry.counter(
                    "stream_exchange_send_blocked_seconds_total",
                    **labels)
                obs.keys.append(
                    ("stream_exchange_send_blocked_seconds_total",
                     labels))
        actor.obs = obs

    def _uninstrument(self, actor, root) -> None:
        from .exchange import ChannelInput, MergeExecutor
        obs = getattr(actor, "obs", None)
        if obs is not None:
            for name, labels in obs.keys:
                self.registry.remove(name, **labels)
        actor.obs = None
        for ex in _iter_chain(root):
            ex._exec_obs = None       # wrapper stays; slot goes dark
            if hasattr(ex, "barrier_queue") and hasattr(ex, "obs"):
                ex.obs = None
            if isinstance(ex, (ChannelInput, MergeExecutor)):
                ex.obs = None
                chans = ([ex.channel] if isinstance(ex, ChannelInput)
                         else list(ex.channels))
                for ch in chans:
                    ch.obs = None
        for ch in dispatcher_channels(actor.dispatcher):
            ch.send_obs = None
