"""Epoch spans + async stack dumps — the tracing/await-tree analogue.

Reference: (a) barriers carry a TracingContext so each epoch is a
distributed trace spanning meta -> CN actors (common/src/util/tracing.rs,
executor/mod.rs:267, actor.rs:195-240); (b) every actor future is
await-tree-instrumented and dumpable via the MonitorService for
stuck-barrier debugging (stream_manager.rs:66).

Single-process TPU analogue:
  * EpochTrace — per-epoch spans recorded by the barrier coordinator:
    inject time, per-actor collect times, sync duration. A slow epoch's
    trace shows WHICH actor held the barrier.
  * dump_task_tree() — the await-tree: every asyncio task's current
    await stack, so a stuck barrier shows exactly which executor
    coroutine is parked where (channel recv, credit wait, device fence).

Cluster (distributed) traces: each ComputeNode's local coordinator
records its OWN EpochTrace (inject_remote starts it with the epoch
re-based to the worker's clock), and the closed span bundle ships to
meta piggybacked on the sealed-report push (cluster/compute_node.py ->
cluster/meta_service.py -> `EpochTracer.ingest_worker`). Meta stitches
them into ONE per-epoch timeline: worker offsets are RELATIVE TO THE
INJECT PUSH (offset 0 on worker wN = the moment wN received meta's
inject), so per-worker sub-blocks line up under meta's span without
any cross-host clock agreement. `traces_to_json` / `traces_to_chrome`
export the same stitched data machine-readably (the chrome form loads
in Perfetto: one pid per worker, one tid per actor).

The span tree of one checkpoint. At metric_level >= info (the default)
every epoch also records a tree of `Span`s on `time.monotonic_ns()`, all
carrying the barrier's `epoch.curr`; `off` records none. They live in the
process-wide `SPAN_LOG` (bounded: whole epochs go from its old end,
counted in `trace_spans_dropped_total`), hang off their `EpochTrace` while
it is in the ring, ride `to_dict` / `from_dict` as offsets from inject,
and all but the five marked * are also entered as a
`jax.profiler.TraceAnnotation` named `rw:<name>` (a poll as
`rw:actor.poll`: it learns its name when it ends), so a kept xplane shows
them over the device lines. Self time = a span less what its children
cover.

  span (parent)                       recorded in                bounds
  ----------------------------------  -------------------------  ------------------------------
  checkpoint* (root)                  EpochTracer.begin ->       inject -> manifest swap (->
                                      annotate                   collected, where nothing flushes)
  collect* (checkpoint)               EpochTracer.begin -> end   inject -> every actor collected:
                                                                 what `latencies_ns` holds
  actor.apply, actor.persist          stream/actor.py            one poll of the chain: a chunk's,
    (collect)                                                    the barrier's. Less its input
                                                                 waits: `apply_ns`, `persist_ns`
  actor.input_wait* (the poll)        ActorObs.add_input_wait    a channel / barrier-queue wait
  dispatch:<StateJit.name>            ops/jit_state.py           host time to enqueue one program,
    (the poll)                                                   a full device queue's block
                                                                 included; one span a call
  agg.purge (the barrier poll)        stream/hash_agg.py         a hash agg's zombie purge (or
                                                                 growth): the rehash's dispatch and
                                                                 the awaited readback of the rebuilt
                                                                 occupancy, its `dispatch:hash_agg_*`
                                                                 and `d2h_wait` children
  topn.flush (the barrier poll)       stream/retract_top_n.py    a top-N's barrier: the dispatch of
                                                                 its ranking (and counting) program,
                                                                 the awaited readback of its ONE
                                                                 watchdog pack (errors, live rows,
                                                                 what changed) and the dispatch of
                                                                 the emitting program at the width
                                                                 the counts allow; children
                                                                 `dispatch:retract_top_n_*`, `d2h_wait`
  d2h_wait (the poll or flush.stage)  utils/d2h.py               a worker thread blocked until the
                                                                 device reaches and ships a buffer,
                                                                 the actor's task (or the uploader's)
                                                                 parked on it and the event loop
                                                                 free; `count` = its bytes. One taken
                                                                 ON the loop thread also counts in
                                                                 d2h_wait_on_loop_seconds_total
  actor.fence (collect)               stream/actor.py            block_until_ready of the epoch's
                                                                 tokens
  flush.queue* (checkpoint)           meta/barrier_manager.py    enqueued -> the uploader takes it:
                                                                 the wait behind the predecessor
  flush (checkpoint)                  _upload_worker             job taken -> manifest swapped
  flush.stage:<table> (flush)         _upload_worker             the ONE (wait, cont) of a table's
                                                                 deferred flush: `wait` (worker
                                                                 thread) is its one d2h_wait child,
                                                                 for the pack the actor enqueued at
                                                                 its barrier; the rest is host-only
                                                                 unpack + state-table write
  flush.seal, flush.upload,           _upload_worker             store.seal; upload_sealed (cluster
    flush.commit (flush)                                         mode: every worker's sealed
                                                                 report); commit_sealed
  flush.loop_wait* (flush.stage,      _upload_worker             a worker thread's result waiting
    flush.upload)                                                for the event loop to come back
                                                                 to the uploader's task

An interval's spans learn their epoch when its barrier reaches the actor,
as `phases` does, so the work of an interval (and the wait for its
barrier) may START before that epoch's inject; everything ends inside its
parent. An input wait happens inside a poll of the chain, so it is the
poll's child and not its sibling.

`EpochTrace.phases[actor]`, the interval sums an actor reports with its
collect (only the keys that end in `_ns` are times):

  key               what
  ----------------  ----------------------------------------------------
  apply_ns          chunk polls less their input waits
  persist_ns        the barrier-yielding poll less its input waits
  align_ns          input_wait_ns + fence_ns (kept: older readers)
  input_wait_ns     channel recv / barrier-queue waits
  fence_ns          the epoch fence
  dispatch_ns       the `dispatch:*` spans inside polls: enqueueing
                    programs, part of apply_ns + persist_ns
  apply_wait_ns     the `d2h_wait` spans inside chunk polls (a hash
                    agg's watchdog fetch: its barrier work ends in the
                    chunk its flush emits), part of apply_ns
  persist_wait_ns   the `d2h_wait` spans inside the barrier poll: the
                    actor parked on an awaited fetch (the loop is not
                    held), part of persist_ns
  mesh_* / agg_* / join_* / topn_*   row and byte counts (see
                    `EpochTrace.phases`)
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import itertools
import threading
import time
import traceback
from collections import deque
from dataclasses import dataclass, field
from typing import NamedTuple

from jax.profiler import TraceAnnotation

from .metrics import TRACE_SPANS_DROPPED

mono = time.monotonic_ns


class Span(NamedTuple):
    epoch: int
    name: str
    parent: int         # sid of the parent span; 0 = the epoch's root
    owner: object       # actor id, "coord" or "uploader"
    t0_ns: int
    t1_ns: int
    sid: int
    count: int = 0      # a d2h_wait's bytes; 0 elsewhere


_next_sid = itertools.count(1).__next__


class SpanLog:
    """The process-wide span store: epoch -> {sid: Span}, oldest epoch
    first. `EpochTracer` keeps 64 epochs and a benchmark window commits
    more, so what a reader selects by epoch lives here. Bounded by spans:
    past `max_spans` whole epochs go from the old end (a tree is under 100
    spans at bench widths, so the default holds 300 checkpoints and more)."""

    def __init__(self, max_spans: int = 1 << 15):
        self.max_spans = max_spans
        self._epochs: dict[int, dict[int, Span]] = {}
        # epoch -> (sid of `checkpoint`, sid of `collect`)
        self._anchors: dict[int, tuple] = {}
        self._n = 0
        self._lock = threading.Lock()

    def open(self, epoch: int, root_sid: int, collect_sid: int) -> dict:
        """Start `epoch`; the dict returned is the log's own (an
        `EpochTrace` holds on to it while it is in the ring)."""
        with self._lock:
            self._anchors[epoch] = (root_sid, collect_sid)
            return self._epochs.setdefault(epoch, {})

    def anchors(self, epoch: int) -> tuple:
        return self._anchors.get(epoch, (0, 0))

    def put(self, *spans: Span) -> None:
        """Add spans; one whose sid the epoch already has replaces it (the
        root is written at collect and again at the manifest swap)."""
        with self._lock:
            for sp in spans:
                d = self._epochs.get(sp.epoch)
                if d is None:
                    d = self._epochs[sp.epoch] = {}
                if sp.sid not in d:
                    self._n += 1
                d[sp.sid] = sp
            while self._n > self.max_spans and len(self._epochs) > 1:
                epoch = next(iter(self._epochs))
                gone = self._epochs.pop(epoch)
                self._anchors.pop(epoch, None)
                self._n -= len(gone)
                TRACE_SPANS_DROPPED.inc(len(gone))

    def spans(self, epoch: int) -> list:
        """The epoch's spans, or [] where the log does not have it."""
        with self._lock:
            return list(self._epochs.get(epoch, {}).values())

    def epochs(self) -> list:
        return list(self._epochs)


SPAN_LOG = SpanLog()

# The scope in force: one per actor (its task sets it; tasks and threads
# spawned under it copy the context and so share the object) and one per
# flush job of the uploader. None = record nothing (metric_level = off,
# or code that runs under no actor).
_SCOPE: contextvars.ContextVar = contextvars.ContextVar(
    "rw_span_scope", default=None)
# spans one scope holds before its flush: an interval of thousands of
# small chunks keeps its first ones and counts the rest as dropped
MAX_PENDING = 4096


current_scope = _SCOPE.get
set_scope = _SCOPE.set


class SpanScope:
    """Where spans wait for their epoch. `cur` is the sid of the span in
    force (0 = none: a child then hangs off the parent `flush` is given);
    `dispatch_ns` / `wait_ns` sum the dispatch and d2h_wait spans recorded
    under a span in force."""

    __slots__ = ("owner", "cur", "cur_t0", "pending", "dispatch_ns",
                 "wait_ns", "dropped")

    def __init__(self, owner):
        self.owner = owner
        self.cur = 0
        self.cur_t0 = 0         # where the span in force started
        self.pending: list = []
        self.dispatch_ns = 0
        self.wait_ns = 0
        self.dropped = 0

    def _add(self, name, parent, t0, t1, sid, count) -> None:
        if len(self.pending) < MAX_PENDING:
            self.pending.append((name, parent, t0, t1, sid, count))
        else:
            self.dropped += 1

    def leaf(self, name: str, t0: int, t1: int, count: int = 0) -> None:
        """A finished span under the span in force, or under the parent
        `flush` is given where it began before that one did (a join's
        other input, waiting since an earlier poll)."""
        self._add(name, self.cur if t0 >= self.cur_t0 else 0, t0, t1,
                  _next_sid(), count)

    def dispatch(self, name: str, t0: int, t1: int) -> None:
        if self.cur:
            self.dispatch_ns += t1 - t0
        self._add(name, self.cur, t0, t1, _next_sid(), 0)

    def wait(self, t0: int, t1: int, nbytes: int) -> None:
        if self.cur:
            self.wait_ns += t1 - t0
        self._add("d2h_wait", self.cur, t0, t1, _next_sid(), nbytes)

    def open(self, t0: int = 0) -> tuple:
        """Put a new span in force; `close` it with the handle."""
        handle = (_next_sid(), self.cur, t0 or mono(), self.cur_t0)
        self.cur, self.cur_t0 = handle[0], handle[2]
        return handle

    def close(self, handle: tuple, name: str, t1: int = 0) -> None:
        sid, prev, t0, prev_t0 = handle
        self.cur, self.cur_t0 = prev, prev_t0
        self._add(name, prev, t0, t1 or mono(), sid, 0)

    def flush(self, epoch: int, parent: int, log: SpanLog = None) -> None:
        """Hand the waiting spans to the log under `epoch`; those with no
        span in force when recorded become children of `parent`."""
        if self.dropped:
            TRACE_SPANS_DROPPED.inc(self.dropped)
            self.dropped = 0
        if self.pending:
            pending, self.pending = self.pending, []
            (log or SPAN_LOG).put(*(
                Span(epoch, name, par or parent, self.owner, t0, t1, sid,
                     count)
                for name, par, t0, t1, sid, count in pending))


@contextlib.contextmanager
def span(name: str):
    """`name` as a child of the span in force, and as `rw:<name>` in the
    profiler's trace; nothing where no scope is in force."""
    sc = _SCOPE.get()
    if sc is None:
        yield
        return
    h = sc.open()
    try:
        with TraceAnnotation("rw:" + name):
            yield
    finally:
        sc.close(h, name)


@dataclass
class EpochTrace:
    epoch: int
    inject_ns: int
    collects: list = field(default_factory=list)   # (actor_id, ns_after)
    # actor_id -> {"apply_ns", "persist_ns", "align_ns"} — the interval's
    # phase split reported by the actor at its collect (stream/actor.py):
    # apply = chunk compute+dispatch, persist = barrier-time flush/commit
    # work in the chain, align = input-channel + fence waiting. A slow
    # epoch's trace shows WHO held the barrier and DOING WHAT. An actor
    # whose chain holds a sharded (mesh) executor adds "mesh_rows",
    # "mesh_rows_max_shard" (rows the shards received from the in-mesh
    # shuffle: all of them, the fullest shard's) and "mesh_shuffle_bytes"
    # (stream/mesh_shuffle.py). An actor whose chain holds a hash agg adds
    # "agg_emit_rows" (rows its barrier flush sent downstream),
    # "agg_evict_groups" (live groups its watermark cleaning zeroed),
    # "agg_purges" (same-capacity rebuilds that dropped the zombies; only
    # where one ran), "agg_rehash_rows" (the groups a rebuild, purge or
    # growth, re-inserted; only where one ran) and, with a retractable
    # MIN/MAX, "agg_extrema_lossy_groups"; any actor whose chain holds a
    # state table adds "row_path_rows" (the rows they took in row form, 0
    # where every write was a columnar batch: stream/monitor.py); one that
    # holds a sorted join adds "join_persist_delete_rows" /
    # "join_persist_insert_rows" (rows its durable flush wrote), from its
    # watchdog fetch "join_live_rows" / "join_capacity" (the fuller pool)
    # and, on one chip, "join_match_rows" (rows its applies emitted) and
    # "join_match_peak" / "join_match_width" (the most equi-key candidates
    # one chunk found, of the side nearest its match buffer's width); one
    # that holds a snapshot join-agg adds "snapshot_rows" /
    # "snapshot_capacity" (its fact store) and "snapshot_dim_rows", from
    # the counts fetch its barrier makes; one that holds a top-N adds, from
    # its watchdog fetch, "topn_live_rows" / "topn_capacity" (rows its store
    # holds once the barrier has pruned it), "topn_emit_rows" (rows its
    # flush sent downstream: inserts, deletes, both halves of update pairs)
    # "topn_pruned_rows" (rows an append-only store dropped as beyond
    # rank N) and "topn_sorted_rows" (rows the interval sorted to keep the
    # store ranked: the chunks' for an append-only store, else the capacity).
    # Counts, not nanoseconds: only the keys that end in "_ns" are times
    # (the module docstring lists them: apply / persist / align and their
    # parts input_wait / fence / dispatch / apply_wait / persist_wait).
    phases: dict = field(default_factory=dict)
    # the epoch's span tree (module docstring): sid -> Span, the very dict
    # SPAN_LOG holds for the epoch, so spans that arrive after the span
    # closed (the background flush) are seen here too. Empty at
    # metric_level = off.
    span_map: dict = field(default_factory=dict, repr=False)
    root_sid: int = 0
    collect_sid: int = 0
    sync_ns: int = 0        # inline store sync duration (pipelining off)
    # checkpoint-pipeline phases (annotated AFTER the span closes — the
    # uploader commits in the background, off the barrier critical path)
    seal_ns: int = 0
    upload_ns: int = 0
    commit_ns: int = 0
    total_ns: int = 0
    # cluster stitching (meta side only): worker_id -> that worker's
    # span dict (an EpochTrace.to_dict() shipped on the sealed push).
    # Worker offsets are relative to the worker's inject RECEIPT, which
    # stitching anchors at meta's inject push — no cross-host clocks.
    worker_spans: dict = field(default_factory=dict)
    # cross-engine broker links: `dir="out"` = a BrokerSink delivery
    # this epoch (carries OUR span id, stamped into the batch meta);
    # `dir="in"` = a BrokerPartitionConnector ingest (carries the
    # UPSTREAM engine's span id read back from that meta). The pair
    # meets again in `stitch_chrome_traces` via matching span ids.
    links: list = field(default_factory=list)

    @property
    def spans(self) -> list:
        """The epoch's spans, by start time."""
        return sorted(self.span_map.values(), key=lambda sp: sp.t0_ns)

    def to_dict(self) -> dict:
        """Wire form of the span (sealed-push piggyback + format=json):
        every time is an OFFSET from inject_ns, so the dict is
        meaningful on any host."""
        return {
            "epoch": self.epoch,
            "collects": [[a, int(dt)] for a, dt in self.collects],
            "phases": {str(a): dict(ph)
                       for a, ph in self.phases.items()},
            "sync_ns": int(self.sync_ns),
            "seal_ns": int(self.seal_ns),
            "upload_ns": int(self.upload_ns),
            "commit_ns": int(self.commit_ns),
            "total_ns": int(self.total_ns),
            "links": [dict(ln) for ln in self.links],
            "spans": [[sp.name, sp.parent, sp.owner,
                       int(sp.t0_ns - self.inject_ns),
                       int(sp.t1_ns - self.inject_ns), sp.sid, sp.count]
                      for sp in self.spans],
        }

    @classmethod
    def from_dict(cls, d: dict) -> "EpochTrace":
        t = cls(int(d["epoch"]), 0)
        t.collects = [(int(a), int(dt))
                      for a, dt in d.get("collects", ())]
        t.phases = {int(a): dict(ph)
                    for a, ph in d.get("phases", {}).items()}
        t.sync_ns = int(d.get("sync_ns", 0))
        t.seal_ns = int(d.get("seal_ns", 0))
        t.upload_ns = int(d.get("upload_ns", 0))
        t.commit_ns = int(d.get("commit_ns", 0))
        t.total_ns = int(d.get("total_ns", 0))
        t.links = [dict(ln) for ln in d.get("links", ())]
        for name, parent, owner, t0, t1, sid, count in d.get("spans", ()):
            t.span_map[int(sid)] = Span(t.epoch, str(name), int(parent),
                                        owner, int(t0), int(t1), int(sid),
                                        int(count))
            if name == "checkpoint":
                t.root_sid = int(sid)
            elif name == "collect":
                t.collect_sid = int(sid)
        return t

    @staticmethod
    def _actor_line(actor_id, dt, ph, prefix="") -> str:
        line = (f"  {prefix}actor {actor_id} collected at "
                f"+{dt / 1e6:.1f}ms")
        if ph:
            line += (f" (apply {ph.get('apply_ns', 0) / 1e6:.1f}ms, "
                     f"persist {ph.get('persist_ns', 0) / 1e6:.1f}ms, "
                     f"align {ph.get('align_ns', 0) / 1e6:.1f}ms)")
            if "dispatch_ns" in ph:
                line += (f" (of which dispatch "
                         f"{ph['dispatch_ns'] / 1e6:.1f}ms, d2h wait "
                         f"{ph['apply_wait_ns'] / 1e6:.1f} + "
                         f"{ph['persist_wait_ns'] / 1e6:.1f}ms; fence "
                         f"{ph['fence_ns'] / 1e6:.1f}ms, input wait "
                         f"{ph['input_wait_ns'] / 1e6:.1f}ms)")
            if "mesh_rows" in ph:
                line += (f" [mesh rows {ph['mesh_rows']}, max shard "
                         f"{ph['mesh_rows_max_shard']}, shuffle "
                         f"{ph['mesh_shuffle_bytes']} B]")
            if "agg_emit_rows" in ph:
                line += f" [agg emitted {ph['agg_emit_rows']} rows"
                if "agg_extrema_lossy_groups" in ph:
                    line += (f", {ph['agg_extrema_lossy_groups']} lossy "
                             f"min/max groups")
                if ph.get("agg_evict_groups"):
                    line += f", evicted {ph['agg_evict_groups']} groups"
                if "agg_purges" in ph:
                    line += f", {ph['agg_purges']} zombie purge(s)"
                if "agg_rehash_rows" in ph:
                    line += f", rehashed {ph['agg_rehash_rows']} groups"
                line += "]"
            if ph.get("row_path_rows"):
                line += f" [{ph['row_path_rows']} state rows in row form]"
            if "join_persist_delete_rows" in ph:
                line += (f" [join persisted -"
                         f"{ph['join_persist_delete_rows']} +"
                         f"{ph['join_persist_insert_rows']} rows]")
            if "join_live_rows" in ph:
                line += (f" [join holds {ph['join_live_rows']} of "
                         f"{ph['join_capacity']} rows")
                if "join_match_rows" in ph:
                    line += (f", matched {ph['join_match_rows']} rows, "
                             f"peak {ph['join_match_peak']} of "
                             f"{ph['join_match_width']} candidates")
                line += "]"
            if "topn_live_rows" in ph:
                line += (f" [top-N holds {ph['topn_live_rows']} of "
                         f"{ph['topn_capacity']} rows, emitted "
                         f"{ph['topn_emit_rows']}, pruned "
                         f"{ph['topn_pruned_rows']}, sorted "
                         f"{ph['topn_sorted_rows']}]")
            if "snapshot_rows" in ph:
                line += (f" [snapshot holds {ph['snapshot_rows']} of "
                         f"{ph['snapshot_capacity']} rows, "
                         f"{ph['snapshot_dim_rows']} dim keys]")
        return line

    def _dispatch_by_actor(self) -> dict:
        """actor -> {program name: (calls, ns)} from the dispatch spans:
        one span a call, summed per name."""
        out: dict = {}
        for sp in self.span_map.values():
            if sp.name.startswith("dispatch:"):
                per = out.setdefault(sp.owner, {})
                n, ns = per.get(sp.name[9:], (0, 0))
                per[sp.name[9:]] = (n + 1, ns + sp.t1_ns - sp.t0_ns)
        return out

    def _flush_lines(self) -> list:
        """The background flush from its spans: inject -> commit, the
        wait in the uploader's queue, each stage with the part of it that
        waited for the device."""
        by_name = {sp.name: sp for sp in self.span_map.values()
                   if sp.owner in ("coord", "uploader")}
        if not {"checkpoint", "collect", "flush.queue",
                "flush"} <= set(by_name):
            return []
        def ms(sp):
            return (sp.t1_ns - sp.t0_ns) / 1e6

        flush_sid = by_name["flush"].sid
        waits: dict = {}        # stage sid -> [d2h wait ns, loop wait ns]
        for sp in self.span_map.values():
            if sp.owner == "uploader" and sp.name in ("d2h_wait",
                                                      "flush.loop_wait"):
                w = waits.setdefault(sp.parent, [0, 0])
                w[sp.name != "d2h_wait"] += sp.t1_ns - sp.t0_ns
        lines = [f"  inject -> commit {ms(by_name['checkpoint']):.1f}ms: "
                 f"collect {ms(by_name['collect']):.1f}ms, flush queued "
                 f"{ms(by_name['flush.queue']):.1f}ms, flush "
                 f"{ms(by_name['flush']):.1f}ms"]
        for sp in self.spans:
            if sp.parent == flush_sid:
                lines.append(
                    f"    {sp.name} {ms(sp):.1f}ms"
                    + (f" (d2h wait {waits[sp.sid][0] / 1e6:.1f}ms, waiting "
                       f"for the loop {waits[sp.sid][1] / 1e6:.1f}ms)"
                       if sp.sid in waits else ""))
        return lines

    def render(self) -> str:
        head = (f"epoch {self.epoch}: total {self.total_ns / 1e6:.1f}ms, "
                f"sync {self.sync_ns / 1e6:.1f}ms")
        if self.seal_ns or self.upload_ns or self.commit_ns:
            head += (f" [bg seal {self.seal_ns / 1e6:.1f}ms, "
                     f"upload {self.upload_ns / 1e6:.1f}ms, "
                     f"commit {self.commit_ns / 1e6:.1f}ms]")
        lines = [head]
        lines += self._flush_lines()
        by_actor = self._dispatch_by_actor()
        for actor_id, dt in sorted(self.collects, key=lambda x: x[1]):
            lines.append(self._actor_line(
                actor_id, dt, self.phases.get(actor_id)))
            if actor_id in by_actor:
                lines.append("    " + ", ".join(
                    f"{name} {n}x {ns / 1e6:.1f}ms" for name, (n, ns)
                    in sorted(by_actor[actor_id].items(),
                              key=lambda kv: -kv[1][1])))
        # stitched per-worker sub-blocks: one timeline, offsets
        # anchored at each worker's inject receipt (= meta's push)
        for wid in sorted(self.worker_spans):
            w = self.worker_spans[wid]
            lines.append(
                f"  -- w{wid} (offsets from inject receipt): "
                f"total {w.get('total_ns', 0) / 1e6:.1f}ms"
                + (f", seal {w['seal_ns'] / 1e6:.1f}ms"
                   f" upload {w['upload_ns'] / 1e6:.1f}ms"
                   f" commit {w['commit_ns'] / 1e6:.1f}ms"
                   if w.get("seal_ns") or w.get("upload_ns")
                   or w.get("commit_ns") else ""))
            phases = w.get("phases", {})
            for actor_id, dt in sorted(w.get("collects", ()),
                                       key=lambda x: x[1]):
                lines.append(self._actor_line(
                    actor_id, dt, phases.get(str(actor_id)),
                    prefix=f"w{wid}/"))
        return "\n".join(lines)


class EpochTracer:
    """Ring of recent epoch traces (the Grafana trace panel stand-in)."""

    def __init__(self, keep: int = 64):
        self._ring: deque[EpochTrace] = deque(maxlen=keep)
        self._open: dict[int, EpochTrace] = {}
        # recovery spans (frontend/session.py notes one per auto-
        # recovery): rendered by /debug/traces next to the epoch spans
        # so a post-mortem shows WHEN recovery ran, at what scope, for
        # how long, and which actors were rebuilt
        self.recoveries: deque[dict] = deque(maxlen=keep)

    def note_recovery(self, scope: str, cause: str, duration_ns: int,
                      actors=()) -> None:
        self.recoveries.append({
            "scope": scope, "cause": cause,
            "duration_ns": int(duration_ns),
            "actors": list(actors),
            "at_ns": time.monotonic_ns()})

    def render_recoveries(self) -> list[str]:
        return [
            (f"recovery scope={r['scope']} cause={r['cause']} "
             f"{r['duration_ns'] / 1e6:.1f}ms "
             f"rebuilt_actors={r['actors']}")
            for r in self.recoveries]

    def begin(self, epoch: int, spans: bool = True) -> None:
        """`spans` False (metric_level = off): the epoch records no span
        tree."""
        t = self._open[epoch] = EpochTrace(epoch, time.monotonic_ns())
        if spans:
            t.root_sid, t.collect_sid = _next_sid(), _next_sid()
            t.span_map = SPAN_LOG.open(epoch, t.root_sid, t.collect_sid)

    def collect(self, epoch: int, actor_id: int) -> None:
        t = self._open.get(epoch)
        if t is not None:
            t.collects.append(
                (actor_id, time.monotonic_ns() - t.inject_ns))

    def collect_phases(self, epoch: int, actor_id: int,
                       phases: dict) -> None:
        """Attach an actor's interval phase split (apply / persist /
        align, in ns) to the open epoch span (reported by the actor just
        before it collects the barrier)."""
        t = self._open.get(epoch)
        if t is not None:
            t.phases[actor_id] = phases

    def end(self, epoch: int, sync_ns: int = 0) -> None:
        t = self._open.pop(epoch, None)
        if t is not None:
            now = time.monotonic_ns()
            t.total_ns = now - t.inject_ns
            t.sync_ns = sync_ns
            self._ring.append(t)
            if t.root_sid:
                # the root ends here unless a background flush follows:
                # `annotate` then writes it again, to the manifest swap
                SPAN_LOG.put(
                    Span(epoch, "checkpoint", 0, "coord", t.inject_ns, now,
                         t.root_sid),
                    Span(epoch, "collect", t.root_sid, "coord",
                         t.inject_ns, now, t.collect_sid))

    def annotate(self, epoch: int, *, seal_ns: int = 0, upload_ns: int = 0,
                 commit_ns: int = 0, committed_at_ns: int = 0) -> None:
        """Attach checkpoint-pipeline phase durations to an epoch whose
        span already closed — the background uploader reports these after
        the barrier completed (which is the whole point of the pipeline).
        `committed_at_ns` (the manifest swap) ends the `checkpoint` span."""
        t = self._open.get(epoch)
        if t is None:
            for cand in reversed(self._ring):
                if cand.epoch == epoch:
                    t = cand
                    break
        if t is not None:
            t.seal_ns, t.upload_ns, t.commit_ns = seal_ns, upload_ns, commit_ns
            if t.root_sid and committed_at_ns:
                SPAN_LOG.put(Span(epoch, "checkpoint", 0, "coord",
                                  t.inject_ns, committed_at_ns, t.root_sid))

    def add_links(self, epoch: int, links) -> None:
        """Attach cross-engine broker link records to an epoch span —
        open first, then the ring (sink deliveries on the exactly-once
        path land after the epoch's span closed, like annotate)."""
        t = self._open.get(epoch)
        if t is None:
            for cand in reversed(self._ring):
                if cand.epoch == epoch:
                    t = cand
                    break
        if t is not None:
            t.links.extend(dict(ln) for ln in links)

    def ingest_worker(self, worker_id: int, spans) -> None:
        """Meta-side stitch point: attach a worker's shipped span
        bundle (list of EpochTrace.to_dict()) to the matching meta
        epoch spans — open first, then the ring (the sealed report that
        carries a bundle usually lands AFTER the epoch's span closed,
        exactly like the background uploader's annotate)."""
        for d in spans or ():
            try:
                canon = EpochTrace.from_dict(d).to_dict()
            except (KeyError, TypeError, ValueError):
                continue            # a malformed bundle never wedges meta
            epoch = canon["epoch"]
            t = self._open.get(epoch)
            if t is None:
                for cand in reversed(self._ring):
                    if cand.epoch == epoch:
                        t = cand
                        break
            if t is not None:
                t.worker_spans[int(worker_id)] = canon

    def unshipped(self, shipped: set) -> list[EpochTrace]:
        """Worker-side: closed spans not yet piggybacked on a sealed
        report (the caller records what it shipped)."""
        return [t for t in self._ring if t.epoch not in shipped]

    def recent(self, n: int = 8) -> list[EpochTrace]:
        return list(self._ring)[-n:]

    def open_traces(self) -> list[EpochTrace]:
        """In-flight (uncollected) epochs — THE data for a stuck
        barrier: which actors already collected, and when."""
        out = []
        now = time.monotonic_ns()
        for t in self._open.values():
            t.total_ns = now - t.inject_ns
            out.append(t)
        return sorted(out, key=lambda t: t.epoch)

    def slowest(self, n: int = 3) -> list[EpochTrace]:
        return sorted(self._ring, key=lambda t: -t.total_ns)[:n]


def dump_task_tree(limit_frames: int = 6) -> str:
    """Await stacks of every live asyncio task (await-tree analogue:
    risectl's stack dump for stuck-barrier debugging). Safe to call from
    inside the loop; excludes the calling task's own dump frames."""
    out = []
    try:
        current = asyncio.current_task()
        tasks = asyncio.all_tasks()
    except RuntimeError:
        return "(no running event loop)"
    for task in sorted(tasks,
                       key=lambda t: t.get_name()):
        if task is current:
            continue
        out.append(f"task {task.get_name()}"
                   f"{' <cancelled>' if task.cancelled() else ''}:")
        frames = task.get_stack(limit=limit_frames)
        if not frames:
            out.append("  (no frames: done or not started)")
            continue
        for f in frames:
            code = f.f_code
            out.append(f"  {code.co_filename.rsplit('/', 1)[-1]}"
                       f":{f.f_lineno} {code.co_name}")
    return "\n".join(out)


class RecoveryRing:
    """Recovery post-mortem spans, owned by the SESSION (not the
    coordinator): a full recovery swaps the coordinator — and with it
    the EpochTracer — so a ring living there died with the very
    recovery it was describing. The session survives the swap; the
    ring survives with it. EpochTracer keeps a back-compat mirror."""

    def __init__(self, keep: int = 64):
        self.recoveries: deque[dict] = deque(maxlen=keep)

    def note_recovery(self, scope: str, cause: str, duration_ns: int,
                      actors=()) -> None:
        self.recoveries.append({
            "scope": scope, "cause": cause,
            "duration_ns": int(duration_ns),
            "actors": list(actors),
            "at_ns": time.monotonic_ns()})

    def render_recoveries(self) -> list[str]:
        return [
            (f"recovery scope={r['scope']} cause={r['cause']} "
             f"{r['duration_ns'] / 1e6:.1f}ms "
             f"rebuilt_actors={r['actors']}")
            for r in self.recoveries]


def _phase_args(ph: dict) -> dict:
    """An actor's phase dict as chrome-trace args: times in ms, the mesh,
    agg and join counts as they are."""
    return {k: v / 1e6 if k.endswith("_ns") else v for k, v in ph.items()}


def traces_to_json(traces, recoveries=()) -> dict:
    """format=json: the stitched spans + recovery ring, verbatim."""
    return {
        "traces": [
            {**t.to_dict(),
             "worker_spans": {str(w): dict(s)
                              for w, s in t.worker_spans.items()}}
            for t in traces],
        "recoveries": [dict(r) for r in recoveries],
    }


# tid of the per-engine "broker i/o" track holding cross-engine link
# slices (far above any real actor id)
BROKER_TID = 9_999_999


def _flow_id(span: str) -> int:
    """Stable chrome flow-event id for a span id string: the SAME id on
    the producer's "s" and the consumer's "f" is what ties a sink
    delivery to the downstream ingest across two engines' exports."""
    import zlib
    return zlib.crc32(str(span).encode()) & 0x7FFFFFFF


def traces_to_chrome(traces) -> list:
    """format=chrome: Chrome trace-event array (Perfetto-loadable).
    One pid per worker (pid 0 = meta), one tid per actor (tid 0 = the
    epoch-level span). All timestamps are µs offsets from the OLDEST
    exported epoch's inject, each epoch anchored at its inject time;
    worker events anchor at the inject push, i.e. the same origin.
    The epoch's span tree (module docstring) goes on the same tracks at
    its real offsets from inject: an actor's polls, with their dispatch /
    d2h_wait / input-wait children nested by time, on the actor's tid,
    `checkpoint` / `collect` / `flush.queue` / `flush` and the flush's
    stages on tid 0 (they replace the seal / upload / commit slices laid
    end to end, which an epoch without spans still gets).
    Cross-engine broker links add a "broker i/o" track per epoch plus
    chrome flow events ("s"/"f" with matching ids) so Perfetto draws an
    arrow from a sink delivery to the downstream engine's ingest once
    two exports are stitched (`stitch_chrome_traces`)."""
    events = []
    base = 0
    for i, t in enumerate(sorted(traces, key=lambda t: t.epoch)):
        spans = t.spans
        # an interval's work may start before its barrier's inject: leave
        # it room, so that no timestamp runs back into the epoch before
        base += max([t.inject_ns - sp.t0_ns for sp in spans]
                    + [-sp[3] for w in t.worker_spans.values()
                       for sp in w.get("spans", ())] + [0])

        def ev(name, pid, tid, ts_ns, dur_ns, **args):
            events.append({
                "name": name, "ph": "X", "cat": "epoch",
                "pid": pid, "tid": tid,
                "ts": round((base + ts_ns) / 1e3, 3),
                "dur": round(max(dur_ns, 0) / 1e3, 3),
                "args": {"epoch": t.epoch, **args}})

        ev(f"epoch {t.epoch}", 0, 0, 0, t.total_ns,
           sync_ms=t.sync_ns / 1e6)
        for sp in spans:
            ev(sp.name, 0, sp.owner if isinstance(sp.owner, int) else 0,
               sp.t0_ns - t.inject_ns, sp.t1_ns - sp.t0_ns, sid=sp.sid,
               parent=sp.parent, **({"bytes": sp.count} if sp.count else {}))
        flushed = any(sp.name == "flush" for sp in spans)
        if (t.seal_ns or t.upload_ns or t.commit_ns) and not flushed:
            off = t.total_ns
            for nm, dur in (("seal", t.seal_ns),
                            ("upload", t.upload_ns),
                            ("commit", t.commit_ns)):
                ev(f"{nm} {t.epoch}", 0, 0, off, dur)
                off += dur
        for actor_id, dt in t.collects:
            ph = t.phases.get(actor_id, {})
            ev(f"collect actor {actor_id}", 0, actor_id, 0, dt,
               **_phase_args(ph))
        for wid in sorted(t.worker_spans):
            w = t.worker_spans[wid]
            ev(f"w{wid} epoch {t.epoch}", wid, 0, 0,
               w.get("total_ns", 0))
            phases = w.get("phases", {})
            for actor_id, dt in w.get("collects", ()):
                ph = phases.get(str(actor_id), {})
                ev(f"w{wid} collect actor {actor_id}", wid,
                   actor_id, 0, dt,
                   **_phase_args(ph))
            for name, parent, owner, t0, t1, sid, _count in w.get(
                    "spans", ()):
                ev(name, wid, owner if isinstance(owner, int) else 0,
                   t0, t1 - t0, sid=sid, parent=parent)
        # cross-engine links: one slice per delivery/ingest on the
        # broker i/o track + a flow event INSIDE it (flow events bind
        # to their enclosing slice by pid/tid/ts)
        span_ns = max(t.total_ns, 1_000_000)
        for ln in t.links:
            where = (f"{ln.get('topic')}[{ln.get('partition')}]"
                     f"@{ln.get('offset')}")
            out = ln.get("dir") == "out"
            name = ("sink deliver " if out else "source ingest ") + where
            span = ln.get("span") if out else ln.get("peer")
            ev(name, 0, BROKER_TID, 0, span_ns, **{
                k: v for k, v in ln.items() if v is not None})
            if span:
                events.append({
                    "name": "xengine", "cat": "broker",
                    "ph": "s" if out else "f", **({} if out
                                                  else {"bp": "e"}),
                    "id": _flow_id(span), "pid": 0, "tid": BROKER_TID,
                    "ts": round((base + span_ns / 2) / 1e3, 3)})
        # epochs laid end to end: each epoch's window begins where the
        # previous one's longest span ended (monotonic offsets without
        # trusting any wall clock)
        base += max(t.total_ns + t.seal_ns + t.upload_ns + t.commit_ns,
                    max((w.get("total_ns", 0)
                         for w in t.worker_spans.values()), default=0),
                    max((sp.t1_ns - t.inject_ns for sp in spans),
                        default=0),
                    1_000_000)
    return events


def stitch_chrome_traces(a_events, b_events, a_name: str = "engine-a",
                         b_name: str = "engine-b"):
    """Merge two engines' chrome exports into ONE Perfetto timeline.

    Engine B's pids are re-based (pid + 100 per worker) so the two
    engines render as separate process groups, process_name metadata
    labels them, and engine B's clock is shifted so every matched
    delivery→ingest flow pair is causal (ingest at-or-after delivery —
    the only cross-engine ordering the broker offsets guarantee).
    Returns `(merged_events, n_links)` where n_links counts flow ids
    present as BOTH an "s" (delivery) and an "f" (ingest)."""
    PID_STRIDE = 100
    b_events = [dict(e) for e in b_events]
    for e in b_events:
        e["pid"] = int(e.get("pid", 0)) + PID_STRIDE
    out_ids = {e["id"]: e["ts"] for e in a_events
               if e.get("ph") == "s" and "id" in e}
    in_ids = {e["id"]: e["ts"] for e in b_events
              if e.get("ph") == "f" and "id" in e}
    # reverse direction too (B sinks into A)
    out_ids.update({e["id"]: e["ts"] for e in b_events
                    if e.get("ph") == "s" and "id" in e})
    in_ids.update({e["id"]: e["ts"] for e in a_events
                   if e.get("ph") == "f" and "id" in e})
    matched = sorted(set(out_ids) & set(in_ids))
    # causality shift: push B late enough that no matched ingest
    # precedes its delivery (both exports start at their own t=0)
    delta = 0.0
    for fid in matched:
        a_ts = out_ids[fid]
        b_ts = in_ids[fid]
        delta = max(delta, a_ts - b_ts + 1.0)
    if delta:
        for e in b_events:
            e["ts"] = round(e.get("ts", 0) + delta, 3)
    merged = []
    for pid_base, name, evs in ((0, a_name, a_events),
                                (PID_STRIDE, b_name, b_events)):
        pids = sorted({int(e.get("pid", 0)) for e in evs})
        for pid in pids:
            wid = pid - pid_base
            label = name if wid == 0 else f"{name}/w{wid}"
            merged.append({"name": "process_name", "ph": "M",
                           "pid": pid, "tid": 0,
                           "args": {"name": label}})
    merged.extend(a_events)
    merged.extend(b_events)
    return merged, len(matched)


def format_stuck_barrier_report(coord, worker_reports=None) -> str:
    """One-call diagnosis: the STUCK epochs' partial spans (who already
    collected, and when), recent completed spans, and the await tree.
    In cluster mode the watchdog passes `worker_reports` (worker_id ->
    that worker's own report text pulled over rpc.py) so a wedged epoch
    names the worker, actor, AND parked await frame.
    (What the reference gets from `risectl trace` + await-tree dump.)"""
    tracer = getattr(coord, "tracer", None)
    lines = []
    if tracer is not None:
        stuck = tracer.open_traces()
        if stuck:
            lines.append("== in-flight (stuck) epochs ==")
            for t in stuck:
                lines.append(t.render())
        lines.append("== recent completed epochs ==")
        for t in tracer.recent():
            lines.append(t.render())
    lines.append("== await tree ==")
    lines.append(dump_task_tree())
    for wid in sorted(worker_reports or ()):
        lines.append(f"== worker w{wid} ==")
        lines.append(str(worker_reports[wid]))
    return "\n".join(lines)
