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
"""

from __future__ import annotations

import asyncio
import time
import traceback
from collections import deque
from dataclasses import dataclass, field


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
    # "agg_emit_rows" (rows its barrier flush sent downstream) and, with a
    # retractable MIN/MAX, "agg_extrema_lossy_groups"; one that holds a
    # sorted join adds "join_persist_delete_rows" /
    # "join_persist_insert_rows" (rows its durable flush wrote), from its
    # watchdog fetch "join_live_rows" / "join_capacity" (the fuller pool)
    # and, on one chip, "join_match_rows" (rows its applies emitted) and
    # "join_match_peak" / "join_match_width" (the most equi-key candidates
    # one chunk found, of the side nearest its match buffer's width).
    # Counts, not nanoseconds: only the keys that end in "_ns" are times.
    phases: dict = field(default_factory=dict)
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
        return t

    @staticmethod
    def _actor_line(actor_id, dt, ph, prefix="") -> str:
        line = (f"  {prefix}actor {actor_id} collected at "
                f"+{dt / 1e6:.1f}ms")
        if ph:
            line += (f" (apply {ph.get('apply_ns', 0) / 1e6:.1f}ms, "
                     f"persist {ph.get('persist_ns', 0) / 1e6:.1f}ms, "
                     f"align {ph.get('align_ns', 0) / 1e6:.1f}ms)")
            if "mesh_rows" in ph:
                line += (f" [mesh rows {ph['mesh_rows']}, max shard "
                         f"{ph['mesh_rows_max_shard']}, shuffle "
                         f"{ph['mesh_shuffle_bytes']} B]")
            if "agg_emit_rows" in ph:
                line += f" [agg emitted {ph['agg_emit_rows']} rows"
                if "agg_extrema_lossy_groups" in ph:
                    line += (f", {ph['agg_extrema_lossy_groups']} lossy "
                             f"min/max groups")
                line += "]"
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
        return line

    def render(self) -> str:
        head = (f"epoch {self.epoch}: total {self.total_ns / 1e6:.1f}ms, "
                f"sync {self.sync_ns / 1e6:.1f}ms")
        if self.seal_ns or self.upload_ns or self.commit_ns:
            head += (f" [bg seal {self.seal_ns / 1e6:.1f}ms, "
                     f"upload {self.upload_ns / 1e6:.1f}ms, "
                     f"commit {self.commit_ns / 1e6:.1f}ms]")
        lines = [head]
        for actor_id, dt in sorted(self.collects, key=lambda x: x[1]):
            lines.append(self._actor_line(
                actor_id, dt, self.phases.get(actor_id)))
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

    def begin(self, epoch: int) -> None:
        self._open[epoch] = EpochTrace(epoch, time.monotonic_ns())

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
            t.total_ns = time.monotonic_ns() - t.inject_ns
            t.sync_ns = sync_ns
            self._ring.append(t)

    def annotate(self, epoch: int, *, seal_ns: int = 0, upload_ns: int = 0,
                 commit_ns: int = 0) -> None:
        """Attach checkpoint-pipeline phase durations to an epoch whose
        span already closed — the background uploader reports these after
        the barrier completed (which is the whole point of the pipeline)."""
        t = self._open.get(epoch)
        if t is None:
            for cand in reversed(self._ring):
                if cand.epoch == epoch:
                    t = cand
                    break
        if t is not None:
            t.seal_ns, t.upload_ns, t.commit_ns = seal_ns, upload_ns, commit_ns

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
    Cross-engine broker links add a "broker i/o" track per epoch plus
    chrome flow events ("s"/"f" with matching ids) so Perfetto draws an
    arrow from a sink delivery to the downstream engine's ingest once
    two exports are stitched (`stitch_chrome_traces`)."""
    events = []
    base = 0
    for i, t in enumerate(sorted(traces, key=lambda t: t.epoch)):
        def ev(name, pid, tid, ts_ns, dur_ns, **args):
            events.append({
                "name": name, "ph": "X", "cat": "epoch",
                "pid": pid, "tid": tid,
                "ts": round((base + ts_ns) / 1e3, 3),
                "dur": round(max(dur_ns, 0) / 1e3, 3),
                "args": {"epoch": t.epoch, **args}})

        ev(f"epoch {t.epoch}", 0, 0, 0, t.total_ns,
           sync_ms=t.sync_ns / 1e6)
        if t.seal_ns or t.upload_ns or t.commit_ns:
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
