"""Exchange: channels, dispatchers, merge — the intra-host communication
backend.

Reference: dispatch at src/stream/src/executor/dispatch.rs (Hash/Broadcast/
Simple/RoundRobin), fan-in alignment at merge.rs:109,267-342, bounded permit
channels at exchange/permit.rs. In the TPU design the *mesh-internal* shuffle
is an XLA all_to_all (parallel/exchange.py); these host channels connect
actors within a process and stand where the reference's permit channels +
gRPC exchange stood (between fragments, and host<->host over DCN).
"""

from __future__ import annotations

import asyncio
import time
from collections import deque
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import (
    ChunkCoalescer, StreamChunk, OP_DELETE, OP_INSERT, OP_UPDATE_DELETE,
    OP_UPDATE_INSERT,
)
from ..common.vnode import VNODE_COUNT, compute_vnodes
from .executor import Executor
from .message import Barrier, BarrierKind, Message, Watermark
from ..ops.jit_state import jit_state
from ..utils.faults import FAULTS, FaultInjected


class Channel:
    """Bounded mpsc channel (permit.rs analogue).

    `obs` (stream/monitor.py ChannelObs, attached at metric_level=debug)
    adds queue-depth and blocked-put (backpressure) accounting labelled
    by the RECEIVING actor: a full queue means the receiver is the
    bottleneck. `send_obs` (a counter labelled by the SENDING actor,
    attached when the sender's chain instruments) charges the same
    parked seconds to the actor that actually paid them — without it,
    "who is losing time to backpressure" and "who is causing it" were
    conflated under one receiver-side label.

    Replay buffering (per-fragment recovery, plan/build.py): with
    `enable_replay()` every sent message is ALSO appended to an ordered
    buffer tagged with a per-channel sequence number. The barrier
    coordinator trims the buffer at every checkpoint COMMIT — it drops
    everything up to and including the barrier that sealed the committed
    epoch — so the buffer always holds exactly the not-yet-durable
    suffix of the stream (bounded by the checkpoint in-flight window).
    When the consuming fragment is rebuilt from the committed epoch,
    `begin_replay()` re-delivers the whole buffer to the NEW consumer
    (prefixed by a synthetic INITIAL barrier standing for the committed
    point, so the rebuilt executors init/recover BEFORE any replayed
    chunk); live queue entries the dead consumer never drained are
    recognized by sequence number and skipped as duplicates, and a
    producer parked on the full queue is unblocked by the new consumer's
    normal draining. The producer never rewinds — its device state and
    its emitted stream are untouched, which is the whole point."""

    def __init__(self, capacity: int = 16):
        self.queue: asyncio.Queue = asyncio.Queue(maxsize=capacity)
        self.obs = None
        self.send_obs = None
        # replay machinery (None/off for plain channels — the hot path
        # below stays the pre-recovery one)
        self._buf = None                  # deque[(seq, msg)] | None
        self._seq = 0
        self._base_barrier = None         # last trimmed (committed) barrier
        self._replay = None               # deque to deliver before queue
        self._last_seq = 0                # max seq ever delivered
        self._stale_ceiling = None        # drop dead-epoch barriers below
        self._skip_refs = None            # chunk ids preloaded downstream

    # ------------------------------------------------------------ replay
    def enable_replay(self) -> None:
        if self._buf is None:
            self._buf = deque()

    @property
    def replay_enabled(self) -> bool:
        return self._buf is not None

    def trim_replay(self, committed_epoch: int) -> None:
        """Drop buffered messages covered by the committed checkpoint:
        everything up to and including the LAST barrier whose
        `epoch.prev <= committed_epoch` (that barrier sealed the epoch;
        all earlier messages are reflected in durable state). The
        dropped barrier is remembered as the replay base — the epoch a
        rebuilt consumer resumes from."""
        buf = self._buf
        if not buf:
            return
        cut, base = -1, None
        for i, (_seq, m) in enumerate(buf):
            if isinstance(m, Barrier) and m.epoch.prev <= committed_epoch:
                cut, base = i, m
        for _ in range(cut + 1):
            buf.popleft()
        if base is not None:
            self._base_barrier = base

    def reset_for_rebuild(self) -> None:
        """Reset an INTRA-CONE edge: both endpoints of this channel are
        being rebuilt (downstream-cone recovery), so everything in flight
        — queued undrained messages, the buffered uncommitted suffix,
        the sequence counters — belongs to dead incarnations. The
        rebuilt producer re-derives the suffix from ITS replayed inputs
        and re-emits it here as fresh messages (starting with the
        synthetic INITIAL barrier it received from the cone's inbound
        frontier), so the rebuilt consumer must see an empty stream, not
        the aborted interval's leftovers."""
        while not self.queue.empty():
            self.queue.get_nowait()
        if self._buf is not None:
            self._buf = deque()
        self._seq = 0
        self._last_seq = 0
        self._replay = None
        self._base_barrier = None
        self._stale_ceiling = None
        self._skip_refs = None

    def begin_replay(self, stale_ceiling: Optional[int] = None,
                     skip_refs: Optional[set] = None) -> int:
        """Arm re-delivery of the buffered suffix to the next consumer.
        Prepends a synthetic INITIAL barrier at the committed point (the
        rebuilt chain's executors init their state tables and reload
        durable state at their first barrier — which must precede every
        replayed chunk). Returns the number of messages to replay.

        `stale_ceiling` (cluster worker recovery): barriers of the
        DROPPED epochs — committed < epoch.curr <= ceiling — are
        filtered out of the replay AND the live stream. In-process cone
        recovery replays them on every leg (all legs saw the same
        stream, so merges align); in the cluster radius a rebuilt
        SOURCE joins straight at the live stream, so a frontier leg
        replaying dead barriers would leave its merge peer one barrier
        short forever. A producer that was parked mid-epoch may even
        dispatch a dead barrier AFTER the rebuild — the ceiling filter
        catches that too.

        `skip_refs` (channel-free mesh replay): object identities of
        chunks the rebuilt consumer already holds — preloaded straight
        from the crashed executor's MeshIngestLog into its pending queue
        — so re-delivering them here would double-apply. The replay
        buffer holds the SAME objects by reference, so identity matching
        is exact; barriers and watermarks still replay for epoch
        alignment. Consumed on match (each ref skips once)."""
        assert self._buf is not None, "replay not enabled on this channel"
        self._stale_ceiling = stale_ceiling
        self._skip_refs = set(skip_refs) if skip_refs else None
        items = deque(self._buf)
        base = self._base_barrier
        if base is not None:
            items.appendleft((None, Barrier(
                base.epoch, BarrierKind.INITIAL, None, (),
                base.inject_time_ns)))
        self._replay = items
        return len(items)

    def _is_stale(self, msg) -> bool:
        c = getattr(self, "_stale_ceiling", None)
        return (c is not None and isinstance(msg, Barrier)
                and msg.kind is not BarrierKind.INITIAL
                and msg.epoch.curr <= c)

    async def send(self, msg: Message) -> None:
        item = msg
        if self._buf is not None:
            self._seq += 1
            item = (self._seq, msg)
            # buffer BEFORE the (possibly blocking) queue put: a sender
            # parked on a full queue at rebuild time already has its
            # message in the buffer, so replay covers it and the queued
            # copy dedupes by seq when it finally lands
            self._buf.append(item)
        obs = self.obs
        send_obs = self.send_obs
        if obs is None and send_obs is None:
            await self.queue.put(item)
            return
        if self.queue.full():
            t0 = time.monotonic()
            await self.queue.put(item)
            dt = time.monotonic() - t0
            if obs is not None:
                obs.blocked_put.inc(dt)
            if send_obs is not None:
                send_obs.inc(dt)
        else:
            self.queue.put_nowait(item)
        if obs is not None:
            obs.depth.set(float(self.queue.qsize()))

    async def recv(self) -> Message:
        while self._replay:
            seq, msg = self._replay.popleft()
            if seq is not None and seq > self._last_seq:
                self._last_seq = seq
            if self._is_stale(msg):
                continue
            skips = getattr(self, "_skip_refs", None)
            if skips and id(msg) in skips:
                skips.discard(id(msg))  # consumer preloaded this chunk
                continue
            return msg
        if self._buf is None:
            msg = await self.queue.get()
            if self.obs is not None:
                self.obs.depth.set(float(self.queue.qsize()))
            return msg
        while True:
            seq, msg = await self.queue.get()
            if self.obs is not None:
                self.obs.depth.set(float(self.queue.qsize()))
            if seq <= self._last_seq:
                continue            # duplicate of a replayed message
            self._last_seq = seq
            if self._is_stale(msg):
                continue            # a dead epoch's barrier, late
            return msg


# ------------------------------------------------------------- dispatchers

class Dispatcher:
    async def dispatch(self, msg: Message) -> None:
        raise NotImplementedError


class TapDispatcher(Dispatcher):
    """Runtime-extendable fanout for MV roots: a downstream `CREATE
    MATERIALIZED VIEW ... FROM <mv>` attaches a channel here while the
    deployment is LIVE (the reference's Add-mutation installs new
    dispatchers the same way, dispatch.rs AddOutput). Attach/detach must
    happen between barriers (the session holds the coordinator's rounds
    lock), so every consumer sees a barrier-aligned prefix.

    A Stop barrier covering ALL of a channel's consumer actors removes
    that channel right after delivering the barrier (the reference drops
    dispatcher outputs at the DropActors barrier) — without this, the
    upstream actor keeps pushing post-stop chunks into a channel nobody
    drains and deadlocks on its bounded capacity."""

    def __init__(self):
        self.channels: list = []          # (Channel, consumer actor ids)

    def add(self, channel, consumer_actor_ids=frozenset()) -> None:
        self.channels.append((channel, frozenset(consumer_actor_ids)))

    def remove(self, channel) -> None:
        self.channels = [(c, ids) for c, ids in self.channels
                         if c is not channel]

    def set_consumers(self, channel, consumer_actor_ids) -> None:
        self.channels = [
            (c, frozenset(consumer_actor_ids) if c is channel else ids)
            for c, ids in self.channels]

    async def dispatch(self, msg: Message) -> None:
        from .message import StopMutation
        for ch, ids in list(self.channels):
            await ch.send(msg)
            if (isinstance(msg, Barrier) and ids
                    and isinstance(msg.mutation, StopMutation)
                    and ids <= msg.mutation.actor_ids):
                self.remove(ch)


class SimpleDispatcher(Dispatcher):
    def __init__(self, output: Channel):
        self.output = output

    async def dispatch(self, msg: Message) -> None:
        await self.output.send(msg)


class BroadcastDispatcher(Dispatcher):
    def __init__(self, outputs: Sequence[Channel]):
        self.outputs = list(outputs)

    async def dispatch(self, msg: Message) -> None:
        for o in self.outputs:
            await o.send(msg)


class HashDispatcher(Dispatcher):
    """vnode-routed fan-out (dispatch.rs:679,737-790): vnode per row from the
    dist-key columns, visibility per output = (vnode_to_output[vnode] == o).
    Update pairs whose halves land on different outputs degrade to
    Delete/Insert (op fixup, :751-790). Chunks keep full capacity — each
    output sees the same arrays with a different mask (zero-copy fan-out)."""

    def __init__(self, outputs: Sequence[Channel], dist_key_indices: Sequence[int],
                 vnode_to_output: np.ndarray):
        assert len(vnode_to_output) == VNODE_COUNT
        self.outputs = list(outputs)
        self.dist_key_indices = tuple(dist_key_indices)
        # the mapping is PASSED to the jitted program, never closed over:
        # a captured device array is a constant buffer re-validated on
        # every invocation, an argument is not
        self.vnode_to_output = jnp.asarray(vnode_to_output, dtype=jnp.int32)
        # NO donation: route outputs are zero-copy views of the input
        # chunk, which other consumers may still hold
        self._route = jit_state(self._route_impl,
                                name="hash_dispatch_route")

    def _route_impl(self, chunk: StreamChunk, vnode_to_output):
        keys = [chunk.columns[i].data for i in self.dist_key_indices]
        vnodes = compute_vnodes(keys)
        out_idx = jnp.take(vnode_to_output, vnodes)
        results = []
        ops = chunk.ops
        is_ud = ops == OP_UPDATE_DELETE
        is_ui = ops == OP_UPDATE_INSERT
        partner_prev = jnp.roll(out_idx, 1)   # UI's partner UD output
        partner_next = jnp.roll(out_idx, -1)  # UD's partner UI output
        pair_split = (is_ui & (out_idx != partner_prev)) | (is_ud & (out_idx != partner_next))
        fixed_ops = jnp.where(pair_split & is_ui, OP_INSERT, ops)
        fixed_ops = jnp.where(pair_split & is_ud, OP_DELETE, fixed_ops).astype(ops.dtype)
        for o in range(len(self.outputs)):
            vis = chunk.vis & (out_idx == o)
            results.append(StreamChunk(chunk.columns, fixed_ops, vis, chunk.schema))
        return tuple(results)

    async def dispatch(self, msg: Message) -> None:
        if isinstance(msg, StreamChunk):
            for o, ch in zip(self.outputs,
                             self._route(msg, self.vnode_to_output)):
                await o.send(ch)
        else:
            for o in self.outputs:
                await o.send(msg)


# ------------------------------------------------------------------ merge

class ChannelInput(Executor):
    """Executor adapter over a channel (ReceiverExecutor, receiver.rs).

    `stop_on(barrier) -> bool` decides which Stop barrier ends the
    stream. Deployment builders pass the owning actor's predicate
    (`b.is_stop(actor_id)`): a shared coordinator's stop mutation may
    target OTHER deployments' actors (MV-on-MV taps route every barrier
    through everyone), and self-terminating on a foreign stop silently
    killed the chain. Default (None) keeps the standalone/test behavior:
    any Stop ends the stream."""

    def __init__(self, channel: Channel, schema, stop_on=None,
                 coalesce_max: int = 0, actor_id=None):
        self.channel = channel
        self.schema = schema
        self.stop_on = stop_on
        # coalesce_max > 0: pack runs of consecutive chunks up to that
        # total capacity into one chunk (flushed before any barrier/
        # watermark, so cross-message ordering is the uncoalesced one)
        self.coalescer = (ChunkCoalescer(coalesce_max) if coalesce_max
                          else None)
        self.identity = "ChannelInput"
        # owning actor id (fault-point context: poison_chunk/channel_stall
        # rules filter on the CONSUMING actor)
        self.actor_id = actor_id
        # owning actor's ActorObs (stream/monitor.py): recv waits are the
        # align component of the interval phase split
        self.obs = None

    async def execute(self):
        from .message import StopMutation
        co = self.coalescer
        while True:
            obs = self.obs
            if obs is None:
                msg = await self.channel.recv()
            else:
                t0 = time.monotonic_ns()
                msg = await self.channel.recv()
                obs.add_input_wait(time.monotonic_ns() - t0)
                if isinstance(msg, StreamChunk):
                    obs.note_chunk_in()
            if FAULTS.active and isinstance(msg, StreamChunk):
                if FAULTS.hit("poison_chunk",
                              actor=self.actor_id) is not None:
                    raise FaultInjected(
                        f"injected poison_chunk at consumer actor "
                        f"{self.actor_id}")
                stall = FAULTS.hit("channel_stall", actor=self.actor_id)
                if stall is not None:
                    await asyncio.sleep(stall.get("ms", 100) / 1e3)
            if co is not None:
                if isinstance(msg, StreamChunk):
                    for out in co.push(msg):
                        yield out
                    continue
                for out in co.flush():
                    yield out
            yield msg
            if isinstance(msg, Barrier)                     and isinstance(msg.mutation, StopMutation):
                if self.stop_on is None or self.stop_on(msg):
                    return


class MergeExecutor(Executor):
    """Fan-in with barrier alignment (merge.rs:267-342): an upstream that
    yields a barrier is blocked until every upstream yields that barrier,
    then ONE barrier is emitted. Watermarks are min-combined per column."""

    def __init__(self, channels: Sequence[Channel], schema, stop_on=None,
                 coalesce_max: int = 0):
        self.channels = list(channels)
        self.schema = schema
        self.stop_on = stop_on            # see ChannelInput.stop_on
        # fan-in is where small-chunk runs concentrate (N upstream actors
        # interleave inside one barrier interval): one coalescer packs the
        # combined stream, flushed before any barrier/watermark emission
        self.coalescer = (ChunkCoalescer(coalesce_max) if coalesce_max
                          else None)
        self.identity = f"Merge({len(self.channels)})"
        # owning actor's ActorObs: time parked in asyncio.wait covers
        # both upstream starvation AND barrier alignment (channels that
        # already delivered their barrier are held out of the wait set)
        self.obs = None

    async def execute(self):
        n = len(self.channels)
        co = self.coalescer
        getters: dict[int, asyncio.Task] = {
            i: asyncio.create_task(c.recv()) for i, c in enumerate(self.channels)}
        pending_barrier: dict[int, Barrier] = {}
        watermarks: dict[int, dict[int, Watermark]] = {i: {} for i in range(n)}
        emitted_wm: dict[int, object] = {}
        try:
            while True:
                waiting = [t for i, t in getters.items() if i not in pending_barrier]
                if not waiting:
                    barrier = next(iter(pending_barrier.values()))
                    from .message import StopMutation
                    stop = (isinstance(barrier.mutation, StopMutation)
                            and (self.stop_on is None
                                 or self.stop_on(barrier)))
                    if co is not None:
                        for out in co.flush():
                            yield out
                    yield barrier
                    pending_barrier.clear()
                    if stop:
                        return
                    for i, c in enumerate(self.channels):
                        getters[i] = asyncio.create_task(c.recv())
                    continue
                obs = self.obs
                if obs is None:
                    done, _ = await asyncio.wait(
                        waiting, return_when=asyncio.FIRST_COMPLETED)
                else:
                    t0 = time.monotonic_ns()
                    done, _ = await asyncio.wait(
                        waiting, return_when=asyncio.FIRST_COMPLETED)
                    obs.add_input_wait(time.monotonic_ns() - t0)
                # fixed channel order, not set order: asyncio.wait's
                # `done` is a set whose iteration follows task object
                # addresses — with several upstreams ready in one pass
                # the merge interleaving would depend on process memory
                # layout (same fix as stream/align.py barrier_align)
                for i in sorted(getters):
                    t = getters[i]
                    if t not in done or i in pending_barrier:
                        continue
                    msg = t.result()
                    if obs is not None and isinstance(msg, StreamChunk):
                        obs.note_chunk_in()
                    if isinstance(msg, Barrier):
                        pending_barrier[i] = msg
                    elif isinstance(msg, Watermark):
                        watermarks[i][msg.col_idx] = msg
                        wm = self._combined_watermark(msg.col_idx, watermarks)
                        if wm is not None and emitted_wm.get(msg.col_idx) != wm.val:
                            emitted_wm[msg.col_idx] = wm.val
                            if co is not None:
                                for out in co.flush():
                                    yield out
                            yield wm
                        getters[i] = asyncio.create_task(self.channels[i].recv())
                    else:
                        if co is not None:
                            for out in co.push(msg):
                                yield out
                        else:
                            yield msg
                        getters[i] = asyncio.create_task(self.channels[i].recv())
        finally:
            for t in getters.values():
                t.cancel()

    def _combined_watermark(self, col_idx: int, watermarks) -> Optional[Watermark]:
        vals = [w[col_idx] for w in watermarks.values() if col_idx in w]
        if len(vals) < len(self.channels):
            return None
        return min(vals, key=lambda w: w.val)
