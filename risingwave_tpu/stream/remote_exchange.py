"""Remote exchange — the cross-host (DCN) tier of the communication
backend.

Reference: src/stream/src/executor/exchange/input.rs:103-120
(RemoteInput), src/compute/src/rpc/service/exchange_service.rs:78
(GetStream) and proto/task_service.proto:103-113 — gRPC streams with
permit-based (credit) backpressure between compute nodes. Mesh-internal
shuffles ride ICI as XLA collectives (parallel/exchange.py); THIS module
carries fragment edges that cross process/host boundaries.

TPU-first wire design: chunks serialize as Arrow IPC record batches
(common/arrow.py — fixed-width columns move as whole buffers, VARCHAR as
dictionary indices against each side's GLOBAL_DICT with the dictionary
shipped in-band), ops ride as an extra int8 column, and only VISIBLE
rows travel. Barriers/watermarks are small JSON frames. Flow control is
credit-based exactly like permit.rs: the receiver grants chunk credits
as its bounded queue drains; the sender awaits credits before writing,
so a slow consumer backpressures through TCP instead of ballooning.

Frame format: 1-byte type ('C' chunk | 'B' barrier | 'W' watermark |
'K' credit grant) + 4-byte big-endian length + payload.
"""

from __future__ import annotations

import asyncio
import io
import json
import struct
from collections import deque
from typing import Optional

import numpy as np

from ..common.chunk import StreamChunk
from ..common.types import Schema
from .executor import Executor
from .message import (
    Barrier, BarrierKind, PauseMutation, ResumeMutation, StopMutation,
    ThrottleMutation, Watermark,
)
from ..common.epoch import EpochPair


def _ser_mutation(m) -> Optional[dict]:
    if m is None:
        return None
    if isinstance(m, StopMutation):
        return {"type": "stop", "actor_ids": sorted(m.actor_ids)}
    if isinstance(m, PauseMutation):
        return {"type": "pause"}
    if isinstance(m, ResumeMutation):
        return {"type": "resume"}
    if isinstance(m, ThrottleMutation):
        return {"type": "throttle", "limits": [list(x) for x in m.limits]}
    raise ValueError(f"unserializable mutation {m!r}")


def _de_mutation(d):
    if d is None:
        return None
    t = d["type"]
    if t == "stop":
        return StopMutation(frozenset(d["actor_ids"]))
    if t == "pause":
        return PauseMutation()
    if t == "resume":
        return ResumeMutation()
    if t == "throttle":
        return ThrottleMutation(tuple(tuple(x) for x in d["limits"]))
    raise ValueError(t)


def _chunk_payload(chunk: StreamChunk) -> bytes:
    import pyarrow as pa
    from ..common.arrow import chunk_to_arrow
    batch = chunk_to_arrow(chunk)
    ops = np.asarray(chunk.ops)[np.asarray(chunk.vis)]
    batch = batch.append_column("__op", pa.array(ops, type=pa.int8()))
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, batch.schema) as w:
        w.write_batch(batch)
    return sink.getvalue()


def _payload_chunk(payload: bytes, schema: Schema,
                   capacity: int) -> StreamChunk:
    import pyarrow as pa
    from ..common.arrow import batch_to_chunk
    with pa.ipc.open_stream(io.BytesIO(payload)) as r:
        table = r.read_all()
    batch = (table.combine_chunks().to_batches()[0]
             if table.num_rows else
             pa.RecordBatch.from_pylist([], schema=table.schema))
    ops = np.asarray(batch.column("__op"), dtype=np.int8)
    data = batch.drop_columns(["__op"])
    cap = max(capacity, 1 << max(0, (batch.num_rows - 1).bit_length()))
    chunk = batch_to_chunk(data, schema, capacity=cap)
    full_ops = np.zeros(cap, dtype=np.int8)
    full_ops[:len(ops)] = ops
    import jax.numpy as jnp
    return StreamChunk(chunk.columns, jnp.asarray(full_ops), chunk.vis,
                       schema)


# this process's cluster worker id (set by cluster/compute_node.py at
# hello) — fault-rule context so `dcn_drop:worker=N` severs exactly one
# node's leg even though the spec arms every process
WORKER_ID = None


async def _write_frame(writer, tag: bytes, payload: bytes) -> None:
    writer.write(tag + struct.pack("!I", len(payload)) + payload)
    await writer.drain()


async def _read_frame(reader):
    hdr = await reader.readexactly(5)
    ln = struct.unpack("!I", hdr[1:])[0]
    return hdr[:1], await reader.readexactly(ln)


class RemoteOutput:
    """Sender half (dispatch target, Channel-compatible `send`).

    Replay buffering (per-worker partial recovery, cluster/): with
    `enable_replay()` every sent message is ALSO retained in an ordered
    buffer, trimmed by meta's `committed` notification to exactly the
    not-yet-durable suffix — the DCN twin of the in-process Channel's
    replay buffer. A vanished receiver then PARKS sends (instead of
    killing the producer actor): `rewind_replay()` re-establishes the
    leg — to the same receiver (rebuilt in place), the same endpoint
    after a severed socket, or a fresh RemoteInput server where the
    consumer was re-placed — and re-feeds a synthetic-INITIAL 'R' frame
    plus the buffered suffix before live sends resume. Without replay
    (the legacy remote-fragment tier), a dead receiver still fails the
    sender fast."""

    def __init__(self, host: str, port: int, credits: int = 0,
                 replay: bool = False):
        # credits start at ZERO: the receiver's initial grant (its queue
        # depth) is the ONLY source of permits, exactly like permit.rs
        self.host = host
        self.port = port
        self._credits = credits          # chunk permits in hand
        self._credit_evt = asyncio.Event()
        self._reader = self._writer = None
        self._credit_task = None
        self._dead = False
        # ---- replay machinery (None/off for legacy senders) ----
        self._buf = deque() if replay else None    # (seq, msg)
        self._seq = 0
        self._sent_through = 0      # highest seq written to the socket
        self._base_barrier = None   # last trimmed (committed) barrier
        # live sends park while a rewind streams the suffix — an
        # interleaved frame would reach the rebuilt consumer ahead of
        # older suffix messages (order corruption)
        self._rewinding = False

    # ------------------------------------------------------------ replay
    def enable_replay(self) -> None:
        if self._buf is None:
            self._buf = deque()

    @property
    def replay_enabled(self) -> bool:
        return self._buf is not None

    def trim_replay(self, committed_epoch: int) -> None:
        """Same trim rule as the in-process Channel: drop everything up
        to and including the LAST barrier whose epoch.prev is covered
        by the committed checkpoint, remembering it as the replay
        base."""
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

    async def connect(self) -> "RemoteOutput":
        self._reader, self._writer = await asyncio.open_connection(
            self.host, self.port)
        self._credit_task = asyncio.create_task(self._credit_loop())
        return self

    async def _credit_loop(self) -> None:
        try:
            while True:
                tag, payload = await _read_frame(self._reader)
                if tag == b"K":
                    self._credits += struct.unpack("!I", payload)[0]
                    self._credit_evt.set()
        except (asyncio.IncompleteReadError, ConnectionResetError,
                OSError):
            # a sender parked on the credit wait must WAKE once the
            # receiver is gone: legacy senders fail fast (recovery
            # teardown otherwise deadlocks — receiver waits for this
            # socket to close while we wait for its credits); replay
            # senders park until rewind_replay re-establishes the leg.
            # NOT on cancellation: a rewind replaces this loop on a
            # live leg, and a loop that marked the leg dead on its way
            # out would park every send after the rewind
            self._dead = True
            self._credit_evt.set()

    async def _write_msg(self, msg) -> None:
        if isinstance(msg, StreamChunk):
            while self._credits <= 0:     # permit-based backpressure
                if self._dead:
                    raise ConnectionResetError("remote receiver is gone")
                self._credit_evt.clear()
                await self._credit_evt.wait()
            self._credits -= 1
            await _write_frame(self._writer, b"C", _chunk_payload(msg))
        elif isinstance(msg, Barrier):
            await _write_frame(self._writer, b"B", json.dumps({
                "curr": msg.epoch.curr, "prev": msg.epoch.prev,
                "kind": msg.kind.value,
                "mutation": _ser_mutation(msg.mutation)}).encode())
        elif isinstance(msg, Watermark):
            await _write_frame(self._writer, b"W", json.dumps({
                "col_idx": msg.col_idx, "dtype": msg.data_type.name,
                "val": int(msg.val)}).encode())
        else:
            raise ValueError(f"unsendable message {type(msg)}")

    async def send(self, msg) -> None:
        from ..utils.faults import FAULTS
        seq = None
        if self._buf is not None:
            self._seq += 1
            seq = self._seq
            # buffer BEFORE the (possibly failing) write: a message
            # parked behind a dead socket is already covered by the
            # next rewind's replay
            self._buf.append((seq, msg))
            if FAULTS.active and FAULTS.hit(
                    "dcn_drop", port=self.port,
                    worker=WORKER_ID) is not None:
                # sever this leg mid-epoch: the write path below sees a
                # closed socket, parks, and waits for the recovery
                # rewind — exactly a mid-flight DCN cable pull
                try:
                    self._writer.close()
                except Exception:  # noqa: BLE001
                    pass
        while True:
            if self._dead or self._rewinding:
                if self._buf is None:
                    raise ConnectionResetError("remote receiver is gone")
                # replay mode: park until rewind_replay re-establishes
                # the leg (recovery teardown cancels parked sends)
                self._credit_evt.clear()
                await self._credit_evt.wait()
                continue
            if seq is not None and seq <= self._sent_through:
                return        # a rewind already wrote this message
            try:
                await self._write_msg(msg)
                if seq is not None:
                    self._sent_through = seq
                return
            except (ConnectionResetError, BrokenPipeError, OSError):
                self._dead = True
                if self._buf is None:
                    raise

    async def rewind_replay(self, host=None, port=None) -> int:
        """Per-worker partial recovery: re-feed the uncommitted suffix
        to a REBUILT consumer. With host/port the leg reconnects (the
        consumer was re-placed onto a fresh RemoteInput server —
        possibly loopback); without, a dead socket reconnects to the
        SAME endpoint (severed leg, consumer rebuilt in place behind
        its surviving server) and a live socket is reused in-band. The
        'R' frame carries the committed base barrier (the consumer
        synthesizes the INITIAL from it and discards everything queued
        before it), then the buffered suffix follows, then `send`
        resumes live. Returns the number of replayed messages."""
        assert self._buf is not None, "replay not enabled on this leg"
        self._rewinding = True      # live sends park until the suffix
        try:                        # has streamed in order
            await self._stop_credit_loop()
            if host is not None or self._dead:
                try:
                    self._writer.close()
                except Exception:  # noqa: BLE001
                    pass
                if host is not None:
                    self.host, self.port = host, port
                self._reader, self._writer = await asyncio.open_connection(
                    self.host, self.port)
            self._credits = 0
            self._dead = False
            self._credit_task = asyncio.create_task(self._credit_loop())
            base = self._base_barrier
            await _write_frame(self._writer, b"R", json.dumps(
                {"curr": base.epoch.curr, "prev": base.epoch.prev,
                 "inject_ns": base.inject_time_ns}
                if base is not None else {}).encode())
            n = 0
            for seq, msg in list(self._buf):
                await self._write_msg(msg)
                self._sent_through = max(self._sent_through, seq)
                n += 1
            return n
        finally:
            self._rewinding = False
            # wake any send parked across the rewind: either its
            # message was covered by the replay, or the leg is live
            # again and it writes in order behind the suffix
            self._credit_evt.set()

    async def _stop_credit_loop(self) -> None:
        """Cancel the credit loop AND wait for it: the caller goes on
        to start a new loop on the same reader (rewind) or to drop the
        leg (close), and the old loop must be off it by then."""
        t, self._credit_task = self._credit_task, None
        if t is not None:
            t.cancel()
            await asyncio.gather(t, return_exceptions=True)

    async def close(self) -> None:
        await self._stop_credit_loop()
        if self._writer:
            # graceful: the frames already written (the stop barrier)
            # still flush; nothing here waits for the peer
            self._writer.close()


class RemoteInput(Executor):
    """Receiver half: a TCP server feeding this executor's stream
    (exchange_service.rs GetStream). Grants credits as the consumer
    drains — the bounded in-flight window IS the backpressure."""

    def __init__(self, schema: Schema, host: str = "127.0.0.1",
                 port: int = 0, capacity: int = 1024,
                 queue_depth: int = 16, stop_on=None):
        self.schema = schema
        self.pk_indices = ()
        self.host = host
        self.port = port
        self.capacity = capacity
        self.queue_depth = queue_depth
        self.stop_on = stop_on
        self.identity = "RemoteInput"
        self._queue: asyncio.Queue = asyncio.Queue()
        self._server = None
        self._conn_writer = None
        self._handlers: set = set()     # live connection-handler tasks
        # per-worker partial recovery: a rebuilt consumer reading a
        # SURVIVING server arms this flag — everything queued before
        # the producer's 'R' rewind frame belongs to the dead
        # incarnation and is discarded at recv
        self._await_rewind = False
        # barriers of the DROPPED epochs (committed < curr <= ceiling)
        # are filtered: a rebuilt source peer joins the live stream
        # directly, so replaying dead barriers on this leg would leave
        # merges misaligned forever (see Channel.begin_replay)
        self.stale_ceiling = None

    def expect_rewind(self, stale_ceiling=None) -> None:
        self._await_rewind = True
        if stale_ceiling is not None:
            self.stale_ceiling = stale_ceiling

    async def start(self) -> "RemoteInput":
        async def handle(reader, writer):
            me = asyncio.current_task()
            self._handlers.add(me)
            try:
                if self._conn_writer is not None \
                        or not self._server.is_serving():
                    # one producer per input (fan-in uses one
                    # RemoteInput per upstream edge) — a second LIVE
                    # connection would steal the credit channel and
                    # deadlock the first sender; a dead producer's slot
                    # frees below so a rewound or re-placed producer
                    # can re-attach. A connection accepted just before
                    # stop() starts its handler after it: turned away
                    return
                self._conn_writer = writer
                # initial credit window
                await _write_frame(writer, b"K",
                                   struct.pack("!I", self.queue_depth))
                while True:
                    tag, payload = await _read_frame(reader)
                    if tag == b"R":
                        # rewind: grant a fresh window HERE (the read
                        # loop), so the producer's replayed chunks flow
                        # before the rebuilt consumer even spawns
                        await _write_frame(
                            writer, b"K",
                            struct.pack("!I", self.queue_depth))
                    await self._queue.put((tag, payload))
            except (asyncio.IncompleteReadError, ConnectionResetError,
                    OSError):
                await self._queue.put((b"X", b""))
            finally:
                # the handler owns the writer it was given, however it
                # ends (peer gone, stop(), loop shutdown): asyncio
                # closes the transport of a handler that RETURNS for
                # nobody, and Server.wait_closed() (3.12+) waits for
                # every transport. abort, not close: this writer only
                # ever carries credit grants, which no one wants now,
                # and abort needs no flush to a peer that may not read
                writer.transport.abort()
                if self._conn_writer is writer:
                    self._conn_writer = None
                self._handlers.discard(me)

        self._server = await asyncio.start_server(handle, self.host,
                                                  self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        return self

    async def stop(self) -> None:
        """Stop listening, end the connection handlers, and wait until
        the loop has let go of every socket — in that order, with no
        help from the peer: each handler drops its own connection."""
        if self._server is None:
            return
        self._server.close()
        handlers = list(self._handlers)
        for t in handlers:
            t.cancel()
        await asyncio.gather(*handlers, return_exceptions=True)
        await self._server.wait_closed()

    async def recv(self):
        """Channel-compatible receive — the cluster partial build
        (plan/build.py build_partial_graph) wires a RemoteInput as ONE
        LEG of a ChannelInput/MergeExecutor, next to local channels.
        Identical decode to execute(); a vanished peer raises (the
        actor's failure report is the cluster's failure detector)."""
        from ..common.types import DataType
        while True:
            tag, payload = await self._queue.get()
            if self._await_rewind and tag != b"R":
                # rebuilt consumer on a surviving server: everything
                # queued before the producer's rewind frame belongs to
                # the dead incarnation (incl. its X disconnect marker)
                continue
            if tag == b"R":
                self._await_rewind = False
                d = json.loads(payload)
                if not d:
                    continue    # no committed base: the suffix is whole
                return Barrier(EpochPair(d["curr"], d["prev"]),
                               BarrierKind.INITIAL, None, (),
                               d.get("inject_ns", 0))
            if tag == b"X":
                raise ConnectionResetError(
                    "remote exchange producer went away")
            if tag == b"C":
                chunk = _payload_chunk(payload, self.schema, self.capacity)
                if self._conn_writer is not None:
                    try:
                        await _write_frame(self._conn_writer, b"K",
                                           struct.pack("!I", 1))
                    except (ConnectionResetError, BrokenPipeError, OSError):
                        self._conn_writer = None
                return chunk
            if tag == b"B":
                d = json.loads(payload)
                if self.stale_ceiling is not None \
                        and d["curr"] <= self.stale_ceiling \
                        and BarrierKind(d["kind"]) \
                        is not BarrierKind.INITIAL:
                    # a dead epoch's barrier (see above) — but never
                    # the INITIAL a rebuilt producer propagates at the
                    # committed base (it necessarily sits below the
                    # ceiling, and the consumer's chain initializes on
                    # it before any recomputed chunk)
                    continue
                return Barrier(EpochPair(d["curr"], d["prev"]),
                               BarrierKind(d["kind"]),
                               mutation=_de_mutation(d["mutation"]))
            if tag == b"W":
                d = json.loads(payload)
                return Watermark(d["col_idx"], DataType[d["dtype"]],
                                 d["val"])

    async def execute(self):
        from ..common.types import DataType
        while True:
            tag, payload = await self._queue.get()
            if tag == b"R":
                continue      # rewinds are a recv()-path (cluster) affair
            if tag == b"X":
                return
            if tag == b"C":
                chunk = _payload_chunk(payload, self.schema,
                                       self.capacity)
                yield chunk
                # grant the credit back once the chunk is in the pipeline
                # (the peer may already be gone after its stop barrier)
                if self._conn_writer is not None:
                    try:
                        await _write_frame(self._conn_writer, b"K",
                                           struct.pack("!I", 1))
                    except (ConnectionResetError, BrokenPipeError, OSError):
                        self._conn_writer = None
            elif tag == b"B":
                d = json.loads(payload)
                b = Barrier(EpochPair(d["curr"], d["prev"]),
                            BarrierKind(d["kind"]),
                            mutation=_de_mutation(d["mutation"]))
                yield b
                if isinstance(b.mutation, StopMutation) and (
                        self.stop_on is None or self.stop_on(b)):
                    return
            elif tag == b"W":
                d = json.loads(payload)
                yield Watermark(d["col_idx"], DataType[d["dtype"]],
                                d["val"])
