"""Planner-integrated remote fragment placement (VERDICT r4 #6).

Reference: a compute node serving a fragment of another job's graph —
meta ships `StreamNode` protobufs to CNs (proto/stream_plan.proto:730,
stream_manager.rs:253) and fragment edges cross nodes through the
exchange service (exchange_service.rs:78). Here the main process ships
the fragment's Node subtree to a worker process (risingwave_tpu.worker)
over a control socket — the v1 IR wire format is a pickle of the plan
dataclasses between TRUSTED processes of one deployment, standing in
for the reference's protobuf — and the data plane is the existing DCN
tier (stream/remote_exchange.py: Arrow-IPC chunks, barrier frames,
credit backpressure).

Topology per remote fragment (all lazy, set up on first execute()):

    main upstream actors ──channel──> pump ──RemoteOutput──> worker in
    worker: [RemoteInput...] -> fragment executors -> RemoteOutput
    main: RemoteInput -> THIS executor -> normal Actor + dispatcher

Barriers flow through the worker and back, so the main-side Actor
collects each barrier only after the remote fragment processed it —
alignment and pacing work unchanged. v1 constraint: the remote
fragment runs VOLATILE (the planner requires streaming_durability = 0),
so recovery replays sources from offset 0 and the materialize upsert
converges the MV (the reference instead re-binds durable state to the
surviving CN set).
"""

from __future__ import annotations

import asyncio
import json
import pickle
import struct
from contextlib import aclosing
from typing import Sequence

from ..common.types import Schema
from .executor import Executor
from .message import Barrier
from .remote_exchange import RemoteInput, RemoteOutput


async def _send_blob(writer, blob: bytes) -> None:
    writer.write(struct.pack("!i", len(blob)) + blob)
    await writer.drain()


async def _recv_blob(reader) -> bytes:
    ln = struct.unpack("!i", await reader.readexactly(4))[0]
    return await reader.readexactly(ln)


class RemoteFragmentExecutor(Executor):
    """Main-process stand-in for a fragment running in a worker."""

    def __init__(self, worker_addr: str, node, in_channels: Sequence,
                 in_schemas: Sequence[Schema], out_schema: Schema,
                 pk_indices=(), actor_id: int = 0):
        self.worker_addr = worker_addr
        self.node = node
        self.in_channels = list(in_channels)
        self.in_schemas = list(in_schemas)
        self.schema = out_schema
        self.pk_indices = tuple(pk_indices)
        self.actor_id = actor_id
        self.identity = f"RemoteFragment({worker_addr}, {node.kind})"

    def fence_tokens(self) -> list:
        return []      # device state lives in the worker process

    async def _pump(self, chan, out: RemoteOutput) -> None:
        while True:
            msg = await chan.recv()
            await out.send(msg)
            # only OUR OWN stop ends the pump: a shared coordinator
            # routes other deployments' stop barriers through every
            # pipeline (same contract as the local build's stop_on)
            if isinstance(msg, Barrier) and msg.mutation is not None \
                    and msg.is_stop(self.actor_id):
                return

    async def execute(self):
        host, _, port = self.worker_addr.partition(":")
        reader, writer = await asyncio.open_connection(host, int(port))
        # bind all interfaces: the worker may live on another host and
        # connects back to us at the address it sees on the control
        # socket (the DCN tier is cross-host by design)
        rx = await RemoteInput(self.schema, host="0.0.0.0",
                               queue_depth=8).start()
        outs, pumps = [], []
        try:
            spec = pickle.dumps({
                "node": self.node,
                "in_schemas": self.in_schemas,
                "out_schema": self.schema,
                "out_port": rx.port,
                "stop_actor_id": self.actor_id,
            })
            await _send_blob(writer, spec)
            reply = json.loads(await _recv_blob(reader))
            for p in reply["input_ports"]:
                outs.append(await RemoteOutput(host, p).connect())
            pumps.extend(asyncio.create_task(self._pump(c, o))
                         for c, o in zip(self.in_channels, outs))
            async with aclosing(rx.execute()) as msgs:
                async for msg in msgs:
                    yield msg
                    if isinstance(msg, Barrier) \
                            and msg.mutation is not None \
                            and msg.is_stop(self.actor_id):
                        break
        finally:
            # the one close order, needing nothing from the worker: end
            # our pumps, drop our sending legs (credit loops awaited),
            # stop the server with its connection, drop the control
            # socket. The actor closes this generator when it returns
            # on the stop barrier (stream/actor.py), so this runs then
            for t in pumps:
                t.cancel()
            await asyncio.gather(*pumps, return_exceptions=True)
            for o in outs:
                await o.close()
            await rx.stop()
            writer.close()
