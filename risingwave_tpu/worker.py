"""Worker — the compute-node process of the deployment.

Reference: the compute node role (compute/src/server.rs:86): it receives
plan fragments from the control plane, builds executors through the same
from_proto registry, and exchanges data with peers.

One listener serves TWO protocols, selected by the connection's first
frame:

  * legacy fragment offload (stream/remote_fragment.py): a pickled spec
    dict ships ONE Node subtree; the worker runs it as an identity-less
    proxied child and streams everything back — kept for v1 remote
    fragments (`SET streaming_fragment_worker`);
  * the cluster control plane (cluster/compute_node.py): the first frame
    is an RPC request (`hello`), after which this process is a
    FIRST-CLASS compute node — it registers with meta, builds and OWNS
    its assigned actors over vnode-partitioned fragments, runs a local
    barrier manager, seals + uploads its own state, and serves its own
    /metrics.

Run: python -m risingwave_tpu.worker [port] [--monitor-port N]
(port 0 = ephemeral; the chosen port prints as the first stdout line
for orchestration).
"""

from __future__ import annotations

import asyncio
import json
import os
import pickle
import struct
import sys


def _pin_jax_platform() -> None:
    """Honor JAX_PLATFORMS IN-PROCESS before any jax use.

    A chip belongs to ONE process: a parent that holds it must spawn
    its workers with JAX_PLATFORMS=cpu (or hand each worker a chip of
    its own). `jax.config.update` states the inherited choice
    explicitly, so nothing that touched jax.config at interpreter
    startup can point this worker at the parent's device."""
    plat = os.environ.get("JAX_PLATFORMS")
    if plat:
        import jax
        jax.config.update("jax_platforms", plat)


async def _recv_blob(reader) -> bytes:
    ln = struct.unpack("!i", await reader.readexactly(4))[0]
    return await reader.readexactly(ln)


async def _send_blob(writer, blob: bytes) -> None:
    writer.write(struct.pack("!i", len(blob)) + blob)
    await writer.drain()


class _StubCoord:
    """Builders never touch the coordinator; actors (which do) are not
    used in the worker — barriers ride the data stream."""

    def register_source(self, q) -> None:
        pass

    def register_actor(self, a) -> None:
        pass


_MONITOR_PORT = 0        # set by main(); workers have ONE listener


async def _handle(reader, writer) -> None:
    from .common.types import Schema  # noqa: F401  (pickle needs types)
    from .plan.build import BUILDERS, ActorCtx, BuildEnv
    from .plan.graph import Exchange
    from .state import MemoryStateStore
    from .stream.message import Barrier
    from .stream.remote_exchange import RemoteInput, RemoteOutput

    peer = writer.get_extra_info("peername")[0]
    try:
        spec = pickle.loads(await _recv_blob(reader))
    except (asyncio.IncompleteReadError, ConnectionResetError):
        writer.close()
        return
    if isinstance(spec, dict) and "method" in spec:
        # cluster control plane: this connection IS meta — promote the
        # process to a first-class compute node for its lifetime
        from .cluster.compute_node import serve_connection
        await serve_connection(reader, writer, spec,
                               monitor_port=_MONITOR_PORT)
        return
    ins = []
    for sch in spec["in_schemas"]:
        ins.append(await RemoteInput(sch, host="0.0.0.0",
                                     queue_depth=8).start())
    await _send_blob(writer, json.dumps(
        {"input_ports": [r.port for r in ins]}).encode())
    out = await RemoteOutput(peer, spec["out_port"]).connect()

    env = BuildEnv(MemoryStateStore(), _StubCoord())
    ctx = ActorCtx(env=env, fragment=None, actor_id=0, actor_idx=0,
                   vnode_bitmap=None, table_ids={})
    pending = list(ins)

    def build(n):
        if isinstance(n, Exchange):
            return pending.pop(0)     # pre-order = port assignment order
        inputs = [build(i) for i in n.inputs]
        args = dict(n.args)
        args["durable"] = False       # v1: remote fragments are volatile
        return BUILDERS[n.kind](args, inputs, ctx, id(n))

    chain = build(spec["node"])
    stop_id = spec.get("stop_actor_id")
    try:
        async for msg in chain.execute():
            await out.send(msg)
            if isinstance(msg, Barrier) and msg.mutation is not None \
                    and (msg.is_stop(stop_id) if stop_id is not None
                         else msg.is_stop_any()):
                break
    except (ConnectionResetError, asyncio.IncompleteReadError, OSError):
        pass            # main went away (crash/recovery): drop fragment
    finally:
        try:
            await out.close()
        except Exception:  # noqa: BLE001
            pass
        for r in ins:
            await r.stop()
        writer.close()


async def serve(port: int = 0, host: str = "127.0.0.1"):
    server = await asyncio.start_server(_handle, host, port)
    print(server.sockets[0].getsockname()[1], flush=True)
    async with server:
        await server.serve_forever()


def main(argv=None) -> None:
    global _MONITOR_PORT
    argv = sys.argv[1:] if argv is None else argv
    args = list(argv)
    if "--monitor-port" in args:
        i = args.index("--monitor-port")
        _MONITOR_PORT = int(args[i + 1])
        del args[i:i + 2]
    port = int(args[0]) if args else 0
    _pin_jax_platform()
    # cluster compute nodes compile the same per-shape programs the
    # coordinator does: share the persistent compilation cache so a
    # worker restarted by recovery starts hot
    from .utils.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    asyncio.run(serve(port))


if __name__ == "__main__":
    main()
