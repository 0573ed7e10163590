"""Remote exchange (VERDICT r3 missing #5 — the DCN tier): Arrow-IPC
chunks + barrier/watermark frames over real TCP with credit-based
backpressure, including a TRUE multi-process pipeline.

Reference: exchange/input.rs RemoteInput, exchange_service.rs GetStream,
proto/task_service.proto permits.
"""

import asyncio
import os
import subprocess
import sys
from collections import Counter

import numpy as np

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import OP_DELETE, OP_INSERT, StreamChunk
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.stream import Barrier, BarrierKind, Watermark
from risingwave_tpu.stream.message import StopMutation
from risingwave_tpu.stream.remote_exchange import RemoteInput, RemoteOutput

SCH = schema(("k", DataType.INT64), ("v", DataType.INT64),
             ("s", DataType.VARCHAR))


async def test_loopback_chunks_barriers_watermarks_credits():
    from risingwave_tpu.common.types import GLOBAL_DICT
    rx = await RemoteInput(SCH, queue_depth=2).start()
    tx = await RemoteOutput("127.0.0.1", rx.port, credits=0).connect()

    sid = GLOBAL_DICT.get_or_insert("hello")

    async def produce():
        await tx.send(Barrier(EpochPair(1, 0), BarrierKind.INITIAL))
        for ep in range(2, 8):
            rows = [(OP_INSERT, i, i * 10, sid) for i in range(ep * 4)]
            ops = np.asarray([r[0] for r in rows], dtype=np.int8)
            cols = [np.asarray([r[1] for r in rows]),
                    np.asarray([r[2] for r in rows]),
                    np.asarray([r[3] for r in rows], dtype=np.int32)]
            await tx.send(StreamChunk.from_numpy(SCH, cols, ops=ops,
                                                 capacity=64))
            await tx.send(Watermark(0, DataType.INT64, ep * 100))
            await tx.send(Barrier(EpochPair(ep, ep - 1)))
        await tx.send(Barrier(EpochPair(8, 7), BarrierKind.CHECKPOINT,
                              mutation=StopMutation(frozenset({1}))))

    prod = asyncio.create_task(produce())
    rows, wms, barriers = [], [], 0
    async for msg in rx.execute():
        if isinstance(msg, StreamChunk):
            rows.extend(msg.to_rows())
        elif isinstance(msg, Watermark):
            wms.append(msg.val)
        else:
            barriers += 1
    await prod
    await tx.close()
    await rx.stop()

    # VARCHAR round-trips through the Arrow dictionary back to an id that
    # DECODES to the same string (ids themselves are stable here because
    # both ends share this process's GLOBAL_DICT)
    from risingwave_tpu.common.types import GLOBAL_DICT as GD
    exp = [(0, (i, i * 10, "hello"))
           for ep in range(2, 8) for i in range(ep * 4)]
    decoded = [(op, (k, v, GD.decode(s))) for op, (k, v, s) in rows]
    assert decoded == exp, f"{len(rows)} vs {len(exp)} rows"
    assert wms == [ep * 100 for ep in range(2, 8)]
    assert barriers == 8


_CHILD = r"""
import asyncio, sys, os
sys.path.insert(0, os.getcwd())
os.environ["JAX_PLATFORMS"] = "cpu"
# A child of a jax-holding parent must never take the parent's device:
# state the CPU platform in-process (jax.config.update) as well as by
# environment, before any jax-using import.
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.connectors import NexmarkGenerator
from risingwave_tpu.stream import Barrier, BarrierKind
from risingwave_tpu.stream.message import StopMutation
from risingwave_tpu.stream.remote_exchange import RemoteOutput

async def main(port):
    tx = await RemoteOutput("127.0.0.1", port, credits=0).connect()
    gen = NexmarkGenerator("bid", chunk_size=256)
    await tx.send(Barrier(EpochPair(1, 0), BarrierKind.INITIAL))
    for ep in range(2, 6):
        await tx.send(gen.next_chunk())
        await tx.send(Barrier(EpochPair(ep, ep - 1)))
    await tx.send(Barrier(EpochPair(6, 5), BarrierKind.CHECKPOINT,
                          mutation=StopMutation(frozenset({1}))))
    await tx.close()

asyncio.run(main(int(sys.argv[1])))
"""


async def test_multiprocess_pipeline():
    """A producer in ANOTHER OS PROCESS streams nexmark chunks over TCP;
    this process filters them — the multi-host fragment-edge shape."""
    from risingwave_tpu.connectors.nexmark import BID_SCHEMA
    from risingwave_tpu.expr import call, col, lit
    from risingwave_tpu.stream import FilterExecutor

    rx = await RemoteInput(BID_SCHEMA, queue_depth=2,
                           capacity=256).start()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    import pathlib
    repo_root = str(pathlib.Path(__file__).resolve().parents[1])
    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD, str(rx.port)],
        cwd=repo_root, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)

    filt = FilterExecutor(rx, call("greater_than", col(2),
                                   lit(5_000_000)))
    got = Counter()

    async def consume():
        async for msg in filt.execute():
            if isinstance(msg, StreamChunk):
                for _, vals in msg.to_rows():
                    got[(vals[0], vals[2])] += 1

    # hard deadline: a child with a sick device (or a platform pin that
    # didn't take) never sends its stop barrier — fail the test with the
    # child's stderr instead of hanging the suite forever
    try:
        await asyncio.wait_for(consume(), timeout=120)
    except asyncio.TimeoutError:
        child.kill()
        err = child.stderr.read().decode()[-500:]
        raise AssertionError(
            f"producer subprocess never finished (device stall?): {err}")
    finally:
        await rx.stop()
    rc = child.wait(timeout=60)
    assert rc == 0, child.stderr.read().decode()[-500:]

    gen_rows = 4 * 256
    from risingwave_tpu.connectors import NexmarkGenerator
    g = NexmarkGenerator("bid", chunk_size=gen_rows)
    c = g.next_chunk()
    auction = np.asarray(c.columns[0].data)[:gen_rows]
    price = np.asarray(c.columns[2].data)[:gen_rows]
    keep = price > 5_000_000
    exp = Counter(zip(auction[keep].tolist(), price[keep].tolist()))
    assert got == exp
    assert got, "oracle vacuous"


async def test_concurrent_rewind_preserves_per_leg_frame_order():
    """Phase-3 parallel rewind (cluster partial recovery, meta's
    partial_rewind): several surviving producer legs stream their
    uncommitted suffixes CONCURRENTLY instead of serially — each leg is
    an independent ordered stream drained by exactly one task, so the
    consumer must still see the 'R' base frame first and then the
    buffered suffix in exact send order on every leg."""
    import json

    legs = []
    for li in range(3):
        rx = await RemoteInput(SCH, queue_depth=2).start()
        tx = await RemoteOutput("127.0.0.1", rx.port,
                                replay=True).connect()
        legs.append((rx, tx))
    # a distinct suffix per leg: barrier epochs carry the leg id so an
    # interleaving across legs could never masquerade as correct order
    for li, (_rx, tx) in enumerate(legs):
        await tx.send(Barrier(EpochPair(1, 0), BarrierKind.INITIAL))
        for ep in range(2, 10):
            await tx.send(Barrier(EpochPair(1000 * li + ep,
                                            1000 * li + ep - 1)))
    # nothing committed => the whole stream is the replay suffix; rewind
    # all legs at once, exactly like the parallel phase 3
    counts = await asyncio.gather(
        *(tx.rewind_replay() for _rx, tx in legs))
    assert counts == [9, 9, 9]
    for li, (rx, tx) in enumerate(legs):
        seen_r = False
        epochs_after_r = []
        # wait for the frames (they cross a real socket): the 'R' and
        # the nine barriers behind it; a lost frame runs into the
        # harness's per-test limit
        while not seen_r or len(epochs_after_r) < 9:
            tag, payload = await rx._queue.get()
            if tag == b"R":
                seen_r = True
                epochs_after_r = []
            elif tag == b"B" and seen_r:
                epochs_after_r.append(json.loads(payload)["curr"])
        assert seen_r, f"leg {li}: no rewind frame"
        expected = [1] + [1000 * li + ep for ep in range(2, 10)]
        assert epochs_after_r == expected, (li, epochs_after_r)
        await tx.close()
        await rx.stop()
