"""Nexmark q1 through the public API: the minimum end-to-end slice.

  SELECT auction, bidder, 0.908 * price, date_time FROM bid

Builds source -> jitted project -> row-id gen -> materialize, runs N barrier
epochs with checkpoints, prints MV stats + barrier latency.

Run: python examples/nexmark_q1.py [num_barriers] [chunk_size]

Runs on the TPU. It refuses any other backend unless the caller asked
for the CPU explicitly (`JAX_PLATFORMS=cpu`), and says which it ran on.
"""

import asyncio
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.connectors import NexmarkGenerator
from risingwave_tpu.expr import call, col, lit
from risingwave_tpu.meta import BarrierCoordinator
from risingwave_tpu.state import MemoryStateStore, StateTable
from risingwave_tpu.stream import (
    Actor, MaterializeExecutor, ProjectExecutor, RowIdGenExecutor, SourceExecutor,
)


async def main(rounds: int = 5, chunk_size: int = 4096) -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu" and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        sys.exit(f"nexmark_q1: jax found no TPU (platform={dev.platform!r}); "
                 "set JAX_PLATFORMS=cpu to run on the CPU on purpose")
    print(f"running on {dev.platform}:{dev.device_kind} x{len(jax.devices())}")
    store = MemoryStateStore()
    barrier_q = asyncio.Queue()
    gen = NexmarkGenerator("bid", chunk_size=chunk_size)

    offsets = StateTable(store, 1, schema(("source_id", DataType.INT64),
                                          ("offset", DataType.INT64)), pk_indices=[0])
    src = SourceExecutor(1, gen, barrier_q, state_table=offsets)
    proj = ProjectExecutor(
        src,
        [col(0), col(1), call("multiply", col(2), lit(0.908)), col(5, DataType.TIMESTAMP)],
        names=["auction", "bidder", "price", "date_time"])
    rid = RowIdGenExecutor(proj)
    mv = StateTable(store, 2, rid.schema, pk_indices=rid.pk_indices)
    mat = MaterializeExecutor(rid, mv)

    coord = BarrierCoordinator(store)
    coord.register_source(barrier_q)
    coord.register_actor(1)
    task = Actor(1, mat, None, coord).spawn()

    t0 = time.perf_counter()
    await coord.run_rounds(rounds, interval_s=0.05)
    await coord.stop_all({1})
    await task
    dt = time.perf_counter() - t0

    n = sum(1 for _ in mv.iter_all())
    some = [r for _, r in zip(range(3), mv.iter_all())]
    print(f"rows materialized: {n} (source offset {gen.offset}) in {dt:.2f}s "
          f"-> {gen.offset / dt:,.0f} rows/s wall")
    print(f"sample rows (auction, bidder, price, date_time, _row_id):")
    for _, row in some:
        print("  ", row)
    print(f"barrier p50 latency: {coord.barrier_latency_percentile(0.5)*1e3:.2f} ms; "
          f"committed epochs: {len(coord.committed_epochs)}")
    off = offsets.get_row((1,))
    print(f"committed source offset: {off[1] if off else None}")
    assert n == gen.offset, "MV row count must equal generated events"


if __name__ == "__main__":
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 5
    chunk = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
    asyncio.run(main(rounds, chunk))
