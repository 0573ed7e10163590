"""Microbenchmark SortedJoinExecutor's apply path in q7/q8 shapes.

Flat-out device throughput of the per-chunk program (probe + evict +
merge), no barriers, no host pipeline — the ceiling the bench configs
are sized against. No d2h transfers inside the timed loop (a blocking
fetch serialises with dispatch); one block_until_ready at the end.
"""

import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common import DataType
from risingwave_tpu.common.types import schema
from risingwave_tpu.connectors import NexmarkGenerator
from risingwave_tpu.connectors.nexmark import NexmarkConfig
from risingwave_tpu.expr import call, col, lit
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.stream.sorted_join import SortedJoinExecutor


class Dummy(Executor):
    def __init__(self, sch):
        self.schema = sch


def bench_q8_shape(chunk_size=131072, capacity=1 << 16, n_iter=60):
    cfg = NexmarkConfig(inter_event_us=100)
    W = 10_000_000
    gen_p = NexmarkGenerator("person", chunk_size=chunk_size, cfg=cfg)
    gen_a = NexmarkGenerator("auction", chunk_size=chunk_size, cfg=cfg)
    P2 = schema(("id", DataType.INT64), ("window_start", DataType.TIMESTAMP))
    A2 = schema(("seller", DataType.INT64), ("window_start", DataType.TIMESTAMP))
    join = SortedJoinExecutor(
        Dummy(P2), Dummy(A2),
        left_key_indices=[0, 1], right_key_indices=[0, 1],
        left_pk_indices=[0, 1], right_pk_indices=[0, 1],
        capacity=capacity, match_factor=2, output_indices=[0, 1],
        append_only=(True, True), clean_watermark_cols=(1, 1),
        watchdog_interval=None)

    proj_p = [col(0), call("tumble_start", col(6, DataType.TIMESTAMP), lit(W))]
    proj_a = [col(7), call("tumble_start", col(5, DataType.TIMESTAMP), lit(W))]

    def next2(gen, exprs, sch):
        c = gen.next_chunk()
        cols = tuple(e.eval(c.columns) for e in exprs)
        from risingwave_tpu.common.chunk import StreamChunk
        return StreamChunk(cols, c.ops, c.vis, sch)

    # warmup / compile
    cp = next2(gen_p, proj_p, P2)
    ca = next2(gen_a, proj_a, A2)
    wm = jnp.int64(0)
    out = join._apply(join.sides[0], join.sides[1], join._errs_dev, cp, wm, side=0)
    join.sides[0] = out[0]
    out = join._apply(join.sides[1], join.sides[0], join._errs_dev, ca, wm, side=1)
    jax.block_until_ready(out)

    t0 = time.perf_counter()
    rows = 0
    for i in range(n_iter):
        cp = next2(gen_p, proj_p, P2)
        wm_p = jnp.int64(gen_p.current_watermark() - W)
        (join.sides[0], od, _, _, vis0, join._errs_dev, _) = join._apply(
            join.sides[0], join.sides[1], join._errs_dev, cp, wm_p, side=0)
        ca = next2(gen_a, proj_a, A2)
        wm_a = jnp.int64(gen_a.current_watermark() - W)
        (join.sides[1], od, _, _, vis1, join._errs_dev, _) = join._apply(
            join.sides[1], join.sides[0], join._errs_dev, ca, wm_a, side=1)
        rows += 2 * chunk_size
    jax.block_until_ready(join.sides[1].n)
    dt = time.perf_counter() - t0
    errs = np.asarray(join._errs_dev)
    print(f"q8-shape: chunk={chunk_size} cap={capacity} "
          f"{rows/dt/1e6:8.1f}M rows/s   ({dt/ (2*n_iter) *1e3:.2f} ms/apply)  "
          f"errs={errs.tolist()}  n=({int(join.sides[0].n)},{int(join.sides[1].n)})")
    return rows / dt


def bench_q7_shape(chunk_size=131072, capacity=1 << 18, n_iter=60):
    cfg = NexmarkConfig(inter_event_us=250)
    W = 10_000_000
    gen = NexmarkGenerator("bid", chunk_size=chunk_size, cfg=cfg)
    BID4 = schema(("auction", DataType.INT64), ("bidder", DataType.INT64),
                  ("price", DataType.INT64), ("date_time", DataType.TIMESTAMP))
    AGG = schema(("window_end", DataType.TIMESTAMP), ("maxprice", DataType.INT64))
    join = SortedJoinExecutor(
        Dummy(BID4), Dummy(AGG),
        left_key_indices=[2], right_key_indices=[1],
        left_pk_indices=[0, 1, 2, 3], right_pk_indices=[0],
        capacity=capacity, match_factor=2,
        append_only=(True, False), clean_watermark_cols=(3, None),
        watchdog_interval=None)
    proj = [col(0), col(1), col(2), col(5, DataType.TIMESTAMP)]

    def next4():
        c = gen.next_chunk()
        cols = tuple(e.eval(c.columns) for e in proj)
        from risingwave_tpu.common.chunk import StreamChunk
        return StreamChunk(cols, c.ops, c.vis, BID4)

    cb = next4()
    wm = jnp.int64(0)
    out = join._apply(join.sides[0], join.sides[1], join._errs_dev, cb, wm, side=0)
    join.sides[0] = out[0]
    jax.block_until_ready(out)

    t0 = time.perf_counter()
    rows = 0
    for i in range(n_iter):
        cb = next4()
        wm_b = jnp.int64(gen.current_watermark() - 2 * W)
        (join.sides[0], od, _, _, vis0, join._errs_dev, _) = join._apply(
            join.sides[0], join.sides[1], join._errs_dev, cb, wm_b, side=0)
        rows += chunk_size
    jax.block_until_ready(join.sides[0].n)
    dt = time.perf_counter() - t0
    errs = np.asarray(join._errs_dev)
    print(f"q7-shape: chunk={chunk_size} cap={capacity} "
          f"{rows/dt/1e6:8.1f}M rows/s   ({dt/n_iter*1e3:.2f} ms/apply)  "
          f"errs={errs.tolist()}  n_left={int(join.sides[0].n)}")
    return rows / dt


if __name__ == "__main__":
    print("devices:", jax.devices())
    for cs in (65536, 131072, 262144):
        bench_q8_shape(chunk_size=cs)
    for cs in (65536, 131072, 262144):
        bench_q7_shape(chunk_size=cs)
