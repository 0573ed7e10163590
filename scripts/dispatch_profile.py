"""Dispatch/recompile micro-harness — q7-shaped pipeline, no TPU needed.

Prints device dispatches per barrier interval and recompiles after
warmup for a canned windowed-agg + join pipeline fed many SMALL chunks
per interval, in two modes:

  baseline   per-chunk applies (chunk batching off, no coalescing)
  optimized  ChunkCoalescer packs the runs + hash_agg scans multiple
             chunks per dispatch (the join applies each packed chunk)

The counters come from ops/jit_state.py (every jitted step program in the
engine routes through it), so the numbers cover the WHOLE chain, not a
single executor. Future PRs run this on the CPU backend to spot dispatch
regressions without a TPU:

    JAX_PLATFORMS=cpu python scripts/dispatch_profile.py

Exit status is 0 iff the optimized mode both reduces dispatches per
interval and performs zero recompiles after warmup.
"""

import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent XLA compilation cache (utils/compile_cache.py): the
# gate re-runs a canned shape every CI round — repeat runs skip the
# compile entirely
from risingwave_tpu.utils.compile_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


N_INTERVALS = 8
WARMUP_INTERVALS = 3
CHUNKS_PER_INTERVAL = 6
CHUNK_CAP = 256          # deliberately small: the dispatch-bound regime
WINDOW = 1 << 10


def _metrics():
    from risingwave_tpu.utils.metrics import GLOBAL_METRICS
    snap = GLOBAL_METRICS.snapshot()

    def total(name):
        return sum(e["value"] for e in snap.get(name, [])
                   if not e["labels"])

    return total("device_dispatch_count"), total("jit_compile_count")


def _bid_schema():
    from risingwave_tpu.common import DataType, schema
    return schema(("auction", DataType.INT64), ("price", DataType.INT64),
                  ("ts", DataType.INT64))


def _chunks(epoch: int, rng) -> list:
    """One interval's worth of small bid-shaped chunks, varying
    cardinality (and therefore visibility masks) per chunk."""
    from risingwave_tpu.common.chunk import StreamChunk
    sch = _bid_schema()
    out = []
    base_ts = epoch * WINDOW * 4
    for i in range(CHUNKS_PER_INTERVAL):
        n = int(rng.randint(CHUNK_CAP // 4, CHUNK_CAP))
        auction = rng.randint(0, 50, size=n).astype(np.int64)
        price = rng.randint(1, 2_000, size=n).astype(np.int64)
        ts = (base_ts + rng.randint(0, WINDOW * 4, size=n)).astype(np.int64)
        out.append(StreamChunk.from_numpy(
            sch, [auction, price, ts], capacity=CHUNK_CAP))
    return out


class _Script:
    """Async source over a scripted message list."""

    def __init__(self, sch, messages):
        self.schema = sch
        self.messages = messages
        self.identity = "DispatchProfileSource"
        self.pk_indices = ()

    def fence_tokens(self):
        return []

    async def execute(self):
        for m in self.messages:
            yield m
            await asyncio.sleep(0)


def _script_messages(seed: int) -> list:
    from risingwave_tpu.common.epoch import EpochPair
    from risingwave_tpu.stream.message import Barrier, BarrierKind
    rng = np.random.RandomState(seed)
    msgs = [Barrier(EpochPair(1, 0), BarrierKind.INITIAL)]
    for e in range(2, 2 + N_INTERVALS):
        msgs.extend(_chunks(e, rng))
        msgs.append(Barrier(EpochPair(e, e - 1)))
    return msgs


def _coalesce_messages(msgs, max_capacity):
    """Receiver-side packing, exactly what ChannelInput/Merge do with
    SET streaming_chunk_coalesce (stream/exchange.py)."""
    from risingwave_tpu.common.chunk import ChunkCoalescer, StreamChunk
    co = ChunkCoalescer(max_capacity)
    out = []
    for m in msgs:
        if isinstance(m, StreamChunk):
            out.extend(co.push(m))
        else:
            out.extend(co.flush())
            out.append(m)
    return out


async def _run_pipeline(optimized: bool) -> dict:
    """q7 shape: bids -> window max agg; agg output JOINed back against
    the bid stream on price (sorted join) -> counted sink."""
    from risingwave_tpu.common.chunk import StreamChunk
    from risingwave_tpu.expr.agg import AggCall, AggKind
    from risingwave_tpu.stream import HashAggExecutor
    from risingwave_tpu.stream.sorted_join import SortedJoinExecutor
    from risingwave_tpu.stream.message import Barrier
    from risingwave_tpu.stream.project import ProjectExecutor
    from risingwave_tpu.expr import call, col, lit

    sch = _bid_schema()
    left_msgs = _script_messages(seed=7)
    right_msgs = _script_messages(seed=7)
    if optimized:
        left_msgs = _coalesce_messages(left_msgs, 8 * CHUNK_CAP)
        right_msgs = _coalesce_messages(right_msgs, 8 * CHUNK_CAP)

    # window_end = ts - ts % W + W, projected in front of the agg
    win = call("add", call("subtract", col(2),
                           call("modulus", col(2), lit(WINDOW))),
               lit(WINDOW))
    proj = ProjectExecutor(_Script(sch, right_msgs),
                           [col(0), col(1), win])
    agg = HashAggExecutor(
        proj, [2], [AggCall(AggKind.MAX, 1, sch[1].data_type,
                            append_only=True)],
        capacity=1 << 12)
    join = SortedJoinExecutor(
        _Script(sch, left_msgs), agg,
        left_key_indices=[1], right_key_indices=[1],
        left_pk_indices=[0, 2], right_pk_indices=[0],
        capacity=1 << 14, match_factor=64)
    if not optimized:
        agg._use_chunk_batching = False

    d0, c0 = _metrics()
    warm_d = warm_c = None
    intervals = 0
    rows = 0
    async for msg in join.execute():
        if isinstance(msg, StreamChunk):
            rows += int(np.asarray(msg.vis).sum())
        elif isinstance(msg, Barrier):
            intervals += 1
            if intervals == WARMUP_INTERVALS + 1:   # +1 = INITIAL barrier
                warm_d, warm_c = _metrics()
    d1, c1 = _metrics()
    steady_intervals = intervals - (WARMUP_INTERVALS + 1)
    return {
        "mode": "optimized" if optimized else "baseline",
        "intervals": intervals - 1,
        "chunks_per_interval": CHUNKS_PER_INTERVAL,
        "join_rows": rows,
        "dispatches_total": d1 - d0,
        "dispatches_per_interval_steady": round(
            (d1 - warm_d) / max(steady_intervals, 1), 2),
        "recompiles_after_warmup": c1 - warm_c,
        "compiles_total": c1 - c0,
    }


async def main() -> int:
    base = await _run_pipeline(optimized=False)
    opt = await _run_pipeline(optimized=True)
    verdict = {
        "dispatch_reduction": round(
            base["dispatches_per_interval_steady"]
            / max(opt["dispatches_per_interval_steady"], 1e-9), 2),
        "zero_recompiles_after_warmup":
            opt["recompiles_after_warmup"] == 0,
        "rows_match": base["join_rows"] == opt["join_rows"],
    }
    print(json.dumps(base))
    print(json.dumps(opt))
    print(json.dumps({"verdict": verdict}))
    ok = (opt["dispatches_per_interval_steady"]
          < base["dispatches_per_interval_steady"]
          and verdict["zero_recompiles_after_warmup"]
          and verdict["rows_match"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
