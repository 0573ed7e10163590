"""Checkpoint-pipeline micro-harness — q7-shaped durable run, no TPU.

Sibling of dispatch_profile.py: a canned tumble-window MAX(price) agg over
nexmark bids (the q7 window side) runs DURABLY against a Hummock store
whose object-store uploads are artificially slowed (the stand-in for a
remote object store), in two modes:

  inline     checkpoint_max_inflight=0 — store.sync() on the barrier
             path, every checkpoint stalls the stream for build+upload
  pipelined  checkpoint_max_inflight=2 — barriers complete at seal; the
             background uploader builds/uploads/commits behind the stream

Prints barrier p50 (inject -> collected) for both modes and exits
non-zero unless BOTH hold:

  * the pipelined barrier p50 is STRICTLY below the inline one (i.e. the
    SST build/upload cost left the barrier critical path), and
  * committed_epoch ordering was never violated (manifest swaps strictly
    in epoch order, store committed epoch == last committed).

CI usage (CPU backend):

    JAX_PLATFORMS=cpu python scripts/checkpoint_profile.py
"""

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent XLA compilation cache (utils/compile_cache.py): the
# gate re-runs a canned shape every CI round — repeat runs skip the
# compile entirely
from risingwave_tpu.utils.compile_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()
os.environ.setdefault("JAX_PLATFORMS", "cpu")


UPLOAD_DELAY_S = 0.04     # simulated object-store PUT latency per SST
WARMUP_ROUNDS = 2
MEASURE_ROUNDS = 10
WINDOW_US = 1_000_000


class SlowObjectStore:
    """In-memory object store with a fixed per-SST upload delay — the
    canned stand-in for a remote object store.
    Manifest swaps stay fast (they are one small PUT in production too)."""

    def __init__(self, inner, delay_s: float):
        self._inner = inner
        self.delay_s = delay_s

    def upload(self, name, data):
        if name.startswith("ssts/"):
            time.sleep(self.delay_s)
        return self._inner.upload(name, data)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _build_pipeline(store):
    """q7's window side: bid -> project(window_end) -> MAX(price) by
    window_end -> materialize, all durable on `store`."""
    from risingwave_tpu.common import DataType, schema
    from risingwave_tpu.connectors import NexmarkGenerator
    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    from risingwave_tpu.expr import call, col, lit
    from risingwave_tpu.expr.agg import agg_max
    from risingwave_tpu.state import StateTable
    from risingwave_tpu.stream import (
        HashAggExecutor, MaterializeExecutor, SourceExecutor,
    )
    from risingwave_tpu.stream.project import ProjectExecutor

    barrier_q = asyncio.Queue()
    gen = NexmarkGenerator("bid", chunk_size=256,
                           cfg=NexmarkConfig(inter_event_us=10_000))
    offsets = StateTable(
        store, table_id=1,
        schema=schema(("source_id", DataType.INT64),
                      ("offset", DataType.INT64)),
        pk_indices=[0])
    src = SourceExecutor(1, gen, barrier_q, state_table=offsets)
    # window_end = ts - ts % W + W (the TUMBLE the q7 planner emits)
    win = call("add", call("subtract", col(5),
                           call("modulus", col(5), lit(WINDOW_US))),
               lit(WINDOW_US))
    proj = ProjectExecutor(src, [col(0), col(2), win],
                           names=["auction", "price", "window_end"])
    agg_table = StateTable(
        store, table_id=2,
        schema=schema(("window_end", DataType.INT64),
                      ("maxprice", DataType.INT64),
                      ("_row_count", DataType.INT64)),
        pk_indices=[0])
    agg = HashAggExecutor(
        proj, group_key_indices=[2],
        agg_calls=[agg_max(1, append_only=True)],
        capacity=1 << 12, state_table=agg_table)
    mv = StateTable(store, table_id=3, schema=agg.schema,
                    pk_indices=list(agg.pk_indices))
    mat = MaterializeExecutor(agg, mv)
    return barrier_q, gen, mat


async def _run_mode(max_inflight: int) -> dict:
    from risingwave_tpu.meta import BarrierCoordinator
    from risingwave_tpu.state.hummock import HummockStateStore
    from risingwave_tpu.state.object_store import InMemObjectStore
    from risingwave_tpu.stream import Actor

    store = HummockStateStore(
        SlowObjectStore(InMemObjectStore(), UPLOAD_DELAY_S))
    barrier_q, gen, mat = _build_pipeline(store)
    coord = BarrierCoordinator(store, checkpoint_max_inflight=max_inflight)
    coord.register_source(barrier_q)
    coord.register_actor(1)
    task = Actor(1, mat, None, coord).spawn()

    await coord.run_rounds(WARMUP_ROUNDS)
    n_warm = len(coord.latencies_ns)
    for _ in range(MEASURE_ROUNDS):
        await asyncio.sleep(0.005)
        b = await coord.inject_barrier()
        await coord.wait_collected(b)
    measured = sorted(coord.latencies_ns[n_warm:])
    p50_s = measured[len(measured) // 2] / 1e9
    await coord.stop_all({1})
    await task

    # ---- ordering gates: manifest swaps strictly in epoch order ----
    commits = coord.committed_epochs
    ordered = all(a < b for a, b in zip(commits, commits[1:]))
    all_committed = (store.committed_epoch() == commits[-1]
                     if commits else False)
    no_leftover = not store._sealed
    return {
        "mode": "pipelined" if max_inflight else "inline",
        "checkpoint_max_inflight": max_inflight,
        "rounds": MEASURE_ROUNDS,
        "barrier_p50_s": round(p50_s, 4),
        "rows": gen.offset,
        "committed_epochs": len(commits),
        "commit_order_ok": bool(ordered and all_committed and no_leftover),
        "upload_overlap_pct": coord.upload_overlap_pct(),
    }


async def main() -> int:
    inline = await _run_mode(0)
    pipelined = await _run_mode(2)
    verdict = {
        "barrier_p50_speedup": round(
            inline["barrier_p50_s"]
            / max(pipelined["barrier_p50_s"], 1e-9), 2),
        "pipelined_strictly_below_inline":
            pipelined["barrier_p50_s"] < inline["barrier_p50_s"],
        "commit_order_ok": (inline["commit_order_ok"]
                            and pipelined["commit_order_ok"]),
        "upload_overlap_pct": pipelined["upload_overlap_pct"],
    }
    print(json.dumps(inline))
    print(json.dumps(pipelined))
    print(json.dumps({"verdict": verdict}))
    ok = (verdict["pipelined_strictly_below_inline"]
          and verdict["commit_order_ok"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
