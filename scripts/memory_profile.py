"""HBM memory-manager gate — growing-keyspace q7 shape, no TPU needed.

A windowed agg + join pipeline whose keyspace GROWS every interval (new
windows arrive, old ones go cold, the occasional late row touches an old
window again) runs twice:

  unbounded   hbm_budget_bytes = 0 — today's grow-forever behavior;
              the run's peak accounted bytes is the reference point
  budgeted    hbm_budget_bytes = the join's reserved pools + half the
              agg's unbounded peak — the MemoryManager evicts the agg's
              cold slots to host at barriers (its table shrinks); the
              sorted join's pools are reserved at their capacity whatever
              they hold, so it spills by OCCUPANCY (past 60%) instead;
              late rows reload through the read-through path of both

Exit status is 0 iff, after warmup:
  * the budgeted run's accounted device state stays under budget at
    every barrier,
  * eviction and at least one read-through reload actually happened, in
    the agg and in the join,
  * the materialized results (changelog applied to a dict) and the join
    match multiset are IDENTICAL to the unbounded run.

    JAX_PLATFORMS=cpu python scripts/memory_profile.py
"""

import asyncio
import json
import os
import sys
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent XLA compilation cache (utils/compile_cache.py): the
# gate re-runs a canned shape every CI round — repeat runs skip the
# compile entirely
from risingwave_tpu.utils.compile_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


N_INTERVALS = 24
WARMUP_INTERVALS = 10
ROWS_PER_INTERVAL = 192
CHUNK_CAP = 256
WINDOW = 1 << 10


def _bid_schema():
    from risingwave_tpu.common import DataType, schema
    return schema(("auction", DataType.INT64), ("price", DataType.INT64),
                  ("window_end", DataType.INT64))


class _Script:
    def __init__(self, sch, messages):
        self.schema = sch
        self.messages = messages
        self.identity = "MemoryProfileSource"
        self.pk_indices = ()

    def fence_tokens(self):
        return []

    async def execute(self):
        for m in self.messages:
            yield m
            await asyncio.sleep(0)


def _script_messages(seed: int) -> list:
    """Growing keyspace: each interval's rows land in a FRESH window
    (plus a sprinkle of late rows into windows several intervals old —
    the read-through reload workload)."""
    from risingwave_tpu.common.chunk import StreamChunk
    from risingwave_tpu.common.epoch import EpochPair
    from risingwave_tpu.stream.message import Barrier, BarrierKind
    rng = np.random.RandomState(seed)
    sch = _bid_schema()
    msgs = [Barrier(EpochPair(1, 0), BarrierKind.INITIAL)]
    for e in range(N_INTERVALS):
        w_end = (e + 1) * WINDOW
        n = ROWS_PER_INTERVAL
        auction = rng.randint(0, 40, size=n).astype(np.int64)
        price = rng.randint(1, 2_000, size=n).astype(np.int64)
        wend = np.full(n, w_end, dtype=np.int64)
        if e >= 6:
            # late rows re-open a long-cold window
            k = 4
            wend[:k] = (e - 5) * WINDOW
        msgs.append(StreamChunk.from_numpy(
            sch, [auction, price, wend], capacity=CHUNK_CAP))
        msgs.append(Barrier(EpochPair(e + 2, e + 1)))
    return msgs


async def _run(budget_bytes: int) -> dict:
    """agg: max(price) per (window_end, auction); join: bids back against
    the agg output on window_end — both stateful stages grow with the
    keyspace unless the manager evicts."""
    from risingwave_tpu.common import DataType, schema
    from risingwave_tpu.common.chunk import StreamChunk
    from risingwave_tpu.expr.agg import AggCall, AggKind
    from risingwave_tpu.memory import MemoryManager
    from risingwave_tpu.state import MemoryStateStore, StateTable
    from risingwave_tpu.stream import HashAggExecutor
    from risingwave_tpu.stream.sorted_join import SortedJoinExecutor
    from risingwave_tpu.stream.message import Barrier

    sch = _bid_schema()
    store = MemoryStateStore()
    agg_state = StateTable(
        store, 1, schema(("window_end", DataType.INT64),
                         ("auction", DataType.INT64),
                         ("state0", DataType.INT64),
                         ("_row_count", DataType.INT64)), (0, 1))
    join_states = (
        StateTable(store, 2, sch, (0, 1, 2)),
        StateTable(store, 3, schema(("window_end", DataType.INT64),
                                    ("auction", DataType.INT64),
                                    ("maxp", DataType.INT64)), (0, 1)),
    )
    agg = HashAggExecutor(
        _Script(sch, _script_messages(seed=7)), [2, 0],
        [AggCall(AggKind.MAX, 1, sch[1].data_type, append_only=True)],
        capacity=1 << 12, state_table=agg_state)
    join = SortedJoinExecutor(
        _Script(sch, _script_messages(seed=7)), agg,
        left_key_indices=[2], right_key_indices=[0],
        left_pk_indices=[0, 1, 2], right_pk_indices=[0, 1],
        capacity=1 << 12, match_factor=64, state_tables=join_states)
    mgr = MemoryManager()
    mgr.register("agg", agg)
    mgr.register("join", join)
    # the join's pools are accounted at their reserved size, which no
    # spill changes: the budget the agg is held to sits on top of them
    if budget_bytes:
        budget_bytes += join.state_bytes()
    mgr.configure(budget_bytes=budget_bytes)

    from risingwave_tpu.common.chunk import OP_INSERT, OP_UPDATE_INSERT
    mat: dict = {}
    # NET multiset of joined rows (insert +1 / delete -1): the join's
    # transient changelog interleaving is alignment-dependent (two-input
    # polling order), but the net materialized result must be exact
    matches = Counter()
    peak = peak_after_warmup = agg_peak = 0
    barriers = 0
    over_budget_barriers = 0
    async for msg in join.execute():
        if isinstance(msg, StreamChunk):
            for op, row in msg.to_rows():
                if op in (OP_INSERT, OP_UPDATE_INSERT):
                    matches[row] += 1
                else:
                    matches[row] -= 1
                    if matches[row] == 0:
                        del matches[row]
        elif isinstance(msg, Barrier):
            barriers += 1
            mgr.on_barrier(msg.epoch.curr)
            total = mgr.total_bytes()
            peak = max(peak, total)
            agg_peak = max(agg_peak, agg.state_bytes())
            if barriers > WARMUP_INTERVALS:
                peak_after_warmup = max(peak_after_warmup, total)
                if budget_bytes and total > budget_bytes:
                    over_budget_barriers += 1
    # the materialized agg result via a second pass over its state table
    for _, row in agg_state.iter_all():
        mat[row[:2]] = row
    return {
        "budget_bytes": budget_bytes,
        "peak_bytes": peak,
        "agg_peak_bytes": agg_peak,
        "peak_after_warmup": peak_after_warmup,
        "over_budget_barriers": over_budget_barriers,
        "evicted_bytes": agg.mem_evicted_bytes,
        "reloads": agg.mem_reload_count,
        "join_reloads": join.mem_reload_count,
        "spilled_rows": agg.mem_spilled_rows + join.mem_spilled_rows,
        "mat": mat,
        "matches": matches,
    }


async def main() -> int:
    base = await _run(0)
    bud = await _run(base["agg_peak_bytes"] // 2)
    verdict = {
        "budget_bytes": bud["budget_bytes"],
        "unbounded_peak": base["peak_bytes"],
        "budgeted_peak_after_warmup": bud["peak_after_warmup"],
        "under_budget_after_warmup": bud["over_budget_barriers"] == 0,
        "evicted_bytes": bud["evicted_bytes"],
        "reloads": bud["reloads"],
        "join_reloads": bud["join_reloads"],
        "spilled_rows_final": bud["spilled_rows"],
        "mat_rows": len(base["mat"]),
        "results_identical": (base["mat"] == bud["mat"]
                              and base["matches"] == bud["matches"]),
    }
    print(json.dumps({k: v for k, v in base.items()
                      if k not in ("mat", "matches")}))
    print(json.dumps({k: v for k, v in bud.items()
                      if k not in ("mat", "matches")}))
    print(json.dumps({"verdict": verdict}))
    ok = (verdict["under_budget_after_warmup"]
          and verdict["evicted_bytes"] > 0
          and verdict["reloads"] > 0
          and verdict["join_reloads"] > 0
          and verdict["results_identical"]
          and verdict["mat_rows"] > 0)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
