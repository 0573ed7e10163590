"""The one sweep that fixes a paced cell's offered rate (run by hand).

    python benchmark/tests/sweep_paced.py --workload q7.paced --k 1,2,3,4 --seconds 30

One process; for each `k` a fresh durable deployment of the cell with
`chunks_per_interval = k` for every source, warmed up, then the cell's own
open-loop window. Prints per `k` how late the barriers were injected (first
and last quarter of the window: lateness that grows means the rate is above
what the engine sustains), freshness and the time per checkpoint.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


async def one(cell, k: int, seed: int, seconds: float) -> dict:
    from benchmark.harness import drive, stats
    cell.traffic = {**cell.traffic, "chunks_per_interval": {
        t: k for t in cell.traffic["chunks_per_interval"]}}
    path = tempfile.mkdtemp(prefix="bench_sweep_")
    try:
        s, _, _ = await drive.deploy(cell, seed, path)
        stamps = drive.Stamps(s.coord)
        await drive.warm_up(s, cell, stamps)
        win = await drive.window(s, cell, stamps, seconds,
                                 drive.BackendCompiles())
        await s.crash()
    finally:
        shutil.rmtree(path, ignore_errors=True)
    recs = win["checkpoints"]
    late = [(r["call_ns"] - r["due_ns"]) / 1e9 for r in recs]
    fresh = [(r["commit_ns"] - r["due_ns"]) / 1e9 for r in recs]
    q = max(1, len(recs) // 4)
    return {"k": k, "rows_per_s_offered": sum(cell.quotas.values()) * 1e3
            / cell.traffic["barrier_interval_ms"],
            "checkpoints": len(recs), "window_s": win["window_s"],
            "late_first_quarter_s": stats.median(late[:q]),
            "late_last_quarter_s": stats.median(late[-q:]),
            "late_max_s": max(late),
            "freshness_p50_s": stats.median(fresh),
            "freshness_first_quarter_s": stats.median(fresh[:q]),
            "freshness_last_quarter_s": stats.median(fresh[-q:]),
            "freshness_p95_s": stats.percentile(fresh, 0.95),
            "collect_p50_s": stats.median(
                [r["collect_latency_ns"] / 1e9 for r in recs]),
            "compiled_in_window": win["compiled_in_window"]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--k", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=4242)
    args = ap.parse_args()
    from benchmark.harness import spec
    rehearsal = os.environ.get("BENCH_REHEARSAL") == "1"
    import risingwave_tpu  # noqa: F401
    from risingwave_tpu.utils.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    bm = spec.load_benchmark()
    for k in (int(x) for x in args.k.split(",")):
        cell = spec.Cell(bm, args.workload, rehearsal=rehearsal)
        print(json.dumps(asyncio.run(one(cell, k, args.seed + k,
                                         args.seconds))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
