"""What a four-chip cell's result line cannot show: where its state lives and
how each mesh fragment's rows spread (run by hand, one process):

    python benchmark/tests/mesh_placement.py --workload q7x4.sat --seed 7 \
        --checkpoints 6

Deploys the cell as `benchmark/run.py` does (same DDL, same traffic, durable,
its warm-up checkpoints), then `--checkpoints` more, and prints one JSON line
per step; anything wrong raises. Asserts, as `chip_smoke.py` does for its mesh
phase: at least two fused mesh fragments registered with the coordinator, and
every state array of every `Sharded*` executor on `chips` distinct devices.
Prints per checkpoint and mesh actor the epoch trace's `mesh_rows`,
`mesh_rows_max_shard`, `mesh_shuffle_bytes` and the skew (shards x max /
rows) — `benchmark/layers/shard_skew.py` reports only the larger — then the
`mesh_*` series of `GLOBAL_METRICS` and the last epoch as `/debug/traces`
renders it. Same chip rules as `benchmark/run.py` (a TPU, or
`JAX_PLATFORMS=cpu BENCH_REHEARSAL=1` with virtual devices). Not part of the
benchmark's command.
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


def log(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def placement(session, mv: str, chips: int) -> dict:
    """identity -> device ids of each `Sharded*` executor's state arrays."""
    import jax
    from benchmark.harness import drive
    placed = {}
    for ex in drive.executors_of(session, mv):
        if not type(ex).__name__.startswith("Sharded"):
            continue
        leaves = [x for x in jax.tree_util.tree_leaves(
            [getattr(ex, "state", None), getattr(ex, "sides", None)])
            if isinstance(x, jax.Array) and x.ndim >= 1]
        if not leaves:
            raise AssertionError(f"{ex.identity}: no device state found")
        per_leaf = min(len({sh.device.id for sh in x.addressable_shards})
                       for x in leaves)
        devs = sorted({sh.device.id for x in leaves
                       for sh in x.addressable_shards})
        if len(devs) != chips or per_leaf != chips:
            raise AssertionError(
                f"{ex.identity}: state on devices {devs} (min per array "
                f"{per_leaf}), wanted {chips} distinct")
        placed[ex.identity] = devs
    if not placed:
        raise AssertionError("no sharded executor deployed")
    return placed


async def run(cell, seed: int, checkpoints: int, store_path: str) -> None:
    from benchmark.harness import drive
    from benchmark.layers import shard_skew
    from risingwave_tpu.utils.metrics import GLOBAL_METRICS
    session, deploy_s, _steps = await drive.deploy(cell, seed, store_path)
    mv = cell.query.MV
    frags = {str(a): list(v) for a, v
             in session.coord.mesh_fragments.items()}
    if len(frags) < 2:
        raise AssertionError(f"mesh fragments: {frags}")
    log({"phase": "deployed", "deploy_s": deploy_s, "mesh_fragments": frags,
         "sharded_state_devices": placement(session, mv, cell.chips)})
    stamps = drive.Stamps(session.coord)
    warm = cell.traffic["warmup_intervals"]
    for i in range(warm + checkpoints):
        rec = await drive.checkpoint(
            session, mv, stamps,
            {t: (i + 1) * q for t, q in cell.quotas.items()})
        await session.coord.drain_uploads()
        skews = shard_skew.skews(rec, cell.chips)
        log({"phase": "warmup" if i < warm else "checkpoint", "k": i,
             "collect_s": rec["collect_latency_ns"] / 1e9,
             "mesh": {str(a): {**{k: v for k, v in p.items()
                                  if k.startswith("mesh_")},
                               "fragment": frags[str(a)][1],
                               "skew": skews.get(a)}
                      for a, p in rec["phases"].items() if "mesh_rows" in p}})
    log({"phase": "registry", "series": {
        k: v for k, v in GLOBAL_METRICS.snapshot().items()
        if k.startswith("mesh_")}})
    log({"phase": "debug_traces",
         "last_epoch": session.coord.tracer._ring[-1].render()})
    log({"phase": "placement_after",
         "sharded_state_devices": placement(session, mv, cell.chips),
         "ok": True})
    await session.crash()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--checkpoints", type=int, default=6)
    args = ap.parse_args()
    from benchmark.harness import spec
    rehearsal = os.environ.get("BENCH_REHEARSAL") == "1"
    cell = spec.Cell(spec.load_benchmark(), args.workload,
                     rehearsal=rehearsal)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax

    import risingwave_tpu  # noqa: F401 — enables x64 before any tracing
    from risingwave_tpu.utils.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    devs = jax.devices()
    if (devs[0].platform == "tpu") == rehearsal or len(devs) < cell.chips:
        print(f"mesh_placement: platform {devs[0].platform!r} x {len(devs)}, "
              f"rehearsal={rehearsal}, cell wants {cell.chips} chips",
              file=sys.stderr)
        return 2
    log({"phase": "start", "cell": cell.name, "seed": args.seed,
         "device": {"platform": devs[0].platform,
                    "kind": devs[0].device_kind, "count": len(devs)}})
    store_path = tempfile.mkdtemp(prefix="bench_mesh_placement_")
    try:
        asyncio.run(run(cell, args.seed, args.checkpoints, store_path))
    finally:
        shutil.rmtree(store_path, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
