"""The engine's own latencies_ns for the window's epochs: inject -> collected, the durable flush excluded (meta/barrier_manager.py). Median."""

from benchmark.harness import readers

LAYER = "barrier coordinator"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    xs = [r["collect_latency_ns"] / 1e9 for r in readers.committed(run)]
    return readers.stats.median(xs) if xs else None
