"""EpochTrace.phases[*].apply_ns: host compute plus dispatch of the executors (stream/hash_agg.py, sorted_join.py), NOT device time. Max over actors, median over checkpoints."""

from benchmark.harness import readers

LAYER = "stateful executors"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    return readers.phase_s_per_ckpt(run, "apply_ns")
