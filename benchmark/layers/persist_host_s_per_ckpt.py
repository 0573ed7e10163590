"""EpochTrace.phases[*].persist_ns: the executors barrier-time flush stages (utils/d2h.py and the row path behind it). Max over actors, median over checkpoints."""

from benchmark.harness import readers

LAYER = "persist d2h"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    return readers.phase_s_per_ckpt(run, "persist_ns")
