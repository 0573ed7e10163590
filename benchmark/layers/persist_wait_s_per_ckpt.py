"""EpochTrace.phases[*].persist_wait_ns (utils/d2h.py: the `d2h_wait` spans inside the barrier poll): the loop thread blocked on a fetch; the part of persist_host_s_per_ckpt that waits for the device. Max over actors, median over checkpoints."""

from benchmark.harness import span_readers

LAYER = "persist d2h"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    return span_readers.phase_s_per_ckpt(run, "persist_wait_ns")
