"""The `d2h_wait` spans under a checkpoint's `flush` (utils/d2h.py fetch_flat / fetch_small on the uploader's worker threads), summed: the part of the flush that is blocked until the device reaches and ships a buffer. Median over the window's committed checkpoints."""

from benchmark.harness import span_readers

LAYER = "persist d2h"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    return span_readers.median_per_tree(run, span_readers.flush_wait_s)
