"""d2h_bytes_total over the window / committed checkpoints (d2h_fetch_count goes to the window line)."""

from benchmark.harness import readers

LAYER = "persist d2h"
UNIT = "bytes"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    return readers.per_checkpoint(
        run, run["window"]["counters"]["d2h_bytes"])
