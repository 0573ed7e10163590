"""checkpoint_seal_seconds + checkpoint_upload_seconds sums over the window / committed checkpoints (state/hummock.py, native/)."""

from benchmark.harness import readers

LAYER = "state store"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    c = run["window"]["counters"]
    return readers.per_checkpoint(run, c["seal_s"] + c["upload_s"])
