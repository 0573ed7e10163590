"""checkpoint_commit_seconds sum over the window / committed checkpoints: the manifest swap."""

from benchmark.harness import readers

LAYER = "state store"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    return readers.per_checkpoint(
        run, run["window"]["counters"]["commit_s"])
