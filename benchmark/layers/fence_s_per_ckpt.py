"""EpochTrace.phases[*].fence_ns (stream/actor.py: block_until_ready of the epoch's tokens, the span `actor.fence`): align_s_per_ckpt without the input wait. Max over actors, median over checkpoints."""

from benchmark.harness import span_readers

LAYER = "actors and exchange"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    return span_readers.phase_s_per_ckpt(run, "fence_ns")
