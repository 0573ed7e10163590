"""EpochTrace.phases[*].align_ns (input wait + epoch fence; stream/actor.py, exchange.py): max over actors, median over checkpoints."""

from benchmark.harness import readers

LAYER = "actors and exchange"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    return readers.phase_s_per_ckpt(run, "align_ns")
