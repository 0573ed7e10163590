"""EpochTrace.phases[*].dispatch_ns (ops/jit_state.py: the `dispatch:<StateJit>` spans inside an actor's polls, summed per interval): host time enqueueing programs, a full device queue's block included; part of apply_host + persist_host. Max over actors, median over checkpoints."""

from benchmark.harness import span_readers

LAYER = "stateful executors"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    return span_readers.phase_s_per_ckpt(run, "dispatch_ns")
