"""Harness clock over the warm-up checkpoints, their durable flush included: compile or load from the cache (ops/jit_state.py)."""

LAYER = "stateful executors"
UNIT = "s"
MOVES = "setup_s"
NEEDS_TRACE = False


def read(run):
    return run["warmup_s"]
