"""Device trace x the StateJit registry (ops/jit_state.py PROGRAMS: program id -> name): device time of the modules `jit_traced(<id>)` whose program is the snapshot join-agg executor's (`snapshot_join_agg_*`: the two appends, the barrier's snapshot recompute `snapshot_join_agg_flush`, its counts and persist packs), per traced checkpoint. Nothing to read where the trace names no such program (an engine before PR 38 registers them under the same names; one before PR 36 has no registry)."""

from benchmark.harness import span_readers

LAYER = "stateful executors"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = True


def read(run):
    return span_readers.device_s_per_ckpt(
        run, lambda name: name.startswith("snapshot_join_agg_"))
