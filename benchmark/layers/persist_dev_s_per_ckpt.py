"""Device trace x the StateJit registry (ops/jit_state.py PROGRAMS: program id -> name): device time of the programs that only serve the durable flush and the barrier's counters (`sorted_join_diff`, `*_persist_view`, `*_watchdog_pack`, `*_mem_pack`), per traced checkpoint, mean over the chips."""

from benchmark.harness import span_readers

LAYER = "persist d2h"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = True


def read(run):
    return span_readers.device_s_per_ckpt(run, span_readers.is_persist)
