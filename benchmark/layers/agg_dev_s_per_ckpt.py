"""Device trace x the StateJit registry (ops/jit_state.py PROGRAMS: program id -> name): device time of the modules `jit_traced(<id>)` whose program is a hash agg's (`hash_agg_*` / `sharded_agg_*`), its persist view and packs excluded, per traced checkpoint, mean over the chips."""

from benchmark.harness import span_readers

LAYER = "stateful executors"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = True


def read(run):
    return span_readers.device_s_per_ckpt(run, span_readers.is_agg)
