"""Device trace x the StateJit registry (ops/jit_state.py PROGRAMS: program id -> name): device time of the modules `jit_traced(<id>)` whose program is a top-N's (`retract_top_n_*`: the per-chunk merge into the sorted store, the barrier's capacity-wide sort and rank, the gather of what changed and the store's compaction), its packs excluded, per traced checkpoint, mean over the chips. Nothing to read where the registry names no such program."""

from benchmark.harness import span_readers

LAYER = "stateful executors"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = True


def is_topn(name: str) -> bool:
    return (name.startswith("retract_top_n_")
            and not span_readers.is_persist(name))


def read(run):
    return span_readers.device_s_per_ckpt(run, is_topn)
