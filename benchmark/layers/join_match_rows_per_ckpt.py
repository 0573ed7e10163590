"""EpochTrace.phases[*].join_match_rows: the rows the sorted joins' applies emitted in one checkpoint (both sides' chunks; matches that passed key equality and the condition: what the join hands its consumer, here the MAX agg; counted inside the apply, brought by the join's one watchdog fetch), summed over the checkpoint's actors, median over the window's checkpoints. Nothing to read where no actor's phases carry the key."""

from benchmark.harness import readers

LAYER = "stateful executors"
UNIT = "rows"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False
KEY = "join_match_rows"


def read(run):
    per = [sum(p[KEY] for p in r["phases"].values() if KEY in p)
           for r in readers.committed(run)
           if any(KEY in p for p in (r.get("phases") or {}).values())]
    return readers.stats.median(per) if per else None
