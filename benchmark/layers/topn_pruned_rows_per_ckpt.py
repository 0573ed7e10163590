"""EpochTrace.phases[*].topn_pruned_rows: the rows an append-only top-N dropped from its store at one checkpoint as beyond rank N (nothing can promote them again: most never reached the MV, the rest left it as deletes; counted on the device, brought by the top-N's one watchdog fetch), summed over the checkpoint's actors, median over the window's checkpoints. Nothing to read where no actor's phases carry the key."""

from benchmark.harness import readers

LAYER = "stateful executors"
UNIT = "rows"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False
KEY = "topn_pruned_rows"


def read(run):
    per = [sum(p[KEY] for p in r["phases"].values() if KEY in p)
           for r in readers.committed(run)
           if any(KEY in p for p in (r.get("phases") or {}).values())]
    return readers.stats.median(per) if per else None
