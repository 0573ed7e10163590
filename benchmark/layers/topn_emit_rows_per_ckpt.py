"""EpochTrace.phases[*].topn_emit_rows: the rows the top-N's barrier flush sent downstream in one checkpoint (inserts, deletes and both halves of every update pair a rank shift makes; counted on the device, brought by the top-N's one watchdog fetch), summed over the checkpoint's actors, median over the window's checkpoints. Nothing to read where no actor's phases carry the key."""

from benchmark.harness import readers

LAYER = "stateful executors"
UNIT = "rows"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False
KEY = "topn_emit_rows"


def read(run):
    per = [sum(p[KEY] for p in r["phases"].values() if KEY in p)
           for r in readers.committed(run)
           if any(KEY in p for p in (r.get("phases") or {}).values())]
    return readers.stats.median(per) if per else None
