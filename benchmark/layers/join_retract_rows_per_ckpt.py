"""EpochTrace.phases[*].join_persist_delete_rows: the rows the sorted joins' durable flush DELETED from their state tables in one checkpoint (both sides; the interval's share of join_persist_rows_total{op="delete"}, from the two counts the persist fetches anyway), summed over the checkpoint's actors, median over the window's checkpoints. Nothing to read where no actor's phases carry the key."""

from benchmark.harness import readers

LAYER = "persist d2h"
UNIT = "rows"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False
KEY = "join_persist_delete_rows"


def read(run):
    per = [sum(p[KEY] for p in r["phases"].values() if KEY in p)
           for r in readers.committed(run)
           if any(KEY in p for p in (r.get("phases") or {}).values())]
    return readers.stats.median(per) if per else None
