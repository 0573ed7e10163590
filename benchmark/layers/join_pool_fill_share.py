"""EpochTrace.phases[*]: 100 x join_live_rows / join_capacity at the window's LAST committed checkpoint: the rows the fuller side's device pool holds over the rows reserved for it (from the join's one watchdog fetch). The join's apply programs cost by the capacity, so this is the share of that work done for rows held; under a sizing rule that keeps a growing pool from doubling inside a run it ends a window near a third. The fullest over the checkpoint's actors. Nothing to read where no actor's phases carry the keys."""

from benchmark.harness import readers

LAYER = "stateful executors"
UNIT = "%"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    for r in reversed(readers.committed(run)):
        per = [100.0 * p["join_live_rows"] / p["join_capacity"]
               for p in (r.get("phases") or {}).values()
               if p.get("join_capacity")]
        if per:
            return max(per)
    return None
