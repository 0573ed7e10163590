"""EpochTrace.phases[*]: 100 x topn_live_rows / topn_capacity at the window's LAST committed checkpoint: the rows the top-N's device store holds once the barrier has pruned it (for an append-only input the rows that can still rank, which is what its state table and the MV hold) over the rows reserved for it (from the top-N's one watchdog fetch). The barrier's sort costs by the capacity, so this is the share of that work done for rows held; the sizing rule (a quarter of the 0.7 growth mark) ends a window at or under 17.5%. The fullest over the checkpoint's actors. Nothing to read where no actor's phases carry the keys."""

from benchmark.harness import readers

LAYER = "stateful executors"
UNIT = "%"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    for r in reversed(readers.committed(run)):
        per = [100.0 * p["topn_live_rows"] / p["topn_capacity"]
               for p in (r.get("phases") or {}).values()
               if p.get("topn_capacity")]
        if per:
            return max(per)
    return None
