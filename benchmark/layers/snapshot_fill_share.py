"""EpochTrace.phases[*]: 100 x snapshot_rows / snapshot_capacity at the window's LAST committed checkpoint: the rows the snapshot join-agg executor's fact store holds over the rows reserved for it (from the counts fetch its barrier makes). Every pass of the barrier's snapshot program is capacity-wide, so this is the share of that work done for rows held; the store doubles (a re-trace: a StateJit compile in the window) past 70. Nothing to read where no actor's phases carry the keys."""

from benchmark.harness import readers

LAYER = "stateful executors"
UNIT = "%"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    for r in reversed(readers.committed(run)):
        per = [100.0 * p["snapshot_rows"] / p["snapshot_capacity"]
               for p in (r.get("phases") or {}).values()
               if p.get("snapshot_capacity")]
        if per:
            return max(per)
    return None
