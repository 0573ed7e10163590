"""EpochTrace.phases[*]: 100 x join_match_peak / join_match_width of one checkpoint: the most equi-key candidates one chunk found in the other side's pool, over the rows of the match buffer it was given (match factor x chunk width), of the join side that came nearest its buffer's end (counted inside the apply, brought by the join's one watchdog fetch). More candidates than the buffer holds fail-stop the epoch, so this is how near that the hand-sized factor runs. The LARGEST over the checkpoint's actors and over the window's checkpoints. Nothing to read where no actor's phases carry the keys."""

from benchmark.harness import readers

LAYER = "stateful executors"
UNIT = "%"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    per = [100.0 * p["join_match_peak"] / p["join_match_width"]
           for r in readers.committed(run)
           for p in (r.get("phases") or {}).values()
           if p.get("join_match_width")]
    return max(per) if per else None
