"""EpochTrace.phases[*].agg_extrema_lossy_groups: live groups of a retractable MIN/MAX whose top-K value buffer has dropped an insert (`lossy`: exact only while the buffer does not drain; counted on the device, brought by the agg's one watchdog fetch), summed over a checkpoint's actors, the LARGEST over the window's checkpoints. Nothing to read where no actor's phases carry the key."""

from benchmark.harness import readers

LAYER = "stateful executors"
UNIT = "groups"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False
KEY = "agg_extrema_lossy_groups"


def read(run):
    per = [sum(p[KEY] for p in r["phases"].values() if KEY in p)
           for r in readers.committed(run)
           if any(KEY in p for p in (r.get("phases") or {}).values())]
    return max(per) if per else None
