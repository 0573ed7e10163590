"""EpochTrace.phases[*].agg_evict_groups: the live groups the hash aggs' watermark cleaning zeroed at one checkpoint's barrier (they stay in the table as zombie slots until a purge; counted on the device with `hash_agg_evict_keys`' mask, brought by the agg's one watchdog fetch), summed over the checkpoint's actors, median over the window's checkpoints. Nothing to read where no actor's phases carry the key."""

from benchmark.harness import readers

LAYER = "stateful executors"
UNIT = "rows"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False
KEY = "agg_evict_groups"


def read(run):
    per = [sum(p[KEY] for p in r["phases"].values() if KEY in p)
           for r in readers.committed(run)
           if any(KEY in p for p in (r.get("phases") or {}).values())]
    return readers.stats.median(per) if per else None
