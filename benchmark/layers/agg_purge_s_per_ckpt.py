"""The `agg.purge` spans (stream/hash_agg.py `_maybe_rebuild_at_barrier`: a hash agg drops its zombie slots by a same-capacity rehash; the span holds the rehash's dispatch and the awaited readback of the rebuilt occupancy, i.e. the device's time for the rehash with the actor parked), summed over the trees of the window's committed checkpoints, over those checkpoints: a MEAN, since a purge falls on one checkpoint in several. 0 where the program counts evictions (`agg_evict_groups` in some actor's phases) and no purge fell in the window; nothing to read where it has neither the span nor the count."""

from benchmark.harness import readers, span_readers

LAYER = "stateful executors"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    ts = span_readers.trees(run)
    if ts is None or not any(
            "agg_evict_groups" in p for r in readers.committed(run)
            for p in (r.get("phases") or {}).values()):
        return None
    return readers.per_checkpoint(run, sum(
        sp.t1_ns - sp.t0_ns for spans in ts for sp in spans
        if sp.name == "agg.purge") / 1e9)
