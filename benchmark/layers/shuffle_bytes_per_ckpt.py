"""EpochTrace.phases[*].mesh_shuffle_bytes: the bytes the all_to_all buffers of one checkpoint held (shards^2 x cap_out x row bytes per chunk, counted at dispatch from the traced shapes), summed over the checkpoint's mesh actors, median over the window's checkpoints. Nothing to read where no actor's phases carry the key."""

from benchmark.harness import readers

LAYER = "mesh plane"
UNIT = "bytes"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    per = [sum(p["mesh_shuffle_bytes"] for p in r["phases"].values()
               if "mesh_shuffle_bytes" in p)
           for r in readers.committed(run)
           if any("mesh_shuffle_bytes" in p
                  for p in (r.get("phases") or {}).values())]
    return readers.stats.median(per) if per else None
