"""EpochTrace.phases[*]: shards x mesh_rows_max_shard / mesh_rows of one mesh actor in one checkpoint (the rows the fullest shard received from the in-mesh shuffle, times the shard count, over the rows all shards received): 1.0 is balanced, the shard count is every row on one shard. The largest over the checkpoint's mesh actors, median over the window's checkpoints. Nothing to read where no actor's phases carry the mesh keys."""

from benchmark.harness import readers

LAYER = "mesh plane"
UNIT = "ratio"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def skews(rec: dict, shards: int) -> dict:
    """actor -> shards x mesh_rows_max_shard / mesh_rows, for the actors of
    one checkpoint record whose shuffle carried rows."""
    return {a: shards * p["mesh_rows_max_shard"] / p["mesh_rows"]
            for a, p in (rec.get("phases") or {}).items()
            if p.get("mesh_rows")}


def read(run):
    shards = run["cell"].chips
    per = [max(s.values()) for r in readers.committed(run)
           if (s := skews(r, shards))]
    return readers.stats.median(per) if per else None
