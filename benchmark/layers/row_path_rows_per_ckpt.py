"""EpochTrace.phases[*].row_path_rows: the rows the actors' state tables took in ROW form in one checkpoint (`StateTable.write_chunk_rows`: the MV's changelog and every table whose schema the columnar codec cannot encode, e.g. one with a VARCHAR's int32 dictionary id; a Python tuple and an encoded key per row, where an all-INT64 table's batch is one columnar segment), summed over the checkpoint's actors, median over the window's checkpoints. A deferred flush writes behind its barrier, so a checkpoint reads what the store drained during its interval. Nothing to read where no actor's phases carry the key."""

from benchmark.harness import readers

LAYER = "persist d2h"
UNIT = "rows"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False
KEY = "row_path_rows"


def read(run):
    per = [sum(p[KEY] for p in r["phases"].values() if KEY in p)
           for r in readers.committed(run)
           if any(KEY in p for p in (r.get("phases") or {}).values())]
    return readers.stats.median(per) if per else None
