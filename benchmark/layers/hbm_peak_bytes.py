"""memory_stats()[peak_bytes_in_use] after the window, on the fullest chip."""

LAYER = "device"
UNIT = "bytes"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    return run["hbm_peak_bytes"] or None
