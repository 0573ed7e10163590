"""checkpoint_backpressure_seconds_total over the window / the window: the share of the window in which injection waited for a free in-flight slot."""

LAYER = "barrier coordinator"
UNIT = "%"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    w = run["window"]
    return 100.0 * w["counters"]["backpressure_s"] / w["window_s"]
