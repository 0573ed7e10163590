"""Device trace: 1 - union of the device-op intervals / the traced interval, on the least busy chip."""

LAYER = "device"
UNIT = "%"
MOVES = "freshness_p50_s"
NEEDS_TRACE = True


def read(run):
    t = run["trace"]
    if not t["devices"] or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s_least"] / t["window_s"])
