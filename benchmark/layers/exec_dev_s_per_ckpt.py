"""Device trace: device time of the StateJit programs (XLA module jit_traced) in the traced checkpoints, per checkpoint, mean over the chips."""

LAYER = "stateful executors"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = True


def read(run):
    t = run["trace"]
    return t["statejit_s"] / t["checkpoints"] if t["checkpoints"] else None
