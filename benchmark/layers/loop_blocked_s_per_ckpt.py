"""d2h_wait_on_loop_seconds_total (utils/d2h.py: seconds a `d2h_wait` was taken ON the event-loop thread, where it holds every actor and the uploader) of the whole process, set-up and check included, over the window's committed checkpoints: 0 where no barrier-time fetch blocks the loop. None on an engine without the counter."""

from benchmark.harness import readers

LAYER = "persist d2h"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    try:
        from risingwave_tpu.utils.metrics import D2H_WAIT_ON_LOOP_SECONDS
    except ImportError:
        return None
    return readers.per_checkpoint(
        run, float(D2H_WAIT_ON_LOOP_SECONDS.value))
