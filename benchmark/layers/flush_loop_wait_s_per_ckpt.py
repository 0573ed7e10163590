"""The `flush.loop_wait` spans of a checkpoint's tree (meta/barrier_manager.py `_off_loop`), summed: how long the finished results of the uploader's worker threads (a stage's fetch, the SST upload) waited for the event loop to run the flush's next step. It is what the actors' work on the loop thread costs the flush; a loop held in a blocking fetch shows here as a whole collect. Median over the window's committed checkpoints."""

from benchmark.harness import span_readers

LAYER = "barrier coordinator"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def loop_wait_s(spans):
    if span_readers.span_s(spans, "flush") is None:
        return None
    return sum(sp.t1_ns - sp.t0_ns for sp in spans
               if sp.name == "flush.loop_wait") / 1e9


def read(run):
    return span_readers.median_per_tree(run, loop_wait_s)
