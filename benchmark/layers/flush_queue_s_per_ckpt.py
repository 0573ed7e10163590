"""The span `flush.queue` (meta/barrier_manager.py: _enqueue_upload -> _upload_worker takes the job): how long a checkpoint waited behind its predecessor's flush. Median over the window's committed checkpoints."""

from benchmark.harness import span_readers

LAYER = "barrier coordinator"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    return span_readers.median_per_tree(
        run, lambda spans: span_readers.span_s(spans, "flush.queue"))
