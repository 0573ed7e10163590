"""The span `flush` (job taken -> manifest swapped) less the `d2h_wait` spans under it: the stages' continuations (encode + state-table write), seal, SST build + upload, commit, and the time a worker thread's result waited for the event loop. Median over the window's committed checkpoints."""

from benchmark.harness import span_readers

LAYER = "state store"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def _flush_host_s(spans):
    flush = span_readers.span_s(spans, "flush")
    return None if flush is None \
        else flush - span_readers.flush_wait_s(spans)


def read(run):
    return span_readers.median_per_tree(run, _flush_host_s)
