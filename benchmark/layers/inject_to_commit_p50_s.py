"""The engine's own staleness: the span `checkpoint` (utils/trace.py: EpochTracer.begin at the inject -> the manifest swap) of each committed checkpoint, the median over the window. In a `sat` cell due = the inject call, so it reads what freshness_p50_s reads, less the wait for an upload slot before the epoch exists."""

from benchmark.harness import span_readers

LAYER = "barrier coordinator"
UNIT = "s"
MOVES = "freshness_p50_s"
NEEDS_TRACE = False


def read(run):
    return span_readers.median_per_tree(
        run, lambda spans: span_readers.span_s(spans, "checkpoint"))
