"""A restarted pipeline cleans its state as the one it replaces did.

The watermark a source sends after the last committed chunk is held by its
consumers, not stored: a windowed join evicts by it during its NEXT apply.
A resumed source therefore re-states the watermark of its committed offsets
before its first chunk, a hash agg with nothing buffered hands it on at
once, and the join's first apply after a restart evicts what the uncrashed
join's would have. Before, that apply ran with no cleaning watermark, the
pool held one interval more than it ever does in steady state, and NEXMark
q7's 2^19 pool crossed its growth threshold inside a timed recovery.
"""

from benchmark.harness import check
from benchmark.queries import q7
from risingwave_tpu.common.chunk import OP_INSERT
from risingwave_tpu.common.types import DataType as DT
from risingwave_tpu.expr.agg import count_star
from risingwave_tpu.frontend import Session
from risingwave_tpu.plan.build import _iter_executor_chain
from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
from risingwave_tpu.stream import BarrierKind, SortedJoinExecutor, Watermark
from risingwave_tpu.stream.sorted_join import NO_WATERMARK

from test_hash_agg import barrier, chunk, run_agg

SEED = 2147483659
W = 10_000_000
CHUNK = 512
CFG = {"window_us": W,
       "generator": {"inter_event_us": 20_000, "emit_watermarks": 1,
                     "watermark_lag_us": 2 * W},
       "session_set": {"streaming_join_capacity": 8192,
                       "streaming_join_match_factor": 2,
                       "streaming_agg_capacity": 256,
                       "streaming_watchdog": 1}}


async def test_a_watermark_with_nothing_buffered_is_handed_on_at_once():
    """Held only behind buffered updates (it must not overtake them): the
    one that arrives before any chunk of the interval goes straight on."""
    src_msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        Watermark(0, DT.INT64, 15),          # nothing buffered: at once
        chunk([(OP_INSERT, 20, 2)]),
        Watermark(0, DT.INT64, 18),          # behind the chunk: held
        barrier(2, 1),
    ]
    _, out = await run_agg(src_msgs, [count_star()])
    assert [(type(m).__name__, getattr(m, "val", None)) for m in out] == [
        ("Barrier", None), ("Watermark", 15), ("StreamChunk", None),
        ("Watermark", 18), ("Barrier", None)]


def _join(s: Session) -> SortedJoinExecutor:
    join, = [ex for roots in s.catalog.mvs["q7"].deployment.roots.values()
             for root in roots for ex in _iter_executor_chain(root)
             if isinstance(ex, SortedJoinExecutor)]
    return join


async def _deploy(s: Session) -> None:
    for stmt in q7.ddl(CFG, {"chunk_size": {"bid": CHUNK},
                             "chunks_per_interval": {"bid": 1}}, SEED):
        await s.execute(stmt)


async def test_the_first_apply_after_a_restart_runs_with_a_cleaning_watermark(
        tmp_path):
    """q7 at 20 ms between events (a 512-bid checkpoint spans 11 s, a
    window and more): six checkpoints, crash, recover, one more. The
    recovered join applies that chunk with cleaning watermarks on both
    sides and evicts by them (its pool does not hold the recovered rows
    plus the whole chunk), compiles ONE replay program a side whatever
    the row counts, and the MV is exact."""
    root = str(tmp_path / "hummock")
    s = Session(store=HummockStateStore(LocalFsObjectStore(root)))
    await _deploy(s)
    await s.tick(6)
    held = _join(s)._n_known[0]
    compiled = _join(s)._replay.compiles    # per program NAME, by process
    assert held > 2 * CHUNK
    await s.crash()
    del s
    s2 = Session(store=HummockStateStore.open(LocalFsObjectStore(root)))
    await s2.recover()
    join = _join(s2)
    assert join._replay.compiles - compiled == 2, "one replay program a side"
    await s2.tick(1)
    assert min(int(x) for x in join._cleaned_to) > NO_WATERMARK
    assert join._n_known[0] < held + CHUNK
    got = check.rows_to_cols(q7.read_mv(s2), q7.DTYPES)
    numbers = check.compare(got, q7.oracle({"bid": 7 * CHUNK}, CFG, SEED),
                            0.0)
    assert all(n["ok"] for n in numbers) and got[0].shape[0] > 0, numbers
    await s2.crash()
