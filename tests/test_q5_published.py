"""NEXMark q5 'Hot Items' AS PUBLISHED (count per auction per HOP window,
the window's max count by a retractable MAX, the join of the two), through
`Session` -> binder -> plan -> actors with no option of its own, against the
benchmark's numpy oracle (`benchmark/queries/q5full.py`, which imports
nothing of the engine) on seeded offsets; and the bound of the retractable
MAX's top-K value buffer (`ops/extrema.py`) as a contract: it fail-stops
before the checkpoint commits, it does not answer wrongly.
"""

import numpy as np
import pytest

from benchmark.harness import check
from benchmark.queries import q5full
from benchmark.reference import nexmark
from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT,
)
from risingwave_tpu.expr.agg import agg_max
from risingwave_tpu.frontend import Session
from risingwave_tpu.state import (
    HummockStateStore, LocalFsObjectStore, MemoryStateStore, StateTable,
)
from risingwave_tpu.stream import BarrierKind, HashAggExecutor
from risingwave_tpu.utils.metrics import (
    GLOBAL_METRICS, HASH_AGG_EMIT_ROWS, HASH_AGG_EXTREMA_ERRORS,
    HASH_AGG_EXTREMA_LOSSY_GROUPS,
)

from test_hash_agg import SCHEMA, ScriptSource, barrier, chunk, emitted_rows

SLIDE, SIZE = 2_000_000, 10_000_000
SEED = 2147483659
PUBLISHED = "SELECT AuctionBids.auction, AuctionBids.num FROM"
PINNED = ("SELECT AuctionBids.auction, AuctionBids.num, "
          "AuctionBids.starttime FROM")


def _config(inter_event_us: int, agg: int, join: int) -> dict:
    return {"generator": {"inter_event_us": inter_event_us,
                          "emit_watermarks": 1},
            "hop_slide_us": SLIDE, "hop_size_us": SIZE,
            "session_set": {"streaming_agg_capacity": agg,
                            "streaming_join_capacity": join,
                            "streaming_join_match_factor": 2048,
                            "streaming_watchdog": 1}}


async def _deploy(s: Session, cfg: dict, chunk_size: int, pinned: bool):
    """The benchmark's own DDL; `pinned` adds `starttime` to the published
    projection, so that every row is held to its window."""
    stmts = q5full.ddl(cfg, {"chunk_size": {"bid": chunk_size},
                             "chunks_per_interval": {"bid": 1}}, SEED)
    assert PUBLISHED in stmts[-1]
    if pinned:
        stmts[-1] = stmts[-1].replace(PUBLISHED, PINNED)
    for stmt in stmts:
        await s.execute(stmt)


def _oracle(n_bids: int, cfg: dict, pinned: bool) -> list:
    ev = nexmark.bids(0, n_bids,
                      inter_event_us=cfg["generator"]["inter_event_us"],
                      base_time=nexmark.base_time_us(SEED))
    cols = q5full.hot_items(ev["auction"], ev["date_time"], SLIDE, SIZE)
    return cols if pinned else cols[:2]


def _read(s: Session, pinned: bool) -> list:
    cols = "auction, num, starttime" if pinned else "auction, num"
    rows = s.query(f"SELECT {cols} FROM q5full")
    return check.rows_to_cols(rows, (np.int64,) * (3 if pinned else 2))


def _assert_exact(got: list, want: list) -> None:
    numbers = check.compare(got, want, 0.0)
    assert all(n["ok"] for n in numbers), numbers
    assert got[0].shape[0] > 0, "no row: the comparison is vacuous"


def _phases(s: Session) -> dict:
    """key -> sum over the actors of the newest epoch's phase dicts."""
    out: dict = {}
    for ph in s.coord.tracer._ring[-1].phases.values():
        for k, v in ph.items():
            if not k.endswith("_ns"):
                out[k] = out.get(k, 0) + v
    return out


@pytest.mark.parametrize("pinned", [False, True],
                         ids=["published", "with_starttime"])
async def test_windows_close_and_the_mv_survives_a_crash(tmp_path, pinned):
    """20 ms between events: a 512-bid checkpoint spans 11 s, so every
    checkpoint closes windows and the watermark cleans the three aggs and
    the join while later windows still retract through max and join. Read
    live, then after `crash()` + `recover()` over the reopened store, then
    after two more checkpoints of the recovered session."""
    root = str(tmp_path / "hummock")
    cfg = _config(20_000, 4096, 4096)
    s = Session(store=HummockStateStore(LocalFsObjectStore(root)))
    await _deploy(s, cfg, 512, pinned)
    await s.tick(5)
    _assert_exact(_read(s, pinned), _oracle(5 * 512, cfg, pinned))
    # windows did close: the join cleaned rows it had persisted
    closed = [r for r in s.query("SELECT starttime FROM q5full")] \
        if pinned else None
    await s.crash()
    del s
    s2 = Session(store=HummockStateStore.open(LocalFsObjectStore(root)))
    await s2.recover()
    _assert_exact(_read(s2, pinned), _oracle(5 * 512, cfg, pinned))
    await s2.tick(2)
    _assert_exact(_read(s2, pinned), _oracle(7 * 512, cfg, pinned))
    if pinned:
        assert len({r[0] for r in closed}) > SIZE // SLIDE, \
            "fewer windows than one slide cycle: none closed"
    await s2.crash()


async def test_a_window_past_32_distinct_counts_goes_lossy_and_stays_exact():
    """2 us between events, four 32,768-bid checkpoints: the hot auctions of
    one window end at 38 distinct counts (1494..1555 beside the cold 1, 2,
    3), more than the MAX's 32 value slots a group: every open window's
    group is lossy by the last checkpoint, and the answer stays exact
    because the buffer holds the best 32 and never drains."""
    cfg = _config(2, 32768, 32768)

    def errors_by_kind():
        # summed over every agg of the process: another test's fail-stop
        # may have counted before this one
        out: dict = {}
        for (name, labels), c in GLOBAL_METRICS.counters.items():
            if name == HASH_AGG_EXTREMA_ERRORS:
                kind = dict(labels)["kind"]
                out[kind] = out.get(kind, 0) + c.value
        return out

    errors0 = errors_by_kind()
    s = Session()
    await _deploy(s, cfg, 32768, pinned=True)
    lossy = []
    for _ in range(4):
        await s.tick(1)
        lossy.append(_phases(s)["agg_extrema_lossy_groups"])
    want = _oracle(4 * 32768, cfg, pinned=True)
    ev = nexmark.bids(0, 4 * 32768, inter_event_us=2,
                      base_time=nexmark.base_time_us(SEED))
    _, counts = np.unique(ev["auction"], return_counts=True)
    assert np.unique(counts).shape[0] > 32
    assert lossy[0] == 0 and lossy[-1] == SIZE // SLIDE, lossy
    _assert_exact(_read(s, pinned=True), want)
    errors = errors_by_kind()
    assert set(errors) == {"underflow", "dropped_delete",
                           "negative_residue"}
    assert all(errors[k] == errors0.get(k, 0) for k in errors), errors
    await s.drop_all()


async def test_retractions_find_their_row_among_thousands_sharing_the_key():
    """q5 joins on the window alone (`num >= maxn` is the join's condition,
    not a key): every (auction, window) count sits in the join's left pool
    under its window's key, some 700 rows a window after sixteen 4,096-bid
    checkpoints, and each checkpoint retracts a few of them (count 1 -> 2).
    A retraction searched by the key alone expanded every row that shares
    it, past the match buffer of a chunk as narrow as the agg now hands on
    ("deletes matched no stored row"), so the pool is ordered by (key, pk)
    and a retraction finds its own row."""
    cfg = _config(2, 16384, 16384)
    s = Session()
    await _deploy(s, cfg, 4096, pinned=True)
    await s.tick(16)
    want = _oracle(16 * 4096, cfg, pinned=True)
    ev = nexmark.bids(0, 16 * 4096, inter_event_us=2,
                      base_time=nexmark.base_time_us(SEED))
    _, counts = np.unique(ev["auction"], return_counts=True)
    assert int((counts == 1).sum()) > 500
    _assert_exact(_read(s, pinned=True), want)
    await s.drop_all()


async def test_the_published_predicate_plans_one_equi_key_and_a_condition():
    """`ON starttime = starttime_c AND num >= maxn`, as upstream's q5.sql
    has it: the window is the join's only equi key, `num >= maxn` its
    condition. The aggregate is no part of the key, so a window's counts
    all share one key and a re-stated maximum probes every one of them;
    probing the max side (unique per window) takes the narrow buffer, the
    other way the session's match factor."""
    from risingwave_tpu.plan.build import _iter_executor_chain
    from risingwave_tpu.stream.sorted_join import SortedJoinExecutor
    assert "AuctionBids.num >= MaxBids.maxn" in q5full.ddl(
        _config(2, 4096, 4096), {"chunk_size": {"bid": 512},
                                 "chunks_per_interval": {"bid": 1}}, SEED)[-1]
    s = Session()
    await _deploy(s, _config(2, 4096, 4096), 512, pinned=False)
    join, = [ex for roots in s.catalog.mvs["q5full"].deployment.roots.values()
             for root in roots for ex in _iter_executor_chain(root)
             if isinstance(ex, SortedJoinExecutor)]
    left, right = (inp.schema for inp in join.inputs)
    assert [left[i].name for i in join.key_indices[0]] == ["starttime"]
    assert [right[i].name for i in join.key_indices[1]] == ["starttime_c"]
    assert join.condition is not None
    assert join.append_only == (False, False)
    assert join.match_factors == (2, 2048)
    await s.drop_all()


def _cascade_counts(n0: int, n1: int, cfg: dict) -> dict:
    """What one checkpoint over bids [n0, n1) moves, by hand from the
    events (no window closes): groups of the count agg that are new or
    whose count moved, windows that are new or whose maximum moved, and
    the rows at the maximum before and after."""
    def state(n):
        ev = nexmark.bids(0, n, inter_event_us=2,
                          base_time=nexmark.base_time_us(SEED))
        base = (ev["date_time"] // SLIDE) * SLIDE
        k = SIZE // SLIDE
        aa = np.tile(ev["auction"], k)
        ws = np.concatenate([base - j * SLIDE for j in range(k)])
        groups: dict = {}
        for a, w in zip(aa.tolist(), ws.tolist()):
            groups[(a, w)] = groups.get((a, w), 0) + 1
        wmax: dict = {}
        for (a, w), c in groups.items():
            wmax[w] = max(wmax.get(w, 0), c)
        return groups, wmax
    g0, m0 = state(n0)
    g1, m1 = state(n1)
    new_g = sum(1 for g in g1 if g not in g0)
    moved_g = sum(1 for g, c in g0.items() if g1[g] != c)
    new_w = sum(1 for w in m1 if w not in m0)
    moved_w = sum(1 for w, c in m0.items() if m1[w] != c)
    return {"count_emit": new_g + 2 * moved_g,
            "max_emit": new_w + 2 * moved_w,
            # the join's durable flush: a moved count / maximum is a delete
            # of the old row and an insert of the new one
            "join_deletes": moved_g + moved_w,
            "join_inserts": new_g + moved_g + new_w + moved_w}


async def test_phase_keys_carry_the_cascades_counts():
    """The epoch trace of each checkpoint carries `agg_emit_rows` (the two
    copies of the count agg and the max agg), `agg_extrema_lossy_groups`,
    `join_persist_delete_rows` / `join_persist_insert_rows`, with the
    counts a hand computation over the events gives."""
    cfg = _config(2, 8192, 8192)

    def lossy_gauges():
        # an earlier test of this process may have crashed its session,
        # which unregisters nothing: compare with what was there before
        return {l for name, l in GLOBAL_METRICS.gauges
                if name == HASH_AGG_EXTREMA_LOSSY_GROUPS}

    gauges0 = lossy_gauges()
    s = Session()
    await _deploy(s, cfg, 2048, pinned=False)

    def label_emits():
        return sum(c.value
                   for (name, _l), c in GLOBAL_METRICS.counters.items()
                   if name == HASH_AGG_EMIT_ROWS)

    emitted0 = label_emits()
    total = 0
    for k in range(3):
        await s.tick(1)
        got = _phases(s)
        want = _cascade_counts(k * 2048, (k + 1) * 2048, cfg)
        # what the join's watchdog fetch adds since PR 34 is q4's to test
        # (tests/test_q4_published.py)
        # and the aggs' evictions and the tables' row-form writes since PR
        # 40 are q8's (tests/test_q8_published.py)
        match = {"join_live_rows", "join_capacity", "join_match_rows",
                 "join_match_peak", "join_match_width", "agg_evict_groups",
                 "row_path_rows"}
        assert match <= set(got)
        assert {k_: v for k_, v in got.items() if k_ not in match} == {
            "agg_emit_rows": 2 * want["count_emit"] + want["max_emit"],
            "agg_extrema_lossy_groups": 0,
            "join_persist_delete_rows": want["join_deletes"],
            "join_persist_insert_rows": want["join_inserts"]}, (k, want)
        total += got["agg_emit_rows"]
    assert label_emits() - emitted0 == total
    assert len(lossy_gauges() - gauges0) == 1
    text = s.coord.tracer._ring[-1].render()
    assert "agg emitted" in text and "join persisted -" in text
    await s.drop_all()
    assert lossy_gauges() == gauges0


# ------------------------------------------------- the contract of the bound

def _max_agg(messages, k: int, state_table=None) -> HashAggExecutor:
    return HashAggExecutor(ScriptSource(SCHEMA, messages), [0], [agg_max(1)],
                           capacity=64, minput_k=k, state_table=state_table)


async def test_more_than_k_distinct_deletes_raise_before_the_commit():
    """40 distinct values into one group of a top-32 buffer (lossy), then
    33 distinct tracked values deleted in ONE chunk: the residue cannot be
    applied to a bounded buffer soundly, so the watchdog raises before the
    barrier leaves the executor — nothing of the epoch reaches the store."""
    store = MemoryStateStore()
    K = 32
    fields = [("k", DataType.INT64)]
    fields += [(f"v{i}", DataType.INT64) for i in range(K)]
    fields += [(f"c{i}", DataType.INT64) for i in range(K)]
    fields += [("lossy", DataType.INT64), ("_row_count", DataType.INT64)]
    table = StateTable(store, table_id=31, schema=schema(*fields),
                       pk_indices=[0])
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 100 + v) for v in range(40)], cap=64),
            barrier(2, 1),
            chunk([(OP_DELETE, 1, 100 + v) for v in range(7, 40)], cap=64),
            barrier(3, 2)]
    agg = _max_agg(msgs, K, table)
    out = []
    with pytest.raises(RuntimeError,
                       match=r"lost its bound.*'dropped_delete': 1"):
        async for m in agg.execute():
            out.append(m)
    # the first epoch's answer went out, the faulted epoch's barrier did not
    assert emitted_rows(out) == [(OP_INSERT, (1, 139))]
    assert [m.epoch.curr for m in out if hasattr(m, "epoch")] == [1, 2]
    store.sync(1)
    rows = [r for _, r in table.iter_all()]
    assert len(rows) == 1 and rows[0][1] == 139 and rows[0][-1] == 40
    assert rows[0][-2] == 1, "the group is lossy in the durable row"


async def test_a_lossy_buffer_admits_nothing_below_its_worst_value():
    """K = 2: {10, 9}, then 7 (dropped: lossy), 9 deleted, 5 inserted, 10
    deleted. The live values are {7, 5}; 7 was never tracked. A buffer that
    took the 5 into its free slot would now answer 5. It must not: a lossy
    group admits nothing worse than its worst tracked value, drains, and
    fail-stops as an underflow."""
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 10), (OP_INSERT, 1, 9)]),
            barrier(2, 1),
            chunk([(OP_INSERT, 1, 7)]),
            barrier(3, 2),
            chunk([(OP_DELETE, 1, 9)]),
            barrier(4, 3),
            chunk([(OP_INSERT, 1, 5)]),
            barrier(5, 4),
            chunk([(OP_DELETE, 1, 10)]),
            barrier(6, 5)]
    out = []
    with pytest.raises(RuntimeError, match=r"lost its bound.*'underflow': 1"):
        async for m in _max_agg(msgs, 2).execute():
            out.append(m)
    assert emitted_rows(out) == [(OP_INSERT, (1, 10))]


async def test_a_lossy_buffer_still_takes_better_values_and_a_reborn_group():
    """The other side of the rule: a lossy group takes any value at least
    as good as its worst tracked one, and a group whose every row was
    deleted starts over (not lossy: nothing untracked is left)."""
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 10), (OP_INSERT, 1, 9), (OP_INSERT, 1, 7)]),
            barrier(2, 1),                       # {10, 9}, lossy
            chunk([(OP_DELETE, 1, 9), (OP_INSERT, 1, 12)]),
            barrier(3, 2),                       # {12, 10}
            chunk([(OP_DELETE, 1, 7)]),          # untracked: legal when lossy
            barrier(4, 3),
            chunk([(OP_DELETE, 1, 12), (OP_DELETE, 1, 10)]),
            barrier(5, 4),                       # no row left
            chunk([(OP_INSERT, 1, 3)]),
            barrier(6, 5)]                       # reborn, exact again
    out = []
    agg = _max_agg(msgs, 2)
    async for m in agg.execute():
        out.append(m)
    assert emitted_rows(out) == [
        (OP_INSERT, (1, 10)),
        (OP_UPDATE_DELETE, (1, 10)), (OP_UPDATE_INSERT, (1, 12)),
        (OP_DELETE, (1, 12)),
        (OP_INSERT, (1, 3))]
    assert not bool(np.asarray(agg.state.agg_states[0][2]).any())


# --------------------------------------------------- the oracle has teeth

def _tied_events():
    """Window [0, 10 s) of slide 2 s: auctions 7 and 8 tie at 3 bids, 9 has
    one; every bid at t = 8.5 s, so only windows 0, 2, ..., 8 s hold it."""
    a = np.asarray([7, 7, 7, 8, 8, 8, 9], dtype=np.int64)
    return a, np.full(a.shape[0], 8_500_000, dtype=np.int64)


@pytest.mark.parametrize("fault", ["none", "count_off_by_one",
                                   "dropped_tie"])
def test_oracle_keeps_ties_and_compare_catches_a_fault(fault):
    a, t = _tied_events()
    want = q5full.hot_items(a, t, SLIDE, SIZE)
    # five windows, both tied auctions in each, the loser in none
    assert sorted(zip(*[c.tolist() for c in want])) == sorted(
        (auc, 3, w) for w in range(0, SIZE, SLIDE) for auc in (7, 8))
    got = [c.copy() for c in want[:2]]
    if fault == "count_off_by_one":
        got[1][3] += 1
    elif fault == "dropped_tie":
        got = [c[1:] for c in got]
    numbers = check.compare(got, want[:2], q5full.FLOAT_RTOL)
    assert all(n["ok"] for n in numbers) == (fault == "none"), numbers
