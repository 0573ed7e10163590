"""NEXMark q4 'Average Price for a Category' AS PUBLISHED (two sources with
no declared key, a join no watermark cleans, MAX per auction into a
retractable FLOAT64 AVG per category, NEXMark's own 50% / 75% key skew),
through `Session` -> binder -> plan -> actors with no option of its own,
against the benchmark's numpy oracle (`benchmark/queries/q4.py`, which takes
nothing from the engine) on seeded offsets; the connector's two skew options
and their defaults; the counts the join's watchdog fetch brings to the epoch
trace; and the two bounds the configuration's SETs were sized by.
"""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, drive
from benchmark.queries import q4
from benchmark.reference import nexmark, nexmark_q4
from risingwave_tpu.common.chunk import StreamChunk
from risingwave_tpu.connectors.nexmark import (
    NexmarkConfig, NexmarkGenerator, gen_bid_columns)
from risingwave_tpu.frontend import Session
from risingwave_tpu.frontend.binder import BindError
from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
from risingwave_tpu.stream.align import LEFT, RIGHT
from risingwave_tpu.stream import sorted_join
from risingwave_tpu.stream.sorted_join import SortedJoinExecutor
from risingwave_tpu.utils.metrics import (
    GLOBAL_METRICS, JOIN_MATCH_BUFFER_PEAK, JOIN_MATCH_ROWS)

SEED = 2147483659
# NEXMark's 46 : 3, as the cell's traffic: both sources cover the same
# 1,600 events every checkpoint
BIDS, AUCTIONS = 46 * 32, 3 * 32
TRAFFIC = {"chunk_size": {"bid": BIDS, "auction": AUCTIONS},
           "chunks_per_interval": {"bid": 1, "auction": 1}}
FACTOR = 16                          # the configuration's: 16 x 3 >= 46


def _config(join: int = 1 << 15, agg: int = 1 << 12,
            inter_event_us: int = 100, **gen) -> dict:
    return {"generator": {"inter_event_us": inter_event_us,
                          "emit_watermarks": 0, "hot_auction_ratio": 2,
                          "hot_bidder_ratio": 4, **gen},
            "session_set": {"streaming_join_capacity": join,
                            "streaming_join_match_factor": FACTOR,
                            "streaming_agg_capacity": agg,
                            "streaming_watchdog": 1}}


async def _deploy(s: Session, cfg: dict) -> SortedJoinExecutor:
    for stmt in q4.ddl(cfg, TRAFFIC, SEED):
        await s.execute(stmt)
    join, = [ex for ex in drive.executors_of(s, q4.MV)
             if isinstance(ex, SortedJoinExecutor)]
    return join


class _Gated:
    """One join input; a chunk of the `late` side passes only after the join
    has applied the other side's chunk of the same interval."""

    def __init__(self, inner, side: int, late: int, taken: list,
                 applied: list):
        self.inner, self.side, self.late = inner, side, late
        self.taken, self.applied = taken, applied

    async def execute(self):
        s = self.side
        async for msg in self.inner.execute():
            if not isinstance(msg, StreamChunk):
                yield msg
                continue
            while s == self.late and self.applied[1 - s] <= self.taken[s]:
                await asyncio.sleep(0)
            self.taken[s] += 1
            yield msg
            # resumed: the join consumed the chunk and asks for the next
            self.applied[s] += 1


def _hold_back(monkeypatch, late: int) -> None:
    """Every sorted join deployed from here on sees its `late` input's
    chunks after the other input's."""
    real = sorted_join.barrier_align

    def align(left, right):
        taken, applied = [0, 0], [0, 0]
        return real(_Gated(left, LEFT, late, taken, applied),
                    _Gated(right, RIGHT, late, taken, applied))
    monkeypatch.setattr(sorted_join, "barrier_align", align)


def _read(s: Session) -> list:
    return check.rows_to_cols(q4.read_mv(s), q4.DTYPES)


def _assert_is_the_oracles(got: list, offsets: dict, cfg: dict) -> None:
    numbers = check.compare(got, q4.oracle(offsets, cfg, SEED),
                            q4.FLOAT_RTOL)
    assert all(n["ok"] for n in numbers), numbers
    assert got[0].shape[0] == nexmark_q4.NUM_CATEGORIES


def _phases(s: Session) -> dict:
    """The join's actor's counts in the newest epoch's phase dict."""
    for ph in s.coord.tracer._ring[-1].phases.values():
        if "join_live_rows" in ph:
            return {k: v for k, v in ph.items() if not k.endswith("_ns")}
    return {}


# ------------------------------------------------ the query, both orders

def test_the_ddl_is_upstreams_statement_and_its_sources_declare_no_key():
    *sets, auction, bid, mv = q4.ddl(_config(), TRAFFIC, SEED)
    assert all(s.startswith("SET ") for s in sets)
    for src in (auction, bid):
        assert "primary_key" not in src
        assert "hot_auction_ratio=2" in src and "hot_bidder_ratio=4" in src
    # tests/test_nexmark_queries.py::test_q4_golden's text, word for word
    assert " ".join(mv.split()) == (
        "CREATE MATERIALIZED VIEW q4 AS "
        "SELECT Q.category, AVG(Q.final) AS avg "
        "FROM (SELECT MAX(B.price) AS final, A.category "
        "FROM auction A, bid B "
        "WHERE A.id = B.auction "
        "AND B.date_time BETWEEN A.date_time AND A.expires "
        "GROUP BY A.id, A.category) Q "
        "GROUP BY Q.category")


@pytest.mark.parametrize("late", [RIGHT, LEFT],
                         ids=["auctions_before_bids", "bids_before_auctions"])
async def test_both_arrival_orders_give_the_oracles_view_and_it_survives_a_crash(
        tmp_path, monkeypatch, late):
    """The configuration's own DDL, durable. Whichever source's chunk the
    join applies first in an interval — the auctions (every bid then finds
    its auction stored) or the bids (every auction chunk then finds nearly
    all of its interval's bids stored: the fan-out the match factor was
    sized for) — the MV is the oracle's: read live, after `crash()` +
    `recover()` over the reopened store, and after two more checkpoints."""
    root = str(tmp_path / "hummock")
    cfg = _config()
    s = Session(store=HummockStateStore(LocalFsObjectStore(root)))
    _hold_back(monkeypatch, late)
    join = await _deploy(s, cfg)
    assert join.append_only == (True, True)
    assert join.clean_specs == (None, None), "a watermark cleans nothing here"
    assert join.match_factors == (FACTOR, FACTOR)
    await s.tick(4)
    offsets = drive.committed_offsets(s, q4.MV)
    assert offsets == {"auction": 4 * AUCTIONS, "bid": 4 * BIDS}
    _assert_is_the_oracles(_read(s), offsets, cfg)
    # the order was the one asked for: the auction chunk's candidates are
    # the bids of its interval on its own auctions when they came first
    # (two thirds of them at 96 auctions a chunk, the others are on the
    # chunk before; 97% and more at the cell's 3,072), none when they come
    # after
    ph = _phases(s)
    if late == LEFT:
        assert ph["join_match_width"] == FACTOR * AUCTIONS
        assert BIDS // 2 < ph["join_match_peak"] <= BIDS
    else:
        assert (ph["join_match_peak"], ph["join_match_width"]) == (
            BIDS, FACTOR * BIDS)
    await s.crash()
    del s
    s2 = Session(store=HummockStateStore.open(LocalFsObjectStore(root)))
    await s2.recover()
    _assert_is_the_oracles(_read(s2), offsets, cfg)
    await s2.tick(2)
    offsets = drive.committed_offsets(s2, q4.MV)
    assert offsets == {"auction": 6 * AUCTIONS, "bid": 6 * BIDS}
    _assert_is_the_oracles(_read(s2), offsets, cfg)
    await s2.crash()


async def test_bids_outside_an_auctions_life_do_not_count():
    """100 ms between events: an auction's 100 in-flight successors span
    167 s, its life is 1..100 s, so a good share of the bids on it come
    after `expires` and the BETWEEN decides the view (at the cell's 100 us
    every bid falls inside)."""
    cfg = _config(inter_event_us=100_000)
    a, b = q4.events({"auction": 4 * AUCTIONS, "bid": 4 * BIDS}, cfg, SEED)
    late_bids = b["date_time"] > a["expires"][np.clip(
        b["auction"] - nexmark.FIRST_AUCTION_ID, 0, 4 * AUCTIONS - 1)]
    assert 0.1 < late_bids.mean() < 0.9
    s = Session()
    await _deploy(s, cfg)
    await s.tick(4)
    _assert_is_the_oracles(
        _read(s), {"auction": 4 * AUCTIONS, "bid": 4 * BIDS}, cfg)
    await s.drop_all()


# ----------------------------------------------------- the oracle catches

def _events(n: int = 8):
    return q4.events({"auction": n * AUCTIONS, "bid": n * BIDS}, _config(),
                     SEED)


def _answer(a: dict, b: dict) -> list:
    final, has = q4.winning_prices(a, b)
    return q4.average_per_category(a["category"][has], final[has])


def _drop_an_auction(a, b):
    keep = np.arange(a["id"].shape[0]) != 17
    return {k: v[keep] for k, v in a.items()}, b


def _price_off_by_one(a, b):
    final, _ = q4.winning_prices(a, b)
    win = int(np.flatnonzero(
        (b["auction"] == a["id"][17]) & (b["price"] == final[17]))[0])
    price = b["price"].copy()
    price[win] += 1
    return a, {**b, "price": price}


def _bid_past_expires(a, b):
    final, _ = q4.winning_prices(a, b)
    on17 = b["auction"] == a["id"][17]
    assert np.count_nonzero(on17 & (b["price"] == final[17])) == 1
    win = int(np.flatnonzero(on17 & (b["price"] == final[17]))[0])
    date_time = b["date_time"].copy()
    date_time[win] = a["expires"][17] + 1
    return a, {**b, "date_time": date_time}


@pytest.mark.parametrize("alter", [_drop_an_auction, _price_off_by_one,
                                   _bid_past_expires])
def test_the_oracle_catches(alter):
    a, b = _events()
    want = _answer(a, b)
    assert all(n["ok"] for n in check.compare(want, want, q4.FLOAT_RTOL))
    numbers = check.compare(_answer(*alter(a, b)), want, q4.FLOAT_RTOL)
    assert [n["what"] for n in numbers if not n["ok"]] == [
        "col1_max_rel_diff"], numbers


def test_an_average_kept_in_float32_is_not_correct():
    """The limit lies between the two readings: a float64 (or, on a TPU, a
    pair of f32: 48 bits) sum reads 0 or a few 1e-15, one f32 ~1e-8."""
    a, b = _events()
    cats, avg = _answer(a, b)
    as_f32 = [cats, avg.astype(np.float32).astype(np.float64)]
    n, = [n for n in check.compare(as_f32, [cats, avg], q4.FLOAT_RTOL)
          if n["what"] == "col1_max_rel_diff"]
    assert not n["ok"] and 1e-9 < n["value"] < 1e-6
    # and a sum that lost its last f32-pair bit (2^-48) still passes
    nudged = [cats, avg * (1 + 2.0 ** -46)]
    assert all(n["ok"] for n in check.compare(nudged, [cats, avg],
                                              q4.FLOAT_RTOL))


# ------------------------------------------------------ the skew options

def _engine_bids(n: int, **cfg) -> list:
    gen = NexmarkGenerator("bid", chunk_size=n, cfg=NexmarkConfig(
        inter_event_us=100, base_time_us=nexmark.base_time_us(SEED), **cfg))
    cols = gen_bid_columns(jnp.int64(0), n, gen.cfg, gen._vocabs)
    return [np.asarray(cols[j]) for j in (0, 1, 2, 5)]


def test_the_defaults_keep_every_older_cells_rows_bit_for_bit():
    """`benchmark/reference/nexmark.py` (not edited) is what the five older
    cells' oracles regenerate: the connector's defaults still make it."""
    assert (NexmarkConfig().hot_auction_ratio,
            NexmarkConfig().hot_bidder_ratio) == (100, 100)
    n = 50_000
    ref = nexmark.bids(0, n, inter_event_us=100,
                       base_time=nexmark.base_time_us(SEED))
    for got, k in zip(_engine_bids(n), ("auction", "bidder", "price",
                                        "date_time")):
        assert got.dtype == np.int64 and np.array_equal(got, ref[k]), k
    mine = nexmark_q4.bids(0, n, inter_event_us=100,
                           base_time=nexmark.base_time_us(SEED),
                           hot_auction_ratio=100, hot_bidder_ratio=100)
    assert all(np.array_equal(mine[k], ref[k]) for k in ref)


def test_ratios_2_and_4_are_nexmarks_skew_and_the_reference_follows():
    n = 200_000
    got = _engine_bids(n, hot_auction_ratio=2, hot_bidder_ratio=4)
    ref = nexmark_q4.bids(0, n, inter_event_us=100,
                          base_time=nexmark.base_time_us(SEED),
                          hot_auction_ratio=2, hot_bidder_ratio=4)
    for g, k in zip(got, ("auction", "bidder", "price", "date_time")):
        assert np.array_equal(g, ref[k]), k
    # a hot id is the first of its bucket of 100 (the bidder's: + 1); a cold
    # one is drawn from the last 100 / 1000 and lands there 1 time in 100 /
    # 1000: 50.5% and 75.0%, three sigma of 200,000 draws is 0.34 points
    hot_auction = (got[0] - nexmark.FIRST_AUCTION_ID) % 100 == 0
    hot_bidder = (got[1] - nexmark.FIRST_PERSON_ID) % 100 == 1
    assert hot_auction.mean() == pytest.approx(0.505, abs=0.004)
    assert hot_bidder.mean() == pytest.approx(0.750, abs=0.004)
    # the defaults: 99% and 99%
    old = _engine_bids(n)
    assert ((old[0] - nexmark.FIRST_AUCTION_ID) % 100 == 0).mean() > 0.985


def test_the_reference_auctions_are_the_engines():
    n = 3 * 4096
    gen = NexmarkGenerator("auction", chunk_size=n, cfg=NexmarkConfig(
        inter_event_us=100, base_time_us=nexmark.base_time_us(SEED)))
    cols = gen.next_chunk().columns
    ref = nexmark_q4.auctions(0, n, inter_event_us=100,
                              base_time=nexmark.base_time_us(SEED))
    for j, k in ((0, "id"), (5, "date_time"), (6, "expires"),
                 (8, "category")):
        assert np.array_equal(np.asarray(cols[j].data), ref[k]), k
    assert set(np.unique(ref["category"])) == set(range(10, 15))


async def test_a_ratio_below_one_is_refused():
    s = Session()
    with pytest.raises(BindError, match="hot_auction_ratio"):
        await s.execute("CREATE SOURCE bid WITH (connector='nexmark', "
                        "table='bid', hot_auction_ratio=0)")


# ------------------------------------------ what the watchdog fetch brings

async def test_the_phase_dict_carries_the_joins_counts():
    """`join_match_rows`, `join_match_peak` / `join_match_width`,
    `join_live_rows` / `join_capacity`, from the ONE fetch the join makes a
    barrier (no new fetch: the d2h count of an interval is what it was)."""
    from risingwave_tpu.utils import metrics as m
    s = Session()
    join = await _deploy(s, _config())
    label = join.mem_name or join.identity
    rows0 = [GLOBAL_METRICS.counter(JOIN_MATCH_ROWS, executor=label,
                                    side=sd).value
             for sd in ("left", "right")]
    await s.tick(2)
    fetches = int(m.D2H_FETCHES.value)
    await s.tick(1)
    # join watchdog 1 + MAX agg watchdog 1 + AVG agg watchdog 1 + their
    # flushes' data: whatever it is, one more tick makes as many again
    per_tick = int(m.D2H_FETCHES.value) - fetches
    await s.tick(1)
    assert int(m.D2H_FETCHES.value) - fetches == 2 * per_tick
    ph = _phases(s)
    assert ph["join_live_rows"] == 4 * BIDS
    assert ph["join_capacity"] == 1 << 15
    # every bid of the interval met its auction, whichever came first
    assert ph["join_match_rows"] == BIDS
    assert 0 < ph["join_match_peak"] <= ph["join_match_width"]
    rows = [GLOBAL_METRICS.counter(JOIN_MATCH_ROWS, executor=label,
                                   side=sd).value - r0
            for sd, r0 in zip(("left", "right"), rows0)]
    assert sum(rows) == 4 * BIDS
    peaks = [GLOBAL_METRICS.gauge(JOIN_MATCH_BUFFER_PEAK, executor=label,
                                  side=sd).value for sd in ("left", "right")]
    assert max(peaks) == ph["join_match_peak"]
    text = s.coord.tracer._ring[-1].render()
    assert f"join holds {4 * BIDS} of {1 << 15} rows, matched {BIDS}" in text
    await s.drop_all()


# --------------------------------- the two bounds the SETs were sized by

def test_an_auction_chunk_meets_at_most_its_own_intervals_bids():
    """Both sources cover the same events every checkpoint and a bid's
    auction precedes it in event time: whatever the order inside an interval,
    an auction chunk finds at most the 46 x k bids of its own interval in the
    pool (factor 16 x 3 x k >= 46 x k), and a bid at most one auction."""
    n = 40
    a, b = q4.events({"auction": n * 3072, "bid": n * 47104}, _config(),
                     SEED)
    bid_interval = np.arange(n * 47104) // 47104
    auction_interval = (b["auction"] - nexmark.FIRST_AUCTION_ID) // 3072
    assert np.all(auction_interval <= bid_interval)
    own = np.bincount(bid_interval[auction_interval == bid_interval],
                      minlength=n)
    assert own.max() == 47104 and own.min() > 0.97 * 47104
    assert own.max() <= FACTOR * 3072
    assert np.unique(a["id"]).shape[0] == a["id"].shape[0]


async def test_a_pool_that_fills_past_its_growth_mark_is_a_statejit_compile():
    """Nothing cleans q4's pools: the bid side doubles at 0.7 occupancy,
    which re-traces the applies. The cell counts on seeing that as a StateJit
    compile in its window (`check.health`), so its capacity is sized for the
    run: here 0.7 x 2^13 rows hold three checkpoints of bids and not four."""
    s = Session()
    join = await _deploy(s, _config(join=1 << 13))
    await s.tick(3)
    before = drive.compiles_by_program()
    assert join.capacity == [1 << 13, 1 << 13] and join.rebuilds == 0
    await s.tick(2)
    assert join.capacity == [1 << 13, 1 << 14] and join.rebuilds == 1
    after = drive.compiles_by_program()
    assert (after["sorted_join_apply_counted"]
            > before["sorted_join_apply_counted"])
    _assert_is_the_oracles(
        _read(s), {"auction": 5 * AUCTIONS, "bid": 5 * BIDS}, _config())
    await s.drop_all()
