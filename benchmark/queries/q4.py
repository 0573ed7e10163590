"""NEXMark q4 'Average Price for a Category' as published (Tucker et al.;
RisingWave `ci/scripts/sql/nexmark/q4.sql`): every auction's winning price
(the MAX of the bids made inside the auction's life), averaged per category.
The statement is the source's, predicate for predicate: the equi key
`A.id = B.auction`, `B.date_time BETWEEN A.date_time AND A.expires`,
`GROUP BY A.id, A.category`, `AVG(Q.final) ... GROUP BY Q.category`. Two
sources, neither with a declared key (upstream's declare none), no watermark:
nothing ever cleans the join's state. The key skew is NEXMark's own
(`hot_auction_ratio` 2, `hot_bidder_ratio` 4: the configuration's
`generator`), because the join key IS `bid.auction`.

The numpy oracle is independent of the engine: its events come from
`benchmark/reference/nexmark_q4.py`, its sums are exact integers and its
average is one float64 division."""

from __future__ import annotations

import numpy as np

from benchmark.reference import nexmark_q4

MV = "q4"
COLUMNS = ("category", "avg")
DTYPES = (np.int64, np.float64)
# `category` (and the row count, 5) is compared exactly; `avg` to 1e-12. A
# category's sum of winning prices is < 2^44 (~50 k auctions x < 9e7), exact
# in a float64 and in the pair of f32 a TPU keeps an f64 in (48 bits of
# significand). The engine's sum is RETRACTABLE: every new maximum of an
# auction is `sum - old + new`, tens of thousands of additions a run, each of
# which could round at 2^-48 ~ 3.6e-15 of the running sum: 1e-12 leaves that
# two decades (on the chip every run read 1.5e-14 or less: the summands and
# sums are integers under 2^48, so only the division rounds; PERF.md). A sum
# kept in ONE f32 (2^-24 ~ 6e-8) reads 1e-9..1e-6 and fails the limit by
# three decades and more.
FLOAT_RTOL = 1e-12

TABLES = ("auction", "bid")


def _require_skew_options() -> None:
    """A program whose connector does not know the two skew options would
    take the DDL below, ignore them and answer for another data set: fail
    before the first statement instead (the one look at the engine in this
    file; the oracle takes nothing from it)."""
    import dataclasses

    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    missing = ({"hot_auction_ratio", "hot_bidder_ratio"}
               - {f.name for f in dataclasses.fields(NexmarkConfig)})
    if missing:
        raise RuntimeError(
            f"the nexmark connector has no option {sorted(missing)}: this "
            "program cannot make NEXMark q4's data")


def ddl(config: dict, traffic: dict, seed: int) -> list:
    _require_skew_options()
    gen = config["generator"]
    stmts = [f"SET {k} = {v}" for k, v in config["session_set"].items()]
    for t in TABLES:
        cs = traffic["chunk_size"][t]
        quota = cs * traffic["chunks_per_interval"][t]
        stmts.append(
            f"CREATE SOURCE {t} WITH (connector='nexmark', table='{t}', "
            f"chunk_size={cs}, inter_event_us={gen['inter_event_us']}, "
            f"base_time_us={nexmark_q4.base_time_us(seed)}, "
            f"hot_auction_ratio={gen['hot_auction_ratio']}, "
            f"hot_bidder_ratio={gen['hot_bidder_ratio']}, "
            f"emit_watermarks={gen['emit_watermarks']}, rate_limit={quota})")
    stmts.append(
        "CREATE MATERIALIZED VIEW q4 AS "
        "SELECT Q.category, AVG(Q.final) AS avg "
        "FROM (SELECT MAX(B.price) AS final, A.category "
        "      FROM auction A, bid B "
        "      WHERE A.id = B.auction "
        "        AND B.date_time BETWEEN A.date_time AND A.expires "
        "      GROUP BY A.id, A.category) Q "
        "GROUP BY Q.category")
    return stmts


def winning_prices(a: dict, b: dict) -> tuple:
    """(final, has): per auction of `a` the largest price among the bids of
    `b` on it made inside [date_time, expires], and whether there is one. A
    bid on an auction `a` does not hold matches nothing."""
    na = a["id"].shape[0]
    final = np.full(na, -1, np.int64)
    if na == 0 or b["auction"].shape[0] == 0:
        return final, final >= 0
    order = np.argsort(a["id"], kind="stable")
    ids = a["id"][order]
    pos = np.clip(np.searchsorted(ids, b["auction"]), 0, na - 1)
    row = order[pos]
    ok = ((ids[pos] == b["auction"])
          & (b["date_time"] >= a["date_time"][row])
          & (b["date_time"] <= a["expires"][row]))
    np.maximum.at(final, row[ok], b["price"][ok])
    return final, final >= 0


def average_per_category(category: np.ndarray, final: np.ndarray) -> list:
    """[category, avg]: an exact integer sum and count per category, one
    float64 division each."""
    cats, inv = np.unique(category, return_inverse=True)
    sums = np.zeros(cats.shape[0], np.int64)
    counts = np.zeros(cats.shape[0], np.int64)
    np.add.at(sums, inv, final)
    np.add.at(counts, inv, 1)
    if int(sums.max(initial=0)) >= 1 << 53:
        raise ValueError("a category's sum no longer fits a float64 exactly")
    return [cats.astype(np.int64),
            sums.astype(np.float64) / counts.astype(np.float64)]


def events(offsets: dict, config: dict, seed: int) -> tuple:
    """Rows `[0, committed offset)` of both tables."""
    gen = config["generator"]
    kw = dict(inter_event_us=gen["inter_event_us"],
              base_time=nexmark_q4.base_time_us(seed))
    a = nexmark_q4.auctions(0, offsets["auction"], **kw)
    b = nexmark_q4.bids(0, offsets["bid"],
                        hot_auction_ratio=gen["hot_auction_ratio"],
                        hot_bidder_ratio=gen["hot_bidder_ratio"], **kw)
    return a, b


def oracle(offsets: dict, config: dict, seed: int) -> list:
    a, b = events(offsets, config, seed)
    final, has = winning_prices(a, b)
    return average_per_category(a["category"][has], final[has])


def read_mv(session) -> list:
    return session.query("SELECT category, avg FROM q4")
