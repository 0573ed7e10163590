"""NEXMark q19 'Auction TOP-10 Price' as published (the NEXMark suite's
extended queries: `nexmark-flink` `q19.sql`; RisingWave
`ci/scripts/sql/nexmark/q19.sql`): the ten highest bids of every auction,
with their rank. The statement is the source's, token for token: `SELECT *`
twice, `ROW_NUMBER() OVER (PARTITION BY auction ORDER BY price DESC) AS
rank_number`, `WHERE rank_number <= 10`, and NO alias on the FROM subquery
(upstream's file has none). The source declares no key (upstream's declares
none): the planner gives each bid a generated row id, which is the stream
key that breaks ties on the price (the earlier bid first, as upstream's group
top-N orders its cache by the order key and then the stream key). The key
skew is NEXMark's own (`hot_auction_ratio` 2, `hot_bidder_ratio` 4: the
configuration's `generator`), because the partition key IS `bid.auction`.

The numpy oracle is independent of the engine: its bids, the three strings as
TEXT by its own rule, and the ranking come from
`benchmark/reference/nexmark_q19.py`. The cell times no recovery, so its MV
is read by `check._read_mv_from_store`, which hands a VARCHAR cell back as
the id this process's dictionary gives the string: `oracle` states each of
the reference's strings as that id by a lookup that NEVER inserts (a string
the dictionary lacks is -1: a mismatch) and the ids are compared with limit
0 — exact, because the dictionary is injective. `oracle_text` / `read_mv`
are the same comparison as TEXT through SQL (`tests/test_q19_published.py`).
All eight columns are compared."""

from __future__ import annotations

import numpy as np

from benchmark.reference import nexmark_q19

MV = "q19"
COLUMNS = ("auction", "bidder", "price", "channel", "url", "date_time",
           "extra", "rank_number")
STRINGS = (3, 4, 6)
# the store scan's cells: the strings as dictionary ids
DTYPES = (np.int64,) * 8
# the SQL read's cells: the strings as text
TEXT_DTYPES = tuple(np.dtype("U40") if j in STRINGS else np.int64
                    for j in range(8))
FLOAT_RTOL = 0.0                      # no float column

TABLES = ("bid",)
TOP = 10


def _require_skew_options() -> None:
    """A program whose connector does not know the two skew options would
    take the DDL below, ignore them and answer for another data set: fail
    before the first statement instead."""
    import dataclasses

    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    missing = ({"hot_auction_ratio", "hot_bidder_ratio"}
               - {f.name for f in dataclasses.fields(NexmarkConfig)})
    if missing:
        raise RuntimeError(
            f"the nexmark connector has no option {sorted(missing)}: this "
            "program cannot make NEXMark q19's data")


def ddl(config: dict, traffic: dict, seed: int) -> list:
    _require_skew_options()
    gen = config["generator"]
    stmts = [f"SET {k} = {v}" for k, v in config["session_set"].items()]
    cs = traffic["chunk_size"]["bid"]
    quota = cs * traffic["chunks_per_interval"]["bid"]
    stmts.append(
        f"CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
        f"chunk_size={cs}, inter_event_us={gen['inter_event_us']}, "
        f"base_time_us={nexmark_q19.base_time_us(seed)}, "
        f"hot_auction_ratio={gen['hot_auction_ratio']}, "
        f"hot_bidder_ratio={gen['hot_bidder_ratio']}, "
        f"emit_watermarks={gen['emit_watermarks']}, rate_limit={quota})")
    stmts.append(
        "CREATE MATERIALIZED VIEW q19 AS "
        "SELECT * FROM "
        "(SELECT *, ROW_NUMBER() OVER "
        "(PARTITION BY auction ORDER BY price DESC) AS rank_number "
        "FROM bid) "
        f"WHERE rank_number <= {TOP}")
    return stmts


def events(offsets: dict, config: dict, seed: int) -> dict:
    """Bids `[0, committed offset)`."""
    gen = config["generator"]
    return nexmark_q19.bids(
        0, offsets["bid"], inter_event_us=gen["inter_event_us"],
        base_time=nexmark_q19.base_time_us(seed),
        hot_auction_ratio=gen["hot_auction_ratio"],
        hot_bidder_ratio=gen["hot_bidder_ratio"])


def oracle_text(offsets: dict, config: dict, seed: int) -> list:
    return nexmark_q19.q19(events(offsets, config, seed), TOP)


def dictionary_ids(strings: np.ndarray) -> np.ndarray:
    """Each string as the id this process's dictionary gives it, -1 where it
    has none (the one look at the engine below `ddl`: the store scan hands
    ids back, and only the process that wrote them can name them)."""
    from risingwave_tpu.common.types import GLOBAL_DICT
    uniq, inv = np.unique(strings, return_inverse=True)
    ids = np.asarray([-1 if (i := GLOBAL_DICT.lookup(str(s))) is None else i
                      for s in uniq], dtype=np.int64)
    return ids[inv] if uniq.size else np.zeros(0, np.int64)


def oracle(offsets: dict, config: dict, seed: int) -> list:
    cols = oracle_text(offsets, config, seed)
    return [dictionary_ids(c) if j in STRINGS else c
            for j, c in enumerate(cols)]


def read_mv(session) -> list:
    return session.query(f"SELECT {', '.join(COLUMNS)} FROM q19")
