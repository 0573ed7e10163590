"""NEXMark q8 'Monitor New Users' as published (Tucker et al.; RisingWave
`ci/scripts/sql/nexmark/q8.sql`): the people who registered and opened an
auction within the same 10 s tumbling window. The statement is the source's,
column for column and predicate for predicate: `P.name` (VARCHAR) projected
and grouped by, both `GROUP BY`s (each a DISTINCT over its key and window),
the join on `P.id = A.seller` and both window bounds; `INTERVAL '10' SECOND`
is written `10000000` (microseconds), as this parser takes it. Neither source
declares a key (upstream's declare none). The seller skew is NEXMark's own
(`hot_seller_bucket` 100: the configuration's `generator`), because the join
key IS `auction.seller`.

The MV holds a string, so the cell reports `recovery_s`: that puts it on
`check.reopen_and_compare`'s SQL read path (`read_mv` below), which decodes a
VARCHAR cell; the store-scan branch would hand back a process-local
dictionary id. The numpy oracle is independent of the engine: its events,
and the name as TEXT by its own rule, come from
`benchmark/reference/nexmark_q8.py`."""

from __future__ import annotations

import numpy as np

from benchmark.reference import nexmark_q8

MV = "q8"
COLUMNS = ("id", "name", "starttime")
# `name` compared as a string, cell for cell, limit 0 (`check.compare`
# takes a numpy `U` column as it stands: `col1_cells_differing`)
DTYPES = (np.int64, np.dtype("U16"), np.int64)
FLOAT_RTOL = 0.0                      # no float column

TABLES = ("person", "auction")


def _require_seller_option() -> None:
    """A program whose connector does not know `hot_seller_bucket` would
    take the DDL below, ignore the option and answer for another data set:
    fail before the first statement instead (the one look at the engine in
    this file; the oracle takes nothing from it)."""
    import dataclasses

    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    if "hot_seller_bucket" not in {f.name for f in
                                   dataclasses.fields(NexmarkConfig)}:
        raise RuntimeError(
            "the nexmark connector has no option 'hot_seller_bucket': this "
            "program cannot make NEXMark q8's data")


def ddl(config: dict, traffic: dict, seed: int) -> list:
    _require_seller_option()
    gen = config["generator"]
    w = config["window_us"]
    stmts = [f"SET {k} = {v}" for k, v in config["session_set"].items()]
    for t in TABLES:
        cs = traffic["chunk_size"][t]
        quota = cs * traffic["chunks_per_interval"][t]
        stmts.append(
            f"CREATE SOURCE {t} WITH (connector='nexmark', table='{t}', "
            f"chunk_size={cs}, inter_event_us={gen['inter_event_us']}, "
            f"base_time_us={nexmark_q8.base_time_us(seed)}, "
            f"hot_seller_bucket={gen['hot_seller_bucket']}, "
            f"emit_watermarks={gen['emit_watermarks']}, "
            f"watermark_lag_us={gen['watermark_lag_us']}, "
            f"rate_limit={quota})")
    stmts.append(
        "CREATE MATERIALIZED VIEW q8 AS "
        "SELECT P.id, P.name, P.starttime "
        "FROM (SELECT id, name, window_start AS starttime, "
        "             window_end AS endtime "
        f"      FROM TUMBLE(person, date_time, {w}) "
        "      GROUP BY id, name, window_start, window_end) P "
        "JOIN (SELECT seller, window_start AS starttime, "
        "             window_end AS endtime "
        f"      FROM TUMBLE(auction, date_time, {w}) "
        "      GROUP BY seller, window_start, window_end) A "
        "ON P.id = A.seller AND P.starttime = A.starttime "
        "AND P.endtime = A.endtime")
    return stmts


def events(offsets: dict, config: dict, seed: int) -> tuple:
    """Rows `[0, committed offset)` of both tables."""
    gen = config["generator"]
    kw = dict(inter_event_us=gen["inter_event_us"],
              base_time=nexmark_q8.base_time_us(seed))
    p = nexmark_q8.persons(0, offsets["person"], **kw)
    a = nexmark_q8.auctions(0, offsets["auction"],
                            hot_seller_bucket=gen["hot_seller_bucket"], **kw)
    return p, a


def oracle(offsets: dict, config: dict, seed: int) -> list:
    p, a = events(offsets, config, seed)
    return nexmark_q8.new_users(p, a, config["window_us"])


def read_mv(session) -> list:
    return session.query("SELECT id, name, starttime FROM q8")
