"""NEXMark q7 'Highest Bid': the bids at their 10 s tumble window's maximum
price. DDL, the numpy oracle over rows `[0, committed offset)`, and the MV
read — copied from `chip_smoke.py` (PR 22); the oracle's events come from
`benchmark/reference/nexmark.py`, not from the engine."""

from __future__ import annotations

import numpy as np

from benchmark.reference import nexmark

MV = "q7"
COLUMNS = ("auction", "price", "bidder", "date_time")
DTYPES = (np.int64,) * 4
FLOAT_RTOL = 0.0                      # every column is an integer


def ddl(config: dict, traffic: dict, seed: int) -> list:
    w = config["window_us"]
    gen = config["generator"]
    cs = traffic["chunk_size"]["bid"]
    quota = cs * traffic["chunks_per_interval"]["bid"]
    stmts = [f"SET {k} = {v}" for k, v in config["session_set"].items()]
    stmts += [
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         f"chunk_size={cs}, inter_event_us={gen['inter_event_us']}, "
         f"base_time_us={nexmark.base_time_us(seed)}, "
         f"emit_watermarks={gen['emit_watermarks']}, "
         f"watermark_lag_us={gen['watermark_lag_us']}, "
         f"rate_limit={quota})"),
        ("CREATE MATERIALIZED VIEW q7 AS "
         "SELECT B.auction, B.price, B.bidder, B.date_time "
         "FROM bid B JOIN ("
         "  SELECT max(price) AS maxprice, window_end "
         f"  FROM TUMBLE(bid, date_time, {w}) GROUP BY window_end) B1 "
         "ON B.price = B1.maxprice "
         f"AND B.date_time > B1.window_end - {w} "
         "AND B.date_time <= B1.window_end"),
    ]
    return stmts


def oracle(offsets: dict, config: dict, seed: int) -> list:
    n = offsets["bid"]
    w = config["window_us"]
    ev = nexmark.bids(0, n,
                      inter_event_us=config["generator"]["inter_event_us"],
                      base_time=nexmark.base_time_us(seed))
    a, b, p, t = ev["auction"], ev["bidder"], ev["price"], ev["date_time"]
    if n == 0:
        return [np.zeros(0, np.int64)] * 4
    # event time is monotone in the event id, so a window is a contiguous
    # run of rows: window_end - W < t <= window_end
    we = ((t + w - 1) // w) * w
    starts = np.flatnonzero(np.r_[True, we[1:] != we[:-1]])
    wmax = np.maximum.reduceat(p, starts)
    keep = p == np.repeat(wmax, np.diff(np.r_[starts, n]))
    return [a[keep], p[keep], b[keep], t[keep]]


def read_mv(session) -> list:
    return session.query("SELECT auction, price, bidder, date_time FROM q7")
