"""NEXMark q5 'Hot Items' as published (Tucker et al.; RisingWave
`ci/scripts/sql/nexmark/q5.sql`): the auctions whose bid count in a
HOP(2 s, 10 s) window reaches that window's maximum count. The SQL is the
source's, column for column: its two-column projection, and its join
predicate `starttime = starttime_c AND num >= maxn` — the window is the
join's only equi key, the comparison with the maximum its condition (Tucker's
CQL writes `num >= ALL`), so a window's counts all stand under one join key
and a maximum that moves is compared with every one of them. The numpy
oracle below is independent of the engine (its events come from
`benchmark/reference/nexmark.py`) and repeats the hop-expand + 1-D unique of
`queries/q5.py`'s oracle, as that file may not be imported for a part; no
count passes its window's maximum, so `>=` keeps the rows `==` keeps."""

from __future__ import annotations

import numpy as np

from benchmark.reference import nexmark

MV = "q5full"
# the MV's two hidden stream-key columns have engine-internal names and are
# not compared: `check.compare` sorts both sides, so this is a comparison of
# multisets — one row per (window, auction at that window's maximum)
COLUMNS = ("auction", "num")
DTYPES = (np.int64,) * 2
FLOAT_RTOL = 0.0                      # every column is an integer


def ddl(config: dict, traffic: dict, seed: int) -> list:
    gen = config["generator"]
    cs = traffic["chunk_size"]["bid"]
    quota = cs * traffic["chunks_per_interval"]["bid"]
    hop = (f"HOP(bid, date_time, {config['hop_slide_us']}, "
           f"{config['hop_size_us']})")
    stmts = [f"SET {k} = {v}" for k, v in config["session_set"].items()]
    stmts += [
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         f"chunk_size={cs}, inter_event_us={gen['inter_event_us']}, "
         f"base_time_us={nexmark.base_time_us(seed)}, "
         f"emit_watermarks={gen['emit_watermarks']}, rate_limit={quota})"),
        ("CREATE MATERIALIZED VIEW q5full AS "
         "SELECT AuctionBids.auction, AuctionBids.num FROM ("
         "  SELECT auction, count(*) AS num, window_start AS starttime "
         f"  FROM {hop} GROUP BY window_start, auction) AuctionBids "
         "JOIN ("
         "  SELECT max(CountBids.num) AS maxn, CountBids.starttime_c FROM ("
         "    SELECT count(*) AS num, window_start AS starttime_c "
         f"    FROM {hop} GROUP BY auction, window_start) CountBids "
         "  GROUP BY CountBids.starttime_c) MaxBids "
         "ON AuctionBids.starttime = MaxBids.starttime_c "
         "AND AuctionBids.num >= MaxBids.maxn"),
    ]
    return stmts


def hot_items(auction: np.ndarray, date_time: np.ndarray, slide: int,
              size: int) -> list:
    """[auction, num, window_start] of every (window, auction) whose count
    is its window's maximum; ties kept: every auction at the maximum is a
    row, an auction that leads several windows stands once per window."""
    if auction.shape[0] == 0:
        return [np.zeros(0, np.int64)] * 3
    base = (date_time // slide) * slide
    k = size // slide
    aa = np.tile(auction, k)
    ws = np.concatenate([base - j * slide for j in range(k)])
    # one int64 key per (auction, window) pair: a 1-D unique is far cheaper
    # than np.unique(axis=0) on millions of rows
    w0 = int(ws.min())
    wi = (ws - w0) // slide
    assert int(wi.max()) < 1 << 24 and int(aa.max()) < 1 << 38
    key, counts = np.unique((aa << 24) | wi, return_counts=True)
    win = key & ((1 << 24) - 1)
    wmax = np.zeros(int(win.max()) + 1, np.int64)
    np.maximum.at(wmax, win, counts)
    keep = counts == wmax[win]
    return [key[keep] >> 24, counts[keep].astype(np.int64),
            win[keep] * slide + w0]


def oracle(offsets: dict, config: dict, seed: int) -> list:
    ev = nexmark.bids(0, offsets["bid"],
                      inter_event_us=config["generator"]["inter_event_us"],
                      base_time=nexmark.base_time_us(seed))
    return hot_items(ev["auction"], ev["date_time"], config["hop_slide_us"],
                     config["hop_size_us"])[:2]


def read_mv(session) -> list:
    return session.query("SELECT auction, num FROM q5full")
