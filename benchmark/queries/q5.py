"""NEXMark q5 'Hot Items', inner aggregate: count(*) per auction per
HOP(2 s, 10 s) window. DDL, numpy oracle and MV read copied from
`chip_smoke.py` (PR 22); events from `benchmark/reference/nexmark.py`."""

from __future__ import annotations

import numpy as np

from benchmark.reference import nexmark

MV = "q5"
COLUMNS = ("auction", "window_start", "n")
DTYPES = (np.int64,) * 3
FLOAT_RTOL = 0.0


def ddl(config: dict, traffic: dict, seed: int) -> list:
    gen = config["generator"]
    cs = traffic["chunk_size"]["bid"]
    quota = cs * traffic["chunks_per_interval"]["bid"]
    stmts = [f"SET {k} = {v}" for k, v in config["session_set"].items()]
    stmts += [
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         f"chunk_size={cs}, inter_event_us={gen['inter_event_us']}, "
         f"base_time_us={nexmark.base_time_us(seed)}, "
         f"emit_watermarks={gen['emit_watermarks']}, rate_limit={quota})"),
        ("CREATE MATERIALIZED VIEW q5 AS "
         "SELECT auction, window_start, count(*) AS n "
         f"FROM HOP(bid, date_time, {config['hop_slide_us']}, "
         f"{config['hop_size_us']}) GROUP BY auction, window_start"),
    ]
    return stmts


def oracle(offsets: dict, config: dict, seed: int) -> list:
    n = offsets["bid"]
    slide, size = config["hop_slide_us"], config["hop_size_us"]
    ev = nexmark.bids(0, n,
                      inter_event_us=config["generator"]["inter_event_us"],
                      base_time=nexmark.base_time_us(seed))
    a, t = ev["auction"], ev["date_time"]
    if n == 0:
        return [np.zeros(0, np.int64)] * 3
    base = (t // slide) * slide
    k = size // slide
    aa = np.tile(a, k)
    ws = np.concatenate([base - j * slide for j in range(k)])
    # one int64 key per (auction, window) pair: a 1-D unique is far cheaper
    # than np.unique(axis=0) on millions of rows
    w0 = int(ws.min())
    wi = (ws - w0) // slide
    assert int(wi.max()) < 1 << 24 and int(aa.max()) < 1 << 38
    key, counts = np.unique((aa << 24) | wi, return_counts=True)
    return [key >> 24, (key & ((1 << 24) - 1)) * slide + w0,
            counts.astype(np.int64)]


def read_mv(session) -> list:
    return session.query("SELECT auction, window_start, n FROM q5")
