"""Bid events with all seven columns, and NEXMark q19 / q18 over them, in
plain numpy — the benchmark's own copy of what the engine's nexmark connector
makes and of what a group top-N answers.

The four integer columns (auction, bidder, price, date_time) and the key skew
are `nexmark_q4.bids`' (NEXMark's own 50% / 75% under `hot_auction_ratio=2,
hot_bidder_ratio=4`). The three strings are stated here as TEXT by the
generator's public rule, restated and not imported: `channel` one of 4 names
(salt 7), `url` one of 1,000 (`https://b.example.com/item/<i>`, salt 8),
`extra` one of 100 (`extra_<i>`, salt 9), each picked by the same
counter-based hash of the event id as every other column. (The public
generator draws a channel of 4 with its own url for 90% of the bids and a
random one otherwise, and pads `extra` to an average bid size: stated as a
deviation in the configuration — the widths are the same, one dictionary id a
cell on the device, one VARCHAR cell in the MV.)

`top_n` is the rule both queries are: rows ranked within their partition by
the order columns, TIES by arrival (the event index ascending — upstream's
group top-N orders by the stream key after the order key, and a keyless
source's stream key is the generated row id), the rows of rank <= n kept.
q19 is `top_n(partition = auction, price DESC, n = 10)` with the rank as a
column; q18 `top_n(partition = (bidder, auction), date_time DESC, n = 1)`
without it.

Imports nothing of `risingwave_tpu`.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import nexmark, nexmark_q4
from benchmark.reference.nexmark import _rand

CHANNELS = np.asarray(["apple", "google", "baidu", "facebook"])
URLS = np.asarray([f"https://b.example.com/item/{i}" for i in range(1000)])
EXTRAS = np.asarray([f"extra_{i}" for i in range(100)])

COLUMNS = ("auction", "bidder", "price", "channel", "url", "date_time",
           "extra")
VOCABULARY = {"channel": CHANNELS, "url": URLS, "extra": EXTRAS}


def bids(start: int, n: int, *, inter_event_us: int, base_time: int,
         hot_auction_ratio: int, hot_bidder_ratio: int) -> dict:
    """Columns of bids `start .. start+n-1`: the four integers as int64, the
    three strings as the generator's PICK into their vocabulary (`column`
    states them as text: millions of bids as text at once are gigabytes)."""
    b = nexmark_q4.bids(start, n, inter_event_us=inter_event_us,
                        base_time=base_time,
                        hot_auction_ratio=hot_auction_ratio,
                        hot_bidder_ratio=hot_bidder_ratio)
    gid = nexmark_q4.bid_gid(start + np.arange(n, dtype=np.int64))
    for name, salt in (("channel", 7), ("url", 8), ("extra", 9)):
        b[name] = _rand(gid, salt, len(VOCABULARY[name]))
    return b


def column(b: dict, name: str, rows=slice(None)) -> np.ndarray:
    """Column `name` of the bids `rows`: an int64 array, or the strings as a
    numpy `U` array."""
    picked = b[name][rows]
    return VOCABULARY[name][picked] if name in VOCABULARY else picked


def top_n(b: dict, partition: tuple, order_col: str, n: int) -> tuple:
    """(rows, rank): the indices of the bids whose rank within their
    partition — by `order_col` DESCENDING, ties by the event index ascending
    — is at most `n`, and that 1-based rank."""
    count = b[order_col].shape[0]
    idx = np.arange(count, dtype=np.int64)
    # np.lexsort: the LAST key is the primary one
    order = np.lexsort((idx, -b[order_col])
                       + tuple(b[c] for c in reversed(partition)))
    new_run = np.ones(count, dtype=bool)
    same = np.ones(max(count - 1, 0), dtype=bool)
    for c in partition:
        s = b[c][order]
        same &= s[1:] == s[:-1]
    new_run[1:] = ~same
    pos = np.arange(count, dtype=np.int64)
    run_start = np.maximum.accumulate(np.where(new_run, pos, 0))
    rank = pos - run_start + 1
    keep = rank <= n
    return order[keep], rank[keep]


def q19(b: dict, n: int = 10) -> list:
    """[auction, bidder, price, channel, url, date_time, extra, rank_number]
    of `SELECT * FROM (SELECT *, ROW_NUMBER() OVER (PARTITION BY auction
    ORDER BY price DESC) AS rank_number FROM bid) WHERE rank_number <= n`."""
    rows, rank = top_n(b, ("auction",), "price", n)
    return [column(b, c, rows) for c in COLUMNS] + [rank]


def q18(b: dict) -> list:
    """The seven bid columns of NEXMark q18 'Find last bid': the last bid
    (by date_time) of every (bidder, auction)."""
    rows, _ = top_n(b, ("bidder", "auction"), "date_time", 1)
    return [column(b, c, rows) for c in COLUMNS]


base_time_us = nexmark.base_time_us
