"""Auction and bid events for NEXMark q4 in plain numpy, with the key skew a
parameter — the benchmark's own copy of what the engine's nexmark connector
makes under `hot_auction_ratio` / `hot_bidder_ratio`.

`benchmark/reference/nexmark.py` fixes both moduli at the connector's default
100 (99% hot) because the older cells run that. q4 joins ON `bid.auction`, so
it runs NEXMark's own skew (ratio 2: 50% of bids on the hot auction of their
bucket of 100; ratio 4: 75% on the hot bidder) and needs the moduli as
arguments. The bucket width stays 100 either way. At 100 / 100 `bids` here
equals `nexmark.bids` cell for cell (a test holds it to that).

Auctions follow the connector's model: id `1000 + k`, event time of the
auction's slot in the 1:3:46 interleaving, `expires` 1..100 s later (salt
25), category `10 + rand(5)` (salt 28). Only the columns q4 reads.

Imports nothing of `risingwave_tpu`; `_rand`, the constants and the seed's
base time come from `nexmark.py`, which is not edited.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import nexmark
from benchmark.reference.nexmark import (
    AUCTION_PROPORTION, BID_PROPORTION, FIRST_AUCTION_ID, FIRST_PERSON_ID,
    IN_FLIGHT_AUCTIONS, NUM_ACTIVE_PEOPLE, PERSON_PROPORTION,
    TOTAL_PROPORTION, _rand)

BUCKET = 100                  # ids per hot auction / hot bidder
FIRST_CATEGORY_ID = 10
NUM_CATEGORIES = 5


def bid_gid(k: np.ndarray) -> np.ndarray:
    """Global event id of bid `k` (bid-local index)."""
    return ((k // BID_PROPORTION) * TOTAL_PROPORTION + PERSON_PROPORTION
            + AUCTION_PROPORTION + k % BID_PROPORTION)


def auction_gid(k: np.ndarray) -> np.ndarray:
    """Global event id of auction `k` (auction-local index)."""
    return ((k // AUCTION_PROPORTION) * TOTAL_PROPORTION + PERSON_PROPORTION
            + k % AUCTION_PROPORTION)


def auctions(start: int, n: int, *, inter_event_us: int,
             base_time: int) -> dict:
    """Columns of auctions `start .. start+n-1` as int64 arrays: id,
    date_time, expires, category."""
    with np.errstate(over="ignore"):
        k = start + np.arange(n, dtype=np.int64)
        gid = auction_gid(k)
        date_time = base_time + gid * inter_event_us
        expires = date_time + (_rand(gid, 25, 100) + 1) * 1_000_000
        category = FIRST_CATEGORY_ID + _rand(gid, 28, NUM_CATEGORIES)
    return {"id": FIRST_AUCTION_ID + k, "date_time": date_time,
            "expires": expires, "category": category}


def bids(start: int, n: int, *, inter_event_us: int, base_time: int,
         hot_auction_ratio: int, hot_bidder_ratio: int) -> dict:
    """Columns of bids `start .. start+n-1` as int64 arrays: auction, bidder,
    price, date_time. A bid is cold with probability 1 / ratio."""
    with np.errstate(over="ignore"):
        k = start + np.arange(n, dtype=np.int64)
        gid = bid_gid(k)
        g, o = gid // TOTAL_PROPORTION, gid % TOTAL_PROPORTION
        n_persons = g * PERSON_PROPORTION + np.minimum(o, PERSON_PROPORTION)
        n_auctions = g * AUCTION_PROPORTION + np.clip(
            o - PERSON_PROPORTION, 0, AUCTION_PROPORTION)

        hot = _rand(gid, 1, hot_auction_ratio) > 0
        hot_auction = ((n_auctions - 1) // BUCKET) * BUCKET
        cold_auction = n_auctions - 1 - _rand(gid, 2, IN_FLIGHT_AUCTIONS)
        auction = FIRST_AUCTION_ID + np.where(
            hot, hot_auction, np.maximum(cold_auction, 0))

        hot_b = _rand(gid, 3, hot_bidder_ratio) > 0
        hot_bidder = ((n_persons - 1) // BUCKET) * BUCKET + 1
        cold_bidder = n_persons - 1 - _rand(gid, 4, NUM_ACTIVE_PEOPLE)
        bidder = FIRST_PERSON_ID + np.where(
            hot_b, hot_bidder, np.maximum(cold_bidder, 0))

        price = (_rand(gid, 6, 900) + 100) * (10 ** _rand(gid, 5, 5))
        date_time = base_time + gid * inter_event_us
    return {"auction": auction, "bidder": bidder, "price": price,
            "date_time": date_time}


base_time_us = nexmark.base_time_us
