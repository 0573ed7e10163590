"""Person and auction events for NEXMark q8 in plain numpy, and the query
itself — the benchmark's own copy of what the engine's nexmark connector
makes for the two topics q8 reads, with the width of the hot seller's bucket
a parameter.

Persons follow the connector's model: person `k` is global event `50 k`
(offset 0 of each group of 1 : 3 : 46), id `1000 + k`, event time
`base + 50 k x inter_event_us`. Its NAME is stated here as a STRING by its
own rule, `"person_" + str(id mod 1000)`: the engine keeps a VARCHAR as a
dictionary id that means nothing outside its process, so the expected cell
is the text. (The public generator draws a first and a last name; the
connector's rule is the deviation the configuration states.)

Auctions: auction `k` is the event at offset 1..3 of its group, seller hot
with probability 1 - 1/4 (salt 26, NEXMark's `hotSellersRatio` 4): the first
person of the current bucket of `hot_seller_bucket` persons; else one of the
last 1,000 persons (salt 27). The connector's default bucket is 4 (its
`HOT_SELLER_RATIO` doubling as the width); NEXMark's own is 100 (Beam
`AuctionGenerator.HOT_SELLER_RATIO`, the `nexmark` crate), which the
configuration sets. Only the columns q8 reads.

The query: the persons whose (id, 10 s window) is also some auction's
(seller, window). Each `GROUP BY` of the published statement is a DISTINCT:
two `np.unique` over (key, window) and one membership test.

Imports nothing of `risingwave_tpu`; `_rand`, the constants and the seed's
base time come from `nexmark.py`, which is not edited.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import nexmark
from benchmark.reference.nexmark import (
    AUCTION_PROPORTION, FIRST_PERSON_ID, NUM_ACTIVE_PEOPLE,
    PERSON_PROPORTION, TOTAL_PROPORTION, _rand)

HOT_SELLER_MODULUS = 4        # an auction is cold with probability 1 / 4
NUM_NAMES = 1000


def person_name(pid: np.ndarray) -> np.ndarray:
    """The name of person `pid`, as text."""
    return np.char.add("person_", (pid % NUM_NAMES).astype("U4"))


def persons(start: int, n: int, *, inter_event_us: int,
            base_time: int) -> dict:
    """Columns of persons `start .. start+n-1` (person-local indices): id
    and date_time as int64, name as a numpy `U` array."""
    k = start + np.arange(n, dtype=np.int64)
    pid = FIRST_PERSON_ID + k
    return {"id": pid, "name": person_name(pid),
            "date_time": base_time + k * TOTAL_PROPORTION * inter_event_us}


def auctions(start: int, n: int, *, inter_event_us: int, base_time: int,
             hot_seller_bucket: int) -> dict:
    """Columns of auctions `start .. start+n-1` (auction-local indices) as
    int64 arrays: seller, date_time."""
    with np.errstate(over="ignore"):
        k = start + np.arange(n, dtype=np.int64)
        gid = ((k // AUCTION_PROPORTION) * TOTAL_PROPORTION
               + PERSON_PROPORTION + k % AUCTION_PROPORTION)
        g, o = gid // TOTAL_PROPORTION, gid % TOTAL_PROPORTION
        n_persons = g * PERSON_PROPORTION + np.minimum(o, PERSON_PROPORTION)
        hot = _rand(gid, 26, HOT_SELLER_MODULUS) > 0
        hot_seller = ((n_persons - 1) // hot_seller_bucket
                      ) * hot_seller_bucket
        cold_seller = n_persons - 1 - _rand(gid, 27, NUM_ACTIVE_PEOPLE)
        seller = FIRST_PERSON_ID + np.where(
            hot, hot_seller, np.maximum(cold_seller, 0))
    return {"seller": seller, "date_time": base_time + gid * inter_event_us}


def new_users(p: dict, a: dict, window_us: int) -> list:
    """[id, name, starttime] of q8: every distinct (id, name, window) of `p`
    whose (id, window) is a distinct (seller, window) of `a`."""
    if p["id"].shape[0] == 0 or a["seller"].shape[0] == 0:
        return [np.zeros(0, np.int64), np.zeros(0, "U10"),
                np.zeros(0, np.int64)]
    pw = p["date_time"] - p["date_time"] % window_us
    aw = a["date_time"] - a["date_time"] % window_us
    w0 = int(min(pw.min(), aw.min()))
    assert int(max(pw.max(), aw.max()) - w0) // window_us < 1 << 24
    assert int(max(p["id"].max(), a["seller"].max())) < 1 << 38
    # a person's name follows from its id, so (id, window) is the whole key
    pkey, first = np.unique((p["id"] << 24) | ((pw - w0) // window_us),
                            return_index=True)
    akey = np.unique((a["seller"] << 24) | ((aw - w0) // window_us))
    hit = first[np.isin(pkey, akey)]
    return [p["id"][hit], p["name"][hit], pw[hit]]


base_time_us = nexmark.base_time_us
