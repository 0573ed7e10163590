"""Bid events in plain numpy — the benchmark's own copy of the data the
engine's nexmark connector makes. NOT the public NEXMark generator in one
respect that matters: the key skew.

What is NEXMark's (Tucker et al.; the `nexmark` crate the reference wraps):
events interleave 1 person : 3 auctions : 46 bids per 50, event time is
`base_time_us + global_event_id * inter_event_us`, the hot auction / bidder
is the first id of the last bucket of 100, prices spread over five decades.
What is NOT: public NEXMark sends a bid to the hot auction with probability
1 - 1/hotAuctionRatio = 50% and to the hot bidder with 75%; the constant 100
there is only the bucket's width. The engine's connector
(`connectors/nexmark.py`) uses its `HOT_*_RATIO = 100` as the probability
modulus too, so 99% of bids hit the hot auction and 99% the hot bidder. The
connector has no option for it and a benchmark PR may not edit the program,
so this copy follows the connector: the oracle must see the rows the engine
saw. Each configuration states the deviation (`deviations.key_skew`), and
PERF.md lists the connector options that would remove it.

Randomness is a counter-based splitmix64 of the global event id (the
connector's scheme, not the crate's), so row `k` is a pure function of `k`
and the configuration: an oracle regenerates rows `[0, committed offset)`
without asking the engine for anything.

Imports nothing of `risingwave_tpu`. A difference between this and the
engine's device generator shows as `correct: false`.
"""

from __future__ import annotations

import numpy as np

PERSON_PROPORTION, AUCTION_PROPORTION, BID_PROPORTION = 1, 3, 46
TOTAL_PROPORTION = 50
HOT_AUCTION_RATIO = 100       # bucket width AND probability modulus, as the
HOT_BIDDER_RATIO = 100        # connector has it (NEXMark's moduli: 2 and 4)
FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
NUM_ACTIVE_PEOPLE = 1000
IN_FLIGHT_AUCTIONS = 100
BASE_TIME_US = 1_500_000_000_000_000

_U = np.uint64


SEED_STEP_US = 10_000_000


def base_time_us(seed: int) -> int:
    """The one source option through which `--seed` changes the data and the
    answer: every timestamp shifts by `seed mod 10000` whole 10 s steps. Whole
    steps, so that the phase of every TUMBLE / HOP window against the stream,
    and with it every state size and program shape, is the same for every
    seed: a shift by milliseconds made the engine compile a seed-dependent
    program variant inside the timed recovery (PERF.md, PR 23)."""
    return BASE_TIME_US + (int(seed) % 10_000) * SEED_STEP_US


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64) + _U(0x9E3779B97F4A7C15)
    x = (x ^ (x >> _U(30))) * _U(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> _U(27))) * _U(0x94D049BB133111EB)
    return x ^ (x >> _U(31))


def _rand(eid: np.ndarray, salt: int, mod: int) -> np.ndarray:
    h = _splitmix64(eid.astype(np.uint64) * _U(2654435761) + _U(salt))
    return (h % _U(mod)).astype(np.int64)


def bids(start: int, n: int, *, inter_event_us: int, base_time: int) -> dict:
    """Columns of bids `start .. start+n-1` (bid-local indices) as int64
    arrays: auction, bidder, price, date_time."""
    with np.errstate(over="ignore"):
        k = start + np.arange(n, dtype=np.int64)
        group, off = k // BID_PROPORTION, k % BID_PROPORTION
        gid = (group * TOTAL_PROPORTION + PERSON_PROPORTION
               + AUCTION_PROPORTION + off)
        g, o = gid // TOTAL_PROPORTION, gid % TOTAL_PROPORTION
        n_persons = g * PERSON_PROPORTION + np.minimum(o, PERSON_PROPORTION)
        n_auctions = g * AUCTION_PROPORTION + np.clip(
            o - PERSON_PROPORTION, 0, AUCTION_PROPORTION)

        hot = _rand(gid, 1, HOT_AUCTION_RATIO) > 0
        hot_auction = ((n_auctions - 1) // HOT_AUCTION_RATIO
                       ) * HOT_AUCTION_RATIO
        cold_auction = n_auctions - 1 - _rand(gid, 2, IN_FLIGHT_AUCTIONS)
        auction = FIRST_AUCTION_ID + np.where(
            hot, hot_auction, np.maximum(cold_auction, 0))

        hot_b = _rand(gid, 3, HOT_BIDDER_RATIO) > 0
        hot_bidder = ((n_persons - 1) // HOT_BIDDER_RATIO
                      ) * HOT_BIDDER_RATIO + 1
        cold_bidder = n_persons - 1 - _rand(gid, 4, NUM_ACTIVE_PEOPLE)
        bidder = FIRST_PERSON_ID + np.where(
            hot_b, hot_bidder, np.maximum(cold_bidder, 0))

        price = (_rand(gid, 6, 900) + 100) * (10 ** _rand(gid, 5, 5))
        date_time = base_time + gid * inter_event_us
    return {"auction": auction, "bidder": bidder, "price": price,
            "date_time": date_time}
