"""Nexmark event generator — device-native datagen source.

Reference: src/connector/src/source/nexmark/ (wraps the public `nexmark`
crate); workloads defined by ci/scripts/sql/nexmark/q*.sql. This is a
re-implementation of the *public Nexmark benchmark generator model* (person/
auction/bid event interleaving 1:3:46 per 50 events, a hot auction / bidder
per bucket of 100 ids) as a pure function `event_index -> row`, vectorized
in jnp so a whole chunk is generated on device per call — the source never
bottlenecks the TPU executors it feeds.

Key skew: a bid goes to its bucket's hot auction with probability
1 - 1/hot_auction_ratio and to the hot bidder with 1 - 1/hot_bidder_ratio.
The DEFAULTS are 100 / 100 (99% / 99% hot: the bucket width 100 doubling as
the modulus, what this connector always did, and what every older cell,
test and oracle was written against). The spec's skew — 50% / 75%, NEXMark's
hotAuctionRatio 2 and hotBidderRatio 4 — is the source options
`hot_auction_ratio=2, hot_bidder_ratio=4`; the bucket width stays 100
either way. An auction goes to a hot seller with probability 3/4 (NEXMark's
hotSellersRatio 4) — the first person of the current bucket of
`hot_seller_bucket` persons: DEFAULT 4 (the modulus doubling as the width,
what this connector always did); the public generator's bucket is 100, the
source option `hot_seller_bucket=100`.

Randomness is a counter-based splitmix64 of the event id: deterministic,
seekable (exactly-once source recovery = remember the next event index,
reference source offsets in state_table_handler.rs), and identical across
hosts.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import StreamChunk, Column
from ..common.types import DataType, GLOBAL_DICT, Schema, schema

# Event interleaving per 50 events (Nexmark spec)
PERSON_PROPORTION = 1
AUCTION_PROPORTION = 3
BID_PROPORTION = 46
TOTAL_PROPORTION = 50

# width of the bucket of ids a hot auction / bidder is the first of; also
# the DEFAULT probability modulus (NexmarkConfig.hot_*_ratio)
HOT_AUCTION_RATIO = 100
HOT_BIDDER_RATIO = 100
HOT_SELLER_RATIO = 4

FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
FIRST_CATEGORY_ID = 10

BID_SCHEMA = schema(
    ("auction", DataType.INT64),
    ("bidder", DataType.INT64),
    ("price", DataType.INT64),
    ("channel", DataType.VARCHAR),
    ("url", DataType.VARCHAR),
    ("date_time", DataType.TIMESTAMP),
    ("extra", DataType.VARCHAR),
)

PERSON_SCHEMA = schema(
    ("id", DataType.INT64),
    ("name", DataType.VARCHAR),
    ("email_address", DataType.VARCHAR),
    ("credit_card", DataType.VARCHAR),
    ("city", DataType.VARCHAR),
    ("state", DataType.VARCHAR),
    ("date_time", DataType.TIMESTAMP),
    ("extra", DataType.VARCHAR),
)

AUCTION_SCHEMA = schema(
    ("id", DataType.INT64),
    ("item_name", DataType.VARCHAR),
    ("description", DataType.VARCHAR),
    ("initial_bid", DataType.INT64),
    ("reserve", DataType.INT64),
    ("date_time", DataType.TIMESTAMP),
    ("expires", DataType.TIMESTAMP),
    ("seller", DataType.INT64),
    ("category", DataType.INT64),
    ("extra", DataType.VARCHAR),
)

_CHANNELS = ["apple", "google", "baidu", "facebook"]
_STATES = ["AZ", "CA", "ID", "OR", "WA", "WY"]
_CITIES = ["Phoenix", "Los Angeles", "San Francisco", "Boise", "Portland",
           "Bend", "Redmond", "Seattle", "Kent", "Cheyenne"]

# Dict-encoded vocabularies: every VARCHAR column draws ids from a
# contiguous range [base, base+size) registered in GLOBAL_DICT, so device
# ids always decode to real strings.
_VOCABS: dict[str, tuple[int, ...]] = {}


def _register_vocab(name: str, strings: list[str]) -> tuple:
    # ids need NOT be contiguous: any of these strings may already be in
    # GLOBAL_DICT (e.g. inserted by a bound SQL literal before the first
    # generator was constructed), so vocab picks gather from an explicit
    # id table instead of doing base+offset arithmetic
    if name not in _VOCABS:
        _VOCABS[name] = tuple(GLOBAL_DICT.get_or_insert(s)
                              for s in strings)
    return _VOCABS[name]


def _ensure_vocabs() -> dict[str, tuple[int, ...]]:
    _register_vocab("channel", _CHANNELS)
    _register_vocab("state", _STATES)
    _register_vocab("city", _CITIES)
    _register_vocab("name", [f"person_{i}" for i in range(1000)])
    _register_vocab("email", [f"user_{i}@example.com" for i in range(1000)])
    _register_vocab("card", [f"{i:04d} {i:04d} {i:04d} {i:04d}" for i in range(1000)])
    _register_vocab("url", [f"https://b.example.com/item/{i}" for i in range(1000)])
    _register_vocab("item", [f"item_{i}" for i in range(1000)])
    _register_vocab("desc", [f"description_{i}" for i in range(100)])
    _register_vocab("extra", [f"extra_{i}" for i in range(100)])
    return dict(_VOCABS)


def _vocab_pick(vocab: tuple, eid: jnp.ndarray, salt: int) -> jnp.ndarray:
    ids = jnp.asarray(vocab, dtype=jnp.int32)
    return ids[_rand(eid, salt, len(vocab))]


def _splitmix64(x: jnp.ndarray) -> jnp.ndarray:
    """Counter-based hash, uint64 -> uint64 (public splitmix64 constants)."""
    x = x.astype(jnp.uint64)
    x = (x + jnp.uint64(0x9E3779B97F4A7C15))
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def _rand(eid: jnp.ndarray, salt: int, mod: int) -> jnp.ndarray:
    """Deterministic uniform int64 in [0, mod)."""
    h = _splitmix64(eid.astype(jnp.uint64) * jnp.uint64(2654435761) + jnp.uint64(salt))
    return (h % jnp.uint64(mod)).astype(jnp.int64)


@dataclass(frozen=True)
class NexmarkConfig:
    base_time_us: int = 1_500_000_000_000_000  # event-time origin (us)
    inter_event_us: int = 10                   # logical event spacing
    num_active_people: int = 1000
    in_flight_auctions: int = 100
    # a bid is cold with probability 1/ratio (the spec's: 2 and 4)
    hot_auction_ratio: int = HOT_AUCTION_RATIO
    hot_bidder_ratio: int = HOT_BIDDER_RATIO
    # persons per hot seller (the spec's: 100); the probability modulus
    # stays HOT_SELLER_RATIO
    hot_seller_bucket: int = HOT_SELLER_RATIO


def _ids_so_far(global_id):
    """Counts of persons/auctions emitted up to global event id (exclusive)."""
    group = global_id // TOTAL_PROPORTION
    off = global_id % TOTAL_PROPORTION
    n_persons = group * PERSON_PROPORTION + jnp.minimum(off, PERSON_PROPORTION)
    n_auctions = group * AUCTION_PROPORTION + jnp.clip(
        off - PERSON_PROPORTION, 0, AUCTION_PROPORTION)
    return n_persons, n_auctions


def _event_time(global_id, cfg: NexmarkConfig):
    return cfg.base_time_us + global_id * cfg.inter_event_us


@partial(jax.jit, static_argnums=(1, 2, 3))
def gen_bid_columns(start_index: jnp.ndarray, n: int, cfg: NexmarkConfig,
                    vocabs: tuple = ()):
    """Bid events k = start_index .. start_index+n-1 (bid-local indices)."""
    V = dict(vocabs)
    k = start_index + jnp.arange(n, dtype=jnp.int64)
    group = k // BID_PROPORTION
    off = k % BID_PROPORTION
    global_id = group * TOTAL_PROPORTION + PERSON_PROPORTION + AUCTION_PROPORTION + off
    n_persons, n_auctions = _ids_so_far(global_id)

    # auction: hot (1 per cfg.hot_auction_ratio chance of cold) -> the
    # first id of the current bucket of HOT_AUCTION_RATIO
    hot = _rand(global_id, 1, cfg.hot_auction_ratio) > 0
    hot_auction = ((n_auctions - 1) // HOT_AUCTION_RATIO) * HOT_AUCTION_RATIO
    cold_auction = n_auctions - 1 - _rand(global_id, 2, cfg.in_flight_auctions)
    auction = FIRST_AUCTION_ID + jnp.where(hot, hot_auction, jnp.maximum(cold_auction, 0))

    hot_b = _rand(global_id, 3, cfg.hot_bidder_ratio) > 0
    hot_bidder = ((n_persons - 1) // HOT_BIDDER_RATIO) * HOT_BIDDER_RATIO + 1
    cold_bidder = n_persons - 1 - _rand(global_id, 4, cfg.num_active_people)
    bidder = FIRST_PERSON_ID + jnp.where(hot_b, hot_bidder, jnp.maximum(cold_bidder, 0))

    # price: roughly log-uniform in [100, 10^7] (spec's price model shape)
    lg = _rand(global_id, 5, 5)  # decade
    mant = _rand(global_id, 6, 900) + 100
    price = mant * (10 ** lg).astype(jnp.int64)

    channel = _vocab_pick(V["channel"], global_id, 7)
    url = _vocab_pick(V["url"], global_id, 8)
    date_time = _event_time(global_id, cfg)
    extra = _vocab_pick(V["extra"], global_id, 9)
    return (auction, bidder, price, channel, url, date_time, extra)


@partial(jax.jit, static_argnums=(1, 2, 3))
def gen_person_columns(start_index: jnp.ndarray, n: int, cfg: NexmarkConfig,
                       vocabs: tuple = ()):
    V = dict(vocabs)
    k = start_index + jnp.arange(n, dtype=jnp.int64)
    global_id = k * TOTAL_PROPORTION  # persons sit at offset 0 of each group
    pid = FIRST_PERSON_ID + k
    name_ids = jnp.asarray(V["name"], dtype=jnp.int32)
    name = name_ids[pid % len(V["name"])]
    email = _vocab_pick(V["email"], global_id, 11)
    card = _vocab_pick(V["card"], global_id, 12)
    city = _vocab_pick(V["city"], global_id, 13)
    state = _vocab_pick(V["state"], global_id, 14)
    date_time = _event_time(global_id, cfg)
    extra = _vocab_pick(V["extra"], global_id, 15)
    return (pid, name, email, card, city, state, date_time, extra)


@partial(jax.jit, static_argnums=(1, 2, 3))
def gen_auction_columns(start_index: jnp.ndarray, n: int, cfg: NexmarkConfig,
                        vocabs: tuple = ()):
    V = dict(vocabs)
    k = start_index + jnp.arange(n, dtype=jnp.int64)
    group = k // AUCTION_PROPORTION
    off = k % AUCTION_PROPORTION
    global_id = group * TOTAL_PROPORTION + PERSON_PROPORTION + off
    n_persons, _ = _ids_so_far(global_id)
    aid = FIRST_AUCTION_ID + k
    item = _vocab_pick(V["item"], global_id, 21)
    desc = _vocab_pick(V["desc"], global_id, 22)
    initial_bid = _rand(global_id, 23, 1000) * 100 + 100
    reserve = initial_bid + _rand(global_id, 24, 1000) * 100
    date_time = _event_time(global_id, cfg)
    expires = date_time + (_rand(global_id, 25, 100) + 1) * 1_000_000
    hot = _rand(global_id, 26, HOT_SELLER_RATIO) > 0
    hot_seller = ((n_persons - 1) // cfg.hot_seller_bucket
                  ) * cfg.hot_seller_bucket
    cold_seller = n_persons - 1 - _rand(global_id, 27, cfg.num_active_people)
    seller = FIRST_PERSON_ID + jnp.where(hot, hot_seller, jnp.maximum(cold_seller, 0))
    category = FIRST_CATEGORY_ID + _rand(global_id, 28, 5)
    return (aid, item, desc, initial_bid, reserve, date_time, expires,
            seller, category, _vocab_pick(V["extra"], global_id, 29))


_TABLES = {
    "bid": (BID_SCHEMA, gen_bid_columns),
    "person": (PERSON_SCHEMA, gen_person_columns),
    "auction": (AUCTION_SCHEMA, gen_auction_columns),
}


class NexmarkGenerator:
    """Split reader for one Nexmark table (reference SplitReader,
    connector/src/source/base.rs). Offset = next event index of this table —
    the exactly-once source state."""

    def __init__(self, table: str, chunk_size: int = 4096,
                 cfg: NexmarkConfig = NexmarkConfig(), start_offset: int = 0):
        self.table = table
        self.schema, self._gen = _TABLES[table]
        self.chunk_size = chunk_size
        self.cfg = cfg
        self.offset = start_offset
        self._vocabs = tuple(sorted(_ensure_vocabs().items()))
        self._vis = jnp.ones(chunk_size, dtype=bool)
        self._ops = jnp.zeros(chunk_size, dtype=jnp.int8)

    def seek(self, offset: int) -> None:
        self.offset = offset

    def next_chunk(self) -> StreamChunk:
        cols = self._gen(jnp.int64(self.offset), self.chunk_size, self.cfg, self._vocabs)
        self.offset += self.chunk_size
        columns = tuple(Column(c) for c in cols)
        return StreamChunk(columns, self._ops, self._vis, self.schema)

    @property
    def watermark_col(self) -> int:
        """Index of date_time in this table's schema."""
        return {"bid": 5, "person": 6, "auction": 5}[self.table]

    def current_watermark(self) -> int:
        """Event-time watermark after the last emitted chunk, computed on the
        HOST from pure offset arithmetic (the generator's event time is
        deterministic in the event id) — no device readback on the hot path.
        Nexmark event time is monotone in the id, so this is exact."""
        if self.offset == 0:
            return self.cfg.base_time_us
        k = self.offset - 1
        if self.table == "bid":
            group, off = divmod(k, BID_PROPORTION)
            gid = group * TOTAL_PROPORTION + PERSON_PROPORTION + AUCTION_PROPORTION + off
        elif self.table == "person":
            gid = k * TOTAL_PROPORTION
        else:
            group, off = divmod(k, AUCTION_PROPORTION)
            gid = group * TOTAL_PROPORTION + PERSON_PROPORTION + off
        return self.cfg.base_time_us + gid * self.cfg.inter_event_us
