"""TPC-H datagen connector — deterministic, seekable `part` / `lineitem`
streams that follow clause 4.2.3 of the TPC-H specification (revision 3) at
a stated scale factor (BASELINE staged config 5, Q17).

Reference workload: /root/reference/e2e_test/tpch/ and the ci q17 SQL. The
reference feeds TPC-H through Kafka from dbgen files; here the rows are
generated on device from the offset counter (counter-based splitmix64, as
nexmark.py), so the stream is deterministic, seekable for exactly-once
replay, and needs no external system. `seed` is a DYNAMIC argument of the
jitted program: another seed is other data from the same executable.

By the letter of clause 4.2.3 (every column Q17 reads):
  P_PARTKEY 1..SF x 200,000, unique; P_MFGR "Manufacturer#M", P_BRAND
  "Brand#MN", M and N uniform in 1..5; P_TYPE one of 6 x 5 x 5 syllables;
  P_SIZE uniform in 1..50; P_CONTAINER one of 5 x 8 syllables;
  P_RETAILPRICE (90000 + ((P_PARTKEY / 10) mod 20001) + 100 x (P_PARTKEY mod
  1000)) / 100; L_PARTKEY uniform in 1..SF x 200,000; L_SUPPKEY (L_PARTKEY +
  i x (S / 4 + (L_PARTKEY - 1) / S)) mod S + 1, i uniform in 0..3, S = SF x
  10,000; L_QUANTITY uniform in 1..50; L_EXTENDEDPRICE L_QUANTITY x
  P_RETAILPRICE; L_DISCOUNT 0.00..0.10; L_TAX 0.00..0.08; L_SHIPDATE
  O_ORDERDATE + 1..121; L_COMMITDATE O_ORDERDATE + 30..90; L_RECEIPTDATE
  L_SHIPDATE + 1..30; L_RETURNFLAG "R" / "A" if received by CURRENTDATE else
  "N"; L_LINESTATUS "O" if shipped after CURRENTDATE else "F";
  L_SHIPINSTRUCT one of 4; L_SHIPMODE one of 7.

Departures (benchmark/configs/tpch-q17-sf1-1chip.json lists them): money is
INT64 cents, discount and tax INT64 hundredths; strings are dictionary ids;
P_NAME is ONE of the spec's 92 colours (the spec joins five), P_COMMENT and
L_COMMENT one noun of the spec's text grammar; an order has exactly four
lines (`l_orderkey = row / 4 + 1`, `l_linenumber = row mod 4 + 1`; the spec
draws 1..7 and uses sparse order keys); the random stream is splitmix64, not
dbgen's; the streams do not end: rows past the table's size (SF x 200,000
parts, which no lineitem names; SF x 6,001,215 lineitems) go on by the rules.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common.chunk import Column, StreamChunk
from ..common.types import DataType, schema
from .nexmark import _register_vocab, _splitmix64

PARTS_PER_SF = 200_000
SUPPLIERS_PER_SF = 10_000

COLOURS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()
MFGRS = [f"Manufacturer#{m}" for m in range(1, 6)]
BRANDS = [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{s} {t}" for s in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for t in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
NOUNS = ("foxes ideas theodolites instructions dependencies excuses platelets "
         "asymptotes courts dolphins multipliers sauternes warthogs frets "
         "dinos attainments").split()
RETURNFLAGS = ["R", "A", "N"]
LINESTATUS = ["O", "F"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]

# days since 1970-01-01
STARTDATE = 8035        # 1992-01-01
ENDDATE = 10591         # 1998-12-31
CURRENTDATE = 9298      # 1995-06-17

# one salt per random column
(_S_MFGR, _S_BRAND, _S_NAME, _S_TYPE, _S_SIZE, _S_CONTAINER, _S_PCOMMENT,
 _S_PARTKEY, _S_SUPP, _S_QUANTITY, _S_DISCOUNT, _S_TAX, _S_ORDERDATE,
 _S_SHIP, _S_COMMIT, _S_RECEIPT, _S_RFLAG, _S_INSTRUCT, _S_MODE,
 _S_LCOMMENT) = range(1, 21)

_V, _I, _D = DataType.VARCHAR, DataType.INT64, DataType.DATE

PART_SCHEMA = schema(
    ("p_partkey", _I), ("p_name", _V), ("p_mfgr", _V), ("p_brand", _V),
    ("p_type", _V), ("p_size", _I), ("p_container", _V),
    ("p_retailprice", _I), ("p_comment", _V))

LINEITEM_SCHEMA = schema(
    ("l_orderkey", _I), ("l_partkey", _I), ("l_suppkey", _I),
    ("l_linenumber", _I), ("l_quantity", _I), ("l_extendedprice", _I),
    ("l_discount", _I), ("l_tax", _I), ("l_returnflag", _V),
    ("l_linestatus", _V), ("l_shipdate", _D), ("l_commitdate", _D),
    ("l_receiptdate", _D), ("l_shipinstruct", _V), ("l_shipmode", _V),
    ("l_comment", _V))

TPCH_SCHEMAS = {"part": PART_SCHEMA, "lineitem": LINEITEM_SCHEMA}

_VOCABS = {"tpch_colour": COLOURS, "tpch_mfgr": MFGRS, "tpch_brand": BRANDS,
           "tpch_type": TYPES, "tpch_container": CONTAINERS,
           "tpch_noun": NOUNS, "tpch_returnflag": RETURNFLAGS,
           "tpch_linestatus": LINESTATUS, "tpch_instruct": INSTRUCTIONS,
           "tpch_mode": MODES}


def _draw(seed, salt: int, counter, lo: int, hi: int):
    """Uniform int64 in [lo, hi], a pure function of (seed, salt, counter)."""
    key = _splitmix64(seed * jnp.uint64(0x9E3779B97F4A7C15)
                      + jnp.uint64(salt))
    h = _splitmix64(key + counter.astype(jnp.uint64))
    return lo + (h % jnp.uint64(hi - lo + 1)).astype(jnp.int64)


def retail_price_cents(partkey):
    """P_RETAILPRICE of clause 4.2.3, in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


@partial(jax.jit, static_argnames=("n",))
def gen_part_columns(seed, offset, ids, *, n: int):
    """`part` rows offset .. offset+n-1 (P_PARTKEY = row + 1)."""
    key = offset + 1 + jnp.arange(n, dtype=jnp.int64)
    m = _draw(seed, _S_MFGR, key, 0, 4)
    brand = m * 5 + _draw(seed, _S_BRAND, key, 0, 4)
    return (key,
            ids["tpch_colour"][_draw(seed, _S_NAME, key, 0,
                                     len(COLOURS) - 1)],
            ids["tpch_mfgr"][m],
            ids["tpch_brand"][brand],
            ids["tpch_type"][_draw(seed, _S_TYPE, key, 0, len(TYPES) - 1)],
            _draw(seed, _S_SIZE, key, 1, 50),
            ids["tpch_container"][_draw(seed, _S_CONTAINER, key, 0,
                                        len(CONTAINERS) - 1)],
            retail_price_cents(key),
            ids["tpch_noun"][_draw(seed, _S_PCOMMENT, key, 0,
                                   len(NOUNS) - 1)])


@partial(jax.jit, static_argnames=("n", "n_parts", "n_suppliers"))
def gen_lineitem_columns(seed, offset, ids, *, n: int, n_parts: int,
                         n_suppliers: int):
    """`lineitem` rows offset .. offset+n-1."""
    row = offset + jnp.arange(n, dtype=jnp.int64)
    orderkey = row // 4 + 1
    partkey = _draw(seed, _S_PARTKEY, row, 1, n_parts)
    S = n_suppliers
    suppkey = (partkey + _draw(seed, _S_SUPP, row, 0, 3)
               * (S // 4 + (partkey - 1) // S)) % S + 1
    quantity = _draw(seed, _S_QUANTITY, row, 1, 50)
    orderdate = _draw(seed, _S_ORDERDATE, orderkey, STARTDATE, ENDDATE - 151)
    shipdate = orderdate + _draw(seed, _S_SHIP, row, 1, 121)
    commitdate = orderdate + _draw(seed, _S_COMMIT, row, 30, 90)
    receiptdate = shipdate + _draw(seed, _S_RECEIPT, row, 1, 30)
    returnflag = jnp.where(receiptdate <= CURRENTDATE,
                           _draw(seed, _S_RFLAG, row, 0, 1), 2)
    linestatus = jnp.where(shipdate > CURRENTDATE, 0, 1)
    return (orderkey, partkey, suppkey, row % 4 + 1, quantity,
            quantity * retail_price_cents(partkey),
            _draw(seed, _S_DISCOUNT, row, 0, 10),
            _draw(seed, _S_TAX, row, 0, 8),
            ids["tpch_returnflag"][returnflag],
            ids["tpch_linestatus"][linestatus],
            shipdate.astype(jnp.int32), commitdate.astype(jnp.int32),
            receiptdate.astype(jnp.int32),
            ids["tpch_instruct"][_draw(seed, _S_INSTRUCT, row, 0, 3)],
            ids["tpch_mode"][_draw(seed, _S_MODE, row, 0, 6)],
            ids["tpch_noun"][_draw(seed, _S_LCOMMENT, row, 0,
                                   len(NOUNS) - 1)])


class TpchGenerator:
    """Connector protocol: next_chunk() / seek(offset) / offset."""

    def __init__(self, table: str, chunk_size: int = 4096,
                 start_offset: int = 0, scale_factor: float = 1.0,
                 seed: int = 0):
        assert table in TPCH_SCHEMAS, table
        if scale_factor <= 0:
            raise ValueError("scale_factor must be positive")
        self.table = table
        self.chunk_size = chunk_size
        self.offset = start_offset
        self.scale_factor = scale_factor
        self.seed = int(seed)
        self.schema = TPCH_SCHEMAS[table]
        self.n_parts = max(1, round(scale_factor * PARTS_PER_SF))
        self.n_suppliers = max(1, round(scale_factor * SUPPLIERS_PER_SF))
        # ids need not be contiguous (nexmark.py _register_vocab): each
        # vocabulary travels as an id table, an argument of the program
        self._vocab_ids = {
            k: jnp.asarray(_register_vocab(k, list(v)), dtype=jnp.int32)
            for k, v in _VOCABS.items()}
        self._vis = jnp.ones(chunk_size, dtype=bool)
        self._ops = jnp.zeros(chunk_size, dtype=jnp.int8)

    def next_chunk(self) -> StreamChunk:
        seed, offset = jnp.uint64(self.seed), jnp.int64(self.offset)
        if self.table == "part":
            cols = gen_part_columns(seed, offset, self._vocab_ids,
                                    n=self.chunk_size)
        else:
            cols = gen_lineitem_columns(
                seed, offset, self._vocab_ids, n=self.chunk_size,
                n_parts=self.n_parts, n_suppliers=self.n_suppliers)
        self.offset += self.chunk_size
        return StreamChunk(tuple(Column(c) for c in cols), self._ops,
                           self._vis, self.schema)

    def seek(self, offset: int) -> None:
        self.offset = offset
