"""TPC-H `part` and `lineitem` rows in plain numpy: clause 4.2.3 of the
specification (revision 3) written out a second time, for the oracles. The
engine's connector (`risingwave_tpu/connectors/tpch.py`) makes the same rows
on the device; a test holds the two to each other prefix for prefix. Imports
nothing of `risingwave_tpu`.

Every random column is `lo + mix(key(seed, salt) + counter) mod (hi - lo + 1)`
with splitmix64 as `mix` and one salt a column; the counter is the row
(lineitem), the part key (part) or the order key (`O_ORDERDATE`). Strings are
returned as indices into the lists below (the engine ships dictionary ids of
the same strings). Money is cents, discount and tax hundredths, dates days
since 1970-01-01.

Clause 4.2.3, as far as a query can read it here: P_PARTKEY 1..SF x 200,000;
P_MFGR "Manufacturer#M", P_BRAND "Brand#MN" with M, N uniform in 1..5;
P_TYPE 6 x 5 x 5 syllables; P_SIZE 1..50; P_CONTAINER 5 x 8 syllables;
P_RETAILPRICE (90000 + ((P_PARTKEY / 10) mod 20001) + 100 x (P_PARTKEY mod
1000)) / 100; L_PARTKEY uniform in 1..SF x 200,000; L_SUPPKEY (L_PARTKEY + i
x (S / 4 + (L_PARTKEY - 1) / S)) mod S + 1; L_QUANTITY 1..50; L_EXTENDEDPRICE
= L_QUANTITY x P_RETAILPRICE; L_DISCOUNT 0..0.10; L_TAX 0..0.08; the three
dates from O_ORDERDATE; L_RETURNFLAG, L_LINESTATUS against CURRENTDATE
1995-06-17. Set by rule, not by the spec (no query of the benchmark reads
them): four lines an order (`l_orderkey = row / 4 + 1`), P_NAME one colour of
the spec's 92, the comments one noun.
"""

from __future__ import annotations

import numpy as np

PARTS_PER_SF = 200_000
SUPPLIERS_PER_SF = 10_000
# the spec's row count of `lineitem` at SF 1 (orders of 1..7 lines)
LINEITEMS_SF1 = 6_001_215

MFGRS = [f"Manufacturer#{m}" for m in range(1, 6)]
BRANDS = [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]
CONTAINERS = [f"{s} {t}" for s in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for t in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
N_COLOURS, N_TYPES, N_NOUNS = 92, 150, 16
RETURNFLAGS = ["R", "A", "N"]
LINESTATUS = ["O", "F"]
N_INSTRUCTIONS, N_MODES = 4, 7

STARTDATE = int(np.datetime64("1992-01-01").astype("datetime64[D]")
                .astype(np.int64))
ENDDATE = int(np.datetime64("1998-12-31").astype("datetime64[D]")
              .astype(np.int64))
CURRENTDATE = int(np.datetime64("1995-06-17").astype("datetime64[D]")
                  .astype(np.int64))

SALTS = {name: k + 1 for k, name in enumerate((
    "mfgr", "brand", "name", "type", "size", "container", "p_comment",
    "partkey", "supp", "quantity", "discount", "tax", "orderdate", "ship",
    "commit", "receipt", "returnflag", "instruct", "mode", "l_comment"))}

_U = np.uint64


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 (the public constants), uint64 -> uint64."""
    with np.errstate(over="ignore"):
        x = x.astype(_U) + _U(0x9E3779B97F4A7C15)
        x = (x ^ (x >> _U(30))) * _U(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> _U(27))) * _U(0x94D049BB133111EB)
        return x ^ (x >> _U(31))


def draw(seed: int, column: str, counter: np.ndarray, lo: int,
         hi: int) -> np.ndarray:
    """Uniform int64 in [lo, hi]."""
    with np.errstate(over="ignore"):
        key = _mix(np.asarray(_U(seed) * _U(0x9E3779B97F4A7C15)
                              + _U(SALTS[column])))
        h = _mix(key + counter.astype(_U))
    return lo + (h % _U(hi - lo + 1)).astype(np.int64)


def n_parts(scale_factor: float) -> int:
    return max(1, round(scale_factor * PARTS_PER_SF))


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def part(start: int, n: int, *, seed: int) -> dict:
    """Rows start .. start+n-1 of `part`, column name -> int64 array."""
    key = start + 1 + np.arange(n, dtype=np.int64)
    m = draw(seed, "mfgr", key, 0, 4)
    return {
        "p_partkey": key,
        "p_name": draw(seed, "name", key, 0, N_COLOURS - 1),
        "p_mfgr": m,
        "p_brand": m * 5 + draw(seed, "brand", key, 0, 4),
        "p_type": draw(seed, "type", key, 0, N_TYPES - 1),
        "p_size": draw(seed, "size", key, 1, 50),
        "p_container": draw(seed, "container", key, 0, len(CONTAINERS) - 1),
        "p_retailprice": retail_price_cents(key),
        "p_comment": draw(seed, "p_comment", key, 0, N_NOUNS - 1)}


def lineitem(start: int, n: int, *, seed: int, scale_factor: float) -> dict:
    """Rows start .. start+n-1 of `lineitem`."""
    row = start + np.arange(n, dtype=np.int64)
    orderkey = row // 4 + 1
    partkey = draw(seed, "partkey", row, 1, n_parts(scale_factor))
    S = max(1, round(scale_factor * SUPPLIERS_PER_SF))
    quantity = draw(seed, "quantity", row, 1, 50)
    orderdate = draw(seed, "orderdate", orderkey, STARTDATE, ENDDATE - 151)
    shipdate = orderdate + draw(seed, "ship", row, 1, 121)
    receiptdate = shipdate + draw(seed, "receipt", row, 1, 30)
    return {
        "l_orderkey": orderkey,
        "l_partkey": partkey,
        "l_suppkey": (partkey + draw(seed, "supp", row, 0, 3)
                      * (S // 4 + (partkey - 1) // S)) % S + 1,
        "l_linenumber": row % 4 + 1,
        "l_quantity": quantity,
        "l_extendedprice": quantity * retail_price_cents(partkey),
        "l_discount": draw(seed, "discount", row, 0, 10),
        "l_tax": draw(seed, "tax", row, 0, 8),
        "l_returnflag": np.where(receiptdate <= CURRENTDATE,
                                 draw(seed, "returnflag", row, 0, 1), 2),
        "l_linestatus": np.where(shipdate > CURRENTDATE, 0, 1),
        "l_shipdate": shipdate,
        "l_commitdate": orderdate + draw(seed, "commit", row, 30, 90),
        "l_receiptdate": receiptdate,
        "l_shipinstruct": draw(seed, "instruct", row, 0, N_INSTRUCTIONS - 1),
        "l_shipmode": draw(seed, "mode", row, 0, N_MODES - 1),
        "l_comment": draw(seed, "l_comment", row, 0, N_NOUNS - 1)}
