"""TPC-H Query 17 'Small-Quantity-Order Revenue' (TPC Benchmark H revision 3,
clause 2.4.17, validation substitution BRAND = Brand#23, CONTAINER = MED
BOX) kept as a streaming materialized view, the way upstream RisingWave
keeps it (`e2e_test/tpch/`, ci q17). The published text:

    select sum(l_extendedprice) / 7.0 as avg_yearly from lineitem, part
    where p_partkey = l_partkey and p_brand = 'Brand#23'
      and p_container = 'MED BOX'
      and l_quantity < (select 0.2 * avg(l_quantity) from lineitem
                        where l_partkey = p_partkey)

The statement below is its DECORRELATED form (the configuration's
`deviations.statement`): the correlated subquery as a join against `SELECT
l_partkey, 0.2 * avg(l_quantity) ... GROUP BY l_partkey` — the rewrite
upstream's optimizer makes itself; same rows in, same row out. The oracle is
written from the published text, not from the rewrite, and in integers:
upstream's `0.2 * avg(l_quantity)` is NUMERIC, exact, so `l_quantity < 0.2 x
sum / count` is `5 x l_quantity x count < sum`, and a row with equality (a
TIE) does not count. Money is INT64 cents (`deviations.decimal`): the MV's
`avg_yearly` is cents / 7.0, a hundred times the published dollars.

The numpy oracle takes nothing from the engine: its rows come from
`benchmark/reference/tpch.py`.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark.reference import tpch

MV = "q17"
COLUMNS = ("avg_yearly",)
DTYPES = (np.float64,)
# The one row's one cell, to 1e-12. The engine sums the qualifying rows'
# cents in INT64 (exact; a window's sum is under 2^31) and divides ONCE by 7.0
# in a FLOAT64, as the oracle does: in a true float64 both round the same
# way; on the chip an f64 is two f32 (48 bits of significand) and its
# division is not correctly rounded: a few 2^-48 ~ 4e-15. 1e-12 leaves that
# two decades. What the limit must catch lies far above it: one row wrongly
# counted or dropped (a tie on the wrong side of `<`) moves the sum by a whole
# l_extendedprice, 1e-4..1e-1 of it; a division in ONE f32 reads 1e-8..6e-8
# (`tests/test_tpch_q17.py` holds both to the limit).
FLOAT_RTOL = 1e-12

TABLES = ("part", "lineitem")

STATEMENT = (
    "CREATE MATERIALIZED VIEW q17 AS "
    "SELECT sum(L.l_extendedprice) / 7.0 AS avg_yearly "
    "FROM lineitem L "
    "JOIN part P ON P.p_partkey = L.l_partkey "
    "JOIN (SELECT l_partkey AS agg_partkey, "
    "             0.2 * avg(l_quantity) AS avg_quantity "
    "      FROM lineitem GROUP BY l_partkey) A "
    "  ON A.agg_partkey = L.l_partkey "
    " AND L.l_quantity < A.avg_quantity "
    "WHERE P.p_brand = '{brand}' AND P.p_container = '{container}'")


def _require_spec_generator() -> None:
    """A program whose tpch connector does not take `scale_factor` and
    `seed`, or whose tables are not the spec's 9 and 16 columns wide, would
    take the DDL below, ignore the options and answer for another data set
    (the 1,000-part toy before PR 38): fail before the first statement
    instead. The one look at the engine in this file."""
    import inspect

    from risingwave_tpu.connectors import tpch as connector
    missing = ({"scale_factor", "seed"} - set(inspect.signature(
        connector.TpchGenerator.__init__).parameters))
    widths = {t: len(connector.TPCH_SCHEMAS[t]) for t in TABLES}
    if missing or widths != {"part": 9, "lineitem": 16}:
        raise RuntimeError(
            f"the tpch connector lacks {sorted(missing)} or its tables are "
            f"{widths} columns wide, not 9 and 16: this program cannot make "
            "TPC-H's data")


def ddl(config: dict, traffic: dict, seed: int) -> list:
    _require_spec_generator()
    gen = config["generator"]
    stmts = [f"SET {k} = {v}" for k, v in config["session_set"].items()]
    for t in TABLES:
        cs = traffic["chunk_size"][t]
        quota = cs * traffic["chunks_per_interval"][t]
        key = ", primary_key='p_partkey'" if t == "part" else ""
        stmts.append(
            f"CREATE SOURCE {t} WITH (connector='tpch', table='{t}', "
            f"scale_factor={gen['scale_factor']}, seed={seed}, "
            f"chunk_size={cs}, rate_limit={quota}{key})")
    stmts.append(STATEMENT.format(brand=gen["brand"],
                                  container=gen["container"]))
    return stmts


def small_quantity_revenue(part: dict, li: dict, brand: str,
                           container: str) -> dict:
    """The published query over the rows given, in integers. `cents`: the
    sum of l_extendedprice over the lineitems of a part that passes the
    filter whose quantity is under a fifth of the part's average quantity
    (`5 x q x count < sum`); `rows` how many they are; `ties` the lineitems
    of those parts with `5 x q x count == sum`, which do NOT count; `parts`
    the parts that pass the filter, `parts_with_lines` those of them some
    lineitem names."""
    ok = ((part["p_brand"] == tpch.BRANDS.index(brand))
          & (part["p_container"] == tpch.CONTAINERS.index(container)))
    keys = np.sort(part["p_partkey"][ok])
    pos = np.clip(np.searchsorted(keys, li["l_partkey"]), 0,
                  max(keys.shape[0] - 1, 0))
    member = (keys[pos] == li["l_partkey"]) if keys.shape[0] \
        else np.zeros(li["l_partkey"].shape[0], bool)
    g, q = pos[member], li["l_quantity"][member]
    count = np.bincount(g, minlength=keys.shape[0])
    total = np.bincount(g, weights=q, minlength=keys.shape[0]) \
        .astype(np.int64)
    lhs, rhs = 5 * q * count[g], total[g]
    small = lhs < rhs
    return {"cents": int(li["l_extendedprice"][member][small].sum()),
            "rows": int(small.sum()), "ties": int((lhs == rhs).sum()),
            "parts": int(keys.shape[0]),
            "parts_with_lines": int(np.count_nonzero(count))}


def events(offsets: dict, config: dict, seed: int) -> tuple:
    """Rows `[0, committed offset)` of both tables."""
    gen = config["generator"]
    return (tpch.part(0, offsets["part"], seed=seed),
            tpch.lineitem(0, offsets["lineitem"], seed=seed,
                          scale_factor=gen["scale_factor"]))


def oracle(offsets: dict, config: dict, seed: int) -> list:
    gen = config["generator"]
    part, li = events(offsets, config, seed)
    r = small_quantity_revenue(part, li, gen["brand"], gen["container"])
    # the tie count belongs on the run's `check` line, which the harness
    # prints and this file cannot add to: a line of its own, just before it
    print(json.dumps({"phase": "oracle", "ties_met": r["ties"],
                      "rows_summed": r["rows"],
                      "qualifying_parts": r["parts"],
                      "qualifying_parts_with_lineitems":
                          r["parts_with_lines"],
                      "sum_cents": r["cents"],
                      "avg_yearly_dollars": r["cents"] / 100 / 7.0}),
          flush=True)
    # SUM over no row is NULL: no finite number, and `compare` refuses it
    avg = np.float64(r["cents"]) / 7.0 if r["rows"] else np.nan
    return [np.asarray([avg], np.float64)]


def read_mv(session) -> list:
    return session.query("SELECT avg_yearly FROM q17")
