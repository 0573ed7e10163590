"""Snapshot join-agg fusion (stream/snapshot_join_agg.py): the q17
shape must LOWER to the fused executor (not silently fall back to the
storm-prone join plan), and the fused result must agree with the
generic changelog plan on the same committed prefix.

Reference: the join-against-own-aggregate sub-plan of
/root/reference/e2e_test/tpch q17.
"""

import numpy as np
import pytest

from risingwave_tpu.common.chunk import (
    OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, StreamChunk)
from risingwave_tpu.common.types import DataType, schema
from risingwave_tpu.expr.agg import AggCall, AggKind
from risingwave_tpu.expr.ir import call, col
from risingwave_tpu.frontend import Session
from risingwave_tpu.stream.snapshot_join_agg import SnapshotJoinAggExecutor
from risingwave_tpu.stream.sorted_join import SortedJoinExecutor

Q17ISH = (
    "SELECT sum(L.l_extendedprice) / 7.0 AS avg_yearly "
    "FROM lineitem L "
    "JOIN part P ON P.p_partkey = L.l_partkey "
    "JOIN (SELECT l_partkey AS agg_partkey, "
    "             0.2 * avg(l_quantity) AS avg_quantity "
    "      FROM lineitem GROUP BY l_partkey) A "
    "  ON A.agg_partkey = L.l_partkey "
    " AND L.l_quantity < A.avg_quantity "
    "WHERE P.p_brand = 'Brand#23'")


def _executors(session, mv_name, klass):
    out = []
    for roots in session.catalog.mvs[mv_name].deployment.roots.values():
        for root in roots:
            node = root
            while node is not None:
                if isinstance(node, klass):
                    out.append(node)
                node = getattr(node, "input", None)
    return out


# a 200-part universe (TPC-H at SF 0.001) under a seed at which four parts
# are Brand#23 (the tests here filter on the brand alone)
SF, SEED = 0.001, 15


async def _mk_sources(s):
    await s.execute(
        "CREATE SOURCE part WITH (connector='tpch', table='part', "
        f"scale_factor={SF}, seed={SEED}, "
        "chunk_size=512, rate_limit=512, primary_key='p_partkey')")
    await s.execute(
        "CREATE SOURCE lineitem WITH (connector='tpch', "
        f"table='lineitem', scale_factor={SF}, seed={SEED}, "
        "chunk_size=512, rate_limit=1024)")


def _prefix(table, n):
    """Rows [0, n) of the engine's own generator, column name -> array."""
    from risingwave_tpu.connectors import TpchGenerator
    g = TpchGenerator(table, chunk_size=max(256, n), scale_factor=SF,
                      seed=SEED)
    c = g.next_chunk()
    return {f.name: np.asarray(col.data)[:n]
            for f, col in zip(g.schema, c.columns)}


async def test_q17_shape_lowers_to_fused_executor():
    s = Session()
    await _mk_sources(s)
    await s.execute(f"CREATE MATERIALIZED VIEW fz AS {Q17ISH}")
    fused = _executors(s, "fz", SnapshotJoinAggExecutor)
    assert fused, "q17 shape did not lower to SnapshotJoinAggExecutor"
    assert not _executors(s, "fz", SortedJoinExecutor), \
        "fused plan still contains a streaming join"
    await s.drop_all()


def _source_offsets(session, mv_name):
    """COMMITTED offsets from the source state tables (the connector's
    in-memory offset runs ahead of the last checkpoint)."""
    from risingwave_tpu.state.storage_table import StorageTable
    from risingwave_tpu.stream.source import SourceExecutor
    offs = {}
    for roots in session.catalog.mvs[mv_name].deployment.roots.values():
        for root in roots:
            node = root
            while node is not None:
                if isinstance(node, SourceExecutor) \
                        and node.state_table is not None:
                    st = StorageTable.for_state_table(node.state_table)
                    rows = list(st.batch_iter())
                    offs.setdefault(node.connector.table, 0)
                    offs[node.connector.table] = max(
                        offs[node.connector.table],
                        int(rows[0][1]) if rows else 0)
                node = getattr(node, "input", None)
    return offs


def _q17ish_oracle(part_n, li_n):
    from risingwave_tpu.common.types import GLOBAL_DICT

    p = _prefix("part", part_n)
    li = _prefix("lineitem", li_n)
    wb = GLOBAL_DICT.get_or_insert("Brand#23")
    ok = {int(k) for k, b in zip(p["p_partkey"], p["p_brand"])
          if int(b) == wb}
    by = {}
    for pk, q, ep in zip(li["l_partkey"], li["l_quantity"],
                         li["l_extendedprice"]):
        by.setdefault(int(pk), []).append((int(q), int(ep)))
    total, n = 0, 0
    for pk, rows in by.items():
        if pk not in ok:
            continue
        thr = 0.2 * sum(q for q, _ in rows) / len(rows)
        sel = [ep for q, ep in rows if q < thr]
        total += sum(sel)
        n += len(sel)
    return (total / 7.0, n)


async def test_fused_matches_generic_plan():
    """Differential: the fused executor AND the changelog join plan
    (SET streaming_snapshot_fuse = 0) each against the host oracle at
    their own committed offsets (the MVs advance from different DDL
    epochs, so their prefixes differ — each must still be exact)."""
    s = Session()
    await _mk_sources(s)
    await s.execute("SET streaming_join_capacity = 32768")
    await s.execute(f"CREATE MATERIALIZED VIEW f1 AS {Q17ISH}")
    assert _executors(s, "f1", SnapshotJoinAggExecutor)
    await s.execute("SET streaming_snapshot_fuse = 0")
    await s.execute(f"CREATE MATERIALIZED VIEW f0 AS {Q17ISH}")
    assert not _executors(s, "f0", SnapshotJoinAggExecutor)
    assert _executors(s, "f0", SortedJoinExecutor)
    await s.tick(4)
    nonvacuous = 0
    for name in ("f1", "f0"):
        got = s.query(f"SELECT avg_yearly FROM {name}")
        assert len(got) == 1
        offs = _source_offsets(s, name)
        exp, nsel = _q17ish_oracle(offs["part"], offs["lineitem"])
        v = got[0][0]
        if nsel == 0:
            # empty sum: fused emits SQL NULL, the generic SimpleAgg 0
            assert v in (None, 0.0)
        else:
            assert v is not None
            assert abs(v - exp) < 1e-6 * max(1.0, abs(exp)), \
                f"{name}: {v} != oracle {exp}"
            nonvacuous += 1
    assert nonvacuous == 2, "differential vacuous — no qualifying rows"
    await s.drop_all()


async def test_sub_where_group_existence():
    """A group whose rows ALL fail the subquery WHERE produces no A row,
    so the inner join must drop its fact rows — even when the residue
    compares against count() (always-valid, 0 for the missing group)."""
    s = Session()
    await _mk_sources(s)
    await s.execute(
        "CREATE MATERIALIZED VIEW ge AS "
        "SELECT count(*) AS n FROM lineitem L "
        "JOIN part P ON P.p_partkey = L.l_partkey "
        "JOIN (SELECT l_partkey AS k, count(l_quantity) AS c "
        "      FROM lineitem WHERE l_quantity > 48 GROUP BY l_partkey) A "
        "  ON A.k = L.l_partkey AND L.l_quantity < A.c + 100")
    assert _executors(s, "ge", SnapshotJoinAggExecutor)
    await s.tick(3)
    got = s.query("SELECT n FROM ge")[0][0]
    offs = _source_offsets(s, "ge")
    p = _prefix("part", offs["part"])
    li = _prefix("lineitem", offs["lineitem"])
    parts_seen = {int(k) for k in p["p_partkey"]}
    has_high = {}
    for pk, q in zip(li["l_partkey"], li["l_quantity"]):
        if int(q) > 48:
            has_high[int(pk)] = has_high.get(int(pk), 0) + 1
    exp = sum(1 for pk, q in zip(li["l_partkey"], li["l_quantity"])
              if int(pk) in parts_seen and int(pk) in has_high
              and int(q) < has_high[int(pk)] + 100)
    n_total = sum(1 for pk in li["l_partkey"] if int(pk) in parts_seen)
    assert 0 < exp < n_total, "oracle not discriminating"
    assert got == exp, f"group existence violated: got {got}, want {exp}"
    await s.drop_all()


async def test_fused_handles_sub_where_and_no_residue():
    """Generalization probes: a WHERE inside the agg subquery (sub-side
    row mask) and a shape with equi-link only (no residue)."""
    s = Session()
    await _mk_sources(s)
    await s.execute(
        "CREATE MATERIALIZED VIEW g1 AS "
        "SELECT count(L.l_extendedprice) AS n, sum(L.l_quantity) AS sq "
        "FROM lineitem L "
        "JOIN part P ON P.p_partkey = L.l_partkey "
        "JOIN (SELECT l_partkey AS k, min(l_quantity) AS mq "
        "      FROM lineitem WHERE l_quantity > 3 GROUP BY l_partkey) A "
        "  ON A.k = L.l_partkey AND L.l_quantity <= A.mq "
        "WHERE P.p_brand = 'Brand#23'")
    assert _executors(s, "g1", SnapshotJoinAggExecutor)
    await s.tick(3)
    got = s.query("SELECT n, sq FROM g1")
    assert len(got) == 1
    n, sq = got[0]
    # oracle on the committed prefix
    from risingwave_tpu.common.types import GLOBAL_DICT
    offs = _source_offsets(s, "g1")
    p = _prefix("part", offs["part"])
    li = _prefix("lineitem", offs["lineitem"])
    wb = GLOBAL_DICT.get_or_insert("Brand#23")
    ok = {int(k) for k, b in zip(p["p_partkey"], p["p_brand"])
          if int(b) == wb}
    mq = {}
    for pk, q in zip(li["l_partkey"], li["l_quantity"]):
        if int(q) > 3:
            mq[int(pk)] = min(mq.get(int(pk), 10**9), int(q))
    exp_n = exp_sq = 0
    for pk, q in zip(li["l_partkey"], li["l_quantity"]):
        if int(pk) in ok and int(pk) in mq and int(q) <= mq[int(pk)]:
            exp_n += 1
            exp_sq += int(q)
    assert n == exp_n and (sq == exp_sq or (sq is None and exp_n == 0)), \
        f"got ({n}, {sq}) want ({exp_n}, {exp_sq})"
    assert exp_n > 0, "oracle vacuous"
    await s.drop_all()


# --------------------------------------------------------------------------
# the barrier program itself: membership and the final aggregate against a
# numpy statement of the query, on hand-built stores

class _Input:
    def __init__(self, schema):
        self.schema = schema

    def fence_tokens(self):
        return []


def _flush_executor(agg=AggKind.SUM, x_type=DataType.INT64, capacity=64,
                    dim_capacity=8, square=False):
    """SELECT <agg>(x) FROM L JOIN P ON P.pk = L.k
       JOIN (SELECT k, sum(q) AS s FROM L GROUP BY k) A
         ON A.k = L.k AND L.q < A.s
    (`square`: the subquery selects sum(q) * sum(q) AS s, a threshold a
    one-row group can pass)"""
    final = AggCall(agg, 2, DataType.INT64 if agg is AggKind.COUNT
                    else x_type, True)
    s = col(0, DataType.INT64)
    return SnapshotJoinAggExecutor(
        _Input(schema(("k", DataType.INT64), ("q", DataType.INT64),
                      ("x", x_type))),
        _Input(schema(("pk", DataType.INT64))),
        fact_key=0, dim_key=0,
        sub_agg_calls=[AggCall(AggKind.SUM, 1, DataType.INT64, True)],
        sub_items=[call("multiply", s, s) if square else s],
        residue=call("less_than", col(1, DataType.INT64),
                     col(3, DataType.INT64)),
        final_agg_calls=[final], final_items=[col(0, final.ret_type)],
        out_names=["v"], out_types=[final.ret_type],
        capacity=capacity, dim_capacity=dim_capacity)


def _aggregated(facts, parts, square=False):
    """Per fact, in arrival order: does the query aggregate it. Facts are
    (k, q, x) triples, any of the three None for SQL NULL; parts are keys.
    A NULL key joins nothing, a NULL quantity is in no sum and under no
    threshold, a group whose quantities are all NULL has no threshold."""
    sums = {}
    for k, q, _ in facts:
        if k is not None and q is not None:
            sums[k] = sums.get(k, 0) + q
    thr = {k: s * s if square else s for k, s in sums.items()}
    return np.asarray([k in thr and k in parts and q is not None
                       and q < thr[k] for k, q, _ in facts], dtype=bool)


def _numpy_statement(facts, parts, agg, square=False):
    """The query over every row so far. None where no row is aggregated
    (SQL NULL; 0 for a count); a NULL x is aggregated into nothing."""
    sel = [x for (_, _, x), s in zip(facts, _aggregated(facts, parts, square))
           if s and x is not None]
    if agg is AggKind.COUNT:
        return len(sel)
    if not sel:
        return None
    return {AggKind.SUM: np.sum, AggKind.MIN: np.min,
            AggKind.MAX: np.max}[agg](np.asarray(sel)).item()


def _sum_in_key_arrival_order(facts, parts, capacity, within_key=1):
    """A FLOAT64 SUM as the barrier program takes it: one reduction over the
    `capacity` lanes laid out by (key, arrival) -- NULL keys and dead lanes
    behind every key, in their own arrival order -- holding x where the row
    is aggregated and 0 elsewhere. `within_key=-1` lays a key's rows out
    last arrival first: what an unstable sort might do."""
    import jax.numpy as jnp
    last = np.iinfo(np.int64).max
    key = np.full(capacity, last, dtype=np.int64)
    key[:len(facts)] = [last if k is None else k for k, _, _ in facts]
    lane = np.zeros(capacity, dtype=np.float64)
    lane[:len(facts)] = [x if s and x is not None else 0.0 for (_, _, x), s
                         in zip(facts, _aggregated(facts, parts))]
    order = np.lexsort((within_key * np.arange(capacity), key))
    return float(jnp.sum(jnp.asarray(lane[order])))


# part 5: the quantities sum to 4, so its second line does not pass `<`
_LINES = [(5, 0, 10), (5, 4, 20), (7, 2, 40), (7, 3, 80), (7, 9, 160),
          (8, 1, 320), (8, 1, 640)]
# run g (key 100 + g) holds g + 1 rows, arriving round robin: 91 rows; a
# row's quantity is its run's number, so a run's threshold is its own
_STAIRS = [(100 + g, g, 1 << (g + r)) for r in range(13)
           for g in range(r, 13)]
# sums whose low bits depend on the order of the addends
_ABSORBING = [0.1, 1e15 + 0.3, 7.7e-3, 2e15, 1e-9, 2.5, 1e15, 0.7, 3e15,
              3e-7, 1.1, 9e14, 5e14, 0.3]
# case -> (executor options, [(lineitems, part keys) of each barrier])
FLUSH_CASES = {
    # a part no lineitem names, a lineitem no part names
    "unnamed_part": ({}, [(_LINES, [5, 99])]),
    # member flips between two flushes: the diff chunk is U- / U+
    "part_one_barrier_later": ({}, [(_LINES, [5]), ([], [7]), ([], [])]),
    # a NULL l_partkey whose data lane reads a live part's key (5): joins
    # nothing though it passes `<`, and its quantity stays out of part 5's
    "null_partkey": ({}, [(_LINES + [(None, 50, 1280), (None, 0, 2560)],
                           [5, 7])]),
    "empty_stores": ({}, [([], []), (_LINES, []), ([], [8])]),
    "part_below_and_above": ({}, [(_LINES, [1, 7, 1000])]),
    "part_store_full": ({}, [(_LINES, [1, 2, 3, 5, 6, 8, 9, 10])]),
    # dead lanes of both stores read key 0: a live lineitem of part 0 is no
    # member until part 0 is there
    "dead_lanes_share_a_key": ({}, [(_LINES + [(0, 1, 1280), (0, 2, 2560)],
                                     [7]), ([], [0])]),
    # the planner promises a unique key; two marks over a run still read > 0
    "duplicate_part_key": ({}, [(_LINES, [7, 7, 5])]),
    "fact_store_full": (dict(capacity=8), [(_LINES + [(9, 1, 1)], [5, 9]),
                                           ([], [7])]),
    "count_final": (dict(agg=AggKind.COUNT), [(_LINES, [5]), ([], [8])]),
    "min_final": (dict(agg=AggKind.MIN), [(_LINES, [7, 8]), ([], [5])]),
    "max_final": (dict(agg=AggKind.MAX), [(_LINES, [5, 8]), ([], [7])]),
    "float64_sum": (dict(x_type=DataType.FLOAT64),
                    [([(k, q, x * 0.1 + 1e-9) for k, q, x in _LINES],
                      [5, 7]), ([], [8])]),
    # the validity bits ride the sort in one word. A NULL quantity inside a
    # run (its data lane reads 1, which would pass) is in no sum and passes
    # nothing: part 7's threshold is 5 without it, 6 with it
    "null_quantity_in_a_run": (
        {}, [(_LINES[:3] + [(7, None, 1280)] + _LINES[3:]
              + [(7, None, 2560), (7, 5, 5120)], [5, 7, 8])]),
    # a NULL x passes but adds nothing, and count(x) does not count it
    "null_x_in_a_run": (
        {}, [(_LINES[:3] + [(7, 1, None)] + _LINES[3:] + [(8, 0, None)],
              [7, 8])]),
    "null_x_not_counted": (
        dict(agg=AggKind.COUNT),
        [(_LINES[:3] + [(7, 1, None)] + _LINES[3:] + [(8, 0, None)],
          [7, 8])]),
    # every quantity of part 6 is NULL: its sum is NULL, it has no
    # threshold, its rows join nothing -- until one quantity is there
    "group_of_null_quantities": (
        {}, [(_LINES + [(6, None, 1280), (6, None, 2560)], [6, 7]),
             ([(6, 3, 5120), (6, 2, 10240)], [])]),
    # ONE run as long as the store (48 rows: the fill crosses 2^k edges
    # that are no run's edge), the store exactly full
    "one_run_spans_the_store": (
        dict(capacity=48),
        [([(7, q % 5, 1 << (q % 40)) for q in range(30)], [7]),
         ([(7, 100 + q, 1 << (q % 40)) for q in range(30, 47)]
          + [(7, -5000, 1)], [])]),
    # every run one row long, keys arriving out of order, the store exactly
    # full: group g's threshold q * q reaches row g and no other
    "all_keys_distinct": (
        dict(capacity=48, square=True),
        [([((k * 29) % 48, k % 4 - 1, 1 << (k % 40)) for k in range(48)],
          [1, 5, 6, 7, 40, 47])]),
    # distinct keys in front of a sentinel run of NULL keys and dead lanes
    "distinct_keys_then_dead_lanes": (
        dict(capacity=80, square=True),
        [([((k * 7) % 50, k % 5 - 2, 1 << (k % 40)) for k in range(50)]
          + [(None, 3, 1 << 41), (None, -2, 1 << 42)], [0, 3, 4, 11, 49]),
         ([], [7, 8])]),
    # runs of 1, 2, .. 13 rows, each with its own threshold
    "runs_of_every_length": (
        dict(capacity=96),
        [(_STAIRS[:40], [100, 101, 105, 108, 112]),
         (_STAIRS[40:], [103, 111])]),
    # one key's rows arrive between other keys' over two barriers: the sum
    # adds them in arrival order
    "float64_sum_order": (
        dict(x_type=DataType.FLOAT64),
        [([(k, 1, x) for x in _ABSORBING[:7] for k in (9, 7, 5)], [7]),
         ([(k, 1, x) for x in _ABSORBING[7:] for k in (7, 9)], [9])]),
}


@pytest.mark.parametrize("case", sorted(FLUSH_CASES))
def test_flush_against_a_numpy_statement(case):
    opts, barriers = FLUSH_CASES[case]
    ex = _flush_executor(**opts)
    agg = ex.final_agg_calls[0].kind
    square = opts.get("square", False)
    fsch, dsch = (i.schema for i in ex.inputs)
    facts, parts, shown = [], [], "nothing yet"
    for new_facts, new_parts in barriers:
        if new_facts:
            # a NULL cell's data lane reads what would join and pass
            there = [np.asarray([r[c] is not None for r in new_facts])
                     for c in range(3)]
            cols = [[blank if r[c] is None else r[c] for r in new_facts]
                    for c, blank in enumerate((5, 1, 999))]
            (ex._fcols, ex._fvalids, ex._fn, ex._errs) = ex._append_fact(
                ex._fcols, ex._fvalids, ex._fn, ex._errs,
                StreamChunk.from_numpy(
                    fsch, cols, capacity=max(16, len(new_facts)),
                    valids=there))
        if new_parts:
            ex._dkeys, ex._dn, ex._errs = ex._append_dim(
                ex._dkeys, ex._dn, ex._errs,
                StreamChunk.from_numpy(dsch, [new_parts], capacity=16))
        facts += new_facts
        parts += new_parts
        ex._prev, ex._prev_valid, ex._emitted, out = ex._flush(
            ex._fcols, ex._fvalids, ex._fn, ex._dkeys, ex._dn,
            ex._prev, ex._prev_valid, ex._emitted)
        assert not np.asarray(ex._errs).any()
        assert (int(ex._fn), int(ex._dn)) == (len(facts), len(parts))
        want = _numpy_statement(facts, parts, agg, square)
        if shown == "nothing yet":
            expect = [(OP_INSERT, want)]
        elif shown == want:
            expect = []
        else:
            expect = [(OP_UPDATE_DELETE, shown), (OP_UPDATE_INSERT, want)]
        got = [(op, v) for op, (v,) in out.to_rows()]
        if isinstance(want, float):
            assert [op for op, _ in got] == [op for op, _ in expect]
            np.testing.assert_allclose([v for _, v in got],
                                       [v for _, v in expect], rtol=1e-12)
            # to the bit: the rows of a run are added in arrival order
            exact = _sum_in_key_arrival_order(facts, parts, ex.capacity)
            assert got[-1][1].hex() == exact.hex()
        else:
            assert got == expect, (len(facts), len(parts))
        shown = want
    if case == "float64_sum_order":
        # the case can tell: another order within a key, another sum
        assert exact != _sum_in_key_arrival_order(
            facts, parts, ex.capacity, within_key=-1)
    if case == "part_store_full":
        assert len(parts) == ex.dim_capacity
    if case in ("fact_store_full", "one_run_spans_the_store",
                "all_keys_distinct"):
        assert len(facts) == ex.capacity


def _eqns(jaxpr, inside=()):
    """Every equation of a jaxpr, those of its sub-jaxprs (loop bodies,
    jits, cond branches) included, each with the names of the jits that
    enclose it, outermost first."""
    for e in jaxpr.eqns:
        yield e, inside
        within = inside + ((e.params["name"],) if e.primitive.name in (
            "jit", "pjit") else ())
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, within)


def _flush_jaxpr(C, Cd):
    import jax
    ex = _flush_executor(capacity=C, dim_capacity=Cd)
    return jax.make_jaxpr(ex._flush_impl)(
        ex._fcols, ex._fvalids, ex._fn, ex._dkeys, ex._dn, ex._prev,
        ex._prev_valid, ex._emitted).jaxpr


def _carried(e):
    """What a loop equation carries from step to step (nothing for any
    other equation)."""
    if e.primitive.name == "scan":
        n = e.params["num_consts"]
        return e.invars[n:n + e.params["num_carry"]]
    if e.primitive.name == "while":
        return e.invars[e.params["cond_nconsts"]
                        + e.params["body_nconsts"]:]
    return ()


_rows = lambda v: v.aval.shape[:1]  # noqa: E731
# the loops that CARRY capacity-wide lanes by design: a stage shifts every
# lane by a power of two and selects (no index per row)
STAGE_LOOPS = {"_move", "_fill_forward"}


def test_no_capacity_wide_pass_asks_a_tiny_target():
    """Three ops the barrier program must not grow back (2.63 + 0.86 s of a
    5.49 s checkpoint at 2^23, PERF.md PR 39): a search loop that carries
    one query per fact row, a gather of as many indices out of the dim
    store, a scatter-add of every row onto one address. A loop that carries
    `C` rows is a stage loop of a log-step move or fill, told apart by the
    jit it sits in; the searches carry `Cd` queries."""
    C, Cd = 4096, 16
    searches = 0
    for e, inside in _eqns(_flush_jaxpr(C, Cd)):
        name = e.primitive.name
        carried = _carried(e)
        if any(_rows(v) == (C,) for v in carried):
            assert inside and inside[-1] in STAGE_LOOPS, \
                f"a loop carries {C} queries: {inside} {e}"
        elif carried:
            assert inside[-1] == "searchsorted" and all(
                _rows(v) in ((), (Cd,)) for v in carried), (inside, e)
            searches += 1
        if name == "gather":
            operand, indices = e.invars[:2]
            assert not (_rows(operand) == (Cd,) and _rows(indices) == (C,)), \
                f"{C} indices gathered from the {Cd}-key dim store: {e}"
        if name.startswith("scatter"):
            assert e.invars[0].aval.size > 1, \
                f"a scatter onto one address: {e}"
    # the two searches of the dim keys in the sorted fact keys are there
    assert searches == 2


def test_no_capacity_wide_lane_moves_by_an_index_vector():
    """The barrier program gathers no `C`-row lane by `C` indices (thirteen
    such passes, `[order]` of the columns and `[gid]` of a group's values,
    were 1.15 s of a 3.0 s checkpoint at 2^23: PERF.md PR 47): the rows ride
    ONE sort as payload, a group's values reach its rows by a move and a
    fill whose stages read at an offset. Inside a stage loop there is no
    gather of more than the one step it reads from its table."""
    C, Cd = 4096, 16
    sorts, stage_loops = [], 0
    for e, inside in _eqns(_flush_jaxpr(C, Cd)):
        name = e.primitive.name
        if name == "gather":
            operand, indices = e.invars[:2]
            assert not (_rows(operand) == (C,) and _rows(indices) == (C,)), \
                f"{C} rows gathered by {C} indices: {inside} {e}"
            assert not set(inside) & STAGE_LOOPS or indices.aval.size == 1, \
                f"a stage gathers by index: {inside} {e}"
        if name == "sort" and _rows(e.invars[0]) == (C,):
            sorts.append(e)
        stage_loops += any(_rows(v) == (C,) for v in _carried(e))
    # one stable sort, one key, the word and the two other columns behind it
    (srt,) = sorts
    assert srt.params["num_keys"] == 1 and srt.params["is_stable"]
    assert len(srt.invars) == 4
    # run starts to group space, group values to the run starts, the fill
    assert stage_loops == 3
