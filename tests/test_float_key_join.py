"""Joins on a FLOAT64 column through SQL: planned as the sorted join like
every other equi-join, durable across a crash, and equal to the nested-loop
reference (tests/_join_reference.py) on inner and outer joins.

The keys share integer parts (1.25 / 1.75 / 1.5), so a hash of the
truncated value would leave the exact compare to tell them apart; `-0.0`
and `0.0` are one key; NULL keys join nothing.
"""

from collections import Counter

import numpy as np
import pytest

from _join_reference import join_rows
from risingwave_tpu.frontend import Session
from risingwave_tpu.plan.build import _iter_executor_chain
from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
from risingwave_tpu.stream.sorted_join import SortedJoinExecutor

T_ROWS = [(1.25, 1), (1.75, 2), (-0.0, 3), (None, 4), (1.25, 5), (7.5, 6)]
U_ROWS = [(1.25, 10), (1.25, 11), (0.0, 12), (None, 13), (2.5, 14),
          (1.5, 15)]
T_LATE = [(2.5, 7), (1.5, 8)]
U_LATE = [(1.75, 20), (1.25, 21)]


def _values(rows):
    return ", ".join(
        "(" + ", ".join("NULL" if v is None else repr(v) for v in r) + ")"
        for r in rows)


async def _tables(s, t_rows=T_ROWS, u_rows=U_ROWS):
    await s.execute("CREATE TABLE t (f float64, a int64)")
    await s.execute("CREATE TABLE u (g float64, b int64)")
    await s.execute(f"INSERT INTO t VALUES {_values(t_rows)}")
    await s.execute(f"INSERT INTO u VALUES {_values(u_rows)}")


def _joins(s, mv):
    return [ex for roots in s.catalog.mvs[mv].deployment.roots.values()
            for root in roots for ex in _iter_executor_chain(root)
            if isinstance(ex, SortedJoinExecutor)]


def _counts(u_rows):
    """u's non-NULL keys grouped, as the MV `c` holds them: (g, n)."""
    return list(Counter(g for g, _ in u_rows if g is not None).items())


async def test_float_key_join_sql_crash_recover_golden(tmp_path):
    """An append-only join (t JOIN u) and one with a retracting side whose
    stream key IS the float key (t JOIN count-per-g), both durable: equal
    to the reference before the crash, and after recovery plus more rows
    on both sides — the late rows match keys stored before the crash."""
    d = str(tmp_path / "data")
    s = Session(store=HummockStateStore(LocalFsObjectStore(d)))
    await _tables(s)
    # (IS NOT NULL: the hash agg folds a NULL group key into the 0 group,
    # whatever the key's type — an older fault, not a join's)
    await s.execute("CREATE MATERIALIZED VIEW c AS SELECT g, count(*) AS n "
                    "FROM u WHERE g IS NOT NULL GROUP BY g")
    await s.execute("CREATE MATERIALIZED VIEW j AS "
                    "SELECT t.f, t.a, u.g, u.b FROM t JOIN u ON t.f = u.g")
    await s.execute("CREATE MATERIALIZED VIEW jc AS "
                    "SELECT t.f, t.a, c.g, c.n FROM t JOIN c ON t.f = c.g")
    await s.tick(4)
    assert Counter(s.query("SELECT f, a, g, b FROM j")) == join_rows(
        T_ROWS, U_ROWS)
    assert Counter(s.query("SELECT f, a, g, n FROM jc")) == join_rows(
        T_ROWS, _counts(U_ROWS))
    for mv in ("j", "jc"):
        (join,) = _joins(s, mv)
        assert join.state_tables[0] is not None
    await s.crash()

    s2 = Session(store=HummockStateStore(LocalFsObjectStore(d)))
    await s2.recover()
    await s2.execute(f"INSERT INTO t VALUES {_values(T_LATE)}")
    await s2.execute(f"INSERT INTO u VALUES {_values(U_LATE)}")
    await s2.tick(4)
    got = Counter(s2.query("SELECT f, a, g, b FROM j"))
    want = join_rows(T_ROWS + T_LATE, U_ROWS + U_LATE)
    assert got == want
    # stored before the crash, matched after it; and the two zeros
    assert got[(1.75, 2, 1.75, 20)] == 1 and got[(-0.0, 3, 0.0, 12)] == 1
    # the counts of 1.25 and 1.75 moved: the join retracted the old rows
    assert Counter(s2.query("SELECT f, a, g, n FROM jc")) == join_rows(
        T_ROWS + T_LATE, _counts(U_ROWS + U_LATE))
    await s2.drop_all()


async def test_float_key_join_plans_sorted_join():
    """EXPLAIN and the deployed fragment graph of a float-keyed join hold
    the one streaming join; an outer and a temporal float-keyed join, both
    refused until the hash join went, plan the same node."""
    s = Session()
    await _tables(s)
    on = "FROM t {} JOIN u {} ON t.f = u.g"
    for how, as_of in (("", ""), ("LEFT OUTER", ""), ("FULL OUTER", ""),
                       ("", "FOR SYSTEM_TIME AS OF PROCTIME()"),
                       ("LEFT", "FOR SYSTEM_TIME AS OF PROCTIME()")):
        lines = [ln for (ln,) in await s.execute(
            "EXPLAIN CREATE MATERIALIZED VIEW x AS SELECT t.a, u.b "
            + on.format(how, as_of))]
        assert [ln.strip() for ln in lines if "join" in ln] == [
            "sorted_join lkeys=[0] rkeys=[0]"], (how, as_of, lines)
    await s.execute("CREATE MATERIALIZED VIEW j AS SELECT t.a, u.b "
                    + on.format("", ""))
    (join,) = _joins(s, "j")
    assert np.dtype(join._col_dtypes[0][0]) == np.float64
    graph = s.catalog.mvs["j"].deployment.rebuild_info["graph"]
    kinds = [n for f in graph.fragments.values()
             for n in _node_kinds(f.root)]
    assert kinds.count("sorted_join") == 1
    await s.drop_all()


def _node_kinds(node):
    if not hasattr(node, "kind"):
        return []
    return [node.kind] + [k for i in node.inputs for k in _node_kinds(i)]


@pytest.mark.parametrize("join_type", ["left", "full"])
async def test_float_key_outer_join_sql_golden(join_type):
    """A FLOAT64-keyed LEFT / FULL OUTER join against the reference: NULL
    keys and keys with no partner show NULL-padded, on the preserved
    side(s) only; a later partner takes the padded row back."""
    s = Session()
    await _tables(s)
    await s.execute(
        "CREATE MATERIALIZED VIEW oj AS SELECT t.f, t.a, u.g, u.b "
        f"FROM t {join_type.upper()} OUTER JOIN u ON t.f = u.g")
    await s.tick(3)
    got = Counter(s.query("SELECT f, a, g, b FROM oj"))
    assert got == join_rows(T_ROWS, U_ROWS, join_type=join_type)
    assert got[(7.5, 6, None, None)] == 1 and got[(None, 4, None, None)] == 1
    assert got[(None, None, 2.5, 14)] == (1 if join_type == "full" else 0)
    await s.execute(f"INSERT INTO u VALUES {_values([(7.5, 30)])}")
    await s.execute(f"INSERT INTO t VALUES {_values(T_LATE)}")
    await s.tick(3)
    got = Counter(s.query("SELECT f, a, g, b FROM oj"))
    assert got == join_rows(T_ROWS + T_LATE, U_ROWS + [(7.5, 30)],
                            join_type=join_type)
    assert got[(7.5, 6, 7.5, 30)] == 1 and (7.5, 6, None, None) not in got
    await s.drop_all()


async def test_float_key_join_on_the_mesh():
    """With `streaming_parallelism_devices` the float-keyed join is placed
    on the device mesh like an integer-keyed one (rows routed by the vnode
    hash of the float key) and gives the reference's rows."""
    from risingwave_tpu.stream.sharded_join import ShardedSortedJoinExecutor
    s = Session()
    await _tables(s)
    await s.execute("SET streaming_parallelism_devices = 4")
    await s.execute("CREATE MATERIALIZED VIEW mj AS "
                    "SELECT t.f, t.a, u.g, u.b FROM t JOIN u ON t.f = u.g")
    (join,) = _joins(s, "mj")
    assert isinstance(join, ShardedSortedJoinExecutor)
    await s.execute(f"INSERT INTO t VALUES {_values(T_LATE)}")
    await s.tick(3)
    await s.execute(f"INSERT INTO u VALUES {_values(U_LATE)}")
    await s.tick(3)
    assert Counter(s.query("SELECT f, a, g, b FROM mj")) == join_rows(
        T_ROWS + T_LATE, U_ROWS + U_LATE)
    await s.drop_all()
