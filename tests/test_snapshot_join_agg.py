"""Snapshot join-agg fusion (stream/snapshot_join_agg.py): the q17
shape must LOWER to the fused executor (not silently fall back to the
storm-prone join plan), and the fused result must agree with the
generic changelog plan on the same committed prefix.

Reference: the join-against-own-aggregate sub-plan of
/root/reference/e2e_test/tpch q17.
"""

import numpy as np

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
