"""TPC-H q17 as a streaming MV (BASELINE staged config 5): deep
join/agg cascade — lineitem x part x (0.2*avg(l_quantity) per partkey),
global retractable sum on top. The avg subquery RETRACTS on every
update, exercising the sorted join's retraction path under a condition
against a float aggregate.

Reference: /root/reference/e2e_test/tpch/ (q17), ci q17.sql.
"""

from risingwave_tpu.frontend import Session
from risingwave_tpu.state.storage_table import StorageTable
from risingwave_tpu.stream.source import SourceExecutor

from benchmark.queries import q17
from benchmark.reference import tpch

Q17 = q17.STATEMENT.format(brand="Brand#23", container="MED BOX")
# TPC-H at SF 0.001 (200 parts, all of them in the first part chunk) under a
# seed at which two parts are Brand#23 in a MED BOX and lineitems of theirs
# count from the second tick on: the published filter is not vacuous
SF, SEED = 0.001, 15
GEN = f"scale_factor={SF}, seed={SEED}"


def _committed_offsets(session, mv_name):
    out = {}
    for roots in session.catalog.mvs[mv_name].deployment.roots.values():
        for root in roots:
            node = root
            while node is not None:
                if isinstance(node, SourceExecutor) \
                        and node.state_table is not None:
                    st = StorageTable.for_state_table(node.state_table)
                    rows = list(st.batch_iter())
                    out.setdefault(node.connector.table, 0)
                    out[node.connector.table] = max(
                        out[node.connector.table],
                        int(rows[0][1]) if rows else 0)
                node = getattr(node, "input", None)
    return out


def _oracle(part_n, li_n, container: bool = True):
    """avg_yearly (cents / 7.0) over the benchmark's numpy rows, the
    threshold in floats as the statement words it."""
    p = tpch.part(0, part_n, seed=SEED)
    li = tpch.lineitem(0, li_n, seed=SEED, scale_factor=SF)
    if container:
        r = q17.small_quantity_revenue(p, li, "Brand#23", "MED BOX")
        return r["cents"] / 7.0
    parts_ok = {int(k) for k, b in zip(p["p_partkey"], p["p_brand"])
                if tpch.BRANDS[int(b)] == "Brand#23"}
    by_part: dict[int, list] = {}
    for pk, q, ep in zip(li["l_partkey"], li["l_quantity"],
                         li["l_extendedprice"]):
        by_part.setdefault(int(pk), []).append((int(q), int(ep)))
    total = 0
    for pk, rows in by_part.items():
        if pk not in parts_ok:
            continue
        thr = 0.2 * (sum(q for q, _ in rows) / len(rows))
        total += sum(ep for q, ep in rows if q < thr)
    return total / 7.0


async def test_q17_streaming_golden():
    s = Session()
    await s.execute("SET streaming_join_capacity = 32768")
    await s.execute(
        f"CREATE SOURCE part WITH (connector='tpch', table='part', {GEN}, "
        "chunk_size=256, rate_limit=256, primary_key='p_partkey')")
    await s.execute(
        f"CREATE SOURCE lineitem WITH (connector='tpch', {GEN}, "
        "table='lineitem', chunk_size=512, rate_limit=1024)")
    await s.execute(Q17)
    await s.tick(5)
    got = s.query("SELECT avg_yearly FROM q17")
    offs = _committed_offsets(s, "q17")
    exp = _oracle(offs["part"], offs["lineitem"])
    assert len(got) == 1
    assert got[0][0] is not None, "q17 produced NULL — oracle vacuous"
    assert abs(got[0][0] - exp) < 1e-6 * max(1.0, abs(exp)), \
        f"q17 diverged: {got[0][0]} vs oracle {exp}"
    assert exp > 0, "q17 oracle vacuous"
    await s.drop_all()


async def test_q17_survives_crash_recovery(tmp_path):
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    import asyncio
    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / "d")))
    s = Session(store=store)
    await s.execute("SET streaming_join_capacity = 32768")
    # brand-only filter: four of the 200 parts pass, where the published
    # brand + container predicate passes two (the exact q17 text is covered
    # by the golden test above and by tests/test_q17_published.py)
    await s.execute(
        f"CREATE SOURCE part WITH (connector='tpch', table='part', {GEN}, "
        "chunk_size=512, rate_limit=512, primary_key='p_partkey')")
    await s.execute(
        f"CREATE SOURCE lineitem WITH (connector='tpch', {GEN}, "
        "table='lineitem', chunk_size=256, rate_limit=512)")
    await s.execute(Q17.replace(
        " AND P.p_container = 'MED BOX'", ""))
    await s.tick(3)
    victim = s.catalog.mvs["q17"].deployment.tasks[-1]
    victim.cancel()
    try:
        await victim
    except (asyncio.CancelledError, Exception):
        pass
    await s.tick(3)
    assert s.recoveries >= 1
    got = s.query("SELECT avg_yearly FROM q17")
    offs = _committed_offsets(s, "q17")
    exp = _oracle(offs["part"], offs["lineitem"], container=False)
    assert len(got) == 1 and got[0][0] is not None, \
        "no qualifying rows after recovery — check is vacuous"
    assert abs(got[0][0] - exp) < 1e-6 * max(1.0, abs(exp)), \
        f"q17 diverged after recovery: {got[0][0]} vs {exp}"
    await s.drop_all()
