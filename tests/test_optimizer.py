"""Bind-time optimizer passes: predicate pushdown + join input pruning
(VERDICT r3 #6 — reference: logical_optimization.rs FilterJoinRule /
column pruning). Structural plan snapshots + an e2e equivalence check.
"""

from collections import Counter

from risingwave_tpu.frontend import Session
from risingwave_tpu.frontend import sql as ast
from risingwave_tpu.frontend.binder import StreamPlanner
from risingwave_tpu.plan.graph import Exchange, Node


def _render(node, depth=0):
    if isinstance(node, Exchange):
        return [f"{'  ' * depth}exchange({node.upstream})"]
    extra = ""
    if node.kind == "sorted_join":
        extra = (f" lkeys={node.args['left_key_indices']}"
                 f" rkeys={node.args['right_key_indices']}")
    if node.kind == "project":
        extra = f" names={node.args.get('names')}"
    out = [f"{'  ' * depth}{node.kind}{extra}"]
    for i in node.inputs:
        out.extend(_render(i, depth + 1))
    return out


def _plan(session, sql_text):
    planner = StreamPlanner(session.catalog, config=session.config)
    return planner, planner.plan_select(ast.parse(sql_text))


async def _nexmark_session():
    s = Session()
    for t in ("auction", "person", "bid"):
        await s.execute(
            f"CREATE SOURCE {t} WITH (connector='nexmark', table='{t}', "
            f"chunk_size=256, rate_limit=512)")
    return s


async def test_q3_pushdown_and_pruning_plan_shape():
    s = await _nexmark_session()
    _, plan = _plan(s, (
        "SELECT P.name, P.city, P.state, A.id "
        "FROM auction AS A JOIN person AS P ON A.seller = P.id "
        "WHERE A.category = 10 AND "
        "(P.state = 'OR' OR P.state = 'ID' OR P.state = 'CA')"))
    join_frag = None
    for f in plan.graph.fragments.values():
        lines = _render(f.root)
        if any("sorted_join" in ln for ln in lines):
            join_frag = f
            break
    assert join_frag is not None
    join = join_frag.root
    while join.kind != "sorted_join":
        join = join.inputs[0]

    def upstream_chain(side):
        """(first project, kinds) walking the join input chain through
        exchanges into upstream fragments (pruning/pushdown are absorbed
        into single-consumer upstream fragments)."""
        kinds, proj = [], None
        n = join.inputs[side]
        while n is not None:
            if isinstance(n, Exchange):
                n = plan.graph.fragments[n.upstream].root
                continue
            kinds.append(n.kind)
            if n.kind == "project" and proj is None:
                proj = n
            n = n.inputs[0] if n.inputs else None
        return proj, kinds

    for side in (0, 1):
        proj, kinds = upstream_chain(side)
        assert proj is not None, kinds
        # WHERE conjunct pushed below the join into the same chain
        assert "filter" in kinds, kinds
    lproj, _ = upstream_chain(0)
    rproj, _ = upstream_chain(1)
    # pruned: auction side needs seller + category(filtered) + id + row_id;
    # the full 10-column auction schema must NOT survive
    assert len(lproj.args["names"]) <= 4, lproj.args["names"]
    assert set(rproj.args["names"]) <= {"id", "name", "city", "state",
                                        "_row_id"}, rproj.args["names"]
    # join fragment root has no residual filter (everything pushed)
    assert join_frag.root.kind != "filter"
    await s.drop_all()


async def test_outer_join_no_pushdown_but_pruned():
    """Outer joins must NOT push WHERE below the join (NULL-row semantics)
    but still prune input columns."""
    s = await _nexmark_session()
    _, plan = _plan(s, (
        "SELECT A.id, P.name FROM auction A "
        "LEFT OUTER JOIN person P ON A.seller = P.id "
        "WHERE A.category = 10"))
    join = None
    for f in plan.graph.fragments.values():
        n = f.root
        stack = [n]
        while stack:
            n = stack.pop()
            if isinstance(n, Node):
                if n.kind == "sorted_join":
                    join = n
                stack.extend(i for i in n.inputs if isinstance(i, Node))
    assert join is not None

    def side_kinds_and_proj(side):
        kinds, proj = [], None
        n = join.inputs[side]
        while n is not None:
            if isinstance(n, Exchange):
                n = plan.graph.fragments[n.upstream].root
                continue
            kinds.append(n.kind)
            if n.kind == "project" and proj is None:
                proj = n
            n = n.inputs[0] if n.inputs else None
        return kinds, proj

    kinds_l, proj_l = side_kinds_and_proj(0)
    kinds_r, proj_r = side_kinds_and_proj(1)
    # inputs pruned but NOT filtered (outer join forbids pushdown)
    assert proj_l is not None and "filter" not in kinds_l, kinds_l
    assert proj_r is not None and "filter" not in kinds_r, kinds_r
    assert len(proj_r.args["names"]) <= 3, proj_r.args["names"]
    await s.drop_all()


async def test_pruned_q3_matches_unpruned_results():
    """The optimizer must not change results: q3 through the full session
    equals the same query with pruning defeated via SELECT of all cols."""
    from risingwave_tpu.common.types import GLOBAL_DICT
    s = await _nexmark_session()
    await s.execute(
        "CREATE MATERIALIZED VIEW q3 AS "
        "SELECT P.name, A.id FROM auction AS A "
        "JOIN person AS P ON A.seller = P.id WHERE A.category = 10")
    await s.tick(3)
    got = Counter(s.query("SELECT name, id FROM q3"))
    # oracle from generator prefixes at committed offsets
    import numpy as np
    from risingwave_tpu.connectors import NexmarkGenerator
    from risingwave_tpu.state.storage_table import StorageTable
    from risingwave_tpu.stream.source import SourceExecutor
    offs = {}
    for roots in s.catalog.mvs["q3"].deployment.roots.values():
        for root in roots:
            node = root
            while node is not None:
                if isinstance(node, SourceExecutor) \
                        and node.state_table is not None:
                    st = StorageTable.for_state_table(node.state_table)
                    rows = list(st.batch_iter())
                    offs[node.connector.table] = (int(rows[0][1])
                                                  if rows else 0)
                node = getattr(node, "input", None)

    def prefix(table, n):
        gen = NexmarkGenerator(table, chunk_size=max(256, n))
        c = gen.next_chunk()
        return [np.asarray(col.data)[:n] for col in c.columns]

    a = prefix("auction", offs["auction"])
    p = prefix("person", offs["person"])
    persons = {int(pid): int(nm) for pid, nm in zip(p[0], p[1])}
    exp = Counter()
    for aid, seller, cat in zip(a[0], a[7], a[8]):
        if int(cat) == 10 and int(seller) in persons:
            exp[(GLOBAL_DICT.decode(persons[int(seller)]), int(aid))] += 1
    assert got == exp
    assert got, "q3 oracle vacuous"
    await s.drop_all()


def _render_graph(plan):
    """Stable text rendering of a whole plan (fragment order = fid)."""
    lines = []
    for fid in sorted(plan.graph.fragments):
        f = plan.graph.fragments[fid]
        lines.append(f"fragment {fid} dispatch={f.dispatch} "
                     f"parallelism={f.parallelism} "
                     f"dist={tuple(f.dist_key_indices or ())}")
        lines.extend("  " + ln for ln in _render(f.root, 1))
    return "\n".join(lines) + "\n"


_GOLDEN_QUERIES = {
    "q3": ("SELECT P.name, P.city, P.state, A.id "
           "FROM auction AS A JOIN person AS P ON A.seller = P.id "
           "WHERE A.category = 10 AND P.state = 'OR'"),
    "q7_shape": ("SELECT B.auction, B.price FROM bid B JOIN ("
                 "SELECT max(price) AS maxprice, window_end "
                 "FROM TUMBLE(bid, date_time, 10000000) "
                 "GROUP BY window_end) B1 ON B.price = B1.maxprice "
                 "AND B.date_time <= B1.window_end"),
    "left_join": ("SELECT A.id, P.name FROM auction A "
                  "LEFT OUTER JOIN person P ON A.seller = P.id"),
}


async def test_plan_snapshots():
    """Golden plan snapshots (reference: src/frontend/planner_test/).
    Regenerate intentionally with REGEN_PLAN_GOLDENS=1 after reviewing
    the diff — a surprise change here IS the signal."""
    import os
    import pathlib
    s = await _nexmark_session()
    gold_dir = pathlib.Path(__file__).parent / "goldens"
    regen = os.environ.get("REGEN_PLAN_GOLDENS") == "1"
    for name, sql_text in _GOLDEN_QUERIES.items():
        _, plan = _plan(s, sql_text)
        got = _render_graph(plan)
        path = gold_dir / f"plan_{name}.txt"
        if regen:
            path.write_text(got)
            continue
        assert path.exists(), (
            f"golden {path} missing — generate deliberately with "
            f"REGEN_PLAN_GOLDENS=1 (a silently regenerated golden would "
            f"bake regressions in)")
        assert got == path.read_text(), (
            f"plan snapshot {name} changed — review and regen with "
            f"REGEN_PLAN_GOLDENS=1:\n{got}")
    await s.drop_all()
