"""HBM memory manager (risingwave_tpu/memory/): exact accounting, LRU
eviction to host spill, read-through reload, and crash recovery with
evicted state.

The equivalence tests drive executors directly with scripted messages and
compare the MATERIALIZED result (changelog applied to a dict / net match
multiset) of a budget-evicted run against an unbounded run — eviction and
reload must be observationally invisible.
"""

import asyncio
from collections import Counter

import numpy as np
import pytest

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, StreamChunk,
)
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.expr.agg import agg_min, agg_sum, count_star
from risingwave_tpu.memory import (HostSpill, MemoryManager, format_bytes,
                                   pytree_bytes)
from risingwave_tpu.state import MemoryStateStore, StateTable
from risingwave_tpu.stream import Barrier, BarrierKind, HashAggExecutor
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.stream.sorted_join import SortedJoinExecutor
from risingwave_tpu.stream.message import Watermark

AGG_SCHEMA = schema(("k", DataType.INT64), ("v", DataType.INT64))


class ScriptSource(Executor):
    def __init__(self, sch, messages):
        self.schema = sch
        self.messages = messages
        self.identity = "ScriptSource"

    async def execute(self):
        for m in self.messages:
            yield m
            await asyncio.sleep(0)


def chunk(sch, rows, cap=64):
    ops = np.asarray([r[0] for r in rows], dtype=np.int8)
    cols = [np.asarray([r[1 + j] for r in rows], dtype=np.int64)
            for j in range(len(rows[0]) - 1)]
    return StreamChunk.from_numpy(sch, cols, ops=ops, capacity=cap)


def barrier(curr, prev, kind=BarrierKind.CHECKPOINT):
    return Barrier(EpochPair(curr, prev), kind)


# ---------------------------------------------------------- accounting
def test_pytree_bytes_exact():
    import jax.numpy as jnp
    tree = (jnp.zeros((4, 8), dtype=jnp.int64),
            [jnp.zeros(3, dtype=jnp.float32)],
            {"x": jnp.zeros((), dtype=bool)}, "aux", 7)
    assert pytree_bytes(tree) == 4 * 8 * 8 + 3 * 4 + 1
    assert format_bytes(2048) == "2.0KiB"


def test_agg_state_bytes_matches_pytree():
    agg = HashAggExecutor(ScriptSource(AGG_SCHEMA, []), [0],
                          [count_star(), agg_sum(1)], capacity=128)
    assert agg.state_bytes() == pytree_bytes(agg.state)
    mgr = MemoryManager()
    name = mgr.register("flow/agg", agg)
    assert mgr.total_bytes() == agg.state_bytes()
    rep = mgr.report()
    assert rep[0]["executor"] == name
    assert rep[0]["state_bytes"] == agg.state_bytes()
    mgr.unregister(name)
    assert mgr.total_bytes() == 0


def test_host_spill_semantics():
    sp = HostSpill()
    sp.add((1,), ("a",))
    sp.add((1,), ("b",))
    sp.set((2,), ("c",))
    assert sp.rows == 3 and len(sp) == 2
    got = sp.take_touched([(1,), (3,)])
    assert got == {(1,): [("a",), ("b",)]} and sp.rows == 1
    dead = sp.purge(lambda k, rows: k[0] == 2)
    assert dead == [((2,), [("c",)])] and not sp


def test_render_prometheus_has_types():
    from risingwave_tpu.utils.metrics import GLOBAL_METRICS
    txt = GLOBAL_METRICS.render_prometheus()
    assert "# TYPE hbm_state_bytes gauge" in txt
    assert "# TYPE hbm_evicted_bytes_total counter" in txt
    assert "# TYPE checkpoint_seal_seconds histogram" in txt
    # plain render stays TYPE-free (REPL dump)
    assert "# TYPE" not in GLOBAL_METRICS.render()


# --------------------------------------------------- agg evict + reload
def _agg_script(n_epochs=10, per=16, retract=True):
    """Changelog-consistent script (retractions name the exact inserted
    value — retractable MIN validates this): fresh keys per epoch, plus
    update pairs and deletes landing on long-cold (evicted) keys."""
    def val(k):
        return (k * 7) % 97
    msgs = [barrier(1, 0, BarrierKind.INITIAL)]
    for e in range(n_epochs):
        base = e * per
        rows = [(OP_INSERT, base + i, val(base + i)) for i in range(per)]
        if e >= 4:
            old = (e - 4) * per
            rows.append((OP_UPDATE_DELETE, old + 1, val(old + 1)))
            rows.append((OP_UPDATE_INSERT, old + 1, val(old + 1) + 1))
            if retract:
                rows.append((OP_DELETE, old + 2, val(old + 2)))
        msgs.append(chunk(AGG_SCHEMA, rows))
        msgs.append(barrier(e + 2, e + 1))
    return msgs


async def _run_agg(budget, agg_calls, msgs, minput_k=8):
    store = MemoryStateStore()
    width = sum((2 * minput_k + 1) if (c.kind.name in ("MIN", "MAX")
                                       and not c.append_only) else 1
                for c in agg_calls)
    fields = [("k", DataType.INT64)]
    fields += [(f"s{j}", DataType.INT64) for j in range(width)]
    fields.append(("_row_count", DataType.INT64))
    st = StateTable(store, 7, schema(*fields), (0,))
    agg = HashAggExecutor(ScriptSource(AGG_SCHEMA, msgs), [0], agg_calls,
                          capacity=1024, state_table=st,
                          minput_k=minput_k)
    agg._mem_min_capacity = 32
    mgr = MemoryManager()
    mgr.register("agg", agg)
    mgr.configure(budget_bytes=budget)
    mat = {}
    async for m in agg.execute():
        if isinstance(m, StreamChunk):
            for op, row in m.to_rows():
                if op in (OP_INSERT, OP_UPDATE_INSERT):
                    mat[row[0]] = row
                else:
                    mat.pop(row[0], None)
        elif isinstance(m, Barrier):
            mgr.on_barrier(m.epoch.curr)
    return agg, mat, st


async def test_hash_agg_evict_reload_equivalence():
    """Evicted-then-touched run (update pairs + deletes landing on spilled
    keys) must materialize exactly like the unbounded run."""
    msgs = _agg_script()
    a0, mat0, _ = await _run_agg(0, [count_star(), agg_sum(1)], msgs)
    budget = a0.state_bytes() // 3
    a1, mat1, _ = await _run_agg(budget, [count_star(), agg_sum(1)], msgs)
    assert a1.mem_evicted_bytes > 0, "eviction never happened"
    assert a1.mem_reload_count > 0, "read-through reload never happened"
    assert a1.state_bytes() < a0.state_bytes()
    assert mat0 == mat1


async def test_hash_agg_retractable_minmax_evict_equivalence():
    """Retractable MIN state (materialized-input top-K buffers) spills its
    full extrema layout and reloads exactly — update pairs retract values
    inside previously evicted groups."""
    msgs = _agg_script()
    a0, mat0, _ = await _run_agg(0, [agg_min(1)], msgs)
    a1, mat1, _ = await _run_agg(a0.state_bytes() // 3, [agg_min(1)], msgs)
    assert a1.mem_evicted_bytes > 0
    assert a1.mem_reload_count > 0
    assert mat0 == mat1


async def test_hash_agg_watermark_cleans_evicted_ranges():
    """Spilled keys below the cleaning watermark leave the spill dict AND
    the durable table, in step with the device-side zeroing."""
    msgs = [barrier(1, 0, BarrierKind.INITIAL)]
    per = 16
    for e in range(8):
        rows = [(OP_INSERT, e * per + i, 1) for i in range(per)]
        msgs.append(chunk(AGG_SCHEMA, rows))
        if e >= 5:
            # watermark passes the early (already evicted) keys
            msgs.append(Watermark(0, DataType.INT64, (e - 4) * per))
        msgs.append(barrier(e + 2, e + 1))
    store = MemoryStateStore()
    st = StateTable(store, 9, schema(("k", DataType.INT64),
                                     ("s0", DataType.INT64),
                                     ("_row_count", DataType.INT64)), (0,))
    agg = HashAggExecutor(ScriptSource(AGG_SCHEMA, msgs), [0],
                          [count_star()], capacity=1024, state_table=st,
                          cleaning_watermark_col=0)
    agg._mem_min_capacity = 32
    mgr = MemoryManager()
    mgr.register("agg", agg)
    mgr.configure(budget_bytes=8192)
    async for m in agg.execute():
        if isinstance(m, Barrier):
            mgr.on_barrier(m.epoch.curr)
    assert agg.mem_evicted_bytes > 0
    # no spilled key below the final watermark (3 * per) survives
    final_wm = 3 * per
    assert all(k[0] >= final_wm for k in agg._spill.keys())
    store.sync(10)
    persisted = [r[0] for _, r in st.iter_all()]
    assert persisted and all(k >= final_wm for k in persisted), \
        f"durable rows below the watermark survived: {sorted(persisted)[:5]}"


# --------------------------------------------------- join evict + reload
LS = schema(("k", DataType.INT64), ("a", DataType.INT64))
RS = schema(("k", DataType.INT64), ("b", DataType.INT64))


def _join_scripts(n_epochs=10, per=12):
    lm = [barrier(1, 0, BarrierKind.INITIAL)]
    rm = [barrier(1, 0, BarrierKind.INITIAL)]
    for e in range(n_epochs):
        base = e * per
        lrows = [(OP_INSERT, base + i, 1000 * e + i) for i in range(per)]
        rrows = [(OP_INSERT, base + i, 2000 * e + i) for i in range(per)]
        if e >= 4:
            old = (e - 4) * per
            # probe, delete and update-pair against long-cold keys
            lrows.append((OP_INSERT, old + 3, 7000 + e))
            rrows.append((OP_DELETE, old + 4, 2000 * (e - 4) + 4))
            rrows.append((OP_UPDATE_DELETE, old + 5, 2000 * (e - 4) + 5))
            rrows.append((OP_UPDATE_INSERT, old + 5, 9000 + e))
        lm.append(chunk(LS, lrows))
        rm.append(chunk(RS, rrows))
        b = barrier(e + 2, e + 1)
        lm.append(b)
        rm.append(b)
    return lm, rm


async def _run_join(spill):
    store = MemoryStateStore()
    stl = StateTable(store, 11, LS, (0, 1))
    str_ = StateTable(store, 12, RS, (0, 1))
    lm, rm = _join_scripts()
    join = SortedJoinExecutor(
        ScriptSource(LS, lm), ScriptSource(RS, rm),
        left_key_indices=[0], right_key_indices=[0],
        left_pk_indices=[0, 1], right_pk_indices=[0, 1],
        capacity=1 << 7, match_factor=8, state_tables=(stl, str_))
    mgr = MemoryManager()
    mgr.register("join", join)
    if spill:
        mgr.configure(budget_bytes=1)
    net = Counter()
    async for m in join.execute():
        if isinstance(m, StreamChunk):
            for op, row in m.to_rows():
                if op in (OP_INSERT, OP_UPDATE_INSERT):
                    net[row] += 1
                else:
                    net[row] -= 1
                    if net[row] == 0:
                        del net[row]
        elif isinstance(m, Barrier):
            mgr.on_barrier(m.epoch.curr)
    return join, net


async def test_retracting_join_spill_reload_equivalence():
    """Retracting sides with no cleaning column: the spill takes a key-hash
    prefix, and inserts, deletes and update pairs against long-spilled
    keys reload them first. The net result equals the unspilled run's."""
    j0, net0 = await _run_join(False)
    j1, net1 = await _run_join(True)
    assert j1.mem_reload_count > 0, "read-through reload never happened"
    assert j0.mem_reload_count == 0 and j0.mem_spilled_rows == 0
    assert net0 == net1, (
        f"net join result diverged: "
        f"{list((net0 - net1).items())[:3]} / "
        f"{list((net1 - net0).items())[:3]}")


# --------------------------------------- crash recovery w/ evicted state
async def test_agg_evict_persist_crash_recover():
    """Executor-level evict -> checkpoint -> crash -> recover: the durable
    table still holds every spilled row, so a fresh executor rebuilds the
    FULL state and materializes identically."""
    msgs = _agg_script(n_epochs=8)
    store = MemoryStateStore()
    st = StateTable(store, 7, schema(("k", DataType.INT64),
                                     ("s0", DataType.INT64),
                                     ("s1", DataType.INT64),
                                     ("_row_count", DataType.INT64)), (0,))
    agg = HashAggExecutor(ScriptSource(AGG_SCHEMA, msgs), [0],
                          [count_star(), agg_sum(1)], capacity=1024,
                          state_table=st)
    agg._mem_min_capacity = 32
    mgr = MemoryManager()
    mgr.register("agg", agg)
    mgr.configure(budget_bytes=agg.state_bytes() // 3)
    last_epoch = 0
    async for m in agg.execute():
        if isinstance(m, Barrier):
            mgr.on_barrier(m.epoch.curr)
            last_epoch = m.epoch.curr
    assert agg.mem_evicted_bytes > 0 and agg.mem_spilled_rows > 0
    store.sync(last_epoch)   # checkpoint commits mid-eviction state

    # "crash": a fresh executor over the same table recovers EVERYTHING
    st2 = StateTable(store, 7, st.schema, (0,))
    st2.init_epoch(last_epoch + 1)
    agg2 = HashAggExecutor(ScriptSource(AGG_SCHEMA, []), [0],
                           [count_star(), agg_sum(1)], capacity=1024,
                           state_table=st2)
    agg2.recover(last_epoch + 1)
    assert not agg2._spill, "recovery must drop the stale spill"
    rows_live = {r[0]: r for _, r in st.iter_all()}
    # the recovered device state re-persists nothing new, but its live
    # groups must cover every durable row incl. previously spilled ones
    occ, live = agg2._live_zombie(agg2.state)
    assert int(live) == len(rows_live)
    # and the spilled rows are point-readable through the store view
    pks = [(k,) for k in list(rows_live)[:8]]
    got = st2.get_rows(pks)
    assert all(g is not None for g in got)


async def test_session_budget_evict_crash_recover_converge(tmp_path):
    """End-to-end: SET hbm_budget_bytes -> MV state evicts under budget ->
    checkpoint -> crash -> auto-recovery -> results converge vs oracle."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    from oracle import committed_offsets, nexmark_prefix
    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / "d")))
    s = Session(store=store)
    await s.execute("CREATE SOURCE bid WITH (connector='nexmark', "
                    "table='bid', chunk_size=256, rate_limit=512)")
    await s.execute("SET streaming_agg_capacity = 4096")
    await s.execute("SET hbm_budget_bytes = 150000")
    await s.execute("CREATE MATERIALIZED VIEW ma AS SELECT auction, "
                    "count(*) AS n, sum(price) AS sp FROM bid "
                    "GROUP BY auction")
    await s.tick(4, max_recoveries=8)
    rep = {r["executor"]: r for r in s.coord.memory.report()}
    agg_rep = next(v for k, v in rep.items() if "HashAgg" in k)
    assert agg_rep["evicted_bytes"] > 0, f"no eviction: {rep}"

    victim = s.catalog.mvs["ma"].deployment.tasks[-1]
    victim.cancel()
    try:
        await victim
    except (asyncio.CancelledError, Exception):
        pass
    await s.tick(2, max_recoveries=8)
    assert s.recoveries >= 1
    got = Counter(s.query("SELECT auction, n, sp FROM ma"))
    off = committed_offsets(s, "ma").get("bid", 0)
    cols = nexmark_prefix("bid", off)
    agg: dict = {}
    for a, p in zip(cols[0], cols[2]):
        n, sp = agg.get(int(a), (0, 0))
        agg[int(a)] = (n + 1, sp + int(p))
    exp = Counter((a, n, sp) for a, (n, sp) in agg.items())
    assert got == exp, (
        f"diverged after recovery: sample "
        f"{list((got - exp).items())[:3]} / "
        f"{list((exp - got).items())[:3]}")
    assert off > 0
    # budget knob + policy surface
    rows = s.show("memory")
    assert rows and any("HashAgg" in r[0] for r in rows)
    out = await s.execute("EXPLAIN MATERIALIZED VIEW ma")
    txt = "\n".join(ln for (ln,) in out)
    assert "state_bytes=" in txt and "evicted_bytes=" in txt
    await s.drop_all()


# ------------------------------------------------- sorted join spill
async def test_sorted_join_spill_reload_equivalence():
    W = 100
    ls = schema(("k", DataType.INT64), ("w", DataType.INT64))
    rs = schema(("k", DataType.INT64), ("w", DataType.INT64))

    def scripts():
        lm = [barrier(1, 0, BarrierKind.INITIAL)]
        rm = [barrier(1, 0, BarrierKind.INITIAL)]
        for e in range(14):
            w = e * W
            lrows = [(OP_INSERT, i, w) for i in range(12)]
            rrows = [(OP_INSERT, i, w) for i in range(0, 12, 2)]
            if e >= 6:
                rrows.append((OP_INSERT, 3, (e - 6) * W))  # late probe
            lm.append(chunk(ls, lrows))
            rm.append(chunk(rs, rrows))
            wmv = max(0, (e - 8) * W)
            lm.append(Watermark(1, DataType.INT64, wmv))
            rm.append(Watermark(1, DataType.INT64, wmv))
            b = barrier(e + 2, e + 1)
            lm.append(b)
            rm.append(b)
        return lm, rm

    async def run(enabled):
        lm, rm = scripts()
        join = SortedJoinExecutor(
            ScriptSource(ls, lm), ScriptSource(rs, rm),
            left_key_indices=[0, 1], right_key_indices=[0, 1],
            left_pk_indices=[0, 1], right_pk_indices=[0, 1],
            capacity=1 << 7, match_factor=8, append_only=(True, True),
            clean_specs=(("pair", 1, 1), ("pair", 1, 1)))
        mgr = MemoryManager()
        mgr.register("join", join)
        if enabled:
            mgr.configure(budget_bytes=1)
        net = Counter()
        async for m in join.execute():
            if isinstance(m, StreamChunk):
                for op, row in m.to_rows():
                    if op in (OP_INSERT, OP_UPDATE_INSERT):
                        net[row] += 1
                    else:
                        net[row] -= 1
                        if net[row] == 0:
                            del net[row]
            elif isinstance(m, Barrier):
                mgr.on_barrier(m.epoch.curr)
        return join, net

    j0, net0 = await run(False)
    j1, net1 = await run(True)
    assert j1.mem_reload_count > 0 or j1.mem_spilled_rows > 0, "no spill"
    assert net0 == net1


# ------------------------------------------------------ config plumbing
async def test_memory_config_plumbs_to_manager():
    from risingwave_tpu.frontend import Session
    s = Session()
    assert not s.coord.memory.enabled
    await s.execute("SET hbm_budget_bytes = 12345")
    assert s.coord.memory.budget_bytes == 12345
    assert s.coord.memory.enabled
    await s.execute("SET memory_eviction_policy = 'none'")
    assert not s.coord.memory.enabled
    with pytest.raises(Exception):
        await s.execute("SET memory_eviction_policy = 'bogus'")


def test_system_params_memory_mutable():
    from risingwave_tpu.common.config import RwConfig, SystemParams
    p = SystemParams(RwConfig())
    assert p.get("hbm_budget_bytes") == 0
    assert p.get("memory_eviction_policy") == "lru"
    p.set("hbm_budget_bytes", 1 << 20)
    assert p.get("hbm_budget_bytes") == 1 << 20
