"""Fused mesh-fragment execution on the 8-device virtual CPU mesh
(ISSUE 8 / ROADMAP item 2): the exchange -> sharded-executor chain runs
as ONE shard_map program per barrier interval — rows vnode-route to
their owner shard via an in-program lax.all_to_all
(parallel/exchange.mesh_ingest_chunk), no host channel hop.

Covered here:
  * bit-identical results vs the single-device executor for a q7-shaped
    agg and a q5-shaped windowed join, incl. crash -> recover from a
    committed epoch through the fused layout
  * device dispatches per interval do not scale with shard count (one
    fused program per interval, not N per-shard programs)
  * shuffle-overflow fail-stop (mesh_shuffle_dropped_rows_total) when
    mesh_shuffle_slack undersizes the per-pair send buckets
  * mesh fragments register with the barrier coordinator as ONE actor
    covering all shards
  * persistent-compile-cache namespacing by backend + machine
    fingerprint (the cpu_aot_loader hazard)
"""

import asyncio
from collections import Counter

import numpy as np
import pytest

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import StreamChunk
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.expr.agg import AggCall, AggKind, agg_sum, count_star
from risingwave_tpu.parallel import make_mesh
from risingwave_tpu.stream import Barrier, BarrierKind, HashAggExecutor
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.stream.sharded_agg import ShardedHashAggExecutor
from risingwave_tpu.stream.sharded_join import ShardedSortedJoinExecutor
from risingwave_tpu.utils.metrics import GLOBAL_METRICS, MESH_SHUFFLE_DROPPED

W = 10_000_000
BID = schema(("auction", DataType.INT64), ("price", DataType.INT64),
             ("wend", DataType.INT64))


class ScriptSource(Executor):
    def __init__(self, sch, messages):
        self.schema = sch
        self.messages = messages
        self.identity = "ScriptSource"
        self.pk_indices = ()

    async def execute(self):
        for m in self.messages:
            yield m
            await asyncio.sleep(0)


def barrier(curr, prev, kind=BarrierKind.CHECKPOINT):
    return Barrier(EpochPair(curr, prev), kind)


def bid_chunk(rng, n=64, cap=64, epoch=0, price=None):
    auction = rng.integers(0, 40, n).astype(np.int64)
    if price is None:
        price = rng.integers(1, 10_000, n).astype(np.int64)
    ts = (epoch * W // 2 + rng.integers(0, W, n)).astype(np.int64)
    wend = ts - ts % W + W
    return StreamChunk.from_numpy(BID, [auction, price, wend],
                                  capacity=cap)


def q7_messages(seed=5, intervals=4, chunks_per=3):
    rng = np.random.default_rng(seed)
    msgs = [barrier(1, 0, BarrierKind.INITIAL)]
    ep = 2
    for i in range(intervals):
        for _ in range(chunks_per):
            msgs.append(bid_chunk(rng, epoch=i))
        msgs.append(barrier(ep, ep - 1))
        ep += 1
    return msgs


async def drive(ex):
    out = []
    async for m in ex.execute():
        out.append(m)
    return out


def changelog(out):
    """Accumulated MV content from a changelog stream (keyed upsert)."""
    from risingwave_tpu.common.chunk import OP_DELETE, OP_UPDATE_DELETE
    mv = Counter()
    for m in out:
        if isinstance(m, StreamChunk):
            for op, row in m.to_rows():
                if op in (OP_DELETE, OP_UPDATE_DELETE):
                    mv[row] -= 1
                    if mv[row] == 0:
                        del mv[row]
                else:
                    mv[row] += 1
    return mv


def _fused_dispatches():
    snap = GLOBAL_METRICS.snapshot()
    return sum(e["value"] for e in snap.get("device_dispatch_count", [])
               if "fused" in e["labels"].get("program", ""))


# ------------------------------------------------------------------ agg

async def test_fused_agg_bit_identical_and_one_dispatch_per_interval():
    """q7-shaped agg (MAX(price), count per tumble window) through the
    fused mesh plane: bit-identical to the single-device executor, and
    the whole multi-chunk interval is ONE fused device dispatch."""
    msgs = q7_messages()
    mesh = make_mesh(8)
    sh = ShardedHashAggExecutor(
        ScriptSource(BID, msgs), [2],
        [AggCall(AggKind.MAX, 1, BID[1].data_type, append_only=True),
         count_star()],
        mesh=mesh, capacity=64)
    d0 = _fused_dispatches()
    got = changelog(await drive(sh))
    d1 = _fused_dispatches()
    plain = HashAggExecutor(
        ScriptSource(BID, msgs), [2],
        [AggCall(AggKind.MAX, 1, BID[1].data_type, append_only=True),
         count_star()],
        capacity=512)
    want = changelog(await drive(plain))
    assert got == want and len(got) > 0
    # 4 intervals x 3 chunks: one fused scan dispatch per interval —
    # chunk count amortized by the in-program lax.scan, shard count by
    # shard_map (N per-shard programs would be 8x this)
    assert sh.mesh_shuffle_applies == 4
    assert d1 - d0 == 4, f"expected 4 fused dispatches, saw {d1 - d0}"


async def test_fused_agg_crash_recover_bit_identical():
    """Fused layout through persist -> crash -> recover from the
    committed epoch -> more input: accumulated MV equals an unsharded
    full run with no crash (exactly the durable contract)."""
    from risingwave_tpu.state import MemoryStateStore, StateTable

    rng = np.random.default_rng(11)

    def chunks(n):
        return [bid_chunk(rng, epoch=i) for i in range(n)]

    phase1, phase2 = chunks(2), chunks(2)
    store = MemoryStateStore()

    def make_table():
        return StateTable(
            store, table_id=9,
            schema=schema(("wend", DataType.INT64),
                          ("mx", DataType.INT64),
                          ("count", DataType.INT64),
                          ("sum", DataType.INT64),
                          ("_row_count", DataType.INT64)),
            pk_indices=[0])

    calls = [AggCall(AggKind.MAX, 1, BID[1].data_type, append_only=True),
             count_star(), agg_sum(1)]
    mesh = make_mesh(8)
    msgs1 = [barrier(1, 0, BarrierKind.INITIAL), phase1[0], barrier(2, 1),
             phase1[1], barrier(3, 2)]
    sh1 = ShardedHashAggExecutor(
        ScriptSource(BID, msgs1), [2], calls, mesh=mesh, capacity=64,
        state_table=make_table())
    out1 = await drive(sh1)
    assert sh1.mesh_shuffle_applies > 0
    store.sync(2)
    del sh1                    # crash: device state dies

    msgs2 = [barrier(3, 2, BarrierKind.INITIAL), phase2[0], barrier(4, 3),
             phase2[1], barrier(5, 4)]
    sh2 = ShardedHashAggExecutor(
        ScriptSource(BID, msgs2), [2], calls, mesh=mesh, capacity=64,
        state_table=make_table())
    out2 = await drive(sh2)
    got = changelog(out1 + out2)

    full = [barrier(1, 0, BarrierKind.INITIAL), phase1[0], barrier(2, 1),
            phase1[1], barrier(3, 2), phase2[0], barrier(4, 3),
            phase2[1], barrier(5, 4)]
    plain = HashAggExecutor(ScriptSource(BID, full), [2], calls,
                            capacity=512)
    want = changelog(await drive(plain))
    assert got == want and len(got) > 0


def _padded_case(what, msgs, mesh):
    """(sharded executor, its unsharded twin) over the same messages."""
    from risingwave_tpu.stream.retract_top_n import RetractableTopNExecutor
    from risingwave_tpu.stream.sharded_top_n import ShardedTopNExecutor
    from risingwave_tpu.stream.sorted_join import SortedJoinExecutor

    def src():
        return ScriptSource(BID, list(msgs))
    if what == "agg":
        calls = [count_star(), agg_sum(1)]
        return (ShardedHashAggExecutor(src(), [0], calls, mesh=mesh,
                                       capacity=32),
                HashAggExecutor(src(), [0], calls, capacity=256))
    if what == "join":
        # both sides take the same chunks: auction = auction, pk = price
        kw = dict(left_key_indices=[0], right_key_indices=[0],
                  left_pk_indices=[1], right_pk_indices=[1],
                  match_factor=16)
        return (ShardedSortedJoinExecutor(src(), src(), mesh,
                                          capacity=64, **kw),
                SortedJoinExecutor(src(), src(), capacity=512, **kw))
    kw = dict(group_key_indices=(0,), order_col=1, limit=3,
              pk_indices=(1,))
    return (ShardedTopNExecutor(src(), mesh=mesh, capacity=64, **kw),
            RetractableTopNExecutor(src(), capacity=512, **kw))


@pytest.mark.parametrize("what,cap", [("agg", 44), ("agg", 4),
                                      ("join", 44), ("top_n", 44)])
async def test_non_divisible_capacity_is_padded_and_stays_fused(what, cap):
    """A capacity the shard count does not divide (44 % 8, and 4 rows
    under the 8 shards) is padded with invisible tail rows where the
    chunk enters the mesh executor and runs the FUSED program like any
    other: same results as the unsharded executor, no shuffle drop."""
    rng = np.random.default_rng(7)
    # prices unique: the join's and the top-N's pk
    chunks = [bid_chunk(rng, n=cap, cap=cap,
                        price=np.arange(cap, dtype=np.int64) + 1000 * i)
              for i in range(2)]
    msgs = [barrier(1, 0, BarrierKind.INITIAL), *chunks, barrier(2, 1)]
    sh, plain = _padded_case(what, msgs, make_mesh(8))
    padded = sh._mesh_chunk(chunks[0])
    assert padded.capacity == (48 if cap == 44 else 8)
    assert int(np.asarray(padded.vis).sum()) == cap
    whole = bid_chunk(rng)                       # 64 rows: 8 a shard
    assert sh._mesh_chunk(whole) is whole, \
        "a capacity the shard count divides must come back untouched"
    before = MESH_SHUFFLE_DROPPED.value
    got = changelog(await drive(sh))
    assert sh.mesh_shuffle_applies > 0, "padded chunk must run fused"
    assert MESH_SHUFFLE_DROPPED.value == before
    if what != "join":
        # the replay point holds the chunks as they came: the frontier
        # channels skip a preloaded chunk by identity
        logged = [c for _, cs in sh.ingest_log.entries() for c in cs]
        assert len(logged) == 2 and logged[0] is chunks[0]
    want = changelog(await drive(plain))
    assert got == want and len(got) > 0


async def test_shuffle_overflow_fail_stops_epoch():
    """An undersized mesh_shuffle_slack drops rows in the all_to_all —
    the barrier watchdog must FAIL-STOP the epoch (raise before the
    checkpoint) and bump mesh_shuffle_dropped_rows_total, never commit
    silently short."""
    # every row shares ONE group key -> one vnode -> every row routes to
    # a single shard: per-(src,dst) demand is the full 32-row slice,
    # slack=1 sizes the bucket at ceil(32/8)*1 = 64-floored... use a
    # large chunk so the floor (64) is genuinely exceeded
    n = 8 * 512
    cols = [np.zeros(n, dtype=np.int64),
            np.arange(n, dtype=np.int64),
            np.full(n, W, dtype=np.int64)]
    ch = StreamChunk.from_numpy(BID, cols, capacity=n)
    msgs = [barrier(1, 0, BarrierKind.INITIAL), ch, barrier(2, 1)]
    mesh = make_mesh(8)
    sh = ShardedHashAggExecutor(
        ScriptSource(BID, msgs), [0], [count_star()], mesh=mesh,
        capacity=1024, mesh_shuffle_slack=1)
    before = MESH_SHUFFLE_DROPPED.value
    with pytest.raises(RuntimeError, match="mesh shuffle overflow"):
        await drive(sh)
    assert MESH_SHUFFLE_DROPPED.value > before


async def test_slack_requires_watchdog():
    """slack > 0 with the watchdog fetch disabled would let a checkpoint
    commit unchecked drops — refused loudly at construction."""
    mesh = make_mesh(8)
    with pytest.raises(ValueError, match="mesh_shuffle_slack"):
        ShardedHashAggExecutor(
            ScriptSource(BID, []), [0], [count_star()], mesh=mesh,
            capacity=32, watchdog_interval=None, mesh_shuffle_slack=2)


async def test_fused_agg_with_slack_zero_drops_balanced_keys():
    """A balanced key set under slack=4 shrinks the receive buffers
    (near-linear per-shard compute) with zero drops and identical
    results (host-recomputed expectation — count/sum per auction)."""
    msgs = q7_messages(seed=9, intervals=2, chunks_per=2)
    mesh = make_mesh(8)
    sh = ShardedHashAggExecutor(
        ScriptSource(BID, msgs), [0], [count_star(), agg_sum(1)],
        mesh=mesh, capacity=64, mesh_shuffle_slack=4)
    before = MESH_SHUFFLE_DROPPED.value
    got = changelog(await drive(sh))
    assert MESH_SHUFFLE_DROPPED.value == before
    agg: dict = {}
    for m in msgs:
        if isinstance(m, StreamChunk):
            for _, row in m.to_rows():
                n, sp = agg.get(row[0], (0, 0))
                agg[row[0]] = (n + 1, sp + row[1])
    want = Counter({(a, n, sp): 1 for a, (n, sp) in agg.items()})
    assert got == want and len(got) > 0


# ----------------------------------------------------------------- join

JOIN_SQL = (f"SELECT P.id, P.window_start "
            f"FROM TUMBLE(person, date_time, {W}) P "
            f"JOIN TUMBLE(auction, date_time, {W}) A "
            f"ON P.id = A.seller AND P.window_start = A.window_start")


async def _mk_join_sources(s):
    await s.execute(
        "CREATE SOURCE person WITH (connector='nexmark', table='person', "
        "primary_key='id', chunk_size=128, rate_limit=256, "
        "emit_watermarks=1)")
    await s.execute(
        "CREATE SOURCE auction WITH (connector='nexmark', "
        "table='auction', primary_key='id', chunk_size=384, "
        "rate_limit=768, emit_watermarks=1)")


def _join_oracle(s, mv):
    """Host recount of the windowed join at the MV's committed offsets."""
    from oracle import committed_offsets, nexmark_prefix
    offs = committed_offsets(s, mv)
    p = nexmark_prefix("person", offs["person"])
    a = nexmark_prefix("auction", offs["auction"])
    persons: dict = {}
    for pid, ts in zip(p[0], p[6]):
        w = int(ts) - int(ts) % W
        persons.setdefault(w, set()).add(int(pid))
    exp = Counter()
    for seller, ts in zip(a[7], a[5]):
        w = int(ts) - int(ts) % W
        if int(seller) in persons.get(w, ()):
            exp[(int(seller), w)] += 1
    return exp


async def test_fused_join_planned_bit_identical_and_recovers(tmp_path):
    """q5/q8-shaped windowed equi-join through the PLANNED fused mesh
    fragment: the sharded join engages the fused shuffle, one mesh
    fragment registers per sharded chain (ONE actor x 8 shards), the
    results match the host recount at the exact committed offsets
    (single-device semantics), and a crash recovers from the committed
    epoch with the fused layout intact."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / "d")))
    s = Session(store=store)
    await _mk_join_sources(s)
    await s.execute("SET streaming_parallelism_devices = 8")
    await s.execute("SET streaming_join_capacity = 16384")
    await s.execute(f"CREATE MATERIALIZED VIEW mj AS {JOIN_SQL}")
    joins = []
    for roots in s.catalog.mvs["mj"].deployment.roots.values():
        for root in roots:
            node = root
            while node is not None:
                if isinstance(node, ShardedSortedJoinExecutor):
                    joins.append(node)
                node = getattr(node, "input", None)
    assert len(joins) == 1
    # the fused chain registered as ONE actor covering 8 shards
    assert any(n == 8 for n, _ in s.coord.mesh_fragments.values())
    await s.tick(2)
    assert joins[0].mesh_shuffle_applies > 0, "fused join never engaged"

    # crash one actor -> auto-recovery from the committed epoch
    victim = s.catalog.mvs["mj"].deployment.tasks[-1]
    victim.cancel()
    try:
        await victim
    except (asyncio.CancelledError, Exception):
        pass
    await s.tick(2, max_recoveries=8)
    assert s.recoveries >= 1
    joins2 = []
    for roots in s.catalog.mvs["mj"].deployment.roots.values():
        for root in roots:
            node = root
            while node is not None:
                if isinstance(node, ShardedSortedJoinExecutor):
                    joins2.append(node)
                node = getattr(node, "input", None)
    assert joins2, "recovery replanned without the fused mesh"
    got = Counter(s.query("SELECT id, window_start FROM mj"))
    assert got == _join_oracle(s, "mj")
    assert sum(got.values()) > 0
    # mesh fragment registry survives recovery; dropping the MV clears it
    assert s.coord.mesh_fragments
    await s.drop_all()
    assert not s.coord.mesh_fragments


# ------------------------------------------------- compile-cache placement

def test_compile_cache_dir_is_shared_across_backends(tmp_path,
                                                     monkeypatch):
    """The cache directory is placed from outside and used AS IS: no
    per-backend / per-machine leaf is derived under it (the directory is
    part of jax's cache key — a leaf that moves with the host never
    hits), and re-application is idempotent."""
    import os

    import jax
    from risingwave_tpu.utils import compile_cache as cc
    orig = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        d1 = cc.enable_persistent_cache()
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        d2 = cc.enable_persistent_cache()
        assert d1 == d2 == str(tmp_path)
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
        assert os.listdir(tmp_path) == []
    finally:
        jax.config.update("jax_compilation_cache_dir", orig)


# ------------------------------------------------- mesh-resident chains

CHAIN_SQL = ("SELECT auction, window_end, max(price) AS maxprice, "
             "count(*) AS n "
             f"FROM TUMBLE(bid, date_time, {W}) "
             "GROUP BY auction, window_end")


async def _chain_session(store=None, pre=()):
    from risingwave_tpu.frontend import Session
    s = Session(store=store)
    if store is None:
        await s.execute("SET streaming_durability = 0")
    await s.execute("SET streaming_parallelism_devices = 8")
    for stmt in pre:
        await s.execute(stmt)
    await s.execute(
        "CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
        "chunk_size=256, rate_limit=1024)")
    await s.execute(f"CREATE MATERIALIZED VIEW m AS {CHAIN_SQL}")
    return s


def _chain_agg(s):
    aggs = []
    for roots in s.catalog.mvs["m"].deployment.roots.values():
        for root in roots:
            node = root
            while node is not None:
                if isinstance(node, ShardedHashAggExecutor):
                    aggs.append(node)
                node = getattr(node, "input", None)
    assert len(aggs) == 1
    return aggs[0]


def _chain_oracle(n):
    """Host recount of the first n bid rows for CHAIN_SQL."""
    from oracle import nexmark_prefix
    cols = nexmark_prefix("bid", n)
    auction, price, ts = cols[0], cols[2], cols[5]
    we = ts - ts % W + W
    agg: dict = {}
    for a, w, p in zip(auction, we, price):
        k = (int(a), int(w))
        m, cnt = agg.get(k, (0, 0))
        agg[k] = (max(m, int(p)), cnt + 1)
    return sorted((a, w, m, cnt) for (a, w), (m, cnt) in agg.items())


async def _quiesce(s):
    from risingwave_tpu.stream.message import PauseMutation
    b = await s.coord.inject_barrier(mutation=PauseMutation())
    await s.coord.wait_collected(b)


def _chain_rows(s):
    return sorted(s.query("SELECT auction, window_end, maxprice, n FROM m"))


async def test_fused_mesh_chain_one_dispatch_per_interval():
    """The q7-shaped source -> project -> sharded-agg chain fuses —
    producer stages hollow into preludes of the consumer's shard_map
    program, exactly one fused dispatch per interval, and the materialized rows
    are bit-identical to the single-device recount at the quiesced
    offset."""
    from risingwave_tpu.stream.source import SourceExecutor
    s = await _chain_session()
    chains = dict(s.coord.mesh_chains)
    assert len(chains) == 1
    (chain, info), = chains.items()
    agg = _chain_agg(s)
    assert agg.mesh_chain == chain and len(agg._mesh_preludes) == 2, \
        "both producer project stages must install as preludes"
    a0 = agg.mesh_shuffle_applies
    await s.tick(4)
    assert agg.mesh_shuffle_applies - a0 == 4, \
        "one fused dispatch per barrier interval"
    await _quiesce(s)
    srcs = [node for roots in s.catalog.mvs["m"].deployment.roots.values()
            for root in roots
            for node in _iter_chain(root)
            if isinstance(node, SourceExecutor)]
    offset = max(g.connector.offset for g in srcs)
    assert _chain_rows(s) == _chain_oracle(offset) and offset > 0
    await s.drop_all()
    assert not s.coord.mesh_chains, "drop must unregister the chain"


def _iter_chain(root):
    node = root
    while node is not None:
        yield node
        node = getattr(node, "input", None)


async def test_mesh_chain_crash_recovers_fused_with_preload(tmp_path):
    """Crash the fused consumer actor mid-stream: mesh-scope recovery
    rebuilds it, the chain re-fuses (preludes reinstalled, hollow
    producers intact), the captured MeshIngestLog suffix preloads into
    the rebuilt fused program (channel-free replay), and the MV converges bit-identical to the host
    recount at the committed offset."""
    from oracle import committed_offsets
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    store = HummockStateStore(LocalFsObjectStore(str(tmp_path / "d")))
    s = await _chain_session(store=store)
    (chain, info), = dict(s.coord.mesh_chains).items()
    await s.tick(3)
    dep = s.catalog.mvs["m"].deployment
    by_id = {a.actor_id: i for i, a in enumerate(dep.actors)}
    victim = dep.tasks[by_id[info["consumer_actor"]]]
    victim.cancel()
    try:
        await victim
    except (asyncio.CancelledError, Exception):
        pass
    await s.tick(3, max_recoveries=8)
    assert s.recoveries >= 1
    assert s.last_recovery["scope"] == "mesh"
    assert list(s.coord.mesh_chains) == [chain], \
        "recovery must re-fuse the chain"
    agg = _chain_agg(s)
    assert len(agg._mesh_preludes) == 2
    await _quiesce(s)
    offset = committed_offsets(s, "m")["bid"]
    assert _chain_rows(s) == _chain_oracle(offset) and offset > 0
    await s.drop_all()


async def test_adaptive_shuffle_slack_sizes_from_observed_occupancy():
    """Adaptive slack (no `mesh_shuffle_slack` given): after a
    few watchdog observations the executor derives a power-of-two cap
    hint >= 2x the worst observed per-(src,dst) send-bucket demand, keeps
    zero-drop semantics, and stays bit-identical to the single-device
    plane."""
    msgs = q7_messages(seed=13, intervals=5, chunks_per=2)
    mesh = make_mesh(8)
    sh = ShardedHashAggExecutor(
        ScriptSource(BID, msgs), [2],
        [AggCall(AggKind.MAX, 1, BID[1].data_type, append_only=True),
         count_star()],
        mesh=mesh, capacity=64)
    assert sh.mesh_shuffle_adaptive, "adaptive sizing must be the default"
    before = MESH_SHUFFLE_DROPPED.value
    got = changelog(await drive(sh))
    assert MESH_SHUFFLE_DROPPED.value == before
    assert sh._fill_obs >= 3 and sh._cap_hint is not None
    # power of two, floored at 2x the all-time peak demand
    hint = sh._cap_hint
    assert hint & (hint - 1) == 0
    assert hint >= 2 * sh._fill_peak > 0
    plain = HashAggExecutor(
        ScriptSource(BID, msgs), [2],
        [AggCall(AggKind.MAX, 1, BID[1].data_type, append_only=True),
         count_star()],
        capacity=512)
    want = changelog(await drive(plain))
    assert got == want and len(got) > 0


async def test_manual_slack_overrides_adaptive():
    """An explicit `mesh_shuffle_slack` keeps the manual sizing —
    adaptive derivation stays off."""
    mesh = make_mesh(8)
    sh = ShardedHashAggExecutor(
        ScriptSource(BID, []), [0], [count_star()], mesh=mesh,
        capacity=32, mesh_shuffle_slack=4)
    assert not sh.mesh_shuffle_adaptive
    assert sh.mesh_shuffle_slack == 4


# ------------------------------------------- two-input fused join chains

async def test_fused_join_chain_hollows_both_sides():
    """Two-input auto-fusion: the q8-shaped join's per-side producer
    fragments (TUMBLE projects over each source leg) hollow into
    per-side preludes of the join's fused shard_map programs — one
    registered chain per side — bit-identical to the host recount at the
    quiesced committed offsets."""
    from risingwave_tpu.frontend import Session
    s = Session()
    await s.execute("SET streaming_durability = 0")
    await s.execute("SET streaming_parallelism_devices = 8")
    await s.execute("SET streaming_join_capacity = 16384")
    await _mk_join_sources(s)
    await s.execute(f"CREATE MATERIALIZED VIEW mj AS {JOIN_SQL}")
    chains = dict(s.coord.mesh_chains)
    sides = sorted(c for c in chains if c[-2:] in ("s0", "s1"))
    assert len(sides) == 2, f"expected one chain per join side: {chains}"
    joins = [node for roots in
             s.catalog.mvs["mj"].deployment.roots.values()
             for root in roots for node in _iter_chain(root)
             if isinstance(node, ShardedSortedJoinExecutor)]
    assert len(joins) == 1
    join = joins[0]
    assert set(join._mesh_preludes) == {0, 1} \
        and all(join._mesh_preludes.values()), \
        "both sides must install prelude stacks"
    a0 = join.mesh_shuffle_applies
    await s.tick(3)
    assert join.mesh_shuffle_applies > a0, "fused join never engaged"
    await _quiesce(s)
    got = Counter(s.query("SELECT id, window_start FROM mj"))
    assert got == _join_oracle(s, "mj") and sum(got.values()) > 0
    await s.drop_all()
    left = dict(s.coord.mesh_chains)
    assert not any(c in left for c in sides), \
        "drop must unregister both side chains"
