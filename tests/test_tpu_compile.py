"""Compile the main path's programs for a DESCRIBED TPU v5e — no chip needed.

The TPU's compiler is installed with jax and compiles for a `v5e:2x2`
topology that is described, not attached (nothing runs; a pass here is a
compile, never a chip run). These few compiles keep every later PR honest
about what the chip's compiler refuses — first found: under x64 it has no
`bitcast-convert` FROM f64 (common/floatbits.py), which sat under every
checkpoint persist of a FLOAT64 column.

Only one process at a time may load the TPU library, so the topology, the
shardings and the meshes are built inside module-scoped fixtures of THIS
file (never at import, in a skipif/parametrize argument or in conftest),
the compiles run in the test's own process, and the persistent compile
cache is switched off around them (a described-device executable can be
written to the cache but never read back).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

# The bench's widths are chunk 131072, join 2^19, agg 2^13. The TPU
# compiler takes about a minute PER PROGRAM there (56-67 s measured for the
# three q7 programs below; 2-12 s at these widths), and what it refuses —
# an op it cannot rewrite, a collective it cannot partition — does not
# depend on the width. The full-width compile of every program of
# q1/q5/q7/q8/q17 (231 programs, none refused, largest 1.8 GB) was made
# once by hand in PR 22 (CHANGES.md); this file keeps the same programs in
# tier-1 at a width that costs seconds.
CHUNK = 4096
JOIN_CAP, AGG_CAP = 1 << 14, 1 << 10
W = 10_000_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from risingwave_tpu.parallel.mesh import VNODE_AXIS
    return Mesh(np.asarray(topo.devices[:4]), (VNODE_AXIS,))


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def q7_executors():
    """The q7 plan of the benchmark's `q7.sat`, deployed (no data is run)."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.plan.build import _iter_executor_chain

    async def deploy():
        s = Session()
        for stmt in [
            f"SET streaming_join_capacity = {JOIN_CAP}",
            "SET streaming_join_match_factor = 2",
            f"SET streaming_agg_capacity = {AGG_CAP}",
            ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
             f"chunk_size={CHUNK}, inter_event_us=250, emit_watermarks=1, "
             f"watermark_lag_us={2 * W})"),
            ("CREATE MATERIALIZED VIEW q7 AS "
             "SELECT B.auction, B.price, B.bidder, B.date_time "
             "FROM bid B JOIN ("
             "  SELECT max(price) AS maxprice, window_end "
             f"  FROM TUMBLE(bid, date_time, {W}) GROUP BY window_end) B1 "
             "ON B.price = B1.maxprice "
             f"AND B.date_time > B1.window_end - {W} "
             "AND B.date_time <= B1.window_end"),
        ]:
            await s.execute(stmt)
        # the actors were never started (no barrier injected): nothing to
        # stop, the executors are only read for their programs and shapes
        return {type(ex).__name__: ex
                for roots in s.catalog.mvs["q7"].deployment.roots.values()
                for root in roots for ex in _iter_executor_chain(root)}

    return asyncio.run(deploy())


def abstract(tree, sharding):
    """Concrete pytree -> ShapeDtypeStructs placed by `sharding`."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def abstract_chunk(schema, capacity, sharding):
    from risingwave_tpu.common.chunk import Column, StreamChunk
    sds = lambda dt: jax.ShapeDtypeStruct((capacity,), dt,  # noqa: E731
                                          sharding=sharding)
    return StreamChunk(
        tuple(Column(sds(f.data_type.jnp_dtype)) for f in schema),
        sds(jnp.int8), sds(jnp.bool_), schema)


def fits_one_chip(compiled, limit=16 * 10**9):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < limit, f"{total} bytes on one 16 GB chip"
    return total


def test_q7_sorted_join_apply(q7_executors, one_chip,
                                             no_persistent_cache):
    from risingwave_tpu.stream.align import LEFT
    join = q7_executors["SortedJoinExecutor"]
    assert join.capacity[LEFT] == JOIN_CAP
    chunk = abstract_chunk(join.inputs[LEFT].schema, CHUNK, one_chip)
    compiled = join._apply._jitted.lower(
        abstract(join.sides[0], one_chip), abstract(join.sides[1], one_chip),
        abstract(join._errs_dev, one_chip), chunk,
        jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip),
        side=LEFT, match_factor=join.match_factors[LEFT]).compile()
    fits_one_chip(compiled)


def test_the_program_id_is_read_from_the_serialized_executable(
        q7_executors, one_chip, no_persistent_cache):
    """The `<id>` of a device trace's `jit_traced(<id>)` is the varint
    field 9 of the second message of libtpu's serialized executable
    (found on a v5e, PERF.md): held here to what THIS libtpu writes, so
    that a new one that moves it fails a test and not three metrics. Two
    programs, two ids; the same program, the same id."""
    from risingwave_tpu.ops.jit_state import program_id_of_serialized
    agg = q7_executors["HashAggExecutor"]
    chunk = abstract_chunk(agg.input.schema, CHUNK, one_chip)
    args = (abstract(agg.state, one_chip),
            abstract(agg._overflow_dev, one_chip))
    ids = []
    for jitted, a in ((agg._apply._jitted, args + (chunk,)),
                      (agg._apply._jitted, args + (chunk,)),
                      (agg._watchdog_pack._jitted,
                       args + (abstract(agg._occ_dev, one_chip),
                               jax.ShapeDtypeStruct((), jnp.int64,
                                                    sharding=one_chip)))):
        ser = bytes(jitted.lower(*a).compile().runtime_executable()
                    .serialize())
        ids.append(program_id_of_serialized(ser))
    assert all(isinstance(i, int) and i >= 1 << 32 for i in ids), ids
    assert ids[0] == ids[1] != ids[2]
    assert program_id_of_serialized(b"") is None
    assert program_id_of_serialized(ser[:100]) is None


def test_q7_sorted_join_durable_diff(q7_executors, one_chip,
                                                    no_persistent_cache):
    """The durable diff by the provenance lane — the program the volatile
    bench cells never reached. As the chip's compiler leaves it: no sort,
    and no loop (the content diff it replaced had two of each: its
    searches were `while` ops over capacity-sized state)."""
    join = q7_executors["SortedJoinExecutor"]
    side = abstract(join.sides[0], one_chip)
    compiled = join._diff._jitted.lower(side, side).compile()
    fits_one_chip(compiled)
    ops = [ln.split("=", 1)[1] for ln in compiled.as_text().splitlines()
           if "=" in ln]
    assert not [op for op in ops if " sort(" in op or " while(" in op]


def test_q7_hash_agg_apply(q7_executors, one_chip,
                                          no_persistent_cache):
    agg = q7_executors["HashAggExecutor"]
    assert agg.capacity == AGG_CAP
    chunk = abstract_chunk(agg.input.schema, CHUNK, one_chip)
    compiled = agg._apply._jitted.lower(
        abstract(agg.state, one_chip),
        abstract(agg._overflow_dev, one_chip), chunk).compile()
    fits_one_chip(compiled)


@pytest.fixture(scope="module")
def q5full_executors():
    """The plan of NEXMark q5 as published (`benchmark/queries/q5full.py`'s
    own DDL, at cut widths), deployed; no data is run."""
    from benchmark.queries import q5full
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.plan.build import _iter_executor_chain
    cfg = {"generator": {"inter_event_us": 2, "emit_watermarks": 1},
           "hop_slide_us": 2_000_000, "hop_size_us": 10_000_000,
           "session_set": {"streaming_agg_capacity": AGG_CAP,
                           "streaming_join_capacity": JOIN_CAP,
                           "streaming_join_match_factor": 2048}}

    async def deploy():
        s = Session()
        for stmt in q5full.ddl(cfg, {"chunk_size": {"bid": CHUNK},
                                     "chunks_per_interval": {"bid": 1}}, 7):
            await s.execute(stmt)
        return [ex for roots
                in s.catalog.mvs["q5full"].deployment.roots.values()
                for root in roots for ex in _iter_executor_chain(root)]

    return asyncio.run(deploy())


@pytest.mark.parametrize("program", ["apply", "flush", "persist_view",
                                     "watchdog_pack"])
def test_q5full_retractable_max_programs(q5full_executors, one_chip,
                                         no_persistent_cache, program):
    """The max-of-counts agg of published q5: its state is the top-32
    value buffer per group (`[C, 32]` int64 values, int32 counts, the lossy
    flag), its input chunk the count agg's flush: at most 2 x capacity rows
    wide, in a real run as wide as the power of two that holds the dirty
    groups. The apply sorts 64-bit keys along both axes, gathers the touched
    groups' buffers and scatters into `[M + 1, 32]` candidate buffers; flush
    and persist view stop at `n_slots` dirty slots: what the chip's compiler
    makes of that is met here, not in the cell's first run."""
    from risingwave_tpu.stream.hash_agg import (
        FLUSH_MIN_SLOTS, HashAggExecutor)
    agg, = [ex for ex in q5full_executors
            if isinstance(ex, HashAggExecutor) and any(ex._retractable)]
    assert agg.capacity == AGG_CAP and agg.minput_k == 32
    vals, cnts, lossy = agg.state.agg_states[0]
    assert (vals.shape, vals.dtype, cnts.dtype, lossy.dtype) == (
        (AGG_CAP, 32), jnp.int64, jnp.int32, jnp.bool_)
    state = abstract(agg.state, one_chip)
    ov = abstract(agg._overflow_dev, one_chip)
    assert ov.shape == (5,) and ov.dtype == jnp.int32
    lowered = {
        "apply": lambda: agg._apply._jitted.lower(
            state, ov, abstract_chunk(agg.input.schema, 2 * AGG_CAP,
                                      one_chip)),
        "flush": lambda: agg._flush._jitted.lower(
            state, n_slots=FLUSH_MIN_SLOTS),
        "persist_view": lambda: agg._persist_view._jitted.lower(
            state, n_slots=FLUSH_MIN_SLOTS),
        "watchdog_pack": lambda: agg._watchdog_pack._jitted.lower(
            state, ov, abstract(agg._occ_dev, one_chip),
            jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)),
    }[program]()
    fits_one_chip(lowered.compile())


@pytest.mark.parametrize("side", ["left", "right"])
def test_q5full_join_apply_with_the_published_condition(
        q5full_executors, one_chip, no_persistent_cache, side):
    """The join of published q5: one equi key (the window), `num >= maxn`
    evaluated inside the apply, both sides retracting. The left apply takes
    the count agg's flush chunk against a max side that is unique per
    window (factor 2); the right apply takes the MAX agg's 256-row chunk
    and expands every count of the re-stated windows: 2048 x 256 = 2^19
    candidates, the buffer the cell runs with."""
    from risingwave_tpu.stream.align import LEFT, RIGHT
    from risingwave_tpu.stream.hash_agg import FLUSH_MIN_SLOTS
    from risingwave_tpu.stream.sorted_join import SortedJoinExecutor
    join, = [ex for ex in q5full_executors
             if isinstance(ex, SortedJoinExecutor)]
    assert join.condition is not None and join.match_factors == (2, 2048)
    s, width = {"left": (LEFT, 2 * AGG_CAP),
                "right": (RIGHT, 2 * FLUSH_MIN_SLOTS)}[side]
    compiled = join._apply._jitted.lower(
        abstract(join.sides[s], one_chip),
        abstract(join.sides[1 - s], one_chip),
        abstract(join._errs_dev, one_chip),
        abstract_chunk(join.inputs[s].schema, width, one_chip),
        jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip),
        side=s, match_factor=join.match_factors[s]).compile()
    fits_one_chip(compiled)


@pytest.fixture(scope="module")
def q4_executors():
    """The plan of NEXMark q4 as published (`benchmark/queries/q4.py`'s own
    DDL, at cut widths; NEXMark's 46 : 3 of bids to auctions), deployed; no
    data is run."""
    from benchmark.queries import q4
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.plan.build import _iter_executor_chain
    cfg = {"generator": {"inter_event_us": 100, "emit_watermarks": 0,
                         "hot_auction_ratio": 2, "hot_bidder_ratio": 4},
           "session_set": {"streaming_agg_capacity": AGG_CAP,
                           "streaming_join_capacity": JOIN_CAP,
                           "streaming_join_match_factor": 16}}

    async def deploy():
        s = Session()
        for stmt in q4.ddl(cfg, {"chunk_size": {"bid": 46 * 64,
                                                "auction": 3 * 64},
                                 "chunks_per_interval": {"bid": 1,
                                                         "auction": 1}}, 7):
            await s.execute(stmt)
        return [ex for roots in s.catalog.mvs["q4"].deployment.roots.values()
                for root in roots for ex in _iter_executor_chain(root)]

    return asyncio.run(deploy())


@pytest.mark.parametrize("program", ["auction_apply", "bid_apply",
                                     "watchdog_pack", "avg_apply",
                                     "avg_persist_view"])
def test_q4_join_and_float_avg_programs(q4_executors, one_chip,
                                        no_persistent_cache, program):
    """q4 as published on one chip: the stream's applies of a join nothing
    cleans — each also folds the rows it emitted and its equi-key candidates
    into the int32[4] the watchdog pack fetches and zeroes (factor 16 on
    both sides: upstream's sources declare no key) — and the retractable
    FLOAT64 SUM / COUNT under the AVG, an f64 being two f32 on the chip: its
    apply, and the persist view that hands the sum to the d2h pack."""
    from risingwave_tpu.stream.align import LEFT, RIGHT
    from risingwave_tpu.stream.hash_agg import (
        FLUSH_MIN_SLOTS, HashAggExecutor)
    from risingwave_tpu.stream.sorted_join import SortedJoinExecutor
    join, = [ex for ex in q4_executors if isinstance(ex, SortedJoinExecutor)]
    avg, = [ex for ex in q4_executors if isinstance(ex, HashAggExecutor)
            and len(ex.group_key_indices) == 1]
    assert join.match_factors == (16, 16) and join.capacity[LEFT] == JOIN_CAP
    assert [c.ret_type.name for c in avg.agg_calls] == ["FLOAT64", "INT64"]

    def apply(s, width):
        return join._apply_counted._jitted.lower(
            abstract(join.sides[s], one_chip),
            abstract(join.sides[1 - s], one_chip),
            abstract(join._errs_dev, one_chip),
            abstract(join._match_dev, one_chip),
            abstract_chunk(join.inputs[s].schema, width, one_chip),
            jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip),
            side=s, match_factor=join.match_factors[s])

    n = abstract(join._n_dev[0], one_chip)
    state = abstract(avg.state, one_chip)
    compiled = {
        "auction_apply": lambda: apply(LEFT, 3 * 64),
        "bid_apply": lambda: apply(RIGHT, 46 * 64),
        "watchdog_pack": lambda: join._watchdog_pack._jitted.lower(
            abstract(join._errs_dev, one_chip), n, n,
            abstract(join._match_dev, one_chip)),
        "avg_apply": lambda: avg._apply._jitted.lower(
            state, abstract(avg._overflow_dev, one_chip),
            abstract_chunk(avg.input.schema, 2 * FLUSH_MIN_SLOTS, one_chip)),
        "avg_persist_view": lambda: avg._persist_view._jitted.lower(
            state, n_slots=FLUSH_MIN_SLOTS),
    }[program]().compile()
    fits_one_chip(compiled)
    if program.startswith("avg"):
        assert "bitcast-convert" not in "".join(
            ln for ln in compiled.as_text().splitlines() if "f64" in ln)


@pytest.fixture(scope="module")
def q8_executors():
    """The plan of NEXMark q8 as published (`benchmark/queries/q8.py`'s own
    DDL, at cut widths; NEXMark's 1 : 3 of persons to auctions), deployed;
    no data is run."""
    from benchmark.queries import q8
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.plan.build import _iter_executor_chain
    cfg = {"generator": {"inter_event_us": 100, "emit_watermarks": 1,
                         "watermark_lag_us": 0, "hot_seller_bucket": 100},
           "window_us": W,
           "session_set": {"streaming_agg_capacity": AGG_CAP,
                           "streaming_join_capacity": JOIN_CAP,
                           "streaming_join_match_factor": 2}}

    async def deploy():
        s = Session()
        for stmt in q8.ddl(cfg, {"chunk_size": {"person": 64 * 8,
                                                "auction": 3 * 64 * 8},
                                 "chunks_per_interval": {"person": 1,
                                                         "auction": 1}}, 7):
            await s.execute(stmt)
        return [ex for roots in s.catalog.mvs["q8"].deployment.roots.values()
                for root in roots for ex in _iter_executor_chain(root)]

    return asyncio.run(deploy())


@pytest.mark.parametrize("program", ["person_apply", "person_rehash",
                                     "person_watchdog_pack",
                                     "person_evict_keys",
                                     "person_persist_view",
                                     "join_person_apply"])
def test_q8_published_programs(q8_executors, one_chip, no_persistent_cache,
                               program):
    """`q8.sat` on one chip: the person aggregate's table is keyed by (id,
    name, window_start, window_end) with the name an int32 dictionary id
    beside three int64 — its apply, the same-capacity rehash that purges its
    zombies (a loop over blocks of survivors; compiled at the first
    barrier, `_precompile_purge`), the watchdog pack that now counts the live groups and those the cleaning
    watermark is about to evict, the evict keys and the persist view — and
    the join's apply of that side, whose rows carry the name too."""
    from risingwave_tpu.stream.align import LEFT
    from risingwave_tpu.stream.hash_agg import HashAggExecutor
    from risingwave_tpu.stream.sorted_join import SortedJoinExecutor
    join, = [ex for ex in q8_executors if isinstance(ex, SortedJoinExecutor)]
    person, = [ex for ex in q8_executors if isinstance(ex, HashAggExecutor)
               and len(ex.group_key_indices) == 4]
    assert [str(np.dtype(d)) for d in person._key_dtypes] == [
        "int64", "int32", "int64", "int64"]
    assert person.cleaning_watermark_key is not None
    state = abstract(person.state, one_chip)
    wm = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    ov = abstract(person._overflow_dev, one_chip)
    compiled = {
        "person_apply": lambda: person._apply._jitted.lower(
            state, ov, abstract_chunk(person.input.schema, 64 * 8, one_chip)),
        "person_rehash": lambda: person._rehash._jitted.lower(
            state, person.capacity),
        "person_watchdog_pack": lambda: person._watchdog_pack._jitted.lower(
            state, ov, abstract(person._occ_dev, one_chip), wm),
        "person_evict_keys": lambda: person._evict_keys._jitted.lower(
            state, wm),
        "person_persist_view": lambda: person._persist_view._jitted.lower(
            state, n_slots=1024),
        "join_person_apply": lambda: join._apply_counted._jitted.lower(
            abstract(join.sides[LEFT], one_chip),
            abstract(join.sides[1 - LEFT], one_chip),
            abstract(join._errs_dev, one_chip),
            abstract(join._match_dev, one_chip),
            abstract_chunk(join.inputs[LEFT].schema, 2048, one_chip), wm,
            side=LEFT, match_factor=join.match_factors[LEFT]),
    }[program]().compile()
    fits_one_chip(compiled)


def test_the_rehash_sorts_blocks_of_survivors_not_the_table(
        q8_executors, one_chip):
    """The person aggregate's purge at the cell's width, 2^19 slots,
    lowered for the chip (nothing is compiled): every sort of
    `hash_agg_rehash` is inside the loop over blocks of survivors and
    REHASH_BLOCK rows wide. Before PR 43 the survivors were ONE chunk as
    wide as the table: eight 2^19-row sorts for ~2,000 survivors, 0.6 s a
    purge on the chip."""
    import re

    from risingwave_tpu.stream.hash_agg import REHASH_BLOCK, HashAggExecutor
    person, = [ex for ex in q8_executors if isinstance(ex, HashAggExecutor)
               and len(ex.group_key_indices) == 4]
    C = 1 << 19
    state = abstract(jax.eval_shape(lambda: person._empty_state(C)),
                     one_chip)
    hlo = person._rehash._jitted.lower(state, C).compiler_ir(
        dialect="hlo").as_hlo_text()
    sorts = [line for line in hlo.splitlines() if " sort(" in line]
    rows = {int(n) for line in sorts
            for n in re.findall(r"\[(\d+)\]", line.split(" sort(")[0])}
    assert sorts and rows == {REHASH_BLOCK} and REHASH_BLOCK < C
    assert " while(" in hlo


@pytest.fixture(scope="module")
def q17_snapshot():
    """TPC-H Q17 as `benchmark/queries/q17.py` states it, deployed (no data
    is run): the fused snapshot join-agg executor."""
    from benchmark.queries import q17
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.plan.build import _iter_executor_chain
    from risingwave_tpu.stream.snapshot_join_agg import (
        SnapshotJoinAggExecutor)
    cfg = {"generator": {"scale_factor": 1, "brand": "Brand#23",
                         "container": "MED BOX"},
           "session_set": {"streaming_join_capacity": JOIN_CAP,
                           "streaming_agg_capacity": 256}}

    async def deploy():
        s = Session()
        for stmt in q17.ddl(cfg, {"chunk_size": {"lineitem": 30 * 64,
                                                 "part": 64},
                                  "chunks_per_interval": {"lineitem": 1,
                                                          "part": 1}}, 7):
            await s.execute(stmt)
        snap, = [ex for roots in s.catalog.mvs["q17"].deployment.roots
                 .values() for root in roots
                 for ex in _iter_executor_chain(root)
                 if isinstance(ex, SnapshotJoinAggExecutor)]
        return snap

    return asyncio.run(deploy())


@pytest.mark.parametrize("program", ["append_fact", "flush", "persist_pack",
                                     "gen_lineitem"])
def test_q17_snapshot_join_agg_programs(q17_snapshot, one_chip,
                                        no_persistent_cache, program):
    """`q17.sat` on one chip: the lineitem store's append, the barrier's
    snapshot recompute with the threshold as one INT64 a group (floor
    division of int64 on the chip), the persist pack's dynamic-offset window,
    and the spec-following generator with its seed a dynamic argument. At the
    cell's 2^23 rows the flush compiled in 158 s by hand (the parent's beside
    it in 146 s; PERF.md, PR 39). Since PR 47 the rows ride the flush's sort
    as payload (eight 32-bit operands where the argsort had three) and a sort
    compiles by its operands: on the chip's host the 2^23 program compiled
    in ~126 s (a cold warm-up of 130.9 s against 4.8 s warm; the parent's
    ~99 s in the same call: 106.0 against 6.8), for a described v5e on this
    sandbox's CPU in 258 s (the parent's beside it in 137 s; PERF.md,
    PR 47)."""
    from risingwave_tpu.connectors import tpch
    snap = q17_snapshot
    assert snap.capacity == JOIN_CAP and len(snap._fcols) == 3
    A = lambda tree: abstract(tree, one_chip)  # noqa: E731
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt,  # noqa: E731
                                             sharding=one_chip)
    gen = tpch.TpchGenerator("lineitem", chunk_size=30 * 64)
    compiled = {
        "append_fact": lambda: snap._append_fact._jitted.lower(
            A(snap._fcols), A(snap._fvalids), A(snap._fn), A(snap._errs),
            abstract_chunk(snap.inputs[0].schema, 30 * 64, one_chip)),
        "flush": lambda: snap._flush._jitted.lower(
            A(snap._fcols), A(snap._fvalids), A(snap._fn), A(snap._dkeys),
            A(snap._dn), A(snap._prev), A(snap._prev_valid),
            A(snap._emitted)),
        "persist_pack": lambda: snap._persist_pack._jitted.lower(
            A(snap._fcols), A(snap._fvalids), A(snap._dkeys),
            scalar(jnp.int32), scalar(jnp.int32), wf=2048, wd=64),
        "gen_lineitem": lambda: tpch.gen_lineitem_columns.lower(
            scalar(jnp.uint64), scalar(jnp.int64), A(gen._vocab_ids),
            n=30 * 64, n_parts=200_000, n_suppliers=10_000),
    }[program]().compile()
    fits_one_chip(compiled)


def test_float_column_diff_lanes_compile(one_chip, no_persistent_cache):
    """A sorted-join side holding an f64 and an f32 column: the diff
    compiles (its row gathers move the floats as they are; nothing
    reinterprets an f64 on the device)."""
    from risingwave_tpu.stream.sorted_join import (SortedJoinExecutor,
                                                   SortedSideState)
    C = 1 << 16
    sds = lambda dt, shape=(C,): jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    st = SortedSideState(
        sds(jnp.int64), (sds(jnp.int64), sds(jnp.float64), sds(jnp.float32)),
        (sds(jnp.bool_),) * 3, sds(jnp.int32), sds(jnp.int32),
        sds(jnp.int32, ()))
    compiled = jax.jit(SortedJoinExecutor._diff_impl).lower(st, st).compile()
    assert "bitcast-convert" not in "".join(
        ln for ln in compiled.as_text().splitlines() if "f64" in ln)


def test_pack_for_fetch_with_f64_and_f32_columns(one_chip,
                                                 no_persistent_cache):
    """The one d2h primitive under every checkpoint persist."""
    from risingwave_tpu.utils.d2h import pack_for_fetch
    n = 1 << 17
    cols = [jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
            for dt in (jnp.int64, jnp.float64, jnp.float32, jnp.int8,
                       jnp.bool_, jnp.float64)]
    compiled = jax.jit(lambda *a: pack_for_fetch(a)[0]).lower(*cols).compile()
    assert "bitcast-convert" not in "".join(
        ln for ln in compiled.as_text().splitlines() if "f64" in ln), \
        "an f64 is reinterpreted on the device"


def test_vnode_hash_over_float_key(one_chip, no_persistent_cache):
    from risingwave_tpu.common.vnode import compute_vnodes, crc32_columns
    n = CHUNK
    f64 = jax.ShapeDtypeStruct((n,), jnp.float64, sharding=one_chip)
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    i64 = jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one_chip)
    jax.jit(lambda a, b, c: compute_vnodes([a, b, c])
            ).lower(f64, f32, i64).compile()
    jax.jit(lambda a, c: crc32_columns([a, c])).lower(f64, i64).compile()


def test_hll_float_bits_compile(one_chip, no_persistent_cache):
    from risingwave_tpu.expr.hll import _bucket_rank_jnp
    f64 = jax.ShapeDtypeStruct((CHUNK,), jnp.float64, sharding=one_chip)
    jax.jit(_bucket_rank_jnp).lower(f64).compile()


@pytest.fixture(scope="module")
def q7_mesh_executors(mesh4):
    """q7 at `streaming_parallelism_devices = 4`, deployed through SQL with
    its mesh made of the four DESCRIBED chips (benchmark cell q7x4.sat's
    plan; no data is run). Nothing can be placed on a described device:
    the initial state lands on four of the suite's virtual CPU devices
    instead; every program is traced against `mesh4`."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.parallel import mesh as mesh_mod
    from risingwave_tpu.plan.build import _iter_executor_chain
    cpu_mesh = mesh_mod.make_mesh(4, devices=jax.devices("cpu"))
    real_put = jax.device_put

    def put(x, sharding=None, **kw):
        if isinstance(sharding, NamedSharding) and sharding.mesh == mesh4:
            sharding = NamedSharding(cpu_mesh, sharding.spec)
        return real_put(x, sharding, **kw)

    async def deploy():
        s = Session()
        for stmt in [
            f"SET streaming_join_capacity = {JOIN_CAP}",
            "SET streaming_join_match_factor = 2",
            f"SET streaming_agg_capacity = {AGG_CAP}",
            "SET streaming_parallelism_devices = 4",
            # rate_limit 0: the source parks on its barrier queue, so no
            # chunk ever reaches a program of the described mesh
            ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
             f"chunk_size={CHUNK}, inter_event_us=250, emit_watermarks=1, "
             f"watermark_lag_us={2 * W}, rate_limit=0)"),
            ("CREATE MATERIALIZED VIEW q7 AS "
             "SELECT B.auction, B.price, B.bidder, B.date_time "
             "FROM bid B JOIN ("
             "  SELECT max(price) AS maxprice, window_end "
             f"  FROM TUMBLE(bid, date_time, {W}) GROUP BY window_end) B1 "
             "ON B.price = B1.maxprice "
             f"AND B.date_time > B1.window_end - {W} "
             "AND B.date_time <= B1.window_end"),
        ]:
            await s.execute(stmt)
        return {type(ex).__name__: ex
                for roots in s.catalog.mvs["q7"].deployment.roots.values()
                for root in roots for ex in _iter_executor_chain(root)}

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mesh_mod, "make_mesh", lambda n, **_kw: mesh4)
        mp.setattr(jax, "device_put", put)
        return asyncio.run(deploy())


def test_fused_sharded_agg_on_the_4_device_mesh(q7_mesh_executors, mesh4,
                                                no_persistent_cache):
    """The sharded agg of q7 at parallelism 4, on the mesh of the four
    described chips: its fused shard_map programs (the hollowed TUMBLE
    projections, the in-mesh all_to_all shuffle, the sharded hash-table
    apply; one chunk, and four chunks as one `lax.scan`: what q7x4.sat
    dispatches per checkpoint) and its barrier watchdog's cross-shard
    reduction; per-device footprint against 16 GB."""
    from risingwave_tpu.parallel.mesh import VNODE_AXIS
    ex = q7_mesh_executors["ShardedHashAggExecutor"]
    assert ex.capacity == AGG_CAP // 4 and len(ex._mesh_preludes) == 2
    sharded = NamedSharding(mesh4, P(VNODE_AXIS))
    bid = q7_mesh_executors["SourceExecutor"].schema
    state = (abstract(ex.state, sharded), abstract(ex._overflow_dev, sharded),
             abstract(ex._dropped_dev, sharded),
             abstract(ex._shuffle_obs_dev, sharded))
    chunk = abstract_chunk(bid, CHUNK, sharded)
    for compiled in (
            ex._get_fused_apply()._jitted.lower(*state, chunk).compile(),
            ex._make_fused_scan(4)._jitted.lower(
                *state, chunk, chunk, chunk, chunk).compile()):
        assert "all-to-all" in compiled.as_text(), \
            "no in-mesh shuffle lowered"
        fits_one_chip(compiled)
    # the watchdog at the dtypes a RUN hands it: the occupancy accumulator
    # is int64 after the first apply, and the TPU lowers a 64-bit
    # all-reduce only for SUM (found on the four real chips: `pmax` of an
    # s64 was refused)
    i32 = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=sharded)
    i64 = jax.ShapeDtypeStruct((4,), jnp.int64, sharding=sharded)
    ov = jax.ShapeDtypeStruct((4, 2), jnp.int32, sharding=sharded)
    obs = jax.ShapeDtypeStruct((4, 2), jnp.int32, sharding=sharded)
    ex._watchdog_pack._jitted.lower(ov, i64, i32, obs).compile()


def test_sharded_agg_purge_on_the_4_device_mesh(q7_mesh_executors, mesh4,
                                                no_persistent_cache):
    """The mesh agg's zombie purge on the four described chips: the
    rehash's loop over blocks of survivors inside `shard_map`, a trip
    count per shard and no collective in it."""
    from risingwave_tpu.parallel.mesh import VNODE_AXIS
    ex = q7_mesh_executors["ShardedHashAggExecutor"]
    sharded = NamedSharding(mesh4, P(VNODE_AXIS))
    compiled = ex._purge._jitted.lower(abstract(ex.state, sharded)).compile()
    text = compiled.as_text()
    assert " while(" in text
    assert not any(op in text for op in ("all-reduce", "all-to-all",
                                         "all-gather", "collective-permute"))
    fits_one_chip(compiled)


def test_fused_sharded_join_on_the_4_device_mesh(q7_mesh_executors, mesh4,
                                                 no_persistent_cache):
    """q7's sharded join on the four described chips: the fused program of
    each side (all_to_all on price / maxprice, then the shard-local probe
    and state update, the provenance lane moved shard by shard) and the
    watchdog pack with the shuffle's observation lanes and the two live-row
    sums."""
    from risingwave_tpu.parallel.mesh import VNODE_AXIS
    from risingwave_tpu.stream.align import LEFT, RIGHT
    join = q7_mesh_executors["ShardedSortedJoinExecutor"]
    assert join.capacity[LEFT] == JOIN_CAP // 4
    sharded = NamedSharding(mesh4, P(VNODE_AXIS))
    wm = jax.ShapeDtypeStruct((), jnp.int64,
                              sharding=NamedSharding(mesh4, P()))
    acc = (abstract(join._errs_dev, sharded),
           abstract(join._dropped_dev, sharded),
           abstract(join._shuffle_obs_dev, sharded))
    for side, cap in ((LEFT, CHUNK), (RIGHT, 2 * AGG_CAP)):
        own, other = (abstract(join.sides[s], sharded)
                      for s in (side, 1 - side))
        compiled = join._apply_program(
            side, join.match_factors[side], True)._jitted.lower(
            own, other, *acc,
            abstract_chunk(join.inputs[side].schema, cap, sharded),
            wm).compile()
        assert "all-to-all" in compiled.as_text()
        fits_one_chip(compiled)
    n = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=sharded)
    join._watchdog_pack_sh._jitted.lower(*acc, n, n).compile()


def _top_n_programs(capacity: int, chunk_rows: int, one_chip,
                    append_only: bool = True):
    """The programs of a group top-N over bid rows (the benchmark's
    `q19.sat`: nine lanes a row with the row id, three of them int32
    dictionary ids), lowered for the described chip: (name, lowered). An
    append-only input: the merge, the rank and the emit; a retracting one:
    the merge and the flush."""
    from risingwave_tpu.connectors.nexmark import BID_SCHEMA
    from risingwave_tpu.stream.executor import Executor
    from risingwave_tpu.stream.retract_top_n import RetractableTopNExecutor
    from risingwave_tpu.stream.row_id import RowIdGenExecutor

    class Bids(Executor):
        schema = BID_SCHEMA

    rows = RowIdGenExecutor(Bids())
    top = RetractableTopNExecutor(
        rows, (0,), order_specs=[(2, True), (7, False)], limit=10,
        capacity=capacity, pk_indices=(7,), append_only=append_only,
        emit_rank=True)
    store = abstract((top.khash, top.cols, top.valids, top.n), one_chip)
    errs = abstract(top._errs_dev, one_chip)
    yield "apply", top._apply._jitted.lower(
        *store, errs, abstract_chunk(rows.schema, chunk_rows, one_chip))
    if not append_only:
        yield "flush", top._flush._jitted.lower(*store, *abstract(
            (top.top_hash, top.top_cols, top.top_valids, top.top_n),
            one_chip))
        return
    yield "rank", top._rank._jitted.lower(store[0], store[1][-1], store[3],
                                          errs)
    yield "emit", top._emit._jitted.lower(
        *store, width=chunk_rows, persist_width=chunk_rows, durable=True)


def _wide_ops(compiled, op: str, width: int) -> list:
    """The instructions `op` of the compiled program that read or write an
    array `width` elements long."""
    return [ln.strip() for ln in compiled.as_text().splitlines()
            if f" {op}(" in ln and f"[{width}]" in ln]


def test_append_only_group_top_n(one_chip, no_persistent_cache):
    """The merge into the store kept in RANK order (the chunk's rows are
    sorted and placed by one search), the rank by run boundaries and the
    gather of what changed with the store's compaction: no program sorts
    the capacity, and the rank program gathers nothing at all. A
    retracting top-N keeps its capacity-wide sorts: the two forms are
    separate programs."""
    cap = 1 << 14
    assert cap != CHUNK
    compiled = {name: low.compile()
                for name, low in _top_n_programs(cap, CHUNK, one_chip)}
    assert list(compiled) == ["apply", "rank", "emit"]
    for exe in compiled.values():
        fits_one_chip(exe)
        assert not _wide_ops(exe, "sort", cap)
    # the chunk's lexsort is N wide; the rank program holds no sort and no
    # gather of any width, the emit no sort
    assert len(_wide_ops(compiled["apply"], "sort", CHUNK)) >= 3
    rank = compiled["rank"].as_text()
    assert " sort(" not in rank and " gather(" not in rank
    assert " sort(" not in compiled["emit"].as_text()
    flush = dict(_top_n_programs(cap, CHUNK, one_chip,
                                 append_only=False))["flush"].compile()
    fits_one_chip(flush)
    assert len(_wide_ops(flush, "sort", cap)) >= 4
    assert _wide_ops(flush, "gather", cap)
