"""Driver benchmark — prints ONE JSON line with the headline metric.

Measures Nexmark pipeline throughput (rows/sec/chip) on the current jax
backend for q1/q5/q7/q8 and reports ALL of them in the single JSON line;
the headline value/vs_baseline is the WORST of the north-star queries
(q7, q8 — BASELINE.md: >=10x CPU rows/s is the target), so the recorded
number can never hide a regressing join. Workload definitions mirror the
reference's Nexmark SQL set (/root/reference/ci/scripts/sql/nexmark/q*.sql);
the metric matches the reference's `stream_source_output_rows_counts` rate
and the barrier-latency histogram (BASELINE.md;
grafana/risingwave-dev-dashboard.dashboard.py:693-715, 894-901).

vs_baseline is MEASURED: the same pipeline shape runs through a vectorized
numpy host implementation (the stand-in for the reference's CPU executors —
the reference publishes no absolute numbers, BASELINE.md) on the same
generated rows in a fresh CPU-only subprocess.

Process isolation: EACH query runs in its own subprocess (a chip belongs
to one process at a time, and one query per process keeps every timed
region clean of the previous query's fetches and compiles); the
orchestrator itself never touches jax. Every level is deadline-bounded:
partial progress is emitted if anything hangs, and a deadline abort or a
failed device probe exits NON-ZERO.

This is the pre-chip harness (its numbers were never taken on the current
chip; README "Measured"); the first `benchmark` issue replaces it.
chip_smoke.py is the proof that the engine runs on the chip.
"""

import asyncio
import json
import os
import re
import subprocess
import sys
import threading
import time

# Persistent XLA compilation cache: the q5/q7/q8 programs compile for a
# long time cold; with the cache warm the whole bench fits the global
# budget. ONE rule (utils/compile_cache.py): a JAX_COMPILATION_CACHE_DIR
# placed from outside is used as is; otherwise the cache is
# <checkout>/.jax_cache — the same fixed path the setdefault below hands
# the query/baseline subprocesses, which then call
# enable_persistent_cache() themselves. The orchestrator itself never
# imports jax — device init belongs in deadline-bounded children only.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")

import numpy as np

# Hard wall-clock budget for the whole bench (driver timeouts are larger;
# this guarantees a JSON line is printed well before any external timeout).
GLOBAL_BUDGET_S = 560.0
# Deadline for the pre-flight jax.devices() probe (a device init that
# hangs inside the first query subprocess would be recorded as that
# query's 0.0 rows/s — the stall must be diagnosed BEFORE any query is
# charged for it).
DEVICE_PROBE_TIMEOUT_S = 120.0
# Per-query subprocess budgets (compile + measure + baseline), seconds.
QUERY_BUDGET_S = {"q1": 60.0, "q5": 150.0, "q7": 150.0, "q8": 170.0,
                  "q17": 150.0, "q7d": 150.0, "q7_kill": 150.0,
                  "q7_kill_interior": 150.0, "q7_kill_worker": 200.0,
                  "q5_8chip": 150.0, "q7_8chip": 150.0,
                  "q5_fused": 150.0, "q7_fused": 150.0,
                  "q5_topn_8chip": 150.0}
# Baseline inputs are fixed (they don't depend on the device run), so the
# orchestrator computes all four baselines in PARALLEL CPU subprocesses
# while the device queries run serially.
BASELINE_CHUNKS = {"q1": (16, 131072), "q5": (8, 131072),
                   "q7": (8, 131072), "q8": (8, 393216),
                   "q17": (64, 8192)}
# q17's data: TPC-H by clause 4.2.3 (connectors/tpch.py) at scale factor
# 0.005, a 1,000-part universe, under a seed at which three parts pass the
# brand and container filter
TPCH_SF, TPCH_SEED = 0.005, 22
# Target duration of the timed measurement region per query.
MEASURE_S = 8.0
# Per-PHASE deadlines (fractions of the query budget): a stalled setup
# or warmup aborts with ITS name on the note instead of silently burning
# the whole budget and reporting a generic "teardown abandoned"
# (BENCH_r05 post-mortem: all four queries recorded 0.0 with zero
# attribution of WHERE they hung).
PHASE_FRACTION = {"setup_ddl": 0.35, "warmup_compile": 0.75,
                  "measure": 0.95, "quiesce": 0.5, "teardown": 0.4}


def _phase(progress: dict, name: str) -> None:
    """Enter a named phase; the watcher enforces the per-phase deadline
    and any abort note names the phase + how long it ran."""
    progress["phase"] = name
    progress["phase_t0"] = time.perf_counter()
    hist = progress.setdefault("phase_history", [])
    hist.append(name)


# ---------------------------------------------------------------- numpy CPU
# Host-side vectorized implementations of the same query shapes, the
# vs_baseline denominator. They consume the same generator chunks (as numpy)
# and maintain the same state, the way the reference's vectorized CPU
# executors would.

def _numpy_q1(chunks) -> float:
    t0 = time.perf_counter()
    acc = 0.0
    for cols, vis in chunks:
        price = cols[2] * 0.908
        acc += float(price[vis].sum())  # force the work
    return time.perf_counter() - t0


def _numpy_q5(chunks, slide_us=2_000_000, size_us=10_000_000) -> float:
    """Incremental hash-agg state as a sorted (keys, counts) pair, updated
    with fully vectorized merges — the numpy analogue of a vectorized CPU
    HashAgg executor (no per-row interpreter loops)."""
    t0 = time.perf_counter()
    state_keys = np.empty(0, dtype=np.int64)
    state_counts = np.empty(0, dtype=np.int64)
    k = size_us // slide_us
    for cols, vis in chunks:
        auction = cols[0][vis].astype(np.int64)
        ts = cols[5][vis]
        first = (ts // slide_us) * slide_us - (k - 1) * slide_us
        keys = np.concatenate([
            (auction << 20) ^ ((first + j * slide_us) // slide_us)
            for j in range(k)])
        uk, uc = np.unique(keys, return_counts=True)
        idx = np.searchsorted(state_keys, uk)
        safe = np.minimum(idx, max(len(state_keys) - 1, 0))
        found = (idx < len(state_keys)) & (
            state_keys[safe] == uk if len(state_keys) else False)
        state_counts[idx[found]] += uc[found]
        if not found.all():
            nk, nc = uk[~found], uc[~found]
            merged = np.concatenate([state_keys, nk])
            order = np.argsort(merged, kind="stable")
            state_keys = merged[order]
            state_counts = np.concatenate([state_counts, nc])[order]
    return time.perf_counter() - t0


def _numpy_q7(chunks, window_us=10_000_000) -> float:
    """Vectorized numpy q7: per-window running max + bids-at-max join.
    Incremental across chunks like a CPU streaming executor would be."""
    t0 = time.perf_counter()
    win_max: dict[int, int] = {}
    emitted = 0
    for cols, vis in chunks:
        price = cols[2][vis]
        ts = cols[5][vis]
        we = (ts - ts % window_us) + window_us
        order = np.argsort(we, kind="stable")
        we_s, p_s = we[order], price[order]
        bounds = np.flatnonzero(np.r_[True, we_s[1:] != we_s[:-1]])
        chunk_max = np.maximum.reduceat(p_s, bounds)
        for w, m in zip(we_s[bounds], chunk_max):
            w = int(w)
            if win_max.get(w, -1) < m:
                win_max[w] = int(m)
        # join: bids whose price equals their window's current max
        cur = np.array([win_max[int(w)] for w in we_s], dtype=p_s.dtype)
        emitted += int((p_s == cur).sum())
    return time.perf_counter() - t0


def _numpy_q8(pchunks, achunks, window_us=10_000_000) -> float:
    """Vectorized numpy q8: per-window person-id set joined with auction
    sellers of the same window, incremental across chunks."""
    t0 = time.perf_counter()
    persons: dict[int, set] = {}
    matches = 0
    for (pcols, pvis), (acols, avis) in zip(pchunks, achunks):
        pid = pcols[0][pvis]
        pts = pcols[6][pvis]
        pw = pts - pts % window_us
        for w in np.unique(pw):
            persons.setdefault(int(w), set()).update(
                pid[pw == w].tolist())
        seller = acols[7][avis]
        ats = acols[5][avis]
        aw = ats - ats % window_us
        for w in np.unique(aw):
            ps = persons.get(int(w))
            if ps:
                matches += int(np.isin(seller[aw == w],
                                       np.fromiter(ps, dtype=np.int64)).sum())
    return time.perf_counter() - t0


def _gen_numpy_chunks(kind: str, n_chunks: int, chunk_size: int, cfg=None):
    """Materialize generator output as numpy (host baseline input)."""
    from risingwave_tpu.connectors import NexmarkGenerator
    kwargs = {} if cfg is None else {"cfg": cfg}
    gen = NexmarkGenerator(kind, chunk_size=chunk_size, **kwargs)
    out = []
    for _ in range(n_chunks):
        c = gen.next_chunk()
        cols = [np.asarray(col.data) for col in c.columns]
        out.append((cols, np.asarray(c.vis)))
    return out


def _numpy_q17(part_cols, li_chunks) -> float:
    """Incremental numpy q17: per-part (sum, count) aggregates plus
    affected-part recompute of sum(extendedprice | quantity < 0.2*avg) —
    the work a vectorized CPU engine pays for the same retraction
    semantics (every lineitem shifts its part's threshold, so all rows
    of affected parts re-evaluate)."""
    from risingwave_tpu.common.types import GLOBAL_DICT
    NUM_PARTS = round(TPCH_SF * 200_000)
    t0 = time.perf_counter()
    want_b = GLOBAL_DICT.get_or_insert("Brand#23")
    want_c = GLOBAL_DICT.get_or_insert("MED BOX")
    # p_partkey, p_brand, p_container of part's 9 columns
    pk, pb, pc = part_cols[0], part_cols[3], part_cols[6]
    # part keys are an unbounded serial (only the first NUM_PARTS are
    # ever referenced by lineitems) — size EVERY per-part array by the
    # same bound so the masks line up
    width = max(int(pk.max()), NUM_PARTS) + 1
    ok = np.zeros(width, dtype=bool)
    ok[pk[(pb == want_b) & (pc == want_c)]] = True
    sumq = np.zeros(width, dtype=np.int64)
    cnt = np.zeros(width, dtype=np.int64)
    contrib = np.zeros(width, dtype=np.float64)
    all_pk = np.empty(0, dtype=np.int64)
    all_q = np.empty(0, dtype=np.int64)
    all_ep = np.empty(0, dtype=np.int64)
    answer = 0.0
    for cols, vis in li_chunks:
        # l_partkey, l_quantity, l_extendedprice of lineitem's 16
        lpk, q, ep = cols[1][vis], cols[4][vis], cols[5][vis]
        np.add.at(sumq, lpk, q)
        np.add.at(cnt, lpk, 1)
        all_pk = np.concatenate([all_pk, lpk])
        all_q = np.concatenate([all_q, q])
        all_ep = np.concatenate([all_ep, ep])
        affected = np.unique(lpk)
        thr = 0.2 * sumq / np.maximum(cnt, 1)
        m = np.isin(all_pk, affected)
        spk = all_pk[m]
        keep = all_q[m] < thr[spk]
        contrib[affected] = 0.0
        np.add.at(contrib, spk[keep], all_ep[m][keep].astype(np.float64))
        answer = float(contrib[ok].sum()) / 7.0
    assert answer >= 0.0
    return time.perf_counter() - t0


def _baseline_main(query: str, n_chunks: int, chunk_size: int) -> None:
    """Subprocess entry (JAX_PLATFORMS=cpu): print baseline rows/s."""
    from risingwave_tpu.connectors.nexmark import NexmarkConfig
    if query == "q1":
        chunks = _gen_numpy_chunks("bid", n_chunks, chunk_size)
        dt = _numpy_q1(chunks)
    elif query == "q7":
        cfg = NexmarkConfig(inter_event_us=250)
        chunks = _gen_numpy_chunks("bid", n_chunks, chunk_size, cfg=cfg)
        dt = _numpy_q7(chunks)
    elif query == "q8":
        cfg = NexmarkConfig(inter_event_us=100)
        # rows counted across BOTH sources at the 1:3 person:auction ratio
        pch = _gen_numpy_chunks("person", n_chunks, chunk_size // 4, cfg=cfg)
        ach = _gen_numpy_chunks("auction", n_chunks,
                                3 * (chunk_size // 4), cfg=cfg)
        dt = _numpy_q8(pch, ach)
    elif query == "q17":
        from risingwave_tpu.connectors import TpchGenerator
        g = TpchGenerator("part", chunk_size=1024, scale_factor=TPCH_SF,
                          seed=TPCH_SEED)
        part_cols = [np.asarray(c.data) for c in g.next_chunk().columns]
        gl = TpchGenerator("lineitem", chunk_size=chunk_size,
                           scale_factor=TPCH_SF, seed=TPCH_SEED)
        chunks = []
        for _ in range(n_chunks):
            c = gl.next_chunk()
            chunks.append(([np.asarray(col.data) for col in c.columns],
                           np.asarray(c.vis)))
        dt = _numpy_q17(part_cols, chunks)
    else:
        cfg = NexmarkConfig(inter_event_us=2)
        chunks = _gen_numpy_chunks("bid", n_chunks, chunk_size, cfg=cfg)
        dt = _numpy_q5(chunks)
    print(json.dumps({"baseline_rows_per_sec": n_chunks * chunk_size / dt}),
          flush=True)


# ------------------------------------------------------------------ device

def _DeviceSink(input):
    """Device-resident blackhole (no host readback) — the library's sink
    executor, shared with the SQL-path benches."""
    from risingwave_tpu.stream.sink import DeviceBlackholeSinkExecutor
    return DeviceBlackholeSinkExecutor(input)


async def _measure(coord, gen, sink, progress: dict, measure_s: float,
                   warmup_rounds: int = 2, interval_s: float = 0.5):
    """Warmup (compile), then pace barriers every `interval_s` while the
    source free-runs between them — the reference's execution model
    (barrier_interval_ms=1000, system_param/mod.rs:77; throughput is the
    source-side rows/s counter, latency the barrier histogram). Progress
    lands in `progress` after every round so a deadline abort still
    reports a number."""
    from risingwave_tpu.utils.metrics import D2H_BYTES
    _phase(progress, "warmup_compile")
    t_c0 = time.perf_counter()
    await coord.run_rounds(warmup_rounds)
    progress["compile_s"] = round(time.perf_counter() - t_c0, 1)
    # Drain the device queue before the timer starts: dispatch is async, so
    # without this the measured region would begin with warmup (and compile)
    # work still queued, and end-of-region sync would charge it to the run.
    if sink.last is not None:
        await asyncio.to_thread(sink.last.block_until_ready)
    start_offset = gen.offset
    d2h_bytes0 = D2H_BYTES.value
    _phase(progress, "measure")
    t0 = time.perf_counter()
    rounds = 0
    while True:
        if interval_s:
            await asyncio.sleep(interval_s)
        else:
            await asyncio.sleep(0)
        b = await coord.inject_barrier()
        await coord.wait_collected(b)
        rounds += 1
        dt = time.perf_counter() - t0
        progress["rows"] = gen.offset - start_offset
        progress["seconds"] = dt
        progress["rounds"] = rounds
        progress["barrier_p50_s"] = coord.barrier_latency_percentile(0.5)
        if dt >= measure_s:
            break
    if sink.last is not None:
        sink.last.block_until_ready()
    progress["seconds"] = time.perf_counter() - t0
    # durable-path health numbers (meaningful for q7d; ~0 elsewhere):
    # bytes/s shipped d2h by the persist paths, and how much of the
    # background durable flush was hidden behind compute (100% = the
    # stream never waited on a full in-flight window)
    d2h_bytes = D2H_BYTES.value - d2h_bytes0
    if d2h_bytes:
        progress["d2h_bytes_per_s"] = round(
            d2h_bytes / progress["seconds"], 1)
    overlap = coord.upload_overlap_pct()
    if overlap is not None:
        progress["upload_overlap_pct"] = overlap


async def bench_q1(progress: dict) -> None:
    """q1 VIA SQL (BASELINE config 1): currency-conversion projection.
    The planner supplies the same single-actor source->project->sink
    chain the round-3 hand-built pipeline hard-coded (q1 is
    host-dispatch-bound: large chunks amortize per-program cost)."""
    ddl = [
        "SET streaming_durability = 0",
        "SET streaming_watchdog = 0",
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         "chunk_size=131072)"),
        ("CREATE SINK q1 AS SELECT auction, bidder, "
         "price * 0.908 AS price, date_time FROM bid "
         "WITH (connector='blackhole_device')"),
    ]
    await _bench_sql(progress, ddl, interval_s=0.5)


def _q5_ddl(mesh_devices: int = 0) -> list:
    # mesh variant: smaller chunks (q7d rationale) — the fused shard_map
    # programs compile fresh and the giant-chunk configuration is a
    # single-device dispatch-amortization tactic the fused interval scan
    # already provides
    cs = 32768 if mesh_devices else 131072
    ddl = [
        "SET streaming_durability = 0",
        "SET streaming_watchdog = 0",
        f"SET streaming_agg_capacity = {1 << 20}",
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         f"chunk_size={cs}, inter_event_us=2, emit_watermarks=1)"),
        ("CREATE SINK q5 AS SELECT auction, window_start, count(*) AS n "
         "FROM HOP(bid, date_time, 2000000, 10000000) "
         "GROUP BY auction, window_start "
         "WITH (connector='blackhole_device')"),
    ]
    if mesh_devices:
        # fused mesh fragment (stream/sharded_agg.py): the agg fragment
        # deploys as ONE actor whose exchange + state shard over the
        # device mesh; same SQL, same per-shard capacity total
        ddl.insert(0,
                   f"SET streaming_parallelism_devices = {mesh_devices}")
    return ddl


async def bench_q5(progress: dict) -> None:
    """q5 core VIA SQL (BASELINE config 2): HOP(2s,10s) + count(*)
    GROUP BY (auction, window_start), watermark-cleaned.

    Sizing is driven by CHURN PER EPOCH (watermark cleaning purges
    closed windows at every barrier): at ~250M rows/s and 2us event
    spacing a 0.2s epoch spans ~50 event-seconds => (50+6 slides)*10k
    ~ 560k peak groups — fits 2^20 under the 0.7 threshold with margin
    (round-2 analysis, unchanged)."""
    await _bench_sql(progress, _q5_ddl(), interval_s=0.2)


async def bench_q5_8chip(progress: dict) -> None:
    """q5 on the 8-device mesh (ROADMAP item 2): the whole agg fragment
    — source-side dispatch, hash exchange, sharded hash tables — runs as
    one shard_map program per barrier interval over all 8 chips. Emitted
    as nexmark_q5_rows_per_sec_8chip alongside the per-chip metric."""
    await _bench_sql(progress, _q5_ddl(mesh_devices=8), interval_s=0.2)


async def bench_q5_fused(progress: dict) -> None:
    """q5 as a mesh-resident CHAIN (ROADMAP 3c): the hop-window producer
    stages hollow into preludes of the sharded agg's fused program —
    zero per-chunk host hops per steady barrier interval, attested by
    the mesh_host_round_trips_total counter riding in the result as
    host_hops_per_interval."""
    await _bench_sql(progress, _q5_ddl(mesh_devices=8), interval_s=0.2,
                     track_host_hops=True)


def _q5_topn_ddl() -> list:
    """q5-shaped top-N (ROADMAP item 3 follow-through): per-key counts
    feeding a global ORDER BY n DESC LIMIT 10 in one statement — the agg
    shards over the mesh as usual and the TopN deploys as ONE actor
    whose retractable snapshot-diff store shards over the same 8 devices
    (stream-key shuffle, per-shard local rank, candidate all_gather).
    The group key is auction % 2^16: the retractable store retains every
    live group, so a free-running bench needs a BOUNDED key space (the
    hop-window q5 bounds it by watermark cleaning instead)."""
    return [
        "SET streaming_parallelism_devices = 8",
        "SET streaming_durability = 0",
        "SET streaming_watchdog = 0",
        f"SET streaming_agg_capacity = {1 << 18}",
        f"SET streaming_top_n_capacity = {1 << 17}",
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         "chunk_size=32768, inter_event_us=2, emit_watermarks=1)"),
        ("CREATE SINK q5t AS SELECT auction % 65536 AS a, count(*) AS n "
         "FROM bid GROUP BY auction % 65536 "
         "ORDER BY n DESC LIMIT 10 "
         "WITH (connector='blackhole_device')"),
    ]


async def bench_q5_topn_8chip(progress: dict) -> None:
    """q5-shaped top-N on the 8-device mesh: source -> sharded count
    agg -> sharded retractable TopN, with the projection prelude chain
    hollowed into the fused per-interval programs. Emitted as
    nexmark_q5_topn_rows_per_sec_8chip plus host_hops_per_interval."""
    await _bench_sql(progress, _q5_topn_ddl(), interval_s=0.2,
                     track_host_hops=True)


async def _bench_sql(progress: dict, ddl: list, interval_s: float,
                     measure_s: float = MEASURE_S, store=None,
                     track_host_hops: bool = False) -> None:
    """Run a query expressed as SQL through the Session — the measured
    number IS the system number (VERDICT r3: "the bench path and the SQL
    path must converge"). The sink is connector='blackhole_device' (no
    host readback); sources free-run between paced barriers exactly like
    the hand-built pipelines did."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.stream.sorted_join import SortedJoinExecutor
    from risingwave_tpu.stream.source import SourceExecutor

    _phase(progress, "setup_ddl")
    s = Session(store=store)
    # stash the live session + loop for the deadline autopsy
    # (_one_query_main._bail dumps trace/await-tree/events on abort)
    progress["session"] = s
    progress["loop"] = asyncio.get_running_loop()
    # arm the stuck-barrier watchdog WELL below the phase deadline: a
    # stall self-diagnoses (remaining actors + await tree, on stderr)
    # before the deadline kills the process with only a phase name
    await s.execute("SET barrier_stall_threshold_ms = 15000")
    for stmt in ddl:
        await s.execute(stmt)
    gens, sink, join = [], None, None
    for d in s.catalog.sinks.values():
        for roots in d.deployment.roots.values():
            for root in roots:
                node = root
                while node is not None:
                    if isinstance(node, SourceExecutor):
                        gens.append(node.connector)
                    if isinstance(node, SortedJoinExecutor):
                        join = node
                    node = getattr(node, "input", None)
        sink = d.executor

    class _Gens:
        @property
        def offset(self):
            return sum(g.offset for g in gens)

    if track_host_hops:
        from risingwave_tpu.stream.monitor import mesh_host_round_trips
        h0 = mesh_host_round_trips()
    await _measure(s.coord, _Gens(), sink, progress, measure_s,
                   interval_s=interval_s)
    if track_host_hops:
        # per-chunk host-plane crossings inside registered mesh chains,
        # averaged over the measured barrier intervals (warmup included
        # — the fused steady state is exactly zero either way)
        progress["host_hops_per_interval"] = round(
            (mesh_host_round_trips() - h0)
            / max(progress.get("rounds", 1), 1), 2)
        progress["mesh_chains"] = len(s.coord.mesh_chains)
    # quiesce: stop the sources producing (the stop barrier would
    # otherwise ride behind a growing backlog)
    _phase(progress, "quiesce")
    from risingwave_tpu.stream.message import PauseMutation
    b = await s.coord.inject_barrier(mutation=PauseMutation())
    await s.coord.wait_collected(b)
    _phase(progress, "teardown")
    if join is not None:
        # The post-run fetch of the join's error counters is bounded: a
        # stalled fetch is REPORTED in the result ("unavailable (d2h
        # stall)"), never waited on past the deadline.
        try:
            import jax as _jax
            errs = await asyncio.wait_for(
                asyncio.to_thread(
                    lambda: [int(x) for x in
                             _jax.device_get(join._errs_dev)]),
                timeout=15.0)
            progress["state_errs_checked"] = True
            if any(errs):
                progress["state_errs"] = errs
        except asyncio.TimeoutError:
            progress["state_errs"] = "unavailable (d2h stall)"
    # NO drop_all here BY DESIGN: executor teardown performs synchronous
    # device syncs that block the event loop in the post-run stalled-d2h
    # regime; this subprocess is isolated, so the paused dataflow is
    # reclaimed by process exit. clean_exit=true means the run finished
    # and exited on its own (vs. being killed by the deadline).
    progress["teardown"] = "skipped by design (isolated subprocess)"
    # signal completion for the emit-and-exit watcher: asyncio.run() would
    # now cancel the actor tasks, whose unwind blocks on device syncs in
    # the stalled-d2h regime — the watcher exits the process instead
    progress["clean_exit"] = True
    progress["pipeline_done"] = True
    await asyncio.Event().wait()      # parked until process exit


W = 10_000_000          # 10s tumble window, microseconds


def _q7_ddl(mesh_devices: int = 0) -> list:
    # mesh variant: smaller chunks, same reasoning as _q5_ddl
    cs = 32768 if mesh_devices else 131072
    ddl = [
        "SET streaming_durability = 0",
        "SET streaming_watchdog = 0",
        f"SET streaming_join_capacity = {1 << 19}",
        "SET streaming_join_match_factor = 2",
        f"SET streaming_agg_capacity = {1 << 13}",
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         f"chunk_size={cs}, inter_event_us=250, emit_watermarks=1, "
         f"watermark_lag_us={2 * W})"),
        ("CREATE SINK q7 AS "
         "SELECT B.auction, B.price, B.bidder, B.date_time "
         "FROM bid B JOIN ("
         "  SELECT max(price) AS maxprice, window_end "
         f"  FROM TUMBLE(bid, date_time, {W}) GROUP BY window_end) B1 "
         "ON B.price = B1.maxprice "
         f"AND B.date_time > B1.window_end - {W} "
         "AND B.date_time <= B1.window_end "
         "WITH (connector='blackhole_device')"),
    ]
    if mesh_devices:
        ddl.insert(0,
                   f"SET streaming_parallelism_devices = {mesh_devices}")
    return ddl


async def bench_q7(progress: dict) -> None:
    """q7 VIA SQL: tumble-window MAX(price) joined back to the bids at the
    max price (BASELINE config 3, reference workload q7.sql). The planner
    supplies what the hand-built round-3 pipeline hard-coded: ONE shared
    bid source (source sharing), sorted-merge join with per-chunk band
    eviction derived from the interval ON-condition, append-only running
    MAX, and input pruning below the join.

    SET streaming_durability=0 keeps state device-resident (the
    reference's in-memory state backend) — same durability class as the
    numpy baseline; the durable path is covered by the crash-recovery
    test suite."""
    await _bench_sql(progress, _q7_ddl(), interval_s=0.05)


async def bench_q7_8chip(progress: dict) -> None:
    """q7 on the 8-device mesh: the sharded agg AND the sharded join
    deploy as fused mesh fragments (one shard_map program per interval
    each; in-mesh all_to_all exchange). Emitted as
    nexmark_q7_rows_per_sec_8chip alongside the per-chip metric."""
    await _bench_sql(progress, _q7_ddl(mesh_devices=8), interval_s=0.05)


async def bench_q7_fused(progress: dict) -> None:
    """q7 as mesh-resident CHAINS: eligible producer fragments hollow
    into the sharded consumers' fused programs (agg-side auto-fusion;
    the join side keeps its per-fragment plane). host_hops_per_interval
    in the result counts any per-chunk host-plane crossings left inside
    registered chains — zero in the fused steady state."""
    await _bench_sql(progress, _q7_ddl(mesh_devices=8), interval_s=0.05,
                     track_host_hops=True)


async def bench_q7d(progress: dict) -> None:
    """q7 with streaming_durability = 1 over the REAL durable backend
    (Hummock LSM on a local-fs object store): quantifies the flush tax
    against the volatile q7 number (VERDICT r4 #3 — the reference never
    runs volatile: state_table.rs:1036 commits at every checkpoint).
    Same SQL, same pacing; the only deltas are durability and the
    backend. Every stateful executor snapshot-diffs its device state,
    fetches the changed rows, encodes them (native C++ codec), and
    commits them into the LSM at each barrier."""
    import glob
    import shutil
    import tempfile
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    # this subprocess exits via os._exit (no atexit): bound the leak by
    # removing previous runs' state dirs instead
    for old in glob.glob(os.path.join(tempfile.gettempdir(), "bench_q7d_*")):
        shutil.rmtree(old, ignore_errors=True)
    store = HummockStateStore(
        LocalFsObjectStore(tempfile.mkdtemp(prefix="bench_q7d_")))
    ddl = [
        "SET streaming_durability = 1",
        "SET streaming_watchdog = 0",
        # checkpoint pipeline: barriers seal and move on; SST build/upload
        # + the d2h persist fetches run on the background uploader, up to
        # 2 epochs behind — the barrier p50 below excludes the flush
        "SET checkpoint_max_inflight = 2",
        f"SET streaming_join_capacity = {1 << 18}",
        "SET streaming_join_match_factor = 2",
        f"SET streaming_agg_capacity = {1 << 13}",
        # smaller chunks than volatile q7: the durable programs compile
        # fresh (diff/persist paths), and the flush tax measurement does
        # not need the giant-chunk configuration
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         f"chunk_size=32768, inter_event_us=250, emit_watermarks=1, "
         f"watermark_lag_us={2 * W})"),
        ("CREATE SINK q7 AS "
         "SELECT B.auction, B.price, B.bidder, B.date_time "
         "FROM bid B JOIN ("
         "  SELECT max(price) AS maxprice, window_end "
         f"  FROM TUMBLE(bid, date_time, {W}) GROUP BY window_end) B1 "
         "ON B.price = B1.maxprice "
         f"AND B.date_time > B1.window_end - {W} "
         "AND B.date_time <= B1.window_end "
         "WITH (connector='blackhole_device')"),
    ]
    progress["note"] = (
        "durable q7 with the PIPELINED checkpoint (checkpoint_max_"
        "inflight=2): barriers complete at seal; the d2h persist fetches "
        "+ SST build/upload/commit run on the background uploader, so "
        "upload_overlap_pct reports how much of the flush hid behind "
        "compute and d2h_bytes_per_s the persist path's d2h rate.")
    await _bench_sql(progress, ddl, interval_s=0.05, store=store)


async def bench_q7_kill(progress: dict) -> None:
    """Recovery-time SLO (ROADMAP item 5 + the recovery-radius PR): the
    durable q7 shape run as a MATERIALIZED VIEW, with a victim killed
    mid-measure through the deterministic fault injector
    (utils/faults.py). The BENCH_Q7_KILL_VICTIM knob picks the radius
    (registered as the q7_kill_interior / q7_kill_worker variants):

      terminal (default)  the MV's materialize actor -> scope=fragment
                          (one actor rebuilt from the committed epoch)
      interior            an interior join/agg actor -> scope=cone
                          (the victim + its downstream consumers
                          rebuild; upstream keeps device state)
      worker              a 2-worker cluster run with one compute-node
                          PROCESS killed -> scope=worker (its actors
                          re-place onto the survivor, whose store stays
                          open at the committed manifest)

    Emits `recovery_ms` (the SLO number), `recovery_scope`/
    `rebuilt_actors` (proof the radius stayed contained), and
    `post_recovery_rows_per_sec` (the pipeline keeps earning after the
    fault)."""
    victim_kind = os.environ.get("BENCH_Q7_KILL_VICTIM", "terminal")
    if victim_kind == "worker":
        await _bench_q7_kill_worker(progress)
        return
    import glob
    import shutil
    import tempfile
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    from risingwave_tpu.stream.source import SourceExecutor
    for old in glob.glob(os.path.join(tempfile.gettempdir(),
                                      "bench_q7k_*")):
        shutil.rmtree(old, ignore_errors=True)
    store = HummockStateStore(
        LocalFsObjectStore(tempfile.mkdtemp(prefix="bench_q7k_")))
    _phase(progress, "setup_ddl")
    s = Session(store=store)
    # stash the live session + loop for the deadline autopsy
    # (_one_query_main._bail dumps trace/await-tree/events on abort)
    progress["session"] = s
    progress["loop"] = asyncio.get_running_loop()
    await s.execute("SET barrier_stall_threshold_ms = 15000")
    for stmt in [
        "SET streaming_durability = 1",
        "SET streaming_watchdog = 0",
        "SET checkpoint_max_inflight = 2",
        f"SET streaming_join_capacity = {1 << 18}",
        "SET streaming_join_match_factor = 2",
        f"SET streaming_agg_capacity = {1 << 13}",
        # smaller chunks + a per-barrier rate limit, unlike q7d: the
        # headline here is recovery_ms, not rows/s, and the bound keeps
        # the crash-window backlog (which the post-recovery rounds must
        # chew through) finite even on an oversubscribed host
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         f"chunk_size=8192, inter_event_us=250, emit_watermarks=1, "
         f"watermark_lag_us={2 * W}, rate_limit=65536)"),
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
    gens = []
    mv = s.catalog.mvs["q7"]
    for roots in mv.deployment.roots.values():
        for root in roots:
            node = root
            while node is not None:
                if isinstance(node, SourceExecutor):
                    gens.append(node.connector)
                node = getattr(node, "input", None)
    _phase(progress, "warmup_compile")
    t_c0 = time.perf_counter()
    await s.tick(2)
    progress["compile_s"] = round(time.perf_counter() - t_c0, 1)
    if victim_kind == "interior":
        # an interior fragment (has downstream consumers, no source):
        # its crash exercises the downstream-cone radius
        from risingwave_tpu.frontend.session import _fragment_node_kinds
        dep = mv.deployment
        graph = dep.rebuild_info["graph"]
        fid = next(f for f in graph.topo_order()
                   if dep.fragment_consumers.get(f)
                   and not any(n.kind == "nexmark_source"
                               for n in _fragment_node_kinds(
                                   graph.fragments[f])))
        victim = dep.frag_actor_ids[fid][0]
    else:
        victim = mv.deployment.frag_actor_ids[mv.mv_fragment][0]
    start_offset = sum(g.offset for g in gens)
    _phase(progress, "measure")
    t0 = time.perf_counter()
    killed = False
    t_post = None
    post_offset = 0
    rounds = 0
    while True:
        await asyncio.sleep(0.05)
        # tick-driven rounds: tick owns failure classification + recovery
        await s.tick(1, max_recoveries=3)
        rounds += 1
        dt = time.perf_counter() - t0
        progress["rows"] = sum(g.offset for g in gens) - start_offset
        progress["seconds"] = dt
        progress["barrier_p50_s"] = s.coord.barrier_latency_percentile(0.5)
        if not killed:
            # arm after the first measured round: the NEXT barrier kills
            # the victim, whatever the per-round wall time is on this box
            killed = True
            await s.execute(
                f"SET fault_injection = 'actor_crash:actor={victim},at=1'")
        elif s.last_recovery is not None and t_post is None:
            t_post = time.perf_counter()
            rounds_at_post = rounds
            progress["recovery_ms"] = round(
                s.last_recovery["duration_s"] * 1e3, 2)
            progress["recovery_scope"] = s.last_recovery["scope"]
            progress["rebuilt_actors"] = s.last_recovery["actors"]
        elif t_post is not None and rounds == rounds_at_post + 1 \
                and post_offset == 0:
            # the first post-recovery round chews the crash-window
            # backlog (the source is backpressured through it, so the
            # generator offset barely moves); the steady-state post-
            # recovery rate is measured from the NEXT round on
            t_post = time.perf_counter()
            post_offset = sum(g.offset for g in gens)
        # the region must contain the fault, its recovery, the backlog
        # round, and one steady post-recovery round (slow-barrier boxes
        # would otherwise exit before the injected crash even fires);
        # 5x the budget bounds a recovery that never lands
        if dt >= MEASURE_S and (
                (t_post is not None and post_offset
                 and rounds >= rounds_at_post + 2)
                or dt >= 5 * MEASURE_S):
            break
    await s.execute("SET fault_injection = ''")
    if post_offset and time.perf_counter() > t_post:
        progress["post_recovery_rows_per_sec"] = round(
            (sum(g.offset for g in gens) - post_offset)
            / (time.perf_counter() - t_post), 1)
    progress["recoveries"] = s.recoveries
    progress["seconds"] = time.perf_counter() - t0
    _phase(progress, "quiesce")
    from risingwave_tpu.stream.message import PauseMutation
    b = await s.coord.inject_barrier(mutation=PauseMutation())
    await s.coord.wait_collected(b)
    _phase(progress, "teardown")
    progress["teardown"] = "skipped by design (isolated subprocess)"
    progress["clean_exit"] = True
    progress["pipeline_done"] = True
    await asyncio.Event().wait()


async def _bench_q7_kill_worker(progress: dict) -> None:
    """q7_kill with victim=worker: the durable q7 MV deployed over a
    2-worker cluster, one compute-node PROCESS killed mid-measure. The
    per-worker recovery radius re-places only the dead node's actors
    (plus their downstream closure) onto the survivor — whose store
    stays open at the committed manifest — and emits recovery_scope=
    worker with the recovery_ms SLO for that radius."""
    import glob
    import shutil
    import socket
    import subprocess
    import tempfile
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    for old in glob.glob(os.path.join(tempfile.gettempdir(),
                                      "bench_q7kw_*")):
        shutil.rmtree(old, ignore_errors=True)
    tmp = tempfile.mkdtemp(prefix="bench_q7kw_")
    _phase(progress, "setup_ddl")
    ports = []
    for _ in range(2):
        sk = socket.socket()
        sk.bind(("127.0.0.1", 0))
        ports.append(sk.getsockname()[1])
        sk.close()
    procs = []
    env = dict(os.environ)
    for port in ports:
        p = subprocess.Popen(
            [sys.executable, "-m", "risingwave_tpu.worker", str(port)],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port),
                                         timeout=1).close()
                break
            except OSError:
                time.sleep(0.2)
        procs.append(p)
    s = Session(store=HummockStateStore(
        LocalFsObjectStore(os.path.join(tmp, "c"))))
    # stash the live session + loop for the deadline autopsy
    # (_one_query_main._bail dumps trace/await-tree/events on abort)
    progress["session"] = s
    progress["loop"] = asyncio.get_running_loop()
    await s.execute("SET barrier_stall_threshold_ms = 15000")
    await s.execute(
        "SET cluster = '" + ",".join(f"127.0.0.1:{p}"
                                     for p in ports) + "'")
    for stmt in [
        f"SET streaming_join_capacity = {1 << 18}",
        "SET streaming_join_match_factor = 2",
        f"SET streaming_agg_capacity = {1 << 13}",
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         f"chunk_size=4096, splits=2, inter_event_us=250, "
         f"emit_watermarks=1, watermark_lag_us={2 * W}, "
         "rate_limit=65536)"),
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
    _phase(progress, "warmup_compile")
    t_c0 = time.perf_counter()
    await s.tick(2)
    progress["compile_s"] = round(time.perf_counter() - t_c0, 1)
    _phase(progress, "measure")
    t0 = time.perf_counter()
    killed = False
    t_post = None
    rounds = rounds_at_post = 0
    while True:
        await asyncio.sleep(0.05)
        await s.tick(1, max_recoveries=4)
        rounds += 1
        dt = time.perf_counter() - t0
        progress["seconds"] = dt
        progress["barrier_p50_s"] = s.coord.barrier_latency_percentile(0.5)
        if not killed:
            killed = True
            procs[1].kill()
        elif s.last_recovery is not None and t_post is None:
            t_post = time.perf_counter()
            rounds_at_post = rounds
            progress["recovery_ms"] = round(
                s.last_recovery["duration_s"] * 1e3, 2)
            progress["recovery_scope"] = s.last_recovery["scope"]
            progress["rebuilt_actors"] = s.last_recovery["actors"]
        if dt >= MEASURE_S and (
                (t_post is not None and rounds >= rounds_at_post + 2)
                or dt >= 5 * MEASURE_S):
            break
    progress["recoveries"] = s.recoveries
    # rows stay 0 on purpose: this variant's headline is recovery_ms at
    # scope=worker, not throughput (the sources live in the workers)
    progress["seconds"] = time.perf_counter() - t0
    _phase(progress, "teardown")
    for p in procs:
        if p.poll() is None:
            p.terminate()
    progress["teardown"] = "skipped by design (isolated subprocess)"
    progress["clean_exit"] = True
    progress["pipeline_done"] = True
    await asyncio.Event().wait()


async def bench_q8(progress: dict) -> None:
    """q8 VIA SQL: persons joined with auctions they opened in the same
    10s tumble window (BASELINE config 4, reference workload q8.sql).
    The planner derives pair-min watermark eviction on the
    (window_start, window_start) key pair — safe even when one side's
    watermark runs ahead, unlike round 3's own-side eviction which needed
    the 1:3 chunk alignment for correctness (here it is only a state-size
    optimization)."""
    ddl = [
        "SET streaming_durability = 0",
        "SET streaming_watchdog = 0",
        f"SET streaming_join_capacity = {1 << 19}",
        "SET streaming_join_match_factor = 2",
        ("CREATE SOURCE person WITH (connector='nexmark', table='person', primary_key='id', "
         "chunk_size=98304, inter_event_us=100, emit_watermarks=1)"),
        ("CREATE SOURCE auction WITH (connector='nexmark', primary_key='id', "
         "table='auction', chunk_size=294912, inter_event_us=100, "
         "emit_watermarks=1)"),
        ("CREATE SINK q8 AS "
         "SELECT P.id, P.window_start "
         f"FROM TUMBLE(person, date_time, {W}) P "
         f"JOIN TUMBLE(auction, date_time, {W}) A "
         "ON P.id = A.seller AND P.window_start = A.window_start "
         "WITH (connector='blackhole_device')"),
    ]
    await _bench_sql(progress, ddl, interval_s=0.05)


async def bench_q17(progress: dict) -> None:
    """TPC-H q17 VIA SQL (BASELINE config 5): lineitem x part x
    (0.2*avg per part), global sum. The planner lowers this shape to the
    fused SnapshotJoinAggExecutor (binder.py _try_snapshot_join_agg):
    inputs accumulate in dense device stores and ONE jitted O(n) program
    per barrier recomputes thresholds + the filtered sum and emits the
    one-row diff — no retraction storms (the changelog plan re-emitted
    every affected part's rows per chunk, measured 0.001x baseline in
    round 4). The numpy baseline pays the same semantics incrementally
    (affected-part recompute per chunk). State grows with the input (no
    watermark exists to clean it), so the metric is wall time over a
    FIXED QUOTA of rows, 8 chunks per barrier.

    The timed run egresses into the device blackhole (zero d2h).
    Correctness of this exact SQL incl. crash recovery is owned by
    tests/test_tpch_q17.py + tests/test_snapshot_join_agg.py; error
    counters are fetched (bounded) after the run."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.stream.snapshot_join_agg import \
        SnapshotJoinAggExecutor
    from risingwave_tpu.stream.source import SourceExecutor

    QUOTA_CHUNKS = 64
    CS = 8192
    _phase(progress, "setup_ddl")
    s = Session()
    # stash the live session + loop for the deadline autopsy
    # (_one_query_main._bail dumps trace/await-tree/events on abort)
    progress["session"] = s
    progress["loop"] = asyncio.get_running_loop()
    await s.execute("SET barrier_stall_threshold_ms = 15000")
    for stmt in [
        "SET streaming_durability = 0",
        "SET streaming_watchdog = 0",
        f"SET streaming_join_capacity = {1 << 20}",
        f"SET streaming_agg_capacity = {1 << 16}",
        (f"CREATE SOURCE part WITH (connector='tpch', table='part', "
         f"scale_factor={TPCH_SF}, seed={TPCH_SEED}, "
         "chunk_size=1024, rate_limit=1024, primary_key='p_partkey')"),
        (f"CREATE SOURCE lineitem WITH (connector='tpch', "
         f"scale_factor={TPCH_SF}, seed={TPCH_SEED}, "
         f"table='lineitem', chunk_size={CS}, rate_limit={16 * CS})"),
        ("CREATE SINK q17 AS "
         "SELECT sum(L.l_extendedprice) / 7.0 AS avg_yearly "
         "FROM lineitem L "
         "JOIN part P ON P.p_partkey = L.l_partkey "
         "JOIN (SELECT l_partkey AS agg_partkey, "
         "             0.2 * avg(l_quantity) AS avg_quantity "
         "      FROM lineitem GROUP BY l_partkey) A "
         "  ON A.agg_partkey = L.l_partkey "
         " AND L.l_quantity < A.avg_quantity "
         "WHERE P.p_brand = 'Brand#23' AND P.p_container = 'MED BOX' "
         "WITH (connector='blackhole_device')"),
    ]:
        await s.execute(stmt)
    gens, fused = [], []
    for d in s.catalog.sinks.values():
        for roots in d.deployment.roots.values():
            for root in roots:
                node = root
                while node is not None:
                    if isinstance(node, SourceExecutor):
                        gens.append(node.connector)
                    if isinstance(node, SnapshotJoinAggExecutor):
                        fused.append(node)
                    node = getattr(node, "input", None)
    assert fused, "q17 did not lower to the fused snapshot executor"
    li = next(g for g in gens if g.table == "lineitem")
    _phase(progress, "warmup_compile")
    t_c0 = time.perf_counter()
    await s.coord.run_rounds(1)
    progress["compile_s"] = round(time.perf_counter() - t_c0, 1)
    base_off = li.offset      # warmup rows are excluded from the metric
    _phase(progress, "measure")
    t0 = time.perf_counter()
    rounds = 0
    while li.offset - base_off < QUOTA_CHUNKS * CS:
        b = await s.coord.inject_barrier()
        await s.coord.wait_collected(b)
        rounds += 1
        # lineitem rows only — the numpy baseline's denominator excludes
        # the part preload, so the ratio must too
        progress["rows"] = li.offset - base_off
        progress["rounds"] = rounds
        progress["barrier_p50_s"] = s.coord.barrier_latency_percentile(0.5)
    progress["seconds"] = time.perf_counter() - t0
    # Quiesce BEFORE the error-counter fetch (root cause of the r05/r06
    # q17 "Array has been deleted with shape=int32[3]" note): without a
    # Pause, the sources keep free-running after the measured region, the
    # event loop keeps appending — and every `_append_fact` DONATES the
    # executor's `_errs` buffer. The worker thread below would grab
    # `j._errs` and lose the race: jax deletes the donated array before
    # `np.asarray` materializes it. After the Pause barrier collects, no
    # chunk (hence no donation) is in flight, so the refs are stable.
    _phase(progress, "quiesce")
    from risingwave_tpu.stream.message import PauseMutation
    b = await s.coord.inject_barrier(mutation=PauseMutation())
    await s.coord.wait_collected(b)
    _phase(progress, "teardown")
    errs_refs = [j._errs for j in fused]
    try:
        errs = await asyncio.wait_for(
            asyncio.to_thread(lambda: [
                int(x) for a in errs_refs for x in np.asarray(a)]),
            timeout=15.0)
        progress["state_errs_checked"] = True
        if any(errs):
            progress["state_errs"] = errs
    except asyncio.TimeoutError:
        progress["state_errs"] = "unavailable (d2h stall)"
    progress["note"] = (
        "fused snapshot recompute (SnapshotJoinAggExecutor): per "
        "barrier one O(n) jitted program over the dense stores, no "
        "retraction storms; the numpy baseline pays the same semantics "
        "as incremental affected-part recompute per chunk.")
    progress["clean_exit"] = True
    progress["pipeline_done"] = True
    await asyncio.Event().wait()


async def bench_broker_ingest(progress: dict) -> None:
    """External-ingress bench (OPT-IN: `python bench.py broker_ingest`;
    not in the default round — the broker path is host-bound by design
    and CI already bounds it at 3x of the datagen path in
    scripts/broker_profile.py). An in-process broker is preloaded with
    JSON records; the measured number is broker-source -> sink ingest
    rows/s through the ordinary barrier loop."""
    import json as _json
    import tempfile
    from risingwave_tpu.broker import Broker, register_inproc
    tmp = tempfile.mkdtemp(prefix="bench_broker_")
    broker = Broker(tmp, fsync=False)
    register_inproc("bench", broker)
    broker.create_topic("ev", 1)
    n = 400_000
    recs = [_json.dumps({"k": i, "v": i * 3}).encode() for i in range(n)]
    for i in range(0, n, 16384):
        broker.append("ev", 0, recs[i:i + 16384])
    ddl = [
        "SET streaming_durability = 0",
        "SET streaming_watchdog = 0",
        ("CREATE SOURCE ev WITH (connector='broker', topic='ev', "
         "brokers='inproc://bench', columns='k int64, v int64', "
         "chunk_size=4096, discovery_interval_ms=0, append_only=1)"),
        ("CREATE SINK bi AS SELECT k, v FROM ev "
         "WITH (connector='blackhole_device')"),
    ]
    await _bench_sql(progress, ddl, interval_s=0.2)


def _q7_kill_victim(victim: str):
    """Registered q7_kill variants: same harness, different recovery
    radius (BENCH_Q7_KILL_VICTIM rides the env into the child)."""
    async def run(progress: dict) -> None:
        os.environ["BENCH_Q7_KILL_VICTIM"] = victim
        try:
            await bench_q7_kill(progress)
        finally:
            os.environ.pop("BENCH_Q7_KILL_VICTIM", None)
    return run


QUERIES = {"q1": bench_q1, "q5": bench_q5, "q7": bench_q7,
           "q8": bench_q8, "q17": bench_q17, "q7d": bench_q7d,
           "q7_kill": bench_q7_kill,
           "q7_kill_interior": _q7_kill_victim("interior"),
           "q7_kill_worker": _q7_kill_victim("worker"),
           "q5_8chip": bench_q5_8chip, "q7_8chip": bench_q7_8chip,
           "q5_fused": bench_q5_fused, "q7_fused": bench_q7_fused,
           "q5_topn_8chip": bench_q5_topn_8chip,
           "broker_ingest": bench_broker_ingest}
NORTH_STAR = ("q7", "q8")


def _query_result(query: str, progress: dict, note: str = "") -> dict:
    rows = progress.get("rows", 0)
    secs = progress.get("seconds", 0.0)
    rps = rows / secs if secs > 0 else 0.0
    base = progress.get("baseline_rows_per_sec")
    out = {
        "rows_per_sec": round(rps, 1),
        "vs_baseline": round(rps / base, 3) if base else None,
        "barrier_p50_s": round(progress.get("barrier_p50_s", 0.0), 6),
        "rows": rows,
        "seconds": round(secs, 3),
        "compile_s": progress.get("compile_s"),
        # the device the number was taken on (None: died before jax init)
        "device": progress.get("device"),
    }
    if base:
        out["baseline_rows_per_sec"] = round(base, 1)
    for k in ("d2h_bytes_per_s", "upload_overlap_pct", "recovery_ms",
              "recovery_scope", "rebuilt_actors", "recoveries",
              "post_recovery_rows_per_sec", "host_hops_per_interval",
              "mesh_chains"):
        if k in progress:
            out[k] = progress[k]
    if progress.get("state_errs"):
        out["state_errs"] = progress["state_errs"]
    if "clean_exit" in progress:
        out["clean_exit"] = progress["clean_exit"]
    if progress.get("note") and not note:
        note = progress["note"]
    if note:
        out["note"] = note
    return out


def _one_query_main(query: str) -> None:
    """Subprocess entry: run ONE query, print JSON result line(s).

    The measured region ends long before teardown does — stop barriers and
    the final error-counter fetch block on the device after a long run. A
    watcher thread prints a PROVISIONAL
    line as soon as the measurement lands; the final line (with state_errs
    if any) overwrites it when teardown completes. The parent takes the
    LAST line, so a teardown hang degrades the note, never the number."""
    progress: dict = {}
    note = ""
    budget = (float(sys.argv[3]) if len(sys.argv) > 3
              else QUERY_BUDGET_S.get(query, 90.0))
    done = threading.Event()
    emit_mu = threading.Lock()
    finals = {"done": False}

    def _emit(note_, final=False):
        # the parent records the LAST line: once the final line (which may
        # carry state_errs) is out, a late provisional print must not
        # follow it
        with emit_mu:
            if finals["done"] and not final:
                return
            if final:
                finals["done"] = True
            print(json.dumps({"query": query,
                              **_query_result(query, progress, note_)}),
                  flush=True)

    def _phase_note() -> str:
        """WHERE the run is stuck, for the abort note: the active phase
        and how long it has been in it (the r05 post-mortem's missing
        attribution)."""
        ph = progress.get("phase")
        if not ph:
            return "before setup (import/jax init)"
        dt = time.perf_counter() - progress.get("phase_t0", 0.0)
        hist = ">".join(progress.get("phase_history", []))
        return f"stuck in phase {ph!r} for {dt:.1f}s (path: {hist})"

    async def _autopsy_report(s) -> str:
        # runs ON the session's loop: the stitched epoch trace + the
        # local await tree, plus every live worker's tree in cluster
        # mode — the same evidence the stuck-barrier watchdog prints
        from risingwave_tpu.utils.trace import \
            format_stuck_barrier_report
        wr = None
        if getattr(s, "cluster", None) is not None:
            try:
                wr = await asyncio.wait_for(s.cluster.dump_tasks_all(),
                                            5)
            except Exception as e:  # noqa: BLE001
                wr = {0: f"(worker pull failed: {e!r})"}
        return format_stuck_barrier_report(s.coord, wr)

    def _autopsy():
        """Deadline-abort post-mortem to stderr: distributed trace +
        merged await tree + event-log tail. Runs on the watcher THREAD;
        a wedged loop degrades to ring-only evidence, never a hang."""
        s = progress.get("session")
        if s is None:
            return
        print(f"== bench autopsy ({query}) ==", file=sys.stderr)
        loop = progress.get("loop")
        try:
            if loop is not None and loop.is_running():
                fut = asyncio.run_coroutine_threadsafe(
                    _autopsy_report(s), loop)
                print(fut.result(timeout=8), file=sys.stderr)
            else:
                from risingwave_tpu.utils.trace import \
                    format_stuck_barrier_report
                print(format_stuck_barrier_report(s.coord),
                      file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — evidence is best-effort
            print(f"(trace dump failed: {e!r})", file=sys.stderr)
        try:
            recs = s.event_log.records(limit=50)
            print(f"-- last {len(recs)} event-log records --",
                  file=sys.stderr)
            for r in recs:
                print(json.dumps(r), file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            print(f"(event log dump failed: {e!r})", file=sys.stderr)
        try:
            # barrier-paced history of the stall-relevant series: the
            # last K samples show WHICH resource was moving (or pinned)
            # when the deadline hit — queue depths, inflight ckpts,
            # source lag, HBM state bytes
            hist = getattr(s, "metrics_history", None) \
                or getattr(s.coord, "metrics_history", None)
            if hist is not None and len(hist):
                print("-- metrics history tail (stall series) --",
                      file=sys.stderr)
                print(hist.dump_tail(), file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            print(f"(metrics history dump failed: {e!r})",
                  file=sys.stderr)
        sys.stderr.flush()

    def _bail(reason: str = ""):
        # no-op once the clean final line is out (ADVICE r3 #5: a late
        # timer must not relabel a successful run as abandoned)
        if finals["done"]:
            return
        progress["clean_exit"] = False
        try:
            _autopsy()
        except Exception:  # noqa: BLE001 — never block the abort line
            pass
        _emit((reason or f"hard deadline {budget}s") + "; "
              + _phase_note(), final=True)
        os._exit(3)      # the partial line is out; the abort is NOT rc 0

    killer = threading.Timer(budget, _bail)
    killer.daemon = True
    killer.start()
    timers = [killer]

    def _watcher():
        provisional = False
        while not done.wait(0.5):
            # per-phase deadline: a stalled phase fails LOUDLY with its
            # name, long before the global budget burns down
            ph = progress.get("phase")
            if ph in PHASE_FRACTION and not progress.get("pipeline_done"):
                limit = PHASE_FRACTION[ph] * budget
                if time.perf_counter() - progress.get("phase_t0",
                                                      0.0) > limit:
                    _bail(f"phase {ph!r} exceeded its "
                          f"{limit:.0f}s deadline")
            if progress.get("pipeline_done"):
                # the pipeline finished and parked: emit the final line
                # and exit without unwinding the asyncio loop (actor
                # cancellation blocks on device syncs post-run)
                for t in timers:
                    t.cancel()
                _emit(note, final=True)
                os._exit(0)
            if (not provisional and progress.get("rows")
                    and progress.get("seconds", 0.0) >= MEASURE_S):
                provisional = True
                _emit("provisional (teardown pending)")
                # the number is recorded; don't let a stalled teardown
                # (blocking d2h) consume the whole budget
                t2 = threading.Timer(35.0, _bail)
                t2.daemon = True
                t2.start()
                timers.append(t2)

    w = threading.Thread(target=_watcher, daemon=True)
    w.start()
    try:
        from risingwave_tpu.utils.compile_cache import \
            enable_persistent_cache
        enable_persistent_cache()
        import jax
        devs = jax.devices()
        progress["device"] = {"platform": devs[0].platform,
                              "kind": devs[0].device_kind,
                              "count": len(devs)}
        asyncio.run(QUERIES[query](progress))
        progress.setdefault("clean_exit", True)
    except Exception as e:  # noqa: BLE001 — a number beats a stack trace
        # ... but the raise SITE costs nothing and names the culprit
        # (the r06 q17 "Array has been deleted" hunt burned a round on a
        # note with no frame)
        import traceback as _tb
        frames = [f for f in _tb.extract_tb(e.__traceback__)
                  if "risingwave_tpu" in (f.filename or "")
                  or "bench.py" in (f.filename or "")]
        at = (f" @ {os.path.basename(frames[-1].filename)}:"
              f"{frames[-1].lineno} {frames[-1].name}" if frames else "")
        note = f"error: {type(e).__name__}: {e}{at}"
        progress["clean_exit"] = False
    for t in timers:
        t.cancel()
    done.set()
    _emit(note, final=True)
    os._exit(0 if progress.get("clean_exit") else 1)


def _probe_device_init(timeout_s: float = DEVICE_PROBE_TIMEOUT_S):
    """Deadline-bounded device-init AND dispatch probe in a SUBPROCESS.

    `jax.devices()` on a sick device can hang indefinitely; probing
    in-process would hang the orchestrator itself (and take the chip the
    query children need). The probe child
    inherits the bench environment (same backend the queries will get).
    Returns (ok, detail) — on stall/failure the caller emits
    `device_init_stall: true` loudly instead of letting the first query
    burn its whole budget on init and record 0.0 rows/s.

    Enumeration alone is NOT health: the probe exercises the full round
    trip the queries depend on — compile a trivial jitted program,
    dispatch it, and fetch the scalar back (d2h). A device that
    enumerates but cannot dispatch or read back fails HERE, attributed,
    before any query is charged for it. The probe also reports
    platform / device_kind / count, which every query result carries.
    """
    src = ("import jax, jax.numpy as jnp; ds = jax.devices(); "
           "y = jax.jit(lambda x: (x * 2).sum())(jnp.arange(64)); "
           "v = int(y); assert v == 4032, v; "
           "print('DEVICES', len(ds), ds[0].platform, "
           "repr(ds[0].device_kind), 'dispatch-ok')")
    try:
        p = subprocess.run([sys.executable, "-c", src],
                           capture_output=True, text=True,
                           timeout=timeout_s,
                           cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return False, (f"jax.devices() did not return within {timeout_s}s "
                       f"(stalled device init)")
    if p.returncode != 0:
        tail = (p.stderr or "").strip().splitlines()[-1:] or [""]
        return False, f"device init failed (rc={p.returncode}): {tail[0][:200]}"
    return True, (p.stdout or "").strip()


def _emit_combined(results: dict, note: str = "",
                   extra: dict = None) -> None:
    """ONE JSON line: headline = worst north-star query."""
    headline_q = None
    headline = None
    for q in NORTH_STAR:
        r = results.get(q)
        if r is None:
            continue
        vb = r.get("vs_baseline")
        key = vb if vb is not None else -1.0
        if headline is None or key < (headline.get("vs_baseline") or -1.0):
            headline, headline_q = r, q
    if headline is None and results:
        headline_q = next(iter(results))
        headline = results[headline_q]
    out = {
        "metric": (f"nexmark_{headline_q}_rows_per_sec_per_chip"
                   if headline_q else "nexmark_rows_per_sec_per_chip"),
        "value": (headline or {}).get("rows_per_sec", 0.0),
        "unit": "rows/s",
        "vs_baseline": (headline or {}).get("vs_baseline"),
        "barrier_p50_s": (headline or {}).get("barrier_p50_s", 0.0),
        "rows": (headline or {}).get("rows", 0),
        "seconds": (headline or {}).get("seconds", 0.0),
        "queries": results,
    }
    # mesh-parallel numbers ride alongside the per-chip headline when the
    # 8chip variants ran (>= 8 devices visible at probe time)
    for q in ("q5", "q7"):
        r8 = results.get(f"{q}_8chip")
        if r8 and r8.get("rows_per_sec"):
            out[f"nexmark_{q}_rows_per_sec_8chip"] = r8["rows_per_sec"]
        rf = results.get(f"{q}_fused")
        if rf and rf.get("rows_per_sec"):
            out[f"nexmark_{q}_fused_rows_per_sec_8chip"] = \
                rf["rows_per_sec"]
            if "host_hops_per_interval" in rf:
                out[f"nexmark_{q}_fused_host_hops_per_interval"] = \
                    rf["host_hops_per_interval"]
    rt = results.get("q5_topn_8chip")
    if rt and rt.get("rows_per_sec"):
        out["nexmark_q5_topn_rows_per_sec_8chip"] = rt["rows_per_sec"]
        if "host_hops_per_interval" in rt:
            out["nexmark_q5_topn_host_hops_per_interval"] = \
                rt["host_hops_per_interval"]
    if extra:
        out.update(extra)
    if note:
        out["note"] = note
    print(json.dumps(out), flush=True)


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--baseline":
        _baseline_main(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--one":
        _one_query_main(sys.argv[2])
        return
    # legacy single-query CLI: `python bench.py q7`
    if len(sys.argv) > 1 and sys.argv[1] in QUERIES:
        _one_query_main(sys.argv[1])
        return

    results: dict = {}
    emit_once = threading.Lock()

    def _bail():
        if emit_once.acquire(blocking=False):
            _emit_combined(results, f"hard deadline {GLOBAL_BUDGET_S}s; "
                                    f"partial")
        os._exit(3)

    killer = threading.Timer(GLOBAL_BUDGET_S, _bail)
    killer.daemon = True
    killer.start()
    t0 = time.perf_counter()
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # pre-flight: fail LOUDLY on a stalled device instead of letting the
    # first query record 0.0 rows/s as "teardown abandoned"
    dev_ok, dev_detail = _probe_device_init()
    if not dev_ok:
        for q in ("q1", "q5", "q7", "q8", "q17", "q7d"):
            results[q] = {"note": "skipped: device init stall"}
        killer.cancel()
        if emit_once.acquire(blocking=False):
            _emit_combined(
                results,
                note=f"DEVICE INIT STALL — no query ran: {dev_detail}",
                extra={"device_init_stall": True})
        sys.exit(2)
    # the probe prints "DEVICES <n> <platform> <kind> dispatch-ok": with >= 8
    # devices visible, the mesh-parallel q5/q7 variants run too (fused
    # mesh fragments, SET streaming_parallelism_devices = 8) and their
    # numbers emit as nexmark_q{5,7}_rows_per_sec_8chip
    m_dev = re.search(r"DEVICES (\d+)", dev_detail or "")
    n_devices = int(m_dev.group(1)) if m_dev else 0
    query_list = ["q1", "q5", "q7", "q8", "q17", "q7d", "q7_kill"]
    if n_devices >= 8:
        query_list += ["q5_8chip", "q7_8chip", "q5_fused", "q7_fused",
                       "q5_topn_8chip"]
    for q in query_list:
        remaining = GLOBAL_BUDGET_S - (time.perf_counter() - t0) - 10
        if remaining <= 40:   # a query needs import+compile time to matter
            results[q] = {"note": "skipped: global deadline"}
            continue
        child_budget = max(20.0, min(QUERY_BUDGET_S.get(q, 90.0),
                                     remaining - 15))
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one", q,
                 str(child_budget)],
                capture_output=True, text=True,
                timeout=child_budget + 15, cwd=here)
            jlines = [ln for ln in p.stdout.splitlines()
                      if ln.startswith("{")]
            if jlines:
                r = json.loads(jlines[-1])
                r.pop("query", None)
                results[q] = r
            else:
                tail = (p.stderr or "").strip().splitlines()[-1:] or [""]
                results[q] = {"note": f"no result (rc={p.returncode}): "
                                      f"{tail[0][:200]}"}
        except subprocess.TimeoutExpired as e:
            # the child may have printed its partial line before hanging
            # in teardown — a recorded number always beats no number
            out = e.stdout or b""
            if isinstance(out, bytes):
                out = out.decode(errors="replace")
            jlines = [ln for ln in out.splitlines()
                      if ln.startswith("{")]
            if jlines:
                r = json.loads(jlines[-1])
                r.pop("query", None)
                r["note"] = (r.get("note", "") +
                             " (killed in teardown)").strip()
                results[q] = r
            else:
                results[q] = {"note": "subprocess timeout"}
        except Exception as e:  # noqa: BLE001
            results[q] = {"note": f"error: {type(e).__name__}: {e}"}
        # re-emit the running combined line after EVERY query: if an
        # external timeout kills this orchestrator, the last printed line
        # still carries everything measured so far
        _emit_combined(results, note="in progress")
    # baselines AFTER the device queries and STRICTLY SERIAL: this host
    # has ONE cpu core (nproc=1), so anything concurrent — device actors
    # or sibling baselines — depresses the numpy numbers 2-4x and
    # corrupts vs_baseline in either direction (round-4 measurement)
    # priority order: q17's ratio is a staged-config deliverable and q1's
    # is the least informative — if the budget runs out, lose q1 first
    baseline_order = ["q17", "q7", "q8", "q5", "q1"]
    assert set(baseline_order) == set(BASELINE_CHUNKS), \
        "baseline_order out of sync with BASELINE_CHUNKS"
    for q in baseline_order:
        n, cs = BASELINE_CHUNKS[q]
        base = None
        remaining = GLOBAL_BUDGET_S - (time.perf_counter() - t0) - 10
        if remaining <= 10:
            continue
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--baseline",
                 q, str(n), str(cs)],
                capture_output=True, text=True, env=env, cwd=here,
                timeout=remaining)
            for line in p.stdout.splitlines():
                if line.startswith("{"):
                    base = json.loads(line)["baseline_rows_per_sec"]
        except Exception:
            pass
        r = results.get(q)
        if r is not None and base:
            r["baseline_rows_per_sec"] = round(base, 1)
            rps = r.get("rows_per_sec")
            if rps:
                r["vs_baseline"] = round(rps / base, 3)
        _emit_combined(results, note="in progress")
    # the mesh variants share their base query's workload: their ratios
    # use the same baselines, and the scaling over the per-chip number
    # (the ROADMAP item-2 deliverable) is reported explicitly
    for q in ("q5", "q7"):
        rq, r8 = results.get(q), results.get(f"{q}_8chip")
        if not (rq and r8):
            continue
        base = rq.get("baseline_rows_per_sec")
        rps = r8.get("rows_per_sec")
        if base and rps:
            r8["baseline_rows_per_sec"] = base
            r8["vs_baseline"] = round(rps / base, 3)
        if rps and rq.get("rows_per_sec"):
            r8["scaling_vs_per_chip"] = round(
                rps / rq["rows_per_sec"], 3)
        _emit_combined(results, note="in progress")
    # the durable variant shares q7's workload: its ratio uses q7's
    # baseline, and the flush tax is reported explicitly
    r7, r7d = results.get("q7"), results.get("q7d")
    if r7 and r7d and r7.get("baseline_rows_per_sec"):
        base = r7["baseline_rows_per_sec"]
        rps = r7d.get("rows_per_sec")
        if rps:
            r7d["baseline_rows_per_sec"] = base
            r7d["vs_baseline"] = round(rps / base, 3)
        if rps and r7.get("rows_per_sec"):
            r7d["durable_fraction_of_volatile"] = round(
                rps / r7["rows_per_sec"], 3)
        _emit_combined(results, note="in progress")
    killer.cancel()
    if emit_once.acquire(blocking=False):
        _emit_combined(results)


if __name__ == "__main__":
    main()
