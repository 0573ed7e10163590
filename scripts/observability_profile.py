"""Observability-plane gate — canned q7 shape, no TPU needed.

Eight checks, rc=0 iff all pass:

  1. OVERHEAD — the q7-shaped pipeline (broadcast source -> window-max
     agg -> join back) runs under real actors + a real coordinator at
     `metric_level=off` and `metric_level=debug`; the debug barrier p50
     must stay within the SAME-MACHINE calibrated limit of off: the
     spread the off-mode passes show against each other (identical
     work, so pure scheduler noise) sets the allowance, floored at 10%
     — a fixed ratio on a noisy box fails runs a null comparison would
     also fail. Each mode runs several passes and takes the best
     per-mode median to damp scheduler noise.
  2. EXPOSITION — the monitor endpoint's /metrics body (served over a
     real socket) must parse as valid Prometheus text exposition:
     families grouped under one `# TYPE`, histogram `le` ascending with
     a trailing +Inf, labels quoted/escaped.
  3. WATCHDOG — a synthetically parked actor (registered, never
     collects) must trip the stuck-barrier watchdog within the
     threshold: barrier_stalls_total increments and the report names the
     remaining actor.
  4. PROFILE PERTURBATION — a 2s on-demand cpu profile sampled while
     the q7 shape keeps pacing barriers must keep the barrier p50
     within 15% of the unprofiled run (and yield parseable stacks).
  5. METRICS HISTORY — the barrier-paced sampler on (interval=1, full
     default allowlist) must keep the barrier p50 within the calibrated
     limit of sampling-off, leave >= 2 samples per tracked series, and
     answer through SQL: GROUP BY / filtered aggregates over
     `rw_metrics` via the normal batch pipeline.
  6. CROSS-ENGINE STITCH — two in-process engines chained through one
     broker topic export their chrome traces; the stitcher must merge
     them into one Perfetto-loadable timeline with >= 1 sink-delivery
     -> source-ingest flow link.
  7. CLUSTER TRACE OVERHEAD — a real 2-worker deployment runs the q7
     DDL with distributed span recording at `debug`; barrier p50 must
     stay within the same-machine calibrated limit of `off` (off runs
     twice, bracketing debug, to supply the null spread; span bundles
     ride every sealed report).
  8. CLUSTER STALL REPORT — a worker-side `channel_stall` fault wedges
     an epoch past the watchdog threshold; the merged report must name
     the stalled WORKER (one `== worker wN ==` section per live worker)
     and the remaining ACTORS.

    JAX_PLATFORMS=cpu python scripts/observability_profile.py
"""

import asyncio
import contextlib
import io
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent XLA compilation cache (utils/compile_cache.py): the
# gate re-runs a canned shape every CI round — repeat runs skip the
# compile entirely
from risingwave_tpu.utils.compile_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


N_INTERVALS = 30
WARMUP_INTERVALS = 8
PASSES = 3
CHUNKS_PER_INTERVAL = 4
CHUNK_CAP = 256
WINDOW = 1 << 10
OVERHEAD_FLOOR = 1.10


def _calibrated_limit(null_p50s) -> float:
    """Same-machine overhead allowance: the off-mode passes run
    IDENTICAL work, so the spread they show against each other is pure
    scheduler noise on this box. Gating debug against that observed
    null ratio (floored at the nominal 10%) keeps the check meaningful
    on a quiet machine without failing noisy CI runners on jitter a
    null comparison would also fail."""
    spread = max(null_p50s) / max(min(null_p50s), 1e-9)
    return round(max(OVERHEAD_FLOOR, spread), 3)


def _bid_schema():
    from risingwave_tpu.common import DataType, schema
    return schema(("auction", DataType.INT64), ("price", DataType.INT64),
                  ("ts", DataType.INT64))


class IntervalSource:
    """Barrier-driven scripted source: emits a fixed batch of canned
    chunks per interval, then parks on the coordinator's barrier queue
    (the same shape a rate-limited connector source has)."""

    def __init__(self, sch, barrier_q, all_chunks):
        self.schema = sch
        self.pk_indices = ()
        self.identity = "IntervalSource"
        self.barrier_q = barrier_q
        self.chunks = all_chunks          # list of per-interval lists
        self.obs = None

    def fence_tokens(self):
        return []

    async def execute(self):
        barrier = await self.barrier_q.get()       # INITIAL
        yield barrier
        i = 0
        while True:
            for ch in self.chunks[i % len(self.chunks)]:
                yield ch
            i += 1
            barrier = await self.barrier_q.get()
            yield barrier
            if barrier.is_stop(0):
                return


def _canned_chunks(seed: int):
    from risingwave_tpu.common.chunk import StreamChunk
    sch = _bid_schema()
    rng = np.random.RandomState(seed)
    intervals = []
    for e in range(N_INTERVALS):
        batch = []
        base_ts = e * WINDOW * 4
        for _ in range(CHUNKS_PER_INTERVAL):
            n = int(rng.randint(CHUNK_CAP // 4, CHUNK_CAP))
            auction = rng.randint(0, 50, size=n).astype(np.int64)
            price = rng.randint(1, 2_000, size=n).astype(np.int64)
            ts = (base_ts
                  + rng.randint(0, WINDOW * 4, size=n)).astype(np.int64)
            batch.append(StreamChunk.from_numpy(
                sch, [auction, price, ts], capacity=CHUNK_CAP))
        intervals.append(batch)
    return intervals


async def _run_q7(metric_level: str, profile_seconds: float = 0.0,
                  history_interval=None) -> dict:
    """q7 shape under real actors: one source actor broadcasting to a
    join actor whose right side is project -> window-max agg.

    With `profile_seconds` > 0, a cpu profile samples from a helper
    thread WHILE barriers keep pacing (the perturbation check): the
    interval loop keeps injecting until the profile window closes, and
    only the latencies that overlap it are measured.

    `history_interval` (0 = sampling off, N = every N barriers)
    configures the coordinator's metrics-history sampler for the
    HISTORY overhead check; None leaves the default."""
    from risingwave_tpu.expr import call, col, lit
    from risingwave_tpu.expr.agg import AggCall, AggKind
    from risingwave_tpu.meta.barrier_manager import BarrierCoordinator
    from risingwave_tpu.state import MemoryStateStore
    from risingwave_tpu.stream import (
        Actor, BroadcastDispatcher, Channel, ChannelInput,
        HashAggExecutor, StopMutation)
    from risingwave_tpu.stream.sorted_join import SortedJoinExecutor
    from risingwave_tpu.stream.project import ProjectExecutor

    sch = _bid_schema()
    coord = BarrierCoordinator(MemoryStateStore(),
                               checkpoint_max_inflight=0)
    coord.stats.configure(metric_level)
    if history_interval is not None:
        coord.metrics_history.configure(interval=history_interval)
    barrier_q: asyncio.Queue = asyncio.Queue()
    coord.register_source(barrier_q)

    src = IntervalSource(sch, barrier_q, _canned_chunks(seed=7))
    ch_l, ch_r = Channel(64), Channel(64)
    src_actor = Actor(1, src, BroadcastDispatcher([ch_l, ch_r]), coord)

    win = call("add", call("subtract", col(2),
                           call("modulus", col(2), lit(WINDOW))),
               lit(WINDOW))
    proj = ProjectExecutor(ChannelInput(ch_r, sch), [col(0), col(1), win])
    agg = HashAggExecutor(
        proj, [2], [AggCall(AggKind.MAX, 1, sch[1].data_type,
                            append_only=True)],
        capacity=1 << 12)
    join = SortedJoinExecutor(
        ChannelInput(ch_l, sch), agg,
        left_key_indices=[1], right_key_indices=[1],
        left_pk_indices=[0, 2], right_pk_indices=[0],
        capacity=1 << 14, match_factor=64)
    join_actor = Actor(2, join, None, coord)

    for actor, root in ((src_actor, src), (join_actor, join)):
        coord.register_actor(actor.actor_id)
        coord.stats.register("q7", actor, root)
    tasks = [src_actor.spawn(), join_actor.spawn()]

    from risingwave_tpu.stream.message import BarrierKind
    b = await coord.inject_barrier(kind=BarrierKind.INITIAL)
    await coord.wait_collected(b)
    lat = []
    prof_task = None
    prof_text = None
    i = 0
    while True:
        b = await coord.inject_barrier()
        await coord.wait_collected(b)
        if i >= WARMUP_INTERVALS:
            if profile_seconds and prof_task is None:
                from risingwave_tpu.utils.profiler import profile_cpu
                prof_task = asyncio.ensure_future(
                    asyncio.to_thread(profile_cpu, profile_seconds))
            lat.append(coord.latencies_ns[-1] / 1e6)
        i += 1
        if prof_task is not None:
            if prof_task.done():
                prof_text = prof_task.result()
                break
        elif i >= N_INTERVALS - 1:
            break
    b = await coord.inject_barrier(mutation=StopMutation(frozenset({1, 2})))
    await coord.wait_collected(b)
    for t in tasks:
        await t
    lat.sort()
    out = {"metric_level": metric_level,
           "p50_ms": round(lat[len(lat) // 2], 3),
           "p90_ms": round(lat[int(len(lat) * 0.9)], 3),
           "intervals": len(lat)}
    if prof_text is not None:
        from risingwave_tpu.utils.profiler import parse_collapsed
        stacks = parse_collapsed(prof_text)
        out["profile_samples"] = sum(c for _, c in stacks)
    if history_interval:
        per_series: dict = {}
        for r in coord.metrics_history.rows():
            key = (r["name"], tuple(sorted(r["labels"].items())))
            per_series[key] = per_series.get(key, 0) + 1
        out["history_series"] = len(per_series)
        out["history_min_samples"] = min(per_series.values(), default=0)
    return out


# ---------------------------------------------------------- exposition check

def parse_exposition(text: str) -> dict:
    """Minimal Prometheus text-format validator: returns
    family -> [(labels_str, value)], raising on malformed lines,
    ungrouped families, or mis-ordered histogram `le` buckets."""
    line_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? ([0-9eE.+-]+|NaN|[+-]Inf)$")
    families: dict = {}
    seen_types: dict = {}
    current = None
    for ln in text.splitlines():
        if not ln.strip():
            continue
        if ln.startswith("# TYPE "):
            _, _, name, typ = ln.split(" ", 3)
            if name in seen_types:
                raise ValueError(f"family {name} declared twice")
            seen_types[name] = typ
            current = name
            continue
        if ln.startswith("#"):
            continue
        m = line_re.match(ln)
        if m is None:
            raise ValueError(f"malformed exposition line: {ln!r}")
        name = m.group(1)
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        fam = name if name in seen_types else base
        if fam != current:
            raise ValueError(
                f"series {name} outside its family block ({current})")
        families.setdefault(fam, []).append(
            (m.group(2) or "", float(m.group(3))
             if m.group(3) not in ("+Inf", "-Inf", "NaN") else m.group(3)))
    # histogram le ordering per labelset
    for fam, typ in seen_types.items():
        if typ != "histogram":
            continue
        by_rest: dict = {}
        for labels, _v in families.get(fam, []):
            if '_le_sentinel' in labels:
                continue
            mle = re.search(r'le="([^"]+)"', labels)
            if mle is None:
                continue
            rest = re.sub(r'le="[^"]+",?', "", labels)
            by_rest.setdefault(rest, []).append(mle.group(1))
        for rest, les in by_rest.items():
            vals = [float("inf") if x == "+Inf" else float(x) for x in les]
            if vals != sorted(vals) or vals[-1] != float("inf"):
                raise ValueError(
                    f"histogram {fam}{rest}: le not ascending to +Inf: "
                    f"{les}")
    return families


async def _check_exposition() -> dict:
    """Serve /metrics from a LIVE session over a real socket and parse."""
    from risingwave_tpu.frontend import Session
    s = Session()
    await s.execute("SET metric_level = debug")
    await s.execute(
        "CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
        "chunk_size=128, rate_limit=128)")
    await s.execute(
        "CREATE MATERIALIZED VIEW obs_gate AS SELECT auction, price "
        "FROM bid")
    await s.tick(3)
    mon = await s.start_monitor(0)
    reader, writer = await asyncio.open_connection("127.0.0.1", mon.port)
    writer.write(b"GET /metrics HTTP/1.0\r\n\r\n")
    raw = await reader.read()
    writer.close()
    head, _, body = raw.decode().partition("\r\n\r\n")
    assert head.startswith("HTTP/1.0 200"), head
    families = parse_exposition(body)
    per_actor = [f for f in families if f.startswith("stream_actor_")]
    await s.stop_monitor()
    await s.drop_all()
    return {"families": len(families),
            "per_actor_families": sorted(per_actor),
            "row_series": len(families.get("stream_actor_row_count", []))}


# ------------------------------------------------------------ watchdog check

async def _check_watchdog() -> dict:
    """A registered actor that never collects must trip the watchdog."""
    from risingwave_tpu.meta.barrier_manager import BarrierCoordinator
    from risingwave_tpu.state import MemoryStateStore
    from risingwave_tpu.utils.metrics import GLOBAL_METRICS

    coord = BarrierCoordinator(MemoryStateStore())
    coord.stall_threshold_ms = 150.0
    coord.register_actor(999)                 # parked forever
    q: asyncio.Queue = asyncio.Queue()
    coord.register_source(q)
    stalls0 = GLOBAL_METRICS.counter("barrier_stalls_total").value
    buf = io.StringIO()
    # the report lands on STDERR (stdout is the JSON result channel)
    with contextlib.redirect_stderr(buf):
        b = await coord.inject_barrier()
        waiter = asyncio.ensure_future(coord.wait_collected(b))
        await asyncio.sleep(0.6)
        coord.collect(999, b)                 # un-park; epoch completes
        await waiter
    report = buf.getvalue()
    stalls = GLOBAL_METRICS.counter("barrier_stalls_total").value - stalls0
    return {"stalls_fired": stalls,
            "report_names_actor": "999" in report,
            "report_has_await_tree": "await tree" in report}


# ------------------------------------------------------------- cluster checks

CLUSTER_WARMUP = 4
CLUSTER_MEASURE = 12
PROFILE_PERTURB_LIMIT = 1.15

W = 10_000_000
CLUSTER_Q7_DDL = [
    ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
     "chunk_size=256, splits=2, rate_limit=512, inter_event_us=250, "
     f"emit_watermarks=1, watermark_lag_us={2 * W})"),
    ("CREATE MATERIALIZED VIEW q7 AS "
     "SELECT B.auction, B.price, B.bidder, B.date_time "
     "FROM bid B JOIN ("
     "  SELECT max(price) AS maxprice, window_end "
     f"  FROM TUMBLE(bid, date_time, {W}) GROUP BY window_end) B1 "
     "ON B.price = B1.maxprice "
     f"AND B.date_time > B1.window_end - {W} "
     "AND B.date_time <= B1.window_end"),
]


def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_worker(port: int):
    import socket
    import subprocess
    import time
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen(
        [sys.executable, "-m", "risingwave_tpu.worker", str(port)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port),
                                     timeout=1).close()
            return p
        except OSError:
            time.sleep(0.2)
    p.terminate()
    raise RuntimeError("worker never started listening")


def _p50(xs):
    xs = sorted(xs)
    return round(xs[len(xs) // 2], 3) if xs else 0.0


async def _check_cluster() -> dict:
    """One 2-worker deployment, two checks:

    TRACE OVERHEAD — the q7 pipeline runs paced rounds with span
    recording at `metric_level=off` and again at `debug` (per-actor
    series + span shipping on every sealed report); the debug barrier
    p50 must stay within 10% of off.

    STALL REPORT — a worker-side `channel_stall` (the spec rides the
    cluster config push and fires inside the WORKER process) wedges an
    epoch past the watchdog threshold; the merged report meta prints
    must carry every live worker's section so it names the stalled
    worker AND its remaining actors."""
    import tempfile

    from risingwave_tpu.frontend import Session
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore

    root = tempfile.mkdtemp(prefix="obsgate-cluster-")
    ports = [_free_port(), _free_port()]
    procs = [_spawn_worker(p) for p in ports]
    out: dict = {}
    try:
        s = Session(store=HummockStateStore(LocalFsObjectStore(
            os.path.join(root, "store"))))
        addr = ",".join(f"127.0.0.1:{p}" for p in ports)
        await s.execute(f"SET cluster = '{addr}'")
        for d in CLUSTER_Q7_DDL:
            await s.execute(d)

        # off runs twice (bracketing debug) so the cluster gate also
        # carries its own same-machine null baseline
        p50 = {"off": [], "debug": []}
        for mode in ("off", "debug", "off"):
            await s.execute(f"SET metric_level = {mode}")
            await s.tick(CLUSTER_WARMUP)
            n0 = len(s.coord.latencies_ns)
            await s.tick(CLUSTER_MEASURE)
            p50[mode].append(_p50([x / 1e6
                                   for x in s.coord.latencies_ns[n0:]]))
        off_best = min(p50["off"])
        out["trace_off_p50_ms"] = off_best
        out["trace_debug_p50_ms"] = p50["debug"][0]
        out["trace_ratio"] = round(
            p50["debug"][0] / max(off_best, 1e-9), 3)
        out["trace_limit"] = _calibrated_limit(p50["off"])

        await s.execute("SET barrier_stall_threshold_ms = 500")
        await s.execute(
            "SET fault_injection = 'channel_stall:ms=4000'")
        buf = io.StringIO()
        with contextlib.redirect_stderr(buf):
            await s.tick(3)
        report = buf.getvalue()
        stalls = s.event_log.records(kind="barrier_stall")
        out["stall_report_fired"] = "[stuck barrier]" in report
        out["stall_report_names_worker"] = (
            "== worker w1 ==" in report and "== worker w2 ==" in report)
        out["stall_report_names_actor"] = bool(
            stalls and stalls[-1].get("remaining"))
        out["stalled_actors"] = (stalls[-1]["remaining"]
                                 if stalls else [])
        await s.execute("SET fault_injection = ''")
        await s.shutdown()
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
                p.wait(timeout=10)
    return out


async def _check_profile_perturbation(baseline_p50: float) -> dict:
    """A 2s on-demand cpu profile sampled WHILE the q7 shape keeps
    pacing barriers must not move the barrier p50 by more than 15% —
    /debug/profile/cpu has to be safe to point at a live cluster."""
    runs = [await _run_q7("debug", profile_seconds=2.0)
            for _ in range(2)]
    prof_p50 = min(r["p50_ms"] for r in runs)
    return {"baseline_p50_ms": baseline_p50,
            "profiled_p50_ms": prof_p50,
            "ratio": round(prof_p50 / max(baseline_p50, 1e-9), 3),
            "profile_samples": max(r.get("profile_samples", 0)
                                   for r in runs)}


# ------------------------------------------------------ metrics history check

async def _check_history() -> dict:
    """METRICS HISTORY — two halves:

    OVERHEAD — the q7 shape runs with the barrier-paced sampler off
    (interval=0) and on (interval=1, full default allowlist at
    metric_level=debug); the sampling-on barrier p50 must stay within
    the same-machine calibrated limit of off, and every sampled series
    must hold >= 2 samples after the run.

    SQL SURFACE — a live Session ticks a real pipeline, then the
    history must answer through the batch pipeline: a GROUP BY over
    rw_metrics returns >= 2 samples per name, and a filtered aggregate
    (max of one series) returns a finite value."""
    import math

    p50 = {"off": [], "on": []}
    on_runs = []
    for _ in range(PASSES):
        for mode, interval in (("off", 0), ("on", 1)):
            r = await _run_q7("debug", history_interval=interval)
            p50[mode].append(r["p50_ms"])
            if mode == "on":
                on_runs.append(r)
    off_best, on_best = min(p50["off"]), min(p50["on"])
    out = {"off_p50_ms": off_best, "on_p50_ms": on_best,
           "ratio": round(on_best / max(off_best, 1e-9), 3),
           "limit": _calibrated_limit(p50["off"]),
           "series": max(r["history_series"] for r in on_runs),
           "min_samples": max(r["history_min_samples"] for r in on_runs)}

    from risingwave_tpu.frontend import Session
    s = Session()
    await s.execute("SET metric_level = debug")
    await s.execute(
        "CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
        "chunk_size=128, rate_limit=256)")
    await s.execute(
        "CREATE MATERIALIZED VIEW hist_gate AS SELECT auction, price "
        "FROM bid")
    await s.tick(8)
    counts = dict(s.query(
        "SELECT name, count(*) FROM rw_metrics GROUP BY name"))
    agg = s.query(
        "SELECT max(value) FROM rw_metrics "
        "WHERE name = 'meta_barrier_latency_seconds_p50'")
    await s.drop_all()
    out["sql_names"] = len(counts)
    out["sql_min_samples"] = int(min(counts.values(), default=0))
    out["sql_max_latency_p50"] = (float(agg[0][0])
                                  if agg and agg[0][0] is not None
                                  else None)
    out["sql_agg_finite"] = bool(
        agg and agg[0][0] is not None and math.isfinite(float(agg[0][0])))
    return out


# --------------------------------------------------- cross-engine trace check

async def _check_xengine_stitch() -> dict:
    """CROSS-ENGINE STITCH — two in-process engines chained through one
    broker topic (A: nexmark -> windowed-agg broker sink; B: broker
    source -> MV). Each engine's tracer exports its own chrome trace;
    `stitch_chrome_traces` must merge them into ONE Perfetto-loadable
    timeline with >= 1 sink-delivery -> source-ingest flow link."""
    import tempfile

    from risingwave_tpu.broker import (Broker, register_inproc,
                                       unregister_inproc)
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.utils.trace import (stitch_chrome_traces,
                                            traces_to_chrome)

    root = tempfile.mkdtemp(prefix="obsgate-xengine-")
    b = Broker(os.path.join(root, "broker"), fsync=False)
    register_inproc("obs_gate_x", b)
    try:
        a = Session()
        await a.execute("SET streaming_watchdog = 0")
        await a.execute(
            "CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
            "chunk_size=128, inter_event_us=2000, rate_limit=512)")
        await a.execute(
            "CREATE SINK q7x AS SELECT window_end, max(price) AS mp "
            "FROM TUMBLE(bid, date_time, 1000000) GROUP BY window_end "
            "WITH (connector='broker', topic='q7x', "
            "brokers='inproc://obs_gate_x')")
        await a.tick(5)
        bs = Session()
        await bs.execute("SET streaming_watchdog = 0")
        await bs.execute(
            "CREATE SOURCE q7 WITH (connector='broker', topic='q7x', "
            "brokers='inproc://obs_gate_x', "
            "columns='window_end timestamp, mp int64', "
            "primary_key='window_end', chunk_size=64, "
            "discovery_interval_ms=0)")
        await bs.execute(
            "CREATE MATERIALIZED VIEW xout AS "
            "SELECT window_end, mp FROM q7")
        await bs.tick(5)
        ev_a = traces_to_chrome(a.coord.tracer.open_traces()
                                + a.coord.tracer.recent())
        ev_b = traces_to_chrome(bs.coord.tracer.open_traces()
                                + bs.coord.tracer.recent())
        merged, n_links = stitch_chrome_traces(
            ev_a, ev_b, a.engine_id, bs.engine_id)
        # Perfetto loads a flat chrome-format event array: every event
        # needs numeric ts and a ph; the stitched ids must still pair
        json.dumps(merged)
        bad = [e for e in merged
               if "ph" not in e
               or not isinstance(e.get("ts", 0), (int, float))]
        rows = bs.query("SELECT window_end, mp FROM xout")
        await a.drop_all()
        await bs.drop_all()
        return {"events_a": len(ev_a), "events_b": len(ev_b),
                "merged_events": len(merged), "links": n_links,
                "malformed_events": len(bad), "rows_through": len(rows)}
    finally:
        unregister_inproc("obs_gate_x")


async def main() -> int:
    # overhead: alternate modes, best median per mode
    p50 = {"off": [], "debug": []}
    for _ in range(PASSES):
        for mode in ("off", "debug"):
            r = await _run_q7(mode)
            p50[mode].append(r["p50_ms"])
    off_p50, dbg_p50 = min(p50["off"]), min(p50["debug"])
    limit = _calibrated_limit(p50["off"])
    overhead = {"off_p50_ms": off_p50, "debug_p50_ms": dbg_p50,
                "ratio": round(dbg_p50 / max(off_p50, 1e-9), 3),
                "limit": limit,
                "passes": p50}
    expo = await _check_exposition()
    wd = await _check_watchdog()
    perturb = await _check_profile_perturbation(dbg_p50)
    # cluster keeps its original slot (same process state as ever for
    # its timing comparison); the new checks run after it
    cluster = await _check_cluster()
    hist = await _check_history()
    xeng = await _check_xengine_stitch()
    verdict = {
        "overhead_within_calibrated_limit": dbg_p50 <= off_p50 * limit,
        "exposition_valid": expo["row_series"] > 0,
        "watchdog_fired": (wd["stalls_fired"] >= 1
                           and wd["report_names_actor"]
                           and wd["report_has_await_tree"]),
        "cluster_trace_overhead_within_calibrated_limit":
            cluster["trace_ratio"] <= cluster["trace_limit"],
        "cluster_stall_report_names_worker_actor": (
            cluster["stall_report_fired"]
            and cluster["stall_report_names_worker"]
            and cluster["stall_report_names_actor"]),
        "cpu_profile_perturbation_within_15pct": (
            perturb["ratio"] <= PROFILE_PERTURB_LIMIT
            and perturb["profile_samples"] > 10),
        "history_overhead_within_calibrated_limit":
            hist["ratio"] <= hist["limit"],
        "history_queryable_via_sql": (
            hist["min_samples"] >= 2 and hist["sql_names"] > 0
            and hist["sql_min_samples"] >= 2 and hist["sql_agg_finite"]),
        "xengine_stitched_with_links": (
            xeng["links"] >= 1 and xeng["malformed_events"] == 0
            and xeng["rows_through"] > 0),
    }
    print(json.dumps({"overhead": overhead}))
    print(json.dumps({"exposition": expo}))
    print(json.dumps({"watchdog": wd}))
    print(json.dumps({"profile_perturbation": perturb}))
    print(json.dumps({"history": hist}))
    print(json.dumps({"xengine": xeng}))
    print(json.dumps({"cluster": cluster}))
    print(json.dumps({"verdict": verdict}))
    return 0 if all(verdict.values()) else 1


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
