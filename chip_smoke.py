"""chip_smoke.py — the quickest proof that the engine still starts on the chip.

Drives the main path once — `Session` -> binder -> `plan/build` -> actors ->
barrier coordinator -> `HummockStateStore(LocalFsObjectStore(dir))` — through
`Session.execute` / `Session.tick` / `Session.query`, with
`streaming_durability` and `streaming_watchdog` left ON, at the shapes the old
bench called real (chunk_size=131072; q7 join capacity 2^19, agg 2^13; q5 agg
2^20; q8 98304/294912-row chunks; q17 64 x 8192 lineitems of TPC-H at SF
0.005), and compares every
materialized view with a numpy recomputation on the same generated events.

    python chip_smoke.py              # one TPU chip: q1, q7 (+restart), q5, q8, q17
    python chip_smoke.py --chips 4    # four chips: q7 on the 4-device mesh vs
                                      # the same bounded input on one chip
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal [--chips 4]
                                      # same phases at a tiny size on the CPU
                                      # (--chips 4 wants XLA_FLAGS=
                                      #  --xla_force_host_platform_device_count=4)

One process; it touches jax itself and starts no child that needs the chip.
Without a TPU it exits non-zero and prints no result (the rehearsal must be
asked for by flag AND environment, and says "cpu" in every line it prints).
Nothing is caught and carried past: a phase that raises, an oracle that
differs, a recovery, a shuffle drop, a join error counter, or a StateJit
compile or a barrier stall after warm-up ends the run with a traceback and
rc != 0 (`--phases q1,q7` runs a subset to save chip time; its last line
says `partial`).

stdout is one JSON object per line; the LAST line is exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
Rates on the phase lines are information about a smoke run on the named
device, not a benchmark.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.metadata
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

W = 10_000_000                      # 10 s tumble window, microseconds
HOP_SLIDE, HOP_SIZE = 2_000_000, 10_000_000

# phase -> (chunk sizes, chunks per barrier interval, intervals).
# FULL is what the chip runs; REHEARSAL is the CPU dry run of the same code.
FULL = {
    "q1": dict(cs=131072, chunks=2, intervals=8),            # 2.1 M bids
    # one chunk per interval: the join keeps the bids of one whole interval
    # plus the 30 s watermark band (an interval's max-price updates leave the
    # agg at the barrier), and two chunks' worth crosses 0.7 x 2^19
    # 32 intervals = 4.2 M bids / 114 windows, not the 20 M the issue asked
    # for: one durable 131072-row checkpoint of this plan takes ~5 s on the
    # chip's host (row-codec + LSM writes of the join's insert/evict diff),
    # and the cold compile of its programs ~400 s of the 1200 s limit
    "q7": dict(cs=131072, chunks=1, intervals=32,
               resume_intervals=3, join_cap=1 << 19, agg_cap=1 << 13),
    # q5 and q8 run 4 intervals, not the issue's 8: with a cold compile
    # cache the five phases took 1167 s of the 1200 s limit at 8 (q8 alone
    # 9.7 s per interval, host-side MV materialization); every comparison
    # is kept
    "q5": dict(cs=131072, chunks=2, intervals=4, agg_cap=1 << 20),
    "q8": dict(cs_person=98304, cs_auction=294912, chunks=1, intervals=4,
               join_cap=1 << 19),
    "q17": dict(cs=8192, chunks=8, intervals=8,              # 64 chunks
                join_cap=1 << 20, agg_cap=1 << 16),
    # warmup=4: the sharded executors' adaptive shuffle slack observes three
    # barriers, then re-traces its fused programs ONCE with the adapted cap
    "q7_mesh": dict(cs=32768, chunks=4, intervals=8, warmup=4,
                    join_cap=1 << 19, agg_cap=1 << 13),
}
REHEARSAL = {
    "q1": dict(cs=4096, chunks=2, intervals=3),
    "q7": dict(cs=4096, chunks=5, intervals=4, resume_intervals=3,
               join_cap=1 << 17, agg_cap=1 << 10),
    "q5": dict(cs=4096, chunks=2, intervals=3, agg_cap=1 << 14),
    "q8": dict(cs_person=1536, cs_auction=4608, chunks=1, intervals=3,
               join_cap=1 << 14),
    "q17": dict(cs=1024, chunks=4, intervals=3,
                join_cap=1 << 15, agg_cap=1 << 12),
    "q7_mesh": dict(cs=4096, chunks=2, intervals=6, warmup=4,
                    join_cap=1 << 17, agg_cap=1 << 10),
}
# barrier intervals that may still compile (first use of every program and
# of the pow2-bucketed persist shapes) unless the phase's sizes say
# otherwise (`warmup`); no StateJit may compile after them
WARMUP_INTERVALS = 2
QUOTA_WAIT_S = 600.0


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ------------------------------------------------------------------ driving

def executors_of(session, name: str):
    """Every executor deployed under MV `name`."""
    from risingwave_tpu.plan.build import _iter_executor_chain
    for roots in session.catalog.mvs[name].deployment.roots.values():
        for root in roots:
            yield from _iter_executor_chain(root)


def sources_of(session, name: str) -> dict:
    """table -> SourceExecutor feeding MV `name`."""
    from risingwave_tpu.stream.source import SourceExecutor
    out = {ex.connector.table: ex for ex in executors_of(session, name)
           if isinstance(ex, SourceExecutor)}
    assert out, f"no source under {name}"
    return out


def committed_offsets(session, name: str) -> dict:
    """table -> offset as COMMITTED in the source's durable state table."""
    from risingwave_tpu.state.storage_table import StorageTable
    out = {}
    for table, ex in sources_of(session, name).items():
        rows = list(StorageTable.for_state_table(ex.state_table).batch_iter())
        out[table] = int(rows[0][1]) if rows else 0
    return out


async def wait_quota(session, name: str, targets: dict) -> None:
    """Block until every source has emitted up to its row quota for this
    barrier interval (it then parks on the barrier queue), so each interval
    carries exactly `rate_limit` rows whatever the device's speed."""
    srcs = sources_of(session, name)
    deadline = time.monotonic() + QUOTA_WAIT_S
    while any(srcs[t].connector.offset < n for t, n in targets.items()):
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"{name}: sources stuck at "
                f"{ {t: srcs[t].connector.offset for t in targets} } "
                f"waiting for {targets}")
        await asyncio.sleep(0.002)


class Counters:
    """Process-wide counters a phase reads before and after itself."""

    def __init__(self):
        from risingwave_tpu.utils import metrics as m
        self.m = m
        self.t0 = time.perf_counter()
        self.v0 = self.read()

    def read(self) -> dict:
        m = self.m
        return {"jit_compiles": int(m.JIT_COMPILES.value),
                "dispatches": int(m.DEVICE_DISPATCHES.value),
                "d2h_fetches": int(m.D2H_FETCHES.value),
                "d2h_bytes": int(m.D2H_BYTES.value),
                "mesh_shuffle_dropped": int(m.MESH_SHUFFLE_DROPPED.value)}

    def delta(self) -> dict:
        now = self.read()
        return {k: now[k] - self.v0[k] for k in now}


def compiles_by_program() -> dict:
    """program name -> StateJit compile count (ops/jit_state.py)."""
    from risingwave_tpu.utils.metrics import GLOBAL_METRICS
    return {dict(labels)["program"]: int(c.value)
            for (name, labels), c in GLOBAL_METRICS.counters.items()
            if name == "jit_compile_count" and labels}


async def run_intervals(session, name: str, quotas: dict, n: int,
                        base: dict, report: dict,
                        warmup: int = WARMUP_INTERVALS) -> None:
    """`n` checkpointed barrier intervals of exactly `quotas` rows each,
    starting from source offsets `base`. The first `warmup` intervals may
    compile; `report` names every StateJit program that compiled later."""
    from risingwave_tpu.utils.metrics import BARRIER_STALLS
    warmup = min(warmup, n - 1)     # at least one interval is held to it
    warm, ticks, waited = None, [], 0.0
    stalls0 = warm_stalls = int(BARRIER_STALLS.value)
    for i in range(n):
        t0 = time.perf_counter()
        await wait_quota(session, name,
                         {t: base[t] + (i + 1) * q for t, q in quotas.items()})
        t1 = time.perf_counter()
        await session.tick(1, max_recoveries=0)
        dt = time.perf_counter() - t1
        waited += t1 - t0
        if i < warmup:
            report["compile_s"] = round(report.get("compile_s", 0.0) + dt
                                        + (t1 - t0), 3)
            warm = compiles_by_program()
            warm_stalls = int(BARRIER_STALLS.value)
        else:
            ticks.append(dt)
        report["barriers"] = report.get("barriers", 0) + 1
    now = compiles_by_program()
    report["compiled_after_warmup"] = {
        k: v - warm.get(k, 0) for k, v in now.items() if v != warm.get(k, 0)}
    # the stuck-barrier reporter (60 s default) fires on a warm-up barrier
    # that sits behind a cold compile; after warm-up it must stay silent
    report["barrier_stalls_in_warmup"] = warm_stalls - stalls0
    report["barrier_stalls_after_warmup"] = (int(BARRIER_STALLS.value)
                                             - warm_stalls)
    # information: seconds a checkpointed barrier took (inject -> collected
    # -> uploads drained) after warm-up, and seconds the sources took to
    # emit their quota before it
    ticks.sort()
    report["tick_s_p50"] = round(ticks[len(ticks) // 2], 4)
    report["tick_s_max"] = round(ticks[-1], 4)
    report["source_quota_wait_s"] = round(waited, 3)


def check_health(session, name: str, report: dict) -> None:
    """Record error/overflow counters, recoveries and late compiles; what is
    wrong goes to report["failed"] and ends the run in finish_report, after
    the phase's line is out."""
    import jax
    errs = {}
    for ex in executors_of(session, name):
        e = getattr(ex, "_errs_dev", None)
        if e is not None:
            errs[ex.identity] = [int(x) for x in np.asarray(
                jax.device_get(e)).ravel()]
    report["join_error_counters"] = errs
    report["recoveries"] = session.recoveries
    failed = report.setdefault("failed", [])
    if any(any(v) for v in errs.values()):
        failed.append(f"join error counters {errs}")
    if session.recoveries:
        failed.append(f"{session.recoveries} recoveries")
    if report["compiled_after_warmup"]:
        failed.append("StateJit programs compiled after the warm-up "
                      f"intervals: {report['compiled_after_warmup']}")
    if report["barrier_stalls_after_warmup"]:
        failed.append(f"{report['barrier_stalls_after_warmup']} barrier "
                      "stall(s) after warm-up")


def finish_report(name: str, report: dict, ctr: Counters, store_dir: str,
                  session, rows_in: int, device_label: str) -> None:
    """Print the phase's line; fail the run if anything in it is wrong."""
    import jax
    d = ctr.delta()
    wall = time.perf_counter() - ctr.t0
    failed = report.pop("failed", [])
    if d["mesh_shuffle_dropped"]:
        failed.append(f"{d['mesh_shuffle_dropped']} shuffle drops")
    sst_dir = os.path.join(store_dir, "ssts")
    ssts = os.listdir(sst_dir) if os.path.isdir(sst_dir) else []
    if not os.path.isfile(os.path.join(store_dir, "MANIFEST")) or not ssts:
        failed.append(f"no MANIFEST + ssts/ under {store_dir}")
    stats = jax.local_devices()[0].memory_stats() or {}
    report.update({
        "phase": name, "ok": not failed, "device": device_label,
        "rows_in": rows_in,
        "committed_epochs": len(session.coord.committed_epochs),
        "wall_s": round(wall, 3),
        "rows_per_s_smoke_not_benchmark": round(rows_in / wall, 1),
        "d2h_fetches": d["d2h_fetches"], "d2h_bytes": d["d2h_bytes"],
        "dispatches": d["dispatches"], "ssts_on_disk": len(ssts),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    })
    if failed:
        report["failed"] = failed
    emit(report)
    if failed:
        raise AssertionError(f"{name}: " + "; ".join(failed))


def open_store(root: str, name: str, reopen: bool = False):
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    d = os.path.join(root, name)
    objs = LocalFsObjectStore(d)
    return (HummockStateStore.open(objs) if reopen
            else HummockStateStore(objs)), d


async def deploy(root: str, store_name: str, ddl: list):
    """Fresh durable Session over its own Hummock directory, `ddl` executed,
    the Initial barrier injected (sources start on their first quota)."""
    from risingwave_tpu.frontend import Session
    ctr, report = Counters(), {}
    store, d = open_store(root, store_name)
    s = Session(store=store)
    for stmt in ddl:
        await s.execute(stmt)
    await s.tick(0)
    return s, d, ctr, report


async def teardown_big_mv(s) -> None:
    """End a phase whose MV holds millions of rows WITHOUT the stop barrier:
    `Session.query` marked the MV wanted by the serving cache, and the next
    collected barrier — shutdown's — would build that cache by scanning the
    whole MV again (tens of seconds of host time nobody reads). Everything
    compared is already committed; the actors are simply abandoned."""
    await s.crash()


async def drive(s, mv: str, quotas: dict, n: int, report: dict,
                base: dict | None = None,
                warmup: int = WARMUP_INTERVALS) -> dict:
    """run_intervals, then the COMMITTED source offsets — which must be
    exactly base + n quotas (the bounded input the oracle recomputes)."""
    base = base or {t: 0 for t in quotas}
    await run_intervals(s, mv, quotas, n, base, report, warmup)
    offs = committed_offsets(s, mv)
    want = {t: base[t] + n * q for t, q in quotas.items()}
    assert offs == want, f"{mv}: committed offsets {offs}, expected {want}"
    return offs


# ---------------------------------------------------------------- oracles
# Straight numpy over the SAME generated events (regenerated through the
# connector at the committed offsets) — independent of the executors.

def regen(table: str, n: int, cs: int, cols: list, *, connector="nexmark",
          inter_event_us=None) -> list:
    """Columns `cols` of the first `n` rows of `table`, generated in the
    source's own chunk size (no fresh generator compile)."""
    if connector == "tpch":
        from risingwave_tpu.connectors.tpch import TpchGenerator
        gen = TpchGenerator(table, chunk_size=cs, scale_factor=TPCH_SF,
                            seed=TPCH_SEED)
    else:
        from risingwave_tpu.connectors.nexmark import (NexmarkConfig,
                                                       NexmarkGenerator)
        cfg = (NexmarkConfig(inter_event_us=inter_event_us)
               if inter_event_us is not None else NexmarkConfig())
        gen = NexmarkGenerator(table, chunk_size=cs, cfg=cfg)
    parts = [[] for _ in cols]
    while gen.offset < n:
        c = gen.next_chunk()
        for k, j in enumerate(cols):
            parts[k].append(np.asarray(c.columns[j].data))
    return [np.concatenate(p)[:n] if p else np.zeros(0, np.int64)
            for p in parts]


def rows_to_sorted(rows: list, dtypes: list) -> list:
    """list of tuples -> per-column arrays, rows in lexicographic order."""
    if not rows:
        return [np.zeros(0, dt) for dt in dtypes]
    cols = [np.asarray([r[j] for r in rows], dtype=dt)
            for j, dt in enumerate(dtypes)]
    return sort_cols(cols)


def sort_cols(cols: list) -> list:
    order = np.lexsort(tuple(reversed(cols)))
    return [c[order] for c in cols]


def assert_same(name: str, got: list, want: list, float_rtol: float) -> int:
    assert len(got) == len(want)
    assert got[0].shape == want[0].shape, (
        f"{name}: {got[0].shape[0]} rows, oracle has {want[0].shape[0]}")
    for j, (g, w) in enumerate(zip(got, want)):
        if np.issubdtype(w.dtype, np.floating):
            assert np.all(np.isfinite(g)), f"{name}: non-finite in col {j}"
            if float_rtol == 0.0:
                ok = np.array_equal(g, w)
            else:
                ok = np.allclose(g, w, rtol=float_rtol, atol=0.0)
            assert ok, (f"{name}: float col {j} differs, max rel "
                        f"{np.max(np.abs(g - w) / np.maximum(np.abs(w), 1e-300))}")
        else:
            assert np.array_equal(g, w), f"{name}: col {j} differs"
    return int(got[0].shape[0])


# ----------------------------------------------------------------- phases

async def phase_q1(sz, root, env) -> None:
    """q1 durable WITH the float column: every MV row == numpy."""
    quota = sz["cs"] * sz["chunks"]
    s, d, ctr, report = await deploy(root, "q1", [
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         f"chunk_size={sz['cs']}, rate_limit={quota})"),
        ("CREATE MATERIALIZED VIEW q1 AS SELECT auction, bidder, "
         "0.908 * price AS price, date_time FROM bid"),
    ])
    off = (await drive(s, "q1", {"bid": quota}, sz["intervals"],
                       report))["bid"]
    got = rows_to_sorted(
        s.query("SELECT auction, bidder, price, date_time FROM q1"),
        [np.int64, np.int64, np.float64, np.int64])
    a, b, p, t = regen("bid", off, sz["cs"], [0, 1, 2, 5])
    want = sort_cols([a, b, 0.908 * p.astype(np.float64), t])
    n = assert_same("q1", got, want, env["float_rtol"])
    assert n == off, f"q1: {n} MV rows != source offset {off}"
    report.update(mv_rows=n, source_offset=off, oracle="equal",
                  float_rtol=env["float_rtol"])
    check_health(s, "q1", report)
    finish_report("q1", report, ctr, d, s, off, env["label"])
    await teardown_big_mv(s)


def q7_ddl(sz, mesh_devices: int = 0) -> list:
    """bench.py `_q7_ddl` shapes, as a durable MATERIALIZED VIEW."""
    quota = sz["cs"] * sz["chunks"]
    ddl = [
        f"SET streaming_join_capacity = {sz['join_cap']}",
        "SET streaming_join_match_factor = 2",
        f"SET streaming_agg_capacity = {sz['agg_cap']}",
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         f"chunk_size={sz['cs']}, inter_event_us=250, emit_watermarks=1, "
         f"watermark_lag_us={2 * W}, rate_limit={quota})"),
        ("CREATE MATERIALIZED VIEW q7 AS "
         "SELECT B.auction, B.price, B.bidder, B.date_time "
         "FROM bid B JOIN ("
         "  SELECT max(price) AS maxprice, window_end "
         f"  FROM TUMBLE(bid, date_time, {W}) GROUP BY window_end) B1 "
         "ON B.price = B1.maxprice "
         f"AND B.date_time > B1.window_end - {W} "
         "AND B.date_time <= B1.window_end"),
    ]
    if mesh_devices:
        ddl.insert(0, f"SET streaming_parallelism_devices = {mesh_devices}")
    return ddl


def q7_events(n: int, cs: int) -> list:
    """(auction, bidder, price, date_time) of the first n q7 bids."""
    return regen("bid", n, cs, [0, 1, 2, 5], inter_event_us=250)


def q7_oracle(events: list, off: int) -> list:
    """Bids at their 10 s tumble window's max price, over rows [0, off)."""
    a, b, p, t = (c[:off] for c in events)
    # event time is monotone in the event id: windows are contiguous runs.
    # window_end - W < t <= window_end
    we = ((t + W - 1) // W) * W
    starts = np.flatnonzero(np.r_[True, we[1:] != we[:-1]])
    wmax = np.maximum.reduceat(p, starts)
    keep = p == np.repeat(wmax, np.diff(np.r_[starts, off]))
    return sort_cols([a[keep], p[keep], b[keep], t[keep]]), len(starts)


def q7_read(s) -> list:
    return rows_to_sorted(
        s.query("SELECT auction, price, bidder, date_time FROM q7"),
        [np.int64] * 4)


async def phase_q7(sz, root, env) -> None:
    """q7 durable at the bench's shapes, then restart from the store."""
    from risingwave_tpu.frontend import Session
    quota = sz["cs"] * sz["chunks"]
    s, d, ctr, report = await deploy(root, "q7", q7_ddl(sz))
    off1 = (await drive(s, "q7", {"bid": quota}, sz["intervals"],
                        report))["bid"]
    # generated once, up to where the resumed incarnation will stop
    events = q7_events(off1 + quota * sz["resume_intervals"], sz["cs"])
    want, n_windows = q7_oracle(events, off1)
    n1 = assert_same("q7", q7_read(s), want, 0.0)
    report.update(mv_rows=n1, source_offset=off1, windows=n_windows,
                  oracle="equal")
    check_health(s, "q7", report)
    epochs1 = len(s.coord.committed_epochs)
    # process death: actors abandoned without the stop protocol, every live
    # object dropped; a fresh Session over the store REOPENED from disk must
    # resume from the committed offset and keep ticking
    await s.crash()
    del s
    store2, _ = open_store(root, "q7", reopen=True)
    assert store2.committed_epoch() > 0
    s2 = Session(store=store2)
    await s2.recover()
    # the reopened store holds the committed offset, and the rebuilt source
    # re-seeked to it (it is already emitting its first quota from there)
    start = committed_offsets(s2, "q7")["bid"]
    assert start == off1, f"q7 reopened at {start}, committed {off1}"
    seen = sources_of(s2, "q7")["bid"].connector.offset
    assert off1 <= seen <= off1 + quota and (seen - off1) % sz["cs"] == 0, \
        f"q7 source resumed at {seen}, committed {off1}"
    r2: dict = {}
    off2 = (await drive(s2, "q7", {"bid": quota}, sz["resume_intervals"],
                        r2, base={"bid": off1}))["bid"]
    want2, n_windows2 = q7_oracle(events, off2)
    n2 = assert_same("q7/resumed", q7_read(s2), want2, 0.0)
    check_health(s2, "q7", r2)
    report.update(resumed_from_offset=start, resumed_to_offset=off2,
                  resumed_mv_rows=n2, resumed_windows=n_windows2,
                  resumed_oracle="equal",
                  resume_compile_s=r2.get("compile_s"),
                  committed_epochs_before_restart=epochs1)
    finish_report("q7", report, ctr, d, s2, off2, env["label"])
    await s2.shutdown()


async def phase_q5(sz, root, env) -> None:
    """q5 core at the bench's shapes: HOP(2s,10s) count(*) per auction."""
    quota = sz["cs"] * sz["chunks"]
    s, d, ctr, report = await deploy(root, "q5", [
        f"SET streaming_agg_capacity = {sz['agg_cap']}",
        ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
         f"chunk_size={sz['cs']}, inter_event_us=2, emit_watermarks=1, "
         f"rate_limit={quota})"),
        ("CREATE MATERIALIZED VIEW q5 AS "
         "SELECT auction, window_start, count(*) AS n "
         f"FROM HOP(bid, date_time, {HOP_SLIDE}, {HOP_SIZE}) "
         "GROUP BY auction, window_start"),
    ])
    off = (await drive(s, "q5", {"bid": quota}, sz["intervals"],
                       report))["bid"]
    got = rows_to_sorted(s.query("SELECT auction, window_start, n FROM q5"),
                         [np.int64] * 3)
    a, t = regen("bid", off, sz["cs"], [0, 5], inter_event_us=2)
    base = (t // HOP_SLIDE) * HOP_SLIDE
    k = HOP_SIZE // HOP_SLIDE
    aa = np.tile(a, k)
    ws = np.concatenate([base - j * HOP_SLIDE for j in range(k)])
    # one int64 key per (auction, window) pair: a 1-D unique is ~50x
    # cheaper than np.unique(axis=0) on millions of rows
    w0 = int(ws.min())
    wi = (ws - w0) // HOP_SLIDE
    assert int(wi.max()) < 1 << 24 and int(aa.max()) < 1 << 38
    key, counts = np.unique((aa << 24) | wi, return_counts=True)
    want = sort_cols([key >> 24, (key & ((1 << 24) - 1)) * HOP_SLIDE + w0,
                      counts.astype(np.int64)])
    n = assert_same("q5", got, want, 0.0)
    report.update(mv_rows=n, source_offset=off, oracle="equal")
    check_health(s, "q5", report)
    finish_report("q5", report, ctr, d, s, off, env["label"])
    await s.shutdown()


async def phase_q8(sz, root, env) -> None:
    """q8 at the bench's shapes: persons x the auctions they opened in the
    same 10 s tumble window."""
    qp, qa = sz["cs_person"] * sz["chunks"], sz["cs_auction"] * sz["chunks"]
    s, d, ctr, report = await deploy(root, "q8", [
        f"SET streaming_join_capacity = {sz['join_cap']}",
        "SET streaming_join_match_factor = 2",
        ("CREATE SOURCE person WITH (connector='nexmark', table='person', "
         f"primary_key='id', chunk_size={sz['cs_person']}, "
         f"inter_event_us=100, emit_watermarks=1, rate_limit={qp})"),
        ("CREATE SOURCE auction WITH (connector='nexmark', "
         f"primary_key='id', table='auction', chunk_size={sz['cs_auction']}, "
         f"inter_event_us=100, emit_watermarks=1, rate_limit={qa})"),
        ("CREATE MATERIALIZED VIEW q8 AS "
         "SELECT P.id, P.window_start "
         f"FROM TUMBLE(person, date_time, {W}) P "
         f"JOIN TUMBLE(auction, date_time, {W}) A "
         "ON P.id = A.seller AND P.window_start = A.window_start"),
    ])
    offs = await drive(s, "q8", {"person": qp, "auction": qa},
                       sz["intervals"], report)
    got = rows_to_sorted(s.query("SELECT id, window_start FROM q8"),
                         [np.int64] * 2)
    pid, pt = regen("person", offs["person"], sz["cs_person"], [0, 6],
                    inter_event_us=100)
    seller, at = regen("auction", offs["auction"], sz["cs_auction"], [7, 5],
                       inter_event_us=100)
    pw, aw = pt - pt % W, at - at % W
    # person ids are unique, so (id, window) pairs are too: one output row
    # per auction whose (seller, window) is a person's (id, window)
    w0 = int(min(pw.min(), aw.min()))
    assert int(max(pw.max(), aw.max()) - w0) // W < 1 << 24
    pkey = (pid << 24) | ((pw - w0) // W)
    akey = (seller << 24) | ((aw - w0) // W)
    hit = np.isin(akey, pkey)
    want = sort_cols([seller[hit], aw[hit]])
    n = assert_same("q8", got, want, 0.0)
    assert n > 0, "q8 produced no rows — oracle vacuous"
    report.update(mv_rows=n, source_offsets=offs, oracle="equal")
    check_health(s, "q8", report)
    finish_report("q8", report, ctr, d, s, sum(offs.values()), env["label"])
    await teardown_big_mv(s)


# TPC-H by clause 4.2.3 (connectors/tpch.py) at scale factor 0.005: a
# 1,000-part universe, all of it in the first part chunk, under a seed at
# which three parts are Brand#23 in a MED BOX (at SF 1 the benchmark's cell
# q17.sat runs it: benchmark/configs/tpch-q17-sf1-1chip.json)
TPCH_SF, TPCH_SEED = 0.005, 22
TPCH_GEN = f"scale_factor={TPCH_SF}, seed={TPCH_SEED}"

Q17_SQL = (
    "CREATE MATERIALIZED VIEW q17 AS "
    "SELECT sum(L.l_extendedprice) / 7.0 AS avg_yearly "
    "FROM lineitem L "
    "JOIN part P ON P.p_partkey = L.l_partkey "
    "JOIN (SELECT l_partkey AS agg_partkey, "
    "             0.2 * avg(l_quantity) AS avg_quantity "
    "      FROM lineitem GROUP BY l_partkey) A "
    "  ON A.agg_partkey = L.l_partkey "
    " AND L.l_quantity < A.avg_quantity "
    "WHERE P.p_brand = 'Brand#23' AND P.p_container = 'MED BOX'")


async def phase_q17(sz, root, env) -> None:
    """TPC-H q17 at the bench's shapes: float aggregates through persist."""
    from risingwave_tpu.common.types import GLOBAL_DICT
    ql = sz["cs"] * sz["chunks"]
    s, d, ctr, report = await deploy(root, "q17", [
        f"SET streaming_join_capacity = {sz['join_cap']}",
        f"SET streaming_agg_capacity = {sz['agg_cap']}",
        (f"CREATE SOURCE part WITH (connector='tpch', table='part', "
         f"{TPCH_GEN}, chunk_size=1024, rate_limit=1024, "
         "primary_key='p_partkey')"),
        (f"CREATE SOURCE lineitem WITH (connector='tpch', table='lineitem', "
         f"{TPCH_GEN}, chunk_size={sz['cs']}, rate_limit={ql})"),
        Q17_SQL,
    ])
    offs = await drive(s, "q17", {"part": 1024, "lineitem": ql},
                       sz["intervals"], report)
    got = s.query("SELECT avg_yearly FROM q17")
    # p_partkey, p_brand, p_container; l_partkey, l_quantity,
    # l_extendedprice of the spec's 9 and 16 columns
    pk, br, ct = regen("part", offs["part"], 1024, [0, 3, 6],
                       connector="tpch")
    lpk, lq, lep = regen("lineitem", offs["lineitem"], sz["cs"], [1, 4, 5],
                         connector="tpch")
    ok_parts = pk[(br == GLOBAL_DICT.get_or_insert("Brand#23"))
                  & (ct == GLOBAL_DICT.get_or_insert("MED BOX"))]
    m = int(lpk.max()) + 1
    cnt = np.bincount(lpk, minlength=m)
    thr = 0.2 * (np.bincount(lpk, weights=lq, minlength=m)
                 / np.maximum(cnt, 1))
    sel = np.isin(lpk, ok_parts) & (lq < thr[lpk])
    want = float(lep[sel].sum()) / 7.0
    assert want > 0, "q17 oracle vacuous"
    assert len(got) == 1 and got[0][0] is not None, got
    assert np.isfinite(got[0][0])
    assert abs(got[0][0] - want) <= 1e-6 * max(1.0, abs(want)), (got, want)
    report.update(mv_rows=1, avg_yearly=got[0][0], oracle_avg_yearly=want,
                  source_offsets=offs, oracle="within 1e-6 relative")
    check_health(s, "q17", report)
    finish_report("q17", report, ctr, d, s, sum(offs.values()), env["label"])
    await s.shutdown()


async def phase_q7_mesh(sz, root, env) -> None:
    """--chips 4: q7 with the sharded agg + sharded join as fused mesh
    fragments over the four devices, against the same bounded input on ONE
    device in the same process."""
    import jax
    quota = sz["cs"] * sz["chunks"]
    results = {}
    for label, md in (("mesh4", 4), ("single", 0)):
        s, d, ctr, report = await deploy(root, f"q7_{label}",
                                         q7_ddl(sz, mesh_devices=md))
        off = (await drive(s, "q7", {"bid": quota}, sz["intervals"], report,
                           warmup=sz["warmup"]))["bid"]
        results[label] = q7_read(s)
        if md:
            # the sharded state really spans the four devices
            placed = {}
            for ex in executors_of(s, "q7"):
                if not type(ex).__name__.startswith("Sharded"):
                    continue
                leaves = [x for x in jax.tree_util.tree_leaves(
                    [getattr(ex, "state", None), getattr(ex, "sides", None)])
                    if isinstance(x, jax.Array) and x.ndim >= 1]
                assert leaves, f"{ex.identity}: no device state found"
                devs = {sh.device.id for x in leaves
                        for sh in x.addressable_shards}
                per_leaf = min(len({sh.device.id
                                    for sh in x.addressable_shards})
                               for x in leaves)
                placed[ex.identity] = sorted(devs)
                assert len(devs) == md and per_leaf == md, (
                    f"{ex.identity}: state on devices {sorted(devs)} "
                    f"(min per array {per_leaf}), wanted {md} distinct")
            assert placed, "no sharded executor deployed"
            assert len(s.coord.mesh_fragments) >= 2, s.coord.mesh_fragments
            report.update(sharded_state_devices=placed,
                          mesh_fragments=len(s.coord.mesh_fragments))
        check_health(s, "q7", report)
        report.update(variant=label, source_offset=off,
                      mv_rows=int(results[label][0].shape[0]))
        finish_report("q7_mesh", report, ctr, d, s, off, env["label"])
        await s.shutdown()
    off = quota * sz["intervals"]
    want, n_windows = q7_oracle(q7_events(off, sz["cs"]), off)
    assert_same("q7_mesh vs single", results["mesh4"], results["single"], 0.0)
    n = assert_same("q7_mesh vs numpy", results["mesh4"], want, 0.0)
    emit({"phase": "q7_mesh_compare", "ok": True, "device": env["label"],
          "mv_rows": n, "windows": n_windows,
          "mesh4_equals_single": True, "oracle": "equal"})


# ------------------------------------------------------------------- main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny-size dry run on the CPU; needs "
                         "JAX_PLATFORMS=cpu in the environment as well")
    ap.add_argument("--phases", metavar="q1,q7,...",
                    help="builder's diagnostic, to spend less chip time: "
                         "run only these phases (the last line then says "
                         "`partial` and does not stand for the contract)")
    args = ap.parse_args()

    env_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if args.cpu_rehearsal and not env_cpu:
        print("chip_smoke: --cpu-rehearsal needs JAX_PLATFORMS=cpu set by "
              "the caller", file=sys.stderr)
        return 2

    import jax
    import jaxlib

    import risingwave_tpu  # noqa: F401 — enables x64 before any tracing
    from risingwave_tpu import native
    from risingwave_tpu.utils.compile_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    cache_warm = any(os.scandir(cache_dir))
    devs = jax.devices()
    platform, kind, count = devs[0].platform, devs[0].device_kind, len(devs)
    if platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: jax found no TPU (platform={platform!r}, "
              f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); this "
              "script proves the chip path and does not fall back. The CPU "
              "dry run is `JAX_PLATFORMS=cpu python chip_smoke.py "
              "--cpu-rehearsal`.", file=sys.stderr)
        return 2
    if platform == "tpu" and args.cpu_rehearsal:
        print("chip_smoke: --cpu-rehearsal on a TPU backend", file=sys.stderr)
        return 2
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees {count} "
              f"device(s)", file=sys.stderr)
        return 2
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    sizes = REHEARSAL if args.cpu_rehearsal else FULL
    env = {
        "label": f"{platform}:{kind}x{count}",
        # a TPU holds an f64 as two f32 (common/floatbits.py): ~2^-48
        # relative; the CPU is IEEE and must agree with numpy exactly
        "float_rtol": 1e-12 if platform == "tpu" else 0.0,
    }
    emit({"phase": "start", "device": env["label"],
          "rehearsal": bool(args.cpu_rehearsal), "chips": args.chips,
          "jax": jax.__version__, "jaxlib": jaxlib.__version__,
          "libtpu": libtpu, "compile_cache_dir": cache_dir,
          "compile_cache_warm": cache_warm,
          "native_row_codec": native.lib() is not None,
          "sizes": {k: v for k, v in sizes.items()
                    if (k == "q7_mesh") == (args.chips == 4)}})

    phases = ([phase_q7_mesh] if args.chips == 4
              else [phase_q1, phase_q7, phase_q5, phase_q8, phase_q17])
    if args.phases:
        want = set(args.phases.split(","))
        phases = [ph for ph in phases
                  if ph.__name__.removeprefix("phase_") in want]
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.perf_counter()
    try:
        for ph in phases:
            key = ph.__name__.removeprefix("phase_")
            asyncio.run(ph(sizes[key], root, env))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    emit({"phase": "done", "device": env["label"],
          "wall_s": round(time.perf_counter() - t0, 3)})
    last = {"ok": True, "device": {"platform": platform, "kind": kind,
                                   "count": count}}
    if args.phases:
        last = {"ok": True, "partial": [ph.__name__ for ph in phases],
                "device": last["device"]}
    emit(last)
    return 0


if __name__ == "__main__":
    sys.exit(main())
