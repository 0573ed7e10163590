"""SQL-queryable telemetry plane — barrier-paced metrics history
(utils/metrics_history.py) and the rw_* system catalog tables
(frontend/system_tables.py) served through the normal batch pipeline,
plus the labelled-series teardown audit (`labelled_series`).

Contracts under test: history is BOUNDED (fine ring at barrier cadence
+ 1/downsample coarse tier, both capped at `retention`), allowlisted,
interval-paced, and durable across a restart; `SELECT` over rw_metrics
/ rw_actors / rw_fragments / rw_events supports filters, aggregates and
joins exactly like any MV scan; dropping an object removes every
labelled series its lifetime registered."""

import json
import time

import pytest

from risingwave_tpu.frontend import Session
from risingwave_tpu.utils.metrics import GLOBAL_METRICS, MetricsRegistry
from risingwave_tpu.utils.metrics_history import MetricsHistory


# ===================================================================
# history store
# ===================================================================

async def test_history_bounded_ring_and_coarse_tier():
    reg = MetricsRegistry()
    g = reg.gauge("source_lag_rows", source="s", split="0")
    hist = MetricsHistory(registry=reg, interval=1, retention=4,
                          downsample=2)
    for e in range(1, 21):
        g.set(float(e))
        hist.on_barrier(e)
    samples = hist.samples("source_lag_rows", source="s", split="0")
    assert len(samples) <= 2 * 4          # fine + coarse, both capped
    epochs = [e for _, e, _ in samples]
    assert epochs[-4:] == [17, 18, 19, 20]        # fine tier: newest
    # coarse tier: every 2nd evicted sample, itself ring-bounded
    assert epochs[:-4] == [9, 11, 13, 15]
    assert [v for _, _, v in samples] == [float(e) for e in epochs]


async def test_history_interval_allowlist_and_disable():
    reg = MetricsRegistry()
    a = reg.gauge("hbm_state_bytes")
    b = reg.gauge("not_tracked")
    hist = MetricsHistory(registry=reg, interval=2, retention=8)
    for e in range(1, 9):
        a.set(float(e))
        b.set(float(e))
        hist.on_barrier(e)
    # interval=2: pulses 1,3,5,7 sample
    assert [e for _, e, _ in hist.samples("hbm_state_bytes")] \
        == [1, 3, 5, 7]
    assert hist.samples("not_tracked") == []      # not allowlisted
    hist.configure(series="not_tracked")          # custom allowlist
    hist.on_barrier(9)
    assert [e for _, e, _ in hist.samples("not_tracked")] == [9]
    hist.configure(interval=0)                    # sampling off
    hist.on_barrier(10)
    hist.on_barrier(11)
    assert [e for _, e, _ in hist.samples("not_tracked")] == [9]


async def test_history_histogram_expands_to_scalar_series():
    reg = MetricsRegistry()
    h = reg.histogram("meta_barrier_latency_seconds")
    hist = MetricsHistory(registry=reg, interval=1)
    for e in range(1, 4):
        h.observe(0.01 * e)
        hist.on_barrier(e)
    p50 = hist.samples("meta_barrier_latency_seconds_p50")
    cnt = hist.samples("meta_barrier_latency_seconds_count")
    assert len(p50) == 3 and len(cnt) == 3
    assert [v for _, _, v in cnt] == [1.0, 2.0, 3.0]
    assert all(v >= 0.0 for _, _, v in p50)


async def test_history_durable_replay_spans_restart(tmp_path):
    root = str(tmp_path)
    reg = MetricsRegistry()
    g = reg.gauge("hbm_state_bytes")
    hist = MetricsHistory(registry=reg, root=root)
    for e in range(1, 6):
        g.set(float(e * 10))
        hist.on_barrier(e)
    hist.close()
    # a fresh store on the same root replays the crc-framed tail
    h2 = MetricsHistory(registry=MetricsRegistry(), root=root)
    samples = h2.samples("hbm_state_bytes")
    assert [e for _, e, _ in samples] == [1, 2, 3, 4, 5]
    assert [v for _, _, v in samples] == [10.0, 20.0, 30.0, 40.0, 50.0]
    h2.close()


async def test_history_retention_shrink_keeps_newest():
    reg = MetricsRegistry()
    g = reg.gauge("hbm_state_bytes")
    hist = MetricsHistory(registry=reg, retention=16)
    for e in range(1, 11):
        g.set(float(e))
        hist.on_barrier(e)
    hist.configure(retention=3)
    assert [e for _, e, _ in hist.samples("hbm_state_bytes")] \
        == [8, 9, 10]


# ===================================================================
# system catalog tables through the batch pipeline
# ===================================================================

SRC_DDL = ("CREATE SOURCE bid WITH (connector='nexmark', table='bid', "
           "chunk_size=128, rate_limit=256)")


async def test_rw_metrics_sql_filter_group_by_aggregate():
    s = Session()
    await s.execute("SET metric_level = debug")
    await s.execute(SRC_DDL)
    await s.execute(
        "CREATE MATERIALIZED VIEW st_mv AS SELECT auction, price "
        "FROM bid")
    await s.tick(6)
    counts = dict(s.query(
        "SELECT name, count(*) FROM rw_metrics GROUP BY name"))
    assert counts and min(counts.values()) >= 2, counts
    # the acceptance shape: filtered per-actor aggregate
    per_actor = s.query(
        "SELECT actor, max(value) FROM rw_metrics "
        "WHERE name = 'stream_actor_row_count' GROUP BY actor")
    assert per_actor, counts.keys()
    assert all(v is not None and v >= 0 for _, v in per_actor)
    await s.drop_all()
    await s.shutdown()


async def test_rw_actors_fragments_events_and_join():
    s = Session()
    await s.execute(SRC_DDL)
    await s.execute(
        "CREATE MATERIALIZED VIEW st_mv AS SELECT auction, price "
        "FROM bid")
    await s.tick(2)
    actors = s.query("SELECT actor_id, fragment_id FROM rw_actors")
    assert actors and all(a is not None for a, _ in actors)
    frags = s.query(
        "SELECT fragment_id, mv, parallelism FROM rw_fragments")
    assert any(m == "st_mv" for _, m, _ in frags)
    assert all(p >= 1 for _, _, p in frags)
    # rw_* join rw_* through the stock batch join
    joined = s.query(
        "SELECT a.actor_id, f.mv FROM rw_actors AS a "
        "JOIN rw_fragments AS f ON a.fragment_id = f.fragment_id")
    assert joined
    assert {a for a, _ in joined} <= {a for a, _ in actors}
    # rw_events: the durable log as a relation, filterable
    s.event_log.emit("marker", n=7)
    rows = s.query("SELECT worker, kind, details FROM rw_events "
                   "WHERE kind = 'marker'")
    assert len(rows) == 1 and rows[0][0] == "meta"
    assert json.loads(rows[0][2])["n"] == 7
    # rw_recoveries binds (empty — nothing crashed)
    assert s.query("SELECT scope, cause FROM rw_recoveries") == []
    await s.drop_all()
    await s.shutdown()


# ===================================================================
# SHOW events filters (parity with /debug/events)
# ===================================================================

async def test_show_events_kind_since_limit():
    s = Session()
    s.event_log.emit("alpha", n=1)
    time.sleep(0.02)
    cut = time.time()
    s.event_log.emit("beta", n=2)
    s.event_log.emit("alpha", n=3)
    rows = await s.execute("SHOW events KIND 'alpha'")
    assert [r[2] for r in rows] == ["alpha", "alpha"]
    rows = await s.execute("SHOW events KIND 'alpha' LIMIT 1")
    assert len(rows) == 1 and json.loads(rows[0][3])["n"] == 3
    rows = await s.execute(f"SHOW events SINCE {cut:.6f}")
    assert [r[2] for r in rows] == ["beta", "alpha"]
    # clauses compose in any order
    rows = await s.execute(
        f"SHOW events KIND 'alpha' SINCE {cut:.6f} LIMIT 5")
    assert [json.loads(r[3])["n"] for r in rows] == [3]
    await s.shutdown()


# ===================================================================
# teardown audit — labelled series die with their owners
# ===================================================================

async def test_serving_cache_gauge_removed_on_drop():
    s = Session()
    await s.execute("CREATE TABLE t (a int64, b int64)")
    await s.execute("INSERT INTO t VALUES (1, 10)")
    await s.tick(2)
    s.query("SELECT a, b FROM t")         # first touch marks wanted
    await s.tick(1)                       # next barrier builds cache
    key = ("serving_cache_rows", (("mv", "t"),))
    assert key in GLOBAL_METRICS.labelled_series("serving_cache_rows")
    await s.drop_all()
    assert key not in GLOBAL_METRICS.labelled_series(
        "serving_cache_rows")
    await s.shutdown()


async def test_retention_floor_gauge_dropped_with_source():
    from risingwave_tpu.state.compactor import BackgroundCompactor

    class _Store:
        def l0_run_count(self):
            return 0

        def read_amp(self):
            return 0.0

    c = BackgroundCompactor(_Store())
    key = ("retention_floor_epoch", (("source", "sub:x"),))
    c.pins.floors = lambda: {"serving": None, "sub:x": 7}
    c._pulse(1)
    assert key in GLOBAL_METRICS.labelled_series("retention_floor_epoch")
    c.pins.floors = lambda: {"serving": None}     # subscription dropped
    c._pulse(2)
    assert key not in GLOBAL_METRICS.labelled_series(
        "retention_floor_epoch")


@pytest.mark.parametrize("flow", ["stateless", "join"])
async def test_no_labelled_series_leak_after_drop_all(flow):
    """The audit itself: a full create/tick/drop cycle must leave ZERO
    new labelled gauge/histogram series behind — anything in the diff
    is stale point-in-time state some teardown path forgot to
    `GLOBAL_METRICS.remove`. Cumulative counters are exempt: totals
    stay meaningful after a drop (and tests elsewhere read them).
    `join`: a sorted join sets `join_live_rows{executor,side}` from its
    own watchdog fetch; the memory manager's unregister takes it away."""
    audit = ("gauge", "histogram")
    before = GLOBAL_METRICS.labelled_series(kinds=audit)
    s = Session()
    await s.execute("SET metric_level = debug")
    if flow == "join":
        for table, rows in (("person", 128), ("auction", 384)):
            await s.execute(
                f"CREATE SOURCE {table} WITH (connector='nexmark', "
                f"table='{table}', primary_key='id', chunk_size={rows}, "
                f"rate_limit={2 * rows}, emit_watermarks=1)")
        await s.execute(
            "CREATE MATERIALIZED VIEW lk AS SELECT P.id, P.window_start "
            "FROM TUMBLE(person, date_time, 10000000) P "
            "JOIN TUMBLE(auction, date_time, 10000000) A "
            "ON P.id = A.seller AND P.window_start = A.window_start")
    else:
        await s.execute(SRC_DDL)
        await s.execute(
            "CREATE MATERIALIZED VIEW lk AS SELECT auction FROM bid")
    await s.tick(3)
    if flow == "join":
        assert GLOBAL_METRICS.labelled_series("join_live_rows") - before
    await s.drop_all()
    await s.shutdown()
    leaked = GLOBAL_METRICS.labelled_series(kinds=audit) - before
    assert not leaked, sorted(leaked)
