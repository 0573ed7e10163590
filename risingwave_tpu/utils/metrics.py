"""Metrics registry — counters, gauges, histograms with labels.

Reference: Prometheus metrics everywhere (`StreamingMetrics` ~150 series,
src/stream/src/executor/monitor/streaming_stats.rs; `MetricLevel` gating;
docs/metrics.md defines barrier latency as THE health metric). This is the
same shape without a Prometheus dependency: a process-local registry whose
`snapshot()`/`render()` can feed any scraper, plus the headline series
pre-registered (source throughput, barrier latency histogram, actor rows).
"""

from __future__ import annotations

import bisect
import threading
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence


class Counter:
    def __init__(self):
        self._v = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._v += amount

    @property
    def value(self) -> float:
        return self._v


class Gauge:
    """Thread-safe gauge with set/inc/dec. Worker threads mutate gauges
    too (serving/pool.py admission accounting runs from done-callbacks
    racing the loop), so the read-modify-write of inc/dec must hold a
    lock — a bare `self.value += x` from two threads loses updates."""

    def __init__(self):
        self._v = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._v = float(v)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._v += amount

    def dec(self, amount: float = 1.0) -> None:
        with self._lock:
            self._v -= amount

    @property
    def value(self) -> float:
        return self._v


class Histogram:
    """Fixed-bucket histogram (Prometheus semantics: cumulative buckets)."""

    DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0,
                       2.5, 5.0, 10.0)

    def __init__(self, buckets: Optional[Sequence[float]] = None):
        self.buckets = tuple(buckets or self.DEFAULT_BUCKETS)
        self.counts = [0] * (len(self.buckets) + 1)
        self.sum = 0.0
        self.n = 0
        # largest observation ever seen: quantiles that land in the
        # +Inf overflow bucket report this instead of silently clamping
        # to buckets[-1] (which under-reported every outlier)
        self.max = 0.0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        with self._lock:
            i = bisect.bisect_left(self.buckets, v)
            self.counts[i] += 1
            self.sum += v
            self.n += 1
            if v > self.max:
                self.max = v

    def percentile(self, p: float) -> float:
        """Approximate percentile from bucket boundaries; quantiles that
        fall in the overflow (+Inf) bucket return the observed max."""
        if self.n == 0:
            return 0.0
        target = p * self.n
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= target:
                return (self.buckets[i] if i < len(self.buckets)
                        else self.max)
        return self.max


def escape_label_value(v) -> str:
    """Prometheus exposition label-value escaping: backslash, double
    quote and newline must be escaped or the line is unparseable."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(labels) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{escape_label_value(v)}"' for k, v in labels)
    return "{" + inner + "}"


def _histogram_lines(name: str, labels, h: "Histogram") -> list[str]:
    """Cumulative bucket/sum/count lines for one histogram series — the
    ONE place the exposition bucket format lives (render and
    render_prometheus both consume it)."""
    lines = []
    acc = 0
    for b, cnt in zip(h.buckets, h.counts):
        acc += cnt
        lab = dict(labels)
        lab["le"] = b
        lines.append(f"{name}_bucket{_fmt_labels(sorted(lab.items()))} {acc}")
    lab = dict(labels)
    lab["le"] = "+Inf"   # required by histogram_quantile
    lines.append(f"{name}_bucket{_fmt_labels(sorted(lab.items()))} {h.n}")
    lines.append(f"{name}_sum{_fmt_labels(labels)} {h.sum}")
    lines.append(f"{name}_count{_fmt_labels(labels)} {h.n}")
    return lines


@dataclass
class MetricsRegistry:
    counters: dict = field(default_factory=dict)
    gauges: dict = field(default_factory=dict)
    histograms: dict = field(default_factory=dict)

    def counter(self, name: str, **labels) -> Counter:
        key = (name, tuple(sorted(labels.items())))
        if key not in self.counters:
            self.counters[key] = Counter()
        return self.counters[key]

    def gauge(self, name: str, **labels) -> Gauge:
        key = (name, tuple(sorted(labels.items())))
        if key not in self.gauges:
            self.gauges[key] = Gauge()
        return self.gauges[key]

    def histogram(self, name: str,
                  buckets: Optional[Sequence[float]] = None,
                  **labels) -> Histogram:
        key = (name, tuple(sorted(labels.items())))
        if key not in self.histograms:
            self.histograms[key] = Histogram(buckets)
        return self.histograms[key]

    def labelled_series(self, prefix: str = "",
                        kinds=("counter", "gauge", "histogram")) -> set:
        """Every (name, labels) key with a NON-empty label set, as
        `(name, (("k","v"), ...))` tuples. The teardown-audit surface:
        tests snapshot this before a create/…/drop cycle and diff after
        — anything new is a series some teardown path forgot to
        `remove()` and /metrics would grow by forever. `kinds` narrows
        the audit: cumulative counters conventionally outlive their
        emitter (totals stay meaningful after a drop), so leak checks
        usually pass kinds=("gauge", "histogram")."""
        by_kind = {"counter": self.counters, "gauge": self.gauges,
                   "histogram": self.histograms}
        out = set()
        for k in kinds:
            for name, labels in by_kind[k]:
                if labels and name.startswith(prefix):
                    out.add((name, labels))
        return out

    def remove(self, name: str, **labels) -> None:
        """Drop one series (all kinds) — dead actors must not linger in
        scrapes forever (stream/monitor.py unregisters through here)."""
        key = (name, tuple(sorted(labels.items())))
        self.counters.pop(key, None)
        self.gauges.pop(key, None)
        self.histograms.pop(key, None)

    # ------------------------------------------------------------ export
    def snapshot(self) -> dict:
        out = {}
        for (name, labels), c in self.counters.items():
            out.setdefault(name, []).append(
                {"labels": dict(labels), "value": c.value})
        for (name, labels), g in self.gauges.items():
            out.setdefault(name, []).append(
                {"labels": dict(labels), "value": g.value})
        for (name, labels), h in self.histograms.items():
            out.setdefault(name, []).append(
                {"labels": dict(labels), "count": h.n, "sum": h.sum,
                 "p50": h.percentile(0.5), "p99": h.percentile(0.99)})
        return out

    def render(self) -> str:
        """Prometheus text exposition (scraper-compatible)."""
        lines = []
        for (name, labels), c in sorted(self.counters.items()):
            lines.append(f"{name}{_fmt_labels(labels)} {c.value}")
        for (name, labels), g in sorted(self.gauges.items()):
            lines.append(f"{name}{_fmt_labels(labels)} {g.value}")
        for (name, labels), h in sorted(self.histograms.items()):
            lines.extend(_histogram_lines(name, labels, h))
        return "\n".join(lines) + "\n"

    def render_prometheus(self) -> str:
        """Full Prometheus text format WITH `# TYPE` metadata, one family
        block per metric name — the exposition a real scrape endpoint (or
        `curl | promtool check metrics`) expects. `render()` stays the
        terse label-value dump for the REPL; this is the export surface
        (the `\\metrics prom` verb and the monitor HTTP `/metrics`)."""
        by_family: dict[str, tuple[str, list[str]]] = {}

        def family(name: str, typ: str) -> list[str]:
            if name not in by_family:
                by_family[name] = (typ, [])
            return by_family[name][1]

        for (name, labels), c in sorted(self.counters.items()):
            family(name, "counter").append(
                f"{name}{_fmt_labels(labels)} {c.value}")
        for (name, labels), g in sorted(self.gauges.items()):
            family(name, "gauge").append(
                f"{name}{_fmt_labels(labels)} {g.value}")
        for (name, labels), h in sorted(self.histograms.items()):
            family(name, "histogram").extend(
                _histogram_lines(name, labels, h))
        lines = []
        for name, (typ, rows) in sorted(by_family.items()):
            lines.append(f"# TYPE {name} {typ}")
            lines.extend(rows)
        return "\n".join(lines) + "\n"


# the process-default registry (reference GLOBAL_METRICS_REGISTRY)
GLOBAL_METRICS = MetricsRegistry()

# Pre-registered process totals for the jitted step programs (incremented
# by ops/jit_state.py — one compile per traced signature, one dispatch per
# program invocation; per-program labelled series ride alongside). The
# north-star queries are host-dispatch-bound, so dispatches per barrier
# interval and recompiles after warmup are headline health series: they
# always render in `\metrics` / scrapes, even at zero.
JIT_COMPILES = GLOBAL_METRICS.counter("jit_compile_count")
DEVICE_DISPATCHES = GLOBAL_METRICS.counter("device_dispatch_count")

# Checkpoint pipeline phases (meta/barrier_manager.py): the old opaque
# `sync_ns` splits into seal (deferred executor flushes + shared-buffer
# seal), upload (SST build + object PUT, runs in background) and commit
# (manifest swap). Always rendered so `\metrics` shows the split even
# before the first checkpoint.
CHECKPOINT_SEAL_SECONDS = GLOBAL_METRICS.histogram(
    "checkpoint_seal_seconds")
CHECKPOINT_UPLOAD_SECONDS = GLOBAL_METRICS.histogram(
    "checkpoint_upload_seconds")
CHECKPOINT_COMMIT_SECONDS = GLOBAL_METRICS.histogram(
    "checkpoint_commit_seconds")
# sealed-but-uncommitted epochs currently in the background uploader
CHECKPOINT_INFLIGHT = GLOBAL_METRICS.gauge("checkpoint_inflight_epochs")
# time barrier injection spent waiting for a free in-flight slot
CHECKPOINT_BACKPRESSURE_SECONDS = GLOBAL_METRICS.counter(
    "checkpoint_backpressure_seconds_total")

# Device->host transfer accounting (utils/d2h.py packs every persist
# payload through fetch_columns): bytes moved and fetch calls made — the
# durable bench's d2h_bytes_per_s comes from here.
D2H_BYTES = GLOBAL_METRICS.counter("d2h_bytes_total")
D2H_FETCHES = GLOBAL_METRICS.counter("d2h_fetch_count")
# seconds a `d2h_wait` (utils/d2h.py) was taken ON the event-loop thread,
# where it holds every actor and the uploader: 0 on the barrier path
D2H_WAIT_ON_LOOP_SECONDS = GLOBAL_METRICS.counter(
    "d2h_wait_on_loop_seconds_total")

# Hash-table probe (ops/hash_table._probe): rows that needed more than the
# fingerprint lane and one key verify. Counted on the device; HashAgg
# publishes it with its per-barrier watchdog fetch. Expected 0 in real runs
# (~1 per 10^8 probed rows); a steady rate means a degenerate fingerprint.
HASH_PROBE_FALLBACK_ROWS = GLOBAL_METRICS.counter(
    "hash_probe_fallback_rows_total")

# Hash agg (stream/hash_agg.py), labelled only, `executor` = the name the
# memory manager registered the agg under (its `identity` outside a flow);
# all from the agg's one per-barrier watchdog fetch:
# - `hash_agg_emit_rows_total{executor}`: rows the barrier flushes sent
#   downstream (inserts, deletes and both halves of every update pair).
# - `hash_agg_extrema_lossy_groups{executor}`: live groups of a retractable
#   MIN/MAX whose top-K value buffer has dropped an insert: exact only
#   while the buffer does not drain (ops/extrema.py). A gauge; goes when
#   the memory manager unregisters the agg.
# - `hash_agg_extrema_errors_total{executor,kind=underflow|dropped_delete|
#   negative_residue}`: the three fail-stop counts of that buffer's bound
#   (a lossy buffer emptied under live rows; more than K distinct deleted
#   values of one group in one chunk; a delete of an untracked value of a
#   group that is not lossy). Any increase fail-stops the epoch before its
#   checkpoint commits.
# - `hash_agg_evict_groups_total{executor}`: live groups the barrier's
#   watermark cleaning zeroed (they stay as zombie slots until a purge).
# - `hash_agg_purges_total{executor}`: same-capacity rebuilds that dropped
#   the zombies (`_maybe_rebuild_at_barrier`; not from the fetch).
# - `hash_agg_rehash_rows_total{executor}`: groups the barrier's rebuilds
#   (purges and growths) re-inserted, which is what a rebuild costs; the
#   live count of the fetch that decided the rebuild.
HASH_AGG_EMIT_ROWS = "hash_agg_emit_rows_total"
HASH_AGG_EVICT_GROUPS = "hash_agg_evict_groups_total"
HASH_AGG_PURGES = "hash_agg_purges_total"
HASH_AGG_REHASH_ROWS = "hash_agg_rehash_rows_total"
HASH_AGG_EXTREMA_LOSSY_GROUPS = "hash_agg_extrema_lossy_groups"
HASH_AGG_EXTREMA_ERRORS = "hash_agg_extrema_errors_total"

# Sorted join (stream/sorted_join.py), labelled only, `executor` = the
# name the memory manager registered the join under (its `identity` when
# it runs outside a flow):
# - `join_persist_rows_total{executor,side=left|right,op=delete|insert}`:
#   rows each durable flush wrote to the side's state table, from the two
#   counts the persist fetches anyway.
# - `join_live_rows{executor,side}`: rows the side's device pool holds (all
#   shards together on a mesh), from the barrier watchdog's fetch; over the
#   pool's capacity it is the fill. Goes when the memory manager
#   unregisters the join.
# - `join_match_rows_total{executor,side}`: rows the applies of the side's
#   chunks emitted (matches that passed key equality and the condition,
#   and an outer join's NULL rows), and
#   `join_match_buffer_peak{executor,side}`: the most equi-key candidates
#   one chunk of the side found in the last barrier interval — what it
#   asked of its match buffer (`match_factor` x the chunk's width; more
#   than that fail-stops the epoch). Both counted inside the apply and
#   brought by the barrier watchdog's fetch; the mesh join publishes
#   neither.
JOIN_PERSIST_ROWS = "join_persist_rows_total"
JOIN_LIVE_ROWS = "join_live_rows"
JOIN_MATCH_ROWS = "join_match_rows_total"
JOIN_MATCH_BUFFER_PEAK = "join_match_buffer_peak"

# Top-N (stream/retract_top_n.py), labelled only, `executor` = the
# executor's `identity`; all from the barrier watchdog's ONE fetch, so a
# transfer-free top-N (`streaming_watchdog = 0`) publishes none:
# - `top_n_live_rows{executor}`: rows the device store holds once the
#   barrier has pruned it (an append-only input: at most offset + limit a
#   group, which is also what the state table holds; a retracting input:
#   every input row). Over the store's capacity it is the fill.
# - `top_n_emit_rows_total{executor}`: rows the barrier flushes sent
#   downstream: inserts, deletes and both halves of update pairs.
# - `top_n_pruned_rows_total{executor}`: rows an append-only store dropped
#   as beyond rank offset + limit (nothing can promote them again).
# - `top_n_sorted_rows_total{executor}`: rows the barrier intervals SORTED
#   to keep the store ranked: an append-only store (kept in rank order)
#   sorts the chunks it merges, a retracting one its whole capacity at
#   every flush. Over the intervals' rows it says which form ran.
TOP_N_LIVE_ROWS = "top_n_live_rows"
TOP_N_EMIT_ROWS = "top_n_emit_rows_total"
TOP_N_PRUNED_ROWS = "top_n_pruned_rows_total"
TOP_N_SORTED_ROWS = "top_n_sorted_rows_total"

# HBM memory manager (memory/manager.py): exact accounted device-state
# bytes vs. the configured budget, plus eviction/reload activity. The
# global series always render; per-executor `hbm_state_bytes{executor=..}`
# gauges ride alongside once flows register.
HBM_STATE_BYTES = GLOBAL_METRICS.gauge("hbm_state_bytes")
HBM_BUDGET_BYTES = GLOBAL_METRICS.gauge("hbm_budget_bytes")
HBM_EVICTED_BYTES = GLOBAL_METRICS.counter("hbm_evicted_bytes_total")
HBM_EVICTIONS = GLOBAL_METRICS.counter("hbm_evictions_total")
HBM_RELOADS = GLOBAL_METRICS.counter("hbm_reloads_total")
HBM_SPILLED_ROWS = GLOBAL_METRICS.gauge("hbm_spilled_rows")
# keys the reload-LFU guard kept device-resident through an eviction
# round (memory/manager.py ReloadGuard: reloaded >= 2x within the
# barrier window -> exempt from the next eviction)
HBM_GUARD_PROTECTED = GLOBAL_METRICS.counter("hbm_guard_protected_total")

# Serving layer (serving/): the read path's health series. Queries are
# host-side numpy over pinned snapshots, so latency buckets reach well
# below the default 1ms floor — point lookups are tens of microseconds.
SERVING_LATENCY_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025,
                           0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                           1.0, 2.5, 5.0)
SERVING_QUERIES = GLOBAL_METRICS.counter("serving_queries_total")
SERVING_LATENCY = GLOBAL_METRICS.histogram(
    "serving_latency_seconds", buckets=SERVING_LATENCY_BUCKETS)
SERVING_CACHE_HITS = GLOBAL_METRICS.counter("serving_cache_hits_total")
SERVING_CACHE_MISSES = GLOBAL_METRICS.counter(
    "serving_cache_misses_total")
SERVING_POINT_LOOKUPS = GLOBAL_METRICS.counter(
    "serving_point_lookups_total")
SERVING_INFLIGHT = GLOBAL_METRICS.gauge("serving_inflight_queries")
SERVING_ADMISSION_WAIT = GLOBAL_METRICS.counter(
    "serving_admission_wait_seconds_total")
SERVING_TIMEOUTS = GLOBAL_METRICS.counter("serving_timeouts_total")

# Stuck-barrier watchdog (meta/barrier_manager.py): incremented once per
# stalled epoch when an in-flight barrier exceeds
# barrier_stall_threshold_ms; the one-shot report rides stdout/logs.
BARRIER_STALLS = GLOBAL_METRICS.counter("barrier_stalls_total")

# Span log (utils/trace.py): spans the process-wide log let go of — whole
# epochs dropped from its old end when it outgrew its bound, and spans an
# actor recorded past its per-interval cap. 0 in a healthy run: a reader
# of the log that finds an epoch missing looks here first.
TRACE_SPANS_DROPPED = GLOBAL_METRICS.counter("trace_spans_dropped_total")

# Mesh-parallel fragment execution (parallel/exchange.py +
# stream/sharded_*.py, host half in stream/mesh_shuffle.py). The series:
# - `mesh_shuffle_dropped_rows_total`: rows the in-mesh all_to_all
#   shuffle dropped because a (src, dst) send bucket overflowed its
#   per-pair capacity (sized too tight for the key skew). Nonzero is a FAIL-STOP: the owning executor
#   raises at the barrier watchdog fetch before the epoch's checkpoint
#   commits, so a dropped row is never silently absent from durable
#   state.
# - `mesh_shuffle_rows_total`: rows the shards received from the
#   shuffle; `mesh_shuffle_max_shard_rows_total`: per barrier interval
#   the rows of the shard that received most, summed over intervals (so
#   shards x max / rows is the skew: 1 balanced, the shard count when
#   one shard gets everything); `mesh_shuffle_bytes_total`: the bytes
#   the all_to_all buffers held (shards^2 x send capacity x row bytes a
#   chunk, from the traced shapes). Process totals here, and the same
#   names `{executor=...}` per sharded executor with the gauge
#   `mesh_shuffle_max_fill{executor=...}` (largest per-(src, dst) send
#   demand of the last interval: the adaptive slack's signal). All of
#   them ride the watchdog fetch the barrier makes anyway; the labelled
#   ones go when their fragment does.
# - `mesh_fragment_shards{actor=...}`, `mesh_chain_fragments{chain=...}`:
#   set when a fused mesh fragment / chain registers with the barrier
#   coordinator (meta/barrier_manager.py), removed when it is dropped.
MESH_SHUFFLE_DROPPED = GLOBAL_METRICS.counter(
    "mesh_shuffle_dropped_rows_total")
MESH_SHUFFLE_ROWS = GLOBAL_METRICS.counter("mesh_shuffle_rows_total")
MESH_SHUFFLE_MAX_SHARD_ROWS = GLOBAL_METRICS.counter(
    "mesh_shuffle_max_shard_rows_total")
MESH_SHUFFLE_BYTES = GLOBAL_METRICS.counter("mesh_shuffle_bytes_total")
MESH_SHUFFLE_MAX_FILL = "mesh_shuffle_max_fill"        # gauge, labelled only
# name -> process total, of the counters a sharded executor also keeps
# under `{executor=...}`
MESH_SHUFFLE_COUNTERS = {
    "mesh_shuffle_rows_total": MESH_SHUFFLE_ROWS,
    "mesh_shuffle_max_shard_rows_total": MESH_SHUFFLE_MAX_SHARD_ROWS,
    "mesh_shuffle_bytes_total": MESH_SHUFFLE_BYTES,
}

# Recovery plane (frontend/session.py): every auto-recovery increments
# `recovery_total{scope=fragment|cone|mesh|worker|full,cause=...}`
# (labelled series ride alongside these process totals) and observes
# its wall-clock duration; tick's exponential backoff between attempts
# accumulates into the backoff counter. Buckets reach low because a
# per-fragment rebuild on a warm process is milliseconds while a full
# DDL replay is seconds. `recovery_flapping{cause}` flips to 1 when a
# cause recovers more than `recovery_flap_threshold` times within the
# trailing window below — the rate then escalates the backoff base and
# /healthz reports `degraded`.
RECOVERY_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                    5.0, 10.0, 30.0)
RECOVERY_FLAP_WINDOW_S = 30.0
RECOVERY_TOTAL = GLOBAL_METRICS.counter("recovery_total")
RECOVERY_DURATION = GLOBAL_METRICS.histogram(
    "recovery_duration_seconds", buckets=RECOVERY_BUCKETS)
RECOVERY_BACKOFF = GLOBAL_METRICS.counter(
    "recovery_backoff_seconds_total")

# Changelog log store (logstore/): exactly-once egress + subscriptions.
# Bytes staged into the durable per-table logs (sink delivery logs + MV
# changelog logs), epochs/rows the background delivery handed to sink
# targets after commit, and per-subscription lag gauges
# (`logstore_subscription_lag_epochs{subscription=...}`) ride alongside
# once flows register.
LOGSTORE_APPEND_BYTES = GLOBAL_METRICS.counter("logstore_append_bytes_total")

# Fault-tolerant storage plane (state/object_store.py ResilientObjectStore,
# state/hummock.py read-path hardening, state/scrub.py): transient object
# faults are absorbed BELOW the recovery machinery. Per-op labelled series
# `object_store_retries_total{op}` / `object_store_op_seconds{op}` ride
# alongside the process totals; crc-retry counts the read-path's one
# re-read of a checksum-mismatched object before it is declared durably
# corrupt, quarantined and (when a backup is attached) restored.
OBJECT_RETRIES = GLOBAL_METRICS.counter("object_store_retries_total")
OBJECT_TMP_SWEPT = GLOBAL_METRICS.counter("object_store_tmp_swept_total")
STORAGE_CRC_RETRIES = GLOBAL_METRICS.counter(
    "storage_crc_retries_total")
STORAGE_QUARANTINED = GLOBAL_METRICS.gauge("storage_quarantined_objects")
STORAGE_RESTORED = GLOBAL_METRICS.counter(
    "storage_restored_from_backup_total")
# Background scrubber (state/scrub.py, barrier-paced by the coordinator):
# objects verified, corruptions found, orphan SSTs currently visible
# (uploaded by a crashed/aborted checkpoint, referenced by no manifest)
# and orphans actually swept after the two-sighting grace.
STORAGE_SCRUB_PASSES = GLOBAL_METRICS.counter("storage_scrub_passes_total")
STORAGE_SCRUB_OBJECTS = GLOBAL_METRICS.counter(
    "storage_scrub_objects_total")
STORAGE_SCRUB_CORRUPTIONS = GLOBAL_METRICS.counter(
    "storage_scrub_corruptions_total")
STORAGE_ORPHAN_OBJECTS = GLOBAL_METRICS.gauge("storage_orphan_objects")
STORAGE_ORPHANS_SWEPT = GLOBAL_METRICS.counter(
    "storage_orphan_swept_total")
# Backup plane (state/backup.py): generation-stamped incremental backups;
# objects copied vs skipped-as-already-backed-up per run, and the last
# generation written (gauge — SHOW storage reads it too).
BACKUP_OBJECTS_COPIED = GLOBAL_METRICS.counter(
    "backup_objects_copied_total")
BACKUP_OBJECTS_SKIPPED = GLOBAL_METRICS.counter(
    "backup_objects_skipped_total")
BACKUP_GENERATION = GLOBAL_METRICS.gauge("backup_last_generation")

# Compaction & retention plane (state/compactor.py): background merges
# off the commit path. Bytes rewritten + run count are the write-
# amplification record; the L0/read-amp gauges are the health line the
# soak gate asserts bounded; the per-source retention floor gauges show
# WHAT is holding GC back (-1 = source pins nothing).
COMPACTION_RUNS = GLOBAL_METRICS.counter("compaction_runs_total")
COMPACTION_BYTES_REWRITTEN = GLOBAL_METRICS.counter(
    "compaction_bytes_rewritten_total")
COMPACTION_SECONDS = GLOBAL_METRICS.histogram("compaction_seconds")
LSM_L0_RUNS = GLOBAL_METRICS.gauge("lsm_l0_runs")
LSM_READ_AMP = GLOBAL_METRICS.gauge("lsm_read_amp")
# Keys staged into a state store by `ingest_batch`, by the form they arrive
# in: `columnar` = a ColumnarSegment (StateTable.write_chunk_columns, any
# schema: no Python object per key from there to the L0 run), `row` = a
# dict (insert / delete / update / write_chunk_rows, a batch's NULL-pk
# rows, the log store, source offsets). columnar / (columnar + row) is the share of a
# checkpoint's keys on the columnar path.
STATE_WRITE_KEYS = {
    columnar: GLOBAL_METRICS.counter(
        "state_write_keys_total", path="columnar" if columnar else "row")
    for columnar in (True, False)}
RETENTION_SEGMENTS_DROPPED = GLOBAL_METRICS.counter(
    "broker_retention_segments_dropped_total")


def retention_floor_gauge(source: str):
    """Per-pin-source floor gauge `retention_floor_epoch{source=...}` —
    labelled series ride the registry on demand (registry dedups by
    (name, labels), so this is idempotent)."""
    return GLOBAL_METRICS.gauge("retention_floor_epoch", source=source)


# Source split observability (stream/source.py): per-split labelled
# gauges `source_split_offset{source,split}` (rows consumed by the
# split, refreshed at barrier cadence) and `source_lag_rows{source,
# split}` (broker high watermark minus consumed offset, from the
# connector's CACHED watermark — external-ingress backlog). Labelled
# series ride the registry on demand; they die with the executor.
SINK_DELIVERED_EPOCHS = GLOBAL_METRICS.counter("sink_delivered_epochs_total")
SINK_DELIVERED_ROWS = GLOBAL_METRICS.counter("sink_delivered_rows_total")
