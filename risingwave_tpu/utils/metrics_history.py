"""Barrier-paced metrics history — the time-series substrate behind
`rw_metrics` and the autoscaling signals ROADMAP item 1 needs.

The live `MetricsRegistry` is a point-in-time surface: a scrape sees
NOW and nothing else. Control loops (and post-mortems) need *history* —
`stream_exchange_blocked_put_seconds` over the last minute, per-worker
HBM as a series, `source_lag_rows` trend — so the coordinator samples a
configurable allowlist of series once per barrier interval into bounded
per-series rings. Two tiers per series:

  * fine ring: the newest `retention` samples at barrier cadence;
  * coarse ring: every `downsample`-th sample evicted from the fine
    ring, so a series keeps `retention` recent points at full
    resolution plus `retention` older points at 1/downsample
    resolution before history falls off entirely.

Optionally the sampler also appends one crc-framed record per pulse to
a durable log next to the event log (same torn-tail framing via
`meta/event_log.py`, subdir "metrics"): a restart replays the tail so
`rw_metrics` spans the crash. Sampling never raises into the barrier
path — a broken history store must not stall the pipeline.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional

from .metrics import GLOBAL_METRICS

# series the stall autopsies care about out of the box (the session runs
# the constructor's defaults; `configure(series=...)` overrides the list)
DEFAULT_SERIES = (
    "meta_barrier_latency_seconds",
    "checkpoint_inflight_epochs",
    "stream_exchange_queue_depth",
    "stream_exchange_blocked_put_seconds_total",
    "stream_actor_busy_seconds_total",
    "stream_actor_row_count",
    "source_lag_rows",
    "source_split_offset",
    "hbm_state_bytes",
    "hbm_budget_bytes",
    "hbm_spilled_rows",
    "serving_cache_rows",
    "barrier_stalls_total",
)

# stall-relevant subset of `dump_tail`
STALL_SERIES = (
    "meta_barrier_latency_seconds",
    "checkpoint_inflight_epochs",
    "stream_exchange_queue_depth",
    "stream_exchange_blocked_put_seconds_total",
    "source_lag_rows",
    "hbm_state_bytes",
)


_UNSET = object()


class _Series:
    __slots__ = ("fine", "coarse", "evicted")

    def __init__(self, retention: int):
        self.fine: deque = deque(maxlen=retention)
        self.coarse: deque = deque(maxlen=retention)
        self.evicted = 0

    def append(self, sample, downsample: int) -> None:
        if len(self.fine) == self.fine.maxlen:
            old = self.fine[0]
            if self.evicted % max(1, downsample) == 0:
                self.coarse.append(old)
            self.evicted += 1
        self.fine.append(sample)

    def samples(self) -> list:
        return list(self.coarse) + list(self.fine)


class MetricsHistory:
    """Bounded per-series sample rings fed by `on_barrier(epoch)`.

    Samples are `(ts, epoch, value)` tuples keyed by
    `(name, sorted-label-items)`. Histogram families expand into
    `<name>_p50` / `<name>_p99` / `<name>_count` scalar series so the
    ring only ever holds numbers.
    """

    def __init__(self, registry=None, interval: int = 1,
                 retention: int = 512, downsample: int = 8,
                 series=None, root=None):
        self.registry = registry if registry is not None else GLOBAL_METRICS
        self._lock = threading.Lock()
        self._series: dict = {}
        self._log = None
        self.interval = 1
        self.retention = 512
        self.downsample = 8
        self.allow: tuple = tuple(DEFAULT_SERIES)
        self._pulses = 0
        self.configure(interval=interval, retention=retention,
                       downsample=downsample, series=series, root=root)

    # -------------------------------------------------------- configure
    def configure(self, interval=None, retention=None, downsample=None,
                  series=None, root=_UNSET) -> None:
        """Re-apply knobs; a retention change re-rings existing series
        (keeping the newest samples), a `root` change re-opens (or
        drops) the durable log and replays its tail."""
        with self._lock:
            if interval is not None:
                self.interval = max(0, int(interval))
            if downsample is not None:
                self.downsample = max(1, int(downsample))
            if series is not None:
                names = [s.strip() for s in series.split(",")] \
                    if isinstance(series, str) else list(series)
                names = [s for s in names if s]
                self.allow = tuple(names) if names else tuple(DEFAULT_SERIES)
            if retention is not None and int(retention) != self.retention:
                self.retention = max(2, int(retention))
                for key, ser in list(self._series.items()):
                    fresh = _Series(self.retention)
                    for s in ser.samples()[-self.retention:]:
                        fresh.fine.append(s)
                    fresh.evicted = ser.evicted
                    self._series[key] = fresh
        if root is not _UNSET:
            self._attach_log(root)

    def _attach_log(self, root) -> None:
        from ..meta.event_log import EventLog
        with self._lock:
            if self._log is not None:
                self._log.close()
                self._log = None
            if not root:
                return
            self._log = EventLog(root, keep=2048, subdir="metrics")
            # replay the durable tail so history spans the restart
            for rec in self._log.records(kind="sample"):
                for name, labels, value in rec.get("series", ()):
                    key = (name, tuple(sorted(
                        (str(k), str(v)) for k, v in labels.items())))
                    ser = self._series.get(key)
                    if ser is None:
                        ser = self._series[key] = _Series(self.retention)
                    ser.append((rec.get("ts", 0.0), rec.get("epoch", 0),
                                float(value)), self.downsample)

    # ----------------------------------------------------------- sample
    def on_barrier(self, epoch: int) -> None:
        """One pulse per completed barrier (coordinator's between-epochs
        window). Never raises."""
        try:
            if self.interval <= 0:
                return
            self._pulses += 1
            if (self._pulses - 1) % self.interval != 0:
                return
            self._sample(int(epoch))
        except Exception:
            pass

    def _sample(self, epoch: int) -> None:
        snap = self.registry.snapshot()
        ts = time.time()
        batch = []
        with self._lock:
            for name in self.allow:
                for row in snap.get(name, ()):
                    labels = row.get("labels", {})
                    if "value" in row:
                        pairs = [(name, row["value"])]
                    else:           # histogram family -> scalar series
                        pairs = [(name + "_p50", row.get("p50", 0.0)),
                                 (name + "_p99", row.get("p99", 0.0)),
                                 (name + "_count", row.get("count", 0))]
                    for sname, value in pairs:
                        try:
                            value = float(value)
                        except (TypeError, ValueError):
                            continue
                        key = (sname, tuple(sorted(
                            (str(k), str(v)) for k, v in labels.items())))
                        ser = self._series.get(key)
                        if ser is None:
                            ser = self._series[key] = _Series(self.retention)
                        ser.append((ts, epoch, value), self.downsample)
                        batch.append((sname, labels, value))
            log = self._log
        if log is not None and batch:
            log.emit("sample", epoch=epoch,
                     series=[[n, dict(l), v] for n, l, v in batch])

    # ------------------------------------------------------------ reads
    def series_names(self) -> list:
        with self._lock:
            return sorted({name for name, _ in self._series})

    def samples(self, name: str, **labels) -> list:
        """All retained `(ts, epoch, value)` for one series (coarse tier
        first, then fine), oldest first. Labels must match exactly."""
        key = (name, tuple(sorted(
            (str(k), str(v)) for k, v in labels.items())))
        with self._lock:
            ser = self._series.get(key)
            return ser.samples() if ser is not None else []

    def rows(self) -> list:
        """Flat `{name, labels, ts, epoch, value}` dicts — the relation
        `rw_metrics` scans (frontend/system_tables.py)."""
        with self._lock:
            items = [(name, dict(lbls), ser.samples())
                     for (name, lbls), ser in self._series.items()]
        out = []
        for name, labels, samples in items:
            for ts, epoch, value in samples:
                out.append({"name": name, "labels": labels, "ts": ts,
                            "epoch": epoch, "value": value})
        return out

    def dump_tail(self, names=STALL_SERIES, k: int = 8) -> str:
        """Human-readable last-K-samples digest of the stall-relevant
        series, for a stall autopsy."""
        lines = []
        with self._lock:
            items = sorted(self._series.items())
        for (name, lbls), ser in items:
            if names is not None and not any(
                    name == n or name.startswith(n) for n in names):
                continue
            tail = ser.samples()[-int(k):]
            if not tail:
                continue
            lab = ",".join(f"{k_}={v}" for k_, v in lbls)
            vals = " ".join(f"e{int(e)}:{v:.6g}" for _, e, v in tail)
            lines.append(f"  {name}{{{lab}}} {vals}")
        return "\n".join(lines)

    def __len__(self) -> int:
        with self._lock:
            return len(self._series)

    def close(self) -> None:
        with self._lock:
            if self._log is not None:
                self._log.close()
                self._log = None
