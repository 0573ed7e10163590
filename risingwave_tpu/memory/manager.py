"""MemoryManager — the per-coordinator HBM budget authority.

Reference: the compute-node memory controller (src/compute/src/memory/
controller.rs) — a control loop that watches total memory against a
budget and tells the executor LRU caches how far to evict. Here the loop
runs at barrier collection (meta/barrier_manager.py calls `on_barrier`
once per completed epoch, when every executor is idle between epochs):

  * accounting is ALWAYS on — `state_bytes()` is pure host arithmetic
    over static pytree shapes, so per-executor and global gauges update
    every barrier at zero device cost;
  * eviction runs only when `hbm_budget_bytes > 0` and
    `memory_eviction_policy == 'lru'`: the worst offenders (largest
    accounted state) are asked to `memory_evict(target_bytes, epoch)`
    until the overage is covered, and occupancy-driven participants
    (dense sorted stores with fixed capacity) get a `memory_maintain`
    tick to spill ahead of their overflow cliff.

Participants are duck-typed executors:
  state_bytes() -> int                      required (registration key)
  memory_evict(target, epoch) -> int freed  optional (budget eviction)
  memory_maintain(epoch) -> None            optional (occupancy spill)
  memory_enable_lru() -> None               optional (start LRU tracking)
plus optional counters read for reports: mem_evicted_bytes,
mem_reload_count, mem_spilled_rows.
"""

from __future__ import annotations

from typing import Optional

from ..utils.metrics import (
    GLOBAL_METRICS, HBM_BUDGET_BYTES, HBM_EVICTED_BYTES, HBM_EVICTIONS,
    HBM_GUARD_PROTECTED, HBM_RELOADS, HBM_SPILLED_ROWS, HBM_STATE_BYTES,
)
from .accounting import format_bytes

POLICY_LRU = "lru"
POLICY_NONE = "none"


class ReloadGuard:
    """Reload-LFU guard (ROADMAP open item): probe-hot-but-never-dirty
    keys look cold to the dirty-bitmap LRU — they get evicted, the next
    probe reloads them, their fresh stamp ages out, and the cycle
    repeats, thrashing the host spill. The guard tracks read-through
    reloads per (executor scope, key); a key reloaded >= `threshold`
    times within the last `window` barriers is EXEMPT from the next
    eviction round — the executor keeps it device-resident (re-inserts
    it) instead of spilling.

    `scope` is any hashable the executor chooses (hash_agg uses
    `id(self)`) so key tuples never collide across executors.
    `window=0` disables the guard."""

    _MAX_EVENTS_PER_KEY = 4

    def __init__(self, window: int = 8, threshold: int = 2):
        self.window = int(window)
        self.threshold = int(threshold)
        self._seq = 0
        self._events: dict = {}       # scope -> {key: [barrier seq, ...]}
        self.protected_total = 0

    def on_barrier(self) -> None:
        self._seq += 1
        if self.window > 0 and self._seq % (2 * self.window) == 0:
            self._prune()

    def note(self, scope, keys) -> None:
        """Record a read-through reload of `keys` in `scope`."""
        if self.window <= 0:
            return
        d = self._events.setdefault(scope, {})
        for k in keys:
            lst = d.setdefault(k, [])
            lst.append(self._seq)
            if len(lst) > self._MAX_EVENTS_PER_KEY:
                del lst[:-self._MAX_EVENTS_PER_KEY]

    def is_protected(self, scope, key) -> bool:
        if self.window <= 0:
            return False
        lst = self._events.get(scope, {}).get(key)
        if not lst:
            return False
        lo = self._seq - self.window
        return sum(1 for s in lst if s >= lo) >= self.threshold

    def note_protected(self, n: int = 1) -> None:
        self.protected_total += n
        HBM_GUARD_PROTECTED.inc(n)

    def _prune(self) -> None:
        lo = self._seq - self.window
        for scope in list(self._events):
            d = self._events[scope]
            for k in [k for k, lst in d.items() if lst[-1] < lo]:
                del d[k]
            if not d:
                del self._events[scope]


def partition_budget(total_bytes: int, n_workers: int) -> int:
    """Cluster HBM budget -> per-worker share (cluster/meta_service.py):
    a cluster-level `SET hbm_budget_bytes` is an even split over the
    live compute nodes — contiguous vnode ranges give every worker the
    same expected state share, so an even split is the placement-
    matched policy. 0 (accounting only) stays 0 everywhere."""
    if total_bytes <= 0:
        return 0
    return max(1, int(total_bytes) // max(1, n_workers))


class MemoryManager:
    def __init__(self, budget_bytes: int = 0, policy: str = POLICY_LRU,
                 guard_window: int = 8, guard_threshold: int = 2):
        self.budget_bytes = int(budget_bytes)
        self.policy = policy
        self._participants: dict[str, object] = {}
        self.evictions = 0
        # reload-LFU guard shared by every participant (set on them as
        # `mem_guard` at registration)
        self.reload_guard = ReloadGuard(guard_window, guard_threshold)

    # ---------------------------------------------------------- config
    @property
    def enabled(self) -> bool:
        return self.budget_bytes > 0 and self.policy == POLICY_LRU

    def configure(self, budget_bytes: Optional[int] = None,
                  policy: Optional[str] = None) -> None:
        """SET hbm_budget_bytes / memory_eviction_policy (the ALTER SYSTEM
        analogue). Enabling starts LRU tracking on every registered
        participant; disabling stops NEW evictions but already-spilled
        state keeps its read-through reload path (dropping it would lose
        exactness)."""
        was = self.enabled
        if budget_bytes is not None:
            self.budget_bytes = int(budget_bytes)
        if policy is not None:
            if policy not in (POLICY_LRU, POLICY_NONE):
                raise ValueError(
                    f"unknown memory_eviction_policy {policy!r} "
                    f"(expected 'lru' or 'none')")
            self.policy = policy
        HBM_BUDGET_BYTES.set(float(self.budget_bytes))
        if self.enabled and not was:
            for p in self._participants.values():
                enable = getattr(p, "memory_enable_lru", None)
                if enable is not None:
                    enable()

    # ------------------------------------------------------ registration
    def register(self, name: str, participant) -> str:
        """Register a stateful executor; returns the (uniquified) name
        used for per-executor metrics and EXPLAIN output."""
        base, i = name, 1
        while name in self._participants:
            i += 1
            name = f"{base}#{i}"
        self._participants[name] = participant
        try:
            participant.mem_guard = self.reload_guard
            # what the participant labels its own series with, so that
            # unregister() finds them
            participant.mem_name = name
        except AttributeError:
            pass
        if self.enabled:
            enable = getattr(participant, "memory_enable_lru", None)
            if enable is not None:
                enable()
        return name

    def unregister(self, name: str) -> None:
        p = self._participants.pop(name, None)
        if p is not None:
            # drop every gauge under the name entirely (hbm_state_bytes
            # from here, what the executor set itself) — a dead executor
            # must not linger in every future scrape
            for key in [k for k in GLOBAL_METRICS.gauges
                        if ("executor", name) in k[1]]:
                GLOBAL_METRICS.gauges.pop(key, None)

    # --------------------------------------------------------- reporting
    def total_bytes(self) -> int:
        return sum(p.state_bytes() for p in self._participants.values())

    def report(self) -> list[dict]:
        """Per-executor accounting rows (\\metrics / EXPLAIN / SHOW)."""
        rows = []
        for name, p in sorted(self._participants.items(),
                              key=lambda kv: -kv[1].state_bytes()):
            row = {
                "executor": name,
                "state_bytes": p.state_bytes(),
                "evicted_bytes": int(getattr(p, "mem_evicted_bytes", 0)),
                "reload_count": int(getattr(p, "mem_reload_count", 0)),
                "spilled_rows": int(getattr(p, "mem_spilled_rows", 0)),
                "guard_protected": int(
                    getattr(p, "mem_guard_protected", 0)),
            }
            # mesh-sharded executors split their state evenly over the
            # device mesh: surface the per-shard (= per-device HBM) share
            shards = int(getattr(p, "mem_shards", 0) or 0)
            if shards > 1:
                row["shards"] = shards
                row["shard_bytes"] = row["state_bytes"] // shards
            rows.append(row)
        return rows

    def render(self) -> list[str]:
        lines = [f"hbm budget: "
                 f"{format_bytes(self.budget_bytes) if self.budget_bytes else 'unset'}"
                 f" policy: {self.policy} "
                 f"total: {format_bytes(self.total_bytes())}"]
        for r in self.report():
            shards = (f" shards={r['shards']}x"
                      f"{format_bytes(r['shard_bytes'])}"
                      if r.get("shards") else "")
            lines.append(
                f"  {r['executor']}: state={format_bytes(r['state_bytes'])} "
                f"evicted={format_bytes(r['evicted_bytes'])} "
                f"reloads={r['reload_count']} "
                f"spilled_rows={r['spilled_rows']} "
                f"guard_protected={r['guard_protected']}{shards}")
        return lines

    # ------------------------------------------------------ control loop
    def on_barrier(self, epoch: int) -> None:
        """Barrier-collection hook: refresh gauges; under an exceeded
        budget, ask the worst offenders to evict. Runs synchronously on
        the event loop with no barrier in flight (meta/barrier_manager.py
        skips the pulse otherwise), so no executor is parked inside its
        barrier handling — eviction dispatches device programs and
        (rarely) blocks the loop on a packed d2h fetch: off the barrier
        path, and counted in `d2h_wait_on_loop_seconds_total`."""
        if not self._participants:
            return
        self.reload_guard.on_barrier()
        total = 0
        spilled = 0
        for name, p in self._participants.items():
            b = p.state_bytes()
            total += b
            spilled += int(getattr(p, "mem_spilled_rows", 0))
            GLOBAL_METRICS.gauge("hbm_state_bytes", executor=name).set(
                float(b))
        HBM_STATE_BYTES.set(float(total))
        HBM_SPILLED_ROWS.set(float(spilled))
        HBM_BUDGET_BYTES.set(float(self.budget_bytes))
        if not self.enabled:
            return
        # occupancy-driven participants spill ahead of their cliff even
        # when the global budget still has headroom
        for p in self._participants.values():
            maintain = getattr(p, "memory_maintain", None)
            if maintain is not None:
                maintain(epoch)
        over = total - self.budget_bytes
        if over <= 0:
            return
        # worst offenders first (largest accounted state)
        for name, p in sorted(self._participants.items(),
                              key=lambda kv: -kv[1].state_bytes()):
            evict = getattr(p, "memory_evict", None)
            if evict is None:
                continue
            freed = int(evict(over, epoch) or 0)
            if freed > 0:
                self.evictions += 1
                HBM_EVICTIONS.inc()
                HBM_EVICTED_BYTES.inc(freed)
                over -= freed
            if over <= 0:
                break

    def note_reload(self, n_keys: int) -> None:
        """Executors report read-through reloads here (process counter;
        their own mem_reload_count feeds the per-executor report)."""
        HBM_RELOADS.inc(n_keys)
