"""The drive loop: deploy, warm up, the measured window, commit stamping.

Copied from `chip_smoke.py`'s `deploy` / `wait_quota` / `run_intervals` /
`committed_offsets` (PR 22) and changed where a benchmark differs from a
smoke run: barriers are injected and collected directly on the coordinator
(`Session.tick` drains the uploader every round, so checkpoints would never
overlap), and a checkpoint counts as done when `coord.commit_listener` fires
at the Hummock manifest swap, not when its barrier is collected.
"""

from __future__ import annotations

import asyncio
import os
import time

import numpy as np

from .tracing import span

QUOTA_WAIT_S = 600.0
mono = time.monotonic_ns


# ------------------------------------------------------- the engine's handles

def executors_of(session, mv: str):
    """Every executor deployed under MV `mv`."""
    from risingwave_tpu.plan.build import _iter_executor_chain
    for roots in session.catalog.mvs[mv].deployment.roots.values():
        for root in roots:
            yield from _iter_executor_chain(root)


def sources_of(session, mv: str) -> dict:
    """table -> SourceExecutor feeding MV `mv`."""
    from risingwave_tpu.stream.source import SourceExecutor
    out = {ex.connector.table: ex for ex in executors_of(session, mv)
           if isinstance(ex, SourceExecutor)}
    assert out, f"no source under {mv}"
    return out


def committed_offsets(session, mv: str) -> dict:
    """table -> offset as COMMITTED in the source's durable state table."""
    from risingwave_tpu.state.storage_table import StorageTable
    out = {}
    for table, ex in sources_of(session, mv).items():
        rows = list(StorageTable.for_state_table(ex.state_table).batch_iter())
        out[table] = int(rows[0][1]) if rows else 0
    return out


async def wait_quota(session, mv: str, targets: dict) -> None:
    """Block until every source has emitted up to its row quota for this
    barrier interval (it then parks on the barrier queue), so each interval
    carries exactly `rate_limit` rows whatever the device's speed."""
    srcs = sources_of(session, mv)
    deadline = time.monotonic() + QUOTA_WAIT_S
    while any(srcs[t].connector.offset < n for t, n in targets.items()):
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"{mv}: sources stuck at "
                f"{ {t: srcs[t].connector.offset for t in targets} } "
                f"waiting for {targets}")
        await asyncio.sleep(0.002)


def compiles_by_program() -> dict:
    """program name -> StateJit compile count (ops/jit_state.py)."""
    from risingwave_tpu.utils.metrics import GLOBAL_METRICS
    return {dict(labels)["program"]: int(c.value)
            for (name, labels), c in GLOBAL_METRICS.counters.items()
            if name == "jit_compile_count" and labels}


class BackendCompiles:
    """Compiles of ANY program (StateJit or an eager jnp op with a new
    shape), counted through jax.monitoring: persistent-cache loads count
    too, so this is information on the window line, not a verdict."""

    def __init__(self):
        import jax
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.n += 1
            self.seconds += duration


def read_counters(compiles: BackendCompiles) -> dict:
    from risingwave_tpu.utils import metrics as m
    return {
        "backend_compiles": compiles.n,
        "backend_compile_s": compiles.seconds,
        "jit_compiles": int(m.JIT_COMPILES.value),
        "dispatches": int(m.DEVICE_DISPATCHES.value),
        "d2h_fetches": int(m.D2H_FETCHES.value),
        "d2h_bytes": int(m.D2H_BYTES.value),
        "mesh_shuffle_dropped": int(m.MESH_SHUFFLE_DROPPED.value),
        "barrier_stalls": int(m.BARRIER_STALLS.value),
        "backpressure_s": float(m.CHECKPOINT_BACKPRESSURE_SECONDS.value),
        "seal_s": float(m.CHECKPOINT_SEAL_SECONDS.sum),
        "upload_s": float(m.CHECKPOINT_UPLOAD_SECONDS.sum),
        "commit_s": float(m.CHECKPOINT_COMMIT_SECONDS.sum),
    }


def join_error_counters(session, mv: str) -> dict:
    import jax
    errs = {}
    for ex in executors_of(session, mv):
        e = getattr(ex, "_errs_dev", None)
        if e is not None:
            errs[ex.identity] = [int(x) for x in np.asarray(
                jax.device_get(e)).ravel()]
    return errs


def open_store(path: str, reopen: bool = False):
    from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
    objs = LocalFsObjectStore(path)
    return HummockStateStore.open(objs) if reopen else HummockStateStore(objs)


def store_on_disk(path: str) -> dict:
    sst_dir = os.path.join(path, "ssts")
    return {"manifest": os.path.isfile(os.path.join(path, "MANIFEST")),
            "ssts": len(os.listdir(sst_dir)) if os.path.isdir(sst_dir)
            else 0}


# --------------------------------------------------------- commit stamping

class Stamps:
    """One record per checkpoint the harness injects, completed by
    `coord.commit_listener` at the manifest swap. The epoch's trace span
    (`EpochTracer` keeps only 64) is copied at the same moment."""

    def __init__(self, coord):
        self.coord = coord
        self.by_prev: dict = {}
        self.records: list = []
        coord.commit_listener = self.on_commit

    def injected(self, barrier, rec: dict) -> None:
        rec["epoch"] = barrier.epoch.curr
        self.by_prev[barrier.epoch.prev] = rec
        self.records.append(rec)

    def on_commit(self, epoch_prev: int, _ssts) -> None:
        now = mono()
        rec = self.by_prev.get(epoch_prev)
        if rec is None:
            return
        rec["commit_ns"] = now
        for tr in reversed(self.coord.tracer._ring):
            if tr.epoch == rec["epoch"]:
                rec["phases"] = {a: dict(p) for a, p in tr.phases.items()}
                rec["seal_ns"], rec["upload_ns"] = tr.seal_ns, tr.upload_ns
                rec["store_commit_ns"] = tr.commit_ns
                break


async def checkpoint(session, mv: str, stamps: Stamps, targets: dict,
                     due_ns: int | None = None, tracer=None) -> dict:
    """One checkpoint: wait until every source has emitted its quota, inject
    the barrier (not before `due_ns`), wait until every actor collected it.
    Its durable flush goes on behind; `Stamps` records the commit."""
    coord = session.coord
    rec = {"due_ns": due_ns}
    if due_ns is not None:
        delay = (due_ns - mono()) / 1e9
        if delay > 0:
            with span(tracer, "pace_sleep"):
                await asyncio.sleep(delay)
    with span(tracer, "quota_wait"):
        await wait_quota(session, mv, targets)
    rec["call_ns"] = mono()
    if due_ns is None:
        rec["due_ns"] = rec["call_ns"]
    with span(tracer, "inject"):
        barrier = await coord.inject_barrier()
    rec["inject_ns"] = mono()
    stamps.injected(barrier, rec)
    with span(tracer, "collect_wait"):
        await coord.wait_collected(barrier)
    rec["collected_ns"] = mono()
    rec["collect_latency_ns"] = coord.latencies_ns[-1]
    return rec


# ------------------------------------------------------------------ phases

async def deploy(cell, seed: int, store_path: str):
    """Fresh durable Session over its own Hummock directory, the cell's DDL
    executed, the Initial barrier collected."""
    from risingwave_tpu.frontend import Session
    t0 = mono()
    s = Session(store=open_store(store_path))
    s.coord.checkpoint_frequency = cell.config["checkpoint_frequency"]
    steps = []
    for stmt in cell.query.ddl(cell.config, cell.traffic, seed):
        t = mono()
        await s.execute(stmt)
        steps.append([stmt.split("(")[0].split("=")[0].strip()[:40],
                      (mono() - t) / 1e9])
    t = mono()
    await s.tick(0)
    steps.append(["initial_barrier", (mono() - t) / 1e9])
    return s, (mono() - t0) / 1e9, steps


async def warm_up(session, cell, stamps: Stamps) -> float:
    """`warmup_intervals` checkpoints at the cell's own shapes: every program
    the window uses compiles (or loads from the cache) here."""
    t0 = mono()
    quotas = cell.quotas
    for i in range(cell.traffic["warmup_intervals"]):
        await checkpoint(session, cell.query.MV, stamps,
                         {t: (i + 1) * q for t, q in quotas.items()})
    await session.coord.drain_uploads()
    return (mono() - t0) / 1e9


async def window(session, cell, stamps: Stamps, seconds: float,
                 compiles: BackendCompiles, tracer=None) -> dict:
    """The measured window: whole checkpoints. `sat`: barriers back to back,
    injected while `seconds` have not passed. `paced`: an open loop, barrier
    k due at `t_open + k * barrier_interval_ms`, all of the window's barriers
    injected however late the engine runs. Closes at the commit of the last
    injected checkpoint."""
    mv, quotas = cell.query.MV, cell.quotas
    tr = cell.traffic
    done = tr["warmup_intervals"]
    paced = tr["mode"] == "paced"
    if tr["mode"] not in ("sat", "paced"):
        raise ValueError(f"traffic mode {tr['mode']!r}")
    interval_ns = int(tr["barrier_interval_ms"] * 1e6)
    n_paced = max(1, int(round(seconds * 1e9 / interval_ns))) if paced else 0
    # the first interval's rows are already on their way: the window opens
    # with a barrier that is due now
    await wait_quota(session, mv,
                     {t: (done + 1) * q for t, q in quotas.items()})
    before = read_counters(compiles)
    compiles0 = compiles_by_program()
    first = len(stamps.records)
    t_open = mono()
    k = 0
    while (k < n_paced) if paced else (k == 0
                                       or mono() - t_open < seconds * 1e9):
        if tracer is not None:
            await tracer.at_checkpoint(
                k, k / n_paced if paced
                else (mono() - t_open) / (seconds * 1e9),
                last_chance=paced and k >= n_paced - 2)
        await checkpoint(
            session, mv, stamps,
            {t: (done + k + 1) * q for t, q in quotas.items()},
            due_ns=t_open + k * interval_ns if paced else None,
            tracer=tracer)
        k += 1
    if tracer is not None:
        await tracer.finish(k)
    with span(tracer, "drain_uploads"):
        await session.coord.drain_uploads()
    recs = stamps.records[first:]
    after = read_counters(compiles)
    compiles1 = compiles_by_program()
    committed = [r for r in recs if "commit_ns" in r]
    t_close = max((r["commit_ns"] for r in committed), default=mono())
    return {
        "t_open_ns": t_open, "t_close_ns": t_close,
        "window_s": (t_close - t_open) / 1e9,
        "checkpoints": recs, "attempted": len(recs),
        "freshness_s": [(r["commit_ns"] - r["due_ns"]) / 1e9
                        for r in committed],
        "failed": len(recs) - len(committed),
        "rows_per_checkpoint": sum(quotas.values()),
        "expected_offsets": {t: (done + k) * q for t, q in quotas.items()},
        "counters": {key: after[key] - before[key] for key in after},
        "compiled_in_window": {
            p: n - compiles0.get(p, 0) for p, n in compiles1.items()
            if n != compiles0.get(p, 0)},
    }
