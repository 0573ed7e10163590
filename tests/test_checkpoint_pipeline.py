"""Async epoch uploader (the checkpoint pipeline): seal/upload/commit
phase split, strict in-order manifest swaps, crash safety at every phase
boundary, and the bounded in-flight window's backpressure.

Reference: src/storage/src/hummock/event_handler/uploader/ — epochs seal
at the barrier, SSTs build/upload in background tasks, version commits
apply strictly in epoch order; recovery replays from the last committed
epoch (commit point = manifest swap, unchanged from the inline path).
"""

import asyncio
import threading
import time
from collections import Counter

import pytest

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.connectors import NexmarkGenerator
from risingwave_tpu.connectors.nexmark import NexmarkConfig
from risingwave_tpu.expr.agg import count_star
from risingwave_tpu.meta import BarrierCoordinator
from risingwave_tpu.state import StateTable
from risingwave_tpu.state.hummock import HummockStateStore
from risingwave_tpu.state.object_store import InMemObjectStore
from risingwave_tpu.state.store import WriteBatch
from risingwave_tpu.stream import (
    Actor, HashAggExecutor, HopWindowExecutor, MaterializeExecutor,
    SourceExecutor,
)


def _batch(epoch, table_id=1, **kv):
    puts = {k.encode(): (v.encode() if v is not None else None)
            for k, v in kv.items()}
    return WriteBatch(table_id, epoch, puts)


# ------------------------------------------------------- store-level phases

def test_sealed_batches_stay_readable_until_commit():
    st = HummockStateStore(InMemObjectStore())
    st.ingest_batch(_batch(1, a="1"))
    b1 = st.seal(1)
    # sealed-but-uncommitted: readable via the staging path...
    assert st.get(b"a") == b"1"
    assert list(st.iter_range(b"", b"")) == [(b"a", b"1")]
    # ...but invisible to committed-only readers (serving isolation)
    assert list(st.iter_range(b"", b"", committed_only=True)) == []
    assert st.committed_epoch() == 0
    st.upload_sealed(b1)
    st.commit_sealed(b1)
    assert st.committed_epoch() == 1
    assert list(st.iter_range(b"", b"", committed_only=True)) == \
        [(b"a", b"1")]


def test_out_of_order_commit_refused():
    """Epoch N+1's upload finishing first must NOT let it commit first:
    a manifest missing epoch N would lose N forever on a crash."""
    st = HummockStateStore(InMemObjectStore())
    st.ingest_batch(_batch(1, a="1"))
    b1 = st.seal(1)
    st.ingest_batch(_batch(2, b="2"))
    b2 = st.seal(2)
    # uploads race: epoch 2's SST lands before epoch 1's
    st.upload_sealed(b2)
    st.upload_sealed(b1)
    with pytest.raises(AssertionError, match="seal order"):
        st.commit_sealed(b2)
    assert st.committed_epoch() == 0          # nothing torn
    st.commit_sealed(b1)
    st.commit_sealed(b2)
    assert st.committed_epoch() == 2
    assert st.get(b"a") == b"1" and st.get(b"b") == b"2"


def test_crash_after_seal_before_commit_replays_exactly_once():
    """Kill after seal (+upload) but before the manifest swap: a reopen
    recovers the last committed epoch; the orphan SST is invisible; the
    fail-stop replay of the lost epoch commits it exactly once."""
    objs = InMemObjectStore()
    st = HummockStateStore(objs)
    st.ingest_batch(_batch(1, a="1"))
    st.sync(1)
    st.ingest_batch(_batch(2, b="2", a="1b"))
    b2 = st.seal(2)
    st.upload_sealed(b2)      # SST uploaded, manifest NOT swapped: "crash"

    st2 = HummockStateStore.open(objs)
    assert st2.committed_epoch() == 1
    assert st2.get(b"b") is None              # orphan SST invisible
    assert st2.get(b"a") == b"1"
    # replay the lost epoch (fail-stop recovery re-runs it from source)
    st2.ingest_batch(_batch(2, b="2", a="1b"))
    st2.sync(2)
    assert st2.committed_epoch() == 2
    assert st2.get(b"a") == b"1b" and st2.get(b"b") == b"2"
    # no dupes: exactly one version of each key in the committed view
    committed = list(st2.iter_range(b"", b"", committed_only=True))
    assert committed == [(b"a", b"1b"), (b"b", b"2")]


def test_reset_uncommitted_drops_sealed_queue():
    st = HummockStateStore(InMemObjectStore())
    st.ingest_batch(_batch(1, a="1"))
    st.seal(1)
    st.reset_uncommitted()
    assert st.get(b"a") is None
    assert not st._sealed


# --------------------------------------------------- engine-level pipeline

class SlowObjectStore:
    """Fixed per-SST upload delay — lets the tests below observe sealed-
    but-uncommitted windows deterministically. Built on the event loop's
    thread: `loop_uploads` counts the SST uploads that ran ON it, which
    is where a barrier's collect runs them and the background uploader
    never does."""

    def __init__(self, inner, delay_s: float):
        self._inner = inner
        self.delay_s = delay_s
        self.sst_uploads = 0
        self.loop_uploads = 0
        self._loop_thread = threading.get_ident()

    def upload(self, name, data):
        if name.startswith("ssts/"):
            self.sst_uploads += 1
            self.loop_uploads += threading.get_ident() == self._loop_thread
            time.sleep(self.delay_s)
        return self._inner.upload(name, data)

    def __getattr__(self, name):
        return getattr(self._inner, name)


SLIDE_US = 2_000_000
SIZE_US = 10_000_000
CFG = NexmarkConfig(inter_event_us=50_000)


def _build_q5(store):
    barrier_q = asyncio.Queue()
    gen = NexmarkGenerator("bid", chunk_size=128, cfg=CFG)
    offsets = StateTable(
        store, table_id=1,
        schema=schema(("source_id", DataType.INT64),
                      ("offset", DataType.INT64)),
        pk_indices=[0])
    src = SourceExecutor(1, gen, barrier_q, state_table=offsets)
    hop = HopWindowExecutor(src, time_col=5, window_slide_us=SLIDE_US,
                            window_size_us=SIZE_US)
    agg_table = StateTable(
        store, table_id=2,
        schema=schema(("auction", DataType.INT64),
                      ("ws", DataType.TIMESTAMP),
                      ("count", DataType.INT64),
                      ("_row_count", DataType.INT64)),
        pk_indices=[0, 1])
    agg = HashAggExecutor(hop, group_key_indices=[0, hop.window_start_idx],
                          agg_calls=[count_star(append_only=True)],
                          capacity=1 << 12, state_table=agg_table)
    mv = StateTable(store, table_id=3, schema=agg.schema,
                    pk_indices=list(agg.pk_indices))
    mat = MaterializeExecutor(agg, mv)
    return barrier_q, gen, mat, mv


def _oracle_q5(offset):
    regen = NexmarkGenerator("bid", chunk_size=128, cfg=CFG)
    expect = Counter()
    while regen.offset < offset:
        cols, _ = regen.next_chunk().to_numpy()
        for a, t in zip(cols[0].tolist(), cols[5].tolist()):
            base = (t // SLIDE_US) * SLIDE_US
            for k in range(SIZE_US // SLIDE_US):
                ws = base - k * SLIDE_US
                if t < ws + SIZE_US:
                    expect[(a, ws)] += 1
    return dict(expect)


async def _run_measured(max_inflight: int, delay_s: float = 0.05):
    """Warmed-up q5 run over a slow object store; returns (coord, store,
    mv, gen, the slow object store, the measured barriers' latencies in
    ns, max in-flight depth observed)."""
    slow = SlowObjectStore(InMemObjectStore(), delay_s=delay_s)
    store = HummockStateStore(slow)
    barrier_q, gen, mat, mv = _build_q5(store)
    coord = BarrierCoordinator(store, checkpoint_max_inflight=max_inflight)
    coord.register_source(barrier_q)
    coord.register_actor(1)
    task = Actor(1, mat, None, coord).spawn()
    await coord.run_rounds(3)          # Initial + warmup (compile)
    n_warm = len(coord.latencies_ns)
    saw_inflight = 0
    for _ in range(6):
        b = await coord.inject_barrier()
        await coord.wait_collected(b)
        saw_inflight = max(saw_inflight, coord._inflight)
    measured = coord.latencies_ns[n_warm:]
    await coord.stop_all({1})
    await task
    return coord, store, mv, gen, slow, measured, saw_inflight


async def test_pipelined_run_commits_in_order_and_converges():
    """Full engine over a slow object store: the upload left the
    barrier's critical path, manifest swaps land strictly in epoch
    order, and the drained result matches the exactly-once oracle.

    What "left the critical path" means is counted, not timed (two
    p50s on a shared CPU flip): inline, every SST upload runs inside a
    checkpoint barrier's collect — on the loop's thread, so that
    barrier cannot be faster than the store's delay; pipelined, no
    upload ever runs there."""
    _, _, _, _, slow, measured, _ = await _run_measured(0)
    assert slow.loop_uploads >= len(measured)
    assert min(measured) >= slow.delay_s * 1e9, (
        "an inline checkpoint barrier completed faster than its upload")
    coord, store, mv, gen, slow, measured, saw_inflight = \
        await _run_measured(2)
    assert slow.sst_uploads >= len(measured)
    assert slow.loop_uploads == 0, (
        f"{slow.loop_uploads} of {slow.sst_uploads} uploads ran inside "
        f"a barrier's collect")
    assert saw_inflight >= 1, "uploads never overlapped the stream"
    # strict in-order commit, fully drained
    commits = coord.committed_epochs
    assert commits == sorted(commits) and len(set(commits)) == len(commits)
    assert store.committed_epoch() == commits[-1]
    assert not store._sealed
    got = {(r[0], r[1]): r[2] for _, r in mv.iter_all()}
    assert got == _oracle_q5(gen.offset)


async def test_crash_with_inflight_uploads_recovers_exactly_once():
    """Process death while sealed epochs sit in the uploader: the next
    incarnation opens at the last MANIFEST (not the last seal) and
    re-running converges to the exactly-once oracle."""
    objs = InMemObjectStore()
    slow = SlowObjectStore(objs, delay_s=0.05)
    store = HummockStateStore(slow)
    barrier_q, gen, mat, mv = _build_q5(store)
    coord = BarrierCoordinator(store, checkpoint_max_inflight=2)
    coord.register_source(barrier_q)
    coord.register_actor(1)
    task = Actor(1, mat, None, coord).spawn()
    await coord.run_rounds(1)
    for _ in range(3):
        b = await coord.inject_barrier()
        await coord.wait_collected(b)
    # crash NOW: in-flight uploads die with the process (abort, no drain)
    task.cancel()
    try:
        await task
    except (asyncio.CancelledError, Exception):
        pass
    await coord.abort_uploads()
    committed_before = store.committed_epoch()

    # incarnation 2 from the objects alone (anything not in the manifest
    # died with the process; orphan SSTs from killed uploads are invisible)
    store2 = HummockStateStore.open(objs)
    assert store2.committed_epoch() == committed_before
    barrier_q2, gen2, mat2, mv2 = _build_q5(store2)
    coord2 = BarrierCoordinator(store2, checkpoint_max_inflight=2)
    coord2.register_source(barrier_q2)
    coord2.register_actor(1)
    task2 = Actor(1, mat2, None, coord2).spawn()
    await coord2.run_rounds(3)
    await coord2.stop_all({1})
    await task2
    assert gen2.offset > 0
    got = {(r[0], r[1]): r[2] for _, r in mv2.iter_all()}
    assert got == _oracle_q5(gen2.offset)


async def test_backpressure_bounds_inflight_window():
    """checkpoint_max_inflight=1 + slow uploads: injection must wait for
    a free slot (recovery replay distance stays bounded), and the wait is
    accounted as backpressure, never as barrier latency."""
    slow = SlowObjectStore(InMemObjectStore(), delay_s=0.05)
    store = HummockStateStore(slow)
    barrier_q, gen, mat, _ = _build_q5(store)
    coord = BarrierCoordinator(store, checkpoint_max_inflight=1)
    coord.register_source(barrier_q)
    coord.register_actor(1)
    task = Actor(1, mat, None, coord).spawn()
    await coord.run_rounds(1)
    for _ in range(4):
        b = await coord.inject_barrier()
        assert coord._inflight <= 1, "in-flight window exceeded"
        await coord.wait_collected(b)
    assert coord.backpressure_wait_ns > 0, \
        "a 1-deep window over a 50ms store must backpressure injection"
    await coord.stop_all({1})
    await task
    overlap = coord.upload_overlap_pct()
    assert overlap is not None and 0.0 <= overlap <= 100.0


async def test_inline_mode_unchanged():
    """checkpoint_max_inflight=0 restores the synchronous path: sync on
    the barrier, no uploader task, committed epoch advances in step."""
    store = HummockStateStore(InMemObjectStore())
    barrier_q, gen, mat, mv = _build_q5(store)
    coord = BarrierCoordinator(store, checkpoint_max_inflight=0)
    assert not coord.pipelined
    coord.register_source(barrier_q)
    coord.register_actor(1)
    task = Actor(1, mat, None, coord).spawn()
    await coord.run_rounds(3)
    assert coord._uploader_task is None
    assert store.committed_epoch() == coord.committed_epochs[-1]
    await coord.stop_all({1})
    await task
    got = {(r[0], r[1]): r[2] for _, r in mv.iter_all()}
    assert got == _oracle_q5(gen.offset)


async def test_upload_failure_fails_stop_at_next_injection():
    """An object-store failure in the background uploader must surface as
    a coordinator error at the next barrier (fail-stop -> recovery), not
    silently drop the checkpoint."""

    class FailingStore(SlowObjectStore):
        def upload(self, name, data):
            if name.startswith("ssts/"):
                raise IOError("object store down")
            return self._inner.upload(name, data)

    store = HummockStateStore(FailingStore(InMemObjectStore(), 0.0))
    barrier_q, gen, mat, _ = _build_q5(store)
    coord = BarrierCoordinator(store, checkpoint_max_inflight=2)
    coord.register_source(barrier_q)
    coord.register_actor(1)
    task = Actor(1, mat, None, coord).spawn()
    with pytest.raises(RuntimeError, match="upload|sync|checkpoint"):
        # several rounds: the first checkpoint enqueues, its failure
        # parks, the next injection raises
        await coord.run_rounds(4)
    task.cancel()
    try:
        await task
    except (asyncio.CancelledError, Exception):
        pass
    await coord.abort_uploads()


async def test_session_set_plumbs_checkpoint_max_inflight():
    from risingwave_tpu.frontend import Session
    s = Session(store=HummockStateStore(InMemObjectStore()))
    assert s.coord.checkpoint_max_inflight == 2
    await s.execute("SET checkpoint_max_inflight = 4")
    assert s.coord.checkpoint_max_inflight == 4
    assert s.store.defer_enabled
    await s.execute("SET checkpoint_max_inflight = 0")
    assert not s.coord.pipelined
    assert not s.store.defer_enabled
