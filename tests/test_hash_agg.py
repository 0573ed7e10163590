"""HashAgg executor: changelog semantics vs a dict-based golden model.

Mirrors the reference's executor-test style (hash_agg.rs #[cfg(test)]):
drive a hand-built source of chunks + barriers, assert the emitted change
rows. The golden model recomputes group aggregates per epoch in plain
Python and diffs them.
"""

import asyncio

import numpy as np
import pytest

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, StreamChunk,
)
from risingwave_tpu.common.epoch import EpochPair
from risingwave_tpu.expr.agg import AggCall, AggKind, agg_max, agg_min, agg_sum, count_star
from risingwave_tpu.state import MemoryStateStore, StateTable
from risingwave_tpu.stream import Barrier, BarrierKind, HashAggExecutor
from risingwave_tpu.stream.executor import Executor

SCHEMA = schema(("k", DataType.INT64), ("v", DataType.INT64))


class ScriptSource(Executor):
    """Yields a scripted list of messages (MockSource analogue)."""

    def __init__(self, sch, messages):
        self.schema = sch
        self.messages = messages
        self.identity = "ScriptSource"

    async def execute(self):
        for m in self.messages:
            yield m
            await asyncio.sleep(0)


def chunk(rows, cap=16):
    """rows: list of (op, k, v)."""
    ops = np.asarray([r[0] for r in rows], dtype=np.int8)
    ks = np.asarray([r[1] for r in rows], dtype=np.int64)
    vs = np.asarray([r[2] for r in rows], dtype=np.int64)
    return StreamChunk.from_numpy(SCHEMA, [ks, vs], ops=ops, capacity=cap)


def barrier(curr, prev, kind=BarrierKind.CHECKPOINT):
    return Barrier(EpochPair(curr, prev), kind)


async def run_agg(messages, agg_calls, capacity=64, state_table=None):
    src = ScriptSource(SCHEMA, messages)
    agg = HashAggExecutor(src, [0], agg_calls, capacity=capacity,
                          state_table=state_table)
    out = []
    async for msg in agg.execute():
        out.append(msg)
    return agg, out


def emitted_rows(out):
    rows = []
    for m in out:
        if isinstance(m, StreamChunk):
            rows.extend(m.to_rows())
    return rows


async def test_count_sum_insert_only():
    msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 1, 10), (OP_INSERT, 1, 20), (OP_INSERT, 2, 5)]),
        barrier(2, 1),
    ]
    _, out = await run_agg(msgs, [count_star(), agg_sum(1)])
    rows = sorted(emitted_rows(out), key=lambda r: r[1][0])
    assert rows == [
        (OP_INSERT, (1, 2, 30)),
        (OP_INSERT, (2, 1, 5)),
    ]


async def test_update_pairs_on_second_epoch():
    msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 1, 10)]),
        barrier(2, 1),
        chunk([(OP_INSERT, 1, 5), (OP_INSERT, 3, 7)]),
        barrier(3, 2),
    ]
    _, out = await run_agg(msgs, [count_star(), agg_sum(1)])
    # second epoch: group 1 updates (UD old, UI new), group 3 born (Insert)
    chunks = [m for m in out if isinstance(m, StreamChunk)]
    assert len(chunks) == 2
    second = chunks[1].to_rows()
    by_key = {}
    for op, row in second:
        by_key.setdefault(row[0], []).append((op, row))
    assert [op for op, _ in by_key[1]] == [OP_UPDATE_DELETE, OP_UPDATE_INSERT]
    assert by_key[1][0][1] == (1, 1, 10)
    assert by_key[1][1][1] == (1, 2, 15)
    assert by_key[3] == [(OP_INSERT, (3, 1, 7))]


async def test_delete_retraction_and_group_death():
    msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 1, 10), (OP_INSERT, 1, 4), (OP_INSERT, 2, 9)]),
        barrier(2, 1),
        chunk([(OP_DELETE, 1, 10), (OP_DELETE, 2, 9)]),
        barrier(3, 2),
    ]
    _, out = await run_agg(msgs, [count_star(), agg_sum(1)])
    chunks = [m for m in out if isinstance(m, StreamChunk)]
    second = chunks[1].to_rows()
    by_key = {}
    for op, row in second:
        by_key.setdefault(row[0], []).append((op, row))
    # group 1 survives with count 1 sum 4; group 2 dies -> Delete of old row
    assert by_key[1] == [(OP_UPDATE_DELETE, (1, 2, 14)), (OP_UPDATE_INSERT, (1, 1, 4))]
    assert by_key[2] == [(OP_DELETE, (2, 1, 9))]


async def test_group_reborn_after_death():
    msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 7, 1)]),
        barrier(2, 1),
        chunk([(OP_DELETE, 7, 1)]),
        barrier(3, 2),
        chunk([(OP_INSERT, 7, 2)]),
        barrier(4, 3),
    ]
    _, out = await run_agg(msgs, [count_star(), agg_sum(1)])
    chunks = [m for m in out if isinstance(m, StreamChunk)]
    assert chunks[1].to_rows() == [(OP_DELETE, (7, 1, 1))]
    # zombie slot reused; rebirth is an Insert, not an Update
    assert chunks[2].to_rows() == [(OP_INSERT, (7, 1, 2))]


async def test_max_append_only():
    msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 1, 10), (OP_INSERT, 1, 30), (OP_INSERT, 1, 20)]),
        barrier(2, 1),
        chunk([(OP_INSERT, 1, 25)]),
        barrier(3, 2),
    ]
    _, out = await run_agg(msgs, [agg_max(1, append_only=True)])
    chunks = [m for m in out if isinstance(m, StreamChunk)]
    assert chunks[0].to_rows() == [(OP_INSERT, (1, 30))]
    # max unchanged -> no-change skip: no changelog rows for the touched
    # group (reference agg_group.rs:71 build_change emits NoChange)
    assert chunks[1].to_rows() == []


async def test_retractable_max_deletes_flip_extremum():
    """Deletes recompute max from the materialized-input buffer
    (reference minput.rs): removing the current max falls back to the
    next-best tracked value."""
    msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 1, 10), (OP_INSERT, 1, 30), (OP_INSERT, 1, 20)]),
        barrier(2, 1),
        chunk([(OP_DELETE, 1, 30)]),
        barrier(3, 2),
        chunk([(OP_DELETE, 1, 20), (OP_INSERT, 1, 5)]),
        barrier(4, 3),
    ]
    agg, out = await run_agg(msgs, [agg_max(1)], capacity=64)
    got = emitted_rows(out)
    assert got == [
        (OP_INSERT, (1, 30)),
        (OP_UPDATE_DELETE, (1, 30)), (OP_UPDATE_INSERT, (1, 20)),
        (OP_UPDATE_DELETE, (1, 20)), (OP_UPDATE_INSERT, (1, 10)),
    ]


async def test_retractable_min_duplicates():
    """Duplicate values carry multiplicity: deleting one instance keeps
    the extremum until the last instance goes."""
    msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 7, 4), (OP_INSERT, 7, 4), (OP_INSERT, 7, 9)]),
        barrier(2, 1),
        chunk([(OP_DELETE, 7, 4)]),
        barrier(3, 2),            # min still 4 (one instance left)
        chunk([(OP_DELETE, 7, 4)]),
        barrier(4, 3),            # min now 9
    ]
    agg, out = await run_agg(msgs, [agg_min(1)], capacity=64)
    got = emitted_rows(out)
    assert got == [
        (OP_INSERT, (7, 4)),
        (OP_UPDATE_DELETE, (7, 4)), (OP_UPDATE_INSERT, (7, 9)),
    ]


async def test_retractable_max_golden_random():
    """Randomized insert/delete stream vs a python multiset model."""
    rng = np.random.default_rng(11)
    live: dict[int, list[int]] = {}
    msgs = [barrier(1, 0, BarrierKind.INITIAL)]
    ep = 2
    for _ in range(5):
        rows = []
        for _ in range(25):
            k = int(rng.integers(0, 6))
            vs = live.setdefault(k, [])
            if vs and rng.random() < 0.4:
                v = vs.pop(int(rng.integers(0, len(vs))))
                rows.append((OP_DELETE, k, v))
            else:
                v = int(rng.integers(0, 50))
                vs.append(v)
                rows.append((OP_INSERT, k, v))
        msgs.append(chunk(rows, cap=32))
        msgs.append(barrier(ep, ep - 1))
        ep += 1
    agg, out = await run_agg(msgs, [agg_max(1)], capacity=64)
    mv = {}
    for op, row in emitted_rows(out):
        if op in (OP_INSERT, OP_UPDATE_INSERT):
            mv[row[0]] = row[1]
        elif op == OP_DELETE:
            mv.pop(row[0], None)
    want = {k: max(vs) for k, vs in live.items() if vs}
    assert mv == want


async def test_retractable_max_persist_recover():
    store = MemoryStateStore()
    K = 4

    def make_table():
        fields = [("k", DataType.INT64)]
        fields += [(f"v{k}", DataType.INT64) for k in range(K)]
        fields += [(f"c{k}", DataType.INT64) for k in range(K)]
        fields += [("lossy", DataType.INT64), ("_row_count", DataType.INT64)]
        return StateTable(store, table_id=21, schema=schema(*fields),
                          pk_indices=[0])

    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, 1, 10), (OP_INSERT, 1, 30)]),
            barrier(2, 1)]
    src = ScriptSource(SCHEMA, msgs)
    agg = HashAggExecutor(src, [0], [agg_max(1)], capacity=64,
                          state_table=make_table(), minput_k=K)
    async for _ in agg.execute():
        pass
    store.sync(1)

    msgs2 = [barrier(3, 2, BarrierKind.INITIAL),
             chunk([(OP_DELETE, 1, 30)]),
             barrier(4, 3)]
    agg2 = HashAggExecutor(ScriptSource(SCHEMA, msgs2), [0], [agg_max(1)],
                           capacity=64, state_table=make_table(), minput_k=K)
    out = []
    async for m in agg2.execute():
        out.append(m)
    got = emitted_rows(out)
    # recovered buffer knows 10 is next: update 30 -> 10, no underflow
    assert got == [(OP_UPDATE_DELETE, (1, 30)), (OP_UPDATE_INSERT, (1, 10))]


async def test_retractable_underflow_fail_stop():
    """K=2 buffer, 3 distinct values: the spill marks the group lossy;
    deleting all tracked values with rows remaining must fail-stop, not
    emit a wrong extremum."""
    msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 1, 10), (OP_INSERT, 1, 20), (OP_INSERT, 1, 30)]),
        barrier(2, 1),
        chunk([(OP_DELETE, 1, 30), (OP_DELETE, 1, 20)]),
        barrier(3, 2),
    ]
    src = ScriptSource(SCHEMA, msgs)
    agg = HashAggExecutor(src, [0], [agg_max(1)], capacity=64, minput_k=2)
    with pytest.raises(RuntimeError, match=r"lost its bound.*'underflow': 1"):
        async for _ in agg.execute():
            pass


async def test_barrier_time_growth():
    # 64-slot table; epoch 1 fills past the 70% watermark -> the table grows
    # at the barrier, and epoch 2's new groups land correctly
    e1 = [(OP_INSERT, k, k) for k in range(50)]
    e2 = [(OP_INSERT, k, k) for k in range(50, 100)]
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk(e1, cap=64), barrier(2, 1),
            chunk(e2, cap=64), barrier(3, 2)]
    agg, out = await run_agg(msgs, [count_star()], capacity=64)
    assert agg.rebuilds >= 1
    assert agg.capacity > 64
    got = sorted(emitted_rows(out), key=lambda r: r[1][0])
    assert len(got) == 100
    assert all(op == OP_INSERT and row[1] == 1 for op, row in got)


async def test_overflow_fail_stop():
    # a 32-slot table cannot absorb 80 distinct groups in one epoch: the
    # async watchdog must fail-stop (recovery replays the epoch in a real
    # cluster)
    rows = [(OP_INSERT, k, k) for k in range(80)]
    msgs = [barrier(1, 0, BarrierKind.INITIAL), chunk(rows, cap=128),
            chunk(rows, cap=128), barrier(2, 1), barrier(3, 2)]
    with pytest.raises(RuntimeError, match="overflow"):
        await run_agg(msgs, [count_star()], capacity=32)


async def test_golden_random_stream():
    """Randomized changelog vs dict model across several epochs."""
    rng = np.random.default_rng(42)
    live: dict[int, list[int]] = {}      # key -> multiset of values
    prev_out: dict[int, tuple] = {}
    msgs = [barrier(1, 0, BarrierKind.INITIAL)]
    expected_epoch_diffs = []
    for epoch in range(2, 6):
        rows = []
        for _ in range(30):
            if live and rng.random() < 0.3:
                k = int(rng.choice(list(live)))
                v = live[k][int(rng.integers(len(live[k])))]
                rows.append((OP_DELETE, k, v))
                live[k].remove(v)
                if not live[k]:
                    del live[k]
            else:
                k = int(rng.integers(0, 12))
                v = int(rng.integers(0, 100))
                rows.append((OP_INSERT, k, v))
                live.setdefault(k, []).append(v)
        msgs.append(chunk(rows, cap=32))
        msgs.append(barrier(epoch, epoch - 1))
        cur_out = {k: (len(vs), sum(vs)) for k, vs in live.items()}
        diff = {}
        for k in set(prev_out) | set(cur_out):
            if prev_out.get(k) != cur_out.get(k):
                diff[k] = (prev_out.get(k), cur_out.get(k))
        expected_epoch_diffs.append(diff)
        prev_out = cur_out

    _, out = await run_agg(msgs, [count_star(), agg_sum(1)], capacity=64)
    chunks = [m for m in out if isinstance(m, StreamChunk)]
    # group emitted rows by epoch (one flush chunk per barrier w/ changes)
    assert len(chunks) == sum(1 for d in expected_epoch_diffs if d)
    ci = 0
    for diff in expected_epoch_diffs:
        if not diff:
            continue
        got = {}
        for op, row in chunks[ci].to_rows():
            got.setdefault(row[0], []).append((op, row[1:]))
        ci += 1
        assert set(got) == set(diff), f"epoch {ci}: wrong group set"
        for k, (old, new) in diff.items():
            if old is None:
                assert got[k] == [(OP_INSERT, new)]
            elif new is None:
                assert got[k] == [(OP_DELETE, old)]
            else:
                assert got[k] == [(OP_UPDATE_DELETE, old), (OP_UPDATE_INSERT, new)]


async def test_persist_and_recover():
    store = MemoryStateStore()

    def make_table():
        return StateTable(
            store, table_id=10,
            schema=schema(("k", DataType.INT64), ("count", DataType.INT64),
                          ("sum", DataType.INT64), ("_row_count", DataType.INT64)),
            pk_indices=[0])

    msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 1, 10), (OP_INSERT, 2, 5), (OP_INSERT, 1, 1)]),
        barrier(2, 1),
    ]
    await run_agg(msgs, [count_star(), agg_sum(1)], state_table=make_table())

    # restart: new executor over same store; apply a delta epoch
    msgs2 = [
        barrier(3, 2, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 1, 100), (OP_DELETE, 2, 5)]),
        barrier(4, 3),
    ]
    _, out2 = await run_agg(msgs2, [count_star(), agg_sum(1)],
                            state_table=make_table())
    rows = emitted_rows(out2)
    by_key = {}
    for op, row in rows:
        by_key.setdefault(row[0], []).append((op, row))
    # group 1 recovered (count 2 sum 11) then updated; group 2 recovered then died
    assert by_key[1] == [(OP_UPDATE_DELETE, (1, 2, 11)), (OP_UPDATE_INSERT, (1, 3, 111))]
    assert by_key[2] == [(OP_DELETE, (2, 1, 5))]


async def test_recover_rebuilds_fingerprints_and_keeps_probing():
    """Durable state, crash, recover(), then chunks that hit recovered keys
    and bring new ones: the MV equals a host recount. Only keys and agg
    state are persisted; `_state_from_rows` re-inserts them, which is what
    writes the recovered table's fingerprint lane (q5's key shape: two
    int64 group keys)."""
    from risingwave_tpu.ops import hash_table as ht
    sch = schema(("auction", DataType.INT64), ("win", DataType.INT64),
                 ("v", DataType.INT64))
    store = MemoryStateStore()

    def make_table():
        return StateTable(
            store, table_id=11,
            schema=schema(("auction", DataType.INT64),
                          ("win", DataType.INT64),
                          ("count", DataType.INT64), ("sum", DataType.INT64),
                          ("_row_count", DataType.INT64)),
            pk_indices=[0, 1])

    rng = np.random.default_rng(5)

    def rows(lo, hi, n):
        return [(int(a) << 33, int(w) * 2_000_000, int(v)) for a, w, v in zip(
            rng.integers(lo, hi, n), rng.integers(0, 5, n),
            rng.integers(1, 100, n))]

    def as_chunk(rs):
        cols = [np.asarray(c, dtype=np.int64) for c in zip(*rs)]
        return StreamChunk.from_numpy(sch, cols, capacity=256)

    mv, oracle = {}, {}

    async def run(messages):
        agg = HashAggExecutor(ScriptSource(sch, messages), [0, 1],
                              [count_star(), agg_sum(2)], capacity=1024,
                              state_table=make_table())
        async for m in agg.execute():
            if isinstance(m, StreamChunk):
                for op, row in m.to_rows():
                    if op in (OP_INSERT, OP_UPDATE_INSERT):
                        mv[row[:2]] = row[2:]
                    else:
                        assert mv.pop(row[:2]) == row[2:]
        return agg

    def count(rs):
        for a, w, v in rs:
            c, s_ = oracle.get((a, w), (0, 0))
            oracle[(a, w)] = (c + 1, s_ + v)

    first = [rows(0, 60, 200), rows(0, 60, 200)]
    for rs in first:
        count(rs)
    await run([barrier(1, 0, BarrierKind.INITIAL), as_chunk(first[0]),
               barrier(2, 1), as_chunk(first[1]), barrier(3, 2)])
    assert mv == oracle and len(mv) > 150

    # the process is gone; a new executor recovers from the store at its
    # INITIAL barrier, then sees old keys (0..60) and new ones (60..120)
    second = [rows(0, 120, 200), rows(30, 120, 200)]
    for rs in second:
        count(rs)
    agg = await run([barrier(4, 3, BarrierKind.INITIAL), as_chunk(second[0]),
                     barrier(5, 4), as_chunk(second[1]), barrier(6, 5)])
    assert mv == oracle and len(mv) > 300
    t = agg.state.table
    occ = np.asarray(t.occupied)
    assert int(occ.sum()) == len(oracle)
    np.testing.assert_array_equal(
        np.asarray(t.fingerprint)[occ],
        np.asarray(ht._fingerprint(ht._key_hash(list(t.keys))))[occ])
    assert agg._probe_fallback_seen == 0


async def test_constant_fingerprint_reaches_the_metric(monkeypatch):
    """The fallback count accumulates on the device and is published with
    the executor's per-barrier watchdog fetch; the answer stays exact."""
    import jax.numpy as jnp
    from risingwave_tpu.ops import hash_table as ht
    from risingwave_tpu.utils.metrics import HASH_PROBE_FALLBACK_ROWS
    monkeypatch.setattr(
        ht, "_fingerprint", lambda h: jnp.full(h.shape, 7, dtype=jnp.uint32))
    before = HASH_PROBE_FALLBACK_ROWS.value
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk([(OP_INSERT, k, 1) for k in range(12)]),
            barrier(2, 1),
            chunk([(OP_INSERT, k, 1) for k in range(12)]),
            barrier(3, 2)]
    agg, out = await run_agg(msgs, [count_star()])
    assert agg._probe_fallback_seen > 0
    assert HASH_PROBE_FALLBACK_ROWS.value - before == agg._probe_fallback_seen
    final = {row[0]: row[1] for op, row in emitted_rows(out)
             if op in (OP_INSERT, OP_UPDATE_INSERT)}
    assert final == {k: 2 for k in range(12)}


async def test_watermark_state_cleaning():
    """Groups below the cleaning watermark are zeroed; reappearing keys at
    or above it stay correct (reference: state-cleaning watermarks,
    hummock_sdk table_watermark.rs)."""
    from risingwave_tpu.common.types import DataType as DT
    from risingwave_tpu.stream import Watermark
    src_msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 10, 1), (OP_INSERT, 20, 2), (OP_INSERT, 30, 3)]),
        barrier(2, 1),
        Watermark(0, DT.INT64, 25),   # groups 10, 20 can never recur
        chunk([(OP_INSERT, 30, 4)]),
        barrier(3, 2),
    ]
    src = ScriptSource(SCHEMA, src_msgs)
    agg = HashAggExecutor(src, [0], [count_star(), agg_sum(1)], capacity=64,
                          cleaning_watermark_col=0)
    out = []
    async for m in agg.execute():
        out.append(m)
    import numpy as np
    # group 30 (>= watermark) survives with correct running state
    chunks = [m for m in out if isinstance(m, StreamChunk)]
    assert chunks[1].to_rows() == [
        (OP_UPDATE_DELETE, (30, 1, 3)), (OP_UPDATE_INSERT, (30, 2, 7))]
    rc = np.asarray(agg.state.row_count)
    occ = np.asarray(agg.state.table.occupied)
    # evicted groups are zombies: occupied but zero rows
    keys = np.asarray(agg.state.table.keys[0])
    for k, alive in [(10, False), (20, False), (30, True)]:
        s = np.flatnonzero(occ & (keys == k))
        assert len(s) == 1
        assert (rc[s[0]] > 0) == alive


async def test_group_key_watermark_follows_the_flushed_interval():
    """A group-key watermark must not overtake the updates this executor
    is still buffering: it leaves AFTER the barrier-time flush chunk and
    before the barrier (reference hash_agg.rs `buffered_watermarks`).
    Forwarded on arrival, a downstream join cleaned its state by it and
    fail-stopped on the retraction that followed ("delete matched no
    stored row") as soon as an interval spanned more event time than the
    source's watermark lag — q7 at chunk_size=131072 on the chip."""
    from risingwave_tpu.common.types import DataType as DT
    from risingwave_tpu.stream import Watermark
    src_msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 10, 1), (OP_INSERT, 20, 2)]),
        Watermark(0, DT.INT64, 15),
        chunk([(OP_INSERT, 20, 5)]),
        Watermark(0, DT.INT64, 18),          # supersedes 15 in the interval
        Watermark(1, DT.INT64, 99),          # not a group key: consumed
        barrier(2, 1),
        barrier(3, 2),                       # idle interval: nothing held
    ]
    _, out = await run_agg(src_msgs, [count_star()])
    kinds = [type(m).__name__ for m in out]
    assert kinds == ["Barrier", "StreamChunk", "Watermark", "Barrier",
                     "Barrier"], kinds
    wm = out[2]
    assert (wm.col_idx, wm.val) == (0, 18)
    assert sorted(out[1].to_rows()) == [
        (OP_INSERT, (10, 1)), (OP_INSERT, (20, 2))]


async def test_eviction_deletes_from_state_table():
    """Watermark eviction must bound DURABLE state too: evicted groups are
    deleted from the state table in the same epoch, and recovery does not
    resurrect them (ADVICE r1; reference: StateTable::update_watermark ->
    Hummock table-watermark pruning)."""
    from risingwave_tpu.common.types import DataType as DT
    from risingwave_tpu.stream import Watermark

    store = MemoryStateStore()

    def make_table():
        return StateTable(
            store, table_id=11,
            schema=schema(("k", DataType.INT64), ("count", DataType.INT64),
                          ("sum", DataType.INT64), ("_row_count", DataType.INT64)),
            pk_indices=[0])

    src_msgs = [
        barrier(1, 0, BarrierKind.INITIAL),
        chunk([(OP_INSERT, 10, 1), (OP_INSERT, 20, 2), (OP_INSERT, 30, 3)]),
        barrier(2, 1),
        Watermark(0, DT.INT64, 25),
        chunk([(OP_INSERT, 30, 4)]),
        barrier(3, 2),
    ]
    src = ScriptSource(SCHEMA, src_msgs)
    agg = HashAggExecutor(src, [0], [count_star(), agg_sum(1)], capacity=64,
                          state_table=make_table(), cleaning_watermark_col=0)
    async for _ in agg.execute():
        pass
    store.sync(3)
    # only group 30 remains durable
    survivors = sorted(r[0] for _, r in make_table().iter_all())
    assert survivors == [30]

    # recovery sees no zombie groups
    msgs2 = [barrier(4, 3, BarrierKind.INITIAL),
             chunk([(OP_INSERT, 30, 5)]), barrier(5, 4)]
    agg2_src = ScriptSource(SCHEMA, msgs2)
    agg2 = HashAggExecutor(agg2_src, [0], [count_star(), agg_sum(1)],
                           capacity=64, state_table=make_table(),
                           cleaning_watermark_col=0)
    out2 = []
    async for m in agg2.execute():
        out2.append(m)
    chunks2 = [m for m in out2 if isinstance(m, StreamChunk)]
    assert chunks2[0].to_rows() == [
        (OP_UPDATE_DELETE, (30, 2, 7)), (OP_UPDATE_INSERT, (30, 3, 12))]


async def test_recover_beyond_constructor_capacity():
    """Recovery must succeed even when more rows were persisted than the
    constructor capacity can hold at target load (ADVICE r1: runtime growth
    is not persisted; recovery sizes the table from the row count)."""
    store = MemoryStateStore()

    def make_table():
        return StateTable(
            store, table_id=12,
            schema=schema(("k", DataType.INT64), ("count", DataType.INT64),
                          ("_row_count", DataType.INT64)),
            pk_indices=[0])

    rows = [(OP_INSERT, k, 0) for k in range(100)]
    msgs = [barrier(1, 0, BarrierKind.INITIAL), chunk(rows, cap=128),
            barrier(2, 1)]
    await run_agg(msgs, [count_star()], capacity=256, state_table=make_table())
    store.sync(2)

    # restart with a much smaller constructor capacity than the 100 rows
    msgs2 = [barrier(3, 2, BarrierKind.INITIAL),
             chunk([(OP_INSERT, 5, 0)]), barrier(4, 3)]
    agg2, out2 = await run_agg(msgs2, [count_star()], capacity=32,
                               state_table=make_table())
    assert agg2.capacity >= 128
    rows2 = emitted_rows(out2)
    assert (OP_UPDATE_INSERT, (5, 2)) in rows2


@pytest.mark.parametrize("watchdog,groups,want_capacity", [
    (1, 3, 2 * 128),            # FLUSH_MIN_SLOTS dirty slots, two rows each
    (1, 300, 2 * 1024),         # the power of two that holds twice 300
    (None, 3, 2 * 4096),        # no watchdog fetch, no count: the capacity
], ids=["few", "grown", "transfer_free"])
async def test_flush_chunk_is_as_wide_as_the_dirty_groups(
        watchdog, groups, want_capacity):
    """The barrier flush hands its consumer a chunk as wide as a power of
    two over the groups the interval touched (the count rides the watchdog
    fetch), not as wide as the table; the width never shrinks again."""
    rows = [(OP_INSERT, k, k) for k in range(groups)]
    msgs = [barrier(1, 0, BarrierKind.INITIAL),
            chunk(rows, cap=512), barrier(2, 1),
            chunk([(OP_INSERT, 0, 7)], cap=512), barrier(3, 2)]
    agg = HashAggExecutor(ScriptSource(SCHEMA, msgs), [0],
                          [count_star(), agg_sum(1)], capacity=4096,
                          watchdog_interval=watchdog)
    out = [m async for m in agg.execute()]
    chunks = [m for m in out if isinstance(m, StreamChunk)]
    assert [c.capacity for c in chunks] == [want_capacity] * 2
    assert sorted(r[1] for r in chunks[0].to_rows()) == [
        (k, 1, k) for k in range(groups)]
    assert chunks[1].to_rows() == [(OP_UPDATE_DELETE, (0, 1, 0)),
                                   (OP_UPDATE_INSERT, (0, 2, 7))]
