"""The state store's columnar write path against a plain dict overlay.

A batch written through `StateTable.write_chunk_columns` stays a
ColumnarSegment (key matrix, value matrix, put lane) through the mem-table,
the shared buffer, the sealed batch and the merge, up to the array-backed L0
run. The reference kept HERE is what the store did before: every write one
`bytes -> bytes | None` entry of a dict per epoch, overlaid oldest to
newest, `build_sstable(epoch, sorted(merged.items()))` for the object.
"""

import struct
import zlib

import numpy as np
import pytest

from risingwave_tpu import native
from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import (OP_DELETE, OP_INSERT,
                                         OP_UPDATE_DELETE, OP_UPDATE_INSERT)
from risingwave_tpu.common.vnode import compute_vnodes_numpy
from risingwave_tpu.state import StateTable, sstable
from risingwave_tpu.state.hummock import HummockStateStore, _sst_path
from risingwave_tpu.state.object_store import InMemObjectStore
from risingwave_tpu.state.serde import RowSerde, encode_memcomparable
from risingwave_tpu.state.sstable import (FixedPart, ListPart, SsTable,
                                          build_sstable)
from risingwave_tpu.state.store import (MemoryStateStore, WriteBatch,
                                        encode_table_key)

needs_native = pytest.mark.skipif(native.lib() is None,
                                  reason="no C++ toolchain")
PUT_OPS = (OP_INSERT, OP_UPDATE_INSERT)
ALL_OPS = (OP_INSERT, OP_DELETE, OP_UPDATE_DELETE, OP_UPDATE_INSERT)
DOMAIN = 5                  # values a pk column takes: duplicates are common
RAW_TABLE = 15              # raw ingest_batch keys, as the log store's
CONTENDED = 16              # a columnar table that raw keys of any width hit


def i64_schema(n):
    return schema(*[(f"c{i}", DataType.INT64) for i in range(n)])


def make_tables(store):
    """Three all-INT64 tables of pk width 1, 2, 3 (the last a singleton:
    vnode 0), one of mixed widths and kinds with a VARCHAR's int32 id in
    its pk, and one more INT64 table whose id raw writes use as well."""
    return [
        StateTable(store, 11, i64_schema(3), (0,), check_consistency=False),
        StateTable(store, 12, i64_schema(4), (0, 1),
                   check_consistency=False),
        StateTable(store, 13, i64_schema(7), (0, 1, 2),
                   dist_key_indices=(), check_consistency=False),
        StateTable(store, 14,
                   schema(("k", DataType.INT64), ("f", DataType.FLOAT64),
                          ("s", DataType.VARCHAR), ("b", DataType.BOOLEAN),
                          ("h", DataType.INT16)),
                   (0, 2), check_consistency=False),
        StateTable(store, CONTENDED, i64_schema(2), (0,),
                   check_consistency=False),
    ]


def ref_key(t: StateTable, row) -> bytes:
    """table id ++ vnode ++ memcomparable(pk), by the per-row codec."""
    if t.dist_key_indices:
        vn = int(compute_vnodes_numpy(
            [np.asarray([row[i]], dtype=t.schema[i].data_type.np_dtype)
             for i in t.dist_key_indices])[0])
    else:
        vn = 0
    pk = tuple(row[i] for i in t.pk_indices)
    types = [t.schema[i].data_type for i in t.pk_indices]
    return encode_table_key(t.table_id, vn,
                            encode_memcomparable(pk, types))


def ref_val(t: StateTable, row) -> bytes:
    return RowSerde(t.schema).encode(tuple(row))


class Model:
    """The dict-per-epoch store, and each table's mem-table as one dict."""

    def __init__(self):
        self.committed: dict = {}
        self.sealed: list = []          # (seal_epoch, {epoch: dict}), oldest first
        self.staged: dict = {}          # epoch -> dict
        self.mem: dict = {}             # table id -> dict

    def layers(self, max_epoch=None):
        """Uncommitted dicts, oldest first."""
        out = []
        for _seal, epochs in self.sealed:
            out += [epochs[e] for e in sorted(epochs)
                    if max_epoch is None or e <= max_epoch]
        out += [self.staged[e] for e in sorted(self.staged)
                if max_epoch is None or e <= max_epoch]
        return out

    def view(self, committed_only=False, max_epoch=None) -> dict:
        merged = dict(self.committed)
        if not committed_only:
            for layer in self.layers(max_epoch):
                merged.update(layer)
        return merged

    def range(self, start, end, **kw):
        return sorted((k, v) for k, v in self.view(**kw).items()
                      if v is not None and start <= k
                      and (not end or k < end))

    def all_keys(self):
        keys = set(self.view())
        for mem in self.mem.values():
            keys.update(mem)
        return sorted(keys)

    def seal(self, epoch):
        eps = {e: self.staged.pop(e) for e in sorted(self.staged)
               if e <= epoch}
        self.sealed.append((epoch, eps))
        merged: dict = {}
        for e in sorted(eps):
            merged.update(eps[e])
        return merged

    def commit_oldest(self):
        _seal, eps = self.sealed.pop(0)
        for e in sorted(eps):
            self.committed.update(eps[e])


class Driver:
    """Random interleavings of every way a write reaches the store."""

    def __init__(self, seed, store=None):
        self.rng = np.random.default_rng(seed)
        self.store = store if store is not None \
            else HummockStateStore(InMemObjectStore())
        self.tables = make_tables(self.store)
        self.model = Model()
        self.epoch = 1
        for t in self.tables:
            t.init_epoch(self.epoch)

    # ------------------------------------------------------------- writes
    def row(self, t: StateTable):
        r = self.rng
        if t.table_id == 14:
            return (int(r.integers(DOMAIN)), float(r.integers(-100, 100)) / 4,
                    int(r.integers(DOMAIN)), bool(r.integers(2)),
                    int(r.integers(-(1 << 15), 1 << 15)))
        return tuple(int(r.integers(DOMAIN)) if i in t.pk_indices
                     else int(r.integers(-(1 << 40), 1 << 40))
                     for i in range(len(t.schema)))

    def note(self, t, row, put):
        self.model.mem.setdefault(t.table_id, {})[ref_key(t, row)] = \
            ref_val(t, row) if put else None

    def op_columns(self, t):
        n = int(self.rng.integers(1, 24))
        rows = [self.row(t) for _ in range(n)]
        ops = self.rng.choice(ALL_OPS, size=n).astype(np.int8)
        vis = self.rng.random(n) > 0.2
        cols = [np.asarray([r[j] for r in rows]) for j in range(len(t.schema))]
        t.write_chunk_columns(ops, cols, vis)
        for r, op, v in zip(rows, ops, vis):
            if v:
                self.note(t, r, op in PUT_OPS)

    def op_rows(self, t):
        n = int(self.rng.integers(1, 8))
        rows = [(int(self.rng.choice(ALL_OPS)), self.row(t))
                for _ in range(n)]
        t.write_chunk_rows(rows)
        for op, r in rows:
            self.note(t, r, op in PUT_OPS)

    def op_insert(self, t):
        r = self.row(t)
        t.insert(r)
        self.note(t, r, True)

    def op_delete(self, t):
        r = self.row(t)
        t.delete(r)
        self.note(t, r, False)

    def op_update(self, t):
        old, new = self.row(t), self.row(t)
        t.update(old, new)
        self.note(t, old, False)
        self.note(t, new, True)

    def op_raw(self, _t):
        """Straight into `ingest_batch`: keys of any width under a table
        id of their own, and — the hard case — under the id of a table
        written in columns, at its width (they join its arrays) or not
        (that table's share of the batch falls back to the dict)."""
        r = self.rng
        puts = {}
        for _ in range(int(r.integers(1, 5))):
            kind = r.integers(4)
            if kind == 0:
                t = self.tables[int(r.choice([1, 4]))]
                row = self.row(t)
                puts[ref_key(t, row)] = \
                    ref_val(t, row) if r.random() < 0.7 else None
            elif kind == 1 and r.random() < 0.3:
                puts[CONTENDED.to_bytes(4, "big") + bytes(r.integers(
                    0, 256, size=int(r.integers(1, 30)), dtype=np.uint8))] \
                    = b"odd" if r.random() < 0.7 else None
            else:
                puts[RAW_TABLE.to_bytes(4, "big") + bytes(r.integers(
                    0, 4, size=int(r.integers(0, 4)), dtype=np.uint8))] \
                    = bytes(r.integers(0, 256, size=int(r.integers(0, 9)),
                                       dtype=np.uint8)) \
                    if r.random() < 0.7 else None
        self.store.ingest_batch(WriteBatch(RAW_TABLE, self.epoch, puts))
        self.model.staged.setdefault(self.epoch, {}).update(puts)

    def write_some(self, lo=3, hi=10):
        kinds = [self.op_columns, self.op_columns, self.op_columns,
                 self.op_rows, self.op_insert, self.op_delete,
                 self.op_update, self.op_raw]
        for _ in range(int(self.rng.integers(lo, hi))):
            t = self.tables[int(self.rng.integers(len(self.tables)))]
            kinds[int(self.rng.integers(len(kinds)))](t)

    def commit_tables(self):
        for t in self.tables:
            t.commit(self.epoch + 1)
            self.model.staged.setdefault(self.epoch, {}).update(
                self.model.mem.pop(t.table_id, {}))
        self.epoch += 1

    def epochs(self, n):
        for _ in range(n):
            self.write_some()
            self.commit_tables()

    # -------------------------------------------------------------- phases
    def seal(self):
        merged = self.model.seal(self.epoch - 1)
        return self.store.seal(self.epoch - 1), merged

    def upload(self, batch, merged):
        """Satellite (a): the object is the dict builder's, byte for byte."""
        self.store.upload_sealed(batch)
        if batch.sst_id is None:
            assert not merged
            return
        want = build_sstable(batch.seal_epoch, sorted(merged.items()))
        assert self.store.objects.read(_sst_path(batch.sst_id)) == want

    def commit(self, batch):
        self.store.commit_sealed(batch)
        self.model.commit_oldest()

    def checkpoint(self):
        batch, merged = self.seal()
        self.upload(batch, merged)
        self.commit(batch)

    # --------------------------------------------------------------- reads
    def check_reads(self):
        """Satellite (b): every read of the store and of the tables equals
        the model, whatever stage the writes are in."""
        store, model, rng = self.store, self.model, self.rng
        keys = model.all_keys()
        probes = keys + [k + b"\0" for k in keys[:5]] + [k[:-1] for k in keys[:5]] \
            + [b"", b"\xff" * 6]
        now, committed = model.view(), model.view(committed_only=True)
        for k in probes:
            assert store.get(k) == now.get(k), k
            assert store.get_committed(k) == committed.get(k), k
        assert store.get_many(probes) == [now.get(k) for k in probes]
        bounds = [(b"", b"")]
        for t in self.tables:
            bounds.append(t.vnode_key_range(0))
            bounds.append((t.table_id.to_bytes(4, "big"),
                           (t.table_id + 1).to_bytes(4, "big")))
        for _ in range(6):
            if keys:
                a, b = sorted(keys[int(i)] for i in rng.integers(
                    len(keys), size=2))
                bounds += [(a, b), (a[:-2], b + b"\1"), (a, b"")]
        staged_epochs = sorted({e for _s, eps in model.sealed for e in eps}
                               | set(model.staged))
        for start, end in bounds:
            assert list(store.iter_range(start, end)) \
                == model.range(start, end)
            assert store.scan_range(start, end) == model.range(start, end)
            assert list(store.iter_range(start, end, committed_only=True)) \
                == model.range(start, end, committed_only=True)
            for e in staged_epochs:
                assert list(store.iter_range(start, end, max_epoch=e)) \
                    == model.range(start, end, max_epoch=e), (start, end, e)
        for t in self.tables[:4]:     # CONTENDED holds keys that are no row
            serde = RowSerde(t.schema)
            view = dict(now)
            view.update(model.mem.get(t.table_id, {}))
            prefix = t.table_id.to_bytes(4, "big")
            assert list(t.iter_all()) \
                == [(k, serde.decode(v)) for k, v in sorted(view.items())
                    if v is not None and k[:4] == prefix]
            rows = [self.row(t) for _ in range(12)]
            pks = [tuple(r[i] for i in t.pk_indices) for r in rows]
            expect = []
            for r in rows:
                v = view.get(ref_key(t, r))
                expect.append(None if v is None else serde.decode(v))
            assert [t.get_row(pk) for pk in pks] == expect
            assert t.get_rows(pks) == expect


# ---------------------------------------------------------------- (a), (b)
@pytest.mark.parametrize("seed", range(6))
def test_random_interleavings_upload_the_dict_builders_bytes(seed):
    d = Driver(seed)
    for it in range(4):
        d.write_some()
        d.check_reads()                       # in the mem-tables
        d.commit_tables()
        d.epochs(int(d.rng.integers(0, 3)))   # several epochs in one batch
        d.check_reads()                       # staged
        b1, m1 = d.seal()
        d.check_reads()                       # sealed
        if it % 2:
            # two batches in flight, the newer one uploaded first
            d.epochs(1)
            d.write_some()
            b2, m2 = d.seal()
            d.upload(b2, m2)
            d.check_reads()
            d.upload(b1, m1)
            d.commit(b1)
            d.check_reads()
            d.commit(b2)
        else:
            d.write_some()                    # staged on top of the sealed
            d.upload(b1, m1)
            d.check_reads()                   # uploaded, not committed
            d.commit(b1)
        d.check_reads()                       # committed
    assert d.store.l0_run_count() >= 4


@needs_native
@pytest.mark.parametrize("row_form", ["insert_only", "null_pk"])
def test_batch_written_tables_reach_l0_as_arrays_and_row_form_ones_as_lists(
        row_form):
    """Whatever its column types, a table written in batches is a FixedPart
    of the run. A ListPart is what row-form writes alone leave (a table
    that only ever sees `insert`), or a key of another width among a
    table's arrays: the row a batch's NULL pk lane put in row form."""
    d = Driver(3)
    for t in d.tables * 3:
        d.op_columns(t)
    lists = StateTable(
        d.store, 17, schema(("k", DataType.INT64), ("s", DataType.VARCHAR)),
        (0, 1), check_consistency=False)
    lists.init_epoch(d.epoch)
    d.tables.append(lists)
    if row_form == "insert_only":
        lists.insert((1, 2))
    else:
        lists.write_chunk_columns(
            np.zeros(3, dtype=np.int8),
            [np.arange(3), np.arange(3, dtype=np.int32)], np.ones(3, bool),
            valids=[None, np.asarray([True, False, True])])
        assert lists.row_path_rows == 1 and len(lists._mem) == 2
    d.commit_tables()
    batch = d.store.seal(d.epoch - 1)
    d.store.upload_sealed(batch)
    d.store.commit_sealed(batch)
    run = d.store._l0[0]
    kinds = {int.from_bytes(p.min_key[:4], "big"): type(p)
             for p in run.parts}
    assert kinds == {11: FixedPart, 12: FixedPart, 13: FixedPart,
                     14: FixedPart, CONTENDED: FixedPart, 17: ListPart}
    assert [p.min_key for p in run.parts] \
        == sorted(p.min_key for p in run.parts)
    assert sorted(r for _k, r in lists.iter_all()) == (
        [(1, 2)] if row_form == "insert_only"
        else [(0, 0), (1, None), (2, 2)])


def test_memory_store_takes_columnar_batches():
    """`MemoryStateStore` is handed the same segments and keeps dicts."""
    d = Driver(5, store=MemoryStateStore())
    for _ in range(3):
        d.write_some()
        d.commit_tables()
        now = d.model.view()
        for k in d.model.all_keys():
            assert d.store.get(k) == now.get(k)
        d.store.sync(d.epoch - 1)
        d.model.seal(d.epoch - 1)
        d.model.commit_oldest()
    live = {k: v for k, v in d.model.committed.items() if v is not None}
    assert d.store._vals == live


# --------------------------------------------------------------------- (c)
def _one_table():
    store = HummockStateStore(InMemObjectStore())
    t = StateTable(store, 7, i64_schema(3), (0,))
    t.init_epoch(1)
    return store, t


def _cols(rows):
    return [np.asarray([r[j] for r in rows], dtype=np.int64)
            for j in range(3)]


def _write(t, op, rows):
    t.write_chunk_columns(np.full(len(rows), op, dtype=np.int8),
                          _cols(rows), np.ones(len(rows), dtype=bool))


@pytest.mark.parametrize("one_call", [False, True])
def test_delete_then_insert_keeps_the_insert(one_call):
    """The join's persist writes an updated row as delete(old) strictly
    before insert(new) on one key: the insert is what lands."""
    store, t = _one_table()
    _write(t, OP_INSERT, [(1, 10, 10), (2, 20, 20)])
    t.commit(2)
    store.sync(1)
    if one_call:
        t.write_chunk_columns(
            np.asarray([OP_DELETE, OP_INSERT], dtype=np.int8),
            _cols([(1, 10, 10), (1, 11, 11)]), np.ones(2, dtype=bool))
    else:
        _write(t, OP_DELETE, [(1, 10, 10)])
        _write(t, OP_INSERT, [(1, 11, 11)])
    assert t.get_row((1,)) == (1, 11, 11)
    t.commit(3)
    assert t.get_row((1,)) == (1, 11, 11)
    store.sync(2)
    assert t.get_row((1,)) == (1, 11, 11)
    assert sorted(r for _k, r in t.iter_all()) == [(1, 11, 11), (2, 20, 20)]
    found, v = store._l0[0].get(t._key_of((1, 0, 0)))
    assert found and v == RowSerde(t.schema).encode((1, 11, 11))


def test_insert_then_delete_leaves_a_tombstone_over_the_committed_row():
    store, t = _one_table()
    _write(t, OP_INSERT, [(1, 10, 10)])
    t.commit(2)
    store.sync(1)
    _write(t, OP_INSERT, [(1, 12, 12)])
    _write(t, OP_DELETE, [(1, 12, 12)])
    assert t.get_row((1,)) is None
    t.commit(3)
    assert t.get_row((1,)) is None
    batch = store.seal(2)
    assert t.get_row((1,)) is None
    store.upload_sealed(batch)
    key = t._key_of((1, 0, 0))
    parsed = SsTable.parse(0, store.objects.read(_sst_path(batch.sst_id)))
    assert parsed.get(key) == (True, None)        # an explicit tombstone
    assert store.get_committed(key) is not None   # the old row, until:
    store.commit_sealed(batch)
    assert store.get_committed(key) is None
    assert t.get_row((1,)) is None and list(t.iter_all()) == []


def test_double_insert_is_caught_through_a_columnar_segment():
    from risingwave_tpu.state.state_table import StateTableError
    _store, t = _one_table()
    _write(t, OP_INSERT, [(1, 10, 10)])
    with pytest.raises(StateTableError):
        t.insert((1, 11, 11))
    _write(t, OP_DELETE, [(1, 10, 10)])
    t.insert((1, 11, 11))
    assert t.get_row((1,)) == (1, 11, 11)


# --------------------------------------------------------------------- (d)
@needs_native
def test_numpy_twin_packs_the_native_builders_bytes(monkeypatch):
    def staged():
        d = Driver(11)
        for _ in range(3):
            for t in d.tables[:3]:
                d.op_columns(t)
            d.commit_tables()
        return d
    native_side, twin_side = staged(), staged()
    b1, m1 = native_side.seal()
    native_side.upload(b1, m1)
    twin_calls = []
    twin = sstable._pack_fixed_numpy
    monkeypatch.setattr(native, "lib", lambda: None)
    monkeypatch.setattr(sstable, "_pack_fixed_numpy",
                        lambda *a: twin_calls.append(1) or twin(*a))
    b2, m2 = twin_side.seal()
    twin_side.upload(b2, m2)
    assert len(twin_calls) == 3                       # one per table
    assert m1 == m2 and any(v is None for v in m1.values())
    assert native_side.store.objects.read(_sst_path(b1.sst_id)) \
        == twin_side.store.objects.read(_sst_path(b2.sst_id))


# --------------------------------------------------------------------- (e)
def _assert_run_equals_its_object(store, run):
    """A run installed without a parse reads like the parse of its bytes."""
    parsed = SsTable.parse(run.sst_id,
                           store.objects.read(_sst_path(run.sst_id)))
    keys = parsed.keys
    assert run.keys == keys and run.vals == parsed.vals
    assert (len(run), run.epoch, run.min_key, run.max_key,
            run.payload_bytes) \
        == (len(parsed), parsed.epoch, parsed.min_key, parsed.max_key,
            parsed.payload_bytes)
    for k in keys + [k + b"\0" for k in keys[::7]] + [b"", b"\xff"]:
        assert run.get(k) == parsed.get(k)
    some = keys[::max(1, len(keys) // 5)]
    cuts = [b""] + some + [k[:-1] for k in some[::2]] \
        + [k + b"\1" for k in some[1::2]]
    for a in cuts:
        for b in cuts:
            assert list(run.iter_range(a, b)) \
                == list(parsed.iter_range(a, b)), (a, b)


@needs_native
def test_parse_gives_arrays_where_the_entry_loop_gives_lists(monkeypatch):
    """What is read back from the object store: `SsTable.parse` through
    the native index (fixed-width tables as FixedParts) against the entry
    by entry loop a machine without a toolchain runs."""
    d = Driver(21)
    d.store.inline_compaction = False
    for _ in range(4):
        d.epochs(2)
        d.checkpoint()
    blobs = {t.sst_id: d.store.objects.read(_sst_path(t.sst_id))
             for t in d.store._l0}
    fast = {i: SsTable.parse(i, b) for i, b in blobs.items()}
    monkeypatch.setattr(native, "lib", lambda: None)
    for i, blob in blobs.items():
        slow = SsTable.parse(i, blob)
        assert [type(p) for p in slow.parts] == [ListPart]
        assert FixedPart in {type(p) for p in fast[i].parts}
        assert (fast[i].epoch, fast[i].keys, fast[i].vals) \
            == (slow.epoch, slow.keys, slow.vals)
        assert fast[i].payload_bytes == slow.payload_bytes
        # and it packs back to the object it came from
        assert sstable.build_sstable_parts(fast[i].epoch, fast[i].parts) \
            == blob
    monkeypatch.undo()
    reopened = HummockStateStore.open(d.store.objects)
    assert list(reopened.iter_range(b"", b"")) \
        == reopened.scan_range(b"", b"") \
        == d.model.range(b"", b"", committed_only=True)
    truncated = bytearray(blobs[next(iter(blobs))])
    del truncated[-30:-4]
    truncated[-4:] = struct.pack("<I", zlib.crc32(bytes(truncated[4:-4])))
    with pytest.raises(sstable.SsTableCorruption):
        SsTable.parse(0, bytes(truncated))


def _dict_merge(store, task):
    """The compactor's merge as it was: a dict overlay of the parsed
    inputs, oldest first, tombstones dropped only at the bottom."""
    merged: dict = {}
    for sst_id in reversed(task.input_ids):          # L1 first, then L0
        t = SsTable.parse(sst_id, store.objects.read(_sst_path(sst_id)))
        merged.update(zip(t.keys, t.vals))
    items = sorted((k, v) for k, v in merged.items()
                   if v is not None or not task.into_l1)
    return build_sstable(task.out_epoch, items)


@pytest.mark.parametrize("seed", range(3))
def test_array_merge_compaction_equals_the_dict_merge(seed):
    d = Driver(100 + seed)
    d.store.inline_compaction = False
    for _ in range(6):
        d.epochs(int(d.rng.integers(1, 3)))
        d.checkpoint()
    for run in d.store._l0:
        _assert_run_equals_its_object(d.store, run)
    # an L0 -> L0 merge of the three oldest runs carries its tombstones
    task = d.store.plan_compaction(10 ** 9, 3, 1 << 40)
    assert not task.into_l1 and len(task.run_ids) == 3
    want = _dict_merge(d.store, task)
    d.store.merge_compaction(task)
    assert d.store.objects.read(_sst_path(task.out_sst_id)) == want
    assert d.store.install_compaction(task) is not None
    assert any(v is None for v in d.store._l0[-1].vals)
    _assert_run_equals_its_object(d.store, d.store._l0[-1])
    d.check_reads()
    # everything into L1: tombstones drop, and only there
    d.epochs(1)
    d.checkpoint()
    task = d.store.plan_compaction(10 ** 9, 64, 1 << 40)
    assert task.into_l1
    want = _dict_merge(d.store, task)
    d.store.merge_compaction(task)
    assert d.store.objects.read(_sst_path(task.out_sst_id)) == want
    d.store.install_compaction(task)
    assert d.store.l0_run_count() == 0
    assert all(v is not None for v in d.store._l1.vals)
    assert task.keys_out == len(d.store._l1)
    _assert_run_equals_its_object(d.store, d.store._l1)
    d.check_reads()
    # a parsed L1 (a reopened store) under fresh array runs
    reopened = HummockStateStore.open(d.store.objects)
    reopened.inline_compaction = False
    d2 = Driver(200 + seed, store=reopened)
    d2.model.committed = dict(d.model.committed)
    d2.epoch = d.epoch
    for t in d2.tables:
        t.init_epoch(d2.epoch)
    d2.epochs(2)
    d2.checkpoint()
    task = reopened.plan_compaction(10 ** 9, 64, 1 << 40)
    assert task.into_l1 and task.l1_id is not None
    want = _dict_merge(reopened, task)
    reopened.merge_compaction(task)
    assert reopened.objects.read(_sst_path(task.out_sst_id)) == want
    reopened.install_compaction(task)
    _assert_run_equals_its_object(reopened, reopened._l1)
    d2.check_reads()


def test_inline_compaction_merges_the_same_arrays():
    d = Driver(42)
    d.store.L0_COMPACT_THRESHOLD = 3
    for _ in range(5):
        d.epochs(1)
        batch, merged = d.seal()
        d.upload(batch, merged)
        l0_before = [t.sst_id for t in d.store._l0]
        l1_before = d.store._l1
        d.commit(batch)
        if d.store._l1 is not l1_before:              # it compacted
            assert d.store.l0_run_count() == 0 and len(l0_before) == 3
            live = sorted((k, v) for k, v in d.model.committed.items()
                          if v is not None)
            assert d.store.objects.read(_sst_path(d.store._l1.sst_id)) \
                == build_sstable(d.store.committed_epoch(), live)
            _assert_run_equals_its_object(d.store, d.store._l1)
        d.check_reads()
    assert d.store._l1 is not None
    reopened = HummockStateStore.open(d.store.objects)
    assert list(reopened.iter_range(b"", b"")) \
        == d.model.range(b"", b"", committed_only=True)


# --------------------------------------------------------------------- (f)
def test_discard_staged_tables_drops_columnar_segments_too():
    d = Driver(7)
    d.epochs(1)
    d.checkpoint()
    for _ in range(3):
        for t in d.tables:
            d.op_columns(t)
            d.op_rows(t)
    d.commit_tables()
    for t in d.tables:
        d.op_columns(t)
    d.commit_tables()                      # two staged epochs
    gone = {11, 14}
    d.store.discard_staged_tables(gone)
    for staged in d.model.staged.values():
        for k in [k for k in staged
                  if int.from_bytes(k[:4], "big") in gone]:
            del staged[k]
    d.check_reads()
    d.checkpoint()                         # what is left uploads as it reads
    d.check_reads()


def test_restage_unconfirmed_brings_columnar_batches_back():
    """A compute-node handle: sealed, uploaded, locally installed, never
    confirmed by meta; partial recovery restages those epochs UNDER the
    writes staged since, out of the local L0."""
    d = Driver(9)
    d.store.manifest_owner = False
    d.epochs(2)
    d.checkpoint()                         # unconfirmed batch 1
    d.epochs(1)
    d.checkpoint()                         # unconfirmed batch 2
    assert d.store.l0_run_count() == 2 and len(d.store._unconfirmed) == 2
    d.write_some()
    d.commit_tables()                      # staged, newer than both
    d.check_reads()
    before = d.model.view()
    d.store.restage_unconfirmed()
    assert d.store.l0_run_count() == 0 and not d.store._unconfirmed
    # the model: nothing was committed after all
    d.model.committed = {}
    d.model.staged = {}
    d.model.sealed = []
    for k, v in before.items():
        assert d.store.get(k) == v
    # the next seal sweeps the restaged epochs with the new one: one SST
    # holding, per key, what the overlay of all of them gives
    batch = d.store.seal(d.epoch - 1)
    d.store.upload_sealed(batch)
    assert d.store.objects.read(_sst_path(batch.sst_id)) \
        == build_sstable(batch.seal_epoch, sorted(before.items()))


# --------------------------------------------------------------------- (g)
@needs_native
async def test_q7_join_and_agg_keys_count_columnar_rows_only_mv_and_source(
        monkeypatch):
    from risingwave_tpu.frontend.session import Session
    from risingwave_tpu.plan.build import _iter_executor_chain
    from risingwave_tpu.state.store import ColumnarSegment
    from risingwave_tpu.utils.metrics import STATE_WRITE_KEYS

    seen: dict = {}                   # table id -> {path: keys}
    real = HummockStateStore.ingest_batch

    def recording(self, batch):
        path = "columnar" if isinstance(batch.puts, ColumnarSegment) \
            else "row"
        by = seen.setdefault(batch.table_id, {})
        by[path] = by.get(path, 0) + len(batch.puts)
        real(self, batch)

    monkeypatch.setattr(HummockStateStore, "ingest_batch", recording)
    before = {c: STATE_WRITE_KEYS[c].value for c in (True, False)}
    s = Session(store=HummockStateStore(InMemObjectStore()))
    w = 1_000_000
    for stmt in ("SET streaming_join_capacity = 4096",
                 "SET streaming_agg_capacity = 256",
                 "SET streaming_durability = 1"):
        await s.execute(stmt)
    await s.execute("CREATE SOURCE bid WITH (connector='nexmark', "
                    "table='bid', chunk_size=256, inter_event_us=1000, "
                    "emit_watermarks=1, watermark_lag_us=500000, "
                    "rate_limit=512)")
    await s.execute(
        "CREATE MATERIALIZED VIEW q7 AS "
        "SELECT B.auction, B.price, B.bidder, B.date_time "
        "FROM bid B JOIN ("
        "  SELECT max(price) AS maxprice, window_end "
        f"  FROM TUMBLE(bid, date_time, {w}) GROUP BY window_end) B1 "
        "ON B.price = B1.maxprice "
        f"AND B.date_time > B1.window_end - {w} "
        "AND B.date_time <= B1.window_end")
    await s.tick(6)
    assert len(s.query("SELECT auction FROM q7")) > 0

    def tables_of(ex):
        for v in vars(ex).values():
            for t in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(t, StateTable):
                    yield t.table_id

    row_ok, columnar_only = set(), set()
    executors = [ex for roots in
                 s.catalog.mvs["q7"].deployment.roots.values()
                 for root in roots for ex in _iter_executor_chain(root)]
    for ex in executors:
        name = type(ex).__name__
        ids = set(tables_of(ex))
        if "Source" in name or "Materialize" in name:
            row_ok |= ids
        elif "Join" in name or "Agg" in name:
            columnar_only |= ids
    assert row_ok and len(columnar_only) >= 3      # two join sides + agg
    wrote = {tid for tid in columnar_only if tid in seen}
    assert len(wrote) >= 2
    for tid in wrote:
        assert set(seen[tid]) == {"columnar"}, (tid, seen[tid])
    assert {tid for tid, by in seen.items() if "row" in by} <= row_ok
    grew = {c: STATE_WRITE_KEYS[c].value - before[c] for c in (True, False)}
    assert grew[True] == sum(by.get("columnar", 0) for by in seen.values())
    assert grew[False] == sum(by.get("row", 0) for by in seen.values())
    assert grew[True] > 0 and grew[False] > 0
    await s.drop_all()
