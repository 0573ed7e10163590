"""`BatchCodec` (state/serde.py) against the per-row codec it stands for.

`StateTable.write_chunk_columns` encodes a whole batch with numpy, whatever
the table's column types. The reference is what the store read and wrote
before: `encode_memcomparable` for the key, `RowSerde.encode` for the
value, one row at a time. The bytes have to be those, so that a store
written one way is read the other.
"""

import struct

import numpy as np
import pytest

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import OP_DELETE, OP_INSERT
from risingwave_tpu.common.vnode import compute_vnodes_numpy
from risingwave_tpu.state import StateTable
from risingwave_tpu.state.hummock import HummockStateStore, _sst_path
from risingwave_tpu.state.object_store import InMemObjectStore
from risingwave_tpu.state.serde import (RowSerde, _fmt_char,
                                        decode_memcomparable,
                                        encode_memcomparable)
from risingwave_tpu.state.store import (ColumnarSegment, MemoryStateStore,
                                        encode_table_key)

TYPES = list(DataType)
EVERY = schema(*[(t.value, t) for t in TYPES])
N = 96


@pytest.mark.parametrize("t", TYPES, ids=lambda t: t.value)
def test_host_width_is_the_value_formats_width(t):
    """The batch codec lays a field out by its numpy dtype, `RowSerde` by
    its struct character: one width, and one kind, for every type."""
    dt = np.dtype(t.np_dtype)
    assert dt.itemsize == struct.calcsize("<" + _fmt_char(t))
    assert dt.kind == {"?": "b", "f": "f", "d": "f"}.get(_fmt_char(t), "i")


def column(t: DataType, rng, n=N) -> np.ndarray:
    """n values of `t`, the edges of its range first."""
    dt = np.dtype(t.np_dtype)
    if dt.kind == "b":
        return rng.integers(2, size=n).astype(bool)
    if dt.kind == "f":
        info = np.finfo(dt)
        edges = [0.0, -0.0, np.inf, -np.inf, np.nan, info.max, info.min,
                 info.tiny, -info.tiny, 1.5, -1.5]
        body = (rng.standard_normal(n) * 10.0 ** rng.integers(-30, 30, n))
        return np.concatenate([edges, body])[:n].astype(dt)
    info = np.iinfo(dt)
    edges = [0, -1, 1, info.min, info.max, info.min + 1, info.max - 1]
    body = rng.integers(info.min, info.max, size=n, dtype=dt, endpoint=True)
    return np.concatenate([np.asarray(edges, dtype=dt), body])[:n]


def batch(sch, rng, n=N, null_share=0.0, not_null=()):
    cols = [column(f.data_type, rng, n) for f in sch]
    valids = [None if j in not_null or not null_share
              else rng.random(n) >= null_share for j in range(len(sch))]
    return cols, valids


def rows_of(cols, valids, idx=None):
    idx = range(len(cols[0])) if idx is None else idx
    return [tuple(c[r].item() if v is None or v[r] else None
                  for c, v in zip(cols, valids)) for r in idx]


def ref_key(t: StateTable, row) -> bytes:
    """table id ++ vnode ++ memcomparable(pk), by the per-row codec."""
    vn = int(compute_vnodes_numpy(
        [np.asarray([0 if row[i] is None else row[i]],
                    dtype=t.schema[i].data_type.np_dtype)
         for i in t.dist_key_indices])[0]) if t.dist_key_indices else 0
    return encode_table_key(t.table_id, vn, encode_memcomparable(
        tuple(row[i] for i in t.pk_indices), t._pk_types, t.pk_descending))


def the_segment(t: StateTable) -> ColumnarSegment:
    (seg,) = [s for s in t._mem if isinstance(s, ColumnarSegment)]
    return seg


# one case per type as THE pk column, ascending and descending: each kind's
# key rule (sign flip at its own width, the float rule, the bool byte)
PK_CASES = [(t, desc) for t in TYPES for desc in (False, True)]


@pytest.mark.parametrize(
    "pk_type,desc", PK_CASES,
    ids=[f"{t.value}-{'desc' if d else 'asc'}" for t, d in PK_CASES])
def test_segment_bytes_are_the_row_codecs(pk_type, desc):
    """A schema holding EVERY type, NULLs in its value columns, one type as
    pk beside an INT64: key and value bytes equal the per-row codec's row
    for row, the segment reads back through `decode`, and the keys of one
    vnode sort as the pk values do."""
    rng = np.random.default_rng(TYPES.index(pk_type) * 2 + desc)
    pk = (TYPES.index(pk_type), TYPES.index(DataType.INT64))
    t = StateTable(MemoryStateStore(), 9, EVERY, pk, dist_key_indices=(),
                   pk_descending=(desc, False), check_consistency=False)
    t.init_epoch(1)
    cols, valids = batch(EVERY, rng, null_share=0.3, not_null=pk)
    ops = rng.choice([OP_INSERT, OP_DELETE], size=N).astype(np.int8)
    vis = rng.random(N) > 0.1
    t.write_chunk_columns(ops, cols, vis, valids)
    assert t.row_path_rows == 0
    seg = the_segment(t)
    idx = np.flatnonzero(vis)
    rows = rows_of(cols, valids, idx)
    serde = RowSerde(EVERY)
    assert len(seg) == len(rows)
    for r, row in enumerate(rows):
        assert seg.keys[r].tobytes() == ref_key(t, row), row
        assert seg.vals[r].tobytes() == serde.encode(row), row
        # what a reader does with the bytes (NaN != NaN: compare re-encoded)
        assert serde.encode(serde.decode(seg.vals[r].tobytes())) \
            == seg.vals[r].tobytes()
        assert encode_memcomparable(
            decode_memcomparable(seg.keys[r, 5:].tobytes(), t._pk_types,
                                 t.pk_descending),
            t._pk_types, t.pk_descending) == seg.keys[r, 5:].tobytes()
    np.testing.assert_array_equal(seg.put, ops[idx] == OP_INSERT)
    # order: the pk column's values along the sorted keys never step back
    # (forward, for a descending column); NaN sorts above +inf, so last
    in_key_order = cols[pk[0]][idx][np.argsort(seg.key_view, kind="stable")]
    steps = np.diff(in_key_order[~np.isnan(in_key_order)]
                    if in_key_order.dtype.kind == "f"
                    else in_key_order.astype(object))
    assert (steps <= 0).all() if desc else (steps >= 0).all()
    if in_key_order.dtype.kind == "f":
        nan_at = np.flatnonzero(np.isnan(in_key_order))
        assert not len(nan_at) or (
            nan_at.max() < len(nan_at) if desc
            else nan_at.min() >= len(in_key_order) - len(nan_at))


@pytest.mark.parametrize("dist", ["first_pk", "other_column", "two_columns"])
def test_vnodes_are_the_row_paths(dist):
    """The vnode byte is `compute_vnodes_numpy` over the dist-key columns
    at their own dtypes, a NULL lane as 0: what `_vnode_of` computes for a
    row's get or delete."""
    sch = schema(("i", DataType.INT64), ("s", DataType.VARCHAR),
                 ("f", DataType.FLOAT64), ("h", DataType.INT16))
    dist_idx = {"first_pk": None, "other_column": (3,),
                "two_columns": (1, 2)}[dist]
    t = StateTable(MemoryStateStore(), 3, sch, (0, 1),
                   dist_key_indices=dist_idx, check_consistency=False)
    t.init_epoch(1)
    rng = np.random.default_rng(5)
    cols, valids = batch(sch, rng, null_share=0.25, not_null=(0, 1))
    t.write_chunk_columns(np.zeros(N, np.int8), cols, np.ones(N, bool),
                          valids)
    seg = the_segment(t)
    rows = rows_of(cols, valids)
    assert [int(k[4]) for k in seg.keys] == [t._vnode_of(r) for r in rows]
    assert len({int(k[4]) for k in seg.keys}) > 8
    for row in rows[:16]:
        assert t.get_row((row[0], row[1]), dist_values=tuple(
            row[i] for i in t.dist_key_indices)) is not None


def test_a_null_pk_lane_takes_the_row_form_and_reads_back():
    sch = schema(("k", DataType.INT64), ("s", DataType.VARCHAR),
                 ("v", DataType.FLOAT32))
    t = StateTable(MemoryStateStore(), 4, sch, (0, 1),
                   check_consistency=False)
    t.init_epoch(1)
    n = 40
    cols = [np.arange(n) % 7, (np.arange(n) % 5).astype(np.int32),
            np.arange(n, dtype=np.float32) / 4]
    valids = [np.arange(n) % 4 != 1, np.arange(n) % 6 != 2,
              np.arange(n) % 3 != 0]
    null_pk = ~(valids[0] & valids[1])
    t.write_chunk_columns(np.zeros(n, np.int8), cols, np.ones(n, bool),
                          valids)
    assert t.row_path_rows == int(null_pk.sum()) > 0
    assert len(the_segment(t)) == n - int(null_pk.sum())
    rows = rows_of(cols, valids)
    want = {}
    for row in rows:                       # last write of a key counts
        want[ref_key(t, row)] = row
    assert dict(t.iter_all()) == want
    for row in rows:
        assert t.get_row((row[0], row[1])) == want[ref_key(t, row)]
    t.commit(2)
    t.store.sync(1)
    assert dict(t.iter_all()) == want
    serde = RowSerde(sch)
    assert t.store._vals == {k: serde.encode(r) for k, r in want.items()}
    # a batch whose every row has a NULL pk leaves no segment at all
    t.write_chunk_columns(np.zeros(2, np.int8), [c[:2] for c in cols],
                          np.ones(2, bool),
                          [np.zeros(2, bool), None, None])
    assert all(isinstance(s, dict) for s in t._mem)


def test_no_valids_means_no_nulls_and_invisible_rows_are_skipped():
    sch = schema(("k", DataType.INT32), ("b", DataType.BOOLEAN))
    t = StateTable(MemoryStateStore(), 5, sch, (0,))
    t.init_epoch(1)
    t.write_chunk_columns(np.zeros(4, np.int8),
                          [np.arange(4), np.asarray([1, 0, 1, 0])],
                          np.asarray([True, False, True, True]))
    assert sorted(r for _k, r in t.iter_all()) \
        == [(0, True), (2, True), (3, False)]
    t.write_chunk_columns(np.zeros(4, np.int8),
                          [np.arange(4), np.zeros(4)], np.zeros(4, bool))
    assert len(t._mem) == 1 and t.row_path_rows == 0


STORE_CASES = {
    "all_int64": (schema(*[(f"c{i}", DataType.INT64) for i in range(4)]),
                  (0, 1), None, 0.0),
    "q8_person": (schema(("id", DataType.INT64), ("name", DataType.VARCHAR),
                         ("starttime", DataType.TIMESTAMP),
                         ("n", DataType.INT64)), (0, 1, 2), None, 0.0),
    "every_type_null_values": (EVERY, (TYPES.index(DataType.VARCHAR),
                                       TYPES.index(DataType.INT64)),
                               None, 0.3),
    "float_pk_descending": (schema(("f", DataType.FLOAT64),
                                   ("d", DataType.DATE),
                                   ("x", DataType.FLOAT32)), (0, 1),
                            (True, False), 0.0),
    "null_pk_lanes": (schema(("k", DataType.INT64), ("s", DataType.VARCHAR),
                             ("v", DataType.INT16)), (0, 1), None, 0.2),
}


@pytest.mark.parametrize("case", STORE_CASES)
def test_rows_and_codec_write_the_same_store(case):
    """The same writes through `write_chunk_rows` and through the codec,
    three epochs with deletes and re-writes of earlier keys: equal
    mem-table reads, equal `iter_all()` after the commit, and the objects
    the two stores upload are the same bytes."""
    sch, pk, desc, null_share = STORE_CASES[case]
    not_null = () if case == "null_pk_lanes" else pk
    stores, tables = [], []
    for _ in range(2):
        store = HummockStateStore(InMemObjectStore())
        t = StateTable(store, 21, sch, pk, pk_descending=desc,
                       check_consistency=False)
        t.init_epoch(1)
        stores.append(store)
        tables.append(t)
    by_rows, by_codec = tables
    rng = np.random.default_rng(11)
    for epoch in (1, 2, 3):
        for _ in range(2):
            cols, valids = batch(sch, rng, 48, null_share, not_null)
            for i in pk:                   # a small domain: keys repeat
                if cols[i].dtype.kind != "f":
                    cols[i] = (cols[i].astype(np.int64) % 6).astype(
                        cols[i].dtype)
            ops = rng.choice([OP_INSERT, OP_DELETE], size=48,
                             p=[0.7, 0.3]).astype(np.int8)
            vis = rng.random(48) > 0.15
            by_codec.write_chunk_columns(ops, cols, vis, valids)
            idx = np.flatnonzero(vis)
            by_rows.write_chunk_rows(
                list(zip(ops[idx].tolist(), rows_of(cols, valids, idx))))
        serde = RowSerde(sch)

        def image(t):
            return [(k, serde.encode(r)) for k, r in t.iter_all()]
        assert image(by_codec) == image(by_rows)
        objects = []
        for store, t in zip(stores, tables):
            t.commit(epoch + 1)
            sealed = store.seal(epoch)
            store.upload_sealed(sealed)
            objects.append(store.objects.read(_sst_path(sealed.sst_id)))
            store.commit_sealed(sealed)
        assert objects[0] == objects[1]
        assert image(by_codec) == image(by_rows)
    assert by_rows.row_path_rows > 0
    assert (by_codec.row_path_rows > 0) == (case == "null_pk_lanes")
