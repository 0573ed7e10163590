"""Native C++ row codec: bit-identical to the Python serde + vnode hash."""

import os

import numpy as np
import pytest

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.vnode import crc32_numpy
from risingwave_tpu.native import crc32_i64_batch, lib

pytestmark = pytest.mark.skipif(lib() is None, reason="no C++ toolchain")


def test_crc32_matches_numpy_and_device_table():
    rng = np.random.default_rng(3)
    vals = rng.integers(-(1 << 62), 1 << 62, size=(128, 2))
    got = crc32_i64_batch(vals)
    want = crc32_numpy([vals[:, 0].astype(np.int64),
                        vals[:, 1].astype(np.int64)])
    np.testing.assert_array_equal(got, want)


def test_write_chunk_columns_equals_rows():
    from risingwave_tpu.state import MemoryStateStore, StateTable
    sch = schema(("k", DataType.INT64), ("v", DataType.INT64),
                 ("w", DataType.INT64))
    rng = np.random.default_rng(7)
    cols = [rng.integers(-(1 << 40), 1 << 40, size=50) for _ in range(3)]
    ops = np.zeros(50, dtype=np.int8)
    ops[40:] = 1  # deletes
    vis = rng.random(50) > 0.2

    s1 = MemoryStateStore()
    t1 = StateTable(s1, 1, sch, (0, 1))
    t1.init_epoch(1)
    t1.write_chunk_columns(ops, cols, vis)
    t1.commit(2)

    s2 = MemoryStateStore()
    t2 = StateTable(s2, 1, sch, (0, 1))
    t2.init_epoch(1)
    rows = [(int(ops[i]), tuple(int(c[i]) for c in cols))
            for i in np.flatnonzero(vis)]
    t2.write_chunk_rows(rows)
    t2.commit(2)

    assert s1._vals == s2._vals  # bit-identical store contents


def test_sst_pack_fixed_matches_python_builder():
    """The native record packer against `build_sstable`'s per-entry loop:
    puts and tombstones mixed, golden bytes for one small run."""
    from risingwave_tpu.native import sst_pack_fixed
    from risingwave_tpu.state.sstable import (FixedPart, _pack_fixed_numpy,
                                              build_sstable,
                                              build_sstable_parts)
    keys = np.asarray([[0, 0, 0, 7, 1], [0, 0, 0, 7, 2], [0, 0, 0, 7, 9]],
                      dtype=np.uint8)
    vals = np.asarray([[0xAA, 0xBB], [0, 0], [0xCC, 0xDD]], dtype=np.uint8)
    put = np.asarray([True, False, True])
    out = np.zeros(3 * (8 + 5) + 2 * 2, dtype=np.uint8)
    assert sst_pack_fixed(keys, vals, put, out) == out.size
    assert out.tobytes() == (
        b"\x05\x00\x00\x00\x00\x00\x00\x07\x01\x02\x00\x00\x00\xaa\xbb"
        b"\x05\x00\x00\x00\x00\x00\x00\x07\x02\xff\xff\xff\xff"
        b"\x05\x00\x00\x00\x00\x00\x00\x07\x09\x02\x00\x00\x00\xcc\xdd")
    np.testing.assert_array_equal(_pack_fixed_numpy(keys, vals, put), out)
    # and back: the index of the records, then the matrices
    from risingwave_tpu.native import sst_index, sst_unpack_fixed
    body = b"head" + out.tobytes() + b"tail"
    koff, klen, vlen = sst_index(body, 4, len(body) - 4, 3)
    assert koff.tolist() == [8, 23, 36] and klen.tolist() == [5, 5, 5]
    assert vlen.tolist() == [2, 0xFFFFFFFF, 2]
    got_keys, got_vals = sst_unpack_fixed(body, koff, put, 5, 2)
    np.testing.assert_array_equal(got_keys, keys)
    np.testing.assert_array_equal(got_vals, vals)
    with pytest.raises(ValueError):
        sst_index(body, 4, len(body) - 5, 3)       # a record cut short
    with pytest.raises(ValueError):
        sst_index(body, 4, len(body) - 4, 2)       # bytes left over
    with pytest.raises(ValueError):
        sst_pack_fixed(keys, vals, put, out[:-1])

    rng = np.random.default_rng(5)
    n = 500
    keys = rng.integers(0, 3, size=(n, 23), dtype=np.uint8)
    keys[:, :4] = (0, 0, 1, 2)
    keys = np.unique(keys, axis=0)          # sorted as bytes, and unique
    n = len(keys)
    vals = rng.integers(0, 256, size=(n, 33), dtype=np.uint8)
    put = rng.random(n) > 0.4
    entries = [(keys[r].tobytes(), vals[r].tobytes() if put[r] else None)
               for r in range(n)]
    assert build_sstable_parts(9, [FixedPart(258, keys, vals, put)]) \
        == build_sstable(9, entries)


def test_stale_artifact_is_rebuilt(tmp_path, monkeypatch):
    """`_rowcodec.so` is built from the tracked source where it is
    missing, unloadable, or the build of an older rowcodec.cc that lacks
    a function this tree calls."""
    import subprocess
    from risingwave_tpu import native
    so = tmp_path / "_rowcodec.so"
    monkeypatch.setattr(native, "_SO", str(so))
    load = native.lib.__wrapped__

    def plant(build) -> None:
        # a NEW file swapped in, newer than the source: writing into the
        # mapped object of the previous load would fault this process
        tmp = tmp_path / "next.so"
        build(tmp)
        os.utime(tmp, (2 ** 31, 2 ** 31))
        os.replace(tmp, so)

    assert load() is not None and so.exists()          # missing: built
    plant(lambda tmp: tmp.write_bytes(b"not an ELF object"))
    assert load().sst_pack_fixed is not None           # unloadable: rebuilt
    old = tmp_path / "old.cc"                          # an older source's build
    old.write_text('extern "C" { void crc32_i64_cols() {} }\n')
    plant(lambda tmp: subprocess.run(
        ["g++", "-shared", "-fPIC", "-o", str(tmp), str(old)], check=True))
    lib_ = load()
    assert lib_.sst_pack_fixed is not None and lib_.crc32_i64_cols is not None
