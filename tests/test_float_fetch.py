"""Float columns through the persist fetch and the float-bit images.

The TPU compiler has no bitcast from f64 (common/floatbits.py), so the
packed d2h fetch carries f64 as its own dtype segment and f32 as its int32
bits. These are the bit-identity checks of that path on the IEEE backend
(siblings of tests/test_native.py's codec identity tests): what is
persisted is what was stored, NaN payloads, signed zeros and subnormals
included, and the device-side hashes agree with their host twins.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common.floatbits import (float_identity_bits,
                                             float_pair_bits,
                                             float_pair_bits_np)
from risingwave_tpu.common.vnode import compute_vnodes, compute_vnodes_numpy
from risingwave_tpu.utils.d2h import (fetch_columns, fetch_prefix_groups,
                                      pack_for_fetch, unpack_fetched)

_F64_BITS = np.array([
    0x0000000000000000,        # +0.0
    0x8000000000000000,        # -0.0
    0x3FF0000000000000,        # 1.0
    0x3FED0E5604189375,        # 0.908
    0x0000000000000001,        # smallest subnormal
    0x800FFFFFFFFFFFFF,        # largest negative subnormal
    0x0010000000000000,        # smallest normal
    0x7FEFFFFFFFFFFFFF,        # largest finite
    0x7FF0000000000000,        # +inf
    0xFFF0000000000000,        # -inf
    0x7FF8000000000000,        # canonical quiet NaN
    0x7FF8000000000001,        # quiet NaN with a payload
    0xFFF8DEADBEEF0001,        # negative quiet NaN with a payload
    0x7FF4000000000001,        # signalling NaN
    0x4340000000000001,        # 2^53 + 2 (needs every mantissa bit)
    0x3FB999999999999A,        # 0.1
], dtype=np.uint64)
_F32_BITS = np.array([
    0x00000000, 0x80000000, 0x3F800000, 0x00000001, 0x807FFFFF, 0x00800000,
    0x7F7FFFFF, 0x7F800000, 0xFF800000, 0x7FC00000, 0x7FC00001, 0xFFC12345,
    0x7FA00001, 0x3DCCCCCD,
], dtype=np.uint32)


def _bits(a: np.ndarray) -> np.ndarray:
    return a.view(np.uint64 if a.dtype == np.float64 else np.uint32)


@pytest.mark.parametrize("vals", [
    pytest.param(_F64_BITS.view(np.float64), id="f64"),
    pytest.param(_F32_BITS.view(np.float32), id="f32"),
])
def test_fetch_columns_round_trips_floats_bit_exactly(vals):
    ints = np.arange(len(vals), dtype=np.int64) - 3
    flags = (np.arange(len(vals)) % 2).astype(bool)
    got_i, got_f, got_b = fetch_columns(
        [jnp.asarray(ints), jnp.asarray(vals), jnp.asarray(flags)])
    assert got_f.dtype == vals.dtype
    np.testing.assert_array_equal(_bits(got_f), _bits(vals))
    np.testing.assert_array_equal(got_i, ints)
    np.testing.assert_array_equal(got_b, flags)


def test_mixed_f64_f32_int_payload_packs_into_two_buffers():
    f64 = _F64_BITS.view(np.float64)
    f32 = _F32_BITS.view(np.float32)
    cols = [jnp.asarray(f64), jnp.arange(5, dtype=jnp.int32),
            jnp.asarray(f32), jnp.asarray(f64[::-1].copy()),
            jnp.arange(3, dtype=jnp.int8)]
    (flat_i, flat_f), metas = pack_for_fetch(cols)
    assert flat_i.dtype == jnp.int64 and flat_f.dtype == jnp.float64
    assert flat_i.shape[0] == 5 + len(f32) + 3
    assert flat_f.shape[0] == 2 * len(f64)
    out = unpack_fetched((np.asarray(flat_i), np.asarray(flat_f)), metas)
    for got, want in zip(out, cols):
        want = np.asarray(want)
        assert got.dtype == want.dtype
        if want.dtype.kind == "f":
            np.testing.assert_array_equal(_bits(got), _bits(want))
        else:
            np.testing.assert_array_equal(got, want)


def test_int_only_payload_has_no_float_buffer():
    (flat_i, flat_f), _ = pack_for_fetch([jnp.arange(4, dtype=jnp.int64)])
    assert flat_f is None and flat_i.shape == (4,)


def test_prefix_groups_trim_float_columns_bit_exactly():
    f64 = jnp.asarray(np.resize(_F64_BITS, 64).view(np.float64))
    f32 = jnp.asarray(np.resize(_F32_BITS, 64).view(np.float32))
    k = jnp.arange(64, dtype=jnp.int64)
    (a, b, c), (d,) = fetch_prefix_groups([([k, f64, f32], 13), ([f64], 5)])
    np.testing.assert_array_equal(a, np.arange(13))
    np.testing.assert_array_equal(_bits(b), _bits(np.asarray(f64))[:13])
    np.testing.assert_array_equal(_bits(c), _bits(np.asarray(f32))[:13])
    np.testing.assert_array_equal(_bits(d), _bits(np.asarray(f64))[:5])


@pytest.mark.parametrize("counts,packed_rows", [
    ((0, 1), 128), ((5, 6), 128), ((64, 0), 128),      # one shape below 64
    ((65, 3), 192), ((300, 64), 576), ((1000, 9), 1088),  # then powers of 2
    ((5000, 5000), 2 * 4096),                          # never past the array
])
def test_small_prefix_counts_share_one_packed_shape(counts, packed_rows):
    """A payload's eager slice / concatenate programs are keyed by the
    bucket of each group: every count up to 64 (0 too) is one bucket, so a
    group that walks through 0, 1, 5, 6 rows beside a large neighbour meets
    no new program; the rows that come back are the exact prefixes."""
    from risingwave_tpu.utils.d2h import (finish_prefix_groups,
                                          prepare_prefix_groups)
    k = jnp.arange(4096, dtype=jnp.int64)
    groups = [([k], counts[0]), ([k + 7], counts[1])]
    flat, metas, meta = prepare_prefix_groups(groups)
    assert flat[0].shape == (packed_rows,)
    (a,), (b,) = finish_prefix_groups(
        tuple(None if f is None else np.asarray(f) for f in flat),
        metas, meta)
    np.testing.assert_array_equal(a, np.arange(min(counts[0], 4096)))
    np.testing.assert_array_equal(b, np.arange(min(counts[1], 4096)) + 7)


def test_identity_bits_are_the_ieee_bits_on_this_backend():
    f64 = _F64_BITS.view(np.float64)
    f32 = _F32_BITS.view(np.float32)
    got = np.asarray(jax.jit(float_identity_bits)(jnp.asarray(f64)))
    np.testing.assert_array_equal(got.view(np.uint64), _F64_BITS)
    got32 = np.asarray(jax.jit(float_identity_bits)(jnp.asarray(f32)))
    np.testing.assert_array_equal(got32, f32.view(np.int32).astype(np.int64))


def _float_keys() -> np.ndarray:
    rng = np.random.default_rng(22)
    return np.concatenate([
        _F64_BITS.view(np.float64),
        rng.standard_normal(4096) * 1e6,
        rng.integers(1, 10**7, 4096) * 0.908,
        np.float64(10.0) ** rng.integers(-300, 300, 512),
    ])


def test_pair_bits_device_equals_host_twin():
    keys = _float_keys()
    dev = np.asarray(jax.jit(float_pair_bits)(jnp.asarray(keys)))
    np.testing.assert_array_equal(dev, float_pair_bits_np(keys))
    # a hash input: +-0.0 and every NaN each share one image, distinct
    # ordinary values keep distinct images
    z = float_pair_bits_np(np.array([0.0, -0.0]))
    assert z[0] == z[1]
    n = float_pair_bits_np(_F64_BITS[10:14].view(np.float64))
    assert len(set(n.tolist())) == 1
    ordinary = keys[16:16 + 8192]
    assert len(np.unique(float_pair_bits_np(ordinary))) \
        == len(np.unique(ordinary))


def test_vnode_hash_of_float_keys_device_equals_host():
    keys = _float_keys()
    ids = np.arange(len(keys), dtype=np.int64)
    with np.errstate(over="ignore", invalid="ignore"):
        k32 = keys.astype(np.float32)
    dev = np.asarray(compute_vnodes(
        [jnp.asarray(keys), jnp.asarray(ids), jnp.asarray(k32)]))
    np.testing.assert_array_equal(dev, compute_vnodes_numpy([keys, ids, k32]))
    assert len(np.unique(dev)) > 200     # still spreads over the vnodes


def test_hll_bucket_rank_of_floats_device_equals_host():
    from risingwave_tpu.expr.hll import _bucket_rank_jnp, _bucket_rank_np
    keys = _float_keys()
    with np.errstate(over="ignore", invalid="ignore"):
        k32 = keys.astype(np.float32)
    for vals in (keys, k32):
        b_np, r_np = _bucket_rank_np(vals)
        b_j, r_j = _bucket_rank_jnp(jnp.asarray(vals))
        np.testing.assert_array_equal(np.asarray(b_j), b_np)
        np.testing.assert_array_equal(np.asarray(r_j), r_np)
