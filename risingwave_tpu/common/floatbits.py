"""Integer images of float columns that the TPU compiler accepts.

Under x64 the TPU compiler rewrites 64-bit element types away, and that
rewrite has no rule for `bitcast-convert` FROM `f64` (to `int64`,
`uint64` or a `[N, 2] uint32`): a TPU holds an f64 as a PAIR of f32
(hi, lo) — about 49 mantissa bits and f32's exponent range — so there
are no IEEE-754 double bits on the device to reinterpret. `f32 -> i32`
is accepted, and so is every conversion and arithmetic op on f64.

Two images, for the two things the engine needs float bits for:

* `float_pair_bits` (+ numpy twin): `(bits(hi) << 32) | bits(lo)` with
  `hi = f32(x)`, `lo = f32(x - hi)` — plain conversions, the SAME
  arithmetic on every backend and on the host, so a device-side hash
  and its host mirror agree. On the TPU it is the value's exact
  representation; on an IEEE backend doubles that differ only below
  ~2^-48 relative share an image, which a HASH input tolerates (vnode
  placement, HLL buckets) and an identity compare does not.
* `float_identity_bits`: an int64 whose equality IS row identity for
  snapshot diffs — the IEEE bits wherever the backend has them (CPU,
  GPU: unchanged behaviour), the pair image on the TPU, chosen at
  lowering time by `lax.platform_dependent`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# every NaN maps to one image (payloads of the hi/lo halves are not
# specified identically across backends)
_NAN_IMAGE = 0x7FC000007FC00000
# f32 subnormals flush to zero on XLA backends and not in numpy: both
# twins flush each half explicitly
_F32_TINY = float(np.finfo(np.float32).tiny)


def float_pair_bits(x: jnp.ndarray) -> jnp.ndarray:
    """float column -> int64 (hi, lo) f32-pair image (see module doc)."""
    x = x.astype(jnp.float64)
    hi = x.astype(jnp.float32)
    hi = jnp.where(jnp.abs(hi) < _F32_TINY, jnp.float32(0), hi)
    # hi = +-inf (|x| beyond f32) gives lo = nan; pin it to 0 so the
    # image stays a function of the value alone
    lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
    lo = jnp.where(jnp.isinf(hi) | (jnp.abs(lo) < _F32_TINY),
                   jnp.float32(0), lo)
    hb = jax.lax.bitcast_convert_type(hi, jnp.int32).astype(jnp.int64)
    lb = jax.lax.bitcast_convert_type(lo, jnp.uint32).astype(jnp.int64)
    return jnp.where(jnp.isnan(x), jnp.int64(_NAN_IMAGE), (hb << 32) | lb)


def float_pair_bits_np(x: np.ndarray) -> np.ndarray:
    """Host twin of `float_pair_bits` — MUST produce identical images."""
    x = np.asarray(x).astype(np.float64)
    with np.errstate(over="ignore", invalid="ignore"):
        hi = x.astype(np.float32)
        hi = np.where(np.abs(hi) < _F32_TINY, np.float32(0), hi)
        lo = (x - hi.astype(np.float64)).astype(np.float32)
        lo = np.where(np.isinf(hi) | (np.abs(lo) < _F32_TINY),
                      np.float32(0), lo)
    hb = hi.view(np.int32).astype(np.int64)
    lb = lo.view(np.uint32).astype(np.int64)
    return np.where(np.isnan(x), np.int64(_NAN_IMAGE), (hb << 32) | lb)


def float_identity_bits(x: jnp.ndarray) -> jnp.ndarray:
    """float column -> int64 identity lane: equal lanes <=> the same
    stored value. f32 reinterprets directly (exact everywhere); f64 is
    the IEEE bit pattern except on the TPU, where the pair image is the
    stored value's exact identity."""
    if x.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(x, jnp.int32).astype(jnp.int64)
    x = x.astype(jnp.float64)
    return jax.lax.platform_dependent(
        x, tpu=float_pair_bits,
        default=lambda v: jax.lax.bitcast_convert_type(v, jnp.int64))
