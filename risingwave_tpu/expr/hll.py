"""HyperLogLog for approx_count_distinct — 64 registers, byte-packed.

Reference: src/expr/impl/src/aggregate/approx_count_distinct/ (the
reference keeps per-bucket structures; the streaming variant there adds
retraction counts). TPU re-design: m = 64 registers packed as 8 int64
words of 8 bytes each, so the whole sketch is EIGHT scalar agg states
per group — the planner lowers approx_count_distinct into 8 hidden
register-word calls (one per word lane) plus an `hll_estimate` post
projection, exactly the way avg lowers to sum+count. Register update
is bytewise max, which each lane computes with 8 segment_max
reductions (a row contributes to exactly one byte of one lane).

Append-only inputs only (register max cannot retract) — the planner
refuses otherwise, like the reference's append-only agg variants.

The SAME hash / bucket / rank / estimator runs in numpy for the batch
engine (hll_estimate_numpy), so streaming and batch agree bit-for-bit
— which keeps the differential fuzzer usable over this aggregate.

Relative error ~ 1.04/sqrt(64) ~ 13%.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..common.floatbits import float_pair_bits, float_pair_bits_np

M = 64               # registers
LANES = 8            # int64 words per sketch
ALPHA_M = 0.709      # alpha for m = 64


# ------------------------------------------------------------------ hash
def _splitmix64_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15))
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def _splitmix64_jnp(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.uint64)
    x = x + jnp.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


# rank = index of lowest set bit of the post-bucket hash bits, 1-based,
# 59 when they are all zero (58 usable bits after the 6 bucket bits).
# PURE INTEGER math (SWAR popcount of low-1): a float log2 of an exact
# power of two came back 2.999... under a cross-machine XLA AOT cache,
# flooring ranks off by one — bit positions must never route through
# floating point.
_MAX_RANK = 59


def _popcount_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = x - ((x >> np.uint64(1)) & np.uint64(0x5555555555555555))
        x = ((x & np.uint64(0x3333333333333333))
             + ((x >> np.uint64(2)) & np.uint64(0x3333333333333333)))
        x = (x + (x >> np.uint64(4))) & np.uint64(0x0F0F0F0F0F0F0F0F)
        return ((x * np.uint64(0x0101010101010101))
                >> np.uint64(56)).astype(np.int64)


def _popcount_jnp(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.uint64)
    x = x - ((x >> jnp.uint64(1)) & jnp.uint64(0x5555555555555555))
    x = ((x & jnp.uint64(0x3333333333333333))
         + ((x >> jnp.uint64(2)) & jnp.uint64(0x3333333333333333)))
    x = (x + (x >> jnp.uint64(4))) & jnp.uint64(0x0F0F0F0F0F0F0F0F)
    return ((x * jnp.uint64(0x0101010101010101))
            >> jnp.uint64(56)).astype(jnp.int64)


def _to_bits_np(vals: np.ndarray) -> np.ndarray:
    """Distinct VALUES must map to distinct BIT patterns: floats take
    their f32-pair image (common/floatbits.py — the TPU compiler has no
    bitcast from f64; a value-cast would collapse every float sharing an
    integer part)."""
    if vals.dtype == np.uint64:
        return vals
    if np.issubdtype(vals.dtype, np.floating):
        return float_pair_bits_np(vals).view(np.uint64)
    return vals.astype(np.int64).view(np.uint64)


def _bucket_rank_np(vals: np.ndarray):
    h = _splitmix64_np(_to_bits_np(vals))
    bucket = (h & np.uint64(M - 1)).astype(np.int64)
    rest = (h >> np.uint64(6)).astype(np.uint64)
    with np.errstate(over="ignore"):
        low = rest & (~rest + np.uint64(1))
        tz = _popcount_np(low - np.uint64(1))
    rank = np.where(rest == 0, _MAX_RANK, tz + 1)
    return bucket, rank.astype(np.int64)


def _to_bits_jnp(vals: jnp.ndarray) -> jnp.ndarray:
    if jnp.issubdtype(vals.dtype, jnp.floating):
        return float_pair_bits(vals).view(jnp.uint64)
    return vals.astype(jnp.int64).view(jnp.uint64)


def _bucket_rank_jnp(vals: jnp.ndarray):
    h = _splitmix64_jnp(_to_bits_jnp(vals))
    bucket = (h & jnp.uint64(M - 1)).astype(jnp.int64)
    rest = (h >> jnp.uint64(6))
    low = rest & (~rest + jnp.uint64(1))
    tz = _popcount_jnp(low - jnp.uint64(1))
    rank = jnp.where(rest == 0, _MAX_RANK, tz + 1)
    return bucket, rank.astype(jnp.int64)


# ------------------------------------------------------- streaming (jnp)
def lane_partial(values: jnp.ndarray, signs: jnp.ndarray,
                 seg_ids: jnp.ndarray, num_segments: int,
                 lane: int) -> jnp.ndarray:
    """Per-segment packed register word for `lane` (buckets
    [8*lane, 8*lane+8))."""
    bucket, rank = _bucket_rank_jnp(values)
    live = signs > 0
    in_lane = (bucket >> 3) == lane
    out = jnp.zeros(num_segments, dtype=jnp.int64)
    for b in range(8):
        v = jnp.where(live & in_lane & ((bucket & 7) == b), rank, 0)
        mx = jax.ops.segment_max(v, seg_ids, num_segments)
        out = out | (jnp.maximum(mx, 0) << (8 * b))
    return out


def lane_combine(state: jnp.ndarray, partial: jnp.ndarray) -> jnp.ndarray:
    out = jnp.zeros_like(state)
    for b in range(8):
        sh = 8 * b
        a = (state >> sh) & 255
        c = (partial >> sh) & 255
        out = out | (jnp.maximum(a, c) << sh)
    return out


def estimate_from_words_jnp(words) -> jnp.ndarray:
    """8 packed int64 word columns [G] -> per-group estimate int64."""
    regs = []
    for w in words:
        for b in range(8):
            regs.append(((w >> (8 * b)) & 255).astype(jnp.float64))
    regs = jnp.stack(regs, axis=-1)            # [G, 64]
    inv = jnp.sum(jnp.exp2(-regs), axis=-1)
    est = ALPHA_M * M * M / inv
    zeros = jnp.sum(regs == 0, axis=-1)
    small = est <= 2.5 * M
    lc = M * jnp.log(jnp.maximum(M / jnp.maximum(zeros, 1), 1.0))
    est = jnp.where(small & (zeros > 0), lc, est)
    return jnp.round(est).astype(jnp.int64)


# ----------------------------------------------------------- batch (np)
def hll_estimate_numpy(vals: np.ndarray, valid: np.ndarray,
                       seg_id: np.ndarray, n_groups: int):
    """-> (estimate int64 [n_groups], out_valid) — identical math to
    the streaming lanes (count of zero rows per group -> NULL)."""
    regs = np.zeros((n_groups, M), dtype=np.int64)
    if len(vals):
        bucket, rank = _bucket_rank_np(np.asarray(vals))
        keep = np.asarray(valid, dtype=bool)
        np.maximum.at(regs, (seg_id[keep], bucket[keep]), rank[keep])
    rf = regs.astype(np.float64)
    inv = np.sum(np.exp2(-rf), axis=-1)
    est = ALPHA_M * M * M / inv
    zeros = np.sum(regs == 0, axis=-1)
    small = est <= 2.5 * M
    lc = M * np.log(np.maximum(M / np.maximum(zeros, 1), 1.0))
    est = np.where(small & (zeros > 0), lc, est)
    cnt = np.bincount(seg_id, weights=np.asarray(valid, np.float64),
                      minlength=n_groups)
    return np.round(est).astype(np.int64), cnt > 0
