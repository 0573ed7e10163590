"""Device-resident bucketed hash table — the state backbone of HashAgg and
HashJoin.

Reference analogue: the executors' group/join hash maps (`JoinHashMap`,
src/stream/src/executor/managed_state/join/mod.rs; `AggGroup` cache keyed by
`HashKey`, hash_agg.rs:50-56). On TPU the map is a struct-of-arrays in HBM:
fixed-capacity key columns + ONE uint32 fingerprint lane.

Layout: capacity C = B buckets x S slots (S static). A key hashes to TWO
candidate buckets (two halves of a splitmix64 chain over the key columns
— power-of-two-choices); it lives in exactly one of their 2S slots.
`fingerprint[slot]` is 0 for an empty slot and otherwise a non-zero 32-bit
remix of the stored key's chain value (`_fingerprint`: 31 bits | 1, mixed
once more so it is independent of the bits that chose the buckets).
Occupancy is DERIVED (`occupied` = `fingerprint != 0`); fingerprints are
derived from the keys, never persisted, and rebuilt by re-insertion.

This shape is chosen for the hardware: a lookup is ONE vectorized [N, 2S]
gather — of the fingerprint lane only, as 2N whole bucket rows — plus
[N]-sized key gathers at the one slot whose fingerprint matched; constant
cost, and an insert is two device sorts plus scatters. Lanes are counted
because a gather on the v5e costs by its INDEX count, not its bytes: an
element gather of N x 2S = 21 M indices (N = 655,360 hop rows) takes 0.18 s,
the same for a `pred` as for a `u32` operand (ledger, PR 24, `q5.sat`;
0.183 s in my chip run, PR 25), where 2N rows of S contiguous u32 take
0.006 s and an [N] element gather 0.006 s (u32) / 0.011 s (int64) (my chip
run, PR 25). Comparing every key lane over all 2S candidates cost
1 + 2 x (int64 key columns) element gathers per chunk; the fingerprint
probe costs one row gather whatever the key arity.

Exact, not probable:
  1. every occupied slot holds the fingerprint of the key stored in it, so a
     key that IS in the table is among the slots whose fingerprint matches;
  2. a fingerprint match is only a candidate: the full key is compared at
     that slot, and only a verified slot is returned;
  3. keys are unique in the table, so the first verified slot is THE slot;
  4. a row whose every fingerprint match failed its verify is absent;
  5. rows with further unverified matches after the first verify (expected
     once per ~10^8 probed rows) walk them in a bounded loop (< 2S trips,
     zero in a chunk with none), counted in `n_fallback`.

The design before the bucketed one (linear open addressing driven by a
`lax.while_loop` claim contest) had per-chunk cost proportional to the
longest probe chain, which degrades sharply with load/clustering: a
saturated table turned one chunk into an O(C)-iteration loop that stalled
the device (observed: TPU watchdog killing the worker). Bounded bucket
probing makes the worst case a constant.

Two-choice balancing keeps bucket overflow improbable up to ~0.7 load
(classic power-of-two-choices: max load ~ mean + lg lg B). Overflow is
reported, never silent: `lookup_or_insert` returns `n_unresolved`, and the
owning executor fail-stops / rebuilds larger (its existing policy).

Within-bucket occupancy is a PREFIX: inserts append at the bucket's fill
point and slots are never freed (groups that empty out stay as zombies;
owners monitor live/zombie load via `needs_rebuild` and rebuild by
re-inserting live entries — also the capacity-growth path flagged in
SURVEY.md §7 hard-parts (a)).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp

# Slots per bucket. 16 keeps the two-choice overflow probability negligible
# at the 0.7 rebuild threshold while the [N, 2S] fingerprint compare stays
# one vectorized gather per chunk.

BUCKET_SLOTS = 16

def compact_mask(mask: jnp.ndarray):
    """The cumsum-scatter compaction idiom used all over the state
    kernels, factored once: for bool [C] `mask`, returns (sel, n) where
    sel int32 [C] holds the indices of the set bits in its first n
    entries (garbage past n) and n is the device count."""
    C = mask.shape[0]
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    sel = jnp.zeros(C, dtype=jnp.int32).at[
        jnp.where(mask, rank, C)].set(jnp.arange(C, dtype=jnp.int32),
                                      mode="drop")
    return sel, jnp.sum(mask.astype(jnp.int32))


def pack_rows(mask: jnp.ndarray, arrays):
    """Group pack kernel for eviction/spill paths: compact the masked
    slots of every array to the buffer prefix in one gather pass.
    Returns (packed arrays tuple, device count) — only the first n rows
    of each packed array are meaningful."""
    sel, n = compact_mask(mask)
    return tuple(a[sel] for a in arrays), n


def lru_stamp(stamp: jnp.ndarray, touched: jnp.ndarray, epoch) -> jnp.ndarray:
    """Advance a per-slot LRU epoch stamp from one interval's touched-slot
    bitmap: one elementwise select per barrier, nothing on the data path.
    (Bucket hashing gives slots no spatial locality, so hotness is tracked
    per SLOT — coarser vnode/bucket group ranges would mix hot and cold
    keys and evict nothing.)"""
    return jnp.where(touched, jnp.int64(epoch), stamp)


def stable_lexsort(keys):
    """np.lexsort semantics (last key primary) as ITERATED single-key
    stable argsorts. jnp.lexsort lowers to one variadic sort whose XLA
    compile time explodes with key count and length (measured: 42s for a
    3-key sort of 32k rows on TPU vs 8s total for this form); K successive
    stable sorts are the textbook definition and compile linearly."""
    order = jnp.argsort(keys[0], stable=True)
    for k in keys[1:]:
        order = order[jnp.argsort(k[order], stable=True)]
    return order


def stable_lexsort_rows(keys):
    """Per-row (axis=1) variant for [C, K] buffers."""
    order = jnp.argsort(keys[0], axis=1, stable=True)
    for k in keys[1:]:
        step = jnp.argsort(jnp.take_along_axis(k, order, axis=1), axis=1,
                           stable=True)
        order = jnp.take_along_axis(order, step, axis=1)
    return order


@jax.tree_util.register_pytree_node_class
@dataclass
class HashTable:
    """keys: per-key-column [C] arrays; fingerprint: uint32 [C], 0 = empty
    slot, else `_fingerprint` of the key stored there."""

    keys: tuple[jnp.ndarray, ...]
    fingerprint: jnp.ndarray

    def tree_flatten(self):
        return (self.keys, self.fingerprint), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, fingerprint = children
        return cls(tuple(keys), fingerprint)

    @property
    def occupied(self) -> jnp.ndarray:
        """bool [C], derived: a slot is occupied iff it holds a fingerprint."""
        return self.fingerprint != 0

    @property
    def capacity(self) -> int:
        return self.fingerprint.shape[0]

    @staticmethod
    def empty(capacity: int, key_dtypes: Sequence) -> "HashTable":
        assert capacity % BUCKET_SLOTS == 0 and capacity >= 2 * BUCKET_SLOTS, \
            f"capacity {capacity} must be a multiple of {BUCKET_SLOTS}"
        return HashTable(
            tuple(jnp.zeros(capacity, dtype=dt) for dt in key_dtypes),
            jnp.zeros(capacity, dtype=jnp.uint32),
        )


def _key_hash(key_cols: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """uint64 [N]: a splitmix64 chain over the key columns, NOT crc32: CRC
    is linear over GF(2), so structured key sets (window multiples x small
    ids — the windowed-agg shape) project onto few residues mod a small
    bucket count and saturate bucket pairs at 30% global load (observed:
    16/16 buckets at 335/1024 occupancy after a memory-eviction rehash
    batch-reinserted such keys). The multiply-xorshift mix is non-linear,
    so those sets disperse like random keys. The crc stays the DISTRIBUTION
    hash (vnodes) — this only places rows within a device table, nothing
    durable moves."""
    h = jnp.full(key_cols[0].shape[0], 0x243F6A8885A308D3,
                 dtype=jnp.uint64)
    for c in key_cols:
        x = h ^ (c.astype(jnp.uint64) * jnp.uint64(0x9E3779B97F4A7C15))
        x = x + jnp.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
        h = x ^ (x >> jnp.uint64(31))
    return h


def _bucket_pair(h: jnp.ndarray, n_buckets: int):
    """Two independent candidate buckets per row (int32 [N] each) from the
    two halves of the key hash, plus a per-key tiebreak bit so equal-fill
    choices split ~50/50 (without it, a burst of new keys within one chunk
    — where fills are all read pre-chunk — would pile into every key's
    first choice)."""
    nb = jnp.uint64(n_buckets)
    h1 = ((h & jnp.uint64(0xFFFFFFFF)) % nb).astype(jnp.int32)
    h2 = ((h >> jnp.uint64(32)) % nb).astype(jnp.int32)
    tie = ((h >> jnp.uint64(31)) & jnp.uint64(1)).astype(bool)
    return h1, h2, tie


def _fingerprint(h: jnp.ndarray) -> jnp.ndarray:
    """uint32 [N], never 0: the top 31 bits of one more multiply-xorshift
    round over the key hash, | 1. The round is a bijection of h that moves
    every bit, so two keys that share both buckets (same residues of h's
    halves) still draw independent fingerprints. (Tests replace this with
    degenerate functions to drive the exact fallback.)"""
    x = (h ^ (h >> jnp.uint64(32))) * jnp.uint64(0xD6E8FEB86659FD93)
    x = (x ^ (x >> jnp.uint64(32))) * jnp.uint64(0xD6E8FEB86659FD93)
    return (x >> jnp.uint64(32)).astype(jnp.uint32) | jnp.uint32(1)


def _probe(table: HashTable, key_cols: Sequence[jnp.ndarray],
           active: jnp.ndarray):
    """Slot of each active row's key (int32 [N], -1 if absent) by ONE
    [N, 2S] gather of the fingerprint lane; see the module docstring for
    why the answer is exact. Also returns what an insert needs: the bucket
    pair, the tiebreak, both buckets' fills, the rows' own fingerprints,
    and n_fallback — the active rows that needed more than the fingerprint
    lane and one verify."""
    S = BUCKET_SLOTS
    B = table.capacity // S
    N = key_cols[0].shape[0]
    h = _key_hash(key_cols)
    h1, h2, tie = _bucket_pair(h, B)
    fp = _fingerprint(h)
    # the one gather: both candidate buckets' fingerprints, lanes [0, S) of
    # bucket h1 then [S, 2S) of bucket h2 — 2N row indices, not 2S x N
    # element indices (30x cheaper on the chip, module docstring)
    lanes = table.fingerprint.reshape(B, S)[
        jnp.stack([h1, h2], axis=1)].reshape(N, 2 * S)
    fill1 = (lanes[:, :S] != 0).sum(axis=1, dtype=jnp.int32)
    fill2 = (lanes[:, S:] != 0).sum(axis=1, dtype=jnp.int32)
    fm = lanes == fp[:, None]          # fp != 0, so a match is occupied
    n_match = fm.sum(axis=1, dtype=jnp.int32)
    lane_ids = jnp.arange(2 * S, dtype=jnp.int32)

    def verify(lane, rows):
        """Compare the full key at one candidate lane per row: [N] gathers."""
        slot = jnp.where(lane < S, h1, h2) * S + lane % S
        eq = rows
        for tk, k in zip(table.keys, key_cols):
            eq = eq & (tk[slot] == k)
        return slot, eq

    lane = jnp.argmax(fm, axis=1).astype(jnp.int32)
    slot, hit = verify(lane, active & (n_match > 0))
    found = jnp.where(hit, slot, -1)
    # keys are unique in the table: a verified slot is final, and a row
    # with no match left to verify is absent. What remains is a row whose
    # first fingerprint match held another key and that has more matches.
    pending = active & ~hit & (n_match > 1)
    n_fallback = jnp.sum(pending.astype(jnp.int32))

    def walk(carry):
        found, lane, tried, pending = carry
        rest = fm & (lane_ids > lane[:, None])
        lane = jnp.argmax(rest, axis=1).astype(jnp.int32)
        slot, hit = verify(lane, pending)
        found = jnp.where(hit, slot, found)
        tried = tried + 1
        return found, lane, tried, pending & ~hit & (n_match > tried)

    found, _, _, _ = jax.lax.while_loop(
        lambda c: c[3].any(), walk, (found, lane, jnp.int32(1), pending))
    return found, h1, h2, tie, fill1, fill2, fp, n_fallback


def lookup(table: HashTable, key_cols: Sequence[jnp.ndarray],
           active: jnp.ndarray, max_probes: int = 0):
    """Read-only probe: slot of each active row's key, -1 if absent.

    One fingerprint gather over both candidate buckets — constant cost.
    (`max_probes` is accepted for API compatibility; probing is inherently
    bounded by the bucket shape.)
    """
    return _probe(table, key_cols, active)[0]


def lookup_or_insert(table: HashTable, key_cols: Sequence[jnp.ndarray],
                     active: jnp.ndarray, max_probes: int = 0):
    """`lookup_or_insert_counted` without the fallback count."""
    return lookup_or_insert_counted(table, key_cols, active)[:3]


def lookup_or_insert_counted(table: HashTable,
                             key_cols: Sequence[jnp.ndarray],
                             active: jnp.ndarray):
    """Find or claim a slot for every active row.

    key_cols: [N] arrays matching table.keys dtypes; active: bool [N]
    (invisible rows resolve immediately to slot -1).

    Returns (table', slots int32 [N] (-1 for inactive/unresolved),
    n_unresolved int32 scalar, n_fallback int32 scalar). n_unresolved > 0
    means both candidate buckets of some new key are full — the caller must
    rebuild larger and retry (two-choice balancing makes this improbable
    below ~0.7 load). n_fallback is `_probe`'s: rows the fingerprint lane
    and one verify did not settle (resolved exactly all the same).

    Insert algorithm (no data-dependent loops past the probe):
      1. probe as in `lookup`;
      2. first device sort groups missing rows by key (in-chunk dedup:
         each distinct new key forms a run, its first row is the leader);
      3. each leader picks the emptier of its two buckets (pre-chunk fill —
         within-bucket occupancy is a prefix, so fill = occupied count);
      4. second device sort ranks leaders within their chosen bucket, the
         run's slot = bucket*S + fill + rank;
      5. scatter keys + fingerprint for leaders; run members inherit the
         leader's slot via a segmented gather; unsort.
    """
    S = BUCKET_SLOTS
    C = table.capacity
    N = key_cols[0].shape[0]
    row_ids = jnp.arange(N, dtype=jnp.int32)

    mslot, h1, h2, tie, fill1, fill2, fp, n_fallback = _probe(
        table, key_cols, active)
    has = mslot >= 0

    choose2 = (fill2 < fill1) | ((fill2 == fill1) & tie)
    c_bucket = jnp.where(choose2, h2, h1)
    c_fill = jnp.minimum(fill1, fill2)

    miss = active & ~has

    # ---- sort 1: group missing rows by key (runs of identical keys) ----
    sort_keys = [row_ids]
    for k in key_cols:
        sort_keys.append(k)
    sort_keys.append(~miss)                       # primary: missing first
    order = stable_lexsort(tuple(sort_keys))
    s_miss = miss[order]
    same = s_miss[1:] & s_miss[:-1]
    for k in key_cols:
        sk = k[order]
        same = same & (sk[1:] == sk[:-1])
    is_leader = s_miss & jnp.concatenate([jnp.array([True]), ~same])
    run_id = jnp.cumsum(is_leader.astype(jnp.int32)) - 1    # per sorted row
    s_bucket = c_bucket[order]
    s_fill = c_fill[order]

    # ---- sort 2: rank leaders within their chosen bucket ----
    B_sentinel = C // S                            # non-leaders sort last
    rank_key = jnp.where(is_leader, s_bucket, B_sentinel)
    order2 = stable_lexsort((jnp.arange(N, dtype=jnp.int32), rank_key))
    r_bucket = rank_key[order2]
    new_bucket = jnp.concatenate(
        [jnp.array([True]), r_bucket[1:] != r_bucket[:-1]])
    pos = jnp.arange(N, dtype=jnp.int32)
    bucket_start = jax.lax.cummax(jnp.where(new_bucket, pos, 0))
    rank = pos - bucket_start
    r_fill = s_fill[order2]
    r_leader = is_leader[order2]
    r_ok = r_leader & (r_fill + rank < S)
    r_slot = jnp.where(r_ok, r_bucket * S + r_fill + rank, -1)

    # scatter leader slots back to sorted-1 space, then spread over runs
    slot_s1 = jnp.zeros(N, dtype=jnp.int32).at[order2].set(r_slot)
    leader_slot_by_run = jnp.full(N + 1, -1, dtype=jnp.int32).at[
        jnp.where(is_leader, run_id, N)].set(
            jnp.where(is_leader, slot_s1, -1), mode="drop")
    s_ins_slot = jnp.where(s_miss, leader_slot_by_run[run_id], -1)

    # ---- write leaders' keys + fingerprint (which IS the occupancy) ----
    w_idx = jnp.where(r_ok, r_slot, C)
    orig2 = order[order2]                          # sorted-2 -> original row
    keys = tuple(tk.at[w_idx].set(k[orig2], mode="drop")
                 for tk, k in zip(table.keys, key_cols))
    fingerprint = table.fingerprint.at[w_idx].set(fp[orig2], mode="drop")

    # ---- unsort + combine ----
    ins_slot = jnp.zeros(N, dtype=jnp.int32).at[order].set(s_ins_slot)
    slots = jnp.where(has, mslot, jnp.where(miss, ins_slot, -1))
    slots = jnp.where(active, slots, -1)
    n_unresolved = jnp.sum((active & (slots < 0)).astype(jnp.int32))
    return HashTable(keys, fingerprint), slots, n_unresolved, n_fallback


def load(table: HashTable) -> jnp.ndarray:
    """Occupied fraction (live + zombie) — rebuild trigger input."""
    return jnp.mean(table.occupied.astype(jnp.float32))


def needs_rebuild(n_occupied: int, n_live: int, capacity: int,
                  hi: float = 0.7) -> tuple[bool, int]:
    """Host-side policy: rebuild when load > hi. Grow 2x only if the LIVE
    set itself crowds the table; a zombie-heavy table rebuilds at the same
    capacity (purge)."""
    if n_occupied <= hi * capacity:
        return False, capacity
    if n_live > 0.5 * hi * capacity:
        return True, capacity * 2
    return True, capacity
