"""Device bucketed (two-choice) hash table kernel tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.ops import hash_table as ht
from risingwave_tpu.ops.hash_table import (
    BUCKET_SLOTS, HashTable, lookup, lookup_or_insert,
    lookup_or_insert_counted, needs_rebuild,
)


def test_insert_then_lookup():
    t = HashTable.empty(64, [jnp.int64])
    keys = jnp.asarray([5, 17, 5, 99, 17, 5], dtype=jnp.int64)
    active = jnp.ones(6, dtype=bool)
    t, slots, n_un = lookup_or_insert(t, [keys], active)
    assert int(n_un) == 0
    slots = np.asarray(slots)
    # identical keys share a slot; distinct keys don't
    assert slots[0] == slots[2] == slots[5]
    assert slots[1] == slots[4]
    assert len({slots[0], slots[1], slots[3]}) == 3
    # read-only lookup agrees
    got = np.asarray(lookup(t, [jnp.asarray([99, 5, 1234], dtype=jnp.int64)],
                            jnp.ones(3, dtype=bool)))
    assert got[0] == slots[3]
    assert got[1] == slots[0]
    assert got[2] == -1  # absent key


def test_inactive_rows_ignored():
    t = HashTable.empty(32, [jnp.int64])
    keys = jnp.asarray([1, 2, 3, 4], dtype=jnp.int64)
    active = jnp.asarray([True, False, True, False])
    t, slots, n_un = lookup_or_insert(t, [keys], active)
    assert int(n_un) == 0
    slots = np.asarray(slots)
    assert slots[1] == -1 and slots[3] == -1
    assert int(t.occupied.sum()) == 2


def test_collision_heavy():
    # 2-bucket table forces heavy collisions; 12 distinct keys must fit
    # (each bucket holds 16, so even all-one-bucket placement fits)
    t = HashTable.empty(32, [jnp.int64])
    keys = jnp.arange(12, dtype=jnp.int64) * 1000
    t, slots, n_un = lookup_or_insert(t, [keys], jnp.ones(12, dtype=bool))
    assert int(n_un) == 0
    assert len(set(np.asarray(slots).tolist())) == 12
    # every key still findable
    got = np.asarray(lookup(t, [keys], jnp.ones(12, dtype=bool)))
    np.testing.assert_array_equal(got, np.asarray(slots))


def test_overflow_reported():
    t = HashTable.empty(32, [jnp.int64])
    keys = jnp.arange(64, dtype=jnp.int64)  # 64 distinct keys, 32 slots
    t, slots, n_un = lookup_or_insert(t, [keys], jnp.ones(64, dtype=bool))
    # whatever fits is inserted; the rest is reported, never silent
    inserted = int(t.occupied.sum())
    assert int(n_un) == 64 - inserted
    assert int(n_un) >= 32
    # resolved rows got real slots, unresolved rows got -1
    slots = np.asarray(slots)
    assert (slots >= 0).sum() == inserted


def test_incremental_fill_two_choice():
    # inserting in small batches lets two-choice balancing see real fills;
    # 28 distinct keys into 32 slots must all land
    t = HashTable.empty(32, [jnp.int64])
    all_slots = {}
    for start in range(0, 28, 4):
        keys = jnp.arange(start, start + 4, dtype=jnp.int64) * 7919
        t, slots, n_un = lookup_or_insert(t, [keys], jnp.ones(4, dtype=bool))
        assert int(n_un) == 0
        for k, s in zip(range(start, start + 4), np.asarray(slots).tolist()):
            all_slots[k] = s
    assert len(set(all_slots.values())) == 28
    # all keys still findable after the table filled up
    keys = jnp.asarray(sorted(all_slots), dtype=jnp.int64) * 7919
    got = np.asarray(lookup(t, [keys], jnp.ones(28, dtype=bool)))
    np.testing.assert_array_equal(got, [all_slots[k] for k in sorted(all_slots)])


def test_multi_column_keys():
    t = HashTable.empty(64, [jnp.int64, jnp.int32])
    a = jnp.asarray([1, 1, 2, 2], dtype=jnp.int64)
    b = jnp.asarray([10, 20, 10, 10], dtype=jnp.int32)
    t, slots, n_un = lookup_or_insert(t, [a, b], jnp.ones(4, dtype=bool))
    assert int(n_un) == 0
    slots = np.asarray(slots)
    assert slots[2] == slots[3]          # (2,10) == (2,10)
    assert len({slots[0], slots[1], slots[2]}) == 3


def test_needs_rebuild_policy():
    assert needs_rebuild(10, 10, 100) == (False, 100)
    # zombie-heavy: purge at same capacity
    assert needs_rebuild(80, 10, 100) == (True, 100)
    # live-heavy: grow
    assert needs_rebuild(80, 60, 100) == (True, 200)


# ---------------------------------------------------------------- probe
# The probe reads ONE fingerprint lane and verifies the full key at the
# matching slot only. Slot placement does not depend on the fingerprint at
# all, so whatever `_fingerprint` returns, every call must give the slots
# and the n_unresolved that an all-lane compare over the 2S candidates
# gives (`all_lane_lookup` below: the probe as it was before the lane).

REAL_FINGERPRINT = ht._fingerprint
FINGERPRINTS = {
    # every occupied slot matches every row: the walk does all the work
    "constant": lambda h: jnp.full(h.shape, 7, dtype=jnp.uint32),
    "two_values": lambda h: (h & jnp.uint64(2)).astype(jnp.uint32) + 1,
    "real": REAL_FINGERPRINT,
}


def all_lane_lookup(table: HashTable, cols) -> np.ndarray:
    """numpy: first of a row's 2S candidate slots that is occupied and
    holds the row's key in every lane; -1 if none."""
    S = BUCKET_SLOTS
    h1, h2, _ = ht._bucket_pair(ht._key_hash(cols), table.capacity // S)
    cand = np.concatenate(
        [np.asarray(h)[:, None] * S + np.arange(S) for h in (h1, h2)], axis=1)
    match = np.asarray(table.occupied)[cand]
    for tk, k in zip(table.keys, cols):
        match &= np.asarray(tk)[cand] == np.asarray(k)[:, None]
    return np.where(match.any(axis=1),
                    np.take_along_axis(cand, match.argmax(axis=1)[:, None],
                                       axis=1)[:, 0], -1)


def drive(capacity: int, rounds: int, n: int, universe: int, seed: int):
    """Random chunks of two-column int64 keys (repeats across chunks,
    duplicate NEW keys within a chunk, inactive rows) through `lookup` and
    `lookup_or_insert_counted`, each row checked against a dict and the
    all-lane compare. Returns what must not depend on the fingerprint
    (every chunk's slots and n_unresolved) and the fallback rows."""
    rng = np.random.default_rng(seed)
    table = HashTable.empty(capacity, [jnp.int64, jnp.int64])
    ref: dict = {}                     # key -> slot
    seen, n_fb = [], 0
    for _ in range(rounds):
        a = rng.integers(0, universe, n) * (1 << 33)      # both u32 halves
        b = rng.integers(-3, 3, n)
        active = rng.random(n) < 0.8
        cols = [jnp.asarray(a, dtype=jnp.int64),
                jnp.asarray(b, dtype=jnp.int64)]
        keys = list(zip(a.tolist(), b.tolist()))
        want = all_lane_lookup(table, cols)
        assert [int(w) for w in want] == [ref.get(k, -1) for k in keys]
        got = np.asarray(lookup(table, cols, jnp.asarray(active)))
        np.testing.assert_array_equal(got, np.where(active, want, -1))

        table, slots, n_un, fb = lookup_or_insert_counted(
            table, cols, jnp.asarray(active))
        slots = np.asarray(slots)
        n_fb += int(fb)
        assert int(n_un) == int((active & (slots < 0)).sum())
        assert (slots[~active] == -1).all()
        for k, s, act in zip(keys, slots.tolist(), active.tolist()):
            if act and s >= 0:
                # a hit keeps its slot; duplicates of a new key share one
                assert ref.setdefault(k, s) == s
        # one slot per distinct key, no key stored twice, nothing else
        occ = np.flatnonzero(np.asarray(table.occupied))
        stored = list(zip(np.asarray(table.keys[0])[occ].tolist(),
                          np.asarray(table.keys[1])[occ].tolist()))
        assert dict(zip(stored, occ.tolist())) == ref
        assert len(stored) == len(ref)
        seen.append((slots.tolist(), int(n_un)))
    return seen, n_fb


@pytest.mark.parametrize("kind", list(FINGERPRINTS))
def test_probe_is_exact_whatever_the_fingerprint(kind, monkeypatch):
    # roomy table: every key lands; then two buckets for 40 keys: a full
    # bucket pair, where n_unresolved must come out as it always did
    shapes = [dict(capacity=256, rounds=6, n=48, universe=30, seed=1),
              dict(capacity=32, rounds=3, n=48, universe=8, seed=2)]
    base = [drive(**kw) for kw in shapes]          # the real fingerprint
    assert all(n_un == 0 for slots, n_un in base[0][0])
    assert base[1][0][-1][1] > 0, "the small table never filled a pair"
    if kind != "real":
        monkeypatch.setattr(ht, "_fingerprint", FINGERPRINTS[kind])
        for kw, (seen, _) in zip(shapes, base):
            got, n_fb = drive(**kw)
            assert got == seen
            if kind == "constant":
                assert n_fb > 0
    else:
        # 10^5 random rows, inserted and then probed again as hits: the
        # fingerprint lane and one verify settle every one
        rng = np.random.default_rng(3)
        cols = [jnp.asarray(rng.integers(-2**62, 2**62, 100_000)),
                jnp.asarray(rng.integers(0, 1000, 100_000))]
        active = jnp.ones(100_000, dtype=bool)
        table = HashTable.empty(1 << 20, [jnp.int64, jnp.int64])
        table, slots, n_un, fb0 = lookup_or_insert_counted(
            table, cols, active)
        _, again, _, fb1 = lookup_or_insert_counted(table, cols, active)
        assert (int(n_un), int(fb0), int(fb1)) == (0, 0, 0)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(slots))


def test_apply_does_one_candidate_gather():
    """A q5-shaped HashAgg apply (two int64 group keys): exactly ONE gather
    whose output has N x 2S elements — the fingerprint lane. The module
    docstring's "ONE gather" is this count; each further one costs 0.18 s
    per chunk on the chip at q5.sat's N."""
    from risingwave_tpu.common import DataType, schema as mk_schema
    from risingwave_tpu.common.chunk import StreamChunk
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.stream import HashAggExecutor

    class _Input:
        schema = mk_schema(("auction", DataType.INT64),
                           ("window_start", DataType.INT64))
        pk_indices = ()

    n = 4096
    agg = HashAggExecutor(_Input(), [0, 1], [count_star()], capacity=1 << 14)
    ch = StreamChunk.from_numpy(
        _Input.schema, [np.arange(n, dtype=np.int64),
                        np.zeros(n, dtype=np.int64)], capacity=n)
    jaxpr = jax.make_jaxpr(agg._apply_impl)(agg.state, agg._overflow_dev, ch)

    def gathers(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "gather":
                yield eqn.outvars[0].aval.shape
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else [v]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from gathers(sub)

    wide = [sh for sh in gathers(jaxpr.jaxpr)
            if int(np.prod(sh)) >= n * 2 * BUCKET_SLOTS]
    assert len(wide) == 1, wide
    assert int(np.prod(wide[0])) == n * 2 * BUCKET_SLOTS
