"""A hash agg's rebuild (`hash_agg_rehash`: zombie purge, growth, the memory
manager's shrink) re-inserts the survivors REHASH_BLOCK at a time inside
one program, the device taking the trip count from its own survivor count.

Held here, against the state the rebuild started from: the rebuilt state
holds exactly the kept groups — live ones and zero-count groups whose
delete is still to be emitted — with every lane's value, a retractable
MIN/MAX's `(vals, cnts, lossy)` included, at block boundaries and past
them, at the table's own capacity and at twice it; every survivor finds a
slot up to the fills a barrier's rebuild can ask for; the mesh agg's
per-shard purge (a trip count per shard, no collective in the loop) keeps
every shard's groups. The blocks are made narrow (`REHASH_BLOCK` patched)
so that a table of a few hundred slots takes several.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common import DataType, schema
from risingwave_tpu.common.chunk import OP_DELETE, OP_INSERT
from risingwave_tpu.expr.agg import agg_max, agg_sum, count_star
from risingwave_tpu.parallel import make_mesh
from risingwave_tpu.stream import BarrierKind, HashAggExecutor, hash_agg
from risingwave_tpu.stream.message import Watermark
from risingwave_tpu.stream.sharded_agg import ShardedHashAggExecutor

from test_hash_agg import SCHEMA, ScriptSource, barrier, chunk

W = 64          # the block of these tests
CAPACITY = 1024
K = 4           # the retractable MAX's value buffer


@pytest.fixture
def narrow_blocks(monkeypatch):
    monkeypatch.setattr(hash_agg, "REHASH_BLOCK", W)


def _agg(capacity=CAPACITY, **kw):
    return HashAggExecutor(
        ScriptSource(SCHEMA, []), [0],
        [count_star(), agg_sum(1, DataType.INT64),
         agg_max(1, DataType.INT64, append_only=False)],
        capacity=capacity, minput_k=K, **kw)


def _state_of(agg, n_keep: int, n_zombie: int, seed: int):
    """A state holding `n_keep` groups a rebuild keeps — two in three live,
    the third a zero-count group that was emitted and is dirty (its delete
    is due at the next flush) — and `n_zombie` it drops (zero-count, clean
    or never emitted), every lane of every group drawn at random, inserted
    a few at a time as a stream would. Returns (state, key -> lanes of the
    kept groups)."""
    rng = np.random.default_rng(seed)
    n = n_keep + n_zombie
    keys = rng.permutation(1 << 20)[:n].astype(np.int64) * 7919
    kept = np.zeros(n, dtype=bool)
    kept[rng.permutation(n)[:n_keep]] = True
    dying = kept & (np.arange(n) % 3 == 2)
    row_count = np.where(kept & ~dying, rng.integers(1, 9, n), 0)
    dirty = np.where(kept, dying | (rng.random(n) < 0.5),
                     rng.random(n) < 0.5)
    prev_exists = np.where(kept, dying | (rng.random(n) < 0.5), ~dirty)
    lanes = dict(
        count=rng.integers(0, 99, n), total=rng.integers(-99, 99, n),
        vals=rng.integers(-999, 999, (n, K)),
        cnts=rng.integers(0, 5, (n, K)).astype(np.int32),
        lossy=rng.random(n) < 0.3,
        row_count=row_count.astype(np.int64), dirty=dirty,
        prev_exists=prev_exists,
        emit0=rng.integers(0, 99, n), emit1=rng.integers(-99, 99, n),
        emit2=rng.integers(-999, 999, n))
    B = 32
    write = jax.jit(lambda st, k, act, ln: agg._write_rows(
        st, [k], act, (ln["count"], ln["total"],
                       (ln["vals"], ln["cnts"], ln["lossy"])),
        ln["row_count"], ln["dirty"], ln["prev_exists"],
        (ln["emit0"], ln["emit1"], ln["emit2"]))[:2])
    state = agg._empty_state(agg.capacity)
    for i in range(0, n, B):
        m = min(B, n - i)
        pad = lambda a: jnp.asarray(np.concatenate(   # noqa: E731
            [a[i:i + m], np.zeros((B - m,) + a.shape[1:], a.dtype)]))
        state, n_un = write(state, pad(keys), jnp.arange(B) < m,
                            {name: pad(a) for name, a in lanes.items()})
        assert int(n_un) == 0
    want = {int(keys[r]): tuple(np.asarray(a[r]).tolist()
                                for a in lanes.values())
            for r in np.flatnonzero(kept)}
    return state, want


def _groups(state, shards: int = 1) -> list:
    """Per shard: key -> every lane's value (agg states, `row_count`,
    `dirty`, `prev_exists`, `prev_emit`, in that order), of its occupied
    slots."""
    st = jax.tree_util.tree_map(np.asarray, state)
    cols = jax.tree_util.tree_leaves(
        (st.agg_states, st.row_count, st.dirty, st.prev_exists,
         st.prev_emit))
    C = st.table.capacity // shards
    return [{int(st.table.keys[0][slot]): tuple(c[slot].tolist()
                                                for c in cols)
             for slot in range(s * C, (s + 1) * C)
             if st.table.fingerprint[slot] != 0}
            for s in range(shards)]


@pytest.mark.parametrize("survivors,grow", [
    (0, 1), (1, 1), (W - 1, 1), (W, 1), (W + 1, 1), (3 * W + 5, 1),
    (3 * W + 5, 2), (W, 2)])
def test_a_rebuild_keeps_exactly_the_kept_groups_lane_for_lane(
        narrow_blocks, survivors, grow):
    agg = _agg()
    state, want = _state_of(agg, survivors, 300 - survivors // 2, survivors)
    assert len(want) == survivors
    # the program the barrier dispatches, by its name: it donates `state`
    rebuilt = agg._rehash(state, grow * CAPACITY)
    assert rebuilt.table.capacity == grow * CAPACITY
    assert _groups(rebuilt) == [want]


def test_a_dead_group_whose_delete_is_due_survives_and_is_deleted_next(
        narrow_blocks):
    """A zero-count group that was emitted and changed since (`dirty &
    prev_exists`) still owes its Delete: it rides the rebuild and the next
    flush emits it, with the values it was last emitted with."""
    agg = _agg()
    state, want = _state_of(agg, 2 * W + 3, 200, 11)
    owed = {k: v for k, v in want.items() if v[5] == 0}
    assert owed and all(v[6] and v[7] for v in owed.values())
    state, cols, ops, vis = agg._flush(agg._rehash(state, CAPACITY))
    cols, ops, vis = (jax.tree_util.tree_map(np.asarray, x)
                      for x in (cols, ops, vis))
    deleted = {int(cols[0][r]): tuple(int(c[r]) for c in cols[1:])
               for r in np.flatnonzero(vis & (ops == OP_DELETE))}
    assert deleted == {k: v[8:] for k, v in owed.items()}
    assert not np.asarray(state.dirty).any()


@pytest.mark.parametrize("fill,in_blocks,in_one_chunk", [
    (0.35, 0, 0), (0.5, 0, 2), (0.65, 0, 54), (0.69, 0, 88)])
def test_every_survivor_finds_a_slot_where_one_chunk_of_them_would_not(
        monkeypatch, fill, in_blocks, in_one_chunk):
    """Survivors left without a slot, by how full they make the new table.
    The most a barrier's rebuild asks is half (a growth doubles a table
    whose live set crowds it; a purge keeps at most ZOMBIE_PURGE_MARK). In
    blocks of one key to eight buckets — the cells' 2^12 into 2^19 slots —
    every survivor is placed up to `needs_rebuild`'s 0.7, because each
    block chooses its buckets by the fills the blocks before it left. All
    in ONE block, as the rebuild was before, the keys are one chunk of
    fresh keys that choose by fills that are all zero: at half full some
    bucket is already asked for more than its 16 slots (ZOMBIE_PURGE_MARK's
    note). The counts are this seed's; the barrier fail-stops on any."""
    old_cap, new_cap = 1 << 14, 1 << 13
    agg = HashAggExecutor(ScriptSource(SCHEMA, []), [0], [count_star()],
                          capacity=old_cap)
    B = 64
    n = int(fill * new_cap) // B * B
    keys = np.random.default_rng(5).permutation(1 << 24)[:n].astype(np.int64)
    write = jax.jit(lambda st, k: agg._write_rows(
        st, [k], jnp.ones(B, bool), (jnp.ones(B, jnp.int64),),
        jnp.ones(B, jnp.int64), None, jnp.ones(B, bool),
        (jnp.ones(B, jnp.int64),))[:2])
    state = agg._empty_state(old_cap)
    for i in range(0, n, B):
        state, n_un = write(state, jnp.asarray(keys[i:i + B]))
        assert int(n_un) == 0
    for block, want in ((new_cap // 128, in_blocks), (old_cap, in_one_chunk)):
        monkeypatch.setattr(hash_agg, "REHASH_BLOCK", block)
        rebuilt, n_un = jax.jit(lambda st: agg._rehash_keep(
            st, st.table.occupied, new_cap))(state)
        assert int(jnp.sum(rebuilt.table.occupied)) == n - int(n_un)
        assert int(n_un) == want


async def test_the_mesh_aggs_purge_keeps_every_shards_groups(narrow_blocks,
                                                             monkeypatch):
    """The mesh agg purges per shard inside `shard_map`: every shard runs
    the loop as many times as ITS survivors ask, and nothing crosses
    shards. The groups and their lanes after a purge are the live ones
    before it, shard by shard."""
    monkeypatch.setattr(hash_agg, "REHASH_BLOCK", 8)
    rows = [(OP_INSERT, k, k % 13) for k in range(400)]
    msgs = [barrier(1, 0, BarrierKind.INITIAL)]
    for e, i in enumerate(range(0, 400, 100)):
        msgs += [chunk(rows[i:i + 100], cap=128), barrier(e + 2, e + 1)]
    # every key below 150 dies at this barrier and stays as a zombie
    msgs += [Watermark(0, DataType.INT64, 150), barrier(6, 5)]
    mesh = make_mesh(8)
    sh = ShardedHashAggExecutor(
        ScriptSource(SCHEMA, msgs), [0],
        [count_star(), agg_sum(1, DataType.INT64)], mesh=mesh,
        capacity=256, cleaning_watermark_col=0)
    async for _ in sh.execute():
        pass
    assert sh.rebuilds == 0
    before = _groups(sh.state, 8)
    live = [{k: v for k, v in shard.items() if v[2] > 0}
            for shard in before]
    assert sum(map(len, live)) == 250 < sum(map(len, before)) == 400
    # shards differ in how many blocks of 8 they need, some need several
    assert len({-(-len(s) // 8) for s in live}) > 1
    sh.state = sh._purge(sh.state)
    assert _groups(sh.state, 8) == live
