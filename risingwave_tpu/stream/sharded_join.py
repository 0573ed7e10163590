"""Vnode-sharded SortedJoin — the streaming join under shard_map over a mesh.

Reference: a hash-distributed join fragment is N parallel actors, each
owning a vnode slice, fed by HashDataDispatcher on the JOIN KEY from both
sides (src/stream/src/executor/hash_join.rs:478 under dispatch.rs:679) —
matching rows land on the same actor because both dispatchers hash the
same key values.

On a TPU mesh the dispatcher+merge pair collapses INTO the jitted step
(same re-design as ShardedHashAggExecutor, sharded_agg.py): each side's
sorted state lives sharded along the `vnode` mesh axis, and both sides'
chunks route to the shard owning vnode = crc32(key) & 255 — identical
hashing on both sides => co-partitioned probes are shard-local. The
per-shard output chunks concatenate along the shard axis into one global
changelog chunk. `capacity` is PER SHARD.

Like the sharded agg's, the input plane is the FUSED MESH SHUFFLE: the
chunk enters row-sliced over the mesh axis (a capacity the shard count
does not divide is padded first, `MeshShuffleHost._mesh_chunk`) and
`parallel/exchange.mesh_ingest_chunk` routes rows to their owner shard
with one in-program `lax.all_to_all` — exchange + probe + state update is
ONE device program per chunk, with shuffle overflow accumulated on device
and fail-stopped at the barrier watchdog.

Inherits ALL semantics (inner/outer, degrees, per-chunk eviction,
netting) from SortedJoinExecutor — `_apply_impl` / `_evict_impl` run
unchanged inside shard_map.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.jit_state import jit_state
from ..parallel.exchange import mesh_ingest_chunk, shuffle_bytes
from ..parallel.mesh import VNODE_AXIS, shard_map
from ..utils.d2h import defer_prefix_flush, fetch_small, off_loop
from .align import LEFT, RIGHT
from .executor import Executor
from .mesh_shuffle import OBS_FILL, OBS_ROWS, MeshShuffleHost, fold_shuffle_obs
from .sorted_join import SortedJoinExecutor, SortedSideState, _empty_sorted_side


def _scalar_n(state: SortedSideState) -> SortedSideState:
    return replace(state, n=state.n.reshape(()))


def _vec_n(state: SortedSideState) -> SortedSideState:
    return replace(state, n=state.n.reshape((1,)))


class ShardedSortedJoinExecutor(MeshShuffleHost, SortedJoinExecutor):
    def __init__(self, left: Executor, right: Executor, mesh: Mesh,
                 **kwargs):
        self._init_mesh_shuffle(
            mesh, 0, kwargs.get("watchdog_interval", 1) is not None)
        # per side: the two legs' producers differ (`set_mesh_preludes`)
        self._mesh_preludes: dict = {}
        super().__init__(left, right, **kwargs)
        shard, repl = P(VNODE_AXIS), P()

        # ---- fused mesh shuffle: exchange + probe in ONE program ----
        # the chunk enters SHARDED over the row axis; the in-mesh
        # all_to_all routes rows to the shard owning their join-key
        # vnode, then the local sorted state probes/updates exactly the
        # owned rows. `dropped` (arg 3) accumulates shuffle overflow per
        # shard for the barrier watchdog's fail-stop.
        def make_apply_fused(side, mf, use_preludes):
            def apply_fused(own, other, errs, dropped, obs, chunk, wm):
                # preludes transform RAW source chunks; recovery's state
                # replay feeds rows already in join-input schema, so its
                # trace (use_preludes=False) must skip them
                pres = (self._mesh_preludes.get(side, ())
                        if use_preludes else ())
                raw_rows = chunk.capacity
                for fn in pres:
                    chunk = fn(chunk)
                cap = self._trace_cap(chunk.capacity)
                self._note_traced_shuffle(
                    shuffle_bytes(chunk, self.key_indices[side],
                                  self.n_shards, cap),
                    raw_rows, side, mf, use_preludes)
                local, n_drop, fill = mesh_ingest_chunk(
                    chunk, self.key_indices[side], self._routing,
                    VNODE_AXIS, self.n_shards, cap)
                out = self._apply_impl(_scalar_n(own), _scalar_n(other),
                                       errs[0], local, wm, side,
                                       match_factor=mf)
                own2, odeg, cols, ops, vis, errs2, _ = out
                return (_vec_n(own2), odeg, cols, ops, vis, errs2[None],
                        (dropped[0] + n_drop)[None],
                        fold_shuffle_obs(obs[0], fill, local.vis)[None],
                        own2.n.reshape((1,)))
            # donation: ONLY the error + shuffle-drop + shuffle-observation
            # accumulators (threaded) — the side states stay aliased by the
            # diff base (_snap: the state as of the last flush, which the
            # persist reads the deleted rows from, per shard, at the
            # positions no live row's `src` lane carries any more)
            return jit_state(shard_map(
                apply_fused, mesh=mesh,
                in_specs=(shard, shard, shard, shard, shard, shard,
                          repl),
                out_specs=(shard,) * 9), donate_argnums=(2, 3, 4),
                name=f"sharded_join_apply_fused_s{side}")

        # sharded programs trace per (side, match_factor): the steady
        # state uses the per-side factors, recovery's generous replay
        # buffer gets its own trace instead of being refused
        applies: dict = {}

        def apply_program(side, mf, use_pre):
            # programs also key by the adaptive cap hint active at trace
            # time (None = zero-drop sizing)
            key = (side, mf, self._cap_hint, use_pre)
            if key not in applies:
                applies[key] = make_apply_fused(side, mf, use_pre)
            return applies[key]
        self._apply_program = apply_program

        def apply_dispatch(own, other, errs, chunk, wm, side,
                           match_factor=None):
            chunk = self._mesh_chunk(chunk)
            mf = match_factor or self.match_factors[side]
            # state replay (recover) feeds join-schema rows, not raw
            # source chunks: skip chain preludes AND the ingest log
            use_pre = not getattr(self, "_state_replay", False)
            # replay point: retain the ingest by reference before the
            # fused program consumes it (MeshIngestLog — the mesh-plane
            # uncommitted suffix)
            if use_pre:
                self.ingest_log.note((side, chunk))
            (own2, odeg, cols, ops, vis, errs2, self._dropped_dev,
             self._shuffle_obs_dev, n) = apply_program(side, mf, use_pre)(
                own, other, errs, self._dropped_dev,
                self._shuffle_obs_dev, chunk, wm)
            self._count_shuffle_dispatch(chunk, side, mf, use_pre)
            self.mesh_shuffle_applies += 1
            return own2, odeg, cols, ops, vis, errs2, n
        self._apply = apply_dispatch

        def replay_dispatch(*args, **kwargs):
            own2, odeg, _, _, _, errs2, n = apply_dispatch(*args, **kwargs)
            return own2, odeg, errs2, n
        self._replay = replay_dispatch

        def make_evict(side):
            def evict_sharded(own, wm, kh):
                return _vec_n(self._evict_impl(_scalar_n(own), wm, kh,
                                               side))
            return jit_state(shard_map(
                evict_sharded, mesh=mesh, in_specs=(shard, repl, repl),
                out_specs=shard), name=f"sharded_join_evict_s{side}")

        evicts = {LEFT: make_evict(LEFT), RIGHT: make_evict(RIGHT)}
        self._evict = lambda own, wm, kh, side: evicts[side](own, wm, kh)

        # sharded accumulators replace the parent's scalars
        sharding = NamedSharding(mesh, P(VNODE_AXIS))
        self._errs_dev = jax.device_put(
            jnp.zeros((self.n_shards, 3), dtype=jnp.int32), sharding)
        zero = jax.device_put(
            jnp.zeros(self.n_shards, dtype=jnp.int32), sharding)
        self._n_dev = [zero, zero]
        # own buffer, NOT an alias of `zero`: the fused apply DONATES the
        # drop accumulator, and donating a buffer `_n_dev` still holds
        # would delete it out from under the watchdog fetch
        self._dropped_dev = jax.device_put(
            jnp.zeros(self.n_shards, dtype=jnp.int32), sharding)
        self._shuffle_obs_dev = self._fresh_shuffle_obs()
        # the fused programs do not count what a chunk asks of its match
        # buffer: `join_match_*` are the one-chip join's
        self._match_dev = None
        self.sides = [self._sharded_empty(s) for s in (LEFT, RIGHT)]
        self._snap = list(self.sides)
        # one packed fetch per barrier: summed errs + shuffle drops + the
        # shuffle observations (max send-bucket demand = the adaptive slack
        # signal; rows received in all and by the fullest shard) + each
        # side's live rows over all shards
        self._watchdog_pack_sh = jit_state(
            lambda errs, dr, so, nl, nr: jnp.concatenate(
                [jnp.sum(errs, axis=0), jnp.sum(dr)[None],
                 jnp.max(so[:, OBS_FILL])[None],
                 jnp.sum(so[:, OBS_ROWS])[None],
                 jnp.max(so[:, OBS_ROWS])[None],
                 jnp.sum(nl)[None], jnp.sum(nr)[None]]),
            name="sharded_join_watchdog_pack")

    # the join applies chunk by chunk and emits as it goes: its replay is
    # the frontier channels', never a preloaded interval
    preload_replay = None

    def set_mesh_preludes(self, side, fns, chain=None) -> None:
        assert self.mesh_shuffle_applies == 0, \
            "mesh preludes must install before the first fused dispatch"
        self._mesh_preludes[side] = tuple(fns)
        if chain is not None:
            self.mesh_chain = chain

    def _sharded_empty(self, side: int) -> SortedSideState:
        S = self.n_shards
        local = _empty_sorted_side(self.capacity[side],
                                   self._col_dtypes[side])
        sharding = NamedSharding(self.mesh, P(VNODE_AXIS))

        def expand(x):
            if x.ndim == 0:
                g = jnp.zeros(S, dtype=x.dtype)
            else:
                g = jnp.tile(x, (S,) + (1,) * (x.ndim - 1))
            return jax.device_put(g, sharding)

        return jax.tree_util.tree_map(expand, local)

    def _empty(self, side: int) -> SortedSideState:
        # called by the parent constructor before the mesh fields exist;
        # replaced by _sharded_empty right after
        return _empty_sorted_side(self.capacity[side],
                                  self._col_dtypes[side])

    # ------------------------------------------------------- durability
    def _shard_slice(self, st: SortedSideState, sh: int,
                     side: int) -> SortedSideState:
        """Shard sh's LOCAL view of a global [S*C] side state."""
        C = self.capacity[side]
        lo = sh * C
        return SortedSideState(
            st.khash[lo:lo + C],
            tuple(c[lo:lo + C] for c in st.cols),
            tuple(v[lo:lo + C] for v in st.valids),
            st.degree[lo:lo + C],
            st.src[lo:lo + C],
            st.n[sh].reshape(()))

    async def _persist(self, barrier) -> None:
        """Durable flush of the sharded sides: per-shard diffs (each
        shard's slice is a valid local sorted state whose `src` lane holds
        shard-local positions, so the parent's diff program applies
        unchanged), with ALL shards'/sides' payloads
        shipped in TWO d2h calls — one counts fetch, one packed buffer
        (the per-call fetch tax would otherwise multiply by 2·S·sides).
        The diff programs dispatch AT the barrier (against non-donated
        snapshot bases), the counts are awaited and the count-dependent
        slicing/packing dispatched by the actor, still at the barrier;
        the wait for that pack runs as a PURE wait on the uploader's
        thread and the writes in its host-only continuation
        (`defer_prefix_flush`; two threads dispatching concurrently
        deadlocks jax)."""
        # stamp the interval's replay point with the epoch this barrier
        # seals; the coordinator drops it when that epoch commits
        self.ingest_log.seal(barrier.epoch.prev)
        tables = [st for st in (self.state_tables[LEFT],
                                self.state_tables[RIGHT]) if st is not None]
        if not tables:
            return
        pending = []     # (table, [per-shard diff tuples])
        for s in (LEFT, RIGHT):
            st = self.state_tables[s]
            if st is None:
                continue
            if self._flush_dirty[s]:
                diffs = [self._diff(
                    self._shard_slice(self.sides[s], sh, s),
                    self._shard_slice(self._snap[s], sh, s))
                    for sh in range(self.n_shards)]
                pending.append((s, st, diffs))
                self._rebase(s)
                self._flush_dirty[s] = False
        new_epoch = barrier.epoch.curr

        def plan(counts):
            groups, ci = [], 0
            for _, _, diffs in pending:
                # the shards of one side at the largest shard's bucket, so
                # the packed shapes repeat from barrier to barrier (d2h.py)
                side = counts[ci:ci + 2 * len(diffs)]
                nd_max, ni_max = int(max(side[0::2])), int(max(side[1::2]))
                for d in diffs:
                    nd, ni = int(counts[ci]), int(counts[ci + 1])
                    ci += 2
                    groups.append((list(d[0]), nd, nd_max))
                    groups.append((list(d[2]), ni, ni_max))

            def write(fetched):
                gi = ci = 0
                for s, st, diffs in pending:
                    for _ in diffs:
                        nd, ni = int(counts[ci]), int(counts[ci + 1])
                        ci += 2
                        self._count_persisted(s, nd, ni)
                        self._write_diff(st, nd, fetched[gi], ni,
                                         fetched[gi + 1])
                        gi += 2
                for st in tables:
                    st.commit(new_epoch)

            return groups, write

        await defer_prefix_flush(
            tables[0].store, barrier.epoch.prev, tables[0].table_id,
            jnp.stack([x for _, _, diffs in pending
                       for d in diffs for x in (d[1], d[3])])
            if pending else None, plan)

    def _src_iota(self, capacity: int) -> jnp.ndarray:
        return jax.device_put(
            jnp.tile(jnp.arange(capacity, dtype=jnp.int32), self.n_shards),
            NamedSharding(self.mesh, P(VNODE_AXIS)))

    def _recover_reset(self, s: int, rows: list) -> None:
        """Per-shard capacity is sized by the WORST shard's row count
        (rows route by vnode-of-key, as the apply path's shuffle does)."""
        if rows:
            keys = [np.asarray([r[k] for r in rows], dtype=np.int64)
                    for k in self.key_indices[s]]
            from ..common.vnode import compute_vnodes_numpy
            shard_of = np.asarray(self._routing)[
                compute_vnodes_numpy(keys)]
            worst = int(np.bincount(
                shard_of, minlength=self.n_shards).max())
        else:
            worst = 0
        while worst > 0.7 * self.capacity[s]:
            self.capacity[s] *= 2
        self.sides[s] = self._sharded_empty(s)

    # ------------------------------------------------- HBM memory manager
    @property
    def mem_shards(self) -> int:
        """Shard count for the memory manager's per-shard breakdown
        (the side states split evenly over the mesh axis)."""
        return self.n_shards

    def state_shard_bytes(self) -> int:
        return self.state_bytes() // self.n_shards

    def _mem_local_slices(self, s: int) -> list:
        """Spill programs run per shard slice — each is a valid local
        sorted side (the same shape trick the sharded persist diff uses),
        so the parent's pack/range kernels apply unchanged."""
        return [self._shard_slice(self.sides[s], sh, s)
                for sh in range(self.n_shards)]

    def _mem_live_ns(self) -> list:
        """Worst-shard occupancy per side (capacity is PER SHARD)."""
        vals = np.asarray(jnp.concatenate([self.sides[LEFT].n,
                                           self.sides[RIGHT].n]))
        S = self.n_shards
        return [int(vals[:S].max()), int(vals[S:].max())]

    # --------------------------------------------------------- watchdog
    async def _check_watchdog(self) -> None:
        vals = await off_loop(fetch_small, self._watchdog_pack_sh(
            self._errs_dev, self._dropped_dev, self._shuffle_obs_dev,
            *self._n_dev))
        n_mo, n_miss, n_ro, n_drop, fill, rows, rows_max, n_l, n_r = (
            int(x) for x in vals)
        self._publish_shuffle(rows, rows_max, fill)
        self._publish_live_rows(n_l, n_r, shards=self.n_shards)
        self._fail_on_shuffle_drops(n_drop)
        if n_mo:
            raise RuntimeError(
                f"sharded-join match-buffer overflow ({n_mo} dropped)")
        if n_ro:
            raise RuntimeError(
                f"sharded-join state overflow ({n_ro} rows dropped; "
                f"per-shard capacity {self.capacity})")
        if n_miss:
            raise RuntimeError(
                f"sharded-join changelog inconsistency: {n_miss} deletes "
                f"matched no stored row")
