"""Vnode-sharded HashAgg — the real executor under shard_map over a mesh.

Reference: a hash-distributed fragment is N parallel actors, each owning a
vnode-bitmap slice of the 256 vnodes, fed by HashDataDispatcher
(proto/stream_plan.proto:834-876, dispatch.rs:679). On a TPU mesh the
dispatcher+merge pair collapses INTO the jitted step: state lives sharded
along the `vnode` mesh axis (global arrays [S*C], each shard seeing a
local [C] table).

The input plane is the FUSED MESH SHUFFLE: the whole fragment —
source-side dispatch, hash exchange, stateful apply — is ONE shard_map-ed
program per barrier interval. The host chunk is sliced CONTIGUOUSLY over
the mesh axis (shard s holds rows [s*L, (s+1)*L); a capacity the shard
count does not divide is padded first, `MeshShuffleHost._mesh_chunk`),
each shard vnode-routes its slice to the owner shards with
`parallel/exchange.mesh_ingest_chunk` (`lax.all_to_all` over ICI — no
host Channel hop, no replication), and applies its local hash table to
exactly the rows it owns. Chunks buffered within an interval batch into
one `lax.scan` inside the same shard_map program, so device dispatches
per interval scale with neither chunk count nor shard count. Shuffle
overflow accumulates on device and FAIL-STOPS the epoch at the barrier
watchdog fetch.

The barrier flush runs per shard and concatenates
along the shard axis into one global changelog chunk.

This is the SAME executor logic as HashAggExecutor — `_apply_impl`,
`_flush_impl`, `_evict_impl`, `_rehash_impl` are inherited unchanged and
wrapped in shard_map; capacities inside are the per-shard local shapes.

Durability: fully supported — `_persist` runs a per-shard persist view
(each shard's dirty rows compact to its local prefix) and ships all
shards' prefixes in two packed d2h calls into the state table, and
`recover` rebuilds the sharded device state by routing durable rows
through the same vnode->shard map the apply path routes by. Per-shard
capacity stays static at runtime (growth would need a global re-layout;
recovery may re-size from the worst shard's row count), and the
transfer-free purge path works per shard.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.chunk import StreamChunk
from ..expr.agg import AggCall
from ..ops.jit_state import jit_state
from ..parallel.exchange import mesh_ingest_chunk, shuffle_bytes
from ..parallel.mesh import VNODE_AXIS, shard_map
from ..utils.d2h import defer_prefix_flush, fetch_small, off_loop
from .executor import Executor
from .hash_agg import AggState, HashAggExecutor
from .mesh_shuffle import OBS_FILL, OBS_ROWS, MeshShuffleHost, fold_shuffle_obs


class ShardedHashAggExecutor(MeshShuffleHost, HashAggExecutor):
    """HashAgg over `mesh`: state sharded on the vnode axis, input routed
    to its owner shard by the fused in-mesh shuffle. `capacity` is PER
    SHARD."""

    def __init__(self, input: Executor, group_key_indices: Sequence[int],
                 agg_calls: Sequence[AggCall], mesh: Mesh,
                 capacity: int = 1 << 14,
                 state_table=None,
                 group_key_names: Optional[Sequence[str]] = None,
                 cleaning_watermark_col: Optional[int] = None,
                 watchdog_interval: Optional[int] = 1,
                 mesh_shuffle_slack: int = 0):
        # `mesh_shuffle_slack` undersizes the send buckets: no SQL reaches
        # it, it is the seam through which tests drive the shuffle's
        # overflow FAIL-STOP; 0 is zero-drop sizing, then adaptive
        self._init_mesh_shuffle(mesh, mesh_shuffle_slack,
                                watchdog_interval is not None)
        super().__init__(input, group_key_indices, agg_calls,
                         capacity=capacity, state_table=state_table,
                         group_key_names=group_key_names,
                         cleaning_watermark_col=cleaning_watermark_col,
                         watchdog_interval=watchdog_interval)
        # re-wrap the inherited step impls in shard_map (the parent set up
        # plain jits over the freshly built sharded state); donation rules
        # match the parent's — the sharded AggState and the per-shard
        # accumulators are threaded, never aliased. Chunk batching runs
        # through the FUSED shard_map scan (_drain_pending below); the
        # parent's unsharded apply and scan programs are never built here.
        mesh_kw = dict(mesh=mesh)
        shard = P(VNODE_AXIS)
        repl = P()

        # ---- fused mesh shuffle: exchange + apply in ONE program ----
        # the chunk enters SHARDED over the row axis (in_spec P(vnode):
        # shard s sees rows [s*L, (s+1)*L)); the in-mesh all_to_all
        # routes rows to their owner shard, then the local hash table
        # applies exactly the owned rows. `dropped` accumulates shuffle
        # overflow per shard; the barrier watchdog fail-stops on it.
        # per-chunk fused programs, keyed by the adaptive cap hint active
        # at trace time (None = zero-drop sizing); scans keyed (k, hint)
        self._fused_applies: dict = {}
        self._fused_scans: dict = {}

        def flush_sharded(state):
            st, cols, ops, vis = self._flush_impl(state)
            return st, cols, ops, vis

        flush_mesh = shard_map(
            flush_sharded, in_specs=(shard,),
            out_specs=(shard, shard, shard, shard), **mesh_kw)
        # called as the one-chip flush is; the mesh watchdog brings no
        # dirty count, so `n_slots` is None and every shard lays its
        # dirty slots out as wide as its table
        self._flush = jit_state(
            lambda state, n_slots=None: flush_mesh(state),
            static_argnames=("n_slots",), donate_argnums=(0,),
            name="sharded_agg_flush")

        def evict_sharded(state, wm):
            return self._evict_impl(state, wm)

        self._evict = jit_state(shard_map(
            evict_sharded, in_specs=(shard, repl), out_specs=shard,
            **mesh_kw), donate_argnums=(0,), name="sharded_agg_evict")

        def purge_sharded(state):
            return self._rehash_impl(state, self.capacity)

        self._purge = jit_state(shard_map(
            purge_sharded, in_specs=(shard,), out_specs=shard, **mesh_kw),
            donate_argnums=(0,), name="sharded_agg_purge")

        def rehash_same_capacity(state, cap):
            # sharded v1 never grows: only same-capacity purges reach here
            assert cap == self.capacity, "sharded agg capacity is static"
            return self._purge(state)
        self._rehash = rehash_same_capacity

        def watchdog_sharded(ov, occ, dr, so):
            # the accumulators promote to int64 through the apply's segment
            # sums, and the TPU lowers a 64-bit all-reduce only for SUM:
            # take the cross-shard MAX of the (slot-count-sized) values in
            # int32
            total_ov = jax.lax.psum(ov[0], VNODE_AXIS)
            max_occ = jax.lax.pmax(occ[0].astype(jnp.int32), VNODE_AXIS)
            total_dr = jax.lax.psum(dr[0], VNODE_AXIS)
            max_fill = jax.lax.pmax(so[0, OBS_FILL], VNODE_AXIS)
            rows = jax.lax.psum(so[0, OBS_ROWS], VNODE_AXIS)
            rows_max = jax.lax.pmax(so[0, OBS_ROWS], VNODE_AXIS)
            return jnp.stack([total_ov[0], max_occ, total_dr, max_fill,
                              total_ov[1], rows, rows_max])[None]

        self._watchdog_pack = jit_state(shard_map(
            watchdog_sharded, in_specs=(shard, shard, shard, shard),
            out_specs=shard,
            **mesh_kw), name="sharded_agg_watchdog_pack")

        def persist_view_sharded(state):
            cols, ops, vis, n_dirty = self._persist_view_impl(state)
            return tuple(cols), ops, vis, n_dirty[None]

        # the parent's eager persist view gathers on sharded arrays
        # (XLA aborts); run it per shard instead — each shard's dirty
        # rows compact to that shard's LOCAL prefix
        self._persist_view_sh = jit_state(shard_map(
            persist_view_sharded, in_specs=(shard,),
            out_specs=(shard, shard, shard, shard), **mesh_kw),
            name="sharded_agg_persist_view")

        # per-shard watchdog accumulators replace the parent's scalars
        sharding = NamedSharding(mesh, P(VNODE_AXIS))
        self._overflow_dev = jax.device_put(
            jnp.zeros((self.n_shards, self._overflow_width),
                      dtype=jnp.int32), sharding)
        self._occ_dev = jax.device_put(
            jnp.zeros(self.n_shards, dtype=jnp.int32), sharding)
        self._dropped_dev = jax.device_put(
            jnp.zeros(self.n_shards, dtype=jnp.int32), sharding)
        # per shard, since the last watchdog fetch: the max send-bucket
        # DEMAND (the adaptive slack signal) and the rows received from the
        # shuffle (mesh_shuffle.py; fresh zeros at each fetch)
        self._shuffle_obs_dev = self._fresh_shuffle_obs()

    # ------------------------------------------------ fused mesh shuffle
    def _fused_step(self, state, overflow, dropped, obs, chunk):
        """One chunk's preludes + shuffle + apply, INSIDE shard_map
        (per-shard views; `chunk` fields are this shard's local [L] row
        slices). Hollow producer stages run here first — device-resident,
        zero host hops — then the in-mesh all_to_all routes the
        transformed rows to their owner shards. Shapes are static under
        trace, so the per-pair send capacity re-derives per
        chunk-capacity signature (and per adaptive cap hint)."""
        raw_rows = chunk.capacity
        for fn in self._mesh_preludes:
            chunk = fn(chunk)
        cap = self._trace_cap(chunk.capacity)
        self._note_traced_shuffle(shuffle_bytes(
            chunk, self.group_key_indices, self.n_shards, cap), raw_rows)
        local, n_drop, fill = mesh_ingest_chunk(
            chunk, self.group_key_indices, self._routing, VNODE_AXIS,
            self.n_shards, cap)
        st, ov, occ = self._apply_impl(state, overflow, local)
        return (st, ov, (dropped + n_drop).astype(dropped.dtype), occ,
                fold_shuffle_obs(obs, fill, local.vis))

    def _get_fused_apply(self):
        prog = self._fused_applies.get(self._cap_hint)
        if prog is not None:
            return prog
        shard = P(VNODE_AXIS)

        def apply_fused(state, overflow, dropped, obs, chunk):
            st, ov, dr, occ, so = self._fused_step(
                state, overflow[0], dropped[0], obs[0], chunk)
            return st, ov[None], dr[None], occ[None], so[None]

        prog = jit_state(shard_map(
            apply_fused, mesh=self.mesh,
            in_specs=(shard,) * 5, out_specs=(shard,) * 5),
            donate_argnums=(0, 1, 2, 3), name="sharded_agg_apply_fused")
        self._fused_applies[self._cap_hint] = prog
        return prog

    def _make_fused_scan(self, k: int):
        """k identically-shaped chunks of one barrier interval, applied
        in ONE device dispatch: lax.scan over the stacked batch INSIDE
        the shard_map program, each step shuffling then applying — the
        whole interval's exchange + compute is a single fused program
        regardless of shard count."""
        shard = P(VNODE_AXIS)

        def scan_body(state, overflow, dropped, obs, *chunks):
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *chunks)

            def step(carry, chunk):
                st, ov, dr, so = carry
                st, ov2, dr2, occ, so2 = self._fused_step(
                    st, ov, dr, so, chunk)
                return (st, ov2.astype(ov.dtype), dr2, so2), occ

            (st, ov, dr, so), occs = jax.lax.scan(
                step, (state, overflow[0], dropped[0], obs[0]), stacked)
            return st, ov[None], dr[None], occs[-1][None], so[None]

        return jit_state(shard_map(
            scan_body, mesh=self.mesh,
            in_specs=(shard, shard, shard, shard) + (shard,) * k,
            out_specs=(shard, shard, shard, shard, shard)),
            donate_argnums=(0, 1, 2, 3),
            name=f"sharded_agg_apply_fused_scan{k}")

    def _apply_chunk_raw(self, chunk: StreamChunk) -> None:
        (self.state, self._overflow_dev, self._dropped_dev,
         self._occ_dev, self._shuffle_obs_dev) = self._get_fused_apply()(
            self.state, self._overflow_dev, self._dropped_dev,
            self._shuffle_obs_dev, chunk)
        self._count_shuffle_dispatch(chunk)
        self.mesh_shuffle_applies += 1
        self._applied_since_flush = True

    def _drain_pending(self) -> None:
        """Interval drain: a multi-chunk run goes through the fused
        shard_map scan (one dispatch), a single chunk or a mixed run
        through the per-chunk fused program. The parent's
        unsharded scan machinery is bypassed entirely — its programs
        would mis-handle the sharded global state."""
        p = self._pending_chunks
        if not p:
            return
        self._pending_chunks = []
        # replay point: retain the interval's ingest BEFORE the fused
        # program consumes it (references only — chunks are never
        # donated on the ingest path). With preludes installed, the RAW
        # source chunk is the replay point — re-running the fused program
        # re-runs the hollowed producer stages too. The log holds the
        # chunk AS IT CAME, the object the frontier channels skip by
        # identity after a preload; the drain pads it, a replay's too.
        for ch in p:
            self.ingest_log.note(ch)
        p = [self._mesh_chunk(ch) for ch in p]
        # replay preloads bypass _enqueue_chunk's shape splitting, so the
        # scan's jnp.stack needs an explicit uniformity check here
        uniform = len({(c.capacity, len(c.columns),
                        tuple(col.valid is not None for col in c.columns))
                       for c in p}) == 1
        if len(p) == 1 or not uniform:
            if not self._mesh_preludes:
                # raw-schema chunks under preludes would confuse the
                # spill reload walk; the sharded agg never spills anyway
                self._mem_check_reload(p)
            for ch in p:
                self._apply_chunk_raw(ch)
            return
        # pow2 batch buckets with all-invisible fillers, exactly like the
        # parent's scan path (zero-copy views of the last chunk's arrays)
        k = 1 << (len(p) - 1).bit_length()
        if k > len(p):
            last = p[-1]
            filler = StreamChunk(last.columns, last.ops,
                                 jnp.zeros(last.capacity, dtype=bool),
                                 last.schema)
            p = p + [filler] * (k - len(p))
        if not self._mesh_preludes:
            self._mem_check_reload(p)
        scan = self._fused_scans.get((k, self._cap_hint))
        if scan is None:
            scan = self._make_fused_scan(k)
            self._fused_scans[(k, self._cap_hint)] = scan
        (self.state, self._overflow_dev, self._dropped_dev,
         self._occ_dev, self._shuffle_obs_dev) = scan(
            self.state, self._overflow_dev, self._dropped_dev,
            self._shuffle_obs_dev, *p)
        self._count_shuffle_dispatch(p[0], chunks=k)
        self.mesh_shuffle_applies += 1
        self._applied_since_flush = True

    # ------------------------------------------------------------ state
    def _initial_state(self, capacity: int) -> AggState:
        """Global state arrays [S*C] placed sharded along the mesh axis
        (_empty_state itself stays LOCAL — jitted impls build per-shard
        scratch state with it inside shard_map)."""
        S = self.n_shards
        local = self._empty_state(capacity)
        sharding = NamedSharding(self.mesh, P(VNODE_AXIS))

        def expand(x):
            g = jnp.tile(x, (S,) + (1,) * (x.ndim - 1)) if x.ndim else x
            return jax.device_put(g, sharding)

        return jax.tree_util.tree_map(expand, local)

    def _precompile_purge(self):
        # the mesh purge is `sharded_agg_purge`, compiled when first needed
        return None

    async def _maybe_rebuild_at_barrier(self) -> None:
        # static per-shard capacity in v1 (growth would need a global
        # re-layout), but zombie PURGING is mesh-safe: when the watchdog's
        # max-shard occupancy crosses the threshold, rebuild at the same
        # capacity to reclaim watermark-evicted slots — without this,
        # default-watchdog pipelines accumulate zombies until a spurious
        # overflow fail-stop
        if self._occ_known > 0.7 * self.capacity:
            self.state = self._purge(self.state)
            self.rebuilds += 1
            self._occ_known = 0  # refreshed by the next watchdog fetch

    def _persist_views(self, barrier):
        """The parent's, over the SHARDED state: the per-shard persist
        view compacts each shard's dirty rows to its LOCAL prefix; the
        evict view is the parent's. Returns `(dev, evict)`: the view's
        `(cols, ops, vis, n_dirty per shard)` and `(key arrays, count)`,
        each None where the barrier has none."""
        # stamp the interval's replay point with the epoch this barrier
        # seals; the coordinator drops it when that epoch commits
        self.ingest_log.seal(barrier.epoch.prev)
        if self.state_table is None:
            return None
        dev = evict = None
        if self._applied_since_flush:
            dev = self._persist_view_sh(self.state)
        if (self.cleaning_watermark_key is not None
                and self._pending_clean_wm is not None):
            keys_dev, n_ev = self._evict_keys(self.state,
                                              self._pending_clean_wm)
            evict = (list(keys_dev), n_ev)
        return dev, evict

    async def _persist(self, barrier, views) -> None:
        """All shards' prefixes ship in TWO d2h calls (counts, then one
        packed buffer — same per-call d2h discipline as the parent's).
        Like the parent's, the counts are awaited and the prefixes packed
        by the actor AT the barrier; the pure wait for that pack, the
        writes and the commit defer to the store (drained by the
        background uploader in pipelined mode)."""
        if views is None:
            return
        dev, evict = views
        st = self.state_table
        # int32 before the concatenate: joining a mesh-sharded int64[S]
        # with a replicated int64[1] ABORTS the TPU compiler (check failure
        # "Unsupported conversion from vmreg/vreg to U64", libtpu 0.0.34;
        # found on four real chips); the same program in int32 compiles
        count_parts = []
        if dev is not None:                            # n_dirty per shard
            count_parts.append(jnp.ravel(dev[3]).astype(jnp.int32))
        if evict is not None:
            count_parts.append(jnp.ravel(evict[1]).astype(jnp.int32))
        new_epoch = barrier.epoch.curr
        C, S = self.capacity, self.n_shards

        def plan(counts):
            groups, i, nev = [], 0, 0
            if dev is not None:
                cols, ops, vis, _ = dev
                # every shard's prefix at the largest shard's bucket, the
                # empty ones too: the packed shapes repeat (d2h.py)
                nd_max = int(max(counts[:S]))
                for sh in range(S if nd_max else 0):
                    lo = sh * C
                    groups.append((
                        [ops[lo:lo + C], vis[lo:lo + C]]
                        + [c[lo:lo + C] for c in cols],
                        int(counts[sh]), nd_max))
                i = S
            n_rows_groups = len(groups)
            if evict is not None:
                nev = int(counts[i])
                if nev:
                    groups.append((evict[0], nev))

            def write(outs):
                for seg in outs[:n_rows_groups]:
                    if len(seg[0]):
                        st.write_chunk_columns(seg[0], seg[2:], seg[1])
                if nev:
                    self._apply_evict_deletes(outs[-1], nev)
                st.commit(new_epoch)

            return groups, write

        await defer_prefix_flush(
            st.store, barrier.epoch.prev, st.table_id,
            jnp.concatenate(count_parts) if count_parts else None, plan)

    def recover(self, barrier_epoch: int) -> None:
        """Rebuild SHARDED device state: rows partition by
        vnode-of-group-key (the same routing the apply path shuffles by),
        each shard's slice is built locally with the parent's machinery,
        and the slices concatenate along the mesh axis. The durable
        persist path is the parent's unchanged — its snapshot-diff view
        is shape-agnostic over the global [S*C] arrays."""
        # channel-free mesh replay: install the preloaded ingest suffix
        # now that the durable state rebuild is about to run on pre-crash
        # committed state (the INITIAL barrier's drain already ran, so
        # these apply in one fused scan at the NEXT barrier).
        if self._replay_preload:
            self._pending_chunks = self._replay_preload \
                + self._pending_chunks
            self._replay_preload = []
        if self.state_table is None:
            return
        rows = [r for _, r in self.state_table.iter_all()]
        if not rows:
            return
        from ..common.vnode import compute_vnodes_numpy
        nk = len(self.group_key_indices)
        key_cols = [np.asarray([r[j] for r in rows], dtype=np.int64)
                    for j in range(nk)]
        shard_of = np.asarray(self._routing)[compute_vnodes_numpy(key_cols)]
        by_shard = [[] for _ in range(self.n_shards)]
        for r, sh in zip(rows, shard_of):
            by_shard[int(sh)].append(r)
        worst = max(len(b) for b in by_shard)
        need = 1 << max(self.capacity.bit_length() - 1,
                        (int(worst / 0.7)).bit_length())
        self.capacity = max(self.capacity, need)
        locals_ = [self._state_from_rows(b, self.capacity)
                   for b in by_shard]
        sharding = NamedSharding(self.mesh, P(VNODE_AXIS))

        def concat(*xs):
            if xs[0].ndim == 0:
                return xs[0]   # replicated scalar (as in _initial_state)
            return jax.device_put(jnp.concatenate(xs), sharding)

        self.state = jax.tree_util.tree_map(concat, *locals_)
        self._occ_known = worst

    # ------------------------------------------------- HBM memory manager
    # Accounting is inherited (pytree_bytes over the global [S*C] arrays
    # is exact), but per-shard capacity is STATIC in v1 — a shrinking
    # rehash would need a global re-layout — so the sharded agg reports
    # bytes and never evicts (ROADMAP open item).
    @property
    def mem_shards(self) -> int:
        """Shard count for the memory manager's per-shard breakdown:
        the global arrays split evenly over the mesh axis, so each
        device holds state_bytes() / n_shards of this executor's HBM."""
        return self.n_shards

    def state_shard_bytes(self) -> int:
        return self.state_bytes() // self.n_shards

    def memory_enable_lru(self) -> None:
        pass

    def memory_evict(self, target_bytes: int, epoch: int) -> int:
        return 0

    async def _check_watchdog(self) -> None:
        vals = (await off_loop(fetch_small, self._watchdog_pack(
            self._overflow_dev, self._occ_dev, self._dropped_dev,
            self._shuffle_obs_dev)))[0]
        n_un, occ, n_drop, fill = (int(vals[0]), int(vals[1]),
                                   int(vals[2]), int(vals[3]))
        self._note_probe_fallback(int(vals[4]))
        self._publish_shuffle(int(vals[5]), int(vals[6]), fill)
        self._fail_on_shuffle_drops(n_drop)
        if n_un:
            raise RuntimeError(
                f"sharded hash-agg overflow ({n_un} rows, per-shard "
                f"capacity {self.capacity})")
        self._occ_known = occ
