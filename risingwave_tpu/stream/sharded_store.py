"""Vnode-sharded dense sorted-row store — the shared mesh plumbing behind
`ShardedTopNExecutor` and `ShardedOverWindowExecutor`.

Both executors keep their FULL input in the dense sorted store
(sorted_store.py) and diff a derived set at each barrier. Sharding that
layout over the vnode mesh axis is identical for both — and identical in
shape to sharded_agg.py, the pattern this module mirrors:

* state arrays go global [S*C] with per-shard [C] views under shard_map
  (`capacity` becomes PER SHARD); the live count and error counters go
  per-shard ([S] / [S*2] int32, mesh-sharded);
* each chunk enters row-sliced over the mesh axis (a capacity the shard
  count does not divide is padded, `MeshShuffleHost._mesh_chunk`) and its
  rows route to their owner shard with `mesh_ingest_chunk` (one
  all_to_all over ICI — no host hop) keyed on
  the executor's ROUTING KEY (group/partition axis; the stream key for
  a global top-N), then applies `sorted_store_apply` per shard; chunks
  buffered within a barrier interval batch into one `lax.scan` inside
  the same program — one fused dispatch per interval;
* hollow producer stages (project / hop_window preludes installed by
  plan/build._fuse_mesh_chains) trace INSIDE the fused program, before
  the shuffle;
* shuffle overflow / store overflow / delete-miss accumulate on device
  and FAIL-STOP at the barrier watchdog fetch (one packed d2h);
* `MeshIngestLog` retains the uncommitted ingest suffix as the
  mesh-plane replay point; `preload_replay` re-feeds it after a
  scope=mesh recovery;
* durable persist/seal/recovery run unchanged through the sharded
  layout: epoch chunks write through to the state table at the barrier,
  and recovery partitions durable rows by the same vnode routing the
  apply path uses, rebuilding each shard's local store.

Per-shard capacity is STATIC at runtime (growth would need a global
re-layout — overflow fail-stops and recovery re-sizes from the worst
shard), matching the sharded agg's contract.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..common.chunk import StreamChunk
from ..ops.jit_state import jit_state
from ..parallel.exchange import mesh_ingest_chunk, shuffle_bytes
from ..parallel.mesh import VNODE_AXIS, shard_map
from ..utils.d2h import fetch_small, off_loop
from .mesh_shuffle import OBS_FILL, OBS_ROWS, MeshShuffleHost, fold_shuffle_obs
from .sorted_join import _HSENTINEL
from .sorted_store import sorted_store_apply


class ShardedSortedStoreMixin(MeshShuffleHost):
    """Mesh plumbing over a (khash, cols, valids, n) sorted store plus a
    same-capacity secondary set. Subclasses (which also inherit the
    single-device executor) must provide:

      route_key_indices   columns the shuffle routes on
      _SECONDARY          (hash, cols, valids) secondary attr names
      _SEC_COUNT          the secondary's live-count attr name
      _flush_local(...)   per-shard flush body (parent's _flush_impl or
                          a mesh-aware variant), called INSIDE shard_map
      _overflow_what      human label for the fail-stop messages

    and call `_init_sharded(...)` AFTER the parent constructor."""

    _SEC_COUNT = ""
    _overflow_what = "sharded sorted store"

    # --------------------------------------------------------------- init
    def _init_sharded(self, mesh,
                      watchdog_interval: Optional[int]) -> None:
        self._init_mesh_shuffle(mesh, 0, watchdog_interval is not None)
        self._pending_chunks: list = []
        self._batch_max = 8
        self._occ_known = 0
        self._alloc_sharded_store()
        self._build_sharded_programs()

    def _sharding(self):
        return NamedSharding(self.mesh, P(VNODE_AXIS))

    def _store_schema(self):
        """Schema of the rows the dense store holds (and the state table
        persists) — the executor's input row layout."""
        return self.schema

    def _alloc_sharded_store(self) -> None:
        """Replace the parent's single-device [C] arrays with global
        [S*C] mesh-sharded ones; counts become per-shard [S] lanes."""
        S, C = self.n_shards, self.capacity
        sharding = self._sharding()

        def put(x):
            return jax.device_put(x, sharding)

        dts = tuple(f.data_type.jnp_dtype for f in self._store_schema())
        self.khash = put(jnp.full(S * C, _HSENTINEL, dtype=jnp.int64))
        self.cols = tuple(put(jnp.zeros(S * C, dtype=dt)) for dt in dts)
        self.valids = tuple(put(jnp.zeros(S * C, dtype=bool)) for _ in dts)
        self.n = put(jnp.zeros(S, dtype=jnp.int32))
        self._alloc_sharded_secondary()
        # per-shard error/overflow accumulators ([row_ovf, del_miss] per
        # shard) + the shuffle watchdog lanes, all mesh-sharded
        self._errs_dev = put(jnp.zeros(S * 2, dtype=jnp.int32))
        self._dropped_dev = put(jnp.zeros(S, dtype=jnp.int32))
        self._shuffle_obs_dev = self._fresh_shuffle_obs()

    def _alloc_sharded_secondary(self) -> None:
        S, C = self.n_shards, self.capacity
        sharding = self._sharding()

        def put(x):
            return jax.device_put(x, sharding)

        h, c, v = self._SECONDARY
        sec_dts = tuple(x.dtype for x in getattr(self, c))
        setattr(self, h, put(jnp.full(S * C, _HSENTINEL, dtype=jnp.int64)))
        setattr(self, c, tuple(put(jnp.zeros(S * C, dtype=dt))
                               for dt in sec_dts))
        setattr(self, v, tuple(put(jnp.zeros(S * C, dtype=bool))
                               for _ in sec_dts))
        setattr(self, self._SEC_COUNT, put(jnp.zeros(S, dtype=jnp.int32)))

    def _build_sharded_programs(self) -> None:
        """(Re)wrap the step impls in shard_map — called at init and
        after a recovery re-size (the programs close over capacity)."""
        shard = P(VNODE_AXIS)
        mesh_kw = dict(mesh=self.mesh)
        name = type(self).__name__

        def flush_sharded(khash, cols, valids, n, sh, sc, sv, sn):
            nh, nc, nv, n2, oc, ops, vis = self._flush_local(
                khash, cols, valids, n[0], sh, sc, sv, sn[0])
            return nh, nc, nv, n2[None], oc, ops, vis

        self._flush = jit_state(shard_map(
            flush_sharded, in_specs=(shard,) * 8,
            out_specs=(shard,) * 7, **mesh_kw),
            donate_argnums=(4, 5, 6, 7), name=f"{name}_flush")

        def watchdog_sharded(errs, n, dr, so):
            e = jax.lax.psum(errs, VNODE_AXIS)            # [2]
            # int32 before the MAX: the TPU lowers a 64-bit all-reduce
            # only for SUM (sharded_agg.py watchdog_sharded)
            mx = jax.lax.pmax(n[0].astype(jnp.int32), VNODE_AXIS)
            td = jax.lax.psum(dr[0], VNODE_AXIS)
            mf = jax.lax.pmax(so[0, OBS_FILL], VNODE_AXIS)
            rows = jax.lax.psum(so[0, OBS_ROWS], VNODE_AXIS)
            rows_max = jax.lax.pmax(so[0, OBS_ROWS], VNODE_AXIS)
            return jnp.concatenate(
                [e, jnp.stack([mx, td, mf, rows, rows_max])]
            ).astype(jnp.int32)[None]

        self._watchdog_pack = jit_state(shard_map(
            watchdog_sharded, in_specs=(shard,) * 4, out_specs=shard,
            **mesh_kw), name=f"{name}_watchdog_pack")

        # per-chunk fused programs keyed by the adaptive cap hint; scans
        # keyed (k, hint) — cleared here so a re-size retraces
        self._fused_applies: dict = {}
        self._fused_scans: dict = {}

    # ------------------------------------------------ fused mesh shuffle
    def _fused_step(self, khash, cols, valids, n, errs, dropped, obs,
                    chunk):
        """Preludes + in-mesh shuffle + sorted-store apply for ONE chunk,
        inside shard_map (per-shard views, scalar n/dropped)."""
        raw_rows = chunk.capacity
        for fn in self._mesh_preludes:
            chunk = fn(chunk)
        cap = self._trace_cap(chunk.capacity)
        self._note_traced_shuffle(shuffle_bytes(
            chunk, self.route_key_indices, self.n_shards, cap), raw_rows)
        local, n_drop, fill = mesh_ingest_chunk(
            chunk, self.route_key_indices, self._routing, VNODE_AXIS,
            self.n_shards, cap)
        kh, c, v, n2, e2 = sorted_store_apply(
            khash, cols, valids, n, errs, local,
            pk_idx=self.pk_indices, capacity=self.capacity)
        return kh, c, v, n2, e2, (dropped + n_drop).astype(dropped.dtype), \
            fold_shuffle_obs(obs, fill, local.vis)

    def _get_fused_apply(self):
        prog = self._fused_applies.get(self._cap_hint)
        if prog is not None:
            return prog
        shard = P(VNODE_AXIS)

        def apply_fused(khash, cols, valids, n, errs, dropped, obs, chunk):
            kh, c, v, n2, e2, dr, so = self._fused_step(
                khash, cols, valids, n[0], errs, dropped[0], obs[0], chunk)
            return kh, c, v, n2[None], e2, dr[None], so[None]

        prog = jit_state(shard_map(
            apply_fused, mesh=self.mesh, in_specs=(shard,) * 8,
            out_specs=(shard,) * 7),
            donate_argnums=(0, 1, 2, 3, 4, 5, 6),
            name=f"{type(self).__name__}_apply_fused")
        self._fused_applies[self._cap_hint] = prog
        return prog

    def _make_fused_scan(self, k: int):
        """One barrier interval's k identically-shaped chunks in ONE
        device dispatch: lax.scan over the stacked batch inside
        shard_map, each step shuffling then applying."""
        shard = P(VNODE_AXIS)

        def scan_body(khash, cols, valids, n, errs, dropped, obs, *chunks):
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *chunks)

            def step(carry, chunk):
                kh, c, v, nn, e, dr, so = carry
                kh, c, v, n2, e2, dr2, so2 = self._fused_step(
                    kh, c, v, nn, e, dr, so, chunk)
                return (kh, c, v, n2.astype(nn.dtype), e2, dr2, so2), ()

            (kh, c, v, nn, e, dr, so), _ = jax.lax.scan(
                step, (khash, cols, valids, n[0], errs, dropped[0],
                       obs[0]), stacked)
            return kh, c, v, nn[None], e, dr[None], so[None]

        return jit_state(shard_map(
            scan_body, mesh=self.mesh,
            in_specs=(shard,) * 7 + (shard,) * k,
            out_specs=(shard,) * 7),
            donate_argnums=(0, 1, 2, 3, 4, 5, 6),
            name=f"{type(self).__name__}_apply_fused_scan{k}")

    def _apply_chunk_raw(self, chunk: StreamChunk) -> None:
        (self.khash, self.cols, self.valids, self.n, self._errs_dev,
         self._dropped_dev, self._shuffle_obs_dev) = \
            self._get_fused_apply()(
                self.khash, self.cols, self.valids, self.n,
                self._errs_dev, self._dropped_dev,
                self._shuffle_obs_dev, chunk)
        self._count_shuffle_dispatch(chunk)
        self.mesh_shuffle_applies += 1
        self._applied_since_flush = True

    def _drain_pending(self) -> None:
        p = self._pending_chunks
        if not p:
            return
        self._pending_chunks = []
        # replay point: retain the interval's ingest BEFORE the fused
        # program consumes it (references only). With preludes installed
        # the RAW source chunk is the replay point — re-running the fused
        # program re-runs the hollowed producer stages too. The log holds
        # the chunk AS IT CAME (the frontier channels skip a preloaded
        # chunk by identity); the drain pads it, a replay's too.
        for ch in p:
            self.ingest_log.note(ch)
        p = [self._mesh_chunk(ch) for ch in p]
        uniform = len({(c.capacity, len(c.columns),
                        tuple(col.valid is not None for col in c.columns))
                       for c in p}) == 1
        if len(p) == 1 or not uniform:
            for ch in p:
                self._apply_chunk_raw(ch)
            return
        k = 1 << (len(p) - 1).bit_length()
        if k > len(p):
            last = p[-1]
            filler = StreamChunk(last.columns, last.ops,
                                 jnp.zeros(last.capacity, dtype=bool),
                                 last.schema)
            p = p + [filler] * (k - len(p))
        scan = self._fused_scans.get((k, self._cap_hint))
        if scan is None:
            scan = self._make_fused_scan(k)
            self._fused_scans[(k, self._cap_hint)] = scan
        (self.khash, self.cols, self.valids, self.n, self._errs_dev,
         self._dropped_dev, self._shuffle_obs_dev) = scan(
            self.khash, self.cols, self.valids, self.n, self._errs_dev,
            self._dropped_dev, self._shuffle_obs_dev, *p)
        self._count_shuffle_dispatch(p[0], chunks=k)
        self.mesh_shuffle_applies += 1
        self._applied_since_flush = True

    # -------------------------------------------------------------- hooks
    def on_chunk(self, chunk: StreamChunk) -> None:
        if self.state_table is not None:
            self._epoch_chunks.append(chunk)
        self._pending_chunks.append(chunk)
        if len(self._pending_chunks) >= self._batch_max:
            self._drain_pending()
        return None

    def flush(self):
        self._drain_pending()
        h, c, v = self._SECONDARY
        sec = (getattr(self, h), getattr(self, c), getattr(self, v),
               getattr(self, self._SEC_COUNT))
        (nh, nc, nv, nn, out_cols, ops, vis) = self._flush(
            self.khash, self.cols, self.valids, self.n, *sec)
        setattr(self, h, nh)
        setattr(self, c, nc)
        setattr(self, v, nv)
        setattr(self, self._SEC_COUNT, nn)
        return StreamChunk(out_cols, ops, vis, self.schema)

    async def check_watchdog(self) -> None:
        # the drain must run BEFORE the fetch so this interval's shuffle
        # drops / store overflow fail-stop the SAME epoch
        self._drain_pending()
        vals = (await off_loop(fetch_small, self._watchdog_pack(
            self._errs_dev, self.n, self._dropped_dev,
            self._shuffle_obs_dev)))[0]
        (n_ovf, n_miss, max_n, n_drop, fill, rows,
         rows_max) = (int(x) for x in vals)
        self._publish_shuffle(rows, rows_max, fill)
        self._fail_on_shuffle_drops(n_drop)
        if n_ovf:
            raise RuntimeError(
                f"{self._overflow_what} overflow ({n_ovf} rows dropped; "
                f"per-shard capacity {self.capacity})")
        if n_miss:
            raise RuntimeError(
                f"{self._overflow_what}: {n_miss} deletes matched no row")
        self._occ_known = max_n

    def persist(self, barrier, flushed) -> None:
        # stamp the interval's replay point with the epoch this barrier
        # seals; the coordinator drops it when that epoch commits
        self.ingest_log.seal(barrier.epoch.prev)
        if self.state_table is None:
            return
        for c in self._epoch_chunks:
            # raw (pre-prelude) chunks are the replay point, but the
            # state table persists EXECUTOR-SCHEMA rows: run the hollow
            # producer stages host-side before writing through
            for fn in self._mesh_preludes:
                c = fn(c)
            vis = np.asarray(c.vis)
            if vis.any():
                self.state_table.write_chunk_columns(
                    np.asarray(c.ops), [np.asarray(col.data)
                                        for col in c.columns], vis)
        self._epoch_chunks = []
        self.state_table.commit(barrier.epoch.curr)

    def recover_state(self, epoch: int) -> None:
        """Durable rebuild through the sharded layout: partition rows by
        the vnode routing, rebuild each shard's local store, concatenate
        along the mesh axis, then seed the diff baseline with one
        discarded sharded flush (same rationale as the parents')."""
        if self._replay_preload:
            self._pending_chunks = self._replay_preload \
                + self._pending_chunks
            self._replay_preload = []
            # the template only flushes epochs that saw input: mark the
            # preloaded suffix as pending work so the NEXT barrier drains
            # and re-emits it even if no fresh chunks arrive
            self._applied_since_flush = True
        if self.state_table is None:
            return
        rows = [r for _, r in self.state_table.iter_all()]
        if not rows:
            return
        from ..common.vnode import compute_vnodes_numpy
        from ..state.storage_table import rows_to_columns
        schema = self._store_schema()
        # NULL routing cells carry data=0 on device (rows_to_columns
        # convention) — mirror that here so rebuild lands rows on the
        # same shard the live apply path routed them to
        route_cols = [np.asarray([0 if r[j] is None else r[j]
                                  for r in rows], dtype=np.int64)
                      for j in self.route_key_indices]
        shard_of = np.asarray(self._routing)[
            compute_vnodes_numpy(route_cols)]
        by_shard = [[] for _ in range(self.n_shards)]
        for r, sh in zip(rows, shard_of):
            by_shard[int(sh)].append(r)
        worst = max(len(b) for b in by_shard)
        need = 1 << max(self.capacity.bit_length() - 1,
                        (int(worst / 0.7)).bit_length())
        if need != self.capacity:
            self.capacity = need
            self._build_sharded_programs()
        C = self.capacity
        dts = tuple(f.data_type.jnp_dtype for f in schema)
        local_apply = jit_state(
            partial(sorted_store_apply, pk_idx=self.pk_indices,
                    capacity=C),
            donate_argnums=(0, 1, 2, 3, 4),
            name=f"{type(self).__name__}_recover_rows")
        locals_ = []
        for part_rows in by_shard:
            kh = jnp.full(C, _HSENTINEL, dtype=jnp.int64)
            cs = tuple(jnp.zeros(C, dtype=dt) for dt in dts)
            vs = tuple(jnp.zeros(C, dtype=bool) for _ in dts)
            nn = jnp.int32(0)
            errs = jnp.zeros(2, dtype=jnp.int32)
            cap = 1 << max(6, max(len(part_rows) - 1, 0).bit_length())
            for ofs in range(0, len(part_rows), cap):
                part = part_rows[ofs:ofs + cap]
                arrays, valids = rows_to_columns(schema, part)
                ch = StreamChunk.from_numpy(
                    schema, arrays, capacity=cap,
                    valids=[None if v.all() else v for v in valids])
                kh, cs, vs, nn, errs = local_apply(kh, cs, vs, nn, errs,
                                                   ch)
            locals_.append((kh, cs, vs, nn[None], errs))
        sharding = self._sharding()

        def concat(*xs):
            return jax.device_put(jnp.concatenate(xs), sharding)

        (self.khash, self.cols, self.valids, self.n,
         self._errs_dev) = jax.tree_util.tree_map(concat, *locals_)
        self._alloc_sharded_secondary()
        self._occ_known = worst
        h, c, v = self._SECONDARY
        sec = (getattr(self, h), getattr(self, c), getattr(self, v),
               getattr(self, self._SEC_COUNT))
        nh, nc, nv, nn, _c, _o, _v = self._flush(
            self.khash, self.cols, self.valids, self.n, *sec)
        setattr(self, h, nh)
        setattr(self, c, nc)
        setattr(self, v, nv)
        setattr(self, self._SEC_COUNT, nn)

    # ------------------------------------------------- HBM memory manager
    @property
    def mem_shards(self) -> int:
        return self.n_shards

    def state_shard_bytes(self) -> int:
        return self.state_bytes() // self.n_shards

    def memory_enable_lru(self) -> None:
        pass

    def memory_evict(self, target_bytes: int, epoch: int) -> int:
        return 0
