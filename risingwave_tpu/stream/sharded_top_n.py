"""Mesh-sharded retractable top-N — q5-shaped ranking ON the mesh plane.

`RetractableTopNExecutor`'s dense sorted store and snapshot-diff flush,
sharded over the vnode mesh axis (sharded_store.py carries the plumbing:
fused `mesh_ingest_chunk` shuffle + per-interval `lax.scan`, watchdog
fail-stop, `MeshIngestLog` replay, durable persist/seal/recovery through
the sharded layout).

Two ranking modes, picked by the plan shape:

* GROUPED (`group_key_indices` non-empty): rows route on the group key,
  so every group lives whole on one shard and the parent's rank-within-
  group flush runs per shard unchanged — ranks never cross shards.

* GLOBAL (the binder's `ORDER BY ... LIMIT k` lowering: no group key):
  rows route on the STREAM KEY (delete/insert netting needs pk
  co-location), so the top-k spans shards. The flush then runs in two
  stages inside one program: each shard locally ranks its rows and
  contributes its best `offset+limit` CANDIDATES (any globally-top row
  is locally-top: local rank never exceeds global rank under the same
  total order), an `all_gather` over the mesh axis replicates the
  S*(offset+limit) candidate rows, and every shard re-ranks them to the
  identical global top set — the emitted diff is vis-masked to shard 0
  so the output appears once. The candidate gather moves O(S*k) rows
  over ICI per barrier, not O(n): the store itself never leaves the
  shards.

Both modes rank by the parent's exact (order keys, stream key) total
order, so the selected set — and therefore the emitted diff — is
identical to the single-device executor's over a retracting input; the
chunk stays `2 x capacity` wide per shard and an append-only input is kept
in full (the single-device executor narrows the one and prunes the
other).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..common.chunk import Column, OP_DELETE, OP_INSERT
from ..ops.hash_table import stable_lexsort
from ..parallel.mesh import VNODE_AXIS
from .executor import Executor
from .retract_top_n import RetractableTopNExecutor
from .sharded_store import ShardedSortedStoreMixin
from .sorted_join import _HSENTINEL, key_hash


class ShardedTopNExecutor(ShardedSortedStoreMixin, RetractableTopNExecutor):

    _SEC_COUNT = "top_n"
    _overflow_what = "sharded top-N store"

    def __init__(self, input: Executor,
                 group_key_indices: Sequence[int],
                 order_col=None, limit: int = 0, offset: int = 0,
                 descending: bool = False,
                 order_specs: Optional[Sequence[tuple]] = None,
                 capacity: int = 1 << 11,
                 state_table=None,
                 pk_indices: Optional[Sequence[int]] = None,
                 watchdog_interval: Optional[int] = 1,
                 append_only: bool = False,
                 emit_rank: bool = False,
                 *, mesh):
        # parent ctor builds the single-device [C] store + programs;
        # _init_sharded replaces them with the [S*C] mesh-sharded layout
        # (capacity is PER SHARD from here on)
        # (an append-only input is kept in full here too: the mesh store
        # prunes nothing, which is only more than it needs)
        super().__init__(input, group_key_indices, order_col, limit,
                         offset, descending, order_specs, capacity,
                         state_table, pk_indices, watchdog_interval,
                         append_only=False, emit_rank=emit_rank)
        self.global_mode = not self.group_key_indices
        assert not (self.global_mode and emit_rank), \
            "a rank column needs a group key (the rank-filter plan)"
        # global mode routes on the stream key: a retraction carries the
        # same pk as its insert, so netting stays shard-local
        self.route_key_indices = (self.group_key_indices
                                  or self.pk_indices)
        if self.global_mode:
            assert self.offset + self.limit <= capacity, \
                "global top-N needs offset+limit <= per-shard capacity " \
                "(each shard contributes that many candidates)"
        self._init_sharded(mesh, watchdog_interval)
        self.identity = (f"ShardedTopN[S={self.n_shards}]"
                         f"(g={self.group_key_indices}, "
                         f"by={self.order_specs}, k={limit})")

    def _store_schema(self):
        return self.store_schema     # the input's: no rank column

    # ------------------------------------------------------------- flush
    def _flush_local(self, khash, cols, valids, n, top_hash, top_cols,
                     top_valids, top_n):
        if not self.global_mode:
            # groups are co-located: the parent's per-group rank diff is
            # exact on each shard's slice
            return self._flush_impl(khash, cols, valids, n, top_hash,
                                    top_cols, top_valids, top_n)
        return self._flush_impl_global(khash, cols, valids, n, top_hash,
                                       top_cols, top_valids, top_n)

    def _flush_impl_global(self, khash, cols, valids, n, top_hash,
                           top_cols, top_valids, top_n):
        C = self.capacity
        S = self.n_shards
        K = min(C, self.offset + self.limit)
        G = S * K
        imax = jnp.iinfo(jnp.int64).max
        live = jnp.arange(C, dtype=jnp.int32) < n

        # stage 1 — local rank: each shard's best K rows are the only
        # possible global top members (same total order ⇒ local rank is
        # a lower bound on global rank; the parent's `_sort_keys` both
        # times, or candidate pruning would be unsound)
        order = stable_lexsort(tuple(
            self._sort_keys(cols)
            + [jnp.where(live, jnp.zeros(C, dtype=jnp.int64), imax)]))
        cand = order[:K]

        def g(x):
            return jax.lax.all_gather(x, VNODE_AXIS, tiled=True)

        g_live = g(live[cand])
        g_cols = [g(c[cand]) for c in cols]
        g_valids = [g(v[cand]) for v in valids]

        # stage 2 — global re-rank of the S*K replicated candidates;
        # dead padding sorts last, rank == position (single group)
        gorder = stable_lexsort(tuple(
            self._sort_keys(g_cols)
            + [jnp.where(g_live, jnp.zeros(G, dtype=jnp.int64), imax)]))
        s_live = g_live[gorder]
        pos = jnp.arange(G, dtype=jnp.int32)
        in_top = s_live & (pos >= self.offset) \
            & (pos < self.offset + self.limit)
        s_cols = [c[gorder] for c in g_cols]
        s_valids = [v[gorder] for v in g_valids]
        rhash = key_hash(s_cols)
        topk = jnp.where(in_top, rhash, _HSENTINEL)
        torder = jnp.argsort(topk, stable=True)
        n_top = jnp.sum(in_top.astype(jnp.int32))

        def fit(x, fill):
            # the diff state is [C] per shard; sentinel/zero padding
            # keeps the hash array sorted for the searchsorted probe
            if G >= C:
                return x[:C]
            return jnp.concatenate(
                [x, jnp.full(C - G, fill, dtype=x.dtype)])

        new_hash = fit(topk[torder], _HSENTINEL)
        new_cols = tuple(fit(c[torder], jnp.zeros((), dtype=c.dtype))
                         for c in s_cols)
        new_valids = tuple(fit(v[torder], False) for v in s_valids)

        def member(a_hash, a_n, b_hash):
            i = jnp.clip(jnp.searchsorted(b_hash, a_hash), 0, C - 1)
            return (jnp.arange(C) < a_n) & (b_hash[i] == a_hash)

        old_still = member(top_hash, top_n, new_hash)
        emit_del = (jnp.arange(C) < top_n) & ~old_still
        new_was = member(new_hash, n_top, top_hash)
        emit_ins = (jnp.arange(C) < n_top) & ~new_was
        # every shard computed the IDENTICAL diff from the replicated
        # candidates — emit it once (shard 0's slice of the output)
        once = jax.lax.axis_index(VNODE_AXIS) == 0
        out_cols = tuple(
            Column(jnp.concatenate([tc, nc]), jnp.concatenate([tv, nv]))
            for tc, nc, tv, nv in zip(top_cols, new_cols, top_valids,
                                      new_valids))
        ops = jnp.concatenate([jnp.full(C, OP_DELETE, dtype=jnp.int8),
                               jnp.full(C, OP_INSERT, dtype=jnp.int8)])
        vis = jnp.concatenate([emit_del, emit_ins]) & once
        return (new_hash, new_cols, new_valids, n_top.astype(jnp.int32),
                out_cols, ops, vis)
