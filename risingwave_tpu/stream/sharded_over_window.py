"""Mesh-sharded over-window — PARTITION BY windows ON the mesh plane.

`GeneralOverWindowExecutor`'s dense sorted store and emitted-set diff,
sharded over the vnode mesh axis. Rows route on the PARTITION BY key,
so every window partition lives whole on one shard and the parent's
sort-and-recompute flush — partition segmentation, rank family, frame
aggregates, lag/lead gathers — runs per shard unchanged: window frames
never cross partitions, so they never cross shards either.

An EMPTY partition_by (one global partition) cannot shard this way and
stays on the single-device executor (the binder only lowers to this
executor when a partition axis exists); all the mesh plumbing — fused
per-interval shuffle+apply scan, watchdog fail-stop, replay log,
durable recovery partitioned by the same routing — comes from
sharded_store.py.
"""

from __future__ import annotations

from typing import Optional, Sequence

from ..common.types import Schema
from .executor import Executor
from .general_over_window import GeneralOverWindowExecutor, WindowSpec
from .sharded_store import ShardedSortedStoreMixin

__all__ = ["ShardedOverWindowExecutor", "WindowSpec"]


class ShardedOverWindowExecutor(ShardedSortedStoreMixin,
                                GeneralOverWindowExecutor):

    _SEC_COUNT = "em_n"
    _overflow_what = "sharded over-window store"

    def __init__(self, input: Executor,
                 partition_by: Sequence[int],
                 order_specs: Sequence[tuple],
                 windows: Sequence[WindowSpec],
                 capacity: int = 1 << 11,
                 state_table=None,
                 pk_indices: Optional[Sequence[int]] = None,
                 watchdog_interval: Optional[int] = 1,
                 *, mesh):
        if not partition_by:
            raise ValueError(
                "ShardedOverWindowExecutor shards along the partition "
                "axis; an OVER () window with no PARTITION BY has "
                "nothing to shard on — use GeneralOverWindowExecutor")
        super().__init__(input, partition_by, order_specs, windows,
                         capacity, state_table, pk_indices,
                         watchdog_interval)
        self.route_key_indices = self.partition_by
        self._init_sharded(mesh, watchdog_interval)
        self.identity = (f"ShardedOverWindow[S={self.n_shards}]"
                         f"(p={self.partition_by}, o={self.order_specs}, "
                         f"f={[w.kind for w in self.windows]})")

    def _store_schema(self):
        # the dense store (and the state table) hold INPUT rows; the
        # executor schema appends the computed window columns
        return Schema(tuple(self.schema)[:self.in_width])

    def _flush_local(self, khash, cols, valids, n, em_hash, em_cols,
                     em_valids, em_n):
        # partitions are co-located: the parent's full sort-and-diff is
        # exact on each shard's slice
        return self._flush_impl(khash, cols, valids, n, em_hash, em_cols,
                                em_valids, em_n)
