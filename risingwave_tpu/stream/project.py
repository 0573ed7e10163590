"""Project / Filter executors — the stateless jit targets.

Reference: src/stream/src/executor/project.rs and filter.rs (~400 LoC each).
Both are pure chunk->chunk maps; each compiles once (fixed chunk capacity =
static shapes) and all expressions in the tree fuse into a single XLA
computation.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..common.chunk import (
    Column, StreamChunk, OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT,
)
from ..common.types import Field, Schema
from ..expr.ir import Expr
from .executor import Executor, StatelessUnaryExecutor
from .message import Watermark
from ..ops.jit_state import jit_state


class ProjectExecutor(StatelessUnaryExecutor):
    # Mesh-chain fusion (plan/build._fuse_mesh_chains): a hollow project
    # passes chunks through UNTOUCHED — its _step_impl runs instead as a
    # prelude INSIDE the downstream sharded executor's fused shard_map
    # program (zero host hops). Watermark mapping stays host-side active:
    # watermarks are control metadata in output coordinates either way.
    mesh_hollow = False

    def mesh_prelude_fn(self):
        """Pure chunk->chunk map safe to run per-SHARD inside shard_map.

        Project qualifies: row-wise, no cross-row structure. (Filter does
        NOT — its UD/UI pair fixup reads the neighbouring row via roll,
        which breaks when an update pair straddles a shard-slice edge.)"""
        return self._step_impl

    def __init__(self, input: Executor, exprs: Sequence[Expr],
                 names: Optional[Sequence[str]] = None,
                 watermark_mapping: Optional[dict[int, int]] = None,
                 watermark_transforms: Optional[dict] = None):
        super().__init__(input)
        self.exprs = tuple(exprs)
        names = names or [f"expr{i}" for i in range(len(exprs))]
        self.schema = Schema(tuple(Field(n, e.ret_type) for n, e in zip(names, exprs)))
        # input col idx -> output col idx for watermark passthrough (the
        # reference derives this from InputRef-only exprs; here explicit)
        self.watermark_mapping = watermark_mapping or {
            e.index: i for i, e in enumerate(self.exprs)
            if type(e).__name__ == "InputRef"
        }
        # input col idx -> (output col idx, host fn) for watermarks through
        # MONOTONE non-decreasing expressions (reference: Watermark::
        # transform_with_expr, e.g. tumble_end) — the caller asserts
        # monotonicity by providing the transform
        self.watermark_transforms = dict(watermark_transforms or {})
        self.identity = f"Project({', '.join(map(repr, self.exprs))})"
        self._step = jit_state(self._step_impl, name="project_step")

    def _step_impl(self, chunk: StreamChunk) -> StreamChunk:
        cols = tuple(e.eval(chunk.columns) for e in self.exprs)
        return StreamChunk(cols, chunk.ops, chunk.vis, self.schema)

    def map_chunk(self, chunk):
        if self.mesh_hollow:
            return chunk            # prelude runs fused downstream
        return self._step(chunk)

    def map_watermark(self, wm: Watermark):
        tf = self.watermark_transforms.get(wm.col_idx)
        if tf is not None:
            # one input watermark may fan out to several monotone outputs
            # (tumble: event time -> window_start AND window_end)
            tfs = tf if isinstance(tf, list) else [tf]
            return [Watermark(out_idx, self.schema[out_idx].data_type,
                              fn(wm.val))
                    for out_idx, fn in tfs]
        out = self.watermark_mapping.get(wm.col_idx)
        return wm.with_idx(out) if out is not None else None


class FilterExecutor(StatelessUnaryExecutor):
    """Filter with changelog op fixup (reference filter.rs:simplified_ops):
    an Update pair whose old row passes but new doesn't becomes a Delete;
    new-passes-only becomes an Insert. Fully vectorized over the pair
    structure (UpdateDelete at i, UpdateInsert at i+1)."""

    def __init__(self, input: Executor, predicate: Expr):
        super().__init__(input)
        self.predicate = predicate
        self.identity = f"Filter({predicate!r})"
        self._step = jit_state(self._step_impl, name="filter_step")

    def _step_impl(self, chunk: StreamChunk) -> StreamChunk:
        pred = self.predicate.eval(chunk.columns)
        cond = pred.data & pred.valid_mask()  # NULL = filtered out
        ops = chunk.ops
        is_ud = ops == OP_UPDATE_DELETE
        is_ui = ops == OP_UPDATE_INSERT
        # cond of the pair partner
        cond_prev = jnp.roll(cond, 1)   # for UI rows: partner UD at i-1
        cond_next = jnp.roll(cond, -1)  # for UD rows: partner UI at i+1
        new_ops = jnp.where(is_ui & cond & ~cond_prev, OP_INSERT, ops)
        new_ops = jnp.where(is_ud & cond & ~cond_next, OP_DELETE, new_ops).astype(ops.dtype)
        return StreamChunk(chunk.columns, new_ops, chunk.vis & cond, chunk.schema)

    def map_chunk(self, chunk):
        out = self._step(chunk)
        return out
