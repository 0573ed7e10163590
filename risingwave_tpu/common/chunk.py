"""Columnar chunks — the data quantum flowing between executors.

Re-design of the reference's DataChunk/StreamChunk
(src/common/src/array/data_chunk.rs:66, array/stream_chunk.rs:44-92) for XLA:
a chunk is a *fixed-capacity* struct-of-arrays pytree. Row count is dynamic
only through the visibility mask — shapes are static so every executor step
compiles once. The reference already carries a visibility bitmap on every
chunk; here it is load-bearing for padding as well.

Ops follow reference `Op` (stream_chunk.rs:44-49):
  INSERT=0  DELETE=1  UPDATE_DELETE=2  UPDATE_INSERT=3
`op_sign` maps insert-like ops to +1 and delete-like to -1 — the sign of a
row's contribution to any linear aggregate, which is how changelog semantics
stay branch-free on device.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .types import DataType, Schema

# Op encoding (int8 on device)
OP_INSERT = 0
OP_DELETE = 1
OP_UPDATE_DELETE = 2
OP_UPDATE_INSERT = 3

DEFAULT_CHUNK_CAPACITY = 4096


def op_sign(ops: jnp.ndarray) -> jnp.ndarray:
    """+1 for Insert/UpdateInsert, -1 for Delete/UpdateDelete."""
    is_insert = (ops == OP_INSERT) | (ops == OP_UPDATE_INSERT)
    return jnp.where(is_insert, jnp.int32(1), jnp.int32(-1))


@jax.tree_util.register_pytree_node_class
@dataclass
class Column:
    """One column: fixed-width data + optional validity (None = all valid)."""

    data: jnp.ndarray
    valid: Optional[jnp.ndarray] = None  # bool mask, True = non-null

    def tree_flatten(self):
        return (self.data, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def valid_mask(self) -> jnp.ndarray:
        if self.valid is None:
            return jnp.ones(self.data.shape[0], dtype=bool)
        return self.valid

    def take(self, idx: jnp.ndarray) -> "Column":
        return Column(
            jnp.take(self.data, idx, axis=0),
            None if self.valid is None else jnp.take(self.valid, idx, axis=0),
        )


@jax.tree_util.register_pytree_node_class
@dataclass
class StreamChunk:
    """ops + columns + visibility. A DataChunk is a StreamChunk with all-INSERT
    ops (the reference keeps two types; one suffices here — batch executors
    simply ignore `ops`)."""

    columns: tuple[Column, ...]
    ops: jnp.ndarray       # int8 [CAP]
    vis: jnp.ndarray       # bool [CAP]
    schema: Schema         # static aux

    def tree_flatten(self):
        return (self.columns, self.ops, self.vis), self.schema

    @classmethod
    def tree_unflatten(cls, schema, children):
        columns, ops, vis = children
        return cls(tuple(columns), ops, vis, schema)

    # -- shape ---------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.ops.shape[0]

    def cardinality(self) -> jnp.ndarray:
        """Number of visible rows (device scalar)."""
        return jnp.sum(self.vis.astype(jnp.int32))

    def num_rows_host(self) -> int:
        return int(np.asarray(self.cardinality()))

    # -- transforms ----------------------------------------------------
    def with_vis(self, vis: jnp.ndarray) -> "StreamChunk":
        return StreamChunk(self.columns, self.ops, vis, self.schema)

    def mask(self, keep: jnp.ndarray) -> "StreamChunk":
        return self.with_vis(self.vis & keep)

    def project(self, indices: Sequence[int]) -> "StreamChunk":
        return StreamChunk(
            tuple(self.columns[i] for i in indices),
            self.ops, self.vis, self.schema.select(indices),
        )

    def take(self, idx: jnp.ndarray, vis: jnp.ndarray) -> "StreamChunk":
        """Row gather (used by compaction / dispatch routing)."""
        return StreamChunk(
            tuple(c.take(idx) for c in self.columns),
            jnp.take(self.ops, idx, axis=0), vis, self.schema,
        )

    def compact(self) -> "StreamChunk":
        """Move visible rows to the front (stable). Keeps capacity."""
        cap = self.capacity
        order = jnp.argsort(~self.vis, stable=True)
        n = self.cardinality()
        new_vis = jnp.arange(cap) < n
        return self.take(order, new_vis)

    # -- host I/O ------------------------------------------------------
    @staticmethod
    def from_numpy(
        schema: Schema,
        arrays: Sequence[np.ndarray],
        ops: Optional[np.ndarray] = None,
        capacity: Optional[int] = None,
        valids: Optional[Sequence[Optional[np.ndarray]]] = None,
    ) -> "StreamChunk":
        n = len(arrays[0]) if arrays else 0
        cap = capacity or max(DEFAULT_CHUNK_CAPACITY, n)
        assert n <= cap, f"{n} rows > capacity {cap}"
        cols = []
        for i, (arr, f) in enumerate(zip(arrays, schema)):
            arr = np.asarray(arr, dtype=f.data_type.np_dtype)
            pad = np.zeros(cap, dtype=f.data_type.np_dtype)
            pad[:n] = arr
            valid = None
            if valids is not None and valids[i] is not None:
                v = np.zeros(cap, dtype=bool)
                v[:n] = valids[i]
                valid = jnp.asarray(v)
            cols.append(Column(jnp.asarray(pad), valid))
        ops_arr = np.zeros(cap, dtype=np.int8)
        if ops is not None:
            ops_arr[:n] = np.asarray(ops, dtype=np.int8)
        vis = np.zeros(cap, dtype=bool)
        vis[:n] = True
        return StreamChunk(tuple(cols), jnp.asarray(ops_arr), jnp.asarray(vis), schema)

    def to_numpy(self) -> tuple[list[np.ndarray], np.ndarray]:
        """Visible rows only -> (columns, ops). Device->host sync."""
        vis = np.asarray(self.vis)
        cols = [np.asarray(c.data)[vis] for c in self.columns]
        ops = np.asarray(self.ops)[vis]
        return cols, ops

    def to_host(self) -> "HostChunk":
        """Every lane of the chunk on the host, at full capacity. A pure
        wait (`np.asarray` of concrete arrays; nothing is dispatched), so
        it may run on a worker thread (`utils/d2h.py` `fetch_chunk`)."""
        return HostChunk(
            np.asarray(self.ops), np.asarray(self.vis),
            [np.asarray(c.data) for c in self.columns],
            [None if c.valid is None else np.asarray(c.valid)
             for c in self.columns])

    def to_rows(self) -> list[tuple]:
        """Visible rows as python tuples (op, values...), NULL lanes as
        None. For materialize/sinks/tests — NULL-ness must survive the
        host boundary or outer-join padding rows materialize as zeros."""
        return self.to_host().rows()


@dataclass(frozen=True)
class HostChunk:
    """A chunk's lanes as numpy arrays (`StreamChunk.to_host`): `valids[j]`
    is None where column j has no NULLs."""

    ops: np.ndarray
    vis: np.ndarray
    cols: list
    valids: list

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in (self.ops, self.vis, *self.cols,
                                      *self.valids) if a is not None)

    def rows(self) -> list[tuple]:
        """The visible rows as `(op, values)`, NULL lanes as None."""
        idx = np.flatnonzero(self.vis)
        lanes = []
        for c, v in zip(self.cols, self.valids):
            lane = c[idx].tolist()
            if v is not None:
                lane = [x if ok else None
                        for x, ok in zip(lane, v[idx].tolist())]
            lanes.append(lane)
        vals = zip(*lanes) if lanes else ((),) * len(idx)
        return list(zip(self.ops[idx].tolist(), vals))


def empty_chunk(schema: Schema, capacity: int = DEFAULT_CHUNK_CAPACITY) -> StreamChunk:
    return StreamChunk.from_numpy(schema, [np.zeros(0, f.data_type.np_dtype) for f in schema], capacity=capacity)


# ------------------------------------------------------------- coalescing

def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _pad_chunk_impl(chunk: StreamChunk, out_capacity: int) -> StreamChunk:
    """Grow a chunk to `out_capacity` with invisible rows (row order and
    update-pair adjacency preserved — padding is strictly at the tail)."""
    pad = out_capacity - chunk.capacity

    def ext(x):
        return jnp.concatenate([x, jnp.zeros(pad, dtype=x.dtype)])

    cols = tuple(
        Column(ext(c.data), None if c.valid is None else ext(c.valid))
        for c in chunk.columns)
    return StreamChunk(cols, ext(chunk.ops), ext(chunk.vis), chunk.schema)


def _concat2_impl(a: StreamChunk, b: StreamChunk) -> StreamChunk:
    """Concatenate two equal-schema chunks (a's rows first)."""
    def cat(x, y):
        return jnp.concatenate([x, y])

    def cat_valid(ca: Column, cb: Column):
        if ca.valid is None and cb.valid is None:
            return None
        va = ca.valid if ca.valid is not None else \
            jnp.ones(ca.capacity, dtype=bool)
        vb = cb.valid if cb.valid is not None else \
            jnp.ones(cb.capacity, dtype=bool)
        return cat(va, vb)

    cols = tuple(Column(cat(ca.data, cb.data), cat_valid(ca, cb))
                 for ca, cb in zip(a.columns, b.columns))
    return StreamChunk(cols, cat(a.ops, b.ops), cat(a.vis, b.vis), a.schema)


# Shared pack programs (lazy: jit_state imports jax utils; chunk.py is
# imported by host-only code paths too). Capacities are bucketed to powers
# of two, so the static-shape set is {pad: (2^i -> 2^j), concat: (2^j,
# 2^j)} — O(log^2 max_capacity) programs TOTAL across all coalescers, and
# zero recompiles once a pipeline's buckets are warm. The inputs are NOT
# donated: dispatchers fan chunks out zero-copy (same arrays, different
# visibility), so a pack input may be aliased by a sibling consumer.
_PACK_PROGRAMS: dict = {}


def _pack_programs():
    if not _PACK_PROGRAMS:
        from ..ops.jit_state import jit_state
        _PACK_PROGRAMS["pad"] = jit_state(
            _pad_chunk_impl, static_argnums=(1,), name="chunk_pad")
        _PACK_PROGRAMS["concat2"] = jit_state(
            _concat2_impl, name="chunk_concat2")
    return _PACK_PROGRAMS


class ChunkCoalescer:
    """Packs consecutive small chunks between barriers into fewer, fuller
    chunks — the host-loop half of making per-barrier-interval device work
    O(1) dispatches.

    Every chunk an executor sees costs one device dispatch per jitted step
    regardless of how few visible rows it carries; sources and exchanges
    frequently emit runs of small chunks inside one barrier interval.  The
    coalescer buffers a run (receiver side, after the channel — it never
    interacts with backpressure), then folds it pairwise into one chunk
    whose capacity is the power-of-two bucket of the run's total capacity.
    Row order is preserved (stable tail-concat), so changelog update pairs
    stay adjacent; visibility masks carry over untouched.

    The pack programs compile once per (capacity-bucket) pair and are
    shared process-wide, so coalescing adds ZERO steady-state recompiles
    while removing k-1 downstream dispatches per k-chunk run — per
    stateful executor in the chain below.

    Protocol: `push(chunk)` returns chunks ready to emit now (a full run,
    or a passthrough); `flush()` drains the pending run — callers MUST
    flush before forwarding a barrier or watermark so cross-message
    ordering is exactly the uncoalesced stream's.
    """

    def __init__(self, max_capacity: int = 4 * DEFAULT_CHUNK_CAPACITY):
        self.max_capacity = max(1, int(max_capacity))
        self._pending: list[StreamChunk] = []
        self._pending_cap = 0
        self.packed = 0          # chunks absorbed into a merge
        self.emitted = 0         # chunks emitted (after packing)

    def push(self, chunk: StreamChunk) -> list[StreamChunk]:
        out: list[StreamChunk] = []
        cap = chunk.capacity
        if cap >= self.max_capacity:
            # too big to pack with anything: drain, then pass through
            out.extend(self.flush())
            out.append(chunk)
            self.emitted += 1
            return out
        if self._pending:
            head = self._pending[0]
            schema_differs = (head.schema is not chunk.schema
                              and head.schema != chunk.schema)
            if (self._pending_cap + cap > self.max_capacity
                    or schema_differs):
                out.extend(self.flush())
        self._pending.append(chunk)
        self._pending_cap += cap
        return out

    def flush(self) -> list[StreamChunk]:
        if not self._pending:
            return []
        run, self._pending, self._pending_cap = self._pending, [], 0
        if len(run) == 1:
            self.emitted += 1
            return run
        progs = _pack_programs()
        merged = run[0]
        for nxt in run[1:]:
            # equalize to the larger power-of-two bucket, then concat —
            # keeps every program signature inside the bucketed set
            target = _next_pow2(max(merged.capacity, nxt.capacity))
            if merged.capacity < target:
                merged = progs["pad"](merged, target)
            if nxt.capacity < target:
                nxt = progs["pad"](nxt, target)
            merged = progs["concat2"](merged, nxt)
        self.packed += len(run)
        self.emitted += 1
        return [merged]


class StreamChunkBuilder:
    """Host-side row accumulator emitting fixed-capacity chunks
    (reference: StreamChunkBuilder, array/stream_chunk_builder.rs).
    Update pairs are kept within a single chunk."""

    def __init__(self, schema: Schema, capacity: int = DEFAULT_CHUNK_CAPACITY):
        self.schema = schema
        self.capacity = capacity
        self._rows: list[tuple[int, tuple]] = []

    def __len__(self):
        return len(self._rows)

    def append_row(self, op: int, values: tuple) -> Optional[StreamChunk]:
        self._rows.append((op, values))
        if len(self._rows) >= self.capacity:
            # Never split an UpdateDelete/UpdateInsert pair across chunks —
            # downstream op-fixup kernels rely on pair adjacency within one
            # chunk (the reference builder reserves a slot the same way).
            held = None
            if len(self._rows) > 1 and self._rows[-1][0] == OP_UPDATE_DELETE:
                held = self._rows.pop()
            chunk = self.take()
            if held is not None:
                self._rows.append(held)
            return chunk
        return None

    def take(self) -> Optional[StreamChunk]:
        if not self._rows:
            return None
        ops = np.asarray([r[0] for r in self._rows], dtype=np.int8)
        arrays = []
        for i, f in enumerate(self.schema):
            arrays.append(np.asarray([r[1][i] for r in self._rows], dtype=f.data_type.np_dtype))
        self._rows = []
        return StreamChunk.from_numpy(self.schema, arrays, ops=ops, capacity=self.capacity)
