"""In-mesh shuffle: HashDispatcher + Merge as one XLA all_to_all.

Reference: the hash exchange (src/stream/src/executor/dispatch.rs:679 routes
rows by vnode to downstream actors over channels/gRPC; merge.rs:109 fans in).
Inside a TPU mesh that whole path collapses to a single collective: each
shard buckets its local rows by destination shard (vnode routing table),
then `lax.all_to_all` swaps buckets over ICI. No host hop, no serialization,
no per-row control flow — the shuffle is one fused device op per chunk.

All functions here run INSIDE shard_map (they use axis collectives); shapes
are per-shard. Rows are (columns..., vis) with fixed capacity; destination
overflow beyond `cap_out` rows per (src,dst) pair is counted and surfaced so
callers size capacities (the host pipeline applies backpressure long before
overflow in practice — chunk capacity bounds per-dest rows).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from ..common.chunk import Column, StreamChunk
from ..common.vnode import compute_vnodes


def shuffle_cap_out(local_rows: int, n_shards: int, slack: int = 0) -> int:
    """Per-(src, dst) send capacity for `shuffle_rows`.

    slack = 0 (the default) is ZERO-DROP sizing: a source shard holds at
    most `local_rows` rows, so `cap_out = local_rows` can never overflow
    regardless of key skew (a chunk whose rows all share one hot vnode —
    e.g. a tumble-window group key inside one barrier interval — routes
    everything to a single shard). The receive buffer is then
    n_shards * local_rows = the global chunk capacity: each shard's apply
    is as wide as the whole chunk, the data moves over ICI, not the host.

    slack = k > 0 sizes for BALANCED routing with k× headroom:
    cap_out = k * ceil(local_rows / n_shards), so each shard's receive
    buffer shrinks to ~k/n_shards of the chunk — the near-linear-compute
    regime for well-distributed keys (q5's (auction, window) groups).
    Overflow is counted on device and FAIL-STOPS the epoch at the next
    barrier watchdog fetch (mesh_shuffle_dropped_rows_total), so an
    undersized slack surfaces loudly instead of dropping rows."""
    if slack <= 0:
        return local_rows
    per_pair = -(-local_rows // n_shards)
    return min(local_rows, max(64, slack * per_pair))


def bucket_by_dest(columns: Sequence[jnp.ndarray], vis: jnp.ndarray,
                   dest: jnp.ndarray, n_dest: int, cap_out: int):
    """Scatter local rows into per-destination send buffers.

    columns: [N] arrays; vis: bool [N]; dest: int32 [N] in [0, n_dest).
    Returns (send_cols: list of [n_dest, cap_out], send_vis: [n_dest, cap_out],
    n_dropped: int32 scalar, max_fill: int32 scalar — the largest
    per-destination demand BEFORE capping, for adaptive bucket sizing).
    """
    onehot = (dest[:, None] == jnp.arange(n_dest, dtype=dest.dtype)[None, :]) & vis[:, None]
    pos = (jnp.cumsum(onehot, axis=0) - onehot).astype(jnp.int32)  # rank within dest
    pos_of_row = jnp.sum(pos * onehot, axis=1)
    ok = vis & (pos_of_row < cap_out)
    n_dropped = jnp.sum(vis & ~ok, dtype=jnp.int32)
    # demand (pre-cap) per destination bucket — the adaptive slack
    # signal: the largest send bucket this shard WANTED this chunk
    max_fill = jnp.max(jnp.sum(onehot, axis=0, dtype=jnp.int32))
    flat = jnp.where(ok, dest * cap_out + pos_of_row, n_dest * cap_out)
    send_cols = []
    for col in columns:
        buf = jnp.zeros(n_dest * cap_out + 1, dtype=col.dtype)
        send_cols.append(buf.at[flat].set(col, mode="drop")[:-1].reshape(n_dest, cap_out))
    vbuf = jnp.zeros(n_dest * cap_out + 1, dtype=bool)
    send_vis = vbuf.at[flat].set(ok, mode="drop")[:-1].reshape(n_dest, cap_out)
    return send_cols, send_vis, n_dropped, max_fill


def shuffle_rows(columns: Sequence[jnp.ndarray], vis: jnp.ndarray,
                 dest: jnp.ndarray, axis_name: str, n_shards: int,
                 cap_out: int):
    """Route rows to their destination shard (call inside shard_map).

    Returns (recv_cols: list of [n_shards*cap_out], recv_vis, n_dropped,
    max_fill): the rows this shard owns, gathered from every source shard.
    """
    send_cols, send_vis, n_dropped, max_fill = bucket_by_dest(
        columns, vis, dest, n_shards, cap_out)
    recv_cols = [
        jax.lax.all_to_all(c, axis_name, split_axis=0, concat_axis=0,
                           tiled=True).reshape(n_shards * cap_out)
        for c in send_cols
    ]
    recv_vis = jax.lax.all_to_all(send_vis, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True).reshape(n_shards * cap_out)
    return recv_cols, recv_vis, n_dropped, max_fill


def shuffle_by_vnode(columns: Sequence[jnp.ndarray], vis: jnp.ndarray,
                     key_columns: Sequence[jnp.ndarray],
                     vnode_to_shard_table: jnp.ndarray,
                     axis_name: str, n_shards: int, cap_out: int):
    """The full HashDispatcher semantics: vnode = crc32(dist_key) % 256
    (vnode.rs:126), shard = routing_table[vnode], then all_to_all."""
    vnodes = compute_vnodes(key_columns)
    dest = jnp.take(vnode_to_shard_table, vnodes)
    return shuffle_rows(columns, vis, dest, axis_name, n_shards, cap_out)


def shuffle_bytes(chunk: StreamChunk, key_indices, n_shards: int,
                  cap_out: int) -> int:
    """Bytes the all_to_all buffers of `mesh_ingest_chunk` hold for one
    chunk, over all shards: n_shards^2 x cap_out rows of ops, every
    column (data + validity) and visibility. Shapes and dtypes only, so
    it can be asked while the program traces."""
    if key_indices is None:
        return 0
    row = chunk.ops.dtype.itemsize + chunk.vis.dtype.itemsize + sum(
        c.data.dtype.itemsize
        + (c.valid.dtype.itemsize if c.valid is not None else 0)
        for c in chunk.columns)
    return n_shards * n_shards * cap_out * row


def mesh_ingest_chunk(chunk: StreamChunk, key_indices, vnode_to_shard_table,
                      axis_name: str, n_shards: int, cap_out: int):
    """The fused exchange ingest (call INSIDE shard_map): this shard's
    LOCAL row slice of a chunk is routed to the shards owning each row's
    vnode — ops, every column (data + validity) and visibility ride one
    all_to_all. Returns (local_chunk, n_dropped, max_fill) where
    `local_chunk` has capacity n_shards * cap_out and holds exactly the
    rows this shard owns, in source-shard-major order. Because the host
    chunk is sliced CONTIGUOUSLY over the mesh axis, source-shard-major
    order IS the original chunk order restricted to the owned rows, so
    per-shard executor semantics (pk-run netting, extrema updates) are the
    unsharded executor's.

    key_indices=None is the mesh-to-mesh NoShuffle leg: the upstream
    shards already own their rows under the downstream distribution, so
    the local slice passes through untouched — ZERO transfer, no
    collective, n_dropped == 0, max_fill = this shard's visible rows."""
    if key_indices is None:
        zero = jnp.zeros((), dtype=jnp.int32)
        occ = jnp.sum(chunk.vis, dtype=jnp.int32)
        return chunk, zero, occ
    payload = [chunk.ops]
    for c in chunk.columns:
        payload.append(c.data)
        if c.valid is not None:
            payload.append(c.valid)
    key_cols = [chunk.columns[i].data for i in key_indices]
    recv, recv_vis, n_dropped, max_fill = shuffle_by_vnode(
        payload, chunk.vis, key_cols, vnode_to_shard_table, axis_name,
        n_shards, cap_out)
    it = iter(recv)
    ops = next(it)
    cols = []
    for c in chunk.columns:
        data = next(it)
        valid = next(it) if c.valid is not None else None
        cols.append(Column(data, valid))
    return StreamChunk(tuple(cols), ops, recv_vis, chunk.schema), n_dropped, max_fill
