"""Packed device→host fetches.

A blocking fetch has a fixed per-call cost and serialises with
dispatch, so every persist path ships its whole payload in a fixed,
small number of calls: one for the host-needed counts, then one packed
payload — an int64 buffer holding every integer/bool/f32 column
(narrower ints widened, f32 as its bits) plus, only when the payload
has f64 columns, one float64 buffer. These helpers keep the pack/unpack
rule in one place.

The rule of the barrier path: the event-loop thread never WAITS for the
device, and no other thread DISPATCHES to it. An actor at its barrier
dispatches its packs on the loop and awaits each pure wait
(`fetch_small`, `fetch_flat`) through `off_loop`, on a worker thread;
the checkpoint uploader runs pure waits and host-only continuations.
`d2h_wait_on_loop_seconds_total` is the time a fetch held the loop all
the same (recovery, the memory manager's eviction and reload).
"""

from __future__ import annotations

import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np

from .trace import TraceAnnotation, current_scope


def pack_for_fetch(arrays):
    """1-D device arrays (host-known lengths) -> (flat, metas).

    `flat` is the pair (int64 buffer, float64 buffer or None): integer,
    bool and f32 columns (f32 as its int32 bits — exact) concatenate
    into the int64 buffer, f64 columns travel as their OWN dtype
    segment. The TPU compiler has no `bitcast-convert` from f64 (the
    chip holds an f64 as two f32s, common/floatbits.py), so an f64 is
    never reinterpreted on the device; the fetched values are whatever
    the backend stores, bit for bit. Fetch with ONE `fetch_flat`, then
    `unpack_fetched`."""
    ints, f64s, metas = [], [], []
    for a in arrays:
        dt = np.dtype(a.dtype)
        if dt == np.float64:
            f64s.append(a)
        elif dt == np.float32:
            ints.append(jax.lax.bitcast_convert_type(
                a, jnp.int32).astype(jnp.int64))
        else:
            ints.append(a.astype(jnp.int64))
        metas.append((int(a.shape[0]), dt))
    flat_i = (jnp.concatenate(ints) if ints
              else jnp.zeros(0, dtype=jnp.int64))
    flat_f = jnp.concatenate(f64s) if f64s else None
    return (flat_i, flat_f), metas


def unpack_fetched(flat, metas) -> list[np.ndarray]:
    flat_i, flat_f = flat
    out, off_i, off_f = [], 0, 0
    for n, dt in metas:
        if dt == np.float64:
            out.append(flat_f[off_f:off_f + n])
            off_f += n
            continue
        seg = flat_i[off_i:off_i + n]
        off_i += n
        if dt == np.float32:
            out.append(seg.astype(np.int32).view(np.float32))
        elif dt == np.int64:
            out.append(seg)
        else:
            out.append(seg.astype(dt))
    return out


def fetch_flat(flat):
    """Blocking d2h of an already-packed `flat` pair — a PURE WAIT
    (`np.asarray` on concrete arrays; no op dispatch), so it and
    `fetch_small` are the d2h primitives safe to run on a worker thread
    (`off_loop`) while the event-loop thread keeps dispatching.
    Dispatching eager jax ops from two threads concurrently deadlocks
    (observed: a background slice gather vs. the loop blocked in
    `_value`); every awaited or deferred wait must therefore bottom out
    here or in `fetch_small`."""
    from .metrics import D2H_BYTES, D2H_FETCHES
    host = _in_wait_span(
        lambda: tuple(None if f is None else np.asarray(f) for f in flat),
        lambda host: sum(h.nbytes for h in host if h is not None))
    for h in host:
        if h is not None:
            D2H_FETCHES.inc()
            D2H_BYTES.inc(h.nbytes)
    return host


def fetch_small(dev) -> np.ndarray:
    """Blocking d2h of one small device array (an executor's barrier
    watchdog counters, a flush's counts): `np.asarray` inside a `d2h_wait`
    span. The wait is for the device to REACH the program that packed it,
    not for the few bytes. Not counted in d2h_bytes_total, which is the
    persist payloads'."""
    return _in_wait_span(lambda: np.asarray(dev), lambda host: host.nbytes)


def fetch_chunk(chunk):
    """Blocking d2h of a whole chunk as it stands (`StreamChunk.to_host`:
    every lane at full capacity, nothing packed, so nothing dispatched): a
    pure wait like `fetch_small`, for the terminal executor that takes its
    chunk to the host anyway. Not counted in d2h_bytes_total either."""
    return _in_wait_span(chunk.to_host, lambda host: host.nbytes)


async def off_loop(fetch, dev):
    """`fetch(dev)` — `fetch_small`, `fetch_flat` or `fetch_chunk`, a pure wait — on a
    worker thread, awaited: the caller's task is parked, the event loop
    runs the other actors and the uploader's continuations meanwhile.
    `dev` is dispatched by the caller, on the loop, before this is called.
    `asyncio.to_thread` copies the context, so the `d2h_wait` span still
    lands under the span in force (the actor's poll). A task cancelled
    here unwinds at once; the thread finishes its wait on its own and the
    result is dropped."""
    return await asyncio.to_thread(fetch, dev)


def _in_wait_span(fetch, nbytes):
    """`fetch()` as a `d2h_wait` span under the span in force — the actor's
    poll or a flush stage, on a worker thread either way (asyncio.to_thread
    copies the context) — with `nbytes(host)` as its count. A fetch made ON
    the event-loop thread holds every actor and the uploader for as long
    as it waits: its seconds go to `d2h_wait_on_loop_seconds_total`."""
    sc = current_scope()
    on_loop = asyncio._get_running_loop() is not None
    if sc is None and not on_loop:
        return fetch()
    t0 = time.monotonic_ns()
    with TraceAnnotation("rw:d2h_wait"):
        host = fetch()
    t1 = time.monotonic_ns()
    if on_loop:
        from .metrics import D2H_WAIT_ON_LOOP_SECONDS
        D2H_WAIT_ON_LOOP_SECONDS.inc((t1 - t0) / 1e9)
    if sc is not None:
        sc.wait(t0, t1, nbytes(host))
    return host


def fetch_columns(arrays) -> list[np.ndarray]:
    """Pack + single fetch + unpack."""
    flat, metas = pack_for_fetch(arrays)
    return unpack_fetched(fetch_flat(flat), metas)


# The narrowest prefix a group is sliced to. Below it every count has ONE
# bucket, 0 included: a payload's eager slice / concatenate programs are
# keyed by the bucket of each of its groups, and small counts (a join side
# that re-states five windows' maxima, then six, then deletes none) walked
# through 0, 1, 8, 16 beside a large neighbour, each new combination a
# compile inside a measured window (17 a window in NEXMark q5 as published,
# 0.2 s each on a chip whose cache had not met them). 64 rows of a payload
# are a few KB.
_MIN_BUCKET = 64


def _bucket(n: int, cap: int) -> int:
    return min(max(1 << max(n - 1, 0).bit_length(), _MIN_BUCKET), cap)


def prepare_prefix_groups(groups):
    """Dispatch-only half of fetch_prefix_groups: slice each group's
    arrays to the pow2 bucket of its host-known prefix length and pack
    everything into ONE packed `flat` payload (pack_for_fetch). Returns
    (flat, metas, group_meta) for `finish_prefix_groups`. MUST run on
    the event-loop thread, by the actor at its barrier: it dispatches
    device ops (see fetch_flat), and a pack the uploader enqueued would
    sit in the device's queue behind the next interval's programs.

    A group is `(arrays, n)` or `(arrays, n, bucket_n)`: sliced to the
    bucket of `bucket_n >= n`, trimmed to `n` on the host. The sharded
    executors give the shards of one payload the SAME `bucket_n` (the
    largest shard's count): per-shard counts sit around total / shards,
    which for a pow2 chunk is itself a bucket edge, so per-group buckets
    flip shard by shard and every new combination of lengths is a new
    eager concatenate program (found on four real chips, PR 26: 6 s of
    compile inside a 48 s window)."""
    sliced, meta = [], []
    for arrays, n, *bucket_n in groups:
        cap = int(arrays[0].shape[0]) if arrays else 0
        b = _bucket(int(bucket_n[0] if bucket_n else n), cap)
        for a in arrays:
            sliced.append(a[:b])
        meta.append((len(arrays), int(n)))
    flat, metas = pack_for_fetch(sliced)
    return flat, metas, meta


def finish_prefix_groups(host_flat, metas, group_meta) -> list:
    """Host-only half: unpack the fetched flat buffer and trim each
    group to its exact prefix length. No device work — safe anywhere."""
    host = unpack_fetched(host_flat, metas)
    out, i = [], 0
    for cnt, n in group_meta:
        out.append([h[:n] for h in host[i:i + cnt]])
        i += cnt
    return out


def fetch_prefix_groups(groups) -> list:
    """groups: [(full_arrays, n_prefix)] -> list of lists of np arrays
    trimmed to n_prefix, via ONE packed fetch. Slice lengths bucket to
    powers of two so the eager slice/concat SHAPES repeat across
    barriers — every fresh shape signature costs a compile, which exact
    per-epoch lengths would pay at every single barrier."""
    flat, metas, meta = prepare_prefix_groups(groups)
    return finish_prefix_groups(fetch_flat(flat), metas, meta)


async def defer_prefix_flush(store, epoch: int, table_id, counts_dev,
                             plan) -> None:
    """The barrier's half of a checkpoint's deferred flush, run by the
    ACTOR before its barrier leaves the executor: the device counts are
    awaited (`counts_dev`: one small array the caller dispatched, or None
    where the host knows them), `plan(counts)` turns them into
    `(groups, write)`, the groups' prefixes are packed — enqueued here,
    ahead of the next interval's programs — and what is left goes to the
    store as ONE stage: a pure `fetch_flat` of that pack and the host-only
    `write(fetched groups)`, which must end in the tables' commit.
    Dispatch everything else the barrier dispatches BEFORE this is
    awaited: the device then has work while the counts travel."""
    counts = (None if counts_dev is None
              else await off_loop(fetch_small, counts_dev))
    groups, write = plan(counts)
    prep = prepare_prefix_groups(groups) if groups else None

    def wait():
        return None if prep is None else fetch_flat(prep[0])

    def cont(host_flat):
        write([] if prep is None
              else finish_prefix_groups(host_flat, prep[1], prep[2]))

    await store.defer_flush(epoch, wait, cont, table_id=table_id)
