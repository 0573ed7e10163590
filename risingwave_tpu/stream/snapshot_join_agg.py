"""Barrier snapshot-recompute for "join against your own aggregate".

Reference shape (TPC-H q17, /root/reference/e2e_test/tpch/):

    SELECT sum(L.x) / 7.0
    FROM L JOIN P ON P.k = L.fk
           JOIN (SELECT fk, 0.2*avg(q) AS thr FROM L GROUP BY fk) A
             ON A.fk = L.fk AND L.q < A.thr
    WHERE <filters on P>

The changelog plan for this is a RETRACTION STORM: every L row shifts
its group's aggregate, so the agg subquery updates its row, the join
re-emits EVERY stored L row of that group, and the final agg retracts
and re-adds them all — per chunk. The reference pays the same storm
through its hash-join cache (hash_join.rs): this is inherent to
changelog propagation, not an implementation defect.

TPU re-design: don't propagate the storm — re-evaluate. All inputs of
the sub-plan are APPEND-ONLY, so the whole sub-plan is a pure function
of the accumulated input prefixes. The executor accumulates inputs in
dense device stores and, at each barrier, ONE jitted program sorts the
fact rows by key (the rows ride the sort as its payload) and recomputes
per-group aggregates (segment reductions over the sorted runs), the
threshold predicate (a group's values reach its rows along the run: no
capacity-wide lane is moved by an index vector), dim-key membership (the
few dim keys searched IN the sorted fact keys, their runs marked in a
difference array and spread by a prefix sum), and the final global
aggregates (plain reductions) — then emits the one-row changelog diff
vs the previous barrier. Zero per-chunk output work, no match buffers,
no storms. This is the snapshot-diff pattern the retractable TopN /
OverWindow / DynamicFilter executors already use, generalized to the
join-against-own-aggregate sub-plan (VERDICT r4 next-round #1).

Durability: append-only stores persist as append-only row logs
((_pos, row) per StateTable) written at each barrier; recovery reloads
the logs and re-runs the snapshot program once to restore the
last-emitted output (the same trick sorted_join.py uses to rebuild
degrees: recompute beats persisting derived state).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import (
    Column, StreamChunk, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT,
    op_sign,
)
from ..common.types import Field, Schema
from ..expr.agg import AggCall, AggKind
from ..ops.jit_state import jit_state
from ..ops.monotone_move import _shifted, compact, expand
from ..utils.d2h import (
    _bucket, fetch_flat, fetch_small, off_loop, pack_for_fetch,
    unpack_fetched)
from .align import LEFT, RIGHT, barrier_align
from .executor import Executor
from .message import Barrier, BarrierKind, Watermark

_I64_MAX = jnp.iinfo(jnp.int64).max


def _valid_of(col: Column, cap: int) -> jnp.ndarray:
    if col.valid is None:
        return jnp.ones(cap, dtype=bool)
    return col.valid


def _reduce_all(spec, vals, signs) -> jnp.ndarray:
    """`spec.partial(vals, signs, <all zeros>, 1)` as a reduction: every
    row belongs to the one segment, so nothing is scattered (a TPU prices
    a scatter by its updates, whatever the target holds)."""
    k, dt = spec.call.kind, spec.state_dtype
    if k is AggKind.COUNT:
        st = jnp.sum(signs.astype(jnp.int64))
    elif k is AggKind.SUM:
        st = jnp.sum(vals.astype(dt) * signs.astype(dt))
    elif k in (AggKind.MIN, AggKind.MAX):
        v = jnp.where(signs > 0, vals.astype(dt), spec.init)
        st = jnp.min(v) if k is AggKind.MIN else jnp.max(v)
    else:
        raise NotImplementedError(k)
    return st[None]


@jax.jit
def _fill_forward(have: jnp.ndarray, lanes: list) -> list:
    """Every position takes each lane's value at the nearest position at or
    before it that `have`s one (position 0 does), by log-step doubling: a
    stage reads 2^k behind and selects. A position still without a value
    after the stages below 2^k has no holder within 2^k - 1 behind it, so
    the holder that feeds the position 2^k behind is its nearest too."""
    C = have.shape[0]
    steps = tuple(1 << b for b in range((C - 1).bit_length()))
    ks = jnp.asarray(steps, jnp.int32)

    def stage(i, carry):
        have, lanes = carry
        behind = lambda x: _shifted(x, ks[i], +1, jnp.zeros((), x.dtype))
        take = ~have & behind(have)
        return have | take, [jnp.where(take, behind(x), x) for x in lanes]

    if steps:
        _, lanes = jax.lax.fori_loop(0, len(steps), stage, (have, lanes))
    return lanes


def _spread_over_runs(newrun: jnp.ndarray, lanes: list) -> list:
    """Lanes in GROUP space (slot g = the g-th run of the sorted rows) ->
    row space (every row of run g reads slot g): `lane[cumsum(newrun) - 1]`
    with no index vector. The rows are sorted, so a group is a run: one
    monotone move puts slot g on its run's first row (run g starts at or
    after g, and no nearer to it than run g - 1 to g - 1), a forward fill
    copies it along the run."""
    C = newrun.shape[0]
    if not lanes:
        return []
    pos = jnp.arange(C, dtype=jnp.int32)
    start, = compact(newrun, [pos], [0])
    n_groups = jnp.sum(newrun, dtype=jnp.int32)
    heads, _ = expand(pos < n_groups, start - pos, C - 1, lanes,
                      [0] * len(lanes))
    return _fill_forward(newrun, heads)


class SnapshotJoinAggExecutor(Executor):
    """Fused (L ⋈ dim ⋈ group-agg(L)) → global agg, evaluated by
    snapshot recompute at barriers.

    fact (LEFT input): the append-only L stream; all rows accumulate.
    dim (RIGHT input): the append-only dimension stream; only its key
    column is stored (after `dim_filter`), and its key must be unique
    (enforced by the planner: it is the source's declared primary key),
    so membership is a mask — never a row multiplier.
    """

    def __init__(self, fact: Executor, dim: Executor, *,
                 fact_key: int,
                 dim_key: int,
                 sub_agg_calls: Sequence[AggCall],
                 sub_items: Sequence,          # Expr over [sub agg outputs]
                 residue,                      # Expr over [L cols ++ sub items]
                 final_agg_calls: Sequence[AggCall],
                 final_items: Sequence,        # Expr over [final agg outputs]
                 out_names: Sequence[str],
                 out_types: Sequence,
                 fact_filter=None,             # Expr over L cols (fact side only)
                 sub_filter=None,              # Expr over L cols (agg side only)
                 dim_filter=None,              # Expr over dim cols
                 capacity: int = 1 << 17,
                 dim_capacity: int = 1 << 14,
                 state_tables: Optional[tuple] = None,
                 watchdog_interval: Optional[int] = 1):
        self.inputs = (fact, dim)
        self.fact_key = fact_key
        self.dim_key = dim_key
        self.sub_agg_calls = tuple(sub_agg_calls)
        self.sub_specs = tuple(c.spec() for c in self.sub_agg_calls)
        self.sub_items = tuple(sub_items)
        self.residue = residue
        self.final_agg_calls = tuple(final_agg_calls)
        self.final_specs = tuple(c.spec() for c in self.final_agg_calls)
        self.final_items = tuple(final_items)
        self.fact_filter = fact_filter
        self.sub_filter = sub_filter
        self.dim_filter = dim_filter
        self.capacity = int(capacity)
        self.dim_capacity = int(dim_capacity)
        self.state_tables = tuple(state_tables) if state_tables \
            else (None, None)
        self._durable = any(st is not None for st in self.state_tables)
        if watchdog_interval not in (None, 1):
            raise ValueError(
                "watchdog_interval must be 1 (check before every "
                "checkpoint commit) or None (transfer-free mode)")
        self.watchdog_interval = watchdog_interval
        self.schema = Schema(tuple(
            Field(n, t) for n, t in zip(out_names, out_types)))
        if len(fact.schema) > 63:
            raise ValueError(
                "snapshot-join-agg fact schema exceeds the 63-column "
                "validity bitmask used for persistence")
        self.pk_indices = ()
        self.identity = "SnapshotJoinAgg"

        self._fact_schema = fact.schema
        self._init_stores()
        # previous emission (device): per-item value + validity, plus the
        # emitted flag — all stay on device so a barrier costs zero d2h
        # in watchdog-free mode
        self._prev = tuple(
            jnp.zeros((), dtype=t.jnp_dtype) for t in out_types)
        self._prev_valid = tuple(jnp.zeros((), dtype=bool)
                                 for _ in out_types)
        self._emitted = jnp.zeros((), dtype=bool)
        # errs[0] = fact overflow, errs[1] = dim overflow,
        # errs[2] = retraction seen on an append-only input
        self._errs = jnp.zeros(3, dtype=jnp.int32)
        # appends thread (store arrays, count, errs) — re-bound at the
        # call sites, aliased nowhere else: donate. _flush reads the
        # stores (NOT donated — they stay live) and consumes/replaces the
        # previous-emission triplet (args 5-7).
        self._append_fact = jit_state(self._append_fact_impl,
                                      donate_argnums=(0, 1, 2, 3),
                                      name="snapshot_join_agg_append_fact")
        self._append_dim = jit_state(self._append_dim_impl,
                                     donate_argnums=(0, 1, 2),
                                     name="snapshot_join_agg_append_dim")
        self._flush = jit_state(self._flush_impl, donate_argnums=(5, 6, 7),
                                name="snapshot_join_agg_flush")
        # the barrier's two fetches, each one program: the five counters,
        # then the rows appended since the last checkpoint
        self._counts = jit_state(
            lambda errs, fn, dn: jnp.concatenate(
                [errs, jnp.stack([fn, dn])]),
            name="snapshot_join_agg_counts")
        self._persist_pack = jit_state(
            self._persist_pack_impl, static_argnames=("wf", "wd"),
            name="snapshot_join_agg_persist_pack")
        self._dirty = False
        # host upper bounds for growth triggers (no d2h on the hot path)
        self._applied_rows_upper = 0
        self._applied_dim_upper = 0
        self._persist_cursor = [0, 0]
        # what the last counts fetch brought, for the actor's phase dict
        self._phase_counts: dict = {}

    # ------------------------------------------------------------- state
    def _init_stores(self):
        C, Cd = self.capacity, self.dim_capacity
        sch = self._fact_schema
        self._fcols = tuple(
            jnp.zeros(C, dtype=f.data_type.jnp_dtype) for f in sch)
        self._fvalids = tuple(jnp.zeros(C, dtype=bool) for _ in sch)
        self._fn = jnp.zeros((), dtype=jnp.int32)
        self._dkeys = jnp.zeros(Cd, dtype=jnp.int64)
        self._dn = jnp.zeros((), dtype=jnp.int32)

    def fence_tokens(self) -> list:
        toks = [self._fn, self._dn, self._emitted]
        for i in self.inputs:
            toks.extend(i.fence_tokens())
        return toks

    # ----------------------------------------------------------- appends
    def _append_fact_impl(self, fcols, fvalids, fn, errs, chunk):
        C = fcols[0].shape[0]
        take = chunk.vis & (op_sign(chunk.ops) > 0)
        retract = jnp.sum(
            (chunk.vis & (op_sign(chunk.ops) < 0)).astype(jnp.int32),
            dtype=jnp.int32)
        rank = jnp.cumsum(take.astype(jnp.int32)) - 1
        n_new = jnp.sum(take.astype(jnp.int32), dtype=jnp.int32)
        dest = jnp.where(take & (fn + rank < C), fn + rank, C)
        overflow = jnp.maximum(fn + n_new - C, 0)
        new_cols = tuple(
            c.at[dest].set(col.data, mode="drop")
            for c, col in zip(fcols, chunk.columns))
        new_valids = tuple(
            v.at[dest].set(_valid_of(col, chunk.capacity), mode="drop")
            for v, col in zip(fvalids, chunk.columns))
        new_n = jnp.minimum(fn + n_new, C).astype(jnp.int32)
        errs = errs.at[0].add(overflow.astype(jnp.int32))
        errs = errs.at[2].add(retract)
        return new_cols, new_valids, new_n, errs

    def _append_dim_impl(self, dkeys, dn, errs, chunk):
        Cd = dkeys.shape[0]
        take = chunk.vis & (op_sign(chunk.ops) > 0)
        retract = jnp.sum(
            (chunk.vis & (op_sign(chunk.ops) < 0)).astype(jnp.int32),
            dtype=jnp.int32)
        kcol = chunk.columns[self.dim_key]
        take &= _valid_of(kcol, chunk.capacity)
        if self.dim_filter is not None:
            p = self.dim_filter.eval(list(chunk.columns))
            take &= p.data.astype(bool) & _valid_of(p, chunk.capacity)
        rank = jnp.cumsum(take.astype(jnp.int32)) - 1
        n_new = jnp.sum(take.astype(jnp.int32), dtype=jnp.int32)
        dest = jnp.where(take & (dn + rank < Cd), dn + rank, Cd)
        overflow = jnp.maximum(dn + n_new - Cd, 0)
        new_keys = dkeys.at[dest].set(
            kcol.data.astype(jnp.int64), mode="drop")
        new_n = jnp.minimum(dn + n_new, Cd).astype(jnp.int32)
        errs = errs.at[1].add(overflow.astype(jnp.int32))
        errs = errs.at[2].add(retract)
        return new_keys, new_n, errs

    # ------------------------------------------------------------- flush
    def _flush_impl(self, fcols, fvalids, fn, dkeys, dn,
                    prev, prev_valid, emitted):
        C = fcols[0].shape[0]
        live = jnp.arange(C) < fn
        fk = fcols[self.fact_key].astype(jnp.int64)
        # a NULL join/group key never matches the dim or the A side
        # (SQL equi semantics): push those rows into the sentinel region
        # with the dead lanes so they join nothing and pollute no group
        skey = jnp.where(live & fvalids[self.fact_key], fk, _I64_MAX)
        # the rows ride the sort: ONE stable sort on the key whose payload is
        # the data lanes the program reads below and one word a row holding
        # `live` (bit 0) and every column's validity (bit k + 1). An index
        # gather of a capacity-wide lane costs a v5e 9-20 ns an element
        # whatever the indices are; a sort moves its payload at memory speed
        # (PERF.md section 6, PR 47). Stable, so the permutation (and with it the
        # order a FLOAT64 sum adds a run's rows in) is `argsort(skey)`'s.
        word = jnp.uint32 if len(fvalids) < 32 else jnp.uint64
        bits = live.astype(word)
        for k, v in enumerate(fvalids):
            bits |= v.astype(word) << (k + 1)
        # every column rides (the planner hands this executor only the
        # columns its plan reads) but an integer key: `sfk` is that column
        # wherever its row is live and its validity bit is set
        rides = [k for k, c in enumerate(fcols) if k != self.fact_key
                 or not jnp.issubdtype(c.dtype, jnp.integer)]
        sfk, bits_s, *rode = jax.lax.sort(
            (skey, bits, *(fcols[k] for k in rides)), num_keys=1,
            is_stable=True)
        live_s = (bits_s & 1) != 0
        valids_s = tuple((bits_s >> (k + 1)) & 1 != 0
                         for k in range(len(fvalids)))
        cols_s = [sfk.astype(fcols[self.fact_key].dtype)] * len(fcols)
        for k, lane in zip(rides, rode):
            cols_s[k] = lane
        newrun = jnp.concatenate(
            [jnp.ones(1, dtype=bool), sfk[1:] != sfk[:-1]])
        gid = (jnp.cumsum(newrun) - 1).astype(jnp.int32)
        env_fact = [Column(d, v) for d, v in zip(cols_s, valids_s)]

        sub_sign = live_s.astype(jnp.int32)
        if self.sub_filter is not None:
            p = self.sub_filter.eval(env_fact)
            sub_sign = jnp.where(
                p.data.astype(bool) & _valid_of(p, C), sub_sign, 0)
        sub_outs = []
        for call, spec in zip(self.sub_agg_calls, self.sub_specs):
            if call.arg is None:
                vals = jnp.zeros(C, dtype=spec.state_dtype)
                rs = sub_sign
            else:
                vals = cols_s[call.arg]
                rs = jnp.where(valids_s[call.arg], sub_sign, 0)
            st = spec.partial(vals, rs, gid, C)
            cnt = jax.ops.segment_sum(
                (rs != 0).astype(jnp.int32), gid, C)
            out_valid = (cnt > 0) if call.kind is not AggKind.COUNT \
                else jnp.ones(C, dtype=bool)
            sub_outs.append(Column(spec.emit(st), out_valid))
        # per-group item exprs, spread over their runs to the row level
        # (each row's lookup key IS the group key — the planner enforces
        # that the A-side equi column equals the GROUP BY column)
        gexists = None
        if self.sub_filter is not None:
            # a group whose rows ALL fail the subquery WHERE produces no
            # A row, so the inner join drops its fact rows (residue
            # validity covers sum/min/max/avg outputs, but count() is 0
            # and valid — existence must be checked explicitly)
            gexists = jax.ops.segment_sum(
                (sub_sign != 0).astype(jnp.int32), gid, C) > 0
        per_group, tree = jax.tree_util.tree_flatten(
            ([e.eval(sub_outs) for e in self.sub_items], gexists))
        row_sub, gexists = tree.unflatten(
            _spread_over_runs(newrun, per_group))

        if self.residue is not None:
            pred = self.residue.eval(env_fact + row_sub)
            keep = pred.data.astype(bool) & _valid_of(pred, C)
        else:
            keep = jnp.ones(C, dtype=bool)
        if self.fact_filter is not None:
            p = self.fact_filter.eval(env_fact)
            keep &= p.data.astype(bool) & _valid_of(p, C)

        # membership, the small side driving (a search costs by its
        # queries): the Cd dim keys are searched in the C sorted fact keys,
        # each live key's run [lo, hi) is marked in a difference array and
        # a prefix sum spreads the marks. Dead dim lanes mark nothing; the
        # sentinel region (dead lanes, NULL keys) is never a member.
        w = (jnp.arange(dkeys.shape[0]) < dn).astype(jnp.int32)
        lo = jnp.searchsorted(sfk, dkeys, side="left")
        hi = jnp.searchsorted(sfk, dkeys, side="right")
        marks = jnp.zeros(C, dtype=jnp.int32).at[
            jnp.concatenate([lo, hi])].add(
                jnp.concatenate([w, -w]), mode="drop")
        member = (jnp.cumsum(marks) > 0) & (sfk != _I64_MAX)
        if gexists is not None:
            member &= gexists

        msign = (live_s & keep & member).astype(jnp.int32)
        fin_outs = []
        for call, spec in zip(self.final_agg_calls, self.final_specs):
            if call.arg is None:
                vals = jnp.zeros(C, dtype=spec.state_dtype)
                rs = msign
            else:
                vals = cols_s[call.arg]
                rs = jnp.where(valids_s[call.arg], msign, 0)
            st = _reduce_all(spec, vals, rs)
            nz = jnp.sum((rs != 0).astype(jnp.int32))
            out_valid = jnp.ones(1, dtype=bool) \
                if call.kind is AggKind.COUNT else (nz > 0)[None]
            fin_outs.append(Column(spec.emit(st), out_valid))
        out_cols = [e.eval(fin_outs) for e in self.final_items]
        cur = tuple(c.data[0] for c in out_cols)
        cur_valid = tuple(_valid_of(c, 1)[0] for c in out_cols)

        same = jnp.ones((), dtype=bool)
        for a, b, av, bv in zip(prev, cur, prev_valid, cur_valid):
            same &= (av == bv) & ((a == b) | ~bv)
        changed = ~(emitted & same)
        # one chunk, capacity 2: [prev as U-, cur as U+/Insert]
        ops = jnp.where(
            emitted,
            jnp.asarray([OP_UPDATE_DELETE, OP_UPDATE_INSERT],
                        dtype=jnp.int8),
            jnp.asarray([OP_INSERT, OP_INSERT], dtype=jnp.int8))
        vis = jnp.stack([changed & emitted, changed])
        chunk_cols = tuple(
            Column(jnp.stack([p, c]), jnp.stack([pv, cv]))
            for p, c, pv, cv in zip(prev, cur, prev_valid, cur_valid))
        out = StreamChunk(chunk_cols, ops, vis, self.schema)
        return cur, cur_valid, jnp.ones((), dtype=bool), out

    # ------------------------------------------------------- housekeeping
    async def _fetch_counts(self) -> tuple:
        """The barrier's one wait for the device to reach the appends: the
        error counters and the two row counts in one awaited fetch. Raises
        what the watchdog raises; returns (fact rows, dim rows) and leaves
        them for the phase dict."""
        *errs, n, nd = (int(x) for x in await off_loop(
            fetch_small, self._counts(self._errs, self._fn, self._dn)))
        if errs[0]:
            raise RuntimeError(
                f"snapshot-join-agg fact store overflow ({errs[0]} rows "
                f"dropped; capacity {self.capacity})")
        if errs[1]:
            raise RuntimeError(
                f"snapshot-join-agg dim store overflow ({errs[1]} rows "
                f"dropped; capacity {self.dim_capacity})")
        if errs[2]:
            raise RuntimeError(
                "snapshot-join-agg saw retractions on an append-only "
                "input — the planner must not fuse retracting inputs")
        self._phase_counts = dict(snapshot_rows=n,
                                  snapshot_capacity=self.capacity,
                                  snapshot_dim_rows=nd)
        return n, nd

    def take_phase_counts(self) -> dict:
        """Rows the two stores hold and the fact store's capacity, as the
        barrier's counts fetch read them (absent where the interval made
        none), for the actor's phase dict."""
        counts, self._phase_counts = self._phase_counts, {}
        return counts

    def _maybe_grow(self, n: int, nd: int) -> None:
        """Double a store while its live count (from the counts fetch)
        crowds its capacity; the jitted programs re-trace at the new
        static shape."""
        grew = False
        while n > 0.7 * self.capacity:
            self.capacity *= 2
            grew = True
        if grew:
            C = self.capacity
            pad = lambda a: jnp.concatenate(
                [a, jnp.zeros(C - a.shape[0], dtype=a.dtype)])
            self._fcols = tuple(pad(c) for c in self._fcols)
            self._fvalids = tuple(pad(v) for v in self._fvalids)
        grew_d = False
        while nd > 0.7 * self.dim_capacity:
            self.dim_capacity *= 2
            grew_d = True
        if grew_d:
            Cd = self.dim_capacity
            self._dkeys = jnp.concatenate(
                [self._dkeys,
                 jnp.zeros(Cd - self._dkeys.shape[0], dtype=jnp.int64)])

    # ----------------------------------------------------------- persist
    def _persist_pack_impl(self, fcols, fvalids, dkeys, lo_f, lo_d, *,
                           wf: int, wd: int):
        """Rows [lo_f, lo_f + wf) of every fact column, their validity as
        one bitmask a row, and keys [lo_d, lo_d + wd) of the dim store, as
        the (int64, float64) pair `fetch_flat` takes; the host trims by
        the true counts."""
        win = lambda a, lo, w: jax.lax.dynamic_slice(a, (lo,), (w,))
        vbits = jnp.zeros(wf, dtype=jnp.int64)
        for k, v in enumerate(fvalids):
            vbits |= win(v, lo_f, wf).astype(jnp.int64) << k
        flat, _ = pack_for_fetch(
            [win(c, lo_f, wf) for c in fcols]
            + [vbits, win(dkeys, lo_d, wd)])
        return flat

    def _dispatch_persist(self, n: int, nd: int):
        """Enqueue the pack of the rows the stores gained since the last
        checkpoint (host-known windows, pow2-bucketed widths so that equal
        intervals share one program). Returns what `_persist` needs, or None
        where nothing is durable."""
        if not self._durable:
            return None
        lo_f, lo_d = self._persist_cursor
        wf = _bucket(n - lo_f, self.capacity)
        wd = _bucket(nd - lo_d, self.dim_capacity)
        # a window that would pass the store's end starts earlier: the
        # host skips what the last checkpoint wrote
        start_f = min(lo_f, self.capacity - wf)
        start_d = min(lo_d, self.dim_capacity - wd)
        flat = self._persist_pack(
            self._fcols, self._fvalids, self._dkeys,
            jnp.int32(start_f), jnp.int32(start_d), wf=wf, wd=wd)
        return flat, (wf, lo_f - start_f, n), (wd, lo_d - start_d, nd)

    async def _persist(self, barrier: Barrier, pack) -> None:
        if pack is None:
            return
        flat, (wf, off_f, n), (wd, off_d, nd) = pack
        metas = ([(wf, f.data_type.np_dtype) for f in self._fact_schema]
                 + [(wf, np.dtype(np.int64)), (wd, np.dtype(np.int64))])
        *fact, dkeys = unpack_fetched(
            await off_loop(fetch_flat, flat), metas)
        lo_f, lo_d = self._persist_cursor
        # `fact` ends with the per-cell validity, a packed bitmask column
        # (NULL cells must survive recovery — their data lanes are
        # undefined)
        rows = ([np.arange(lo_f, n, dtype=np.int64)]
                + [c[off_f:off_f + n - lo_f] for c in fact],
                [np.arange(lo_d, nd, dtype=np.int64),
                 dkeys[off_d:off_d + nd - lo_d]])
        for s, st in enumerate(self.state_tables):
            if st is None:
                continue
            k = rows[s][0].shape[0]
            if k:
                st.write_chunk_columns(
                    np.full(k, OP_INSERT, dtype=np.int8), rows[s],
                    np.ones(k, dtype=bool))
            st.commit(barrier.epoch.curr)
        self._persist_cursor = [n, nd]

    def recover(self) -> None:
        if all(st is None for st in self.state_tables):
            return
        rows_f = [r for _, r in self.state_tables[LEFT].iter_all()] \
            if self.state_tables[LEFT] is not None else []
        rows_d = [r for _, r in self.state_tables[RIGHT].iter_all()] \
            if self.state_tables[RIGHT] is not None else []
        while len(rows_f) > 0.7 * self.capacity:
            self.capacity *= 2
        while len(rows_d) > 0.7 * self.dim_capacity:
            self.dim_capacity *= 2
        self._init_stores()
        if rows_f:
            rows_f.sort(key=lambda r: r[0])
            arrays = [
                np.asarray([r[k + 1] for r in rows_f],
                           dtype=f.data_type.np_dtype)
                for k, f in enumerate(self._fact_schema)]
            vbits = np.asarray([r[1 + len(self._fact_schema)]
                                for r in rows_f], dtype=np.int64)
            C = self.capacity
            self._fcols = tuple(
                jnp.asarray(np.concatenate(
                    [a, np.zeros(C - len(a), dtype=a.dtype)]))
                for a in arrays)
            self._fvalids = tuple(
                jnp.asarray(np.concatenate(
                    [((vbits >> k) & 1).astype(bool),
                     np.zeros(C - len(rows_f), dtype=bool)]))
                for k in range(len(self._fact_schema)))
            self._fn = jnp.asarray(len(rows_f), dtype=jnp.int32)
        if rows_d:
            rows_d.sort(key=lambda r: r[0])
            keys = np.asarray([r[1] for r in rows_d], dtype=np.int64)
            Cd = self.dim_capacity
            self._dkeys = jnp.asarray(np.concatenate(
                [keys, np.zeros(Cd - len(keys), dtype=np.int64)]))
            self._dn = jnp.asarray(len(rows_d), dtype=jnp.int32)
        self._persist_cursor = [len(rows_f), len(rows_d)]
        self._applied_rows_upper = len(rows_f)
        self._applied_dim_upper = len(rows_d)
        if rows_f or rows_d:
            # restore the last-emitted output: rows reach the log only
            # via a barrier whose flush already emitted, so the
            # recomputed output equals what downstream last saw
            self._prev, self._prev_valid, self._emitted, _ = self._flush(
                self._fcols, self._fvalids, self._fn, self._dkeys,
                self._dn, self._prev, self._prev_valid, self._emitted)

    # ------------------------------------------------------------ stream
    async def execute(self):
        first = True
        async for kind, s, msg in barrier_align(*self.inputs):
            if kind == "chunk":
                if s == LEFT:
                    (self._fcols, self._fvalids, self._fn,
                     self._errs) = self._append_fact(
                        self._fcols, self._fvalids, self._fn,
                        self._errs, msg)
                    self._applied_rows_upper += msg.capacity
                else:
                    self._dkeys, self._dn, self._errs = self._append_dim(
                        self._dkeys, self._dn, self._errs, msg)
                    self._applied_dim_upper += msg.capacity
                if self.watchdog_interval is None and (
                        self._applied_rows_upper > 0.9 * self.capacity
                        or self._applied_dim_upper
                        > 0.9 * self.dim_capacity):
                    # growth needs the true counts; without the
                    # watchdog's barrier d2h, pay one here instead of
                    # overflowing (and surface any pending errors —
                    # they must never be swallowed in this mode)
                    n, nd = await self._fetch_counts()
                    self._maybe_grow(n, nd)
                    self._applied_rows_upper = n
                    self._applied_dim_upper = nd
                self._dirty = True
            elif kind == "barrier":
                barrier: Barrier = msg
                if first or barrier.kind is BarrierKind.INITIAL:
                    first = False
                    for st in self.state_tables:
                        if st is not None:
                            st.init_epoch(barrier.epoch.curr)
                    self.recover()
                    yield barrier
                    continue
                stopping = barrier.mutation is not None \
                    and barrier.is_stop_any()
                if self._dirty:
                    self._dirty = False
                    pack = None
                    if self.watchdog_interval or self._durable:
                        n, nd = await self._fetch_counts()
                        self._maybe_grow(n, nd)
                        # before the flush: the device reaches the small
                        # pack first and the rows travel and are written
                        # while it recomputes the snapshot
                        pack = self._dispatch_persist(n, nd)
                    (self._prev, self._prev_valid, self._emitted,
                     out) = self._flush(
                        self._fcols, self._fvalids, self._fn,
                        self._dkeys, self._dn, self._prev,
                        self._prev_valid, self._emitted)
                    await self._persist(barrier, pack)
                    yield out
                elif stopping and self.watchdog_interval:
                    await self._fetch_counts()
                    for st in self.state_tables:
                        if st is not None:
                            st.commit(barrier.epoch.curr)
                else:
                    for st in self.state_tables:
                        if st is not None:
                            st.commit(barrier.epoch.curr)
                yield barrier
            else:
                # watermarks do not pass a global aggregate (no group
                # column survives) — same as SimpleAgg
                continue
