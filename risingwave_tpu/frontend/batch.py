"""Batch engine — numpy host executors over committed MV snapshots.

Reference: src/batch/src/executor/ — RowSeqScan, Filter, HashAgg
(hash_agg.rs), HashJoin (hash_join.rs), Sort (sort.rs), Limit (limit.rs),
Project. Serving reads pull rows OUT of the system, so this path stays on
the host deliberately (a blocking d2h per query would also serialise with
the dispatch of the streaming dataflow sharing the process).

Pipeline: scan (with per-column validity from the serde — NULL cells are
real NULLs here) -> filter -> join -> group-agg -> project -> sort ->
limit/offset. All vectorized numpy; aggregates follow SQL NULL semantics
(count(x) skips NULLs, sum/min/max ignore NULLs, avg = sum/count).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..common.types import DataType, Field, GLOBAL_DICT, Schema
from ..expr.agg import AggKind
from ..state.storage_table import StorageTable
from . import sql as ast
from .binder import (AGG_FUNCS, BindError, Scope, bind_scalar, contains_agg,
                     expand_star, split_conjuncts, equi_pair, auto_name)
from .np_eval import eval_numpy


class _Rel:
    """A bound batch relation: columns + validity + name scope."""

    def __init__(self, cols, valids, scope: Scope):
        self.cols = cols
        self.valids = valids
        self.scope = scope

    @property
    def n(self) -> int:
        return len(self.cols[0]) if self.cols else 0

    def mask(self, m: np.ndarray) -> "_Rel":
        return _Rel([c[m] for c in self.cols],
                    [v[m] for v in self.valids], self.scope)


def _scan_mv(catalog, name: str, alias: Optional[str]) -> _Rel:
    mv = catalog.mvs.get(name)
    if mv is None:
        raise BindError(f"unknown MV {name!r}")
    st = StorageTable.for_state_table(mv.table)
    cols, valids = st.to_numpy_with_validity()
    return _Rel(cols, valids, Scope.of(mv.schema, alias or name))


def _bind_rel(catalog, rel, scan=_scan_mv) -> _Rel:
    if isinstance(rel, ast.TableRel):
        return scan(catalog, rel.name, rel.alias)
    if isinstance(rel, ast.JoinRel):
        left = _bind_rel(catalog, rel.left, scan)
        right = _bind_rel(catalog, rel.right, scan)
        return _hash_join(left, right, rel.on,
                          getattr(rel, "join_type", "inner"))
    raise BindError(f"batch queries cannot read {rel!r}")


def _hash_join(left: _Rel, right: _Rel, on, join_type: str = "inner") -> _Rel:
    """Equi-join, all JoinTypes (batch/src/executor/hash_join.rs): build
    on the right, probe with the left. The ON residue filters MATCHED
    pairs (outer-join semantics: a left row whose matches all fail the
    residue still emits NULL-padded), then unmatched rows are appended
    with the other side's columns NULL."""
    lkeys, rkeys, residue = [], [], []
    for conj in split_conjuncts(on):
        pair = equi_pair(conj, left.scope, right.scope)
        if pair is not None:
            lkeys.append(pair[0])
            rkeys.append(pair[1])
        else:
            residue.append(conj)
    if not lkeys:
        raise BindError("batch join needs at least one equi condition")
    # composite keys -> sort/searchsorted merge; NULL keys never match
    lvalid = np.ones(left.n, dtype=bool)
    rvalid = np.ones(right.n, dtype=bool)
    for i in lkeys:
        lvalid &= left.valids[i]
    for i in rkeys:
        rvalid &= right.valids[i]
    lkc = [np.asarray(left.cols[i]) for i in lkeys]
    rkc = [np.asarray(right.cols[i]) for i in rkeys]
    if len(lkc) > 1:
        # composite keys -> ONE dense rank over the combined tuples, so
        # the probe below stays a single vectorized searchsorted (the
        # same rank-space trick sorted_join.py uses on device)
        both = [np.concatenate([l, r]) for l, r in zip(lkc, rkc)]
        oo = np.lexsort(tuple(reversed(both)))
        same = np.ones(max(0, len(oo) - 1), dtype=bool)
        for c in both:
            sc = c[oo]
            same &= sc[1:] == sc[:-1]
        run = np.concatenate([[True], ~same])   # new run if ANY col differs
        rank_sorted = np.cumsum(run) - 1
        rank = np.empty(len(oo), dtype=np.int64)
        rank[oo] = rank_sorted
        lkc = [rank[:left.n]]
        rkc = [rank[left.n:]]
    order = np.argsort(rkc[0], kind="stable")
    order = order[rvalid[order]]
    rs = [rkc[0][order]]
    lo = np.searchsorted(rs[0], lkc[0], "left")
    hi = np.searchsorted(rs[0], lkc[0], "right")
    lens = np.where(lvalid, hi - lo, 0)
    li = np.repeat(np.arange(left.n), lens)
    starts = np.repeat(lo, lens)
    within = np.arange(len(li)) - np.repeat(
        np.cumsum(lens) - lens, lens)
    ri = order[starts + within]

    scope = Scope.join(left.scope, right.scope)
    if residue:
        e = residue[0]
        for r in residue[1:]:
            e = ast.BinOp("and", e, r)
        pred = bind_scalar(e, scope)
        pcols = [c[li] for c in left.cols] + [c[ri] for c in right.cols]
        pvalids = [v[li] for v in left.valids] + [v[ri] for v in right.valids]
        v, valid = eval_numpy(pred, pcols, pvalids)
        keep = np.asarray(v, dtype=bool) & valid
        li, ri = li[keep], ri[keep]

    if join_type == "inner":
        cols = [c[li] for c in left.cols] + [c[ri] for c in right.cols]
        valids = ([v[li] for v in left.valids]
                  + [v[ri] for v in right.valids])
        return _Rel(cols, valids, scope)

    # outer joins: append unmatched rows with the other side NULL-padded
    extra_l = np.empty(0, dtype=np.int64)
    extra_r = np.empty(0, dtype=np.int64)
    if join_type in ("left", "full"):
        lmatched = np.zeros(left.n, dtype=bool)
        lmatched[li] = True
        extra_l = np.nonzero(~lmatched)[0]
    if join_type in ("right", "full"):
        rmatched = np.zeros(right.n, dtype=bool)
        rmatched[ri] = True
        extra_r = np.nonzero(~rmatched)[0]

    def pad(c, n):
        return np.zeros(n, dtype=np.asarray(c).dtype)

    cols, valids = [], []
    for c, v in zip(left.cols, left.valids):
        c = np.asarray(c)
        cols.append(np.concatenate([c[li], c[extra_l], pad(c, len(extra_r))]))
        valids.append(np.concatenate(
            [v[li], v[extra_l], np.zeros(len(extra_r), dtype=bool)]))
    for c, v in zip(right.cols, right.valids):
        c = np.asarray(c)
        cols.append(np.concatenate([c[ri], pad(c, len(extra_l)), c[extra_r]]))
        valids.append(np.concatenate(
            [v[ri], np.zeros(len(extra_l), dtype=bool), v[extra_r]]))
    return _Rel(cols, valids, scope)


def _agg_reduce(kind: AggKind, vals, valid, seg_id, n_groups):
    """Per-group reduction with SQL NULL semantics."""
    if kind is AggKind.COUNT:
        return np.bincount(seg_id, weights=valid.astype(np.float64),
                           minlength=n_groups).astype(np.int64), None
    out_valid = np.bincount(seg_id, weights=valid.astype(np.float64),
                            minlength=n_groups) > 0
    if kind is AggKind.SUM:
        w = np.where(valid, vals, 0)
        if np.issubdtype(vals.dtype, np.integer):
            acc = np.zeros(n_groups, dtype=np.int64)
            np.add.at(acc, seg_id, w.astype(np.int64))   # exact int sums
            return acc, out_valid
        return np.bincount(seg_id, weights=w.astype(np.float64),
                           minlength=n_groups), out_valid
    # min/max: mask invalid with +-inf sentinels
    if np.issubdtype(vals.dtype, np.integer):
        lo, hi = np.iinfo(vals.dtype).min, np.iinfo(vals.dtype).max
    else:
        lo, hi = -np.inf, np.inf
    out = np.full(n_groups, lo if kind is AggKind.MAX else hi,
                  dtype=vals.dtype)
    sentinel = lo if kind is AggKind.MAX else hi
    w = np.where(valid, vals, sentinel)
    op = np.maximum if kind is AggKind.MAX else np.minimum
    np_op_at = op.at
    np_op_at(out, seg_id, w)
    return out, out_valid


_AGG_KINDS = {"count": AggKind.COUNT, "sum": AggKind.SUM,
              "min": AggKind.MIN, "max": AggKind.MAX}


def run_batch_select(catalog, sel: ast.Select) -> list[tuple]:
    return run_batch_select_full(catalog, sel)[2]


def run_batch_select_full(catalog, sel: ast.Select, scan=None):
    """-> (names, DataTypes, rows) — the wire layer needs the row
    description, not just the rows. `scan` overrides how a TableRel
    materializes (the serving layer injects pinned-snapshot relations
    here); the default is the StorageTable committed-snapshot scan."""
    rel = _bind_rel(catalog, sel.rel, scan if scan is not None else _scan_mv)
    if sel.where is not None:
        pred = bind_scalar(sel.where, rel.scope)
        v, valid = eval_numpy(pred, rel.cols, rel.valids)
        rel = rel.mask(np.asarray(v, dtype=bool) & valid)

    items = expand_star(sel.items, rel.scope.schema)
    has_agg = bool(sel.group_by) or any(contains_agg(it.expr)
                                        for it in items)
    if has_agg:
        out_cols, out_valids, out_names, out_types = _run_agg(
            rel, sel, items)
    else:
        out_cols, out_valids, out_names, out_types = [], [], [], []
        for j, it in enumerate(items):
            e = bind_scalar(it.expr, rel.scope)
            v, valid = eval_numpy(e, rel.cols, rel.valids)
            if np.ndim(v) == 0:
                v = np.full(rel.n, v)
                valid = np.ones(rel.n, dtype=bool)
            out_cols.append(np.asarray(v))
            out_valids.append(valid)
            out_names.append(it.alias or auto_name(it.expr, j))
            out_types.append(e.ret_type)

    # ---- ORDER BY (batch/src/executor/sort.rs) ----
    if sel.order_by and out_cols and len(out_cols[0]):
        keys = []
        for e, desc in reversed(sel.order_by):
            j = _order_col(e, out_cols, out_names)
            arr = out_cols[j]
            if out_types[j] is DataType.VARCHAR:
                # dict ids are insertion-ordered, not lexicographic:
                # rank by decoded strings
                strs = np.asarray([GLOBAL_DICT.decode(int(x))
                                   for x in arr])
                _, rank = np.unique(strs, return_inverse=True)
            else:
                # rank-space keys: negation-free DESC (int negation
                # overflows at the dtype edges)
                _, rank = np.unique(arr, return_inverse=True)
            if desc:
                rank = rank.max(initial=0) - rank
            keys.append(rank)
        order = np.lexsort(tuple(keys))
        out_cols = [c[order] for c in out_cols]
        out_valids = [v[order] for v in out_valids]

    # ---- LIMIT / OFFSET (limit.rs) ----
    if sel.offset or sel.limit is not None:
        stop = (sel.offset + sel.limit) if sel.limit is not None else None
        out_cols = [c[sel.offset:stop] for c in out_cols]
        out_valids = [v[sel.offset:stop] for v in out_valids]

    n = len(out_cols[0]) if out_cols else 0

    def cell(j, i):
        if not out_valids[j][i]:
            return None
        v = out_cols[j][i].item()
        if out_types[j] is DataType.VARCHAR:
            return GLOBAL_DICT.decode(int(v))
        if out_types[j] is DataType.BOOLEAN:
            return bool(v)   # the row serde stores booleans as ints
        return v

    return out_names, out_types, [
        tuple(cell(j, i) for j in range(len(out_cols))) for i in range(n)]


def _order_col(e, out_cols, out_names) -> int:
    """ORDER BY resolves against output positions (1-based literal ints)
    then output aliases."""
    if isinstance(e, ast.Lit) and isinstance(e.value, int):
        idx = e.value - 1
        if not 0 <= idx < len(out_cols):
            raise BindError(f"ORDER BY position {e.value} out of range")
        return idx
    if isinstance(e, ast.ColRef) and e.qualifier is None \
            and e.name in out_names:
        return out_names.index(e.name)
    raise BindError(f"ORDER BY must reference an output column: {e!r}")


def _run_agg(rel: _Rel, sel: ast.Select, items):
    """GROUP BY + aggregates (batch/src/executor/hash_agg.rs): group ids
    via lexsort runs; per-call reductions via bincount / ufunc.at."""
    keys = [bind_scalar(g, rel.scope) for g in sel.group_by]
    key_vals = []
    key_valids = []
    for k in keys:
        v, valid = eval_numpy(k, rel.cols, rel.valids)
        key_vals.append(np.asarray(v))
        key_valids.append(valid)

    if keys and rel.n:
        # zero out NULL cells first: a computed key's invalid lanes carry
        # garbage values, and SQL groups all NULL keys together
        key_vals = [np.where(valid, v, 0)
                    for v, valid in zip(key_vals, key_valids)]
        sort_cols = []
        for v, valid in zip(reversed(key_vals), reversed(key_valids)):
            sort_cols.append(v)
            sort_cols.append(~valid)
        order = np.lexsort(tuple(sort_cols))
        # a new group starts where ANY key column differs from the
        # previous sorted row (the old &= ~same demanded EVERY key
        # change, collapsing multi-key GROUP BY into far too few groups
        # — caught by the approx_count_distinct oracle, round 5)
        run_start = np.zeros(rel.n, dtype=bool)
        for v, valid in zip(key_vals, key_valids):
            sv, svd = v[order], valid[order]
            diff = (sv[1:] != sv[:-1]) | (svd[1:] != svd[:-1])
            run_start[1:] |= diff
        run_start[0] = True
        gid_sorted = np.cumsum(run_start) - 1
        n_groups = int(gid_sorted[-1]) + 1 if rel.n else 0
        seg_id = np.empty(rel.n, dtype=np.int64)
        seg_id[order] = gid_sorted
        rep = order[run_start]           # representative row per group
    elif keys:
        n_groups = 0
        seg_id = np.empty(0, dtype=np.int64)
        rep = np.empty(0, dtype=np.int64)
    else:
        n_groups = 1
        seg_id = np.zeros(rel.n, dtype=np.int64)
        rep = None

    def eval_agg(e):
        """-> (values [n_groups], valid) for one aggregate call."""
        assert isinstance(e, ast.Func) and e.name in AGG_FUNCS
        if e.name in ("bool_and", "bool_or"):
            ee = bind_scalar(e.args[0], rel.scope)
            v, valid = eval_numpy(ee, rel.cols, rel.valids)
            b = np.asarray(v, dtype=bool)
            cn = np.bincount(seg_id, weights=valid.astype(np.float64),
                             minlength=n_groups)
            want = (valid & ~b) if e.name == "bool_and" else (valid & b)
            cf = np.bincount(seg_id, weights=want.astype(np.float64),
                             minlength=n_groups)
            out = (cf == 0) if e.name == "bool_and" else (cf > 0)
            return out, cn > 0
        if e.name == "approx_count_distinct":
            # same deterministic 64-register HLL as the streaming path
            # (expr/hll.py) so the two engines agree EXACTLY
            from ..expr.hll import hll_estimate_numpy
            ee = bind_scalar(e.args[0], rel.scope)
            v, valid = eval_numpy(ee, rel.cols, rel.valids)
            return hll_estimate_numpy(
                np.asarray(v), np.asarray(valid), seg_id, n_groups)
        if e.name == "avg":
            sv, svalid = eval_agg(ast.Func("sum", e.args))
            cv, _ = eval_agg(ast.Func("count", e.args))
            safe = np.where(cv == 0, 1, cv)
            if svalid is None:
                svalid = np.ones(n_groups, dtype=bool)
            return sv / safe, svalid & (cv > 0)
        if e.name == "count" and (not e.args or (
                isinstance(e.args[0], ast.ColRef)
                and e.args[0].name == "*")):
            vals = np.ones(rel.n, dtype=np.int64)
            valid = np.ones(rel.n, dtype=bool)
        else:
            ee = bind_scalar(e.args[0], rel.scope)
            v, valid = eval_numpy(ee, rel.cols, rel.valids)
            vals = np.asarray(v)
            if (ee.ret_type is DataType.VARCHAR
                    and e.name in ("min", "max")):
                # dict ids are insertion-ordered; min/max over VARCHAR
                # must rank lexicographically (ADVICE r3 #3): reduce over
                # ranks of the decoded strings, then map the winning rank
                # back to its dict id
                uniq, inv = np.unique(vals, return_inverse=True)
                if len(uniq) == 0:
                    return (np.zeros(n_groups, dtype=np.int64),
                            np.zeros(n_groups, dtype=bool))
                strs = np.asarray(GLOBAL_DICT.decode_many(uniq))
                order = np.argsort(strs)          # rank -> uniq position
                rank_of = np.empty(len(uniq), dtype=np.int64)
                rank_of[order] = np.arange(len(uniq))
                ranks, out_valid = _agg_reduce(_AGG_KINDS[e.name],
                                               rank_of[inv], valid,
                                               seg_id, n_groups)
                safe = np.clip(ranks, 0, len(uniq) - 1)
                return uniq[order][safe].astype(np.int64), out_valid
        out, out_valid = _agg_reduce(_AGG_KINDS[e.name], vals, valid,
                                     seg_id, n_groups)
        return out, out_valid

    def eval_item(e):
        """Scalar-over-aggregates evaluation at the group level."""
        if isinstance(e, ast.Lit):
            return np.full(n_groups, e.value), np.ones(n_groups, bool)
        if not contains_agg(e):
            # agg-free expressions match a GROUP BY key AS A WHOLE first
            # (`auction % 7` with GROUP BY auction % 7 — found by the
            # SQL fuzzer), then fall through to decomposition so
            # expressions OVER keys (`auction + 1` with GROUP BY
            # auction) still evaluate
            eb = bind_scalar(e, rel.scope)
            for j2, _k in enumerate(keys):
                if repr(bind_scalar(sel.group_by[j2],
                                    rel.scope)) == repr(eb):
                    assert rep is not None
                    return key_vals[j2][rep], key_valids[j2][rep]
            if not isinstance(e, (ast.BinOp, ast.UnOp)):
                raise BindError(
                    f"{e!r} must be an aggregate or appear in GROUP BY")
        if isinstance(e, ast.Func) and e.name in AGG_FUNCS:
            v, valid = eval_agg(e)
            if valid is None:                  # COUNT: always valid
                valid = np.ones(n_groups, dtype=bool)
            return v, valid
        if isinstance(e, ast.BinOp):
            a, av = eval_item(e.left)
            b, bv = eval_item(e.right)
            import operator
            ops = {"add": operator.add, "subtract": operator.sub,
                   "multiply": operator.mul,
                   "equal": operator.eq, "not_equal": operator.ne,
                   "less_than": operator.lt,
                   "less_than_or_equal": operator.le,
                   "greater_than": operator.gt,
                   "greater_than_or_equal": operator.ge}
            if e.op == "divide":
                safe = np.where(np.asarray(b) == 0, 1, b)
                return np.asarray(a) / safe, av & bv & (np.asarray(b) != 0)
            if e.op not in ops:
                raise BindError(
                    f"unsupported operator {e.op!r} over aggregates")
            return ops[e.op](np.asarray(a), np.asarray(b)), av & bv
        raise BindError(f"{e!r} must be an aggregate or appear in GROUP BY")

    out_cols, out_valids, out_names, out_types = [], [], [], []
    for j, it in enumerate(items):
        v, valid = eval_item(it.expr)
        if valid is None:
            valid = np.ones(n_groups, dtype=bool)
        arr = np.asarray(v)
        out_cols.append(arr)
        out_valids.append(np.asarray(valid, dtype=bool))
        out_names.append(it.alias or auto_name(it.expr, j))
        out_types.append(_item_type(it.expr, rel, keys, sel))
    return out_cols, out_valids, out_names, out_types


def _item_type(e, rel, keys, sel) -> DataType:
    if isinstance(e, ast.Func) and e.name in AGG_FUNCS:
        if e.name == "count":
            return DataType.INT64
        if e.name == "avg":
            return DataType.FLOAT64
        try:
            return bind_scalar(e.args[0], rel.scope).ret_type
        except BindError:
            return DataType.INT64
    try:
        return bind_scalar(e, rel.scope).ret_type
    except BindError:
        return DataType.INT64
