"""Binder + streaming planner: SQL AST -> fragment-graph IR.

Reference: src/frontend binder/ + planner/ + stream_fragmenter (AST ->
bound algebra -> stream plan -> StreamFragmentGraph cut at exchanges).
This thin version binds names against the catalog, lowers expressions onto
the engine's Expr IR, and emits a `StreamGraph` directly:

  FROM source            -> source fragment
  TUMBLE(...)            -> + project appending window_start/window_end
  HOP(...)               -> + hop_window node
  JOIN ... ON            -> two upstream fragments + sorted_join fragment
                            (equi conjunctions become key columns, the
                            rest becomes the non-equi condition)
  WHERE                  -> filter node
  GROUP BY + aggregates  -> pre-project (group keys + agg args), hash_agg
                            fragment hash-dispatched on the keys, post-
                            project for SELECT order / AVG = SUM/COUNT
  plain SELECT           -> project (+ row_id for the MV pk)
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from fractions import Fraction
from typing import Optional

from ..common.types import DataType, Schema
from ..expr.agg import AggCall, AggKind
from ..expr.ir import Expr, FuncCall, InputRef, call, col, lit
from ..plan import Exchange, Fragment, Node, StreamGraph
from . import sql as ast

AGG_FUNCS = {"count", "sum", "min", "max", "avg", "bool_and", "bool_or",
             "approx_count_distinct"}


class BindError(Exception):
    pass


@dataclass
class Scope:
    """Visible columns: (qualifier, name) -> (index, dtype)."""

    schema: Schema
    names: dict = field(default_factory=dict)

    @classmethod
    def of(cls, schema: Schema, qualifier: Optional[str]) -> "Scope":
        s = cls(schema)
        for i, f in enumerate(schema):
            s.names.setdefault((None, f.name), (i, f.data_type))
            if qualifier:
                s.names[(qualifier, f.name)] = (i, f.data_type)
        return s

    @classmethod
    def join(cls, left: "Scope", right: "Scope") -> "Scope":
        fields = tuple(left.schema) + tuple(right.schema)
        s = cls(Schema(fields))
        off = len(left.schema)
        for (q, n), (i, t) in left.names.items():
            s.names.setdefault((q, n), (i, t))
        for (q, n), (i, t) in right.names.items():
            if (q, n) in s.names and q is None:
                # ambiguous unqualified name: drop it
                del s.names[(q, n)]
                continue
            s.names[(q, n)] = (i + off, t)
        return s

    def resolve(self, ref: ast.ColRef) -> tuple[int, DataType]:
        # a qualified name must match its qualifier exactly — falling back
        # to the unqualified name would silently bind b.x inside a's scope
        key = (ref.qualifier, ref.name)
        if key in self.names:
            return self.names[key]
        raise BindError(f"unknown column {ref.qualifier or ''}.{ref.name}")


def bind_scalar(e, scope: Scope) -> Expr:
    """SQL expression AST -> engine Expr IR (no aggregates allowed)."""
    if isinstance(e, ast.Lit):
        return lit(e.value)
    if isinstance(e, ast.ColRef):
        i, t = scope.resolve(e)
        return col(i, t)
    if isinstance(e, ast.UnOp):
        return call(e.op, bind_scalar(e.arg, scope))
    if isinstance(e, ast.BinOp):
        return call(e.op, bind_scalar(e.left, scope),
                    bind_scalar(e.right, scope))
    if isinstance(e, ast.Func):
        if e.name in AGG_FUNCS:
            raise BindError(f"aggregate {e.name} not allowed here")
        return call(e.name, *[bind_scalar(a, scope) for a in e.args])
    raise BindError(f"cannot bind {e!r}")


def contains_agg(e) -> bool:
    if isinstance(e, ast.Func):
        return e.name in AGG_FUNCS or any(contains_agg(a) for a in e.args)
    if isinstance(e, ast.BinOp):
        return contains_agg(e.left) or contains_agg(e.right)
    if isinstance(e, ast.UnOp):
        return contains_agg(e.arg)
    return False


@dataclass
class RelInfo:
    """Stream properties the reference tracks in plan_base: the STREAM KEY
    (positions in the relation's output that uniquely identify a changelog
    row — what retractions address), append-only-ness, and the columns a
    WATERMARK flows on (reference: watermark_columns in plan_base, derived
    by the watermark inference pass — drives join/agg state cleaning)."""

    stream_key: Optional[tuple] = None      # None = keyless (needs row_id)
    append_only: bool = True
    wm_cols: frozenset = frozenset()


# date_time column index per nexmark table (the connector's declared
# watermark column, connectors/nexmark.py watermark_col)
_NEXMARK_WM_COL = {"bid": 5, "person": 6, "auction": 5}


@dataclass
class BoundPlan:
    graph: StreamGraph
    mv_fragment: int            # the fragment whose root will materialize
    schema: Schema
    pk_indices: tuple
    append_only: bool = True


class TumbleStartTransform:
    """Monotone watermark transform `v -> window_start(v)` as a
    PICKLABLE callable: Node args ship to cluster compute nodes as the
    wire IR, and a closure would refuse to pickle."""

    __slots__ = ("size",)

    def __init__(self, size: int):
        self.size = size

    def __call__(self, v):
        return v - v % self.size


class TumbleEndTransform(TumbleStartTransform):
    def __call__(self, v):
        return (v - v % self.size) + self.size


class StreamPlanner:
    def __init__(self, catalog, parallelism: int = 1, config=None):
        self.catalog = catalog
        self.parallelism = parallelism   # hash-distributed fragments
        self.config = config or {}
        self.graph = StreamGraph()
        self._next_fid = 1

    def cfg(self, name: str, default):
        return self.config.get(name, default)

    def durable(self) -> bool:
        """Stateful executors flush to state tables at barriers unless the
        session selected the in-memory state backend."""
        return bool(self.cfg("streaming_durability", 1))

    def fid(self) -> int:
        f = self._next_fid
        self._next_fid = f + 1
        return f

    def source_fragment(self, name: str) -> int:
        """Source fragments are SHARED within one plan (reference: the
        source-sharing rewrite, ShareSourceRewriter) — a query reading
        `bid` twice (q7: raw stream + windowed agg over it) runs ONE
        generator/connector, not two. The cached fragment stays bare;
        consumers attach through Exchange so later grafts (WHERE, window
        projects) never mutate a shared root."""
        if not hasattr(self, "_source_frags"):
            self._source_frags = {}
        if not hasattr(self, "used_sources"):
            self.used_sources = set()
        self.used_sources.add(name)
        if name not in self._source_frags:
            src = self.catalog.source(name)
            node = Node("nexmark_source", dict(src.options, durable=True,
                                               source_name=name))
            # split-managed sources scale with the session parallelism,
            # bounded by their split count (source_manager.rs assignment)
            n_splits = int(src.options.get("splits", 1))
            f = self.graph.add(Fragment(
                self.fid(), node, dispatch="broadcast",
                parallelism=max(1, min(self.parallelism, n_splits))))
            self._source_frags[name] = f.fid
        return self._source_frags[name]

    # ----------------------------------------------------------- relations
    def plan_rel(self, rel, rank_filter=None) -> tuple[int, Scope, RelInfo]:
        """Returns (fragment id, scope over its output, stream info).
        `rank_filter`: what `_match_rank_filter` found over a subquery."""
        if isinstance(rel, ast.TableRel):
            # an MV name resolves to a backfilled stream scan over it
            # (MV-on-MV, reference StreamScan/Chain); sources otherwise
            if rel.name in getattr(self.catalog, "mvs", {}):
                mv = self.catalog.mvs[rel.name]
                node = Node("stream_scan", dict(mv=rel.name))
                f = self.graph.add(Fragment(self.fid(), node,
                                            dispatch="broadcast"))
                return (f.fid, Scope.of(mv.schema, rel.alias or rel.name),
                        RelInfo(stream_key=tuple(mv.pk_indices),
                                append_only=getattr(mv, "append_only",
                                                    False)))
            src = self.catalog.source(rel.name)
            sfid = self.source_fragment(rel.name)
            # indirection fragment: WHERE/project grafts land here, the
            # shared source root stays untouched
            f = self.graph.add(Fragment(self.fid(), Node(
                "no_op", {}, inputs=(Exchange(sfid),)),
                dispatch="broadcast"))
            wm = frozenset()
            wmcol = _NEXMARK_WM_COL.get(src.options.get("table"))
            if src.options.get("emit_watermarks") and wmcol is not None:
                wm = frozenset({wmcol})
            pk_opt = src.options.get("primary_key")
            # generator/file sources only ever insert; a broker topic
            # can carry changelog ops (`__op`), so it declares
            # append-only explicitly or plans retract-capable
            ao = bool(src.options.get("append_only", True))
            return (f.fid, Scope.of(src.schema, rel.alias or rel.name),
                    RelInfo(None if pk_opt is None else (pk_opt,), ao,
                            wm))
        if isinstance(rel, ast.WindowRel):
            src = self.catalog.source(rel.inner.name)
            scope = Scope.of(src.schema, None)
            i, t = scope.resolve(ast.ColRef(rel.time_col))
            src_node = Exchange(self.source_fragment(rel.inner.name))
            if rel.kind == "tumble":
                exprs = [col(j, f.data_type)
                         for j, f in enumerate(src.schema)]
                exprs.append(call("tumble_start", col(i, t), lit(rel.size)))
                exprs.append(call("tumble_end", col(i, t), lit(rel.size)))
                names = list(src.schema.names) + ["window_start",
                                                  "window_end"]
                W = rel.size
                node = Node("project", dict(
                    exprs=exprs, names=names,
                    watermark_transforms={
                        i: [(len(names) - 2, TumbleStartTransform(W)),
                            (len(names) - 1, TumbleEndTransform(W))]}),
                    inputs=(src_node,))
                f = self.graph.add(Fragment(self.fid(), node,
                                            dispatch="broadcast"))
                out_schema = Schema(tuple(
                    list(src.schema)
                    + [type(src.schema[0])("window_start", t),
                       type(src.schema[0])("window_end", t)]))
            else:
                node = Node("hop_window", dict(
                    time_col=i, slide_us=rel.slide, size_us=rel.size),
                    inputs=(src_node,))
                f = self.graph.add(Fragment(self.fid(), node,
                                            dispatch="broadcast"))
                from ..common.types import Field
                out_schema = Schema(tuple(
                    list(src.schema) + [Field("window_start", t),
                                        Field("window_end", t)]))
            wm = frozenset()
            if src.options.get("emit_watermarks"):
                # tumble transforms the event-time watermark onto BOTH
                # window columns; hop emits it on window_start only
                wm = (frozenset({len(src.schema), len(src.schema) + 1})
                      if rel.kind == "tumble"
                      else frozenset({len(src.schema)}))
            # tumble is 1:1 so a declared source pk remains a stream key;
            # hop emits one row PER WINDOW so the key widens to
            # (pk, window_start)
            pk_opt = src.options.get("primary_key")
            sk = None
            if pk_opt is not None:
                sk = ((pk_opt,) if rel.kind == "tumble"
                      else (pk_opt, len(src.schema)))
            return (f.fid, Scope.of(out_schema, rel.alias or rel.inner.name),
                    RelInfo(sk, True, wm))
        if isinstance(rel, ast.JoinRel):
            lf, ls, li = self.plan_rel(rel.left)
            rf, rs, ri = self.plan_rel(rel.right)
            # Join-state pk must be a REAL stream key (reference: plan_base
            # stream_key). A keyless append-only side gets a row_id column
            # (ADVICE r2 #5); a side that already HAS a stream key (MV
            # scan, agg subquery) keeps it — generating fresh row ids for
            # retraction rows would orphan every delete.
            from ..common.types import Field

            def side_key(fid_, scope_, info_):
                if info_.stream_key is not None:
                    return scope_, tuple(info_.stream_key)
                if not info_.append_only:
                    raise BindError("keyless retracting join input")
                frag_ = self.graph.fragments[fid_]
                frag_.root = Node("row_id_gen", {}, inputs=(frag_.root,))
                sch = Schema(tuple(scope_.schema)
                             + (Field("_row_id", DataType.SERIAL),))
                return Scope(sch, dict(scope_.names)), (len(sch) - 1,)

            ls, lpk = side_key(lf, ls, li)
            rs, rpk = side_key(rf, rs, ri)
            jscope = Scope.join(ls, rs)
            lkeys, rkeys, residue = [], [], []
            for conj in split_conjuncts(rel.on):
                pair = equi_pair(conj, ls, rs)
                if pair is not None:
                    lkeys.append(pair[0])
                    rkeys.append(pair[1])
                else:
                    residue.append(conj)
            if not lkeys:
                raise BindError("join needs at least one equi condition")
            cond = None
            if residue:
                e = residue[0]
                for r in residue[1:]:
                    e = ast.BinOp("and", e, r)
                cond = bind_scalar(e, jscope)
            jt = getattr(rel, "join_type", "inner")
            temporal = getattr(rel, "temporal", False)
            if temporal and jt not in ("inner", "left"):
                raise BindError("temporal joins are INNER or LEFT")
            if temporal and not li.append_only:
                # a retractable stream side would emit deletes for rows
                # downstream never saw (the table side's emissions are
                # suppressed) — the reference requires append-only too
                raise BindError(
                    "temporal joins need an append-only stream side")
            # --- watermark-driven state cleaning (reference: the stream
            # planner's watermark inference + interval-join condition
            # analysis, optimizer/plan_node/stream_hash_join.rs clean_*):
            # a side may evict rows below its watermark on column c when
            # future matches against them are impossible — (1) c is an
            # equi-key whose partner column is also watermarked (windowed
            # joins: both sides advance together), or (2) a residual
            # conjunct bands c against watermarked columns of the other
            # side (interval joins: old rows fall out of every future
            # band). Outer joins never clean (degree accounting).
            clean_l = clean_r = None
            if jt == "inner":
                for kpos, (lk, rk) in enumerate(zip(lkeys, rkeys)):
                    if lk in li.wm_cols and rk in ri.wm_cols:
                        clean_l = ("pair", lk, kpos)
                        clean_r = ("pair", rk, kpos)
                        break
                if clean_l is None and clean_r is None:
                    for conj in residue:
                        b = band_bound(conj, ls, rs, li.wm_cols, ri.wm_cols)
                        if b is None:
                            continue
                        bside, own_col, other_col, delta = b
                        info_side = li if bside == "l" else ri
                        own_wm = own_col in info_side.wm_cols
                        # a RETRACTING side may still emit deletes for
                        # rows the band bound already evicted (the other
                        # side's watermark can run ahead of ours). Safe
                        # only if the side is append-only, or its own
                        # column is watermarked so the executor caps the
                        # bound at min(own wm, band bound).
                        if not (info_side.append_only or own_wm):
                            continue
                        cap = (own_col if own_wm
                               and not info_side.append_only else None)
                        spec = ("band", own_col, other_col, delta, cap)
                        if bside == "l" and clean_l is None:
                            clean_l = spec
                        elif bside == "r" and clean_r is None:
                            clean_r = spec
                        if clean_l is not None and clean_r is not None:
                            break
            wd = 1 if self.cfg("streaming_watchdog", 1) else None
            # per-side match buffers: probing a side whose rows are
            # UNIQUE per join key (stream key covered by its equi keys)
            # yields at most one match per probe row; the wide default
            # factor is only for skewed many-per-key sides
            mf = self.cfg("streaming_join_match_factor", 64)
            mf_l = min(2, mf) if set(rpk) <= set(rkeys) else mf
            mf_r = min(2, mf) if set(lpk) <= set(lkeys) else mf
            node = Node("sorted_join", dict(
                left_key_indices=lkeys, right_key_indices=rkeys,
                left_pk_indices=list(lpk),
                right_pk_indices=list(rpk),
                condition=cond, join_type=jt, temporal=temporal,
                capacity=self.cfg("streaming_join_capacity", 1 << 17),
                match_factor=mf, match_factors=(mf_l, mf_r),
                append_only=(li.append_only, ri.append_only),
                clean_specs=(clean_l, clean_r),
                mesh_devices=self.cfg(
                    "streaming_parallelism_devices", 1),
                watchdog_interval=wd,
                durable=self.durable()),
                inputs=(Exchange(lf), Exchange(rf)))
            f = self.graph.add(Fragment(self.fid(), node,
                                        dispatch="broadcast"))
            rw = self.cfg("streaming_fragment_worker", "")
            if rw:
                # DCN placement: this fragment deploys in the worker
                # process (stream/remote_fragment.py). v1 runs the
                # remote fragment volatile, so the SESSION must be
                # volatile too (recovery then replays sources from 0
                # and the materialize upsert converges the MV)
                if self.durable():
                    raise BindError(
                        "streaming_fragment_worker requires "
                        "streaming_durability = 0 (v1: remote fragments "
                        "hold no durable state)")
                if self.parallelism != 1:
                    raise BindError(
                        "streaming_fragment_worker requires "
                        "streaming_parallelism = 1 (remote fragments "
                        "and their upstreams are singleton in v1)")
                f.remote_worker = rw
            # stash for the bind-time optimizer passes (_optimize_join):
            # filter pushdown + join-input pruning run once the consuming
            # SELECT is known
            if not hasattr(self, "_join_frags"):
                self._join_frags = {}
            self._join_frags[f.fid] = dict(
                node=node, nl=len(ls.schema), lsch=ls.schema,
                rsch=rs.schema, jt=jt)
            off = len(ls.schema)
            jkey = tuple(lpk) + tuple(off + i for i in rpk)
            # the executor forwards min-of-sides watermarks on equi-key
            # columns where BOTH sides carry one. Inner joins only: an
            # outer join's NULL-padded rows emit values on the padded
            # side's key column at arbitrary future times, which would
            # violate the advertised watermark downstream.
            out_wm = set()
            if jt == "inner":
                for lk, rk in zip(lkeys, rkeys):
                    if lk in li.wm_cols and rk in ri.wm_cols:
                        out_wm |= {lk, off + rk}
            # temporal: the table side's updates emit nothing, so the
            # output is append-only iff the STREAM side is
            ao_out = ((li.append_only and jt == "inner") if temporal
                      else (li.append_only and ri.append_only
                            and jt == "inner"))
            return (f.fid, jscope,
                    RelInfo(stream_key=jkey, append_only=ao_out,
                            wm_cols=frozenset(out_wm)))
        if isinstance(rel, ast.SubqueryRel):
            # FROM (SELECT ...) alias — plan the inner query WITHOUT
            # materialization; its changelog feeds the outer plan
            # directly (reference: StreamProject/Agg subplans compose,
            # no intermediate MV)
            from ..common.types import Field
            sub_fid, names, types, pk_hint, ao, wm = self._plan_query(
                rel.select, rank_filter=rank_filter)
            schema = Schema(tuple(Field(n, t)
                                  for n, t in zip(names, types)))
            return (sub_fid, Scope.of(schema, rel.alias),
                    RelInfo(stream_key=pk_hint, append_only=ao,
                            wm_cols=wm))
        raise BindError(f"cannot plan relation {rel!r}")

    # -------------------------------------------------------------- select
    def plan_sink(self, sel: ast.Select, options: dict) -> "BoundPlan":
        """CREATE SINK: the plan terminates in a sink node instead of a
        materialize (reference: StreamSink, sink desc from the WITH
        options)."""
        fid, names, types, pk_hint, append_only, _wm = self._plan_query(sel)
        frag = self.graph.fragments[fid]
        from ..common.types import Field
        frag.root = Node("sink", dict(options), inputs=(frag.root,))
        out = Schema(tuple(Field(n, t) for n, t in zip(names, types)))
        return BoundPlan(self.graph, fid, out, tuple(pk_hint or ()),
                         append_only)

    def plan_select(self, sel: ast.Select) -> BoundPlan:
        fid, names, types, pk_hint, append_only, _wm = self._plan_query(sel)
        frag = self.graph.fragments[fid]
        from ..common.types import Field
        if pk_hint is None:
            frag.root = Node("row_id_gen", {}, inputs=(frag.root,))
            mv = self.graph.add(Fragment(self.fid(), Node(
                "materialize", dict(pk_indices=[len(names)]),
                inputs=(Exchange(fid),))))
            out = Schema(tuple(
                [Field(n, t) for n, t in zip(names, types)]
                + [Field("_row_id", DataType.SERIAL)]))
            return BoundPlan(self.graph, mv.fid, out, (len(names),),
                             append_only)
        mv = self.graph.add(Fragment(self.fid(), Node(
            "materialize", dict(pk_indices=list(pk_hint)),
            inputs=(Exchange(fid),))))
        out = Schema(tuple(Field(n, t) for n, t in zip(names, types)))
        return BoundPlan(self.graph, mv.fid, out, tuple(pk_hint),
                         append_only)

    def _plan_query(self, sel: ast.Select, rank_filter=None):
        """Plan one SELECT (no materialization). Returns (fragment id,
        out names, out DataTypes, pk_hint, append_only) — pk_hint is the
        output positions forming the stream key, or None when the stream
        is keyless append-only (caller adds a row_id). `rank_filter`
        (limit, emit_rank): the SELECT is the subquery of a rank filter,
        its ROW_NUMBER() window plans a group top-N."""
        top_spec = (list(sel.order_by), sel.limit, sel.offset)
        want_top_n = sel.limit is not None
        if (sel.order_by or sel.offset) and not want_top_n:
            raise BindError(
                "streaming ORDER BY needs a LIMIT (a TopN MV); unbounded "
                "ORDER BY belongs in batch SELECTs over the MV")
        if want_top_n and not sel.order_by:
            raise BindError("streaming LIMIT needs ORDER BY (TopN)")
        # comma join: FROM a, b WHERE ... — the join condition lives in
        # WHERE; hoist it into ON (single 2-way comma join supported)
        rel, where = sel.rel, sel.where
        if isinstance(rel, ast.JoinRel) and rel.on is None:
            if isinstance(rel.left, ast.JoinRel) and rel.left.on is None:
                raise BindError("only one comma join is supported")
            if where is None:
                raise BindError("comma join needs join conditions in WHERE")
            rel = ast.JoinRel(rel.left, rel.right, where)
            where = None
        fused = self._try_snapshot_join_agg(ast.Select(
            list(sel.items), rel, where, sel.group_by,
            list(sel.order_by), sel.limit, sel.offset))
        if fused is not None:
            return fused

        # `rank <= N` directly over ROW_NUMBER() OVER (PARTITION BY ..): the
        # subquery plans a group top-N and the conjunct goes
        matched = _match_rank_filter(rel, where, sel)
        if matched is not None:
            where = matched[1]
        fid, scope, info = self.plan_rel(
            rel, rank_filter=None if matched is None else matched[0])
        frag = self.graph.fragments[fid]
        sel = ast.Select(expand_star(sel.items, scope.schema), rel,
                         where, sel.group_by, list(sel.order_by),
                         sel.limit, sel.offset,
                         emit_on_close=getattr(sel, "emit_on_close",
                                               False))

        jinfo = getattr(self, "_join_frags", {}).get(fid)
        if jinfo is not None and frag.root is jinfo["node"]:
            scope, info, sel = self._optimize_join(jinfo, scope, info, sel)

        # `col > now()`-style conjuncts lower to DynamicFilter against a
        # Now fragment (reference: the NOW() rewrite producing
        # StreamDynamicFilter + StreamNow); the rest become a plain filter
        if sel.where is not None:
            plain, dynamic = [], []
            for conj in split_conjuncts(sel.where):
                df = _now_conjunct(conj, scope)
                if df is None:
                    plain.append(conj)
                else:
                    dynamic.append(df)
            # static predicates graft FIRST: rows they reject must never
            # occupy the dynamic filter's bounded device state
            if dynamic and plain:
                e0 = plain[0]
                for c in plain[1:]:
                    e0 = ast.BinOp("and", e0, c)
                frag.root = Node("filter",
                                 dict(predicate=bind_scalar(e0, scope)),
                                 inputs=(frag.root,))
                plain = []
            for key_col, op in dynamic:
                if info.stream_key is None:
                    if not info.append_only:
                        raise BindError(
                            "keyless retracting dynamic-filter input")
                    from ..common.types import Field
                    frag.root = Node("row_id_gen", {},
                                     inputs=(frag.root,))
                    sch2 = Schema(tuple(scope.schema) + (
                        Field("_row_id", DataType.SERIAL),))
                    scope = Scope(sch2, dict(scope.names))
                    info = RelInfo((len(sch2) - 1,), True, info.wm_cols)
                now_f = self.graph.add(Fragment(
                    self.fid(), Node("now", {}), dispatch="broadcast"))
                frag.root = Node("dynamic_filter", dict(
                    key_col=key_col, op=op,
                    pk_indices=list(info.stream_key),
                    capacity=self.cfg("streaming_dynamic_filter_capacity",
                                      1 << 14),
                    watchdog_interval=(
                        1 if self.cfg("streaming_watchdog", 1) else None)),
                    inputs=(frag.root, Exchange(now_f.fid)))
                # output retracts when the threshold moves
                info = RelInfo(info.stream_key, False, info.wm_cols)
            w = None
            for c in plain:
                w = c if w is None else ast.BinOp("and", w, c)
            sel = ast.Select(sel.items, sel.rel, w, sel.group_by,
                             sel.order_by, sel.limit, sel.offset)
        if sel.where is not None:
            pred = bind_scalar(sel.where, scope)
            frag.root = Node("filter", dict(predicate=pred),
                             inputs=(frag.root,))

        if any(isinstance(it.expr, ast.WindowFunc) for it in sel.items):
            out = self._plan_over_window(sel, fid, scope, info, rank_filter)
            if want_top_n:
                out = self._plan_top_n(top_spec, out)
            return out

        has_agg = bool(sel.group_by) or any(
            contains_agg(it.expr) for it in sel.items)
        from ..expr.ir import InputRef

        def project_wm(exprs):
            """Watermarks survive a projection on InputRef columns (the
            project executor's default watermark_mapping)."""
            return frozenset(
                j for j, e in enumerate(exprs)
                if isinstance(e, InputRef) and e.index in info.wm_cols)

        if not has_agg:
            exprs, names = [], []
            for j, it in enumerate(sel.items):
                exprs.append(bind_scalar(it.expr, scope))
                names.append(it.alias or auto_name(it.expr, j))
            if info.append_only:
                frag.root = Node("project", dict(exprs=exprs, names=names),
                                 inputs=(frag.root,))
                out = (fid, names, [e.ret_type for e in exprs], None, True,
                       project_wm(exprs))
                if want_top_n:
                    out = self._plan_top_n(top_spec, out)
                return out
            # retracting input: its stream key must survive projection so
            # deletes keep addressing the same rows (the reference appends
            # hidden stream-key columns the same way)
            assert info.stream_key is not None
            key_pos = []
            for ki in info.stream_key:
                found = None
                for j, e in enumerate(exprs):
                    if isinstance(e, InputRef) and e.index == ki:
                        found = j
                        break
                if found is None:
                    t = scope.schema[ki].data_type
                    exprs.append(col(ki, t))
                    names.append(f"_sk{ki}")
                    found = len(exprs) - 1
                key_pos.append(found)
            frag.root = Node("project", dict(exprs=exprs, names=names),
                             inputs=(frag.root,))
            out = (fid, names, [e.ret_type for e in exprs],
                   tuple(key_pos), False, project_wm(exprs))
            if want_top_n:
                out = self._plan_top_n(top_spec, out)
            return out

        afid, names, types, pk, wm_out = self._plan_agg(sel, fid, scope,
                                                        info)
        out = (afid, names, types, pk, False, wm_out)
        if want_top_n:
            out = self._plan_top_n(top_spec, out)
        return out

    # ------------------------------------------- snapshot join-agg fusion
    def _try_snapshot_join_agg(self, sel: ast.Select):
        """Fuse the q17 shape — SELECT <global aggs over L> FROM L JOIN
        dim JOIN (SELECT k, <agg exprs> FROM L GROUP BY k) A ON A.k = L.k
        [AND residue] WHERE <single-side filters> — into ONE
        barrier-snapshot executor (stream/snapshot_join_agg.py) when
        every input is append-only. The changelog plan for this shape is
        an inherent retraction storm (each L row shifts its group's
        aggregate, re-emitting the whole group through the join);
        snapshot recompute at barriers is O(n) total. Returns a
        _plan_query result tuple, or None to fall back to the generic
        join plan (SET streaming_snapshot_fuse = 0 forces the fallback).

        Reference: dynamic_filter.rs re-evaluates a changing scalar RHS
        per barrier; this generalizes that to the join-against-own-
        aggregate sub-plan of /root/reference/e2e_test/tpch q17.
        """
        from ..common.types import Field
        from ..expr.ir import input_refs, remap_inputs

        if not self.cfg("streaming_snapshot_fuse", 1):
            return None
        if (sel.group_by or sel.order_by or sel.limit is not None
                or sel.offset):
            return None
        if not sel.items or not all(
                isinstance(it.expr, ast.Lit) or contains_agg(it.expr)
                for it in sel.items):
            return None
        if not isinstance(sel.rel, ast.JoinRel):
            return None
        leaves: list = []
        bad: list = []

        def flat(r):
            if isinstance(r, ast.JoinRel):
                if (getattr(r, "join_type", "inner") != "inner"
                        or getattr(r, "temporal", False) or r.on is None):
                    bad.append(r)
                    return
                flat(r.left)
                leaves.append((r.right, r.on))
            else:
                leaves.append((r, None))

        flat(sel.rel)
        if bad or len(leaves) != 3:
            return None
        rels = [l for l, _ in leaves]
        if not isinstance(rels[0], ast.TableRel):
            return None
        sub_pos_leaf = [i for i in (1, 2)
                        if isinstance(rels[i], ast.SubqueryRel)]
        dim_pos_leaf = [i for i in (1, 2)
                        if isinstance(rels[i], ast.TableRel)]
        if len(sub_pos_leaf) != 1 or len(dim_pos_leaf) != 1:
            return None
        fact_rel = rels[0]
        dim_rel = rels[dim_pos_leaf[0]]
        sub_rel = rels[sub_pos_leaf[0]]
        asel = sub_rel.select
        if (not isinstance(asel, ast.Select)
                or len(asel.group_by) != 1 or asel.order_by
                or asel.limit is not None or asel.offset
                or not isinstance(asel.rel, ast.TableRel)
                or asel.rel.name != fact_rel.name):
            return None
        # both scans of L must see identical rows: require a SOURCE
        # (an MV could change between the two logical scans' backfills)
        if fact_rel.name in getattr(self.catalog, "mvs", {}) \
                or dim_rel.name in getattr(self.catalog, "mvs", {}):
            return None
        try:
            fact_src = self.catalog.source(fact_rel.name)
            dim_src = self.catalog.source(dim_rel.name)
        except Exception:
            return None
        dim_pk = dim_src.options.get("primary_key")
        if dim_pk is None:
            return None    # the membership mask needs a UNIQUE dim key
        fscope = Scope.of(fact_src.schema, fact_rel.alias or fact_rel.name)
        dscope = Scope.of(dim_src.schema, dim_rel.alias or dim_rel.name)
        nl, nd = len(fscope.schema), len(dscope.schema)

        # ---- the subquery: key + agg items over its own scan scope
        ascan = Scope.of(fact_src.schema, asel.rel.alias or asel.rel.name)
        try:
            gkey = bind_scalar(asel.group_by[0], ascan)
        except BindError:
            return None
        if not isinstance(gkey, InputRef):
            return None
        fact_key = gkey.index

        def make_decomp(calls: list, scope_: Scope):
            def arg_of(e):
                try:
                    b = bind_scalar(e, scope_)
                except BindError:
                    return None
                return b.index if isinstance(b, InputRef) else None

            def decomp(e):
                if isinstance(e, ast.Func) and e.name in AGG_FUNCS:
                    if e.name == "count":
                        a = None
                        if not getattr(e, "star", False) and e.args:
                            a = arg_of(e.args[0])
                            if a is None:
                                return None
                        calls.append(AggCall(AggKind.COUNT, a,
                                             DataType.INT64, True))
                        return col(len(calls) - 1, DataType.INT64)
                    if not e.args:
                        return None
                    a = arg_of(e.args[0])
                    if a is None:
                        return None
                    at = scope_.schema[a].data_type
                    if at is DataType.VARCHAR and e.name != "count":
                        return None
                    if e.name == "avg":
                        calls.append(AggCall(AggKind.SUM, a,
                                             DataType.FLOAT64, True))
                        s_ = len(calls) - 1
                        calls.append(AggCall(AggKind.COUNT, a,
                                             DataType.INT64, True))
                        return call("divide", col(s_, DataType.FLOAT64),
                                    col(s_ + 1, DataType.INT64))
                    if e.name == "sum":
                        ret = (DataType.FLOAT64
                               if at in (DataType.FLOAT64,
                                         DataType.FLOAT32)
                               else DataType.INT64)
                        calls.append(AggCall(AggKind.SUM, a, ret, True))
                        return col(len(calls) - 1, ret)
                    kind = (AggKind.MIN if e.name == "min"
                            else AggKind.MAX)
                    calls.append(AggCall(kind, a, at, True))
                    return col(len(calls) - 1, at)
                if isinstance(e, ast.Lit):
                    return lit(e.value)
                if isinstance(e, ast.BinOp):
                    l_, r_ = decomp(e.left), decomp(e.right)
                    if l_ is None or r_ is None:
                        return None
                    return call(e.op, l_, r_)
                if isinstance(e, ast.UnOp):
                    a_ = decomp(e.arg)
                    return None if a_ is None else call(e.op, a_)
                return None
            return decomp

        sub_agg_calls: list[AggCall] = []
        decomp_sub = make_decomp(sub_agg_calls, ascan)
        a_fields, a_items, key_item = [], [], None
        # item -> (ratio, L column) where the item is `ratio * avg(column)`
        # over an integer column: a threshold NUMERIC states exactly
        a_ratio_avg = {}
        for j, it in enumerate(asel.items):
            ra = _ratio_times_avg(it.expr, ascan)
            if ra is not None:
                a_ratio_avg[j] = ra
            name = it.alias or auto_name(it.expr, j)
            if not contains_agg(it.expr):
                try:
                    b = bind_scalar(it.expr, ascan)
                except BindError:
                    return None
                if (not isinstance(b, InputRef) or b.index != fact_key
                        or key_item is not None):
                    return None
                key_item = j
                a_fields.append(Field(name, b.ret_type))
                a_items.append(None)
            else:
                e2 = decomp_sub(it.expr)
                if e2 is None:
                    return None
                a_fields.append(Field(name, e2.ret_type))
                a_items.append(e2)
        if key_item is None:
            return None
        sub_filter = None
        if asel.where is not None:
            try:
                sub_filter = bind_scalar(asel.where, ascan)
            except BindError:
                return None

        # ---- classify every ON + WHERE conjunct
        ascope = Scope.of(Schema(tuple(a_fields)), sub_rel.alias)
        parts = {dim_pos_leaf[0]: dscope, sub_pos_leaf[0]: ascope}
        full = Scope.join(Scope.join(fscope, parts[1]), parts[2])
        offs = {1: nl, 2: nl + len(parts[1].schema)}
        dim_off = offs[dim_pos_leaf[0]]
        a_off = offs[sub_pos_leaf[0]]
        na = len(a_fields)
        conjs = []
        for _, on in leaves[1:]:
            conjs += split_conjuncts(on)
        if sel.where is not None:
            conjs += split_conjuncts(sel.where)
        fact_link = dim_link = None
        fact_filters, dim_filters, residues = [], [], []
        for conj in conjs:
            p = equi_pair(conj, fscope, dscope)
            if p is not None:
                if dim_link is not None or p[1] != dim_pk:
                    return None
                dim_link = p[0]
                continue
            p = equi_pair(conj, fscope, ascope)
            if p is not None and p[1] == key_item:
                if fact_link is not None or p[0] != fact_key:
                    return None
                fact_link = p[0]
                continue
            try:
                b = bind_scalar(conj, full)
            except BindError:
                return None
            refs = input_refs(b)
            if all(i < nl for i in refs):
                fact_filters.append(b)
            elif all(dim_off <= i < dim_off + nd for i in refs):
                dim_filters.append(remap_inputs(
                    b, {i: i - dim_off for i in refs}))
            elif all(i < nl or (a_off <= i < a_off + na
                                and i - a_off != key_item)
                     for i in refs):
                residues.append(b)
            else:
                return None
        # membership is computed on the group-key column — the dim must
        # be keyed by the same L column the aggregate groups on
        if fact_link is None or dim_link is None or dim_link != fact_key:
            return None

        # sub items are numbered as the residue comes to read them: an item
        # nothing reads (a FLOAT64 threshold folded away below) is never
        # computed, nor are the aggregate calls only it used
        sub_items: list = []
        sub_pos: dict = {}

        def sub_ref(key, make):
            if key not in sub_pos:
                sub_pos[key] = len(sub_items)
                sub_items.append(make())
            e = sub_items[sub_pos[key]]
            return col(nl + sub_pos[key], e.ret_type)

        def to_residue(b):
            exact = _exact_avg_compare(
                b, nl, a_off, a_ratio_avg, fscope, sub_agg_calls, sub_ref)
            if exact is not None:
                return exact
            return remap_inputs(b, {
                i: i if i < nl else sub_ref(
                    i - a_off, lambda: a_items[i - a_off]).index
                for i in sorted(input_refs(b))})

        def combine(es):
            if not es:
                return None
            e = es[0]
            for r in es[1:]:
                e = call("and", e, r)
            return e

        residue = combine([to_residue(b) for b in residues])
        fact_filter = combine(fact_filters)
        dim_filter = combine(dim_filters)
        used_calls = sorted(set().union(
            *(input_refs(e) for e in sub_items)) if sub_items else ())
        cmap = {o: n for n, o in enumerate(used_calls)}
        sub_items = [remap_inputs(e, cmap) for e in sub_items]
        sub_agg_calls = [sub_agg_calls[o] for o in used_calls]

        # ---- final (global) aggregates over L columns only
        final_agg_calls: list[AggCall] = []
        decomp_fin = make_decomp(final_agg_calls, fscope)
        final_items, names, types = [], [], []
        for j, it in enumerate(sel.items):
            e2 = decomp_fin(it.expr)
            if e2 is None:
                return None
            final_items.append(e2)
            names.append(it.alias or auto_name(it.expr, j))
            types.append(e2.ret_type)

        # ---- everything matches: plan the two scans and emit the node
        lf, _, linfo = self.plan_rel(fact_rel)
        df, _, dinfo = self.plan_rel(dim_rel)
        if not (linfo.append_only and dinfo.append_only):
            return None
        # the fact store reserves `capacity` rows for every column it is
        # given and persists every one: hand it the columns the plan reads
        # (q17: 3 of lineitem's 16), the dim side its key and filter columns
        keep_f = {fact_key}
        keep_f |= {c.arg for c in sub_agg_calls + final_agg_calls
                   if c.arg is not None}
        for e in (sub_filter, fact_filter):
            if e is not None:
                keep_f |= input_refs(e)
        if residue is not None:
            keep_f |= {i for i in input_refs(residue) if i < nl}
        keep_d = {dim_pk} | (input_refs(dim_filter)
                             if dim_filter is not None else set())
        keep_f, keep_d = sorted(keep_f), sorted(keep_d)
        fmap = {o: n for n, o in enumerate(keep_f)}
        dmap = {o: n for n, o in enumerate(keep_d)}
        rmap = {**fmap, **{nl + k: len(keep_f) + k
                           for k in range(len(sub_items))}}
        remap = lambda e, m: None if e is None else remap_inputs(e, m)
        recall = lambda c: c if c.arg is None else replace(c, arg=fmap[c.arg])
        inputs = []
        for fid_, keep, sch in ((lf, keep_f, fscope.schema),
                                (df, keep_d, dscope.schema)):
            inp = Exchange(fid_)
            if len(keep) < len(sch) \
                    and not self._push_prune_upstream(fid_, keep, sch):
                inp = Node("project", dict(
                    exprs=[col(i, sch[i].data_type) for i in keep],
                    names=[sch[i].name for i in keep]), inputs=(inp,))
            inputs.append(inp)
        wd = 1 if self.cfg("streaming_watchdog", 1) else None
        node = Node("snapshot_join_agg", dict(
            fact_key=fmap[fact_key], dim_key=dmap[dim_pk],
            sub_agg_calls=[recall(c) for c in sub_agg_calls],
            sub_items=sub_items, residue=remap(residue, rmap),
            final_agg_calls=[recall(c) for c in final_agg_calls],
            final_items=final_items, out_names=names, out_types=types,
            fact_filter=remap(fact_filter, fmap),
            sub_filter=remap(sub_filter, fmap),
            dim_filter=remap(dim_filter, dmap),
            capacity=self.cfg("streaming_join_capacity", 1 << 17),
            dim_capacity=self.cfg("streaming_agg_capacity", 1 << 16),
            durable=self.durable(), watchdog_interval=wd),
            inputs=tuple(inputs))
        f = self.graph.add(Fragment(self.fid(), node, dispatch="simple"))
        return (f.fid, names, types, (), False, frozenset())

    # ----------------------------------------------------- optimizer passes
    def _optimize_join(self, jinfo, scope: Scope, info: RelInfo,
                       sel: ast.Select):
        """Bind-time rewrite passes on a SELECT directly over a join
        (reference: logical_optimization.rs rules, scoped to the two that
        shape device state):

        1. PREDICATE PUSHDOWN (inner joins): WHERE conjuncts touching one
           side move below the join, shrinking its probe+state input.
           (FilterJoinRule / push_down_filters.)
        2. JOIN INPUT PRUNING: each side's input narrows to the columns
           the join or the SELECT actually uses — on TPU the win is
           direct, join state is dense SoA so every pruned column is HBM
           bandwidth off the per-chunk merge. (PruneJoinRule /
           column pruning.)
        """
        node, nl = jinfo["node"], jinfo["nl"]
        lsch, rsch = jinfo["lsch"], jinfo["rsch"]
        args = node.args

        def refs(e) -> set:
            if isinstance(e, ast.ColRef):
                return {scope.resolve(e)[0]}
            if isinstance(e, ast.BinOp):
                return refs(e.left) | refs(e.right)
            if isinstance(e, ast.UnOp):
                return refs(e.arg)
            if isinstance(e, ast.Func):
                out = set()
                for a in e.args:
                    out |= refs(a)
                return out
            return set()

        # ---- 1. filter pushdown ----
        if sel.where is not None and jinfo["jt"] == "inner":
            from ..expr.ir import remap_inputs

            def push_filter(side: int, pred) -> None:
                inp = node.inputs[side]
                # absorb into a single-consumer upstream fragment so the
                # channel carries filtered chunks; else wrap locally
                if (isinstance(inp, Exchange) and
                        len(self.graph.consumers(inp.upstream)) == 1):
                    up = self.graph.fragments[inp.upstream]
                    up.root = Node("filter", dict(predicate=pred),
                                   inputs=(up.root,))
                else:
                    wrapped = Node("filter", dict(predicate=pred),
                                   inputs=(inp,))
                    node.inputs = tuple(
                        wrapped if i == side else x
                        for i, x in enumerate(node.inputs))

            keep = []
            for conj in split_conjuncts(sel.where):
                cols = refs(conj)
                if cols and max(cols) < nl:
                    push_filter(0, bind_scalar(conj, scope))
                elif cols and min(cols) >= nl:
                    push_filter(1, remap_inputs(
                        bind_scalar(conj, scope),
                        {i: i - nl for i in cols}))
                else:
                    keep.append(conj)
            w = None
            for c in keep:
                w = c if w is None else ast.BinOp("and", w, c)
            sel = ast.Select(sel.items, sel.rel, w, sel.group_by,
                             sel.order_by, sel.limit, sel.offset)

        # ---- 2. join input pruning ----
        used = set(info.stream_key or ())
        for it in sel.items:
            used |= refs(it.expr)
        if sel.where is not None:
            used |= refs(sel.where)
        for g in sel.group_by:
            used |= refs(g)
        for e, _ in sel.order_by:
            try:
                used |= refs(e)          # may be an output alias/ordinal
            except BindError:
                pass
        need_l = {i for i in used if i < nl}
        need_r = {i - nl for i in used if i >= nl}
        need_l |= set(args["left_key_indices"]) | set(args["left_pk_indices"])
        need_r |= set(args["right_key_indices"]) | set(args["right_pk_indices"])
        cond = args.get("condition")
        if cond is not None:
            from ..expr.ir import input_refs
            for i in input_refs(cond):
                (need_l if i < nl else need_r).add(i if i < nl else i - nl)
        specs = args.get("clean_specs") or (None, None)
        for s, spec in enumerate(specs):
            if spec is None:
                continue
            own, other = (need_l, need_r) if s == 0 else (need_r, need_l)
            own.add(spec[1])
            if spec[0] == "band":
                other.add(spec[2])
                if len(spec) > 4 and spec[4] is not None:
                    own.add(spec[4])
        if len(need_l) == nl and len(need_r) == len(rsch):
            return scope, info, sel     # nothing to prune

        keep_l, keep_r = sorted(need_l), sorted(need_r)
        lmap = {o: n for n, o in enumerate(keep_l)}
        rmap = {o: n for n, o in enumerate(keep_r)}
        jmap = {**{o: lmap[o] for o in keep_l},
                **{o + nl: len(keep_l) + rmap[o] for o in keep_r}}
        new_inputs = []
        for keep, sch, inp in ((keep_l, lsch, node.inputs[0]),
                               (keep_r, rsch, node.inputs[1])):
            # prefer absorbing the pruning into the upstream fragment
            # (single-consumer): its projects then COMPUTE only the kept
            # columns and the channel carries narrow chunks
            if (isinstance(inp, Exchange)
                    and self._push_prune_upstream(inp.upstream, keep, sch)):
                new_inputs.append(inp)
            else:
                new_inputs.append(Node("project", dict(
                    exprs=[col(i, sch[i].data_type) for i in keep],
                    names=[sch[i].name for i in keep]),
                    inputs=(inp,)))
        node.inputs = tuple(new_inputs)
        args["left_key_indices"] = [lmap[i] for i in args["left_key_indices"]]
        args["right_key_indices"] = [rmap[i] for i in args["right_key_indices"]]
        args["left_pk_indices"] = [lmap[i] for i in args["left_pk_indices"]]
        args["right_pk_indices"] = [rmap[i] for i in args["right_pk_indices"]]
        if cond is not None:
            from ..expr.ir import remap_inputs
            args["condition"] = remap_inputs(cond, jmap)
        if any(specs):
            def remap_spec(spec, s):
                if spec is None:
                    return None
                m, om = (lmap, rmap) if s == 0 else (rmap, lmap)
                if spec[0] == "band":
                    cap = (m[spec[4]] if len(spec) > 4
                           and spec[4] is not None else None)
                    return ("band", m[spec[1]], om[spec[2]], spec[3], cap)
                return (spec[0], m[spec[1]]) + tuple(spec[2:])
            args["clean_specs"] = (remap_spec(specs[0], 0),
                                   remap_spec(specs[1], 1))
        # rebuild scope / RelInfo over the pruned joined schema
        new_fields = tuple(scope.schema[o]
                           for o in sorted(jmap, key=lambda o: jmap[o]))
        new_scope = Scope(Schema(new_fields),
                          {k: (jmap[i], t) for k, (i, t) in
                           scope.names.items() if i in jmap})
        new_info = RelInfo(
            stream_key=(None if info.stream_key is None
                        else tuple(jmap[i] for i in info.stream_key)),
            append_only=info.append_only,
            wm_cols=frozenset(jmap[i] for i in info.wm_cols if i in jmap))
        return new_scope, new_info, sel

    def _push_prune_upstream(self, up_fid: int, keep: list,
                             sch: Schema) -> bool:
        """Absorb an input pruning into the upstream fragment when this
        join is its only consumer. A `project` root narrows to the kept
        exprs (unneeded window/passthrough computations disappear
        entirely); `row_id_gen` composes through (its serial column is
        always the last kept index); a bare `no_op` gets the project
        grafted above it. Returns False when the upstream is shared or
        has an unsupported root (caller falls back to a local project)."""
        if len(self.graph.consumers(up_fid)) != 1:
            return False
        frag = self.graph.fragments[up_fid]
        # a hash-dispatching fragment routes on OUTPUT positions — they
        # move with the pruning (or block it if a dist key is dropped)
        if frag.dispatch == "hash" and frag.dist_key_indices:
            pos = {o: n for n, o in enumerate(keep)}
            if not all(d in pos for d in frag.dist_key_indices):
                return False
            new_dist = tuple(pos[d] for d in frag.dist_key_indices)
        else:
            new_dist = None

        def prune_project(p: Node, keep_idx: list) -> Node:
            exprs = p.args["exprs"]
            names = p.args.get("names") or [f"e{i}"
                                            for i in range(len(exprs))]
            args = dict(exprs=[exprs[i] for i in keep_idx],
                        names=[names[i] for i in keep_idx])
            tf = p.args.get("watermark_transforms")
            if tf:
                pos = {o: n for n, o in enumerate(keep_idx)}
                new_tf = {}
                for in_col, spec in tf.items():
                    specs = spec if isinstance(spec, list) else [spec]
                    kept = [(pos[o], fn) for o, fn in specs if o in pos]
                    if kept:
                        new_tf[in_col] = kept
                if new_tf:
                    args["watermark_transforms"] = new_tf
            return Node("project", args, inputs=p.inputs)

        def graft(inner: Node, keep_idx: list) -> Node:
            return Node("project", dict(
                exprs=[col(i, sch[i].data_type) for i in keep_idx],
                names=[sch[i].name for i in keep_idx]),
                inputs=(inner,))

        root = frag.root
        if root.kind == "project":
            frag.root = prune_project(root, keep)
        elif root.kind == "row_id_gen":
            rid = len(sch) - 1
            if rid not in keep:
                return False
            inner_keep = [i for i in keep if i < rid]
            inner = root.inputs[0]
            root.inputs = ((prune_project(inner, inner_keep)
                            if inner.kind == "project"
                            else graft(inner, inner_keep)),)
        else:
            # any other root (filter, no_op, stream_scan, agg...): graft
            # the narrowing project on top — the channel still narrows
            frag.root = graft(root, keep)
        if new_dist is not None:
            frag.dist_key_indices = new_dist
        return True

    def _plan_over_window(self, sel: ast.Select, fid: int, scope: Scope,
                          info: RelInfo, rank_filter=None):
        """SELECT items with OVER clauses -> a general_over_window node
        computing every window function in one pass (reference:
        StreamOverWindow from LogicalOverWindow; all calls must share one
        window definition, like the reference's OverWindow grouping).

        With `rank_filter` = (limit, emit_rank) — the SELECT is the
        subquery of `WHERE rank <= limit` and its one window function is
        ROW_NUMBER() with a PARTITION BY (`_match_rank_filter`) — the node
        is a `retract_top_n` WITH group keys instead (reference:
        over_window_to_topn_rule -> StreamGroupTopN, append-only when its
        input is): order = the window's ORDER BY, then the stream key
        ascending; the rank is an output column only where the outer query
        reads it. It stays in the fragment of its input; where the session
        is parallel that fragment hash-dispatches on the partition columns
        into a fragment of the top-N's own."""
        from ..common.types import Field
        from ..stream.general_over_window import WindowSpec
        frag = self.graph.fragments[fid]
        if sel.group_by:
            raise BindError(
                "window functions cannot be combined with GROUP BY in "
                "one SELECT; aggregate in a subquery first")
        wfs = [it.expr for it in sel.items
               if isinstance(it.expr, ast.WindowFunc)]
        over0 = (tuple(map(repr, wfs[0].partition_by)),
                 tuple((repr(e), d) for e, d in wfs[0].order_by))
        for w in wfs[1:]:
            if (tuple(map(repr, w.partition_by)),
                    tuple((repr(e), d) for e, d in w.order_by)) != over0:
                raise BindError(
                    "all window functions in one SELECT must share the "
                    "same OVER (PARTITION BY ... ORDER BY ...) clause")

        def col_of(e) -> int:
            if not isinstance(e, ast.ColRef):
                raise BindError(
                    "window PARTITION BY / ORDER BY / arguments must be "
                    "plain columns")
            return scope.resolve(e)[0]

        partition_by = [col_of(e) for e in wfs[0].partition_by]
        order_specs = []
        for e, desc in wfs[0].order_by:
            i = col_of(e)
            if scope.schema[i].data_type is DataType.VARCHAR:
                raise BindError(
                    "window ORDER BY over VARCHAR is unsupported (dict "
                    "encoding is not lexicographic)")
            order_specs.append((i, bool(desc)))
        if not order_specs:
            raise BindError("window functions need ORDER BY in OVER()")

        # retractions address rows by the stream key; keyless append-only
        # inputs get a generated row id (same as join inputs)
        sk = info.stream_key
        if sk is None:
            if not info.append_only:
                raise BindError("keyless retracting over-window input")
            frag.root = Node("row_id_gen", {}, inputs=(frag.root,))
            sch2 = Schema(tuple(scope.schema)
                          + (Field("_row_id", DataType.SERIAL),))
            scope = Scope(sch2, dict(scope.names))
            sk = (len(sch2) - 1,)

        if rank_filter is not None:
            return self._plan_group_top_n(
                sel, fid, scope, info, rank_filter, partition_by,
                order_specs, sk)

        windows = []
        for j, w in enumerate(wfs):
            name = w.func.name
            if name in ("row_number", "rank", "dense_rank"):
                windows.append(WindowSpec(name, name=f"w{j}"))
            elif name in ("lag", "lead"):
                if not w.func.args:
                    raise BindError(f"window {name}() needs an argument")
                ai = col_of(w.func.args[0])
                off = 1
                if len(w.func.args) > 1:
                    a1 = w.func.args[1]
                    if not (isinstance(a1, ast.Lit)
                            and isinstance(a1.value, int)
                            and a1.value >= 1):
                        raise BindError(
                            f"{name}() offset must be a positive "
                            "integer literal")
                    off = a1.value
                windows.append(WindowSpec(
                    name, arg=ai, offset=off, name=f"w{j}"))
            elif name == "first_value":
                if not w.func.args:
                    raise BindError("first_value() needs an argument")
                windows.append(WindowSpec(
                    name, arg=col_of(w.func.args[0]), name=f"w{j}"))
            elif name in ("sum", "count", "avg"):
                if not w.func.args:
                    raise BindError(f"window {name}() needs an argument")
                ai = col_of(w.func.args[0])
                if (name in ("sum", "avg")
                        and scope.schema[ai].data_type
                        is DataType.VARCHAR):
                    raise BindError(
                        f"window {name}() over VARCHAR is meaningless "
                        "(dict ids are not numbers)")
                windows.append(WindowSpec(
                    name, arg=ai, preceding=w.preceding, name=f"w{j}"))
            else:
                raise BindError(
                    f"unsupported window function {name!r} (have: "
                    "row_number, rank, dense_rank, lag, lead, "
                    "first_value, sum, count, avg)")

        eowc = getattr(sel, "emit_on_close", False)
        if eowc:
            # EMIT ON WINDOW CLOSE: the leading ORDER BY column must be
            # watermarked ascending so row finality is decidable
            oc, odesc = order_specs[0]
            if odesc or oc not in info.wm_cols:
                raise BindError(
                    "EMIT ON WINDOW CLOSE needs the leading window "
                    "ORDER BY column ascending and watermarked")
            if any(w.kind == "lead" for w in windows):
                raise BindError(
                    "EMIT ON WINDOW CLOSE cannot finalize lead()")
        ow_args = dict(
            partition_by=partition_by, order_specs=order_specs,
            windows=windows, pk_indices=list(sk),
            capacity=self.cfg("streaming_over_window_capacity", 1 << 14),
            durable=self.durable())
        if not eowc:
            # mesh mode: partitions shard over the device mesh inside
            # ONE executor (partition-key routing keeps frames local);
            # the EOWC variant stays single-device (frontier state is
            # host-ordered)
            ow_args.update(
                mesh_devices=self.cfg("streaming_parallelism_devices", 1),
                watchdog_interval=(
                    1 if self.cfg("streaming_watchdog", 1) else None))
        frag.root = Node(
            "eowc_over_window" if eowc else "general_over_window",
            ow_args, inputs=(frag.root,))
        in_width = len(scope.schema)
        win_fields = []
        out_sch = list(scope.schema)
        for w2 in windows:
            t = w2.ret_type(scope.schema)
            out_sch.append(Field(w2.name, t))
            win_fields.append(t)
        ext_scope = Scope(Schema(tuple(out_sch)), dict(scope.names))

        # final projection: SELECT order + hidden stream-key columns
        exprs, names = [], []
        wj = 0
        for j, it in enumerate(sel.items):
            if isinstance(it.expr, ast.WindowFunc):
                exprs.append(col(in_width + wj, win_fields[wj]))
                names.append(it.alias or f"w{wj}")
                wj += 1
            else:
                exprs.append(bind_scalar(it.expr, ext_scope))
                names.append(it.alias or auto_name(it.expr, j))
        from ..expr.ir import InputRef
        key_pos = _keep_stream_key(exprs, names, sk, ext_scope.schema)
        frag.root = Node("project", dict(exprs=exprs, names=names),
                         inputs=(frag.root,))
        # EOWC output is append-only (final rows, exactly once) and
        # carries the watermark forward on the order column if selected
        wm_out = frozenset()
        if eowc:
            oc = order_specs[0][0]
            wm_out = frozenset(
                j2 for j2, e2 in enumerate(exprs)
                if isinstance(e2, InputRef) and e2.index == oc)
        return (fid, names, [e.ret_type for e in exprs], tuple(key_pos),
                eowc, wm_out)

    def _plan_group_top_n(self, sel, fid: int, scope: Scope, info: RelInfo,
                          rank_filter, partition_by, order_specs, sk):
        """The rank-filter half of `_plan_over_window`: the node, then the
        SELECT's projection with the hidden stream key."""
        limit, emit_rank = rank_filter
        frag = self.graph.fragments[fid]
        ordered = {c for c, _ in order_specs}
        md = self.cfg("streaming_parallelism_devices", 1)
        args = dict(
            group_key_indices=list(partition_by),
            order_specs=list(order_specs) + [(k, False) for k in sk
                                             if k not in ordered],
            limit=limit, offset=0, durable=self.durable(),
            pk_indices=list(sk),
            capacity=self.cfg("streaming_top_n_capacity", 1 << 14),
            mesh_devices=md,
            watchdog_interval=(
                1 if self.cfg("streaming_watchdog", 1) else None),
            append_only=bool(info.append_only), emit_rank=emit_rank)
        if self.parallelism > 1 and md == 1:
            # every group whole on one actor
            frag.dispatch = "hash"
            frag.dist_key_indices = tuple(partition_by)
            # (its own dispatch: by the stream key, re-pointed below at
            # where the projection leaves it)
            frag = self.graph.add(Fragment(self.fid(), Node(
                "retract_top_n", args, inputs=(Exchange(fid),)),
                dispatch="hash", dist_key_indices=tuple(sk),
                parallelism=self.parallelism))
            fid = frag.fid
        else:
            frag.root = Node("retract_top_n", args, inputs=(frag.root,))
        in_width = len(scope.schema)
        exprs, names = [], []
        for j, it in enumerate(sel.items):
            if isinstance(it.expr, ast.WindowFunc):
                if emit_rank:
                    exprs.append(col(in_width, DataType.INT64))
                    names.append(it.alias)
            else:
                exprs.append(bind_scalar(it.expr, scope))
                names.append(it.alias or auto_name(it.expr, j))
        key_pos = _keep_stream_key(exprs, names, sk, scope.schema)
        frag.root = Node("project", dict(exprs=exprs, names=names),
                         inputs=(frag.root,))
        if frag.dispatch == "hash":
            frag.dist_key_indices = tuple(key_pos)
        # ranks can change retroactively: no watermark survives, and the
        # output retracts whatever the input did
        return (fid, names, [e.ret_type for e in exprs], tuple(key_pos),
                False, frozenset())

    def _plan_top_n(self, top_spec, planned):
        """Streaming ORDER BY + LIMIT -> RetractableTopN over the query's
        changelog (reference: StreamTopN; retraction-capable because the
        input may be an agg/join changelog)."""
        order_by, limit, offset = top_spec
        fid, names, types, pk_hint, append_only, _wm = planned
        frag = self.graph.fragments[fid]
        order_specs = []
        for e, desc in order_by:
            idx = None
            if isinstance(e, ast.Lit) and isinstance(e.value, int):
                idx = e.value - 1
            elif isinstance(e, ast.ColRef) and e.qualifier is None \
                    and e.name in names:
                idx = names.index(e.name)
            if idx is None or not 0 <= idx < len(names):
                raise BindError(
                    "streaming ORDER BY must name an output column")
            if types[idx] is DataType.VARCHAR:
                # dict ids order by insertion, not lexicographically; a
                # streaming TopN over them would silently return wrong
                # rows (ADVICE r3 #2) — the batch path ranks decoded
                # strings, so point users there
                raise BindError(
                    "streaming ORDER BY over VARCHAR is unsupported "
                    "(dict encoding is not lexicographic); ORDER BY in "
                    "a batch SELECT over the MV instead")
            order_specs.append((idx, bool(desc)))
        if pk_hint is None:
            raise BindError(
                "streaming TopN over a keyless stream is unsupported "
                "(add GROUP BY or aggregate first)")
        # the TopN is a SINGLETON fragment (default parallelism=1)
        # downstream of the (possibly hash-parallel) input: per-shard
        # top-Ns would union to up to limit*parallelism wrong rows
        # (reference: StreamTopN is a singleton below the hash agg).
        # Mesh mode: still ONE actor, but the store shards over the
        # N-device mesh inside the executor (stream-key routing +
        # candidate all_gather keep the global rank exact)
        md = self.cfg("streaming_parallelism_devices", 1)
        wd = 1 if self.cfg("streaming_watchdog", 1) else None
        top = self.graph.add(Fragment(self.fid(), Node(
            "retract_top_n", dict(
                group_key_indices=(), order_specs=order_specs,
                limit=limit, offset=offset, durable=self.durable(),
                pk_indices=list(pk_hint),
                capacity=self.cfg("streaming_top_n_capacity", 1 << 14),
                mesh_devices=md,
                watchdog_interval=wd),
            inputs=(Exchange(fid),)), dispatch="simple"))
        # ranks can change retroactively: no watermark survives a TopN
        return top.fid, names, types, pk_hint, False, frozenset()

    def _plan_agg(self, sel: ast.Select, fid: int, scope: Scope,
                  info: RelInfo):
        from ..common.types import Field
        frag = self.graph.fragments[fid]
        # pre-project: group keys then agg args
        keys = [bind_scalar(g, scope) for g in sel.group_by]
        key_names = [auto_name(g, j) for j, g in enumerate(sel.group_by)]
        agg_specs = []           # (kind, pre_col or None)
        pre_exprs = list(keys)
        pre_names = list(key_names)

        def add_arg(e) -> int:
            pre_exprs.append(bind_scalar(e, scope))
            pre_names.append(f"a{len(pre_exprs)}")
            return len(pre_exprs) - 1

        # map SELECT items onto (group key | agg output) slots
        items_plan = []          # per item: ("key", idx) | ("agg", idx) | ("avg", s, c)
        agg_calls: list[AggCall] = []

        def add_call(kind: AggKind, arg: Optional[int],
                     ret: DataType) -> int:
            # append-only inputs get the cheap agg variants (running
            # max/min instead of retractable top-K buffers) — the
            # reference picks them by the same plan property
            agg_calls.append(AggCall(kind, arg, ret,
                                     append_only=info.append_only))
            return len(agg_calls) - 1

        nk = len(keys)

        def agg_post(e) -> Expr:
            """One aggregate call -> its post-project expression over
            [keys..., agg outputs...]."""
            if e.name in ("bool_and", "bool_or"):
                # fully retractable via two counts (reference
                # impl/src/aggregate/bool_and.rs keeps the same pair):
                # cn = non-null inputs, cf = false (bool_and) / true
                # (bool_or) inputs; NULL when cn = 0
                x = e.args[0]
                cn = add_call(AggKind.COUNT, add_arg(x), DataType.INT64)
                inner = ast.UnOp("not", x) if e.name == "bool_and" else x
                hit = ast.Func("case", [inner, ast.Lit(1)])
                cf = add_call(AggKind.COUNT, add_arg(hit),
                              DataType.INT64)
                cond = call("greater_than",
                            col(nk + cn, DataType.INT64), lit(0))
                val = call("equal" if e.name == "bool_and"
                           else "greater_than",
                           col(nk + cf, DataType.INT64), lit(0))
                return call("case", cond, val)
            if e.name == "approx_count_distinct":
                # 8 hidden register-word calls + estimate projection
                # (expr/hll.py); NULL when the group saw no rows
                if not info.append_only:
                    raise BindError(
                        "approx_count_distinct needs an append-only "
                        "input (register max cannot retract)")
                a = add_arg(e.args[0])
                cn = add_call(AggKind.COUNT, a, DataType.INT64)
                lanes = []
                for L in range(8):
                    agg_calls.append(AggCall(
                        AggKind.HLL_REG, a, DataType.INT64,
                        append_only=True, lane=L))
                    lanes.append(len(agg_calls) - 1)
                est = call("hll_estimate",
                           *[col(nk + j, DataType.INT64) for j in lanes])
                cond = call("greater_than",
                            col(nk + cn, DataType.INT64), lit(0))
                return call("case", cond, est)
            if e.name == "count":
                idx = add_call(AggKind.COUNT,
                               None if e.star else add_arg(e.args[0]),
                               DataType.INT64)
                return col(nk + idx, DataType.INT64)
            if e.name == "avg":
                a = add_arg(e.args[0])
                s = add_call(AggKind.SUM, a, DataType.FLOAT64)
                c = add_call(AggKind.COUNT, a, DataType.INT64)
                return call("divide", col(nk + s, DataType.FLOAT64),
                            col(nk + c, DataType.INT64))
            if e.name == "sum":
                a = add_arg(e.args[0])
                at = pre_exprs[a].ret_type
                ret = (DataType.FLOAT64
                       if at in (DataType.FLOAT64, DataType.FLOAT32)
                       else DataType.INT64)
                return col(nk + add_call(AggKind.SUM, a, ret), ret)
            a = add_arg(e.args[0])
            kind = AggKind.MIN if e.name == "min" else AggKind.MAX
            at = pre_exprs[a].ret_type
            if at is DataType.VARCHAR:
                # same hazard as the streaming ORDER BY guard: dict ids
                # are not lexicographic, and the stream agg reduces raw
                # ids — batch SELECTs rank the decoded strings instead
                raise BindError(
                    f"streaming {e.name}() over VARCHAR is unsupported "
                    "(dict encoding is not lexicographic); aggregate in "
                    "a batch SELECT over the MV instead")
            return col(nk + add_call(kind, a, at), at)

        def post_of(e) -> Expr:
            """Scalar expression OVER aggregates/keys (sum(x)/7.0,
            0.2*avg(q), sum(x)*(k+1), ...) -> post-project expression
            (reference: the planner splits such items into LogicalAgg +
            LogicalProject the same way). A GROUP BY key may match at
            ANY level; other agg-free leaves must be literal-only."""
            if isinstance(e, ast.Func) and e.name in AGG_FUNCS:
                return agg_post(e)
            if isinstance(e, ast.Lit):
                return lit(e.value)
            if not contains_agg(e):
                bound = bind_scalar(e, scope)
                for kj, ke in enumerate(keys):
                    if repr(ke) == repr(bound):
                        return col(kj, keys[kj].ret_type)
            if isinstance(e, ast.BinOp):
                return call(e.op, post_of(e.left), post_of(e.right))
            if isinstance(e, ast.UnOp):
                return call(e.op, post_of(e.arg))
            raise BindError(
                f"{e}: non-aggregate parts of a SELECT item must appear "
                f"in GROUP BY")

        post, names = [], []
        for j, it in enumerate(sel.items):
            e = it.expr
            names.append(it.alias or auto_name(e, j))
            if not contains_agg(e):
                bound = bind_scalar(e, scope)
                for kj, ke in enumerate(keys):
                    if repr(ke) == repr(bound):
                        items_plan.append(("key", kj))
                        post.append(col(kj, keys[kj].ret_type))
                        break
                else:
                    raise BindError(
                        f"{it.alias or e}: non-aggregate SELECT item "
                        f"must appear in GROUP BY")
            else:
                items_plan.append(("expr",))
                post.append(post_of(e))

        frag.root = Node("project", dict(exprs=pre_exprs, names=pre_names),
                         inputs=(frag.root,))
        # group keys that are direct refs to watermarked input columns:
        # the first becomes the agg's state-cleaning column (groups below
        # the watermark can never change again — reference: the agg's
        # state-cleaning watermark from watermark inference)
        from ..expr.ir import InputRef
        wm_keys = [kj for kj, ke in enumerate(keys)
                   if isinstance(ke, InputRef) and ke.index in info.wm_cols]
        wd = 1 if self.cfg("streaming_watchdog", 1) else None
        if keys:
            frag.dispatch = "hash"
            frag.dist_key_indices = tuple(range(len(keys)))
            # mesh mode: ONE actor whose state shards over an N-device
            # jax Mesh inside the executor (the dispatcher+merge pair
            # collapses into the jitted step; SURVEY §2.3)
            md = self.cfg("streaming_parallelism_devices", 1)
            agg = self.graph.add(Fragment(self.fid(), Node(
                "hash_agg", dict(
                    group_key_indices=list(range(len(keys))),
                    agg_calls=agg_calls, durable=self.durable(),
                    capacity=self.cfg("streaming_agg_capacity", 1 << 16),
                    cleaning_watermark_col=(wm_keys[0] if wm_keys
                                            else None),
                    mesh_devices=md,
                    watchdog_interval=wd),
                inputs=(Exchange(fid),)),
                dispatch="hash",
                dist_key_indices=tuple(range(len(keys))),
                parallelism=(1 if md > 1 else self.parallelism)))
        else:
            # global aggregation: a singleton SimpleAgg fragment
            # (reference: DistId::Singleton, simple_agg.rs)
            frag.dispatch = "simple"
            agg = self.graph.add(Fragment(self.fid(), Node(
                "simple_agg", dict(agg_calls=agg_calls, durable=self.durable()),
                inputs=(Exchange(fid),)),
                dispatch="simple"))

        # MV pk = the group keys, which must survive projection: append any
        # key not already selected
        pk = []
        key_out = {}
        for kj in range(nk):
            found = None
            for j, plan in enumerate(items_plan):
                if plan[0] == "key" and plan[1] == kj:
                    found = j
                    break
            if found is None:
                post.append(col(kj, keys[kj].ret_type))
                names.append(f"_key{kj}")
                found = len(post) - 1
            pk.append(found)
            key_out[kj] = found
        agg.root = Node("project", dict(exprs=post, names=names),
                        inputs=(agg.root,))
        # group-key watermarks pass through the agg re-indexed, then
        # through the post-project on their InputRef positions
        wm_out = frozenset(key_out[kj] for kj in wm_keys)
        return (agg.fid, names, [e.ret_type for e in post], tuple(pk),
                wm_out)


def _keep_stream_key(exprs: list, names: list, sk, schema) -> list:
    """Positions of the stream-key columns `sk` in the projection `exprs` /
    `names`, each appended as a hidden `_sk<i>` column where the SELECT
    does not carry it (the reference appends hidden stream-key columns the
    same way)."""
    from ..expr.ir import InputRef
    key_pos = []
    for ki in sk:
        found = next((j for j, e in enumerate(exprs)
                      if isinstance(e, InputRef) and e.index == ki), None)
        if found is None:
            exprs.append(col(ki, schema[ki].data_type))
            names.append(f"_sk{ki}")
            found = len(exprs) - 1
        key_pos.append(found)
    return key_pos


def _names_column(e, name: str) -> bool:
    """Whether the AST `e` reads a column called `name` (or `*`)."""
    if isinstance(e, ast.ColRef):
        return e.name in (name, "*")
    if isinstance(e, (list, tuple)):
        return any(_names_column(x, name) for x in e)
    if hasattr(e, "__dataclass_fields__") and not isinstance(
            e, (ast.Select, ast.SubqueryRel, ast.TableRel, ast.WindowRel,
                ast.JoinRel)):
        return any(_names_column(getattr(e, f), name)
                   for f in e.__dataclass_fields__)
    return False


def _rank_bound(conj, name: str, qualifier) -> Optional[int]:
    """N where `conj` is `name <= N`, `name < N + 1`, `name = 1` (or the
    mirrored comparison) over a plain reference to the rank column."""
    if not isinstance(conj, ast.BinOp):
        return None
    flip = {"less_than": "greater_than", "greater_than": "less_than",
            "less_than_or_equal": "greater_than_or_equal",
            "greater_than_or_equal": "less_than_or_equal",
            "equal": "equal"}
    op, ref, lit_ = conj.op, conj.left, conj.right
    if isinstance(ref, ast.Lit) and op in flip:
        op, ref, lit_ = flip[op], lit_, ref
    if not (isinstance(ref, ast.ColRef) and ref.name == name
            and ref.qualifier in (None, qualifier)
            and isinstance(lit_, ast.Lit) and isinstance(lit_.value, int)
            and not isinstance(lit_.value, bool)):
        return None
    n = {"less_than_or_equal": lit_.value, "less_than": lit_.value - 1,
         "equal": 1 if lit_.value == 1 else None}.get(op)
    return n if n is not None and n >= 1 else None


def _match_rank_filter(rel, where, sel: ast.Select):
    """((limit, emit_rank), the rest of `where`) where `rel` is a subquery
    whose ONE window function is an aliased ROW_NUMBER() OVER (PARTITION BY
    .. ORDER BY ..) and a conjunct of `where` bounds that column from
    above; None otherwise (RANK() with ties, several window functions, no
    partition: the general over-window plan). `emit_rank`: whether
    anything else of the outer SELECT reads the column."""
    if not isinstance(rel, ast.SubqueryRel) or where is None:
        return None
    inner = rel.select
    if (inner.group_by or inner.order_by or inner.limit is not None
            or inner.offset or getattr(inner, "emit_on_close", False)):
        return None
    wfs = [it for it in inner.items if isinstance(it.expr, ast.WindowFunc)]
    if len(wfs) != 1:
        return None
    w, name = wfs[0].expr, wfs[0].alias
    if (name is None or w.func.name != "row_number" or not w.partition_by
            or not w.order_by):
        return None
    limit, rest = None, []
    for conj in split_conjuncts(where):
        n = _rank_bound(conj, name, rel.alias)
        if n is None:
            rest.append(conj)
        else:
            limit = n if limit is None else min(limit, n)
    if limit is None:
        return None
    emit_rank = _names_column(
        [[it.expr for it in sel.items], rest, sel.group_by,
         [e for e, _ in sel.order_by]], name)
    remaining = None
    for c in rest:
        remaining = c if remaining is None else ast.BinOp("and", remaining, c)
    return (limit, emit_rank), remaining


def split_conjuncts(e) -> list:
    if isinstance(e, ast.BinOp) and e.op == "and":
        return split_conjuncts(e.left) + split_conjuncts(e.right)
    return [e]


def band_bound(conj, ls: Scope, rs: Scope, lwm: frozenset, rwm: frozenset):
    """Interval-join cleaning derivation (reference: the condition
    analysis behind stream interval joins): a comparison conjunct
    normalizing to `X.a > Y.o + d` (op may be any of > >= < <=, the small
    side affine in one column) lets side X evict rows with a below
    wm(Y.o) + d — every FUTURE Y row has o >= wm(Y.o), so old X rows fall
    out of every future band. Requires Y.o to carry a watermark. Returns
    (side_of_X, a_index, o_index, d) or None."""
    if not isinstance(conj, ast.BinOp):
        return None
    if conj.op in ("greater_than", "greater_than_or_equal"):
        big, small = conj.left, conj.right
    elif conj.op in ("less_than", "less_than_or_equal"):
        big, small = conj.right, conj.left
    else:
        return None

    def affine(e):
        if isinstance(e, ast.ColRef):
            return e, 0
        if isinstance(e, ast.BinOp) and e.op in ("add", "subtract"):
            if (isinstance(e.left, ast.ColRef) and isinstance(e.right, ast.Lit)
                    and isinstance(e.right.value, int)):
                return e.left, (e.right.value if e.op == "add"
                                else -e.right.value)
            if (e.op == "add" and isinstance(e.right, ast.ColRef)
                    and isinstance(e.left, ast.Lit)
                    and isinstance(e.left.value, int)):
                return e.right, e.left.value
        return None

    bg = affine(big)
    sm = affine(small)
    if bg is None or sm is None:
        return None
    # normalize (big_col + bd) > (small_col + sd)  ->  big_col >
    # small_col + (sd - bd), so `b.dt <= a.dt + 10` also cleans side a
    big, bd = bg
    other_ref, sd = sm
    delta = sd - bd

    def side_of(ref):
        try:
            return ("l", ls.resolve(ref)[0])
        except BindError:
            pass
        try:
            return ("r", rs.resolve(ref)[0])
        except BindError:
            return None

    sb, so = side_of(big), side_of(other_ref)
    if sb is None or so is None or sb[0] == so[0]:
        return None
    if (so[1] not in lwm) if so[0] == "l" else (so[1] not in rwm):
        return None
    return sb[0], sb[1], so[1], delta


def equi_pair(e, ls: Scope, rs: Scope) -> Optional[tuple[int, int]]:
    """col_of_left = col_of_right -> (left_idx, right_idx)."""
    if not (isinstance(e, ast.BinOp) and e.op == "equal"):
        return None
    a, b = e.left, e.right
    if not (isinstance(a, ast.ColRef) and isinstance(b, ast.ColRef)):
        return None

    def side(ref):
        try:
            return ("l", ls.resolve(ref)[0])
        except BindError:
            pass
        try:
            return ("r", rs.resolve(ref)[0])
        except BindError:
            return None

    sa, sb = side(a), side(b)
    if sa is None or sb is None or sa[0] == sb[0]:
        return None
    if sa[0] == "l":
        return (sa[1], sb[1])
    return (sb[1], sa[1])


def expand_star(items, schema) -> list:
    """SELECT * -> one item per schema column (aliases = column names),
    skipping internal columns like _row_id."""
    out = []
    for it in items:
        if isinstance(it.expr, ast.ColRef) and it.expr.name == "*":
            for f in schema:
                if not f.name.startswith("_"):
                    out.append(ast.SelectItem(ast.ColRef(f.name), f.name))
        else:
            out.append(it)
    return out


def auto_name(e, j: int) -> str:
    if isinstance(e, ast.ColRef):
        return e.name
    if isinstance(e, ast.Func):
        return e.name
    return f"expr{j}"

_INTS = (DataType.INT16, DataType.INT32, DataType.INT64)


def _ratio_times_avg(e, scope: Scope):
    """(ratio, column) where the AST `e` is `avg(column)` over an integer
    column times or over numeric literals (`0.2 * avg(q)`, `avg(q) / 5`),
    the ratio as the exact fraction the literals' decimal text states
    (`0.2` is 1/5, not the float64 nearest to it); else None."""
    def number(x):
        if isinstance(x, ast.Lit) and not isinstance(x.value, bool) \
                and isinstance(x.value, (int, float)):
            return Fraction(repr(x.value))
        return None

    if isinstance(e, ast.Func) and e.name == "avg" and len(e.args) == 1 \
            and isinstance(e.args[0], ast.ColRef):
        try:
            i, t = scope.resolve(e.args[0])
        except BindError:
            return None
        return (Fraction(1), i) if t in _INTS else None
    if not isinstance(e, ast.BinOp) or e.op not in ("multiply", "divide"):
        return None
    c, inner = number(e.right), _ratio_times_avg(e.left, scope)
    if e.op == "multiply" and inner is None:
        c, inner = number(e.left), _ratio_times_avg(e.right, scope)
    if c is None or inner is None or c <= 0:
        return None
    return (inner[0] * c if e.op == "multiply" else inner[0] / c), inner[1]


def _exact_avg_compare(b, nl: int, a_off: int, ratio_avg: dict,
                       fscope: Scope, sub_agg_calls: list, sub_ref):
    """`x < c * avg(y)` (any of the four orderings, either way round)
    with `x` an integer L column, `y` an integer L column and `c` a
    decimal literal, as an INTEGER comparison: upstream evaluates the
    right side in NUMERIC, exactly, so with c = num / den a row with
    `den * x * count = num * sum` is a tie and `<` leaves it out. In
    FLOAT64 the product `0.2 * (sum / count)` rounds, and on a TPU,
    where an f64 is two f32, it can land on the other side of `x`. For
    an integer x, `x < r` is `x < ceil(r)` and `x <= r` is `x <=
    floor(r)`: one INT64 threshold a group, `ceil` or `floor` of `num *
    sum / (den * count)` by floor division. Returns the rewritten
    conjunct over [L columns ++ sub items], or None where `b` is not of
    that shape."""
    flip = {"less_than": "greater_than",
            "less_than_or_equal": "greater_than_or_equal",
            "greater_than": "less_than",
            "greater_than_or_equal": "less_than_or_equal"}
    if not (isinstance(b, FuncCall) and b.name in flip
            and all(isinstance(a, InputRef) for a in b.args)):
        return None
    (x, thr), op = b.args, b.name
    if x.index >= nl:
        (thr, x), op = b.args, flip[b.name]
    j = thr.index - a_off
    if not (x.index < nl and j in ratio_avg
            and fscope.schema[x.index].data_type in _INTS):
        return None
    ratio, y = ratio_avg[j]
    if not 0 < ratio.numerator < 1 << 20 \
            or not ratio.denominator < 1 << 20:
        return None
    up = op in ("less_than", "greater_than_or_equal")

    def threshold():
        sub_agg_calls.append(AggCall(AggKind.SUM, y, DataType.INT64,
                                     True))
        sub_agg_calls.append(AggCall(AggKind.COUNT, y, DataType.INT64,
                                     True))
        s_ = len(sub_agg_calls) - 2
        num = call("multiply", lit(ratio.numerator),
                   col(s_, DataType.INT64))
        den = call("multiply", lit(ratio.denominator),
                   col(s_ + 1, DataType.INT64))
        # INT64 `divide` floors, and is NULL over no rows (count 0)
        if up:
            return call("neg", call("divide", call("neg", num), den))
        return call("divide", num, den)

    return call(op, col(x.index, x.ret_type),
                sub_ref(("exact", j, up), threshold))


def _now_conjunct(conj, scope):
    """`col OP now()` (either side) -> (col_index, dynamic-filter op)."""
    if not isinstance(conj, ast.BinOp):
        return None
    ops = {"greater_than", "greater_than_or_equal", "less_than",
           "less_than_or_equal"}
    if conj.op not in ops:
        return None

    def is_now(e):
        return isinstance(e, ast.Func) and e.name == "now" and not e.args

    flip = {"greater_than": "less_than",
            "greater_than_or_equal": "less_than_or_equal",
            "less_than": "greater_than",
            "less_than_or_equal": "greater_than_or_equal"}
    if isinstance(conj.left, ast.ColRef) and is_now(conj.right):
        return scope.resolve(conj.left)[0], conj.op
    if is_now(conj.left) and isinstance(conj.right, ast.ColRef):
        return scope.resolve(conj.right)[0], flip[conj.op]
    return None
