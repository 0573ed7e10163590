"""Fragment-graph builder — the `from_proto` registry seam.

Reference: from_proto/mod.rs:105-126 (41-way `NodeBody` -> ExecutorBuilder
match) + LocalStreamManager::build_actors (task/stream_manager.rs:253):
recursively instantiate executors from the plan, wrap the fragment root in
its dispatcher, spawn actors, register everything with the barrier manager.

Deployment model (v1, single process): each fragment becomes
`parallelism` actors; inter-fragment edges are bounded channels; hash
dispatch partitions rows by vnode(dist_keys) across the consumer's actors
with the contiguous vnode->actor mapping (parallel/mesh.py); a consumer of
a parallel fragment merges with barrier alignment. State tables of a
parallel stateful fragment share one table id and split the vnode space by
bitmap — exactly the reference's vnode-partitioned state contract.
"""

from __future__ import annotations

import asyncio
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

import numpy as np

from ..common.types import DataType, Field as SchemaField, Schema
from ..common.vnode import VNODE_COUNT
from ..meta.barrier_manager import BarrierCoordinator
from ..parallel.mesh import shard_vnode_bitmaps, vnode_to_shard
from ..state.state_table import StateTable
from ..state.store import StateStore
from ..stream import (
    Actor, AppendOnlyDedupExecutor, BroadcastDispatcher, Channel,
    ChannelInput, FilterExecutor, HashAggExecutor,
    HashDispatcher, HopWindowExecutor,
    MaterializeExecutor, MergeExecutor, ProjectExecutor, RowIdGenExecutor,
    SimpleAggExecutor, SimpleDispatcher, SortedJoinExecutor, SourceExecutor,
    StatelessSimpleAggExecutor,
)
from ..stream.executor import Executor
from .graph import Exchange, Fragment, Node, StreamGraph

BUILDERS: dict[str, Callable] = {}


def register_builder(kind: str):
    def deco(fn):
        BUILDERS[kind] = fn
        return fn
    return deco


class BuildEnv:
    """Shared build-time services: the state store, table-id allocation,
    and the barrier coordinator being wired up."""

    def __init__(self, store: StateStore, coord: BarrierCoordinator,
                 channel_capacity: int = 64, chunk_coalesce_max: int = 0,
                 partial_recovery: bool = True):
        self.store = store
        self.coord = coord
        self.channel_capacity = channel_capacity
        # > 0: exchange receivers (ChannelInput/Merge) pack runs of small
        # chunks up to this total capacity into one chunk per dispatch
        # (SET streaming_chunk_coalesce; common/chunk.py ChunkCoalescer)
        self.chunk_coalesce_max = chunk_coalesce_max
        # exchange channels keep a replay buffer of the not-yet-committed
        # message suffix so a failed terminal fragment can be rebuilt
        # alone and fed the in-flight interval again (Channel.enable_
        # replay; SET partial_recovery = 0 turns it off, every failure
        # then takes the full-recovery path)
        self.partial_recovery = partial_recovery
        self._next_table_id = 1
        self._next_actor_id = 1
        # session services for cross-MV nodes (stream_scan taps); set by
        # the owning Session, None in engine-level tests
        self.session = None
        self.pending_taps: list = []          # (upstream MvDef, Channel)
        self.pending_source_queues: list = []
        self.pending_enumerators: list = []    # broker split enumerators
        # label prefix for memory-manager registration — the Session sets
        # this to the MV/sink name around build_graph so EXPLAIN and
        # \metrics attribute HBM to the flow that owns it
        self.memory_scope: Optional[str] = None

    def alloc_table_id(self) -> int:
        t = self._next_table_id
        self._next_table_id += 1
        return t

    def alloc_actor_id(self) -> int:
        a = self._next_actor_id
        self._next_actor_id += 1
        return a

    def state_table(self, table_id: int, schema: Schema,
                    pk_indices: Sequence[int],
                    vnode_bitmap: Optional[np.ndarray] = None) -> StateTable:
        return StateTable(self.store, table_id=table_id, schema=schema,
                          pk_indices=pk_indices, vnode_bitmap=vnode_bitmap)


@dataclass
class ActorCtx:
    """Per-actor build context handed to node builders."""

    env: BuildEnv
    fragment: Fragment
    actor_id: int
    actor_idx: int            # position within the fragment [0, parallelism)
    vnode_bitmap: Optional[np.ndarray]
    table_ids: dict           # node id -> table id (shared across actors)

    def table_id(self, key) -> int:
        """Stable table id per plan node, shared by a fragment's actors.
        NOT dict.setdefault(key, alloc()) — that evaluates alloc() even on
        hits, burning ids per actor and making the id sequence depend on
        PARALLELISM, which breaks recovery/rescale (a rebuilt graph must
        find its tables at the same ids)."""
        if key not in self.table_ids:
            self.table_ids[key] = self.env.alloc_table_id()
        return self.table_ids[key]


@dataclass
class Deployment:
    coord: BarrierCoordinator
    actors: list[Actor] = field(default_factory=list)
    roots: dict[int, list[Executor]] = field(default_factory=dict)
    tasks: list[asyncio.Task] = field(default_factory=list)
    source_queues: list = field(default_factory=list)
    memory_names: list = field(default_factory=list)
    mesh_actor_ids: list = field(default_factory=list)
    mesh_chains: list = field(default_factory=list)    # chain labels
    # split enumerators created by this deployment's source builders
    # (broker discovery, connectors/broker.py) — unregistered on stop
    enumerators: list = field(default_factory=list)
    # ---- per-fragment recovery bookkeeping (frontend/session.py) ----
    actor_fragment: dict = field(default_factory=dict)   # actor_id -> fid
    frag_actor_ids: dict = field(default_factory=dict)   # fid -> [ids]
    frag_memory_names: dict = field(default_factory=dict)
    frag_source_queues: dict = field(default_factory=dict)
    frag_tables: dict = field(default_factory=dict)      # fid -> table map
    fragment_consumers: dict = field(default_factory=dict)
    replay_channels: list = field(default_factory=list)
    # fid -> [MeshIngestLog] — the fused fragments' replay points, so a
    # per-fragment rebuild swaps the old incarnation's log out of the
    # coordinator's trim pulse (stream/sharded_agg.py)
    frag_ingest_logs: dict = field(default_factory=dict)
    # ---- per-ACTOR bookkeeping (cluster worker rebuilds, where a
    # fragment's actors split across workers and rebuild individually)
    actor_memory_names: dict = field(default_factory=dict)
    actor_source_queues: dict = field(default_factory=dict)
    actor_root: dict = field(default_factory=dict)    # actor_id -> root
    # everything rebuild_fragment needs to re-run one fragment's build:
    # {"graph","env","channels","built_schema","consumers"}; None when
    # the deployment came from a path without rebuild support (cluster)
    rebuild_info: Optional[dict] = None

    def spawn(self) -> "Deployment":
        self.tasks = [a.spawn() for a in self.actors]
        return self

    async def stop(self) -> None:
        """Stop THIS deployment's actors (a shared coordinator may drive
        several deployments; the stop mutation names only ours) and
        deregister them so later barriers don't wait on the dead."""
        ids = {a.actor_id for a in self.actors}
        try:
            await self.coord.stop_all(ids)
        finally:
            # a failed coordinator raises before the stop barrier reaches
            # anyone; surviving actors must still be torn down, not leaked
            for t in self.tasks:
                if not t.done():
                    t.cancel()
                try:
                    await t
                except (asyncio.CancelledError, Exception):
                    pass
            for a in self.actors:
                self.coord.actor_ids.discard(a.actor_id)
                # per-actor streaming series die with the actor (their
                # labels would otherwise linger in every future scrape)
                self.coord.stats.unregister(a.actor_id)
            for q in self.source_queues:
                if q in self.coord.source_queues:
                    self.coord.source_queues.remove(q)
            unreg_src = getattr(self.coord, "unregister_source_exec", None)
            if unreg_src is not None:
                for a in self.actors:
                    unreg_src(a.actor_id)
            unreg_en = getattr(self.coord,
                               "unregister_split_enumerator", None)
            if unreg_en is not None:
                for en in self.enumerators:
                    unreg_en(en)
            for n in self.memory_names:
                self.coord.memory.unregister(n)
            for a in self.mesh_actor_ids:
                self.coord.unregister_mesh_fragment(a)
            unreg_ch = getattr(self.coord, "unregister_mesh_chain", None)
            if unreg_ch is not None:
                for c in self.mesh_chains:
                    unreg_ch(c)
            unreg = getattr(self.coord, "unregister_replay_channels", None)
            if unreg is not None and self.replay_channels:
                unreg(self.replay_channels)


def _iter_executor_chain(root):
    """Every executor reachable from a fragment root through its
    input(s) — the registration walk for the memory manager."""
    seen: set[int] = set()
    stack = [root]
    while stack:
        node = stack.pop()
        if id(node) in seen or node is None:
            continue
        seen.add(id(node))
        yield node
        inp = getattr(node, "input", None)
        if inp is not None:
            stack.append(inp)
        for i in getattr(node, "inputs", ()) or ():
            stack.append(i)


def _register_memory(dep: Deployment, env: BuildEnv, root,
                     actor_id: int, fid=None) -> None:
    """Register every stateful executor in the chain (duck-typed on
    `state_bytes`) with the coordinator's MemoryManager, labelled by the
    owning flow so operators can see which MV owns the HBM."""
    scope = env.memory_scope or "flow"
    for ex in _iter_executor_chain(root):
        if hasattr(ex, "state_bytes"):
            name = env.coord.memory.register(
                f"{scope}/{ex.identity}@a{actor_id}", ex)
            dep.memory_names.append(name)
            dep.actor_memory_names.setdefault(actor_id, []).append(name)
            if fid is not None:
                dep.frag_memory_names.setdefault(fid, []).append(name)


def _register_mesh(dep: Deployment, env: BuildEnv, root,
                   actor_id: int, fid=None) -> None:
    """The fused mesh plane: an exchange -> sharded-executor chain that
    the builders lowered onto the device mesh announces itself to the
    barrier coordinator — the fragment's S shards collect every epoch as
    ONE actor (a single collective boundary), and /healthz can see the
    mesh topology. The executor's
    MeshIngestLog (the mesh-plane replay point) registers next to the
    exchange replay buffers so the commit pulse trims it to the
    uncommitted ingest suffix."""
    reg = getattr(env.coord, "register_mesh_fragment", None)
    if reg is None:
        return
    # the executors' mesh_shuffle_*{executor=...} series are named as the
    # memory manager names its series, and go when the fragment does
    scope = env.memory_scope or "flow"
    labels = []
    for ex in _iter_executor_chain(root):
        if hasattr(ex, "take_mesh_interval"):
            ex.mesh_label = f"{scope}/{ex.identity}@a{actor_id}"
            labels.append(ex.mesh_label)
    for ex in _iter_executor_chain(root):
        n = getattr(ex, "n_shards", 0)
        if n and getattr(ex, "mesh", None) is not None:
            reg(actor_id, n, getattr(ex, "identity", type(ex).__name__),
                labels)
            dep.mesh_actor_ids.append(actor_id)
            ilog = getattr(ex, "ingest_log", None)
            if ilog is not None and getattr(env, "partial_recovery",
                                            True):
                reg2 = getattr(env.coord, "register_replay_channels",
                               None)
                if reg2 is not None:
                    reg2([ilog])
                    dep.replay_channels.append(ilog)
                    if fid is not None:
                        dep.frag_ingest_logs.setdefault(
                            fid, []).append(ilog)
            return                  # one registration per actor


def _hollow_producer(dep: Deployment, env, stages, u_fid, c_fid, chain,
                     actors_by_id) -> None:
    """The producer half of a fused chain, once its stages are installed
    as the consumer's preludes: the stages pass chunks through, their
    actors dispatch nothing and leave the epoch fence to the consumer,
    and the chain registers with the coordinator."""
    for s in stages:
        s.mesh_hollow = True
    for aid in dep.frag_actor_ids.get(u_fid, []):
        a = actors_by_id.get(aid)
        if a is not None:
            a.fence_exempt = True
    reg = getattr(env.coord, "register_mesh_chain", None)
    if reg is not None:
        c_aids = dep.frag_actor_ids.get(c_fid, [])
        reg(chain, (u_fid, c_fid), c_aids[0] if c_aids else -1)
        if chain not in dep.mesh_chains:
            dep.mesh_chains.append(chain)


def _fuse_join_sides(dep: Deployment, graph, env, consumers, c_fid, frag,
                     join, actors_by_id) -> None:
    """Two-input chain fusion for the sharded join: hollow eligible
    producer chains on BOTH sides independently. Each side gets its own
    per-side chain (`f<u>-f<c>s<side>`): the sides' producers differ, so
    one side may hollow while the other keeps its host stages — the
    fused program runs whichever preludes installed for the side it is
    tracing; a chain is registered when, and only when, it is hollowed. Side order comes from the plan tree: the sorted_join node's
    input legs, each a direct Exchange leaf (an in-fragment subtree
    between exchange and join disqualifies that side — the built input
    is then not a ChannelInput)."""
    legs = getattr(join, "inputs", ())
    if len(legs) != 2 or any(type(i).__name__ != "ChannelInput"
                             for i in legs):
        return

    def find_join(n):
        if isinstance(n, Exchange):
            return None
        if n.kind == "sorted_join":
            return n
        for i in n.inputs:
            r = find_join(i)
            if r is not None:
                return r
        return None

    jnode = find_join(frag.root)
    if jnode is None or len(jnode.inputs) != 2 \
            or not all(isinstance(i, Exchange) for i in jnode.inputs):
        return
    for side, leg in enumerate(jnode.inputs):
        u_fid = leg.upstream
        uf = graph.fragments.get(u_fid)
        if (uf is None or uf.parallelism != 1
                or getattr(uf, "remote_worker", None)
                or len(consumers.get(u_fid, ())) != 1
                or len(dep.roots.get(u_fid, ())) != 1):
            continue
        stages, p_node = [], dep.roots[u_fid][0]
        while p_node is not None and hasattr(p_node, "mesh_prelude_fn"):
            stages.append(p_node)
            p_node = getattr(p_node, "input", None)
        if not stages or not (isinstance(p_node, SourceExecutor)
                              or type(p_node).__name__ == "ChannelInput"):
            continue
        chain = f"f{u_fid}-f{c_fid}s{side}"
        if not join._mesh_preludes.get(side):
            join.set_mesh_preludes(
                side, [s.mesh_prelude_fn() for s in reversed(stages)],
                chain=chain)
        _hollow_producer(dep, env, stages, u_fid, c_fid, chain,
                         actors_by_id)


def _fuse_mesh_chains(dep: Deployment, graph, env, consumers) -> None:
    """Mesh-resident pipelines: extend the per-fragment mesh plane to a
    whole producer -> shuffle -> consumer CHAIN. A singleton producer
    fragment whose executor chain is nothing but prelude-capable
    stateless stages (Project / HopWindow — `mesh_prelude_fn`) over a
    source, feeding exactly one sharded-agg fragment over a single
    ChannelInput leg, is HOLLOWED: its stages pass raw source chunks
    through untouched and their `_step_impl`s install as preludes INSIDE
    the consumer's fused shard_map program. The chain then runs
    device-resident end-to-end per barrier interval — the host touches
    only barrier control, the persist d2h, and the MeshIngestLog replay
    point (which now logs RAW source chunks, so a mesh-scope replay
    re-runs the hollowed stages too). The producer actor turns
    fence-exempt: it dispatches no device programs of its own, the
    consumer's fence covers the chain.

    Eligibility is conservative — any miss leaves the PR 8 per-fragment
    plane untouched: producer must be singleton, local, single-consumer
    (source-sharing fragments keep their host stages); Filter never
    qualifies (its UD/UI pair fixup reads across rows). A chain is
    registered when, and only when, it is hollowed.

    Runs after build_graph and again after rebuild_fragment (idempotent:
    surviving hollow producers re-hollow, a surviving consumer keeps its
    installed preludes — the stage impls are pure and config-identical
    across incarnations)."""
    actors_by_id = {a.actor_id: a for a in dep.actors}
    for c_fid, roots in dep.roots.items():
        f = graph.fragments.get(c_fid)
        if f is None or len(roots) != 1 \
                or getattr(f, "remote_worker", None):
            continue
        # consumer: first sharded executor in the chain. Tuple-valued
        # _mesh_preludes is the single-input form (agg / top-N /
        # over-window); dict-valued marks the join's per-side variant,
        # which runs its own two-input eligibility walk
        sharded, node = None, roots[0]
        while node is not None:
            if isinstance(getattr(node, "_mesh_preludes", None), tuple) \
                    and getattr(node, "mesh", None) is not None:
                sharded = node
                break
            if isinstance(getattr(node, "_mesh_preludes", None), dict) \
                    and getattr(node, "mesh", None) is not None:
                _fuse_join_sides(dep, graph, env, consumers, c_fid, f,
                                 node, actors_by_id)
                break
            node = getattr(node, "input", None)
        if sharded is None:
            continue
        if type(getattr(sharded, "input", None)).__name__ \
                != "ChannelInput":
            continue
        # the single upstream edge into this fragment
        ups = [u for u, cons in consumers.items()
               if any(d == c_fid for d, _k in cons)]
        if len(ups) != 1:
            continue
        u_fid = ups[0]
        uf = graph.fragments[u_fid]
        if (uf.parallelism != 1 or getattr(uf, "remote_worker", None)
                or len(consumers.get(u_fid, ())) != 1
                or len(dep.roots.get(u_fid, ())) != 1):
            continue
        # producer: only prelude-capable stages above the fragment's
        # inlet — either an in-fragment source or the channel leg from a
        # dedicated source fragment (the binder splits sources out, so
        # the common shape is source-fragment -> project-fragment ->
        # agg-fragment; hollowing the middle one is semantics-preserving
        # regardless of what feeds it: raw chunks pass through untouched)
        stages, p_node = [], dep.roots[u_fid][0]
        while p_node is not None and hasattr(p_node, "mesh_prelude_fn"):
            stages.append(p_node)
            p_node = getattr(p_node, "input", None)
        if not stages or not (isinstance(p_node, SourceExecutor)
                              or type(p_node).__name__ == "ChannelInput"):
            continue
        chain = f"f{u_fid}-f{c_fid}"
        if not sharded._mesh_preludes:
            # source-most stage runs first inside the fused program
            sharded.set_mesh_preludes(
                [s.mesh_prelude_fn() for s in reversed(stages)],
                chain=chain)
        _hollow_producer(dep, env, stages, u_fid, c_fid, chain,
                         actors_by_id)


def _build_fragment_actor(graph, env, dep, channels, built_schema,
                          f, fid, idx, actor_id, vnode_bitmap,
                          frag_tables, consumers):
    """Build ONE actor of fragment `f` (executor chain from the node
    tree, exchange legs resolved against the channel matrices, output
    dispatcher) and register it everywhere — the shared body of the
    initial `build_graph` loop and `rebuild_fragment` (per-fragment
    recovery re-runs exactly this with the ORIGINAL actor id and table
    map, so the rebuilt chain binds the same state)."""
    ctx = ActorCtx(env=env, fragment=f, actor_id=actor_id,
                   actor_idx=idx, vnode_bitmap=vnode_bitmap,
                   table_ids=frag_tables)
    # per-actor Exchange occurrence counters: the build walk visits
    # leaves in the same pre-order as StreamGraph.edges()
    edge_seen: dict[int, int] = {}

    def build_node(n):
        if isinstance(n, Exchange):
            k = edge_seen.get(n.upstream, 0)
            edge_seen[n.upstream] = k + 1
            up = graph.fragments[n.upstream]
            matrix = channels[(n.upstream, fid, k)]
            sch = built_schema[n.upstream]
            # terminate only on THIS actor's stop (a shared
            # coordinator routes other deployments' stops here too)
            stop_on = (lambda b, aid=ctx.actor_id: b.is_stop(aid))
            co = env.chunk_coalesce_max
            if up.dispatch == "simple" and up.parallelism > 1:
                # NoShuffle: 1:1 actor pairing
                return ChannelInput(matrix[idx][idx], sch,
                                    stop_on=stop_on, coalesce_max=co,
                                    actor_id=ctx.actor_id)
            chans = [matrix[u][idx] for u in range(up.parallelism)]
            if len(chans) == 1:
                return ChannelInput(chans[0], sch, stop_on=stop_on,
                                    coalesce_max=co,
                                    actor_id=ctx.actor_id)
            return MergeExecutor(chans, sch, stop_on=stop_on,
                                 coalesce_max=co)
        inputs = [build_node(i) for i in n.inputs]
        return BUILDERS[n.kind](dict(n.args), inputs, ctx, id(n))

    root = build_node(f.root)
    dep.roots[fid].append(root)
    _register_memory(dep, env, root, actor_id, fid=fid)
    _register_mesh(dep, env, root, actor_id, fid=fid)
    dispatcher = _dispatcher_for(graph, f, consumers[fid], channels, idx)
    env.coord.register_actor(actor_id)
    actor = Actor(actor_id, root, dispatcher, env.coord)
    # streaming-stats registration rides the same walk as the memory
    # manager's: per-actor series (metric_level=debug) appear labelled
    # by the owning flow
    env.coord.stats.register(env.memory_scope or "flow", actor, root)
    dep.actor_fragment[actor_id] = fid
    dep.frag_actor_ids.setdefault(fid, []).append(actor_id)
    return root, actor


def build_graph(graph: StreamGraph, env: BuildEnv) -> Deployment:
    env.pending_source_queues = []
    env.pending_enumerators = []
    dep = Deployment(coord=env.coord)
    # channels[(up_fid, down_fid, edge_k)][u_actor][d_actor] — one matrix
    # PER EXCHANGE EDGE, so a fragment consuming the same upstream twice
    # (self-join) gets independent channels on each input
    channels: dict[tuple[int, int, int], list[list[Channel]]] = {}
    built_schema: dict[int, Schema] = {}

    order = graph.topo_order()
    consumers = {fid: graph.consumers(fid) for fid in order}

    # allocate the channel matrices first (consumers may be built after
    # producers, but the producer's dispatcher needs the channels)
    replay = getattr(env, "partial_recovery", True)
    for fid in order:
        f = graph.fragments[fid]
        for d_fid, k in consumers[fid]:
            d = graph.fragments[d_fid]
            mat = [
                [Channel(env.channel_capacity) for _ in range(d.parallelism)]
                for _ in range(f.parallelism)]
            if replay and not getattr(d, "remote_worker", None):
                for row in mat:
                    for ch in row:
                        ch.enable_replay()
                        dep.replay_channels.append(ch)
            channels[(fid, d_fid, k)] = mat
    reg = getattr(env.coord, "register_replay_channels", None)
    if reg is not None and dep.replay_channels:
        # the coordinator trims every buffer at each checkpoint commit,
        # keeping the replay window == the uncommitted suffix
        reg(dep.replay_channels)

    for fid in order:
        f = graph.fragments[fid]
        dep.roots[fid] = []
        dep.fragment_consumers[fid] = list(consumers[fid])
        if getattr(f, "remote_worker", None):
            # DCN placement (stream/remote_fragment.py): the fragment
            # runs in a worker process; locally it appears as ONE actor
            # whose executor chain crosses the process boundary, so
            # barrier collection happens only after the round trip
            assert f.parallelism == 1, "remote fragments are singleton"
            actor_id = env.alloc_actor_id()
            in_chans, in_schemas = [], []
            edge_seen_r: dict = {}

            def walk(n):
                if isinstance(n, Exchange):
                    k = edge_seen_r.get(n.upstream, 0)
                    edge_seen_r[n.upstream] = k + 1
                    up = graph.fragments[n.upstream]
                    assert up.parallelism == 1, \
                        "remote fragment upstreams are singleton"
                    in_chans.append(channels[(n.upstream, fid, k)][0][0])
                    in_schemas.append(built_schema[n.upstream])
                    return
                for i in n.inputs:
                    walk(i)

            walk(f.root)
            out_schema = _infer_fragment_schema(graph, f, built_schema)
            from ..stream.remote_fragment import RemoteFragmentExecutor
            root = RemoteFragmentExecutor(
                f.remote_worker, f.root, in_chans, in_schemas, out_schema,
                actor_id=actor_id)
            built_schema[fid] = out_schema
            dep.roots[fid].append(root)
            dispatcher = _dispatcher_for(graph, f, consumers[fid],
                                         channels, 0)
            env.coord.register_actor(actor_id)
            actor = Actor(actor_id, root, dispatcher, env.coord)
            dep.actors.append(actor)
            env.coord.stats.register(env.memory_scope or "flow",
                                     actor, root)
            continue
        bitmaps = (shard_vnode_bitmaps(f.parallelism)
                   if f.parallelism > 1 else [None])
        # table ids are shared across a fragment's actors (vnode-split)
        frag_tables: dict = {}
        dep.frag_tables[fid] = frag_tables
        q_before = len(env.pending_source_queues)
        for idx in range(f.parallelism):
            actor_id = env.alloc_actor_id()
            root, actor = _build_fragment_actor(
                graph, env, dep, channels, built_schema, f, fid, idx,
                actor_id, bitmaps[idx], frag_tables, consumers)
            dep.actors.append(actor)
            if idx == 0:
                built_schema[fid] = root.schema
        dep.frag_source_queues[fid] = list(
            env.pending_source_queues[q_before:])
    dep.source_queues = list(env.pending_source_queues)
    dep.enumerators = list(env.pending_enumerators)
    _fuse_mesh_chains(dep, graph, env, consumers)
    dep.rebuild_info = {"graph": graph, "env": env, "channels": channels,
                        "built_schema": built_schema,
                        "consumers": consumers}
    return dep


def rebuild_fragment(dep: Deployment, fid: int) -> list[Actor]:
    """Per-fragment recovery: tear down ONE fragment's registrations and
    rebuild its actors in place — same actor ids, same table ids (the
    shared `frag_tables` map re-binds every durable table), same channel
    matrices (upstream producers keep their ends untouched). The caller
    (Session._partial_recover) has already cancelled the old tasks,
    discarded the fragment's staged writes, and arms channel replay
    AFTER this returns, BEFORE spawning the new actors. Mirrors the
    reference's partial/regional recovery, meta/src/barrier/recovery.rs
    (only the failed fragment's actors are recreated)."""
    info = dep.rebuild_info
    assert info is not None, "deployment has no rebuild support"
    graph, env = info["graph"], info["env"]
    channels, built_schema = info["channels"], info["built_schema"]
    consumers = info["consumers"]
    f = graph.fragments[fid]
    coord = env.coord

    # drop the old incarnation's per-fragment registrations
    for name in dep.frag_memory_names.pop(fid, []):
        coord.memory.unregister(name)
        if name in dep.memory_names:
            dep.memory_names.remove(name)
    for q in dep.frag_source_queues.pop(fid, []):
        if q in coord.source_queues:
            coord.source_queues.remove(q)
        if q in dep.source_queues:
            dep.source_queues.remove(q)
    old_ids = dep.frag_actor_ids.pop(fid)
    for aid in old_ids:
        coord.stats.unregister(aid)
        if aid in dep.mesh_actor_ids:
            coord.unregister_mesh_fragment(aid)
            dep.mesh_actor_ids.remove(aid)
    # the old incarnation's mesh replay point leaves the trim pulse —
    # the rebuilt executor registers a fresh one
    old_logs = dep.frag_ingest_logs.pop(fid, [])
    if old_logs:
        unreg = getattr(coord, "unregister_replay_channels", None)
        if unreg is not None:
            unreg(old_logs)
        dep.replay_channels = [c for c in dep.replay_channels
                               if not any(c is o for o in old_logs)]

    # rebuild with the ORIGINAL ids; builders re-read durable state at
    # their first barrier (the committed epoch — the caller discarded
    # this fragment's staged suffix)
    q_before = len(env.pending_source_queues)
    dep.roots[fid] = []
    bitmaps = (shard_vnode_bitmaps(f.parallelism)
               if f.parallelism > 1 else [None])
    frag_tables = dep.frag_tables[fid]
    by_id = {a.actor_id: i for i, a in enumerate(dep.actors)}
    new_actors = []
    for idx in range(f.parallelism):
        actor_id = old_ids[idx]
        _root, actor = _build_fragment_actor(
            graph, env, dep, channels, built_schema, f, fid, idx,
            actor_id, bitmaps[idx], frag_tables, consumers)
        dep.actors[by_id[actor_id]] = actor
        new_actors.append(actor)
    new_queues = env.pending_source_queues[q_before:]
    dep.frag_source_queues[fid] = list(new_queues)
    dep.source_queues.extend(new_queues)
    # re-fuse: a rebuilt producer re-hollows against the surviving
    # consumer; a rebuilt consumer re-installs preludes from the
    # surviving producer's stages (idempotent for untouched chains)
    _fuse_mesh_chains(dep, graph, env, consumers)
    return new_actors


def _dispatcher_for(graph, f, cons, channels, idx):
    """Output dispatcher for actor `idx` of fragment `f` (shared by the
    local and remote-fragment build paths)."""
    if not cons:
        return None
    per_consumer = []
    for d_fid, k in cons:
        d = graph.fragments[d_fid]
        outs = channels[(f.fid, d_fid, k)][idx]
        if f.dispatch == "hash":
            if d.parallelism == 1:
                # a singleton consumer needs no host-side vnode routing:
                # with one output every row lands there and update pairs
                # cannot split, so the per-chunk route program is pure
                # dispatch overhead. This is where the fused MESH
                # fragment's source-side dispatch goes on-device — the
                # consumer's shard_map ingest does the routing with an
                # in-mesh all_to_all instead (stream/sharded_*.py).
                per_consumer.append(SimpleDispatcher(outs[0]))
            else:
                per_consumer.append(HashDispatcher(
                    outs, f.dist_key_indices,
                    vnode_to_shard(d.parallelism)))
        elif f.dispatch == "broadcast":
            per_consumer.append(BroadcastDispatcher(outs))
        else:
            assert d.parallelism == f.parallelism, \
                "simple dispatch is 1:1 (NoShuffle)"
            per_consumer.append(SimpleDispatcher(outs[idx]))
    return (per_consumer[0] if len(per_consumer) == 1
            else FanoutDispatcher(per_consumer))


def _infer_fragment_schema(graph, frag, built_schema) -> Schema:
    """Planner-level schema of a fragment's output WITHOUT building its
    executors (the remote build needs it before the worker exists)."""
    def rec(n):
        if isinstance(n, Exchange):
            return built_schema[n.upstream]
        ins = [rec(i) for i in n.inputs]
        k = n.kind
        if k == "sorted_join":
            fields = tuple(ins[0]) + tuple(ins[1])
            oi = n.args.get("output_indices")
            if oi is not None:
                fields = tuple(fields[i] for i in oi)
            return Schema(fields)
        if k == "project":
            return Schema(tuple(
                SchemaField(nm, e.ret_type)
                for e, nm in zip(n.args["exprs"], n.args["names"])))
        if k in ("filter", "no_op", "dedup"):
            return ins[0]
        if k == "row_id_gen":
            return Schema(tuple(ins[0])
                          + (SchemaField("_row_id", DataType.SERIAL),))
        raise NotImplementedError(
            f"schema inference for remote fragment node {k!r}")
    return rec(frag.root)


class FanoutDispatcher:
    """One dispatcher per consumer fragment (reference DispatchExecutor
    holds a dispatcher LIST, dispatch.rs:421)."""

    def __init__(self, dispatchers):
        self.dispatchers = list(dispatchers)

    async def dispatch(self, msg) -> None:
        for d in self.dispatchers:
            await d.dispatch(msg)


# ----------------------------------------------------------------- builders

@register_builder("nexmark_source")
def _build_source(args, inputs, ctx: ActorCtx, key):
    from ..connectors import NexmarkGenerator
    from ..connectors.nexmark import NexmarkConfig
    from ..connectors.split import BlockSplitConnector

    barrier_q: asyncio.Queue = asyncio.Queue()
    ctx.env.coord.register_source(barrier_q)
    ctx.env.pending_source_queues.append(barrier_q)
    st = None
    if args.get("durable"):
        tid = ctx.table_id(key)
        st = ctx.env.state_table(
            tid, Schema((SchemaField("split_id", DataType.INT64),
                         SchemaField("offset", DataType.INT64))), (0,))
    P = ctx.fragment.parallelism
    name = args.get("source_name")
    rate = args.get("rate_limit")

    if args.get("connector") == "broker":
        ex = _build_broker_source(args, ctx, barrier_q, st, name, P, rate)
        ctx.env.coord.register_source_exec(ex)
        return ex

    def make_gen():
        if args.get("connector") == "jsonl":
            from ..connectors.file_source import (JsonlFileConnector,
                                                  parse_columns)
            return JsonlFileConnector(
                args["path"], parse_columns(args["columns"]),
                chunk_size=args.get("chunk_size", 256))
        if args.get("connector") == "tpch":
            from ..connectors.tpch import TpchGenerator
            return TpchGenerator(args["table"],
                                 chunk_size=args.get("chunk_size", 8192),
                                 scale_factor=args.get("scale_factor", 1.0),
                                 seed=args.get("seed", 0))
        cfg = (NexmarkConfig(**args.get("cfg", {}))
               if args.get("cfg") else None)
        return NexmarkGenerator(args["table"],
                                chunk_size=args.get("chunk_size", 8192),
                                **({"cfg": cfg} if cfg else {}))

    n_splits = int(args.get("splits", 1))
    assert n_splits >= P, \
        f"source parallelism {P} exceeds its {n_splits} split(s)"
    if n_splits == 1 and P == 1:
        ex = SourceExecutor(
            ctx.actor_id, make_gen(), barrier_q, state_table=st,
            emit_watermarks=args.get("emit_watermarks", False),
            watermark_lag_us=args.get("watermark_lag_us", 0),
            rate_limit_rows_per_barrier=args.get("rate_limit"),
            name=name)
        ctx.env.coord.register_source_exec(ex)
        return ex
    # split assignment: split k -> actor (k % P); a re-assigned split
    # recovers its committed offset wherever it lands (reference:
    # source_manager.rs split (re)assignment)
    my_splits = [(k, BlockSplitConnector(make_gen(), k, n_splits))
                 for k in range(n_splits) if k % P == ctx.actor_idx]
    ex = SourceExecutor(
        ctx.actor_id, barrier_queue=barrier_q, state_table=st,
        splits=my_splits,
        emit_watermarks=args.get("emit_watermarks", False),
        watermark_lag_us=args.get("watermark_lag_us", 0),
        rate_limit_rows_per_barrier=(None if rate is None
                                     else max(1, rate // P)),
        name=name)
    ctx.env.coord.register_source_exec(ex)
    return ex


def _build_broker_source(args, ctx: ActorCtx, barrier_q, st, name, P,
                         rate):
    """Broker-partition source (connectors/broker.py): splits ARE the
    topic's partitions as of build time (split k -> actor k % P, the
    standard rule), and ONE shared enumerator per fragment watches for
    partition growth — new splits arrive at a barrier via
    AddSplitsMutation, with offsets committed from that barrier on."""
    from ..connectors.broker import (BrokerPartitionConnector,
                                     BrokerSplitEnumerator)
    from ..connectors.file_source import parse_columns
    from ..broker.client import BrokerClient

    schema = parse_columns(args["columns"])
    brokers, topic = args["brokers"], args["topic"]
    chunk_size = int(args.get("chunk_size", 256))
    client = BrokerClient(brokers)
    # idempotent ensure: partition count only ever grows, so the live
    # count is >= the count the DDL was bound against
    n_parts = client.create_topic(topic=topic,
                                  partitions=int(args.get("partitions",
                                                          1)))
    client.close()
    assert n_parts >= P, \
        f"source parallelism {P} exceeds topic {topic!r}'s " \
        f"{n_parts} partition(s)"
    my_splits = [(k, BrokerPartitionConnector(brokers, topic, k, schema,
                                              chunk_size=chunk_size))
                 for k in range(n_parts) if k % P == ctx.actor_idx]
    interval_s = int(args.get("discovery_interval_ms", 1000)) / 1e3
    en = ctx.env.coord.split_enumerator(
        id(ctx.fragment),
        lambda: BrokerSplitEnumerator(
            brokers, topic, schema, chunk_size, P, n_parts,
            poll_interval_s=interval_s))
    en.register_actor(ctx.actor_idx, ctx.actor_id)
    en.observe_build(n_parts)
    pend = getattr(ctx.env, "pending_enumerators", None)
    if pend is not None and en not in pend:
        pend.append(en)
    return SourceExecutor(
        ctx.actor_id, barrier_queue=barrier_q, state_table=st,
        splits=my_splits,
        rate_limit_rows_per_barrier=(None if rate is None
                                     else max(1, int(rate) // P)),
        name=name)


@register_builder("project")
def _build_project(args, inputs, ctx, key):
    return ProjectExecutor(inputs[0], args["exprs"],
                           names=args.get("names"),
                           watermark_mapping=args.get("watermark_mapping"),
                           watermark_transforms=args.get("watermark_transforms"))


@register_builder("filter")
def _build_filter(args, inputs, ctx, key):
    return FilterExecutor(inputs[0], args["predicate"])


@register_builder("no_op")
def _build_no_op(args, inputs, ctx, key):
    from ..stream.misc import NoOpExecutor
    return NoOpExecutor(inputs[0])


@register_builder("hop_window")
def _build_hop(args, inputs, ctx, key):
    return HopWindowExecutor(inputs[0], time_col=args["time_col"],
                             window_slide_us=args["slide_us"],
                             window_size_us=args["size_us"],
                             output_indices=args.get("output_indices"))


def _agg_state_schema(in_schema: Schema, group_key_indices, agg_calls,
                      minput_k: int) -> Schema:
    from ..expr.agg import AggKind
    fields = [in_schema[i] for i in group_key_indices]
    for j, c in enumerate(agg_calls):
        if c.kind in (AggKind.MIN, AggKind.MAX) and not c.append_only:
            # retractable extrema persist their top-K value buffer
            fields += [SchemaField(f"s{j}v{k}", c.ret_type)
                       for k in range(minput_k)]
            fields += [SchemaField(f"s{j}c{k}", DataType.INT64)
                       for k in range(minput_k)]
            fields.append(SchemaField(f"s{j}lossy", DataType.INT64))
        else:
            fields.append(SchemaField(f"state{j}", c.ret_type))
    fields.append(SchemaField("_row_count", DataType.INT64))
    return Schema(tuple(fields))


@register_builder("hash_agg")
def _build_hash_agg(args, inputs, ctx: ActorCtx, key):
    st = None
    minput_k = args.get("minput_k", 32)
    if args.get("durable"):
        gk = tuple(args["group_key_indices"])
        sch = _agg_state_schema(inputs[0].schema, gk, args["agg_calls"],
                                minput_k)
        tid = ctx.table_id(key)
        st = ctx.env.state_table(tid, sch, tuple(range(len(gk))),
                                 vnode_bitmap=ctx.vnode_bitmap)
    md = args.get("mesh_devices", 1)
    if md > 1:
        from ..parallel.mesh import make_mesh
        from ..stream.sharded_agg import ShardedHashAggExecutor
        return ShardedHashAggExecutor(
            inputs[0], args["group_key_indices"], args["agg_calls"],
            mesh=make_mesh(md),
            capacity=args.get("capacity", 1 << 16) // md,
            state_table=st,
            group_key_names=args.get("group_key_names"),
            cleaning_watermark_col=args.get("cleaning_watermark_col"),
            watchdog_interval=args.get("watchdog_interval", 1))
    return HashAggExecutor(
        inputs[0], args["group_key_indices"], args["agg_calls"],
        capacity=args.get("capacity", 1 << 16),
        state_table=st,
        group_key_names=args.get("group_key_names"),
        cleaning_watermark_col=args.get("cleaning_watermark_col"),
        watchdog_interval=args.get("watchdog_interval", 1),
        minput_k=minput_k)


@register_builder("sorted_join")
def _build_sorted_join(args, inputs, ctx: ActorCtx, key):
    state_tables = None
    if args.get("durable"):
        tabs = []
        for s, inp in enumerate(inputs):
            tid = ctx.table_id((key, s))
            pk = tuple(args["left_pk_indices" if s == 0 else "right_pk_indices"])
            tabs.append(ctx.env.state_table(
                tid, inp.schema, pk, vnode_bitmap=ctx.vnode_bitmap))
        state_tables = tuple(tabs)
    md = args.get("mesh_devices", 1)
    cls = SortedJoinExecutor
    extra = {}
    if md > 1:
        from ..parallel.mesh import make_mesh
        from ..stream.sharded_join import ShardedSortedJoinExecutor
        cls = ShardedSortedJoinExecutor
        extra = dict(mesh=make_mesh(md))
    return cls(
        inputs[0], inputs[1], **extra,
        left_key_indices=args["left_key_indices"],
        right_key_indices=args["right_key_indices"],
        left_pk_indices=args["left_pk_indices"],
        right_pk_indices=args["right_pk_indices"],
        capacity=args.get("capacity", 1 << 17) // md,
        match_factor=args.get("match_factor", 2),
        match_factors=args.get("match_factors"),
        condition=args.get("condition"),
        join_type=args.get("join_type", "inner"),
        output_indices=args.get("output_indices"),
        append_only=tuple(args.get("append_only", (False, False))),
        clean_watermark_cols=tuple(args.get("clean_watermark_cols",
                                            (None, None))),
        clean_specs=(tuple(args["clean_specs"])
                     if args.get("clean_specs") is not None else None),
        state_tables=state_tables,
        temporal=args.get("temporal", False),
        watchdog_interval=args.get("watchdog_interval", 1))


@register_builder("general_over_window")
def _build_general_over_window(args, inputs, ctx: ActorCtx, key):
    from ..stream.general_over_window import GeneralOverWindowExecutor
    pk = tuple(args["pk_indices"])
    st = None
    if args.get("durable"):
        st = ctx.env.state_table(ctx.table_id(key), inputs[0].schema, pk,
                                 vnode_bitmap=ctx.vnode_bitmap)
    md = args.get("mesh_devices", 1)
    # no partition axis -> nothing to shard on: stay single-device
    if md > 1 and args["partition_by"]:
        from ..parallel.mesh import make_mesh
        from ..stream.sharded_over_window import ShardedOverWindowExecutor
        return ShardedOverWindowExecutor(
            inputs[0], args["partition_by"], args["order_specs"],
            args["windows"],
            capacity=args.get("capacity", 1 << 14) // md,
            state_table=st, pk_indices=pk,
            watchdog_interval=args.get("watchdog_interval", 1),
            mesh=make_mesh(md))
    return GeneralOverWindowExecutor(
        inputs[0], args["partition_by"], args["order_specs"],
        args["windows"], capacity=args.get("capacity", 1 << 14),
        state_table=st, pk_indices=pk,
        watchdog_interval=args.get("watchdog_interval", 1))


@register_builder("eowc_over_window")
def _build_eowc_over_window(args, inputs, ctx: ActorCtx, key):
    from ..stream.eowc_over_window import EowcOverWindowExecutor
    pk = tuple(args["pk_indices"])
    st = ft = None
    if args.get("durable"):
        st = ctx.env.state_table(ctx.table_id((key, 0)), inputs[0].schema,
                                 pk, vnode_bitmap=ctx.vnode_bitmap)
        ft = ctx.env.state_table(
            ctx.table_id((key, 1)),
            Schema((SchemaField("slot", DataType.INT64),
                    SchemaField("emitted_to", DataType.INT64))), (0,))
    return EowcOverWindowExecutor(
        inputs[0], args["partition_by"], args["order_specs"],
        args["windows"], capacity=args.get("capacity", 1 << 14),
        state_table=st, frontier_table=ft, pk_indices=pk,
        watchdog_interval=args.get("watchdog_interval", 1))


@register_builder("now")
def _build_now(args, inputs, ctx, key):
    from ..stream.dynamic import NowExecutor
    barrier_q: asyncio.Queue = asyncio.Queue()
    ctx.env.coord.register_source(barrier_q)
    ctx.env.pending_source_queues.append(barrier_q)
    return NowExecutor(barrier_q)


@register_builder("project_set")
def _build_project_set(args, inputs, ctx, key):
    from ..stream.project_set import ProjectSetExecutor
    return ProjectSetExecutor(inputs[0], args["items"],
                              max_rows_per_input=args.get("max_k", 16),
                              names=args.get("names"))


@register_builder("dynamic_filter")
def _build_dynamic_filter(args, inputs, ctx, key):
    from ..stream.dynamic import DynamicFilterExecutor
    return DynamicFilterExecutor(
        inputs[0], inputs[1], args["key_col"],
        op=args.get("op", "greater_than"),
        capacity=args.get("capacity", 1 << 14),
        pk_indices=args.get("pk_indices"),
        watchdog_interval=args.get("watchdog_interval", 1))


@register_builder("dedup")
def _build_dedup(args, inputs, ctx: ActorCtx, key):
    st = None
    if args.get("durable"):
        tid = ctx.table_id(key)
        gk = tuple(args["dedup_key_indices"])
        sch = Schema(tuple(inputs[0].schema[i] for i in gk))
        st = ctx.env.state_table(tid, sch, tuple(range(len(gk))),
                                 vnode_bitmap=ctx.vnode_bitmap)
    return AppendOnlyDedupExecutor(
        inputs[0], args["dedup_key_indices"],
        capacity=args.get("capacity", 1 << 16), state_table=st,
        watchdog_interval=args.get("watchdog_interval", 1))


@register_builder("simple_agg")
def _build_simple_agg(args, inputs, ctx: ActorCtx, key):
    st = None
    if args.get("durable"):
        calls = args["agg_calls"]
        fields = [SchemaField("slot", DataType.INT64)]
        fields += [SchemaField(f"state{j}", c.ret_type)
                   for j, c in enumerate(calls)]
        fields.append(SchemaField("_row_count", DataType.INT64))
        tid = ctx.table_id(key)
        st = ctx.env.state_table(tid, Schema(tuple(fields)), (0,))
    return SimpleAggExecutor(inputs[0], args["agg_calls"], state_table=st,
                             combine_partials=args.get("combine_partials",
                                                       False))


@register_builder("stateless_simple_agg")
def _build_stateless_agg(args, inputs, ctx, key):
    return StatelessSimpleAggExecutor(inputs[0], args["agg_calls"])


@register_builder("snapshot_join_agg")
def _build_snapshot_join_agg(args, inputs, ctx: ActorCtx, key):
    from ..stream.snapshot_join_agg import SnapshotJoinAggExecutor
    state_tables = None
    if args.get("durable"):
        fact_sch = Schema(
            (SchemaField("_pos", DataType.SERIAL),)
            + tuple(inputs[0].schema)
            + (SchemaField("_validbits", DataType.INT64),))
        dim_sch = Schema((SchemaField("_pos", DataType.SERIAL),
                          SchemaField("_key", DataType.INT64)))
        state_tables = (
            ctx.env.state_table(ctx.table_id((key, 0)), fact_sch, (0,)),
            ctx.env.state_table(ctx.table_id((key, 1)), dim_sch, (0,)))
    return SnapshotJoinAggExecutor(
        inputs[0], inputs[1],
        fact_key=args["fact_key"], dim_key=args["dim_key"],
        sub_agg_calls=args["sub_agg_calls"],
        sub_items=args["sub_items"], residue=args["residue"],
        final_agg_calls=args["final_agg_calls"],
        final_items=args["final_items"],
        out_names=args["out_names"], out_types=args["out_types"],
        fact_filter=args.get("fact_filter"),
        sub_filter=args.get("sub_filter"),
        dim_filter=args.get("dim_filter"),
        capacity=args.get("capacity", 1 << 17),
        dim_capacity=args.get("dim_capacity", 1 << 14),
        state_tables=state_tables,
        watchdog_interval=args.get("watchdog_interval", 1))


@register_builder("row_id_gen")
def _build_row_id(args, inputs, ctx: ActorCtx, key):
    return RowIdGenExecutor(inputs[0], instance=ctx.actor_id)


@register_builder("stream_scan")
def _build_stream_scan(args, inputs, ctx: ActorCtx, key):
    """CREATE MV ... FROM <mv>: live tap on the upstream MV's root actor +
    snapshot backfill over its StorageTable (no_shuffle_backfill.rs)."""
    from ..state.storage_table import StorageTable
    from ..stream import Channel, ChannelInput
    from ..stream.backfill import BackfillExecutor, backfill_progress_schema
    session = ctx.env.session
    assert session is not None, "stream_scan needs a session catalog"
    mv = session.catalog.mvs[args["mv"]]
    ch = Channel(ctx.env.channel_capacity)
    mv.tap.add(ch)
    ctx.env.pending_taps.append((mv, ch))
    storage = StorageTable.for_state_table(mv.table)
    st = None
    if args.get("durable", True):
        sch = backfill_progress_schema(mv.schema, mv.pk_indices)
        st = ctx.env.state_table(ctx.table_id(key), sch, (0,))
    return BackfillExecutor(
        ChannelInput(ch, mv.schema,
                     stop_on=lambda b, aid=ctx.actor_id: b.is_stop(aid),
                     actor_id=ctx.actor_id),
        storage, state_table=st,
        batch_rows=args.get("batch_rows", 65536))


@register_builder("retract_top_n")
def _build_retract_top_n(args, inputs, ctx: ActorCtx, key):
    from ..stream.retract_top_n import RetractableTopNExecutor
    pk = tuple(args.get("pk_indices")
               or inputs[0].pk_indices
               or range(len(inputs[0].schema)))
    st = None
    if args.get("durable"):
        st = ctx.env.state_table(ctx.table_id(key), inputs[0].schema, pk,
                                 vnode_bitmap=ctx.vnode_bitmap)
    kw = dict(order_col=args.get("order_col"),
              order_specs=args.get("order_specs"),
              limit=args["limit"], offset=args.get("offset", 0),
              descending=args.get("descending", False),
              state_table=st, pk_indices=pk,
              watchdog_interval=args.get("watchdog_interval", 1),
              append_only=args.get("append_only", False),
              emit_rank=args.get("emit_rank", False))
    groups = args.get("group_key_indices", ())
    capacity = args.get("capacity", 1 << 14)
    md = args.get("mesh_devices", 1)
    if md > 1:
        from ..parallel.mesh import make_mesh
        from ..stream.sharded_top_n import ShardedTopNExecutor
        return ShardedTopNExecutor(inputs[0], groups, capacity=capacity // md,
                                   mesh=make_mesh(md), **kw)
    return RetractableTopNExecutor(inputs[0], groups, capacity=capacity, **kw)


@register_builder("sink")
def _build_sink(args, inputs, ctx: ActorCtx, key):
    from ..stream.sink import (BlackholeSink, CallbackSink,
                               DeviceBlackholeSinkExecutor, FileSink,
                               SinkExecutor)
    connector = args.get("connector", "blackhole")
    force = args.get("type") == "append-only" or str(
        args.get("force_append_only", "")).lower() in ("true", "1")
    if connector == "blackhole_device":
        return DeviceBlackholeSinkExecutor(inputs[0])
    if connector == "blackhole":
        target = BlackholeSink()
    elif connector == "file":
        target = FileSink(args["path"], schema=inputs[0].schema)
    elif connector == "callback":
        target = CallbackSink(args["callback"])
    elif connector == "broker":
        from ..connectors.broker import BrokerSink
        parts = int(args.get("partitions", 1))
        if parts > 1 and not force:
            # one delivery batch lands WHOLE in one partition (the
            # atomicity the seq-in-topic dedupe rests on), and a
            # consumer interleaves partitions arbitrarily — a
            # retraction in p0 racing its re-insert in p1 would make
            # the downstream state order-dependent. Inserts commute;
            # retractions need the single-partition total order.
            raise ValueError(
                "broker sink with partitions > 1 requires an "
                "append-only changelog (WITH type='append-only')")
        target = BrokerSink(args["brokers"], args["topic"],
                            schema=inputs[0].schema, partitions=parts)
        # cross-engine trace stamping: delivered batch metas carry this
        # engine's identity + epoch span so a downstream engine's
        # ingest links back (utils/trace.py stitch_chrome_traces)
        session = getattr(ctx.env, "session", None)
        target.engine_id = getattr(session, "engine_id", None) \
            or f"engine-{id(ctx.env) & 0xFFFF:04x}"
        target.tracer = ctx.env.coord.tracer
    else:
        raise ValueError(f"unknown sink connector {connector!r}")
    # Exactly-once via the changelog log store (logstore/): default for
    # file/callback targets on a meta-local (manifest-owning) store —
    # the epoch batch persists WITH the checkpoint and a background
    # delivery task writes it to the target after the commit. Blackhole
    # (the bench egress) skips the log by default: durably persisting
    # every epoch for a row counter is pure write amplification.
    # `WITH (exactly_once = 0/1)` overrides either way. A cluster
    # compute node never owns the manifest (it cannot observe meta's
    # commit point), so cluster sinks stay on the direct path — the
    # deploy-time guard in cluster/meta_service.py rejects an explicit
    # exactly_once request loudly instead of degrading silently.
    default_eo = connector in ("file", "callback", "broker")
    exactly_once = bool(int(args.get("exactly_once", default_eo)))
    log = hub = None
    if exactly_once and getattr(ctx.env.store, "manifest_owner", True):
        from ..logstore.log import SinkChangelog
        log = SinkChangelog(ctx.env.store, ctx.table_id((key, "log")),
                            inputs[0].schema)
        hub = ctx.env.coord.logstore
    return SinkExecutor(inputs[0], target, force_append_only=force,
                        log=log, hub=hub,
                        name=ctx.env.memory_scope or f"sink_a{ctx.actor_id}")


@register_builder("materialize")
def _build_materialize(args, inputs, ctx: ActorCtx, key):
    tid = ctx.table_id(key)
    st = ctx.env.state_table(tid, inputs[0].schema,
                             tuple(args.get("pk_indices",
                                            inputs[0].pk_indices)),
                             vnode_bitmap=ctx.vnode_bitmap)
    kw = {}
    if args.get("conflict") is not None:
        kw["conflict"] = args["conflict"]
    return MaterializeExecutor(inputs[0], st, **kw)


# ====================================================================
# Cluster (multi-process) build — cluster/: meta assigns fragments to
# compute nodes by vnode range; every process derives the SAME actor and
# state-table ids from the pickled graph alone (no id exchange), builds
# only its assigned actors, and cross-worker fragment edges ride the DCN
# tier (stream/remote_exchange.py).
# ====================================================================

def fragment_node_order(frag: Fragment) -> list:
    """The fragment's Node tree in the builder's visit order (post-order,
    inputs first — the order `build_node` constructs executors and the
    order builders request state-table ids). Exchange leaves excluded.
    Deterministic across processes: it depends only on tree SHAPE, which
    pickling preserves."""
    out = []

    def rec(n):
        if isinstance(n, Exchange):
            return
        for i in n.inputs:
            rec(i)
        out.append(n)

    rec(frag.root)
    return out


def _state_table_keys(kind: str, args: dict, key) -> list:
    """The exact `ctx.table_id(...)` keys the registered builder for
    `kind` will request, in request order — the single source of truth
    the deterministic pre-assigner shares with the builders above."""
    durable = bool(args.get("durable"))
    if kind in ("nexmark_source", "hash_agg", "general_over_window",
                "dedup", "simple_agg", "retract_top_n"):
        return [key] if durable else []
    if kind in ("sorted_join", "eowc_over_window", "snapshot_join_agg"):
        return [(key, 0), (key, 1)] if durable else []
    if kind == "stream_scan":
        return [key] if args.get("durable", True) else []
    if kind == "materialize":
        return [key]
    return []


def assign_graph_ids(graph: StreamGraph, actor_id_base: int,
                     table_id_base: int):
    """Deterministically derive every actor id and state-table id of a
    graph from the graph alone: fragments in topo order, nodes in builder
    visit order, actors idx-ordered within a fragment. Meta and every
    compute node run this on the same pickled graph and agree on all ids
    without exchanging them (ids must agree — vnode-partitioned state
    tables are SHARED across workers, and stop mutations name global
    actor ids).

    Returns (actors, tables, next_actor_id, next_table_id) where
    `actors[fid]` is the fragment's actor-id list and `tables[fid]` the
    prefilled `ActorCtx.table_ids` dict (keys are (fid, node_idx)-based,
    matching what the partial build passes to builders)."""
    next_actor = actor_id_base
    next_table = table_id_base
    actors: dict[int, list[int]] = {}
    tables: dict[int, dict] = {}
    for fid in graph.topo_order():
        f = graph.fragments[fid]
        actors[fid] = list(range(next_actor, next_actor + f.parallelism))
        next_actor += f.parallelism
        tab: dict = {}
        for idx, n in enumerate(fragment_node_order(f)):
            for k in _state_table_keys(n.kind, n.args, (fid, idx)):
                tab[k] = next_table
                next_table += 1
        tables[fid] = tab
    return actors, tables, next_actor, next_table


def infer_fragment_schemas(graph: StreamGraph,
                           on_node=None) -> dict[int, Schema]:
    """Planner-level output schema of EVERY fragment without building a
    single executor — what a compute node needs to wire exchange
    receivers for fragments built on OTHER nodes. Mirrors each
    executor's own schema computation; kinds without a rule refuse
    cluster deploy loudly instead of guessing. `on_node(node, input_
    schemas)` is a per-node hook (the cluster deploy's supported-plan
    checks ride it)."""
    out: dict[int, Schema] = {}

    def node_schema(n, fid) -> Schema:
        if isinstance(n, Exchange):
            return out[n.upstream]
        ins = [node_schema(i, fid) for i in n.inputs]
        if on_node is not None:
            on_node(n, ins)
        k, a = n.kind, n.args
        if k == "nexmark_source":
            conn = a.get("connector", "nexmark")
            if conn == "jsonl":
                from ..connectors.file_source import parse_columns
                return parse_columns(a["columns"])
            if conn == "tpch":
                from ..connectors.tpch import TPCH_SCHEMAS
                return TPCH_SCHEMAS[a["table"]]
            from ..connectors.nexmark import (AUCTION_SCHEMA, BID_SCHEMA,
                                              PERSON_SCHEMA)
            return {"bid": BID_SCHEMA, "person": PERSON_SCHEMA,
                    "auction": AUCTION_SCHEMA}[a["table"]]
        if k == "project":
            names = a.get("names") or [f"expr{i}"
                                       for i in range(len(a["exprs"]))]
            return Schema(tuple(SchemaField(nm, e.ret_type)
                                for nm, e in zip(names, a["exprs"])))
        if k in ("filter", "no_op", "dedup", "retract_top_n",
                 "materialize", "sink", "dynamic_filter"):
            if k == "retract_top_n" and a.get("emit_rank"):
                from ..stream.retract_top_n import RANK_COLUMN
                return Schema(tuple(ins[0])
                              + (SchemaField(RANK_COLUMN, DataType.INT64),))
            return ins[0]
        if k == "row_id_gen":
            return Schema(tuple(ins[0])
                          + (SchemaField("_row_id", DataType.SERIAL),))
        if k == "hop_window":
            full = list(ins[0]) + [
                SchemaField("window_start", DataType.TIMESTAMP),
                SchemaField("window_end", DataType.TIMESTAMP)]
            oi = a.get("output_indices")
            idx = tuple(oi) if oi is not None else tuple(range(len(full)))
            return Schema(tuple(full[i] for i in idx))
        if k == "hash_agg":
            gk = list(a["group_key_indices"])
            names = list(a.get("group_key_names")
                         or [ins[0][i].name for i in gk])
            return Schema(tuple(
                [SchemaField(nm, ins[0][i].data_type)
                 for nm, i in zip(names, gk)]
                + [SchemaField(f"agg{j}", c.ret_type)
                   for j, c in enumerate(a["agg_calls"])]))
        if k in ("simple_agg", "stateless_simple_agg"):
            return Schema(tuple(SchemaField(f"agg{j}", c.ret_type)
                                for j, c in enumerate(a["agg_calls"])))
        if k == "sorted_join":
            fields = tuple(ins[0]) + tuple(ins[1])
            oi = a.get("output_indices")
            if oi is not None:
                fields = tuple(fields[i] for i in oi)
            return Schema(fields)
        if k == "snapshot_join_agg":
            return Schema(tuple(SchemaField(nm, t) for nm, t in
                                zip(a["out_names"], a["out_types"])))
        raise NotImplementedError(
            f"cluster deploy: no schema rule for node kind {k!r}")

    for fid in graph.topo_order():
        out[fid] = node_schema(graph.fragments[fid].root, fid)
    return out


def cluster_remote_edges(graph: StreamGraph, placement: dict):
    """All cross-worker (edge, producer actor, consumer actor) pairs:
    [((up_fid, down_fid, edge_k, u, d), up_worker, down_worker)].
    Deterministic order — both endpoints derive the same pair list."""
    pairs = []
    for fid in graph.topo_order():
        f = graph.fragments[fid]
        for d_fid, k in graph.consumers(fid):
            d = graph.fragments[d_fid]
            for u in range(f.parallelism):
                for di in range(d.parallelism):
                    if f.dispatch == "simple" and f.parallelism > 1 \
                            and u != di:
                        continue          # NoShuffle pairs 1:1
                    uw = placement[fid][u]
                    dw = placement[d_fid][di]
                    if uw != dw:
                        pairs.append(((fid, d_fid, k, u, di), uw, dw))
    return pairs


def build_partial_graph(graph: StreamGraph, env: BuildEnv,
                        placement: dict, my_worker: int,
                        actors: dict, tables: dict,
                        schemas: dict[int, Schema],
                        remote_ins: dict, remote_outs: dict) -> Deployment:
    """Compute-node side of `LocalStreamManager::build_actors`: build and
    spawn ONLY the actors `placement` assigns to `my_worker`, with the
    pre-derived global ids (`assign_graph_ids`) and with cross-worker
    exchange legs resolved to the DCN endpoints the caller prepared
    (`remote_ins[(up,down,k,u,d)]` = recv()-able channel from a remote
    producer; `remote_outs[...]` = connected RemoteOutput to a remote
    consumer). Local legs use ordinary bounded channels exactly like
    `build_graph`."""
    env.pending_source_queues = []
    dep = Deployment(coord=env.coord)
    channels: dict[tuple[int, int, int], dict] = {}
    order = graph.topo_order()
    consumers = {fid: graph.consumers(fid) for fid in order}

    # local-local channel matrix entries only (sparse dict by (u, d));
    # replay buffers on every local leg, trimmed by meta's `committed`
    # push — a worker-local frontier edge replays into a rebuilt
    # consumer exactly like the single-process path
    replay = getattr(env, "partial_recovery", True)
    for fid in order:
        f = graph.fragments[fid]
        for d_fid, k in consumers[fid]:
            d = graph.fragments[d_fid]
            mat: dict = {}
            for u in range(f.parallelism):
                for di in range(d.parallelism):
                    if placement[fid][u] == my_worker \
                            and placement[d_fid][di] == my_worker:
                        ch = Channel(env.channel_capacity)
                        if replay:
                            ch.enable_replay()
                            dep.replay_channels.append(ch)
                        mat[(u, di)] = ch
            channels[(fid, d_fid, k)] = mat
    reg = getattr(env.coord, "register_replay_channels", None)
    if reg is not None and dep.replay_channels:
        reg(dep.replay_channels)

    def edge_chan(up_fid, fid, k, u, di):
        """Channel-like the consumer (fid actor di, local) reads for
        producer actor u of up_fid — a local Channel or a remote leg."""
        if placement[up_fid][u] == my_worker:
            return channels[(up_fid, fid, k)][(u, di)]
        return remote_ins[(up_fid, fid, k, u, di)]

    for fid in order:
        f = graph.fragments[fid]
        dep.roots[fid] = []
        frag_tables = tables[fid]
        for idx in range(f.parallelism):
            if placement[fid][idx] != my_worker:
                continue
            bitmaps = (shard_vnode_bitmaps(f.parallelism)
                       if f.parallelism > 1 else [None])
            actor_id = actors[fid][idx]
            ctx = ActorCtx(env=env, fragment=f, actor_id=actor_id,
                           actor_idx=idx, vnode_bitmap=bitmaps[idx],
                           table_ids=frag_tables)
            edge_seen: dict[int, int] = {}
            node_idx = {id(n): i
                        for i, n in enumerate(fragment_node_order(f))}

            def build_node(n):
                if isinstance(n, Exchange):
                    k = edge_seen.get(n.upstream, 0)
                    edge_seen[n.upstream] = k + 1
                    up = graph.fragments[n.upstream]
                    sch = schemas[n.upstream]
                    stop_on = (lambda b, aid=ctx.actor_id: b.is_stop(aid))
                    co = env.chunk_coalesce_max
                    if up.dispatch == "simple" and up.parallelism > 1:
                        return ChannelInput(
                            edge_chan(n.upstream, fid, k, idx, idx), sch,
                            stop_on=stop_on, coalesce_max=co)
                    chans = [edge_chan(n.upstream, fid, k, u, idx)
                             for u in range(up.parallelism)]
                    if len(chans) == 1:
                        return ChannelInput(chans[0], sch, stop_on=stop_on,
                                            coalesce_max=co)
                    return MergeExecutor(chans, sch, stop_on=stop_on,
                                         coalesce_max=co)
                inputs = [build_node(i) for i in n.inputs]
                return BUILDERS[n.kind](dict(n.args), inputs, ctx,
                                        (fid, node_idx[id(n)]))

            q_before = len(env.pending_source_queues)
            root = build_node(f.root)
            dep.roots[fid].append(root)
            _register_memory(dep, env, root, actor_id)
            _register_mesh(dep, env, root, actor_id, fid=fid)
            dispatcher = _cluster_dispatcher(graph, f, consumers[fid],
                                             channels, placement,
                                             my_worker, remote_outs, idx)
            env.coord.register_actor(actor_id)
            actor = Actor(actor_id, root, dispatcher, env.coord)
            dep.actors.append(actor)
            env.coord.stats.register(env.memory_scope or "flow",
                                     actor, root)
            dep.actor_fragment[actor_id] = fid
            dep.frag_actor_ids.setdefault(fid, []).append(actor_id)
            dep.actor_source_queues[actor_id] = list(
                env.pending_source_queues[q_before:])
            dep.actor_root[actor_id] = root
    dep.source_queues = list(env.pending_source_queues)
    # worker rebuild support (cluster partial recovery): the channel
    # dict rides with the deployment so a closure rebuild can reuse the
    # surviving legs and replace the dead ones
    dep.rebuild_info = {"graph": graph, "env": env, "channels": channels,
                        "consumers": consumers}
    return dep


def build_closure_actors(graph, env, dep, new_placement, my_worker,
                         actors, tables, schemas, closure,
                         in_leg, out_leg) -> list[Actor]:
    """Per-worker partial recovery, compute-node side: build the
    CLOSURE actors assigned to `my_worker` under the NEW placement —
    the dead worker's re-placed actors plus this worker's in-place
    rebuilds — with the ORIGINAL global ids and table maps (the shared
    vnode-partitioned state re-binds at the committed view exactly like
    `rebuild_fragment`). Edge legs resolve through the caller's
    resolvers, which route each edge per its recovery disposition
    (reused surviving channel, rewound remote leg, or a fresh pair
    between two rebuilt actors):

        in_leg(up_fid, fid, k, u, di)  -> recv()-able input leg
        out_leg(fid, d_fid, k, u, di)  -> awaitable send target

    Returns the new Actor list; the caller tears the old incarnations
    down first and spawns these after arming replay."""
    new_actors: list[Actor] = []
    for fid in graph.topo_order():
        f = graph.fragments[fid]
        for idx in sorted(closure.get(fid, ())):
            if new_placement[fid][idx] != my_worker:
                continue
            bitmaps = (shard_vnode_bitmaps(f.parallelism)
                       if f.parallelism > 1 else [None])
            actor_id = actors[fid][idx]
            ctx = ActorCtx(env=env, fragment=f, actor_id=actor_id,
                           actor_idx=idx, vnode_bitmap=bitmaps[idx],
                           table_ids=tables[fid])
            edge_seen: dict[int, int] = {}
            node_idx = {id(n): i
                        for i, n in enumerate(fragment_node_order(f))}

            def build_node(n):
                if isinstance(n, Exchange):
                    k = edge_seen.get(n.upstream, 0)
                    edge_seen[n.upstream] = k + 1
                    up = graph.fragments[n.upstream]
                    sch = schemas[n.upstream]
                    stop_on = (lambda b, aid=ctx.actor_id: b.is_stop(aid))
                    co = env.chunk_coalesce_max
                    if up.dispatch == "simple" and up.parallelism > 1:
                        return ChannelInput(
                            in_leg(n.upstream, fid, k, idx, idx), sch,
                            stop_on=stop_on, coalesce_max=co,
                            actor_id=ctx.actor_id)
                    chans = [in_leg(n.upstream, fid, k, u, idx)
                             for u in range(up.parallelism)]
                    if len(chans) == 1:
                        return ChannelInput(chans[0], sch,
                                            stop_on=stop_on,
                                            coalesce_max=co,
                                            actor_id=ctx.actor_id)
                    return MergeExecutor(chans, sch, stop_on=stop_on,
                                         coalesce_max=co)
                inputs = [build_node(i) for i in n.inputs]
                return BUILDERS[n.kind](dict(n.args), inputs, ctx,
                                        (fid, node_idx[id(n)]))

            q_before = len(env.pending_source_queues)
            root = build_node(f.root)
            dep.roots.setdefault(fid, []).append(root)
            _register_memory(dep, env, root, actor_id)
            _register_mesh(dep, env, root, actor_id, fid=fid)
            cons = graph.consumers(fid)
            dispatcher = None
            if cons:
                per_consumer = []
                for d_fid, k in cons:
                    d = graph.fragments[d_fid]
                    if f.dispatch == "hash":
                        if d.parallelism == 1:
                            per_consumer.append(SimpleDispatcher(
                                out_leg(fid, d_fid, k, idx, 0)))
                        else:
                            per_consumer.append(HashDispatcher(
                                [out_leg(fid, d_fid, k, idx, di)
                                 for di in range(d.parallelism)],
                                f.dist_key_indices,
                                vnode_to_shard(d.parallelism)))
                    elif f.dispatch == "broadcast":
                        per_consumer.append(BroadcastDispatcher(
                            [out_leg(fid, d_fid, k, idx, di)
                             for di in range(d.parallelism)]))
                    else:
                        per_consumer.append(SimpleDispatcher(
                            out_leg(fid, d_fid, k, idx, idx)))
                dispatcher = (per_consumer[0] if len(per_consumer) == 1
                              else FanoutDispatcher(per_consumer))
            env.coord.register_actor(actor_id)
            actor = Actor(actor_id, root, dispatcher, env.coord)
            env.coord.stats.register(env.memory_scope or "flow",
                                     actor, root)
            dep.actor_fragment[actor_id] = fid
            dep.frag_actor_ids.setdefault(fid, []).append(actor_id)
            new_queues = list(env.pending_source_queues[q_before:])
            dep.actor_source_queues[actor_id] = new_queues
            dep.source_queues.extend(new_queues)
            dep.actor_root[actor_id] = root
            new_actors.append(actor)
    return new_actors


def _cluster_dispatcher(graph, f, cons, channels, placement, my_worker,
                        remote_outs, idx):
    """Output dispatcher for LOCAL actor `idx` of fragment `f`: per
    consumer-actor targets are local channels or connected RemoteOutputs
    (both are awaitable `send(msg)` sinks, so the dispatchers are
    agnostic)."""
    if not cons:
        return None
    per_consumer = []
    for d_fid, k in cons:
        d = graph.fragments[d_fid]

        def target(di):
            if placement[d_fid][di] == my_worker:
                return channels[(f.fid, d_fid, k)][(idx, di)]
            return remote_outs[(f.fid, d_fid, k, idx, di)]

        if f.dispatch == "hash":
            if d.parallelism == 1:
                # same singleton-consumer simplification as
                # _dispatcher_for: one output = no routing needed
                per_consumer.append(SimpleDispatcher(target(0)))
            else:
                outs = [target(di) for di in range(d.parallelism)]
                per_consumer.append(HashDispatcher(
                    outs, f.dist_key_indices,
                    vnode_to_shard(d.parallelism)))
        elif f.dispatch == "broadcast":
            per_consumer.append(BroadcastDispatcher(
                [target(di) for di in range(d.parallelism)]))
        else:
            assert d.parallelism == f.parallelism, \
                "simple dispatch is 1:1 (NoShuffle)"
            per_consumer.append(SimpleDispatcher(target(idx)))
    return (per_consumer[0] if len(per_consumer) == 1
            else FanoutDispatcher(per_consumer))
