"""Actor — the schedulable unit driving one executor chain.

Reference: src/stream/src/executor/actor.rs:138-247 — an infinite loop pulling
the chain's final stream, fanning out through the dispatcher, reporting every
barrier to the local barrier manager (`collect`), exiting on a Stop mutation.
Here actors are asyncio tasks; device work inside executors runs async to the
host loop (XLA dispatch is non-blocking until results are fetched).

Observability (stream/monitor.py): when the coordinator's StreamingStats
attaches an `ActorObs` (metric_level >= info), the loop times every poll
of the chain and splits each barrier interval into apply (chunk compute +
dispatch), persist (the barrier-yielding poll — the chain's flush/commit
work), and align (input-channel waits reported by the exchange inputs +
the epoch fence). The split rides to the EpochTracer at collect time, so
`\trace` answers "who held epoch N and doing what". Each poll and the
fence are also spans (`actor.apply` / `actor.persist` / `actor.fence`,
utils/trace.py): the actor's `SpanScope` is the scope in force in its task,
so a `StateJit` call or a d2h fetch deep in the chain's generators records
itself as the poll's child, and the barrier hands the interval's spans to
the log under its epoch. At metric_level=off `self.obs` is None, no scope
is in force and the loop is the uninstrumented one.
"""

from __future__ import annotations

import asyncio
from contextlib import aclosing
from typing import Optional, Protocol

from ..common.chunk import StreamChunk
from ..utils.faults import FAULTS, FaultInjected
from ..utils.trace import set_scope
from .exchange import Dispatcher
from .executor import Executor
from .message import Barrier
from .monitor import dispatcher_fanout


class BarrierCollector(Protocol):
    def collect(self, actor_id: int, barrier: Barrier) -> None: ...


class Actor:
    def __init__(self, actor_id: int, consumer: Executor,
                 dispatcher: Optional[Dispatcher],
                 collector: Optional[BarrierCollector]):
        self.actor_id = actor_id
        self.consumer = consumer
        self.dispatcher = dispatcher
        self.collector = collector
        self.rows_processed = 0
        # per-chain epoch fence (plan/build._fuse_mesh_chains): a HOLLOW
        # producer actor dispatches no device programs of its own — its
        # stages run inside the downstream fused program, whose actor's
        # fence covers the whole chain — so its barrier path skips the
        # token gather + block
        self.fence_exempt = False
        # per-actor instrument bundle (stream/monitor.py ActorObs);
        # attached/removed by the coordinator's StreamingStats
        self.obs = None

    async def run(self) -> None:
        try:
            await self._run_inner()
        except BaseException as e:
            # report the death so barrier collection fails fast instead of
            # hanging the coordinator (reference: collection failure =>
            # global recovery, barrier/recovery.rs:332)
            failed = getattr(self.collector, "actor_failed", None)
            if failed is not None:
                failed(self.actor_id, e)
            raise

    async def _run_inner(self) -> None:
        last_token = None
        # the chain is closed HERE, where it is dropped: an actor that
        # stops or dies never resumes the generator, and an executor's
        # `finally` (sockets, tasks) would otherwise run whenever the
        # loop finalizes the collected generator — later, as a detached
        # task nobody awaits
        async with aclosing(self.consumer.execute()) as it:
            scoped = None       # the obs whose scope is in force
            while True:
                obs = self.obs
                if obs is not scoped:
                    scoped = obs
                    set_scope(obs.scope if obs is not None else None)
                if obs is not None:
                    poll = obs.begin_poll()
                try:
                    msg = await it.__anext__()
                except StopAsyncIteration:
                    return
                if obs is not self.obs:
                    # re-instrumented while parked in the poll (SET
                    # metric_level): restart the span at the switch point so
                    # this very message already reports under the new level
                    obs = scoped = self.obs
                    set_scope(obs.scope if obs is not None else None)
                    if obs is not None:
                        poll = obs.begin_poll()
                if isinstance(msg, StreamChunk):
                    if msg.columns:
                        last_token = msg.columns[0].data
                    if self.dispatcher is not None:
                        await self.dispatcher.dispatch(msg)
                    if obs is not None:
                        # poll span minus the channel-recv wait accrued inside
                        # it = actual chunk compute + dispatch time
                        obs.end_poll(poll, barrier=False)
                        obs.note_chunk_out(msg,
                                           dispatcher_fanout(self.dispatcher))
                elif isinstance(msg, Barrier):
                    if FAULTS.active and FAULTS.hit(
                            "actor_crash", actor=self.actor_id,
                            epoch=msg.epoch.curr) is not None:
                        # before the dispatch: downstream never sees this
                        # barrier, exactly like a mid-interval executor death
                        raise FaultInjected(
                            f"injected actor_crash at actor {self.actor_id} "
                            f"epoch {msg.epoch.curr}")
                    barrier = msg.with_passed(self.actor_id)
                    if self.dispatcher is not None:
                        await self.dispatcher.dispatch(barrier)
                    if obs is not None:
                        # the barrier-yielding poll is the chain's barrier
                        # work: every executor's flush/persist/commit runs
                        # inside it before the barrier emerges
                        obs.end_poll(poll, barrier=True)
                    # Epoch fence: the barrier is only reported collected once
                    # every device program of the epoch has actually executed
                    # (the chain dispatches asynchronously) — the last chunk
                    # covers per-chunk programs; executor fence tokens cover
                    # barrier-time programs (flush/evict/purge) dispatched
                    # after it. block_until_ready moves no data (a d2h
                    # transfer here would serialise with dispatch).
                    # Blocking runs in a worker thread so other actors keep
                    # draining.
                    from .executor import gather_fence_tokens
                    if self.fence_exempt:
                        tokens = []
                    else:
                        tokens = ([last_token]
                                  if last_token is not None else [])
                        tokens.extend(gather_fence_tokens(self.consumer))
                    if obs is not None:
                        fence = obs.begin_fence()
                    for tok in tokens:
                        if hasattr(tok, "block_until_ready"):
                            await asyncio.to_thread(tok.block_until_ready)
                    last_token = None
                    if obs is not None:
                        obs.end_fence(fence)
                        phases = obs.on_barrier()
                        ph = getattr(self.collector, "collect_phases", None)
                        if ph is not None:
                            ph(self.actor_id, barrier, phases)
                        obs.flush_spans(barrier.epoch.curr)
                    stop = barrier.is_stop(self.actor_id)
                    if stop:
                        # BEFORE the collect: whoever stops a deployment
                        # cancels its tasks once the stop barrier is
                        # collected, and a cancelled close is half a close
                        await it.aclose()
                    if self.collector is not None:
                        self.collector.collect(self.actor_id, barrier)
                    if stop:
                        return
                else:
                    if self.dispatcher is not None:
                        await self.dispatcher.dispatch(msg)
                    if obs is not None:
                        obs.end_poll(poll, barrier=False)

    def spawn(self) -> asyncio.Task:
        return asyncio.create_task(self.run(), name=f"actor-{self.actor_id}")
