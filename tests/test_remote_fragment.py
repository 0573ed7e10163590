"""Planner-placed remote fragments (VERDICT r4 #6): a join fragment
runs in a SECOND OS PROCESS (risingwave_tpu.worker) connected by the
DCN tier, with barriers aligning across the boundary and session
recovery rebuilding the cross-process topology.

Reference: exchange/input.rs:103-120 + exchange_service.rs:78 (the
reference's every CN serves fragments to peers).
"""

import asyncio
import os
import subprocess
import sys
from collections import Counter

import pytest

from risingwave_tpu.common import EpochPair
from risingwave_tpu.frontend import Session
from risingwave_tpu.stream.actor import Actor
from risingwave_tpu.stream.executor import Executor
from risingwave_tpu.stream.message import Barrier, StopMutation
from risingwave_tpu.stream.remote_fragment import RemoteFragmentExecutor

W = 10_000_000
JOIN_SQL = (f"SELECT P.id, P.window_start "
            f"FROM TUMBLE(person, date_time, {W}) P "
            f"JOIN TUMBLE(auction, date_time, {W}) A "
            f"ON P.id = A.seller AND P.window_start = A.window_start")


async def _other_tasks() -> list:
    """Every task but ours, as of NOW. Only the loop's own finalizers
    get time: below an actor's root executor `async for` leaves each
    input's generator to them, a task of one step per level with no
    await in any; the root, the one level whose `finally` waits, is
    closed by the actor (test_actor_closes_its_chain_before_it_ends).
    A finalizer that has not ended after 100 steps is reported too."""
    def others(finalizers: bool) -> list:
        return [t for t in asyncio.all_tasks()
                if t is not asyncio.current_task() and finalizers == (
                    type(t.get_coro()).__name__ == "async_generator_athrow")]
    now = others(False)
    for _ in range(100):
        if not others(True):
            break
        await asyncio.sleep(0)
    return now + others(True)


async def _drop_all_leaves_no_task(s) -> None:
    """After drop_all() no task of the dropped deployment is alive:
    credit loops, connection handlers, pumps (the coordinator's
    cancelled watchdog is not the deployment's)."""
    await s.drop_all()
    left = [t for t in await _other_tasks()
            if t.get_name() != "barrier-watchdog"]
    assert not left, f"alive after drop_all: {left}"


async def _shutdown_leaves_no_task(s) -> None:
    """After shutdown() no task at all — asyncio.run's close then has
    nothing to cancel and nothing to wait for."""
    await s.shutdown()
    left = await _other_tasks()
    assert not left, f"alive after shutdown: {left}"


class _SlowClose(Executor):
    """A root whose `finally` waits, as RemoteFragmentExecutor's does."""

    def __init__(self, msgs):
        self.msgs, self.closed = msgs, False

    async def execute(self):
        try:
            for m in self.msgs:
                yield m
            await asyncio.Event().wait()
        finally:
            for _ in range(5):
                await asyncio.sleep(0)
            self.closed = True


class _Collector:
    def __init__(self, root):
        self.root, self.closed_at_collect = root, []

    def collect(self, actor_id, barrier):
        self.closed_at_collect.append(self.root.closed)


class _Refuses:
    async def dispatch(self, msg):
        raise RuntimeError("downstream gone")


@pytest.mark.parametrize("how", ["stop", "dies"])
async def test_actor_closes_its_chain_before_it_ends(how):
    """The actor closes the generator it drops: on its stop barrier
    BEFORE the collect (Deployment.stop cancels the tasks once the stop
    barrier is collected), and when it dies between two polls. Parent:
    `closed` stays False until the loop finalizes the generator."""
    b1 = Barrier(EpochPair(2, 1))
    stop = Barrier(EpochPair(3, 2), mutation=StopMutation(frozenset({7})))
    root = _SlowClose([b1, stop])
    coll = _Collector(root)
    actor = Actor(7, root, _Refuses() if how == "dies" else None, coll)
    if how == "stop":
        await actor.spawn()
        assert coll.closed_at_collect == [False, True]
    else:
        with pytest.raises(RuntimeError, match="downstream gone"):
            await actor.spawn()
    assert root.closed
    assert not await _other_tasks()


@pytest.fixture()
def worker_proc():
    # no pipes at all: pytest's fd-level capture interacts badly with a
    # child sharing its stdio — pick a free port up front and poll for
    # the listener instead of reading it from the worker's stdout
    import socket
    import time
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen(
        [sys.executable, "-m", "risingwave_tpu.worker", str(port)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port),
                                     timeout=1).close()
            break
        except OSError:
            time.sleep(0.2)
    else:
        p.terminate()
        raise RuntimeError("worker never started listening")
    yield port
    p.terminate()
    p.wait(timeout=10)


async def _mk(s, port):
    # volatile session (v1 remote fragments hold no durable state) and
    # NO watermark eviction: volatile recovery replays both sources
    # from offset 0 with a different chunk interleaving than the
    # original run, and eviction under the replayed watermarks could
    # drop early-window state the re-run still needs — the DURABLE
    # eviction+recovery interaction is covered by test_mesh_sql.py
    await s.execute("SET streaming_durability = 0")
    await s.execute(f"SET streaming_fragment_worker = '127.0.0.1:{port}'")
    await s.execute(
        "CREATE SOURCE person WITH (connector='nexmark', table='person', "
        "primary_key='id', chunk_size=128, rate_limit=256)")
    await s.execute(
        "CREATE SOURCE auction WITH (connector='nexmark', "
        "table='auction', primary_key='id', chunk_size=384, "
        "rate_limit=768)")
    await s.execute(f"CREATE MATERIALIZED VIEW rj AS {JOIN_SQL}")


def _oracle(offs):
    from oracle import nexmark_prefix
    p = nexmark_prefix("person", offs["person"])
    a = nexmark_prefix("auction", offs["auction"])
    persons: dict = {}
    for pid, ts in zip(p[0], p[6]):
        w = int(ts) - int(ts) % W
        persons.setdefault(w, set()).add(int(pid))
    exp = Counter()
    for seller, ts in zip(a[7], a[5]):
        w = int(ts) - int(ts) % W
        if int(seller) in persons.get(w, ()):
            exp[(int(seller), w)] += 1
    return exp


def _source_offsets(session, mv):
    """Volatile sessions have no offset state tables: read the
    connectors directly AFTER quiescing (tick boundaries make the
    committed prefix equal the connector offset here)."""
    from risingwave_tpu.stream.source import SourceExecutor
    offs: dict = {}
    for roots in session.catalog.mvs[mv].deployment.roots.values():
        for root in roots:
            node = root
            while node is not None:
                if isinstance(node, SourceExecutor):
                    offs[node.connector.table] = node.connector.offset
                node = getattr(node, "input", None)
    return offs


async def test_join_fragment_runs_in_worker_process(worker_proc):
    s = Session()
    await _mk(s, worker_proc)
    rf = [r for roots in
          s.catalog.mvs["rj"].deployment.roots.values() for r in roots
          if isinstance(r, RemoteFragmentExecutor)]
    assert rf, "join fragment was not placed remotely"
    await s.tick(4)
    # quiesce: pause sources so the connector offsets match the
    # materialized prefix exactly
    from risingwave_tpu.stream.message import PauseMutation
    b = await s.coord.inject_barrier(mutation=PauseMutation())
    await s.coord.wait_collected(b)
    # epochs commit IN ORDER at the NEXT barrier: two quiesce rounds
    # after the pause make everything the offsets cover durable
    for _ in range(2):
        b = await s.coord.inject_barrier()
        await s.coord.wait_collected(b)
    got = Counter(s.query("SELECT id, window_start FROM rj"))
    exp = _oracle(_source_offsets(s, "rj"))
    assert sum(exp.values()) > 0, "oracle vacuous"
    assert got == exp, (
        f"remote join diverged: {sum(got.values())} vs "
        f"{sum(exp.values())}; {list((got - exp).items())[:3]} / "
        f"{list((exp - got).items())[:3]}")
    await _drop_all_leaves_no_task(s)
    await _shutdown_leaves_no_task(s)


async def test_remote_fragment_survives_recovery(worker_proc):
    s = Session()
    await _mk(s, worker_proc)
    await s.tick(2)
    victim = s.catalog.mvs["rj"].deployment.tasks[-1]
    victim.cancel()
    try:
        await victim
    except (asyncio.CancelledError, Exception):
        pass
    await s.tick(3)
    assert s.recoveries >= 1
    rf = [r for roots in
          s.catalog.mvs["rj"].deployment.roots.values() for r in roots
          if isinstance(r, RemoteFragmentExecutor)]
    assert rf, "recovery dropped the remote placement"
    from risingwave_tpu.stream.message import PauseMutation
    b = await s.coord.inject_barrier(mutation=PauseMutation())
    await s.coord.wait_collected(b)
    for _ in range(2):
        b = await s.coord.inject_barrier()
        await s.coord.wait_collected(b)
    got = Counter(s.query("SELECT id, window_start FROM rj"))
    exp = _oracle(_source_offsets(s, "rj"))
    assert sum(exp.values()) > 0
    assert got == exp, (
        f"post-recovery divergence: {sum(got.values())} vs "
        f"{sum(exp.values())}")
    await _drop_all_leaves_no_task(s)
    await _shutdown_leaves_no_task(s)


async def test_single_process_session_leaves_no_task():
    """The same end state without a worker: the coordinator's watchdog
    is awaited by shutdown(), not left to the loop's close."""
    s = Session()
    await s.execute("SET streaming_durability = 0")
    await s.execute(
        "CREATE SOURCE person WITH (connector='nexmark', table='person', "
        "primary_key='id', chunk_size=128, rate_limit=256)")
    await s.execute("CREATE MATERIALIZED VIEW p AS SELECT id FROM person")
    await s.tick(2)
    assert s.query("SELECT id FROM p")
    await _shutdown_leaves_no_task(s)
