"""Host half of a fused mesh fragment's in-mesh shuffle.

The sharded executors (`sharded_agg`, `sharded_join`, `sharded_store`)
take a chunk row-sliced over the mesh axis and route its rows inside one
shard_map program (`parallel/exchange.mesh_ingest_chunk`). What the host
does around that program is the same for all of them and lives here:

* ENTRY — `_mesh_chunk`: a chunk whose capacity the shard count does not
  divide grows to the next multiple with invisible tail rows; the chain
  preludes (`set_mesh_preludes`) and the replay point (`MeshIngestLog`,
  `preload_replay`).
* SIZING — the per-(src, dst) send capacity a program is traced with:
  the manual `mesh_shuffle_slack`, else the adaptive hint derived from
  the send demand the barrier watchdog observed, else zero-drop sizing.
* ACCOUNTING — what crossed the mesh in a barrier interval, and how
  unevenly. The programs accumulate per shard, on the device, the
  largest send-bucket demand and the rows the shard RECEIVED
  (`_shuffle_obs_dev`, int32 [S, 2]); the watchdog pack the barrier
  fetch already brings to the host reduces them over the vnode axis
  (max fill, `psum` and `pmax` of the rows). The bytes the all_to_all
  buffers held are known from the traced shapes and counted at
  dispatch. `_publish_shuffle` puts them into `GLOBAL_METRICS`
  (`mesh_shuffle_*{executor=...}` beside the process totals) and keeps
  the interval's numbers for the epoch trace
  (`take_mesh_interval` <- `stream/monitor.ActorObs.on_barrier`).
  No device fetch of its own: with the watchdog off nothing is
  published and the trace reads 0.
"""

from __future__ import annotations

from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..common.chunk import StreamChunk, _pack_programs
from ..parallel.exchange import shuffle_cap_out
from ..parallel.mesh import VNODE_AXIS, vnode_to_shard
from ..utils.metrics import (GLOBAL_METRICS, MESH_SHUFFLE_COUNTERS,
                             MESH_SHUFFLE_DROPPED, MESH_SHUFFLE_MAX_FILL)

# lanes of `_shuffle_obs_dev[shard]`
OBS_FILL, OBS_ROWS = 0, 1

_NO_INTERVAL = {"mesh_rows": 0, "mesh_rows_max_shard": 0,
                "mesh_shuffle_bytes": 0}


def fold_shuffle_obs(obs, fill, local_vis):
    """One chunk into a shard's [2] observation lanes (inside shard_map):
    the largest send demand so far, the rows received so far."""
    rows = jnp.sum(local_vis, dtype=jnp.int32)
    return jnp.stack([jnp.maximum(obs[OBS_FILL], fill),
                      obs[OBS_ROWS] + rows])


class MeshIngestLog:
    """Host-side per-interval ingest snapshot of a fused mesh fragment —
    the mesh-plane REPLAY POINT. Every chunk entering the fused
    shard_map program is also retained here BY REFERENCE (device arrays
    are immutable and the ingest path never donates them, so holding
    them moves no data), stamped with the epoch its barrier seals, and
    dropped when that epoch COMMITS — the coordinator trims this log
    through the same pulse that trims the exchange replay buffers
    (plan/build.py registers it next to the fragment's channels). The
    log therefore always holds exactly the uncommitted ingest suffix,
    bounded by `checkpoint_max_inflight`; a mesh fragment failure
    re-runs the fused program from the committed epoch over this
    suffix (delivered back through the armed frontier channels) instead
    of tearing down the deployment. A hard cap backstops executors
    driven without a coordinator (engine-level tests)."""

    HARD_CAP = 8
    replay_enabled = True

    def __init__(self):
        self._pending: list = []
        self._log = deque()

    def note(self, item) -> None:
        self._pending.append(item)

    def seal(self, epoch: int) -> None:
        """Stamp the open interval's ingests with the epoch its barrier
        seals (called from the executor's barrier-time persist)."""
        if self._pending:
            self._log.append((epoch, self._pending))
            self._pending = []
            while len(self._log) > self.HARD_CAP:
                self._log.popleft()

    def trim_replay(self, committed_epoch: int) -> None:
        while self._log and self._log[0][0] <= committed_epoch:
            self._log.popleft()

    def entries(self) -> list:
        return list(self._log)

    def chunk_count(self) -> int:
        return sum(len(chunks) for _, chunks in self._log) \
            + len(self._pending)


class MeshShuffleHost:
    """Mixin of the sharded executors; needs `self.mesh`, `self.n_shards`
    and `self.identity`."""

    # `plan/build._register_mesh` names the executor as the memory manager
    # does ("<flow>/<identity>@a<actor>"); bare executors use their identity
    mesh_label: Optional[str] = None

    def _init_mesh_shuffle(self, mesh, slack: int,
                           watchdog_on: bool) -> None:
        self.mesh = mesh
        self.n_shards = mesh.shape[VNODE_AXIS]
        self._routing = jnp.asarray(vnode_to_shard(self.n_shards))
        self.mesh_shuffle_slack = int(slack)
        if self.mesh_shuffle_slack and not watchdog_on:
            raise ValueError(
                "mesh_shuffle_slack > 0 needs the barrier watchdog fetch "
                "(watchdog_interval=1): shuffle drops would otherwise go "
                "unchecked and a checkpoint could commit with rows "
                "missing; transfer-free pipelines must use slack 0 "
                "(zero-drop sizing)")
        # adaptive shuffle slack: send-bucket capacity derived from
        # OBSERVED per-destination demand (watchdog-fetched max fill,
        # asymmetric EWMA + peak floor). Engages only under zero-drop
        # sizing and only with the watchdog fetch active — overflow
        # under an adapted cap still fail-stops, recovery replays, and the
        # fresh executor restarts at zero-drop sizing.
        self.mesh_shuffle_adaptive = (self.mesh_shuffle_slack == 0
                                      and watchdog_on)
        # mesh-chain fusion (plan/build._fuse_mesh_chains): hollow producer
        # stage impls run INSIDE the fused program, before the shuffle
        self._mesh_preludes = ()
        self.mesh_chain: Optional[str] = None
        self._replay_preload: list = []
        # fused dispatches (one per interval batch in steady state)
        self.mesh_shuffle_applies = 0
        # mesh-plane replay point: the uncommitted ingest suffix, held
        # host-side by reference
        self.ingest_log = MeshIngestLog()
        self._cap_hint: Optional[int] = None
        self._fill_ewma = 0.0
        self._fill_peak = 0
        self._fill_obs = 0
        # all_to_all buffer bytes of ONE chunk, by the program's host-side
        # signature (noted while it traces), and this interval's running sum
        self._shuffle_chunk_bytes: dict = {}
        self._interval_bytes = 0
        self._interval = dict(_NO_INTERVAL)

    # ------------------------------------------------------------- entry
    def _mesh_chunk(self, chunk: StreamChunk) -> StreamChunk:
        """What the fused program is handed for `chunk`: shard_map slices
        the rows contiguously over the mesh axis, so a capacity the shard
        count does not divide grows to the next multiple with invisible
        tail rows (row order and update-pair adjacency kept). Any other
        chunk comes back as the SAME object, without a dispatch."""
        short = -chunk.capacity % self.n_shards
        if not short:
            return chunk
        return _pack_programs()["pad"](chunk, chunk.capacity + short)

    def set_mesh_preludes(self, fns, chain: Optional[str] = None) -> None:
        """Install hollow producer-stage impls (project / hop_window
        `_step_impl`s, root-to-source order reversed so the source-most
        runs first) to execute INSIDE the fused program, upstream of the
        shuffle. Must install before the first fused trace — the compiled
        programs close over the prelude list."""
        assert self.mesh_shuffle_applies == 0, \
            "mesh preludes must install before the first fused dispatch"
        self._mesh_preludes = tuple(fns)
        self.mesh_chain = chain

    def preload_replay(self, chunks) -> None:
        """Channel-free mesh replay: the uncommitted ingest suffix
        captured from the crashed executor's MeshIngestLog (plus its
        undrained pending chunks) is fed straight into the fused
        program — staged here, installed into the pending queue by the
        executor's recovery at the INITIAL barrier (AFTER the durable
        state rebuild; the INITIAL's own drain runs before it, so
        prepending now would apply the suffix to pre-recovery state),
        then re-run as one fused scan at the next barrier and re-noted
        into the fresh log by that drain. The frontier channels skip
        these chunks by identity (Channel.begin_replay skip_refs);
        barriers and watermarks still replay through them for epoch
        alignment."""
        self._replay_preload = list(chunks)

    # ------------------------------------------------------------ sizing
    def _trace_cap(self, local_rows: int) -> int:
        """Per-(src,dst) send capacity at TRACE time: the constructor's
        slack wins; otherwise the adaptive hint (2x pow2-quantized
        observed peak demand) once enough barriers have been observed;
        zero-drop sizing until then."""
        if not self.mesh_shuffle_adaptive or self._cap_hint is None:
            return shuffle_cap_out(local_rows, self.n_shards,
                                   self.mesh_shuffle_slack)
        return min(local_rows, max(64, self._cap_hint))

    def _note_send_fill(self, fill: int) -> None:
        """Adaptive slack observation (barrier-collection cadence): track
        the max per-destination send demand with an ASYMMETRIC EWMA —
        jumps up instantly on a larger fill (overflow safety beats
        smoothing), decays slowly on smaller ones — plus an all-time peak
        floor. The cap hint is 2x the pow2-ceiling of the worst signal
        and only engages after 3 observations, so caps never shrink below
        twice the worst demand ever seen; a workload whose skew suddenly
        doubles past that still fail-stops and replays at zero-drop."""
        if not self.mesh_shuffle_adaptive:
            return
        if fill > self._fill_ewma:
            self._fill_ewma = float(fill)
        else:
            self._fill_ewma = 0.8 * self._fill_ewma + 0.2 * fill
        self._fill_peak = max(self._fill_peak, fill)
        self._fill_obs += 1
        if self._fill_obs < 3:
            return
        worst = max(self._fill_ewma, float(self._fill_peak), 1.0)
        self._cap_hint = 1 << (int(2 * worst) - 1).bit_length()

    # -------------------------------------------------------- accounting
    def _fresh_shuffle_obs(self):
        return jax.device_put(
            jnp.zeros((self.n_shards, 2), dtype=jnp.int32),
            NamedSharding(self.mesh, P(VNODE_AXIS)))

    def _note_traced_shuffle(self, nbytes: int, local_rows: int,
                             *which) -> None:
        """Called while a fused program traces: the bytes its all_to_all
        buffers hold for one chunk (`parallel/exchange.shuffle_bytes`),
        under what the host knows of the program at dispatch — the cap
        hint in force, the raw chunk's rows per shard, and `which` of the
        executor's programs it is."""
        self._shuffle_chunk_bytes[(self._cap_hint, local_rows, *which)] = \
            int(nbytes)

    def _count_shuffle_dispatch(self, chunk, *which, chunks: int = 1) -> None:
        """After a fused dispatch of `chunks` chunks shaped like `chunk`
        (fillers included: their buffers cross the mesh like any other)."""
        self._interval_bytes += chunks * self._shuffle_chunk_bytes[
            (self._cap_hint, chunk.capacity // self.n_shards, *which)]

    def _publish_shuffle(self, rows: int, rows_max: int, fill: int) -> None:
        """At the barrier's watchdog fetch: the interval's numbers into the
        registry and kept for the epoch trace; the adaptive slack sees the
        fill; the device lanes start over."""
        nbytes, self._interval_bytes = self._interval_bytes, 0
        label = self.mesh_label or self.identity
        for (name, total), n in zip(MESH_SHUFFLE_COUNTERS.items(),
                                    (rows, rows_max, nbytes)):
            total.inc(n)
            GLOBAL_METRICS.counter(name, executor=label).inc(n)
        GLOBAL_METRICS.gauge(MESH_SHUFFLE_MAX_FILL,
                             executor=label).set(float(fill))
        self._interval = {"mesh_rows": rows, "mesh_rows_max_shard": rows_max,
                          "mesh_shuffle_bytes": nbytes}
        self._note_send_fill(fill)
        self._shuffle_obs_dev = self._fresh_shuffle_obs()

    def _fail_on_shuffle_drops(self, n_drop: int) -> None:
        """Fail-stop BEFORE this epoch's checkpoint commits: a row the
        shuffle dropped was never applied, so committing would make the
        loss durable and silent. Recovery replays from the last committed
        epoch; the slack needs raising (0 = zero-drop)."""
        if n_drop:
            MESH_SHUFFLE_DROPPED.inc(n_drop)
            raise RuntimeError(
                f"mesh shuffle overflow: {n_drop} rows dropped en route "
                f"to their owner shard (per-pair send capacity sized by "
                f"mesh_shuffle_slack={self.mesh_shuffle_slack}; 0 = "
                f"zero-drop sizing)")

    def take_mesh_interval(self) -> dict:
        """The barrier interval's `mesh_rows`, `mesh_rows_max_shard`,
        `mesh_shuffle_bytes` for the actor's phase dict; starts the next."""
        iv, self._interval = self._interval, dict(_NO_INTERVAL)
        return iv
