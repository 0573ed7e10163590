"""jit_state — the one jax.jit wrapper for state-threading programs.

Every stateful executor jits a handful of step programs (`_apply`,
`_flush`, `_evict`, `_rehash`, ...) and threads a large device-resident
state pytree through them functionally.  Wrapping them uniformly here buys
two things the raw `jax.jit` call sites could not:

* **Buffer donation** — `donate_argnums` marks the threaded state (and
  device-resident accumulators) as consumed, so XLA reuses the table
  buffers in place instead of allocating a fresh copy of the full state
  every chunk.  The hot-path cost of NOT donating is one full HBM
  alloc+copy of the hash-table arrays per chunk per executor.  Donation is
  real on this stack's CPU backend too (donated arrays are deleted), which
  keeps aliasing bugs visible under the tier-1 tests instead of only on
  TPU.  CALLERS MUST NOT hold other references to donated arrays — the
  executors thread `self.state = self._apply(self.state, ...)`, which is
  exactly the safe shape.  State that is aliased elsewhere (snapshot diff
  bases, `prev_*` emission copies) must NOT be donated; those call sites
  say so explicitly.

* **Dispatch / recompile accounting** — the north-star workloads are
  host-dispatch-bound (a sub-millisecond program costs more to dispatch
  than to run), so dispatches-per-barrier-interval and
  recompiles-after-warmup are first-class metrics.  The wrapper counts a
  dispatch per call and a compile per trace (the traced Python body runs
  exactly once per new static signature), into both per-program labelled
  counters and the process totals `jit_compile_count` /
  `device_dispatch_count` in GLOBAL_METRICS (surfaced by the `\\metrics`
  REPL command and scripts/dispatch_profile.py).

* **A name for every program** — every program here is `jax.jit(traced)`,
  so to XLA each is the module `jit_traced` and a device trace shows
  `jit_traced(<program id>)`. The module keeps that name (the benchmark's
  `exec_dev_s_per_ckpt` sums the modules that match it); identity comes
  from this side. The body is traced inside `jax.named_scope(self.name)`, so
  every op's `op_name` carries the executor step, and each compiled
  signature enters `PROGRAMS` once, as (name, static arguments, program
  id), right after the call that compiled it: `.lower().compile()` of the
  same arguments finds jax's own cached trace and executable, so nothing
  is traced or compiled a second time. The id sits inside the serialized
  executable (`program_id_of_serialized`), which costs by its size to
  make: `programs_by_id()` reads the ids when asked, after a trace, and
  names the trace's modules. Under a span scope (utils/trace.py) a call is also the
  span `dispatch:<name>`: the host time to enqueue it.
"""

from __future__ import annotations

import time
import warnings
from typing import NamedTuple, Optional, Sequence

import jax

from ..utils.metrics import (
    DEVICE_DISPATCHES, GLOBAL_METRICS, JIT_COMPILES,
)
from ..utils.trace import TraceAnnotation, current_scope

# A donated buffer whose shape matches no output (e.g. a growing rehash)
# is simply not reused; jax warns per lowering. The fallback is the
# pre-donation behavior, not an error — keep the logs quiet.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


class Program(NamedTuple):
    """One compiled signature of a StateJit."""
    name: str                   # the StateJit's
    statics: tuple              # ((argument, value), ...) of its static args
    program_id: Optional[int]   # the <id> of the xplane's `jit_traced(<id>)`

    @property
    def label(self) -> str:
        """`sorted_join_apply[side=0,match_factor=2]`."""
        if not self.statics:
            return self.name
        return self.name + "[" + ",".join(
            f"{k}={v}" for k, v in self.statics) + "]"


# every signature a StateJit compiled in this process, in compile order
PROGRAMS: list = []


def programs_by_id() -> dict:
    """program id -> Program, for naming a device trace's modules. Reads
    the ids not read yet (a serialization each: call it after the window,
    not in it)."""
    while _UNRESOLVED:
        index, executable = _UNRESOLVED.popitem()
        try:
            pid = program_id_of_serialized(bytes(executable.serialize()))
        except Exception:   # noqa: BLE001 — a name less, never a failure
            pid = None
        PROGRAMS[index] = PROGRAMS[index]._replace(program_id=pid)
    return {p.program_id: p for p in PROGRAMS if p.program_id is not None}


def _varint(b: bytes, i: int) -> tuple:
    v = shift = 0
    while True:
        c = b[i]
        i += 1
        v |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return v, i


def program_id_of_serialized(ser: bytes) -> Optional[int]:
    """The program id inside a serialized TPU executable (jax 0.9.0,
    libtpu's PjRt format, found on a v5e by searching every host-side
    handle for the ids a trace showed, PERF.md): the bytes are a run of
    varint-length-prefixed protobuf messages, and the id is the varint
    field 9 at the top level of the SECOND one. `fingerprint` (32 bytes, in
    the first message) and the HLO module's `id` are other numbers. None
    where the bytes are not of that form."""
    try:
        n, i = _varint(ser, 0)          # the first message: skipped
        n, i = _varint(ser, i + n)      # the second
        end = i + n
        while i < end:
            key, i = _varint(ser, i)
            field, wire = key >> 3, key & 7
            if wire == 0:
                v, i = _varint(ser, i)
                if field == 9:
                    return v
            elif wire == 2:
                n, i = _varint(ser, i)
                i += n
            elif wire == 1:
                i += 8
            elif wire == 5:
                i += 4
            else:
                return None
    except IndexError:
        pass
    return None


# Serializing an executable costs by its size (seconds for a 200 MB one, and
# nothing says it lets go of the GIL), so an id is read when somebody asks
# for it, which is after a trace, never in the call that compiled. Until
# then the executable itself waits here, PROGRAMS index -> executable: the
# newest MAX_UNRESOLVED of them (a process that recompiles for ever and is
# never asked keeps no more).
_UNRESOLVED: dict = {}
MAX_UNRESOLVED = 1024


def _want_program_id(index: int, compiled) -> None:
    """Keep PROGRAMS[index]'s executable for its id; only a TPU's traces
    show one."""
    executable = compiled.runtime_executable()
    if getattr(getattr(executable, "client", None), "platform", "") != "tpu":
        return
    _UNRESOLVED[index] = executable
    if len(_UNRESOLVED) > MAX_UNRESOLVED:
        del _UNRESOLVED[next(iter(_UNRESOLVED))]


def _short(v):
    return v if isinstance(v, (int, str, bool, float, type(None))) \
        else type(v).__name__


class StateJit:
    """A jitted program with donation + dispatch/recompile counters.

    Call it exactly like the jitted function. `dispatches` / `compiles`
    expose host-side totals for tests and the dispatch_profile harness.
    """

    def __init__(self, fn, *, donate_argnums: Sequence[int] = (),
                 static_argnums=None, static_argnames=None,
                 name: Optional[str] = None):
        self.name = name or getattr(fn, "__name__", "step").lstrip("_")
        self._dispatch_c = GLOBAL_METRICS.counter(
            "device_dispatch_count", program=self.name)
        self._compile_c = GLOBAL_METRICS.counter(
            "jit_compile_count", program=self.name)

        self._span = "dispatch:" + self.name
        self._anno = "rw:" + self._span
        nums = (static_argnums,) if isinstance(static_argnums, int) \
            else tuple(static_argnums or ())
        names = (static_argnames,) if isinstance(static_argnames, str) \
            else tuple(static_argnames or ())
        # set by `traced`, taken by the call that ran it: the static
        # arguments of a signature that was just compiled
        self._fresh: Optional[tuple] = None

        def traced(*args, **kwargs):
            # runs once per trace == once per compiled signature
            self._compile_c.inc()
            JIT_COMPILES.inc()
            self._fresh = tuple(
                [(i, _short(args[i])) for i in nums if i < len(args)]
                + [(k, _short(kwargs[k])) for k in names if k in kwargs])
            with jax.named_scope(self.name):
                return fn(*args, **kwargs)

        jit_kwargs: dict = {}
        if donate_argnums:
            jit_kwargs["donate_argnums"] = tuple(donate_argnums)
        if static_argnums is not None:
            jit_kwargs["static_argnums"] = static_argnums
        if static_argnames is not None:
            jit_kwargs["static_argnames"] = static_argnames
        self._jitted = jax.jit(traced, **jit_kwargs)

    def __call__(self, *args, **kwargs):
        self._dispatch_c.inc()
        DEVICE_DISPATCHES.inc()
        self._fresh = None      # a call that raised mid-trace left it
        sc = current_scope()
        if sc is None:
            out = self._jitted(*args, **kwargs)
        else:
            t0 = time.monotonic_ns()
            with TraceAnnotation(self._anno):
                out = self._jitted(*args, **kwargs)
            sc.dispatch(self._span, t0, time.monotonic_ns())
        if self._fresh is not None:
            self._register(args, kwargs)
        return out

    def precompile(self, *args, **kwargs) -> None:
        """Trace, lower and compile the signature of these arguments without
        running it (nothing is donated, nothing dispatched): a later call
        with the same shapes finds jax's cached trace and executable, and a
        persistent compile cache has the program from here on. For programs
        whose first call would otherwise fall into somebody's measured
        window (a hash agg's purge). Counts as the compile it is."""
        self._fresh = None
        self._jitted.lower(*args, **kwargs).compile()
        if self._fresh is not None:
            self._register(args, kwargs)

    def _register(self, args, kwargs) -> None:
        """Enter the signature this call compiled into PROGRAMS. Lowering
        the same arguments again reads jax's caches (the trace, the
        lowering and its executable), donated arguments included: their
        shapes outlive their buffers."""
        statics, self._fresh = self._fresh, None
        PROGRAMS.append(Program(self.name, statics, None))
        try:
            _want_program_id(len(PROGRAMS) - 1,
                             self._jitted.lower(*args, **kwargs).compile())
        except Exception:   # noqa: BLE001 — called under another trace
            pass

    @property
    def dispatches(self) -> int:
        return int(self._dispatch_c.value)

    @property
    def compiles(self) -> int:
        return int(self._compile_c.value)


def jit_state(fn, *, donate_argnums: Sequence[int] = (),
              static_argnums=None, static_argnames=None,
              name: Optional[str] = None) -> StateJit:
    """`jax.jit` with buffer donation for the threaded state pytree plus
    dispatch/recompile counters. Drop-in at every stateful executor's jit
    call site; see the module docstring for the donation aliasing rules."""
    return StateJit(fn, donate_argnums=donate_argnums,
                    static_argnums=static_argnums,
                    static_argnames=static_argnames, name=name)
