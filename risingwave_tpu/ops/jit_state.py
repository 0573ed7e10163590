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
"""

from __future__ import annotations

import warnings
from typing import Optional, Sequence

import jax

from ..utils.metrics import (
    DEVICE_DISPATCHES, GLOBAL_METRICS, JIT_COMPILES,
)

# A donated buffer whose shape matches no output (e.g. a growing rehash)
# is simply not reused; jax warns per lowering. The fallback is the
# pre-donation behavior, not an error — keep the logs quiet.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


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

        def traced(*args, **kwargs):
            # runs once per trace == once per compiled signature
            self._compile_c.inc()
            JIT_COMPILES.inc()
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
        return self._jitted(*args, **kwargs)

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
