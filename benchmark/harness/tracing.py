"""`--trace 1`: the profiler around two consecutive checkpoints in the middle
of the window, the harness's own spans on the same clock, and a sampler of
the host's threads for the same interval.

The spans are the harness's calls into the engine (quota wait, inject,
collect wait, drain); spans inside the engine are a later `tracing` PR.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
import sys
import tempfile
import threading
import time

mono = time.monotonic_ns
TRACED_CHECKPOINTS = 2
SYNC_SPAN = "bench_clock_sync"


def span(tracer, name: str):
    """A harness span around a call into the engine; nothing without a
    tracer."""
    return tracer.span(name) if tracer is not None \
        else contextlib.nullcontext()


_IDLE_FILES = ("selectors.py", "threading.py", "queue.py")


def _idle(frame) -> bool:
    """A thread parked in the event loop's select, a lock, a queue, or a
    pool worker waiting for work."""
    code = frame.f_code
    return (code.co_filename.endswith(_IDLE_FILES)
            or (code.co_filename.endswith("futures/thread.py")
                and code.co_name == "_worker"))


def _frame_label(frame) -> str:
    """The innermost frame of the engine or the benchmark on this stack, or
    the innermost frame at all."""
    inner = None
    f = frame
    while f is not None:
        fn = f.f_code.co_filename
        tok = f"{fn.rsplit('/', 1)[-1]}:{f.f_code.co_name}"
        if inner is None:
            inner = tok
        if "risingwave_tpu" in fn or "/benchmark/" in fn:
            return tok
        f = f.f_back
    return inner or "?"


class HostSampler(threading.Thread):
    """Samples every thread's stack at ~100 Hz with a timestamp, so that an
    idle gap of the device can be labelled by what the host was doing."""

    def __init__(self, hz: float = 100.0):
        super().__init__(name="bench-host-sampler", daemon=True)
        self.interval = 1.0 / hz
        self.samples: list = []      # (t_ns, main label, [worker labels])
        self._stop_evt = threading.Event()
        self._main = threading.main_thread().ident

    def run(self) -> None:
        me = threading.get_ident()
        while not self._stop_evt.is_set():
            main, workers = "?", []
            for ident, frame in sys._current_frames().items():
                if ident == me:
                    continue
                if ident == self._main:
                    main = ("event_loop_idle" if _idle(frame)
                            else _frame_label(frame))
                elif not _idle(frame):
                    workers.append(_frame_label(frame))
            self.samples.append((mono(), main, workers))
            time.sleep(self.interval)

    def stop(self) -> None:
        self._stop_evt.set()
        self.join()


class WindowTracer:
    """Starts the profiler at the first checkpoint after 40% of the window
    (or where only two checkpoints are left) and stops it two checkpoints
    later."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.started_at = None       # checkpoint index
        self.t_start_ns = self.t_stop_ns = self.sync_ns = None
        self.sampler = None
        self.spans: list = []
        self.samples: list = []
        self.stopped = False
        self.n_traced = 0

    @contextlib.contextmanager
    def span(self, name: str):
        """Kept in memory (monotonic ns) and written into the profiler's
        trace as a `TraceAnnotation`, while the profiler runs."""
        if self.started_at is None or self.stopped:
            yield
            return
        import jax
        t0 = mono()
        with jax.profiler.TraceAnnotation("bench:" + name):
            try:
                yield
            finally:
                self.spans.append((name, t0, mono()))

    async def at_checkpoint(self, k: int, progress: float,
                            last_chance: bool = False) -> None:
        """`progress`: the share of the window that has passed."""
        if self.started_at is None and (progress >= 0.4 or last_chance):
            self._start(k)
        elif (self.started_at is not None and not self.stopped
              and k >= self.started_at + TRACED_CHECKPOINTS):
            self._stop(k)

    async def finish(self, k: int) -> None:
        if self.started_at is not None and not self.stopped:
            self._stop(k)

    def _start(self, k: int) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # per-row Python would fill the trace
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started_at = k
        self.t_start_ns = mono()
        self.sync_ns = self.t_start_ns
        with jax.profiler.TraceAnnotation(SYNC_SPAN):
            time.sleep(0.001)
        self.sampler = HostSampler()
        self.sampler.start()

    def _stop(self, k: int) -> None:
        import jax
        self.n_traced = k - self.started_at
        self.t_stop_ns = mono()
        self.sampler.stop()
        self.samples = self.sampler.samples
        jax.profiler.stop_trace()
        self.stopped = True

    def xplane_path(self) -> str | None:
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None

    def cleanup(self) -> None:
        keep = os.environ.get("BENCH_KEEP_TRACE")
        path = self.xplane_path()
        if keep and path:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(keep, os.path.basename(path)))
        shutil.rmtree(self.dir, ignore_errors=True)
