"""Test harness: the suite runs on the CPU, on eight virtual devices.

Tests never take a chip (the chip path is proven by `benchmark/run.py`
on the machine that has one): they must be deterministic and multi-device
wherever they run, so this file forces the CPU backend with
`--xla_force_host_platform_device_count=8` before jax initializes — by
environment for child processes and by `jax.config.update` for this one.
Sharding semantics are validated on that virtual mesh (the reference's
analogue is the single-process madsim cluster, SURVEY.md §4)."""

import asyncio
import contextlib
import faulthandler
import inspect
import os
import signal

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# XLA's CPU client sizes its thread pool by the box's cores (NPROC, when
# set, stands in for them: xla's DefaultThreadPoolSize). On 8 cores the
# 8 virtual devices of one collective need every thread of the pool;
# whatever else holds one then keeps the eighth participant out, and
# after 40 s the rendezvous aborts the process (7 of 8 arrived; 5 of 56
# runs of test_fused_join_planned_bit_identical_and_recovers, 8 at
# once). Twice the devices leaves slack: 0 of 96.
os.environ.setdefault("NPROC", str(max(16, os.cpu_count() or 1)))

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache (utils/compile_cache.py — the one rule:
# $JAX_COMPILATION_CACHE_DIR as is, else <checkout>/.jax_cache): every
# test builds fresh executors, so identical q7/join/shard_map shapes
# re-trace in file after file and each pays the same multi-second
# compile again — the disk cache dedupes those within one suite run (and
# across runs on the same box). Only the compile is skipped; programs
# and results are bit-identical.
from risingwave_tpu.utils.compile_cache import enable_persistent_cache

enable_persistent_cache()

import pytest

# The one deadline of the suite: every test gets this long for its
# setup + call + teardown, the event loop's close inside `asyncio.run`
# included (a hang there is behind every `wait_for` of the test body).
TEST_LIMIT_S = 240.0


@contextlib.contextmanager
def time_limit(seconds: float, what: str):
    """pytest-timeout's signal method (the plugin is not in the image):
    after `seconds` SIGALRM dumps every thread's stack and raises
    `pytest.fail` in the main thread, wherever it is — xdist runs tests
    on the worker's main thread. The test FAILS with a traceback and the
    worker goes on to its next test. Nests: the outer timer and handler
    come back on exit."""
    def on_alarm(signum, frame):
        faulthandler.dump_traceback(file=2, all_threads=True)
        # a raise that lands inside a task's step is kept by that task
        # and may never surface: the next one will
        signal.setitimer(signal.ITIMER_REAL, seconds)
        pytest.fail(f"{what} ran into the {seconds:g} s per-test limit")

    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    old_timer = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *old_timer)
        signal.signal(signal.SIGALRM, old_handler)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item, nextitem):
    with time_limit(TEST_LIMIT_S, item.nodeid):
        return (yield)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Run `async def` tests under asyncio (pytest-asyncio is not in the
    image; this is the 10-line equivalent)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {n: pyfuncitem.funcargs[n] for n in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True
    return None
