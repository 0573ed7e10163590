"""Test harness: the suite runs on the CPU, on eight virtual devices.

Tests never take a chip (the chip path is proven by `python chip_smoke.py`
on the machine that has one): they must be deterministic and multi-device
wherever they run, so this file forces the CPU backend with
`--xla_force_host_platform_device_count=8` before jax initializes — by
environment for child processes and by `jax.config.update` for this one.
Sharding semantics are validated on that virtual mesh (the reference's
analogue is the single-process madsim cluster, SURVEY.md §4)."""

import asyncio
import inspect
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

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
