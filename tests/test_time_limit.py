"""The harness's per-test limit (tests/conftest.py `time_limit`): the
hang it exists for is an event loop whose close never ends — the test
body is over, `asyncio.run` cancels what is left and waits for a task
that took its one cancellation and awaited again.
"""

import asyncio
import signal
import time

import pytest

from conftest import TEST_LIMIT_S, time_limit


def test_limit_fails_a_loop_close_that_never_ends():
    async def stubborn():
        while True:
            try:
                await asyncio.sleep(3600)
            except asyncio.CancelledError:
                pass            # swallowed: the close waits forever

    async def body():
        asyncio.create_task(stubborn())
        await asyncio.sleep(0)

    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception, match="1 s per-test limit"):
        with time_limit(1.0, "probe"):
            asyncio.run(body())
    assert time.monotonic() - t0 < 10
    # this test's own limit (the hook's) is armed again behind it
    remaining, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 1.0 < remaining <= TEST_LIMIT_S
