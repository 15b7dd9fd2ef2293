"""The per-test time limit of conftest.py, so it cannot silently stop working."""

import asyncio
import time

import pytest

from conftest import TestLimitExceeded, time_limit


def test_limit_fails_an_await_nothing_completes():
    async def forever():
        await asyncio.get_running_loop().create_future()

    with pytest.raises(TestLimitExceeded) as ei:
        with time_limit(0.2, "tests/test_x.py::test_hangs (call)"):
            asyncio.run(forever())
    msg = str(ei.value)
    assert "tests/test_x.py::test_hangs (call) exceeded its limit of 0.2 s" in msg
    assert "test_limit.py" in msg and "forever" in msg  # the place it waited


def test_limit_is_lifted_when_the_block_ends():
    with time_limit(0.1, "quick"):
        pass
    time.sleep(0.3)  # a timer left armed would raise here
