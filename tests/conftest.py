"""Test harness config: force JAX onto a virtual 8-device CPU mesh.

The tests need no accelerator: sharding logic is validated on eight virtual
CPU devices, Pallas kernels run in interpret mode, and what only the chip's
compiler can say is asked of a DESCRIBED chip (tests/test_chip_compile.py).
The environment is set here, before any test module imports jax; the chip
itself is exercised by ``chip_smoke.py``.

Tiers: ``pytest tests/ -m 'not slow'`` is tier 1 (the driver's gate, run with
``-n 6 --dist loadfile``); ``pytest tests/`` is everything. Every phase of
every test runs under one time limit (``TEST_LIMIT_S`` below).
"""

import asyncio
import contextlib
import os
import signal

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("DYN_LOG", "warning")

import pytest


@pytest.fixture
def anyio_backend():
    return "asyncio"


# ---------------------------------------------------------------- test tiers
# ``@pytest.mark.slow`` (``pytest.param(..., marks=pytest.mark.slow)`` for one
# case of a parametrised test) is the only way a test is called slow: heavy
# sharding/parity variants and multi-process drives that keep a faster sibling
# in tier 1.


# --------------------------------------------------------- per-test time limit
#: seconds any one phase (setup, call, teardown) of a test may take. A hang
#: then FAILS that test, by name, and the worker goes on to the next, instead
#: of the whole run sleeping until the driver's clock cuts it. Four times the
#: slowest tier-1 test on 6 busy workers (test_parity.py::test_llama_parity,
#: 21-42 s over three whole runs; ROADMAP D13).
TEST_LIMIT_S = 180.0


class TestLimitExceeded(Exception):
    __test__ = False  # an exception, not a test class


def _first_attr(obj, *names):
    return next((v for v in (getattr(obj, n, None) for n in names)
                 if v is not None), None)


def _await_chain(coro) -> str:
    """``file:line fn`` of every frame down the await chain of a suspended
    coroutine (Task.get_stack() shows only the outermost)."""
    places = []
    while coro is not None:
        frame = _first_attr(coro, "cr_frame", "gi_frame", "ag_frame")
        if frame is None:  # a Future: the end of the chain
            break
        places.append(f"{os.path.basename(frame.f_code.co_filename)}:"
                      f"{frame.f_lineno} {frame.f_code.co_name}")
        coro = _first_attr(coro, "cr_await", "gi_yieldfrom", "ag_await")
    return " -> ".join(places)


def _waiting_tasks() -> str:
    """Where each task of the loop running in this thread is suspended; the
    main thread's own traceback only shows the loop idling in ``select``."""
    try:
        tasks = asyncio.all_tasks()
    except RuntimeError:  # no loop running: the traceback has the place
        return ""
    return "".join(f"\n  {t.get_name()}: {_await_chain(t.get_coro())}" for t in tasks)


@contextlib.contextmanager
def time_limit(seconds: float, name: str):
    """Raise TestLimitExceeded in the main thread once ``seconds`` have
    passed, and every ``seconds`` again while the block has not ended (the
    clean-up that the first raise sets off may hang too). An interval timer
    and SIGALRM: no dependency, and it reaches a loop idling in ``select``.
    It raises and does not kill, so ``finally`` blocks run and spawned
    processes are reaped."""
    def on_alarm(signum, frame):
        raise TestLimitExceeded(
            f"{name} exceeded its limit of {seconds:g} s{_waiting_tasks()}")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


def _limited(phase: str):
    @pytest.hookimpl(wrapper=True)
    def hook(item):
        with time_limit(TEST_LIMIT_S, f"{item.nodeid} ({phase})"):
            return (yield)
    return hook


pytest_runtest_setup = _limited("setup")
pytest_runtest_call = _limited("call")
pytest_runtest_teardown = _limited("teardown")


# ------------------------------------------------------------- chaos fixture
# Seeded fault injection (dynamo_tpu/runtime/chaos.py). Usage:
#
#     async def test_x(chaos):
#         inj = chaos("stream.send:drop=0.1;engine.step:error=0.05", seed=7)
#         ... drive the stack; assert inj.counts afterwards ...
#
# The injector is GLOBAL (the hooks live in hot paths); the fixture
# guarantees it is removed again so no other test inherits the faults.

@pytest.fixture
def chaos():
    from dynamo_tpu.runtime.chaos import configure_chaos

    def _install(spec: str, seed: int = 0):
        return configure_chaos(spec, seed=seed)

    try:
        yield _install
    finally:
        configure_chaos(None)
