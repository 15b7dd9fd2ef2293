"""Test harness config: force JAX onto a virtual 8-device CPU mesh.

The tests need no accelerator: sharding logic is validated on eight virtual
CPU devices, Pallas kernels run in interpret mode, and what only the chip's
compiler can say is asked of a DESCRIBED chip (tests/test_chip_compile.py).
The environment is set here, before any test module imports jax; the chip
itself is exercised by ``chip_smoke.py``.
"""

import os

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
# Default `pytest tests/` = fast tier (< ~8 min): the slow tier
# (tests/slow_tier.txt — heavy sharding/parity variants with faster siblings)
# is deselected. DYN_TEST_FULL=1 runs everything (the pre-snapshot gate).
# Explicitly-named tests always run: `pytest tests/test_mla.py::x` works
# regardless of tier.

def _slow_tier() -> set:
    path = os.path.join(os.path.dirname(__file__), "slow_tier.txt")
    try:
        with open(path) as f:
            return {ln.strip() for ln in f
                    if ln.strip() and not ln.startswith("#")}
    except OSError:
        return set()


def pytest_collection_modifyitems(config, items):
    if os.environ.get("DYN_TEST_FULL"):
        return
    if any("::" in a for a in config.args):
        return  # explicit node selection overrides tiering
    slow = _slow_tier()
    # node ids are root-relative when run from the repo root; normalize so
    # `cd tests && pytest` keeps the same tier
    def in_slow(item):
        nid = item.nodeid
        return nid in slow or f"tests/{nid}" in slow

    dropped = [it for it in items if in_slow(it)]
    if dropped:
        config.hook.pytest_deselected(items=dropped)
        items[:] = [it for it in items if not in_slow(it)]


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
