"""Test fixtures.

Mirrors the reference's strategy (SURVEY §4): a shared single-node runtime
for most tests (ray: ray_start_shared fixtures), explicit multi-agent
Cluster for scheduling/fault tests, and jax pinned to an 8-device virtual
CPU platform so multi-chip sharding logic runs on one machine
(the fake-ICI analog of ray's FakeMultiNodeProvider / MockNcclGroup).
"""
import os

# 8 virtual CPU devices stand in for an 8-chip slice in all sharding tests.
# JAX_PLATFORMS=cpu comes from the environment (set here when absent);
# the device count goes through jax.config (`jax_num_cpu_devices`)
# before any backend initializes.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402,F401 - imported before any backend init

from ray_tpu._private.config import ensure_cpu_devices  # noqa: E402

ensure_cpu_devices(8)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: process-killing fault-injection suites (test_chaos*, "
        "test_failpoints) — each test runs its own cluster and kills "
        "pieces of it; deselect with -m 'not chaos' for a quiet pass")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Per-test watchdog (pytest-timeout isn't in this image): SIGALRM
    interrupts a wedged main-thread wait, failing THAT test with a live
    stack instead of hanging the whole suite — distributed-runtime bugs
    here historically manifest as infinite gets."""
    import signal

    budget = int(os.environ.get("RAY_TPU_TEST_TIMEOUT_S", "900"))

    def _fire(signum, frame):
        # All-thread dump first: the main-thread frame usually shows only
        # a queue/future wait — the THE interesting stack (executor
        # threads, IO loop) is elsewhere.
        import faulthandler
        import sys

        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        raise TimeoutError(
            f"watchdog: {item.nodeid} exceeded {budget}s "
            f"(frame: {frame.f_code.co_filename}:{frame.f_lineno})")

    old = signal.signal(signal.SIGALRM, _fire)
    signal.alarm(budget)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def ray_shared():
    """Shared local cluster (4 CPUs): initialized on first use, re-created
    if another fixture (e.g. the multi-node cluster) tore it down."""
    import ray_tpu

    if not ray_tpu.is_initialized():
        ray_tpu.init(resources={"CPU": 4})
    yield ray_tpu


@pytest.fixture(scope="session", autouse=True)
def _shutdown_at_end():
    yield
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
