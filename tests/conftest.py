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
import jax  # noqa: E402 - imported before any backend init

# The tests' own CPU compiles skip the back end's optimisation passes:
# what tier-1 spends is compile time, not run time (ROADMAP D18).  In this
# process alone and not through the environment: a benchmark rehearsal is
# a child that RUNS its programs for seconds, and unoptimised it passes
# its 400 s limit (CHANGES.md PR 56).  `tests/test_chip_compile.py`,
# which reads the TPU compiler's own memory analysis, switches it back.
jax.config.update("jax_disable_most_optimizations", True)

from ray_tpu._private.config import ensure_cpu_devices  # noqa: E402

ensure_cpu_devices(8)

import pytest  # noqa: E402


# One limit for every phase (setup, call, teardown) of every test.  Of
# the 1,826 cases of a loaded six-worker run all but two take less
# (CHANGES.md PR 56 has the table; the two, rehearsals of 248 and 210 s,
# carry their own limit of 420 s), and six wedged workers still cost a
# 1,470 s run an eighth of its clock.  A test that needs longer says so on itself:
# @pytest.mark.time_limit(seconds), with the reason beside it.
TEST_LIMIT_S = 180
# The backstop ends the process this long after the alarm should have
# fired: room for the scrub below (ray_tpu.shutdown() is bounded at 16 s).
_BACKSTOP_GRACE_S = 30
# A test that swallowed the watchdog's error is hit again this often.
_REFIRE_S = 10

_real_stderr_fd = None

# The cases that take a worker 45 s or more, with the seconds each took in
# the parent's whole run of PR 56 (six workers; CHANGES.md has the table).  They are started in the
# run's first minutes and not wherever the alphabet puts them: a worker
# that starts a three-minute case in the run's last minute IS the run's
# tail.  What the whole run may take is a budget (ROADMAP D18).
LONG_CASES = {
    "tests/test_bench_families.py::test_the_mimo_cell_rehearses_on_the_cpu":
        248,
    "tests/test_bench_families.py::test_the_dots3_cell_rehearses_on_the_cpu":
        210,
    "tests/test_bench_families.py::test_the_cohere_cell_rehearses_on_the_cpu":
        146,
    "tests/test_bench_families.py::test_the_solar_cell_rehearses_on_the_cpu":
        118,
    "tests/test_chip_compile.py::test_served_glm_engine_fits_one_chip_and_"
    "copies_no_state_or_pool": 100,
    "tests/test_bench_families.py::"
    "test_the_granite_cell_rehearses_on_the_cpu": 93,
    "tests/test_chip_compile.py::test_served_dots3_engine_fits_one_chip_and_"
    "copies_no_ring_or_pool": 83,
    "tests/test_chip_compile.py::test_served_mimo_engine_fits_one_chip_and_"
    "copies_no_ring_or_pool": 82,
    "tests/test_bench_families.py::test_the_sala_cell_rehearses_on_the_cpu":
        75,
    "tests/test_chip_compile.py::test_served_granite_engine_fits_one_chip":
        72,
    "tests/test_chip_compile.py::test_served_command_a_plus_fits_one_chip_"
    "and_copies_no_ring_or_pool": 58,
    "tests/test_chip_compile.py::test_served_nemotron_engine_fits_one_chip_"
    "and_copies_no_lane_state": 57,
    "tests/test_chip_compile.py::test_served_sarvam_engine_fits_one_chip": 52,
    "tests/test_device_worker.py::"
    "test_chip_smoke_tiny_rehearsal_fails_cleanly": 51,
    "tests/test_harness_limits.py::"
    "test_native_call_is_ended_by_the_backstop": 48,
}


def pytest_collection_modifyitems(items):
    """`LONG_CASES` first, spread evenly over the collection's first
    quarter, a long one beside a short one: `--dist load` deals that
    quarter out at the start, in one run of consecutive cases a worker,
    so cases put side by side at the very head would all be ONE
    worker's."""
    long = sorted((it for it in items if it.nodeid in LONG_CASES),
                  key=lambda it: -LONG_CASES[it.nodeid])
    if not long:
        return
    # the longest, the shortest, the second longest, ...
    long = [long.pop(0 if i % 2 == 0 else -1) for i in range(len(long))]
    rest = [it for it in items if it.nodeid not in LONG_CASES]
    stride = max(1, len(items) // 4 // len(long))
    for i, it in enumerate(long):
        rest.insert(i * stride, it)
    items[:] = rest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: process-killing fault-injection suites (test_chaos*, "
        "test_failpoints) — each test runs its own cluster and kills "
        "pieces of it; deselect with -m 'not chaos' for a quiet pass")
    config.addinivalue_line(
        "markers",
        f"time_limit(seconds): this test's own watchdog limit for each "
        f"phase, in place of the suite's {TEST_LIMIT_S} s")
    _route_worker_logs_through_logging()


def _route_worker_logs_through_logging():
    """Worker log lines forwarded to this driver go through `logging`,
    where pytest's capture holds them with the test that caused them,
    and not to a stderr that between tests is pytest's progress line
    (the driver's fallback count reads whole lines of dots)."""
    import logging

    from ray_tpu._private.worker import CoreWorker

    log = logging.getLogger("ray_tpu.worker_logs")

    async def _on_log_lines(self, _topic, payload):
        node = payload.get("node_id", "?")
        for src, line in payload.get("lines", []):
            log.info("(%s, node=%s) %s", src, node, line)

    CoreWorker._on_log_lines = _on_log_lines


def _stderr_outside_capture(config) -> int:
    """A descriptor of the stderr this process was started with: the
    backstop's dump must outlive the process, and pytest's capture file
    does not."""
    global _real_stderr_fd
    if _real_stderr_fd is None:
        capman = config.pluginmanager.getplugin("capturemanager")
        if capman is None:
            _real_stderr_fd = os.dup(2)
        else:
            with capman.global_and_fixture_disabled():
                _real_stderr_fd = os.dup(2)
    return _real_stderr_fd


def _runtime_descendants():
    import psutil

    out = []
    for p in psutil.Process().children(recursive=True):
        try:
            if "ray_tpu._private" in " ".join(p.cmdline()):
                out.append(p)
        except psutil.Error:
            continue
    return out


def _scrub_runtime():
    """After a timeout the worker must be clean for the next test:
    shut the runtime down (bounded) and SIGKILL every runtime process
    this pytest process started, so `ray_shared` and every fresh
    cluster of the tests that follow start from nothing."""
    import psutil

    import ray_tpu

    procs = _runtime_descendants()
    if ray_tpu.is_initialized():
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001 - the kill below is the floor
            pass
    for p in procs:
        try:
            p.kill()
        except psutil.Error:
            pass
    psutil.wait_procs(procs, timeout=5.0)


def _limited(item, when):
    """Watchdog around one phase of one test (pytest-timeout isn't in
    this image).  SIGALRM interrupts a wedged main-thread wait and fails
    THAT test with every thread's stack; behind it faulthandler's own
    timer, which needs nothing of the main thread, dumps the stacks and
    ends the process when the main thread sits in a native call that
    never returns (xdist then fails the test by name and replaces the
    worker)."""
    import faulthandler
    import signal
    import sys

    marker = item.get_closest_marker("time_limit")
    limit = int(marker.args[0]) if marker else TEST_LIMIT_S
    fired = []

    def _fire(signum, frame):
        # All-thread dump first: the main-thread frame usually shows only
        # a queue/future wait — the interesting stack (executor threads,
        # IO loop) is elsewhere.
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        fired.append(f"{frame.f_code.co_filename}:{frame.f_lineno}")
        signal.alarm(_REFIRE_S)
        raise TimeoutError(
            f"watchdog: {item.nodeid} exceeded {limit}s in {when} "
            f"(frame: {fired[-1]})")

    old = signal.signal(signal.SIGALRM, _fire)
    faulthandler.dump_traceback_later(
        limit + _BACKSTOP_GRACE_S, exit=True,
        file=_stderr_outside_capture(item.config))
    signal.alarm(limit)
    try:
        result = yield
        if fired:
            raise TimeoutError(
                f"watchdog: {item.nodeid} exceeded {limit}s in {when} "
                f"and swallowed the error (frame: {fired[0]})")
        return result
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
        if fired:
            _scrub_runtime()
        faulthandler.cancel_dump_traceback_later()


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _limited(item, "setup"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _limited(item, "call"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    # Module- and session-scoped finalisers run in the teardown of the
    # last test that used them, so they are under this limit too.
    return (yield from _limited(item, "teardown"))


_rss_after_trim = [0]


def _rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


@pytest.fixture(autouse=True)
def _hand_freed_memory_back():
    """A compile at a cell's real widths leaves GiBs of FREED heap in
    glibc's arenas (two `test_chip_compile.py` cases: 2.29 GiB held, 0.65
    after `malloc_trim`), so a worker's footprint only grew: six workers
    held 75 GiB by a whole run's seventeenth minute and the sandbox's
    monitor ended them (PR 52).  Trims when the process has grown by a
    GiB since the last trim; reading the size costs microseconds."""
    yield
    if _rss_bytes() - _rss_after_trim[0] > 1 << 30:
        import ctypes
        import gc

        gc.collect()
        try:
            ctypes.CDLL("libc.so.6").malloc_trim(0)
        except (OSError, AttributeError):       # another libc: nothing to do
            pass
        _rss_after_trim[0] = _rss_bytes()


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
