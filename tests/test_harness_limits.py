"""The tier-1 harness itself (tests/conftest.py): one limit per test on
setup, call and teardown, a backstop that needs nothing of the main
thread, a clean worker after either fires, and a progress line that
holds only dots.  Each case is a `python -m pytest` child on a few-line
test file under tmp_path that loads tests/conftest.py as a plugin; the
short limits come from the file's own `time_limit` markers."""
import os
import re
import subprocess
import sys

import pytest

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS_DIR)

_WEDGES = {
    "call": """
import time
import pytest

@pytest.mark.time_limit(2)
def test_wedge():
    time.sleep(120)

def test_after():
    pass
""",
    "setup": """
import time
import pytest

@pytest.fixture
def slow_setup():
    time.sleep(120)
    yield

@pytest.mark.time_limit(2)
def test_wedge(slow_setup):
    pass

def test_after():
    pass
""",
    "teardown": """
import time
import pytest

@pytest.fixture
def slow_teardown():
    yield
    time.sleep(120)

@pytest.mark.time_limit(2)
def test_wedge(slow_teardown):
    pass

def test_after():
    pass
""",
}


def _run_pytest(tmp_path, body, *extra, timeout=150):
    """(rc, stdout, stderr, seconds) of one pytest child over `body`;
    the seconds are the child's own count of its run (its last line),
    which leaves out what a loaded box takes to import jax."""
    path = tmp_path / "test_case.py"
    path.write_text(body)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [TESTS_DIR, REPO, os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "conftest", "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly",
         "--rootdir", str(tmp_path), *extra, str(path)],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=timeout)
    took = re.search(r" in ([0-9.]+)s", out.stdout.strip().rsplit(
        "\n", 1)[-1])
    return (out.returncode, out.stdout, out.stderr,
            float(took.group(1)) if took else float("inf"))


@pytest.mark.parametrize("phase", ["call", "setup", "teardown"])
def test_wedged_phase_fails_by_name_with_stacks(tmp_path, phase):
    """A test that sleeps past its limit in any phase fails by name
    within limit + 15 s, with every thread's stack in its report, and
    the test after it still runs."""
    rc, out, err, took = _run_pytest(tmp_path, _WEDGES[phase])
    text = out + err
    assert rc == 1, text[-3000:]
    assert took < 2 + 15, (took, text[-3000:])
    assert re.search(r"(FAILED|ERROR) test_case\.py::test_wedge", text), \
        text[-3000:]
    assert f"exceeded 2s in {phase}" in text, text[-3000:]
    assert "most recent call first" in text, text[-3000:]
    # test_after ran; a teardown wedge's own call had passed before it.
    assert {"call": "1 failed, 1 passed", "setup": "1 passed, 1 error",
            "teardown": "2 passed, 1 error"}[phase] in text, text[-3000:]


def test_native_call_is_ended_by_the_backstop(tmp_path):
    """A main thread inside a native call that no signal interrupts (a
    second lock of a plain pthread mutex) never runs the alarm's Python
    handler.  faulthandler's timer dumps the stacks to the real stderr
    and ends the xdist worker; xdist fails that test by name and a new
    worker runs the rest."""
    body = """
import ctypes
import pytest

@pytest.mark.time_limit(2)
def test_wedge():
    libc = ctypes.CDLL(None)
    mutex = ctypes.create_string_buffer(128)
    libc.pthread_mutex_init(mutex, None)
    libc.pthread_mutex_lock(mutex)
    libc.pthread_mutex_lock(mutex)

def test_after():
    pass
"""
    rc, out, err, took = _run_pytest(tmp_path, body, "-p", "xdist",
                                     "-n", "1")
    text = out + err
    assert rc == 1, text[-3000:]
    assert took < 2 + 30 + 40, (took, text[-3000:])
    assert "crashed while running 'test_case.py::test_wedge'" in text, \
        text[-3000:]
    assert re.search(r"Timeout \(0:00:3\d\)!", err), err[-3000:]
    assert "most recent call first" in err, err[-3000:]
    assert "1 failed, 1 passed" in text, text[-3000:]


def test_timed_out_test_leaves_no_runtime_behind(tmp_path):
    """The test after a timed-out one that left its cluster up starts
    with ray_tpu uninitialised and no runtime process below it."""
    body = """
import time
import psutil
import pytest
import ray_tpu

def _runtime_below():
    return [p.pid for p in psutil.Process().children(recursive=True)
            if "ray_tpu._private" in " ".join(p.cmdline())]

@pytest.mark.time_limit(25)
def test_wedge():
    ray_tpu.init(resources={"CPU": 1})
    assert _runtime_below()
    time.sleep(120)

def test_after():
    assert not ray_tpu.is_initialized()
    assert _runtime_below() == []
"""
    rc, out, err, took = _run_pytest(tmp_path, body)
    text = out + err
    assert rc == 1, text[-3000:]
    assert took < 25 + 30, (took, text[-3000:])
    assert "FAILED test_case.py::test_wedge" in text, text[-3000:]
    assert "exceeded 25s in call" in text, text[-3000:]
    assert "1 failed, 1 passed" in text, text[-3000:]


def test_progress_line_of_a_cluster_run_is_dots_only(tmp_path):
    """Worker log lines forwarded to the driver reach `logging` (where
    pytest's capture keeps them with their test), never the stderr that
    between tests is the progress line."""
    body = """
import logging
import time
import ray_tpu

def test_before():
    pass

def test_boots_a_cluster(caplog, capfd):
    caplog.set_level(logging.INFO, logger="ray_tpu.worker_logs")
    ray_tpu.init(resources={"CPU": 1})
    try:
        @ray_tpu.remote
        def noisy():
            print("MARKER_LINE_FROM_WORKER")
            return 1

        assert ray_tpu.get(noisy.remote(), timeout=60) == 1
        deadline = time.monotonic() + 20
        while (time.monotonic() < deadline
               and "MARKER_LINE_FROM_WORKER" not in caplog.text):
            time.sleep(0.1)
        assert "MARKER_LINE_FROM_WORKER" in caplog.text
        assert "MARKER_LINE_FROM_WORKER" not in capfd.readouterr().err
    finally:
        ray_tpu.shutdown()

def test_after():
    pass
"""
    rc, out, err, _ = _run_pytest(tmp_path, body)
    assert rc == 0, (out + err)[-3000:]
    lines = [ln for ln in (out + err).splitlines() if ln.strip()]
    assert re.fullmatch(r"\.\.\. +\[100%\]", lines[0]), lines
    assert re.fullmatch(r"3 passed.*", lines[1]), lines
    assert len(lines) == 2, lines
