"""Cluster tooling tests: state API, metrics, jobs, workflow, runtime envs,
autoscaler.

Mirrors ray: python/ray/tests/test_state_api*.py, test_metrics_agent.py,
dashboard/modules/job/tests, workflow tests, test_runtime_env*.py, and the
FakeMultiNodeProvider-based autoscaler tests (SURVEY §4).
"""
import json
import os
import time

import pytest

import ray_tpu


def test_state_api(ray_shared):
    from ray_tpu.utils import state

    @ray_tpu.remote
    class Probe:
        def ping(self):
            return 1

    @ray_tpu.remote
    def a_task():
        return 1

    p = Probe.remote()
    ray_tpu.get(p.ping.remote())
    ray_tpu.get(a_task.remote())
    nodes = state.list_nodes()
    assert nodes and nodes[0]["state"] == "ALIVE"
    actors = state.list_actors(filters=[("state", "=", "ALIVE")])
    assert any(a["class_name"] == "Probe" for a in actors)
    # task events flush on a period (ray: TaskEventBuffer push interval)
    deadline = time.monotonic() + 10
    tasks = []
    while time.monotonic() < deadline and not tasks:
        tasks = state.list_tasks()
        time.sleep(0.3)
    assert tasks
    summary = state.summarize_tasks()
    assert summary["cluster"]["total_tasks"] >= 1
    ray_tpu.kill(p)


def test_metrics(ray_shared):
    from ray_tpu.utils import metrics as m
    from ray_tpu.utils import state

    c = m.Counter("test_requests", "reqs", tag_keys=("route",))
    c.inc(2, tags={"route": "/a"})
    c.inc(1, tags={"route": "/b"})
    g = m.Gauge("test_inflight")
    g.set(7)
    h = m.Histogram("test_latency", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(5.0)
    snap = c.snapshot()
    assert {v["value"] for v in snap["values"]} == {2.0, 1.0}
    # flushed to the controller and visible via the state API
    deadline = time.monotonic() + 3 * m.FLUSH_PERIOD_S
    found = False
    while time.monotonic() < deadline and not found:
        for worker_snap in state.list_metrics():
            names = {s["name"] for s in worker_snap["metrics"]}
            if {"test_requests", "test_inflight"} <= names:
                found = True
        time.sleep(0.3)
    assert found, "metrics never reached the controller KV"


def test_job_submission(ray_shared):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    jid = client.submit_job(
        entrypoint="python -c \"print('job says hi')\"",
        metadata={"owner": "test"})
    status = client.wait_until_finished(jid, timeout_s=60)
    assert status == "SUCCEEDED"
    assert "job says hi" in client.get_job_logs(jid)
    jobs = client.list_jobs()
    assert any(j["job_id"] == jid for j in jobs)


def test_job_failure_status(ray_shared):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    jid = client.submit_job(entrypoint="python -c 'import sys; sys.exit(3)'")
    assert client.wait_until_finished(jid, timeout_s=60) == "FAILED"
    assert client.get_job_info(jid)["return_code"] == 3


def test_workflow_run_and_resume(ray_shared, tmp_path):
    from ray_tpu import workflow

    calls = {"n": 0}

    @ray_tpu.remote
    def flaky(x):
        return x + 1

    @ray_tpu.remote
    def double(x):
        return x * 2

    from ray_tpu.dag import InputNode

    with InputNode() as inp:
        dag = double.bind(flaky.bind(inp))

    storage = str(tmp_path / "wf")
    out = workflow.run(dag, 5, workflow_id="wf1", storage=storage)
    assert out == 12
    assert workflow.get_status("wf1", storage=storage) == "SUCCEEDED"
    assert workflow.get_output("wf1", storage=storage) == 12
    # resume of a finished workflow replays from checkpoints
    assert workflow.resume("wf1", storage=storage) == 12
    assert ("wf1", "SUCCEEDED") in workflow.list_all(storage=storage)
    workflow.delete("wf1", storage=storage)
    assert workflow.get_status("wf1", storage=storage) == "NOT_FOUND"


def test_workflow_step_checkpoint_skips_done(ray_shared, tmp_path):
    from ray_tpu import workflow
    from ray_tpu.dag import InputNode

    marker = tmp_path / "ran_count"
    marker.write_text("0")

    @ray_tpu.remote
    def counted(x, marker_path):
        n = int(open(marker_path).read()) + 1
        open(marker_path, "w").write(str(n))
        return x + n

    with InputNode() as inp:
        dag = counted.bind(inp, str(marker))

    storage = str(tmp_path / "wf")
    out1 = workflow.run(dag, 10, workflow_id="wf2", storage=storage)
    out2 = workflow.resume("wf2", storage=storage)
    assert out1 == out2 == 11
    assert marker.read_text() == "1"   # step executed exactly once


def test_runtime_env_env_vars(ray_shared):
    @ray_tpu.remote
    def read_env():
        return os.environ.get("RAY_TPU_TEST_FLAG", "missing")

    ref = read_env.options(
        runtime_env={"env_vars": {"RAY_TPU_TEST_FLAG": "on"}}).remote()
    assert ray_tpu.get(ref) == "on"
    # and without the env, the variable must not leak from the pooled worker
    assert ray_tpu.get(read_env.remote()) == "missing"


def test_runtime_env_working_dir(ray_shared, tmp_path):
    pkg = tmp_path / "mypkg"
    pkg.mkdir()
    (pkg / "mymod_rt_env.py").write_text("VALUE = 'from-working-dir'\n")

    @ray_tpu.remote
    def use_module():
        import mymod_rt_env

        return mymod_rt_env.VALUE

    ref = use_module.options(
        runtime_env={"working_dir": str(pkg)}).remote()
    assert ray_tpu.get(ref) == "from-working-dir"


def _make_wheel(wheel_dir, name: str, version: str, source: str) -> None:
    """Hand-roll a minimal pure-python wheel (no build backend needed —
    a wheel is a zip with dist-info metadata)."""
    import zipfile

    tag = f"{name}-{version}"
    whl = wheel_dir / f"{tag}-py3-none-any.whl"
    with zipfile.ZipFile(whl, "w") as zf:
        zf.writestr(f"{name}/__init__.py", source)
        zf.writestr(f"{tag}.dist-info/METADATA",
                    f"Metadata-Version: 2.1\nName: {name}\n"
                    f"Version: {version}\n")
        zf.writestr(f"{tag}.dist-info/WHEEL",
                    "Wheel-Version: 1.0\nGenerator: test\n"
                    "Root-Is-Purelib: true\nTag: py3-none-any\n")
        zf.writestr(f"{tag}.dist-info/RECORD", "")


def test_runtime_env_pip_offline(ray_shared, tmp_path):
    """pip runtime env from a local wheel dir (ray: runtime_env/pip.py
    minus the network): the env's task imports the package; a plain task
    on the same pooled worker must NOT see it."""
    wheel_dir = tmp_path / "wheels"
    wheel_dir.mkdir()
    _make_wheel(wheel_dir, "envtestpkg", "1.0", "VALUE = 42\n")

    @ray_tpu.remote
    def with_pkg():
        import envtestpkg

        return envtestpkg.VALUE

    @ray_tpu.remote
    def without_pkg():
        try:
            import envtestpkg  # noqa: F401

            return "leaked"
        except ImportError:
            return "isolated"

    env = {"pip": {"packages": ["envtestpkg"],
                   "wheel_dir": str(wheel_dir)}}
    assert ray_tpu.get(with_pkg.options(runtime_env=env).remote()) == 42
    assert ray_tpu.get(without_pkg.remote()) == "isolated"
    # Version pinning resolves from the same local dir.
    _make_wheel(wheel_dir, "envtestpkg", "2.0", "VALUE = 43\n")
    env2 = {"pip": {"packages": ["envtestpkg==2.0"],
                    "wheel_dir": str(wheel_dir)}}
    assert ray_tpu.get(with_pkg.options(runtime_env=env2).remote()) == 43


def test_runtime_env_venv_isolated_interpreter(ray_shared, tmp_path):
    """venv runtime env = a DEDICATED worker on an isolated interpreter
    (the conda analog; ray: runtime_env/conda.py + the env-keyed
    WorkerPool).  The env's tasks run under the venv prefix with its
    offline-installed package; plain workers never see either."""
    import sys

    wheel_dir = tmp_path / "wheels"
    wheel_dir.mkdir()
    _make_wheel(wheel_dir, "venvonlypkg", "1.0", "VALUE = 7\n")

    @ray_tpu.remote
    def probe():
        import venvonlypkg

        return sys.prefix, venvonlypkg.VALUE

    @ray_tpu.remote
    def plain():
        try:
            import venvonlypkg  # noqa: F401

            return "leaked"
        except ImportError:
            return sys.prefix

    env = {"venv": {"packages": ["venvonlypkg"],
                    "wheel_dir": str(wheel_dir)}}
    prefix, val = ray_tpu.get(
        probe.options(runtime_env=env).remote(), timeout=180)
    assert val == 7
    assert "/venv/" in prefix and prefix != sys.prefix
    assert ray_tpu.get(plain.remote(), timeout=60) != prefix

    # Same env hash reuses the same dedicated worker (keyed pool);
    # actors route through the venv path too.
    @ray_tpu.remote
    class EnvActor:
        def where(self):
            return sys.prefix

    a = EnvActor.options(runtime_env=env).remote()
    assert ray_tpu.get(a.where.remote(), timeout=180) == prefix
    ray_tpu.kill(a)


def test_lease_park_is_bounded_and_node_recovers():
    """A lease request that can't be satisfied parks agent-side for at
    most `lease_park_s`, then gets an explicit {"retry": True} reply.
    Before the fix the agent parked forever: the client timed out, and
    when capacity freed the agent granted a lease into a future nobody
    read — a worker leased-to-nobody that the dead-submitter probe never
    reaps (the submitter is alive), wedging the node one worker at a
    time (suite post-mortem: every later lease request timed out while
    all worker processes sat idle)."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(resources={"CPU": 1},
                 _system_config={"lease_park_s": 0.3,
                                 "max_workers_per_node": 1,
                                 "prestart_workers": 1})
    try:
        from ray_tpu._private.worker import global_worker

        @ray_tpu.remote(num_cpus=1)
        def hold(sec):
            time.sleep(sec)
            return 1

        @ray_tpu.remote(num_cpus=1)
        def quick():
            return 2

        core = global_worker()
        r = hold.remote(6.0)
        # Probe with raw lease requests until one finds the CPU taken:
        # that one must come back {"retry": True} (bounded park), never
        # hang to the RPC timeout.
        deadline = time.monotonic() + 30
        while True:
            reply, _ = core.call(
                core.agent_addr, "request_lease",
                {"resources": {"CPU": 1.0}, "submitter": core.address},
                timeout=10.0)
            if reply.get("retry"):
                break
            if reply.get("granted"):
                # Raced ahead of hold's own lease: give it back.
                core.call(core.agent_addr, "return_lease",
                          {"lease_id": reply["lease_id"]}, timeout=5.0)
            assert time.monotonic() < deadline, f"no retry reply: {reply}"
            time.sleep(0.2)
        # The node is NOT wedged: the held task finishes and fresh work
        # still schedules onto the single worker (a leaked zombie lease
        # would hold both the CPU and the only worker slot forever).
        assert ray_tpu.get(r, timeout=60) == 1
        assert ray_tpu.get(quick.remote(), timeout=60) == 2
    finally:
        ray_tpu.shutdown()
        ray_tpu.init(resources={"CPU": 4})


def test_venv_lease_evicts_idle_worker_at_cap(tmp_path):
    """Keyed pools must not deadlock at the worker cap: with the pool
    full of idle PLAIN workers, a venv lease evicts one and completes
    (before the fix it pended forever — nothing returns a lease when
    everyone is idle)."""
    import sys

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    wheel_dir = tmp_path / "wheels"
    wheel_dir.mkdir()
    _make_wheel(wheel_dir, "capevictpkg", "1.0", "VALUE = 1\n")
    ray_tpu.init(resources={"CPU": 4},
                 _system_config={"max_workers_per_node": 1})
    try:
        @ray_tpu.remote
        def plain():
            return sys.prefix

        @ray_tpu.remote
        def in_venv():
            import capevictpkg

            return sys.prefix, capevictpkg.VALUE

        plain_prefix = ray_tpu.get(plain.remote(), timeout=60)
        env = {"venv": {"packages": ["capevictpkg"],
                        "wheel_dir": str(wheel_dir)}}
        prefix, val = ray_tpu.get(
            in_venv.options(runtime_env=env).remote(), timeout=180)
        assert val == 1 and prefix != plain_prefix
        # ...and back: a plain task evicts the idle venv worker.
        assert ray_tpu.get(plain.remote(), timeout=60) == plain_prefix
    finally:
        ray_tpu.shutdown()      # `ray_shared` starts the next test's


def test_venv_rejected_for_tpu_tasks(ray_shared):
    @ray_tpu.remote
    def f():
        return 1

    with pytest.raises(ValueError, match="unsupported for TPU"):
        f.options(num_tpus=1, runtime_env={"venv": True}).remote()


def test_cli_status_and_list(ray_shared):
    """Smoke the CLI code paths in-process (full subprocess CLI covered by
    job submission)."""
    from ray_tpu._private.worker import global_worker
    from ray_tpu.scripts import cli

    class A:
        address = global_worker().controller_addr

    # _require_address picks up explicit address
    assert cli._require_address(A) == A.address


def test_cli_status_and_memory(ray_shared):
    """`ray-tpu status` and `ray-tpu memory` against a live cluster
    (ray: `ray status` / `ray memory` CLI)."""
    import subprocess
    import sys

    from ray_tpu._private.worker import global_worker

    addr = global_worker().controller_addr
    for cmd, expect in (("status", "node(s)"), ("memory", "cluster:")):
        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu.scripts.cli", cmd,
             "--address", addr],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-1000:]
        assert expect in out.stdout, out.stdout


def test_workflow_retries_timeout_events(ray_shared, tmp_path):
    """Workflow hardening (ray: workflow_executor.py): per-step retries
    with a durable event stream, step timeouts, and bounded concurrency."""
    from ray_tpu import workflow
    from ray_tpu.dag.dag_node import InputNode

    storage = str(tmp_path / "wf")
    flaky_marker = tmp_path / "flaky"
    flaky_marker.write_text("0")

    @ray_tpu.remote
    def flaky(x, marker):
        n = int(open(marker).read()) + 1
        open(marker, "w").write(str(n))
        if n < 3:
            raise RuntimeError(f"attempt {n} fails")
        return x + 100

    with InputNode() as inp:
        dag = flaky.bind(inp, str(flaky_marker))

    events = []
    out = workflow.run(dag, 1, workflow_id="wf-retry", storage=storage,
                       step_max_retries=3, on_event=events.append)
    assert out == 101
    kinds = [e["event"] for e in events]
    assert kinds.count("failed") == 2 and kinds.count("retry") == 2
    assert kinds[-1] == "completed"
    # The durable stream matches what the listener saw.
    stored = workflow.list_events("wf-retry", storage=storage)
    assert [e["event"] for e in stored] == kinds

    # Step timeout surfaces as TimeoutError after exhausting retries.
    @ray_tpu.remote
    def sleepy():
        import time as _t

        _t.sleep(30)
        return "late"

    with InputNode() as inp2:
        dag2 = sleepy.bind()

    with pytest.raises((TimeoutError, Exception)):
        workflow.run(dag2, workflow_id="wf-timeout", storage=storage,
                     step_timeout_s=1.0)


def test_workflow_concurrency_limit(ray_shared, tmp_path):
    """max_concurrent_steps bounds in-flight steps: with limit 1, step
    wall-clocks never overlap."""
    import json as _json

    from ray_tpu import workflow
    from ray_tpu.dag.dag_node import InputNode, MultiOutputNode

    storage = str(tmp_path / "wf")
    log = tmp_path / "spans.jsonl"

    @ray_tpu.remote
    def span(i, path):
        import time as _t

        t0 = _t.time()
        _t.sleep(0.3)
        with open(path, "a") as f:
            f.write(_json.dumps([t0, _t.time()]) + "\n")
        return i

    with InputNode() as inp:
        dag = MultiOutputNode([span.bind(i, str(log)) for i in range(3)])

    out = workflow.run(dag, None, workflow_id="wf-conc", storage=storage,
                       max_concurrent_steps=1)
    assert sorted(out) == [0, 1, 2]
    spans = sorted(_json.loads(x) for x in log.read_text().splitlines())
    for (s0, e0), (s1, _e1) in zip(spans, spans[1:]):
        assert s1 >= e0 - 0.05, f"steps overlapped: {spans}"


def test_runtime_env_custom_plugin(ray_shared):
    """The plugin seam (ray: runtime_env/plugin.py RuntimeEnvPlugin):
    a user-defined kind ships BY VALUE in the descriptor — prepare on
    the driver, fetch+activate/deactivate around execution on a pooled
    worker, no worker-side registration."""
    from ray_tpu.runtime_env import RuntimeEnvPlugin

    class StampPlugin(RuntimeEnvPlugin):
        name = "stamp"
        priority = 3

        def __init__(self, tag):
            self.tag = tag

        def prepare(self, value, core):
            return {"tag": self.tag, "prepared": True}

        def fetch(self, wire, core):
            # Worker-side build step: write a marker file once.
            import tempfile
            self._path = tempfile.gettempdir() + f"/rt_stamp_{wire['tag']}"
            with open(self._path, "w") as f:
                f.write("built")

        def activate(self, wire, core, ctx):
            import os
            ctx["old"] = os.environ.get("RAY_TPU_STAMP")
            os.environ["RAY_TPU_STAMP"] = wire["tag"]

        def deactivate(self, wire, core, ctx):
            import os
            if ctx.get("old") is None:
                os.environ.pop("RAY_TPU_STAMP", None)
            else:
                os.environ["RAY_TPU_STAMP"] = ctx["old"]

    @ray_tpu.remote
    def read_stamp():
        import os
        return os.environ.get("RAY_TPU_STAMP")

    out = ray_tpu.get(read_stamp.options(
        runtime_env={"plugins": [StampPlugin("alpha")]}).remote(),
        timeout=120)
    assert out == "alpha"
    # Deactivation: the next task in the pooled worker sees a clean env.
    assert ray_tpu.get(read_stamp.remote(), timeout=120) is None


def test_workflow_api_extras(ray_shared, tmp_path):
    """Round-4 workflow parity: continuation, sleep, wait_for_event,
    metadata, resume_all, cancellation error (ray: workflow/__init__)."""
    import time as _time

    from ray_tpu import workflow

    storage = str(tmp_path / "wfx")

    # Dynamic continuation: a step returns continuation(sub-dag).
    @ray_tpu.remote
    def fib(n):
        if n <= 1:
            return n
        return workflow.continuation(fib_sum.bind(n))

    @ray_tpu.remote
    def add(a, b):
        return a + b

    @ray_tpu.remote
    def fib_sum(n):
        return workflow.continuation(add.bind(fib.bind(n - 1),
                                              fib.bind(n - 2)))

    out = workflow.run(fib.bind(6), workflow_id="wfib",
                       storage=storage)
    assert out == 8
    # Replay: the entire continuation tree comes from checkpoints.
    assert workflow.resume("wfib", storage=storage) == 8

    # sleep is a durable step: replay is instant.
    t0 = _time.monotonic()
    workflow.run(workflow.sleep(1.0), workflow_id="wsleep",
                 storage=storage)
    took_first = _time.monotonic() - t0
    assert took_first >= 1.0
    t0 = _time.monotonic()
    assert workflow.resume("wsleep", storage=storage) == 1.0
    assert _time.monotonic() - t0 < max(1.0, took_first / 2)

    # wait_for_event completes when the listener's poll returns.
    marker = tmp_path / "event-armed"

    class FileEvent(workflow.EventListener):
        def poll_for_event(self, path):
            import os as _os
            import time as _t

            while not _os.path.exists(path):
                _t.sleep(0.05)
            return "armed"

    import threading

    threading.Timer(0.5, lambda: marker.write_text("x")).start()
    out = workflow.run(
        workflow.wait_for_event(FileEvent, str(marker)),
        workflow_id="wevent", storage=storage)
    assert out == "armed"

    # metadata + resume_all + cancellation error.
    meta = workflow.get_metadata("wsleep", storage=storage)
    assert meta["status"] == "SUCCEEDED"
    assert meta["steps"]
    assert workflow.resume_all(storage=storage) == []
    workflow.cancel("wevent", storage=storage)
    assert workflow.get_status("wevent", storage=storage) == "CANCELED"
    # A cancelled workflow's completed output is still readable; a
    # cancelled one WITHOUT output raises the typed error.
    workflow.run(workflow.sleep(0.0), workflow_id="wc2", storage=storage)
    workflow.cancel("wc2", storage=storage)
    import os as _os
    import shutil as _shutil

    _shutil.rmtree(_os.path.join(storage, "wc2", "steps"))
    with pytest.raises(workflow.WorkflowCancellationError):
        workflow.get_output("wc2", storage=storage)
