"""The device-worker contract, on the CPU: a node TOLD it has a chip
routes every TPU lease (task or serve replica) into ONE process, plain
workers stay pinned to the CPU, and the device worker's jax-facing
environment is built without a hidden fallback.  (The same path on a
real chip is chip_smoke.py's job.)"""
import json
import os
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu._private import node_agent

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IN_TREE_CACHE = os.path.join(REPO, ".jax_cache")


@pytest.fixture(scope="module")
def tpu_node():
    """A one-node cluster told it has one chip (the tests' CPU route)."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(resources={"CPU": 4, "TPU": 1})
    yield ray_tpu
    ray_tpu.shutdown()


def _whoami():
    import jax

    return {"pid": os.getpid(),
            "device_worker": os.environ.get("RAY_TPU_IS_DEVICE_WORKER"),
            "jax_platforms": os.environ.get("JAX_PLATFORMS"),
            "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
            "platform": jax.devices()[0].platform}


def test_tpu_task_and_llm_replica_share_the_device_worker(tpu_node):
    from ray_tpu import serve

    probe = ray_tpu.remote(num_tpus=1)(_whoami)
    # Ask for the device BEFORE serve.run: the replica then holds the
    # node's only TPU unit until serve.delete.
    before = ray_tpu.get(probe.remote(), timeout=120)
    assert before["device_worker"] == "1"
    app = serve.deployment(serve.LLMServer).options(
        name="llm", ray_actor_options={"num_tpus": 1},
    ).bind("debug", max_batch=2, max_len=64, page_size=16)
    handle = serve.run(app, name="dw")
    try:
        out = handle.remote({"prompt": [1, 2, 3],
                             "max_new_tokens": 4}).result(timeout_s=120)
        assert len(out["tokens"]) == 4
        (rep,) = serve.replica_metrics("dw")["dw"]["llm"].values()
        assert rep["pid"] == before["pid"]
        serve.delete("dw")
        # The device worker outlives its replica: same process again.
        after = ray_tpu.get(probe.remote(), timeout=120)
        assert after["pid"] == before["pid"]
    finally:
        # Leave no handle to this cluster's serve controller behind for
        # the next test file in this process.
        serve.shutdown()


def test_plain_task_is_pinned_to_the_cpu(tpu_node):
    me = ray_tpu.get(ray_tpu.remote(_whoami).remote(), timeout=120)
    assert me["device_worker"] == "0"
    assert me["jax_platforms"] == "cpu" and me["platform"] == "cpu"


def test_device_worker_cache_dir_in_the_cluster(tpu_node):
    """Outside value untouched when set, the fixed in-tree path when
    not — whichever this test run's environment has."""
    me = ray_tpu.get(ray_tpu.remote(num_tpus=1)(_whoami).remote(),
                     timeout=120)
    assert me["cache_dir"] == os.environ.get("JAX_COMPILATION_CACHE_DIR",
                                             IN_TREE_CACHE)


@pytest.mark.parametrize("entries,want", [
    (["accel0", "accel1", "accel2", "accel3", "null", "shm"], 4),
    (["vfio/0", "vfio/vfio", "null"], 1),
    (["vfio/0", "vfio/1", "vfio/2", "vfio/3", "vfio/vfio"], 4),
    (["vfio/vfio", "null", "tty0"], 0),
    (["null", "tty0"], 0),
], ids=["accel", "vfio-one", "vfio-four", "vfio-no-chip", "none"])
def test_detect_chips_against_a_fake_dev(tmp_path, entries, want):
    for e in entries:
        p = tmp_path / e
        p.parent.mkdir(parents=True, exist_ok=True)
        p.touch()
    assert node_agent.detect_chips(str(tmp_path)) == want
    assert node_agent.detect_chips(str(tmp_path / "missing")) == 0


def test_detect_resources_told_beats_found(monkeypatch):
    monkeypatch.setattr(node_agent, "detect_chips", lambda: 4)
    monkeypatch.delenv("RAY_TPU_CHIPS", raising=False)
    assert node_agent.detect_resources()["TPU"] == 4.0
    monkeypatch.setenv("RAY_TPU_CHIPS", "1")
    assert node_agent.detect_resources()["TPU"] == 1.0
    monkeypatch.setenv("RAY_TPU_CHIPS", "0")
    assert "TPU" not in node_agent.detect_resources()


@pytest.mark.parametrize("outside", ["/somewhere/else", None],
                         ids=["outside-set", "outside-unset"])
@pytest.mark.parametrize("detected", [True, False],
                         ids=["chips-detected", "chips-told"])
def test_device_worker_env(outside, detected):
    env = {"JAX_PLATFORMS": "cpu"}
    if outside:
        env["JAX_COMPILATION_CACHE_DIR"] = outside
    node_agent.device_worker_env(env, detected)
    assert env["JAX_COMPILATION_CACHE_DIR"] == (outside or IN_TREE_CACHE)
    # Found chips: jax must come up on them or raise.  Told: the
    # caller's platform stands (the tests' CPU route).
    assert env["JAX_PLATFORMS"] == ("tpu,cpu" if detected else "cpu")


def test_chip_smoke_tiny_rehearsal_fails_cleanly():
    """The whole script on the CPU: walks every step, ends with a
    parseable last line, "ok": false, exit 1 — and no process of its
    cluster survives it."""
    env = {**os.environ, "RAY_TPU_CHIPS": "1", "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--size", "tiny"],
        env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    steps = [l.get("step") for l in lines]
    for step in ("init", "device_probe", "serve_run", "first_response",
                 "requests_done", "replica", "kernels", "teardown",
                 "shutdown"):
        assert step in steps, (step, proc.stdout[-2000:],
                               proc.stderr[-2000:])
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert proc.returncode == 1
    shut = lines[steps.index("shutdown")]
    assert shut["leftover_processes"] == [] and shut["leftover_shm"] == []
    rep = lines[steps.index("replica")]
    assert rep["pid"] == rep["device_worker_pid"] and rep["completed"] >= 8


def test_chip_smoke_without_a_chip_stops_at_step_two():
    """No chip found and none told: the default run fails at step 2
    with a message that says so (never a pending lease), exit 1."""
    env = {k: v for k, v in os.environ.items() if k != "RAY_TPU_CHIPS"}
    if node_agent.detect_chips():
        pytest.skip("this host has a chip")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**env, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    assert proc.returncode == 1
    assert lines[-1]["ok"] is False and lines[-1]["device"]["count"] == 0
    assert any("advertises no TPU" in f for f in lines[-2]["failures"])
    assert "serve_run" not in [l.get("step") for l in lines]
