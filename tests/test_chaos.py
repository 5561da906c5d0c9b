"""Chaos: random worker kills under load; retried tasks all complete.

Mirrors ray: python/ray/_private/test_utils.py:1433 (ResourceKillerActor)
and the nightly chaos suites — the framework's availability story is that
task retries + lineage + the worker reaper absorb process churn.
"""
import os
import random
import signal
import subprocess
import threading
import time

import pytest

import ray_tpu

pytestmark = pytest.mark.chaos


def _chaos_seed() -> int:
    """Kill-schedule seed: logged at test start so a flake reproduces —
    rerun with RAY_TPU_CHAOS_SEED=<logged value>.  Without the override
    each run draws a fresh schedule (time-derived), so the suite still
    explores; WITH it the victim sequence is replayed exactly."""
    env = os.environ.get("RAY_TPU_CHAOS_SEED", "")
    seed = int(env) if env else (time.time_ns() % (1 << 31))
    print(f"\n[chaos] kill schedule seed: {seed} "
          f"(replay with RAY_TPU_CHAOS_SEED={seed})", flush=True)
    return seed


def _worker_pids() -> list[int]:
    """Workers of THIS cluster only: children of our spawned agent (a
    machine-wide grep could kill another test session's workers).
    Zygote-forked workers keep the zygote's argv (fork doesn't rewrite
    it), so they are found as children OF the zygote instead."""
    from ray_tpu import api as _api

    agent_pids = {str(p.pid) for p in _api._head_processes}
    out = subprocess.run(["ps", "-eo", "pid,ppid,args"],
                         capture_output=True, text=True).stdout
    rows = []
    for line in out.splitlines():
        parts = line.split(None, 2)
        if len(parts) == 3:
            rows.append(parts)
    zygote_pids = {pid for pid, ppid, args in rows
                   if ppid in agent_pids
                   and "ray_tpu._private.zygote" in args}
    pids = []
    for pid, ppid, args in rows:
        cold = (ppid in agent_pids
                and "ray_tpu._private.worker_main" in args)
        warm = ppid in zygote_pids
        if cold or warm:
            try:
                pids.append(int(pid))
            except ValueError:
                pass
    return pids


def test_tasks_survive_random_worker_kills():
    seed = _chaos_seed()
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(resources={"CPU": 4})
    try:
        @ray_tpu.remote(max_retries=20)
        def work(i):
            time.sleep(0.1)
            return i * i

        stop = threading.Event()
        killed = []

        def killer():
            # Kill interval must exceed worker startup (~2s on a slow
            # box), or the cluster
            # livelocks replacing workers that die before registering —
            # the reference's ResourceKiller paces kills the same way.
            rng = random.Random(seed)
            last_kill = 0.0
            while not stop.is_set() and len(killed) < 6:
                time.sleep(0.25)           # poll fast, kill paced
                if time.monotonic() - last_kill < 2.0:
                    continue
                pids = _worker_pids()
                if pids:
                    victim = rng.choice(pids)
                    try:
                        os.kill(victim, signal.SIGKILL)
                        killed.append(victim)
                        last_kill = time.monotonic()
                    except ProcessLookupError:
                        pass

        t = threading.Thread(target=killer, daemon=True)
        t.start()
        try:
            refs = [work.remote(i) for i in range(120)]
            results = ray_tpu.get(refs, timeout=240)
        finally:
            stop.set()
            t.join(timeout=5)
        assert results == [i * i for i in range(120)]
        assert killed, "chaos thread never killed a worker"
    finally:
        ray_tpu.shutdown()


def test_call_behind_a_failed_address_resolve_raises(monkeypatch):
    """A call queued behind an actor-address resolve that FAILS had no
    owner: the error ended the sender task and the call's ref stayed
    pending for ever.  Found under a 1 Hz `stack_dump.collect()`, which
    (before one GIL-holding handler replaced the two signals) ended the
    controller with SIGSEGV while a fresh actor's first call waited for
    its address; `get_actor_info` then ran into its own 150 s deadline.
    Here the resolve fails at once (the 150 s are not the point): the
    caller gets the runtime's error, inside 30 s."""
    from ray_tpu._private.worker import global_worker
    from ray_tpu.exceptions import ActorError

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(resources={"CPU": 2})
    try:
        @ray_tpu.remote
        class Echo:
            def pid(self):
                return os.getpid()

        async def _no_answer(_st):
            raise TimeoutError("controller did not answer")

        monkeypatch.setattr(global_worker(), "_do_resolve", _no_answer)
        a = Echo.remote()
        t0 = time.monotonic()
        with pytest.raises(ActorError, match="could not be resolved"):
            ray_tpu.get(a.pid.remote(), timeout=60)
        assert time.monotonic() - t0 < 30
        # The worker that holds the actor vanishes too: nothing waits.
        monkeypatch.undo()
        pid = ray_tpu.get(a.pid.remote(), timeout=60)
        os.kill(pid, signal.SIGKILL)
        t0 = time.monotonic()
        with pytest.raises(ActorError):
            ray_tpu.get(a.pid.remote(), timeout=60)
        assert time.monotonic() - t0 < 30
    finally:
        ray_tpu.shutdown()
