"""Entrypoint for agent-forked worker processes.

Analog of the reference's default_worker.py
(ray: python/ray/_private/workers/default_worker.py): read connection info
from the environment the agent set, start the CoreWorker, serve until told
to exit.
"""
from __future__ import annotations

import logging
import os


def _watch_parent() -> None:
    """Exit when the owning agent dies — workers must never outlive it.

    Zygote-forked workers (see _private/zygote.py) watch the AGENT's pid
    from RAY_TPU_AGENT_PID: their direct parent is the zygote, and a
    zygote restart must not take live actors down with it."""
    import threading
    import time

    from ray_tpu._private.stack_dump import proc_stat

    agent_pid = int(os.environ.get("RAY_TPU_AGENT_PID") or 0)

    def _alive() -> bool:
        if agent_pid:
            try:
                os.kill(agent_pid, 0)
            except ProcessLookupError:
                return False
            except PermissionError:
                return True
            # An agent that died under a parent that has not reaped it
            # (a driver holding its Popen) still answers kill(pid, 0).
            stat = proc_stat(agent_pid)
            return not stat or stat[0] != "Z"
        return os.getppid() > 1

    def _loop():
        while True:
            if not _alive():
                os._exit(0)
            time.sleep(1.0)

    threading.Thread(target=_loop, daemon=True, name="parent-watch").start()


def _extend_sys_path() -> None:
    """Append the driver's sys.path (shipped via env at init) so that
    by-reference pickles of driver-module functions resolve here."""
    import json
    import sys

    raw = os.environ.get("RAY_TPU_DRIVER_SYS_PATH")
    if not raw:
        return
    for p in json.loads(raw):
        if p not in sys.path:
            sys.path.append(p)


def _pin_jax_platform() -> None:
    """Apply the JAX_PLATFORMS env var via jax.config when jax is
    already imported (a forked child of a parent that loaded it); the
    backend only initializes lazily, so config.update still takes
    effect.  Plain (non-device) workers get JAX_PLATFORMS=cpu from the
    agent so they never grab the TPU chip, which belongs to the device
    worker (ray analog: CUDA_VISIBLE_DEVICES isolation in worker_pool).
    """
    plat = os.environ.get("JAX_PLATFORMS")
    if not plat:
        return
    import sys

    if "jax" not in sys.modules:
        # jax is not loaded (the zygote deliberately keeps jax out of
        # the warm graph): the env var itself governs the platform
        # whenever jax IS first imported — paying the ~0.5s import here
        # just to call config.update was the dominant per-worker boot
        # cost.
        return
    try:
        import jax

        jax.config.update("jax_platforms", plat)
    except Exception:  # noqa: BLE001 - backend already up; run as-is
        pass


def main() -> None:
    import time as _time
    _boot_t0 = _time.monotonic()
    _trace = os.environ.get("RAY_TPU_BOOT_TRACE")

    def _mark(phase: str) -> None:
        if _trace:
            print(f"BOOT {os.getpid()} {phase} "
                  f"{(_time.monotonic() - _boot_t0) * 1000:.1f}ms",
                  flush=True)

    _mark("enter")
    from ray_tpu._private.stack_dump import install as _install_stack

    _install_stack('worker')
    _mark("stack")
    _pin_jax_platform()
    _mark("jaxpin")
    _watch_parent()
    _extend_sys_path()
    _mark("pre")
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s worker[%(process)d]: %(message)s")
    from ray_tpu.logging_config import configure_process_logging
    configure_process_logging()
    from ray_tpu._private.config import Config
    from ray_tpu._private.worker import CoreWorker, set_global_worker

    _mark("imports")
    config = Config().override(None)
    core = CoreWorker(
        mode="worker",
        controller_addr=os.environ["RAY_TPU_CONTROLLER_ADDR"],
        agent_addr=os.environ["RAY_TPU_AGENT_ADDR"],
        config=config,
        worker_id=os.environ["RAY_TPU_WORKER_ID"],
        node_id=os.environ.get("RAY_TPU_NODE_ID", ""),
        pub_addr=os.environ.get("RAY_TPU_PUB_ADDR", ""),
    )
    # Publish the global BEFORE start(): start() registers with the agent,
    # and a queued lease can push a task that runs user code immediately —
    # user code that calls back into the API (handle.method.remote(),
    # ray_tpu.get) resolves the worker through global_worker().  Setting
    # it after start() left a window where that raised "not initialized"
    # (seen as a flaky test_handle_passing under heavy box load).
    set_global_worker(core)
    _mark("core_init")
    core.start()
    _mark("started")
    try:
        core._shutdown.wait()
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
