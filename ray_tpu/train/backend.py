"""Training backends: per-worker collective/runtime setup hooks.

Analog of ray: python/ray/train/backend.py (Backend.on_start/on_shutdown)
and torch/config.py:65,150 (_TorchBackend.on_start = pick rendezvous addr,
dist.init_process_group on every worker).

TPU difference (SURVEY §2.4 "Collective backend"): inside a slice there is
no process-group object to build — XLA schedules ICI collectives from the
jit'd program.  The backend's only job is the *multi-host* jax runtime
rendezvous: worker 0 donates coordinator ip:port, every worker calls
jax.distributed.initialize(coordinator, num_processes, process_id), after
which jax.devices() spans the whole slice and pjit programs are global.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

if TYPE_CHECKING:
    from ray_tpu.train.worker_group import WorkerGroup


class BackendConfig:
    """Declarative backend selector (ray: train/backend.py
    BackendConfig): subclasses name the Backend that implements their
    setup via backend_cls."""

    @property
    def backend_cls(self) -> type:
        return Backend


class Backend:
    def on_start(self, worker_group: "WorkerGroup") -> None:  # noqa: B027
        pass

    def on_shutdown(self, worker_group: "WorkerGroup") -> None:  # noqa: B027
        pass

    def on_training_start(self, worker_group: "WorkerGroup") -> None:  # noqa: B027,E501
        pass

    def on_epoch_start(self, workers: list, epoch: int) -> None:  # noqa: B027,E501
        """Elastic membership change (ISSUE 8): `workers` is the NEW
        roster in rank order (survivors first, joiners appended).  The
        backend re-forms whatever per-gang runtime it owns at the new
        world size; the base backend owns nothing."""
        pass


def _jax_distributed_init(coordinator: str, num_processes: int,
                          process_id: int,
                          survivable: bool = False) -> bool:
    """Runs inside each TrainWorker actor.

    `survivable` (elastic gangs, ISSUE 8): the default XLA coordination
    client LOG(QFATAL)s the whole process when any task misses
    heartbeats ("Terminating process because the JAX distributed
    service detected fatal errors") — one preempted host becomes a
    gang-wide massacre, which is exactly what the membership-epoch
    protocol exists to avoid.  For the duration of initialize() the
    client factory is patched to install a log-only callback, disable
    shutdown-on-destruction (a dropped half-shut client must not block
    in its destructor), and bound the shutdown barrier at seconds, not
    the 5-minute default (a dead peer fails the barrier — survivors
    must not serve a 5-minute sentence for it at every epoch change).
    """
    import jax

    if num_processes == 1:
        return True          # single process: local devices already global
    if not survivable:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
        return True
    import logging as _logging

    from jax._src import distributed as jdist

    orig = jdist._jax.get_distributed_runtime_client

    def _factory(addr, node_id, **kw):
        kw["missed_heartbeat_callback"] = lambda *a: _logging.getLogger(
            __name__).warning(
            "jax coordination heartbeat failure (surviving: the elastic "
            "epoch transition re-forms the gang): %s", a)
        kw["shutdown_on_destruction"] = False
        kw["shutdown_timeout"] = 5
        return orig(addr, node_id, **kw)

    jdist._jax.get_distributed_runtime_client = _factory
    try:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    finally:
        jdist._jax.get_distributed_runtime_client = orig
    return True


def _jax_distributed_teardown() -> bool:
    """Dismantle this process's jax.distributed state even when the old
    gang is half-dead: a dead peer fails the shutdown barrier, and the
    orderly path leaves the module state set (so a later initialize
    raises 'should only be called once') — force-drop the handles."""
    import jax
    from jax._src import distributed as jdist

    try:
        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001 - barrier failed / never initialized
        state = jdist.global_state
        for attr in ("client", "service", "preemption_sync_manager"):
            try:
                setattr(state, attr, None)
            except Exception:  # noqa: BLE001
                pass
    return True


def _jax_distributed_reinit(coordinator: str, num_processes: int,
                            process_id: int) -> bool:
    """Epoch transition on a SURVIVING process: tear down the previous
    incarnation's distributed runtime (its world no longer exists) and
    re-join at the new size.  A fresh joiner has nothing to shut down —
    the call degrades to a plain initialize."""
    _jax_distributed_teardown()
    return _jax_distributed_init(coordinator, num_processes, process_id,
                                 survivable=True)


class JaxBackend(Backend):
    """Multi-host jax runtime bring-up over the worker group."""

    def on_start(self, worker_group: "WorkerGroup") -> None:
        n = worker_group.num_workers
        if n <= 1:
            return
        ip, port = worker_group.execute_single(0, "get_address")
        coordinator = f"{ip}:{port}"
        import ray_tpu
        from ray_tpu.train.elastic import elastic_enabled

        ray_tpu.get([
            w.run_fn.remote(_jax_distributed_init, coordinator, n, i,
                            elastic_enabled())
            for i, w in enumerate(worker_group.workers)
        ])

    def on_epoch_start(self, workers: list, epoch: int) -> None:
        """Re-form the multi-host jax runtime over the new roster: the
        new rank 0 donates a fresh coordinator port, every member
        shutdown+initializes at the new world size.  Failure aborts the
        epoch transition (the driver falls back to a full restart) —
        silently continuing with a stale device world would make the
        first global pjit hang."""
        n = len(workers)
        if n <= 1:
            # Shrink to one process: drop the stale distributed state so
            # local devices are the whole world again.
            import ray_tpu

            try:
                ray_tpu.get([w.run_fn.remote(_jax_distributed_reinit,
                                             "", 1, 0) for w in workers],
                            timeout=30.0)
            except Exception:  # noqa: BLE001 - best effort at world 1
                pass
            return
        import ray_tpu

        ip, port = ray_tpu.get(workers[0].get_address.remote(),
                               timeout=30.0)
        coordinator = f"{ip}:{port}"
        ray_tpu.get([
            w.run_fn.remote(_jax_distributed_reinit, coordinator, n, i)
            for i, w in enumerate(workers)
        ], timeout=120.0)

    def on_shutdown(self, worker_group: "WorkerGroup") -> None:
        def _shut():
            import jax

            try:
                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001
                pass
            return True

        try:
            worker_group.execute("run_fn", _shut, _timeout=10.0)
        except Exception:  # noqa: BLE001
            pass
