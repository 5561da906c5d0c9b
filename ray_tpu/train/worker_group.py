"""WorkerGroup: N gang-placed train-worker actors.

Analog of ray: python/ray/train/_internal/worker_group.py:102 (actors in a
placement group) + backend_executor's rendezvous.  Each TrainWorker is one
jax process (one per host on a pod — SURVEY §7: jax wants one process per
host owning all local chips); the train fn runs on a thread inside the
actor so the actor stays responsive for result polling and shutdown.
"""
from __future__ import annotations

import socket
import threading
import traceback
from typing import Any, Callable

import ray_tpu
from ray_tpu.train import session as session_mod
from ray_tpu.utils.placement_group import (PlacementGroup, placement_group,
                                           remove_placement_group)


class TrainWorker:
    """Actor: hosts one train process (rank) of the group."""

    def __init__(self):
        self._thread: threading.Thread | None = None
        self._session = None
        self._finished = False
        self._error: str | None = None
        self._result: Any = None

    # --------------------------------------------------------- rendezvous
    def get_address(self) -> tuple[str, int]:
        """(ip, free_port) for the jax.distributed coordinator (worker 0)."""
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        return socket.gethostbyname(socket.gethostname()), port

    def get_node_id(self) -> str:
        return ray_tpu.get_runtime_context().get_node_id()

    def run_fn(self, fn: Callable, *args, **kwargs):
        """Execute an arbitrary callable in the worker process (backend
        hooks, debugging probes)."""
        return fn(*args, **kwargs)

    def init_collective_group(self, world_size: int, rank: int,
                              backend: str = "object_store",
                              group_name: str = "train_host") -> int:
        """Join the trainer's host-side DCN collective group (ISSUE 5):
        the BackendExecutor forms one group across the worker gang so
        the train loop can sync host-side state (data-loader offsets,
        eval metrics, optimizer-shard exchanges) over the ring/tree
        schedules — `session.host_allreduce_async` overlaps that sync
        with the next step's input pipeline."""
        from ray_tpu import collective as col

        col.init_collective_group(world_size, rank, backend, group_name)
        return rank

    def setup_env(self, env: dict[str, str]) -> bool:
        import os

        os.environ.update(env)
        return True

    # ---------------------------------------------------------- execution
    def start_train_fn(self, fn: Callable, config: dict, *,
                       world_rank: int, world_size: int, local_rank: int,
                       trial_name: str, checkpoint=None,
                       dataset_shards: dict | None = None,
                       host_group: str | None = None,
                       epoch: int = 0, joined: bool = False) -> bool:
        self._finished = False
        self._error = None
        self._result = None
        self._session = sess = session_mod.init_session(
            world_rank=world_rank, world_size=world_size,
            local_rank=local_rank,
            node_id=ray_tpu.get_runtime_context().get_node_id(),
            trial_name=trial_name, checkpoint=checkpoint, config=config,
            dataset_shards=dataset_shards, host_group=host_group,
            epoch=epoch, joined=joined)

        def run():
            try:
                import inspect

                sig = inspect.signature(fn)
                self._result = fn(config) if len(
                    sig.parameters) >= 1 else fn()
            except StopIteration:
                pass
            except BaseException:  # noqa: BLE001
                # An incarnation interrupted at an elastic epoch barrier
                # unwinds however it can (collective error on the
                # drained group, StopIteration escaping a generator...):
                # that fallout is transition mechanics, not a failure.
                if not sess.epoch_abort:
                    self._error = traceback.format_exc()
            finally:
                # Async checkpoint writes must land before the loop is
                # declared done: an unflushed background save would race
                # the coordinator's final checkpoint collection — and a
                # FAILED write must surface as this rank's error, not
                # vanish (the flush re-raises the first failure).
                try:
                    from ray_tpu.train import checkpoint as ckpt_mod

                    ckpt_mod.flush_pending_writes()
                except Exception:  # noqa: BLE001
                    if self._error is None and not sess.epoch_abort:
                        self._error = traceback.format_exc()
                self._finished = True
                # The error rides the done message: the driver parks
                # this rank's peers when the FIRST rank fails, not when
                # the last one gives up waiting for it in a collective.
                sess.out.put({"type": "done", "error": self._error})

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        return True

    def next_result(self, timeout: float = 1.0) -> dict | None:
        """Drain one message from the session queue (None on timeout)."""
        import queue as q

        if self._session is None:
            return {"type": "done", "error": None}
        try:
            msg = self._session.out.get(timeout=timeout)
        except q.Empty:
            if self._finished:
                return {"type": "done", "error": self._error}
            return None
        return msg

    # ------------------------------------------------------ elastic epochs
    def park_at_barrier(self, epoch: int) -> bool:
        """First half of an elastic epoch transition (ISSUE 8): stop the
        running train fn at its next session touchpoint (report /
        host_allreduce / host_broadcast all raise StopIteration once the
        stop flag is up) and mark the incarnation as epoch-aborted so
        its unwind fallout never reads as a training failure.  The
        driver destroys the stale collective group right after this
        call, which unparks any rank blocked inside a collective."""
        from ray_tpu import failpoints

        if failpoints.ACTIVE:
            # Failpoint window: a survivor parking at the epoch barrier
            # (crash = the survivor dies mid-transition and the driver
            # must shrink further; delay = slow barrier, visible in
            # elastic_shrink_mttr_ms).
            failpoints.fire("train.epoch_barrier")
        s = self._session
        if s is not None:
            s.epoch_abort = True
            s.stop_event.set()
            # Unjam a report() blocked on the bounded outbound queue.
            import queue as q

            try:
                while True:
                    s.out.get_nowait()
            except q.Empty:
                pass
        return True

    def join_train(self, timeout: float = 20.0) -> dict:
        """Second half of the barrier: wait (bounded) for the train-fn
        thread to exit, draining the outbound queue so a blocked report
        can finish, then forget the stale epoch's collective group
        locally (the driver already destroyed the shared rendezvous).
        parked=False means the thread is wedged past the deadline — the
        driver treats that worker as lost."""
        import queue as q
        import time as _t

        t = self._thread
        s = self._session
        deadline = _t.monotonic() + timeout
        while t is not None and t.is_alive() and _t.monotonic() < deadline:
            if s is not None:
                try:
                    while True:
                        s.out.get_nowait()
                except q.Empty:
                    pass
            t.join(timeout=0.1)
        parked = t is None or not t.is_alive()
        if s is not None and s.host_group:
            from ray_tpu import collective as col

            col.deregister_collective_group(s.host_group)
        import os

        return {"parked": parked, "pid": os.getpid()}

    def get_status(self) -> dict:
        return {"finished": self._finished, "error": self._error}

    def get_result(self) -> Any:
        return self._result

    def stop(self) -> bool:
        if self._session is not None:
            self._session.stop_event.set()
        return True


class WorkerGroup:
    """Owns the PG + actors.  `execute` fans a callable to all workers.

    Elastic epochs (ISSUE 8) patch the group IN PLACE: `remove_worker`
    kills a slot's actor and eagerly releases its PG bundle (honest
    free capacity for the autoscaler and the regrow path);
    `restore_worker` places a fresh actor on a re-reserved bundle.
    Removed slots hold None — `execute` fans over live workers only."""

    def __init__(self, num_workers: int, bundles: list[dict],
                 strategy: str = "PACK",
                 pg: PlacementGroup | None = None):
        self.num_workers = num_workers
        self._own_pg = pg is None
        self.pg = pg or placement_group(bundles, strategy=strategy)
        if not self.pg.ready(timeout=120.0):
            raise RuntimeError(
                f"placement group {self.pg.id} not ready "
                f"(bundles={bundles}, strategy={strategy})")
        cls = ray_tpu.remote(TrainWorker)
        self.workers = [
            cls.options(
                num_cpus=0,     # resources held by the PG bundle
                placement_group=self.pg,
                placement_group_bundle_index=i).remote()
            for i in range(num_workers)
        ]

    def execute(self, method: str, *args, _timeout: float | None = None,
                **kwargs) -> list:
        """Call `method` on every live worker, gather results."""
        return ray_tpu.get([getattr(w, method).remote(*args, **kwargs)
                            for w in self.workers if w is not None],
                           timeout=_timeout)

    def execute_async(self, method: str, *args, **kwargs) -> list:
        return [getattr(w, method).remote(*args, **kwargs)
                for w in self.workers if w is not None]

    def execute_single(self, idx: int, method: str, *args, **kwargs):
        return ray_tpu.get(
            getattr(self.workers[idx], method).remote(*args, **kwargs))

    # ------------------------------------------------------ elastic patching
    def remove_worker(self, idx: int, release_bundle: bool = True) -> None:
        """Drop one slot: kill its actor (no-op if already dead) and
        eagerly release its PG bundle so the reservation doesn't sit on
        the agent until trial end (ISSUE-8 satellite — the autoscaler /
        regrow path must see honest free capacity)."""
        w = self.workers[idx]
        self.workers[idx] = None
        if w is not None:
            try:
                ray_tpu.kill(w)
            except Exception:  # noqa: BLE001 - already dead
                pass
        if release_bundle:
            try:
                from ray_tpu.utils.placement_group import release_bundles

                release_bundles(self.pg, [idx])
            except Exception:  # noqa: BLE001 - node already reaped it
                pass

    def reschedule_lost_bundles(self) -> str:
        """Kick the controller's bundle scheduler for released slots
        (regrow step 1); returns the PG state."""
        from ray_tpu.utils.placement_group import \
            reschedule_placement_group

        return reschedule_placement_group(self.pg)

    def pg_state(self) -> str:
        from ray_tpu.utils.placement_group import placement_group_state

        return placement_group_state(self.pg)

    def restore_worker(self, idx: int):
        """Place a fresh TrainWorker on slot `idx`'s (re-reserved)
        bundle; the caller must confirm liveness before trusting it."""
        assert self.workers[idx] is None, f"slot {idx} still occupied"
        cls = ray_tpu.remote(TrainWorker)
        w = cls.options(num_cpus=0, placement_group=self.pg,
                        placement_group_bundle_index=idx).remote()
        self.workers[idx] = w
        return w

    def shutdown(self) -> None:
        for w in self.workers:
            if w is None:
                continue
            try:
                ray_tpu.kill(w)
            except Exception:  # noqa: BLE001
                pass
        self.workers = []
        if self._own_pg:
            try:
                remove_placement_group(self.pg)
            except Exception:  # noqa: BLE001
                pass
