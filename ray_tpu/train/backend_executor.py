"""BackendExecutor: drives a WorkerGroup through a training run.

Analog of ray: python/ray/train/_internal/backend_executor.py:67
(start :129, start_training :445, get_next_results :572, _restart
:740-756).  Responsibilities: gang-place workers, run the backend
rendezvous, launch the user train fn everywhere, drain per-worker report
streams in lock-step, and recover from worker failure.

Recovery paths (ISSUE 8):
- **Elastic** (default, >= 2 workers): membership epochs — shrink to the
  surviving processes and resume from the newest async checkpoint, then
  regrow when capacity returns (train/elastic.py; SURVEY §7 "elastic
  restart with slice granularity" made rank-granular).
- **Legacy restart loop** (RAY_TPU_ELASTIC=0, or single worker): tear
  the whole group down and respawn, up to FailureConfig.max_failures —
  with one refinement: when every worker is still ALIVE (a transient
  train-fn error), the live gang is reused instead of respawned.
"""
from __future__ import annotations

import logging
import time
from typing import Any, Callable

import ray_tpu
from ray_tpu.exceptions import ActorError, WorkerCrashedError
from ray_tpu.train.backend import Backend, JaxBackend
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import FailureConfig, ScalingConfig
from ray_tpu.train.worker_group import WorkerGroup

logger = logging.getLogger(__name__)


class TrainingFailedError(RuntimeError):
    pass


def _dataset_shards(config: dict, n: int) -> tuple[list[dict], dict]:
    """Per-worker dataset iterators + the config with the dataset keys
    stripped.  One streaming_split iterator per worker per split
    dataset (ray: DataParallelTrainer wiring train.get_dataset_shard
    through the data StreamSplitDataIterator); called per gang launch,
    so an elastic epoch re-splits at the new world size."""
    shards_per_worker: list[dict] = [{} for _ in range(n)]
    to_split = config.get("_datasets_to_split", "all")
    if isinstance(to_split, str) and to_split != "all":
        to_split = [to_split]    # membership, never substring match
    for name, ds in (config.get("_datasets") or {}).items():
        if to_split == "all" or name in to_split:
            its = ds.streaming_split(n)
            for i in range(n):
                shards_per_worker[i][name] = its[i]
        else:
            # Unsplit datasets replicate: every worker iterates the
            # whole thing (ray: DataConfig.datasets_to_split).
            for i in range(n):
                shards_per_worker[i][name] = ds.iterator()
    config = {k: v for k, v in config.items()
              if k not in ("_datasets", "_datasets_to_split")}
    return shards_per_worker, config


class BackendExecutor:
    def __init__(self, scaling: ScalingConfig,
                 backend: Backend | None = None,
                 failure: FailureConfig | None = None,
                 trial_name: str = "train"):
        self.scaling = scaling
        self.backend = backend or JaxBackend()
        self.failure = failure or FailureConfig()
        self.trial_name = trial_name
        self.worker_group: WorkerGroup | None = None
        self._num_failures = 0
        # Elastic introspection (ISSUE 8): the ElasticRun driving this
        # executor (None on the legacy path), and the legacy restart
        # loop's failure→relaunched wall time for the same-run MTTR A/B.
        self.elastic = None
        self.restart_mttr_ms: float | None = None
        self._fail_t0: float | None = None

    # ----------------------------------------------------------- lifecycle
    def start(self) -> None:
        self.worker_group = WorkerGroup(
            self.scaling.num_workers, self.scaling.bundles(),
            strategy=self.scaling.placement_strategy)
        self.backend.on_start(self.worker_group)

    def shutdown(self) -> None:
        if self.worker_group is not None:
            try:
                self.backend.on_shutdown(self.worker_group)
            except Exception:  # noqa: BLE001
                pass
            if self.worker_group.num_workers >= 2:
                # The host collective group's detached rendezvous would
                # otherwise outlive the run (the round-10
                # destroy_collective_group works from the driver even
                # though the group's registries live in the workers).
                try:
                    from ray_tpu import collective as col

                    col.destroy_collective_group(
                        getattr(self, "_host_group",
                                f"train_host:{self.trial_name}"))
                except Exception:  # noqa: BLE001
                    pass
            self.worker_group.shutdown()
            self.worker_group = None

    def _workers_all_alive(self) -> bool:
        """Ping every worker of the current group (short deadline): True
        iff all answer — the reuse-don't-respawn gate of the legacy
        retry path."""
        wg = self.worker_group
        if wg is None or not wg.workers or any(
                w is None for w in wg.workers):
            return False
        try:
            wg.execute("get_status", _timeout=10.0)
            return True
        except Exception:  # noqa: BLE001 - someone is dead/wedged
            return False

    def _quiesce_group(self) -> bool:
        """Prepare a live gang for in-place reuse: park every worker's
        train fn (a previous incarnation's thread still unwinding after
        start_train_fn resets worker state would poison the retry with
        a phantom error), destroy the stale collective group (a
        same-name re-create needs a fresh rendezvous, and the destroy
        unparks any rank still blocked in a collective), then join the
        fn threads.  False → the caller falls back to a full restart."""
        wg = self.worker_group
        try:
            wg.execute("park_at_barrier", 0, _timeout=30.0)
            from ray_tpu import collective as col

            try:
                col.destroy_collective_group(
                    getattr(self, "_host_group",
                            f"train_host:{self.trial_name}"))
            except Exception:  # noqa: BLE001 - never formed (1 worker)
                pass
            return all(st["parked"] for st in wg.execute(
                "join_train", 20.0, _timeout=40.0))
        except Exception:  # noqa: BLE001 - someone died after the ping
            return False

    def _restart(self) -> None:
        # Failpoint window: the group-restart path itself (delay = slow
        # recovery observable in MTTR; error = restart refused).
        from ray_tpu import failpoints

        if failpoints.ACTIVE:
            failpoints.fire("train.group_restart")
        logger.warning("restarting worker group (failure %d)",
                       self._num_failures)
        self.shutdown()
        self.start()

    # ------------------------------------------------------------ training
    def run(self, train_fn: Callable, config: dict | None = None,
            on_report: Callable[[list[dict]], Any] | None = None,
            resume_checkpoint: Checkpoint | None = None,
            latest_checkpoint: Callable[[], Checkpoint | None]
            | None = None) -> list:
        """Run train_fn on all workers to completion.  `on_report` sees the
        per-round list of rank reports (aligned, one per worker) and may
        return "stop" to early-stop.  Returns per-worker return values.

        `latest_checkpoint` (ray: backend_executor.py:740-756 pairs
        _restart with the session's newest checkpoint): after a group
        restart the retry resumes from the NEWEST checkpoint reported so
        far, not the run's original resume point — without it a failure
        at step 900/1000 replays from step 0.
        """
        config = config or {}
        self._host_group = f"train_host:{self.trial_name}"
        if self.scaling.num_workers >= 2:
            # Elastic membership epochs (ISSUE 8): shrink to survivors
            # on rank loss, regrow at an epoch boundary.  Kill switch
            # RAY_TPU_ELASTIC=0 (read here, per run) keeps the legacy
            # restart loop below for same-run A/B.
            from ray_tpu.train import elastic

            if elastic.elastic_enabled():
                self.elastic = elastic.ElasticRun(self)
                return self.elastic.run(train_fn, config, on_report,
                                        resume_checkpoint,
                                        latest_checkpoint)
        max_failures = self.failure.max_failures
        while True:
            resume = resume_checkpoint
            if latest_checkpoint is not None:
                resume = latest_checkpoint() or resume_checkpoint
            try:
                return self._run_once(train_fn, config, on_report,
                                      resume)
            except (TrainingFailedError, ActorError,
                    WorkerCrashedError) as e:
                # Any actor/worker failure inside a run round counts as a
                # training failure: raw ActorError can surface from
                # group-wide calls (get_status/get_result/execute) when a
                # worker dies between result polls — same recovery.
                if not isinstance(e, TrainingFailedError):
                    e = TrainingFailedError(f"worker group failure: {e!r}")
                self._num_failures += 1
                if max_failures >= 0 and self._num_failures > max_failures:
                    raise e from None
                self._fail_t0 = time.perf_counter()
                if self._workers_all_alive() and self._quiesce_group():
                    # ISSUE-8 satellite: a transient train-fn error with
                    # every worker still alive (e.g. one rank's step
                    # raised) does not need a gang respawn — quiesce the
                    # live processes and reuse them.
                    logger.warning(
                        "retrying on the surviving worker group "
                        "(failure %d: all workers alive)",
                        self._num_failures)
                else:
                    self._restart()

    def _run_once(self, train_fn, config, on_report,
                  resume_checkpoint) -> list:
        wg = self.worker_group
        if wg is None:
            raise RuntimeError("executor not started")
        n = wg.num_workers
        # local ranks: position within each node's worker list
        node_ids = wg.execute("get_node_id")
        seen: dict[str, int] = {}
        local_ranks = []
        for nid in node_ids:
            local_ranks.append(seen.get(nid, 0))
            seen[nid] = local_ranks[-1] + 1
        self.backend.on_training_start(wg)
        # Host-side DCN collective group over the gang (ISSUE 5): the
        # train loop syncs host state through session.host_allreduce
        # (ring/tree schedules, async overlap) instead of bespoke RPCs.
        host_group = None
        if n >= 2:
            from ray_tpu import collective as col

            host_group = getattr(self, "_host_group",
                                 f"train_host:{self.trial_name}")
            col.create_collective_group(wg.workers, n, list(range(n)),
                                        group_name=host_group)
        shards_per_worker, config = _dataset_shards(config, n)
        ray_tpu.get([
            w.start_train_fn.remote(
                train_fn, config, world_rank=i, world_size=n,
                local_rank=local_ranks[i], trial_name=self.trial_name,
                checkpoint=resume_checkpoint,
                dataset_shards=shards_per_worker[i],
                host_group=host_group)
            for i, w in enumerate(wg.workers)
        ])
        if self._fail_t0 is not None:
            # Legacy restart loop's MTTR: failure caught → whole gang
            # relaunched (the elastic path's same-run A/B reference).
            self.restart_mttr_ms = round(
                (time.perf_counter() - self._fail_t0) * 1e3, 1)
            self._fail_t0 = None

        done = [False] * n
        pending: list[list[dict]] = [[] for _ in range(n)]
        while not all(done):
            progressed = False
            for i, w in enumerate(wg.workers):
                if done[i] or pending[i]:
                    continue
                try:
                    msg = ray_tpu.get(w.next_result.remote(timeout=1.0),
                                      timeout=60.0)
                except Exception as e:  # noqa: BLE001 - worker death
                    raise TrainingFailedError(
                        f"worker {i} died: {e!r}") from e
                if msg is None:
                    continue
                progressed = True
                if msg["type"] == "done":
                    done[i] = True
                    if msg.get("error"):
                        # Not once every rank is done: its peers may
                        # sit inside a collective it will never join.
                        # Reports already drained still reach the
                        # caller (a fresher resume point).
                        while on_report is not None and any(pending):
                            on_report([p.pop(0) for p in pending if p])
                        raise TrainingFailedError(
                            f"train fn failed on rank {i}:\n"
                            f"{msg['error']}")
                elif msg["type"] == "report":
                    pending[i].append(msg)
            # lock-step: emit a round once every live worker reported
            if all(p or done[i] for i, p in enumerate(pending)) and \
                    any(pending):
                round_msgs = [p.pop(0) if p else None for p in pending]
                if on_report is not None:
                    verdict = on_report(
                        [m for m in round_msgs if m is not None])
                    if verdict == "stop":
                        wg.execute("stop")
            if not progressed:
                time.sleep(0.05)

        # Every rank's done message came without an error.
        return wg.execute("get_result")
