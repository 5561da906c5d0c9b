"""Elastic gang training: membership epochs over a surviving worker gang.

ISSUE 8 / ROADMAP item 2.  The legacy recovery unit is the whole group —
any rank failure sends BackendExecutor through a full teardown + respawn
(_restart), re-paying every worker spawn and compile.  This module makes
membership a first-class, *versioned* property of the run instead:

- The driver owns a monotonically increasing **epoch** naming the current
  gang roster.  Epoch e's host collective group is
  ``train_host:<trial>:<e>`` — a fresh rendezvous per roster, so a stale
  incarnation can never satisfy (or wedge) the next one.
- **Shrink**: when a rank is lost (actor death, node death, a collective
  deadline naming it), survivors PARK at an epoch barrier
  (``TrainWorker.park_at_barrier`` stops the train fn at its next
  session touchpoint), the driver destroys the stale group — draining
  any rank still parked inside a collective with the dead peer — then
  re-forms the group at the new world size, re-runs the backend's
  per-gang bring-up (jax.distributed at the new world), and relaunches
  the train fn on the SURVIVING PROCESSES from the newest async
  checkpoint.  No process restart: imports, jit caches and the warmed
  arena are kept, so shrink MTTR is barrier + relaunch, not spawn +
  compile.
- **Regrow**: the dead slot's PG bundle is released eagerly (honest free
  capacity) and the controller's bundle scheduler re-reserves it as soon
  as the autoscaler (or a replacement in-process node) supplies
  capacity.  The driver then spawns a replacement worker on the
  re-reserved bundle WHILE the survivors keep training, and only the
  final roster flip interrupts them: at the next epoch boundary the
  joiner starts with ``session.joined=True`` and NO checkpoint — it
  receives current parameters from rank 0 via the collective broadcast
  (``train.host_broadcast``), so regrow works even when the replacement
  host does not share the checkpoint filesystem.

Elastic train fns opt into two session contracts (both no-ops for plain
fns on the legacy path): resume state from ``train.get_checkpoint()``
when present, and pass the initial state through
``train.host_broadcast`` so a joined rank bootstraps from rank 0.

Kill switch ``RAY_TPU_ELASTIC=0`` restores the restart-only loop
(same-run A/B); ``RAY_TPU_ELASTIC_REGROW=0`` keeps shrink but never
grows back.  Failpoint sites: ``train.epoch_barrier`` (a survivor
parking), ``train.rank_join`` (a joiner mid-parameter-broadcast).
"""
from __future__ import annotations

import logging
import os
import time
from typing import Callable

import ray_tpu
from ray_tpu import collective as col
from ray_tpu import tracing
from ray_tpu.train import backend_executor as _be

logger = logging.getLogger(__name__)

_TRUTHY = ("1", "true", "yes", "on")


def elastic_enabled() -> bool:
    """RAY_TPU_ELASTIC=0 restores the legacy restart loop (read at run
    start, so one process can A/B both paths)."""
    return os.environ.get("RAY_TPU_ELASTIC", "1").lower() in _TRUTHY


def regrow_enabled() -> bool:
    return os.environ.get(
        "RAY_TPU_ELASTIC_REGROW", "1").lower() in _TRUTHY


def epoch_group_name(trial_name: str, epoch: int) -> str:
    return f"train_host:{trial_name}:{epoch}"


class ElasticRun:
    """One elastic training run: drives the executor's WorkerGroup
    through membership epochs.  Created per BackendExecutor.run call;
    `stats` carries the transition log and MTTR rows the bench reads."""

    def __init__(self, executor: "_be.BackendExecutor"):
        self.exec = executor
        self.wg = executor.worker_group
        self.trial = executor.trial_name
        self.epoch = 0
        # Roster: PG-slot id per rank, in rank order.  Slot i owns PG
        # bundle i forever; ranks are re-assigned contiguously at every
        # epoch (survivors keep relative order, joiners append).
        self.active: list[int] = list(range(self.wg.num_workers))
        self._lost: set[int] = set()
        self._group_name: str | None = None
        self._stopping = False
        # Per-epoch dataset shard iterators: the DRIVER's handles own
        # the streaming_split coordinator actors — dropping them
        # mid-epoch kills every worker's shard with "handle out of
        # scope" (the legacy path keeps them alive in _run_once's
        # frame; this run object is the elastic equivalent).
        self._shards: list | None = None
        # ("shrink"|"regrow", t0): an MTTR clock started at failure
        # detection / roster flip, stamped into stats once the new
        # epoch's fns are relaunched.
        self._mttr_t0: tuple | None = None
        self.stats: dict = {"transitions": [], "epochs": 0}

    # ---------------------------------------------------------------- api
    def run(self, train_fn: Callable, config: dict, on_report,
            resume_checkpoint, latest_checkpoint) -> list:
        max_failures = self.exec.failure.max_failures
        failures = 0

        def newest():
            if latest_checkpoint is not None:
                return latest_checkpoint() or resume_checkpoint
            return resume_checkpoint

        def fail(exc: Exception) -> None:
            """One involuntary transition burns one max_failures round;
            budget exhausted raises `exc` itself."""
            nonlocal failures
            failures += 1
            self.exec._num_failures = failures
            if 0 <= max_failures < failures:
                raise exc from None

        pending: tuple | None = (resume_checkpoint, frozenset())
        while True:
            if pending is not None:
                ckpt, joined = pending
                try:
                    self._launch(train_fn, config, ckpt,
                                 joined_slots=joined)
                    pending = None
                    if self._mttr_t0 is not None:
                        # MTTR clock stops only once the fns are
                        # RELAUNCHED (start refs resolved), not at
                        # roster re-form.
                        key, t0 = self._mttr_t0
                        self._mttr_t0 = None
                        self.stats[f"elastic_{key}_mttr_ms"] = round(
                            (time.perf_counter() - t0) * 1e3, 1)
                except Exception as e:  # noqa: BLE001 - epoch bring-up
                    # A rank can die DURING the launch (e.g. a joiner
                    # crashing in its bootstrap broadcast before the
                    # start reply lands): classify survivors and
                    # shrink, exactly like a mid-epoch death — full
                    # restart only when nobody answers the barrier.
                    logger.warning("epoch %d launch failed: %r",
                                   self.epoch, e)
                    fail(_be.TrainingFailedError(
                        f"epoch {self.epoch} launch failed: {e!r}"))
                    survivors = self._transition(self.active)
                    if survivors:
                        try:
                            self._reform(survivors, kind="shrink")
                            pending = (newest(), frozenset())
                            continue
                        except Exception as e2:  # noqa: BLE001
                            logger.warning("epoch re-form failed: %r",
                                           e2)
                    self._full_restart()
                    pending = (newest(), frozenset())
                    continue
            kind, payload, err = self._poll(on_report)
            if kind == "done":
                return payload
            if kind == "fn_error":
                # Same failure-budget contract as the legacy loop: a
                # train-fn error burns one max_failures round, then the
                # LIVE gang retries at the next epoch from the newest
                # checkpoint (the transition parks the failed rank's
                # peers, some of them inside a collective it left, and
                # drops whoever does not answer — no respawn needed).
                fail(_be.TrainingFailedError(payload))
                survivors = self._transition(self.active)
                if not survivors:
                    self._full_restart()
                    pending = (newest(), frozenset())
                    continue
                try:
                    self._reform(survivors, kind="retry")
                except Exception as e:  # noqa: BLE001
                    logger.warning("retry re-form failed: %r", e)
                    self._full_restart()
                pending = (newest(), frozenset())
                continue
            if kind == "dead":
                fail(_be.TrainingFailedError(
                    f"rank lost at epoch {self.epoch}: {err!r}"))
                t0 = time.perf_counter()
                for slot in payload:
                    self._remove_slot(slot)
                survivors = self._transition(
                    [s for s in self.active if s not in payload])
                if not survivors:
                    logger.warning(
                        "no survivors at epoch %d: full restart",
                        self.epoch)
                    self._full_restart()
                    pending = (newest(), frozenset())
                    continue
                try:
                    self._reform(survivors, kind="shrink")
                except Exception as e:  # noqa: BLE001 - backend re-init
                    logger.warning("epoch re-form failed: %r", e)
                    self._full_restart()
                    pending = (newest(), frozenset())
                    continue
                pending = (newest(), frozenset())
                self._mttr_t0 = ("shrink", t0)
            elif kind == "regrow":
                joiners = payload
                t0 = time.perf_counter()
                survivors = self._transition(self.active)
                if not survivors:
                    self._full_restart()
                    pending = (newest(), frozenset())
                    continue
                roster = survivors + [s for s in joiners
                                      if s not in survivors]
                self._lost -= set(joiners)
                try:
                    self._reform(roster, kind="regrow")
                except Exception as e:  # noqa: BLE001
                    logger.warning("regrow re-form failed: %r", e)
                    self._full_restart()
                    pending = (newest(), frozenset())
                    continue
                pending = (newest(), frozenset(joiners))
                self._mttr_t0 = ("regrow", t0)

    # ------------------------------------------------------------- launch
    def _launch(self, train_fn, config, resume_checkpoint,
                joined_slots=frozenset()) -> None:
        wg = self.wg
        roster = list(self.active)
        n = len(roster)
        workers = [wg.workers[s] for s in roster]
        node_ids = ray_tpu.get(
            [w.get_node_id.remote() for w in workers], timeout=60.0)
        seen: dict[str, int] = {}
        local_ranks = []
        for nid in node_ids:
            local_ranks.append(seen.get(nid, 0))
            seen[nid] = local_ranks[-1] + 1
        self.exec.backend.on_training_start(wg)
        self._group_name = epoch_group_name(self.trial, self.epoch) \
            if n >= 2 else None
        # Keep the executor's shutdown pointed at the CURRENT epoch's
        # group (each stale epoch's group is destroyed at its own
        # transition; the last one falls to shutdown).
        self.exec._host_group = self._group_name or \
            f"train_host:{self.trial}"
        if self._group_name is not None:
            col.create_collective_group(workers, n, list(range(n)),
                                        group_name=self._group_name)
        shards, config = _be._dataset_shards(config, n)
        self._shards = shards
        ray_tpu.get([
            w.start_train_fn.remote(
                train_fn, config, world_rank=r, world_size=n,
                local_rank=local_ranks[r], trial_name=self.trial,
                checkpoint=None if roster[r] in joined_slots
                else resume_checkpoint,
                dataset_shards=shards[r], host_group=self._group_name,
                epoch=self.epoch, joined=roster[r] in joined_slots)
            for r, w in enumerate(workers)
        ], timeout=120.0)
        self.stats["epochs"] = self.epoch
        self.stats.setdefault("world_by_epoch", {})[self.epoch] = n

    # --------------------------------------------------------------- poll
    def _flush_pending(self, pending: list, on_report) -> None:
        """Deliver reports still buffered for lock-step alignment before
        a transition return: their checkpoints must reach the manager
        (a fresher resume point, and trainer-side ephemeral-checkpoint
        cleanup) instead of being silently dropped.  Stop verdicts only
        flag _stopping — the roster is about to be interrupted anyway."""
        while any(pending):
            round_msgs = [p.pop(0) if p else None for p in pending]
            if on_report is not None:
                verdict = on_report(
                    [m for m in round_msgs if m is not None])
                if verdict == "stop":
                    self._stopping = True

    def _poll(self, on_report) -> tuple:
        """Drain report streams in lock-step (legacy semantics) with two
        elastic differences: a per-rank failure names the LOST SLOT
        instead of failing the run, and a ~1 Hz side-poll spawns
        replacement workers as soon as released bundles re-reserve."""
        wg = self.wg
        roster = list(self.active)
        n = len(roster)
        done = [False] * n
        pending: list[list] = [[] for _ in range(n)]
        next_regrow = 0.0
        while not all(done):
            progressed = False
            for r, slot in enumerate(roster):
                if done[r] or pending[r]:
                    continue
                try:
                    msg = ray_tpu.get(
                        wg.workers[slot].next_result.remote(timeout=1.0),
                        timeout=60.0)
                except Exception as e:  # noqa: BLE001 - rank lost
                    self._flush_pending(pending, on_report)
                    return ("dead", [slot], e)
                if msg is None:
                    continue
                progressed = True
                if msg["type"] == "done":
                    done[r] = True
                    if msg.get("error"):
                        # Its peers may sit inside a collective that
                        # this rank will never join: hand the error up
                        # now, and the transition parks and frees them
                        # as it does after a kill.
                        self._flush_pending(pending, on_report)
                        return ("fn_error",
                                f"train fn failed on rank {r} "
                                f"(epoch {self.epoch}):\n{msg['error']}",
                                None)
                elif msg["type"] == "report":
                    pending[r].append(msg)
            if all(p or done[i] for i, p in enumerate(pending)) and \
                    any(pending):
                round_msgs = [p.pop(0) if p else None for p in pending]
                if on_report is not None:
                    verdict = on_report(
                        [m for m in round_msgs if m is not None])
                    if verdict == "stop":
                        self._stopping = True
                        wg.execute("stop")
            now = time.monotonic()
            if (self._lost and not self._stopping and regrow_enabled()
                    and now >= next_regrow):
                next_regrow = now + 1.0
                joiners = self._try_regrow()
                if joiners:
                    self._flush_pending(pending, on_report)
                    return ("regrow", joiners, None)
            if not progressed:
                time.sleep(0.05)
        # Every rank's done message came without an error.
        results = []
        for slot in roster:
            try:
                results.append(ray_tpu.get(
                    wg.workers[slot].get_result.remote(), timeout=30.0))
            except Exception as e:  # noqa: BLE001 - died while finishing
                return ("dead", [slot], e)
        return ("done", results, None)

    # ------------------------------------------------------------- regrow
    def _try_regrow(self) -> list[int] | None:
        """Non-disruptive regrow prep: once the PG reports CREATED again
        (every released bundle re-reserved), spawn replacement workers
        on the lost slots.  Survivors keep training throughout — only
        the roster flip after this returns interrupts them."""
        try:
            if self.wg.pg_state() != "CREATED":
                return None
        except Exception:  # noqa: BLE001 - controller hiccup: retry
            return None
        joiners = []
        for slot in sorted(self._lost):
            w = self.wg.restore_worker(slot)
            try:
                ray_tpu.get(w.get_node_id.remote(), timeout=60.0)
            except Exception as e:  # noqa: BLE001 - capacity raced away
                logger.warning("regrow probe on slot %d failed: %r",
                               slot, e)
                self.wg.remove_worker(slot)
                try:
                    self.wg.reschedule_lost_bundles()
                except Exception:  # noqa: BLE001
                    pass
                # Partial regrow: slots already restored this tick must
                # join NOW — their live actors would trip
                # restore_worker's occupied-slot assert on the next
                # tick; the failed slot retries at a later epoch.
                break
            joiners.append(slot)
        return joiners or None

    # -------------------------------------------------------- transitions
    def _remove_slot(self, slot: int) -> None:
        """Eagerly drop a lost slot: kill the corpse, release its PG
        bundle, ask the scheduler to start re-filling the hole, and
        post an autoscaler demand floor for the full gang."""
        self.wg.remove_worker(slot)
        self._lost.add(slot)
        try:
            self.wg.reschedule_lost_bundles()
        except Exception:  # noqa: BLE001 - controller transient
            pass
        self._post_autoscaler_demand()

    def _transition(self, roster_slots: list[int]) -> list[int]:
        """Epoch barrier: park every candidate survivor, destroy the
        stale collective group (draining ranks parked inside a
        collective with the dead peer), and join each train-fn thread.
        Returns the slots that actually parked; the rest are removed."""
        wg = self.wg
        park = [(s, wg.workers[s].park_at_barrier.remote(self.epoch))
                for s in roster_slots if wg.workers[s] is not None]
        if self._group_name is not None:
            col.destroy_collective_group(
                self._group_name,
                reason=f"membership epoch {self.epoch} of trial "
                       f"{self.trial!r} ended (elastic transition)")
        survivors = []
        for s, ref in park:
            try:
                ray_tpu.get(ref, timeout=30.0)
                st = ray_tpu.get(
                    wg.workers[s].join_train.remote(timeout=20.0),
                    timeout=40.0)
                if st["parked"]:
                    survivors.append(s)
                    continue
                logger.warning("slot %d wedged at the epoch barrier; "
                               "treating as lost", s)
            except Exception as e:  # noqa: BLE001 - died at the barrier
                logger.warning("slot %d lost at the epoch barrier: %r",
                               s, e)
            self._remove_slot(s)
        return survivors

    def _reform(self, roster: list[int], kind: str) -> None:
        # Flight recorder: one span per membership transition (the MTTR
        # anatomy — group destroy, backend re-init — lands on the same
        # timeline as the collectives it unblocks).
        with tracing.span(f"elastic.{kind}",
                          attrs={"world": len(roster),
                                 "trial": self.trial}) as sp:
            self.epoch += 1
            sp["epoch"] = self.epoch
            self.active = roster
            workers = [self.wg.workers[s] for s in roster]
            self.exec.backend.on_epoch_start(workers, self.epoch)
            self._post_autoscaler_demand()
        self.stats["transitions"].append(
            {"epoch": self.epoch, "kind": kind, "world": len(roster)})
        logger.warning("membership epoch %d (%s): world_size=%d "
                       "slots=%s", self.epoch, kind, len(roster), roster)

    def _full_restart(self) -> None:
        """Fallback when elastic has nothing to salvage (no survivors,
        or epoch bring-up failed): the legacy teardown + respawn, folded
        into the epoch sequence as a fresh full roster."""
        # A transition degraded to a respawn must not stamp an
        # elastic_* MTTR row — the legacy restart_mttr_ms covers it.
        self._mttr_t0 = None
        self.exec._restart()
        self.wg = self.exec.worker_group
        self.epoch += 1
        self.active = list(range(self.wg.num_workers))
        self._lost = set()
        self._group_name = None
        self.stats["transitions"].append(
            {"epoch": self.epoch, "kind": "restart",
             "world": len(self.active)})

    def _post_autoscaler_demand(self) -> None:
        """While shrunk, pin an autoscaler demand floor for the FULL
        gang (the regrow path's capacity request); clear it once whole
        again.  Best-effort — no autoscaler, no harm."""
        try:
            from ray_tpu.autoscaler import request_resources

            bundles = self.exec.scaling.bundles() if self._lost else []
            request_resources(bundles=bundles, requester="elastic")
        except Exception:  # noqa: BLE001
            pass
