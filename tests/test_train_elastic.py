"""Elastic gang training (ISSUE 8): membership epochs over a surviving
worker gang.

Covers the epoch protocol end-to-end on the local runtime and an
in-process multi-node cluster:

- SIGKILL a rank mid-step: survivors continue at W-1 WITHOUT a process
  restart (same pid across epochs), then the gang regrows to W at a
  later epoch with the joiner bootstrapping parameters from rank 0 via
  host_broadcast (checkpoint=None for joiners).
- Seeded loss-trajectory equivalence: the W-1 segment of a shrunk run
  is bit-identical to a fixed-(W-1) run resumed from the same
  checkpoint (deterministic resharding contract), with the rank lost
  via cluster_utils kill_node.
- Failpoint sites train.epoch_barrier / train.rank_join: a survivor
  delayed (or killed) at the barrier, and the JOINING rank killed
  mid-parameter-broadcast — the epoch aborts cleanly back to the
  surviving roster, then regrows; both end at zero leaked arena pins
  and destroyed stale collective groups.
- Legacy path (RAY_TPU_ELASTIC=0) satellite: a transient train-fn error
  with every worker alive reuses the live gang instead of respawning.
- PG bundle patching: remove_worker eagerly releases the dead slot's
  bundle (honest free capacity), reschedule + restore re-fill it.
"""
import os
import time

import pytest

import ray_tpu
from ray_tpu.train import Checkpoint
from ray_tpu.train.backend_executor import BackendExecutor
from ray_tpu.train.checkpoint import CheckpointManager
from ray_tpu.train.config import FailureConfig, ScalingConfig


def _sgd_loop(config):
    """Deterministic data-parallel SGD whose trajectory is a pure
    function of (resume state, step, world_size): per-step data is
    seeded by the GLOBAL step and sized 4*W rows, each rank reduces its
    contiguous shard, gradients sum over the gang.  Elastic contract:
    resume from the checkpoint when present, then pass the state
    through host_broadcast so a joined rank bootstraps from rank 0."""
    import hashlib
    import os
    import signal
    import time

    import numpy as np

    from ray_tpu import train
    from ray_tpu.train import Checkpoint

    ctx = train.get_context()
    W = ctx.get_world_size()
    rank = ctx.get_world_rank()
    ckpt = train.get_checkpoint()
    state = {"params": np.zeros(8, np.float64), "step": np.int64(0)}
    if ckpt is not None:
        d = ckpt.to_dict()
        state = {"params": np.asarray(d["params"], np.float64),
                 "step": np.int64(d["step"] + 1)}
    state = train.host_broadcast(state)
    params = np.asarray(state["params"], np.float64)
    start = step = int(state["step"])
    while step < config["total_steps"]:
        marker = config.get("kill_marker")
        if (marker and step == config.get("kill_at", -1)
                and rank == config.get("kill_rank", 1)
                and not os.path.exists(marker)):
            open(marker, "w").close()
            if config.get("kill_mode") == "exit":
                # Non-signal death: keeps one-shot SIGKILL-presuming
                # failpoint scrubbing (on_child_sigkill) out of tests
                # that arm a DIFFERENT crash site for a later process.
                os._exit(17)
            os.kill(os.getpid(), signal.SIGKILL)
        if (config.get("error_marker") and rank == 1
                and step == config.get("error_at", -1)
                and not os.path.exists(config["error_marker"])):
            open(config["error_marker"], "w").close()
            raise ValueError("transient step failure")
        rng = np.random.RandomState(1000 + step)
        data = rng.randn(4 * W, 8)
        shard = data[rank * 4:(rank + 1) * 4]
        grad = train.host_allreduce(shard.sum(axis=0))
        params = params - 0.01 * np.asarray(grad, np.float64)
        h = hashlib.blake2b(params.tobytes(), digest_size=8).hexdigest()
        train.report({"step": step, "phash": h, "world": W,
                      "epoch": ctx.get_epoch(), "pid": os.getpid(),
                      "start": start, "joined": ctx.get_joined()},
                     checkpoint=Checkpoint.from_dict(
                         {"params": params, "step": step}))
        if config.get("step_sleep_s"):
            time.sleep(config["step_sleep_s"])
        step += 1


def _drive(loop, config, num_workers, storage, trial,
           max_failures=4, scaling_kwargs=None):
    """Minimal trainer harness around BackendExecutor so tests can
    introspect executor.elastic (stats, transitions) directly."""
    executor = BackendExecutor(
        ScalingConfig(num_workers=num_workers, num_cpus_per_worker=0.5,
                      **(scaling_kwargs or {})),
        failure=FailureConfig(max_failures=max_failures),
        trial_name=trial)
    manager = CheckpointManager(str(storage))
    history = []

    def on_report(msgs):
        by_rank = {m["rank"]: m for m in msgs}
        rank0 = by_rank.get(0) or msgs[0]
        history.append(rank0["metrics"])
        ckpt = next((m["checkpoint"] for m in msgs
                     if m.get("checkpoint")), None)
        if ckpt is not None:
            manager.register(ckpt, rank0["metrics"])

    executor.start()
    error = None
    try:
        executor.run(loop, dict(config), on_report=on_report,
                     latest_checkpoint=lambda: manager.latest_checkpoint)
    except Exception as e:  # noqa: BLE001 - surfaced to the test
        error = e
    finally:
        executor.shutdown()
    return executor, history, manager, error


def _assert_stale_groups_destroyed(trial, max_epoch):
    """Every past epoch's rendezvous actor must be gone (get_actor
    filters DEAD actors)."""
    for e in range(max_epoch + 1):
        with pytest.raises(Exception):
            ray_tpu.get_actor(f"collective_rdv:train_host:{trial}:{e}")


class TestElasticShrinkRegrow:
    def test_shrink_and_regrow_without_process_restart(self, ray_shared,
                                                       tmp_path):
        """SIGKILL rank 1 mid-step: the gang shrinks to W-1 and
        continues on the SAME surviving process (pid-stable rank 0),
        loses at most one checkpoint interval (interval=1 step here),
        then regrows to W at a later epoch with the joiner
        bootstrapping via broadcast (joined=True, no checkpoint)."""
        marker = tmp_path / "killed_once"
        executor, history, _, error = _drive(
            _sgd_loop,
            {"total_steps": 10, "kill_at": 3, "step_sleep_s": 0.3,
             "kill_marker": str(marker)},
            num_workers=2, storage=tmp_path / "store", trial="el_sr")
        assert marker.exists(), "kill never armed - test is vacuous"
        assert error is None, error
        worlds = [m["world"] for m in history]
        assert 1 in worlds, f"never shrank: {worlds}"
        assert worlds[-1] == 2, f"never regrew: {worlds}"
        # No process restart for the survivor: rank 0's pid never
        # changes, across both transitions.
        assert len({m["pid"] for m in history}) == 1, history
        # Steps lost <= one checkpoint interval (1): the first
        # post-shrink report starts at most one step before the kill.
        shrink_start = next(m["start"] for m in history
                            if m["world"] == 1)
        assert shrink_start >= 3 - 1, history
        # Stats: one shrink and one regrow transition, MTTR rows set.
        st = executor.elastic.stats
        kinds = [t["kind"] for t in st["transitions"]]
        assert kinds == ["shrink", "regrow"], st
        assert st["elastic_shrink_mttr_ms"] > 0
        assert st["elastic_regrow_mttr_ms"] > 0
        _assert_stale_groups_destroyed("el_sr", executor.elastic.epoch)

def test_trajectory_matches_fixed_world_run(tmp_path, monkeypatch):
    """Seeded loss-trajectory equivalence (ISSUE-8 satellite): the W-1
    segment of an elastic run whose rank-1 NODE is hard-killed
    (cluster_utils kill_node) is bit-identical, step for step, to a
    fixed W=1 run resumed from the same checkpoint.  Regrow is off so
    the shrunk segment runs to completion on the surviving node."""
    import threading

    from ray_tpu.cluster_utils import Cluster

    monkeypatch.setenv("RAY_TPU_ELASTIC_REGROW", "0")
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    cluster = Cluster()
    cluster.start_head()
    n1 = cluster.add_node(resources={"CPU": 2})
    n2 = cluster.add_node(resources={"CPU": 2})
    ray_tpu.init(address=cluster.address)
    try:
        cluster.wait_for_nodes(2)
        progress = tmp_path / "progress"
        progress.mkdir()

        def loop(config):
            import os as _os

            from ray_tpu import train

            ctx = train.get_context()
            with open(_os.path.join(
                    config["progress_dir"],
                    f"rank{ctx.get_world_rank()}.{ctx.get_epoch()}"),
                    "w") as f:
                f.write(ctx.get_node_id())
            _sgd_loop(config)

        box = {}

        def run():
            box["out"] = _drive(
                loop,
                {"total_steps": 8, "step_sleep_s": 0.4,
                 "progress_dir": str(progress)},
                num_workers=2, storage=tmp_path / "el_store",
                trial="el_traj",
                scaling_kwargs={"placement_strategy": "STRICT_SPREAD"})

        t = threading.Thread(target=run, daemon=True)
        t.start()
        # Kill the node hosting rank 1 once it has reported in.
        deadline = time.monotonic() + 120
        victim = None
        while time.monotonic() < deadline and victim is None:
            f = progress / "rank1.0"
            if f.exists() and f.read_text():
                node_id = f.read_text()
                victim = next((n for n in (n1, n2)
                               if n["node_id"] == node_id), None)
            time.sleep(0.2)
        assert victim is not None, "rank1 never reported its node"
        time.sleep(1.0)     # let a couple of steps land
        cluster.kill_node(victim)
        t.join(timeout=300)
        assert not t.is_alive(), "elastic fit wedged after node kill"
        executor, history, manager, error = box["out"]
        assert error is None, error
        worlds = [m["world"] for m in history]
        assert 1 in worlds and worlds[-1] == 1, worlds
        assert any(t_["kind"] == "shrink"
                   for t_ in executor.elastic.stats["transitions"])
        # The elastic run's W=1 segment started from this checkpoint:
        shrink_start = next(m["start"] for m in history
                            if m["world"] == 1)
        resume_ckpt = None
        for d in sorted(os.listdir(manager.storage_path)):
            if not d.startswith("checkpoint_"):
                continue
            c = Checkpoint(os.path.join(manager.storage_path, d))
            if c.to_dict()["step"] == shrink_start - 1:
                resume_ckpt = c
        assert resume_ckpt is not None, \
            f"no checkpoint for step {shrink_start - 1}"
        # Reference: fixed W=1 from the same checkpoint, same loop.
        executor2 = BackendExecutor(
            ScalingConfig(num_workers=1, num_cpus_per_worker=0.5),
            failure=FailureConfig(max_failures=0), trial_name="el_ref")
        ref_history = []
        executor2.start()
        try:
            executor2.run(_sgd_loop, {"total_steps": 8},
                          on_report=lambda ms: ref_history.append(
                              ms[0]["metrics"]),
                          resume_checkpoint=resume_ckpt)
        finally:
            executor2.shutdown()
        ref_by_step = {m["step"]: m["phash"] for m in ref_history}
        compared = 0
        for m in history:
            if m["world"] != 1:
                continue
            assert m["phash"] == ref_by_step[m["step"]], \
                (m, ref_by_step)
            compared += 1
        assert compared >= 2, history
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_elastic_transient_error_retries_live_gang(ray_shared, tmp_path):
    """A train-fn error on the elastic path burns one max_failures
    round (same budget contract as the legacy loop) and retries the
    LIVE gang at the next epoch — pid-stable, no respawn."""
    t0 = time.monotonic()
    executor, history, _, error = _drive(
        _sgd_loop,
        {"total_steps": 4, "error_marker": str(tmp_path / "err_once"),
         "error_at": 2},
        num_workers=2, storage=tmp_path / "store", trial="el_retry",
        max_failures=1)
    took = time.monotonic() - t0
    assert (tmp_path / "err_once").exists(), "error never armed"
    assert error is None, error
    # Rank 0 sat in host_allreduce when rank 1 raised: it is parked and
    # freed when the error is REPORTED, not when the collective's own
    # 120 s deadline gives up on the missing contribution.
    assert took < 30, f"peers of the failed rank waited: {took:.1f}s"
    kinds = [t["kind"] for t in executor.elastic.stats["transitions"]]
    assert kinds == ["retry"], kinds
    assert len({m["pid"] for m in history}) == 1, history
    assert history[-1]["step"] == 3 and history[-1]["world"] == 2


def test_legacy_transient_error_reuses_live_group(ray_shared, tmp_path,
                                                  monkeypatch):
    """ISSUE-8 satellite (legacy path): a transient train-fn error with
    every worker still ALIVE retries on the live gang — same worker
    pids after the retry, no respawn."""
    monkeypatch.setenv("RAY_TPU_ELASTIC", "0")
    t0 = time.monotonic()
    executor, history, _, error = _drive(
        _sgd_loop,
        {"total_steps": 4, "error_marker": str(tmp_path / "err_once"),
         "error_at": 2},
        num_workers=2, storage=tmp_path / "store", trial="el_legacy",
        max_failures=1)
    took = time.monotonic() - t0
    assert (tmp_path / "err_once").exists(), "error never armed"
    assert error is None, error
    assert took < 30, f"peers of the failed rank waited: {took:.1f}s"
    assert executor.elastic is None     # legacy path ran
    # One pid per rank across the WHOLE run including the retry: the
    # group was reused, not respawned.  rank0 history only carries
    # rank0's pid; assert on it plus the restart MTTR row being set by
    # the reuse path.
    assert len({m["pid"] for m in history}) == 1, history
    assert executor._num_failures == 1


def test_worker_group_bundle_patching(ray_shared):
    """PG patching primitives under the elastic path: remove_worker
    eagerly releases the slot's bundle (free capacity visible at the
    controller), reschedule + restore re-fill the slot."""
    from ray_tpu.train.worker_group import WorkerGroup

    def _free_cpu():
        return sum(n["available"].get("CPU", 0.0)
                   for n in ray_tpu.nodes() if n["state"] == "ALIVE")

    def _settled_free(timeout=30):
        """Free CPU once the heartbeat-lagged view stops moving."""
        deadline = time.monotonic() + timeout
        prev, stable = None, 0
        while time.monotonic() < deadline and stable < 8:
            f = _free_cpu()
            stable = stable + 1 if f == prev else 0
            prev = f
            time.sleep(0.25)
        return prev

    def _wait_free(target, timeout=30):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if _free_cpu() == pytest.approx(target):
                return True
            time.sleep(0.2)
        return False

    wg = WorkerGroup(2, [{"CPU": 0.5}, {"CPU": 0.5}])
    try:
        # Both reservations visible (heartbeat-lagged) before baselining.
        base = _settled_free()
        wg.remove_worker(1)
        assert _wait_free(base + 0.5), \
            f"bundle not eagerly released (free={_free_cpu()}, " \
            f"base={base})"
        assert wg.reschedule_lost_bundles() in ("PENDING", "CREATED")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and wg.pg_state() != "CREATED":
            time.sleep(0.2)
        assert wg.pg_state() == "CREATED"
        w = wg.restore_worker(1)
        assert ray_tpu.get(w.get_node_id.remote(), timeout=60)
    finally:
        wg.shutdown()


@pytest.mark.chaos
class TestElasticChaos:
    """Failpoint-driven epoch-transition chaos.  Own cluster per test
    (sites are armed via env BEFORE init so agents/workers inherit)."""

    def _fresh_cluster(self, spec):
        from ray_tpu._private import failpoints

        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        failpoints.configure(spec)
        ray_tpu.init(resources={"CPU": 4})

    def teardown_method(self, method):
        from ray_tpu._private import failpoints

        failpoints.reset()
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()

    def test_rank_kill_with_barrier_delay(self, tmp_path):
        """train.epoch_barrier=delay slows the survivor's park; the
        shrink still completes, the run finishes at full world, zero
        leaked arena pins, stale groups destroyed."""
        from test_chaos_adversarial import _arena_pins_settle

        self._fresh_cluster("train.epoch_barrier=delay:300")
        marker = tmp_path / "killed_once"
        executor, history, _, error = _drive(
            _sgd_loop,
            {"total_steps": 8, "kill_at": 2, "step_sleep_s": 0.3,
             "kill_marker": str(marker)},
            num_workers=2, storage=tmp_path / "store", trial="el_fp1")
        assert marker.exists() and error is None, error
        assert 1 in [m["world"] for m in history]
        # The armed delay fired in a worker during park_at_barrier.
        from ray_tpu._private.worker import global_worker

        core = global_worker()
        reply, _ = core.call(core.controller_addr, "failpoints",
                             {"op": "counters", "broadcast": True},
                             timeout=30.0)
        fired = 0
        for agent in reply.get("nodes", {}).values():
            for w in agent.get("workers", {}).values():
                c = w.get("counters", {}).get("train.epoch_barrier")
                if c:
                    fired += c["fired"]
        assert fired >= 1, reply
        stats = _arena_pins_settle()
        assert not stats.get("swept_dead_pins", 0), stats
        _assert_stale_groups_destroyed("el_fp1", executor.elastic.epoch)

    def test_joiner_killed_mid_broadcast_aborts_epoch(self, tmp_path):
        """train.rank_join=crash SIGKILLs the JOINING rank inside its
        bootstrap broadcast: the regrow epoch aborts cleanly back to
        the surviving roster, a later regrow (the one-shot site was
        scrubbed by the agent reaper) brings the gang back to W, and
        nothing leaks."""
        from test_chaos_adversarial import _arena_pins_settle

        self._fresh_cluster("train.rank_join=nth:1+crash")
        marker = tmp_path / "killed_once"
        executor, history, _, error = _drive(
            _sgd_loop,
            {"total_steps": 12, "kill_at": 2, "step_sleep_s": 0.3,
             "kill_marker": str(marker), "kill_mode": "exit"},
            num_workers=2, storage=tmp_path / "store", trial="el_fp2",
            max_failures=6)
        assert marker.exists() and error is None, error
        worlds = [m["world"] for m in history]
        assert 1 in worlds, worlds
        assert worlds[-1] == 2, f"never regrew after joiner crash: " \
                                f"{worlds}"
        kinds = [t["kind"] for t in executor.elastic.stats["transitions"]]
        # shrink (the kill), regrow (joiner crashes mid-broadcast),
        # shrink (abort back to survivors), regrow (clean join).
        assert kinds.count("shrink") >= 2, kinds
        assert kinds.count("regrow") >= 2, kinds
        assert kinds[-1] == "regrow", kinds
        # The survivor never restarted through all four transitions.
        assert len({m["pid"] for m in history}) == 1, history
        stats = _arena_pins_settle()
        assert not stats.get("swept_dead_pins", 0), stats
        _assert_stale_groups_destroyed("el_fp2", executor.elastic.epoch)


def test_reshard_state_roundtrip():
    """reshard_state lays a host-restored TrainState onto a DIFFERENT
    mesh bit-identically (the deterministic-resharding contract the
    trajectory test exercises end-to-end)."""
    import jax
    import numpy as np

    from ray_tpu._private.config import ensure_cpu_devices

    ensure_cpu_devices(8)
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.train import step as ts

    cfg = llama.LlamaConfig(vocab_size=64, dim=32, n_layers=1, n_heads=2,
                            n_kv_heads=1, ffn_dim=64, max_seq=32,
                            remat=False)
    opt = ts.default_optimizer(total_steps=10)
    mesh_a = create_mesh(MeshConfig(data=4, fsdp=2),
                         devices=jax.devices()[:8])
    state = ts.sharded_init(jax.random.PRNGKey(0), cfg, opt, mesh_a)
    host = jax.tree.map(lambda x: np.asarray(x), state)
    mesh_b = create_mesh(MeshConfig(data=2, fsdp=2),
                         devices=jax.devices()[:4])
    resharded = ts.reshard_state(host, cfg, opt, mesh_b)
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(resharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
