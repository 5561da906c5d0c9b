"""Train library: JaxTrainer.fit end-to-end on the local runtime
(worker-group actors, report/checkpoint plumbing, failure restart).

Mirrors the reference's Train tests (ray: python/ray/train/tests/) which
run against a single-node ray.init with CPU backends.
"""
import os

import pytest

from ray_tpu.train import (Checkpoint, CheckpointConfig, FailureConfig,
                           JaxTrainer, RunConfig, ScalingConfig)


def _simple_loop(config):
    from ray_tpu import train

    ctx = train.get_context()
    for i in range(config.get("steps", 3)):
        train.report({"step": i, "loss": 1.0 / (i + 1),
                      "rank": ctx.get_world_rank(),
                      "world_size": ctx.get_world_size()})


class TestJaxTrainer:
    def test_fit_single_worker(self, ray_shared, tmp_path):
        trainer = JaxTrainer(
            _simple_loop,
            train_loop_config={"steps": 3},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="t1", storage_path=str(tmp_path)))
        result = trainer.fit()
        assert result.error is None
        assert result.metrics["step"] == 2
        assert result.metrics["world_size"] == 1
        assert len(result.metrics_history) == 3

    def test_fit_two_workers_lockstep(self, ray_shared, tmp_path):
        trainer = JaxTrainer(
            _simple_loop,
            train_loop_config={"steps": 2},
            scaling_config=ScalingConfig(num_workers=2,
                                         num_cpus_per_worker=0.5),
            run_config=RunConfig(name="t2", storage_path=str(tmp_path)))
        result = trainer.fit()
        assert result.error is None
        # rank-0 metrics are the authoritative stream
        assert result.metrics["rank"] == 0
        assert result.metrics["world_size"] == 2

    def test_checkpoint_roundtrip(self, ray_shared, tmp_path):
        def loop(config):
            from ray_tpu import train

            ckpt = train.get_checkpoint()
            start = ckpt.to_dict()["step"] + 1 if ckpt else 0
            for i in range(start, start + 2):
                train.report({"step": i},
                             checkpoint=Checkpoint.from_dict({"step": i}))

        trainer = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="ck", storage_path=str(tmp_path)))
        r1 = trainer.fit()
        assert r1.metrics["step"] == 1
        assert r1.checkpoint is not None

        trainer2 = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="ck2", storage_path=str(tmp_path)),
            resume_from_checkpoint=r1.checkpoint)
        r2 = trainer2.fit()
        assert r2.metrics["step"] == 3   # resumed from step 1

    def test_num_to_keep(self, ray_shared, tmp_path):
        def loop(config):
            from ray_tpu import train

            for i in range(4):
                train.report({"step": i},
                             checkpoint=Checkpoint.from_dict({"step": i}))

        trainer = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                name="keep", storage_path=str(tmp_path),
                checkpoint_config=CheckpointConfig(num_to_keep=2)))
        r = trainer.fit()
        ckpt_dirs = [d for d in os.listdir(r.path)
                     if d.startswith("checkpoint_")]
        assert len(ckpt_dirs) == 2
        assert r.checkpoint.to_dict()["step"] == 3

    def test_train_fn_error_surfaces(self, ray_shared, tmp_path):
        def bad_loop(config):
            raise ValueError("boom at step 0")

        trainer = JaxTrainer(
            bad_loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="err", storage_path=str(tmp_path)))
        result = trainer.fit()
        assert result.error is not None
        assert "boom at step 0" in str(result.error)

    def test_stop_criteria(self, ray_shared, tmp_path):
        def loop(config):
            from ray_tpu import train

            for i in range(100):
                train.report({"step": i})

        trainer = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="stop", storage_path=str(tmp_path),
                                 stop={"step": 5}))
        result = trainer.fit()
        assert result.error is None
        assert result.metrics["step"] < 100

    def test_jax_train_step_in_worker(self, ray_shared, tmp_path):
        """End-to-end slice: sharded llama train step inside a train worker
        (the §7-step-5 'one model' milestone, scaled to the test box)."""
        def loop(config):
            import jax

            from ray_tpu._private.config import ensure_cpu_devices

            ensure_cpu_devices(8)
            import jax.numpy as jnp

            from ray_tpu import train
            from ray_tpu.models import llama
            from ray_tpu.parallel.mesh import MeshConfig, create_mesh
            from ray_tpu.train import step as ts

            cfg = llama.LlamaConfig(
                vocab_size=128, dim=64, n_layers=1, n_heads=2, n_kv_heads=1,
                ffn_dim=128, max_seq=64, remat=False)
            # Reused workers may have initialized jax with 1 device already;
            # shard over whatever is available.
            n = len(jax.devices())
            mesh = create_mesh(MeshConfig(data=-1, fsdp=2 if n % 2 == 0 else 1),
                               devices=jax.devices())
            opt = ts.default_optimizer(total_steps=10)
            state = ts.sharded_init(jax.random.PRNGKey(0), cfg, opt, mesh)
            fn = ts.sharded_train_step(cfg, opt, mesh)
            tok = jnp.zeros((8, 32), jnp.int32)   # divisible by data×fsdp
            batch = {"inputs": tok, "targets": tok}
            with jax.set_mesh(mesh):
                for i in range(2):
                    state, m = fn(state, batch)
                    train.report({"loss": float(m["loss"]), "step": i})
            train.report(
                {"final": True},
                checkpoint=Checkpoint.from_pytree(
                    {"step": state.step}, use_orbax=False))

        trainer = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="e2e", storage_path=str(tmp_path)))
        result = trainer.fit()
        assert result.error is None
        assert result.checkpoint is not None
        restored = result.checkpoint.to_pytree()
        assert int(restored["step"]) == 2


class TestAsyncCheckpointWriter:
    """ISSUE-5 satellite: from_pytree_async offloads serialization+write
    to a background thread; wait()/register()/pickling are the explicit
    flush points."""

    def test_async_write_waits_and_round_trips(self, tmp_path):
        import numpy as np

        tree = {"w": np.arange(2048, dtype=np.float32), "step": 7}
        ckpt = Checkpoint.from_pytree_async(tree, use_orbax=False)
        assert ckpt.wait() is ckpt
        restored = ckpt.to_pytree()
        assert int(restored["step"]) == 7
        np.testing.assert_array_equal(restored["w"], tree["w"])

    def test_register_flushes_pending_write(self, tmp_path):
        import numpy as np

        from ray_tpu.train.checkpoint import CheckpointManager

        mgr = CheckpointManager(str(tmp_path))
        ckpt = Checkpoint.from_pytree_async(
            {"w": np.ones(1 << 18, np.float32)}, use_orbax=False)
        stored = mgr.register(ckpt, {"loss": 1.0})
        # register() waited: the copied directory is complete.
        restored = stored.to_pytree()
        assert float(restored["w"][0]) == 1.0

    def test_pickle_is_a_flush_point(self, tmp_path):
        import pickle

        import numpy as np

        ckpt = Checkpoint.from_pytree_async(
            {"w": np.full(1 << 18, 3.0, np.float32)}, use_orbax=False)
        clone = pickle.loads(pickle.dumps(ckpt))
        # The reconstructed handle reads a complete directory.
        assert float(clone.to_pytree()["w"][0]) == 3.0

    def test_flush_pending_writes(self):
        import numpy as np

        from ray_tpu.train.checkpoint import flush_pending_writes

        Checkpoint.from_pytree_async({"w": np.zeros(16)},
                                     use_orbax=False)
        flush_pending_writes()
        # Idempotent with nothing in flight.
        assert flush_pending_writes() == 0


class TestHostCollective:
    """ISSUE-5 tentpole train wiring: the executor forms a host-DCN
    collective group over the gang and host_allreduce_async overlaps
    the sync with the next step's work."""

    def test_host_allreduce_async_in_train_loop(self, ray_shared,
                                                tmp_path):
        def loop(config):
            import numpy as np

            from ray_tpu import train

            ctx = train.get_context()
            work = train.host_allreduce_async(
                np.full(8, float(ctx.get_world_rank() + 1), np.float32))
            # ... next step's input pipeline would run here ...
            summed = work.wait(60)
            train.report({"sum": float(summed[0]),
                          "rank": ctx.get_world_rank()})

        trainer = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=2,
                                         num_cpus_per_worker=0.5),
            run_config=RunConfig(name="hostcol",
                                 storage_path=str(tmp_path)))
        result = trainer.fit()
        assert result.error is None
        assert result.metrics["sum"] == 3.0      # ranks 1+2

    def test_host_allreduce_single_rank_identity(self, ray_shared,
                                                 tmp_path):
        def loop(config):
            import numpy as np

            from ray_tpu import train

            out = train.host_allreduce(np.full(4, 5.0, np.float32))
            train.report({"v": float(out[0])})

        trainer = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="hostcol1",
                                 storage_path=str(tmp_path)))
        result = trainer.fit()
        assert result.error is None
        assert result.metrics["v"] == 5.0


class TestMultiHostJax:
    def test_jax_distributed_global_mesh_psum(self, ray_shared, tmp_path):
        """Two train workers = two jax processes forming ONE global mesh
        via the JaxBackend rendezvous; a cross-process collective
        (global-array sum) produces the allreduced value on every rank
        (the multi-host path of SURVEY §7 step 5, testable on CPU)."""
        def loop(config):
            import jax
            import jax.numpy as jnp
            import numpy as np
            from jax.sharding import Mesh, NamedSharding
            from jax.sharding import PartitionSpec as P

            from ray_tpu.train import get_context, report

            assert jax.process_count() == 2
            assert jax.device_count() >= 2
            rank = get_context().get_world_rank()
            mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
            arr = jax.make_array_from_callback(
                (2,), NamedSharding(mesh, P("data")),
                lambda idx: np.array([float(rank + 1)]))
            total = float(jax.jit(jnp.sum)(arr))   # cross-process reduce
            report({"total": total, "rank": rank})

        trainer = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=2,
                                         num_cpus_per_worker=0.5),
            run_config=RunConfig(name="mh", storage_path=str(tmp_path)))
        result = trainer.fit()
        assert result.error is None
        assert result.metrics["total"] == 3.0     # 1 (rank0) + 2 (rank1)

    def test_8b_recipe_real_step_two_processes(self, ray_shared,
                                               tmp_path):
        """The llama3-8b RECIPE path — dp x fsdp x tp mesh, logical-axis
        shardings, sharded_init / sharded_train_step — executed for REAL
        across two jax processes (4 local CPU devices each, one global
        8-device mesh via the JaxBackend rendezvous), tiny dims, with
        numerics asserted: loss decreases over steps.  This is the
        multi-host half of SURVEY §7 step 5 that the abstract 8B trace
        cannot cover."""
        def loop(config):
            import jax

            # Before any device query in this worker process.
            from ray_tpu._private.config import ensure_cpu_devices

            ensure_cpu_devices(4)
            import jax.numpy as jnp
            import numpy as np

            from ray_tpu.models import llama
            from ray_tpu.parallel.mesh import MeshConfig, create_mesh
            from ray_tpu.train import report
            from ray_tpu.train import step as train_step

            assert jax.process_count() == 2
            assert len(jax.devices()) == 8, jax.devices()
            # The 8B recipe's axes at dryrun scale: dp x fsdp x tp.
            mesh = create_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
            cfg = llama.LlamaConfig(
                vocab_size=256, dim=128, n_layers=2, n_heads=4,
                n_kv_heads=2, ffn_dim=256, max_seq=64, remat=True)
            opt = train_step.default_optimizer(lr=1e-2, warmup=1,
                                               total_steps=20)
            state = train_step.sharded_init(jax.random.PRNGKey(0), cfg,
                                            opt, mesh)
            step = train_step.sharded_train_step(cfg, opt, mesh)
            b_sh = train_step.batch_shardings(mesh)
            rng = np.random.RandomState(1)
            toks = rng.randint(0, 256, (4, 64)).astype(np.int32)
            batch = {
                "inputs": jax.make_array_from_callback(
                    (4, 64), b_sh, lambda idx: toks[idx]),
                "targets": jax.make_array_from_callback(
                    (4, 64), b_sh, lambda idx: toks[idx]),
            }
            losses = []
            with jax.set_mesh(mesh):
                for _ in range(3):
                    state, m = step(state, batch)
                    losses.append(float(m["loss"]))
            report({"losses": losses})

        trainer = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=2,
                                         num_cpus_per_worker=0.5),
            run_config=RunConfig(name="recipe8b",
                                 storage_path=str(tmp_path)))
        result = trainer.fit()
        assert result.error is None, result.error
        losses = result.metrics["losses"]
        assert losses[-1] < losses[0], losses


def _resumable_loop(config):
    """Checkpoint-per-step loop whose rank 1 hard-kills itself ONCE at the
    configured step (marker file arms the kill exactly one incarnation)."""
    import os
    import signal
    import time

    from ray_tpu import train

    ctx = train.get_context()
    ckpt = train.get_checkpoint()
    start = ckpt.to_dict()["step"] + 1 if ckpt else 0
    for i in range(start, config["total_steps"]):
        marker = config.get("kill_marker")
        if (marker and i == config.get("kill_at", -1)
                and ctx.get_world_rank() == 1
                and not os.path.exists(marker)):
            open(marker, "w").close()
            os.kill(os.getpid(), signal.SIGKILL)
        if config.get("progress_dir"):
            with open(os.path.join(config["progress_dir"],
                                   f"rank{ctx.get_world_rank()}"),
                      "w") as f:
                f.write(f"{ctx.get_node_id()} {i}")
        train.report({"step": i, "start": start,
                      "rank": ctx.get_world_rank()},
                     checkpoint=Checkpoint.from_dict({"step": i}))
        if config.get("step_sleep_s"):
            time.sleep(config["step_sleep_s"])


class TestTrainElasticity:
    """Chaos tests for the LEGACY group-restart path (ray:
    backend_executor.py:740-756 _restart + max_failures): the round-4
    verdict's most under-tested claim — recovery is implemented but no
    test killed anything mid-fit().  Pinned to RAY_TPU_ELASTIC=0 since
    round 12: the elastic membership-epoch path (default) turns these
    kills into shrink-and-continue (tests/test_train_elastic.py); these
    tests keep the restart loop honest for the kill-switch A/B."""

    def test_worker_sigkill_restarts_and_resumes(self, ray_shared,
                                                 tmp_path, monkeypatch):
        """SIGKILL rank 1 mid-run: the group restarts within
        max_failures and the retry resumes from the NEWEST checkpoint
        (not the run's original resume point)."""
        monkeypatch.setenv("RAY_TPU_ELASTIC", "0")
        marker = tmp_path / "killed_once"
        # step_sleep paces the loop to the executor's poll cadence so the
        # checkpointed rounds 0-2 EMIT before the kill; an instant loop
        # dies with its reports still queued worker-side and the retry
        # legitimately restarts from scratch.
        trainer = JaxTrainer(
            _resumable_loop,
            train_loop_config={"total_steps": 6, "kill_at": 3,
                               "step_sleep_s": 0.4,
                               "kill_marker": str(marker)},
            scaling_config=ScalingConfig(num_workers=2,
                                         num_cpus_per_worker=0.5),
            run_config=RunConfig(
                name="chaos_worker_kill", storage_path=str(tmp_path),
                failure_config=FailureConfig(max_failures=1)))
        result = trainer.fit()
        assert marker.exists(), "kill never armed - test is vacuous"
        assert result.error is None, result.error
        assert result.metrics["step"] == 5
        # The retry resumed from the newest full-round checkpoint: some
        # report in the history carries start > 0.  A replay-from-zero
        # (the pre-round-5 behavior: _restart reused the ORIGINAL
        # resume_checkpoint) would report start == 0 everywhere.
        starts = {m.get("start") for m in result.metrics_history}
        assert any(s > 0 for s in starts if s is not None), starts

    def test_max_failures_exhausted_surfaces_error(self, ray_shared,
                                                   tmp_path, monkeypatch):
        """Unconditional rank-1 suicide: restarts stop after
        max_failures and the failure surfaces in Result.error."""
        monkeypatch.setenv("RAY_TPU_ELASTIC", "0")

        def always_dies(config):
            import os
            import signal

            from ray_tpu import train

            ctx = train.get_context()
            if ctx.get_world_rank() == 1:
                os.kill(os.getpid(), signal.SIGKILL)
            train.report({"step": 0})

        trainer = JaxTrainer(
            always_dies,
            scaling_config=ScalingConfig(num_workers=2,
                                         num_cpus_per_worker=0.5),
            run_config=RunConfig(
                name="chaos_exhaust", storage_path=str(tmp_path),
                failure_config=FailureConfig(max_failures=1)))
        result = trainer.fit()
        assert result.error is not None
        msg = str(result.error)
        assert "died" in msg or "worker" in msg, msg


def test_node_agent_kill_mid_fit(tmp_path, monkeypatch):
    """Kill the NODE AGENT hosting the train workers mid-fit(): worker
    death propagates, the group restarts on surviving nodes, and the run
    completes from the latest checkpoint (the reference's recovery unit
    — lose a host, keep the run).  Legacy-path pin, see class note."""
    import threading
    import time

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    monkeypatch.setenv("RAY_TPU_ELASTIC", "0")

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
        trainer = JaxTrainer(
            _resumable_loop,
            train_loop_config={"total_steps": 8, "step_sleep_s": 0.3,
                               "progress_dir": str(progress)},
            scaling_config=ScalingConfig(num_workers=2,
                                         num_cpus_per_worker=0.5),
            run_config=RunConfig(
                name="chaos_node_kill", storage_path=str(tmp_path),
                failure_config=FailureConfig(max_failures=2)))
        box = {}

        def run_fit():
            box["result"] = trainer.fit()

        t = threading.Thread(target=run_fit, daemon=True)
        t.start()
        # Wait for both ranks to make progress, then kill the agent of
        # whichever NON-HEAD node hosts rank 0.
        deadline = time.monotonic() + 120
        victim = None
        while time.monotonic() < deadline and victim is None:
            f = progress / "rank0"
            if f.exists():
                node_id, step = f.read_text().split()
                if int(step) >= 1:
                    victim = next((n for n in (n1, n2)
                                   if n["node_id"] == node_id), None)
            time.sleep(0.2)
        assert victim is not None, "rank0 never reported progress"
        cluster.kill_node(victim)
        t.join(timeout=240)
        assert not t.is_alive(), "fit() wedged after node kill"
        result = box["result"]
        assert result.error is None, result.error
        assert result.metrics["step"] == 7
        starts = {m.get("start") for m in result.metrics_history}
        assert any(s > 0 for s in starts if s is not None), starts
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
