"""Online-RLHF chaos suite (marker `chaos`): the loop survives dying
rollout actors and a dying learner.

- `rl.rollout_step=nth:1+crash` on a rollout actor: its in-flight GRPO
  group is lost mid-generation; the trainer replaces the actor,
  bootstraps it to the current policy over the object plane, and
  REGENERATES the group — training completes every requested update,
  ending at zero leaked arena pins and zero leaked KV blocks.
- `rl.weight_sync=nth:1+crash` on the learner actor: it dies inside
  the broadcast window; parked receivers are drained via
  destroy_collective_group(reason), the learner resumes from the
  newest COMPLETED async checkpoint, the weight-sync group re-forms at
  a fresh epoch, and training continues.

Pattern notes: armable actor classes are defined inside a factory so
cloudpickle ships them BY VALUE (the test_pd_disagg discipline), and
the crash arms use the failpoint `crash` action (SIGKILL — no cleanup
runs in the victim).
"""
import os
import time

import numpy as np
import pytest

import ray_tpu


def _classes():
    """Armable rollout/learner classes, shipped by value."""
    from ray_tpu.rl.rlhf import GRPOLearner
    from ray_tpu.rl.rollout_llm import LLMRolloutWorker

    class ArmableWorker(LLMRolloutWorker):
        def arm(self, site, action):
            import os as _os

            from ray_tpu._private import failpoints as fp

            fp.arm(site, action)
            return _os.getpid()

    class ArmableLearner(GRPOLearner):
        def arm(self, site, action):
            import os as _os

            from ray_tpu._private import failpoints as fp

            fp.arm(site, action)
            return _os.getpid()

    return ArmableWorker, ArmableLearner


@pytest.fixture
def rt():
    if not ray_tpu.is_initialized():
        ray_tpu.init(resources={"CPU": 6})
    yield ray_tpu


@pytest.fixture(scope="module")
def small():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=128, max_seq=256, remat=False, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    return cfg, params


def _trainer(small, tmp_path, **kw):
    from ray_tpu.rl.rlhf import RLHFConfig, RLHFTrainer

    cfg, params = small
    worker_cls, learner_cls = _classes()
    base = dict(model=cfg, params=params, seed=0, n_prompts=4,
                prompt_len=10, group_size=4, prompts_per_step=2,
                max_new_tokens=5, lr=1e-2,
                num_rollout_workers=2, remote_learner=True,
                checkpoint_every=1,
                checkpoint_dir=str(tmp_path / "ckpt"),
                worker_cls=worker_cls, learner_cls=learner_cls,
                engine=dict(max_batch=8, max_len=128, page_size=8,
                            steps_per_sync=3))
    base.update(kw)
    return RLHFTrainer(RLHFConfig(**base))


def _wait_versions(workers, want: list[int],
                   timeout: float = 60.0) -> list[int]:
    """recv_weights returns at STAGING; the engine swap lands between
    sync windows (ms later on an idle engine) — poll stats for
    visibility instead of racing it."""
    deadline = time.monotonic() + timeout
    vs = []
    while time.monotonic() < deadline:
        vs = [ray_tpu.get(w.stats.remote(), timeout=120)
              ["weight_version"] for w in workers]
        if vs == want:
            return vs
        time.sleep(0.2)
    return vs


def _wait_dead(pid: int, timeout: float = 60.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return
        time.sleep(0.2)
    raise AssertionError(f"armed pid {pid} still alive — the "
                         "failpoint never fired")


def test_update_weights_multi_ref_shards(rt, small):
    """The sharded object-plane push: each ref resolves to a disjoint
    top-level slice of the param dict and update_weights merges them
    (non-dict shards rejected)."""
    import jax

    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = small
    eng = LLMEngine(cfg, params, seed=0, paged=True, max_batch=2,
                    max_len=64, page_size=8)
    eng.start()
    try:
        new = jax.tree.map(np.asarray,
                           llama.init_params(jax.random.PRNGKey(5),
                                             cfg))
        refs = [ray_tpu.put({k: new[k]}) for k in new]
        v = eng.update_weights(refs, 4)
        assert v == 4
        deadline = time.monotonic() + 30
        while eng.stats()["weight_version"] < 4:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        np.testing.assert_array_equal(
            np.asarray(eng.params["final_norm"]),
            np.asarray(new["final_norm"]))
        with pytest.raises(ValueError, match="dict shards"):
            eng.update_weights([ray_tpu.put({"embed": new["embed"]}),
                                ray_tpu.put([1, 2])])
    finally:
        eng.stop()


def test_actor_workers_with_in_driver_learner(rt, small, tmp_path):
    """The third topology: actor rollout workers + an IN-DRIVER learner
    — the driver itself is rank 0 of the broadcast group (receivers
    dispatched first; rank 0's tree_broadcast blocks until every child
    consumed its chunks)."""
    tr = _trainer(small, tmp_path, remote_learner=False,
                  checkpoint_every=0)
    try:
        ms = [tr.step() for _ in range(2)]
        assert [m["version"] for m in ms] == [1, 2]
        assert tr.stats()["worker_versions"] == [2, 2]
        vs = _wait_versions(tr.workers, [2, 2])
        assert vs == [2, 2], vs
    finally:
        tr.shutdown()


@pytest.mark.chaos
def test_rollout_actor_crash_regenerates_group(rt, small, tmp_path):
    """A rollout actor SIGKILLed with a group in flight: the step still
    completes (group regenerated on the replacement, which the trainer
    bootstrapped to the current policy), survivors keep their prefix
    caches, and nothing leaks."""
    from test_chaos_adversarial import _arena_pins_settle

    tr = _trainer(small, tmp_path)
    try:
        m = tr.step()
        assert m["version"] == 1
        pid = ray_tpu.get(tr.workers[0].arm.remote(
            "rl.rollout_step", "nth:1+crash"), timeout=120)
        m = tr.step()
        assert m["version"] == 2
        assert tr.rollout_regens >= 1
        _wait_dead(pid)
        # The replacement really carries the current policy (it booted
        # at version 0 from the seed).
        vs = _wait_versions(tr.workers, [2, 2])
        assert vs == [2, 2], vs
        # One more clean round on the healed fleet.
        m = tr.step()
        assert m["version"] == 3 and np.isfinite(m["loss"])
        for w in tr.workers:
            assert ray_tpu.get(w.kv_check.remote(), timeout=120)["ok"]
        stats = _arena_pins_settle()
        assert not stats.get("swept_dead_pins", 0), stats
    finally:
        tr.shutdown()


@pytest.mark.chaos
def test_learner_crash_resumes_from_newest_checkpoint(rt, small,
                                                      tmp_path):
    """The learner SIGKILLed inside the weight-sync window: recovery
    rebuilds it from the newest COMPLETED async checkpoint, re-forms
    the broadcast group at a fresh epoch, re-syncs the restored
    policy, and training continues — counting one learner restart and
    leaking nothing."""
    from test_chaos_adversarial import _arena_pins_settle

    tr = _trainer(small, tmp_path)
    try:
        tr.step()
        tr.step()
        assert tr.version == 2
        # Make the v2 save durable so recovery has a NEWEST checkpoint.
        newest = tr.flush_checkpoints()
        assert newest is not None and newest[0] == 2
        epoch_before = tr.stats()["epoch"]
        pid = ray_tpu.get(tr.learner.arm.remote(
            "rl.weight_sync", "nth:1+crash"), timeout=120)
        m = tr.step()            # update v3 → sync crashes → resume v2
        _wait_dead(pid)
        assert tr.learner_restarts == 1
        st = tr.stats()
        # Resumed FROM v2: the crashed sync's version was re-derived
        # from the restored checkpoint and re-broadcast on a fresh
        # rendezvous epoch.
        assert st["version"] == 2
        assert st["worker_versions"] == [2, 2]
        assert st["epoch"] > epoch_before
        assert m["version"] == 3          # the pre-crash update itself
        # Training continues from the restored state.
        m = tr.step()
        assert m["version"] == 3 and np.isfinite(m["loss"])
        assert tr.stats()["worker_versions"] == [3, 3]
        for w in tr.workers:
            assert ray_tpu.get(w.kv_check.remote(), timeout=120)["ok"]
        stats = _arena_pins_settle()
        assert not stats.get("swept_dead_pins", 0), stats
    finally:
        tr.shutdown()
