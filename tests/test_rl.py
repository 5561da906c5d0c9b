"""RL library tests: env, runners, PPO learning, DQN machinery, Tune interop.

Mirrors ray: rllib/**/tests (learning tests assert reward improvement on
CartPole with small budgets — e.g. rllib/algorithms/ppo/tests/test_ppo.py).
"""
import numpy as np


def test_cartpole_env_dynamics():
    from ray_tpu.rl.env import CartPole

    env = CartPole(seed=0)
    obs = env.reset()
    assert obs.shape == (4,)
    total = 0.0
    done = False
    steps = 0
    while not done and steps < 600:
        obs, r, term, trunc = env.step(steps % 2)
        total += r
        done = term or trunc
        steps += 1
    assert 1 <= steps <= 500


def test_env_runner_sampling(ray_shared):
    import jax

    from ray_tpu.rl import models
    from ray_tpu.rl.env_runner import EnvRunnerGroup

    params = models.to_numpy(
        models.policy_value_init(jax.random.PRNGKey(0), 4, 2, hidden=16))
    group = EnvRunnerGroup("CartPole-v1", num_env_runners=2)
    batches = group.sample(params, 64)
    assert len(batches) == 2
    for b in batches:
        assert b["obs"].shape == (64, 4)
        assert "advantages" in b and "value_targets" in b
        assert abs(float(b["advantages"].mean())) < 0.2   # normalized
    group.stop()


def test_ppo_learns_cartpole(ray_shared):
    from ray_tpu.rl import PPOConfig

    config = (PPOConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2)
              .training(lr=1e-3, train_batch_size=1024, num_sgd_iter=6,
                        minibatch_size=256, entropy_coeff=0.01)
              .debugging(seed=0))
    algo = config.build()
    first = None
    best = -1.0
    for i in range(12):
        result = algo.step()
        ret = result["episode_return_mean"]
        if first is None and ret == ret:
            first = ret
        if ret == ret:
            best = max(best, ret)
        if best >= 120.0:
            break
    algo.cleanup()
    assert first is not None, "no episodes completed"
    assert best >= 60.0, (
        f"PPO failed to improve: first={first:.1f} best={best:.1f}")
    assert best > first * 1.2 or best >= 100.0


def test_dqn_machinery(ray_shared):
    from ray_tpu.rl import DQNConfig

    config = (DQNConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=1)
              .training(train_batch_size=128, learning_starts=128,
                        sgd_batch_size=32)
              .debugging(seed=0))
    algo = config.build()
    for _ in range(3):
        result = algo.step()
    # After learning_starts, TD updates happen and epsilon decays.
    assert "learner/td_error" in result or "learner/buffer_size" in result
    assert algo._timesteps >= 3 * 128
    algo.cleanup()


def test_algorithm_checkpoint_roundtrip(ray_shared, tmp_path):
    from ray_tpu.rl import PPOConfig

    algo = (PPOConfig().environment("CartPole-v1")
            .env_runners(num_env_runners=1)
            .training(train_batch_size=128)).build()
    algo.step()
    d = str(tmp_path / "ck")
    import os

    os.makedirs(d, exist_ok=True)
    algo.save_checkpoint(d)
    ts = algo._timesteps
    algo2 = (PPOConfig().environment("CartPole-v1")
             .env_runners(num_env_runners=1)
             .training(train_batch_size=128)).build()
    algo2.load_checkpoint(d)
    assert algo2._timesteps == ts
    p1 = algo._params_np["pi"]["w0"]
    p2 = algo2._params_np["pi"]["w0"]
    np.testing.assert_allclose(p1, p2)
    algo.cleanup()
    algo2.cleanup()


def test_impala_vtrace_learns(ray_shared):
    from ray_tpu.rl import IMPALAConfig

    config = (IMPALAConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2)
              .training(lr=2e-3, train_batch_size=512, entropy_coeff=0.01)
              .debugging(seed=0))
    algo = config.build()
    first, best = None, -1.0
    for _ in range(10):
        result = algo.step()
        ret = result["episode_return_mean"]
        if first is None and ret == ret:
            first = ret
        if ret == ret:
            best = max(best, ret)
        assert "learner/mean_rho" in result
        if best >= 100.0:
            break
    algo.cleanup()
    assert first is not None, "no episodes completed"
    assert best >= 40.0, f"IMPALA failed to improve: best={best:.1f}"


def test_sac_machinery(ray_shared):
    from ray_tpu.rl import SACConfig

    config = (SACConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=1)
              .training(train_batch_size=128, learning_starts=128,
                        sgd_batch_size=32, updates_per_step=2)
              .debugging(seed=0))
    algo = config.build()
    result = {}
    for _ in range(3):
        result = algo.step()
    assert "learner/critic_loss" in result or "learner/buffer_size" in result
    # Temperature must stay positive and finite.
    if "learner/alpha" in result:
        assert 0.0 < result["learner/alpha"] < 100.0
    assert algo._timesteps >= 3 * 128
    algo.cleanup()


def test_bc_offline_cloning(ray_shared):
    """BC clones an expert policy from logged (obs, action) pairs without
    env interaction during updates (ray: rllib/algorithms/bc over
    offline data)."""
    import numpy as np

    from ray_tpu.rl import BCConfig
    from ray_tpu.rl.env import CartPole

    # Expert: push the cart toward balancing (simple angle policy).
    env = CartPole(seed=3)
    obs_l, act_l = [], []
    obs = env.reset()
    for _ in range(600):
        a = int(obs[2] + 0.3 * obs[3] > 0)    # lean-direction expert
        obs_l.append(obs.copy())
        act_l.append(a)
        obs, _, term, trunc = env.step(a)
        if term or trunc:
            obs = env.reset()
    data = {"obs": np.array(obs_l, np.float32),
            "actions": np.array(act_l, np.int64)}

    config = (BCConfig()
              .environment("CartPole-v1")
              .training(lr=2e-3, num_sgd_iter=8, minibatch_size=64)
              .offline(offline_data=data)
              .debugging(seed=0))
    algo = config.build()
    result = {}
    for _ in range(6):
        result = algo.step()
    acc = result.get("learner/action_accuracy", 0.0)
    algo.cleanup()
    assert acc > 0.9, f"BC failed to clone the expert: acc={acc:.2f}"
