"""RL breadth: CQL offline learning + multi-agent PPO (reference:
rllib/algorithms/cql + rllib/env/multi_agent_env_runner.py).
Seeded learning tests per the repo's test discipline.
"""
import numpy as np
import pytest


def _expert_transitions(n_steps: int, seed: int = 3) -> dict:
    """Logged transitions from the lean-direction expert (+ light
    exploration noise so Q-learning sees off-policy actions)."""
    from ray_tpu.rl.env import CartPole

    rng = np.random.default_rng(seed)
    env = CartPole(seed=seed)
    cols = {k: [] for k in ("obs", "actions", "rewards", "next_obs",
                            "dones")}
    obs = env.reset()
    for _ in range(n_steps):
        if rng.random() < 0.2:
            a = int(rng.integers(2))
        else:
            a = int(obs[2] + 0.3 * obs[3] > 0)
        nxt, r, term, trunc = env.step(a)
        cols["obs"].append(obs.copy())
        cols["actions"].append(a)
        cols["rewards"].append(r)
        cols["next_obs"].append(nxt.copy())
        cols["dones"].append(float(term))
        obs = env.reset() if (term or trunc) else nxt
    return {
        "obs": np.array(cols["obs"], np.float32),
        "actions": np.array(cols["actions"], np.int64),
        "rewards": np.array(cols["rewards"], np.float32),
        "next_obs": np.array(cols["next_obs"], np.float32),
        "dones": np.array(cols["dones"], np.float32),
    }


def test_cql_offline_learns(ray_shared):
    """CQL learns a usable policy from logged transitions only: greedy
    eval return beats the random-policy baseline (~20 on CartPole)."""
    from ray_tpu.rl import CQLConfig

    config = (CQLConfig()
              .environment("CartPole-v1")
              .training(lr=2e-3, sgd_batch_size=128, cql_alpha=0.5,
                        updates_per_step=24)
              .offline(offline_data=_expert_transitions(2000))
              .debugging(seed=0))
    algo = config.build()
    result = {}
    for _ in range(10):
        result = algo.step()
    ret = result["episode_return_mean"]
    assert result["learner/cql_penalty"] == result["learner/cql_penalty"]
    algo.cleanup()
    assert ret > 45, f"CQL offline policy too weak: return={ret:.1f}"


def test_multicartpole_env_protocol(ray_shared):
    from ray_tpu.rl import MultiCartPole

    env = MultiCartPole(seed=0, num_agents=3)
    obs = env.reset()
    assert set(obs) == {"agent_0", "agent_1", "agent_2"}
    obs2, rew, term, trunc, infos = env.step({a: 0 for a in env.agents})
    assert set(rew) == set(obs2) == set(obs) == set(infos)
    assert all(r == 1.0 for r in rew.values())
    # Run an agent to termination: the final obs must be reported via
    # infos while obs carries the fresh episode's reset observation.
    for _ in range(600):
        obs2, rew, term, trunc, infos = env.step(
            {a: 0 for a in env.agents})
        ended = [a for a in env.agents if term[a] or trunc[a]]
        if ended:
            a = ended[0]
            assert "final_obs" in infos[a]
            assert not np.allclose(infos[a]["final_obs"], obs2[a])
            break
    else:
        raise AssertionError("no episode ever ended")


def test_multi_agent_ppo_learns(ray_shared):
    """Shared-policy multi-agent PPO on MultiCartPole: pooled episode
    return improves well past the random baseline (~20)."""
    from ray_tpu.rl import MultiAgentPPOConfig

    config = (MultiAgentPPOConfig()
              .environment("MultiCartPole")
              .env_runners(num_env_runners=2)
              .training(lr=3e-3, train_batch_size=512, num_sgd_iter=6,
                        minibatch_size=128)
              .debugging(seed=0))
    algo = config.build()
    best = 0.0
    for _ in range(12):
        result = algo.step()
        ret = result["episode_return_mean"]
        if ret == ret:                      # skip NaN (no episodes yet)
            best = max(best, ret)
        if best > 60:
            break
    algo.cleanup()
    assert best > 60, f"multi-agent PPO failed to learn: best={best:.1f}"


def test_multi_agent_distinct_policies(ray_shared):
    """Two policies, one per agent: batches route to the right learner
    and both policies update."""
    from ray_tpu.rl import MultiAgentPPOConfig

    config = (MultiAgentPPOConfig()
              .environment("MultiCartPole")
              .env_runners(num_env_runners=1)
              .multi_agent(policies=["p0", "p1"],
                           policy_mapping={"agent_0": "p0",
                                           "agent_1": "p1"})
              .training(train_batch_size=256, num_sgd_iter=2,
                        minibatch_size=64)
              .debugging(seed=0))
    algo = config.build()
    before = {pid: algo._params_np[pid]["pi"]["w0"].copy()
              for pid in ("p0", "p1")}
    result = algo.step()
    after = algo._params_np
    for pid in ("p0", "p1"):
        assert any(f"{pid}/" in k for k in result), result.keys()
        assert not np.allclose(before[pid], after[pid]["pi"]["w0"]), \
            f"policy {pid} never updated"
    algo.cleanup()


def test_appo_vtrace_clip_learns(ray_shared):
    """APPO (rllib: algorithms/appo/appo.py:277): clipped surrogate on
    V-trace advantages + target-net KL.  Seeded threshold like IMPALA's."""
    from ray_tpu.rl import APPOConfig

    config = (APPOConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2)
              .training(lr=2e-3, train_batch_size=512,
                        entropy_coeff=0.01, clip_param=0.4,
                        kl_coeff=0.2, tau=0.05)
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
        assert "learner/mean_kl" in result
        if best >= 100.0:
            break
    algo.cleanup()
    assert first is not None, "no episodes completed"
    assert best >= 40.0, f"APPO failed to improve: best={best:.1f}"


def test_connector_pipeline_surgery(ray_shared):
    """ConnectorV2 pipelines (rllib: connectors/connector_v2.py:29):
    composition, list surgery, and the shared env->learner pieces."""
    from ray_tpu.rl.connectors import (ConcatFragments, ConnectorCtx,
                                       ConnectorPipelineV2, FnConnector,
                                       RecordEpisodeMetrics,
                                       StackFragments)

    frags = [
        {"obs": np.ones((4, 3), np.float32),
         "episode_returns": np.array([10.0], np.float32)},
        {"obs": np.zeros((4, 3), np.float32),
         "episode_returns": np.array([], np.float32)},
    ]

    class Sink:
        _episode_returns = []
        _timesteps = 0

    ctx = ConnectorCtx(Sink)
    pipe = ConnectorPipelineV2(RecordEpisodeMetrics(), ConcatFragments())
    out = pipe([dict(f) for f in frags], ctx)
    assert out["obs"].shape == (8, 3)
    assert Sink._episode_returns == [10.0] and Sink._timesteps == 8

    # Stacked layout for the V-trace family.
    pipe2 = ConnectorPipelineV2(StackFragments())
    stacked = pipe2([{"obs": f["obs"]} for f in frags], ConnectorCtx())
    assert stacked["obs"].shape == (2, 4, 3)

    # Surgery: insert a normalizer before concat, remove it again.
    norm = FnConnector(lambda d, c: d, name="Norm")
    pipe.insert_before("ConcatFragments", norm)
    assert [p.name for p in pipe.pieces] == [
        "RecordEpisodeMetrics", "Norm", "ConcatFragments"]
    pipe.remove("Norm").append(norm).prepend(
        FnConnector(lambda d, c: d, name="First"))
    assert pipe.pieces[0].name == "First"
    assert pipe.pieces[-1].name == "Norm"
    with pytest.raises(ValueError):
        pipe.insert_after("Missing", norm)


def test_marwil_offline_learns(ray_shared):
    """MARWIL (rllib: algorithms/marwil/marwil.py): advantage-weighted
    cloning beats the random baseline from logged transitions only, and
    the exp-weights actually spread (beta>0 is not plain BC)."""
    from ray_tpu.rl import MARWILConfig

    config = (MARWILConfig()
              .environment("CartPole-v1")
              .training(lr=2e-3, beta=1.0, num_sgd_iter=8,
                        minibatch_size=256)
              .offline(offline_data=_expert_transitions(2000))
              .debugging(seed=0))
    algo = config.build()
    result = {}
    for _ in range(8):
        result = algo.step()
    ret = result["episode_return_mean"]
    assert result["learner/mean_weight"] > 0
    assert result["learner/action_accuracy"] > 0.7
    algo.cleanup()
    assert ret > 45, f"MARWIL offline policy too weak: return={ret:.1f}"


def test_marwil_beta_zero_is_bc(ray_shared):
    """beta=0 collapses the weight to 1: loss equals plain BC's NLL."""
    import jax.numpy as jnp

    from ray_tpu.rl.bc import BC
    from ray_tpu.rl.marwil import MARWIL, discounted_returns

    data = _expert_transitions(256)
    returns = discounted_returns(data["rewards"], data["dones"], 0.99)
    batch = {"obs": jnp.asarray(data["obs"]),
             "actions": jnp.asarray(data["actions"]),
             "returns": jnp.asarray(returns)}
    import jax

    from ray_tpu.rl import models

    params = models.policy_value_init(jax.random.PRNGKey(0), 4, 2)
    cfg = {"beta": 0.0, "vf_coeff": 0.0}
    m_loss, m_aux = MARWIL.loss_builder(cfg)(params, batch)
    b_loss, _ = BC.loss_builder({})(params, batch)
    assert abs(float(m_loss) - float(b_loss)) < 1e-5
    assert abs(float(m_aux["mean_weight"]) - 1.0) < 1e-6


def test_dreamerv3_machinery(ray_shared):
    """DreamerV3 (rllib: algorithms/dreamerv3): RSSM world model +
    imagination-trained actor-critic.  Machinery test in the style of
    SAC/DQN's: the world model demonstrably learns (reconstruction +
    reward losses drop), imagination losses stay finite, episodes
    complete under the learned policy."""
    from ray_tpu.rl import DreamerV3Config

    config = (DreamerV3Config()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=1)
              .training(train_batch_size=256, updates_per_step=3)
              .debugging(seed=0))
    algo = config.build()
    first, last = None, None
    for _ in range(6):
        m = algo.step()
        wm = m.get("learner/wm_loss")
        if wm is not None and wm == wm:
            if first is None:
                first = wm
            last = wm
            for key in ("learner/actor_loss", "learner/critic_loss",
                        "learner/entropy"):
                assert m[key] == m[key], f"{key} is NaN"
    algo.cleanup()
    assert first is not None, "world model never trained"
    assert last < first, f"world-model loss did not drop: {first}->{last}"
    assert len(algo._episode_returns) > 0, "no episodes completed"
