"""Online GRPO-style RLHF: serve-engine rollouts → TPU learner → live
weight broadcast (ROADMAP item 5, the scenario-diversity flagship — one
workload exercising serve, rl, the collectives, and the object plane).

The loop
--------
1. **Rollout** — `LLMRolloutWorker`s (rl/rollout_llm.py) generate K
   completions per prompt through the paged-KV serve engine; the radix
   prefix cache makes a GRPO group cost ~one prompt prefill.
   Trajectories (token ids, behavior logprobs, rewards) come back as
   object-plane refs the trainer hands straight to the learner.
2. **Update** — `GRPOLearner` computes group-relative advantages
   (reward standardized within each K-completion group — no value
   network) and one clipped-surrogate policy update, jitted; params
   follow the logical-axis sharding rules through the model's own
   constraints, so the same update runs single-device (tests) or
   sharded (a real mesh).  Learner RNG is `fold_in(base, version)` —
   never global numpy state — so runs are bit-reproducible.
3. **Sync** — fresh weights broadcast to every generation engine via
   the ring collectives' `broadcast_pytree` (ONE packed transport) and
   land through `LLMEngine.update_weights`: the engine swaps trees
   BETWEEN decode sync windows, so decode never drains or pauses.
   Staleness is bounded: generation never lags the learner by more
   than `max_weight_lag` versions (the trainer forces a sync first).

Failure model (chaos-tested, tests/test_rlhf_chaos.py)
------------------------------------------------------
- A dying rollout actor (`rl.rollout_step` crash) loses only its
  in-flight group: the trainer respawns the worker, pushes the current
  weights, and regenerates the group (prefix cache makes the retry
  cheap on survivors).
- A dying learner (`rl.weight_sync` crash) resumes from the newest
  COMPLETED async checkpoint (train.checkpoint's background writer);
  parked broadcast waiters are drained via
  `destroy_collective_group(reason)` and the group re-forms at the
  next epoch, exactly like elastic training's membership epochs.

Kill switches: RAY_TPU_RL_WEIGHT_SYNC=0 freezes the serving policy
(generation keeps running on the last synced weights — the same-run
frozen-policy A/B); per-trainer `sync_every=0` never broadcasts.

Layering: core primitives + public facades only (collective,
serve-engine surface, ray_tpu.failpoints, train.checkpoint) — enforced
by tests/test_layering.py.
"""
from __future__ import annotations

import dataclasses
import os
import time
import uuid
from typing import Any, Callable

import numpy as np

import ray_tpu


@dataclasses.dataclass
class RLHFConfig:
    """Knobs for the online loop (picklable: ships to learner/rollout
    actors whole)."""
    model: Any = "debug"            # llama_configs name or LlamaConfig
    params: Any = None              # explicit init params (tests)
    seed: int = 0
    # Prompt pool (synthetic, seeded): n_prompts of prompt_len tokens.
    n_prompts: int = 8
    prompt_len: int = 12
    # GRPO shape.
    group_size: int = 4
    prompts_per_step: int = 2
    max_new_tokens: int = 8
    temperature: float = 1.0
    eos_id: int | None = None
    # Learner.
    lr: float = 1e-3
    clip: float = 0.2
    kl_coeff: float = 0.0
    adv_eps: float = 1e-4
    minibatch_size: int | None = None
    # Topology: 0 rollout workers = everything in-process (bench/unit
    # tests, bit-deterministic); >0 = ray_tpu actors + collective
    # broadcast.  remote_learner puts the learner in its own actor
    # (required for learner-crash recovery to be survivable).
    num_rollout_workers: int = 0
    remote_learner: bool = False
    # Weight sync: broadcast every `sync_every` updates (0 = never);
    # generation may lag the learner by at most `max_weight_lag`
    # versions before the trainer forces a sync.
    sync_every: int = 1
    max_weight_lag: int = 1
    # Async checkpoints every N updates (0 = off) under checkpoint_dir.
    checkpoint_every: int = 0
    checkpoint_dir: str | None = None
    # Engine kwargs for rollout workers (page_size, kv_pages, ...).
    engine: dict = dataclasses.field(default_factory=dict)
    # Reward: "near_token" | "target_token" | callable(prompt, completion).
    reward: Any = "near_token"
    target_token: int | None = None
    rollout_retries: int = 2        # regen attempts per dead rollout
    # Extension points: custom rollout-worker / learner classes (same
    # constructor contracts as LLMRolloutWorker / GRPOLearner).  Used
    # for custom generation stacks — and by the chaos suites to plant
    # failpoint-arming hooks inside specific actors.
    worker_cls: Any = None
    learner_cls: Any = None
    name: str = "rlhf"


def _to_config(config, overrides) -> RLHFConfig:
    if config is None:
        cfg = RLHFConfig()
    elif isinstance(config, RLHFConfig):
        cfg = dataclasses.replace(config)
    else:
        cfg = RLHFConfig(**dict(config))
    for k, v in (overrides or {}).items():
        if not hasattr(cfg, k):
            raise ValueError(f"unknown RLHF config field {k!r}")
        setattr(cfg, k, v)
    return cfg


def _model_config(cfg: RLHFConfig):
    from ray_tpu.models import llama

    return llama.llama_configs()[cfg.model] \
        if isinstance(cfg.model, str) else cfg.model


def _reward_fn(cfg: RLHFConfig) -> Callable:
    from ray_tpu.rl import rollout_llm

    mcfg = _model_config(cfg)
    target = cfg.target_token if cfg.target_token is not None \
        else mcfg.vocab_size // 3
    if callable(cfg.reward):
        return cfg.reward
    if cfg.reward == "near_token":
        return rollout_llm.near_token_reward(target, mcfg.vocab_size)
    if cfg.reward == "target_token":
        return rollout_llm.target_token_reward(target)
    raise ValueError(
        f"unknown reward {cfg.reward!r}; valid: 'near_token', "
        "'target_token', or a callable(prompt, completion)")


def group_advantages(rewards, group_size: int, eps: float = 1e-4):
    """Group-relative advantages (the GRPO estimator, no value
    network): standardize each K-completion group's rewards to zero
    mean/unit std.  A degenerate group (all rewards equal) contributes
    zero advantage — eps keeps it finite, not resurrected.  Works
    jitted (jnp) and eagerly (numpy)."""
    import jax.numpy as jnp

    r = jnp.asarray(rewards, jnp.float32)
    g = r.reshape(-1, group_size)
    mean = g.mean(axis=1, keepdims=True)
    std = g.std(axis=1, keepdims=True)
    return ((g - mean) / (std + eps)).reshape(-1)


def _concat_trajs(trajs: list[dict]) -> dict:
    """Stack worker trajectory batches into one learner batch, padding
    to the widest T (all are pow2-padded already, so this is a max)."""
    T = max(t["tokens"].shape[1] for t in trajs)

    def padded(key, width):
        out = []
        for t in trajs:
            a = np.asarray(t[key])
            if a.shape[1] < width:
                a = np.pad(a, ((0, 0), (0, width - a.shape[1])))
            out.append(a)
        return np.concatenate(out, axis=0)

    return {
        "tokens": padded("tokens", T).astype(np.int32),
        "logprobs": padded("logprobs", T - 1).astype(np.float32),
        "mask": padded("mask", T - 1).astype(np.float32),
        "rewards": np.concatenate(
            [np.asarray(t["rewards"], np.float32) for t in trajs]),
        "group_size": trajs[0]["group_size"],
        "rollout_tokens": int(sum(t["rollout_tokens"] for t in trajs)),
        "weight_version": min(int(t["weight_version"]) for t in trajs),
    }


class GRPOLearner:
    """Jitted GRPO policy update over llama params.

    Runs in-process or as a `ray_tpu.remote` actor (all state
    reconstructible from config + checkpoints).  The update consumes a
    trajectory batch and returns metrics INCLUDING the advantages
    (numpy) — the determinism tests hash them bit-for-bit.

    `mesh` (optional) shards params by the logical-axis rules
    (parallel.sharding.shard_params over llama.param_logical_axes);
    the jitted update then runs under GSPMD with the model's own
    sharding constraints.  Single-device (CPU tests) when None."""

    def __init__(self, config=None, params: Any = None, mesh=None,
                 **overrides):
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu.models import llama

        cfg = _to_config(config, overrides)
        self.cfg = cfg
        self.mcfg = _model_config(cfg)
        self.params = params if params is not None else (
            cfg.params if cfg.params is not None else llama.init_params(
                jax.random.PRNGKey(cfg.seed), self.mcfg))
        if mesh is not None:
            from ray_tpu.parallel.sharding import shard_params

            self.params = shard_params(
                self.params, llama.param_logical_axes(self.mcfg), mesh)
        self.tx = optax.adam(cfg.lr)
        self.opt_state = self.tx.init(self.params)
        self.version = 0
        # fold_in-derived keys only (RL test discipline: global numpy
        # state would break cross-process reproducibility).
        self._base_key = jax.random.PRNGKey(cfg.seed + 101)
        self._pending_ckpt = None       # (version, path, Checkpoint)
        self._adv = jax.jit(
            lambda r: group_advantages(r, cfg.group_size, cfg.adv_eps))

        clip, klc = cfg.clip, cfg.kl_coeff
        mcfg = self.mcfg

        def _update(params, opt_state, tokens, mask, blogp, adv):
            def loss_fn(p):
                lp = llama.token_logprobs(p, tokens, mcfg)  # [B, T-1]
                ratio = jnp.exp(lp - blogp)
                a = adv[:, None]
                per = jnp.minimum(
                    ratio * a,
                    jnp.clip(ratio, 1.0 - clip, 1.0 + clip) * a)
                denom = jnp.maximum(mask.sum(), 1.0)
                pi_loss = -(per * mask).sum() / denom
                # k1 KL estimate vs the behavior policy (bounds the
                # off-policy drift live sync introduces).
                kl = ((blogp - lp) * mask).sum() / denom
                return pi_loss + klc * kl, (pi_loss, kl,
                                            (ratio * mask).sum() / denom)

            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss, aux

        self._update = jax.jit(_update)

    # ----------------------------------------------------------- update
    def update(self, trajs) -> dict:
        """One GRPO update over a list of trajectory batches (or refs —
        a bare ObjectRef argument resolves before dispatch on the actor
        path, and we resolve explicitly for the in-process path)."""
        import jax.numpy as jnp

        from ray_tpu.object_ref import ObjectRef

        trajs = [ray_tpu.get(t) if isinstance(t, ObjectRef) else t
                 for t in (trajs if isinstance(trajs, (list, tuple))
                           else [trajs])]
        batch = _concat_trajs(trajs)
        B = batch["tokens"].shape[0]
        K = batch["group_size"]
        if B % K:
            raise ValueError(
                f"batch rows {B} not a multiple of group_size {K} — "
                "trajectory groups arrived truncated")
        adv_all = np.asarray(self._adv(batch["rewards"]))
        mb = self.cfg.minibatch_size or B
        idx_order = np.arange(B)
        if mb < B:
            import jax

            # Deterministic shuffle: fold_in(base, version) — the RL
            # seeding discipline (no global numpy RNG).
            idx_order = np.asarray(jax.random.permutation(
                jax.random.fold_in(self._base_key, self.version), B))
        loss = pi_loss = kl = ratio = 0.0
        n_mb = 0
        for s in range(0, B, mb):
            idx = idx_order[s:s + mb]
            self.params, self.opt_state, l, aux = self._update(
                self.params, self.opt_state,
                jnp.asarray(batch["tokens"][idx]),
                jnp.asarray(batch["mask"][idx]),
                jnp.asarray(batch["logprobs"][idx]),
                jnp.asarray(adv_all[idx]))
            loss, (pi_loss, kl, ratio) = float(l), [float(x)
                                                   for x in aux]
            n_mb += 1
        self.version += 1
        return {
            "version": self.version,
            "loss": loss, "policy_loss": pi_loss, "kl": kl,
            "ratio_mean": ratio,
            "reward_mean": float(batch["rewards"].mean()),
            "reward_std": float(batch["rewards"].std()),
            "advantages": adv_all,
            "rollout_tokens": batch["rollout_tokens"],
            "batch_weight_version": batch["weight_version"],
            "minibatches": n_mb,
        }

    # ---------------------------------------------------- weight export
    def broadcast_weights(self, group_name: str,
                          src_rank: int = 0) -> int:
        """Rank-0 side of the live weight sync: ship the current param
        tree through the ring collectives as ONE packed transport.
        Failpoint `rl.weight_sync` fires INSIDE the sync window (a
        crash here models the learner dying mid-broadcast — survivors
        unpark via the trainer's destroy_collective_group)."""
        from ray_tpu import collective, failpoints

        if failpoints.ACTIVE:
            failpoints.fire("rl.weight_sync")
        collective.broadcast_pytree(self.params, src_rank, group_name)
        return self.version

    def init_collective_group(self, world_size: int, rank: int,
                              backend: str = "object_store",
                              group_name: str = "default") -> None:
        from ray_tpu import collective

        collective.init_collective_group(world_size, rank, backend,
                                         group_name)

    def deregister_collective_group(self, group_name: str) -> None:
        """Drop THIS process's state for a stale weight-sync epoch
        (thread pools; the rendezvous actor is destroyed by the
        trainer)."""
        from ray_tpu import collective

        collective.deregister_collective_group(group_name)

    def get_params_numpy(self):
        """Host copy of the param tree.  Transfers are kicked async
        FIRST: a synchronous per-leaf fetch serializes one device→host
        sync per leaf (hundreds of leaves — the same rule as
        broadcast_pytree's packing)."""
        import jax

        for x in jax.tree_util.tree_leaves(self.params):
            try:
                x.copy_to_host_async()
            except AttributeError:
                pass
        return jax.tree.map(np.asarray, self.params)

    def param_hash(self) -> str:
        """Stable content hash of the param tree (determinism tests;
        process-stable — never Python hash())."""
        import hashlib

        import jax

        h = hashlib.blake2b(digest_size=16)
        for leaf in jax.tree_util.tree_leaves(self.params):
            h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
        return h.hexdigest()

    # ------------------------------------------------------ checkpoints
    def save_async(self, path: str) -> int:
        """Kick an ASYNC checkpoint of (params, opt_state, version) —
        the background writer overlaps the next rollout/update;
        `ckpt_wait()` confirms completion (the trainer only treats a
        checkpoint as the newest resumable state once confirmed)."""
        from ray_tpu.train.checkpoint import Checkpoint

        ckpt = Checkpoint.from_pytree_async(
            {"params": self.params, "opt_state": self.opt_state,
             "version": np.asarray(self.version)}, path=path)
        self._pending_ckpt = (self.version, path, ckpt)
        return self.version

    def ckpt_wait(self) -> tuple | None:
        """Block for the in-flight async save; returns (version, path)
        once durable, None if nothing pending."""
        if self._pending_ckpt is None:
            return None
        version, path, ckpt = self._pending_ckpt
        ckpt.wait()
        self._pending_ckpt = None
        return (version, path)

    def load(self, path: str) -> int:
        """Resume from a COMPLETED checkpoint directory.  The restore
        targets THIS learner's freshly-built state tree: orbax needs
        the target to reconstruct container types (a targetless
        restore hands optax's namedtuple states back as plain dicts —
        the first post-resume update then dies inside the jit)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.train.checkpoint import Checkpoint

        state = Checkpoint(path).to_pytree(
            target={"params": self.params, "opt_state": self.opt_state,
                    "version": np.asarray(self.version)})
        self.params = jax.tree.map(jnp.asarray, state["params"])
        self.opt_state = jax.tree.map(jnp.asarray, state["opt_state"])
        self.version = int(np.asarray(state["version"]))
        return self.version

    def pid(self) -> int:
        return os.getpid()


class RLHFTrainer:
    """The online loop driver: rollouts → learner update → async
    checkpoint → live weight broadcast, with rollout-actor and learner
    crash recovery.  `num_rollout_workers=0` runs everything in-process
    (seeded, bit-deterministic — the bench and determinism-test mode);
    otherwise rollout workers (and optionally the learner) are
    ray_tpu actors and weight sync rides the collective broadcast."""

    def __init__(self, config: RLHFConfig | dict | None = None,
                 **overrides):
        cfg = _to_config(config, overrides)
        self.cfg = cfg
        mcfg = _model_config(cfg)
        rng = np.random.default_rng(cfg.seed)
        self.prompts = [rng.integers(
            1, mcfg.vocab_size, cfg.prompt_len).tolist()
            for _ in range(cfg.n_prompts)]
        self._reward = _reward_fn(cfg)
        self._uid = uuid.uuid4().hex[:8]
        self._epoch = 0
        self._group_formed = False
        self._prompt_cursor = 0
        self.version = 0
        self.weight_syncs = 0
        self.weight_sync_ms = 0.0
        self.rollout_regens = 0
        self.learner_restarts = 0
        self._newest_ckpt: tuple | None = None    # (version, path)
        self._worker_version: list[int] = []
        self._local = cfg.num_rollout_workers == 0
        self._build_learner()
        self._build_workers()

    # ------------------------------------------------------------ build
    def _worker_kwargs(self, i: int) -> dict:
        return dict(model=self.cfg.model, params=self.cfg.params,
                    seed=self.cfg.seed, engine=dict(self.cfg.engine),
                    reward_fn=self._reward,
                    name=f"{self.cfg.name}-w{i}")

    def _build_learner(self) -> None:
        lcls = self.cfg.learner_cls or GRPOLearner
        if self.cfg.remote_learner:
            if self._local:
                raise ValueError(
                    "remote_learner requires num_rollout_workers >= 1 "
                    "(a lone in-process loop has nothing to broadcast "
                    "to)")
            cls = ray_tpu.remote(lcls)
            self.learner = cls.options(num_cpus=1).remote(self.cfg)
            # Fail fast if the actor can't build (model typo etc.).
            ray_tpu.get(self.learner.pid.remote())
        else:
            self.learner = lcls(self.cfg)

    def _build_workers(self) -> None:
        from ray_tpu.rl.rollout_llm import LLMRolloutWorker

        wcls = self.cfg.worker_cls or LLMRolloutWorker
        if self._local:
            self.workers = [wcls(**self._worker_kwargs(0))]
            self._worker_version = [0]
            return
        cls = ray_tpu.remote(wcls)
        self.workers = [
            cls.options(num_cpus=1, max_concurrency=4).remote(
                **self._worker_kwargs(i))
            for i in range(self.cfg.num_rollout_workers)]
        ray_tpu.get([w.pid.remote() for w in self.workers])
        self._worker_version = [0] * len(self.workers)

    def _replace_worker(self, i: int) -> None:
        """Respawn a dead rollout actor and bootstrap it to the CURRENT
        policy via a direct object-plane weight push (it initializes at
        version 0 from the seed); the collective group re-forms lazily
        at the next broadcast (membership changed — the elastic-epoch
        rule)."""
        from ray_tpu.rl.rollout_llm import LLMRolloutWorker

        try:
            ray_tpu.kill(self.workers[i])
        except Exception:  # noqa: BLE001 - already dead
            pass
        if self._group_formed:
            # The dead member invalidates the epoch: reap its detached
            # rendezvous NOW (idempotent if _sync_weights already did)
            # — N crashes must not leak N rendezvous actors.
            from ray_tpu import collective

            try:
                collective.destroy_collective_group(
                    self._group_name(),
                    reason=f"rlhf rollout worker {i} replaced; epoch "
                           f"{self._epoch} abandoned")
            except Exception:  # noqa: BLE001 - best effort
                pass
        cls = ray_tpu.remote(self.cfg.worker_cls or LLMRolloutWorker)
        self.workers[i] = cls.options(
            num_cpus=1, max_concurrency=4).remote(
                **self._worker_kwargs(i))
        if self.version > 0:
            # Remote learner: pass the learner call's RESULT REF as the
            # argument — the param tree moves learner→worker over the
            # object plane; the driver never materializes it.
            params = self.learner.get_params_numpy.remote() \
                if self.cfg.remote_learner \
                else self.learner.get_params_numpy()
            v = ray_tpu.get(self.workers[i].update_weights.remote(
                params, self.version), timeout=120)
            self._worker_version[i] = v
        self._group_formed = False

    # ------------------------------------------------- learner recovery
    def _learner_call(self, method: str, *args, timeout: float = 300.0):
        fn = getattr(self.learner, method)
        if self.cfg.remote_learner:
            return ray_tpu.get(fn.remote(*args), timeout=timeout)
        return fn(*args)

    def _recover_learner(self) -> None:
        """A dead learner actor resumes from the newest COMPLETED async
        checkpoint (or from seed-initial state when none finished);
        parked broadcast waiters are drained first so no worker eats a
        collective deadline."""
        self.learner_restarts += 1
        if self._group_formed:
            from ray_tpu import collective

            collective.destroy_collective_group(
                self._group_name(),
                reason=f"rlhf learner died (restart "
                       f"{self.learner_restarts}); weight sync epoch "
                       f"{self._epoch} abandoned")
            self._group_formed = False
        try:
            ray_tpu.kill(self.learner)
        except Exception:  # noqa: BLE001
            pass
        cls = ray_tpu.remote(self.cfg.learner_cls or GRPOLearner)
        self.learner = cls.options(num_cpus=1).remote(self.cfg)
        if self._newest_ckpt is not None:
            self.version = self._learner_call(
                "load", self._newest_ckpt[1])
        else:
            self.version = 0
            ray_tpu.get(self.learner.pid.remote())

    # -------------------------------------------------------- collective
    def _group_name(self) -> str:
        return f"rlhf_w:{self.cfg.name}:{self._uid}:{self._epoch}"

    def _form_group(self) -> None:
        """(Re-)form the weight-broadcast group: learner rank 0, rollout
        workers ranks 1..W — a fresh epoch-suffixed name per membership
        change, the elastic-training rendezvous rule."""
        from ray_tpu import collective

        # Drop every member's LOCAL state for the previous epoch first
        # (op/prefetch thread pools in each process — the rendezvous
        # actor itself is reaped by whoever abandoned the epoch);
        # best-effort, a dead member is being replaced anyway.
        if self._epoch >= 1:
            prev = self._group_name()
            try:
                refs = [w.deregister_collective_group.remote(prev)
                        for w in self.workers]
                if self.cfg.remote_learner:
                    refs.append(
                        self.learner.deregister_collective_group
                        .remote(prev))
                else:
                    self.learner.deregister_collective_group(prev)
                ray_tpu.get(refs, timeout=60)
            except Exception:  # noqa: BLE001 - best effort
                pass
        self._epoch += 1
        name = self._group_name()
        world = 1 + len(self.workers)
        refs = []
        if self.cfg.remote_learner:
            refs.append(self.learner.init_collective_group.remote(
                world, 0, "object_store", name))
        else:
            # In-driver learner: rank 0 lives in THIS process.
            self.learner.init_collective_group(world, 0,
                                               "object_store", name)
        refs += [w.init_collective_group.remote(
            world, r + 1, "object_store", name)
            for r, w in enumerate(self.workers)]
        ray_tpu.get(refs, timeout=120)
        self._group_formed = True

    # ----------------------------------------------------------- rollout
    def _next_prompts(self) -> list[list[int]]:
        n = min(self.cfg.prompts_per_step, len(self.prompts))
        out = [self.prompts[(self._prompt_cursor + j)
                            % len(self.prompts)] for j in range(n)]
        self._prompt_cursor = (self._prompt_cursor + n) \
            % len(self.prompts)
        return out

    def _rollout_kwargs(self) -> dict:
        return dict(group_size=self.cfg.group_size,
                    max_new_tokens=self.cfg.max_new_tokens,
                    temperature=self.cfg.temperature,
                    eos_id=self.cfg.eos_id)

    def _gather_rollouts(self, prompts: list) -> list:
        """Dispatch prompt groups across workers.  In-process mode
        returns trajectory dicts; actor mode returns the rollout REFS
        untouched — they ride to the learner as object-plane refs (the
        learner pulls trajectory bytes straight from each rollout
        worker's arena; the driver never holds the bulk).  Failures
        surface when the learner resolves them — step() heals dead
        workers and regenerates."""
        if self._local:
            return [self.workers[0].rollout(prompts,
                                            **self._rollout_kwargs())]
        shards: dict[int, list] = {}
        for j, p in enumerate(prompts):
            shards.setdefault(j % len(self.workers), []).append(p)
        kw = self._rollout_kwargs()
        return [self.workers[i].rollout.remote(ps, **kw)
                for i, ps in shards.items()]

    def _heal_workers(self) -> None:
        """Replace every dead rollout actor (liveness probe per
        worker); survivors keep their engines — and their prefix
        caches, which is what makes a regenerated group cheap."""
        for i, w in enumerate(self.workers):
            try:
                ray_tpu.get(w.pid.remote(), timeout=60)
            except Exception:  # noqa: BLE001 - dead actor
                self._replace_worker(i)

    # ------------------------------------------------------ weight sync
    def _sync_weights(self) -> None:
        """Push the current learner policy to every generation engine.
        Local mode: a direct update_weights staging.  Actor mode: ring
        broadcast (learner rank 0 + every worker's recv thread), timed
        end-to-end as weight_sync_ms.  Decode never pauses — engines
        swap between sync windows."""
        from ray_tpu import tracing

        t0 = time.perf_counter()
        with tracing.span("rl.weight_sync",
                          attrs={"version": self.version,
                                 "mode": "local" if self._local
                                 else ("driver_learner"
                                       if not self.cfg.remote_learner
                                       else "remote_learner")}):
            self._sync_weights_inner()
        self.weight_syncs += 1
        self.weight_sync_ms += (time.perf_counter() - t0) * 1000.0

    def _sync_weights_inner(self) -> None:
        from ray_tpu import failpoints

        if self._local:
            if failpoints.ACTIVE:
                failpoints.fire("rl.weight_sync")
            v = self.learner.version
            ret = self.workers[0].update_weights(
                self.learner.get_params_numpy(), v)
            if ret == v:
                # Staged (not kill-switched): wait until the engine
                # SWAPPED (stats().weight_version flips) — the next
                # rollout must sample the new policy, or two identical
                # runs could diverge on swap timing (local mode's
                # bit-determinism contract).  A frozen engine
                # (RAY_TPU_RL_WEIGHT_SYNC=0) returned its CURRENT
                # version instead, so there is nothing to wait for.
                deadline = time.monotonic() + 30.0
                while self.workers[0].stats()["weight_version"] < v:
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"weight v{v} never became visible on the "
                            "local engine (loop dead?)")
                    time.sleep(0.001)
            self._worker_version[0] = ret
        elif not self.cfg.remote_learner:
            # Actor workers, in-driver learner: dispatch every
            # receiver FIRST, then broadcast from this process (rank 0
            # blocks until each child consumed its chunks — the
            # receivers above are already running).  A learner crash
            # here IS a driver crash, so no recovery arm.
            if not self._group_formed:
                self._form_group()
            name = self._group_name()
            recv = [w.recv_weights.remote(self.version, name)
                    for w in self.workers]
            self.learner.broadcast_weights(name)
            for i, r in enumerate(recv):
                self._worker_version[i] = ray_tpu.get(r, timeout=300)
        else:
            if not self._group_formed:
                self._form_group()
            name = self._group_name()
            bc = self.learner.broadcast_weights.remote(name)
            recv = [w.recv_weights.remote(self.version, name)
                    for w in self.workers]
            try:
                v = ray_tpu.get(bc, timeout=300)
                for i, r in enumerate(recv):
                    self._worker_version[i] = ray_tpu.get(r,
                                                          timeout=300)
            except Exception:  # noqa: BLE001 - sync failed: diagnose
                # A dead ROLLOUT worker or a collective deadline also
                # lands here — probe the learner before condemning it
                # (recovering a HEALTHY learner would roll training
                # back to the last checkpoint, or to seed with
                # checkpoint_every=0).
                learner_dead = False
                try:
                    self._learner_call("pid", timeout=60)
                except Exception:  # noqa: BLE001
                    learner_dead = True
                if learner_dead:
                    self._recover_learner()
                else:
                    from ray_tpu import collective

                    # Unpark any receiver still waiting on the stale
                    # epoch and reap its detached rendezvous — then
                    # replace whichever worker actually died.
                    collective.destroy_collective_group(
                        self._group_name(),
                        reason="rlhf weight sync failed (rollout "
                               "worker died mid-broadcast?); epoch "
                               f"{self._epoch} abandoned")
                    self._group_formed = False
                    self._heal_workers()
                # Drain any still-parked receivers, then re-sync on a
                # fresh epoch so every (possibly replaced) member lands
                # on the current policy.
                for r in recv:
                    try:
                        ray_tpu.get(r, timeout=60)
                    except Exception:  # noqa: BLE001 - drained/aborted
                        pass
                self._form_group()
                name = self._group_name()
                bc = self.learner.broadcast_weights.remote(name)
                recv = [w.recv_weights.remote(self.version, name)
                        for w in self.workers]
                ray_tpu.get(bc, timeout=300)
                for i, r in enumerate(recv):
                    self._worker_version[i] = ray_tpu.get(r,
                                                          timeout=300)

    def _lag_exceeded(self) -> bool:
        return (self.version - min(self._worker_version)
                > self.cfg.max_weight_lag)

    def _update_with_recovery(self, trajs):
        """Learner update with crash recovery.  A failure here is
        either the learner dying (liveness probe fails → rebuild from
        the newest async checkpoint, retry) or a trajectory ref whose
        rollout worker died (probe passes → re-raise so step()
        regenerates the group)."""
        try:
            return self._learner_call("update", trajs)
        except Exception:  # noqa: BLE001
            if not self.cfg.remote_learner:
                raise
            try:
                self._learner_call("pid", timeout=60)
                alive = True
            except Exception:  # noqa: BLE001
                alive = False
            if alive:
                raise        # lost trajectories — step() regenerates
            self._recover_learner()
            return self._learner_call("update", trajs)

    # ------------------------------------------------------------- loop
    def step(self) -> dict:
        """One full cycle: rollout → update → (async checkpoint) →
        (broadcast).  The staleness bound runs FIRST: generation must
        never start more than max_weight_lag versions behind."""
        if self.cfg.sync_every and self.version and self._lag_exceeded():
            # sync_every=0 means NEVER broadcast — the lag bound only
            # applies when sync is enabled at all.
            self._sync_weights()
        prompts = self._next_prompts()
        if self._local:
            metrics = self._learner_call(
                "update", self._gather_rollouts(prompts))
        else:
            metrics = last_err = None
            for _attempt in range(1 + self.cfg.rollout_retries):
                trajs = self._gather_rollouts(prompts)
                try:
                    metrics = self._update_with_recovery(trajs)
                    last_err = None
                    break
                except Exception as e:  # noqa: BLE001 - rollout lost
                    last_err = e
                    self.rollout_regens += 1
                    self._heal_workers()
            if metrics is None:
                raise RuntimeError(
                    f"rollouts failed {1 + self.cfg.rollout_retries}x "
                    "(workers crash-looping?)") from last_err
        self.version = metrics["version"]
        if self.cfg.checkpoint_every and \
                self.version % self.cfg.checkpoint_every == 0:
            self._checkpoint()
        if self.cfg.sync_every and \
                self.version % self.cfg.sync_every == 0:
            self._sync_weights()
        metrics["weight_syncs"] = self.weight_syncs
        metrics["rollout_regens"] = self.rollout_regens
        metrics["learner_restarts"] = self.learner_restarts
        return metrics

    def run(self, n_updates: int) -> list[dict]:
        return [self.step() for _ in range(n_updates)]

    def _checkpoint(self) -> None:
        """Async save; the PREVIOUS save is confirmed (waited) first and
        becomes the newest resumable checkpoint — so the learner-crash
        recovery never points at a half-written directory."""
        base = self.cfg.checkpoint_dir
        if base is None:
            import tempfile

            base = tempfile.mkdtemp(prefix="rlhf-ckpt-")
            self.cfg.checkpoint_dir = base
        done = self._learner_call("ckpt_wait")
        if done is not None:
            self._newest_ckpt = done
        path = os.path.join(base, f"v{self.version:06d}")
        self._learner_call("save_async", path)

    def flush_checkpoints(self) -> tuple | None:
        """Force the in-flight save durable (tests/benches call this
        before killing the learner so there IS a newest checkpoint)."""
        done = self._learner_call("ckpt_wait")
        if done is not None:
            self._newest_ckpt = done
        return self._newest_ckpt

    # ------------------------------------------------------------ admin
    def stats(self) -> dict:
        out = {
            "version": self.version,
            "weight_syncs": self.weight_syncs,
            "weight_sync_ms": round(self.weight_sync_ms, 3),
            "rollout_regens": self.rollout_regens,
            "learner_restarts": self.learner_restarts,
            "epoch": self._epoch,
            "worker_versions": list(self._worker_version),
            "newest_ckpt": self._newest_ckpt,
        }
        if self._local:
            out["workers"] = [self.workers[0].stats()]
        return out

    def shutdown(self) -> None:
        if self._local:
            self.workers[0].stop()
            return
        if self._group_formed:
            from ray_tpu import collective

            try:
                collective.destroy_collective_group(
                    self._group_name(), reason="rlhf trainer shutdown")
            except Exception:  # noqa: BLE001
                pass
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:  # noqa: BLE001
                pass
        if self.cfg.remote_learner:
            try:
                ray_tpu.kill(self.learner)
            except Exception:  # noqa: BLE001
                pass
