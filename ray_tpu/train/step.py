"""Sharded training step: the compute core of ray_tpu.train.

The reference's Train library never owns the step — users write torch loops
and ray wraps DDP around them (ray: python/ray/train/torch/train_loop_utils.py:158).
Here the framework owns an XLA-native step: loss/grad/optimizer fused into
one jitted program whose parallelism (dp/fsdp/tp/sp) is purely a layout
choice from ray_tpu.parallel.sharding — XLA inserts the ICI collectives
(psum for grads under dp, all-gather/reduce-scatter for fsdp params under
GSPMD, per-layer all-reduces under tp).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.parallel.sharding import logical_sharding, param_shardings


def model_module(cfg: llama.LlamaConfig):
    """Model family for a config: moe for MoEConfig (a LlamaConfig
    subclass, so it must be checked first), llama otherwise.  Keeps the
    train helpers honest — an MoE config must never silently build a
    dense model."""
    from ray_tpu.models import moe

    if isinstance(cfg, moe.MoEConfig):
        return moe
    return llama


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                      warmup: int = 100, total_steps: int = 10000,
                      b1: float = 0.9, b2: float = 0.95,
                      grad_clip: float = 1.0) -> optax.GradientTransformation:
    """AdamW + cosine schedule + global-norm clip (the Llama pretrain recipe)."""
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(total_steps, warmup + 1), end_value=lr * 0.1)
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def create_train_state(key: jax.Array, cfg: llama.LlamaConfig,
                       optimizer: optax.GradientTransformation) -> TrainState:
    params = model_module(cfg).init_params(key, cfg)
    return TrainState(params=params, opt_state=optimizer.init(params),
                      step=jnp.zeros((), jnp.int32))


def make_train_step(cfg: llama.LlamaConfig,
                    optimizer: optax.GradientTransformation,
                    loss_fn: Callable | None = None) -> Callable:
    """Returns step(state, batch) -> (state, metrics). Pure; jit outside."""
    loss_fn = loss_fn or model_module(cfg).loss_fn

    def step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        def compute_loss(params):
            return loss_fn(params, batch, cfg)

        # named scopes: op metadata for XProf / --dump-trace only
        with jax.named_scope("loss"):
            loss, grads = jax.value_and_grad(compute_loss)(state.params)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        new_state = TrainState(params=params, opt_state=opt_state,
                               step=state.step + 1)
        return new_state, {"loss": loss, "grad_norm": gnorm,
                           "step": new_state.step}

    return step


# ------------------------------------------------------- sharded wrappers
def _rules_for(mesh: Mesh) -> dict | None:
    """Sharding rules for a mesh: on a stage-bearing (pipeline) mesh the
    stacked "layers" param axis shards over "stage", so each stage holds
    its contiguous layer block and pipelined_loss_fn's per-stage reshape
    moves no data.  None = the default LOGICAL_RULES."""
    if mesh.shape.get("stage", 1) > 1:
        from ray_tpu.parallel.sharding import LOGICAL_RULES

        return {**LOGICAL_RULES, "layers": "stage"}
    return None


def state_shardings(cfg: llama.LlamaConfig, mesh: Mesh,
                    optimizer: optax.GradientTransformation):
    """NamedShardings for a TrainState: params follow the logical-axes
    table; optimizer-state leaves mirror whichever param they track
    (matched by shape), scalars replicate."""
    model = model_module(cfg)
    axes = model.param_logical_axes(cfg)
    p_sh = param_shardings(axes, mesh, rules=_rules_for(mesh))

    params_shape = jax.eval_shape(
        lambda k: model.init_params(k, cfg), jax.random.PRNGKey(0))
    shape_to_sh = {}
    for (path_a, leaf), (path_b, sh) in zip(
            jax.tree_util.tree_leaves_with_path(params_shape),
            jax.tree_util.tree_leaves_with_path(p_sh)):
        shape_to_sh[leaf.shape] = sh
    replicated = NamedSharding(mesh, P())

    def opt_leaf_sharding(leaf):
        return shape_to_sh.get(leaf.shape, replicated)

    opt_shape = jax.eval_shape(
        lambda: optimizer.init(
            jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params_shape)))
    o_sh = jax.tree.map(opt_leaf_sharding, opt_shape)
    return TrainState(params=p_sh, opt_state=o_sh, step=replicated)


def batch_shardings(mesh: Mesh):
    """One sharding for every batch leaf ([b, s] token arrays) — used as a
    jit prefix pytree, so any batch dict layout works."""
    return logical_sharding(mesh, ("batch", "seq"))


def sharded_init(key: jax.Array, cfg: llama.LlamaConfig,
                 optimizer: optax.GradientTransformation,
                 mesh: Mesh) -> TrainState:
    """Initialize params directly into their sharded layout (no host-side
    full copy: jit with out_shardings materializes each shard on-device)."""
    st_sh = state_shardings(cfg, mesh, optimizer)
    with jax.set_mesh(mesh):
        init = jax.jit(
            functools.partial(create_train_state, cfg=cfg,
                              optimizer=optimizer),
            out_shardings=st_sh)
        return init(key)


def reshard_state(state, cfg: llama.LlamaConfig,
                  optimizer: optax.GradientTransformation,
                  mesh: Mesh):
    """Re-lay a TrainState pytree (host arrays from a checkpoint, or
    arrays sharded for a DIFFERENT mesh) onto `mesh` via the logical-axis
    rules — the elastic resume hook (ISSUE 8): after a membership-epoch
    world-size change the physical mesh changed but the logical table
    didn't, so a device_put of every leaf to its new NamedSharding is the
    whole resharding story.  Deterministic: same checkpoint + same mesh
    => bit-identical device state regardless of the world size it was
    saved under."""
    st_sh = state_shardings(cfg, mesh, optimizer)
    return jax.tree.map(
        lambda x, s: jax.device_put(jnp.asarray(x), s), state, st_sh)


def sharded_train_step(cfg: llama.LlamaConfig,
                       optimizer: optax.GradientTransformation,
                       mesh: Mesh, loss_fn: Callable | None = None,
                       n_micro: int | None = None):
    """Jitted step with explicit state/batch shardings; donates the state
    (params update in place in HBM).  On a stage-bearing mesh the trunk
    runs the GPipe pipeline (llama.pipelined_loss_fn) automatically."""
    if loss_fn is None and mesh.shape.get("stage", 1) > 1:
        # fsdp/tensor/data compose with the pipeline (only "stage" is
        # manual inside pipeline_apply; GSPMD shards the in-stage compute
        # over the auto axes).  seq (ring attention nests its own
        # shard_map) and expert (no pipelined MoE trunk) do not yet.
        unsupported = [a for a in ("seq", "expert")
                       if mesh.shape.get(a, 1) > 1]
        if unsupported:
            raise NotImplementedError(
                f"pipeline meshes compose with data/fsdp/tensor; axes "
                f"{unsupported} > 1 are not supported inside the "
                "pipelined trunk yet")

        def loss_fn(params, batch, cfg_, _mesh=mesh, _nm=n_micro):
            pl = getattr(model_module(cfg_), "pipelined_loss_fn", None)
            if pl is None:
                raise NotImplementedError(
                    f"{model_module(cfg_).__name__} has no pipelined "
                    "trunk; pipeline meshes (stage>1) currently support "
                    "the llama family")
            return pl(params, batch, cfg_, _mesh, _nm)
    st_sh = state_shardings(cfg, mesh, optimizer)
    b_sh = batch_shardings(mesh)
    step = make_train_step(cfg, optimizer, loss_fn)
    return jax.jit(step, in_shardings=(st_sh, b_sh),
                   out_shardings=(st_sh, None), donate_argnums=(0,))
