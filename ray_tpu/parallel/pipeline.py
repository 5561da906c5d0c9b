"""Pipeline parallelism: GPipe-style microbatching over a mesh axis.

The reference has no native PP either (SURVEY §2.4: compiled DAGs +
NCCL channels are the building blocks Ray offers; actual pipelining comes
from user libraries).  Here PP is a *collective program*: stages live on a
"stage" mesh axis, activations move stage→stage with ppermute inside
`shard_map`, and the schedule is a `lax.scan` over microbatches + bubble
steps — all statically shaped, fully under one jit (the TPU-idiomatic
formulation; per-stage actors + host channels remain available through
ray_tpu.dag for cross-slice pipelines over DCN).

Usage:
    fn(stage_params, x) -> y          # one stage's computation
    out = pipeline_apply(fn, stacked_params, microbatches, axis="stage")

`stacked_params` has a leading [n_stages, ...] axis sharded over the
stage mesh axis; `microbatches` is [n_micro, mb, ...].
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, microbatches,
                   mesh: Mesh, axis: str = "stage"):
    """Run microbatches through all pipeline stages (GPipe schedule).

    stage_fn(params_for_one_stage, x [mb, ...]) -> y [mb, ...] with the
    same shape (stages must preserve activation shape, as in a decoder
    trunk).  Returns [n_micro, mb, ...] outputs after the last stage.

    The shard_map is *partially manual*: only the stage axis is manual
    (lax.ppermute needs explicit neighbor sends); every other mesh axis
    (data/fsdp/tensor/seq) stays automatic, so GSPMD shards the in-stage
    compute exactly as it would outside the pipeline — fsdp all-gathers
    the per-stage params, tensor inserts the Megatron all-reduces, the
    microbatch dim stays data-parallel.  That is how PP composes with
    every other strategy without this file knowing about any of them.

    Total steps = n_micro + n_stages - 1 (the pipeline bubble); each step
    every stage computes one microbatch then shifts activations to the
    next stage with ppermute (rides ICI neighbors when the stage axis is
    laid out contiguously).
    """
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    steps = n_micro + n_stages - 1

    # XLA's CPU backend (the 8-device virtual test platform) crashes
    # promoting the bf16 all-reduce that shard_map's transpose inserts
    # over the manual axis for the replicated-in microbatch cotangent
    # ("Invalid binary instruction opcode copy" in AllReducePromotion).
    # Trampoline the microbatches through f32 at the boundary there so
    # that psum is f32; compute inside stays in the model dtype.  TPU
    # all-reduces bf16 natively — no trampoline, no cost.
    mb_dtype = microbatches.dtype
    f32_boundary = (mb_dtype == jnp.bfloat16
                    and jax.devices()[0].platform == "cpu")
    if f32_boundary:
        microbatches = microbatches.astype(jnp.float32)

    def per_stage(params, mb):        # runs with a LOCAL stage view
        mb = mb.astype(mb_dtype)
        # params leading axis is the local stage shard: [1, ...] → drop it
        params = jax.tree.map(lambda p: p[0], params)
        stage_idx = lax.axis_index(axis)
        state = jnp.zeros_like(mb[0])           # current activation
        outputs = jnp.zeros_like(mb)

        def step(carry, t):
            state, outputs = carry
            # stage 0 feeds itself from the microbatch queue (zeros once
            # the queue is drained — the pipeline bubble)
            feed = jnp.where(t < n_micro, t, 0)
            fed = jnp.where(t < n_micro, mb[feed],
                            jnp.zeros_like(state))
            state = jnp.where(stage_idx == 0, fed, state)
            y = stage_fn(params, state)
            # last stage writes result for microbatch t - (n_stages - 1)
            out_t = t - (n_stages - 1)
            write = jnp.logical_and(stage_idx == n_stages - 1, out_t >= 0)
            outputs = lax.cond(
                write,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_t, 0), 0),
                lambda o: o, outputs)
            # shift activations to the next stage (ring permute)
            y = lax.ppermute(
                y, axis,
                perm=[(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (y, outputs), None

        (_, outputs), _ = lax.scan(step, (state, outputs),
                                   jnp.arange(steps))
        # only the last stage holds real outputs; broadcast them so every
        # shard returns identically (psum over one-hot mask).  f32: XLA's
        # CPU backend crashes promoting bf16 all-reduces produced inside
        # partial-manual regions (AllReducePromotion check failure), and
        # on TPU the widened all-reduce is one per pipeline call — noise.
        mask = (stage_idx == n_stages - 1).astype(jnp.float32)
        outputs = lax.psum(outputs.astype(jnp.float32) * mask,
                           axis).astype(outputs.dtype)
        return outputs

    # Specs name only the manual axis; sharding over the auto axes rides
    # through on the arrays' own (GSPMD) shardings.
    params_spec = jax.tree.map(lambda _: P(axis), stage_params)
    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(params_spec, P()),
        out_specs=P(),
        axis_names=frozenset({axis}),
        check_vma=False)
    return fn(stage_params, microbatches)


def stack_stage_params(per_stage_params: list):
    """[pytree, ...] per stage → one pytree with leading [n_stages, ...]."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def stage_sharding(mesh: Mesh, axis: str = "stage"):
    """NamedSharding placing the leading stage axis on the mesh axis."""
    return NamedSharding(mesh, P(axis))
