"""Llama-3-8B train step traces abstractly over a v5e-64-shaped mesh.

The north-star config (Llama-3-8B pretrain on a v5e-64) can't run on
CI hardware; what CAN be verified is that the FULL-SIZE model's sharded
step is well-formed: parameter shapes/shardings, the loss/grad/optimizer
program, and the dp×fsdp×tp layout all trace without materializing a
single array (jax.eval_shape) over an abstract 64-device mesh.
"""
import numpy as np
import pytest


def test_llama3_8b_sharded_step_traces_over_64_device_mesh():
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh

    from ray_tpu.models import llama
    from ray_tpu.train import step as train_step

    cfg = llama.llama_configs()["llama3-8b"]
    assert 7.9e9 < cfg.num_params() < 8.2e9, cfg.num_params()

    # v5e-64 layout: dp=2 × fsdp=16 × tp=2 (the 8B recipe in SURVEY §7).
    mesh = AbstractMesh((2, 16, 2), ("data", "fsdp", "tensor"),
                        axis_types=(jax.sharding.AxisType.Auto,) * 3)
    optimizer = train_step.default_optimizer(total_steps=100)

    def init():
        return train_step.create_train_state(
            jax.random.PRNGKey(0), cfg, optimizer)

    with jax.sharding.use_abstract_mesh(mesh):
        state_shape = jax.eval_shape(init)
        n_param_bytes = sum(
            np.prod(x.shape) * x.dtype.itemsize
            for x in jax.tree.leaves(state_shape.params))
        # 8B bf16 params ≈ 16GB total (pre-sharding).
        assert 15e9 < n_param_bytes < 17e9

        step_fn = train_step.make_train_step(cfg, optimizer)
        batch = jax.ShapeDtypeStruct((64, 2048), jnp.int32)
        out_state, metrics = jax.eval_shape(
            step_fn, state_shape, {"inputs": batch, "targets": batch})
    # The step is shape-preserving and produces scalar metrics.
    assert jax.tree.structure(out_state.params) == \
        jax.tree.structure(state_shape.params)
    for a, b in zip(jax.tree.leaves(out_state.params),
                    jax.tree.leaves(state_shape.params)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert metrics["loss"].shape == ()
