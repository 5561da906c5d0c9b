"""Sharded train step: loss decreases, parallelism layouts agree.

The decisive property (the reference never tests this because torch DDP
owns it; here GSPMD does): the SAME step function under different mesh
layouts (pure-dp, fsdp, tp, sp/ring) produces the SAME loss trajectory.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.train import step as train_step

CFG = llama.LlamaConfig(vocab_size=256, dim=128, n_layers=2, n_heads=4,
                        n_kv_heads=2, ffn_dim=256, max_seq=128, remat=False)


def _batch(b=8, s=64):
    key = jax.random.PRNGKey(7)
    tok = jax.random.randint(key, (b, s), 0, CFG.vocab_size, jnp.int32)
    return {"inputs": tok, "targets": jnp.roll(tok, -1, axis=1)}


def _run(mesh_cfg, n_steps=3, cfg=CFG):
    mesh = create_mesh(mesh_cfg, devices=jax.devices()[:8])
    opt = train_step.default_optimizer(lr=1e-3, warmup=1, total_steps=100)
    state = train_step.sharded_init(jax.random.PRNGKey(0), cfg, opt, mesh)
    fn = train_step.sharded_train_step(cfg, opt, mesh)
    batch = _batch()
    losses = []
    with jax.set_mesh(mesh):
        for _ in range(n_steps):
            state, m = fn(state, batch)
            losses.append(float(m["loss"]))
    return losses


class TestShardedTrainStep:
    def test_loss_decreases_dp(self):
        losses = _run(MeshConfig(data=8))
        assert losses[-1] < losses[0]

    def test_layouts_agree(self):
        ref = _run(MeshConfig(data=8))
        for mc in (MeshConfig(data=2, fsdp=4),
                   MeshConfig(data=2, fsdp=2, tensor=2),
                   MeshConfig(data=1, fsdp=8)):
            got = _run(mc)
            np.testing.assert_allclose(got, ref, rtol=2e-3,
                                       err_msg=f"{mc} diverged from dp")

    def test_ring_attention_layout_agrees(self):
        ref = _run(MeshConfig(data=8))
        import dataclasses

        cfg_sp = dataclasses.replace(CFG, use_ring_attention=True)
        got = _run(MeshConfig(data=2, seq=4), cfg=cfg_sp)
        np.testing.assert_allclose(got, ref, rtol=2e-3)

    def test_metrics_shape(self):
        mesh = create_mesh(MeshConfig(data=8), devices=jax.devices()[:8])
        opt = train_step.default_optimizer()
        state = train_step.sharded_init(jax.random.PRNGKey(0), CFG, opt, mesh)
        fn = train_step.sharded_train_step(CFG, opt, mesh)
        batch = _batch()
        with jax.set_mesh(mesh):
            state, m = fn(state, batch)
        assert int(m["step"]) == 1
        assert float(m["grad_norm"]) > 0


class TestGraftEntry:
    def test_entry_compiles(self):
        import __graft_entry__ as g

        fn, args = g.entry()
        out = jax.jit(fn)(*args)
        assert out.shape[-1] == 2048

    def test_dryrun_multichip(self):
        import __graft_entry__ as g

        g.dryrun_multichip(8)
