"""The Llama family's plain reference against the program's model code,
tiny, on the CPU.  Everything is reached the way the harness reaches it:
through the family file."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=96,
            vocab_size=128, rope_theta=1e6, rms_norm_eps=1e-5,
            max_position_embeddings=64)


@pytest.fixture(scope="module")
def fam():
    from benchmarks.harness import spec

    return spec.load_family("llama")


@pytest.fixture(scope="module")
def ref_mod(fam):
    return fam.reference()


def _program(cfg):
    """The program's model module for a config, as `train/step.py`
    picks it."""
    from ray_tpu.train import step as train_step

    return train_step.model_module(cfg)


@pytest.fixture(scope="module")
def setup(fam):
    import jax
    import jax.numpy as jnp

    cfg = dataclasses.replace(fam.program_config(TINY, max_seq=32),
                              dtype=jnp.float32, remat=False)
    params = fam.init_params(jax.random.PRNGKey(3), cfg)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (1, 24), 0,
                                         TINY["vocab_size"]))
    return cfg, params, toks


def test_reference_logits_match_the_programs_forward(setup, ref_mod):
    cfg, params, toks = setup
    want = np.asarray(_program(cfg).forward(params, toks, cfg))[0]
    got = np.asarray(ref_mod.logits(params, toks[0], TINY))
    # float32 on both sides: they differ in summation order only
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_reference_loss_matches_the_programs_loss(setup, ref_mod):
    cfg, params, toks = setup
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    want = float(_program(cfg).loss_fn(params, batch, cfg))
    lg = ref_mod.logits(params, toks[0, :-1], TINY)
    assert float(ref_mod.cross_entropy(lg, toks[0, 1:])) == \
        pytest.approx(want, rel=1e-4)


def test_teacher_forcing_catches_a_skipped_layer(setup, ref_mod):
    import jax

    decoder = ref_mod
    _, params, toks = setup
    prompt = toks[0, :12].tolist()
    seq, out = list(prompt), []
    for _ in range(6):          # the reference's own greedy continuation
        lg = np.asarray(decoder.logits(params, seq, TINY))[-1]
        out.append(int(lg.argmax()))
        seq.append(out[-1])
    gaps = decoder.teacher_forced_gaps(params, prompt, out, TINY)
    assert max(gaps) == pytest.approx(0.0, abs=1e-5)
    one_layer = dict(params, layers=jax.tree.map(lambda a: a[:1],
                                                 params["layers"]))
    bad = decoder.teacher_forced_gaps(one_layer, prompt, out, TINY)
    assert max(bad) > 0.05


# ------------------------------------------ the train cell's `correct`
@pytest.fixture(scope="module")
def train_check(setup, fam, ref_mod):
    """What the train loop compares, tiny and on one CPU device: the
    program's step (bfloat16, as the cell runs it) and forward on one
    small batch, and the reference on the same batch and parameters."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.train import step as train_step

    model = dict(TINY, num_hidden_layers=4)
    cfg = fam.program_config(model, max_seq=32)
    params = fam.init_params(jax.random.PRNGKey(5), cfg)
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(6), (2, 33), 0, model["vocab_size"]))
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}

    def program(params, cfg):
        opt = optax.sgd(0.0)
        state = train_step.TrainState(params=params,
                                      opt_state=opt.init(params),
                                      step=jnp.zeros((), jnp.int32))
        _, m = jax.jit(train_step.make_train_step(cfg, opt))(state, batch)
        return {"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"]),
                "logprobs": np.asarray(
                    _program(cfg).token_logprobs(params, toks, cfg),
                    np.float32)}

    ref = ref_mod.loss_and_gradient(params, batch["inputs"],
                                    batch["targets"], model)
    return model, cfg, params, batch, program, ref


def test_the_written_out_backward_matches_autodiff(train_check, ref_mod):
    import jax
    import jax.numpy as jnp

    decoder = ref_mod
    model, _, params, batch, _, ref = train_check

    def loss(p):
        return sum(decoder.cross_entropy(decoder.logits(p, x, model), y)
                   for x, y in zip(batch["inputs"], batch["targets"])) / 2

    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(loss)(p32)
    norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g))
                              for g in jax.tree.leaves(grads))))
    assert ref["loss"] == pytest.approx(float(want), rel=1e-5)
    assert ref["grad_norm"] == pytest.approx(norm, rel=1e-4)
    assert ref["logprobs"].shape == batch["targets"].shape


def test_train_check_passes_the_program_as_it_is(train_check, fam):
    from benchmarks.harness import train_loop

    _, cfg, params, _, program, ref = train_check
    assert train_loop.judge(program(params, cfg), ref, fam) == []


def _skip_a_layer(params, cfg):
    import jax

    return (dict(params, layers=jax.tree.map(lambda a: a[:-1],
                                             params["layers"])),
            dataclasses.replace(cfg, n_layers=cfg.n_layers - 1))


def _through_fp8(params, cfg):
    import jax
    import jax.numpy as jnp

    return (jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn)
                         .astype(a.dtype), params), cfg)


def _no_attention(params, cfg):
    import jax.numpy as jnp

    return (dict(params, layers=dict(
        params["layers"], wo=jnp.zeros_like(params["layers"]["wo"]))), cfg)


@pytest.mark.parametrize("fault", [_skip_a_layer, _through_fp8,
                                   _no_attention])
def test_train_check_catches(train_check, fam, fault):
    from benchmarks.harness import train_loop

    _, cfg, params, _, program, ref = train_check
    problems = train_loop.judge(program(*fault(params, cfg)), ref, fam)
    assert any("log-probabilities" in p for p in problems), problems
