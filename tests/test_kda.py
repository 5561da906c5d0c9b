"""ops/kda.py (the gated delta rule with a per-channel decay): the
chunked scan and the one-step kernel (interpret mode) against the
token-by-token recurrence, at lengths that end mid-chunk, across
stretches, with lanes that hold no request."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda, ssm


def _inputs(b, T, H=4, dk=16, dv=16, lens=None, seed=0, lower=-5.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (b, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, T, H, dk)))
    v = jax.random.normal(ks[2], (b, T, H, dv))
    g = lower * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (b, T, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, T, H)))
    if lens is not None:
        live = jnp.arange(T)[None, :] < jnp.asarray(lens)[:, None]
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
    return q, k, v, g, beta


def _rel(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("T,chunk,lens", [
    (40, 16, [40, 27]),     # a row whose true length ends mid-chunk
    (16, 16, [16, 1]),      # one chunk; a row of one token
    (7, 16, [7, 5]),        # shorter than a chunk
    (64, 8, [33, 64]),      # many chunks
    (70, 32, [70, 41]),     # the served chunk
    (24, 1, [24, 9]),       # a chunk a position: the recurrence itself
])
def test_kda_scan_equals_the_recurrence_at_the_true_length(T, chunk, lens):
    q, k, v, g, beta = _inputs(2, T, lens=lens)
    o, S = jax.jit(lambda *a: kda.kda_scan(*a, chunk=chunk))(
        q, k, v, g, beta)
    for i, n in enumerate(lens):
        want_o, want_S = kda.kda_recurrence(
            q[i, :n], k[i, :n], v[i, :n], g[i, :n], beta[i, :n])
        assert _rel(o[i, :n], want_o) < 2e-5
        assert _rel(S[i], want_S) < 2e-5


def test_kda_scan_carries_the_state_between_stretches(monkeypatch):
    """The chunk-local parts are made a stretch at a time; the state
    crosses the stretches' edges as it crosses the chunks'."""
    monkeypatch.setattr(kda, "SUPER", 16)
    q, k, v, g, beta = _inputs(1, 56, lens=[50], seed=3)
    o, S = kda.kda_scan(q, k, v, g, beta, chunk=8)
    want_o, want_S = kda.kda_recurrence(q[0, :50], k[0, :50], v[0, :50],
                                        g[0, :50], beta[0, :50])
    assert _rel(o[0, :50], want_o) < 2e-5 and _rel(S[0], want_S) < 2e-5


def test_the_strongest_decay_the_gate_allows_stays_finite():
    """Every channel at the lower bound for a whole chunk of 32: measured
    from the chunk's middle, exp(+-(G - G_m)) reaches exp(80), inside
    float32; the pairs above the diagonal overflow and are masked."""
    q, k, v, g, beta = _inputs(1, 64, seed=5)
    g = jnp.full_like(g, -5.0)
    assert kda.max_chunk(-5.0) == 32
    o, S = kda.kda_scan(q, k, v, g, beta, chunk=kda.max_chunk(-5.0))
    want_o, want_S = kda.kda_recurrence(q[0], k[0], v[0], g[0], beta[0])
    assert bool(jnp.all(jnp.isfinite(o))) and _rel(o[0], want_o) < 2e-5
    assert bool(jnp.all(jnp.isfinite(S))) and _rel(S[0], want_S) < 2e-5


def test_the_unit_lower_inverse_is_the_inverse():
    A = jnp.tril(jax.random.normal(jax.random.PRNGKey(1), (3, 16, 16)), -1)
    inv = kda._unit_lower_inverse(A * 0.3)
    eye = jnp.eye(16)
    assert float(jnp.abs(inv @ (eye + A * 0.3) - eye).max()) < 1e-5


@pytest.mark.parametrize("live", [[0, 1, 0, 1], [1, 1, 1, 1], [0, 0, 0, 0]])
def test_kda_update_is_one_recurrence_step_and_leaves_idle_lanes(live):
    """Layer 1 of three, four lanes: each live lane's state and output are
    one step of the recurrence from what it held; the idle lanes' and every
    other layer's state are bit-unchanged, the idle lanes' output 0."""
    L, nb, H, dk, dv = 3, 4, 4, 16, 16
    q, k, v, g, beta = (a[0] for a in _inputs(1, nb, seed=7))
    state = jax.random.normal(jax.random.PRNGKey(9), (L, nb, H, dk, dv))
    lanes, count = ssm.live_lanes(jnp.asarray(live, bool))
    new, y = jax.jit(kda.kda_update)(state, jnp.int32(1), lanes, count,
                                     q, k, v, g, beta)
    assert int(count) == sum(live)
    for lane in range(nb):
        if live[lane]:
            want_o, want_S = kda.kda_recurrence(
                q[lane:lane + 1], k[lane:lane + 1], v[lane:lane + 1],
                g[lane:lane + 1], beta[lane:lane + 1], state[1, lane])
            assert _rel(new[1, lane], want_S) < 1e-6
            assert _rel(y[lane], want_o[0]) < 1e-5
        else:
            assert bool(jnp.all(new[1, lane] == state[1, lane]))
            assert float(jnp.abs(y[lane]).max()) == 0.0
    assert bool(jnp.all(new[0] == state[0]) & jnp.all(new[2] == state[2]))


def test_the_update_continues_the_scan():
    """The state a prefill hands a lane, then decode steps: the same
    outputs as the recurrence over the whole sequence."""
    q, k, v, g, beta = _inputs(1, 21, seed=11)
    _, S = kda.kda_scan(q[:, :17], k[:, :17], v[:, :17], g[:, :17],
                        beta[:, :17], chunk=8)
    state = S[None]                                 # one layer, one lane
    lanes, count = ssm.live_lanes(jnp.asarray([True]))
    outs = []
    for t in range(17, 21):
        state, y = kda.kda_update(state, jnp.int32(0), lanes, count,
                                  q[:, t], k[:, t], v[:, t], g[:, t],
                                  beta[:, t])
        outs.append(y[0])
    want_o, want_S = kda.kda_recurrence(q[0], k[0], v[0], g[0], beta[0])
    assert _rel(jnp.stack(outs), want_o[17:]) < 2e-5
    assert _rel(state[0, 0], want_S) < 2e-5


def test_update_cost_counts_the_state_twice():
    fl, by = kda.update_cost(64, 128, 128, 10.0)
    assert by == 10 * (2 * 4 * 64 * 128 * 128 + 4 * 64 * (3 * 128 + 257))
    assert fl == 10 * 7.0 * 64 * 128 * 128
    assert np.isclose(by / 10, 8.55e6, rtol=0.01)
