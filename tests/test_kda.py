"""ops/kda.py (the gated delta rule with a per-channel decay): the
scan kernel in BOTH its forms (under a gate's lower bound, the form a
call that says nothing gets: the chunk's middle anchors every pair;
`unbounded`: pairs anchored by halves) and
the one-step kernel (interpret mode) against the token-by-token
recurrence, at lengths that end mid-chunk, across position blocks, with
blocks past a row's length that get no step, with lanes that hold no
request.  (`tests/test_solar_open2.py` holds the exact form where only it
stands: beta near 2 on repeated keys, decays of -30 a step.)  And
`kda_conv`, what precedes the scan in one pass, against the XLA expression
a decode step keeps (`models/kda_layer._qkv(_conv(...))`)."""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kda_layer
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


@pytest.mark.parametrize("unbounded", [True, False])
@pytest.mark.parametrize("T,chunk,lens", [
    (40, 16, [40, 27]),     # a row whose true length ends mid-chunk
    (16, 16, [16, 1]),      # one chunk; a row of one token
    (7, 16, [7, 5]),        # shorter than a chunk
    (64, 8, [33, 64]),      # many chunks
    (70, 32, [70, 41]),     # the served chunk
    (24, 1, [24, 9]),       # a chunk a position: the recurrence itself
])
def test_kda_scan_equals_the_recurrence_at_the_true_length(T, chunk, lens,
                                                           unbounded):
    """Both forms: what a gate bounded at -5 allows, and the exact one."""
    q, k, v, g, beta = _inputs(2, T, lens=lens)
    o, S = jax.jit(lambda *a: kda.kda_scan(
        *a, chunk=chunk, unbounded=unbounded))(q, k, v, g, beta)
    for i, n in enumerate(lens):
        want_o, want_S = kda.kda_recurrence(
            q[i, :n], k[i, :n], v[i, :n], g[i, :n], beta[i, :n])
        assert _rel(o[i, :n], want_o) < 2e-5
        assert _rel(S[i], want_S) < 2e-5


def test_kda_scan_carries_the_state_between_stretches(monkeypatch):
    """The state lives in the kernel's scratch from a position block to
    the next (a block of 16, two chunks of 8): it crosses the blocks'
    edges as it crosses the chunks'."""
    monkeypatch.setattr(kda, "POSITIONS", 16)
    q, k, v, g, beta = _inputs(1, 56, lens=[50], seed=3)
    o, S = kda.kda_scan(q, k, v, g, beta, chunk=8)
    want_o, want_S = kda.kda_recurrence(q[0, :50], k[0, :50], v[0, :50],
                                        g[0, :50], beta[0, :50])
    assert _rel(o[0, :50], want_o) < 2e-5 and _rel(S[0], want_S) < 2e-5


@pytest.mark.parametrize("T,lens", [
    (56, [50, 56]),     # the last block crossed; a full row
    (64, [32, 48]),     # lengths on block edges
    (48, [1, 48]),      # a row of one token
    (64, [9, 64]),      # a row shorter than one block beside a full one
])
def test_blocks_past_a_rows_length_get_no_step(monkeypatch, T, lens):
    """With `lengths`: the same o at the true positions and the same
    state as without, and zeros from the first block that starts at or
    past the length (blocks of 16 positions)."""
    monkeypatch.setattr(kda, "POSITIONS", 16)
    q, k, v, g, beta = _inputs(2, T, lens=lens, seed=13)
    o, S = kda.kda_scan(q, k, v, g, beta, chunk=8)
    o_len, S_len = kda.kda_scan(q, k, v, g, beta, chunk=8,
                                lengths=jnp.asarray(lens, jnp.int32))
    assert _rel(S_len, S) < 1e-6
    for i, n in enumerate(lens):
        assert _rel(o_len[i, :n], o[i, :n]) < 1e-6
        past = -(-n // 16) * 16
        assert float(jnp.abs(o_len[i, past:]).sum()) == 0.0
        want_o, want_S = kda.kda_recurrence(
            q[i, :n], k[i, :n], v[i, :n], g[i, :n], beta[i, :n])
        assert _rel(o_len[i, :n], want_o) < 2e-5
        assert _rel(S_len[i], want_S) < 2e-5


@pytest.mark.parametrize("H,heads", [(3, 1), (6, 3), (4, 2), (5, 5)])
def test_a_grid_step_takes_a_divisor_of_the_heads(monkeypatch, H, heads):
    """`HEADS` is an upper bound: H = 3 under 2 heads a step runs one a
    step, and every head's state and output are the recurrence's."""
    monkeypatch.setattr(kda, "HEADS", {3: 2, 6: 4, 4: 2, 5: 8}[H])
    assert kda.heads_a_step(H) == heads
    q, k, v, g, beta = _inputs(1, 24, H=H, seed=17)
    o, S = kda.kda_scan(q, k, v, g, beta, chunk=8)
    want_o, want_S = kda.kda_recurrence(q[0], k[0], v[0], g[0], beta[0])
    assert _rel(o[0], want_o) < 2e-5 and _rel(S[0], want_S) < 2e-5


def test_the_chip_takes_whole_tiles_and_names_the_shape_otherwise():
    kda.scan_tiles(128, 128, 32)
    kda.scan_tiles(256, 128, 8)
    for dk, dv, chunk in [(16, 16, 8), (128, 64, 32), (128, 128, 4)]:
        with pytest.raises(ValueError, match=f"dk={dk}, dv={dv}, "
                                             f"chunk={chunk}"):
            kda.scan_tiles(dk, dv, chunk)


def test_off_the_interpreter_a_debug_width_is_refused(monkeypatch):
    monkeypatch.setattr(kda, "_interpret", lambda: False)
    q, k, v, g, beta = _inputs(1, 16)
    with pytest.raises(ValueError, match="dk=16"):
        kda.kda_scan(q, k, v, g, beta, chunk=8)


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


@pytest.mark.parametrize("n,C", [(16, 16), (32, 8), (24, 1), (12, 6)])
def test_the_unit_lower_inverse_is_the_inverse(n, C):
    """A group's matrix: strictly lower triangular inside its diagonal
    blocks of C, zero outside them."""
    at = jnp.arange(n)
    own = (at[:, None] // C == at[None, :] // C) & (at[None, :] < at[:, None])
    A = jnp.where(own, jax.random.normal(jax.random.PRNGKey(1), (n, n)), 0)
    inv, = kda._unit_lower_inverse([A * 0.3], C)
    eye = jnp.eye(n)
    assert float(jnp.abs(inv @ (eye + A * 0.3) - eye).max()) < 1e-5


def _summed_powers(A):
    """(I + A)^-1 as the finite product (I - A)(I + A^2)(I + A^4)...: what
    the kernel formed before PR 58."""
    n = A.shape[0]
    inv, X, m = jnp.eye(n) - A, A @ A, 2
    while m < n:
        inv, X, m = inv + inv @ X, X @ X, 2 * m
    return inv


@pytest.mark.parametrize("beta,g,off", [(1.99, 0.0, 1e3), (1.99, -0.01, 1e3),
                                        (0.99, 0.0, 1.0), (0.99, -0.01, 1.0)])
def test_the_inverse_stands_where_the_summed_powers_cancel(beta, g, off):
    """A chunk of 32 whose keys repeat at a slow decay g a step: A's entry
    (t, j) is beta exp(g (t - j)), the true inverse's entries stay under
    2, the power series' terms reach binomial(32, 16) beta^32 and cancel
    to nothing: at a write strength inside (0, 1) too (GLM's range)."""
    n = 32
    at = jnp.arange(n)
    A = jnp.where(at[None, :] < at[:, None],
                  beta * jnp.exp(g * (at[:, None] - at[None, :])), 0.0)
    want = jnp.linalg.inv(jnp.eye(n) + A)
    inv, = kda._unit_lower_inverse([A], n)
    assert float(jnp.abs(inv - want).max()) < 1e-4
    assert float(jnp.abs(_summed_powers(A) - want).max()) > off


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


def test_scan_cost_is_the_rows_in_and_out_and_the_chunks_products():
    """At the served widths: 5 x 4 bytes a channel a position beside
    beta, the state a row; a (head, chunk) is ~5 MFLOP, two thirds of it
    the two products with the [128, 128] state."""
    fl, by = kda.scan_cost(64, 128, 128, 32, 8192.0, rows=1.0)
    assert by == 8192 * 4 * 64 * (5 * 128 + 1) + 4 * 64 * 128 * 128
    assert np.isclose(by, 1.35e9, rtol=0.01)
    C, d = 32, 128
    chunk = (2 * 2 * C * C * d            # A, B
             + 8 * 2 * C ** 3             # the inverse's products
             + 2 * 2 * C * C * d          # W, U0
             + 2 * 2 * C * d * d          # [Qd; W] S
             + 2 * C * C * d              # B U
             + 2 * C * d * d)             # Ke^T U
    assert fl == chunk * 64 * 8192 / 32
    assert np.isclose(chunk, 4.98e6, rtol=0.01)
    # a chunk of 8: the inverse is (I - A)(I + A^2)(I + A^4), 4 products
    fl8, _ = kda.scan_cost(1, 16, 16, 8, 8.0)
    assert fl8 == (4 * 2 * 8 * 8 * 16 + 4 * 2 * 8 ** 3
                   + 2 * 8 * 8 * 16 + 3 * 2 * 8 * 16 * 16)
    # by halves the pairs are formed once a LEVEL (five at a chunk of 32,
    # three at 8), the inverse in as many products as before
    flh, byh = kda.scan_cost(64, 128, 128, 32, 8192.0, rows=1.0, halved=True)
    assert byh == by
    assert flh - fl == 4 * 2 * 2 * C * C * d * 64 * 8192 / 32
    fl8h, _ = kda.scan_cost(1, 16, 16, 8, 8.0, halved=True)
    assert fl8h - fl8 == 2 * 4 * 8 * 8 * 16


def _projection(b, T, H, dk, K, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(ks[0], (b, T, 3 * H * dk)).astype(dtype),
            (0.5 * jax.random.normal(ks[1], (K, 3 * H * dk))).astype(dtype))


def _padded_rows(proj, K):
    """The projection under K - 1 rows of zeros: what the convolution
    reads before position 0."""
    return jnp.pad(proj, ((0, 0), (K - 1, 0), (0, 0)))


@pytest.mark.parametrize("T,lens,H,dk,K,P,heads,dtype", [
    (40, [40, 27], 4, 16, 4, 16, 2, jnp.bfloat16),  # ragged; inside a block
    (64, [32, 1], 4, 16, 4, 16, 2, jnp.bfloat16),   # at a block's edge; of 1
    (48, [17, 33], 4, 16, 4, 16, 4, jnp.bfloat16),  # an edge in the reach
    (50, [50, 18], 3, 16, 3, 32, 8, jnp.float32),   # T no multiple; K = 3
    (7, None, 2, 8, 2, 1024, 4, jnp.bfloat16),      # a short row, whole
    (160, [160, 130], 2, 128, 4, 128, 1, jnp.bfloat16),     # the served dk
])
def test_kda_conv_is_the_convolved_projection_split_and_of_unit_length(
        monkeypatch, T, lens, H, dk, K, P, heads, dtype):
    """q, k, v of ONE pass over the projection are `_qkv(_conv(...))` of
    its shifted slices below each row's length and zeros from it on,
    whatever the projection holds there (NaN here); blocks of P positions
    (a block's first K - 1 positions read the block's before) and of
    `heads` heads (two column blocks a section at H = 4 under 2)."""
    monkeypatch.setattr(kda, "CONV_POSITIONS", P)
    monkeypatch.setattr(kda, "CONV_HEADS", heads)
    proj, conv_w = _projection(2, T, H, dk, K, dtype, seed=T)
    xp = _padded_rows(proj, K)
    want = kda_layer._qkv(
        kda_layer._conv([xp[:, i:i + T] for i in range(K)],
                        {"conv_w": conv_w}),
        SimpleNamespace(n_heads=H, kda_head_dim=dk))
    live = jnp.arange(T)[None, :] < jnp.asarray(lens or [T, T])[:, None]
    got = jax.jit(lambda x, w, n: kda.kda_conv(x, w, H, n))(
        jnp.where(live[..., None], proj, jnp.nan), conv_w,
        None if lens is None else jnp.asarray(lens, jnp.int32))
    for g, w in zip(got, want):
        assert g.shape == (2, T, H, dk) and g.dtype == jnp.float32
        for i, n in enumerate(lens or [T, T]):
            assert float(jnp.max(jnp.abs(g[i, :n] - w[i, :n]))) < 2e-6
            assert float(jnp.abs(g[i, n:]).sum()) == 0.0


def test_kda_conv_names_what_it_cannot_take(monkeypatch):
    proj, conv_w = _projection(1, 16, 2, 16, 10, jnp.bfloat16)
    with pytest.raises(ValueError, match="at most 9 positions; got 10"):
        kda.kda_conv(proj, conv_w, 2)
    monkeypatch.setattr(kda, "_interpret", lambda: False)
    with pytest.raises(ValueError, match="kda_conv on the chip.*dk=16"):
        kda.kda_conv(proj, conv_w[:4], 2)


@pytest.mark.parametrize("lens", [[0, 1], [2, 3], [4, 24]])
def test_the_layers_inputs_hand_the_rows_before_each_true_length(lens):
    """`kda_layer.inputs` hands the K - 1 pre-convolution rows before each
    row's TRUE length, zeros standing for the rows before position 0 (a
    length under K - 1), and g, beta zeroed from the length on."""
    T, H, dk, K, d = 24, 2, 16, 4, 8
    cfg = SimpleNamespace(n_heads=H, kda_head_dim=dk, conv_kernel=K)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    lp = {"w_qkv": jax.random.normal(ks[0], (d, 3 * H * dk), jnp.bfloat16),
          "conv_w": jax.random.normal(ks[1], (K, 3 * H * dk), jnp.bfloat16)}
    h = jax.random.normal(ks[2], (2, T, d), jnp.bfloat16)

    def gate(h, lp, cfg):
        return (-jnp.ones(h.shape[:2] + (H, dk), jnp.float32),
                jnp.ones(h.shape[:2] + (H,), jnp.float32))

    n = jnp.asarray(lens, jnp.int32)
    q, k, v, g, beta, rows = kda_layer.inputs(h, lp, cfg, n, gate)
    xp = _padded_rows(h @ lp["w_qkv"], K)
    assert rows.dtype == xp.dtype and rows.shape == (2, K - 1, 3 * H * dk)
    for i, m in enumerate(lens):
        assert bool(jnp.all(rows[i] == xp[i, m:m + K - 1]))
        assert float(jnp.abs(g[i, m:]).sum() + jnp.abs(beta[i, m:]).sum()
                     + jnp.abs(q[i, m:]).sum()) == 0.0
        assert bool(jnp.all(g[i, :m] == -1.0) & jnp.all(beta[i, :m] == 1.0))
