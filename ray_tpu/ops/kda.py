"""Gated delta-rule linear attention with a per-channel decay (KDA, the
Kimi Linear layer) for serving: the chunked scan a prefill runs and the
one-step update a decode step runs.

The recurrence.  A head keeps a state matrix S [dk, dv] in float32; for
token t with a key k_t and a query q_t [dk] (the caller normalises and
scales them), a value v_t [dv], a decay a_t in (0, 1]^dk (one number a
KEY CHANNEL, not one a head: `g_t = log a_t` is what is passed) and a
write strength beta_t in [0, 1]:

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

i.e. the state is decayed a row at a time, then CORRECTED by a rank-one
term that needs `k_t^T Diag(a_t) S_{t-1}` before the write.  Not
`ops/ssm.py`'s rule (a scalar decay a head and a plain outer-product
input): here the update reads the decayed state before it writes it.

`kda_update` (Pallas, `pallas_call(name="kda_update")`): one decode step
of one layer.  The state array [layers, lanes, H, dk, dv] is read and
written THROUGH `input_output_aliases`; the grid is a work list of the
LIVE lanes (`ops/ssm.live_lanes`), one step a lane, the layer a
scalar-prefetched index.  A lane that holds no request gets no step:
its 4 MB a layer are neither read nor written.  Inside a step every
head's [dk, dv] block is walked twice (decay and `k^T S`; then the
write and `q^T S`), dk down the sublanes and dv along the lanes, so both
sums are adds of whole registers; the per-channel vectors (a, k, q)
arrive as [dk, H] so that a head's is a column.

`kda_scan` (XLA, under `jax.named_scope("kda_scan")`): the same
recurrence over whole rows in the chunked WY form.  Within a chunk of C
positions, with G_t = g_1 + ... + g_t (per channel, from the chunk's
start) and S_0 the state at its start,

    A_tj = beta_t sum_d k_t[d] k_j[d] exp(G_t[d] - G_j[d])      (j < t)
    (I + A) U = beta * (V - (K * exp(G)) S_0)       a TRIANGULAR solve
    o_t = (q_t * exp(G_t))^T S_0 + sum_{j<=t} B_tj u_j,
        B_tj = sum_d q_t[d] k_j[d] exp(G_t[d] - G_j[d])
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

A and B are products of K exp(G - G_m) with K exp(G_m - G), G_m the
chunk's MIDDLE (the reference cancels in every pair; on and below the
diagonal the exponents add up to at most 0): either factor grows with
half the chunk, so the chunk is bounded by the gate's lower bound (C / 2
* |bound| <= 80, float32's range: `max_chunk`; 32 positions at the
published -5; pairs above the diagonal may overflow and are masked).
The inverse of the unit lower-triangular I + A is the finite product
(I - A)(I + A^2)(I + A^4)... (A is nilpotent), log2(C) small matmuls.
Everything that does not need S_0 is computed for `SUPER` positions at
once; only `U = U0 - W S_0`, the output and the state's step run a chunk
after the other.  The caller sets g = 0 and beta = 0 past a row's true
length: the state returned IS the state at the true length.  All in
float32 at `Precision.HIGHEST`: a lane keeps that state for hundreds of
steps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import flash_attention

F32 = jnp.float32
_HI = lax.Precision.HIGHEST
SUPER = 1024        # positions whose chunk-local parts are made at once


def _interpret() -> bool:
    return flash_attention._interpret()


def max_chunk(gate_lower_bound: float) -> int:
    """The longest chunk `kda_scan` may take for a log-decay bounded
    below by `gate_lower_bound` (< 0): exp(+-(G - G_middle)) must stay
    in float32."""
    return max(1, 2 * int(80.0 / max(abs(gate_lower_bound), 1e-6)))


def kda_recurrence(q, k, v, g, beta, state=None):
    """The recurrence token by token (the oracle of both kernels).
    q, k, g [T, H, dk]; v [T, H, dv]; beta [T, H]; state [H, dk, dv] or
    None (zeros).  Returns (o [T, H, dv], the state after) in float32."""
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    H, dk, dv = k.shape[1], k.shape[2], v.shape[2]
    if state is None:
        state = jnp.zeros((H, dk, dv), F32)

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[:, :, None] * S
        kS = jnp.einsum("hd,hdv->hv", kt, S, precision=_HI)
        S = S + kt[:, :, None] * (bt[:, None] * (vt - kS))[:, None, :]
        return S, jnp.einsum("hd,hdv->hv", qt, S, precision=_HI)

    state, o = lax.scan(step, state.astype(F32), (q, k, v, g, beta))
    return o, state


def _unit_lower_inverse(A):
    """(I + A)^-1 for A [..., C, C] strictly lower triangular."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=F32)
    X = -A
    inv = eye + X
    n = 2
    while n < C:
        X = jnp.matmul(X, X, precision=_HI)
        inv = jnp.matmul(inv, eye + X, precision=_HI)
        n *= 2
    return inv


def _chunk_parts(q, k, v, g, beta, C: int):
    """What a stretch's chunks need that does not depend on the carried
    state.  q, k, g [b, H, n, C, dk]; v [b, H, n, C, dv]; beta [b, H, n,
    C].  Returns (QW [.., 2 C, dk]: Qe over W, what multiplies the carried
    state in ONE pass over it; U0 [.., C, dv]; O0 [.., C, dv]; Ke [.., C,
    dk]; dec [.., dk])."""
    G = jnp.cumsum(g, axis=-2)
    rel = G - G[..., (C - 1) // 2:(C - 1) // 2 + 1, :]  # from the middle
    up, down = jnp.exp(rel), jnp.exp(-rel)
    low = jnp.tril(jnp.ones((C, C), bool), -1)
    A = jnp.where(low, jnp.einsum("...td,...jd->...tj", k * up, k * down,
                                  precision=_HI), 0.0) * beta[..., None]
    Bm = jnp.where(low | jnp.eye(C, dtype=bool),
                   jnp.einsum("...td,...jd->...tj", q * up, k * down,
                              precision=_HI), 0.0)
    T = _unit_lower_inverse(A)
    decay = jnp.exp(G)                    # from the chunk's start: <= 1
    W = jnp.matmul(T, k * decay * beta[..., None], precision=_HI)
    U0 = jnp.matmul(T, v * beta[..., None], precision=_HI)
    Qe = q * decay - jnp.matmul(Bm, W, precision=_HI)
    O0 = jnp.matmul(Bm, U0, precision=_HI)
    Ke = k * jnp.exp(G[..., -1:, :] - G)
    return (jnp.concatenate([Qe, W], axis=-2), U0, O0, Ke,
            decay[..., -1, :])


def kda_scan(q, k, v, g, beta, chunk: int):
    """The recurrence over whole rows, chunked.

    q, k [b, T, H, dk] (normalised; q scaled); v [b, T, H, dv]; g
    [b, T, H, dk] float32 (the log decay, <= 0, ZERO past a row's true
    length); beta [b, T, H] float32 (ZERO past it); `chunk` positions a
    chunk (`max_chunk` bounds it).  Returns (o [b, T, H, dv] float32,
    the state after the last position [b, H, dk, dv] float32)."""
    b, T, H, dk = k.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    big = min(SUPER, -(-T // C) * C)          # a stretch: whole chunks
    big -= big % C
    pad = -T % big
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    ns, n = (T + pad) // big, big // C
    with jax.named_scope("kda_scan"):
        def stretches(a):       # [b, T, H, ...] -> [ns, b, H, n, C, ...]
            a = a.reshape(b, ns, n, C, H, *a.shape[3:])
            return jnp.moveaxis(jnp.moveaxis(a, 4, 2), 1, 0)

        def stretch(S, xs):
            qs, ks, vs, gs, bs = (a.astype(F32) for a in xs)
            parts = _chunk_parts(qs, ks, vs, gs, bs, C)

            def one(S, p):                    # a chunk, from its state S
                QW, U0, O0, Ke, dec = p
                qs = jnp.matmul(QW, S, precision=_HI)        # [.., 2 C, dv]
                U = U0 - qs[..., C:, :]
                S = dec[..., None] * S + jnp.einsum(
                    "bhcd,bhcv->bhdv", Ke, U, precision=_HI)
                return S, qs[..., :C, :] + O0

            S, o = lax.scan(one, S, tuple(jnp.moveaxis(p, 2, 0)
                                          for p in parts))
            return S, jnp.moveaxis(o, 0, 2)   # [b, H, n, C, dv]

        S, o = lax.scan(stretch, jnp.zeros((b, H, dk, dv), F32),
                        tuple(stretches(a) for a in (q, k, v, g, beta)))
        # [ns, b, H, n, C, dv] -> [b, T, H, dv]
        o = jnp.moveaxis(o, 0, 1).reshape(b, ns, H, n * C, dv)
        o = jnp.moveaxis(o, 2, 3).reshape(b, ns * big, H, dv)
    return o[:, :T], S


def _update_kernel(lanes_ref, layer_ref,              # scalar prefetch
                   s_ref, cols_ref, rows_ref,         # blocked inputs
                   o_ref, y_ref):
    """One lane's step, a head after the other.  s_ref [1, 1, H, dk, dv];
    cols_ref [1, 3, dk, H] = (a, k, q) with a head's vector a COLUMN;
    rows_ref [1, 2, H, dv] = (v, beta repeated along dv)."""
    del lanes_ref, layer_ref                          # the index maps' own
    H, dk, dv = s_ref.shape[2], s_ref.shape[3], s_ref.shape[4]
    a_all, k_all, q_all = cols_ref[0, 0], cols_ref[0, 1], cols_ref[0, 2]
    for h in range(H):                        # unrolled: static columns
        def col(x):
            return jnp.broadcast_to(x[:, h:h + 1], (dk, dv))

        a, k, q = col(a_all), col(k_all), col(q_all)
        v = rows_ref[0, 0, h:h + 1, :]                # [1, dv]
        beta = rows_ref[0, 1, h:h + 1, :]
        # first pass: the decayed state, and k^T of it
        s = a * s_ref[0, 0, h].astype(F32)
        u = beta * (v - jnp.sum(k * s, axis=0, keepdims=True))
        # second pass: the rank-one write, and q^T of the result
        s = s + k * u
        o_ref[0, 0, h] = s.astype(o_ref.dtype)
        y_ref[0, h:h + 1, :] = jnp.sum(q * s, axis=0, keepdims=True)


def kda_update(state, layer, lanes, count, q, k, v, g, beta):
    """One token's update of layer `layer` for the `count` lanes
    `lanes[:count]` (`ops/ssm.live_lanes`), in place.

    state [layers, lanes, H, dk, dv] float32 (donated: the result
    aliases it); layer a scalar int32; q, k, g [lanes, H, dk] (q scaled,
    g the log decay); v [lanes, H, dv]; beta [lanes, H].  Returns (state,
    o [lanes, H, dv] float32 = S^T q after the write; rows of lanes
    outside the list are 0)."""
    L, nb, H, dk, dv = state.shape
    cols = jnp.stack([jnp.exp(g.astype(F32)), k.astype(F32),
                      q.astype(F32)], axis=1)          # [nb, 3, H, dk]
    cols = jnp.swapaxes(cols, 2, 3)                    # [nb, 3, dk, H]
    rows = jnp.stack([v.astype(F32), jnp.broadcast_to(
        beta.astype(F32)[..., None], (nb, H, dv))], axis=1)

    def state_map(i, lanes, layer):
        return (layer[0], lanes[i], 0, 0, 0)

    def lane_map4(i, lanes, layer):
        return (lanes[i], 0, 0, 0)

    def lane_map3(i, lanes, layer):
        return (lanes[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(count,),                        # the device's own number
        in_specs=[pl.BlockSpec((1, 1, H, dk, dv), state_map),
                  pl.BlockSpec((1, 3, dk, H), lane_map4),
                  pl.BlockSpec((1, 2, H, dv), lane_map4)],
        out_specs=[pl.BlockSpec((1, 1, H, dk, dv), state_map),
                   pl.BlockSpec((1, H, dv), lane_map3)],
    )
    block = H * dk * dv * state.dtype.itemsize
    new, y = pl.pallas_call(
        _update_kernel,
        name="kda_update",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((nb, H, dv), F32)],
        # operand 2 (after the two prefetched scalars) is the state
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the lane's state in and out, double-buffered, and room
            vmem_limit_bytes=max(32 << 20, 6 * block)),
        interpret=_interpret(),
    )(lanes, jnp.reshape(layer, (1,)).astype(jnp.int32), state, cols, rows)
    listed = jnp.any((lanes[None, :] == jnp.arange(nb)[:, None])
                     & (jnp.arange(nb)[None, :] < count), axis=1)
    return new, jnp.where(listed[:, None, None], y, 0.0)


def update_cost(H: int, dk: int, dv: int, lane_steps: float
                ) -> tuple[float, float]:
    """(flops, bytes) the `kda_update` calls NEED for `lane_steps`
    (lane, layer, step) triples that were work: the lane's state read
    and written once (float32), a, k, q, v, beta in and o out (float32),
    and a state element's decay, its two sums and its write (a multiply,
    two multiply-adds, a multiply-add)."""
    nbytes = 2 * 4 * H * dk * dv + 4 * H * (3 * dk + 2 * dv + 1)
    return 7.0 * H * dk * dv * lane_steps, float(nbytes) * lane_steps
