"""The served KDA mixer (gated delta-rule linear attention with a
per-channel decay, the Kimi Linear layer; kernels in `ops/kda.py`), shared
by every serving module that has one (`models/glm5_next.py`,
`models/solar_open2.py`): the in-projection with its short convolution,
the output gate and head norm, the prompt pass around `kda_scan` and the
decode step around `kda_update`.

    q, k = L2Norm(silu(Conv(u W_q))), L2Norm(silu(Conv(u W_k)))
    v = silu(Conv(u W_v))          Conv: depthwise, causal, `conv_kernel`
    (log a, beta) = gate(u)        the CALLER's
    S_t = (I - beta k k^T) Diag(a) S_{t-1} + beta k v^T
    o = S_t^T q / sqrt(dk)
    y = W_o (RMSNorm_head(o) * sigmoid(u W_g1 W_g2))

Two things are the caller's to say.  The GATE: `gate(h, lp, cfg) -> (g
[..., H, dk], beta [..., H])` float32, its form and beta's range (GLM's
decay is bounded below and its beta a sigmoid; Solar-Open2's decay is the
published -exp(A_log) softplus(.) and its beta reaches 2).  A serving
module passes ITS OWN name for it, looked up at the call, so that a
test's control can stand in for that module's gate alone.  And whether
the gate has NO lower bound (`prefill(..., unbounded=True)`): `kda_scan`
then takes the form that is exact for any decay; else the form that
needs a bound, with `cfg.kda_chunk` within `ops/kda.max_chunk` of it.

`cfg` is the serving module's config; it gives `n_heads`, `kda_head_dim`,
`conv_kernel`, `kda_chunk`, `norm_eps`, `dtype` and `state_dtype`.  A
layer's weights: `norm1`, `w_qkv` [d, 3 H dk], `conv_w` [K, 3 H dk], the
gate's (`wf1`, `wf2`, `A_log`, `dt_bias`, `w_beta`), `wg1`, `wg2`,
`o_norm`, `wo` (`init_layer`).

Device-side names: `kda_in_proj`, `kda_conv` (over whole rows the kernel
of that name, `ops/kda.kda_conv`: the convolution, silu, the split and the
unit length in one pass over the projection, and the gather of the rows a
lane keeps; in a decode step the XLA expression `_qkv(_conv(...))`, one
token a lane), `kda_scan` (the prefill kernel) / `kda_update` (the decode
kernel), `kda_out`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import rmsnorm
from ray_tpu.ops import kda, live_rows

F32 = jnp.float32

# no residual path around: what a sublayer computes from its [..., d] input
# (`around` = (enter, leave): enter(x) -> (the sublayer's input, what leave
# needs); leave(x, that, y) -> what goes on; `models/glm5_next.mhc_halves`)
BARE = (lambda x: (x, ())), (lambda x, maps, y: y)


def init_layer(w, keys, cfg) -> dict:
    """A layer's KDA weights: matrices through the caller's `w(shape,
    fan_in)`; the gate's and the output gate's rank is the head's width;
    A_log = log(U(0.5, 2)) and dt_bias = U(-6, -1), which suit a gate
    that is a sigmoid of them (a family of another gate draws its own
    over these two)."""
    d, H, dk = cfg.dim, cfg.n_heads, cfg.kda_head_dim
    inner = H * dk
    return dict(
        w_qkv=w((d, 3 * inner), d),
        conv_w=w((cfg.conv_kernel, 3 * inner), cfg.conv_kernel),
        wf1=w((d, dk), d), wf2=w((dk, inner), dk),
        A_log=jnp.log(jax.random.uniform(next(keys), (H,), F32, 0.5, 2.0)),
        dt_bias=jax.random.uniform(next(keys), (inner,), F32, -6.0, -1.0),
        w_beta=w((d, H), d), wg1=w((d, dk), d), wg2=w((dk, inner), dk),
        o_norm=jnp.ones((dk,), cfg.dtype), wo=w((inner, d), inner))


def matmul_params(cfg) -> int:
    """W_q, W_k, W_v, W_o, the decay and output gates' low-rank pairs,
    beta."""
    d, H, r = cfg.dim, cfg.n_heads, cfg.kda_head_dim
    return 4 * d * H * r + 2 * (d * r + r * H * r) + d * H


def state_bytes(cfg) -> int:
    """Bytes of ONE lane's state in ONE layer: the heads' matrices and the
    convolution's last rows."""
    inner = cfg.n_heads * cfg.kda_head_dim
    return (inner * cfg.kda_head_dim * jnp.dtype(cfg.state_dtype).itemsize
            + (cfg.conv_kernel - 1) * 3 * inner
            * jnp.dtype(cfg.dtype).itemsize)


def _l2norm(x):
    xf = x.astype(F32)
    return xf * lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + 1e-6)


def _conv(rows, lp):
    """silu(sum_i conv_w[i] * rows[i]) in float32; rows oldest first."""
    acc = sum(r.astype(F32) * lp["conv_w"][i].astype(F32)
              for i, r in enumerate(rows))
    return jax.nn.silu(acc)


def _qkv(act, cfg):
    """The convolved projections [..., 3 inner] float32 -> (q scaled, k,
    v) [..., H, dk], q and k of unit length."""
    shape = act.shape[:-1] + (cfg.n_heads, cfg.kda_head_dim)
    q, k, v = (a.reshape(shape) for a in jnp.split(act, 3, axis=-1))
    return _l2norm(q) * cfg.kda_head_dim ** -0.5, _l2norm(k), v


def inputs(h, lp, cfg, true_lens, gate):
    """Everything the scan takes, over whole rows h [b, T, d] (normed):
    (q, k, v [b, T, H, dk] float32 as `_qkv(_conv(...))` has them, from
    ONE pass over the projection (`ops/kda.kda_conv`), g [b, T, H, dk],
    beta [b, T, H], all five ZERO at and past each row's true length,
    conv rows [b, K-1, 3 inner]: the pre-convolution rows before each
    row's TRUE length, zeros before position 0)."""
    T, K = h.shape[1], cfg.conv_kernel
    with jax.named_scope("kda_in_proj"):
        proj = h @ lp["w_qkv"]
        g, beta = gate(h, lp, cfg)
    with jax.named_scope("kda_conv"):
        q, k, v = kda.kda_conv(proj, lp["conv_w"], cfg.n_heads, true_lens)
        at = true_lens[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]
        rows = jnp.where((at >= 0)[..., None], jnp.take_along_axis(
            proj, jnp.maximum(at, 0)[..., None], axis=1), 0)
    live = jnp.arange(T)[None, :] < true_lens[:, None]
    return (q, k, v, jnp.where(live[..., None, None], g, 0.0),
            jnp.where(live[..., None], beta, 0.0), rows)


def out(o, h, lp, cfg):
    """W_o (RMSNorm_head(o) * sigmoid(u W_g1 W_g2)); o [..., H, dv]
    float32."""
    with jax.named_scope("kda_out"):
        gate = jax.nn.sigmoid(((h @ lp["wg1"]) @ lp["wg2"]).astype(F32))
        y = rmsnorm(o, lp["o_norm"], cfg.norm_eps).reshape(gate.shape)
        return (y * gate).astype(cfg.dtype) @ lp["wo"]


def prefill(x, lp, cfg, true_lens, gate, around=BARE,
            unbounded: bool = False):
    """The KDA mixer over whole rows x [b, T, d]: (what it computes,
    (conv rows [b, K-1, 3 inner], the state at each row's TRUE length
    [b, H, dk, dv] in `state_dtype`)).  What follows the scan (the head
    norm, the gate, `wo`) is computed a position alone and walks the rows
    up to the longest true length (`live_rows.walk`): zeros past the
    walked chunks.  What precedes it is not walked (walked, the copies of
    its chunks into the buffers the loop carries cost more than the
    padding skipped: PERF.md section 6, PR 55): the projection and the
    gate stay whole, and `kda_conv` writes q, k, v in place a block of
    positions at a time, zeros from a row's true length on, and fetches
    no block wholly past it (PERF.md section 6, PR 60).  `around`: the
    halves of a residual path (GLM's `mhc_halves`; `x + y` for a plain
    one), the second computed inside the walk; x is then whatever the
    first takes and so is the result."""
    enter, leave = around
    x_in, maps = enter(x)
    h = rmsnorm(x_in, lp["norm1"], cfg.norm_eps)
    q, k, v, g, beta, rows = inputs(h, lp, cfg, true_lens, gate)
    # the three conv rows a request are gathered BEFORE the scan: left to
    # the scheduler the gather came last in a program with the walks, and
    # every KDA layer's projection (0.4 GB) lived to its end
    q, rows = lax.optimization_barrier((q, rows))
    o, state = kda.kda_scan(q, k, v, g, beta, cfg.kda_chunk, true_lens,
                            unbounded=unbounded)

    def after(args, _first):
        x, maps, h, o = args
        return leave(x, maps, out(o, h, lp, cfg))

    return (live_rows.walk(after, (x, maps, h, o), jnp.max(true_lens)),
            (rows, state.astype(cfg.state_dtype)))


def decode_inputs(x, lp, conv, cfg, gate):
    """What `kda_update` takes for ONE token a lane: x [B, d], conv [B,
    K-1, 3 inner] (the lane's last pre-convolution rows).  Returns (h
    the normed input, (q, k, v [B, H, dk], g [B, H, dk], beta [B, H]),
    conv shifted by the token's row)."""
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    with jax.named_scope("kda_in_proj"):
        proj = h @ lp["w_qkv"]
        g, beta = gate(h, lp, cfg)
    with jax.named_scope("kda_conv"):
        q, k, v = _qkv(_conv([conv[:, i] for i in range(conv.shape[1])]
                             + [proj], lp), cfg)
        conv = jnp.concatenate([conv[:, 1:], proj[:, None]], axis=1)
    return h, (q, k, v, g, beta), conv


def decode(x, lp, conv, state, layer, lanes, count, cfg, gate):
    """One token of the KDA mixer for every lane: x [B, d], conv [B, K-1,
    3 inner], state the lanes' state of EVERY KDA layer (updated in place
    at `layer` for the listed lanes).  Returns (what it computes, conv,
    state)."""
    h, ins, conv = decode_inputs(x, lp, conv, cfg, gate)
    with jax.named_scope("kda_update"):
        state, o = kda.kda_update(state, layer, lanes, count, *ins)
    return out(o, h, lp, cfg), conv, state
