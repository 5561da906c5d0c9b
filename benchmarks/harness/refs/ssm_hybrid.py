"""Plain reference of the hybrid state-space decoder (`ssm_hybrid`:
`model_type` `granitemoehybrid` with no routed layer), float32
`jax.numpy`.  Imports nothing of the program under test.

The equations, from the model's `config.json` and transformers'
`modeling_granitemoehybrid.py` (torch path).  `x_0 = Embed[t] *
embedding_multiplier`; for layer l, r = `residual_multiplier`,

    x = x + r * Mixer_l(RMSNorm(x; norm1_l))
    x = x + r * W_2(silu(a) * b),  [a, b] = W_13 RMSNorm(x; norm2_l)

RMSNorm with `rms_norm_eps`; `logits = RMSNorm(x; final_norm) Embed^T /
logits_scaling` (`tie_word_embeddings`).

- `layer_types[l] == "attention"`: q, k, v = W_q u, W_k u, W_v u (no
  bias), heads of hidden / heads; NO position embedding
  (`position_embedding_type` "nope"); causal softmax of q k^T *
  `attention_multiplier`; each kv head serves heads / kv_heads query
  heads; W_o.
- `"mamba"` (Mamba-2, `mamba_n_groups` 1), per token t:
  [z, xBC, dt] = W_in u, split inner / inner + 2 N / heads (inner =
  `mamba_n_heads` x `mamba_d_head`, N = `mamba_d_state`);
  xBC_t = silu(conv_b + sum_i conv_w[i] xBC_{t-(K-1)+i}), K =
  `mamba_d_conv`, zeros before the sequence; x, B, C = split(xBC);
  dt_t = softplus(dt_t + dt_bias), A = -exp(A_log), a scalar a head;
  h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t, h a [head_dim, N]
  matrix a head; y_t = h_t C_t + D x_t;
  y = RMSNorm(y * silu(z); gate_norm) (the gate BEFORE the norm), W_out y.

Step 5 is the TOKEN-BY-TOKEN recurrence, a `lax.scan` over positions:
deliberately not the chunked form the program's prefill uses.  Full
causal attention, no cache, no batching: one sequence at once, a Python
loop over the layers.  Departures, each forced or harmless:
- parameters arrive in the layout of the program under test (layers
  stacked by KIND: `mamba` and `attn` are dicts of [layers of the kind,
  ...] arrays, in the order of `layer_types`; matrices
  input-major so y = x @ W; W_1 and W_3 side by side as `w13` [d, 2f];
  W_in's columns as two matrices, `in_zx` [d, inner + inner + 2 N] for
  z and xBC and `in_dt` [d, heads];
  `conv_w` [K, channels], row i the tap on xBC_{t-(K-1)+i}) and in the
  dtype it serves them in; cast to float32 here, one layer at a time;
- matmuls run under `default_matmul_precision("highest")`: on a TPU a
  float32 matmul is otherwise done in bfloat16 passes;
- a lane's state is returned as [N, heads x head_dim] (the heads'
  matrices transposed, side by side): the layout the program keeps it
  in, so that the two can be compared without a transpose of either;
- a layer's two halves, the head and the recurrence alone can be called
  one at a time (`mixer_half` / `mamba_half`, `mlp_half`, `head`,
  `recurrence`): the family's judge gives each the input the program's
  own block had.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

F32 = jnp.float32
ATTN = "attention"


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def attention_op(u, lp, m: dict):
    s, d = u.shape
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // nh
    q = (u @ lp["wq"].astype(F32)).reshape(s, nh, hd)
    k = (u @ lp["wk"].astype(F32)).reshape(s, nkv, hd)
    v = (u @ lp["wv"].astype(F32)).reshape(s, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * F32(m["attention_multiplier"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, nh * hd)
    return o @ lp["wo"].astype(F32)


def recurrence(x, dt, A, B, C):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t; y_t = h_t C_t,
    token by token.  x [s, H, P], dt [s, H], A [H], B, C [s, N], float32.
    Returns (y [s, H, P], the state after the last token [N, H * P])."""
    s, H, P = x.shape
    N = B.shape[-1]

    def step(h, t):
        xt, dtt, Bt, Ct = t
        h = (jnp.exp(dtt * A)[None, :, None] * h
             + Bt[:, None, None] * (dtt[:, None] * xt)[None])
        return h, jnp.einsum("n,nhp->hp", Ct, h)

    h, y = jax.lax.scan(step, jnp.zeros((N, H, P), F32), (x, dt, B, C))
    return y, h.reshape(N, H * P)


def mamba_inputs(u, lp, m: dict):
    """Steps 1-4: (z [s, inner], x [s, H, P], dt [s, H], B, C [s, N],
    the last K - 1 rows of xBC BEFORE the convolution: what a lane keeps
    of the sequence)."""
    s = u.shape[0]
    H, P, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    K, inner = m["mamba_d_conv"], H * P
    zx = u @ lp["in_zx"].astype(F32)
    z, xbc, dt = zx[:, :inner], zx[:, inner:], u @ lp["in_dt"].astype(F32)
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    kept = xp[s:]
    w = lp["conv_w"].astype(F32)
    xbc = jax.nn.silu(lp["conv_b"].astype(F32)
                      + sum(w[i] * xp[i:i + s] for i in range(K)))
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))
    return (z, xbc[:, :inner].reshape(s, H, P), dt,
            xbc[:, inner:inner + N], xbc[:, inner + N:], kept)


def mamba_op(u, lp, m: dict):
    """(W_out y, the state after the last token [N, inner], the last
    K - 1 pre-convolution rows [K - 1, inner + 2 N])."""
    s = u.shape[0]
    z, x, dt, B, C, kept = mamba_inputs(u, lp, m)
    y, h = recurrence(x, dt, -jnp.exp(lp["A_log"].astype(F32)), B, C)
    y = (y + lp["D"].astype(F32)[:, None] * x).reshape(s, -1)
    y = _rmsnorm(y * jax.nn.silu(z), lp["gate_norm"],
                 float(m["rms_norm_eps"]))
    return y @ lp["out_proj"].astype(F32), h, kept


def mlp(u, lp):
    f = lp["w2"].shape[0]
    ab = u @ lp["w13"].astype(F32)
    return (jax.nn.silu(ab[:, :f]) * ab[:, f:]) @ lp["w2"].astype(F32)


_JITTED: dict = {}


def _jitted(m: dict) -> dict:
    """The jitted pieces for model `m`, made once."""
    key = json.dumps(m, sort_keys=True, default=str)
    if key not in _JITTED:
        eps, r = float(m["rms_norm_eps"]), F32(m["residual_multiplier"])

        def mamba(x, lp):
            d, h, kept = mamba_op(_rmsnorm(x, lp["norm1"], eps), lp, m)
            return x + r * d, h, kept

        _JITTED[key] = {
            "attention": jax.jit(lambda x, lp: x + r * attention_op(
                _rmsnorm(x, lp["norm1"], eps), lp, m)),
            "mamba": jax.jit(mamba),
            "mlp": jax.jit(lambda x, lp: x + r * mlp(
                _rmsnorm(x, lp["norm2"], eps), lp)),
            "head": jax.jit(lambda x, n, e: _rmsnorm(x, n, eps)
                            @ e.astype(F32).T / F32(m["logits_scaling"])),
            "recurrence": jax.jit(recurrence),
        }
    return _JITTED[key]


_MLP_KEYS = ("norm2", "w13", "w2")


@jax.jit
def _cut(stack: dict, i):
    return {k: v[i] for k, v in stack.items()}


def layers(params: dict, m: dict):
    """(kind, the layer's own parameters) for each layer in order, cut
    from the two stacks the program holds them in, one a kind (one
    compiled slice a kind, the layer's number an argument)."""
    seen = {"mamba": 0, ATTN: 0}
    for kind in m["layer_types"]:
        stack = params["attn" if kind == ATTN else "mamba"]
        yield kind, _cut(stack, seen[kind])
        seen[kind] += 1


def mamba_half(x, lp: dict, m: dict):
    """The first half of a Mamba layer for x [s, d] float32: (x + r *
    Mixer(RMSNorm(x; norm1)), the state after the last token [N, inner],
    the last K - 1 pre-convolution rows)."""
    lp = {k: v for k, v in lp.items() if k not in _MLP_KEYS}
    with jax.default_matmul_precision("highest"):
        return _jitted(m)["mamba"](x, lp)


def mixer_half(x, lp: dict, kind: str, m: dict):
    """The first half of a layer for x [s, d] float32: (x + r *
    Mixer(RMSNorm(x; norm1)), the Mamba state after the last token
    [N, inner] or None)."""
    if kind != ATTN:
        return mamba_half(x, lp, m)[:2]
    lp = {k: v for k, v in lp.items() if k not in _MLP_KEYS}
    with jax.default_matmul_precision("highest"):
        return _jitted(m)["attention"](x, lp), None


def mlp_half(x, lp: dict, m: dict):
    with jax.default_matmul_precision("highest"):
        return _jitted(m)["mlp"](x, {k: lp[k] for k in _MLP_KEYS})


def head(x, params: dict, m: dict):
    """x [s, d] before the final norm -> logits [s, vocab]."""
    with jax.default_matmul_precision("highest"):
        return _jitted(m)["head"](x, params["final_norm"], params["embed"])


@jax.jit
def _embed(table, tokens, multiplier):
    return table[tokens].astype(F32) * multiplier


def embed(params: dict, tokens, m: dict):
    return _embed(params["embed"], jnp.asarray(tokens),
                  F32(m["embedding_multiplier"]))


def logits(params: dict, tokens, m: dict, last: int | None = None):
    """tokens [s] -> logits [s, vocab] float32 (the last `last` rows
    only, if given: the head is the widest matmul)."""
    x = embed(params, tokens, m)
    for kind, lp in layers(params, m):
        x, _ = mixer_half(x, lp, kind, m)
        x = mlp_half(x, lp, m)
    return head(x if last is None else x[-last:], params, m)


def token_gaps(params: dict, prompt: list[int], served: list[int],
               model: dict) -> list[float]:
    """For each served token: the reference's largest logit at that
    position minus the reference's logit OF the served token (0 when the
    reference would have chosen it too), given the prompt and the served
    tokens before it."""
    seq = list(prompt) + list(served[:-1])
    lg = logits(params, seq, model, last=len(served))
    return [float(g) for g in _below_the_top(lg, jnp.asarray(served))]


@jax.jit
def _below_the_top(lg, served):
    got = jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
    return jnp.max(lg, axis=-1) - got


# the name the harness's seam gives this quantity; what the family holds
# to its limits beside it is in `families/ssm_hybrid.py` (`Judge`)
teacher_forced_gaps = token_gaps
