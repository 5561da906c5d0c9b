"""Plain reference of the LFM2-MoE decoder (`lfm2_moe`), float32
`jax.numpy`.  Imports nothing of the program under test.

The equations, from the model's `config.json` and transformers'
`modeling_lfm2_moe.py`.  `x_0 = Embed[t]`; for layer l

    h  = x + Op_l(RMSNorm(x; operator_norm_l))
    x' = h + FF_l(RMSNorm(h; ffn_norm_l))

RMSNorm with `norm_eps`; at the end `RMSNorm(x; embedding_norm)`, then
the head, which is the embedding transposed (tied: the LFM2 family ties
it; the config does not say, so the configuration file lists it under
`assumed`).

- Op = attention where `layer_types[l] == "full_attention"`: q = W_q x,
  k = W_k x, v = W_v x (no bias), split into heads of hidden/heads; q and
  k RMS-normed over the head dimension, one weight vector each shared by
  the heads; RoPE (`rope_theta`, default type, rotate-half pairing) on q
  and k; causal softmax attention, scale head_dim**-0.5, each kv head
  serving heads/kv_heads query heads; W_o.
- Op = short convolution elsewhere: [B, C, u] = split3(W_in x);
  z = B * u; c_t = sum_j w[j] * z_{t-(L-1)+j} for j < L = `conv_L_cache`
  (depthwise, causal, no bias, z zero before the sequence); y = C * c;
  W_out y.
- FF for l < `num_dense_layers`: W_2(silu(W_1 x) * W_3 x).
- FF elsewhere: s = sigmoid(W_g x); SELECTED are the top
  `num_experts_per_tok` of s + expert_bias (`use_expert_bias`); weights
  w_i = s_i of the selected (the bias does not enter them);
  `norm_topk_prob`: w /= (sum w + 1e-6); w *= `routed_scaling_factor`;
  y = sum_i w_i W2_i(silu(W1_i x) * W3_i x).  Every assignment is
  computed; nothing is dropped.

No kernels, no cache, no batching: the whole sequence at once, a Python
loop over the layers and over the experts.  Departures, each forced or
harmless:
- parameters arrive in the layout of the program under test (a list of
  per-layer dicts, matrices input-major so y = x @ W; the experts' W_1
  and W_3 side by side as `w13` [E, d, 2f]; `conv_w` [L, d], row j the
  tap on z_{t-(L-1)+j}) and in the dtype it serves them in; they are
  cast to float32 here, one layer (one expert) at a time;
- matmuls run under `default_matmul_precision("highest")`: on a TPU a
  float32 matmul is otherwise done in bfloat16 passes;
- the loop over experts multiplies every position by every expert and
  masks (plain, and dense): it never gathers by the choice;
- a layer's two halves and the head can be called one at a time
  (`op_half`, `ff_half`, `head`): the family's judge gives each the
  input the program's own half had (`families/lfm2_moe.Judge`), and
  `ff_half` reports each position's routing margin by its own scores.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

F32 = jnp.float32
ATTN = "full_attention"


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, theta):
    """x [s, heads, hd]; position p rotates the pair (i, i + hd/2) by
    p * theta**(-2i/hd)."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_op(h, lp, m: dict):
    s, d = h.shape
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // nh
    eps = float(m["norm_eps"])
    theta = float(m["rope_parameters"]["rope_theta"])
    q = (h @ lp["wq"].astype(F32)).reshape(s, nh, hd)
    k = (h @ lp["wk"].astype(F32)).reshape(s, nkv, hd)
    v = (h @ lp["wv"].astype(F32)).reshape(s, nkv, hd)
    q = _rope(_rmsnorm(q, lp["q_norm"], eps), theta)
    k = _rope(_rmsnorm(k, lp["k_norm"], eps), theta)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, nh * hd)
    return o @ lp["wo"].astype(F32)


def conv_op(h, lp, m: dict):
    s, d = h.shape
    L = m["conv_L_cache"]
    B, C, u = jnp.split(h @ lp["w_in"].astype(F32), 3, axis=-1)
    z = jnp.concatenate([jnp.zeros((L - 1, d), F32), B * u])
    w = lp["conv_w"].astype(F32)
    c = sum(w[j] * z[j:j + s] for j in range(L))
    return (C * c) @ lp["w_out"].astype(F32)


def dense_ff(h, lp):
    return (jax.nn.silu(h @ lp["w1"].astype(F32))
            * (h @ lp["w3"].astype(F32))) @ lp["w2"].astype(F32)


def router(h, lp, m: dict):
    """(weights [s, E]: w_i at the selected experts and 0 elsewhere,
    margin [s]: by how much the last selected score beats the first one
    left out, bias counted)."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ lp["router"].astype(F32))
    sel = s + lp["expert_bias"].astype(F32) if m["use_expert_bias"] else s
    top, idx = jax.lax.top_k(sel, k + 1)
    chosen = jnp.zeros_like(s, bool).at[
        jnp.arange(s.shape[0])[:, None], idx[:, :k]].set(True)
    w = jnp.where(chosen, s, 0.0)
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return w * F32(m["routed_scaling_factor"]), top[:, k - 1] - top[:, k]


def expert(h, w13, w2):
    f = w2.shape[0]
    a = h @ w13.astype(F32)
    return (jax.nn.silu(a[:, :f]) * a[:, f:]) @ w2.astype(F32)


_JITTED: dict = {}


def _jitted(m: dict) -> dict:
    """The jitted pieces for model `m`, made once (a loop of scored
    sequences must not compile them again)."""
    key = json.dumps(m, sort_keys=True, default=str)
    if key not in _JITTED:
        _JITTED[key] = _make_jitted(m)
    return _JITTED[key]


def _make_jitted(m: dict) -> dict:
    eps = float(m["norm_eps"])
    return {
        "attn": jax.jit(lambda x, lp: x + attention_op(
            _rmsnorm(x, lp["op_norm"], eps), lp, m)),
        "conv": jax.jit(lambda x, lp: x + conv_op(
            _rmsnorm(x, lp["op_norm"], eps), lp, m)),
        "dense": jax.jit(lambda x, lp: x + dense_ff(
            _rmsnorm(x, lp["ffn_norm"], eps), lp)),
        "norm": jax.jit(lambda x, w: _rmsnorm(x, w, eps)),
        "router": jax.jit(lambda h, lp: router(h, lp, m)),
        # one expert's part, added to `acc`; the expert is picked from
        # the layer's arrays INSIDE the program (an eager slice of 25 MB
        # per expert and matrix was most of this reference's wall time)
        "expert": jax.jit(lambda acc, h, w, w13, w2, e:
                          acc + w[:, e, None] * expert(h, w13[e], w2[e])),
        "head": jax.jit(lambda x, n, e: _rmsnorm(x, n, eps)
                        @ e.astype(F32).T),
    }


def op_half(x, lp: dict, lid: int, m: dict):
    """The first half of layer `lid`: x + Op(RMSNorm(x; operator_norm))
    for x [s, d] float32."""
    op = "attn" if m["layer_types"][lid] == ATTN else "conv"
    with jax.default_matmul_precision("highest"):
        return _jitted(m)[op](x, {k: v for k, v in lp.items()
                                  if k not in ("w13", "w2", "w1", "w3")})


def ff_half(x, lp: dict, lid: int, m: dict):
    """The second half of layer `lid`: (x + FF(RMSNorm(x; ffn_norm)),
    margin [s]: by how much the last selected score beats the first one
    left out at each position; None for a dense layer)."""
    fn = _jitted(m)
    with jax.default_matmul_precision("highest"):
        if lid < m["num_dense_layers"]:
            return fn["dense"](x, {k: lp[k] for k in
                                   ("ffn_norm", "w1", "w3", "w2")}), None
        h = fn["norm"](x, lp["ffn_norm"])
        w, margin = fn["router"](h, {k: lp[k] for k in
                                     ("router", "expert_bias")})
        y = jnp.zeros_like(x)
        for e in range(m["num_experts"]):
            y = fn["expert"](y, h, w, lp["w13"], lp["w2"], e)
        return x + y, margin


def head(x, params: dict, m: dict):
    """x [s, d] before the final norm -> logits [s, vocab]."""
    with jax.default_matmul_precision("highest"):
        return _jitted(m)["head"](x, params["final_norm"], params["embed"])


def logits(params: dict, tokens, m: dict, last: int | None = None):
    """tokens [s] -> logits [s, vocab] float32 (the last `last` rows
    only, if given: the head is the widest matmul)."""
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    for lid, lp in enumerate(params["layers"]):
        x, _ = ff_half(op_half(x, lp, lid, m), lp, lid, m)
    return head(x if last is None else x[-last:], params, m)


def token_gaps(params: dict, prompt: list[int], served: list[int],
               model: dict) -> list[float]:
    """For each served token: the reference's largest logit at that
    position minus the reference's logit OF the served token (0 when the
    reference would have chosen it too), given the prompt and the served
    tokens before it.  The reference decides every expert choice itself;
    it is never told the served path's."""
    seq = list(prompt) + list(served[:-1])
    lg = logits(params, seq, model, last=len(served))
    top = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], -1)[:, 0]
    return [float(g) for g in (top - got)]


# the name the harness's seam gives this quantity; what the `lfm2_moe`
# family holds to its limits, and why not the worst token alone, is in
# `families/lfm2_moe.py` (`Judge`)
teacher_forced_gaps = token_gaps
