"""Plain reference of the `solar_open2` decoder (`model_type`
`solar_open2`, e.g. Solar-Open2-250B): KDA linear-attention layers whose
decay gate has no lower bound and whose write strength reaches 2, three
in four, beside a gated softmax GQA layer without position embedding,
every layer routed with a shared expert.  float32 `jax.numpy`; imports
nothing of the program under test.

The equations, from the model's `config.json` keys (no network in the
sandbox: what the keys leave open is marked ASSUMED here and listed under
`assumed` in the configuration file; u = RMSNorm(x; `rms_norm_eps`,
weight)).  Every layer

    x <- x + Mixer(RMSNorm(x));  x <- x + MoE(RMSNorm(x))

and logits = RMSNorm(x_L) W_head (`tie_word_embeddings` false).

GQA mixer (layer l in `gqa_layers`): q = u W_q (`num_attention_heads` x
`head_dim`), k = u W_k, v = u W_v (`num_key_value_heads` x `head_dim`), no
rotary embedding (`use_rope` false), no bias, causal softmax at
head_dim^-0.5, each key/value head serving heads / kv heads query heads;
o <- o * sigmoid(u W_gate) with W_gate [hidden, heads x head_dim], an
element a gate (`use_gqa_gate`; ASSUMED: the Qwen3-Next form, the config
names only the switch); y = o W_o.

KDA mixer (the other layers; `linear_attn_config`: `num_heads` x
`head_dim`, `num_kv_heads` null: v has the q heads), per head: q, k =
L2Norm(silu(Conv(u W_q))), L2Norm(silu(Conv(u W_k))), v = silu(Conv(u
W_v)) (depthwise causal convolution of `short_conv_kernel_size`, zeros
before the sequence); log a = -exp(A_log) softplus(u W_f1 W_f2 + dt_bias)
a key channel, in (-inf, 0] and NOT clamped (the published Kimi Linear
layer's gate; `kda_use_full_proj` false: the low-rank pair, its rank and
the output gate's = head_dim: ASSUMED); beta = 2 sigmoid(u W_beta) a head
(`kda_allow_neg_eigval`: I - beta k k^T then has an eigenvalue in (-1,
1)); token by token S_t = (I - beta k k^T) Diag(a) S_{t-1} + beta k v^T,
o_t = S_t^T q_t head_dim^-0.5; y = W_o(RMSNorm_head(o) * sigmoid(u W_g1
W_g2)).  The state is float32 (ASSUMED).

MoE (every layer: `first_k_dense_replace` 0; `intermediate_size` is then
unused): sigmoid scores over ALL `router_experts` in float32, the top
`num_experts_per_tok` of score + bias selected (ASSUMED: Solar Open's
`glm4_moe` lineage; the config gives `norm_topk_prob` and the factor
only), w = `routed_scaling_factor` score / (sum over the selected +
1e-6), the sum over the selected SwiGLU experts (ASSUMED: silu, the
config has no `hidden_act`) in `experts_held` (the cut: what the other
chips' experts would add is left out, as in the program) plus ONE shared
SwiGLU expert of `n_shared_experts` x `moe_intermediate_size` (ASSUMED
width) for every token.

No kernels, no cache, no batching: one sequence at once, Python loops
over layers, a scan over the held experts and over blocks of queries.
Departures, each forced or harmless: parameters arrive in the program's
layout and dtype and are cast to float32 a piece at a time (`w_qkv` =
[W_q | W_k | W_v] side by side, `w13` = the held experts' W_1 and W_3
side by side); matmuls under `default_matmul_precision("highest")`; the
attention's scores a block of QUERY_BLOCK queries at a time; the experts'
loop multiplies every position by every held expert and masks.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256           # queries a block of the attention's scores
MIXER_KEYS = {
    "kda": ("norm1", "w_qkv", "conv_w", "wf1", "wf2", "A_log", "dt_bias",
            "w_beta", "wg1", "wg2", "o_norm", "wo"),
    "gqa": ("norm1", "wq", "wk", "wv", "w_gate", "wo")}
FF_KEYS = ("norm2", "router", "expert_bias", "w13", "w2", "sw1", "sw3",
           "sw2")


def _f(a):
    return a.astype(F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f(w)


def _eps(m: dict):
    return F32(m["rms_norm_eps"])


def kind(lid: int, m: dict) -> str:
    return "gqa" if lid in m["gqa_layers"] else "kda"


# ---------------------------------------------------------------- KDA mixer
def kda_inputs(u, lp, m: dict):
    """u [s, d] (normed) -> (q scaled, k, v [s, H, dk], log a [s, H, dk],
    beta [s, H], the pre-convolution rows [s, 3 H dk])."""
    la = m["linear_attn_config"]
    H, dk, K = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    s = u.shape[0]
    proj = u @ _f(lp["w_qkv"])
    xp = jnp.pad(proj, ((K - 1, 0), (0, 0)))
    conv = sum(xp[i:i + s] * _f(lp["conv_w"][i]) for i in range(K))
    q, k, v = (a.reshape(s, H, dk)
               for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    f = (u @ _f(lp["wf1"])) @ _f(lp["wf2"])
    g = -jnp.repeat(jnp.exp(_f(lp["A_log"])), dk) * jax.nn.softplus(
        f + _f(lp["dt_bias"]))
    factor = 2.0 if m["kda_allow_neg_eigval"] else 1.0
    beta = factor * jax.nn.sigmoid(u @ _f(lp["w_beta"]))
    return (l2(q) * dk ** -0.5, l2(k), v, g.reshape(s, H, dk), beta, proj)


def recurrence(q, k, v, g, beta, state=None):
    """Token by token.  Returns (o [s, H, dv], the state after [H, dk,
    dv])."""
    H, dk = k.shape[1:]
    if state is None:
        state = jnp.zeros((H, dk, v.shape[-1]), F32)

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[:, :, None] * S
        u = bt[:, None] * (vt - jnp.einsum("hd,hdv->hv", kt, S))
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hd,hdv->hv", qt, S)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def kda_mix(x, lp, m: dict):
    """x [s, d] -> (y [s, d], the state after the last token [H, dk, dv],
    the last K-1 pre-convolution rows [K-1, 3 H dk])."""
    K = m["linear_attn_config"]["short_conv_kernel_size"]
    u = _rmsnorm(x, lp["norm1"], _eps(m))
    q, k, v, g, beta, proj = kda_inputs(u, lp, m)
    o, state = recurrence(q, k, v, g, beta)
    gate = jax.nn.sigmoid((u @ _f(lp["wg1"])) @ _f(lp["wg2"]))
    y = (_rmsnorm(o, lp["o_norm"], _eps(m)).reshape(gate.shape) * gate) \
        @ _f(lp["wo"])
    kept = jnp.pad(proj, ((K - 1, 0), (0, 0)))[-(K - 1):]
    return y, state, kept


# ---------------------------------------------------------------- GQA mixer
def gqa_mix(x, lp, m: dict):
    """x [s, d] -> (y [s, d], k, v [s, kv heads, head_dim]: what a cache
    would hold)."""
    s = x.shape[0]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    u = _rmsnorm(x, lp["norm1"], _eps(m))
    q = (u @ _f(lp["wq"])).reshape(s, nh, hd)
    k = (u @ _f(lp["wk"])).reshape(s, nkv, hd)
    v = (u @ _f(lp["wv"])).reshape(s, nkv, hd)
    kk = jnp.repeat(k, nh // nkv, axis=1)
    vv = jnp.repeat(v, nh // nkv, axis=1)
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, nh, hd)
    at = jnp.arange(s + pad).reshape(-1, qb)

    def block(args):
        qi, pos = args
        scores = jnp.einsum("qhd,khd->hqk", qi, kk) * F32(hd ** -0.5)
        causal = jnp.arange(s)[None, :] <= pos[:, None]
        att = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                             axis=-1)
        return jnp.einsum("hqk,khd->qhd", att, vv)

    o = jax.lax.map(block, (qs, at)).reshape(s + pad, nh * hd)[:s]
    if m["use_gqa_gate"]:
        o = o * jax.nn.sigmoid(u @ _f(lp["w_gate"]))
    return o @ _f(lp["wo"]), k, v


# ------------------------------------------------------------ feed-forward
def router(h, lp, m: dict):
    """(weights [s, router_experts]: w_e at the selected experts and 0
    elsewhere, margin [s]: the last selected score over the first left
    out, bias counted)."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ _f(lp["router"]))
    top, idx = jax.lax.top_k(s + _f(lp["expert_bias"]), k + 1)
    picked = jnp.zeros_like(s, bool).at[
        jnp.arange(s.shape[0])[:, None], idx[:, :k]].set(True)
    w = jnp.where(picked, s, 0.0)
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return w * F32(m["routed_scaling_factor"]), top[:, k - 1] - top[:, k]


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ _f(w1)) * (h @ _f(w3))) @ _f(w2)


def ff(x, lp, m: dict):
    """x [s, d] -> (the held experts' part [s, d], the shared expert's
    part [s, d], the routing margin [s]): the layer adds the first two.
    The loop over the held experts multiplies every position by every
    expert and masks by the weights."""
    h = _rmsnorm(x, lp["norm2"], _eps(m))
    w, margin = router(h, lp, m)
    lo, hi = m["experts_held"]
    f = lp["w2"].shape[1]

    def expert(acc, e):         # e: the place in the held arrays
        y = _swiglu(h, lp["w13"][e][:, :f], lp["w13"][e][:, f:], lp["w2"][e])
        return acc + jnp.take(w, lo + e, axis=1)[:, None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(hi - lo))
    return routed, _swiglu(h, lp["sw1"], lp["sw3"], lp["sw2"]), margin


# ------------------------------------------------------------- the decoder
_JITTED: dict = {}


def _jitted(m: dict) -> dict:
    key = json.dumps(m, sort_keys=True, default=str)
    if key not in _JITTED:
        _JITTED[key] = {
            "kda": jax.jit(lambda x, lp: kda_mix(x, lp, m)),
            "gqa": jax.jit(lambda x, lp: gqa_mix(x, lp, m)),
            "ff": jax.jit(lambda x, lp: ff(x, lp, m)),
            "head": jax.jit(lambda x, n, w: _rmsnorm(x, n, _eps(m)) @ _f(w)),
            "recurrence": jax.jit(recurrence),
            # a KDA layer's gate alone, from the layer's input: (log a
            # [s, H, dk], beta [s, H])
            "gate": jax.jit(lambda x, lp: kda_inputs(
                _rmsnorm(x, lp["norm1"], _eps(m)), lp, m)[3:5]),
        }
    return _JITTED[key]


def mixer(x, lp, lid: int, m: dict):
    """The mixer of layer `lid` from its input x [s, d]: (y, info): info
    = {"state", "conv"} of a KDA layer, {"k", "v"} of a GQA layer."""
    which = kind(lid, m)
    # the mixer's own weights only: layers of a kind share a program
    lp = {k: lp[k] for k in MIXER_KEYS[which]}
    with jax.default_matmul_precision("highest"):
        out = _jitted(m)[which](x, lp)
    if which == "kda":
        return out[0], {"state": out[1], "conv": out[2]}
    return out[0], {"k": out[1], "v": out[2]}


def layer(x, lp, lid: int, m: dict):
    """(x after layer `lid`, x between its mixer and its MoE, info: the
    mixer's beside {"routed", "shared", "margin"})."""
    y, info = mixer(x, lp, lid, m)
    x_mid = x + y
    with jax.default_matmul_precision("highest"):
        routed, shared, margin = _jitted(m)["ff"](
            x_mid, {k: lp[k] for k in FF_KEYS})
    return x_mid + routed + shared, x_mid, dict(
        info, routed=routed, shared=shared, margin=margin)


def embed(params: dict, tokens):
    return _f(params["embed"][jnp.asarray(tokens)])


def head(x, params: dict, m: dict):
    """x [s, d] before the final norm -> logits [s, vocab]."""
    with jax.default_matmul_precision("highest"):
        return _jitted(m)["head"](x, params["final_norm"],
                                  params["lm_head"])


def logits(params: dict, tokens, m: dict, last: int | None = None):
    """tokens [s] -> logits [s, vocab] float32 (the last `last` rows only,
    if given: the head is the widest matmul)."""
    x = embed(params, tokens)
    for lid, lp in enumerate(params["layers"]):
        x = layer(x, lp, lid, m)[0]
    return head(x if last is None else x[-last:], params, m)


def token_gaps(params: dict, prompt: list[int], served: list[int],
               model: dict) -> list[float]:
    """For each served token: the reference's largest logit at that
    position minus the reference's logit OF the served token (0 when the
    reference would have chosen it too), given the prompt and the served
    tokens before it."""
    seq = list(prompt) + list(served[:-1])
    lg = logits(params, seq, model, last=len(served))
    got = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], -1)[:, 0]
    return [float(x) for x in (jnp.max(lg, axis=-1) - got)]


# the name the harness's seam gives this quantity; what the family holds
# to its limits beside it is in `families/solar_open2.py` (`Judge`)
teacher_forced_gaps = token_gaps
