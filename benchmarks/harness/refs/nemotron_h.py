"""Plain reference of the hybrid decoder whose layers are a mixer OR a
feed-forward part alone (`nemotron_h` with `moe_latent_size`), float32
`jax.numpy`.  Imports nothing of the program under test.

The equations, from the model's `config.json` and transformers'
`modeling_nemotron_h.py` (torch path).  `x_0 = Embed[t]`; layer l is ONE
residual branch, its kind the l-th letter of `hybrid_override_pattern`:

    x = x + Mixer_l(RMSNorm(x; norm_l))          (`NemotronHBlock`)

RMSNorm with `layer_norm_epsilon`; `logits = W_head RMSNorm(x; norm_f)`
(`tie_word_embeddings` false).

- `M` (`NemotronHMamba2Mixer`), per token t, u the normed input:
  [z, xBC, dt] = W_in u, split inner / inner + 2 G N / heads (inner =
  `mamba_num_heads` x `mamba_head_dim`, G = `n_groups`, N =
  `ssm_state_size`); xBC_t = silu(conv_b + sum_i conv_w[i]
  xBC_{t-(K-1)+i}), K = `conv_kernel`, zeros before the sequence; x, B,
  C = split(xBC), B and C a [G, N] each, head h reading group h //
  (heads / G); dt_t = softplus(dt_t + dt_bias) (`time_step_limit`
  unset: no clamp), A = -exp(A_log), a scalar a head; h_t = exp(dt_t A)
  h_{t-1} + dt_t x_t (outer) B_t, h a [head_dim, N] matrix a head; y_t =
  h_t C_t + D x_t; y = RMSNormGated(y, z) = RMSNorm of y * silu(z) taken
  over each GROUP's inner / G columns (`group_size`; the gate BEFORE the
  norm), times the weight; W_out y.
- `*` (`NemotronHAttention`): q, k, v = W_q u, W_k u, W_v u (no bias),
  heads of `head_dim`; NO position embedding (the model uses none:
  `rope_theta` is published and unused); causal softmax of q k^T *
  head_dim**-0.5; each kv head serves heads / kv_heads query heads; W_o.
- `E` (`NemotronHMOE`, `moe_latent_size` set): s = sigmoid(W_g u) over
  ALL `router_experts`, float32; SELECTED are the `num_experts_per_tok`
  largest of s + e_score_correction_bias (`n_group` = `topk_group` = 1:
  no group limit; the bias does not enter the weights); w_e =
  `routed_scaling_factor` * s_e / (sum over the selected + 1e-20)
  (`norm_topk_prob`); c = W_fc1 u; r = sum over the selected experts
  that lie in `experts_held` of w_e W2_e relu(W1_e c)**2 (what the
  experts on the other chips of the expert-parallel group would add is
  left out, as in the program: the configuration's cut); the layer adds
  W_fc2 r + W_s2 relu(W_s1 u)**2 (the shared expert, on u).

Step 5 is the TOKEN-BY-TOKEN recurrence, a `lax.scan` over positions:
deliberately not the chunked form the program's prefill uses.  Full
causal attention, no cache, no batching: one sequence at once, a Python
loop over the layers.  Departures, each forced or harmless:
- parameters arrive in the layout of the program under test (a list of
  per-layer dicts, matrices input-major so y = x @ W; `conv_w` [K,
  channels], row i the tap on xBC_{t-(K-1)+i}; the held experts as `w1`
  [held, latent, f], `w2` [held, f, latent]) and in the dtype it serves
  them in; cast to float32 here, a piece at a time;
- matmuls run under `default_matmul_precision("highest")`: on a TPU a
  float32 matmul is otherwise done in bfloat16 passes;
- attention runs a block of QUERIES at a time, so that the [heads, s,
  s] scores of a long sequence fit beside the served weights;
- the loop over experts multiplies every position by every held expert
  and masks by the router's weight: it never gathers by the choice;
- a lane's state is returned as [N, heads x head_dim] (the heads'
  matrices transposed, side by side): the layout the program keeps it
  in, so that the two can be compared without a transpose of either;
- a layer can be called alone (`layer`), and so can the recurrence, the
  gated norm, the router and the two parts of an `E` layer: the family's
  judge gives each the input the program's own block had.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

F32 = jnp.float32
MAMBA, MOE, ATTN = "M", "E", "*"
QUERY_BLOCK = 512           # queries a block of the attention's scores
KEYS = {MAMBA: ("norm", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log",
                "D", "gate_norm", "out_proj"),
        ATTN: ("norm", "wq", "wk", "wv", "wo"),
        MOE: ("norm", "router", "expert_bias", "fc1", "w1", "w2", "fc2",
              "sw1", "sw2")}


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _eps(m: dict) -> float:
    return float(m["layer_norm_epsilon"])


# ------------------------------------------------------------------ `M`
def recurrence(x, dt, A, B, C, h0=None):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t; y_t = h_t C_t,
    token by token.  x [s, H, P], dt [s, H], A [H], B, C [s, G, N] (head
    h reads group h // (H / G)), float32; h0 the state before [N, H * P]
    (absent: zeros).  Returns (y [s, H, P], the state after the last
    token [N, H * P])."""
    s, H, P = x.shape
    G, N = B.shape[1:]
    per = H // G

    def step(h, t):
        xt, dtt, Bt, Ct = t
        Bh, Ch = jnp.repeat(Bt, per, axis=0), jnp.repeat(Ct, per, axis=0)
        h = (jnp.exp(dtt * A)[None, :, None] * h
             + Bh.T[:, :, None] * (dtt[:, None] * xt)[None])   # [N, H, P]
        return h, jnp.einsum("hn,nhp->hp", Ch, h)

    h0 = jnp.zeros((N, H, P), F32) if h0 is None else h0.reshape(N, H, P)
    h, y = jax.lax.scan(step, h0, (x, dt, B, C))
    return y, h.reshape(N, H * P)


def mamba_inputs(u, lp, m: dict):
    """Steps 1-4: (z [s, inner], x [s, H, P], dt [s, H], B, C [s, G, N],
    the last K - 1 rows of xBC BEFORE the convolution: what a lane keeps
    of the sequence)."""
    s = u.shape[0]
    H, P, N = m["mamba_num_heads"], m["mamba_head_dim"], m["ssm_state_size"]
    G, K, inner = m["n_groups"], m["conv_kernel"], H * P
    zxd = u @ lp["in_proj"].astype(F32)
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:2 * inner + 2 * G * N],
                  zxd[:, 2 * inner + 2 * G * N:])
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    kept = xp[s:]
    w = lp["conv_w"].astype(F32)
    xbc = jax.nn.silu(lp["conv_b"].astype(F32)
                      + sum(w[i] * xp[i:i + s] for i in range(K)))
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))
    return (z, xbc[:, :inner].reshape(s, H, P), dt,
            xbc[:, inner:inner + G * N].reshape(s, G, N),
            xbc[:, inner + G * N:].reshape(s, G, N), kept)


def gated_norm(y, z, w, m: dict):
    """RMSNormGated: y * silu(z), RMSNorm over each group's inner / G
    columns, times the weight [inner]."""
    g = y * jax.nn.silu(z)
    g = g.reshape(g.shape[0], m["n_groups"], -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + _eps(m))
    return g.reshape(y.shape) * w.astype(F32)


def mamba_op(u, lp, m: dict):
    """(W_out y, the state after the last token [N, inner], the last
    K - 1 pre-convolution rows [K - 1, inner + 2 G N])."""
    s = u.shape[0]
    z, x, dt, B, C, kept = mamba_inputs(u, lp, m)
    y, h = recurrence(x, dt, -jnp.exp(lp["A_log"].astype(F32)), B, C)
    y = (y + lp["D"].astype(F32)[:, None] * x).reshape(s, -1)
    return gated_norm(y, z, lp["gate_norm"], m) @ lp["out_proj"].astype(F32), \
        h, kept


# ------------------------------------------------------------------ `*`
def attention_op(u, lp, m: dict):
    """(W_o of the causal attention, k, v [s, kv heads, head_dim]: what a
    cache would hold)."""
    s = u.shape[0]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    q = (u @ lp["wq"].astype(F32)).reshape(s, nh, hd)
    k = (u @ lp["wk"].astype(F32)).reshape(s, nkv, hd)
    v = (u @ lp["wv"].astype(F32)).reshape(s, nkv, hd)
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
    return o @ lp["wo"].astype(F32), k, v


# ------------------------------------------------------------------ `E`
def router(u, lp, m: dict):
    """(weights [s, router_experts]: w_e at the selected experts and 0
    elsewhere, margin [s]: by how much the last selected score beats the
    first one left out, bias counted)."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(u @ lp["router"].astype(F32))
    top, idx = jax.lax.top_k(s + lp["expert_bias"].astype(F32), k + 1)
    chosen = jnp.zeros_like(s, bool).at[
        jnp.arange(s.shape[0])[:, None], idx[:, :k]].set(True)
    w = jnp.where(chosen, s, 0.0)
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * F32(m["routed_scaling_factor"]), top[:, k - 1] - top[:, k]


def _relu2(a):
    return jnp.square(jnp.maximum(a, 0.0))


def moe_op(u, lp, m: dict):
    """(W_fc2 of the held experts' part, the shared expert's part, the
    routing margin [s]): the layer adds the first two."""
    w, margin = router(u, lp, m)
    c = u @ lp["fc1"].astype(F32)
    lo, hi = m["experts_held"]

    def expert(acc, e):
        y = _relu2(c @ lp["w1"][e].astype(F32)) @ lp["w2"][e].astype(F32)
        return acc + jnp.take(w, lo + e, axis=1)[:, None] * y, None

    r, _ = jax.lax.scan(expert, jnp.zeros_like(c), jnp.arange(hi - lo))
    shared = _relu2(u @ lp["sw1"].astype(F32)) @ lp["sw2"].astype(F32)
    return r @ lp["fc2"].astype(F32), shared, margin


# ------------------------------------------------------------ the model
_JITTED: dict = {}


def _jitted(m: dict) -> dict:
    """The jitted pieces for model `m`, made once."""
    key = json.dumps(m, sort_keys=True, default=str)
    if key not in _JITTED:
        def normed(fn):
            return jax.jit(lambda x, lp: fn(
                _rmsnorm(x, lp["norm"], _eps(m)), lp, m))

        _JITTED[key] = {
            MAMBA: normed(mamba_op), ATTN: normed(attention_op),
            MOE: normed(moe_op),
            "head": jax.jit(lambda x, n, w: _rmsnorm(x, n, _eps(m))
                            @ w.astype(F32)),
            "recurrence": jax.jit(recurrence),
        }
    return _JITTED[key]


def layer(x, lp: dict, kind: str, m: dict):
    """Layer of `kind` for x [s, d] float32: (x + Mixer(RMSNorm(x)),
    info: {"state", "conv"} of an `M` layer, {"k", "v"} of a `*` layer,
    {"routed", "shared", "margin"} of an `E` layer)."""
    lp = {k: lp[k] for k in KEYS[kind]}
    with jax.default_matmul_precision("highest"):
        out = _jitted(m)[kind](x, lp)
    if kind == MAMBA:
        return x + out[0], {"state": out[1], "conv": out[2]}
    if kind == ATTN:
        return x + out[0], {"k": out[1], "v": out[2]}
    return x + out[0] + out[1], {"routed": out[0], "shared": out[1],
                                 "margin": out[2]}


def head(x, params: dict, m: dict):
    """x [s, d] before the final norm -> logits [s, vocab]."""
    with jax.default_matmul_precision("highest"):
        return _jitted(m)["head"](x, params["final_norm"],
                                  params["lm_head"])


def embed(params: dict, tokens):
    return params["embed"][jnp.asarray(tokens)].astype(F32)


def logits(params: dict, tokens, m: dict, last: int | None = None):
    """tokens [s] -> logits [s, vocab] float32 (the last `last` rows
    only, if given: the head is the widest matmul)."""
    x = embed(params, tokens)
    for kind, lp in zip(m["hybrid_override_pattern"], params["layers"]):
        x, _ = layer(x, lp, kind, m)
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
# to its limits beside it is in `families/nemotron_h.py` (`Judge`)
teacher_forced_gaps = token_gaps
