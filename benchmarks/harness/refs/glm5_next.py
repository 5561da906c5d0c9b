"""Plain reference of the `glm5_next` decoder (`model_type`
`glm5_next_text`, e.g. GLM-5.3-Flash): KDA linear-attention layers beside
latent-attention layers read through a learned selection, a multi-stream
residual (mHC) around every sublayer, routed experts with a shared
expert.  float32 `jax.numpy`; imports nothing of the program under test.

The equations, from the model's `config.json` keys (no network in the
sandbox: what the keys leave open is marked ASSUMED here and listed under
`assumed` in the configuration file; h = RMSNorm(.), eps `rms_norm_eps`).

Residual path (`mhc`; n = `hc_mult`, `hc_sinkhorn_iters`, `hc_eps`): the
stream is X [n, d] a token, X_0 = the embedding copied to the n rows;
every sublayer F is wrapped

    x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)
    H_pre = sigmoid(a_pre (x~ phi_pre) + b_pre), H_post = 2 sigmoid(a_post
    (x~ phi_post) + b_post), H_res = Sinkhorn(exp(a_res mat(x~ phi_res) +
    b_res)) (rows then columns normalised, `hc_sinkhorn_iters` times)
    X <- H_res X + H_post (outer) F(H_pre X)

and logits = h(sum of the rows of X_L) W_head (ASSUMED: summed; untied).

KDA mixer (`layer_types` "linear_attention"; `linear_attn_config`), u =
h(input), per head: q, k = L2Norm(silu(Conv(u W_q))), L2Norm(silu(Conv(u
W_k))), v = silu(Conv(u W_v)) (depthwise causal convolution of
`short_conv_kernel_size`, zeros before the sequence); log a =
gate_lower_bound * sigmoid(exp(A_log) (u W_f1 W_f2 + dt_bias)) a key
channel (ASSUMED form; rank of W_f, W_g = head_dim: ASSUMED); beta =
sigmoid(u W_beta); token by token S_t = (I - beta k k^T) Diag(a) S_{t-1}
+ beta k v^T, o_t = S_t^T q_t head_dim^-0.5; y = W_o(RMSNorm_head(o) *
sigmoid(u W_g1 W_g2)).  The state is float32 (ASSUMED).

Sparse latent mixer ("deepseek_sparse_attention"): c^q = h(u W_qa), q_i
= (c^q W_qb)_i; c = h(u W_kva); k_i = c W_UK,i, v_i = c W_UV,i; no RoPE
(`mla_use_nope`, `qk_rope_head_dim` 0).  Indexer (`index_n_heads` J,
`index_head_dim` w): q^I_j = RoPE(c^q W_qI)_j, k^I = RoPE(LayerNorm(u
W_kI)), weights (J w)^-0.5 u W_w; RoPE ASSUMED on the first 64 of the
width, interleaved pairs (`indexer_rope_interleave`), theta 10,000.
`index_kpool` g, `index_kpool_compress`: ONE index key a COMPLETE group
of g positions, the MEAN of its keys (ASSUMED: the mean; only it is
kept).  score I_{t,G} = sum_j w_{t,j} relu(q^I_{t,j} . kbar_G) over the
complete groups (g G + g - 1 <= t); selected(t) = the positions of the
`index_topk` / g best groups (ASSUMED: top-k counted in tokens) UNION the
positions of t's own incomplete group (`index_kpool_always_select_tail`)
that are <= t; o_i = softmax over selected(t) of head_dim^-0.5 q_i . k_i
times v_i; y = concat(o_i) W_o.  (`pool_index_keys`, `select_rows`: the
two functions that hold the assumed mechanism.)

Feed-forward: SwiGLU of `intermediate_size` where `mlp_layer_types` says
"dense"; elsewhere sigmoid scores over ALL `router_experts`, the top
`num_experts_per_tok` of score + bias selected (`noaux_tc`; `n_group` 1),
w = `routed_scaling_factor` score / (sum over the selected + 1e-6), the
sum over the selected experts in `experts_held` (the cut: what the other
chips' experts would add is left out, as in the program) plus the shared
expert.  Every SwiGLU clamped (`swiglu_limit` L; ASSUMED form: gate <-
min(gate, L), up <- clip(up, -L, L)).

Not here, as not in the program: the multi-token-prediction layer and the
vision tower.

No kernels, no cache, no batching: one sequence at once, Python loops
over layers, experts and blocks of heads.  Departures, each forced or
harmless: parameters arrive in the program's layout and dtype and are
cast to float32 a piece at a time (`w_qkv` = [W_q | W_k | W_v] side by
side, `w13` = the held experts' W_1 and W_3 side by side, `w_uk` [H, qk,
r], `w_uv` [H, r, v]); matmuls under `default_matmul_precision
("highest")`; attention a block of heads at a time; the experts' loop
multiplies every position by every held expert and masks; a sparse
mixer may be GIVEN the chosen groups (`chosen`), for a judge that
compares both sides under one selection.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30
INDEX_ROPE_DIM = 64         # ASSUMED
INDEX_THETA = 10000.0       # ASSUMED
KDA, DSA = "linear_attention", "deepseek_sparse_attention"
FF_KEYS = ("norm2", "w1", "w3", "w2", "router", "expert_bias", "w13", "sw1",
           "sw3", "sw2", "hc_ffn", "hc_mix")


def _f(a):
    return a.astype(F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f(w)


def _clamped_swiglu(gate, up, m):
    lim = F32(m["swiglu_limit"])
    return jax.nn.silu(jnp.minimum(gate, lim)) * jnp.clip(up, -lim, lim)


# ------------------------------------------------------- the residual path
def sinkhorn(mat, iters: int):
    for _ in range(iters):
        mat = mat / jnp.sum(mat, -1, keepdims=True)
        mat = mat / jnp.sum(mat, -2, keepdims=True)
    return mat


def mhc_maps(X, hp, m: dict):
    """X [s, n, d] -> (H_pre [s, n], H_post [s, n], H_res [s, n, n])."""
    n = m["hc_mult"]
    flat = X.reshape(X.shape[0], -1)
    xt = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                              + F32(m["hc_eps"]))
    z = xt @ _f(hp["phi"])
    a, b = _f(hp["a"]), _f(hp["b"])
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    return pre, post, sinkhorn(res, m["hc_sinkhorn_iters"])


def mhc_in(X, pre):
    return jnp.einsum("sn,snd->sd", pre, X)


def mhc_out(X, y, post, res):
    return jnp.einsum("smn,snd->smd", res, X) + post[:, :, None] * y[:, None]


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
    g = F32(la["gate_lower_bound"]) * jax.nn.sigmoid(
        jnp.repeat(jnp.exp(_f(lp["A_log"])), dk) * (f + _f(lp["dt_bias"])))
    beta = jax.nn.sigmoid(u @ _f(lp["w_beta"]))
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
    eps = F32(m["rms_norm_eps"])
    K = m["linear_attn_config"]["short_conv_kernel_size"]
    u = _rmsnorm(x, lp["norm1"], eps)
    q, k, v, g, beta, proj = kda_inputs(u, lp, m)
    o, state = recurrence(q, k, v, g, beta)
    gate = jax.nn.sigmoid((u @ _f(lp["wg1"])) @ _f(lp["wg2"]))
    y = (_rmsnorm(o, lp["o_norm"], eps).reshape(gate.shape) * gate) \
        @ _f(lp["wo"])
    kept = jnp.pad(proj, ((K - 1, 0), (0, 0)))[-(K - 1):]
    return y, state, kept


# ------------------------------------------------------ sparse latent mixer
def index_rope(x, m: dict):
    """x [s, heads, w] at positions 0..s-1 (ASSUMED: see the module)."""
    rd = INDEX_ROPE_DIM if x.shape[-1] >= 2 * INDEX_ROPE_DIM \
        else x.shape[-1] // 2
    inv = INDEX_THETA ** (-jnp.arange(0, rd, 2, dtype=F32) / rd)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inv
    pairs = x[..., :rd].reshape(*x.shape[:-1], rd // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    rot = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], -1)
    return jnp.concatenate([rot.reshape(*x.shape[:-1], rd), x[..., rd:]], -1)


def pool_index_keys(ki, m: dict):
    """ki [s, w] -> [s // g, w]: ONE key a complete group, the mean
    (ASSUMED)."""
    g = m["index_kpool"]
    n = ki.shape[0] // g
    return jnp.mean(ki[:n * g].reshape(n, g, -1), axis=1)


def select_rows(scores, m: dict, chosen=None):
    """scores [s, G] of the complete groups -> (mask [s, s]: the
    positions each query attends, chosen [s, G] bool, margin [s]: by how
    much the last chosen group beats the first left out, +inf where none
    is left out).  `chosen` given: that selection instead of the scores'
    own (the tail and the causal bound are this function's own still)."""
    g = m["index_kpool"]
    top = m["index_topk"] // g
    s, G = scores.shape
    t = jnp.arange(s)
    n_complete = (t + 1) // g
    valid = jnp.arange(G)[None, :] < n_complete[:, None]
    masked = jnp.where(valid, scores, NEG)
    if G < top + 1:
        masked = jnp.pad(masked, ((0, 0), (0, top + 1 - G)),
                         constant_values=NEG)
    val, idx = jax.lax.top_k(masked, top + 1)
    margin = jnp.where(val[:, top] > 0.5 * NEG, val[:, top - 1]
                       - val[:, top], jnp.inf)
    if chosen is None:
        chosen = jnp.zeros((s, max(G, top + 1)), bool).at[
            t[:, None], idx[:, :top]].set(val[:, :top] > 0.5 * NEG)[:, :G]
    by_group = jnp.repeat(chosen, g, axis=1)
    by_group = jnp.pad(by_group, ((0, 0), (0, s - by_group.shape[1])))
    own = t[None, :] >= (n_complete * g)[:, None]
    return (by_group | own) & (t[None, :] <= t[:, None]), chosen, margin


def dsa_index(u, cq, lp, m: dict):
    """(scores [s, G] float32 of every group, kbar [G, w], k^I [s, w])."""
    J, w = m["index_n_heads"], m["index_head_dim"]
    s = u.shape[0]
    qi = index_rope((cq @ _f(lp["wqi"])).reshape(s, J, w), m)
    ki = u @ _f(lp["wki"])
    mu = jnp.mean(ki, -1, keepdims=True)
    var = jnp.mean((ki - mu) ** 2, -1, keepdims=True)
    ki = (ki - mu) * jax.lax.rsqrt(var + F32(m["rms_norm_eps"])) \
        * _f(lp["ki_norm_w"]) + _f(lp["ki_norm_b"])
    ki = index_rope(ki[:, None, :], m)[:, 0]
    wts = (u @ _f(lp["ww"])) * (J * w) ** -0.5
    kbar = pool_index_keys(ki, m)
    sc = jnp.einsum("sjw,gw->sjg", qi, kbar)
    return jnp.sum(jax.nn.relu(sc) * wts[:, :, None], axis=1), kbar, ki


def dsa_mix(x, lp, m: dict, chosen=None, heads_at_once: int = 8):
    """x [s, d] -> (y [s, d], info): info = {"latent": the cache rows
    [s, r], "index": the pooled index keys [s // g, w], "ipart": the sum
    of the index keys of the last, incomplete group [w], "mask", "chosen",
    "margin": `select_rows`}.  The heads' scores are held a block of
    heads at a time."""
    eps = F32(m["rms_norm_eps"])
    H, qk = m["num_attention_heads"], m["qk_nope_head_dim"]
    g = m["index_kpool"]
    s = x.shape[0]
    u = _rmsnorm(x, lp["norm1"], eps)
    cq = _rmsnorm(u @ _f(lp["wqa"]), lp["q_norm"], eps)
    c = _rmsnorm(u @ _f(lp["wkva"]), lp["kv_norm"], eps)
    scores, kbar, ki = dsa_index(u, cq, lp, m)
    mask, chosen, margin = select_rows(scores, m, chosen)
    hb = min(heads_at_once, H)

    def block(h0):
        wqb = jax.lax.dynamic_slice_in_dim(lp["wqb"], h0 * qk, hb * qk, 1)
        uk = jax.lax.dynamic_slice_in_dim(lp["w_uk"], h0, hb, 0)
        uv = jax.lax.dynamic_slice_in_dim(lp["w_uv"], h0, hb, 0)
        q = (cq @ _f(wqb)).reshape(s, hb, qk)
        k = jnp.einsum("sc,hnc->shn", c, _f(uk))
        v = jnp.einsum("sc,hcv->shv", c, _f(uv))
        sc = jnp.einsum("thn,shn->hts", q, k) * qk ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], sc, NEG), axis=-1)
        return jnp.einsum("hts,shv->thv", p, v)

    o = jax.lax.map(block, jnp.arange(0, H, hb))      # [H / hb, s, hb, v]
    y = jnp.moveaxis(o, 0, 1).reshape(s, -1) @ _f(lp["wo"])
    ipart = jnp.sum(ki[s // g * g:], axis=0)
    return y, {"latent": c, "index": kbar, "ipart": ipart, "mask": mask,
               "chosen": chosen, "margin": margin}


# ------------------------------------------------------------ feed-forward
def router(h, lp, m: dict):
    """(weights [s, router_experts], margin [s]: the last selected score
    over the first left out, bias counted)."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ _f(lp["router"]))
    top, idx = jax.lax.top_k(s + _f(lp["expert_bias"]), k + 1)
    picked = jnp.zeros_like(s, bool).at[
        jnp.arange(s.shape[0])[:, None], idx[:, :k]].set(True)
    w = jnp.where(picked, s, 0.0)
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return w * F32(m["routed_scaling_factor"]), top[:, k - 1] - top[:, k]


def expert(h, w13, w2, m: dict):
    f = w2.shape[0]
    a = h @ _f(w13)
    return _clamped_swiglu(a[:, :f], a[:, f:], m) @ _f(w2)


def ff_front(x, lp, dense: bool, m: dict):
    """What of a feed-forward needs no expert: a dense layer's whole (y,
    None), or a routed layer's (the shared expert's y, (h, weights,
    margin))."""
    h = _rmsnorm(x, lp["norm2"], F32(m["rms_norm_eps"]))
    if dense:
        return _clamped_swiglu(h @ _f(lp["w1"]), h @ _f(lp["w3"]), m) \
            @ _f(lp["w2"]), None
    w, margin = router(h, lp, m)
    return _clamped_swiglu(h @ _f(lp["sw1"]), h @ _f(lp["sw3"]), m) \
        @ _f(lp["sw2"]), (h, w, margin)


def ff(x, lp, lid: int, m: dict):
    """x [s, d] -> (y [s, d], the routing margin [s] or None).  The loop
    over the held experts multiplies every position by every expert and
    masks by the weights."""
    fn = _jitted(m)
    kind = m["mlp_layer_types"][lid]
    small = {k: v for k, v in lp.items()
             if k in ("norm2", "w1", "w3", "router", "expert_bias", "sw1",
                      "sw3", "sw2") or (k == "w2" and kind == "dense")}
    y, routed = fn["ff_front"][kind](x, small)
    if routed is None:
        return y, None
    h, w, margin = routed
    lo, hi = m["experts_held"]
    for e in range(hi - lo):            # e: the place in the held arrays
        y = fn["expert"](y, h, w, lp["w13"], lp["w2"], e, lo + e)
    return y, margin


# ------------------------------------------------------------- the decoder
_JITTED: dict = {}


def _jitted(m: dict) -> dict:
    key = json.dumps(m, sort_keys=True, default=str)
    if key not in _JITTED:
        _JITTED[key] = {
            "expert": jax.jit(lambda acc, h, w, w13, w2, e, col: acc
                              + w[:, col, None] * expert(h, w13[e], w2[e], m)),
            "ff_front": {kind: jax.jit(lambda x, lp, kind=kind: ff_front(
                x, lp, kind == "dense", m)) for kind in ("dense", "sparse")},
            "kda": jax.jit(lambda x, lp: kda_mix(x, lp, m)),
            "dsa": jax.jit(lambda x, lp: dsa_mix(x, lp, m)),
            "dsa_given": jax.jit(lambda x, lp, chosen:
                                 dsa_mix(x, lp, m, chosen)),
            "maps": jax.jit(lambda X, hp: mhc_maps(X, hp, m)),
            "mix": jax.jit(lambda X, pre: mhc_in(X, pre)),
            "out": jax.jit(mhc_out),
            "recurrence": jax.jit(recurrence),
        }
    return _JITTED[key]


def mixer(x, lp, lid: int, m: dict, chosen=None):
    """The mixer of layer `lid` from its input x [s, d]: (y, info)."""
    with jax.default_matmul_precision("highest"):
        # the mixer's own weights only: layers of a kind share a program
        lp = {k: v for k, v in lp.items() if k not in FF_KEYS}
        if m["layer_types"][lid] == KDA:
            y, state, kept = _jitted(m)["kda"](x, lp)
            return y, {"state": state, "conv": kept}
        if chosen is None:
            return _jitted(m)["dsa"](x, lp)
        return _jitted(m)["dsa_given"](x, lp, chosen)


def sublayer(X, hp, m: dict, fn):
    """X [s, n, d] -> (X after the wrapped sublayer, fn's second
    result)."""
    with jax.default_matmul_precision("highest"):
        fns = _jitted(m)
        pre, post, res = fns["maps"](X, hp)
        y, info = fn(fns["mix"](X, pre))
        return fns["out"](X, y, post, res), info


def layer(X, lp, lid: int, m: dict, chosen=None):
    """(X after layer `lid`, X between its two sublayers, the mixer's
    info, the routing margin or None)."""
    X_mid, info = sublayer(X, lp["hc_mix"], m,
                           lambda x: mixer(x, lp, lid, m, chosen))
    with jax.default_matmul_precision("highest"):
        X_out, margin = sublayer(X_mid, lp["hc_ffn"], m,
                                 lambda x: ff(x, lp, lid, m))
    return X_out, X_mid, info, margin


def embed(params: dict, tokens, m: dict):
    """X_0 [s, n, d]."""
    x = _f(params["embed"][jnp.asarray(tokens)])
    return jnp.broadcast_to(x[:, None, :],
                            (x.shape[0], m["hc_mult"], x.shape[1]))


def head(X, params: dict, m: dict):
    """X [s, n, d] -> logits [s, vocab]."""
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(jnp.sum(X, axis=1), params["final_norm"],
                     F32(m["rms_norm_eps"]))
        return x @ _f(params["lm_head"])


def logits(params: dict, tokens, m: dict, last: int | None = None):
    """tokens [s] -> logits [s, vocab] float32 (the last `last` rows only,
    if given)."""
    X = embed(params, tokens, m)
    for lid, lp in enumerate(params["layers"]):
        X = layer(X, lp, lid, m)[0]
    return head(X if last is None else X[-last:], params, m)


def token_gaps(params: dict, prompt: list[int], served: list[int],
               model: dict) -> list[float]:
    """For each served token: the reference's largest logit at that
    position minus the reference's logit OF the served token, given the
    prompt and the served tokens before it."""
    seq = list(prompt) + list(served[:-1])
    lg = logits(params, seq, model, last=len(served))
    got = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], -1)[:, 0]
    return [float(x) for x in (jnp.max(lg, axis=-1) - got)]


teacher_forced_gaps = token_gaps
