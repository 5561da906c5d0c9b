"""Plain reference of the `dots3_note` decoder (`model_type` `dots3_note`,
e.g. dots3-note-prev): window latent-attention layers beside full ones
that attend a learned selection of their context, each kind with its own
latent attention, a headwise sigmoid gate on every attention output,
routed experts with a shared expert.  float32 `jax.numpy`; imports
nothing of the program under test.

The equations, from the model's `config.json` keys (no network in the
sandbox: what the keys leave open is marked ASSUMED here and listed under
`assumed` in the configuration file; N = RMSNorm, eps `rms_norm_eps`;
pre-norm residual h = x + Attn(N(x)), y = h + FFN(N(h))).

Attention of either kind of layer (a full layer reads the keys
`num_attention_heads` H, `q_lora_rank`, `kv_lora_rank`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`, `rope_theta`; a
window layer the same keys under `swa_`; `kind_keys`), u = N(x):

    c_q = s_q N(u W_qa);  [q^n_h | q^r_h] = (c_q W_qb)_h;  q^r_h <- RoPE(q^r_h)
    [c | k^r] = u W_kva;  c <- s_kv N(c);  k^r <- RoPE(k^r), one for all heads
    k^n_{s,h} = W_UK,h c_s;  v_{s,h} = W_UV,h c_s
    a = softmax over S_t of (q^n_h . k^n_{s,h} + q^r_h . k^r_s) / sqrt(nope + rope)
    o_h = sum a v_{s,h};  g = sigmoid(u W_g);  Attn = [g_h o_h]_h W_o

ASSUMED, the one reading of a key's meaning: `apply_mla_qkv_lora_rescale`
is LongCat-Flash's scale correction, s_q = sqrt(hidden / q_lora_rank),
s_kv = sqrt(hidden / kv_lora_rank) of the layer's own kind (`lora_scales`;
1 without the key).  ASSUMED conventions: RoPE rotate-half (the halves
[0, d/2) and [d/2, d) pair up) at the kind's own theta (`rope`); the gate
(`attention_gate_type` headwise) reads the normed layer input (`gate`).

Full layer: S_t = the `index_topk` best s <= t by the indexer's scores,
t itself always (its own score counts as the largest), all of them while
t < `index_topk` (`select_rows`).  Indexer (`index_n_heads` J,
`index_head_dim` w; DeepSeek-V3.2's, a key a token): q^I_j = RoPE(c_q
W_qI)_j, k^I = RoPE(LayerNorm(u W_kI)), weights (J w)^-0.5 u W_w, I_{t,s} =
sum_j w_{t,j} relu(q^I_{t,j} . k^I_s); ASSUMED: its RoPE on the first 64
of the width, INTERLEAVED pairs (2i, 2i + 1), at the full layers' theta
(`index_rope`).

Window layer: S_t = {s : t - `sliding_window_size` < s <= t} (ASSUMED: the
window counts the query's own position: 513 = 512 before + itself;
`window_rows`).

Feed-forward: SwiGLU of `intermediate_size` in the first
`first_k_dense_replace` layers; elsewhere sigmoid scores over ALL
`router_experts`, the top `num_experts_per_tok` of score + bias selected
(`noaux_tc`, no groups), w = `routed_scaling_factor` score / (sum over the
selected + 1e-6), the sum over the selected experts in `experts_held`
(the cut: what the other chips' experts would add is left out, as in the
program) plus the shared expert.

Not here, as not in the program: the vision and audio towers and the
multi-token-prediction layer.

No kernels, no cache, no batching: one sequence at once, Python loops
over layers, experts and blocks of heads.  Departures, each forced or
harmless: parameters arrive in the program's layout and dtype and are
cast to float32 a piece at a time (`w13` = the held experts' W_1 and W_3
side by side, `w_uk` [H, nope, r], `w_uv` [H, r, v]); matmuls under
`default_matmul_precision("highest")`; attention a block of heads at a
time and the indexer an index head at a time; the experts' loop
multiplies every position by every held expert and masks; a full layer
may be GIVEN the rows each query attends (`chosen`), for a judge that
compares both sides under one selection, and a window layer another
window (`window`), for a judge that reads the window's edge.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30
INDEX_ROPE_DIM = 64         # ASSUMED
FULL, WINDOW = "full_attention", "sliding_attention"
FF_KEYS = ("norm2", "w1", "w3", "w2", "router", "expert_bias", "w13", "sw1",
           "sw3", "sw2")


def _f(a):
    return a.astype(F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f(w)


def _swiglu(gate, up):
    return jax.nn.silu(gate) * up


# ------------------------------------------------------------- attention
def kind_keys(m: dict, kind: str) -> dict:
    """The sizes of one kind of layer's latent attention, under plain
    names, from the published keys (a window layer's carry `swa_`)."""
    p = "" if kind == FULL else "swa_"
    return {"H": m[p + "num_attention_heads"], "qr": m[p + "q_lora_rank"],
            "r": m[p + "kv_lora_rank"], "nope": m[p + "qk_nope_head_dim"],
            "rope": m[p + "qk_rope_head_dim"], "v": m[p + "v_head_dim"],
            "theta": float(m[p + "rope_theta"])}


def lora_scales(m: dict, k: dict) -> tuple[float, float]:
    """(s_q, s_kv) (ASSUMED: see the module)."""
    if not m["apply_mla_qkv_lora_rescale"]:
        return 1.0, 1.0
    d = m["hidden_size"]
    return (d / k["qr"]) ** 0.5, (d / k["r"]) ** 0.5


def rope(x, theta: float):
    """Rotate-half RoPE of x [s, heads, w] at positions 0..s-1 over the
    whole width (ASSUMED convention)."""
    w = x.shape[-1]
    inv = theta ** (-jnp.arange(0, w, 2, dtype=F32) / w)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inv
    a, b = x[..., :w // 2], x[..., w // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def index_rope(x, theta: float):
    """The indexer's RoPE of x [s, heads, w] (ASSUMED: the first 64 of
    the width, or half of a narrower one; interleaved pairs)."""
    rd = INDEX_ROPE_DIM if x.shape[-1] >= 2 * INDEX_ROPE_DIM \
        else x.shape[-1] // 2
    inv = theta ** (-jnp.arange(0, rd, 2, dtype=F32) / rd)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inv
    pairs = x[..., :rd].reshape(*x.shape[:-1], rd // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    rot = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], -1)
    return jnp.concatenate([rot.reshape(*x.shape[:-1], rd), x[..., rd:]], -1)


def gate(u, lp):
    """[s, H]: the headwise gate, from the NORMED layer input (ASSUMED)."""
    return jax.nn.sigmoid(u @ _f(lp["wg"]))


def index_scores(u, cq, lp, m: dict):
    """(scores [s, s] float32 of every key for every query, k^I [s, w]),
    an index head at a time."""
    J, w = m["index_n_heads"], m["index_head_dim"]
    s = u.shape[0]
    theta = float(m["rope_theta"])
    qi = index_rope((cq @ _f(lp["wqi"])).reshape(s, J, w), theta)
    ki = u @ _f(lp["wki"])
    mu = jnp.mean(ki, -1, keepdims=True)
    var = jnp.mean((ki - mu) ** 2, -1, keepdims=True)
    ki = (ki - mu) * jax.lax.rsqrt(var + F32(m["rms_norm_eps"])) \
        * _f(lp["ki_norm_w"]) + _f(lp["ki_norm_b"])
    ki = index_rope(ki[:, None, :], theta)[:, 0]
    wts = (u @ _f(lp["ww"])) * (J * w) ** -0.5

    def head(acc, xs):
        q, wt = xs                                    # [s, w], [s]
        return acc + jax.nn.relu(q @ ki.T) * wt[:, None], None

    sc, _ = jax.lax.scan(head, jnp.zeros((s, s), F32),
                         (jnp.moveaxis(qi, 1, 0), wts.T))
    return sc, ki


FIRM = 0.02         # of the larger of two scores: well past bfloat16's


def select_rows(scores, m: dict):
    """scores [s, s] -> (mask [s, s]: the rows each query attends: the
    `index_topk` best at or below it, itself always; margin [s]: by how
    much the last chosen row beats the first left out, +inf where none
    is left out; firm [s, s]: the chosen rows whose score beats the first
    left out by more than FIRM of the larger of the two, which no
    rounding of a sound program can drop)."""
    top = m["index_topk"]
    s = scores.shape[0]
    t = jnp.arange(s)
    own = t[None, :] == t[:, None]
    masked = jnp.where(own, -NEG, jnp.where(t[None, :] < t[:, None],
                                            scores, NEG))
    if s < top + 1:
        masked = jnp.pad(masked, ((0, 0), (0, top + 1 - s)),
                         constant_values=NEG)
    val, idx = jax.lax.top_k(masked, top + 1)
    margin = jnp.where(val[:, top] > 0.5 * NEG, val[:, top - 1]
                       - val[:, top], jnp.inf)
    kth = val[:, top - 1]
    # of equal scores the lower row first, as `top_k` orders them
    last = jnp.max(jnp.where(val[:, :top] == kth[:, None], idx[:, :top],
                             -1), axis=-1)
    cols = jnp.arange(masked.shape[1])
    mask = ((masked > kth[:, None])
            | ((masked == kth[:, None]) & (cols[None, :] <= last[:, None]))) \
        & (masked > 0.5 * NEG)
    out = val[:, top:top + 1]
    firm = mask & (masked - out > FIRM * jnp.maximum(jnp.abs(masked),
                                                     jnp.abs(out)))
    return mask[:, :s], margin, firm[:, :s]


def window_rows(s: int, window: int):
    """[s, s] bool: a query attends its own position and the window - 1
    before it (ASSUMED: the window counts the query's own position)."""
    t = jnp.arange(s)
    back = t[:, None] - t[None, :]
    return (back >= 0) & (back < window)


def _heads_at_once(H: int, s: int) -> int:
    hb = 8
    while hb > 1 and (hb * s * s > 3e8 or H % hb):
        hb //= 2
    return hb


def attn(x, lp, kind: str, m: dict, chosen=None, window=None):
    """x [s, d] -> (y [s, d], info): info = {"row": what a token's cache
    row holds, [c | k^r] [s, r + rope]; "mask": the rows each query
    attends [s, s]; for a full layer "index": k^I [s, w], and "margin",
    "own_mask" (the scores' own selection) and "firm": `select_rows`}.  `chosen` [s, s] bool: that selection instead of the
    scores' own (a full layer); `window`: that window instead of the
    published (a window layer)."""
    k = kind_keys(m, kind)
    eps = F32(m["rms_norm_eps"])
    H, nope, rp = k["H"], k["nope"], k["rope"]
    s = x.shape[0]
    s_q, s_kv = lora_scales(m, k)
    u = _rmsnorm(x, lp["norm1"], eps)
    cq = s_q * _rmsnorm(u @ _f(lp["wqa"]), lp["q_norm"], eps)
    kv = u @ _f(lp["wkva"])
    c = s_kv * _rmsnorm(kv[:, :k["r"]], lp["kv_norm"], eps)
    k_r = rope(kv[:, None, k["r"]:], k["theta"])[:, 0]
    info = {"row": jnp.concatenate([c, k_r], -1)}
    if kind == FULL:
        scores, ki = index_scores(u, cq, lp, m)
        own, margin, firm = select_rows(scores, m)
        mask = own if chosen is None else (
            chosen & (jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]))
        info.update(index=ki, margin=margin, own_mask=own, firm=firm)
    else:
        mask = window_rows(s, m["sliding_window_size"] if window is None
                           else window)
    info["mask"] = mask
    hb = _heads_at_once(H, s)
    qd = nope + rp

    def block(h0):
        wqb = jax.lax.dynamic_slice_in_dim(lp["wqb"], h0 * qd, hb * qd, 1)
        uk = jax.lax.dynamic_slice_in_dim(lp["w_uk"], h0, hb, 0)
        uv = jax.lax.dynamic_slice_in_dim(lp["w_uv"], h0, hb, 0)
        q = (cq @ _f(wqb)).reshape(s, hb, qd)
        q_r = rope(q[..., nope:], k["theta"])
        kn = jnp.einsum("sc,hnc->shn", c, _f(uk))
        v = jnp.einsum("sc,hcv->shv", c, _f(uv))
        sc = (jnp.einsum("thn,shn->hts", q[..., :nope], kn)
              + jnp.einsum("thr,sr->hts", q_r, k_r)) * qd ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], sc, NEG), axis=-1)
        return jnp.einsum("hts,shv->thv", p, v)

    o = jax.lax.map(block, jnp.arange(0, H, hb))      # [H / hb, s, hb, v]
    o = jnp.moveaxis(o, 0, 1).reshape(s, H, -1)
    y = (o * gate(u, lp)[:, :, None]).reshape(s, -1) @ _f(lp["wo"])
    return y, info


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


def expert(h, w13, w2):
    f = w2.shape[0]
    a = h @ _f(w13)
    return _swiglu(a[:, :f], a[:, f:]) @ _f(w2)


def ff_front(x, lp, dense: bool, m: dict):
    """What of a feed-forward needs no expert: a dense layer's whole (y,
    None), or a routed layer's (the shared expert's y, (h, weights,
    margin))."""
    h = _rmsnorm(x, lp["norm2"], F32(m["rms_norm_eps"]))
    if dense:
        return _swiglu(h @ _f(lp["w1"]), h @ _f(lp["w3"])) @ _f(lp["w2"]), \
            None
    w, margin = router(h, lp, m)
    return _swiglu(h @ _f(lp["sw1"]), h @ _f(lp["sw3"])) @ _f(lp["sw2"]), \
        (h, w, margin)


def is_dense(lid: int, m: dict) -> bool:
    return lid < m["first_k_dense_replace"]


def ff(x, lp, lid: int, m: dict):
    """x [s, d] -> (y [s, d], the routing margin [s] or None).  The loop
    over the held experts multiplies every position by every expert and
    masks by the weights."""
    fn = _jitted(m)
    dense = is_dense(lid, m)
    small = {k: v for k, v in lp.items()
             if k in ("norm2", "w1", "w3", "router", "expert_bias", "sw1",
                      "sw3", "sw2") or (k == "w2" and dense)}
    y, routed = fn["ff_front"][dense](x, small)
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
                              + w[:, col, None] * expert(h, w13[e], w2[e])),
            "ff_front": {dense: jax.jit(lambda x, lp, dense=dense: ff_front(
                x, lp, dense, m)) for dense in (True, False)},
            "attn": {kind: jax.jit(lambda x, lp, kind=kind:
                                   attn(x, lp, kind, m))
                     for kind in (FULL, WINDOW)},
            "attn_given": jax.jit(lambda x, lp, chosen:
                                  attn(x, lp, FULL, m, chosen=chosen)),
            "attn_window": jax.jit(lambda x, lp, window:
                                   attn(x, lp, WINDOW, m, window=window),
                                   static_argnums=2),
        }
    return _JITTED[key]


def mixer(x, lp, lid: int, m: dict, chosen=None, window=None):
    """The attention half of layer `lid` from its input x [s, d]: (what
    it adds, info)."""
    with jax.default_matmul_precision("highest"):
        # the attention's own weights only: layers of a kind share a program
        lp = {k: v for k, v in lp.items() if k not in FF_KEYS}
        kind = m["layer_types"][lid]
        if chosen is not None:
            return _jitted(m)["attn_given"](x, lp, chosen)
        if window is not None:
            return _jitted(m)["attn_window"](x, lp, window)
        return _jitted(m)["attn"][kind](x, lp)


def layer(x, lp, lid: int, m: dict):
    """(x after layer `lid`, x between its two halves, the attention's
    info, the routing margin or None)."""
    y, info = mixer(x, lp, lid, m)
    x_mid = x + y
    with jax.default_matmul_precision("highest"):
        y, margin = ff(x_mid, lp, lid, m)
    return x_mid + y, x_mid, info, margin


def embed(params: dict, tokens, m: dict):
    return _f(params["embed"][jnp.asarray(tokens)])


def head(x, params: dict, m: dict):
    """x [s, d] -> logits [s, vocab]."""
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(x, params["final_norm"], F32(m["rms_norm_eps"]))
        return x @ _f(params["lm_head"])


def logits(params: dict, tokens, m: dict, last: int | None = None):
    """tokens [s] -> logits [s, vocab] float32 (the last `last` rows only,
    if given)."""
    x = embed(params, tokens, m)
    for lid, lp in enumerate(params["layers"]):
        x = layer(x, lp, lid, m)[0]
    return head(x if last is None else x[-last:], params, m)


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
