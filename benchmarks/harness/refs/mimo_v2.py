"""Plain reference of the `mimo_v2_flash` decoder (e.g. MiMo-V2-Flash):
window grouped-query layers with a learned sink a head beside global
grouped-query layers of another kv-head count, keys wider than values,
rotary on a part of each head at a theta a kind, the values scaled,
routed experts without a shared one.  float32 `jax.numpy`; imports
nothing of the program under test.

The equations, from the model's `config.json` keys (no network in the
sandbox: what the keys leave open is marked ASSUMED here and listed under
`assumed` in the configuration file; N = RMSNorm, eps
`layernorm_epsilon`; pre-norm residual h = x + Attn(N(x)), y = h +
FFN(N(h)); `attention_bias` false: no bias).

Attention of either kind of layer (`kind_keys`: a global layer reads
`num_attention_heads` H, `num_key_value_heads` G, `head_dim` dk,
`v_head_dim` dv, `rope_theta`; a window layer the same under `swa_`), u =
N(x), query head h reading kv head h // (H / G):

    q_h = RoPE((u W_q)_h);  k_g = RoPE((u W_k)_g);  v_g = (u W_v)_g
    a_{t,s} = q_{t,h} . k_{s,g} / sqrt(dk)
    global (`add_full_attention_sink_bias` false):
        o_{t,h} = sum_{s <= t} softmax_s(a_{t,s}) v_{s,g}
    window (`add_swa_attention_sink_bias`: a learned s_h a head):
        o_{t,h} = sum_{s in S_t} e^{a_{t,s}} v_{s,g}
                  / (e^{s_h} + sum_{s in S_t} e^{a_{t,s}})
    Attn = `attention_value_scale` [o_h]_h W_o

ASSUMED: RoPE is rotate-half over the FIRST int(`partial_rotary_factor` x
dk) = 64 columns of a head, the rest unturned (`rope_dims`, `rope`); S_t
= {s : t - `sliding_window` < s <= t}: the window counts the query's own
position (`window_rows`); `attention_chunk_size` is the published
kernel's tiling of the window and changes no equation.

Feed-forward: SwiGLU of `intermediate_size` where `moe_layer_freq[l]` is
0; elsewhere sigmoid scores over ALL `router_experts`, the top
`num_experts_per_tok` of score + bias selected (`noaux_tc`, one group), w
= score / (sum over the selected + 1e-6) x `routed_scaling_factor` (null:
1, ASSUMED), the sum over the selected experts in `experts_held` (the
cut: what the other chips' experts would add is left out, as in the
program); `n_shared_experts` null: none.

Not here, as not in the program: the multi-token-prediction layers.

No kernels, no cache, no batching: one sequence at once, Python loops
over layers, experts and blocks of heads.  Departures, each forced or
harmless: parameters arrive in the program's layout and dtype and are
cast to float32 a piece at a time (`w13` = the held experts' W_1 and W_3
side by side); matmuls under `default_matmul_precision("highest")`;
attention a block of kv heads at a time; the experts' loop multiplies
every position by every held expert and masks; a window layer may be
given another window (`window`), for a judge that reads the window's
edge.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30
GLOBAL, WINDOW = 0, 1           # `hybrid_layer_pattern`'s entries
FF_KEYS = ("norm2", "w1", "w3", "w2", "router", "expert_bias", "w13")


def _f(a):
    return a.astype(F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f(w)


def _swiglu(gate, up):
    return jax.nn.silu(gate) * up


# ------------------------------------------------------------- attention
def kind_keys(m: dict, kind: int) -> dict:
    """The sizes of one kind of layer's attention, under plain names,
    from the published keys (a window layer's carry `swa_`)."""
    p = "" if kind == GLOBAL else "swa_"
    return {"H": m[p + "num_attention_heads"],
            "G": m[p + "num_key_value_heads"], "dk": m[p + "head_dim"],
            "dv": m[p + "v_head_dim"], "theta": float(m[p + "rope_theta"])}


def rope_dims(m: dict, dk: int) -> int:
    """How many columns of a head turn (ASSUMED: the integer part of
    `partial_rotary_factor` x the head's width, the FIRST columns)."""
    return int(m["partial_rotary_factor"] * dk)


def rope(x, theta: float, rd: int):
    """Rotate-half RoPE of x [s, heads, w] at positions 0..s-1 over its
    first `rd` columns (the halves [0, rd/2) and [rd/2, rd) pair up)."""
    inv = theta ** (-jnp.arange(0, rd, 2, dtype=F32) / rd)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inv
    a, b = x[..., :rd // 2], x[..., rd // 2:rd]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang),
                            x[..., rd:]], -1)


def window_rows(s: int, window: int):
    """[s, s] bool: a query attends its own position and the window - 1
    before it (ASSUMED: the window counts the query's own position)."""
    t = jnp.arange(s)
    back = t[:, None] - t[None, :]
    return (back >= 0) & (back < window)


def _groups_at_once(G: int, rep: int, s: int) -> int:
    gb = G
    while gb > 1 and (gb * rep * s * s > 3e8 or G % gb):
        gb //= 2
    return gb


def attn(x, lp, kind: int, m: dict, window=None):
    """x [s, d] -> (y [s, d], info): info = {"k": the keys a token's
    cache row holds [s, G, dk] (turned), "v": the values [s, G, dv],
    "mask": the rows each query attends [s, s]}.  `window`: that window
    instead of the published (a window layer)."""
    k = kind_keys(m, kind)
    H, G, dk, dv = k["H"], k["G"], k["dk"], k["dv"]
    rep = H // G
    s = x.shape[0]
    rd = rope_dims(m, dk)
    u = _rmsnorm(x, lp["norm1"], F32(m["layernorm_epsilon"]))
    keys = rope((u @ _f(lp["wk"])).reshape(s, G, dk), k["theta"], rd)
    vals = (u @ _f(lp["wv"])).reshape(s, G, dv)
    if kind == GLOBAL:
        mask = window_rows(s, s)
        sink = jnp.full((H,), NEG, F32)         # no sink: a column of 0
    else:
        mask = window_rows(s, m["sliding_window"] if window is None
                           else window)
        sink = _f(lp["sink"])
    gb = _groups_at_once(G, rep, s)

    def block(g0):
        wq = jax.lax.dynamic_slice_in_dim(lp["wq"], g0 * rep * dk,
                                          gb * rep * dk, 1)
        q = rope((u @ _f(wq)).reshape(s, gb * rep, dk), k["theta"], rd)
        q = q.reshape(s, gb, rep, dk)
        kg = jax.lax.dynamic_slice_in_dim(keys, g0, gb, 1)
        vg = jax.lax.dynamic_slice_in_dim(vals, g0, gb, 1)
        sc = jnp.einsum("tgrd,sgd->grts", q, kg) * dk ** -0.5
        sc = jnp.where(mask[None, None], sc, NEG)
        col = jax.lax.dynamic_slice_in_dim(sink, g0 * rep, gb * rep, 0)
        col = jnp.broadcast_to(col.reshape(gb, rep, 1, 1), (gb, rep, s, 1))
        p = jax.nn.softmax(jnp.concatenate([sc, col], -1), axis=-1)
        return jnp.einsum("grts,sgv->tgrv", p[..., :-1], vg)

    o = jax.lax.map(block, jnp.arange(0, G, gb))   # [G / gb, s, gb, rep, dv]
    o = jnp.moveaxis(o, 0, 1).reshape(s, H * dv)
    y = (F32(m["attention_value_scale"]) * o) @ _f(lp["wo"])
    return y, {"k": keys, "v": vals, "mask": mask}


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
    scale = m["routed_scaling_factor"]
    return w * F32(1.0 if scale is None else scale), top[:, k - 1] - top[:, k]


def expert(h, w13, w2):
    f = w2.shape[0]
    a = h @ _f(w13)
    return _swiglu(a[:, :f], a[:, f:]) @ _f(w2)


def ff_front(x, lp, dense: bool, m: dict):
    """What of a feed-forward needs no expert: a dense layer's whole (y,
    None), or a routed layer's (zeros: no shared expert, (h, weights,
    margin))."""
    h = _rmsnorm(x, lp["norm2"], F32(m["layernorm_epsilon"]))
    if dense:
        return _swiglu(h @ _f(lp["w1"]), h @ _f(lp["w3"])) @ _f(lp["w2"]), \
            None
    w, margin = router(h, lp, m)
    return jnp.zeros_like(x), (h, w, margin)


def is_dense(lid: int, m: dict) -> bool:
    return not m["moe_layer_freq"][lid]


def ff(x, lp, lid: int, m: dict):
    """x [s, d] -> (y [s, d], the routing margin [s] or None).  The loop
    over the held experts multiplies every position by every expert and
    masks by the weights."""
    fn = _jitted(m)
    dense = is_dense(lid, m)
    small = {k: v for k, v in lp.items()
             if k in ("norm2", "w1", "w3", "router", "expert_bias")
             or (k == "w2" and dense)}
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
                     for kind in (GLOBAL, WINDOW)},
            "attn_window": jax.jit(lambda x, lp, window:
                                   attn(x, lp, WINDOW, m, window=window),
                                   static_argnums=2),
        }
    return _JITTED[key]


def mixer(x, lp, lid: int, m: dict, window=None):
    """The attention half of layer `lid` from its input x [s, d]: (what
    it adds, info)."""
    with jax.default_matmul_precision("highest"):
        # the attention's own weights only: layers of a kind share a program
        lp = {k: v for k, v in lp.items() if k not in FF_KEYS}
        if window is not None:
            return _jitted(m)["attn_window"](x, lp, window)
        return _jitted(m)["attn"][m["hybrid_layer_pattern"][lid]](x, lp)


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
        x = _rmsnorm(x, params["final_norm"], F32(m["layernorm_epsilon"]))
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
