"""Plain reference of the `cohere2_moe` decoder (e.g. Command A+): a
PARALLEL block under one LayerNorm (attention and the feed-forward both
read u = LN(x) and are added to x together), window layers with
interleaved rotary beside global layers without any, 128 query heads
over 8 kv heads, routed experts beside four shared experts AVERAGED, a
tied head.  float32 `jax.numpy`; imports nothing of the program under
test.

The equations, from the model's `config.json` keys (no network in the
sandbox: what the keys leave open is marked ASSUMED here and listed under
`assumed` in the configuration file; `attention_bias` false: no bias
anywhere; x [s, d]):

    u = LN(x) = (x - mean x) / sqrt(var x + `layer_norm_eps`) . g
        (ASSUMED, `layer_norm`: the Cohere family's LayerNorm, the mean
        subtracted, a weight, no bias)
    q = u W_q [s, H, dk];  k = u W_k [s, G, dk];  v = u W_v [s, G, dk]
        (`use_qk_norm` false: no q/k norm)
    `layer_types[l]` "sliding_attention": q, k <- RoPE over all dk
        columns (`rotary_pct` 1), INTERLEAVED pairs (2i, 2i + 1)
        (`position_embedding_type` rope_gptj), theta `rope_theta`; query
        t attends s with t - `sliding_window` < s <= t (ASSUMED,
        `window_rows`: the window counts the query's own position)
    "full_attention": no rotary at all (ASSUMED, `turned`: NoPE, from
        the family's convention); query t attends every s <= t
    a = softmax(q k^T / sqrt dk) v, query head h reading kv head
        h // (H / G), heads side by side, . W_o
    s = sigmoid(u W_r) over ALL `router_experts`; E = the top
        `num_experts_per_tok` of s (`expert_selection_fn` sigmoid; no
        bias key); w_e = s_e / (sum over E + 1e-6) (`norm_topk_prob`)
    routed = sum over e in E AND in `experts_held` of w_e W2_e(silu(W1_e
        u) * W3_e u) (the cut: what the other chips' experts would add
        is left out, as in the program)
    shared = 1/S sum_{j < S} W2s_j(silu(W1s_j u) * W3s_j u), S =
        `num_shared_experts` (ASSUMED, `shared`:
        `shared_expert_combination_strategy` "average" = the mean of the
        shared experts' outputs, added at weight 1; an expert's and a
        shared expert's width both `intermediate_size`)
    y = x + a + routed + shared            (`use_parallel_block`)
    logits = `logit_scale` . LN_f(y_L) E^T  (`tie_word_embeddings`)

`first_k_dense_replace` 0: no leading dense layer, so
`prefix_dense_intermediate_size` and
`prefix_dense_sliding_window_pattern` are inert.  Not here, as not in
the program: the vision tower.

No kernels, no cache, no batching: one sequence at once, Python loops
over layers and experts, attention a kv head and a block of queries at
a time (8,192 positions x 128 heads fit beside the engine).  Departures,
each forced or harmless: parameters arrive in the program's layout and
dtype and are cast to float32 a piece at a time: `w13` = the held
experts' W_1 and W_3 side by side; W_q and W_k with every head's even
columns first and its odd ones after, put back in the published order
here (`published_columns`) before the literal interleaved rotary; the
shared experts side by side in `sw1`, `sw3` and stacked in `sw2` DIVIDED
by their count, taken apart here into S experts with their own W_2
(`shared_of`).  Matmuls under `default_matmul_precision("highest")`; the
experts' loop multiplies every position by every held expert and masks;
a window layer may be given another window (`window`), for a judge that
reads the window's edge.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30
GLOBAL, WINDOW = "full_attention", "sliding_attention"
FF_KEYS = ("router", "w13", "w2", "sw1", "sw3", "sw2")
QUERY_BLOCK = 512


def _f(a):
    return a.astype(F32)


def layer_norm(x, w, eps):
    """ASSUMED: the mean subtracted, a weight, no bias."""
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f(w)


def _swiglu(gate, up):
    return jax.nn.silu(gate) * up


# ------------------------------------------------------------- attention
def published_columns(w, heads: int, dk: int):
    """w [d, heads * dk] as the program holds it (a head's even columns,
    then its odd ones) -> in the published order."""
    d = w.shape[0]
    halves = w.reshape(d, heads, 2, dk // 2)
    return jnp.swapaxes(halves, 2, 3).reshape(d, heads * dk)


def turned(kind: str) -> bool:
    """ASSUMED: a full-attention layer has no rotary at all (NoPE)."""
    return kind == WINDOW


def rope(x, theta: float):
    """Interleaved RoPE (`rope_gptj`) of x [s, heads, dk] at positions
    0..s-1 over all its columns: columns 2i and 2i + 1 pair up."""
    dk = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dk, 2, dtype=F32) / dk)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inv
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)],
                     -1).reshape(x.shape)


def window_rows(t, s, window: int):
    """[queries, keys] bool for query positions t and key positions s: a
    query attends its own position and the window - 1 before it
    (ASSUMED: the window counts the query's own position)."""
    back = t[:, None] - s[None, :]
    return (back >= 0) & (back < window)


def attn(u, lp, kind: str, m: dict, window=None):
    """u [s, d] normed -> (a [s, d], info): info = {"k": the keys a
    token's cache row holds [s, G, dk] (turned in a window layer, in the
    PUBLISHED column order), "v": the values [s, G, dk]}.  `window`:
    that window instead of the published (a window layer)."""
    H, G, dk = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    rep = H // G
    s = u.shape[0]
    theta = float(m["rope_theta"])
    keys = (u @ _f(published_columns(lp["wk"], G, dk))).reshape(s, G, dk)
    vals = (u @ _f(lp["wv"])).reshape(s, G, dk)
    if turned(kind):
        keys = rope(keys, theta)
        width = m["sliding_window"] if window is None else window
    else:
        width = s
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    at = jnp.arange(s)

    def group(g):
        """One kv head's `rep` query heads, a block of queries at a
        time: [s, rep, dk]."""
        wq = jax.lax.dynamic_slice_in_dim(lp["wq"], g * rep * dk, rep * dk,
                                          1)
        q = (u @ _f(published_columns(wq, rep, dk))).reshape(s, rep, dk)
        if turned(kind):
            q = rope(q, theta)
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        kg = jax.lax.dynamic_index_in_dim(keys, g, 1, keepdims=False)
        vg = jax.lax.dynamic_index_in_dim(vals, g, 1, keepdims=False)

        def block(q0):
            sc = jnp.einsum("trd,sd->rts",
                            jax.lax.dynamic_slice_in_dim(q, q0, qb, 0),
                            kg) * dk ** -0.5
            mask = window_rows(q0 + jnp.arange(qb), at, width)
            p = jax.nn.softmax(jnp.where(mask[None], sc, NEG), axis=-1)
            return jnp.einsum("rts,sv->trv", p, vg)     # [qb, rep, dk]

        return jax.lax.map(block, jnp.arange(0, s + pad, qb)).reshape(
            s + pad, rep, dk)[:s]

    o = jax.lax.map(group, jnp.arange(G))               # [G, s, rep, dk]
    o = jnp.moveaxis(o, 0, 1).reshape(s, H * dk)
    return o @ _f(lp["wo"]), {"k": keys, "v": vals}


# ------------------------------------------------------------ feed-forward
def router(u, lp, m: dict):
    """(weights [s, router_experts], margin [s]: the last selected score
    over the first left out)."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(u @ _f(lp["router"]))
    top, idx = jax.lax.top_k(s, k + 1)
    picked = jnp.zeros_like(s, bool).at[
        jnp.arange(s.shape[0])[:, None], idx[:, :k]].set(True)
    w = jnp.where(picked, s, 0.0)
    if m["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return w, top[:, k - 1] - top[:, k]


def expert(u, w13, w2):
    f = w2.shape[0]
    a = u @ _f(w13)
    return _swiglu(a[:, :f], a[:, f:]) @ _f(w2)


def shared_of(lp, j: int, m: dict):
    """Shared expert j's (W_1, W_3, W_2) out of the program's layout:
    its columns of `sw1` and `sw3`, its rows of `sw2` times the count
    the program divided them by."""
    S, f = m["num_shared_experts"], m["intermediate_size"]
    cut = slice(j * f, (j + 1) * f)
    return lp["sw1"][:, cut], lp["sw3"][:, cut], _f(lp["sw2"][cut]) * S


def shared(u, lp, m: dict):
    """ASSUMED: "average" = the mean of the shared experts' outputs."""
    S = m["num_shared_experts"]
    total = jnp.zeros_like(u)
    for j in range(S):
        w1, w3, w2 = shared_of(lp, j, m)
        total = total + _swiglu(u @ _f(w1), u @ _f(w3)) @ w2
    return total / S


def ff(u, lp, m: dict):
    """u [s, d] normed -> (routed + shared [s, d], the routing margin
    [s]).  The loop over the held experts multiplies every position by
    every expert and masks by the weights."""
    fn = _jitted(m)
    small = {k: v for k, v in lp.items()
             if k in ("router", "sw1", "sw3", "sw2")}
    y, w, margin = fn["ff_front"](u, small)
    lo, hi = m["experts_held"]
    for e in range(hi - lo):            # e: the place in the held arrays
        y = fn["expert"](y, u, w, lp["w13"], lp["w2"], e, lo + e)
    return y, margin


# ------------------------------------------------------------- the decoder
_JITTED: dict = {}


def _jitted(m: dict) -> dict:
    key = json.dumps(m, sort_keys=True, default=str)
    if key not in _JITTED:
        eps = F32(m["layer_norm_eps"])
        _JITTED[key] = {
            "norm": jax.jit(lambda x, w: layer_norm(x, w, eps)),
            "expert": jax.jit(lambda acc, u, w, w13, w2, e, col: acc
                              + w[:, col, None] * expert(u, w13[e], w2[e])),
            "ff_front": jax.jit(lambda u, lp: (shared(u, lp, m),)
                                + router(u, lp, m)),
            "attn": {kind: jax.jit(lambda u, lp, kind=kind:
                                   attn(u, lp, kind, m))
                     for kind in (GLOBAL, WINDOW)},
            "attn_window": jax.jit(lambda u, lp, window:
                                   attn(u, lp, WINDOW, m, window=window),
                                   static_argnums=2),
        }
    return _JITTED[key]


def normed(x, lp, m: dict):
    """The block's one norm of its input x [s, d]."""
    return _jitted(m)["norm"](x, lp["norm"])


def mixer(u, lp, lid: int, m: dict, window=None):
    """The attention of layer `lid` from its NORMED input u [s, d]:
    (what it adds, info)."""
    with jax.default_matmul_precision("highest"):
        # the attention's own weights only: layers of a kind share a program
        lp = {k: v for k, v in lp.items() if k not in FF_KEYS}
        if window is not None:
            return _jitted(m)["attn_window"](u, lp, window)
        return _jitted(m)["attn"][m["layer_types"][lid]](u, lp)


def layer(x, lp, lid: int, m: dict):
    """(x after layer `lid`, u, what its attention adds, what its
    feed-forward adds, the attention's info, the routing margin)."""
    u = normed(x, lp, m)
    a, info = mixer(u, lp, lid, m)
    with jax.default_matmul_precision("highest"):
        y, margin = ff(u, lp, m)
    return x + a + y, u, a, y, info, margin


def embed(params: dict, tokens, m: dict):
    return _f(params["embed"][jnp.asarray(tokens)])


def head(x, params: dict, m: dict):
    """x [s, d] -> logits [s, vocab]: the tied embedding, transposed."""
    with jax.default_matmul_precision("highest"):
        x = layer_norm(x, params["final_norm"], F32(m["layer_norm_eps"]))
        return F32(m["logit_scale"]) * (x @ _f(params["embed"]).T)


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
