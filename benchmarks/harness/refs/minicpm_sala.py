"""Plain reference of the `minicpm_sala` decoder (`model_type`
`minicpm_sala`, e.g. MiniCPM-SALA): lightning linear-attention layers with
a fixed decay a head, three in four, beside a GQA softmax layer without
position embedding that past `dense_len` attends only the key blocks it
selects (InfLLM-v2), every layer a dense SwiGLU, under muP scaling.
float32 `jax.numpy`; imports nothing of the program under test.

The equations, from the model's `config.json` keys (no network in the
sandbox: what the keys leave open is marked ASSUMED here and listed under
`assumed` in the configuration file; u = RMSNorm(x; `rms_norm_eps`,
weight); c = `scale_depth` / sqrt(`published_layers`), the PUBLISHED depth
under a cut).  h_0 = `scale_emb` Embed(token); every layer

    x <- x + c Mixer(RMSNorm(x));  x <- x + c SwiGLU(RMSNorm(x))

SwiGLU (silu(u W_gate) * u W_up) W_down; logits = (RMSNorm(x_L) /
(`hidden_size` / `dim_model_base`)) W_head (`tie_word_embeddings` false),
over the `vocab_size` first columns of the head's table.

Sparse mixer (`mixer_types[l]` = `minicpm4`): q = u W_q
(`num_attention_heads` x `head_dim`), k = u W_k, v = u W_v
(`num_key_value_heads` x `head_dim`), RMSNorm a head on q and k
(`qk_norm`; ASSUMED on both mixers, weight [head_dim]), no rotary
embedding (`attn_use_rope` false), causal softmax at head_dim^-0.5, each
key/value head g serving heads / kv heads query heads.  A query at
position t < `dense_len` attends every j <= t (ASSUMED: the rule is read a
QUERY, so that a prompt pass and a decode step agree; MiniCPM4 switches a
whole forward).  Past it, query t of kv head g attends the positions j <=
t of: the first `init_blocks` blocks of `block_size`; every block that
holds a position of t - `window_size` + 1 ... t; and the `topk` best-scored
of the OTHER blocks at or below its own (all of them while fewer exist;
of equal scores the lower block), by

    s_{t,g,b} = max over the kernels c that overlap block b of
    r_{t,g,c} = sum over the heads h of g of
                softmax_c(q_{t,h} . kbar_{g,c} head_dim^-0.5)

kbar_{g,c} = mean(k_{g, c stride} ... k_{g, c stride + kernel - 1}) (the
keys after their norm), the softmax over the kernels that END at or before
t (ASSUMED: exact; MiniCPM4's kernel approximates its log-sum-exp); a
block no visible kernel overlaps is never taken.  The seven sizes are
MiniCPM4's published `sparse_config` (ASSUMED: the row has none).  Then o
<- o * sigmoid(u W_g), an element a gate (`attn_use_output_gate`; ASSUMED
form), y = o W_o.

Lightning mixer (`lightning-attn`): q = u W_q, k = u W_k, v = u W_v, each
`lightning_nh` x `lightning_head_dim`; RMSNorm a head on q and k; RoPE
(`rope_theta`, the whole head, halves paired) on q and k
(`lightning_use_rope`); q times head_dim^-0.5 (`lightning_scale`); token by
token S_t = lambda_h S_{t-1} + k_t v_t^T, o_t = S_t^T q_t, lambda_h =
exp(-2^(-8 (h + 1) / H) (1 - l / (`published_layers` - 1) + 1e-5)), l the
PUBLISHED layer index (ASSUMED: `decay`); y = (RMSNorm_head(o) *
sigmoid(u W_g)) W_o (`use_output_norm`, `use_output_gate`; ASSUMED forms).
The state is float32 (ASSUMED).  No activation on q, k, v beside the norm
(ASSUMED).  `mup_denominator` and `rand_init` touch initialisation only.

No kernels, no cache, no batching: one sequence at once, Python loops over
layers, a scan over tokens and over blocks of queries.  Departures, each
forced or harmless: parameters arrive in the program's layout and dtype
and are cast to float32 a piece at a time (`w13` = [W_gate | W_up] side by
side, the head's table padded with zero columns); matmuls under
`default_matmul_precision("highest")`; the attention's scores and the
kernels' a block of QUERY_BLOCK queries at a time, the SwiGLU a block of
rows at a time.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256           # queries a block of the attention's scores
ROW_BLOCK = 2048            # rows a block of the SwiGLU
SELECT_BLOCK = 64           # queries a block of the kernels' scores
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
MIXER_KEYS = {
    SPARSE: ("norm1", "wq", "wk", "wv", "q_norm", "k_norm", "w_gate", "wo"),
    LIGHTNING: ("norm1", "wq", "wk", "wv", "q_norm", "k_norm", "o_norm",
                "w_gate", "wo")}
FF_KEYS = ("norm2", "w13", "w2")


def _f(a):
    return a.astype(F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f(w)


def _eps(m: dict):
    return F32(m["rms_norm_eps"])


def kind(lid: int, m: dict) -> str:
    return m["mixer_types"][lid]


def residual_scale(m: dict) -> float:
    """c = scale_depth / sqrt(the PUBLISHED depth)."""
    return m["scale_depth"] / m["published_layers"] ** 0.5


def _blocked(fn, block: int, *arrays):
    """fn over arrays of [s, ...] each, `block` rows of all at a time (one
    array: fn takes it; several: fn takes the tuple)."""
    s = arrays[0].shape[0]
    b = min(block, s)
    pad = -s % b
    parts = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)
                          ).reshape(-1, b, *a.shape[1:]) for a in arrays)
    out = jax.lax.map(fn, parts[0] if len(parts) == 1 else parts)
    return out.reshape(s + pad, *out.shape[2:])[:s]


# ------------------------------------------------------------ sparse mixer
def rules(pos, nb: int, sc: dict):
    """What of a selection is no choice, for queries at `pos` [n] over nb
    blocks: (below [n, nb]: the blocks at or below the query's own; forced
    [n, nb]: the first `init_blocks` and every block the window touches;
    dense [n, 1]: the query lies under `dense_len` and attends everything
    below)."""
    blk = sc["block_size"]
    b, p = jnp.arange(nb)[None, :], pos[:, None]
    below = b <= p // blk
    forced = (b < sc["init_blocks"]) | (
        (b >= (p - sc["window_size"] + 1) // blk) & below)
    return below, forced, p < sc["dense_len"]


def selection(q, k, m: dict):
    """q [s, nh, hd], k [s, nkv, hd] (after their norm) -> [nkv, s, blocks]
    bool: the blocks each query attends (every block at or below its own
    where its position is under `dense_len`)."""
    sc = m["sparse_config"]
    blk, ker, stride = sc["block_size"], sc["kernel_size"], sc["kernel_stride"]
    s, nh, hd = q.shape
    nkv = k.shape[1]
    nb = -(-s // blk)
    C = max((s - ker) // stride + 1, 0)       # kernels that fit
    if C == 0:
        at = jnp.arange(s)
        return jnp.broadcast_to(jnp.arange(nb)[None, :] <= (at // blk)[:, None],
                                (nkv, s, nb))
    starts = jnp.arange(C) * stride
    kbar = jnp.mean(k[starts[:, None] + jnp.arange(ker)[None, :]], axis=1)
    qg = q.reshape(s, nkv, nh // nkv, hd)
    b = jnp.arange(nb)
    # kernel c overlaps block b: c stride + kernel > b block and c stride <
    # (b + 1) block
    overlap = ((starts[:, None] + ker > b[None, :] * blk)
               & (starts[:, None] < (b[None, :] + 1) * blk))      # [C, nb]

    def block(args):
        qi, pos = args                        # [n, nkv, rep, hd], [n]
        dots = jnp.einsum("ngrd,cgd->ngrc", qi, kbar) * F32(hd ** -0.5)
        vis = starts[None, :] + ker - 1 <= pos[:, None]           # [n, C]
        p = jax.nn.softmax(jnp.where(vis[:, None, None, :], dots, -jnp.inf),
                           axis=-1)
        r = jnp.where(vis[:, None, :], jnp.sum(
            jnp.where(vis[:, None, None, :], p, 0.0), axis=2), -jnp.inf)
        sb = jnp.max(jnp.where(overlap[None, None], r[..., None], -jnp.inf),
                     axis=2)                                      # [n, g, nb]
        below, forced, dense = (a[:, None, :] for a in rules(pos, nb, sc))
        cand = jnp.where(below & ~forced, sb, -jnp.inf)
        width = max(nb, sc["topk"])
        cand_w = jnp.pad(cand, ((0, 0), (0, 0), (0, width - nb)),
                         constant_values=-jnp.inf)
        val, idx = jax.lax.top_k(cand_w, sc["topk"])
        taken = jnp.zeros(cand_w.shape, bool).at[
            jnp.arange(cand.shape[0])[:, None, None],
            jnp.arange(nkv)[None, :, None], idx].set(val > -jnp.inf)[..., :nb]
        return jnp.where(dense, below, (forced | taken) & below)

    # (a query before the first kernel ends has no visible kernel: its
    # softmax is over nothing; jnp gives NaN there, masked by `vis`)
    chosen = _blocked(block, SELECT_BLOCK, qg, jnp.arange(s))
    return jnp.moveaxis(chosen, 1, 0)


def sparse_mix(x, lp, m: dict, given=None, use_given=None):
    """x [s, d] -> (y [s, d], k, v [s, kv heads, head_dim]: what a cache
    would hold, chosen [kv heads, s, blocks]: the selection it attended
    under).  `given` [kv heads, s, blocks]: the SCORED blocks to attend in
    place of its own top-k; the first blocks, the window and `dense_len`
    stay this reference's (a handed selection that lacks them does not
    take them away).  `use_given` (a traced flag, so that ONE compiled
    program serves both): attend under `given` only where it is true."""
    s = x.shape[0]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    blk = m["sparse_config"]["block_size"]
    u = _rmsnorm(x, lp["norm1"], _eps(m))
    q = _rmsnorm((u @ _f(lp["wq"])).reshape(s, nh, hd), lp["q_norm"], _eps(m))
    k = _rmsnorm((u @ _f(lp["wk"])).reshape(s, nkv, hd), lp["k_norm"],
                 _eps(m))
    v = (u @ _f(lp["wv"])).reshape(s, nkv, hd)
    if given is None or use_given is not None:
        chosen = selection(q, k, m)
    if given is not None:
        # the scored choice is handed; the rules stay this file's
        below, forced, dense = rules(jnp.arange(s), given.shape[-1],
                                     m["sparse_config"])
        handed = jnp.where(dense, below, (forced | given) & below)
        chosen = handed if use_given is None else jnp.where(
            use_given, handed, chosen)
    qg = q.reshape(s, nkv, nh // nkv, hd)
    keys = jnp.arange(s)

    def block(args):
        qi, pos, pick = args                  # pick [n, nkv, nb]
        scores = jnp.einsum("ngrd,kgd->ngrk", qi, k) * F32(hd ** -0.5)
        admit = (jnp.repeat(pick, blk, axis=-1)[..., :s]
                 & (keys[None, None, :] <= pos[:, None, None]))
        att = jax.nn.softmax(jnp.where(admit[:, :, None], scores, -jnp.inf),
                             axis=-1)
        return jnp.einsum("ngrk,kgd->ngrd", att, v)

    o = _blocked(block, QUERY_BLOCK, qg, keys,
                 jnp.moveaxis(chosen, 0, 1)).reshape(s, nh * hd)
    if m["attn_use_output_gate"]:
        o = o * jax.nn.sigmoid(u @ _f(lp["w_gate"]))
    return o @ _f(lp["wo"]), k, v, chosen


# --------------------------------------------------------- lightning mixer
def decay(lid: int, m: dict):
    """lambda_h [H] of PUBLISHED layer `lid` (ASSUMED: the ALiBi slopes
    the public lightning-attention code builds, scaled down layer by
    layer; the paper's closed form exp(-(8 h / H) (1 - l / L)) is the
    other reading, see the configuration's `assumed.lightning`)."""
    H, L = m["lightning_nh"], m["published_layers"]
    slopes = F32(2.0) ** (-8.0 * (jnp.arange(H, dtype=F32) + 1.0) / H)
    return jnp.exp(-slopes * F32(1.0 - lid / (L - 1) + 1e-5))


def _rope(x, theta: float):
    """x [s, H, hd] at positions 0 ... s - 1: halves paired."""
    s, _, hd = x.shape
    inv = 1.0 / (F32(theta) ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    f = jnp.arange(s, dtype=F32)[:, None, None] * inv
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(f) - x2 * jnp.sin(f),
                            x2 * jnp.cos(f) + x1 * jnp.sin(f)], axis=-1)


def recurrence(q, k, v, lam, state=None, round_to=None):
    """Token by token.  q, k, v [s, H, hd], lam [H].  Returns (o [s, H,
    hd], the state after [H, hd (k), hd (v)]).  `round_to`: a dtype the
    state is rounded to after every token (a control: what a lane that
    kept it in that dtype would hold)."""
    H, hd = k.shape[1:]
    if state is None:
        state = jnp.zeros((H, hd, v.shape[-1]), F32)

    def step(S, xs):
        qt, kt, vt = xs
        S = lam[:, None, None] * S + kt[:, :, None] * vt[:, None, :]
        if round_to is not None:
            S = S.astype(round_to).astype(F32)
        return S, jnp.einsum("hd,hdv->hv", qt, S)

    state, o = jax.lax.scan(step, state, (q, k, v))
    return o, state


def lightning_mix(x, lp, lid: int, m: dict):
    """x [s, d] -> (y [s, d], the state after the last token [H, hd, hd])."""
    s = x.shape[0]
    H, hd = m["lightning_nh"], m["lightning_head_dim"]
    u = _rmsnorm(x, lp["norm1"], _eps(m))
    q = _rmsnorm((u @ _f(lp["wq"])).reshape(s, H, hd), lp["q_norm"], _eps(m))
    k = _rmsnorm((u @ _f(lp["wk"])).reshape(s, H, hd), lp["k_norm"], _eps(m))
    v = (u @ _f(lp["wv"])).reshape(s, H, hd)
    if m["lightning_use_rope"]:
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    o, state = recurrence(q * F32(hd ** -0.5), k, v, decay(lid, m))
    if m["use_output_norm"]:
        o = _rmsnorm(o, lp["o_norm"], _eps(m))
    o = o.reshape(s, H * hd)
    if m["use_output_gate"]:
        o = o * jax.nn.sigmoid(u @ _f(lp["w_gate"]))
    return o @ _f(lp["wo"]), state


# ------------------------------------------------------------ feed-forward
def ff(x, lp, m: dict):
    """x [s, d] -> SwiGLU(RMSNorm(x)) [s, d], a block of rows at a time."""
    f = m["intermediate_size"]
    w13, w2 = lp["w13"], lp["w2"]

    def rows(h):
        return (jax.nn.silu(h @ _f(w13[:, :f])) * (h @ _f(w13[:, f:]))) \
            @ _f(w2)

    return _blocked(rows, ROW_BLOCK, _rmsnorm(x, lp["norm2"], _eps(m)))


# ------------------------------------------------------------- the decoder
_JITTED: dict = {}


def _jitted(m: dict) -> dict:
    key = json.dumps(m, sort_keys=True, default=str)
    if key not in _JITTED:
        _JITTED[key] = {
            # ONE program under its own selection or a handed one
            SPARSE: jax.jit(lambda x, lp, given, use: sparse_mix(
                x, lp, m, given, use)),
            LIGHTNING: jax.jit(lambda x, lp, lid: lightning_mix(x, lp, lid,
                                                                m)),
            "ff": jax.jit(lambda x, lp: ff(x, lp, m)),
            "head": jax.jit(lambda x, n, w: (
                _rmsnorm(x, n, _eps(m))
                / F32(m["hidden_size"] / m["dim_model_base"])) @ _f(w)),
        }
    return _JITTED[key]


def mixer(x, lp, lid: int, m: dict, given=None):
    """The mixer of layer `lid` from its input x [s, d]: (y, info): info =
    {"state"} of a lightning layer, {"k", "v", "chosen", "y"} of a sparse
    one (attended under `given`, a selection [kv heads, s, blocks], if one
    is handed; "y" the mixer's output once more, for a judge that reads a
    whole forward's infos)."""
    which = kind(lid, m)
    lp = {k: lp[k] for k in MIXER_KEYS[which]}
    with jax.default_matmul_precision("highest"):
        if which == LIGHTNING:
            y, state = _jitted(m)[which](x, lp, lid)
            return y, {"state": state}
        nb = -(-x.shape[0] // m["sparse_config"]["block_size"])
        none = jnp.zeros((m["num_key_value_heads"], x.shape[0], nb), bool)
        y, k, v, chosen = _jitted(m)[which](
            x, lp, none if given is None else given, given is not None)
    return y, {"k": k, "v": v, "chosen": chosen, "y": y}


def layer(x, lp, lid: int, m: dict, given=None):
    """(x after layer `lid`, x between its mixer and its SwiGLU, the
    mixer's info)."""
    c = F32(residual_scale(m))
    y, info = mixer(x, lp, lid, m, given)
    x_mid = x + c * y
    with jax.default_matmul_precision("highest"):
        out = x_mid + c * _jitted(m)["ff"](x_mid,
                                           {k: lp[k] for k in FF_KEYS})
    return out, x_mid, info


def embed(params: dict, tokens, m: dict):
    return F32(m["scale_emb"]) * _f(params["embed"][jnp.asarray(tokens)])


def head(x, params: dict, m: dict):
    """x [s, d] before the final norm -> logits [s, vocab]."""
    with jax.default_matmul_precision("highest"):
        return _jitted(m)["head"](x, params["final_norm"],
                                  params["lm_head"])[:, :m["vocab_size"]]


def forward(params: dict, tokens, m: dict, given: dict | None = None):
    """tokens [s] -> (x [s, d] before the final norm, infos: a layer's
    mixer info each).  `given`: {layer: selection} for the sparse layers
    that are to attend under a handed selection."""
    x = embed(params, tokens, m)
    infos = []
    for lid, lp in enumerate(params["layers"]):
        x, _, info = layer(x, lp, lid, m, (given or {}).get(lid))
        infos.append(info)
    return x, infos


def logits(params: dict, tokens, m: dict, last: int | None = None,
           given: dict | None = None):
    """tokens [s] -> logits [s, vocab] float32 (the last `last` rows only,
    if given: the head is the widest matmul)."""
    x = forward(params, tokens, m, given)[0]
    return head(x if last is None else x[-last:], params, m)


def gaps_of(lg, served) -> list[float]:
    got = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], -1)[:, 0]
    return [float(x) for x in (jnp.max(lg, axis=-1) - got)]


def token_gaps(params: dict, prompt: list[int], served: list[int],
               model: dict) -> list[float]:
    """For each served token: the reference's largest logit at that
    position minus the reference's logit OF the served token (0 when the
    reference would have chosen it too), given the prompt and the served
    tokens before it."""
    seq = list(prompt) + list(served[:-1])
    return gaps_of(logits(params, seq, model, last=len(served)), served)


# the name the harness's seam gives this quantity; what the family holds
# to its limits beside it is in `families/minicpm_sala.py` (`Judge`)
teacher_forced_gaps = token_gaps
