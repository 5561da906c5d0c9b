"""Plain reference of the latent-attention decoder with routed experts and
a shared expert (`mla_moe`; `model_type` `sarvam_mla`), float32
`jax.numpy`.  Imports nothing of the program under test.

The equations, from the model's `config.json` keys (the sandbox has no
network: what the keys leave open is listed under `assumed` in the
configuration file).  `x_0 = Embed[t]`; for layer l

    h  = x + Attn_l(RMSNorm(x; attn_norm_l))
    x' = h + FF_l(RMSNorm(h; ffn_norm_l))

RMSNorm with `rms_norm_eps`; at the end `RMSNorm(x; final_norm)` and the
head (untied: `tie_word_embeddings` false).

- Attn, the EXPANDED form only (H = `num_attention_heads`, i a head):
  q_i = (h W_q)_i of `q_head_dim` = [q_nope_i (`qk_nope_head_dim`) |
  q_rope_i (`qk_rope_head_dim`)] (no `q_lora_rank`: projected from h);
  [c | k_r] = h W_kva, c of `kv_lora_rank`, k_r of `qk_rope_head_dim`,
  one a token; `use_qk_norm`: c <- RMSNorm(c; kv_norm), q_i <-
  RMSNorm(q_i; q_norm), one weight of `q_head_dim` shared by the heads;
  q_rope_i, k_r <- RoPE(., position) with the `deepseek_yarn`
  frequencies (rotate-half pairing); k_nope_i = c W_UK,i^T, v_i = c
  W_UV,i; k_i = [k_nope_i | k_r]; o_i = softmax(s q_i k_i^T + causal)
  v_i with s = q_head_dim**-0.5 * m**2, m = 0.1 * mscale_all_dim *
  ln(factor) + 1; concat_i(o_i) W_o.  Every position attends its
  prefix; there is no cache and no absorbed form here.
- FF for l < `first_k_dense_replace`: W_2(silu(W_1 x) * W_3 x).
- FF elsewhere: sigma = sigmoid(W_r x) over ALL `router_experts`;
  SELECTED are the top `num_experts_per_tok` of sigma + expert_bias
  (`moe_router_enable_expert_bias`; the bias does not enter the
  weights); w_e = `routed_scaling_factor` * sigma_e / (sum over the
  selected + 1e-6); y = sum over the selected experts that lie in
  `experts_held` of w_e W2_e(silu(W1_e x) * W3_e x) (what the experts on
  the other chips of the expert-parallel group would add is left out,
  as in the program: the configuration's cut), plus the shared expert's
  W2_s(silu(W1_s x) * W3_s x).

No kernels, no cache, no batching: one sequence at once, Python loops
over layers, experts and blocks of heads.  Departures, each forced or
harmless:
- parameters arrive in the layout of the program under test (a list of
  per-layer dicts, matrices input-major so y = x @ W; `w_uk` [H, nope,
  rank] and `w_uv` [H, rank, v] apart; the held experts' W_1 and W_3
  side by side as `w13` [held, d, 2f]) and in the dtype it serves them
  in; they are cast to float32 here, a piece at a time;
- matmuls run under `default_matmul_precision("highest")`;
- attention runs a block of heads at a time, so that the [heads, s, s]
  scores of a long sequence fit beside the served weights;
- the loop over experts multiplies every position by every held expert
  and masks: it never gathers by the choice;
- a layer's two halves and the head can be called one at a time
  (`op_half`, `ff_half`, `head`), `ff_half` reports each position's
  routing margin by its own scores, and `cache_rows` gives what the
  first half's attention reads of each token (the rows a cache holds).
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
# elements of one block's [heads, s, s] float32 scores (1 GiB)
SCORE_BLOCK_ELEMS = 2 ** 28


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def yarn_inv_freq(m: dict):
    """[qk_rope_head_dim / 2] inverse frequencies of `deepseek_yarn`:
    a dimension that turns more than `beta_fast` times over the original
    context keeps theta**(-2i/dim); one that turns fewer than `beta_slow`
    times is divided by `factor`; a linear ramp over the dimensions
    between."""
    rs = m["rope_scaling"]
    dim, theta = m["qk_rope_head_dim"], float(m["rope_theta"])
    orig = rs["original_max_position_embeddings"]

    def dim_of(turns):
        return (dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    plain = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    return plain / rs["factor"] * ramp + plain * (1.0 - ramp)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(m: dict) -> float:
    rs = m["rope_scaling"]
    return (m["q_head_dim"] ** -0.5
            * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2)


def _rope(x, m: dict):
    """x [s, heads, dim]; position p rotates the pair (i, i + dim/2) by
    p * inv_freq[i]; cos and sin times mscale / mscale_all_dim."""
    s, _, dim = x.shape
    rs = m["rope_scaling"]
    ang = jnp.arange(s, dtype=F32)[:, None] * yarn_inv_freq(m)[None, :]
    k = _mscale(rs["factor"], rs["mscale"]) / _mscale(
        rs["factor"], rs["mscale_all_dim"])
    cos, sin = jnp.cos(ang)[:, None, :] * k, jnp.sin(ang)[:, None, :] * k
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def latent(h, lp, m: dict):
    """h [s, d] -> (c [s, rank] normed, k_r [s, rope] rotated)."""
    r = m["kv_lora_rank"]
    kv = h @ lp["wkva"].astype(F32)
    c, k_r = kv[:, :r], kv[:, r:]
    if m["use_qk_norm"]:
        c = _rmsnorm(c, lp["kv_norm"], float(m["rms_norm_eps"]))
    return c, _rope(k_r[:, None, :], m)[:, 0]


def heads_block(h, c, k_r, wq, q_norm, w_uk, w_uv, m: dict):
    """The attention outputs of one block of heads: wq [d, hb * 192],
    w_uk [hb, nope, rank], w_uv [hb, rank, v] -> [s, hb * v]."""
    s = h.shape[0]
    hb, nope = w_uk.shape[0], m["qk_nope_head_dim"]
    q = (h @ wq.astype(F32)).reshape(s, hb, m["q_head_dim"])
    if m["use_qk_norm"]:
        q = _rmsnorm(q, q_norm, float(m["rms_norm_eps"]))
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], m)], -1)
    k_nope = jnp.einsum("sc,hnc->shn", c, w_uk.astype(F32))
    v = jnp.einsum("sc,hcv->shv", c, w_uv.astype(F32))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, None, :],
                                  (s, hb, k_r.shape[-1]))], -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * softmax_scale(m)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khv->qhv", att, v).reshape(s, -1)


def dense_ff(h, lp):
    return (jax.nn.silu(h @ lp["w1"].astype(F32))
            * (h @ lp["w3"].astype(F32))) @ lp["w2"].astype(F32)


def shared_ff(h, lp):
    return (jax.nn.silu(h @ lp["sw1"].astype(F32))
            * (h @ lp["sw3"].astype(F32))) @ lp["sw2"].astype(F32)


def router(h, lp, m: dict):
    """(weights [s, router_experts]: w_e at the selected experts and 0
    elsewhere, margin [s]: by how much the last selected score beats the
    first one left out, bias counted)."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ lp["router"].astype(F32))
    sel = s + lp["expert_bias"].astype(F32) \
        if m["moe_router_enable_expert_bias"] else s
    top, idx = jax.lax.top_k(sel, k + 1)
    chosen = jnp.zeros_like(s, bool).at[
        jnp.arange(s.shape[0])[:, None], idx[:, :k]].set(True)
    w = jnp.where(chosen, s, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return w * F32(m["routed_scaling_factor"]), top[:, k - 1] - top[:, k]


def expert(h, w13, w2):
    f = w2.shape[0]
    a = h @ w13.astype(F32)
    return (jax.nn.silu(a[:, :f]) * a[:, f:]) @ w2.astype(F32)


_JITTED: dict = {}


def _jitted(m: dict) -> dict:
    key = json.dumps(m, sort_keys=True, default=str)
    if key not in _JITTED:
        _JITTED[key] = _make_jitted(m)
    return _JITTED[key]


def _make_jitted(m: dict) -> dict:
    eps = float(m["rms_norm_eps"])
    qd, vd = m["q_head_dim"], m["v_head_dim"]

    def block(h, c, k_r, lp, h0, hb):
        # the block's weights are cut out INSIDE the program
        wq = jax.lax.dynamic_slice_in_dim(lp["wq"], h0 * qd, hb * qd, 1)
        uk = jax.lax.dynamic_slice_in_dim(lp["w_uk"], h0, hb, 0)
        uv = jax.lax.dynamic_slice_in_dim(lp["w_uv"], h0, hb, 0)
        wo = jax.lax.dynamic_slice_in_dim(lp["wo"], h0 * vd, hb * vd, 0)
        return heads_block(h, c, k_r, wq, lp["q_norm"], uk, uv, m) \
            @ wo.astype(F32)

    return {
        "norm": jax.jit(lambda x, w: _rmsnorm(x, w, eps)),
        "latent": jax.jit(lambda h, lp: latent(h, lp, m)),
        "block": jax.jit(block, static_argnums=(5,)),
        "dense": jax.jit(lambda x, lp: x + dense_ff(
            _rmsnorm(x, lp["ffn_norm"], eps), lp)),
        "shared": jax.jit(lambda h, lp: shared_ff(h, lp)),
        "router": jax.jit(lambda h, lp: router(h, lp, m)),
        "expert": jax.jit(lambda acc, h, w, w13, w2, e, col:
                          acc + w[:, col, None] * expert(h, w13[e], w2[e])),
        "head": jax.jit(lambda x, n, w: _rmsnorm(x, n, eps)
                        @ w.astype(F32)),
    }


def _heads_per_block(s: int, heads: int) -> int:
    hb = max(1, min(heads, SCORE_BLOCK_ELEMS // max(1, s * s)))
    while heads % hb:
        hb -= 1
    return hb


def op_half(x, lp: dict, lid: int, m: dict):
    """The first half of layer `lid`: x + Attn(RMSNorm(x; attn_norm))
    for x [s, d] float32."""
    fn = _jitted(m)
    H = m["num_attention_heads"]
    hb = _heads_per_block(x.shape[0], H)
    attn = {k: lp[k] for k in ("wq", "q_norm", "w_uk", "w_uv", "wo")}
    with jax.default_matmul_precision("highest"):
        h = fn["norm"](x, lp["attn_norm"])
        c, k_r = fn["latent"](h, {k: lp[k] for k in ("wkva", "kv_norm")})
        for h0 in range(0, H, hb):
            x = x + fn["block"](h, c, k_r, attn, h0, hb)
    return x


def cache_rows(x, lp: dict, lid: int, m: dict):
    """What layer `lid` would CACHE of x [s, d] float32: [c | k_r] a
    token, [s, kv_lora_rank + qk_rope_head_dim].  The reference keeps no
    cache; this is the same `latent` its attention reads."""
    fn = _jitted(m)
    with jax.default_matmul_precision("highest"):
        h = fn["norm"](x, lp["attn_norm"])
        c, k_r = fn["latent"](h, {k: lp[k] for k in ("wkva", "kv_norm")})
    return jnp.concatenate([c, k_r], axis=-1)


def ff_half(x, lp: dict, lid: int, m: dict):
    """The second half of layer `lid`: (x + FF(RMSNorm(x; ffn_norm)),
    margin [s] or None for a dense layer)."""
    fn = _jitted(m)
    with jax.default_matmul_precision("highest"):
        if lid < m["first_k_dense_replace"]:
            return fn["dense"](x, {k: lp[k] for k in
                                   ("ffn_norm", "w1", "w3", "w2")}), None
        h = fn["norm"](x, lp["ffn_norm"])
        w, margin = fn["router"](h, {k: lp[k] for k in
                                     ("router", "expert_bias")})
        y = fn["shared"](h, {k: lp[k] for k in ("sw1", "sw3", "sw2")})
        lo, hi = m["experts_held"]
        for e in range(hi - lo):        # e: the place in the held arrays
            y = fn["expert"](y, h, w, lp["w13"], lp["w2"], e, lo + e)
        return x + y, margin


def head(x, params: dict, m: dict):
    """x [s, d] before the final norm -> logits [s, vocab]."""
    with jax.default_matmul_precision("highest"):
        return _jitted(m)["head"](x, params["final_norm"],
                                  params["lm_head"])


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
    tokens before it."""
    seq = list(prompt) + list(served[:-1])
    lg = logits(params, seq, model, last=len(served))
    top = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], -1)[:, 0]
    return [float(g) for g in (top - got)]


# the name the harness's seam gives this quantity; what the `mla_moe`
# family holds to its limits is in `families/mla_moe.py` (`Judge`)
teacher_forced_gaps = token_gaps
