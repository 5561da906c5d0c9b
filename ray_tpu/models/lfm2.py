"""LFM2-MoE decoder (`model_type` `lfm2_moe`, e.g. LFM2-24B-A2B), served.

Gated short convolutions beside GQA attention, a few leading dense
SwiGLU layers and routed experts after them.  This module gives the
serving seam (`ray_tpu.models.serving_model`) what `serve/llm.LLMEngine`
runs: `init_params`, `init_paged_cache`, `prefill`,
`scatter_prefill_pages`, `decode_step_paged`.  It has none of the
optional capabilities (`serving_spec`'s `caps` is empty): a lane carries
convolution state that no KV page holds, so a radix prefix hit cannot
restore it (no `prefill_with_prefix`), and there are no LoRA hooks and
no KV export/import.

The equations (transformers' `modeling_lfm2_moe.py`, from the model's
`config.json`).  `x_0 = Embed[t]`; for layer l

    h  = x + Op_l(RMSNorm(x; operator_norm_l))
    x' = h + FF_l(RMSNorm(h; ffn_norm_l))

RMSNorm with `norm_eps`; at the end `RMSNorm(x; embedding_norm)` and the
head, which is the embedding transposed (tied).

- Op = attention where `layer_types[l] == "full_attention"`: q, k, v =
  W_q x, W_k x, W_v x (no bias), split into n_heads / n_kv_heads /
  n_kv_heads heads of head_dim; q and k each RMS-normed over head_dim by
  ONE weight vector shared by the heads (`q_norm`, `k_norm`); RoPE
  (rotate-half pairing) on q and k; causal softmax attention at scale
  head_dim**-0.5, each kv head serving n_heads/n_kv_heads query heads;
  W_o.
- Op = short convolution elsewhere: [B, C, u] = split3(W_in x);
  z = B * u; c_t = sum_{j<L} w[j] * z_{t-(L-1)+j} (depthwise, causal,
  kernel L = `conv_L_cache`, no bias, z zero before the sequence);
  y = C * c; W_out y.  A lane's state is its last L-1 rows of z in each
  convolution layer.
- FF for l < `num_dense_layers`: W_2(silu(W_1 x) * W_3 x).
- FF elsewhere, routed: s = sigmoid(W_g x), in float32; the top-k of
  s + expert_bias are SELECTED; their weights are the s of the selected
  (the bias does not enter the weights), divided by (their sum + 1e-6)
  (`norm_topk_prob`) and scaled by `routed_scaling_factor`;
  y = sum_i w_i W2_i(silu(W1_i x) * W3_i x).  Every assignment is
  computed: no capacity, no drop (`ops/grouped_matmul.py`).

Departures, each forced or harmless:
- the router's matmul runs at `Precision.HIGHEST` on float32 casts of the
  bfloat16 activations and weights (a TPU's default float32 matmul is one
  bfloat16 pass);
- z, and so the lane state, is rounded to the serving dtype; the
  convolution's three products are summed in float32;
- W_1 and W_3 of the experts are held side by side as one `w13`
  [E, d, 2f], so one grouped matmul feeds both;
- layers are a LIST of per-layer dicts (they are of four shapes), never
  stacked: nothing is copied out of a stacked array each step;
- rows that hold no request (a dead lane, a prompt's padding) are routed
  nowhere: they cost no expert a weight read and come out of FF as 0.

Device-side names: `short_conv`, `moe_router`, `moe_experts` beside the
ones `llama.py` uses (`embed`, `attn_qkv`, `rope`, `attn`, `attn_out`,
`mlp`, `norm`, `lm_head`, `kv_write`); the grouped matmul's kernel is
`moe_gmm`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import llama, routed
from ray_tpu.models.llama import apply_rope, attention, embed_lookup, rmsnorm
from ray_tpu.models.routed import route
from ray_tpu.models.serving import ServingSpec
from ray_tpu.ops.rope import rope_frequencies

ATTN = "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    dim: int = 2048
    layer_types: tuple = (("conv", "conv")
                          + (ATTN, "conv", "conv", "conv") * 9
                          + (ATTN, "conv"))
    n_dense_layers: int = 2
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 11776            # the dense layers' SwiGLU
    moe_ffn_dim: int = 1536         # one expert's
    n_experts: int = 64
    top_k: int = 4
    conv_kernel: int = 3            # `conv_L_cache`
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling: float = 1.0
    max_seq: int = 128000
    dtype: Any = jnp.bfloat16

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def is_attn(self, lid: int) -> bool:
        return self.layer_types[lid] == ATTN

    def is_routed(self, lid: int) -> bool:
        return lid >= self.n_dense_layers


def serving_configs() -> dict[str, Lfm2MoeConfig]:
    return {
        "lfm2-24b-a2b": Lfm2MoeConfig(),
        "lfm2-debug": Lfm2MoeConfig(
            vocab_size=256, dim=128, layer_types=("conv", ATTN, "conv"),
            n_dense_layers=1, n_heads=4, n_kv_heads=2, ffn_dim=256,
            moe_ffn_dim=128, n_experts=8, top_k=2, max_seq=128),
    }


def attn_layers(cfg: Lfm2MoeConfig) -> int:
    return sum(cfg.is_attn(i) for i in range(cfg.n_layers))


def _routed_layers(cfg: Lfm2MoeConfig) -> int:
    return max(0, cfg.n_layers - cfg.n_dense_layers)


# ---------------------------------------------------------------- params
def init_params(key: jax.Array, cfg: Lfm2MoeConfig,
                expert_bias_std: float = 0.02) -> dict:
    """Every weight from one key: normal, fan-in scaled; norm weights 1.
    `expert_bias` is drawn N(0, expert_bias_std): beside sigmoid scores
    whose 4th and 5th largest of 64 lie ~0.02 apart, 0.02 changes about
    half the selections and leaves the load near uniform (0.1 sent most
    rows to the dozen experts with the largest bias: 38 of 64 hit where
    uniform routing hits 58; my chip run, PR 28)."""
    d, hd, f, E = cfg.dim, cfg.head_dim, cfg.moe_ffn_dim, cfg.n_experts
    keys = iter(jax.random.split(key, 2 + 8 * cfg.n_layers))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * fan_in ** -0.5).astype(cfg.dtype)

    layers = []
    for lid in range(cfg.n_layers):
        lp = {"op_norm": jnp.ones((d,), cfg.dtype),
              "ffn_norm": jnp.ones((d,), cfg.dtype)}
        if cfg.is_attn(lid):
            lp.update(wq=w((d, cfg.n_heads * hd), d),
                      wk=w((d, cfg.n_kv_heads * hd), d),
                      wv=w((d, cfg.n_kv_heads * hd), d),
                      wo=w((cfg.n_heads * hd, d), cfg.n_heads * hd),
                      q_norm=jnp.ones((hd,), cfg.dtype),
                      k_norm=jnp.ones((hd,), cfg.dtype))
        else:
            lp.update(w_in=w((d, 3 * d), d),
                      conv_w=w((cfg.conv_kernel, d), cfg.conv_kernel),
                      w_out=w((d, d), d))
        if cfg.is_routed(lid):
            lp.update(router=w((d, E), d),
                      expert_bias=expert_bias_std * jax.random.normal(
                          next(keys), (E,), jnp.float32),
                      w13=w((E, d, 2 * f), d), w2=w((E, f, d), f))
        else:
            lp.update(w1=w((d, cfg.ffn_dim), d), w3=w((d, cfg.ffn_dim), d),
                      w2=w((cfg.ffn_dim, d), cfg.ffn_dim))
        layers.append(lp)
    return {"embed": w((cfg.vocab_size, d), d), "layers": layers,
            "final_norm": jnp.ones((d,), cfg.dtype)}


def project_logits(params: dict, h: jnp.ndarray) -> jnp.ndarray:
    """The head: the embedding, transposed (tied)."""
    with jax.named_scope("lm_head"):
        return lax.dot_general(h, params["embed"],
                               (((h.ndim - 1,), (1,)), ((), ())))


# ------------------------------------------------------------ the layers
def routed_ffn(h2, lp, cfg: Lfm2MoeConfig, live=None,
               experts: tuple[int, int] | None = None):
    """`routed.routed_ffn` under THIS module's `route` (looked up at the
    call, so a test's control can stand in for it)."""
    return routed.routed_ffn(h2, lp, cfg, live, experts, route_fn=route)


def ffn(x, lp, lid: int, cfg: Lfm2MoeConfig, live=None):
    """The second half of layer `lid`, what it ADDS to x [..., d]:
    FF(RMSNorm(x)); and the counts of a routed layer or None.  Prefill
    and decode share it."""
    h = rmsnorm(x, lp["ffn_norm"], cfg.norm_eps)
    if not cfg.is_routed(lid):
        with jax.named_scope("mlp"):
            g = jax.nn.silu((h @ lp["w1"]).astype(jnp.float32))
            return (g.astype(cfg.dtype) * (h @ lp["w3"])) @ lp["w2"], None
    y, counts = routed_ffn(h.reshape(-1, cfg.dim), lp, cfg,
                           None if live is None else live.reshape(-1))
    return y.reshape(x.shape), counts


def _conv_taps(zs, conv_w):
    """sum_j conv_w[j] * zs[j], in float32; zs: L arrays of one shape,
    oldest first."""
    acc = sum(z.astype(jnp.float32) * conv_w[j].astype(jnp.float32)
              for j, z in enumerate(zs))
    return acc.astype(zs[-1].dtype)


def _qk_norm(q, k, lp, cfg: Lfm2MoeConfig):
    return (rmsnorm(q, lp["q_norm"], cfg.norm_eps),
            rmsnorm(k, lp["k_norm"], cfg.norm_eps))


# ---------------------------------------------------------------- prefill
def prefill_op(x, lp, lid: int, cfg: Lfm2MoeConfig, true_lens):
    """The first half of layer `lid` over whole rows, what it ADDS to
    x [b, P, d]: Op(RMSNorm(x)).  Returns (d, k, v, state): k, v
    [b, P, kvh, hd]
    with RoPE applied (attention layers, else None); state [b, L-1, d],
    the z rows before each row's TRUE length, zeros where the prompt is
    shorter (convolution layers, else None)."""
    b, P, _ = x.shape
    hd, L = cfg.head_dim, cfg.conv_kernel
    h = rmsnorm(x, lp["op_norm"], cfg.norm_eps)
    if cfg.is_attn(lid):
        cos, sin = rope_frequencies(hd, P, cfg.rope_theta)
        with jax.named_scope("attn_qkv"):
            q = (h @ lp["wq"]).reshape(b, P, cfg.n_heads, hd)
            k = (h @ lp["wk"]).reshape(b, P, cfg.n_kv_heads, hd)
            v = (h @ lp["wv"]).reshape(b, P, cfg.n_kv_heads, hd)
            q, k = _qk_norm(q, k, lp, cfg)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        o = attention(q, k, v, causal=True, lengths=true_lens)
        with jax.named_scope("attn_out"):
            d = o.reshape(b, P, -1) @ lp["wo"]
        return d, k.astype(cfg.dtype), v.astype(cfg.dtype), None
    with jax.named_scope("short_conv"):
        B, C, u = jnp.split(h @ lp["w_in"], 3, axis=-1)
        zp = jnp.pad(B * u, ((0, 0), (L - 1, 0), (0, 0)))
        c = _conv_taps([zp[:, j:j + P] for j in range(L)], lp["conv_w"])
        d = (C * c) @ lp["w_out"]
        # z rows true_len-(L-1) .. true_len-1 are zp rows
        # true_len .. true_len+L-2
        at = true_lens[:, None] + jnp.arange(L - 1)[None, :]
        return d, None, None, jnp.take_along_axis(zp, at[..., None], axis=1)


def prefill(params: dict, tokens: jnp.ndarray, cfg: Lfm2MoeConfig,
            true_lens: jnp.ndarray | None = None, lora=None):
    """Prompt pass.  tokens [b, P], right-padded; true_lens [b] (absent:
    every row is P long); `lora` is the seam's slot for adapters, which
    this model has not (None).  Returns (hidden [b, P, d] after the final
    norm, ks, vs: per ATTENTION layer [b, P, kvh, hd] with RoPE applied,
    state: per CONVOLUTION layer [b, L-1, d], the z rows before each
    row's TRUE length (zeros where the prompt is shorter), counts int32
    [routed layers, 3] (see routed_ffn))."""
    b, P = tokens.shape
    if true_lens is None:
        true_lens = jnp.full((b,), P, jnp.int32)
    live = jnp.arange(P)[None, :] < true_lens[:, None]
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    ks, vs, state, counts = [], [], [], []
    for lid, lp in enumerate(params["layers"]):
        d, k, v, st = prefill_op(x, lp, lid, cfg, true_lens)
        x = x + d
        if st is None:
            ks.append(k)
            vs.append(v)
        else:
            state.append(st)
        y, cnt = ffn(x, lp, lid, cfg, live)
        x = x + y
        if cnt is not None:
            counts.append(cnt)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, ks, vs, state, routed.stack_counts(counts)


# ------------------------------------------------------------ paged cache
def init_paged_cache(cfg: Lfm2MoeConfig, batch: int, n_pages: int,
                     page: int) -> dict:
    """The page pool of `llama.init_paged_kv_cache`, with leaves for the
    ATTENTION layers only (indexed by attention-layer number), and
    `state`: per convolution layer [batch, L-1, d], the lanes' last z
    rows."""
    shape = (n_pages, cfg.n_kv_heads, page, cfg.head_dim)
    n_attn = attn_layers(cfg)
    return {"k": [jnp.zeros(shape, cfg.dtype) for _ in range(n_attn)],
            "v": [jnp.zeros(shape, cfg.dtype) for _ in range(n_attn)],
            "pos": jnp.zeros((batch,), jnp.int32),
            "state": [jnp.zeros((batch, cfg.conv_kernel - 1, cfg.dim),
                                cfg.dtype)
                      for _ in range(cfg.n_layers - n_attn)]}


def scatter_prefill_pages(cache: dict, ks, vs, state, page_ids, rows,
                          slots, true_lens, aligned: bool = True) -> dict:
    """Write a prefill wave's K/V into the page pool (llama's scatter)
    and each row's convolution state into its lane (duplicate padding
    rows write one lane the same rows)."""
    out = llama.scatter_prefill_pages(
        {"k": cache["k"], "v": cache["v"], "pos": cache["pos"]}, ks, vs,
        page_ids, rows, slots, true_lens, aligned=aligned)
    with jax.named_scope("state_write"):
        out["state"] = [s.at[slots].set(new)
                        for s, new in zip(cache["state"], state)]
    return out


# ----------------------------------------------------------------- decode
def decode_step_paged(params: dict, pages: dict, tails: dict, state: list,
                      tokens: jnp.ndarray, pos: jnp.ndarray,
                      tail_start: jnp.ndarray, j, page_table: jnp.ndarray,
                      cfg: Lfm2MoeConfig, lora=None, plan=None):
    """One decode step over the paged cache, the in-block tail (see
    llama.decode_step_paged: pages are read-only, new K/V rows land in
    the tails at column j) and the lanes' convolution state (carried:
    each convolution layer shifts its lane rows by one).  A lane whose
    table row starts at the trash page holds no request: it is routed
    nowhere and attends nothing (`plan`: the block's `attention_plan`,
    as in llama.decode_step_paged).  Returns (logits [B, vocab]
    float32, tails, state, counts int32 [routed layers, 3])."""
    from ray_tpu.ops.paged_attention import (lanes_live,
                                             paged_decode_attention)

    B = tokens.shape[0]
    hd = cfg.head_dim
    n_rep = cfg.n_heads // cfg.n_kv_heads
    live = lanes_live(page_table)
    x = embed_lookup(params["embed"], tokens[:, None], cfg.dtype)[:, 0]
    max_len = page_table.shape[1] * pages["k"][0].shape[2]
    cos, sin = rope_frequencies(hd, max_len, cfg.rope_theta)
    new_tk, new_tv, new_state, counts = [], [], [], []
    for lid, lp in enumerate(params["layers"]):
        h = rmsnorm(x, lp["op_norm"], cfg.norm_eps)
        if cfg.is_attn(lid):
            ai = len(new_tk)
            with jax.named_scope("attn_qkv"):
                q = (h @ lp["wq"]).reshape(B, 1, cfg.n_heads, hd)
                k = (h @ lp["wk"]).reshape(B, 1, cfg.n_kv_heads, hd)
                v = (h @ lp["wv"]).reshape(B, 1, cfg.n_kv_heads, hd)
                q, k = _qk_norm(q, k, lp, cfg)
            q = apply_rope(q, cos, sin, positions=pos[:, None])
            k = apply_rope(k, cos, sin, positions=pos[:, None])
            qg = q.reshape(B, cfg.n_kv_heads, n_rep, hd)
            kn = k[:, 0].astype(cfg.dtype)[:, :, None, :]
            vn = v[:, 0].astype(cfg.dtype)[:, :, None, :]
            with jax.named_scope("kv_write"):
                tk = lax.dynamic_update_slice(tails["k"][ai], kn,
                                              (0, 0, j, 0))
                tv = lax.dynamic_update_slice(tails["v"][ai], vn,
                                              (0, 0, j, 0))
            with jax.named_scope("attn"):
                o = paged_decode_attention(
                    qg.astype(cfg.dtype), pages["k"][ai], pages["v"][ai],
                    tk, tv, page_table, pos, tail_start, plan=plan)
            new_tk.append(tk)
            new_tv.append(tv)
            with jax.named_scope("attn_out"):
                x = x + o.reshape(B, cfg.n_heads * hd) @ lp["wo"]
        else:
            with jax.named_scope("short_conv"):
                s = state[len(new_state)]                # [B, L-1, d]
                Bg, C, u = jnp.split(h @ lp["w_in"], 3, axis=-1)
                z = Bg * u
                c = _conv_taps([s[:, i] for i in range(s.shape[1])] + [z],
                               lp["conv_w"])
                x = x + (C * c) @ lp["w_out"]
                new_state.append(
                    jnp.concatenate([s[:, 1:], z[:, None]], axis=1))
        y, cnt = ffn(x, lp, lid, cfg, live)
        x = x + y
        if cnt is not None:
            counts.append(cnt)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = project_logits(params, x).astype(jnp.float32)
    return logits, {"k": new_tk, "v": new_tv}, new_state, routed.stack_counts(counts)


# the serving seam's names (models/serving.py)
serve_prefill = prefill
serve_scatter = scatter_prefill_pages
serve_decode_step = decode_step_paged


def prefill_params(cfg: Lfm2MoeConfig) -> tuple[int, int]:
    """Matmul parameters a prefill program STREAMS whatever it holds and
    those ONE position multiplies (`routed.prefill_params`)."""
    d, hd = cfg.dim, cfg.head_dim
    n_attn = attn_layers(cfg)
    rest = (n_attn * d * hd * 2 * (cfg.n_heads + cfg.n_kv_heads)
            + (cfg.n_layers - n_attn) * 4 * d * d
            + (cfg.n_layers - _routed_layers(cfg)) * 3 * d * cfg.ffn_dim)
    return routed.prefill_params(cfg, rest, _routed_layers(cfg))


def serving_spec(cfg: Lfm2MoeConfig) -> ServingSpec:
    """No optional capability: the convolution layers' last rows are
    lane state no KV page holds."""
    from ray_tpu.ops.flash_attention import PREFILL_COUNTERS, prefill_work

    return ServingSpec(
        lane_state_layers=cfg.n_layers - attn_layers(cfg),
        prefill_params=prefill_params(cfg),
        routed_layers=_routed_layers(cfg),
        counters={**PREFILL_COUNTERS, **routed.COUNTERS},
        prefill_work=prefill_work,
        routed_work=functools.partial(routed.routed_work, cfg, None))
