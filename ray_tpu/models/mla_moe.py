"""Latent-attention decoder with routed experts and a shared expert
(`model_type` `sarvam_mla`, e.g. sarvam-105b; the DeepSeek-V2/V3 layer),
served.

This module gives the serving seam (`ray_tpu.models.serving_model`) what
`serve/llm.LLMEngine` runs.  It has none of the optional capabilities
(`serving_spec`'s `caps` is empty): the prefix cache's suffix prefill,
LoRA and KV export/import all read a K pool and a V pool, and this
model's pool is one LATENT row a token (ROADMAP M4 keeps them).

The equations, as the published keys give them (h = RMSNorm(x), eps
`rms_norm_eps`; i over the heads):

    q_i = (h W_q)_i = [q_nope_i (128) | q_rope_i (64)]      no q_lora_rank
    [c | k_r] = h W_kva          c: kv_lora_rank 512, k_r: 64, one a token
    c <- RMSNorm(c);  q_i <- RMSNorm(q_i)                     use_qk_norm
    q_rope_i, k_r <- RoPE_yarn(., pos)
    the cache row of a token is [c | k_r]
  expanded (prefill):  [k_nope_i | v_i] = c W_kvb,i;  k_i = [k_nope_i | k_r]
    o_i = softmax(s q_i k_i^T + causal) v_i
  absorbed (decode):   q~_i = q_nope_i W_UK,i^T  (512)
    p = softmax(s (q~_i . c_t + q_rope_i . k_r,t));  o_i = (sum_t p_t c_t) W_UV,i
  with W_kvb,i = [W_UK,i | W_UV,i];  s = 192**-0.5 * m**2,
  m = 0.1 * mscale_all_dim * ln(factor) + 1;  x += concat_i(o_i) W_o.
  FF of the first `n_dense_layers`: SwiGLU of `ffn_dim`.  FF elsewhere
  (h2 = RMSNorm(x)): sigma = sigmoid(h2 W_r) over ALL `n_experts`; the
  top `top_k` of sigma + bias are selected; w_e = routed_scaling *
  sigma_e / sum over the selected; y = sum over the selected experts
  THIS CHIP HOLDS (`experts_held`) of w_e SwiGLU_e(h2), plus
  SwiGLU_shared(h2); x += y.  (`models/routed.py`, shared with lfm2.)

The two attention paths run over the same weights: `w_uk` [H, 128, 512]
and `w_uv` [H, 512, 128] are split once at init and held as the decode
program reads them, so no step re-lays-out a weight.

The cache row is stored `row_width` = 640 wide, [c | k_r | 0 x 64]: a
bfloat16 array is tiled 128 lanes wide, so a 576-wide row-major leaf
occupies 640 columns whatever it declares, and a leaf DECLARED 576 wide
is laid out page-minor by the TPU compiler to save that padding and
copied whole (340 MB a layer) before every kernel call.  The kernel
scores over all 640 columns (the padding adds 0) and takes the first
512 as values.

What is read where (each in ONE function, so that a test's control can
stand in for it): `queries` (the q projection, its norm, its RoPE),
`latent_rows` (the down-projection, the latent's norm, k_r's RoPE),
`softmax_scale`, `yarn_frequencies`, `route` (models/routed.py),
`shared_ffn`, `decode_attention` (which columns are values).

Device-side names: `mla_q`, `mla_kv_down`, `mla_absorb`, `mla_attn`,
`mla_out`, `shared_expert` beside `moe_router`, `moe_experts`, `embed`,
`mlp`, `norm`, `lm_head`, `kv_write`; the decode kernel is `mla_attn`,
the prefill kernel `flash_fwd` (q/k 192 wide, v 128).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import routed
from ray_tpu.models.llama import apply_rope, embed_lookup, rmsnorm
from ray_tpu.models.routed import route, shared_ffn
from ray_tpu.models.serving import ServingSpec
from ray_tpu.ops.attention import attention
from ray_tpu.ops.paged_attention import lanes_live, mla_decode_attention

LANE = 128                  # a bfloat16 tile's lanes


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 262144
    dim: int = 4096
    n_layers: int = 32
    n_dense_layers: int = 1         # `first_k_dense_replace`
    n_heads: int = 64
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 16384            # the dense layers' SwiGLU
    moe_ffn_dim: int = 2048         # one expert's (and the shared one's)
    n_experts: int = 128            # the ROUTER's width
    experts_held: tuple = (0, 128)  # the range of them this chip holds
    top_k: int = 8
    n_shared_experts: int = 1
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0       # `rope_scaling` (deepseek_yarn)
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_max: int = 4096
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling: float = 2.5
    max_seq: int = 131072
    dtype: Any = jnp.bfloat16

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def row_used(self) -> int:
        """Columns of a cache row that hold something: [c | k_r]."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def row_width(self) -> int:
        """Columns a cache row is STORED at: whole lane tiles."""
        return -(-self.row_used // LANE) * LANE

    def is_routed(self, lid: int) -> bool:
        return lid >= self.n_dense_layers


def serving_configs() -> dict[str, MlaMoeConfig]:
    return {
        "sarvam-105b": MlaMoeConfig(),
        "mla-debug": MlaMoeConfig(
            vocab_size=256, dim=128, n_layers=3, n_dense_layers=1,
            n_heads=4, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
            v_head_dim=16, ffn_dim=256, moe_ffn_dim=64, n_experts=8,
            experts_held=(0, 8), top_k=2, rope_original_max=32,
            max_seq=128),
    }


def _routed_layers(cfg: MlaMoeConfig) -> int:
    return max(0, cfg.n_layers - cfg.n_dense_layers)


# ---------------------------------------------------------------- params
def init_params(key: jax.Array, cfg: MlaMoeConfig,
                expert_bias_std: float = 0.02) -> dict:
    """Every weight from one key: normal, fan-in scaled; norm weights 1;
    the experts of `experts_held` only.  `expert_bias` is drawn N(0,
    expert_bias_std) over all `n_experts` (the router's): beside sigmoid
    scores whose 8th and 9th largest of 128 lie ~0.01 apart this moves
    the selection and leaves the load near uniform (PR 28's finding for
    lfm2: a bias ten times the spacing starves most experts)."""
    d, H, f = cfg.dim, cfg.n_heads, cfg.moe_ffn_dim
    r, nope, vd = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.v_head_dim
    G = cfg.experts_held[1] - cfg.experts_held[0]
    fs = f * cfg.n_shared_experts
    keys = iter(jax.random.split(key, 3 + 16 * cfg.n_layers))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * fan_in ** -0.5).astype(cfg.dtype)

    layers = []
    for lid in range(cfg.n_layers):
        lp = {"attn_norm": jnp.ones((d,), cfg.dtype),
              "ffn_norm": jnp.ones((d,), cfg.dtype),
              "wq": w((d, H * cfg.qk_head_dim), d),
              "q_norm": jnp.ones((cfg.qk_head_dim,), cfg.dtype),
              "wkva": w((d, cfg.row_used), d),
              "kv_norm": jnp.ones((r,), cfg.dtype),
              "w_uk": w((H, nope, r), r),
              "w_uv": w((H, r, vd), r),
              "wo": w((H * vd, d), H * vd)}
        if cfg.is_routed(lid):
            lp.update(router=w((d, cfg.n_experts), d),
                      expert_bias=expert_bias_std * jax.random.normal(
                          next(keys), (cfg.n_experts,), jnp.float32),
                      w13=w((G, d, 2 * f), d), w2=w((G, f, d), f),
                      sw1=w((d, fs), d), sw3=w((d, fs), d),
                      sw2=w((fs, d), fs))
        else:
            lp.update(w1=w((d, cfg.ffn_dim), d), w3=w((d, cfg.ffn_dim), d),
                      w2=w((cfg.ffn_dim, d), cfg.ffn_dim))
        layers.append(lp)
    return {"embed": w((cfg.vocab_size, d), d), "layers": layers,
            "final_norm": jnp.ones((d,), cfg.dtype),
            "lm_head": w((d, cfg.vocab_size), d)}


def project_logits(params: dict, h: jnp.ndarray) -> jnp.ndarray:
    """The head (untied)."""
    with jax.named_scope("lm_head"):
        return h @ params["lm_head"]


# ------------------------------------------------------------- attention
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: MlaMoeConfig) -> float:
    """s = qk_head_dim**-0.5 * m**2, m by `mscale_all_dim`."""
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return cfg.qk_head_dim ** -0.5 * m * m


def yarn_frequencies(cfg: MlaMoeConfig, max_seq: int):
    """cos, sin [max_seq, qk_rope_dim / 2] of `deepseek_yarn`: the
    frequencies of the dimensions that turn fewer than `beta_slow` times
    over the original context are divided by `factor`, those that turn
    more than `beta_fast` times are kept, a linear ramp between; both
    tables times mscale / mscale_all_dim (1 as published)."""
    dim, theta = cfg.qk_rope_dim, cfg.rope_theta

    def corr(turns):
        return (dim * math.log(cfg.rope_original_max
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
        0.0, 1.0)
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                             / dim))
    inv = extra / cfg.rope_factor * (1.0 - keep) + extra * keep
    ang = jnp.outer(jnp.arange(max_seq, dtype=jnp.float32), inv)
    m = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def queries(h, lp, cfg: MlaMoeConfig, cos, sin, positions=None):
    """h [b, s, d] -> (q_nope [b, s, H, 128], q_rope [b, s, H, 64]):
    projected directly from h, RMS-normed over each head's 192 by one
    weight, RoPE on the rotary part."""
    b, s, _ = h.shape
    with jax.named_scope("mla_q"):
        q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads, cfg.qk_head_dim)
        q = rmsnorm(q, lp["q_norm"], cfg.norm_eps)
        q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
        return q_nope, apply_rope(q_rope, cos, sin, positions=positions)


def latent_rows(h, lp, cfg: MlaMoeConfig, cos, sin, positions=None):
    """h [b, s, d] -> (c [b, s, 512] RMS-normed, k_r [b, s, 64] with
    RoPE): what a token's cache row holds."""
    with jax.named_scope("mla_kv_down"):
        kv = h @ lp["wkva"]
        c, k_r = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
        c = rmsnorm(c, lp["kv_norm"], cfg.norm_eps)
        k_r = apply_rope(k_r[:, :, None, :], cos, sin,
                         positions=positions)[:, :, 0]
        return c, k_r


def cache_row(c, k_r, cfg: MlaMoeConfig):
    """[..., row_width]: [c | k_r | zeros], in the serving dtype."""
    pad = jnp.zeros(c.shape[:-1] + (cfg.row_width - cfg.row_used,),
                    cfg.dtype)
    return jnp.concatenate(
        [c.astype(cfg.dtype), k_r.astype(cfg.dtype), pad], axis=-1)


def decode_attention(q, pages, tail, page_table, pos, tail_start,
                     cfg: MlaMoeConfig, plan):
    """The absorbed path's attention over the latent pool: scores over
    the whole row, values = its first `kv_lora_rank` columns."""
    return mla_decode_attention(
        q, pages, tail, page_table, pos, tail_start,
        dv=cfg.kv_lora_rank, sm_scale=softmax_scale(cfg), plan=plan)


def prefill_op(x, lp, lid: int, cfg: MlaMoeConfig, true_lens=None):
    """The attention half of layer `lid` over whole rows, EXPANDED: what
    it adds to x [b, P, d], and the rows' cache rows [b, P, 1,
    row_width].  true_lens [b] (absent: every row is P long) lets the
    attention kernel pass over the blocks of padding."""
    b, P, _ = x.shape
    cos, sin = yarn_frequencies(cfg, P)
    h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    q_nope, q_rope = queries(h, lp, cfg, cos, sin)
    c, k_r = latent_rows(h, lp, cfg, cos, sin)
    with jax.named_scope("mla_absorb"):      # the same arrays, expanding
        k_nope = jnp.einsum("bpc,hnc->bphn", c, lp["w_uk"])
        v = jnp.einsum("bpc,hcv->bphv", c, lp["w_uv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None, :], q_rope.shape)],
        axis=-1)
    with jax.named_scope("mla_attn"):
        o = attention(q, k.astype(q.dtype), v.astype(q.dtype),
                      sm_scale=softmax_scale(cfg), lengths=true_lens)
    with jax.named_scope("mla_out"):
        d = o.reshape(b, P, -1) @ lp["wo"]
    return d, cache_row(c, k_r, cfg)[:, :, None, :]


def routed_ffn(h2, lp, cfg: MlaMoeConfig, live=None):
    """`routed.routed_ffn` for the experts this chip holds, under THIS
    module's `route` (looked up at the call, so a test's control can
    stand in for it)."""
    return routed.routed_ffn(h2, lp, cfg, live, cfg.experts_held,
                             route_fn=route)


def ffn(x, lp, lid: int, cfg: MlaMoeConfig, live=None):
    """The second half of layer `lid`, what it ADDS to x [..., d], and
    the counts of a routed layer or None.  Prefill and decode share
    it."""
    h = rmsnorm(x, lp["ffn_norm"], cfg.norm_eps)
    if not cfg.is_routed(lid):
        with jax.named_scope("mlp"):
            return routed.swiglu(h, lp["w1"], lp["w3"], lp["w2"],
                                 cfg.dtype), None
    h2 = h.reshape(-1, cfg.dim)
    y, counts = routed_ffn(h2, lp, cfg,
                           None if live is None else live.reshape(-1))
    y = y + shared_ffn(h2, lp, cfg.dtype)
    return y.reshape(x.shape), counts


# ---------------------------------------------------------------- prefill
def prefill(params: dict, tokens: jnp.ndarray, cfg: MlaMoeConfig,
            true_lens: jnp.ndarray | None = None, lora=None):
    """Prompt pass, expanded path.  tokens [b, P], right-padded;
    true_lens [b] (absent: every row is P long).  Returns the seam's
    (hidden [b, P, d] after the final norm, rows: per layer [b, P, 1,
    row_width] cache rows, [] (no second pool), [] (no lane state),
    counts int32 [routed layers, 3])."""
    b, P = tokens.shape
    if true_lens is None:
        true_lens = jnp.full((b,), P, jnp.int32)
    live = jnp.arange(P)[None, :] < true_lens[:, None]
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    rows, counts = [], []
    for lid, lp in enumerate(params["layers"]):
        d, row = prefill_op(x, lp, lid, cfg, true_lens)
        x = x + d
        rows.append(row)
        y, cnt = ffn(x, lp, lid, cfg, live)
        x = x + y
        if cnt is not None:
            counts.append(cnt)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, rows, [], [], routed.stack_counts(counts)


# ------------------------------------------------------------ paged cache
def init_paged_cache(cfg: MlaMoeConfig, batch: int, n_pages: int,
                     page: int) -> dict:
    """ONE pool leaf a layer, [n_pages, 1, page, row_width]: a token's
    row [c | k_r | 0], shared by every head."""
    shape = (n_pages, 1, page, cfg.row_width)
    return {"latent": [jnp.zeros(shape, cfg.dtype)
                       for _ in range(cfg.n_layers)],
            "pos": jnp.zeros((batch,), jnp.int32), "state": []}


def scatter_prefill_pages(cache: dict, rows, _none, state, page_ids,
                          row_ids, slots, true_lens,
                          aligned: bool = True) -> dict:
    """Write a prefill wave's cache rows into the pool (llama's block
    writes, a leaf at a time)."""
    from ray_tpu.models.llama import scatter_rows

    with jax.named_scope("kv_write"):
        latent = [scatter_rows(p, new, page_ids, row_ids, aligned)
                  for p, new in zip(cache["latent"], rows)]
    return {"latent": latent,
            "pos": cache["pos"].at[slots].set(true_lens), "state": state}


# ----------------------------------------------------------------- decode
def decode_step_paged(params: dict, pages: dict, tails: dict, state: list,
                      tokens: jnp.ndarray, pos: jnp.ndarray,
                      tail_start: jnp.ndarray, j, page_table: jnp.ndarray,
                      cfg: MlaMoeConfig, lora=None, plan=None):
    """One decode step, ABSORBED path, over the latent pool and the
    in-block tail (see llama.decode_step_paged: pages are read-only, the
    new row lands in the tail at column j).  A lane whose table row
    starts at the trash page holds no request: it attends nothing and is
    routed nowhere.  Returns (logits [B, vocab] float32, tails, state,
    counts int32 [routed layers, 3])."""
    B = tokens.shape[0]
    H = cfg.n_heads
    live = lanes_live(page_table)
    x = embed_lookup(params["embed"], tokens[:, None], cfg.dtype)  # [B,1,d]
    max_len = page_table.shape[1] * pages["latent"][0].shape[2]
    cos, sin = yarn_frequencies(cfg, max_len)
    qpad = jnp.zeros((B, H, cfg.row_width - cfg.row_used), cfg.dtype)
    new_tails, counts = [], []
    for lid, lp in enumerate(params["layers"]):
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q_nope, q_rope = queries(h, lp, cfg, cos, sin, pos[:, None])
        c, k_r = latent_rows(h, lp, cfg, cos, sin, pos[:, None])
        with jax.named_scope("mla_absorb"):
            qa = jnp.einsum("bhn,hnc->bhc", q_nope[:, 0], lp["w_uk"])
        q = jnp.concatenate([qa.astype(cfg.dtype),
                             q_rope[:, 0].astype(cfg.dtype), qpad], -1)
        with jax.named_scope("kv_write"):
            tail = lax.dynamic_update_slice(
                tails["latent"][lid], cache_row(c, k_r, cfg)[:, :, None, :],
                (0, 0, j, 0))
        with jax.named_scope("mla_attn"):
            o = decode_attention(q, pages["latent"][lid], tail, page_table,
                                 pos, tail_start, cfg, plan)
        new_tails.append(tail)
        with jax.named_scope("mla_absorb"):
            ov = jnp.einsum("bhc,hcv->bhv", o, lp["w_uv"])
        with jax.named_scope("mla_out"):
            x = x + (ov.reshape(B, 1, H * cfg.v_head_dim).astype(cfg.dtype)
                     @ lp["wo"])
        y, cnt = ffn(x, lp, lid, cfg, live[:, None])
        x = x + y
        if cnt is not None:
            counts.append(cnt)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = project_logits(params, x[:, 0]).astype(jnp.float32)
    return logits, {"latent": new_tails}, state, routed.stack_counts(counts)


# the serving seam's names (models/serving.py)
serve_prefill = prefill
serve_scatter = scatter_prefill_pages
serve_decode_step = decode_step_paged


def prefill_params(cfg: MlaMoeConfig) -> tuple[int, int]:
    """Matmul parameters a prefill program STREAMS whatever it holds and
    those ONE position multiplies (`routed.prefill_params`): of a
    position's `top_k` experts this chip multiplies the share it holds."""
    d, H, r = cfg.dim, cfg.n_heads, cfg.kv_lora_rank
    attn = (d * H * cfg.qk_head_dim + d * cfg.row_used
            + H * r * (cfg.qk_nope_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * d)
    shared = 3 * d * cfg.moe_ffn_dim * cfg.n_shared_experts
    rest = (cfg.n_layers * attn + _routed_layers(cfg) * shared
            + (cfg.n_layers - _routed_layers(cfg)) * 3 * d * cfg.ffn_dim)
    return routed.prefill_params(cfg, rest, _routed_layers(cfg),
                                 cfg.experts_held)


def serving_spec(cfg: MlaMoeConfig) -> ServingSpec:
    """No optional capability (its pool is no K and V page) and no lane
    state."""
    from ray_tpu.ops.flash_attention import PREFILL_COUNTERS, prefill_work

    return ServingSpec(
        prefill_params=prefill_params(cfg),
        routed_layers=_routed_layers(cfg),
        counters={**PREFILL_COUNTERS, **routed.COUNTERS},
        prefill_work=prefill_work,
        routed_work=functools.partial(routed.routed_work, cfg,
                                      cfg.experts_held))
