"""A decoder of WINDOW latent-attention layers beside a few FULL ones that
read their latent pool through a learned selection, each kind with its
own latent attention, a headwise sigmoid gate on every attention output,
routed experts and a shared expert (`model_type` `dots3_note`, e.g.
dots3-note-prev), served.  This module gives the serving seam
(`ray_tpu.models.serving_model`) what `serve/llm.LLMEngine` runs.  It has
none of the optional capabilities (`serving_spec`'s `caps` is empty): a
lane carries a RING of latent rows a window layer that no page holds, and
the pool is a latent row and an index key a token.

The equations (N = RMSNorm, eps `norm_eps`; pre-norm residual h = x +
Attn(N(x)), y = h + FFN(N(h)); what the published keys leave open is
marked "assumed" and lives in ONE function here and ONE in the reference
`benchmarks/harness/refs/dots3_note.py`).

**Both kinds of layer** (`LatentKind`: heads H, `q_lora_rank`,
`kv_lora_rank` r, nope, rope, v, `rope_theta`), u = N(x):

    c_q = s_q N(u W_qa);  [q^n_h | q^r_h] = (c_q W_qb)_h;  q^r <- RoPE(q^r)
    [c | k^r] = u W_kva;  c <- s_kv N(c);  k^r <- RoPE(k^r), one for all heads
    the cached row of a token: [c | k^r]
    k^n_{s,h} = W_UK,h c_s;  v_{s,h} = W_UV,h c_s
    a = softmax over S_t of (q^n_h . k^n_{s,h} + q^r_h . k^r_s) / sqrt(nope + rope)
    o_h = sum a v_{s,h};  g = sigmoid(u W_g) (H);  Attn = [g_h o_h]_h W_o

s_q = sqrt(dim / q_lora_rank), s_kv = sqrt(dim / kv_lora_rank) with
`lora_rescale` (assumed: `apply_mla_qkv_lora_rescale` is LongCat-Flash's
scale correction; `lora_scales`), else 1.  RoPE rotate-half at the kind's
own theta (`models/mla_moe.latent_rows`, `ops/rope`).  The gate reads the
normed layer input (assumed; `gated`).  Prefill runs expanded, decode
absorbed (q~_h = q^n_h W_UK,h; values = the row's first r columns, then
W_UV), as `models/mla_moe` does.

**Full layer** (`layer_types[l] == "full_attention"`): S_t = the
`index_topk` best s <= t by the indexer's scores, t itself always, all of
them while t < `index_topk`.  Indexer (`index_inputs`; DeepSeek-V3.2's, a
key a TOKEN): q^I_j = RoPE(c_q W_qI)_j, k^I = RoPE(LayerNorm(u W_kI)), w =
(J d_I)^-0.5 u W_w, I_{t,s} = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s)
(`ops/sparse_attention` at `group` 1); rotary on the first
`index_rope_dim` of the width, interleaved pairs, at the full layers' theta
(assumed; `models/glm5_next.index_rope`).  Pool: a latent row [c | k^r |
0] `row_width` wide and an index key, a token each.

**Window layer** (`"sliding_attention"`): S_t = {s : t - window < s <= t}
(assumed: `window` counts the query's own position).  No pool page: a
lane keeps the last rows in a RING of `ring_rows` >= window rows, the row
of position p in slot p mod ring_rows (`ops/window_attention`), written in
place by the decode step and filled by the scatter from a prefill row's
last positions.  Prefill: `flash_fwd` under a band
(`ops/flash_attention`: key blocks wholly before the band are not
walked).

**Feed-forward**: SwiGLU of `ffn_dim` in the first `n_dense_layers`;
elsewhere `models/routed.py`: sigmoid scores over ALL `n_experts`, top
`top_k` of score + bias, w = `routed_scaling` score / sum, the experts
THIS CHIP HOLDS (`experts_held`) plus the shared expert.

**Lane state** (`init_paged_cache()["state"]`): `{"window": [one [lanes,
ring_rows, row_width] array a window layer]}`.  **Pool**: `{"latent":
[n_pages, 1, page, row_width] a full layer; "index": [n_pages, 1, page,
index_dim] a full layer}`.

Not served: the vision and audio towers and the multi-token-prediction
layer.

Device-side names: `mla_q`, `mla_kv_down`, `mla_absorb`, `mla_out`,
`attn_gate`, `dsa_index`, `dsa_select`, `dsa_attn` (the full layers'
decode kernel; `dsa_prefill` their prefill kernel), `swa_attn` (the window
layers' decode kernel; `flash_fwd` their prefill kernel), `state_write`,
beside `moe_router`, `moe_experts`, `shared_expert`, `embed`, `mlp`,
`lm_head`, `kv_write`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import mla_moe, routed
from ray_tpu.models.glm5_next import index_rope
from ray_tpu.models.llama import (apply_rope, embed_lookup, rmsnorm,
                                  scatter_rows)
from ray_tpu.models.routed import route, shared_ffn
from ray_tpu.models.serving import ServingSpec, merged
from ray_tpu.ops import (flash_attention, sparse_attention as dsa, ssm,
                         window_attention as swa)
from ray_tpu.ops.attention import attention
from ray_tpu.ops.norms import layernorm
from ray_tpu.ops.paged_attention import lanes_live
from ray_tpu.ops.rope import rope_frequencies

FULL, WINDOW = "full_attention", "sliding_attention"
F32 = jnp.float32
# The indexer's scores of INDEX_BLOCK queries x every index head x every
# key below their segment's end are held at once in float32 (268 MB at
# 128 x 64 x 8,192: over ~270 MB the chip ran a pass sixteen times slower
# a byte, PERF.md section 6, PR 41); a segment of INDEX_SEGMENT queries
# shares one key extent, so a prefill program holds T / INDEX_SEGMENT
# bodies a full layer whatever its length.
INDEX_BLOCK = 128
INDEX_SEGMENT = 2048


@dataclasses.dataclass(frozen=True)
class LatentKind:
    """One kind of layer's latent attention (what `models/mla_moe`'s
    `latent_rows` and `cache_row` read of a config, too)."""
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    rope_theta: float
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def row_used(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def row_width(self) -> int:
        """Columns a row is STORED at: whole lane tiles."""
        return -(-self.row_used // mla_moe.LANE) * mla_moe.LANE


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 152064
    dim: int = 5120
    layer_types: tuple = (FULL,) + ((FULL,) + (WINDOW,) * 3) * 11 + (FULL,)
    n_dense_layers: int = 1         # `first_k_dense_replace`
    full: LatentKind = LatentKind(128, 1024, 512, 128, 64, 128, 8.0e7)
    swa: LatentKind = LatentKind(64, 1024, 1024, 192, 64, 128, 5.0e4)
    window: int = 513               # `sliding_window_size`, own position in
    ring_rows: int = 640            # >= window, whole sublane tiles
    lora_rescale: bool = True       # `apply_mla_qkv_lora_rescale`
    index_heads: int = 64
    index_dim: int = 128
    index_rope_dim: int = 64        # assumed
    index_topk: int = 2048          # in tokens = in rows
    ffn_dim: int = 13824
    moe_ffn_dim: int = 1536
    n_experts: int = 256            # the ROUTER's width
    experts_held: tuple = (0, 256)
    top_k: int = 8
    n_shared_experts: int = 1
    use_expert_bias: bool = True    # `topk_method` noaux_tc
    norm_topk_prob: bool = True
    routed_scaling: float = 1.0
    norm_eps: float = 1e-5
    max_seq: int = 524288
    dtype: Any = jnp.bfloat16

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def index_theta(self) -> float:
        """The indexer turns at its (full) layer's own theta (assumed)."""
        return self.full.rope_theta

    def kind(self, lid: int) -> LatentKind:
        return self.full if self.layer_types[lid] == FULL else self.swa

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def before(self, lid: int) -> int:
        """Layers of layer `lid`'s kind that come before it."""
        return self.layer_types[:lid].count(self.layer_types[lid])

    def is_routed(self, lid: int) -> bool:
        return lid >= self.n_dense_layers


def serving_configs() -> dict[str, Dots3NoteConfig]:
    return {
        "dots3-note-prev": Dots3NoteConfig(),
        "dots3-note-debug": Dots3NoteConfig(
            vocab_size=256, dim=64,
            layer_types=(FULL, FULL, WINDOW, WINDOW),
            full=LatentKind(4, 48, 32, 16, 8, 16, 8.0e7),
            swa=LatentKind(2, 48, 64, 24, 8, 16, 5.0e4),
            window=9, ring_rows=16, index_heads=2, index_dim=16,
            index_rope_dim=8, index_topk=16, ffn_dim=128, moe_ffn_dim=32,
            n_experts=8, experts_held=(0, 8), top_k=2, max_seq=128),
    }


def _decode_work(cfg: Dots3NoteConfig, rows, k: int, page: int, maxp: int
                 ) -> tuple[dict, dict]:
    """One decode window of `k` steps over live lanes that start it on
    `rows` cached rows each: what the full layers' selection read (a key
    a token: every row below and at the query is scored) and what the
    window layers' rings gave."""
    return merged(dsa.decode_work(cfg.count(FULL), 1, cfg.index_topk, rows,
                                  k, page, maxp),
                  swa.decode_work(cfg.count(WINDOW), cfg.window, rows, k))


def serving_spec(cfg: Dots3NoteConfig) -> ServingSpec:
    """No optional capability.  A window layer keeps a ring of rows a lane,
    filled from a prefill row's last positions: the bytes of the rings ONE
    prefill row hands the scatter.  Prefill attention: `flash_fwd` under a
    band (window layers), `dsa.masked_prefill_attention` (full layers)."""
    n_win = cfg.count(WINDOW)
    return ServingSpec(
        lane_state_layers=n_win,
        prefill_state_bytes=(n_win * cfg.ring_rows * cfg.swa.row_width
                             * jnp.dtype(cfg.dtype).itemsize),
        prefill_params=prefill_params(cfg),
        routed_layers=_routed_layers(cfg),
        counters={**flash_attention.PREFILL_COUNTERS,
                  **flash_attention.BAND_COUNTERS, **dsa.PREFILL_COUNTERS,
                  **dsa.COUNTERS, **swa.COUNTERS, **routed.COUNTERS},
        decode_work=functools.partial(_decode_work, cfg),
        prefill_work=lambda true_lens, bucket: merged(
            flash_attention.band_work(cfg.window, true_lens, bucket),
            dsa.prefill_work(cfg.count(FULL), true_lens, bucket)),
        routed_work=functools.partial(routed.routed_work, cfg,
                                      cfg.experts_held))


def _routed_layers(cfg: Dots3NoteConfig) -> int:
    return max(0, cfg.n_layers - cfg.n_dense_layers)


def _latent_params(k: LatentKind, d: int) -> int:
    return (d * k.q_lora_rank + k.q_lora_rank * k.n_heads * k.qk_head_dim
            + d * k.row_used
            + k.n_heads * k.kv_lora_rank * (k.qk_nope_dim + k.v_head_dim)
            + d * k.n_heads + k.n_heads * k.v_head_dim * d)


def prefill_params(cfg: Dots3NoteConfig) -> tuple[int, int]:
    """Matmul parameters a prefill program STREAMS whatever it holds and
    those ONE position multiplies (`routed.prefill_params`)."""
    d = cfg.dim
    indexer = (cfg.full.q_lora_rank * cfg.index_heads * cfg.index_dim
               + d * (cfg.index_dim + cfg.index_heads))
    shared = 3 * d * cfg.moe_ffn_dim * cfg.n_shared_experts
    rest = (cfg.count(FULL) * (_latent_params(cfg.full, d) + indexer)
            + cfg.count(WINDOW) * _latent_params(cfg.swa, d)
            + _routed_layers(cfg) * shared
            + cfg.n_dense_layers * 3 * d * cfg.ffn_dim)
    return routed.prefill_params(cfg, rest, _routed_layers(cfg),
                                 cfg.experts_held)


# ---------------------------------------------------------------- params
def init_params(key: jax.Array, cfg: Dots3NoteConfig,
                expert_bias_std: float = 0.02) -> dict:
    """Every weight from one key: matrices normal, fan-in scaled, in the
    serving dtype; norm weights 1; the experts of `experts_held` only;
    `expert_bias` N(0, expert_bias_std) over all `n_experts`."""
    d, f = cfg.dim, cfg.moe_ffn_dim
    fs = f * cfg.n_shared_experts
    G = cfg.experts_held[1] - cfg.experts_held[0]
    keys = iter(jax.random.split(key, 3 + 24 * cfg.n_layers))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(cfg.dtype)

    layers = []
    for lid, kind in enumerate(cfg.layer_types):
        k = cfg.kind(lid)
        H, r, qr = k.n_heads, k.kv_lora_rank, k.q_lora_rank
        lp = {"norm1": jnp.ones((d,), cfg.dtype),
              "norm2": jnp.ones((d,), cfg.dtype),
              "wqa": w((d, qr), d), "q_norm": jnp.ones((qr,), cfg.dtype),
              "wqb": w((qr, H * k.qk_head_dim), qr),
              "wkva": w((d, k.row_used), d),
              "kv_norm": jnp.ones((r,), cfg.dtype),
              "w_uk": w((H, k.qk_nope_dim, r), r),
              "w_uv": w((H, r, k.v_head_dim), r),
              "wg": w((d, H), d),
              "wo": w((H * k.v_head_dim, d), H * k.v_head_dim)}
        if kind == FULL:
            lp.update(
                wqi=w((qr, cfg.index_heads * cfg.index_dim), qr),
                wki=w((d, cfg.index_dim), d),
                ki_norm_w=jnp.ones((cfg.index_dim,), cfg.dtype),
                ki_norm_b=jnp.zeros((cfg.index_dim,), cfg.dtype),
                ww=w((d, cfg.index_heads), d))
        if cfg.is_routed(lid):
            lp.update(router=w((d, cfg.n_experts), d),
                      expert_bias=expert_bias_std * jax.random.normal(
                          next(keys), (cfg.n_experts,), F32),
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


# ------------------------------------------------------------ feed-forward
def routed_ffn(h2, lp, cfg: Dots3NoteConfig, live=None):
    """`routed.routed_ffn` for the experts this chip holds, under THIS
    module's `route`."""
    return routed.routed_ffn(h2, lp, cfg, live, cfg.experts_held,
                             route_fn=route)


def ffn(x, lp, lid: int, cfg: Dots3NoteConfig, live=None):
    """The second half of layer `lid`, what it ADDS to x [..., d], and
    the counts of a routed layer or None.  Prefill and decode share
    it."""
    h = rmsnorm(x, lp["norm2"], cfg.norm_eps)
    if not cfg.is_routed(lid):
        with jax.named_scope("mlp"):
            return routed.swiglu(h, lp["w1"], lp["w3"], lp["w2"],
                                 cfg.dtype), None
    h2 = h.reshape(-1, cfg.dim)
    y, counts = routed_ffn(h2, lp, cfg,
                           None if live is None else live.reshape(-1))
    y = y + shared_ffn(h2, lp, cfg.dtype)
    return y.reshape(x.shape), counts


# ------------------------------------------------------- latent attention
def lora_scales(k: LatentKind, cfg: Dots3NoteConfig) -> tuple[float, float]:
    """(s_q, s_kv): what the two low-rank latents are scaled by after
    their norms (assumed: `apply_mla_qkv_lora_rescale` = sqrt(dim /
    rank), LongCat-Flash's correction; 1 without the key)."""
    if not cfg.lora_rescale:
        return 1.0, 1.0
    return ((cfg.dim / k.q_lora_rank) ** 0.5,
            (cfg.dim / k.kv_lora_rank) ** 0.5)


def _rescaled(lp, k: LatentKind, cfg: Dots3NoteConfig) -> dict:
    """`lp` with the two latents' norm weights times `lora_scales` (in
    float32: the norm multiplies there, so a latent is rounded once)."""
    s_q, s_kv = lora_scales(k, cfg)
    return {**lp, "q_norm": lp["q_norm"].astype(F32) * s_q,
            "kv_norm": lp["kv_norm"].astype(F32) * s_kv}


def latent_inputs(h, lp, k: LatentKind, cfg: Dots3NoteConfig, positions,
                  tables):
    """h [b, T, d] normed, positions [b, T] or None (0..T-1), tables the
    kind's (cos, sin) -> (c_q [b, T, qr], q_nope [b, T, H, nope], q_rope
    [b, T, H, rope] turned, c [b, T, r], k_r [b, T, rope] turned)."""
    b, T, _ = h.shape
    lp = _rescaled(lp, k, cfg)
    with jax.named_scope("mla_q"):
        cq = rmsnorm(h @ lp["wqa"], lp["q_norm"], k.norm_eps)
        q = (cq @ lp["wqb"]).reshape(b, T, k.n_heads, k.qk_head_dim)
        q_nope, q_rope = jnp.split(q, [k.qk_nope_dim], axis=-1)
        q_rope = apply_rope(q_rope, *tables, positions=positions)
    c, k_r = mla_moe.latent_rows(h, lp, k, *tables, positions=positions)
    return cq, q_nope, q_rope, c, k_r


def gated(o, h, lp, cfg: Dots3NoteConfig):
    """The headwise gate and the output projection: o [..., H, v] float
    heads' outputs, h [..., d] the NORMED layer input (assumed: the gate
    reads it) -> [..., d]."""
    with jax.named_scope("attn_gate"):
        g = jax.nn.sigmoid((h @ lp["wg"]).astype(F32))
        o = (o.astype(F32) * g[..., None]).astype(cfg.dtype)
    with jax.named_scope("mla_out"):
        return o.reshape(*o.shape[:-2], -1) @ lp["wo"]


def _expanded(q_nope, q_rope, c, k_r, lp):
    """The expanded path's (q, k, v) [b, T, H, .] from the latents."""
    with jax.named_scope("mla_absorb"):
        k_nope = jnp.einsum("bpc,hnc->bphn", c, lp["w_uk"])
        v = jnp.einsum("bpc,hcv->bphv", c, lp["w_uv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None, :], q_rope.shape)],
        axis=-1)
    return q, k.astype(q.dtype), v.astype(q.dtype)


def _absorbed(q_nope, q_rope, lp, k: LatentKind):
    """One token's absorbed query [B, H, row_width]: [q^n W_UK | q^r |
    0]."""
    with jax.named_scope("mla_absorb"):
        qa = jnp.einsum("bhn,hnc->bhc", q_nope, lp["w_uk"])
    pad = jnp.zeros(qa.shape[:2] + (k.row_width - k.row_used,), k.dtype)
    return jnp.concatenate([qa.astype(k.dtype), q_rope.astype(k.dtype),
                            pad], axis=-1)


def _tables(k: LatentKind, n: int):
    return rope_frequencies(k.qk_rope_dim, n, k.rope_theta)


# ---------------------------------------------------------- the full layer
def index_inputs(h, cq, lp, cfg: Dots3NoteConfig, positions):
    """(q^I [b, T, J, w], k^I [b, T, w], weights [b, T, J] float32) of
    the indexer, from the normed input and the query latent."""
    b, T, _ = h.shape
    with jax.named_scope("dsa_index"):
        qi = (cq @ lp["wqi"]).reshape(b, T, cfg.index_heads, cfg.index_dim)
        qi = index_rope(qi, positions, cfg)
        ki = layernorm(h @ lp["wki"], lp["ki_norm_w"], lp["ki_norm_b"],
                       cfg.norm_eps)
        ki = index_rope(ki[:, :, None, :], positions, cfg)[:, :, 0]
        w = (h @ lp["ww"]).astype(F32) \
            * (cfg.index_heads * cfg.index_dim) ** -0.5
    return qi, ki, w


def selection_mask(qi, w, ki, cfg: Dots3NoteConfig):
    """[b, T, T] bool: the rows each query of whole rows attends (its
    `index_topk` best at or below it, itself always).  The scores are
    held INDEX_BLOCK queries at a time, a segment of INDEX_SEGMENT
    queries over the keys up to its end."""
    b, T = qi.shape[:2]
    out = []
    for lo in range(0, T, INDEX_SEGMENT):
        hi = min(lo + INDEX_SEGMENT, T)
        blk = INDEX_BLOCK if (hi - lo) % INDEX_BLOCK == 0 else hi - lo
        keys = ki[:, :hi]

        def body(q0, keys=keys, blk=blk, hi=hi):
            with jax.named_scope("dsa_index"):
                s = dsa.index_scores(
                    lax.dynamic_slice_in_dim(qi, q0, blk, 1),
                    lax.dynamic_slice_in_dim(w, q0, blk, 1), keys)
            with jax.named_scope("dsa_select"):
                return dsa.selected_mask(s, q0 + jnp.arange(blk), hi, 1,
                                         cfg.index_topk, own=True)[0]

        m = lax.map(body, jnp.arange(lo, hi, blk))     # [n, b, blk, hi]
        m = jnp.moveaxis(m, 0, 1).reshape(b, hi - lo, hi)
        out.append(jnp.pad(m, ((0, 0), (0, 0), (0, T - hi))))
    return jnp.concatenate(out, axis=1)


def _dense_masked_attention(q, k, v, mask, scale: float):
    """softmax(scale q k^T over the pairs `mask` admits) v in XLA, the
    scores in memory (a short bucket)."""
    s = jnp.einsum("bthd,bshd->bhts", q, k,
                   preferred_element_type=F32) * scale
    p = jax.nn.softmax(jnp.where(mask[:, None], s, dsa.NEG_INF), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v,
                      preferred_element_type=F32).astype(q.dtype)


def full_prefill(x, lp, cfg: Dots3NoteConfig, true_lens,
                 want_selection: bool = False):
    """The full layer's attention half over whole rows x [b, T, d],
    EXPANDED: (what it adds to x, (latent rows [b, T, 1, row_width],
    index keys [b, T, 1, index_dim])); with `want_selection` a third
    entry, the rows each query attends [b, T, T] (a judge's reading).
    `true_lens` [b]: the kernel walks no query block wholly past them."""
    b, T, _ = x.shape
    k = cfg.full
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (b, T))
    cq, q_nope, q_rope, c, k_r = latent_inputs(h, lp, k, cfg, None,
                                               _tables(k, T))
    qi, ki, w = index_inputs(h, cq, lp, cfg, positions)
    mask = selection_mask(qi, w, ki, cfg)
    q, kk, v = _expanded(q_nope, q_rope, c, k_r, lp)
    with jax.named_scope("dsa_attn"):
        if dsa.prefill_block(T):
            o = dsa.masked_prefill_attention(
                q, kk, v, mask.astype(jnp.int8), true_lens,
                sm_scale=k.qk_head_dim ** -0.5)
        else:
            o = _dense_masked_attention(q, kk, v, mask,
                                        k.qk_head_dim ** -0.5)
    kept = (mla_moe.cache_row(c, k_r, k)[:, :, None, :],
            ki.astype(cfg.dtype)[:, :, None, :])
    if want_selection:
        kept += (mask,)
    return gated(o, h, lp, cfg), kept


def full_decode(x, lp, latent_pages, index_pages, latent_tail, index_tail,
                page_table, pos, tail_start, j, lanes, count,
                cfg: Dots3NoteConfig, want_selection: bool = False,
                plan: dict | None = None):
    """One token of the full layer's attention half for every lane,
    ABSORBED: x [B, d]; the two pool leaves of the layer (read-only) and
    their tails (the new row and key land at column j).  Returns (what
    it adds, latent tail, index tail); with `want_selection` a fourth
    entry, (the positions of the rows read and of the tail's, whether
    each is attended): a judge's reading.  `plan`: the window's
    `attention_plan`, for the form of `dsa.decode_attend` that walks
    pages (built there if not given)."""
    B = x.shape[0]
    k = cfg.full
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    max_len = page_table.shape[1] * latent_pages.shape[2]   # positions
    cq, q_nope, q_rope, c, k_r = latent_inputs(
        h[:, None], lp, k, cfg, pos[:, None], _tables(k, max_len))
    qi, ki, w = index_inputs(h[:, None], cq, lp, cfg, pos[:, None])
    with jax.named_scope("kv_write"):
        latent_tail = lax.dynamic_update_slice(
            latent_tail, mla_moe.cache_row(c, k_r, k)[:, :, None, :],
            (0, 0, j, 0))
        index_tail = lax.dynamic_update_slice(
            index_tail, ki.astype(cfg.dtype)[:, :, None, :], (0, 0, j, 0))
    rows_at, ok, chosen = dsa.decode_select(
        qi[:, 0], w[:, 0], index_pages, index_tail, page_table, pos,
        tail_start, 1, cfg.index_topk, own=True)
    q = _absorbed(q_nope[:, 0], q_rope[:, 0], lp, k)
    o, rpos, admit = dsa.decode_attend(
        q, latent_pages, latent_tail, page_table, pos, tail_start, rows_at,
        ok, chosen, lanes, count, group=1, dv=k.kv_lora_rank,
        sm_scale=k.qk_head_dim ** -0.5, plan=plan)
    with jax.named_scope("mla_absorb"):
        ov = jnp.einsum("bhc,hcv->bhv", o, lp["w_uv"])
    y = gated(ov, h, lp, cfg)
    if want_selection:
        return y, latent_tail, index_tail, (rpos, admit)
    return y, latent_tail, index_tail


# -------------------------------------------------------- the window layer
def window_prefill(x, lp, cfg: Dots3NoteConfig, true_lens):
    """The window layer's attention half over whole rows x [b, T, d],
    EXPANDED under the band: (what it adds to x, each row's ring at its
    TRUE length [b, ring_rows, row_width])."""
    T = x.shape[1]
    k = cfg.swa
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    _, q_nope, q_rope, c, k_r = latent_inputs(h, lp, k, cfg, None,
                                              _tables(k, T))
    q, kk, v = _expanded(q_nope, q_rope, c, k_r, lp)
    with jax.named_scope("swa_attn"):
        o = attention(q, kk, v, sm_scale=k.qk_head_dim ** -0.5,
                      lengths=true_lens, window=cfg.window)
    with jax.named_scope("state_write"):
        ring = swa.ring_from_rows(mla_moe.cache_row(c, k_r, k), true_lens,
                                  cfg.ring_rows)
    return gated(o, h, lp, cfg), ring


def window_decode(x, lp, ring, pos, max_len: int, listed, lanes, count,
                  cfg: Dots3NoteConfig):
    """One token of the window layer's attention half for every lane,
    ABSORBED: x [B, d], ring [B, ring_rows, row_width] the lanes' rings
    of this layer (the token's row is written at slot pos mod ring_rows,
    in place), max_len the positions a lane can reach, listed [B] the
    lanes that hold a request (lanes, count: their work list).  Returns
    (what it adds, ring)."""
    B = x.shape[0]
    k = cfg.swa
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    _, q_nope, q_rope, c, k_r = latent_inputs(
        h[:, None], lp, k, cfg, pos[:, None], _tables(k, max_len))
    with jax.named_scope("kv_write"):
        # (a lane that holds no request keeps its ring as it was)
        at = (jnp.arange(B), pos % cfg.ring_rows)
        ring = ring.at[at].set(jnp.where(
            listed[:, None], mla_moe.cache_row(c, k_r, k)[:, 0], ring[at]))
    q = _absorbed(q_nope[:, 0], q_rope[:, 0], lp, k)
    with jax.named_scope("swa_attn"):
        o = swa.swa_decode_attention(
            q, ring, swa.ring_bias(pos, cfg.ring_rows, cfg.window), lanes,
            count, dv=k.kv_lora_rank, sm_scale=k.qk_head_dim ** -0.5)
    with jax.named_scope("mla_absorb"):
        ov = jnp.einsum("bhc,hcv->bhv", o, lp["w_uv"])
    return gated(ov, h, lp, cfg), ring


# ---------------------------------------------------------------- prefill
def layer_prefill(params, x, lid: int, cfg: Dots3NoteConfig, true_lens):
    """Layer `lid` over whole rows x [b, T, d]: (x after it, what its
    attention hands the pool or the lane, the routed counts or None).
    The prefill program's body; the benchmark's judge calls it a layer at
    a time."""
    lp = params["layers"][lid]
    T = x.shape[1]
    live = jnp.arange(T)[None, :] < true_lens[:, None]
    mixer = full_prefill if cfg.layer_types[lid] == FULL else window_prefill
    y, kept = mixer(x, lp, cfg, true_lens)
    x = x + y
    y, cnt = ffn(x, lp, lid, cfg, live)
    return x + y, kept, cnt


def prefill(params: dict, tokens: jnp.ndarray, cfg: Dots3NoteConfig,
            true_lens: jnp.ndarray | None = None, lora=None):
    """Prompt pass.  tokens [b, T], right-padded; true_lens [b] (absent:
    every row is T long).  Returns the seam's (hidden [b, T, d] after the
    final norm; the latent rows, a full layer [b, T, 1, row_width]; the
    index keys, a full layer [b, T, 1, index_dim]; state: {"window": a
    window layer each [b, ring_rows, row_width]}, every row's at its TRUE
    length; counts int32 [routed layers, 4])."""
    b, T = tokens.shape
    if true_lens is None:
        true_lens = jnp.full((b,), T, jnp.int32)
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens, cfg.dtype)
    latent, index, rings, counts = [], [], [], []
    for lid, kind in enumerate(cfg.layer_types):
        x, kept, cnt = layer_prefill(params, x, lid, cfg, true_lens)
        if kind == FULL:
            latent.append(kept[0])
            index.append(kept[1])
        else:
            rings.append(kept)
        if cnt is not None:
            counts.append(cnt)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, latent, index, {"window": rings}, routed.stack_counts(counts)


# ------------------------------------------------------------ paged cache
def init_paged_cache(cfg: Dots3NoteConfig, batch: int, n_pages: int,
                     page: int) -> dict:
    """TWO pool leaves a FULL layer, a row a token each: the latent rows
    [n_pages, 1, page, row_width] and the index keys [n_pages, 1, page,
    index_dim]; a window layer holds no page: its lanes' rings are the
    `state` (the module's docstring)."""
    if cfg.ring_rows < cfg.window:
        raise ValueError(f"ring_rows {cfg.ring_rows} under the window "
                         f"{cfg.window}")
    n_full = cfg.count(FULL)
    return {
        "latent": [jnp.zeros((n_pages, 1, page, cfg.full.row_width),
                             cfg.dtype) for _ in range(n_full)],
        "index": [jnp.zeros((n_pages, 1, page, cfg.index_dim), cfg.dtype)
                  for _ in range(n_full)],
        "pos": jnp.zeros((batch,), jnp.int32),
        "state": {"window": [
            jnp.zeros((batch, cfg.ring_rows, cfg.swa.row_width), cfg.dtype)
            for _ in range(cfg.count(WINDOW))]}}


def scatter_prefill_pages(cache: dict, latent, index, state, page_ids,
                          row_ids, slots, true_lens,
                          aligned: bool = True) -> dict:
    """Write a prefill wave's rows into both pool leaves and each row's
    rings into its lane, where the lanes' rings lie (the cache is
    donated)."""
    with jax.named_scope("kv_write"):
        out = {name: [scatter_rows(p, new, page_ids, row_ids, aligned)
                      for p, new in zip(cache[name], rows)]
               for name, rows in (("latent", latent), ("index", index))}
        out["pos"] = cache["pos"].at[slots].set(true_lens)
    with jax.named_scope("state_write"):
        out["state"] = {"window": [
            lanes.at[slots].set(new.astype(lanes.dtype))
            for lanes, new in zip(cache["state"]["window"],
                                  state["window"])]}
    return out


# ----------------------------------------------------------------- decode
def decode_step_paged(params: dict, pages: dict, tails: dict, state: dict,
                      tokens: jnp.ndarray, pos: jnp.ndarray,
                      tail_start: jnp.ndarray, j, page_table: jnp.ndarray,
                      cfg: Dots3NoteConfig, lora=None, plan=None):
    """One decode step over both pool leaves, their in-block tails and
    the lanes' rings.  A lane whose table row starts at the trash page
    holds no request: it attends nothing, is routed nowhere and its rings
    are not touched.
    `plan` (the paged kernels' work list of pages) is the full layers'
    where their attention walks pages (`dsa.walks`); a ring needs no
    table.  Returns (logits [B,
    vocab] float32, tails, state, counts int32 [routed layers, 4])."""
    live = lanes_live(page_table)
    lanes, count = ssm.live_lanes(live)
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens, cfg.dtype)      # [B, d]
    latent_t, index_t = list(tails["latent"]), list(tails["index"])
    rings = list(state["window"])
    max_len = page_table.shape[1] * pages["latent"][0].shape[2]
    counts = []
    for lid, kind in enumerate(cfg.layer_types):
        lp = params["layers"][lid]
        i = cfg.before(lid)
        if kind == FULL:
            y, latent_t[i], index_t[i] = full_decode(
                x, lp, pages["latent"][i], pages["index"][i], latent_t[i],
                index_t[i], page_table, pos, tail_start, j, lanes, count,
                cfg, plan=plan)
        else:
            y, rings[i] = window_decode(x, lp, rings[i], pos, max_len,
                                        live, lanes, count, cfg)
        x = x + y
        y, cnt = ffn(x, lp, lid, cfg, live)
        x = x + y
        if cnt is not None:
            counts.append(cnt)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = project_logits(params, x).astype(F32)
    return (logits, {"latent": latent_t, "index": index_t},
            {"window": rings}, routed.stack_counts(counts))


# the serving seam's names (models/serving.py)
serve_prefill = prefill
serve_scatter = scatter_prefill_pages
serve_decode_step = decode_step_paged
