"""A decoder of gated delta-rule linear-attention layers (KDA) whose decay
gate has NO lower bound and whose write strength reaches 2, three layers
in four, beside a gated softmax GQA layer WITHOUT position embedding in
pages, every layer routed with a shared expert (`model_type`
`solar_open2`, e.g. Solar-Open2-250B), served.  This module gives the
serving seam (`ray_tpu.models.serving_model`) what `serve/llm.LLMEngine`
runs.  It has none of the optional capabilities (`serving_spec`'s `caps`
is empty): a lane carries a state matrix a head a KDA layer that no page
holds, so a radix prefix hit cannot restore it.

The equations (u = RMSNorm(x; `norm_eps`, weight); what the published
keys leave open is marked "assumed" and lives in ONE function here and
ONE in the reference `benchmarks/harness/refs/solar_open2.py`):

    x <- x + Mixer_l(RMSNorm(x));  x <- x + MoE_l(RMSNorm(x))

then `logits = W_head RMSNorm(x; final_norm)` (the head is untied).

**GQA mixer** (l in `gqa_layers`; `gqa_prefill`, the decode step): q = u
W_q [H x hd], k = u W_k, v = u W_v [kvh x hd], NO rotary embedding, no
bias, causal softmax of q k^T hd^-0.5, H / kvh query heads a key/value
head; o <- o * sigmoid(u W_gate), W_gate [d, H hd], an element a gate
(assumed: the config says `use_gqa_gate` only; `gqa_gate`); y = o W_o.
The cache row is K and V.

**KDA mixer** (the other layers; `models/kda_layer.py` under THIS
module's `kda_gate`, `ops/kda.py`), per head of `kda_head_dim`:

    q, k = L2Norm(silu(Conv(u W_q))), L2Norm(silu(Conv(u W_k)))
    v = silu(Conv(u W_v))          Conv: depthwise, causal, `conv_kernel`
    log a = -exp(A_log) softplus(u W_f1 W_f2 + dt_bias)   in (-inf, 0]
    beta = 2 sigmoid(u W_beta)                             in (0, 2)
    S_t = (I - beta k k^T) Diag(a) S_{t-1} + beta k v^T
    o = S_t^T q / sqrt(dk)
    y = W_o (RMSNorm_head(o) * sigmoid(u W_g1 W_g2))

the published Kimi Linear layer's gate (`kda_use_full_proj` false: the
low-rank pairs; their rank = the head's width: assumed) with
`kda_allow_neg_eigval`'s factor 2.  The gate has no bound: `kda_scan`
runs the form that is exact for any decay.

**MoE** (`models/routed.py`): sigmoid scores over ALL `n_experts` in
float32, a bias in the choice only, top `top_k`, w = `routed_scaling`
score / sum over the selected (assumed: the router's sigmoid and bias,
Solar Open's `glm4_moe` lineage); the SwiGLU experts THIS CHIP HOLDS
(`experts_held`) plus one shared SwiGLU expert of `n_shared_experts x
moe_ffn_dim` for every token.

**Layers** are a list, one dict a layer, and every program unrolls them
(four here: one period of the published forty-eight).

**Lane state** (`init_paged_cache()["state"]`): `{"conv": [KDA layers,
lanes, K-1, 3 H dk] the last pre-convolution rows; "kda": [KDA layers,
lanes, H, dk, dv] float32, updated in place by `kda_update`}`, beside a K
and a V pool leaf a GQA layer.

Device-side names: `kda_in_proj`, `kda_conv` (in a prefill the kernel
of that name: q, k, v from one pass over the projection; in a decode step
an XLA expression), `kda_scan` (the prefill
kernel) / `kda_update` (the decode kernel), `kda_out`, `attn_qkv`, `attn`
(`flash_fwd` / `paged_attn`), `gqa_gate`, `attn_out`, `moe_router`,
`moe_experts` (the grouped matmul's kernel is `moe_gmm`),
`shared_expert`, `lm_head`, beside `embed`, `kv_write`, `state_write`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import kda_layer, llama, routed
from ray_tpu.models.llama import attention, embed_lookup, rmsnorm
from ray_tpu.models.routed import route, shared_ffn
from ray_tpu.models.serving import ServingSpec, merged
from ray_tpu.ops import flash_attention, live_rows, ssm
from ray_tpu.ops.paged_attention import lanes_live, paged_decode_attention

GQA, KDA = "gqa", "kda"
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    dim: int = 4096
    n_layers: int = 48
    gqa_layers: tuple = tuple(range(0, 48, 4))
    n_heads: int = 64               # both mixers'
    n_kv_heads: int = 8             # the GQA layers'
    head_dim: int = 128
    kda_head_dim: int = 128         # `linear_attn_config.head_dim`
    conv_kernel: int = 4            # `short_conv_kernel_size`
    kda_chunk: int = 32             # no bound forces it (ROADMAP M9 d)
    moe_ffn_dim: int = 1280
    n_experts: int = 320            # the ROUTER's width
    experts_held: tuple = (0, 320)
    top_k: int = 8
    n_shared_experts: int = 1
    use_expert_bias: bool = True    # assumed
    norm_topk_prob: bool = True
    routed_scaling: float = 1.0
    norm_eps: float = 1e-5
    max_seq: int = 1048576
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32

    @property
    def layer_types(self) -> tuple:
        return tuple(GQA if lid in self.gqa_layers else KDA
                     for lid in range(self.n_layers))

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def before(self, lid: int) -> int:
        """Layers of layer `lid`'s kind that come before it."""
        kinds = self.layer_types
        return kinds[:lid].count(kinds[lid])


def serving_configs() -> dict[str, SolarOpen2Config]:
    return {
        "solar-open2-250b": SolarOpen2Config(),
        "solar-open2-debug": SolarOpen2Config(
            vocab_size=256, dim=64, n_layers=4, gqa_layers=(0,), n_heads=4,
            n_kv_heads=2, head_dim=16, kda_head_dim=16, kda_chunk=8,
            moe_ffn_dim=32, n_experts=8, experts_held=(0, 8), top_k=2,
            max_seq=128),
    }


def prefill_params(cfg: SolarOpen2Config) -> tuple[int, int]:
    """Matmul parameters a prefill program STREAMS whatever it holds and
    those ONE position multiplies (`routed.prefill_params`)."""
    d, qd = cfg.dim, cfg.n_heads * cfg.head_dim
    gqa = 3 * d * qd + 2 * d * cfg.n_kv_heads * cfg.head_dim
    shared = 3 * d * cfg.moe_ffn_dim * cfg.n_shared_experts
    rest = (cfg.count(KDA) * kda_layer.matmul_params(cfg)
            + cfg.count(GQA) * gqa + cfg.n_layers * shared)
    return routed.prefill_params(cfg, rest, cfg.n_layers, cfg.experts_held)


def serving_spec(cfg: SolarOpen2Config) -> ServingSpec:
    """No optional capability.  The KDA layers keep a state matrix a
    head, which `kda_scan` fills a prefill (in chunks of `kda_chunk`) and
    `kda_update` updates a decode step, beside a convolution's last rows:
    the bytes of both that ONE prefill row hands the scatter program.
    The GQA layers prefill through `flash_fwd` (`prefill_attn_blocks`)."""
    n_kda = cfg.count(KDA)
    return ServingSpec(
        lane_state_layers=n_kda,
        prefill_state_bytes=n_kda * kda_layer.state_bytes(cfg),
        prefill_params=prefill_params(cfg), routed_layers=cfg.n_layers,
        counters={**flash_attention.PREFILL_COUNTERS, **ssm.SCAN_COUNTERS,
                  **live_rows.COUNTERS, **routed.COUNTERS},
        decode_work=lambda rows, k, *_table: ssm.update_work(
            n_kda, len(rows), k),
        prefill_work=lambda true_lens, bucket: merged(
            flash_attention.prefill_work(true_lens, bucket),
            ssm.scan_work(n_kda, cfg.kda_chunk, true_lens, bucket),
            live_rows.prefill_work(true_lens, bucket)),
        routed_work=functools.partial(routed.routed_work, cfg,
                                      cfg.experts_held))


# ---------------------------------------------------------------- params
def init_params(key: jax.Array, cfg: SolarOpen2Config,
                expert_bias_std: float = 0.02) -> dict:
    """Every weight from one key: matrices normal, fan-in scaled, in the
    serving dtype; norm weights 1; the experts of `experts_held` only;
    `expert_bias` N(0, expert_bias_std) over all `n_experts`.  The
    recurrence in the published layer's regime (assumed, as the
    checkpoint's values are not in the config): A_log = log(U(1, 16)),
    dt_bias the inverse softplus of a log-uniform dt in [0.001, 0.1], so
    a channel's decay a step lies between exp(-0.001) and exp(-1.6)
    before the input moves it, and a steep channel under a large input
    falls by exp(-20) and more: past what a bounded gate allows."""
    d, H, f = cfg.dim, cfg.n_heads, cfg.moe_ffn_dim
    inner = H * cfg.kda_head_dim
    qd, kvd = H * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    fs = f * cfg.n_shared_experts
    G = cfg.experts_held[1] - cfg.experts_held[0]
    keys = iter(jax.random.split(key, 4 + 24 * cfg.n_layers))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(cfg.dtype)

    layers = []
    for kind in cfg.layer_types:
        lp = {"norm1": jnp.ones((d,), cfg.dtype),
              "norm2": jnp.ones((d,), cfg.dtype)}
        if kind == KDA:
            lp.update(kda_layer.init_layer(w, keys, cfg))
            dt = jnp.exp(jax.random.uniform(
                next(keys), (inner,), F32, jnp.log(0.001), jnp.log(0.1)))
            lp.update(
                A_log=jnp.log(jax.random.uniform(next(keys), (H,), F32,
                                                 1.0, 16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)))
        else:
            lp.update(wq=w((d, qd), d), wk=w((d, kvd), d), wv=w((d, kvd), d),
                      w_gate=w((d, qd), d), wo=w((qd, d), qd))
        lp.update(router=w((d, cfg.n_experts), d),
                  expert_bias=expert_bias_std * jax.random.normal(
                      next(keys), (cfg.n_experts,), F32),
                  w13=w((G, d, 2 * f), d), w2=w((G, f, d), f),
                  sw1=w((d, fs), d), sw3=w((d, fs), d), sw2=w((fs, d), fs))
        layers.append(lp)
    return {"embed": w((cfg.vocab_size, d), d), "layers": layers,
            "final_norm": jnp.ones((d,), cfg.dtype),
            "lm_head": w((d, cfg.vocab_size), d)}


def project_logits(params: dict, h: jnp.ndarray) -> jnp.ndarray:
    """The head (untied)."""
    with jax.named_scope("lm_head"):
        return h @ params["lm_head"]


# plain residual around a mixer that walks its rows: x + what it computes
RESIDUAL = (lambda x: (x, ())), (lambda x, _maps, y: x + y.astype(x.dtype))


# ---------------------------------------------------------------- KDA mixer
def kda_gate(h, lp, cfg: SolarOpen2Config):
    """The published layer's gate: the log decay a key channel,
    -exp(A_log) softplus(u W_f1 W_f2 + dt_bias) in (-inf, 0], NOT clamped,
    and the write strength a head, 2 sigmoid(u W_beta) in (0, 2)
    (`kda_allow_neg_eigval`): (g [..., H, dk], beta [..., H]) float32.
    THIS family's gate: the rest of the mixer is `models/kda_layer.py`'s."""
    f = (h @ lp["wf1"]) @ lp["wf2"]
    A = jnp.repeat(jnp.exp(lp["A_log"]), cfg.kda_head_dim)
    g = -A * jax.nn.softplus(f.astype(F32) + lp["dt_bias"])
    beta = 2.0 * jax.nn.sigmoid((h @ lp["w_beta"]).astype(F32))
    return g.reshape(*h.shape[:-1], cfg.n_heads, cfg.kda_head_dim), beta


def kda_prefill(x, lp, cfg: SolarOpen2Config, true_lens):
    """x + the KDA mixer over whole rows x [b, T, d] (zeros past the
    walked chunks), and what it hands the lane (`kda_layer.prefill` under
    this module's gate, which has no bound)."""
    return kda_layer.prefill(x, lp, cfg, true_lens, kda_gate, RESIDUAL,
                             unbounded=True)


def kda_decode(x, lp, conv, state, layer, lanes, count,
               cfg: SolarOpen2Config):
    """`kda_layer.decode` under this module's gate: (what the mixer
    computes, conv shifted, state)."""
    return kda_layer.decode(x, lp, conv, state, layer, lanes, count, cfg,
                            kda_gate)


# ---------------------------------------------------------------- GQA mixer
def softmax_scale(cfg: SolarOpen2Config) -> float:
    return cfg.head_dim ** -0.5


def gqa_gate(o, h, lp, cfg: SolarOpen2Config):
    """o * sigmoid(u W_gate), an element a gate (assumed form); o [...,
    H hd] the attention's output, h the normed input."""
    with jax.named_scope("gqa_gate"):
        gate = jax.nn.sigmoid((h @ lp["w_gate"]).astype(F32))
        return (o.astype(F32) * gate).astype(cfg.dtype)


def gqa_prefill(x, lp, cfg: SolarOpen2Config, true_lens):
    """x + the gated GQA mixer over whole rows x [b, T, d], and the rows
    for the pool (k, v [b, T, kvh, hd]).  What follows the attention (the
    gate, `wo`, the residual) walks the rows up to the longest true
    length: zeros past the walked chunks."""
    b, T, _ = x.shape
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    with jax.named_scope("attn_qkv"):
        q = (h @ lp["wq"]).reshape(b, T, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["wk"]).reshape(b, T, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"]).reshape(b, T, cfg.n_kv_heads, cfg.head_dim)
    o = attention(q, k, v, causal=True, lengths=true_lens,
                  sm_scale=softmax_scale(cfg))

    def after(args, _first):
        x, h, o = args
        o = gqa_gate(o.reshape(*o.shape[:2], -1), h, lp, cfg)
        with jax.named_scope("attn_out"):
            return x + (o @ lp["wo"]).astype(x.dtype)

    return (live_rows.walk(after, (x, h, o), jnp.max(true_lens)),
            (k.astype(cfg.dtype), v.astype(cfg.dtype)))


# ------------------------------------------------------------ feed-forward
def routed_ffn(h2, lp, cfg: SolarOpen2Config, live=None):
    """`routed.routed_ffn` for the experts this chip holds, under THIS
    module's `route`."""
    return routed.routed_ffn(h2, lp, cfg, live, cfg.experts_held,
                             route_fn=route)


def ffn(x, lp, cfg: SolarOpen2Config, live=None):
    """x + MoE(RMSNorm(x)) for x [..., d]: (that, the routed counts).
    Prefill and decode share it.  Whole rows x [b, T, d] (a prefill): the
    routed loop walks the live rows itself, and the shared expert with
    the residual after it walks up to the last `live` position
    (`live_rows.walk`), zeros past the walked chunks."""
    h = rmsnorm(x, lp["norm2"], cfg.norm_eps)
    y, counts = routed_ffn(h.reshape(-1, cfg.dim), lp, cfg,
                           None if live is None else live.reshape(-1))

    def shared(args, _first):
        x, h, y = args
        return x + (y + shared_ffn(h, lp, cfg.dtype)).astype(x.dtype)

    args = (x, h, y.reshape(h.shape))
    if x.ndim < 3:          # a decode step's [B, d]: one token a lane
        return shared(args, None), counts
    n_live = x.shape[1] if live is None else live_rows.count(live)
    return live_rows.walk(shared, args, n_live), counts


# ---------------------------------------------------------------- prefill
def layer_prefill(params, x, lid: int, cfg: SolarOpen2Config, true_lens):
    """Layer `lid` over whole rows x [b, T, d]: (x after it, what its
    mixer hands the pool or the lane, the routed counts).  The prefill
    program's body; the benchmark's judge calls it a layer at a time."""
    lp = params["layers"][lid]
    live = jnp.arange(x.shape[1])[None, :] < true_lens[:, None]
    mixer = kda_prefill if cfg.layer_types[lid] == KDA else gqa_prefill
    x, kept = mixer(x, lp, cfg, true_lens)
    x, cnt = ffn(x, lp, cfg, live)
    return x, kept, cnt


def prefill(params: dict, tokens: jnp.ndarray, cfg: SolarOpen2Config,
            true_lens: jnp.ndarray | None = None, lora=None):
    """Prompt pass.  tokens [b, T], right-padded; true_lens [b] (absent:
    every row is T long); `lora` is the seam's slot for adapters, which
    this model has not (None).  Returns (hidden [b, T, d] after the final
    norm; ks, vs: a GQA layer each [b, T, kvh, hd]; state: {"conv": a KDA
    layer each [b, K-1, 3 H dk], "kda": a KDA layer each [b, H, dk, dv]},
    every row's at its TRUE length; counts int32 [layers, COUNTS])."""
    b, T = tokens.shape
    if true_lens is None:
        true_lens = jnp.full((b,), T, jnp.int32)
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens, cfg.dtype)
    ks, vs, counts = [], [], []
    state = {"conv": [], "kda": []}
    for lid, kind in enumerate(cfg.layer_types):
        x, kept, cnt = layer_prefill(params, x, lid, cfg, true_lens)
        if kind == KDA:
            state["conv"].append(kept[0])
            state["kda"].append(kept[1])
        else:
            ks.append(kept[0])
            vs.append(kept[1])
        counts.append(cnt)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, ks, vs, state, routed.stack_counts(counts)


# ------------------------------------------------------------ paged cache
def init_paged_cache(cfg: SolarOpen2Config, batch: int, n_pages: int,
                     page: int) -> dict:
    """The page pool of `llama.init_paged_kv_cache` with leaves for the
    GQA layers only, and `state` (the module's docstring)."""
    shape = (n_pages, cfg.n_kv_heads, page, cfg.head_dim)
    n_gqa, n_kda = cfg.count(GQA), cfg.count(KDA)
    dk = cfg.kda_head_dim
    return {"k": [jnp.zeros(shape, cfg.dtype) for _ in range(n_gqa)],
            "v": [jnp.zeros(shape, cfg.dtype) for _ in range(n_gqa)],
            "pos": jnp.zeros((batch,), jnp.int32),
            "state": {
                "conv": jnp.zeros((n_kda, batch, cfg.conv_kernel - 1,
                                   3 * cfg.n_heads * dk), cfg.dtype),
                "kda": jnp.zeros((n_kda, batch, cfg.n_heads, dk, dk),
                                 cfg.state_dtype)}}


def scatter_prefill_pages(cache: dict, ks, vs, state, page_ids, rows,
                          slots, true_lens, aligned: bool = True) -> dict:
    """Write a prefill wave's K/V into the page pool (llama's scatter)
    and each row's state into its lane, where the lanes' state lies (the
    cache is donated; duplicate padding rows write one lane the same
    values)."""
    out = llama.scatter_prefill_pages(
        {"k": cache["k"], "v": cache["v"], "pos": cache["pos"]}, ks, vs,
        page_ids, rows, slots, true_lens, aligned=aligned)
    with jax.named_scope("state_write"):
        out["state"] = {
            name: lanes.at[:, slots].set(jnp.stack(state[name]).astype(
                lanes.dtype))
            for name, lanes in cache["state"].items()}
    return out


# ----------------------------------------------------------------- decode
def gqa_decode(x, lp, k_pages, v_pages, tk, tv, page_table, pos,
               tail_start, j, cfg: SolarOpen2Config, plan=None):
    """One token of the gated GQA mixer for every lane: x [B, d], the
    layer's two pool leaves (read-only) and their tails (the new row
    lands at column j).  Returns (what it computes, the K tail, the V
    tail)."""
    B = x.shape[0]
    hd, n_rep = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    with jax.named_scope("attn_qkv"):
        # the products held flat, or wq / wk / wv are re-laid-out every
        # step (llama._decode_qkv)
        q, k, v = llama._decode_qkv(h[:, None], lp, cfg)
        q = q.reshape(B, cfg.n_kv_heads, n_rep, hd)
        k = k.reshape(B, cfg.n_kv_heads, 1, hd)
        v = v.reshape(B, cfg.n_kv_heads, 1, hd)
    with jax.named_scope("kv_write"):
        tk = lax.dynamic_update_slice(tk, k.astype(cfg.dtype), (0, 0, j, 0))
        tv = lax.dynamic_update_slice(tv, v.astype(cfg.dtype), (0, 0, j, 0))
    with jax.named_scope("attn"):
        o = paged_decode_attention(
            q.astype(cfg.dtype), k_pages, v_pages, tk, tv, page_table, pos,
            tail_start, plan=plan, sm_scale=softmax_scale(cfg))
    o = gqa_gate(o.reshape(B, cfg.n_heads * hd), h, lp, cfg)
    with jax.named_scope("attn_out"):
        return o @ lp["wo"], tk, tv


def decode_step_paged(params: dict, pages: dict, tails: dict, state: dict,
                      tokens: jnp.ndarray, pos: jnp.ndarray,
                      tail_start: jnp.ndarray, j, page_table: jnp.ndarray,
                      cfg: SolarOpen2Config, lora=None, plan=None):
    """One decode step over the paged cache, the in-block tails (see
    llama.decode_step_paged) and the lanes' state.  A lane whose table
    row starts at the trash page holds no request: it attends nothing,
    is routed nowhere, and neither its state matrices nor its
    convolution rows are touched.  Returns (logits [B, vocab] float32,
    tails, state, counts int32 [layers, routed.COUNTS])."""
    live = lanes_live(page_table)
    lanes, count = ssm.live_lanes(live)
    x = embed_lookup(params["embed"], tokens[:, None], cfg.dtype)[:, 0]
    conv, kda_state = state["conv"], state["kda"]
    new_tk, new_tv, counts = [], [], []
    for lid, kind in enumerate(cfg.layer_types):
        lp, i = params["layers"][lid], cfg.before(lid)
        if kind == KDA:
            y, rows, kda_state = kda_decode(
                x, lp, conv[i], kda_state, jnp.int32(i), lanes, count, cfg)
            conv = conv.at[i].set(
                jnp.where(live[:, None, None], rows, conv[i]))
        else:
            y, tk, tv = gqa_decode(
                x, lp, pages["k"][i], pages["v"][i], tails["k"][i],
                tails["v"][i], page_table, pos, tail_start, j, cfg, plan)
            new_tk.append(tk)
            new_tv.append(tv)
        x, cnt = ffn(x + y.astype(x.dtype), lp, cfg, live)
        counts.append(cnt)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = project_logits(params, x).astype(F32)
    return (logits, {"k": new_tk, "v": new_tv},
            {"conv": conv, "kda": kda_state}, routed.stack_counts(counts))


# the serving seam's names (models/serving.py)
serve_prefill = prefill
serve_scatter = scatter_prefill_pages
serve_decode_step = decode_step_paged
