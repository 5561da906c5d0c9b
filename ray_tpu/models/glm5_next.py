"""A decoder of gated delta-rule linear-attention layers (KDA) beside a
few latent-attention layers that read their pool through a LEARNED
SELECTION, every sublayer wrapped in a multi-stream residual (mHC), with
routed experts and a shared expert (`model_type` `glm5_next_text`, e.g.
GLM-5.3-Flash), served.  This module gives the serving seam
(`ray_tpu.models.serving_model`) what `serve/llm.LLMEngine` runs.  It has
none of the optional capabilities (`serving_spec`'s `caps` is empty): a
lane carries a state matrix a head a KDA layer that no page holds, and
the pool is a latent row a token beside a pooled index key a group.

The equations (h = RMSNorm(.), eps `norm_eps`; what the published keys
leave open is marked "assumed" and lives in ONE function here and ONE in
the reference `benchmarks/harness/refs/glm5_next.py`).

**Residual path** (`mhc`, n = `hc_mult` streams; manifold-constrained
hyper-connections).  The stream is X [n, d] a token, X_0 = the
embedding copied to the n rows.  Every sublayer F (a mixer or a
feed-forward, its own pre-norm inside) is wrapped (`mhc_maps`,
`sublayer`):

    x~ = vec(X) / rms(vec(X); hc_eps)                        float32
    H_pre  = sigmoid(a_pre (x~ phi_pre) + b_pre)              [n]
    H_post = 2 sigmoid(a_post (x~ phi_post) + b_post)         [n]
    H_res  = Sinkhorn(exp(a_res mat(x~ phi_res) + b_res))     [n, n]
    X <- H_res X + H_post (outer) F(H_pre X)

Sinkhorn = rows then columns normalised, `hc_iters` times.  Logits =
h(sum of the n rows of X_L) W_head (assumed: summed; head untied).  A
PREFILL keeps the n streams apart, n arrays [b, T, d] (`mhc_halves`,
`layer_prefill`): the same equations with no [.., n, d] array formed.

**KDA mixer** (`layer_types[l] == "linear_attention"`; the glue is
`models/kda_layer.py`'s, shared with `models/solar_open2.py`, under THIS
module's `kda_gate`; `ops/kda.py`), u = h(input), per head of
`kda_head_dim`:

    q, k = L2Norm(silu(Conv(u W_q))), L2Norm(silu(Conv(u W_k)))
    v = silu(Conv(u W_v))          Conv: depthwise, causal, `conv_kernel`
    log a = gate_lower_bound * sigmoid(exp(A_log) (u W_f1 W_f2 + dt_bias))
    beta = sigmoid(u W_beta)
    S_t = (I - beta k k^T) Diag(a) S_{t-1} + beta k v^T
    o = S_t^T q / sqrt(dk)
    y = W_o (RMSNorm_head(o) * sigmoid(u W_g1 W_g2))

(the bounded gate's form and the rank of W_f, W_g = the head's width are
assumed: `kda_gate`).  No position embedding.

**Sparse latent mixer** (`"deepseek_sparse_attention"`; `dsa_inputs`,
`ops/sparse_attention.py`): c^q = h(u W_qa), q_i = (c^q W_qb)_i; the
cache row c = h(u W_kva), `kv_lora_rank` wide, nothing else (no RoPE on
the attention: `mla_use_nope`).  Indexer: q^I_j = RoPE(c^q W_qI)_j,
k^I = RoPE(LayerNorm(u W_kI)), w = (J d_I)^-0.5 u W_w; the index pool
holds ONE key a COMPLETE group of `index_pool` positions, their mean
(assumed; `pool_index_keys`); a query scores the complete groups below
it, keeps the `index_topk / index_pool` best and attends their
positions and its own incomplete group (`selected_mask`; a decode step:
`dsa.decode_attend`, which walks the lane's pages under a bias a row
where the table holds at most `dsa.RATIO` selections' rows, as every
served cell's does, and gathers the rows through `select_rows` under a
longer table).  k_{s,i} = c_s W_UK,i, v_{s,i} = c_s W_UV,i; prefill
runs expanded, decode absorbed over the selected rows.  The indexer's
rotary: the first `index_rope_dim` of `index_dim`, interleaved pairs,
theta `index_theta` (assumed: `index_rope`).

**Feed-forward**: SwiGLU of `ffn_dim` where `ffn_types[l] == "dense"`;
elsewhere `models/routed.py`: sigmoid scores over ALL `n_experts`, top
`top_k` of score + bias, w = `routed_scaling` score / sum, the experts
THIS CHIP HOLDS (`experts_held`) plus the shared expert.  Every SwiGLU
is clamped (`swiglu_limit`; assumed form: `routed.clamp`).

**Layers** are a list, one dict a layer, and every program unrolls them
(five here: a period of the published forty-five); the lanes' state is
ONE array a kind over the layers of that kind.

**Lane state** (`init_paged_cache()["state"]`): `{"conv": [KDA layers,
lanes, K-1, 3 H dk] the last pre-convolution rows; "kda": [KDA layers,
lanes, H, dk, dv] float32, updated in place by `kda_update`; "ipart":
[sparse layers, lanes, index_dim] float32, the sum of the index keys of
the lane's incomplete group}`.  **Pool**: `{"latent": [n_pages, 1, page,
kv_lora_rank] a sparse layer; "index": [n_pages, 1, page / index_pool,
index_dim] a sparse layer}`: the second leaf holds one row a GROUP of
positions, and the engine takes its tail and its merge from that shape.

Not served: the multi-token-prediction layer (`num_nextn_predict_layers`)
and the vision tower.

Device-side names: `kda_in_proj`, `kda_conv` (in a prefill the kernel
of that name: q, k, v from one pass over the projection; in a decode step
an XLA expression), `kda_scan` (the prefill
kernel: a head's state stays in VMEM across a row's chunks, position
blocks past the row's true length get no step) / `kda_update` (the
decode kernel), `kda_out`, `dsa_index`, `dsa_select`,
`dsa_attn`, `mla_q`, `mla_kv_down`, `mla_absorb`, `mla_out`, `mhc_mix`,
`state_write`, beside `moe_router`, `moe_experts`, `shared_expert`,
`embed`, `mlp`, `lm_head`, `kv_write`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import kda_layer, routed
from ray_tpu.models.kda_layer import BARE
from ray_tpu.models.llama import embed_lookup, rmsnorm, scatter_rows
from ray_tpu.models.routed import route, shared_ffn
from ray_tpu.models.serving import ServingSpec, merged
from ray_tpu.ops import kda  # noqa: F401  (`kda.max_chunk`: `kda_chunk`)
from ray_tpu.ops import live_rows, sparse_attention as dsa, ssm
from ray_tpu.ops.norms import layernorm
from ray_tpu.ops.paged_attention import lanes_live

KDA = "linear_attention"
DSA = "deepseek_sparse_attention"
DENSE, SPARSE = "dense", "sparse"
F32 = jnp.float32
# The sparse prefill attention holds the scores of HEAD_BLOCK heads x
# Q_BLOCK queries x every key below them at once, in float32: 134 MB at
# 8,192 keys.  At 16 x 1,024 (537 MB) the chip ran the softmax's passes
# sixteen times slower a byte than at 268 MB (46.9 ms against 1.17 ms a
# pass: my chip run, PR 41; PERF.md section 6).
Q_BLOCK = 512
HEAD_BLOCK = 8


@dataclasses.dataclass(frozen=True)
class Glm5NextConfig:
    vocab_size: int = 154880
    dim: int = 4096
    layer_types: tuple = ((KDA,) * 3 + (DSA,)) * 11 + (KDA,)
    ffn_types: tuple = (DENSE,) * 3 + (SPARSE,) * 42
    n_heads: int = 64               # both mixers'
    kda_head_dim: int = 128         # `linear_attn_config.head_dim`
    conv_kernel: int = 4            # `short_conv_kernel_size`
    gate_lower_bound: float = -5.0
    kda_chunk: int = 32             # `ops/kda.max_chunk(gate_lower_bound)`
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_head_dim: int = 256          # `qk_nope_head_dim` (no rotary part)
    v_head_dim: int = 256
    index_heads: int = 32
    index_dim: int = 128
    index_rope_dim: int = 64        # assumed
    index_theta: float = 10000.0    # assumed
    index_topk: int = 2048          # in TOKENS
    index_pool: int = 4             # positions a pooled index key
    ffn_dim: int = 12288
    moe_ffn_dim: int = 2048
    n_experts: int = 288            # the ROUTER's width
    experts_held: tuple = (0, 288)
    top_k: int = 8
    n_shared_experts: int = 1
    use_expert_bias: bool = True    # `topk_method` noaux_tc
    norm_topk_prob: bool = True
    routed_scaling: float = 2.5
    swiglu_limit: float = 10.0
    hc_mult: int = 4
    hc_iters: int = 20
    hc_eps: float = 1e-6
    norm_eps: float = 1e-5
    max_seq: int = 1048576
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kda_inner(self) -> int:
        return self.n_heads * self.kda_head_dim

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def before(self, lid: int) -> int:
        """Layers of layer `lid`'s kind that come before it."""
        return self.layer_types[:lid].count(self.layer_types[lid])

    def is_routed(self, lid: int) -> bool:
        return self.ffn_types[lid] == SPARSE


def serving_configs() -> dict[str, Glm5NextConfig]:
    return {
        "glm-5.3-flash": Glm5NextConfig(),
        "glm5-next-debug": Glm5NextConfig(
            vocab_size=256, dim=64, layer_types=(KDA, DSA, KDA, KDA),
            ffn_types=(DENSE, SPARSE, SPARSE, SPARSE), n_heads=4,
            kda_head_dim=16, kda_chunk=8, q_lora_rank=48, kv_lora_rank=32,
            qk_head_dim=16, v_head_dim=16, index_heads=2, index_dim=16,
            index_rope_dim=8, index_topk=16, ffn_dim=128, moe_ffn_dim=32,
            n_experts=8, experts_held=(0, 8), top_k=2, max_seq=128),
    }


def _decode_work(cfg: Glm5NextConfig, rows, k: int, page: int, maxp: int
                 ) -> tuple[dict, dict]:
    """One decode window of `k` steps over live lanes that start it on
    `rows` cached rows each: `kda_update`'s lane-steps and what the
    selection read (`ops/sparse_attention.decode_work`)."""
    return merged(ssm.update_work(cfg.count(KDA), len(rows), k),
                  dsa.decode_work(cfg.count(DSA), cfg.index_pool,
                                  cfg.index_topk, rows, k, page, maxp))


def serving_spec(cfg: Glm5NextConfig) -> ServingSpec:
    """No optional capability.  The KDA layers keep a state matrix a
    head, which `kda_scan` fills a prefill (in chunks of `kda_chunk`)
    and `kda_update` updates a decode step, beside a convolution's last
    rows; a sparse layer an index key in the making: the bytes of all
    that ONE prefill row hands the scatter program.  The sparse
    prefill attention is `dsa.masked_prefill_attention`, not
    `flash_fwd`: `dsa_prefill_blocks*`, no `prefill_attn_blocks`."""
    n_kda = cfg.count(KDA)
    return ServingSpec(
        lane_state_layers=n_kda,
        prefill_state_bytes=(n_kda * kda_layer.state_bytes(cfg)
                             + cfg.count(DSA) * 4 * cfg.index_dim),
        prefill_params=prefill_params(cfg),
        routed_layers=_routed_layers(cfg),
        counters={**ssm.SCAN_COUNTERS, **dsa.PREFILL_COUNTERS,
                  **dsa.COUNTERS, **live_rows.COUNTERS, **routed.COUNTERS},
        decode_work=functools.partial(_decode_work, cfg),
        prefill_work=lambda true_lens, bucket: merged(
            ssm.scan_work(n_kda, cfg.kda_chunk, true_lens, bucket),
            dsa.prefill_work(cfg.count(DSA), true_lens, bucket),
            live_rows.prefill_work(true_lens, bucket)),
        routed_work=functools.partial(routed.routed_work, cfg,
                                      cfg.experts_held))


def _routed_layers(cfg: Glm5NextConfig) -> int:
    return cfg.ffn_types.count(SPARSE)


def prefill_params(cfg: Glm5NextConfig) -> tuple[int, int]:
    """Matmul parameters a prefill program STREAMS whatever it holds and
    those ONE position multiplies (`routed.prefill_params`)."""
    d, H = cfg.dim, cfg.n_heads
    kda_p = kda_layer.matmul_params(cfg)
    dsa_p = (d * cfg.q_lora_rank + cfg.q_lora_rank * H * cfg.qk_head_dim
             + d * cfg.kv_lora_rank
             + H * cfg.kv_lora_rank * (cfg.qk_head_dim + cfg.v_head_dim)
             + H * cfg.v_head_dim * d
             + cfg.q_lora_rank * cfg.index_heads * cfg.index_dim
             + d * (cfg.index_dim + cfg.index_heads))
    hc = 2 * cfg.hc_mult * d * (2 * cfg.hc_mult + cfg.hc_mult ** 2)
    shared = 3 * d * cfg.moe_ffn_dim * cfg.n_shared_experts
    rest = (cfg.count(KDA) * kda_p + cfg.count(DSA) * dsa_p
            + cfg.n_layers * hc + _routed_layers(cfg) * shared
            + cfg.ffn_types.count(DENSE) * 3 * d * cfg.ffn_dim)
    return routed.prefill_params(cfg, rest, _routed_layers(cfg),
                                 cfg.experts_held)


# ---------------------------------------------------------------- params
def init_params(key: jax.Array, cfg: Glm5NextConfig,
                expert_bias_std: float = 0.02) -> dict:
    """Every weight from one key: matrices normal, fan-in scaled, in the
    serving dtype; norm weights 1; the experts of `experts_held` only.
    The recurrence in a regime where the state matters (assumed, as the
    checkpoint's values are not in the config): A_log = log(U(0.5, 2)),
    dt_bias = U(-6, -1), so a channel's decay a step lies between
    exp(-0.012) and exp(-1.3) before the input moves it.  The mHC maps
    away from the identity (assumed): a_pre = a_post = 1, a_res = 0.5, b_pre
    = b_post = 0, b_res = 2 I, phi normal at (n d)^-0.5, so that H_res
    keeps most of a stream where it is and mixes the rest by the token.
    `expert_bias` N(0, expert_bias_std) over all `n_experts`."""
    d, H, n = cfg.dim, cfg.n_heads, cfg.hc_mult
    f, fs = cfg.moe_ffn_dim, cfg.moe_ffn_dim * cfg.n_shared_experts
    G = cfg.experts_held[1] - cfg.experts_held[0]
    keys = iter(jax.random.split(key, 4 + 40 * cfg.n_layers))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(cfg.dtype)

    def hc():
        m = 2 * n + n * n
        return {"phi": w((n * d, m), n * d),
                "a": jnp.asarray([1.0, 1.0, 0.5], F32),
                "b": jnp.concatenate([jnp.zeros((2 * n,), F32),
                                      2.0 * jnp.eye(n, dtype=F32).ravel()])}

    layers = []
    for lid, kind in enumerate(cfg.layer_types):
        lp = {"norm1": jnp.ones((d,), cfg.dtype),
              "norm2": jnp.ones((d,), cfg.dtype),
              "hc_mix": hc(), "hc_ffn": hc()}
        if kind == KDA:
            lp.update(kda_layer.init_layer(w, keys, cfg))
        else:
            r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
            lp.update(
                wqa=w((d, qr), d), q_norm=jnp.ones((qr,), cfg.dtype),
                wqb=w((qr, H * cfg.qk_head_dim), qr), wkva=w((d, r), d),
                kv_norm=jnp.ones((r,), cfg.dtype),
                w_uk=w((H, cfg.qk_head_dim, r), r),
                w_uv=w((H, r, cfg.v_head_dim), r),
                wo=w((H * cfg.v_head_dim, d), H * cfg.v_head_dim),
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


# ------------------------------------------------------- the residual path
def sinkhorn(m, iters: int):
    """Rows, then columns, normalised, `iters` times (m > 0, [..., n, n])."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


def mhc_maps(X, hp, cfg: Glm5NextConfig):
    """X [..., n, d] -> (H_pre [..., n], H_post [..., n], H_res [..., n,
    n]) float32.  The norm's scale is one number a token, so it is
    applied to the n (n + 2) projections and not to the n d inputs."""
    n = cfg.hc_mult
    flat = X.reshape(*X.shape[:-2], n * X.shape[-1])
    ms = jnp.mean(jnp.square(flat.astype(F32)), axis=-1, keepdims=True)
    z = jnp.dot(flat, hp["phi"], preferred_element_type=F32) \
        * lax.rsqrt(ms + cfg.hc_eps)
    return _maps_of(z, hp, cfg)


def _maps_of(z, hp, cfg: Glm5NextConfig):
    """The three maps from the normed projections z [..., n (n + 2)]."""
    n = cfg.hc_mult
    a, b = hp["a"], hp["b"]
    pre = jax.nn.sigmoid(a[0] * z[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + b[n:2 * n])
    res = jnp.exp(a[2] * z[..., 2 * n:] + b[2 * n:]).reshape(
        *z.shape[:-1], n, n)
    return pre, post, sinkhorn(res, cfg.hc_iters)


def mhc_halves(hp, cfg: Glm5NextConfig):
    """The two halves of the residual path around a sublayer of WHOLE
    ROWS, each computed a token alone, over the n streams APART, xs = n
    arrays [b, T, d] (`layer_prefill`): enter(xs) -> (H_pre X [b, T, d],
    the maps it keeps for leave); leave(xs, those, y [b, T, d]) -> the n
    streams of H_res X + H_post (outer) y.  `sublayer`'s equations, a mix
    written as n multiply-adds a stream in float32 over arrays whose
    rows are whole tiles, where the einsums over [.., n, d] had every
    array re-tiled to n rows a tile and back (PERF.md section 6, PR 55).
    """
    n, d = cfg.hc_mult, cfg.dim

    def total(terms):
        return functools.reduce(jnp.add, list(terms))

    def mix(weights, xs):       # sum_j weights[..., j] xs[j], float32
        return total(weights[..., j, None] * x.astype(F32)
                     for j, x in enumerate(xs))

    def enter(xs):
        with jax.named_scope("mhc_mix"):
            sq = total(jnp.sum(jnp.square(x.astype(F32)), axis=-1,
                               keepdims=True) for x in xs)
            z = total(jnp.dot(x, hp["phi"][j * d:(j + 1) * d],
                              preferred_element_type=F32)
                      for j, x in enumerate(xs))
            pre, post, res = _maps_of(
                z * lax.rsqrt(sq / (n * d) + cfg.hc_eps), hp, cfg)
            x_in = mix(pre, xs).astype(cfg.dtype)
        return x_in, (post, res)

    def leave(xs, maps, y):
        post, res = maps
        with jax.named_scope("mhc_mix"):
            y = y.astype(F32)
            return tuple((mix(res[..., m, :], xs)
                          + post[..., m, None] * y).astype(cfg.dtype)
                         for m in range(n))

    return enter, leave


def sublayer(X, hp, cfg: Glm5NextConfig, fn):
    """X <- H_res X + H_post (outer) fn(H_pre X); fn returns what the
    sublayer computes from its [..., d] input, and anything else it has
    to hand back.  Returns (X, that).  (A prefill layer hands the halves
    to its mixer and its feed-forward instead, `around=mhc_halves`, which
    compute the second inside their walk of the rows: `layer_prefill`.)"""
    with jax.named_scope("mhc_mix"):
        pre, post, res = mhc_maps(X, hp, cfg)
        x_in = jnp.einsum("...n,...nd->...d", pre, X.astype(F32)
                          ).astype(cfg.dtype)
    y, aux = fn(x_in)
    with jax.named_scope("mhc_mix"):
        X = (jnp.einsum("...mn,...nd->...md", res, X.astype(F32))
             + post[..., None] * y.astype(F32)[..., None, :])
    return X.astype(cfg.dtype), aux


# ------------------------------------------------------------ feed-forward
def routed_ffn(h2, lp, cfg: Glm5NextConfig, live=None):
    """`routed.routed_ffn` for the experts this chip holds, under THIS
    module's `route`."""
    return routed.routed_ffn(h2, lp, cfg, live, cfg.experts_held,
                             route_fn=route)


def ffn(x, lp, lid: int, cfg: Glm5NextConfig, live=None, around=BARE):
    """The feed-forward of layer `lid` from its input x [..., d] (its
    pre-norm inside): (what it computes, the counts of a routed layer or
    None).  Prefill and decode share it.  Whole rows x [b, T, d] (a
    prefill) walk up to the last `live` position (`live_rows.walk`),
    zeros past the walked chunks: the dense layer's as one body, a routed
    layer's shared expert after the routed loop (which walks the live
    rows itself).  `around`: the halves of the residual path
    (`mhc_halves`), computed inside the same bodies; x is then the n
    streams apart and so is the result."""
    enter, leave = around
    if around is not BARE or x.ndim >= 3:
        T = jax.tree.leaves(x)[0].shape[1]
        n_live = T if live is None else live_rows.count(live)
        walk = functools.partial(live_rows.walk, n_live=n_live)
    else:           # a decode step's [B, d]: one token a lane
        def walk(fn, arrays):
            return fn(arrays, None)

    def normed(x):
        x_in, maps = enter(x)
        return rmsnorm(x_in, lp["norm2"], cfg.norm_eps), maps

    if not cfg.is_routed(lid):
        def dense(x, _first):
            h, maps = normed(x)
            with jax.named_scope("mlp"):
                return leave(x, maps, routed.swiglu(
                    h, lp["w1"], lp["w3"], lp["w2"], cfg.dtype,
                    cfg.swiglu_limit))
        return walk(dense, x), None
    h, maps = normed(x)
    y, counts = routed_ffn(h.reshape(-1, cfg.dim), lp, cfg,
                           None if live is None else live.reshape(-1))

    def shared(args, _first):
        x, maps, h, y = args
        return leave(x, maps, y + shared_ffn(h, lp, cfg.dtype,
                                             cfg.swiglu_limit))

    return walk(shared, (x, maps, h, y.reshape(h.shape))), counts


# ---------------------------------------------------------------- KDA mixer
def kda_gate(h, lp, cfg: Glm5NextConfig):
    """The log decay a key channel, in [gate_lower_bound, 0] (assumed
    form), and the write strength a head: (g [..., H, dk], beta [..., H])
    float32.  THIS family's gate: the rest of the mixer is
    `models/kda_layer.py`'s, which takes it as an argument."""
    f = (h @ lp["wf1"]) @ lp["wf2"]
    A = jnp.repeat(jnp.exp(lp["A_log"]), cfg.kda_head_dim)
    g = cfg.gate_lower_bound * jax.nn.sigmoid(
        A * (f.astype(F32) + lp["dt_bias"]))
    beta = jax.nn.sigmoid((h @ lp["w_beta"]).astype(F32))
    return g.reshape(*h.shape[:-1], cfg.n_heads, cfg.kda_head_dim), beta


def kda_inputs(h, lp, cfg: Glm5NextConfig, true_lens):
    """`kda_layer.inputs` under this module's gate."""
    return kda_layer.inputs(h, lp, cfg, true_lens, kda_gate)


def kda_prefill(x, lp, cfg: Glm5NextConfig, true_lens, around=BARE):
    """`kda_layer.prefill` under this module's gate (bounded: the scan's
    bounded form); `around`: the halves of the residual path
    (`mhc_halves`), x then the n streams apart and so the result."""
    return kda_layer.prefill(x, lp, cfg, true_lens, kda_gate, around)


def kda_decode_inputs(x, lp, conv, cfg: Glm5NextConfig):
    """`kda_layer.decode_inputs` under this module's gate."""
    return kda_layer.decode_inputs(x, lp, conv, cfg, kda_gate)


def kda_decode(x, lp, conv, state, layer, lanes, count,
               cfg: Glm5NextConfig):
    """`kda_layer.decode` under this module's gate."""
    return kda_layer.decode(x, lp, conv, state, layer, lanes, count, cfg,
                            kda_gate)


# ------------------------------------------------------ sparse latent mixer
def index_rope(x, positions, cfg: Glm5NextConfig):
    """Rotary embedding of the indexer (assumed: the first
    `index_rope_dim` of the width, INTERLEAVED pairs (2i, 2i + 1), theta
    `index_theta`).  x [..., T, heads, w]; positions [..., T]."""
    rd = cfg.index_rope_dim
    inv = cfg.index_theta ** (-jnp.arange(0, rd, 2, dtype=F32) / rd)
    ang = positions.astype(F32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xr = x[..., :rd].astype(F32).reshape(*x.shape[:-1], rd // 2, 2)
    a, b = xr[..., 0], xr[..., 1]
    rot = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return jnp.concatenate(
        [rot.reshape(*x.shape[:-1], rd).astype(x.dtype), x[..., rd:]], -1)


def dsa_inputs(h, lp, cfg: Glm5NextConfig, positions):
    """h [b, T, d] normed, positions [b, T] -> (q [b, T, H, qk], c [b,
    T, r] the cache rows, q^I [b, T, J, w], k^I [b, T, w], w [b, T, J]
    float32)."""
    b, T, _ = h.shape
    with jax.named_scope("mla_q"):
        cq = rmsnorm(h @ lp["wqa"], lp["q_norm"], cfg.norm_eps)
        q = (cq @ lp["wqb"]).reshape(b, T, cfg.n_heads, cfg.qk_head_dim)
    with jax.named_scope("mla_kv_down"):
        c = rmsnorm(h @ lp["wkva"], lp["kv_norm"], cfg.norm_eps)
    with jax.named_scope("dsa_index"):
        qi = (cq @ lp["wqi"]).reshape(b, T, cfg.index_heads, cfg.index_dim)
        qi = index_rope(qi, positions, cfg)
        ki = layernorm(h @ lp["wki"], lp["ki_norm_w"], lp["ki_norm_b"],
                       cfg.norm_eps)
        ki = index_rope(ki[:, :, None, :], positions, cfg)[:, :, 0]
        w = (h @ lp["ww"]).astype(F32) \
            * (cfg.index_heads * cfg.index_dim) ** -0.5
    return q, c, qi, ki, w


def _masked_attention(q, k, v, masks, scale: float):
    """softmax(scale q k^T + mask) v, a block of heads at a time and
    inside it a block of queries at a time.  q [b, T, H, dq], k [b, T, H,
    dq], v [b, T, H, dv]; masks: for each block of Q_BLOCK queries in
    turn, [b, queries, keys up to the block's end] bool."""
    b, T, H, _ = q.shape
    hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 else H

    def heads(a):           # [b, T, H, w] -> [H / hb, b, T, hb, w]
        return jnp.moveaxis(a.reshape(b, T, H // hb, hb, a.shape[-1]), 2, 0)

    def block(xs):
        # the scores go through memory: once written, once read for the
        # row maximum, once for exp and the row sum; the weights leave in
        # the serving dtype and are normalised AFTER they met the values
        qh, kh, vh = xs
        outs, lo = [], 0
        for mask in masks:
            n, hi = mask.shape[1], mask.shape[2]
            s = jnp.einsum("bthd,bshd->bhts", qh[:, lo:lo + n], kh[:, :hi],
                           preferred_element_type=F32) * scale
            s = jnp.where(mask[:, None], s, dsa.NEG_INF)
            p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            o = jnp.einsum("bhts,bshd->bthd", p.astype(vh.dtype),
                           vh[:, :hi], preferred_element_type=F32)
            norm = jnp.sum(p, axis=-1)                   # [b, h, t]
            outs.append((o / jnp.moveaxis(norm, 1, 2)[..., None]
                         ).astype(q.dtype))
            lo += n
        return jnp.concatenate(outs, axis=1)

    o = lax.map(block, (heads(q), heads(k), heads(v)))
    return jnp.moveaxis(o, 0, 2).reshape(b, T, H, v.shape[-1])


def dsa_prefill(x, lp, cfg: Glm5NextConfig, true_lens,
                want_selection: bool = False, around=BARE):
    """The sparse latent mixer over whole rows x [b, T, d], EXPANDED:
    (what it computes, (latent rows [b, T, 1, r], index rows [b, T / g,
    1, w], the sum of the index keys of each row's incomplete group at
    its TRUE length [b, w] float32)); with `want_selection` a third
    entry, (the rows each query attends [b, T, T], the groups its scores
    chose [b, T, T / g]) (a judge's reading; the engine never asks).
    The output projection walks the rows up to the longest true length
    (`live_rows.walk`): zeros past the walked chunks.  `around`: as
    `kda_prefill`'s."""
    enter, leave = around
    x_in, maps = enter(x)
    b, T, _ = x_in.shape
    g = cfg.index_pool
    h = rmsnorm(x_in, lp["norm1"], cfg.norm_eps)
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (b, T))
    q, c, qi, ki, w = dsa_inputs(h, lp, cfg, positions)
    with jax.named_scope("dsa_index"):
        kbar = dsa.pool_index_keys(ki, g).astype(cfg.dtype)
        at = jnp.arange(T)[None, :]
        part = (at >= (true_lens // g * g)[:, None]) \
            & (at < true_lens[:, None])
        ipart = jnp.sum(jnp.where(part[..., None], ki.astype(F32), 0.0), 1)
    with jax.named_scope("mla_absorb"):
        k = jnp.einsum("bpc,hnc->bphn", c, lp["w_uk"])
        v = jnp.einsum("bpc,hcv->bphv", c, lp["w_uv"])
    masks, picks = [], []
    for lo in range(0, T, Q_BLOCK):     # a block's keys end where it does
        hi = min(lo + Q_BLOCK, T)
        with jax.named_scope("dsa_index"):
            scores = dsa.index_scores(qi[:, lo:hi], w[:, lo:hi],
                                      kbar[:, :hi // g])
        with jax.named_scope("dsa_select"):
            mask, chosen = dsa.selected_mask(
                scores, jnp.arange(lo, hi), hi, g, cfg.index_topk)
        masks.append(mask)
        picks.append(chosen)
    def whole(blocks, width):       # a block's keys padded out to `width`
        return jnp.concatenate([
            jnp.pad(a, ((0, 0), (0, 0), (0, width - a.shape[2])))
            for a in blocks], axis=1)

    with jax.named_scope("dsa_attn"):
        if dsa.prefill_block(T):
            # the kernel walks the block pairs below the diagonal and
            # inside the true lengths; the mask rides as bytes
            o = dsa.masked_prefill_attention(
                q, k.astype(q.dtype), v.astype(q.dtype),
                whole(masks, T).astype(jnp.int8), true_lens,
                sm_scale=cfg.qk_head_dim ** -0.5)
        else:               # a short bucket: XLA, the scores in memory
            o = _masked_attention(q, k, v, masks, cfg.qk_head_dim ** -0.5)

    def after(args, _first):
        x, maps, o = args
        with jax.named_scope("mla_out"):
            return leave(x, maps, o.reshape(*o.shape[:2], -1) @ lp["wo"])

    y = live_rows.walk(after, (x, maps, o), jnp.max(true_lens))
    kept = (c.astype(cfg.dtype)[:, :, None, :], kbar[:, :, None, :], ipart)
    if want_selection:
        kept += ((whole(masks, T), whole(picks, T // g)),)
    return y, kept


def dsa_decode(x, lp, latent_pages, index_pages, latent_tail, index_tail,
               ipart, page_table, pos, tail_start, j, lanes, count,
               cfg: Glm5NextConfig, want_selection: bool = False,
               plan: dict | None = None):
    """One token of the sparse latent mixer for every lane, ABSORBED:
    x [B, d]; the two pool leaves of the layer (read-only), their tails
    (the new latent row lands at column j; a group the token completes
    lands in the index tail), ipart [B, w] the lane's incomplete group's
    sum.  Returns (what it computes, latent tail, index tail, ipart);
    with `want_selection` a fifth entry, (the groups the scores chose
    [B, n], which of them count [B, n], the positions of the rows read
    and of the tail's, whether each is attended): a judge's reading.
    `plan`: the window's `attention_plan`, for the form of
    `dsa.decode_attend` that walks pages (built there if not given)."""
    B = x.shape[0]
    g = cfg.index_pool
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    q, c, qi, ki, w = dsa_inputs(h[:, None], lp, cfg, pos[:, None])
    with jax.named_scope("kv_write"):
        latent_tail = lax.dynamic_update_slice(
            latent_tail, c.astype(cfg.dtype)[:, :, None, :], (0, 0, j, 0))
        acc = ipart + ki[:, 0].astype(F32)
        full = (pos + 1) % g == 0
        row = pos // g - tail_start // g
        put = full[:, None] & (row[:, None]
                               == jnp.arange(index_tail.shape[2])[None, :])
        index_tail = jnp.where(
            put[:, None, :, None],
            (acc / g).astype(cfg.dtype)[:, None, None, :], index_tail)
        ipart = jnp.where(full[:, None], 0.0, acc)
    groups, ok, chosen = dsa.decode_select(
        qi[:, 0], w[:, 0], index_pages, index_tail, page_table, pos,
        tail_start, g, cfg.index_topk)
    with jax.named_scope("mla_absorb"):
        qa = jnp.einsum("bhn,hnc->bhc", q[:, 0], lp["w_uk"]
                        ).astype(cfg.dtype)
    o, rpos, admit = dsa.decode_attend(
        qa, latent_pages, latent_tail, page_table, pos, tail_start, groups,
        ok, chosen, lanes, count, group=g, dv=cfg.kv_lora_rank,
        sm_scale=cfg.qk_head_dim ** -0.5, plan=plan)
    with jax.named_scope("mla_absorb"):
        ov = jnp.einsum("bhc,hcv->bhv", o, lp["w_uv"])
    with jax.named_scope("mla_out"):
        y = ov.reshape(B, -1).astype(cfg.dtype) @ lp["wo"]
    if want_selection:
        return y, latent_tail, index_tail, ipart, (groups, ok, rpos, admit)
    return y, latent_tail, index_tail, ipart


# ---------------------------------------------------------------- prefill
def embed_streams(params, tokens, cfg: Glm5NextConfig):
    """X_0 [..., n, d]: the embedding copied to the n streams."""
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens, cfg.dtype)
        return jnp.broadcast_to(x[..., None, :],
                                x.shape[:-1] + (cfg.hc_mult, cfg.dim))


def final_hidden(params, X, cfg: Glm5NextConfig):
    """h(sum of the streams) (assumed: summed)."""
    x = jnp.sum(X.astype(F32), axis=-2).astype(cfg.dtype)
    return rmsnorm(x, params["final_norm"], cfg.norm_eps)


def layer_prefill(params, X, lid: int, cfg: Glm5NextConfig, true_lens):
    """Layer `lid` over whole rows, X the n streams APART (a tuple of n
    arrays [b, T, d]: a stream's rows are then whole tiles and no
    sublayer writes the streams side by side): (X after it, what its
    mixer hands the pool and the lane, the routed counts or None).  The
    prefill program's body."""
    lp = params["layers"][lid]
    T = X[0].shape[1]
    live = jnp.arange(T)[None, :] < true_lens[:, None]
    mixer = kda_prefill if cfg.layer_types[lid] == KDA else dsa_prefill
    X, kept = mixer(X, lp, cfg, true_lens,
                    around=mhc_halves(lp["hc_mix"], cfg))
    X, cnt = ffn(X, lp, lid, cfg, live, mhc_halves(lp["hc_ffn"], cfg))
    return X, kept, cnt


def prefill(params: dict, tokens: jnp.ndarray, cfg: Glm5NextConfig,
            true_lens: jnp.ndarray | None = None, lora=None):
    """Prompt pass.  tokens [b, T], right-padded; true_lens [b] (absent:
    every row is T long).  Returns the seam's (hidden [b, T, d] after
    the final norm; the latent rows, a sparse layer [b, T, 1, r]; the
    index rows, a sparse layer [b, T / g, 1, w]; state: {"conv", "kda":
    a KDA layer each, "ipart": a sparse layer each}, every row's at its
    TRUE length; counts int32 [routed layers, 4])."""
    b, T = tokens.shape
    if true_lens is None:
        true_lens = jnp.full((b,), T, jnp.int32)
    with jax.named_scope("embed"):      # X_0: the embedding, n times
        X = (embed_lookup(params["embed"], tokens, cfg.dtype),) * cfg.hc_mult
    latent, index, counts = [], [], []
    state = {"conv": [], "kda": [], "ipart": []}
    for lid, kind in enumerate(cfg.layer_types):
        X, kept, cnt = layer_prefill(params, X, lid, cfg, true_lens)
        if kind == KDA:
            state["conv"].append(kept[0])
            state["kda"].append(kept[1])
        else:
            latent.append(kept[0])
            index.append(kept[1])
            state["ipart"].append(kept[2])
        if cnt is not None:
            counts.append(cnt)
    hidden = live_rows.walk(
        lambda xs, _first: final_hidden(params, jnp.stack(xs, axis=-2), cfg),
        X, jnp.max(true_lens))
    return hidden, latent, index, state, routed.stack_counts(counts)


# ------------------------------------------------------------ paged cache
def init_paged_cache(cfg: Glm5NextConfig, batch: int, n_pages: int,
                     page: int) -> dict:
    """TWO pool leaves a sparse layer: the latent rows [n_pages, 1, page,
    r], one a token, and the pooled index keys [n_pages, 1, page / g, w],
    one a GROUP of g positions; and `state` (the module's docstring)."""
    g = cfg.index_pool
    if page % g:
        raise ValueError(f"page {page} is no multiple of index_pool {g}")
    n_dsa, n_kda = cfg.count(DSA), cfg.count(KDA)
    dk = cfg.kda_head_dim
    return {
        "latent": [jnp.zeros((n_pages, 1, page, cfg.kv_lora_rank),
                             cfg.dtype) for _ in range(n_dsa)],
        "index": [jnp.zeros((n_pages, 1, page // g, cfg.index_dim),
                            cfg.dtype) for _ in range(n_dsa)],
        "pos": jnp.zeros((batch,), jnp.int32),
        "state": {
            "conv": jnp.zeros((n_kda, batch, cfg.conv_kernel - 1,
                               3 * cfg.kda_inner), cfg.dtype),
            "kda": jnp.zeros((n_kda, batch, cfg.n_heads, dk, dk),
                             cfg.state_dtype),
            "ipart": jnp.zeros((n_dsa, batch, cfg.index_dim), F32)}}


def scatter_prefill_pages(cache: dict, latent, index, state, page_ids,
                          row_ids, slots, true_lens,
                          aligned: bool = True) -> dict:
    """Write a prefill wave's rows into both pool leaves and each row's
    state into its lane, where the lanes' state lies (the cache is
    donated).  An index row covers g positions: its place is its first
    position's, g times coarser."""
    g = cache["latent"][0].shape[2] // cache["index"][0].shape[2]
    with jax.named_scope("kv_write"):
        out = {
            "latent": [scatter_rows(p, new, page_ids, row_ids, aligned)
                       for p, new in zip(cache["latent"], latent)],
            "index": [scatter_rows(p, new, page_ids[:, ::g],
                                   row_ids[:, ::g] // g, aligned)
                      for p, new in zip(cache["index"], index)],
            "pos": cache["pos"].at[slots].set(true_lens)}
    with jax.named_scope("state_write"):
        out["state"] = {
            name: lanes.at[:, slots].set(jnp.stack(state[name]).astype(
                lanes.dtype))
            for name, lanes in cache["state"].items()}
    return out


# ----------------------------------------------------------------- decode
def decode_step_paged(params: dict, pages: dict, tails: dict, state: dict,
                      tokens: jnp.ndarray, pos: jnp.ndarray,
                      tail_start: jnp.ndarray, j, page_table: jnp.ndarray,
                      cfg: Glm5NextConfig, lora=None, plan=None):
    """One decode step over both pool leaves, their in-block tails and
    the lanes' state.  A lane whose table row starts at the trash page
    holds no request: it attends nothing, is routed nowhere and its
    state matrices are not touched.  `plan` (the paged kernels' work
    list of pages) is the sparse layers' where their attention walks
    pages (`dsa.walks`).  Returns (logits
    [B, vocab] float32, tails, state, counts int32 [routed layers, 4])."""
    live = lanes_live(page_table)
    lanes, count = ssm.live_lanes(live)
    X = embed_streams(params, tokens, cfg)                    # [B, n, d]
    conv, kda_state, ipart = state["conv"], state["kda"], state["ipart"]
    latent_t, index_t = list(tails["latent"]), list(tails["index"])
    counts = []
    for lid, kind in enumerate(cfg.layer_types):
        lp = params["layers"][lid]
        i = cfg.before(lid)
        if kind == KDA:
            def mixer(x, lp=lp, i=i):
                y, rows, st = kda_decode(x, lp, conv[i], kda_state,
                                         jnp.int32(i), lanes, count, cfg)
                return y, (rows, st)

            X, (rows, kda_state) = sublayer(X, lp["hc_mix"], cfg, mixer)
            conv = conv.at[i].set(rows)
        else:
            def mixer(x, lp=lp, i=i):
                y, lt, it, ip = dsa_decode(
                    x, lp, pages["latent"][i], pages["index"][i],
                    latent_t[i], index_t[i], ipart[i], page_table, pos,
                    tail_start, j, lanes, count, cfg, plan=plan)
                return y, (lt, it, ip)

            X, (latent_t[i], index_t[i], ip) = sublayer(
                X, lp["hc_mix"], cfg, mixer)
            ipart = ipart.at[i].set(jnp.where(live[:, None], ip, ipart[i]))
        X, cnt = sublayer(X, lp["hc_ffn"], cfg,
                          lambda x, lp=lp, lid=lid: ffn(x, lp, lid, cfg,
                                                        live))
        if cnt is not None:
            counts.append(cnt)
    logits = project_logits(params, final_hidden(params, X, cfg)
                            ).astype(F32)
    return (logits, {"latent": latent_t, "index": index_t},
            {"conv": conv, "kda": kda_state, "ipart": ipart},
            routed.stack_counts(counts))


# the serving seam's names (models/serving.py)
serve_prefill = prefill
serve_scatter = scatter_prefill_pages
serve_decode_step = decode_step_paged
