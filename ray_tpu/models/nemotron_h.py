"""A hybrid decoder whose layers are a mixer OR a feed-forward part
alone (`model_type` `nemotron_h` with `moe_latent_size`, e.g.
NVIDIA-Nemotron-3-Super-120B-A12B): Mamba-2 state-space layers in
several groups, routed relu**2 experts that work in a latent narrower
than the stream, and a few GQA attention layers without position
embedding, served.  This module gives the serving seam
(`ray_tpu.models.serving_model`) what `serve/llm.LLMEngine` runs.  It has
none of the optional capabilities (`serving_spec`'s `caps` is empty): a
lane carries a state matrix a head a state-space layer that no KV page
holds, so a radix prefix hit cannot restore it.

The equations (transformers' `modeling_nemotron_h.py`, torch path, from
the model's `config.json`).  `x_0 = Embed[t]`; layer l is ONE residual
branch, its kind the l-th letter of `hybrid_override_pattern`:

    x = x + Mixer_l(RMSNorm(x; norm_l))

then `logits = W_head RMSNorm(x; final_norm)` (the head is untied).

- `M`, Mamba-2, u the normed input, per token t:
  1. [z, xBC, dt] = W_in u, split inner / inner + 2 G N / heads;
  2. xBC_t = silu(conv_b + sum_i conv_w[i] * xBC_{t-(K-1)+i}), i < K =
     `conv_kernel`: depthwise, causal, zeros before the sequence.  The
     lane keeps the last K-1 PRE-convolution rows of xBC;
  3. xBC splits into x (heads x head_dim), B, C (G groups x N each; head
     h reads group h // (heads / G));
  4. dt_t = softplus(dt_t + dt_bias), A = -exp(A_log), float32, a
     scalar a head;
  5. h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t;
     y_t = h_t C_t + D x_t (`ops/ssm.py`: `ssd_scan` over a prompt,
     `ssm_update` in a decode step);
  6. y = RMSNorm(y * silu(z); gate_norm) taken over each GROUP's inner /
     G columns, not over the row (`gated_group_norm`), then W_out y.
- `*`, attention: q, k, v without bias, NO rotary embedding, causal
  softmax of q k^T * head_dim**-0.5, each kv head serving n_heads /
  n_kv_heads query heads; W_o.
- `E`, the latent routed layer: s = sigmoid(W_g u) over ALL `n_experts`
  in float32; the `top_k` largest of s + bias are selected; w_e =
  `routed_scaling` * s_e / sum over the selected; c = W_fc1 u (dim ->
  `moe_latent`, no activation); r = sum over the selected experts THIS
  CHIP HOLDS (`experts_held`) of w_e W2_e relu(W1_e c)**2; the layer adds
  W_fc2 r + W_s2 relu(W_s1 u)**2 (the shared expert, on the layer's input
  at full width).  W_fc2 is linear and has no bias, so the parts of
  disjoint expert ranges still add up after it (`models/routed.py`:
  the router reads u while the experts multiply c, `relu2_experts`).

**Layers are a list** (`params["layers"][l]`, a dict of the layer's kind)
and every program unrolls them: runs of one kind are one layer long
almost everywhere (`MEMEMEM*EME`), so a scan over a run has nothing to
scan, and the served cut is one period.

**Lane state** (`init_paged_cache()["state"]`): `{"conv": [M layers,
lanes, K-1, inner + 2 G N] in the serving dtype; "ssm": [M layers, lanes,
N, inner] in `state_dtype` (float32: a recurrence rounded to bfloat16
every step compounds over hundreds of steps)}`, ONE array each, beside a
K and a V pool leaf an attention layer.  The decode step hands the SSM
array to `ssm_update`, which updates the live lanes' blocks where they
lie, a group of columns a step; a lane that holds no request keeps both
bit for bit.

Device-side names: `ssm_in_proj`, `ssm_conv`, `ssd_scan` (prefill) /
`ssm_update` (the decode kernel), `ssm_gate_norm`, `ssm_out`,
`moe_router`, `moe_latent_down`, `moe_experts` (the grouped matmul's
kernel is `moe_gmm`), `moe_latent_up`, `shared_expert`, `attn_qkv`,
`attn`, `attn_out`, `lm_head`, beside `embed`, `norm`, `kv_write`,
`state_write`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import llama, routed
from ray_tpu.models.llama import attention, embed_lookup, rmsnorm
from ray_tpu.models.routed import route
from ray_tpu.models.serving import ServingSpec, merged
from ray_tpu.models.ssm_hybrid import _conv    # the same convolution
from ray_tpu.ops import ssm

MAMBA, MOE, ATTN = "M", "E", "*"
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    dim: int = 4096
    pattern: str = (                # `hybrid_override_pattern`
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    ssm_heads: int = 128            # `mamba_num_heads`
    ssm_head_dim: int = 64          # `mamba_head_dim`
    ssm_groups: int = 8             # `n_groups`
    ssm_state: int = 128            # `ssm_state_size`
    conv_kernel: int = 4
    ssm_chunk: int = 128            # `chunk_size`
    moe_latent: int = 1024          # `moe_latent_size`
    moe_ffn_dim: int = 2688         # one expert's (`moe_intermediate_size`)
    shared_ffn_dim: int = 5376      # `moe_shared_expert_intermediate_size`
    n_experts: int = 512            # the ROUTER's width
    experts_held: tuple = (0, 512)  # the range of them this chip holds
    top_k: int = 22
    use_expert_bias: bool = True    # `e_score_correction_bias`
    norm_topk_prob: bool = True
    routed_scaling: float = 5.0
    norm_eps: float = 1e-5
    max_seq: int = 262144
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.ssm_groups * self.ssm_state

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    def before(self, lid: int) -> int:
        """How many layers of layer `lid`'s kind come before it."""
        return self.pattern[:lid].count(self.pattern[lid])


def serving_configs() -> dict[str, NemotronHConfig]:
    return {
        "nemotron-3-super-120b-a12b": NemotronHConfig(),
        "nemotron-h-debug": NemotronHConfig(
            vocab_size=256, dim=64, pattern="MEM*E", n_heads=4,
            n_kv_heads=2, head_dim=16, ssm_heads=4, ssm_head_dim=16,
            ssm_groups=2, ssm_state=16, ssm_chunk=8, moe_latent=32,
            moe_ffn_dim=48, shared_ffn_dim=96, n_experts=8,
            experts_held=(0, 8), top_k=3, max_seq=128),
    }


def prefill_params(cfg: NemotronHConfig) -> tuple[int, int]:
    """Matmul parameters a prefill program STREAMS whatever it holds and
    those ONE position multiplies (`routed.prefill_params`): of a
    position's `top_k` experts this chip multiplies the share it holds;
    an expert is two matrices of `moe_latent` x `moe_ffn_dim`."""
    d, qd = cfg.dim, cfg.n_heads * cfg.head_dim
    mamba = d * (cfg.inner + cfg.conv_dim + cfg.ssm_heads) + cfg.inner * d
    attn = 2 * d * qd + 2 * d * cfg.n_kv_heads * cfg.head_dim
    moe = 2 * d * cfg.moe_latent + 2 * d * cfg.shared_ffn_dim
    rest = (cfg.count(MAMBA) * mamba + cfg.count(ATTN) * attn
            + cfg.count(MOE) * moe)
    return routed.prefill_params(
        cfg, rest, cfg.count(MOE), cfg.experts_held,
        one=2 * cfg.moe_latent * cfg.moe_ffn_dim)


def serving_spec(cfg: NemotronHConfig) -> ServingSpec:
    """No optional capability.  The state-space layers keep a state
    matrix, which `ssd_scan` fills a prefill and `ssm_update` updates a
    decode step, and a convolution's last rows: the bytes of both that
    ONE prefill row hands the scatter program."""
    from ray_tpu.ops.flash_attention import PREFILL_COUNTERS, prefill_work

    n = cfg.count(MAMBA)
    per_layer = (cfg.ssm_state * cfg.inner
                 * jnp.dtype(cfg.state_dtype).itemsize
                 + (cfg.conv_kernel - 1) * cfg.conv_dim
                 * jnp.dtype(cfg.dtype).itemsize)
    return ServingSpec(
        lane_state_layers=n, prefill_state_bytes=n * per_layer,
        prefill_params=prefill_params(cfg), routed_layers=cfg.count(MOE),
        counters={**PREFILL_COUNTERS, **ssm.SCAN_COUNTERS,
                  **routed.COUNTERS},
        decode_work=lambda rows, k, *_table: ssm.update_work(
            n, len(rows), k),
        prefill_work=lambda true_lens, bucket: merged(
            prefill_work(true_lens, bucket),
            ssm.scan_work(n, cfg.ssm_chunk, true_lens, bucket)),
        routed_work=functools.partial(routed.routed_work, cfg,
                                      cfg.experts_held))


# ---------------------------------------------------------------- params
def init_params(key: jax.Array, cfg: NemotronHConfig,
                expert_bias_std: float = 0.02) -> dict:
    """Every weight from one key.  Matrices normal, fan-in scaled, in
    the serving dtype; norm weights 1; the experts of `experts_held`
    only; `expert_bias` N(0, expert_bias_std) over all `n_experts`
    (`mla_moe.init_params` says why); and the recurrence in its
    published regime: A_log = log(U(1, 16)), dt_bias the inverse
    softplus of a log-uniform dt in [0.001, 0.1] (`time_step_min`,
    `time_step_max`), D = 1 (float32, as the kernel takes them)."""
    d, inner, C, H = cfg.dim, cfg.inner, cfg.conv_dim, cfg.ssm_heads
    K, r, f, fs = (cfg.conv_kernel, cfg.moe_latent, cfg.moe_ffn_dim,
                   cfg.shared_ffn_dim)
    qd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    G = cfg.experts_held[1] - cfg.experts_held[0]
    keys = iter(jax.random.split(key, 2 + 8 * cfg.n_layers))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(cfg.dtype)

    def mamba():
        dt = jnp.exp(jax.random.uniform(
            next(keys), (H,), F32, jnp.log(0.001), jnp.log(0.1)))
        return {"in_proj": w((d, inner + C + H), d),
                "conv_w": w((K, C), K), "conv_b": jnp.zeros((C,), cfg.dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(next(keys), (H,), F32,
                                                    1.0, 16.0)),
                "D": jnp.ones((H,), F32),
                "gate_norm": jnp.ones((inner,), cfg.dtype),
                "out_proj": w((inner, d), inner)}

    def attn():
        return {"wq": w((d, qd), d), "wk": w((d, kvd), d),
                "wv": w((d, kvd), d), "wo": w((qd, d), qd)}

    def moe():
        return {"router": w((d, cfg.n_experts), d),
                "expert_bias": expert_bias_std * jax.random.normal(
                    next(keys), (cfg.n_experts,), F32),
                "fc1": w((d, r), d), "w1": w((G, r, f), r),
                "w2": w((G, f, r), f), "fc2": w((r, d), r),
                "sw1": w((d, fs), d), "sw2": w((fs, d), fs)}

    make = {MAMBA: mamba, ATTN: attn, MOE: moe}
    layers = [dict(make[kind](), norm=jnp.ones((d,), cfg.dtype))
              for kind in cfg.pattern]
    return {"embed": w((cfg.vocab_size, d), d), "layers": layers,
            "final_norm": jnp.ones((d,), cfg.dtype),
            "lm_head": w((d, cfg.vocab_size), d)}


def project_logits(params: dict, h: jnp.ndarray) -> jnp.ndarray:
    """The head (untied)."""
    with jax.named_scope("lm_head"):
        return h @ params["lm_head"]


# ------------------------------------------------------- the Mamba mixer
def _in_proj(h, lp, cfg: NemotronHConfig):
    """Step 1: (z, xBC, dt); the three widths are whole lane tiles."""
    with jax.named_scope("ssm_in_proj"):
        zxd = h @ lp["in_proj"]
        at = cfg.inner + cfg.conv_dim
        return zxd[..., :cfg.inner], zxd[..., cfg.inner:at], zxd[..., at:]


def _split(act, cfg: NemotronHConfig):
    """Step 3: activated xBC [..., conv_dim] -> (x [..., inner], B, C
    [..., G, N])."""
    G, N = cfg.ssm_groups, cfg.ssm_state
    B = act[..., cfg.inner:cfg.inner + G * N]
    C = act[..., cfg.inner + G * N:]
    return (act[..., :cfg.inner], B.reshape(*B.shape[:-1], G, N),
            C.reshape(*C.shape[:-1], G, N))


def gated_group_norm(y, z, weight, cfg: NemotronHConfig):
    """RMSNormGated with `group_size` = inner / G: y * silu(z), then an
    RMSNorm over each GROUP's columns, then the weight [inner]; y
    float32 [..., inner]."""
    g = y * jax.nn.silu(z.astype(F32))
    shape = g.shape
    g = g.reshape(*shape[:-1], cfg.ssm_groups, -1)
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                      + cfg.norm_eps)
    return (g.reshape(shape) * weight.astype(F32)).astype(cfg.dtype)


def _gate_out(y, z, lp, cfg: NemotronHConfig):
    """Step 6: W_out of the gated norm by group; y float32 [..., inner]."""
    with jax.named_scope("ssm_gate_norm"):
        g = gated_group_norm(y, z, lp["gate_norm"], cfg)
    with jax.named_scope("ssm_out"):
        return g @ lp["out_proj"]


def scan_inputs(h, lp, cfg: NemotronHConfig, true_lens):
    """Steps 1-4 over whole rows h [b, T, d] (normed): (z, x [b, T, H,
    P], dt [b, T, H] float32, ZERO past each row's true length, B, C
    [b, T, G, N], conv rows [b, K-1, conv_dim]: the pre-convolution xBC
    rows before each row's TRUE length, zeros where it is shorter)."""
    b, T, _ = h.shape
    K = cfg.conv_kernel
    z, xbc, dt = _in_proj(h, lp, cfg)
    with jax.named_scope("ssm_conv"):
        xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        act = _conv([xp[:, i:i + T] for i in range(K)], lp, cfg)
        # rows true_len-(K-1) .. true_len-1 of xbc are xp rows
        # true_len .. true_len+K-2
        at = true_lens[:, None] + jnp.arange(K - 1)[None, :]
        rows = jnp.take_along_axis(xp, at[..., None], axis=1)
    x, Bm, Cm = _split(act, cfg)
    dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"])
    dt = jnp.where(jnp.arange(T)[None, :, None] < true_lens[:, None, None],
                   dt, 0.0)
    return (z, x.reshape(b, T, cfg.ssm_heads, cfg.ssm_head_dim), dt, Bm, Cm,
            rows)


def scan_state(xs, dt, Bm, Cm, lp, cfg: NemotronHConfig):
    """Step 5 over whole rows, from what `scan_inputs` gives: (y [b, T,
    H, P] float32, D x added; the state after each row's last position
    with dt > 0, [b, N, inner] in `state_dtype`: what a lane is handed)."""
    y, state = ssm.ssd_scan(xs, dt, -jnp.exp(lp["A_log"]), Bm, Cm,
                            cfg.ssm_chunk)
    return (y + lp["D"][:, None] * xs.astype(F32),
            state.astype(cfg.state_dtype))


def mamba_prefill(h, lp, cfg: NemotronHConfig, true_lens):
    """The Mamba mixer over whole normed rows h [b, T, d]: what it ADDS
    to the stream, and the lane's state at each row's TRUE length: (d,
    (conv rows [b, K-1, conv_dim], ssm [b, N, inner] in `state_dtype`))."""
    b, T, _ = h.shape
    z, xs, dt, Bm, Cm, rows = scan_inputs(h, lp, cfg, true_lens)
    y, state = scan_state(xs, dt, Bm, Cm, lp, cfg)
    return _gate_out(y.reshape(b, T, cfg.inner), z, lp, cfg), (rows, state)


def decode_inputs(h, lp, conv, cfg: NemotronHConfig):
    """Steps 1-3 for ONE token a lane: h [B, d] normed, conv [B, K-1,
    conv_dim] (the lane's last pre-convolution rows).  Returns (z, x [B,
    inner], dt [B, H] float32 with dt_bias added and BEFORE the
    softplus, which is the kernel's, B, C [B, G, N], conv shifted by the
    token's row)."""
    z, xbc, dt = _in_proj(h, lp, cfg)
    with jax.named_scope("ssm_conv"):
        act = _conv([conv[:, i] for i in range(conv.shape[1])] + [xbc],
                    lp, cfg)
        conv = jnp.concatenate([conv[:, 1:], xbc[:, None]], axis=1)
    x, Bv, Cv = _split(act, cfg)
    return z, x, dt.astype(F32) + lp["dt_bias"], Bv, Cv, conv


def mamba_decode(h, lp, conv, ssm_state, layer, lanes, count,
                 cfg: NemotronHConfig):
    """One token of the Mamba mixer for every lane: h [B, d] normed,
    conv [B, K-1, conv_dim], ssm_state the lanes' state of EVERY Mamba
    layer (updated in place at `layer` for the listed lanes).  Returns
    (what the mixer adds, conv shifted, ssm_state)."""
    P = cfg.ssm_head_dim
    z, xs, dt, Bv, Cv, conv = decode_inputs(h, lp, conv, cfg)
    ssm_state, y = ssm.ssm_update(
        ssm_state, layer, lanes, count, xs, jnp.repeat(dt, P, axis=-1),
        Bv, Cv, jnp.repeat(lp["A_log"], P), jnp.repeat(lp["D"], P))
    return _gate_out(y, z, lp, cfg), conv, ssm_state


# ---------------------------------------------------------- the attention
def softmax_scale(cfg: NemotronHConfig) -> float:
    return cfg.head_dim ** -0.5


def attn_prefill(h, lp, cfg: NemotronHConfig, true_lens):
    """The attention mixer over whole normed rows: (what it ADDS to the
    stream, (k, v [b, T, kvh, hd]))."""
    b, T, _ = h.shape
    with jax.named_scope("attn_qkv"):
        q = (h @ lp["wq"]).reshape(b, T, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["wk"]).reshape(b, T, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"]).reshape(b, T, cfg.n_kv_heads, cfg.head_dim)
    o = attention(q, k, v, causal=True, lengths=true_lens,
                  sm_scale=softmax_scale(cfg))
    with jax.named_scope("attn_out"):
        d = o.reshape(b, T, -1) @ lp["wo"]
    return d, (k.astype(cfg.dtype), v.astype(cfg.dtype))


# -------------------------------------------------- the latent routed layer
def routed_ffn(c, h2, lp, cfg: NemotronHConfig, live=None):
    """`routed.routed_ffn` over the latent rows c [T, moe_latent] for the
    experts this chip holds, the router reading h2 [T, dim], under THIS
    module's `route` (looked up at the call, so a test's control can
    stand in for it)."""
    return routed.routed_ffn(c, lp, cfg, live, cfg.experts_held,
                             route_fn=route, router_rows=h2,
                             expert_fn=routed.relu2_experts)


def moe(h, lp, cfg: NemotronHConfig, live=None):
    """The `E` layer for normed rows h [..., d]: (what it ADDS to the
    stream, the routed counts).  Prefill and decode share it."""
    h2 = h.reshape(-1, cfg.dim)
    with jax.named_scope("moe_latent_down"):
        c = h2 @ lp["fc1"]
    r, counts = routed_ffn(c, h2, lp, cfg,
                           None if live is None else live.reshape(-1))
    with jax.named_scope("moe_latent_up"):
        y = r @ lp["fc2"]
    with jax.named_scope("shared_expert"):
        y = y + routed.relu2(h2 @ lp["sw1"]) @ lp["sw2"]
    return y.reshape(h.shape), counts


# ---------------------------------------------------------------- prefill
def layer_prefill(params, x, lid: int, cfg: NemotronHConfig, true_lens):
    """Layer `lid` over whole rows x [b, T, d]: (x after the layer, what
    the mixer hands the lane or the pool (None for an `E` layer), the
    routed counts or None).  The prefill program's body; the benchmark's
    judge calls it a layer at a time."""
    lp, kind = params["layers"][lid], cfg.pattern[lid]
    h = rmsnorm(x, lp["norm"], cfg.norm_eps)
    kept = cnt = None
    if kind == MAMBA:
        d, kept = mamba_prefill(h, lp, cfg, true_lens)
    elif kind == ATTN:
        d, kept = attn_prefill(h, lp, cfg, true_lens)
    else:
        live = jnp.arange(x.shape[1])[None, :] < true_lens[:, None]
        d, cnt = moe(h, lp, cfg, live)
    return x + d.astype(x.dtype), kept, cnt


def prefill(params: dict, tokens: jnp.ndarray, cfg: NemotronHConfig,
            true_lens: jnp.ndarray | None = None, lora=None):
    """Prompt pass.  tokens [b, T], right-padded; true_lens [b] (absent:
    every row is T long); `lora` is the seam's slot for adapters, which
    this model has not (None).  Returns (hidden [b, T, d] after the
    final norm; ks, vs: per ATTENTION layer [b, T, kvh, hd]; state:
    {"conv": per Mamba layer [b, K-1, conv_dim], "ssm": per Mamba layer
    [b, N, inner]}, each row's at its TRUE length; counts int32 [routed
    layers, routed.COUNTS])."""
    b, T = tokens.shape
    if true_lens is None:
        true_lens = jnp.full((b,), T, jnp.int32)
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    ks, vs, conv, states, counts = [], [], [], [], []
    for lid, kind in enumerate(cfg.pattern):
        x, kept, cnt = layer_prefill(params, x, lid, cfg, true_lens)
        if kind == MAMBA:
            conv.append(kept[0])
            states.append(kept[1])
        elif kind == ATTN:
            ks.append(kept[0])
            vs.append(kept[1])
        else:
            counts.append(cnt)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return (x, ks, vs, {"conv": conv, "ssm": states},
            routed.stack_counts(counts))


# ------------------------------------------------------------ paged cache
def init_paged_cache(cfg: NemotronHConfig, batch: int, n_pages: int,
                     page: int) -> dict:
    """The page pool of `llama.init_paged_kv_cache` with leaves for the
    ATTENTION layers only, and `state`: the lanes' convolution rows and
    their state matrices, ONE array each over the Mamba layers;
    `ssm_update` updates the second in place."""
    shape = (n_pages, cfg.n_kv_heads, page, cfg.head_dim)
    n_attn, n_mamba = cfg.count(ATTN), cfg.count(MAMBA)
    return {"k": [jnp.zeros(shape, cfg.dtype) for _ in range(n_attn)],
            "v": [jnp.zeros(shape, cfg.dtype) for _ in range(n_attn)],
            "pos": jnp.zeros((batch,), jnp.int32),
            "state": {
                "conv": jnp.zeros((n_mamba, batch, cfg.conv_kernel - 1,
                                   cfg.conv_dim), cfg.dtype),
                "ssm": jnp.zeros((n_mamba, batch, cfg.ssm_state,
                                  cfg.inner), cfg.state_dtype)}}


def scatter_prefill_pages(cache: dict, ks, vs, state, page_ids, rows,
                          slots, true_lens, aligned: bool = True) -> dict:
    """Write a prefill wave's K/V into the page pool (llama's scatter)
    and each row's state into its lane, where the lanes' state lies (the
    cache is donated; duplicate padding rows write one lane the same
    values).  `state` as `prefill` returns it: an array a Mamba layer."""
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
def decode_step_paged(params: dict, pages: dict, tails: dict, state: dict,
                      tokens: jnp.ndarray, pos: jnp.ndarray,
                      tail_start: jnp.ndarray, j, page_table: jnp.ndarray,
                      cfg: NemotronHConfig, lora=None, plan=None):
    """One decode step over the paged cache, the in-block tail (see
    llama.decode_step_paged) and the lanes' state.  A lane whose table
    row starts at the trash page holds no request: it attends nothing,
    is routed nowhere, and neither its state matrices nor its
    convolution rows are touched.  Returns (logits [B, vocab] float32,
    tails, state, counts int32 [routed layers, routed.COUNTS])."""
    from ray_tpu.ops.paged_attention import (lanes_live,
                                             paged_decode_attention)

    B = tokens.shape[0]
    hd, n_rep = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    live = lanes_live(page_table)
    lanes, count = ssm.live_lanes(live)
    x = embed_lookup(params["embed"], tokens[:, None], cfg.dtype)[:, 0]
    conv, ssm_state = state["conv"], state["ssm"]
    new_tk, new_tv, counts = [], [], []
    for lid, kind in enumerate(cfg.pattern):
        lp, i = params["layers"][lid], cfg.before(lid)
        h = rmsnorm(x, lp["norm"], cfg.norm_eps)
        if kind == MAMBA:
            d, rows, ssm_state = mamba_decode(
                h, lp, conv[i], ssm_state, jnp.int32(i), lanes, count, cfg)
            conv = conv.at[i].set(
                jnp.where(live[:, None, None], rows, conv[i]))
        elif kind == MOE:
            d, cnt = moe(h, lp, cfg, live)
            counts.append(cnt)
        else:
            with jax.named_scope("attn_qkv"):
                # the products held flat, or wq / wk / wv are
                # re-laid-out every step (llama._decode_qkv)
                q, k, v = llama._decode_qkv(h[:, None], lp, cfg)
                q = q.reshape(B, cfg.n_kv_heads, n_rep, hd)
                k = k.reshape(B, cfg.n_kv_heads, 1, hd)
                v = v.reshape(B, cfg.n_kv_heads, 1, hd)
            with jax.named_scope("kv_write"):
                tk = lax.dynamic_update_slice(
                    tails["k"][i], k.astype(cfg.dtype), (0, 0, j, 0))
                tv = lax.dynamic_update_slice(
                    tails["v"][i], v.astype(cfg.dtype), (0, 0, j, 0))
            with jax.named_scope("attn"):
                o = paged_decode_attention(
                    q.astype(cfg.dtype), pages["k"][i], pages["v"][i],
                    tk, tv, page_table, pos, tail_start, plan=plan,
                    sm_scale=softmax_scale(cfg))
            new_tk.append(tk)
            new_tv.append(tv)
            with jax.named_scope("attn_out"):
                d = o.reshape(B, cfg.n_heads * hd) @ lp["wo"]
        x = x + d.astype(x.dtype)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = project_logits(params, x).astype(F32)
    return (logits, {"k": new_tk, "v": new_tv},
            {"conv": conv, "ssm": ssm_state}, routed.stack_counts(counts))


# the serving seam's names (models/serving.py)
serve_prefill = prefill
serve_scatter = scatter_prefill_pages
serve_decode_step = decode_step_paged
