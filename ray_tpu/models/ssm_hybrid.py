"""A hybrid decoder of state-space (Mamba-2) layers beside a few
attention layers without position embedding (`model_type`
`granitemoehybrid` with no routed layer, e.g. granite-4.0-h-micro),
served.  This module gives the serving seam
(`ray_tpu.models.serving_model`) what `serve/llm.LLMEngine` runs.  It
has none of the optional capabilities (`serving_spec`'s `caps` is
empty): a lane
carries a state matrix a head a layer that no KV page holds, so a radix
prefix hit cannot restore it.

The equations (transformers' `modeling_granitemoehybrid.py`, torch path,
from the model's `config.json`).  `x_0 = Embed[t] * embed_scale`; for
layer l, with r = `residual_scale`,

    x = x + r * Mixer_l(RMSNorm(x; norm1_l))
    x = x + r * W_2(silu(a) * b),  [a, b] = W_13 RMSNorm(x; norm2_l)

then `logits = RMSNorm(x; final_norm) Embed^T / logits_scale` (the head
is the embedding, tied).

- Mixer = attention where `layer_types[l] == "attention"`: q, k, v
  without bias, NO rotary embedding, causal softmax of q k^T *
  `attn_scale` (a given number, not head_dim**-0.5), each kv head
  serving n_heads / n_kv_heads query heads; W_o.
- Mixer = Mamba-2 elsewhere, u the normed input, per token t:
  1. [z, xBC, dt] = W_in u, split inner / inner + 2 N / heads (W_in is
     held as `in_zx` and `in_dt`: `_in_proj`);
  2. xBC_t = silu(conv_b + sum_i conv_w[i] * xBC_{t-(K-1)+i}), i < K =
     `conv_kernel`: depthwise, causal, zeros before the sequence.  The
     lane keeps the last K-1 PRE-convolution rows of xBC;
  3. xBC splits into x (heads x head_dim), B, C (N each: one group);
  4. dt_t = softplus(dt_t + dt_bias), A = -exp(A_log), float32, a
     scalar a head;
  5. h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t;
     y_t = h_t C_t + D x_t (`ops/ssm.py`: `ssd_scan` over a prompt,
     `ssm_update` in a decode step);
  6. y = RMSNorm(y * silu(z); gate_norm) (the gate BEFORE the norm),
     then W_out y.

**Layers are stacked by KIND** (`params["mamba"]`, `params["attn"]`:
a dict of `[layers of the kind, ...]` arrays each, both with the layer's
norms and SwiGLU), and every program is built from the RUNS of
`layer_types`, stretches of consecutive layers of one kind ((5 x mamba,
attention, 4 x mamba) x 4 is nine runs: 5, 1, 9, 1, 9, 1, 9, 1, 4): it
scans a Mamba run with one layer as the body (the layer's weights picked
by a traced index) and unrolls the attention layers between, so it is
built from a body a run, not from 40.  Not a scan over the published
period of ten: the page pool is a LIST of leaves, one an attention
layer, which the engine owns (its tails and merges follow it), and a
traced period cannot pick a list's element; and in the prefill program a
scan over periods around the runs' scans either stacks the layers' state
twice or copies the carried 1.2 GB (sandbox compile: 2.1 / 3.4 GB of
temporaries against 1.3) and compiles no faster (10.5 s against 11.6).

**Lane state** (`init_paged_cache()["state"]`): `{"conv": [Mamba
layers, lanes, K-1, inner + 2 N] in the serving dtype; "ssm": [Mamba
layers, lanes, N, heads * head_dim] in `state_dtype` (float32: a
recurrence rounded to bfloat16 every step compounds over hundreds of
steps)}`, ONE array each.  The decode step hands the SSM array to
`ssm_update`, which updates the live lanes' blocks where they lie; a
layer's convolution rows (26 KB a lane) are shifted and written back by
a `dynamic_update_slice` on the scan's carry.

Device-side names: `ssm_in_proj`, `ssm_conv`, `ssd_scan` (prefill) /
`ssm_update` (the decode kernel), `ssm_gate_norm`, `ssm_out`,
`state_write`, beside `embed`, `attn_qkv`, `attn`, `attn_out`, `mlp`,
`lm_head`, `kv_write`.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import llama
from ray_tpu.models.llama import attention, embed_lookup, rmsnorm
from ray_tpu.models.serving import ServingSpec, merged
from ray_tpu.ops import ssm

ATTN = "attention"
MAMBA = "mamba"
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SsmHybridConfig:
    vocab_size: int = 100352
    dim: int = 2048
    layer_types: tuple = ((MAMBA,) * 5 + (ATTN,) + (MAMBA,) * 4) * 4
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    ffn_dim: int = 8192             # `shared_intermediate_size`
    ssm_heads: int = 64             # `mamba_n_heads`
    ssm_head_dim: int = 64          # `mamba_d_head`
    ssm_state: int = 128            # `mamba_d_state`
    conv_kernel: int = 4            # `mamba_d_conv`
    ssm_chunk: int = 256            # `mamba_chunk_size`
    norm_eps: float = 1e-5
    attn_scale: float = 0.015625    # `attention_multiplier`
    embed_scale: float = 12.0       # `embedding_multiplier`
    residual_scale: float = 0.22    # `residual_multiplier`
    logits_scale: float = 8.0       # `logits_scaling` (a divisor)
    max_seq: int = 131072
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.ssm_state

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def runs(self) -> list[tuple[str, int, int]]:
        """(kind, layers, how many layers of that kind came before) for
        each stretch of consecutive layers of one kind."""
        out, seen = [], {ATTN: 0, MAMBA: 0}
        for kind in self.layer_types:
            if out and out[-1][0] == kind:
                out[-1][1] += 1
            else:
                out.append([kind, 1, seen[kind]])
            seen[kind] += 1
        return [tuple(r) for r in out]


def serving_configs() -> dict[str, SsmHybridConfig]:
    return {
        "granite-4.0-h-micro": SsmHybridConfig(),
        "ssm-hybrid-debug": SsmHybridConfig(
            vocab_size=256, dim=64,
            layer_types=(MAMBA, MAMBA, ATTN, MAMBA) * 2, n_heads=4,
            n_kv_heads=2, head_dim=16, ffn_dim=128, ssm_heads=4,
            ssm_head_dim=16, ssm_state=16, ssm_chunk=8, max_seq=128),
    }


def serving_spec(cfg: SsmHybridConfig) -> ServingSpec:
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
        counters={**PREFILL_COUNTERS, **ssm.SCAN_COUNTERS},
        decode_work=lambda rows, k, *_table: ssm.update_work(
            n, len(rows), k),
        prefill_work=lambda true_lens, bucket: merged(
            prefill_work(true_lens, bucket),
            ssm.scan_work(n, cfg.ssm_chunk, true_lens, bucket)))


# ---------------------------------------------------------------- params
def init_params(key: jax.Array, cfg: SsmHybridConfig) -> dict:
    """Every weight from one key.  Matrices normal, fan-in scaled, in
    the serving dtype; the embedding normal at dim**-0.5 / embed_scale,
    so that what enters the first layer (the row times `embed_scale`) is
    fan-in scaled like every branch's output, and the TIED head's logit
    of the input token (embed_scale * |row|**2 over the stream's norm)
    stays under the other logits' spread: at dim**-0.5 alone the row
    times 12 outweighs forty layers' branches, the head reads it back
    and a random model repeats its input whatever its layers compute;
    norm weights 1; and the recurrence in its published regime: A_log =
    log(U(1, 16)), dt_bias the inverse softplus of a log-uniform dt in
    [0.001, 0.1], D = 1 (float32, as the kernel takes them)."""
    d, f, inner, C = cfg.dim, cfg.ffn_dim, cfg.inner, cfg.conv_dim
    H, K = cfg.ssm_heads, cfg.conv_kernel
    keys = iter(jax.random.split(key, 16))

    def w(shape, fan_in, scale=1.0):
        return (jax.random.normal(next(keys), shape, F32)
                * (fan_in ** -0.5 / scale)).astype(cfg.dtype)

    def both(n):
        return {"norm1": jnp.ones((n, d), cfg.dtype),
                "norm2": jnp.ones((n, d), cfg.dtype),
                "w13": w((n, d, 2 * f), d), "w2": w((n, f, d), f)}

    n, qd, kvd = cfg.count(ATTN), cfg.n_heads * cfg.head_dim, \
        cfg.n_kv_heads * cfg.head_dim
    attn = dict(both(n), wq=w((n, d, qd), d), wk=w((n, d, kvd), d),
                wv=w((n, d, kvd), d), wo=w((n, qd, d), qd))
    n = cfg.count(MAMBA)
    dt = jnp.exp(jax.random.uniform(
        next(keys), (n, H), F32, jnp.log(0.001), jnp.log(0.1)))
    mamba = dict(
        both(n), in_zx=w((n, d, inner + C), d), in_dt=w((n, d, H), d),
        conv_w=w((n, K, C), K), conv_b=jnp.zeros((n, C), cfg.dtype),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        A_log=jnp.log(jax.random.uniform(next(keys), (n, H), F32, 1.0,
                                         16.0)),
        D=jnp.ones((n, H), F32), gate_norm=jnp.ones((n, inner), cfg.dtype),
        out_proj=w((n, inner, d), inner))
    return {"embed": w((cfg.vocab_size, d), d, cfg.embed_scale),
            "mamba": mamba,
            "attn": attn, "final_norm": jnp.ones((d,), cfg.dtype)}


def project_logits(params: dict, h: jnp.ndarray) -> jnp.ndarray:
    """The head: the embedding, transposed (tied).  The seam's head has
    no config, so `logits_scale` is applied to the hidden state it is
    given (`scaled_hidden`), by the programs that make it."""
    with jax.named_scope("lm_head"):
        return lax.dot_general(h, params["embed"],
                               (((h.ndim - 1,), (1,)), ((), ())))


# ------------------------------------------------------------ the layers
def mlp(x, lp, cfg: SsmHybridConfig):
    """The second half of a layer, what it ADDS to x [..., d]."""
    h = rmsnorm(x, lp["norm2"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        ab = h @ lp["w13"]
        a, b = ab[..., :cfg.ffn_dim], ab[..., cfg.ffn_dim:]
        y = (jax.nn.silu(a.astype(F32)).astype(cfg.dtype) * b) @ lp["w2"]
    return (cfg.residual_scale * y.astype(F32)).astype(cfg.dtype)


def _in_proj(h, lp, cfg: SsmHybridConfig):
    """Step 1: (z, xBC, dt).  W_in is held as two matrices, [z | xBC]
    (inner + conv_dim = 8448 columns, whole 128-lane tiles) and dt (a
    column a head): at its published 8512 columns the chip stores the
    stacked matrix hidden-minor and every decode window copied all of it
    (1.25 GB) into the layout the matmul reads."""
    with jax.named_scope("ssm_in_proj"):
        zx = h @ lp["in_zx"]
        return zx[..., :cfg.inner], zx[..., cfg.inner:], h @ lp["in_dt"]


def _conv(rows, lp, cfg: SsmHybridConfig):
    """silu(conv_b + sum_i conv_w[i] * rows[i]) in float32; rows: K
    arrays of one shape, oldest first."""
    acc = lp["conv_b"].astype(F32) + sum(
        r.astype(F32) * lp["conv_w"][i].astype(F32)
        for i, r in enumerate(rows))
    return jax.nn.silu(acc).astype(cfg.dtype)


def _gate_out(y, z, lp, cfg: SsmHybridConfig):
    """Step 6: W_out RMSNorm(y * silu(z)); y float32 [..., inner]."""
    with jax.named_scope("ssm_gate_norm"):
        g = rmsnorm(y * jax.nn.silu(z.astype(F32)), lp["gate_norm"],
                    cfg.norm_eps).astype(cfg.dtype)
    with jax.named_scope("ssm_out"):
        return g @ lp["out_proj"]


def scan_inputs(h, lp, cfg: SsmHybridConfig, true_lens):
    """Steps 1-4 over whole rows h [b, T, d] (normed): (z, x [b, T, H,
    P], dt [b, T, H] float32, ZERO past each row's true length, B, C
    [b, T, N], conv rows [b, K-1, conv_dim]: the pre-convolution xBC
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
    x = act[..., :cfg.inner].reshape(b, T, cfg.ssm_heads, cfg.ssm_head_dim)
    Bm = act[..., cfg.inner:cfg.inner + cfg.ssm_state]
    Cm = act[..., cfg.inner + cfg.ssm_state:]
    dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"])
    dt = jnp.where(jnp.arange(T)[None, :, None] < true_lens[:, None, None],
                   dt, 0.0)
    return z, x, dt, Bm, Cm, rows


def scan_state(xs, dt, Bm, Cm, lp, cfg: SsmHybridConfig):
    """Step 5 over whole rows, from what `scan_inputs` gives: (y [b, T,
    H, P] float32, D x added; the state after each row's last position
    with dt > 0, [b, N, inner] in `state_dtype`: what a lane is handed)."""
    y, state = ssm.ssd_scan(xs, dt, -jnp.exp(lp["A_log"]), Bm[:, :, None],
                            Cm[:, :, None], cfg.ssm_chunk)  # one group
    return (y + lp["D"][:, None] * xs.astype(F32),
            state.astype(cfg.state_dtype))


def mamba_prefill(x, lp, cfg: SsmHybridConfig, true_lens):
    """The Mamba mixer over whole rows x [b, T, d], what it ADDS to x,
    and the lane's state at each row's TRUE length: (d, conv rows
    [b, K-1, conv_dim], ssm [b, N, inner] in `state_dtype`)."""
    b, T, _ = x.shape
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    z, xs, dt, Bm, Cm, rows = scan_inputs(h, lp, cfg, true_lens)
    y, state = scan_state(xs, dt, Bm, Cm, lp, cfg)
    d = _gate_out(y.reshape(b, T, cfg.inner), z, lp, cfg)
    return ((cfg.residual_scale * d.astype(F32)).astype(cfg.dtype), rows,
            state)


def attn_prefill(x, lp, cfg: SsmHybridConfig, true_lens):
    """The attention mixer over whole rows: (what it ADDS to x, k, v
    [b, T, kvh, hd])."""
    b, T, _ = x.shape
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    with jax.named_scope("attn_qkv"):
        q = (h @ lp["wq"]).reshape(b, T, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["wk"]).reshape(b, T, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"]).reshape(b, T, cfg.n_kv_heads, cfg.head_dim)
    o = attention(q, k, v, causal=True, lengths=true_lens,
                  sm_scale=cfg.attn_scale)
    with jax.named_scope("attn_out"):
        d = o.reshape(b, T, -1) @ lp["wo"]
    return ((cfg.residual_scale * d.astype(F32)).astype(cfg.dtype),
            k.astype(cfg.dtype), v.astype(cfg.dtype))


def _layer(lp, i):
    """Layer i's own weights out of a kind's stack (i may be traced: a
    dynamic slice, which the matmul that reads it fuses)."""
    return jax.tree.map(lambda a: a[i], lp)


def mamba_layer_prefill(params, x, g, cfg: SsmHybridConfig, true_lens):
    """Mamba layer `g` (its number among the Mamba layers; may be
    traced) over whole rows: (x after the layer, conv rows, ssm state,
    what the mixer and the SwiGLU each ADDED).  The prefill program's
    scan body; the benchmark's judge calls it a layer at a time."""
    li = _layer(params["mamba"], g)
    d, rows, st = mamba_prefill(x, li, cfg, true_lens)
    x = x + d
    m = mlp(x, li, cfg)
    return x + m, rows, st, (d, m)


def attn_layer_prefill(params, x, a, cfg: SsmHybridConfig, true_lens):
    """Attention layer `a` (its number among the attention layers) over
    whole rows: (x after the layer, k, v, what the mixer and the SwiGLU
    each added)."""
    li = _layer(params["attn"], a)
    d, k, v = attn_prefill(x, li, cfg, true_lens)
    x = x + d
    m = mlp(x, li, cfg)
    return x + m, k, v, (d, m)


def mamba_layer_decode(params, x, conv, ssm_state, g, lanes, count,
                       cfg: SsmHybridConfig):
    """Mamba layer `g` for one token a lane: conv [Mamba layers, B, K-1,
    conv_dim] and ssm_state the lanes' state of every layer.  Returns (x
    after the layer, conv, ssm_state), layer g's rows of both updated
    where they lie.  The decode program's scan body."""
    li = _layer(params["mamba"], g)
    x, rows, ssm_state = mamba_decode(
        x, li, lax.dynamic_index_in_dim(conv, g, keepdims=False),
        ssm_state, g, lanes, count, cfg)
    conv = lax.dynamic_update_index_in_dim(conv, rows, g, 0)
    return x + mlp(x, li, cfg), conv, ssm_state


# ---------------------------------------------------------------- prefill
def prefill(params: dict, tokens: jnp.ndarray, cfg: SsmHybridConfig,
            true_lens: jnp.ndarray | None = None, lora=None):
    """Prompt pass.  tokens [b, T], right-padded; true_lens [b] (absent:
    every row is T long); `lora` is the seam's slot for adapters, which
    this model has not (None).  Returns (hidden [b, T, d] after the
    final norm, divided by `logits_scale` so that the engine's
    `project_logits` gives the logits; ks, vs: per ATTENTION layer
    [b, T, kvh, hd]; state: {"conv": per Mamba run [layers, b, K-1,
    conv_dim], "ssm": per Mamba run [layers, b, N, inner]}, each row's
    at its TRUE length; counts int32 [0, 4])."""
    b, T = tokens.shape
    if true_lens is None:
        true_lens = jnp.full((b,), T, jnp.int32)
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    x = (x.astype(F32) * cfg.embed_scale).astype(cfg.dtype)

    def mamba_layer(x, g):
        x, rows, st, _ = mamba_layer_prefill(params, x, g, cfg, true_lens)
        return x, (rows, st)

    ks, vs, conv, states = [], [], [], []
    for kind, n, before in cfg.runs():
        if kind == MAMBA:
            x, (rows, st) = lax.scan(mamba_layer, x,
                                     before + jnp.arange(n))
            conv.append(rows)
            states.append(st)
            continue
        for a in range(before, before + n):
            x, k, v, _ = attn_layer_prefill(params, x, a, cfg, true_lens)
            ks.append(k)
            vs.append(v)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return (scaled_hidden(x, cfg), ks, vs,
            {"conv": conv, "ssm": states}, llama._no_counts())


def scaled_hidden(x, cfg: SsmHybridConfig):
    """The normed hidden state over `logits_scale` (a power of two at
    the published value: exact in any dtype), so that the head every
    caller uses (`project_logits`, which has no config) gives
    logits / logits_scale."""
    return (x.astype(F32) / cfg.logits_scale).astype(x.dtype)


# ------------------------------------------------------------ paged cache
def init_paged_cache(cfg: SsmHybridConfig, batch: int, n_pages: int,
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
    values).  `state` as `prefill` returns it: an array a Mamba run."""
    out = llama.scatter_prefill_pages(
        {"k": cache["k"], "v": cache["v"], "pos": cache["pos"]}, ks, vs,
        page_ids, rows, slots, true_lens, aligned=aligned)
    with jax.named_scope("state_write"):
        out["state"] = {}
        for name, lanes in cache["state"].items():
            at = 0
            for run in state[name]:           # the run's layers, in order
                lanes = lanes.at[at:at + run.shape[0], slots].set(run)
                at += run.shape[0]
            out["state"][name] = lanes
    return out


# ----------------------------------------------------------------- decode
def decode_inputs(x, lp, conv, cfg: SsmHybridConfig):
    """Steps 1-3 for ONE token a lane: x [B, d], conv [B, K-1, conv_dim]
    (the lane's last pre-convolution rows).  Returns (z, x [B, inner],
    dt [B, H] float32 with dt_bias added and BEFORE the softplus, which
    is the kernel's, B, C [B, N], conv shifted by the token's row)."""
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    z, xbc, dt = _in_proj(h, lp, cfg)
    with jax.named_scope("ssm_conv"):
        act = _conv([conv[:, i] for i in range(conv.shape[1])] + [xbc],
                    lp, cfg)
        conv = jnp.concatenate([conv[:, 1:], xbc[:, None]], axis=1)
    return (z, act[:, :cfg.inner], dt.astype(F32) + lp["dt_bias"],
            act[:, cfg.inner:cfg.inner + cfg.ssm_state],
            act[:, cfg.inner + cfg.ssm_state:], conv)


def mamba_decode(x, lp, conv, ssm_state, layer, lanes, count,
                 cfg: SsmHybridConfig):
    """One token of the Mamba mixer for every lane: x [B, d], conv
    [B, K-1, conv_dim], ssm_state the lanes' state of EVERY layer
    (updated in place at `layer` for the listed lanes).  Returns (x + r *
    mixer, conv, ssm_state)."""
    P = cfg.ssm_head_dim
    z, xs, dt, Bv, Cv, conv = decode_inputs(x, lp, conv, cfg)
    ssm_state, y = ssm.ssm_update(
        ssm_state, layer, lanes, count, xs, jnp.repeat(dt, P, axis=-1),
        Bv[:, None], Cv[:, None],             # one group
        jnp.repeat(lp["A_log"], P), jnp.repeat(lp["D"], P))
    d = _gate_out(y, z, lp, cfg)
    return (x + (cfg.residual_scale * d.astype(F32)).astype(cfg.dtype),
            conv, ssm_state)


def decode_step_paged(params: dict, pages: dict, tails: dict, state: dict,
                      tokens: jnp.ndarray, pos: jnp.ndarray,
                      tail_start: jnp.ndarray, j, page_table: jnp.ndarray,
                      cfg: SsmHybridConfig, lora=None, plan=None):
    """One decode step over the paged cache, the in-block tail (see
    llama.decode_step_paged) and the lanes' state.  A lane whose table
    row starts at the trash page holds no request: it attends nothing
    and its state matrices are not touched.  Returns (logits [B, vocab]
    float32, tails, state, counts int32 [0, 4])."""
    from ray_tpu.ops.paged_attention import (lanes_live,
                                             paged_decode_attention)

    B = tokens.shape[0]
    hd = cfg.head_dim
    n_rep = cfg.n_heads // cfg.n_kv_heads
    lanes, count = ssm.live_lanes(lanes_live(page_table))
    x = embed_lookup(params["embed"], tokens[:, None], cfg.dtype)[:, 0]
    x = (x.astype(F32) * cfg.embed_scale).astype(cfg.dtype)
    new_tk, new_tv = [], []

    def mamba_layer(carry, g):
        return mamba_layer_decode(params, *carry, g, lanes, count, cfg), None

    carry = (x, state["conv"], state["ssm"])
    for kind, n, before in cfg.runs():
        if kind == MAMBA:
            carry, _ = lax.scan(
                mamba_layer, carry,
                before + jnp.arange(n, dtype=jnp.int32))
            continue
        x = carry[0]
        for ai in range(before, before + n):
            li = _layer(params["attn"], ai)
            h = rmsnorm(x, li["norm1"], cfg.norm_eps)
            with jax.named_scope("attn_qkv"):
                # the products held flat, or wq / wk / wv are
                # re-laid-out every step (llama._decode_qkv)
                q, k, v = llama._decode_qkv(h[:, None], li, cfg)
                q = q.reshape(B, cfg.n_kv_heads, n_rep, hd)
                k = k.reshape(B, cfg.n_kv_heads, 1, hd)
                v = v.reshape(B, cfg.n_kv_heads, 1, hd)
            with jax.named_scope("kv_write"):
                tk = lax.dynamic_update_slice(
                    tails["k"][ai], k.astype(cfg.dtype), (0, 0, j, 0))
                tv = lax.dynamic_update_slice(
                    tails["v"][ai], v.astype(cfg.dtype), (0, 0, j, 0))
            with jax.named_scope("attn"):
                o = paged_decode_attention(
                    q.astype(cfg.dtype), pages["k"][ai], pages["v"][ai],
                    tk, tv, page_table, pos, tail_start, plan=plan,
                    sm_scale=cfg.attn_scale)
            new_tk.append(tk)
            new_tv.append(tv)
            with jax.named_scope("attn_out"):
                d = o.reshape(B, cfg.n_heads * hd) @ li["wo"]
            x = x + (cfg.residual_scale * d.astype(F32)).astype(cfg.dtype)
            x = x + mlp(x, li, cfg)
        carry = (x,) + carry[1:]
    x, conv, ssm_state = carry
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = project_logits(params, scaled_hidden(x, cfg)).astype(F32)
    return (logits, {"k": new_tk, "v": new_tv},
            {"conv": conv, "ssm": ssm_state}, llama._no_counts())


# the serving seam's names (models/serving.py)
serve_prefill = prefill
serve_scatter = scatter_prefill_pages
serve_decode_step = decode_step_paged
