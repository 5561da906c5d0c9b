"""A decoder of WINDOW grouped-query layers with a learned sink beside a
few GLOBAL grouped-query layers of another kv-head count, keys wider than
values, rotary on a part of each head at a theta a kind, the values
scaled, routed experts without a shared one (`model_type`
`mimo_v2_flash`, e.g. MiMo-V2-Flash), served.  This module gives the
serving seam (`ray_tpu.models.serving_model`) what `serve/llm.LLMEngine`
runs.  It has none of the optional capabilities (`serving_spec`'s `caps`
is empty): a lane carries a K and a V RING a window layer that no page
holds, and the pool is the global layers' K and V pages alone, a K page
wider than a V page.  A K row is STORED at whole lane tiles (`k_store`:
192 -> 256, zeros past the head's width): a resident array whose minor
dimension is a tile and a half is kept rows-minor by the chip and copied
whole, in and out, by every program that reads it (PERF.md section 6, PR
52), and the tiled layout pads the half tile anyway.

The equations (N = RMSNorm, eps `norm_eps`; pre-norm residual h = x +
Attn(N(x)), y = h + FFN(N(h)); no bias anywhere; what the published keys
leave open is marked "assumed" and lives in ONE function here and ONE in
the reference `benchmarks/harness/refs/mimo_v2.py`).

**Both kinds of layer**, u = N(x), H = `n_heads` query heads over G kv
heads (G = `n_kv_heads` in a global layer, `swa_n_kv_heads` in a window
layer; query head h reads kv head h // (H / G)), dk = `qk_head_dim`, dv =
`v_head_dim`:

    q_h = RoPE((u W_q)_h);  k_g = RoPE((u W_k)_g);  v_g = (u W_v)_g
    a_{t,s} = q_{t,h} . k_{s,g} / sqrt(dk)
    Attn = `value_scale` [o_h]_h W_o

RoPE rotate-half over the FIRST `rope_dim` = int(`partial_rotary_factor`
x dk) columns of a head, the rest as they came (assumed: `partial_rope`),
at `rope_theta` in a global layer and `swa_rope_theta` in a window layer.
The value scale multiplies the heads' outputs before W_o, which by
linearity is the values scaled (`scaled_out`).

**Global layer** (`layer_types[l] == "full_attention"`): o_{t,h} = sum_{s
<= t} softmax_s(a_{t,s}) v_{s,g}, no sink.  Pool: K pages [n, G, page,
k_store] beside V pages [n, G, page, dv].  Prefill `flash_fwd`, decode
`paged_attn`.

**Window layer** (`"sliding_attention"`): S_t = {s : t - window < s <= t}
(assumed: `window` counts the query's own position), and a learned sink
s_h a head joins the softmax's denominator and carries no value:

    o_{t,h} = sum_{s in S_t} e^{a_{t,s} - m} v_{s,g}
              / (e^{s_h - m} + sum_{s in S_t} e^{a_{t,s} - m})

No pool page: a lane keeps the last `ring_rows` >= window rows of K and
of V a kv head in two RINGS, the row of position p in slot p mod
ring_rows (`ops/window_attention`), written in place by the decode step
and filled by the scatter from a prefill row's last positions.  Prefill:
`flash_fwd` under a band, the sink folded in from the kernel's
log-sum-exp (`ops/flash_attention`), under the device-side name
`swa_band`.

**Feed-forward**: SwiGLU of `ffn_dim` where `moe_layers[l]` is 0;
elsewhere `models/routed.py`: sigmoid scores over ALL `n_experts`, top
`top_k` of score + bias, w = `routed_scaling` score / sum, the experts
THIS CHIP HOLDS (`experts_held`); no shared expert.

**Lane state** (`init_paged_cache()["state"]`): `{"window_k": [one
[lanes, G, ring_rows, k_store] array a window layer], "window_v": [...
dv]}`.  **Pool**: `{"k": [n_pages, G, page, k_store] a global layer,
"v": [..., dv]}`.

Not served: the multi-token-prediction layers.

Device-side names: `attn_qkv`, `attn_global` (the global layers'
attention: `flash_fwd` in prefill, `paged_attn` in decode), `attn_window`
(the window layers': `swa_band` in prefill, `swa_attn` in decode),
`ring_write`, `attn_out`, beside `moe_router`, `moe_experts`, `embed`,
`mlp`, `lm_head`, `kv_write`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import routed
from ray_tpu.models.llama import (apply_rope, embed_lookup, rmsnorm,
                                  scatter_rows)
from ray_tpu.models.routed import route
from ray_tpu.models.serving import ServingSpec
from ray_tpu.ops import (flash_attention, live_rows, ssm,
                         window_attention as swa)
from ray_tpu.ops.attention import attention
from ray_tpu.ops.paged_attention import lanes_live, paged_decode_attention
from ray_tpu.ops.rope import rope_frequencies

GLOBAL, WINDOW = "full_attention", "sliding_attention"
F32 = jnp.float32
LANE = 128


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 152576
    dim: int = 4096
    layer_types: tuple = ((GLOBAL,) + (WINDOW,) * 4
                          + ((GLOBAL,) + (WINDOW,) * 5) * 7 + (GLOBAL,))
    moe_layers: tuple = (0,) + (1,) * 47     # `moe_layer_freq`
    n_heads: int = 64
    n_kv_heads: int = 4             # a global layer's
    swa_n_kv_heads: int = 8         # a window layer's
    qk_head_dim: int = 192
    v_head_dim: int = 128
    rope_dim: int = 64              # int(partial_rotary_factor 0.334 x 192)
    rope_theta: float = 5.0e6
    swa_rope_theta: float = 1.0e4
    window: int = 128               # `sliding_window`, own position in
    ring_rows: int = 128            # >= window
    value_scale: float = 0.707      # `attention_value_scale`
    ffn_dim: int = 16384
    moe_ffn_dim: int = 2048
    n_experts: int = 256            # the ROUTER's width
    experts_held: tuple = (0, 256)
    top_k: int = 8
    use_expert_bias: bool = True    # `topk_method` noaux_tc
    norm_topk_prob: bool = True
    routed_scaling: float = 1.0     # `routed_scaling_factor` null
    norm_eps: float = 1e-5
    max_seq: int = 262144
    dtype: Any = jnp.bfloat16

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def k_store(self) -> int:
        """Columns a K row is STORED at: whole lane tiles."""
        return -(-self.qk_head_dim // LANE) * LANE

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def before(self, lid: int) -> int:
        """Layers of layer `lid`'s kind that come before it."""
        return self.layer_types[:lid].count(self.layer_types[lid])

    def is_routed(self, lid: int) -> bool:
        return bool(self.moe_layers[lid])

    def kv_heads(self, kind: str) -> int:
        return self.n_kv_heads if kind == GLOBAL else self.swa_n_kv_heads

    def theta(self, kind: str) -> float:
        return self.rope_theta if kind == GLOBAL else self.swa_rope_theta


def serving_configs() -> dict[str, MimoV2Config]:
    return {
        "mimo-v2-flash": MimoV2Config(),
        "mimo-v2-debug": MimoV2Config(
            vocab_size=256, dim=64,
            layer_types=(GLOBAL, WINDOW, WINDOW, GLOBAL, WINDOW),
            moe_layers=(0, 1, 1, 1, 1), n_heads=8, n_kv_heads=2,
            swa_n_kv_heads=4, qk_head_dim=24, v_head_dim=16, rope_dim=8,
            window=9, ring_rows=9, ffn_dim=128, moe_ffn_dim=32,
            n_experts=8, experts_held=(0, 8), top_k=2, max_seq=128),
    }


def _routed_layers(cfg: MimoV2Config) -> int:
    return sum(cfg.moe_layers)


def attn_params(cfg: MimoV2Config, kind: str) -> int:
    """W_q, W_k, W_v and W_o of one layer of `kind`."""
    H, G = cfg.n_heads, cfg.kv_heads(kind)
    return cfg.dim * ((H + G) * cfg.qk_head_dim + G * cfg.v_head_dim
                      + H * cfg.v_head_dim)


def prefill_params(cfg: MimoV2Config) -> tuple[int, int]:
    """Matmul parameters a prefill program STREAMS whatever it holds and
    those ONE position multiplies (`routed.prefill_params`)."""
    rest = (cfg.count(GLOBAL) * attn_params(cfg, GLOBAL)
            + cfg.count(WINDOW) * attn_params(cfg, WINDOW)
            + (cfg.n_layers - _routed_layers(cfg)) * 3 * cfg.dim
            * cfg.ffn_dim)
    return routed.prefill_params(cfg, rest, _routed_layers(cfg),
                                 cfg.experts_held)


def _decode_work(cfg: MimoV2Config, rows, k: int, page: int, maxp: int
                 ) -> tuple[dict, dict]:
    """One decode window of `k` steps over live lanes that start it on
    `rows` cached rows each: what the window layers' rings gave (the
    global layers' rows are the engine's own `attn_ctx_rows`, a lane and
    not a layer)."""
    del page, maxp
    return swa.decode_work(cfg.count(WINDOW), cfg.window, rows, k)


def _prefill_work(cfg: MimoV2Config, true_lens, bucket: int
                  ) -> tuple[dict, dict]:
    """One prefill program: the global layers' causal walk
    (`prefill_attn_blocks`) and the window layers' banded walk beside the
    causal walk at its own blocks (`prefill_swa_blocks`), a layer of each
    kind; and the positions its position-wise halves compute
    (`prefill_walked_tokens`)."""
    band, _ = flash_attention.band_work(cfg.window, true_lens, bucket)
    work, _ = flash_attention.prefill_work(true_lens, bucket)
    work.update({k: band[k] for k in flash_attention.BAND_COUNTERS})
    walked, shown = live_rows.prefill_work(true_lens, bucket)
    return {**work, **walked}, shown


def serving_spec(cfg: MimoV2Config) -> ServingSpec:
    """No optional capability.  A window layer keeps a K and a V ring a
    lane, filled from a prefill row's last positions: the bytes of the
    rings ONE prefill row hands the scatter."""
    n_win = cfg.count(WINDOW)
    return ServingSpec(
        lane_state_layers=n_win,
        prefill_state_bytes=(
            n_win * cfg.ring_rows * cfg.swa_n_kv_heads
            * (cfg.k_store + cfg.v_head_dim)
            * jnp.dtype(cfg.dtype).itemsize),
        prefill_params=prefill_params(cfg),
        routed_layers=_routed_layers(cfg),
        counters={**flash_attention.PREFILL_COUNTERS,
                  **flash_attention.BAND_COUNTERS, **live_rows.COUNTERS,
                  **swa.COUNTERS, **routed.COUNTERS},
        decode_work=functools.partial(_decode_work, cfg),
        prefill_work=functools.partial(_prefill_work, cfg),
        routed_work=functools.partial(routed.routed_work, cfg,
                                      cfg.experts_held))


# ---------------------------------------------------------------- params
def init_params(key: jax.Array, cfg: MimoV2Config,
                expert_bias_std: float = 0.02) -> dict:
    """Every weight from one key: matrices normal, fan-in scaled, in the
    serving dtype; norm weights 1; a window layer's sinks N(0, 1) a head
    (float32); the experts of `experts_held` only; `expert_bias` N(0,
    expert_bias_std) over all `n_experts`."""
    d, f = cfg.dim, cfg.moe_ffn_dim
    H, dk, dv = cfg.n_heads, cfg.qk_head_dim, cfg.v_head_dim
    held = cfg.experts_held[1] - cfg.experts_held[0]
    keys = iter(jax.random.split(key, 3 + 12 * cfg.n_layers))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(cfg.dtype)

    layers = []
    for lid, kind in enumerate(cfg.layer_types):
        G = cfg.kv_heads(kind)
        lp = {"norm1": jnp.ones((d,), cfg.dtype),
              "norm2": jnp.ones((d,), cfg.dtype),
              "wq": w((d, H * dk), d), "wk": w((d, G * dk), d),
              "wv": w((d, G * dv), d), "wo": w((H * dv, d), H * dv)}
        if kind == WINDOW:
            lp["sink"] = jax.random.normal(next(keys), (H,), F32)
        if cfg.is_routed(lid):
            lp.update(router=w((d, cfg.n_experts), d),
                      expert_bias=expert_bias_std * jax.random.normal(
                          next(keys), (cfg.n_experts,), F32),
                      w13=w((held, d, 2 * f), d), w2=w((held, f, d), f))
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
def routed_ffn(h2, lp, cfg: MimoV2Config, live=None):
    """`routed.routed_ffn` for the experts this chip holds, under THIS
    module's `route`."""
    return routed.routed_ffn(h2, lp, cfg, live, cfg.experts_held,
                             route_fn=route)


def ffn(x, lp, lid: int, cfg: MimoV2Config, live=None):
    """The second half of layer `lid`, what it ADDS to x [..., d], and
    the counts of a routed layer or None.  Prefill and decode share it;
    whole rows x [b, T, d] pass the dense layer's up to the last `live`
    position (`live_rows.walk`)."""
    if not cfg.is_routed(lid):
        def dense(x, _first=None):
            h = rmsnorm(x, lp["norm2"], cfg.norm_eps)
            with jax.named_scope("mlp"):
                return routed.swiglu(h, lp["w1"], lp["w3"], lp["w2"],
                                     cfg.dtype)
        if x.ndim == 3:
            n_live = x.shape[1] if live is None else live_rows.count(live)
            return live_rows.walk(dense, x, n_live), None
        return dense(x), None
    h = rmsnorm(x, lp["norm2"], cfg.norm_eps)
    y, counts = routed_ffn(h.reshape(-1, cfg.dim), lp, cfg,
                           None if live is None else live.reshape(-1))
    return y.reshape(x.shape), counts


# --------------------------------------------------------------- attention
def partial_rope(x, cos, sin, positions, cfg: MimoV2Config):
    """Rotate-half RoPE over the FIRST `rope_dim` columns of every head of
    x [b, T, heads, dk], the rest as they came (assumed: which columns
    turn)."""
    turned = apply_rope(x[..., :cfg.rope_dim], cos, sin, positions=positions)
    return jnp.concatenate([turned, x[..., cfg.rope_dim:]], axis=-1)


def qkv(h, lp, kind: str, cfg: MimoV2Config, positions, n_pos: int,
        first=None):
    """h [b, T, d] normed, positions [b, T] or None (`first` .. `first` +
    T - 1; 0 .. T - 1 without one), `n_pos` the positions the rotary
    tables cover -> (q [b, T, H, dk], k [b, T, G, dk], both turned; v [b,
    T, G, dv])."""
    b, T, _ = h.shape
    G = cfg.kv_heads(kind)
    tables = rope_frequencies(cfg.rope_dim, n_pos, cfg.theta(kind))
    if first is not None:
        tables = tuple(lax.dynamic_slice_in_dim(t, first, T) for t in tables)
    with jax.named_scope("attn_qkv"):
        q = (h @ lp["wq"]).reshape(b, T, cfg.n_heads, cfg.qk_head_dim)
        k = (h @ lp["wk"]).reshape(b, T, G, cfg.qk_head_dim)
        v = (h @ lp["wv"]).reshape(b, T, G, cfg.v_head_dim)
        q = partial_rope(q, *tables, positions, cfg)
        k = partial_rope(k, *tables, positions, cfg)
    return q, k.astype(cfg.dtype), v.astype(cfg.dtype)


def stored(a, cfg: MimoV2Config):
    """a [..., dk] keys (or the queries that meet them) at the width a K
    row is stored at: zeros past the head's width."""
    return jnp.pad(a, ((0, 0),) * (a.ndim - 1)
                   + ((0, cfg.k_store - a.shape[-1]),))


def scaled_out(o, lp, cfg: MimoV2Config):
    """The value scale and the output projection: o [..., H, dv] the
    heads' outputs -> [..., d]."""
    with jax.named_scope("attn_out"):
        o = (o.astype(F32) * cfg.value_scale).astype(cfg.dtype)
        return o.reshape(*o.shape[:-2], -1) @ lp["wo"]


def _scale(cfg: MimoV2Config) -> float:
    return cfg.qk_head_dim ** -0.5


def normed_qkv(x, lp, kind: str, cfg: MimoV2Config, n_live):
    """The first position-wise half of a prefill layer over whole rows x
    [b, T, d], up to position `n_live` (`live_rows.walk`): the norm, the
    three products and the rotary part."""
    n_pos = x.shape[1]

    def rows(x, first):
        h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
        return qkv(h, lp, kind, cfg, None, n_pos, first)

    return live_rows.walk(rows, x, n_live)


def scaled_out_rows(o, lp, cfg: MimoV2Config, n_live):
    """`scaled_out` of whole rows' heads o [b, T, H, dv], up to position
    `n_live`."""
    return live_rows.walk(lambda o, _first: scaled_out(o, lp, cfg), o,
                          n_live)


def global_prefill(x, lp, cfg: MimoV2Config, true_lens):
    """The global layer's attention half over whole rows x [b, T, d]:
    (what it adds to x, (K rows [b, T, G, k_store], V rows [b, T, G,
    dv])).  What is computed a position alone stops at the longest true
    length."""
    n_live = jnp.max(true_lens)
    q, k, v = normed_qkv(x, lp, GLOBAL, cfg, n_live)
    with jax.named_scope("attn_global"):
        o = attention(q, k, v, sm_scale=_scale(cfg), lengths=true_lens)
    return scaled_out_rows(o, lp, cfg, n_live), (stored(k, cfg), v)


def window_prefill(x, lp, cfg: MimoV2Config, true_lens):
    """The window layer's attention half over whole rows x [b, T, d]
    under the band, the sink in the denominator: (what it adds to x, each
    row's (K ring [b, G, ring_rows, k_store], V ring [b, G, ring_rows,
    dv]) at its TRUE length).  What is computed a position alone stops at
    the longest true length."""
    n_live = jnp.max(true_lens)
    q, k, v = normed_qkv(x, lp, WINDOW, cfg, n_live)
    with jax.named_scope("attn_window"):
        o = attention(q, k, v, sm_scale=_scale(cfg), lengths=true_lens,
                      window=cfg.window, sink=lp["sink"])
    with jax.named_scope("ring_write"):
        rings = tuple(swa.kv_ring_from_rows(a, true_lens, cfg.ring_rows)
                      for a in (stored(k, cfg), v))
    return scaled_out_rows(o, lp, cfg, n_live), rings


def global_decode(x, lp, k_pages, v_pages, k_tail, v_tail, page_table, pos,
                  tail_start, j, cfg: MimoV2Config, plan: dict | None = None):
    """One token of the global layer's attention half for every lane: x
    [B, d]; the layer's two pool leaves (read-only) and their tails (the
    new rows land at column j).  Returns (what it adds, K tail, V
    tail)."""
    B = x.shape[0]
    G = cfg.n_kv_heads
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    max_len = page_table.shape[1] * k_pages.shape[2]
    q, k, v = qkv(h[:, None], lp, GLOBAL, cfg, pos[:, None], max_len)
    with jax.named_scope("kv_write"):
        k_tail = lax.dynamic_update_slice(
            k_tail, stored(k, cfg)[:, 0, :, None, :], (0, 0, j, 0))
        v_tail = lax.dynamic_update_slice(
            v_tail, v[:, 0, :, None, :], (0, 0, j, 0))
    qg = stored(q, cfg)[:, 0].reshape(B, G, cfg.n_heads // G, cfg.k_store)
    with jax.named_scope("attn_global"):
        o = paged_decode_attention(
            qg.astype(cfg.dtype), k_pages, v_pages, k_tail, v_tail,
            page_table, pos, tail_start, plan=plan, sm_scale=_scale(cfg))
    return (scaled_out(o.reshape(B, cfg.n_heads, cfg.v_head_dim), lp, cfg),
            k_tail, v_tail)


def window_decode(x, lp, k_ring, v_ring, pos, max_len: int, listed, lanes,
                  count, cfg: MimoV2Config):
    """One token of the window layer's attention half for every lane: x
    [B, d]; k_ring [B, G, ring_rows, k_store], v_ring [B, G, ring_rows, dv]
    the lanes' rings of this layer (the token's rows are written at slot
    pos mod ring_rows, in place); max_len the positions a lane can reach;
    listed [B] the lanes that hold a request (lanes, count: their work
    list).  Returns (what it adds, K ring, V ring)."""
    B = x.shape[0]
    G = cfg.swa_n_kv_heads
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    q, k, v = qkv(h[:, None], lp, WINDOW, cfg, pos[:, None], max_len)
    with jax.named_scope("ring_write"):
        k_ring = swa.kv_ring_write(k_ring, stored(k, cfg)[:, 0], pos, listed)
        v_ring = swa.kv_ring_write(v_ring, v[:, 0], pos, listed)
    qg = stored(q, cfg)[:, 0].reshape(B, G, cfg.n_heads // G, cfg.k_store)
    with jax.named_scope("attn_window"):
        o = swa.kv_ring_attention(
            qg.astype(cfg.dtype), k_ring, v_ring,
            swa.ring_bias(pos, cfg.ring_rows, cfg.window),
            lp["sink"].reshape(G, -1), lanes, count, sm_scale=_scale(cfg))
    return (scaled_out(o.reshape(B, cfg.n_heads, cfg.v_head_dim), lp, cfg),
            k_ring, v_ring)


# ---------------------------------------------------------------- prefill
def layer_prefill(params, x, lid: int, cfg: MimoV2Config, true_lens):
    """Layer `lid` over whole rows x [b, T, d]: (x after it, what its
    attention hands the pool or the lane, the routed counts or None).
    The prefill program's body; the benchmark's judge calls it a layer at
    a time."""
    lp = params["layers"][lid]
    live = jnp.arange(x.shape[1])[None, :] < true_lens[:, None]
    mixer = global_prefill if cfg.layer_types[lid] == GLOBAL \
        else window_prefill
    y, kept = mixer(x, lp, cfg, true_lens)
    x = x + y
    y, cnt = ffn(x, lp, lid, cfg, live)
    return x + y, kept, cnt


def prefill(params: dict, tokens: jnp.ndarray, cfg: MimoV2Config,
            true_lens: jnp.ndarray | None = None, lora=None):
    """Prompt pass.  tokens [b, T], right-padded; true_lens [b] (absent:
    every row is T long).  Returns the seam's (hidden [b, T, d] after the
    final norm; the K rows, a global layer [b, T, G, k_store]; the V rows
    [b, T, G, dv]; state: {"window_k", "window_v": a window layer each
    [b, G, ring_rows, k_store | dv]}, every row's at its TRUE length; counts
    int32 [routed layers, 5])."""
    b, T = tokens.shape
    if true_lens is None:
        true_lens = jnp.full((b,), T, jnp.int32)
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens, cfg.dtype)
    ks, vs, ring_k, ring_v, counts = [], [], [], [], []
    for lid, kind in enumerate(cfg.layer_types):
        x, kept, cnt = layer_prefill(params, x, lid, cfg, true_lens)
        if kind == GLOBAL:
            ks.append(kept[0])
            vs.append(kept[1])
        else:
            ring_k.append(kept[0])
            ring_v.append(kept[1])
        if cnt is not None:
            counts.append(cnt)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return (x, ks, vs, {"window_k": ring_k, "window_v": ring_v},
            routed.stack_counts(counts))


# ------------------------------------------------------------ paged cache
def init_paged_cache(cfg: MimoV2Config, batch: int, n_pages: int,
                     page: int) -> dict:
    """A K and a V pool leaf a GLOBAL layer, a row a token each, the K
    leaf wider: [n_pages, G, page, k_store] and [n_pages, G, page, dv]; a
    window layer holds no page: its lanes' rings are the `state` (the
    module's docstring)."""
    if cfg.ring_rows < cfg.window:
        raise ValueError(f"ring_rows {cfg.ring_rows} under the window "
                         f"{cfg.window}")
    n_glob, n_win = cfg.count(GLOBAL), cfg.count(WINDOW)
    G, Gw = cfg.n_kv_heads, cfg.swa_n_kv_heads

    def leaves(n, shape):
        return [jnp.zeros(shape, cfg.dtype) for _ in range(n)]

    return {
        "k": leaves(n_glob, (n_pages, G, page, cfg.k_store)),
        "v": leaves(n_glob, (n_pages, G, page, cfg.v_head_dim)),
        "pos": jnp.zeros((batch,), jnp.int32),
        "state": {
            "window_k": leaves(n_win, (batch, Gw, cfg.ring_rows,
                                       cfg.k_store)),
            "window_v": leaves(n_win, (batch, Gw, cfg.ring_rows,
                                       cfg.v_head_dim))}}


def scatter_prefill_pages(cache: dict, ks, vs, state, page_ids, row_ids,
                          slots, true_lens, aligned: bool = True) -> dict:
    """Write a prefill wave's rows into both pool leaves and each row's
    rings into its lane, where the lanes' rings lie (the cache is
    donated)."""
    with jax.named_scope("kv_write"):
        out = {name: [scatter_rows(p, new, page_ids, row_ids, aligned)
                      for p, new in zip(cache[name], rows)]
               for name, rows in (("k", ks), ("v", vs))}
        out["pos"] = cache["pos"].at[slots].set(true_lens)
    with jax.named_scope("ring_write"):
        out["state"] = swa.kv_rings_scatter(cache["state"], state, slots)
    return out


# ----------------------------------------------------------------- decode
def decode_step_paged(params: dict, pages: dict, tails: dict, state: dict,
                      tokens: jnp.ndarray, pos: jnp.ndarray,
                      tail_start: jnp.ndarray, j, page_table: jnp.ndarray,
                      cfg: MimoV2Config, lora=None, plan=None):
    """One decode step over both pool leaves, their in-block tails and
    the lanes' rings.  A lane whose table row starts at the trash page
    holds no request: it attends nothing, is routed nowhere and its rings
    are not touched.  `plan` (the paged kernel's work list of pages) is
    the global layers'; a ring needs no table.  Returns (logits [B,
    vocab] float32, tails, state, counts int32 [routed layers, 5])."""
    live = lanes_live(page_table)
    lanes, count = ssm.live_lanes(live)
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens, cfg.dtype)      # [B, d]
    k_t, v_t = list(tails["k"]), list(tails["v"])
    ring_k, ring_v = list(state["window_k"]), list(state["window_v"])
    max_len = page_table.shape[1] * pages["k"][0].shape[2]
    counts = []
    for lid, kind in enumerate(cfg.layer_types):
        lp = params["layers"][lid]
        i = cfg.before(lid)
        if kind == GLOBAL:
            y, k_t[i], v_t[i] = global_decode(
                x, lp, pages["k"][i], pages["v"][i], k_t[i], v_t[i],
                page_table, pos, tail_start, j, cfg, plan=plan)
        else:
            y, ring_k[i], ring_v[i] = window_decode(
                x, lp, ring_k[i], ring_v[i], pos, max_len, live, lanes,
                count, cfg)
        x = x + y
        y, cnt = ffn(x, lp, lid, cfg, live)
        x = x + y
        if cnt is not None:
            counts.append(cnt)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = project_logits(params, x).astype(F32)
    return (logits, {"k": k_t, "v": v_t},
            {"window_k": ring_k, "window_v": ring_v},
            routed.stack_counts(counts))


# the serving seam's names (models/serving.py)
serve_prefill = prefill
serve_scatter = scatter_prefill_pages
serve_decode_step = decode_step_paged
