"""A decoder whose layer is a PARALLEL block under one LayerNorm:
attention and a routed feed-forward with averaged shared experts both
read u = LN(x) and are added to x together; window layers with rotary
beside global layers without any (`model_type` `cohere2_moe`, e.g.
Command A+), served.  This module gives the serving seam
(`ray_tpu.models.serving_model`) what `serve/llm.LLMEngine` runs.  It has
none of the optional capabilities (`serving_spec`'s `caps` is empty): a
lane carries a K and a V RING a window layer that no page holds, and the
pool is the global layers' K and V pages alone.

The equations (x [T, d]; no bias anywhere; what the published keys leave
open is marked "assumed" and lives in ONE function here and ONE in the
reference `benchmarks/harness/refs/cohere2_moe.py`):

    u = LN(x) = (x - mean x) / sqrt(var x + eps) . g      (assumed: the
        Cohere family's LayerNorm, mean subtracted, a weight, no bias)
    q = u W_q [T, H, dk];  k = u W_k [T, G, dk];  v = u W_v [T, G, dk]
    window layer (`layer_types[l] == "sliding_attention"`): q, k <- RoPE
        over all dk columns, pairs (2i, 2i + 1), theta `rope_theta`;
        query t attends s with t - window < s <= t (assumed: the window
        counts the query's own position)
    global layer (`"full_attention"`): no rotary at all (assumed: NoPE);
        query t attends every s <= t
    a = softmax(q k^T / sqrt dk) v, query head h reads kv head h // (H /
        G), heads side by side, . W_o
    routed = `models/routed.py`: sigmoid scores over ALL `n_experts` in
        float32, top `top_k`, w = score / sum over the selected, the
        experts THIS CHIP HOLDS (`experts_held`)
    shared = 1/S sum_j W2_j(silu(W1_j u) * W3_j u), S = `n_shared`
        (assumed: "average" = the mean of the shared experts' outputs,
        added at weight 1)
    y = x + a + routed + shared
    logits = `logit_scale` . LN_f(y_L) E^T                 (E tied)

**What the program holds otherwise than published, each equal by a test**
(`tests/test_cohere2_moe.py`).  W_q and W_k hold every head's EVEN
columns first and its odd ones after (`ops/rope.half_from_interleaved`,
as a checkpoint loader would permute them once): the rotary that pairs
neighbours is then `apply_rope`'s, which pairs the halves, and every
score is the published one (q and k permuted alike; the global layers'
are permuted too and turn nothing).  The S shared experts are ONE SwiGLU
S times as wide (`sw1`, `sw3` [d, S f] the experts side by side, `sw2`
[S f, d] their down-projections stacked and divided by S: a power of two
here, exact in bfloat16).

**Window layer**: no pool page: a lane keeps the last `ring_rows` >=
window rows of K and of V a kv head in two RINGS, the row of position p
in slot p mod ring_rows (`ops/window_attention`), written in place by the
decode step and filled by the scatter from a prefill row's last
positions.  Decode `swa_attn`, the rings walked in blocks under a running
softmax; prefill `flash_fwd` under a band, named `swa_band` on the
device.  **Global layer**: K and V pages [n, G, page, dk]; prefill
`flash_fwd`, decode `paged_attn`.

**Lane state** (`init_paged_cache()["state"]`): `{"window_k": [one
[lanes, G, ring_rows, dk] array a window layer], "window_v": [...]}`.
**Pool**: `{"k": [n_pages, G, page, dk] a global layer, "v": [...]}`.

Not served: the vision tower.

Device-side names: `attn_qkv`, `attn_global` (`flash_fwd` in prefill,
`paged_attn` in decode), `attn_window` (`swa_band`, `swa_attn`),
`ring_write`, `attn_out`, beside `moe_router`, `moe_experts`,
`shared_expert`, `embed`, `lm_head`, `kv_write`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import routed
from ray_tpu.models.llama import apply_rope, embed_lookup, scatter_rows
from ray_tpu.models.routed import route
from ray_tpu.models.serving import ServingSpec
from ray_tpu.ops import (flash_attention, live_rows, ssm,
                         window_attention as swa)
from ray_tpu.ops.attention import attention
from ray_tpu.ops.norms import layernorm
from ray_tpu.ops.paged_attention import lanes_live, paged_decode_attention
from ray_tpu.ops.rope import half_from_interleaved, rope_frequencies

GLOBAL, WINDOW = "full_attention", "sliding_attention"
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 262144
    dim: int = 4096
    layer_types: tuple = ((WINDOW,) * 3 + (GLOBAL,)) * 8
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 5.0e4
    window: int = 4096              # `sliding_window`, own position in
    ring_rows: int = 4096           # >= window
    moe_ffn_dim: int = 4096         # an expert's and a shared expert's
    n_experts: int = 128            # the ROUTER's width
    experts_held: tuple = (0, 128)
    top_k: int = 8
    n_shared: int = 4               # `num_shared_experts`, averaged
    use_expert_bias: bool = False   # no bias key in the config
    norm_topk_prob: bool = True
    routed_scaling: float = 1.0
    norm_eps: float = 1e-5          # `layer_norm_eps`
    logit_scale: float = 1.0
    max_seq: int = 200000
    dtype: Any = jnp.bfloat16

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def before(self, lid: int) -> int:
        """Layers of layer `lid`'s kind that come before it."""
        return self.layer_types[:lid].count(self.layer_types[lid])


def serving_configs() -> dict[str, Cohere2MoeConfig]:
    return {
        "command-a-plus": Cohere2MoeConfig(),
        "cohere2-moe-debug": Cohere2MoeConfig(
            vocab_size=256, dim=64,
            layer_types=(WINDOW, WINDOW, GLOBAL, WINDOW), n_heads=8,
            n_kv_heads=2, head_dim=16, window=9, ring_rows=9,
            moe_ffn_dim=32, n_experts=8, experts_held=(0, 8), top_k=2,
            n_shared=2, max_seq=128),
    }


def attn_params(cfg: Cohere2MoeConfig) -> int:
    """W_q, W_k, W_v and W_o of one layer."""
    return 2 * cfg.dim * cfg.head_dim * (cfg.n_heads + cfg.n_kv_heads)


def prefill_params(cfg: Cohere2MoeConfig) -> tuple[int, int]:
    """Matmul parameters a prefill program STREAMS whatever it holds and
    those ONE position multiplies (`routed.prefill_params`)."""
    rest = cfg.n_layers * (attn_params(cfg) + cfg.n_shared * 3 * cfg.dim
                           * cfg.moe_ffn_dim)
    return routed.prefill_params(cfg, rest, cfg.n_layers, cfg.experts_held)


def _decode_work(cfg: Cohere2MoeConfig, rows, k: int, page: int, maxp: int
                 ) -> tuple[dict, dict]:
    """One decode window of `k` steps over live lanes that start it on
    `rows` cached rows each: what the window layers' rings gave and the
    rows of the ring blocks the kernel walked for it (the global layers'
    rows are the engine's own `attn_ctx_rows`, a lane and not a
    layer)."""
    del page, maxp
    return swa.decode_work(cfg.count(WINDOW), cfg.window, rows, k,
                           ring=cfg.ring_rows)


def _prefill_work(cfg: Cohere2MoeConfig, true_lens, bucket: int
                  ) -> tuple[dict, dict]:
    """One prefill program: the global layers' causal walk
    (`prefill_attn_blocks`), the window layers' banded walk beside the
    causal walk at its own blocks (`prefill_swa_blocks`), a layer of each
    kind, and the positions its position-wise halves compute
    (`prefill_walked_tokens`)."""
    band, _ = flash_attention.band_work(cfg.window, true_lens, bucket)
    work, _ = flash_attention.prefill_work(true_lens, bucket)
    work.update({k: band[k] for k in flash_attention.BAND_COUNTERS})
    walked, shown = live_rows.prefill_work(true_lens, bucket)
    return {**work, **walked}, shown


def serving_spec(cfg: Cohere2MoeConfig) -> ServingSpec:
    """No optional capability.  A window layer keeps a K and a V ring a
    lane, filled from a prefill row's last positions: the bytes of the
    rings ONE prefill row hands the scatter."""
    n_win = cfg.count(WINDOW)
    return ServingSpec(
        lane_state_layers=n_win,
        prefill_state_bytes=(n_win * cfg.ring_rows * cfg.n_kv_heads * 2
                             * cfg.head_dim
                             * jnp.dtype(cfg.dtype).itemsize),
        prefill_params=prefill_params(cfg),
        routed_layers=cfg.n_layers,
        counters={**flash_attention.PREFILL_COUNTERS,
                  **flash_attention.BAND_COUNTERS, **live_rows.COUNTERS,
                  **swa.COUNTERS, **swa.BLOCK_COUNTERS, **routed.COUNTERS},
        decode_work=functools.partial(_decode_work, cfg),
        prefill_work=functools.partial(_prefill_work, cfg),
        routed_work=functools.partial(routed.routed_work, cfg,
                                      cfg.experts_held))


# ---------------------------------------------------------------- params
def init_params(key: jax.Array, cfg: Cohere2MoeConfig) -> dict:
    """Every weight from one key: matrices normal, fan-in scaled, in the
    serving dtype; norm weights 1; the experts of `experts_held` only;
    the embedding (the tied head) at dim**-0.5, so that a random model's
    logits are its layers' and not its input's; W_q and W_k as drawn with
    every head's columns permuted, the shared experts side by side (the
    module's docstring)."""
    d, f, S = cfg.dim, cfg.moe_ffn_dim, cfg.n_shared
    H, G, dk = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    held = cfg.experts_held[1] - cfg.experts_held[0]
    keys = iter(jax.random.split(key, 1 + 10 * cfg.n_layers))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                * fan_in ** -0.5).astype(cfg.dtype)

    layers = []
    for _ in cfg.layer_types:
        layers.append({
            "norm": jnp.ones((d,), cfg.dtype),
            "wq": w((d, H * dk), d)[:, half_from_interleaved(dk, H)],
            "wk": w((d, G * dk), d)[:, half_from_interleaved(dk, G)],
            "wv": w((d, G * dk), d), "wo": w((H * dk, d), H * dk),
            "router": w((d, cfg.n_experts), d),
            "w13": w((held, d, 2 * f), d), "w2": w((held, f, d), f),
            "sw1": w((d, S * f), d), "sw3": w((d, S * f), d),
            "sw2": (w((S * f, d), f).astype(F32) / S).astype(cfg.dtype)})
    return {"embed": w((cfg.vocab_size, d), d), "layers": layers,
            "final_norm": jnp.ones((d,), cfg.dtype)}


def norm(x, weight, cfg: Cohere2MoeConfig):
    """The block's ONE norm, and the final one (assumed: LayerNorm with
    a weight and no bias)."""
    return layernorm(x, weight, None, cfg.norm_eps)


def final_hidden(x, params: dict, cfg: Cohere2MoeConfig):
    """What the head is handed: the final norm, times `logit_scale` (the
    seam's head has no config)."""
    x = norm(x, params["final_norm"], cfg)
    return x if cfg.logit_scale == 1.0 else (
        x.astype(F32) * cfg.logit_scale).astype(x.dtype)


def project_logits(params: dict, h: jnp.ndarray) -> jnp.ndarray:
    """The head: the embedding, transposed (tied)."""
    with jax.named_scope("lm_head"):
        return lax.dot_general(h, params["embed"],
                               (((h.ndim - 1,), (1,)), ((), ())))


# ------------------------------------------------------------ feed-forward
def routed_ffn(u2, lp, cfg: Cohere2MoeConfig, live=None):
    """`routed.routed_ffn` for the experts this chip holds, under THIS
    module's `route`."""
    return routed.routed_ffn(u2, lp, cfg, live, cfg.experts_held,
                             route_fn=route)


def shared_experts(u, lp, cfg: Cohere2MoeConfig):
    """The mean of the shared experts' outputs for rows u [..., d]: one
    SwiGLU over them side by side, the division in `sw2`."""
    return routed.shared_ffn(u, lp, cfg.dtype)


def ffn(u, lp, cfg: Cohere2MoeConfig, live=None):
    """The block's feed-forward half from the normed rows u [..., d]:
    (routed + shared, the routed counts).  Decode, and the benchmark's
    judge; a prefill program computes the shared part inside its walk
    (`out_rows`)."""
    y, counts = routed_ffn(u.reshape(-1, cfg.dim), lp, cfg,
                           None if live is None else live.reshape(-1))
    return y.reshape(u.shape) + shared_experts(u, lp, cfg), counts


# --------------------------------------------------------------- attention
def rope(x, kind: str, cfg: Cohere2MoeConfig, positions, n_pos: int,
         first=None):
    """The rotary of x [b, T, heads, dk] whose columns are permuted
    (evens, then odds): a window layer's, over the whole head; a global
    layer turns nothing (assumed: NoPE).  positions [b, T] or None
    (`first` .. `first` + T - 1; 0 .. T - 1 without one), `n_pos` the
    positions the tables cover."""
    if kind == GLOBAL:
        return x
    tables = rope_frequencies(cfg.head_dim, n_pos, cfg.rope_theta)
    if first is not None:
        tables = tuple(lax.dynamic_slice_in_dim(t, first, x.shape[1])
                       for t in tables)
    return apply_rope(x, *tables, positions=positions)


def qkv(u, lp, kind: str, cfg: Cohere2MoeConfig, positions, n_pos: int,
        first=None):
    """u [b, T, d] normed -> (q [b, T, H, dk], k [b, T, G, dk], both
    turned in a window layer; v [b, T, G, dk])."""
    b, T, _ = u.shape
    H, G, dk = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        q = rope((u @ lp["wq"]).reshape(b, T, H, dk), kind, cfg, positions,
                 n_pos, first)
        k = rope((u @ lp["wk"]).reshape(b, T, G, dk), kind, cfg, positions,
                 n_pos, first)
        v = (u @ lp["wv"]).reshape(b, T, G, dk)
    return q, k.astype(cfg.dtype), v.astype(cfg.dtype)


def attn_out(o, lp):
    """The output projection: o [..., H, dk] the heads' outputs -> [...,
    d]."""
    with jax.named_scope("attn_out"):
        return o.reshape(*o.shape[:-2], -1) @ lp["wo"]


def _scale(cfg: Cohere2MoeConfig) -> float:
    return cfg.head_dim ** -0.5


def attn_rows(x, lp, kind: str, cfg: Cohere2MoeConfig, true_lens):
    """The block's norm and its attention over whole rows x [b, T, d]:
    (u [b, T, d], the heads' outputs o [b, T, H, dk], what the layer
    keeps: a global layer's (K rows, V rows) [b, T, G, dk], a window
    layer's (K ring, V ring) [b, G, ring_rows, dk] at each row's TRUE
    length).  What is computed a position alone stops at the longest
    true length (`live_rows.walk`)."""
    n_pos = x.shape[1]

    def rows(x, first):
        u = norm(x, lp["norm"], cfg)
        return (u,) + qkv(u, lp, kind, cfg, None, n_pos, first)

    u, q, k, v = live_rows.walk(rows, x, jnp.max(true_lens))
    if kind == GLOBAL:
        with jax.named_scope("attn_global"):
            o = attention(q, k, v, sm_scale=_scale(cfg), lengths=true_lens)
        return u, o, (k, v)
    with jax.named_scope("attn_window"):
        o = attention(q, k, v, sm_scale=_scale(cfg), lengths=true_lens,
                      window=cfg.window, band_name="swa_band")
    with jax.named_scope("ring_write"):
        rings = tuple(swa.kv_ring_from_rows(a, true_lens, cfg.ring_rows)
                      for a in (k, v))
    return u, o, rings


def out_rows(o, u, lp, cfg: Cohere2MoeConfig, n_live):
    """What a prefill layer adds a position alone, over whole rows up to
    position `n_live`: the output projection of the heads' o [b, T, H,
    dk] and the shared experts of u [b, T, d]."""
    return live_rows.walk(
        lambda ou, _first: attn_out(ou[0], lp)
        + shared_experts(ou[1], lp, cfg), (o, u), n_live)


def global_decode(u, lp, k_pages, v_pages, k_tail, v_tail, page_table, pos,
                  tail_start, j, cfg: Cohere2MoeConfig,
                  plan: dict | None = None):
    """One token of a global layer's attention for every lane: u [B, d]
    normed; the layer's two pool leaves (read-only) and their tails (the
    new rows land at column j).  Returns (what it adds, K tail, V
    tail)."""
    B = u.shape[0]
    G = cfg.n_kv_heads
    max_len = page_table.shape[1] * k_pages.shape[2]
    q, k, v = qkv(u[:, None], lp, GLOBAL, cfg, pos[:, None], max_len)
    with jax.named_scope("kv_write"):
        k_tail = lax.dynamic_update_slice(
            k_tail, k[:, 0, :, None, :], (0, 0, j, 0))
        v_tail = lax.dynamic_update_slice(
            v_tail, v[:, 0, :, None, :], (0, 0, j, 0))
    qg = q[:, 0].reshape(B, G, cfg.n_heads // G, cfg.head_dim)
    with jax.named_scope("attn_global"):
        o = paged_decode_attention(
            qg.astype(cfg.dtype), k_pages, v_pages, k_tail, v_tail,
            page_table, pos, tail_start, plan=plan, sm_scale=_scale(cfg))
    return (attn_out(o.reshape(B, cfg.n_heads, cfg.head_dim), lp), k_tail,
            v_tail)


def window_decode(u, lp, k_ring, v_ring, pos, max_len: int, listed, lanes,
                  count, cfg: Cohere2MoeConfig, bias=None,
                  plan: dict | None = None):
    """One token of a window layer's attention for every lane: u [B, d]
    normed; k_ring, v_ring [B, G, ring_rows, dk] the lanes' rings of this
    layer (the token's rows are written at slot pos mod ring_rows, in
    place); max_len the positions a lane can reach; listed [B] the lanes
    that hold a request (lanes, count: their work list); bias, plan: the
    step's `ring_bias` and `ring_plan`, which the window layers share
    (made here if not given).  Returns (what it adds, K ring, V ring)."""
    B = u.shape[0]
    G = cfg.n_kv_heads
    q, k, v = qkv(u[:, None], lp, WINDOW, cfg, pos[:, None], max_len)
    with jax.named_scope("ring_write"):
        k_ring = swa.kv_ring_write(k_ring, k[:, 0], pos, listed)
        v_ring = swa.kv_ring_write(v_ring, v[:, 0], pos, listed)
    qg = q[:, 0].reshape(B, G, cfg.n_heads // G, cfg.head_dim)
    with jax.named_scope("attn_window"):
        if bias is None:
            bias = swa.ring_bias(pos, cfg.ring_rows, cfg.window)
        o = swa.kv_ring_attention(
            qg.astype(cfg.dtype), k_ring, v_ring, bias, None, lanes, count,
            sm_scale=_scale(cfg), plan=plan)
    return (attn_out(o.reshape(B, cfg.n_heads, cfg.head_dim), lp), k_ring,
            v_ring)


# ---------------------------------------------------------------- prefill
def layer_prefill(params, x, lid: int, cfg: Cohere2MoeConfig, true_lens):
    """Layer `lid` over whole rows x [b, T, d]: (x after it, what its
    attention hands the pool or the lane, the routed counts).  ONE norm;
    attention, the routed experts and the shared ones all read it, and
    what they give is added to x together.  The prefill program's body;
    the benchmark's judge calls it a layer at a time."""
    lp = params["layers"][lid]
    live = jnp.arange(x.shape[1])[None, :] < true_lens[:, None]
    u, o, kept = attn_rows(x, lp, cfg.layer_types[lid], cfg, true_lens)
    y, cnt = routed_ffn(u.reshape(-1, cfg.dim), lp, cfg, live.reshape(-1))
    return (x + out_rows(o, u, lp, cfg, jnp.max(true_lens))
            + y.reshape(x.shape), kept, cnt)


def prefill(params: dict, tokens: jnp.ndarray, cfg: Cohere2MoeConfig,
            true_lens: jnp.ndarray | None = None, lora=None):
    """Prompt pass.  tokens [b, T], right-padded; true_lens [b] (absent:
    every row is T long).  Returns the seam's (hidden [b, T, d] after the
    final norm; the K rows, a global layer [b, T, G, dk]; the V rows;
    state: {"window_k", "window_v": a window layer each [b, G, ring_rows,
    dk]}, every row's at its TRUE length; counts int32 [layers, 5])."""
    b, T = tokens.shape
    if true_lens is None:
        true_lens = jnp.full((b,), T, jnp.int32)
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens, cfg.dtype)
    ks, vs, ring_k, ring_v, counts = [], [], [], [], []
    for lid, kind in enumerate(cfg.layer_types):
        x, kept, cnt = layer_prefill(params, x, lid, cfg, true_lens)
        (ks if kind == GLOBAL else ring_k).append(kept[0])
        (vs if kind == GLOBAL else ring_v).append(kept[1])
        counts.append(cnt)
    return (final_hidden(x, params, cfg), ks, vs,
            {"window_k": ring_k, "window_v": ring_v},
            routed.stack_counts(counts))


# ------------------------------------------------------------ paged cache
def init_paged_cache(cfg: Cohere2MoeConfig, batch: int, n_pages: int,
                     page: int) -> dict:
    """A K and a V pool leaf a GLOBAL layer, a row a token each:
    [n_pages, G, page, dk]; a window layer holds no page: its lanes'
    rings are the `state` (the module's docstring)."""
    if cfg.ring_rows < cfg.window:
        raise ValueError(f"ring_rows {cfg.ring_rows} under the window "
                         f"{cfg.window}")
    swa.ring_blocks(cfg.ring_rows)          # whole blocks, or it raises
    G, dk = cfg.n_kv_heads, cfg.head_dim

    def leaves(n, shape):
        return [jnp.zeros(shape, cfg.dtype) for _ in range(n)]

    pages = (n_pages, G, page, dk)
    rings = (batch, G, cfg.ring_rows, dk)
    return {"k": leaves(cfg.count(GLOBAL), pages),
            "v": leaves(cfg.count(GLOBAL), pages),
            "pos": jnp.zeros((batch,), jnp.int32),
            "state": {"window_k": leaves(cfg.count(WINDOW), rings),
                      "window_v": leaves(cfg.count(WINDOW), rings)}}


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
                      cfg: Cohere2MoeConfig, lora=None, plan=None):
    """One decode step over both pool leaves, their in-block tails and
    the lanes' rings.  A lane whose table row starts at the trash page
    holds no request: it attends nothing, is routed nowhere and its rings
    are not touched.  `plan` (the paged kernel's work list of pages) is
    the global layers'; the window layers share the step's ring bias and
    its work list of ring blocks.  Returns (logits [B, vocab] float32,
    tails, state, counts int32 [layers, 5])."""
    live = lanes_live(page_table)
    lanes, count = ssm.live_lanes(live)
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens, cfg.dtype)      # [B, d]
    k_t, v_t = list(tails["k"]), list(tails["v"])
    ring_k, ring_v = list(state["window_k"]), list(state["window_v"])
    max_len = page_table.shape[1] * pages["k"][0].shape[2]
    with jax.named_scope("attn_window"):
        bias = swa.ring_bias(pos, cfg.ring_rows, cfg.window)
        ring_plan = swa.ring_plan(bias, lanes, count)
    counts = []
    for lid, kind in enumerate(cfg.layer_types):
        lp = params["layers"][lid]
        i = cfg.before(lid)
        u = norm(x, lp["norm"], cfg)
        if kind == GLOBAL:
            a, k_t[i], v_t[i] = global_decode(
                u, lp, pages["k"][i], pages["v"][i], k_t[i], v_t[i],
                page_table, pos, tail_start, j, cfg, plan=plan)
        else:
            a, ring_k[i], ring_v[i] = window_decode(
                u, lp, ring_k[i], ring_v[i], pos, max_len, live, lanes,
                count, cfg, bias=bias, plan=ring_plan)
        y, cnt = ffn(u, lp, cfg, live)
        x = x + a + y
        counts.append(cnt)
    logits = project_logits(params, final_hidden(x, params, cfg))
    return (logits.astype(F32), {"k": k_t, "v": v_t},
            {"window_k": ring_k, "window_v": ring_v},
            routed.stack_counts(counts))


# the serving seam's names (models/serving.py)
serve_prefill = prefill
serve_scatter = scatter_prefill_pages
serve_decode_step = decode_step_paged
