"""Llama-3-family decoder, TPU-first.

Design (vs the reference's torch models, which Ray never owns — model code
arrives via user libraries; this framework ships its own):
  - pure functional: params are a pytree of jnp arrays; `forward` is a free
    function, jit/pjit/shard_map compose directly
  - layers are *stacked* on a leading [n_layers, ...] axis and driven by
    `lax.scan` — one compiled layer body regardless of depth (compile time
    and HBM code size stay flat at 70B scale)
  - `jax.checkpoint` on the scanned body: activations rematerialized in
    backward (HBM-bandwidth trade per the TPU guide)
  - logical-axis metadata per param feeds ray_tpu.parallel.sharding: the
    same model runs pure-DP, ZeRO-3 ("fsdp"), Megatron-TP ("tensor"),
    sequence-parallel ("seq"), or any mix, by choosing a mesh
  - bf16 params/activations, fp32 for softmax/norm/logits/loss
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.serving import ServingSpec
from ray_tpu.ops.attention import attention
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.parallel.sharding import with_sharding_constraint

# Names on the device side: every part of the decoder runs under a
# `jax.named_scope` (embed, layer_weights, attn_qkv, rope, attn,
# attn_out, mlp, norm, lm_head, kv_write), so XProf and the benchmark's
# --dump-trace show which part an op belongs to.  Scopes change op
# metadata only, never the compiled program.  `layer_weights` (a layer's
# slices of the stacked arrays) must hold no device time: each slice is a
# view read inside its matmul.  It held 2.1 ms of an 18.5 ms step while
# the decode step reshaped the q/k/v products into heads (`_decode_qkv`
# says why; PERF.md section 6, PR 29).
rmsnorm = jax.named_call(rmsnorm, name="norm")
apply_rope = jax.named_call(apply_rope, name="rope")
attention = jax.named_call(attention, name="attn")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "flash_resid": recompute everything except the flash kernel's
    # (o, lse) residuals and the attention block's output — ~1.8x faster
    # backward, and `o @ wo` with its collectives is not rebuilt; costs
    # (o + lse + a residual-wide row) per layer in HBM.  "nothing": full
    # recompute (the old profile) for models at the HBM ceiling.
    remat_mode: str = "flash_resid"
    use_ring_attention: bool = False   # set when mesh has a "seq" axis > 1

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def flops_per_token(self) -> float:
        """Approximate training FLOPs per token (fwd+bwd ≈ 6N + attention)."""
        n_params = self.num_params()
        attn = 12 * self.n_layers * self.dim * self.max_seq  # rough
        return 6.0 * n_params + attn

    def num_params(self) -> int:
        d, f, v = self.dim, self.ffn_dim, self.vocab_size
        per_layer = (d * self.n_heads * self.head_dim        # wq
                     + 2 * d * self.n_kv_heads * self.head_dim  # wk, wv
                     + self.n_heads * self.head_dim * d      # wo
                     + 3 * d * f                             # gate, up, down
                     + 2 * d)                                # norms
        return v * d * 2 + self.n_layers * per_layer + d


def llama_configs() -> dict[str, LlamaConfig]:
    """Preset family (Llama-3 shapes + scaled-down bench/debug configs)."""
    return {
        "llama3-8b": LlamaConfig(),
        "llama3-70b": LlamaConfig(dim=8192, n_layers=80, n_heads=64,
                                  n_kv_heads=8, ffn_dim=28672),
        "llama3-1b": LlamaConfig(dim=2048, n_layers=16, n_heads=32,
                                 n_kv_heads=8, ffn_dim=8192,
                                 vocab_size=128256),
        # bench config: fits one v5e chip (16GB HBM) with optimizer state.
        # head_dim 128 (not 64) so the Pallas flash kernel's MXU-tile gate
        # accepts it — fwd AND the remat recompute run the kernel instead
        # of materializing [s,s] scores.  remat stays on: at batch 8 ×
        # seq 2048 the fp32 MLP activations alone are ~6 GB/layer-group
        # without it.
        "bench-350m": LlamaConfig(dim=1024, n_layers=24, n_heads=8,
                                  n_kv_heads=4, ffn_dim=4096,
                                  vocab_size=32768, max_seq=2048),
        "debug": LlamaConfig(dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                             ffn_dim=256, vocab_size=256, max_seq=128,
                             remat=False),
    }


serving_configs = llama_configs      # the serving seam's presets


# ---------------------------------------------------------------- params
def param_logical_axes(cfg: LlamaConfig) -> dict:
    """Logical-axes pytree matching init_params' structure (consumed by
    parallel.sharding.param_shardings)."""
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "heads"),
            "wv": ("layers", "embed", "heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", None),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


def init_params(key: jax.Array, cfg: LlamaConfig) -> dict:
    d, hd = cfg.dim, cfg.head_dim
    L = cfg.n_layers
    keys = jax.random.split(key, 8)

    def norm_init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.dtype)

    return {
        "embed": norm_init(keys[0], (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": jnp.ones((L, d), cfg.dtype),
            "wq": norm_init(keys[1], (L, d, cfg.n_heads * hd), d),
            "wk": norm_init(keys[2], (L, d, cfg.n_kv_heads * hd), d),
            "wv": norm_init(keys[3], (L, d, cfg.n_kv_heads * hd), d),
            "wo": norm_init(keys[4], (L, cfg.n_heads * hd, d),
                            cfg.n_heads * hd),
            "mlp_norm": jnp.ones((L, d), cfg.dtype),
            "w_gate": norm_init(keys[5], (L, d, cfg.ffn_dim), d),
            "w_up": norm_init(keys[6], (L, d, cfg.ffn_dim), d),
            "w_down": norm_init(keys[7], (L, cfg.ffn_dim, d), cfg.ffn_dim),
        },
        "final_norm": jnp.ones((d,), cfg.dtype),
        "lm_head": norm_init(keys[0], (d, cfg.vocab_size), d),
    }


def remat_policy(cfg: "LlamaConfig | None" = None):
    """Rematerialization policy per cfg.remat_mode.

    "flash_resid" (default): recompute everything EXCEPT the
    flash-attention kernel's residuals (output + log-sum-exp, named in
    ops/flash_attention._flash_vjp_fwd) and the attention block's
    output `x + o @ wo` (named in _attention_block).  Attention
    dominates the step at these shapes, and nothing_saveable re-runs the
    forward kernel inside the backward just to rebuild (o, lse) — saving
    them took bench-350m from 814ms to 449ms per step (MFU 0.335 ->
    0.61) on v5e.  Costs (o + lse) per layer in HBM: b*s*(h*d*2 + h*4)
    bytes — ~36 MB/layer at b8 x s2048 x h8 x d128.  The block's output
    is where the MLP's recompute starts: kept, the backward rebuilds
    q, k and v for the kernels but not `o @ wo`, its partial sums over
    "tensor" nor wo's gather over "fsdp" (one product, one all-reduce
    and two all-gathers a layer fewer; +2.2 % tokens/s on mistral-7b,
    fsdp=2 x tensor=2, v5e).  Costs b*s*D*2 bytes a layer: 1.33 GB a
    chip over 20 layers at 2 x 4,096 positions a chip and a 4,096-wide
    residual in bfloat16 (the compiler's temporaries 6.93 -> 8.26 GB).
    q, k and v are NOT kept: beside the block's output they cost
    0.79 GB more (16.0 of a v5e's 16.9 GB there) and ran no faster on
    the chip — what the backward saves the forward spends stacking
    them (PERF.md section 6, PR 59).  When the XLA fallback runs (no
    flash names), only the block's output is kept.

    "nothing": full recompute — the minimal-HBM profile for models at
    the memory ceiling.

    "dots": save all non-batch matmul outputs (qkv/o/mlp projections) —
    the maximal-HBM profile; backward recomputes only elementwise ops.

    "flash_dots": dots PLUS the flash residuals — without the flash
    names the backward re-runs the attention kernel just to rebuild
    (o, lse) even though every projection around it was saved."""
    mode = cfg.remat_mode if cfg is not None else "flash_resid"
    if mode == "nothing":
        return jax.checkpoint_policies.nothing_saveable
    if mode == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if mode == "flash_dots":
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                "flash_o", "flash_lse"))
    if mode != "flash_resid":
        raise ValueError(
            f"unknown remat_mode {mode!r}; valid: 'flash_resid', "
            "'nothing', 'dots', 'flash_dots'")
    return jax.checkpoint_policies.save_only_these_names(
        "flash_o", "flash_lse", "attn_block_out")


# --------------------------------------------------------------- forward
def _attention_block(x, lp, cfg: LlamaConfig, cos, sin):
    b, s, d = x.shape
    h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope("attn_qkv"):
        q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if cfg.use_ring_attention:
        from ray_tpu.parallel.ring import ring_attention_gspmd

        o = ring_attention_gspmd(q, k, v, seq_axis="seq")
    else:
        o = attention(q, k, v, causal=True)
    o = o.reshape(b, s, cfg.n_heads * cfg.head_dim)
    with jax.named_scope("attn_out"):
        # named for remat_policy: the MLP's recompute starts from it
        return checkpoint_name(x + (o @ lp["wo"]), "attn_block_out")


@functools.partial(jax.named_call, name="mlp")
def _mlp_block(x, lp, cfg: LlamaConfig):
    h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    gate = h @ lp["w_gate"]
    up = h @ lp["w_up"]
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    h = with_sharding_constraint(h, ("batch", "seq", "mlp"))
    return x + (h @ lp["w_down"])


def dense_layer(x, lp, cfg: LlamaConfig, cos, sin):
    """One dense decoder layer (attention + MLP) — the SINGLE definition
    shared by forward() and pipelined_loss_fn so the two trunks cannot
    diverge."""
    return _mlp_block(_attention_block(x, lp, cfg, cos, sin), lp, cfg)


def head_loss(params: dict, x: jnp.ndarray, targets: jnp.ndarray,
              mask, cfg: LlamaConfig) -> jnp.ndarray:
    """Shared trunk tail: final norm → lm_head (fp32) → cross entropy."""
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        logits = (x @ params["lm_head"]).astype(jnp.float32)
    return cross_entropy(logits, targets, mask)


@functools.partial(jax.named_call, name="embed")
def embed_lookup(table: jnp.ndarray, tokens: jnp.ndarray,
                 dtype) -> jnp.ndarray:
    """Token-embedding lookup that stays efficient under a vocab-sharded
    table.  A plain gather over a "tensor"-sharded vocab axis makes the
    GSPMD partitioner all-gather the table, fully replicate the result,
    and reshard ("[SPMD] Involuntary full rematerialization" in the
    multichip dryrun).  With vocab sharded we contract a one-hot matrix
    against the table instead: the matmul rides the MXU, every device
    touches only its vocab shard, and XLA inserts one psum over the
    tensor axis (the iota-embed trick of public TPU LLM codebases)."""
    from ray_tpu.parallel.sharding import logical_axis_size

    if logical_axis_size("vocab") > 1:
        one_hot = jax.nn.one_hot(tokens, table.shape[0], dtype=table.dtype)
        return jnp.einsum("bsv,vd->bsd", one_hot, table,
                          preferred_element_type=jnp.float32).astype(dtype)
    return table[tokens].astype(dtype)


def run_trunk(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
              layer_fn) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Shared decoder trunk: embed → scanned (remat) layers → final norm →
    lm_head.  `layer_fn(x, lp, cos, sin, aux) -> (x, aux)` lets variants
    (e.g. models.moe's routed FFN) swap the layer body without
    re-implementing the scaffold.  Returns (logits fp32, aux)."""
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    x = with_sharding_constraint(x, ("batch", "seq", None))
    cos, sin = rope_frequencies(cfg.head_dim, s, cfg.rope_theta)

    def layer(carry, lp):
        x, aux = carry
        x, aux = layer_fn(x, lp, cos, sin, aux)
        x = with_sharding_constraint(x, ("batch", "seq", None))
        return (x, aux), None

    body = layer
    if cfg.remat:
        body = jax.checkpoint(layer, policy=remat_policy(cfg))
    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                           params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        logits = (x @ params["lm_head"]).astype(jnp.float32)
    return with_sharding_constraint(logits, ("batch", "seq", "vocab")), aux


def forward(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
            ) -> jnp.ndarray:
    """tokens [b, s] int32 → logits [b, s, vocab] float32."""
    def layer_fn(x, lp, cos, sin, aux):
        return dense_layer(x, lp, cfg, cos, sin), aux

    logits, _ = run_trunk(params, tokens, cfg, layer_fn)
    return logits


def split_batch(batch: dict) -> tuple[jnp.ndarray, jnp.ndarray]:
    """{"tokens": [b, s+1]} or {"inputs", "targets"} → (inputs, targets)."""
    if "inputs" in batch:
        return batch["inputs"], batch["targets"]
    return batch["tokens"][:, :-1], batch["tokens"][:, 1:]


# Shared across model families; re-exported here for API stability.
from ray_tpu.ops.losses import cross_entropy  # noqa: E402,F401


def loss_fn(params: dict, batch: dict, cfg: LlamaConfig) -> jnp.ndarray:
    """Next-token cross entropy; batch = {"tokens": [b, s+1] int32} or
    {"inputs", "targets"}."""
    inputs, targets = split_batch(batch)
    logits = forward(params, inputs, cfg)
    return cross_entropy(logits, targets, batch.get("mask"))


def token_logprobs(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
                   ) -> jnp.ndarray:
    """Per-token log-probability scoring path (the RLHF trajectory
    scorer): out[b, t] = log p(tokens[b, t+1] | tokens[b, :t+1]) for
    t in [0, s-2] — one teacher-forced forward, fp32 log-softmax
    (sampling-scale logits overflow bf16 sums), shape [b, s-1].

    Positions past a sequence's true length score garbage (padding
    attends causally like any token) — callers mask, exactly like
    cross_entropy's mask contract.  The serve engine's decode samples
    from these same logits, so scoring a generated completion under the
    generating params reproduces the behavior policy's logprobs."""
    logits = forward(params, tokens[:, :-1], cfg)        # [b, s-1, v] f32
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(
        logp, tokens[:, 1:, None].astype(jnp.int32), axis=-1)[..., 0]


def pipelined_loss_fn(params: dict, batch: dict, cfg: LlamaConfig,
                      mesh, n_micro: int | None = None) -> jnp.ndarray:
    """loss_fn with the decoder trunk pipelined over the mesh's "stage"
    axis (GPipe microbatching via parallel.pipeline.pipeline_apply).

    The stacked [L, ...] layer params reshape to [n_stages, L/S, ...];
    with the "layers" logical axis mapped to "stage" in the sharding
    rules (train.step activates this automatically on stage-bearing
    meshes) each stage holds exactly its contiguous layer block, so the
    reshape moves no data.  Embed and lm_head/loss run outside the
    pipeline (replicated over the stage axis, batch-parallel as usual).
    Inside the pipeline only "stage" is manual (pipeline_apply); the
    microbatch dim stays data-parallel and per-stage params stay
    fsdp/tensor-sharded under plain GSPMD — PP composes with dp, fsdp
    and tp as pure layout."""
    from ray_tpu.parallel.pipeline import pipeline_apply
    from ray_tpu.parallel.sharding import logical_axis_size

    n_stages = mesh.shape["stage"]
    L = cfg.n_layers
    if L % n_stages:
        raise ValueError(f"n_layers {L} not divisible by stage={n_stages}")
    inputs, targets = split_batch(batch)
    b, s = inputs.shape
    n_micro = n_micro or max(2, n_stages)
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by n_micro={n_micro}")
    batch_shards = logical_axis_size("batch", mesh)
    if (b // n_micro) % batch_shards:
        raise ValueError(
            f"microbatch size {b // n_micro} not divisible by the batch "
            f"sharding (data x fsdp = {batch_shards}); choose n_micro so "
            "that batch / n_micro % (data * fsdp) == 0")
    x = embed_lookup(params["embed"], inputs, cfg.dtype)
    x = with_sharding_constraint(x, ("batch", "seq", None), mesh)
    # Row r -> (microbatch r % n_micro, slot r // n_micro): the INTERLEAVED
    # assignment, not the block-contiguous one.  With the flat batch dim
    # contiguously sharded over data x fsdp, splitting it micro-major
    # ([n_micro, b/n_micro]) would need a strided device layout on the mb
    # dim that GSPMD cannot express — it replicates + repartitions instead
    # ("[SPMD] Involuntary full rematerialization", fwd and again in the
    # grad transpose).  Splitting slot-major then swapping axes keeps each
    # device's rows in place: [b] -> [b/n_micro, n_micro] is a contiguous
    # split of the sharded dim, and the swap only relabels dims.  Which
    # rows share a microbatch is semantically irrelevant (the pipeline is
    # row-wise; the inverse swap below restores row order for the loss).
    mb = x.reshape(b // n_micro, n_micro, s, x.shape[-1]).swapaxes(0, 1)
    mb = with_sharding_constraint(mb, (None, "batch", "seq", None), mesh)
    stage_layers = jax.tree.map(
        lambda p: p.reshape(n_stages, L // n_stages, *p.shape[1:]),
        params["layers"])

    def stage_fn(lp_stage, act):
        # rope tables fold to constants (static shapes); recomputed per
        # stage rather than closed over (shard_map closure discipline).
        cos, sin = rope_frequencies(cfg.head_dim, s, cfg.rope_theta)

        def one(carry, lp):
            return dense_layer(carry, lp, cfg, cos, sin), None

        body = one
        if cfg.remat:
            body = jax.checkpoint(one, policy=remat_policy(cfg))
        act, _ = lax.scan(body, act, lp_stage)
        return act

    out = pipeline_apply(stage_fn, stage_layers, mb, mesh, axis="stage")
    x = out.swapaxes(0, 1).reshape(b, s, x.shape[-1])
    x = with_sharding_constraint(x, ("batch", "seq", None), mesh)
    return head_loss(params, x, targets, batch.get("mask"), cfg)


# ------------------------------------------------------------------ lora
# Batched multi-LoRA (S-LoRA/Punica style): adapters live in per-target
# BANKS — stacked [L, n_slots, din, r] / [L, n_slots, r, dout] arrays —
# and a per-request int32 index row-gathers each request's slot inside
# ONE jitted program (BGMV).  The banks are jit ARGUMENTS, never closure
# constants: loading an adapter swaps arrays without a retrace (static
# rank bucket per the XLA invariants).  Slot 0 is all-zeros = the base
# model (y + 0.0 == y), so a batch freely mixes adapter and base rows.
LORA_TARGETS = ("wq", "wk", "wv", "wo")


def lora_target_dims(cfg: LlamaConfig) -> dict[str, tuple[int, int]]:
    """(din, dout) per LoRA-targetable projection — the shape contract
    init_lora_adapter, the engine's bank validation, and merge_lora all
    share."""
    hd = cfg.head_dim
    return {
        "wq": (cfg.dim, cfg.n_heads * hd),
        "wk": (cfg.dim, cfg.n_kv_heads * hd),
        "wv": (cfg.dim, cfg.n_kv_heads * hd),
        "wo": (cfg.n_heads * hd, cfg.dim),
    }


def init_lora_adapter(key: jax.Array, cfg: LlamaConfig, rank: int, *,
                      targets: tuple | None = None,
                      scale: float = 1.0) -> dict:
    """Random adapter {"rank", "targets": {t: {"a": [L, din, r],
    "b": [L, r, dout]}}}.  The LoRA scale is folded into b at init (the
    serving path never multiplies by alpha/r at decode time); b is
    random — a zero-init b (the training convention) would make every
    synthetic adapter a no-op."""
    if rank < 1:
        raise ValueError(f"lora rank must be >= 1, got {rank}")
    dims = lora_target_dims(cfg)
    targets = tuple(targets) if targets is not None else LORA_TARGETS
    bad = set(targets) - set(dims)
    if bad:
        raise ValueError(f"unknown lora targets {sorted(bad)}; valid: "
                         f"{sorted(dims)}")
    L = cfg.n_layers
    out = {}
    for t in targets:
        din, dout = dims[t]
        key, ka, kb = jax.random.split(key, 3)
        out[t] = {
            "a": (jax.random.normal(ka, (L, din, rank), jnp.float32)
                  * (din ** -0.5)).astype(cfg.dtype),
            "b": (jax.random.normal(kb, (L, rank, dout), jnp.float32)
                  * (rank ** -0.5) * scale).astype(cfg.dtype),
        }
    return {"rank": int(rank), "targets": out}


def merge_lora(params: dict, adapter: dict, cfg: LlamaConfig) -> dict:
    """Dense-merge an adapter into a copy of params (W + A @ B, fp32
    accumulate) — the reference arm the token-identity tests compare
    the batched engine against."""
    layers = dict(params["layers"])
    for t, ab in adapter["targets"].items():
        w = layers[t]
        delta = jnp.einsum("ldr,lro->ldo",
                           jnp.asarray(ab["a"]).astype(jnp.float32),
                           jnp.asarray(ab["b"]).astype(jnp.float32))
        layers[t] = (w.astype(jnp.float32) + delta).astype(w.dtype)
    return {**params, "layers": layers}


def _lora_proj(h, w, bank, idx):
    """h @ w plus the per-request low-rank delta (h @ A[idx]) @ B[idx].

    bank: {"a": [n_slots, din, r], "b": [n_slots, r, dout]} — ONE
    layer's slice of the engine bank — or None (plain projection).
    idx: [b] int32 adapter slots.  The delta accumulates in fp32 and
    casts once; slot 0's all-zero rows contribute an exact 0.0."""
    y = h @ w
    if bank is None:
        return y
    a = bank["a"][idx]                                  # [b, din, r]
    bb = bank["b"][idx]                                 # [b, r, dout]
    t = jnp.einsum("b...d,bdr->b...r", h, a,
                   preferred_element_type=jnp.float32)
    d = jnp.einsum("b...r,bro->b...o", t, bb.astype(jnp.float32))
    return y + d.astype(y.dtype)


def _lora_layer_slice(lora, lid):
    """Per-layer bank views for the unrolled decode/suffix paths."""
    if not lora:
        return None, None
    return (jax.tree.map(lambda a: a[lid], lora["banks"]),
            lora["idx"])


def _decode_qkv(h, lp, cfg: LlamaConfig, lb=None, idx=None):
    """One decode token's q, k, v as heads ([b, 1, heads, head_dim]).

    The three products are held FLAT behind an optimization barrier, so
    the split into heads is a view of the [b, 1, heads*head_dim]
    activations.  A reshape that touches the dot is folded into it: XLA
    then wants wq/wk/wv as [heads, head_dim, in] and transposes all
    three, every layer, on every step (768 MB a step at Mistral-7B-d16;
    PERF.md section 6, PR 29; tests/test_chip_compile.py guards it)."""
    b = h.shape[0]
    lb = lb or {}
    q, k, v = lax.optimization_barrier(tuple(
        _lora_proj(h, lp[t], lb.get(t), idx) for t in ("wq", "wk", "wv")))
    return (q.reshape(b, 1, cfg.n_heads, cfg.head_dim),
            k.reshape(b, 1, cfg.n_kv_heads, cfg.head_dim),
            v.reshape(b, 1, cfg.n_kv_heads, cfg.head_dim))


# ---------------------------------------------------------------- decode
def prefill(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
            lora: dict | None = None, true_lens: jnp.ndarray | None = None,
            ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prompt pass for serving: final hidden states plus the per-layer
    K/V to seed a decode cache.

    tokens [b, P] (right-padded).  Returns (hidden [b, P, dim] post final
    norm — callers project ONLY the rows they need through lm_head; a
    full [b, P, vocab] fp32 logits tensor would be GBs at serving shapes,
    k [L, b, P, n_kv, hd], v likewise), RoPE already applied.  Padding
    rows produce garbage K/V that decode never attends to: the decode
    mask admits only kpos <= pos and each decode step overwrites its own
    position before reading it (see decode_step_paged).  true_lens [b]
    (absent: every row is P long) lets the attention kernel pass over
    the blocks of padding.

    lora: None/{} (base model) or {"idx": [b] int32 slots, "banks":
    {target: {"a": [L, n_slots, din, r], "b": [L, n_slots, r, dout]}}}
    — the banks scan alongside params["layers"], so the one compiled
    layer body serves every adapter mix.
    """
    b, P = tokens.shape
    lora = lora or None
    idx = lora["idx"] if lora else None
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    cos, sin = rope_frequencies(cfg.head_dim, P, cfg.rope_theta)

    def layer(x, scanned):
        lp = scanned[0]
        lb = scanned[1] if lora else {}
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        with jax.named_scope("attn_qkv"):
            q = _lora_proj(h, lp["wq"], lb.get("wq"), idx) \
                .reshape(b, P, cfg.n_heads, cfg.head_dim)
            k = _lora_proj(h, lp["wk"], lb.get("wk"), idx) \
                .reshape(b, P, cfg.n_kv_heads, cfg.head_dim)
            v = _lora_proj(h, lp["wv"], lb.get("wv"), idx) \
                .reshape(b, P, cfg.n_kv_heads, cfg.head_dim)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        o = attention(q, k, v, causal=True, lengths=true_lens)
        with jax.named_scope("attn_out"):
            x = x + _lora_proj(o.reshape(b, P, -1), lp["wo"],
                               lb.get("wo"), idx)
        x = _mlp_block(x, lp, cfg)
        return x, (k.astype(cfg.dtype), v.astype(cfg.dtype))

    xs = (params["layers"], lora["banks"]) if lora \
        else (params["layers"],)
    x, (ks, vs) = lax.scan(layer, x, xs)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, ks, vs


def init_paged_kv_cache(cfg: LlamaConfig, batch: int, n_pages: int,
                        page: int) -> dict:
    """Shared page-pool KV cache (ops/paged_attention.py): per-layer
    [n_pages, kvh, page, hd] leaves, not a [max_len] window per slot.
    Page 0 is the TRASH page — inactive slots' table rows point at it,
    so their (ignored) decode writes land somewhere harmless.  HBM cost
    scales with the page budget, not max_len x slots — the long-context
    serving enabler (SURVEY §7 "bucketed shapes/paged KV via Pallas").
    Layout is kv-head major (contiguous per-head page rows in VMEM)."""
    shape = (n_pages, cfg.n_kv_heads, page, cfg.head_dim)
    return {"k": [jnp.zeros(shape, cfg.dtype)
                  for _ in range(cfg.n_layers)],
            "v": [jnp.zeros(shape, cfg.dtype)
                  for _ in range(cfg.n_layers)],
            "pos": jnp.zeros((batch,), jnp.int32)}


def scatter_rows(pool, new, page_ids: jnp.ndarray, rows: jnp.ndarray,
                 aligned: bool = True):
    """Write a prefill wave's rows into ONE leaf of a page pool.

    pool [n_pages, kvh, page, w]; new [W, P, kvh, w]; page_ids/rows:
    [W, P] (page id + in-page row per token position; positions past a
    slot's allocation point at the trash page).  Duplicate wave-padding
    rows write identical data, so scatter order is irrelevant.

    Fast paths write PAGE-ALIGNED BLOCKS with a single [n] advanced
    index on the pool's page axis: the original [W, P] per-token
    coordinate scatter cost ~50ms of a 64x128 wave's prefill on a v5e
    (measured round 5: 193ms vs 143ms for the bare forward) — per-token
    scatters are the one indexed-write shape XLA:TPU cannot tile.
    Bucketed prompt lengths and power-of-two pages make every wave
    page-aligned in practice; the coordinate path remains as the
    general fallback — and is FORCED with aligned=False (prefix-cache
    suffix waves start mid-span at per-request offsets, so rows don't
    begin at 0)."""
    W, P = page_ids.shape
    page = pool.shape[2]
    if not aligned:
        return pool.at[page_ids, :, rows].set(new)
    if P <= page:
        # One (partial) page per wave member: block-write rows [0, P).
        return pool.at[page_ids[:, 0], :, :P, :].set(
            new.transpose(0, 2, 1, 3))
    if P % page == 0:
        # m whole pages per wave member: flatten to W*m full-page writes.
        m = P // page
        flat = page_ids[:, ::page].reshape(W * m)
        kvh, w = new.shape[2], new.shape[3]
        return pool.at[flat].set(
            new.reshape(W, m, page, kvh, w).transpose(0, 1, 3, 2, 4)
            .reshape(W * m, kvh, page, w))
    return pool.at[page_ids, :, rows].set(new)


@functools.partial(jax.named_call, name="kv_write")
def scatter_prefill_pages(cache: dict, ks, vs, page_ids: jnp.ndarray,
                          rows: jnp.ndarray, slots: jnp.ndarray,
                          true_lens: jnp.ndarray,
                          aligned: bool = True) -> dict:
    """Write a prefill wave's K/V into the page pool, a leaf at a time
    (`scatter_rows`): ks/vs [L, W, P, kvh, hd] from prefill().  Returns
    the updated cache."""
    nk = len(cache["k"])
    k = [scatter_rows(cache["k"][li], ks[li], page_ids, rows, aligned)
         for li in range(nk)]
    v = [scatter_rows(cache["v"][li], vs[li], page_ids, rows, aligned)
         for li in range(nk)]
    pos = cache["pos"].at[slots].set(true_lens)
    return {"k": k, "v": v, "pos": pos}


def prefill_with_prefix(params: dict, tokens: jnp.ndarray,
                        pos0: jnp.ndarray, cfg: LlamaConfig,
                        k_pages: list, v_pages: list,
                        prefix_table: jnp.ndarray,
                        lora: dict | None = None,
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Suffix prompt pass over a CACHED paged prefix (the radix
    prefix-cache fast path: prefill runs only on the tokens the cache
    didn't cover).

    tokens [b, S]: suffix tokens, right-padded; suffix token j sits at
    absolute position pos0[b] + j.  pos0 [b]: per-request prefix length
    (a multiple of the page size — the block manager matches full
    blocks only, so suffix writes never land in a shared page).
    k_pages/v_pages: per-layer page-pool leaves (READ-only here);
    prefix_table [b, maxp]: the requests' page-table rows.

    Each layer gathers its prefix rows dense ([b, maxp*page, kvh, hd] —
    prefill-scale traffic, paid once per admitted wave, never during
    decode) and runs GQA attention where suffix query i admits prefix
    keys < pos0[b] plus suffix keys j <= i.  Layers are UNROLLED like
    decode_step_paged: scanning would force the page pools into stacked
    scan inputs, copying every pool per wave.

    Returns (hidden [b, S, dim] post final norm, ks, vs [L, b, S, kvh,
    hd]) — the same contract as prefill(), so the engine's page scatter
    and first-token sampling reuse one code path for both."""
    from ray_tpu.ops.paged_attention import gather_pages

    b, S = tokens.shape
    page = k_pages[0].shape[2]
    Pp = prefix_table.shape[1] * page
    n_rep = cfg.n_heads // cfg.n_kv_heads
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    cos, sin = rope_frequencies(cfg.head_dim, Pp + S, cfg.rope_theta)
    positions = pos0[:, None] + jnp.arange(S)[None, :]       # [b, S]
    # Masks (shared by every layer): prefix keys admitted while they
    # fall below the request's cached-prefix length; suffix keys are
    # plain causal within the suffix.
    prefix_admit = (jnp.arange(Pp)[None, None, :]
                    < pos0[:, None, None])                   # [b, 1, Pp]
    causal = (jnp.arange(S)[None, :, None]
              >= jnp.arange(S)[None, None, :])               # [1, S, S]
    admit = jnp.concatenate(
        [jnp.broadcast_to(prefix_admit, (b, S, Pp)),
         jnp.broadcast_to(causal, (b, S, S))], axis=2)       # [b, S, Pp+S]

    ks_out, vs_out = [], []
    for lid in range(cfg.n_layers):
        with jax.named_scope("layer_weights"):
            lp = jax.tree.map(lambda a: a[lid], params["layers"])
        lb, lidx = _lora_layer_slice(lora, lid)
        lb = lb or {}
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        with jax.named_scope("attn_qkv"):
            q = _lora_proj(h, lp["wq"], lb.get("wq"), lidx) \
                .reshape(b, S, cfg.n_heads, cfg.head_dim)
            k = _lora_proj(h, lp["wk"], lb.get("wk"), lidx) \
                .reshape(b, S, cfg.n_kv_heads, cfg.head_dim)
            v = _lora_proj(h, lp["wv"], lb.get("wv"), lidx) \
                .reshape(b, S, cfg.n_kv_heads, cfg.head_dim)
        q = apply_rope(q, cos, sin, positions=positions)
        k = apply_rope(k, cos, sin, positions=positions)
        ks_out.append(k.astype(cfg.dtype))
        vs_out.append(v.astype(cfg.dtype))
        with jax.named_scope("attn"):
            pk = gather_pages(k_pages[lid], prefix_table)  # [b,Pp,kvh,hd]
            pv = gather_pages(v_pages[lid], prefix_table)
            ck = jnp.concatenate([pk, k.astype(cfg.dtype)], axis=1)
            cv = jnp.concatenate([pv, v.astype(cfg.dtype)], axis=1)
            qg = q.reshape(b, S, cfg.n_kv_heads, n_rep, cfg.head_dim)
            a = jnp.einsum("bsgrd,bkgd->bgrsk", qg, ck,
                           preferred_element_type=jnp.float32)
            a *= cfg.head_dim ** -0.5
            a = jnp.where(admit[:, None, None, :, :], a, -1e30)
            probs = jax.nn.softmax(a, axis=-1).astype(cfg.dtype)
            o = jnp.einsum("bgrsk,bkgd->bsgrd", probs, cv)
            o = o.reshape(b, S, cfg.n_heads * cfg.head_dim)
        with jax.named_scope("attn_out"):
            x = x + _lora_proj(o, lp["wo"], lb.get("wo"), lidx)
        x = _mlp_block(x, lp, cfg)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, jnp.stack(ks_out), jnp.stack(vs_out)


def decode_step_paged(params: dict, pages: dict, tails: dict,
                      tokens: jnp.ndarray, pos: jnp.ndarray,
                      tail_start: jnp.ndarray, j, page_table: jnp.ndarray,
                      cfg: LlamaConfig, lora: dict | None = None,
                      plan: dict | None = None) -> tuple[jnp.ndarray, dict]:
    """One decode step over the paged cache + in-block tail.

    pages {"k"/"v": [L x [n_pages, kvh, page, hd]]} are READ-ONLY here
    (loop-invariant for the whole K-step block — any per-step write of
    a scan-carried pool copies the entire buffer; see
    ops/paged_attention.py).  New K/V rows land in tails
    {"k"/"v": [L x [B, kvh, kt, hd]]} at the shared in-block column
    `j` (a scalar: every slot's pos advances in lockstep, so
    pos - tail_start is uniform).  After the block, the engine merges
    tails into pages with ops.paged_attention.merge_tail_pages.  `plan`
    is the block's `attention_plan`, the same for every layer and step
    (the engine builds it once a block; each kernel call builds its own
    if none is given).

    q, k, v come from `_decode_qkv`: the products stay flat behind a
    barrier, or the compiler re-lays-out wq/wk/wv every step (PR 29)."""
    from ray_tpu.ops.paged_attention import paged_decode_attention

    b = tokens.shape[0]
    n_rep = cfg.n_heads // cfg.n_kv_heads
    x = embed_lookup(params["embed"], tokens[:, None], cfg.dtype)
    # RoPE table covers the PAGED window (maxp * page), which may exceed
    # cfg.max_seq — long-context serving is the point of this path.
    max_len = page_table.shape[1] * pages["k"][0].shape[2]
    cos, sin = rope_frequencies(cfg.head_dim, max_len, cfg.rope_theta)

    new_tk, new_tv = [], []
    for lid in range(cfg.n_layers):
        with jax.named_scope("layer_weights"):
            lp = jax.tree.map(lambda a: a[lid], params["layers"])
        lb, lidx = _lora_layer_slice(lora, lid)
        lb = lb or {}
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        with jax.named_scope("attn_qkv"):
            q, k, v = _decode_qkv(h, lp, cfg, lb, lidx)
        q = apply_rope(q, cos, sin, positions=pos[:, None])
        k = apply_rope(k, cos, sin, positions=pos[:, None])
        qg = q.reshape(b, cfg.n_kv_heads, n_rep, cfg.head_dim)
        kn = k[:, 0].astype(cfg.dtype)[:, :, None, :]   # [B, kvh, 1, hd]
        vn = v[:, 0].astype(cfg.dtype)[:, :, None, :]
        with jax.named_scope("kv_write"):
            tk = lax.dynamic_update_slice(tails["k"][lid], kn,
                                          (0, 0, j, 0))
            tv = lax.dynamic_update_slice(tails["v"][lid], vn,
                                          (0, 0, j, 0))
        with jax.named_scope("attn"):
            o = paged_decode_attention(
                qg.astype(cfg.dtype), pages["k"][lid], pages["v"][lid],
                tk, tv, page_table, pos, tail_start, plan=plan)
        new_tk.append(tk)
        new_tv.append(tv)
        with jax.named_scope("attn_out"):
            x = x + _lora_proj(
                o.reshape(b, 1, cfg.n_heads * cfg.head_dim),
                lp["wo"], lb.get("wo"), lidx)
        x = _mlp_block(x, lp, cfg)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        logits = (x[:, 0] @ params["lm_head"]).astype(jnp.float32)
    return logits, {"k": new_tk, "v": new_tv}


# ------------------------------------------------------ the serving seam
# The functions above under the ONE signature serve/llm.LLMEngine calls
# for every model (models/serving.py): a dense decoder's lanes keep no
# state beside the page pool (an empty list: no leaf in any program) and
# it has no routed layers to count (a [0, 4] array).
def serving_spec(cfg: LlamaConfig) -> ServingSpec:
    """Every optional capability, and of work counters the prefill
    kernel's alone."""
    from ray_tpu.ops.flash_attention import PREFILL_COUNTERS, prefill_work

    return ServingSpec(caps=frozenset({"prefix", "lora", "kv_transfer"}),
                       counters=PREFILL_COUNTERS, prefill_work=prefill_work)


def project_logits(params: dict, h: jnp.ndarray) -> jnp.ndarray:
    return h @ params["lm_head"]


def _no_counts() -> jnp.ndarray:
    return jnp.zeros((0, 4), jnp.int32)


def init_paged_cache(cfg: LlamaConfig, batch: int, n_pages: int,
                     page: int) -> dict:
    return {**init_paged_kv_cache(cfg, batch, n_pages, page), "state": []}


def serve_prefill(params, tokens, cfg, true_lens, lora=None):
    hidden, ks, vs = prefill(params, tokens, cfg, lora, true_lens)
    return hidden, ks, vs, [], _no_counts()


def serve_scatter(cache, ks, vs, state, page_ids, rows, slots, true_lens,
                  aligned: bool = True) -> dict:
    return {**scatter_prefill_pages(cache, ks, vs, page_ids, rows, slots,
                                    true_lens, aligned=aligned),
            "state": state}


def serve_decode_step(params, pages, tails, state, tokens, pos, tail_start,
                      j, page_table, cfg, lora=None, plan=None):
    logits, tails = decode_step_paged(params, pages, tails, tokens, pos,
                                      tail_start, j, page_table, cfg, lora,
                                      plan)
    return logits, tails, state, _no_counts()
