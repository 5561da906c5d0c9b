"""Mixtral-style sparse Mixture-of-Experts decoder, TPU-first: a
capacity-DROPPING routed layer for TRAINING (`forward`, `loss_fn`; no
prefill, decode or cache).  Tokens past an expert's capacity are dropped,
so its logits depend on the batch.  The SERVED routed layer, which drops
nothing, is `models/routed.routed_ffn` over `ops/grouped_matmul.gmm`.

The reference has no MoE anywhere (SURVEY §2.4: expert parallelism ABSENT
— greenfield for this framework).  Design follows the GShard/Switch TPU
lineage rather than ragged GPU kernels:

  - top-k routing with a fixed per-expert **capacity**: dispatch/combine
    are dense one-hot einsums with static shapes, so XLA tiles them onto
    the MXU and inserts the expert all-to-alls when the "expert" mesh axis
    is real (logical axis "expert" → mesh "expert" in
    parallel.sharding.LOGICAL_RULES)
  - expert weights carry a leading [E, ...] axis sharded over the expert
    mesh axis; tokens sharded over batch travel to experts via the
    GSPMD-inserted all-to-all and come back weighted by router probs
  - Switch-style load-balance auxiliary loss keeps routing uniform
  - attention/norm/rope reuse the llama blocks — an MoE model is the
    llama trunk with the dense MLP swapped for the routed one

Reference hooks (for parity checks): Ray's only "model family" role is
gang-scheduling user models; this module is cited from SURVEY §2.4 row
"Expert parallel (EP/MoE)".
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import llama
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.parallel.sharding import with_sharding_constraint


@dataclasses.dataclass(frozen=True)
class MoEConfig(llama.LlamaConfig):
    n_experts: int = 8
    experts_per_token: int = 2
    # capacity per expert = capacity_factor * tokens * k / E (rounded up
    # to a multiple of 8 for MXU-friendly tiling)
    capacity_factor: float = 1.25
    router_aux_coeff: float = 0.01

    def num_params(self) -> int:
        d, f = self.dim, self.ffn_dim
        dense = super().num_params()
        # replace the dense 3*d*f MLP with E experts + router
        per_layer_delta = (self.n_experts - 1) * 3 * d * f \
            + d * self.n_experts
        return dense + self.n_layers * per_layer_delta

    def active_params(self) -> int:
        """Params touched per token (the MoE efficiency headline)."""
        d, f = self.dim, self.ffn_dim
        dense = super().num_params()
        per_layer_delta = (self.experts_per_token - 1) * 3 * d * f \
            + d * self.n_experts
        return dense + self.n_layers * per_layer_delta


def moe_configs() -> dict[str, MoEConfig]:
    return {
        # Mixtral-8x7B shape
        "mixtral-8x7b": MoEConfig(
            vocab_size=32000, dim=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, ffn_dim=14336, max_seq=32768,
            rope_theta=1e6, n_experts=8, experts_per_token=2),
        "moe-debug": MoEConfig(
            vocab_size=2048, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=256, max_seq=256, n_experts=4, experts_per_token=2),
    }


# ---------------------------------------------------------------- params
def param_logical_axes(cfg: MoEConfig) -> dict:
    axes = llama.param_logical_axes(cfg)
    layer_axes = dict(axes["layers"])
    for name in ("w_gate", "w_up", "w_down"):
        del layer_axes[name]
    layer_axes.update({
        "router": ("layers", "embed", "expert"),
        "we_gate": ("layers", "expert", "embed", "mlp"),
        "we_up": ("layers", "expert", "embed", "mlp"),
        "we_down": ("layers", "expert", "mlp", "embed"),
    })
    axes["layers"] = layer_axes
    return axes


def init_params(key: jax.Array, cfg: MoEConfig) -> dict:
    params = llama.init_params(key, cfg)
    d, f, E, L = cfg.dim, cfg.ffn_dim, cfg.n_experts, cfg.n_layers
    keys = jax.random.split(jax.random.fold_in(key, 1), 4)

    def ninit(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.dtype)

    lp = params["layers"]
    for name in ("w_gate", "w_up", "w_down"):
        del lp[name]
    lp["router"] = ninit(keys[0], (L, d, E), d)
    lp["we_gate"] = ninit(keys[1], (L, E, d, f), d)
    lp["we_up"] = ninit(keys[2], (L, E, d, f), d)
    lp["we_down"] = ninit(keys[3], (L, E, f, d), f)
    return params


# --------------------------------------------------------------- routing
def _capacity(tokens: int, cfg: MoEConfig) -> int:
    cap = int(cfg.capacity_factor * tokens * cfg.experts_per_token
              / cfg.n_experts)
    return max(8, -(-cap // 8) * 8)


def route(h: jnp.ndarray, router_w: jnp.ndarray, cfg: MoEConfig):
    """Top-k routing with capacity (GShard dispatch/combine tensors).

    h [T, d] → dispatch [T, E, C] bool-ish, combine [T, E, C] float,
    aux_loss scalar.  T = b*s tokens; all shapes static.
    """
    T = h.shape[0]
    C = _capacity(T, cfg)
    E, K = cfg.n_experts, cfg.experts_per_token
    logits = (h.astype(jnp.float32) @ router_w.astype(jnp.float32))  # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)

    # Switch aux loss: E * sum_e (fraction_tokens_e * mean_prob_e)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))

    # top-k expert choice per token
    topk_p, topk_e = lax.top_k(probs, K)                     # [T,K]
    # position of each (token, k) in its expert's queue, computed via a
    # cumulative count over tokens (static-shape scan replacement)
    onehot = jax.nn.one_hot(topk_e, E, dtype=jnp.int32)      # [T,K,E]
    flat = onehot.reshape(T * K, E)
    pos_in_expert = jnp.cumsum(flat, axis=0) - flat          # [T*K,E]
    pos = (pos_in_expert * flat).sum(-1).reshape(T, K)       # [T,K]
    keep = pos < C                                           # capacity drop
    gate = topk_p * keep                                     # [T,K]
    denom = jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    gate = gate / denom                                      # renormalize

    slot = jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                          dtype=jnp.float32)[..., :C]        # [T,K,C]
    # combine[t,e,c] = sum_k gate[t,k] * [expert k == e] * slot[t,k,c]
    combine = jnp.einsum("tk,tke,tkc->tec",
                         gate.astype(jnp.float32),
                         onehot.astype(jnp.float32), slot)
    dispatch = (combine > 0).astype(h.dtype)
    return dispatch, combine.astype(h.dtype), aux


def moe_block(x: jnp.ndarray, lp: dict, cfg: MoEConfig):
    """Routed-FFN residual block (replaces llama._mlp_block).

    x [b, s, d] → (y [b, s, d], aux scalar)."""
    b, s, d = x.shape
    h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    ht = h.reshape(b * s, d)
    dispatch, combine, aux = route(ht, lp["router"], cfg)
    # send tokens to experts: [E, C, d]; E sharded over the expert axis →
    # XLA inserts the all-to-all here
    xe = jnp.einsum("tec,td->ecd", dispatch, ht)             # [E,C,d]
    xe = with_sharding_constraint(xe, ("expert", None, None))
    gate = jnp.einsum("ecd,edf->ecf", xe, lp["we_gate"])
    up = jnp.einsum("ecd,edf->ecf", xe, lp["we_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    out = jnp.einsum("ecf,efd->ecd", act, lp["we_down"])     # [E,C,d]
    out = with_sharding_constraint(out, ("expert", None, None))
    # bring results home weighted by gates (reverse all-to-all)
    y = jnp.einsum("tec,ecd->td", combine, out.astype(jnp.float32))
    y = y.astype(x.dtype).reshape(b, s, d)
    return x + y, aux


# --------------------------------------------------------------- forward
def forward(params: dict, tokens: jnp.ndarray, cfg: MoEConfig,
            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [b, s] → (logits [b, s, vocab] fp32, aux_loss scalar)."""
    def layer_fn(x, lp, cos, sin, aux):
        y = llama._attention_block(x, lp, cfg, cos, sin)
        y, a = moe_block(y, lp, cfg)
        return y, aux + a

    logits, aux = llama.run_trunk(params, tokens, cfg, layer_fn)
    return logits, aux / cfg.n_layers


def loss_fn(params: dict, batch: dict, cfg: MoEConfig) -> jnp.ndarray:
    """Next-token cross entropy (mask-aware) + router load-balance aux."""
    inputs, targets = llama.split_batch(batch)
    logits, aux = forward(params, inputs, cfg)
    return llama.cross_entropy(logits, targets, batch.get("mask")) \
        + cfg.router_aux_coeff * aux
