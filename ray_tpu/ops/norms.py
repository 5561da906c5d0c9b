"""Normalization ops.

RMSNorm in fp32 accumulation regardless of input dtype (bf16-safe): the
variance reduction is tiny relative to the surrounding matmuls, so XLA fuses
it into the neighboring ops; a Pallas kernel buys nothing here (HBM-bound
either way) — kernels are reserved for attention where fusion actually
fails (see ops/flash_attention.py).
"""
from __future__ import annotations

import jax.numpy as jnp


def rmsnorm(x: jnp.ndarray, weight: jnp.ndarray,
            eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jnp.reciprocal(jnp.sqrt(var + eps))
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray,
              bias: jnp.ndarray | None, eps: float = 1e-6) -> jnp.ndarray:
    """Pre-LN transformer norm (ViT-style models); fp32 accumulation like
    rmsnorm, same fuse-into-neighbors rationale.  `bias` None: a norm
    with a weight alone (the Cohere decoders')."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    normed = (xf - mean) * jnp.reciprocal(jnp.sqrt(var + eps))
    out = normed * scale.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(dtype)
