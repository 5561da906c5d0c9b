"""Rotary position embeddings (RoPE), Llama-3 style with NTK scaling hooks."""
from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 500000.0,
                     dtype=jnp.float32) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute cos/sin tables [max_seq, head_dim//2]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               positions: jnp.ndarray | None = None) -> jnp.ndarray:
    """Rotate q/k.  x: [batch, seq, heads, head_dim]; tables [max_seq, hd/2].

    positions: optional [batch, seq] absolute positions (decode-time cache
    offsets); defaults to arange(seq).
    """
    b, s, h, d = x.shape
    if positions is None:
        cos_s = cos[:s][None, :, None, :]     # [1, s, 1, d/2]
        sin_s = sin[:s][None, :, None, :]
    else:
        cos_s = cos[positions][:, :, None, :]  # [b, s, 1, d/2]
        sin_s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos_s - x2 * sin_s, x2 * cos_s + x1 * sin_s], axis=-1)
    return out.astype(x.dtype)


def half_from_interleaved(head_dim: int, heads: int = 1) -> jnp.ndarray:
    """[heads * head_dim] int32: the columns of a projection whose heads'
    rotary pairs NEIGHBOURS, (2i, 2i + 1) (GPT-J's form, `rope_gptj`), in
    the order that pairs the halves (each head's even columns, then its
    odd ones).  q and k permuted alike keep every score, and `apply_rope`
    over the permuted columns is the interleaved rotary over the
    published ones, permuted: a served model permutes W_q and W_k once,
    as a checkpoint loader would, and no program strides over the
    lanes."""
    one = jnp.concatenate([jnp.arange(0, head_dim, 2),
                           jnp.arange(1, head_dim, 2)])
    return (jnp.arange(heads)[:, None] * head_dim + one[None, :]).reshape(-1)
