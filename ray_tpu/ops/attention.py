"""Attention dispatcher: Pallas flash kernel on TPU, XLA einsum fallback.

The hot op of the whole framework (SURVEY §7: attention is where fusion
genuinely fails without a kernel — the [b, h, s, s] score matrix must never
materialize in HBM at long context).
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

logger = logging.getLogger(__name__)

# Shapes the "auto" gate sent to the XLA path ON A TPU, each logged once
# and counted here at trace time (one count a compiled program, not a
# call): a prefill that should run in the flash kernel and does not is
# then visible (`xla_fallbacks()`), not silent.
_XLA_FALLBACKS: dict[tuple, int] = {}


def xla_fallbacks() -> dict[tuple, int]:
    """{(sq, skv, head_dim, causal): programs traced} of the attention
    calls that took the XLA path on a TPU."""
    return dict(_XLA_FALLBACKS)


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Expand KV heads for grouped-query attention."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(
        k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def xla_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True,
                  q_offset: int | jnp.ndarray = 0,
                  sm_scale: float | None = None,
                  window: int | None = None, sink=None) -> jnp.ndarray:
    """Reference implementation: fp32 softmax, GQA, causal mask.

    q: [b, sq, hq, d]; k/v: [b, skv, hkv, d] (v may be narrower).
    q_offset shifts query positions relative to kv positions (decode
    with a cache); sm_scale defaults to d**-0.5; window (causal only):
    a query attends its own position and the window - 1 before it;
    sink [hq]: a column of the softmax a head that carries no value.
    """
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, skv = q.shape[1], k.shape[1]
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(skv)[None, :]
        mask = qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        logits = jnp.where(mask[None, None], logits, -1e30)
    logits = logits.astype(jnp.float32)
    if sink is None:
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        col = jnp.broadcast_to(sink.astype(jnp.float32)[None, :, None, None],
                               logits.shape[:3] + (1,))
        probs = jax.nn.softmax(jnp.concatenate([logits, col], axis=-1),
                               axis=-1)[..., :-1]
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "impl", "sm_scale",
                                             "window", "band_name"))
def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True, impl: str = "auto",
              q_offset: int | jnp.ndarray = 0,
              sm_scale: float | None = None,
              lengths: jnp.ndarray | None = None,
              window: int | None = None,
              sink: jnp.ndarray | None = None,
              band_name: str = "flash_fwd") -> jnp.ndarray:
    """Multi-head attention with GQA.

    impl: "auto" picks the Pallas flash kernel on TPU for long-enough
    sequences, XLA otherwise (short sequences / CPU tests / decode).
    v may be narrower than q and k (latent attention's expanded path:
    192 / 128); sm_scale defaults to head_dim**-0.5.
    lengths: int32 [b], the true lengths of right-padded rows (causal
    only, forward only).  The flash kernel copies and multiplies nothing
    for the query blocks wholly past a length and leaves zeros there; the
    XLA path ignores it, and what either gives in a padded row is no
    one's to read: causal true rows need no length.
    window: a query attends its own position and the window - 1 before
    it (causal only, forward only); the flash kernel walks no key block
    that lies wholly before a query block's band.
    sink: float [hq], a learned column of the softmax a head that
    carries no value (forward only, one device; the flash kernel of such
    a call is named `swa_band` on the device).
    """
    if window is not None and not causal:
        raise ValueError("a window takes causal attention")
    use_flash = False
    if impl == "flash":
        use_flash = True
    elif impl == "auto":
        on_tpu = any(d.platform == "tpu" for d in jax.devices())
        # Flash kernel requires seq multiple of its block size; a
        # head_dim under 128 lanes is zero-padded.
        use_flash = (on_tpu and causal and q.shape[1] == k.shape[1]
                     and q.shape[1] % 128 == 0)
        if on_tpu and not use_flash:
            key = (q.shape[1], k.shape[1], q.shape[-1], causal)
            if key not in _XLA_FALLBACKS:
                logger.info("attention: XLA path on a TPU for sq=%d skv=%d "
                            "head_dim=%d causal=%s (the flash kernel takes "
                            "causal self-attention over a multiple of 128 "
                            "tokens)", *key)
            _XLA_FALLBACKS[key] = _XLA_FALLBACKS.get(key, 0) + 1
    if use_flash:
        return _flash_padded(q, k, v, causal, sm_scale, lengths, window,
                             sink, band_name)
    return xla_attention(q, k, v, causal=causal, q_offset=q_offset,
                         sm_scale=sm_scale, window=window, sink=sink)


def _flash_padded(q, k, v, causal: bool, sm_scale: float | None = None,
                  lengths=None, window: int | None = None, sink=None,
                  band_name: str = "flash_fwd"):
    """The flash kernel at any head_dim: a width (q and k's, or v's)
    under 128 lanes is zero-padded to 128, the scale given as the TRUE
    head_dim's; wider ones go as they are (192 / 128 compiles:
    tests/test_chip_compile.py; behind the model's transposes it runs as
    fast as padded to 256: PERF.md section 6, PR 38).  Exact: the padded
    columns add 0 to every score and the padded columns of the output
    are cut off."""
    d, dv = q.shape[-1], v.shape[-1]
    if min(d, dv) >= 128:
        return _flash_per_shard(q, k, v, causal, sm_scale, lengths, window,
                                sink, band_name)

    def pad(a):
        return jnp.pad(a, ((0, 0),) * 3 + ((0, max(128 - a.shape[-1], 0)),))

    o = _flash_per_shard(pad(q), pad(k), pad(v), causal,
                         d ** -0.5 if sm_scale is None else sm_scale,
                         lengths, window, sink, band_name)
    return o[..., :dv]


def _flash_per_shard(q, k, v, causal: bool, sm_scale: float | None = None,
                     lengths=None, window: int | None = None, sink=None,
                     band_name: str = "flash_fwd"):
    """The Pallas kernel; under an ambient multi-device mesh, one call
    per shard (jax refuses a Mosaic kernel under GSPMD at lowering:
    "wrap the call in a shard_map").  The layout — what splits over
    which mesh axes, and the GQA rule — is parallel/sharding's; lengths
    split as q's rows do."""
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.parallel.sharding import attention_shard_specs

    fn = functools.partial(flash_attention, causal=causal,
                           sm_scale=sm_scale,
                           **({} if window is None else {
                               "window": window, "band_name": band_name}))
    specs = attention_shard_specs(q.shape, k.shape)
    if sink is not None:
        if specs is not None:
            raise ValueError("a sink takes attention on one device")
        fn = functools.partial(fn, sink=sink)
    if specs is None:
        return fn(q, k, v, lengths=lengths)
    mesh, axis_names, q_spec, kv_spec = specs
    return jax.shard_map(       # lengths None: a tree of no leaves
        lambda q, k, v, n: fn(q, k, v, lengths=n), mesh=mesh,
        axis_names=axis_names, check_vma=False,
        in_specs=(q_spec, kv_spec, kv_spec, PartitionSpec(q_spec[0])),
        out_specs=q_spec)(q, k, v, lengths)
