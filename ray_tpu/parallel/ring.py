"""Ring attention: sequence/context parallelism over a "seq" mesh axis.

ABSENT from the reference (SURVEY §2.4 / §5 "Long-context": ray has no
sequence parallelism anywhere; it only gang-schedules user libraries).
Greenfield TPU design: the sequence axis is sharded over the mesh, each
device holds a contiguous token chunk, and KV chunks rotate around the ICI
ring via `lax.ppermute` while each device accumulates its queries' attention
in streaming-softmax (log-sum-exp merge) form — the full [s, s] score matrix
never exists, and each step's compute overlaps the next hop's transfer
(XLA pipelines ppermute with the einsums).

Causality with contiguous sharding lets each device skip the fully-masked
steps (`lax.cond` on src_idx > my_idx), so total work matches single-device
causal attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str = "seq", causal: bool = True) -> jnp.ndarray:
    """Blockwise ring attention; call inside shard_map with the sequence
    dimension sharded over `axis_name` (contiguous chunks).

    q: [b, s_loc, hq, d]; k/v: [b, s_loc, hkv, d] → [b, s_loc, hq, d].
    fp32 softmax statistics; bf16 matmul inputs preserved.
    """
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, s_loc, hq, d = q.shape
    n_rep = hq // k.shape[2]
    scale = d ** -0.5
    q_off = my_idx * s_loc

    qpos = q_off + jnp.arange(s_loc)[:, None]           # [s_loc, 1]

    def blk(carry, t):
        k_t, v_t, m, l, acc = carry
        src_idx = (my_idx - t) % n                       # origin of k_t
        k_off = src_idx * s_loc

        def compute(args):
            m, l, acc = args
            kk = _repeat_kv(k_t, n_rep)
            vv = _repeat_kv(v_t, n_rep)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                kpos = k_off + jnp.arange(s_loc)[None, :]
                mask = qpos >= kpos                      # [s_loc, s_loc]
                s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))       # [b,h,sq]
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])            # [b,h,sq,sk] f32
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = (acc * alpha[..., None]
                       + jnp.einsum("bhqk,bkhd->bhqd", p.astype(vv.dtype), vv,
                                    preferred_element_type=jnp.float32))
            return m_new, l_new, acc_new

        if causal:
            # Chunks strictly in the future are fully masked: skip compute.
            m, l, acc = lax.cond(src_idx > my_idx,
                                 lambda args: args, compute, (m, l, acc))
        else:
            m, l, acc = compute((m, l, acc))

        # Rotate KV to the next device on the ring (i → i+1).
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_t = lax.ppermute(k_t, axis_name, perm)
        v_t = lax.ppermute(v_t, axis_name, perm)
        return (k_t, v_t, m, l, acc), None

    m0 = jnp.full((b, hq, s_loc), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hq, s_loc), jnp.float32)
    acc0 = jnp.zeros((b, hq, s_loc, d), jnp.float32)
    (_, _, _, l, acc), _ = lax.scan(
        blk, (k, v, m0, l0, acc0), jnp.arange(n))

    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).astype(q.dtype)           # [b,h,sq,d]
    return out.transpose(0, 2, 1, 3)                     # → [b,sq,h,d]


def ring_attention_gspmd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         seq_axis: str = "seq",
                         causal: bool = True) -> jnp.ndarray:
    """GSPMD entry point: call from inside jit on globally-sharded arrays
    ([b, s, h, d] with s sharded over `seq_axis`); opens a shard_map region
    manual only over the sequence axis (batch/tensor axes stay automatic).
    Falls back to plain attention when there is no sequence axis to ring
    over (mesh absent or seq size 1)."""
    mesh = jax.sharding.get_abstract_mesh()
    if (mesh is None or seq_axis not in mesh.axis_names
            or mesh.shape[seq_axis] <= 1):
        from ray_tpu.ops.attention import attention

        return attention(q, k, v, causal=causal)
    spec = P(None, seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={seq_axis}, check_vma=False)
    return fn(q, k, v)
