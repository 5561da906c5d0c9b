"""Pallas flash attention for TPU (FlashAttention-2 style, causal, GQA).

The [b, h, s, s] score matrix never materializes in HBM: the forward kernel
streams KV blocks through VMEM, keeping a running (max, sum, acc) per query
block; the backward is two kernels (dq; dkv) recomputing P from the saved
log-sum-exp, FlashAttention-2 style.

This is the framework's own kernel (the reference delegates attention to
user libraries entirely — ray has no attention op); layout is [b, h, s, d]
inside the kernel.  Default blocks are block_q=512 / block_k=1024
(measured best for the backward kernels on v5e; see DEFAULT_BLOCK_Q);
the dispatcher halves them until they divide the sequence, so any
seq % 128 == 0 works.

Constraints: seq % 128 == 0 (the dispatcher in ray_tpu.ops.attention
falls back to XLA otherwise, and zero-pads a head_dim that is no multiple
of 128 lanes).  The forward takes values narrower than the keys (latent
attention's expanded path: q/k 192, v 128); the backward one width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured on v5e (bench-350m, b8 x s2048): fwd is flat across block
# sizes (~8 TF/s — the kernel beats jax's splash at 5.2 TF/s on the same
# shape), but the BACKWARD kernels run ~1.8x faster at bq=512/bk=1024
# than at 128/128 (12.5ms vs 22.7ms fwd+bwd per layer-call).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, sm_scale: float, causal: bool):
    """One (batch, head, q-block, KV-block) program.  KV is the MINOR
    grid dimension, so each program sees one [block_k, d] slice — VMEM
    stays bounded at ANY sequence length (whole-KV residency OOMed
    scoped vmem at 32k).  The running (max, sum, acc) live in scratch,
    which persists across the sequential kv iterations; o/lse write out
    on the last one.

    q_ref: [block_q, d]; k_ref/v_ref: [block_k, d]; o_ref: [block_q, d];
    lse_ref: [block_q, 128] (value broadcast across lanes — TPU tiles
    need a 128 minor dim).
    """
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    num_kv = pl.num_programs(3)
    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_or(not causal,
                            k_start <= q_start + block_q - 1))
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[:, 0]                      # [bq]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)           # [bq]
        p = jnp.exp(s - m_cur[:, None])           # [bq, bk] f32
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[:, 0] = m_cur

    @pl.when(ki == num_kv - 1)
    def _write():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows: zeros, no NaN
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[:, 0] = m_ref[:, 0] + jnp.log(l)


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    """q: [b, hq, sq, d]; k: [b, hkv, skv, d]; v: [b, hkv, skv, dv]
    (dv = d everywhere but latent attention's expanded path, whose keys
    are wider than its values) → (o [b, hq, sq, dv], lse [b, hq, sq])."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    dv = v.shape[3]
    n_rep = hq // hkv
    grid = (b, hq, sq // block_q)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal),
        name="flash_fwd",
        grid=(*grid, skv // block_k),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, qi, ki,
                         n_rep=n_rep: (bi, hi // n_rep, ki, 0)),
            pl.BlockSpec((None, None, block_k, dv),
                         lambda bi, hi, qi, ki,
                         n_rep=n_rep: (bi, hi // n_rep, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, dv),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 128),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)
    return out, lse[..., 0]


# ----------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, sm_scale: float, causal: bool):
    """dQ for one (b, h, q-block, KV-block); KV is the minor grid dim
    (streamed like the forward — whole-KV residency OOMs at 32k).
    dS = P * (dO V^T - delta); dQ = dS K * scale, accumulated in scratch
    across the sequential kv iterations."""
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    num_kv = pl.num_programs(3)
    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_or(not causal,
                            k_start <= q_start + block_q - 1))
    def _compute():
        q = q_ref[...]
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[:, 0]
        delta = delta_ref[:, 0]
        k = k_ref[...]
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                     # [bq, bk]
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_kv - 1)
    def _write():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, sm_scale: float, causal: bool, n_rep: int):
    """dK/dV for one (b, kv-head, kv-block); the q axis is the MINOR grid
    dimension, so q/do/lse/delta stream through VMEM one block at a time
    (whole-sequence blocks would blow VMEM at long context — the
    long-context path is the point of this kernel).  dk/dv accumulate in
    scratch, which persists across the sequential q iterations, and write
    out on the last one.  dV = P^T dO; dK = dS^T Q * scale."""
    block_k, d = k_ref.shape
    block_q = q_ref.shape[1]
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    num_q = pl.num_programs(3)
    k_start = ki * block_k
    q_start = qi * block_q

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(jnp.logical_or(not causal, q_start + block_q - 1 >= k_start))
    def _compute():
        k = k_ref[...]
        v = v_ref[...]
        for rep in range(n_rep):        # small constant (GQA group)
            q = q_ref[rep]
            do = do_ref[rep].astype(jnp.float32)
            lse = lse_ref[rep, :, 0]
            delta = delta_ref[rep, :, 0]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if causal:
                qpos = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                kpos = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])                      # [bq, bk]
            dv_acc[...] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, None]) * sm_scale          # [bq, bk]
            dk_acc[...] += jax.lax.dot_general(
                ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _write():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(sm_scale, causal, block_q, block_k, res, g):
    q, k, v, o, lse = res
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    n_rep = hq // hkv
    do = g

    # delta = rowsum(dO * O)  [b, hq, sq] — cheap elementwise, leave to XLA.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse_b = jnp.broadcast_to(lse[..., None], (*lse.shape, 128))
    delta_b = jnp.broadcast_to(delta[..., None], (*delta.shape, 128))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal),
        name="flash_bwd_dq",
        grid=(b, hq, sq // block_q, skv // block_k),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, qi, ki,
                         n_rep=n_rep: (bi, hi // n_rep, ki, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, qi, ki,
                         n_rep=n_rep: (bi, hi // n_rep, ki, 0)),
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 128),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 128),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
    )(q, k, v, do, lse_b, delta_b)

    # dK/dV: grid over kv heads × kv blocks × q blocks (q minor, so each
    # program streams one [n_rep, block_q, d] slice — VMEM stays bounded
    # at any sequence length; dk/dv accumulate in scratch across the
    # sequential q iterations).
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          n_rep=n_rep),
        name="flash_bwd_dkv",
        grid=(b, hkv, skv // block_k, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, None, n_rep, block_q, d),
                         lambda bi, hi, ki, qi: (bi, hi, 0, qi, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, n_rep, block_q, d),
                         lambda bi, hi, ki, qi: (bi, hi, 0, qi, 0)),
            pl.BlockSpec((None, None, n_rep, block_q, 128),
                         lambda bi, hi, ki, qi: (bi, hi, 0, qi, 0)),
            pl.BlockSpec((None, None, n_rep, block_q, 128),
                         lambda bi, hi, ki, qi: (bi, hi, 0, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(_reshape_heads(q, hkv, n_rep), k, v,
      _reshape_heads(do, hkv, n_rep),
      _reshape_heads(lse_b, hkv, n_rep),
      _reshape_heads(delta_b, hkv, n_rep))
    return dq, dk, dv


def _reshape_heads(x, hkv, n_rep):
    """[b, hq, ...] → [b, hkv, n_rep, ...] grouped by kv head."""
    b = x.shape[0]
    return x.reshape(b, hkv, n_rep, *x.shape[2:])


def _interpret() -> bool:
    """Compiled on a TPU; interpreted on the CPU so tests exercise the
    same kernel code.  Any other backend is an error — never a silent
    interpreter run."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            "Pallas TPU kernels run compiled on 'tpu' and interpreted "
            f"on 'cpu'; the default backend is {backend!r}")
    return backend == "cpu"


# ---------------------------------------------------------------- dispatch
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, sm_scale, causal, block_q, block_k):
    o, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k)
    return o


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    o, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k)
    # Name the residuals so a remat policy can SAVE them: under
    # jax.checkpoint with nothing_saveable, the backward re-runs this
    # whole forward kernel just to regenerate (o, lse) — per-layer
    # fwd+bwd drops ~40% when the policy keeps these instead
    # (models/llama.py remat_policy()).
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_vjp_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = True, sm_scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K):
    """Flash attention with GQA.  q: [b, sq, hq, d]; k/v: [b, skv, hkv, d];
    returns [b, sq, hq, d] (layout matches ray_tpu.ops.attention).  v may
    be [b, skv, hkv, dv] with dv != d (forward only: the backward kernels
    take one width); the result is then [b, sq, hq, dv]."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    # Blocks must DIVIDE the sequence (the grids floor-divide): halve the
    # power-of-two defaults until they do, never below the 128 MXU tile.
    # seq % 128 == 0 is the dispatcher's entry gate, so power-of-two
    # blocks always land; a non-power-of-two caller block that can't
    # divide is an error rather than a silent degenerate grid.
    block_q = min(block_q, qt.shape[2])
    while qt.shape[2] % block_q and block_q > 128:
        block_q //= 2
    block_k = min(block_k, kt.shape[2])
    while kt.shape[2] % block_k and block_k > 128:
        block_k //= 2
    if qt.shape[2] % block_q or kt.shape[2] % block_k:
        raise ValueError(
            f"block sizes ({block_q}, {block_k}) do not divide seq "
            f"({qt.shape[2]}, {kt.shape[2]}); use power-of-two blocks")
    if vt.shape[3] != qt.shape[3]:
        o, _ = _flash_fwd(qt, kt, vt, sm_scale, causal, block_q, block_k)
    else:
        o = _flash(qt, kt, vt, sm_scale, causal, block_q, block_k)
    return o.transpose(0, 2, 1, 3)
