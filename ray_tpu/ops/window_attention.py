"""Decode attention of a WINDOW layer over a per-lane ring of rows, for
serving: latent rows that every head shares (`swa_decode_attention`), or
a K and a V ring a kv head with grouped queries and a learned sink
(`kv_ring_attention`).

A layer whose queries attend only the last `window` positions keeps no
page: a lane holds a RING of `R >= window` rows in the cache's lane state
(`models/serving.py`: `state`), the row of position p in slot p mod R.
A decode step writes its own row into its slot (in place: the state is
donated through the decode program) and `swa_attn` attends the live
slots for every head at once, one grid step a LIVE lane, reading the
lane's ring where it lies: no gather, no table.  `ring_bias` is the one
reading of which slots a position attends; `ring_from_rows` fills a ring
from a prefill's rows.

A K/V ring is [lanes, kv heads, R, width], a K and a V apart (keys may
be wider than values): `kv_ring_write` writes the step's row a head, one
contiguous row an update, and `kv_ring_attention` attends the lane's
rings with every query head of a kv head's group at once, the sink a
column of the softmax's denominator that carries no value.  R may be the
window itself: the row a step overwrites (position p - R) is the one the
window has just left.

Device-side name: `swa_attn` (both kernels' `pallas_call` name: a model
has one or the other).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import flash_attention

F32 = jnp.float32
NEG_INF = -1e30


def ring_positions(pos, ring: int):
    """pos [B] -> [B, ring]: the position whose row slot i of a lane's
    ring holds once the row of `pos` is written (negative: nothing
    yet)."""
    i = jnp.arange(ring)[None, :]
    return pos[:, None] - jnp.mod(pos[:, None] - i, ring)


def ring_bias(pos, ring: int, window: int):
    """[B, ring] float32: 0 for the slots a query at `pos` attends (its
    own position and the window - 1 before it), -1e30 elsewhere."""
    held = ring_positions(pos, ring)
    keep = (held >= 0) & (pos[:, None] - held < window)
    return jnp.where(keep, 0.0, NEG_INF).astype(F32)


def ring_from_rows(rows, true_lens, ring: int):
    """rows [b, T, w] a prefill's cache rows, true_lens [b] -> [b, ring,
    w]: each row's ring as a decode step at position true_len finds it
    (slot i: the last position below true_len that is i mod ring; zeros
    where there is none)."""
    held = ring_positions(true_lens - 1, ring)               # [b, ring]
    got = jnp.take_along_axis(
        rows, jnp.clip(held, 0, rows.shape[1] - 1)[..., None], axis=1)
    return jnp.where((held >= 0)[..., None], got, jnp.zeros_like(got))


def _listed(lanes, count, B: int):
    """[B] bool: the lanes among the first `count` of the work list."""
    return jnp.any((lanes[None, :] == jnp.arange(B)[:, None])
                   & (jnp.arange(B)[None, :] < count), axis=1)


def _swa_kernel(lanes_ref, q_ref, rows_ref, bias_ref, o_ref, *, dv: int,
                sm_scale: float):
    """One lane: q_ref [H, dk]; rows_ref [R, dk] the lane's ring (key
    AND value: the first dv columns); bias_ref [1, R]; o_ref [H, dv]."""
    del lanes_ref
    rows = rows_ref[...]
    s = lax.dot_general(q_ref[...], rows, (((1,), (1,)), ((), ())),
                        preferred_element_type=F32) * sm_scale \
        + bias_ref[...]
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)
    o = lax.dot_general(p.astype(rows.dtype), rows[:, :dv],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=F32)
    o_ref[...] = (o / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def swa_decode_attention(q, ring, bias, lanes, count, *, dv: int,
                         sm_scale: float):
    """Attention of every head over a lane's ring.

    q [B, H, dk] absorbed queries; ring [B, R, dk] the lanes' rings of
    ONE layer, this step's row written; bias [B, R] (`ring_bias`);
    lanes, count: the work list of the live lanes
    (`ops/ssm.live_lanes`).  Returns o [B, H, dv]; a lane outside the
    list reads 0."""
    B, H, dk = q.shape
    R = ring.shape[1]

    def lane3(i, lanes):
        return (lanes[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(count,),
        in_specs=[pl.BlockSpec((None, H, dk), lane3),
                  pl.BlockSpec((None, R, dk), lane3),
                  pl.BlockSpec((None, 1, R), lane3)],
        out_specs=pl.BlockSpec((None, H, dv), lane3),
    )
    o = pl.pallas_call(
        functools.partial(_swa_kernel, dv=dv, sm_scale=sm_scale),
        name="swa_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 6 * R * dk * 2)),
        interpret=flash_attention._interpret(),
    )(lanes, q, ring, bias[:, None, :])
    listed = _listed(lanes, count, B)
    return jnp.where(listed[:, None, None], o, jnp.zeros_like(o))


def kv_ring_from_rows(rows, true_lens, ring: int):
    """rows [b, T, kvh, w] a prefill's K or V rows -> [b, kvh, ring, w]:
    `ring_from_rows` a kv head."""
    b, T, kvh, w = rows.shape
    got = ring_from_rows(rows.reshape(b, T, kvh * w), true_lens, ring)
    return got.reshape(b, ring, kvh, w).transpose(0, 2, 1, 3)


def kv_ring_write(ring, new, pos, listed):
    """ring [B, kvh, R, w] the lanes' K or V rings of one layer; new [B,
    kvh, w] this step's row a head; written at slot pos mod R of the
    lanes that hold a request (`listed` [B]; another lane's ring stays
    as it was: its update names a slot past the ring and is dropped).
    The head is an index too, so each update is one contiguous row
    (`ops/paged_attention.merge_tail_pages` has why)."""
    B, kvh, R, _ = ring.shape
    slot = jnp.where(listed, pos % R, R)
    return ring.at[jnp.arange(B)[:, None], jnp.arange(kvh)[None, :],
                   slot[:, None]].set(new.astype(ring.dtype), mode="drop")


def _kv_ring_kernel(lanes_ref, q_ref, k_ref, v_ref, bias_ref, sink_ref,
                    o_ref, *, sm_scale: float):
    """One lane: q_ref [kvh, rep, dk]; k_ref [kvh, R, dk], v_ref [kvh, R,
    dv] the lane's rings; bias_ref [1, R]; sink_ref [kvh, rep, 128] (the
    head's sink across the lanes); o_ref [kvh, rep, dv]."""
    del lanes_ref
    batch = ((0,), (0,))
    s = lax.dot_general(q_ref[...].astype(F32), k_ref[...].astype(F32),
                        (((2,), (2,)), batch),
                        preferred_element_type=F32) * sm_scale \
        + bias_ref[...]
    sink = sink_ref[:, :, :1]
    m = jnp.maximum(jnp.max(s, axis=2, keepdims=True), sink)
    p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=2, keepdims=True) + jnp.exp(sink - m)
    o = lax.dot_general(p, v_ref[...].astype(F32), (((2,), (1,)), batch),
                        preferred_element_type=F32)
    o_ref[...] = (o / l).astype(o_ref.dtype)


def kv_ring_attention(q, k_ring, v_ring, bias, sink, lanes, count, *,
                      sm_scale: float):
    """Grouped-query attention over a lane's K and V rings with a sink.

    q [B, kvh, rep, dk]; k_ring [B, kvh, R, dk], v_ring [B, kvh, R, dv]
    the lanes' rings of ONE layer, this step's rows written; bias [B, R]
    (`ring_bias`); sink [kvh, rep] float32, a head's learned column of
    the softmax (it joins the denominator and carries no value); lanes,
    count: the work list of the live lanes (`ops/ssm.live_lanes`).
    Returns o [B, kvh, rep, dv]; a lane outside the list reads 0."""
    B, kvh, rep, dk = q.shape
    R, dv = k_ring.shape[2], v_ring.shape[3]

    def lane3(i, lanes):
        return (lanes[i], 0, 0)

    def lane4(i, lanes):
        return (lanes[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(count,),
        in_specs=[pl.BlockSpec((None, kvh, rep, dk), lane4),
                  pl.BlockSpec((None, kvh, R, dk), lane4),
                  pl.BlockSpec((None, kvh, R, dv), lane4),
                  pl.BlockSpec((None, 1, R), lane3),
                  pl.BlockSpec((kvh, rep, 128), lambda i, lanes: (0, 0, 0))],
        out_specs=pl.BlockSpec((None, kvh, rep, dv), lane4),
    )
    o = pl.pallas_call(
        functools.partial(_kv_ring_kernel, sm_scale=sm_scale),
        name="swa_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, rep, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=flash_attention._interpret(),
    )(lanes, q, k_ring, v_ring, bias[:, None, :],
      jnp.broadcast_to(sink.astype(F32)[:, :, None], (kvh, rep, 128)))
    listed = _listed(lanes, count, B)
    return jnp.where(listed[:, None, None, None], o, jnp.zeros_like(o))


# What a serving module with window layers reports of them, a live lane's
# every decode step, x those layers (models/serving.ServingSpec.counters).
COUNTERS = {
    "swa_rows_context": "Rows in a lane's context at a window layer's "
                        "decode step, summed over live lanes, steps and "
                        "window layers",
    "swa_rows_attended": "Rows of its ring the step attended (the window "
                         "or the context, whichever is less), summed "
                         "likewise",
    "swa_lane_steps": "Live lanes x steps x window layers: the grid steps "
                      "swa_attn took",
}


def decode_work(layers: int, window: int, rows, k: int
                ) -> tuple[dict, dict]:
    """One decode window of `k` steps over live lanes that start it on
    `rows` cached rows each, x `layers` window layers (host arithmetic),
    as COUNTERS' rows; the span shows the same."""
    ctx = attended = 0
    for r in rows:
        for c in range(r + 1, r + 1 + k):
            ctx += c
            attended += min(c, window)
    work = {"swa_rows_context": ctx * layers,
            "swa_rows_attended": attended * layers,
            "swa_lane_steps": len(rows) * k * layers}
    return work, work


def attn_cost(H: int, dk: int, dv: int, rows: float
              ) -> tuple[float, float]:
    """(flops, bytes) `swa_attn` NEEDS to attend `rows` live ring rows in
    all (summed over lanes, layers and steps): each read once at its
    width (bfloat16) and scored and weighed for every head."""
    return 2.0 * H * (dk + dv) * rows, 2.0 * dk * rows
