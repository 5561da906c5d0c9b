"""Decode attention of a WINDOW layer over a per-lane ring of latent
rows, for serving.

A layer whose queries attend only the last `window` positions keeps no
page: a lane holds a RING of `R >= window` rows in the cache's lane state
(`models/serving.py`: `state`), the row of position p in slot p mod R.
A decode step writes its own row into its slot (in place: the state is
donated through the decode program) and `swa_attn` attends the live
slots for every head at once, one grid step a LIVE lane, reading the
lane's ring where it lies: no gather, no table.  `ring_bias` is the one
reading of which slots a position attends; `ring_from_rows` fills a ring
from a prefill's rows.

Device-side name: `swa_attn` (the kernel's `pallas_call` name too).
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
    listed = jnp.any((lanes[None, :] == jnp.arange(B)[:, None])
                     & (jnp.arange(B)[None, :] < count), axis=1)
    return jnp.where(listed[:, None, None], o, jnp.zeros_like(o))


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
