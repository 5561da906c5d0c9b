"""Grouped matmul for a routed-expert layer that drops no token.

`gmm(rows, weights, group_sizes)`: rows [m, k] sorted by group, weights
[G, k, n], group_sizes [G] int32 -> [m, n], row i multiplied by the
weights of the group it lies in.  Rows past `sum(group_sizes)` belong to
no group and come out zero (a layer that holds a range of the experts
sorts the other experts' rows there).  There is no capacity: a group may
hold every row or none.

On a TPU this is one Pallas kernel, called `moe_gmm` (the name the
benchmark's roofline is keyed on).  The grid walks (column tile, VISIT):
a visit is one (group, row tile) pair that share rows, in group order.
A group's weight block is fetched when its first visit starts and is
kept while the visits that follow name the same group, so every group
that holds a row is streamed once a column tile, and a group that holds
none has no visit: its weights are never read.  A row tile that two
groups share is visited by each in turn and stored under a row mask
(the technique of jax's megablox `gmm`; this one keeps the whole
contraction in one block and so needs no accumulator).

The visit axis of the grid is bounded by the number of visits that are
work, a value the device holds (`visits`' `total`, as `paged_attn`'s
grid is bounded by its plan's count): the list has the static length
`visits_static`, the most visits there can be, and the entries past
`total` pad it and are never walked.  A layer that holds a quarter of
the experts, or whose rows are mostly padding, pays for the visits its
rows make and not for the list; with no row at all no step runs, the
output is left unwritten, and `gmm` zeroes it as it zeroes every row
past `sum(group_sizes)`.

Elsewhere (the CPU tests) it is `jax.lax.ragged_dot`, as `xla_attention`
stands in for the flash kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import flash_attention

# Row tile: decode sends a few rows to each group (64 lanes x 4 over 64
# experts), so a small tile wastes the least of the MXU's rows on other
# groups' rows; 16 is bfloat16's sublane tile.  Prefill sends hundreds.
ROW_TILE_SMALL = 16
ROW_TILE_LARGE = 256
COL_TILE = 512


def _on_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def _interpret() -> bool:
    # the flash kernel's rule (compiled on a TPU, interpreted on the
    # CPU), asked where it lives so that whoever steers it steers both
    return flash_attention._interpret()


def row_tile(m: int) -> int:
    """The row tile `gmm` walks `m` rows by."""
    return ROW_TILE_SMALL if m <= 64 * ROW_TILE_SMALL else ROW_TILE_LARGE


def visits_static(m: int, G: int) -> int:
    """The length of the visit list `gmm` builds for `m` rows over `G`
    groups, the most visits there can be: every row tile once, and once
    more for each group boundary inside a tile."""
    return -(-m // row_tile(m)) + G - 1


def visits(group_sizes: jnp.ndarray, m: int, tm: int):
    """The kernel's visit list for rows tiled by `tm`.

    Returns (group_of_visit [V], tile_of_visit [V], group_offsets [G+1],
    total) with V = m/tm + G - 1, the most visits there can be, and
    `total` (a scalar) the visits that are work: the kernel walks the
    first `total` entries; the others repeat the last of them."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    last = jnp.maximum(ends - 1, 0) // tm
    n_tiles = jnp.where(group_sizes > 0, last - first + 1, 0)
    v_end = jnp.cumsum(n_tiles)              # visits up to and with g
    total = v_end[-1]
    V = m // tm + G - 1
    v = jnp.minimum(jnp.arange(V, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    g = jnp.searchsorted(v_end, v, side="right").astype(jnp.int32)
    g = jnp.minimum(g, G - 1)
    tile = first[g] + (v - (v_end[g] - n_tiles[g]))
    tile = jnp.clip(tile, 0, m // tm - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               ends.astype(jnp.int32)])
    return g, tile, offsets, total


def n_visits(group_sizes: jnp.ndarray, m: int):
    """The visits `gmm` walks for `m` rows in groups of `group_sizes`: a
    scalar the device holds, `visits_static(m, G)` at most."""
    tm = row_tile(m)
    return visits(group_sizes.astype(jnp.int32), m + -m % tm, tm)[3]


def _kernel(g_ref, t_ref, off_ref, x_ref, w_ref, o_ref, *, tm: int):
    v = pl.program_id(1)
    g = g_ref[v]
    row0 = t_ref[v] * tm
    acc = jnp.dot(x_ref[...], w_ref[...],
                  preferred_element_type=jnp.float32)
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    mine = (rows >= off_ref[g]) & (rows < off_ref[g + 1])
    # the tile's other rows are another visit's (or nobody's: zeroed
    # by the caller); what the buffer holds for them is kept
    o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)
                           ).astype(o_ref.dtype)


def _gmm_pallas(rows, weights, group_sizes, tm: int, tn: int):
    m, k = rows.shape
    G, _, n = weights.shape
    g, tile, offsets, total = visits(group_sizes, m, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, total),                # the device's own number
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, v, g, t, off: (t[v], 0)),
            pl.BlockSpec((None, k, tn),
                         lambda j, v, g, t, off: (g[v], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, g, t, off: (t[v], j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        name="moe_gmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(g, tile, offsets, rows, weights)


def gmm(rows: jnp.ndarray, weights: jnp.ndarray, group_sizes: jnp.ndarray,
        impl: str = "auto") -> jnp.ndarray:
    """rows [m, k] sorted by group, weights [G, k, n], group_sizes [G]
    -> [m, n] in rows' dtype; rows past sum(group_sizes) come out zero.
    impl: "auto" (the kernel on a TPU, `ragged_dot` elsewhere), "pallas",
    "xla"."""
    m, k = rows.shape
    n = weights.shape[2]
    group_sizes = group_sizes.astype(jnp.int32)
    if impl == "auto":
        impl = "pallas" if _on_tpu() and n % 128 == 0 and k % 128 == 0 \
            else "xla"
    if impl == "xla":
        out = jax.lax.ragged_dot(rows, weights, group_sizes,
                                 preferred_element_type=jnp.float32)
        out = out.astype(rows.dtype)
    else:
        tm = row_tile(m)
        tn = COL_TILE if n % COL_TILE == 0 else 128
        pad = -m % tm
        x = jnp.pad(rows, ((0, pad), (0, 0))) if pad else rows
        out = _gmm_pallas(x, weights, group_sizes, tm, tn)[:m]
    held = jnp.arange(m)[:, None] < jnp.sum(group_sizes)
    return jnp.where(held, out, jnp.zeros((), out.dtype))
