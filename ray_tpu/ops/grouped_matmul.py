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

Both tiles follow the call's shape and nothing else (`row_tile`,
`col_tile`; the comment over the constants says why): a call of few
rows walks 128-row tiles, a call of many rows 256-row tiles, and both
fetch a hit expert's weights in blocks of up to 1,024 columns (8 MiB at
`k` = 4096).  The kernel asks for the VMEM its blocks need
(`vmem_bytes`).  An output element is one whole-`k` contraction inside
one block, so no tile changes a bit of it.

The visit axis of the grid is bounded by the number of visits that are
work, a value the device holds (`visits`' `total`, as `paged_attn`'s
grid is bounded by its plan's count): the list has the static length
`visits_static`, the most visits there can be, and the entries past
`total` pad it and are never walked.  A layer that holds a quarter of
the experts, or whose rows are mostly padding, pays for the visits its
rows make and not for the list; with no row at all no step runs, the
output is left unwritten, and `gmm` zeroes it as it zeroes every row
past `sum(group_sizes)`.

Who zeroes what.  The kernel writes the rows its visits name and no
other, so a row past `sum(group_sizes)` holds whatever the buffer held
(NaN as well) until `gmm`'s `where` at its end, a pass over the whole
[m, n] output.  A caller pays it for the rows it hands over:
`models/routed.routed_ffn` hands a block of the sorted list at a time
and only the blocks that hold a held row, and every output meets that
`where` before it meets a router weight.

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

# The tiles follow the call's shape (`row_tile`, `col_tile`); the kernel
# alone on a v5e picked them (PERF.md section 5, PR 43).
#
# Rows.  Pallas has ONE weight block in flight, and a group whose rows
# cross a row-tile boundary is visited twice, the second visit
# multiplying with no copy behind it.  Few rows (decode: 64 lanes x 4 over
# 64 experts; a prefill wave of up to SMALL_ROWS rows) are bound by the
# copy of the weights, so their row tile is the MXU's 128 rows: a 16-row
# tile had thirteen such visits in LFM2's decode and fed the MXU no
# faster, which loads a weight tile for 16 rows as for 128.  Many rows
# are bound by the multiply and walk 256-row tiles.
#
# Columns.  1,024 read fastest or tied at every served shape.  Narrower
# blocks pay a gap after each at `n` = 4096 (0.3-0.6 us a 512-column
# block, pieces of 1 KB that lie 8 KB apart; none at 3072 or 2048), and
# re-read the row tile `n / tn` times where the rows are many.  Wider ones pay their own copy bare once a call, the first
# block's, which hides behind nothing (20 us of a 1.08 ms call at 16
# MiB), and Mosaic compiles a whole-expert call in 3 s against 1.
# WEIGHT_BLOCK_BYTES only keeps two buffers of a block inside VMEM where
# `k` is far larger than any served.
ROW_TILE_SMALL = 128
ROW_TILE_LARGE = 256
SMALL_ROWS = 1024
COL_TILE = 1024
WEIGHT_BLOCK_BYTES = 16 << 20


def _on_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def _interpret() -> bool:
    # the flash kernel's rule (compiled on a TPU, interpreted on the
    # CPU), asked where it lives so that whoever steers it steers both
    return flash_attention._interpret()


def row_tile(m: int) -> int:
    """The row tile `gmm` walks `m` rows by (fewer rows than a small
    tile are ONE tile of whole sublane tiles)."""
    if m > SMALL_ROWS:
        return ROW_TILE_LARGE
    return min(ROW_TILE_SMALL, m + -m % 16)


def col_tile(k: int, n: int, itemsize: int) -> int:
    """The column tile `gmm` walks [G, k, n] weights by: the widest
    divisor of `n` in lane tiles (`n` itself where there is none) up to
    COL_TILE whose weight block `k x tn x itemsize` stays within
    WEIGHT_BLOCK_BYTES, the narrowest where none does."""
    tiles = [t for t in range(128, n + 1, 128) if n % t == 0] or [n]
    return max([t for t in tiles if t <= COL_TILE
                and k * t * itemsize <= WEIGHT_BLOCK_BYTES] or tiles[:1])


def vmem_bytes(tm: int, k: int, tn: int, itemsize: int) -> int:
    """What `moe_gmm` asks of VMEM for its blocks: the weight block, the
    row tile and the output tile, each double-buffered, the float32
    product before it is stored, and room for the compiler's own."""
    blocks = (k * tn + tm * k + tm * tn) * itemsize
    return 2 * blocks + tm * tn * 4 + (8 << 20)


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
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(tm, k, tn, weights.dtype.itemsize)),
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
        tn = col_tile(k, n, weights.dtype.itemsize)
        pad = -m % tm
        x = jnp.pad(rows, ((0, pad), (0, 0))) if pad else rows
        out = _gmm_pallas(x, weights, group_sizes, tm, tn)[:m]
    held = jnp.arange(m)[:, None] < jnp.sum(group_sizes)
    return jnp.where(held, out, jnp.zeros((), out.dtype))
