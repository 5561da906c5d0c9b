"""State-space (selective scan) layers for serving: the chunked scan a
prefill runs and the one-step update a decode step runs.

The recurrence (Mamba-2's; the H heads lie in G groups of H / G
adjacent heads, and the heads of a group share B and C: head h reads
group h // (H / G)).  A head keeps a state matrix h [P, N] in float32;
for token t, with dt_t > 0 and A < 0 scalars of the head, x_t [P], B_t,
C_t [N] of the head's group:

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (outer) B_t
    y_t = h_t C_t                        (the caller adds D * x_t)

**Layout.**  A lane's state in one layer is held as [N, H * P] float32
(N = 128 rows of H * P = 4096 or 8192 columns at the served widths),
the heads' [P, N] matrices transposed and side by side, a group's heads
adjacent.  So B_t and C_t are the only COLUMNS in the update (N long, one
pair a group); dt, the decay and x are rows that broadcast down the
sublanes, and `y = sum_n C[n] * h[n, :]` is a sum over rows: adds of
whole vector registers, no reduction across lanes.  All layers' lanes
are ONE array [layers, lanes, N, H * P].

`ssm_update` (Pallas, `pallas_call(name="ssm_update")`): one decode
step of one layer.  The state array is read and written THROUGH
`input_output_aliases`: the kernel's grid is a work list of (LIVE
lane, group) pairs (`live_lanes`), one step a pair, bounded by a count
the device holds, with the layer a scalar-prefetched index.  A step's
block is ONE group's columns of one lane's state, [N, H * P / G], under
that group's B and C (2 MB at one group of 4096 columns, 0.5 MB at eight
of 8192: a lane's whole 4 MB there, in and out and double-buffered,
would pass a v5e's scoped VMEM).  A lane that holds no request gets no
step: its state is neither read nor written, and it is bit-unchanged
afterwards.  Nothing the size of a layer's lanes is copied or selected
over.

`ssd_scan` (XLA einsums under `jax.named_scope("ssd_scan")`): the same
recurrence over whole rows, chunked ("SSD").  In a chunk of Q positions Y
= (L o (C B^T)) (dt X), L[i, j] = exp(sum_{j<k<=i} dt_k A) for i >= j.
That, each chunk's input to the state and what a carried state adds are
matmuls BATCHED over the chunks, outside the loop; the `lax.scan` carries
the state ALONE and stacks it a chunk's start, chunk major.  (A loop that
stacked y wrote a chunk's 4 MB in 512-byte pieces, 140 us where the bytes
take 5: the function says more.)  L comes from differences of one cumsum
of dt A in float32, masked BEFORE the exponential.  The caller sets dt = 0
past a row's true length, so the state returned IS the state there.  What
feeds the state is float32 at `Precision.HIGHEST`: a lane keeps it long.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import flash_attention

F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def _interpret() -> bool:
    # the flash kernel's rule, asked where it lives (ops/grouped_matmul)
    return flash_attention._interpret()


# What a serving module whose lanes keep a state matrix reports of the
# two kernels that fill and update it (models/serving.ServingSpec
# .counters): the one-step update's work list, and prefill_scan_chunks /
# prefill_scan_chunks_dense = the share of the chunked scan's chunks that
# lie below the rows' true lengths.
SCAN_COUNTERS = {
    "ssm_lane_steps": "Lane states the one-step state-space update read "
                      "and wrote: live lanes x decode steps x state-space "
                      "layers",
    "prefill_scan_chunks": "Chunks of the chunked state-space scan below "
                           "the rows' true lengths, summed over prefill "
                           "programs and state-space layers",
    "prefill_scan_chunks_dense": "Chunks of the padded prefill programs "
                                 "the chunked state-space scan walked, "
                                 "summed likewise",
}


def update_work(layers: int, lanes: int, steps: int) -> tuple[dict, dict]:
    """`ServingSpec.decode_work`'s part of `layers` such layers: the
    lane states a window of `steps` steps over `lanes` live lanes
    updates."""
    work = {"ssm_lane_steps": lanes * steps * layers}
    return work, work


def scan_work(layers: int, chunk: int, true_lens, bucket: int
              ) -> tuple[dict, dict]:
    """`ServingSpec.prefill_work`'s part of `layers` such layers: the
    scan walks every `chunk` positions of the padded program; the chunks
    below a row's true length are work."""
    below = layers * sum(-(-int(n) // chunk) for n in true_lens)
    return {"prefill_scan_chunks": below,
            "prefill_scan_chunks_dense":
            layers * len(true_lens) * -(-bucket // chunk)}, \
        {"scan_chunks": below}


def live_lanes(live) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The work list of `ssm_update` for lanes `live` [B] bool: (lanes
    [B] int32, the live lanes ascending and then the last of them
    repeated: valid indices no step visits; count, how many are live)."""
    B = live.shape[0]
    count = jnp.sum(live, dtype=jnp.int32)
    # a live lane's place in the list = how many live lanes precede it
    place = jnp.cumsum(live.astype(jnp.int32)) - 1
    lanes = jnp.zeros((B,), jnp.int32).at[
        jnp.where(live, place, B)].set(jnp.arange(B, dtype=jnp.int32),
                                       mode="drop")
    last = lanes[jnp.maximum(count - 1, 0)]
    return jnp.where(jnp.arange(B) < count, lanes, last), count


LANES, SUBLANES = 128, 8         # a float32 vector register's shape


def _update_kernel(lanes_ref, layer_ref,              # scalar prefetch
                   s_ref, x_ref, dt_ref, b_ref, c_ref, alog_ref, d_ref,
                   o_ref, y_ref, bc_ref):
    """One lane's step over one group's columns.  The block [N, cols] is
    walked a register at a time, column tile by column tile and down the
    rows, so that nothing the size of the block is a temporary: a tile
    of the state is loaded, updated, stored and added into y's
    accumulator while it is in registers."""
    del lanes_ref, layer_ref                          # the index maps' own
    n, cols = s_ref.shape[2], s_ref.shape[3]
    tw, th = bc_ref.shape[2], _tile_rows(n)           # a tile: [th, tw]

    def spread(row):
        """[1, n] -> [n, LANES]: entry i of the row along row i (the
        diagonal of the row laid over n sublanes, a masked sum along the
        lanes, broadcast back along them)."""
        eye = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
               == lax.broadcasted_iota(jnp.int32, (n, n), 1))
        col = jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (n, n)), 0.0),
                      axis=1, keepdims=True)
        return jnp.broadcast_to(col, (n, tw))

    # B and C, a value a state ROW, each spread over a register's lanes
    bc_ref[0] = spread(b_ref[0].astype(F32))
    bc_ref[1] = spread(c_ref[0].astype(F32))

    def tile(c, carry):
        at = pl.ds(pl.multiple_of(c * tw, tw), tw)
        x = x_ref[0, :, at].astype(F32)               # [1, tw]
        # steps 4-5 of the mixer: dt = softplus(dt + dt_bias) (the bias
        # is added by the caller, where dt is expanded to a column a
        # channel), A = -exp(A_log), both a scalar a head
        dt = jax.nn.softplus(dt_ref[0, :, at].astype(F32))
        decay = jnp.broadcast_to(jnp.exp(dt * -jnp.exp(alog_ref[:, at])),
                                 (th, tw))
        dtx = jnp.broadcast_to(dt * x, (th, tw))
        acc = jnp.zeros((th, tw), F32)
        for r in range(n // th):                      # unrolled
            rows = pl.ds(r * th, th)
            new = (decay * s_ref[0, 0, rows, at].astype(F32)
                   + bc_ref[0, rows, :] * dtx)
            o_ref[0, 0, rows, at] = new.astype(o_ref.dtype)
            acc = acc + bc_ref[1, rows, :] * new
        y_ref[0, :, at] = (jnp.sum(acc, axis=0, keepdims=True)
                           + d_ref[:, at] * x)
        return carry

    lax.fori_loop(0, cols // tw, tile, 0)


def _tile_rows(n: int) -> int:
    return SUBLANES if n % SUBLANES == 0 else n


def ssm_update(state, layer, lanes, count, x, dt, B, C, A_log, D):
    """One token's update of layer `layer` for the `count` lanes
    `lanes[:count]` (`live_lanes`), in place.

    state [layers, lanes, N, HP] (float32; donated: the result aliases
    it), layer a scalar int32, x [lanes, HP] (after the convolution and
    its activation), dt [lanes, HP] float32 (raw, dt_bias added, a
    head's value repeated over its P columns), B, C [lanes, G, N] (group
    g's are those of columns [g HP / G, (g + 1) HP / G)), A_log, D [HP]
    float32 (a head's value repeated likewise).  Returns (state, y
    [lanes, HP] float32 = h C + D x; rows of lanes outside the list are
    0)."""
    L, nb, N, HP = state.shape
    G = B.shape[1]
    cols = HP // G                   # a group's columns: a step's block
    # a register's width at the served sizes; a debug-sized group
    # narrower than that is one tile
    tw = LANES if cols % LANES == 0 else cols

    # step i is group i % G of the i // G-th listed lane
    def state_map(i, lanes, layer):
        return (layer[0], lanes[i // G], 0, i % G)

    def row_map(i, lanes, layer):
        return (lanes[i // G], 0, i % G)

    def group_map(i, lanes, layer):       # B, C as [lanes * G, 1, N]
        return (lanes[i // G] * G + i % G, 0, 0)

    def const_map(i, lanes, layer):
        return (0, i % G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(count * G,),                    # the device's own number
        in_specs=[
            pl.BlockSpec((1, 1, N, cols), state_map),
            pl.BlockSpec((1, 1, cols), row_map),
            pl.BlockSpec((1, 1, cols), row_map),
            pl.BlockSpec((1, 1, N), group_map),
            pl.BlockSpec((1, 1, N), group_map),
            pl.BlockSpec((1, cols), const_map),
            pl.BlockSpec((1, cols), const_map),
        ],
        out_specs=[pl.BlockSpec((1, 1, N, cols), state_map),
                   pl.BlockSpec((1, 1, cols), row_map)],
        scratch_shapes=[pltpu.VMEM((2, N, tw), F32)],
    )
    block = N * cols * 4
    new, y = pl.pallas_call(
        _update_kernel,
        name="ssm_update",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((nb, 1, HP), F32)],
        # operand 2 (after the two prefetched scalars) is the state
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the state block in and out, double-buffered
            vmem_limit_bytes=max(32 << 20, 6 * block)),
        interpret=_interpret(),
    )(lanes, jnp.reshape(layer, (1,)).astype(jnp.int32),
      state, x[:, None, :], dt[:, None, :], B.reshape(nb * G, 1, N),
      C.reshape(nb * G, 1, N), A_log.astype(F32)[None, :],
      D.astype(F32)[None, :])
    # no step wrote the rows of a lane outside the list: they hold
    # whatever the buffer did
    listed = jnp.any((lanes[None, :] == jnp.arange(nb)[:, None])
                     & (jnp.arange(nb)[None, :] < count), axis=1)
    return new, jnp.where(listed[:, None], y[:, 0], 0.0)


def ssd_scan(x, dt, A, B, C, chunk: int):
    """The recurrence over whole rows, chunked.

    x [b, T, H, P]; dt [b, T, H] float32, after softplus and ZERO past a
    row's true length; A [H] float32 (negative); B, C [b, T, G, N] (head
    h reads group h // (H / G)); `chunk` positions a chunk (a T under it
    is one short chunk; T is padded up to whole chunks with dt = 0).
    Returns (y [b, T, H, P] float32 without the D term, the state after
    the last position [b, N, H * P] float32).

    Mamba-2's four steps.  What happens inside a chunk, a chunk's input
    to the state, and what a state carried into a chunk adds (C h decayed
    from the chunk's start) depend on the chunk's own inputs and on the
    state at its start alone, so they are computed for ALL chunks at
    once, batched over (row, chunk, group).  The loop carries the state,
    h' = decay * h + S_c (4 MB a row at the served widths), and emits it
    at each chunk's START, stacked on the major-most axis: a chunk's slab
    is contiguous.  A loop that emits y a chunk (the form to PR 48) has
    its stacked output laid out by the product inside the loop, positions
    minor and the chunk's index second-minor, and wrote each chunk's 4 MB
    in 512-byte pieces: 140 us a chunk where the bytes take 5, 9.0 of the
    scan's 10.65 ms a layer at 64 chunks (PERF.md section 6, PR 49).

    The compiler takes the SMALL factor of a product for the matmul's
    weights, so [Q, Q] times [Q, P] comes out [P, Q], positions minor,
    whatever is written.  So x is transposed once a chunk going in, the
    state is kept [G, K, P, N] as its product gives it, and y is
    transposed once a chunk coming out: whole rows of [T, H * P]."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    K = H // G                                # heads a group
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, B, C))
    nc = (T + pad) // Q
    with jax.named_scope("ssd_scan"):
        def by_group(eq, lhs, rhs, **kw):
            """`einsum(eq)`, where `eq` names the group axis `g` in both
            operands and in the result.  At ONE group the axis is
            dropped from all three: the one-group contraction itself
            (as a batch axis of length 1 the CPU backend rounds it
            otherwise)."""
            if G > 1:
                return jnp.einsum(eq, lhs, rhs, preferred_element_type=F32,
                                  **kw)
            at = [t.index("g") for t in eq.replace("->", ",").split(",")]
            out = jnp.einsum(eq.replace("g", ""), lhs.squeeze(at[0]),
                             rhs.squeeze(at[1]),
                             preferred_element_type=F32, **kw)
            return jnp.expand_dims(out, at[2])

        def rows(a):      # [b, nc, Q, H]: a head's values along its chunk
            return jnp.moveaxis(a, 3, 2).reshape(b, nc, G, K, 1, Q)

        # views, chunk by chunk: [b, nc, Q, ...]
        xc, dtc, Bc, Cc = (a.reshape(b, nc, Q, *a.shape[2:])
                           for a in (x, dt, B, C))
        # (1) inside every chunk at once.  The running sum of dt * A of
        # each chunk, [b, nc, Q, H], falling.  `cumsum`, not a product
        # with a triangle of ones: on the chip its float32 error is 1e-5
        # of a sum of -43, a product at the default precision rounds to
        # bfloat16 (2e-2), and one at Precision.HIGHEST never came back
        # in a replica's program (PERF.md section 6, PR 39)
        cs = jnp.cumsum(dtc * A, axis=2)
        csh = jnp.moveaxis(cs, 3, 2)                        # [b, nc, H, Q]
        seg = csh[..., :, None] - csh[..., None, :]
        tri = jnp.tril(jnp.ones((Q, Q), bool))
        Lm = jnp.exp(jnp.where(tri, seg, -jnp.inf))         # [b, nc, H, i, j]
        CB = by_group("bcign,bcjgn->bcgij", Cc, Bc)
        # x with a chunk's positions MINOR, [b, nc, G, K, P, j]: ONE
        # transposition a chunk, and behind the barrier it moves x's own
        # dtype, not a float32 copy
        xt = jnp.swapaxes(xc.reshape(b, nc, Q, H * P), 2, 3)
        xt = lax.optimization_barrier(xt.reshape(b, nc, G, K, P, Q))
        xdt = xt.astype(F32) * rows(dtc)
        y = by_group("bcgkij,bcgkpj->bcgkpi",
                     (CB[:, :, :, None] * Lm.reshape(b, nc, G, K, Q, Q)
                      ).astype(x.dtype), xdt.astype(x.dtype))
        # (2) every chunk's input to the state, decayed to the chunk's
        # end, and the chunk's own decay, chunk-major: the loop's operands.
        # The state is [b, G, K, P, N] here, as the product gives it
        to_end = jnp.exp(cs[:, :, -1:] - cs)                # [b, nc, Q, H]
        S = by_group("bcjgn,bcgkpj->cbgkpn", Bc.astype(F32),
                     xdt * rows(to_end), precision=_HI)
        if nc > 1:
            # (3) the loop carries the state alone and emits it at each
            # chunk's START: whole slabs of a stack whose MAJOR axis is
            # the chunk
            def step(h, xs):
                decay, Sc = xs
                return decay.reshape(b, G, K, 1, 1) * h + Sc, h

            h, starts = lax.scan(
                step, jnp.zeros((b, G, K, P, N), F32),
                (jnp.moveaxis(jnp.exp(cs[:, :, -1]), 1, 0), S))
            # (4) what the carried state adds: C_i h, decayed from the
            # chunk's start to i
            y += by_group("bcign,cbgkpn->bcgkpi", Cc.astype(F32), starts) \
                * rows(jnp.exp(cs))
        else:                     # one chunk: a program without a loop
            h = S[0]
        # back to positions major, ONE transposition a chunk: whole rows
        y = jnp.swapaxes(y.reshape(b, nc, H * P, Q), 2, 3)
        h = jnp.moveaxis(h, 4, 1)                           # [b, N, G, K, P]
    return y.reshape(b, nc * Q, H, P)[:, :T], h.reshape(b, N, H * P)
