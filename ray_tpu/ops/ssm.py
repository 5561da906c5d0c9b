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
recurrence over whole rows in the chunked ("SSD") form.  Inside a chunk
of Q positions Y = (L o (C B^T)) (dt * X), L[i, j] = exp(sum_{j<k<=i}
dt_k A) for i >= j, all matmuls; between chunks the state is carried by
a `lax.scan`.  L comes from differences of one cumulative sum of dt * A
in float32, masked BEFORE the exponential (never a quotient of
exponentials).  The caller sets dt = 0 past a row's true length: the
decay is then 1 and the input 0, so the state returned IS the state at
the true length.  What feeds the state (the chunk's input to it and its
decay) is computed in float32 at `Precision.HIGHEST`: a lane keeps that
state for hundreds of steps.
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
    the last position [b, N, H * P] float32)."""
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
        def chunks(a):                        # [b, T, ...] -> [nc, b, Q, ...]
            return jnp.moveaxis(a.reshape(b, nc, Q, *a.shape[2:]), 1, 0)

        def grouped(a):                 # [b, Q, H, ...] -> [b, Q, G, K, ...]
            return a.reshape(b, Q, G, K, *a.shape[3:])

        def by_group(eq, lhs, rhs, **kw):
            """`einsum(eq)`, where `eq` names the group axis `g` in both
            operands and in the result.  At ONE group the axis is
            dropped from all three: the one-group contraction itself,
            bit for bit and program for program (as a batch axis of
            length 1 the CPU backend rounds it otherwise)."""
            if G > 1:
                return jnp.einsum(eq, lhs, rhs, preferred_element_type=F32,
                                  **kw)
            at = [t.index("g") for t in eq.replace("->", ",").split(",")]
            out = jnp.einsum(eq.replace("g", ""), lhs.squeeze(at[0]),
                             rhs.squeeze(at[1]),
                             preferred_element_type=F32, **kw)
            return jnp.expand_dims(out, at[2])

        tri = jnp.tril(jnp.ones((Q, Q), bool))

        def step(h, xs):
            xc, dtc, Bc, Cc = xs        # [b,Q,H,P] [b,Q,H] [b,Q,G,N] x2
            # the running sum of dt * A, [b, Q, H], falling.  `cumsum`, not
            # a product with a triangle of ones: on the chip its float32
            # error is 1e-5 of a sum of -43, a product at the default
            # precision rounds to bfloat16 (2e-2), and one at
            # Precision.HIGHEST never came back in a replica's program
            # (PERF.md section 6, PR 39)
            cs = jnp.cumsum(dtc * A, axis=1)
            csh = jnp.moveaxis(cs, 2, 1)      # [b, H, Q]
            seg = csh[:, :, :, None] - csh[:, :, None, :]
            Lm = jnp.exp(jnp.where(tri, seg, -jnp.inf))     # [b, H, i, j]
            CB = by_group("bign,bjgn->bgij", Cc, Bc)
            xdt = xc.astype(F32) * dtc[..., None]           # [b, Q, H, P]
            y = by_group("bgkij,bjgkp->bigkp",
                         (CB[:, :, None] * Lm.reshape(b, G, K, Q, Q)
                          ).astype(x.dtype),
                         grouped(xdt.astype(x.dtype)))
            # what the carried state adds: C_i h, decayed from the
            # chunk's start to i
            hh = h.reshape(b, N, G, K, P)
            y += by_group("bign,bngkp->bigkp", Cc.astype(F32), hh) \
                * grouped(jnp.exp(cs))[..., None]
            # the state at the chunk's end
            to_end = jnp.exp(cs[:, -1:, :] - cs)            # [b, Q, H]
            hh = (jnp.exp(cs[:, -1]).reshape(b, 1, G, K, 1) * hh
                  + by_group("bjgn,bjgkp->bngkp", Bc.astype(F32),
                             grouped(xdt * to_end[..., None]),
                             precision=_HI))
            return hh.reshape(b, N, H * P), y.reshape(b, Q, H, P)

        h, ys = lax.scan(step, jnp.zeros((b, N, H * P), F32),
                         tuple(chunks(a) for a in (x, dt, B, C)))
        y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * Q, H, P)
    return y[:, :T], h
