"""Gated delta-rule linear attention with a per-channel decay (KDA, the
Kimi Linear layer) for serving: the chunked scan a prefill runs, what
forms its q, k and v from the layer's projection (`kda_conv`), and the
one-step update a decode step runs.

The recurrence.  A head keeps a state matrix S [dk, dv] in float32; for
token t with a key k_t and a query q_t [dk] (the caller normalises and
scales them), a value v_t [dv], a decay a_t in (0, 1]^dk (one number a
KEY CHANNEL, not one a head: `g_t = log a_t` is what is passed, ANY
value <= 0) and a write strength beta_t in [0, 2] (past 1 the factor
`I - beta k k^T` has a negative eigenvalue along k):

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

i.e. the state is decayed a row at a time, then CORRECTED by a rank-one
term that needs `k_t^T Diag(a_t) S_{t-1}` before the write.  Not
`ops/ssm.py`'s rule (a scalar decay a head and a plain outer-product
input): here the update reads the decayed state before it writes it.

`kda_update` (Pallas, `pallas_call(name="kda_update")`): one decode step
of one layer.  The state array [layers, lanes, H, dk, dv] is read and
written THROUGH `input_output_aliases`; the grid is a work list of the
LIVE lanes (`ops/ssm.live_lanes`), one step a lane, the layer a
scalar-prefetched index.  A lane that holds no request gets no step:
its 4 MB a layer are neither read nor written.  Inside a step every
head's [dk, dv] block is walked twice (decay and `k^T S`; then the
write and `q^T S`), dk down the sublanes and dv along the lanes, so both
sums are adds of whole registers; the per-channel vectors (a, k, q)
arrive as [dk, H] so that a head's is a column.

`kda_scan` (Pallas, `pallas_call(name="kda_scan")`): the same
recurrence over whole rows in the chunked WY form.  Within a chunk of C
positions, with G_t = g_1 + ... + g_t (per channel, from the chunk's
start) and S_0 the state at its start,

    A_tj = beta_t sum_d k_t[d] k_j[d] exp(G_t[d] - G_j[d])      (j < t)
    (I + A) U = beta * (V - (K * exp(G)) S_0)       a TRIANGULAR solve
    o_t = (q_t * exp(G_t))^T S_0 + sum_{j<=t} B_tj u_j,
        B_tj = sum_d q_t[d] k_j[d] exp(G_t[d] - G_j[d])
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

Two forms of A and B, chosen by what the CALLER knows of the gate
(`kda_scan(..., unbounded=)`; nothing is clamped in either).

A gate bounded below (GLM's; the form a call that says nothing gets): A
and B are products of K exp(G - G_m) with
K exp(G_m - G), G_m the chunk's MIDDLE (the reference cancels in every
pair; on and below the diagonal the exponents add up to at most 0):
either factor grows with half the chunk, so the chunk is bounded by the
gate's lower bound (C / 2 * |bound| <= 80, float32's range: `max_chunk`;
32 positions at the published -5; pairs above the diagonal may overflow
and are masked).

No bound (the published layer's -exp(A_log) softplus(.), in (-inf, 0]):
every pair (t, j), j < t, is anchored at a position BETWEEN them, so both
exponents are <= 0 and an underflow is the true answer (`_halved_pairs`).
By halving: at the level of half-size s the pairs whose t lies in the
upper and j in the lower half of a block of 2 s positions take the lower
half's last position as anchor; the levels s = 1, 2, 4, ... < C hold
every pair once.  The exponents are SUMS of g over the stretch between
(`_segment_sums`: shifted adds, restarted every s rows), never
differences of two long sums, so a steep step earlier in the chunk costs
a later pair no bits; a level is one masked product over the group's
rows, log2(C) of them for the bounded form's one.

T = (I + A)^-1 by halves too, in both forms (`_unit_lower_inverse`): T <-
T - T A_off T a level, A_off the part of A between a block's two halves;
its intermediate terms are entries of true inverses, where the terms of
the finite product (I - A)(I + A^2)(I + A^4)... reach binomial(C, C / 2)
beta^C on repeated keys and cancel to nothing (numpy float32, C = 32, a
repeated unit key: beta 0.99 at g = -0.01 a step the product 4.0 off,
beta 1.99 8e5; this 1e-7 ... 5e-6; on the chip the product is 2.3 % of the
bounded kernel's time faster, 7.57 against 7.75 ms at [1, 8192, 64 x 128]).

With T, W = T (beta K exp(G)) and U0 = T (beta V): U = U0 - W S_0.

Where the bytes live.  q, k, v, g are read as [b, T, H dk]: a head's
rows are a column block of one lane tile, no transpose is made, o
leaves the same way.  The grid is (row, `HEADS` heads, a block of
`POSITIONS` positions), the positions minor and sequential; the heads'
states [dk, dv] live in a VMEM scratch from the row's first block to its
last and reach HBM once, at the end.  Inside a grid step a `fori_loop`
walks GROUPS of `ROWS` positions: everything that does not need the
state is made for the group's chunks at once (`_group_parts`), as
products with [ROWS, ROWS] matrices that are zero outside the diagonal
blocks of C (one tile of the MXU, where a chunk alone fills a sixteenth
of it); then the chunks one after the other, two products with the state
each.  Every chain of products is a chain of MXU latencies, and the
compiler issues them in the order they are written: so each stage is
written for all the step's heads, side by side, before the next (9.5 ms
a layer at two heads, 8.4 at four; a head after the other: 13.6).  A
block wholly at or past a row's `lengths` gets no step: its inputs are
not fetched, its o is zeros, the state passes through (the caller's g = 0
and beta = 0 there make it the identity anyway; a block the length
crosses is computed whole).  The state returned IS the state at the true
length.  All in float32 at `Precision.HIGHEST`: a lane keeps that state
for hundreds of steps.  Interpret mode runs any shape; the chip takes
what it can tile (`scan_tiles`).

`kda_conv` (Pallas, `pallas_call(name="kda_conv")`): what the scan reads
of a layer's projection `h W_qkv` [b, T, 3 H dk], in ONE pass over it:
the short causal convolution down each column, silu, the split into
heads, q and k of unit length.  It reads the bfloat16 projection once
and writes q, k, v float32 once (1.21 GB at [1, 8192, 3 x 64 x 128]:
1.84 ms on a v5e, 80 % of the memory's rate; no MXU), where the XLA
expression wrote the convolved projection as float32 and read it back
around the reduction (13.0 ms alone; PERF.md section 6, PR 60).  The
grid is (row, a block of positions, a block of heads); a step takes the
same block of each of the three sections and the sublane tile of rows
before it (the convolution's reach), and writes its outputs at its own
index: nothing is carried.  Zeros from a row's length on; a block
wholly past it is not fetched.
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
_HI = lax.Precision.HIGHEST
POSITIONS = 512     # a grid step's block of positions
ROWS = 128          # a group: the chunks whose local parts are made at once
HEADS = 4           # heads a grid step takes
CONV_POSITIONS = 1024   # `kda_conv`: a grid step's block of positions,
CONV_HEADS = 4          # its heads (of each of q, k and v),
CONV_ROWS = 128         # the rows its body takes at once
HALO = 16           # rows fetched before a block: a bfloat16 sublane tile


def _interpret() -> bool:
    return flash_attention._interpret()


def max_chunk(gate_lower_bound: float) -> int:
    """The longest chunk `kda_scan` may take for a log-decay bounded
    below by `gate_lower_bound` (< 0): exp(+-(G - G_middle)) must stay
    in float32."""
    return max(1, 2 * int(80.0 / max(abs(gate_lower_bound), 1e-6)))


def kda_recurrence(q, k, v, g, beta, state=None):
    """The recurrence token by token (the oracle of both kernels).
    q, k, g [T, H, dk]; v [T, H, dv]; beta [T, H]; state [H, dk, dv] or
    None (zeros).  Returns (o [T, H, dv], the state after) in float32."""
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    H, dk, dv = k.shape[1], k.shape[2], v.shape[2]
    if state is None:
        state = jnp.zeros((H, dk, dv), F32)

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[:, :, None] * S
        kS = jnp.einsum("hd,hdv->hv", kt, S, precision=_HI)
        S = S + kt[:, :, None] * (bt[:, None] * (vt - kS))[:, None, :]
        return S, jnp.einsum("hd,hdv->hv", qt, S, precision=_HI)

    state, o = lax.scan(step, state.astype(F32), (q, k, v, g, beta))
    return o, state


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, precision=_HI,
                           preferred_element_type=F32)


def _chunk_sums(g, C: int):
    """The running sum of g [R, dk] down the rows, restarted at every
    chunk of C rows: log2(C) shifted adds (no product: the sum is exact
    float32 adds, and the MXU is what the kernel waits on)."""
    at = lax.broadcasted_iota(jnp.int32, g.shape, 0) % C
    s = 1
    while s < C:
        g = g + jnp.where(at >= s, pltpu.roll(g, s, 0), 0.0)
        s *= 2
    return g


def _segment_sums(g, s: int, C: int, backward: bool = False):
    """The running sum of g [R, dk] down the rows (`backward`: up them),
    inclusive, restarted every `s` positions of a chunk of C rows: shifted
    adds as `_chunk_sums`.  Exact float32 adds of the g's of one stretch."""
    R = g.shape[0]
    p = lax.broadcasted_iota(jnp.int32, g.shape, 0) % C
    at = p % s
    h = 1
    while h < s:
        if backward:    # the row h below, inside the stretch and the chunk
            g = g + jnp.where((at < s - h) & (p < C - h),
                              pltpu.roll(g, R - h, 0), 0.0)
        else:
            g = g + jnp.where(at >= h, pltpu.roll(g, h, 0), 0.0)
        h *= 2
    return g


def _halved_pairs(q, k, g, C: int):
    """[A / beta; B] [2 R, R] for q, k, g [R, dk] of one head, exact for
    any g <= 0: A_tj = sum_d k_t k_j exp(G_t - G_j) strictly below the
    diagonal of each chunk of C, B_tj = sum_d q_t k_j exp(G_t - G_j) on
    and below it, zeros elsewhere.  A level of half-size s anchors the
    pairs (t in the upper, j in the lower half of a block of 2 s
    positions) at the lower half's last row a: exp(G_t - G_a) is the
    forward stretch sum at t, exp(G_a - G_j) the backward one at j less
    g_j; both <= 1, ONE exp a level since a row is upper or lower."""
    R, dk = k.shape
    row = lax.broadcasted_iota(jnp.int32, (2 * R, R), 0) % R
    col = lax.broadcasted_iota(jnp.int32, (2 * R, R), 1)
    lower_part = lax.broadcasted_iota(jnp.int32, (2 * R, R), 0) >= R
    p = lax.broadcasted_iota(jnp.int32, (R, dk), 0) % C
    out = jnp.where(lower_part & (row == col), jnp.concatenate(
        [jnp.zeros((R, 1), F32), jnp.sum(q * k, axis=1, keepdims=True)]),
        0.0)
    s = 1
    while s < C:
        upper = (p // s) % 2 == 1
        e = jnp.exp(jnp.where(
            upper, _segment_sums(g, s, C),
            _segment_sums(g, s, C, backward=True) - g))
        up = jnp.where(upper, e, 0.0)
        pairs = _dot(jnp.concatenate([k * up, q * up]),
                     k * jnp.where(upper, 0.0, e), (((1,), (1,)), ((), ())))
        same = ((row // C == col // C)
                & (row % C // (2 * s) == col % C // (2 * s)))
        out = out + jnp.where(same, pairs, 0.0)
        s *= 2
    return out


def _unit_lower_inverse(As, C: int):
    """(I + A)^-1 for every A [R, R] of the list `As`, each strictly
    lower triangular inside its diagonal blocks of C and zero outside
    them: block by block, T <- T - T A_off T for the half-sizes s = 1, 2,
    4, ... < C, A_off the entries of A between the two halves of a block
    of 2 s positions (the inverse of [[M1, 0], [A21, M2]] is [[T1, 0],
    [-T2 A21 T1, T2]]); every intermediate term is an entry of a true
    inverse.  (NOT A's powers summed, (I - A)(I + A^2)(I + A^4)...: their
    terms reach binomial(C, C / 2) beta^C on keys that repeat and cancel
    to nothing; module docstring.)  The R / C blocks are held SIDE BY
    SIDE, [C, R], and multiplied from the right by the block-diagonal
    [R, R] form: C rows pushed through the MXU's whole width where [R, R]
    by [R, R] pushes R for the same blocks; two products a level from s =
    2 (T is I below it).  The list's chains are independent and are
    written level by level, side by side: the order the products are
    issued in."""
    R = As[0].shape[0]
    n = R // C
    own = (lax.broadcasted_iota(jnp.int32, (R, R), 0) // C
           == lax.broadcasted_iota(jnp.int32, (R, R), 1) // C)
    rowp = lax.broadcasted_iota(jnp.int32, (C, R), 0)
    colp = lax.broadcasted_iota(jnp.int32, (C, R), 1) % C

    def blocks(side):             # [C, R] -> block-diagonal [R, R]
        return jnp.where(own, jnp.concatenate([side] * n), 0.0)

    def between(X, s):            # the same block of 2 s, another half
        return jnp.where((rowp // (2 * s) == colp // (2 * s))
                         & (rowp // s != colp // s), X, 0.0)

    Xs = [sum(A[i * C:(i + 1) * C] for i in range(n)) for A in As]
    invs = [jnp.where(rowp == colp, 1.0, 0.0) - between(X, 1) for X in Xs]
    s = 2
    while s < C:
        mids = [_dot(inv, blocks(between(X, s))) for X, inv in zip(Xs, invs)]
        invs = [inv - _dot(mid, blocks(inv)) for mid, inv in zip(mids, invs)]
        s *= 2
    return [blocks(inv) for inv in invs]


def _column(row):
    """A row [1, n] as a column [n, 1] (a masked sum along the lanes)."""
    n = row.shape[-1]
    eye = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _group_parts(ins, C: int, bounded: bool):
    """What a GROUP of R = n C positions needs that does not depend on
    the carried state, for every head of the list `ins` = [(q, k, v, g,
    beta)] (q, k, g [R, dk]; v [R, dv]; beta [R, 1]), made for the n
    chunks at once as products with [R, R] matrices that are zero
    outside the diagonal blocks of C (at R = 128 one tile of the MXU,
    where a chunk alone would fill a sixteenth).  A head's: (QW: a
    chunk's [Qd; W] [2 C, dk], what multiplies the state in one pass
    over it; U0 [R, dv]; Ke [R, dk]; dec: a chunk's decay [dk, 1]; B
    [R, R]), returned as five lists over the heads.  The heads are
    independent: every stage is written for all of them before the
    next.  `bounded`: the caller's gate has a lower bound that lets the
    chunk's middle anchor every pair; else the pairs by halves, exact
    for any g <= 0 (the module's docstring)."""
    R, dk = ins[0][1].shape
    n = R // C
    row = lax.broadcasted_iota(jnp.int32, (R, R), 0)
    col = lax.broadcasted_iota(jnp.int32, (R, R), 1)
    own = row // C == col // C                        # the same chunk

    def of_chunk(x, at):          # row `at` of every chunk, over its rows
        x = x.reshape(n, C, x.shape[-1])[:, at:at + 1]
        return jnp.broadcast_to(x, (n, C, x.shape[-1])).reshape(R, -1)

    Gs = [_chunk_sums(g, C) for _, _, _, g, _ in ins]
    if bounded:
        ABs = []
        for (q, k, _, _, _), G in zip(ins, Gs):
            rel = G - of_chunk(G, (C - 1) // 2)       # from the middle
            up = jnp.exp(rel)
            ABs.append(_dot(jnp.concatenate([k * up, q * up]),
                            k * jnp.exp(-rel), (((1,), (1,)), ((), ()))))
        As = [jnp.where(own & (col < row), AB[:R], 0.0) for AB in ABs]
    else:
        ABs = [_halved_pairs(q, k, g, C) for q, k, _, g, _ in ins]
        As = [AB[:R] for AB in ABs]
    Ts = _unit_lower_inverse(
        [A * beta for A, (_, _, _, _, beta) in zip(As, ins)], C)
    parts = []
    for (q, k, v, g, beta), G, AB, T in zip(ins, Gs, ABs, Ts):
        decay = jnp.exp(G)                # from the chunk's start: <= 1
        # to the chunk's end: a difference of two sums from its start
        # under a bound, else the sum of the stretch itself
        last = of_chunk(G, C - 1) if bounded else None
        WU = _dot(T, jnp.concatenate([k * decay * beta, v * beta], axis=1))
        Qd = q * decay
        QW = [jnp.concatenate([Qd[i * C:(i + 1) * C],
                               WU[i * C:(i + 1) * C, :dk]])
              for i in range(n)]
        parts.append((QW, WU[:, dk:], k * jnp.exp(
            last - G if bounded
            else _segment_sums(g, C, C, backward=True) - g),
                      [_column(jnp.exp(G[(i + 1) * C - 1:(i + 1) * C]))
                       for i in range(n)],
                      jnp.where(own & (col <= row), AB[R:], 0.0)))
    return [list(x) for x in zip(*parts)]             # a list a part


def _scan_kernel(lens_ref,                            # scalar prefetch
                 q_ref, k_ref, v_ref, g_ref, beta_ref,
                 o_ref, s_ref, state, *, C: int, R: int, heads: int,
                 bounded: bool):
    """One position block of `heads` heads of one row.  q_ref, k_ref,
    g_ref [1, P, heads dk]; v_ref, o_ref [1, P, heads dv]; beta_ref
    [1, P, H]; s_ref [1, heads, dk, dv]; `state` the heads' [dk, dv] in
    VMEM, which lives across the row's position blocks."""
    i, j = pl.program_id(0), pl.program_id(2)
    P, H = beta_ref.shape[1], beta_ref.shape[2]
    dk, dv = state.shape[1], state.shape[2]
    head0 = pl.program_id(1) * heads

    @pl.when(j == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    live = j * P < lens_ref[i]

    @pl.when(live)
    def _():
        lane = lax.broadcasted_iota(jnp.int32, (R, H), 1)

        def group(r, S):
            rows = pl.ds(pl.multiple_of(r * R, R), R)
            betas = beta_ref[0, rows, :]
            ins = []
            for h in range(heads):
                kk = slice(h * dk, (h + 1) * dk)
                vv = slice(h * dv, (h + 1) * dv)
                ins.append((
                    q_ref[0, rows, kk], k_ref[0, rows, kk],
                    v_ref[0, rows, vv], g_ref[0, rows, kk],
                    jnp.sum(jnp.where(lane == head0 + h, betas, 0.0),
                            axis=1, keepdims=True)))
            QW, U0, Ke, dec, Bm = _group_parts(ins, C, bounded)
            # the chunks, from their state: `U = U0 - W S` beside the
            # rows `Qd S`, then `S <- dec S + Ke^T U`
            S = list(S)
            qs, us = ([[] for _ in range(heads)] for _ in range(2))
            for c in range(R // C):
                at = slice(c * C, (c + 1) * C)
                xs = [_dot(QW[h][c], S[h]) for h in range(heads)]
                for h in range(heads):                # x [2 C, dv]
                    qs[h].append(xs[h][:C])
                    us[h].append(U0[h][at] - xs[h][C:])
                ups = [_dot(Ke[h][at], us[h][-1], (((0,), (0,)), ((), ())))
                       for h in range(heads)]
                S = [dec[h][c] * S[h] + ups[h] for h in range(heads)]
            for h in range(heads):                    # o = Qd S + B U
                o_ref[0, rows, h * dv:(h + 1) * dv] = jnp.concatenate(
                    qs[h]) + _dot(Bm[h], jnp.concatenate(us[h]))
            return tuple(S)

        S = lax.fori_loop(0, P // R, group,
                          tuple(state[h] for h in range(heads)))
        for h in range(heads):
            state[h] = S[h]

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        s_ref[0] = state[...]


def heads_a_step(H: int, most: int | None = None) -> int:
    """The heads a grid step takes: the largest divisor of H that is at
    most `most` (`HEADS`, where the call says nothing)."""
    return max(h for h in range(1, (most or HEADS) + 1) if H % h == 0)


def _held(lens, i, j, P: int):
    """The position block a step (row i, block j) of blocks of P fetches:
    its own, or past the row's length the block the length ends in (the
    one the step before fetched: nothing is read)."""
    return jnp.minimum(j, jnp.maximum(lens[i] - 1, 0) // P)


def scan_tiles(dk: int, dv: int, chunk: int,
               kernel: str = "kda_scan") -> None:
    """What the compiled kernel can tile: a head's block of the [b, T,
    H dk] views is a column block of whole lane tiles, a chunk whole
    sublane tiles.  (Interpret mode runs any shape.)"""
    if dk % 128 or dv % 128 or chunk % 8:
        raise ValueError(
            f"{kernel} on the chip takes dk and dv multiples of 128 and "
            f"a chunk a multiple of 8; got dk={dk}, dv={dv}, "
            f"chunk={chunk}")


def kda_scan(q, k, v, g, beta, chunk: int, lengths=None,
             unbounded: bool = False):
    """The recurrence over whole rows, chunked.

    q, k [b, T, H, dk] (normalised; q scaled); v [b, T, H, dv]; g
    [b, T, H, dk] float32 (the log decay, <= 0, ZERO past a row's true
    length); beta [b, T, H] float32 in [0, 2] (ZERO past it); `chunk`
    positions a chunk; `lengths` [b] int32 or None: a position block
    wholly at or past a row's length gets no step (its o is 0, the state
    passes through: what g = 0 and beta = 0 there give anyway).
    `unbounded`: False, the caller's gate has a lower bound and `chunk` is
    within `max_chunk` of it (the caller's to hold): one product anchors a
    chunk's pairs at its middle; True: any g <= 0, the pairs anchored by
    halves.  Returns (o [b, T, H, dv] float32, the state
    after the last position [b, H, dk, dv] float32)."""
    b, T, H, dk = k.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    interpret = _interpret()
    if not interpret:
        scan_tiles(dk, dv, C)
    chunks = -(-T // C)
    R = C * max(1, min(ROWS // C, POSITIONS // C, chunks))  # a group
    P = R * max(1, min(POSITIONS // R, -(-T // R)))   # a position block
    heads = heads_a_step(H)
    pad = -T % P
    q, k, v, g = (jnp.pad(a.astype(F32), ((0, 0), (0, pad), (0, 0), (0, 0))
                          ).reshape(b, T + pad, -1) for a in (q, k, v, g))
    beta = jnp.pad(beta.astype(F32), ((0, 0), (0, pad), (0, 0)))
    if lengths is None:
        lengths = jnp.full((b,), T, jnp.int32)

    def block(i, h, j, lens):
        return (i, _held(lens, i, j, P), h)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, H // heads, (T + pad) // P),
        in_specs=[pl.BlockSpec((1, P, heads * dk), block),
                  pl.BlockSpec((1, P, heads * dk), block),
                  pl.BlockSpec((1, P, heads * dv), block),
                  pl.BlockSpec((1, P, heads * dk), block),
                  pl.BlockSpec((1, P, H), lambda i, h, j, lens:
                               (i, _held(lens, i, j, P), 0))],
        out_specs=[pl.BlockSpec((1, P, heads * dv),
                                lambda i, h, j, lens: (i, j, h)),
                   pl.BlockSpec((1, heads, dk, dv),
                                lambda i, h, j, lens: (i, h, 0, 0))],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), F32)],
    )
    o, S = pl.pallas_call(
        functools.partial(_scan_kernel, C=C, R=R, heads=heads,
                          bounded=not unbounded),
        name="kda_scan",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, T + pad, H * dv), F32),
                   jax.ShapeDtypeStruct((b, H, dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v, g, beta)
    return o[:, :T].reshape(b, T, H, dv), S


def _conv_kernel(lens_ref,                            # scalar prefetch
                 xq_ref, xk_ref, xv_ref, bq_ref, bk_ref, bv_ref,
                 wq_ref, wk_ref, wv_ref,
                 q_ref, k_ref, v_ref, *, dk: int, R: int):
    """One position block of `heads` heads of one row, for each of the
    three sections of the projection: x*_ref [1, P, heads dk] (the
    projection's dtype), b*_ref [1, HALO, heads dk] the rows before it,
    w*_ref [K, heads dk] float32; q_ref, k_ref, v_ref [1, P, heads dk]
    float32.  A head's [R, dk] at a time: the K - 1 shifted copies are
    rolls down the sublanes of the rows with the 8 before them on top."""
    i, j = pl.program_id(0), pl.program_id(1)
    P, K = q_ref.shape[1], wq_ref.shape[0]
    n = lens_ref[i]
    live = j * P < n

    @pl.when(live)
    def _():
        def rows(r, carry):
            r0 = pl.multiple_of(r * R, R)
            keep = (j * P + r0 + lax.broadcasted_iota(jnp.int32, (R, 1), 0)
                    < n)
            for x_ref, b_ref, w_ref, o_ref, scale in (
                    (xq_ref, bq_ref, wq_ref, q_ref, dk ** -0.5),
                    (xk_ref, bk_ref, wk_ref, k_ref, 1.0),
                    (xv_ref, bv_ref, wv_ref, v_ref, None)):
                for h in range(o_ref.shape[2] // dk):
                    at = slice(h * dk, (h + 1) * dk)
                    # the 8 rows before: the block's own, or (its first
                    # rows) the block's before; zeros before position 0
                    own = x_ref[0, pl.ds(pl.multiple_of(
                        jnp.maximum(r0 - HALO, 0), HALO), HALO),
                                at].astype(F32)
                    before = jnp.where(
                        r > 0, own,
                        jnp.where(j > 0, b_ref[0, :, at].astype(F32), 0.0))
                    x = jnp.concatenate(
                        [before[HALO - 8:],
                         x_ref[0, pl.ds(r0, R), at].astype(F32)])
                    acc = x * w_ref[K - 1:K, at]
                    for s in range(1, K):
                        acc = acc + (pltpu.roll(x, s, 0)
                                     * w_ref[K - 1 - s:K - s, at])
                    act = jax.nn.silu(acc[8:])
                    if scale is not None:             # unit length
                        act = act * lax.rsqrt(jnp.sum(
                            act * act, axis=1, keepdims=True) + 1e-6)
                        if scale != 1.0:
                            act = act * scale
                    o_ref[0, pl.ds(r0, R), at] = jnp.where(keep, act, 0.0)
            return carry

        lax.fori_loop(0, P // R, rows, 0)

    @pl.when(jnp.logical_not(live))
    def _():
        for o_ref in (q_ref, k_ref, v_ref):
            o_ref[...] = jnp.zeros_like(o_ref)


def kda_conv(proj, conv_w, H: int, lengths=None):
    """What `kda_scan` takes of a layer's projection, in one pass over it:
    the short causal convolution down each column, silu, the split into
    heads, q and k of unit length, q scaled by dk ** -0.5.

    proj [b, T, 3 H dk] (q's columns, then k's, then v's; read ONCE, in
    its own dtype); conv_w [K, 3 H dk], K - 1 <= 8 (row K - 1 multiplies
    the position itself, row 0 the one K - 1 before; zeros before
    position 0); `lengths` [b] int32 or None.  Returns q, k, v [b, T, H,
    dk] float32, ZEROS at and past a row's length (a position block
    wholly there is not fetched).  The grid is (row, a block of
    `CONV_POSITIONS` positions, `CONV_HEADS` heads); a step writes its
    three blocks at its own index and carries nothing.  Float32
    throughout, the `1e-6` under the root `models/kda_layer._l2norm`'s.
    Interpret mode runs any shape; the chip takes what it can tile
    (`scan_tiles`)."""
    b, T, width = proj.shape
    K = conv_w.shape[0]
    dk = width // (3 * H)
    if K - 1 > 8:
        raise ValueError(f"kda_conv takes a convolution of at most 9 "
                         f"positions; got {K}")
    interpret = _interpret()
    if not interpret:
        scan_tiles(dk, dk, HALO, "kda_conv")
    heads = heads_a_step(H, CONV_HEADS)
    P = min(CONV_POSITIONS, -(-T // HALO) * HALO)     # a position block
    R = CONV_ROWS if P % CONV_ROWS == 0 else HALO
    pad = -T % P
    proj = jnp.pad(proj, ((0, 0), (0, pad), (0, 0)))
    conv_w = conv_w.astype(F32)
    if lengths is None:
        lengths = jnp.full((b,), T, jnp.int32)
    nh = H // heads                       # column blocks a section

    def fetched(i, j, h, lens):
        # at or past the length: the block the row's last live step read
        return _held(lens, i, j, P), jnp.where(j * P < lens[i], h, nh - 1)

    def block(section):
        def index(i, j, h, lens):
            at, col = fetched(i, j, h, lens)
            return (i, at, section * nh + col)
        return pl.BlockSpec((1, P, heads * dk), index)

    def before(section):
        def index(i, j, h, lens):
            at, col = fetched(i, j, h, lens)
            return (i, jnp.maximum(at * (P // HALO) - 1, 0),
                    section * nh + col)
        return pl.BlockSpec((1, HALO, heads * dk), index)

    def weights(section):
        return pl.BlockSpec((K, heads * dk), lambda i, j, h, lens:
                            (0, section * nh + fetched(i, j, h, lens)[1]))

    out = pl.BlockSpec((1, P, heads * dk), lambda i, j, h, lens: (i, j, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, (T + pad) // P, nh),
        in_specs=([block(s) for s in range(3)]
                  + [before(s) for s in range(3)]
                  + [weights(s) for s in range(3)]),
        out_specs=[out, out, out],
    )
    q, k, v = pl.pallas_call(
        functools.partial(_conv_kernel, dk=dk, R=R),
        name="kda_conv",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, T + pad, H * dk), F32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(lengths.astype(jnp.int32), *[proj] * 6, *[conv_w] * 3)
    return tuple(a[:, :T].reshape(b, T, H, dk) for a in (q, k, v))


def _update_kernel(lanes_ref, layer_ref,              # scalar prefetch
                   s_ref, cols_ref, rows_ref,         # blocked inputs
                   o_ref, y_ref):
    """One lane's step, a head after the other.  s_ref [1, 1, H, dk, dv];
    cols_ref [1, 3, dk, H] = (a, k, q) with a head's vector a COLUMN;
    rows_ref [1, 2, H, dv] = (v, beta repeated along dv)."""
    del lanes_ref, layer_ref                          # the index maps' own
    H, dk, dv = s_ref.shape[2], s_ref.shape[3], s_ref.shape[4]
    a_all, k_all, q_all = cols_ref[0, 0], cols_ref[0, 1], cols_ref[0, 2]
    for h in range(H):                        # unrolled: static columns
        def col(x):
            return jnp.broadcast_to(x[:, h:h + 1], (dk, dv))

        a, k, q = col(a_all), col(k_all), col(q_all)
        v = rows_ref[0, 0, h:h + 1, :]                # [1, dv]
        beta = rows_ref[0, 1, h:h + 1, :]
        # first pass: the decayed state, and k^T of it
        s = a * s_ref[0, 0, h].astype(F32)
        u = beta * (v - jnp.sum(k * s, axis=0, keepdims=True))
        # second pass: the rank-one write, and q^T of the result
        s = s + k * u
        o_ref[0, 0, h] = s.astype(o_ref.dtype)
        y_ref[0, h:h + 1, :] = jnp.sum(q * s, axis=0, keepdims=True)


def kda_update(state, layer, lanes, count, q, k, v, g, beta):
    """One token's update of layer `layer` for the `count` lanes
    `lanes[:count]` (`ops/ssm.live_lanes`), in place.

    state [layers, lanes, H, dk, dv] float32 (donated: the result
    aliases it); layer a scalar int32; q, k, g [lanes, H, dk] (q scaled,
    g the log decay); v [lanes, H, dv]; beta [lanes, H].  Returns (state,
    o [lanes, H, dv] float32 = S^T q after the write; rows of lanes
    outside the list are 0)."""
    L, nb, H, dk, dv = state.shape
    cols = jnp.stack([jnp.exp(g.astype(F32)), k.astype(F32),
                      q.astype(F32)], axis=1)          # [nb, 3, H, dk]
    cols = jnp.swapaxes(cols, 2, 3)                    # [nb, 3, dk, H]
    rows = jnp.stack([v.astype(F32), jnp.broadcast_to(
        beta.astype(F32)[..., None], (nb, H, dv))], axis=1)

    def state_map(i, lanes, layer):
        return (layer[0], lanes[i], 0, 0, 0)

    def lane_map4(i, lanes, layer):
        return (lanes[i], 0, 0, 0)

    def lane_map3(i, lanes, layer):
        return (lanes[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(count,),                        # the device's own number
        in_specs=[pl.BlockSpec((1, 1, H, dk, dv), state_map),
                  pl.BlockSpec((1, 3, dk, H), lane_map4),
                  pl.BlockSpec((1, 2, H, dv), lane_map4)],
        out_specs=[pl.BlockSpec((1, 1, H, dk, dv), state_map),
                   pl.BlockSpec((1, H, dv), lane_map3)],
    )
    block = H * dk * dv * state.dtype.itemsize
    new, y = pl.pallas_call(
        _update_kernel,
        name="kda_update",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((nb, H, dv), F32)],
        # operand 2 (after the two prefetched scalars) is the state
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the lane's state in and out, double-buffered, and room
            vmem_limit_bytes=max(32 << 20, 6 * block)),
        interpret=_interpret(),
    )(lanes, jnp.reshape(layer, (1,)).astype(jnp.int32), state, cols, rows)
    listed = jnp.any((lanes[None, :] == jnp.arange(nb)[:, None])
                     & (jnp.arange(nb)[None, :] < count), axis=1)
    return new, jnp.where(listed[:, None, None], y, 0.0)


def update_cost(H: int, dk: int, dv: int, lane_steps: float
                ) -> tuple[float, float]:
    """(flops, bytes) the `kda_update` calls NEED for `lane_steps`
    (lane, layer, step) triples that were work: the lane's state read
    and written once (float32), a, k, q, v, beta in and o out (float32),
    and a state element's decay, its two sums and its write (a multiply,
    two multiply-adds, a multiply-add)."""
    nbytes = 2 * 4 * H * dk * dv + 4 * H * (3 * dk + 2 * dv + 1)
    return 7.0 * H * dk * dv * lane_steps, float(nbytes) * lane_steps


def scan_cost(H: int, dk: int, dv: int, chunk: int, positions: float,
              rows: float = 0.0, halved: bool = False
              ) -> tuple[float, float]:
    """(flops, bytes) the `kda_scan` calls NEED for `positions` true
    positions in `rows` rows: q, k, g, v in and o out once (float32),
    beta, the state written a row; and a (head, chunk)'s products as the
    kernel forms them (A and B, once under a bounded gate and once a
    LEVEL by halves, `halved`: log2(chunk) masked products over the
    chunk; the 2 (log2(chunk) - 1) products of the inverse; W and U0;
    `[Qd; W] S`; `B U`; `Ke^T U`), a multiply-add two operations.  Six
    bfloat16 passes a float32 product are the chip's price, not the
    algorithm's: not counted."""
    C = chunk
    levels = max(0, (C - 1).bit_length())
    inverse = 2 * max(0, levels - 1)
    a_chunk = ((levels if halved else 1) * 2 * C * C * (2 * dk)  # A, B
               + inverse * 2 * C ** 3
               + 2 * C * C * (dk + dv)                # W, U0
               + 2 * (2 * C) * dk * dv                # [Qd; W] S
               + 2 * C * C * dv                       # B U
               + 2 * C * dk * dv)                     # Ke^T U
    nbytes = (4 * H * (3 * dk + 2 * dv + 1) * positions
              + 4 * H * dk * dv * rows)
    return float(a_chunk) * H * positions / C, float(nbytes)
