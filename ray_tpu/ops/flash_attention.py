"""Pallas flash attention for TPU (FlashAttention-2 style, causal, GQA).

The [b, h, s, s] score matrix never materializes in HBM: the forward kernel
streams KV blocks through VMEM, keeping a running (max, sum, acc) per query
block; the backward is two kernels (dq; dkv) recomputing P from the saved
log-sum-exp, FlashAttention-2 style.

The forward walks the (row, query block, key block) triples that are WORK
and no others (`key_blocks`, `_walk`): nothing above the diagonal and, with
`lengths` (the true lengths of right-padded rows), nothing in a query block
that lies wholly past its row's length, which comes out as zeros.  The
minor grid axis is the walk, bounded by its longest row's count; the causal
mask is applied only in the blocks the diagonal crosses; the log-sum-exp is
an output only of the call that keeps it (for the backward, or to fold a
learned sink into the softmax afterwards).  With `window`
(a sliding-window layer: a query attends its own position and the window -
1 before it) the walk of a query block starts at the key block its band
starts in (`first_key_blocks`), and the blocks the band's lower edge
crosses are masked like the diagonal's.

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
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured on v5e: the BACKWARD kernels run ~1.8x faster at bq=512/bk=1024
# than at 128/128 (bench-350m, b8 x s2048: 12.5 ms vs 22.7 ms fwd+bwd per
# layer-call).  The forward at these blocks (PR 38, PERF.md section 6):
# 1 x 64 heads x 8192 at q/k 192, v 128 in sarvam's prefill program,
# lengths 4,097-8,192: 8.43 ms a call (17.53 on the static grid before
# the walk); kernel alone 14.34 ms at the full length, 9.37 at 6,144,
# 6.51 at 4,097 (19.43 before; each 1.47 over what the program pays, the
# re-layout of a bare 192-wide argument); 1 x 32 x 1024: 0.21 ms (0.39).
# A step that does nothing costs 0.16 us, which is why the walk's axis
# is bounded by a count and not only clamped; the mask outside the
# diagonal's blocks was 0.8 % and the scale over the scores 1 % (left
# where it is: the bytes stay).  PR 57 (PERF.md section 6, the kernel
# alone by form): the running max read and written as the [block_q, 128]
# lane-broadcast array the scratch is, never as a column (`_lanes`), took
# a 512 x 512 step from 1.85 to 1.11 us (the banded calls: -38 % under a
# band of 4,096, -20 ... -26 % under 513 and 128) and a 512 x 1,024 step
# from 2.20 to 2.14; the running sum kept a lane's share and added up
# across lanes once a query block (`_lane_sums`) took 3 ... 6 % more off
# the causal calls and the train step's forward and ~1 % off the banded.
# The edge mask as one iota difference against two scalars, or dropped,
# the scale dropped, and exp2 for exp moved no call by 1 %: left as they
# were.  2 / 4 heads a step read 3 ... 6 % under one: not taken.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30


# ------------------------------------------------------------------ forward
# What one step of the forward walk does, a bit each (`_walk`'s `flag`).
_FIRST, _LAST, _INSIDE, _EDGE = 1, 2, 4, 8


def fit_blocks(sq: int, skv: int, block_q: int = DEFAULT_BLOCK_Q,
               block_k: int = DEFAULT_BLOCK_K) -> tuple[int, int]:
    """The blocks a call runs at: the power-of-two defaults halved until
    they divide the sequence, never below the 128 MXU tile."""
    block_q = min(block_q, sq)
    while sq % block_q and block_q > 128:
        block_q //= 2
    block_k = min(block_k, skv)
    while skv % block_k and block_k > 128:
        block_k //= 2
    return block_q, block_k


def first_key_blocks(sq: int, block_q: int, block_k: int, window: int,
                     xp=np):
    """[query blocks]: the first key block a query block reads under a
    band of `window` positions (a query at t attends t - window + 1 ..
    t): the one its FIRST row's band starts in."""
    q_start = xp.arange(-(-sq // block_q)) * block_q
    return xp.maximum(q_start - (window - 1), 0) // block_k


def key_blocks(sq: int, skv: int, lengths, block_q: int, block_k: int,
               causal: bool = True, xp=np, window: int | None = None):
    """[rows, query blocks]: how many key blocks are WORK for each query
    block.  Causal: those up to the one the block's last row ends in
    (with `window`: from `first_key_blocks` on);
    none for a query block that lies wholly past its row's length
    (`lengths` [rows]; absent: one row, `sq` long, for all).  A block the
    length crosses counts whole: the causal mask keeps its true rows
    from the padded keys."""
    q_start = xp.arange(-(-sq // block_q)) * block_q
    last_key = xp.minimum(q_start + block_q, skv) - 1 if causal \
        else xp.full_like(q_start, skv - 1)
    n = (last_key // block_k + 1)[None, :]
    if window is not None:
        n = n - first_key_blocks(sq, block_q, block_k, window, xp)[None, :]
    if lengths is None:
        return n
    return xp.where(q_start[None, :] < lengths[:, None], n, 0)


def attn_blocks(sq: int, lengths, block_q: int, block_k: int,
                window: int | None = None) -> int:
    """The (row, query block, key block) triples that causal
    self-attention over right-padded rows of `lengths` (host integers)
    multiplies (under a band of `window` positions, if given): what
    `flash_fwd` walks of a dense grid's rows x `sq // block_q` x
    `sq // block_k`."""
    return int(key_blocks(sq, sq, np.asarray(lengths), block_q,
                          block_k, window=window).sum())


# What a serving module whose prefill calls `flash_fwd` reports of it
# (models/serving.ServingSpec.counters): prefill_attn_blocks /
# prefill_attn_blocks_dense = the share of a prefill program's attention
# grid that is under the diagonal and inside its rows' true lengths.
PREFILL_COUNTERS = {
    "prefill_attn_blocks": "(row, query block, key block) triples flash_fwd "
                           "multiplies, a full-prompt prefill program",
    "prefill_attn_blocks_dense": "Rows x query blocks x key blocks of the "
                                 "same programs",
}


def prefill_work(true_lens, bucket: int) -> tuple[dict, dict]:
    """`ServingSpec.prefill_work` of a program of len(true_lens) rows
    padded to `bucket`, every layer's call of which walks the same: the
    triples that are work, and all of them (host arithmetic)."""
    bq, bk = fit_blocks(bucket, bucket)
    return {"prefill_attn_blocks": attn_blocks(bucket, true_lens, bq, bk),
            "prefill_attn_blocks_dense":
            len(true_lens) * -(-bucket // bq) * -(-bucket // bk)}, {}


# What a serving module whose window layers' prefill is `flash_fwd` under
# a band reports beside PREFILL_COUNTERS (which then count the banded
# walk): prefill_swa_blocks / prefill_swa_blocks_dense = the share of the
# causal walk inside the rows' true lengths that the band leaves, and
# prefill_swa_edge_blocks / prefill_swa_blocks = the share of the banded
# walk's steps that pay the mask.
BAND_COUNTERS = {
    "prefill_swa_blocks": "(row, query block, key block) triples flash_fwd "
                          "multiplies under a window layer's band, a "
                          "full-prompt prefill program",
    "prefill_swa_blocks_dense": "The triples the same calls would multiply "
                                "without the band (causal, inside the true "
                                "lengths)",
    "prefill_swa_edge_blocks": "Of prefill_swa_blocks, the triples the "
                               "diagonal or the band's lower edge crosses "
                               "(masked steps)",
}


def band_blocks(sq: int, block_q: int = DEFAULT_BLOCK_Q,
                block_k: int = DEFAULT_BLOCK_K) -> tuple[int, int]:
    """The blocks a BANDED call runs at: `fit_blocks`, the key block no
    longer than the query block, whatever the band's width.  Measured
    alone on the chip at 8,192 positions, 6,144 of them true (PR 57,
    PERF.md section 6), ms a call at 512 x 512 / 512 x 1,024: a band of
    4,096 (128 heads over 8 of 128) 10.84 / 12.01, of 513 (64 heads of
    256 / 128) 3.63 / 4.43, of 128 (64 over 8 of 192 / 128, the
    log-sum-exp kept) 4.02 / 4.86; before `_fwd_kernel` kept its
    running max and sum as lane tiles they read 17.87 / 12.46, 4.71 /
    4.62 and 5.11 / 5.07.  A step now costs what it multiplies (1.1 us
    at 512 x 512, 2.2 at 512 x 1,024), so the shorter key block wins by
    the masked pairs it does not multiply: a query block past a band of
    4,096 walks 9 key blocks of 512 (4,608 keys for its 4,096 + 511) or
    5 of 1,024 (5,120)."""
    block_q, block_k = fit_blocks(sq, sq, block_q, block_k)
    return block_q, min(block_q, block_k)


def edge_blocks(sq: int, lengths, block_q: int, block_k: int,
                window: int | None = None) -> int:
    """Of `attn_blocks`' triples, those `_walk` flags `_EDGE`: the steps
    that build and apply the mask (host arithmetic on the walk's own
    tables)."""
    n_keys = key_blocks(sq, sq, np.asarray(lengths), block_q, block_k,
                        window=window)
    steps = int(key_blocks(sq, sq, None, block_q, block_k,
                           window=window).sum())
    flag = _walk(n_keys, steps, block_q, block_k, True, np,
                 **_band(sq, block_q, block_k, window))[2]
    return int((flag & _EDGE != 0).sum())


def band_work(window: int, true_lens, bucket: int) -> tuple[dict, dict]:
    """`prefill_work` of a program whose `flash_fwd` calls run under a
    band of `window` positions."""
    bq, bk = band_blocks(bucket)
    walked = attn_blocks(bucket, true_lens, bq, bk, window)
    return {"prefill_attn_blocks": walked,
            "prefill_attn_blocks_dense":
            len(true_lens) * -(-bucket // bq) * -(-bucket // bk),
            "prefill_swa_blocks": walked,
            "prefill_swa_blocks_dense":
            attn_blocks(bucket, true_lens, bq, bk),
            "prefill_swa_edge_blocks":
            edge_blocks(bucket, true_lens, bq, bk, window)}, {}


def _band(sq: int, block_q: int, block_k: int, window: int | None, xp=np):
    """`_walk`'s two band arguments; none without a window."""
    return {} if window is None else {
        "first": first_key_blocks(sq, block_q, block_k, window, xp),
        "window": window}


def _walk(n_keys, steps: int, block_q: int, block_k: int, causal: bool, xp,
          first=None, window: int | None = None):
    """The forward kernel's steps: `n_keys` [rows, query blocks]
    (`key_blocks`) -> qi, ki, flag int32 [rows * steps] (`steps` a row,
    the count a full-length row takes) and the steps each row needs
    [rows].  A query block takes one step a key block that is work, or
    ONE step that only writes its zeros.  The steps past a row's count
    name the blocks already resident, so nothing is copied for them, and
    do nothing.  Under a band (`window`, `first` [query blocks] =
    `first_key_blocks`) a query block's key blocks start at its `first`,
    and a block the band's lower edge crosses is masked like one the
    diagonal crosses."""
    per_q = xp.maximum(n_keys, 1)
    ends = xp.cumsum(per_q, axis=1)
    total = ends[:, -1]
    p = xp.arange(steps)[None, :]
    at = xp.minimum(p, total[:, None] - 1)
    qi = (at[:, :, None] >= ends[:, None, :]).sum(-1)

    def of(a):
        return xp.take_along_axis(a, qi, axis=1)

    mine, live = of(per_q), of(n_keys) > 0
    ki = at - (of(ends) - mine)
    if window is None:
        kabs = ki
        crosses = (ki + 1) * block_k - 1 > qi * block_q if causal \
            else xp.zeros_like(ki, dtype=bool)
    else:
        first = xp.broadcast_to(first[None, :], n_keys.shape)
        kabs = ki + of(first)
        crosses = ((kabs + 1) * block_k - 1 > qi * block_q) \
            | (kabs * block_k < (qi + 1) * block_q - window)
    flag = xp.where(
        p < total[:, None],
        (ki == 0) * _FIRST + (ki == mine - 1) * _LAST
        + live * xp.where(crosses, _EDGE, _INSIDE), 0)
    # a block of zeros names the keys its row multiplied last
    resident = xp.maximum(n_keys.max(axis=1) - 1, 0) if window is None \
        else xp.where(n_keys > 0, first + n_keys - 1, 0).max(axis=1)
    ki = xp.where(live, kabs, resident[:, None])
    return tuple(a.astype(xp.int32).reshape(-1)
                 for a in (qi, ki, flag)) + (total,)


def _fwd_kernel(qi_ref, ki_ref, flag_ref, q_ref, k_ref, v_ref, o_ref, *refs,
                sm_scale: float, stride: int, window: int | None = None):
    """One step of the walk of one (batch row, head): `_walk` names its
    query block, its key block and what to do with them.  The key blocks
    of a query block are consecutive steps of the MINOR grid dimension,
    so each step sees one [block_k, d] slice — VMEM stays bounded at ANY
    sequence length (whole-KV residency OOMed scoped vmem at 32k).  The
    running (max, sum, acc) live in scratch, which persists across the
    steps; o (and lse, for the call that keeps it) write out on a query
    block's last.

    q_ref: [block_q, d]; k_ref: [block_k, d]; v_ref: [block_k, dv];
    o_ref: [block_q, dv]; lse_ref: [block_q, 128] (value broadcast across
    lanes — TPU tiles need a 128 minor dim).
    """
    *lse_ref, acc_ref, m_ref, l_ref = refs
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]
    at = pl.program_id(0) * stride + pl.program_id(2)
    flag = flag_ref[at]

    @pl.when(flag & _FIRST != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(edge: bool):
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if edge:                  # the diagonal crosses this block
            qpos = qi_ref[at] * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki_ref[at] * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = qpos >= kpos
            if window is not None:      # the band: the last `window` keys
                keep &= qpos - kpos < window
            s = jnp.where(keep, s, NEG_INF)
        # m: [bq, 128], a row's max in every lane, as the scratch is;
        # l: a lane's share of the row's sum, added up on the last step
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - _lanes(m_cur, block_k))   # [bq, bk] f32
        l_ref[...] = l_ref[...] * alpha + _lane_sums(p)
        acc_ref[...] = (acc_ref[...] * _lanes(alpha, v.shape[1])
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_cur

    pl.when(flag & _EDGE != 0)(functools.partial(update, True))
    pl.when(flag & _INSIDE != 0)(functools.partial(update, False))

    @pl.when(flag & _LAST != 0)
    def _write():
        l = jnp.broadcast_to(jnp.sum(l_ref[...], axis=1, keepdims=True),
                             l_ref.shape)
        l = jnp.where(l == 0.0, 1.0, l)   # nothing admitted: zeros, no NaN
        o_ref[...] = (acc_ref[...] / _lanes(l, o_ref.shape[1])
                      ).astype(o_ref.dtype)
        for ref in lse_ref:
            ref[...] = m_ref[...] + jnp.log(l)


def _lanes(x, n: int):
    """[rows, 128], a row's value in every lane -> [rows, n] the same:
    whole lane tiles side by side (no column is read out and broadcast:
    at 512 keys a step that cost 0.75 us of a step's 1.85 on the chip)."""
    if n % 128:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if n == 128 else pltpu.repeat(x, n // 128, 1)


def _lane_sums(p):
    """[rows, n] -> [rows, 128] whose lanes add up to each row's sum: the
    lane tiles added to one another, and no sum ACROSS lanes a step (at
    1,024 keys a step that was 5 % of the causal call on the chip).  A
    block of no whole lane tiles: the row's sum in lane 0."""
    rows, n = p.shape
    if n % 128:
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
        return jnp.where(lane == 0, jnp.sum(p, axis=1, keepdims=True), 0.0)
    part = p[:, :128]
    for i in range(1, n // 128):
        part = part + p[:, i * 128:(i + 1) * 128]
    return part


def _flash_fwd(q, k, v, lengths, sm_scale, causal, block_q, block_k,
               keep_lse: bool, window: int | None = None,
               name: str = "flash_fwd"):
    """q: [b, hq, sq, d]; k: [b, hkv, skv, d]; v: [b, hkv, skv, dv]
    (dv = d everywhere but latent attention's expanded path, whose keys
    are wider than its values); lengths: int32 [b] or None (every row
    `sq` long) -> o [b, hq, sq, dv], zeros in the query blocks wholly
    past a row's length, and lse [b, hq, sq] if `keep_lse` (the backward
    kernels' residual), else None.  `window`: a query attends its own
    position and the window - 1 before it, and the key blocks wholly
    before that band are not walked.  `name`: the kernel's device-side
    name (`flash_attention` names the calls with a sink apart)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    dv = v.shape[3]
    n_rep = hq // hkv
    if window is not None and not (causal and sq == skv):
        raise ValueError("a window takes causal self-attention")
    n_keys, xp = key_blocks(sq, skv, None, block_q, block_k, causal,
                            window=window), np
    steps = int(n_keys.sum())         # what a row of the full length takes
    if lengths is not None:
        if not causal:
            raise ValueError("lengths take causal attention: without the "
                             "mask a true row sees the padded keys")
        n_keys, xp = key_blocks(sq, skv, lengths.astype(jnp.int32), block_q,
                                block_k, causal, jnp, window), jnp
    *tables, total = _walk(n_keys, steps, block_q, block_k, causal, xp,
                           **_band(sq, block_q, block_k, window, xp))
    # no lengths: one row of tables for every row, all of it static
    stride, n_steps = (0, steps) if lengths is None \
        else (steps, jnp.max(total))

    def q_map(bi, hi, p, qi, ki, flag):
        return (bi, hi, qi[bi * stride + p], 0)

    def kv_map(bi, hi, p, qi, ki, flag):
        return (bi, hi // n_rep, ki[bi * stride + p], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hq, n_steps),        # with lengths, the device's number
        in_specs=[
            pl.BlockSpec((None, None, block_q, d), q_map),
            pl.BlockSpec((None, None, block_k, d), kv_map),
            pl.BlockSpec((None, None, block_k, dv), kv_map),
        ],
        out_specs=[pl.BlockSpec((None, None, block_q, dv), q_map)]
        + [pl.BlockSpec((None, None, block_q, 128), q_map)] * keep_lse,
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    out, *lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, stride=stride,
                          **({} if window is None else {"window": window})),
        name=name,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype)]
        + [jax.ShapeDtypeStruct((b, hq, sq, 128), jnp.float32)] * keep_lse,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(*tables, q, k, v)
    return out, (lse[0][..., 0] if keep_lse else None)


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
    return _flash_fwd(q, k, v, None, sm_scale, causal, block_q, block_k,
                      keep_lse=False)[0]


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    o, lse = _flash_fwd(q, k, v, None, sm_scale, causal, block_q, block_k,
                        keep_lse=True)
    # Name the residuals so a remat policy can SAVE them: under
    # jax.checkpoint with nothing_saveable, the backward re-runs this
    # whole forward kernel just to regenerate (o, lse) — per-layer
    # fwd+bwd drops ~40% when the policy keeps these instead
    # (models/llama.py remat_policy()).
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_vjp_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_forward_only(q, k, v, lengths, sm_scale, causal, block_q,
                        block_k, window=None, name="flash_fwd"):
    return _flash_fwd(q, k, v, lengths, sm_scale, causal, block_q, block_k,
                      keep_lse=False, window=window, name=name)[0]


def _no_backward(q, k, v, lengths, *_):
    raise TypeError(
        "flash attention's backward kernels take one width, no lengths and "
        "no window: "
        f"q {q.shape}, v {v.shape}, lengths "
        f"{None if lengths is None else lengths.shape}")


_flash_forward_only.defvjp(_no_backward, _no_backward)


def flash_attention(q, k, v, causal: bool = True, sm_scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K, lengths=None,
                    window: int | None = None, sink=None,
                    band_name: str = "flash_fwd"):
    """Flash attention with GQA.  q: [b, sq, hq, d]; k/v: [b, skv, hkv, d];
    returns [b, sq, hq, d] (layout matches ray_tpu.ops.attention).  v may
    be [b, skv, hkv, dv] with dv != d; the result is then [b, sq, hq, dv].
    lengths: int32 [b], the true length of each right-padded row (causal
    only): true rows come out as without it, the query blocks wholly past
    a length as zeros, and nothing is copied or multiplied for those.
    window: a query attends its own position and the window - 1 before
    it (causal self-attention only; the key block is then no longer than
    the query block, `band_blocks`).
    sink: float [hq], a learned column of the softmax a head that carries
    no value (o = sum_j e^{a_j} v_j / (e^{sink} + sum_j e^{a_j})): the
    kernel runs without it and hands back the log-sum-exp, and the
    output is scaled by sigmoid(lse - sink), which is the same number;
    the kernel of such a call is named `swa_band` on the device, so a
    trace tells a model's sink layers from its causal ones.
    band_name: the device-side name of a banded call WITHOUT a sink (a
    model whose window layers have none passes `swa_band`, to the same
    end).
    All four are forward only (the backward kernels take one width and
    whole rows): differentiating such a call raises."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    # Blocks must DIVIDE the sequence (the walk floor-divides).
    # seq % 128 == 0 is the dispatcher's entry gate, so power-of-two
    # blocks always land; a non-power-of-two caller block that can't
    # divide is an error rather than a silent degenerate grid.
    block_q, block_k = fit_blocks(qt.shape[2], kt.shape[2], block_q, block_k)
    if window is not None:
        block_q, block_k = band_blocks(qt.shape[2], block_q, block_k)
    if qt.shape[2] % block_q or kt.shape[2] % block_k:
        raise ValueError(
            f"block sizes ({block_q}, {block_k}) do not divide seq "
            f"({qt.shape[2]}, {kt.shape[2]}); use power-of-two blocks")
    if sink is not None:
        o, lse = _flash_fwd(qt, kt, vt, lengths, sm_scale, causal, block_q,
                            block_k, keep_lse=True, window=window,
                            name="swa_band")
        share = jax.nn.sigmoid(lse - sink.astype(jnp.float32)[None, :, None])
        o = (o.astype(jnp.float32) * share[..., None]).astype(o.dtype)
    elif window is not None:
        o = _flash_forward_only(qt, kt, vt, lengths, sm_scale, causal,
                                block_q, block_k, window, band_name)
    elif lengths is None and vt.shape[3] == qt.shape[3]:
        o = _flash(qt, kt, vt, sm_scale, causal, block_q, block_k)
    else:
        o = _flash_forward_only(qt, kt, vt, lengths, sm_scale, causal,
                                block_q, block_k)
    return o.transpose(0, 2, 1, 3)
