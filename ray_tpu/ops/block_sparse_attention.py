"""Block-sparse GQA attention that selects its own key blocks (InfLLM-v2,
MiniCPM4's `sparse_config`), for serving over a paged K/V cache: the
scores, the selection, the decode kernel that attends the blocks a
SELECTION names, and the prefill kernel in which every query keeps its
own blocks.

The mechanism.  Nothing is learned for it: a query scores KERNELS of its
layer's own keys, kernel c the mean of the `kernel` keys from position
c * `stride` on (`kernel` = 2 `stride`), with its own q,

    r_{t,g,c} = sum over the heads h of kv head g of
                softmax_c(q_{t,h} . kbar_{g,c} * sm_scale)

the softmax over the kernels that END at or before t; a BLOCK of `block`
keys scores the best of the kernels that overlap it.  A query past
`dense_len` attends the positions j <= t of the first `init_blocks`
blocks, of every block its window of `window` positions touches, and of
the `topk` best-scored of the others, one selection a KV HEAD; a query
below `dense_len` attends everything.  Scores and selection are ONE
kernel, `bsa_index`: a query block's scores live in VMEM and a bit a
(query, block) leaves it.

What is cached beside K and V.  A kernel spans two strides, so a row a
kernel would complete late and straddle pages; the pool keeps a row a
STRIDE, m_c = mean(k_{c stride} ... k_{c stride + stride - 1})
(`ops/sparse_attention.pool_index_keys`), and kbar_c = (m_c + m_{c+1}) /
2 is formed where the scores are taken: `page / stride` rows a page, the
seam's pool of grouped rows.

A decode step (`decode_attention`): while the lanes' table cannot pass
`dense_len`, or no live lane has, it is `paged_attn` as it is.  Else the
lane's stride rows are gathered through its table (32 B a token), scored
and selected (`decode_select`), the selection becomes a bias a (kv head,
row) of the table and `bsa_attn` walks `attention_plan`'s work list, one
grid step a live (lane, page) pair, the rows nobody chose weighed 0.  The
rows of the running decode block (`ops/paged_attention`) ride behind;
they lie inside the window.

A prefill (`prefill_select`, then `prefill_attention`: the kernel
`bsa_prefill`): the selection is a bit a (query, kv head, block), widened
to positions inside the kernel by one small product; a step is one (query
block, key block) pair of one kv head for ALL its query heads, so the
mask is widened once for the group; pairs above the diagonal or past a
row's true length copy and compute nothing, and a key block no query of
the query block chose is not multiplied.

Device-side names: `bsa_index`, `bsa_attn` and `bsa_prefill` (the
`pallas_call` names of the three kernels), `bsa_select` (a decode step's
bias).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import flash_attention, paged_attention
from ray_tpu.ops.paged_attention import attention_plan, lanes_live
from ray_tpu.ops.sparse_attention import M_FLOOR

F32 = jnp.float32
NEG_INF = -1e30
LANE = 128


def _interpret() -> bool:
    return flash_attention._interpret()


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of a selection (MiniCPM4's `sparse_config`)."""
    block: int = 64
    kernel: int = 32
    stride: int = 16
    window: int = 2048
    init_blocks: int = 1
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        if self.kernel != 2 * self.stride or self.block % self.stride:
            raise ValueError(
                "a kernel of two strides and a block of whole strides are "
                f"what the stride pool expresses, not {self}")


# --------------------------------------------- the scores and the selection
def top_set(masked, k: int):
    """Inside a kernel: the set `lax.top_k(masked, k)` takes (of equal
    scores the lower index first; nothing at NEG_INF), as a bit an entry of
    masked [n, w], WITHOUT a sort: the scores are no less than 0, where a
    float's bit pattern orders as the float does, so the k-th largest is
    found a bit at a time (31 counts of the entries at or above a trial
    value), and the ties at it by a running count (log2 w rolls).  A
    prompt pass asks this of every query: a sort of [512 queries, 2, 512]
    was 2 ms a trip on the chip, a seventh of the 1 x 32,768 program
    (PERF.md section 6, PR 61)."""
    w = masked.shape[-1]
    valid = masked > 0.5 * NEG_INF
    bits = jnp.where(valid, lax.bitcast_convert_type(
        jnp.where(masked == 0.0, 0.0, masked).astype(F32), jnp.int32), -1)

    def count(hit):
        return jnp.sum(jnp.where(hit, 1.0, 0.0), axis=-1, keepdims=True)

    kth = jnp.zeros(masked.shape[:-1] + (1,), jnp.int32)
    for i in range(31):
        trial = kth | (1 << (30 - i))
        kth = jnp.where(count(bits >= trial) >= k, trial, kth)
    above, at = bits > kth, bits == kth
    lane = lax.broadcasted_iota(jnp.int32, (1, w), 1)
    run, step = jnp.where(at, 1.0, 0.0), 1
    while step < w:
        run = run + jnp.where(lane >= step, pltpu.roll(run, step, 1), 0.0)
        step *= 2
    return valid & (above | (at & (run <= k - count(above))))


def forced_blocks(pos, b, shape: Shape):
    """The blocks `b` [1, nb] a query at `pos` [n, 1] attends unscored: the
    first `init_blocks` and every block its window touches (no division:
    block b's last position against the window's first)."""
    return (b < shape.init_blocks) | (
        ((b + 1) * shape.block > pos - shape.window + 1)
        & (b * shape.block <= pos))


def _index_kernel(base_ref, len_ref, q_ref, m_ref, o_ref, r_ref, *,
                  shape: Shape, sm_scale: float, bq: int, nbp: int):
    """The blocks `bq` queries of one kv head attend.  q_ref [rep, bq, hd]
    the group's heads, or [rep, hd] for ONE query (a decode step's: its
    heads are then the rows of one product); m_ref [ratio nbp, hd] the
    stride means, stride c = ratio b + j at row j nbp + b, so that block
    b's kernels are lane b of `ratio` aligned slices; o_ref [bq, nbp] a
    bit a block; r_ref [ratio, bq, nbp] float32 the kernels' scores summed
    over the heads."""
    n, qi = pl.program_id(0), pl.program_id(2)
    ratio = shape.block // shape.stride
    first = base_ref[n] + qi * bq
    pos = first + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    b = lax.broadcasted_iota(jnp.int32, (1, nbp), 1)
    below = b * shape.block <= pos                           # [bq, nbp]
    live = qi * bq < len_ref[n]
    dense = first + bq - 1 < shape.dense_len   # every query attends all

    def put(bit):
        o_ref[...] = jnp.where(bit, 1, 0).astype(o_ref.dtype)

    @pl.when(~live)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live & dense)
    def _dense():
        put(below)

    @pl.when(live & ~dense)
    def _scored():
        # kernel c = strides c and c + 1, visible where it ENDS at or
        # before the query
        vis = [(ratio * b + j + 2) * shape.stride <= pos + 1
               for j in range(ratio)]

        def kernels(rows):
            """rows [n, hd] -> each slice's softmax weights [n, nbp]."""
            d = lax.dot_general(rows, m_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
            seg = [d[:, j * nbp:(j + 1) * nbp] for j in range(ratio)]
            # stride ratio b + ratio is block b + 1's first
            nxt = seg[1:] + [pltpu.roll(seg[0], nbp - 1, 1)]
            s = [jnp.where(vis[j], 0.5 * (seg[j] + nxt[j]) * sm_scale,
                           NEG_INF) for j in range(ratio)]
            top = functools.reduce(jnp.maximum, [
                jnp.max(x, axis=1, keepdims=True) for x in s])
            e = [jnp.exp(x - jnp.maximum(top, M_FLOOR)) for x in s]
            den = jnp.maximum(sum(jnp.sum(x, axis=1, keepdims=True)
                                  for x in e), 1e-30)
            return [x / den for x in e]

        if len(q_ref.shape) == 2:       # one query: its heads the rows
            r = [jnp.sum(x, axis=0, keepdims=True)
                 for x in kernels(q_ref[...])]
        else:
            r_ref[...] = jnp.zeros_like(r_ref)

            def head(h, carry):
                for j, x in enumerate(kernels(q_ref[h])):
                    r_ref[j] += x
                return carry

            lax.fori_loop(0, q_ref.shape[0], head, 0)
            r = [r_ref[j] for j in range(ratio)]
        r = [jnp.where(vis[j], r[j], NEG_INF) for j in range(ratio)]
        # block b's kernels: ratio b - 1 ... ratio b + ratio - 1
        before = jnp.where(b >= 1, pltpu.roll(r[-1], 1, 1), NEG_INF)
        score = functools.reduce(jnp.maximum, r + [before])
        forced = forced_blocks(pos, b, shape)
        top = top_set(jnp.where(below & ~forced, score, NEG_INF), shape.topk)
        put(below & ((pos < shape.dense_len) | forced | top))


def blocks_padded(strides: int, shape: Shape) -> int:
    """Blocks `strides` stride rows lie in, in whole lane tiles."""
    ratio = shape.block // shape.stride
    return -(-(-(-strides // ratio)) // LANE) * LANE


def bsa_index(q, m, base, lens, shape: Shape, sm_scale: float):
    """Which blocks each query attends (the kernel `bsa_index`: the scores
    and the selection; nothing but a bit a (query, block) leaves it).

    q [n, kvh, rep, t, hd] the queries of each kv head's group, row i's at
    positions base[i] ... base[i] + t - 1, or [n, kvh, rep, hd]: one query
    a row, at base[i] (a decode step); m [n, kvh, M, hd] its stride means
    (a stride that is not complete at a query holds anything: no visible
    kernel reads it); lens [n]: the queries of row i that are any (a
    query block wholly past them reads 0).  Returns [n, kvh, t,
    `blocks_padded(M)`] (t = 1 for one query), int8 (int32 where a query
    block is no whole int8 tile): 1 where the query attends the block:
    every block at or below its own below `dense_len`; past it the first,
    its window's and the `topk` best-scored of the others, by

        max over the kernels c = ratio b - 1 ... ratio b + ratio - 1 of
        sum over the group's heads of softmax_c(q . kbar_c sm_scale)

    kbar_c = (m_c + m_{c+1}) / 2, the softmax over the visible kernels."""
    n, kvh, rep, hd = q.shape[:3] + q.shape[-1:]
    t = q.shape[3] if q.ndim == 5 else 1
    M = m.shape[2]
    ratio = shape.block // shape.stride
    nbp = blocks_padded(M, shape)
    # block-major -> stride-in-block-major: a block's kernels side by side
    mp = jnp.pad(m.astype(q.dtype),
                 ((0, 0), (0, 0), (0, ratio * nbp - M), (0, 0)))
    mp = jnp.swapaxes(mp.reshape(n, kvh, nbp, ratio, hd), 2, 3).reshape(
        n, kvh, ratio * nbp, hd)
    if q.ndim == 5:
        bq = 256 if t >= 256 else -(-t // 8) * 8     # whole query blocks
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, -t % bq), (0, 0)))
        q_spec = pl.BlockSpec((None, None, rep, bq, hd),
                              lambda i, g, qi, *_: (i, g, 0, qi, 0))
    else:
        bq = 1
        q_spec = pl.BlockSpec((None, None, rep, hd),
                              lambda i, g, qi, *_: (i, g, 0, 0))
    tp = -(-t // bq) * bq
    out = pl.pallas_call(
        functools.partial(_index_kernel, shape=shape, sm_scale=sm_scale,
                          bq=bq, nbp=nbp),
        name="bsa_index",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, kvh, tp // bq),
            in_specs=[q_spec,
                      pl.BlockSpec((None, None, ratio * nbp, hd),
                                   lambda i, g, qi, *_: (i, g, 0, 0))],
            out_specs=pl.BlockSpec((None, None, bq, nbp),
                                   lambda i, g, qi, *_: (i, g, qi, 0)),
            scratch_shapes=[pltpu.VMEM((ratio, bq, nbp), F32)]),
        out_shape=jax.ShapeDtypeStruct(
            (n, kvh, tp, nbp), jnp.int8 if bq % 32 == 0 else jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=_interpret(),
    )(base.astype(jnp.int32), lens.astype(jnp.int32), q, mp)
    return out[:, :, :t]


def selection_counts(context: int, shape: Shape) -> tuple[int, int]:
    """Host arithmetic for a query with `context` rows at and below it:
    (blocks it attends, rows it attends)."""
    t = context - 1
    if t < shape.dense_len:
        return t // shape.block + 1, context
    first = max((t - shape.window + 1) // shape.block, 0)
    init = min(shape.init_blocks, first)
    picked = min(shape.topk, first - init)
    return (init + picked + t // shape.block - first + 1,
            (init + picked) * shape.block + context - first * shape.block)


# ------------------------------------------------------- the decode kernel
def _attn_kernel(lane_ref, col_ref, page_ref, npg_ref,     # scalar prefetch
                 q_ref, kp_ref, vp_ref, bias_ref, kt_ref, vt_ref, tbias_ref,
                 o_ref, acc_ref, m_ref, l_ref, *, sm_scale: float):
    """One (lane, page) step: the walk of `ops/paged_attention._kernel`,
    every admission in the bias, a bias a kv head.  q_ref [kvh, rep, hd];
    kp_ref, vp_ref [kvh, page, hd]; bias_ref [kvh, 1, page]; kt_ref,
    vt_ref [kvh, kt, hd] the running block's rows, tbias_ref [1, kt]."""
    del page_ref                              # the index maps read it
    i = pl.program_id(0)
    col = col_ref[i]
    npages = npg_ref[lane_ref[i]]

    @pl.when(col == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def flash_update(k, v, bias):
        s = lax.dot_general(
            q_ref[0].astype(F32), k.astype(F32),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=F32) * sm_scale + bias   # [kvh, rep, n]
        m_prev = m_ref[:, :, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2))
        alpha = jnp.exp(m_prev - m_cur)
        # (a page with no row admitted: every weight 0, not exp(0))
        p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_cur[..., None]), 0.0)
        l_ref[:, :, 0] = l_ref[:, :, 0] * alpha + jnp.sum(p, axis=2)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + lax.dot_general(
            p, v.astype(F32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=F32)
        m_ref[:, :, 0] = m_cur

    @pl.when(col < npages)
    def _pages():
        flash_update(kp_ref[0], vp_ref[0], bias_ref[...])

    @pl.when(col >= npages - 1)               # the lane's last step
    def _tail():
        flash_update(kt_ref[0], vt_ref[0], tbias_ref[...][None])
        l = l_ref[:, :, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def bsa_attention(q, k_pages, v_pages, bias, k_tail, v_tail, tail_bias,
                  plan: dict, npages, live, *, sm_scale: float):
    """Attention over the rows a bias admits, pages read where they lie.

    q [B, kvh, rep, hd]; k_pages, v_pages [n_pages, kvh, page, hd]; bias
    [B, columns, kvh, 1, page] float32 (0 attended, NEG_INF not); k_tail,
    v_tail [B, kvh, kt, hd], tail_bias [B, 1, kt]; plan: a work list
    {"lane", "col", "page", "count"} of (lane, page) steps, lanes in
    order and a lane's columns ascending; npages [B]: the steps of a
    lane that read a page (its last attends the tail too; a lane with
    none takes one step for its tail); live [B].  Returns o [B, kvh, rep,
    hd]; a lane outside the list reads 0."""
    B, kvh, rep, hd = q.shape
    page = k_pages.shape[2]
    kt = k_tail.shape[2]

    def page_map(i, lane, col, pages, *_):
        return (pages[i], 0, 0, 0)

    def bias_map(i, lane, col, *_):
        return (lane[i], col[i], 0, 0, 0)

    def lane_map3(i, lane, *_):
        return (lane[i], 0, 0)

    def lane_map4(i, lane, *_):
        return (lane[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(plan["count"],),
        in_specs=[pl.BlockSpec((1, kvh, rep, hd), lane_map4),
                  pl.BlockSpec((1, kvh, page, hd), page_map),
                  pl.BlockSpec((1, kvh, page, hd), page_map),
                  pl.BlockSpec((None, None, kvh, 1, page), bias_map),
                  pl.BlockSpec((1, kvh, kt, hd), lane_map4),
                  pl.BlockSpec((1, kvh, kt, hd), lane_map4),
                  pl.BlockSpec((None, 1, kt), lane_map3)],
        out_specs=pl.BlockSpec((1, kvh, rep, hd), lane_map4),
        scratch_shapes=[pltpu.VMEM((kvh, rep, hd), F32),
                        pltpu.VMEM((kvh, rep, LANE), F32),
                        pltpu.VMEM((kvh, rep, LANE), F32)],
    )
    o = pl.pallas_call(
        functools.partial(_attn_kernel, sm_scale=sm_scale),
        name="bsa_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(plan["lane"], plan["col"], plan["page"], npages.astype(jnp.int32),
      q, k_pages, v_pages, bias, k_tail, v_tail, tail_bias)
    return jnp.where(live[:, None, None, None], o, jnp.zeros_like(o))


def _bias(admit):
    return jnp.where(admit, 0.0, NEG_INF).astype(F32)


def _tail_bias(pos, tail_start, kt: int):
    """[B, 1, kt]: the running block's rows up to the token's own (they
    lie inside the window, and below `dense_len` everything is attended)."""
    tpos = tail_start[:, None] + jnp.arange(kt)[None, :]
    return _bias(tpos <= pos[:, None])[:, None, :]


def decode_select(q, idx_pages, idx_tail, page_table, pos, tail_start,
                  shape: Shape, sm_scale: float):
    """One decode step's selection for every lane.  q [B, kvh, rep, hd];
    idx_pages [n_pages, kvh, page / stride, hd] the stride pool (rows
    below tail_start // stride); idx_tail [B, kvh, R, hd] the strides the
    running block completed (row r = stride tail_start // stride + r).
    Returns chosen [B, kvh, nb] bool, nb = table columns x page / block;
    an idle lane's reads False."""
    B, maxp = page_table.shape
    per = idx_pages.shape[2]                      # stride rows a page
    with jax.named_scope("bsa_index"):
        m = idx_pages[page_table]                 # [B, maxp, kvh, per, hd]
        lane = jnp.arange(B)
        for r in range(idx_tail.shape[2]):
            c = tail_start // shape.stride + r      # past the table: dropped
            m = m.at[lane, c // per, :, c % per].set(idx_tail[:, :, r],
                                                     mode="drop")
        m = jnp.swapaxes(m, 1, 2).reshape(B, m.shape[2], maxp * per, -1)
        got = bsa_index(q, m, pos, lanes_live(page_table).astype(jnp.int32),
                        shape, sm_scale)
        return got[:, :, 0, :maxp * per * shape.stride // shape.block] != 0


def decode_attention(q, k_pages, v_pages, idx_pages, k_tail, v_tail,
                     idx_tail, page_table, pos, tail_start, shape: Shape,
                     *, sm_scale: float, plan: dict | None = None):
    """One decode step's attention of every lane (the current token's K
    and V already in the tails, the stride it completed in `idx_tail`).
    Returns o [B, kvh, rep, hd]; an idle lane reads 0."""
    def dense(_):
        return paged_attention.paged_decode_attention(
            q, k_pages, v_pages, k_tail, v_tail, page_table, pos,
            tail_start, plan=plan, sm_scale=sm_scale)

    B, maxp = page_table.shape
    page, kt = k_pages.shape[2], k_tail.shape[2]
    if maxp * page <= shape.dense_len:        # no lane can pass it
        return dense(None)

    def sparse(_):
        chosen = decode_select(q, idx_pages, idx_tail, page_table, pos,
                               tail_start, shape, sm_scale)
        with jax.named_scope("bsa_select"):
            kpos = jnp.arange(maxp * page)
            admit = (jnp.repeat(chosen, shape.block, axis=-1)
                     & (kpos < tail_start[:, None])[:, None]
                     & (kpos <= pos[:, None])[:, None])      # [B, kvh, rows]
            bias = _bias(admit).reshape(B, -1, maxp, 1, page).swapaxes(1, 2)
            tail_bias = _tail_bias(pos, tail_start, kt)
        npages = -(-jnp.minimum(tail_start, maxp * page) // page)
        with jax.named_scope("bsa_attn"):
            return bsa_attention(
                q, k_pages, v_pages, bias, k_tail, v_tail, tail_bias,
                plan or attention_plan(page_table, tail_start, page),
                npages, lanes_live(page_table), sm_scale=sm_scale)

    past = jnp.any(lanes_live(page_table) & (pos >= shape.dense_len))
    return lax.cond(past, sparse, dense, None)


# ------------------------------------------------------ the prefill kernel
def prefill_select(q, m, lengths, shape: Shape, sm_scale: float):
    """Every query's blocks in a prompt pass.  q [b, kvh, rep, T, hd], m
    [b, kvh, T / stride, hd] the stride means, lengths [b].  Returns
    `bsa_index`'s bits [b, kvh, T, blocks in whole lane tiles] for the
    query at each position below its row's length."""
    with jax.named_scope("bsa_index"):
        return bsa_index(q, m, jnp.zeros_like(lengths), lengths, shape,
                         sm_scale)


def _prefill_kernel(len_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, acc_ref,
                    m_ref, l_ref, *, sm_scale: float, bq: int, bk: int,
                    shift: int):
    """One (query block, key block) pair of one kv head, all `rep` query
    heads of its group: q_ref [rep, bq, hd], k_ref, v_ref [bk, hd],
    mask_ref [bq, LANE] int8 (a bit a block: the LANE blocks the key
    block's lie in), o_ref [rep, bq, hd]."""
    bi, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    rep, _, hd = q_ref.shape
    per = bk >> shift                          # blocks a key block
    last_k = (qi * bq + bq - 1) // bk

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bits = mask_ref[...].astype(jnp.int32).astype(F32)                       # [bq, LANE]
    off = (ki * per) % LANE
    lane = lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
    mine = (lane >= off) & (lane < off + per)
    hit = jnp.max(jnp.where(mine, bits, 0.0)) > 0.0

    @pl.when((qi * bq < len_ref[bi]) & (ki <= last_k) & hit)
    def _pair():
        # a block's bit over its positions: one small product
        j = lax.broadcasted_iota(jnp.int32, (LANE, bk), 0)
        c = lax.broadcasted_iota(jnp.int32, (LANE, bk), 1)
        spread = (j == off + jnp.right_shift(c, shift)).astype(jnp.bfloat16)
        wide = lax.dot_general(bits.astype(jnp.bfloat16), spread,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=F32)   # [bq, bk]
        rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        admit = (wide > 0.5) & (cols <= rows)
        s = lax.dot_general(q_ref[...].reshape(rep * bq, hd), k_ref[...],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * sm_scale
        s = jnp.where(admit[None], s.reshape(rep, bq, bk), NEG_INF
                      ).reshape(rep * bq, bk)
        m_prev = m_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        m_ref[:, 0] = m_cur

    @pl.when(ki == pl.num_programs(3) - 1)
    def _done():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)     # nothing attended: zeros
        o_ref[...] = (acc_ref[...] / l[:, None]).reshape(
            rep, bq, hd).astype(o_ref.dtype)


def prefill_blocks(T: int, shape: Shape) -> tuple[int, int]:
    """(query block, key block) `bsa_prefill` takes rows of T positions
    at; (0, 0) where it does not (the caller then runs the attention in
    XLA): T in whole 128-row query blocks, key blocks of 512 where they
    divide T, each whole blocks of the selection."""
    bk = 512 if T % 512 == 0 else 128
    blk = shape.block
    if T % 128 or bk % blk or LANE % (bk // blk) or blk & (blk - 1):
        return 0, 0
    return 128, bk


def prefill_attention(q, k, v, mask, lengths, shape: Shape, *,
                      sm_scale: float):
    """softmax(sm_scale q k^T over the positions j <= t of the blocks
    `mask` names) v over right-padded rows.

    q [b, kvh, rep, T, hd]; k, v [b, T, kvh, hd]; mask [b, kvh, T, blocks
    in whole lane tiles] int8 (`prefill_select`); lengths int32 [b].
    Returns o [b, T, kvh, rep, hd]; every query of a query block wholly
    past its row's length reads 0."""
    b, kvh, rep, T, hd = q.shape
    bq, bk = prefill_blocks(T, shape)
    if not bq:
        return _masked_attention(q, k, v, mask, shape, sm_scale)
    per = bk // shape.block

    def last(qi):
        return (qi * bq + bq - 1) // bk

    def qmap(bi, g, qi, ki, lens):
        return (bi, g, 0, qi, 0)

    def kmap(bi, g, qi, ki, lens):
        return (bi, g, jnp.minimum(ki, last(qi)), 0)

    def mmap(bi, g, qi, ki, lens):
        return (bi, g, qi, jnp.minimum(ki, last(qi)) * per // LANE)

    kh, vh = (jnp.swapaxes(a, 1, 2) for a in (k, v))      # [b, kvh, T, hd]
    o = pl.pallas_call(
        functools.partial(_prefill_kernel, sm_scale=sm_scale, bq=bq, bk=bk,
                          shift=shape.block.bit_length() - 1),
        name="bsa_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kvh, T // bq, T // bk),
            in_specs=[pl.BlockSpec((None, None, rep, bq, hd), qmap),
                      pl.BlockSpec((None, None, bk, hd), kmap),
                      pl.BlockSpec((None, None, bk, hd), kmap),
                      pl.BlockSpec((None, None, bq, LANE), mmap)],
            out_specs=pl.BlockSpec((None, None, rep, bq, hd), qmap),
            scratch_shapes=[pltpu.VMEM((rep * bq, hd), F32),
                            pltpu.VMEM((rep * bq, LANE), F32),
                            pltpu.VMEM((rep * bq, LANE), F32)]),
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, T, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=_interpret(),
    )(lengths.astype(jnp.int32), q, kh, vh, mask.astype(jnp.int8))
    return jnp.transpose(o, (0, 3, 1, 2, 4))


def _masked_attention(q, k, v, mask, shape: Shape, sm_scale: float):
    """`prefill_attention` in XLA, the scores in memory: rows the kernel's
    blocks do not divide (a short bucket, a test's)."""
    T = q.shape[3]
    at = jnp.arange(T)
    admit = (jnp.repeat(mask != 0, shape.block, axis=-1)[..., :T]
             & (at[None, :] <= at[:, None]))                 # [b, kvh, T, T]
    s = jnp.einsum("bgrtd,bsgd->bgrts", q, k,
                   preferred_element_type=F32) * sm_scale
    p = jax.nn.softmax(jnp.where(admit[:, :, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bgrts,bsgd->btgrd", p.astype(v.dtype), v,
                      preferred_element_type=F32).astype(q.dtype)


# What a serving module with this attention reports of it, a live lane's
# every decode step and every prompt's strides, x those layers
# (models/serving.ServingSpec.counters).
COUNTERS = {
    "bsa_rows_context": "Rows in a lane's context at a block-sparse layer's "
                        "decode step (what a dense step would read), summed "
                        "over live lanes, steps and those layers",
    "bsa_rows_attended": "Rows the step attended (a selection's, or all of "
                         "them below dense_len), summed likewise",
    "bsa_blocks_selected": "Blocks those rows lie in, summed likewise",
    "bsa_dense_steps": "Lane-steps below dense_len (everything attended), "
                       "summed likewise",
    "kernel_keys_written": "Stride rows written beside K and V (prefill "
                           "programs and decode steps), x those layers",
}


def decode_work(layers: int, shape: Shape, rows, k: int) -> tuple[dict, dict]:
    """One decode window of `k` steps over live lanes that start it on
    `rows` cached rows each, x `layers` such layers, as COUNTERS' rows
    (host arithmetic, `selection_counts`); the span shows the same."""
    ctx = att = blocks = dense = keys = 0
    for r in rows:
        for c in range(r + 1, r + 1 + k):
            nb, n = selection_counts(c, shape)
            ctx, att, blocks = ctx + c, att + n, blocks + nb
            dense += c <= shape.dense_len
        keys += (r + k) // shape.stride - r // shape.stride
    work = dict(zip(COUNTERS, (n * layers for n in
                               (ctx, att, blocks, dense, keys))))
    return work, work


def prefill_work(layers: int, shape: Shape, true_lens) -> tuple[dict, dict]:
    """The stride rows a prompt pass writes, x `layers`."""
    return {"kernel_keys_written":
            layers * sum(int(n) // shape.stride for n in true_lens)}, {}
