"""Paged-KV decode attention for TPU serving (Pallas).

The serve engine's KV cache lives in a shared pool of big pages
([n_pages, kvh, page, hd] per layer — kv-head major, so each head's
page rows are CONTIGUOUS in VMEM) instead of dense per-slot windows,
so HBM holds only what active sequences actually use — the
vLLM/PagedAttention idea re-shaped for TPU: big pages (hundreds of
rows, one pipelined DMA each) rather than CUDA's 16-row blocks.

The write path is the part that kills naive TPU decode: ANY per-step
update of a large cache carried through `lax.scan` copies the whole
buffer (measured: the row write alone cost more than the attention —
16ms/step of pure copies at b64xS512x24L).  So the decode block is
organised to never write the pools inside the scan:

  - PAGES are loop-invariant during a K-step decode block: the kernel
    only READS them, one whole page a grid step, by a DMA that Pallas
    issues while the step before computes.
  - New K/V rows accumulate in a small dense TAIL [B, kvh, K, hd]
    (one dynamic_update_slice per step at the shared in-block column —
    every slot's pos advances in lockstep, so the column index is a
    scalar).  The kernel attends pages AND tail with one flash
    accumulator; page rows >= the block-start snapshot are masked out
    (their live values are in the tail).
  - After the block, ONE scatter merges the tail into the pages —
    whole-pool traffic once per K steps instead of per step.

The kernel's grid is a WORK LIST, not lanes x table columns
(`attention_plan`): one step for each (lane, page) pair that holds
rows below the block-start snapshot, lanes in order and each lane's
pages ascending, the lane's tail attended in the step of its last
page.  A lane whose table row starts at the trash page holds no
request: it gets no step, no DMA and no compute, and its output rows
are 0.  The table and the snapshot do not change inside a block, so
the engine builds the list once a block and every layer's call of
every step reads it; the grid's bound is the list's length, a value
the device holds.  What the kernel still wastes: it copies WHOLE pages
(512 rows) however few rows of the last one are below the snapshot.

No reference analog (ray delegates attention entirely to user
libraries); the serving role matches what vLLM's paged_attention CUDA
kernels do under ray Serve deployments.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import _interpret

NEG_INF = -1e30


def lanes_live(page_table):
    """[B] bool: the lanes that hold a request.  The engine zeroes a
    finished lane's table row, so a row that STARTS at the trash page
    (page 0) is an idle lane, whatever its position has run to."""
    return page_table[:, 0] != 0


def attention_plan(page_table, tail_start, page: int) -> dict:
    """The steps `paged_decode_attention` walks for one decode block.

    page_table [B, maxp] int32 (`lanes_live` says which rows hold a
    request), tail_start [B] int32 (the block-start snapshot; an idle
    lane's may have run away), page = rows a page.

    A live lane takes max(pages, 1) steps, pages = its pages that hold
    rows < tail_start: one a page, ascending, the tail attended in the
    last (a lane with no row below the snapshot takes one step for its
    tail alone).  Returns int32 arrays of the static length B * maxp —
    `lane` [N], `col` [N] (the table column; 0 opens a lane), `page` [N]
    (the pool page to copy) — and `count`, the scalar number of steps
    that are work.  Entries from `count` on repeat the last one: valid
    indices that no step visits."""
    B, maxp = page_table.shape
    pages = -(-jnp.minimum(tail_start, maxp * page) // page)
    n = jnp.where(lanes_live(page_table), jnp.maximum(pages, 1), 0)
    end = jnp.cumsum(n)
    count = end[-1]
    i = jnp.minimum(jnp.arange(B * maxp), jnp.maximum(count - 1, 0))
    # the first lane whose steps end after i (B - 1, col 0, if none is live)
    lane = jnp.minimum(jnp.sum(end[None, :] <= i[:, None], axis=1), B - 1)
    col = i - (end - n)[lane]
    return {"lane": lane, "col": col, "page": page_table[lane, col],
            "count": count}


def _kernel(lane_ref, col_ref, page_ref, pos_ref, ts_ref,   # scalar prefetch
            q_ref, kp_ref, vp_ref, kt_ref, vt_ref,   # blocked inputs
            o_ref,                            # output
            acc_ref, m_ref, l_ref,            # scratch
            *, page: int, maxp: int, rep: int, kt: int, sm_scale: float):
    del page_ref                              # the index maps read it
    i = pl.program_id(0)
    b = lane_ref[i]
    col = col_ref[i]
    pos = pos_ref[b]
    ts = jnp.minimum(ts_ref[b], maxp * page)  # block-start snapshot
    # Pages hold rows < ts; the tail holds rows ts..pos.
    npages = (ts + page - 1) // page

    @pl.when(col == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def flash_update(s, v):
        """Batched flash-accumulation: s [kvh, rep, n] admitted scores,
        v [kvh, n, dv] values — one op set for ALL heads (per-head
        loops cost ~4x in tiny-op dispatch at rep=2 shapes)."""
        m_prev = m_ref[:, :, 0]                       # [kvh, rep]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., None])             # [kvh, rep, n]
        l_ref[:, :, 0] = l_ref[:, :, 0] * alpha + jnp.sum(p, axis=2)
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32),
            (((2,), (1,)), ((0,), (0,))),             # batch kvh
            preferred_element_type=jnp.float32)       # [kvh, rep, dv]
        acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
        m_ref[:, :, 0] = m_cur

    @pl.when(col < npages)
    def _pages():
        q = q_ref[0].astype(jnp.float32)     # [kvh, rep, hd]
        kpos = col * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, rep, page), 2)
        admit = kpos < ts                    # tail owns rows >= ts
        k = kp_ref[0].astype(jnp.float32)    # [kvh, page, hd]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale
        flash_update(jnp.where(admit, s, NEG_INF), vp_ref[0])

    @pl.when(col >= npages - 1)              # the lane's last step
    def _tail():
        q = q_ref[0].astype(jnp.float32)
        jpos = ts + jax.lax.broadcasted_iota(jnp.int32, (1, rep, kt), 2)
        admit = jpos <= pos
        k = kt_ref[0].astype(jnp.float32)    # [kvh, kt, hd]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale
        flash_update(jnp.where(admit, s, NEG_INF), vt_ref[0])
        l = l_ref[:, :, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, k_tail, v_tail,
                           page_table, pos, tail_start, *,
                           plan: dict | None = None,
                           sm_scale: float | None = None):
    """Paged + tail decode attention (READ-only on every input).

    q:          [B, kvh, rep, hd]   current-token queries (RoPE applied)
    k_pages/v_pages: [n_pages, kvh, page, hd | dv]  shared page pools
                (rows < tail_start; loop-invariant during a block); the
                values may be narrower than the keys (q/k 192, v 128)
    k_tail/v_tail:   [B, kvh, kt, hd | dv]  current block's accumulated rows
                (row j = absolute position tail_start + j; the CURRENT
                token's K/V must already be written at pos - tail_start)
    page_table: [B, maxp] int32     page ids per slot (page 0 = trash;
                a row that starts there is an idle lane)
    pos:        [B] int32           current attend position
    tail_start: [B] int32           pos snapshot at block start
    plan:       `attention_plan(page_table, tail_start, page)`, which a
                caller with many calls on one table and snapshot (a
                block's layers x steps) builds once; built here if not
                given

    Returns o [B, kvh, rep, dv]; an idle lane's rows are 0.
    """
    B, kvh, rep, hd = q.shape
    dv = v_pages.shape[3]
    page = k_pages.shape[2]
    kt = k_tail.shape[2]
    maxp = page_table.shape[1]
    if sm_scale is None:
        sm_scale = hd ** -0.5
    if plan is None:
        plan = attention_plan(page_table, tail_start, page)

    # Consecutive steps name different pages (a lane's next page, then
    # the next live lane's first), so the pipeline copies step i + 1's
    # page while step i computes.
    def page_map(i, lane, col, pages, *_):
        return (pages[i], 0, 0, 0)

    def lane_map(i, lane, *_):
        return (lane[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(plan["count"],),                # the device's own number
        in_specs=[
            pl.BlockSpec((1, kvh, rep, hd), lane_map),
            pl.BlockSpec((1, kvh, page, hd), page_map),
            pl.BlockSpec((1, kvh, page, dv), page_map),
            pl.BlockSpec((1, kvh, kt, hd), lane_map),
            pl.BlockSpec((1, kvh, kt, dv), lane_map),
        ],
        out_specs=pl.BlockSpec((1, kvh, rep, dv), lane_map),
        scratch_shapes=[
            pltpu.VMEM((kvh, rep, dv), jnp.float32),
            pltpu.VMEM((kvh, rep, 128), jnp.float32),
            pltpu.VMEM((kvh, rep, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, page=page, maxp=maxp, rep=rep,
                               kt=kt, sm_scale=sm_scale)
    o = pl.pallas_call(
        kernel,
        name="paged_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, rep, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(plan["lane"], plan["col"], plan["page"], pos, tail_start,
      q, k_pages, v_pages, k_tail, v_tail)
    # No step wrote an idle lane's rows: they hold whatever the buffer
    # did (NaN in interpret mode).
    live = lanes_live(page_table)
    return jnp.where(live[:, None, None, None], o, jnp.zeros_like(o))


def _mla_kernel(lane_ref, col_ref, page_ref, pos_ref, ts_ref,  # prefetch
                q_ref, rp_ref, rt_ref,        # blocked inputs
                o_ref,                        # output
                acc_ref, m_ref, l_ref,        # scratch
                *, page: int, maxp: int, kt: int, dv: int, sm_scale: float):
    """One step of `mla_decode_attention`: the walk of `_kernel`, over
    rows that are key AND value.  q_ref [H, dk]; rp_ref [page, dk] (one
    page of one layer's pool, copied once for all H heads); rt_ref
    [kt, dk]; o_ref [H, dv]."""
    del page_ref
    i = pl.program_id(0)
    b = lane_ref[i]
    col = col_ref[i]
    pos = pos_ref[b]
    ts = jnp.minimum(ts_ref[b], maxp * page)
    npages = (ts + page - 1) // page

    @pl.when(col == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def flash_update(rows, admit):
        """rows [n, dk]: scores over all dk columns, values = the first
        dv of the same rows."""
        s = jax.lax.dot_general(
            q_ref[...], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [H, n]
        s = jnp.where(admit, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_cur

    @pl.when(col < npages)
    def _pages():
        kpos = col * page + jax.lax.broadcasted_iota(
            jnp.int32, (1, page), 1)
        flash_update(rp_ref[...], kpos < ts)   # the tail owns rows >= ts

    @pl.when(col >= npages - 1)                # the lane's last step
    def _tail():
        jpos = ts + jax.lax.broadcasted_iota(jnp.int32, (1, kt), 1)
        flash_update(rt_ref[...], jpos <= pos)
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def mla_decode_attention(q, row_pages, row_tail, page_table, pos,
                         tail_start, *, dv: int, sm_scale: float,
                         plan: dict | None = None):
    """Paged + tail decode attention over a LATENT cache (multi-head
    latent attention, absorbed form): every head attends the SAME cached
    rows, and a row is key and value at once.

    q:         [B, H, dk]  absorbed queries, dk = latent + rotary width
    row_pages: [n_pages, 1, page, dk]  one layer's pool of cached rows
               [latent | rotary key] (rows < tail_start)
    row_tail:  [B, 1, kt, dk]  the block's rows (the current token's
               already written at pos - tail_start)
    dv:        the leading columns of a row that are its VALUE (the
               latent); scores run over all dk
    page_table, pos, tail_start, plan: as `paged_decode_attention`, whose
    work list this walks: one step a live (lane, page) pair, ONE page
    copy serving all H heads, the tail attended in the lane's last step.

    Returns o [B, H, dv] (to be expanded by the value up-projection); an
    idle lane's rows are 0."""
    B, H, dk = q.shape
    page = row_pages.shape[2]
    kt = row_tail.shape[2]
    maxp = page_table.shape[1]
    if plan is None:
        plan = attention_plan(page_table, tail_start, page)

    def page_map(i, lane, col, pages, *_):
        return (pages[i], 0, 0, 0)

    def lane_map3(i, lane, *_):
        return (lane[i], 0, 0)

    def lane_map4(i, lane, *_):
        return (lane[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(plan["count"],),
        in_specs=[
            pl.BlockSpec((None, H, dk), lane_map3),
            pl.BlockSpec((None, None, page, dk), page_map),
            pl.BlockSpec((None, None, kt, dk), lane_map4),
        ],
        out_specs=pl.BlockSpec((None, H, dv), lane_map3),
        scratch_shapes=[
            pltpu.VMEM((H, dv), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_mla_kernel, page=page, maxp=maxp, kt=kt,
                               dv=dv, sm_scale=sm_scale)
    o = pl.pallas_call(
        kernel,
        name="mla_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(plan["lane"], plan["col"], plan["page"], pos, tail_start,
      q, row_pages, row_tail)
    live = lanes_live(page_table)
    return jnp.where(live[:, None, None], o, jnp.zeros_like(o))


def mla_decode_reference(q, row_pages, row_tail, page_table, pos,
                         tail_start, *, dv: int, sm_scale: float):
    """Pure-jax oracle of `mla_decode_attention`: materializes each
    lane's gathered rows (test-scale only)."""
    B, H, dk = q.shape
    page = row_pages.shape[2]
    kt = row_tail.shape[2]
    maxp = page_table.shape[1]
    rows = row_pages[page_table][:, :, 0].reshape(B, maxp * page, dk)
    rows = jnp.concatenate([rows, row_tail[:, 0]], axis=1).astype(
        jnp.float32)                                  # [B, n + kt, dk]
    s = jnp.einsum("bhd,bkd->bhk", q.astype(jnp.float32), rows) * sm_scale
    kpos = jnp.arange(maxp * page)[None, :] < tail_start[:, None]
    jpos = (tail_start[:, None] + jnp.arange(kt)[None, :]) <= pos[:, None]
    admit = jnp.concatenate([kpos, jpos], axis=1)[:, None, :]
    p = jax.nn.softmax(jnp.where(admit, s, NEG_INF), axis=-1)
    o = jnp.einsum("bhk,bkd->bhd", p, rows[..., :dv])
    live = lanes_live(page_table)
    return jnp.where(live[:, None, None], o, 0.0).astype(q.dtype)


def merge_tail_pages(pages, tail, page_table, tail_start, n_rows,
                     per: int = 1):
    """Scatter a finished block's tail rows into the page pool.

    pages [n_pages, kvh, page_rows, hd]; tail [B, kvh, kt, hd].  `per`
    positions share a row of this leaf (1: a row a token, the K, V and
    latent pools; an index pool keeps one pooled key a group of `per`
    positions, and a page of it `page_rows` = page / per rows): row j of
    slot b is row tail_start[b] // per + j of the lane, for the rows
    that the `n_rows` positions from tail_start[b] on COMPLETED
    ((tail_start + n_rows) // per - tail_start // per of them; with per
    = 1, j < n_rows).  Positions past a slot's allocation resolve to the
    trash page via the zeroed table columns.  Call ONCE per decode block
    with `pages` donated: the rows are written in place.

    The head is an index too, so each update is one contiguous row of
    the pool as stored.  Indexed by (page, row) alone the update is a
    [kvh, hd] window strided over the page: XLA:TPU then transposes the
    WHOLE pool, scatters and transposes back, two copies of every pool
    a block (27.4 ms a block of 8 steps at Mistral-7B-d16 against 4.8;
    PERF.md section 6, PR 29).  Rows narrower than a lane tile
    (head_dim 64) scatter slowly one by one (7.7 ms against 5.3 for
    LFM2's four pools), so they keep the window."""
    B, kvh, kt, hd = tail.shape
    page = pages.shape[2]
    maxp = page_table.shape[1]
    j = jnp.arange(kt)[None, :]                       # [1, kt]
    first = tail_start[:, None] // per
    apos = jnp.minimum(first + j, maxp * page - 1)
    cols = apos // page                                # [B, kt]
    rows = apos % page
    pids = jnp.take_along_axis(page_table, cols, axis=1)   # [B, kt]
    # Rows beyond the block's actual length go to the trash page so a
    # short block can't clobber live data with stale tail columns.
    done = (tail_start[:, None] + n_rows) // per - first
    pids = jnp.where(j < done, pids, 0)
    value = tail.transpose(0, 2, 1, 3)                 # [B, kt, kvh, hd]
    if hd % 128:
        return pages.at[pids, :, rows].set(value)
    heads = jnp.arange(kvh)[None, None, :]
    return pages.at[pids[:, :, None], heads, rows[:, :, None]].set(value)


def gather_pages(pages, page_table):
    """Materialize per-slot dense KV windows from the page pool.

    pages [n_pages, kvh, page, hd] + page_table [B, maxp] →
    [B, maxp*page, kvh, hd].  The prefix-cache suffix prefill reads a
    request's CACHED prefix rows through this gather (a one-shot,
    prefill-scale HBM read — the decode path never materializes it);
    rows past a slot's allocation resolve to the trash page and are
    masked by the caller's prefix-length mask."""
    B, maxp = page_table.shape
    _, kvh, page, hd = pages.shape
    g = pages[page_table]                      # [B, maxp, kvh, page, hd]
    return g.transpose(0, 1, 3, 2, 4).reshape(B, maxp * page, kvh, hd)


def paged_decode_reference(q, k_pages, v_pages, k_tail, v_tail,
                           page_table, pos, tail_start, *,
                           sm_scale: float | None = None):
    """Pure-jax oracle: materializes gathered KV (test-scale only).  An
    idle lane (table row starting at the trash page) reads 0."""
    B, kvh, rep, hd = q.shape
    page = k_pages.shape[2]
    kt = k_tail.shape[2]
    maxp = page_table.shape[1]
    if sm_scale is None:
        sm_scale = hd ** -0.5
    ks = k_pages[page_table]            # [B, maxp, kvh, page, hd]
    vs = v_pages[page_table]
    ks = ks.transpose(0, 2, 1, 3, 4).reshape(B, kvh, maxp * page, hd)
    vs = vs.transpose(0, 2, 1, 3, 4).reshape(B, kvh, maxp * page, -1)
    kpos = jnp.arange(maxp * page)[None, None, None, :]
    sp = jnp.einsum("bhrd,bhkd->bhrk", q.astype(jnp.float32),
                    ks.astype(jnp.float32)) * sm_scale
    sp = jnp.where(kpos < tail_start[:, None, None, None], sp, NEG_INF)
    jpos = (tail_start[:, None, None, None]
            + jnp.arange(kt)[None, None, None, :])
    st = jnp.einsum("bhrd,bhjd->bhrj", q.astype(jnp.float32),
                    k_tail.astype(jnp.float32)) * sm_scale
    st = jnp.where(jpos <= pos[:, None, None, None], st, NEG_INF)
    s = jnp.concatenate([sp, st], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    vals = jnp.concatenate([vs, v_tail.astype(jnp.float32)], axis=2)
    o = jnp.einsum("bhrk,bhkd->bhrd", p, vals.astype(jnp.float32))
    live = lanes_live(page_table)
    return jnp.where(live[:, None, None, None], o, 0.0).astype(q.dtype)
