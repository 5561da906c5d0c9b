"""Learned sparse attention over a latent pool (DeepSeek-V3.2's indexer
with pooled index keys), for serving: the indexer's scores, the top-k
selection, and the decode kernel that attends the latent rows a
SELECTION names.

The mechanism.  Beside a token's cached latent row the layer keeps an
INDEX KEY, pooled `group` positions to a row: the index pool holds ONE
key a COMPLETE group (`pool_index_keys`: the mean of the group's keys).
A query scores every complete group below it,

    I_{t,g} = sum_j w_{t,j} relu(q^I_{t,j} . kbar_g)        (`index_scores`)

keeps the `top` best (`select_groups`) and attends the positions of
those groups and of its own incomplete group (`selected_mask`: the one
reading of which rows a selection means, for whole rows of queries;
`walk_bias` / `select_rows`: the same for one decode step, as a bias
over the lane's table or as rows gathered from the pool).  Below `top`
complete groups the selection keeps everything and the layer is dense
latent attention.  At `group` 1 (a key a TOKEN:
`models/dots3_note.py`) no group is ever incomplete, and `own` keeps the
query's own row whatever its score.

A decode step (`decode_select` + `decode_attend`): the index keys of
the lane's pages are gathered through its table (256 B a group: 37 MB a
step at 64 lanes x 9,216 positions), scored, the top groups taken, and
the rows attended in one of two forms, named by the table's shape alone
(`walks`: no option):

  - the WALK, where the table holds at most RATIO times the rows a
    selection gathers (both served cells: 9,216 rows against 2,176):
    `walk_bias` turns the selection into a bias a row of the table (a
    chosen group's bit repeated over its rows: no gather, no scatter) and
    the kernel `dsa_walk_attention` walks `attention_plan`'s work list,
    one grid step a live (lane, page) pair, flash accumulation across
    pages, the rows nobody chose weighed 0.  It reads every page of the
    lane, a few times the rows it needs, because a row of a whole page
    streams at a tenth of what a gathered row costs;
  - the GATHER, past that (a long-context engine: the selection is a
    small share of the table): `select_rows` gathers the top groups'
    latent rows `group` rows at a time (contiguous in a page) and the
    kernel `dsa_decode_attention` attends them, one grid step a LIVE
    lane: a selection names rows, so this kernel walks lanes and reads
    rows that were gathered for it.

Either kernel attends every head at once.  Rows of the running decode
block are not in the pool yet (`ops/paged_attention`): they ride behind
the pool's rows, each admitted if the selection holds its group.

Device-side names: `dsa_index`, `dsa_select`, `dsa_attn` (either
kernel's `pallas_call` name too: one call a sparse layer a step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import flash_attention
from ray_tpu.ops.paged_attention import attention_plan, lanes_live

F32 = jnp.float32
NEG_INF = -1e30
LANE = 128


def _interpret() -> bool:
    # the flash kernel's rule, asked where it lives (as ops/ssm.py does)
    return flash_attention._interpret()


def pool_index_keys(keys, group: int):
    """keys [..., T, w] -> [..., T // group, w]: ONE key a complete group
    of `group` positions, their mean (float32; the config's
    `index_kpool_compress`: only this pooled key is kept)."""
    *lead, T, w = keys.shape
    n = T // group
    return jnp.mean(keys[..., :n * group, :].astype(F32).reshape(
        *lead, n, group, w), axis=-2)


def index_scores(q, w, kbar):
    """q [..., t, J, w] index queries, w [..., t, J] their weights, kbar
    [..., G, w] pooled keys -> [..., t, G] float32: sum over the J index
    heads of w_j relu(q_j . kbar_g)."""
    s = jnp.einsum("...tjw,...gw->...tjg", q, kbar.astype(q.dtype),
                   preferred_element_type=F32)
    return jnp.sum(jax.nn.relu(s) * w.astype(F32)[..., None], axis=-2)


def select_groups(scores, n_complete, top: int):
    """The `top` best of the first `n_complete` groups of each row (of
    equal scores the lower group first, `lax.top_k`'s order).  scores
    [..., G] float32, n_complete [...] int32.  Returns (idx [..., top]
    int32, ok [..., top]: False where fewer than `top` groups are
    complete, (kth [...], last [...]): the smallest selected score and
    the highest group selected at that score, from which `selected_mask`
    rebuilds the same set without a scatter)."""
    G = scores.shape[-1]
    valid = jnp.arange(G) < n_complete[..., None]
    # (`lax.top_k` orders -0.0 below 0.0, the comparisons that rebuild
    # its set from (kth, last) do not: one zero)
    masked = jnp.where(valid, jnp.where(scores == 0.0, 0.0, scores),
                       NEG_INF)
    if G < top:
        masked = jnp.pad(masked, [(0, 0)] * (masked.ndim - 1)
                         + [(0, top - G)], constant_values=NEG_INF)
    val, idx = lax.top_k(masked, top)
    ok = val > 0.5 * NEG_INF
    kth = jnp.min(jnp.where(ok, val, -NEG_INF), axis=-1)
    last = jnp.max(jnp.where(ok & (val == kth[..., None]), idx, -1), axis=-1)
    return idx.astype(jnp.int32), ok, (kth, last)


def _top_set(scores, kth, last):
    """The set `lax.top_k` picked, rebuilt from `select_groups`' (kth,
    last) as a bit a group [..., G]: above the k-th score, or at it and
    no later than the last group taken there.  No scatter."""
    g = jnp.arange(scores.shape[-1])
    return ((scores > kth[..., None])
            | ((scores == kth[..., None]) & (g <= last[..., None])))


def selected_mask(scores, pos, n_keys: int, group: int, top: int,
                  own: bool = False):
    """Which of `n_keys` key positions each query attends.  scores
    [..., t, G] (`index_scores`), pos [t] the queries' positions.
    `own` (a key a TOKEN, `group` 1: no group is ever incomplete): the
    query's own key is selected whatever its score.
    Returns (mask [..., t, n_keys] bool: s <= t's position, and s in one
    of the `top // group` best complete groups (group g + group - 1 <=
    position) or in the query's own incomplete group (the config's
    `index_kpool_always_select_tail`); chosen [..., t, G] bool: the groups
    the scores chose)."""
    n_complete = (pos + 1) // group
    if own:
        scores = jnp.where(jnp.arange(scores.shape[-1]) == pos[:, None],
                           -NEG_INF, scores)
    _, _, (kth, last) = select_groups(scores, jnp.broadcast_to(
        n_complete, scores.shape[:-1]), top // group)
    chosen = _top_set(scores, kth, last) \
        & (jnp.arange(scores.shape[-1]) < n_complete[:, None])
    keys = jnp.arange(n_keys)
    by_group = jnp.repeat(chosen, group, axis=-1)[..., :n_keys]
    if by_group.shape[-1] < n_keys:
        by_group = jnp.pad(by_group, [(0, 0)] * (by_group.ndim - 1)
                           + [(0, n_keys - by_group.shape[-1])])
    tail = keys[None, :] >= (n_complete * group)[:, None]
    return (by_group | tail) & (keys[None, :] <= pos[:, None]), chosen


def decode_select(q, w, idx_pages, idx_tail, page_table, pos, tail_start,
                  group: int, top: int, own: bool = False):
    """One decode step's selection for every lane (`own`, at `group` 1:
    the query's own key, in the tail, is selected whatever its score).

    q [B, J, w], w [B, J]; idx_pages [n_pages, 1, page // group, w] the
    index pool (groups complete below `tail_start`); idx_tail [B, 1, R,
    w] the groups the running block completed (row r = group
    tail_start // group + r).  Returns (groups [B, top // group] int32
    absolute group numbers, ok [B, top // group], chosen [B, G + R] bool:
    the same set as a bit a scored group, the pool's G = table columns x
    page // group first and then the tail's, rebuilt from `select_groups`'
    (kth, last) as `selected_mask` does: no scatter)."""
    B, maxp = page_table.shape
    with jax.named_scope("dsa_index"):
        kbar = idx_pages[page_table][:, :, 0]         # [B, maxp, rows, w]
        kbar = kbar.reshape(B, -1, kbar.shape[-1])
        G = kbar.shape[1]
        kbar = jnp.concatenate([kbar, idx_tail[:, 0]], axis=1)
        s = index_scores(q[:, None], w[:, None], kbar)[:, 0]   # [B, G + R]
        g0 = tail_start // group
        r = jnp.arange(idx_tail.shape[2])
        done = (g0[:, None] + r[None, :] + 1) * group - 1 <= pos[:, None]
        valid = jnp.concatenate(
            [jnp.arange(G)[None, :] < g0[:, None], done], axis=1)
        s = jnp.where(valid, s, NEG_INF)
        if own:
            mine = jnp.concatenate(
                [jnp.zeros((B, G), bool),
                 (g0[:, None] + r[None, :]) * group == pos[:, None]], axis=1)
            s = jnp.where(mine, -NEG_INF, s)
    with jax.named_scope("dsa_select"):
        idx, ok, (kth, last) = select_groups(
            s, jnp.full((B,), s.shape[1], jnp.int32), top // group)
        groups = jnp.where(idx < G, idx, g0[:, None] + idx - G)
        chosen = _top_set(s, kth, last) & (s > 0.5 * NEG_INF)
    return groups, ok, chosen


def _bias(admit):
    """float32, 0 where a row is attended and -1e30 elsewhere."""
    return jnp.where(admit, 0.0, NEG_INF).astype(F32)


def select_rows(latent_pages, latent_tail, page_table, pos, tail_start,
                groups, ok, group: int):
    """The rows a selection names, gathered for `dsa_decode_attention`.

    latent_pages [n_pages, 1, page, w] (rows below `tail_start`),
    latent_tail [B, 1, K, w] (the running block's rows), groups, ok
    [B, n] (`decode_select`).  Returns (rows [B, S, w] gathered from the
    pool, bias [B, S], tail_bias [B, K]: float32, 0 where the row is
    attended and -1e30 elsewhere; rpos [B, S + K], admit [B, S + K]: every
    row's position and whether it is attended, the tail's last), S = (n +
    1) * group rounded up to whole lane tiles (groups nobody chose fill
    it).  A selected group's rows at or past `tail_start` are not in the
    pool: they are admitted in the tail, as are the rows of the query's
    own incomplete group."""
    n_pages, _, page, w = latent_pages.shape
    B = groups.shape[0]
    K = latent_tail.shape[2]
    with jax.named_scope("dsa_select"):
        # the query's own incomplete group rides behind the chosen ones:
        # its rows below `tail_start` are in the pool like any other's
        n = rows_gathered(group, groups.shape[1] * group) // group
        fill = n - groups.shape[1] - 1
        groups = jnp.concatenate(
            [groups, ((pos + 1) // group)[:, None],
             jnp.zeros((B, fill), groups.dtype)], axis=1)
        ok = jnp.concatenate([ok, jnp.ones((B, 1), bool),
                              jnp.zeros((B, fill), bool)], axis=1)
        # a row at a time: the pool seen as [pages x rows, w] is the
        # same bytes, while `group` rows side by side are another layout
        # of a tiled array (the whole pool copied, 604 MB a window); the
        # page is looked up a GROUP at a time (a group lies in one page)
        first = groups * group                             # [B, n]
        col = jnp.minimum(first // page, page_table.shape[1] - 1)
        at = jnp.take_along_axis(page_table, col, axis=1) * page \
            + first % page
        step = jnp.arange(group)[None, None, :]
        rows = latent_pages.reshape(n_pages * page, w)[
            (at[:, :, None] + step).reshape(B, n * group)]
        rpos = (first[:, :, None] + step).reshape(B, n * group)
        admit = (jnp.repeat(ok, group, axis=1)
                 & (rpos < tail_start[:, None]))
        tpos = tail_start[:, None] + jnp.arange(K)[None, :]      # [B, K]
        own = tpos >= ((pos + 1) // group * group)[:, None]
        held = jnp.any((tpos // group)[:, :, None] == jnp.where(
            ok, groups, -1)[:, None, :], axis=-1)
        t_admit = (tpos <= pos[:, None]) & (own | held)
        return (rows, _bias(admit), _bias(t_admit),
                jnp.concatenate([rpos, tpos], axis=1),
                jnp.concatenate([admit, t_admit], axis=1))


def _dsa_kernel(lanes_ref, q_ref, rows_ref, bias_ref, tail_ref, tbias_ref,
                o_ref, *, dv: int, sm_scale: float):
    """One lane: q_ref [H, dk]; rows_ref [S, dk] gathered from the pool
    and tail_ref [K, dk] the running block's (key AND value: the first dv
    columns); bias_ref [1, S], tbias_ref [1, K]; o_ref [H, dv]."""
    del lanes_ref

    def scores(rows, bias):
        return lax.dot_general(
            q_ref[...], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=F32) * sm_scale + bias

    def weigh(p, rows):
        return lax.dot_general(p.astype(rows.dtype), rows[:, :dv],
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=F32)

    rows, tail = rows_ref[...], tail_ref[...]
    s, st = scores(rows, bias_ref[...]), scores(tail, tbias_ref[...])
    m = jnp.maximum(jnp.max(s, axis=1, keepdims=True),
                    jnp.max(st, axis=1, keepdims=True))
    # (a lane with no row admitted: every weight 0, not exp(0))
    p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m), 0.0)
    pt = jnp.where(st > 0.5 * NEG_INF, jnp.exp(st - m), 0.0)
    l = jnp.sum(p, axis=1, keepdims=True) + jnp.sum(pt, axis=1,
                                                    keepdims=True)
    o = weigh(p, rows) + weigh(pt, tail)
    o_ref[...] = (o / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def dsa_decode_attention(q, rows, bias, tail, tail_bias, lanes, count, *,
                         dv: int, sm_scale: float):
    """Attention of every head over the rows a selection named.

    q [B, H, dk] absorbed queries; rows [B, S, dk], bias [B, S], tail_bias
    [B, K] (`select_rows`); tail [B, K, dk] the running block's rows;
    lanes, count: the work list of the live lanes (`ops/ssm.live_lanes`).
    Returns o [B, H, dv]; a lane outside the list reads 0."""
    B, H, dk = q.shape
    S, K = rows.shape[1], tail.shape[1]

    def lane3(i, lanes):
        return (lanes[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(count,),
        in_specs=[pl.BlockSpec((None, H, dk), lane3),
                  pl.BlockSpec((None, S, dk), lane3),
                  pl.BlockSpec((None, 1, S), lane3),
                  pl.BlockSpec((None, K, dk), lane3),
                  pl.BlockSpec((None, 1, K), lane3)],
        out_specs=pl.BlockSpec((None, H, dv), lane3),
    )
    o = pl.pallas_call(
        functools.partial(_dsa_kernel, dv=dv, sm_scale=sm_scale),
        name="dsa_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 6 * S * dk * 2)),
        interpret=_interpret(),
    )(lanes, q, rows, bias[:, None, :], tail, tail_bias[:, None, :])
    listed = jnp.any((lanes[None, :] == jnp.arange(B)[:, None])
                     & (jnp.arange(B)[None, :] < count), axis=1)
    return jnp.where(listed[:, None, None], o, jnp.zeros_like(o))


# -------------------------------------------- decode: the walk over pages
def walk_bias(chosen, pos, tail_start, n_rows: int, K: int, group: int):
    """The rows `decode_select` chose, as a bias a row of the lane's
    table (`dsa_walk_attention`), with no gather and no scatter.

    chosen [B, n_rows // group + R] (`decode_select`); n_rows = table
    columns x page, K the tail's rows.  A pool row is admitted iff it is
    below `tail_start` and its group is chosen or it lies in the query's
    own incomplete group; a tail row by `select_rows`' rule.  Returns
    (bias [B, n_rows], tail_bias [B, K]: float32, 0 or -1e30; rpos, admit
    [B, n_rows + K]: every row's position and whether it is attended, the
    tail's last)."""
    B = chosen.shape[0]
    G = n_rows // group
    with jax.named_scope("dsa_select"):
        open_at = ((pos + 1) // group * group)[:, None]
        kpos = jnp.broadcast_to(jnp.arange(n_rows)[None, :], (B, n_rows))
        g0 = (tail_start // group)[:, None]
        # (the group `tail_start` cuts is scored in the tail: its bit
        # there speaks for its rows below the cut)
        admit = ((jnp.repeat(chosen[:, :G], group, axis=1)
                  | ((kpos >= g0 * group) & chosen[:, G:G + 1])
                  | (kpos >= open_at)) & (kpos < tail_start[:, None]))
        tpos = tail_start[:, None] + jnp.arange(K)[None, :]      # [B, K]
        r = tpos // group - g0
        held = jnp.any((r[:, :, None] == jnp.arange(chosen.shape[1] - G))
                       & chosen[:, None, G:], axis=-1)
        t_admit = (tpos <= pos[:, None]) & ((tpos >= open_at) | held)
        return (_bias(admit), _bias(t_admit),
                jnp.concatenate([kpos, tpos], axis=1),
                jnp.concatenate([admit, t_admit], axis=1))


def _walk_kernel(lane_ref, col_ref, page_ref, ts_ref,       # prefetch
                 q_ref, rp_ref, bias_ref, rt_ref, tbias_ref,
                 o_ref, acc_ref, m_ref, l_ref,
                 *, page: int, maxp: int, dv: int, sm_scale: float):
    """One (lane, page) step of `dsa_walk_attention`: the walk of
    `ops/paged_attention._mla_kernel`, every admission in the bias.
    q_ref [H, dk]; rp_ref [page, dk] one page of the pool and rt_ref
    [K, dk] the running block's rows (key AND value: the first dv
    columns); bias_ref [1, page], tbias_ref [1, K]; o_ref [H, dv]."""
    del page_ref                              # the index maps read it
    i = pl.program_id(0)
    col = col_ref[i]
    ts = jnp.minimum(ts_ref[lane_ref[i]], maxp * page)
    npages = (ts + page - 1) // page

    @pl.when(col == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def flash_update(rows, bias):
        s = lax.dot_general(
            q_ref[...], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=F32) * sm_scale + bias       # [H, n]
        m_prev = m_ref[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        # (a page with no row admitted: every weight 0, not exp(0))
        p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_cur), 0.0)
        l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p.astype(rows.dtype), rows[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        m_ref[:, :1] = m_cur

    @pl.when(col < npages)
    def _pages():
        flash_update(rp_ref[...], bias_ref[...])

    @pl.when(col >= npages - 1)               # the lane's last step
    def _tail():
        flash_update(rt_ref[...], tbias_ref[...])
        l = l_ref[:, :1]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def dsa_walk_attention(q, row_pages, bias, row_tail, tail_bias, page_table,
                       tail_start, *, dv: int, sm_scale: float,
                       plan: dict | None = None):
    """Attention of every head over the rows a bias admits, the lane's
    pool pages read where they lie.

    q [B, H, dk] absorbed queries; row_pages [n_pages, 1, page, dk] the
    layer's pool; bias [B, table columns x page], tail_bias [B, K]
    (`walk_bias`); row_tail [B, 1, K, dk]; plan:
    `attention_plan(page_table, tail_start, page)`, whose work list this
    walks (one step a live (lane, page) pair, the tail attended in the
    lane's last), built here if not given.  Returns o [B, H, dv]; an idle
    lane reads 0."""
    B, H, dk = q.shape
    page = row_pages.shape[2]
    K = row_tail.shape[2]
    maxp = page_table.shape[1]
    if plan is None:
        plan = attention_plan(page_table, tail_start, page)

    def page_map(i, lane, col, pages, *_):
        return (pages[i], 0, 0, 0)

    def bias_map(i, lane, col, *_):
        return (lane[i], col[i], 0, 0)

    def lane_map3(i, lane, *_):
        return (lane[i], 0, 0)

    def lane_map4(i, lane, *_):
        return (lane[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(plan["count"],),
        in_specs=[pl.BlockSpec((None, H, dk), lane_map3),
                  pl.BlockSpec((None, None, page, dk), page_map),
                  pl.BlockSpec((None, None, 1, page), bias_map),
                  pl.BlockSpec((None, None, K, dk), lane_map4),
                  pl.BlockSpec((None, 1, K), lane_map3)],
        out_specs=pl.BlockSpec((None, H, dv), lane_map3),
        scratch_shapes=[pltpu.VMEM((H, dv), F32),
                        pltpu.VMEM((H, LANE), F32),
                        pltpu.VMEM((H, LANE), F32)],
    )
    o = pl.pallas_call(
        functools.partial(_walk_kernel, page=page, maxp=maxp, dv=dv,
                          sm_scale=sm_scale),
        name="dsa_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(plan["lane"], plan["col"], plan["page"], tail_start,
      q, row_pages, bias.reshape(B, maxp, 1, page), row_tail,
      tail_bias[:, None, :])
    live = lanes_live(page_table)
    return jnp.where(live[:, None, None], o, jnp.zeros_like(o))


# A gathered row costs what RATIO streamed ones do (one lookup and one
# 1-row copy against a page's share of one long copy; PERF.md section 5,
# the kernel-alone table by ratio)
RATIO = 8


def rows_gathered(group: int, top: int) -> int:
    """Rows `select_rows` gathers a lane: those of the `top // group`
    best groups and of the query's own, in whole lane tiles."""
    return -(-(top // group + 1) * group // LANE) * LANE


def walks(table_rows: int, group: int, top: int) -> bool:
    """Which form a decode step's sparse attention takes, a rule of the
    call's shape: the walk (`dsa_walk_attention`: every page of the
    lane's table, a bias a row) where the table's rows are at most RATIO
    times the rows a selection gathers, else `select_rows` +
    `dsa_decode_attention`."""
    return table_rows <= RATIO * rows_gathered(group, top)


def decode_attend(q, latent_pages, latent_tail, page_table, pos, tail_start,
                  groups, ok, chosen, lanes, count, *, group: int, dv: int,
                  sm_scale: float, plan: dict | None = None):
    """One decode step's attention over what `decode_select` chose, in
    the form `walks` names for the table's shape.  Returns (o [B, H, dv],
    rpos, admit: the positions read and whether each is attended)."""
    n_rows = page_table.shape[1] * latent_pages.shape[2]
    if walks(n_rows, group, groups.shape[1] * group):
        bias, tail_bias, rpos, admit = walk_bias(
            chosen, pos, tail_start, n_rows, latent_tail.shape[2], group)
        with jax.named_scope("dsa_attn"):
            o = dsa_walk_attention(
                q, latent_pages, bias, latent_tail, tail_bias, page_table,
                tail_start, dv=dv, sm_scale=sm_scale, plan=plan)
        return o, rpos, admit
    rows, bias, tail_bias, rpos, admit = select_rows(
        latent_pages, latent_tail, page_table, pos, tail_start, groups, ok,
        group)
    with jax.named_scope("dsa_attn"):
        o = dsa_decode_attention(
            q, rows, bias, latent_tail[:, 0], tail_bias, lanes, count,
            dv=dv, sm_scale=sm_scale)
    return o, rpos, admit


def selection_counts(context: int, group: int, top: int
                     ) -> tuple[int, int]:
    """Host arithmetic for a query with `context` rows below and at it
    (its position + 1): (complete groups it scores, rows it attends:
    those of the best `top // group` groups and of its own incomplete
    group)."""
    complete = context // group
    return complete, (min(complete, top // group) * group
                      + context % group)


def attn_cost(H: int, dk: int, dv: int, rows: float
              ) -> tuple[float, float]:
    """(flops, bytes) `dsa_attn` NEEDS to attend `rows` selected rows in
    all (summed over lanes and steps): each row read once at its width
    (bfloat16) and scored and weighed for every head."""
    return 2.0 * H * (dk + dv) * rows, 2.0 * dk * rows


# ------------------------------------ prefill: masked flash on flash's walk
# `dsa_prefill` walks what `flash_fwd` walks (`flash_attention._walk` over
# `key_blocks`): the (row, query block, key block) triples under the
# diagonal and inside the rows' true lengths, one step each, the minor
# grid axis bounded by the longest row's count; a query block wholly past
# its row's length takes ONE step, which writes its zeros.  What it adds
# to that walk is the mask block a step, which alone says which pairs
# count: causality too, so the blocks the diagonal crosses (and the half
# of a 1,024-key block that lies above it) are no special case.
M_FLOOR = -1e20     # the running max starts here: exp(NEG_INF - m) is 0


def _prefill_kernel(qi_ref, ki_ref, flag_ref, q_ref, k_ref, v_ref, mask_ref,
                    o_ref, acc_ref, m_ref, l_ref, *, sm_scale: float,
                    stride: int):
    """One step of the walk of one (row, head): `_walk`'s flag says
    whether it opens a query block, multiplies a key block, closes the
    query block.  The flash accumulation of `ops/flash_attention`, the
    pairs a query attends given as a mask block: a pair the mask leaves
    out scores NEG_INF under a running max that never falls below
    M_FLOOR, so its weight is exactly 0."""
    fa = flash_attention
    flag = flag_ref[pl.program_id(0) * stride + pl.program_id(2)]

    @pl.when(flag & fa._FIRST != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(flag & (fa._INSIDE | fa._EDGE) != 0)
    def _pair():
        s = lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * sm_scale
        s = jnp.where(mask_ref[...] != 0, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        m_ref[:, 0] = m_cur

    @pl.when(flag & fa._LAST != 0)
    def _done():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)     # nothing attended: zeros
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def prefill_block(T: int) -> int:
    """Whether `masked_prefill_attention` takes rows of T positions: the
    largest of 512, 256, 128 that divides T; 0 where none does (the
    caller then runs the attention in XLA)."""
    return next((n for n in (512, 256, 128) if T % n == 0), 0)


def masked_prefill_attention(q, k, v, mask, lengths, *, sm_scale: float,
                             block_q: int = flash_attention.DEFAULT_BLOCK_Q,
                             block_k: int = flash_attention.DEFAULT_BLOCK_K):
    """softmax(sm_scale q k^T over the pairs `mask` admits) v over
    right-padded rows, the walk of `flash_fwd`: the block pairs under the
    diagonal and inside a row's true length, nothing else copied or
    computed.

    q, k [b, T, H, dq]; v [b, T, H, dv]; mask [b, T, T] int8 (1: the
    query attends the key; it holds nothing above the diagonal, which
    is all the causality the kernel has); lengths int32 [b], the rows'
    true lengths.  T a multiple of 128 (`prefill_block`); the blocks are
    `flash_attention.fit_blocks(T, T, block_q, block_k)`.  Returns o
    [b, T, H, dv]; a query that attends nothing reads 0, and so does
    every query of a query block wholly past its row's length."""
    b, T, H, dq = q.shape
    dv = v.shape[-1]
    fa = flash_attention
    bq, bk = fa.fit_blocks(T, T, block_q, block_k)
    steps = int(fa.key_blocks(T, T, None, bq, bk).sum())  # a full-length row
    *tables, total = fa._walk(
        fa.key_blocks(T, T, lengths.astype(jnp.int32), bq, bk, True, jnp),
        steps, bq, bk, True, jnp)

    def qmap(bi, h, p, qi, ki, flag):
        return (bi, h, qi[bi * steps + p], 0)

    def kmap(bi, h, p, qi, ki, flag):
        return (bi, h, ki[bi * steps + p], 0)

    def mmap(bi, h, p, qi, ki, flag):
        return (bi, qi[bi * steps + p], ki[bi * steps + p])

    qh, kh, vh = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))   # [b, H, T, w]
    o = pl.pallas_call(
        functools.partial(_prefill_kernel, sm_scale=sm_scale, stride=steps),
        name="dsa_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, H, jnp.max(total)),        # the device's number
            in_specs=[pl.BlockSpec((None, None, bq, dq), qmap),
                      pl.BlockSpec((None, None, bk, dq), kmap),
                      pl.BlockSpec((None, None, bk, dv), kmap),
                      pl.BlockSpec((None, bq, bk), mmap)],
            out_specs=pl.BlockSpec((None, None, bq, dv), qmap),
            scratch_shapes=[pltpu.VMEM((bq, dv), F32),
                            pltpu.VMEM((bq, LANE), F32),
                            pltpu.VMEM((bq, LANE), F32)]),
        out_shape=jax.ShapeDtypeStruct((b, H, T, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(*tables, qh, kh, vh, mask)
    return jnp.swapaxes(o, 1, 2)


# What a serving module whose prefill calls `dsa_prefill` reports of it
# (models/serving.ServingSpec.counters): dsa_prefill_blocks /
# dsa_prefill_blocks_dense = the share of the causal walk that lies
# inside the rows' true lengths.
PREFILL_COUNTERS = {
    "dsa_prefill_blocks": "(row, query block, key block) triples "
                          "dsa_prefill multiplies, a full-prompt prefill "
                          "program, x sparse layers",
    "dsa_prefill_blocks_dense": "The triples the same calls would multiply "
                                "for rows of the full length (causal)",
}


def prefill_work(layers: int, true_lens, bucket: int) -> tuple[dict, dict]:
    """`ServingSpec.prefill_work` of a program of len(true_lens) rows
    padded to `bucket` whose `layers` sparse layers each call
    `masked_prefill_attention` (host arithmetic,
    `flash_attention.attn_blocks`); 0 where the bucket runs in XLA
    (`prefill_block`)."""
    walked = dense = 0
    if prefill_block(bucket):
        bq, bk = flash_attention.fit_blocks(bucket, bucket)
        walked, dense = (
            layers * flash_attention.attn_blocks(bucket, lens, bq, bk)
            for lens in (true_lens, [bucket] * len(true_lens)))
    return {"dsa_prefill_blocks": walked,
            "dsa_prefill_blocks_dense": dense}, {}


# What a serving module with learned sparse attention reports of its
# selection, a live lane's every decode step, x those layers
# (models/serving.ServingSpec.counters).
COUNTERS = {
    "dsa_rows_context": "Rows in a lane's context at a sparse layer's "
                        "decode step, summed over live lanes, steps and "
                        "sparse layers",
    "dsa_groups_scored": "Complete groups the indexer scored, summed "
                         "likewise",
    "dsa_rows_selected": "Rows the selection attended, summed likewise",
    "dsa_rows_read": "Rows the attend path read from the pool (whole "
                     "pages where it walks them, a selection's gathered "
                     "rows elsewhere), summed likewise",
}


def decode_work(layers: int, group: int, top: int, rows, k: int, page: int,
                maxp: int) -> tuple[dict, dict]:
    """One decode window of `k` steps over live lanes that start it on
    `rows` cached rows each, x `layers` sparse layers, under a table of
    `maxp` columns of `page` rows (host arithmetic, `selection_counts`
    and `walks`), as COUNTERS' rows; the span shows the same."""
    sel = [0, 0, 0, 0]      # in COUNTERS' order
    walk = walks(maxp * page, group, top)
    for r in rows:
        for ctx in range(r + 1, r + 1 + k):
            scored, kept = selection_counts(ctx, group, top)
            sel[0] += ctx
            sel[1] += scored
            sel[2] += kept
        # (the pool holds the window's first `r` rows for all its steps)
        sel[3] += k * (-(-min(r, maxp * page) // page) * page if walk
                       else rows_gathered(group, top))
    work = dict(zip(COUNTERS, (n * layers for n in sel)))
    return work, work
