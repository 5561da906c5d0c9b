"""models/glm5_next.py (KDA linear-attention layers beside latent
attention read through a learned selection, an mHC residual, routed
experts) against the plain float32 reference the benchmark holds it to
(`benchmarks/harness/refs/glm5_next.py`, which imports nothing of the
program): the prompt pass, paged decode through both pool leaves and the
lane state past the point where the selection starts to drop rows, the
ENGINE's own logits with lanes reused (one engine run shared by the
file's cases: `family_contract`), the pooled index key written once,
the expert shares, the residual maps, the counters and the controls a
sound comparison must fail."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_contract as contract  # rootdir-relative (no pkg)
import sparse_walk_cases
from family_contract import gap as _gap, tokens as _tokens
from serving_reference import Seam, served_logits

from benchmarks.harness.refs import glm5_next as ref
from ray_tpu.models import glm5_next, named_config, serving_model
from ray_tpu.ops import (live_rows, paged_attention,
                         sparse_attention as dsa, ssm)
from ray_tpu.serve.llm import LLMEngine, LLMServer

# float32 weights: the served path and the reference then differ by
# summation order alone
CFG = dataclasses.replace(named_config("glm5-next-debug"),
                          dtype=jnp.float32)
PAGE, K = 16, 4
TOL = 5e-5
CONTROL = 2e-3
N_KDA = CFG.count(glm5_next.KDA)


def model_of(cfg) -> dict:
    return dict(
        hc_mult=cfg.hc_mult, hc_eps=cfg.hc_eps,
        hc_sinkhorn_iters=cfg.hc_iters, rms_norm_eps=cfg.norm_eps,
        linear_attn_config=dict(
            num_heads=cfg.n_heads, head_dim=cfg.kda_head_dim,
            short_conv_kernel_size=cfg.conv_kernel,
            gate_lower_bound=cfg.gate_lower_bound),
        num_attention_heads=cfg.n_heads, qk_nope_head_dim=cfg.qk_head_dim,
        index_kpool=cfg.index_pool, index_topk=cfg.index_topk,
        index_n_heads=cfg.index_heads, index_head_dim=cfg.index_dim,
        num_experts_per_tok=cfg.top_k, norm_topk_prob=True,
        routed_scaling_factor=cfg.routed_scaling,
        swiglu_limit=cfg.swiglu_limit, layer_types=list(cfg.layer_types),
        mlp_layer_types=list(cfg.ffn_types),
        num_hidden_layers=cfg.n_layers,
        experts_held=list(cfg.experts_held))


MODEL = model_of(CFG)
# The sound program's seam, compiled once a shape for the file (true
# lengths are arguments), and the reference at ONE length (54 is the
# longest sequence a case reads: 40 prompt tokens and 14 served).
SOUND = Seam(glm5_next, CFG)
_ref_logits = contract.one_length(
    lambda p, seq: ref.logits(p, seq, MODEL), 56)


@pytest.fixture(scope="module")
def params():
    return glm5_next.init_params(jax.random.PRNGKey(7), CFG)


# --------------------------------------------------- (a) the prompt pass
PREFILL_LENS = [5, 16, 37]


@pytest.fixture(scope="module")
def prefill_rows(params):
    """ONE prompt pass for the lengths: a row each of one program."""
    return contract.prefill_rows(
        SOUND, params, [_tokens(n, n) for n in PREFILL_LENS], PREFILL_LENS)


@pytest.mark.parametrize("n", PREFILL_LENS)
def test_prefill_logits_equal_the_reference(params, prefill_rows, n):
    """37 positions: nine complete groups of which four are kept, so the
    selection drops rows in the second half of the prompt.  Every true
    position of the row against the reference's."""
    toks, h = prefill_rows
    i = PREFILL_LENS.index(n)
    got = glm5_next.project_logits(params, h[i, :n])
    assert _gap(got, _ref_logits(params, toks[i, :n])) < TOL


@pytest.mark.parametrize("form", ["walk", "gather"])
@pytest.mark.parametrize("n,bucket,new", [(21, 32, 11), (3, 16, 9)])
def test_padded_prefill_then_paged_decode_equals_the_reference(
        params, monkeypatch, n, bucket, new, form):
    """The prompt padded to a bucket beside a longer row, scattered into
    both pool leaves and lane 1, then decode in windows of four: a group
    of index keys completes mid-window, groups straddle the windows'
    edges, and the context passes the selection's size.  In both forms of
    the decode step's sparse attention (the table here is narrow: the
    walk; RATIO 0: the gather a long table gets)."""
    seam = SOUND
    if form == "gather":
        monkeypatch.setattr(dsa, "RATIO", 0)
        seam = _GATHER
    tok = _tokens(n + new, 3 * n)
    got = served_logits(seam, params, CFG, tok[:n], tok[n:], bucket,
                        page=PAGE, k=K)
    assert _gap(got, _ref_logits(params, tok, last=new + 1)) < TOL


def _apart(got, want) -> float:
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


# `dsa.RATIO` picks the form of the DECODE step's sparse attention and
# nothing else (`dsa.walks`): the gather's seam is the sound one with a
# decode step of its own, traced (at its first call) under RATIO 0.
_GATHER = SOUND.retraced("decode_step")
# The prompt pass walked in chunks of 8 and of 5 positions: its own
# program a chunk (traced under `_chunked`), the scatter and the decode
# step the sound seam's.
_WALKED = {chunk: SOUND.retraced("serve_prefill")
           for chunk in (8, 5)}


def _chunked(mp, chunk):
    """The prompt pass's position-wise parts walked in chunks of `chunk`
    positions (None: the module's own, one chunk at these sizes: the bare
    functions, straight-line).  The seam whose prompt pass is traced
    under it."""
    if chunk:
        mp.setattr(live_rows, "walk",
                   functools.partial(live_rows.walk, chunk=chunk))
    return _WALKED[chunk]


@pytest.mark.parametrize("n,bucket,new,chunk", [(21, 32, 11, 8),
                                                (3, 16, 9, 5)])
def test_walked_prefill_then_paged_decode_equals_the_reference(
        params, monkeypatch, n, bucket, new, chunk):
    """The same with the prompt pass's walks looped over chunks of 8
    positions and of 5 (which divide no bucket: the last chunk is
    clamped)."""
    tok = _tokens(n + new, 3 * n)
    got = served_logits(_chunked(monkeypatch, chunk), params, CFG, tok[:n],
                        tok[n:], bucket, page=PAGE, k=K)
    assert _gap(got, _ref_logits(params, tok, last=new + 1)) < TOL


@pytest.mark.parametrize("lens,T,chunk", [
    ([21], 32, 8), ([13, 32], 32, 8), ([30, 11], 37, 8), ([19], 32, 5),
    ([1, 1], 16, 8)],
    ids=lambda v: "_".join(map(str, v)) if isinstance(v, list) else str(v))
def test_the_walked_prefill_hands_what_the_straight_line_hands(
        params, monkeypatch, lens, T, chunk):
    """Everything the seam returns, for one row and for two of unequal
    lengths, at a T the chunk divides (32 by 8) and at ones it does not
    (37 by 8, 32 by 5): the hidden row at each last true position, the
    latent and index rows below the true lengths, the conv rows, the KDA
    states, `ipart` and the routed counts are the straight-line
    program's; past the walked chunks the hidden rows are zeros."""
    toks = jnp.asarray(np.stack([_tokens(T, 5 + i) for i in range(len(lens))]))
    tl = jnp.asarray(lens, jnp.int32)
    want = SOUND.serve_prefill(params, toks, tl)
    got = _chunked(monkeypatch, chunk).serve_prefill(params, toks, tl)
    g = CFG.index_pool
    for i, n in enumerate(lens):
        assert _gap(got[0][i, n - 1], want[0][i, n - 1]) < TOL
        assert _gap(got[1][0][i, :n], want[1][0][i, :n]) < TOL
        if n >= g:
            assert _gap(got[2][0][i, :n // g], want[2][0][i, :n // g]) < TOL
        assert _apart(got[3]["ipart"][0][i], want[3]["ipart"][0][i]) < TOL
        for j in range(N_KDA):
            assert _gap(got[3]["kda"][j][i], want[3]["kda"][j][i]) < TOL
            assert _apart(got[3]["conv"][j][i], want[3]["conv"][j][i]) < TOL
    np.testing.assert_array_equal(np.asarray(got[4]), np.asarray(want[4]))
    done = min(T, live_rows.walked(T, max(lens), chunk))
    assert not np.asarray(got[0][:, done:]).any()
    assert np.asarray(want[0][:, max(lens):]).any() or max(lens) == T


@pytest.mark.parametrize("part", ["dense", "routed", "kda", "dsa"])
@pytest.mark.parametrize("lens,T", [([21, 9], 32), ([19], 29)],
                         ids=["two_rows_T32", "one_row_T29"])
def test_a_sublayers_halves_ride_in_the_walks(params, monkeypatch, part,
                                              lens, T):
    """A prefill layer hands the residual path's halves, written over the
    n streams apart, to the mixer or the feed-forward (`around=`), which
    compute the second inside their walk of the rows: the streams after
    it are `sublayer`'s around the bare function at every live position,
    zeros past the walked chunks; and the bare function walked alone (the
    judge's call) is the straight line too."""
    b = len(lens)
    tl = jnp.asarray(lens, jnp.int32)
    live = jnp.arange(T)[None, :] < tl[:, None]
    X = jax.random.normal(jax.random.PRNGKey(3), (b, T, CFG.hc_mult, CFG.dim))
    lid = {"dense": 0, "routed": 1, "kda": 0, "dsa": 1}[part]
    lp = params["layers"][lid]
    hp = lp["hc_ffn" if part in ("dense", "routed") else "hc_mix"]

    def fn(x, **kw):
        if part in ("dense", "routed"):
            return glm5_next.ffn(x, lp, lid, CFG, live, **kw)
        mixer = glm5_next.kda_prefill if part == "kda" \
            else glm5_next.dsa_prefill
        return mixer(x, lp, CFG, tl, **kw)

    # each of the three a program of its own (a compile, not a dispatch
    # an operation)
    around = jax.jit(lambda X: glm5_next.sublayer(X, hp, CFG, fn))
    want, aux = around(X)
    _chunked(monkeypatch, 8)
    alone, _ = jax.jit(lambda X: glm5_next.sublayer(X, hp, CFG, fn))(X)
    got, aux2 = jax.jit(lambda X: fn(
        tuple(X[:, :, j] for j in range(CFG.hc_mult)),
        around=glm5_next.mhc_halves(hp, CFG)))(X)
    got = jnp.stack(got, axis=2)
    done = min(T, live_rows.walked(T, max(lens), 8))
    for i, n in enumerate(lens):
        assert _gap(got[i, :n], want[i, :n]) < TOL
        assert _gap(alone[i, :n], want[i, :n]) < TOL
    assert not np.asarray(got[:, done:]).any()
    if part == "dsa":       # latent rows, index rows below the lengths
        for i, n in enumerate(lens):
            assert _apart(aux2[0][i, :n], aux[0][i, :n]) < TOL
            assert _apart(aux2[1][i, :n // 4], aux[1][i, :n // 4]) < TOL
        aux2, aux = aux2[2], aux[2]
    for a, w in zip(jax.tree.leaves(aux2), jax.tree.leaves(aux)):
        assert _apart(a, w) < TOL


@pytest.mark.parametrize("lens,bucket,want", [
    ([6216], 8192, 7 * 1024), ([4097], 8192, 5 * 1024),
    ([8192], 8192, 8192), ([5000, 7169], 8192, 2 * 8192),
    ([1500], 2048, 2048), ([900], 1024, 1024), ([300, 40, 7, 7], 512,
                                                4 * 512)])
def test_the_prefill_work_counts_the_positions_the_walks_compute(
        lens, bucket, want):
    """`prefill_walked_tokens`: rows x the chunks of `live_rows.CHUNK`
    under the longest true length for a bucket over a chunk, rows x
    bucket at or under one."""
    C = live_rows.CHUNK
    work, shown = glm5_next.serving_spec(CFG).prefill_work(
        np.asarray(lens, np.int32), bucket)
    assert work["prefill_walked_tokens"] == want == len(lens) * (
        -(-max(lens) // C) * C if bucket > C else bucket)
    assert shown["walked_tokens"] == want
    assert set(work) <= set(glm5_next.serving_spec(CFG).counters)


def test_the_prefill_hands_the_state_at_the_true_length(params):
    tok = _tokens(32, 5)
    lens = jnp.asarray([32, 13], jnp.int32)
    toks = jnp.asarray(np.stack([tok, tok]))
    _, latent, index, state, _ = SOUND.serve_prefill(params, toks, lens)
    X = ref.embed(params, tok[:13], MODEL)
    kda_i = 0
    for lid, lp in enumerate(params["layers"]):
        X, _, info, _ = ref.layer(X, lp, lid, MODEL)
        if CFG.layer_types[lid] == glm5_next.KDA:
            assert _gap(state["kda"][kda_i][1], info["state"]) < TOL
            assert _gap(state["conv"][kda_i][1], info["conv"]) < TOL
            kda_i += 1
        else:
            assert _gap(latent[0][1, :13, 0], info["latent"]) < TOL
            assert _gap(index[0][1, :3, 0], info["index"]) < TOL
            assert _gap(state["ipart"][0][1], info["ipart"]) < TOL


# ----------------------------------------- (b) the selection's mechanism
def test_sparse_decode_is_dense_latent_decode_below_the_selections_size():
    """While no more groups are complete than the selection keeps, the
    gathered rows are the whole context: `dsa_attn` over them equals the
    dense latent kernel's oracle."""
    B, H, w, page, maxp, g, top, kt = 3, 4, 32, 16, 4, 4, 64, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    pages = jax.random.normal(ks[0], (1 + B * maxp, 1, page, w))
    tail = jax.random.normal(ks[1], (B, 1, kt, w))
    q = jax.random.normal(ks[2], (B, H, w))
    table = jnp.arange(1, 1 + B * maxp, dtype=jnp.int32).reshape(B, maxp)
    table = table.at[1].set(0)                   # lane 1 holds no request
    ts = jnp.asarray([37, 0, 22], jnp.int32)
    pos = ts + 2
    n_complete = (pos + 1) // g
    scores = jax.random.normal(ks[3], (B, top // g + 2))
    idx, ok, _ = dsa.select_groups(
        jnp.where(jnp.arange(scores.shape[1])[None] < n_complete[:, None],
                  scores, dsa.NEG_INF),
        jnp.full((B,), scores.shape[1], jnp.int32), top // g)
    rows, bias, tail_bias, _, _ = dsa.select_rows(pages, tail, table, pos,
                                                  ts, idx, ok, g)
    lanes, count = ssm.live_lanes(paged_attention.lanes_live(table))
    got = dsa.dsa_decode_attention(q, rows, bias, tail[:, 0], tail_bias,
                                   lanes, count, dv=w, sm_scale=0.3)
    want = paged_attention.mla_decode_reference(
        q, pages, tail, table, pos, ts, dv=w, sm_scale=0.3)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(got[1]).max()) == 0.0


@pytest.mark.parametrize("case", sparse_walk_cases.CASES)
def test_the_walk_attends_what_the_gather_attends(monkeypatch, case):
    """Groups of 4, the query's own incomplete group always attended:
    the walk over the lane's pages under a bias, the gather + `dsa_attn`
    and a sort-and-softmax oracle admit the same positions and give the
    same output: past the selection's size, under it (dense), with the
    window's complete groups chosen (rows held in the tail, and the rows
    of the group the block start cuts in the pool), with equal scores at
    the k-th, with a page partly below the block start; an idle lane
    reads 0."""
    out = sparse_walk_cases.check(case, 4, 16, False, monkeypatch)
    sets = out["walk"][1]
    if case == "dense":
        assert sets[0] == list(range(13)) and sets[3] == list(range(8))
    elif case != "cut_page":
        # four groups and the own one's rows (position 63: none open)
        assert [len(sets[b]) for b in (0, 2, 3)] == [16, 16 + 3, 16 + 3]
    if case == "held":
        # lane 2 (block start 37, query 42): group 9 (36..39) is scored
        # in the tail and row 36 lies in the pool
        assert {36, 37, 38, 39} <= set(sets[2])


@pytest.mark.parametrize("group,top,H,dk", [(1, 2048, 128, 640),
                                            (4, 2048, 64, 512)])
def test_the_tables_shape_names_the_form(group, top, H, dk):
    """`walks`: a table of at most RATIO selections' rows is walked, a
    wider one gathered from, at both cells' widths; 18 columns of 512 (the
    cells') and the judge's narrower tables walk.  The rows read tell the
    forms apart without running either."""
    S = dsa.rows_gathered(group, top)
    assert S == 2176 and dsa.walks(18 * 512, group, top)
    edge = dsa.RATIO * S // 512
    K = 8

    def read(maxp):
        i32 = jnp.int32
        B, n = 2, top // group
        sd = jax.ShapeDtypeStruct
        _, rpos, admit = jax.eval_shape(
            lambda *a: dsa.decode_attend(*a, group=group, dv=512,
                                         sm_scale=0.1),
            sd((B, H, dk), jnp.bfloat16),
            sd((1 + B * maxp, 1, 512, dk), jnp.bfloat16),
            sd((B, 1, K, dk), jnp.bfloat16), sd((B, maxp), i32),
            sd((B,), i32), sd((B,), i32), sd((B, n), i32),
            sd((B, n), jnp.bool_),
            sd((B, maxp * 512 // group + -(-K // group)), jnp.bool_),
            sd((B,), i32), sd((), i32))
        assert rpos.shape == admit.shape
        return rpos.shape[1] - K

    assert dsa.walks(edge * 512, group, top)
    assert not dsa.walks((edge + 1) * 512, group, top)
    assert read(edge) == edge * 512 and read(edge + 1) == S
    # host arithmetic of the same rule: whole pages on the walk, S a
    # lane-step on the gather
    for maxp, want in ((edge, 3 * 13 * 512), (edge + 1, 3 * S)):
        work, shown = dsa.decode_work(1, group, top, [6500], 3, 512, maxp)
        assert work == shown and work["dsa_rows_read"] == want


# (T, the rows' true lengths, block_q, block_k; None: `fit_blocks`' own)
_PREFILL_CASES = {
    "128": (128, [128], None, None),
    "384": (384, [384], 128, 128),
    "two_lengths": (512, [200, 512], 128, 128),
    "keys_past_the_diagonal": (512, [512], 128, 256),
    "four_key_blocks": (512, [512, 130], 256, 128),
    "length_inside_a_block": (384, [300], 128, 128),
}


@pytest.mark.parametrize("case", list(_PREFILL_CASES))
def test_the_masked_prefill_kernel_equals_a_masked_softmax(case):
    """`dsa_prefill` (interpret mode): one block; three blocks of 128 with
    the pairs above the diagonal never walked; rows of two true lengths
    in one call (a query block wholly past its row's length reads exactly
    0); key blocks that reach past the diagonal (the mask alone carries
    causality); several key blocks a query block; a length that ends
    inside a block.  A query that attends nothing reads 0 under the
    floored max, in a key block or in all of them."""
    T, lens, bq, bk = _PREFILL_CASES[case]
    b, H, dq = len(lens), 2, 32
    ks = jax.random.split(jax.random.PRNGKey(T), 4)
    q, k, v = (jax.random.normal(ks[i], (b, T, H, dq)) for i in range(3))
    mask = ((jax.random.uniform(ks[3], (b, T, T)) < 0.3)
            | jnp.eye(T, dtype=bool)[None]) & jnp.tril(
                jnp.ones((T, T), bool))[None]
    mask = mask.at[:, 5].set(False)
    if T > 300:     # a query whose first two key blocks admit nothing
        mask = mask.at[:, 300, :256].set(False)
    blocks = {} if bq is None else {"block_q": bq, "block_k": bk}
    got = dsa.masked_prefill_attention(
        q, k, v, mask.astype(jnp.int8), jnp.asarray(lens, jnp.int32),
        sm_scale=0.2, **blocks)
    s = jnp.where(mask[:, None],
                  jnp.einsum("bthd,bshd->bhts", q, k) * 0.2, -1e30)
    want = jnp.einsum("bhts,bshd->bthd",
                      jax.nn.softmax(s, -1) * mask[:, None], v)
    for r, n in enumerate(lens):
        assert float(jnp.abs(got[r, :n] - want[r, :n]).max()) < 1e-5
        past = -(-n // (bq or T)) * (bq or T)   # the first block past it
        assert float(jnp.abs(got[r, past:]).sum()) == 0.0
    assert float(jnp.abs(got[:, 5]).max()) == 0.0
    assert dsa.prefill_block(8192) == 512 and dsa.prefill_block(37) == 0


@pytest.mark.parametrize("lens, bucket", [([8192], 8192), ([4097], 8192),
                                          ([6216, 900], 8192),
                                          ([2750], 2816), ([9, 17], 32)])
def test_dsa_prefill_blocks_count_the_steps_the_walk_multiplies(lens, bucket):
    """Host arithmetic: `dsa_prefill_blocks` is the steps `_walk` flags
    as work for the same lengths, x sparse layers; the dense count is a
    full-length row's; the rows that count `flash_fwd`'s banded walk in
    dots3's spec are what `band_work` alone gives, to the integer; a
    bucket the kernel does not take (XLA) counts nothing."""
    from ray_tpu.models import dots3_note
    from ray_tpu.ops import flash_attention as fa

    big = dots3_note.Dots3NoteConfig(
        vocab_size=19072, layer_types=(dots3_note.FULL,) * 2
        + (dots3_note.WINDOW,) * 3, experts_held=(0, 32))
    work, shown = dots3_note.serving_spec(big).prefill_work(lens, bucket)
    band, _ = fa.band_work(big.window, lens, bucket)
    assert shown == {} and {n: work[n] for n in band} == band
    glm, _ = glm5_next.serving_spec(CFG).prefill_work(lens, bucket)
    if not dsa.prefill_block(bucket):
        assert work["dsa_prefill_blocks"] == glm["dsa_prefill_blocks"] == 0
        assert work["dsa_prefill_blocks_dense"] == 0
        return
    bq, bk = fa.fit_blocks(bucket, bucket)
    full = fa.key_blocks(bucket, bucket, None, bq, bk)
    n_keys = fa.key_blocks(bucket, bucket, np.asarray(lens), bq, bk)
    *_, flag, total = fa._walk(n_keys, int(full.sum()), bq, bk, True, np)
    walked = int((flag & (fa._INSIDE | fa._EDGE) != 0).sum())
    assert work["dsa_prefill_blocks"] == 2 * walked
    assert work["dsa_prefill_blocks_dense"] == 2 * len(lens) * int(full.sum())
    assert glm["dsa_prefill_blocks"] == walked          # one sparse layer
    assert glm["dsa_prefill_blocks_dense"] == len(lens) * int(full.sum())
    # the steps beyond the work: one a query block wholly past the length
    assert int(total.sum()) - walked == int((n_keys == 0).sum())


def test_a_prompt_of_a_kernel_bucket_equals_the_reference(params):
    """128 positions: the bucket at which the prefill's sparse attention
    runs in the kernel and not in XLA."""
    tok = _tokens(128, 9)
    # (without lengths: every row is the program's width long)
    h = jax.jit(lambda p, t: glm5_next.prefill(p, t, CFG)[0])(
        params, jnp.asarray(tok[None]))
    got = glm5_next.project_logits(params, h[0])
    assert _gap(got, ref.logits(params, tok, MODEL)) < TOL


def test_the_selection_keeps_the_best_groups_and_the_own_group():
    """Six complete groups, two kept: the query attends their eight rows
    and the rows of its own incomplete group, nothing else."""
    g, top = 4, 8
    pos = jnp.asarray([26])                     # groups 0..5 complete
    scores = jnp.asarray([[0.1, 0.9, -0.3, 0.5, 0.2, 0.0, 7.0]])
    mask, chosen = dsa.selected_mask(scores, pos, 28, g, top)
    assert chosen[0].tolist() == [False, True, False, True, False, False,
                                  False]
    assert np.flatnonzero(np.asarray(mask[0])).tolist() == [
        4, 5, 6, 7, 12, 13, 14, 15, 24, 25, 26]
    assert dsa.selection_counts(27, g, top) == (6, 8 + 3)
    assert dsa.selection_counts(7, g, top) == (1, 4 + 3)


def test_a_group_completing_mid_window_writes_its_pooled_key_once(params):
    """Decode four steps from position 13: position 15 completes group 3,
    whose pooled key lands in the index tail's row 0 at that step and is
    not touched again; the merge writes it, and only it, to the pool."""
    n = 13
    tok = _tokens(n + K, 17)
    toks = jnp.asarray(np.stack([tok[:16], tok[:16]]))
    lens = jnp.asarray([16, n], jnp.int32)
    h, latent, index, state, _ = SOUND.serve_prefill(params, toks, lens)
    cache = glm5_next.init_paged_cache(CFG, 2, 9, PAGE)
    table = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
    cols = np.arange(16) // PAGE
    cache = SOUND.serve_scatter(
        cache, latent, index, state, jnp.asarray(table[:, cols]),
        jnp.tile(jnp.arange(16) % PAGE, (2, 1)), jnp.arange(2), lens)
    before = cache["index"][0]
    pages = {"latent": cache["latent"], "index": cache["index"]}
    tails = {"latent": [jnp.zeros((2, 1, K, CFG.kv_lora_rank))],
             "index": [jnp.zeros((2, 1, 1, CFG.index_dim))]}
    st, ts = cache["state"], cache["pos"]
    seen = []
    for j in range(K):
        _, tails, st, _ = SOUND.decode_step(
            params, pages, tails, st, jnp.asarray([1, int(tok[n + j])]),
            ts + j, ts, j, jnp.asarray(table))
        seen.append(np.asarray(tails["index"][0][1, 0, 0]))
    assert not seen[0].any() and not seen[1].any()      # positions 13, 14
    assert seen[2].any() and (seen[3] == seen[2]).all()  # 15 completes it
    X = ref.embed(params, tok, MODEL)
    X = ref.layer(X, params["layers"][0], 0, MODEL)[0]
    info = ref.layer(X, params["layers"][1], 1, MODEL)[2]
    assert _gap(seen[2], info["index"][3]) < TOL
    merged = paged_attention.merge_tail_pages(
        before, tails["index"][0], jnp.asarray(table), ts, K, per=4)
    changed = np.argwhere(np.asarray(merged != before).any(-1))
    # lane 1's group 3 (its first page); lane 0, at positions 16..19,
    # completed group 4 (the first row of its second page); nothing else
    assert changed.tolist() == [[2, 0, 0], [5, 0, 3]]


# ------------------------------------------------ (c) through the engine
PROMPTS = (40, 3, 17, 1, 29)
NEW, LANES = 14, 3


@pytest.fixture(scope="module")
def served(params):
    """ONE engine run for the file (`family_contract.served_run`): three
    lanes whose state was marked, a request of 9 + 9 tokens alone, then
    five prompts at once."""
    return contract.served_run(
        glm5_next, CFG, params, lanes=LANES, kv_pages=19, page=PAGE, k=K,
        prompts=[_tokens(n, 10 + n).tolist() for n in PROMPTS], new=NEW)


def test_engine_logits_equal_the_reference_across_lane_reuse(
        params, served):
    """Three lanes, a request and then five prompts: a lane that served
    one request serves another, and neither the KDA state, the incomplete
    group's sum nor a pool row may leak.  The LOGITS the engine's own
    programs computed at every served position equal the reference's
    full forward; the counters equal the host arithmetic they stand
    for."""
    st = served["stats"]
    assert st["completed"] == 1 + len(PROMPTS) and st["preemptions"] == 0
    for i, (prompt, out) in enumerate(zip(served["prompts"],
                                          served["outs"])):
        assert len(out["tokens"]) == NEW
        want = _ref_logits(params, (prompt + out["tokens"])[:-1], last=NEW)
        assert contract.engine_gap(served, i, want) < TOL
    loop = st["loop"]
    assert loop["ssm_lane_steps"] == loop["lane_steps_live"] * N_KDA
    assert loop["prefill_scan_chunks"] == N_KDA * sum(
        -(-len(p) // CFG.kda_chunk)
        for p in served["prompts"] + [served["first_prompt"]])
    # every live lane-step of the sparse layer: its context, the complete
    # groups it scored, the rows it attended (under 100 %: it was sparse)
    assert loop["dsa_groups_scored"] <= loop["dsa_rows_context"] // 4
    assert loop["dsa_rows_selected"] < loop["dsa_rows_context"]
    assert loop["dsa_rows_selected"] <= loop["lane_steps_live"] * (
        CFG.index_topk + 3)
    cache = st["cache"]
    assert cache["kind"] == "latent" and set(cache["by_leaf"]) == {
        "latent", "index"}
    assert cache["by_leaf"]["index"]["positions_per_row"] == 4
    assert cache["by_leaf"]["latent"]["positions_per_row"] == 1
    assert cache["row_bytes"] == 4 * (CFG.kv_lora_rank + CFG.index_dim // 4)
    lane = st["lane_state"]
    assert lane["layers"] == N_KDA and set(lane["by_kind"]) == {
        "conv", "kda", "ipart"}
    assert lane["by_kind"]["kda"] == N_KDA * LANES * 4 * 16 * 16 * 4
    assert lane["prefix_cache"] == "off: lane state"


def test_an_idle_lanes_state_is_bit_unchanged_by_a_decode_window(served):
    """The run's first request (9 + 9 tokens) alone in an engine of three
    lanes: one lane's KDA state and incomplete group's sum were written,
    the idle lanes' are bit-unchanged."""
    assert len(served["first"]["tokens"]) == 9
    used = contract.lanes_written(
        served, lambda s: np.moveaxis(s["kda"], 1, 0))
    assert len(used) == 1
    assert contract.lanes_written(
        served, lambda s: np.moveaxis(s["ipart"], 1, 0)) in ([], used)


# ------------------------------------------------------ (d) the residual
def test_the_residual_map_is_doubly_stochastic(params):
    X = jax.random.normal(jax.random.PRNGKey(4), (2, 9, CFG.hc_mult,
                                                  CFG.dim))
    pre, post, res = glm5_next.mhc_maps(X, params["layers"][0]["hc_mix"],
                                        CFG)
    assert float(jnp.abs(res.sum(-1) - 1).max()) < 1e-3
    assert float(jnp.abs(res.sum(-2) - 1).max()) < 1e-3
    assert bool(jnp.all((pre > 0) & (pre < 1) & (post > 0) & (post < 2)))
    want = ref.mhc_maps(X[0], params["layers"][0]["hc_mix"], MODEL)
    for got, w in zip((pre, post, res), want):
        assert _gap(got[0], w) < TOL


def test_identity_maps_make_the_plain_residual(monkeypatch):
    """H_res = I, H_pre = H_post = e_0: stream 0 is x + F(x), the others
    stand still."""
    n = CFG.hc_mult
    e0 = jnp.zeros((n,)).at[0].set(1.0)
    monkeypatch.setattr(glm5_next, "mhc_maps", lambda X, hp, cfg: (
        jnp.broadcast_to(e0, X.shape[:-1]),
        jnp.broadcast_to(e0, X.shape[:-1]),
        jnp.broadcast_to(jnp.eye(n), X.shape[:-2] + (n, n))))
    X = jax.random.normal(jax.random.PRNGKey(8), (5, n, CFG.dim))
    out, _ = glm5_next.sublayer(X, None, CFG, lambda x: (jnp.tanh(x), None))
    assert float(jnp.abs(out[:, 0] - (X[:, 0] + jnp.tanh(X[:, 0]))).max()) \
        < 1e-6
    assert bool(jnp.all(out[:, 1:] == X[:, 1:]))


# ------------------------------------------------- (e) ranges of experts
def test_the_eight_shares_and_the_shared_expert_once_add_up():
    """Eight chips each hold one of the router's eight experts; every one
    computes the shared expert alike.  Their routed parts plus the shared
    expert counted ONCE are the uncut layer of the reference."""
    lp = glm5_next.init_params(jax.random.PRNGKey(3), CFG)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.dim))
    want, _ = ref.ff(x, lp, 1, MODEL)
    h2 = glm5_next.rmsnorm(x, lp["norm2"], CFG.norm_eps)
    parts = glm5_next.shared_ffn(h2, lp, CFG.dtype, CFG.swiglu_limit)
    n = 0
    for lo in range(8):
        chip = dataclasses.replace(CFG, experts_held=(lo, lo + 1))
        held = dict(lp, w13=lp["w13"][lo:lo + 1], w2=lp["w2"][lo:lo + 1])
        y, c = glm5_next.routed_ffn(h2, held, chip)
        parts, n = parts + y, n + int(c[2])
    assert float(jnp.abs(parts - want).max()) < TOL
    assert n == 24 * CFG.top_k
    chip = dataclasses.replace(CFG, experts_held=(2, 5))
    held = dict(lp, w13=lp["w13"][2:5], w2=lp["w2"][2:5])
    got, _ = glm5_next.ffn(x, held, 1, chip)
    want, _ = ref.ff(x, held, 1, model_of(chip))
    assert float(jnp.abs(got - want).max()) < TOL


def test_the_clamp_bounds_a_swiglu():
    from ray_tpu.models import routed

    gate = jnp.asarray([-30.0, 3.0, 30.0])
    up = jnp.asarray([-30.0, 3.0, 30.0])
    g, u = routed.clamp(gate, up, 10.0)
    assert g.tolist() == [-30.0, 3.0, 10.0] and u.tolist() == [-10.0, 3.0,
                                                              10.0]
    assert routed.clamp(gate, up, 0.0) == (gate, up)


# ------------------------------------------------------- (f) the controls
# A control changes one equation of one kind of layer, and its patch has
# to be traced: it runs on the model cut to its first two layers, which
# keep every kind (a KDA mixer over a dense feed-forward, the sparse
# latent mixer over routed experts, each under the mHC residual, and the
# head over the summed streams), against the reference of the same cut.
SHALLOW = dataclasses.replace(CFG, layer_types=CFG.layer_types[:2],
                              ffn_types=CFG.ffn_types[:2])


def _sound(params, cfg=SHALLOW, model=None, seam=None):
    """The served path (a padded prompt pass, the scatter, eleven decode
    steps in windows of four) against the reference's full forward, on
    `cfg`'s layers; without a `seam`, every program traced anew."""
    params = dict(params, layers=params["layers"][:cfg.n_layers])
    tok = _tokens(32, 41)
    got = served_logits(seam or Seam(glm5_next, cfg), params, cfg,
                        tok[:21], tok[21:], 32, page=PAGE, k=K)
    return _gap(got, ref.logits(params, tok, model or model_of(cfg),
                                last=12))


def _no_tail(scores, pos, n_keys, group, top, _f=dsa.selected_mask):
    mask, chosen = _f(scores, pos, n_keys, group, top)
    own = jnp.arange(n_keys)[None, :] >= ((pos + 1) // group * group)[:, None]
    return mask & ~own, chosen


def _last_groups(q, w, kbar):
    return jnp.broadcast_to(jnp.arange(kbar.shape[-2], dtype=jnp.float32),
                            q.shape[:-2] + (kbar.shape[-2],))


CONTROLS = {
    "no_decay_gate": lambda mp: mp.setattr(
        glm5_next, "kda_gate", lambda h, lp, cfg, _f=glm5_next.kda_gate: (
            jnp.zeros_like(_f(h, lp, cfg)[0]), _f(h, lp, cfg)[1])),
    "sinkhorn_once": lambda mp: mp.setattr(
        glm5_next, "sinkhorn",
        lambda m, iters, _f=glm5_next.sinkhorn: _f(m, 1)),
    "last_rows_selected": lambda mp: mp.setattr(dsa, "index_scores",
                                                _last_groups),
    "tail_not_selected": lambda mp: mp.setattr(dsa, "selected_mask",
                                               _no_tail),
    "index_key_is_the_groups_last": lambda mp: mp.setattr(
        dsa, "pool_index_keys", lambda keys, group: keys[
            ..., group - 1:keys.shape[-2] // group * group:group, :]),
    "streams_not_summed": lambda mp: mp.setattr(
        glm5_next, "final_hidden",
        lambda params, X, cfg: glm5_next.rmsnorm(
            X[..., 0, :], params["final_norm"], cfg.norm_eps)),
}


def test_the_sound_program_is_inside_the_tolerance(params):
    """The whole model (the file's seam), and the cut the controls run
    on."""
    assert _sound(params, CFG, seam=SOUND) < TOL
    assert _sound(params) < TOL


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_every_control_exceeds_the_tolerance(params, monkeypatch, control):
    CONTROLS[control](monkeypatch)
    assert _sound(params) > CONTROL


def test_a_bfloat16_state_exceeds_the_tolerance(params):
    """The lanes' state matrices kept in bfloat16: handed over rounded,
    re-rounded by every decode step."""
    assert _sound(params, dataclasses.replace(
        SHALLOW, state_dtype=jnp.bfloat16), model_of(SHALLOW)) > 10 * TOL


def test_an_unclamped_swiglu_exceeds_the_tolerance(params, monkeypatch):
    """At fan-in scaled weights no SwiGLU input reaches the published
    limit (|gate| ~ 1 against 10), so the control runs at a limit that
    binds, under which the sound program still equals the reference."""
    cfg = dataclasses.replace(SHALLOW, swiglu_limit=0.5)
    model = model_of(cfg)
    assert _sound(params, cfg, model) < TOL
    monkeypatch.setattr(glm5_next.routed, "clamp",
                        lambda gate, up, limit: (gate, up))
    assert _sound(params, cfg, model) > CONTROL


# ------------------------------------------------------------ (g) serving
def test_the_seam_declares_what_the_engine_counts():
    model = serving_model(CFG)
    assert model is glm5_next
    spec = model.serving_spec(CFG)
    assert spec.lane_state_layers == 3 and spec.routed_layers == 3
    assert not spec.caps
    # one sparse layer that selects 16 rows by groups of 4, three KDA
    # layers whose scan walks chunks of 8: through what they count
    # a table of 6 pages of 16 is walked: 40 rows lie in 3 pages
    assert spec.decode_work([40], 1, 16, 6)[0] == {
        "ssm_lane_steps": 3, "dsa_rows_context": 41,
        "dsa_groups_scored": 10, "dsa_rows_selected": 16 + 1,
        "dsa_rows_read": 48}
    # past RATIO selections of table the gather reads S = 128 a step
    assert spec.decode_work([40], 1, 16, 8 * dsa.RATIO + 1)[0][
        "dsa_rows_read"] == 128
    # (a bucket of 32 runs the sparse attention in XLA: no `dsa_prefill`)
    assert spec.prefill_work([9, 17], 32) == (
        {"prefill_scan_chunks": 3 * (2 + 3),
         "prefill_scan_chunks_dense": 3 * 2 * 4,
         "dsa_prefill_blocks": 0, "dsa_prefill_blocks_dense": 0,
         "prefill_walked_tokens": 2 * 32},
        {"scan_chunks": 15, "walked_tokens": 64})
    # its prefill attention is not `flash_fwd`
    assert "prefill_attn_blocks" not in spec.counters
    streamed, multiplied = spec.prefill_params
    assert (streamed, multiplied) == model.prefill_params(CFG)
    assert streamed > multiplied > 0
    big = glm5_next.Glm5NextConfig(
        vocab_size=19456, layer_types=(glm5_next.KDA, glm5_next.DSA)
        + (glm5_next.KDA,) * 3, ffn_types=("dense",) + ("sparse",) * 4,
        experts_held=(0, 36))
    # the ISSUE's arithmetic: 17.37 MB of lane state a row
    assert glm5_next.serving_spec(big).prefill_state_bytes == 4 * (4194304 + 147456) + 512
    assert glm5_next.kda.max_chunk(big.gate_lower_bound) == big.kda_chunk


def test_a_latent_pool_with_lane_state_is_served_without_the_prefix_cache(
        params):
    # (an engine that is refused at construction: nothing compiles)
    with pytest.raises(ValueError, match="prefix_cache=True refused"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  kv_pages=9, prefix_cache=True)
    with pytest.raises(ValueError, match="no multiple of index_pool"):
        glm5_next.init_paged_cache(CFG, 2, 9, 6)


def test_the_server_serves_the_preset_by_name():
    # an engine of its own: the preset as published (bfloat16), found by
    # its name and served through `LLMServer`
    srv = LLMServer("glm5-next-debug", max_batch=2, max_len=64,
                    page_size=PAGE, kv_pages=9, steps_per_sync=K)
    try:
        out = srv.engine.generate([5, 6, 7, 8, 9], max_new_tokens=6)
        assert len(out["tokens"]) == 6
        assert srv.engine.stats()["cache"]["kind"] == "latent"
    finally:
        srv.shutdown()
