"""`models/cohere2_moe.py` on the CPU at the debug preset, in float32:
the served path (a padded prompt pass, the scatter into pages and rings,
paged decode over rings that wrap) against the plain reference's full
forward pass, logits and not tokens; the blocked ring kernel against a
plain softmax; the two things the program holds otherwise than published
(permuted q/k columns, stacked shared experts) against the published
forms; the expert-parallel shares against the uncut layer."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_contract as contract  # rootdir-relative (no pkg)
from family_contract import gap as _gap, tokens as _tokens
from serving_reference import Seam, served_logits

from benchmarks.harness.refs import cohere2_moe as ref
from ray_tpu.models import cohere2_moe, named_config, routed, serving_model
from ray_tpu.ops import flash_attention, live_rows, ssm
from ray_tpu.ops import rope as rope_ops
from ray_tpu.ops import window_attention as swa
from ray_tpu.ops.attention import xla_attention
from ray_tpu.serve.llm import LLMServer

# float32 weights: the served path and the reference then differ by
# summation order alone
CFG = dataclasses.replace(named_config("cohere2-moe-debug"),
                          dtype=jnp.float32)
PAGE, K = 16, 4
TOL = 5e-5
WINDOW, RING = CFG.window, CFG.ring_rows        # 9, 9: the least ring


def model_of(cfg) -> dict:
    return dict(
        hidden_size=cfg.dim, layer_norm_eps=cfg.norm_eps,
        layer_types=list(cfg.layer_types),
        num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, sliding_window=cfg.window,
        intermediate_size=cfg.moe_ffn_dim,
        num_shared_experts=cfg.n_shared,
        num_experts_per_tok=cfg.top_k, norm_topk_prob=True,
        logit_scale=cfg.logit_scale, experts_held=list(cfg.experts_held))


MODEL = model_of(CFG)


# The sound program's seam, compiled once a shape for the file (true
# lengths are arguments), and the reference at ONE length (54 is the
# longest sequence a case reads).
SOUND = Seam(cohere2_moe, CFG)
_ref_logits = contract.one_length(
    lambda p, seq: ref.logits(p, seq, MODEL), 56)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda key: cohere2_moe.init_params(key, CFG))(
        jax.random.PRNGKey(7))


# ------------------------------------- (a) the served path, the reference
PREFILL_LENS = [5, WINDOW, 16, 37]


@pytest.fixture(scope="module")
def prefill_rows(params):
    """ONE prompt pass for the lengths: a row each of one program."""
    return contract.prefill_rows(
        SOUND, params, [_tokens(n, n) for n in PREFILL_LENS], PREFILL_LENS)


@pytest.mark.parametrize("n", PREFILL_LENS)
def test_prefill_logits_equal_the_reference(params, prefill_rows, n):
    """A prompt shorter than, equal to and longer than the window (9):
    every true position of its row against the reference's."""
    toks, h = prefill_rows
    i = PREFILL_LENS.index(n)
    got = cohere2_moe.project_logits(params, h[i, :n])
    assert _gap(got, _ref_logits(params, toks[i, :n])) < TOL


# The prompt pass walked in chunks of 8 and of 5 positions: its own
# program a chunk (traced under `_chunked`), the scatter and the decode
# step the sound seam's.
_WALKED = {chunk: SOUND.retraced("serve_prefill") for chunk in (8, 5)}


def _chunked(mp, chunk):
    """The prompt pass looped over chunks of `chunk` positions (None: as
    the debug sizes run it, one chunk), and the seam whose prompt pass is
    traced under it."""
    if chunk is None:
        return SOUND
    mp.setattr(live_rows, "walk",
               functools.partial(live_rows.walk, chunk=chunk))
    return _WALKED[chunk]


@pytest.mark.parametrize("n,bucket,new,chunk", [
    (21, 32, 11, None), (3, 16, 22, None), (WINDOW - 1, 16, 12, None),
    (WINDOW, 16, 10, None),
    # the looped prompt pass: chunks that divide the bucket, over several
    # chunks and from the window's edge; chunks that divide none, where
    # the one chunk and the last of five are clamped
    (21, 32, 11, 8), (WINDOW, 16, 10, 8), (3, 16, 22, 5), (21, 32, 11, 5)],
    ids=lambda v: str(v))
def test_padded_prefill_then_paged_decode_equals_the_reference(
        params, monkeypatch, n, bucket, new, chunk):
    """The prompt padded to a bucket beside a longer row, scattered into
    the global layer's pages and lane 1's rings, then decode in windows
    of four: from 3 rows the context passes the window and the ring's
    wrap (9) twice over while decoding; from 21 it starts past both,
    every step overwriting the row the window has just left; from 8 the
    first step fills the window, from 9 it wraps.  And the same with the
    prompt pass looped over chunks of 8 positions and of 5 (which divide
    no bucket: the last chunk is clamped)."""
    tok = _tokens(n + new, 3 * n)
    got = served_logits(_chunked(monkeypatch, chunk), params, CFG, tok[:n],
                        tok[n:], bucket, page=PAGE, k=K)
    assert _gap(got, _ref_logits(params, tok, last=new + 1)) < TOL


def test_the_prefill_hands_pages_and_rings_their_rows(params):
    """The global layer's K rows carry no rotary and the rings hold each
    slot's LAST position, against the reference's K and V (whose columns
    are the published ones: the program's are permuted)."""
    n, bucket = 21, 32
    tok = np.zeros((1, bucket), np.int32)
    tok[0, :n] = _tokens(n, 5)
    # (the second row of the seam's two-row program of 32 positions)
    _, ks, vs, state, _ = jax.tree.map(lambda a: a[1:], SOUND.serve_prefill(
        params, jnp.asarray(np.concatenate([tok, tok])),
        jnp.asarray([bucket, n], jnp.int32)))
    x = ref.embed(params, tok[0, :n], MODEL)
    seen = {"window": 0, "global": 0}
    for lid, lp in enumerate(params["layers"]):
        x, _, _, _, info, _ = ref.layer(x, lp, lid, MODEL)
        want_k = np.asarray(info["k"])
        dk = CFG.head_dim
        want_k = want_k.reshape(n, -1, dk // 2, 2).swapaxes(-1, -2).reshape(
            n, -1, dk)                       # as the program's columns lie
        i = CFG.before(lid)
        if CFG.layer_types[lid] == cohere2_moe.GLOBAL:
            seen["global"] += 1
            assert _gap(ks[i][0, :n], want_k) < TOL
            assert _gap(vs[i][0, :n], info["v"]) < TOL
            continue
        seen["window"] += 1
        held = (n - 1) - (n - 1 - np.arange(RING)) % RING
        ring = np.asarray(state["window_k"][i][0]).transpose(1, 0, 2)
        assert _gap(ring, want_k[held]) < TOL
        ring = np.asarray(state["window_v"][i][0]).transpose(1, 0, 2)
        assert _gap(ring, np.asarray(info["v"])[held]) < TOL
    assert seen == {"window": 3, "global": 1}


# ------------------------------------------ (b) the blocked ring kernel
def _ring_case(B, G, rep, R, dk, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, G, rep, dk)),
            jax.random.normal(ks[1], (B, G, R, dk)),
            jax.random.normal(ks[2], (B, G, R, dk)))


@pytest.mark.parametrize("blocks,fill,with_sink", [
    # every (blocks, fill) once; one block and a walked ring each with and
    # without the sink, full and partly filled
    (1, "full", False), (1, "part", True), (2, "full", True),
    (2, "part", False), (8, "full", False), (8, "part", True)],
    ids=lambda v: ("sink" if v else "nope") if isinstance(v, bool)
    else str(v))
def test_the_blocked_ring_kernel_equals_a_plain_softmax(blocks, fill,
                                                        with_sink):
    """`swa_attn` (interpret mode) over a ring of 1, 2 and 8 blocks of 8
    rows, full (every lane past the wrap) and partly filled (lanes that
    hold 1, 5, 11 ... rows: the kernel walks the head of the ring
    alone), with and without the sink; lane 1 holds no request."""
    B, G, rep, dk, block = 4, 2, 4, 16, 8
    R = block * blocks
    q, kr, vr = _ring_case(B, G, rep, R, dk, blocks)
    pos = (jnp.asarray([3 * R + 2, 0, 2 * R, R + 5]) if fill == "full"
           else jnp.asarray([0, 0, 4, min(10, R - 1)]))
    live = jnp.asarray([True, False, True, True])
    lanes, count = ssm.live_lanes(live)
    bias = swa.ring_bias(pos, R, R)
    sink = jax.random.normal(jax.random.PRNGKey(9), (G, rep)) \
        if with_sink else None
    got = swa.kv_ring_attention(q, kr, vr, bias, sink, lanes, count,
                                sm_scale=dk ** -0.5, block=block)
    s = jnp.einsum("bgrd,bgsd->bgrs", q, kr) * dk ** -0.5 \
        + bias[:, None, None, :]
    if with_sink:
        col = jnp.broadcast_to(sink[None, :, :, None], s.shape[:3] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, col], -1), -1)[..., :-1]
    else:
        p = jax.nn.softmax(s, -1)
    want = jnp.where(live[:, None, None, None],
                     jnp.einsum("bgrs,bgsd->bgrd", p, vr), 0.0)
    assert _gap(got, want) < 1e-5
    plan = swa.ring_plan(bias, lanes, count, block)
    steps = int(plan["count"])
    contexts = [int(p) + 1 for p, ok in zip(pos, live) if ok]
    assert steps == sum(swa.blocks_attended(c, R, R, min(block, R))
                        for c in contexts)
    if fill == "full":
        assert steps == 3 * blocks
    elif blocks == 8:
        assert steps == 1 + 1 + 2       # the heads of three rings


@pytest.mark.parametrize("ring,window,block", [(16, 9, 4), (32, 32, 8),
                                               (24, 17, 8)])
def test_the_plan_walks_the_blocks_the_window_touches(ring, window, block):
    """A ring longer than the window: the live slots are an arc of the
    ring, which may cross its end; the device's plan and the host's
    arithmetic name the same blocks at every context."""
    B = 3 * ring
    pos = jnp.arange(B)
    live = jnp.ones((B,), bool)
    lanes, count = ssm.live_lanes(live)
    bias = swa.ring_bias(pos, ring, window)
    plan = swa.ring_plan(bias, lanes, count, block)
    n = int(plan["count"])
    lane, blk, flag = (np.asarray(plan[k])[:n] for k in
                       ("lane", "blk", "flag"))
    held = np.asarray(bias).reshape(B, ring // block, block).max(-1) > -1
    for b in range(B):
        mine = blk[lane == b]
        assert mine.tolist() == np.flatnonzero(held[b]).tolist()
        assert len(mine) == swa.blocks_attended(b + 1, window, ring, block)
        flags = flag[lane == b]
        assert flags[0] & 1 and flags[-1] & 2
        assert not (flags[1:] & 1).any() and not (flags[:-1] & 2).any()


def test_a_ring_of_no_whole_number_of_blocks_is_refused():
    with pytest.raises(ValueError, match="whole number of blocks"):
        swa.ring_blocks(4100)
    assert swa.ring_blocks(4096) == (1024, 4)
    assert swa.ring_blocks(128) == (128, 1)
    assert swa.ring_blocks(9) == (9, 1)


@pytest.mark.parametrize("T,window,lens,blocks", [
    (2048, 1025, [2048, 1100], (128, 128)),     # 9-10 key blocks a query block
    (2048, 1024, [1300, 2048], (256, 256)),     # 5 of them, 2 of them edges
    (1024, 1024, [1024, 515], (128, 128)),      # the band holds every key
])
def test_the_banded_flash_kernel_over_several_key_blocks_equals_a_masked_softmax(
        T, window, lens, blocks):
    """The window layers' prefill call (`flash_fwd` named `swa_band`, no
    sink, 16 query heads over 1 kv head of 128) under a band that spans
    several KEY blocks, ragged lengths with a row ending inside a query
    block past the window, against XLA's masked softmax on every row's
    true positions."""
    ks = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(ks[0], (2, T, 16, 128))
    k = jax.random.normal(ks[1], (2, T, 1, 128))
    v = jax.random.normal(ks[2], (2, T, 1, 128))
    got = flash_attention.flash_attention(
        q, k, v, sm_scale=0.09, block_q=blocks[0], block_k=blocks[1],
        window=window, lengths=jnp.asarray(lens, jnp.int32),
        band_name="swa_band")
    want = xla_attention(q, k, v, sm_scale=0.09, window=window)
    assert flash_attention.band_blocks(T, *blocks) == blocks
    for row, n in enumerate(lens):
        assert _gap(got[row, :n], want[row, :n]) < 1e-5
    short = int(np.argmin(lens))
    assert not np.asarray(
        got[short, -(-lens[short] // blocks[0]) * blocks[0]:]).any()
    # the band's walk: fewer steps than the causal walk, most unmasked
    walked = flash_attention.attn_blocks(T, lens, *blocks, window)
    assert walked < flash_attention.attn_blocks(T, lens, *blocks) \
        or window >= T
    assert flash_attention.edge_blocks(T, lens, *blocks, window) < walked


def test_a_band_of_4096_walks_nine_key_blocks_a_query_block():
    """Command A+'s window layers at 8,192 run at `band_blocks`' 512 x 512
    (faster on the chip than `fit_blocks`' 512 x 1,024 once a step cost
    what it multiplies): a query block past the window walks 9 key blocks
    (5 at 1,024, of 1,024 keys more), one before it the causal count; two
    of a query block's steps are masked edges (the diagonal's block and,
    from the window on, the lower edge's); `band_work` counts the same."""
    assert flash_attention.band_blocks(8192) == (512, 512)
    n = flash_attention.key_blocks(8192, 8192, None, 512, 512, window=4096)
    causal = flash_attention.key_blocks(8192, 8192, None, 512, 512)
    assert n[0, :8].tolist() == causal[0, :8].tolist() == list(range(1, 9))
    assert n[0, 8:].tolist() == [9] * 8
    assert flash_attention.key_blocks(8192, 8192, None, 512, 1024,
                                      window=4096)[0, 8:].tolist() == [5] * 8
    first = flash_attention.first_key_blocks(8192, 512, 512, 4096)
    qi, ki, flag, total = flash_attention._walk(
        n, int(n.sum()), 512, 512, True, np, first=first, window=4096)
    assert total.tolist() == [36 + 8 * 9]
    tenth = ki[qi == 9].tolist(), flag[qi == 9].tolist()
    assert tenth == (list(range(1, 10)), [1 | 8] + [4] * 7 + [2 | 8])
    work, _ = flash_attention.band_work(4096, [8192], 8192)
    assert work == {
        "prefill_attn_blocks": 108, "prefill_attn_blocks_dense": 256,
        "prefill_swa_blocks": 108, "prefill_swa_blocks_dense": 136,
        "prefill_swa_edge_blocks": 16 + 8}
    mean, _ = flash_attention.band_work(4096, [6689], 8192)
    assert (mean["prefill_swa_blocks"], mean["prefill_swa_edge_blocks"]) \
        == (36 + 6 * 9, 14 + 6)


def test_decode_work_counts_the_rows_of_the_blocks_walked():
    """A ring of 4,096 rows in blocks of 1,024 at a window of 4,096:
    a lane on 1,500 rows attends them all and reads two blocks; one past
    the window reads the ring."""
    work, shown = swa.decode_work(3, 4096, [1499, 5000], 1, ring=4096)
    assert work == shown
    assert work["swa_rows_attended"] == 3 * (1500 + 4096)
    assert work["swa_rows_read"] == 3 * (2048 + 4096)
    assert work["swa_rows_context"] == 3 * (1500 + 5001)
    assert "swa_rows_read" not in swa.decode_work(5, 128, [300], 8)[0]


# --------------------- (c) what the program holds otherwise than published
def test_interleaved_rotary_is_the_rotate_half_of_permuted_columns():
    """W_q as published under the rotary that pairs neighbours (2i, 2i +
    1), the reference's literal form = W_q with every head's even columns
    first under the rotary that pairs the halves, permuted: every score
    is the same."""
    heads, dk, d, T = 3, 16, 24, 5
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (T, d))
    wq, wk = (jax.random.normal(k, (d, heads * dk)) for k in ks[1:])
    tables = rope_ops.rope_frequencies(dk, 64, 5.0e4)
    cols = rope_ops.half_from_interleaved(dk, heads)
    q_pub, k_pub = (ref.rope((x @ w).reshape(T, heads, dk), 5.0e4)
                    for w in (wq, wk))
    q_prog, k_prog = (rope_ops.apply_rope(
        (x @ w[:, cols]).reshape(1, T, heads, dk), *tables)[0]
        for w in (wq, wk))
    one = np.asarray(rope_ops.half_from_interleaved(dk))
    assert _gap(q_prog, q_pub[..., one]) < 1e-6
    scores = functools.partial(jnp.einsum, "thd,shd->hts")
    assert _gap(scores(q_prog, k_prog), scores(q_pub, k_pub)) < 1e-5
    # the literal form, written out for the first pair of the first head
    a, b = x @ wq[:, 0], x @ wq[:, 1]
    cos, sin = tables[0][:T, 0], tables[1][:T, 0]
    assert _gap(q_pub[:, 0, 0], a * cos - b * sin) < 1e-5
    assert _gap(q_pub[:, 0, 1], b * cos + a * sin) < 1e-5
    # and the reference puts the program's columns back where they were
    back = ref.published_columns(wq[:, cols], heads, dk)
    assert np.array_equal(np.asarray(back), np.asarray(wq))


def test_the_stacked_shared_experts_are_the_four_averaged(params):
    """ONE SwiGLU over the shared experts side by side, the division in
    its down-projection, against the reference's separate experts, each
    with its own W_2, averaged."""
    lp = params["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(4), (11, CFG.dim))
    got = cohere2_moe.shared_experts(u, lp, CFG)
    with jax.default_matmul_precision("highest"):
        want = ref.shared(u, lp, MODEL)
        each = [ref._swiglu(u @ w1, u @ w3) @ w2 for w1, w3, w2 in
                (ref.shared_of(lp, j, MODEL) for j in range(CFG.n_shared))]
    assert _gap(got, want) < TOL
    assert _gap(sum(each) / CFG.n_shared, want) < 1e-6
    # summed and not averaged is another layer
    assert _gap(sum(each), want) > 0.5


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(params):
    """Eight chips each hold one of the router's eight experts (the
    deployment's eight of 128 on sixteen, at the debug size) and every
    chip computes the shared experts alike: the ranks' routed parts and
    the shared experts counted ONCE are the uncut reference's layer."""
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.dim))
    u = ref.normed(x, lp, MODEL)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.ff(u, lp, MODEL)
    parts, n = cohere2_moe.shared_experts(u, lp, CFG), 0
    for lo in range(8):
        chip = dataclasses.replace(CFG, experts_held=(lo, lo + 1))
        held = dict(lp, w13=lp["w13"][lo:lo + 1], w2=lp["w2"][lo:lo + 1])
        y, c = cohere2_moe.routed_ffn(u, held, chip)
        parts, n = parts + y, n + int(c[2])
    assert float(jnp.abs(parts - want).max()) < TOL
    assert n == 24 * CFG.top_k
    chip = dataclasses.replace(CFG, experts_held=(2, 5))
    held = dict(lp, w13=lp["w13"][2:5], w2=lp["w2"][2:5])
    got, _ = cohere2_moe.ffn(u, held, chip)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.ff(u, held, model_of(chip))
    assert float(jnp.abs(got - want).max()) < TOL


def test_the_norm_is_a_layernorm_and_the_block_is_parallel(params):
    """One layer by hand: the mean is subtracted, attention and the
    feed-forward read the SAME normed rows, and all is added at once."""
    lp = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 12, CFG.dim)) + 0.5
    y, _, _ = cohere2_moe.layer_prefill(params, x, 0, CFG,
                                        jnp.asarray([12], jnp.int32))
    want, u, a, f, _, _ = ref.layer(x[0], lp, 0, MODEL)
    assert _gap(y[0], want) < TOL
    xf = np.asarray(x[0], np.float64)
    byhand = (xf - xf.mean(-1, keepdims=True)) / np.sqrt(
        xf.var(-1) + CFG.norm_eps)[:, None]
    assert _gap(u, byhand) < 1e-5
    assert _gap(cohere2_moe.norm(x, lp["norm"], CFG)[0], byhand) < 1e-5
    # the sequential block is another layer
    with jax.default_matmul_precision("highest"):
        seq = ref.ff(ref.normed(x[0] + a, lp, MODEL), lp, MODEL)[0]
    assert _gap(x[0] + a + seq, want) > 0.05


# ------------------------------------------------ (d) through the engine
PROMPTS = (3, WINDOW, 14, 21, 30)
NEW = 12


@pytest.fixture(scope="module")
def served(params):
    """ONE engine run for the file (`family_contract.served_run`): three
    lanes whose rings were marked, a request of 9 + 9 tokens alone, then
    five prompts at once (under, at and past the window)."""
    return contract.served_run(
        cohere2_moe, CFG, params, lanes=3, kv_pages=19, page=PAGE, k=K,
        prompts=[_tokens(n, 10 + n).tolist() for n in PROMPTS], new=NEW)


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_engine_logits_equal_the_reference_across_lane_reuse(
        params, served, i):
    """A lane that served one request serves another, and neither a
    ring's rows nor a page may leak.  The LOGITS the engine's own
    programs computed at every served position equal the reference's
    full forward."""
    seq = served["prompts"][i] + served["outs"][i]["tokens"]
    want = _ref_logits(params, seq[:-1], last=NEW)
    assert contract.engine_gap(served, i, want) < TOL


def test_the_engine_counts_what_the_layers_read(served):
    st = served["stats"]
    assert st["completed"] == 1 + len(PROMPTS) and st["preemptions"] == 0
    loop = st["loop"]
    n_glob = CFG.count(cohere2_moe.GLOBAL)
    n_win = CFG.count(cohere2_moe.WINDOW)
    steps = loop["lane_steps_live"]
    assert loop["swa_lane_steps"] == steps * n_win
    assert loop["swa_rows_context"] == loop["attn_ctx_rows"] * n_win
    # under 100 %: the window bounded the work
    assert loop["swa_rows_attended"] < loop["swa_rows_context"]
    assert loop["swa_rows_attended"] <= steps * n_win * WINDOW
    # the debug ring is one block: a step reads all nine rows of it
    assert loop["swa_rows_read"] == steps * n_win * RING
    assert loop["swa_rows_read"] >= loop["swa_rows_attended"]
    assert 0 < loop["prefill_swa_blocks"] <= loop["prefill_swa_blocks_dense"]
    assert 0 < loop["prefill_swa_edge_blocks"] <= loop["prefill_swa_blocks"]
    assert loop["prefill_attn_blocks"] > 0
    assert loop["prefill_walked_tokens"] == loop["prefill_padded_tokens"] > 0
    # every layer is routed
    assert loop["moe_layer_steps"] == loop["decode_steps"] * CFG.n_layers
    assert loop["moe_assignments"] > 0
    cache = st["cache"]
    # window layers hold no page: a K and a V leaf a GLOBAL layer only
    assert cache["kind"] == "kv" and set(cache["by_leaf"]) == {"k", "v"}
    assert cache["layers"] == n_glob
    assert cache["row_bytes"] == 4 * CFG.n_kv_heads * 2 * CFG.head_dim
    lane = st["lane_state"]
    assert lane["layers"] == n_win
    ring = n_win * 3 * RING * CFG.n_kv_heads * CFG.head_dim * 4
    assert lane["by_kind"] == {"window_k": ring, "window_v": ring}
    assert lane["bytes"] == 2 * ring
    assert lane["prefix_cache"] == "off: lane state"


def test_the_rings_are_written_in_place(served):
    """One request of 9 + 9 tokens in an engine of three lanes: the idle
    lanes' rings are bit-unchanged, the live lane's were written by the
    scatter and then a slot a step; and the decode program hands every
    ring back in the buffer it came in (donated and aliased: no second
    ring)."""
    assert len(served["first"]["tokens"]) == 9
    for name in ("window_k", "window_v"):
        for layer in range(CFG.count(cohere2_moe.WINDOW)):
            assert len(contract.lanes_written(
                served, lambda s: s[name][layer])) == 1
    text = served["lowered"].as_text()
    ring = f"tensor<3x{CFG.n_kv_heads}x{RING}x{CFG.head_dim}xf32>"
    # each ring, a K and a V a window layer, is an argument that aliases
    # an output
    assert text.count(ring + " {tf.aliasing_output") \
        == 2 * CFG.count(cohere2_moe.WINDOW)


def test_the_seam_declares_what_the_engine_counts():
    spec = serving_model(CFG).serving_spec(CFG)
    assert spec.caps == frozenset()
    assert spec.lane_state_layers == 3 and spec.routed_layers == 4
    assert spec.prefill_state_bytes == 3 * RING * CFG.n_kv_heads * 2 \
        * CFG.head_dim * 4
    assert {"swa_rows_read", "swa_rows_attended", "swa_rows_context",
            "swa_lane_steps", "prefill_walked_tokens", "moe_experts_hit",
            "prefill_swa_blocks", "prefill_swa_edge_blocks"} <= set(
                spec.counters)
    streamed, multiplied = spec.prefill_params
    attn = 2 * CFG.dim * CFG.head_dim * (CFG.n_heads + CFG.n_kv_heads)
    one = 3 * CFG.dim * CFG.moe_ffn_dim
    rest = 4 * (attn + CFG.n_shared * one + CFG.dim * CFG.n_experts)
    assert streamed == rest + 4 * 8 * one
    assert multiplied == rest + 4 * CFG.top_k * one
    full = named_config("command-a-plus")
    assert full.n_layers == 32 and full.count(cohere2_moe.GLOBAL) == 8
    assert full.layer_types[:4] == (cohere2_moe.WINDOW,) * 3 + (
        cohere2_moe.GLOBAL,)
    assert cohere2_moe.attn_params(full) == 142_606_336
    assert routed.COUNTS == 5


def test_the_server_serves_the_preset_by_name():
    # an engine of its own: the preset as published (bfloat16), found by
    # its name and served through `LLMServer`
    srv = LLMServer(model="cohere2-moe-debug", max_batch=2, max_len=64,
                    page_size=PAGE, kv_pages=9, steps_per_sync=K)
    try:
        out = srv.engine.generate(_tokens(12, 3).tolist(), max_new_tokens=6)
        assert len(out["tokens"]) == 6
        st = srv.engine.stats()
        assert st["cache"]["kind"] == "kv"
        assert st["lane_state"]["layers"] == 3
        assert st["loop"]["swa_rows_read"] > 0
    finally:
        srv.shutdown()
