"""models/mimo_v2.py (window grouped-query layers with a learned sink kept
as a K and a V ring a lane beside global grouped-query layers of another
kv-head count in pages, keys wider than values, rotary on a part of each
head, routed experts without a shared one) against the plain float32
reference the benchmark holds it to (`benchmarks/harness/refs/mimo_v2.py`,
which imports nothing of the program): the prompt pass, paged + ring
decode across the ring's wrap, the ENGINE's own logits with lanes reused
(one engine run shared by the file's cases: `family_contract`), the banded `flash_fwd` with
the sink, the ring kernel, the rings written in place, the expert shares,
the counters and the controls a sound comparison must fail."""
from __future__ import annotations

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_contract as contract  # rootdir-relative (no pkg)
from family_contract import gap as _gap, tokens as _tokens
from serving_reference import Seam, served_logits

from benchmarks.harness.refs import mimo_v2 as ref
from ray_tpu.models import mimo_v2, named_config, serving_model
from ray_tpu.ops import (flash_attention, live_rows, ssm,
                         window_attention as swa)
from ray_tpu.ops.attention import xla_attention
from ray_tpu.serve.llm import LLMEngine, LLMServer

# float32 weights: the served path and the reference then differ by
# summation order alone
CFG = dataclasses.replace(named_config("mimo-v2-debug"), dtype=jnp.float32)
PAGE, K = 16, 4
TOL = 5e-5
CONTROL = 2e-3
WINDOW, RING = CFG.window, CFG.ring_rows        # 9, 9: the least ring


def model_of(cfg) -> dict:
    return dict(
        hidden_size=cfg.dim, layernorm_epsilon=cfg.norm_eps,
        hybrid_layer_pattern=[0 if k == mimo_v2.GLOBAL else 1
                              for k in cfg.layer_types],
        moe_layer_freq=list(cfg.moe_layers),
        num_attention_heads=cfg.n_heads,
        swa_num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        swa_num_key_value_heads=cfg.swa_n_kv_heads,
        head_dim=cfg.qk_head_dim, swa_head_dim=cfg.qk_head_dim,
        v_head_dim=cfg.v_head_dim, swa_v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, swa_rope_theta=cfg.swa_rope_theta,
        # 8 of 24 columns turn: int(0.334 x 24) = 8, as int(0.334 x 192) = 64
        partial_rotary_factor=0.334, sliding_window=cfg.window,
        attention_value_scale=cfg.value_scale,
        num_experts_per_tok=cfg.top_k, norm_topk_prob=True,
        routed_scaling_factor=None, experts_held=list(cfg.experts_held))


MODEL = model_of(CFG)


# The sound program's seam, compiled once a shape for the file (true
# lengths are arguments), and the reference at ONE length (54 is the
# longest sequence a case reads: 40 prompt tokens and 14 served).
SOUND = Seam(mimo_v2, CFG)
_ref_logits = contract.one_length(
    lambda p, seq: ref.logits(p, seq, MODEL), 56)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda key: mimo_v2.init_params(key, CFG))(
        jax.random.PRNGKey(7))


def test_the_rotary_part_is_the_published_ones():
    assert ref.rope_dims({"partial_rotary_factor": 0.334}, 192) == 64
    assert ref.rope_dims(MODEL, CFG.qk_head_dim) == CFG.rope_dim == 8
    assert mimo_v2.MimoV2Config().rope_dim == 64


# --------------------------------------------------- (a) the prompt pass
PREFILL_LENS = [5, WINDOW, 16, 37]


@pytest.fixture(scope="module")
def prefill_rows(params):
    """ONE prompt pass for the lengths: a row each of one program."""
    return contract.prefill_rows(
        SOUND, params, [_tokens(n, n) for n in PREFILL_LENS], PREFILL_LENS)


@pytest.mark.parametrize("n", PREFILL_LENS)
def test_prefill_logits_equal_the_reference(params, prefill_rows, n):
    """5: under the window; 9: the window full for the first time; 37:
    the band has moved on four times over.  Every true position of the
    row against the reference's."""
    toks, h = prefill_rows
    i = PREFILL_LENS.index(n)
    got = mimo_v2.project_logits(params, h[i, :n])
    assert _gap(got, _ref_logits(params, toks[i, :n])) < TOL


# The prompt pass walked in chunks of some width: its own program a width
# (traced under `_chunked`), the scatter and the decode step the sound
# seam's.
_WALKED = collections.defaultdict(lambda: SOUND.retraced("serve_prefill"))


def _chunked(mp, chunk):
    """The prompt pass's position-wise halves walked in chunks of `chunk`
    positions (None: the module's own, one chunk at these sizes), and the
    seam whose prompt pass is traced under it."""
    if not chunk:
        return SOUND
    mp.setattr(live_rows, "walk",
               functools.partial(live_rows.walk, chunk=chunk))
    return _WALKED[chunk]


@pytest.mark.parametrize("chunk", [None, 8, 5], ids=lambda c: f"chunk_{c}")
@pytest.mark.parametrize("n,bucket,new", [(21, 32, 11), (3, 16, 22),
                                          (WINDOW - 1, 16, 12)])
def test_padded_prefill_then_paged_decode_equals_the_reference(
        params, monkeypatch, n, bucket, new, chunk):
    """The prompt padded to a bucket beside a longer row, scattered into
    the K and V pages and lane 1's rings, then decode in windows of four:
    from 3 rows the context passes the window and the ring's wrap (9)
    twice over while decoding; from 21 it starts past both, every step
    overwriting the row the window has just left; from 8 the first step
    fills the window.  And the same with the prompt pass looped over
    chunks of 8 positions (a chunk short of the bucket's end is zeros)
    and of 5 (which divide no bucket: the last chunk is clamped)."""
    tok = _tokens(n + new, 3 * n)
    got = served_logits(_chunked(monkeypatch, chunk), params, CFG, tok[:n],
                        tok[n:], bucket, page=PAGE, k=K)
    assert _gap(got, _ref_logits(params, tok, last=new + 1)) < TOL


def test_the_prefill_hands_pages_and_rings_their_rows(params):
    """A row of true length 21 in a bucket of 32: a global layer's K and
    V rows are the reference's (4 and 2 kv heads: the two kinds differ),
    and slot i of a window layer's rings holds the last position below 21
    that is i mod 9 (12 ... 20)."""
    tok = _tokens(32, 5)
    lens = jnp.asarray([32, 21], jnp.int32)
    toks = jnp.asarray(np.stack([tok, tok]))
    _, ks, vs, state, _ = SOUND.serve_prefill(params, toks, lens)
    dk = CFG.qk_head_dim        # 24, stored a lane tile wide (128)
    assert ks[0].shape[2:] == (CFG.n_kv_heads, CFG.k_store)
    assert vs[0].shape[2:] == (CFG.n_kv_heads, CFG.v_head_dim)
    assert state["window_k"][0].shape == (2, CFG.swa_n_kv_heads, RING,
                                          CFG.k_store)
    assert (CFG.k_store, mimo_v2.MimoV2Config().k_store) == (128, 256)
    x = ref.embed(params, tok[:21], MODEL)
    seen = {mimo_v2.GLOBAL: 0, mimo_v2.WINDOW: 0}
    for lid, lp in enumerate(params["layers"]):
        kind = CFG.layer_types[lid]
        x, _, info, _ = ref.layer(x, lp, lid, MODEL)
        i = seen[kind]
        seen[kind] += 1
        if kind == mimo_v2.GLOBAL:
            assert _gap(ks[i][1, :21, :, :dk], info["k"]) < TOL
            assert not np.asarray(ks[i][..., dk:]).any()
            assert _gap(vs[i][1, :21], info["v"]) < TOL
            continue
        for name, want in (("window_k", info["k"]), ("window_v", info["v"])):
            ring = np.asarray(state[name][i][1])           # [G, R, w]
            w = want.shape[-1]
            assert not ring[..., w:].any()
            for slot in range(RING):
                p = 20 - (20 - slot) % RING
                assert 12 <= p <= 20 and p % RING == slot
                assert _gap(ring[:, slot, :w], np.asarray(want)[p]) < TOL


@pytest.mark.parametrize("lens,bucket,chunk", [
    ([21, 32], 32, 8), ([9, 3], 32, 8), ([17], 48, 16), ([30, 11], 37, 8),
    ([1, 1], 16, 8)], ids=lambda v: "_".join(map(str, v))
    if isinstance(v, list) else str(v))
def test_the_looped_prefill_is_the_straight_line_prefill_on_the_true_rows(
        params, lens, bucket, chunk):
    """`prefill` with its position-wise halves walked in chunks against
    `prefill` straight-line: the K and V rows of the true positions, the
    rings, the hidden row at the last true position and the routed
    counts; past the walked chunks the rows handed to the pool are zeros
    (the scatter reads by the true lengths)."""
    tok = jnp.asarray(np.stack([_tokens(bucket, 7 + i)
                                for i in range(len(lens))]))
    n = jnp.asarray(lens, jnp.int32)
    want = SOUND.serve_prefill(params, tok, n)
    with pytest.MonkeyPatch.context() as mp:
        walked = _chunked(mp, chunk).serve_prefill
        low = walked.lower(params, tok, n)
        got = walked(params, tok, n)
    # two loops a layer and the dense layer's third
    assert low.as_text().count("stablehlo.while") >= 2 * CFG.n_layers + 1
    done = min(bucket, -(-max(lens) // chunk) * chunk)
    for row, m in enumerate(lens):
        assert _gap(got[0][row, m - 1], want[0][row, m - 1]) < TOL
        for g, w in zip(got[1] + got[2], want[1] + want[2]):
            assert _gap(g[row, :m], w[row, :m]) < TOL
            assert not np.asarray(g[row, done:]).any()
    for name in ("window_k", "window_v"):
        for g, w in zip(got[3][name], want[3][name]):
            assert _gap(g, w) < TOL
    np.testing.assert_array_equal(np.asarray(got[4]), np.asarray(want[4]))


@pytest.mark.parametrize("block", ["global", "window", "ffn"])
def test_a_judged_block_loops_at_a_length_the_chunk_does_not_divide(
        params, block):
    """The blocks the benchmark's judge calls by name, at a true length
    no chunk divides (it jits them at 1,400 and such): the last chunk
    starts at T - chunk, and the block is the straight-line one."""
    T, chunk = 29, 8
    lid = {"global": 0, "window": 1, "ffn": 0}[block]
    lp = params["layers"][lid]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, CFG.dim))
    n = jnp.asarray([T], jnp.int32)
    fns = {"global": lambda: mimo_v2.global_prefill(x, lp, CFG, n),
           "window": lambda: mimo_v2.window_prefill(x, lp, CFG, n),
           "ffn": lambda: mimo_v2.ffn(x, lp, lid, CFG,
                                      jnp.arange(T)[None] < T)[0]}
    want = jax.jit(lambda: fns[block]())()
    with pytest.MonkeyPatch.context() as mp:
        _chunked(mp, chunk)
        looped = jax.jit(lambda: fns[block]())      # traced under the patch
        text = looped.lower().as_text()
        got = looped()
    assert "stablehlo.while" in text
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _gap(g, w) < TOL


# ------------------------------------------------------ (b) the kernels
def _ring_case(B=3, G=2, rep=4, R=16, dk=24, dv=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, G, rep, dk)),
            jax.random.normal(ks[1], (B, G, R, dk)),
            jax.random.normal(ks[2], (B, G, R, dv)),
            jax.random.normal(ks[3], (G, rep)))


@pytest.mark.parametrize("ring,window", [(16, 9), (9, 9), (128, 128)])
def test_the_ring_kernel_equals_a_dense_masked_softmax_with_the_sink(
        ring, window):
    """Grouped queries over a lane's K and V rings (keys wider than
    values), the sink a column of the denominator: against a dense
    softmax over [live slots | sink] whose sink column carries no value;
    positions under the window, at the wrap and far past it; the lane
    outside the work list reads 0."""
    q, kr, vr, sink = _ring_case(R=ring)
    pos = jnp.asarray([3, ring, 5 * ring + 2])
    live = jnp.asarray([True, False, True])
    lanes, count = ssm.live_lanes(live)
    bias = swa.ring_bias(pos, ring, window)
    got = swa.kv_ring_attention(q, kr, vr, bias, sink, lanes, count,
                                sm_scale=0.2)
    s = jnp.einsum("bgrd,bgkd->bgrk", q, kr) * 0.2 + bias[:, None, None, :]
    col = jnp.broadcast_to(sink[None, :, :, None], s.shape[:3] + (1,))
    p = jax.nn.softmax(jnp.concatenate([s, col], -1), axis=-1)[..., :-1]
    want = jnp.einsum("bgrk,bgkv->bgrv", p, vr)
    assert got.shape == (3, 2, 4, 16)
    assert not np.asarray(got[1]).any()
    for b in (0, 2):
        assert _gap(got[b], want[b]) < 1e-5
    # the sink takes its share: without it the weights sum to 1
    assert float(jnp.abs(p.sum(-1) - 1).max()) > 0.1
    n_live = np.asarray(bias == 0.0).sum(-1)
    assert n_live.tolist() == [4, min(ring + 1, window), window]


def test_a_ring_row_is_written_where_the_window_left_one():
    """The step's K row a head at slot pos mod R of the lanes that hold a
    request; an idle lane's ring is not touched."""
    _, kr, _, _ = _ring_case(R=9)
    new = jnp.full((3, 2, 24), 7.0)
    pos = jnp.asarray([4, 13, 30])
    out = np.asarray(swa.kv_ring_write(kr, new, pos,
                                       jnp.asarray([True, False, True])))
    before = np.asarray(kr)
    assert (out[1] == before[1]).all()
    for b, slot in ((0, 4), (2, 3)):
        assert (out[b, :, slot] == 7.0).all()
        others = np.arange(9) != slot
        assert (out[b][:, others] == before[b][:, others]).all()
    rows = jnp.arange(2 * 20 * 2 * 3, dtype=jnp.float32).reshape(2, 20, 2, 3)
    ring = np.asarray(swa.kv_ring_from_rows(rows, jnp.asarray([20, 5]), 9))
    assert ring.shape == (2, 2, 9, 3)
    for slot in range(9):
        p = 19 - (19 - slot) % 9
        assert (ring[0, :, slot] == np.asarray(rows[0, p])).all()
    assert (ring[1, :, :5] == np.asarray(rows[1, :5]).transpose(1, 0, 2)
            ).all() and not ring[1, :, 5:].any()


@pytest.mark.parametrize("T,window,lens,blocks", [
    (512, 128, None, None),
    (384, 128, [384, 131], None),
    (256, 65, [77, 256], None),
    # a band of several KEY blocks (9-10 of 128, 5 of 256 a query block),
    # a row that ends inside a query block past the window
    (2048, 1025, [2048, 1100], (128, 128)),
    (2048, 1024, [1300, 2048], (256, 256)),
])
def test_the_banded_flash_kernel_with_a_sink_equals_a_masked_softmax(
        T, window, lens, blocks):
    """`flash_fwd` under a band of 128 with a learned sink a head, 8
    query heads over 2 kv heads at keys wider than values (192 / 128),
    against XLA's masked softmax with the sink as an extra column; with
    lengths, on every row's true positions."""
    b, H, G = (1 if lens is None else len(lens)), 8, 2
    ks = jax.random.split(jax.random.PRNGKey(T), 4)
    q = jax.random.normal(ks[0], (b, T, H, 192))
    k = jax.random.normal(ks[1], (b, T, G, 192))
    v = jax.random.normal(ks[2], (b, T, G, 128))
    sink = jax.random.normal(ks[3], (H,))
    given = {} if blocks is None else {"block_q": blocks[0],
                                       "block_k": blocks[1]}
    got = flash_attention.flash_attention(
        q, k, v, sm_scale=0.07, window=window, sink=sink, **given,
        lengths=None if lens is None else jnp.asarray(lens, jnp.int32))
    want = xla_attention(q, k, v, sm_scale=0.07, window=window, sink=sink)
    bare = xla_attention(q, k, v, sm_scale=0.07, window=window)
    for row, n in enumerate(lens or [T]):
        assert _gap(got[row, :n], want[row, :n]) < 1e-5
        assert _gap(bare[row, :n], want[row, :n]) > 1e-2
    bq, _ = flash_attention.band_blocks(T, *(blocks or ()))
    if lens is not None:        # query blocks wholly past a length: zeros
        short = int(np.argmin(lens))
        assert not np.asarray(
            got[short, -(-lens[short] // bq) * bq:]).any()


def test_a_band_of_128_walks_two_key_blocks_a_query_block():
    """The band's width does not pick the blocks (the chip was fastest at
    512 x 512 under a band of 128, of 513 and of 4,096: `band_blocks`):
    a 1 x 8192 call under a band of 128 walks 2 key blocks of 512 a query
    block, the first 1: 31 of the causal walk's 136, every one of them a
    masked edge step."""
    assert flash_attention.band_blocks(8192) == (512, 512)
    work, _ = flash_attention.band_work(128, [8192], 8192)
    assert (work["prefill_swa_blocks"], work["prefill_swa_blocks_dense"]) \
        == (31, 136)
    assert work["prefill_swa_edge_blocks"] == 31
    short, _ = flash_attention.band_work(128, [4097], 8192)
    assert short["prefill_swa_blocks"] == 1 + 8 * 2


# ------------------------------------------------ (c) through the engine
PROMPTS = (40, 3, WINDOW - 1, 1, 17)
NEW = 14


@pytest.fixture(scope="module")
def served(params):
    """ONE engine run for the file (`family_contract.served_run`): three
    lanes whose rings were marked, a request of 9 + 9 tokens alone, then
    five prompts at once (under, at and past the window)."""
    return contract.served_run(
        mimo_v2, CFG, params, lanes=3, kv_pages=19, page=PAGE, k=K,
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
    n_glob, n_win = CFG.count(mimo_v2.GLOBAL), CFG.count(mimo_v2.WINDOW)
    steps = loop["lane_steps_live"]
    assert loop["swa_lane_steps"] == steps * n_win
    # the engine's own count of the rows a lane's context holds a step (a
    # lane and not a layer: the paged kernel's two layers read it twice)
    assert loop["swa_rows_context"] == loop["attn_ctx_rows"] * n_win
    # under 100 %: the window bounded the work
    assert loop["swa_rows_attended"] < loop["swa_rows_context"]
    assert loop["swa_rows_attended"] <= steps * n_win * WINDOW
    assert 0 < loop["prefill_swa_blocks"] <= loop["prefill_swa_blocks_dense"]
    assert 0 < loop["prefill_swa_edge_blocks"] <= loop["prefill_swa_blocks"]
    assert loop["prefill_attn_blocks"] > 0
    # every bucket here is one chunk: the halves walk what the bucket pads
    assert loop["prefill_walked_tokens"] == loop["prefill_padded_tokens"] > 0
    assert loop["moe_layer_steps"] > 0 and loop["moe_assignments"] > 0
    cache = st["cache"]
    # window layers hold no page: a K and a V leaf a GLOBAL layer only
    assert cache["kind"] == "kv" and set(cache["by_leaf"]) == {"k", "v"}
    assert cache["layers"] == n_glob
    assert cache["row_bytes"] == 4 * CFG.n_kv_heads * (
        CFG.k_store + CFG.v_head_dim)
    lane = st["lane_state"]
    assert lane["layers"] == n_win
    assert set(lane["by_kind"]) == {"window_k", "window_v"}
    assert lane["by_kind"]["window_k"] == n_win * 3 * RING \
        * CFG.swa_n_kv_heads * CFG.k_store * 4
    assert lane["by_kind"]["window_v"] == n_win * 3 * RING \
        * CFG.swa_n_kv_heads * CFG.v_head_dim * 4
    assert lane["prefix_cache"] == "off: lane state"


def test_the_rings_are_written_in_place(served):
    """One request of 9 + 9 tokens in an engine of three lanes: the idle
    lanes' rings are bit-unchanged, the live lane's were written by the
    scatter and then a slot a step; and the decode program hands every
    ring back in the buffer it came in (donated and aliased: no second
    ring)."""
    assert len(served["first"]["tokens"]) == 9
    for name in ("window_k", "window_v"):
        for layer in range(CFG.count(mimo_v2.WINDOW)):
            assert len(contract.lanes_written(
                served, lambda s: s[name][layer])) == 1
    text = served["lowered"].as_text()
    n_win = CFG.count(mimo_v2.WINDOW)
    for w in (CFG.k_store, CFG.v_head_dim):
        ring = f"tensor<3x{CFG.swa_n_kv_heads}x{RING}x{w}xf32>"
        # each ring is an argument that aliases an output
        assert text.count(ring + " {tf.aliasing_output") == n_win


# ------------------------------------------------- (d) ranges of experts
def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer(params):
    """Eight chips each hold one of the router's eight experts (the
    deployment's sixteen of 256, at the debug size); there is no shared
    expert, so nothing is counted twice: their parts are the uncut layer
    of the reference."""
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.dim))
    want, _ = ref.ff(x, lp, 1, MODEL)
    h2 = mimo_v2.rmsnorm(x, lp["norm2"], CFG.norm_eps)
    parts, n = 0.0, 0
    for lo in range(8):
        chip = dataclasses.replace(CFG, experts_held=(lo, lo + 1))
        held = dict(lp, w13=lp["w13"][lo:lo + 1], w2=lp["w2"][lo:lo + 1])
        y, c = mimo_v2.routed_ffn(h2, held, chip)
        parts, n = parts + y, n + int(c[2])
    assert float(jnp.abs(parts - want).max()) < TOL
    assert n == 24 * CFG.top_k
    chip = dataclasses.replace(CFG, experts_held=(2, 5))
    held = dict(lp, w13=lp["w13"][2:5], w2=lp["w2"][2:5])
    got, _ = mimo_v2.ffn(x, held, 1, chip)
    want, _ = ref.ff(x, held, 1, model_of(chip))
    assert float(jnp.abs(got - want).max()) < TOL


# ------------------------------------------------------- (e) the controls
# A control changes one equation of one kind of layer, and its patch has
# to be traced: it runs on the model cut to its first two layers, which
# keep both kinds (a global layer over the dense feed-forward, a window
# layer with its sink and rings over routed experts), against the
# reference of the same cut.
SHALLOW = dataclasses.replace(CFG, layer_types=CFG.layer_types[:2],
                              moe_layers=CFG.moe_layers[:2])


def _sound(params, cfg=SHALLOW, seam=None):
    """The served path (a padded prompt pass, the scatter, eleven decode
    steps in windows of four) against the reference's full forward, on
    `cfg`'s layers; without a `seam`, every program traced anew.  The
    reference is the PUBLISHED model's at that depth, whatever equation
    `cfg` changed."""
    params = dict(params, layers=params["layers"][:cfg.n_layers])
    tok = _tokens(32, 41)
    got = served_logits(seam or Seam(mimo_v2, cfg), params, cfg, tok[:21],
                        tok[21:], 32, page=PAGE, k=K)
    if seam is SOUND:
        return _gap(got, _ref_logits(params, tok, last=12))
    return _gap(got, ref.logits(params, tok, model_of(SHALLOW), last=12))


def _global_grouping(q, k, v, _f=mimo_v2.attention, **kw):
    """A window layer's queries grouped as a global layer's: twice as
    many query heads a kv head, over the first half of the kv heads."""
    if kw.get("sink") is not None:
        k, v = (a[:, :, :CFG.n_kv_heads] for a in (k, v))
    return _f(q, k, v, **kw)


CONTROLS = {
    "sink_left_out": lambda mp: mp.setattr(
        swa, "kv_ring_attention",
        lambda q, k, v, bias, sink, *a, _f=swa.kv_ring_attention, **kw: _f(
            q, k, v, bias, jnp.full_like(sink, -1e30), *a, **kw)),
    "value_scale_left_out": lambda mp: mp.setattr(
        mimo_v2, "scaled_out",
        lambda o, lp, cfg, _f=mimo_v2.scaled_out: _f(
            o, lp, dataclasses.replace(cfg, value_scale=1.0))),
    "global_head_grouping": lambda mp: mp.setattr(
        mimo_v2, "attention", _global_grouping),
    "fp8_ring": lambda mp: mp.setattr(
        swa, "kv_ring_from_rows",
        lambda rows, *a, _f=swa.kv_ring_from_rows: _f(
            rows.astype(jnp.float8_e4m3fn).astype(rows.dtype), *a)),
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


@pytest.mark.parametrize("change", [dict(window=WINDOW - 1),
                                    dict(window=WINDOW + 1, ring_rows=16),
                                    dict(rope_dim=12)],
                         ids=["window_8", "window_10", "rotary_12_of_24"])
def test_a_changed_equation_exceeds_the_tolerance(params, change):
    assert _sound(params, dataclasses.replace(SHALLOW, **change)) > CONTROL


def test_the_references_window_edge_is_the_published_one(params):
    """The reference given another window differs from itself: the
    judge's edge reading has something to read."""
    x = ref.embed(params, _tokens(30, 2), MODEL)
    lid = CFG.layer_types.index(mimo_v2.WINDOW)
    lp = params["layers"][lid]
    y, info = ref.mixer(x, lp, lid, MODEL)
    assert np.asarray(info["mask"]).sum(-1).max() == WINDOW
    for w in (WINDOW - 1, WINDOW + 1):
        other, _ = ref.mixer(x, lp, lid, MODEL, window=w)
        assert _gap(other, y) > CONTROL
        assert _gap(other[:w - 1], y[:w - 1]) < 1e-6


# ------------------------------------------------------------ (f) serving
def test_the_seam_declares_what_the_engine_counts():
    model = serving_model(CFG)
    assert model is mimo_v2
    spec = model.serving_spec(CFG)
    assert spec.lane_state_layers == 3 and spec.routed_layers == 4
    assert not spec.caps
    # three window layers of 9 rows; the global layers' rows are the
    # engine's own counter
    assert spec.decode_work([40], 1, 16, 6)[0] == {
        "swa_rows_context": 3 * 41, "swa_rows_attended": 3 * 9,
        "swa_lane_steps": 3}
    assert spec.decode_work([3, 40], 2, 16, 6)[0]["swa_rows_attended"] \
        == 3 * (4 + 5 + 9 + 9)
    work, shown = spec.prefill_work([9, 17], 32)
    assert shown == {"walked_tokens": 64} and set(work) == {
        "prefill_attn_blocks", "prefill_attn_blocks_dense",
        "prefill_swa_blocks", "prefill_swa_blocks_dense",
        "prefill_swa_edge_blocks",
        "prefill_walked_tokens"}
    assert set(work) <= set(spec.counters)
    streamed, multiplied = spec.prefill_params
    assert (streamed, multiplied) == model.prefill_params(CFG)
    assert streamed > multiplied > 0
    big = dataclasses.replace(
        mimo_v2.MimoV2Config(), vocab_size=19072,
        layer_types=mimo_v2.MimoV2Config().layer_types[:7],
        moe_layers=mimo_v2.MimoV2Config().moe_layers[:7],
        experts_held=(0, 16))
    assert big.layer_types == (mimo_v2.GLOBAL,) + (mimo_v2.WINDOW,) * 4 \
        + (mimo_v2.GLOBAL, mimo_v2.WINDOW)
    # a lane's rings: 5 layers x 128 rows x 8 kv heads x (192 stored 256
    # wide + 128) bf16 (the ISSUE counted 5,120 B a row at 192)
    assert mimo_v2.serving_spec(big).prefill_state_bytes == 5 * 128 * 6144
    assert (mimo_v2.attn_params(big, mimo_v2.GLOBAL),
            mimo_v2.attn_params(big, mimo_v2.WINDOW)) == (89_128_960,
                                                          94_371_840)
    # the 1 x 8192 program: the band's 31 of its causal 136 at 512 x 512,
    # the global layers' causal 72 of 128 at 512 x 1024
    work, _ = mimo_v2.serving_spec(big).prefill_work([8192], 8192)
    assert (work["prefill_swa_blocks"], work["prefill_swa_blocks_dense"],
            work["prefill_attn_blocks"],
            work["prefill_attn_blocks_dense"]) == (31, 136, 72, 128)


@pytest.mark.parametrize("lens,bucket,want", [
    ([6216], 8192, 7 * 1024), ([4097], 8192, 5 * 1024),
    ([8192], 8192, 8192), ([5000, 7169], 8192, 2 * 8192),
    ([1500], 2048, 2048), ([900], 1024, 1024), ([300, 40, 7, 7], 512,
                                                4 * 512)])
def test_the_prefill_work_counts_the_positions_the_halves_walk(
        lens, bucket, want):
    """`prefill_walked_tokens`: rows x the chunks of `live_rows.CHUNK`
    under the longest true length for a bucket over a chunk, rows x
    bucket at or under one."""
    C = live_rows.CHUNK
    work, shown = mimo_v2.serving_spec(CFG).prefill_work(
        np.asarray(lens, np.int32), bucket)
    assert work["prefill_walked_tokens"] == want == len(lens) * (
        -(-max(lens) // C) * C if bucket > C else bucket)
    assert shown == {"walked_tokens": want}


def test_lane_state_is_served_without_the_prefix_cache(params):
    # (an engine that is refused at construction: nothing compiles)
    with pytest.raises(ValueError, match="prefix_cache=True refused"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  kv_pages=9, prefix_cache=True)
    with pytest.raises(ValueError, match="under the window"):
        mimo_v2.init_paged_cache(
            dataclasses.replace(CFG, ring_rows=8), 2, 9, PAGE)


def test_the_server_serves_the_preset_by_name():
    # an engine of its own: the preset as published (bfloat16), found by
    # its name and served through `LLMServer`
    srv = LLMServer("mimo-v2-debug", max_batch=2, max_len=64,
                    page_size=PAGE, kv_pages=9, steps_per_sync=K)
    try:
        out = srv.engine.generate([5, 6, 7, 8, 9], max_new_tokens=6)
        assert len(out["tokens"]) == 6
        st = srv.engine.stats()
        assert st["cache"]["kind"] == "kv"
        assert set(st["lane_state"]["by_kind"]) == {"window_k", "window_v"}
    finally:
        srv.shutdown()


def test_the_preset_is_served_through_serve_run():
    """`serve.run(LLMServer)` in the node's device worker, as the
    benchmark's replica is started: the normal path end to end (an
    engine of its own, in another process)."""
    import ray_tpu
    from ray_tpu import serve

    # `ray_shared` leaves its cluster up for the next test of the process.
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(resources={"CPU": 4, "TPU": 1})
    try:
        app = serve.deployment(serve.LLMServer).options(
            name="llm", ray_actor_options={"num_tpus": 1},
        ).bind("mimo-v2-debug", max_batch=2, max_len=64, page_size=PAGE,
               steps_per_sync=K)
        handle = serve.run(app, name="mimo")
        out = handle.remote({"prompt": list(range(1, 12)),
                             "max_new_tokens": 12}).result(timeout_s=240)
        assert len(out["tokens"]) == 12
        serve.delete("mimo")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
