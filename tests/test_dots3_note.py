"""models/dots3_note.py (window latent-attention layers kept as a ring a
lane beside full ones read through a learned selection over a key a
token, a headwise gate, routed experts) against the plain float32
reference the benchmark holds it to (`benchmarks/harness/refs/
dots3_note.py`, which imports nothing of the program): the prompt pass,
paged decode across the ring's wrap and the selection's switch, the
ENGINE's own logits with lanes reused (one engine run shared by the
file's cases: `family_contract`), the banded `flash_fwd` at dv != d, the ring written in
place, the expert shares, the counters and the controls a sound
comparison must fail."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_contract as contract  # rootdir-relative (no pkg)
import sparse_walk_cases
from family_contract import gap as _gap, tokens as _tokens
from serving_reference import Seam, served_logits

from benchmarks.harness.refs import dots3_note as ref
from ray_tpu.models import dots3_note, named_config, serving_model
from ray_tpu.ops import (flash_attention, sparse_attention as dsa,
                         window_attention as swa)
from ray_tpu.ops.attention import xla_attention
from ray_tpu.serve.llm import LLMEngine, LLMServer

# float32 weights: the served path and the reference then differ by
# summation order alone
_DEBUG = named_config("dots3-note-debug")
CFG = dataclasses.replace(
    _DEBUG, dtype=jnp.float32,
    full=dataclasses.replace(_DEBUG.full, dtype=jnp.float32),
    swa=dataclasses.replace(_DEBUG.swa, dtype=jnp.float32))
PAGE, K = 16, 4
TOL = 5e-5
CONTROL = 2e-3
WINDOW, RING, TOP = CFG.window, CFG.ring_rows, CFG.index_topk   # 9, 16, 16


def model_of(cfg) -> dict:
    m = dict(
        hidden_size=cfg.dim, rms_norm_eps=cfg.norm_eps,
        layer_types=list(cfg.layer_types),
        first_k_dense_replace=cfg.n_dense_layers,
        apply_mla_qkv_lora_rescale=cfg.lora_rescale,
        sliding_window_size=cfg.window, index_n_heads=cfg.index_heads,
        index_head_dim=cfg.index_dim, index_topk=cfg.index_topk,
        num_experts_per_tok=cfg.top_k, norm_topk_prob=True,
        routed_scaling_factor=cfg.routed_scaling,
        experts_held=list(cfg.experts_held))
    for p, k in (("", cfg.full), ("swa_", cfg.swa)):
        m.update({p + "num_attention_heads": k.n_heads,
                  p + "q_lora_rank": k.q_lora_rank,
                  p + "kv_lora_rank": k.kv_lora_rank,
                  p + "qk_nope_head_dim": k.qk_nope_dim,
                  p + "qk_rope_head_dim": k.qk_rope_dim,
                  p + "v_head_dim": k.v_head_dim,
                  p + "rope_theta": k.rope_theta})
    return m


MODEL = model_of(CFG)


# The sound program's seam, compiled once a shape for the file (true
# lengths are arguments), and the reference at ONE length (54 is the
# longest sequence a case reads: 40 prompt tokens and 14 served).
SOUND = Seam(dots3_note, CFG)
_ref_logits = contract.one_length(
    lambda p, seq: ref.logits(p, seq, MODEL), 56)
# `dsa.RATIO` picks the form of the DECODE step's selected attention and
# nothing else (`dsa.walks`): the gather's seam is the sound one with a
# decode step of its own, traced (at its first call) under RATIO 0.
_GATHER = SOUND.retraced("decode_step")


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda key: dots3_note.init_params(key, CFG))(
        jax.random.PRNGKey(7))


# --------------------------------------------------- (a) the prompt pass
PREFILL_LENS = [5, WINDOW, 16, 37]


@pytest.fixture(scope="module")
def prefill_rows(params):
    """ONE prompt pass for the lengths: a row each of one program."""
    return contract.prefill_rows(
        SOUND, params, [_tokens(n, n) for n in PREFILL_LENS], PREFILL_LENS)


@pytest.mark.parametrize("n", PREFILL_LENS)
def test_prefill_logits_equal_the_reference(params, prefill_rows, n):
    """5: under the window and the selection; 9: the window full for the
    first time; 37: the selection drops rows and the band has moved on.
    Every true position of the row against the reference's."""
    toks, h = prefill_rows
    i = PREFILL_LENS.index(n)
    got = dots3_note.project_logits(params, h[i, :n])
    assert _gap(got, _ref_logits(params, toks[i, :n])) < TOL


@pytest.mark.parametrize("form", ["walk", "gather"])
@pytest.mark.parametrize("n,bucket,new", [(21, 32, 11), (3, 16, 22),
                                          (WINDOW - 1, 16, 12)])
def test_padded_prefill_then_paged_decode_equals_the_reference(
        params, monkeypatch, n, bucket, new, form):
    """The prompt padded to a bucket beside a longer row, scattered into
    both pool leaves and lane 1's rings, then decode in windows of four:
    from 3 rows the context passes the window (9), the ring's wrap (16)
    and the selection's size (16) while decoding; from 21 it starts past
    all three; from 8 the first step fills the window.  In both forms of
    the full layers' decode attention (the table here is narrow: the
    walk; RATIO 0: the gather a long table gets)."""
    seam = SOUND
    if form == "gather":
        monkeypatch.setattr(dsa, "RATIO", 0)
        seam = _GATHER
    tok = _tokens(n + new, 3 * n)
    got = served_logits(seam, params, CFG, tok[:n], tok[n:], bucket,
                        page=PAGE, k=K)
    assert _gap(got, _ref_logits(params, tok, last=new + 1)) < TOL


def test_the_prefill_hands_pool_and_ring_their_rows(params):
    """A row of true length 21 in a bucket of 32: the pool's rows are
    the reference's, and slot i of a window layer's ring holds the last
    position below 21 that is i mod 16 (5 ... 20)."""
    tok = _tokens(32, 5)
    lens = jnp.asarray([32, 21], jnp.int32)
    toks = jnp.asarray(np.stack([tok, tok]))
    _, latent, index, state, _ = SOUND.serve_prefill(params, toks, lens)
    x = ref.embed(params, tok[:21], MODEL)
    seen = {dots3_note.FULL: 0, dots3_note.WINDOW: 0}
    for lid, lp in enumerate(params["layers"]):
        kind = CFG.layer_types[lid]
        x, _, info, _ = ref.layer(x, lp, lid, MODEL)
        i = seen[kind]
        seen[kind] += 1
        used = CFG.kind(lid).row_used
        if kind == dots3_note.FULL:
            assert _gap(latent[i][1, :21, 0, :used], info["row"]) < TOL
            assert not np.asarray(latent[i][1, :21, 0, used:]).any()
            assert _gap(index[i][1, :21, 0], info["index"]) < TOL
        else:
            ring = np.asarray(state["window"][i][1])
            want = np.asarray(info["row"])
            for slot in range(RING):
                p = slot if slot + RING > 20 else slot + RING
                assert _gap(ring[slot, :used], want[p]) < TOL, (slot, p)


# ------------------------------------------------ (b) the two mechanisms
def test_the_ring_names_the_windows_rows():
    """Slot i holds the last position at or below `pos` that is i mod R;
    a query attends its own position and the window - 1 before it, never
    a slot that holds nothing yet."""
    pos = jnp.asarray([0, 3, 8, 9, 15, 16, 40])
    held = np.asarray(swa.ring_positions(pos, 16))
    keep = np.asarray(swa.ring_bias(pos, 16, 9)) == 0.0
    for b, p in enumerate(pos.tolist()):
        assert sorted(held[b][keep[b]].tolist()) == list(
            range(max(0, p - 8), p + 1))
        assert (held[b] % 16 == np.arange(16)).all()


@pytest.mark.parametrize("T,window,lens,blocks", [
    (512, 65, None, (128, 128)),
    (384, 129, [384, 131], (128, 128)),
    (256, 257, [77, 256], (128, 128)),
    # a band of several KEY blocks: 1,025 + 127 keys of a query block lie
    # in 9-10 blocks of 128 (two of them masked edges); row 1 ends inside
    # a query block whose lower edge is a partial block; a caller's key
    # block longer than its query block is cut to it (`band_blocks`)
    (2048, 1025, [2048, 1100], (128, 128)),
    (2048, 1024, [1300, 2048], (256, 512)),
    # key blocks of no whole lane tile (the running sum's lane 0)
    (192, 65, [192, 70], (64, 64)),
])
def test_the_banded_flash_kernel_equals_a_masked_softmax(T, window, lens,
                                                         blocks):
    """`flash_fwd` under a band at keys wider than values (256 / 128, a
    window layer's expanded path) against XLA's masked softmax; with
    lengths, on every row's true positions."""
    b, H = (1 if lens is None else len(lens)), 1
    ks = jax.random.split(jax.random.PRNGKey(T), 3)
    q = jax.random.normal(ks[0], (b, T, H, 256))
    k = jax.random.normal(ks[1], (b, T, H, 256))
    v = jax.random.normal(ks[2], (b, T, H, 128))
    got = flash_attention.flash_attention(
        q, k, v, sm_scale=0.07, block_q=blocks[0], block_k=blocks[1],
        window=window,
        lengths=None if lens is None else jnp.asarray(lens, jnp.int32))
    want = xla_attention(q, k, v, sm_scale=0.07, window=window)
    for row, n in enumerate(lens or [T]):
        assert _gap(got[row, :n], want[row, :n]) < 1e-5
    bq, bk = flash_attention.band_blocks(T, *blocks)
    assert (bq, bk) == (blocks[0], min(blocks))
    if lens is not None:        # wholly past a row's length: zeros
        short = int(np.argmin(lens))
        assert not np.asarray(
            got[short, -(-lens[short] // bq) * bq:]).any()
    walked = flash_attention.attn_blocks(T, lens or [T], bq, bk, window)
    assert walked <= flash_attention.attn_blocks(T, lens or [T], bq, bk)
    if window < T // 4:         # the band leaves blocks out
        assert walked < flash_attention.attn_blocks(T, lens or [T], bq, bk)
    edges = flash_attention.edge_blocks(T, lens or [T], bq, bk, window)
    assert 0 < edges <= walked
    if window >= 4 * bk:        # a wide band: most steps pay no mask
        assert edges < walked / 2


def test_a_call_without_a_window_walks_what_it_walked():
    """The walk's tables of a call without a band are the parent's, bit
    for bit (the other five families' prefill programs)."""
    n = flash_attention.key_blocks(1024, 1024, np.asarray([1024, 300]),
                                   256, 512)
    assert n.tolist() == [[1, 1, 2, 2], [1, 1, 0, 0]]
    qi, ki, flag, total = flash_attention._walk(n, 6, 256, 512, True, np)
    assert total.tolist() == [6, 4]
    assert qi.reshape(2, 6).tolist() == [[0, 1, 2, 2, 3, 3],
                                         [0, 1, 2, 3, 3, 3]]
    assert ki.reshape(2, 6).tolist() == [[0, 0, 0, 1, 0, 1],
                                         [0, 0, 0, 0, 0, 0]]


def test_the_selection_keeps_the_best_rows_and_the_own_row():
    """A key a token: of 40 rows the 16 best at or below the query, the
    query's own whatever its score."""
    scores = jnp.asarray(np.random.default_rng(0).normal(size=(1, 4, 40)),
                         jnp.float32)
    pos = jnp.asarray([3, 15, 16, 39])
    mask, _ = dsa.selected_mask(scores, pos, 40, 1, 16, own=True)
    mask = np.asarray(mask[0])
    for t, p in enumerate(pos.tolist()):
        assert mask[t, p] and not mask[t, p + 1:].any()
        assert mask[t].sum() == min(p + 1, 16)
        s = np.asarray(scores[0, t, :p + 1]).copy()
        s[p] = np.inf
        assert set(np.argsort(-s)[:16].tolist()) >= set(
            np.nonzero(mask[t])[0].tolist())
    assert dsa.selection_counts(40, 1, 16) == (40, 16)


@pytest.mark.parametrize("case", sparse_walk_cases.CASES)
def test_the_walk_attends_what_the_gather_attends(monkeypatch, case):
    """A key a token, the query's own row forced: the walk over the
    lane's pages under a bias, the gather + `dsa_attn` and a
    sort-and-softmax oracle admit the same positions and give the same
    output: past the selection's size, under it (dense), with the
    window's rows chosen (held in the tail), with equal scores at the
    k-th, with a page partly below the block start; an idle lane reads
    0."""
    out = sparse_walk_cases.check(case, 1, 16, True, monkeypatch)
    sets = out["walk"][1]
    pos = {0: 63, 2: 42, 3: 78}
    if case == "dense":
        assert sets[0] == list(range(13)) and sets[3] == list(range(8))
    elif case != "cut_page":
        assert all(len(sets[b]) == 16 and pos[b] in sets[b] for b in pos)
    if case == "held":
        # the window's rows outscore the pool's: lane 3 (block start 71,
        # query 78) attends its 8 and the 8 best below
        assert set(range(71, 79)) <= set(sets[3])


# ------------------------------------------------ (c) through the engine
PROMPTS = (40, 3, WINDOW - 1, 1, 17)
NEW = 14


@pytest.fixture(scope="module")
def served(params):
    """ONE engine run for the file (`family_contract.served_run`): three
    lanes whose rings were marked, a request of 9 + 9 tokens alone, then
    five prompts at once (under, at and past the window and the
    selection's size)."""
    return contract.served_run(
        dots3_note, CFG, params, lanes=3, kv_pages=19, page=PAGE, k=K,
        prompts=[_tokens(n, 10 + n).tolist() for n in PROMPTS], new=NEW)


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_engine_logits_equal_the_reference_across_lane_reuse(
        params, served, i):
    """A lane that served one request serves another, and neither a
    ring's rows nor a pool row may leak.  The LOGITS the engine's own
    programs computed at every served position equal the reference's
    full forward."""
    seq = served["prompts"][i] + served["outs"][i]["tokens"]
    want = _ref_logits(params, seq[:-1], last=NEW)
    assert contract.engine_gap(served, i, want) < TOL


def test_the_engine_counts_what_the_layers_read(served):
    st = served["stats"]
    assert st["completed"] == 1 + len(PROMPTS) and st["preemptions"] == 0
    loop = st["loop"]
    n_full, n_win = CFG.count(dots3_note.FULL), CFG.count(dots3_note.WINDOW)
    steps = loop["lane_steps_live"]
    assert loop["swa_lane_steps"] == steps * n_win
    assert loop["swa_rows_context"] * n_full \
        == loop["dsa_rows_context"] * n_win
    # under 100 %: the window bounded the work, the selection was sparse
    assert loop["swa_rows_attended"] < loop["swa_rows_context"]
    assert loop["swa_rows_attended"] <= steps * n_win * WINDOW
    assert loop["dsa_rows_selected"] < loop["dsa_rows_context"]
    assert loop["dsa_rows_selected"] <= steps * n_full * TOP
    assert loop["dsa_groups_scored"] == loop["dsa_rows_context"]
    # the walk reads whole pages of 16: no more than a page over the
    # context a step, and more than the 16 rows a selection keeps
    assert loop["dsa_rows_selected"] < loop["dsa_rows_read"] \
        < loop["dsa_rows_context"] + steps * n_full * PAGE
    assert loop["dsa_rows_read"] % PAGE == 0
    assert 0 < loop["prefill_swa_blocks"] <= loop["prefill_swa_blocks_dense"]
    assert 0 < loop["prefill_swa_edge_blocks"] <= loop["prefill_swa_blocks"]
    assert loop["prefill_attn_blocks"] == loop["prefill_swa_blocks"]
    cache = st["cache"]
    # window layers hold no pool page: two leaves a FULL layer only
    assert cache["kind"] == "latent" and set(cache["by_leaf"]) == {
        "latent", "index"}
    assert cache["layers"] == n_full
    assert all(b["positions_per_row"] == 1 and b["layers"] == n_full
               for b in cache["by_leaf"].values())
    assert cache["row_bytes"] == 4 * (CFG.full.row_width + CFG.index_dim)
    lane = st["lane_state"]
    assert lane["layers"] == n_win and set(lane["by_kind"]) == {"window"}
    assert lane["by_kind"]["window"] == n_win * 3 * RING \
        * CFG.swa.row_width * 4
    assert lane["prefix_cache"] == "off: lane state"


def test_the_ring_is_written_in_place(served):
    """One request of 9 + 9 tokens in an engine of three lanes: the idle
    lanes' rings are bit-unchanged, the live lane's were written by the
    scatter and then a slot a step; and the decode program hands every
    ring back in the buffer it came in (donated and aliased: no second
    ring)."""
    assert len(served["first"]["tokens"]) == 9
    for layer in range(CFG.count(dots3_note.WINDOW)):
        assert len(contract.lanes_written(
            served, lambda s: s["window"][layer])) == 1
    text = served["lowered"].as_text()
    n_win = CFG.count(dots3_note.WINDOW)
    ring = f"tensor<3x{RING}x{CFG.swa.row_width}xf32>"
    # each ring is an argument that aliases an output
    assert text.count(ring + " {tf.aliasing_output") == n_win


# ------------------------------------------------- (d) ranges of experts
def test_the_eight_shares_and_the_shared_expert_once_add_up(params):
    """Eight chips each hold one of the router's eight experts; every one
    computes the shared expert alike.  Their routed parts plus the shared
    expert counted ONCE are the uncut layer of the reference."""
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.dim))
    want, _ = ref.ff(x, lp, 1, MODEL)
    h2 = dots3_note.rmsnorm(x, lp["norm2"], CFG.norm_eps)
    parts = dots3_note.shared_ffn(h2, lp, CFG.dtype)
    n = 0
    for lo in range(8):
        chip = dataclasses.replace(CFG, experts_held=(lo, lo + 1))
        held = dict(lp, w13=lp["w13"][lo:lo + 1], w2=lp["w2"][lo:lo + 1])
        y, c = dots3_note.routed_ffn(h2, held, chip)
        parts, n = parts + y, n + int(c[2])
    assert float(jnp.abs(parts - want).max()) < TOL
    assert n == 24 * CFG.top_k
    chip = dataclasses.replace(CFG, experts_held=(2, 5))
    held = dict(lp, w13=lp["w13"][2:5], w2=lp["w2"][2:5])
    got, _ = dots3_note.ffn(x, held, 1, chip)
    want, _ = ref.ff(x, held, 1, model_of(chip))
    assert float(jnp.abs(got - want).max()) < TOL


# ------------------------------------------------------- (e) the controls
# A control changes one equation of one kind of layer, and its patch has
# to be traced: it runs on the model cut to two layers that keep both
# kinds (the first full layer, its selection over the dense feed-forward,
# and the first window layer, its ring over routed experts; both gated
# and rescaled), against the reference of the same cut.
SHALLOW = dataclasses.replace(
    CFG, layer_types=(dots3_note.FULL, dots3_note.WINDOW))
_KEPT = (0, CFG.layer_types.index(dots3_note.WINDOW))


def _sound(params, cfg=SHALLOW, seam=None):
    """The served path (a padded prompt pass, the scatter, eleven decode
    steps in windows of four) against the reference's full forward, on
    the cut's layers (the whole model's under the file's seam); without a
    `seam`, every program traced anew.  The reference is the PUBLISHED
    model's at that depth, whatever equation `cfg` changed."""
    tok = _tokens(32, 41)
    if seam is not SOUND:
        params = dict(params, layers=[params["layers"][i] for i in _KEPT])
    got = served_logits(seam or Seam(dots3_note, cfg), params, cfg,
                        tok[:21], tok[21:], 32, page=PAGE, k=K)
    if seam is SOUND:
        return _gap(got, _ref_logits(params, tok, last=12))
    return _gap(got, ref.logits(params, tok, model_of(SHALLOW), last=12))


def _own_not_forced(scores, pos, n_keys, group, top, own=False,
                    _f=dsa.selected_mask):
    mask, chosen = _f(scores, pos, n_keys, group, top)
    return mask & (jnp.arange(n_keys)[None, :] != pos[:, None]), chosen


CONTROLS = {
    "gate_left_out": lambda mp: mp.setattr(
        dots3_note.jax.nn, "sigmoid", lambda x: jnp.ones_like(x)),
    "rescale_left_out": lambda mp: mp.setattr(
        dots3_note, "lora_scales", lambda k, cfg: (1.0, 1.0)),
    "own_row_not_selected": lambda mp: mp.setattr(dsa, "selected_mask",
                                                  _own_not_forced),
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


@pytest.mark.parametrize("window", [WINDOW - 1, WINDOW + 1])
def test_a_window_off_by_one_exceeds_the_tolerance(params, window):
    assert _sound(params,
                  dataclasses.replace(SHALLOW, window=window)) > CONTROL


def test_the_references_window_edge_is_the_published_one(params):
    """The reference given another window differs from itself: the
    judge's edge reading has something to read."""
    x = ref.embed(params, _tokens(30, 2), MODEL)
    lid = CFG.layer_types.index(dots3_note.WINDOW)
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
    assert model is dots3_note
    spec = model.serving_spec(CFG)
    assert spec.lane_state_layers == 2 and spec.routed_layers == 3
    assert not spec.caps
    # two full layers that select 16 rows, two window layers of 9 rows
    # (a table of 6 pages of 16 is walked: 40 rows lie in 3 pages)
    assert spec.decode_work([40], 1, 16, 6)[0] == {
        "dsa_rows_context": 2 * 41, "dsa_groups_scored": 2 * 41,
        "dsa_rows_selected": 2 * 16, "dsa_rows_read": 2 * 48,
        "swa_rows_context": 2 * 41,
        "swa_rows_attended": 2 * 9, "swa_lane_steps": 2}
    # past RATIO selections of table the gather reads S = 128 a step
    assert spec.decode_work([40], 1, 16, 8 * dsa.RATIO + 1)[0][
        "dsa_rows_read"] == 2 * 128
    assert spec.decode_work([3, 40], 2, 16, 6)[0]["swa_rows_attended"] \
        == 2 * (4 + 5 + 9 + 9)
    work, shown = spec.prefill_work([9, 17], 32)
    assert shown == {} and set(work) == {
        "prefill_attn_blocks", "prefill_attn_blocks_dense",
        "prefill_swa_blocks", "prefill_swa_blocks_dense",
        "prefill_swa_edge_blocks",
        "dsa_prefill_blocks", "dsa_prefill_blocks_dense"}
    streamed, multiplied = spec.prefill_params
    assert (streamed, multiplied) == model.prefill_params(CFG)
    assert streamed > multiplied > 0
    big = dots3_note.Dots3NoteConfig(
        vocab_size=19072, layer_types=(dots3_note.FULL,) * 2
        + (dots3_note.WINDOW,) * 3, experts_held=(0, 32))
    # the ISSUE's arithmetic: a row's rings, 3 x 640 x 1,152 bf16; the
    # rows as stored
    assert dots3_note.serving_spec(big).prefill_state_bytes \
        == 3 * 640 * 1152 * 2
    assert (big.full.row_width, big.swa.row_width) == (640, 1152)
    assert big.layer_types == dots3_note.Dots3NoteConfig().layer_types[:5]
    # the 1 x 8192 program's band: 31 of the causal walk's 136 triples
    work, _ = dots3_note.serving_spec(big).prefill_work([8192], 8192)
    assert work["prefill_swa_blocks"] == 31
    assert work["prefill_swa_blocks_dense"] == 136


def test_lane_state_is_served_without_the_prefix_cache(params):
    # (an engine that is refused at construction: nothing compiles)
    with pytest.raises(ValueError, match="prefix_cache=True refused"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  kv_pages=9, prefix_cache=True)
    with pytest.raises(ValueError, match="under the window"):
        dots3_note.init_paged_cache(
            dataclasses.replace(CFG, ring_rows=8), 2, 9, PAGE)


def test_the_server_serves_the_preset_by_name():
    # an engine of its own: the preset as published (bfloat16), found by
    # its name and served through `LLMServer`
    srv = LLMServer("dots3-note-debug", max_batch=2, max_len=64,
                    page_size=PAGE, kv_pages=9, steps_per_sync=K)
    try:
        out = srv.engine.generate([5, 6, 7, 8, 9], max_new_tokens=6)
        assert len(out["tokens"]) == 6
        st = srv.engine.stats()
        assert st["cache"]["kind"] == "latent"
        assert set(st["lane_state"]["by_kind"]) == {"window"}
    finally:
        srv.shutdown()
