"""models/mla_moe.py (latent attention, routed experts of which a chip
holds a range, a shared expert) against the plain float32 reference the
benchmark holds it to (`benchmarks/harness/refs/mla_moe.py`, which
imports nothing of the program and has the EXPANDED attention only): the
prompt pass, the prompt pass at a padded bucket followed by ABSORBED paged
decode through the latent pool, the engine with lanes reused and a forced
preempt-and-recompute (one engine run shared by the cases that read a
sound run: `family_contract`), the expert ranges and the shared expert adding up
to the uncut layer, the router, the controls a sound comparison must
fail, the engine's counters, and what the engine refuses for a model
whose pool is no K and V."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_contract as contract  # rootdir-relative (no pkg)
from family_contract import tokens as _tokens
from serving_reference import Seam, served_logits

from benchmarks.harness.refs import mla_moe as ref
from ray_tpu.models import mla_moe, routed, serving_model
from ray_tpu.ops import grouped_matmul
from ray_tpu.serve.llm import LLMEngine, LLMServer

# float32 weights: the served path and the reference then differ by
# summation order alone (and by absorbed against expanded, which is
# exact), so the bound is tight and every control stands far outside it.
# This chip holds experts 0-3 of the router's 8.
CFG = mla_moe.MlaMoeConfig(
    vocab_size=256, dim=64, n_layers=3, n_dense_layers=1, n_heads=4,
    kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    ffn_dim=96, moe_ffn_dim=32, n_experts=8, experts_held=(0, 4), top_k=4,
    rope_original_max=32, max_seq=128, dtype=jnp.float32)


def model_of(cfg) -> dict:
    """The published keys the reference reads, for a program config."""
    return dict(
        num_attention_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
        q_head_dim=cfg.qk_head_dim, qk_nope_head_dim=cfg.qk_nope_dim,
        qk_rope_head_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        rope_scaling=dict(
            type="deepseek_yarn", factor=cfg.rope_factor,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
            mscale=cfg.rope_mscale, mscale_all_dim=cfg.rope_mscale_all_dim,
            original_max_position_embeddings=cfg.rope_original_max),
        use_qk_norm=True, first_k_dense_replace=cfg.n_dense_layers,
        num_experts_per_tok=cfg.top_k, moe_router_enable_expert_bias=True,
        routed_scaling_factor=cfg.routed_scaling,
        experts_held=list(cfg.experts_held))


MODEL = model_of(CFG)
TOL = 2e-4          # float32 against float32: summation order
CONTROL = 2e-2      # what every control must exceed, 100 x TOL
PAGE, K = 16, 4


# The sound program's seam, compiled once a shape for the file (true
# lengths are arguments), and the reference at ONE length (54 is the
# longest sequence a case reads: 40 prompt tokens and 14 served).
SOUND = Seam(mla_moe, CFG)
_ref_logits = contract.one_length(
    lambda p, seq: ref.logits(p, seq, MODEL), 56)
# A control changes one equation of the attention or of the routed layer,
# and its patch has to be traced: it runs on the model cut to its first
# two layers, which keep both kinds (latent attention over the dense
# feed-forward, then over the routed experts and the shared one), against
# the reference of the same cut.
SHALLOW = dataclasses.replace(CFG, n_layers=2)


@pytest.fixture(scope="module")
def params():
    return mla_moe.init_params(jax.random.PRNGKey(7), CFG)


def _worst(params, n=21, bucket=32, follow=2 * K, cfg=CFG):
    """The sound program through the file's seam; on the cut, through
    programs traced anew."""
    prompt, nxt = _tokens(n, 1), _tokens(follow, 2)
    seq = list(prompt) + list(nxt)
    if cfg is CFG:
        seam = SOUND
        want = _ref_logits(params, seq, last=follow + 1)
    else:
        seam = Seam(mla_moe, cfg)
        params = dict(params, layers=params["layers"][:cfg.n_layers])
        want = ref.logits(params, seq, model_of(cfg), last=follow + 1)
    got = served_logits(seam, params, cfg, prompt, nxt, bucket,
                        page=PAGE, k=K)
    return float(jnp.max(jnp.abs(got - want)))


# ---------------------------------- (1), (2) against the full forward
PREFILL_LENS = [1, 2, 17, 32]


@pytest.fixture(scope="module")
def prefill_rows(params):
    """ONE prompt pass for the four lengths: the same 32 tokens in four
    rows of one program, a true length each."""
    toks, h = contract.prefill_rows(
        SOUND, params, [_tokens(32, 3)] * len(PREFILL_LENS), PREFILL_LENS)
    return toks[0], h


@pytest.mark.parametrize("n", PREFILL_LENS)
def test_prefill_logits_equal_the_reference(params, prefill_rows, n):
    toks, h = prefill_rows
    got = mla_moe.project_logits(params, h[PREFILL_LENS.index(n), :n])
    want = _ref_logits(params, toks[:n])
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.mark.parametrize("n,bucket", [(21, 32), (1, 32), (16, 32), (33, 64)])
def test_padded_prefill_then_paged_decode_equals_the_reference(
        params, n, bucket):
    """Logits, not tokens: the ABSORBED path over the latent pool (rows
    scattered by prefill, then two windows of K steps through the tail
    and its merge) against the reference's EXPANDED full forward."""
    assert _worst(params, n=n, bucket=bucket) < TOL


def test_the_yarn_frequencies_are_the_references():
    cos, sin = mla_moe.yarn_frequencies(CFG, 64)
    ang = np.arange(64)[:, None] * np.asarray(ref.yarn_inv_freq(MODEL))
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=1e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=1e-5)
    # published keys: the slow dimensions are divided by 40, the fast kept
    big = mla_moe.MlaMoeConfig()
    inv = np.asarray(ref.yarn_inv_freq(model_of(big)))
    plain = 1.0 / big.rope_theta ** (np.arange(0, 64, 2) / 64)
    assert inv[0] == pytest.approx(plain[0])
    assert inv[-1] == pytest.approx(plain[-1] / 40.0)
    assert mla_moe.softmax_scale(big) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40.0) + 1.0) ** 2)
    assert ref.softmax_scale(model_of(big)) == pytest.approx(
        mla_moe.softmax_scale(big))


# ------------------------------------------------ (3) through the engine
PROMPTS = (40, 3, 17, 1, 29)


@pytest.fixture(scope="module")
def served(params):
    """ONE engine run for the file (`family_contract.served_run`): two
    lanes over a pool of six pages, a request of 21 + 9 tokens alone,
    then five prompts at once, which the pool cannot hold together."""
    return contract.served_run(
        mla_moe, CFG, params, lanes=2, kv_pages=6, page=PAGE, k=K,
        first=(21, 5, 9), new=14,
        prompts=[_tokens(n, 10 + n).tolist() for n in PROMPTS])


def _reference_agrees(params, prompt, served) -> int:
    lg = _ref_logits(params, list(prompt) + served[:-1], last=len(served))
    top2 = np.sort(lg, axis=-1)[:, -2:]
    clear = top2[:, 1] - top2[:, 0] > TOL
    assert (np.argmax(lg, -1)[clear] == np.asarray(served)[clear]).all()
    return int(clear.sum())


def test_engine_generates_the_reference_tokens(params, served):
    """Two lanes, a request and then five prompts of other lengths: lanes
    are reused, and a pool too small for both forces a
    preempt-and-recompute.  Greedy tokens equal the reference's wherever
    its top-two margin exceeds the tolerance; the counters say what the
    pool holds and what the chip's range of experts computed."""
    prompts, outs, st = served["prompts"], served["outs"], served["stats"]
    assert st["preemptions"] >= 1
    assert st["completed"] == 1 + len(PROMPTS)
    clear = sum(_reference_agrees(params, p, o["tokens"])
                for p, o in zip(prompts, outs))
    assert clear >= 5 * 14 - 3
    loop, routed_layers = st["loop"], mla_moe.serving_spec(CFG).routed_layers
    assert loop["moe_layer_steps"] % (routed_layers * K) == 0
    # every selection is either computed here or an absent chip's
    assert loop["moe_assignments"] > 0 and loop["moe_assignments_absent"] > 0
    assert (loop["moe_assignments"] + loop["moe_assignments_absent"]
            == loop["lane_steps_live"] * CFG.top_k * routed_layers)
    assert 0 < loop["moe_experts_hit"] <= 4 * loop["moe_layer_steps"]
    # a decode step routes 2 lanes x 4 selections: one row tile and, at
    # most, a visit more for each of the 3 boundaries between 4 experts
    assert 0 < loop["moe_visits"] <= loop["moe_visits_static"] \
        == 4 * loop["moe_layer_steps"]
    assert 0 < loop["prefill_moe_visits"] <= loop["prefill_moe_visits_static"]
    assert (loop["prefill_moe_assignments"]
            + loop["prefill_moe_assignments_absent"]
            >= 4 * sum(map(len, prompts)) * routed_layers)
    assert st["cache"] == {
        "kind": "latent", "row_bytes": CFG.row_width * 4, "layers": 3,
        "pool_bytes": 3 * 6 * PAGE * CFG.row_width * 4,
        "by_leaf": {"latent": {
            "row_bytes": CFG.row_width * 4, "positions_per_row": 1,
            "layers": 3, "pool_bytes": 3 * 6 * PAGE * CFG.row_width * 4}}}
    assert "lane_state" not in st and st["prefix_cache"] is False


@pytest.mark.parametrize("held", [(0, 4), (0, 2), (6, 8)],
                         ids=["a_half", "a_quarter", "the_last_quarter"])
def test_the_share_of_the_visit_list_that_is_work(held):
    """`moe_visits / moe_visits_static`, decode and prefill apart: the
    visits the grouped matmul walked over the length its lists were
    padded to.  A prompt of 40 in a bucket of 64 routes 160 selections
    of the list's 256 rows; a chip that holds a quarter of the experts
    computes about 40 of them, so its list (two row tiles of 128 and a
    boundary a group) is not all work.  (Under this
    model's floor the one-row program of the longest bucket holds the
    row where the 64-bucket's is not built: serve/prefill_plan.py.)"""
    # an engine of its own a share: its experts are another range, and
    # the counts are those of ONE prompt in a fresh engine
    cfg = dataclasses.replace(CFG, experts_held=held)
    eng = LLMEngine(cfg, mla_moe.init_params(jax.random.PRNGKey(7), cfg),
                    max_batch=2, max_len=96, page_size=PAGE,
                    steps_per_sync=K)
    eng.start()
    try:
        eng.generate(_tokens(40, 5).tolist(), max_new_tokens=5)
        loop = eng.stats()["loop"]
    finally:
        eng.stop()
    G, layers = held[1] - held[0], mla_moe.serving_spec(cfg).routed_layers
    assert loop["prefill_moe_layer_steps"] == layers
    bucket = min(b for w, b in eng._prefill_programs if w == 1 and b >= 64)
    assert loop["prefill_padded_tokens"] == bucket
    rows = bucket * cfg.top_k
    assert loop["prefill_moe_visits_static"] == layers * (
        -(-rows // grouped_matmul.row_tile(rows)) + G - 1)
    assert loop["moe_visits_static"] == loop["moe_layer_steps"] * (1 + G - 1)
    for p in ("", "prefill_"):
        assert 0 < loop[p + "moe_visits"] <= loop[p + "moe_visits_static"]
        # a visit holds a row, and a row tile 128 of them at most
        assert loop[p + "moe_visits"] >= loop[p + "moe_assignments"] / 128
    if G == 2:
        assert loop["prefill_moe_visits"] < \
            loop["prefill_moe_visits_static"]


def test_attn_ctx_rows_counts_what_the_kernel_admits(served):
    """The run's first request, 21 prompt tokens and 9 new ones alone in
    the engine: the first comes from the prefill, two windows of K=4 from
    positions 21 and 25.  The host's count equals the rows the kernel's
    masks admit (pages below the block start, the tail up to the
    position), step by step."""
    assert len(served["first_prompt"]) == 21
    loop = served["first_stats"]["loop"]
    admitted = 0
    for ts in (21, 25):
        for j in range(K):
            pos = ts + j
            admitted += int(np.sum(np.arange(96) < ts)) \
                + int(np.sum(ts + np.arange(K) <= pos))
    assert loop["attn_ctx_rows"] == admitted == 204
    assert loop["attn_steps"] == 2 + 2       # ceil(21/16), ceil(25/16)


@pytest.mark.parametrize("held,block", [((0, 2), 64), ((0, 8), 128)],
                         ids=["a_quarter", "all"])
def test_the_rows_a_routed_layer_moves_follow_what_the_chip_holds(
        monkeypatch, held, block):
    """`moe_rows_moved` beside `moe_assignments` + `moe_assignments_absent`:
    a prefill program whose sorted list (a bucket x top 4) is longer than
    `routed.BLOCK` (patched below it) gathers the blocks that hold a held
    row and no other, so the rows it moves follow the experts held and
    the prompt's true length; a decode step (2 lanes x 4 assignments) is
    one block, moved whole; and the tokens are the whole-list engine's."""
    cfg = dataclasses.replace(CFG, experts_held=held)
    params = mla_moe.init_params(jax.random.PRNGKey(7), cfg)
    toks, loops = [], []
    for b in (routed.BLOCK, block):
        # an engine a block size: its programs are traced under the patch
        monkeypatch.setattr(routed, "BLOCK", b)
        eng = LLMEngine(cfg, params, max_batch=2, max_len=96,
                        page_size=PAGE, steps_per_sync=K)
        eng.start()
        try:
            toks.append(eng.generate(_tokens(40, 5).tolist(),
                                     max_new_tokens=5)["tokens"])
            loops.append(eng.stats()["loop"])
        finally:
            eng.stop()
    assert toks[0] == toks[1]
    whole, loop = loops
    layers = mla_moe.serving_spec(cfg).routed_layers
    listed = loop["prefill_padded_tokens"] * cfg.top_k * layers
    assert whole["prefill_moe_rows_moved"] == listed     # one block a layer
    held_rows = loop["prefill_moe_assignments"]
    moved = loop["prefill_moe_rows_moved"]
    assert moved % block == 0
    assert held_rows <= moved < held_rows + layers * block
    assert held_rows + loop["prefill_moe_assignments_absent"] \
        == 40 * cfg.top_k * layers
    if held != (0, 8):
        assert moved < listed                 # the saving
    for lp in loops:            # a decode step: the whole list, 8 rows
        assert lp["moe_rows_moved"] == lp["moe_layer_steps"] * 2 * cfg.top_k


# ------------------------------------------------- (4) ranges of experts
def test_the_parts_of_four_expert_ranges_and_the_shared_expert_once_add_up(
        params):
    """Four chips each hold two of the router's eight experts; every one
    computes the shared expert alike.  Their routed parts plus the shared
    expert counted ONCE are the uncut layer of the uncut reference."""
    whole = dataclasses.replace(CFG, experts_held=(0, 8))
    lp = mla_moe.init_params(jax.random.PRNGKey(3), whole)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.dim))
    want, _ = ref.ff_half(x, lp, 1, model_of(whole))
    h2 = mla_moe.rmsnorm(x, lp["ffn_norm"], CFG.norm_eps)
    parts, n = mla_moe.shared_ffn(h2, lp, CFG.dtype), 0
    for lo in range(0, 8, 2):
        chip = dataclasses.replace(CFG, experts_held=(lo, lo + 2))
        held = dict(lp, w13=lp["w13"][lo:lo + 2], w2=lp["w2"][lo:lo + 2])
        y, c = mla_moe.routed_ffn(h2, held, chip)
        parts, n = parts + y, n + int(c[2])
    assert float(jnp.abs(x + parts - want).max()) < TOL
    assert n == 24 * CFG.top_k
    # and one chip's layer is its own part plus the shared expert
    chip = dataclasses.replace(CFG, experts_held=(2, 4))
    held = dict(lp, w13=lp["w13"][2:4], w2=lp["w2"][2:4])
    got, _ = mla_moe.ffn(x, held, 1, chip)
    want, _ = ref.ff_half(x, held, 1, model_of(chip))
    assert float(jnp.abs(x + got - want).max()) < TOL


def test_the_bias_moves_the_selection_and_not_the_weights(params):
    lp = params["layers"][1]
    h2 = jax.random.normal(jax.random.PRNGKey(6), (64, CFG.dim))
    idx0, w0 = mla_moe.route(h2, dict(lp, expert_bias=jnp.zeros(8)), CFG)
    idx1, w1 = mla_moe.route(h2, dict(lp, expert_bias=jnp.linspace(
        -0.2, 0.2, 8)), CFG)
    assert (np.sort(np.asarray(idx0), -1)
            != np.sort(np.asarray(idx1), -1)).any()
    s = jax.nn.sigmoid(h2 @ lp["router"])
    want = jnp.take_along_axis(s, idx1, -1)
    want = CFG.routed_scaling * want / (want.sum(-1, keepdims=True) + 1e-6)
    assert float(jnp.abs(w1 - want).max()) < 1e-5       # weights: s alone
    np.testing.assert_allclose(np.asarray(w0.sum(-1)), 2.5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(w1.sum(-1)), 2.5, atol=1e-4)


# ---------------------------------------------------------- (5) controls
def _latent_norm_skipped(h, lp, cfg, cos, sin, positions=None):
    kv = h @ lp["wkva"]
    c, k_r = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
    k_r = mla_moe.apply_rope(k_r[:, :, None, :], cos, sin,
                             positions=positions)[:, :, 0]
    return c, k_r


def _rope_term_left_out(h, lp, cfg, cos, sin, positions=None):
    q_nope, q_rope = _QUERIES(h, lp, cfg, cos, sin, positions)
    return q_nope, jnp.zeros_like(q_rope)


def _normalised_over_the_held(h2, lp, cfg):
    idx, wts = _ROUTE(h2, lp, cfg)
    lo, hi = cfg.experts_held
    held = jnp.where((idx >= lo) & (idx < hi), wts, 0.0)
    return idx, cfg.routed_scaling * held / (
        held.sum(-1, keepdims=True) + 1e-6)


def _rows_through_fp8(c, k_r, cfg):
    return _CACHE_ROW(c, k_r, cfg).astype(jnp.float8_e4m3fn).astype(
        cfg.dtype)


def _values_from_the_wrong_columns(q, pages, tail, *a):
    """The same scores (q and the rows rolled alike), values from the
    columns one rotary width further on."""
    n = -CFG.qk_rope_dim
    return _DECODE_ATTENTION(jnp.roll(q, n, -1), jnp.roll(pages, n, -1),
                             jnp.roll(tail, n, -1), *a)


_QUERIES, _ROUTE, _CACHE_ROW, _DECODE_ATTENTION = (
    mla_moe.queries, mla_moe.route, mla_moe.cache_row,
    mla_moe.decode_attention)
CONTROLS = {
    "the_latents_norm_skipped": ("latent_rows", _latent_norm_skipped),
    "q_rope_k_r_left_out_of_the_score": ("queries", _rope_term_left_out),
    "the_scale_without_m_squared": (
        "softmax_scale", lambda cfg: cfg.qk_head_dim ** -0.5),
    "the_shared_expert_left_out": (
        "shared_ffn", lambda h2, lp, dtype: jnp.zeros_like(h2)),
    "weights_normalised_over_the_held_experts_only": (
        "route", _normalised_over_the_held),
    "the_cache_rows_through_fp8": ("cache_row", _rows_through_fp8),
    "values_read_from_the_wrong_columns": (
        "decode_attention", _values_from_the_wrong_columns),
}


@pytest.mark.parametrize("control", ["sound"] + list(CONTROLS))
def test_every_control_exceeds_the_tolerance(params, monkeypatch, control):
    if control == "sound":       # the whole model, then the cut
        assert _worst(params) < TOL
    else:
        monkeypatch.setattr(mla_moe, *CONTROLS[control])
    worst = _worst(params, cfg=SHALLOW)
    if control == "sound":
        assert worst < TOL
    else:
        assert worst > CONTROL


# ------------------- (5b) the benchmark family's judge sees the cache rows
@pytest.mark.parametrize("rows", ["sound", "through_fp8"])
def test_the_familys_judge_reads_the_cache_rows(monkeypatch, rows):
    """`benchmarks/harness/families/mla_moe.py` holds a run to three
    readings; the served tokens' gap cannot tell an fp8 cache from a
    sound run's tail (PERF.md section 6, PR 34), so the rows the program
    hands the cache are read against the reference's: in bfloat16 under
    the family's limit, through fp8 over it, the blocks untouched."""
    from benchmarks.harness import spec

    cell = spec.load_cell("sarvam105b.docs.closed")
    fam, config = cell.family, cell.config
    fam.rehearsal(config)
    model = fam.published(config)
    cfg = fam.program_config(model, max_seq=128)
    params = jax.jit(lambda k: fam.init_params(k, cfg))(
        jax.random.PRNGKey(11))
    monkeypatch.setattr(fam, "_BLOCKS", {})      # traces hold `cache_row`
    if rows == "through_fp8":
        monkeypatch.setattr(mla_moe, "cache_row", _rows_through_fp8)
    got = fam.block_errors(params, list(_tokens(100, 5) % 256), model)
    assert got["row_at"].endswith(".cache")
    if rows == "sound":
        assert got["row_worst"] < fam.ROW_ERR_TOL
    else:
        assert got["row_worst"] > fam.ROW_ERR_TOL
        assert got["worst"] < fam.BLOCK_ERR_TOL * 4   # no block reads it


# --------------------------------------------- (6) what the engine refuses
def test_a_latent_pool_is_served_without_what_reads_a_k_and_v_pool(params):
    # (engines that are refused at construction, and one never started:
    # nothing of theirs compiles)
    assert serving_model(CFG) is mla_moe
    assert mla_moe.route is routed.route       # one router, two modules
    with pytest.raises(ValueError, match="no prefill_with_prefix"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  prefix_cache=True)
    with pytest.raises(ValueError, match="no LoRA hooks"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  lora_slots=2, lora_rank=4)
    eng = LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE)
    assert eng.stats()["prefix_cache"] is False
    assert eng.stats()["cache"]["kind"] == "latent"
    with pytest.raises(ValueError, match="no KV export/import"):
        eng.submit([1, 2, 3], prefill_only=True)
    with pytest.raises(ValueError, match="no KV export/import"):
        eng.kv_graft(list(range(PAGE)), np.zeros(1), kv_len=PAGE)


@pytest.mark.parametrize("kw,match", [
    (dict(lora_slots=2, lora_rank=4), "no LoRA hooks"),
    (dict(role="prefill", decode_deployment="decode"), "serve it unified"),
    (dict(role="decode"), "serve it unified"),
    (dict(prefix_cache=True), "no prefill_with_prefix"),
])
def test_the_server_refuses_at_construction(params, kw, match):
    with pytest.raises(ValueError, match=match):
        LLMServer(CFG, params=params, max_batch=2, max_len=64,
                  page_size=PAGE, **kw)


def test_the_server_serves_a_preset_by_name():
    # an engine of its own: the preset as published (bfloat16), found by
    # its name and served through `LLMServer`
    srv = LLMServer("mla-debug", max_batch=2, max_len=64, page_size=PAGE)
    try:
        out = srv.engine.generate([5, 6, 7], max_new_tokens=5)
        assert len(out["tokens"]) == 5
        assert srv._prefix_client is None       # no demotion either
        assert srv.stats()["cache"]["kind"] == "latent"
    finally:
        srv.engine.stop()


def test_prefill_params_equal_a_count_over_the_tree(params):
    """`prefill_params`: every matmul leaf of the layers is streamed, the
    experts HELD and the shared one among them (no embedding, no head:
    one position a row); a position multiplies them all but the held
    experts, of which the share of its `top_k` that this chip holds."""
    from ray_tpu.serve.prefill_plan import FLOOR_TOKENS

    held = CFG.experts_held[1] - CFG.experts_held[0]
    experts = sum(lp[k].size for lp in params["layers"] if "w13" in lp
                  for k in ("w13", "w2"))
    shared = sum(lp[k].size for lp in params["layers"] if "sw1" in lp
                 for k in ("sw1", "sw2", "sw3"))
    matmul = sum(a.size for lp in params["layers"] for a in lp.values()
                 if a.ndim >= 2)
    assert experts == mla_moe.serving_spec(CFG).routed_layers * held \
        * 3 * CFG.dim * CFG.moe_ffn_dim
    assert shared == mla_moe.serving_spec(CFG).routed_layers * 3 * CFG.dim \
        * CFG.moe_ffn_dim * CFG.n_shared_experts
    streamed, multiplied = mla_moe.prefill_params(CFG)
    assert streamed == matmul
    assert multiplied == matmul - experts + experts * CFG.top_k \
        // CFG.n_experts
    # (an engine that is built and never started: its plan is read,
    # nothing compiles)
    eng = LLMEngine(CFG, params, max_batch=16, max_len=128, page_size=PAGE)
    assert FLOOR_TOKENS < eng._prefill_floor \
        == FLOOR_TOKENS * streamed // multiplied \
        == eng.stats()["loop"]["prefill_floor_positions"]
    assert eng._width_buckets == [1, 2, 4, 8, 16]
    # widths 2 and 4 only where they are free: never past the floor
    assert all(w * b <= eng._prefill_floor
               for w, b in eng._prefill_programs if w in (2, 4))
