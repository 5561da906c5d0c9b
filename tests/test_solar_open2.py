"""models/solar_open2.py (KDA layers whose decay gate has no lower bound
and whose write strength reaches 2, three in four, beside a gated NoPE
GQA layer in pages, every layer routed with a shared expert) against the
plain float32 reference the benchmark holds it to
(`benchmarks/harness/refs/solar_open2.py`: the token-by-token recurrence,
a dense loop over the experts, importing nothing of the program): the
prompt pass at a padded bucket followed by paged decode through the pool
and the lane state, the ENGINE's own logits with lanes reused and a dead
lane bit-unchanged (one engine run shared by the file's cases:
`family_contract`), both KDA kernels against the recurrence where this
family takes them (beta near 2 on repeated keys, log decays of -30 a
step), the expert ranges' parts adding up to the uncut layer, the
controls a sound comparison must fail, and the counters."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_contract as contract  # rootdir-relative (no pkg)
from family_contract import gap as _gap, tokens as _tokens
from serving_reference import Seam, served_logits

from benchmarks.harness.refs import solar_open2 as ref
from ray_tpu.models import named_config, routed, serving_model, solar_open2
from ray_tpu.ops import kda, ssm
from ray_tpu.serve.llm import LLMEngine, LLMServer

# float32 weights: the served path and the reference then differ by
# summation order alone, so the bound is tight and every control stands
# far outside it
CFG = dataclasses.replace(named_config("solar-open2-debug"),
                          dtype=jnp.float32)
MODEL = dict(
    gqa_layers=list(CFG.gqa_layers), num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, use_gqa_gate=True,
    linear_attn_config={"num_heads": 4, "head_dim": 16,
                        "short_conv_kernel_size": 4},
    kda_allow_neg_eigval=True, rms_norm_eps=1e-5, num_experts_per_tok=2,
    norm_topk_prob=True, routed_scaling_factor=1.0, experts_held=[0, 8])
TOL = 2e-5          # float32 against float32, of the logits' scale
CONTROL = 2e-3      # what every control must exceed, 100 x TOL
PAGE, K = 16, 4
N_KDA, N_GQA = CFG.count("kda"), CFG.count("gqa")

# The sound program's seam, compiled once a shape for the file (true
# lengths are arguments), and the reference at ONE length (54 is the
# longest sequence a case reads: 40 prompt tokens and 14 served).
SOUND = Seam(solar_open2, CFG)
_ref_logits = contract.one_length(
    lambda p, seq: ref.logits(p, seq, MODEL), 56)


@pytest.fixture(scope="module")
def params():
    return solar_open2.init_params(jax.random.PRNGKey(7), CFG)


def _worst(params_served, params_ref, n=21, bucket=32, follow=2 * K,
           cfg=CFG, model=MODEL, seam=None):
    """Without a `seam`, through programs traced anew (a control's patch
    has to be traced)."""
    prompt, nxt = _tokens(n, 1), _tokens(follow, 2)
    got = served_logits(seam or Seam(solar_open2, cfg), params_served, cfg,
                        prompt, nxt, bucket, page=PAGE, k=K)
    seq = list(prompt) + list(nxt)
    if seam is SOUND:
        return _gap(got, _ref_logits(params_ref, seq, last=follow + 1))
    return _gap(got, ref.logits(params_ref, seq, model, last=follow + 1))


# ------------------------- (a) prefill, then decode, against the forward
PREFILL_LENS = [1, 2, 17, 32]


@pytest.fixture(scope="module")
def prefill_rows(params):
    """The same 32 tokens at the four lengths, two rows a pass: the file's
    ONE prompt program (two rows of 32 positions, `served_logits`'), a
    true length each."""
    toks = _tokens(32, 3)
    hs = [contract.prefill_rows(SOUND, params, [toks] * 2, lens)[1]
          for lens in (PREFILL_LENS[:2], PREFILL_LENS[2:])]
    return toks, jnp.concatenate(hs)


@pytest.mark.parametrize("n", PREFILL_LENS)
def test_prefill_logits_equal_the_reference(params, prefill_rows, n):
    toks, h = prefill_rows
    got = solar_open2.project_logits(params, h[PREFILL_LENS.index(n), :n])
    assert _gap(got, _ref_logits(params, toks[:n])) < TOL


@pytest.mark.parametrize("n,bucket", [(21, 32), (1, 32), (2, 32), (3, 32),
                                      (9, 32)])
def test_padded_prefill_then_paged_decode_equals_the_reference(
        params, n, bucket):
    """true_len a multiple of nothing (not of the chunk of 8 either): the
    lane state must be the state and the convolution rows at the TRUE
    length (zeros where the prompt is shorter than three), the pool the K
    and V rows below it, and two windows of K steps carry them on."""
    assert _worst(params, params, n=n, bucket=bucket, seam=SOUND) < TOL


def test_the_prefill_hands_the_state_and_the_rows_at_the_true_length(params):
    toks = _tokens(32, 5)
    # (the second row of the seam's two-row program of 32 positions)
    _, ks, vs, state, counts = SOUND.serve_prefill(
        params, jnp.asarray(np.stack([toks, toks])),
        jnp.asarray([32, 13], jnp.int32))
    ks, vs, state = jax.tree.map(lambda a: a[1:], (ks, vs, state))
    x = ref.embed(params, toks[:13])
    want = {"state": [], "conv": [], "k": [], "v": []}
    for lid, lp in enumerate(params["layers"]):
        x, _, info = ref.layer(x, lp, lid, MODEL)
        for name in want:
            if name in info:
                want[name].append(info[name])
    assert len(state["kda"]) == len(state["conv"]) == N_KDA
    for got, exp in zip(state["kda"], want["state"]):
        assert got.shape == (1, 4, 16, 16)
        assert _gap(got[0], exp) < 1e-5
    for got, exp in zip(state["conv"], want["conv"]):
        assert got.shape == (1, 3, 3 * 64)
        assert _gap(got[0], exp) < 1e-5
    assert len(ks) == len(vs) == N_GQA
    for got, exp in zip(ks + vs, want["k"] + want["v"]):
        assert _gap(got[0, :13], exp) < 1e-5
    # every position below the true lengths (the program's two rows: 32
    # and 13) chose top_k of the 8 experts, all held, in every layer
    assert counts.shape == (CFG.n_layers, routed.COUNTS)
    assert counts[:, 2].tolist() == [(32 + 13) * CFG.top_k] * CFG.n_layers


# ------------------------------------------------ (a) through the engine
PROMPTS = (40, 3, 17, 1, 29)
NEW, LANES = 14, 2


@pytest.fixture(scope="module")
def served(params):
    """ONE engine run for the file (`family_contract.served_run`): two
    lanes whose state was marked, a request of 9 + 9 tokens alone, then
    five prompts at once (two lanes: every wave is as wide as its rows,
    so the counters are the prompts' own)."""
    return contract.served_run(
        solar_open2, CFG, params, lanes=LANES, kv_pages=12, page=PAGE, k=K,
        prompts=[_tokens(n, 10 + n).tolist() for n in PROMPTS], new=NEW)


def test_engine_logits_equal_the_reference_across_lane_reuse(
        params, served):
    """Two lanes, a request and then five prompts of other lengths: more
    requests than lanes, so a lane that served one request serves
    another, and no state may leak.  The LOGITS the engine's own programs
    computed at every served position equal the reference's full forward,
    and the counters equal what the kernels' work lists admit."""
    st = served["stats"]
    assert st["completed"] == 1 + len(PROMPTS) and st["preemptions"] == 0
    for i, (prompt, out) in enumerate(zip(served["prompts"],
                                          served["outs"])):
        assert len(out["tokens"]) == NEW
        want = _ref_logits(params, (prompt + out["tokens"])[:-1], last=NEW)
        assert contract.engine_gap(served, i, want) < TOL
    # the counters: live lanes x K x KDA layers a window; the chunks of 8
    # positions below the true lengths; every assignment of a live lane
    # computed, for every expert is held
    prompts = served["prompts"] + [served["first_prompt"]]
    loop = st["loop"]
    assert loop["ssm_lane_steps"] == loop["lane_steps_live"] * N_KDA
    assert loop["prefill_scan_chunks"] == N_KDA * sum(
        -(-len(p) // 8) for p in prompts)
    assert loop["prefill_scan_chunks"] <= loop["prefill_scan_chunks_dense"]
    assert 0 < loop["prefill_attn_blocks"] <= loop[
        "prefill_attn_blocks_dense"]
    assert loop["prefill_walked_tokens"] >= sum(len(p) for p in prompts)
    assert loop["moe_assignments"] == (loop["lane_steps_live"]
                                       * CFG.n_layers * CFG.top_k)
    assert loop["moe_assignments_absent"] == 0
    assert loop["prefill_moe_assignments"] == CFG.n_layers * CFG.top_k * sum(
        len(p) for p in prompts)
    lane = st["lane_state"]
    assert lane["layers"] == N_KDA
    assert lane["by_kind"] == {"conv": N_KDA * LANES * 3 * 3 * 64 * 4,
                               "kda": N_KDA * LANES * 4 * 16 * 16 * 4}
    assert lane["prefix_cache"] == "off: lane state"
    assert st["cache"]["kind"] == "kv"


def test_a_dead_lanes_state_is_bit_unchanged_by_a_decode_window(served):
    """The run's first request (9 + 9 tokens) alone in an engine of two
    lanes whose state was marked: the windows' steps update its lane's
    state matrices and convolution rows, every one, and leave the other
    lane's as they were, bit for bit."""
    for name in ("kda", "conv"):
        (used,) = contract.lanes_written(
            served, lambda s: np.moveaxis(s[name], 1, 0))
        before, after = (s[name] for s in served["state"])
        flat = lambda a: a[:, used].reshape(N_KDA, -1)  # noqa: E731
        assert (flat(after) != flat(before)).any(axis=-1).all(), name


def test_the_decode_program_aliases_the_state_and_the_pool(served):
    """The lanes' state matrices go through `kda_update` in place: the
    lowered decode program donates the cache it is handed."""
    text = served["lowered"].as_text()
    # the two state arrays and a K and a V leaf a GQA layer, at the least
    assert text.count("tf.aliasing_output") >= 2 + 2 * N_GQA


# -------------------- (c) the kernels where this family takes them
def _kernel_inputs(T, H=2, dk=16, seed=0, repeat=True, beta=1.99):
    """Keys that REPEAT (one key a head, every position), beta near 2, a
    slow decay in most channels and -30 a step in about one of seven."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (1, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (1, T, H, dk)))
    if repeat:
        k = jnp.broadcast_to(k[:, :1], k.shape)
    v = jax.random.normal(ks[2], (1, T, H, dk))
    g = -0.05 * jax.nn.sigmoid(jax.random.normal(ks[3], (1, T, H, dk)))
    g = jnp.where(jax.random.uniform(ks[4], g.shape) < 0.15, -30.0, g)
    return q, k, v, g, jnp.full((1, T, H), beta, jnp.float32)


def _rel(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("T,chunk,repeat,beta", [
    (96, 32, True, 1.99),       # the served chunk, three of them
    (70, 32, True, 1.5),        # a length that ends mid-chunk
    (40, 16, False, 1.99),      # distinct keys
    (21, 8, True, 1.99),        # the debug preset's chunk
])
def test_kda_scan_is_the_recurrence_at_steep_decays_and_beta_near_2(
        T, chunk, repeat, beta):
    """`unbounded`: pairs anchored between them, the inverse by
    halves.  Finite, and the recurrence's answer, where the bounded form
    overflows (a decay of -30 a step past the chunk's middle) or cancels
    to nothing (repeated keys: the power series' terms reach 1e13)."""
    q, k, v, g, b = _kernel_inputs(T, repeat=repeat, beta=beta)
    o, S = jax.jit(lambda *a: kda.kda_scan(*a, chunk=chunk, unbounded=True)
                   )(q, k, v, g, b)
    want_o, want_S = kda.kda_recurrence(q[0], k[0], v[0], g[0], b[0])
    assert bool(jnp.all(jnp.isfinite(o)) & jnp.all(jnp.isfinite(S)))
    assert _rel(o[0], want_o) < 5e-5 and _rel(S[0], want_S) < 5e-5


def test_the_bounded_form_is_not_this_familys(params):
    """What the exact form is for: on the same inputs the form a bounded
    gate allows (the one a call that says nothing gets) is far off (its
    chunk's middle anchor overflows under -30 a step), and a gate CLAMPED
    at GLM's -5 is another recurrence."""
    q, k, v, g, b = _kernel_inputs(96)
    want_o, _ = kda.kda_recurrence(q[0], k[0], v[0], g[0], b[0])
    o, _ = kda.kda_scan(q, k, v, g, b, chunk=32)
    assert not bool(jnp.all(jnp.isfinite(o))) or _rel(o[0], want_o) > 1.0
    clamped, _ = kda.kda_recurrence(q[0], k[0], v[0], jnp.maximum(g[0], -5.0),
                                    b[0])
    assert _rel(clamped, want_o) > CONTROL


def test_kda_update_is_one_recurrence_step_at_beta_near_2():
    """Four lanes of which two hold a request, repeated keys, beta 1.99,
    -30 in some channels: a state's component along k changes sign."""
    q, k, v, g, b = (a[0] for a in _kernel_inputs(4, H=4, seed=3))
    state = jax.random.normal(jax.random.PRNGKey(9), (2, 4, 4, 16, 16))
    live = jnp.asarray([False, True, False, True])
    lanes, count = ssm.live_lanes(live)
    new, y = jax.jit(kda.kda_update)(state, jnp.int32(1), lanes, count,
                                     q, k, v, g, b)
    for lane in (1, 3):
        sl = slice(lane, lane + 1)
        want_o, want_S = kda.kda_recurrence(q[sl], k[sl], v[sl], g[sl],
                                            b[sl], state[1, lane])
        assert _rel(new[1, lane], want_S) < 1e-6
        assert _rel(y[lane], want_o[0]) < 1e-5
        along = jnp.einsum("hd,hdv->hv", k[lane], state[1, lane])
        after = jnp.einsum("hd,hdv->hv", k[lane], new[1, lane]
                           - b[lane][:, None, None] * k[lane][:, :, None]
                           * v[lane][:, None, :])
        assert float(jnp.mean(jnp.sign(along) != jnp.sign(after))) > 0.5
    assert bool(jnp.all(new[1, 0] == state[1, 0])
                & jnp.all(new[0] == state[0]))
    assert bool(jnp.all(jnp.isfinite(new)) & jnp.all(jnp.isfinite(y)))


# ----------------------------------- (b) the share ties to the model
def _range_params(params, lo, hi):
    """The parameters a chip that holds experts lo..hi holds."""
    layers = [dict(lp, w13=lp["w13"][lo:hi], w2=lp["w2"][lo:hi])
              for lp in params["layers"]]
    return dict(params, layers=layers)


@pytest.mark.parametrize("ranges", [
    [(i, i + 1) for i in range(8)],         # eight chips, as deployed
    [(0, 8)],                               # one chip holds them all
    [(0, 3), (3, 8)]])
def test_the_expert_ranges_parts_add_up_to_the_uncut_layer(params, ranges):
    """What each range's chip computes, with the shared expert (which
    every chip computes alike) counted once, adds up to the UNCUT
    reference's layer."""
    lid = 1
    lp = params["layers"][lid]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.dim))
    with jax.default_matmul_precision("highest"):
        want_routed, want_shared, _ = ref.ff(x, lp, MODEL)
    h = solar_open2.rmsnorm(x, lp["norm2"], CFG.norm_eps)
    shared = routed.shared_ffn(h, lp, CFG.dtype)
    assert _gap(shared, want_shared) < TOL
    total, computed = 0.0, 0
    for lo, hi in ranges:
        cfg = dataclasses.replace(CFG, experts_held=(lo, hi))
        held = _range_params(params, lo, hi)["layers"][lid]
        y, counts = solar_open2.ffn(x, held, cfg)
        total = total + (y - x - shared)        # the chip's routed part
        computed += int(counts[2])
        # and the reference given the same share agrees with the chip
        with jax.default_matmul_precision("highest"):
            part, _, _ = ref.ff(x, held, dict(MODEL, experts_held=[lo, hi]))
        assert _gap(y - x - shared, part) < 5 * TOL
    assert computed == 24 * CFG.top_k
    assert _gap(total + shared, want_routed + want_shared) < TOL


def test_an_eighth_of_the_experts_served_equals_the_reference_of_the_share(
        params):
    """The cut as the benchmark runs it: experts 2..3 of 8 held, the
    router over all 8, prefill then decode against the reference given
    the same share."""
    params, cfg, model = _cut(params)     # the GQA layer and a KDA layer
    cfg = dataclasses.replace(cfg, experts_held=(2, 3))
    held = _range_params(params, 2, 3)
    model = dict(model, experts_held=[2, 3])
    seam = Seam(solar_open2, cfg)         # the share's programs, once
    assert _worst(held, held, cfg=cfg, model=model, seam=seam) < TOL
    # and it is no other share's
    assert _worst(held, _range_params(params, 4, 5), cfg=cfg,
                  model=dict(model, experts_held=[4, 5]), seam=seam) > CONTROL


# ------------------------------------- each piece, each order: controls
_GATE, _SCATTER = solar_open2.kda_gate, solar_open2.scatter_prefill_pages
_UPDATE = kda.kda_update


def _bounded_gate(h, lp, cfg):
    """GLM's form on the same weights: -5 sigmoid(exp(A_log) (.))."""
    _, beta = _GATE(h, lp, cfg)
    f = (h @ lp["wf1"]) @ lp["wf2"]
    A = jnp.repeat(jnp.exp(lp["A_log"]), cfg.kda_head_dim)
    g = -5.0 * jax.nn.sigmoid(A * (f + lp["dt_bias"]))
    return g.reshape(*h.shape[:-1], cfg.n_heads, cfg.kda_head_dim), beta


def _state_through_bf16(*a, **kw):
    new, y = _UPDATE(*a, **kw)
    return new.astype(jnp.bfloat16).astype(new.dtype), y


def _scatter_zero_state(cache, ks, vs, state, *a, **kw):
    return _SCATTER(cache, ks, vs, jax.tree.map(jnp.zeros_like, state),
                    *a, **kw)


def _cut(params, n=2):
    """(parameters, program config, the reference's model) of the first
    `n` layers: the GQA layer and a KDA layer, both routed."""
    return (dict(params, layers=params["layers"][:n]),
            dataclasses.replace(CFG, n_layers=n),
            MODEL)


@pytest.mark.parametrize("control", [
    "sound", "gate_of_the_bounded_form", "beta_without_its_factor_2",
    "gqa_gate_left_out", "gqa_gate_a_number_a_head",
    "lane_state_zeroed_at_admission", "state_through_bfloat16"])
def test_every_control_exceeds_the_tolerance(params, monkeypatch, control):
    """A control changes one equation of one kind of layer, and its patch
    has to be traced: it runs on the model cut to its first two layers
    (the GQA layer and a KDA layer), against the reference of the same
    cut."""
    params, cfg, model = _cut(params)
    served = params
    if control == "gate_of_the_bounded_form":
        monkeypatch.setattr(solar_open2, "kda_gate", _bounded_gate)
    elif control == "beta_without_its_factor_2":
        monkeypatch.setattr(solar_open2, "kda_gate", lambda h, lp, cfg: (
            _GATE(h, lp, cfg)[0], 0.5 * _GATE(h, lp, cfg)[1]))
    elif control == "gqa_gate_left_out":
        monkeypatch.setattr(solar_open2, "gqa_gate",
                            lambda o, h, lp, cfg: o.astype(cfg.dtype))
    elif control == "gqa_gate_a_number_a_head":
        monkeypatch.setattr(solar_open2, "gqa_gate", lambda o, h, lp, cfg: (
            o * jnp.repeat(jax.nn.sigmoid(
                (h @ lp["w_gate"])[..., ::cfg.head_dim]), cfg.head_dim, -1)))
    elif control == "lane_state_zeroed_at_admission":
        monkeypatch.setattr(solar_open2, "serve_scatter",
                            _scatter_zero_state)
    elif control == "state_through_bfloat16":
        monkeypatch.setattr(kda, "kda_update", _state_through_bf16)
    worst = _worst(served, params, cfg=cfg, model=model)
    if control == "sound":
        assert worst < TOL
    elif control == "state_through_bfloat16":
        # a rounding of 2**-9 of the state a step, over the eight steps
        # walked: far over the sound reading, under the other controls
        # (the benchmark's judge reads the state itself)
        assert worst > 10 * TOL
    else:
        assert worst > CONTROL


# ----------------------------------------------------- spec and presets
def test_the_spec_counts_the_state_and_the_parameters_of_the_cut():
    """The served cut's arithmetic (ISSUE 58): 13.0 MB of lane state a
    lane, a KDA mixer of 137.7 M, the GQA mixer of 109.1 M, and the
    planner's floor from streamed / multiplied."""
    cfg = dataclasses.replace(
        named_config("solar-open2-250b"), n_layers=4, gqa_layers=(0,),
        experts_held=(0, 40), vocab_size=24576)
    spec = solar_open2.serving_spec(cfg)
    assert spec.lane_state_layers == 3 and spec.routed_layers == 4
    assert spec.prefill_state_bytes == 3 * (64 * 128 * 128 * 4
                                            + 3 * 24576 * 2)
    kda_p = 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
    gqa_p = 3 * 4096 * 8192 + 2 * 4096 * 1024
    one = 3 * 4096 * 1280
    rest = 3 * kda_p + gqa_p + 4 * one + 4 * 4096 * 320
    assert (kda_p, gqa_p, one) == (137_625_600, 109_051_904, 15_728_640)
    assert spec.prefill_params == (rest + 4 * 40 * one,
                                   rest + 4 * 8 * 40 * one // 320)
    assert spec.caps == frozenset()
    assert set(spec.counters) >= {"ssm_lane_steps", "prefill_scan_chunks",
                                  "prefill_attn_blocks", "moe_assignments",
                                  "prefill_walked_tokens"}


def test_the_published_preset_is_the_published_model():
    cfg = named_config("solar-open2-250b")
    assert (cfg.n_layers, cfg.count("kda"), cfg.count("gqa")) == (48, 36, 12)
    assert cfg.layer_types[:5] == ("gqa", "kda", "kda", "kda", "gqa")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (64, 8, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.moe_ffn_dim) == (320, 8, 1280)
    assert not hasattr(cfg, "gate_lower_bound")


def test_a_model_with_lane_state_is_served_without_the_prefix_cache(params):
    # (engines that are refused at construction: nothing compiles)
    assert serving_model(CFG) is solar_open2
    with pytest.raises(ValueError, match="radix prefix hit cannot restore"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  prefix_cache=True)
    with pytest.raises(ValueError, match="no LoRA hooks"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  lora_slots=2, lora_rank=4)


def test_the_server_serves_the_preset_by_name():
    # an engine of its own: the preset as published (bfloat16), found by
    # its name and served through `LLMServer`
    srv = LLMServer("solar-open2-debug", max_batch=2, max_len=64,
                    page_size=PAGE)
    try:
        out = srv.engine.generate([5, 6, 7], max_new_tokens=5)
        assert len(out["tokens"]) == 5
        assert srv._prefix_client is None       # no demotion either
    finally:
        srv.engine.stop()
