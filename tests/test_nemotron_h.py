"""models/nemotron_h.py (layers that are a mixer OR a feed-forward part
alone: Mamba-2 in several groups, routed relu**2 experts in a latent
narrower than the stream, NoPE GQA attention) against the plain float32
reference the benchmark holds it to (`benchmarks/harness/refs/
nemotron_h.py`: the token-by-token recurrence, a dense loop over the
experts, importing nothing of the program): the prompt pass at a padded
bucket followed by paged decode through the pool and the lane state, the
ENGINE's own logits with lanes of different lengths reused and a dead
lane bit-unchanged (one engine run shared by the file's cases:
`family_contract`), the expert ranges' parts adding up to the uncut
layer, the gated norm by group, the routed layer's two forms against a
dense loop, the controls a sound comparison must fail, and the
counters."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_contract as contract  # rootdir-relative (no pkg)
from family_contract import gap as _gap, tokens as _tokens
from serving_reference import Seam, served_logits

from benchmarks.harness.refs import nemotron_h as ref
from ray_tpu.models import named_config, nemotron_h, routed, serving_model
from ray_tpu.ops import ssm
from ray_tpu.serve.llm import LLMEngine, LLMServer

# float32 weights: the served path and the reference then differ by
# summation order alone, so the bound is tight and every control stands
# far outside it
CFG = dataclasses.replace(named_config("nemotron-h-debug"),
                          dtype=jnp.float32)
MODEL = dict(hybrid_override_pattern=CFG.pattern, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
             mamba_head_dim=16, n_groups=2, ssm_state_size=16,
             conv_kernel=4, layer_norm_epsilon=1e-5, num_experts_per_tok=3,
             norm_topk_prob=True, routed_scaling_factor=5.0,
             experts_held=[0, 8], router_experts=8)
TOL = 2e-5          # float32 against float32, of the logits' scale
CONTROL = 2e-3      # what every control must exceed, 100 x TOL
PAGE, K = 16, 4
N_MAMBA, N_MOE = CFG.count("M"), CFG.count("E")
MOE_LAYER = CFG.pattern.index("E")


# The sound program's seam, compiled once a shape for the file (true
# lengths are arguments), and the reference at ONE length (54 is the
# longest sequence a case reads: 40 prompt tokens and 14 served).
SOUND = Seam(nemotron_h, CFG)
_ref_logits = contract.one_length(
    lambda p, seq: ref.logits(p, seq, MODEL), 56)


@pytest.fixture(scope="module")
def params():
    return nemotron_h.init_params(jax.random.PRNGKey(7), CFG)


def _worst(params_served, params_ref, n=21, bucket=32, follow=2 * K,
           cfg=CFG, model=MODEL, seam=None):
    """Without a `seam`, through programs traced anew (a control's patch
    has to be traced)."""
    prompt, nxt = _tokens(n, 1), _tokens(follow, 2)
    got = served_logits(seam or Seam(nemotron_h, cfg), params_served, cfg,
                        prompt, nxt, bucket, page=PAGE, k=K)
    seq = list(prompt) + list(nxt)
    if seam is SOUND:
        return _gap(got, _ref_logits(params_ref, seq, last=follow + 1))
    return _gap(got, ref.logits(params_ref, seq, model, last=follow + 1))


# ------------------------- (a) prefill, then decode, against the forward
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
    got = nemotron_h.project_logits(params, h[PREFILL_LENS.index(n), :n])
    assert _gap(got, _ref_logits(params, toks[:n])) < TOL


@pytest.mark.parametrize("n,bucket", [(21, 32), (1, 32), (2, 32), (3, 32),
                                      (33, 64), (9, 32)])
def test_padded_prefill_then_paged_decode_equals_the_reference(
        params, n, bucket):
    """true_len a multiple of nothing (not of the chunk of 8 either): the
    lane state must be the state and the convolution rows at the TRUE
    length (zeros where the prompt is shorter than three), and two
    windows of K steps carry them on."""
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
    for kind, lp in zip(CFG.pattern, params["layers"]):
        x, info = ref.layer(x, lp, kind, MODEL)
        for name in want:
            if name in info:
                want[name].append(info[name])
    assert len(state["ssm"]) == len(state["conv"]) == N_MAMBA
    for got, exp in zip(state["ssm"], want["state"]):
        assert got.shape == (1, 16, 64)
        assert _gap(got[0], exp) < 1e-5
    for got, exp in zip(state["conv"], want["conv"]):
        assert got.shape == (1, 3, CFG.conv_dim)
        assert _gap(got[0], exp) < 1e-5
    for got, exp in zip(ks + vs, want["k"] + want["v"]):
        assert _gap(got[0, :13], exp) < 1e-5
    # every position below the true lengths (the program's two rows: 32
    # and 13) chose top_k of the 8 experts, all held
    assert counts.shape == (N_MOE, routed.COUNTS)
    assert counts[:, 2].tolist() == [(32 + 13) * CFG.top_k] * N_MOE


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
        nemotron_h, CFG, params, lanes=LANES, kv_pages=12, page=PAGE, k=K,
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
    # the counters: live lanes x K x Mamba layers a window; the chunks of
    # 8 positions below the true lengths; every assignment of a live lane
    # computed, for every expert is held
    prompts = served["prompts"] + [served["first_prompt"]]
    loop = st["loop"]
    assert loop["ssm_lane_steps"] == loop["lane_steps_live"] * N_MAMBA
    assert loop["prefill_scan_chunks"] == N_MAMBA * sum(
        -(-len(p) // 8) for p in prompts)
    assert loop["prefill_scan_chunks"] <= loop["prefill_scan_chunks_dense"]
    assert loop["moe_assignments"] == (loop["lane_steps_live"] * N_MOE
                                       * CFG.top_k)
    assert loop["moe_assignments_absent"] == 0
    assert loop["prefill_moe_assignments"] == N_MOE * CFG.top_k * sum(
        len(p) for p in prompts)
    lane = st["lane_state"]
    assert lane["layers"] == N_MAMBA
    assert lane["by_kind"] == {
        "conv": N_MAMBA * LANES * 3 * CFG.conv_dim * 4,
        "ssm": N_MAMBA * LANES * 16 * 64 * 4}
    assert lane["prefix_cache"] == "off: lane state"
    assert st["cache"]["kind"] == "kv"


def test_a_dead_lanes_state_is_bit_unchanged_by_a_decode_window(served):
    """The run's first request (9 + 9 tokens) alone in an engine of two
    lanes whose state was marked: the windows' steps update its lane's
    state matrices and convolution rows, every one, and leave the other
    lane's as they were, bit for bit."""
    for name in ("ssm", "conv"):
        (used,) = contract.lanes_written(
            served, lambda s: np.moveaxis(s[name], 1, 0))
        before, after = (s[name] for s in served["state"])
        assert (after[:, used] != before[:, used]).any(
            axis=(-1, -2)).all(), name


# ----------------------------------- (b) the share ties to the model
def _range_params(params, lo, hi):
    """The parameters a chip that holds experts lo..hi holds."""
    layers = [dict(lp, w1=lp["w1"][lo:hi], w2=lp["w2"][lo:hi])
              if "w1" in lp else lp for lp in params["layers"]]
    return dict(params, layers=layers)


@pytest.mark.parametrize("ranges", [
    [(0, 2), (2, 4), (4, 6), (6, 8)],       # four chips, as deployed
    [(0, 8)],                               # one chip holds them all
    [(0, 1), (1, 8)]])
def test_the_expert_ranges_parts_add_up_to_the_uncut_layer(params, ranges):
    """What each range's chip computes after W_fc2, with the shared
    expert (which every chip computes alike) counted once, adds up to
    the UNCUT reference's `E` layer."""
    lp = params["layers"][MOE_LAYER]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.dim))
    want, info = ref.layer(x, lp, "E", MODEL)
    h = nemotron_h.rmsnorm(x, lp["norm"], CFG.norm_eps)
    with jax.named_scope("shared"):
        shared = routed.relu2(h @ lp["sw1"]) @ lp["sw2"]
    assert _gap(shared, info["shared"]) < TOL
    total, computed = 0.0, 0
    for lo, hi in ranges:
        cfg = dataclasses.replace(CFG, experts_held=(lo, hi))
        held = _range_params(params, lo, hi)["layers"][MOE_LAYER]
        y, counts = nemotron_h.moe(h, held, cfg)
        total = total + (y - shared)            # the chip's routed part
        computed += int(counts[2])
        # and the reference given the same share agrees with the chip
        _, part = ref.layer(x, held, "E", dict(MODEL,
                                               experts_held=[lo, hi]))
        assert _gap(y - shared, part["routed"]) < 5 * TOL
    assert computed == 24 * CFG.top_k
    assert _gap(x + total + shared, want) < TOL


def test_a_quarter_of_the_experts_served_equals_the_reference_of_the_share(
        params):
    """The cut as the benchmark runs it: experts 2..4 of 8 held, the
    router over all 8, prefill then decode against the reference given
    the same share."""
    cfg = dataclasses.replace(CFG, experts_held=(2, 4))
    held = _range_params(params, 2, 4)
    model = dict(MODEL, experts_held=[2, 4])
    seam = Seam(nemotron_h, cfg)         # the share's programs, once
    assert _worst(held, held, cfg=cfg, model=model, seam=seam) < TOL
    # and it is no other share's
    assert _worst(held, _range_params(params, 4, 6), cfg=cfg,
                  model=dict(MODEL, experts_held=[4, 6]), seam=seam) > CONTROL


# --------------------------------------- (d) the gated norm, by group
def test_the_gated_norm_is_taken_over_each_group():
    k = jax.random.split(jax.random.PRNGKey(3), 2)
    # the groups at different scales: a norm over the row would flatten
    # the small group
    y = jax.random.normal(k[0], (5, CFG.inner)) * jnp.repeat(
        jnp.asarray([1.0, 30.0]), CFG.inner // 2)
    z = jax.random.normal(k[1], (5, CFG.inner))
    w = 1.0 + 0.1 * jax.random.normal(k[0], (CFG.inner,))
    got = nemotron_h.gated_group_norm(y, z, w, CFG)
    assert _gap(got, ref.gated_norm(y, z, w, MODEL)) < 1e-6
    whole = nemotron_h.rmsnorm(y * jax.nn.silu(z), w, CFG.norm_eps)
    assert _gap(whole, got) > 0.5
    # each group's columns have unit mean square before the weight
    unit = np.asarray(got / w).reshape(5, CFG.ssm_groups, -1)
    assert np.allclose((unit ** 2).mean(-1), 1.0, atol=1e-3)


# ------------------------------- (e) the routed layer's two forms
def _dense_loop(h2, router_rows, lp, cfg, form, experts, live=None):
    """Every row through every held expert, masked by the router's
    weight: no sort, no gather, no grouped matmul."""
    idx, wts = routed.route(router_rows, lp, cfg)
    lo, hi = experts
    out = jnp.zeros((h2.shape[0], lp["w2"].shape[-1]))
    for e in range(lo, hi):
        w = jnp.sum(jnp.where(idx == e, wts, 0.0), axis=-1)
        if live is not None:
            w = jnp.where(live, w, 0.0)
        if form == "relu2":
            y = jnp.square(jax.nn.relu(h2 @ lp["w1"][e - lo])) \
                @ lp["w2"][e - lo]
        else:
            a = h2 @ lp["w13"][e - lo]
            f = a.shape[-1] // 2
            y = (jax.nn.silu(a[:, :f]) * a[:, f:]) @ lp["w2"][e - lo]
        out = out + w[:, None] * y
    return out


def _routed_case(form, experts, seed=0):
    """(h2 the experts multiply, the rows the router reads, lp, cfg) at
    8 experts top 3: the relu**2 form reads a router input TWICE as wide
    as the experts' rows."""
    cfg = dataclasses.replace(CFG, experts_held=experts)
    d, f, G = 32, 48, experts[1] - experts[0]
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    wide = jax.random.normal(k[0], (40, 64))
    lp = {"router": jax.random.normal(k[1], (64, 8)) / 8,
          "expert_bias": 0.02 * jax.random.normal(k[2], (8,)),
          "w2": jax.random.normal(k[3], (G, f, d)) / 7}
    if form == "relu2":
        lp["w1"] = jax.random.normal(k[4], (G, d, f)) / 6
        return wide[:, :d] + 1.0, wide, lp, cfg
    lp["w13"] = jax.random.normal(k[4], (G, 64, 2 * f)) / 8
    lp["w2"] = jax.random.normal(k[3], (G, f, 64)) / 7
    return wide, None, lp, cfg


@pytest.mark.parametrize("block", [None, 16, 64])
@pytest.mark.parametrize("experts", [(0, 8), (2, 5)])
@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_routed_ffn_equals_a_dense_loop_over_the_experts(
        monkeypatch, form, experts, block):
    """Both forms of an expert, the whole list or walked in blocks
    (`BLOCK` patched below the 120 assignments), a range held, rows dead:
    `routed_ffn` against a loop that multiplies every row by every
    expert.  The relu**2 form's router reads rows the experts never
    see."""
    h2, wide, lp, cfg = _routed_case(form, experts)
    live = jnp.arange(40) % 5 != 0
    if block:
        monkeypatch.setattr(routed, "BLOCK", block)
    kw = ({"router_rows": wide, "expert_fn": routed.relu2_experts}
          if form == "relu2" else {})
    got, counts = routed.routed_ffn(h2, lp, cfg, live, experts, **kw)
    want = _dense_loop(h2, h2 if wide is None else wide, lp, cfg, form,
                       experts, live)
    assert got.shape == want.shape
    assert _gap(got, want) < TOL
    idx, _ = routed.route(h2 if wide is None else wide, lp, cfg)
    held = ((idx >= experts[0]) & (idx < experts[1])
            & live[:, None]).sum()
    assert int(counts[2]) == int(held)
    assert int(counts[4]) == -(-int(held) // (block or 120)) * (block or 120)


def test_the_router_reads_its_own_rows_and_not_the_experts(params):
    """The same latent rows under two router inputs are routed
    differently; the same router input over two latents is routed
    alike."""
    h2, wide, lp, cfg = _routed_case("relu2", (0, 8))
    other = jax.random.normal(jax.random.PRNGKey(9), wide.shape)
    kw = {"expert_fn": routed.relu2_experts}
    a, _ = routed.routed_ffn(h2, lp, cfg, router_rows=wide, **kw)
    b, _ = routed.routed_ffn(h2, lp, cfg, router_rows=other, **kw)
    assert _gap(a, b) > 0.1
    want = _dense_loop(h2, other, lp, cfg, "relu2", (0, 8))
    assert _gap(b, want) < TOL


# ------------------------------------- each piece, each order: controls
def _whole_row_norm(y, z, weight, cfg):
    return nemotron_h.rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)),
                              weight, cfg.norm_eps).astype(cfg.dtype)


def _norm_before_gate(y, z, weight, cfg):
    """The group's RMSNorm of y alone, gated afterwards."""
    g = y.reshape(*y.shape[:-1], cfg.ssm_groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + cfg.norm_eps)
    return (g.reshape(y.shape) * weight.astype(jnp.float32)
            * jax.nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)


def _route_top_k_less_one(h2, lp, cfg):
    """The k - 1 largest, normalised over themselves; the k-th column
    names the first again under a weight of 0."""
    idx, wts = _ROUTE(h2, lp, dataclasses.replace(cfg, top_k=cfg.top_k - 1))
    return (jnp.concatenate([idx, idx[:, :1]], axis=1),
            jnp.concatenate([wts, jnp.zeros_like(wts[:, :1])], axis=1))


def _route_on_the_latent(c, h2, lp, cfg, live=None):
    """The router reading the experts' rows (through a router cut to
    their width)."""
    cut = dict(lp, router=lp["router"][:c.shape[1]])
    return routed.routed_ffn(c, cut, cfg, live, cfg.experts_held,
                             expert_fn=routed.relu2_experts)


def _gated_experts(rows, lp, sizes, cfg):
    """silu in place of relu**2."""
    from ray_tpu.ops.grouped_matmul import gmm

    return gmm(jax.nn.silu(gmm(rows, lp["w1"], sizes)), lp["w2"], sizes)


def _one_group(act, cfg):
    x, B, C = _SPLIT(act, cfg)
    return x, jnp.repeat(B[..., :1, :], cfg.ssm_groups, -2), \
        jnp.repeat(C[..., :1, :], cfg.ssm_groups, -2)


def _scatter_zero_state(cache, ks, vs, state, *a, **kw):
    return _SCATTER(cache, ks, vs, jax.tree.map(jnp.zeros_like, state),
                    *a, **kw)


def _state_through_bf16(*a, **kw):
    new, y = _UPDATE(*a, **kw)
    return new.astype(jnp.bfloat16).astype(new.dtype), y


def _dt_unmasked(h, lp, cfg, true_lens):
    full = jnp.full_like(true_lens, h.shape[1])
    got = _SCAN_INPUTS(h, lp, cfg, true_lens)
    return got[:2] + (_SCAN_INPUTS(h, lp, cfg, full)[2],) + got[3:]


_ROUTE, _SPLIT = nemotron_h.route, nemotron_h._split
_SCATTER, _UPDATE = nemotron_h.scatter_prefill_pages, ssm.ssm_update
_SCAN_INPUTS = nemotron_h.scan_inputs


# A control changes one equation of one kind of layer, and its patch has
# to be traced: it runs on the model cut to the first layers that still
# hold the layer it changes ("ME": a Mamba mixer and a routed layer,
# which every control but two needs; "MEM" for the second Mamba layer
# skipped; "MEM*" for the attention layer's scale), against the
# reference of the same cut.
DEPTH = {"a_mamba_layer_skipped": 3, "attention_scale_1": 4}


def _cut(params, n):
    """(parameters, program config, the reference's model) of the first
    `n` layers."""
    return (dict(params, layers=params["layers"][:n]),
            dataclasses.replace(CFG, pattern=CFG.pattern[:n]),
            dict(MODEL, hybrid_override_pattern=CFG.pattern[:n]))


def _without(params, kind, name, nth=0):
    """The parameters with `name` of the nth layer of `kind` zeroed."""
    lid = [i for i, c in enumerate(CFG.pattern) if c == kind][nth]
    layers = list(params["layers"])
    layers[lid] = dict(layers[lid],
                       **{name: jnp.zeros_like(layers[lid][name])})
    return dict(params, layers=layers)


@pytest.mark.parametrize("control", [
    "sound", "norm_over_the_whole_row", "norm_before_gate",
    "route_top_k_less_one", "router_reads_the_latent",
    "silu_in_place_of_relu2", "every_head_reads_group_0",
    "shared_expert_left_out", "latent_up_left_out", "D_left_out",
    "attention_scale_1", "scaling_factor_left_out",
    "dt_unmasked_past_the_true_length", "lane_state_zeroed_at_admission",
    "a_mamba_layer_skipped", "state_through_bfloat16"])
def test_every_control_exceeds_the_tolerance(params, monkeypatch, control):
    if control == "sound":       # the whole model, the file's seam
        assert _worst(params, params, seam=SOUND) < TOL
    params, cfg, model = _cut(params, DEPTH.get(control, 2))
    served = params
    if control == "norm_over_the_whole_row":
        monkeypatch.setattr(nemotron_h, "gated_group_norm", _whole_row_norm)
    elif control == "norm_before_gate":
        monkeypatch.setattr(nemotron_h, "gated_group_norm",
                            _norm_before_gate)
    elif control == "route_top_k_less_one":
        monkeypatch.setattr(nemotron_h, "route", _route_top_k_less_one)
    elif control == "router_reads_the_latent":
        monkeypatch.setattr(nemotron_h, "routed_ffn", _route_on_the_latent)
    elif control == "silu_in_place_of_relu2":
        monkeypatch.setattr(routed, "relu2_experts", _gated_experts)
    elif control == "every_head_reads_group_0":
        monkeypatch.setattr(nemotron_h, "_split", _one_group)
    elif control == "shared_expert_left_out":
        served = _without(params, "E", "sw2")
    elif control == "latent_up_left_out":
        served = _without(params, "E", "fc2")
    elif control == "D_left_out":
        served = _without(params, "M", "D")
    elif control == "attention_scale_1":
        monkeypatch.setattr(nemotron_h, "softmax_scale", lambda cfg: 1.0)
    elif control == "scaling_factor_left_out":
        cfg = dataclasses.replace(cfg, routed_scaling=1.0)
    elif control == "dt_unmasked_past_the_true_length":
        monkeypatch.setattr(nemotron_h, "scan_inputs", _dt_unmasked)
    elif control == "lane_state_zeroed_at_admission":
        monkeypatch.setattr(nemotron_h, "serve_scatter",
                            _scatter_zero_state)
    elif control == "a_mamba_layer_skipped":
        served = _without(params, "M", "out_proj", 1)
    elif control == "state_through_bfloat16":
        monkeypatch.setattr(ssm, "ssm_update", _state_through_bf16)
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
    """The served cut's arithmetic (ISSUE 48): 4.26 MB of lane state a
    lane a layer, and the planner's floor from streamed / multiplied."""
    cfg = dataclasses.replace(
        named_config("nemotron-3-super-120b-a12b"), pattern="MEMEMEM*EME",
        experts_held=(0, 128), vocab_size=32768)
    spec = nemotron_h.serving_spec(cfg)
    assert spec.lane_state_layers == 5 and spec.routed_layers == 5
    assert spec.prefill_state_bytes == 5 * (128 * 8192 * 4
                                            + 3 * 10240 * 2)
    mamba = 4096 * (8192 + 10240 + 128) + 8192 * 4096
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256
    moe = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    one = 2 * 1024 * 2688
    rest = 5 * mamba + attn + 5 * moe
    assert (mamba, attn, moe, one) == (109_576_192, 35_651_584,
                                       54_525_952, 5_505_024)
    assert spec.prefill_params == (rest + 5 * 128 * one,
                                   rest + 5 * 22 * 128 * one // 512)
    assert spec.caps == frozenset()


def test_the_published_preset_is_the_published_model():
    cfg = named_config("nemotron-3-super-120b-a12b")
    assert (cfg.n_layers, cfg.count("M"), cfg.count("E"), cfg.count("*")) \
        == (88, 40, 40, 8)
    assert cfg.pattern[:11] == "MEMEMEM*EME"
    assert (cfg.inner, cfg.conv_dim) == (8192, 10240)
    assert cfg.inner + cfg.conv_dim + cfg.ssm_heads == 145 * 128


def test_a_model_with_lane_state_is_served_without_the_prefix_cache(params):
    # (engines that are refused at construction: nothing compiles)
    assert serving_model(CFG) is nemotron_h
    with pytest.raises(ValueError, match="radix prefix hit cannot restore"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  prefix_cache=True)
    with pytest.raises(ValueError, match="no LoRA hooks"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  lora_slots=2, lora_rank=4)


def test_the_server_serves_the_preset_by_name():
    # an engine of its own: the preset as published (bfloat16), found by
    # its name and served through `LLMServer`
    srv = LLMServer("nemotron-h-debug", max_batch=2, max_len=64,
                    page_size=PAGE)
    try:
        out = srv.engine.generate([5, 6, 7], max_new_tokens=5)
        assert len(out["tokens"]) == 5
        assert srv._prefix_client is None       # no demotion either
    finally:
        srv.engine.stop()
