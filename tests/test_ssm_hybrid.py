"""models/ssm_hybrid.py (Mamba-2 state-space layers beside NoPE GQA
attention, Granite's scalars) and ops/ssm.py against the plain float32
reference the benchmark holds them to (`benchmarks/harness/refs/
ssm_hybrid.py`: the token-by-token recurrence, importing nothing of the
program): the chunked scan at lengths that are no multiple of the chunk,
the one-step kernel with idle lanes, the prompt pass at a padded bucket
followed by paged decode through the pool and the lane state, the ENGINE's
own logits with lanes reused and more requests than lanes (one engine run
shared by the file's cases: `family_contract`), the controls a sound
comparison must fail, and the counters."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_contract as contract  # rootdir-relative (no pkg)
from family_contract import gap as _gap, tokens as _tokens
from serving_reference import Seam, served_logits

from benchmarks.harness.refs import nemotron_h as ref_groups
from benchmarks.harness.refs import ssm_hybrid as ref
from ray_tpu.models import named_config, serving_model, ssm_hybrid
from ray_tpu.ops import paged_attention, ssm
from ray_tpu.serve.llm import LLMEngine, LLMServer
from ray_tpu.serve.prefill_plan import PREFILL_MAX_STATE_BYTES, plan_wave

# float32 weights: the served path and the reference then differ by
# summation order alone, so the bound is tight and every control stands
# far outside it
# (and scores at head_dim**-0.5, a given number all the same: at the
# preset's 1/64 the softmax over 30 keys is flat and no control of the
# attention layer could part from the reference)
CFG = dataclasses.replace(named_config("ssm-hybrid-debug"),
                          dtype=jnp.float32, attn_scale=0.25)
MODEL = dict(num_attention_heads=4, num_key_value_heads=2,
             rms_norm_eps=1e-5, attention_multiplier=CFG.attn_scale,
             embedding_multiplier=12, residual_multiplier=0.22,
             logits_scaling=8, mamba_n_heads=4, mamba_d_head=16,
             mamba_d_state=16, mamba_d_conv=4,
             layer_types=list(CFG.layer_types))
TOL = 2e-5          # float32 against float32, of the logits' scale
CONTROL = 2e-3      # what every control must exceed, 100 x TOL


# (`_gap`: a share of the reference's largest logit; the embedding is
# drawn small, so the logits are of scale 1e-2: `ssm_hybrid.init_params`)
PAGE, K = 16, 4
N_MAMBA = CFG.layer_types.count("mamba")
# The sound program's seam, compiled once a shape for the file (true
# lengths are arguments), and the reference at ONE length (54 is the
# longest sequence a case reads: 40 prompt tokens and 14 served).
SOUND = Seam(ssm_hybrid, CFG)
_ref_logits = contract.one_length(
    lambda p, seq: ref.logits(p, seq, MODEL), 56)


@pytest.fixture(scope="module")
def params():
    return ssm_hybrid.init_params(jax.random.PRNGKey(7), CFG)


def _scan_inputs(b, T, H=4, P=16, N=16, lens=None, seed=0, G=1):
    """B and C [b, T, G, N]: `ssd_scan` takes them a group of heads."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (b, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, T, H)) - 2)
    if lens is not None:
        dt = jnp.where(jnp.arange(T)[None, :, None]
                       < jnp.asarray(lens)[:, None, None], dt, 0.0)
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    B = jax.random.normal(k[3], (b, T, G, N))
    C = jax.random.normal(k[4], (b, T, G, N))
    return x, dt, A, B, C


def _recurrence(x, dt, A, B, C):
    """The recurrence token by token for ONE row (the plain references'
    `lax.scan` over positions: granite's for one group, the grouped
    model's for more), in `ssd_scan`'s shapes."""
    if B.shape[2] == 1:
        y, h = ref.recurrence(x[0], dt[0], A, B[0, :, 0], C[0, :, 0])
    else:
        y, h = ref_groups.recurrence(x[0], dt[0], A, B[0], C[0])
    return y[None], h[None]


# ------------------------------------------ (a) the chunked scan, ops/ssm
@pytest.mark.parametrize("T,chunk,lens,H,G", [
    (20, 8, [13, 20], 4, 1),    # no multiple of the chunk, right padding
    (32, 8, [1, 31], 4, 1),     # a row of one token
    (5, 8, [5, 3], 4, 1),       # a bucket under one chunk: one short chunk
    (64, 16, [64, 17], 4, 1),
    (20, 8, [13, 20], 16, 8),   # eight groups of two heads
    (32, 8, [1, 31], 4, 2),
    (64, 16, [64, 17], 8, 8),   # a head a group
    (5, 8, [5, 3], 16, 8),
    # the served SHAPE of the walk, 64 chunks a row (the 1 x 8192 program
    # at a chunk of 128): one token, a length inside a chunk, the whole row
    (512, 8, [1, 300, 512], 16, 8),
    (512, 8, [1, 300, 512], 4, 1),
])
def test_ssd_scan_equals_the_recurrence_at_the_true_length(T, chunk, lens,
                                                           H, G):
    x, dt, A, B, C = _scan_inputs(len(lens), T, H=H, lens=lens, G=G)
    y, h = ssm.ssd_scan(x, dt, A, B, C, chunk)
    for row, n in enumerate(lens):
        cut = [a[row:row + 1, :n] for a in (x, dt)] + [A] \
            + [a[row:row + 1, :n] for a in (B, C)]
        want_y, want_h = _recurrence(*cut)
        scale = float(jnp.max(jnp.abs(want_y)))
        assert float(jnp.max(jnp.abs(y[row, :n] - want_y[0]))) < 1e-5 * scale
        assert float(jnp.max(jnp.abs(h[row] - want_h[0]))) \
            < 1e-5 * float(jnp.max(jnp.abs(want_h)))


def _ssd_scan_one_group(x, dt, A, B, C, chunk: int):
    """`ssd_scan` as it stood while every head shared ONE B and C ([b, T,
    N]; PR 39 to PR 47) and a `lax.scan` over the chunks did a chunk's
    whole work and stacked its y (to PR 48), kept here to hold the grouped,
    batched form to it."""
    F32, _HI = jnp.float32, jax.lax.Precision.HIGHEST
    b, T, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, B, C))
    nc = (T + pad) // Q

    def chunks(a):
        return jnp.moveaxis(a.reshape(b, nc, Q, *a.shape[2:]), 1, 0)

    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def step(h, xs):
        xc, dtc, Bc, Cc = xs
        cs = jnp.cumsum(dtc * A, axis=1)
        csh = jnp.moveaxis(cs, 2, 1)
        seg = csh[:, :, :, None] - csh[:, :, None, :]
        Lm = jnp.exp(jnp.where(tri, seg, -jnp.inf))
        G = jnp.einsum("bin,bjn->bij", Cc, Bc, preferred_element_type=F32)
        xdt = xc.astype(F32) * dtc[..., None]
        y = jnp.einsum("bhij,bjhp->bihp", (G[:, None] * Lm).astype(x.dtype),
                       xdt.astype(x.dtype), preferred_element_type=F32)
        hh = h.reshape(b, N, H, P)
        y += jnp.einsum("bin,bnhp->bihp", Cc.astype(F32), hh,
                        preferred_element_type=F32) * jnp.exp(cs)[..., None]
        to_end = jnp.exp(cs[:, -1:, :] - cs)
        hh = (jnp.exp(cs[:, -1])[:, None, :, None] * hh
              + jnp.einsum("bjn,bjhp->bnhp", Bc.astype(F32),
                           xdt * to_end[..., None], precision=_HI,
                           preferred_element_type=F32))
        return hh.reshape(b, N, H * P), y

    h, ys = jax.lax.scan(step, jnp.zeros((b, N, H * P), F32),
                         tuple(chunks(a) for a in (x, dt, B, C)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * Q, H, P)
    return y[:, :T], h


F32_SUM = 1e-6     # float32 sums in another order, of the largest value
BF16_EPS = 2.0 ** -8    # one rounding of a bfloat16 operand


@pytest.mark.parametrize("T,chunk,lens,dtype,y_tol,h_tol", [
    (20, 8, [13, 20], jnp.float32, F32_SUM, F32_SUM),
    (32, 8, [1, 31], jnp.float32, F32_SUM, F32_SUM),
    # one chunk: no state comes in, so y is the product inside the chunk
    # alone and keeps its bits; the state is the batched product's
    (5, 8, [5, 3], jnp.float32, 0.0, F32_SUM),
    (32, 8, [32, 9], jnp.bfloat16, BF16_EPS, BF16_EPS)])
def test_ssd_scan_with_one_group_is_the_one_group_form_bit_for_bit(
        T, chunk, lens, dtype, y_tol, h_tol):
    """Granite's call (G = 1, the debug preset's 4 heads of 16 over a
    state of 16): the grouped scan, its products batched over the chunks
    (PR 49), against the one-group scan that walked them in a loop.  The
    same products on the same operands in the same precisions; a batched
    product sums in another order on this backend, so a case is held to
    `==` where the bits still agree and to a sum's rounding where not."""
    x, dt, A, B, C = _scan_inputs(2, T, lens=lens)
    x, B, C = (a.astype(dtype) for a in (x, B, C))
    y, h = ssm.ssd_scan(x, dt, A, B, C, chunk)
    y1, h1 = _ssd_scan_one_group(x, dt, A, B[:, :, 0], C[:, :, 0], chunk)
    for got, want, tol in ((y, y1, y_tol), (h, h1, h_tol)):
        assert float(jnp.max(jnp.abs(got - want))) \
            <= tol * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("T,chunk,G,loops", [
    (8, 8, 8, False), (5, 8, 8, False), (8, 8, 1, False), (16, 8, 8, True)])
def test_ssd_scan_of_one_chunk_lowers_to_a_program_without_a_loop(
        T, chunk, G, loops):
    """Every served program of at most one chunk (granite's of <= 256
    positions): nothing is carried, so nothing loops; two chunks do."""
    x, dt, A, B, C = _scan_inputs(2, T, H=8, G=G)
    text = jax.jit(ssm.ssd_scan, static_argnums=5).lower(
        x, dt, A, B, C, chunk).as_text()
    assert ("while" in text) == loops


def test_ssd_scan_carries_the_state_between_chunks():
    """Three chunks of 8 against one chunk of 24: what a chunk hands the
    next (the decayed state, and its share of the next chunk's y) is the
    only difference between the two programs."""
    x, dt, A, B, C = _scan_inputs(1, 24)
    y, h = ssm.ssd_scan(x, dt, A, B, C, 8)
    y1, h1 = ssm.ssd_scan(x, dt, A, B, C, 24)
    want_y, want_h = _recurrence(x, dt, A, B, C)
    for got_y, got_h in ((y, h), (y1, h1)):
        assert float(jnp.max(jnp.abs(got_y - want_y))) < 1e-4
        assert float(jnp.max(jnp.abs(got_h - want_h))) < 1e-4


# -------------------------------------- (b) the one-step kernel, ops/ssm
def _update_inputs(nb=6, L=3, H=4, P=16, N=16, seed=1, G=1):
    """B and C [nb, G, N]: `ssm_update` takes them a group of heads."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(k[0], (L, nb, N, H * P))
    x = jax.random.normal(k[1], (nb, H * P))
    dt = jnp.repeat(jax.random.normal(k[2], (nb, H)), P, axis=1)
    A_log = jnp.repeat(jnp.log(jnp.arange(1.0, H + 1)), P)
    D = jnp.repeat(jax.random.normal(k[3], (H,)), P)
    return (state, x, dt, jax.random.normal(k[4], (nb, G, N)),
            jax.random.normal(k[5], (nb, G, N)), A_log, D)


@pytest.mark.parametrize("live,heads,G", [
    ([0, 1, 1, 0, 1, 0], 16, 1), ([1, 1, 1, 1, 1, 1], 4, 1),
    ([0, 0, 0, 0, 0, 1], 16, 1), ([0, 0, 0, 0, 0, 0], 4, 1),
    # groups: a step's block is one group's columns of one lane.  16
    # heads in 2 groups: a block of one register-wide tile; in 8: a
    # narrower tile; 64 heads in 8: a register-wide tile a group
    ([0, 1, 1, 0, 1, 0], 16, 2), ([1, 1, 1, 1, 1, 1], 16, 8),
    ([0, 1, 0, 0, 1, 1], 64, 8), ([0, 0, 0, 0, 0, 0], 16, 8),
    ([0, 0, 0, 1, 0, 0], 4, 4)])
def test_ssm_update_is_one_recurrence_step_and_leaves_idle_lanes(
        live, heads, G):
    # 16 heads of 16: a lane's block is two register-wide column tiles;
    # 4 heads: one narrower tile
    state, x, dt, B, C, A_log, D = _update_inputs(H=heads, G=G)
    live = jnp.asarray(live, bool)
    lanes, count = ssm.live_lanes(live)
    assert int(count) == int(live.sum())
    assert lanes[:int(count)].tolist() == np.flatnonzero(live).tolist()
    new, y = ssm.ssm_update(state, jnp.int32(1), lanes, count, x, dt, B, C,
                            A_log, D)
    d = jax.nn.softplus(dt)
    # a column reads its group's B and C: [nb, N, HP]
    per = state.shape[-1] // G
    Bc = jnp.repeat(jnp.moveaxis(B, 1, 2), per, axis=2)
    Cc = jnp.repeat(jnp.moveaxis(C, 1, 2), per, axis=2)
    want = (jnp.exp(d * -jnp.exp(A_log))[:, None] * state[1]
            + Bc * (d * x)[:, None])
    want_y = jnp.sum(Cc * want, axis=1) + D * x
    on, off = np.flatnonzero(live), np.flatnonzero(~live)
    assert float(jnp.max(jnp.abs(new[1][on] - want[on]), initial=0)) < 1e-5
    assert float(jnp.max(jnp.abs(y[on] - want_y[on]), initial=0)) < 1e-4
    # an idle lane's state is bit-unchanged, its y rows are 0, and no
    # other layer is touched
    assert bool(jnp.all(new[1][off] == state[1][off]))
    assert bool(jnp.all(y[off] == 0))
    assert bool(jnp.all(new[0] == state[0])) \
        and bool(jnp.all(new[2] == state[2]))


def _ssm_update_one_group(state, layer, lanes, count, x, dt, B, C, A_log, D):
    """`ssm_update`'s call as it stood while every head shared ONE B and C
    (a step a lane, the lane's whole [N, HP] block; PR 39 to PR 47)
    around the kernel body of today, kept here to hold the grouped call
    to its bits."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    F32 = jnp.float32
    L, nb, N, HP = state.shape
    tw = ssm.LANES if HP % ssm.LANES == 0 else HP
    state_map = lambda i, lanes, layer: (layer[0], lanes[i], 0, 0)  # noqa: E731
    row_map = lambda i, lanes, layer: (lanes[i], 0, 0)      # noqa: E731
    const_map = lambda i, lanes, layer: (0, 0)              # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(count,),
        in_specs=[pl.BlockSpec((1, 1, N, HP), state_map),
                  pl.BlockSpec((1, 1, HP), row_map),
                  pl.BlockSpec((1, 1, HP), row_map),
                  pl.BlockSpec((1, 1, N), row_map),
                  pl.BlockSpec((1, 1, N), row_map),
                  pl.BlockSpec((1, HP), const_map),
                  pl.BlockSpec((1, HP), const_map)],
        out_specs=[pl.BlockSpec((1, 1, N, HP), state_map),
                   pl.BlockSpec((1, 1, HP), row_map)],
        scratch_shapes=[pltpu.VMEM((2, N, tw), F32)])
    new, y = pl.pallas_call(
        ssm._update_kernel, name="ssm_update", grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((nb, 1, HP), F32)],
        input_output_aliases={2: 0}, interpret=True,
    )(lanes, jnp.reshape(layer, (1,)).astype(jnp.int32), state,
      x[:, None, :], dt[:, None, :], B[:, None, :], C[:, None, :],
      A_log.astype(F32)[None, :], D.astype(F32)[None, :])
    listed = jnp.any((lanes[None, :] == jnp.arange(nb)[:, None])
                     & (jnp.arange(nb)[None, :] < count), axis=1)
    return new, jnp.where(listed[:, None], y[:, 0], 0.0)


@pytest.mark.parametrize("live,heads", [
    ([0, 1, 1, 0, 1, 0], 4), ([1, 1, 1, 1, 1, 1], 16),
    ([0, 0, 0, 0, 0, 0], 4)])
def test_ssm_update_with_one_group_is_the_one_group_call_bit_for_bit(
        live, heads):
    """Granite's call (G = 1; the debug preset's 4 heads of 16, and a
    block of two register-wide tiles): the work list of (lane, group)
    pairs is the work list of lanes."""
    state, x, dt, B, C, A_log, D = _update_inputs(H=heads)
    lanes, count = ssm.live_lanes(jnp.asarray(live, bool))
    new, y = ssm.ssm_update(state, jnp.int32(2), lanes, count, x, dt, B, C,
                            A_log, D)
    new1, y1 = _ssm_update_one_group(state, jnp.int32(2), lanes, count, x,
                                     dt, B[:, 0], C[:, 0], A_log, D)
    on = np.flatnonzero(np.asarray(live))
    assert bool(jnp.all(new[2][on] == new1[2][on]))
    assert bool(jnp.all(y == y1))
    assert bool(jnp.all(new[:2] == state[:2]))


# ------------------------- (c) prefill, then decode, against the forward
def _worst(params, n=21, bucket=32, follow=2 * K):
    """The sound program (the file's seam) against the reference."""
    prompt, nxt = _tokens(n, 1), _tokens(follow, 2)
    got = served_logits(SOUND, params, CFG, prompt, nxt, bucket,
                        page=PAGE, k=K)
    want = _ref_logits(params, list(prompt) + list(nxt), last=follow + 1)
    return _gap(got, want)


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
    got = ssm_hybrid.project_logits(params, h[PREFILL_LENS.index(n), :n])
    assert _gap(got, _ref_logits(params, toks[:n])) < TOL


@pytest.mark.parametrize("n,bucket", [(21, 32), (1, 32), (2, 32), (3, 32),
                                      (33, 64), (9, 32)])
def test_padded_prefill_then_paged_decode_equals_the_reference(
        params, n, bucket):
    """true_len a multiple of nothing (not of the chunk of 8 either): the
    lane state must be the state and the convolution rows at the TRUE
    length (zeros where the prompt is shorter than three), and two
    windows of K steps carry them on."""
    assert _worst(params, n=n, bucket=bucket) < TOL


def test_the_prefill_hands_the_state_at_the_true_length(params):
    toks = _tokens(32, 5)
    # (the second row of the seam's two-row program of 32 positions)
    _, _, _, state, _ = SOUND.serve_prefill(
        params, jnp.asarray(np.stack([toks, toks])),
        jnp.asarray([32, 13], jnp.int32))
    got = jnp.concatenate(state["ssm"])[:, 1]           # [Mamba layers, ...]
    x = ref.embed(params, toks[:13], MODEL)
    want = []
    for kind, lp in ref.layers(params, MODEL):
        x, h = ref.mixer_half(x, lp, kind, MODEL)
        x = ref.mlp_half(x, lp, MODEL)
        if h is not None:
            want.append(h)
    assert got.shape == (N_MAMBA, 16, 64)
    assert float(jnp.max(jnp.abs(got - jnp.stack(want)))) < 1e-5


# ------------------------------------------------ (c) through the engine
PROMPTS = (40, 3, 17, 1, 29)
NEW, LANES = 14, 2


@pytest.fixture(scope="module")
def served(params):
    """ONE engine run for the file (`family_contract.served_run`): two
    lanes whose state was marked, a request of 9 + 9 tokens alone, then
    five prompts at once (two lanes: every wave is as wide as its rows,
    so the scan's counters are the prompts' own)."""
    return contract.served_run(
        ssm_hybrid, CFG, params, lanes=LANES, kv_pages=12, page=PAGE, k=K,
        prompts=[_tokens(n, 10 + n).tolist() for n in PROMPTS], new=NEW)


def test_engine_logits_equal_the_reference_across_lane_reuse(
        params, served):
    """Two lanes, a request and then five prompts of other lengths:
    more requests than lanes, so a lane that served one request serves
    another, and no state may leak.  The LOGITS the engine's own programs
    computed at every served position equal the reference's full forward,
    and the new counters equal what the kernel's work list admits."""
    st = served["stats"]
    assert st["completed"] == 1 + len(PROMPTS) and st["preemptions"] == 0
    for i, (prompt, out) in enumerate(zip(served["prompts"],
                                          served["outs"])):
        assert len(out["tokens"]) == NEW
        want = _ref_logits(params, (prompt + out["tokens"])[:-1], last=NEW)
        assert contract.engine_gap(served, i, want) < TOL
    # the counters: live lanes x K x Mamba layers a window; the chunks of
    # 8 positions below the true lengths and of the padded programs
    loop = st["loop"]
    assert loop["ssm_lane_steps"] == loop["lane_steps_live"] * N_MAMBA
    assert loop["prefill_scan_chunks"] == N_MAMBA * sum(
        -(-len(p) // 8)
        for p in served["prompts"] + [served["first_prompt"]])
    assert loop["prefill_scan_chunks"] <= loop["prefill_scan_chunks_dense"]
    assert loop["prefill_scan_chunks_dense"] % N_MAMBA == 0
    lane = st["lane_state"]
    assert lane["layers"] == N_MAMBA
    assert lane["by_kind"] == {"conv": N_MAMBA * LANES * 3 * 96 * 4,
                               "ssm": N_MAMBA * LANES * 16 * 64 * 4}
    assert lane["bytes"] == sum(lane["by_kind"].values())
    assert lane["prefix_cache"] == "off: lane state"
    assert st["prefix_cache"] is False


@pytest.mark.parametrize("live", [[1, 0, 1, 1], [0, 0, 0, 0], [1, 1, 1, 1]])
def test_the_host_counts_what_the_kernels_work_list_admits(live):
    """`loop.ssm_lane_steps` adds len(active) x K x layers a window; the
    kernel's grid is `count` steps a layer-step, from the table the engine
    sends: a lane is live where its table row does not start at the trash
    page."""
    table = jnp.asarray([[3 if on else 0, 0] for on in live], jnp.int32)
    lanes, count = ssm.live_lanes(paged_attention.lanes_live(table))
    assert int(count) == sum(live)
    assert sorted(lanes[:int(count)].tolist()) == \
        [i for i, on in enumerate(live) if on]


def test_an_idle_lanes_state_is_bit_unchanged_by_a_decode_window(served):
    """The run's first request (9 + 9 tokens) alone in an engine of two
    lanes whose state was marked: the windows' steps update every state
    matrix of its lane and leave the other lane's as they were."""
    (used,) = contract.lanes_written(
        served, lambda s: np.moveaxis(s["ssm"], 1, 0))
    before, after = (s["ssm"] for s in served["state"])
    assert (after[:, used] != before[:, used]).any(axis=(-1, -2)).all()


# ------------------------------------- (d) each scalar, each order: controls
# A control changes one scalar or one equation of one kind of layer, and
# its patch has to be traced: it runs on the model cut to its first three
# layers, which keep both kinds (two Mamba layers, the second the one a
# control skips, and the NoPE attention layer, each over its MLP, under
# Granite's scalars), against the reference of the same cut.
SHALLOW = dataclasses.replace(CFG, layer_types=CFG.layer_types[:3])
SHALLOW_MODEL = dict(MODEL, layer_types=list(SHALLOW.layer_types))


@pytest.fixture(scope="module")
def shallow():
    return ssm_hybrid.init_params(jax.random.PRNGKey(7), SHALLOW)


def _cut_worst(served, sound, cfg=SHALLOW, model=SHALLOW_MODEL):
    """`_worst` for a control: `served` (the cut's parameters, or a
    control's change of them) through programs traced anew for `cfg`,
    against the reference `model` on the `sound` parameters."""
    prompt, nxt = _tokens(21, 1), _tokens(2 * K, 2)
    got = served_logits(Seam(ssm_hybrid, cfg), served, cfg, prompt, nxt,
                        32, page=PAGE, k=K)
    want = ref.logits(sound, list(prompt) + list(nxt), model,
                      last=2 * K + 1)
    return _gap(got, want)


def _no(name):
    return lambda cfg: dataclasses.replace(cfg, **{name: 1.0})


def _rope_applied(x, lp, cfg, true_lens):
    """Attention with a rotary embedding on q and k (prefill only: the
    control needs one path to part from the reference)."""
    from ray_tpu.models.llama import apply_rope
    from ray_tpu.ops.rope import rope_frequencies

    cos, sin = rope_frequencies(cfg.head_dim, x.shape[1], 10000.0)
    real = ssm_hybrid.attention

    def attention(q, k, v, **kw):
        return real(apply_rope(q, cos, sin), apply_rope(k, cos, sin), v, **kw)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(ssm_hybrid, "attention", attention)
        return _ATTN_PREFILL(x, lp, cfg, true_lens)


def _norm_before_gate(y, z, lp, cfg):
    g = ssm_hybrid.rmsnorm(y, lp["gate_norm"], cfg.norm_eps) \
        * jax.nn.silu(z.astype(jnp.float32))
    return g.astype(cfg.dtype) @ lp["out_proj"]


def _dt_unmasked(h, lp, cfg, true_lens):
    z, x, _, B, C, rows = _SCAN_INPUTS(h, lp, cfg, true_lens)
    full = jnp.full_like(true_lens, h.shape[1])
    return (z, x, _SCAN_INPUTS(h, lp, cfg, full)[2], B, C, rows)


def _conv_rows_at_the_padded_length(h, lp, cfg, true_lens):
    full = jnp.full_like(true_lens, h.shape[1])
    return _SCAN_INPUTS(h, lp, cfg, true_lens)[:5] \
        + (_SCAN_INPUTS(h, lp, cfg, full)[5],)


def _scatter_zero_state(cache, ks, vs, state, *a, **kw):
    return _SCATTER(cache, ks, vs, jax.tree.map(jnp.zeros_like, state),
                    *a, **kw)


def _state_through_bf16(*a, **kw):
    new, y = _UPDATE(*a, **kw)
    return new.astype(jnp.bfloat16).astype(new.dtype), y


_ATTN_PREFILL, _SCAN_INPUTS = ssm_hybrid.attn_prefill, ssm_hybrid.scan_inputs
_SCATTER, _UPDATE = ssm_hybrid.scatter_prefill_pages, ssm.ssm_update


@pytest.mark.parametrize("control", [
    "sound", "attention_scale_1_over_8", "rope_applied",
    "residual_multiplier_left_out", "embedding_multiplier_left_out",
    "logits_scaling_left_out", "norm_before_gate", "D_left_out",
    "dt_unmasked_past_the_true_length",
    "conv_rows_at_the_padded_length", "lane_state_zeroed_at_admission",
    "a_mamba_layer_skipped", "state_through_bfloat16"])
def test_every_control_exceeds_the_tolerance(params, shallow, monkeypatch,
                                            control):
    served, cfg = shallow, SHALLOW
    if control == "sound":       # the whole model, then the cut
        assert _worst(params) < TOL
    elif control == "attention_scale_1_over_8":
        cfg = dataclasses.replace(SHALLOW, attn_scale=0.125)
    elif control == "rope_applied":
        monkeypatch.setattr(ssm_hybrid, "attn_prefill", _rope_applied)
    elif control == "residual_multiplier_left_out":
        cfg = _no("residual_scale")(SHALLOW)
    elif control == "embedding_multiplier_left_out":
        cfg = _no("embed_scale")(SHALLOW)
    elif control == "logits_scaling_left_out":
        cfg = _no("logits_scale")(SHALLOW)
    elif control == "norm_before_gate":
        monkeypatch.setattr(ssm_hybrid, "_gate_out", _norm_before_gate)
    elif control == "D_left_out":
        served = dict(shallow, mamba=dict(
            shallow["mamba"], D=jnp.zeros_like(shallow["mamba"]["D"])))
    elif control == "dt_unmasked_past_the_true_length":
        monkeypatch.setattr(ssm_hybrid, "scan_inputs", _dt_unmasked)
    elif control == "conv_rows_at_the_padded_length":
        monkeypatch.setattr(ssm_hybrid, "scan_inputs",
                            _conv_rows_at_the_padded_length)
    elif control == "lane_state_zeroed_at_admission":
        monkeypatch.setattr(ssm_hybrid, "serve_scatter", _scatter_zero_state)
    elif control == "a_mamba_layer_skipped":
        served = dict(shallow, mamba=dict(
            shallow["mamba"],
            out_proj=shallow["mamba"]["out_proj"].at[1].set(0.0)))
    elif control == "state_through_bfloat16":
        monkeypatch.setattr(ssm, "ssm_update", _state_through_bf16)
        # a rounding of 2**-9 of the state a step moves these logits by
        # 7e-4 of their scale over the eight steps walked: 400 x the
        # sound reading (1.5e-6) and 30 x the tolerance, where every
        # other control stands 5,000 x outside it (the benchmark's judge
        # reads the state itself: families/ssm_hybrid.STATE_ERR_TOL).  On
        # the WHOLE model: the cut's two Mamba layers move them by
        # 1.7 x this control's bound, the model's six by 3.4 x
        assert _cut_worst(params, params, CFG, MODEL) > 10 * TOL
        return
    worst = _cut_worst(served, shallow, cfg)
    if control == "sound":
        assert worst < TOL
    else:
        assert worst > CONTROL


# ---------------------------------------------- the planner's state ceiling
@pytest.mark.parametrize("row_bytes,widest", [
    (0, 16), (PREFILL_MAX_STATE_BYTES // 16, 16),
    (PREFILL_MAX_STATE_BYTES // 16 + 1, 8),
    (PREFILL_MAX_STATE_BYTES // 8 + 1, 1)])
def test_the_planner_bounds_a_programs_width_by_the_state_handed_over(
        row_bytes, widest):
    lengths = [100] * 16
    plan, capped = plan_wave(lengths, [1, 8, 16], [32, 64, 128], 16,
                             row_bytes)
    assert sorted(i for rows, _, _ in plan for i in rows) == list(range(16))
    assert max(w for _, w, _ in plan) == widest
    assert capped == (widest < 16)
    assert all(w * row_bytes <= PREFILL_MAX_STATE_BYTES or w == 1
               for _, w, _ in plan)


def test_the_published_state_fits_eight_rows_a_program():
    cfg = named_config("granite-4.0-h-micro")
    row = ssm_hybrid.serving_spec(cfg).prefill_state_bytes
    assert row == 36 * (128 * 4096 * 4 + 3 * 4352 * 2) == 76_437_504
    assert 8 * row <= PREFILL_MAX_STATE_BYTES < 16 * row


# --------------------------------------------- what the engine refuses
def test_a_state_space_model_is_served_without_the_prefix_cache(params):
    # (engines that are refused, and one that is never started: nothing
    # of theirs compiles)
    assert serving_model(CFG) is ssm_hybrid
    with pytest.raises(ValueError, match="radix prefix hit cannot restore"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  prefix_cache=True)
    with pytest.raises(ValueError, match="no LoRA hooks"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  lora_slots=2, lora_rank=4)
    eng = LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE)
    with pytest.raises(ValueError, match="no KV export/import"):
        eng.submit([1, 2, 3], prefill_only=True)


def test_the_server_serves_the_preset_by_name():
    # an engine of its own: the preset as published (bfloat16), found by
    # its name and served through `LLMServer`
    srv = LLMServer("ssm-hybrid-debug", max_batch=2, max_len=64,
                    page_size=PAGE)
    try:
        out = srv.engine.generate([5, 6, 7], max_new_tokens=5)
        assert len(out["tokens"]) == 5
        assert srv._prefix_client is None       # no demotion either
    finally:
        srv.engine.stop()
