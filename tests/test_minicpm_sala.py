"""models/minicpm_sala.py (lightning linear attention with a fixed decay a
head, three layers in four, on `ops/ssm.py` at a group a head; a GQA layer
without position embedding that past `dense_len` attends the key blocks it
selects, in pages beside a pool of stride means; dense SwiGLU; muP
scaling) against the plain float32 reference the benchmark holds it to
(`benchmarks/harness/refs/minicpm_sala.py`: the token-by-token recurrence,
the kernels' means of 32 keys, importing nothing of the program): the
prompt pass at a padded bucket (the kernel `bsa_prefill` and the XLA form)
followed by paged decode through the three pool leaves and the lane state,
across `dense_len` mid-decode, the ENGINE's own logits with lanes reused
and a dead lane bit-unchanged (one engine run: `family_contract`), the
selection against the reference's at a kernel that crosses a page, one
that completes mid-block, a window over block 0 and fewer blocks than
`topk`, a decode step's walk under its bias, `ssd_scan` / `ssm_update` at G = H
with a head that does not decay, the controls a sound comparison must
fail, and the counters."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

import family_contract as contract  # rootdir-relative (no pkg)
from family_contract import gap as _gap, tokens as _tokens
from serving_reference import Seam, served_logits

from benchmarks.harness.refs import minicpm_sala as ref
from ray_tpu.models import minicpm_sala, named_config, serving_model
from ray_tpu.ops import block_sparse_attention as bsa
from ray_tpu.ops import ssm
from ray_tpu.ops.sparse_attention import pool_index_keys

# float32 weights: the served path and the reference then differ by
# summation order alone, so the bound is tight and every control stands
# far outside it
CFG = dataclasses.replace(named_config("minicpm-sala-debug"),
                          dtype=jnp.float32)
SPARSE_CONFIG = dict(block_size=8, kernel_size=4, kernel_stride=2,
                     window_size=16, init_blocks=1, topk=2, dense_len=32)
MODEL = dict(
    mixer_types=list(CFG.layer_types), published_layers=32, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, vocab_size=256, rms_norm_eps=1e-6, scale_emb=12,
    scale_depth=1.4, dim_model_base=16, lightning_nh=4,
    lightning_head_dim=16, lightning_use_rope=True, rope_theta=10000,
    use_output_gate=True, use_output_norm=True, attn_use_output_gate=True,
    sparse_config=SPARSE_CONFIG)
SHAPE = CFG.selection
TOL = 2e-5          # float32 against float32, of the logits' scale
CONTROL = 2e-3      # what every control must exceed, 100 x TOL
PAGE, K = 16, 4

SOUND = Seam(minicpm_sala, CFG)
_ref_logits = contract.one_length(
    lambda p, seq: ref.logits(p, seq, MODEL), 56)


@pytest.fixture(scope="module")
def params():
    return minicpm_sala.init_params(jax.random.PRNGKey(7), CFG)


def test_the_debug_config_is_served_by_this_module():
    assert serving_model(CFG) is minicpm_sala
    assert CFG.layer_types == ("minicpm4",) + ("lightning-attn",) * 3
    assert dataclasses.asdict(SHAPE) == {
        "block": 8, "kernel": 4, "stride": 2, "window": 16,
        "init_blocks": 1, "topk": 2, "dense_len": 32}
    with pytest.raises(ValueError, match="two strides"):
        bsa.Shape(kernel=48)


# ------------------------- (a) prefill, then decode, against the forward
@pytest.mark.parametrize("T,n", [(128, 100),     # the kernel `bsa_prefill`
                                 (64, 57)])      # the XLA form
def test_prefill_logits_equal_the_reference(params, T, n):
    """Every position of a row past `dense_len`: below it everything is
    attended, past it the blocks the query's own scores chose."""
    toks = _tokens(T, 3)
    h = SOUND.serve_prefill(params, jnp.asarray(np.stack([toks, toks])),
                            jnp.asarray([T, n], jnp.int32))[0]
    got = minicpm_sala.project_logits(params, h[1, :n])
    assert _gap(got, ref.logits(params, toks[:n], MODEL)) < TOL


def _worst(params_served, params_ref, n=27, bucket=32, follow=3 * K,
           cfg=CFG, model=MODEL, seam=None):
    """Without a `seam`, through programs traced anew (a control's patch
    has to be traced)."""
    prompt, nxt = _tokens(n, 1), _tokens(follow, 2)
    got = served_logits(seam or Seam(minicpm_sala, cfg), params_served, cfg,
                        prompt, nxt, bucket, page=PAGE, k=K)
    seq = list(prompt) + list(nxt)
    if seam is SOUND:
        return _gap(got, _ref_logits(params_ref, seq, last=follow + 1))
    return _gap(got, ref.logits(params_ref, seq, model, last=follow + 1))


@pytest.mark.parametrize("n,bucket,follow", [
    (27, 32, 12),       # crosses dense_len 32 at the sixth decode step
    (40, 64, 12),       # a prompt pass past it, then selected steps
    (33, 64, 8),        # fewer blocks to choose from than topk
    (21, 32, 8),        # below it all the way: `paged_attn`'s branch
    (1, 32, 4)])
def test_padded_prefill_then_paged_decode_equals_the_reference(
        params, n, bucket, follow):
    """true_len a multiple of nothing: the lane state must be the state at
    the TRUE length, the pool the K, V and stride rows below it, the
    stride's sum what is left of it, and windows of K steps carry them on
    (a stride row lands in its page when its second key does; a kernel of
    four keys crosses the pages of 16 at every boundary)."""
    assert _worst(params, params, n=n, bucket=bucket, follow=follow,
                  seam=SOUND) < TOL


def test_the_prefill_hands_the_state_and_the_rows_at_the_true_length(params):
    toks = _tokens(64, 5)
    _, ks, vs, state, counts = SOUND.serve_prefill(
        params, jnp.asarray(np.stack([toks, toks])),
        jnp.asarray([64, 45], jnp.int32))
    ks, vs, state = jax.tree.map(lambda a: a[1:], (ks, vs, state))
    _, infos = ref.forward(params, toks[:45], MODEL)
    want = [i["state"] for i in infos if "state" in i]
    assert len(state["lightning"]) == 3 and counts.shape == (0, 4)
    for got, exp in zip(state["lightning"], want):
        assert got.shape == (1, 16, 64)
        assert _gap(got[0].reshape(16, 4, 16).transpose(1, 0, 2), exp) < 1e-5
    k = np.asarray(infos[0]["k"])
    assert _gap(ks[0][0, :45], k) < 1e-5
    assert _gap(vs["v"][0][0, :45], infos[0]["v"]) < 1e-5
    # 22 complete strides of two keys, and the 45th key alone
    assert vs["index"][0].shape == (1, 32, 2, 16)
    assert _gap(vs["index"][0][0, :22],
                k[:44].reshape(22, 2, 2, 16).mean(1)) < 1e-5
    assert _gap(state["kpart"][0][0], k[44].reshape(-1)) < 1e-5


# ------------------------------------------------ (a) through the engine
PROMPTS = (40, 3, 29, 17)
NEW, LANES = 10, 2


@pytest.fixture(scope="module")
def served(params):
    """The file's ONE engine: four requests over two lanes (lanes reused,
    a prompt past `dense_len`, one that crosses it while decoding), after
    a first request served alone."""
    return contract.served_run(
        minicpm_sala, CFG, params,
        prompts=[_tokens(n, 10 + n).tolist() for n in PROMPTS], new=NEW,
        lanes=LANES, kv_pages=1 + LANES * 6, page=PAGE, k=K, max_len=96)


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_the_engines_own_logits_equal_the_reference(params, served, i):
    seq = served["prompts"][i] + served["outs"][i]["tokens"]
    want = _ref_logits(params, seq[:-1], last=NEW)
    assert contract.engine_gap(served, i, want) < TOL


def test_a_lane_that_holds_no_request_is_bit_unchanged(served):
    """The first request, served alone on a fresh engine: one lane's state
    matrices and one lane's stride sum changed, the other lane's bits did
    not."""
    for leaf in ("lightning", "kpart"):
        assert len(contract.lanes_written(
            served, lambda s: list(s[leaf]))) == 1


def test_the_engine_reports_the_pool_the_state_and_the_work(served):
    st = served["stats"]
    cache = st["cache"]
    assert cache["kind"] == "kv" and cache["layers"] == 1
    assert cache["by_leaf"]["index"]["positions_per_row"] == 2
    # K and V 2 x 2 heads x 16 x 4 B, and a stride row's half a token
    assert cache["row_bytes"] == 2 * 128 + 64
    assert st["lane_state"]["layers"] == 3
    assert set(st["lane_state"]["by_kind"]) == {"lightning", "kpart"}
    loop = st["loop"]
    assert 0 < loop["bsa_rows_attended"] < loop["bsa_rows_context"]
    assert loop["bsa_dense_steps"] > 0 and loop["kernel_keys_written"] > 0
    assert loop["ssm_lane_steps"] > 0 and loop["prefill_scan_chunks"] > 0


# ---------------------------------------------------- (b) the selection
def _qk(T, seed=0):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.standard_normal((T, 4, 16)), jnp.float32),
            jnp.asarray(r.standard_normal((T, 2, 16)), jnp.float32))


@pytest.mark.parametrize("dense_len,T", [
    (32, 96),       # kernels across pages of 16, completing mid-block
    (8, 40)])       # past dense_len 8 the window of 16 still holds block 0
def test_the_selection_is_the_references(dense_len, T):
    """Every query of a row: the stride pool's kbar = (m_c + m_{c+1}) / 2
    against the reference's mean of four keys, the visible kernels, the
    blocks' maxima, the forced blocks and the top-k (fewer candidates than
    `topk` just past `dense_len`)."""
    shape = dataclasses.replace(SHAPE, dense_len=dense_len)
    q, k = _qk(T)
    means = pool_index_keys(jnp.swapaxes(k, 0, 1), shape.stride)
    got = bsa.prefill_select(
        q.reshape(1, T, 2, 2, 16).transpose(0, 2, 3, 1, 4), means[None],
        jnp.asarray([T]), shape, 16 ** -0.5)[0, :, :, :T // 8] != 0
    want = ref.selection(q, k, dict(MODEL, sparse_config=dict(
        SPARSE_CONFIG, dense_len=dense_len)))
    assert got.shape == want.shape == (2, T, T // 8)
    assert bool(jnp.all(got == want))
    at = np.arange(T)
    rows = np.asarray(got).sum(-1)
    # past dense_len a query keeps at most first + window's 3 + topk blocks
    assert rows[:, at >= dense_len].max() <= 1 + 3 + 2
    if T > 48:
        assert rows[:, at >= 48].min() >= 1 + 2 + 2
    for t in (dense_len, T - 1):
        nb, n = bsa.selection_counts(t + 1, shape)
        assert nb == rows[0, t]
        assert n == sum(min(8, t + 1 - 8 * b)
                        for b in np.nonzero(np.asarray(got)[0, t])[0])


def test_the_top_set_without_a_sort_is_top_ks():
    """Ties at the k-th value (the lower index first), fewer candidates
    than k, zeros and entries at NEG_INF."""
    r = np.random.default_rng(0)
    x = r.integers(0, 6, (40, 24)).astype(np.float32) / 4.0
    x[r.random((40, 24)) < 0.3] = bsa.NEG_INF
    x[0], x[1, 3:] = bsa.NEG_INF, bsa.NEG_INF
    for k in (1, 5, 30):
        want = np.zeros(x.shape, bool)
        order = np.argsort(-x, axis=-1, kind="stable")[:, :k]
        np.put_along_axis(want, order, True, axis=-1)
        want &= x > 0.5 * bsa.NEG_INF
        got = pl.pallas_call(       # (it rolls lanes: a kernel's code)
            lambda x_ref, o_ref: o_ref.__setitem__(..., jnp.where(
                bsa.top_set(x_ref[...], k), 1, 0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32),
            interpret=True)(jnp.asarray(x)) != 0
        assert bool(jnp.all(got == want))
        assert int(want.sum(-1).max()) <= k


def _decode_case(maxp, B=3, seed=0):
    """Lanes of 57, 0 (idle) and 41 rows in pages of 16 under a table of
    `maxp` columns, a running block of K rows of which two are written."""
    r = np.random.default_rng(seed)
    n_pages = 1 + B * 4
    kp, vp = (jnp.asarray(r.standard_normal((n_pages, 2, PAGE, 16)),
                          jnp.float32) for _ in range(2))
    idx = jnp.mean(kp.reshape(n_pages, 2, PAGE // 2, 2, 16), axis=3)
    table = np.zeros((B, maxp), np.int32)
    table[0, :4], table[2, :4] = [1, 2, 3, 4], [9, 10, 11, 12]
    ts = jnp.asarray([56, 0, 40], jnp.int32)
    pos = ts + 1
    tk, tv = (jnp.asarray(r.standard_normal((B, 2, K, 16)), jnp.float32)
              for _ in range(2))
    ti = jnp.mean(tk[:, :, :2], axis=2, keepdims=True)
    q = jnp.asarray(r.standard_normal((B, 2, 2, 16)), jnp.float32)
    return (q, kp, vp, idx, tk, tv, ti, jnp.asarray(table), pos, ts)


@pytest.mark.parametrize("maxp", [4, 80])
def test_a_decode_step_attends_the_rows_its_selection_names(maxp):
    """Lanes under a table of 4 columns and of 80 (76 of them the trash
    page's), one idle: `bsa_attn`'s walk under the selection's bias
    against a masked softmax over the rows the selection names."""
    got = jax.jit(lambda *a: bsa.decode_attention(
        *a, SHAPE, sm_scale=0.25))(*_decode_case(maxp))
    q, kp, vp, idx, tk, tv, ti, table, pos, ts = _decode_case(4)
    chosen = bsa.decode_select(q, idx, ti, table, pos, ts, SHAPE, 0.25)
    assert not bool(chosen[1].any())                 # the idle lane
    keys = jnp.concatenate([jnp.swapaxes(kp[table], 1, 2).reshape(
        3, 2, 64, 16), tk], axis=2)
    vals = jnp.concatenate([jnp.swapaxes(vp[table], 1, 2).reshape(
        3, 2, 64, 16), tv], axis=2)
    kpos = jnp.arange(64)
    admit = jnp.concatenate([
        jnp.repeat(chosen, 8, axis=-1) & (kpos < ts[:, None])[:, None],
        jnp.broadcast_to((ts[:, None] + jnp.arange(K) <= pos[:, None])[
            :, None], (3, 2, K))], axis=-1)
    s = jnp.einsum("bgrd,bgkd->bgrk", q, keys) * 0.25
    want = jnp.einsum("bgrk,bgkd->bgrd", jax.nn.softmax(
        jnp.where(admit[:, :, None], s, -1e30), axis=-1), vals)
    want = want.at[1].set(0.0)                       # the idle lane
    # the selection selects: lane 0 attends fewer rows than it holds
    assert int(admit[0, 0].sum()) < 58
    assert _gap(got, want) < 1e-5
    assert bool(jnp.all(got[1] == 0.0))


# ------------------------------------------- (c) ops/ssm.py at G = H
def test_ssd_scan_and_ssm_update_at_a_group_a_head_with_a_head_of_a_zero():
    """Lightning's shape on the shared scan: every head its own B and C,
    dt = 1 below the true length, and a head with A = 0 (no decay: what
    the schedule's last layer all but is)."""
    H, hd, T, n = 4, 16, 24, 19
    r = np.random.default_rng(0)
    q, k, v = (jnp.asarray(r.standard_normal((T, H, hd)), jnp.float32) * 0.5
               for _ in range(3))
    for lid in (1, 31):     # the program's schedule is the reference's
        assert _gap(jnp.exp(-minicpm_sala.decay_rates(CFG, lid)),
                    ref.decay(lid, MODEL)) < 1e-6
    # (the last published layer hardly decays: 1e-5 of a slope)
    assert float(ref.decay(31, MODEL).min()) > 0.99999
    rates = minicpm_sala.decay_rates(CFG, 1).at[0].set(0.0)
    lam = jnp.exp(-rates)
    o_ref, s_ref = ref.recurrence(q[:n], k[:n], v[:n], lam)
    dt = jnp.broadcast_to((jnp.arange(T) < n).astype(jnp.float32)[:, None],
                          (T, H))
    y, state = ssm.ssd_scan(v[None], dt[None], -rates, k[None], q[None], 8)
    assert _gap(y[0, :n], o_ref) < 1e-5
    as_heads = state[0].reshape(hd, H, hd).transpose(1, 0, 2)
    assert _gap(as_heads, s_ref) < 1e-5
    # one more token through the decode kernel, lanes 0 and 2 of three
    lanes3 = jnp.stack([state[0], 7.0 + state[0], 2.0 * state[0]])[None]
    live = jnp.asarray([True, False, True])
    lanes, count = ssm.live_lanes(live)
    new, y1 = ssm.ssm_update(
        lanes3, jnp.int32(0), lanes, count,
        jnp.tile(v[n].reshape(1, -1), (3, 1)),
        jnp.full((3, H * hd), minicpm_sala.DT_ONE, jnp.float32),
        jnp.tile(k[n][None], (3, 1, 1)), jnp.tile(q[n][None], (3, 1, 1)),
        jnp.repeat(jnp.log(rates), hd), jnp.zeros((H * hd,), jnp.float32))
    o1, s1 = ref.recurrence(q[n:n + 1], k[n:n + 1], v[n:n + 1], lam, s_ref)
    assert _gap(new[0, 0].reshape(hd, H, hd).transpose(1, 0, 2), s1) < 1e-5
    assert _gap(y1[0].reshape(H, hd), o1[0]) < 1e-5
    assert bool(jnp.all(new[0, 1] == lanes3[0, 1]))      # the idle lane


# ------------------------------------------------------- (d) the controls
def _prefill_gap(params, cfg=CFG, T=64, n=57):
    toks = _tokens(T, 3)
    h = jax.jit(lambda p, t, l: minicpm_sala.serve_prefill(p, t, cfg, l))(
        params, jnp.asarray(toks[None]), jnp.asarray([n], jnp.int32))[0]
    return _gap(minicpm_sala.project_logits(params, h[0, :n]),
                ref.logits(params, toks[:n], MODEL))


@pytest.mark.parametrize("name", [
    "scale_emb", "scale_depth", "logits_scale", "window", "first_block",
    "qk_norm", "output_gate", "output_norm", "topk"])
def test_a_rule_left_out_fails_the_comparison(params, monkeypatch, name):
    """Each of muP's three scalings, the window, the first block, a norm,
    a gate, a block fewer than `topk`: the prompt pass alone reads it."""
    cfg = CFG
    if name in ("scale_emb", "scale_depth"):
        cfg = dataclasses.replace(CFG, **{name: 1.0})
    elif name == "logits_scale":
        cfg = dataclasses.replace(CFG, dim_model_base=CFG.dim)
    elif name == "topk":
        cfg = dataclasses.replace(CFG, topk=1)
    elif name == "window":
        cfg = dataclasses.replace(CFG, window_size=8)
    elif name == "first_block":
        cfg = dataclasses.replace(CFG, init_blocks=0)
    elif name == "qk_norm":
        monkeypatch.setattr(minicpm_sala, "head_norm",
                            lambda x, w, cfg: x)
    elif name == "output_gate":
        monkeypatch.setattr(minicpm_sala, "output_gate",
                            lambda o, h, lp, cfg: o.astype(cfg.dtype))
    elif name == "output_norm":
        monkeypatch.setattr(
            minicpm_sala, "lightning_out",
            lambda y, h, lp, cfg: minicpm_sala.output_gate(
                y.reshape(*y.shape[:-2], -1).astype(cfg.dtype), h, lp, cfg)
            @ lp["wo"])
    assert _prefill_gap(params, cfg) > CONTROL


def test_a_state_rounded_to_bfloat16_fails_the_comparison(params):
    """(39 positions of 2**-9 a step: 30 x TOL here, not CONTROL's 100 x;
    the benchmark's judge reads the state itself, after 16 k positions.)"""
    cfg = dataclasses.replace(CFG, state_dtype=jnp.bfloat16)
    assert _worst(params, params, cfg=cfg) > 10 * TOL


def test_the_reference_given_a_selection_attends_under_it(params):
    """The judge's tight reading: the reference under a handed selection
    equals its own when the selection is its own, moves when a query's
    scored blocks are taken away, and keeps the first block and the window
    whatever is handed."""
    toks = _tokens(64, 3)
    x = ref.embed(params, toks, MODEL)
    lp = params["layers"][0]
    y_own, info = ref.mixer(x, lp, 0, MODEL)
    y_same, _ = ref.mixer(x, lp, 0, MODEL, given=info["chosen"])
    assert _gap(y_same, y_own) < 1e-6
    fewer = info["chosen"].at[:, 40:].set(False)
    y_less, less = ref.mixer(x, lp, 0, MODEL, given=fewer)
    assert _gap(y_less[40:], y_own[40:]) > CONTROL
    assert _gap(y_less[:40], y_own[:40]) < 1e-6
    # query 63: block 0 and the window's blocks 6 and 7 stay
    assert np.nonzero(np.asarray(less["chosen"])[0, 63])[0].tolist() == [
        0, 6, 7]


# ------------------------------------------------------ (e) the counters
def test_the_work_counters_are_host_arithmetic():
    spec = minicpm_sala.serving_spec(CFG)
    assert spec.lane_state_layers == 3 and not spec.caps
    assert spec.prefill_state_bytes == 4 * (3 * 16 * 64 + 32)
    work, shown = spec.decode_work([20, 60], 4, PAGE, 6)
    # lane 1: contexts 21 ... 24, all below dense_len 32; lane 2: 61 ... 64
    # past it: block 0, the window's blocks from (t - 15) // 8 on, 2 more
    att = sum(range(21, 25)) + sum(
        bsa.selection_counts(c, SHAPE)[1] for c in range(61, 65))
    assert bsa.selection_counts(61, SHAPE) == (1 + 2 + 3, 24 + 61 - 40)
    assert work["bsa_rows_context"] == sum(range(21, 25)) + sum(range(61, 65))
    assert work["bsa_rows_attended"] == att < work["bsa_rows_context"]
    assert work["bsa_dense_steps"] == 4
    assert work["kernel_keys_written"] == 2 + 2
    assert work["ssm_lane_steps"] == 2 * 4 * 3 and shown == work
    pre, _ = spec.prefill_work([45, 64], 64)
    assert pre["kernel_keys_written"] == 22 + 32
    assert pre["prefill_scan_chunks"] == 3 * (6 + 8)
    assert "prefill_attn_blocks" not in pre        # past dense_len: no flash
    assert "prefill_attn_blocks" in spec.prefill_work([20], 32)[0]
    assert set(spec.counters) >= set(bsa.COUNTERS) | set(ssm.SCAN_COUNTERS)
