"""models/lfm2.py (LFM2-MoE: gated short convolutions beside GQA
attention, routed experts that drop no token) against the plain float32
reference the benchmark holds it to (`benchmarks/harness/refs/
lfm2_moe.py`, which imports nothing of the program): the prompt pass, the
prompt pass at a padded bucket followed by paged decode through the pool
and the lane state, the engine with lanes reused and a forced
preempt-and-recompute (the file's one engine run: `family_contract`), the grouped matmul under skew, expert ranges, the
controls a sound comparison must fail, and what the engine refuses for a
model with lane state."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_contract as contract  # rootdir-relative (no pkg)
from family_contract import tokens as _tokens
from serving_reference import Seam, served_logits

from benchmarks.harness.refs import lfm2_moe as ref
from ray_tpu.models import lfm2, serving_model
from ray_tpu.ops import grouped_matmul
from ray_tpu.serve.llm import LLMEngine, LLMServer

ATTN = lfm2.ATTN
# float32 weights: the served path and the reference then differ by
# summation order alone, so the bound is tight and every control stands
# far outside it
CFG = lfm2.Lfm2MoeConfig(
    vocab_size=256, dim=64, layer_types=("conv", ATTN, "conv"),
    n_dense_layers=1, n_heads=4, n_kv_heads=2, ffn_dim=96, moe_ffn_dim=32,
    n_experts=8, top_k=4, max_seq=128, dtype=jnp.float32)
MODEL = dict(num_attention_heads=4, num_key_value_heads=2, norm_eps=1e-5,
             rope_parameters={"rope_theta": 1e6}, conv_L_cache=3,
             num_experts=8, num_experts_per_tok=4, use_expert_bias=True,
             norm_topk_prob=True, routed_scaling_factor=1,
             layer_types=list(CFG.layer_types), num_dense_layers=1)
TOL = 2e-4          # float32 against float32: summation order
CONTROL = 2e-2      # what every control must exceed, 100 x TOL
PAGE, K = 16, 4


# The sound program's seam, compiled once a shape for the file (true
# lengths are arguments), and the reference at ONE length (54 is the
# longest sequence a case reads: 40 prompt tokens and 14 served).
SOUND = Seam(lfm2, CFG)
_ref_logits = contract.one_length(
    lambda p, seq: ref.logits(p, seq, MODEL), 56)


@pytest.fixture(scope="module")
def params():
    return lfm2.init_params(jax.random.PRNGKey(7), CFG)


def _worst(params_served, params_ref, n=21, bucket=32, follow=2 * K,
           seam=SOUND):
    """Through the file's seam; a control (the model is three layers:
    cut to two, one of them routed, a changed router moves the logits by
    less than the controls' bound) through one traced under its patch."""
    prompt, nxt = _tokens(n, 1), _tokens(follow, 2)
    got = served_logits(seam, params_served, CFG, prompt, nxt, bucket,
                        page=PAGE, k=K)
    want = _ref_logits(params_ref, list(prompt) + list(nxt),
                       last=follow + 1)
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
    got = lfm2.project_logits(params, h[PREFILL_LENS.index(n), :n])
    want = _ref_logits(params, toks[:n])
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.mark.parametrize("n,bucket", [(21, 32), (1, 32), (2, 32), (33, 64)])
def test_padded_prefill_then_paged_decode_equals_the_reference(
        params, n, bucket):
    """true_len a multiple of nothing; the lane state must be the z rows
    before the TRUE length (zeros where the prompt is shorter than two),
    and two windows of K steps carry it on."""
    assert _worst(params, params, n=n, bucket=bucket) < TOL


# ------------------------------------------------ (3) through the engine
PROMPTS = (40, 3, 17, 1, 29)


@pytest.fixture(scope="module")
def served(params):
    """ONE engine run for the file (`family_contract.served_run`): two
    lanes over a pool of six pages, a request of 9 + 9 tokens alone, then
    five prompts at once, which the pool cannot hold together."""
    return contract.served_run(
        lfm2, CFG, params, lanes=2, kv_pages=6, page=PAGE, k=K, new=14,
        prompts=[_tokens(n, 10 + n).tolist() for n in PROMPTS])


def _reference_agrees(params, prompt, served) -> int:
    """Teacher-forced under the reference's full forward: wherever its
    top-two margin exceeds the tolerance, the served token is its
    choice.  Returns how many positions were that clear."""
    lg = _ref_logits(params, list(prompt) + served[:-1], last=len(served))
    top2 = np.sort(lg, axis=-1)[:, -2:]
    clear = top2[:, 1] - top2[:, 0] > TOL
    assert (np.argmax(lg, -1)[clear] == np.asarray(served)[clear]).all()
    return int(clear.sum())


def test_engine_generates_the_reference_tokens(params, served):
    """Two lanes, a request and then five prompts of other lengths: lanes
    are reused, and a pool too small for both forces a
    preempt-and-recompute.  Greedy tokens equal the reference's wherever
    its top-two margin exceeds the tolerance."""
    prompts, outs, st = served["prompts"], served["outs"], served["stats"]
    assert st["preemptions"] >= 1
    assert st["completed"] == 1 + len(PROMPTS)
    clear = sum(_reference_agrees(params, p, o["tokens"])
                for p, o in zip(prompts, outs))
    assert clear >= 5 * 14 - 3
    # the routed layers' counters, and the lane state's declaration
    loop, routed_layers = st["loop"], lfm2.serving_spec(CFG).routed_layers
    assert loop["moe_layer_steps"] > 0
    assert loop["moe_layer_steps"] % (routed_layers * K) == 0
    assert 0 < loop["moe_experts_hit"] <= 8 * loop["moe_layer_steps"]
    assert loop["moe_max_load"] >= loop["moe_assignments"] \
        / (8 * loop["moe_layer_steps"])
    assert loop["prefill_moe_assignments"] >= 4 * sum(map(len, prompts)) \
        * routed_layers
    assert st["lane_state"] == {
        "layers": 2, "bytes": 2 * 2 * 2 * 64 * 4,
        "by_kind": {"rows": 2 * 2 * 2 * 64 * 4},
        "prefix_cache": "off: lane state"}
    assert st["prefix_cache"] is False


# --------------------------------------------------- (4) dropless, skewed
def _loop_ffn(h2, lp, idx, wts):
    """The routed FF one row and one assignment at a time."""
    f = CFG.moe_ffn_dim
    out = np.zeros(h2.shape, np.float32)
    for t in range(h2.shape[0]):
        for e, w in zip(np.asarray(idx[t]), np.asarray(wts[t])):
            a = np.asarray(h2[t]) @ np.asarray(lp["w13"][e])
            act = a[:f] / (1 + np.exp(-a[:f])) * a[f:]
            out[t] += w * (act @ np.asarray(lp["w2"][e]))
    return out


@pytest.mark.parametrize("case", ["all_to_one", "an_empty_expert",
                                  "as_routed"])
def test_the_routed_layer_drops_nothing_under_skew(params, case):
    lp = dict(params["layers"][2])
    if case == "all_to_one":        # one expert and its three followers
        lp["expert_bias"] = jnp.asarray([9., 8, 7, 6, 0, 0, 0, 0])
    elif case == "an_empty_expert":
        lp["expert_bias"] = lp["expert_bias"].at[5].set(-9.0)
    h2 = jax.random.normal(jax.random.PRNGKey(3), (40, CFG.dim))
    idx, wts = lfm2.route(h2, lp, CFG)
    y, counts = lfm2.routed_ffn(h2, lp, CFG)
    assert float(np.abs(np.asarray(y) - _loop_ffn(h2, lp, idx, wts)).max()) \
        < TOL
    hit, load, n, *_ = (int(c) for c in counts)
    assert n == 40 * 4                      # every assignment computed
    if case == "all_to_one":
        assert (hit, load) == (4, 40)
    elif case == "an_empty_expert":
        assert hit <= 7 and 5 not in np.asarray(idx)


def test_expert_bias_moves_the_selection_and_not_the_weights(params):
    lp = dict(params["layers"][2])
    h2 = jax.random.normal(jax.random.PRNGKey(4), (64, CFG.dim))
    idx0, w0 = lfm2.route(h2, dict(lp, expert_bias=jnp.zeros(8)), CFG)
    idx1, w1 = lfm2.route(h2, lp, CFG)
    assert (np.sort(idx0) != np.sort(idx1)).any()       # selection moved
    s = jax.nn.sigmoid(h2 @ lp["router"])
    want = jnp.take_along_axis(s, idx1, -1)
    want = want / (want.sum(-1, keepdims=True) + 1e-6)
    assert float(jnp.abs(w1 - want).max()) < 1e-6       # weights: s alone


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("sizes", [[6] * 8, [48] + [0] * 7,
                                   [0, 0, 10, 0, 20, 1, 0, 5], [0] * 8])
def test_gmm_equals_a_loop_over_groups(impl, sizes):
    """Both forms (the kernel interpreted here), balanced, all in one
    group, empty groups, and rows that belong to no group (zero)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (48, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 128, 256))
    got = grouped_matmul.gmm(x, w, jnp.asarray(sizes, jnp.int32), impl=impl)
    off = np.concatenate([[0], np.cumsum(sizes)])
    want = np.zeros((48, 256), np.float32)
    for g in range(8):
        want[off[g]:off[g + 1]] = np.asarray(x)[off[g]:off[g + 1]] \
            @ np.asarray(w)[g]
    assert float(np.abs(np.asarray(got) - want).max()) < 1e-4


def test_gmm_visits_no_empty_group():
    """The kernel's visit list names only groups that hold a row, so an
    expert nobody was sent to is never fetched."""
    sizes = jnp.asarray([0, 0, 10, 0, 20, 1, 0, 5], jnp.int32)
    g, tile, _, total = grouped_matmul.visits(sizes, 48, 16)
    assert set(np.asarray(g).tolist()) == {2, 4, 5, 7}
    assert g.shape[0] == 48 // 16 + 8 - 1
    # gmm itself walks 48 rows as one tile, 384 as three of 128
    assert grouped_matmul.visits_static(48, 8) == 1 + 8 - 1
    assert grouped_matmul.visits_static(384, 8) == 3 + 8 - 1
    assert (np.diff(np.asarray(tile)) >= 0).all()
    # rows 0-9 | 10-29 | 30 | 31-35: tiles 0 | 0, 1 | 1 | 1, 2
    assert int(total) == 6
    assert len(set(zip(np.asarray(g).tolist(),
                       np.asarray(tile).tolist()))) == 6


def _gmm_over_the_padded_list(rows, weights, group_sizes, tm, tn):
    """The form `moe_gmm` had before its grid was bounded by the visits
    that are work, kept as the reference: a STATIC grid over the whole
    padded list, every padded visit multiplying and storing again what
    the last real one stored."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = rows.shape
    n = weights.shape[2]
    g, tile, offsets, _ = grouped_matmul.visits(group_sizes, m, tm)

    def kernel(g_ref, t_ref, off_ref, x_ref, w_ref, o_ref):
        v = pl.program_id(1)
        acc = jnp.dot(x_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
        r = t_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (r >= off_ref[g_ref[v]]) & (r < off_ref[g_ref[v] + 1])
        o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, g.shape[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, g, t, off: (t[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, g, t, off: (g[v], 0, j))],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, g, t, off: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        interpret=True)(g, tile, offsets, rows, weights)
    held = jnp.arange(m)[:, None] < jnp.sum(group_sizes)
    return jnp.where(held, out, jnp.zeros((), out.dtype))


@pytest.mark.parametrize("m", [48, 384, 2048],
                         ids=["one_tile", "tile128", "tile256"])
@pytest.mark.parametrize("sizes", [
    lambda m: [m // 8] * 8,                     # the list is full
    lambda m: [m // 16, 0, m // 8, 0, 0, m // 16, 0, 0],  # 3/4: nobody's
    lambda m: [0, 0, 0, 0, 0, 0, 1, 0],         # one row
    lambda m: [0] * 8,                          # total = 0: no step runs
], ids=["full", "a_quarter", "one_row", "none"])
def test_gmm_walks_the_visits_that_are_work_bit_for_bit(m, sizes):
    """Bounding the grid by `total` changes no bit: a padded visit only
    stored again what the last real one had stored.  With no visit at
    all the kernel writes nothing, and every row is exactly 0."""
    sizes = jnp.asarray(sizes(m), jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(0), (m, 128), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 128, 256), jnp.bfloat16)
    got = grouped_matmul.gmm(x, w, sizes, impl="pallas")
    tm = grouped_matmul.row_tile(m)
    want = _gmm_over_the_padded_list(x, w, sizes, tm, 128)
    got, want = np.asarray(got), np.asarray(want)
    assert not np.isnan(got.astype(np.float32)).any()
    assert (got.view(np.uint16) == want.view(np.uint16)).all()
    assert (got[int(sizes.sum()):].astype(np.float32) == 0).all()


@pytest.mark.parametrize("G,m,k,n", [
    (64, 256, 2048, 3072), (64, 256, 1536, 2048),     # LFM2 decode
    (32, 256, 4096, 4096), (32, 256, 2048, 4096),     # sarvam decode
    (36, 512, 4096, 4096), (36, 512, 2048, 4096),     # GLM decode
    (64, 65536, 2048, 3072), (64, 4096, 1536, 2048),  # prefill waves
    (32, 65536, 4096, 4096),
])
def test_gmm_col_tile_fits_and_divides(G, m, k, n):
    """The column tile of every served shape divides `n` by lane tiles
    and is the WIDEST such divisor up to COL_TILE whose weight block
    stays within the byte budget: 1,024 columns at every served shape,
    few rows or many (the kernel-alone table of PERF.md section 5), 128
    rows a tile up to 1,024 rows and 256 above; and what the blocks ask
    of VMEM leaves a v5e's 128 MiB room."""
    tn = grouped_matmul.col_tile(k, n, 2)
    assert n % tn == 0 and tn % 128 == 0
    budget = grouped_matmul.WEIGHT_BLOCK_BYTES
    assert k * tn * 2 <= budget
    wider = [t for t in range(tn + 128, n + 1, 128) if n % t == 0]
    assert all(t > grouped_matmul.COL_TILE or k * t * 2 > budget
               for t in wider)
    assert tn == 1024
    tm = grouped_matmul.row_tile(m)
    assert tm == (128 if m <= 1024 else 256)
    assert 2 * k * tn * 2 < grouped_matmul.vmem_bytes(tm, k, tn, 2) \
        < 48 << 20


def test_gmm_col_tile_keeps_two_blocks_inside_vmem():
    """Where `k` is far larger than any served, the byte budget and not
    COL_TILE bounds the block; where nothing fits, the narrowest tile."""
    assert grouped_matmul.col_tile(16384, 4096, 2) == 512
    assert grouped_matmul.col_tile(1 << 20, 4096, 2) == 128
    assert grouped_matmul.col_tile(128, 200, 2) == 200   # no lane tile


@pytest.mark.parametrize("n,budget,tn", [(256, None, 256), (768, None, 768),
                                         (768, 384 * 128 * 2, 384),
                                         (2048, None, 1024)],
                         ids=["n256", "n768", "n768_by_384", "n2048_by_1024"])
@pytest.mark.parametrize("sizes", [
    [6] * 8,                            # the list is full
    [3, 0, 6, 0, 0, 3, 0, 0],           # 3/4 of the rows: nobody's
    [0, 0, 0, 0, 0, 0, 1, 0],           # one row
    [0] * 8,                            # total = 0: no step runs
], ids=["full", "a_quarter", "one_row", "none"])
def test_gmm_wide_column_tile_changes_no_bit(monkeypatch, sizes, n, budget,
                                             tn):
    """An output element is one whole-`k` contraction inside one block,
    so the block's width changes no bit: `gmm` on the tile its rule
    picks (a whole group's weights, what a small budget leaves, or
    COL_TILE columns of a wider group) against the 128-wide form over
    the padded list."""
    if budget is not None:
        monkeypatch.setattr(grouped_matmul, "WEIGHT_BLOCK_BYTES", budget)
    assert grouped_matmul.col_tile(128, n, 2) == tn
    sizes = jnp.asarray(sizes, jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(0), (48, 128), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 128, n), jnp.bfloat16)
    got = grouped_matmul.gmm(x, w, sizes, impl="pallas")
    want = _gmm_over_the_padded_list(x, w, sizes, 16, 128)
    got, want = np.asarray(got), np.asarray(want)
    assert not np.isnan(got.astype(np.float32)).any()
    assert (got.view(np.uint16) == want.view(np.uint16)).all()
    assert (got[int(sizes.sum()):].astype(np.float32) == 0).all()


@pytest.mark.parametrize("rows,experts,live", [
    (40, (0, 8), "all"), (40, (2, 4), "all"), (40, (0, 8), "none"),
    (40, (6, 8), "half"), (512, (0, 8), "all"), (512, (4, 6), "half"),
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_routed_ffn_counts_the_visits_that_are_work(params, rows, experts,
                                                    live):
    """counts[3] is the number of REAL entries of the list `gmm` builds
    for the layer's sorted rows (one for each row tile a group that
    holds a row lies in), and never more than the padded length."""
    lp = params["layers"][2]
    lo, hi = experts
    held = dict(lp, w13=lp["w13"][lo:hi], w2=lp["w2"][lo:hi])
    h2 = jax.random.normal(jax.random.PRNGKey(8), (rows, CFG.dim))
    mask = {"all": None, "none": jnp.zeros(rows, bool),
            "half": jnp.arange(rows) % 2 == 0}[live]
    _, counts = lfm2.routed_ffn(h2, held, CFG, live=mask, experts=experts)
    idx = np.asarray(lfm2.route(h2, lp, CFG)[0])
    if mask is not None:
        idx = idx[np.asarray(mask)]
    sizes = np.bincount(idx.ravel(), minlength=8)[lo:hi]
    m = rows * CFG.top_k
    tm = grouped_matmul.row_tile(m)
    off = np.concatenate([[0], np.cumsum(sizes)])
    real = sum((off[i + 1] - 1) // tm - off[i] // tm + 1
               for i in range(hi - lo) if sizes[i])
    g, tile, _, total = grouped_matmul.visits(
        jnp.asarray(sizes, jnp.int32), m + -m % tm, tm)
    assert int(counts[3]) == int(total) == real
    if real:
        assert real == len(set(zip(np.asarray(g).tolist(),
                                   np.asarray(tile).tolist())))
    assert int(counts[2]) == sizes.sum()
    static = -(-m // tm) + (hi - lo) - 1
    assert real <= static == lfm2.routed.routed_visits(CFG, rows, experts)


# ------------------------------------------------- (5) ranges of experts
def test_the_parts_of_four_expert_ranges_add_up_to_the_layer(params):
    cfg = dataclasses.replace(CFG, n_experts=8)
    lp = params["layers"][1]
    h2 = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.dim))
    whole, counts = lfm2.routed_ffn(h2, lp, cfg)
    parts, n = 0.0, 0
    for lo in range(0, 8, 2):
        held = dict(lp, w13=lp["w13"][lo:lo + 2], w2=lp["w2"][lo:lo + 2])
        y, c = lfm2.routed_ffn(h2, held, cfg, experts=(lo, lo + 2))
        parts, n = parts + y, n + int(c[2])
    assert float(jnp.abs(parts - whole).max()) < TOL
    assert n == int(counts[2]) == 24 * 4


# ------------------------------------- (5b) the held head, in blocks
def _whole_list_ffn(h2, lp, cfg, live=None, experts=None):
    """The routed layer over the WHOLE sorted list, the form before the
    blocks: every one of the T k rows gathered, multiplied and read back
    by place.  The plain control: (y, sizes)."""
    T, d = h2.shape
    k, f = cfg.top_k, cfg.moe_ffn_dim
    lo, hi = experts or (0, cfg.n_experts)
    idx, wts = lfm2.route(h2, lp, cfg)
    flat = np.asarray(idx).reshape(T * k)
    held = (flat >= lo) & (flat < hi)
    if live is not None:
        held &= np.repeat(np.asarray(live), k)
    group = np.where(held, flat - lo, hi - lo)
    order = np.argsort(group, kind="stable")
    place = np.argsort(order, kind="stable")
    sizes = jnp.asarray(np.bincount(group, minlength=hi - lo + 1)[:hi - lo],
                        jnp.int32)
    h13 = grouped_matmul.gmm(h2[order // k], lp["w13"], sizes)
    act = jax.nn.silu(h13[:, :f]) * h13[:, f:]
    y = grouped_matmul.gmm(act, lp["w2"], sizes)[place].reshape(T, k, d)
    return jnp.sum(y * wts[..., None], axis=1), np.asarray(sizes)


def _blocked_case(params, case):
    """(h2, lp, live, experts) of one case of the blocked layer at 40
    rows x top 4 = 160 assignments."""
    lp = dict(params["layers"][2])
    live, experts = None, None
    if case == "none_held":
        live = jnp.zeros(40, bool)
    elif case == "one_expert_holds_every_row":
        lp["expert_bias"] = jnp.asarray([9., 8, 7, 6, 0, 0, 0, 0])
        experts = (0, 1)                       # 40 rows, all expert 0's
    elif case == "total_on_a_boundary":
        live = jnp.arange(40) < 16             # 16 rows x 4 = 64 held
    elif case == "a_group_split_by_a_boundary":
        lp["expert_bias"] = jnp.asarray([9., 8, 7, 6, 0, 0, 0, 0])
    elif case == "rows_dead":
        live = jnp.arange(40) % 3 != 0
    elif case == "a_range_held":
        experts = (2, 5)
    if experts:
        lp.update(w13=lp["w13"][slice(*experts)],
                  w2=lp["w2"][slice(*experts)])
    h2 = jax.random.normal(jax.random.PRNGKey(11), (40, CFG.dim))
    return h2, lp, live, experts


BLOCKED_CASES = ["all_held", "none_held", "one_expert_holds_every_row",
                 "total_on_a_boundary", "a_group_split_by_a_boundary",
                 "rows_dead", "a_range_held"]


@pytest.mark.parametrize("B", [16, 32, 64])
@pytest.mark.parametrize("case", BLOCKED_CASES)
def test_the_blocked_layer_equals_the_whole_list_layer(params, monkeypatch,
                                                       case, B):
    """`routed_ffn` walks the held head of the sorted list in blocks of
    `BLOCK` rows (patched below the 160 assignments, so the loop runs):
    the same layer as over the whole list, the blocks that hold a held
    row and no other, every visit counted."""
    h2, lp, live, experts = _blocked_case(params, case)
    want, sizes = _whole_list_ffn(h2, lp, CFG, live, experts)
    monkeypatch.setattr(lfm2.routed, "BLOCK", B)
    got, counts = lfm2.routed_ffn(h2, lp, CFG, live=live, experts=experts)
    assert float(jnp.abs(got - want).max()) < TOL
    total = int(sizes.sum())
    walked = -(-total // B)
    assert [int(c) for c in counts] == [
        int((sizes > 0).sum()), int(sizes.max()), total,
        # a visit: a (group, block) pair that share a row (a block of at
        # most 128 rows is one row tile)
        sum(int(grouped_matmul.visits(
            jnp.asarray(np.diff(np.clip(np.concatenate(
                [[0], np.cumsum(sizes)]), b * B, (b + 1) * B)), jnp.int32),
            B, B)[3]) for b in range(walked)),
        walked * B]
    blocks = -(-160 // B)
    assert walked == {"all_held": blocks, "none_held": 0,
                      "one_expert_holds_every_row": -(-40 // B),
                      "total_on_a_boundary": 64 // B,
                      "a_group_split_by_a_boundary": blocks}.get(case,
                                                                 walked)


def test_the_blocked_layer_walks_no_one_or_two_or_every_block(params,
                                                              monkeypatch):
    """The trip count is the device's: 0, 1, 2 and all 10 blocks of 16
    rows as the live rows grow, and `live` rows dead change nothing
    else."""
    monkeypatch.setattr(lfm2.routed, "BLOCK", 16)
    lp = params["layers"][2]
    h2 = jax.random.normal(jax.random.PRNGKey(12), (40, CFG.dim))
    for n_live, walked in ((0, 0), (3, 1), (4, 1), (5, 2), (8, 2),
                           (40, 10)):
        live = jnp.arange(40) < n_live
        y, counts = lfm2.routed_ffn(h2, lp, CFG, live=live)
        assert int(counts[4]) == walked * 16 and int(counts[2]) == n_live * 4
        want, _ = _whole_list_ffn(h2, lp, CFG, live)
        assert float(jnp.abs(y - want).max()) < TOL
        assert float(jnp.abs(y[n_live:]).max(initial=0.0)) == 0.0


def test_one_expert_holding_every_row_drops_nothing_in_any_block(
        params, monkeypatch):
    """Every row on ONE held expert, its group cut by every block
    boundary: each row equals the dense product with that expert's
    weights under the row's router weight, to float32 rounding."""
    h2, lp, _, experts = _blocked_case(params, "one_expert_holds_every_row")
    idx, wts = lfm2.route(h2, lp, CFG)
    assert (np.asarray(idx) == 0).sum() == 40
    w = np.asarray(jnp.sum(jnp.where(idx == 0, wts, 0.0), axis=1))
    a = np.asarray(h2) @ np.asarray(lp["w13"][0])
    f = CFG.moe_ffn_dim
    want = w[:, None] * ((a[:, :f] / (1 + np.exp(-a[:, :f])) * a[:, f:])
                         @ np.asarray(lp["w2"][0]))
    for B in (16, 4096):
        monkeypatch.setattr(lfm2.routed, "BLOCK", B)
        got, counts = lfm2.routed_ffn(h2, lp, CFG, experts=experts)
        assert float(np.abs(np.asarray(got) - want).max()) < TOL
        assert [int(c) for c in counts[:3]] == [1, 40, 40]
        assert int(counts[4]) == (48 if B == 16 else 160)


def test_the_blocks_rows_are_added_into_column_strips(monkeypatch):
    """The float32 sum the blocks' rows are added into is held as strips
    of columns (`ACC_BYTES` each at most, 128 columns at least): at a
    width of 320 and a budget that leaves 128 columns a strip it is
    three strips, the last of 64, and the layer is the whole-list
    layer."""
    cfg = dataclasses.replace(CFG, dim=320)
    ks = jax.random.split(jax.random.PRNGKey(13), 4)
    lp = {"router": jax.random.normal(ks[0], (320, 8)) * 320 ** -0.5,
          "expert_bias": jnp.zeros(8),
          "w13": jax.random.normal(ks[1], (8, 320, 64)) * 320 ** -0.5,
          "w2": jax.random.normal(ks[2], (8, 32, 320)) * 32 ** -0.5}
    h2 = jax.random.normal(ks[3], (40, 320))
    live = jnp.arange(40) % 4 != 1
    want, sizes = _whole_list_ffn(h2, lp, cfg, live)
    monkeypatch.setattr(lfm2.routed, "BLOCK", 32)
    monkeypatch.setattr(lfm2.routed, "ACC_BYTES", 4 * 40 * 128)
    got, counts = lfm2.routed_ffn(h2, lp, cfg, live=live)
    assert float(jnp.abs(got - want).max()) < TOL
    assert int(counts[4]) == -(-int(sizes.sum()) // 32) * 32
    text = jax.jit(lambda h: lfm2.routed_ffn(h, lp, cfg, live=live)).lower(
        h2).as_text()
    assert text.count("tensor<40x128xf32>, tensor<40x128xf32>, "
                      "tensor<40x64xf32>") > 0


def test_a_jitted_blocked_layer_holds_one_loop_and_a_short_list_none(
        params, monkeypatch):
    """The behaviour follows the call's static shape and nothing else: at
    most `BLOCK` assignments lower to straight-line code (every decode
    program), more of them to ONE loop whose trip count the device
    holds."""
    monkeypatch.setattr(lfm2.routed, "BLOCK", 32)
    lp = params["layers"][2]
    text = {}
    for rows in (8, 40):
        h2 = jnp.zeros((rows, CFG.dim))
        text[rows] = jax.jit(
            lambda h: lfm2.routed_ffn(h, lp, CFG)).lower(h2).as_text()
    assert "while" not in text[8] and text[40].count("stablehlo.while") == 1


def test_routed_work_reports_the_rows_moved(params):
    spec = serving_model(CFG).serving_spec(CFG)
    counts = np.asarray([[3, 9, 20, 4, 64], [2, 8, 12, 3, 32]], np.int32)
    for prefill, pre in ((False, ""), (True, "prefill_")):
        work, _ = spec.routed_work(counts, 1, 16, 16, prefill)
        assert work[pre + "moe_rows_moved"] == 96
        assert work[pre + "moe_assignments"] == 32
        assert work[pre + "moe_assignments_absent"] == 16 * 4 * 2 - 32
        assert pre + "moe_rows_moved" in spec.counters


# ---------------------------------------------------------- (6) controls
def _through_fp8(params):
    def q(a):
        return a.astype(jnp.float8_e4m3fn).astype(a.dtype)
    return dict(params, layers=[
        dict(lp, w13=q(lp["w13"]), w2=q(lp["w2"])) if "w13" in lp else lp
        for lp in params["layers"]])


def _skip_routed_layer(params, lid=1):
    layers = list(params["layers"])
    layers[lid] = dict(layers[lid], w2=jnp.zeros_like(layers[lid]["w2"]))
    return dict(params, layers=layers)


def _route_dropping_one(h2, lp, cfg):
    idx, wts = _ROUTE(h2, lp, cfg)
    return idx, wts.at[:, -1].set(0.0)


def _route_bias_in_weights(h2, lp, cfg):
    idx, _ = _ROUTE(h2, lp, cfg)
    s = jax.nn.sigmoid(h2 @ lp["router"]) + lp["expert_bias"]
    w = jnp.take_along_axis(s, idx, -1)
    return idx, w / (w.sum(-1, keepdims=True) + 1e-6)


def _scatter_zero_state(cache, ks, vs, state, *a, **kw):
    return _SCATTER(cache, ks, vs, [jnp.zeros_like(s) for s in state],
                    *a, **kw)


_ROUTE, _SCATTER = lfm2.route, lfm2.scatter_prefill_pages


@pytest.mark.parametrize("control", [
    "sound", "experts_through_fp8", "a_routed_layer_skipped",
    "one_selected_expert_dropped", "expert_bias_in_the_weights",
    "lane_state_zeroed_at_admission"])
def test_every_control_exceeds_the_tolerance(params, monkeypatch, control):
    served = params
    if control == "experts_through_fp8":
        served = _through_fp8(params)
    elif control == "a_routed_layer_skipped":
        served = _skip_routed_layer(params)
    elif control == "one_selected_expert_dropped":
        monkeypatch.setattr(lfm2, "route", _route_dropping_one)
    elif control == "expert_bias_in_the_weights":
        monkeypatch.setattr(lfm2, "route", _route_bias_in_weights)
    elif control == "lane_state_zeroed_at_admission":
        monkeypatch.setattr(lfm2, "serve_scatter", _scatter_zero_state)
    # a patch has to be traced; changed parameters are arguments of the
    # file's programs
    seam = SOUND if served is not params else Seam(lfm2, CFG)
    worst = _worst(served, params, seam=seam)
    if control == "sound":
        assert worst < TOL
    else:
        assert worst > CONTROL


# --------------------------------------------- (7) what the engine refuses
def test_a_model_with_lane_state_is_served_without_the_prefix_cache(params):
    # (engines that are refused at construction, and one never started:
    # nothing of theirs compiles)
    assert serving_model(CFG) is lfm2
    with pytest.raises(ValueError, match="radix prefix hit cannot restore"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  prefix_cache=True)
    with pytest.raises(ValueError, match="no LoRA hooks"):
        LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE,
                  lora_slots=2, lora_rank=4)
    eng = LLMEngine(CFG, params, max_batch=2, max_len=64, page_size=PAGE)
    assert eng.stats()["prefix_cache"] is False
    assert eng.stats()["lane_state"]["prefix_cache"] == "off: lane state"
    with pytest.raises(ValueError, match="no KV export/import"):
        eng.submit([1, 2, 3], prefill_only=True)
    with pytest.raises(ValueError, match="no KV export/import"):
        eng.kv_graft(list(range(PAGE)), np.zeros(1), kv_len=PAGE)


@pytest.mark.parametrize("kw,match", [
    (dict(lora_slots=2, lora_rank=4), "no LoRA hooks"),
    (dict(role="prefill", decode_deployment="decode"), "serve it unified"),
    (dict(role="decode"), "serve it unified"),
    (dict(prefix_cache=True), "radix prefix hit cannot restore"),
])
def test_the_server_refuses_at_construction(params, kw, match):
    with pytest.raises(ValueError, match=match):
        LLMServer(CFG, params=params, max_batch=2, max_len=64,
                  page_size=PAGE, **kw)


def test_the_server_serves_a_preset_by_name():
    # an engine of its own: the preset as published (bfloat16), found by
    # its name and served through `LLMServer`
    srv = LLMServer("lfm2-debug", max_batch=2, max_len=64, page_size=PAGE)
    try:
        out = srv.engine.generate([5, 6, 7], max_new_tokens=5)
        assert len(out["tokens"]) == 5
        assert srv._prefix_client is None       # no demotion either
    finally:
        srv.engine.stop()


# ------------------- (8) what a prefill program streams and multiplies
def test_prefill_params_equal_a_count_over_the_tree(params):
    """`prefill_params`: every matmul leaf of the layers is streamed (no
    embedding: a lookup, and the tied head keeps one position a row);
    a position multiplies them all but the experts, of which its
    `top_k` of `n_experts`.  The engine's floor follows the ratio; a
    module without the function reads ratio 1."""
    from ray_tpu.models import llama, ssm_hybrid
    from ray_tpu.serve.prefill_plan import FLOOR_TOKENS, programs_under

    experts = sum(lp[k].size for lp in params["layers"] if "w13" in lp
                  for k in ("w13", "w2"))
    matmul = sum(a.size for lp in params["layers"]
                 for k, a in lp.items() if a.ndim >= 2 and k != "conv_w")
    assert experts == lfm2.serving_spec(CFG).routed_layers * CFG.n_experts \
        * 3 * CFG.dim * CFG.moe_ffn_dim
    streamed, multiplied = lfm2.prefill_params(CFG)
    assert streamed == matmul
    assert multiplied == matmul - experts + experts * CFG.top_k \
        // CFG.n_experts
    # (engines that are built and never started: their plans are read,
    # nothing of theirs compiles)
    eng = LLMEngine(CFG, params, max_batch=16, max_len=128, page_size=PAGE)
    floor = FLOOR_TOKENS * streamed // multiplied
    assert FLOOR_TOKENS < floor == eng._prefill_floor \
        == eng.stats()["loop"]["prefill_floor_positions"]
    assert eng._width_buckets == [1, 2, 4, 8, 16]
    assert eng._prefill_programs == programs_under(
        floor, [1, 2, 4, 8, 16], eng._buckets, frozenset({2, 4}))
    assert all(w * b <= floor for w, b in eng._prefill_programs
               if w in (2, 4))
    # four lanes: width 4 is the chunk, and holds every bucket
    small = LLMEngine(CFG, params, max_batch=4, max_len=128, page_size=PAGE)
    assert small._width_buckets == [1, 2, 4]
    assert {b for w, b in small._prefill_programs if w == 4} \
        >= {small._buckets[-1]}
    for mod, name in ((llama, "debug"), (ssm_hybrid, "ssm-hybrid-debug")):
        dense_cfg = mod.serving_configs()[name]
        assert mod.serving_spec(dense_cfg).prefill_params is None
        dense = LLMEngine(dense_cfg, max_batch=16,
                          max_len=64, page_size=PAGE)
        assert dense._prefill_floor == FLOOR_TOKENS
        assert dense._width_buckets == [1, 8, 16]
        assert dense._prefill_programs is None
        assert dense.stats()["loop"]["prefill_floor_positions"] == 256
