"""Paged-KV decode: kernel numerics, engine equivalence, long context.

The serving-side answer to SURVEY §7's "bucketed shapes/paged KV via
Pallas" hard part (reference analog: vLLM paged attention under ray
Serve; ray itself has no attention op).  Kernel runs in interpret mode
on CPU — same code path as the TPU build.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from serving_reference import reference_greedy  # rootdir-relative (no pkg)


def test_kernel_matches_reference_across_page_counts():
    from ray_tpu.ops.paged_attention import (paged_decode_attention,
                                             paged_decode_reference)

    rng = np.random.default_rng(0)
    B, kvh, rep, hd, kt = 4, 2, 2, 32, 4
    page, n_pages, maxp = 8, 20, 4
    q = jnp.asarray(rng.normal(size=(B, kvh, rep, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, kvh, page, hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, kvh, page, hd)),
                     jnp.float32)
    ktail = jnp.asarray(rng.normal(size=(B, kvh, kt, hd)), jnp.float32)
    vtail = jnp.asarray(rng.normal(size=(B, kvh, kt, hd)), jnp.float32)
    table = np.zeros((B, maxp), np.int32)
    ids = iter(range(1, n_pages))
    for b in range(B):
        for p in range(maxp):
            table[b, p] = next(ids)
    table = jnp.asarray(table)
    # Block starts spanning 0..4 pages incl. boundaries; pos = ts + j.
    ts = jnp.asarray([0, 7, 8, 27], jnp.int32)
    pos = ts + 2
    args = (q, kp, vp, ktail, vtail, table, pos, ts)
    o_ref = paged_decode_reference(*args)
    o = paged_decode_attention(*args)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=1e-5)


def test_merge_tail_roundtrip():
    """merge_tail_pages + a fresh-block attend == attending the same
    rows from the tail (the block-boundary invariant)."""
    from ray_tpu.ops.paged_attention import (merge_tail_pages,
                                             paged_decode_attention)

    rng = np.random.default_rng(1)
    B, kvh, rep, hd, kt = 2, 2, 1, 16, 4
    page, n_pages, maxp = 8, 10, 2
    q = jnp.asarray(rng.normal(size=(B, kvh, rep, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, kvh, page, hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, kvh, page, hd)),
                     jnp.float32)
    ktail = jnp.asarray(rng.normal(size=(B, kvh, kt, hd)), jnp.float32)
    vtail = jnp.asarray(rng.normal(size=(B, kvh, kt, hd)), jnp.float32)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    ts = jnp.asarray([3, 6], jnp.int32)
    pos = ts + (kt - 1)
    o_in_block = paged_decode_attention(q, kp, vp, ktail, vtail, table,
                                        pos, ts)
    # Merge the block, start a new one at ts' = pos + 1 with empty tail.
    kp2 = merge_tail_pages(kp, ktail, table, ts, kt)
    vp2 = merge_tail_pages(vp, vtail, table, ts, kt)
    empty = jnp.zeros_like(ktail)
    o_next = paged_decode_attention(q, kp2, vp2, empty, empty, table,
                                    pos, pos + 1)
    np.testing.assert_allclose(np.asarray(o_in_block),
                               np.asarray(o_next), atol=1e-5)


@pytest.mark.parametrize("n_rows", [6, 4], ids=["whole", "short"])
@pytest.mark.parametrize("hd", [128, 64, 256], ids=["hd128", "hd64", "hd256"])
def test_merge_tail_pages_writes_the_block_rows_and_no_other(hd, n_rows):
    """Both scatters of merge_tail_pages (rows of whole lane tiles, one
    or two, go in place, one contiguous row an update; narrower ones as a
    [kvh, hd] window) against a loop in numpy: a block that crosses a
    page edge, one that ends its page, a short block whose stale columns
    must land on the trash page, and a slot without pages."""
    from ray_tpu.ops.paged_attention import merge_tail_pages

    rng = np.random.default_rng(hd + n_rows)
    B, kvh, kt, page, maxp, n_pages = 4, 2, 6, 8, 3, 8
    pages = rng.normal(size=(n_pages, kvh, page, hd)).astype(np.float32)
    tail = rng.normal(size=(B, kvh, kt, hd)).astype(np.float32)
    table = np.asarray([[1, 2, 0], [3, 4, 5], [6, 7, 0], [0, 0, 0]],
                       np.int32)
    ts = np.asarray([5, 10, 2, 0], np.int32)
    want = pages.copy()
    for b in range(B):
        for j in range(n_rows):
            apos = min(ts[b] + j, maxp * page - 1)
            want[table[b, apos // page], :, apos % page] = tail[b, :, j]
    got = np.asarray(jax.jit(merge_tail_pages, static_argnums=4)(
        jnp.asarray(pages), jnp.asarray(tail), jnp.asarray(table),
        jnp.asarray(ts), n_rows))
    np.testing.assert_array_equal(got[1:], want[1:])   # page 0: trash
    assert not np.array_equal(got[1:], pages[1:])


def test_kernel_clamps_runaway_idle_pos():
    """An idle slot's pos keeps advancing between reuses; the kernel must
    clamp rather than index past the table."""
    from ray_tpu.ops.paged_attention import paged_decode_attention

    B, kvh, rep, hd, kt = 2, 1, 1, 16, 2
    page, n_pages, maxp = 8, 4, 2
    q = jnp.ones((B, kvh, rep, hd), jnp.float32)
    kp = jnp.zeros((n_pages, kvh, page, hd), jnp.float32)
    vp = jnp.zeros((n_pages, kvh, page, hd), jnp.float32)
    ktail = jnp.ones((B, kvh, kt, hd), jnp.float32)
    vtail = jnp.ones((B, kvh, kt, hd), jnp.float32)
    table = jnp.zeros((B, maxp), jnp.int32)
    ts = jnp.asarray([3, 10_000], jnp.int32)   # slot 1 ran away
    o = paged_decode_attention(q, kp, vp, ktail, vtail, table, ts + 1,
                               ts)
    assert np.all(np.isfinite(np.asarray(o)))


# lanes that hold a request, by name; the rest are idle (a table row
# of zeros, the trash page, and a `pos` that ran away)
_LIVE_MIXES = {
    "idle_first": [0, 0, 1, 1, 1, 1],
    "idle_last": [1, 1, 1, 1, 0, 0],
    "interleaved": [1, 0, 1, 0, 0, 1],
    "all_idle": [0, 0, 0, 0, 0, 0],
    "all_live": [1, 1, 1, 1, 1, 1],
}


def _lanes(live, page, maxp, seed):
    """A table and block starts for `live`: each live lane gets the pages
    its rows need (one at least: its row must not start at the trash
    page) from a shuffled pool, block starts of 0, one row short of a
    page, exactly a page, every page full, and two in between, rotated by
    the seed; an idle lane's start ran away."""
    rng = np.random.default_rng(seed)
    starts = np.roll([0, page - 1, page, maxp * page, page + 3, 2 * page],
                     seed)
    B = len(live)
    n_pages = 1 + B * maxp
    free = list(rng.permutation(np.arange(1, n_pages)))
    table = np.zeros((B, maxp), np.int32)
    ts = np.full((B,), 10_000, np.int32)
    for b in np.flatnonzero(live):
        ts[b] = starts[b]
        for c in range(max(-(-ts[b] // page), 1)):
            table[b, c] = free.pop()
    return table, ts, n_pages


@pytest.mark.parametrize("hd,rep,dv", [(128, 4, 128), (64, 6, 64),
                                       (128, 6, 128), (64, 4, 64),
                                       (192, 16, 128)],
                         ids=["hd128-rep4", "hd64-rep6", "hd128-rep6",
                              "hd64-rep4", "hd192-dv128-rep16"])
@pytest.mark.parametrize("mix", list(_LIVE_MIXES))
def test_kernel_walks_live_lanes_only(mix, hd, rep, dv):
    """The grid is the live (lane, page) pairs: with the trash page and
    every page no live lane lists holding NaN, live lanes give the
    reference's rows and idle lanes exactly 0, wherever the idle lanes
    sit and however far their positions ran.  Values may be narrower
    than keys (192 / 128, sixteen query heads a kv head)."""
    from ray_tpu.ops.paged_attention import (paged_decode_attention,
                                             paged_decode_reference)

    live = np.asarray(_LIVE_MIXES[mix], bool)
    B, kvh, kt, page, maxp = len(live), 2, 4, 8, 3
    seed = list(_LIVE_MIXES).index(mix)
    table, ts, n_pages = _lanes(live, page, maxp, seed)
    rng = np.random.default_rng(100 + seed)

    def rand(*shape):
        return rng.normal(size=shape).astype(np.float32)

    q, kp, vp = rand(B, kvh, rep, hd), rand(n_pages, kvh, page, hd), \
        rand(n_pages, kvh, page, dv)
    ktail, vtail = rand(B, kvh, kt, hd), rand(B, kvh, kt, dv)
    pos = ts + 2
    dead = np.setdiff1d(np.arange(n_pages), table[table > 0])
    assert 0 in dead and len(dead) > 1
    poisoned = [jnp.asarray(x).at[dead].set(jnp.nan) for x in (kp, vp)]
    rest = [jnp.asarray(x) for x in (ktail, vtail, table, pos, ts)]
    got = np.asarray(jax.jit(paged_decode_attention)(
        jnp.asarray(q), *poisoned, *rest))
    # the oracle gathers every table column, the zeroed ones too, and
    # 0 x NaN is NaN: it reads the pools as they were
    want = np.asarray(paged_decode_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), *rest))
    assert got.shape == (B, kvh, rep, dv)
    np.testing.assert_array_equal(got[~live], 0.0)
    np.testing.assert_array_equal(want[~live], 0.0)
    np.testing.assert_allclose(got[live], want[live], atol=1e-5)
    if live.any():
        assert np.abs(want[live]).max() > 0.1


@pytest.mark.parametrize("seed", range(6))
def test_attention_plan_lists_each_live_page_once_in_order(seed):
    """The plan alone, no kernel: for random tables and block starts its
    count is the live lanes' pages (one step at least a live lane, the
    tail's), and its first `count` entries are every live (lane, column)
    once, lanes ascending and a lane's columns ascending, each with the
    table's page; what follows repeats the last entry."""
    from ray_tpu.ops.paged_attention import attention_plan

    rng = np.random.default_rng(seed)
    B, maxp, page = int(rng.integers(1, 12)), int(rng.integers(1, 6)), 16
    table = rng.integers(1, 500, size=(B, maxp)).astype(np.int32)
    live = rng.random(B) < (0.0, 0.3, 0.6, 0.9, 1.0, 0.5)[seed]
    table[~live, 0] = 0
    ts = rng.integers(0, maxp * page + 1, size=B).astype(np.int32)
    ts[rng.random(B) < 0.2] = 0
    ts[~live] = 1_000_000
    plan = jax.jit(attention_plan, static_argnums=2)(
        jnp.asarray(table), jnp.asarray(ts), page)
    want = [(b, c) for b in range(B) if live[b]
            for c in range(max(-(-int(ts[b]) // page), 1))]
    count = int(plan["count"])
    assert count == len(want)
    lane, col, pg = (np.asarray(plan[k]) for k in ("lane", "col", "page"))
    assert lane.shape == col.shape == pg.shape == (B * maxp,)
    assert list(zip(lane[:count], col[:count])) == want
    np.testing.assert_array_equal(pg, table[lane, col])
    last = max(count - 1, 0)
    assert (lane[last:] == lane[last]).all() and \
        (col[last:] == col[last]).all()


def test_engine_counts_the_plans_steps():
    """`stats()["loop"]["attn_steps"]`, which the engine adds up from the
    lengths it holds on the host, is the sum of the counts of the plans
    its decode windows built on the device; `attn_steps_dense` is the
    lanes x (columns + 1) grid a window."""
    from ray_tpu.models import llama
    from ray_tpu.ops.paged_attention import attention_plan

    eng = _engine(llama.llama_configs()["debug"], page_size=16)
    counts = []
    k = eng.steps_per_sync
    decode = eng._decode_fns[k]

    def counting(params, cache, tokens, temps, table, *rest):
        counts.append(int(attention_plan(table, cache["pos"],
                                         eng.page)["count"]))
        return decode(params, cache, tokens, temps, table, *rest)

    eng._decode_fns[k] = counting
    try:
        # prompts under a page, over one and over two; the longest
        # decode crosses a page edge; lanes finish at different windows
        futs = [eng.submit(list(range(1, 1 + n)), max_new_tokens=m)
                for n, m in ((5, 3), (20, 30), (40, 9))]
        for f in futs:
            f.result(timeout=180)
        loop = eng.stats()["loop"]
    finally:
        eng.stop()
    assert len(counts) >= 4 and len(set(counts)) > 1
    assert loop["attn_steps"] == sum(counts)
    assert loop["attn_steps_dense"] == len(counts) * 4 * (eng._maxp + 1)
    assert 0 < loop["attn_steps"] < loop["attn_steps_dense"]


def _debug_f32():
    """The debug model in float32: greedy tokens then follow the
    arithmetic and not bf16 near-ties, so `llama.forward` can referee."""
    import dataclasses

    from ray_tpu.models import llama

    cfg = dataclasses.replace(llama.llama_configs()["debug"],
                              dtype=jnp.float32)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _engine(cfg, params=None, *, max_len=128, **kw):
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(cfg, params, max_batch=4, max_len=max_len, seed=0,
                    **kw)
    eng.start()
    return eng


def test_paged_engine_matches_full_forward_greedy():
    cfg, params = _debug_f32()
    paged = _engine(cfg, params, page_size=16)
    try:
        prompts = [[1, 2, 3, 4, 5], [7, 8, 9],
                   [11, 12, 13, 14, 15, 16, 17], [2, 4]]
        fp = [paged.submit(p, max_new_tokens=12) for p in prompts]
        for p, f in zip(prompts, fp):
            assert f.result(timeout=120)["tokens"] == \
                reference_greedy(params, cfg, p, 12), p
    finally:
        paged.stop()


def test_paged_pool_backpressure():
    """More concurrent requests than the page pool holds: admission
    blocks FIFO on the pool and every request still completes."""
    from ray_tpu.models import llama

    eng = _engine(llama.llama_configs()["debug"], page_size=16,
                  kv_pages=5)                       # 4 usable pages
    try:
        futs = [eng.submit([1, 2, 3], max_new_tokens=10)
                for _ in range(6)]
        res = [f.result(timeout=180)["tokens"] for f in futs]
        assert all(len(r) == 10 for r in res)
    finally:
        eng.stop()


def test_long_context_engine_no_dense_prealloc():
    """max_len=32768 with a small page pool: the engine must NOT
    preallocate dense per-slot windows (VERDICT round-2 item 1's done
    condition), and a request whose span crosses several pages decodes
    correctly."""
    cfg, params = _debug_f32()
    eng = _engine(cfg, params, max_len=32768, page_size=64, kv_pages=9)
    try:
        # Pool memory is 9 pages x 64 rows — NOT slots x 32768:
        pool_rows = eng.cache["k"][0].shape[0] * eng.cache["k"][0].shape[2]
        assert pool_rows < 4 * 32768 // 10, pool_rows
        prompt = list(np.arange(1, 150) % (cfg.vocab_size - 1) + 1)
        out = eng.submit(prompt, max_new_tokens=40).result(timeout=300)
        assert len(out["tokens"]) == 40
        # The full forward on the whole context at every step: greedy
        # tokens must agree (the paged path is not approximate).
        assert out["tokens"] == reference_greedy(params, cfg, prompt, 40)
    finally:
        eng.stop()


@pytest.mark.parametrize("lora", [False, True], ids=["base", "lora"])
@pytest.mark.parametrize("n_heads,n_kv_heads", [(4, 4), (8, 2), (12, 2)],
                         ids=["rep1", "rep4", "rep6"])
def test_decode_step_paged_equals_the_reshape_on_the_product(
        monkeypatch, n_heads, n_kv_heads, lora):
    """`llama._decode_qkv` holds the q/k/v products flat behind a barrier
    (so that the compiler leaves the weights' layout alone: PERF.md
    section 6, PR 29).  The arithmetic is what it was: written as before,
    the reshape into heads on each product, the step gives the same
    logits and the same new K/V rows, with and without adapter banks."""
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=256, dim=32 * n_heads, n_layers=2,
                            n_heads=n_heads, n_kv_heads=n_kv_heads,
                            ffn_dim=256, max_seq=128, remat=False)
    keys = iter(jax.random.split(jax.random.PRNGKey(n_heads), 32))
    params = llama.init_params(next(keys), cfg)
    B, page, maxp, kt, j = 3, 16, 4, 4, 1
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    n_pages = 1 + B * maxp

    def rand(shape):
        return jax.random.normal(next(keys), shape, jnp.float32) \
            .astype(cfg.dtype)

    pages = {kv: [rand((n_pages, kvh, page, hd))
                  for _ in range(cfg.n_layers)] for kv in "kv"}
    tails = {kv: [rand((B, kvh, kt, hd)) for _ in range(cfg.n_layers)]
             for kv in "kv"}
    table = jnp.arange(1, n_pages, dtype=jnp.int32).reshape(B, maxp)
    tail_start = jnp.asarray([5, 17, 40], jnp.int32)
    args = (jnp.asarray([7, 99, 200], jnp.int32), tail_start + j,
            tail_start, j, table, cfg)
    bank = None
    if lora:
        slots, rank = 3, 4                       # slot 0: the base model
        bank = {"idx": jnp.asarray([0, 2, 1], jnp.int32), "banks": {
            t: {"a": rand((cfg.n_layers, slots, din, rank)).at[:, 0].set(0),
                "b": rand((cfg.n_layers, slots, rank, dout)).at[:, 0].set(0)}
            for t, (din, dout) in llama.lora_target_dims(cfg).items()}}

    def as_before(h, lp, cfg, lb=None, idx=None):
        b, lb = h.shape[0], lb or {}
        return (llama._lora_proj(h, lp["wq"], lb.get("wq"), idx)
                .reshape(b, 1, cfg.n_heads, cfg.head_dim),
                llama._lora_proj(h, lp["wk"], lb.get("wk"), idx)
                .reshape(b, 1, cfg.n_kv_heads, cfg.head_dim),
                llama._lora_proj(h, lp["wv"], lb.get("wv"), idx)
                .reshape(b, 1, cfg.n_kv_heads, cfg.head_dim))

    # traced anew for each side: the second runs the patched projection
    logits, new_tails = jax.jit(
        lambda p, pg, tl, lo: llama.decode_step_paged(
            p, pg, tl, *args, lo))(params, pages, tails, bank)
    monkeypatch.setattr(llama, "_decode_qkv", as_before)
    want, want_tails = jax.jit(
        lambda p, pg, tl, lo: llama.decode_step_paged(
            p, pg, tl, *args, lo))(params, pages, tails, bank)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    for kv in "kv":
        for got, ref in zip(new_tails[kv], want_tails[kv]):
            np.testing.assert_array_equal(
                np.asarray(got, np.float32), np.asarray(ref, np.float32))
    if lora:        # the banks do something: lane 0 rides slot 0, the base
        base, _ = jax.jit(
            lambda p, pg, tl: llama.decode_step_paged(
                p, pg, tl, *args))(params, pages, tails)
        np.testing.assert_array_equal(np.asarray(base[0]),
                                      np.asarray(want[0]))
        assert float(jnp.abs(base[1:] - want[1:]).max()) > 1e-3


# ------------------------------------------- latent rows: `mla_attn`
def _mla_case(seed, B=5, H=4, dk=48, page=8, maxp=4, kt=4, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    n_pages = 1 + B * maxp
    q = jnp.asarray(rng.normal(size=(B, H, dk)), dtype)
    rows = jnp.asarray(rng.normal(size=(n_pages, 1, page, dk)), dtype)
    tail = jnp.asarray(rng.normal(size=(B, 1, kt, dk)), dtype)
    table = np.arange(1, n_pages, dtype=np.int32).reshape(B, maxp)
    return q, rows, tail, table


@pytest.mark.parametrize("ts", [
    [0, 7, 8, 27, 32],          # no page yet, a partial last page, whole
    [5, 13, 21, 29, 3],         # every lane ends inside a page
], ids=["boundaries", "partial_last_pages"])
def test_mla_attn_matches_a_plain_gather(ts):
    """One cached row a token serves every head as key AND value: scores
    over the row's dk columns, values = its first dv.  Against the
    oracle that gathers each lane's rows."""
    from ray_tpu.ops.paged_attention import (mla_decode_attention,
                                             mla_decode_reference)

    q, rows, tail, table = _mla_case(0)
    ts = jnp.asarray(ts, jnp.int32)
    for j in (0, 3):
        args = (q, rows, tail, jnp.asarray(table), ts + j, ts)
        got = mla_decode_attention(*args, dv=32, sm_scale=0.3)
        want = mla_decode_reference(*args, dv=32, sm_scale=0.3)
        assert got.shape == (5, 4, 32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


def test_mla_attn_leaves_idle_lanes_exactly_zero_and_reads_no_trash():
    """Lanes 1 and 3 hold no request (their table rows start at the trash
    page, their positions have run away): they get no step and read
    exactly 0, and NaNs in the trash page and in their tails reach
    nobody."""
    from ray_tpu.ops.paged_attention import (attention_plan,
                                             mla_decode_attention,
                                             mla_decode_reference)

    q, rows, tail, table = _mla_case(1)
    table[1] = table[3] = 0
    rows = rows.at[0].set(jnp.nan)
    tail = tail.at[1].set(jnp.nan).at[3].set(jnp.nan)
    ts = jnp.asarray([9, 10 ** 6, 17, 99, 30], jnp.int32)
    plan = attention_plan(jnp.asarray(table), ts, 8)
    assert int(plan["count"]) == 2 + 3 + 4
    args = (q, rows, tail, jnp.asarray(table), ts + 1, ts)
    got = mla_decode_attention(*args, dv=32, sm_scale=0.3, plan=plan)
    want = mla_decode_reference(
        q, rows.at[0].set(0.0), tail.at[1].set(0.0).at[3].set(0.0),
        *args[3:], dv=32, sm_scale=0.3)
    assert (np.asarray(got[1]) == 0).all() and (np.asarray(got[3]) == 0).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_mla_attn_in_bfloat16_at_a_padded_row_width():
    """The served form: bfloat16 rows stored a lane tile wider than they
    are used (zeros), q padded alike: the padding adds nothing."""
    from ray_tpu.ops.paged_attention import (mla_decode_attention,
                                             mla_decode_reference)

    q, rows, tail, table = _mla_case(2, dk=40, dtype=jnp.bfloat16)
    pad = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, 24)])  # noqa
    ts = jnp.asarray([4, 12, 20, 28, 31], jnp.int32)
    args = (jnp.asarray(table), ts + 2, ts)
    got = mla_decode_attention(pad(q), pad(rows), pad(tail), *args,
                               dv=32, sm_scale=0.2)
    want = mla_decode_reference(q, rows, tail, *args, dv=32, sm_scale=0.2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


@pytest.mark.parametrize("per,kt,ts,n_rows,want", [
    # positions 13..16 complete group 3 (position 15): one row
    (4, 1, 13, 4, [3]),
    # positions 8..15: groups 2 and 3, the block started on an edge
    (4, 2, 8, 8, [2, 3]),
    # positions 10..17: groups 2 (ends at 11) and 3 (15); 16, 17 open one
    (4, 2, 10, 8, [2, 3]),
    # positions 9..10 complete nothing
    (4, 1, 9, 2, []),
    # a window of three steps from 5: position 7 completes group 1
    (4, 1, 5, 3, [1]),
    # two positions a row, a page of 4 rows: rows 3, 4 (a page's edge)
    (2, 2, 6, 4, [3, 4]),
])
def test_merge_tail_pages_of_a_leaf_with_a_row_a_group(per, kt, ts, n_rows,
                                                       want):
    """A leaf that holds ONE row a group of `per` positions (a pooled
    index key): tail row j is row ts // per + j of the lane, and only the
    rows that the block's positions COMPLETED are written, each exactly
    once, into the page that holds the group."""
    from ray_tpu.ops.paged_attention import merge_tail_pages

    page_rows, w = 8 // per if per == 2 else 4, 128
    pages = jnp.zeros((7, 1, page_rows, w), jnp.float32)
    tail = 1.0 + jnp.arange(2 * kt, dtype=jnp.float32).reshape(
        2, 1, kt, 1) * jnp.ones((w,), jnp.float32)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    tstart = jnp.asarray([ts, 0], jnp.int32)
    out = merge_tail_pages(pages, tail, table, tstart, n_rows, per=per)
    got = {}
    for p, _, r in np.argwhere(np.asarray(out != 0).any(-1)):
        if p == 0:
            continue        # the trash page takes the rows not completed
        lane, col = divmod(int(p) - 1, 3)
        got.setdefault(lane, []).append(
            (col * page_rows + int(r), float(out[p, 0, r, 0])))
    rows0 = sorted(got.get(0, []))
    assert [g for g, _ in rows0] == want
    assert [v for _, v in rows0] == [1.0 + j for j in range(len(want))]
    # lane 1 started at 0: its block completed n_rows // per groups
    assert [g for g, _ in sorted(got.get(1, []))] == list(
        range(n_rows // per))
