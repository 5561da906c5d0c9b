"""The engine thread's own timeline (PR 25): `llm.loop.*` phase spans,
the `loop` counters of `LLMEngine.stats()`, the kernels' names, and the
benchmark's readers of them; and the engine running its prefill plans
(PR 26; the planner alone is in test_prefill_plan.py).  CPU, debug-sized
model, a few seconds.
"""
import random
import time

import pytest

PHASES = ("admit", "prefill_dispatch", "prefill_sync", "fund",
          "decode_dispatch", "decode_sync", "deliver", "idle")


@pytest.fixture(scope="module")
def small():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=128, max_seq=256, remat=False, dtype=jnp.float32)
    return cfg, llama.init_params(jax.random.PRNGKey(7), cfg)


def _engine(small, **kw):
    from ray_tpu.serve.llm import LLMEngine

    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 256)
    kw.setdefault("page_size", 16)
    kw.setdefault("steps_per_sync", 4)
    return LLMEngine(small[0], small[1], seed=0, **kw)


def _one_wave(eng, prompts, max_new_tokens, cache_ok=()):
    """Queue the prompts while the loop is stopped, so they ride ONE
    wave (as the benchmark's warm-up does), and wait for them;
    `cache_ok` names the rows that may match the prefix cache."""
    eng.stop()
    futs = [eng.submit(p, max_new_tokens=max_new_tokens,
                       _cache_ok=i in cache_ok)
            for i, p in enumerate(prompts)]
    eng.start()
    return [f.result(timeout=120.0) for f in futs]


def _prompt(n, off=0):
    return [(i * 7 + off) % 127 + 1 for i in range(n)]


def _dispatch_spans(eng):
    return [s for s in _loop_spans(eng._loop_trace[0])
            if s["name"] == "llm.loop.prefill_dispatch"]


def _loop_spans(trace_id=None):
    from ray_tpu import tracing

    out = [r for r in tracing.snapshot()
           if r["name"].startswith("llm.loop.")
           and (trace_id is None or r["tid"] == trace_id)]
    return sorted(out, key=lambda r: r["t0"])


@pytest.fixture(scope="module")
def traced_run(small):
    """Two waves and twenty decode windows on a warm engine: the spans
    of the engine's own trace, its root, and the engine.  ONE run for
    the cases that only read a sound warm run's spans and counters (they
    change nothing of it); a case that needs an engine of its own says
    why where it builds it."""
    from ray_tpu import tracing

    eng = _engine(small)                # K = 4
    eng.start()
    try:
        # warm both wave widths and the decode program
        _one_wave(eng, [_prompt(100, i) for i in range(3)], 3)
        _one_wave(eng, [_prompt(40)], 3)
        time.sleep(0.15)
        # the measured stretch starts here (stop/start above leaves the
        # thread's timeline with holes: there is no thread in them)
        mark, steps0 = time.time(), eng.work["decode_steps"]
        first = [eng.submit(_prompt(100, i), max_new_tokens=81,
                            _cache_ok=False) for i in range(3)]
        while eng.work["decode_steps"] < steps0 + 5 * 4:     # 5 windows
            time.sleep(0.002)
        second = eng.submit(_prompt(40, 9), max_new_tokens=9,
                            _cache_ok=False)
        for f in first + [second]:
            f.result(timeout=120.0)
        time.sleep(0.15)
    finally:
        eng.stop()
    tid, root_sid = eng._loop_trace
    roots = [r for r in tracing.snapshot() if r["name"] == "llm.engine"
             and r["tid"] == tid]
    spans = [s for s in _loop_spans(tid) if s["t1"] > mark]
    return eng, spans, roots, root_sid


def test_phases_partition_the_loop_thread(traced_run):
    eng, spans, _, _ = traced_run
    assert {s["name"][len("llm.loop."):] for s in spans} == set(PHASES)
    assert sum(s["name"] == "llm.loop.decode_dispatch" for s in spans) >= 20
    assert sum(s["name"] == "llm.loop.prefill_dispatch" for s in spans) == 2
    overlap = uncovered = 0.0
    end = spans[0]["t1"]
    for s in spans[1:]:
        if s["t0"] < end:
            overlap += min(end, s["t1"]) - s["t0"]
        else:
            uncovered += s["t0"] - end
        end = max(end, s["t1"])
    busy = sum(s["t1"] - s["t0"] for s in spans
               if s["name"] != "llm.loop.idle")
    assert overlap == 0.0
    assert uncovered < 0.02 * busy, (uncovered, busy)


def test_phases_hang_off_the_engine_root_and_iter_is_monotone(traced_run):
    eng, spans, roots, root_sid = traced_run
    assert len(roots) == 1 and roots[0]["sid"] == root_sid
    assert roots[0]["t0"] == roots[0]["t1"] and roots[0]["par"] == ""
    assert roots[0]["attrs"] == {
        "engine": eng.name, "max_batch": 4, "steps_per_sync": 4,
        "page_size": 16}
    assert all(s["par"] == root_sid for s in spans)
    iters = [s["attrs"]["iter"] for s in spans
             if s["name"] != "llm.loop.idle"]
    assert iters == sorted(iters) and len(set(iters)) >= 20
    # one iteration's phases come in the loop's order
    order = {p: i for i, p in enumerate(PHASES)}
    by_iter: dict = {}
    for s in spans:
        if "iter" in s["attrs"]:
            by_iter.setdefault(s["attrs"]["iter"], []).append(
                order[s["name"][len("llm.loop."):]])
    assert all(v == sorted(v) and len(v) == len(set(v))
               for v in by_iter.values())
    disp = [s for s in spans if s["name"] == "llm.loop.prefill_dispatch"]
    assert disp[-1]["attrs"]["rows"] == 1
    assert set(disp[-1]["attrs"]) == {
        "iter", "rows", "width_bucket", "len_bucket", "true_tokens",
        "padded_tokens", "chunks", "plan", "cpu_ms"}
    dec = [s for s in spans if s["name"] == "llm.loop.decode_dispatch"]
    assert {s["attrs"]["steps"] for s in dec} == {4}
    assert max(s["attrs"]["lanes"] for s in dec) == 4


def test_consecutive_idle_iterations_are_one_span(small):
    from ray_tpu import tracing

    # its own: an engine that is left idle, its idle spans counted from its start
    eng = _engine(small)
    eng.start()
    try:
        _one_wave(eng, [_prompt(40)], 6)        # warm
        time.sleep(0.1)
        tracing.clear()
        time.sleep(0.4)                         # >= 7 waits time out
        eng.generate(_prompt(40, 3), max_new_tokens=6, _cache_ok=False)
        # the reply is out before the thread is back in its wait: stop
        # only once the iteration after the last deliver has funded
        deadline = time.time() + 30.0
        while _loop_spans(eng._loop_trace[0])[-1]["name"] \
                != "llm.loop.fund" and time.time() < deadline:
            time.sleep(0.005)
    finally:
        eng.stop()
    spans = _loop_spans(eng._loop_trace[0])
    first_admit = next(s for s in spans if s["name"] == "llm.loop.admit")
    idle = [s for s in spans if s["name"] == "llm.loop.idle"
            and s["t0"] < first_admit["t0"]]
    assert len(idle) == 1
    assert idle[0]["t1"] - idle[0]["t0"] >= 0.35
    # the submit woke the loop: the next phase is the admit that took it
    assert 0.0 <= first_admit["t0"] - idle[0]["t1"] < 0.05
    assert first_admit["attrs"]["admitted"] == 1
    # nothing is recorded inside the stretch: what runs there runs
    # under the idle phase, in the recorder as in a profiler trace
    assert not [s for s in spans if s is not idle[0]
                and idle[0]["t0"] <= s["t0"] < idle[0]["t1"]]
    # the stretch after the request is closed when the loop stops
    assert spans[-1]["name"] == "llm.loop.idle"
    assert eng.stats()["loop"]["phase_s"]["idle"] >= 0.35


def test_prefill_counters_by_hand(small):
    from ray_tpu.serve.prefill_plan import FLOOR_TOKENS

    # its own: the counters are those of this test's waves alone
    eng = _engine(small)        # 4 lanes: widths {1, 4}, buckets 32 ... 256
    eng.start()
    try:
        s0 = eng.stats()
        _one_wave(eng, [_prompt(100, i) for i in range(3)], 1)
        s1 = eng.stats()
        _one_wave(eng, [_prompt(200, 1), _prompt(20, 2), _prompt(100, 3)], 1)
        s2 = eng.stats()
    finally:
        eng.stop()
    assert (eng._width_buckets, FLOOR_TOKENS) == ([1, 4], 256)

    def delta(a, b):
        return {k: b["loop"][k] - a["loop"][k] for k in b["loop"]
                if not isinstance(b["loop"][k], dict)}     # the splits

    # three equal rows: 4 x 128 = 512 positions, under three 1 x 128 at
    # the floor each (768): one program, as arrival order gave
    d = delta(s0, s1)
    assert d["prefill_padded_tokens"] == 4 * 128
    assert d["prefill_true_tokens"] == 300
    assert s1["loop"]["prefill_true_tokens"] == s1["prefill_tokens"]
    assert d["decode_steps"] == 0       # one token each: prefill only
    assert (d["prefill_programs"], d["prefill_waves"],
            d["prefill_waves_split"]) == (1, 1, 0)
    # 200, 20 and 100 tokens: arrival order gave 4 x 256 = 1,024; by
    # length, {20, 100} in 4 x 128 (512) and 200 in 1 x 256 (256) cost 768,
    # as three 1-wide programs at the floor would: the tie goes to two
    d = delta(s1, s2)
    assert d["prefill_padded_tokens"] == 4 * 128 + 1 * 256
    assert d["prefill_true_tokens"] == 320
    assert (d["prefill_programs"], d["prefill_waves"],
            d["prefill_waves_split"]) == (2, 1, 1)
    first, second = [{k: v for k, v in s["attrs"].items() if k != "cpu_ms"}
                     for s in _dispatch_spans(eng)]
    assert first == {
        "iter": first["iter"], "rows": 3, "chunks": 1, "plan": "4x128",
        "width_bucket": 4, "len_bucket": 128, "true_tokens": 300,
        "padded_tokens": 512}
    assert second == {
        "iter": second["iter"], "rows": 3, "chunks": 2,
        "plan": "4x128,1x256", "width_bucket": 4, "len_bucket": 256,
        "true_tokens": 320, "padded_tokens": 768}


@pytest.mark.parametrize("kind", ["paged", "cached_prefix"])
def test_mixed_wave_gives_the_tokens_of_one_at_a_time(small, kind):
    kw = {"prefix_cache": True} if kind == "cached_prefix" else {}
    # arrival order is not length order; three length buckets and more
    prompts = [_prompt(200, 1), _prompt(20, 2), _prompt(100, 3),
               _prompt(50, 4)]
    plan, cached = "8x64,1x128,1x256", ()
    # two of their own: one serves the prompts one at a time, the other in one mixed wave
    ref_eng = _engine(small, max_batch=8)
    ref_eng.start()
    eng = _engine(small, max_batch=8, **kw)
    eng.start()
    try:
        if kind == "cached_prefix":
            # 48 committed tokens = 3 pages; row 4 shares them and
            # prefills a 30-token suffix beside a 25-token full row
            eng.generate(_prompt(48, 9), max_new_tokens=2)
            prompts += [_prompt(48, 9) + _prompt(30, 5), _prompt(25, 6)]
            cached = (4,)
        ref = [ref_eng.generate(p, max_new_tokens=9, _cache_ok=False)["tokens"]
               for p in prompts]
        s0 = eng.stats()
        got = [r["tokens"] for r in
               _one_wave(eng, prompts, 9, cache_ok=cached)]
        s1 = eng.stats()
    finally:
        eng.stop()
        ref_eng.stop()
    assert got == ref
    assert all(len(t) == 9 for t in got)
    span = _dispatch_spans(eng)[-1]["attrs"]
    assert span["plan"] == plan and span["rows"] == len(prompts)
    d = {k: s1["loop"][k] - s0["loop"][k] for k in
         ("prefill_programs", "prefill_waves", "prefill_waves_split")}
    assert d == {"prefill_programs": plan.count(",") + 1,
                 "prefill_waves": 1, "prefill_waves_split": 1}
    if kind == "cached_prefix":
        assert s1["prefix_hit_tokens"] - s0["prefix_hit_tokens"] == 48
        assert s1["prefill_tokens"] - s0["prefill_tokens"] \
            == 200 + 20 + 100 + 50 + 30 + 25


def test_no_compile_after_a_warmup_of_equal_rows(small):
    """`bench_warmup`'s sequence (stop, submit `w` equal prompts, start)
    for every (width, bucket) the traffic can reach, then mixed waves of
    that range: the jitted prefill programs' caches do not grow."""
    # its own: the programs' caches are counted from a cold engine
    eng = _engine(small, max_batch=8)
    lo, hi = 33, 128
    buckets = [b for b in eng._buckets if 64 <= b <= 128]
    eng.start()
    try:
        for b in buckets:
            for w in eng._width_buckets:
                _one_wave(eng, [[1 + (i + j) % 97 for j in range(min(b, hi))]
                                for i in range(w)], 1)
        programs = (eng._prefill_fwd, eng._scatter_pages)
        sizes = [f._cache_size() for f in programs]
        assert sizes[0] == len(buckets) * len(eng._width_buckets)
        rng = random.Random(5)
        s0 = eng.stats()["loop"]
        for n in (1, 2, 3, 4, 5, 8, 8, 6, 2, 7):
            _one_wave(eng, [_prompt(rng.randint(lo, hi), rng.randint(0, 90))
                            for _ in range(n)], 1)
        s1 = eng.stats()["loop"]
    finally:
        eng.stop()
    assert [f._cache_size() for f in programs] == sizes
    assert s1["program_builds"] == s0["program_builds"]
    assert s1["program_build_s"] == s0["program_build_s"]
    assert s1["prefill_waves"] - s0["prefill_waves"] == 10
    assert s1["prefill_waves_split"] > s0["prefill_waves_split"]
    plans = {p for s in _dispatch_spans(eng)
             for p in s["attrs"]["plan"].split(",")}
    assert plans <= {f"{w}x{b}" for w in eng._width_buckets for b in buckets}


def test_a_routed_engine_plans_over_its_own_programs_and_builds_none_after():
    """A model that says its programs stream more than a position
    multiplies (`prefill_params`): the floor and the programs follow the
    ratio, `bench_warmup`'s walk over `_width_buckets` x buckets runs
    every built program the range reaches, and mixed waves after it
    build nothing; `prefill_programs_at_floor` and
    `prefill_floor_positions` by hand."""
    from ray_tpu.models import lfm2, named_config
    from ray_tpu.serve.llm import LLMEngine

    cfg = named_config("lfm2-debug")
    # 2 routed layers x 8 experts x 3 x 128 x 128 = 786,432 of the
    # 1,067,008 matmul parameters of the layers; a position multiplies
    # 2 of 8: 477,184.  256 x 1,067,008 // 477,184 = 572 positions.
    assert lfm2.prefill_params(cfg) == (1_067_008, 477_184)
    eng = LLMEngine(cfg, seed=0, max_batch=8, max_len=128, page_size=16,
                    steps_per_sync=4)
    assert eng._width_buckets == [1, 2, 4, 8]
    built = {"1x128", "2x128", "4x64", "4x128", "8x32", "8x64", "8x128"}
    assert {f"{w}x{b}" for w, b in eng._prefill_programs} == built
    lo, hi = 33, 120        # a prompt is shorter than max_len
    eng.start()
    try:
        for b in (64, 128):
            for w in eng._width_buckets:
                _one_wave(eng, [[1 + (i + j) % 97 for j in range(min(b, hi))]
                                for i in range(w)], 1)
        warm = {p for s in _dispatch_spans(eng)
                for p in s["attrs"]["plan"].split(",")}
        assert warm == built - {"8x32"}     # no prompt maps to bucket 32
        programs = (eng._prefill_fwd, eng._scatter_pages)
        sizes = [f._cache_size() for f in programs]
        assert sizes[0] == len(warm)
        s0 = eng.stats()["loop"]
        assert s0["prefill_floor_positions"] == 572
        assert s0["prefill_programs"] == 8      # one a (width, bucket) pair
        assert s0["prefill_programs_at_floor"] == 7     # 8 x 128 is past it
        _one_wave(eng, [_prompt(40, 1), _prompt(100, 2)], 1)    # 2 x 128
        _one_wave(eng, [_prompt(100, i) for i in range(8)], 1)  # 8 x 128
        _one_wave(eng, [_prompt(n, n) for n in (40, 50, 60)], 1)  # 4 x 64
        s1 = eng.stats()["loop"]
        rng = random.Random(40)
        for n in (1, 2, 3, 4, 5, 8, 8, 6, 2, 7):
            _one_wave(eng, [_prompt(rng.randint(lo, hi), rng.randint(0, 90))
                            for _ in range(n)], 1)
        s2 = eng.stats()["loop"]
    finally:
        eng.stop()
    assert [s["attrs"]["plan"] for s in _dispatch_spans(eng)[8:11]] \
        == ["2x128", "8x128", "4x64"]
    assert (s1["prefill_programs"] - s0["prefill_programs"],
            s1["prefill_programs_at_floor"]
            - s0["prefill_programs_at_floor"]) == (3, 2)
    assert [f._cache_size() for f in programs] == sizes
    assert s2["program_builds"] == s0["program_builds"]
    assert s2["prefill_waves"] - s1["prefill_waves"] == 10
    assert {p for s in _dispatch_spans(eng)
            for p in s["attrs"]["plan"].split(",")} == warm


def test_lane_steps_live_by_hand(small):
    # its own: the counters are those of this test's requests alone
    eng = _engine(small)                # K = 4, 4 lanes
    eng.start()
    try:
        s0 = eng.stats()["loop"]
        # the first token comes from prefill, 8 more = 2 windows
        _one_wave(eng, [_prompt(20, i) for i in range(3)], 9)
        s1 = eng.stats()["loop"]
    finally:
        eng.stop()
    assert s1["decode_steps"] - s0["decode_steps"] == 8
    assert s1["lane_steps_live"] - s0["lane_steps_live"] == 3 * 4 * 2
    assert set(s1["phase_s"]) == set(PHASES)
    assert all(s1["phase_s"][p] >= s0["phase_s"][p] for p in PHASES)
    assert s1["phase_s"]["decode_sync"] > s0["phase_s"]["decode_sync"]


def _blocks_by_brute_force(sq, lengths, bq, bk):
    """Reduce the causal-and-length mask [row, q, k] by blocks: a triple
    is work if its query block holds a true row and any of its (q, k)
    is under the diagonal."""
    import numpy as np

    pos = np.arange(sq)
    causal = pos[:, None] >= pos[None, :]
    n = 0
    for length in lengths:
        for q0 in range(0, sq, bq):
            if q0 >= length:
                continue
            n += sum(bool(causal[q0:q0 + bq, k0:k0 + bk].any())
                     for k0 in range(0, sq, bk))
    return n


@pytest.mark.parametrize("sq,bq,bk,lengths", [
    (512, 128, 256, (1,)), (512, 128, 256, (127,)), (512, 128, 256, (128,)),
    (512, 128, 256, (129,)), (512, 128, 256, (512,)),
    (512, 128, 256, (1, 128, 129)), (512, 128, 256, (127, 512, 300)),
    (1024, 512, 1024, (33, 600, 1024)), (96, 96, 96, (5, 96)),
    (2048, 512, 1024, (1536,)), (8192, 512, 1024, (4097, 6144, 8192)),
], ids=str)
def test_attn_blocks_against_the_mask_reduced_by_blocks(sq, bq, bk, lengths):
    from ray_tpu.ops.flash_attention import attn_blocks

    assert attn_blocks(sq, lengths, bq, bk) == \
        _blocks_by_brute_force(sq, lengths, bq, bk)


def test_prefill_attn_blocks_are_the_programs_the_engine_dispatched(small):
    """`loop.prefill_attn_blocks` is `attn_blocks` summed over the
    full-prompt prefill programs, from the lengths sent with each (a
    program's padding rows repeat its last); `_dense` is rows x query
    blocks x key blocks, the grid `flash_fwd` walked before it took
    lengths."""
    from ray_tpu.ops.flash_attention import attn_blocks, fit_blocks

    # its own: the dispatch spans and the counter are those of this test's waves alone
    eng = _engine(small)
    sent = []
    fwd = eng._prefill_fwd

    def recording(params, tokens, lens, *a):
        sent.append((tokens.shape, [int(n) for n in lens]))
        return fwd(params, tokens, lens, *a)

    eng._prefill_fwd = recording
    eng.start()
    try:
        _one_wave(eng, [_prompt(100, 1), _prompt(20, 2), _prompt(200, 3)], 3)
        _one_wave(eng, [_prompt(40)], 3)
        loop = eng.stats()["loop"]
    finally:
        eng.stop()
    assert len(sent) >= 2
    want = dense = 0
    for (w, b), lens in sent:
        bq, bk = fit_blocks(b, b)
        want += attn_blocks(b, lens, bq, bk)
        dense += w * (b // bq) * (b // bk)
    assert loop["prefill_attn_blocks"] == want
    assert loop["prefill_attn_blocks_dense"] == dense
    assert 0 < want <= dense


def test_attn_steps_by_hand(small):
    """Pages of 16 rows, 16 table columns, 4 lanes: three requests of 20
    rows hold two pages each in both of their windows (block starts 20
    and 24), so the attention kernel's grid is 3 x 2 steps a window of
    the 4 x (16 + 1) a grid over lanes and columns would walk; the
    dispatch span carries the window's steps."""
    # its own: the counters are those of this test's one request alone
    eng = _engine(small)                # K = 4
    eng.start()
    try:
        s0 = eng.stats()["loop"]
        _one_wave(eng, [_prompt(20, i) for i in range(3)], 9)
        s1 = eng.stats()["loop"]
    finally:
        eng.stop()
    assert s1["attn_steps"] - s0["attn_steps"] == 2 * 3 * 2
    assert s1["attn_steps_dense"] - s0["attn_steps_dense"] == 2 * 4 * 17
    spans = [s for s in _loop_spans(eng._loop_trace[0])
             if s["name"] == "llm.loop.decode_dispatch"]
    assert [s["attrs"]["attn_steps"] for s in spans] == [6, 6]
    assert [s["attrs"]["lanes"] for s in spans] == [3, 3]


def test_counters_advance_with_tracing_off(small):
    from ray_tpu import tracing

    tracing.set_enabled(False)
    try:
        tracing.clear()
        # its own: built and run with tracing off
        eng = _engine(small)
        eng.start()
        try:
            _one_wave(eng, [_prompt(20, i) for i in range(2)], 9)
            st = eng.stats()
            loop = st["loop"]
        finally:
            eng.stop()
        assert loop["decode_steps"] == 8 and loop["lane_steps_live"] == 16
        assert loop["prefill_padded_tokens"] == 4 * 32
        assert loop["phase_s"]["decode_dispatch"] > 0
        assert loop["phase_s"]["prefill_dispatch"] > 0
        # and what PR 36 records beside them: no recorder needed
        assert loop["phase_cpu_s"]["decode_dispatch"] > 0
        assert loop["phase_cpu_s"]["prefill_dispatch"] > 0
        # this engine's own programs, at least (the counters are the
        # process's: an earlier test's engine may have built the rest)
        assert loop["program_builds"] >= 1 and loop["program_build_s"] > 0
        assert st["threads"]["by_name"]["llm-engine"] > 0
        assert eng._loop_trace is None
        assert not [r for r in tracing.snapshot()
                    if r["name"].startswith("llm.")]
    finally:
        tracing.set_enabled(True)


# ------------------------- PR 36: did the thread RUN where it was, who
# ------------------------- ran beside it, and what was built
HOST_PHASES = ("admit", "prefill_dispatch", "fund", "decode_dispatch",
               "deliver")


def test_phase_cpu_beside_phase_wall(traced_run):
    eng, spans, _, _ = traced_run
    loop = eng.stats()["loop"]
    assert set(loop["phase_cpu_s"]) == set(loop["phase_s"]) == set(PHASES)
    for p in PHASES:
        # a thread cannot run longer than the wall (1 ms: the two clocks
        # are read one after the other)
        assert 0.0 <= loop["phase_cpu_s"][p] <= loop["phase_s"][p] + 1e-3, p
    # the waits burn no CPU: the engine thread sleeps in them
    assert loop["phase_cpu_s"]["idle"] < 0.5 * loop["phase_s"]["idle"]
    assert spans and all("cpu_ms" in s["attrs"] for s in spans)
    for s in spans:
        assert 0.0 <= s["attrs"]["cpu_ms"] \
            <= (s["t1"] - s["t0"]) * 1e3 + 1.0, s


def _stood_and_rival(eng, windows=20):
    """Seconds the engine thread stood in its host phases, and CPU
    seconds the ledger's row `serve-call` gained (the rival is named as
    a caller's thread is), over `windows` decode windows of one
    request."""
    s0 = eng.stats()
    eng.generate(_prompt(40, 3), max_new_tokens=4 * windows + 1,
                 _cache_ok=False)
    s1 = eng.stats()

    def stood(s):
        return sum(s["loop"]["phase_s"][p] - s["loop"]["phase_cpu_s"][p]
                   for p in HOST_PHASES)

    def rival(s):
        return s["threads"]["by_name"].get("serve-call", 0.0)

    return stood(s1) - stood(s0), rival(s1) - rival(s0)


@pytest.mark.parametrize("rival", ["spins", "sleeps"])
def test_a_rival_thread_shows_in_stood_time_and_in_the_ledger(small, rival):
    """A thread that spins in pure Python holds the GIL a switch
    interval at a time: the engine thread stands in its host phases
    each time it comes back from native code, and the ledger shows the
    rival's CPU.  One that sleeps raises neither."""
    import sys
    import threading

    stop = threading.Event()

    def spin():
        n = 0
        while not stop.is_set():
            n += 1

    def sleep():
        while not stop.wait(0.01):
            pass

    t = threading.Thread(target=spin if rival == "spins" else sleep,
                         name="serve-call_0", daemon=True)
    interval = sys.getswitchinterval()
    # its own: a rival thread runs beside its loop
    eng = _engine(small)
    eng.start()
    try:
        _one_wave(eng, [_prompt(40)], 9)        # warm
        alone, none = _stood_and_rival(eng)
        # a handoff costs the engine thread one interval: long enough to
        # stand out from a busy box's scheduling
        sys.setswitchinterval(0.05)
        t.start()
        beside, rival_cpu = _stood_and_rival(eng)
    finally:
        sys.setswitchinterval(interval)
        stop.set()
        t.join(timeout=30.0)
        eng.stop()
    assert not t.is_alive() and none == 0.0
    if rival == "spins":
        # twenty windows, at least one handoff each
        assert beside > alone + 0.4, (alone, beside)
        assert rival_cpu > 0.4, rival_cpu
    else:
        # under what the spinner must pass, not under a scheduler's
        # whim: both are sums of wall less CPU over twenty windows, and
        # a busy box or a CPU clock that ticks moves either by tenths
        assert beside < alone + 0.4, (alone, beside)
        assert rival_cpu < 0.1, rival_cpu


def _burn(seconds):
    t_end = time.thread_time() + seconds
    while time.thread_time() < t_end:
        pass


def test_the_ledger_sums_a_pool_under_its_prefix(small):
    import concurrent.futures
    import threading

    def burn(barrier):
        barrier.wait(timeout=30.0)      # one task a thread of the pool
        _burn(0.03)

    # its own: a pool of rival threads runs beside its loop
    eng = _engine(small)
    pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=3, thread_name_prefix="serve-call")
    try:
        barrier = threading.Barrier(3)
        for f in [pool.submit(burn, barrier) for _ in range(3)]:
            f.result(timeout=60.0)
        led = eng.stats()["threads"]
    finally:
        pool.shutdown()
    by = led["by_name"]
    assert set(led) == {"wall_s", "process_cpu_s", "by_name"}
    assert led["wall_s"] <= time.time()
    assert by["serve-call"] >= 3 * 0.03
    assert by["MainThread"] > 0
    # the process's clock also holds the native threads and the ended
    assert led["process_cpu_s"] >= sum(by.values())


@pytest.mark.parametrize("name,row", [
    ("actor-0000000375f5_0", "actor"),          # an actor's executor
    ("actor-0000000375f5-io_1", "actor"),       # and its named group's
    (None, "other"),            # "Thread-<n> (serve)": a connection's
    ("kv-store", "other"),
    ("llm-stall-watch", "llm-stall-watch"),     # PR 50: the stall watcher
])
def test_the_ledger_rows_are_a_fixed_set(name, row):
    """However threads are named and however many come and go, the
    ledger's rows (and with them the labels of
    `serve_llm_thread_cpu_seconds` and the engine's `_metrics_last`) are
    `_THREAD_ROWS` and `other`; a thread that has ended is left out, not
    asked for its clock."""
    import threading

    from ray_tpu.serve import llm

    burnt, leave = threading.Event(), threading.Event()

    def serve():
        _burn(0.03)
        burnt.set()
        leave.wait(30.0)

    t = threading.Thread(target=serve, name=name, daemon=True)
    before = llm._thread_cpu_ledger()["by_name"].get(row, 0.0)
    t.start()
    try:
        assert burnt.wait(30.0)
        by = llm._thread_cpu_ledger()["by_name"]
    finally:
        leave.set()
        t.join(timeout=30.0)
    assert set(by) <= set(llm._THREAD_ROWS) | {"other"}, by
    assert by[row] >= before + 0.03 - 1e-6
    assert not t.is_alive()
    # its CPU went with it: the row is what the live threads hold
    gone = llm._thread_cpu_ledger()["by_name"]
    assert gone.get(row, 0.0) <= by[row] - 0.03 + 0.02, (by, gone)


def _build_spans():
    from ray_tpu import tracing

    return [r for r in tracing.snapshot() if r["name"] == "llm.program_build"]


def test_an_unwarmed_shape_is_three_build_spans_and_a_count(small):
    """A prompt whose length bucket the warm-up did not cover builds the
    prefill program of that shape (and the page scatter of its pages):
    three `llm.program_build` spans a program, on the recorder's clock,
    and `program_builds` up by the compile stages; a second wave of the
    shape builds nothing."""
    from ray_tpu import tracing

    # its own: a shape it was never warmed for is built in mid-run
    eng = _engine(small)
    eng.start()
    try:
        _one_wave(eng, [_prompt(40)], 9)        # 1 x 64, and the decode
        tracing.clear()
        s0, t0 = eng.stats()["loop"], time.time()
        _one_wave(eng, [_prompt(100, 1)], 9)    # 1 x 128: not covered
        s1, t1 = eng.stats()["loop"], time.time()
        built = _build_spans()
        tracing.clear()
        _one_wave(eng, [_prompt(100, 2)], 9)
        s2 = eng.stats()["loop"]
        again = _build_spans()
    finally:
        eng.stop()
    compiles = [b for b in built if b["attrs"]["stage"] == "compile"]
    prefill = [b for b in built if "_prefill_fwd_only" in b["attrs"]["fun"]
               and b["attrs"]["depth"] == 0]
    assert [b["attrs"]["stage"] for b in prefill] == [
        "trace", "lower", "compile"]
    assert all(b["attrs"]["thread"] == "llm-engine" for b in prefill)
    assert prefill[2]["attrs"]["cache"] in ("hit", "miss", "off")
    assert all("cache" not in b["attrs"] for b in prefill[:2])
    # JAX's own stamps: inside the stretch, one stage after the other
    assert t0 <= prefill[0]["t0"] and prefill[2]["t1"] <= t1
    assert all(a["t1"] <= b["t0"] + 1e-6
               for a, b in zip(prefill, prefill[1:]))
    # the prefill program and the scatter of its pages, nothing else
    assert len(compiles) == 2
    assert s1["program_builds"] - s0["program_builds"] == len(compiles)
    assert s1["program_cache_misses"] - s0["program_cache_misses"] == sum(
        b["attrs"]["cache"] == "miss" for b in compiles)
    # outermost stages only: a helper traced inside is in its caller's
    outer_s = sum(b["t1"] - b["t0"] for b in built
                  if b["attrs"]["depth"] == 0)
    assert s1["program_build_s"] - s0["program_build_s"] \
        == pytest.approx(outer_s, abs=1e-4)
    # (a helper an earlier test traced at this shape is not traced again)
    assert all(b["t1"] - b["t0"] >= 1e-3 for b in built
               if b["attrs"]["depth"] and b["attrs"]["stage"] != "compile")
    # no build roots a trace of its own (`tracing.slowest` would rank
    # each stage as a request): all hang off the process's one root
    from ray_tpu.serve import llm

    assert {(b["tid"], b["par"]) for b in built} == {llm._build_root}
    assert not again
    assert (s2["program_builds"], s2["program_build_s"]) == (
        s1["program_builds"], s1["program_build_s"])


def test_request_scoped_spans_are_what_they_were(small):
    """Names, attrs and count of a traced request's spans: the
    benchmark's readers (decode_windows, prefill_spans_in_trace,
    paged_attn_roofline) read exactly these."""
    from ray_tpu import tracing

    # its own: the trace was cleared, so every span is this request's
    eng = _engine(small)
    eng.start()
    try:
        with tracing.span("client") as _:
            ctx = tracing.current()
            fut = eng.submit(_prompt(40), max_new_tokens=9, _cache_ok=False)
        fut.result(timeout=120.0)
    finally:
        eng.stop()
    mine = sorted((r for r in tracing.snapshot()
                   if r["tid"] == ctx[0] and r["name"] != "client"),
                  key=lambda r: (r["t0"], r["name"]))
    assert all(r["par"] == ctx[1] for r in mine)
    got = [(r["name"], r["attrs"]) for r in mine]
    ttft = [a for n, a in got if n == "llm.first_token"]
    assert list(ttft[0]) == ["ttft_ms"]
    assert sorted(got, key=lambda g: g[0]) == sorted([
        ("llm.queue", {}),
        ("llm.prefill", {"prompt_tokens": 40, "prefill_from": 0,
                         "cached_tokens": 0}),
        ("llm.first_token", ttft[0]),
        ("llm.decode_window", {"steps": 4, "weight_version": 0}),
        ("llm.decode_window", {"steps": 4, "weight_version": 0}),
    ], key=lambda g: g[0])
    win = [r for r in mine if r["name"] == "llm.decode_window"]
    pre = next(r for r in mine if r["name"] == "llm.prefill")
    assert pre["t1"] <= win[0]["t0"] <= win[0]["t1"] <= win[1]["t0"]


def test_lora_request_carries_its_adapter_on_the_prefill_span(small):
    import jax

    from ray_tpu import tracing
    from ray_tpu.models import llama

    # its own: an engine with LoRA slots
    eng = _engine(small, lora_slots=2, lora_rank=4)
    eng.start()
    try:
        eng.load_adapter("t/a", llama.init_lora_adapter(
            jax.random.PRNGKey(1), small[0], 4))
        with tracing.span("client"):
            tid = tracing.current()[0]
            fut = eng.submit(_prompt(12), max_new_tokens=3,
                             model_id="t/a")
        fut.result(timeout=120.0)
    finally:
        eng.stop()
    mine = [r for r in tracing.snapshot() if r["tid"] == tid]
    pre = next(r for r in mine if r["name"] == "llm.prefill")
    assert pre["attrs"]["model_id"] == "t/a" and pre["attrs"]["slot"] >= 1
    assert not [r for r in tracing.snapshot()
                if r["name"] == "serve.adapter_apply"]


def test_operator_metrics(small):
    """The TPOT histogram's own boundaries, and pad factor / occupancy
    as Prometheus counters (the 1 Hz delta path)."""
    from ray_tpu.serve import llm

    # its own: a named engine, whose metrics carry the name
    eng = _engine(small, name="timeline-metrics")
    eng.start()
    try:
        _one_wave(eng, [_prompt(20, i) for i in range(3)], 9)
        eng.stats()                     # forces a flush
    finally:
        eng.stop()
    m = llm._engine_metrics()

    def value(key):
        return next(v["value"] for v in m[key].snapshot()["values"]
                    if v["tags"]["engine"] == "timeline-metrics")

    assert value("prefill_padded_tokens") == 4 * 32
    assert value("prefill_programs") == 1
    assert value("prefill_tokens") == 60
    assert value("lane_steps_live") == 24 and value("decode_steps") == 8
    assert value("attn_steps") == 12 and value("attn_steps_dense") == 136
    # one 4 x 32 program, one block a row: nothing to pass over
    assert value("prefill_attn_blocks") == 4
    assert value("prefill_attn_blocks_dense") == 4
    for key in ("prefill_attn_blocks", "prefill_attn_blocks_dense"):
        assert m[key].snapshot()["name"] == "serve_llm_" + key
    assert m["tpot"].boundaries == [1, 2, 5, 10, 15, 20, 25, 30, 40, 50,
                                    75, 100, 250, 1000]
    # every TPOT the benchmark has read (17-28 ms) no longer shares a bucket
    assert len({sum(x > b for b in m["tpot"].boundaries)
                for x in (17.0, 22.5, 28.0)}) == 3
    assert m["ttft"].boundaries[:4] == [1.0, 5.0, 10.0, 25.0]
    # PR 36: the engine thread's CPU by phase, the process's threads' by
    # name, and the programs built, each under its Prometheus name
    assert [m[k].name for k in ("phase_cpu_s", "thread_cpu_s",
                                "program_builds", "program_build_s")] == [
        "serve_llm_phase_cpu_seconds", "serve_llm_thread_cpu_seconds",
        "serve_llm_program_builds", "serve_llm_program_build_seconds"]

    def rows(key, tag):
        return {v["tags"][tag]: v["value"]
                for v in m[key].snapshot()["values"]
                if v["tags"]["engine"] == "timeline-metrics"}

    by_phase = rows("phase_cpu_s", "phase")
    assert set(by_phase) <= set(PHASES) and by_phase["decode_dispatch"] > 0
    assert 0 < sum(by_phase.values()) <= sum(eng.phase_cpu_s.values()) + 1e-6
    assert rows("thread_cpu_s", "thread")["llm-engine"] > 0
    assert value("program_builds") >= 1 and value("program_build_s") > 0
    # PR 50: the loop's stalls (the warm-up's cold builds are stalls) and
    # the collector's pauses by generation
    assert [m[k].name for k in ("stalls", "stall_s", "gc_pause_s")] == [
        "serve_llm_stalls", "serve_llm_stall_seconds",
        "serve_llm_gc_pause_seconds"]
    loop = eng.stats()["loop"]
    if loop["stalls"]:
        assert value("stalls") == loop["stalls"]
        assert value("stall_s") == pytest.approx(loop["stall_s"], abs=1e-5)
    by_gen = rows("gc_pause_s", "generation")
    assert by_gen and set(by_gen) <= {"0", "1", "2"}
    assert sum(by_gen.values()) <= loop["gc_pause_s"] + 1e-5


# ------------------------- PR 50: a stall of the loop leaves a record of
# ------------------------- itself, and the collector's pauses are spans
def _spans_named(name, since=0.0):
    from ray_tpu import tracing

    return sorted((r for r in tracing.snapshot()
                   if r["name"] == name and r["t0"] >= since),
                  key=lambda r: r["t0"])


def _decoding(eng, windows):
    """A request of `windows` decode windows, submitted; returns its
    future once the engine has run three of them."""
    steps0 = eng.work["decode_steps"]
    fut = eng.submit(_prompt(40, 5), max_new_tokens=4 * windows + 1,
                     _cache_ok=False)
    deadline = time.time() + 60.0
    while eng.work["decode_steps"] < steps0 + 12 and time.time() < deadline:
        time.sleep(0.002)
    return fut


def _hold_the_interpreter(seconds):
    """(function, its argument): keep the interpreter for `seconds` on
    the wall clock, as one long C call that never lets go would (a call
    sized by a count of work holds it half as long on a box that starved
    the sizing): no waiter's timer asks for a handoff meanwhile."""
    import sys

    def hold(seconds):
        interval = sys.getswitchinterval()
        sys.setswitchinterval(60.0)
        try:
            end = time.perf_counter() + seconds
            while time.perf_counter() < end:
                pass
        finally:
            sys.setswitchinterval(interval)

    return hold, seconds


def _rival(fn, arg):
    """Run `fn(arg)` on a thread named as a caller's is; (start, end) on
    the wall clock.  The thread lives on a little, as a pool's does: the
    ledger holds no thread that has ended."""
    import threading

    at = []

    def run():
        at.append(time.time())
        fn(arg)
        at.append(time.time())
        time.sleep(0.2)

    t = threading.Thread(target=run, name="serve-call_0", daemon=True)
    t.start()
    t.join(timeout=120.0)
    assert not t.is_alive()
    return at


class _SleepsOnce:
    """A request's token sink whose sixth `put` sleeps: a callback that
    blocks the engine thread inside `deliver`."""

    def __init__(self, seconds):
        self.seconds, self.n, self.at = seconds, 0, None

    def put(self, tok):
        self.n += 1
        if self.n == 6:
            t0 = time.time()
            time.sleep(self.seconds)
            self.at = (t0, time.time())


def test_a_callback_that_sleeps_in_deliver_is_one_stall_held_by_the_engine(
        small, capfd, monkeypatch):
    import json

    from ray_tpu import tracing
    from ray_tpu.serve import llm

    monkeypatch.setattr(llm, "_stall_blocks", 0)
    # its own: a callback stalls its deliver phase
    eng = _engine(small)
    eng.start()
    try:
        _one_wave(eng, [_prompt(40)], 9)        # warm
        time.sleep(0.1)
        tracing.clear()
        capfd.readouterr()
        s0, mark = eng.stats()["loop"], time.time()
        sink = _SleepsOnce(0.4)
        eng.submit(_prompt(40, 3), max_new_tokens=41, _cache_ok=False,
                   token_queue=sink).result(timeout=120.0)
        time.sleep(0.15)
        s1 = eng.stats()["loop"]
    finally:
        eng.stop()
    stalls = _spans_named("llm.stall", mark)
    # a busy box may add a late wake of its own: the one under test is
    # the one over the sleep
    (st,) = [s for s in stalls if s["t0"] < sink.at[1] and s["t1"] > sink.at[0]]
    a = st["attrs"]
    assert (a["phase"], a["trigger"], a["held"]) == (
        "deliver", "host_phase", "engine")
    assert st["tid"], st["par"] == eng._loop_trace
    assert 300.0 <= a["stood_ms"] <= 520.0
    assert a["stood_ms"] == pytest.approx((st["t1"] - st["t0"]) * 1e3, abs=1.0)
    # inside the provoked interval to 60 ms
    assert -0.06 <= st["t0"] - sink.at[0] <= 0.0
    assert abs(st["t1"] - sink.at[1]) <= 0.06
    # the thread slept: no CPU of its own, the watcher on time
    assert a["engine_cpu_ms"] < 100.0 and a["late_wake_ms"] < 200.0
    assert a["gc_ms"] < 100.0 and a["build_ms"] == 0.0
    assert (a["lanes"], a["pending"]) == (1, 0)
    assert min(a["faults_major"], a["faults_minor"],
               a["switches_involuntary"]) >= 0
    frames = a["engine_frames"].split(" | ")
    assert len(frames) <= 8 and "test_engine_timeline.py" in frames[0]
    assert frames[0].endswith(" put") and "_loop_once" in a["engine_frames"]
    assert isinstance(json.loads(a["by_thread_cpu_ms"]), list)
    counted = [s for s in stalls if s["attrs"]["phase"] != "idle"]
    # a span says which of `loop.stalls` it is
    assert [s["attrs"]["nth"] for s in counted] == list(
        range(s0["stalls"] + 1, s1["stalls"] + 1))
    assert s1["stalls"] - s0["stalls"] == len(counted) >= 1
    assert s1["stall_s"] - s0["stall_s"] == pytest.approx(
        sum(s["attrs"]["stood_ms"] for s in counted) / 1e3, abs=1e-3)
    # every thread's stack on stderr, one block, headed by the span's id
    err = capfd.readouterr().err
    block = err[err.index("llm.stall " + st["sid"]):]
    assert "  thread llm-engine [llm-engine]" in block
    assert "  thread llm-stall-watch [llm-stall-watch]" in block
    assert "  thread MainThread [MainThread]" in block
    assert "test_engine_timeline.py" in block and " put" in block


def test_a_thread_that_keeps_the_interpreter_is_a_late_wake_held_by_it(small):
    import json

    from ray_tpu import tracing
    from ray_tpu.serve import llm

    # half as long again as the latest wake a sound run may show
    fn, arg = _hold_the_interpreter(1.5 * llm.LATE_WAKE_S)
    # its own: a rival thread keeps the interpreter from it
    eng = _engine(small)
    eng.start()
    try:
        _one_wave(eng, [_prompt(40)], 9)        # warm
        tracing.clear()
        s0 = eng.stats()["loop"]
        fut = _decoding(eng, 50)
        held = _rival(fn, arg)
        fut.result(timeout=120.0)
        time.sleep(0.15)
        s1 = eng.stats()["loop"]
    finally:
        eng.stop()
    (st,) = [s for s in _spans_named("llm.stall", held[0] - 0.06)
             if s["t0"] < held[1]]
    a = st["attrs"]
    assert a["trigger"] == "late_wake" and a["held"] == "interpreter"
    assert a["late_wake_ms"] >= llm.LATE_WAKE_S * 1e3
    assert a["phase"] != "idle" and s0["stalls"] < a["nth"] <= s1["stalls"]
    # the missed wake was due within one sleep of the hold's start
    assert -0.01 <= st["t0"] - held[0] <= 0.06
    assert abs(st["t1"] - held[1]) <= 0.06
    rows = json.loads(a["by_thread_cpu_ms"])
    assert rows[0][0] == "serve-call" and rows[0][1] >= 0.2 * a["stood_ms"]
    assert a["process_cpu_ms"] >= rows[0][1] and a["engine_cpu_ms"] < 100.0
    assert s1["stalls"] - s0["stalls"] >= 1
    assert s1["stall_s"] - s0["stall_s"] >= llm.LATE_WAKE_S
    # the rows were read over a stretch that begins at most
    # WATCH_LEDGER_EVERY wakes before the stall, and a wake after it
    assert a["stood_ms"] <= a["ledger_ms"] <= a["stood_ms"] + 1e3 * (
        (llm.WATCH_LEDGER_EVERY + 2) * llm.WATCH_S + 0.06)


def test_a_collection_on_a_rival_thread_is_a_gc_pause_the_stall_accounts_for(
        small, monkeypatch):
    import gc

    from ray_tpu import tracing
    from ray_tpu.serve import llm

    # a collection of a few million objects takes 0.3-0.4 s: the watcher
    # is held to the host rule's constant here, not to twice a sound
    # run's latest wake, so that the heap under test stays small
    monkeypatch.setattr(llm, "LATE_WAKE_S", llm.HOST_STALL_S)
    # its own: a rival thread collects garbage beside it
    eng = _engine(small)
    eng.start()
    gc.collect()
    gc.disable()        # the collection under test is the rival's
    try:
        junk = [[] for _ in range(4_000_000)]
        _one_wave(eng, [_prompt(40)], 9)        # warm
        tracing.clear()
        s0 = eng.stats()["loop"]
        fut = _decoding(eng, 50)
        at = _rival(lambda _: gc.collect(), None)
        fut.result(timeout=120.0)
        time.sleep(0.15)
        s1 = eng.stats()["loop"]
    finally:
        gc.enable()
        eng.stop()
        junk = None     # noqa: F841 - held until here
    (pause,) = [s for s in _spans_named("llm.gc_pause", at[0] - 0.01)
                if s["attrs"]["generation"] == 2 and s["t1"] <= at[1] + 0.01]
    assert pause["t1"] - pause["t0"] >= 0.2
    assert pause["attrs"]["thread"] == "serve-call"
    assert set(pause["attrs"]) == {"generation", "collected",
                                   "uncollectable", "thread"}
    # never a trace of its own: it hangs off the process's one root
    assert (pause["tid"], pause["par"]) == llm._build_root
    (st,) = [s for s in _spans_named("llm.stall", at[0] - 0.06)
             if s["t0"] < at[1]]
    a = st["attrs"]
    assert a["trigger"] == "late_wake" and a["held"] == "interpreter"
    over = min(st["t1"], pause["t1"]) - max(st["t0"], pause["t0"])
    assert over >= 0.2 and a["gc_ms"] == pytest.approx(over * 1e3, abs=2.0)
    assert a["gc_ms"] >= 0.8 * a["stood_ms"]
    assert -0.01 <= st["t0"] - pause["t0"] <= 0.06
    assert abs(st["t1"] - pause["t1"]) <= 0.06
    g0, g1 = s0["gc_by_generation"]["2"], s1["gc_by_generation"]["2"]
    assert g1["pauses"] - g0["pauses"] >= 1
    assert g1["pause_s"] - g0["pause_s"] >= pause["t1"] - pause["t0"] - 1e-3
    assert s1["gc_pauses"] - s0["gc_pauses"] >= 1
    assert s1["gc_pause_s"] - s0["gc_pause_s"] >= g1["pause_s"] - g0["pause_s"] \
        - 1e-5
    assert set(s1["gc_by_generation"]) == {"0", "1", "2"}


def test_a_sound_run_of_fifty_windows_records_no_stall(small):
    from ray_tpu import tracing

    # its own: fifty windows, and the stalls counted from its start
    eng = _engine(small)
    eng.start()
    try:
        _one_wave(eng, [_prompt(40)], 9)        # warm
        for _ in range(3):      # a busy box may stall one run, not three
            time.sleep(0.1)
            tracing.clear()
            s0, mark = eng.stats()["loop"], time.time()
            eng.generate(_prompt(40, 3), max_new_tokens=4 * 50 + 1,
                         _cache_ok=False)
            time.sleep(0.15)
            s1 = eng.stats()["loop"]
            stalls = _spans_named("llm.stall", mark)
            assert s1["stalls"] - s0["stalls"] == sum(
                s["attrs"]["phase"] != "idle" for s in stalls)
            if not stalls:
                break
    finally:
        eng.stop()
    assert s1["decode_steps"] - s0["decode_steps"] == 4 * 50
    assert not stalls and s1["stalls"] == s0["stalls"]
    assert s1["stall_s"] == s0["stall_s"]


def test_a_stall_in_idle_is_a_span_and_is_not_counted(small):
    from ray_tpu import tracing
    from ray_tpu.serve import llm

    fn, arg = _hold_the_interpreter(1.5 * llm.LATE_WAKE_S)
    # its own: the interpreter is kept from it while it idles
    eng = _engine(small)
    eng.start()
    try:
        _one_wave(eng, [_prompt(40)], 9)        # warm
        time.sleep(0.2)                         # the loop is in its wait
        tracing.clear()
        s0 = eng.stats()["loop"]
        held = _rival(fn, arg)
        time.sleep(0.15)
        s1 = eng.stats()["loop"]
    finally:
        eng.stop()
    stalls = [s for s in _spans_named("llm.stall", held[0] - 0.06)
              if s["t0"] < held[1]]
    assert stalls and {s["attrs"]["phase"] for s in stalls} == {"idle"}
    assert stalls[0]["attrs"]["trigger"] == "late_wake"
    assert stalls[0]["attrs"]["held"] == "interpreter"
    assert "iter" not in stalls[0]["attrs"]     # the wait has none
    assert stalls[0]["attrs"]["nth"] == 0
    assert (s1["stalls"], s1["stall_s"]) == (s0["stalls"], s0["stall_s"])


def test_stall_and_gc_counters_advance_with_tracing_off(small):
    import gc

    from ray_tpu import tracing

    tracing.set_enabled(False)
    try:
        tracing.clear()
        # its own: built and run with tracing off
        eng = _engine(small)
        eng.start()
        try:
            _one_wave(eng, [_prompt(40)], 9)    # warm
            # the watcher counts a stall at its first wake after it ended
            # (every 0.05 s): the warm-up's builds are counted before s0
            time.sleep(0.1)
            s0, t0 = eng.stats()["loop"], time.monotonic()
            eng.submit(_prompt(40, 3), max_new_tokens=41, _cache_ok=False,
                       token_queue=_SleepsOnce(0.4)).result(timeout=120.0)
            gc.collect()
            # the watcher counts the stall at its first wake after it
            # ended: a wake a loaded machine may give it late (a whole
            # six-worker run: a fixed 0.15 s was not always enough), so
            # the counter is waited for, not slept for
            deadline = time.monotonic() + 10.0
            while (eng.stats()["loop"]["stalls"] == s0["stalls"]
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            s1, t1 = eng.stats()["loop"], time.monotonic()
        finally:
            eng.stop()
        assert s1["stalls"] - s0["stalls"] >= 1
        # with the recorder off the phase's wall at entry is the watcher's;
        # stalls are stretches of ONE thread, so whatever else a loaded
        # machine makes stall, they sum to no more than the wall between
        # the two readings (the fixed 1.0 s a loaded run passed)
        assert 0.3 <= s1["stall_s"] - s0["stall_s"] <= t1 - t0
        assert s1["gc_pauses"] - s0["gc_pauses"] >= 1
        assert s1["gc_pause_s"] > s0["gc_pause_s"]
        assert s1["gc_by_generation"]["2"]["pauses"] \
            > s0["gc_by_generation"]["2"]["pauses"]
        assert not [r for r in tracing.snapshot()
                    if r["name"].startswith("llm.")]
    finally:
        tracing.set_enabled(True)


def test_the_watcher_has_a_ledger_row_and_ends_with_stop(small):
    import threading

    from ray_tpu.serve import llm

    def watchers():
        return [t for t in threading.enumerate()
                if t.name == "llm-stall-watch"]

    assert "llm-stall-watch" in llm._THREAD_ROWS
    before = len(watchers())
    # its own: the watcher thread is counted before its start and after its stop
    eng = _engine(small)
    assert len(watchers()) == before            # not before start()
    eng.start()
    try:
        eng.start()                             # once, however often
        assert len(watchers()) == before + 1
        eng.generate(_prompt(40), max_new_tokens=9, _cache_ok=False)
        assert "llm-stall-watch" in eng.stats()["threads"]["by_name"]
        # two attribute stores a phase: what the watcher reads
        assert eng._phase_now is None or eng._phase_now[0] in PHASES
    finally:
        eng.stop()
    assert len(watchers()) == before and eng._watch_thread is None
    assert eng._phase_now is None
    eng.start()                                 # and comes back with it
    try:
        assert len(watchers()) == before + 1
    finally:
        eng.stop()
    assert len(watchers()) == before


@pytest.mark.parametrize("trigger,stood,late,cpu,held", [
    ("host_phase", 400.0, 2.0, 5.0, "engine"),      # the watcher on time
    ("sync", 2500.0, 2.0, 5.0, "device"),
    ("late_wake", 400.0, 390.0, 395.0, "interpreter"),  # somebody ran
    ("host_phase", 400.0, 300.0, 395.0, "interpreter"),
    ("late_wake", 400.0, 390.0, 12.0, "process"),   # nobody did
    ("sync", 2500.0, 900.0, 100.0, "process"),
])
def test_held_is_derived_from_the_stalls_own_numbers(trigger, stood, late,
                                                     cpu, held):
    from ray_tpu.serve import llm

    assert llm._stall_held(trigger, stood, late, cpu) == held


# ------------------------------------------------- the kernels' names
def _lowered(fn, *args):
    import jax

    return jax.jit(fn).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("which", ["flash_fwd", "flash_bwd", "paged_attn"])
def test_kernel_names_in_the_lowered_text(which):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops import paged_attention as pa

    q = jnp.zeros((1, 256, 4, 128), jnp.bfloat16)
    k = jnp.zeros((1, 256, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    if which == "flash_fwd":
        text = _lowered(fa.flash_attention, q, k, k)
        assert "flash_fwd" in text and "flash_bwd" not in text
    elif which == "flash_bwd":
        text = _lowered(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)
        assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text
    else:
        B, kvh, rep, hd, page, kt, maxp = 2, 2, 2, 128, 16, 8, 4
        text = _lowered(
            pa.paged_decode_attention,
            jnp.zeros((B, kvh, rep, hd), jnp.bfloat16),
            jnp.zeros((8, kvh, page, hd), jnp.bfloat16),
            jnp.zeros((8, kvh, page, hd), jnp.bfloat16),
            jnp.zeros((B, kvh, kt, hd), jnp.bfloat16),
            jnp.zeros((B, kvh, kt, hd), jnp.bfloat16),
            jnp.zeros((B, maxp), jnp.int32), jnp.full((B,), 20, jnp.int32),
            jnp.full((B,), 16, jnp.int32))
        assert "paged_attn" in text


def test_decoder_scopes_in_the_lowered_text(small):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg, params = small
    text = _lowered(lambda p, t: llama.prefill(p, t, cfg), params,
                    jnp.zeros((2, 32), jnp.int32))
    def scoped(text, scope):        # a name-stack entry, not a file name
        return f'"{scope}/' in text or f"/{scope}/" in text

    for scope in ("embed", "attn_qkv", "rope", "attn", "attn_out", "mlp",
                  "norm"):
        assert scoped(text, scope), scope
    tails = {n: [jnp.zeros((2, cfg.n_kv_heads, 4, cfg.head_dim))
                 for _ in range(cfg.n_layers)] for n in ("k", "v")}
    pages = llama.init_paged_kv_cache(cfg, 2, 8, 16)
    text = _lowered(
        lambda p, pg, tl, t, pos, ts, tab: llama.decode_step_paged(
            p, pg, tl, t, pos, ts, 0, tab, cfg),
        params, {"k": pages["k"], "v": pages["v"]}, tails,
        jnp.zeros((2,), jnp.int32), jnp.full((2,), 5, jnp.int32),
        jnp.full((2,), 5, jnp.int32), jnp.zeros((2, 4), jnp.int32))
    for scope in ("layer_weights", "kv_write", "lm_head", "paged_attn"):
        assert scoped(text, scope), scope


# ------------------------------------------- the benchmark's new readers
def _span(phase, t0, t1, **attrs):
    return {"name": "llm.loop." + phase, "t0": t0, "t1": t1, "tid": "e",
            "attrs": attrs}


def _build(fun, stage, t0, t1, depth=0, **attrs):
    return {"name": "llm.program_build", "t0": t0, "t1": t1, "tid": "b",
            "attrs": {"fun": fun, "stage": stage, "depth": depth,
                      "thread": "llm-engine", **attrs}}


def _stall(t0, t1, **attrs):
    return {"name": "llm.stall", "t0": t0, "t1": t1, "tid": "e",
            "attrs": {"stood_ms": (t1 - t0) * 1e3, **attrs}}


def _gc_pause(t0, t1, generation, thread):
    return {"name": "llm.gc_pause", "t0": t0, "t1": t1, "tid": "b",
            "attrs": {"generation": generation, "collected": 7,
                      "uncollectable": 0, "thread": thread}}


def _synthetic_run(parent=False, pr50=True):
    """Twelve iterations of 100 ms starting at t = 1000: 2 ms admit, 1 ms
    fund, 3 ms decode_dispatch, 90 ms decode_sync, 4 ms deliver; then
    idle.  The chip is idle in the first 10 ms of each iteration (admit +
    fund + dispatch + 4 ms of the sync) and all through the idle phase.
    Since PR 36 the engine thread ran 0.5 of admit's 2 ms, all of fund,
    1 of dispatch's 3 and 0.5 of deliver's 4 (it stood 7 of the 10); the
    replica built 7.5 s of programs before the window and one of 250 ms
    inside it; between the readings of `stats`, 4 s apart, its other
    threads gained 1.0 CPU second.  `parent`: the run of a program from
    before PR 36, which records none of that.  Since PR 50 (`pr50`) the
    loop stalled twice with work waiting: 300 ms in iteration 5's deliver
    (a callback slept; over three of the chip's 10 ms gaps) and 120 ms
    after the traced stretch, inside the profiler's stop, behind a
    `serve-call` thread; once more in `idle` (150 ms of the idle gap: a
    span, no count); and the collector ran 60 times for 350 ms, 250 of
    them one generation-2 pause on a `serve-call` thread."""
    spans, gaps = [], []
    for i in range(12):
        t = 1000.0 + 0.1 * i
        spans += [_span("admit", t, t + .002, iter=i, admitted=0,
                        cpu_ms=0.5),
                  _span("fund", t + .002, t + .003, iter=i, cpu_ms=1.0),
                  _span("decode_dispatch", t + .003, t + .006, iter=i,
                        lanes=3, steps=8, cpu_ms=1.0),
                  _span("decode_sync", t + .006, t + .096, iter=i,
                        cpu_ms=0.1),
                  _span("deliver", t + .096, t + .1, iter=i, cpu_ms=0.5)]
        gaps.append((0.010, 0.1 * i, 0.1 * i + 0.010))
    spans.append(_span("idle", 1001.2, 1001.7, pending=0, cpu_ms=0.0))
    gaps.append((0.5, 1.2, 1.7))
    spans.append({"name": "llm.queue", "t0": 1000.0, "t1": 1002.0,
                  "tid": "r", "attrs": {}})
    loop0 = {"prefill_padded_tokens": 1000, "prefill_true_tokens": 400,
             "lane_steps_live": 50, "decode_steps": 10}
    loop1 = {"prefill_padded_tokens": 9000, "prefill_true_tokens": 2400,
             "lane_steps_live": 2450, "decode_steps": 106}
    stats0, stats1 = {"loop": loop0}, {"loop": loop1}
    if parent:
        for s in spans:
            s["attrs"].pop("cpu_ms", None)
    else:
        spans += [
            _build("_prefill_fwd_only", "trace", 990.0, 991.0),
            _build("attention", "trace", 990.2, 990.7, depth=1),
            _build("jit(_prefill_fwd_only)", "lower", 991.0, 991.5),
            _build("jit(_prefill_fwd_only)", "compile", 991.5, 997.5,
                   cache="hit"),
            _build("jit(_decode_k)", "compile", 1000.5, 1000.75,
                   cache="miss")]
        loop0.update(program_builds=1, program_build_s=7.5,
                     program_cache_misses=0,
                     phase_s={"admit": 1.0}, phase_cpu_s={"admit": 0.5})
        loop1.update(program_builds=2, program_build_s=7.75,
                     program_cache_misses=1,
                     phase_s={"admit": 1.024}, phase_cpu_s={"admit": 0.506})
        stats0["threads"] = {
            "wall_s": 999.0, "process_cpu_s": 20.0,
            "by_name": {"llm-engine": 10.0, "serve-call": 5.0,
                        "raytpu-io": 1.0}}
        stats1["threads"] = {
            "wall_s": 1003.0, "process_cpu_s": 23.0,
            "by_name": {"llm-engine": 11.0, "serve-call": 5.6,
                        "raytpu-io": 1.3, "llm-kv-export": 0.1}}
    if pr50 and not parent:
        spans += [
            _stall(1000.5, 1000.8, phase="deliver", iter=5, nth=4,
                   trigger="host_phase", held="engine", late_wake_ms=1.0,
                   by_thread_cpu_ms='[["serve-call", 2.5]]'),
            _stall(1001.3, 1001.45, phase="idle", nth=0,
                   trigger="late_wake", held="interpreter",
                   late_wake_ms=150.0,
                   by_thread_cpu_ms='[["serve-call", 149.0]]'),
            _stall(1002.5, 1002.62, phase="decode_sync", iter=13, nth=5,
                   trigger="late_wake", held="interpreter",
                   late_wake_ms=120.0,
                   by_thread_cpu_ms='[["serve-call", 118.0]]'),
            _gc_pause(1000.52, 1000.77, 2, "serve-call"),
            _gc_pause(1001.0, 1001.004, 1, "serve-call"),
            _gc_pause(1002.0, 1002.05, 2, "llm-engine"),
            _gc_pause(990.0, 990.3, 2, "MainThread")]    # the set-up's
        loop0.update(stalls=3, stall_s=9.0, gc_pauses=100, gc_pause_s=0.5,
                     gc_by_generation={
                         "0": {"pauses": 90, "pause_s": 0.1},
                         "1": {"pauses": 8, "pause_s": 0.1},
                         "2": {"pauses": 2, "pause_s": 0.3}})
        loop1.update(stalls=5, stall_s=9.42, gc_pauses=160, gc_pause_s=0.85,
                     gc_by_generation={
                         "0": {"pauses": 140, "pause_s": 0.12},
                         "1": {"pauses": 16, "pause_s": 0.13},
                         "2": {"pauses": 4, "pause_s": 0.6}})
    return {
        "spans": spans, "window_wall": (1000.0, 1002.0),
        "trace_wall": (999.9, 1002.9),
        "setup": {"serve_run_s": 20.0, "warmup_s": 9.0},
        "stats": (stats0, stats1),
        "trace": {"start_wall_s": 1000.0, "t_lo": 0.0, "t_hi": 2.0,
                  "window_s": 2.0, "busy_s": 1.38,
                  "devices": [{"busy_s": 1.38, "modules": [], "by_op": [],
                               "gaps": sorted(gaps, reverse=True)}]}}


EMPTY_RUN = {"spans": [], "window_wall": (0.0, 1.0), "stats": ({}, {}),
             "trace": None}


@pytest.mark.parametrize("fn,want", [
    ("host_ms_per_window", 10.0),        # 2 + 1 + 3 + 4 ms
    ("prefill_pad_factor", 4.0),         # 8000 / 2000
    ("lanes_live", 25.0),                # 2400 / 96
    # 12 gaps x (2 + 1 + 3 + 4 ms of the sync) = 120 ms of 2 s
    ("device_idle_with_work_pct", 6.0),
    # PR 36's five, by metric file: 1.5 + 0 + 2 + 3.5 ms stood
    ("engine.stood_ms_per_window.open", 7.0),
    ("engine.stood_ms_per_window.closed", 7.0),
    ("engine.program_build_ms_in_window.open", 250.0),
    ("engine.program_build_ms_in_window.closed", 250.0),
    ("setup.program_build_s", 7.5),
    # PR 50's four: 300 ms stalled (the one in idle is no count, the 120
    # ms under the profiler's stop are tracing's), the collector's 350 ms
    ("engine.stall_ms_in_window.open", 300.0),
    ("engine.stall_ms_in_window.closed", 300.0),
    ("engine.gc_pause_ms_in_window.open", 350.0),
    ("engine.gc_pause_ms_in_window.closed", 350.0),
])
def test_timeline_readers_on_a_synthetic_run(fn, want, capsys):
    import json

    from benchmarks.harness import spec, timeline

    # a name with a dot is a metric's file, read as the harness reads it
    read = spec.load_reader(fn).read if "." in fn else getattr(timeline, fn)
    assert read(_synthetic_run()) == pytest.approx(want)
    assert read(dict(EMPTY_RUN)) is None
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.splitlines() if ln.startswith("{")]
    if "." in fn:
        # a parent's run (no cpu_ms, no threads, no program_build_s):
        # nothing to read, no exception
        assert read(_synthetic_run(parent=True)) is None
        part = lines[0]
        if "stood_ms" in fn:
            assert part["step"] == "stood_by_phase"
            assert part["iterations"] == 12
            assert part["mean_ms"] == pytest.approx(
                {"wall": 10.0, "ran": 3.0, "stood": 7.0})
            by = part["by_phase_mean_ms"]
            assert by["deliver"]["stood"] == pytest.approx(3.5)
            assert by["fund"]["ran"] == pytest.approx(1.0)
            assert by["decode_dispatch"]["wall_p50_p90_max"] \
                == pytest.approx([3.0] * 3)
            assert "prefill_dispatch" not in by             # no wave
            assert part["sync_cpu_mean_ms"] == pytest.approx(
                {"prefill_sync": 0.0, "decode_sync": 0.1})
            # under ten iterations: too few
            run = _synthetic_run()
            run["spans"] = [s for s in run["spans"]
                            if s["attrs"].get("iter", 0) < 9]
            assert read(run) is None
            # a thread clock that ticks (the chip machine's: 10 ms): a
            # phase reads 0 or 10 ms, and only the sums mean anything
            run = _synthetic_run()
            for sp in run["spans"]:
                if "cpu_ms" in sp["attrs"]:
                    sp["attrs"]["cpu_ms"] = 10.0 * (
                        sp["name"] == "llm.loop.decode_dispatch"
                        and sp["attrs"]["iter"] % 4 == 0)
            capsys.readouterr()
            # 12 x 10 ms of wall less 3 ticks, over 12 windows
            assert read(run) == pytest.approx(7.5)
            part = json.loads(capsys.readouterr().out.splitlines()[0])
            assert set(part["by_phase_mean_ms"]["admit"]) == set(
                by["admit"])                # one shape, whatever the clock
            assert part["by_phase_mean_ms"]["decode_dispatch"]["stood"] \
                == pytest.approx(3.0 - 2.5)
        elif "stall_ms" in fn:
            # a program that records PR 36's keys and not PR 50's
            assert read(_synthetic_run(pr50=False)) is None
            assert part["step"] == "stalls_in_window"
            assert (part["stalls"], part["stall_s"]) == (
                2, pytest.approx(0.42))
            assert part["by_held_n_ms"] == {
                "engine": [1, pytest.approx(300.0)],
                "interpreter": [1, pytest.approx(120.0)]}
            assert part["in_profiler_ms"] == pytest.approx(120.0)
            first, idle, last = part["spans"]
            assert (first["phase"], first["held"], first["iter"]) == (
                "deliver", "engine", 5)
            assert first["at_s"] == pytest.approx(1.5)
            assert first["by_thread_cpu_ms"] == [["serve-call", 2.5]]
            # three of the chip's 10 ms gaps lie under it
            assert first["device_idle_s"] == pytest.approx(0.03)
            assert first["in_measured_window"] and not first[
                "in_profiler"]
            assert idle["phase"] == "idle"
            assert idle["device_idle_s"] == pytest.approx(0.15)
            # after the traced stretch: no device time to set it against
            assert last["device_idle_s"] is None
            assert last["in_profiler"] and not last[
                "in_measured_window"]
            # a run that was not traced: the counters and the spans alone,
            # and no profiler to set a stall aside for
            run = _synthetic_run()
            run["trace"] = None
            capsys.readouterr()
            assert read(run) == pytest.approx(420.0)
            part = json.loads(capsys.readouterr().out.splitlines()[0])
            assert [r["device_idle_s"] for r in part["spans"]] == [None] * 3
            assert part["in_profiler_ms"] == 0.0
        elif "gc_pause" in fn:
            assert read(_synthetic_run(pr50=False)) is None
            assert part["step"] == "gc_in_window"
            assert (part["pauses"], part["pause_s"]) == (
                60, pytest.approx(0.35))
            assert part["by_generation"]["2"] == {
                "pauses": 2, "pause_s": pytest.approx(0.3)}
            assert part["by_generation"]["0"]["pauses"] == 50
            assert part["spans"] == 3           # not the set-up's
            assert part["by_thread_n_s"] == {
                "serve-call": [2, pytest.approx(0.254)],
                "llm-engine": [1, pytest.approx(0.05)]}
            assert part["longest_ms"][0] == [
                pytest.approx(250.0), 2, "serve-call", 7,
                pytest.approx(1.52)]
            assert [r[0] for r in part["longest_ms"]] == pytest.approx(
                [250.0, 50.0, 4.0])
        elif "in_window" in fn:
            assert part["step"] == "builds_in_window"
            assert (part["program_builds"],
                    part["program_cache_misses"]) == (1, 1)
            assert part["spans"] == [["jit(_decode_k)", "compile", "miss",
                                      pytest.approx(250.0), "llm-engine", 0]]
        else:
            assert part["step"] == "setup_builds"
            assert (part["serve_run_s"], part["warmup_s"]) == (20.0, 9.0)
            assert part["by_stage_n_s"] == {
                "trace": [1, pytest.approx(1.0)],       # not the nested one
                "lower": [1, pytest.approx(0.5)],
                "compile": [1, pytest.approx(6.0)]}
            assert part["compile_by_cache_n_s"]["hit"] == [
                1, pytest.approx(6.0)]
            assert part["longest_s"][0][:2] == [
                "jit(_prefill_fwd_only)", pytest.approx(6.5)]
    if fn == "device_idle_with_work_pct":
        by = next(ln for ln in lines if ln["step"] == "idle_by_phase")
        assert by["by_phase_s"]["idle"] == pytest.approx(0.5)
        assert by["by_phase_s"]["decode_sync"] == pytest.approx(0.048)
        assert by["share_of_idle_in_gaps"] == pytest.approx(1.0)
        assert by["uncovered_s"] == pytest.approx(0.0, abs=1e-9)
        assert by["longest_ms"][0][1] == {"idle": pytest.approx(500.0)}
        part = next(ln for ln in lines if ln["step"] == "loop_partition")
        assert part["window"]["overlap_s"] == pytest.approx(0.0, abs=1e-9)
        # a parent without the phases: nothing to read, no exception
        run = _synthetic_run()
        run["spans"] = [s for s in run["spans"]
                        if not s["name"].startswith("llm.loop.")]
        assert timeline.device_idle_with_work_pct(run) is None
        assert timeline.host_ms_per_window(run) is None


@pytest.mark.parametrize("traced", [True, False])
def test_thread_cpu_line_says_when_it_holds_the_profiler(traced, capsys):
    """The thread ledger between the two readings of `stats` is printed
    beside the stood time and is no metric: where a device trace was
    taken and stopped between the readings, the rivals' CPU is the
    profiler's, and the line says so."""
    import json

    from benchmarks.harness import stood

    run = _synthetic_run()
    if not traced:
        run["trace"] = None
    stood._log_thread_cpu(run)
    part = json.loads(capsys.readouterr().out.splitlines()[0])
    assert part["step"] == "thread_cpu" and part["wall_s"] == 4.0
    assert part["holds_profiler"] is traced
    # (0.6 + 0.3 + 0.1 CPU s of the rows that are not the engine's) over
    # the 4 s between the readings
    assert part["rivals_pct_of_a_core"] == pytest.approx(25.0)
    assert [n for n, _ in part["top5_cpu_s"]] == [
        "serve-call", "raytpu-io", "llm-kv-export"]
    assert part["engine_cpu_s"] == pytest.approx(1.0)
    # 3.0 s of the process less 1.0 + 0.6 + 0.3 + 0.1
    assert part["unaccounted_cpu_s"] == pytest.approx(1.0)
    assert part["engine_phase_cpu_s"]["admit"] == pytest.approx(.006)
    # a parent's run holds no ledger: no line, no exception
    stood._log_thread_cpu(_synthetic_run(parent=True))
    assert not capsys.readouterr().out


def test_flash_bwd_only_roofline_reads_the_named_kernels():
    import types

    from benchmarks.harness import flops, peaks, timeline

    model = {"hidden_size": 4096, "num_attention_heads": 32,
             "num_key_value_heads": 8, "head_dim": 128,
             "num_hidden_layers": 20}
    cell = types.SimpleNamespace(
        chips=4, config={"train": {"batch": 4, "seq": 4096}})
    by_op = [
        ["jit_step", "transpose_jvp_flash_bwd_dq__.3 custom-call bf16[2]",
         40, 0.30],
        ["jit_step", "transpose_jvp_flash_bwd_dkv__.2 custom-call bf16[2]",
         40, 0.50],
        ["jit_step", "jvp_flash_fwd_.1 custom-call bf16[2]", 80, 0.40],
        ["jit_other", "flash_bwd_dq.9 custom-call bf16[2]", 5, 9.0],
        ["jit_step", "fusion.7 fusion bf16[2]", 40, 1.0]]
    run = {"cell": cell, "model": model, "rec": {"trace_steps": 2},
           "device": {"kind": "TPU v5 lite"},
           "trace": {"window_s": 2.6, "busy_s": 2.6,
                     "devices": [{"by_op": by_op, "modules": [],
                                  "gaps": [], "busy_s": 2.6}]}}
    f, b = flops.flash_bwd_cost(model, 4, 4096)
    least, _ = peaks.roofline_s(f * 2 * 20 / 4, b * 2 * 20 / 4,
                                "TPU v5 lite")
    assert timeline.flash_bwd_only_roofline(run) == pytest.approx(
        100.0 * least / 0.80)
    run["trace"]["devices"][0]["by_op"] = by_op[2:]     # the parent
    assert timeline.flash_bwd_only_roofline(run) is None
    assert timeline.flash_bwd_only_roofline(
        {"rec": None, "trace": None}) is None
