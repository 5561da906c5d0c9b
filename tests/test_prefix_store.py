"""Cluster prefix-cache economy: tiered KV store with cross-replica
prefix sharing (ISSUE 12 tentpole).

Engine level: cold radix leaves demote into store entries covering the
whole path's KV; a graft into a fresh engine must make decode
TOKEN-IDENTICAL to a cold re-prefill (temperature 0 AND sampled — the
same parity contract as KV migration), with clean block accounting on
both sides and a stale weight version NEVER grafted.

Server level (in-process, injected StoreDirectory): the full
demote → publish → lookup → fetch → graft miss path, the per-request
and env kill switches, RLHF-swap invalidation, and the shutdown
zero-leak contract kv_check() enforces.

Serve level (cluster_utils in-process cluster): the store through the
real controller directory, plus the chaos shape — a replica killed
MID-DEMOTION by the serve.prefix_demote failpoint with clean
accounting on every survivor.

Debug-scale fp32 on the CPU mesh — same discipline as
test_pd_disagg.py.
"""
import asyncio
import os
import time

import pytest


@pytest.fixture(scope="module")
def small():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=128, max_seq=128, remat=False, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    return cfg, params


def _engine(small, **kw):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = small
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("page_size", 8)
    kw.setdefault("steps_per_sync", 4)
    eng = LLMEngine(cfg, params, seed=0, paged=True, **kw)
    eng.start()
    return eng


PROMPT = [(i * 7 + 3) % 127 + 1 for i in range(21)]   # 2 full pages + 5

# Aggressive demotion knobs for tests: every refcount-0 leaf is cold
# immediately and the cost model always approves.
FAST = dict(min_idle=0, period_s=0.01, watermark_frac=0.0, limit=4,
            max_inflight=4, min_tokens=8, migrate_ms=0.0)


def _demote_all(eng, timeout=30.0):
    """Install a capture callback and wait until the engine has
    demoted its cold leaves into `store` (hash -> entry)."""
    store = {}

    def cb(entry):
        store[entry["hashes"][-1]] = entry
        return True

    eng.set_prefix_store(cb, min_idle=0, period_s=0.01,
                         watermark_frac=0.0, limit=4, max_inflight=4)
    eng._wake.set()
    deadline = time.time() + timeout
    while not store and time.time() < deadline:
        time.sleep(0.02)
    return store


# ------------------------------------------------------------- engine
@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_demote_graft_token_parity(small, temp):
    """The graft-parity contract: decode after grafting a stored
    prefix is token-identical to a cold re-prefill, greedy AND sampled
    (grafted KV is byte-identical to locally-computed KV; per-request
    sampling keys do the rest)."""
    a = _engine(small, name="a")
    try:
        ref = a.generate(PROMPT, max_new_tokens=6, temperature=temp)
        store = _demote_all(a)
        assert store, "no demotion happened"
        a._mgr.check()
        assert a._mgr.demotions >= 1
    finally:
        a.stop()
    entry = max(store.values(), key=lambda e: e["depth"])
    b = _engine(small, name="b")
    try:
        out = b.kv_graft(entry["tokens"], entry["kv"],
                         kv_len=entry["depth"] * 8,
                         weight_version=0).result(timeout=120)
        assert out["grafted"] == entry["depth"]
        r = b.generate(PROMPT, max_new_tokens=6, temperature=temp)
        assert r["tokens"] == ref["tokens"]
        # The graft really served the prompt's full blocks from cache.
        assert b._mgr.hit_tokens >= 16
        assert b.prefill_tokens < len(PROMPT)
        b._mgr.check()
        assert b._mgr.available() == b._mgr.n_blocks
    finally:
        b.stop()


def test_demote_scan_finish_accounting(small):
    """BlockManager demotion accounting: scan pins the whole path,
    finish(drop=True) evicts exactly the cold chain, finish(drop=False)
    keeps tier 1 intact — check() passes throughout and a re-referenced
    leaf is never dropped."""
    from ray_tpu.serve.kv_blocks import BlockManager

    m = BlockManager(8, 4)
    toks = list(range(12))                 # 3 full chunks
    blocks = m.allocate(3)
    m.commit(toks, blocks)
    m.release(blocks)
    m.check()
    cands = m.demote_scan(limit=4, min_idle=0)
    assert len(cands) == 1                 # one cold leaf = one entry
    c = cands[0]
    assert c["blocks"] == blocks and c["depth"] == 3
    assert c["tokens"] == toks
    # Pinned: not evictable, scan won't re-pick it.
    assert m.evictable_count() == 0
    assert m.demote_scan(limit=4, min_idle=0) == []
    m.check()
    # drop=False keeps the tree; pins released.
    m.demote_finish(c["leaf"], c["blocks"], drop=False)
    assert m.cached_count() == 3 and m.evictable_count() == 3
    m.check()
    # drop=True evicts the whole cold chain.
    c = m.demote_scan(limit=4, min_idle=0)[0]
    freed = m.demote_finish(c["leaf"], c["blocks"], drop=True)
    assert freed == 3 and m.cached_count() == 0
    assert m.free_count() == 8 and m.demotions == 3
    m.check()
    # A leaf matched mid-demotion survives drop=True.
    blocks = m.allocate(2)
    m.commit(toks[:8], blocks)
    m.release(blocks)
    c = m.demote_scan(limit=1, min_idle=0)[0]
    got = m.match(toks[:8])                # reader appears mid-flight
    assert m.demote_finish(c["leaf"], c["blocks"], drop=True) == 0
    assert m.cached_count() == 2
    m.release(got)
    m.check()


def test_demote_respects_min_idle_and_watermark(small):
    from ray_tpu.serve.kv_blocks import BlockManager

    m = BlockManager(8, 4)
    blocks = m.allocate(2)
    m.commit(list(range(8)), blocks)
    m.release(blocks)
    # Too fresh for min_idle, no pool pressure: nothing demotes.
    assert m.demote_scan(limit=4, min_idle=100, watermark=0) == []
    # Pool pressure overrides coldness (demote-before-evict).
    cands = m.demote_scan(limit=4, min_idle=100, watermark=8)
    assert len(cands) == 1
    m.demote_finish(cands[0]["leaf"], cands[0]["blocks"], drop=False)
    m.check()


def test_kv_graft_validation(small):
    import numpy as np

    eng = _engine(small)
    try:
        with pytest.raises(ValueError, match="multiple"):
            eng.kv_graft(PROMPT[:13], np.zeros(1), kv_len=13)
        with pytest.raises(ValueError, match="cover exactly"):
            eng.kv_graft(PROMPT[:13],
                         np.zeros((2, 2, 2, 2, 8, 16), np.float32),
                         kv_len=16)
        with pytest.raises(ValueError, match="shape"):
            eng.kv_graft(PROMPT[:16],
                         np.zeros((2, 2, 2, 2, 4, 16), np.float32),
                         kv_len=16)
        eng._mgr.check()
        assert eng._mgr.available() == eng._mgr.n_blocks
    finally:
        eng.stop()


def test_stale_weight_version_never_grafts(small):
    """The RLHF-swap safety contract at the engine edge: a graft
    tagged with a weight version other than the engine's CURRENT one
    is refused — zero blocks allocated, zero stale KV committed."""
    import numpy as np

    eng = _engine(small)
    try:
        kv = np.zeros((2, 2, 2, 2, 8, 16), np.float32)
        out = eng.kv_graft(PROMPT[:16], kv, kv_len=16,
                           weight_version=7).result(timeout=120)
        assert out == {"grafted": 0, "reason": "stale_version"}
        assert eng._mgr.cached_count() == 0
        eng._mgr.check()
        assert eng._mgr.available() == eng._mgr.n_blocks
    finally:
        eng.stop()


def test_graft_failpoint_engine_survives(small):
    """serve.prefix_graft=error: the graft future fails (the server's
    cue to fall back to a plain prefill), the engine loop survives, no
    block leaks."""
    import numpy as np

    from ray_tpu._private import failpoints

    eng = _engine(small)
    try:
        failpoints.configure("serve.prefix_graft=nth:1+error")
        kv = np.zeros((2, 2, 2, 2, 8, 16), np.float32)
        fut = eng.kv_graft(PROMPT[:16], kv, kv_len=16)
        with pytest.raises(failpoints.FailpointError):
            fut.result(timeout=120)
        eng._mgr.check()
        assert eng._mgr.available() == eng._mgr.n_blocks
        assert len(eng.generate(PROMPT, max_new_tokens=3)["tokens"]) == 3
    finally:
        failpoints.reset()
        eng.stop()


def test_demote_failpoint_releases_pins(small):
    """serve.prefix_demote=error: the publish leg faults mid-demotion;
    the pins drop, tier 1 keeps the leaf (nothing was stored), the
    engine keeps serving, and accounting stays clean."""
    from ray_tpu._private import failpoints

    eng = _engine(small)
    try:
        eng.generate(PROMPT, max_new_tokens=4)
        cached = eng._mgr.cached_count()
        assert cached >= 2
        failpoints.configure("serve.prefix_demote=error")
        seen = []
        eng.set_prefix_store(lambda e: seen.append(e) or True,
                             min_idle=0, period_s=0.01,
                             watermark_frac=0.0)
        eng._wake.set()
        deadline = time.time() + 30
        while eng.demote_failures == 0 and time.time() < deadline:
            time.sleep(0.02)
        assert eng.demote_failures >= 1
        assert not seen                      # publish never completed
        # Give in-flight finishes a beat, then assert clean state.
        deadline = time.time() + 10
        while eng._demote_inflight and time.time() < deadline:
            time.sleep(0.02)
        eng._mgr.check()
        assert eng._mgr.cached_count() == cached   # leaf NOT dropped
        assert eng._mgr.demotions == 0
        assert len(eng.generate(PROMPT, max_new_tokens=3)["tokens"]) == 3
    finally:
        failpoints.reset()
        eng.stop()


# ---------------------------------- how a demoted path leaves the device
def _prompt(depth: int, salt: int = 0) -> list:
    """A prompt whose finished request leaves `depth` sealed pages."""
    return [(i * 11 + 5 + 17 * salt) % 127 + 1 for i in range(depth * 8 + 3)]


def _record_scans(eng) -> dict:
    """{leaf hash: blocks} of every candidate the engine's scans take."""
    scanned, scan = {}, eng._mgr.demote_scan

    def demote_scan(**kw):
        cands = scan(**kw)
        scanned.update({c["hash"]: list(c["blocks"]) for c in cands})
        return cands

    eng._mgr.demote_scan = demote_scan
    return scanned


def _wait(cond, timeout=30.0) -> bool:
    deadline = time.time() + timeout
    while not cond() and time.time() < deadline:
        time.sleep(0.01)
    return bool(cond())


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_demoted_entry_bit_equal_to_stacked_gather(small, depth):
    """The entry the callback receives, fetched a page a call and a
    piece a fetch, is bit-equal to ONE stacked `_gather_kv` of the same
    blocks ([2, L, depth, kvh, page, hd], what demotion fetched before
    PR 33), and a graft of it serves the same tokens."""
    import jax.numpy as jnp
    import numpy as np

    prompt = _prompt(depth)
    a = _engine(small, name="a")
    try:
        ref = a.generate(prompt, max_new_tokens=4)
        scanned = _record_scans(a)
        store = _demote_all(a)
        assert _wait(lambda: a._demote_inflight == 0)
        (h, entry), = store.items()
        assert entry["depth"] == depth
        assert entry["pieces"] == depth * 2 * 2      # 2 layers, K and V
        # the loop is idle and nothing was admitted since: the pool
        # still holds what the scan pinned
        want = np.asarray(a._gather_kv(
            a.cache["k"], a.cache["v"],
            jnp.asarray(scanned[h], jnp.int32)))
        assert entry["kv"].shape == want.shape == (2, 2, depth, 2, 8, 16)
        assert entry["kv"].dtype == want.dtype
        assert entry["kv"].flags["C_CONTIGUOUS"]
        assert entry["kv"].tobytes() == want.tobytes()
        loop = a.stats()["loop"]
        assert loop["demote_pages"] == depth
        assert loop["demote_bytes"] == want.nbytes
        assert loop["demote_fetch_s"] > 0.0
        a._mgr.check()
    finally:
        a.stop()
    b = _engine(small, name="b")
    try:
        out = b.kv_graft(entry["tokens"], entry["kv"], kv_len=depth * 8,
                         weight_version=0).result(timeout=120)
        assert out["grafted"] == depth
        assert b.generate(prompt, max_new_tokens=4)["tokens"] \
            == ref["tokens"]
        assert b._mgr.hit_tokens >= depth * 8
        b._mgr.check()
    finally:
        b.stop()


@pytest.fixture
def backend_compiles():
    """Counts this process's XLA backend compiles, on any thread."""
    from jax._src import monitoring

    seen = []

    def listen(event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            seen.append(event)

    monitoring.register_event_duration_secs_listener(listen)
    yield seen
    monitoring.unregister_event_duration_listener(listen)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_demotion_meets_no_new_program_after_warmup(small, depth,
                                                    backend_compiles):
    """ONE page gather, compiled when the callback is installed: after
    warmup() a demotion of any depth compiles nothing (before PR 33 each
    power-of-two depth was a new `_gather_kv` program, compiled on the
    engine thread inside a decode window)."""
    eng = _engine(small)
    try:
        assert eng._gather_page is None
        n0 = len(backend_compiles)
        # installed, but nothing is cold yet
        eng.set_prefix_store(lambda e: True, min_idle=1 << 30,
                             watermark_frac=0.0)
        assert len(backend_compiles) == n0 + 1
        program = eng._gather_page
        eng.warmup(buckets=[16, 32])
        eng.generate(_prompt(depth), max_new_tokens=4)
        n1 = len(backend_compiles)
        store = _demote_all(eng)
        assert _wait(lambda: eng._demote_inflight == 0)
        assert [e["depth"] for e in store.values()] == [depth]
        assert len(backend_compiles) == n1
        assert eng._gather_page is program
        assert eng._gather_kv._cache_size() == 0    # demotion's old program
    finally:
        eng.stop()


@pytest.mark.parametrize("case", ["no_callback", "prefix_cache_off"])
def test_engine_without_demotion_builds_no_page_gather(small, case,
                                                       backend_compiles):
    eng = _engine(small, prefix_cache=(case != "prefix_cache_off"))
    try:
        n0 = len(backend_compiles)
        eng.set_prefix_store(
            None if case == "no_callback" else (lambda e: True))
        eng.warmup(buckets=[16])
        assert eng._gather_page is None
        assert eng._gather_page_jit._cache_size() == 0
        eng.set_prefix_store(None)
        assert len(backend_compiles) > n0           # warmup's programs
        assert eng.stats()["loop"]["demote_pages"] == 0
    finally:
        eng.stop()


class _Piece:
    """Stands for one gathered [kvh, page, hd] device array and records
    every way of asking for its bytes."""

    def __init__(self, arr, log):
        self.arr, self.log = arr, log
        self.shape, self.dtype = arr.shape, arr.dtype

    def copy_to_host_async(self):
        self.log.append("async")

    def __array__(self, *a, **kw):
        self.log.append("array")
        raise AssertionError("a piece was fetched around _fetch_piece")


def _stub_fetch(eng, gate=None, window_s=0.0):
    """Route the engine's gathers and fetches through recorders: returns
    (events, state) where events holds ("window" | "gather", iter) in
    dispatch order and state["max"] the most pieces ever outstanding.
    `gate` holds every fetch until set; a window takes `window_s` more."""
    import threading

    import numpy as np

    events, log = [], []
    state = {"out": 0, "max": 0, "fetched": 0, "log": log,
             "window": threading.Event()}
    gather = eng._gather_page

    def gather_page(k, v, pid):
        events.append(("gather", eng._iter))
        return tuple(_Piece(x, log) for x in gather(k, v, pid))

    def fetch_piece(piece):
        state["out"] += 1
        state["max"] = max(state["max"], state["out"])
        if gate is not None:
            assert gate.wait(30.0)
        out = np.asarray(piece.arr)
        state["fetched"] += 1
        state["out"] -= 1
        return out

    eng._gather_page, eng._fetch_piece = gather_page, fetch_piece
    for k, decode in list(eng._decode_fns.items()):
        def window(*a, _decode=decode):
            events.append(("window", eng._iter))
            state["window"].set()
            time.sleep(window_s)
            return _decode(*a)
        eng._decode_fns[k] = window
    return events, state


def test_gathers_queue_behind_the_window_one_piece_on_its_way(small):
    """A scan that finds candidates while lanes decode dispatches their
    page gathers AFTER that iteration's decode window, and the export
    thread asks for one piece at a time: never two outstanding, never a
    whole-path `copy_to_host_async`."""
    eng = _engine(small)
    try:
        for d in (1, 2, 3):
            eng.generate(_prompt(d, salt=d), max_new_tokens=4)
        store = {}
        eng.set_prefix_store(lambda e: True, min_idle=1 << 30,
                             watermark_frac=0.0)     # compiled, idle
        events, state = _stub_fetch(eng, window_s=0.02)
        # 28 windows of 20 ms and more: live lanes until the scans are done
        fut = eng.submit(_prompt(1, salt=9)[:7], max_new_tokens=110)
        assert state["window"].wait(30.0)

        def cb(entry):
            store[entry["hashes"][-1]] = entry
            return True

        eng.set_prefix_store(cb, min_idle=0, period_s=0.001,
                             watermark_frac=0.0, limit=4, max_inflight=4)
        fut.result(timeout=120)
        assert _wait(lambda: len(store) >= 3)
        eng.set_prefix_store(cb, min_idle=1 << 30, watermark_frac=0.0)
        assert _wait(lambda: not eng._demote_inflight)
        # the three cold paths, and perhaps the finished request's own
        depths = sorted(e["depth"] for e in store.values())
        assert depths[:3] == [1, 2, 3]
        assert state["max"] == 1 and state["log"] == []
        assert state["fetched"] == sum(depths) * 2 * 2
        by_iter: dict = {}
        for what, it in events:
            by_iter.setdefault(it, []).append(what)
        behind = [seq for seq in by_iter.values()
                  if "gather" in seq and "window" in seq]
        assert behind, "no scan met a live window: " + repr(by_iter)
        for seq in behind:
            assert seq[0] == "window" and seq.count("window") == 1, seq
        eng._mgr.check()
    finally:
        eng.stop()


@pytest.mark.parametrize("case", ["weight_swap", "publish_refusal",
                                  "failpoint"])
def test_demotion_cut_mid_fetch_finishes_once_and_unpins(small, case):
    """Whatever ends a demotion between its first piece and its publish
    (the policy swapped under it; the store refuses the entry; the
    serve.prefix_demote failpoint, which still fires AFTER the fetch and
    BEFORE the publish), demote_finish runs exactly once for the path,
    nothing is dropped from tier 1 and every pin is released."""
    import threading

    import jax

    from ray_tpu._private import failpoints
    from ray_tpu.models import llama

    cfg, _params = small
    eng = _engine(small)
    try:
        eng.generate(_prompt(2), max_new_tokens=4)
        cached = eng._mgr.cached_count()
        assert cached == 2
        eng.set_prefix_store(lambda e: True, min_idle=1 << 30,
                             watermark_frac=0.0)
        gate = threading.Event()
        _events, state = _stub_fetch(eng, gate)
        finished, finish = [], eng._mgr.demote_finish

        def demote_finish(leaf, blocks, drop):
            finished.append((list(blocks), drop))
            return finish(leaf, blocks, drop=drop)

        eng._mgr.demote_finish = demote_finish
        published, fired_mid_fetch = [], None
        if case == "failpoint":
            failpoints.configure("serve.prefix_demote=nth:1+error")

        def cb(entry):
            published.append(entry)
            return case != "publish_refusal"

        eng.set_prefix_store(cb, min_idle=0, period_s=0.01,
                             watermark_frac=0.0, limit=1, max_inflight=1)
        eng._wake.set()
        # the export thread holds the first piece: mid-fetch
        assert _wait(lambda: state["out"] == 1)
        assert eng._demote_inflight == 1 and not finished
        assert eng._mgr.evictable_count() == 0          # pinned
        if case == "weight_swap":
            eng.update_weights(
                llama.init_params(jax.random.PRNGKey(99), cfg), version=1)
            eng._wake.set()
            assert _wait(lambda: eng.weight_version == 1)
        if case == "failpoint":
            fired_mid_fetch = failpoints.counters()[
                "serve.prefix_demote"]["fired"]
        gate.set()
        assert _wait(lambda: len(finished) >= 1
                     and eng._demote_inflight == 0)
        # no later scan takes the path again before we look
        eng.set_prefix_store(None)
        assert state["fetched"] == 2 * 2 * 2             # the whole path
        assert len(finished) == 1 and finished[0][1] is False
        assert len(published) == (1 if case == "publish_refusal" else 0)
        assert eng.demote_published == 0 and eng._mgr.demotions == 0
        assert eng.demote_failures == (1 if case == "failpoint" else 0)
        if case == "failpoint":
            assert fired_mid_fetch == 0    # not before the fetch ended
            assert failpoints.counters()[
                "serve.prefix_demote"]["fired"] == 1
        eng._mgr.check()
        if case == "weight_swap":          # the swap flushed the tree
            assert eng._mgr.available() == eng._mgr.n_blocks
        else:
            assert eng._mgr.cached_count() == cached
            assert eng._mgr.evictable_count() == cached
        assert len(eng.generate(PROMPT, max_new_tokens=3)["tokens"]) == 3
    finally:
        failpoints.reset()
        eng.stop()


# ------------------------------------------------------------- server
def _server(small, directory, seed=3, **extra):
    from ray_tpu.serve.llm import LLMServer

    cfg, _params = small
    pscfg = dict(FAST, directory=directory, **extra.pop("store", {}))
    return LLMServer(cfg, max_batch=4, max_len=128, page_size=8,
                     seed=seed, steps_per_sync=4, prefix_store=pscfg,
                     **extra)


def _wait_entries(directory, n=1, timeout=30.0):
    deadline = time.time() + timeout
    while directory.stats()["entries"] < n and time.time() < deadline:
        time.sleep(0.02)
    return directory.stats()["entries"]


def test_server_store_round_trip_and_kill_switches(small):
    """Full miss path through two LLMServers sharing one directory:
    s1 serves + demotes, s2 grafts and answers token-identically.
    Both kill switches (per-request payload key, RAY_TPU_PREFIX_STORE
    env) stop fetching in the same run."""
    from ray_tpu.serve.prefix_store import StoreDirectory

    d = StoreDirectory()
    s1 = _server(small, d)
    s2 = _server(small, d)
    try:
        ref = asyncio.run(s1({"prompt": PROMPT, "max_new_tokens": 6}))
        assert _wait_entries(d) >= 1
        out = asyncio.run(s2({"prompt": PROMPT, "max_new_tokens": 6}))
        assert out["tokens"] == ref["tokens"]
        st = s2.stats()["prefix_store"]
        assert st["fetches"] == 1 and st["grafts"] == 1
        assert st["graft_tokens"] >= 16
        assert s2.engine.kv_grafts == 1
        # Per-request kill switch: a store-capable miss must not fetch.
        s3 = _server(small, d, seed=3)
        try:
            asyncio.run(s3({"prompt": PROMPT, "max_new_tokens": 2,
                            "prefix_store": False}))
            assert s3.stats()["prefix_store"]["fetches"] == 0
            # Env kill switch, read per request (same-run A/B).
            os.environ["RAY_TPU_PREFIX_STORE"] = "0"
            try:
                asyncio.run(s3({"prompt": PROMPT[:16] + [9, 9, 9],
                                "max_new_tokens": 2}))
                assert s3.stats()["prefix_store"]["fetches"] == 0
            finally:
                os.environ.pop("RAY_TPU_PREFIX_STORE", None)
        finally:
            s3.shutdown()
        for s in (s1, s2):
            assert s.kv_check()["ok"]
    finally:
        s1.shutdown()
        s2.shutdown()
    # Shutdown withdrew every replica's entries: tier 2 died with the
    # app, and post-shutdown kv_check asserts the zero-leak contract.
    assert d.stats()["entries"] == 0
    assert s1.kv_check()["prefix_store_objects"] == 0


def test_kv_check_asserts_leak_after_shutdown(small):
    """The satellite contract: kv_check() RAISES when a tier-2 object
    outlives shutdown (simulated leak — the normal path is covered by
    the round-trip test)."""
    from ray_tpu.serve.prefix_store import StoreDirectory

    d = StoreDirectory()
    s = _server(small, d)
    s.shutdown()
    assert s.kv_check()["prefix_store_objects"] == 0
    s._prefix_client._objects[123] = (None, 0, 64)   # forged leak
    with pytest.raises(AssertionError, match="leaked after"):
        s.kv_check()


def test_weight_swap_invalidates_store(small):
    """The RLHF-swap test (acceptance): entries published under v0 are
    never grafted after the consumer swaps to v1 (lookup's version
    filter), the publisher's swap reclaims its v0 entries, and the run
    ends with zero stale hits, zero leaked KV blocks, zero arena pins."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.prefix_store import StoreDirectory

    cfg, _params = small
    d = StoreDirectory()
    s1 = _server(small, d)
    s2 = _server(small, d)
    try:
        asyncio.run(s1({"prompt": PROMPT, "max_new_tokens": 6}))
        assert _wait_entries(d) >= 1
        # Consumer swaps to v1 BEFORE ever touching the store: the v0
        # entry must never graft into a v1 engine.
        tree = llama.init_params(jax.random.PRNGKey(99), cfg)
        s2.update_weights(tree, version=1)
        deadline = time.time() + 30
        while s2.engine.weight_version != 1 and time.time() < deadline:
            time.sleep(0.02)
        out = asyncio.run(s2({"prompt": PROMPT, "max_new_tokens": 4}))
        assert len(out["tokens"]) == 4
        st = s2.stats()["prefix_store"]
        assert st["grafts"] == 0 and s2.engine.kv_grafts == 0
        # Publisher swaps too: its v0 entries drop from the directory.
        s1.update_weights(tree, version=1)
        deadline = time.time() + 30
        while time.time() < deadline:
            entries = d.stats()["entries"]
            if all(e["weight_version"] >= 1
                   for a in d._apps.values()
                   for e in a["entries"].values()) or entries == 0:
                break
            time.sleep(0.05)
        assert s1.kv_check()["ok"] and s2.kv_check()["ok"]
    finally:
        s1.shutdown()
        s2.shutdown()
    assert d.stats()["entries"] == 0


def test_directory_lookup_filters_and_partial_depth(small):
    """StoreDirectory semantics: every hash along a chain indexes the
    entry (a shallower prompt grafts a SLICE); page/seed/version
    mismatches are never returned; byte cap evicts oldest."""
    import numpy as np

    from ray_tpu.serve.kv_router import chain_hash
    from ray_tpu.serve.prefix_store import StoreDirectory

    d = StoreDirectory()
    h1 = chain_hash(0, tuple(range(8)))
    h2 = chain_hash(h1, tuple(range(8, 16)))
    meta = {"hashes": [h1, h2], "page": 8, "seed": 0,
            "weight_version": 0, "nbytes": 100, "replica": "r1"}
    assert d.publish("app", meta, np.zeros(2))
    # Full-depth and partial-depth lookups hit the same entry.
    assert d.lookup("app", [h1, h2], 8, 0, 0)["depth"] == 2
    assert d.lookup("app", [h1], 8, 0, 0)["depth"] == 1
    # min_depth demands STRICTLY deeper than the local match.
    assert d.lookup("app", [h1], 8, 0, 0, min_depth=1) is None
    # Filters: wrong page / seed / version never graft.
    assert d.lookup("app", [h1, h2], 16, 0, 0) is None
    assert d.lookup("app", [h1, h2], 8, 5, 0) is None
    assert d.lookup("app", [h1, h2], 8, 0, 3) is None
    # Replica scrub.
    assert d.forget("app", replica="r1") == 1
    assert d.lookup("app", [h1, h2], 8, 0, 0) is None
    # Byte cap: oldest entry evicted first.
    d2 = StoreDirectory(max_bytes=150)
    d2.publish("app", dict(meta, hashes=[h1], nbytes=100), np.zeros(1))
    time.sleep(0.01)
    d2.publish("app", dict(meta, hashes=[h2], nbytes=100), np.zeros(1))
    assert d2.stats()["entries"] == 1 and d2.evicted == 1
    assert d2.lookup("app", [h1], 8, 0, 0) is None


def test_cost_model_gates_fetch(small):
    """A miss whose best-case gain can't beat the migration cost never
    even costs the directory round trip; a worthwhile one does."""
    from ray_tpu.serve import prefix_store as pstore

    assert not pstore.migration_worth_it(8, 0, {"migrate_ms": 4.7,
                                                "prefill_us_per_token":
                                                40.0})
    assert pstore.migration_worth_it(896, 1 << 20,
                                     {"migrate_ms": 4.7,
                                      "prefill_us_per_token": 40.0,
                                      "bw_gbps": 2.0})
    from ray_tpu.serve.prefix_store import StoreDirectory

    d = StoreDirectory()
    s = _server(small, d, store={"migrate_ms": 1e9})
    try:
        asyncio.run(s({"prompt": PROMPT, "max_new_tokens": 2}))
        st = s.stats()["prefix_store"]
        # Pre-gate: no lookup, no fetch — the cost model said no.
        assert st["fetches"] == 0 and st["lookup_misses"] == 0
        assert d.stats()["lookups"] == 0
    finally:
        s.shutdown()


# -------------------------------------------------------------- serve
def _armable_llm():
    """LLMServer + a failpoint-arming hook shipped by value (the serve
    chaos pattern of test_pd_disagg.py)."""
    class ArmableLLM:
        def __init__(self, *a, **k):
            from ray_tpu.serve.llm import LLMServer

            self._inner = LLMServer(*a, **k)

        def arm(self, site, action):
            import os as _os

            from ray_tpu._private import failpoints as fp

            fp.arm(site, action)
            return _os.getpid()

        def __getattr__(self, name):
            return getattr(self._inner, name)

        async def __call__(self, request):
            return await self._inner(request)

    return ArmableLLM


@pytest.fixture
def serve_ray(small):
    import ray_tpu
    from ray_tpu import serve

    if not ray_tpu.is_initialized():
        ray_tpu.init(resources={"CPU": 4})
    serve.start()
    yield serve
    serve.shutdown()


SERVE_STORE = dict(min_idle=0, period_s=0.05, watermark_frac=0.0,
                   limit=4, max_inflight=4, min_tokens=8,
                   migrate_ms=0.0)


def _store_app(serve, cfg, *, replicas=2, cls=None, seed=11):
    from ray_tpu.serve.llm import LLMServer

    LLM = serve.deployment(cls or LLMServer).options(
        name="llm", num_replicas=replicas, max_ongoing_requests=4)
    return LLM.bind(cfg, max_batch=2, max_len=64, page_size=8,
                    steps_per_sync=4, seed=seed,
                    prefix_store=SERVE_STORE)


def _ref_tokens(cfg, prompt, n, seed=11):
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(cfg, None, seed=seed, paged=True, max_batch=2,
                    max_len=64, page_size=8, steps_per_sync=4)
    eng.start()
    try:
        return eng.generate(prompt, max_new_tokens=n)["tokens"]
    finally:
        eng.stop()


def _ctrl(serve):
    import ray_tpu

    from ray_tpu.serve.controller import CONTROLLER_NAME

    return ray_tpu.get_actor(CONTROLLER_NAME)


def test_store_through_serve_controller_directory(serve_ray, small):
    """Full-stack economy: a prompt served (and demoted) on one
    replica grafts from the controller directory on whichever replica
    the repeat lands on — token-identical to an unsplit engine, with
    the demote/publish/graft counters visible in replica_metrics and
    zero leaks at app delete."""
    import ray_tpu

    cfg, _params = small
    h = serve_ray.run(_store_app(serve_ray, cfg), name="ps_app",
                      route_prefix="/ps")
    ctrl = _ctrl(serve_ray)
    try:
        ref = _ref_tokens(cfg, PROMPT[:16], 4)
        out1 = h.remote({"prompt": PROMPT[:16],
                         "max_new_tokens": 4}).result(timeout_s=300)
        assert out1["tokens"] == ref
        # The serving replica demotes its cold chain into the
        # controller directory.
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st = ray_tpu.get(ctrl.prefix_store_stats.remote(),
                             timeout=30.0)
            if st["entries"] >= 1:
                break
            time.sleep(0.2)
        assert st["entries"] >= 1, st
        # The repeat grafts (its own replica demoted the tier-1 copy;
        # whichever replica wins pow-2 pulls from tier 2).
        out2 = h.remote({"prompt": PROMPT[:16],
                         "max_new_tokens": 4}).result(timeout_s=300)
        assert out2["tokens"] == ref
        rm = serve_ray.replica_metrics("ps_app", deployment="llm")
        stats = [m["user_stats"]
                 for m in rm["ps_app"]["llm"].values()
                 if "user_stats" in m]
        assert sum(s["demote_published"] for s in stats) >= 1
        assert sum(s["kv_grafts"] for s in stats) >= 1
        dh = serve_ray.get_deployment_handle("llm", "ps_app")
        for _ in range(3):
            assert dh.kv_check.remote().result(timeout_s=120)["ok"]
    finally:
        serve_ray.delete("ps_app")
    # App delete scrubbed the directory (controller-side refs too).
    st = ray_tpu.get(ctrl.prefix_store_stats.remote(), timeout=30.0)
    assert st["entries"] == 0, st
    # A replica that demotes while it drains publishes AFTER that
    # scrub (the `assert 1 == 0` this test showed under load): the
    # directory refuses what nothing would scrub again.
    late = ray_tpu.get(ctrl.prefix_store_publish.remote(
        "ps_app", {"hashes": [1], "page": 16, "nbytes": 8,
                   "replica": "gone", "deployment": "llm"},
        [ray_tpu.put(b"late")]), timeout=30.0)
    assert late == {"ok": False, "live": []}, late
    st = ray_tpu.get(ctrl.prefix_store_stats.remote(), timeout=30.0)
    assert st["entries"] == 0, st


@pytest.mark.chaos
def test_replica_crash_mid_demotion_clean_accounting(serve_ray, small):
    """serve.prefix_demote=crash: the replica dies BETWEEN the KV
    gather and the directory registration.  The app keeps serving
    (controller replaces the replica), every surviving engine passes
    kv_check, the dead replica's directory entries are scrubbed, and
    no arena pin leaks."""
    from test_chaos_adversarial import _arena_pins_settle

    import ray_tpu

    cfg, _params = small
    h = serve_ray.run(
        _store_app(serve_ray, cfg, replicas=2, cls=_armable_llm()),
        name="ps_chaos", route_prefix="/psc")
    ctrl = _ctrl(serve_ray)
    try:
        ref = _ref_tokens(cfg, PROMPT[:16], 4)
        dh = serve_ray.get_deployment_handle("llm", "ps_chaos")
        armed = set()
        for _ in range(40):
            armed.add(dh.arm.remote(
                "serve.prefix_demote",
                "nth:1+crash").result(timeout_s=120))
            if len(armed) == 2:
                break
        assert len(armed) == 2, f"could not arm both replicas: {armed}"
        # Traffic on distinct prompts: every replica that finishes a
        # request demotes — and dies at the failpoint.
        for i in range(6):
            p = [(x + i * 31) % 127 + 1 for x in range(16)]
            try:
                h.remote({"prompt": p,
                          "max_new_tokens": 2}).result(timeout_s=300)
            except Exception:  # noqa: BLE001 - racing a dying replica
                pass
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            alive = []
            for pid in armed:
                try:
                    os.kill(pid, 0)
                    alive.append(pid)
                except ProcessLookupError:
                    pass
            if not alive:
                break
            time.sleep(0.5)
        else:
            raise AssertionError(
                f"armed replicas {alive} still alive — "
                f"serve.prefix_demote never fired")
        # The app still serves, token-identically (fresh replicas).
        out = h.remote({"prompt": PROMPT[:16],
                        "max_new_tokens": 4}).result(timeout_s=300)
        assert out["tokens"] == ref
        # Clean accounting on every survivor.
        checks = [dh.kv_check.remote().result(timeout_s=120)
                  for _ in range(4)]
        assert all(c["ok"] for c in checks)
        assert all(c.get("prefix_store_objects", 0) >= 0
                   for c in checks)
        # Forget accounting moved on the controller (the dead
        # replicas' entries were scrubbed on removal — their objects
        # died with the owning processes regardless).
        st = ray_tpu.get(ctrl.prefix_store_stats.remote(), timeout=30.0)
        assert st["entries"] >= 0      # directory responsive post-chaos
        stats = _arena_pins_settle()
        assert not stats.get("swept_dead_pins", 0), stats
    finally:
        serve_ray.delete("ps_chaos")


def test_publish_reregisters_and_reconciles(small):
    """Review-found lifecycle defects, pinned: (1) a publish whose
    entry the directory since dropped (cap eviction / failure scrub /
    controller restart) must RE-REGISTER — a local-cache dedupe that
    returns True without the directory holding the entry lets the
    engine evict the LAST copy; (2) an entry the byte cap evicts
    within its own publish reports ok=False (keep tier 1); (3) the
    publish reply's live-list prunes primary refs of entries the
    directory dropped, so the byte cap bounds arena bytes too."""
    import numpy as np

    from ray_tpu.serve.kv_router import chain_hash
    from ray_tpu.serve.prefix_store import (PrefixStoreClient,
                                            StoreDirectory)

    d = StoreDirectory(max_bytes=250)
    c = PrefixStoreClient(app="a", deployment="llm", replica_id="r1",
                          seed=0, page=8, directory=d)
    h1 = chain_hash(0, tuple(range(8)))
    kv = np.zeros(4, np.float32)         # nbytes=16 (meta carries it)
    e1 = dict(tokens=list(range(8)), kv=kv, hashes=[h1], depth=1,
              page=8, weight_version=0)
    assert c.publish(e1)
    assert d.stats()["entries"] == 1
    # Directory loses the entry behind the client's back.
    d.forget("a", hashes=[h1])
    assert d.stats()["entries"] == 0
    # Dedupe hit must still re-register, not return a hollow True.
    assert c.publish(e1)
    assert d.stats()["entries"] == 1
    # Oversized entry: evicted within its own publish -> ok False,
    # and the client keeps no primary ref for it.
    big = np.zeros(200, np.float32)      # 800 bytes > max_bytes
    h2 = chain_hash(0, tuple(range(8, 16)))
    e2 = dict(tokens=list(range(8, 16)), kv=big, hashes=[h2], depth=1,
              page=8, weight_version=0)
    assert not c.publish(e2)
    assert d.stats()["entries"] == 1     # e1 survived, e2 never landed
    assert c.object_count() == 1
    # Cap-evicted sibling entries prune from the client on the next
    # publish round trip (the live-list reconciliation).
    d.forget("a", hashes=[h1])
    h3 = chain_hash(0, tuple(range(16, 24)))
    e3 = dict(tokens=list(range(16, 24)), kv=kv, hashes=[h3], depth=1,
              page=8, weight_version=0)
    assert c.publish(e3)
    assert c.object_count() == 1         # h1's primary ref dropped
    assert set(o for o in c._objects) == {h3}
