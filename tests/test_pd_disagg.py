"""Disaggregated prefill/decode serving: KV migration over the object
plane.

Engine level: a request prefilled on engine A, its KV pages exported and
imported into engine B, must decode the EXACT token stream a single
engine would have produced — at temperature 0 and 0.8 (the per-request
sampling keys travel with the migration).  Block accounting ends clean
on both sides (BlockManager.check()).

Serve level: a prefill-pool replica ships sealed KV pages to a decode
replica through the object plane; kill switches restore unified
serving; chaos tests (marker `chaos`) kill/fault the decode side
mid-migration and require completion with zero leaked arena pins and
zero leaked KV blocks.

Debug-scale fp32 on the CPU mesh — same discipline as
test_prefix_cache.py.
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


def _migrate(small, prompt, temp, new_tokens=10):
    """prefill on one engine → kv_export → kv_import on another →
    decode to completion.  Returns (result, prefill_engine,
    decode_engine)."""
    pre_e = _engine(small, name="pre")
    dec_e = _engine(small, name="dec")
    pre = pre_e.submit(prompt, max_new_tokens=1, temperature=temp,
                       prefill_only=True).result(timeout=300)
    exp = pre["kv_export"]
    assert exp["len"] == len(prompt)
    assert exp["kv"].shape[2] == -(-len(prompt) // 8)
    out = dec_e.kv_import(
        prompt, exp["tokens"], exp["kv"], kv_len=exp["len"],
        max_new_tokens=new_tokens, temperature=temp,
        sample_seed=exp["sample_seed"]).result(timeout=300)
    return out, pre_e, dec_e


@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_migrated_decode_token_parity(small, temp):
    """The migration-parity contract: migrated-KV decode is
    token-identical to an uninterrupted single-engine run, greedy AND
    sampled (the exporter's sample_seed + matching engine seeds pin the
    stream)."""
    single = _engine(small)
    try:
        ref = single.generate(PROMPT, max_new_tokens=10,
                              temperature=temp)
    finally:
        single.stop()
    out, pre_e, dec_e = _migrate(small, PROMPT, temp)
    try:
        assert out["tokens"] == ref["tokens"]
        assert out["tokens"][0] == ref["tokens"][0]   # t0 carried over
        assert pre_e.kv_exports == 1
        assert dec_e.kv_imports == 1
    finally:
        pre_e.stop()
        dec_e.stop()


def test_migration_block_accounting_clean(small):
    """Zero leaked KV blocks on either side: after the migrated request
    completes, both managers pass check() and every block is free or
    cached-evictable (available == pool size)."""
    out, pre_e, dec_e = _migrate(small, PROMPT, 0.0)
    try:
        assert len(out["tokens"]) == 10
        for eng in (pre_e, dec_e):
            eng._mgr.check()
            assert eng._mgr.available() == eng._mgr.n_blocks
        # The prefill side committed the prompt's full blocks — a
        # follow-up local request prefix-hits them (the prefill pool
        # keeps its radix value even though decode moved away).
        pre_e.generate(PROMPT, max_new_tokens=2)
        assert pre_e._mgr.hit_tokens >= 16
    finally:
        pre_e.stop()
        dec_e.stop()


def test_kv_import_validation(small):
    import numpy as np

    eng = _engine(small)
    try:
        kv_ok = np.zeros((2, 2, 3, 2, 8, 16), np.float32)
        with pytest.raises(ValueError, match="kv_len"):
            eng.kv_import(PROMPT, [5], kv_ok, kv_len=7,
                          max_new_tokens=4)
        with pytest.raises(ValueError, match="shape"):
            eng.kv_import(PROMPT, [5], np.zeros((2, 2, 3, 2, 4, 16),
                                                np.float32),
                          kv_len=len(PROMPT), max_new_tokens=4)
        with pytest.raises(ValueError, match="first "):
            eng.kv_import(PROMPT, [], kv_ok, kv_len=len(PROMPT))
        with pytest.raises(ValueError, match="max_len"):
            eng.kv_import(PROMPT, [5], kv_ok, kv_len=len(PROMPT),
                          max_new_tokens=1000)
        with pytest.raises(ValueError, match="max_new_tokens"):
            # Over-budget token list: would under-reserve pages and
            # blow up the jitted scatter ON THE ENGINE LOOP.
            eng.kv_import(PROMPT, [5, 6, 7],
                          np.zeros((2, 2, 3, 2, 8, 16), np.float32),
                          kv_len=len(PROMPT) + 2, max_new_tokens=2)
        eng._mgr.check()
        assert eng._mgr.available() == eng._mgr.n_blocks
    finally:
        eng.stop()


def test_kv_export_failpoint_releases_blocks(small):
    """serve.kv_export=error: the export window faults AFTER prefill —
    the future fails (the server's cue to fall back to unified local
    serving), the engine loop survives, and no block leaks."""
    from ray_tpu._private import failpoints

    eng = _engine(small)
    try:
        failpoints.configure("serve.kv_export=nth:1+error")
        fut = eng.submit(PROMPT, max_new_tokens=1, prefill_only=True)
        with pytest.raises(failpoints.FailpointError):
            fut.result(timeout=300)
        eng._mgr.check()
        assert eng._mgr.available() == eng._mgr.n_blocks
        # Engine still serves (the loop caught the injected error).
        assert len(eng.generate(PROMPT, max_new_tokens=3)["tokens"]) == 3
    finally:
        failpoints.reset()
        eng.stop()


def test_kv_import_failpoint_fires_at_entry(small):
    from ray_tpu._private import failpoints

    import numpy as np

    eng = _engine(small)
    try:
        failpoints.configure("serve.kv_import=nth:1+error")
        with pytest.raises(failpoints.FailpointError):
            eng.kv_import(PROMPT, [5],
                          np.zeros((2, 2, 3, 2, 8, 16), np.float32),
                          kv_len=len(PROMPT), max_new_tokens=4)
        eng._mgr.check()
        assert eng._mgr.available() == eng._mgr.n_blocks
    finally:
        failpoints.reset()
        eng.stop()


def test_prefill_only_eos_skips_export(small):
    """A prefill whose first token IS eos has nothing to migrate: the
    engine finishes it down the normal path (no pin, no gather, no
    host fetch) and the result carries no kv_export."""
    eng = _engine(small)
    try:
        t0 = eng.generate(PROMPT, max_new_tokens=1)["tokens"][0]
        out = eng.submit(PROMPT, max_new_tokens=1, eos_id=t0,
                         prefill_only=True).result(timeout=300)
        assert out["tokens"] == [t0]
        assert "kv_export" not in out
        assert eng.kv_exports == 0
        eng._mgr.check()
    finally:
        eng.stop()


def test_pd_kill_switch_serves_unified_locally(small, monkeypatch):
    """RAY_TPU_PD_DISAGG=0 on a prefill-role server: requests are
    served end-to-end on the local engine (no export, no migration) —
    the legacy unified path, restorable in the same run."""
    from ray_tpu.serve.llm import LLMServer

    cfg, params = small
    monkeypatch.setenv("RAY_TPU_PD_DISAGG", "0")
    srv = LLMServer(cfg, params=params, max_batch=2, max_len=64,
                    page_size=8, seed=0, role="prefill",
                    decode_deployment="decode")
    try:
        out = asyncio.run(srv.__call__(
            {"prompt": PROMPT[:12], "max_new_tokens": 4}))
        assert len(out["tokens"]) == 4
        assert srv.engine.kv_exports == 0
        assert srv.stats()["pd"]["migrations"] == 0
        # Per-request override is the other same-run toggle.
        monkeypatch.delenv("RAY_TPU_PD_DISAGG")
        out2 = asyncio.run(srv.__call__(
            {"prompt": PROMPT[:12], "max_new_tokens": 4,
             "disagg": False}))
        assert len(out2["tokens"]) == 4
        assert srv.engine.kv_exports == 0
    finally:
        srv.shutdown()


def test_llmserver_role_validation(small):
    from ray_tpu.serve.llm import LLMServer

    cfg, params = small
    with pytest.raises(ValueError, match="role"):
        LLMServer(cfg, params=params, role="shard")
    with pytest.raises(ValueError, match="decode pool"):
        LLMServer(cfg, params=params, role="prefill")
    # A dangling decode target (role not prefill) would silently serve
    # unified forever — rejected at construction.
    with pytest.raises(ValueError, match="only applies"):
        LLMServer(cfg, params=params, decode_deployment="d")
    # reconfigure enforces the same combination checks, and a REJECTED
    # reconfigure must leave the server untouched.
    srv = LLMServer(cfg, params=params, max_batch=2, max_len=64,
                    page_size=8)
    try:
        with pytest.raises(ValueError, match="decode pool"):
            srv.reconfigure({"role": "prefill"})
        assert srv._role == "unified" and srv._decode_dep is None
    finally:
        srv.shutdown()


# ---------------------------------------------------------------- serve
def _armable_llm():
    """LLMServer + a test hook to arm a failpoint inside THIS replica's
    process (the serve-chaos pattern of test_failpoints.py).  Defined
    inside a function so cloudpickle ships it BY VALUE — replica
    workers need no importable test module."""
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


def _ref_tokens(cfg, prompt, n, seed=11):
    """What an UNSPLIT engine produces: built exactly the way a replica
    builds its engine (params derived from the engine seed), so serve
    PD results can be compared token-for-token."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(cfg, None, seed=seed, paged=True, max_batch=2,
                    max_len=64, page_size=8, steps_per_sync=4)
    eng.start()
    try:
        return eng.generate(prompt, max_new_tokens=n)["tokens"]
    finally:
        eng.stop()


def _pd_app(serve, cfg, *, decode_replicas=1, decode_cls=None,
            prefill_cls=None, seed=11):
    from ray_tpu.serve.llm import LLMServer

    ekw = dict(max_batch=2, max_len=64, page_size=8, steps_per_sync=4,
               seed=seed)
    Decode = serve.deployment(decode_cls or LLMServer).options(
        name="decode", num_replicas=decode_replicas,
        max_ongoing_requests=4)
    decode_app = Decode.bind(cfg, role="decode", **ekw)
    Prefill = serve.deployment(prefill_cls or LLMServer).options(
        name="prefill", num_replicas=1, max_ongoing_requests=4)
    return Prefill.bind(cfg, role="prefill",
                        decode_deployment=decode_app, **ekw)


@pytest.fixture
def serve_ray(small):
    import ray_tpu
    from ray_tpu import serve

    if not ray_tpu.is_initialized():
        ray_tpu.init(resources={"CPU": 4})
    serve.start()
    yield serve
    serve.shutdown()


def test_pd_through_serve_token_parity(serve_ray, small):
    """Full-stack disaggregation: client → prefill replica → KV pages
    through the object plane → decode replica → client, with greedy
    tokens identical to a unified single-engine run, and the migration
    visible in both replicas' metrics."""
    cfg, params = small
    h = serve_ray.run(_pd_app(serve_ray, cfg), name="pd_app",
                      route_prefix="/pd")
    try:
        ref = _ref_tokens(cfg, PROMPT[:13], 6)
        out = h.remote({"prompt": PROMPT[:13],
                        "max_new_tokens": 6}).result(timeout_s=300)
        assert out["tokens"] == ref
        assert out.get("disagg") is True
        rm = serve_ray.replica_metrics("pd_app")
        pre = next(iter(rm["pd_app"]["prefill"].values()))["user_stats"]
        dec = next(iter(rm["pd_app"]["decode"].values()))["user_stats"]
        assert pre["kv_exports"] >= 1
        assert pre["pd"]["migrations"] >= 1
        assert pre["pd"]["kv_migrate_bytes"] > 0
        assert dec["kv_imports"] >= 1
        assert dec["pd"]["kv_pull_bytes"] > 0
        # Per-request kill switch: unified on the prefill replica.
        out2 = h.remote({"prompt": PROMPT[:13], "max_new_tokens": 6,
                         "disagg": False}).result(timeout_s=300)
        assert out2["tokens"] == ref
        rm2 = serve_ray.replica_metrics("pd_app")
        pre2 = next(iter(rm2["pd_app"]["prefill"].values()))["user_stats"]
        assert pre2["pd"]["migrations"] == pre["pd"]["migrations"]
        # Prefix-summary digest moved once serving committed blocks —
        # the signal the cache-aware router polls.
        assert pre2["kv"]["prefix_summary"]["digest"] != 0
    finally:
        serve_ray.delete("pd_app")


@pytest.mark.chaos
def test_decode_crash_mid_migration_completes_on_survivor(serve_ray,
                                                          small):
    """serve.kv_import=crash armed on BOTH replicas of a 2-replica
    decode pool: the chosen decode replica dies mid-migration, the
    handle requeues the import — cache-aware routing would otherwise
    steer every identical prompt to whichever replica imported first,
    so a single armed replica might never be chosen — and the requeue
    target dies too.  The request must STILL complete with the right
    tokens (replacement import, full re-prefill on a freshly started
    replica, or the prefill engine's local fallback — all
    greedy-identical), ending at zero leaked arena pins and zero
    leaked KV blocks on every surviving engine."""
    from test_chaos_adversarial import _arena_pins_settle

    cfg, params = small
    h = serve_ray.run(
        _pd_app(serve_ray, cfg, decode_replicas=2,
                decode_cls=_armable_llm()),
        name="pd_chaos", route_prefix="/pdc")
    try:
        ref = _ref_tokens(cfg, PROMPT[:13], 6)
        dh = serve_ray.get_deployment_handle("decode", "pd_chaos")
        # Arm EVERY decode replica: sequential no-prompt arm calls ride
        # pow-2, which ties are randomized — loop until both pids seen.
        armed = set()
        for _ in range(40):
            armed.add(dh.arm.remote(
                "serve.kv_import", "nth:1+crash").result(timeout_s=120))
            if len(armed) == 2:
                break
        assert len(armed) == 2, f"could not arm both replicas: {armed}"
        results = [h.remote({"prompt": PROMPT[:13],
                             "max_new_tokens": 6}).result(timeout_s=300)
                   for _ in range(4)]
        for r in results:
            assert r["tokens"] == ref
        # The window genuinely fired: the first migration's target died,
        # and its requeue killed the second armed replica too.
        deadline = time.monotonic() + 60
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
                f"armed decode replicas {alive} still alive — "
                f"serve.kv_import never fired")
        # Zero leaked KV blocks on every live engine (kv_check raises
        # on any inconsistency; several calls spread over the pool).
        checks = [dh.kv_check.remote().result(timeout_s=120)
                  for _ in range(4)]
        assert all(c["ok"] for c in checks)
        ph = serve_ray.get_deployment_handle("prefill", "pd_chaos")
        assert ph.kv_check.remote().result(timeout_s=120)["ok"]
        # Zero leaked arena pins: the dead replica's borrow of the
        # migrated KV object must be swept.
        stats = _arena_pins_settle()
        assert not stats.get("swept_dead_pins", 0), stats
    finally:
        serve_ray.delete("pd_chaos")


@pytest.mark.chaos
def test_kv_import_error_falls_back_to_full_reprefill(serve_ray, small):
    """serve.kv_import=error on the (single) decode replica: the import
    faults without killing the replica; the prefill replica falls back
    to a FULL re-prefill on that surviving decode replica — request
    completes (greedy-identical), fallback counted, all block managers
    clean, no leaked pins."""
    from test_chaos_adversarial import _arena_pins_settle

    cfg, params = small
    h = serve_ray.run(
        _pd_app(serve_ray, cfg, decode_replicas=1,
                decode_cls=_armable_llm()),
        name="pd_fb", route_prefix="/pdf")
    try:
        ref = _ref_tokens(cfg, PROMPT[:13], 6)
        dh = serve_ray.get_deployment_handle("decode", "pd_fb")
        dh.arm.remote("serve.kv_import",
                      "nth:1+error").result(timeout_s=120)
        out = h.remote({"prompt": PROMPT[:13],
                        "max_new_tokens": 6}).result(timeout_s=300)
        assert out["tokens"] == ref
        assert out.get("pd_fallback") == "full_reprefill"
        rm = serve_ray.replica_metrics("pd_fb")
        pre = next(iter(rm["pd_fb"]["prefill"].values()))["user_stats"]
        dec = next(iter(rm["pd_fb"]["decode"].values()))["user_stats"]
        assert pre["pd"]["fallbacks"] >= 1
        assert dec["kv_imports"] == 0          # the import never landed
        # The survivor really re-prefilled the whole prompt.
        assert dec["prefill_tokens"] >= 13
        assert dh.kv_check.remote().result(timeout_s=120)["ok"]
        ph = serve_ray.get_deployment_handle("prefill", "pd_fb")
        assert ph.kv_check.remote().result(timeout_s=120)["ok"]
        stats = _arena_pins_settle()
        assert not stats.get("swept_dead_pins", 0), stats
    finally:
        serve_ray.delete("pd_fb")


@pytest.mark.chaos
def test_kv_export_error_serves_locally(serve_ray, small):
    """serve.kv_export=error on the prefill replica: the export window
    faults; the replica serves the request unified on its own engine
    (fallback='export_failed' → local path) with no leaked blocks."""
    cfg, params = small
    h = serve_ray.run(
        _pd_app(serve_ray, cfg, prefill_cls=_armable_llm()),
        name="pd_exp", route_prefix="/pde")
    try:
        ph = serve_ray.get_deployment_handle("prefill", "pd_exp")
        ph.arm.remote("serve.kv_export",
                      "nth:1+error").result(timeout_s=120)
        out = h.remote({"prompt": PROMPT[:13],
                        "max_new_tokens": 6}).result(timeout_s=300)
        assert len(out["tokens"]) == 6
        assert out.get("pd_fallback") == "export_failed"
        rm = serve_ray.replica_metrics("pd_exp")
        pre = next(iter(rm["pd_exp"]["prefill"].values()))["user_stats"]
        assert pre["pd"]["fallbacks"] >= 1
        assert pre["pd"]["migrations"] == 0
        assert ph.kv_check.remote().result(timeout_s=120)["ok"]
    finally:
        serve_ray.delete("pd_exp")
