"""Continuous-batched LLM engine: numerics vs full forward, slot reuse,
concurrency, and the Serve deployment body.

Reference analog: serve LLM workloads (ray: release/serve_tests/) — here
correctness-tested at debug scale on CPU: incremental prefill+decode must
reproduce the full-context forward pass exactly (fp32).
"""
import concurrent.futures

import numpy as np
import pytest

from serving_reference import Seam, reference_greedy, served_logits


@pytest.fixture(scope="module")
def small():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=128, max_seq=64, remat=False, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    return cfg, params


@pytest.mark.parametrize("n_heads,n_kv_heads", [(4, 4), (8, 2)],
                         ids=["rep1", "rep4"])
def test_padded_prefill_then_paged_decode_equals_the_full_forward(
        n_heads, n_kv_heads):
    """The seam's three programs against `llama.forward` on the whole
    sequence: a prompt of 21 at a bucket of 32 through serve_prefill and
    serve_scatter, then two windows of K teacher-forced steps of
    serve_decode_step with the tails merged between them, give the full
    forward's logits at every position (float32: summation order)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=256, dim=16 * n_heads, n_layers=2, n_heads=n_heads,
        n_kv_heads=n_kv_heads, ffn_dim=128, max_seq=128, remat=False,
        dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(n_heads), cfg)
    rng = np.random.default_rng(1)
    prompt, follow = rng.integers(0, 256, 21), rng.integers(0, 256, 8)
    got = served_logits(Seam(llama, cfg), params, cfg, prompt, follow, 32)
    seq = jnp.asarray([list(prompt) + list(follow)])
    want = llama.forward(params, seq, cfg)[0, len(prompt) - 1:]
    assert got.shape == want.shape and float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=0)


@pytest.mark.parametrize("how", ["engine", "server", "reconfigure"])
def test_paged_false_is_refused(small, how):
    """The dense layout is gone; `paged` stays accepted for the callers
    that pass True.  A refused reconfigure leaves the server as it was."""
    from ray_tpu.serve.llm import LLMEngine, LLMServer

    cfg, params = small
    gone = "dense KV layout was removed"
    if how == "engine":
        with pytest.raises(ValueError, match=gone):
            LLMEngine(cfg, params, max_batch=2, max_len=64, paged=False)
        return
    if how == "server":
        with pytest.raises(ValueError, match=gone):
            LLMServer(cfg, params=params, max_batch=2, max_len=64,
                      paged=False)
        return
    server = LLMServer(cfg, params=params, max_batch=2, max_len=64,
                       kv_pages=9, page_size=16, paged=True)
    try:
        eng, kwargs = server.engine, dict(server._engine_kwargs)
        with pytest.raises(ValueError, match=gone):
            server.reconfigure({"paged": False, "role": "decode",
                                "kv_blocks": 17})
        assert server.engine is eng and eng._thread.is_alive()
        assert server._role == "unified"
        assert server._engine_kwargs == kwargs and eng.n_pages == 9
        assert len(eng.generate([3, 1, 4], max_new_tokens=4)["tokens"]) == 4
    finally:
        server.engine.stop()


def test_engine_matches_full_forward_greedy(small):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = small
    eng = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        for prompt in ([5, 9, 2], [17, 3, 44, 8, 11, 23, 6]):
            got = eng.generate(prompt, max_new_tokens=8)
            assert got["tokens"] == reference_greedy(
                params, cfg, prompt, 8), prompt
            assert got["ttft_s"] > 0 and got["total_s"] >= got["ttft_s"]
    finally:
        eng.stop()


def test_continuous_batching_oversubscribed(small):
    """More requests than slots: admission waits for free slots, every
    request completes, greedy results stay independent of batching."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = small
    eng = LLMEngine(cfg, params, max_batch=2, max_len=64)
    eng.start()
    try:
        prompts = [[i + 1, i + 2, i + 3] for i in range(5)]
        futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        results = [f.result(timeout=120) for f in futs]
        assert eng.completed == 5
        for p, r in zip(prompts, results):
            assert r["tokens"] == reference_greedy(params, cfg, p, 6), p
    finally:
        eng.stop()


def test_eos_stops_generation(small):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = small
    eng = LLMEngine(cfg, params, max_batch=1, max_len=64)
    try:
        free_run = eng.generate([5, 9, 2], max_new_tokens=8)
        eos = free_run["tokens"][2]
        stopped = eng.generate([5, 9, 2], max_new_tokens=8, eos_id=eos)
        assert stopped["tokens"] == free_run["tokens"][:3]
    finally:
        eng.stop()


def test_llm_server_deployment_body(small):
    import asyncio

    from ray_tpu.serve.llm import LLMServer

    cfg, params = small
    server = LLMServer(cfg, params=params, max_batch=2, max_len=64)
    try:
        async def drive():
            return await asyncio.gather(*[
                server({"prompt": [3, 1, 4], "max_new_tokens": 4})
                for _ in range(3)])

        results = asyncio.run(drive())
        assert all(len(r["tokens"]) == 4 for r in results)
        assert server.stats()["completed"] >= 3
    finally:
        server.engine.stop()
