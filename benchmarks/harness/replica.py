"""The replica the serve cells deploy: `LLMServer` with methods ADDED and
none changed.  It lives with the benchmark because only the process that
holds the chip can make the weights there, trace the device, and run the
plain reference on the served parameters.  Every `bench_*` method runs
outside the measured window except the two that bracket the trace."""
from __future__ import annotations

import os
import time

from ray_tpu.serve.llm import LLMServer

from . import spec


def _device_info() -> dict:
    import jax

    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "pid": os.getpid(),
            "memory_peak_bytes": max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs),
            "bytes_limit": stats.get("bytes_limit"),
            "is_device_worker": os.environ.get("RAY_TPU_IS_DEVICE_WORKER"),
            "compilation_cache_dir": os.environ.get(
                "JAX_COMPILATION_CACHE_DIR")}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62 (PRNGKey itself takes
    32 signed bits)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


class BenchLLMServer(LLMServer):
    def __init__(self, model: dict, *, family: str, seed: int, **engine_kw):
        import jax

        fam = spec.load_family(family, "serve")
        cfg = fam.program_config(model, max_seq=engine_kw["max_len"])
        t0 = time.perf_counter()
        # ONE jitted program makes every weight on the device from the
        # seed, in the dtype it is served in (PR 22: eager init compiled
        # a program per distinct shape, ~80 s cold).
        params = jax.jit(lambda k: fam.init_params(k, cfg))(seed_key(seed))
        jax.block_until_ready(params)
        self._bench_family = fam
        self._bench_model = dict(model)
        self._bench_times = {"init_params_s": time.perf_counter() - t0}
        t0 = time.perf_counter()
        # the engine's own seed only keys SAMPLING (the cells decode
        # greedily) and is baked into its programs as a constant: a seed
        # that moved with --seed would miss the compile cache in every run
        super().__init__(cfg, params=params, seed=0, **engine_kw)
        self._bench_times["engine_init_s"] = time.perf_counter() - t0
        self._bench_trace_dir = None

    # ------------------------------------------------------------ set-up
    def bench_probe(self) -> dict:
        return {**_device_info(), **self._bench_times}

    def bench_warmup(self, min_prompt: int, max_prompt: int) -> dict:
        """Run, once each, exactly the programs this traffic can reach:
        every (wave width, length bucket) prefill whose bucket a prompt
        of min_prompt..max_prompt tokens maps to, then one decode window.
        The engine forms a wave from what waits when it looks, so the
        loop is stopped while each wave's requests are queued."""
        eng = self.engine

        def bucket(n):
            return next(b for b in eng._buckets if b >= n)

        buckets = [b for b in eng._buckets
                   if bucket(min_prompt) <= b <= bucket(max_prompt)]
        done = []
        for b in buckets:
            n = min(b, max_prompt)
            for w in eng._width_buckets:
                t0 = time.perf_counter()
                eng.stop()
                futs = [eng.submit([1 + (i + j) % 97 for j in range(n)],
                                   max_new_tokens=1, _cache_ok=False)
                        for i in range(w)]
                eng.start()
                for f in futs:
                    f.result(timeout=600.0)
                done.append([w, b, time.perf_counter() - t0])
        t0 = time.perf_counter()
        k = eng.steps_per_sync
        eng.generate(list(range(1, min_prompt + 1)), max_new_tokens=k + 1,
                     _cache_ok=False)
        done.append([eng.max_batch, f"decode_k{k}", time.perf_counter() - t0])
        return {"programs": done}

    # ------------------------------------------------------------- trace
    def bench_trace_start(self, trace_dir: str) -> float:
        from . import trace_reduce

        self._bench_trace_dir = trace_dir
        trace_reduce.start(trace_dir)
        return time.time()

    def bench_trace_stop(self) -> float:
        import jax

        jax.profiler.stop_trace()
        return time.time()

    def bench_trace_reduce(self, dump_to: str | None = None) -> dict:
        from . import trace_reduce

        # 50 ms off each end: the profiler's own start and stop
        return trace_reduce.reduce_dir(self._bench_trace_dir, dump_to,
                                       margin_s=0.05)

    def bench_spans(self, prefix: str = "llm.") -> list[dict]:
        """This process's flight-recorder ring, the engine's spans only."""
        from ray_tpu import tracing

        return [{"name": r["name"], "t0": r["t0"], "t1": r["t1"],
                 "tid": r["tid"], "attrs": r["attrs"]}
                for r in tracing.snapshot() if r["name"].startswith(prefix)]

    def bench_device_stats(self) -> dict:
        return _device_info()

    # ----------------------------------------------------------- correct
    def bench_reference(self, samples: list) -> dict:
        """Teacher-forced logit gaps of served tokens under the family's
        plain reference, on the parameters this replica serves."""
        ref = self._bench_family.reference()
        t0 = time.perf_counter()
        gaps = [ref.teacher_forced_gaps(
            self.engine.params, prompt, served, self._bench_model)
            for prompt, served in samples]
        return {"gaps": gaps, "wall_s": time.perf_counter() - t0}
