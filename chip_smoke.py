#!/usr/bin/env python3
"""Chip smoke: the served-model path, once, on a real TPU.

Default run (one chip): `ray_tpu.init()` finds the chip itself, a
`num_tpus=1` task and an `LLMServer` replica land in the node's device
worker, eight greedy requests at Llama-3-8B widths (depth cut to fit
one 16 GB chip) come back through the serve handle, and the cluster is
torn down with nothing left behind.  This parent process never
initializes a jax backend: the chip belongs to the device worker.

`--chips 4` runs ONLY the sharded train step on a four-chip mesh (in
one child process) against the same parameters on a one-device mesh.
`--size tiny` is the CPU rehearsal (`debug` model); it walks every step
and always ends `"ok": false` — no argument makes a CPU run pass.

Output: one JSON object per line.  Numbers are smoke readings, never
benchmarks.  The LAST line is `{"ok": ..., "device": {...}}`; the exit
code is 0 only when every step passed on a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import signal
import subprocess
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

# The served deployment, per --size.  "full" is llama3-8b at its
# published widths; only n_layers is cut.  Depth 8 was settled from
# compiled.memory_analysis() of the engine's programs on a described
# v5e chip (tests/test_chip_compile.py re-checks it): params 5.6 GB +
# 0.55 GB page pool + program temporaries stay under 16 GB with room
# for init's fp32 temporaries.
SIZES = {
    "full": dict(model="llama3-8b", n_layers=8, max_len=2048,
                 page_size=512, max_batch=8, steps_per_sync=8,
                 prompt_lens=(128, 512), new_tokens=32),
    "tiny": dict(model="debug", n_layers=2, max_len=128,
                 page_size=16, max_batch=8, steps_per_sync=8,
                 prompt_lens=(32, 64), new_tokens=32),
}
# The four-chip train step, per --size: (model, n_layers, seq, batch).
TRAIN_SIZES = {
    "full": dict(model="llama3-8b", n_layers=2, seq=2048, batch=4),
    "tiny": dict(model="debug", n_layers=2, seq=64, batch=4),
}
N_TRAIN_STEPS = 3
DEADLINE_S = 1100          # whole-run watchdog (the contract: 1200 s)


def emit(**kv) -> None:
    print(json.dumps(kv), flush=True)


def served_config(size: str):
    """(LlamaConfig, engine kwargs) of the served deployment."""
    from ray_tpu.models import llama

    s = SIZES[size]
    cfg = dataclasses.replace(llama.llama_configs()[s["model"]],
                              n_layers=s["n_layers"])
    eng = dict(max_batch=s["max_batch"], max_len=s["max_len"],
               page_size=s["page_size"],
               steps_per_sync=s["steps_per_sync"], paged=True)
    return cfg, eng


def prefill_shapes(size: str) -> list[tuple[int, int]]:
    """(wave width, length bucket) of every prefill program the eight
    requests can reach: lone requests ride width 1, bursts width 8."""
    s = SIZES[size]
    return [(w, p) for p in s["prompt_lens"] for w in (1, s["max_batch"])]


def engine_lowerings(cfg, eng_kw: dict, shapes, sharding=None) -> dict:
    """Lower the paged engine's prefill and decode programs at the
    served shapes from ABSTRACT params (no weights are made; the engine
    allocates only its page pool).  `sharding` places every argument —
    a test passes a described chip's SingleDeviceSharding to compile
    for a chip that is not attached."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def abstract(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    params = abstract(jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg)))
    eng = LLMEngine(cfg, params, **eng_kw)
    i32, f32 = jnp.int32, jnp.float32
    out = {}
    def lora(rows):      # None without adapter banks (`lora_slots`)
        return abstract(eng._lora_args([0] * rows))

    for w, p in shapes:
        out[f"prefill_w{w}_p{p}"] = eng._prefill_fwd.lower(
            params, sds((w, p), i32), sds((w,), i32), sds((w,), i32),
            sds((w,), f32), sds((w,), i32), sds((w,), i32), lora(w))
    b, k = eng.max_batch, eng.steps_per_sync
    out[f"decode_k{k}"] = eng._decode_fns[k].lower(
        params, abstract(eng.cache), sds((b,), i32), sds((b,), f32),
        sds((b, eng._maxp), i32), sds((b,), i32), sds((b,), i32), lora(b))
    return out


def _backend_initialized() -> bool:
    """Did THIS process bring up a jax backend?"""
    xb = sys.modules.get("jax._src.xla_bridge")
    return bool(xb is not None and xb.backends_are_initialized())


class Smoke:
    """Failure ledger: a step that raises is recorded and ends the run
    (later steps need it); a failed check is recorded and the walk goes
    on.  `ok` is true only with an empty ledger."""

    def __init__(self):
        self.failures: list[str] = []
        self.device = {"platform": None, "kind": None, "count": 0}

    def check(self, cond: bool, what: str) -> bool:
        if not cond:
            self.failures.append(what)
            emit(check_failed=what)
        return bool(cond)

    def finish(self) -> int:
        self.check(not _backend_initialized(),
                   "the parent process initialized a jax backend")
        ok = not self.failures
        if not ok:
            emit(failures=self.failures)
        emit(ok=ok, device=self.device)
        return 0 if ok else 1


# ------------------------------------------------------------ device side
def _device_probe() -> dict:
    """Runs in the device worker: what jax sees there."""
    import jax

    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "default_backend": jax.default_backend(),
            "pid": os.getpid(),
            "is_device_worker": os.environ.get("RAY_TPU_IS_DEVICE_WORKER"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
            "compilation_cache_dir": os.environ.get(
                "JAX_COMPILATION_CACHE_DIR")}


def _device_kernel_counts(cfg, eng_kw: dict, shapes) -> dict:
    """Runs in the device worker: lower the served programs there (same
    process, same backend as the replica) and count the Pallas kernels
    in each."""
    return {name: low.as_text().count("tpu_custom_call")
            for name, low in engine_lowerings(cfg, eng_kw, shapes).items()}


# ------------------------------------------------------------ one chip
def run_serve(size: str, seed: int, smoke: Smoke) -> None:
    import random

    import psutil

    import ray_tpu
    from ray_tpu import serve

    s = SIZES[size]
    cfg, eng_kw = served_config(size)
    emit(step="config", size=size, model=s["model"],
         n_layers_chosen=cfg.n_layers, dim=cfg.dim, n_heads=cfg.n_heads,
         n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
         ffn_dim=cfg.ffn_dim, vocab_size=cfg.vocab_size,
         dtype=str(getattr(cfg.dtype, "__name__", cfg.dtype)),
         params=cfg.num_params(), **eng_kw)
    if size == "tiny":
        smoke.check(False, "--size tiny is a rehearsal and never passes")

    probe = ray_tpu.remote(num_tpus=1)(_device_probe)
    kernels = ray_tpu.remote(num_tpus=1)(_device_kernel_counts)

    # 1. the node finds its chip itself
    t0 = time.perf_counter()
    node_ids: list[str] = []
    ray_tpu.init()
    try:
        node_ids = [n["node_id"] for n in ray_tpu.nodes()]
        total = ray_tpu.cluster_resources()
        emit(step="init", wall_s=time.perf_counter() - t0, resources=total)

        # 2. the device worker holds the chip
        if not total.get("TPU"):
            raise RuntimeError(
                "the node advertises no TPU resource: detect_resources() "
                "found no chip device nodes (/dev/accel*, /dev/vfio/<n>) "
                "and RAY_TPU_CHIPS is unset — a num_tpus=1 lease would "
                "stay pending")
        t0 = time.perf_counter()
        dev = ray_tpu.get(probe.remote(), timeout=300.0)
        emit(step="device_probe", wall_s=time.perf_counter() - t0, **dev)
        smoke.device = {"platform": dev["platform"], "kind": dev["kind"],
                        "count": dev["count"]}
        on_tpu = smoke.check(
            dev["platform"] == "tpu" and dev["default_backend"] == "tpu",
            f"the device worker's jax came up on {dev['platform']!r}, "
            "not 'tpu'")
        if not on_tpu and size == "full":
            raise RuntimeError("no TPU: not serving at full width")
        smoke.check(dev["is_device_worker"] == "1",
                    "the num_tpus=1 task did not run in the device worker")
        smoke.check(dev["count"] == 1, f"{dev['count']} devices, want 1")

        # 3. the served model, in the same process
        t_setup = time.perf_counter()
        app = serve.deployment(serve.LLMServer).options(
            name="llm", ray_actor_options={"num_tpus": 1},
        ).bind(cfg, seed=seed, **eng_kw)
        handle = serve.run(app, name="smoke", timeout_s=600.0)
        emit(step="serve_run", wall_s=time.perf_counter() - t_setup)

        # 4. eight requests: one alone (first response, compile
        # included), an identical pair, then the rest as a burst
        rng = random.Random(seed)
        short, long_ = s["prompt_lens"]

        def prompt(n):
            return [rng.randrange(cfg.vocab_size) for _ in range(n)]

        twin = prompt(short)
        groups = [[prompt(short)], [twin, list(twin)],
                  [prompt(short)] + [prompt(long_) for _ in range(4)]]
        results, t_req0 = [], time.perf_counter()
        for group in groups:
            t0 = time.perf_counter()
            pending = [handle.remote({"prompt": p,
                                      "max_new_tokens": s["new_tokens"]})
                       for p in group]
            for p, resp in zip(group, pending):
                out = resp.result(timeout_s=600.0)
                results.append((p, out))
                emit(step="request", n=len(results), prompt_len=len(p),
                     ttft_s=out.get("ttft_s"),
                     wall_s=time.perf_counter() - t0,
                     n_tokens=len(out["tokens"]))
            if len(results) == 1:
                emit(step="first_response",
                     setup_s=time.perf_counter() - t_setup,
                     note="set-up: replica start, weight init and "
                          "compiles included")
        wall = time.perf_counter() - t_req0
        n_new = sum(len(o["tokens"]) for _, o in results)
        emit(step="requests_done", n=len(results), wall_s=wall,
             smoke_tokens_per_s=n_new / wall,
             smoke_ttft_s=[o.get("ttft_s") for _, o in results],
             note="smoke readings, compiles included")
        smoke.check(len(results) == 8, "fewer than 8 responses")
        smoke.check(all(len(o["tokens"]) == s["new_tokens"]
                        and all(0 <= t < cfg.vocab_size
                                for t in o["tokens"])
                        for _, o in results),
                    f"a request did not return {s['new_tokens']} "
                    "in-vocabulary tokens")
        smoke.check(results[1][1]["tokens"] == results[2][1]["tokens"],
                    "identical prompts returned different tokens")
        smoke.check(len({tuple(o["tokens"]) for _, o in results}) > 1,
                    "every request returned the same tokens")

        reps = serve.replica_metrics("smoke")["smoke"]["llm"]
        (rep,) = reps.values()
        completed = (rep.get("user_stats") or {}).get("completed")
        emit(step="replica", pid=rep.get("pid"), completed=completed,
             device_worker_pid=dev["pid"])
        smoke.check((completed or 0) >= 8,
                    f"replica_metrics shows completed={completed}")
        smoke.check(rep.get("pid") == dev["pid"],
                    "the replica is not in the device worker's process")

        # 5. tear down; the device worker outlives its replica
        t0 = time.perf_counter()
        serve.delete("smoke")
        counts = ray_tpu.get(
            kernels.remote(cfg, eng_kw, prefill_shapes(size)),
            timeout=600.0)
        emit(step="kernels", tpu_custom_call=counts)
        smoke.check(all(n > 0 for n in counts.values()),
                    "a served program holds no Pallas kernel "
                    f"(tpu_custom_call counts: {counts})")
        after = ray_tpu.get(probe.remote(), timeout=300.0)
        emit(step="teardown", wall_s=time.perf_counter() - t0,
             pid=after["pid"],
             peak_bytes_in_use=after["peak_bytes_in_use"],
             bytes_limit=after["bytes_limit"],
             compilation_cache_dir=after["compilation_cache_dir"])
        smoke.check(after["pid"] == dev["pid"],
                    "the device worker did not survive serve.delete")
        smoke.check(not on_tpu or bool(after["peak_bytes_in_use"]),
                    "no peak device bytes reported")
        serve.shutdown()
    finally:
        # Every process below this one is the cluster it started.
        mine = psutil.Process().children(recursive=True)
        ray_tpu.shutdown()
        _, alive = psutil.wait_procs(mine, timeout=10.0)
        left = [p.pid for p in alive]
        shm = [f for n in node_ids
               for f in glob.glob(f"/dev/shm/raytpu_{n[:16]}_*")]
        emit(step="shutdown", leftover_processes=left, leftover_shm=shm)
        smoke.check(not left, f"processes left behind: {left}")
        smoke.check(not shm, f"shm segments left behind: {shm}")


# ------------------------------------------------------------ four chips
def multichip_child(size: str, seed: int) -> None:
    """The child of `--chips 4`: the ONE process that touches the chips.
    Sharded train step on a fsdp=2 x tensor=2 mesh, then the same
    seed-initialized parameters and tokens on a one-device mesh.  A full
    train state at these widths does not fit one chip beside its
    gradients (memory_analysis on a described chip: see
    tests/test_chip_compile.py), so the comparator is the forward loss
    the step differentiates."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import param_shardings
    from ray_tpu.train import step as train_step

    smoke = Smoke()
    try:
        t = TRAIN_SIZES[size]
        devs = jax.devices()
        smoke.device = {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs)}
        smoke.check(devs[0].platform == "tpu",
                    f"jax came up on {devs[0].platform!r}, not 'tpu'")
        if size == "tiny":
            smoke.check(False, "--size tiny is a rehearsal and never passes")
        if len(devs) != 4:
            raise RuntimeError(f"{len(devs)} devices, want 4")
        cfg = dataclasses.replace(llama.llama_configs()[t["model"]],
                                  n_layers=t["n_layers"], max_seq=t["seq"])
        emit(step="config", size=size, model=t["model"],
             n_layers=cfg.n_layers, seq=t["seq"], batch=t["batch"],
             params=cfg.num_params(), mesh={"fsdp": 2, "tensor": 2})
        mesh4 = create_mesh(MeshConfig(fsdp=2, tensor=2), devices=devs)
        mesh1 = create_mesh(MeshConfig(), devices=devs[:1])
        optimizer = train_step.default_optimizer(total_steps=10)
        key = jax.random.PRNGKey(seed)
        tokens = np.asarray(jax.random.randint(
            jax.random.PRNGKey(seed + 1), (t["batch"], t["seq"]), 0,
            cfg.vocab_size, jnp.int32))
        batch = {"inputs": tokens, "targets": tokens}

        t0 = time.perf_counter()
        state = train_step.sharded_init(key, cfg, optimizer, mesh4)
        jax.block_until_ready(state)
        emit(step="sharded_init", wall_s=time.perf_counter() - t0)

        # every parameter is laid out as its logical axes say
        want = param_shardings(llama.param_logical_axes(cfg), mesh4)
        per_device = {d.id: 0 for d in devs}
        bad = []
        for (path, leaf), sh in zip(
                jax.tree_util.tree_leaves_with_path(state.params),
                jax.tree.leaves(want)):
            name = jax.tree_util.keystr(path)
            shard_bytes = (int(np.prod(sh.shard_shape(leaf.shape)))
                           * leaf.dtype.itemsize)
            if not leaf.sharding.is_equivalent_to(sh, leaf.ndim):
                bad.append(f"{name}: {leaf.sharding.spec} != {sh.spec}")
            if len(leaf.sharding.device_set) != 4:
                bad.append(f"{name}: spans "
                           f"{len(leaf.sharding.device_set)} devices")
            for shard in leaf.addressable_shards:
                per_device[shard.device.id] += shard.data.nbytes
                if shard.data.nbytes != shard_bytes:
                    bad.append(f"{name}: shard of {shard.data.nbytes} B, "
                               f"want {shard_bytes} B")
        total = sum(x.nbytes for x in jax.tree.leaves(state.params))
        emit(step="param_layout", param_bytes_total=total,
             param_bytes_per_device=per_device, mismatches=bad[:8])
        smoke.check(not bad, f"parameter layout: {bad[:3]}")
        smoke.check(max(per_device.values()) < 0.26 * total,
                    "a device holds more than a quarter of the params")

        step_fn = train_step.sharded_train_step(cfg, optimizer, mesh4)
        with jax.set_mesh(mesh4):
            t0 = time.perf_counter()
            lowered = step_fn.lower(state, batch)
            compiled = lowered.compile()
            hlo = compiled.as_text()
            n_kernels = lowered.as_text().count("tpu_custom_call")
            collectives = {c: hlo.count(f" {c}(") + hlo.count(f" {c}-start(")
                           for c in ("all-gather", "all-reduce",
                                     "reduce-scatter", "all-to-all",
                                     "collective-permute")}
            mem = compiled.memory_analysis()
            emit(step="compile_4chip", wall_s=time.perf_counter() - t0,
                 tpu_custom_call=n_kernels, collectives=collectives,
                 argument_bytes=getattr(mem, "argument_size_in_bytes", None),
                 temp_bytes=getattr(mem, "temp_size_in_bytes", None))
            smoke.check(sum(collectives.values()) > 0,
                        "the 4-chip step holds no collective")
            smoke.check(n_kernels >= 3,
                        f"{n_kernels} tpu_custom_call in the 4-chip step, "
                        "want the flash forward and its two backward "
                        "kernels")
            losses = []
            for i in range(N_TRAIN_STEPS):
                t0 = time.perf_counter()
                state, m = step_fn(state, batch)
                losses.append(float(m["loss"]))
                emit(step="train_step", n=i + 1, loss=losses[-1],
                     grad_norm=float(m["grad_norm"]),
                     wall_s=time.perf_counter() - t0)
        smoke.check(all(np.isfinite(losses)), f"losses {losses}")
        peak4 = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs}
        del state

        # the comparator: same seed, same tokens, one device
        p_sh1 = param_shardings(llama.param_logical_axes(cfg), mesh1)
        with jax.set_mesh(mesh1):
            params1 = jax.jit(lambda k: llama.init_params(k, cfg),
                              out_shardings=p_sh1)(key)
            ref = float(jax.jit(
                lambda p, b: llama.loss_fn(p, b, cfg))(params1, batch))
        rel = abs(losses[0] - ref) / abs(ref)
        emit(step="compare", loss_4chip_step1=losses[0],
             loss_1device_forward=ref, rel_diff=rel,
             peak_bytes_in_use_per_device=peak4)
        smoke.check(np.isfinite(ref) and rel < 2e-2,
                    f"step-1 loss {losses[0]} vs one-device {ref}")
    except Exception:  # noqa: BLE001 - reported, then exit non-zero
        traceback.print_exc()
        smoke.failures.append("multichip: " + traceback.format_exc(limit=1)
                              .strip().splitlines()[-1])
    emit(child_result=True, failures=smoke.failures, device=smoke.device)


def run_multichip(size: str, seed: int, smoke: Smoke) -> None:
    """Parent side of `--chips 4`: stays off jax, relays the child."""
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(HERE, ".jax_cache"))
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "import chip_smoke; chip_smoke.multichip_child"
         f"({size!r}, {seed})"],
        cwd=HERE, env=env, stdout=subprocess.PIPE, text=True)
    result = None
    try:
        for line in proc.stdout:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                obj = None
            if isinstance(obj, dict) and obj.get("child_result"):
                result = obj
            else:
                print(line, flush=True)
        rc = proc.wait(timeout=60.0)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if result is None:
        raise RuntimeError(f"the 4-chip child ended (rc={rc}) with no result")
    smoke.device = result["device"]
    smoke.failures.extend(result["failures"])
    smoke.check(rc == 0, f"the 4-chip child exited with {rc}")
    smoke.check(smoke.device["count"] == 4,
                f"{smoke.device['count']} devices, want 4")


def _versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size", choices=sorted(SIZES), default="full",
                    help="tiny = CPU rehearsal (never passes)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = ONLY the sharded train step on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    def _deadline(signum, frame):
        raise TimeoutError(f"chip_smoke exceeded {DEADLINE_S}s")

    # The cluster's processes are `python -m ray_tpu...` children: they
    # find the package through the environment, whatever the cwd.
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (HERE, os.environ.get("PYTHONPATH")) if p)
    signal.signal(signal.SIGALRM, _deadline)
    signal.alarm(DEADLINE_S)
    smoke = Smoke()
    t0 = time.perf_counter()
    try:
        emit(step="start", chips=args.chips, size=args.size,
             seed=args.seed, **_versions())
        if args.chips == 4:
            run_multichip(args.size, args.seed, smoke)
        else:
            run_serve(args.size, args.seed, smoke)
    except Exception as e:  # noqa: BLE001 - recorded, exit non-zero
        traceback.print_exc()
        smoke.failures.append(f"{type(e).__name__}: {e}")
    signal.alarm(0)
    emit(step="end", wall_s=time.perf_counter() - t0)
    return smoke.finish()


if __name__ == "__main__":
    sys.exit(main())
