#!/usr/bin/env python3
"""Compile the benchmark's programs at their REAL sizes for a DESCRIBED
v5e (the TPU compiler is installed in the sandbox; no chip is attached)
and print what each needs: argument, output and temporary bytes, kernel
counts, collectives.  The model comes from the configuration's family
file, so a new family's programs are sized here before chip time is
spent.  One process, run by hand (a test calls `serve` at a tiny size on
the CPU's devices, never the described chip):

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_check.py [config ...]

A compile that passes is not a chip run.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def emit(**kv):
    print(json.dumps(kv), flush=True)


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: getattr(m, k + "_size_in_bytes", None)
            for k in ("argument", "output", "temp", "alias")}


def serve(name: str, cfg: dict, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness import spec
    from ray_tpu.serve.llm import LLMEngine

    one = SingleDeviceSharding(topo.devices[0])
    fam = spec.config_family(cfg)
    model = fam.published(cfg)
    eng_kw = dict(cfg["engine"], paged=True)
    lcfg = fam.program_config(model, max_seq=eng_kw["max_len"])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def abstract(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    t0 = time.perf_counter()
    init = jax.jit(lambda k: fam.init_params(k, lcfg),
                   out_shardings=one).lower(
        sds((2,), jnp.uint32)).compile()
    emit(config=name, program="init_params", wall_s=time.perf_counter() - t0,
         params=fam.param_count(model), **mem(init))
    params = abstract(jax.eval_shape(
        lambda: fam.init_params(jax.random.PRNGKey(0), lcfg)))
    eng = LLMEngine(lcfg, params, **eng_kw)
    i32, f32 = jnp.int32, jnp.float32
    b, k = eng.max_batch, eng.steps_per_sync
    t0 = time.perf_counter()
    low = eng._decode_fns[k].lower(
        params, abstract(eng.cache), sds((b,), i32), sds((b,), f32),
        sds((b, eng._maxp), i32), sds((b,), i32), sds((b,), i32), None)
    emit(config=name, program=f"decode_k{k}",
         wall_s=time.perf_counter() - t0,
         tpu_custom_call=low.as_text().count("tpu_custom_call"),
         **mem(low.compile()))
    w = max(eng._width_buckets)
    for p in sorted({eng._buckets[-2], 1024, 4096} & set(eng._buckets)):
        t0 = time.perf_counter()
        low = eng._prefill_fwd.lower(
            params, sds((w, p), i32), sds((w,), i32), sds((w,), i32),
            sds((w,), f32), sds((w,), i32), sds((w,), i32), None)
        emit(config=name, program=f"prefill_w{w}_p{p}",
             wall_s=time.perf_counter() - t0,
             tpu_custom_call=low.as_text().count("tpu_custom_call"),
             **mem(low.compile()))


def train(name: str, cfg: dict, topo) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from benchmarks.harness import spec
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.train import step as train_step

    t = cfg["train"]
    fam = spec.config_family(cfg)
    lcfg = fam.program_config(fam.published(cfg), max_seq=t["seq"],
                              remat_mode=t["remat_mode"])
    mesh = create_mesh(MeshConfig(**t["mesh"]), devices=list(topo.devices))
    assert isinstance(mesh, Mesh)
    optimizer = getattr(train_step, t["optimizer"])(
        total_steps=t["total_steps"])
    st_sh = train_step.state_shardings(lcfg, mesh, optimizer)
    b_sh = train_step.batch_shardings(mesh)
    state = jax.eval_shape(lambda k: train_step.create_train_state(
        k, lcfg, optimizer), jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, st_sh)
    tok = jax.ShapeDtypeStruct((t["batch"], t["seq"]), np.int32,
                               sharding=b_sh)
    step_fn = train_step.sharded_train_step(lcfg, optimizer, mesh)
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        low = step_fn.lower(state, {"inputs": tok, "targets": tok})
        comp = low.compile()
    hlo = comp.as_text()
    emit(config=name, program="train_step", wall_s=time.perf_counter() - t0,
         tpu_custom_call=low.as_text().count("tpu_custom_call"),
         collectives={c: hlo.count(f" {c}(") + hlo.count(f" {c}-start(")
                      for c in ("all-gather", "all-reduce", "reduce-scatter",
                                "all-to-all", "collective-permute")},
         **mem(comp))


def main() -> None:
    from jax.experimental import topologies

    from benchmarks.harness import spec

    from ray_tpu.ops import flash_attention, paged_attention

    # the kernels ask jax.default_backend(), which is the CPU here:
    # steer them to their compiled form in this tool, as
    # tests/test_chip_compile.py does in its fixture
    flash_attention._interpret = lambda: False
    paged_attention._interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # attention()'s "auto" gate asks jax.devices() for a TPU
    import jax

    jax.devices = lambda *a: list(topo.devices)
    bench = spec.benchmark_json()
    want = sys.argv[1:] or [c["name"] for c in bench["configs"]]
    for c in bench["configs"]:
        if c["name"] not in want:
            continue
        cfg = spec.load_json(os.path.join(ROOT, c["file"]))
        (serve if cfg["kind"] == "serve" else train)(c["name"], cfg, topo)


if __name__ == "__main__":
    main()
