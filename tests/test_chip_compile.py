"""Compile the main path's kernels for a DESCRIBED TPU v5e chip.

No chip is attached: the TPU compiler installed here compiles for a
topology that is described, so Mosaic refusals (tile alignment, VMEM
budget) and programs that do not fit 16 GB show up in the sandbox at no
chip time.  A compile that passes is not a chip run.

This is the ONLY file that describes a chip, and the description
happens inside a fixture: only the xdist worker that runs this file may
load the TPU library (see the on-chip-measurement guide, section 2).
Interpret mode is steered off from here (the program's own gate sees the
CPU backend), not through an option of ops/.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

HBM_BYTES = 16 * 1024 ** 3      # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """interpret=False although the default backend is the CPU."""
    from ray_tpu.ops import flash_attention, paged_attention

    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)


def _compile(fn, *args):
    low = jax.jit(fn).lower(*args)
    return low, low.compile()


@pytest.mark.parametrize("B,kvh,rep,hd,page,kt,maxp", [
    (64, 4, 2, 128, 512, 64, 1),      # bench-350m serving shape
    (8, 8, 4, 128, 512, 8, 4),        # llama3-8b widths, chip_smoke's
    (8, 8, 4, 128, 512, 63, 4),       # unaligned tail length
    (8, 8, 4, 64, 512, 8, 4),         # head_dim 64
])
def test_paged_decode_attention_compiles(one_chip, compiled_kernels,
                                         B, kvh, rep, hd, page, kt, maxp):
    from ray_tpu.ops.paged_attention import paged_decode_attention

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n_pages = 1 + B * maxp
    low, _ = _compile(
        paged_decode_attention,
        s((B, kvh, rep, hd)), s((n_pages, kvh, page, hd)),
        s((n_pages, kvh, page, hd)), s((B, kvh, kt, hd)),
        s((B, kvh, kt, hd)), s((B, maxp), jnp.int32),
        s((B,), jnp.int32), s((B,), jnp.int32))
    assert low.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("b,s,h,kvh,hd,grad", [
    (8, 2048, 8, 4, 128, True),       # bench-350m train shape
    (1, 32768, 8, 4, 128, True),      # long context
    (2, 2048, 32, 8, 128, True),      # llama3-8b widths, 4-chip step
    (1, 8192, 32, 8, 128, True),
    (4, 128, 32, 8, 128, False),      # shortest served prefill bucket
    (8, 512, 32, 8, 128, False),      # chip_smoke's widest prefill wave
])
def test_flash_attention_compiles(one_chip, compiled_kernels,
                                  b, s, h, kvh, hd, grad):
    from ray_tpu.ops.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((b, s, h, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kvh, hd), jnp.bfloat16,
                              sharding=one_chip)
    if grad:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: flash_attention(*a).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)
    else:
        fn = flash_attention
    low, _ = _compile(fn, q, kv, kv)
    assert low.as_text().count("tpu_custom_call") == (3 if grad else 1)


@pytest.mark.parametrize("m,k,n", [
    (256, 2048, 3072),        # LFM2-24B-A2B decode: 64 lanes x 4 -> w13
    (256, 1536, 2048),        # ... -> w2
    (65536, 2048, 3072),      # its widest prefill wave: 16 x 1024 x 4
    (4096, 1536, 2048),
])
def test_grouped_matmul_compiles(one_chip, compiled_kernels, m, k, n):
    """ops/grouped_matmul.py's `moe_gmm` at the served widths: 64 experts,
    a 16-row tile for decode and a 256-row one for prefill."""
    from ray_tpu.ops.grouped_matmul import gmm

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    low, c = _compile(lambda x, w, g: gmm(x, w, g, impl="pallas"),
                      s((m, k)), s((64, k, n)), s((64,), jnp.int32))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "moe_gmm" in low.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 2 * 1024 ** 3


@pytest.mark.parametrize("b,s", [(16, 1024), (8, 128)])
def test_attention_pads_head_dim_64_into_the_flash_kernel(
        topo, one_chip, compiled_kernels, monkeypatch, b, s):
    """head_dim 64 (LFM2: 32 query over 8 kv heads of 64): attention()'s
    gate sends it to `flash_fwd` zero-padded to 128 lanes, not to XLA."""
    import importlib

    # ray_tpu.ops exports the function under the module's name
    attention = importlib.import_module("ray_tpu.ops.attention")
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    q = jax.ShapeDtypeStruct((b, s, 32, 64), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, 8, 64), jnp.bfloat16, sharding=one_chip)
    low, _ = _compile(lambda q, k, v: attention.attention(q, k, v), q, kv, kv)
    assert low.as_text().count("tpu_custom_call") == 1
    assert "flash_fwd" in low.as_text()
    assert (s, s, 64, True) not in attention.xla_fallbacks()


@pytest.mark.parametrize("op", ["merge_tail_pages", "gather_pages"])
def test_page_ops_compile_at_served_widths(one_chip, op):
    from ray_tpu.ops import paged_attention

    B, kvh, hd, page, kt, maxp = 8, 8, 128, 512, 8, 4

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = s((1 + B * maxp, kvh, page, hd))
    table = s((B, maxp), jnp.int32)
    if op == "merge_tail_pages":
        _, c = _compile(
            lambda p, t, tb, ts: paged_attention.merge_tail_pages(
                p, t, tb, ts, kt),
            pages, s((B, kvh, kt, hd)), table, s((B,), jnp.int32))
    else:
        _, c = _compile(paged_attention.gather_pages, pages, table)
    assert c.memory_analysis().temp_size_in_bytes < HBM_BYTES


def test_served_engine_fits_one_chip(topo, one_chip, compiled_kernels,
                                     monkeypatch):
    """chip_smoke.py's deployment (llama3-8b widths at the depth it
    chose): the decode program and the widest prefill wave compile for
    one chip, hold their kernels, and fit 16 GB beside each other's
    resident state (params + page pool are arguments of both)."""
    import chip_smoke

    # attention()'s auto gate asks jax.devices() for a TPU.
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg, eng_kw = chip_smoke.served_config("full")
    lows = chip_smoke.engine_lowerings(
        cfg, eng_kw, [(eng_kw["max_batch"], 512)], sharding=one_chip)
    assert sorted(lows) == ["decode_k8", "prefill_w8_p512"]
    for name, low in lows.items():
        assert low.as_text().count("tpu_custom_call") >= 1, name
        mem = low.compile().memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: args {mem.argument_size_in_bytes / 2**30:.2f} GiB "
              f"temps {mem.temp_size_in_bytes / 2**30:.2f} GiB "
              f"total {total / 2**30:.2f} GiB")
        # 2 GiB of headroom for what the process keeps besides this
        # program (the other program's outputs, staging buffers).
        assert total < HBM_BYTES - 2 * 1024 ** 3, (name, total)


def test_sharded_train_step_lowers_for_four_chips(topo, compiled_kernels,
                                                  monkeypatch):
    """`chip_smoke.py --chips 4`'s step (llama3-8b widths, fsdp=2 x
    tensor=2 over the described 2x2): GSPMD cannot partition a Mosaic
    kernel — jax refuses at LOWERING unless attention() makes the call
    per shard — so lowering alone guards it.  Forward + two backward
    kernels must be in the program."""
    import dataclasses

    import chip_smoke
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.train import step as train_step

    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    t = chip_smoke.TRAIN_SIZES["full"]
    cfg = dataclasses.replace(llama.llama_configs()[t["model"]],
                              n_layers=t["n_layers"], max_seq=t["seq"])
    mesh = create_mesh(MeshConfig(fsdp=2, tensor=2),
                       devices=list(topo.devices))
    opt = train_step.default_optimizer(total_steps=10)
    st_sh = train_step.state_shardings(cfg, mesh, opt)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(
            lambda k: train_step.create_train_state(k, cfg, opt),
            jax.random.PRNGKey(0)),
        st_sh)
    tok = jax.ShapeDtypeStruct((t["batch"], t["seq"]), jnp.int32,
                               sharding=train_step.batch_shardings(mesh))
    step = train_step.sharded_train_step(cfg, opt, mesh)
    with jax.set_mesh(mesh):
        low = step.lower(state, {"inputs": tok, "targets": tok})
    assert low.as_text().count("tpu_custom_call") == 3
