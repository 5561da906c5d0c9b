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
import functools
import math
import os
import re

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
    # and every pass on: what is read here is the compiler's own
    # memory analysis (tests/conftest.py turns most passes off)
    passes_off = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_disable_most_optimizations", passes_off)
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
    (32, 8, 4, 128, 512, 8, 4),       # Mistral-7B as the benchmark serves it
    (8, 8, 6, 128, 512, 8, 16),       # Codestral-22B: a group of 6, 16 columns
    (64, 8, 4, 64, 512, 8, 4),        # LFM2-24B-A2B: 64 lanes, head_dim 64
])
def test_paged_decode_attention_compiles(one_chip, compiled_kernels,
                                         B, kvh, rep, hd, page, kt, maxp):
    """The grid's bound is the plan's count, a value on the device: the
    chip's compiler takes a traced grid dimension."""
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


@pytest.mark.parametrize("G,m,k,n", [
    (64, 256, 2048, 3072),    # LFM2-24B-A2B decode: 64 lanes x 4 -> w13
    (64, 256, 1536, 2048),    # ... -> w2
    (64, 65536, 2048, 3072),  # its widest prefill wave: 16 x 1024 x 4
    (64, 4096, 1536, 2048),
    (32, 65536, 4096, 4096),  # sarvam-105b, 32 of 128 held: 1 x 8192 x 8
    (32, 65536, 2048, 4096),
    (32, 256, 4096, 4096),    # its decode: 32 lanes x 8
    (32, 256, 2048, 4096),
    (36, 512, 4096, 4096),    # GLM-5.3-Flash, 36 of 288 held: 64 lanes x 8
    (36, 512, 2048, 4096),
])
def test_grouped_matmul_compiles(one_chip, compiled_kernels, G, m, k, n):
    """ops/grouped_matmul.py's `moe_gmm` at the served widths, a 128-row
    tile for decode and a 256-row one for prefill: the visit axis of its
    grid is bounded by a count the device holds, beside the static
    column axis, and the chip's compiler takes it.  Through `gmm`
    itself: the column tile is `col_tile`'s (1,024 columns: 8 MiB a
    block of a `d` = 4096 model, past the compiler's default limit) and
    the VMEM asked for `vmem_bytes`', so a block Mosaic has no room for
    is refused here."""
    from ray_tpu.ops.grouped_matmul import gmm

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    low, c = _compile(lambda x, w, g: gmm(x, w, g, impl="pallas"),
                      s((m, k)), s((G, k, n)), s((G,), jnp.int32))
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


@pytest.mark.parametrize("B,maxp,n_pages", [(32, 4, 129), (8, 16, 161)],
                         ids=["mistral7b", "codestral22b"])
def test_merge_tail_pages_scatters_in_place(one_chip, B, maxp, n_pages):
    """The block's rows go into the DONATED pool where it lies: no copy
    of the pool beside it.  Indexed by (page, row) alone, XLA:TPU
    transposed the whole 135 MB pool, scattered, and transposed back:
    one pool of temporaries and 3.3 ms of a 15.7 ms decode step on the
    chip (PERF.md section 6, PR 29)."""
    from ray_tpu.ops import paged_attention

    kvh, hd, page, kt = 8, 128, 512, 8

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    c = jax.jit(
        lambda p, t, tb, ts: paged_attention.merge_tail_pages(
            p, t, tb, ts, kt), donate_argnums=0).lower(
        s((n_pages, kvh, page, hd)), s((B, kvh, kt, hd)),
        s((B, maxp), jnp.int32), s((B,), jnp.int32)).compile()
    pool_bytes = n_pages * kvh * page * hd * 2
    assert c.memory_analysis().temp_size_in_bytes < pool_bytes // 8
    assert _pool_copies(c.as_text(), pool_bytes // 2) == []


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


# ----------------------------- what the decode program's step loop holds
# Read from the compiled program's text: the static counter of PERF.md
# section 6, PR 29.  A weight is read once, by the matmul that uses it;
# an instruction of the K-step scan's body that WRITES something the
# size of a weight (a re-layout, a slice copied out of the stacked
# arrays, a page pool copied) is traffic the step does not need.
_ARRAY = re.compile(r"\b[a-z]+\d+\w*\[([0-9,]*)\]\{([0-9,]*)")
_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(")
# views, control flow and the second half of an asynchronous copy
_NO_WRITE = {"parameter", "get-tuple-element", "tuple", "bitcast",
             "constant", "while", "conditional", "call", "copy-done",
             "slice-done", "dynamic-slice-done", "async-done"}


def _arrays(shape: str) -> list:
    """(elements, minor-to-major layout) of each array in an
    instruction's output shape (a tuple has several)."""
    return [(math.prod(map(int, dims.split(","))), layout)
            for dims, layout in _ARRAY.findall(shape) if dims]


def _computations(hlo: str) -> dict:
    comps, name = {}, None
    for line in hlo.splitlines():
        if name is None:
            m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
            if m:
                name = m.group(1)
                comps[name] = []
        elif line.startswith("}"):
            name = None
        else:
            comps[name].append(line)
    return comps


def _holds_matmul(comps, name, seen) -> bool:
    if name in seen or name not in comps:
        return False
    seen.add(name)
    return any(re.search(r"\b(convolution|dot)\(", ln)
               or any(_holds_matmul(comps, c, seen)
                      for c in re.findall(r"calls=%([\w.\-]+)", ln))
               for ln in comps[name])


def _while_bodies(comps: dict, scope: str = "") -> list:
    """The bodies of the `while` instructions (under `scope`, if
    given)."""
    return [b for lines in comps.values() for ln in lines if scope in ln
            for b in re.findall(r"\bwhile\(.*body=%([\w.\-]+)", ln)]


def _loops_of(hlo: str, scope: str) -> list:
    """The `while` instructions of a compiled text under `scope` that
    are not a binary search's (`searchsorted` lowers to one: the visit
    list of every `gmm` call holds it, and held it before)."""
    return [ln for ln in hlo.splitlines()
            if re.search(r"\bwhile\(", ln) and scope in ln
            and "searchsorted" not in ln]


def _loop_lines(hlo: str, body: str | None = None) -> list:
    """Every instruction line of the `while` bodies (of the one named
    `body` alone, if given) and of whatever they call, fusions
    included."""
    comps = _computations(hlo)
    todo = _while_bodies(comps) if body is None else [body]
    seen, lines = set(), []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        lines += comps[name]
        todo += [c for ln in comps[name] for c in re.findall(
            r"(?:calls|to_apply|body|condition|true_computation"
            r"|false_computation)=%([\w.\-]+)", ln)]
    return lines


def weight_sized_writes(hlo: str, min_elems: int, scope: str = "",
                        shapes=None) -> list:
    """(instruction, op, scope) of every instruction in a `while` body
    (and what it calls) whose output has at least `min_elems` elements
    and is not a matmul fusion, a view, or an asynchronous prefetch that
    keeps the layout (a DMA of the stored bytes: the one read).  With
    `scope`, of the loops under that name alone; with `shapes` (a set of
    dimension tuples), of the outputs that are shaped like one of them
    alone (a loop over chunks of activations writes chunks larger than a
    small weight: a weight is known by its dimensions)."""
    comps = _computations(hlo)
    todo = _while_bodies(comps, scope)
    seen, found = set(), []
    while todo:
        body = todo.pop()
        if body in seen or body not in comps:
            continue
        seen.add(body)
        for ln in comps[body]:
            m = _INSTR.match(ln)
            if not m:
                continue
            name, out, op = m.groups()
            todo += re.findall(
                r"(?:to_apply|body|true_computation|false_computation)"
                r"=%([\w.\-]+)", ln)
            if op in _NO_WRITE or "ConcatBitcast" in ln:
                continue
            arrays = _arrays(out)
            if op.endswith("-start"):
                # ((operands), output, context): same layout = prefetch
                big = [a for a in arrays if a[0] >= min_elems]
                if len({layout for _, layout in big}) <= 1:
                    continue
            if not any(n >= min_elems for n, _ in arrays):
                continue
            if shapes is not None and not shapes & {
                    tuple(map(int, dims.split(",")))
                    for dims, _ in _ARRAY.findall(out) if dims}:
                continue
            if op == "fusion" and _holds_matmul(
                    comps, re.search(r"calls=%([\w.\-]+)", ln).group(1),
                    set()):
                continue
            scope = re.search(r'op_name="([^"]*)"', ln)
            found.append((name, op, scope.group(1) if scope else ""))
    return found


def scan_loop_writes(hlo: str, min_elems: int) -> list:
    """What the chunk loop of `ssd_scan` writes that has at least
    `min_elems` elements, as (name, op, dimensions, minor-to-major
    layout); a `dynamic-update-slice` is named so, fused or not."""
    lines = {m.group(1): m.group(2) for ln in hlo.splitlines()
             for m in [_INSTR.match(ln)] if m}
    out = []
    for name, op, scope in weight_sized_writes(hlo, min_elems):
        if "ssd_scan/while" not in scope:
            continue
        dims, layout = max(_ARRAY.findall(lines[name]),
                           key=lambda a: math.prod(map(int, a[0].split(","))))
        if "dynamic_update_slice" in scope.rsplit("/", 1)[-1]:
            op = "dynamic-update-slice"
        out.append((name, op, [int(d) for d in dims.split(",")],
                    [int(d) for d in layout.split(",")]))
    return out


def assert_scan_loop_stacks_whole_slabs(hlo: str, slab_elems: int,
                                        row_elems: int, chunks: int):
    """PR 49: the loop carries the state alone.  Of what it writes that is
    at least one chunk's states (`slab_elems` = b x N x H x P), nothing
    but an in-place `dynamic-update-slice` has a whole row's y of elements
    (`row_elems` = b x T x H x P), and every such update stacks on its
    MAJOR-most dimension, the chunk's index (`lax.scan` stacks on axis 0):
    a chunk's slab is contiguous.  Until PR 48 the loop stacked y itself
    with the chunk second-minor: 140 us a chunk where the bytes take 5."""
    assert "ssd_scan/while" in hlo            # the scope is there to read
    for name, op, dims, layout in scan_loop_writes(hlo, slab_elems):
        if op == "dynamic-update-slice":
            assert dims[0] == chunks and layout[-1] == 0, (name, dims, layout)
        else:
            assert math.prod(dims) < row_elems, (name, op, dims)


def test_scan_loop_writes_reads_a_program_text():
    """The parent's layout is found (f32[64,1,128,128,64]{2,0,1,4,3}: the
    chunk second-minor) and a stack of whole slabs passes."""
    def text(shape, layout):
        return f"""HloModule m
%body (p: (s32[], {shape})) -> (s32[], {shape}) {{
  %p = (s32[], {shape}{{{layout}}}) parameter(0)
  %dynamic_update_slice.15 = {shape}{{{layout}:T(8,128)}} dynamic-update-slice(%a, %b, %i), metadata={{op_name="jit(f)/ssd_scan/while/body/dynamic_update_slice"}}
}}
ENTRY %main () -> f32[] {{
  %w = (s32[], {shape}{{{layout}}}) while(%t), condition=%cond, body=%body
}}
"""
    bad = text("f32[64,1,128,128,64]", "2,0,1,4,3")
    assert scan_loop_writes(bad, 8192 * 8192) == [
        ("dynamic_update_slice.15", "dynamic-update-slice",
         [64, 1, 128, 128, 64], [2, 0, 1, 4, 3])]
    with pytest.raises(AssertionError):
        assert_scan_loop_stacks_whole_slabs(bad, 128 * 8192, 8192 * 8192, 64)
    assert_scan_loop_stacks_whole_slabs(
        text("bf16[64,1,8,16,64,128]", "5,4,3,2,1,0"), 128 * 8192,
        8192 * 8192, 64)


def _pool_copies(hlo: str, min_elems: int) -> list:
    """Names of the `copy` instructions of at least `min_elems` elements
    anywhere outside a fusion: a layout change of something resident."""
    found = []
    for name, lines in _computations(hlo).items():
        if "fused_computation" in name:
            continue
        for ln in lines:
            m = _INSTR.match(ln)
            if m and m.group(3) == "copy" and any(
                    n >= min_elems for n, _ in _arrays(m.group(2))):
                found.append(m.group(1))
    return found


def test_weight_sized_writes_reads_a_program_text():
    """The reader itself, on four instructions of a real program: the
    re-layout and the copy are found, the matmul fusion, the view and the
    layout-keeping prefetch are not."""
    hlo = """HloModule m
%fused_mm (p0: bf16[32,4096], p1: bf16[2,4096,4096]) -> bf16[32,4096] {
  %p0 = bf16[32,4096]{1,0} parameter(0)
  %p1 = bf16[2,4096,4096]{2,1,0} parameter(1)
  ROOT %convolution.1 = bf16[32,4096]{1,0} convolution(%p0, %p1)
}
%fused_slice (p0: bf16[2,4096,4096]) -> bf16[1,4096,4096] {
  %p0 = bf16[2,4096,4096]{1,2,0} parameter(0)
  ROOT %slice.1 = bf16[1,4096,4096]{1,2,0} slice(%p0), slice={[0:1]}
}
%body (arg: (s32[], bf16[2,4096,4096])) -> (s32[], bf16[2,4096,4096]) {
  %arg = (s32[], bf16[2,4096,4096]{2,1,0}) parameter(0)
  %w = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %relayout = bf16[1,4096,4096]{1,2,0:T(8,128)(2,1)} fusion(%w), kind=kLoop, calls=%fused_slice, metadata={op_name="jit(f)/while/body/layer_weights/slice"}
  %copy.1 = bf16[2,4096,4096]{1,2,0:T(8,128)(2,1)} copy(%w)
  %slice-start = ((bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)}), bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%w), slice={[0:1]}
  %mm = bf16[32,4096,128]{2,1,0} fusion(%w, %w), kind=kOutput, calls=%fused_mm
  ROOT %t = (s32[], bf16[2,4096,4096]{2,1,0}) tuple(%w)
}
ENTRY %main (p: bf16[2,4096,4096]) -> bf16[2,4096,4096] {
  %p = bf16[2,4096,4096]{2,1,0} parameter(0)
  %while.1 = (s32[], bf16[2,4096,4096]{2,1,0}) while(%p), condition=%cond, body=%body
}
"""
    found = weight_sized_writes(hlo, 4096 * 1024)
    assert [(n, op) for n, op, _ in found] == [("relayout", "fusion"),
                                                ("copy.1", "copy")]
    assert found[0][2].endswith("layer_weights/slice")


@pytest.mark.parametrize("widths,engine,lora_slots", [
    # Mistral-7B-v0.3 (benchmarks/configs/mistral-7b-v0.3-d16.json)
    (dict(dim=4096, n_heads=32, n_kv_heads=8, ffn_dim=14336),
     dict(max_batch=32, max_len=2048, kv_pages=129), 0),
    # Codestral-22B-v0.1: 48 query heads over 8, a GQA group of 6
    (dict(dim=6144, n_heads=48, n_kv_heads=8, ffn_dim=16384),
     dict(max_batch=8, max_len=8192, kv_pages=161), 0),
    # Mistral widths with adapter banks on all four projections
    (dict(dim=4096, n_heads=32, n_kv_heads=8, ffn_dim=14336),
     dict(max_batch=32, max_len=2048, kv_pages=129), 4),
], ids=["mistral7b", "codestral22b", "mistral7b-lora"])
def test_decode_step_loop_writes_nothing_the_size_of_a_weight(
        topo, one_chip, compiled_kernels, monkeypatch, widths, engine,
        lora_slots):
    """The engine's K-step paged decode program at the benchmark's widths
    (2 layers: the body is per layer): every weight byte is read once, by
    its matmul.  Written with the reshape into heads ON the q/k/v
    products, the step transposed wq, wk and wv of every layer
    (`{1,2,0}` copies under `layer_weights`, 2.1 ms of an 18.5 ms step
    on the chip; PERF.md section 6, PR 29)."""
    import chip_smoke
    from ray_tpu.models import llama

    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg = llama.LlamaConfig(vocab_size=32768, n_layers=2,
                            max_seq=engine["max_len"], **widths)
    eng_kw = dict(engine, paged=True, page_size=512, steps_per_sync=8,
                  lora_slots=lora_slots, lora_rank=16 if lora_slots else 0)
    hlo = chip_smoke.engine_lowerings(
        cfg, eng_kw, [], sharding=one_chip)["decode_k8"].compile().as_text()
    assert "while(" in hlo and "paged_attn" in hlo
    smallest_weight = cfg.dim * cfg.n_kv_heads * cfg.head_dim
    assert weight_sized_writes(hlo, smallest_weight) == []
    # ... and once a window, outside the loop, `merge_tail_pages` writes
    # the block's rows into the pools in place
    pool = engine["kv_pages"] * cfg.n_kv_heads * 512 * cfg.head_dim
    assert _pool_copies(hlo, pool) == []


@pytest.mark.parametrize("widths,engine,n_layers", [
    (dict(dim=4096, n_heads=32, n_kv_heads=8, ffn_dim=14336),
     dict(max_batch=32, max_len=2048, kv_pages=129), 16),
    (dict(dim=6144, n_heads=48, n_kv_heads=8, ffn_dim=16384),
     dict(max_batch=8, max_len=8192, kv_pages=161), 8),
], ids=["mistral7b", "codestral22b"])
def test_demotion_page_gather_reads_one_page_a_layer(topo, one_chip, widths,
                                                     engine, n_layers):
    """The ONE program the prefix store's demotion runs (a call a page,
    PERF.md section 6, PR 33), at the benchmark's widths and depths: it
    writes 2·L arrays of [kvh, page, hd] and nothing else; no page pool
    is copied, re-laid-out or even matched in size by anything it
    produces.  An engine nobody installed a callback on has none."""
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    cfg = llama.LlamaConfig(vocab_size=32768, n_layers=n_layers,
                            max_seq=engine["max_len"], **widths)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0),
                                                 cfg)))
    eng = LLMEngine(cfg, params, paged=True, page_size=512,
                    steps_per_sync=8, **engine)
    assert eng._gather_page is None
    c = eng._lower_page_gather(sharding=one_chip).compile()
    piece = (cfg.n_kv_heads, 512, cfg.head_dim)
    outs = jax.tree.leaves(c.out_info)
    assert [o.shape for o in outs] == [piece] * (2 * n_layers)
    piece_bytes = math.prod(piece) * 2
    pool = engine["kv_pages"] * math.prod(piece)
    mem = c.memory_analysis()
    assert mem.output_size_in_bytes <= 2 * n_layers * (piece_bytes + 1024)
    assert mem.temp_size_in_bytes <= 2 * n_layers * piece_bytes
    hlo = c.as_text()
    assert _pool_copies(hlo, pool // 2) == []
    big = [(m.group(1), m.group(3))
           for lines in _computations(hlo).values() for ln in lines
           for m in [_INSTR.match(ln)]
           if m and m.group(3) not in _NO_WRITE
           and any(n >= pool // 2 for n, _ in _arrays(m.group(2)))]
    assert big == []


def test_decode_step_loop_reads_the_attention_plan_and_builds_none(
        topo, one_chip, compiled_kernels, monkeypatch):
    """Mistral-7B-d16 as the benchmark serves it: the window's work list
    (`attention_plan`, under the scope `attn_plan`) is built before the
    K-step loop and nowhere in it, and the loop's body still holds one
    `paged_attn` kernel a layer, each bounded by the plan's count (its
    first operand, a scalar)."""
    import chip_smoke
    from ray_tpu.models import llama

    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg = llama.LlamaConfig(vocab_size=32768, n_layers=16, max_seq=2048,
                            dim=4096, n_heads=32, n_kv_heads=8,
                            ffn_dim=14336)
    eng_kw = dict(max_batch=32, max_len=2048, kv_pages=129, paged=True,
                  page_size=512, steps_per_sync=8)
    hlo = chip_smoke.engine_lowerings(
        cfg, eng_kw, [], sharding=one_chip)["decode_k8"].compile().as_text()
    assert hlo.count("attn_plan") > 0
    loop = _loop_lines(hlo)
    assert [ln for ln in loop if "attn_plan" in ln] == []
    calls = [ln for ln in loop if "custom-call(" in ln and "paged_attn" in ln]
    assert len(calls) == cfg.n_layers
    operands = {re.search(r"custom-call\(%([\w.\-]+)", ln).group(1)
                for ln in calls}
    count, = operands                   # one count for all sixteen
    bound = next(ln for ln in loop
                 if re.match(rf"\s*%{re.escape(count)} = ", ln))
    assert re.match(r"\s*%[\w.\-]+ = s32\[\]", bound), bound

# ------------------------------------ the latent-attention model (PR 34)
def _sarvam(n_layers=None):
    """benchmarks/configs/sarvam-105b-ep4.json as the benchmark builds
    it: (program config, engine keywords, the family)."""
    from benchmarks.harness import spec

    cfg = spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "sarvam-105b-ep4.json"))
    if n_layers is not None:
        cfg["num_hidden_layers"] = n_layers
    fam = spec.config_family(cfg)
    eng_kw = dict(cfg["engine"], paged=True)
    return (fam.program_config(fam.published(cfg),
                               max_seq=eng_kw["max_len"]), eng_kw, fam)


def _sarvam_lowerings(one_chip, n_layers, shapes):
    from ray_tpu.models import mla_moe
    from ray_tpu.serve.llm import LLMEngine

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def abstract(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    cfg, eng_kw, _ = _sarvam(n_layers)
    params = abstract(jax.eval_shape(
        lambda: mla_moe.init_params(jax.random.PRNGKey(0), cfg)))
    eng = LLMEngine(cfg, params, **eng_kw)
    i32, f32 = jnp.int32, jnp.float32
    b, k = eng.max_batch, eng.steps_per_sync
    out = {f"decode_k{k}": eng._decode_fns[k].lower(
        params, abstract(eng.cache), sds((b,), i32), sds((b,), f32),
        sds((b, eng._maxp), i32), sds((b,), i32), sds((b,), i32), None)}
    for w, p in shapes:
        out[f"prefill_w{w}_p{p}"] = eng._prefill_fwd.lower(
            params, sds((w, p), i32), sds((w,), i32), sds((w,), i32),
            sds((w,), f32), sds((w,), i32), sds((w,), i32), None)
    return cfg, eng, out


def test_mla_attn_compiles_and_copies_no_pool(one_chip, compiled_kernels):
    """`mla_attn` at sarvam-105b's served widths: 32 lanes, 64 heads over
    ONE 640-wide row a token (576 used), pages of 512, 18 table columns.
    The pool is read where it lies: a leaf declared 576 wide was laid
    out page-minor and copied whole (340 MB) before the call."""
    from ray_tpu.ops.paged_attention import mla_decode_attention

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, H, dk, dv, page, kt, maxp, n_pages = 32, 64, 640, 512, 512, 8, 18, 577
    low, c = _compile(
        lambda q, rp, rt, t, p, ts: mla_decode_attention(
            q, rp, rt, t, p, ts, dv=dv, sm_scale=0.135),
        s((B, H, dk)), s((n_pages, 1, page, dk)), s((B, 1, kt, dk)),
        s((B, maxp), jnp.int32), s((B,), jnp.int32), s((B,), jnp.int32))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "mla_attn" in low.as_text()
    pool = n_pages * page * dk
    assert c.memory_analysis().temp_size_in_bytes < 16 * 1024 ** 2
    assert _pool_copies(c.as_text(), pool // 2) == []


def test_flash_forward_compiles_at_keys_wider_than_values(one_chip,
                                                          compiled_kernels):
    """The expanded path's prefill attention: 64 heads, q/k 192 wide, v
    and the output 128, one row of 8,192 positions, under the name the
    rooflines read (`flash_fwd`)."""
    from ray_tpu.ops.flash_attention import flash_attention

    def s(d):
        return jax.ShapeDtypeStruct((1, 8192, 64, d), jnp.bfloat16,
                                    sharding=one_chip)

    low, c = _compile(lambda q, k, v: flash_attention(q, k, v, sm_scale=0.1),
                      s(192), s(192), s(128))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "flash_fwd" in low.as_text()
    assert jax.tree.leaves(c.out_info)[0].shape == (1, 8192, 64, 128)


@pytest.mark.parametrize("b,s,h,kvh,d,dv", [
    (1, 8192, 64, 64, 192, 128),      # sarvam-105b's expanded prefill
    (8, 1024, 32, 8, 128, 128),       # Mistral-7B's widest wave
])
def test_flash_forward_with_lengths_compiles(one_chip, compiled_kernels,
                                             b, s, h, kvh, d, dv):
    """`flash_fwd` over right-padded rows: the walk's tables and the
    lengths' count ride as scalar prefetch, the minor grid axis is bounded
    by a number the device holds, and a forward-only call has no
    log-sum-exp among its results (f32 [b, h, s, 128]: 268 MB a layer at
    sarvam's widths)."""
    from ray_tpu.ops.flash_attention import flash_attention

    def sds(heads, width):
        return jax.ShapeDtypeStruct((b, s, heads, width), jnp.bfloat16,
                                    sharding=one_chip)

    low, c = _compile(
        lambda q, k, v, n: flash_attention(q, k, v, sm_scale=0.1, lengths=n),
        sds(h, d), sds(kvh, d), sds(kvh, dv),
        jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "flash_fwd" in low.as_text()
    assert f"f32[{b},{h},{s},128]" not in c.as_text()
    assert jax.tree.leaves(c.out_info)[0].shape == (b, s, h, dv)


def test_flash_train_shard_keeps_lse_and_its_backward(one_chip,
                                                      compiled_kernels):
    """The train cell's per-shard call (2 rows x 16 heads x 4,096, no
    lengths) through `jax.grad`: the vjp's forward is `flash_fwd` WITH
    the log-sum-exp, beside the two backward kernels that read it."""
    from ray_tpu.ops.flash_attention import flash_attention

    def sds(heads):
        return jax.ShapeDtypeStruct((2, 4096, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    low, c = _compile(
        lambda q, k, v: jax.grad(
            lambda *a: flash_attention(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v), sds(16), sds(4), sds(4))
    txt = low.as_text()
    assert txt.count("tpu_custom_call") == 3
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in txt
    assert "f32[2,16,4096,128]" in c.as_text()


@pytest.mark.time_limit(600)
def test_served_sarvam_engine_fits_one_chip(topo, one_chip, compiled_kernels,
                                            monkeypatch):
    """sarvam-105b-ep4 as the benchmark serves it (6 layers, 32 of 128
    experts, a quarter of the vocabulary, 32 lanes over 577 latent
    pages): the decode program and the ONE prefill program its traffic
    reaches (1 x 8192: the planner's ceiling forms no wider one) compile
    for one chip and leave 0.8 GB beside weights + pool."""
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg, eng, lows = _sarvam_lowerings(one_chip, None, [(1, 8192)])
    st = eng._cache_stats()
    pool = 577 * 512 * 1280 * 6
    assert st == {"kind": "latent", "row_bytes": 1280, "layers": 6,
                  "pool_bytes": pool, "by_leaf": {"latent": {
                      "row_bytes": 1280, "positions_per_row": 1,
                      "layers": 6, "pool_bytes": pool}}}
    resident = st["pool_bytes"] + 2 * sum(
        math.prod(a.shape) for a in jax.tree.leaves(eng.params))
    assert resident > 0.75 * HBM_BYTES       # a deployment's fill
    kernels = {"decode_k8": ("mla_attn", "moe_gmm"),
               "prefill_w1_p8192": ("flash_fwd", "moe_gmm")}
    for name, low in lows.items():
        txt = low.as_text()
        for kern in kernels[name]:
            assert kern in txt, (name, kern)
        mem = low.compile().memory_analysis()
        held = resident + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: temps {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"resident {resident / 1e9:.2f} GB")
        assert held < 16.9e9 - 0.8e9, (name, held)


def test_sarvam_decode_step_loop_writes_no_weight_and_builds_no_plan(
        topo, one_chip, compiled_kernels, monkeypatch):
    """The absorbed decode program at the served widths (the dense layer
    and one routed layer: the body is per layer): W_UK and W_UV are held
    as the step reads them, so nothing the size of a weight is written
    inside the K-step loop (the smallest matrix a step multiplies is
    W_kva, 4096 x 576); the latent pool is merged in place once a
    window; the loop holds one `mla_attn` a layer, bounded by the plan
    built before it."""
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg, eng, lows = _sarvam_lowerings(one_chip, 2, [])
    hlo = lows["decode_k8"].compile().as_text()
    assert "while(" in hlo and "mla_attn" in hlo and "moe_gmm" in hlo
    assert _loops_of(hlo, "moe_experts") == []      # one block: no loop
    assert weight_sized_writes(hlo, cfg.dim * cfg.row_used) == []
    assert _pool_copies(hlo, eng.n_pages * 512 * cfg.row_width) == []
    loop = _loop_lines(hlo)
    assert hlo.count("attn_plan") > 0
    # the plan's arrays are carried into the loop (views of the loop's
    # argument); no instruction of the loop computes one
    built = [m.group(1) for ln in loop if "attn_plan" in ln
             for m in [_INSTR.match(ln)] if m and m.group(3) not in _NO_WRITE]
    assert built == []
    calls = [ln for ln in loop if "custom-call(" in ln and "mla_attn" in ln]
    assert len(calls) == cfg.n_layers
    assert len({re.search(r"custom-call\(%([\w.\-]+)", ln).group(1)
                for ln in calls}) == 1


def _lowered_train_step(topo, cfg, opt, mesh_axes: dict, batch: int,
                        seq: int):
    """`train/step.py`'s sharded step over the described chips, lowered
    from shapes (no array exists without a chip)."""
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.train import step as train_step

    mesh = create_mesh(MeshConfig(**mesh_axes), devices=list(topo.devices))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(
            lambda k: train_step.create_train_state(k, cfg, opt),
            jax.random.PRNGKey(0)),
        train_step.state_shardings(cfg, mesh, opt))
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                               sharding=train_step.batch_shardings(mesh))
    step = train_step.sharded_train_step(cfg, opt, mesh)
    with jax.set_mesh(mesh):
        return step.lower(state, {"inputs": tok, "targets": tok})


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
    from ray_tpu.train import step as train_step

    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    t = chip_smoke.TRAIN_SIZES["full"]
    cfg = dataclasses.replace(llama.llama_configs()[t["model"]],
                              n_layers=t["n_layers"], max_seq=t["seq"])
    low = _lowered_train_step(
        topo, cfg, train_step.default_optimizer(total_steps=10),
        dict(fsdp=2, tensor=2), t["batch"], t["seq"])
    assert low.as_text().count("tpu_custom_call") == 3


def test_train_cell_step_does_not_recompute_the_attention_output(
        topo, compiled_kernels, monkeypatch):
    """`mistral7b.train.fsdp2tp2`'s own step (the configuration's file
    through its family, fsdp=2 x tensor=2 over the described 2x2),
    COMPILED: under `remat_mode="flash_resid"` the scanned layer's
    backward finds o, lse and the block's output saved, so the two loop
    bodies hold 7 products (forward) and 19 (14 of the backward + the
    recomputed q, k, v, gate and up; 20 while `o @ wo` was rebuilt),
    one and two kernel calls, and the program fits the chip beside its
    state."""
    from benchmarks.harness import spec
    from ray_tpu.train import step as train_step

    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    conf = spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "mistral-7b-v0.3-d20-train4.json"))
    t, fam = conf["train"], spec.config_family(conf)
    assert t["remat_mode"] == "flash_resid"
    cfg = fam.program_config(fam.published(conf), max_seq=t["seq"],
                             remat_mode=t["remat_mode"])
    opt = getattr(train_step, t["optimizer"])(total_steps=t["total_steps"])
    low = _lowered_train_step(topo, cfg, opt, t["mesh"], t["batch"],
                              t["seq"])
    assert low.as_text().count("tpu_custom_call") == 3
    comp = low.compile()
    hlo = comp.as_text()

    def count(lines, what):
        return sum(1 for ln in lines if re.search(what, ln))

    bodies = sorted(
        (count(lines, r"\b(convolution|dot)\("),
         count(lines, r"custom-call\(.*tpu_custom_call"))
        for lines in (_loop_lines(hlo, b)
                      for b in set(_while_bodies(_computations(hlo)))))
    assert bodies == [(7, 1), (19, 2)]
    mem = comp.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"train_step: arguments {mem.argument_size_in_bytes / 1e9:.2f} "
          f"GB, temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB")
    assert held < 16.9e9, held


# ------------------------------- the state-space hybrid model (PR 39)
def _granite_lowerings(one_chip, shapes, layer_types=None):
    """benchmarks/configs/granite-4.0-h-micro.json as the benchmark
    builds it (or with other `layer_types`: the bodies are per run)."""
    from benchmarks.harness import spec
    from ray_tpu.serve.llm import LLMEngine

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def abstract(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    conf = spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "granite-4.0-h-micro.json"))
    if layer_types is not None:
        conf.update(layer_types=layer_types,
                    num_hidden_layers=len(layer_types))
    fam = spec.config_family(conf)
    eng_kw = dict(conf["engine"], paged=True)
    cfg = fam.program_config(fam.published(conf), max_seq=eng_kw["max_len"])
    params = abstract(jax.eval_shape(
        lambda: fam.init_params(jax.random.PRNGKey(0), cfg)))
    eng = LLMEngine(cfg, params, **eng_kw)
    i32, f32 = jnp.int32, jnp.float32
    b, k = eng.max_batch, eng.steps_per_sync
    out = {f"decode_k{k}": eng._decode_fns[k].lower(
        params, abstract(eng.cache), sds((b,), i32), sds((b,), f32),
        sds((b, eng._maxp), i32), sds((b,), i32), sds((b,), i32), None)}
    for w, p in shapes:
        out[f"prefill_w{w}_p{p}"] = eng._prefill_fwd.lower(
            params, sds((w, p), i32), sds((w,), i32), sds((w,), i32),
            sds((w,), f32), sds((w,), i32), sds((w,), i32), None)
    return cfg, eng, out


@pytest.mark.parametrize("lanes,layers,HP,G", [
    (64, 36, 4096, 1), (64, 9, 4096, 1), (8, 36, 4096, 1),
    (64, 5, 8192, 8)])
def test_ssm_update_compiles_at_the_served_widths(one_chip, compiled_kernels,
                                                  lanes, layers, HP, G):
    """granite-4.0-h-micro's lanes: 36 layers x [128, 4096] float32 a
    lane in one group, one 2 MB block a grid step; the nemotron_h cut's:
    5 layers x [128, 8192] in eight groups, a 0.5 MB block of one group's
    1,024 columns a step (the lane's whole 4 MB, in and out and
    double-buffered, is over the scoped VMEM): read and written through
    the alias; nothing the size of the state is a temporary."""
    from ray_tpu.ops import ssm

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L, N = layers, 128
    low = jax.jit(ssm.ssm_update, donate_argnums=(0,)).lower(
        s((L, lanes, N, HP), jnp.float32), s((), jnp.int32),
        s((lanes,), jnp.int32), s((), jnp.int32), s((lanes, HP)),
        s((lanes, HP), jnp.float32), s((lanes, G, N)), s((lanes, G, N)),
        s((HP,), jnp.float32), s((HP,), jnp.float32))
    assert low.as_text().count("tpu_custom_call") == 1
    c = low.compile()
    mem = c.memory_analysis()
    state_bytes = L * lanes * N * HP * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 64
    assert _pool_copies(c.as_text(), lanes * N * HP) == []


@pytest.mark.time_limit(600)
def test_served_granite_engine_fits_one_chip(topo, one_chip, compiled_kernels,
                                             monkeypatch):
    """granite-4.0-h-micro as the benchmark serves it, WHOLE (40 layers,
    64 lanes, 257 pages): the decode program and the widest prefill
    program its traffic reaches (8 x 1024: the planner's state ceiling
    keeps 16 rows apart) compile for one chip and
    leave 1.5 GB beside weights + lane state + pool."""
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg, eng, lows = _granite_lowerings(one_chip, [(8, 1024)])
    lane = eng.stats()["lane_state"]
    assert lane["by_kind"] == {"conv": 36 * 64 * 3 * 4352 * 2,
                               "ssm": 36 * 64 * 128 * 4096 * 4}
    cache = eng._cache_stats()
    leaf = {"row_bytes": 8 * 64 * 2, "positions_per_row": 1, "layers": 4,
            "pool_bytes": 257 * 512 * 2048 * 2}
    assert cache == {"kind": "kv", "row_bytes": 2 * 8 * 64 * 2,
                     "layers": 4, "pool_bytes": 257 * 512 * 2048 * 4,
                     "by_leaf": {"k": leaf, "v": leaf}}
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(eng.params))
    resident = weights + lane["bytes"] + eng._cache_stats()["pool_bytes"]
    assert 12.3e9 < resident < 12.4e9        # 73 % of the chip
    kernels = {"decode_k8": ("ssm_update", "paged_attn"),
               "prefill_w8_p1024": ("flash_fwd",)}
    for name, low in lows.items():
        txt = low.as_text()
        for kern in kernels[name]:
            assert kern in txt, (name, kern)
        c = low.compile()
        mem = c.memory_analysis()
        held = resident + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: temps {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"resident {resident / 1e9:.2f} GB, held {held / 1e9:.2f} GB")
        assert held < 16.9e9 - 1.5e9, (name, held)
        if name == "prefill_w8_p1024":
            # four chunks of 256 a row (0.67 GB of temporaries to PR 48)
            assert_scan_loop_stacks_whole_slabs(
                c.as_text(), 8 * cfg.ssm_state * cfg.inner,
                8 * 1024 * cfg.inner, 4)


def test_granite_decode_step_loop_copies_no_lane_state_and_no_weight(
        topo, one_chip, compiled_kernels, monkeypatch):
    """The decode program at the served widths, one period of the
    published pattern (the bodies are per run): inside the K-step loop
    the lanes' state matrices (4.8 GB at 36 layers; 1.2 GB here) are
    touched by `ssm_update` alone, which aliases them: no copy, select,
    broadcast or scatter the size of ONE LAYER's lanes (64 x 128 x 4096)
    exists in the compiled program, inside the loop or outside it; no
    weight is re-laid-out in the loop; and the state is donated through
    the program."""
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    cfg, eng, lows = _granite_lowerings(one_chip, [], period)
    c = lows["decode_k8"].compile()
    hlo = c.as_text()
    assert "while(" in hlo and "ssm_update" in hlo and "paged_attn" in hlo
    layer_state = 64 * 128 * 4096
    found = weight_sized_writes(hlo, layer_state)
    assert found and all(op == "custom-call" and "ssm_update" in scope
                         for _, op, scope in found), found
    # (the bfloat16 page pools of head_dim 64 are copied to the layout
    # the kernels read, once a window: PERF.md section 7; the state is
    # the float32 array)
    lines = {m.group(1): ln for ln in hlo.splitlines()
             for m in [_INSTR.match(ln)] if m}
    assert [n for n in _pool_copies(hlo, layer_state)
            if " f32[" in lines[n].split("copy(")[0]] == []
    # the smallest matrix a step multiplies is wk / wv, 2048 x 512; what
    # the loop does write of that size are the convolution rows (64 x 3
    # x 4352 a layer, shifted and stacked by run), under no matmul's name
    small = [f for f in weight_sized_writes(hlo, cfg.dim * 512)
             if "ssm_update" not in f[2]]
    assert [f for f in small if any(scope in f[2] for scope in (
        "ssm_in_proj", "ssm_out", "mlp", "attn_qkv", "attn_out",
        "lm_head"))] == []
    # the whole state is an argument the result aliases
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= 9 * layer_state * 4
    loop = _loop_lines(hlo)
    calls = [ln for ln in loop if "custom-call(" in ln and "ssm_update" in ln]
    assert len(calls) == 2                  # a body a Mamba run


# ------------------------------------- Nemotron-3-Super, one period (PR 48)
def _nemotron_lowerings(one_chip, shapes):
    """benchmarks/configs/nemotron-3-super-120b-a12b-ep4.json as the
    benchmark builds it."""
    from benchmarks.harness import spec
    from ray_tpu.serve.llm import LLMEngine

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def abstract(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    conf = spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "nemotron-3-super-120b-a12b-ep4.json"))
    fam = spec.config_family(conf)
    eng_kw = dict(conf["engine"], paged=True)
    cfg = fam.program_config(fam.published(conf), max_seq=eng_kw["max_len"])
    params = abstract(jax.eval_shape(
        lambda: fam.init_params(jax.random.PRNGKey(0), cfg)))
    eng = LLMEngine(cfg, params, **eng_kw)
    i32, f32 = jnp.int32, jnp.float32
    b, k = eng.max_batch, eng.steps_per_sync
    out = {f"decode_k{k}": eng._decode_fns[k].lower(
        params, abstract(eng.cache), sds((b,), i32), sds((b,), f32),
        sds((b, eng._maxp), i32), sds((b,), i32), sds((b,), i32), None)}
    for w, p in shapes:
        out[f"prefill_w{w}_p{p}"] = eng._prefill_fwd.lower(
            params, sds((w, p), i32), sds((w,), i32), sds((w,), i32),
            sds((w,), f32), sds((w,), i32), sds((w,), i32), None)
    return cfg, eng, out


@pytest.mark.time_limit(900)
def test_served_nemotron_engine_fits_one_chip_and_copies_no_lane_state(
        topo, one_chip, compiled_kernels, monkeypatch):
    """nemotron-3-super-120b-a12b-ep4 as the benchmark serves it (one
    period MEMEMEM*EME, 128 of 512 experts held, 64 lanes, 1,153 pages):
    the decode program and the ONE prefill program its traffic reaches
    (1 x 8192) compile for one chip beside 11.3 GB of weights, lane
    state and pool; inside the K-step loop the lanes' state (1.34 GB) is
    touched by `ssm_update` alone, which aliases it."""
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg, eng, lows = _nemotron_lowerings(one_chip, [(1, 8192)])
    lane = eng.stats()["lane_state"]
    assert lane["by_kind"] == {"conv": 5 * 64 * 3 * 10240 * 2,
                               "ssm": 5 * 64 * 128 * 8192 * 4}
    cache = eng._cache_stats()
    assert (cache["kind"], cache["layers"], cache["row_bytes"]) == (
        "kv", 1, 2 * 2 * 128 * 2)
    streamed, multiplied = eng._spec.prefill_params
    assert eng._prefill_floor == 256 * streamed // multiplied == 1112
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(eng.params))
    # bfloat16 but for dt_bias, A_log, D and the router's biases (float32)
    assert weights == 2 * 4_648_163_712 + 2 * (5 * 3 * 128 + 5 * 512)
    resident = weights + lane["bytes"] + cache["pool_bytes"]
    assert 11.2e9 < resident < 11.4e9        # 67 % of the chip
    kernels = {"decode_k8": ("ssm_update", "moe_gmm", "paged_attn"),
               "prefill_w1_p8192": ("flash_fwd", "moe_gmm")}
    for name, low in lows.items():
        txt = low.as_text()
        for kern in kernels[name]:
            assert kern in txt, (name, kern)
        c = low.compile()
        mem = c.memory_analysis()
        held = resident + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: temps {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"resident {resident / 1e9:.2f} GB, held {held / 1e9:.2f} GB")
        assert held < 16.9e9 - 1.0e9, (name, held)
        hlo = c.as_text()
        if name != "decode_k8":
            # 64 chunks of 128 (2.42 GB of temporaries to PR 48, of which
            # the stacked y and its re-layout)
            assert_scan_loop_stacks_whole_slabs(
                hlo, cfg.ssm_state * cfg.inner, 8192 * cfg.inner, 64)
            assert mem.temp_size_in_bytes < 2.0e9
            continue
        layer_state = 64 * 128 * 8192
        found = weight_sized_writes(hlo, layer_state)
        assert found and all(op == "custom-call" and "ssm_update" in scope
                             for _, op, scope in found), found
        assert mem.alias_size_in_bytes >= 5 * layer_state * 4
        loop = _loop_lines(hlo)
        assert len([ln for ln in loop if "custom-call(" in ln
                    and "ssm_update" in ln]) == 5
        assert len([ln for ln in loop if "custom-call(" in ln
                    and "moe_gmm" in ln]) == 10


# ------------------------------------------------- GLM-5.3-Flash (PR 41)
def _glm_lowerings(one_chip, shapes, config="glm-5.3-flash-ep8"):
    """benchmarks/configs/<config>.json as the benchmark builds it."""
    from benchmarks.harness import spec
    from ray_tpu.serve.llm import LLMEngine

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def abstract(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    conf = spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", config + ".json"))
    fam = spec.config_family(conf)
    eng_kw = dict(conf["engine"], paged=True)
    cfg = fam.program_config(fam.published(conf), max_seq=eng_kw["max_len"])
    params = abstract(jax.eval_shape(
        lambda: fam.init_params(jax.random.PRNGKey(0), cfg)))
    eng = LLMEngine(cfg, params, **eng_kw)
    i32, f32 = jnp.int32, jnp.float32
    b, k = eng.max_batch, eng.steps_per_sync
    out = {f"decode_k{k}": eng._decode_fns[k].lower(
        params, abstract(eng.cache), sds((b,), i32), sds((b,), f32),
        sds((b, eng._maxp), i32), sds((b,), i32), sds((b,), i32), None)}
    for w, p in shapes:
        out[f"prefill_w{w}_p{p}"] = eng._prefill_fwd.lower(
            params, sds((w, p), i32), sds((w,), i32), sds((w,), i32),
            sds((w,), f32), sds((w,), i32), sds((w,), i32), None)
    return cfg, eng, out


@pytest.mark.parametrize("lanes,layers,dtype", [
    (64, 4, jnp.float32), (8, 34, jnp.float32), (64, 4, jnp.bfloat16)])
def test_kda_update_compiles_at_the_served_widths(one_chip, compiled_kernels,
                                                  lanes, layers, dtype):
    """GLM-5.3-Flash's lanes: 64 heads x [128, 128] float32 a lane a
    layer, one 4 MB block a grid step, read and written through the alias
    (a head's vectors cut out of [dk, H] as static columns); nothing the
    size of the state is a temporary."""
    from ray_tpu.ops import kda

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    H, dk = 64, 128
    low = jax.jit(kda.kda_update, donate_argnums=(0,)).lower(
        s((layers, lanes, H, dk, dk), dtype), s((), jnp.int32),
        s((lanes,), jnp.int32), s((), jnp.int32), s((lanes, H, dk)),
        s((lanes, H, dk)), s((lanes, H, dk)), s((lanes, H, dk)),
        s((lanes, H)))
    assert low.as_text().count("tpu_custom_call") == 1
    c = low.compile()
    mem = c.memory_analysis()
    state_bytes = layers * lanes * H * dk * dk * jnp.dtype(dtype).itemsize
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 64
    assert _pool_copies(c.as_text(), lanes * H * dk * dk) == []


@pytest.mark.parametrize("b,T,with_lengths", [
    (1, 8192, True), (1, 2750, False), (2, 1024, True)])
def test_kda_scan_compiles_at_the_served_widths(one_chip, compiled_kernels,
                                                b, T, with_lengths):
    """GLM-5.3-Flash's prefill scan: 64 heads of [128, 128], chunks of 32,
    ONE kernel whose operands are the [b, T, H dk] views the model's
    producers write (from jit arguments in [b, T, H, dk] the view is a
    copy each, which the program never pays); the judge's call is 2,750
    positions without lengths."""
    from ray_tpu.ops import kda

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    H, dk = 64, 128

    def scan(q, k, v, g, beta, lens):
        shape = (b, T, H, dk)
        o, S = kda.kda_scan(q.reshape(shape), k.reshape(shape),
                            v.reshape(shape), g.reshape(shape), beta, 32,
                            lens if with_lengths else None)
        return o.reshape(b, T, H * dk), S

    x = s((b, T, H * dk))
    low, c = _compile(scan, x, x, x, x, s((b, T, H)), s((b,), jnp.int32))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "kda_scan" in low.as_text()
    # nothing the size of an operand beside them but the padding of a
    # length that is no multiple of the position block
    row = b * T * H * dk * 4
    assert c.memory_analysis().temp_size_in_bytes < (
        row // 8 if T % kda.POSITIONS == 0 else 6 * row)


@pytest.mark.parametrize("b,T", [(1, 8192), (1, 2816), (2, 1024)])
def test_kda_conv_compiles_at_the_served_widths(one_chip, compiled_kernels,
                                                b, T):
    """What precedes the scan in GLM-5.3-Flash's and Solar-Open2's KDA
    layers: the bfloat16 projection [b, T, 3 x 64 x 128] under a
    convolution of 4 positions, ONE kernel that writes q, k, v in the
    [b, T, H dk] view `kda_scan` reads and nothing the size of either
    beside them (the judges' row of 2,816 positions is no multiple of the
    position block: the projection is padded and the outputs are cut)."""
    from ray_tpu.ops import kda

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    H, dk, K = 64, 128, 4
    low, c = _compile(
        lambda x, w, n: [a.reshape(b, T, H * dk)
                         for a in kda.kda_conv(x, w, H, n)],
        s((b, T, 3 * H * dk)), s((K, 3 * H * dk)), s((b,), jnp.int32))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "kda_conv" in low.as_text()
    proj = b * T * 3 * H * dk * 2
    assert c.memory_analysis().temp_size_in_bytes < (
        proj // 8 if T % kda.CONV_POSITIONS == 0 else 4 * proj)


def test_dsa_attn_compiles_and_copies_no_pool(one_chip, compiled_kernels):
    """The sparse step's gather and kernel at the served widths: 513
    groups of 4 rows a lane gathered out of the latent pool into 2,176
    rows (a row at a time: the pool is never copied or re-laid-out),
    attended with the block's 8 tail rows by 64 heads in one grid step."""
    from ray_tpu.ops import sparse_attention as dsa

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    B, H, w, page, maxp, n_pages = 64, 64, 512, 512, 18, 1153

    def step(q, pages, tail, table, pos, ts, groups, ok, lanes, count):
        rows, bias, tail_bias, _, _ = dsa.select_rows(
            pages, tail, table, pos, ts, groups, ok, 4)
        return dsa.dsa_decode_attention(q, rows, bias, tail[:, 0], tail_bias,
                                        lanes, count, dv=w, sm_scale=0.0625)

    i32 = jnp.int32
    low, c = _compile(
        step, s((B, H, w)), s((n_pages, 1, page, w)), s((B, 1, 8, w)),
        s((B, maxp), i32), s((B,), i32), s((B,), i32), s((B, 512), i32),
        s((B, 512), jnp.bool_), s((B,), i32), s((), i32))
    assert low.as_text().count("tpu_custom_call") == 1
    hlo = c.as_text()
    assert _pool_copies(hlo, n_pages * page * w // 2) == []
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * B * 2176 * w * 2 + (64 << 20)


@pytest.mark.parametrize("H,dk,dv,group", [
    (128, 640, 512, 1),     # dots3-note-prev: a key a token, rotary rows
    (64, 512, 512, 4),      # glm-5.3-flash: groups of 4
])
def test_dsa_walk_compiles_and_gathers_no_row(one_chip, compiled_kernels,
                                              H, dk, dv, group):
    """The sparse step's other form at both cells' shapes (64 lanes, 18
    columns of 512: 4.2 selections of context, under `RATIO`): the bias
    of a lane's 9,216 pool rows from the selection's bits, and ONE kernel
    that walks the plan's pages under it.  No row gathered, no page
    looked up a row, the pool never copied."""
    from ray_tpu.ops import sparse_attention as dsa
    from ray_tpu.ops.paged_attention import attention_plan

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    B, page, maxp, n_pages, top, K = 64, 512, 18, 1153, 2048, 8
    assert dsa.walks(maxp * page, group, top)
    n_sel, scored = top // group, maxp * page // group + -(-K // group)

    def step(q, pages, tail, table, pos, ts, groups, ok, chosen, lanes,
             count):
        o, _, _ = dsa.decode_attend(
            q, pages, tail, table, pos, ts, groups, ok, chosen, lanes,
            count, group=group, dv=dv, sm_scale=0.0625,
            plan=attention_plan(table, ts, page))
        return o

    i32 = jnp.int32
    low, c = _compile(
        step, s((B, H, dk)), s((n_pages, 1, page, dk)), s((B, 1, K, dk)),
        s((B, maxp), i32), s((B,), i32), s((B,), i32), s((B, n_sel), i32),
        s((B, n_sel), jnp.bool_), s((B, scored), jnp.bool_), s((B,), i32),
        s((), i32))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "dsa_attn" in low.as_text()
    hlo = c.as_text()
    assert _pool_copies(hlo, n_pages * page * dk // 2) == []
    # nothing the size of the rows a selection names is ever made: the
    # bias, its bits and the plan
    assert c.memory_analysis().temp_size_in_bytes < B * 2176 * dk * 2 // 4
    assert not re.search(rf"(bf16|s32)\[{B * 2176}[,\]]", hlo)


@pytest.mark.parametrize("H, dq, dv", [(64, 256, 256), (128, 192, 128)])
def test_dsa_prefill_kernel_compiles_at_the_served_widths(
        one_chip, compiled_kernels, H, dq, dv):
    """The masked flash kernel of the 1 x 8192 prefill on `flash_fwd`'s
    walk under the rows' true lengths (a device value): 64 heads, q / k /
    v 256 wide (GLM), and 128 heads of 192 / 128 (dots3); blocks of 512
    queries x 1,024 keys with the mask a byte a pair."""
    from ray_tpu.ops import sparse_attention as dsa

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    T = 8192
    low, c = _compile(
        lambda q, k, v, m, n: dsa.masked_prefill_attention(
            q, k, v, m, n, sm_scale=dq ** -0.5),
        s((1, T, H, dq)), s((1, T, H, dq)), s((1, T, H, dv)),
        s((1, T, T), jnp.int8), s((1,), jnp.int32))
    assert low.as_text().count("tpu_custom_call") == 1
    # the three transposes to head-major and the one back (and the walk's
    # tables), nothing else
    assert c.memory_analysis().temp_size_in_bytes \
        < 1.05 * T * H * 2 * (2 * dq + 2 * dv)


@pytest.mark.time_limit(900)
def test_served_glm_engine_fits_one_chip_and_copies_no_state_or_pool(
        topo, one_chip, compiled_kernels, monkeypatch):
    """glm-5.3-flash-ep8 as the benchmark serves it (5 layers, 64 lanes,
    1,153 pages): the decode program and the 1 x 8192 prefill program its
    traffic runs compile for one chip beside weights + lane state + both
    pool leaves.  Inside the K-step loop the lanes' KDA state (1.07 GB)
    is touched by `kda_update` alone, which aliases it, and neither pool
    leaf is copied, selected over or re-laid-out, in the loop or outside
    it: the merges scatter in place and the sparse step gathers rows."""
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg, eng, lows = _glm_lowerings(one_chip, [(1, 8192)])
    lane = eng.stats()["lane_state"]
    assert lane["by_kind"] == {"conv": 4 * 64 * 3 * 24576 * 2,
                               "kda": 4 * 64 * 64 * 128 * 128 * 4,
                               "ipart": 64 * 128 * 4}
    cache = eng._cache_stats()
    assert cache["by_leaf"]["latent"] == {
        "row_bytes": 1024, "positions_per_row": 1, "layers": 1,
        "pool_bytes": 1153 * 512 * 1024}
    assert cache["by_leaf"]["index"] == {
        "row_bytes": 256, "positions_per_row": 4, "layers": 1,
        "pool_bytes": 1153 * 128 * 256}
    assert cache["row_bytes"] == 1024 + 64
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(eng.params))
    resident = weights + lane["bytes"] + cache["pool_bytes"]
    assert 11.1e9 < resident < 11.3e9        # 66 % of the chip
    assert eng._prefill_floor > 1000 and (1, 8192) in eng._prefill_programs
    kernels = {"decode_k8": ("kda_update", "dsa_attn", "moe_gmm"),
               "prefill_w1_p8192": ("moe_gmm", "dsa_prefill", "kda_scan")}
    compiled = {}
    for name, low in lows.items():
        txt = low.as_text()
        for kern in kernels[name]:
            assert kern in txt, (name, kern)
        compiled[name] = c = low.compile()
        mem = c.memory_analysis()
        held = resident + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: temps {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"resident {resident / 1e9:.2f} GB, held {held / 1e9:.2f} GB")
        assert held < 16.9e9 - 1.5e9, (name, held)
    # the prefill's four scans are four kernels, each handed what the
    # layer's own fusions wrote: no copy of a [1, 8192, 8192] float32
    hlo = compiled["prefill_w1_p8192"].as_text()
    scans = [ln for ln in hlo.splitlines()
             if "custom-call(" in ln and "/kda_scan/" in ln]
    assert len(scans) == 4
    assert not [ln for ln in hlo.splitlines()
                if re.search(r"f32\[1,8192,8192\]\S* copy\(", ln)]
    # What follows a layer's kernel or its routed loop (the output
    # projection, the shared expert, the residual path's second half),
    # the dense feed-forward and the final norm are loops over chunks of
    # the rows under a count the device holds (`ops/live_rows.walk`): two
    # a layer and one.  A weight is read by its matmul and by nothing
    # else in a body.  The program's temporaries stand 0.2 GB over the
    # straight-line program's (2.66 GB in this compile: PERF.md section
    # 6, PR 55; without the barrier in `kda_prefill` they were 4.07).
    assert len(_loops_of(hlo, "/live_rows/")) == 2 * cfg.n_layers + 1
    shapes = {a.shape for a in jax.tree.leaves(eng.params) if a.ndim >= 2}
    assert (8192, 4096) in shapes and (16384, 24) in shapes
    smallest = min(math.prod(w) for w in shapes)
    assert weight_sized_writes(hlo, smallest, "/live_rows/", shapes) == []
    assert weight_sized_writes(hlo, smallest, "/live_rows/")
    assert compiled["prefill_w1_p8192"].memory_analysis(
        ).temp_size_in_bytes <= 2.9e9
    c = compiled["decode_k8"]
    hlo = c.as_text()
    assert "while(" in hlo and "/live_rows/" not in hlo
    assert _loops_of(hlo, "moe_experts") == []      # one block: no loop
    layer_state = 64 * 64 * 128 * 128        # one KDA layer's lanes
    # (the one other array of that size is the sparse step's gathered
    # rows, 64 lanes x 2,176 rows x 512: what the selection reads, 0.14 GB)
    found = [f for f in weight_sized_writes(hlo, layer_state)
             if "dsa_select" not in f[2]]
    assert found and all(op == "custom-call" and "kda_update" in scope
                         for _, op, scope in found), found
    # no copy of anything with a pool leaf's 1,153 pages (W_qb [1536,
    # 16384] IS copied, to the layout the 64-row matmul reads, once a
    # window outside the loop: 50 MB, PERF.md section 7)
    lines = {m.group(1): ln for ln in hlo.splitlines()
             for m in [_INSTR.match(ln)] if m}
    assert [n for n in _pool_copies(hlo, 1153 * 128 * 128)
            if "[1153," in lines[n].split("copy(")[0]] == []
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * layer_state * 4 + cache[
        "pool_bytes"]
    loop = _loop_lines(hlo)
    assert len([ln for ln in loop if "custom-call(" in ln
                and "kda_update" in ln]) == 4          # a call a KDA layer
    assert len([ln for ln in loop if "custom-call(" in ln
                and "dsa_attn" in ln]) == 1


# ------------------------------------------------- dots3-note-prev (PR 45)
@pytest.mark.parametrize("lanes,with_lengths", [(64, True), (8, False)])
def test_swa_kernels_compile_at_the_served_widths(one_chip, compiled_kernels,
                                                  lanes, with_lengths):
    """dots3-note-prev's window layers: `swa_attn` over a lane's ring of
    640 rows x 1,152 for 64 heads at once (the ring read where it lies:
    no copy of it), and `flash_fwd` under the band of 513 at 64 heads of
    256 / 128 over 8,192 positions."""
    from ray_tpu.ops import flash_attention, window_attention as swa

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, ring, pos, lanes_, count):
        return swa.swa_decode_attention(
            q, ring, swa.ring_bias(pos, 640, 513), lanes_, count, dv=1024,
            sm_scale=256 ** -0.5)

    low, c = _compile(step, s((lanes, 64, 1152)), s((lanes, 640, 1152)),
                      s((lanes,), jnp.int32), s((lanes,), jnp.int32),
                      s((), jnp.int32))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "swa_attn" in low.as_text()
    assert _pool_copies(c.as_text(), lanes * 640 * 1152 // 2) == []

    def band(q, k, v, n):
        return flash_attention.flash_attention(
            q, k, v, sm_scale=256 ** -0.5, window=513,
            lengths=n if with_lengths else None)

    low, _ = _compile(band, s((1, 8192, 64, 256)), s((1, 8192, 64, 256)),
                      s((1, 8192, 64, 128)), s((1,), jnp.int32))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "flash_fwd" in low.as_text()


@pytest.mark.time_limit(900)
def test_served_dots3_engine_fits_one_chip_and_copies_no_ring_or_pool(
        topo, one_chip, compiled_kernels, monkeypatch):
    """dots3-note-prev-ep8 as the benchmark serves it (5 layers, 64 lanes,
    1,153 pages): the decode program and the 1 x 8192 prefill program its
    traffic runs compile for one chip beside weights + rings + both pool
    leaves of the two FULL layers.  The window layers hold no page: their
    rows are the lanes' rings (0.28 GB), which the decode program hands
    back in the buffers they came in, written a row a lane a step and
    read by `swa_attn` where they lie; neither a ring nor a pool leaf is
    copied, in the loop or outside it."""
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg, eng, lows = _glm_lowerings(one_chip, [(1, 8192)],
                                    "dots3-note-prev-ep8")
    lane = eng.stats()["lane_state"]
    ring = 64 * 640 * 1152                  # one window layer's lanes
    assert lane["layers"] == 3
    assert lane["by_kind"] == {"window": 3 * ring * 2}
    cache = eng._cache_stats()
    assert cache["by_leaf"]["latent"] == {
        "row_bytes": 1280, "positions_per_row": 1, "layers": 2,
        "pool_bytes": 2 * 1153 * 512 * 1280}
    assert cache["by_leaf"]["index"] == {
        "row_bytes": 256, "positions_per_row": 1, "layers": 2,
        "pool_bytes": 2 * 1153 * 512 * 256}
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(eng.params))
    resident = weights + lane["bytes"] + cache["pool_bytes"]
    assert 10.2e9 < resident < 10.35e9       # 61 % of the chip
    assert eng._prefill_floor > 1000 and (1, 8192) in eng._prefill_programs
    assert eng._spec.prefill_state_bytes == 3 * 640 * 1152 * 2
    kernels = {"decode_k8": ("swa_attn", "dsa_attn", "moe_gmm"),
               "prefill_w1_p8192": ("moe_gmm", "dsa_prefill", "flash_fwd")}
    compiled = {}
    for name, low in lows.items():
        txt = low.as_text()
        for kern in kernels[name]:
            assert kern in txt, (name, kern)
        compiled[name] = c = low.compile()
        mem = c.memory_analysis()
        held = resident + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: temps {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"resident {resident / 1e9:.2f} GB, held {held / 1e9:.2f} GB")
        assert held < 16.9e9 - 1.5e9, (name, held)
    c = compiled["decode_k8"]
    hlo = c.as_text()
    assert "while(" in hlo
    assert _loops_of(hlo, "moe_experts") == []      # one block: no loop
    lines = {m.group(1): ln for ln in hlo.splitlines()
             for m in [_INSTR.match(ln)] if m}
    # no copy of a ring (64 lanes x 640 rows) or of a pool leaf (1,153
    # pages), anywhere in the program
    copies = _pool_copies(hlo, ring // 2)
    assert [n for n in copies
            if "[64,640," in lines[n].split("copy(")[0]
            or "[1153," in lines[n].split("copy(")[0]] == []
    # inside the loop the only writers of something ring-sized are the
    # row write (a scatter in place) and nothing else; the gathered rows
    # of the selection (64 x 2,176 x 640) belong to `dsa_select`
    found = [f for f in weight_sized_writes(hlo, ring)
             if "dsa_select" not in f[2] and "dsa_index" not in f[2]]
    assert all("kv_write" in scope for _, _, scope in found), found
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= lane["bytes"] + cache["pool_bytes"]
    loop = _loop_lines(hlo)
    assert len([ln for ln in loop if "custom-call(" in ln
                and "swa_attn" in ln]) == 3        # a call a window layer
    assert len([ln for ln in loop if "custom-call(" in ln
                and "dsa_attn" in ln]) == 2        # a call a full layer


# ------------------------------- the routed layer's blocks (PR 47)
def _routed_layer(config: str):
    """(the serving module, its program config) of a benchmark
    configuration with routed layers, as the benchmark builds it."""
    from benchmarks.harness import spec
    from ray_tpu.models import serving_model

    conf = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                       config + ".json"))
    fam = spec.config_family(conf)
    cfg = fam.program_config(fam.published(conf),
                             max_seq=conf["engine"]["max_len"])
    return serving_model(cfg), cfg


@pytest.mark.parametrize("config,lanes,positions", [
    ("dots3-note-prev-ep8", 64, 8192), ("glm-5.3-flash-ep8", 64, 8192),
    ("sarvam-105b-ep4", 32, 8192), ("lfm2-24b-a2b-d9", 64, 4096)])
def test_routed_layer_moves_a_block_of_rows_and_decode_holds_no_loop(
        topo, one_chip, compiled_kernels, monkeypatch, config, lanes,
        positions):
    """One routed layer at the served widths, compiled for the chip in
    its two shapes.  Prefill (`positions` x top k assignments, more than
    `routed.BLOCK`): ONE loop over the blocks of the sorted list; the
    rows gathered in, both `gmm` outputs with their zeroing selects and
    the SwiGLU are a BLOCK's rows and the rows go back by an add at
    their token's row, so NOTHING in the program has the list's rows at
    the model's width or the experts'.  Decode (`lanes` x top k, one
    block): straight-line code, no loop but the visit lists' binary
    searches, and no scatter into the layer's output."""
    from ray_tpu.models import routed

    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    mod, cfg = _routed_layer(config)
    lid = max(i for i in range(cfg.n_layers) if cfg.is_routed(i))

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    lp = jax.tree.map(sds, jax.eval_shape(
        lambda: mod.init_params(jax.random.PRNGKey(0), cfg))["layers"][lid])
    k, d, f = cfg.top_k, cfg.dim, cfg.moe_ffn_dim
    hlo = {}
    for rows in (lanes, positions):
        h2 = jax.ShapeDtypeStruct((rows, d), cfg.dtype, sharding=one_chip)
        live = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip)
        hlo[rows] = jax.jit(
            lambda h, lp, live: mod.routed_ffn(h, lp, cfg, live)
        ).lower(h2, lp, live).compile().as_text()
        assert "moe_gmm" in hlo[rows]
    assert lanes * k <= routed.BLOCK < positions * k
    assert _loops_of(hlo[lanes], "moe_experts") == []
    assert len(_loops_of(hlo[positions], "moe_experts")) == 1
    assert not re.search(rf"f32\[{lanes},\d+\]\S* scatter\(", hlo[lanes])
    listed, text = positions * k, hlo[positions]
    for width in (d, 2 * f, f):
        assert not re.search(rf"\[{listed},{width}\]", text)
        if width != f:
            assert re.search(rf"bf16\[{routed.BLOCK},{width}\]", text)
    strip = min(d, routed.ACC_BYTES // (4 * positions))
    assert re.search(rf"f32\[{positions},{strip}\]\S* scatter\(", text)


# ------------------------------------------------- MiMo-V2-Flash (PR 52)
def test_paged_attn_compiles_at_keys_wider_than_values(one_chip,
                                                       compiled_kernels):
    """MiMo-V2-Flash's global layers: 64 query heads over 4 kv heads (a
    group of 16), K pages 192 wide beside V pages of 128, 64 lanes x 18
    columns of 512 rows; the 192-wide keys stored 256 wide, and as they
    are."""
    from ray_tpu.ops.paged_attention import paged_decode_attention

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, kvh, rep, page, kt, maxp = 64, 4, 16, 512, 8, 18
    for dk in (256, 192):
        low, _ = _compile(
            functools.partial(paged_decode_attention, sm_scale=192 ** -0.5),
            s((B, kvh, rep, dk)), s((1153, kvh, page, dk)),
            s((1153, kvh, page, 128)), s((B, kvh, kt, dk)),
            s((B, kvh, kt, 128)), s((B, maxp), jnp.int32),
            s((B,), jnp.int32), s((B,), jnp.int32))
        assert low.as_text().count("tpu_custom_call") == 1
        assert "paged_attn" in low.as_text()


@pytest.mark.parametrize("lanes,with_lengths", [(64, True), (8, False)])
def test_kv_ring_kernels_compile_at_the_served_widths(
        one_chip, compiled_kernels, lanes, with_lengths):
    """MiMo-V2-Flash's window layers: `swa_attn` over a lane's K ring [8,
    128, 256] (192 stored 256) and V ring [8, 128, 128] for 8 query heads a kv head with
    the sink (the rings read where they lie: no copy of them), the row
    write a head, and the flash forward under the band of 128 with the
    sink (`swa_band`) at 64 heads over 8 of 192 / 128 over 8,192
    positions."""
    from ray_tpu.ops import flash_attention, window_attention as swa

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, kr, vr, k, v, pos, sink, live, lanes_, count):
        kr = swa.kv_ring_write(kr, k, pos, live)
        vr = swa.kv_ring_write(vr, v, pos, live)
        o = swa.kv_ring_attention(
            q, kr, vr, swa.ring_bias(pos, 128, 128), sink, lanes_, count,
            sm_scale=192 ** -0.5)
        return o, kr, vr

    low = jax.jit(step, donate_argnums=(1, 2)).lower(
        s((lanes, 8, 8, 256)), s((lanes, 8, 128, 256)),
        s((lanes, 8, 128, 128)), s((lanes, 8, 256)), s((lanes, 8, 128)),
        s((lanes,), jnp.int32), s((8, 8), jnp.float32),
        s((lanes,), jnp.bool_), s((lanes,), jnp.int32), s((), jnp.int32))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "swa_attn" in low.as_text()
    c = low.compile()
    assert _pool_copies(c.as_text(), lanes * 8 * 128 * 128 // 2) == []

    def band(q, k, v, sink, n):
        return flash_attention.flash_attention(
            q, k, v, sm_scale=192 ** -0.5, window=128, sink=sink,
            lengths=n if with_lengths else None)

    low, _ = _compile(band, s((1, 8192, 64, 192)), s((1, 8192, 8, 192)),
                      s((1, 8192, 8, 128)), s((64,), jnp.float32),
                      s((1,), jnp.int32))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "swa_band" in low.as_text()


@pytest.mark.time_limit(900)
def test_served_mimo_engine_fits_one_chip_and_copies_no_ring_or_pool(
        topo, one_chip, compiled_kernels, monkeypatch):
    """mimo-v2-flash-ep16 as the benchmark serves it (7 layers, 64 lanes,
    1,153 pages): the decode program and the 1 x 8192 prefill program its
    traffic runs compile for one chip beside weights + rings + the K and
    V pages of the two GLOBAL layers.  The window layers hold no page:
    their rows are the lanes' rings, which the decode program hands back
    in the buffers they came in, written a row a head a lane a step and
    read by `swa_attn` where they lie; neither a ring nor a pool leaf is
    copied, in the loop or outside it."""
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg, eng, lows = _glm_lowerings(one_chip, [(1, 8192)],
                                    "mimo-v2-flash-ep16")
    lane = eng.stats()["lane_state"]
    ring = 64 * 8 * 128                     # rows of one layer's lanes
    assert lane["layers"] == 5
    assert lane["by_kind"] == {"window_k": 5 * ring * 256 * 2,
                               "window_v": 5 * ring * 128 * 2}
    cache = eng._cache_stats()
    # a K row is stored 256 wide (192 + 64 of zeros: whole lane tiles)
    assert cache["by_leaf"]["k"] == {
        "row_bytes": 4 * 256 * 2, "positions_per_row": 1, "layers": 2,
        "pool_bytes": 2 * 1153 * 512 * 4 * 256 * 2}
    assert cache["by_leaf"]["v"]["pool_bytes"] == 2 * 1153 * 512 * 4 * 128 * 2
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(eng.params))
    resident = weights + lane["bytes"] + cache["pool_bytes"]
    assert 10.7e9 < resident < 10.8e9        # 64 % of the chip
    assert (1, 8192) in eng._prefill_programs
    assert eng._spec.prefill_state_bytes == 5 * 128 * 6144
    kernels = {"decode_k8": ("swa_attn", "paged_attn", "moe_gmm"),
               "prefill_w1_p8192": ("moe_gmm", "swa_band", "flash_fwd")}
    compiled = {}
    for name, low in lows.items():
        txt = low.as_text()
        for kern in kernels[name]:
            assert kern in txt, (name, kern)
        compiled[name] = c = low.compile()
        mem = c.memory_analysis()
        held = resident + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: temps {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"resident {resident / 1e9:.2f} GB, held {held / 1e9:.2f} GB")
        assert held < 16.9e9 - 1.5e9, (name, held)
    # The prefill program's position-wise halves are loops over chunks
    # of the rows (`ops/live_rows.walk`: norm + q/k/v + rotary, and value
    # scale + `wo`, a layer; the dense layer's norm + SwiGLU), under a
    # count the device holds.  A weight is read by its matmul and by
    # nothing else in a body (re-laid or converted inside, it would be
    # written once a TRIP), and the program's temporaries are a chunk's,
    # not a row's (the straight-line program's: 2.79 GB in this compile,
    # PERF.md section 6, PR 53).
    c = compiled["prefill_w1_p8192"]
    hlo = c.as_text()
    assert len(_loops_of(hlo, "/live_rows/")) == 2 * cfg.n_layers + 1
    weights = {a.shape for a in jax.tree.leaves(eng.params) if a.ndim >= 2}
    assert (4096, 12288) in weights and (16384, 4096) in weights
    smallest = min(math.prod(w) for w in weights)
    assert weight_sized_writes(hlo, smallest, "/live_rows/", weights) == []
    # ... while the reader does see the chunks the bodies write
    assert weight_sized_writes(hlo, smallest, "/live_rows/")
    assert c.memory_analysis().temp_size_in_bytes <= 1.5e9
    c = compiled["decode_k8"]
    hlo = c.as_text()
    assert "while(" in hlo
    assert _loops_of(hlo, "moe_experts") == []      # one block: no loop
    lines = {m.group(1): ln for ln in hlo.splitlines()
             for m in [_INSTR.match(ln)] if m}
    # no copy of a ring (64 lanes x 8 heads x 128 rows) or of a pool leaf
    # (1,153 pages), anywhere in the program
    copies = _pool_copies(hlo, ring * 128 // 2)
    assert [n for n in copies
            if "[64,8,128," in lines[n].split("copy(")[0]
            or "[1153," in lines[n].split("copy(")[0]] == []
    # inside the loop the only writers of something ring-sized are the
    # rows' writes (scatters in place)
    found = weight_sized_writes(hlo, ring * 128)
    assert all("ring_write" in scope for _, _, scope in found), found
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= lane["bytes"] + cache["pool_bytes"]
    loop = _loop_lines(hlo)
    assert len([ln for ln in loop if "custom-call(" in ln
                and "swa_attn" in ln]) == 5        # a call a window layer
    assert len([ln for ln in loop if "custom-call(" in ln
                and "paged_attn" in ln]) == 2      # a call a global layer


# ------------------------------------------------- Command A+ (PR 54)
@pytest.mark.parametrize("ring,lanes", [(4096, 64), (128, 8)])
def test_the_blocked_ring_kernel_compiles_at_the_served_widths(
        one_chip, compiled_kernels, ring, lanes):
    """Command A+'s window layers: `swa_attn` over a lane's K and V rings
    [8, 4096, 128], 16 query heads a kv head and no sink, walked in
    blocks of 1,024 rows under the step's plan (a ring of 128 rows: ONE
    block, the same kernel); the rings are read where they lie (no copy
    of them) and written a row a head."""
    from ray_tpu.ops import ssm, window_attention as swa

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, kr, vr, k, v, pos, live):
        lanes_, count = ssm.live_lanes(live)
        kr = swa.kv_ring_write(kr, k, pos, live)
        vr = swa.kv_ring_write(vr, v, pos, live)
        o = swa.kv_ring_attention(
            q, kr, vr, swa.ring_bias(pos, ring, ring), None, lanes_, count,
            sm_scale=128 ** -0.5)
        return o, kr, vr

    low = jax.jit(step, donate_argnums=(1, 2)).lower(
        s((lanes, 8, 16, 128)), s((lanes, 8, ring, 128)),
        s((lanes, 8, ring, 128)), s((lanes, 8, 128)), s((lanes, 8, 128)),
        s((lanes,), jnp.int32), s((lanes,), jnp.bool_))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "swa_attn" in low.as_text()
    c = low.compile()
    assert _pool_copies(c.as_text(), lanes * 8 * ring * 128 // 2) == []
    assert c.memory_analysis().temp_size_in_bytes < 64 << 20


def test_a_band_of_4096_without_a_sink_is_named_swa_band(
        one_chip, compiled_kernels):
    """The flash forward under a band of 4,096 at 128 heads over 8 of 128
    over 8,192 positions, no sink: `band_name` names the kernel, and the
    call compiles at `band_blocks`' 512 x 512 with its running max and
    sum kept lane-broadcast (a walk of 36 + 8 x 9 steps a head)."""
    from ray_tpu.ops import flash_attention

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def band(q, k, v, n):
        return flash_attention.flash_attention(
            q, k, v, window=4096, lengths=n, band_name="swa_band")

    low, _ = _compile(band, s((1, 8192, 128, 128)), s((1, 8192, 8, 128)),
                      s((1, 8192, 8, 128)), s((1,), jnp.int32))
    assert low.as_text().count("tpu_custom_call") == 1
    assert "swa_band" in low.as_text()
    assert "flash_fwd" not in low.as_text()
    steps = flash_attention.attn_blocks(
        8192, [8192], *flash_attention.band_blocks(8192), window=4096)
    assert steps == 108 and f"tensor<{steps}xi32>" in low.as_text()


@pytest.mark.time_limit(900)
def test_served_command_a_plus_fits_one_chip_and_copies_no_ring_or_pool(
        topo, one_chip, compiled_kernels, monkeypatch):
    """command-a-plus-ep16 as the benchmark serves it (4 layers, 64 lanes,
    1,153 pages): the decode program and the 1 x 8192 prefill program its
    traffic runs compile for one chip beside weights + 3.2 GB of rings +
    the K and V pages of the ONE global layer.  The window layers hold no
    page: their rows are the lanes' rings, which the decode program hands
    back in the buffers they came in, written a row a head a lane a step
    and read by `swa_attn` where they lie; neither a ring nor a pool leaf
    is copied, in the loop or outside it."""
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg, eng, lows = _glm_lowerings(one_chip, [(1, 8192)],
                                    "command-a-plus-ep16")
    lane = eng.stats()["lane_state"]
    ring = 64 * 8 * 4096                    # rows of one layer's lanes
    assert lane["layers"] == 3
    assert lane["by_kind"] == {"window_k": 3 * ring * 128 * 2,
                               "window_v": 3 * ring * 128 * 2}
    cache = eng._cache_stats()
    assert cache["by_leaf"]["k"] == {
        "row_bytes": 8 * 128 * 2, "positions_per_row": 1, "layers": 1,
        "pool_bytes": 1153 * 512 * 8 * 128 * 2}
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(eng.params))
    resident = weights + lane["bytes"] + cache["pool_bytes"]
    assert 11.85e9 < resident < 11.92e9      # 70 % of the chip
    assert (1, 8192) in eng._prefill_programs
    assert eng._spec.prefill_state_bytes == 3 * 4096 * 8 * 256 * 2
    kernels = {"decode_k8": ("swa_attn", "paged_attn", "moe_gmm"),
               "prefill_w1_p8192": ("moe_gmm", "swa_band", "flash_fwd")}
    compiled = {}
    for name, low in lows.items():
        txt = low.as_text()
        for kern in kernels[name]:
            assert kern in txt, (name, kern)
        compiled[name] = c = low.compile()
        mem = c.memory_analysis()
        held = resident + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: temps {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"resident {resident / 1e9:.2f} GB, held {held / 1e9:.2f} GB")
        assert held < 16.9e9 - 1.0e9, (name, held)
    c = compiled["prefill_w1_p8192"]
    hlo = c.as_text()
    # two walks a layer: norm + q/k/v + rotary, and `wo` + shared experts
    assert len(_loops_of(hlo, "/live_rows/")) == 2 * cfg.n_layers
    c = compiled["decode_k8"]
    hlo = c.as_text()
    assert "while(" in hlo
    assert _loops_of(hlo, "moe_experts") == []      # one block: no loop
    lines = {m.group(1): ln for ln in hlo.splitlines()
             for m in [_INSTR.match(ln)] if m}
    # no copy of a ring (64 lanes x 8 heads x 4,096 rows) or of a pool
    # leaf (1,153 pages), anywhere in the program
    copies = _pool_copies(hlo, ring * 128 // 2)
    assert [n for n in copies
            if "[64,8,4096," in lines[n].split("copy(")[0]
            or "[1153," in lines[n].split("copy(")[0]] == []
    # inside the loop the only writers of something ring-sized are the
    # rows' writes (scatters in place)
    found = weight_sized_writes(hlo, ring * 128)
    assert all("ring_write" in scope for _, _, scope in found), found
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= lane["bytes"] + cache["pool_bytes"]
    loop = _loop_lines(hlo)
    assert len([ln for ln in loop if "custom-call(" in ln
                and "swa_attn" in ln]) == 3        # a call a window layer
    assert len([ln for ln in loop if "custom-call(" in ln
                and "paged_attn" in ln]) == 1      # the global layer's


# --------------------------------------------- Solar-Open2-250B (PR 58)
def test_kda_scan_compiles_in_both_forms(one_chip, compiled_kernels):
    """The two forms of the scan at 64 heads of [128, 128], chunks of 32,
    one row of 8,192: under GLM's bound the chunk's middle anchors every
    pair; without one (Solar-Open2's gate, `unbounded`) the pairs by
    halves; the inverse by halves in both.  Both are ONE kernel named
    `kda_scan`, and the exact form is the larger program."""
    from ray_tpu.ops import kda

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    b, T, H, dk = 1, 8192, 64, 128
    size = {}
    for unbounded in (False, True):
        def scan(q, k, v, g, beta, lens, unbounded=unbounded):
            shape = (b, T, H, dk)
            o, S = kda.kda_scan(q.reshape(shape), k.reshape(shape),
                                v.reshape(shape), g.reshape(shape), beta,
                                32, lens, unbounded=unbounded)
            return o.reshape(b, T, H * dk), S

        x = s((b, T, H * dk))
        low, c = _compile(scan, x, x, x, x, s((b, T, H)),
                          s((b,), jnp.int32))
        text = low.as_text()
        assert text.count("tpu_custom_call") == 1 and "kda_scan" in text
        assert c.memory_analysis().temp_size_in_bytes < b * T * H * dk // 2
        size[unbounded] = len(text)
    assert size[True] > size[False]


@pytest.mark.time_limit(900)
def test_served_solar_open2_fits_one_chip_and_copies_no_state_or_pool(
        topo, one_chip, compiled_kernels, monkeypatch):
    """solar-open2-250b-ep8 as the benchmark serves it (one period G K K
    K, 40 of 320 experts held, 64 lanes, 1,153 pages): the decode program
    and the ONE prefill program its traffic reaches (1 x 8192) compile for
    one chip beside 9.87 GB of weights, lane state and pool; inside the
    K-step loop the lanes' state matrices (0.81 GB) are touched by
    `kda_update` alone, which aliases them, and the pool is not copied."""
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg, eng, lows = _glm_lowerings(one_chip, [(1, 8192)],
                                    "solar-open2-250b-ep8")
    lane = eng.stats()["lane_state"]
    assert lane["layers"] == 3
    assert lane["by_kind"] == {"conv": 3 * 64 * 3 * 24576 * 2,
                               "kda": 3 * 64 * 64 * 128 * 128 * 4}
    cache = eng._cache_stats()
    assert (cache["kind"], cache["layers"], cache["row_bytes"]) == (
        "kv", 1, 2 * 8 * 128 * 2)
    assert cache["pool_bytes"] == 1153 * 512 * 4096
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(eng.params))
    # bfloat16 but for A_log, dt_bias and the router's biases (float32)
    assert weights == 2 * 3_308_353_344 + 2 * (3 * (64 + 8192) + 4 * 320)
    resident = weights + lane["bytes"] + cache["pool_bytes"]
    assert 9.85e9 < resident < 9.89e9           # 58 % of the chip
    assert (1, 8192) in eng._prefill_programs
    assert eng._spec.prefill_state_bytes == 3 * (64 * 128 * 128 * 4
                                                 + 3 * 24576 * 2)
    kernels = {"decode_k8": ("kda_update", "paged_attn", "moe_gmm"),
               "prefill_w1_p8192": ("kda_scan", "flash_fwd", "moe_gmm")}
    for name, low in lows.items():
        txt = low.as_text()
        for kern in kernels[name]:
            assert kern in txt, (name, kern)
        c = low.compile()
        mem = c.memory_analysis()
        held = resident + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: temps {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"resident {resident / 1e9:.2f} GB, held {held / 1e9:.2f} GB")
        assert held < 16.9e9 - 1.0e9, (name, held)
        hlo = c.as_text()
        if name != "decode_k8":
            # a walk after each mixer and one after each routed loop
            assert len(_loops_of(hlo, "/live_rows/")) == 2 * cfg.n_layers
            assert mem.temp_size_in_bytes < 3.5e9
            continue
        layer_state = 64 * 64 * 128 * 128
        found = weight_sized_writes(hlo, layer_state)
        assert found and all(op == "custom-call" and "kda_update" in scope
                             for _, op, scope in found), found
        assert mem.alias_size_in_bytes >= lane["bytes"] + cache["pool_bytes"]
        assert mem.temp_size_in_bytes < 0.1e9
        loop = _loop_lines(hlo)
        assert len([ln for ln in loop if "custom-call(" in ln
                    and "kda_update" in ln]) == 3       # a call a KDA layer
        assert len([ln for ln in loop if "custom-call(" in ln
                    and "paged_attn" in ln]) == 1       # the GQA layer's
        assert len([ln for ln in loop if "custom-call(" in ln
                    and "moe_gmm" in ln]) == 8          # two a routed layer


def test_bsa_attn_compiles_at_the_served_widths(one_chip, compiled_kernels):
    """MiniCPM-SALA's decode step at the served widths (32 lanes, 2 kv
    heads x 16 query heads x 128) under the benchmark's table of 66
    columns: `bsa_index` scores and selects a lane's 528 blocks, the
    selection is a bias a row and `bsa_attn` walks the lane's pages."""
    from ray_tpu.ops import block_sparse_attention as bsa

    shape = bsa.Shape()

    def s(shape_, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape_, dtype, sharding=one_chip)

    B, n_pages, maxp = 32, 2113, 66
    low, c = _compile(
        lambda *a: bsa.decode_attention(*a, shape, sm_scale=128 ** -0.5),
        s((B, 2, 16, 128)), s((n_pages, 2, 512, 128)),
        s((n_pages, 2, 512, 128)), s((n_pages, 2, 32, 128)),
        s((B, 2, 8, 128)), s((B, 2, 8, 128)), s((B, 2, 1, 128)),
        s((B, maxp), jnp.int32), s((B,), jnp.int32), s((B,), jnp.int32))
    txt = low.as_text()
    for kern in ("bsa_index", "bsa_attn", "paged_attn"):
        assert txt.count(kern) >= 1, kern
    # the pool is not copied
    assert not _pool_copies(c.as_text(), n_pages * 2 * 512 * 128)


@pytest.mark.parametrize("T", [32768,      # the cell's one bucket
                               16512])     # the judge's sample: no whole
#                                            number of query blocks
def test_bsa_index_compiles_at_the_served_widths(one_chip, compiled_kernels,
                                                 T):
    """A prompt's scores and selection, 256 queries of a kv head's 16
    heads a step against its T / 16 stride rows: a bit a (query, block)
    leaves the kernel, the mask `bsa_prefill` reads, and nothing of [T, 16,
    T / 16] is written."""
    from ray_tpu.ops import block_sparse_attention as bsa

    shape = bsa.Shape()

    def s(shape_, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape_, dtype, sharding=one_chip)

    low, c = _compile(
        lambda q, m, lens: bsa.prefill_select(q, m, lens, shape,
                                              128 ** -0.5),
        s((1, 2, 16, T, 128)), s((1, 2, T // 16, 128)), s((1,), jnp.int32))
    assert low.as_text().count("bsa_index") >= 1
    mem = c.memory_analysis()
    assert mem.output_size_in_bytes == 2 * T * bsa.blocks_padded(
        T // 16, shape)                                   # int8
    # (the stride rows re-laid; q padded where T is no whole query blocks)
    assert mem.temp_size_in_bytes < (0.05e9 if T % 256 == 0 else 0.5e9)


def test_bsa_prefill_compiles_at_the_served_widths(one_chip,
                                                   compiled_kernels):
    """One 32,768-row prompt, 16 query heads a kv head a step: the mask a
    bit a (query, block), widened inside the kernel."""
    from ray_tpu.ops import block_sparse_attention as bsa

    shape = bsa.Shape()
    assert bsa.prefill_blocks(32768, shape) == (128, 512)
    T = 32768

    def s(shape_, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape_, dtype, sharding=one_chip)

    low, c = _compile(
        lambda q, k, v, mask, lens: bsa.prefill_attention(
            q, k, v, mask, lens, shape, sm_scale=128 ** -0.5),
        s((1, 2, 16, T, 128)), s((1, T, 2, 128)), s((1, T, 2, 128)),
        s((1, 2, T, T // 64), jnp.int8), s((1,), jnp.int32))
    assert low.as_text().count("bsa_prefill") >= 1
    # q and o transposed once each, the mask as bytes: nothing T x T
    assert c.memory_analysis().temp_size_in_bytes < 1.0e9


@pytest.mark.time_limit(600)
def test_served_minicpm_sala_fits_one_chip_and_copies_no_state_or_pool(
        topo, one_chip, compiled_kernels, monkeypatch):
    """minicpm-sala-9b-d4 as the benchmark serves it (one period S L L L
    at the published widths, 32 lanes, 2,113 pages): the decode program
    and the ONE prefill program its traffic reaches (1 x 32,768) compile
    for one chip beside 4.77 GB of weights, lane state and pools; inside
    the K-step loop the lanes' state matrices (0.20 GB) are touched by
    `ssm_update` alone, which aliases them, and no pool is copied."""
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg, eng, lows = _glm_lowerings(one_chip, [(1, 32768)],
                                    "minicpm-sala-9b-d4")
    lane = eng.stats()["lane_state"]
    assert lane["layers"] == 3
    assert lane["by_kind"] == {"lightning": 3 * 32 * 128 * 4096 * 4,
                               "kpart": 32 * 256 * 4}
    cache = eng._cache_stats()
    assert (cache["kind"], cache["layers"], cache["row_bytes"]) == (
        "kv", 1, 2 * 2 * 128 * 2 + 512 // 16)
    assert cache["pool_bytes"] == 2113 * (512 * 1024 + 32 * 512)
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(eng.params))
    assert weights == 2 * 1_711_216_000
    resident = weights + lane["bytes"] + cache["pool_bytes"]
    assert 4.75e9 < resident < 4.78e9           # 28 % of the chip
    # one row of 32,768 is the only program at that bucket
    # (serve/prefill_plan.PREFILL_MAX_TOKENS)
    assert eng._spec.prefill_state_bytes == 3 * 32 * 128 * 128 * 4 + 1024
    kernels = {"decode_k8": ("ssm_update", "bsa_index", "bsa_attn",
                             "paged_attn"),
               "prefill_w1_p32768": ("bsa_index", "bsa_prefill")}
    for name, low in lows.items():
        txt = low.as_text()
        for kern in kernels[name]:
            assert kern in txt, (name, kern)
        c = low.compile()
        mem = c.memory_analysis()
        held = resident + mem.temp_size_in_bytes + (
            mem.output_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: temps {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"resident {resident / 1e9:.2f} GB, held {held / 1e9:.2f} GB")
        assert held < 16.9e9 - 1.0e9, (name, held)
        hlo = c.as_text()
        if name != "decode_k8":
            assert "flash_fwd" not in txt       # past dense_len
            # a walk after each mixer and one for each SwiGLU
            assert len(_loops_of(hlo, "/live_rows/")) == 2 * cfg.n_layers
            # a quarter of the chip with what is resident, and no more
            # than the scan's float32 arrays ask
            assert 0.25 * 16.9e9 < held and mem.temp_size_in_bytes < 4.5e9
            continue
        layer_state = 32 * 128 * 4096
        found = weight_sized_writes(hlo, layer_state)
        assert found and all(op == "custom-call" and "ssm_update" in scope
                             for _, op, scope in found), found
        assert mem.alias_size_in_bytes >= lane["bytes"] + cache["pool_bytes"]
        assert mem.temp_size_in_bytes < 0.1e9
        loop = _loop_lines(hlo)
        assert len([ln for ln in loop if "custom-call(" in ln
                    and "ssm_update" in ln]) == 3   # a call a lightning layer
        assert not _pool_copies(hlo, 2113 * 2 * 512 * 128)
