"""The family seam: a configuration names its model family, one file gives
the harness everything it knows about the architecture, and the kernel
rooflines read their kernels by name.  `data/family_example.py` is the
second family (README, "A model family"): no file of the repo is edited
to run it."""
from __future__ import annotations

import dataclasses
import json
import os
import types

import pytest

from benchmarks.harness import flops, peaks, readers, spec, timeline, \
    trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
EXAMPLE = os.path.join(HERE, "data", "family_example.py")
MISTRAL = dict(hidden_size=4096, num_attention_heads=32,
               num_key_value_heads=8, head_dim=128, intermediate_size=14336,
               vocab_size=32768, rope_theta=1000000.0, rms_norm_eps=1e-05,
               max_position_embeddings=32768)
CODESTRAL = dict(MISTRAL, hidden_size=6144, num_attention_heads=48,
                 intermediate_size=16384)
# what `model.published` / `model.llama_config` gave at the parent of PR 27
# for the three configuration files, written down, not computed
GOLDEN = {
    "mistral-7b-v0.3-d16": (
        dict(MISTRAL, num_hidden_layers=16),
        dict(vocab_size=32768, dim=4096, n_layers=16, n_heads=32,
             n_kv_heads=8, ffn_dim=14336, max_seq=2048, rope_theta=1e6,
             norm_eps=1e-5, remat=True, remat_mode="flash_resid",
             use_ring_attention=False), {}),
    "codestral-22b-v0.1-d8": (
        dict(CODESTRAL, num_hidden_layers=8),
        dict(vocab_size=32768, dim=6144, n_layers=8, n_heads=48,
             n_kv_heads=8, ffn_dim=16384, max_seq=8192, rope_theta=1e6,
             norm_eps=1e-5, remat=True, remat_mode="flash_resid",
             use_ring_attention=False), {}),
    "mistral-7b-v0.3-d20-train4": (
        dict(MISTRAL, num_hidden_layers=20),
        dict(vocab_size=32768, dim=4096, n_layers=20, n_heads=32,
             n_kv_heads=8, ffn_dim=14336, max_seq=4096, rope_theta=1e6,
             norm_eps=1e-5, remat=True, remat_mode="flash_resid",
             use_ring_attention=False), {"remat_mode": "flash_resid"}),
}
# the example family's model, tiny: 1 attention layer of 2
TINY = dict(hidden_size=64, num_hidden_layers=2,
            layer_types=["conv", "full_attention"], num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=96, vocab_size=128,
            norm_eps=1e-5, rope_parameters={"rope_theta": 1e6},
            max_position_embeddings=64)


@pytest.fixture()
def example(monkeypatch):
    """The example family under the name `example`, and every module
    `spec.load_family` hands out while the test runs."""
    real_path, real_load, loaded = spec.family_path, spec.load_family, []
    monkeypatch.setattr(spec, "family_path",
                        lambda n: EXAMPLE if n == "example" else real_path(n))

    def load(name, kind=None):
        loaded.append(real_load(name, kind))
        return loaded[-1]

    monkeypatch.setattr(spec, "load_family", load)
    return loaded


def _calls(loaded) -> set:
    return {c for m in loaded for c in getattr(m, "CALLS", ())}


# ------------------------------------------- (a) the Llama family, moved
@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_the_llama_family_gives_what_the_parent_gave(name):
    import jax.numpy as jnp

    want_model, want_cfg, extra = GOLDEN[name]
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      name + ".json"))
    assert "family" not in cfg            # absent means llama
    fam = spec.config_family(cfg)
    model = fam.published(cfg)
    assert model == want_model
    assert fam.vocab_size(model) == 32768
    max_seq = cfg["engine"]["max_len"] if cfg["kind"] == "serve" \
        else cfg["train"]["seq"]
    got = fam.program_config(model, max_seq=max_seq, **extra)
    fields = dataclasses.asdict(got)
    assert fields.pop("dtype") == jnp.bfloat16
    assert fields == want_cfg
    assert fam.kernel_layers(model, "flash_fwd") == \
        fam.kernel_layers(model, "paged_attn") == want_cfg["n_layers"]
    assert (fam.REFERENCE_GAP_TOL, fam.LOGPROB_RMS_TOL, fam.GRAD_NORM_RTOL,
            fam.LOSS_RTOL) == (0.15, 0.08, 1.5e-2, 2e-3)
    assert fam.reference().__file__ == os.path.join(
        spec.BENCH_DIR, "harness", "refs", "decoder.py")


def test_the_llama_family_refuses_a_head_dim_the_program_cannot_express():
    fam = spec.load_family("llama")
    with pytest.raises(ValueError, match="head_dim"):
        fam.program_config(dict(MISTRAL, num_hidden_layers=2, head_dim=64),
                           max_seq=64)


# --------------------------------- (d) a family that cannot be run stops
def test_an_unknown_family_stops_at_load_cell(tmp_path):
    bench = spec.benchmark_json()
    cfg = spec.load_json(os.path.join(spec.ROOT, bench["configs"][0]["file"]))
    (tmp_path / "benchmarks" / "configs").mkdir(parents=True)
    (tmp_path / "benchmarks" / "traffic").symlink_to(
        os.path.join(spec.BENCH_DIR, "traffic"))
    (tmp_path / bench["configs"][0]["file"]).write_text(
        json.dumps(dict(cfg, family="no-such-family")))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(SystemExit) as e:
        spec.load_cell(bench["workloads"][0]["name"], root=str(tmp_path))
    assert "no-such-family" in str(e.value)
    assert spec.family_path("no-such-family") in str(e.value)


@pytest.mark.parametrize("attr,kind", [
    ("kernel_layers", "serve"), ("reference", "train"),
    ("published", "serve"), ("REFERENCE_GAP_TOL", "serve"),
    ("LOSS_RTOL", "train")])
def test_a_family_file_that_lacks_an_attribute_stops_at_load(
        tmp_path, monkeypatch, attr, kind):
    src = open(EXAMPLE, encoding="utf-8").read()
    path = tmp_path / "lacking.py"
    path.write_text(src + f"\ndel {attr}\n")
    monkeypatch.setattr(spec, "family_path", lambda n: str(path))
    with pytest.raises(SystemExit) as e:
        spec.config_family({"family": "lacking", "kind": kind})
    assert str(path) in str(e.value) and attr in str(e.value)
    # a serve cell needs no train tolerance, and the other way round
    other = "train" if kind == "serve" else "serve"
    if attr.isupper():
        spec.config_family({"family": "lacking", "kind": other})


# ------------------------------------ (b) the second family is the one used
def test_spec_takes_keys_counts_and_tolerances_from_the_named_family(example):
    fam = spec.config_family(dict(TINY, family="example", kind="serve"))
    model = fam.published(dict(TINY, engine={}, kind="serve"))
    assert model == TINY and "rms_norm_eps" not in model
    assert fam.kernel_layers(model, "flash_fwd") == 1     # of 2 layers
    assert fam.REFERENCE_GAP_TOL == 0.25
    # the stand-in cell readers are given in tier-1 names no family
    bare = types.SimpleNamespace(config={"train": {}})
    assert spec.family_of(bare).__name__ == "bench_family_llama"
    assert spec.family_of(types.SimpleNamespace(
        config={"family": "example"})).KEYS == fam.KEYS
    cfg = dict(TINY, family="example", kind="serve")
    fam.rehearsal(cfg)
    assert cfg["layer_types"] == ["conv", "full_attention"] \
        and cfg["hidden_size"] == 64 and cfg["vocab_size"] == 256


def test_the_replica_serves_and_scores_through_the_named_family(example):
    from benchmarks.harness import replica

    srv = replica.BenchLLMServer(
        TINY, family="example", seed=5_000_000_011, max_batch=2, max_len=64,
        page_size=16, kv_pages=9, steps_per_sync=2, paged=True,
        prefix_store={"enabled": False})
    try:
        assert srv._bench_family.KEYS[2] == "layer_types"
        assert (srv._cfg.dim, srv._cfg.n_layers, srv._cfg.norm_eps) == \
            (64, 2, 1e-5)
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        served = srv.engine.generate(prompt, max_new_tokens=4,
                                     _cache_ok=False)["tokens"]
        out = srv.bench_reference([(prompt, [int(t) for t in served])])
    finally:
        srv.shutdown()
    # float32 weights would read 0; bfloat16 near-ties stay small
    assert len(out["gaps"][0]) == 4 and max(out["gaps"][0]) < 0.25
    assert {"program_config", "init_params", "teacher_forced_gaps"} <= \
        _calls(example)


def test_the_train_loop_steps_and_judges_through_the_named_family(
        example, tmp_path):
    import jax

    from benchmarks.harness import train_loop

    n = len(jax.devices())
    rec = train_loop.loop({
        "model": TINY, "family": "example", "seed": 2_147_483_659,
        "train": {"mesh": {"fsdp": n, "tensor": 1}, "batch": 2 * n,
                  "seq": 32, "remat_mode": "flash_resid",
                  "optimizer": "default_optimizer", "total_steps": 100,
                  "loss_every": 2},
        "seconds": 0.2, "chips": n, "trace": False, "dump_trace": False,
        "rehearse": True, "cell": "x", "root": str(tmp_path),
        "distinct_batches": 2, "warm_steps": 1,
        "check": {"sequences": 2 * n, "positions": 16}})
    assert rec["steps"] >= 2 and rec["check"]["positions"] == 16
    assert rec["check"]["program"]["loss"] == pytest.approx(
        rec["check"]["reference"]["loss"], rel=3e-3)
    assert {"program_config", "loss_and_gradient"} <= _calls(example)
    # the family's tolerances are the ones `judge` holds the program to
    prog = {"loss": 1.0, "grad_norm": 1.0, "logprobs": [[0.0]]}
    ref = {"loss": 1.0025, "grad_norm": 1.0, "logprobs": [[0.085]]}
    fam = next(m for m in example if hasattr(m, "CALLS"))
    assert train_loop.judge(prog, ref, fam) == []
    problems = train_loop.judge(prog, ref, spec.load_family("llama"))
    assert len(problems) == 2 and "tolerance 0.08" in problems[0] \
        and "tolerance 0.002" in problems[1]


def test_compile_check_builds_the_named_familys_programs(example, capsys):
    import jax

    from benchmarks.tools import compile_check

    cfg = dict(TINY, family="example", kind="serve",
               engine={"max_batch": 2, "max_len": 64, "page_size": 16,
                       "kv_pages": 9, "steps_per_sync": 2})
    compile_check.serve("example-tiny", cfg,
                        types.SimpleNamespace(devices=jax.devices()))
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    progs = [ln["program"] for ln in lines]
    assert progs[0] == "init_params" and "decode_k2" in progs
    assert lines[0]["params"] == example[0].param_count(TINY)
    assert {"program_config", "init_params"} <= _calls(example)


# ----------------------- (b, c) the rooflines: by kernel name, family count
def _red(by_op):
    return {"window_s": 1.0, "busy_s": 1.0, "start_wall_s": 100.0,
            "t_lo": 0.0, "t_hi": 1.0,
            "devices": [{"by_op": by_op, "modules": [], "gaps": [],
                         "busy_s": 1.0}]}


def _serve_run(cell, model, by_op):
    """One prefill of 300 tokens and one decode window of K=2 steps of
    the same request, wholly inside the traced second."""
    spans = [{"name": "llm.prefill", "t0": 100.1, "t1": 100.2, "tid": 7,
              "attrs": {"prompt_tokens": 300}},
             {"name": "llm.decode_window", "t0": 100.3, "t1": 100.4,
              "tid": 7, "attrs": {"steps": 2}}]
    return {"cell": cell, "model": model, "spans": spans,
            "engine": {"steps_per_sync": 2}, "trace": _red(by_op),
            "device": {"kind": "TPU v5 lite"}}


def _pct(need_flops, need_bytes, kernel_s):
    return 100.0 * peaks.roofline_s(need_flops, need_bytes,
                                    "TPU v5 lite")[0] / kernel_s


@pytest.mark.parametrize("family,layers", [("llama", 10), ("example", 2)])
def test_the_rooflines_read_their_kernels_by_name_and_the_familys_count(
        example, family, layers):
    model = dict(MISTRAL, num_hidden_layers=10) if family == "llama" else \
        dict(TINY, hidden_size=4096, num_attention_heads=32,
             num_key_value_heads=8, num_hidden_layers=10,
             layer_types=["conv"] * 4 + ["full_attention"]
             + ["conv"] * 4 + ["full_attention"])
    cell = types.SimpleNamespace(
        chips=4, family=spec.load_family(family),
        config={"train": {"batch": 4, "seq": 4096}})
    other = "jit__prefill_suffix"
    by_op = [
        ["jit__prefill_fwd_only", "flash_fwd.5 custom-call bf16[1,512]",
         layers, 0.010],
        # a second kernel in the prefill program, a kernel's namesake in
        # another program, a fusion: none is flash_fwd's
        ["jit__prefill_fwd_only", "grouped_mm.7 custom-call bf16[8]", 8, 0.5],
        [other, "flash_fwd.5 custom-call bf16[1,512]", 3, 0.7],
        ["jit__prefill_fwd_only", "fusion.1 fusion bf16[2]", 9, 0.9],
        ["jit__decode_k_paged", "paged_attn.18 custom-call bf16[2]",
         2 * layers, 0.004],
        ["jit__decode_k_paged", "short_conv.3 custom-call bf16[2]", 16, 0.3],
        ["jit_step", "flash_fwd.1 custom-call bf16[2]", layers, 0.02],
        ["jit_step", "transpose_jvp_flash_bwd_dq__.1 custom-call bf16[2]",
         layers, 0.03],
        ["jit_step", "flash_bwd_dkv.1 custom-call bf16[2]", layers, 0.05],
        ["jit_step", "grouped_mm.9 custom-call bf16[2]", 64, 2.0]]
    run = _serve_run(cell, model, by_op)
    f, b = flops.flash_fwd_cost(model, [300])
    assert readers.flash_fwd_roofline(run) == pytest.approx(
        _pct(f * layers, b * layers, 0.010))
    f, b = flops.paged_attn_cost(model, [301, 302])
    assert readers.paged_attn_roofline(run) == pytest.approx(
        _pct(f * layers, b * layers, 0.004))
    run["rec"] = {"trace_steps": 1}
    n = layers / 4                                   # per chip
    f_f, b_f = flops.flash_fwd_cost(model, [4096] * 4)
    f_b, b_b = flops.flash_bwd_cost(model, 4, 4096)
    assert readers.flash_bwd_roofline(run) == pytest.approx(
        _pct((f_f + f_b) * n, (b_f + b_b) * n, 0.10))
    assert timeline.flash_bwd_only_roofline(run) == pytest.approx(
        _pct(f_b * n, b_b * n, 0.08))
    assert flops.train_flops_per_step(cell.family, model, 4, 4096) == \
        pytest.approx(6.0 * cell.family.matmul_params(model) * 4 * 4096
                      + 3 * 4.0 * 4 * (4096 * 4097 // 2) * 32 * 128 * layers)


@pytest.mark.parametrize("name,hit", [
    ("flash_fwd.5 custom-call bf16[1,512,32,128]", True),
    ("jvp_flash_fwd_.1 custom-call bf16[2]", True),
    ("transpose_jvp_flash_fwd__.12 custom-call", True),
    ("flash_fwd.5 fusion bf16[2]", False),
    ("flash_fwd_v2.5 custom-call bf16[2]", False),
    ("flash_bwd_dq.5 custom-call bf16[2]", False),
    ("custom-call.17 custom-call u8[4096]", False),
    ("grouped_mm.7 custom-call bf16[8]", False)])
def test_kernel_op_matches_the_kernels_own_events_only(name, hit):
    import re

    assert bool(re.search(readers.kernel_op("flash_fwd"), name)) == hit


def test_on_the_recorded_trace_a_name_selects_what_the_kind_selected():
    """The recorded trace is of the sandbox's CPU backend and holds no
    Pallas kernel, so its three `dot_general.1` events are given a
    kernel's name and kind (as `trace_reduce.short_name` would write
    them) and one more op the kind of a custom call under another name:
    by kind (the parent's `KERNEL_OP`) both are read, by name the
    kernel's alone, event for event what the kind read before the other
    was there."""
    path = os.path.join(HERE, "data", "recorded.xplane.pb")
    want = spec.load_json(os.path.join(HERE, "data", "recorded.expect.json"))
    tr = trace_reduce.load(path)
    (dev,) = tr["devices"].values()
    dots = [o for o in dev["ops"] if o[0].startswith("dot_general")]
    assert len(dots) == want["op_count"]
    others = sorted({o[0] for o in dev["ops"]} - {dots[0][0]})
    rename = {dots[0][0]: "flash_fwd.5 custom-call f32[64,64]",
              others[0]: "custom-call.3 custom-call u8[16]"}
    dev["ops"] = [(rename.get(n, n), s, d, m) for n, s, d, m in dev["ops"]]
    lo = min(o[1] for o in dev["ops"])
    hi = max(o[1] + o[2] for o in dev["ops"])
    red = {"devices": [trace_reduce.reduce_device(dev, lo, hi)]}
    kind = r" custom-call( |$)"           # KERNEL_OP at the parent
    by_kind = trace_reduce.op_time(red, want["program"], kind)
    by_name = trace_reduce.op_time(red, want["program"],
                                   readers.kernel_op("flash_fwd"))
    assert by_name == (want["op_count"], pytest.approx(want["op_s"],
                                                       rel=1e-9))
    assert by_kind[0] > by_name[0] and by_kind[1] > by_name[1]
    only = [r for r in red["devices"][0]["by_op"] if "flash_fwd" in r[1]]
    assert (sum(r[2] for r in only), sum(r[3] for r in only)) == \
        (by_name[0], pytest.approx(by_name[1]))


@pytest.mark.parametrize("which", ["serve", "train"])
def test_on_rows_recorded_on_the_chip_a_name_reads_the_kernels_alone(which):
    """The custom-call rows of one traced run of a serve cell and of the
    train cell, as the chip gave them (PR 27): the programs also hold
    XLA's own custom calls (buffer allocation, bitcasts), which the
    selection by kind counted as kernel events and which last no time.
    By name each roofline reads its kernels' events and no other; where
    it is named after all of the program's kernels, the self time is what
    the kind read, to a hundred-thousandth."""
    rec = spec.load_json(os.path.join(
        HERE, "data", f"recorded.kernel_rows.{which}.json"))
    red = {"devices": [{"by_op": rec["rows"]}]}
    for r in rec["readers"]:
        kind = trace_reduce.op_time(red, r["program"], r" custom-call( |$)")
        name = trace_reduce.op_time(red, r["program"],
                                    readers.kernel_op(*r["kernels"]))
        assert kind == (r["by_kind"][0], pytest.approx(r["by_kind"][1]))
        assert name == (r["by_name"][0], pytest.approx(r["by_name"][1]))
        assert name[0] < kind[0] and name[1] <= kind[1]
        if r["all_the_programs_kernels"]:
            assert name[1] == pytest.approx(kind[1], rel=1e-5)
        # every event the name selects is one of the named kernels'
        mine = [row for row in rec["rows"]
                if r["program"] in row[0]
                and any(row[1].startswith(k + ".") for k in r["kernels"])]
        assert sum(row[2] for row in mine) == name[0]
