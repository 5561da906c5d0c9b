"""The model family `ssm_hybrid` through the seam PR 27 built: the cell
`granite4h.batch.closed` is found by files and `BENCHMARK.json` entries
alone, the family file answers everything the harness asks (without
importing jax at load, and stopping with a sentence on a checkout whose
program cannot serve it), its counts are the program's own at the
published size, the replica serves and scores through it, its judge
passes a sound program and fails the controls, `--rehearse` walks the
cell on the CPU, and the two metrics the cell brings read a synthetic
run."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import peaks, spec

CELL = "granite4h.batch.closed"
TINY = dict(
    attention_bias=False, attention_multiplier=0.25, embedding_multiplier=12,
    hidden_act="silu", hidden_size=64, intermediate_size=96,
    layer_types=["mamba", "mamba", "attention", "mamba"],
    logits_scaling=8, mamba_chunk_size=8, mamba_conv_bias=True,
    mamba_d_conv=4, mamba_d_head=16, mamba_d_state=16, mamba_expand=2,
    mamba_n_groups=1, mamba_n_heads=8, mamba_proj_bias=False,
    max_position_embeddings=64, normalization_function="rmsnorm",
    num_attention_heads=4, num_experts_per_tok=0, num_hidden_layers=4,
    num_key_value_heads=2, num_local_experts=0,
    position_embedding_type="nope", residual_multiplier=0.22,
    rms_norm_eps=1e-5, shared_intermediate_size=96,
    tie_word_embeddings=True, vocab_size=256)


@pytest.fixture(scope="module")
def granite_cell():
    return spec.load_cell(CELL)


def _catalog_row() -> dict | None:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    return next(r for r in rows if r["name"] == "granite-4.0-h-micro")


# ------------------------------------------ the cell, by files alone
def test_the_granite_cell_is_found_by_its_files(granite_cell):
    c = granite_cell
    assert (c.config_name, c.traffic_name, c.chips, c.kind, c.loop,
            c.family_name) == (
        "granite-4.0-h-micro", "batch-closed-96", 1, "serve", "closed",
        "ssm_hybrid")
    cfg = granite_cell.config
    assert cfg["reduced"] == [] and cfg["published"] == {}
    assert {"source", "assumed", "stands_for"} <= set(cfg)
    assert {"state_dtype", "weights_init", "head_dim"} <= set(cfg["assumed"])
    model = granite_cell.family.published(cfg)
    assert model["layer_types"] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    assert (model["hidden_size"], model["mamba_n_heads"],
            model["mamba_d_head"], model["mamba_d_state"],
            model["shared_intermediate_size"], model["vocab_size"]) == (
        2048, 64, 64, 128, 8192, 100352)
    assert cfg["engine"] == {"max_batch": 64, "max_len": 2048,
                             "page_size": 512, "kv_pages": 257,
                             "steps_per_sync": 8}
    assert {m["name"] for m in granite_cell.end_to_end} == {
        "serve_tok_s", "setup_s"}
    assert {m["name"] for m in granite_cell.per_layer} >= {
        "kernel.ssm_update_roofline.closed",
        "model.ssm_update_share_of_decode_pct.closed",
        "model.decode_step_ms.closed", "engine.lanes_live.closed",
        "model.prefill_share_of_device_pct.closed", "setup.warmup_s"}
    t = granite_cell.traffic
    assert t["clients"] == 1.5 * cfg["engine"]["max_batch"]


def test_the_configuration_holds_every_number_of_the_catalog_row(
        granite_cell):
    row = _catalog_row()
    if row is None:
        pytest.skip("no model-configs catalog on this machine")
    assert granite_cell.config["source"] == row["source_url"]
    assert {k: granite_cell.config[k] for k in row["config"]} == row["config"]


# ----------------------------------------------- the family file itself
def test_the_ssm_hybrid_family_loads_without_jax():
    code = ("import sys; from benchmarks.harness import spec; "
            "f = spec.load_family('ssm_hybrid', 'serve'); "
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules; "
            "print(f.REFERENCE_GAP_TOL)")
    out = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert float(out.stdout) > 0


def test_a_checkout_without_the_state_space_model_stops_with_a_sentence(
        monkeypatch, tmp_path):
    """The parent of PR 39 with this benchmark laid over it: the family
    file stops in the driver process, before a cluster is started."""
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        spec.load_family("ssm_hybrid", "serve")
    assert "ray_tpu.models.ssm_hybrid" in str(e.value)
    assert "cannot serve" in str(e.value)


def test_granite_counts_are_the_programs_at_the_published_size(granite_cell):
    """Abstract shapes: nothing is allocated."""
    import jax

    fam = granite_cell.family
    model = fam.published(granite_cell.config)
    cfg = fam.program_config(model, max_seq=2048)
    shapes = jax.eval_shape(
        lambda: fam.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert fam.param_count(model) == n == 3_191_396_096
    assert fam.matmul_params(model) == n - 36 * (
        5 * 4352 + 3 * 64 + 4096) - 81 * 2048
    lane = 36 * 128 * 4096 * 4
    assert fam.lane_state_bytes(model) * 36 == lane == 75_497_472
    assert fam.decode_step_bytes(model, lanes=55) == \
        2 * fam.matmul_params(model) + 2 * 55 * lane
    assert (fam.kernel_layers(model, "ssm_update"),
            fam.kernel_layers(model, "flash_fwd"),
            fam.kernel_layers(model, "paged_attn")) == (36, 4, 4)
    assert fam.vocab_size(model) == 100352
    assert (cfg.head_dim, cfg.n_layers, cfg.inner, cfg.conv_dim,
            cfg.attn_scale, cfg.logits_scale) == (64, 40, 4096, 4352,
                                                  1 / 64, 8.0)
    assert [n for _, n, _ in cfg.runs()] == [5, 1, 9, 1, 9, 1, 9, 1, 4]


@pytest.mark.parametrize("change,match", [
    (dict(mamba_n_groups=2), "group"),
    (dict(position_embedding_type="rope"), "position embedding"),
    (dict(num_local_experts=8), "routed"),
    (dict(num_hidden_layers=39), "layer_types"),
    (dict(mamba_conv_bias=False), "convolution without bias")])
def test_ssm_hybrid_program_config_refuses_what_the_program_cannot_express(
        granite_cell, change, match):
    model = granite_cell.family.published(granite_cell.config)
    with pytest.raises(ValueError, match=match):
        granite_cell.family.program_config(dict(model, **change), max_seq=64)


def test_ssm_hybrid_rehearsal_shrinks_both_kinds_of_layer():
    fam = spec.load_family("ssm_hybrid", "serve")
    cfg = dict(spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "granite-4.0-h-micro.json")))
    fam.rehearsal(cfg)
    pc = fam.program_config(fam.published(cfg), max_seq=64)
    assert (pc.dim, pc.n_layers, pc.inner) == (64, 8, 128)
    assert set(pc.layer_types) == {"mamba", "attention"}


def test_ssm_update_cost_by_hand(granite_cell):
    model = granite_cell.family.published(granite_cell.config)
    fl, by = granite_cell.family.ssm_update_cost(model, lane_steps=55 * 36)
    one = 2 * 128 * 4096 * 4 + 2 * 4096 + 4 * 128 + 4 * 64 + 4 * 4096
    assert by == one * 55 * 36 and one == 4_219_648
    assert fl == 5 * 128 * 4096 * 55 * 36
    # memory-bound by far: the state's bytes are the need
    assert by / 819e9 > 30 * fl / 197e12


# --------------------------------------------- served and scored (tiny)
def test_the_replica_serves_and_scores_through_the_ssm_hybrid_family():
    from benchmarks.harness import replica

    srv = replica.BenchLLMServer(
        TINY, family="ssm_hybrid", seed=5_000_000_011, max_batch=2,
        max_len=64, page_size=16, kv_pages=9, steps_per_sync=2, paged=True)
    try:
        assert type(srv._cfg).__name__ == "SsmHybridConfig"
        st = srv.engine.stats()
        assert st["lane_state"]["prefix_cache"] == "off: lane state"
        assert set(st["lane_state"]["by_kind"]) == {"conv", "ssm"}
        assert srv._prefix_client is None
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        served = srv.engine.generate(prompt, max_new_tokens=6,
                                     _cache_ok=False)["tokens"]
        out = srv.bench_reference([(prompt, [int(t) for t in served])])
        loop = srv.engine.stats()["loop"]
    finally:
        srv.shutdown()
    assert len(out["gaps"][0]) == 6
    assert max(out["gaps"][0]) < srv._bench_family.REFERENCE_GAP_TOL
    assert loop["ssm_lane_steps"] > 0 and loop["prefill_scan_chunks"] > 0


# ------------------- the judge, as `serve_cell._check_outputs` asks it
# bfloat16 at an eighth of the widths: what the judge does on the chip,
# through the engine, and the rule `serve_cell` applies to its answer
JUDGED = dict(TINY, hidden_size=256, num_attention_heads=4,
              num_key_value_heads=2, intermediate_size=512,
              shared_intermediate_size=512, mamba_n_heads=8,
              mamba_d_head=64, mamba_d_state=32, mamba_chunk_size=32,
              num_hidden_layers=5, vocab_size=1024,
              layer_types=["mamba", "mamba", "attention", "mamba", "mamba"])


def _state_bf16(prog, ssm):
    """The lane's state kept in bfloat16: rounded where the prefill hands
    it over and after every decode step."""
    import jax.numpy as jnp

    prefill, update = prog.mamba_prefill, ssm.ssm_update

    def rounded(a):
        return a.astype(jnp.bfloat16).astype(a.dtype)

    def handed(x, lp, cfg, lens):
        d, rows, st = prefill(x, lp, cfg, lens)
        return d, rows, rounded(st)

    def stepped(*a, **kw):
        new, y = update(*a, **kw)
        return rounded(new), y
    return {(prog, "mamba_prefill"): handed, (ssm, "ssm_update"): stepped}


def _dt_unmasked(prog, ssm):
    import jax.numpy as jnp

    real = prog.scan_inputs

    def unmasked(h, lp, cfg, lens):
        z, x, _, B, C, rows = real(h, lp, cfg, lens)
        full = jnp.full_like(lens, h.shape[1])
        return z, x, real(h, lp, cfg, full)[2], B, C, rows
    return {(prog, "scan_inputs"): unmasked}


def _mamba_layer_skipped(prog, ssm):
    import jax.numpy as jnp

    real = prog._gate_out
    return {(prog, "_gate_out"): lambda y, z, lp, cfg:
            jnp.zeros_like(real(y, z, lp, cfg))}


def _in_proj_fp8(prog, ssm):
    import jax
    import jax.numpy as jnp

    real = prog._in_proj

    def fp8(a):
        return jax.lax.optimization_barrier(
            a.astype(jnp.float8_e4m3fn)).astype(a.dtype)
    return {(prog, "_in_proj"): lambda h, lp, cfg: real(
        h, dict(lp, in_zx=fp8(lp["in_zx"]), in_dt=fp8(lp["in_dt"])), cfg)}


def _scale_one_eighth(prog, ssm):
    """Scores at half the configuration's scale (the published model:
    head_dim**-0.5 = 1/8 where `attention_multiplier` says 1/64)."""
    real = prog.attention
    return {(prog, "attention"): lambda q, k, v, **kw: real(
        q, k, v, **dict(kw, sm_scale=0.125))}


def _state_scattered_to_the_next_lane(prog, ssm):
    """A fault of the ENGINE's path that none of the judge's own programs
    runs: an admitted row's pages go where they belong and its state into
    the lane beside its own, so a request decodes from what the lane held
    before (another request's state, or none).  Only the served tokens
    can show it."""
    real = prog.serve_scatter

    def shifted(cache, ks, vs, state, page_ids, rows, slots, true_lens,
                aligned=True):
        args = (ks, vs, state, page_ids, rows)
        out = real(cache, *args, slots, true_lens, aligned=aligned)
        beside = (slots + 1) % cache["pos"].shape[0]
        return dict(out, state=real(cache, *args, beside, true_lens,
                                    aligned=aligned)["state"])
    return {(prog, "serve_scatter"): shifted}


def _idle_lanes_stepped(prog, ssm):
    """The one-step kernel's work list names EVERY lane: a lane that holds
    no request is read, decayed and written."""
    import jax.numpy as jnp

    real = ssm.live_lanes
    return {(ssm, "live_lanes"): lambda live: real(jnp.ones_like(live))}


@pytest.fixture(scope="module")
def ssm_judged():
    """Serve three prompts of the sample's length through the engine
    under a patch of the program, and judge them as the replica does:
    -> (family, worst value over the requests, gaps, printed readings)."""
    import contextlib
    import io

    import jax
    import numpy as np

    from ray_tpu.models import ssm_hybrid as prog
    from ray_tpu.ops import ssm
    from ray_tpu.serve.llm import LLMEngine

    fam = spec.load_family("ssm_hybrid", "serve")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 1024, 96).tolist() for _ in range(3)]

    def run(patch):
        cfg = fam.program_config(JUDGED, max_seq=256)
        params = fam.init_params(jax.random.PRNGKey(3), cfg)
        saved = {k: getattr(*k) for k in patch}
        for (mod, name), fn in patch.items():
            setattr(mod, name, fn)
        fam._BLOCKS.clear()
        fam.Judge._seen.clear()
        try:
            eng = LLMEngine(cfg, params, seed=0, max_batch=4, max_len=256,
                            page_size=32, kv_pages=33, steps_per_sync=4,
                            paged=True)
            eng.start()
            try:
                futs = [eng.submit(p, max_new_tokens=24) for p in prompts]
                served = [[int(t) for t in f.result(timeout=600)["tokens"]]
                          for f in futs]
            finally:
                eng.stop()
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                gaps = [fam.reference().teacher_forced_gaps(
                    params, p, t, JUDGED) for p, t in zip(prompts, served)]
        finally:
            for (mod, name), fn in saved.items():
                setattr(mod, name, fn)
            fam._BLOCKS.clear()
            fam.Judge._seen.clear()
        said = [json.loads(line) for line in out.getvalue().splitlines()]
        return fam, max(max(g) for g in gaps), gaps, said

    memo: dict = {}
    return lambda name, make=None: memo.setdefault(
        name, run(make(prog, ssm) if make else {}))


def test_the_ssm_hybrid_judge_passes_a_sound_program(ssm_judged):
    fam, worst, gaps, said = ssm_judged("sound")
    assert worst <= fam.REFERENCE_GAP_TOL
    assert all(len(g) == 24 for g in gaps)
    assert all(max(g) > 0.0 for g in gaps)
    for line in said:
        assert line["worst_block_err"] < fam.BLOCK_ERR_TOL / 1.5
        assert line["worst_state_err"] < fam.STATE_ERR_TOL / 3
        assert line["worst_state_from_x_err"] < fam.STATE_FROM_X_TOL / 1.5
        assert line["worst_token_gap"] < fam.REFERENCE_GAP_TOL
        # embed, head, and per layer: the mixer, the MLP, and for a
        # Mamba layer its convolution rows, a decode step and two states
        assert {b[0]: b[1] for b in line["by_block"]} == {
            "embed": 1, "head": 1, "mamba": 4, "attention": 1, "mlp": 5,
            "conv_rows": 4, "decode_step": 4, "prefill": 4, "decode": 4,
            "idle_lanes": 4, "prefill_from_x": 4, "decode_from_x": 4}


@pytest.mark.parametrize("make,held_by", [
    (_state_bf16, "state_err"), (_dt_unmasked, "state_err"),
    (_mamba_layer_skipped, "block_err"), (_in_proj_fp8, "block_err"),
    (_scale_one_eighth, "block_err"),
    (_state_scattered_to_the_next_lane, "token_gap"),
    (_idle_lanes_stepped, "state_err")],
    ids=lambda f: f.__name__.strip("_") if callable(f) else f)
def test_the_ssm_hybrid_judge_fails_a_control(ssm_judged, make, held_by):
    fam, worst, _, said = ssm_judged(make.__name__, make)
    assert not worst <= fam.REFERENCE_GAP_TOL
    limit = {"token_gap": ("worst_token_gap", fam.REFERENCE_GAP_TOL),
             "state_err": ("worst_state_err", fam.STATE_ERR_TOL),
             "block_err": ("worst_block_err", fam.BLOCK_ERR_TOL)}[held_by]
    for line in said:
        assert line[limit[0]] > 1.5 * limit[1]


# ------------------------------------------------- rehearsal, on the CPU
@pytest.mark.time_limit(420)
def test_the_granite_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "8", "--trace", "0", "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=400)
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0   # never passes
    # (how many requests END inside so short a window is the machine's
    # load, not the cell's: the walk is what is held)
    assert last["metrics"]["rehearsal.setup_s"]["value"] > 0
    # the replica's own lines reach the run's output through its log
    assert "ssm_hybrid.judge" in out.stdout + out.stderr
    assert '"a rehearsal is never correct"' in out.stdout


# ------------------------------------------ the two metrics it brings
def _run(cell, by_op, modules, s0, s1, spans=()):
    red = {"window_s": 1.0, "busy_s": 1.0, "start_wall_s": 100.0,
           "t_lo": 0.0, "t_hi": 1.0,
           "devices": [{"by_op": by_op, "modules": modules, "gaps": [],
                        "busy_s": 1.0}]}
    return {"cell": cell, "model": cell.family.published(cell.config),
            "engine": {"steps_per_sync": 8}, "trace": red,
            "spans": list(spans), "stats": ({"loop": s0}, {"loop": s1}),
            "device": {"kind": "TPU v5 lite"}}


def _dispatches(times, lanes):
    """`llm.loop.decode_dispatch` spans as the engine records them for a
    state-space model: a window of 8 steps over `lanes` live lanes."""
    return [{"name": "llm.loop.decode_dispatch", "t0": t, "t1": t + 0.002,
             "attrs": {"lanes": lanes, "steps": 8,
                       "ssm_lane_steps": 8 * lanes * 36}} for t in times]


def _counters(windows, lanes):
    return {"decode_steps": windows * 8,
            "ssm_lane_steps": windows * 8 * lanes * 36}


def test_the_two_ssm_readers_on_a_synthetic_run(granite_cell, capsys):
    by_op = [
        ["jit__decode_k_paged", "ssm_update.20 custom-call f32[36,64,128,4096]",
         160, 0.30],
        ["jit__decode_k_paged", "ssm_update.21 custom-call f32[36,64,128,4096]",
         128, 0.20],
        ["jit__decode_k_paged", "paged_attn.3 custom-call", 16, 0.05],
        ["jit__decode_k_paged", "fusion.12", 64, 0.25],
    ]
    # 4 decode events of 0.2 s in the traced stretch (wall 100..101), 64
    # lanes live in each of its windows; the counters run on through the
    # drain, where the lanes empty (52 a step over all), and are not read
    modules = [("jit__decode_k_paged(3)", 0.2 * i, 0.2) for i in range(4)]
    inside = [100.0 + 0.2 * i for i in range(4)]
    run = _run(granite_cell, by_op, modules, _counters(10, 52),
               _counters(110, 52),
               _dispatches(inside, 64) + _dispatches([99.5, 101.5], 30))
    share = spec.load_reader(
        "model.ssm_update_share_of_decode_pct.closed").read(run)
    assert share == pytest.approx(100 * 0.5 / 0.8)
    roof = spec.load_reader("kernel.ssm_update_roofline.closed").read(run)
    # 288 traced calls of the kernel (8 x 36 of them a whole window)
    fl, by = granite_cell.family.ssm_update_cost(run["model"], 288 * 64)
    want = 100 * peaks.roofline_s(fl, by, "TPU v5 lite")[0] / 0.5
    assert roof == pytest.approx(want)
    assert 0 < roof < 100
    assert '"bound": "memory"' in capsys.readouterr().out
    # idle lanes are no work: with 20 live lanes the need falls with them
    few = _run(granite_cell, by_op, modules, _counters(10, 52),
               _counters(110, 52), _dispatches(inside, 20))
    assert spec.load_reader("kernel.ssm_update_roofline.closed").read(few) \
        == pytest.approx(roof * 20 / 64)
    # no dispatch span in the stretch (tracing's spans lost): nothing read
    none = _run(granite_cell, by_op, modules, _counters(10, 52),
                _counters(110, 52), _dispatches([99.5], 64))
    assert spec.load_reader("kernel.ssm_update_roofline.closed").read(none) \
        is None


@pytest.mark.parametrize("name", [
    "kernel.ssm_update_roofline.closed",
    "model.ssm_update_share_of_decode_pct.closed"])
def test_a_program_without_the_ssm_counters_or_kernel_reads_nothing(
        granite_cell, name):
    """The parent's program under this benchmark: no `ssm_*` counter, no
    `ssm_update` event; the reader returns None and does not raise."""
    by_op = [["jit__decode_k_paged", "paged_attn.3 custom-call", 16, 0.05]]
    modules = [("jit__decode_k_paged(3)", 0.0, 0.2)]
    run = _run(granite_cell, by_op, modules, {"decode_steps": 1},
               {"decode_steps": 9})
    assert spec.load_reader(name).read(run) is None
    assert spec.load_reader(name).read(dict(run, trace=None)) is None
    lfm2 = spec.load_cell("lfm2moe.batch.closed")
    other = _run(lfm2, by_op, modules, _counters(1, 55), _counters(9, 55))
    assert spec.load_reader(name).read(other) is None
