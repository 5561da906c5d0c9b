"""The model family `minicpm_sala` through the harness's seam: the cell
`minicpmsala.longdocs.closed` is found by files and `BENCHMARK.json`
entries alone, the configuration holds the catalog row's numbers but for
what `reduced` names, the family file answers everything the harness asks
(without importing jax at load, and stopping with a sentence on a checkout
whose program cannot serve it), its counts are the program's own at the
published widths and equal the arithmetic the configuration file states,
its cost functions equal a hand count, its judge passes a sound program
and fails the controls (the window dropped, the first block dropped, a
norm skipped, a muP scaling left out, a state in bfloat16, a kernel's mean
over one stride), the cell rehearses on the CPU, and the five metrics the
cell brings read a synthetic run."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import peaks, spec

SALA_CELL = "minicpmsala.longdocs.closed"
SALA_CONFIG = "minicpm-sala-9b-d4"
SALA_NEW_METRICS = ("kernel.bsa_attn_roofline.closed",
                    "kernel.bsa_prefill_roofline.closed",
                    "model.bsa_share_of_decode_pct.closed",
                    "model.bsa_share_of_prefill_pct.closed",
                    "engine.bsa_selected_pct.closed")
SALA_SHARED_METRICS = (
    "kernel.ssm_update_roofline.closed",
    "model.ssm_update_share_of_decode_pct.closed",
    "engine.prefill_walked_factor.closed",
    "model.prefill_share_of_device_pct.closed")


@pytest.fixture(scope="module")
def sala_cell():
    return spec.load_cell(SALA_CELL)


def _sala_config() -> dict:
    return dict(spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                            SALA_CONFIG + ".json")))


def _sala_catalog_row() -> dict | None:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    return next(r for r in rows if r["name"] == "MiniCPM-SALA")


# ------------------------------------------ the cell, by files alone
def test_the_sala_cell_is_found_by_its_files(sala_cell):
    cell = sala_cell
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.kind,
            cell.loop, cell.family_name) == (
        SALA_CONFIG, "longdocs-closed-48", 1, "serve", "closed",
        "minicpm_sala")
    t = cell.traffic
    assert t["prompt_len"]["clip"] == [16385, 32768]
    assert (t["clients"], t["transport"], t["sharing"]["share"],
            t["expect_preemptions"]) == (48, "unary", 0.0, 0)
    reported = {m["name"] for m in cell.per_layer}
    assert {*SALA_NEW_METRICS, *SALA_SHARED_METRICS} <= reported
    # every metric all closed serve cells report is reported here too
    granite = {m["name"] for m in spec.load_cell("granite4h.batch.closed"
                                                 ).per_layer}
    assert granite <= reported
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    eng = cell.config["engine"]
    assert eng["kv_pages"] == eng["max_batch"] * (
        eng["max_len"] // eng["page_size"]) + 1 == 2113
    assert eng["max_len"] == t["prompt_len"]["clip"][1] \
        + t["output_len"]["clip"][1]
    bench = spec.benchmark_json()
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert all(len(e["why"]) <= 200
               for e in bench["workloads"] + bench["configs"])
    for name in SALA_NEW_METRICS:
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        reader = spec.load_reader(name)
        assert (entry["layer"], entry["source"], entry["moves"],
                entry["unit"], entry["better"], entry["workloads"]) == (
            reader.LAYER, reader.SOURCE, reader.MOVES, reader.UNIT,
            reader.BETTER, [SALA_CELL])


def test_the_sala_configuration_holds_the_catalog_row_but_for_the_cut(
        sala_cell):
    row = _sala_catalog_row()
    if row is None:
        pytest.skip("no model-configs catalog on this machine")
    cfg = sala_cell.config
    assert cfg["source"] == row["source_url"]
    (entry,) = [c for c in spec.benchmark_json()["configs"]
                if c["name"] == sala_cell.config_name]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers",
                                                  "mixer_types"]
    assert entry["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v and cfg[k] != v
        else:
            assert cfg[k] == v, k
    # the cut: the FIRST period of the published layers, in its ratio
    assert cfg["num_hidden_layers"] == 4
    assert cfg["mixer_types"] == row["config"]["mixer_types"][:4] == [
        "minicpm4"] + ["lightning-attn"] * 3
    assert row["config"]["mixer_types"].count("minicpm4") * 4 == 32
    assert cfg["assumed"]["sparse_config"] == dict(
        block_size=64, kernel_size=32, kernel_stride=16, window_size=2048,
        init_blocks=1, topk=64, dense_len=8192)
    assert set(cfg["assumed"]) >= {"sparse_config", "scores", "lightning",
                                   "norms_and_gates", "mup", "vocabulary",
                                   "state_dtype", "weights_init"}
    assert "about eight times" in cfg["stands_for"]


# ----------------------------------------------- the family file itself
def test_the_sala_family_loads_without_jax():
    code = ("import sys; from benchmarks.harness import spec; "
            "f = spec.load_family('minicpm_sala', 'serve'); "
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules; "
            "print(f.REFERENCE_GAP_TOL)")
    out = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert float(out.stdout) > 0


def test_a_checkout_without_minicpm_sala_stops_with_a_sentence(monkeypatch,
                                                               tmp_path):
    """The parent of PR 61 with this benchmark laid over it: the family
    file stops in the driver process, before a cluster is started."""
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        spec.load_family("minicpm_sala", "serve")
    assert "ray_tpu.models.minicpm_sala" in str(e.value)
    assert "cannot serve" in str(e.value)


def test_sala_counts_are_the_programs_and_the_files_arithmetic(sala_cell):
    """Abstract shapes: nothing is allocated."""
    import jax

    fam = sala_cell.family
    model = fam.published(sala_cell.config)
    cfg = fam.program_config(model, max_seq=33792)
    shapes = jax.eval_shape(
        lambda: fam.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    sparse_p, light_p, ffn_p = 52_428_800, 83_886_080, 201_326_592
    assert (fam._sparse_params(model), fam._lightning_params(model),
            fam._ffn_params(model)) == (sparse_p, light_p, ffn_p)
    matmul = sparse_p + 3 * light_p + 4 * ffn_p + 73448 * 4096
    assert fam.matmul_params(model) == matmul
    small = 9 * 4096 + 2 * 128 * 4 + 3 * 128
    assert fam.param_count(model) == n == (
        matmul + 73448 * 4096 + small + 24 * 4096) == 1_711_216_000
    assert "1.711 B parameters" in sala_cell.config["reduced_why"]
    assert shapes["lm_head"].shape == (4096, 73472)
    assert (cfg.layer_types, cfg.published_layers, cfg.n_kv_heads,
            cfg.lightning_chunk) == (
        ("minicpm4",) + ("lightning-attn",) * 3, 32, 2, 128)
    sel = cfg.selection
    assert (sel.block, sel.kernel, sel.stride, sel.window, sel.init_blocks,
            sel.topk, sel.dense_len) == (64, 32, 16, 2048, 1, 64, 8192)
    assert abs(cfg.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    assert (cfg.scale_emb, cfg.logits_scale) == (12.0, 16.0)
    assert fam.lane_state_bytes(model) == 2_097_152
    # a decode step of 32 lanes at 24.6 k of context: the weights, the
    # lanes' state in and out, 6,208 selected rows and 1,536 stride rows
    assert fam._selected_rows(model, 24576) == 64 + 64 * 64 + 2048
    assert fam.decode_step_bytes(model) == pytest.approx(
        2.0 * matmul + 2.0 * 32 * 3 * 2_097_152
        + 32 * (6208 * 1024 + 1536 * 512))
    assert 3.4e9 < fam.decode_step_bytes(model) < 3.6e9      # ISSUE 61: ~3.5
    assert [fam.kernel_layers(model, k) for k in (
        "ssm_update", "bsa_index", "bsa_attn", "bsa_prefill",
        "kda_update")] == [3, 1, 1, 1, 0]
    assert fam.vocab_size(model) == 73448
    # the seam's own arithmetic agrees with the family's
    from ray_tpu.models import minicpm_sala
    from ray_tpu.ops import block_sparse_attention as bsa
    spec_ = minicpm_sala.serving_spec(cfg)
    assert spec_.prefill_state_bytes == 3 * 2_097_152 + 1024
    for ctx in (100, 8192, 8193, 10300, 24576, 33792):
        assert bsa.selection_counts(ctx, sel)[1] == fam._selected_rows(
            model, ctx)


@pytest.mark.parametrize("change,match", [
    (dict(attn_use_rope=True), "rotary"),
    (dict(lightning_use_rope=False), "without theirs"),
    (dict(attn_use_output_gate=False), "output gate"),
    (dict(use_output_norm=False), "output norm"),
    (dict(qk_norm=False), "their norm"),
    (dict(tie_word_embeddings=True), "tied head"),
    (dict(lightning_nkv=8), "grouped keys"),
    (dict(mixer_types=["minicpm4", "mamba", "x", "y"]), "does not know")])
def test_sala_program_config_refuses_what_the_program_cannot_express(
        sala_cell, change, match):
    model = sala_cell.family.published(sala_cell.config)
    with pytest.raises(ValueError, match=match):
        sala_cell.family.program_config(dict(model, **change), max_seq=64)


def test_sala_rehearsal_shrinks_to_one_period():
    fam = spec.load_family("minicpm_sala", "serve")
    cfg = _sala_config()
    fam.rehearsal(cfg)
    pc = fam.program_config(fam.published(cfg), max_seq=64)
    assert (pc.dim, pc.layer_types, pc.n_heads, pc.n_kv_heads, pc.dense_len,
            pc.block_size, pc.published_layers) == (
        64, ("minicpm4",) + ("lightning-attn",) * 3, 4, 2, 128, 8, 32)


def test_sala_costs_by_hand(sala_cell):
    fam = sala_cell.family
    model = fam.published(sala_cell.config)
    # one attended row: 32 heads x 128 x (score + weigh) x 2, K and V of
    # 2 kv heads x 128 bf16; a lane-step's q and o, 4,096 bf16 each
    fl, by = fam.bsa_attn_cost(model, 1000.0, 10.0)
    assert (fl, by) == (4.0 * 32 * 128 * 1000, 1024.0 * 1000 + 16384.0 * 10)
    # a prompt of 8,192 + 2 tokens: the dense triangle, then two queries of
    # the first block + 64 blocks + their window's rows
    fl, by = fam.bsa_prefill_cost(model, [8194])
    rows = 8192 * 8193 // 2 + sum(
        64 + 64 * 64 + t + 1 - 64 * ((t - 2047) // 64) for t in (8192, 8193))
    assert fl == 4.0 * 32 * 128 * rows
    assert by == 2.0 * 68 * 128 * 8194
    # at 24,576 tokens the selection halves the causal pairs
    sel, _ = fam.bsa_prefill_cost(model, [24576])
    assert 0.4 < sel / (4.0 * 32 * 128 * 24576 * 24577 // 2) < 0.5
    fl, by = fam.ssm_update_cost(model, 96.0)
    assert fl == 5.0 * 128 * 4096 * 96
    assert by == 96.0 * (2 * 2_097_152 + 2 * 4096 + 4 * 4096 + 8 * 4096)


# --------------------------------------------------- the judge (tiny, CPU)
@pytest.fixture(scope="module")
def sala_judged():
    """A debug-sized model at `--rehearse` sizes (the served tokens
    through the engine: `tests/test_minicpm_sala.py`), and the
    reference's forward under its own selection."""
    import jax
    import numpy as np

    from benchmarks.harness.refs import minicpm_sala as ref

    fam = spec.load_family("minicpm_sala", "serve")
    cfg = _sala_config()
    fam.rehearsal(cfg)
    model = fam.published(cfg)
    pc = fam.program_config(model, max_seq=512)
    params = fam.init_params(jax.random.PRNGKey(5), pc)
    tokens = np.random.default_rng(2).integers(0, 512, 300).tolist()
    _, infos = ref.forward(params, tokens, model)
    return fam, model, params, tokens, infos


def _sala_limits(fam):
    # (what a debug-sized layer adds is 64 channels of a bfloat16 stream
    # whose rounding is a fiftieth of it, where the served layer's is a
    # hundred-and-thirtieth, and 1,376 scored blocks miss by the handful
    # where the served sample's million miss by the thousand: three times
    # the room for those two readings; the controls clear them tenfold)
    return dict(fam.LIMITS, layer_err=3 * fam.LAYER_ERR_TOL,
                missed_share=3 * fam.MISSED_SHARE_MAX)


def test_the_sala_judge_passes_a_sound_program(sala_judged):
    fam, model, params, tokens, infos = sala_judged
    r = fam.selection_readings(params, tokens, model, infos)
    for name, limit in _sala_limits(fam).items():
        assert r[name] < limit, (name, r)
    assert r["blocks_taken"] > 4 * 100      # 172 queries past 128, 2 heads


def _sala_control(name, mp, fam):
    import jax.numpy as jnp

    from ray_tpu.models import minicpm_sala as prog
    from ray_tpu.ops import block_sparse_attention as bsa

    pc = fam.program_config
    forced = bsa.forced_blocks

    def configured(**change):
        mp.setattr(fam, "program_config", lambda m, max_seq, **kw:
                   pc(m, max_seq, **{**change, **kw}))

    if name == "state_in_bfloat16":
        configured(state_dtype=jnp.bfloat16)
    elif name == "window_dropped":
        configured(window_size=8)
    elif name == "first_block_dropped":
        mp.setattr(bsa, "forced_blocks", lambda pos, b, shape: forced(
            pos, b, shape) & (b >= shape.init_blocks))
    elif name == "qk_norm_skipped":
        mp.setattr(prog, "head_norm", lambda x, w, cfg: x)
    elif name == "scale_depth_left_out":
        configured(scale_depth=1.0)
    elif name == "logits_scale_left_out":
        configured(dim_model_base=64)
    elif name == "kernel_of_one_stride":
        index = bsa.bsa_index
        mp.setattr(bsa, "bsa_index", lambda q, m, *a: index(
            q, jnp.concatenate([m[..., :1, :], m[..., :-1, :]], -2), *a))


@pytest.mark.parametrize("name,held_by", [
    ("state_in_bfloat16", "scan_err"), ("window_dropped", "mixer_err"),
    ("first_block_dropped", "mixer_err"),
    ("qk_norm_skipped", "lightning_err"),
    ("scale_depth_left_out", "layer_err"),
    ("logits_scale_left_out", "head_err"),
    ("kernel_of_one_stride", "missed_share")])
def test_the_sala_judge_fails_a_control(sala_judged, monkeypatch, name,
                                        held_by):
    """The controls of ISSUE 61, at `--rehearse` sizes: each fails the
    reading named, whose limit the sound program passes."""
    fam, model, params, tokens, infos = sala_judged
    monkeypatch.setattr(fam, "_PROGRAMS", {})
    _sala_control(name, monkeypatch, fam)
    r = fam.selection_readings(params, tokens, model, infos)
    assert r[held_by] > _sala_limits(fam)[held_by], (held_by, r)


# ------------------------------------------------- rehearsal, on the CPU
@pytest.mark.time_limit(420)
def test_the_sala_cell_rehearses_on_the_cpu():
    """The walk is what is held (the last line's shape), not how many
    requests END inside so short a window."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", SALA_CELL,
         "--seed", "2147483693", "--seconds", "6", "--trace", "0",
         "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=400)
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0   # never passes
    assert last["metrics"]["rehearsal.setup_s"]["value"] > 0
    assert '"a rehearsal is never correct"' in out.stdout


# ------------------------------------------ the metrics the cell brings
def _sala_run(cell, by_op, modules, spans=(), stats=None):
    red = {"window_s": 1.0, "busy_s": 1.0, "start_wall_s": 100.0,
           "t_lo": 0.0, "t_hi": 1.0,
           "devices": [{"by_op": by_op, "modules": modules, "gaps": [],
                        "busy_s": 1.0}]}
    return {"cell": cell, "model": cell.family.published(cell.config),
            "engine": {"steps_per_sync": 8}, "trace": red,
            "spans": list(spans),
            "stats": stats or ({"loop": {}}, {"loop": {}}),
            "device": {"kind": "TPU v5 lite"}}


_SALA_OPS = [
    ["jit__decode_k_paged", "bsa_attn.7 custom-call", 16, 0.02],
    ["jit__decode_k_paged", "ssm_update.3 custom-call", 48, 0.03],
    ["jit__decode_k_paged", "fusion.2", 64, 0.05],
    ["jit__prefill_fwd_only", "bsa_prefill.4 custom-call", 2, 0.08],
    ["jit__prefill_fwd_only", "bsa_index.5 custom-call", 2, 0.03],
    ["jit__decode_k_paged", "bsa_index.8 custom-call", 16, 0.005],
    ["jit__prefill_fwd_only", "fusion.9", 40, 0.3],
    ["jit__scatter", "fusion.3", 2, 0.02]]
_SALA_MODULES = [("jit__prefill_fwd_only(7)", 0.1, 0.2),
                 ("jit__prefill_fwd_only(7)", 0.4, 0.2),
                 ("jit__scatter(9)", 0.65, 0.1),
                 ("jit__decode_k_paged(3)", 0.8, 0.1)]
_SALA_SPANS = [
    {"name": "llm.prefill", "t0": 100.1, "t1": 100.3, "tid": 0,
     "attrs": {"prompt_tokens": 20000}},
    {"name": "llm.prefill", "t0": 100.4, "t1": 100.6, "tid": 1,
     "attrs": {"prompt_tokens": 30000}},
    {"name": "llm.loop.decode_dispatch", "t0": 100.8, "t1": 100.81, "tid": 2,
     "attrs": {"steps": 8, "lanes": 30, "bsa_rows_attended": 8 * 30 * 6208,
               "bsa_rows_context": 8 * 30 * 24000}},
    {"name": "llm.loop.decode_dispatch", "t0": 100.9, "t1": 100.91, "tid": 2,
     "attrs": {"steps": 8, "lanes": 32, "bsa_rows_attended": 8 * 32 * 6208,
               "bsa_rows_context": 8 * 32 * 24000}}]


def test_the_bsa_readers_on_a_synthetic_run(sala_cell, capsys):
    stats = ({"loop": {"bsa_rows_context": 1000, "bsa_rows_attended": 1000}},
             {"loop": {"bsa_rows_context": 1000 + 24000,
                       "bsa_rows_attended": 1000 + 6208}})
    run = _sala_run(sala_cell, _SALA_OPS, _SALA_MODULES, _SALA_SPANS, stats)
    read = {n: spec.load_reader(n).read(run) for n in SALA_NEW_METRICS}
    fam, model = sala_cell.family, run["model"]
    # 16 traced calls of a mean 31 lanes x 6,208 rows
    fl, by = fam.bsa_attn_cost(model, 16 * 31 * 6208.0, 16 * 31.0)
    assert read["kernel.bsa_attn_roofline.closed"] == pytest.approx(
        100 * peaks.roofline_s(fl, by, "TPU v5 lite")[0] / 0.02)
    fl, by = fam.bsa_prefill_cost(model, [20000, 30000])
    assert read["kernel.bsa_prefill_roofline.closed"] == pytest.approx(
        100 * peaks.roofline_s(fl, by, "TPU v5 lite")[0] / 0.08)
    # the scores and the selection beside the attention
    assert read["model.bsa_share_of_decode_pct.closed"] == pytest.approx(
        100 * 0.025 / 0.1)
    # over the prefill programs' time, the scatter's counted
    assert read["model.bsa_share_of_prefill_pct.closed"] == pytest.approx(
        100 * 0.11 / 0.5)
    assert read["engine.bsa_selected_pct.closed"] == pytest.approx(
        100 * 6208 / 24000)
    assert all(0 < v < 100 for v in read.values())
    # the shared state-space readers at a group a head: this family's cost
    lanes = 31.0
    fl, by = fam.ssm_update_cost(model, 48 * lanes)
    run["spans"] = [dict(s, attrs=dict(s["attrs"], ssm_lane_steps=s[
        "attrs"]["lanes"] * 8 * 3)) if "lanes" in s["attrs"] else s
        for s in _SALA_SPANS]
    assert spec.load_reader("kernel.ssm_update_roofline.closed").read(run) \
        == pytest.approx(100 * peaks.roofline_s(fl, by, "TPU v5 lite")[0]
                         / 0.03)
    capsys.readouterr()


@pytest.mark.parametrize("name", SALA_NEW_METRICS)
def test_a_program_without_the_selection_reads_nothing(sala_cell, name):
    """Another family's cell, or this cell on a trace that holds no `bsa`
    event and counters that lack the rows (the parent of PR 61), or an
    untraced run: the reader returns None and does not raise."""
    by_op = [["jit__decode_k_paged", "paged_attn.3 custom-call", 16, 0.05],
             ["jit__prefill_fwd_only", "flash_fwd.4 custom-call", 6, 0.02]]
    modules = [("jit__decode_k_paged(3)", 0.0, 0.2),
               ("jit__prefill_fwd_only(7)", 0.3, 0.2)]
    reader = spec.load_reader(name)
    for cell in (spec.load_cell("granite4h.batch.closed"), sala_cell):
        run = _sala_run(cell, by_op, modules, _SALA_SPANS[:2])
        assert reader.read(run) is None
        assert reader.read(dict(run, trace=None)) is None
