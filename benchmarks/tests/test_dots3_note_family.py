"""The model family `dots3_note` through the harness's seam: the cell
`dots3note.docs.closed` is found by files and `BENCHMARK.json` entries
alone, the configuration holds the catalog row's numbers but for what
`reduced` names, the family file answers everything the harness asks
(without importing jax at load, and stopping with a sentence on a checkout
whose program cannot serve it), its counts are the program's own at the
published widths, its judge passes a sound program and fails the
controls, `--rehearse` walks the cell on the CPU, and the metrics the cell
brings read a synthetic run."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import peaks, spec

CELL = "dots3note.docs.closed"
CONFIG = "dots3-note-prev-ep8"
NEW_METRICS = ("kernel.swa_attn_roofline.closed",
               "model.swa_share_of_decode_pct.closed",
               "engine.swa_attended_pct.closed",
               "kernel.swa_prefill_roofline.closed")
SHARED_METRICS = ("kernel.dsa_attn_roofline.closed",
                  "model.dsa_share_of_decode_pct.closed",
                  "engine.dsa_selected_pct.closed")


@pytest.fixture(scope="module")
def dots_cell():
    return spec.load_cell(CELL)


def _config() -> dict:
    return dict(spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                            CONFIG + ".json")))


def _catalog_row() -> dict | None:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    return next(r for r in rows if r["name"] == "dots3-note-prev")


# ------------------------------------------ the cell, by files alone
def test_the_dots3_cell_is_found_by_its_files(dots_cell):
    cell = dots_cell
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.kind,
            cell.loop, cell.family_name) == (
        CONFIG, "docs-closed-96", 1, "serve", "closed", "dots3_note")
    # the same traffic as the other two docs cells: they differ by model
    for other in ("sarvam105b.docs.closed", "glm53flash.docs.closed"):
        assert spec.load_cell(other).traffic_name in (
            "docs-closed-48", cell.traffic_name)
    assert cell.traffic["prompt_len"]["clip"] == [4097, 8192]
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) | set(SHARED_METRICS) <= reported
    assert {"kernel.moe_gmm_roofline.closed", "engine.lanes_live.closed",
            "model.prefill_share_of_device_pct.closed",
            "setup.program_build_s"} <= reported
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    eng = cell.config["engine"]
    assert eng["kv_pages"] == eng["max_batch"] * (
        eng["max_len"] // eng["page_size"]) + 1
    bench = spec.benchmark_json()
    assert len(bench["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_dots3_configuration_holds_the_catalog_row_but_for_the_cut(
        dots_cell):
    row = _catalog_row()
    if row is None:
        pytest.skip("no model-configs catalog on this machine")
    cfg = dots_cell.config
    assert cfg["source"] == row["source_url"]
    (entry,) = [c for c in spec.benchmark_json()["configs"]
                if c["name"] == dots_cell.config_name]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"]
    assert entry["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v and cfg[k] != v
        else:
            assert cfg[k] == v, k
    # the leading dense layer and one whole period, as published
    assert cfg["layer_types"] == row["config"]["layer_types"][:5]
    assert cfg["n_routed_experts"] * cfg["expert_parallel"]["chips"] == \
        cfg["published"]["n_routed_experts"]
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["vocab_size"] % 128 == 0
    # the reading of a key's meaning comes first
    assert list(cfg["assumed"])[1] == "apply_mla_qkv_lora_rescale"
    assert set(cfg["assumed"]) >= {"window", "selection", "indexer", "gate",
                                   "rope", "index_key_dtype", "not_served"}
    assert "8-way" in cfg["stands_for"] and "11-stage" in cfg["stands_for"]


# ----------------------------------------------- the family file itself
def test_the_dots3_family_loads_without_jax():
    code = ("import sys; from benchmarks.harness import spec; "
            "f = spec.load_family('dots3_note', 'serve'); "
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules; "
            "print(f.REFERENCE_GAP_TOL)")
    out = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert float(out.stdout) > 0


def test_a_checkout_without_dots3_stops_with_a_sentence(monkeypatch,
                                                        tmp_path):
    """The parent of PR 45 with this benchmark laid over it: the family
    file stops in the driver process, before a cluster is started."""
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        spec.load_family("dots3_note", "serve")
    assert "ray_tpu.models.dots3_note" in str(e.value)
    assert "cannot serve" in str(e.value)


def test_dots3_counts_are_the_programs_at_the_published_widths(dots_cell):
    """Abstract shapes: nothing is allocated."""
    import jax

    fam = dots_cell.family
    model = fam.published(dots_cell.config)
    cfg = fam.program_config(model, max_seq=9216)
    shapes = jax.eval_shape(
        lambda: fam.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert fam.param_count(model) == n == 4_087_809_536
    assert (cfg.n_layers, cfg.count("sliding_attention"), cfg.window,
            cfg.ring_rows, cfg.experts_held, cfg.n_experts) == (
        5, 3, 513, 640, (0, 32), 256)
    assert (cfg.full.row_width, cfg.swa.row_width) == (640, 1152)
    assert (cfg.full.rope_theta, cfg.swa.rope_theta) == (8.0e7, 5.0e4)
    # the ISSUE's count at 2 B a parameter, by part
    assert fam._attn_params(model, "") + fam._indexer_params(model) \
        == 144_048_128
    assert fam._attn_params(model, "swa_") == 90_832_896
    # a token multiplies one held expert a routed layer (8 x 32 / 256)
    held = 4 * 32 * 3 * 5120 * 1536
    assert fam.matmul_params(model) == fam._non_expert_matmul_params(
        model) + 4 * 3 * 5120 * 1536
    assert fam.decode_step_bytes(model) == 2.0 * (
        fam._non_expert_matmul_params(model) + held)
    assert (fam.kernel_layers(model, "swa_attn"),
            fam.kernel_layers(model, "dsa_attn"),
            fam.kernel_layers(model, "moe_gmm"),
            fam.kernel_layers(model, "flash_fwd"),
            fam.kernel_layers(model, "mla_attn")) == (3, 2, 4, 3, 0)
    assert fam.vocab_size(model) == 19072 and model["num_experts"] == 32
    # the seam's own arithmetic agrees with the family's
    from ray_tpu.models import dots3_note
    streamed, multiplied = dots3_note.prefill_params(cfg)
    d = 5120
    assert streamed == fam._non_expert_matmul_params(model) - 19072 * d \
        + held
    assert multiplied == fam.matmul_params(model) - 19072 * d
    # the cache beside the weights: two leaves a FULL layer, a ring a
    # window layer (abstract)
    cache = jax.eval_shape(lambda: dots3_note.init_paged_cache(
        cfg, 64, 1153, 512))
    nbytes = lambda t: sum(a.size * a.dtype.itemsize       # noqa: E731
                           for a in jax.tree.leaves(t))
    assert len(cache["latent"]) == len(cache["index"]) == 2
    assert nbytes(cache["latent"]) + nbytes(cache["index"]) \
        == 2 * 1153 * 512 * (1280 + 256)
    assert nbytes(cache["state"]) == 64 * 3 * 640 * 2304


@pytest.mark.parametrize("change,match", [
    (dict(attention_gate_type="none"), "gate"),
    (dict(rope_scaling={"type": "yarn"}), "rotary"),
    (dict(topk_method="greedy"), "router"),
    (dict(num_hidden_layers=6), "layer list"),
    (dict(swa_num_key_value_heads=8), "grouped keys"),
    (dict(tie_word_embeddings=True), "tied head")])
def test_dots3_program_config_refuses_what_the_program_cannot_express(
        dots_cell, change, match):
    model = dots_cell.family.published(dots_cell.config)
    with pytest.raises(ValueError, match=match):
        dots_cell.family.program_config(dict(model, **change), max_seq=64)


def test_dots3_rehearsal_shrinks_both_kinds_of_layer():
    fam = spec.load_family("dots3_note", "serve")
    cfg = _config()
    fam.rehearsal(cfg)
    pc = fam.program_config(fam.published(cfg), max_seq=64)
    assert (pc.dim, pc.n_layers, pc.n_experts, pc.experts_held, pc.window,
            pc.ring_rows) == (64, 4, 8, (0, 4), 9, 128)
    assert set(pc.layer_types) == {"full_attention", "sliding_attention"}


def test_dots3_costs_by_hand(dots_cell):
    fam = dots_cell.family
    model = fam.published(dots_cell.config)
    fl, by = fam.swa_attn_cost(model, rows=513 * 57)
    assert by == 2 * 1088 * 513 * 57
    assert fl == 2.0 * 64 * (1088 + 1024) * 513 * 57
    from ray_tpu.ops import window_attention
    assert window_attention.attn_cost(64, 1088, 1024, 513 * 57) == (fl, by)
    fl, by = fam.dsa_attn_cost(model, rows=2048 * 57)
    assert by == 2 * 576 * 2048 * 57
    assert fl == 2.0 * 128 * (576 + 512) * 2048 * 57
    # the band: a prompt of 8,192 scores 513 rows a query but for the
    # first 512 queries
    fl, by = fam.swa_prefill_cost(model, [8192, 100])
    pairs = 513 * 514 // 2 + (8192 - 513) * 513 + 100 * 101 // 2
    assert fl == 2.0 * pairs * 64 * (256 + 128)
    assert by == 2.0 * 8292 * 64 * (2 * 256 + 2 * 128)


# --------------------------------------------------- the judge (tiny, CPU)
@pytest.fixture(scope="module")
def dots_judged():
    """A debug-sized model served by the program's own prefill and decode
    (not the engine: `tests/test_dots3_note.py` holds that) and judged."""
    import jax
    import numpy as np

    fam = spec.load_family("dots3_note", "serve")
    cfg = _config()
    fam.rehearsal(cfg)
    model = fam.published(cfg)
    pc = fam.program_config(model, max_seq=256)
    params = jax.jit(lambda k: fam.init_params(k, pc))(
        jax.random.PRNGKey(5))
    tokens = np.random.default_rng(2).integers(0, 512, 150).tolist()
    return fam, model, params, tokens


def test_the_dots3_judge_passes_a_sound_program(dots_judged):
    fam, model, params, tokens = dots_judged
    b = fam.block_errors(params, tokens, model)
    assert b["block"][0] < fam.BLOCK_ERR_TOL, b["block"]
    assert b["rows"][0] < fam.ROW_ERR_TOL, b["rows"]
    assert b["select"][0] < fam.SELECT_MISS_TOL, b["select"]
    assert b["edge"][0] < fam.EDGE_TOL, b["edge"]
    kinds = {k for k, *_ in b["by_block"]}
    assert {"full_attention", "sliding_attention", "decode_step", "latent",
            "index", "ring", "ring_step", "ring_other_slots", "own_row",
            "prefill_rows", "ffn", "head", "prefill.8", "prefill.10",
            "decode_step.8", "decode_step.10"} <= kinds


def _dots_control(name, mp, fam):
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models import dots3_note as prog
    from ray_tpu.ops import sparse_attention as dsa

    if name == "gate_left_out":
        mp.setattr(prog, "gated", lambda o, h, lp, cfg, _g=prog.gated: _g(
            o, h, dict(lp, wg=jnp.zeros_like(lp["wg"])), cfg) * 2)
    elif name == "rescale_left_out":
        mp.setattr(prog, "lora_scales", lambda k, cfg: (1.0, 1.0))
    elif name in ("window_512", "window_514"):
        pc = fam.program_config
        delta = -1 if name == "window_512" else 1
        mp.setattr(fam, "program_config", lambda m, max_seq, **kw:
                   (lambda c: dataclasses.replace(c, window=c.window + delta)
                    )(pc(m, max_seq, **kw)))
    elif name == "last_rows_selected":
        mp.setattr(dsa, "index_scores", lambda q, w, kbar: jnp.broadcast_to(
            jnp.arange(kbar.shape[-2], dtype=jnp.float32),
            q.shape[:-2] + (kbar.shape[-2],)))
    elif name == "fp8_latents":
        rows = prog.mla_moe.cache_row
        mp.setattr(prog.mla_moe, "cache_row", lambda c, k_r, k: rows(
            c.astype(jnp.float8_e4m3fn).astype(c.dtype), k_r, k))


@pytest.mark.parametrize("name,held_by", [
    ("gate_left_out", "block"), ("rescale_left_out", "block"),
    ("window_512", "edge"), ("window_514", "edge"),
    ("last_rows_selected", "select"), ("fp8_latents", "rows")])
def test_the_dots3_judge_fails_a_control(dots_judged, monkeypatch, name,
                                         held_by):
    fam, model, params, tokens = dots_judged
    limits = {"block": fam.BLOCK_ERR_TOL, "rows": fam.ROW_ERR_TOL,
              "select": fam.SELECT_MISS_TOL, "edge": fam.EDGE_TOL}
    monkeypatch.setattr(fam, "_BLOCKS", {})
    _dots_control(name, monkeypatch, fam)
    b = fam.block_errors(params, tokens, model)
    assert b[held_by][0] > limits[held_by], (held_by, b[held_by])


# ------------------------------------------------- rehearsal, on the CPU
@pytest.mark.time_limit(420)
def test_the_dots3_cell_rehearses_on_the_cpu():
    """The walk is what is held (the last line's shape), not how many
    requests END inside so short a window nor which of the replica's own
    lines were forwarded before the teardown: both are the machine's
    load."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "6", "--trace", "0", "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=400)
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0   # never passes
    assert last["metrics"]["rehearsal.setup_s"]["value"] > 0
    assert '"a rehearsal is never correct"' in out.stdout


# ------------------------------------------ the metrics the cell brings
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
    return [{"name": "llm.loop.decode_dispatch", "t0": t, "t1": t + 0.002,
             "attrs": {"lanes": lanes, "steps": 8,
                       "dsa_rows_selected": 8 * lanes * 2 * 2048,
                       "dsa_rows_context": 8 * lanes * 2 * 6500,
                       "swa_rows_attended": 8 * lanes * 3 * 513,
                       "swa_rows_context": 8 * lanes * 3 * 6500,
                       "swa_lane_steps": 8 * lanes * 3}}
            for t in times]


def _prefills(times, tokens=6000):
    return [{"name": "llm.prefill", "t0": t, "t1": t + 0.1, "tid": i,
             "attrs": {"prompt_tokens": tokens}}
            for i, t in enumerate(times)]


def _counters(windows, lanes):
    return {"decode_steps": windows * 8,
            "dsa_rows_selected": windows * 8 * lanes * 2 * 2048,
            "dsa_rows_context": windows * 8 * lanes * 2 * 6500,
            "swa_rows_attended": windows * 8 * lanes * 3 * 513,
            "swa_rows_context": windows * 8 * lanes * 3 * 6500}


def test_the_dots3_readers_on_a_synthetic_run(dots_cell, capsys):
    cell = dots_cell
    by_op = [
        ["jit__decode_k_paged", "swa_attn.7 custom-call bf16[64,64,1024]",
         96, 0.06],
        ["jit__decode_k_paged", "dsa_attn.3 custom-call bf16[64,128,512]",
         64, 0.16],
        ["jit__decode_k_paged", "moe_gmm.5 custom-call", 256, 0.2],
        ["jit__decode_k_paged", "fusion.12", 64, 0.38],
        ["jit__prefill_fwd_only", "flash_fwd.4 custom-call", 6, 0.02],
        ["jit__prefill_fwd_only", "dsa_prefill.2 custom-call", 4, 0.05],
    ]
    modules = [("jit__decode_k_paged(3)", 0.2 * i, 0.2) for i in range(4)]
    inside = [100.0 + 0.2 * i for i in range(4)]
    run = _run(cell, by_op, modules, _counters(10, 52), _counters(110, 52),
               _dispatches(inside, 64) + _dispatches([99.5, 101.5], 30)
               + _prefills([100.1, 100.5]))
    names = NEW_METRICS + SHARED_METRICS
    read = {n: spec.load_reader(n).read(run) for n in names}
    assert read["model.swa_share_of_decode_pct.closed"] == \
        pytest.approx(100 * 0.06 / 0.8)
    assert read["model.dsa_share_of_decode_pct.closed"] == \
        pytest.approx(100 * 0.16 / 0.8)
    assert read["engine.swa_attended_pct.closed"] == \
        pytest.approx(100 * 513 / 6500)
    assert read["engine.dsa_selected_pct.closed"] == \
        pytest.approx(100 * 2048 / 6500)
    fl, by = cell.family.swa_attn_cost(run["model"], 96 * 64 * 513)
    assert read["kernel.swa_attn_roofline.closed"] == pytest.approx(
        100 * peaks.roofline_s(fl, by, "TPU v5 lite")[0] / 0.06)
    fl, by = cell.family.dsa_attn_cost(run["model"], 64 * 64 * 2048)
    assert read["kernel.dsa_attn_roofline.closed"] == pytest.approx(
        100 * peaks.roofline_s(fl, by, "TPU v5 lite")[0] / 0.16)
    fl, by = cell.family.swa_prefill_cost(run["model"], [6000, 6000])
    assert read["kernel.swa_prefill_roofline.closed"] == pytest.approx(
        100 * peaks.roofline_s(3 * fl, 3 * by, "TPU v5 lite")[0] / 0.02)
    assert all(0 < read[n] < 100 for n in names)
    capsys.readouterr()


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_window_layers_reads_nothing(dots_cell, name):
    """The parent's program under this benchmark, or another family's
    cell: no `swa_*` counter, no `swa_attn` event, no cost function; the
    reader returns None and does not raise."""
    by_op = [["jit__decode_k_paged", "paged_attn.3 custom-call", 16, 0.05],
             ["jit__prefill_fwd_only", "flash_fwd.4 custom-call", 6, 0.02]]
    modules = [("jit__decode_k_paged(3)", 0.0, 0.2)]
    spans = _prefills([100.1])
    other = _run(spec.load_cell("sarvam105b.docs.closed"), by_op, modules,
                 {"decode_steps": 1}, {"decode_steps": 9}, spans)
    assert spec.load_reader(name).read(other) is None
    assert spec.load_reader(name).read(dict(other, trace=None)) is None
    if name != "kernel.swa_prefill_roofline.closed":
        # this family's cell on a program that lacks the kernel
        mine = _run(dots_cell, by_op, modules, {"decode_steps": 1},
                    {"decode_steps": 9}, spans)
        assert spec.load_reader(name).read(mine) is None
