"""The model family `nemotron_h` through the harness's seam: the cell
`nemotron3super.docs.closed` is found by files and `BENCHMARK.json`
entries alone, the configuration holds the catalog row's numbers but for
what `reduced` names, the family file answers everything the harness asks
(without importing jax at load, and stopping with a sentence on a checkout
whose program cannot serve it), its counts are the program's own at the
published widths and equal the arithmetic the configuration file states,
its cost functions equal a hand count, its judge passes a sound program
and fails the three controls, and the metric the cell brings reads a
synthetic run."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import spec

CELL = "nemotron3super.docs.closed"
CONFIG = "nemotron-3-super-120b-a12b-ep4"
NEW_METRIC = "engine.moe_rows_per_expert_hit.closed"
SHARED_METRICS = ("kernel.ssm_update_roofline.closed",
                  "model.ssm_update_share_of_decode_pct.closed",
                  "kernel.moe_gmm_roofline.closed",
                  "model.moe_gmm_share_of_decode_pct.closed",
                  "engine.moe_experts_hit_pct.closed",
                  "model.prefill_share_of_device_pct.closed")


@pytest.fixture(scope="module")
def cell():
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
    return next(r for r in rows
                if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")


# ------------------------------------------ the cell, by files alone
def test_the_cell_is_found_by_its_files(cell):
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.kind,
            cell.loop, cell.family_name) == (
        CONFIG, "docs-closed-96", 1, "serve", "closed", "nemotron_h")
    assert cell.traffic["prompt_len"]["clip"] == [4097, 8192]
    reported = {m["name"] for m in cell.per_layer}
    assert {NEW_METRIC, *SHARED_METRICS} <= reported
    assert {"engine.lanes_live.closed", "model.decode_step_ms.closed",
            "setup.program_build_s", "setup.warmup_s"} <= reported
    # every metric all closed serve cells report is reported here too
    dots = {m["name"] for m in spec.load_cell("dots3note.docs.closed"
                                              ).per_layer}
    assert {n for n in dots if not any(k in n for k in ("dsa", "swa"))} \
        <= reported
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    eng = cell.config["engine"]
    assert eng["kv_pages"] == eng["max_batch"] * (
        eng["max_len"] // eng["page_size"]) + 1
    bench = spec.benchmark_json()
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NEW_METRIC]
    reader = spec.load_reader(NEW_METRIC)
    assert (entry["layer"], entry["source"], entry["moves"], entry["unit"],
            entry["better"], entry["workloads"]) == (
        reader.LAYER, reader.SOURCE, reader.MOVES, reader.UNIT,
        reader.BETTER, [CELL])


def test_the_configuration_holds_the_catalog_row_but_for_the_cut(cell):
    row = _catalog_row()
    if row is None:
        pytest.skip("no model-configs catalog on this machine")
    cfg = cell.config
    assert cfg["source"] == row["source_url"]
    (entry,) = [c for c in spec.benchmark_json()["configs"]
                if c["name"] == cell.config_name]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert entry["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v and cfg[k] != v
        else:
            assert cfg[k] == v, k
    # the cut: the FIRST period of the published pattern, in its ratio
    assert cfg["hybrid_override_pattern"] == \
        row["config"]["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert cfg["expert_parallel"] == {"chips": 4, "rank": 0}
    assert cfg["n_routed_experts"] * 4 == row["config"]["n_routed_experts"]
    assert cfg["vocab_size"] * 4 == row["config"]["vocab_size"]
    assert set(cfg["assumed"]) >= {"position_embedding", "state_dtype",
                                   "gated_norm_group_size",
                                   "time_step_limit", "weights_init"}
    assert "multi_token_prediction" in cfg["not_served"]


# ----------------------------------------------- the family file itself
def test_the_family_loads_without_jax():
    code = ("import sys; from benchmarks.harness import spec; "
            "f = spec.load_family('nemotron_h', 'serve'); "
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules; "
            "print(f.REFERENCE_GAP_TOL)")
    out = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert float(out.stdout) > 0


def test_a_checkout_without_the_model_stops_with_a_sentence(monkeypatch,
                                                           tmp_path):
    """The parent of PR 48 with this benchmark laid over it: the family
    file stops in the driver process, before a cluster is started."""
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        spec.load_family("nemotron_h", "serve")
    assert "ray_tpu.models.nemotron_h" in str(e.value)
    assert "cannot serve" in str(e.value)


def test_counts_are_the_programs_and_the_files_arithmetic(cell):
    """Abstract shapes: nothing is allocated."""
    import jax

    fam = cell.family
    model = fam.published(cell.config)
    cfg = fam.program_config(model, max_seq=9216)
    shapes = jax.eval_shape(
        lambda: fam.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    # the arithmetic of `reduced_why`
    want = (5 * 109_640_064 + 35_655_680 + 5 * (54_530_560 + 704_643_072)
            + 268_435_456 + 4_096)
    assert fam.param_count(model) == n == want == 4_648_163_712
    assert "4,648,163,712" in cell.config["reduced_why"]
    assert (cfg.pattern, cfg.experts_held, cfg.n_experts, cfg.top_k,
            cfg.ssm_groups, cfg.ssm_chunk) == (
        "MEMEMEM*EME", (0, 128), 512, 22, 8, 128)
    # a token multiplies 22 / 4 held experts a routed layer
    rest = fam._non_expert_matmul_params(model)
    assert fam.matmul_params(model) == rest + 5 * 22 * 5_505_024 // 4
    assert fam.lane_state_bytes(model) == 4_194_304
    hit = 128 * (1 - (1 - 22 / 512) ** 64)
    assert fam.expected_experts_hit(model, 64) == pytest.approx(hit)
    assert fam.decode_step_bytes(model, lanes=64) == pytest.approx(
        2.0 * (rest + 5 * hit * 5_505_024) + 2.0 * 64 * 5 * 4_194_304
        + 64 * 6400 * 1024)
    assert 11.5e9 < fam.decode_step_bytes(model) < 11.9e9
    assert (fam.kernel_layers(model, "ssm_update"),
            fam.kernel_layers(model, "moe_gmm"),
            fam.kernel_layers(model, "paged_attn")) == (5, 5, 1)
    assert fam.vocab_size(model) == 32768 and model["num_experts"] == 128
    # the seam's own arithmetic agrees with the family's
    from ray_tpu.models import nemotron_h
    streamed, multiplied = nemotron_h.prefill_params(cfg)
    assert streamed == rest - 32768 * 4096 + 5 * 128 * 5_505_024
    assert multiplied == fam.matmul_params(model) - 32768 * 4096


@pytest.mark.parametrize("change,match", [
    (dict(mlp_hidden_act="silu"), "relu2"),
    (dict(moe_latent_size=0), "latent"),
    (dict(n_group=8), "router"),
    (dict(num_hidden_layers=12), "pattern"),
    (dict(hybrid_override_pattern="MEMEMEM-EME"), "pattern"),
    (dict(tie_word_embeddings=True), "tied head"),
    (dict(mamba_num_heads=64), "inner width")])
def test_program_config_refuses_what_the_program_cannot_express(
        cell, change, match):
    model = cell.family.published(cell.config)
    with pytest.raises(ValueError, match=match):
        cell.family.program_config(dict(model, **change), max_seq=64)


def test_rehearsal_shrinks_to_all_three_kinds_of_layer():
    fam = spec.load_family("nemotron_h", "serve")
    cfg = _config()
    fam.rehearsal(cfg)
    pc = fam.program_config(fam.published(cfg), max_seq=64)
    assert (pc.dim, pc.pattern, pc.n_experts, pc.experts_held,
            pc.ssm_groups) == (64, "MEM*E", 8, (0, 4), 2)


def test_costs_by_hand(cell):
    """At the published widths: a lane-layer of `ssm_update` is 4.19 MB
    read and 4.19 MB written beside its vectors; an expert hit is TWO
    matrices of 1,024 x 2,688 streamed once, an assignment two matmuls."""
    model = cell.family.published(cell.config)
    fl, by = cell.family.ssm_update_cost(model, lane_steps=57 * 5)
    one = (2 * 128 * 8192 * 4           # the state, in and out
           + 8192 * 2                   # x, bfloat16
           + 2 * 8 * 128 * 2            # B and C of 8 groups x 128
           + 128 * 4                    # dt, a float32 a head
           + 8192 * 4)                  # y, float32
    assert one == 8_442_368
    assert by == one * 57 * 5 and fl == 5.0 * 128 * 8192 * 57 * 5
    assert by / 819e9 > 20 * fl / 197e12          # memory-bound by far
    fl, by = cell.family.moe_gmm_cost(model, assignments=352.0,
                                      experts_hit=120.0)
    assert fl == 2.0 * 2 * 1024 * 2688 * 352
    assert by == 2.0 * (2 * 1024 * 2688 * 120
                        + (1024 + 2688 + 2688 + 1024) * 352)
    assert by / 819e9 > 5 * fl / 197e12           # the experts' stream


# --------------------------------------------------- the judge (tiny, CPU)
@pytest.fixture(scope="module")
def judged():
    """A debug-sized model at `--rehearse` sizes, judged block by block
    (the served tokens through the engine: `tests/test_nemotron_h.py`)."""
    import jax
    import numpy as np

    fam = spec.load_family("nemotron_h", "serve")
    cfg = _config()
    fam.rehearsal(cfg)
    model = fam.published(cfg)
    pc = fam.program_config(model, max_seq=256)
    params = fam.init_params(jax.random.PRNGKey(5), pc)
    tokens = np.random.default_rng(2).integers(0, 512, 150).tolist()
    return fam, model, params, tokens


def test_the_judge_passes_a_sound_program(judged):
    fam, model, params, tokens = judged
    b = fam.block_errors(params, tokens, model)
    assert b["block"][0] < fam.BLOCK_ERR_TOL, b["block"]
    assert b["state"][0] < fam.STATE_ERR_TOL, b["state"]
    assert b["from_x"][0] < fam.STATE_FROM_X_TOL, b["from_x"]
    assert b["loose_share"] < fam.LOOSE_SHARE_MAX
    kinds = {k for k, *_ in b["by_block"]}
    assert {"M", "E", "*", "scan", "update", "idle_lanes", "decode_step",
            "conv_rows", "k_rows", "v_rows", "head", "prefill",
            "decode"} <= kinds


def _control(name, mp, fam):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import nemotron_h as prog

    if name == "state_in_bfloat16":
        pc = fam.program_config
        mp.setattr(fam, "program_config", lambda m, max_seq, **kw:
                   pc(m, max_seq, state_dtype=jnp.bfloat16, **kw))
    elif name == "norm_over_the_whole_row":
        mp.setattr(prog, "gated_group_norm", lambda y, z, w, cfg:
                   prog.rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)), w,
                                cfg.norm_eps).astype(cfg.dtype))
    elif name == "route_top_21":
        route = prog.route

        def one_less(h2, lp, cfg):
            idx, wts = route(h2, lp, dataclasses.replace(
                cfg, top_k=cfg.top_k - 1))
            return (jnp.concatenate([idx, idx[:, :1]], axis=1),
                    jnp.concatenate([wts, jnp.zeros_like(wts[:, :1])],
                                    axis=1))

        mp.setattr(prog, "route", one_less)


@pytest.mark.parametrize("name,held_by", [
    ("state_in_bfloat16", "state"), ("norm_over_the_whole_row", "block"),
    ("route_top_21", "block")])
def test_the_judge_fails_a_control(judged, monkeypatch, name, held_by):
    """The three controls of ISSUE 48, at `--rehearse` sizes: the state
    kept in the nearest precision below the stated one, the gated norm
    taken over the whole row, one expert fewer selected than published."""
    fam, model, params, tokens = judged
    limits = {"block": fam.BLOCK_ERR_TOL, "state": fam.STATE_ERR_TOL}
    monkeypatch.setattr(fam, "_BLOCKS", {})
    _control(name, monkeypatch, fam)
    b = fam.block_errors(params, tokens, model)
    assert b[held_by][0] > limits[held_by], (held_by, b[held_by])


# ------------------------------------------ the metric the cell brings
def _run(cell, s0, s1):
    return {"cell": cell, "model": cell.family.published(cell.config),
            "engine": {"steps_per_sync": 8}, "trace": None, "spans": [],
            "stats": ({"loop": s0}, {"loop": s1}),
            "device": {"kind": "TPU v5 lite"}}


def test_the_new_reader_on_a_synthetic_run(cell):
    """57 lanes x 22 / 4 assignments a layer-step over ~118 experts hit."""
    s0 = {"moe_layer_steps": 40, "moe_assignments": 40 * 313,
          "moe_experts_hit": 40 * 118}
    s1 = {"moe_layer_steps": 440, "moe_assignments": 440 * 313,
          "moe_experts_hit": 440 * 118}
    read = spec.load_reader(NEW_METRIC).read(_run(cell, s0, s1))
    assert read == pytest.approx(313 / 118)


@pytest.mark.parametrize("other", ["granite4h.batch.closed",
                                   "mistral7b.batch.closed"])
def test_a_program_without_the_counters_reads_nothing(cell, other):
    """The parent's program under this benchmark, or a cell of a model
    that routes nothing: the reader returns None and does not raise."""
    reader = spec.load_reader(NEW_METRIC)
    run = _run(spec.load_cell(other), {"decode_steps": 1},
               {"decode_steps": 9})
    assert reader.read(run) is None
    assert reader.read(_run(cell, {}, {"moe_experts_hit": 0,
                                       "moe_assignments": 0})) is None
