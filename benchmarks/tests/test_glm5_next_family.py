"""The model family `glm5_next` through the harness's seam: the cell
`glm53flash.docs.closed` is found by files and `BENCHMARK.json` entries
alone, the configuration holds the catalog row's numbers but for what
`reduced` names, the family file answers everything the harness asks
(without importing jax at load, and stopping with a sentence on a checkout
whose program cannot serve it), its counts are the program's own at the
published widths, its judge passes a sound program and fails the
controls, and the metrics the cell brings read a synthetic run."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import peaks, spec

CELL = "glm53flash.docs.closed"
NEW_METRICS = ("kernel.kda_update_roofline.closed",
               "model.kda_update_share_of_decode_pct.closed",
               "kernel.dsa_attn_roofline.closed",
               "model.dsa_share_of_decode_pct.closed",
               "engine.dsa_selected_pct.closed")


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def _catalog_row() -> dict | None:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    return next(r for r in rows if r["name"] == "GLM-5.3-Flash")


# ------------------------------------------ the cell, by files alone
def test_the_cell_is_found_by_its_files(cell):
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.kind,
            cell.loop, cell.family_name) == (
        "glm-5.3-flash-ep8", "docs-closed-96", 1, "serve", "closed",
        "glm5_next")
    t = cell.traffic
    assert (t["clients"], t["population"], t["ramp_s"], t["drain_s"],
            t["expect_preemptions"]) == (96, 128, 40.0, 60.0, 0)
    assert t["prompt_len"]["clip"] == [4097, 8192]
    assert t["output_len"]["clip"] == [256, 1024]
    other = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                        "docs-closed-48.json"))
    assert t["population_seed"] != other["population_seed"]
    assert t["prompt_len"] == other["prompt_len"]
    assert t["output_len"] == other["output_len"]
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= reported
    assert {"kernel.moe_gmm_roofline.closed", "engine.lanes_live.closed",
            "model.prefill_share_of_device_pct.closed"} <= reported
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    eng = cell.config["engine"]
    assert eng["kv_pages"] == eng["max_batch"] * (
        eng["max_len"] // eng["page_size"]) + 1


def test_the_configuration_holds_the_catalog_row_but_for_the_cut(cell):
    row = _catalog_row()
    if row is None:
        pytest.skip("no model-configs catalog on this machine")
    cfg = cell.config
    assert cfg["source"] == row["source_url"]
    (entry,) = [c for c in spec.benchmark_json()["configs"]
                if c["name"] == cell.config_name]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == \
        row["source_url"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v and cfg[k] != v
        else:
            assert cfg[k] == v, k
    # no width moved inside the one nested group that is cut
    la, pub = cfg["linear_attn_config"], row["config"]["linear_attn_config"]
    assert {k: v for k, v in la.items() if not k.endswith("layers")} == \
        {k: v for k, v in pub.items() if not k.endswith("layers")}
    assert set(cfg["assumed"]) >= {"index_key_pooling", "kda_gate", "mhc",
                                   "state_dtype", "not_served"}


# ----------------------------------------------- the family file itself
def test_the_family_loads_without_jax():
    code = ("import sys; from benchmarks.harness import spec; "
            "f = spec.load_family('glm5_next', 'serve'); "
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules; "
            "print(f.REFERENCE_GAP_TOL)")
    out = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert float(out.stdout) > 0


def test_a_checkout_without_the_model_stops_with_a_sentence(monkeypatch,
                                                           tmp_path):
    """The parent of PR 41 with this benchmark laid over it: the family
    file stops in the driver process, before a cluster is started."""
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        spec.load_family("glm5_next", "serve")
    assert "ray_tpu.models.glm5_next" in str(e.value)
    assert "cannot serve" in str(e.value)


def test_counts_are_the_programs_at_the_published_widths(cell):
    """Abstract shapes: nothing is allocated."""
    import jax

    fam = cell.family
    model = fam.published(cell.config)
    cfg = fam.program_config(model, max_seq=9216)
    shapes = jax.eval_shape(
        lambda: fam.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert fam.param_count(model) == n == 4_718_936_462
    assert (cfg.n_layers, cfg.count("linear_attention"), cfg.kda_chunk,
            cfg.experts_held, cfg.n_experts) == (5, 4, 32, (0, 36), 288)
    # a token multiplies one held expert a routed layer (8 x 36 / 288)
    held = 4 * 36 * 3 * 4096 * 2048
    assert fam.matmul_params(model) == fam._non_expert_matmul_params(
        model) + 4 * 3 * 4096 * 2048
    assert fam.decode_step_bytes(model, lanes=64) == 2.0 * (
        fam._non_expert_matmul_params(model) + held) + 2.0 * 64 * 4 * 4194304
    assert fam.lane_state_bytes(model) == 4_194_304
    assert (fam.kernel_layers(model, "kda_update"),
            fam.kernel_layers(model, "dsa_attn"),
            fam.kernel_layers(model, "moe_gmm"),
            fam.kernel_layers(model, "flash_fwd")) == (4, 1, 4, 0)
    assert fam.vocab_size(model) == 19456 and model["num_experts"] == 36
    # the seam's own arithmetic agrees with the family's
    from ray_tpu.models import glm5_next
    streamed, multiplied = glm5_next.prefill_params(cfg)
    d = 4096
    assert streamed == fam._non_expert_matmul_params(model) - 19456 * d \
        + held
    assert multiplied == fam.matmul_params(model) - 19456 * d


@pytest.mark.parametrize("change,match", [
    (dict(mhc=False), "mHC"),
    (dict(qk_rope_head_dim=64), "rotary"),
    (dict(n_group=8), "router"),
    (dict(num_hidden_layers=6), "layer lists"),
    (dict(index_kpool_compress=False), "index pool"),
    (dict(tie_word_embeddings=True), "tied head")])
def test_program_config_refuses_what_the_program_cannot_express(
        cell, change, match):
    model = cell.family.published(cell.config)
    with pytest.raises(ValueError, match=match):
        cell.family.program_config(dict(model, **change), max_seq=64)


def test_rehearsal_shrinks_both_kinds_of_mixer():
    fam = spec.load_family("glm5_next", "serve")
    cfg = dict(spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "glm-5.3-flash-ep8.json")))
    fam.rehearsal(cfg)
    pc = fam.program_config(fam.published(cfg), max_seq=64)
    assert (pc.dim, pc.n_layers, pc.n_experts, pc.experts_held) == (
        64, 4, 8, (0, 4))
    assert set(pc.layer_types) == {"linear_attention",
                                   "deepseek_sparse_attention"}


def test_costs_by_hand(cell):
    model = cell.family.published(cell.config)
    fl, by = cell.family.kda_update_cost(model, lane_steps=57 * 4)
    one = 2 * 4 * 64 * 128 * 128 + 4 * 64 * (3 * 128 + 2 * 128 + 1)
    assert by == one * 57 * 4 and fl == 7.0 * 64 * 128 * 128 * 57 * 4
    assert by / 819e9 > 20 * fl / 197e12          # memory-bound by far
    from ray_tpu.ops import kda
    assert kda.update_cost(64, 128, 128, 57 * 4) == (fl, by)
    fl, by = cell.family.dsa_attn_cost(model, rows=2051 * 57)
    assert by == 2 * 512 * 2051 * 57
    assert fl == 2.0 * 64 * 1024 * 2051 * 57
    from ray_tpu.ops import sparse_attention
    assert sparse_attention.attn_cost(64, 512, 512, 2051 * 57) == (fl, by)


# --------------------------------------------------- the judge (tiny, CPU)
@pytest.fixture(scope="module")
def judged():
    """A debug-sized model served by the program's own prefill and decode
    (not the engine: `tests/test_glm5_next.py` holds that) and judged."""
    import jax
    import numpy as np

    fam = spec.load_family("glm5_next", "serve")
    cfg = dict(spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "glm-5.3-flash-ep8.json")))
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
    assert b["rows"][0] < fam.ROW_ERR_TOL, b["rows"]
    assert b["state"][0] < fam.STATE_ERR_TOL, b["state"]
    assert b["from_x"][0] < fam.STATE_FROM_X_TOL, b["from_x"]
    assert b["select"][0] < fam.SELECT_MISS_TOL, b["select"]
    kinds = {k for k, *_ in b["by_block"]}
    assert {"scan", "update", "idle_lanes", "decode_step", "latent",
            "index", "own_group", "prefill_rows", "ffn", "head"} <= kinds


def _control(name, mp):
    import jax.numpy as jnp

    from ray_tpu.models import glm5_next as prog
    from ray_tpu.ops import sparse_attention as dsa

    if name == "no_decay_gate":
        gate = prog.kda_gate
        mp.setattr(prog, "kda_gate", lambda h, lp, cfg: (
            jnp.zeros_like(gate(h, lp, cfg)[0]), gate(h, lp, cfg)[1]))
    elif name == "sinkhorn_once":
        sk = prog.sinkhorn
        mp.setattr(prog, "sinkhorn", lambda m, iters: sk(m, 1))
    elif name == "last_rows_selected":
        mp.setattr(dsa, "index_scores", lambda q, w, kbar: jnp.broadcast_to(
            jnp.arange(kbar.shape[-2], dtype=jnp.float32),
            q.shape[:-2] + (kbar.shape[-2],)))
    elif name == "tail_not_selected":
        sm, sr = dsa.selected_mask, dsa.select_rows

        def no_tail(scores, pos, n_keys, group, top):
            mask, chosen = sm(scores, pos, n_keys, group, top)
            own = jnp.arange(n_keys)[None, :] >= (
                (pos + 1) // group * group)[:, None]
            return mask & ~own, chosen

        def no_own(pages, tail, table, pos, ts, groups, ok, group):
            rows, bias, tbias, rpos, admit = sr(pages, tail, table, pos, ts,
                                                groups, ok, group)
            own = rpos >= ((pos + 1) // group * group)[:, None]
            S = bias.shape[1]
            return (rows, jnp.where(own[:, :S], dsa.NEG_INF, bias),
                    jnp.where(own[:, S:], dsa.NEG_INF, tbias), rpos,
                    admit & ~own)

        mp.setattr(dsa, "selected_mask", no_tail)
        mp.setattr(dsa, "select_rows", no_own)


@pytest.mark.parametrize("name,held_by", [
    ("no_decay_gate", "block"), ("sinkhorn_once", "block"),
    ("last_rows_selected", "select"), ("tail_not_selected", "select"),
    ("bfloat16_state", "state")])
def test_the_judge_fails_a_control(judged, monkeypatch, name, held_by):
    import jax.numpy as jnp

    fam, model, params, tokens = judged
    limits = {"block": fam.BLOCK_ERR_TOL, "state": fam.STATE_ERR_TOL,
              "select": fam.SELECT_MISS_TOL}
    monkeypatch.setattr(fam, "_BLOCKS", {})
    if name == "bfloat16_state":
        pc = fam.program_config
        monkeypatch.setattr(fam, "program_config", lambda m, max_seq, **kw:
                            pc(m, max_seq, state_dtype=jnp.bfloat16, **kw))
    else:
        _control(name, monkeypatch)
    b = fam.block_errors(params, tokens, model)
    assert b[held_by][0] > limits[held_by], (held_by, b[held_by])


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


def _dispatches(times, lanes, rows=2051):
    return [{"name": "llm.loop.decode_dispatch", "t0": t, "t1": t + 0.002,
             "attrs": {"lanes": lanes, "steps": 8,
                       "ssm_lane_steps": 8 * lanes * 4,
                       "dsa_rows_selected": 8 * lanes * rows,
                       "dsa_rows_context": 8 * lanes * 6500}}
            for t in times]


def _counters(windows, lanes):
    return {"decode_steps": windows * 8,
            "ssm_lane_steps": windows * 8 * lanes * 4,
            "dsa_rows_selected": windows * 8 * lanes * 2051,
            "dsa_rows_context": windows * 8 * lanes * 6500}


def test_the_new_readers_on_a_synthetic_run(cell, capsys):
    by_op = [
        ["jit__decode_k_paged",
         "kda_update.7 custom-call f32[4,64,64,128,128]", 128, 0.30],
        ["jit__decode_k_paged", "dsa_attn.3 custom-call bf16[64,64,512]",
         32, 0.08],
        ["jit__decode_k_paged", "moe_gmm.5 custom-call", 256, 0.2],
        ["jit__decode_k_paged", "fusion.12", 64, 0.22],
    ]
    modules = [("jit__decode_k_paged(3)", 0.2 * i, 0.2) for i in range(4)]
    inside = [100.0 + 0.2 * i for i in range(4)]
    run = _run(cell, by_op, modules, _counters(10, 52), _counters(110, 52),
               _dispatches(inside, 64) + _dispatches([99.5, 101.5], 30))
    read = {n: spec.load_reader(n).read(run) for n in NEW_METRICS}
    assert read["model.kda_update_share_of_decode_pct.closed"] == \
        pytest.approx(100 * 0.30 / 0.8)
    assert read["model.dsa_share_of_decode_pct.closed"] == \
        pytest.approx(100 * 0.08 / 0.8)
    assert read["engine.dsa_selected_pct.closed"] == \
        pytest.approx(100 * 2051 / 6500)
    fl, by = cell.family.kda_update_cost(run["model"], 128 * 64)
    assert read["kernel.kda_update_roofline.closed"] == pytest.approx(
        100 * peaks.roofline_s(fl, by, "TPU v5 lite")[0] / 0.30)
    fl, by = cell.family.dsa_attn_cost(run["model"], 32 * 64 * 2051)
    assert read["kernel.dsa_attn_roofline.closed"] == pytest.approx(
        100 * peaks.roofline_s(fl, by, "TPU v5 lite")[0] / 0.08)
    assert all(0 < read[n] < 100 for n in NEW_METRICS)
    assert '"bound": "memory"' in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_counters_or_kernels_reads_nothing(cell, name):
    """The parent's program under this benchmark: no `dsa_*` counter, no
    `kda_update` or `dsa_attn` event; the reader returns None and does not
    raise."""
    by_op = [["jit__decode_k_paged", "paged_attn.3 custom-call", 16, 0.05]]
    modules = [("jit__decode_k_paged(3)", 0.0, 0.2)]
    run = _run(cell, by_op, modules, {"decode_steps": 1},
               {"decode_steps": 9})
    assert spec.load_reader(name).read(run) is None
    assert spec.load_reader(name).read(dict(run, trace=None)) is None
    other = _run(spec.load_cell("granite4h.batch.closed"), by_op, modules,
                 {"decode_steps": 1, "ssm_lane_steps": 5},
                 {"decode_steps": 9, "ssm_lane_steps": 50})
    assert spec.load_reader(name).read(other) is None
