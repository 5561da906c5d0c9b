"""The model family `cohere2_moe` through the harness's seam: the cell
`commandaplus.docs.closed` is found by files and `BENCHMARK.json` entries
alone, the configuration holds the catalog row's numbers but for what
`reduced` names, the family file answers everything the harness asks
(without importing jax at load, and stopping with a sentence on a checkout
whose program cannot serve it), its counts are the program's own at the
published widths and equal the arithmetic the configuration file states,
its cost functions equal a hand count, its judge passes a sound program
and fails the ten controls, `--rehearse` walks the cell on the CPU, and
the two metrics the cell brings read a synthetic run."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import peaks, spec

COHERE_CELL = "commandaplus.docs.closed"
COHERE_CONFIG = "command-a-plus-ep16"
COHERE_NEW_METRICS = ("kernel.flash_fwd_roofline.closed",
                      "model.attn_share_of_prefill_pct.closed")
COHERE_SHARED_METRICS = (
    "kernel.swa_attn_roofline.closed", "model.swa_share_of_decode_pct.closed",
    "engine.swa_attended_pct.closed", "kernel.swa_band_roofline.closed",
    "kernel.paged_attn_roofline.closed",
    "model.paged_attn_share_of_decode_pct.closed",
    "kernel.moe_gmm_roofline.closed",
    "model.moe_gmm_share_of_decode_pct.closed",
    "engine.moe_experts_hit_pct.closed",
    "engine.moe_rows_per_expert_hit.closed",
    "engine.prefill_walked_factor.closed",
    "model.prefill_share_of_device_pct.closed")


@pytest.fixture(scope="module")
def cohere_cell():
    return spec.load_cell(COHERE_CELL)


def _cohere_config() -> dict:
    return dict(spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                            COHERE_CONFIG + ".json")))


def _cohere_catalog_row() -> dict | None:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    return next(r for r in rows if r["name"] == "command-a-plus-05-2026")


# ------------------------------------------ the cell, by files alone
def test_the_cohere_cell_is_found_by_its_files(cohere_cell):
    cell = cohere_cell
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.kind,
            cell.loop, cell.family_name) == (
        COHERE_CONFIG, "docs-closed-96", 1, "serve", "closed",
        "cohere2_moe")
    # the same traffic, engine and deployment as mimo's cell: they differ
    # by the model alone
    other = spec.load_cell("mimov2flash.docs.closed")
    assert other.traffic_name == cell.traffic_name
    assert other.config["engine"] == cell.config["engine"]
    assert other.config["deployment"] == cell.config["deployment"]
    reported = {m["name"] for m in cell.per_layer}
    assert set(COHERE_NEW_METRICS) | set(COHERE_SHARED_METRICS) <= reported
    # everything the other routed docs cell with rings reports, and the
    # two readers this cell brings
    assert reported == {m["name"] for m in other.per_layer} | set(
        COHERE_NEW_METRICS)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    bench = spec.benchmark_json()
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(COHERE_CELL) == 11 and len(names) >= 12
    assert sum(w["chips"] == 4 for w in bench["workloads"][:12]) == 1
    for name in COHERE_NEW_METRICS:
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [COHERE_CELL]
        reader = spec.load_reader(name)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES, reader.UNIT,
                reader.BETTER) == (entry["layer"], entry["source"],
                                   entry["moves"], entry["unit"],
                                   entry["better"])
    for entry in bench["configs"][-1:] + bench["workloads"][11:12]:
        assert len(entry["why"]) <= 200 and len(
            entry.get("source", "")) <= 200


def test_the_cohere_configuration_holds_the_catalog_row_but_for_the_cut(
        cohere_cell):
    row = _cohere_catalog_row()
    if row is None:
        pytest.skip("no model-configs catalog on this machine")
    cfg = cohere_cell.config
    assert cfg["source"] == row["source_url"]
    (entry,) = [c for c in spec.benchmark_json()["configs"]
                if c["name"] == cohere_cell.config_name]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    assert entry["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v and cfg[k] != v
        else:
            assert cfg[k] == v, k
    # one whole period, as published
    assert cfg["layer_types"] == row["config"]["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert cfg["num_experts"] * cfg["expert_parallel"]["chips"] == \
        cfg["published"]["num_experts"]
    assert cfg["expert_parallel"] == {"chips": 16, "rank": 0}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["vocab_size"] % 128 == 0
    assert set(cfg["assumed"]) >= {
        "expert_width", "shared_experts", "layer_norm", "window", "nope",
        "rope_columns", "prefix_dense", "router", "ring_rows",
        "not_served"}
    assert "vision tower" in cfg["assumed"]["not_served"]
    assert "16-way" in cfg["stands_for"]
    assert set(spec.load_family("cohere2_moe", "serve").KEYS) == set(
        row["config"])


# ----------------------------------------------- the family file itself
def test_the_cohere_family_loads_without_jax():
    code = ("import sys; from benchmarks.harness import spec; "
            "f = spec.load_family('cohere2_moe', 'serve'); "
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules; "
            "print(f.REFERENCE_GAP_TOL)")
    out = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert float(out.stdout) > 0


def test_a_checkout_without_cohere_stops_with_a_sentence(monkeypatch,
                                                         tmp_path):
    """The parent of PR 54 with this benchmark laid over it: the family
    file stops in the driver process, before a cluster is started."""
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        spec.load_family("cohere2_moe", "serve")
    assert "ray_tpu.models.cohere2_moe" in str(e.value)
    assert "cannot serve" in str(e.value)


def test_cohere_counts_are_the_programs_at_the_published_widths(
        cohere_cell):
    """Abstract shapes: nothing is allocated."""
    import jax

    fam = cohere_cell.family
    model = fam.published(cohere_cell.config)
    cfg = fam.program_config(model, max_seq=9216)
    shapes = jax.eval_shape(
        lambda: fam.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert fam.param_count(model) == n == 3_122_679_808
    assert (cfg.n_layers, cfg.count("sliding_attention"), cfg.window,
            cfg.ring_rows, cfg.experts_held, cfg.n_experts, cfg.n_shared,
            cfg.top_k) == (4, 3, 4096, 4096, (0, 8), 128, 4, 8)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rope_theta,
            cfg.norm_eps, cfg.logit_scale) == (128, 8, 128, 5.0e4, 1e-5, 1.0)
    # the ISSUE's count, by part
    one = 3 * 4096 * 4096
    assert fam._attn_params(model) == 142_606_336
    assert fam._expert_params(model) == one == 50_331_648
    layer = 142_606_336 + 4 * one + 4096 * 128 + 8 * one
    assert n == 4 * (layer + 4096) + 32768 * 4096 + 4096
    # a token multiplies half a held expert a layer (8 x 8 / 128)
    assert fam.matmul_params(model) == fam._non_expert_matmul_params(
        model) + 4 * one // 2
    assert fam.decode_step_bytes(model) == 2.0 * (
        fam._non_expert_matmul_params(model) + 4 * 8 * one)
    assert (fam.kernel_layers(model, "swa_attn"),
            fam.kernel_layers(model, "paged_attn"),
            fam.kernel_layers(model, "moe_gmm"),
            fam.kernel_layers(model, "flash_fwd"),
            fam.kernel_layers(model, "swa_band"),
            fam.kernel_layers(model, "mla_attn")) == (3, 1, 4, 1, 3, 0)
    assert fam.vocab_size(model) == 32768 and model["num_experts"] == 8
    # the seam's own arithmetic agrees with the family's (the embedding
    # is a lookup and the head one position a row: neither is counted)
    from ray_tpu.models import cohere2_moe
    streamed, multiplied = cohere2_moe.prefill_params(cfg)
    assert streamed == fam._non_expert_matmul_params(model) \
        - 32768 * 4096 + 4 * 8 * one
    assert multiplied == fam.matmul_params(model) - 32768 * 4096
    # the cache beside the weights: a K and a V leaf for the ONE global
    # layer, a K and a V ring a window layer (abstract)
    cache = jax.eval_shape(lambda: cohere2_moe.init_paged_cache(
        cfg, 64, 1153, 512))
    nbytes = lambda t: sum(a.size * a.dtype.itemsize       # noqa: E731
                           for a in jax.tree.leaves(t))
    assert len(cache["k"]) == len(cache["v"]) == 1
    assert nbytes(cache["k"]) + nbytes(cache["v"]) \
        == 1153 * 512 * 8 * 256 * 2
    assert nbytes(cache["state"]) == 64 * 3 * 4096 * 8 * 256 * 2
    resident = 2 * n + nbytes(cache["k"]) + nbytes(cache["v"]) \
        + nbytes(cache["state"])
    assert 11.87e9 < resident < 11.90e9     # the file's 11.88 GB: 70 %
    assert resident > 0.25 * 16.9e9


@pytest.mark.parametrize("change,match", [
    (dict(use_parallel_block=False), "sequential block"),
    (dict(tie_word_embeddings=False), "untied head"),
    (dict(rms_norm_eps=1e-5), "RMSNorm"),
    (dict(position_embedding_type="rope_llama"), "rotary"),
    (dict(rotary_pct=0.5), "rotary"),
    (dict(shared_expert_combination_strategy="sum"), "averaged"),
    (dict(expert_selection_fn="softmax"), "router"),
    (dict(first_k_dense_replace=1), "leading dense"),
    (dict(num_hidden_layers=5), "layer list"),
    (dict(use_qk_norm=True), "q/k norms")])
def test_cohere_program_config_refuses_what_the_program_cannot_express(
        cohere_cell, change, match):
    model = cohere_cell.family.published(cohere_cell.config)
    with pytest.raises(ValueError, match=match):
        cohere_cell.family.program_config(dict(model, **change), max_seq=64)


def test_cohere_rehearsal_shrinks_both_kinds_of_layer():
    fam = spec.load_family("cohere2_moe", "serve")
    cfg = _cohere_config()
    fam.rehearsal(cfg)
    pc = fam.program_config(fam.published(cfg), max_seq=64)
    assert (pc.dim, pc.n_layers, pc.n_experts, pc.experts_held, pc.window,
            pc.ring_rows, pc.n_shared) == (64, 3, 8, (0, 4), 9, 16, 2)
    assert set(pc.layer_types) == {"full_attention", "sliding_attention"}


def test_cohere_costs_by_hand(cohere_cell):
    fam = cohere_cell.family
    model = fam.published(cohere_cell.config)
    # a live ring row: K and V, 128 + 128 wide, once a kv head; scored and
    # weighed for 128 query heads
    fl, by = fam.swa_attn_cost(model, rows=4096 * 52)
    assert by == 2 * 8 * 256 * 4096 * 52             # 4,096 B a row
    assert fl == 2.0 * 128 * 256 * 4096 * 52
    assert fam.paged_attn_cost(model, rows=6500 * 52) == (
        2.0 * 128 * 256 * 6500 * 52, 2.0 * 8 * 256 * 6500 * 52)
    # the band: a prompt of 8,192 scores 4,096 rows a query but for the
    # first 4,095 queries; a prompt under the window is causal
    fl, by = fam.swa_band_cost(model, [8192, 100])
    pairs = 4096 * 4097 // 2 + (8192 - 4096) * 4096 + 100 * 101 // 2
    assert fl == 2.0 * pairs * 128 * 256
    assert by == 2.0 * 8292 * 256 * (128 + 8)
    fl, by = fam.flash_fwd_cost(model, [8192, 100])
    assert fl == 2.0 * (8192 * 8193 // 2 + 100 * 101 // 2) * 128 * 256
    assert by == 2.0 * 8292 * 256 * (128 + 8)
    fl, by = fam.moe_gmm_cost(model, assignments=200.0, experts_hit=7.0)
    one = 3 * 4096 * 4096
    assert fl == 2.0 * one * 200
    assert by == 2.0 * (one * 7 + (2 * 4096 + 3 * 4096) * 200)


# --------------------------------------------------- the judge (tiny, CPU)
# at the debug sizes a ring is 16 rows: walked, as the served ring of 4,096
# is, where a block is 8 rows (the controls of the walk's work list; the
# sound program under the same block beside them)
DEBUG_RING_BLOCK = 8


@pytest.fixture(scope="module")
def cohere_judged():
    """A debug-sized model judged on 150 tokens: sixteen windows of 9, so
    the band's lower edge crosses every block and the ring of 16 rows
    has wrapped nine times."""
    import jax
    import numpy as np

    fam = spec.load_family("cohere2_moe", "serve")
    cfg = _cohere_config()
    fam.rehearsal(cfg)
    model = fam.published(cfg)
    pc = fam.program_config(model, max_seq=256)
    params = jax.jit(lambda k: fam.init_params(k, pc))(
        jax.random.PRNGKey(5))
    tokens = np.random.default_rng(2).integers(0, 512, 150).tolist()
    return fam, model, params, tokens


def test_the_cohere_judge_passes_a_sound_program(cohere_judged):
    fam, model, params, tokens = cohere_judged
    b = fam.block_errors(params, tokens, model)
    assert b["block"][0] < fam.BLOCK_ERR_TOL, b["block"]
    assert b["ffn"][0] < fam.FFN_ERR_TOL, b["ffn"]
    assert b["rows"][0] < fam.ROW_ERR_TOL, b["rows"]
    assert b["edge"][0] < fam.EDGE_TOL, b["edge"]
    assert b["loose_share"] < fam.LOOSE_SHARE_MAX
    kinds = {k for k, *_ in b["by_block"]}
    assert {"global", "window", "global_decode_step", "window_decode_step",
            "page_k", "page_v", "tail_k", "tail_v", "ring_k", "ring_v",
            "ring_step_k", "ring_step_v", "ring_other_slots",
            "ring_empty_slots", "ffn", "layer", "head", "prefill.8",
            "prefill.10", "decode_step.8", "decode_step.10"} <= kinds


def _cohere_control(name, mp, fam):
    """The fault `name` patched into the program, as the judge's blocks
    (and the engine's programs) then run it."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks.harness.refs import cohere2_moe as ref
    from ray_tpu.models import cohere2_moe as prog
    from ray_tpu.ops import window_attention as swa
    from ray_tpu.ops.norms import rmsnorm

    if name in ("window_one_short", "window_one_long"):
        delta = -1 if name == "window_one_short" else 1
        pc = fam.program_config

        def patched(m, max_seq, **kw):
            c = pc(m, max_seq, **kw)
            return dataclasses.replace(c, window=c.window + delta)
        mp.setattr(fam, "program_config", patched)
    elif name == "rotary_in_the_global_layer":
        rope = prog.rope
        mp.setattr(prog, "rope", lambda x, kind, *a, **kw: rope(
            x, prog.WINDOW, *a, **kw))
    elif name == "rotate_half_on_unpermuted_weights":
        qkv = prog.qkv

        def published(u, lp, kind, cfg, *a, **kw):
            lp = dict(lp, wq=ref.published_columns(lp["wq"], cfg.n_heads,
                                                   cfg.head_dim),
                      wk=ref.published_columns(lp["wk"], cfg.n_kv_heads,
                                               cfg.head_dim))
            return qkv(u, lp, kind, cfg, *a, **kw)
        mp.setattr(prog, "qkv", published)
    elif name == "shared_experts_summed":
        shared = prog.shared_experts
        mp.setattr(prog, "shared_experts", lambda u, lp, cfg: (
            shared(u, lp, cfg).astype(jnp.float32) * cfg.n_shared
        ).astype(u.dtype))
    elif name == "sequential_block":
        def sequential(params, x, lid, cfg, true_lens):
            lp = params["layers"][lid]
            live = jnp.arange(x.shape[1])[None, :] < true_lens[:, None]
            _, o, kept = prog.attn_rows(x, lp, cfg.layer_types[lid], cfg,
                                        true_lens)
            h = x + prog.attn_out(o, lp)
            y, cnt = prog.ffn(prog.norm(h, lp["norm"], cfg), lp, cfg, live)
            return h + y, kept, cnt
        mp.setattr(prog, "layer_prefill", sequential)
    elif name == "rmsnorm":
        mp.setattr(prog, "layernorm",
                   lambda x, w, bias, eps: rmsnorm(x, w, eps))
    elif name == "ring_slot_one_off":
        write = swa.kv_ring_write
        mp.setattr(swa, "kv_ring_write", lambda ring, new, pos, listed:
                   write(ring, new, pos + 1, listed))
    elif name in ("plan_block_one_off_past_the_first_lane",
                  "plan_one_step_short"):
        # faults of the step's work list over SEVERAL live lanes (a
        # walked ring: more than one block): the block index one off for
        # every lane past the first live one (its first block never
        # read, its last read twice); the list one step short (the last
        # lane's last block never read, its output never written)
        plan = swa.ring_plan

        def faulty(bias, lanes, count, block=None):
            p = plan(bias, lanes, count, block)
            if name == "plan_one_step_short":
                return dict(p, count=jnp.maximum(p["count"] - 1, 0))
            nb = swa.ring_blocks(bias.shape[1], block)[1]
            return dict(p, blk=jnp.where(
                p["lane"] > lanes[0], jnp.minimum(p["blk"] + 1, nb - 1),
                p["blk"]))
        mp.setattr(swa, "ring_plan", faulty)
    elif name == "kv_rows_in_fp8":
        # the nearest precision below the one the configuration states:
        # what pages, tails and rings hold (and the prefill attends)
        # rounded to float8 e4m3's 3 bits of mantissa
        qkv = prog.qkv

        def fp8(a):
            return jax.lax.reduce_precision(a, exponent_bits=4,
                                            mantissa_bits=3)

        def rounded(*a, **kw):
            q, k, v = qkv(*a, **kw)
            return q, fp8(k), fp8(v)
        mp.setattr(prog, "qkv", rounded)
    elif name == "router_scores_in_bfloat16":
        def bf16(a):
            """Rounded to bfloat16's 8 bits of mantissa where the chip's
            compiler may not keep the excess (it drops a convert pair)."""
            return jax.lax.reduce_precision(a, exponent_bits=8,
                                            mantissa_bits=7)

        def route(h2, lp, cfg):
            logits = bf16(jnp.dot(h2.astype(jnp.float32),
                                  lp["router"].astype(jnp.float32),
                                  precision=jax.lax.Precision.HIGHEST))
            s = bf16(jax.nn.sigmoid(logits))
            _, idx = jax.lax.top_k(s, cfg.top_k)
            wts = jnp.take_along_axis(s, idx, axis=-1)
            wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + 1e-6)
            return idx.astype(jnp.int32), wts
        mp.setattr(prog, "route", route)
    else:
        raise KeyError(name)


def _cohere_correct(fam, params, tokens, model, mp, capsys):
    """`correct` of one request as the HARNESS decides it
    (`serve_cell._check_outputs`: the judge's worst number against the
    family's one limit), and the judge's own line.  The served token is
    the reference's choice after `tokens`, so reading (1) is 0 and the
    blocks on `tokens` decide."""
    import jax.numpy as jnp

    from benchmarks.harness.refs import cohere2_moe as ref

    served = [int(jnp.argmax(ref.logits(params, tokens, model, last=1)[0]))]
    judge = fam.reference()
    mp.setattr(judge, "_seen", {})
    mp.setattr(judge, "_blocks_done", [])
    capsys.readouterr()
    gaps = judge.teacher_forced_gaps(params, tokens, served, model)
    line = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    return max(gaps) <= fam.REFERENCE_GAP_TOL, line


@pytest.mark.parametrize("ring_block", [None, DEBUG_RING_BLOCK],
                         ids=["one_block", "walked"])
def test_the_cohere_judge_calls_a_sound_program_correct(
        cohere_judged, monkeypatch, capsys, ring_block):
    from ray_tpu.ops import window_attention as swa

    fam, model, params, tokens = cohere_judged
    if ring_block:
        monkeypatch.setattr(fam, "_BLOCKS", {})
        monkeypatch.setattr(swa, "RING_BLOCK", ring_block)
    correct, line = _cohere_correct(fam, params, tokens, model, monkeypatch,
                                    capsys)
    assert correct, line
    assert line["mean_token_gap"] == 0.0 and "worst_edge" in line


COHERE_CONTROLS = [
    ("window_one_short", "edge"), ("window_one_long", "edge"),
    ("rotary_in_the_global_layer", "block"),
    ("rotate_half_on_unpermuted_weights", "block"),
    ("shared_experts_summed", "ffn"), ("sequential_block", "ffn"),
    ("rmsnorm", "block"), ("ring_slot_one_off", "row"),
    ("router_scores_in_bfloat16", "ffn"), ("kv_rows_in_fp8", "row"),
    ("plan_block_one_off_past_the_first_lane", "block"),
    ("plan_one_step_short", "block")]


@pytest.mark.parametrize("name,held_by", COHERE_CONTROLS)
def test_the_cohere_judge_fails_a_control(cohere_judged, monkeypatch,
                                          capsys, name, held_by):
    """Each control through the judge's own fold and the harness's own
    comparison: `correct` comes out false, the limit named being one it
    is over."""
    from ray_tpu.ops import window_attention as swa

    fam, model, params, tokens = cohere_judged
    monkeypatch.setattr(fam, "_BLOCKS", {})
    if name.startswith("plan_"):
        monkeypatch.setattr(swa, "RING_BLOCK", DEBUG_RING_BLOCK)
    _cohere_control(name, monkeypatch, fam)
    correct, line = _cohere_correct(fam, params, tokens, model, monkeypatch,
                                    capsys)
    assert not correct, line
    reading = "worst_edge" if held_by == "edge" else f"worst_{held_by}_err"
    # (not under its limit: an output never written reads NaN)
    assert not line[reading][0] <= line[f"{held_by}_limit"], line


# ------------------------------------------------- rehearsal, on the CPU
@pytest.mark.time_limit(420)
def test_the_cohere_cell_rehearses_on_the_cpu():
    """The walk is what is held (the last line's shape), not how many
    requests END inside so short a window."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", COHERE_CELL,
         "--seed", "2147483659", "--seconds", "6", "--trace", "0",
         "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=400)
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0   # never passes
    assert last["metrics"]["rehearsal.setup_s"]["value"] > 0
    assert '"a rehearsal is never correct"' in out.stdout


# ------------------------------------------ the metrics the cell brings
def _cohere_run(cell, by_op, modules, spans=()):
    red = {"window_s": 1.0, "busy_s": 1.0, "start_wall_s": 100.0,
           "t_lo": 0.0, "t_hi": 1.0,
           "devices": [{"by_op": by_op, "modules": modules, "gaps": [],
                        "busy_s": 1.0}]}
    return {"cell": cell, "model": cell.family.published(cell.config),
            "engine": {"steps_per_sync": 8}, "trace": red,
            "spans": list(spans), "stats": ({"loop": {}}, {"loop": {}}),
            "device": {"kind": "TPU v5 lite"}}


def _cohere_prefills(times, tokens=6000):
    return [{"name": "llm.prefill", "t0": t, "t1": t + 0.1, "tid": i,
             "attrs": {"prompt_tokens": tokens}}
            for i, t in enumerate(times)]


def test_the_cohere_readers_on_a_synthetic_run(cohere_cell, capsys):
    cell = cohere_cell
    by_op = [
        ["jit__decode_k_paged", "swa_attn.7 custom-call", 96, 0.3],
        ["jit__prefill_fwd_only",
         "flash_fwd.4 custom-call bf16[1,128,8192,128]", 2, 0.05],
        ["jit__prefill_fwd_only", "swa_band.2 custom-call", 6, 0.09],
        ["jit__prefill_fwd_only", "fusion.9", 40, 0.2],
        ["jit__scatter", "fusion.3", 2, 0.01],
    ]
    modules = [("jit__prefill_fwd_only(7)", 0.1, 0.2),
               ("jit__prefill_fwd_only(7)", 0.5, 0.2),
               ("jit__scatter(9)", 0.7, 0.1),
               ("jit__decode_k_paged(3)", 0.8, 0.2)]
    run = _cohere_run(cell, by_op, modules,
                      _cohere_prefills([100.1, 100.5]))
    read = {n: spec.load_reader(n).read(run) for n in COHERE_NEW_METRICS}
    fl, by = cell.family.flash_fwd_cost(run["model"], [6000, 6000])
    assert read["kernel.flash_fwd_roofline.closed"] == pytest.approx(
        100 * peaks.roofline_s(fl, by, "TPU v5 lite")[0] / 0.05)
    # both kernels over the prefill programs' time, the scatter's counted
    assert read["model.attn_share_of_prefill_pct.closed"] == \
        pytest.approx(100 * (0.05 + 0.09) / 0.5)
    assert all(0 < v < 100 for v in read.values())
    # a wave the trace's edge cuts is in the kernel's time and not in the
    # spans: the work is scaled by touching / inside
    cut = _cohere_run(cell, by_op, modules,
                      _cohere_prefills([99.95, 100.1, 100.5]))
    assert spec.load_reader("kernel.flash_fwd_roofline.closed").read(cut) \
        == pytest.approx(1.5 * read["kernel.flash_fwd_roofline.closed"])
    capsys.readouterr()


@pytest.mark.parametrize("name", COHERE_NEW_METRICS)
def test_a_program_without_the_cohere_kernels_reads_nothing(cohere_cell,
                                                            name):
    """The parent's program under this benchmark, or another family's
    cell: no `swa_band` event, no `flash_fwd_cost`; the reader returns
    None and does not raise."""
    by_op = [["jit__decode_k_paged", "mla_attn.3 custom-call", 16, 0.05],
             ["jit__prefill_fwd_only", "flash_fwd.4 custom-call", 6, 0.02]]
    modules = [("jit__decode_k_paged(3)", 0.0, 0.2),
               ("jit__prefill_fwd_only(7)", 0.3, 0.2)]
    spans = _cohere_prefills([100.1])
    other = _cohere_run(spec.load_cell("sarvam105b.docs.closed"), by_op,
                        modules, spans)
    assert spec.load_reader(name).read(other) is None
    assert spec.load_reader(name).read(dict(other, trace=None)) is None
    # this family's cell on a program that lacks the kernels
    mine = _cohere_run(cohere_cell, by_op[:1], modules[:1], spans)
    assert spec.load_reader(name).read(mine) is None
    assert spec.load_reader(name).read(dict(mine, trace=None)) is None
