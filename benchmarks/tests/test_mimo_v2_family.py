"""The model family `mimo_v2` through the harness's seam: the cell
`mimov2flash.docs.closed` is found by files and `BENCHMARK.json` entries
alone, the configuration holds the catalog row's numbers but for what
`reduced` names, the family file answers everything the harness asks
(without importing jax at load, and stopping with a sentence on a checkout
whose program cannot serve it), its counts are the program's own at the
published widths and equal the arithmetic the configuration file states,
its cost functions equal a hand count, its judge passes a sound program
and fails the seven controls, `--rehearse` walks the cell on the CPU, and
the metrics the cell brings read a synthetic run."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import peaks, spec

MIMO_CELL = "mimov2flash.docs.closed"
MIMO_CONFIG = "mimo-v2-flash-ep16"
MIMO_NEW_METRICS = ("model.paged_attn_share_of_decode_pct.closed",
                    "kernel.paged_attn_roofline.closed",
                    "kernel.swa_band_roofline.closed")
MIMO_SHARED_METRICS = ("kernel.swa_attn_roofline.closed",
                       "model.swa_share_of_decode_pct.closed",
                       "engine.swa_attended_pct.closed")


@pytest.fixture(scope="module")
def mimo_cell():
    return spec.load_cell(MIMO_CELL)


def _mimo_config() -> dict:
    return dict(spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                            MIMO_CONFIG + ".json")))


def _mimo_catalog_row() -> dict | None:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    return next(r for r in rows if r["name"] == "MiMo-V2-Flash")


# ------------------------------------------ the cell, by files alone
def test_the_mimo_cell_is_found_by_its_files(mimo_cell):
    cell = mimo_cell
    assert (cell.config_name, cell.traffic_name, cell.chips, cell.kind,
            cell.loop, cell.family_name) == (
        MIMO_CONFIG, "docs-closed-96", 1, "serve", "closed", "mimo_v2")
    # the same traffic and engine as dots3's cell: they differ by model
    other = spec.load_cell("dots3note.docs.closed")
    assert other.traffic_name == cell.traffic_name
    assert other.config["engine"] == cell.config["engine"]
    reported = {m["name"] for m in cell.per_layer}
    assert set(MIMO_NEW_METRICS) | set(MIMO_SHARED_METRICS) <= reported
    assert {"kernel.moe_gmm_roofline.closed", "engine.lanes_live.closed",
            "engine.moe_rows_per_expert_hit.closed",
            "model.moe_gmm_share_of_decode_pct.closed",
            "model.decode_step_ms.closed",
            "model.prefill_share_of_device_pct.closed",
            "engine.stall_ms_in_window.closed",
            "setup.program_build_s"} <= reported
    # dots3's banded reader reads `flash_fwd`, which here is the global
    # layers' alone: the band has a name and a reader of its own
    assert "kernel.swa_prefill_roofline.closed" not in reported
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    bench = spec.benchmark_json()
    assert [w["name"] for w in bench["workloads"]].index(MIMO_CELL) == 10
    assert sum(w["chips"] == 4 for w in bench["workloads"][:11]) == 1
    for name in MIMO_NEW_METRICS:
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [MIMO_CELL]
        reader = spec.load_reader(name)
        assert (reader.LAYER, reader.SOURCE, reader.MOVES, reader.UNIT,
                reader.BETTER) == (entry["layer"], entry["source"],
                                   entry["moves"], entry["unit"],
                                   entry["better"])


def test_the_mimo_configuration_holds_the_catalog_row_but_for_the_cut(
        mimo_cell):
    row = _mimo_catalog_row()
    if row is None:
        pytest.skip("no model-configs catalog on this machine")
    cfg = mimo_cell.config
    assert cfg["source"] == row["source_url"]
    (entry,) = [c for c in spec.benchmark_json()["configs"]
                if c["name"] == mimo_cell.config_name]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"]
    assert entry["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v and cfg[k] != v
        else:
            assert cfg[k] == v, k
    # the leading dense layer and one whole period, as published
    assert cfg["hybrid_layer_pattern"] == \
        row["config"]["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"] == row["config"]["moe_layer_freq"][:7]
    assert cfg["n_routed_experts"] * cfg["expert_parallel"]["chips"] == \
        cfg["published"]["n_routed_experts"]
    assert cfg["expert_parallel"] == {"chips": 16, "rank": 0}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["vocab_size"] % 128 == 0
    assert set(cfg["assumed"]) >= {
        "rotary", "window", "attention_chunk_size", "sink", "value_scale",
        "routed_scaling_factor", "n_shared_experts", "router", "k_store",
        "not_served"}
    assert "16-way" in cfg["stands_for"]
    assert set(spec.load_family("mimo_v2", "serve").KEYS) == set(
        row["config"])


# ----------------------------------------------- the family file itself
def test_the_mimo_family_loads_without_jax():
    code = ("import sys; from benchmarks.harness import spec; "
            "f = spec.load_family('mimo_v2', 'serve'); "
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules; "
            "print(f.REFERENCE_GAP_TOL)")
    out = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert float(out.stdout) > 0


def test_a_checkout_without_mimo_stops_with_a_sentence(monkeypatch,
                                                       tmp_path):
    """The parent of PR 52 with this benchmark laid over it: the family
    file stops in the driver process, before a cluster is started."""
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        spec.load_family("mimo_v2", "serve")
    assert "ray_tpu.models.mimo_v2" in str(e.value)
    assert "cannot serve" in str(e.value)


def test_mimo_counts_are_the_programs_at_the_published_widths(mimo_cell):
    """Abstract shapes: nothing is allocated."""
    import jax

    fam = mimo_cell.family
    model = fam.published(mimo_cell.config)
    cfg = fam.program_config(model, max_seq=9216)
    shapes = jax.eval_shape(
        lambda: fam.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert fam.param_count(model) == n == 3_429_955_392
    assert (cfg.n_layers, cfg.count("sliding_attention"), cfg.window,
            cfg.ring_rows, cfg.experts_held, cfg.n_experts, cfg.rope_dim,
            cfg.k_store) == (7, 5, 128, 128, (0, 16), 256, 64, 256)
    assert (cfg.rope_theta, cfg.swa_rope_theta, cfg.value_scale) == (
        5.0e6, 1.0e4, 0.707)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.swa_n_kv_heads,
            cfg.qk_head_dim, cfg.v_head_dim) == (64, 4, 8, 192, 128)
    # the ISSUE's count at 2 B a parameter, by part
    assert fam._attn_params(model, "") == 89_128_960
    assert fam._attn_params(model, "swa_") == 94_371_840
    # a token multiplies half a held expert a routed layer (8 x 16 / 256)
    one = 3 * 4096 * 2048
    assert fam.matmul_params(model) == fam._non_expert_matmul_params(
        model) + 6 * one // 2
    assert fam.decode_step_bytes(model) == 2.0 * (
        fam._non_expert_matmul_params(model) + 6 * 16 * one)
    assert (fam.kernel_layers(model, "swa_attn"),
            fam.kernel_layers(model, "paged_attn"),
            fam.kernel_layers(model, "moe_gmm"),
            fam.kernel_layers(model, "flash_fwd"),
            fam.kernel_layers(model, "swa_band"),
            fam.kernel_layers(model, "mla_attn")) == (5, 2, 6, 2, 5, 0)
    assert fam.vocab_size(model) == 19072 and model["num_experts"] == 16
    # the seam's own arithmetic agrees with the family's
    from ray_tpu.models import mimo_v2
    streamed, multiplied = mimo_v2.prefill_params(cfg)
    d = 4096
    assert streamed == fam._non_expert_matmul_params(model) - 19072 * d \
        + 6 * 16 * one
    assert multiplied == fam.matmul_params(model) - 19072 * d
    # the cache beside the weights: a K (stored 256 wide) and a V leaf a
    # GLOBAL layer, a K and a V ring a window layer (abstract)
    cache = jax.eval_shape(lambda: mimo_v2.init_paged_cache(
        cfg, 64, 1153, 512))
    nbytes = lambda t: sum(a.size * a.dtype.itemsize       # noqa: E731
                           for a in jax.tree.leaves(t))
    assert len(cache["k"]) == len(cache["v"]) == 2
    assert nbytes(cache["k"]) + nbytes(cache["v"]) \
        == 2 * 1153 * 512 * 4 * (256 + 128) * 2
    assert nbytes(cache["state"]) == 64 * 5 * 128 * 8 * (256 + 128) * 2
    resident = 2 * n + nbytes(cache["k"]) + nbytes(cache["v"]) \
        + nbytes(cache["state"])
    assert 10.73e9 < resident < 10.75e9     # the file's 10.74 GB


@pytest.mark.parametrize("change,match", [
    (dict(add_swa_attention_sink_bias=False), "sink"),
    (dict(add_full_attention_sink_bias=True), "sink"),
    (dict(topk_method="greedy"), "router"),
    (dict(num_hidden_layers=6), "layer lists"),
    (dict(n_shared_experts=1), "shared expert"),
    (dict(swa_head_dim=128), "other widths"),
    (dict(tie_word_embeddings=True), "tied head")])
def test_mimo_program_config_refuses_what_the_program_cannot_express(
        mimo_cell, change, match):
    model = mimo_cell.family.published(mimo_cell.config)
    with pytest.raises(ValueError, match=match):
        mimo_cell.family.program_config(dict(model, **change), max_seq=64)


def test_mimo_rehearsal_shrinks_both_kinds_of_layer():
    fam = spec.load_family("mimo_v2", "serve")
    cfg = _mimo_config()
    fam.rehearsal(cfg)
    pc = fam.program_config(fam.published(cfg), max_seq=64)
    assert (pc.dim, pc.n_layers, pc.n_experts, pc.experts_held, pc.window,
            pc.ring_rows, pc.rope_dim) == (64, 3, 8, (0, 4), 9, 16, 8)
    assert set(pc.layer_types) == {"full_attention", "sliding_attention"}
    assert pc.n_kv_heads != pc.swa_n_kv_heads


def test_mimo_costs_by_hand(mimo_cell):
    fam = mimo_cell.family
    model = fam.published(mimo_cell.config)
    fl, by = fam.swa_attn_cost(model, rows=128 * 57)
    assert by == 2 * 8 * 320 * 128 * 57             # 5,120 B a row
    assert fl == 2.0 * 64 * 320 * 128 * 57
    fl, by = fam.paged_attn_cost(model, rows=6500 * 57)
    assert by == 2 * 4 * 320 * 6500 * 57            # 2,560 B a row
    assert fl == 2.0 * 64 * 320 * 6500 * 57
    # the band: a prompt of 8,192 scores 128 rows a query but for the
    # first 127 queries
    fl, by = fam.swa_band_cost(model, [8192, 100])
    pairs = 128 * 129 // 2 + (8192 - 128) * 128 + 100 * 101 // 2
    assert fl == 2.0 * pairs * 64 * 320
    assert by == 2.0 * 8292 * 320 * (64 + 8)


# --------------------------------------------------- the judge (tiny, CPU)
@pytest.fixture(scope="module")
def mimo_judged():
    """A debug-sized model served by the program's own prefill and decode
    (not the engine: `tests/test_mimo_v2.py` holds that) and judged."""
    import jax
    import numpy as np

    fam = spec.load_family("mimo_v2", "serve")
    cfg = _mimo_config()
    fam.rehearsal(cfg)
    model = fam.published(cfg)
    pc = fam.program_config(model, max_seq=256)
    params = jax.jit(lambda k: fam.init_params(k, pc))(
        jax.random.PRNGKey(5))
    tokens = np.random.default_rng(2).integers(0, 512, 150).tolist()
    return fam, model, params, tokens


def test_the_mimo_judge_passes_a_sound_program(mimo_judged):
    fam, model, params, tokens = mimo_judged
    b = fam.block_errors(params, tokens, model)
    assert b["block"][0] < fam.BLOCK_ERR_TOL, b["block"]
    assert b["ffn"][0] < fam.FFN_ERR_TOL, b["ffn"]
    assert b["rows"][0] < fam.ROW_ERR_TOL, b["rows"]
    assert b["edge"][0] < fam.EDGE_TOL, b["edge"]
    kinds = {k for k, *_ in b["by_block"]}
    assert {"global", "window", "global_decode_step", "window_decode_step",
            "page_k", "page_v", "tail_k", "tail_v", "pad_k", "ring_k",
            "ring_v", "ring_step_k", "ring_step_v", "ring_other_slots",
            "ffn", "head", "prefill.8", "prefill.10", "decode_step.8",
            "decode_step.10"} <= kinds


def _mimo_control(name, mp, fam):
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models import mimo_v2 as prog
    from ray_tpu.ops import window_attention as swa

    def config_with(change):
        """The program's config with `change(config)`'s fields replaced."""
        pc = fam.program_config

        def patched(m, max_seq, **kw):
            c = pc(m, max_seq, **kw)
            return dataclasses.replace(c, **change(c))
        mp.setattr(fam, "program_config", patched)

    def no_sink(sink):
        return jnp.full_like(sink, -1e30)

    if name == "sink_left_out":
        attn, ring = prog.attention, swa.kv_ring_attention

        def attn_without(q, k, v, **kw):
            if kw.get("sink") is not None:
                kw["sink"] = no_sink(kw["sink"])
            return attn(q, k, v, **kw)
        mp.setattr(prog, "attention", attn_without)
        mp.setattr(swa, "kv_ring_attention",
                   lambda q, k, v, bias, sink, *a, **kw: ring(
                       q, k, v, bias, no_sink(sink), *a, **kw))
    elif name in ("window_127", "window_129"):
        delta = -1 if name == "window_127" else 1
        config_with(lambda c: dict(
            window=c.window + delta,
            ring_rows=fam.ring_rows(c.window + delta)))
    elif name == "value_scale_left_out":
        config_with(lambda c: dict(value_scale=1.0))
    elif name == "global_head_grouping":
        # a window layer's queries grouped as a global layer's: twice as
        # many query heads a kv head, over the first half of the kv heads
        attn = prog.attention

        def grouped(q, k, v, **kw):
            if kw.get("sink") is not None:
                k, v = (a[:, :, :a.shape[2] // 2] for a in (k, v))
            return attn(q, k, v, **kw)
        mp.setattr(prog, "attention", grouped)
    elif name == "rotary_over_half_the_head":
        config_with(lambda c: dict(rope_dim=c.qk_head_dim // 2))
    elif name == "fp8_ring":
        fill, write = swa.kv_ring_from_rows, swa.kv_ring_write

        def fp8(a):
            return a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        mp.setattr(swa, "kv_ring_from_rows",
                   lambda rows, *a: fill(fp8(rows), *a))
        mp.setattr(swa, "kv_ring_write",
                   lambda ring, new, *a: write(ring, fp8(new), *a))
    elif name == "bf16_softmax":
        # both kinds' prefill attention with the scores, the exponentials,
        # their sum and the division in bfloat16 (the products accumulate
        # in float32, as the matrix unit does)
        import jax

        def bf16_softmax(q, k, v, *, sm_scale, lengths=None, window=None,
                         sink=None):
            del lengths         # causal: a padded row reaches no true one
            rep = q.shape[2] // k.shape[2]
            k, v = (jnp.repeat(a, rep, axis=2) for a in (k, v))
            s = (jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
                 * sm_scale).astype(jnp.bfloat16)
            at = jnp.arange(q.shape[1])
            keep = at[:, None] >= at[None, :]
            if window is not None:
                keep &= at[:, None] - at[None, :] < window
            s = jnp.where(keep, s, -jnp.inf)
            if sink is not None:
                col = jnp.broadcast_to(
                    sink.astype(jnp.bfloat16)[None, :, None, None],
                    s.shape[:3] + (1,))
                s = jnp.concatenate([s, col], axis=-1)
            p = jax.nn.softmax(s, axis=-1)[..., :q.shape[1]]
            assert p.dtype == jnp.bfloat16
            return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                              preferred_element_type=jnp.float32
                              ).astype(q.dtype)
        mp.setattr(prog, "attention", bf16_softmax)


def _mimo_correct(fam, params, tokens, model, mp, capsys):
    """`correct` of one request as the HARNESS decides it
    (`serve_cell._check_outputs`: the judge's worst number against the
    family's one limit), and the judge's own line.  The served token is
    the reference's choice after `tokens`, so reading (1) is 0 and the
    blocks on `tokens` decide."""
    import jax.numpy as jnp

    from benchmarks.harness.refs import mimo_v2 as ref

    served = [int(jnp.argmax(ref.logits(params, tokens, model, last=1)[0]))]
    judge = fam.reference()
    mp.setattr(judge, "_seen", {})
    mp.setattr(judge, "_blocks_done", [])
    capsys.readouterr()
    gaps = judge.teacher_forced_gaps(params, tokens, served, model)
    line = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    return max(gaps) <= fam.REFERENCE_GAP_TOL, line


def test_the_mimo_judge_calls_a_sound_program_correct(mimo_judged,
                                                      monkeypatch, capsys):
    fam, model, params, tokens = mimo_judged
    correct, line = _mimo_correct(fam, params, tokens, model, monkeypatch,
                                  capsys)
    assert correct, line
    assert line["mean_token_gap"] == 0.0 and "worst_edge" in line


@pytest.mark.parametrize("name,held_by", [
    ("sink_left_out", "block"), ("window_127", "edge"),
    ("window_129", "edge"), ("value_scale_left_out", "block"),
    ("global_head_grouping", "block"),
    ("rotary_over_half_the_head", "block"), ("fp8_ring", "row")])
def test_the_mimo_judge_fails_a_control(mimo_judged, monkeypatch, capsys,
                                        name, held_by):
    """Each control through the judge's own fold and the harness's own
    comparison: `correct` comes out false, the limit named being one it
    is over.  Rotary over 96 of 192 columns at the published widths is 12
    of 24 here: half the head."""
    fam, model, params, tokens = mimo_judged
    monkeypatch.setattr(fam, "_BLOCKS", {})
    _mimo_control(name, monkeypatch, fam)
    correct, line = _mimo_correct(fam, params, tokens, model, monkeypatch,
                                  capsys)
    assert not correct, line
    reading = "worst_edge" if held_by == "edge" else f"worst_{held_by}_err"
    assert line[reading][0] > line[f"{held_by}_limit"], line


def test_a_bf16_softmax_is_within_a_bfloat16_blocks_own_rounding(
        mimo_judged, monkeypatch):
    """The one control the judge does NOT fail, held so that the record
    stays true: a block's output is rounded to bfloat16 once whatever the
    softmax was computed in, and rounding the scores and the
    probabilities as well adds an error of that same size (the worst
    position of the attention halves reads 1.1 x the sound program's
    here, under a limit 1.6 x the sound's).  No limit on a bfloat16
    program's blocks can tell the two apart."""
    fam, model, params, tokens = mimo_judged
    sound = fam.block_errors(params, tokens, model)["block"][0]
    monkeypatch.setattr(fam, "_BLOCKS", {})
    _mimo_control("bf16_softmax", monkeypatch, fam)
    got = fam.block_errors(params, tokens, model)["block"][0]
    assert sound < got < min(1.5 * sound, fam.BLOCK_ERR_TOL), (sound, got)


# ------------------------------------------------- rehearsal, on the CPU
@pytest.mark.time_limit(420)
def test_the_mimo_cell_rehearses_on_the_cpu():
    """The walk is what is held (the last line's shape), not how many
    requests END inside so short a window nor which of the replica's own
    lines were forwarded before the teardown: both are the machine's
    load."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", MIMO_CELL,
         "--seed", "2147483659", "--seconds", "6", "--trace", "0",
         "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=400)
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0   # never passes
    assert last["metrics"]["rehearsal.setup_s"]["value"] > 0
    assert '"a rehearsal is never correct"' in out.stdout


# ------------------------------------------ the metrics the cell brings
def _mimo_run(cell, by_op, modules, s0, s1, spans=()):
    red = {"window_s": 1.0, "busy_s": 1.0, "start_wall_s": 100.0,
           "t_lo": 0.0, "t_hi": 1.0,
           "devices": [{"by_op": by_op, "modules": modules, "gaps": [],
                        "busy_s": 1.0}]}
    return {"cell": cell, "model": cell.family.published(cell.config),
            "engine": {"steps_per_sync": 8}, "trace": red,
            "spans": list(spans), "stats": ({"loop": s0}, {"loop": s1}),
            "device": {"kind": "TPU v5 lite"}}


def _mimo_dispatches(times, lanes):
    return [{"name": "llm.loop.decode_dispatch", "t0": t, "t1": t + 0.002,
             "attrs": {"lanes": lanes, "steps": 8,
                       "swa_rows_attended": 8 * lanes * 5 * 128,
                       "swa_rows_context": 8 * lanes * 5 * 6500,
                       "swa_lane_steps": 8 * lanes * 5}}
            for t in times]


def _mimo_prefills(times, tokens=6000):
    return [{"name": "llm.prefill", "t0": t, "t1": t + 0.1, "tid": i,
             "attrs": {"prompt_tokens": tokens}}
            for i, t in enumerate(times)]


def _mimo_counters(windows, lanes):
    return {"decode_steps": windows * 8,
            "attn_ctx_rows": windows * 8 * lanes * 6500,
            "swa_rows_attended": windows * 8 * lanes * 5 * 128,
            "swa_rows_context": windows * 8 * lanes * 5 * 6500}


def test_the_mimo_readers_on_a_synthetic_run(mimo_cell, capsys):
    cell = mimo_cell
    by_op = [
        ["jit__decode_k_paged", "swa_attn.7 custom-call bf16[64,8,8,128]",
         160, 0.03],
        ["jit__decode_k_paged", "paged_attn.3 custom-call bf16[64,4,16,128]",
         64, 0.2],
        ["jit__decode_k_paged", "moe_gmm.5 custom-call", 384, 0.3],
        ["jit__decode_k_paged", "fusion.12", 64, 0.27],
        ["jit__prefill_fwd_only", "flash_fwd.4 custom-call", 4, 0.05],
        ["jit__prefill_fwd_only", "swa_band.2 custom-call", 10, 0.03],
    ]
    modules = [("jit__decode_k_paged(3)", 0.2 * i, 0.2) for i in range(4)]
    inside = [100.0 + 0.2 * i for i in range(4)]
    run = _mimo_run(cell, by_op, modules, _mimo_counters(10, 52),
                    _mimo_counters(110, 52),
                    _mimo_dispatches(inside, 64)
                    + _mimo_dispatches([99.5, 101.5], 30)
                    + _mimo_prefills([100.1, 100.5]))
    names = MIMO_NEW_METRICS + MIMO_SHARED_METRICS
    read = {n: spec.load_reader(n).read(run) for n in names}
    assert read["model.swa_share_of_decode_pct.closed"] == \
        pytest.approx(100 * 0.03 / 0.8)
    assert read["model.paged_attn_share_of_decode_pct.closed"] == \
        pytest.approx(100 * 0.2 / 0.8)
    assert read["engine.swa_attended_pct.closed"] == \
        pytest.approx(100 * 128 / 6500)
    fl, by = cell.family.swa_attn_cost(run["model"], 160 * 64 * 128)
    assert read["kernel.swa_attn_roofline.closed"] == pytest.approx(
        100 * peaks.roofline_s(fl, by, "TPU v5 lite")[0] / 0.03)
    # 4 traced windows x 8 steps of 52 lanes x 6,500 rows, in 2 layers
    fl, by = cell.family.paged_attn_cost(run["model"], 32 * 52 * 6500)
    assert read["kernel.paged_attn_roofline.closed"] == pytest.approx(
        100 * peaks.roofline_s(2 * fl, 2 * by, "TPU v5 lite")[0] / 0.2)
    fl, by = cell.family.swa_band_cost(run["model"], [6000, 6000])
    assert read["kernel.swa_band_roofline.closed"] == pytest.approx(
        100 * peaks.roofline_s(5 * fl, 5 * by, "TPU v5 lite")[0] / 0.03)
    assert all(0 < read[n] < 100 for n in names)
    capsys.readouterr()


@pytest.mark.parametrize("name", MIMO_NEW_METRICS)
def test_a_program_without_the_mimo_kernels_reads_nothing(mimo_cell, name):
    """The parent's program under this benchmark, or another family's
    cell: no `swa_band` event, no `paged_attn_cost`; the reader returns
    None and does not raise."""
    by_op = [["jit__decode_k_paged", "mla_attn.3 custom-call", 16, 0.05],
             ["jit__prefill_fwd_only", "flash_fwd.4 custom-call", 6, 0.02]]
    modules = [("jit__decode_k_paged(3)", 0.0, 0.2)]
    spans = _mimo_prefills([100.1])
    counters = ({"decode_steps": 1, "attn_ctx_rows": 10},
                {"decode_steps": 9, "attn_ctx_rows": 90})
    other = _mimo_run(spec.load_cell("sarvam105b.docs.closed"), by_op,
                      modules, *counters, spans)
    assert spec.load_reader(name).read(other) is None
    assert spec.load_reader(name).read(dict(other, trace=None)) is None
    # this family's cell on a program that lacks the kernel
    mine = _mimo_run(mimo_cell, by_op, modules, *counters, spans)
    assert spec.load_reader(name).read(mine) is None
