"""Tests of the yardstick itself (run with `pytest benchmarks/tests -q`,
outside tier-1)."""
from __future__ import annotations

import json
import os
import re
import statistics

import pytest

from benchmarks.harness import (flops, loadgen, peaks, spec, stats,
                                trace_reduce)

HERE = os.path.dirname(os.path.abspath(__file__))
MISTRAL = dict(hidden_size=4096, num_hidden_layers=16,
               num_attention_heads=32, num_key_value_heads=8, head_dim=128,
               intermediate_size=14336, vocab_size=32768)
CODESTRAL = dict(hidden_size=6144, num_hidden_layers=8,
                 num_attention_heads=48, num_key_value_heads=8, head_dim=128,
                 intermediate_size=16384, vocab_size=32768)
LLAMA = spec.load_family("llama")     # the family both shapes are of


# ------------------------------------------------------------ arithmetic
@pytest.mark.parametrize("vals,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 90, 90.1),
    ([7.0], 90, 7.0),
    ([10, 20], 90, 19.0),
])
def test_percentile(vals, q, want):
    assert stats.percentile(vals, q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_iqr_share_is_the_contracts_spread():
    vals = [100, 101, 99, 102, 98, 100]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_share(vals) == pytest.approx((q3 - q1) / 100.0)


def test_lateness_is_never_negative():
    assert stats.lateness_ms([0.0, 1.0, 2.0], [0.0005, 0.9, 2.25]) == \
        pytest.approx([0.5, 0.0, 250.0])


def test_union_and_gaps_count_nesting_once():
    iv = [(0.0, 1.0), (0.2, 0.4), (0.9, 1.5), (3.0, 4.0)]
    assert stats.union_length(iv) == pytest.approx(2.5)
    got = stats.gaps(iv, 0.0, 5.0)
    assert [g for pair in got for g in pair] == \
        pytest.approx([1.5, 3.0, 4.0, 5.0])


# -------------------------------------------------------------- schedule
def _traffic(name):
    return spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                       name + ".json"))


def _gaps(reqs):
    return sorted(y.due_s - x.due_s for x, y in zip(reqs, reqs[1:]))


def test_open_schedule_is_a_pure_function_of_the_seed():
    t = _traffic("chat-poisson")
    a = loadgen.open_schedule(t, 32768, 5_000_000_011, 40.0)
    b = loadgen.open_schedule(t, 32768, 5_000_000_011, 40.0)
    c = loadgen.open_schedule(t, 32768, 12, 40.0)
    assert [(r.due_s, r.prompt, r.out_len) for r in a] == \
        [(r.due_s, r.prompt, r.out_len) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # every seed offers the same SET of sizes and of gaps, in an order
    # of its own: the work is the same, no seed replays another
    def shape(reqs):
        return [(len(r.prompt), r.out_len) for r in reqs]

    assert sorted(shape(a)) == sorted(shape(c)) and shape(a) != shape(c)
    total = t["ramp_s"] + 40.0
    assert sorted(_gaps(a) + [total - a[-1].due_s]) == pytest.approx(
        sorted(_gaps(c) + [total - c[-1].due_s]), abs=1e-9)
    assert [r.due_s for r in a] != [r.due_s for r in c]
    total = t["ramp_s"] + 40.0
    assert len(a) == len(c) == round(t["arrivals"]["rate_rps"] * total)
    assert a[0].due_s == 0.0 and a[-1].due_s < total
    lo, hi = t["prompt_len"]["clip"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    assert all(0 <= tok < 32768 for r in a[:20] for tok in r.prompt)


def test_closed_pool_repeats_one_set_in_orders_of_the_seed():
    t = _traffic("batch-closed")
    k = t["population"]
    a = loadgen.closed_pool(t, 32768, 3)
    c = loadgen.closed_pool(t, 32768, 2_147_483_659)
    assert len(a) == len(c) == t["pool_size"]

    def shape(reqs):
        return [(len(r.prompt), r.out_len) for r in reqs]

    # every round of `population` requests is the same set, so a run
    # that gets further through the pool meets the same sizes
    rounds = [sorted(shape(a[i:i + k])) for i in range(0, 4 * k, k)]
    assert all(r == rounds[0] for r in rounds)
    assert sorted(shape(c[:k])) == rounds[0]
    assert shape(a[:k]) != shape(c[:k]) and shape(a[:k]) != shape(a[k:2 * k])


def test_rate_override():
    code = _traffic("code-poisson")
    o = loadgen.open_schedule(code, 32768, 3, 10.0, rate_rps=2.0)
    assert len(o) == round(2.0 * (10.0 + code["ramp_s"]))


def test_generator_takes_bursts_and_sharing_from_the_file():
    t = dict(_traffic("chat-poisson"),
             arrivals={"process": "gamma", "cv": 3.0, "rate_rps": 5.0},
             sharing={"prefix_len": 40, "share": 1.0})
    reqs = loadgen.open_schedule(t, 1000, 1, 40.0)
    gaps = _gaps(reqs)
    cv = statistics.pstdev(gaps) / statistics.mean(gaps)
    assert cv > 1.8                       # burstier than Poisson (cv 1)
    assert len({tuple(r.prompt[:33]) for r in reqs}) == 1


def test_open_loop_times_from_due_and_reports_failures():
    reqs = [loadgen.Request(i, 0.02 * i, [1, 2, 3], 4) for i in range(5)]

    def send(r, clock):
        if r.idx == 3:
            raise RuntimeError("refused")
        r.first_s = r.last_s = clock.now()
        r.n_tokens = 4

    clock = loadgen.Clock()
    loadgen.run_open(reqs, send, clock, stop_s=0.07, drain_s=2.0)
    assert [r.sent_s is not None for r in reqs] == [True] * 4 + [False]
    assert all(r.sent_s >= r.due_s for r in reqs[:4])
    assert reqs[3].error.startswith("RuntimeError") and not reqs[3].ok
    assert reqs[0].ok


# ----------------------------------------------------------------- flops
def test_param_counts_of_both_shapes():
    assert LLAMA.param_count(MISTRAL) == 3_758_231_552
    assert LLAMA.param_count(CODESTRAL) == 3_523_319_808
    # by hand, Mistral d16: embed + head 2*32768*4096; a layer:
    # 4096*4096*2 (wq, wo) + 2*4096*1024 (wk, wv) + 3*4096*14336 + 2*4096
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert LLAMA.param_count(MISTRAL) == 2 * 32768 * 4096 + 16 * layer + 4096
    assert LLAMA.matmul_params(MISTRAL) == \
        32768 * 4096 + 16 * (layer - 2 * 4096)


def test_causal_attention_is_counted_once():
    assert flops.causal_pairs(4) == 10
    pairs = 4096 * 4097 // 2
    assert flops.attn_flops_fwd(pairs, MISTRAL) == 4.0 * pairs * 32 * 128
    assert flops.attn_flops_fwd(pairs, CODESTRAL) == 4.0 * pairs * 48 * 128
    full = 4.0 * 4096 * 4096 * 32 * 128          # unmasked
    assert flops.attn_flops_fwd(pairs, MISTRAL) / full == \
        pytest.approx(0.5, abs=2e-4)


def test_train_flops_by_hand_no_recompute():
    m = dict(MISTRAL, num_hidden_layers=20)
    n = LLAMA.matmul_params(m)
    attn = 3 * 4.0 * 4 * (4096 * 4097 // 2) * 32 * 128 * 20
    want = 6.0 * n * 4 * 4096 + attn
    assert flops.train_flops_per_step(LLAMA, m, 4, 4096) == \
        pytest.approx(want)
    assert want == pytest.approx(0.49e15, rel=0.05)   # the issue's 0.49 PFLOP


def test_kernel_costs():
    f, b = flops.flash_fwd_cost(CODESTRAL, [2048])
    assert f == 4.0 * (2048 * 2049 // 2) * 48 * 128
    assert b == 2.0 * 2048 * 128 * (2 * 48 + 2 * 8)
    f, b = flops.paged_attn_cost(MISTRAL, [100, 300])
    assert b == 2.0 * 400 * 2 * 8 * 128 and f == 4.0 * 400 * 32 * 128
    f2, _ = flops.flash_bwd_cost(MISTRAL, 1, 2048)
    assert f2 == 2.5 * flops.flash_fwd_cost(MISTRAL, [2048])[0]
    assert LLAMA.decode_step_bytes(MISTRAL) == \
        2.0 * LLAMA.matmul_params(MISTRAL)


# ----------------------------------------------------------------- peaks
def test_peaks_raise_on_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.roofline_s(1.0, 1.0, "cpu")
    t, bound = peaks.roofline_s(197e12, 1.0, "TPU v5 lite")
    assert t == pytest.approx(1.0) and bound == "compute"
    t, bound = peaks.roofline_s(1.0, 819e9, "TPU v5 lite")
    assert t == pytest.approx(1.0) and bound == "memory"


# ----------------------------------------------------------------- trace
def test_self_time_and_idle_share_on_known_events():
    # a `while` of 10 ms enclosing two ops of 3 ms, then a gap of 5 ms,
    # then a kernel of 5 ms: busy 15 of 20 ms, idle share 25 %
    dev = {"ops": [("while.1", 0.000, 0.010, None),
                   ("fusion.2", 0.001, 0.003, None),
                   ("custom-call.3", 0.005, 0.003, None),
                   ("custom-call.3", 0.015, 0.005, None)],
           "modules": [("jit__decode_k_paged(42)", 0.000, 0.010, None),
                       ("jit__prefill_fwd_only(7)", 0.015, 0.005, None)]}
    red = trace_reduce.reduce_device(dev, 0.0, 0.020)
    assert red["busy_s"] == pytest.approx(0.015)
    assert 1 - red["busy_s"] / 0.020 == pytest.approx(0.25)
    by = {(m, n): (c, t) for m, n, c, t in red["by_op"]}
    assert by[("jit__decode_k_paged", "while.1")] == (1, pytest.approx(0.004))
    assert by[("jit__decode_k_paged", "custom-call.3")] == \
        (1, pytest.approx(0.003))
    assert by[("jit__prefill_fwd_only", "custom-call.3")] == \
        (1, pytest.approx(0.005))
    assert red["gaps"][0] == pytest.approx((0.005, 0.010, 0.015))
    whole = {"devices": [red], "window_s": 0.020, "start_wall_s": 100.0}
    assert trace_reduce.op_time(whole, "decode", "custom-call") == \
        (1, pytest.approx(0.003))
    assert trace_reduce.module_durations(whole, "decode_k") == [0.010]
    assert trace_reduce.top_ops(whole, 1)[0][0] == \
        "jit__prefill_fwd_only/custom-call.3"
    spans = [{"name": "llm.prefill", "t0": 100.009, "t1": 100.016}]
    assert trace_reduce.attribute_gaps(whole, spans, 1) == \
        [["llm.prefill", pytest.approx(0.005)]]


def test_reduction_reads_the_recorded_trace():
    """A small trace recorded with jax.profiler and kept here: parsing
    it through ProfileData gives the idle share and op time that reading
    its events by hand gives."""
    path = os.path.join(HERE, "data", "recorded.xplane.pb")
    want = spec.load_json(os.path.join(HERE, "data", "recorded.expect.json"))
    red = trace_reduce.reduce(path)
    assert red["n_devices"] == want["n_devices"]
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    n, t = trace_reduce.op_time(red, want["program"], want["op"])
    assert (n, t) == (want["op_count"], pytest.approx(want["op_s"], rel=1e-9))


# ------------------------------------------------------------------ data
def test_every_name_resolves_and_uses_allowed_characters():
    bench = spec.benchmark_json()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metrics = bench["end_to_end"] + bench["per_layer"]
    for group in (bench["configs"], bench["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
        assert all(spec.NAME_RE.match(n) for n in names), names
    assert all(spec.NAME_RE.match(w["traffic"]) for w in bench["workloads"])
    assert all(re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
               and m["better"] in ("lower", "higher")
               and m["source"] in spec.SOURCES for m in metrics)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    assert all(0 < m["bound"] <= 0.1 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.kind in ("serve", "train")
        assert cell.family is not None and cell.family_name == "llama"
        assert cell.loop in ("open", "closed", "steps")
        assert cell.config["reduced"] == ["num_hidden_layers"]
        assert {"source", "assumed", "stands_for"} <= set(cell.config)
        assert len(w["why"]) <= 200
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in bench["per_layer"]:
        reader = spec.load_reader(m["name"])
        assert (reader.LAYER, reader.SOURCE, reader.MOVES, reader.UNIT,
                reader.BETTER) == (m["layer"], m["source"], m["moves"],
                                   m["unit"], m["better"])
        # reported only in cells that report the metric it moves
        moved = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in moved.get("workloads", cells), (m["name"], c)
    assert sorted(spec.list_readers()) == sorted(
        m["name"] for m in bench["per_layer"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    for root, _, files in os.walk(spec.BENCH_DIR):
        if "__pycache__" in root:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), spec.ROOT)
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_a_cell_of_each_kind_is_added_by_files_alone(tmp_path, monkeypatch):
    """A throw-away configuration of a second model family, traffic mix,
    cell and per-layer metric: files and BENCHMARK.json entries only."""
    bdir = tmp_path / "benchmarks"
    for d in ("configs", "traffic"):
        (bdir / d).mkdir(parents=True)
    cfg = spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "mistral-7b-v0.3-d16.json"))
    cfg["num_hidden_layers"] = 12
    # the family file of a later PR (`data/family_example.py`): named by
    # the configuration, which spells its keys the family's way
    cfg.update(family="example", norm_eps=cfg.pop("rms_norm_eps"),
               rope_parameters={"rope_theta": cfg.pop("rope_theta")},
               layer_types=["conv", "conv", "full_attention"] * 4)
    real_family = spec.family_path
    monkeypatch.setattr(
        spec, "family_path",
        lambda n: os.path.join(HERE, "data", "family_example.py")
        if n == "example" else real_family(n))
    (bdir / "configs" / "extra-d12.json").write_text(json.dumps(cfg))
    tr = dict(_traffic("chat-poisson"),
              arrivals={"process": "gamma", "cv": 3.0, "rate_rps": 7.0})
    (bdir / "traffic" / "extra-burst.json").write_text(json.dumps(tr))
    bench = spec.benchmark_json()
    bench["configs"].append({"name": "extra-d12", "source": cfg["source"],
                             "file": "benchmarks/configs/extra-d12.json",
                             "reduced": ["num_hidden_layers"], "why": "x"})
    bench["workloads"].append({"name": "extra.burst", "config": "extra-d12",
                               "traffic": "extra-burst", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "tpot_p50_ms":
            m["workloads"].append("extra.burst")
    bench["per_layer"].append(
        {"name": "extra.completed", "unit": "requests", "better": "higher",
         "source": "program_counter", "layer": "engine loop",
         "moves": "tpot_p50_ms", "workloads": ["extra.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    metric = tmp_path / "extra.completed.py"
    metric.write_text(
        'LAYER = "engine loop"\nSOURCE = "program_counter"\n'
        'MOVES = "tpot_p50_ms"\nUNIT = "requests"\nBETTER = "higher"\n\n\n'
        'def read(run):\n'
        '    s0, s1 = run["stats"]\n'
        '    return s1["completed"] - s0["completed"]\n')
    real = spec.metric_path
    monkeypatch.setattr(spec, "metric_path",
                        lambda n: str(metric) if n == "extra.completed"
                        else real(n))
    cell = spec.load_cell("extra.burst", root=str(tmp_path))
    assert cell.config["num_hidden_layers"] == 12
    assert cell.family_name == "example"
    model = cell.family.published(cell.config)
    assert model["rope_parameters"] == {"rope_theta": 1000000.0}
    assert cell.family.kernel_layers(model, "paged_attn") == 4    # of 12
    assert cell.family.param_count(model) == LLAMA.param_count(
        dict(MISTRAL, num_hidden_layers=12))
    assert cell.traffic["arrivals"]["process"] == "gamma"
    assert {m["name"] for m in cell.end_to_end} == \
        {"tpot_p50_ms", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["extra.completed"]
    out = spec.read_per_layer(cell, {"stats": ({"completed": 3},
                                               {"completed": 10})})
    assert out == {"extra.completed": {"value": 7.0, "unit": "requests"}}
    reqs = loadgen.open_schedule(cell.traffic, cfg["vocab_size"], 1, 10.0)
    assert len(reqs) == round(7.0 * (10.0 + tr["ramp_s"]))


def test_a_reader_with_nothing_to_read_is_left_out():
    cell = spec.load_cell("mistral7b.chat.steady")
    run = {"cell": cell, "setup": {"serve_run_s": 5.0}, "spans": [],
           "requests": [], "trace": None, "window_wall": (0.0, 1.0),
           "engine": {"steps_per_sync": 8}, "e2e": {}}
    assert spec.read_per_layer(cell, run) == \
        {"setup.serve_run_s": {"value": 5.0, "unit": "s"}}
