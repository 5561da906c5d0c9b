"""The readers of PR 50 (`harness/stall.py` and the four metric files
`engine.stall_ms_in_window.*`, `engine.gc_pause_ms_in_window.*`): found
by files and `BENCHMARK.json` entries alone, they read a synthetic run's
counters and spans, print the lines `stalls_in_window` and `gc_in_window`,
and find nothing to read, without raising, in the run of a program that
records neither (the parent of PR 50)."""
from __future__ import annotations

import json

import pytest

from benchmarks.harness import spec, stall

METRICS = {
    "engine.stall_ms_in_window.open": "tpot_p50_ms",
    "engine.stall_ms_in_window.closed": "serve_tok_s",
    "engine.gc_pause_ms_in_window.open": "tpot_p50_ms",
    "engine.gc_pause_ms_in_window.closed": "serve_tok_s",
}


def _stall_span(t0, t1, **attrs):
    return {"name": "llm.stall", "t0": t0, "t1": t1, "tid": "e",
            "attrs": {"stood_ms": (t1 - t0) * 1e3, **attrs}}


def _run(records=True, traced=True):
    """Between two readings of `stats` 60 s apart (wall 100 and 160): one
    stall of 829 ms inside `decode_sync`, the watcher 790 ms late behind a
    `serve-call` thread, over one 800 ms idle gap of the chip; and 40
    collections, 0.9 s, 0.7 of them one generation-2 pause under it."""
    loop0 = {"program_build_s": 50.0}
    loop1 = {"program_build_s": 50.0}
    spans = [{"name": "llm.loop.decode_sync", "t0": 130.0, "t1": 131.0,
              "tid": "e", "attrs": {"iter": 7}}]
    if records:
        loop0.update(stalls=12, stall_s=31.0, gc_pauses=500, gc_pause_s=2.0,
                     gc_by_generation={"0": {"pauses": 480, "pause_s": 0.5},
                                       "2": {"pauses": 4, "pause_s": 1.2}})
        loop1.update(stalls=13, stall_s=31.829, gc_pauses=540,
                     gc_pause_s=2.9,
                     gc_by_generation={"0": {"pauses": 519, "pause_s": 0.7},
                                       "2": {"pauses": 5, "pause_s": 1.9}})
        spans += [
            _stall_span(130.1, 130.929, phase="decode_sync", iter=7, nth=13,
                        trigger="late_wake", held="interpreter",
                        late_wake_ms=790.0, gc_ms=700.0,
                        by_thread_cpu_ms='[["serve-call", 801.5]]'),
            _stall_span(50.0, 52.0, phase="prefill_dispatch", iter=1, nth=4,
                        trigger="host_phase", held="engine",
                        build_ms=1990.0, by_thread_cpu_ms="[]"),  # warm-up
            {"name": "llm.gc_pause", "t0": 130.15, "t1": 130.85, "tid": "b",
             "attrs": {"generation": 2, "collected": 11, "uncollectable": 0,
                       "thread": "serve-call"}}]
    mk = (lambda w, loop: {"loop": loop, "threads": {
        "wall_s": w, "process_cpu_s": 0.0, "by_name": {}}})
    return {"spans": spans, "window_wall": (105.0, 156.0),
            "trace_wall": (125.0, 140.0),
            "stats": (mk(100.0, loop0), mk(160.0, loop1)),
            "trace": ({"start_wall_s": 125.5, "t_lo": 0.0, "t_hi": 6.0,
                       "window_s": 6.0, "busy_s": 5.1,
                       "devices": [{"busy_s": 5.1, "modules": [],
                                    "by_op": [],
                                    "gaps": [(0.8, 4.62, 5.42),
                                             (0.0997, 1.0, 1.0997)]}]}
                      if traced else None)}


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_is_found_by_its_file_and_its_entry(name):
    reader = spec.load_reader(name)
    (entry,) = [m for m in spec.benchmark_json()["per_layer"]
                if m["name"] == name]
    assert (entry["layer"], entry["source"], entry["moves"], entry["unit"],
            entry["better"]) == (reader.LAYER, reader.SOURCE, reader.MOVES,
                                 reader.UNIT, reader.BETTER) == (
        "engine loop", "program_counter", METRICS[name], "ms", "lower")
    # the cells that report the stood time report these too
    (stood,) = [m for m in spec.benchmark_json()["per_layer"]
                if m["name"] == "engine.stood_ms_per_window."
                + name.rsplit(".", 1)[1]]
    assert entry["workloads"] == stood["workloads"]
    for cell in entry["workloads"]:
        assert name in {m["name"] for m in spec.load_cell(cell).per_layer}


@pytest.mark.parametrize("name,want", [
    ("engine.stall_ms_in_window.closed", 829.0),
    ("engine.stall_ms_in_window.open", 829.0),
    ("engine.gc_pause_ms_in_window.closed", 900.0),
    ("engine.gc_pause_ms_in_window.open", 900.0),
])
def test_the_metric_reads_a_synthetic_run_and_nothing_on_a_parents(
        name, want, capsys):
    read = spec.load_reader(name).read
    assert read(_run()) == pytest.approx(want)
    assert len(_lines(capsys)) == 1
    # the parent of PR 50, and a run with no record at all
    assert read(_run(records=False)) is None
    assert read({"spans": [], "stats": ({}, {}), "trace": None}) is None
    assert read({}) is None
    assert not _lines(capsys)
    # read as the harness reads a cell's metrics: a number, its unit
    cell = spec.load_cell("sarvam105b.docs.closed" if "closed" in name
                          else "mistral7b.chat.steady")
    out = spec.read_per_layer(cell, {**_run(), "cell": cell})
    assert out[name] == {"value": pytest.approx(want), "unit": "ms"}
    assert name not in spec.read_per_layer(
        cell, {**_run(records=False), "cell": cell})


def test_the_line_stalls_in_window_sets_a_stall_beside_the_chips_gaps(
        capsys):
    assert stall.stall_ms_in_window(_run()) == pytest.approx(829.0)
    (line,) = _lines(capsys)
    assert line["step"] == "stalls_in_window"
    assert (line["stalls"], line["stall_s"]) == (1, pytest.approx(0.829))
    assert line["by_held_n_ms"] == {"interpreter": [1, pytest.approx(829.0)]}
    (row,) = line["spans"]                      # not the warm-up's
    assert (row["phase"], row["trigger"], row["held"], row["iter"]) == (
        "decode_sync", "late_wake", "interpreter", 7)
    assert row["at_s"] == pytest.approx(30.1)
    assert row["wall_ms"] == pytest.approx(829.0)
    assert row["by_thread_cpu_ms"] == [["serve-call", 801.5]]
    # the chip's 800 ms gap lies at wall 130.12-130.92, inside the stall
    assert row["device_idle_s"] == pytest.approx(0.8)
    assert row["in_measured_window"] and not row["in_profiler"]
    assert line["in_profiler_ms"] == 0.0
    assert line["counted_late"] == []
    # the warm-up's last compile, counted a wake after the first reading
    # (its `nth` says so: 13 of `loop.stalls` 12 -> 14): set aside, and
    # named; the one before it (`nth` 12) was counted before the reading
    run = _run()
    run["stats"][1]["loop"].update(stalls=14, stall_s=34.229)
    run["spans"][1]["attrs"]["nth"] = 14
    run["spans"] += [
        _stall_span(97.5, 99.9, phase="decode_dispatch", iter=3, nth=13,
                    trigger="host_phase", held="engine", build_ms=2390.0,
                    by_thread_cpu_ms="[]"),
        _stall_span(90.0, 93.0, phase="decode_dispatch", iter=2, nth=12,
                    trigger="host_phase", held="engine", build_ms=2990.0,
                    by_thread_cpu_ms="[]")]
    assert stall.stall_ms_in_window(run) == pytest.approx(829.0)
    (line,) = _lines(capsys)
    assert line["counted_late"] == [["decode_dispatch", pytest.approx(2400.0),
                                     2390.0, pytest.approx(-0.1)]]
    assert (line["stalls"], len(line["spans"])) == (2, 1)
    # a stall whose span the ring no longer holds stays in the number
    run["spans"] = [s for s in run["spans"] if s["attrs"].get("nth") != 13]
    assert stall.stall_ms_in_window(run) == pytest.approx(3229.0)
    _lines(capsys)
    # the same stall where no trace was taken
    stall.stall_ms_in_window(_run(traced=False))
    (line,) = _lines(capsys)
    assert line["spans"][0]["device_idle_s"] is None
    # and in a run whose profiler was still stopping when it struck: what
    # tracing costs, kept on the line and taken off the number
    run = _run()
    run["trace"]["t_hi"] = 4.0                  # traced to wall 129.5
    assert stall.stall_ms_in_window(run) == pytest.approx(0.0)
    (line,) = _lines(capsys)
    assert (line["stalls"], line["stall_s"]) == (1, pytest.approx(0.829))
    assert line["spans"][0]["in_profiler"]
    assert line["in_profiler_ms"] == pytest.approx(829.0)
    assert line["spans"][0]["device_idle_s"] is None
    # or was starting: the two seconds up to the traced stretch
    run = _run()
    run["trace"]["start_wall_s"] = 130.5
    assert stall.stall_ms_in_window(run) == pytest.approx(0.0)
    (line,) = _lines(capsys)
    assert line["spans"][0]["in_profiler"]
    assert line["in_profiler_ms"] == pytest.approx(829.0)
    assert line["spans"][0]["device_idle_s"] == pytest.approx(0.0)


def test_the_line_gc_in_window_says_which_generation_on_which_thread(capsys):
    assert stall.gc_pause_ms_in_window(_run()) == pytest.approx(900.0)
    (line,) = _lines(capsys)
    assert line["step"] == "gc_in_window"
    assert (line["pauses"], line["pause_s"]) == (40, pytest.approx(0.9))
    assert line["by_generation"] == {
        "0": {"pauses": 39, "pause_s": pytest.approx(0.2)},
        "2": {"pauses": 1, "pause_s": pytest.approx(0.7)}}
    assert line["spans"] == 1
    assert line["by_thread_n_s"] == {"serve-call": [1, pytest.approx(0.7)]}
    assert line["longest_ms"] == [[pytest.approx(700.0), 2, "serve-call", 11,
                                   pytest.approx(30.15)]]


def test_by_thread_rows_that_are_no_json_read_as_none():
    assert stall._rows('[["serve-call", 1.5]]') == [["serve-call", 1.5]]
    assert stall._rows("not json") == [] and stall._rows(None) == []
