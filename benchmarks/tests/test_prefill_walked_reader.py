"""The reader of PR 53 (`metrics/engine.prefill_walked_factor.closed.py`):
found by its file and its `BENCHMARK.json` entry alone, it reads the two
loop counters of a synthetic run, and finds nothing to read, without
raising, in the run of a program that lacks the counter (the parent of PR
53, or another family's cell)."""
from __future__ import annotations

import pytest

from benchmarks.harness import spec

NAME = "engine.prefill_walked_factor.closed"
CELL = "mimov2flash.docs.closed"


def _run(walked=True):
    """Between two readings of `stats`: 20 programs of 1 x 8192 over
    prompts of 6,216 positions on average, their position-wise halves
    walked to 6.7 chunks of 1,024 each."""
    loop0 = {"prefill_true_tokens": 50_000, "prefill_padded_tokens": 65_536}
    loop1 = {"prefill_true_tokens": 50_000 + 20 * 6216,
             "prefill_padded_tokens": 65_536 + 20 * 8192}
    if walked:
        loop0["prefill_walked_tokens"] = 57_344
        loop1["prefill_walked_tokens"] = 57_344 + 134 * 1024
    return {"stats": ({"loop": loop0}, {"loop": loop1})}


def test_the_walked_factor_is_found_by_its_files():
    (entry,) = [m for m in spec.benchmark_json()["per_layer"]
                if m["name"] == NAME]
    mod = spec.load_reader(NAME)
    assert (entry["layer"], entry["source"], entry["moves"], entry["unit"],
            entry["better"]) == (mod.LAYER, mod.SOURCE, mod.MOVES, mod.UNIT,
                                 mod.BETTER) == (
        "engine loop", "program_counter", "serve_tok_s", "x", "lower")
    assert entry["workloads"] == [CELL]
    assert NAME in {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert NAME not in {m["name"] for m in spec.load_cell(
        "dots3note.docs.closed").per_layer}
    assert spec.benchmark_json()["per_layer"][-1] == entry   # appended


def test_the_walked_factor_reads_the_counters():
    mod = spec.load_reader(NAME)
    assert mod.read(_run()) == pytest.approx(134 * 1024 / (20 * 6216))
    pad = spec.load_reader("engine.prefill_pad_factor.closed").read(_run())
    assert 1.0 < mod.read(_run()) < pad == pytest.approx(8192 / 6216)
    cell = spec.load_cell(CELL)
    line = spec.read_per_layer(cell, {**_run(), "cell": cell})
    assert line[NAME] == {"value": mod.read(_run()), "unit": "x"}


@pytest.mark.parametrize("run", [
    _run(walked=False),
    {"stats": ({}, {})},
    {"stats": 2 * ({"loop": {"prefill_walked_tokens": 9,
                             "prefill_true_tokens": 7}},)},
], ids=["no_counter", "no_loop", "no_prefill_in_the_window"])
def test_a_program_without_the_counter_reads_nothing(run):
    assert spec.load_reader(NAME).read(run) is None
    cell = spec.load_cell(CELL)
    assert NAME not in spec.read_per_layer(cell, {**run, "cell": cell})
