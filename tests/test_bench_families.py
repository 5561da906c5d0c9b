"""Tier-1's view of the benchmark's model-family seam: the cases of
`benchmarks/tests/test_family.py` (PR 27; the Llama family moved, a
second family as files alone, rooflines by kernel name) and of
`benchmarks/tests/test_lfm2_family.py` (the `lfm2_moe` family and its
cell) and of `benchmarks/tests/test_ssm_hybrid_family.py` (the
`ssm_hybrid` family and its cell) and of
`benchmarks/tests/test_dots3_note_family.py` (the `dots3_note` family and
its cell), of `benchmarks/tests/test_stall_reader.py` (PR 50's four
readers), of `benchmarks/tests/test_mimo_v2_family.py` (the `mimo_v2`
family and its cell), of
`benchmarks/tests/test_prefill_walked_reader.py` (PR 53's reader) and of
`benchmarks/tests/test_cohere2_moe_family.py` (the `cohere2_moe` family
and its cell), imported so that they run, and count, with `pytest
tests/`."""
from benchmarks.harness import spec
from benchmarks.tests import test_dots3_note_family as _dots3
from benchmarks.tests import test_mimo_v2_family as _mimo
from benchmarks.tests import test_prefill_walked_reader as _walked
from benchmarks.tests.test_family import *  # noqa: F401,F403
from benchmarks.tests.test_lfm2_family import *  # noqa: F401,F403
from benchmarks.tests.test_ssm_hybrid_family import *  # noqa: F401,F403
from benchmarks.tests.test_dots3_note_family import *  # noqa: F401,F403
from benchmarks.tests.test_stall_reader import *  # noqa: F401,F403
from benchmarks.tests.test_mimo_v2_family import *  # noqa: F401,F403
from benchmarks.tests.test_prefill_walked_reader import *  # noqa: F401,F403
from benchmarks.tests.test_cohere2_moe_family import *  # noqa: F401,F403


def test_the_dots3_cell_is_found_by_its_files(dots_cell, monkeypatch):
    """The benchmark's own case, given the benchmark as PR 45 knew it:
    the case counts the cells (9, one of them on four chips), later PRs
    add cells, and its file is the benchmark's, which only a `benchmark`
    PR edits.  Everything else it checks runs as written."""
    bench = spec.benchmark_json()
    known = dict(bench, workloads=bench["workloads"][:9])
    monkeypatch.setattr(spec, "benchmark_json",
                        lambda root=spec.ROOT: known)
    _dots3.test_the_dots3_cell_is_found_by_its_files(dots_cell)


def _as_pr_53_knew_it(monkeypatch):
    """The benchmark without what PR 54 added: its cell's name out of
    every `workloads` list, its configuration, and the two per-layer
    entries it appended.  The cases below are the benchmark's own, which
    assert that a metric lists ONE cell or is the last appended; their
    files are the benchmark's, which only a `benchmark` PR edits."""
    bench = spec.benchmark_json()
    cell, config = COHERE_CELL, COHERE_CONFIG        # noqa: F405

    def before(metrics):
        return [dict(m, workloads=[w for w in m["workloads"] if w != cell])
                if "workloads" in m else m
                for m in metrics if m["name"] not in COHERE_NEW_METRICS]  # noqa: F405,E501

    known = dict(
        bench,
        configs=[c for c in bench["configs"] if c["name"] != config],
        workloads=[w for w in bench["workloads"] if w["name"] != cell],
        end_to_end=before(bench["end_to_end"]),
        per_layer=before(bench["per_layer"]))
    monkeypatch.setattr(spec, "benchmark_json",
                        lambda root=spec.ROOT: known)


def test_the_mimo_cell_is_found_by_its_files(monkeypatch):
    _as_pr_53_knew_it(monkeypatch)
    _mimo.test_the_mimo_cell_is_found_by_its_files(
        spec.load_cell(_mimo.MIMO_CELL))


def test_the_walked_factor_is_found_by_its_files(monkeypatch):
    _as_pr_53_knew_it(monkeypatch)
    _walked.test_the_walked_factor_is_found_by_its_files()
