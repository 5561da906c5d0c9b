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
from benchmarks.tests import test_cohere2_moe_family as _cohere
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
from benchmarks.tests.test_solar_open2_family import *  # noqa: F401,F403
from benchmarks.tests.test_minicpm_sala_family import *  # noqa: F401,F403


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


def _without_what_was_added(monkeypatch, *added):
    """The benchmark without what later PRs added, each (its cell, its
    configuration, the per-layer entries it appended): the cell's name
    out of every `workloads` list, the configuration and those entries.
    The cases below are the benchmark's own, which assert that a metric
    lists ONE cell or is the last appended; their files are the
    benchmark's, which only a `benchmark` PR edits."""
    bench = spec.benchmark_json()
    cells = {cell for cell, _, _ in added}
    configs = {config for _, config, _ in added}
    new = {name for _, _, names in added for name in names}

    def before(metrics):
        return [dict(m, workloads=[w for w in m["workloads"]
                                   if w not in cells])
                if "workloads" in m else m
                for m in metrics if m["name"] not in new]

    known = dict(
        bench,
        configs=[c for c in bench["configs"] if c["name"] not in configs],
        workloads=[w for w in bench["workloads"] if w["name"] not in cells],
        end_to_end=before(bench["end_to_end"]),
        per_layer=before(bench["per_layer"]))
    monkeypatch.setattr(spec, "benchmark_json",
                        lambda root=spec.ROOT: known)


PR_54 = (COHERE_CELL, COHERE_CONFIG, COHERE_NEW_METRICS)        # noqa: F405
PR_58 = (SOLAR_CELL, SOLAR_CONFIG, SOLAR_NEW_METRICS)           # noqa: F405
PR_61 = (SALA_CELL, SALA_CONFIG, SALA_NEW_METRICS)              # noqa: F405


def test_the_mimo_cell_is_found_by_its_files(monkeypatch):
    _without_what_was_added(monkeypatch, PR_54, PR_58, PR_61)
    _mimo.test_the_mimo_cell_is_found_by_its_files(
        spec.load_cell(_mimo.MIMO_CELL))


def test_the_walked_factor_is_found_by_its_files(monkeypatch):
    _without_what_was_added(monkeypatch, PR_54, PR_58, PR_61)
    _walked.test_the_walked_factor_is_found_by_its_files()


def test_the_cohere_cell_is_found_by_its_files(monkeypatch):
    """PR 58's cell reports `kernel.flash_fwd_roofline.closed` beside the
    one the case knew."""
    _without_what_was_added(monkeypatch, PR_58, PR_61)
    _cohere.test_the_cohere_cell_is_found_by_its_files(
        spec.load_cell(COHERE_CELL))                            # noqa: F405
