"""Tier-1's view of the benchmark's model-family seam: the cases of
`benchmarks/tests/test_family.py` (PR 27; the Llama family moved, a
second family as files alone, rooflines by kernel name) and of
`benchmarks/tests/test_lfm2_family.py` (the `lfm2_moe` family and its
cell) and of `benchmarks/tests/test_ssm_hybrid_family.py` (the
`ssm_hybrid` family and its cell) and of
`benchmarks/tests/test_dots3_note_family.py` (the `dots3_note` family and
its cell), imported so that they run, and count, with `pytest tests/`."""
from benchmarks.tests.test_family import *  # noqa: F401,F403
from benchmarks.tests.test_lfm2_family import *  # noqa: F401,F403
from benchmarks.tests.test_ssm_hybrid_family import *  # noqa: F401,F403
from benchmarks.tests.test_dots3_note_family import *  # noqa: F401,F403
